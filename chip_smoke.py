"""The quickest proof that the system still starts on the chip.

One process, one import of JAX, everything in-process through the functions
the entry points call. Without options, on ONE TPU chip:

  (a) device check — anything but a TPU is a non-zero exit, at once;
  (b) the main path's Pallas kernels, compiled by Mosaic (never
      interpreted), against their dense references;
  (c) the trainer: GPT-2 medium at published width and depth, seq 1024,
      bf16 compute / fp32 master / AdamW, through ``scripts/train.py``'s
      ``main`` (-> ``train.fit``); then the tick executor against
      single-device autodiff at the same width;
  (d) serving: ``ServingEngine`` on a one-chip mesh at GPT-2 medium widths
      against ``generate()`` and, token by token, a plain forward pass.

With ``--four-chips`` (the builder runs it; needs four TPU devices), the
pipeline across chips and what it is compared with, and no other phase:
D=4 1F1B parity at GPT-2 XL widths against the one-chip oracle, then
GPT-2 XL at all 48 layers through ``train.fit`` with every chip's memory
and the layer leaves' shardings printed before the first step and between
steps.

Any failed phase raises: the exit code is non-zero and no result line is
printed. The last line of a passing run is one JSON object,
``{"ok": true, "device": {...}}``. Step and compile seconds printed on
the way are smoke timings — one reading each, for orientation — not
measurements.
"""

import argparse
import contextlib
import importlib.util
import json
import math
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

BATCH, SEQ = 8, 1024  # every training phase: 8 sequences of 1024 tokens
# gpt2-medium at that batch (packed kernels, static causal strips); a
# gpt2-xl pipeline microbatch (25 heads: odd, so the classic form, strips
# too); then the ragged length whose backward the v5e compiler used to
# refuse (one block spans the row, no whole strips: the one-tile form);
# then gpt2-medium's 8192 tokens as short rows, where 'auto' takes the
# kernels since PR 33 (two strips of 128 at 256; two of 256 forward and four
# of 128 backward at 512); last, latent attention's two widths (a fifth
# number: the values' head width; PR 34) in blocks of 512 and in one block
FLASH_SHAPES = ((BATCH, SEQ, 16, 64), (2, SEQ, 25, 64), (2, 1000, 12, 64),
                (32, 256, 16, 64), (16, 512, 16, 64),
                (1, 2048, 8, 192, 128), (2, SEQ, 8, 192, 128))
XENT_SHAPE = (BATCH * SEQ, 50257)

# bf16 keeps 8 bits of mantissa (eps = 2**-8 = 3.9e-3). A kernel and its
# dense reference round at different points (the flash kernels round the
# probabilities and ds to bf16 before their MXU dots; the reference keeps
# f32 until the end), so they agree to a few eps of the tensor's scale.
KERNEL_TOL = 3e-2
# Executor vs single-device autodiff, bf16 compute over fp32 masters: the
# executor sums per-microbatch losses and fp32 grads in its own order and
# XLA fuses (so rounds to bf16) at different points in the two programs.
# The loss is a mean over thousands of tokens, so its roundings average
# out, and so does the norm of the whole gradient. One leaf does not: a
# bias gradient is a sum over every token with heavy cancellation, which
# the executor rounds to bf16 once per microbatch and the oracle once in
# all, so a leaf is allowed a dozen eps (2.2e-2 was seen at a tiny width).
LOSS_TOL = 2e-3
NORM_TOL = 1e-2
LEAF_TOL = 5e-2
# Greedy serving vs generate(): the engine prefills in chunks and decodes
# through a C-wide channel, the oracle prefills whole and decodes one row,
# so their bf16 logits can differ by a quantum or two — 2**-6 = 0.0156 for
# a logit between 2 and 4, which is where the largest of 50257 nearly flat
# random-init logits sits. The top two are often closer than that (on the
# chip, four of six requests left generate()'s tokens at a gap of 0 or one
# quantum), and after such a near-tie the two decode different texts. So
# EVERY engine token is held to a plain forward pass on the engine's own
# prefix — within four quanta of that row's largest logit — and a
# disagreement with generate() is admitted only as such a near-tie.
TIE_TOL = 4 * 2 ** -6


def log(msg: str) -> None:
    print(msg, flush=True)


class CacheCounter:
    """Persistent-compile-cache traffic, from JAX's own monitoring events."""

    def __init__(self, cache_dir: str):
        import jax.monitoring
        self.dir = cache_dir
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def entries(self) -> int:
        if not os.path.isdir(self.dir):
            return 0
        return sum(1 for f in os.listdir(self.dir) if not f.endswith("-atime"))

    @contextlib.contextmanager
    def phase(self, name: str):
        h, m, t = self.hits, self.misses, time.perf_counter()
        log(f"--- {name}")
        yield
        log(f"--- {name}: done in {time.perf_counter() - t:.1f}s "
            f"(compile cache: {self.hits - h} hits, "
            f"{self.misses - m} misses)")


def rel_err(got, want) -> float:
    """max |got - want| over the reference's scale, in f32 on the device."""
    import jax.numpy as jnp
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def compile_for_chip(fn, *args):
    """Lower, require the Mosaic custom call (an interpreted kernel lowers
    to plain HLO and has none), compile. Returns (compiled, seconds)."""
    import jax
    lowered = jax.jit(fn).lower(*args)
    if "tpu_custom_call" not in lowered.as_text():
        raise AssertionError("no tpu_custom_call in the lowered program: "
                             "the kernel was interpreted, not compiled")
    t = time.perf_counter()
    compiled = lowered.compile()
    return compiled, time.perf_counter() - t


def check_kernels(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from distributed_training_with_pipeline_parallelism_tpu.ops import (
        layers, pallas_attention, pallas_xent)

    def with_vjp(attend):
        def run(q, k, v, do):
            out, vjp = jax.vjp(attend, q, k, v)
            return (out,) + vjp(do)
        return run

    def flash(q, k, v):
        return pallas_attention.flash_attention(q, k, v, causal=True)

    def dense(q, k, v):
        b, s, h, dh = q.shape

        def flat(x):
            return x.transpose(0, 2, 1, 3).reshape(b * h, s, x.shape[-1])

        out = pallas_attention._dense_attention(flat(q), flat(k), flat(v),
                                                True)
        return out.reshape(b, h, s, v.shape[-1]).transpose(0, 2, 1, 3)

    for shape in FLASH_SHAPES:
        qk, values = shape[:4], shape[:3] + shape[-1:]
        q, k, v, do = (jax.random.normal(kk, sh, jnp.bfloat16)
                       for kk, sh in zip(jax.random.split(
                           jax.random.key(seed), 4), (qk, qk, values, values)))
        compiled, secs = compile_for_chip(with_vjp(flash), q, k, v, do)
        got = compiled(q, k, v, do)
        want = jax.jit(with_vjp(dense))(q, k, v, do)
        errs = {n: rel_err(g, w)
                for n, g, w in zip(("out", "dq", "dk", "dv"), got, want)}
        log(f"flash fwd+bwd {shape} bf16: compiled for the chip in "
            f"{secs:.1f}s; max error over the dense reference's scale: "
            + ", ".join(f"{n} {e:.2e}" for n, e in errs.items()))
        bad = {n: e for n, e in errs.items() if not e < KERNEL_TOL}
        if bad:
            raise AssertionError(f"flash {shape} off its dense reference "
                                 f"by more than {KERNEL_TOL}: {bad}")
    del q, k, v, do, got, want

    n, vocab = XENT_SHAPE
    logits = jax.random.normal(jax.random.key(seed + 1), (n, vocab),
                               jnp.bfloat16)
    targets = jax.random.randint(jax.random.key(seed + 2), (n,), 0, vocab)
    fused, secs = compile_for_chip(
        jax.value_and_grad(pallas_xent.fused_cross_entropy_loss),
        logits, targets)
    loss, grad = fused(logits, targets)
    want, want_grad = jax.jit(jax.value_and_grad(
        layers.cross_entropy_loss))(logits, targets)
    e_loss = abs(float(loss) - float(want)) / abs(float(want))
    e_grad = rel_err(grad, want_grad)
    log(f"fused cross-entropy ({n}, {vocab}) bf16: compiled for the chip in "
        f"{secs:.1f}s; loss {float(loss):.4f} vs {float(want):.4f} "
        f"(rel {e_loss:.2e}), grad rel {e_grad:.2e}")
    if not (e_loss < KERNEL_TOL and e_grad < KERNEL_TOL):
        raise AssertionError("fused cross-entropy off its XLA reference")


def check_barrier(seed: int) -> None:
    """Does ``jax.block_until_ready`` wait for the device? Time it against
    a host fetch of the result on a dispatch that takes many milliseconds."""
    import jax
    import jax.numpy as jnp

    x = jax.random.normal(jax.random.key(seed), (4096, 4096), jnp.bfloat16)

    @jax.jit
    def work(x):
        return jax.lax.fori_loop(
            0, 200, lambda _, y: (y @ x) * jnp.bfloat16(0.01), x).sum()

    float(work(x))  # compile and warm
    t0 = time.perf_counter()
    out = work(x)
    t_dispatch = time.perf_counter() - t0
    jax.block_until_ready(out)
    t_block = time.perf_counter() - t0
    float(out)
    t_fetch_after = time.perf_counter() - t0 - t_block
    t0 = time.perf_counter()
    float(work(x))
    t_fetch_only = time.perf_counter() - t0
    waits = t_block > 0.5 * t_fetch_only
    log(f"barrier check: dispatch returned in {t_dispatch * 1e3:.2f} ms, "
        f"block_until_ready in {t_block * 1e3:.2f} ms, host fetch after it "
        f"{t_fetch_after * 1e3:.2f} ms; fetch alone {t_fetch_only * 1e3:.2f} "
        f"ms -> block_until_ready {'WAITS' if waits else 'DOES NOT WAIT'} "
        "for the device")
    if not waits:
        raise AssertionError("jax.block_until_ready returned before the "
                             "device finished: utils.metrics.force_completion"
                             " relies on it")


def memory_line(dev) -> str:
    """The device's memory as its runtime reports it; a peak at the limit
    fails the run."""
    s = dev.memory_stats()
    if not s["peak_bytes_in_use"] < s["bytes_limit"]:
        raise AssertionError(f"device {dev.id} peaked at its limit")
    return (f"device {dev.id}: in use {s['bytes_in_use'] / 1e9:.3f} GB, peak "
            f"{s['peak_bytes_in_use'] / 1e9:.3f} GB of "
            f"{s['bytes_limit'] / 1e9:.3f} GB")


def train_one_chip(seed: int, steps: int = 6) -> None:
    """GPT-2 medium exactly as published, through the command-line path."""
    import jax

    spec = importlib.util.spec_from_file_location(
        "dtpp_scripts_train", os.path.join(ROOT, "scripts", "train.py"))
    train_script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(train_script)
    with tempfile.TemporaryDirectory() as tmp:
        metrics = os.path.join(tmp, "metrics.jsonl")
        argv = ["--model", "gpt2-medium", "--pipe", "1", "--microbatches", "4",
                "--batch", str(BATCH), "--seq", str(SEQ), "--dtype", "bfloat16",
                "--param-dtype", "float32", "--flash", "auto", "--fused-xent",
                "--steps", str(steps), "--seed", str(seed),
                "--metrics", metrics]
        log("scripts/train.py " + " ".join(argv[:-2]))
        params, history = train_script.main(argv)
        with open(metrics) as fh:
            rows = [json.loads(line) for line in fh]
    n_params = sum(x.size for x in jax.tree.leaves(params))
    del params
    losses = [loss for _, loss in history]
    log(f"gpt2-medium: {n_params / 1e6:.1f}M parameters, {len(losses)} AdamW "
        f"steps, losses {', '.join(f'{x:.4f}' for x in losses)}")
    log("smoke timing (one reading, not a measurement): first step with its "
        f"compile {rows[0]['elapsed_s']:.1f}s; later steps "
        + ", ".join(f"{r['elapsed_s']:.3f}s" for r in rows[1:]))
    want = math.log(50257)
    if len(losses) < 5 or not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"trainer losses not finite: {losses}")
    if abs(losses[0] - want) > 0.5:
        raise AssertionError(f"first loss {losses[0]:.3f} is not near "
                             f"ln(50257) = {want:.3f}")
    log(memory_line(jax.devices()[0]))


def smoke_config(name: str, **overrides):
    """The trainer's configuration: bf16 compute over fp32 masters, flash
    by the 'auto' rule (which must pick the kernel here), fused CE."""
    from distributed_training_with_pipeline_parallelism_tpu.models.gpt2 import (
        gpt2_config)
    cfg = gpt2_config(name, dtype="bfloat16", param_dtype="float32",
                      use_flash_attention="auto", use_fused_xent=True,
                      **overrides)
    if not cfg.flash_for(True, SEQ):
        raise AssertionError("--flash auto did not resolve to the Pallas "
                             "kernel on this device")
    return cfg


def executor_parity(cfg, mesh, sched, oracle_device, seed: int,
                    force_tick_executor: bool) -> None:
    """The tick executor's (loss, grads) on ``mesh`` against
    ``jax.value_and_grad(transformer_loss)`` on ONE device, same seeded
    params and batch — the oracle the CPU tests use, at real width."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import SingleDeviceSharding

    from distributed_training_with_pipeline_parallelism_tpu.models.transformer import (
        transformer_loss)
    from distributed_training_with_pipeline_parallelism_tpu.parallel.pipeline import (
        make_pipeline_step)
    from distributed_training_with_pipeline_parallelism_tpu.utils import train
    from distributed_training_with_pipeline_parallelism_tpu.utils.data import (
        batch_sharding)

    step = make_pipeline_step(cfg, mesh, sched,
                              force_tick_executor=force_tick_executor)
    params = train.init_params(cfg, mesh, jax.random.key(seed))
    tokens, targets = next(train.synthetic_data(cfg, BATCH, SEQ, seed=seed))
    placed = jax.device_put((tokens, targets), batch_sharding(mesh))
    t = time.perf_counter()
    compiled = step.lower(params, *placed).compile()
    t_compile = time.perf_counter() - t
    loss, grads = compiled(params, *placed)
    loss = float(loss)
    ma = compiled.memory_analysis()
    log(f"executor ({sched.name}, {sched.n_microbatches} microbatches, "
        f"{mesh.shape['pipe']} stage(s)): compiled in {t_compile:.1f}s "
        f"(smoke timing); loss {loss:.5f}")
    log("the compiler's count for that program, per device: argument "
        f"{ma.argument_size_in_bytes / 1e9:.3f} + output "
        f"{ma.output_size_in_bytes / 1e9:.3f} + temp "
        f"{ma.temp_size_in_bytes / 1e9:.3f} GB; after running it, "
        + memory_line(oracle_device))

    # the oracle holds the whole batch's activations on one chip: the
    # executor's gradients wait on the host meanwhile, and the two are then
    # compared where the executor left its own, leaf by leaf
    resting = jax.tree.map(lambda g: g.sharding, grads)
    grads = jax.device_get(grads)
    params, tokens, targets = jax.device_put(
        (params, tokens, targets), SingleDeviceSharding(oracle_device))
    want_loss, want_grads = jax.jit(jax.value_and_grad(
        lambda p: transformer_loss(cfg, p, tokens, targets)))(params)
    want_loss = float(want_loss)
    del params
    grads, want_grads = jax.device_put((grads, want_grads),
                                       (resting, resting))

    @jax.jit
    def compare(got, want):
        def leaf(g, w):
            g, w = g.astype(jnp.float32), w.astype(jnp.float32)
            return jnp.linalg.norm((g - w).ravel()), jnp.linalg.norm(w.ravel())
        return (jax.tree.map(leaf, got, want), optax.global_norm(got),
                optax.global_norm(want))

    errs, norm, want_norm = jax.device_get(compare(grads, want_grads))
    # each leaf's L2 error over its own norm — or, for a leaf whose true
    # gradient is zero and whose computed one is bf16 rounding noise (a key
    # bias shifts every score of a softmax row alike), over a hundredth of
    # the whole gradient's
    worst_path, worst = max(
        ((path, err / max(ref, 1e-2 * want_norm)) for path, (err, ref) in
         jax.tree_util.tree_flatten_with_path(
             errs, is_leaf=lambda x: isinstance(x, tuple))[0]),
        key=lambda kv: kv[1])
    e_loss = abs(loss - want_loss) / abs(want_loss)
    e_norm = abs(norm - want_norm) / want_norm
    log(f"oracle (single device {oracle_device.id}): loss {want_loss:.5f} "
        f"(rel diff {e_loss:.2e}); global grad norm {norm:.5f} vs "
        f"{want_norm:.5f} (rel diff {e_norm:.2e}); worst leaf "
        f"{jax.tree_util.keystr(worst_path)} rel L2 {worst:.2e}")
    if not (e_loss < LOSS_TOL and e_norm < NORM_TOL and worst < LEAF_TOL):
        raise AssertionError(
            f"executor off the single-device oracle (tolerances: loss "
            f"{LOSS_TOL}, grad norm {NORM_TOL}, any leaf {LEAF_TOL})")


def serve_one_chip(seed: int) -> None:
    """Continuous batching on a one-chip mesh at GPT-2 medium widths, bf16,
    contiguous KV, greedy — tokens against ``generate()``, and each one
    against a plain forward pass on the engine's own prefix."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_training_with_pipeline_parallelism_tpu.models import (
        transformer as tfm)
    from distributed_training_with_pipeline_parallelism_tpu.models.generate import (
        make_generate_fn)
    from distributed_training_with_pipeline_parallelism_tpu.models.gpt2 import (
        gpt2_config)
    from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import (
        make_mesh)
    from distributed_training_with_pipeline_parallelism_tpu.serving import (
        Request, ServingEngine, make_serving_step_fn)

    cfg = gpt2_config("medium", dtype="bfloat16")
    new_tokens, chunk, prompt_max = 16, 64, 384
    params = tfm.transformer_init(jax.random.key(seed), cfg)
    program = make_serving_step_fn(
        cfg, make_mesh(n_pipe=1), n_slots=4, max_len=512,
        prompt_max=prompt_max, out_max=new_tokens, prefill_chunk=chunk)
    engine = ServingEngine(program, params)
    rng = np.random.RandomState(seed)
    # more requests than slots (so slots are recycled mid-flight), prompt
    # lengths off the chunk size; three lengths keep the oracle's compiles few
    requests = [Request(rid=i,
                        prompt=rng.randint(0, cfg.vocab_size, size=n).tolist(),
                        max_new_tokens=new_tokens)
                for i, n in enumerate((200, 333, 257, 333, 200, 257))]
    t = time.perf_counter()
    result = engine.run(requests, policy="continuous")
    log(f"serving engine: {len(result.completions)} of {len(requests)} "
        f"requests in {result.ticks} ticks, {time.perf_counter() - t:.1f}s "
        f"with its compile (smoke timing); step program compiled "
        f"{program.step._cache_size()}x")
    if len(result.completions) != len(requests):
        raise AssertionError("the engine did not complete every request")
    if program.step._cache_size() != 1:
        raise AssertionError("the serving block compiled more than once")

    oracle = make_generate_fn(cfg, new_tokens, max_len=program.mlen_alloc)
    ctx_len = prompt_max + new_tokens  # one compile: causal, so right-padded

    @jax.jit
    def decode_logits(p, ctx, start):
        """A plain forward pass; the rows that choose the new tokens."""
        lg = tfm.transformer_apply(cfg, p, ctx)[0]
        return jax.lax.dynamic_slice_in_dim(
            lg, start, new_tokens).astype(jnp.float32)

    exact = ties = 0
    worst = 0.0
    for c in sorted(result.completions, key=lambda c: c.rid):
        plen = len(c.prompt)
        if len(c.tokens) != new_tokens:
            raise AssertionError(f"request {c.rid}: {len(c.tokens)} tokens")
        # every token the engine chose, teacher-forced on the engine's OWN
        # prefix: row i saw prompt + tokens[:i] and must put tokens[i] within
        # a near-tie of its largest logit — so a KV or slot fault late in
        # decode, or in a recycled slot, cannot hide behind an earlier tie
        ctx = np.zeros((1, ctx_len), np.int32)
        ctx[0, :plen + new_tokens] = list(c.prompt) + c.tokens
        lg = np.asarray(decode_logits(params, ctx, plen - 1))
        gaps = lg.max(-1) - lg[np.arange(new_tokens), c.tokens]
        worst = max(worst, float(gaps.max()))
        if not gaps.max() < TIE_TOL:
            i = int(gaps.argmax())
            raise AssertionError(
                f"request {c.rid}: engine token {i} ({c.tokens[i]}) is "
                f"{gaps[i]:.4f} under the largest logit of a plain forward "
                f"pass on its own prefix (>= {TIE_TOL})")
        out = np.asarray(oracle(params, np.asarray([c.prompt], np.int32)))
        want = [int(x) for x in out[0, plen:]]
        if c.tokens == want:
            exact += 1
            continue
        # generate() shares the prefix up to the first disagreement, so row j
        # above judges its choice too
        j = next(i for i, (a, b) in enumerate(zip(c.tokens, want)) if a != b)
        gap = float(lg[j, want[j]] - lg[j, c.tokens[j]])
        log(f"request {c.rid} (prompt {plen}): engine and generate() agree "
            f"on {j} tokens, then pick {c.tokens[j]} vs {want[j]}, whose "
            f"logits differ by {gap:.4f} on a plain forward pass")
        if not abs(gap) < TIE_TOL:
            raise AssertionError(
                f"request {c.rid} diverged from generate() at token {j} "
                f"and it is no bf16 near-tie (gap {gap:.4f} >= {TIE_TOL})")
        ties += 1
    log(f"serving parity with generate(): {exact} of {len(requests)} requests "
        f"token-for-token, {ties} left it at a bf16 near-tie; all "
        f"{len(requests) * new_tokens} engine tokens are within "
        f"{worst:.4f} of the largest logit of a plain forward pass on the "
        f"engine's own prefix (tolerance {TIE_TOL:.4f})")
    log(memory_line(jax.devices()[0]))


def describe_ring(mesh) -> None:
    devs = list(mesh.devices.reshape(-1))
    log("ring order (make_mesh reshapes jax.devices() in list order; stage "
        "d sends to d+1, the last to the first): "
        + " -> ".join(f"stage {i} = device {d.id} coords {tuple(d.coords)}"
                      for i, d in enumerate(devs)))


def train_four_chips(seed: int, steps: int = 3) -> None:
    """GPT-2 XL, all 48 layers, D=4 1F1B through ``train.fit`` as
    ``scripts/train.py --model gpt2-xl --pipe 4 --schedule 1F1B
    --microbatches 8 --dtype bfloat16 --param-dtype float32 --seq 1024``
    builds it, with an observer on the data stream: it runs before the
    first step and between steps, prints every chip's memory and where the
    layer leaves live, and fails if any is whole on one chip."""
    import jax
    import jax.numpy as jnp

    import distributed_training_with_pipeline_parallelism_tpu as dtpp
    from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import (
        make_mesh)
    from distributed_training_with_pipeline_parallelism_tpu.utils import train
    from distributed_training_with_pipeline_parallelism_tpu.utils.data import (
        batch_sharding, prefetch_to_device)

    cfg = smoke_config("xl")
    mesh = make_mesh(n_pipe=4)
    sched = dtpp.ScheduleConfig(name="1F1B", n_microbatches=8)
    devices = list(mesh.devices.reshape(-1))
    probe = (cfg.n_layers, cfg.dim, cfg.ffn_dim)  # lin1.w and its moments

    def observe(data):
        for i, batch in enumerate(data):
            live = [a for a in jax.live_arrays() if a.ndim >= 2
                    and a.shape[0] == cfg.n_layers  # a stacked layer leaf
                    and jnp.issubdtype(a.dtype, jnp.floating)]
            jax.block_until_ready(live)
            log(f"before step {i}:" if i else "before the first step:")
            for dev in devices:
                log("  " + memory_line(dev))
            for a in live:
                shard = a.addressable_shards[0].data.shape
                if a.shape == probe:
                    log(f"  layer leaf {a.shape} {a.dtype} (lin1.w or its "
                        f"Adam moment): {a.sharding.spec} over "
                        f"{len(a.sharding.device_set)} devices, shard {shard}")
                if (shard[0] * 4 != cfg.n_layers
                        or len(a.sharding.device_set) != 4):
                    raise AssertionError(
                        f"a layer leaf {a.shape} is not quartered over the "
                        f"chips: {a.sharding}, shard {shard}")
            n_probe = sum(a.shape == probe for a in live)
            if n_probe != 3:  # the weight, mu, nu — and no stray copy
                raise AssertionError(f"{n_probe} live arrays of shape "
                                     f"{probe}, expected 3")
            yield batch

    params = train.init_params(cfg, mesh, jax.random.key(seed))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"gpt2-xl: {n_params / 1e9:.3f}B parameters born in the resting "
        f"layout; lin1.w {params['layers']['lin1']['w'].sharding.spec}, "
        f"embed.tok {params['embed']['tok'].sharding.spec}")
    data = observe(prefetch_to_device(
        train.synthetic_data(cfg, BATCH, SEQ, seed=seed),
        sharding=batch_sharding(mesh)))
    t = time.perf_counter()
    params, history = train.fit(cfg, mesh, sched, params, data, steps,
                                optimizer=train.adamw(total_steps=steps),
                                log_every=1)
    losses = [loss for _, loss in history]
    log(f"{len(losses)} AdamW steps in {time.perf_counter() - t:.1f}s with "
        "the compile (smoke timing); losses "
        + ", ".join(f"{x:.4f}" for x in losses))
    log("after the last step:")
    for dev in devices:
        log("  " + memory_line(dev))
    if len(losses) != steps or not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"losses not finite: {losses}")
    if abs(losses[0] - math.log(50257)) > 0.5:
        raise AssertionError(f"first loss {losses[0]:.3f} not near ln(50257)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the four-chip pipeline phase (and nothing else)")
    ap.add_argument("--seed", type=int, default=0,
                    help="weights and batches are random, made from this")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    need = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU device(s); JAX sees "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        return 1

    import jaxlib
    from importlib import metadata

    import distributed_training_with_pipeline_parallelism_tpu as dtpp
    from distributed_training_with_pipeline_parallelism_tpu.parallel import native
    from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import (
        make_mesh)
    from distributed_training_with_pipeline_parallelism_tpu.utils.compile_cache import (
        enable_compile_cache)

    cache = CacheCounter(enable_compile_cache())
    log(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu "
        f"{metadata.version('libtpu')}; platform {devices[0].platform}, "
        f"device_kind {devices[0].device_kind!r}, {len(devices)} device(s)")
    log(f"compile cache: {cache.dir} ({cache.entries()} entries at start)")
    log("schedule tables: " + (
        "C++ engine, built by make from csrc/ in this checkout"
        if native.native_available() else "Python compiler (no C++ build)"))

    if args.four_chips:
        mesh = make_mesh(n_pipe=4)
        describe_ring(mesh)
        with cache.phase("D=4 1F1B parity at GPT-2 XL widths, 12 layers"):
            executor_parity(
                smoke_config("xl", n_layers=12), mesh,
                dtpp.ScheduleConfig(name="1F1B", n_microbatches=8),
                devices[0], args.seed, force_tick_executor=False)
        with cache.phase("GPT-2 XL, 48 layers, D=4 1F1B through train.fit"):
            train_four_chips(args.seed)
    else:
        with cache.phase("kernels against their dense references"):
            check_kernels(args.seed)
            check_barrier(args.seed)
        with cache.phase("GPT-2 medium trainer through scripts/train.py"):
            train_one_chip(args.seed)
        with cache.phase("tick executor against single-device autodiff, "
                         "GPT-2 medium"):
            executor_parity(
                smoke_config("medium"), make_mesh(n_pipe=1),
                dtpp.ScheduleConfig(name="1F1B", n_microbatches=4),
                devices[0], args.seed, force_tick_executor=True)
        with cache.phase("serving engine against generate(), GPT-2 medium"):
            serve_one_chip(args.seed)

    log(f"compile cache: {cache.entries()} entries at the end, {cache.hits} "
        f"hits and {cache.misses} misses in this run")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
