"""The main path, compiled by the TPU's own compiler for a chip that is
described, not attached (``v5e:2x2``): the Pallas kernels at real widths,
the whole gpt2-medium train step on one chip, and the gpt2-xl-width
pipe=4 train step on the four-chip mesh with its resting shardings.

Interpret mode (every other test of the kernels) cannot see what Mosaic
refuses — a ragged lane slice, too much VMEM — nor what does not fit HBM;
these compiles can, at no chip time. Nothing runs: a compile that passes
is not a chip run (``chip_smoke.py`` is). The topology is described inside
a module fixture — only the xdist worker that is handed this file loads
libtpu, and it skips, not errors, where none can be described.
"""

import base64
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from distributed_training_with_pipeline_parallelism_tpu.models.gpt2 import (
    gpt2_config)
from distributed_training_with_pipeline_parallelism_tpu.models.transformer import (
    transformer_init)
from distributed_training_with_pipeline_parallelism_tpu.ops import (
    layers, pallas_attention, pallas_xent)
from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import (
    make_mesh)
from distributed_training_with_pipeline_parallelism_tpu.parallel.pipeline import (
    param_shardings)
from distributed_training_with_pipeline_parallelism_tpu.utils import (
    profiling, train)
from distributed_training_with_pipeline_parallelism_tpu.utils.config import (
    ScheduleConfig)

# what the v5e compiler itself reports as the limit: the one place the
# number is written, beside the budget that is computed from it
HBM_BYTES = layers.COMPILER_HBM_BYTES["TPU v5 lite"]
#: what every 8k step leaves of it, by the compiler's own count
MARGIN_BYTES = 0.03 * HBM_BYTES


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    env = pytest.MonkeyPatch()
    env.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        env.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to a persistent cache
    # but never read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()
    env.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """The kernels ask ``jax.devices()`` whether to interpret, and here that
    is the CPU: steer them to the Mosaic lowering (``pallas_xent`` imports
    the predicate by name, so both modules)."""
    for mod in (pallas_attention, pallas_xent):
        monkeypatch.setattr(mod, "_use_interpret", lambda: False)


def _bytes(compiled):
    ma = compiled.memory_analysis()
    return {"argument": ma.argument_size_in_bytes,
            "output": ma.output_size_in_bytes,
            "temp": ma.temp_size_in_bytes,
            "alias": ma.alias_size_in_bytes}


@pytest.mark.parametrize("shape,window,blocks", [
    ((8, 1024, 16, 64), None, 1024),    # gpt2-medium, the smoke's batch
    ((2, 1024, 25, 64), None, 1024),    # gpt2-xl: odd head count, unpacked path
    ((4, 1024, 12, 64), None, 1024),    # gpt2-small
    ((2, 1000, 12, 64), None, 1000),    # ragged: one block spans the row
    ((2, 4096, 32, 128), 1024, 512),    # windowed, head_dim 128
    # nemotron_h's attention layer: a whole row's q, do and f32 dq pass the
    # default 16 MiB of VMEM in the backward, which asks for more (PR 30).
    # In blocks of 512 since PR 37 (256 was forced from 8192 rows on): the
    # compiler wants 11.70 MiB forward, under its default, and 20.65 backward
    ((2, 8192, 32, 128), None, 512),
    # gpt2-medium's 8192 tokens as short rows: 'auto' takes the kernels from
    # seq 256 (PR 33), strips of 128 / of 256 forward and 128 backward
    ((32, 256, 16, 64), None, 256),
    ((16, 512, 16, 64), None, 512),
    # lfm2's attention layer: heads of 64 at 8192 rows run the classic
    # kernels (the packed slab would be 32 MiB), and a 64-wide row takes 128
    # lanes in VMEM: the backward's residency is that of heads of 128, and
    # is asked for ('Scoped allocation with size 16.50M and limit 16.00M'
    # while the request counted 64; PR 36)
    ((2, 8192, 32, 64), None, 512),
    # a window at 8192 rows takes the same rule and the same requests (the
    # masked body, no static diagonal: 12.36 MiB forward, 20.84 backward)
    ((2, 8192, 32, 128), 1024, 512),
], ids=["medium", "xl-25-heads", "small", "ragged-1000", "window-1024",
        "nemotron-8192x128", "medium-b32s256", "medium-b16s512",
        "lfm2-8192x64", "window-1024-8192x128"])
def test_flash_fwd_bwd_compiles(one_chip, mosaic, caplog, shape, window,
                                blocks):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return pallas_attention.flash_attention(
            q, k, v, causal=True, window=window).astype(jnp.float32).sum()

    with caplog.at_level("INFO"):
        text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
            x, x, x).compile().as_text()
    assert text.count("tpu_custom_call") >= 2  # forward and backward kernels
    assert f"blocks {blocks} x {blocks}," in caplog.text  # what the rule picks


@pytest.mark.parametrize("seq,blocks", [(8192, 512), (1024, 1024)],
                         ids=["blocks-of-512", "one-block-strips"])
def test_flash_two_widths_compile(one_chip, mosaic, caplog, seq, blocks):
    """Latent attention's geometry, 32 heads of 192 for q and k and of 128
    for v, at the benchmark cell's 8192 rows and in one block: Mosaic takes
    the two widths as they are. O and dV come out 128 wide and dQ, dK 192:
    nothing is padded to a common width in HBM. At 8192 rows the blocks are
    512 since PR 37, and BOTH kernels ask for VMEM: 192 takes 256 lanes
    there, so the forward's k and v rows are 12 MiB and it wants 16.20 of
    the default 16 ('Scoped allocation with size 16.20M and limit 16.00M'
    while it asked for nothing), the backward 33.39 (33.00 was asked while
    the request was a factor on the rows and did not count the tiles). (v
    padded to 192 through the equal-width kernels is REFUSED at 8192 rows in
    any block; PR 34.)"""
    q = jax.ShapeDtypeStruct((2, seq, 32, 192), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((2, seq, 32, 128), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return pallas_attention.flash_attention(
            q, k, v, causal=True).astype(jnp.float32).sum()

    with caplog.at_level("INFO"):
        text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
            q, q, v).compile().as_text()
    assert (f"classic kernels, 2 x {seq} x 32 x 192 (values 128), "
            f"blocks {blocks} x {blocks},") in caplog.text
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 2, calls
    fwd, bwd = sorted(calls, key=lambda line: "f32[64,%d,192]" % seq in line)
    assert f"(bf16[64,{seq},128]" in fwd            # O
    assert (f"(f32[64,{seq},192]" in bwd and f"bf16[64,{seq},128]" in bwd
            and f"bf16[64,{seq},192]" in bwd)       # dQ, dK, dV


def test_fused_xent_compiles(one_chip, mosaic):
    logits = jax.ShapeDtypeStruct((8192, 50257), jnp.bfloat16,
                                  sharding=one_chip)
    targets = jax.ShapeDtypeStruct((8192,), jnp.int32, sharding=one_chip)
    text = jax.jit(jax.value_and_grad(
        pallas_xent.fused_cross_entropy_loss)).lower(
            logits, targets).compile().as_text()
    assert "tpu_custom_call" in text


def test_nemotron_h_layers_compile_at_published_widths(one_chip):
    """One Mamba-2 layer and one expert layer of the benchmark's
    nemotron-twotower-30b-a3b configuration, forward and backward at batch 1
    x seq 8192: the chunked scan's einsums and the held experts' gated
    products are accepted by the chip's compiler. The whole 9-layer
    step at batch 2 is rehearsed by hand with :func:`lower_train_step`."""
    from distributed_training_with_pipeline_parallelism_tpu.models import (
        nemotron_h)
    from distributed_training_with_pipeline_parallelism_tpu.utils.config import (
        ModelConfig)
    cfg = ModelConfig(
        arch="nemotron_h", dim=2688, n_layers=2, n_heads=32, n_kv_heads=2,
        head_dim_override=128, vocab_size=16384, hybrid_override_pattern="ME",
        experts_held=tuple(range(8)), dtype="bfloat16", param_dtype="float32")
    h = jax.ShapeDtypeStruct((1, 8192, 2688), jnp.bfloat16, sharding=one_chip)
    for kind in ("mamba", "moe"):
        params = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16,
                                           sharding=one_chip),
            jax.eval_shape(lambda: nemotron_h.mixer_init(
                jax.random.key(0), cfg, kind)))

        def loss(p, x, kind=kind):
            return nemotron_h.mixer_apply(cfg, kind, p, x)[0].astype(
                jnp.float32).sum()

        jax.jit(jax.grad(loss)).lower(params, h).compile()


def _joyai_sublayer(one_chip, kind):
    """(cfg, abstract params, abstract input) of one sublayer of the
    benchmark's joyai-llm-flash configuration at batch 2 x seq 8192."""
    from distributed_training_with_pipeline_parallelism_tpu.models import (
        nemotron_h)
    cfg = nemotron_h.nemotron_h_config(
        "joyai-stage", dtype="bfloat16", param_dtype="float32",
        use_flash_attention=True)
    h = jax.ShapeDtypeStruct((2, 8192, cfg.dim), jnp.bfloat16,
                             sharding=one_chip)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16,
                                       sharding=one_chip),
        jax.eval_shape(lambda: nemotron_h.mixer_init(jax.random.key(0), cfg,
                                                     kind)))
    return cfg, params, h


@pytest.mark.parametrize("kind", ["mla", "mlp", "moe"])
def test_joyai_llm_flash_layers_compile_at_published_widths(one_chip, mosaic,
                                                            kind):
    """One sublayer of each kind the benchmark's joyai-llm-flash
    configuration has — latent attention through the flash kernels at
    192 | 128, the dense gated MLP, the gated experts (8 of 256 held) —
    forward and backward at batch 2 x seq 8192, the cell's shapes. The whole
    16-sublayer step is ``test_joyai_llm_flash_train_step_fits_one_chip``
    (``slow``: a minute of the compiler on every core)."""
    from distributed_training_with_pipeline_parallelism_tpu.models import (
        nemotron_h)
    cfg, params, h = _joyai_sublayer(one_chip, kind)

    def loss(p, x):
        return nemotron_h.mixer_apply(cfg, kind, p, x)[0].astype(
            jnp.float32).sum()

    text = jax.jit(jax.grad(loss)).lower(params, h).compile().as_text()
    assert (text.count("tpu_custom_call") == 2) == (kind == "mla")


def test_lfm2_shortconv_compiles_at_published_widths(one_chip):
    """The new mixer of the benchmark's lfm2-8b-a1b configuration, the gated
    short convolution (three taps over 2048 channels), forward and backward
    at batch 2 x seq 8192, the cell's shapes. Its attention is
    ``test_flash_fwd_bwd_compiles[lfm2-8192x64]``'s kernels behind norms and a
    rotation, its gated experts and dense MLP the joyai configuration's forms
    at other widths; the whole 12-sublayer step was compiled by hand with
    :func:`lower_train_step` (12.503 GB with ``remat_layers``; the plain
    program is refused at 17.42 GB; PR 36)."""
    from distributed_training_with_pipeline_parallelism_tpu.models import (
        nemotron_h)
    cfg = nemotron_h.nemotron_h_config(
        "lfm2-stage", dtype="bfloat16", param_dtype="float32")
    h = jax.ShapeDtypeStruct((2, 8192, cfg.dim), jnp.bfloat16,
                             sharding=one_chip)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16,
                                       sharding=one_chip),
        jax.eval_shape(lambda: nemotron_h.mixer_init(jax.random.key(0), cfg,
                                                     "shortconv")))

    def loss(p, x):
        return nemotron_h.mixer_apply(cfg, "shortconv", p, x)[0].astype(
            jnp.float32).sum()

    text = jax.jit(jax.grad(loss)).lower(params, h).compile().as_text()
    assert "tpu_custom_call" not in text and "model/shortconv" in text


@pytest.mark.parametrize("wrap,calls", [("remat_layer", 2), ("bare", 3)])
def test_joyai_llm_flash_remat_layer_runs_the_forward_kernel_once(
        one_chip, mosaic, wrap, calls):
    """The latent-attention sublayer as the cell runs it, rematerialised:
    under ``ops.layers.remat_layer`` the compiled gradient holds the forward
    kernel once and the backward once; under the bare ``jax.checkpoint`` (the
    cell until PR 35) it holds the forward a second time, only to get back
    the output and the log-sum-exp the backward kernel reads."""
    from distributed_training_with_pipeline_parallelism_tpu.models import (
        nemotron_h)
    from distributed_training_with_pipeline_parallelism_tpu.ops.layers import (
        remat_layer)
    cfg, params, h = _joyai_sublayer(one_chip, "mla")
    layer = lambda p, x: nemotron_h.mixer_apply(cfg, "mla", p, x)[0]  # noqa: E731
    layer = (remat_layer(layer, 1) if wrap == "remat_layer"
             else jax.checkpoint(layer))

    def loss(p, x):
        return layer(p, x).astype(jnp.float32).sum()

    # the value too: a gradient alone leaves the first forward dead
    text = jax.jit(jax.value_and_grad(loss)).lower(params, h).compile(
        ).as_text()
    assert text.count("tpu_custom_call") == calls


def lower_train_step(cfg, mesh, sched, batch, seq):
    """``make_train_step`` lowered on ``mesh`` from shapes alone: params and
    AdamW state in their resting shardings, the batch over 'data'. Returns
    (lowered, abstract params)."""
    optimizer = train.adamw()

    def abstract(shapes, shardings):
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes, shardings)

    params = abstract(
        jax.eval_shape(lambda: transformer_init(jax.random.key(0), cfg)),
        param_shardings(cfg, mesh))
    opt_state = abstract(jax.eval_shape(optimizer.init, params),
                         train.opt_state_shardings(optimizer, params, mesh))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                  sharding=NamedSharding(mesh, P("data")))
    step = train.make_train_step(cfg, mesh, sched, optimizer)
    return step.lower(params, opt_state, tokens, tokens), params


@pytest.mark.parametrize("batch,seq", [(8, 1024), (32, 256)],
                         ids=["b8s1024", "b32s256"])
def test_gpt2_medium_train_step_fits_one_chip(topo, mosaic, batch, seq):
    """Published width and depth, bf16 compute / fp32 master / AdamW, 8192
    tokens a step as 8 x 1024 and as 32 x 256: the compiler counts 15.4 GB
    of 15.75 with the update in place (17.1 GB without the donation), both
    flash kernels are in the program, and its own rematerialisation pass
    has cloned nothing. At 32 x 256 that is what ``flash_for``'s 'auto'
    taking the kernels from seq 256 buys (here it sees the CPU, so flash is
    forced): dense attention stores [32, 16, 256, 256] scores in every
    layer, the count is 18.5 GB, and XLA runs the head's 1024 x 50257
    matmul three times (``fusion.N.remat``, ``.remat2``: 12% of the step on
    the chip, PERF.md section 6, PR 33)."""
    cfg = gpt2_config("medium", dtype="bfloat16", param_dtype="float32",
                      use_flash_attention=True, use_fused_xent=True)
    mesh = make_mesh(n_pipe=1, devices=topo.devices[:1])
    lowered, _ = lower_train_step(
        cfg, mesh, ScheduleConfig(name="1F1B", n_microbatches=4), batch, seq)
    compiled = lowered.compile()
    b = _bytes(compiled)
    assert b["alias"] > 0.9 * b["argument"], b  # params + moments in place
    assert (b["argument"] + b["output"] + b["temp"] - b["alias"]
            < HBM_BYTES), b
    text = compiled.as_text()
    # a Mosaic call carries its kernel as MLIR bytecode, the name in clear
    kernels = set()
    for body in re.findall(r'"body":"([A-Za-z0-9+/=]+)"', text):
        kernels.update(re.findall(rb"_flash_(?:fwd|bwd)_kernel",
                                  base64.b64decode(body)))
    assert kernels == {b"_flash_fwd_kernel", b"_flash_bwd_kernel"}, kernels
    names = re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = ", text, re.M)
    assert len(names) > 1000, len(names)
    assert [n for n in names if ".remat" in n] == []


def _compile_8k_step(topo, preset):
    """One of the benchmark's 8k cells as it runs — ``nemotron_h_config(preset)``
    at published widths, bf16 over fp32 masters, AdamW, the flash kernels,
    the fused cross-entropy, ``remat_layers``, batch 2 x seq 8192 in two
    microbatches — compiled for the described chip: (parameter count, bytes
    the compiler counts, calls of the forward and of the backward kernel,
    the program's text, the record of what ``remat_layers`` was granted to
    keep: the mesh is made of the described chip, so the budget is the
    chip's)."""
    from distributed_training_with_pipeline_parallelism_tpu.models.nemotron_h import (
        nemotron_h_config)
    cfg = nemotron_h_config(
        preset, dtype="bfloat16", param_dtype="float32",
        use_flash_attention=True, use_fused_xent=True, remat_layers=True)
    mesh = make_mesh(n_pipe=1, devices=topo.devices[:1])
    lowered, params = lower_train_step(
        cfg, mesh, ScheduleConfig(name="1F1B", n_microbatches=2), 2, 8192)
    kept = profiling.host_spans()["setup/remat_keep"]["notes"]
    compiled = lowered.compile()
    b = _bytes(compiled)
    assert b["alias"] > 0.9 * b["argument"], b  # params + moments in place
    text = compiled.as_text()
    kernels = []
    for body in re.findall(r'"body":"([A-Za-z0-9+/=]+)"', text):
        kernels += re.findall(rb"_flash_(?:fwd|bwd)_kernel",
                              base64.b64decode(body))
    return (sum(x.size for x in jax.tree.leaves(params)),
            b["argument"] + b["output"] + b["temp"] - b["alias"],
            (kernels.count(b"_flash_fwd_kernel"),
             kernels.count(b"_flash_bwd_kernel")), text, kept)


@pytest.mark.slow
def test_joyai_llm_flash_train_step_fits_one_chip(topo, mosaic, caplog):
    """The benchmark cell ``joyai-llm-flash.train-b2s8192`` as it runs: the
    first eight layers at published widths (latent attention, a dense gated
    MLP, seven layers of gated experts; 622.0 M parameters), bf16 over fp32
    masters, AdamW, batch 2 x seq 8192, ``remat_layers`` — which this count
    decides: the plain program is refused ('Used 19.90G of 15.75G hbm',
    compiled by hand with :func:`lower_train_step`, PR 34), this one counted
    13.121 GB until PR 39: 12.109 for every layer recomputed from its input,
    and 8 x (134 MB + 2 MB) for the flash kernels' output and log-sum-exp,
    which ``remat_layer`` keeps (PR 35). Since PR 39 the chip's room is
    spent on named product outputs, the later layers' first: 32 of the 62
    offered, 2.722 GB — the last four expert sublayers' ``x W1`` and
    ``x W3`` with their shared experts' pairs, one more shared pair and a
    half, the latent down-projections of the last five attention sublayers
    and the last one's up-projections — at a count of 15.128 GB, 3.9% under
    the chip.
    Each of the eight attention sublayers
    runs the forward kernel once and the backward once: 16 calls of the two
    kernels at the two widths, 24 while the forward ran again in every
    backward; in blocks of 512 since PR 37 (the count does not move: the
    blocks live in VMEM). ``slow``: it takes the chip's compiler a minute on
    every core the suite shares (and half of that is spent whatever the
    depth); tier 1 compiles each kind of sublayer at these shapes
    (``test_joyai_llm_flash_layers_compile_at_published_widths``) and the
    rematerialised attention sublayer
    (``test_joyai_llm_flash_remat_layer_runs_the_forward_kernel_once``)."""
    with caplog.at_level("INFO"):
        n_params, total, calls, text, kept = _compile_8k_step(topo,
                                                              "joyai-stage")
    assert n_params == 621_989_632
    # 15.128 (PR 39); 13.121 with the flash pair alone (PR 35, PR 37)
    assert 14.9e9 < total < 15.25e9 < HBM_BYTES - MARGIN_BYTES, total
    assert (kept["names_granted"], kept["names_offered"]) == (32, 62)
    assert abs(kept["granted_bytes"] / 1e9 - 2.722) < 0.01
    assert kept["granted"][:4] == ["15:experts_w1", "15:experts_w3",
                                   "15:mlp_up", "15:mlp_gate"]
    assert {"9:experts_w1", "9:experts_w3", "14:mla_q_b", "14:mla_kv_b",
            "6:mla_q_a"} <= set(kept["granted"])
    assert "7:experts_w1" in kept["refused_for_room"]
    assert "granted 32 of them, 2.722 GB" in caplog.text
    assert calls == (8, 8)
    assert "bf16[64,8192,192]" in text and "bf16[64,8192,128]" in text
    assert "x 192 (values 128), blocks 512 x 512, no strips" in caplog.text


@pytest.mark.slow
@pytest.mark.parametrize("preset,gb,calls,shape,granted", [
    ("lfm2-stage", 14.606, (2, 2), "2 x 8192 x 32 x 64",
     (13, 22, 3.406, "11:experts_w1 11:experts_w3 10:attn_q 10:attn_k "
      "10:attn_v 9:experts_w1 9:experts_w3 8:shortconv_in 7:experts_w1 "
      "7:experts_w3 6:shortconv_in 2:attn_q 2:attn_k")),
    ("stage", 15.066, (1, 1), "2 x 8192 x 32 x 128",
     (8, 15, 1.340, "8:experts_w1 8:mlp_up 7:mamba_in 6:mlp_up 5:attn_q "
      "5:attn_k 5:attn_v 3:mlp_up")),
], ids=["lfm2", "nemotron"])
def test_8k_train_steps_fit_one_chip_in_blocks_of_512(topo, mosaic, caplog,
                                                      preset, gb, calls,
                                                      shape, granted):
    """The cells ``lfm2-8b-a1b.train-b2s8192`` and
    ``nemotron-twotower-30b-a3b.train-b2s8192`` as they run, COMPOSED: the
    flash kernels in blocks of 512 at 8192 rows beside the weight-gradient
    products. ``_auto_block`` forced 256 there until PR 37, because such a
    composed step had crashed the v5e compiler at 512 in round 5 (an
    earlier kernel, an earlier step); the compiler takes both now, and counts
    what it counted at 256 to every digit. Since PR 39 the count holds what
    ``remat_layers`` was granted from the chip's room (12.503 and 14.654 GB
    with the flash pair alone): three expert sublayers' pairs, two
    in-projections and most of both attention layers' q/k/v in ``lfm2``,
    3.406 GB; in ``nemotron``, whose Mamba-2 layers' backward leaves 0.44
    GB, the last expert sublayer's ``x W1``, the last Mamba-2 in-projection
    (both held at no Mamba-2 layer's backward), three shared experts' ``up``
    and the attention layer's q/k/v, 1.340 GB. ``slow`` for the reason
    ``test_joyai_llm_flash_train_step_fits_one_chip`` is: 35 and 40 s of
    every core."""
    with caplog.at_level("INFO"):
        _, total, got, _, kept = _compile_8k_step(topo, preset)
    assert abs(total / 1e9 - gb) < 0.05, total
    assert total < HBM_BYTES - MARGIN_BYTES, total
    n, of, gbytes, names = granted
    assert (kept["names_granted"], kept["names_offered"]) == (n, of)
    assert abs(kept["granted_bytes"] / 1e9 - gbytes) < 0.01
    assert kept["granted"] == names.split()
    assert f"granted {n} of them, {gbytes:.3f} GB" in caplog.text
    assert got == calls
    assert f"classic kernels, {shape}, blocks 512 x 512, no strips" in caplog.text


def test_gpt2_xl_width_pipe4_rests_sharded(topo, mosaic):
    """GPT-2 XL widths over the four described chips: each chip is handed
    a quarter of the layer leaves and their moments plus the replicated
    embedding and head, the update is in place, and the ring's hops are in
    the program. Depth is cut to 12 layers and the microbatches to 4 (the
    unrolled tick program compiles in seconds per table row, whatever the
    depth; ``chip_smoke.py --four-chips`` runs 8 microbatches and all 48
    layers, rehearsed by hand with :func:`lower_train_step`)."""
    cfg = gpt2_config("xl", n_layers=12, dtype="bfloat16",
                      param_dtype="float32", use_flash_attention=True,
                      use_fused_xent=True)
    mesh = make_mesh(n_pipe=4, devices=topo.devices)
    lowered, params = lower_train_step(
        cfg, mesh, ScheduleConfig(name="1F1B", n_microbatches=4), 8, 1024)
    for leaf in jax.tree.leaves(params["layers"]):
        assert leaf.sharding.spec[0] == "pipe", leaf
    compiled = lowered.compile()
    b = _bytes(compiled)

    def nbytes(tree):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))

    # weights + AdamW mu and nu, all fp32: 3x; layers quartered
    want = 3 * (nbytes(params["layers"]) / 4
                + nbytes(params["embed"]) + nbytes(params["head"]))
    assert abs(b["argument"] - want) < 0.02 * want, (b, want)
    assert b["alias"] > 0.9 * b["argument"], b
    assert (b["argument"] + b["output"] + b["temp"] - b["alias"]
            < HBM_BYTES), b
    text = compiled.as_text()
    assert "collective-permute" in text  # also as -start/-done pairs
    assert "tpu_custom_call" in text
