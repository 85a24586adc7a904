"""The ``qwen3_next`` family on the patterned stack — Gated DeltaNet beside
gated attention (heads with an output gate, q/k norms, RoPE on a quarter of
their columns), each over softmax-routed experts with a gated shared expert,
as one expert-parallel rank holds it — at a small size on the CPU: the
chunked delta rule against the token recurrence (outputs and gradients, at
chunk boundaries, in groups of heads), the inverses its recompute writes for
the backward kernel, which builds none, the program against the plain
reference the benchmark keeps (``benchmark/reference/qwen3_next.py``, the
same file the chip run is held to) on the loss and every gradient leaf and
each mixer alone, the partial rotation, the four shares of an expert layer
adding up to the whole with the shared expert counted once, the kinds the
other configurations run lowering to the parent's programs, and the normal
training path with its regions."""

import importlib.util
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distributed_training_with_pipeline_parallelism_tpu as dtpp
from distributed_training_with_pipeline_parallelism_tpu.models import (
    nemotron_h, transformer as tfm)
from distributed_training_with_pipeline_parallelism_tpu.ops import (
    attention, experts, gated_deltanet as gdn)
from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import (
    make_mesh)
from distributed_training_with_pipeline_parallelism_tpu.utils import train
from distributed_training_with_pipeline_parallelism_tpu.utils.profiling import (
    classify)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# both mixers and two expert sublayers; 16 experts, 4 held; key heads
# serving two value heads each, as published; heads of 32 turning 8 columns
SIZES = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=32, vocab_size=128, hybrid_override_pattern="GE*E",
    max_position_embeddings=4096, rms_norm_eps=1e-6, rope_theta=10000000,
    partial_rotary_factor=0.25, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, linear_conv_kernel_dim=4, router_width=16,
    experts_held=[0, 1, 2, 3], num_experts_per_tok=3,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    norm_topk_prob=True)
SEQ = 80  # one whole chunk of 64 and a padded one


@pytest.fixture(scope="module")
def ref():
    """The benchmark's plain reference, by path as its runner loads it."""
    path = os.path.join(ROOT, "benchmark", "reference", "qwen3_next.py")
    spec = importlib.util.spec_from_file_location("reference_qwen3_next",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def seeded(params, key=7):
    """``params`` with q/k norm scales that are not all one (a norm whose
    scale is one number commutes with the rotation) and decays that keep
    some memory across the sequence (``A_log = log U[0, 16]`` forgets most
    heads within a position or two)."""
    kq, kk, ka = jax.random.split(jax.random.key(key), 3)
    layers = dict(params["layers"])
    attn, lin = dict(layers["attn"]), dict(layers["gdn"])
    inner = dict(attn["attn"])
    for name, k in (("q_layernorm", kq), ("k_layernorm", kk)):
        scale = inner[name]["scale"]
        inner[name] = {"scale": jax.random.uniform(k, scale.shape, minval=0.5,
                                                   maxval=1.5)}
    attn["attn"] = inner
    lin["A_log"] = jnp.log(jax.random.uniform(ka, lin["A_log"].shape,
                                              minval=0.01, maxval=0.3))
    layers.update(attn=attn, gdn=lin)
    return dict(params, layers=layers)


def batch(seq=SEQ, rows=2, seed=1):
    toks = np.random.default_rng(seed).integers(
        0, SIZES["vocab_size"], (rows, seq + 1), dtype=np.int32)
    return toks[:, :-1], toks[:, 1:]


@pytest.fixture(scope="module")
def compiled(ref):
    cfg = ref.model_config(SIZES, {})
    return types.SimpleNamespace(
        cfg=cfg,
        params=jax.jit(lambda k: seeded(tfm.transformer_init(k, cfg)))(
            jax.random.key(0)),
        ref_grads=jax.jit(jax.value_and_grad(
            lambda p, x, y: ref.loss(p, x, y, SIZES))))


def test_the_tree_is_the_sources(compiled):
    layers = compiled.params["layers"]
    assert set(layers) == {"gdn", "attn", "moe"}
    lin = layers["gdn"]
    assert set(lin) == {"norm", "in_proj_qkvz", "in_proj_ba", "conv",
                        "A_log", "dt_bias", "gate_norm", "out_proj"}
    # q | k | v | z: 2 x 2 x 16 + 2 x 4 x 16; b | a: 2 x 4
    assert lin["in_proj_qkvz"]["w"].shape == (1, 64, 192)
    assert lin["in_proj_ba"]["w"].shape == (1, 64, 8)
    assert lin["conv"]["w"].shape == (1, 4, 128)   # q | k | v, no bias
    assert lin["gate_norm"]["scale"].shape == (1, 16)
    a = layers["attn"]["attn"]
    assert a["q"]["w"].shape == (1, 64, 4 * 2 * 32)   # query and gate a head
    assert a["k"]["w"].shape == (1, 64, 2 * 32)
    assert a["q_layernorm"]["scale"].shape == (1, 32)
    moe = layers["moe"]
    assert set(moe) == {"norm", "router", "experts", "shared", "shared_gate"}
    assert set(moe["router"]) == {"w"}                 # softmax: no bias
    assert moe["shared_gate"]["w"].shape == (2, 64, 1)
    assert moe["experts"]["w1"].shape == (2, 4, 64, 32)


# (positions, log-decay g from a standard normal n, key heads under the
# four value heads, beta's logit shift)
RULE_CASES = {
    "two-chunks": (128, lambda n: -0.2 * jax.nn.softplus(n), 4, 0.0),
    "three-chunks": (192, lambda n: -0.2 * jax.nn.softplus(n), 4, 0.0),
    "weak-decay": (128, lambda n: -1e-3 * jax.nn.softplus(n), 4, 0.0),
    "strong-decay": (128, lambda n: -5.0 + 0.5 * jnp.tanh(n), 4, 0.0),
    "repeated-keys": (128, lambda n: -0.2 * jax.nn.softplus(n), 2, 0.0),
    "beta-near-1": (128, lambda n: -0.2 * jax.nn.softplus(n), 4, 6.0),
}


def rule_inputs(case):
    """``q, k, v, g, beta`` and a cotangent for ``o``: two rows of
    ``RULE_CASES[case]``, four value heads of 8, key heads of 16."""
    seq, decay, key_heads, shift = RULE_CASES[case]
    ks = jax.random.split(jax.random.key(seq), 6)
    q = gdn.l2norm(jax.random.normal(ks[0], (2, seq, key_heads, 16))) / 4.0
    k = gdn.l2norm(jax.random.normal(ks[1], (2, seq, key_heads, 16)))
    v = jax.random.normal(ks[2], (2, seq, 4, 8))
    g = decay(jax.random.normal(ks[3], (2, seq, 4)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (2, seq, 4)) + shift)
    return q, k, v, g, beta, jax.random.normal(ks[5], (2, seq, 4, 8))


@pytest.mark.parametrize("case", sorted(RULE_CASES))
def test_chunked_rule_is_the_token_recurrence(ref, case):
    """Outputs and the gradients of every input: the kernel pair across
    chunk boundaries (interpreted here), its forward and its hand-written
    backward, against the reference's position-by-position recurrence under
    autodiff; in ``repeated-keys`` each key head serves two value heads, as
    in the mixer (lengths that are no multiple of the chunk:
    ``test_each_mixer_alone_equals_the_reference``, whose mixer pads 80
    positions to 128)."""
    key_heads = RULE_CASES[case][2]
    q, k, v, g, beta, cot = rule_inputs(case)

    def chunked(*x):
        return jnp.vdot(gdn.chunked_delta_rule(*x), cot)

    def token(q, k, *x):  # the reference reads keys repeated to the values
        q, k = (jnp.repeat(y, 4 // key_heads, axis=2) for y in (q, k))
        return jnp.vdot(ref.recurrence(q, k, *x), cot)

    with jax.default_matmul_precision("highest"):
        got, g_got = jax.jit(jax.value_and_grad(
            chunked, argnums=range(5)))(q, k, v, g, beta)
        want, g_want = jax.jit(jax.value_and_grad(
            token, argnums=range(5)))(q, k, v, g, beta)
    assert abs(float(got - want)) < 1e-5 * abs(float(want))
    for a, b in zip(g_got, g_want):
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 1e-5


@pytest.mark.parametrize("case", sorted(RULE_CASES))
def test_recompute_writes_each_chunks_inverse(case):
    """The forward kernel called for the backward (``keep_states=True``)
    writes each grid step's ``T``, block diagonal: every head's block
    inverts ``I + A``, ``A`` the chunk's strictly lower ``beta_i k_i.k_j
    e^{gamma_i - gamma_j}`` built here from the inputs in float64, to 1e-6,
    and nothing lies between heads. Its ``o`` is the forward phase's call's,
    which (``keep_states=False``) returns ``o`` alone."""
    q, k, v, g, beta, _ = rule_inputs(case)
    rows, seq, key_heads, _ = q.shape
    heads, n, c = gdn._heads(4), gdn.CHUNK, seq // gdn.CHUNK
    o = gdn._forward(q, k, v, g, beta, keep_states=False)
    assert isinstance(o, jax.Array) and o.shape == (rows, seq, 4, 8)
    o_kept, _, t = gdn._forward(q, k, v, g, beta, keep_states=True)
    np.testing.assert_array_equal(o_kept, o)
    assert t.shape == (rows, 4 // heads, c, heads * n, heads * n)
    t = np.asarray(t, np.float64).reshape(rows, 4 // heads, c, heads, n,
                                          heads, n)
    diag = np.einsum("rscanbm,ab->rscanbm", t, np.eye(heads))
    assert not np.any(t - diag), "T is not zero between heads"
    blocks = np.einsum("rscanam->rsacnm", t).reshape(rows, 4, c, n, n)
    # each value head's keys, chunks, cumulative log-decays and its A
    kc = np.repeat(np.asarray(k, np.float64), 4 // key_heads, axis=2)
    kc = kc.reshape(rows, c, n, 4, -1).transpose(0, 3, 1, 2, 4)
    gamma = np.cumsum(np.asarray(g, np.float64).reshape(rows, c, n, 4),
                      axis=2).transpose(0, 3, 1, 2)
    b = np.asarray(beta, np.float64).reshape(rows, c, n, 4).transpose(
        0, 3, 1, 2)
    lower = np.tril(np.ones((n, n), bool), -1)
    lag = np.where(lower, gamma[..., :, None] - gamma[..., None, :], -np.inf)
    a = (b[..., :, None] * np.einsum("rhcid,rhcjd->rhcij", kc, kc)
         * np.exp(lag))
    err = np.abs((np.eye(n) + a) @ blocks - np.eye(n)).max()
    assert err < 1e-6, err


def test_program_equals_reference_loss_and_every_gradient(compiled):
    """Float32, where the two agree to rounding (the chunked rule sums in
    another order than the recurrence: the decay parameters read 1e-5 apart);
    bf16 over fp32 masters is ``test_train_step_on_the_normal_path``'s."""
    x, y = batch()
    got, g_got = jax.jit(jax.value_and_grad(
        lambda p: tfm.transformer_loss(compiled.cfg, p, x, y)))(
            compiled.params)
    want, g_want = compiled.ref_grads(compiled.params, x, y)
    assert abs(float(got) - float(want)) / float(want) < 1e-6
    apart = jax.tree.map(
        lambda a, b: float(jnp.linalg.norm(a.astype(jnp.float32) - b)
                           / (jnp.linalg.norm(b) + 1e-30)), g_got, g_want)
    for path, rel in jax.tree_util.tree_flatten_with_path(apart)[0]:
        assert rel < 1e-4, (jax.tree_util.keystr(path), rel)


def sublayer(ref, params, letter):
    return next(p for l, p in ref.layers_of(params, SIZES) if l == letter)


@pytest.mark.parametrize("letter", list("G*E"))
def test_each_mixer_alone_equals_the_reference(ref, compiled, letter):
    p = sublayer(ref, compiled.params, letter)
    x = jax.random.normal(jax.random.key(5), (2, SEQ, 64))
    with jax.default_matmul_precision("highest"):
        got, counts = jax.jit(lambda p, x: nemotron_h.mixer(
            compiled.cfg, ref.STACK[letter], p, x))(p, x)
    want = jax.jit(lambda p, x: ref.mixer(letter, p, x, SIZES))(p, x)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert (counts is not None) == (letter == "E")


def test_partial_rope_turns_the_first_columns_only(ref):
    """``apply_rope`` with angles for 8 of 32 columns turns those 8 in split
    halves (4 | 4) and passes the other 24 as they are."""
    x = jax.random.normal(jax.random.key(2), (2, 16, 3, 32))
    got, want = jax.jit(lambda x: (
        attention.apply_rope(x, attention.rope_frequencies(8, 16, 1e7)),
        ref.rope_halves(x[..., :8], 1e7)))(x)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    np.testing.assert_allclose(got[..., :8], want, rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(got[..., :8] - x[..., :8]).max()) > 0.1


def test_the_four_shares_add_up_to_the_uncut_layer(ref):
    """16 experts over 4 ranks of 4: the parts the shares give, with the
    gated shared expert — every rank's — counted once, are the whole layer as
    the reference computes it with every expert held."""
    whole = dict(SIZES, experts_held=list(range(16)))
    cfg = ref.model_config(whole, {})
    p = nemotron_h.mixer_init(jax.random.key(2), cfg, "moe")
    x = jax.random.normal(jax.random.key(3), (40, cfg.dim))
    apply = jax.jit(experts.experts_apply, static_argnums=(3, 4, 5, 6))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: ref._experts(p, x, whole))(p, x)
        total, seen = jnp.zeros_like(want), 0
        for rank in range(4):
            mine = dict(router=p["router"], experts=jax.tree.map(
                lambda w: w[4 * rank:4 * rank + 4], p["experts"]))
            if rank == 0:
                mine.update(shared=p["shared"], shared_gate=p["shared_gate"])
            out, counts = apply(
                mine, x, jnp.arange(4 * rank, 4 * rank + 4),
                cfg.num_experts_per_tok, cfg.routed_scaling_factor,
                cfg.router_norm_eps, cfg.router_scoring)
            seen += int(counts.sum())
            total = total + out
    assert seen == 40 * cfg.num_experts_per_tok  # every assignment, once
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-6)


# sha256 (16 digits) of the jaxprs of the kinds the other configurations
# run, as the parent commit (378529e) traces them: the options this family
# adds are off there, and the sigmoid routing, the ungated attention and
# their initialisers are the programs they were
PARENT_JAXPRS = {
    "debug/moe": "6526009478fac687", "debug/moe/init": "5dfc56284e32db90",
    "lfm2-debug/moe": "0d7ac28077f5234b",
    "lfm2-debug/moe/init": "916bf0750bf39505",
    "joyai-debug/moe": "e2e4feba4d7aaff3",
    "joyai-debug/moe/init": "474c3c7b38555e63",
    "debug/attn": "4119762f13dae5e2", "debug/attn/init": "96973d2ea1071ca6",
    "lfm2-debug/attn": "77ac86e541c3c0a1",
    "lfm2-debug/attn/init": "cb187e09ff1e27e2"}


@pytest.mark.parametrize("which", sorted(PARENT_JAXPRS))
def test_other_kinds_trace_the_parents_programs(which):
    import hashlib
    preset, kind, *init = which.split("/")
    cfg = nemotron_h.nemotron_h_config(preset)
    if init:
        fn, args = (lambda k: nemotron_h.mixer_init(k, cfg, kind),
                    (jax.random.key(0),))
    else:
        p = jax.eval_shape(lambda: nemotron_h.mixer_init(
            jax.random.key(0), cfg, kind))
        fn, args = (lambda p, x: nemotron_h.mixer_apply(cfg, kind, p, x),
                    (p, jax.ShapeDtypeStruct((2, 48, cfg.dim), jnp.float32)))
    text = str(jax.make_jaxpr(fn)(*args))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == (
        PARENT_JAXPRS[which])


@pytest.fixture(scope="module")
def trained(ref):
    """Two AdamW steps through the normal path, bf16 over fp32,
    rematerialised."""
    cfg = ref.model_config(SIZES, dict(
        dtype="bfloat16", param_dtype="float32", remat_layers=True))
    mesh = make_mesh(n_pipe=1, devices=jax.devices()[:1])
    sched = dtpp.ScheduleConfig(name="1F1B", n_microbatches=2)
    opt = train.adamw(total_steps=10)
    params = train.init_params(cfg, mesh, jax.random.key(0))
    before = jax.tree.map(jnp.copy, params)
    opt_state = train.init_opt_state(opt, params, mesh)
    x, y = batch()
    fn = train.make_train_step(cfg, mesh, sched, opt)
    jaxpr = jax.make_jaxpr(fn)(params, opt_state, x, y)
    step = fn.lower(params, opt_state, x, y).compile()
    params, opt_state, loss = step(params, opt_state, x, y)
    after, _, _ = step(params, opt_state, x, y)  # the first has lr 0
    return cfg, before, after, float(loss), step, (x, y), jaxpr


def test_train_step_on_the_normal_path(trained, compiled):
    cfg, before, after, loss, _, (x, y), _ = trained
    want, _ = compiled.ref_grads(jax.device_get(before), x, y)
    assert abs(loss - float(want)) / float(want) < 2e-3
    moved = jax.jit(lambda a, b: jax.tree.map(
        lambda u, v: jnp.abs(u - v).max(), a, b))(before, after)
    for path in (("gdn", "A_log"), ("gdn", "in_proj_qkvz", "w"),
                 ("gdn", "conv", "w"), ("attn", "attn", "q_layernorm",
                                        "scale"),
                 ("moe", "shared_gate", "w"), ("moe", "experts", "w3")):
        leaf = moved["layers"]
        for key in path:
            leaf = leaf[key]
        assert leaf > 0, path
    cast = jax.eval_shape(lambda p: tfm.compute_cast(cfg, p), before)
    assert cast["layers"]["moe"]["router"]["w"].dtype == jnp.float32
    assert cast["layers"]["gdn"]["A_log"].dtype == jnp.float32
    assert cast["layers"]["gdn"]["dt_bias"].dtype == jnp.float32
    assert cast["layers"]["gdn"]["in_proj_qkvz"]["w"].dtype == jnp.bfloat16


def test_compiled_step_names_the_regions(trained):
    names = re.findall(r'op_name="([^"]*)"', trained[4].as_text())
    read = {classify(n) for n in names}
    for region in ("model/gdn", "model/gdn_scan", "model/attn", "model/moe",
                   "model/moe_experts", "model/head_loss"):
        assert any(r == region for _, r in read), region
    for phase in ("forward", "backward", "recompute"):  # remat_layers
        assert (phase, "model/gdn_scan") in read, phase
    assert not any(r in ("model/ssm", "model/shortconv", "model/mlp")
                   for _, r in read)


def test_both_kernels_run_inside_the_scan_region_in_every_phase(
        trained, pallas_kernels):
    """The step runs the forward kernel in the forward and in
    ``remat_layers``' recompute and the backward kernel in the backward,
    every call inside ``model/gdn_scan`` (the region the benchmark's
    ``model.gdn_scan_share_pct`` and ``kernels.gdn_scan_roofline_pct``
    read); in the compiled step the interpreted kernels' grid loops, the
    region's only loops, fall in the same three phases."""
    assert sorted(set(pallas_kernels(trained[6].jaxpr))) == [
        ("_gdn_bwd_kernel", "backward", "model/gdn_scan"),
        ("_gdn_fwd_kernel", "forward", "model/gdn_scan"),
        ("_gdn_fwd_kernel", "recompute", "model/gdn_scan")]
    loops = {classify(name) for name in re.findall(
        r'^\s*(?:ROOT )?%?while[\w.\-]* = [^\n]*op_name="([^"]*)"',
        trained[4].as_text(), re.M)}
    assert {(p, r) for p, r in loops if r == "model/gdn_scan"} == {
        ("forward", "model/gdn_scan"), ("recompute", "model/gdn_scan"),
        ("backward", "model/gdn_scan")}


def _dot_generals(jaxpr) -> int:
    """``dot_general`` equations in ``jaxpr`` and the jaxprs inside it."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "dot_general"
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _dot_generals(sub)
    return n


def test_backward_kernel_builds_no_inverse():
    """The backward kernel reads each chunk's ``T`` from the recompute and
    runs its second forward of the chunk only up to what the gradients
    read. Its products, two heads a grid step, were 46 in the parent: the
    chunk's forward 21 (``k k^T`` 1, the inverse by halves 10 — two a level
    for the five levels past the first —, ``w``, ``u`` 2, ``q k^T`` 1, the
    updates 2 — one a head —, ``o`` 3, the leaving states 2) and its
    gradients 25. Now 46 - 10 (the inverse) - 3 (``o``) - 2 (the leaving
    states) = 31; the forward kernel keeps its 21."""
    q, k, v, g, beta, cot = rule_inputs("repeated-keys")
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *x: jnp.vdot(gdn.chunked_delta_rule(*x), cot),
        argnums=range(5)))(q, k, v, g, beta)
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                body = eqn.params["jaxpr"]
                found[body.debug_info.func_src_info.split()[0]] = (
                    _dot_generals(body))
            for param in eqn.params.values():
                for sub in (param if isinstance(param, (list, tuple))
                            else [param]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(jaxpr.jaxpr)
    assert found == {"_gdn_fwd_kernel": 21, "_gdn_bwd_kernel": 46 - 10 - 5}


def test_new_knobs_are_checked_and_named(monkeypatch):
    x = jnp.zeros((1, 64, 2, 16))
    with pytest.raises(ValueError, match="a multiple of 64"):
        gdn.chunked_delta_rule(x[:, :48], x[:, :48], x[:, :48],
                               x[:, :48, :, 0], x[:, :48, :, 0])
    monkeypatch.setattr(gdn, "_use_interpret", lambda: False)  # as on a TPU
    with pytest.raises(NotImplementedError, match="multiples of 128"):
        gdn.chunked_delta_rule(x, x, x, x[..., 0], x[..., 0])
    base = dict(arch="nemotron_h", dim=64, n_layers=2, n_heads=4,
                vocab_size=128, hybrid_override_pattern="GE",
                n_routed_experts=8, num_experts_per_tok=2)
    cfg = dtpp.ModelConfig(**base)
    assert (cfg.router_scoring, cfg.shared_expert_gate, cfg.attn_output_gate,
            cfg.partial_rotary_factor, cfg.rotary_dim) == (
                "sigmoid", False, False, 1.0, 16)
    with pytest.raises(ValueError, match="router_scoring"):
        dtpp.ModelConfig(**base, router_scoring="top1")
    with pytest.raises(ValueError, match="linear_num_value_heads"):
        dtpp.ModelConfig(**base, linear_num_value_heads=24)
    with pytest.raises(ValueError, match="partial_rotary_factor"):
        dtpp.ModelConfig(**base, partial_rotary_factor=0.0)
    with pytest.raises(ValueError, match="attn_rope"):
        dtpp.ModelConfig(**base, attn_rope=True, partial_rotary_factor=0.2)
    with pytest.raises(ValueError, match="shared_expert_gate"):
        dtpp.ModelConfig(**base, shared_expert_gate=True,
                         moe_shared_expert_intermediate_size=0)
    with pytest.raises(ValueError, match="'G' Gated DeltaNet"):
        dtpp.ModelConfig(**dict(base, hybrid_override_pattern="GX"))
    stage = nemotron_h.nemotron_h_config("qwen3-next-stage")
    assert (stage.hybrid_override_pattern, stage.head_dim, stage.rotary_dim,
            stage.n_kv_heads, stage.held_experts) == (
                "GEGEGE*E", 256, 64, 2, tuple(range(32)))
    with pytest.raises(NotImplementedError, match="Gated DeltaNet layer"):
        nemotron_h.not_served("models/generate.py", stage)
