"""The ``nemotron_h`` family — a patterned stack of Mamba-2, attention and
expert layers as one expert-parallel rank holds it — at a small size on the
CPU: the program against the plain reference the benchmark keeps
(``benchmark/reference/nemotron_h.py``, the same file the chip run is held
to), the chunked scan against the literal recurrence, the shares of a layer
adding up to the whole, a collapsed routing dropping nothing, the named
errors, and that a stack of one kind is untouched."""

import dataclasses
import importlib.util
import logging
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
    experts, mamba2)
from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import (
    make_mesh)
from distributed_training_with_pipeline_parallelism_tpu.utils import train
from distributed_training_with_pipeline_parallelism_tpu.utils.profiling import (
    HYBRID_REGIONS, classify)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SIZES = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    vocab_size=128, hybrid_override_pattern="MEM*E",
    max_position_embeddings=4096, layer_norm_epsilon=1e-5, rope_theta=10000,
    mamba_num_heads=8, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
    conv_kernel=4, chunk_size=8, time_step_min=0.001, time_step_max=0.1,
    time_step_floor=1e-4, router_width=16, experts_held=[0, 1, 2, 3],
    num_experts_per_tok=3, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=48, routed_scaling_factor=2.5)


@pytest.fixture(scope="module")
def ref():
    """The benchmark's plain reference, by path as its runner loads it."""
    path = os.path.join(ROOT, "benchmark", "reference", "nemotron_h.py")
    spec = importlib.util.spec_from_file_location("reference_nemotron_h", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def batch(seq, rows=2, seed=1):
    toks = np.random.default_rng(seed).integers(
        0, SIZES["vocab_size"], (rows, seq + 1), dtype=np.int32)
    return toks[:, :-1], toks[:, 1:]


@pytest.fixture(scope="module")
def compiled(ref):
    """What several tests run on the float32 debug model, each jitted ONCE
    for the module (a function made inside a test compiles again in every
    case): the reference's loss with its gradients, its loss alone, its
    routing counts, and the program's loss, alone and with its gradients."""
    cfg = ref.model_config(SIZES, {})
    return types.SimpleNamespace(
        cfg=cfg, params=jax.jit(lambda k: tfm.transformer_init(k, cfg))(
            jax.random.key(0)),  # one program, not one an op
        ref_grads=jax.jit(jax.value_and_grad(
            lambda p, x, y: ref.loss(p, x, y, SIZES))),
        ref_loss=jax.jit(lambda p, x, y: ref.loss(p, x, y, SIZES)),
        ref_counts=jax.jit(lambda p, x: ref.routing_counts(p, x, SIZES)),
        loss=jax.jit(lambda p, x, y: tfm.transformer_loss(cfg, p, x, y)),
        grads=jax.jit(jax.value_and_grad(
            lambda p, x, y: tfm.transformer_loss(cfg, p, x, y))))


# bf16 over fp32 masters at this size (48 tokens, so little averages out):
# measured 2.8e-4 on the loss and at most 5.1e-2 relative L2 on a gradient
# leaf; float32 agrees to rounding (8e-7 on the worst leaf)
@pytest.mark.parametrize("numerics,loss_tol,grad_tol", [
    ({}, 1e-6, 1e-5),
    (dict(dtype="bfloat16", param_dtype="float32"), 2e-3, 0.15),
], ids=["float32", "bf16-over-fp32"])
@pytest.mark.parametrize("seq", [24, 21], ids=["chunks", "ragged"])
def test_program_equals_reference_loss_and_every_gradient(
        ref, compiled, numerics, seq, loss_tol, grad_tol):
    cfg = ref.model_config(SIZES, numerics)
    params = compiled.params  # fp32 masters under either numerics
    x, y = batch(seq)
    program = compiled.grads if not numerics else jax.jit(jax.value_and_grad(
        lambda p, x, y: tfm.transformer_loss(cfg, p, x, y)))
    got, g_got = program(params, x, y)
    want, g_want = compiled.ref_grads(params, x, y)
    assert abs(float(got) - float(want)) / float(want) < loss_tol
    apart = jax.tree.map(
        lambda a, b: float(jnp.linalg.norm(a.astype(jnp.float32) - b)
                           / (jnp.linalg.norm(b) + 1e-30)), g_got, g_want)
    for path, rel in jax.tree_util.tree_flatten_with_path(apart)[0]:
        assert rel < grad_tol, (jax.tree_util.keystr(path), rel)
    # the router's bias is a buffer: no gradient reaches it
    assert not np.any(np.asarray(g_got["layers"]["moe"]["router"]["bias"]))


def literal_recurrence(x, dt, A, B, C):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t; y_t = S_t C_t — numpy,
    one time step at a time."""
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    B, C = (np.repeat(m, H // G, axis=2) for m in (B, C))
    S = np.zeros((b, H, P, N))
    y = np.zeros((b, T, H, P))
    for t in range(T):
        S = (np.exp(dt[:, t] * A)[..., None, None] * S
             + (dt[:, t, :, None] * x[:, t])[..., None] * B[:, t, :, None, :])
        y[:, t] = (S * C[:, t, :, None, :]).sum(-1)
    return y


def scanned_recurrence(x, dt, A, B, C):
    """The same recurrence as a ``lax.scan`` over time steps, float32 at
    HIGHEST: what ``jax.grad`` differentiates for the kernels' backward."""
    H, G = x.shape[2], B.shape[2]
    B, C = (jnp.repeat(m, H // G, axis=2) for m in (B, C))

    def step(S, t):
        x_t, dt_t, B_t, C_t = t
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
        return S, jnp.einsum("bhpn,bhn->bhp", S, C_t, precision="highest")

    S0 = jnp.zeros((x.shape[0], H, x.shape[3], B.shape[3]))
    _, y = jax.lax.scan(step, S0, tuple(jnp.moveaxis(m, 1, 0)
                                        for m in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1)


# (positions, heads, head width, groups, state, chunk): chunks after chunks,
# one chunk, many chunks, one head a group, every head in one group
SCAN_CASES = {
    "chunk4": (16, 4, 3, 2, 5, 4), "one-chunk": (16, 4, 3, 2, 5, 16),
    "many-chunks": (64, 4, 3, 2, 5, 4), "a-head-a-group": (16, 4, 3, 4, 5, 4),
    "one-group": (24, 6, 4, 1, 8, 8)}


def scan_inputs(case, seed=0):
    T, H, P, G, N, _ = SCAN_CASES[case]
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(2, T, H, P)).astype(np.float32)
    dt = rng.uniform(0.01, 0.5, (2, T, H)).astype(np.float32)
    A = -rng.uniform(0.5, 4.0, (H,)).astype(np.float32)
    B, C = (rng.normal(size=(2, T, G, N)).astype(np.float32) for _ in "BC")
    return x, dt, A, B, C


@pytest.mark.parametrize("chunk", sorted(SCAN_CASES))
def test_chunked_scan_equals_literal_recurrence(chunk):
    """The kernel pair's forward (interpreted here) in float32 against the
    recurrence one step at a time: a state rounded to bf16 between chunks
    reads some 1e-3 off."""
    x, dt, A, B, C = scan_inputs(chunk)
    got = mamba2.ssd_chunked(*(jnp.asarray(a) for a in (x, dt, A, B, C)),
                             SCAN_CASES[chunk][-1])
    np.testing.assert_allclose(got, literal_recurrence(x, dt, A, B, C),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("case", ["chunk4", "one-chunk", "many-chunks",
                                  "one-group"])
def test_scan_gradients_equal_the_recurrences(case):
    """The hand-written backward: the gradients of ``x``, ``dt``, ``A``,
    ``B`` and ``C`` against ``jax.grad`` of the recurrence as a plain
    ``lax.scan`` (``dt`` reaches ``y`` through ``x dt`` and through the
    log-decay ``dt A``; ``A`` through the decay alone, summed over rows and
    positions)."""
    args = tuple(jnp.asarray(a) for a in scan_inputs(case, seed=1))
    cot = jax.random.normal(jax.random.key(2), args[0].shape)

    def chunked(*a):
        return jnp.vdot(mamba2.ssd_chunked(*a, SCAN_CASES[case][-1]), cot)

    def scanned(*a):
        return jnp.vdot(scanned_recurrence(*a), cot)

    with jax.default_matmul_precision("highest"):
        got, g_got = jax.jit(jax.value_and_grad(
            chunked, argnums=range(5)))(*args)
        want, g_want = jax.jit(jax.value_and_grad(
            scanned, argnums=range(5)))(*args)
    assert abs(float(got - want)) < 1e-5 * abs(float(want))
    for name, a, b in zip("x dt A B C".split(), g_got, g_want):
        rel = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert rel < 1e-5, (name, rel)


def test_the_shares_add_up_to_the_uncut_layer(ref):
    """16 experts over 4 ranks of 4: the routed parts of all shares plus the
    shared expert counted once are the whole layer as the reference computes
    it with every expert held."""
    whole = dict(SIZES, experts_held=list(range(16)))
    cfg = ref.model_config(whole, {})
    p = jax.jit(lambda k: nemotron_h.mixer_init(k, cfg, "moe"))(
        jax.random.key(2))
    x = jax.random.normal(jax.random.key(3), (40, cfg.dim))
    # one compiled program a side: the held ids are data (an array)
    apply = jax.jit(experts.experts_apply, static_argnums=(3, 4))
    with jax.default_matmul_precision("highest"):
        want, shared = jax.jit(lambda p, x: (
            ref._experts(p, x, whole),
            ref._relu2(x @ p["shared"]["up"]["w"]) @ p["shared"]["down"]["w"])
        )(p, x)
        total, seen = shared, 0
        for rank in range(4):
            mine = dict(p, experts=jax.tree.map(lambda w: w[4 * rank:4 * rank + 4],
                                                p["experts"]))
            out, counts = apply(
                mine, x, jnp.arange(4 * rank, 4 * rank + 4),
                cfg.num_experts_per_tok, cfg.routed_scaling_factor)
            seen += int(counts.sum())
            total = total + (out - shared)
    assert seen == 40 * cfg.num_experts_per_tok  # every assignment, once
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bias", [0.0, 1.0], ids=["at-init", "collapsed"])
def test_nothing_is_dropped_whatever_is_routed(compiled, bias):
    """There is no buffer to overflow: with every token sent to the same
    three held experts (the routing a rank's share of training collapses to,
    here by the router's bias) the loss is still the reference's, and
    ``routing_stats`` counts what the reference's router chooses."""
    x, y = batch(24)
    cfg = compiled.cfg
    params = jax.tree.map(lambda w: w, compiled.params)  # a tree of its own
    params["layers"]["moe"]["router"]["bias"] = params["layers"]["moe"][
        "router"]["bias"].at[:, :3].set(bias)
    got = float(compiled.loss(params, x, y))
    want = float(compiled.ref_loss(params, x, y))
    assert abs(got - want) / want < 1e-6
    stats = nemotron_h.routing_stats(cfg, params, x)
    assert stats["tokens_per_expert"].shape == (2, 4)  # 2 E layers, 4 held
    np.testing.assert_array_equal(stats["tokens_per_expert"],
                                  compiled.ref_counts(params, x))
    if bias:  # sigmoid scores lie in (0, 1): a bias of 1 always wins
        np.testing.assert_array_equal(stats["tokens_per_expert"],
                                      [[48, 48, 48, 0]] * 2)
    np.testing.assert_allclose(
        stats["max_over_mean"],
        stats["tokens_per_expert"].max(-1) / stats["tokens_per_expert"].mean(-1))
    assert "E1: max/mean" in nemotron_h.describe_routing(stats)


def test_build_says_pattern_and_held_experts(ref, caplog):
    with caplog.at_level(logging.INFO):  # said while tracing: no run needed
        cfg = ref.model_config(SIZES, {})
        params = jax.eval_shape(
            lambda: tfm.transformer_init(jax.random.key(0), cfg))
        jax.eval_shape(lambda p: tfm.transformer_loss(cfg, p, *batch(24)),
                       params)
    assert "MEM*E" in caplog.text and "[0, 1, 2, 3] of 16" in caplog.text


def base(**over):
    return dict(dict(arch="nemotron_h", dim=64, n_layers=5, n_heads=4,
                     n_kv_heads=2, head_dim_override=16, vocab_size=128,
                     hybrid_override_pattern="MEM*E", mamba_num_heads=8,
                     mamba_head_dim=8, n_groups=2, ssm_state_size=16,
                     n_routed_experts=16, num_experts_per_tok=3,
                     experts_held=(0, 1, 2, 3)), **over)


@pytest.mark.parametrize("over,error,match", [
    (dict(hybrid_override_pattern="MEMXE"), ValueError, r"unknown layer kind"),
    (dict(hybrid_override_pattern="MEM"), ValueError, "n_layers"),
    (dict(conv_L_cache=0), ValueError, "conv_L_cache"),
    (dict(experts_held=(0, 16)), ValueError, "experts_held"),
    (dict(tie_embeddings=True), NotImplementedError, "tie_embeddings"),
    (dict(arch="gpt2", hybrid_override_pattern="MEM*E"), ValueError,
     "requires arch"),
], ids=["letter", "length", "taps", "held", "tied", "other-arch"])
def test_configuration_errors_are_named(over, error, match):
    with pytest.raises(error, match=match):
        dtpp.ModelConfig(**base(**over))


@pytest.mark.parametrize("axes,match", [
    (dict(n_pipe=2), "pipeline stages"),
    (dict(n_pipe=1, n_model=2), "tensor-parallel"),
    (dict(n_pipe=1, n_seq=2), "sequence-parallel"),
], ids=["pipe", "tp", "sp"])
def test_meshes_it_does_not_run_are_named_errors(axes, match):
    cfg = dtpp.ModelConfig(**base())
    mesh = make_mesh(devices=jax.devices()[:2], **axes)
    sched = dtpp.ScheduleConfig(name="1F1B", n_microbatches=2)
    with pytest.raises(NotImplementedError, match=match):
        train.init_params(cfg, mesh, jax.random.key(0))
    with pytest.raises(NotImplementedError, match=match):
        train.make_train_step(cfg, mesh, sched, train.adamw())
    with pytest.raises(NotImplementedError, match=match):
        train.make_eval_fn(cfg, mesh, sched)


def test_generation_and_serving_are_named_errors():
    from distributed_training_with_pipeline_parallelism_tpu.models.generate import (
        generate)
    from distributed_training_with_pipeline_parallelism_tpu.parallel.pipelined_decode import (
        make_pipeline_generate_fn)
    from distributed_training_with_pipeline_parallelism_tpu.serving.engine import (
        make_serving_step_fn)
    cfg = dtpp.ModelConfig(**base())
    mesh = make_mesh(n_pipe=1, devices=jax.devices()[:1])
    with pytest.raises(NotImplementedError, match="generation"):
        generate(cfg, {}, jnp.zeros((1, 4), jnp.int32), 2)
    with pytest.raises(NotImplementedError, match="generation"):
        make_pipeline_generate_fn(cfg, mesh, 2)
    with pytest.raises(NotImplementedError, match="generation"):
        make_serving_step_fn(cfg, mesh, n_slots=2, max_len=16, prompt_max=8,
                             out_max=8)


@pytest.fixture(scope="module")
def trained(ref):
    """Two AdamW steps through the normal path, bf16 over fp32."""
    cfg = ref.model_config(SIZES, dict(
        dtype="bfloat16", param_dtype="float32", remat_layers=True))
    mesh = make_mesh(n_pipe=1, devices=jax.devices()[:1])
    sched = dtpp.ScheduleConfig(name="1F1B", n_microbatches=2)
    opt = train.adamw(total_steps=10)
    params = train.init_params(cfg, mesh, jax.random.key(0))
    before = jax.tree.map(jnp.copy, params)
    opt_state = train.init_opt_state(opt, params, mesh)
    step = train.make_train_step(cfg, mesh, sched, opt)
    x, y = batch(24)
    jaxpr = jax.make_jaxpr(step)(params, opt_state, x, y)
    step = step.lower(params, opt_state, x, y).compile()  # once, and kept
    params, opt_state, loss = step(params, opt_state, x, y)
    after, _, _ = step(params, opt_state, x, y)  # the first has lr 0
    return cfg, before, after, float(loss), step, (x, y), jaxpr


def test_train_step_on_the_normal_path(trained, compiled):
    cfg, before, after, loss, _, (x, y), _ = trained
    want = float(compiled.ref_loss(before, x, y))
    assert abs(loss - want) / want < 2e-3
    moved = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                         before, after)
    assert moved["layers"]["mamba"]["A_log"] > 0
    assert moved["layers"]["moe"]["experts"]["w1"] > 0
    assert moved["layers"]["moe"]["router"]["bias"] == 0  # the buffer stays
    # fp32 masters; the decay parameters and the router compute in float32
    cast = tfm.compute_cast(cfg, before)
    kept = {"A_log", "dt_bias", "D"}
    for path, leaf in jax.tree_util.tree_flatten_with_path(cast)[0]:
        names = {getattr(k, "key", None) for k in path}
        want_dtype = (jnp.float32 if names & (kept | {"router"})
                      else jnp.bfloat16)
        assert leaf.dtype == want_dtype, jax.tree_util.keystr(path)


def test_fit_and_eval_on_the_normal_path():
    """``fit`` trains it and calls ``on_log`` at its log points (where
    ``scripts/train.py`` says the routing), and the forward-only eval
    program gives the training loss."""
    cfg = nemotron_h.nemotron_h_config("debug", vocab_size=128)
    mesh = make_mesh(n_pipe=1, devices=jax.devices()[:1])
    sched = dtpp.ScheduleConfig(name="1F1B", n_microbatches=2)
    params = train.init_params(cfg, mesh, jax.random.key(0))
    x, y = batch(32)
    want = float(jax.jit(lambda p: tfm.transformer_loss(cfg, p, x, y))(params))
    assert abs(float(train.make_eval_fn(cfg, mesh, sched)(params, x, y))
               - want) < 1e-5
    said = []
    _, history = train.fit(
        cfg, mesh, sched, params, train.synthetic_data(cfg, 2, 32, seed=3),
        num_steps=2, verbose=False, log_every=1,
        on_log=lambda i, p, toks: said.append((i, nemotron_h.describe_routing(
            nemotron_h.routing_stats(cfg, p, toks)))))
    assert len(history) == 2 and all(np.isfinite(v) for _, v in history)
    assert [i for i, _ in said] == [0, 1]
    assert all(line.startswith("E0: max/mean") for _, line in said)


def test_remat_layers_changes_no_number(compiled):
    x, y = batch(24)
    params = compiled.params
    remat = dataclasses.replace(compiled.cfg, remat_layers=True)
    _, a = compiled.grads(params, x, y)
    b = jax.jit(jax.grad(
        lambda p: tfm.transformer_loss(remat, p, x, y)))(params)
    for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(u, v, rtol=1e-5, atol=1e-7)


def test_compiled_step_names_the_four_regions(trained, pallas_kernels):
    """Every region of the stack is named in the compiled step, and the
    scan's kernel pair runs inside ``model/ssm_scan`` (the region the
    benchmark's ``model.ssm_scan_share_pct`` reads): the forward kernel in
    the forward and in ``remat_layers``' recompute, the backward kernel in
    the backward; in the compiled step the interpreted kernels' grid loops,
    the region's only loops, fall in the same three phases."""
    names = re.findall(r'op_name="([^"]*)"', trained[4].as_text())
    read = {classify(n) for n in names}
    # the latent-attention region is tests/test_latent_attention.py's, the
    # short convolution's tests/test_lfm2_moe.py's, Gated DeltaNet's
    # tests/test_qwen3_next.py's
    for region in HYBRID_REGIONS + ("model/attn", "model/head_loss"):
        if region not in ("model/mla_latent", "model/shortconv", "model/gdn",
                          "model/gdn_scan"):
            assert any(r == region for _, r in read), region
    assert ("backward", "model/ssm_scan") in read
    assert ("recompute", "model/moe") in read  # remat_layers: a second run
    assert sorted(set(pallas_kernels(trained[6].jaxpr))) == [
        ("_ssd_bwd_kernel", "backward", "model/ssm_scan"),
        ("_ssd_fwd_kernel", "forward", "model/ssm_scan"),
        ("_ssd_fwd_kernel", "recompute", "model/ssm_scan")]
    loops = {classify(name) for name in re.findall(
        r'^\s*(?:ROOT )?%?while[\w.\-]* = [^\n]*op_name="([^"]*)"',
        trained[4].as_text(), re.M)}
    assert {(p, r) for p, r in loops if r == "model/ssm_scan"} == {
        ("forward", "model/ssm_scan"), ("recompute", "model/ssm_scan"),
        ("backward", "model/ssm_scan")}


@pytest.mark.parametrize("op_name,expected", [
    ("jit(train_step)/jvp(model/layers)/checkpoint/model/ssm/model/ssm_scan/"
     "dot_general", ("forward", "model/ssm_scan")),
    ("jit(train_step)/transpose(jvp(model/layers))/checkpoint/"
     "rematted_computation/model/ssm/dot_general", ("recompute", "model/ssm")),
    ("jit(train_step)/transpose(jvp(model/layers))/checkpoint/model/moe/"
     "model/moe_experts/dot_general", ("backward", "model/moe_experts")),
    ("jit(train_step)/jvp(model/layers)/checkpoint/model/moe/sort",
     ("forward", "model/moe")),
    ("jit(train_step)/transpose(jvp(model/layers))/checkpoint/"
     "rematted_computation/model/shortconv/mul", ("recompute",
                                                  "model/shortconv")),
])
def test_classify_reads_the_hybrid_regions(op_name, expected):
    assert classify(op_name) == expected


@pytest.mark.parametrize("arch,keys", [
    ("gpt2", {"ln1", "attn", "ln2", "lin1", "lin2"}),
    ("llama", {"rms1", "attn", "rms2", "w1", "w2", "w3"}),
    ("ref_decoder", {"self_attn", "cross_attn", "ln1", "ln2", "ln3", "lin1",
                     "lin2"}),
])
def test_a_stack_of_one_kind_keeps_its_scan_and_its_tree(arch, keys):
    cfg = dtpp.ModelConfig(arch=arch, dim=32, n_layers=3, n_heads=4,
                           vocab_size=64, ffn_dim=64, max_seq_len=16)
    params = tfm.transformer_init(jax.random.key(0), cfg)
    assert set(params["layers"]) == keys
    assert all(leaf.shape[0] == 3 for leaf in jax.tree.leaves(params["layers"]))
    tokens = jnp.zeros((2, 8), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p: tfm.transformer_loss(cfg, p, tokens, tokens))(params)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1 and scans[0].params["length"] == 3
