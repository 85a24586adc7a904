"""Latent attention (MLA) over a dense-then-experts stack — the kinds of layer
the ``joyai-llm-flash`` configuration brought to the patterned stack — at a
small size on the CPU: the program against the plain reference the benchmark
keeps (``benchmark/reference/joyai_llm_flash.py``, the same file the chip run
is held to), the flash kernels at two head widths against dense attention,
equal-width calls lowering to the text they lowered to before, the rotary
turn pair by pair, the 32 shares of an expert layer adding up to the whole in
the gated form, the new region, AdamW's decay mask, the named errors."""

import dataclasses
import hashlib
import importlib.util
import logging
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distributed_training_with_pipeline_parallelism_tpu as dtpp
from distributed_training_with_pipeline_parallelism_tpu.models import (
    nemotron_h, transformer as tfm)
from distributed_training_with_pipeline_parallelism_tpu.ops import (
    attention, experts)
from distributed_training_with_pipeline_parallelism_tpu.ops.pallas_attention import (
    _dense_attention, flash_attention)
from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import (
    make_mesh)
from distributed_training_with_pipeline_parallelism_tpu.utils import train
from distributed_training_with_pipeline_parallelism_tpu.utils.profiling import (
    HYBRID_REGIONS, REGIONS, classify)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the debug size (``nemotron_h_config("joyai-debug")``) under the source's keys
SIZES = dict(
    hidden_size=64, num_attention_heads=4, vocab_size=256,
    hybrid_override_pattern="L-LELE", max_position_embeddings=4096,
    rms_norm_eps=1e-6, rope_theta=32000000, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=96, hidden_act="silu", router_width=16,
    experts_held=[0, 1, 2, 3], num_experts_per_tok=3, n_shared_experts=1,
    moe_intermediate_size=32, routed_scaling_factor=2.5)


@pytest.fixture(scope="module")
def ref():
    """The benchmark's plain reference, by path as its runner loads it."""
    path = os.path.join(ROOT, "benchmark", "reference", "joyai_llm_flash.py")
    spec = importlib.util.spec_from_file_location("reference_joyai", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def batch(seq, rows=2, seed=1):
    toks = np.random.default_rng(seed).integers(
        0, SIZES["vocab_size"], (rows, seq + 1), dtype=np.int32)
    return toks[:, :-1], toks[:, 1:]


@pytest.fixture(scope="module")
def reference_grads(ref):
    """The reference's loss and gradients on one batch with one set of
    float32 weights, compiled once for the module."""
    params = jax.jit(lambda k: tfm.transformer_init(  # one program, not one an op
        k, ref.model_config(SIZES, {})))(jax.random.key(0))
    x, y = batch(32)
    want, g_want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, x, y, SIZES)))(params)
    return params, x, y, float(want), g_want


def test_debug_size_is_the_adapters(ref):
    assert ref.model_config(SIZES, {}) == nemotron_h.nemotron_h_config(
        "joyai-debug")
    stage = nemotron_h.nemotron_h_config("joyai-stage")
    assert (stage.dim, stage.n_layers, stage.hybrid_override_pattern) == (
        2048, 16, "L-" + "LE" * 7)


# bf16 over fp32 masters at this size (64 tokens, so little averages out):
# measured 3.4e-4 on the loss and at most 3.5e-2 relative L2 on a gradient
# leaf; float32 agrees to rounding (1.5e-6 on the worst leaf). With the
# kernels (interpret mode: blocks of 16 at two widths, 24 and 16) float32
# agrees as closely.
@pytest.mark.parametrize("numerics,loss_tol,grad_tol", [
    ({}, 1e-6, 1e-5),
    (dict(use_flash_attention=True), 1e-6, 1e-5),
    (dict(dtype="bfloat16", param_dtype="float32"), 2e-3, 0.15),
], ids=["float32", "float32-flash", "bf16-over-fp32"])
def test_program_equals_reference_loss_and_every_gradient(
        ref, reference_grads, numerics, loss_tol, grad_tol):
    cfg = ref.model_config(SIZES, numerics)
    params, x, y, want, g_want = reference_grads  # fp32 masters in every case
    got, g_got = jax.jit(jax.value_and_grad(
        lambda p: tfm.transformer_loss(cfg, p, x, y)))(params)
    assert abs(float(got) - want) / want < loss_tol
    apart = jax.tree.map(
        lambda a, b: float(jnp.linalg.norm(a.astype(jnp.float32) - b)
                           / (jnp.linalg.norm(b) + 1e-30)), g_got, g_want)
    for path, rel in jax.tree_util.tree_flatten_with_path(apart)[0]:
        assert rel < grad_tol, (jax.tree_util.keystr(path), rel)
    # the router's bias is a buffer: no gradient reaches it
    assert not np.any(np.asarray(g_got["layers"]["moe"]["router"]["bias"]))
    assert set(g_got["layers"]) == {"mla", "mlp", "moe"}


def test_mla_mixer_equals_reference(ref):
    """One latent-attention sublayer alone, forward and every gradient leaf,
    dense and through the kernels."""
    cfg = ref.model_config(SIZES, {})
    p = jax.jit(lambda k: nemotron_h.mixer_init(k, cfg, "mla"))(
        jax.random.key(4))
    x = jax.random.normal(jax.random.key(5), (2, 32, cfg.dim))
    want, g_want = jax.jit(jax.value_and_grad(
        lambda p: jnp.sum(jnp.sin(ref.mixer("L", p, x, SIZES)))))(p)
    for flash in (False, True):
        c = dataclasses.replace(cfg, use_flash_attention=flash)
        got, g_got = jax.jit(jax.value_and_grad(lambda p: jnp.sum(jnp.sin(
            nemotron_h.mixer(c, "mla", p, x)[0]))))(p)
        np.testing.assert_allclose(got, want, rtol=1e-5)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            a, b, rtol=2e-4, atol=2e-5), g_got, g_want)


# the smallest shapes that still have two widths and, in one block, two
# strips (256 rows: a forward strip of 128) or, in blocks, two of them
@pytest.mark.parametrize("seq,blocks", [(256, None), (128, 64), (100, 32)],
                         ids=["one-block-strips", "multi-block", "ragged"])
def test_flash_two_widths_equals_dense(seq, blocks):
    """q, k 24 wide and v 16: out, dq, dk, dv against dense attention."""
    b, h, dqk, dv = 1, 2, 24, 16
    ks = jax.random.split(jax.random.key(seq), 4)
    q, k = (jax.random.normal(key, (b, seq, h, dqk)) for key in ks[:2])
    v, g = (jax.random.normal(key, (b, seq, h, dv)) for key in ks[2:])
    kw = dict(block_q=blocks, block_k=blocks) if blocks else {}

    def flat(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, seq, -1)

    def dense(q, k, v):
        out = _dense_attention(flat(q), flat(k), flat(v), True)
        return out.reshape(b, h, seq, dv).transpose(0, 2, 1, 3)

    def with_grads(fn):  # each side one program, not one an op
        def both(q, k, v, g):
            out, vjp = jax.vjp(fn, q, k, v)
            return (out,) + vjp(g)
        return jax.jit(both)(q, k, v, g)

    got = with_grads(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                     **kw))
    want = with_grads(dense)
    assert got[0].shape == (b, seq, h, dv)
    for name, a, w in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.shape == w.shape, name
        np.testing.assert_allclose(a, w, rtol=2e-5, atol=2e-5, err_msg=name)


def test_flash_logs_the_two_widths(caplog):
    q = jax.ShapeDtypeStruct((2, 512, 3, 24), jnp.float32)
    v = jax.ShapeDtypeStruct((2, 512, 3, 16), jnp.float32)
    with caplog.at_level("INFO"):
        jax.eval_shape(lambda q, v: flash_attention(q, q, v, causal=True),
                       q, v)
    assert "classic kernels, 2 x 512 x 3 x 24 (values 16), blocks" in caplog.text


# sha256 of the lowered text (interpret mode on the CPU: the kernels' own
# operations as HLO) of flash forward + vjp at EQUAL widths, read at the
# parent of PR 34 (commit 3ff3fec) in this installation, by this test's own
# function run in that tree under this suite's conftest (the text holds the
# jitted function's name and the device count): a call whose widths are equal
# compiles the program it compiled before the kernels learnt two. The number
# the lowering puts on a private function's name is taken off first
# (``@_where_48``): naming the forward rules' residuals (PR 35,
# ``checkpoint_name``: it lowers to nothing) moved that number by one and
# nothing else, and these are that commit's texts read so.
EQUAL_WIDTH_TEXTS = {
    "packed-strips": (
        (1, 256, 2, 64), "bfloat16", {},
        "09121f6b48183e2e63ca4b2441d82330344a374f90f3c72ae4b6d4fdf1be2323"),
    "packed-multi-block": (
        (1, 256, 2, 64), "bfloat16", dict(block_q=128, block_k=128),
        "4943f5319127116922e30ac082e80368d8d7a08ce2101e53b9eba8b06528be5b"),
    "classic-strips": (
        (1, 256, 3, 64), "bfloat16", {},
        "1a150e9122ea7ba0f3590c3744d198f0538cbb37aa2df39dd36ca6d66f059c16"),
    "classic-multi-block": (
        (1, 256, 3, 32), "float32", dict(block_q=64, block_k=64),
        "4610dfc4c9d3042d3fe000a48c53b9ba8f8caa56a991625f19f098d906d14e70"),
    "classic-ragged": (
        (1, 200, 3, 64), "float32", dict(block_q=64, block_k=64),
        "2c8ce1f12b58687acb5addbbf28a7f0cd7322d463e61945088f3fd1cdd4f8c04"),
    "classic-window": (
        (1, 256, 2, 128), "bfloat16", dict(block_q=64, block_k=64, window=96),
        "d3d9e03d41facb9b4bc181b2e27823483a1547e6fb63e852576d7490a0c072e5"),
    "classic-noncausal": (
        (1, 128, 3, 64), "float32", dict(block_q=64, block_k=64, causal=False),
        "140495ae8fe34bc31c064289c9f551f951430539fcb8d373422ff821e2a4f88d"),
}


@pytest.mark.parametrize("case", list(EQUAL_WIDTH_TEXTS))
def test_equal_width_calls_lower_to_the_parents_text(case):
    shape, dtype, kw, parents = EQUAL_WIDTH_TEXTS[case]
    kw = dict(kw)
    causal = kw.pop("causal", True)
    x = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))

    def both(q, k, v, g):
        out, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, **kw), q, k, v)
        return (out,) + vjp(g)

    assert _text_sha256(both, x) == parents


def _text_sha256(both, x):
    text = jax.jit(both).lower(x, x, x, x).as_text()
    text = re.sub(r"@(\w+?)_\d+\b", r"@\1", text)
    return hashlib.sha256(text.encode()).hexdigest()


def test_interleaved_rope_turns_pairs_in_place():
    """The source's convention, written out pair by pair in numpy: (x[2i],
    x[2i+1]) of position t turns by t * theta^(-2i/d)."""
    rng = np.random.default_rng(0)
    b, s, h, d, theta = 2, 12, 3, 8, 32e6
    x = rng.normal(size=(b, s, h, d)).astype(np.float32)
    want = np.zeros_like(x)
    for t in range(s):
        for i in range(d // 2):
            a = t * theta ** (-2.0 * i / d)
            x0, x1 = x[:, t, :, 2 * i], x[:, t, :, 2 * i + 1]
            want[:, t, :, 2 * i] = x0 * np.cos(a) - x1 * np.sin(a)
            want[:, t, :, 2 * i + 1] = x0 * np.sin(a) + x1 * np.cos(a)
    angles = attention.rope_frequencies(d, s, theta)
    got = attention.apply_rope_interleaved(jnp.asarray(x), angles)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # not the half-split convention of apply_rope
    assert not np.allclose(attention.apply_rope(jnp.asarray(x), angles), want,
                           atol=1e-3)
    # bf16 activations stay bf16
    assert attention.apply_rope_interleaved(
        jnp.asarray(x, jnp.bfloat16), angles).dtype == jnp.bfloat16


def test_the_32_shares_add_up_to_the_uncut_gated_layer(ref):
    """256 experts over 32 ranks of 8, gated form: the routed parts of all
    shares plus the shared expert counted once are the whole layer as the
    reference computes it with every expert held."""
    whole = dict(SIZES, router_width=256, num_experts_per_tok=8,
                 experts_held=list(range(256)))
    cfg = ref.model_config(whole, {})
    p = nemotron_h.mixer_init(jax.random.key(2), cfg, "moe")
    assert set(p["experts"]) == {"w1", "w2", "w3"}
    x = jax.random.normal(jax.random.key(3), (24, cfg.dim))
    apply = jax.jit(experts.experts_apply, static_argnums=(3, 4))
    with jax.default_matmul_precision("highest"):
        want = ref._experts(p, x, whole)
        shared = ref._mlp(p["shared"], x)
        total, seen = shared, 0
        for rank in range(32):
            mine = dict(p, experts=jax.tree.map(
                lambda w: w[8 * rank:8 * rank + 8], p["experts"]))
            # ONE compiled program: the held ids are data (an array), not
            # a static argument that compiles once a rank
            out, counts = apply(mine, x, jnp.arange(8 * rank, 8 * rank + 8),
                                cfg.num_experts_per_tok,
                                cfg.routed_scaling_factor)
            seen += int(counts.sum())
            total = total + (out - shared)
    assert seen == 24 * cfg.num_experts_per_tok  # every assignment, once
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)


def test_build_says_the_new_kinds(caplog):
    cfg = nemotron_h.nemotron_h_config("joyai-debug")
    with caplog.at_level(logging.INFO):  # said while tracing: no run needed
        jax.eval_shape(lambda: tfm.transformer_init(jax.random.key(0), cfg))
    assert ("pattern L-LELE (3 mla, 1 mlp, 2 moe); experts held [0, 1, 2, 3] "
            "of 16; MLPs and experts silu") in caplog.text


@pytest.mark.parametrize("over,error,match", [
    (dict(hybrid_override_pattern="L-LEXE"), ValueError, "unknown layer kind"),
    (dict(mlp_hidden_act="gelu"), ValueError, "mlp_hidden_act"),
    (dict(qk_rope_head_dim=7), ValueError, "pairs"),
    (dict(tie_embeddings=True), NotImplementedError, "tie_embeddings"),
    (dict(dropout=0.1), NotImplementedError, "dropout"),
])
def test_configuration_errors_are_named(over, error, match):
    with pytest.raises(error, match=match):
        nemotron_h.nemotron_h_config("joyai-debug", **over)


@pytest.mark.parametrize("axes,match", [
    (dict(n_pipe=2), "pipeline stages"),
    (dict(n_pipe=1, n_model=2), "tensor-parallel Mamba-2, short-convolution, latent-attention"),
    (dict(n_pipe=1, n_seq=2), "sequence-parallel"),
    (dict(n_pipe=1, n_expert=2), "expert-parallel exchange"),
])
def test_meshes_it_does_not_run_are_named_errors(axes, match):
    cfg = nemotron_h.nemotron_h_config("joyai-debug")
    mesh = make_mesh(**axes)
    with pytest.raises(NotImplementedError, match=match):
        nemotron_h.check_mesh(cfg, mesh)


def test_generation_and_serving_are_named_errors():
    cfg = nemotron_h.nemotron_h_config("joyai-debug")
    with pytest.raises(NotImplementedError, match="compressed key-value latent"):
        nemotron_h.not_served("generate", cfg)


@pytest.fixture(scope="module")
def trained():
    """Two AdamW steps through the normal path, bf16 over fp32."""
    cfg = nemotron_h.nemotron_h_config(
        "joyai-debug", dtype="bfloat16", param_dtype="float32",
        remat_layers=True)
    mesh = make_mesh(n_pipe=1, devices=jax.devices()[:1])
    sched = dtpp.ScheduleConfig(name="1F1B", n_microbatches=2)
    opt = train.adamw(total_steps=10)
    params = train.init_params(cfg, mesh, jax.random.key(0))
    opt_state = train.init_opt_state(opt, params, mesh)
    step = train.make_train_step(cfg, mesh, sched, opt)
    x, y = batch(32, rows=4)
    lowered = step.lower(params, opt_state, x, y)
    before = jax.tree.map(jnp.copy, params)
    params, opt_state, loss = step(params, opt_state, x, y)
    after, _, _ = step(params, opt_state, x, y)  # the first has lr 0
    return cfg, before, after, float(loss), lowered.compile().as_text()


def test_train_step_on_the_normal_path(trained):
    cfg, before, after, loss, _ = trained
    assert abs(loss - np.log(cfg.vocab_size)) < 0.5
    moved = jax.tree.map(lambda a, b: bool(jnp.any(a != b)), before, after)
    bias = moved["layers"]["moe"]["router"].pop("bias")
    assert not bias                       # a buffer: the optimizer leaves it
    assert all(jax.tree.leaves(moved))    # every other leaf was updated


def test_compiled_step_names_the_regions(trained):
    names = re.findall(r'op_name="([^"]*)"', trained[-1])
    read = {classify(n) for n in names}
    for region in ("model/attn", "model/mla_latent", "model/mlp", "model/moe",
                   "model/moe_experts", "model/head_loss", "train/optimizer"):
        assert any(r == region for _, r in read), region
    assert ("backward", "model/mla_latent") in read
    assert ("recompute", "model/mla_latent") in read  # remat_layers
    assert not any(r.startswith("model/ssm") for _, r in read)


@pytest.mark.parametrize("op_name,expected", [
    ("jit(train_step)/jvp(model/layers)/model/attn/model/mla_latent/dot_general",
     ("forward", "model/mla_latent")),
    ("jit(train_step)/transpose(jvp(model/layers))/model/attn/model/mla_latent/"
     "mul", ("backward", "model/mla_latent")),
    ("jit(train_step)/transpose(jvp(model/layers))/rematted_computation/"
     "model/attn/model/mla_latent/dot_general",
     ("recompute", "model/mla_latent")),
    ("jit(train_step)/jvp(model/layers)/model/attn/pallas_call",
     ("forward", "model/attn")),
])
def test_classify_reads_the_latent_region(op_name, expected):
    assert classify(op_name) == expected
    assert "model/mla_latent" in HYBRID_REGIONS
    assert "model/mla_latent" not in REGIONS  # a GPT-2 step names all of those


def test_adamw_decays_the_new_matrices_and_nothing_else():
    cfg = nemotron_h.nemotron_h_config("joyai-debug")
    params = jax.eval_shape(lambda: tfm.transformer_init(jax.random.key(0),
                                                         cfg))
    # the mask optax is given, rebuilt by the rule train.adamw documents
    decayed = {jax.tree_util.keystr(path) for path, _ in
               jax.tree_util.tree_flatten_with_path(params)[0]
               if getattr(path[-1], "key", None) in ("w", "w1", "w2", "w3")}
    mla = "['layers']['mla']['attn']"
    assert {f"{mla}['{m}']['w']" for m in ("q_a", "q_b", "kv_a", "kv_b",
                                           "o")} <= decayed
    assert {f"['layers']['moe']['experts']['{m}']" for m in ("w1", "w2",
                                                             "w3")} <= decayed
    assert {f"['layers']['mlp']['{m}']['w']" for m in ("gate", "up",
                                                       "down")} <= decayed
    assert not any("norm" in k or "bias" in k or "tok" in k for k in decayed)
    # and the optimizer itself: a zero gradient moves exactly the decayed
    opt = train.adamw(total_steps=10, warmup_steps=0, weight_decay=0.5)
    real = jax.jit(lambda k: tfm.transformer_init(k, cfg))(jax.random.key(0))
    zero = jax.tree.map(jnp.zeros_like, real)
    updates, _ = jax.jit(lambda g, p: opt.update(g, opt.init(p), p))(zero,
                                                                     real)
    moved = {jax.tree_util.keystr(path) for path, u in
             jax.tree_util.tree_flatten_with_path(updates)[0]
             if bool(jnp.any(u != 0))}
    assert moved == decayed
