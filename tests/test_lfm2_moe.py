"""The ``lfm2_moe`` family on the patterned stack — gated short convolutions
and grouped-query attention with per-head q/k norms and RoPE, over a dense
gated MLP and then gated experts WITHOUT a shared expert, as one
expert-parallel rank holds it — at a small size on the CPU: the program
against the plain reference the benchmark keeps
(``benchmark/reference/lfm2_moe.py``, the same file the chip run is held to)
on the loss and every gradient leaf, each mixer alone, the router with a
nonzero ``expert_bias``, the q/k norms before the rotation, the four shares
of an expert layer adding up to the whole, and the normal training path with
its regions. Every function is jitted once for the module."""

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
    experts, mamba2, shortconv)
from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import (
    make_mesh)
from distributed_training_with_pipeline_parallelism_tpu.utils import train
from distributed_training_with_pipeline_parallelism_tpu.utils.profiling import (
    classify)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# both mixers, the dense MLP and two expert sublayers; 8 experts, 4 held
SIZES = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    vocab_size=128, hybrid_override_pattern="C-*ECE",
    max_position_embeddings=4096, norm_eps=1e-5, rope_theta=1000000,
    conv_L_cache=3, conv_bias=False, intermediate_size=96, router_width=8,
    experts_held=[0, 1, 2, 3], num_experts_per_tok=2,
    moe_intermediate_size=32, routed_scaling_factor=1, norm_topk_prob=True,
    use_expert_bias=True)
SEQ = 24


@pytest.fixture(scope="module")
def ref():
    """The benchmark's plain reference, by path as its runner loads it."""
    path = os.path.join(ROOT, "benchmark", "reference", "lfm2_moe.py")
    spec = importlib.util.spec_from_file_location("reference_lfm2_moe", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def seeded(params, key=7):
    """``params`` with what an initialiser leaves trivial made to matter: a
    NONZERO ``expert_bias`` (with the bias at zero, choosing by ``s + bias``
    and weighting by ``s`` coincide with choosing by ``s``) and q/k norm
    scales that are not all one (a norm whose scale is one number commutes
    with the rotation, so the order would not show)."""
    kb, kq, kk = jax.random.split(jax.random.key(key), 3)
    layers = dict(params["layers"])
    moe, attn = dict(layers["moe"]), dict(layers["attn"])
    bias = moe["router"]["bias"]
    moe["router"] = dict(moe["router"], bias=0.3 * jax.random.normal(
        kb, bias.shape))
    inner = dict(attn["attn"])
    for name, k in (("q_layernorm", kq), ("k_layernorm", kk)):
        scale = inner[name]["scale"]
        inner[name] = {"scale": jax.random.uniform(k, scale.shape, minval=0.5,
                                                   maxval=1.5)}
    attn["attn"] = inner
    layers.update(moe=moe, attn=attn)
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
    assert set(layers) == {"shortconv", "attn", "mlp", "moe"}
    assert set(layers["shortconv"]) == {"norm", "in_proj", "conv", "out_proj"}
    assert layers["shortconv"]["in_proj"]["w"].shape == (2, 64, 192)
    assert layers["shortconv"]["conv"]["w"].shape == (2, 3, 64)  # no bias
    assert set(layers["attn"]["attn"]) == {"q", "k", "v", "o", "q_layernorm",
                                           "k_layernorm"}
    assert layers["attn"]["attn"]["k"]["w"].shape == (1, 64, 32)  # 2 kv heads
    assert layers["attn"]["attn"]["q_layernorm"]["scale"].shape == (1, 16)
    assert set(layers["moe"]) == {"norm", "router", "experts"}  # no shared
    assert layers["moe"]["router"]["w"].shape == (2, 64, 8)
    assert layers["moe"]["experts"]["w1"].shape == (2, 4, 64, 32)


def test_program_equals_reference_loss_and_every_gradient(compiled):
    """Float32, where the two agree to rounding; bf16 over fp32 masters is
    ``test_train_step_on_the_normal_path``'s, through ``make_train_step``."""
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
        assert rel < 2e-5, (jax.tree_util.keystr(path), rel)
    # expert_bias is a buffer: no gradient reaches it
    assert not np.any(np.asarray(g_got["layers"]["moe"]["router"]["bias"]))


def sublayer(ref, params, letter):
    return next(p for l, p in ref.layers_of(params, SIZES) if l == letter)


@pytest.mark.parametrize("letter", list("C*-E"))
def test_each_mixer_alone_equals_the_reference(ref, compiled, letter):
    p = sublayer(ref, compiled.params, letter)
    x = jax.random.normal(jax.random.key(5), (2, SEQ, 64))
    with jax.default_matmul_precision("highest"):
        got, counts = jax.jit(lambda p, x: nemotron_h.mixer(
            compiled.cfg, ref.STACK[letter], p, x))(p, x)
    want = jax.jit(lambda p, x: ref.mixer(letter, p, x, SIZES))(p, x)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    assert (counts is not None) == (letter == "E")


def test_router_chooses_by_score_plus_bias_and_weights_by_score(ref, compiled):
    p = sublayer(ref, compiled.params, "E")
    x = jax.random.normal(jax.random.key(6), (96, 64))
    ids, weights = experts.route(p["router"], x, 2, 1.0, 1e-6)
    chosen, want = ref._route(p, x, SIZES)
    got = jnp.zeros_like(want).at[jnp.arange(96)[:, None], ids].set(weights)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    # the bias moved choices (else the test shows nothing) ...
    unbiased, _ = experts.route(dict(p["router"], bias=jnp.zeros(8)), x, 2,
                                1.0, 1e-6)
    moved = np.sort(ids, -1) != np.sort(unbiased, -1)
    assert moved.any(-1).mean() > 0.1
    # ... and is not in the weights: they are sigmoid scores over their sum
    score = jax.nn.sigmoid(x @ p["router"]["w"])
    picked = jnp.take_along_axis(score, ids, -1)
    np.testing.assert_allclose(
        weights, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-5)
    # the epsilon is the configuration's: 1e-20 is another number
    _, other = experts.route(p["router"], x, 2, 1.0)
    assert float(jnp.abs(other - weights).max()) > 1e-7


def test_qk_norm_comes_before_the_rotation(ref, compiled):
    """With scales that differ by column the two orders differ; the program's
    is the source's (norm, then rotate)."""
    p = sublayer(ref, compiled.params, "*")
    x = jax.random.normal(jax.random.key(8), (2, SEQ, 64))
    got, _ = jax.jit(lambda p, x: nemotron_h.mixer(compiled.cfg, "attn", p,
                                                   x))(p, x)

    def orders(a, x):
        def attention(first, then):
            q = (x @ a["q"]["w"]).reshape(2, SEQ, 2, 2, 16)
            k = (x @ a["k"]["w"]).reshape(2, SEQ, 2, 16)
            q, k = (then(n, first(n, m)) for n, m in (("q_layernorm", q),
                                                      ("k_layernorm", k)))
            scores = jnp.einsum("bqjgd,bkjd->bjgqk", q, k) / 4.0
            scores = jnp.where(jnp.tril(jnp.ones((SEQ, SEQ), bool)), scores,
                               -jnp.inf)
            v = (x @ a["v"]["w"]).reshape(2, SEQ, 2, 16)
            return jnp.einsum("bjgqk,bkjd->bqjgd", jax.nn.softmax(scores, -1),
                              v).reshape(2, SEQ, 64) @ a["o"]["w"]

        norm = lambda n, m: ref._rms_norm(a[n]["scale"], m, 1e-5)  # noqa: E731
        turn = lambda n, m: ref.rope_halves(m, 1e6)  # noqa: E731
        return attention(norm, turn), attention(turn, norm)

    source, swapped = jax.jit(orders)(p["attn"], x)
    np.testing.assert_allclose(got, source, rtol=2e-5, atol=2e-6)
    assert float(jnp.linalg.norm(got - swapped)
                 / jnp.linalg.norm(got)) > 1e-2


def test_the_four_shares_add_up_to_the_uncut_layer(ref, compiled):
    """8 experts over 4 ranks of 2: the parts all shares give are the whole
    layer as the reference computes it with every expert held. There is no
    shared expert, so nothing is counted once."""
    whole = dict(SIZES, experts_held=list(range(8)))
    cfg = ref.model_config(whole, {})
    p = nemotron_h.mixer_init(jax.random.key(2), cfg, "moe")
    p["router"]["bias"] = 0.3 * jax.random.normal(jax.random.key(4), (8,))
    assert "shared" not in p
    x = jax.random.normal(jax.random.key(3), (40, cfg.dim))
    # one compiled program: the held ids are data (an array)
    apply = jax.jit(experts.experts_apply, static_argnums=(3, 4, 5))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: ref._experts(p, x, whole))(p, x)
        total, seen = jnp.zeros_like(want), 0
        for rank in range(4):
            mine = dict(p, experts=jax.tree.map(
                lambda w: w[2 * rank:2 * rank + 2], p["experts"]))
            out, counts = apply(
                mine, x, jnp.arange(2 * rank, 2 * rank + 2),
                cfg.num_experts_per_tok, cfg.routed_scaling_factor,
                cfg.router_norm_eps)
            seen += int(counts.sum())
            total = total + out
    assert seen == 40 * cfg.num_experts_per_tok  # every assignment, once
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-6)


def test_one_convolution_two_callers():
    """``causal_conv1d`` without a bias is the same sum; the short
    convolution with ``conv_bias`` carries one on all three modules, as the
    source's does."""
    x = jax.random.normal(jax.random.key(0), (2, 9, 6))
    w = jax.random.normal(jax.random.key(1), (3, 6))
    np.testing.assert_array_equal(mamba2.causal_conv1d(x, w),
                                  mamba2.causal_conv1d(x, w, jnp.zeros(6)))
    literal = sum(w[i] * jnp.pad(x, ((0, 0), (2 - i, 0), (0, 0)))[:, :9]
                  for i in range(3))
    np.testing.assert_allclose(mamba2.causal_conv1d(x, w), literal, rtol=1e-6)
    p = shortconv.shortconv_init(jax.random.key(2), 6, 3, bias=True)
    assert all("b" in p[m] for m in ("in_proj", "conv", "out_proj"))
    assert shortconv.shortconv_apply(p, x).shape == x.shape


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
    step = train.make_train_step(cfg, mesh, sched, opt).lower(
        params, opt_state, x, y).compile()
    params, opt_state, loss = step(params, opt_state, x, y)
    after, _, _ = step(params, opt_state, x, y)  # the first has lr 0
    return cfg, before, after, float(loss), step, (x, y)


def test_train_step_on_the_normal_path(trained, compiled):
    cfg, before, after, loss, _, (x, y) = trained
    want, _ = compiled.ref_grads(jax.device_get(before), x, y)
    assert abs(loss - float(want)) / float(want) < 2e-3
    moved = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                         before, after)
    assert moved["layers"]["shortconv"]["conv"]["w"] > 0
    assert moved["layers"]["attn"]["attn"]["q_layernorm"]["scale"] > 0
    assert moved["layers"]["moe"]["experts"]["w3"] > 0
    assert moved["layers"]["moe"]["router"]["bias"] == 0  # the buffer stays
    cast = tfm.compute_cast(cfg, before)
    assert cast["layers"]["moe"]["router"]["w"].dtype == jnp.float32
    assert cast["layers"]["shortconv"]["conv"]["w"].dtype == jnp.bfloat16


def test_compiled_step_names_the_regions(trained):
    names = re.findall(r'op_name="([^"]*)"', trained[4].as_text())
    read = {classify(n) for n in names}
    for region in ("model/shortconv", "model/attn", "model/mlp", "model/moe",
                   "model/moe_experts", "model/head_loss"):
        assert any(r == region for _, r in read), region
    assert ("backward", "model/shortconv") in read
    assert ("recompute", "model/shortconv") in read  # remat_layers
    assert not any(r in ("model/ssm", "model/mla_latent") for _, r in read)


def test_new_knobs_are_checked_and_named():
    base = dict(arch="nemotron_h", dim=64, n_layers=2, n_heads=4,
                vocab_size=128, hybrid_override_pattern="CE",
                n_routed_experts=8, num_experts_per_tok=2)
    assert dtpp.ModelConfig(**base).router_norm_eps == 1e-20  # the siblings'
    with pytest.raises(ValueError, match="conv_L_cache"):
        dtpp.ModelConfig(**base, conv_L_cache=0)
    with pytest.raises(ValueError, match="no shared expert"):
        dtpp.ModelConfig(**base, moe_shared_expert_intermediate_size=-1)
    with pytest.raises(ValueError, match="attn_rope"):
        dtpp.ModelConfig(**dict(base, dim=60, n_heads=4), attn_rope=True,
                         head_dim_override=15)
    cfg = nemotron_h.nemotron_h_config("lfm2-debug")
    assert (cfg.qk_layernorm, cfg.attn_rope, cfg.router_norm_eps,
            cfg.moe_shared_expert_intermediate_size) == (True, True, 1e-6, 0)
    stage = nemotron_h.nemotron_h_config("lfm2-stage")
    assert (stage.hybrid_override_pattern, stage.head_dim, stage.n_kv_heads,
            stage.held_experts) == ("C-*ECECECE*E", 64, 8, tuple(range(8)))
