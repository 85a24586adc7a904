"""The plain float32 reference against the program, on the CPU at a small
width: equal in float32, within the chip's tolerance in bf16, and outside
it for the faults the tolerance is there to catch."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import manifest as mf

SIZES = dict(n_embd=512, n_layer=8, n_head=8, n_inner=None, vocab_size=8192,
             n_positions=128, layer_norm_epsilon=1e-5)
SEQ = 128


@pytest.fixture(scope="module")
def setting():
    from distributed_training_with_pipeline_parallelism_tpu.models.transformer import (
        transformer_init, transformer_loss)
    family = mf.load_reference("gpt2")
    cfg = family.model_config(SIZES, dict(
        dtype="bfloat16", param_dtype="float32", use_flash_attention=False))
    params = transformer_init(jax.random.key(3), cfg)
    toks = np.random.default_rng(0).integers(
        0, SIZES["vocab_size"], (4, SEQ + 1), dtype=np.int32)
    x, y = toks[:, :-1], toks[:, 1:]
    want = float(jax.jit(lambda p: family.loss(p, x, y, SIZES))(params))

    def program(cfg, params):
        got = float(jax.jit(lambda p: transformer_loss(cfg, p, x, y))(params))
        return abs(got - want) / want

    return family, cfg, params, program, want


def test_adapter_maps_the_published_keys():
    family = mf.load_reference("gpt2")
    for name, (dim, layers, heads) in {"gpt2-medium": (1024, 24, 16),
                                       "gpt2-xl": (1600, 48, 25)}.items():
        config = mf.load_config(mf.load_manifest(), name)
        cfg = family.model_config(config["sizes"], config["numerics"])
        assert (cfg.arch, cfg.dim, cfg.n_layers, cfg.n_heads, cfg.ffn_dim,
                cfg.vocab_size, cfg.max_seq_len) == (
                    "gpt2", dim, layers, heads, 4 * dim, 50257, 1024)
        assert (cfg.dtype, cfg.param_dtype, cfg.use_flash_attention,
                cfg.use_fused_xent, cfg.tie_embeddings) == (
                    "bfloat16", "float32", "auto", True, False)
        assert family.flash_call_shape(config["sizes"], 2, 1024) == (
            2, 1024, heads, 64)


def test_reference_is_the_programs_mathematics(setting):
    family, cfg, params, program, want = setting
    assert abs(want - np.log(SIZES["vocab_size"])) < 0.5
    assert program(dataclasses.replace(cfg, dtype="float32"), params) < 1e-6


def test_bf16_is_inside_the_tolerance_and_faults_are_outside(setting):
    family, cfg, params, program, _ = setting
    assert program(cfg, params) < family.LOSS_TOL
    dropped = dict(params, layers=jax.tree.map(lambda a: a[:-1],
                                               params["layers"]))
    assert program(dataclasses.replace(cfg, n_layers=cfg.n_layers - 1),
                   dropped) > family.LOSS_TOL
    fp8 = jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        if a.ndim >= 2 else a, params)
    assert program(cfg, fp8) > family.LOSS_TOL
