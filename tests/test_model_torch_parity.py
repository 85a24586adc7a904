"""Numerical parity of the ref_decoder model against torch (CPU).

The reference model (SURVEY.md C2) is nn.Embedding -> N x
nn.TransformerDecoderLayer(batch_first=True) called as layer(h, h) -> LayerNorm
-> Linear. We copy a torch model's weights into our pytree and require the
forward logits and the token-wise CE loss to agree.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import torch
import torch.nn as nn

import distributed_training_with_pipeline_parallelism_tpu as dtpp
from distributed_training_with_pipeline_parallelism_tpu.models import transformer as tfm
from distributed_training_with_pipeline_parallelism_tpu.ops.layers import cross_entropy_loss

CFG = dtpp.ModelConfig(dim=64, n_layers=2, n_heads=4, vocab_size=101, ffn_dim=128)


class TorchRefModel(nn.Module):
    """Behavioral twin of the reference Transformer (dropout disabled)."""

    def __init__(self, cfg):
        super().__init__()
        self.tok_embeddings = nn.Embedding(cfg.vocab_size, cfg.dim)
        self.layers = nn.ModuleList([
            nn.TransformerDecoderLayer(cfg.dim, cfg.n_heads, dim_feedforward=cfg.ffn_dim,
                                       dropout=0.0, batch_first=True)
            for _ in range(cfg.n_layers)
        ])
        self.norm = nn.LayerNorm(cfg.dim)
        self.output = nn.Linear(cfg.dim, cfg.vocab_size)

    def forward(self, tokens):
        h = self.tok_embeddings(tokens)
        for layer in self.layers:
            h = layer(h, h)
        return self.output(self.norm(h))


def _t2j(t):
    return jnp.asarray(t.detach().numpy())


def _mha_params(mha, dim):
    wq, wk, wv = mha.in_proj_weight.chunk(3, dim=0)
    bq, bk, bv = mha.in_proj_bias.chunk(3, dim=0)
    return {
        "q": {"w": _t2j(wq).T, "b": _t2j(bq)},
        "k": {"w": _t2j(wk).T, "b": _t2j(bk)},
        "v": {"w": _t2j(wv).T, "b": _t2j(bv)},
        "o": {"w": _t2j(mha.out_proj.weight).T, "b": _t2j(mha.out_proj.bias)},
    }


def _ln_params(ln):
    return {"scale": _t2j(ln.weight), "bias": _t2j(ln.bias)}


def torch_to_pytree(model, cfg):
    per_layer = []
    for layer in model.layers:
        per_layer.append({
            "self_attn": _mha_params(layer.self_attn, cfg.dim),
            "cross_attn": _mha_params(layer.multihead_attn, cfg.dim),
            "ln1": _ln_params(layer.norm1),
            "ln2": _ln_params(layer.norm2),
            "ln3": _ln_params(layer.norm3),
            "lin1": {"w": _t2j(layer.linear1.weight).T, "b": _t2j(layer.linear1.bias)},
            "lin2": {"w": _t2j(layer.linear2.weight).T, "b": _t2j(layer.linear2.bias)},
        })
    layers = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
    return {
        "embed": {"tok": _t2j(model.tok_embeddings.weight)},
        "layers": layers,
        "head": {"norm": _ln_params(model.norm),
                 "out": {"w": _t2j(model.output.weight).T, "b": _t2j(model.output.bias)}},
    }


@pytest.fixture(scope="module")
def torch_model_and_params():
    torch.manual_seed(0)
    model = TorchRefModel(CFG).eval()
    return model, torch_to_pytree(model, CFG)


def test_forward_parity(torch_model_and_params):
    model, params = torch_model_and_params
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, CFG.vocab_size, (4, 16))
    with torch.no_grad():
        ref = model(torch.from_numpy(tokens)).numpy()
    got = np.asarray(tfm.transformer_apply(CFG, params, jnp.asarray(tokens)))
    np.testing.assert_allclose(got, ref, atol=2e-4, rtol=2e-4)


def test_loss_parity(torch_model_and_params):
    model, params = torch_model_and_params
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, CFG.vocab_size, (4, 16))
    targets = rng.integers(0, CFG.vocab_size, (4, 16))
    with torch.no_grad():
        logits = model(torch.from_numpy(tokens))
        ref_loss = nn.CrossEntropyLoss()(
            logits.reshape(-1, CFG.vocab_size), torch.from_numpy(targets).reshape(-1)
        ).item()
    got_loss = float(tfm.transformer_loss(CFG, params, jnp.asarray(tokens), jnp.asarray(targets)))
    assert abs(got_loss - ref_loss) < 2e-4


def test_init_shapes_and_grads():
    params = tfm.transformer_init(jax.random.key(0), CFG)
    assert params["embed"]["tok"].shape == (CFG.vocab_size, CFG.dim)
    assert params["layers"]["lin1"]["w"].shape == (CFG.n_layers, CFG.dim, CFG.ffn_dim)
    tokens = jnp.zeros((2, 8), dtype=jnp.int32)
    targets = jnp.zeros((2, 8), dtype=jnp.int32)
    loss, grads = jax.value_and_grad(
        lambda p: tfm.transformer_loss(CFG, p, tokens, targets))(params)
    assert np.isfinite(float(loss))
    leaves = jax.tree.leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in leaves)


@pytest.mark.parametrize("arch,kw", [
    ("gpt2", {}),
    ("llama", dict(n_kv_heads=2)),
])
def test_other_arches_forward(arch, kw):
    cfg = dtpp.ModelConfig(dim=64, n_layers=2, n_heads=4, vocab_size=101,
                           ffn_dim=128, max_seq_len=32, arch=arch, **kw)
    params = tfm.transformer_init(jax.random.key(0), cfg)
    tokens = jnp.zeros((2, 8), dtype=jnp.int32)
    logits = tfm.transformer_apply(cfg, params, tokens)
    assert logits.shape == (2, 8, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits)))


def test_gpt2_causality():
    cfg = dtpp.ModelConfig(dim=64, n_layers=2, n_heads=4, vocab_size=101,
                           ffn_dim=128, max_seq_len=32, arch="gpt2")
    params = tfm.transformer_init(jax.random.key(0), cfg)
    rng = np.random.default_rng(2)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 16)))
    base = tfm.transformer_apply(cfg, params, tokens)
    perturbed = tokens.at[0, -1].set((int(tokens[0, -1]) + 1) % cfg.vocab_size)
    out = tfm.transformer_apply(cfg, params, perturbed)
    # future-token change must not affect earlier positions
    np.testing.assert_allclose(np.asarray(out[0, :-1]), np.asarray(base[0, :-1]),
                               atol=1e-5, rtol=1e-5)
    assert not np.allclose(np.asarray(out[0, -1]), np.asarray(base[0, -1]))


# ---------------------------------------------------------------------------
# unroll_layers: the straight-line layer loop must match the lax.scan path
# (the GPT-2 configurations run through it — docs/performance.md "MFU sprint")
# ---------------------------------------------------------------------------


@pytest.mark.smoke
@pytest.mark.parametrize("arch,extra", [
    ("gpt2", {}),
    ("llama", {"n_kv_heads": 2}),
    ("gpt2", {"remat_layers": True}),
    ("gpt2", {"dropout": 0.2}),
])
def test_unroll_layers_matches_scan(arch, extra):
    """Loss and grads (and, with dropout, the exact per-layer masks) are
    identical between unroll_layers=True and the default scan."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    import distributed_training_with_pipeline_parallelism_tpu as dtpp
    from distributed_training_with_pipeline_parallelism_tpu.models import (
        transformer as tfm)

    cfg = dtpp.ModelConfig(dim=32, n_layers=4, n_heads=4, vocab_size=64,
                           ffn_dim=64, max_seq_len=16, arch=arch, **extra)
    params = tfm.transformer_init(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (4, 8), 0, cfg.vocab_size)
    rng = jax.random.key(7) if cfg.dropout else None

    def loss_of(c):
        if rng is None:
            return jax.value_and_grad(
                lambda p: tfm.transformer_loss(c, p, tokens, tokens))(params)
        return jax.value_and_grad(
            lambda p: tfm.transformer_loss(c, p, tokens, tokens,
                                           rng=rng))(params)

    l_scan, g_scan = loss_of(cfg)
    l_unroll, g_unroll = loss_of(dataclasses.replace(cfg, unroll_layers=True))
    assert float(jnp.abs(l_scan - l_unroll)) < 1e-6
    errs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                        g_scan, g_unroll)
    assert max(jax.tree.leaves(errs)) < 1e-5
