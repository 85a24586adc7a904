"""GPT-2 as published (Radford et al. 2019; the ``config.json`` keys of
``huggingface.co/openai-community/gpt2*``), in plain ``jax.numpy`` and
float32: pre-LayerNorm blocks, causal softmax attention, tanh-GELU MLP,
learned positions, mean next-token cross-entropy. No kernels, no cache, no
mixed precision, no code of the program: it reads the program's parameter
tree and nothing else of it.

Departure from the published model, the same as the configuration files
state: the output matrix is ``head.out.w``, not the transposed token
embedding (``ModelConfig.tie_embeddings`` is false in these cells).

Everything runs under ``jax.default_matmul_precision("highest")``: on a TPU
a float32 matrix product is otherwise done in bf16 passes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# |program's loss - this loss| / this loss, on the check batch. The program
# computes in bf16 over fp32 masters (8 bits of mantissa, eps 3.9e-3); its
# loss is a mean over 4096 tokens, so the roundings average out. Measured on
# the v5e at gpt2-medium over ten seeds (PR 26): median 6.6e-6, largest
# 1.9e-5. 1e-4 is five times the largest and still fails what a scalar loss
# at random init can show: on the CPU at gpt2-medium width and depth a
# dropped layer moves it by 2.0e-4 and fp8-rounded weights by 3.9e-4
# (tests/test_bench_reference.py injects both at a smaller width). It cannot
# see per-tensor int8 weights (3.8e-6, under bf16's own noise): PERF.md,
# section 7.
LOSS_TOL = 1e-4


def model_config(sizes: dict, numerics: dict):
    """The program's configuration for these published sizes: how the keys
    of GPT-2's ``config.json`` name ``ModelConfig``'s fields."""
    from distributed_training_with_pipeline_parallelism_tpu.utils.config import (
        ModelConfig)
    dim = sizes["n_embd"]
    return ModelConfig(
        arch="gpt2", dim=dim, n_layers=sizes["n_layer"],
        n_heads=sizes["n_head"], ffn_dim=sizes.get("n_inner") or 4 * dim,
        vocab_size=sizes["vocab_size"], max_seq_len=sizes["n_positions"],
        **numerics)


def _layer_norm(p, x, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _linear(p, x):
    return x @ p["w"] + p["b"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def _block(p, x, n_heads, eps):
    b, s, dim = x.shape
    a = _layer_norm(p["ln1"], x, eps)

    def heads(t):
        return t.reshape(b, s, n_heads, dim // n_heads).transpose(0, 2, 1, 3)

    q, k, v = (heads(_linear(p["attn"][n], a)) for n in "qkv")
    scores = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(dim / n_heads)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    mixed = jax.nn.softmax(scores, axis=-1) @ v
    x = x + _linear(p["attn"]["o"], mixed.transpose(0, 2, 1, 3).reshape(b, s, dim))
    m = _layer_norm(p["ln2"], x, eps)
    return x + _linear(p["lin2"], _gelu_new(_linear(p["lin1"], m)))


def loss(params, tokens, targets, sizes: dict):
    """Mean next-token cross-entropy of ``tokens`` [rows, seq] against
    ``targets`` [rows, seq], float32 throughout."""
    eps = sizes.get("layer_norm_epsilon", 1e-5)
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda w: w.astype(jnp.float32), params)
        x = params["embed"]["tok"][tokens] + params["embed"]["pos"][:tokens.shape[1]]
        x, _ = jax.lax.scan(
            lambda h, layer: (_block(layer, h, sizes["n_head"], eps), None),
            x, params["layers"])
        logits = _layer_norm(params["head"]["norm"], x, eps) @ params["head"]["out"]["w"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None], -1).mean()


def train_flops_per_token(sizes: dict, seq: int) -> float:
    from benchmark.flops import model
    return model.train_flops_per_token(sizes, seq)


def flash_call_shape(sizes: dict, rows: int, seq: int) -> tuple:
    """(rows, seq, heads, head_dim) of one flash-attention call: a layer's
    attention over one microbatch."""
    return (rows, seq, sizes["n_head"], sizes["n_embd"] // sizes["n_head"])
