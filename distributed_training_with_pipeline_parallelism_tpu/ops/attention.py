"""Multi-head attention as a pure function.

Semantics match ``torch.nn.MultiheadAttention`` (batch_first): packed Q/K/V
projections, scaled dot-product over heads, output projection. Exposed as
separate q/k/v weight leaves so stage-stacking and tensor-parallel sharding
stay natural; the torch-parity test splits torch's packed ``in_proj_weight``
into these leaves.

Supports grouped-query attention (n_kv_heads < n_heads), an optional RoPE
rotation (the Llama family; the patterned stack's attention where
``cfg.attn_rope``) and an optional RMSNorm over every query and key head
before it (LFM2). Below it, multi-head latent attention in its
training form (:func:`mla_apply`: low-rank query and key-value paths, RoPE in
interleaved pairs on the rotary columns only, query/key heads wider than
value heads).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from .layers import (dropout_apply, linear_init, linear_apply, named_product,
                     rms_norm_apply, rms_norm_init, sharded_dropout_apply)


def mha_init(key: jax.Array, dim: int, n_heads: int, n_kv_heads: Optional[int] = None,
             bias: bool = True, o_bias: Optional[bool] = None,
             head_dim: Optional[int] = None, qk_norm: bool = False) -> Dict:
    """``bias`` covers q/k/v; ``o_bias`` the output projection (defaults to
    ``bias`` — Qwen2-family blocks set bias=True, o_bias=False).
    ``head_dim`` decouples per-head width from ``dim // n_heads``
    (Gemma-family blocks). ``qk_norm`` adds ``q_layernorm`` and
    ``k_layernorm`` (LFM2's names): one RMSNorm scale over the ``head_dim``
    columns, shared by every query head, and one by every key head, which
    :func:`qkv_project` applies where the leaves are."""
    n_kv_heads = n_kv_heads or n_heads
    head_dim = head_dim or dim // n_heads
    kq, kk, kv, ko = jax.random.split(key, 4)
    params = {
        "q": linear_init(kq, dim, n_heads * head_dim, bias=bias),
        "k": linear_init(kk, dim, n_kv_heads * head_dim, bias=bias),
        "v": linear_init(kv, dim, n_kv_heads * head_dim, bias=bias),
        "o": linear_init(ko, n_heads * head_dim, dim,
                         bias=bias if o_bias is None else o_bias),
    }
    if qk_norm:
        params["q_layernorm"] = rms_norm_init(head_dim)
        params["k_layernorm"] = rms_norm_init(head_dim)
    return params


def _split_heads(x: jax.Array, n_heads: int) -> jax.Array:
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, -1)


def rope_frequencies(head_dim: int, max_seq_len: int, theta: float = 10000.0,
                     scaling: Optional[tuple] = None) -> jax.Array:
    """Precompute RoPE angles [max_seq_len, head_dim//2].

    ``scaling`` applies Llama-3.1 long-context frequency scaling — a tuple
    ``(factor, low_freq_factor, high_freq_factor,
    original_max_position_embeddings)`` matching transformers'
    ``rope_scaling`` with ``rope_type="llama3"``: wavelengths shorter than
    ``orig/high`` keep their frequency, longer than ``orig/low`` divide by
    ``factor``, and the band between interpolates smoothly.
    """
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if scaling is not None:
        factor, low_f, high_f, orig_max = scaling
        wavelen = 2.0 * jnp.pi / inv
        smooth = (orig_max / wavelen - low_f) / (high_f - low_f)
        mid = (1.0 - smooth) * inv / factor + smooth * inv
        inv = jnp.where(wavelen > orig_max / low_f, inv / factor,
                        jnp.where(wavelen < orig_max / high_f, inv, mid))
    t = jnp.arange(max_seq_len, dtype=jnp.float32)
    return jnp.outer(t, inv)


def apply_rope(x: jax.Array, angles: jax.Array) -> jax.Array:
    """Rotate [b, s, h, d] query/key tensors by per-position angles [s, d//2]."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)  # rotation runs in f32; don't promote bf16 activations


def band_mask(n_q: int, n_k: int, window: Optional[int] = None,
              q_offset=0) -> jax.Array:
    """Causal [n_q, n_k] mask, optionally banded to a sliding window: query
    i (at global position q_offset + i) sees keys in
    ``[pos - window + 1, pos]``. The single source of the window
    convention — used by the dense train path, the flash kernel's backward,
    and the KV-cache decode path."""
    iq = q_offset + jnp.arange(n_q)[:, None]
    ik = jnp.arange(n_k)[None, :]
    mask = iq >= ik
    if window is not None:
        mask &= iq - ik < window
    return mask


def gqa_expand(k: jax.Array, v: jax.Array, n_heads: int):
    """Repeat kv heads up to n_heads for grouped-query attention (no-op for MHA)."""
    n_kv = k.shape[2]
    if n_kv != n_heads:
        rep = n_heads // n_kv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


def scaled_dot_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         mask: Optional[jax.Array] = None,
                         dropout_rate: float = 0.0,
                         dropout_rng=None,
                         head_shard: Optional[tuple] = None) -> jax.Array:
    """Core attention: q [b,s,h,d] x k/v [b,t,h,d] -> [b,s,h,d].

    ``mask`` broadcasts against scores [b,h,s,t]; False positions are dropped.
    Shared by the training path (:func:`mha_apply`) and the KV-cache decode
    path (:mod:`..models.generate`) so the two cannot drift. Softmax runs in
    f32 regardless of activation dtype. ``dropout_rng`` (train mode) applies
    dropout to the attention probabilities, as torch's MultiheadAttention
    does with a nonzero ``dropout`` constructor arg. ``head_shard`` —
    ``(axis_name, n_shards)`` when the head dim is a tensor/sequence-parallel
    local shard — keys the dropout mask to the *global* head index so the
    sharded run reproduces the unsharded masks exactly.
    """
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], dtype=q.dtype))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    # checkpoint: saves only the [b,h,s,t] scores for backward (the f32
    # softmax output and its compute-dtype copy — 3x the scores bytes —
    # are recomputed, a pointwise cost). Cuts every stored-activation
    # path's residual traffic; the flash kernel path never builds these.
    probs = jax.checkpoint(
        lambda s: jax.nn.softmax(s.astype(jnp.float32),
                                 axis=-1).astype(q.dtype))(scores)
    if head_shard is not None and head_shard[1] > 1:
        probs = sharded_dropout_apply(probs, dropout_rate, dropout_rng,
                                      axis=head_shard[0],
                                      n_shards=head_shard[1], shard_dim=1)
    else:
        probs = dropout_apply(probs, dropout_rate, dropout_rng)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def qkv_project(params: Dict, q_in: jax.Array, kv_in: jax.Array, n_heads: int,
                rope_angles: Optional[jax.Array] = None,
                expand_gqa: bool = True, norm_eps: float = 1e-5):
    """Shared attention prologue: linear q/k/v projections, head split,
    the per-head q/k RMSNorm where the parameters hold one
    (:func:`mha_init`'s ``qk_norm``; BEFORE the rotation, as the source
    applies it), optional RoPE, optional GQA expansion. Used by the dense path
    (:func:`mha_apply`) and both sequence-parallel wrappers
    (``parallel.ring_attention`` / ``parallel.ulysses``) so the projection
    conventions cannot drift between them."""
    head_dim = params["q"]["w"].shape[1] // n_heads
    n_kv = params["k"]["w"].shape[1] // head_dim
    def product(m, x, heads):
        return _split_heads(named_product(
            linear_apply(params[m], x), "attn_" + m, x.shape[-1]), heads)

    q = product("q", q_in, n_heads)
    k = product("k", kv_in, n_kv)
    v = product("v", kv_in, n_kv)
    if "q_layernorm" in params:
        q = rms_norm_apply(params["q_layernorm"], q, norm_eps)
        k = rms_norm_apply(params["k_layernorm"], k, norm_eps)
    if rope_angles is not None:
        q = apply_rope(q, rope_angles)
        k = apply_rope(k, rope_angles)
    if expand_gqa:
        k, v = gqa_expand(k, v, n_heads)
    return q, k, v


def mha_apply(params: Dict, q_in: jax.Array, kv_in: jax.Array, n_heads: int,
              causal: bool = False, rope_angles: Optional[jax.Array] = None,
              flash: bool = False, tp_axis: Optional[str] = None,
              window: Optional[int] = None, dropout_rate: float = 0.0,
              dropout_rng=None, tp_size: int = 1,
              norm_eps: float = 1e-5) -> jax.Array:
    """Attention: queries from ``q_in``, keys/values from ``kv_in`` (both [b, s, d]).
    ``norm_eps`` is the q/k norms', where the parameters hold them
    (:func:`qkv_project`).

    ``flash=True`` routes the core attention through the fused Pallas kernel
    (:mod:`.pallas_attention`) instead of dense XLA softmax-matmuls.

    ``tp_axis`` enables Megatron tensor parallelism inside a manual-SPMD
    region: the q/k/v/o weight leaves are the caller's *local shards*
    (heads column-split; ``n_heads`` is the local head count), the inputs
    are replicated (``tp_copy`` marks them so input cotangents sum), and
    the output projection is row-parallel (``tp_reduce`` completes it).
    """
    from .collectives import tp_attention_inputs, tp_output_projection
    q_in, kv_in = tp_attention_inputs(q_in, kv_in, tp_axis)
    q, k, v = qkv_project(params, q_in, kv_in, n_heads, rope_angles,
                          norm_eps=norm_eps)
    if flash:
        if dropout_rng is not None and dropout_rate > 0.0:
            raise ValueError("flash attention does not support attention-prob "
                             "dropout (guarded in ModelConfig)")
        from .pallas_attention import flash_attention
        out = flash_attention(q, k, v, causal=causal, window=window)
    else:
        mask = None
        if causal:
            s = q_in.shape[1]
            mask = band_mask(s, s, window)[None, None]
        out = scaled_dot_attention(
            q, k, v, mask, dropout_rate, dropout_rng,
            head_shard=(tp_axis, tp_size) if tp_axis is not None else None)
    out = out.reshape(q_in.shape[0], q_in.shape[1], -1)
    return tp_output_projection(params["o"], out, tp_axis)


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2/V3-style MLA), training form
# ---------------------------------------------------------------------------


def apply_rope_interleaved(x: jax.Array, angles: jax.Array) -> jax.Array:
    """Rotate [b, s, h, d] by per-position angles [s, d//2] in the
    INTERLEAVED convention (``rope_interleave``): the pair ``(x[2i],
    x[2i+1])`` turns by angle ``i`` and stays where it was
    (:func:`apply_rope` pairs ``x[i]`` with ``x[i + d/2]``). A pair's
    partner ``(x0, x1) -> (-x1, x0)`` is a product with a constant signed
    permutation: exact in any dtype, and no ``[.., d/2, 2]`` reshape, whose
    two-wide minor dimension a TPU tiles to 128 lanes."""
    d = x.shape[-1]
    even = jnp.arange(0, d, 2)
    swap = (jnp.zeros((d, d), x.dtype).at[even + 1, even].set(-1)
            .at[even, even + 1].set(1))
    partner = jnp.einsum("bshd,de->bshe", x, swap,
                         precision=jax.lax.Precision.HIGHEST)
    cos = jnp.repeat(jnp.cos(angles), 2, axis=-1)[None, :, None, :]
    sin = jnp.repeat(jnp.sin(angles), 2, axis=-1)[None, :, None, :]
    return (x * cos + partner * sin).astype(x.dtype)  # f32 inside, as apply_rope


def mla_init(key: jax.Array, dim: int, n_heads: int, q_lora_rank: int,
             kv_lora_rank: int, qk_nope_head_dim: int, qk_rope_head_dim: int,
             v_head_dim: int) -> Dict:
    """The two low-rank paths (``*_a`` down, a norm on the latent, ``*_b`` up
    per head) and the output projection; no biases. ``kv_a`` also gives the
    one ``qk_rope_head_dim``-wide key every head shares."""
    kqa, kqb, kka, kkb, ko = jax.random.split(key, 5)
    qk = qk_nope_head_dim + qk_rope_head_dim
    return {
        "q_a": linear_init(kqa, dim, q_lora_rank, bias=False),
        "q_norm": rms_norm_init(q_lora_rank),
        "q_b": linear_init(kqb, q_lora_rank, n_heads * qk, bias=False),
        "kv_a": linear_init(kka, dim, kv_lora_rank + qk_rope_head_dim,
                            bias=False),
        "kv_norm": rms_norm_init(kv_lora_rank),
        "kv_b": linear_init(kkb, kv_lora_rank,
                            n_heads * (qk_nope_head_dim + v_head_dim),
                            bias=False),
        "o": linear_init(ko, n_heads * v_head_dim, dim, bias=False),
    }


@jax.named_scope("model/mla_latent")
def mla_project(params: Dict, x: jax.Array, n_heads: int,
                qk_rope_head_dim: int, rope_theta: float, eps: float):
    """Everything between the normed input ``x`` [b, s, d] and the attention
    core's operands: ``q``, ``k`` [b, s, h, nope + rope] and ``v`` [b, s, h,
    v_head_dim] (the widths are the parameters'). ``c_q = norm(x W_qa)``, ``[q_nope | q_pe] = c_q W_qb``;
    ``[c_kv | k_pe] = x W_kva``, ``[k_nope | v] = norm(c_kv) W_kvb``; the
    ``rope`` parts rotated (interleaved pairs, angles in float32, no
    scaling), ``k_pe`` one head for all."""
    b, s, _ = x.shape
    rope = qk_rope_head_dim
    def product(m, x):
        return named_product(linear_apply(params[m], x), "mla_" + m,
                             x.shape[-1])

    c_q = rms_norm_apply(params["q_norm"], product("q_a", x), eps)
    q = product("q_b", c_q).reshape(b, s, n_heads, -1)
    nope = q.shape[-1] - rope
    c_kv = product("kv_a", x)
    kv_rank = c_kv.shape[-1] - rope
    kv = product("kv_b", rms_norm_apply(
        params["kv_norm"], c_kv[..., :kv_rank], eps)).reshape(b, s, n_heads, -1)
    angles = rope_frequencies(rope, s, rope_theta)
    q_pe = apply_rope_interleaved(q[..., nope:], angles)
    k_pe = apply_rope_interleaved(c_kv[:, :, None, kv_rank:], angles)
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_pe, (b, s, n_heads, rope))], axis=-1)
    return q, k, kv[..., nope:]


def mla_apply(params: Dict, x: jax.Array, n_heads: int, qk_rope_head_dim: int,
              rope_theta: float, eps: float, flash: bool = False) -> jax.Array:
    """Causal latent attention on the normed ``x`` [b, s, d]: scores over
    ``nope + rope`` columns scaled by ``1/sqrt(nope + rope)``, values and
    output over ``v_head_dim`` — through the Pallas kernels at the two widths
    as they are where ``flash`` (:func:`.pallas_attention.flash_attention`),
    dense otherwise."""
    b, s, _ = x.shape
    q, k, v = mla_project(params, x, n_heads, qk_rope_head_dim, rope_theta,
                          eps)
    if flash:
        from .pallas_attention import flash_attention
        out = flash_attention(q, k, v, causal=True)
    else:
        out = scaled_dot_attention(q, k, v, band_mask(s, s)[None, None])
    return linear_apply(params["o"], out.reshape(b, s, -1))
