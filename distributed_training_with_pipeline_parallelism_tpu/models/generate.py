"""Autoregressive decoding with a static-shape KV cache.

The reference has no inference path at all (its models are randomly
initialized, trained for throughput measurement, and discarded —
``LLMsDistributedTrainingHelper.py:191-194``); a complete framework needs one.
This module is TPU-first by construction:

- The KV cache is a **fixed-shape** ring of ``[n_layers, B, max_len, kv_heads,
  head_dim]`` buffers updated with ``lax.dynamic_update_slice`` — no growing
  arrays, so the whole decode loop jits once and runs as a single XLA program.
- Prefill and decode share one code path: ``_forward_with_cache`` processes S
  new positions starting at a traced offset (S = prompt length for prefill,
  S = 1 per decode step), attending each query against the full cache under a
  position mask. One implementation, no prefill/decode drift.
- The token loop is a ``lax.scan`` over decode steps (no Python loop, no
  per-step dispatch); sampling (greedy / temperature / top-k / top-p) happens
  on device.

Supports the ``gpt2`` and ``llama`` block families. ``ref_decoder`` is
rejected: the reference model is non-causal with no positional encoding
(SURVEY.md C2), so autoregressive decoding is semantically undefined for it.

Scope note: this module's decode loop runs single-device or GSPMD-TP
(tests/test_generate.py::test_generate_with_tp_sharded_params). Decoding
over a PIPELINE mesh lives in :mod:`..parallel.pipelined_decode`
(round 4): naively pipelining one-token steps would run at 1/D
utilization (each step's compute cannot fill even one stage), so that
executor round-robins M >= D independent batch streams through the
stages — steady-state-full like training microbatches, with the sampled
token riding the same +1 ring home (stage D-1 -> 0 IS the +1 hop).
Batch scoring over a pipe mesh is
``parallel.pipeline.make_pipeline_forward`` (fill-drain, V chunks
supported), and eval losses on any dense training mesh are
``make_pipeline_loss_fn``. For models too big for one chip at decode
time, TP (here) splits the bandwidth-bound weight reads; pipelined
decode splits the model depth-wise with the same stage slicing as
training.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import (apply_rope, gqa_expand, rope_frequencies,
                             scaled_dot_attention)
from ..ops.layers import (embedding_apply, layer_norm_apply, linear_apply,
                          rms_norm_apply)
from ..utils.config import ModelConfig
from .transformer import head_apply, mlp_block

Pytree = Dict


def init_cache(cfg: ModelConfig, batch_size: int, max_len: int,
               dtype=None) -> Pytree:
    """Allocate an all-zeros KV cache: leaves [n_layers, B, max_len, Hkv, hd]."""
    from .nemotron_h import not_served
    not_served("models/generate.py", cfg)
    n_kv = cfg.n_kv_heads or cfg.n_heads
    shape = (cfg.n_layers, batch_size, max_len, n_kv, cfg.head_dim)
    dtype = dtype or jnp.dtype(cfg.dtype)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _attend_cached(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                   offset: jax.Array, n_heads: int,
                   window: Optional[int] = None) -> jax.Array:
    """Attention of S new queries against the full cached sequence.

    q: [B, S, H, hd] at global positions offset..offset+S-1;
    k_cache/v_cache: [B, T, Hkv, hd]. A key at cache index j is visible to the
    query at global position i iff j <= i — which simultaneously enforces
    causality inside the new block and masks the unwritten cache tail.
    """
    from ..ops.attention import band_mask
    k_cache, v_cache = gqa_expand(k_cache, v_cache, n_heads)
    s, t = q.shape[1], k_cache.shape[1]
    mask = band_mask(s, t, window, q_offset=offset)[None, None]
    out = scaled_dot_attention(q, k_cache, v_cache, mask)
    return out.reshape(q.shape[0], s, -1)


def _layer_step(cfg: ModelConfig, lp: Pytree, h: jax.Array, k_cache: jax.Array,
                v_cache: jax.Array, offset: jax.Array,
                rope_slice: Optional[jax.Array],
                tp_axis: Optional[str] = None, tp_size: int = 1,
                prefill: bool = False
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One block over S new positions; writes their k/v into the cache at
    ``offset`` and returns (h_out, k_cache, v_cache).

    ``tp_axis`` (round 5, inside shard_map only) runs the block
    Megatron-sharded over that mesh axis: q/k/v column-parallel (local
    head shards — the KV cache holds ``Hkv/tp_size`` heads per model
    rank), o and the MLP down-projection row-parallel with one psum each.
    Decode is where TP shines — small batch, weight-read bound — and the
    weight reads split ``tp_size`` ways.

    ``prefill=True`` is a STATIC promise by the caller that ``offset`` is
    zero and the cache holds nothing before this call — the S new
    positions are the whole sequence, so their attention is plain causal
    self-attention over the new block. Under that promise the call is
    eligible for the Pallas flash kernel with the training path's exact
    fallback discipline (``cfg.flash_for``: 'auto' = causal TPU
    sequences >= 256, dense elsewhere); sites with traced offsets —
    decode steps, the serving engine's chunked prefill — must keep the
    default and stay on the cached dense path."""
    b, s, _ = h.shape
    n_heads = cfg.n_heads // tp_size
    n_kv = (cfg.n_kv_heads or cfg.n_heads) // tp_size
    if cfg.arch == "gpt2":
        a = layer_norm_apply(lp["ln1"], h)
    else:
        a = rms_norm_apply(lp["rms1"], h, cfg.rms_eps)
    ap = lp["attn"]
    q = linear_apply(ap["q"], a).reshape(b, s, n_heads, cfg.head_dim)
    k = linear_apply(ap["k"], a).reshape(b, s, n_kv, cfg.head_dim)
    v = linear_apply(ap["v"], a).reshape(b, s, n_kv, cfg.head_dim)
    if rope_slice is not None:
        q = apply_rope(q, rope_slice)
        k = apply_rope(k, rope_slice)
    k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype),
                                           (0, offset, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype),
                                           (0, offset, 0, 0))
    if prefill and cfg.flash_for(True, s):
        # the new block IS the whole visible sequence (offset==0 promise),
        # so attend q against the pre-cache k/v through the flash kernel —
        # the cached tail is all masked zeros either way
        from ..ops.pallas_attention import flash_attention
        kf, vf = gqa_expand(k, v, n_heads)
        att = flash_attention(q, kf, vf, causal=True,
                              window=cfg.sliding_window).reshape(b, s, -1)
    else:
        att = _attend_cached(q, k_cache, v_cache, offset, n_heads,
                             cfg.sliding_window)
    if tp_axis is None:
        attn = linear_apply(ap["o"], att)
    else:
        from ..ops.collectives import tp_output_projection
        attn = tp_output_projection(ap["o"], att, tp_axis)
    return (mlp_block(cfg, lp, h + attn, tp_axis=tp_axis, tp_size=tp_size),
            k_cache, v_cache)


def _embed_at(cfg: ModelConfig, embed: Pytree, tokens: jax.Array,
              offset: jax.Array) -> jax.Array:
    """Embed S new tokens at global positions offset..offset+S-1 (decode
    twin of the training-path embed — gpt2 needs pos[offset:offset+s],
    not embed_apply's [:s])."""
    from .transformer import embed_apply
    if cfg.arch == "gpt2":
        h = embedding_apply(embed["tok"], tokens)
        if cfg.embed_scale:  # MoE-LM Gemma convention: scale precedes pos
            h = h * (cfg.dim ** 0.5)
        pos = jax.lax.dynamic_slice_in_dim(embed["pos"], offset,
                                           tokens.shape[1])
        return h + pos
    # the training-path embed (incl. Gemma's sqrt(dim) scaling) — shared
    # so decode cannot drift from train/eval
    return embed_apply(cfg, embed, tokens)


def rope_slice_at(cfg: ModelConfig, max_len: int, offset: jax.Array,
                  s: int) -> Optional[jax.Array]:
    """RoPE angles for S new positions starting at ``offset`` (None for
    non-RoPE archs)."""
    if cfg.arch != "llama":
        return None
    angles = rope_frequencies(cfg.head_dim, max_len, cfg.rope_theta,
                              cfg.rope_scaling)
    return jax.lax.dynamic_slice_in_dim(angles, offset, s)


def layers_with_cache(cfg: ModelConfig, layers: Pytree, h: jax.Array,
                      k_cache: jax.Array, v_cache: jax.Array,
                      offset: jax.Array, rope_slice: Optional[jax.Array],
                      tp_axis: Optional[str] = None, tp_size: int = 1,
                      prefill: bool = False
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Scan a stack of blocks over S new positions with per-layer KV
    caches [L, B, T, Hkv(/tp_size), hd]. Shared by the single-device
    decode and the pipelined decode's stage bodies (each stage passes its
    layer slice and cache shard; with ``tp_axis`` the layer leaves are
    Megatron model-axis shards). ``prefill`` flags statically-zero-offset
    fresh-cache calls as flash-eligible (see :func:`_layer_step`)."""
    def body(carry, xs):
        lp, kc, vc = xs
        h, kc, vc = _layer_step(cfg, lp, carry, kc, vc, offset, rope_slice,
                                tp_axis=tp_axis, tp_size=tp_size,
                                prefill=prefill)
        return h, (kc, vc)

    return jax.lax.scan(body, h, (layers, k_cache, v_cache))


def _forward_with_cache(cfg: ModelConfig, params: Pytree, cache: Pytree,
                        tokens: jax.Array, offset: jax.Array,
                        prefill: bool = False
                        ) -> Tuple[jax.Array, Pytree]:
    """Run S new tokens (global positions offset..offset+S-1) through the model.

    Returns (last-position logits [B, V], updated cache). Serves as both
    prefill (offset=0, S=prompt_len, pass ``prefill=True`` for the flash
    fast path) and decode step (S=1).
    """
    if cfg.arch not in ("gpt2", "llama"):
        raise ValueError(
            f"generation is undefined for arch {cfg.arch!r}: the reference "
            "block is non-causal with no positional encoding (SURVEY.md C2)")
    from .transformer import compute_cast
    params = compute_cast(cfg, params)  # decode in the compute dtype too
    b, s = tokens.shape
    h = _embed_at(cfg, params["embed"], tokens, offset)
    rope_slice = rope_slice_at(cfg, cache["k"].shape[2], offset, s)
    h, (k_new, v_new) = layers_with_cache(cfg, params["layers"], h,
                                          cache["k"], cache["v"], offset,
                                          rope_slice)
    logits = head_apply(cfg, params["head"], h[:, -1:],
                        embed=params["embed"])[:, 0]
    return logits, {"k": k_new, "v": v_new}


def token_logprob(cfg: ModelConfig, logits: jax.Array,
                  tok: jax.Array) -> jax.Array:
    """Log-probability [B] f32 of the chosen token ``tok`` [B] under
    ``logits`` [B, V] — the decode-path twin of the training loss core:
    ``cfg.use_fused_xent`` routes through the Pallas fused-NLL kernel
    (``ops.pallas_xent``, which never materializes the [B, V]
    log-softmax), the default through the XLA formulation. Identical
    values either way (the kernel is tested against the formulation)."""
    if cfg.use_fused_xent:
        from ..ops.pallas_xent import fused_softmax_xent
        return -fused_softmax_xent(logits, tok.astype(jnp.int32))
    logz = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(logz, tok.astype(jnp.int32)[:, None],
                               axis=-1)[:, 0]


def sample_logits(key: Optional[jax.Array], logits: jax.Array,
                  temperature: float = 0.0, top_k: Optional[int] = None,
                  top_p: Optional[float] = None) -> jax.Array:
    """Draw next-token ids [B] from logits [B, V].

    temperature=0 is greedy argmax (no key needed); otherwise categorical
    sampling after temperature scaling, optional top-k truncation, and
    optional top-p (nucleus) truncation.
    """
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits.astype(jnp.float32) / temperature
    if top_k is not None:
        top_k = min(top_k, logits.shape[-1])
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        # smallest prefix with mass >= top_p: cut at the last logit whose
        # *preceding* (exclusive) cumulative mass is < top_p
        exclusive_cdf = jnp.cumsum(probs, axis=-1) - probs
        cutoff_idx = jnp.sum(exclusive_cdf < top_p, axis=-1) - 1
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx[:, None], axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1)


def generate(cfg: ModelConfig, params: Pytree, prompt: jax.Array,
             max_new_tokens: int, *, key: Optional[jax.Array] = None,
             temperature: float = 0.0, top_k: Optional[int] = None,
             top_p: Optional[float] = None,
             max_len: Optional[int] = None,
             eos_id: Optional[int] = None,
             return_lengths: bool = False,
             return_logprobs: bool = False) -> jax.Array:
    """Generate ``max_new_tokens`` continuations of ``prompt`` [B, P].

    Returns [B, P + max_new_tokens]. Pure and jittable (see
    :func:`make_generate_fn` for the pre-jitted closure); the decode loop is a
    single ``lax.scan``.

    With ``eos_id`` decoding is EOS-aware while keeping every shape
    static: once a row emits ``eos_id`` it is *frozen* — its KV-cache
    writes are masked (``jnp.where`` keeps the old cache bit-for-bit)
    and every subsequent emitted token is forced to ``eos_id``. With
    ``return_lengths=True`` (requires ``eos_id``) returns
    ``(tokens [B, P+N], lengths [B])`` where ``lengths`` counts emitted
    tokens per row including the EOS itself (N when no EOS appeared).
    These are exactly the freeze semantics of the pipelined decoder and
    the serving executor, so all three stay token-for-token comparable.

    With ``return_logprobs=True`` the result additionally carries the
    emitted tokens' log-probabilities [B, N] f32 (appended last), each
    computed from the same logits its token was sampled from through
    :func:`token_logprob` (``cfg.use_fused_xent`` routes the Pallas
    fused-NLL kernel). EOS-frozen rows report 0.0 for their forced
    tokens — forced, not sampled — matching the pipelined decoder.
    """
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if return_lengths and eos_id is None:
        raise ValueError("return_lengths=True requires an eos_id (without "
                         "one every row emits exactly max_new_tokens)")
    b, p = prompt.shape
    total = p + max_new_tokens
    max_len = max_len or total
    if total > max_len:
        raise ValueError(f"prompt ({p}) + max_new_tokens ({max_new_tokens}) "
                         f"exceeds max_len ({max_len})")
    if cfg.arch == "gpt2" and total > cfg.max_seq_len:
        # past the learned position table, dynamic_slice would clamp and
        # silently reuse the last position's embedding
        raise ValueError(f"prompt ({p}) + max_new_tokens ({max_new_tokens}) "
                         f"exceeds the gpt2 position table "
                         f"(max_seq_len={cfg.max_seq_len})")
    if temperature != 0.0 and key is None:
        raise ValueError("sampling (temperature != 0) requires a PRNG key")
    cache = init_cache(cfg, b, max_len)
    logits, cache = _forward_with_cache(cfg, params, cache, prompt,
                                        jnp.int32(0), prefill=True)
    keys = jax.random.split(key if key is not None else jax.random.key(0),
                            max_new_tokens)
    first = sample_logits(keys[0], logits, temperature, top_k, top_p)

    lps = None
    if not return_logprobs:
        if eos_id is None:
            def step(carry, step_key):
                cache, tok, pos = carry
                logits, cache = _forward_with_cache(cfg, params, cache,
                                                    tok[:, None], pos)
                nxt = sample_logits(step_key, logits, temperature, top_k,
                                    top_p)
                return (cache, nxt, pos + 1), tok

            (_, last, _), toks = jax.lax.scan(
                step, (cache, first, jnp.int32(p)), keys[1:])
        else:
            # a row is done once the token it is ABOUT to consume is EOS —
            # that token's KV never enters the cache and all later emissions
            # are forced to eos_id (same freeze rule as pipelined_decode)
            def step(carry, step_key):
                cache, tok, pos, done = carry
                logits, cache2 = _forward_with_cache(cfg, params, cache,
                                                     tok[:, None], pos)
                m = done[None, :, None, None, None]
                cache = jax.tree.map(lambda old, new: jnp.where(m, old, new),
                                     cache, cache2)
                nxt = sample_logits(step_key, logits, temperature, top_k,
                                    top_p)
                nxt = jnp.where(done, jnp.asarray(eos_id, nxt.dtype), nxt)
                return (cache, nxt, pos + 1, done | (nxt == eos_id)), tok

            done0 = first == eos_id
            (_, last, _, _), toks = jax.lax.scan(
                step, (cache, first, jnp.int32(p), done0), keys[1:])
    else:
        # same loops with the token's logprob riding the carry; kept as a
        # separate Python branch so the default jaxpr is untouched
        lp0 = token_logprob(cfg, logits, first)
        if eos_id is None:
            def step(carry, step_key):
                cache, tok, lp, pos = carry
                logits, cache = _forward_with_cache(cfg, params, cache,
                                                    tok[:, None], pos)
                nxt = sample_logits(step_key, logits, temperature, top_k,
                                    top_p)
                return (cache, nxt, token_logprob(cfg, logits, nxt),
                        pos + 1), (tok, lp)

            (_, last, last_lp, _), (toks, lp_toks) = jax.lax.scan(
                step, (cache, first, lp0, jnp.int32(p)), keys[1:])
        else:
            def step(carry, step_key):
                cache, tok, lp, pos, done = carry
                logits, cache2 = _forward_with_cache(cfg, params, cache,
                                                     tok[:, None], pos)
                m = done[None, :, None, None, None]
                cache = jax.tree.map(lambda old, new: jnp.where(m, old, new),
                                     cache, cache2)
                nxt = sample_logits(step_key, logits, temperature, top_k,
                                    top_p)
                # frozen rows emit FORCED eos, not a sample: logprob 0.0
                nlp = jnp.where(done, 0.0, token_logprob(cfg, logits, nxt))
                nxt = jnp.where(done, jnp.asarray(eos_id, nxt.dtype), nxt)
                return (cache, nxt, nlp, pos + 1,
                        done | (nxt == eos_id)), (tok, lp)

            done0 = first == eos_id
            (_, last, last_lp, _, _), (toks, lp_toks) = jax.lax.scan(
                step, (cache, first, lp0, jnp.int32(p), done0), keys[1:])
        lps = jnp.concatenate([jnp.moveaxis(lp_toks, 0, 1),
                               last_lp[:, None]], axis=1)

    new = jnp.concatenate([jnp.moveaxis(toks, 0, 1), last[:, None]], axis=1)
    out = jnp.concatenate([prompt, new.astype(prompt.dtype)], axis=1)
    res = (out,)
    if return_lengths:
        hit = new == eos_id
        lengths = jnp.where(hit.any(axis=1), jnp.argmax(hit, axis=1) + 1,
                            max_new_tokens).astype(jnp.int32)
        res = res + (lengths,)
    if return_logprobs:
        res = res + (lps,)
    return res if len(res) > 1 else out


def make_generate_fn(cfg: ModelConfig, max_new_tokens: int, *,
                     temperature: float = 0.0, top_k: Optional[int] = None,
                     top_p: Optional[float] = None,
                     max_len: Optional[int] = None,
                     eos_id: Optional[int] = None,
                     return_lengths: bool = False,
                     return_logprobs: bool = False):
    """Jitted (params, prompt, key) -> tokens closure over the static knobs."""
    fn = functools.partial(generate, cfg, max_new_tokens=max_new_tokens,
                           temperature=temperature, top_k=top_k, top_p=top_p,
                           max_len=max_len, eos_id=eos_id,
                           return_lengths=return_lengths,
                           return_logprobs=return_logprobs)
    return jax.jit(lambda params, prompt, key=None: fn(params, prompt, key=key))
