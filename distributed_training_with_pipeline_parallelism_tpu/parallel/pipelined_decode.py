"""Autoregressive decoding over a pipeline mesh (round 4, VERDICT r3 item 8).

The reference has no inference path at all; this closes the last mesh gap
of this framework's own inference story — training meshes slice a model
depth-wise over 'pipe', and now decode runs on that same slicing (until
round 4 only ``make_pipeline_forward``'s batch-scoring path was
pipelined; the token-by-token decode loop was single-device/TP only).

Naively pipelining a one-token decode step runs at 1/D utilization by
construction: each step's compute is a sliver with a strict
stage-(d+1)-after-stage-d dependency. The executor here instead
round-robins ``M >= D`` INDEPENDENT batch streams through the stages —
the decode-time analog of training microbatches:

- tick u, device d works on stream ``(u - d) mod M``: in steady state
  every stage is busy every tick, on a [B/M, 1, dim] sliver of a
  different stream.
- the sampled token needs to travel stage D-1 -> stage 0 for its
  stream's next round; on a ring that hop IS the +1 permute, so one
  ``ppermute`` carries both payloads each tick — hidden states d -> d+1
  and tokens D-1 -> 0. No second collective, no host round-trip.
- stream g re-enters stage 0 at tick ``g + e*M`` (its round-e token
  arrived at ``g + (e-1)*M + D``), which is why ``M >= D`` is required
  for a stall-free schedule.
- each device holds the KV cache for ITS layer slice only
  ``[lps, B, max_len, Hkv, hd]`` — the model is depth-split at decode
  exactly as it is at training, so a model that only fits sharded can
  still generate. Warmup/drain ticks take a ``lax.cond`` noop branch,
  so inactive devices never touch their caches.

Prefill is the same round-robin over whole prompts (a fill-drain pass,
M + D - 1 ticks, Python-unrolled), writing each stage's prompt KV and
sampling every stream's first token on the last stage.

Sampling semantics, cache layout and the per-layer math are shared with
:mod:`..models.generate` (``layers_with_cache`` / ``sample_logits``), so
pipelined greedy decode emits exactly the single-device tokens
(tests/test_pipelined_decode.py).

The whole-prompt prefill pass runs with ``prefill=True`` — offset is
statically zero and every stage's cache is fresh, so the blocks route
attention through the Pallas flash kernel under the training path's
``cfg.flash_for`` fallback discipline (``ops.pallas_attention``); decode
ticks (s=1, traced offsets) and the serving engine's chunked prefill
stay on the cached dense path. ``return_logprobs`` likewise reuses the
training loss's kernel dispatch (``cfg.use_fused_xent`` ->
``ops.pallas_xent``) for the emitted tokens' log-probabilities.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..models.generate import (_embed_at, init_cache, layers_with_cache,
                               rope_slice_at, sample_logits)
from ..models.transformer import compute_cast, head_apply
from ..utils.config import ModelConfig
from .mesh import MODEL_AXIS, PIPE_AXIS
from .pipeline import (_check_tp_divisibility, _dense_layer_specs,
                       _shard_map, stack_stage_layers)


def _slot_cache_apply(cfg: ModelConfig, layers_d, h, kc, vc, g, n_rows: int,
                      offset, s: int, *, tp_axis: Optional[str] = None,
                      tp_size: int = 1, live_rows=None,
                      prefill: bool = False):
    """One stage's layer slice on ``h`` [n_rows, s, dim] for slot/stream
    ``g``: slice that slot's cache rows (``g*n_rows .. (g+1)*n_rows``),
    run the blocks, write the new k/v back.

    ``live_rows`` (optional [n_rows] bool) masks the cache write-back per
    batch row — frozen rows (EOS-finished streams, retired serving slots)
    keep their previous k/v bit-for-bit, so completed requests stop
    mutating state without changing any shape. Shared by the static
    round-robin decoder below and the continuous-batching serving
    executor (:mod:`..serving.engine`).

    ``prefill=True`` marks statically-zero-offset fresh-cache calls
    (the round-robin decoder's whole-prompt prefill) flash-eligible —
    the blocks then route attention through the Pallas kernel under the
    training path's ``cfg.flash_for`` fallback discipline. The serving
    engine's chunked prefill consumes TRACED offsets and must keep the
    default dense path (see :func:`..models.generate._layer_step`)."""
    kg = jax.lax.dynamic_slice_in_dim(kc, g * n_rows, n_rows, axis=1)
    vg = jax.lax.dynamic_slice_in_dim(vc, g * n_rows, n_rows, axis=1)
    rope = rope_slice_at(cfg, kc.shape[2], offset, s)
    h, (kg2, vg2) = layers_with_cache(cfg, layers_d, h, kg, vg, offset, rope,
                                      tp_axis=tp_axis, tp_size=tp_size,
                                      prefill=prefill)
    if live_rows is not None:
        m = live_rows[None, :, None, None, None]
        kg2 = jnp.where(m, kg2, kg)
        vg2 = jnp.where(m, vg2, vg)
    kc = jax.lax.dynamic_update_slice_in_dim(kc, kg2, g * n_rows, axis=1)
    vc = jax.lax.dynamic_update_slice_in_dim(vc, vg2, g * n_rows, axis=1)
    return h, kc, vc


def _head_token(cfg: ModelConfig, head_c, embed_c, y_last, key, *,
                temperature: float = 0.0, top_k: Optional[int] = None,
                top_p: Optional[float] = None, tp_axis: Optional[str] = None,
                tp_size: int = 1, vocab_parallel: bool = False,
                return_logprobs: bool = False):
    """Next-token ids [B] from the last-position hidden ``y_last``
    [B, 1, dim] — the last-stage head of both decode executors (the
    caller conds on its stage index so other stages skip the vocab
    matmul entirely).

    Greedy under TP goes vocab-parallel when ``vocab_parallel``: each
    model rank reads only its V/T column slice of the head weight (the
    O(dim*V) head read is often the largest weight in a decode tick —
    replicating it would cap the TP speedup well below T) and the argmax
    merges via a [T, B] all_gather of per-shard (max, argmax) pairs.
    First-max-wins on both levels reproduces the global argmax tie-break
    (lowest index) exactly. Sampling keeps the replicated head: top-k /
    top-p need globally truncated logits.

    ``return_logprobs`` (replicated head only — the caller disables the
    vocab-parallel fast path) additionally returns the sampled token's
    log-probability [B] f32 via :func:`..models.generate.token_logprob`
    (``cfg.use_fused_xent`` -> the Pallas fused-NLL kernel)."""
    if not vocab_parallel:
        logits = head_apply(cfg, head_c, y_last, embed=embed_c)[:, 0]
        tok = sample_logits(key, logits, temperature, top_k,
                            top_p).astype(jnp.int32)
        if return_logprobs:
            from ..models.generate import token_logprob
            return tok, token_logprob(cfg, logits, tok)
        return tok
    if return_logprobs:
        raise ValueError("return_logprobs needs the replicated head "
                         "(full logits); vocab_parallel must be off")
    from ..models.transformer import head_norm_apply
    t = jax.lax.axis_index(tp_axis)
    Vl = cfg.vocab_size // tp_size
    hn = head_norm_apply(cfg, head_c, y_last)[:, 0]  # [B, dim]
    if cfg.tie_embeddings:
        wsl = jax.lax.dynamic_slice_in_dim(
            embed_c["tok"], t * Vl, Vl, axis=0)  # [Vl, dim]
        logits_l = hn @ wsl.T
    else:
        wsl = jax.lax.dynamic_slice_in_dim(
            head_c["out"]["w"], t * Vl, Vl, axis=1)
        logits_l = hn @ wsl  # gpt2/llama heads carry no bias
    val = jnp.max(logits_l, axis=-1)
    idx = jnp.argmax(logits_l, axis=-1) + t * Vl
    vals = jax.lax.all_gather(val, tp_axis)  # [T, B]
    idxs = jax.lax.all_gather(idx, tp_axis)
    win = jnp.argmax(vals, axis=0)
    return jnp.take_along_axis(idxs, win[None], axis=0)[0].astype(jnp.int32)


def spec_accept_len(drafts, targets):
    """Longest-matching-prefix acceptance for greedy speculative decoding
    (serving.engine's verify step; Leviathan et al., arXiv:2211.17192).

    ``drafts`` [gamma]: the draft model's proposed tokens. ``targets``
    [>= gamma]: the target model's per-row argmaxes over the verify
    chunk, where row ``i`` conditions on the context *through draft
    ``i``* — so ``targets[i]`` is what greedy decoding would emit after
    accepting ``drafts[:i+1]``... but also, crucially, ``targets[i-1]``
    is what it emits after ``drafts[:i]``, which is why draft ``i`` is
    acceptable iff ``drafts[i] == targets[i-1]`` with ``targets[-1]``
    read as the free token row 0 yields. Returns ``n_accepted = 1 +
    run-length of the matching prefix`` in ``[1, gamma+1]`` — bit-exact
    greedy by construction: the first mismatch row's own argmax is the
    token greedy would have emitted, and it rides the tok channel as
    ``targets[n_accepted - 1]``. Traceable (jnp) and numpy-compatible,
    so the unit tests run it directly on host arrays."""
    drafts = jnp.asarray(drafts)
    g = drafts.shape[0]
    hit = jnp.cumprod(
        (drafts == jnp.asarray(targets)[:g]).astype(jnp.int32))
    return 1 + hit.sum()


def make_pipeline_generate_fn(cfg: ModelConfig, mesh: Mesh,
                              max_new_tokens: int, *,
                              n_streams: Optional[int] = None,
                              temperature: float = 0.0,
                              top_k: Optional[int] = None,
                              top_p: Optional[float] = None,
                              max_len: Optional[int] = None,
                              eos_id: Optional[int] = None,
                              return_lengths: bool = False,
                              return_logprobs: bool = False):
    """Build a jitted ``(params, prompt[, key]) -> tokens [B, P+N]``
    decoder over ``mesh``'s 'pipe' axis.

    ``return_logprobs=True`` appends the emitted tokens' log-probs
    [B, N] f32 to the result — computed on the last stage from the same
    logits each token was sampled from (``cfg.use_fused_xent`` routes
    the Pallas fused-NLL kernel, the training loss's dispatch), ridden
    home on the same ring hop as the token, banked next to it on stage
    0. EOS-frozen rows report 0.0 for forced tokens. Disables the
    vocab-parallel greedy head (logprobs need full logits). Matches the
    single-device ``generate(..., return_logprobs=True)`` row for row.

    ``eos_id`` makes decoding EOS-aware: once a request emits ``eos_id``
    its stream freezes — subsequent banked tokens are forced to
    ``eos_id`` and every stage masks that request's KV-cache writes (a
    live-row mask rides the same ring hop as the data, so jit shapes
    never change), and a stream whose requests have ALL finished skips
    its stage compute entirely instead of burning ticks to
    ``max_new_tokens``. With ``return_lengths=True`` (requires
    ``eos_id``) the decoder returns ``(tokens [B, P+N], lengths [B])``
    where ``lengths`` counts emitted tokens per request including the
    EOS itself.

    ``params`` is the full-model pytree (stage slicing happens inside,
    via the training executor's ``stack_stage_layers``); ``prompt`` is
    [B, P] with uniform length P and ``B`` divisible by ``n_streams``
    (default: the pipe degree D). Greedy when ``temperature == 0``;
    sampling knobs match :func:`..models.generate.sample_logits`.

    A 'model' mesh axis (round 5) composes Megatron TP inside each
    stage: layer weights are model-axis shards (the training executor's
    stacked specs), each model rank caches only its kv-head shard, and
    the o/down projections psum per layer — decode is weight-read bound
    at small batch, so TP splits exactly the bandwidth that limits it.
    The KV cache stays stage-sliced over 'pipe' as before. Seq/expert
    axes remain unsupported here.
    """
    from ..models.nemotron_h import not_served
    not_served("parallel/pipelined_decode.py", cfg)
    if cfg.arch not in ("gpt2", "llama"):
        raise ValueError(
            f"generation is undefined for arch {cfg.arch!r} (see "
            "models.generate)")
    D = mesh.shape[PIPE_AXIS]
    T = mesh.shape.get(MODEL_AXIS, 1)
    for ax, n in mesh.shape.items():
        if ax not in (PIPE_AXIS, MODEL_AXIS) and n > 1:
            raise NotImplementedError(
                f"pipelined decode composes pipe x model meshes; axis "
                f"{ax!r} has size {n} (batch scoring via "
                "make_pipeline_forward supports the full mesh space)")
    _check_tp_divisibility(cfg, T)
    tp_axis = MODEL_AXIS if T > 1 else None
    if cfg.n_layers % D:
        raise ValueError(f"n_layers={cfg.n_layers} must divide over {D} "
                         "stages")
    M = n_streams or D
    if M < D:
        raise ValueError(f"n_streams={M} must be >= the pipe degree {D} "
                         "(fewer streams than stages stalls the ring)")
    N = max_new_tokens
    if N < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {N}")
    if return_lengths and eos_id is None:
        raise ValueError("return_lengths=True requires an eos_id (without "
                         "one every stream emits exactly max_new_tokens)")
    if temperature != 0.0:
        need_key = True
    else:
        need_key = False
    want_lp = return_logprobs

    def spmd(layers_stacked, embed, head, prompt, key_data):
        d = jax.lax.axis_index(PIPE_AXIS)
        layers_d = jax.tree.map(lambda x: x[0, 0], layers_stacked)  # [lps,..]
        layers_d = compute_cast(cfg, layers_d)
        embed_c = compute_cast(cfg, embed)
        head_c = compute_cast(cfg, head)
        B, Pp = prompt.shape
        Bg = B // M
        total = Pp + N
        mlen = max_len or total
        lps = cfg.n_layers // D
        # under TP each model rank caches only ITS kv-head shard
        n_kv = (cfg.n_kv_heads or cfg.n_heads) // T
        kc = jnp.zeros((lps, B, mlen, n_kv, cfg.head_dim),
                       jnp.dtype(cfg.dtype))
        vc = kc
        prompt_g = prompt.reshape(M, Bg, Pp)
        base_key = jax.random.wrap_key_data(key_data)

        perm = [(i, (i + 1) % D) for i in range(D)]

        def ring(tree):
            return jax.tree.map(
                lambda x: jax.lax.ppermute(x, PIPE_AXIS, perm), tree)

        def stage_apply(h, kc, vc, g, offset, s, live_rows=None,
                        prefill=False):
            """This device's layer slice on [Bg, s, dim] for stream g
            (shared :func:`_slot_cache_apply`; ``live_rows`` masks cache
            writes of EOS-frozen requests; ``prefill`` flags the
            statically-zero-offset whole-prompt pass flash-eligible)."""
            return _slot_cache_apply(cfg, layers_d, h, kc, vc, g, Bg,
                                     offset, s, tp_axis=tp_axis, tp_size=T,
                                     live_rows=live_rows, prefill=prefill)

        # ------------------------------------------------------------------
        # prefill: fill-drain over whole prompts, M + D ticks (the +1 tick
        # delivers the last stream's first token back to stage 0)
        # ------------------------------------------------------------------
        h_chan = jnp.zeros((Bg, Pp, cfg.dim), jnp.dtype(cfg.dtype))
        tok_chan = jnp.zeros((Bg,), jnp.int32)
        token_buf = jnp.zeros((M, Bg), jnp.int32)
        out_buf = jnp.zeros((N, M, Bg), jnp.int32)
        # token logprobs ride/bank exactly like the tokens themselves
        lp_chan = jnp.zeros((Bg,), jnp.float32) if want_lp else None
        lp_buf = jnp.zeros((N, M, Bg), jnp.float32) if want_lp else None
        # EOS bookkeeping lives on stage 0 only (it banks every token);
        # stages d > 0 learn liveness from the mask riding the ring. All
        # of it is gated at Python level so the eos_id=None jaxpr is
        # unchanged.
        use_eos = eos_id is not None
        done = jnp.zeros((M, Bg), bool) if use_eos else None

        vocab_parallel_head = (tp_axis is not None and not need_key
                               and cfg.vocab_size % T == 0 and not want_lp)

        def head_sample(y_last, g, e):
            """Last stage only: logits + sample via the shared
            :func:`_head_token` (vocab-parallel greedy under TP); other
            stages skip the vocab matmul entirely. With ``want_lp`` the
            pair (tok, logprob) comes back instead of the bare token."""
            def live():
                key = (jax.random.fold_in(jax.random.fold_in(base_key, e), g)
                       if need_key else None)
                return _head_token(cfg, head_c, embed_c, y_last, key,
                                   temperature=temperature, top_k=top_k,
                                   top_p=top_p, tp_axis=tp_axis, tp_size=T,
                                   vocab_parallel=vocab_parallel_head,
                                   return_logprobs=want_lp)

            if want_lp:
                return jax.lax.cond(
                    d == D - 1, live,
                    lambda: (jnp.zeros((Bg,), jnp.int32),
                             jnp.zeros((Bg,), jnp.float32)))
            return jax.lax.cond(d == D - 1, live,
                                lambda: jnp.zeros((Bg,), jnp.int32))

        for t in range(M + D):
            # bank last tick's token arrival (stage 0 only)
            wp = t - D  # prefill stream whose first token arrives now
            if 0 <= wp < M:
                is_d0 = d == 0
                token_buf = jnp.where(is_d0,
                                      token_buf.at[wp].set(tok_chan),
                                      token_buf)
                out_buf = jnp.where(is_d0, out_buf.at[0, wp].set(tok_chan),
                                    out_buf)
                if want_lp:  # the first token is always genuinely sampled
                    lp_buf = jnp.where(is_d0, lp_buf.at[0, wp].set(lp_chan),
                                       lp_buf)
                if use_eos:  # a prompt may yield EOS as its FIRST token
                    done = jnp.where(is_d0,
                                     done.at[wp].set(tok_chan == eos_id),
                                     done)
            w = t - d  # this device's active stream this tick
            active = (w >= 0) & (w < M)
            g = jnp.clip(w, 0, M - 1)

            def unit(op):
                kc, vc = op
                x = jnp.where(d == 0,
                              _embed_at(cfg, embed_c, prompt_g[g],
                                        jnp.int32(0)).astype(h_chan.dtype),
                              h_chan)
                y, kc, vc = stage_apply(x, kc, vc, g, jnp.int32(0), Pp,
                                        prefill=True)
                if want_lp:
                    tok, lp = head_sample(y[:, -1:], g, 0)
                    return (kc, vc), y, tok, lp
                tok = head_sample(y[:, -1:], g, 0)
                return (kc, vc), y, tok

            def noop(op):
                z = (op, jnp.zeros_like(h_chan), jnp.zeros((Bg,), jnp.int32))
                return z + (jnp.zeros((Bg,), jnp.float32),) if want_lp else z

            # one ring carries everything: h for d < D-1, token (and its
            # logprob) for d == D-1
            if want_lp:
                (kc, vc), y, tok, lp = jax.lax.cond(active, unit, noop,
                                                    (kc, vc))
                h_chan, tok_chan, lp_chan = ring((y, tok, lp))
            else:
                (kc, vc), y, tok = jax.lax.cond(active, unit, noop, (kc, vc))
                h_chan, tok_chan = ring((y, tok))

        # ------------------------------------------------------------------
        # decode: lax.scan over M*(N-1) + D round-robin ticks (the last
        # tick does no compute — it exists only to bank the final
        # stage-(D-1) -> 0 token arrival)
        # ------------------------------------------------------------------
        h1 = jnp.zeros((Bg, 1, cfg.dim), jnp.dtype(cfg.dtype))

        def tick(carry, u):
            # carry layout: 6 fixed slots, then (done, lives_chan) when
            # EOS-aware, then (lp_buf, lp_chan) when logprobs ride along
            h_chan, tok_chan, kc, vc, token_buf, out_buf = carry[:6]
            i = 6
            if use_eos:
                done, lives_chan = carry[i:i + 2]
                i += 2
            else:
                done = lives_chan = None
            if want_lp:
                lp_buf, lp_chan = carry[i:i + 2]
            else:
                lp_buf = lp_chan = None
            # bank the arrival from tick u-1 (which left the last stage at
            # entry index (u - D) // M, producing output token index +1)
            wa = u - D
            ga = jnp.clip(wa % M, 0, M - 1)
            ia = jnp.clip(wa // M + 1, 0, N - 1)
            bank = (wa >= 0) & (d == 0)
            # finished rows emit forced EOS from then on; the garbage the
            # skipped/frozen compute produced never reaches the output
            tok_eff = (jnp.where(done[ga], jnp.int32(eos_id), tok_chan)
                       if use_eos else tok_chan)
            token_buf = jnp.where(bank, token_buf.at[ga].set(tok_eff),
                                  token_buf)
            out_buf = jnp.where(bank, out_buf.at[ia, ga].set(tok_eff),
                                out_buf)
            if want_lp:
                # forced-EOS rows bank 0.0 (not sampled), same rule as the
                # single-device generate; `done` is still pre-update here
                lp_eff = (jnp.where(done[ga], 0.0, lp_chan) if use_eos
                          else lp_chan)
                lp_buf = jnp.where(bank, lp_buf.at[ia, ga].set(lp_eff),
                                   lp_buf)
            if use_eos:
                done = jnp.where(
                    bank, done.at[ga].set(done[ga] | (tok_eff == eos_id)),
                    done)

            w = u - d
            active = (w >= 0) & (w < M * (N - 1))
            g = jnp.clip(w % M, 0, M - 1)
            e = jnp.clip(w // M, 0, max(N - 2, 0))  # entry index
            pos = Pp + e  # the consumed token's global position

            if use_eos:
                # banking above ran first, so in the M == D case where a
                # stream's token arrives and is consumed in the same tick,
                # `done` already reflects it. Stage 0 reads its own table;
                # later stages reuse the mask that rode in with the data.
                lives = jnp.where(d == 0, ~done[g], lives_chan)
                # a stream whose rows ALL hit EOS skips its stage compute
                # entirely — that's the satellite's "stop burning ticks"
                active = active & jnp.any(lives)
            else:
                lives = None

            def unit(op):
                kc, vc = op
                x = jnp.where(d == 0,
                              _embed_at(cfg, embed_c, token_buf[g][:, None],
                                        pos).astype(h1.dtype),
                              h_chan)
                y, kc, vc = stage_apply(x, kc, vc, g, pos, 1, live_rows=lives)
                if want_lp:
                    tok, lp = head_sample(y, g, e + 1)
                    return (kc, vc), y, tok, lp
                tok = head_sample(y, g, e + 1)
                return (kc, vc), y, tok

            def noop(op):
                z = (op, jnp.zeros_like(h1), jnp.zeros((Bg,), jnp.int32))
                return z + (jnp.zeros((Bg,), jnp.float32),) if want_lp else z

            if want_lp:
                (kc, vc), y, tok, lp = jax.lax.cond(active, unit, noop,
                                                    (kc, vc))
            else:
                (kc, vc), y, tok = jax.lax.cond(active, unit, noop, (kc, vc))
                lp = None
            payload = [y, tok]
            if use_eos:
                payload.append(lives & active)
            if want_lp:
                payload.append(lp)
            ringed = ring(tuple(payload))
            h_chan, tok_chan = ringed[0], ringed[1]
            j = 2
            if use_eos:
                lives_chan = ringed[j]
                j += 1
            if want_lp:
                lp_chan = ringed[j]
            out = (h_chan, tok_chan, kc, vc, token_buf, out_buf)
            if use_eos:
                out = out + (done, lives_chan)
            if want_lp:
                out = out + (lp_buf, lp_chan)
            return out, None

        T_dec = M * (N - 1) + D
        if T_dec > 0 and N > 1:
            carry0 = (h1, tok_chan, kc, vc, token_buf, out_buf)
            if use_eos:
                carry0 = carry0 + (done, jnp.zeros((Bg,), bool))
            if want_lp:
                carry0 = carry0 + (lp_buf, lp_chan)
            carry, _ = jax.lax.scan(tick, carry0, jnp.arange(T_dec))
            token_buf, out_buf = carry[4], carry[5]
            if want_lp:
                lp_buf = carry[6 + (2 if use_eos else 0)]

        # outputs live on device 0; psum replicates across the pipe ring
        out = jax.lax.psum(jnp.where(d == 0, out_buf, 0), PIPE_AXIS)
        # [N, M, Bg] -> [B, N]
        toks = jnp.moveaxis(out, 0, -1).reshape(B, N)
        if want_lp:
            lpo = jax.lax.psum(jnp.where(d == 0, lp_buf, 0.0), PIPE_AXIS)
            lps = jnp.moveaxis(lpo, 0, -1).reshape(B, N)
        if not use_eos:
            return (toks, lps) if want_lp else toks
        hit = toks == eos_id
        lengths = jnp.where(hit.any(axis=1), jnp.argmax(hit, axis=1) + 1,
                            N).astype(jnp.int32)
        return (toks, lengths, lps) if want_lp else (toks, lengths)

    # layers: 'pipe' on the stage dim, plus Megatron 'model' dims when a
    # model axis is present (same stacked-layout specs as the training
    # executor, so a pp x tp-trained pytree decodes in-place)
    layer_spec = (_dense_layer_specs(cfg, T, None) if T > 1
                  else P(PIPE_AXIS))
    sharded = _shard_map(
        spmd, mesh,
        in_specs=(layer_spec, P(), P(), P(), P()),
        out_specs=P(),
    )

    @jax.jit
    def _gen(params, prompt, key_data):
        with jax.named_scope("decode/stack"):
            stacked = stack_stage_layers(params["layers"], D, 1)
        with jax.named_scope("decode/pipeline"):
            res = sharded(stacked, params["embed"], params["head"], prompt,
                          key_data)
        # spmd returns toks[, lengths when eos-aware][, logprobs]
        new = res[0] if (eos_id is not None or want_lp) else res
        toks = jnp.concatenate([prompt, new.astype(prompt.dtype)], axis=1)
        outs = (toks,)
        if return_lengths:
            outs = outs + (res[1],)
        if want_lp:
            outs = outs + (res[-1],)
        return outs if len(outs) > 1 else toks

    def gen(params, prompt, key=None):
        # precondition checks run OUTSIDE jit so violations surface as
        # plain ValueErrors at the call site, not mid-trace
        B, Pp = prompt.shape
        if B % M:
            raise ValueError(
                f"batch {B} is not divisible by n_streams={M}; each "
                "round-robin stream carries B/M requests, so pad the batch "
                "or pick n_streams dividing it")
        total = Pp + N
        mlen = max_len or total
        if total > mlen:
            raise ValueError(f"prompt ({Pp}) + max_new_tokens ({N}) "
                             f"exceeds max_len ({mlen})")
        if cfg.arch == "gpt2" and total > cfg.max_seq_len:
            raise ValueError(f"prompt ({Pp}) + max_new_tokens ({N}) "
                             f"exceeds the gpt2 position table "
                             f"(max_seq_len={cfg.max_seq_len})")
        if need_key and key is None:
            raise ValueError("sampling (temperature != 0) requires a PRNG "
                             "key")
        key = key if key is not None else jax.random.key(0)
        return _gen(params, prompt, jax.random.key_data(key))

    return gen
