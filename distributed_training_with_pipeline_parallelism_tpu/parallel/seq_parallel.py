"""Sequence-parallel training path: full model over a 'seq' mesh axis.

Shards the *sequence* dimension of activations over a 'seq' mesh axis —
embeddings, LayerNorms and MLPs are position-wise (purely local), and the
attention core runs under one of two strategies, selected by ``attn_impl``:
``"ring"`` (K/V ppermute ring, :mod:`.ring_attention`) or ``"ulysses"``
(head-scatter/seq-gather all-to-all, :mod:`.ulysses`). Loss and grads are
exact either way: identical to the unsharded model up to float
associativity.

This is the long-context scaling story the reference lacks entirely
(SURVEY.md §5: fixed seq 128, no sequence parallelism of any kind). It
composes with data parallelism (add a 'data' axis) and is orthogonal to the
pipeline executor.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..models.transformer import ModelConfig
from ..ops.layers import (select_xent, embedding_apply,
                          layer_norm_apply, linear_apply, remat_layer,
                          rms_norm_apply)
from .mesh import SEQ_AXIS
from .pipeline import _shard_map
from .ring_attention import local_rope_angles, ring_mha_apply
from .ulysses import ulysses_mha_apply

Pytree = Any

ATTN_IMPLS = {"ring": ring_mha_apply, "ulysses": ulysses_mha_apply}


def sp_layer_apply(cfg: ModelConfig, params, h: jax.Array, axis_name: str,
                   rope_angles, attn_impl: str = "ring",
                   tp_axis: Optional[str] = None, tp_size: int = 1,
                   rng: Optional[jax.Array] = None,
                   sp_size: int = 1) -> jax.Array:
    """Sequence-sharded twin of ``models.transformer.layer_apply``.

    With ``tp_axis`` the block is additionally Megatron tensor-parallel
    (ring or, since round 5, Ulysses attention): weight leaves are local
    model-axis shards, norms replicated — the 4-D
    ``data x pipe x model x seq`` composition. Under Ulysses the local
    head shard must further divide by the seq-axis size.

    ``rng`` (train mode) enables dropout at the same sites (and with the
    same per-site streams) as the dense ``layer_apply``: residual and
    FFN-inner masks are the full-sequence masks' local slices
    (``sharded_dropout_apply`` over dim 1 with ``sp_size`` shards), and
    attention-prob masks ride Ulysses' post-scatter head blocks — so an sp
    run reproduces the unsharded masks exactly. Ring attention draws its
    attention-prob masks blockwise, keyed on (q-chunk, k-chunk) global
    coordinates (ring-step invariant; see
    :func:`..parallel.ring_attention.ring_attention`) — valid dropout with
    correct after-softmax semantics, though the mask layout is a function
    of the shard count rather than the unsharded oracle's."""
    from ..models.transformer import _ffn_out, _tp_in
    from ..ops.layers import sharded_dropout_apply

    sp_mha = ATTN_IMPLS[attn_impl]
    heads = cfg.n_heads // tp_size
    p = cfg.dropout if rng is not None else 0.0

    def site(i: int) -> Optional[jax.Array]:
        return None if rng is None else jax.random.fold_in(rng, i)

    def drop(x, i):
        """Residual/FFN dropout on a [b, s_local, ...] seq shard."""
        return sharded_dropout_apply(x, p, site(i), axis=axis_name,
                                     n_shards=sp_size, shard_dim=1)

    if cfg.arch == "ref_decoder":
        mem = h
        sa = sp_mha(params["self_attn"], h, h, heads, axis_name,
                    tp_axis=tp_axis, dropout_rate=p, dropout_rng=site(0))
        x = layer_norm_apply(params["ln1"], h + drop(sa, 1))
        ca = sp_mha(params["cross_attn"], x, mem, heads, axis_name,
                    tp_axis=tp_axis, dropout_rate=p, dropout_rng=site(2))
        x = layer_norm_apply(params["ln2"], x + drop(ca, 3))
        ff = _ffn_out(params["lin2"],
                      drop(jax.checkpoint(jax.nn.relu)(
                          linear_apply(params["lin1"],
                                       _tp_in(x, tp_axis))), 4),
                      tp_axis)
        return layer_norm_apply(params["ln3"], x + drop(ff, 5))
    if cfg.arch == "gpt2":
        a = layer_norm_apply(params["ln1"], h)
        attn = sp_mha(params["attn"], a, a, heads, axis_name,
                      causal=True, tp_axis=tp_axis, dropout_rate=p,
                      dropout_rng=site(0))
        h = h + drop(attn, 1)
        m = _tp_in(layer_norm_apply(params["ln2"], h), tp_axis)
        ff = _ffn_out(params["lin2"],
                      jax.checkpoint(jax.nn.gelu)(
                          linear_apply(params["lin1"], m)),
                      tp_axis)
        return h + drop(ff, 2)
    if cfg.arch == "llama":
        a = rms_norm_apply(params["rms1"], h, cfg.rms_eps)
        attn = sp_mha(params["attn"], a, a, heads, axis_name,
                      causal=True, rope_angles=rope_angles, tp_axis=tp_axis,
                      dropout_rate=p, dropout_rng=site(0),
                      window=cfg.sliding_window)
        h = h + drop(attn, 1)
        m = _tp_in(rms_norm_apply(params["rms2"], h, cfg.rms_eps), tp_axis)
        act = jax.nn.silu if cfg.mlp_act == "silu" else jax.nn.gelu
        ff = _ffn_out(params["w2"],
                      jax.checkpoint(lambda a, b: act(a) * b)(
                          linear_apply(params["w1"], m),
                          linear_apply(params["w3"], m)),
                      tp_axis)
        return h + drop(ff, 2)
    raise ValueError(f"unknown arch {cfg.arch!r}")


def sp_embed_apply(cfg: ModelConfig, embed, tokens: jax.Array,
                   axis_name: str, rng: Optional[jax.Array] = None,
                   sp_size: int = 1) -> jax.Array:
    """Sequence-sharded embed: token lookup plus (gpt2) the learned position
    rows offset by this shard's global position. Shared by the standalone
    sp loss and the pipeline executor's seq-sharded stages. ``rng`` applies
    GPT-2's embedding dropout with the full-sequence mask's local slice."""
    from ..ops.layers import sharded_dropout_apply
    x = embedding_apply(embed["tok"], tokens)
    if cfg.embed_scale:
        # Gemma scales embedding OUTPUTS by sqrt(dim) — position-wise, so
        # it applies unchanged to a sequence shard
        x = x * (cfg.dim ** 0.5)
    if cfg.arch == "gpt2":
        my = jax.lax.axis_index(axis_name)
        s_local = tokens.shape[1]
        x = x + jax.lax.dynamic_slice_in_dim(
            embed["pos"], my * s_local, s_local, axis=0)
        x = sharded_dropout_apply(x, cfg.dropout, rng, axis=axis_name,
                                  n_shards=sp_size, shard_dim=1)
    return x


def sp_body_apply(cfg: ModelConfig, layers, h: jax.Array, axis_name: str,
                  attn_impl: str = "ring", tp_axis: Optional[str] = None,
                  tp_size: int = 1, rng: Optional[jax.Array] = None,
                  layer_offset=0, sp_size: int = 1) -> jax.Array:
    """Sequence-sharded twin of ``models.transformer.body_apply``: scan the
    stacked layers with ring/Ulysses attention over ``axis_name``. ``rng``/
    ``layer_offset`` follow the dense body's convention: layer i folds
    ``layer_offset + i`` so masks key off the *global* layer index."""
    rope = (local_rope_angles(cfg, h.shape[1], axis_name)
            if cfg.arch == "llama" else None)
    n = jax.tree.leaves(layers)[0].shape[0]

    def step(carry, xs):
        layer_params, i = xs
        rng_l = (None if rng is None
                 else jax.random.fold_in(rng, layer_offset + i))
        return sp_layer_apply(cfg, layer_params, carry, axis_name, rope,
                              attn_impl=attn_impl, tp_axis=tp_axis,
                              tp_size=tp_size, rng=rng_l,
                              sp_size=sp_size), None

    if cfg.remat_layers:
        step = remat_layer(step, n)
    h, _ = jax.lax.scan(step, h, (layers, jnp.arange(n)))
    return h


def make_sp_loss_fn(cfg: ModelConfig, mesh: Mesh, attn_impl: str = "ring",
                    ) -> Callable[[Pytree, jax.Array, jax.Array], jax.Array]:
    """Sequence-parallel loss: ``(params, tokens, targets) -> scalar``.
    Differentiable — wrap in ``jax.value_and_grad`` (+jit) for training;
    shard_map's transpose rules turn the forward collectives into the
    matching backward collectives (reverse ring / inverse all-to-all).

    ``attn_impl``: ``"ring"`` (no cap on the parallel degree) or
    ``"ulysses"`` (requires ``n_heads % axis size == 0``)."""
    if attn_impl not in ATTN_IMPLS:
        raise ValueError(f"attn_impl must be one of {sorted(ATTN_IMPLS)}, "
                         f"got {attn_impl!r}")
    D = mesh.shape[SEQ_AXIS]

    def spmd_loss(params, tokens, targets):
        # tokens/targets arrive as [B, S/D] local chunks
        from ..models.transformer import head_apply
        from ..ops.layers import select_masked_xent_sum
        h = sp_embed_apply(cfg, params["embed"], tokens, SEQ_AXIS)
        h = h.astype(jnp.dtype(cfg.dtype))
        h = sp_body_apply(cfg, params["layers"], h, SEQ_AXIS,
                          attn_impl=attn_impl)
        # head (incl. the final norm and the tied-embedding vocab matmul
        # when cfg.tie_embeddings — the table rides in replicated, so its
        # head grad needs no extra collective beyond shard_map's psum)
        logits = head_apply(cfg, params["head"], h,
                            embed=params["embed"] if cfg.tie_embeddings
                            else None)
        if cfg.pad_token_id is not None:
            # ignore-index masking, globally normalized: per-shard masked
            # NLL sums and valid counts psum over 'seq' so the result is
            # total_nll / global_valid_count — NOT a mean of per-shard
            # means, which would overweight shards rich in pad tokens
            # (mirrors the pipeline executor's global_pad_scale)
            s, n = select_masked_xent_sum(cfg.use_fused_xent)(
                logits, targets, cfg.pad_token_id)
            s = jax.lax.psum(s, SEQ_AXIS)
            n = jax.lax.psum(n.astype(jnp.float32), SEQ_AXIS)
            return s / jnp.maximum(n, 1.0)
        local = select_xent(cfg.use_fused_xent)(logits, targets)  # mean over local tokens
        return jax.lax.psum(local, SEQ_AXIS) / D  # equal chunks -> global mean

    return _shard_map(
        spmd_loss, mesh,
        in_specs=(P(), P(None, SEQ_AXIS), P(None, SEQ_AXIS)),
        out_specs=P(),
    )
