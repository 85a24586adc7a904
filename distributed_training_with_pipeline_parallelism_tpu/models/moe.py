"""Mixture-of-Experts feed-forward layers and a MoE decoder LM — the OLDER,
capacity-routed one.

Two expert layers live in this repo. This module is the first: softmax
top-k with a per-expert capacity that DROPS tokens past it, dense one-hot
dispatch (``route()``'s ``[T, k, E, C]`` tensor does not fit a chip at real
sizes), a load-balancing auxiliary loss, its own decoder LM (``moe_lm_*``)
and a ``moe=`` argument through the executors. The newer layer,
:mod:`..ops.experts` (the ``E`` layers of ``arch="nemotron_h"``, PR 30),
uses NONE of this module's routing: sigmoid scores over the router's whole
width, top-k, nothing dispatched and so nothing dropped (every held expert
on every token, gated), one expert-parallel rank's share, on the normal path
with no ``moe=``; its expert function comes in two forms, relu^2 (two
matrices) and the gated silu one (three; PR 34), where this module's experts
are a gelu MLP. This module and ``parallel/expert_parallel.py`` stay
until that layer has its exchange across chips (ROADMAP R1, D7).


The reference is dense-FFN only (SURVEY.md §2.4, EP row: "NO — dense FFN
only (`nn.TransformerDecoderLayer`)"), so this module is beyond-parity
capability. The design is TPU-first throughout:

- **Capacity-based routing** (GShard, arXiv:2006.16668; Switch,
  arXiv:2101.03961): every shape is static under jit. Each expert processes
  exactly ``capacity`` token slots; dispatch and combine are dense one-hot
  tensors so the whole layer is four einsums that tile onto the MXU —
  no gather/scatter, no dynamic shapes, no host control flow.
- **Top-k token-choice gating** with per-slot priority: slot-0 assignments
  of all tokens beat slot-1 assignments, positions within an expert queue
  come from a cumulative sum, and tokens past capacity are dropped (their
  combine weight is zero — the residual stream carries them unchanged).
- **Load-balancing auxiliary loss** (Switch §2.2): ``E * Σ_e f_e · p_e``
  where ``f_e`` is the fraction of tokens whose top-1 choice is expert e
  and ``p_e`` the mean router probability — minimized (=1) at uniform load.
- **Expert parallelism**: pass ``axis_name`` to run with experts sharded
  over a mesh axis; token slots travel to their experts and back via two
  ``jax.lax.all_to_all`` collectives (see
  :mod:`..parallel.expert_parallel`). With ``axis_name=None`` the same
  math runs unsharded — the correctness oracle the EP path is tested
  against.

The router always computes in float32 (bf16 softmax over experts is the
classic MoE instability).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import mha_apply, mha_init
from ..ops.layers import (select_xent, embedding_apply, embedding_init,
                          layer_norm_apply, layer_norm_init, linear_apply,
                          linear_init)
from ..utils.config import ModelConfig


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Routing hyperparameters for MoE FFN layers.

    ``capacity_factor`` scales each expert's token-slot budget
    ``C = ceil(top_k * T * capacity_factor / n_experts)``; set it to
    ``n_experts`` to guarantee zero drops (used by the EP-vs-dense
    equivalence tests). ``ffn_dim=None`` inherits the model's dense
    ``ffn_dim``.
    """

    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    ffn_dim: Optional[int] = None

    def __post_init__(self):
        if self.top_k < 1 or self.top_k > self.n_experts:
            raise ValueError(f"top_k={self.top_k} must be in [1, {self.n_experts}]")

    def capacity(self, n_tokens: int) -> int:
        return max(1, math.ceil(self.top_k * n_tokens * self.capacity_factor
                                / self.n_experts))


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------


def route(probs: jax.Array, top_k: int, capacity: int,
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Token-choice top-k routing with per-expert capacity.

    probs: [T, E] router probabilities (float32). Returns
    ``(dispatch, combine, aux)`` where dispatch/combine are [T, E, C]
    (dispatch is combine's nonzero indicator; combine carries renormalized
    gate weights) and ``aux`` is the Switch load-balancing scalar.
    """
    T, E = probs.shape
    gate, idx = jax.lax.top_k(probs, top_k)  # [T, k]
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(idx, E, dtype=probs.dtype)  # [T, k, E]
    # Queue positions: priority is (slot, token) lexicographic — every
    # token's first choice outranks any token's second choice.
    flat = onehot.transpose(1, 0, 2).reshape(top_k * T, E)
    pos = jnp.cumsum(flat, axis=0) - flat
    pos = pos.reshape(top_k, T, E).transpose(1, 0, 2)  # [T, k, E]
    keep = onehot * (pos < capacity)
    pos_onehot = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                                dtype=probs.dtype)  # [T, k, E, C]
    combine = jnp.einsum("tk,tke,tkec->tec", gate, keep, pos_onehot)
    dispatch = (combine > 0).astype(probs.dtype)
    top1 = onehot[:, 0]  # [T, E]
    aux = E * jnp.sum(jnp.mean(top1, axis=0) * jnp.mean(probs, axis=0))
    return dispatch, combine, aux


# ---------------------------------------------------------------------------
# MoE FFN layer
# ---------------------------------------------------------------------------


def moe_ffn_init(key: jax.Array, dim: int, ffn_dim: int, n_experts: int) -> Dict:
    kr, k1, k2 = jax.random.split(key, 3)
    b1 = 1.0 / math.sqrt(dim)
    b2 = 1.0 / math.sqrt(ffn_dim)
    return {
        "router": {"w": jax.random.uniform(kr, (dim, n_experts),
                                           minval=-b1, maxval=b1)},
        "w1": jax.random.uniform(k1, (n_experts, dim, ffn_dim),
                                 minval=-b1, maxval=b1),
        "b1": jnp.zeros((n_experts, ffn_dim)),
        "w2": jax.random.uniform(k2, (n_experts, ffn_dim, dim),
                                 minval=-b2, maxval=b2),
        "b2": jnp.zeros((n_experts, dim)),
    }


def _expert_mlp(params: Dict, x: jax.Array,
                tp_axis: Optional[str] = None) -> jax.Array:
    """Per-expert gelu MLP on [E_local, N, d] slot blocks (batched einsums).

    With ``tp_axis`` the expert matrices are Megatron-split over that mesh
    axis — w1/b1 column-parallel on the ffn dim, w2 row-parallel with one
    psum completing the partial outputs and b2 (replicated) added once —
    exactly the dense ``lin1``/``lin2`` pattern, batched over experts."""
    if tp_axis is not None:
        from ..ops.collectives import tp_copy, tp_reduce
        x = tp_copy(x, tp_axis)
    h = jnp.einsum("end,edf->enf", x, params["w1"]) + params["b1"][:, None]
    out = jnp.einsum("enf,efd->end", jax.nn.gelu(h), params["w2"])
    if tp_axis is not None:
        out = tp_reduce(out, tp_axis)
    return out + params["b2"][:, None]


def moe_ffn_apply(params: Dict, x: jax.Array, moe: MoEConfig,
                  axis_name: Optional[str] = None,
                  tp_axis: Optional[str] = None,
                  ) -> Tuple[jax.Array, jax.Array]:
    """MoE FFN on [B, S, d] activations -> ([B, S, d], aux loss scalar).

    With ``axis_name`` set (inside shard_map), experts are sharded over that
    mesh axis (leading expert dim of w1/b1/w2/b2 is the local shard) and
    token slots route through two ``all_to_all`` hops:

        dispatch [E, C, d] -> a2a -> local experts on [G, D*C, d] -> a2a back

    Tokens (the batch) are sharded over the same axis, so routing state
    (dispatch/combine/capacity) is per-shard — standard local load balancing.
    """
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    E = moe.n_experts
    logits = xt.astype(jnp.float32) @ params["router"]["w"].astype(jnp.float32)
    C = moe.capacity(B * S)
    dispatch, combine, aux = route(jax.nn.softmax(logits, axis=-1),
                                   moe.top_k, C)
    dispatch = dispatch.astype(x.dtype)
    combine = combine.astype(x.dtype)

    slots = jnp.einsum("tec,td->ecd", dispatch, xt)  # [E, C, d]
    if axis_name is None:
        if params["w1"].shape[0] != E:
            raise ValueError(
                f"params hold {params['w1'].shape[0]} experts, config says {E} "
                f"(running an expert-sharded pytree without axis_name?)")
        out = _expert_mlp(params, slots, tp_axis)  # [E, C, d]
    else:
        D = jax.lax.psum(1, axis_name)
        G = params["w1"].shape[0]  # local experts
        if G * D != E:
            raise ValueError(f"{G} local experts x {D} shards != {E}")
        send = slots.reshape(D, G, C, d)
        recv = jax.lax.all_to_all(send, axis_name, 0, 0)  # [D_src, G, C, d]
        hid = recv.transpose(1, 0, 2, 3).reshape(G, D * C, d)
        hid = _expert_mlp(params, hid, tp_axis)
        back = hid.reshape(G, D, C, d).transpose(1, 0, 2, 3)
        out = jax.lax.all_to_all(back, axis_name, 0, 0).reshape(E, C, d)
    y = jnp.einsum("tec,ecd->td", combine, out)
    return y.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# MoE decoder LM (gpt2-style blocks with MoE FFN)
# ---------------------------------------------------------------------------


def moe_layer_init(key: jax.Array, cfg: ModelConfig, moe: MoEConfig) -> Dict:
    ka, km = jax.random.split(key)
    return {
        "ln1": layer_norm_init(cfg.dim),
        "attn": mha_init(ka, cfg.dim, cfg.n_heads),
        "ln2": layer_norm_init(cfg.dim),
        "moe": moe_ffn_init(km, cfg.dim, moe.ffn_dim or cfg.ffn_dim,
                            moe.n_experts),
    }


def moe_layer_apply(cfg: ModelConfig, moe: MoEConfig, params: Dict,
                    h: jax.Array, axis_name: Optional[str] = None,
                    tp_axis: Optional[str] = None,
                    tp_size: int = 1,
                    rng: Optional[jax.Array] = None,
                    sp_axis: Optional[str] = None,
                    sp_attn_impl: str = "ring",
                    sp_size: int = 1,
                    ) -> Tuple[jax.Array, jax.Array]:
    """One MoE decoder block. ``axis_name`` shards experts (EP);
    ``tp_axis``/``tp_size`` additionally Megatron-shards the attention
    heads and each expert's ffn dim over the model axis — EP moves whole
    experts across devices, TP splits every expert's matmuls, and the two
    compose (each expert shard group runs its ffn slice).

    ``sp_axis`` (round 5) runs the block with the SEQUENCE sharded over
    that mesh axis: attention goes through the ring/Ulysses transport
    (``sp_attn_impl``) exactly as dense seq-parallel stages do, while the
    MoE FFN — position-wise by construction — routes each shard's LOCAL
    tokens with local capacity, the same local-routing semantics the EP
    path already uses for its batch sharding (capacity is computed from
    the local token count, so routing statistics are per-shard). No new
    collective: the expert all_to_all stays on the expert axis.

    ``rng`` (train mode, round 4) enables dropout at the dense gpt2
    block's sites: attention probabilities (stream 0), the attention
    residual (1), and the MoE-FFN residual (2). The FFN mask lands on the
    COMBINED expert output — position-wise on [B, S, d] — not on
    per-expert slot blocks, so it is invariant to the EP/TP partitioning
    by construction (no per-expert-slot mask streams needed) and follows
    the same (key, shard, microbatch, layer, site) convention as the
    dense executor (tests/test_moe_pipeline.py asserts the partition
    invariance). With ``sp_axis`` the residual/FFN masks are the
    full-sequence masks' local slices (``sharded_dropout_apply`` over
    dim 1, the dense sp path's rule), so a seq-sharded run reproduces
    the unsharded masks exactly; attention-prob masks follow the
    transport's own convention (Ulysses: oracle-exact post-scatter head
    blocks; ring: blockwise global-coordinate masks)."""
    from ..ops.layers import sharded_dropout_apply
    p = cfg.dropout if rng is not None else 0.0

    def site(i: int) -> Optional[jax.Array]:
        return None if rng is None else jax.random.fold_in(rng, i)

    def drop(x, i):
        # plain dropout_apply when sp_axis is None (the helper's own
        # fallback), local mask slices when seq-sharded
        return sharded_dropout_apply(x, p, site(i), axis=sp_axis,
                                     n_shards=sp_size, shard_dim=1)

    a = layer_norm_apply(params["ln1"], h)
    if sp_axis is not None:
        from ..parallel.seq_parallel import ATTN_IMPLS
        attn = ATTN_IMPLS[sp_attn_impl](
            params["attn"], a, a, cfg.n_heads // tp_size, sp_axis,
            causal=True, tp_axis=tp_axis, dropout_rate=p,
            dropout_rng=site(0))
    else:
        attn = mha_apply(params["attn"], a, a, cfg.n_heads // tp_size,
                         causal=True, tp_axis=tp_axis, tp_size=tp_size,
                         dropout_rate=p, dropout_rng=site(0))
    h = h + drop(attn, 1)
    m = layer_norm_apply(params["ln2"], h)
    y, aux = moe_ffn_apply(params["moe"], m, moe, axis_name, tp_axis)
    return h + drop(y, 2), aux


def moe_lm_init(key: jax.Array, cfg: ModelConfig, moe: MoEConfig) -> Dict:
    ke, kp, kl, ko = jax.random.split(key, 4)
    embed = {
        "tok": embedding_init(ke, cfg.vocab_size, cfg.dim),
        "pos": 0.02 * jax.random.normal(kp, (cfg.max_seq_len, cfg.dim)),
    }
    layers = jax.vmap(lambda k: moe_layer_init(k, cfg, moe))(
        jax.random.split(kl, cfg.n_layers))
    # tied embeddings (round 4): like transformer_init, the head is only
    # the norm — the vocab matmul reuses embed["tok"] (head_apply)
    head = {"norm": layer_norm_init(cfg.dim)}
    if not cfg.tie_embeddings:
        head["out"] = linear_init(ko, cfg.dim, cfg.vocab_size, bias=False)
    params = {"embed": embed, "layers": layers, "head": head}
    dtype = jnp.dtype(cfg.dtype)
    if dtype != jnp.float32:
        params = jax.tree.map(lambda x: x.astype(dtype), params)
    return params


def moe_lm_logits_aux(cfg: ModelConfig, moe: MoEConfig, params: Dict,
                      tokens: jax.Array,
                      axis_name: Optional[str] = None):
    """MoE LM forward: -> (logits [B, S, V], summed per-layer aux loss).
    The shared core of :func:`moe_lm_loss` and test oracles. With
    ``cfg.tie_embeddings`` the vocab matmul reuses the embedding table
    (round 4 — the pipeline executor's MoE stages share the same
    ``_stage_ce`` tied-head path)."""
    h = embedding_apply(params["embed"]["tok"], tokens)
    if cfg.embed_scale:
        # Gemma convention (models.transformer.embed_apply): embedding
        # OUTPUTS scale by sqrt(dim) while the tied head keeps the
        # unscaled table; scale before the positional rows so those stay
        # unscaled too (matching seq_parallel.sp_embed_apply's order)
        h = h * (cfg.dim ** 0.5)
    h = h + params["embed"]["pos"][: tokens.shape[1]]
    h = h.astype(jnp.dtype(cfg.dtype))

    def step(carry, layer_params):
        h, aux = carry
        h, a = moe_layer_apply(cfg, moe, layer_params, h, axis_name)
        return (h, aux + a), None

    (h, aux), _ = jax.lax.scan(step, (h, jnp.zeros((), jnp.float32)),
                               params["layers"])
    from .transformer import head_apply
    logits = head_apply(cfg, params["head"], h,
                        embed=params["embed"] if cfg.tie_embeddings
                        else None)
    return logits, aux


def moe_lm_loss(cfg: ModelConfig, moe: MoEConfig, params: Dict,
                tokens: jax.Array, targets: jax.Array,
                axis_name: Optional[str] = None) -> jax.Array:
    """CE loss + mean per-layer aux loss. Differentiable; works unsharded
    (``axis_name=None``) or inside the EP shard_map (tokens batch-sharded,
    experts sharded — :func:`..parallel.expert_parallel.make_ep_loss_fn`).

    With ``cfg.pad_token_id`` the CE normalizes by the (axis-global) valid
    count; the routing aux loss stays token-uniform (pad positions are
    routed and occupy expert capacity, so load balance legitimately counts
    them)."""
    logits, aux = moe_lm_logits_aux(cfg, moe, params, tokens, axis_name)
    aux_term = moe.aux_loss_weight * aux / cfg.n_layers
    if cfg.pad_token_id is not None:
        from ..ops.layers import select_masked_xent_sum
        s, n = select_masked_xent_sum(cfg.use_fused_xent)(
            logits, targets, cfg.pad_token_id)
        if axis_name is not None:
            s = jax.lax.psum(s, axis_name)
            n = jax.lax.psum(n, axis_name)
            aux_term = (jax.lax.psum(aux_term, axis_name)
                        / jax.lax.psum(1, axis_name))
        return s / jnp.maximum(n, 1) + aux_term
    loss = select_xent(cfg.use_fused_xent)(logits, targets) + aux_term
    if axis_name is not None:
        loss = jax.lax.psum(loss, axis_name) / jax.lax.psum(1, axis_name)
    return loss
