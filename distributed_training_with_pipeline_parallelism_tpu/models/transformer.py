"""Decoder-only transformer LMs as pure-JAX parameter pytrees.

Three block families selected by ``ModelConfig.arch``:

- ``ref_decoder`` — reference parity: the reference model
  (``LLMsDistributedTrainingHelper.py:31-55``) is ``nn.Embedding`` → N ×
  ``nn.TransformerDecoderLayer(dim, n_heads, batch_first=True)`` → ``LayerNorm``
  → ``Linear(dim, vocab)``, called as ``layer(h, h)`` — i.e. each decoder layer
  runs self-attention *and* cross-attention where the memory is the layer's own
  input hidden state; post-LN; relu FFN of width 2048; **no** causal mask and
  **no** positional encoding (the reference never passes masks or positions).
- ``gpt2`` — pre-LN, learned position embeddings, causal self-attn, gelu MLP.
- ``llama`` — pre-RMSNorm, RoPE, grouped-query causal attention, SwiGLU MLP,
  no biases.
- ``nemotron_h`` — ONE mixer a layer, chosen by ``cfg.hybrid_override_pattern``
  (Mamba-2, attention, latent attention, a dense MLP, experts): the layers
  and their stack are :mod:`.nemotron_h`; ``layers`` is then a dict of
  per-kind stacks walked in pattern order, not one scan (regions
  ``model/ssm``, ``model/ssm_scan``, ``model/moe``, ``model/moe_experts``,
  ``model/mla_latent`` beside ``model/attn`` and ``model/mlp``).

Every block names itself for the profiler with ``jax.named_scope`` —
``model/embed``, ``model/layers``, ``model/attn``, ``model/mlp``,
``model/head_loss``: the vocabulary of ``utils/profiling.py:REGIONS`` — at
the one function every path shares (fused step, tick-executor stage body,
serving forward), so a trace of any of them splits the same way.

Parameters are organized for pipeline stage-slicing (SURVEY.md §7: the C3
``manual_model_split`` equivalent is a pytree partition, not module deletion):

    {"embed": {...}, "layers": <leaves stacked on axis 0 over n_layers>,
     "head": {"norm": ..., "out": ...}}
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..ops.attention import mha_apply, mha_init, rope_frequencies
from ..ops.layers import (dropout_apply, embedding_apply, embedding_init,
                          layer_norm_apply, layer_norm_init, linear_apply,
                          linear_init, remat_layer, rms_norm_apply,
                          rms_norm_init, select_xent, sharded_dropout_apply)
from ..utils.config import ModelConfig
from . import nemotron_h

# ---------------------------------------------------------------------------
# Per-layer init / apply
# ---------------------------------------------------------------------------


def layer_init(key: jax.Array, cfg: ModelConfig) -> Dict:
    ks = jax.random.split(key, 8)
    if cfg.arch == "ref_decoder":
        return {
            "self_attn": mha_init(ks[0], cfg.dim, cfg.n_heads),
            "cross_attn": mha_init(ks[1], cfg.dim, cfg.n_heads),
            "ln1": layer_norm_init(cfg.dim),
            "ln2": layer_norm_init(cfg.dim),
            "ln3": layer_norm_init(cfg.dim),
            "lin1": linear_init(ks[2], cfg.dim, cfg.ffn_dim),
            "lin2": linear_init(ks[3], cfg.ffn_dim, cfg.dim),
        }
    if cfg.arch == "gpt2":
        return {
            "ln1": layer_norm_init(cfg.dim),
            "attn": mha_init(ks[0], cfg.dim, cfg.n_heads),
            "ln2": layer_norm_init(cfg.dim),
            "lin1": linear_init(ks[2], cfg.dim, cfg.ffn_dim),
            "lin2": linear_init(ks[3], cfg.ffn_dim, cfg.dim),
        }
    if cfg.arch == "llama":
        return {
            "rms1": rms_norm_init(cfg.dim),
            "attn": mha_init(ks[0], cfg.dim, cfg.n_heads, cfg.n_kv_heads,
                             bias=cfg.attention_qkv_bias, o_bias=False,
                             head_dim=cfg.head_dim),
            "rms2": rms_norm_init(cfg.dim),
            "w1": linear_init(ks[2], cfg.dim, cfg.ffn_dim, bias=False),
            "w2": linear_init(ks[3], cfg.ffn_dim, cfg.dim, bias=False),
            "w3": linear_init(ks[4], cfg.dim, cfg.ffn_dim, bias=False),
        }
    raise ValueError(f"unknown arch {cfg.arch!r}")


def layer_apply(cfg: ModelConfig, params: Dict, h: jax.Array,
                rope_angles: Optional[jax.Array] = None,
                tp_axis: Optional[str] = None, tp_size: int = 1,
                rng: Optional[jax.Array] = None) -> jax.Array:
    """One decoder block. With ``tp_axis`` set the block runs Megatron
    tensor-parallel inside a manual-SPMD region: weight leaves are local
    shards (attention heads and FFN hidden dim column-split ``tp_size``
    ways), norms replicated, and the two row-parallel projections complete
    with a psum (see :mod:`..ops.collectives`).

    ``rng`` (train mode) enables dropout at the torch sites: attention
    probabilities inside each MHA, each residual branch, and the FFN's inner
    activation (``nn.TransformerDecoderLayer``'s dropout/dropout1/2/3 for the
    ref arch; GPT-2's attn/resid dropout). Each site folds a distinct stream
    from ``rng``, so one per-layer key determines every mask."""
    fl = cfg.flash_for(cfg.causal, h.shape[1])
    heads = cfg.n_heads // tp_size
    p = cfg.dropout

    def site(i: int) -> Optional[jax.Array]:
        return None if rng is None else jax.random.fold_in(rng, i)

    if cfg.arch == "ref_decoder":
        mem = h  # the reference calls layer(h, h): memory is the layer's input
        # post-LN: each block's norm follows its residual add
        with jax.named_scope("model/attn"):
            sa = mha_apply(params["self_attn"], h, h, heads, flash=fl,
                           tp_axis=tp_axis, tp_size=tp_size, dropout_rate=p,
                           dropout_rng=site(0))
            x = layer_norm_apply(params["ln1"],
                                 h + dropout_apply(sa, p, site(1)))
            ca = mha_apply(params["cross_attn"], x, mem, heads, flash=fl,
                           tp_axis=tp_axis, tp_size=tp_size, dropout_rate=p,
                           dropout_rng=site(2))
            x = layer_norm_apply(params["ln2"],
                                 x + dropout_apply(ca, p, site(3)))
        with jax.named_scope("model/mlp"):
            # the FFN-inner activation is a column-parallel local shard
            # under TP: its mask is the global mask's local slice
            # (oracle-exact)
            ff = _ffn_out(params["lin2"],
                          sharded_dropout_apply(
                              jax.checkpoint(jax.nn.relu)(
                                  linear_apply(params["lin1"],
                                               _tp_in(x, tp_axis))),
                              p, site(4), axis=tp_axis, n_shards=tp_size,
                              shard_dim=-1),
                          tp_axis)
            return layer_norm_apply(params["ln3"],
                                    x + dropout_apply(ff, p, site(5)))
    if cfg.arch == "gpt2":
        with jax.named_scope("model/attn"):
            a = layer_norm_apply(params["ln1"], h)
            attn = mha_apply(params["attn"], a, a, heads, causal=cfg.causal,
                             flash=fl, tp_axis=tp_axis, tp_size=tp_size,
                             dropout_rate=p, dropout_rng=site(0))
        h = h + dropout_apply(attn, p, site(1))
        return mlp_block(cfg, params, h, tp_axis=tp_axis, tp_size=tp_size,
                         rng=site(2), dropout=p)
    if cfg.arch == "llama":
        with jax.named_scope("model/attn"):
            a = rms_norm_apply(params["rms1"], h, cfg.rms_eps)
            attn = mha_apply(params["attn"], a, a, heads, causal=cfg.causal,
                             rope_angles=rope_angles, flash=fl,
                             tp_axis=tp_axis, tp_size=tp_size,
                             window=cfg.sliding_window, dropout_rate=p,
                             dropout_rng=site(0))
        h = h + dropout_apply(attn, p, site(1))
        return mlp_block(cfg, params, h, tp_axis=tp_axis, tp_size=tp_size,
                         rng=site(2), dropout=p)
    raise ValueError(f"unknown arch {cfg.arch!r}")


def _tp_in(x: jax.Array, tp_axis: Optional[str]) -> jax.Array:
    if tp_axis is None:
        return x
    from ..ops.collectives import tp_copy
    return tp_copy(x, tp_axis)


def _ffn_out(params: Dict, z: jax.Array, tp_axis: Optional[str]) -> jax.Array:
    if tp_axis is None:
        return linear_apply(params, z)
    from ..ops.collectives import row_parallel_linear
    return row_parallel_linear(params, z, tp_axis)


@jax.named_scope("model/mlp")
def mlp_block(cfg: ModelConfig, params: Dict, h: jax.Array,
              tp_axis: Optional[str] = None, tp_size: int = 1,
              rng: Optional[jax.Array] = None, dropout: float = 0.0) -> jax.Array:
    """Post-attention half of a gpt2/llama block (norm + MLP + residual).

    Shared between the training path (:func:`layer_apply`) and the KV-cache
    decode path (:mod:`.generate`, which never passes an rng) so the two
    cannot drift. ``rng`` applies residual-branch dropout to the MLP output.

    With ``cfg.tp_overlap`` resolving to ``"ring"`` (TP only, dropout-free,
    seq divisible by ``tp_size``), the block's TP boundary runs the
    collective-matmul forms instead of the replicated copy/psum pair: the
    sequence is sharded at the norm output, the all-gather overlaps the
    up-projection and the reduce-scatter the down-projection, and the
    residual re-replicates via one ring gather (see
    :mod:`..ops.collectives`)."""
    if (tp_axis is not None and tp_size > 1 and cfg.tp_overlap != "none"
            and (rng is None or dropout == 0.0)):
        from ..parallel.tensor_parallel import resolve_tp_overlap
        if resolve_tp_overlap(cfg.tp_overlap, tp_size, h.shape[1]) == "ring":
            return _mlp_block_ring(cfg, params, h, tp_axis, tp_size)
    # the activations are checkpointed: backward saves only the [.., ffn]
    # pre-activation and recomputes the (tanh-)gelu/silu chain — without
    # this autodiff banks ~6 ffn-sized intermediates per layer, the
    # dominant residual cost of stored-activation backwards
    if cfg.arch == "gpt2":
        m = _tp_in(layer_norm_apply(params["ln2"], h), tp_axis)
        ff = _ffn_out(params["lin2"],
                      jax.checkpoint(jax.nn.gelu)(
                          linear_apply(params["lin1"], m)),
                      tp_axis)
        return h + dropout_apply(ff, dropout, rng)
    m = _tp_in(rms_norm_apply(params["rms2"], h, cfg.rms_eps), tp_axis)
    act = jax.nn.silu if cfg.mlp_act == "silu" else jax.nn.gelu
    ff = _ffn_out(params["w2"],
                  jax.checkpoint(lambda a, b: act(a) * b)(
                      linear_apply(params["w1"], m),
                      linear_apply(params["w3"], m)),
                  tp_axis)
    return h + dropout_apply(ff, dropout, rng)


def _mlp_block_ring(cfg: ModelConfig, params: Dict, h: jax.Array,
                    tp_axis: str, tp_size: int) -> jax.Array:
    """Collective-matmul MLP: sequence-shard the norm output (free slice of
    a replicated value), overlap the gather with the up-projection and the
    scatter with the down-projection, re-replicate for the residual. The
    up-projection is bit-identical to the unfused path; the down-projection
    sums partials in ring order (numerical, not bitwise, parity)."""
    from ..ops.collectives import seq_all_gather, seq_scatter
    from ..parallel.tensor_parallel import (tp_all_gather_matmul,
                                            tp_matmul_reduce_scatter)
    if cfg.arch == "gpt2":
        m = seq_scatter(layer_norm_apply(params["ln2"], h), tp_axis, tp_size)
        z = tp_all_gather_matmul(m, params["lin1"]["w"], tp_axis, tp_size,
                                 mode="ring") + params["lin1"]["b"]
        ff = tp_matmul_reduce_scatter(jax.checkpoint(jax.nn.gelu)(z),
                                      params["lin2"]["w"], tp_axis, tp_size,
                                      mode="ring")
        ff = seq_all_gather(ff, tp_axis, tp_size) + params["lin2"]["b"]
        return h + ff
    m = seq_scatter(rms_norm_apply(params["rms2"], h, cfg.rms_eps),
                    tp_axis, tp_size)
    act = jax.nn.silu if cfg.mlp_act == "silu" else jax.nn.gelu
    z1 = tp_all_gather_matmul(m, params["w1"]["w"], tp_axis, tp_size,
                              mode="ring")
    z3 = tp_all_gather_matmul(m, params["w3"]["w"], tp_axis, tp_size,
                              mode="ring")
    ff = tp_matmul_reduce_scatter(
        jax.checkpoint(lambda a, b: act(a) * b)(z1, z3),
        params["w2"]["w"], tp_axis, tp_size, mode="ring")
    return h + seq_all_gather(ff, tp_axis, tp_size)


# ---------------------------------------------------------------------------
# Whole-model init / apply
# ---------------------------------------------------------------------------


def transformer_init(key: jax.Array, cfg: ModelConfig) -> Dict:
    ke, kp, kl, kn, ko = jax.random.split(key, 5)
    if cfg.arch == "ref_decoder":
        # torch nn.Embedding parity: N(0, 1) (the reference's init)
        tok = embedding_init(ke, cfg.vocab_size, cfg.dim)
    else:
        # GPT-2/Llama convention: N(0, 0.02) — essential under tied
        # embeddings, where N(0,1) rows make initial logits ~sqrt(dim) hot
        tok = 0.02 * jax.random.normal(ke, (cfg.vocab_size, cfg.dim))
    embed: Dict = {"tok": tok}
    if cfg.arch == "gpt2":
        embed["pos"] = 0.02 * jax.random.normal(kp, (cfg.max_seq_len, cfg.dim))
    if cfg.arch == "nemotron_h":
        layers = nemotron_h.stack_init(kl, cfg)  # one stack per kind
    else:
        layer_keys = jax.random.split(kl, cfg.n_layers)
        layers = jax.vmap(lambda k: layer_init(k, cfg))(layer_keys)
    rms = cfg.arch in ("llama", "nemotron_h")
    norm = rms_norm_init(cfg.dim) if rms else layer_norm_init(cfg.dim)
    if cfg.tie_embeddings:
        head = {"norm": norm}  # logits come from embed.tok.T
    elif rms:
        head = {"norm": norm,
                "out": linear_init(ko, cfg.dim, cfg.vocab_size, bias=False)}
    else:
        head = {"norm": norm,
                "out": linear_init(ko, cfg.dim, cfg.vocab_size, bias=cfg.arch == "ref_decoder")}
    params = {"embed": embed, "layers": layers, "head": head}
    dtype = jnp.dtype(cfg.storage_dtype)  # master-weight dtype under mixing
    if dtype != jnp.float32:
        params = jax.tree.map(lambda x: x.astype(dtype), params)
    return params


def compute_cast(cfg: ModelConfig, tree: Dict) -> Dict:
    """Cast a parameter (sub)tree from storage to compute dtype. Identity
    when no mixed precision is configured. Sits INSIDE autodiff at every
    use site, so cotangents flow back in the storage dtype."""
    if not cfg.mixed_precision:
        return tree
    if cfg.arch == "nemotron_h":  # decay parameters and router stay float32
        return nemotron_h.compute_cast(cfg, tree)
    dtype = jnp.dtype(cfg.dtype)
    return jax.tree.map(lambda x: x.astype(dtype), tree)


@jax.named_scope("model/embed")
def embed_apply(cfg: ModelConfig, embed: Dict, tokens: jax.Array,
                rng: Optional[jax.Array] = None) -> jax.Array:
    h = embedding_apply(embed["tok"], tokens)
    if cfg.embed_scale:
        # Gemma scales embedding OUTPUTS by sqrt(dim); the tied head keeps
        # the unscaled table, so this cannot fold into the weights
        h = h * (cfg.dim ** 0.5)
    if cfg.arch == "gpt2":
        h = h + embed["pos"][: tokens.shape[1]]
        h = dropout_apply(h, cfg.dropout, rng)  # GPT-2 embedding dropout
    return h


def _rope(cfg: ModelConfig, seq_len: int) -> Optional[jax.Array]:
    if cfg.arch != "llama":
        return None
    return rope_frequencies(cfg.head_dim, seq_len, cfg.rope_theta,
                            cfg.rope_scaling)


# model/layers names the stack's own work — slicing a layer's weights out of
# the stacked leaves, writing and reading the scan's stacked residuals, the
# residual adds; the blocks inside name themselves
@jax.named_scope("model/layers")
def body_apply(cfg: ModelConfig, layers: Dict, h: jax.Array,
               tp_axis: Optional[str] = None, tp_size: int = 1,
               rng: Optional[jax.Array] = None,
               layer_offset=0) -> jax.Array:
    """Run a stack of layers whose leaves are stacked on axis 0 (any count).

    ``rng`` (train mode) enables dropout; each layer folds
    ``layer_offset + i`` from it, where ``layer_offset`` is the stack's first
    *global* layer index — so masks depend only on (rng, global layer, site),
    making a pipeline-stage run reproduce exactly the masks of any other
    stage partitioning of the same model (asserted in tests/test_dropout.py).

    nemotron_h: ``layers`` is the dict of per-kind stacks and the WHOLE
    pattern is walked (:func:`.nemotron_h.stack_apply`); a stack of one kind
    keeps the scan below.

    ``cfg.remat_layers``: each layer is recomputed in the backward from its
    input, all but the flash kernels' output and log-sum-exp, which are
    kept where the kernels run (:func:`..ops.layers.remat_layer`).
    """
    if cfg.arch == "nemotron_h":
        if tp_axis is not None or rng is not None:
            raise NotImplementedError("arch='nemotron_h' layers are not "
                                      "written for tensor parallelism or "
                                      "dropout")
        return nemotron_h.stack_apply(cfg, layers, h)[0]
    rope = _rope(cfg, h.shape[1])
    n = jax.tree.leaves(layers)[0].shape[0]

    if cfg.unroll_layers:
        # straight-line layers: no scan boundary, so XLA fuses across
        # layers and autodiff residuals stay SSA values instead of
        # round-tripping HBM through stacked scan outputs (the same
        # finding as the executor's unrolled stored backward,
        # docs/performance.md). Compile time grows with depth; measured
        # +5-12% train-step throughput for gpt2-small on one v5e chip.
        def one(layer_params, x, i):
            rng_l = (None if rng is None
                     else jax.random.fold_in(rng, layer_offset + i))
            return layer_apply(cfg, layer_params, x, rope, tp_axis=tp_axis,
                               tp_size=tp_size, rng=rng_l)

        if cfg.remat_layers:
            one = remat_layer(one, n, static_argnums=(2,))
        for i in range(n):
            h = one(jax.tree.map(lambda x: x[i], layers), h, i)
        return h

    def step(carry, xs):
        layer_params, i = xs
        rng_l = None if rng is None else jax.random.fold_in(rng, layer_offset + i)
        return layer_apply(cfg, layer_params, carry, rope,
                           tp_axis=tp_axis, tp_size=tp_size, rng=rng_l), None

    if cfg.remat_layers:
        # rematerialize each layer in backward: activation memory drops from
        # O(layers x intermediates) to O(layers) block inputs, and the flash
        # kernels' output and log-sum-exp where they run
        step = remat_layer(step, n)
    out, _ = jax.lax.scan(step, h, (layers, jnp.arange(n)))
    return out


def head_norm_apply(cfg: ModelConfig, head: Dict, h: jax.Array) -> jax.Array:
    """The head's final norm (arch-dispatched) — shared with the executor's
    vocab-parallel loss branch so the two cannot drift."""
    if cfg.arch in ("llama", "nemotron_h"):
        return rms_norm_apply(head["norm"], h, cfg.rms_eps)
    return layer_norm_apply(head["norm"], h)


@jax.named_scope("model/head_loss")
def head_apply(cfg: ModelConfig, head: Dict, h: jax.Array,
               embed: Optional[Dict] = None) -> jax.Array:
    hn = head_norm_apply(cfg, head, h)
    # flatten [B, S, d] -> [B*S, d] around the vocab matmul: a 2-D dot
    # gets the default output layout, which the fused-CE kernel (and any
    # flat consumer) reads without a relayout — the 3-D form cost a
    # measured 2.5 ms/step full-logits copy at GPT-2 vocab (docs/profiles/)
    lead = hn.shape[:-1]
    hn2 = hn.reshape(-1, hn.shape[-1]) if hn.ndim > 2 else hn
    if cfg.tie_embeddings:
        assert embed is not None, "tied head needs the embedding table"
        logits = hn2 @ embed["tok"].T
    else:
        logits = linear_apply(head["out"], hn2)
    return logits.reshape(*lead, logits.shape[-1]) if hn.ndim > 2 else logits


def transformer_apply(cfg: ModelConfig, params: Dict, tokens: jax.Array,
                      rng: Optional[jax.Array] = None) -> jax.Array:
    """Full-model forward: tokens [B, S] -> logits [B, S, V].

    ``rng`` (train mode) enables dropout: layer i folds stream i, the
    embedding folds stream ``n_layers`` — the same convention the pipeline
    executor uses per microbatch, so executor masks are checkable against
    this path."""
    rng_e = None if rng is None else jax.random.fold_in(rng, cfg.n_layers)
    params = compute_cast(cfg, params)  # bf16 compute over fp32 masters
    h = embed_apply(cfg, params["embed"], tokens, rng=rng_e)
    h = body_apply(cfg, params["layers"], h, rng=rng)
    return head_apply(cfg, params["head"], h, embed=params["embed"])


def transformer_loss(cfg: ModelConfig, params: Dict, tokens: jax.Array,
                     targets: jax.Array,
                     rng: Optional[jax.Array] = None) -> jax.Array:
    """Single-device reference loss — the ground truth the pipeline executors
    are verified against (a check the reference itself never performs,
    SURVEY.md §4). With ``cfg.pad_token_id`` set, pad targets are ignored
    and the mean divides by the valid count."""
    logits = transformer_apply(cfg, params, tokens, rng=rng)
    with jax.named_scope("model/head_loss"):  # the head's half: head_apply
        if cfg.pad_token_id is not None:
            from ..ops.layers import select_masked_xent_sum
            s, n = select_masked_xent_sum(cfg.use_fused_xent)(
                logits, targets, cfg.pad_token_id)
            return s / jnp.maximum(n, 1)
        return select_xent(cfg.use_fused_xent)(logits, targets)
