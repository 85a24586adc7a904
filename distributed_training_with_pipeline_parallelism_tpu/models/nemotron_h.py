"""The patterned stack (``arch="nemotron_h"``, after the family that brought
it: NVIDIA Nemotron-H / Nemotron-3 hybrids): pre-norm residual layers
``h <- h + mixer_l(RMSNorm_l(h))`` in which every layer is ONE mixer, chosen
by a letter of ``cfg.hybrid_override_pattern``:

- ``M`` — Mamba-2 (:mod:`..ops.mamba2`);
- ``C`` — a gated short convolution (:mod:`..ops.shortconv`; LFM2's ``conv``
  operator);
- ``*`` — causal grouped-query attention, no bias, through
  :func:`..ops.attention.mha_apply` and so through the Pallas flash kernels
  where ``cfg.flash_for`` says so. As Nemotron-H has it, no positional
  encoding (the state-space layers carry position; ``rope_theta`` is carried
  by the source's config and unused); as LFM2 has it, an RMSNorm over every
  query and key head (``cfg.qk_layernorm``: the leaves are there or not)
  and then RoPE in split halves (``cfg.attn_rope``);
- ``L`` — causal multi-head latent attention (:func:`..ops.attention.
  mla_apply`): low-rank query and key-value paths, RoPE on the
  ``qk_rope_head_dim`` columns only, scores over ``qk_nope_head_dim +
  qk_rope_head_dim`` and values over ``v_head_dim``, through the same
  kernels at the two widths;
- ``-`` — a dense MLP of width ``cfg.ffn_dim`` (:func:`..ops.experts.
  mlp_apply`);
- ``E`` — routed experts, and a shared expert where
  ``cfg.moe_shared_expert_intermediate_size`` is not 0 (:mod:`..ops.experts`),
  as the expert-parallel rank that holds ``cfg.held_experts`` computes them.

``-`` and ``E`` take the form ``cfg.mlp_hidden_act`` names: ``relu2``
(Nemotron-H) or the gated ``silu``. A DeepSeek-style block — two pre-norm
residual sublayers, attention then FFN — is two letters: ``L-`` a leading
dense layer, ``LE`` an expert layer; an LFM2 block likewise (``C-``, ``CE``,
``*E``).

Parameters: ``layers`` is ``{"mamba": .., "shortconv": .., "attn": ..,
"mla": .., "mlp": .., "moe": ..}`` (the kinds the pattern has), each the
layers of one kind stacked on axis 0 in pattern order; the stack is walked in
pattern order as straight-line code (layers of different kinds share no
scan), each layer under ``jax.checkpoint`` where ``cfg.remat_layers``
(:func:`..ops.layers.remat_layer`: all is recomputed in the backward but
the flash kernels' output and log-sum-exp, and the named product outputs
that :func:`kept_names` grants each layer from the room the chip has).
``transformer_init`` / ``body_apply`` of :mod:`.transformer` dispatch here,
so ``transformer_loss``, ``train.init_params`` and ``train.make_train_step``
run this family on the normal path.

What it does not run, by a named error: ``pipe`` > 1 and tensor, sequence or
fsdp axes (:func:`check_mesh`), ``models/generate.py`` and ``serving/``.
"""

from __future__ import annotations

import functools
import logging
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import (mha_apply, mha_init, mla_apply, mla_init,
                             rope_frequencies)
from ..ops.experts import experts_apply, experts_init, mlp_apply, mlp_init
from ..ops.layers import (Offer, choose_kept, current_room, offers_of,
                          remat_layer, rms_norm_apply, rms_norm_init)
from ..ops.mamba2 import mamba2_apply, mamba2_init
from ..ops.shortconv import shortconv_apply, shortconv_init
from ..utils.config import ModelConfig
from ..utils.profiling import annotate

#: pattern letter -> the key of its stack under ``params["layers"]``
KINDS = {"M": "mamba", "C": "shortconv", "*": "attn", "L": "mla",
         "-": "mlp", "E": "moe"}
#: leaves that stay in the storage dtype under mixed precision: the
#: recurrence's decay parameters and the whole router are float32 whatever
#: ``cfg.dtype`` is
FLOAT32_LEAVES = frozenset(("A_log", "dt_bias", "D", "router"))

#: what a layer's backward holds at once, in multiples of its named
#: products' bytes, where it is not the 0.75 of a layer of plain products
#: (the Mamba-2 scan's float32 decay tiles: 1.9 GB at the benchmark's shapes)
WORKING_SET = {"mamba": 5.7}

_log = logging.getLogger(__name__)


def nemotron_h_config(name: str = "stage", **overrides) -> ModelConfig:
    """``stage``: the first nine layers of Nemotron-Labs-TwoTower-30B-A3B's
    language model at published widths as one rank of 16-way expert
    parallelism holds them (8 of 128 experts, an eighth of the vocabulary:
    667 M parameters; ``benchmark/configs/nemotron-twotower-30b-a3b.json``
    has the source and the arithmetic). ``debug``: those kinds of layer at toy
    widths, for the CPU. ``joyai-stage``: the first eight layers of
    JoyAI-LLM-Flash (latent attention; a dense gated MLP, then seven layers
    of gated experts) at published widths as one rank of 32-way expert
    parallelism holds them (8 of 256 experts, an eighth of the vocabulary:
    622 M parameters; ``benchmark/configs/joyai-llm-flash.json``).
    ``joyai-debug``: its kinds of layer at toy widths. ``lfm2-stage``: layers
    0 and 2-6 of LFM2-8B-A1B (gated short convolutions and grouped-query
    attention with q/k norms and RoPE, over a dense gated MLP and then five
    layers of gated experts without a shared expert) at published widths as
    one rank of 4-way expert parallelism holds them (8 of 32 experts, a
    quarter of the vocabulary: 640 M parameters;
    ``benchmark/configs/lfm2-8b-a1b.json``). ``lfm2-debug``: its kinds of
    layer at toy widths."""
    lfm2 = dict(mlp_hidden_act="silu", moe_shared_expert_intermediate_size=0,
                routed_scaling_factor=1.0, router_norm_eps=1e-6,
                rope_theta=1e6, qk_layernorm=True, attn_rope=True)
    sizes = {
        "stage": dict(dim=2688, n_heads=32, n_kv_heads=2,
                      head_dim_override=128, vocab_size=16384,
                      max_seq_len=262144, hybrid_override_pattern="MEMEM*EME",
                      experts_held=tuple(range(8))),
        "joyai-stage": dict(dim=2048, n_heads=32, vocab_size=16160,
                            max_seq_len=131072, rms_eps=1e-6,
                            rope_theta=32e6, ffn_dim=7168,
                            hybrid_override_pattern="L-" + "LE" * 7,
                            mlp_hidden_act="silu", n_routed_experts=256,
                            experts_held=tuple(range(8)),
                            num_experts_per_tok=8, moe_intermediate_size=768,
                            moe_shared_expert_intermediate_size=768),
        "joyai-debug": dict(dim=64, n_heads=4, vocab_size=256,
                            max_seq_len=4096, rms_eps=1e-6, rope_theta=32e6,
                            ffn_dim=96, hybrid_override_pattern="L-LELE",
                            mlp_hidden_act="silu", n_routed_experts=16,
                            experts_held=(0, 1, 2, 3), num_experts_per_tok=3,
                            moe_intermediate_size=32,
                            moe_shared_expert_intermediate_size=32,
                            q_lora_rank=48, kv_lora_rank=32,
                            qk_nope_head_dim=16, qk_rope_head_dim=8,
                            v_head_dim=16),
        "lfm2-stage": dict(lfm2, dim=2048, n_heads=32, n_kv_heads=8,
                           vocab_size=16384, max_seq_len=128000, ffn_dim=7168,
                           hybrid_override_pattern="C-*E" + "CE" * 3 + "*E",
                           n_routed_experts=32, experts_held=tuple(range(8)),
                           num_experts_per_tok=4, moe_intermediate_size=1792),
        "lfm2-debug": dict(lfm2, dim=64, n_heads=4, n_kv_heads=2,
                           vocab_size=256, max_seq_len=4096, ffn_dim=96,
                           hybrid_override_pattern="C-*ECE",
                           n_routed_experts=8, experts_held=(0, 1, 2, 3),
                           num_experts_per_tok=2, moe_intermediate_size=32),
        "debug": dict(dim=64, n_heads=4, n_kv_heads=2, head_dim_override=16,
                      vocab_size=256, max_seq_len=4096,
                      hybrid_override_pattern="MEM*E", mamba_num_heads=8,
                      mamba_head_dim=8, n_groups=2, ssm_state_size=16,
                      chunk_size=16, n_routed_experts=16,
                      experts_held=(0, 1, 2, 3), num_experts_per_tok=3,
                      moe_intermediate_size=32,
                      moe_shared_expert_intermediate_size=48),
    }
    if name not in sizes:
        raise ValueError(f"unknown nemotron_h size {name!r}; options: "
                         f"{sorted(sizes)}")
    kw = dict(sizes[name], arch="nemotron_h")
    kw.update(overrides)
    kw.setdefault("n_layers", len(kw["hybrid_override_pattern"]))
    return ModelConfig(**kw)


def layer_plan(cfg: ModelConfig) -> List[Tuple[str, int]]:
    """``(stack key, index inside that stack)`` for every layer, in order."""
    seen: Dict[str, int] = {}
    plan = []
    for letter in cfg.hybrid_override_pattern:
        kind = KINDS[letter]
        plan.append((kind, seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return plan


def mixer_init(key: jax.Array, cfg: ModelConfig, kind: str) -> Dict:
    norm = rms_norm_init(cfg.dim)
    gated = cfg.mlp_hidden_act == "silu"  # the form of "mlp" and "moe"
    if kind == "mamba":
        return {"norm": norm, **mamba2_init(
            key, cfg.dim, cfg.mamba_num_heads, cfg.mamba_head_dim,
            cfg.n_groups, cfg.ssm_state_size, cfg.conv_kernel,
            cfg.time_step_min, cfg.time_step_max, cfg.time_step_floor)}
    if kind == "shortconv":
        return {"norm": norm, **shortconv_init(key, cfg.dim, cfg.conv_L_cache,
                                               cfg.conv_bias)}
    if kind == "attn":
        return {"norm": norm, "attn": mha_init(
            key, cfg.dim, cfg.n_heads, cfg.n_kv_heads, bias=False,
            head_dim=cfg.head_dim, qk_norm=cfg.qk_layernorm)}
    if kind == "mla":
        return {"norm": norm, "attn": mla_init(
            key, cfg.dim, cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim)}
    if kind == "mlp":
        return {"norm": norm, **mlp_init(jax.random.split(key, 3), cfg.dim,
                                         cfg.ffn_dim, gated)}
    if kind == "moe":
        return {"norm": norm, **experts_init(
            key, cfg.dim, cfg.n_routed_experts, len(cfg.held_experts),
            cfg.moe_intermediate_size,
            cfg.moe_shared_expert_intermediate_size, gated)}
    raise ValueError(f"unknown layer kind {kind!r}")


def stack_init(key: jax.Array, cfg: ModelConfig) -> Dict:
    """``params["layers"]``: per kind, its layers stacked on axis 0. Says at
    ``logging.INFO`` what was built."""
    plan = layer_plan(cfg)
    keys = jax.random.split(key, len(plan))
    stacks = {}
    for kind in dict.fromkeys(k for k, _ in plan):
        mine = jnp.stack([keys[i] for i, (k, _) in enumerate(plan)
                          if k == kind])
        stacks[kind] = jax.vmap(lambda k: mixer_init(k, cfg, kind))(mine)
    _log.info("nemotron_h: pattern %s (%s); experts held %s of %d; MLPs and "
              "experts %s", cfg.hybrid_override_pattern,
              ", ".join(f"{sum(k == kind for k, _ in plan)} {kind}"
                        for kind in stacks),
              list(cfg.held_experts), cfg.n_routed_experts,
              cfg.mlp_hidden_act)
    return stacks


def mixer(cfg: ModelConfig, kind: str, params: Dict, x: jax.Array):
    """One layer's mixer on ``x`` [B, T, d], already normed -> (out [B, T,
    d], the assignments each held expert got or None)."""
    if kind == "mamba":
        return mamba2_apply(
            params, x, cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
            cfg.ssm_state_size, cfg.chunk_size, cfg.rms_eps), None
    if kind == "shortconv":
        return shortconv_apply(params, x), None
    if kind == "attn":
        angles = (rope_frequencies(cfg.head_dim, x.shape[1], cfg.rope_theta)
                  if cfg.attn_rope else None)
        return mha_apply(params["attn"], x, x, cfg.n_heads, causal=True,
                         rope_angles=angles, norm_eps=cfg.rms_eps,
                         flash=cfg.flash_for(True, x.shape[1])), None
    if kind == "mla":
        return mla_apply(params["attn"], x, cfg.n_heads, cfg.qk_rope_head_dim,
                         cfg.rope_theta, cfg.rms_eps,
                         flash=cfg.flash_for(True, x.shape[1])), None
    if kind == "mlp":
        return mlp_apply(params, x), None
    if kind == "moe":
        b, t, d = x.shape
        out, counts = experts_apply(
            params, x.reshape(b * t, d), cfg.held_experts,
            cfg.num_experts_per_tok, cfg.routed_scaling_factor,
            cfg.router_norm_eps)
        return out.reshape(b, t, d), counts
    raise ValueError(f"unknown layer kind {kind!r}")


#: the profiler region of a layer's norm and mixer, by kind
SCOPES = {"mamba": "model/ssm", "shortconv": "model/shortconv",
          "attn": "model/attn", "mla": "model/attn", "mlp": "model/mlp",
          "moe": "model/moe"}


def mixer_apply(cfg: ModelConfig, kind: str, params: Dict, h: jax.Array):
    """One residual layer ``h + mixer(RMSNorm(h))`` -> (h, counts or None)."""
    with jax.named_scope(SCOPES[kind]):
        out, counts = mixer(cfg, kind, params, rms_norm_apply(
            params["norm"], h, cfg.rms_eps))
        return h + out, counts


def instants(cfg: ModelConfig, layers: Dict, h: jax.Array,
             offers: List[List[Offer]], room: float) -> List[float]:
    """What the layers BEFORE layer ``L`` may keep together while ``L``'s
    backward runs, for ``L`` = 0 .. the number of layers (the last: after
    the forward, when everything kept is held) — an estimate of what the
    compiler will count, from shapes, calibrated against its count of the
    three 8k steps (``tests/test_chip_compile.py``).

    ``room`` is what the tracing step declared (:func:`..ops.layers.
    remat_room`): the chip less parameters, optimizer state, compute copies,
    ALL gradients and the margin. At instant ``L`` there is that, plus the
    gradients not made yet — the stacks whose last layer comes before ``L``:
    a stack's gradient is one buffer, there from the first of its layers the
    backward reaches — less what the rematerialised stack holds then
    whatever is granted: the inputs of the layers up to ``L``, the flash
    pair of the attention layers before it (output [b, s, heads x d_v] and a
    float32 log-sum-exp a head and row), and layer ``L``'s own working set,
    :data:`WORKING_SET` times its named products."""
    plan = layer_plan(cfg)
    b, s, _ = h.shape
    h_bytes = h.size * h.dtype.itemsize
    width = jnp.dtype(cfg.storage_dtype).itemsize
    grads = {kind: sum(x.size * width for x in jax.tree.leaves(stack))
             for kind, stack in layers.items()}
    last = {kind: l for l, (kind, _) in enumerate(plan)}
    v_dim = ({"attn": cfg.head_dim, "mla": cfg.v_head_dim}
             if cfg.flash_for(True, s) else {})
    out, flash = [], 0
    for l, (kind, _) in enumerate(plan):
        working = WORKING_SET.get(kind, 0.75) * sum(
            o.nbytes for o in offers[l])
        out.append(room + sum(g for k, g in grads.items() if last[k] < l)
                   - (l + 1) * h_bytes - flash - working)
        if kind in v_dim:
            flash += b * s * cfg.n_heads * (v_dim[kind] * h.dtype.itemsize + 4)
    return out + [room + sum(grads.values()) - len(plan) * h_bytes - flash]


def kept_names(cfg: ModelConfig, layers: Dict, h: jax.Array,
               ) -> List[Tuple[str, ...]]:
    """The names every layer of the pattern is granted beside the flash
    pair, chosen where the step is traced: ONE walk over shapes (each
    kind's mixer under ``jax.eval_shape``: no FLOP runs) lists the named
    product outputs, and :func:`..ops.layers.choose_kept` grants them,
    dearest to recompute per byte first, within what :func:`instants` finds
    room for — nothing on a device whose memory the table does not know, so
    nothing on the CPU. Kept as the host span ``setup/remat_keep``, whose
    notes are the record of the choice."""
    plan = layer_plan(cfg)
    with annotate("setup/remat_keep") as span:
        by_kind = {kind: offers_of(
            functools.partial(mixer_apply, cfg, kind),
            jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                         layers[kind]),
            jax.ShapeDtypeStruct(h.shape, h.dtype))
            for kind in dict.fromkeys(k for k, _ in plan)}
        offers = [by_kind[kind] for kind, _ in plan]
        room = current_room()
        rooms = (instants(cfg, layers, h, offers, room) if room > 0
                 else [0.0] * (len(plan) + 1))
        keep, record = choose_kept(offers, rooms)
        span.note(room_bytes=int(room),
                  instant_bytes=[int(r) for r in rooms], **record)
    return keep


def stack_apply(cfg: ModelConfig, layers: Dict, h: jax.Array,
                ) -> Tuple[jax.Array, List[jax.Array]]:
    """Walk the pattern -> (h, the expert layers' counts in order). Under
    ``cfg.remat_layers`` every layer is recomputed in the backward from its
    input, but for what :func:`..ops.layers.remat_layer` keeps: the flash
    kernels' output and log-sum-exp of an attention layer, and the named
    product outputs :func:`kept_names` granted that layer from the chip's
    room (``x W1`` and ``x W3`` of an expert layer, an MLP's ``up`` and
    ``gate``, the in-projections and the attention projections: each whole
    or not at all)."""
    plan = layer_plan(cfg)
    keep = (kept_names(cfg, layers, h) if cfg.remat_layers
            else [()] * len(plan))
    wrapped: Dict[Tuple[str, ...], Callable] = {}
    counts = []
    for (kind, i), names in zip(plan, keep):
        one = mixer_apply
        if cfg.remat_layers:
            if names not in wrapped:
                wrapped[names] = remat_layer(mixer_apply, len(plan), names,
                                             static_argnums=(0, 1))
            one = wrapped[names]
        h, c = one(cfg, kind, jax.tree.map(lambda x: x[i], layers[kind]), h)
        if c is not None:
            counts.append(c)
    return h, counts


def compute_cast(cfg: ModelConfig, tree: Dict) -> Dict:
    """Storage -> compute dtype for every leaf but :data:`FLOAT32_LEAVES`."""
    dtype = jnp.dtype(cfg.dtype)

    def cast(path, x):
        names = {getattr(p, "key", None) for p in path}
        return x if names & FLOAT32_LEAVES else x.astype(dtype)

    return jax.tree_util.tree_map_with_path(cast, tree)


def check_mesh(cfg: ModelConfig, mesh) -> None:
    """The patterned stack runs as ONE pipeline stage on a mesh without
    model/seq/expert axes; anything else is a named error, not another
    program."""
    if cfg.arch != "nemotron_h":
        return
    missing = []
    if mesh.shape.get("pipe", 1) > 1:
        missing.append("a patterned stack cut into pipeline stages "
                       "(parallel/pipeline.py:stack_stage_layers stacks "
                       "identical layers only)")
    for axis, what in (("model", "tensor-parallel"), ("seq", "sequence-"
                       "parallel"), ("expert", "expert-parallel exchange of")):
        if mesh.shape.get(axis, 1) > 1:
            missing.append(f"{what} Mamba-2, short-convolution, latent-"
                           f"attention and expert layers ('{axis}' axis)")
    if missing:
        raise NotImplementedError(
            "arch='nemotron_h' runs on one pipeline stage; not written: "
            + "; ".join(missing))


@functools.partial(jax.jit, static_argnums=0)
def routing_stats(cfg: ModelConfig, params: Dict, tokens: jax.Array) -> Dict:
    """What the expert layers do with ``tokens`` [B, S], per expert layer in
    pattern order: ``tokens_per_expert`` [layers, held] and ``max_over_mean``
    [layers] (the busiest held expert over the mean). One forward pass
    through the layers, jitted, for tests and operators (``scripts/train.py``
    logs :func:`describe_routing` of it at ``fit``'s log points)."""
    from .transformer import compute_cast as cast, embed_apply  # circular
    params = cast(cfg, params)
    h = embed_apply(cfg, params["embed"], tokens)
    _, counts = stack_apply(cfg, params["layers"], h)
    if not counts:
        raise ValueError(f"pattern {cfg.hybrid_override_pattern!r} has no "
                         "expert layer")
    counts = jnp.stack(counts)
    return {"tokens_per_expert": counts,
            "max_over_mean": counts.max(-1) / jnp.maximum(
                counts.mean(-1, dtype=jnp.float32), 1e-9)}


def describe_routing(stats: Dict) -> str:
    """One line for a log: per expert layer, the busiest held expert."""
    stats = jax.device_get(stats)
    return "; ".join(
        f"E{i}: max/mean {m:.2f}, busiest {int(c.max())} of {int(c.sum())} "
        "local assignments"
        for i, (c, m) in enumerate(zip(stats["tokens_per_expert"],
                                       stats["max_over_mean"])))


def not_served(what: str, cfg: ModelConfig) -> None:
    """``models/generate.py`` and ``serving/`` keep a KV cache per layer and
    nothing else: no recurrent state, no expert layer."""
    if cfg.arch == "nemotron_h":
        raise NotImplementedError(
            f"{what}: arch='nemotron_h' is not written for generation — a "
            "decode step of a Mamba-2 layer needs its convolution window and "
            "state-space state cached beside the attention layers' keys and "
            "values, a short-convolution layer its window of gated inputs, "
            "a latent-attention layer its compressed key-value "
            "latent and shared rotary key (and the absorbed products that "
            "read them), and an expert layer a decode path")
