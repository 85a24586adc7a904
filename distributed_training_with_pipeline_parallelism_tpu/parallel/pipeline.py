"""SPMD pipeline-parallel executor: tick tables -> one jitted program.

TPU-native replacement for the reference's entire L2+L1 stack (SURVEY.md §1):
where torch builds per-process ``PipelineStage`` objects exchanging
activations via batched gloo P2P (``stage.py:463-603``) under a Python
schedule loop (``schedules.py:740``), here the *whole pipeline* — all stages,
all microbatches, forward and backward — is a single ``shard_map``-ped
program over a ``Mesh(('data', 'pipe'))``:

- **Stage placement**: layer parameters are stacked ``[D, V, layers/stage, ...]``
  and sharded over the 'pipe' axis — the pytree-partition equivalent of the
  reference's ``manual_model_split`` module deletion
  (``LLMsDistributedTrainingHelper.py:60-94``), including the interleaved wrap
  placement ``stage = rank + world_size * v`` (``:208``).
- **Transport**: every tick ends with two ``jax.lax.ppermute`` ring shifts
  (+1 for activations, -1 for gradients) — the ICI-native replacement for
  ``dist.batch_isend_irecv`` over gloo/TCP (SURVEY.md U6). Shapes are static
  under jit, so the reference's runtime shape-metadata exchange
  (``stage.py:1720-1744``) has no equivalent here at all.
- **Schedule execution**: a ``lax.scan`` over the compiled tick table
  (:mod:`.schedules`). Each tick conditionally runs one forward or backward
  unit; devices idle in the bubble run the (cheap) false branches. This is
  the SPMD analog of upstream's lowered action-IR interpreter
  (``_PipelineScheduleRuntime._step_microbatches``, ``schedules.py:2407``).
- **Backward**: rematerializing — the forward unit saves only the stage
  *input* per in-flight microbatch in a slot-addressed rotating buffer sized
  from the schedule's actual activation lifetimes (so 1F1B keeps its
  O(in-flight) ~ O(D) activation-memory advantage over GPipe's O(M));
  the backward unit recomputes the stage forward under ``jax.value_and_grad``
  — one extra stage forward per backward, the standard TPU trade of MXU FLOPs
  for HBM (SURVEY.md §7 hard-part (b)).
- **Loss / grad semantics**: token-mean CE per microbatch on the last stage,
  accumulated across microbatches and scaled by 1/M — reproducing upstream's
  ``scale_grads`` behavior (``schedules.py:692-694``) and the reference's
  ``tokenwise_loss_fn`` (``LLMsDistributedTrainingHelper.py:197-201``), so a
  pipeline step's (loss, grads) match a single-device full-batch step.
"""

from __future__ import annotations

import logging
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..models.transformer import (body_apply, compute_cast, embed_apply,
                                  head_apply, head_norm_apply,
                                  transformer_loss)
from ..ops.layers import (global_pad_scale, linear_apply, remat_layer,
                          select_masked_xent_sum, select_xent)
from ..utils.config import ModelConfig, ScheduleConfig
from .mesh import (DATA_AXIS, EXPERT_AXIS, MODEL_AXIS, PIPE_AXIS,
                   SEQ_AXIS)
from .schedules import (BANK_BEFORE_B, BANK_BEFORE_F, BANK_BEFORE_W,
                        BANK_END, COL_BWD_ASLOT, COL_BWD_GSLOT,
                        COL_BWD_LOCAL_SLOT, COL_BWD_M, COL_BWD_V,
                        COL_FWD_LOCAL_SLOT, COL_FWD_M,
                        COL_FWD_SLOT, COL_FWD_V, COL_STORE_B_POS_SLOT,
                        COL_STORE_B_SLOT, COL_STORE_F_NEG_SLOT,
                        COL_STORE_F_SLOT, COL_W_ASLOT, COL_W_GSLOT, COL_W_M,
                        COL_W_V, CompiledSchedule, compile_schedule,
                        overlap_bank_stages)


def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


Pytree = Any


def _fsdp_shard_dims(cfg: ModelConfig, n_data: int, T: int = 1) -> Pytree:
    """Per-leaf 'data'-shard dim under pp x fsdp (ZeRO-3): for MATRICES
    (q/k/v/o/ffn weights — template leaves are layer-stacked ``[L, w0,
    ...]``, so a matrix has ndim >= 3) the first weight dim that (a) is not
    Megatron-sharded over 'model' when ``T > 1`` — the round-4 pp x fsdp x
    tp composition puts 'data' and 'model' on DIFFERENT dims of the same
    leaf — and (b) divides ``n_data``. ``-1`` = replicated over 'data'
    (norm scales, biases: they are O(dim), noise next to the matrices, and
    sharding them would add latency-bound collectives per tick for
    nothing). Dim indices are the layer template's ([L, w0, w1, ...]);
    the executor's stacked [D, V, lps, w0, ...] layout offsets them by +2,
    while the in-shard_map gathers/scatters (chunk-selected [lps, w0,
    ...]) use them as-is. The SINGLE source of the layout —
    ``make_pipeline_grad_fn``'s in/out specs and ``fsdp_shard_params``'s
    placement must agree or jit silently reshards every leaf every step."""
    from ..models.transformer import transformer_init
    template = jax.eval_shape(
        lambda: transformer_init(jax.random.key(0), cfg))["layers"]
    if T > 1:
        from .tensor_parallel import _layer_specs
        tp_specs = _layer_specs(cfg)
    else:
        tp_specs = jax.tree.map(lambda _: P(), template)

    def dim_for(leaf, spec):
        if leaf.ndim < 3:
            return -1
        entries = tuple(spec) + (None,) * (leaf.ndim - len(tuple(spec)))
        for dim in range(1, leaf.ndim):
            if entries[dim] is None and leaf.shape[dim] % n_data == 0:
                return dim
        return -1

    return jax.tree.map(dim_for, template, tp_specs,
                        is_leaf=lambda x: isinstance(x, P))


def _dense_layer_specs(cfg: ModelConfig, T: int, fsdp_dims) -> Pytree:
    """Stacked-layout ([D, V, lps, w0, ...]) PartitionSpecs for dense
    stages: the Megatron 'model' placement (``T > 1``) merged with the
    per-leaf fsdp 'data' dims (stacked offset +2). Each leaf carries at
    most one axis per dim — :func:`_fsdp_shard_dims` picked 'data' dims
    disjoint from the 'model' ones."""
    if T > 1:
        from .tensor_parallel import pipeline_layer_specs
        base = pipeline_layer_specs(cfg, PIPE_AXIS)
    else:
        base = jax.tree.map(lambda _: P(PIPE_AXIS), fsdp_dims)
    if fsdp_dims is None:
        return base
    return _merge_fsdp_into_stacked(base, fsdp_dims)


def _merge_fsdp_into_stacked(base: Pytree, fsdp_dims: Pytree) -> Pytree:
    """Overlay per-leaf fsdp 'data' dims (template space, offset +2 for
    the stacked [D, V, lps, ...] layout) onto stacked PartitionSpecs."""

    def merge(spec, dm):
        if dm < 0:
            return spec
        e = list(tuple(spec))
        e += [None] * (dm + 3 - len(e))
        assert e[dm + 2] is None, (spec, dm)
        e[dm + 2] = DATA_AXIS
        return P(*e)

    return jax.tree.map(merge, base, fsdp_dims,
                        is_leaf=lambda x: isinstance(x, P))


def _compile(name: str, D: int, V: int, M: int) -> CompiledSchedule:
    """Compile via the native C++ engine where it could be built (bit-identical
    to the Python compiler — see tests/test_native_engine.py), else in Python.
    Custom registered schedules always compile in Python (their order
    functions are Python). With ``DTPP_VERIFY_TABLES`` set, the compiled
    table additionally passes the static hazard verifier
    (``analysis.table_check``) before it reaches the executor."""
    from ..analysis import maybe_verify_schedule
    from . import native
    from .schedules import is_custom, verify_artifact_pin
    if is_custom(name) or name == "ZBV":
        # custom orders are Python functions; ZBV's order is synthesized by
        # a Python greedy simulation the C++ engine does not mirror
        cs = compile_schedule(name, D, V, M)
        maybe_verify_schedule(cs)
        return cs
    if native.native_available():
        cs = native.compile_schedule_native(name, D, V, M)
    else:  # no C++ toolchain here: the Python reference implementation
        cs = compile_schedule(name, D, V, M)
    # Artifact-backed names always take the is_custom path above (their
    # order fns are Python), but re-check the pin here too so a native
    # table can never shadow a certified artifact name.
    verify_artifact_pin(cs)
    maybe_verify_schedule(cs)
    return cs


# ---------------------------------------------------------------------------
# Stage slicing: full-model pytree <-> stacked per-device layout
# ---------------------------------------------------------------------------


def _stage_index_map(placement: str, D: int, V: int):
    """[D, V] array: global stage held by (device, chunk)."""
    import numpy as np

    from .schedules import placement_stage_of
    return np.array([[placement_stage_of(placement, d, v, D)
                      for v in range(V)] for d in range(D)])


def stack_stage_layers(layers: Pytree, n_devices: int, n_virtual: int,
                       placement: str = "wrap") -> Pytree:
    """[L, ...] leaves -> [D, V, L/S, ...]: device d, chunk v holds global
    stage ``placement_stage_of(d, v)`` — wrap (the reference's
    ``stage = rank + world_size * v``) or vshape (ZB-V)."""

    def reshape(x):
        L = x.shape[0]
        S = n_devices * n_virtual
        if L % S != 0:
            raise ValueError(f"n_layers={L} must divide evenly into {S} stages")
        lps = L // S
        if placement == "wrap":
            return (x.reshape(n_virtual, n_devices, lps, *x.shape[1:])
                    .swapaxes(0, 1))
        idx = _stage_index_map(placement, n_devices, n_virtual)
        return x.reshape(S, lps, *x.shape[1:])[idx]

    return jax.tree.map(reshape, layers)


def unstack_stage_layers(stacked: Pytree, placement: str = "wrap") -> Pytree:
    """Inverse of :func:`stack_stage_layers`: [D, V, lps, ...] -> [L, ...]."""

    def reshape(x):
        D, V, lps = x.shape[:3]
        if placement == "wrap":
            return x.swapaxes(0, 1).reshape(V * D * lps, *x.shape[3:])
        idx = _stage_index_map(placement, D, V).reshape(-1)  # [D*V] -> stage
        flat = x.reshape(D * V, lps, *x.shape[3:])
        import numpy as np
        inv = np.argsort(idx)  # stage -> (d, v) flat position
        return flat[inv].reshape(V * D * lps, *x.shape[3:])

    return jax.tree.map(reshape, stacked)


# ---------------------------------------------------------------------------
# The executor
# ---------------------------------------------------------------------------



def _masked_store(buf, reg, slot):
    """Bank ``reg`` into ``buf[slot]`` when slot >= 0, else no-op (shared by
    the training and forward-only executors)."""
    active = slot >= 0
    ss = jnp.maximum(slot, 0)
    new = jnp.where(active, reg, buf[ss])
    return buf.at[ss].set(new)


def _stage_ce(cfg, head_p, embed_p, y, tgt, *, tp_axis, T,
              tp_vocab_parallel, pad_scale, loss_norm):
    with jax.named_scope("pp/loss"):
        return _stage_ce_impl(cfg, head_p, embed_p, y, tgt, tp_axis=tp_axis,
                              T=T, tp_vocab_parallel=tp_vocab_parallel,
                              pad_scale=pad_scale, loss_norm=loss_norm)


def _stage_ce_impl(cfg, head_p, embed_p, y, tgt, *, tp_axis, T,
                   tp_vocab_parallel, pad_scale, loss_norm):
    """Last-stage cross entropy for one microbatch — plain, ignore-index
    masked, or Megatron vocab-parallel (incl. the tied-embedding vocab-row
    slice). The ONE implementation shared by the training executor's stage
    objective and the forward-only eval executor, so train and eval losses
    cannot drift. With pad masking the returned value is the masked SUM
    scaled by the caller's global ``pad_scale`` (which absorbs
    ``loss_norm``); otherwise the token mean divided by ``loss_norm``.

    Under vocab-parallel + tied embeddings each model shard uses its
    vocab-row slice of the (replicated) embedding as the local head
    columns; ``tp_copy`` on the table makes the backward psum the
    per-shard partial row-grads into the full table grad, while the
    stage-0 lookup grad stays unwrapped (it is computed replicated, so a
    psum would T-fold it)."""
    if tp_vocab_parallel:
        with jax.named_scope("model/head_loss"):
            return _vocab_parallel_ce(cfg, head_p, embed_p, y, tgt, tp_axis,
                                      T, pad_scale, loss_norm)
    logits = head_apply(cfg, head_p, y, embed=embed_p)  # sets the scope too
    with jax.named_scope("model/head_loss"):
        if cfg.pad_token_id is not None:
            s, _ = select_masked_xent_sum(cfg.use_fused_xent)(
                logits, tgt, cfg.pad_token_id)
            return s * pad_scale  # scale absorbs loss_norm
        return select_xent(cfg.use_fused_xent)(logits, tgt) / loss_norm


def _vocab_parallel_ce(cfg, head_p, embed_p, y, tgt, tp_axis, T, pad_scale,
                       loss_norm):
    """Megatron parallel CE: head matmul column-split over 'model'; the
    [mb, s, V] logits never materialize."""
    from ..ops.collectives import (tp_copy, vocab_parallel_masked_xent_sum,
                                   vocab_parallel_xent)
    yn = tp_copy(head_norm_apply(cfg, head_p, y), tp_axis)
    if cfg.tie_embeddings:
        v_loc = cfg.vocab_size // T
        my = jax.lax.axis_index(tp_axis)
        tok = tp_copy(embed_p["tok"], tp_axis)
        w_loc = jax.lax.dynamic_slice_in_dim(tok, my * v_loc, v_loc, 0)
        logits_local = yn @ w_loc.T
    else:
        logits_local = linear_apply(head_p["out"], yn)
    if cfg.pad_token_id is not None:
        s, _ = vocab_parallel_masked_xent_sum(
            logits_local, tgt, tp_axis, cfg.pad_token_id)
        return s * pad_scale  # scale absorbs loss_norm
    return vocab_parallel_xent(logits_local, tgt, tp_axis) / loss_norm


def _check_tp_divisibility(cfg: ModelConfig, T: int) -> None:
    """Megatron-TP shape contract, shared by every builder that accepts a
    'model' axis (train step, forward-only loss, batch inference) so the
    three cannot drift."""
    if T <= 1:
        return
    n_kv = cfg.n_kv_heads or cfg.n_heads
    if cfg.n_heads % T or n_kv % T or cfg.ffn_dim % T:
        raise ValueError(
            f"tensor parallelism needs n_heads ({cfg.n_heads}), "
            f"n_kv_heads ({n_kv}) and ffn_dim ({cfg.ffn_dim}) divisible "
            f"by the model-axis size {T}")


def _moe_layer_specs(cfg: ModelConfig, moe, T: int, n_ep: int) -> Pytree:
    """Per-leaf PartitionSpecs for the stacked MoE layer pytree.

    Stacked MoE layer layout [D, V, lps, ...]: expert stacks (leading
    expert dim = axis 3) sharded over 'expert'; with a model axis the
    attention heads and each expert's ffn dim are additionally
    Megatron-split (w1/b1 column, w2 row, router/norms/b2 replicated).
    Specs are derived per-leaf from the real layer tree (eval_shape: no
    arrays materialize) via the shared EP predicate. Shared by the
    training executor and the forward-only eval program so the two cannot
    disagree about where an expert leaf lives."""
    from ..models.moe import moe_layer_init
    from .expert_parallel import is_expert_leaf
    template = jax.eval_shape(
        lambda: moe_layer_init(jax.random.key(0), cfg, moe))

    def moe_leaf_spec(path, _):
        keys = [p.key for p in path if hasattr(p, "key")]
        ep = EXPERT_AXIS if (n_ep > 1 and is_expert_leaf(path)) else None
        if T > 1 and "moe" in keys:
            name = keys[-1]
            # stacked dims [pipe, V, lps] then [E(, dim/ffn), ...]
            moe_specs = {"w1": P(PIPE_AXIS, None, None, ep, None,
                                 MODEL_AXIS),
                         "b1": P(PIPE_AXIS, None, None, ep, MODEL_AXIS),
                         "w2": P(PIPE_AXIS, None, None, ep, MODEL_AXIS,
                                 None),
                         "b2": P(PIPE_AXIS, None, None, ep, None)}
            return moe_specs.get(name, P(PIPE_AXIS))  # router: replicated
        if T > 1 and "attn" in keys:
            proj, wb = keys[-2], keys[-1]
            if proj == "o":  # row-parallel; bias replicated, added once
                return (P(PIPE_AXIS, None, None, MODEL_AXIS, None)
                        if wb == "w" else P(PIPE_AXIS))
            return (P(PIPE_AXIS, None, None, None, MODEL_AXIS)
                    if wb == "w" else P(PIPE_AXIS, None, None, MODEL_AXIS))
        if ep is not None:
            return P(PIPE_AXIS, None, None, EXPERT_AXIS)
        return P(PIPE_AXIS)

    return jax.tree_util.tree_map_with_path(moe_leaf_spec, template)


def _moe_template_specs(cfg: ModelConfig, moe, T: int, n_ep: int) -> Pytree:
    """Full-model-layout ([L, w0, ...]) PartitionSpecs for MoE layer
    leaves: :func:`_moe_layer_specs`' stacked [D, V, lps, ...] placement
    with the three leading stack dims dropped (entry 0 — the layer-stack
    dim — left free for the caller to claim, e.g. 'pipe' in
    :func:`fsdp_shard_params`'s resting layout)."""
    stacked = _moe_layer_specs(cfg, moe, T, n_ep)

    def unstack(spec):
        return P(None, *tuple(spec)[3:])

    return jax.tree.map(unstack, stacked,
                        is_leaf=lambda x: isinstance(x, P))


def _moe_fsdp_shard_dims(cfg: ModelConfig, moe, n_data: int, T: int,
                         n_ep: int) -> Pytree:
    """MoE twin of :func:`_fsdp_shard_dims` (pp x fsdp x MoE, VERDICT r4
    item 3): per-leaf 'data'-shard dim chosen to avoid BOTH the Megatron
    'model' dim and the expert dim the EP axis owns — e.g. w1 [L, E, d, f]
    under ep x tp shards 'data' on d, the only free matrix dim. The
    router and per-expert biases (b1/b2) stay replicated, mirroring the
    dense rule's treatment of norms/biases (O(dim·E) leaves, noise next
    to the expert matrices, and sharding them would add latency-bound
    collectives per tick). Dim indices are the layer-STACKED template's
    ([L, w0, ...]) — same conventions as the dense helper, which is why
    the template comes from ``moe_lm_init``'s vmapped layer stack, not
    the per-layer ``moe_layer_init`` (per-layer leaves would shift every
    dim by one and misclassify [d, d] attention matrices as biases)."""
    from ..models.moe import moe_lm_init
    template = jax.eval_shape(
        lambda: moe_lm_init(jax.random.key(0), cfg, moe))["layers"]
    specs = _moe_template_specs(cfg, moe, T, n_ep)

    def dim_for(path, leaf, spec):
        keys = [p.key for p in path if hasattr(p, "key")]
        if leaf.ndim < 3 or "router" in keys or keys[-1] in ("b1", "b2"):
            return -1
        entries = tuple(spec) + (None,) * (leaf.ndim - len(tuple(spec)))
        for dim in range(1, leaf.ndim):
            if entries[dim] is None and leaf.shape[dim] % n_data == 0:
                return dim
        return -1

    return jax.tree_util.tree_map_with_path(
        dim_for, template, specs, is_leaf=lambda x: isinstance(x, P))


def _resolve_fsdp_dims(cfg: ModelConfig, moe, n_data: int, T: int,
                       n_ep: int, fsdp: bool):
    """The per-leaf fsdp 'data'-dim map shared by the training executor,
    the forward-only eval program, and ``fsdp_shard_params`` — one
    resolution site so train/eval/placement can never disagree about
    where a leaf's 'data' shard lives (the silent-reshard drift the
    helpers' docstrings warn about)."""
    if not fsdp:
        return None
    if moe is not None:
        return _moe_fsdp_shard_dims(cfg, moe, n_data, T, n_ep)
    return _fsdp_shard_dims(cfg, n_data, T)


def _stage_param_specs(cfg: ModelConfig, moe, T: int, n_ep: int, fsdp: bool,
                       fsdp_dims, tp_vocab_parallel: bool):
    """``(layer_spec, head_spec)`` — where the executors' shard_maps take the
    parameters in and hand the gradients out, so also where parameters and
    optimizer moments REST between steps (:func:`param_shardings`). Shared
    by the training executor and the forward-only eval program.

    Layers ride the stacked [D, V, lps, ...] layout: 'pipe' on the device
    dim; Megatron 'model' placement (heads and FFN hidden column-split,
    o/down row-split) and the per-leaf fsdp 'data' dims merged in — pp x
    tp, pp x fsdp and pp x fsdp x tp all come from the one helper. The
    embedding is replicated (``P()`` at every call site)."""
    if moe is not None:
        layer_spec = _moe_layer_specs(cfg, moe, T, n_ep)
        if fsdp_dims is not None:
            layer_spec = _merge_fsdp_into_stacked(layer_spec, fsdp_dims)
    elif T > 1 or fsdp:
        layer_spec = _dense_layer_specs(cfg, T, fsdp_dims)
    else:
        layer_spec = P(PIPE_AXIS)
    if tp_vocab_parallel and not cfg.tie_embeddings:
        # vocab-sharded head: out.w [dim, V] column-split, bias (ref arch)
        # split with it; the norm stays replicated
        out_spec = ({"w": P(None, MODEL_AXIS), "b": P(MODEL_AXIS)}
                    if cfg.arch == "ref_decoder"
                    else {"w": P(None, MODEL_AXIS)})
        head_spec = {"norm": P(), "out": out_spec}
    else:
        # tied + vocab-parallel: the head is only the norm; the vocab split
        # is a row-slice of the replicated embedding inside the objective
        head_spec = P()
    return layer_spec, head_spec


def _batch_spec(n_seq: int, n_ep: int) -> P:
    if n_seq > 1:
        # with an expert axis too (MoE x seq, round 5) the batch shards
        # over data x expert while the sequence shards over seq
        lead = (DATA_AXIS, EXPERT_AXIS) if n_ep > 1 else DATA_AXIS
        return P(lead, SEQ_AXIS)
    if n_ep > 1:
        return P((DATA_AXIS, EXPERT_AXIS))  # batch over data x expert
    return P(DATA_AXIS)


def model_init(cfg: ModelConfig, moe=None) -> Callable[[jax.Array], Pytree]:
    """``key -> params``: the init of the full-model pytree the executors
    take — ``moe_lm_init`` with a MoEConfig, else ``transformer_init``."""
    if moe is not None:
        from ..models.moe import moe_lm_init
        return lambda key: moe_lm_init(key, cfg, moe)
    from ..models.transformer import transformer_init
    return lambda key: transformer_init(key, cfg)


def param_shardings(cfg: ModelConfig, mesh: Mesh, moe=None,
                    fsdp: bool = False,
                    tp_vocab_parallel: bool = False) -> Pytree:
    """Where the full-model pytree rests on ``mesh`` between steps: one
    ``NamedSharding`` per leaf of ``transformer_init`` (``moe_lm_init`` with
    ``moe``), read off the executor's own in/out specs
    (:func:`_stage_param_specs`) with the stacked [D, V, lps, ...] layer
    dims folded back onto the ``[L, ...]`` layer axis — layer leaves
    'pipe'-sharded there (plus their 'model'/'data'/'expert' dims),
    embedding replicated, head as the executor takes it. Initialise INTO
    this layout (``jax.jit(init, out_shardings=...)``) and each device only
    ever holds its stages' share; gradients leave the shard_map in the same
    layout, so the elementwise optimizer update keeps it. With
    ``n_virtual > 1`` the wrap placement's strided stage->device map makes
    the per-step stacking a (sharded) permute; with V=1 it is movement-free."""
    from jax.sharding import NamedSharding

    from ..models.nemotron_h import check_mesh
    check_mesh(cfg, mesh)  # a patterned stack rests on one stage only
    n_data = mesh.shape.get(DATA_AXIS, 1)
    T = mesh.shape.get(MODEL_AXIS, 1)
    n_ep = mesh.shape.get(EXPERT_AXIS, 1)
    layer_spec, head_spec = _stage_param_specs(
        cfg, moe, T, n_ep, fsdp,
        _resolve_fsdp_dims(cfg, moe, n_data, T, n_ep, fsdp),
        tp_vocab_parallel)
    shapes = jax.eval_shape(model_init(cfg, moe), jax.random.key(0))

    def is_spec(x):
        return isinstance(x, P)

    specs = {"embed": P(), "head": head_spec,
             "layers": jax.tree.map(  # [D, V, lps, w...] -> [L, w...]
                 lambda sp: P(*(tuple(sp)[:1] + tuple(sp)[3:])), layer_spec,
                 is_leaf=is_spec)}
    # each spec may stand for a whole subtree (P() for the embedding)
    return jax.tree.map(
        lambda sp, sub: jax.tree.map(lambda _: NamedSharding(mesh, sp), sub),
        specs, shapes, is_leaf=is_spec)


def _check_patterned_stack(cfg: ModelConfig, mesh: Mesh,
                           sched: ScheduleConfig, moe, fsdp: bool) -> None:
    """A patterned stack (``arch='nemotron_h'``: per-kind stacks walked in
    pattern order) is ONE stage: ``stack_stage_layers`` cuts stacks of
    identical layers only. What is not written raises by name."""
    if cfg.arch != "nemotron_h":
        return
    from ..models.nemotron_h import check_mesh
    check_mesh(cfg, mesh)
    if sched.n_virtual > 1 or fsdp or moe is not None:
        raise NotImplementedError(
            "arch='nemotron_h' runs as one pipeline stage with its own "
            "expert layers: virtual stages, fsdp=True and moe= (the "
            "capacity-routed MoEConfig blocks of models/moe.py) are not "
            "written for it")


def _check_moe_mesh(cfg: ModelConfig, moe, T: int, n_seq: int,
                    n_ep: int) -> None:
    """The MoE mesh-composition contract, shared by the training executor
    and the forward-only eval program (raise identically on both).

    A seq axis composes since round 5: attention rides the ring/Ulysses
    transport while the (position-wise) MoE FFN routes each shard's
    local tokens with local capacity — the EP path's local-routing
    semantics applied to the sequence dimension. Dropout composes too:
    the residual/FFN masks are the full-sequence masks' local slices
    (``sharded_dropout_apply``, the dense sp path's rule)."""
    if cfg.arch != "gpt2":
        raise ValueError("MoE pipeline blocks are gpt2-style; set "
                         "arch='gpt2'")
    if moe.n_experts % n_ep:
        raise ValueError(f"n_experts={moe.n_experts} must divide over "
                         f"{n_ep} expert shards")
    if T > 1 and (moe.ffn_dim or cfg.ffn_dim) % T:
        raise ValueError(
            f"MoE expert ffn_dim={moe.ffn_dim or cfg.ffn_dim} must be "
            f"divisible by the model-axis size {T}")


# Auto-unroll threshold for the tick executor: tables at or below this many
# tick rows compile as straight-line code (each row's units traced once
# more), above it the lax.scan form keeps compile time bounded. Set from
# round-5 v5e measurements (docs/performance.md "Unroll-vs-scan
# crossover"; GPipe D=1 remat executor, per-microbatch shapes fixed):
# unrolled beats scanned at EVERY size measured — 1.19-1.20x through 32
# rows, narrowing to ~1.05x at 48-64 rows — so there is no throughput
# crossover to encode; the binding cost is compile time, which grows
# ~2.2 s/row (14 s at 8 rows -> 140 s at 64).
# 64 rows covers every ladder config (Interleaved D=4/V=2/M=8 = 38 rows,
# GPipe D=1 M=32 = 64) at <= ~2.5 min compile; beyond it the measured win
# trend (shrinking) no longer justifies unbounded compile growth. Callers
# iterating interactively can pass unroll_ticks=False for ~7 s compiles.
_UNROLL_TICKS_LIMIT = 64
# The FORWARD-only executor (make_pipeline_forward / eval) keeps the
# round-4 budget: its per-row economics (forward ticks, no backward) were
# not part of the round-5 measurement.
_UNROLL_FWD_TICKS_LIMIT = 32


def _concrete_know(col_vals):
    """Concrete (unrolled-tick) knowledge of a unit predicate across the
    pipe axis: True = every device takes the unit, False = none does,
    None = mixed, or no concrete row (the scan path)."""
    if col_vals is None:
        return None
    if (col_vals >= 0).all():
        return True
    if (col_vals < 0).all():
        return False
    return None


# Test instrumentation for the phase-compressed executor: when set, called
# once per PYTHON TRACE of a phase body (not per scanned tick) — the
# compile-counter tests assert the trace count tracks unique patterns, not
# table length (tests/test_pipeline.py::test_phase_executor_trace_count).
_PHASE_TRACE_HOOK = None

logger = logging.getLogger(__name__)


def _phase_compressed_ticks(tick, carry, table, phases, bank_stages=None):
    """Drive a tick program as per-phase ``lax.scan`` s with per-pattern
    specialized bodies — the ``unroll_ticks="phases"`` executor core,
    shared by the training and forward-only programs.

    ``phases`` is :func:`..schedules.compress_schedule`'s segmentation of
    the host-side table. Each phase is refined to its MINIMAL MASK PERIOD
    ``q``: the affine descriptor needs a period long enough for slot
    indices to advance affinely (a full slot-reuse cycle, which grows with
    M in 1F1B's steady state), but the executor feeds the real table rows
    as scanned inputs, so only the active/idle structure has to repeat —
    the steady state's F/B alternation is a 2-tick body regardless of M.
    Each distinct (mask pattern, successor mask) builds ONE body closure,
    memoized so repeated patterns (and every same-shaped length-1
    warmup/cooldown row) share a single trace: ``lax.scan`` caches body
    jaxprs per function object, so compile cost scales with unique
    patterns, not ticks. Inside a body every tick gets the exact
    per-position mask as its concrete row (cond elision via ``know``,
    store elision) and the next position's mask as ``next_concrete``
    (dead-ppermute elision); at a phase boundary the next mask is the
    union of the in-phase position 0 and the successor phase's first row —
    conservative is sound, because a ppermute whose arrival no device
    banks is dead (``_masked_store`` skips slot -1), so results stay
    bit-exact against the plain scan executor.

    ``bank_stages`` (opt-in, ``[T, 4]`` int from ``..schedules.
    overlap_bank_stages``) enables the double-buffered ring discipline:
    each body position banks its ring arrivals at the per-position stage
    folded over every tick the position covers (min across blocks —
    banking earlier than latest-safe is always lockstep-correct). The
    stage tuple joins the memo key, so two phases sharing a mask pattern
    but differing in bank stages compile separate bodies."""
    memo = {}
    n_cols = phases[0].base.shape[-1]
    end_mask = np.full(phases[0].base.shape[1:], -1, np.int32)  # [D, C]

    def pseudo(mask):
        """bool mask [D, C] -> a concrete row stand-in (0 active, -1 idle):
        exactly the information the elision checks read from real rows."""
        return np.where(mask, 0, -1).astype(np.int32)

    for j, ph in enumerate(phases):
        base_mask = ph.base >= 0  # [period, D, C]
        p, L = ph.period, ph.length
        q = next(qq for qq in range(1, p + 1)
                 if p % qq == 0
                 and (base_mask
                      == np.tile(base_mask[:qq], (p // qq, 1, 1))).all())
        masks_q = base_mask[:q]
        succ = (pseudo(phases[j + 1].base[0] >= 0) if j + 1 < len(phases)
                else end_mask)  # after the last tick nothing banks
        if L // q > 1:
            # at the block boundary the next row is position 0 of the next
            # block — except on the last block, where it is the successor
            # phase; the body is one program for all blocks, so take the
            # union (0 = active wins)
            succ = np.maximum(succ, pseudo(masks_q[0]))
        if bank_stages is None:
            st_q = None
        else:
            st_q = bank_stages[ph.start:ph.start + L].reshape(
                L // q, q, -1).min(axis=0)  # [q, 4]
        key = (q, masks_q.tobytes(), succ.tobytes(),
               None if st_q is None else st_q.tobytes())
        if key not in memo:
            rows_c = [pseudo(m) for m in masks_q]
            nxts = rows_c[1:] + [succ]
            stages_c = ([None] * q if st_q is None
                        else [tuple(int(v) for v in st_q[i])
                              for i in range(q)])

            def body(c, xs, _rows=rows_c, _nxts=nxts, _stages=stages_c):
                if _PHASE_TRACE_HOOK is not None:
                    _PHASE_TRACE_HOOK()
                with jax.named_scope("pp/tick_body"):
                    for i, (rc, nc) in enumerate(zip(_rows, _nxts)):
                        # kwarg only when staged: the forward-only tick
                        # (which shares this driver) stays lockstep
                        kw = ({} if _stages[i] is None
                              else {"bank_stages": _stages[i]})
                        c, _ = tick(c, xs[i], concrete=rc, next_concrete=nc,
                                    **kw)
                return c, None

            memo[key] = body
        xs = table[ph.start:ph.start + L].reshape(L // q, q, -1, n_cols)
        with jax.named_scope(f"pp/phase{j}"):
            carry, _ = jax.lax.scan(memo[key], carry, xs)
    return carry


def make_pipeline_grad_fn(cfg: ModelConfig, mesh: Mesh, sched: ScheduleConfig,
                          force_tick_executor: bool = False, moe=None,
                          sp_attn_impl: str = "ring",
                          tp_vocab_parallel: bool = False,
                          fsdp: bool = False,
                          remat_backward=None,
                          unroll_ticks=None,
                          dynamics=None,
                          comm_overlap: str = "none",
                          ) -> Callable[[Pytree, jax.Array, jax.Array],
                                        Tuple[jax.Array, Pytree]]:
    """Build an (unjitted) ``(params, tokens, targets) -> (loss, grads)``
    pipeline step — compose with an optimizer under one jit (see
    :mod:`..utils.train`) or jit directly via :func:`make_pipeline_step`.
    With ``cfg.dropout > 0`` the step takes a fourth argument — a per-step
    PRNG key — and runs train-mode dropout with masks that depend only on
    (key, data shard, microbatch, global layer, site), i.e. independent of
    the (D, V) stage partitioning (tests/test_dropout.py asserts this).

    ``params`` is the full-model pytree from ``transformer_init`` (or
    ``moe_lm_init`` when ``moe`` — a :class:`..models.moe.MoEConfig` — is
    given: stages then run MoE blocks, experts sharded over an 'expert'
    mesh axis when present, and the loss gains the routing aux term,
    microbatch-averaged). ``grads`` comes back in the same layout. ``tokens``/``targets`` are ``[B, S]`` with
    ``B`` divisible by (n_data * n_microbatches); the batch is split over the
    'data' mesh axis, then into microbatches along dim 0 (upstream
    ``DEFAULT_CHUNK_DIM=0``, ``microbatch.py:57``).

    ``remat_backward`` selects the backward's activation policy (measured
    policy table in docs/performance.md "Backward policy"):

    - ``None`` (default, auto): at D == 1 (incl. pure data/tensor/seq
      meshes and the benchmark's ``force_tick_executor`` runs), the
      UNROLLED stored program — microbatches as straight-line code,
      autodiff residuals managed and fused by XLA; measured the fastest
      single-chip formulation. At D > 1, the REMATERIALIZING backward:
      on TPU the backward's stage-forward recompute costs ~1.33x FLOPs on
      the MXU, which measures cheaper than pushing stored residuals
      through HBM scan boundaries at both the reference config and
      gpt2-small seq 1024.
    - ``True``: always rematerialize — the forward unit saves only the
      stage *input*; the backward recomputes the stage forward. Minimal
      activation memory (O(in-flight) stage inputs).
    - ``False``: stored-activation backward — nothing is recomputed,
      matching the reference's torch-autograd semantics (its backward
      stashes, never recomputes — ``LLMsDistributedTrainingHelper.py:
      98-143`` via upstream ``stage.py:857/937``). Phase-separated
      schedules (GPipe/BFS: per-device all-F-then-all-B) differentiate
      through the forward tick scan (:func:`_make_phase_stored_grad_fn`);
      other schedules bank the stage body's ``jax.vjp`` residuals in
      slot-addressed buffers (x-independent residuals — weights, casts,
      RoPE — are re-derived live instead of stored, see
      :mod:`.stored_backward`). Those banks have ``cs.n_act_slots``
      entries, and 1F1B's packed table keeps ``min(M, 2D-1)`` microbatches
      in flight on stage 0 (7 at D = 4, where the unpacked table of before
      PR 29 kept 4): the stored backward's memory grows with it, the
      default's only by that many stage inputs. Raises on configurations
      that cannot support it (split-backward schedules, whose W units
      re-derive parameter grads by design; ``fsdp=True``, where residuals
      would pin the just-in-time-gathered full weights).

    ``unroll_ticks`` selects the tick-executor formulation (docs/
    performance.md "Executor formulations"); it changes the loop form
    only, so it composes with every backward policy and mesh axis:

    - ``True`` (round 4, VERDICT r3 item 2 — the SPMD analog of
      upstream's per-rank lowered-IR execution, ``schedules.py:
      2279-2337``): emit the tick program as straight-line code instead
      of a ``lax.scan`` over table rows. Each tick's per-device COLUMN
      VALUES stay dynamic (``table[t][axis_index]`` scalar reads — one
      program for all devices), but the tick LOOP is a Python loop over
      the concrete table, so the scan boundary — which forces every
      cross-tick value through HBM and blocks forward/backward fusion —
      disappears, and per-tick structure specializes against the
      concrete rows: units that every device takes lose their
      ``lax.cond``, all-idle units and never-banked ring transfers are
      elided entirely (warmup ticks carry no backward ring hop, cooldown
      no forward one). Worth 1.05-1.2x throughput over the scan form on
      v5e, but compile time grows ~2.2 s per table row (14 s at 8 rows,
      ~140 s at 64 — docs/performance.md "Unroll-vs-scan crossover").
    - ``"phases"``: the phase-compressed executor. The table is
      segmented into periodic phases (:func:`..schedules.
      compress_schedule`), each unique active/idle pattern is traced
      ONCE as a specialized body (same concrete-``know`` cond elision
      and dead-ppermute elision as the unrolled form), and each phase
      runs as a ``lax.scan`` feeding the real table rows as scanned
      inputs. Compile cost scales with unique patterns, not ticks —
      steady-state 1F1B is one 2-tick body regardless of M — so large
      tables compile in a handful of traces instead of minutes, while
      per-tick dispatch overhead still disappears.
    - ``False``: one cond-dispatched ``lax.scan`` over the whole table —
      the bounded-compile escape hatch (~7 s regardless of table size;
      pays ``tick_executor_overhead`` per tick). Use when iterating
      interactively.
    - ``None`` (auto, default): ``True`` for tables of at most
      ``_UNROLL_TICKS_LIMIT`` (= 64) rows, ``"phases"`` above (a one-line
      ``logging.info`` records when that auto phase-compression fires).

    ``dynamics`` (truthy, default None) additionally accumulates each
    microbatch's squared gradient norm in an ``[M]`` f32 carry — the
    backward/W units already materialize one gradient per (stage,
    microbatch), and stages partition the (untied) parameters, so a
    pipe-axis psum completes ``|g_m|^2`` with no extra backward work.
    The step then returns ``(loss, grads, sq_mb)``; ``sq_mb[m]`` feeds
    the gradient-noise-scale estimator (:mod:`..utils.dynamics`, data
    replicas averaged — each holds a different microbatch sample).
    Supported on dense untied-embedding pipe x data meshes with the tick
    executor only (raises otherwise: the degenerate 1-stage fast path
    and the phase-stored program never materialize per-microbatch
    grads, and tied embeddings / tensor / seq / expert sharding break
    the stages-partition-the-params norm decomposition). When falsy the
    traced program is byte-identical to a build without the argument
    (tests/test_dynamics.py pins the jaxpr).

    ``comm_overlap`` selects the ring-hop discipline (docs/performance.md
    "Comm/compute overlap"):

    - ``"none"`` (default): lockstep — every tick banks last tick's ring
      arrivals into the edge slots at the tick top, so each ppermute is a
      data dependency of ALL of the next tick's compute.
    - ``"ring"``: double-buffered edge slots. The recv register a
      ppermute lands in is held across the next tick's units and
      committed to its edge slot only at the channel's bank stage — the
      latest point the static classifier
      (:func:`..schedules.overlap_bank_stages`) proves conflict-free —
      so the hop overlaps every unit that doesn't read or write the
      banked slot (in 1F1B's steady state the grad arrival is consumed
      by B, which runs AFTER F: the backward ring hop overlaps the whole
      forward unit). Bit-identical to ``"none"`` by construction
      (tests/test_overlap.py). Requires the unrolled or phase-compressed
      executor — the cond-dispatched scan sees only traced rows, so
      ``unroll_ticks=False`` raises.
    - ``"auto"``: ``"ring"`` whenever the resolved executor supports it
      (unrolled / phases), ``"none"`` otherwise (scan, phase-stored,
      degenerate 1-stage).

    ``fsdp=True`` (pp x fsdp, ZeRO-3 within the pipeline): per-stage layer
    weights live sharded over the 'data' axis (per-leaf weight dim from
    :func:`_fsdp_shard_dims` — use :func:`fsdp_shard_params` to place
    them), each tick's active virtual chunk is all-gathered just in time
    inside the compute unit, and layer gradients are reduce-scattered per
    backward tick, so the grad accumulator carry is sharded too.
    Per-device layer-param residency drops from full-stage to 1/n_data of
    it (+ one transient gathered chunk); grads/optimizer state inherit the
    sharding through the returned pytree. Composes with Megatron TP
    (round 4): on a 3-D ``data x pipe x model`` mesh each matrix leaf is
    'model'-split on its Megatron dim and 'data'-split on a DIFFERENT
    dim, so residency is ~1/(D * T * n_data). Composes with MoE/expert
    stages too (round 5): expert matrices pick a 'data' dim disjoint
    from both the EP-owned expert dim and the Megatron dim
    (:func:`_moe_fsdp_shard_dims`) — expert models are precisely where
    parameter sharding pays. A seq axis composes too (round 5): the
    weight all-gathers ride 'data' while activations shard over 'seq' —
    orthogonal by construction.
    """
    _check_patterned_stack(cfg, mesh, sched, moe, fsdp)
    D = mesh.shape[PIPE_AXIS]
    n_data = mesh.shape.get(DATA_AXIS, 1)
    T = mesh.shape.get(MODEL_AXIS, 1)
    n_seq = mesh.shape.get(SEQ_AXIS, 1)
    n_ep = mesh.shape.get(EXPERT_AXIS, 1)
    V = sched.n_virtual
    M = sched.n_microbatches
    cs: CompiledSchedule = _compile(sched.name, D, V, M)
    tp_axis = MODEL_AXIS if T > 1 else None
    sp_axis = SEQ_AXIS if n_seq > 1 else None
    if sp_attn_impl not in ("ring", "ulysses"):
        raise ValueError(f"sp_attn_impl must be 'ring' or 'ulysses', "
                         f"got {sp_attn_impl!r}")
    if tp_vocab_parallel:
        if T <= 1:
            raise ValueError("tp_vocab_parallel needs a 'model' mesh axis")
        if cfg.vocab_size % T:
            raise ValueError(f"vocab_size={cfg.vocab_size} must divide over "
                             f"the model-axis size {T}")
    # Only ring attention puts a ppermute (flat-pair collective) inside the
    # schedule units; Ulysses' all_to_all is grouped, so its units may keep
    # the efficient cond dispatch.
    uniform_units = sp_axis is not None and sp_attn_impl == "ring"
    units_hold_collectives = T > 1 or n_seq > 1 or n_ep > 1 or fsdp
    _check_tp_divisibility(cfg, T)
    ep_axis = EXPERT_AXIS if n_ep > 1 else None
    if n_ep > 1 and moe is None:
        raise ValueError("mesh has an 'expert' axis but no MoEConfig given")
    if fsdp and n_data <= 1:
        raise ValueError("fsdp=True needs a 'data' mesh axis to shard "
                         "parameters over")
    # fsdp x seq composes (round 5): the weight all-gathers ride the
    # 'data' axis while activations shard over 'seq' — orthogonal by
    # construction, and the epilogue's per-leaf reductions already do the
    # right thing (psum_scatter over 'data' per tick, then the seq psum
    # completes every leaf's token share)
    fsdp_dims = _resolve_fsdp_dims(cfg, moe, n_data, T, n_ep, fsdp)
    use_dropout = cfg.dropout > 0.0
    # pad masking composes with every supported mesh, including MoE/expert
    # stages: the CE is globally valid-count normalized while the routing
    # aux loss stays token-uniform (routing happens for pad positions too —
    # they occupy expert capacity, so load balance legitimately counts them)
    if moe is not None:
        _check_moe_mesh(cfg, moe, T, n_seq, n_ep)
    if comm_overlap not in ("none", "ring", "auto"):
        raise ValueError(f"comm_overlap must be 'none', 'ring', or 'auto', "
                         f"got {comm_overlap!r}")
    dyn = bool(dynamics)
    if dyn:
        blockers = []
        if moe is not None:
            blockers.append("moe")
        if fsdp:
            blockers.append("fsdp")
        if T > 1:
            blockers.append("a 'model' mesh axis")
        if n_seq > 1:
            blockers.append("a 'seq' mesh axis")
        if n_ep > 1:
            blockers.append("an 'expert' mesh axis")
        if cfg.tie_embeddings:
            # the tied embedding takes grads from BOTH the first stage
            # (wgrad through stage_embed) and the last (the head's vocab
            # matmul), so per-stage squared norms no longer sum to
            # |g_m|^2 — the decomposition the accumulator relies on
            blockers.append("tie_embeddings")
        if blockers:
            raise ValueError(
                "dynamics per-microbatch accumulation needs stages to "
                "partition the parameters (dense untied pipe x data "
                "mesh); unsupported here: " + ", ".join(blockers))
    if (D == 1 and n_data == 1 and T == 1 and n_seq == 1 and V == 1
            and moe is None and not use_dropout and not force_tick_executor):
        if dyn:
            raise ValueError(
                "dynamics=True needs the tick executor's per-microbatch "
                "gradients; the degenerate 1-stage fast path computes one "
                "fused full-batch gradient — pass force_tick_executor="
                "True with remat_backward=True")
        # Degenerate 1-stage pipeline == a plain full-batch train step: the
        # microbatch-accumulated, 1/M-scaled loss/grads equal the full-batch
        # mean exactly (asserted in tests/test_pipeline.py), so skip the tick
        # machinery and its rematerializing backward entirely and let XLA
        # fuse the whole step. The schedule was still compiled above, so
        # invalid (name, D, V, M) combinations raise identically.
        def degenerate_step(params, tokens, targets):
            # same config contract as the tick executor's shard_map assert
            assert tokens.shape[0] % M == 0, (
                f"batch {tokens.shape[0]} not divisible by n_microbatches={M}")
            return jax.value_and_grad(
                lambda p: transformer_loss(cfg, p, tokens, targets))(params)

        return degenerate_step
    split = cs.split_backward  # ZB-H1 family: B is dgrad-only, W carries wgrad
    # Backward-policy resolution, from v5e measurements (docs/performance.md
    # "Backward policy"):
    #
    # - D == 1 (any non-split schedule — every schedule's grads are
    #   order-independent and the table is device-symmetric): the UNROLLED
    #   stored program — straight-line microbatch code, autodiff residuals
    #   fused by XLA. Measured fastest (no scan boundary).
    # - D > 1: REMATERIALIZING backward by default. Stored variants
    #   (scan-vjp for phase-separated GPipe/BFS, slot-buffer residual
    #   banking otherwise) are opt-in via remat_backward=False: on TPU the
    #   backward's stage-forward recompute rides the MXU at ~1.33x FLOPs
    #   while stored residuals ride HBM through scan boundaries — measured
    #   SLOWER than remat at both the reference config and gpt2-small
    #   seq 1024 on one chip. (The reference's torch-CPU runtime has the
    #   opposite economics, hence its stash-don't-recompute backward.)
    # - Split-backward schedules and fsdp always rematerialize (W's
    #   recompute fills bubbles by design; fsdp residuals would pin
    #   gathered full weights).
    phase_ok = (not split and cs.placement == "wrap" and moe is None
                and not fsdp
                and (D == 1 or sched.name in ("GPipe", "BFS")))
    if remat_backward is None:
        use_phase = phase_ok and D == 1
        use_stored = False
    elif remat_backward:
        use_phase = use_stored = False
    else:
        if split:
            raise ValueError(
                f"remat_backward=False is incompatible with split-backward "
                f"schedule {sched.name!r}: its W units re-derive parameter "
                f"grads from saved inputs by design (that recompute is what "
                f"fills the bubble ticks)")
        if fsdp:
            raise ValueError(
                "remat_backward=False is incompatible with fsdp=True: the "
                "stage body's residuals would pin each tick's just-in-time "
                "all-gathered full weights per in-flight microbatch, "
                "voiding the ZeRO-3 residency bound")
        use_phase = phase_ok
        use_stored = not phase_ok
    if use_phase:
        if dyn:
            raise ValueError(
                "dynamics=True needs per-microbatch gradients; the "
                "phase-stored program differentiates through its forward "
                "tick scan and never materializes them — pass "
                "remat_backward=True for the tick executor")
        if comm_overlap == "ring":
            raise ValueError(
                "comm_overlap='ring' is incompatible with the phase-stored "
                "backward (it differentiates through the forward tick scan "
                "and has no per-tick bank sites) — pass remat_backward="
                "True/None for the tick executor, or comm_overlap='auto' "
                "to fall back to lockstep here")
        return _make_phase_stored_grad_fn(cfg, mesh, sched, sp_attn_impl,
                                          tp_vocab_parallel)
    n_rows = cs.table.shape[0]
    logger.info(
        "pipeline: %s D=%d V=%d M=%d tick table: %d rows, %d of %d cells "
        "work, %d of them packed (more than one unit in the tick)",
        cs.name, D, V, M, n_rows, cs.work_cells, n_rows * D, cs.packed)
    if unroll_ticks is None:
        # auto: unroll small tables (straight-line specialization, ~2.2 s
        # compile per row); beyond the budget the PHASE-COMPRESSED form —
        # per-pattern specialized scan bodies — replaces the old
        # cond-dispatched whole-table scan as the default
        unroll_ticks = (True if cs.table.shape[0] <= _UNROLL_TICKS_LIMIT
                        else "phases")
        if unroll_ticks == "phases":
            logger.info(
                "pipeline: %d-row tick table exceeds _UNROLL_TICKS_LIMIT=%d; "
                "auto-selecting the phase-compressed executor "
                "(unroll_ticks='phases'; pass unroll_ticks=False for the "
                "bounded-compile scan form, or True to force full unrolling)",
                cs.table.shape[0], _UNROLL_TICKS_LIMIT)
    if unroll_ticks not in (True, False, "phases"):
        raise ValueError(f"unroll_ticks must be True, False, 'phases', or "
                         f"None (auto), got {unroll_ticks!r}")
    if comm_overlap == "auto":
        comm_overlap = "ring" if unroll_ticks in (True, "phases") else "none"
    elif comm_overlap == "ring" and unroll_ticks is False:
        raise ValueError(
            "comm_overlap='ring' needs static per-tick bank stages; the "
            "cond-dispatched scan executor (unroll_ticks=False) sees only "
            "traced rows — use unroll_ticks=True or 'phases' (or "
            "comm_overlap='auto' to fall back to lockstep)")
    bank_stages_tab = (overlap_bank_stages(cs.table)
                       if comm_overlap == "ring" else None)
    if unroll_ticks == "phases":
        from .schedules import compress_schedule
        phases = compress_schedule(cs.table)
    else:
        phases = None
    table = jnp.asarray(cs.table)  # [T, D, N_COLS]
    dtype = jnp.dtype(cfg.dtype)
    fwd_perm = [(i, (i + 1) % D) for i in range(D)]
    bwd_perm = [(i, (i - 1) % D) for i in range(D)]
    # vshape placement (ZB-V): some transfers ride the reverse rings or stay
    # on-device; the last stage lives at (device 0, chunk 1), not (D-1, V-1)
    placement = cs.placement
    reverse_routes = cs.uses_reverse_routes
    from .schedules import (placement_chunk_of, placement_device_of)
    last_dev = placement_device_of(placement, D * V - 1, D)
    last_chunk = placement_chunk_of(placement, D * V - 1, D)

    lps = cfg.n_layers // (D * V)  # layers per stage (stack_stage_layers checks)

    def spmd_fn(layers_stacked, embed, head, tokens, targets, rng_data=None):
        # Shapes inside shard_map: layers_stacked leaves [1, V, lps, ...];
        # embed/head replicated; tokens/targets [B_local, S]; rng_data (train
        # mode, dropout > 0) is the step key's raw data, replicated.
        d = jax.lax.axis_index(PIPE_AXIS)
        layers_local = jax.tree.map(lambda x: x[0], layers_stacked)
        is_first_dev = d == 0
        is_last_dev = d == last_dev  # wrap: D-1; vshape: 0 (the V returns)

        def stage_of(vv):
            """Traced global stage index of this device's chunk vv."""
            if placement == "wrap":
                return vv * D + d
            return jnp.where(vv == 0, d, 2 * D - 1 - d)

        if use_dropout:
            base_rng = jax.random.wrap_key_data(rng_data)
            if n_data > 1:  # decorrelate masks across data replicas
                base_rng = jax.random.fold_in(
                    base_rng, jax.lax.axis_index(DATA_AXIS))
            if n_ep > 1:
                # 'expert' doubles as a batch axis (batch_spec shards the
                # batch over data x expert): each expert shard holds
                # DIFFERENT tokens, so its masks must draw a distinct
                # stream too
                base_rng = jax.random.fold_in(
                    base_rng, jax.lax.axis_index(EXPERT_AXIS))
        else:
            base_rng = None

        def mb_rng(mm):
            """Per-microbatch dropout stream. Masks depend only on (step key,
            data shard, microbatch, global layer, site) — independent of the
            (D, V) stage partitioning, and identical between the forward unit
            and the rematerializing backward of the same microbatch."""
            return None if base_rng is None else jax.random.fold_in(base_rng, mm)

        b_local, seq = tokens.shape
        assert b_local % M == 0, (
            f"local batch {b_local} not divisible by n_microbatches={M}")
        mb = b_local // M
        tokens_mb = tokens.reshape(M, mb, seq)
        targets_mb = targets.reshape(M, mb, seq)
        mb_shape = (mb, seq, cfg.dim)
        # tied embeddings: the head argument of the stage objective bundles
        # the embedding so the last stage's VJP produces its grad
        head_bundle = (head, embed) if cfg.tie_embeddings else head

        def stage_body(layer_p, x, vv=0, mm=0):
            # XProf legibility: every stage-compute op lands under pp/...
            with jax.named_scope("pp/stage_body"):
                return _stage_body_impl(layer_p, x, vv, mm)

        def _stage_body_impl(layer_p, x, vv=0, mm=0):
            """-> (y, aux): aux is the stage's summed routing load-balance
            loss (MoE stages), else a constant 0 that XLA eliminates.
            ``(vv, mm)`` select the dropout stream (train mode): the stack's
            global layer offset is ``(vv*D + d) * lps``."""
            zero = jnp.zeros((), jnp.float32)
            layer_p = compute_cast(cfg, layer_p)  # bf16 compute, fp32 masters
            if moe is not None:
                from ..models.moe import moe_layer_apply
                rng_mb = mb_rng(mm)
                offset = stage_of(vv) * lps

                def mstep(carry, xs):
                    lp, i = xs
                    h, aux = carry
                    # per-layer dropout stream keyed on the GLOBAL layer
                    # index, matching the dense body's convention — masks
                    # are (D, V)-partition invariant
                    rng_l = (None if rng_mb is None
                             else jax.random.fold_in(rng_mb, offset + i))
                    h, a = moe_layer_apply(cfg, moe, lp, h, ep_axis,
                                           tp_axis=tp_axis, tp_size=T,
                                           rng=rng_l, sp_axis=sp_axis,
                                           sp_attn_impl=sp_attn_impl,
                                           sp_size=n_seq)
                    return (h, aux + a), None

                if cfg.remat_layers:
                    mstep = remat_layer(mstep, lps)
                (y, aux), _ = jax.lax.scan(mstep, (x, zero),
                                           (layer_p, jnp.arange(lps)))
                return y, aux
            if sp_axis is None:
                return (body_apply(cfg, layer_p, x, tp_axis=tp_axis,
                                   tp_size=T, rng=mb_rng(mm),
                                   layer_offset=stage_of(vv) * lps), zero)
            # sequence-sharded stage: ring/Ulysses attention across 'seq'
            # (ring optionally Megatron head-sharded over 'model' as well)
            from .seq_parallel import sp_body_apply
            return (sp_body_apply(cfg, layer_p, x, sp_axis,
                                  attn_impl=sp_attn_impl,
                                  tp_axis=tp_axis, tp_size=T,
                                  rng=mb_rng(mm),
                                  layer_offset=stage_of(vv) * lps,
                                  sp_size=n_seq), zero)

        def stage_embed(embed_p, toks, mm=0):
            with jax.named_scope("pp/embed"):
                embed_p = compute_cast(cfg, embed_p)
                rng_mb = mb_rng(mm)
                rng_e = (None if rng_mb is None
                         else jax.random.fold_in(rng_mb, cfg.n_layers))
                if sp_axis is None:
                    return embed_apply(cfg, embed_p, toks, rng=rng_e)
                from .seq_parallel import sp_embed_apply
                return sp_embed_apply(cfg, embed_p, toks, sp_axis, rng=rng_e,
                                      sp_size=n_seq)

        def select_v(tree, v):
            return jax.tree.map(
                lambda x: jax.lax.dynamic_index_in_dim(x, v, 0, keepdims=False),
                tree)

        def stage_params(vv):
            """This tick's active chunk parameters. Under fsdp the sharded
            leaves all-gather over 'data' just in time — only ONE chunk's
            full weights are ever resident, and only for the tick. The
            gather dim is per-leaf (``_fsdp_shard_dims``): with TP, 'data'
            rides a different dim than the leaf's 'model' shard."""
            p = select_v(layers_local, vv)
            if not fsdp:
                return p
            return jax.tree.map(
                lambda x, dm: jax.lax.all_gather(x, DATA_AXIS, axis=dm,
                                                 tiled=True) if dm >= 0
                else x,
                p, fsdp_dims)

        def scatter_chunk_grads(gp):
            """ZeRO-2 half of fsdp: reduce-scatter this tick's full chunk
            grads over 'data' so the accumulator carry stays sharded (the
            scatter also performs the cross-replica grad sum for these
            leaves — the epilogue skips its data-psum for them)."""
            if not fsdp:
                return gp
            return jax.tree.map(
                lambda g, dm: jax.lax.psum_scatter(
                    g, DATA_AXIS, scatter_dimension=dm, tiled=True)
                if dm >= 0 else g,
                gp, fsdp_dims)

        masked_store = _masked_store

        # Every device's objective is its local share; the shards' implicit
        # SPMD sum is the global mean, so no collective sits inside the
        # objective. The reported loss is psum'd once, outside the schedule.
        loss_norm = n_seq * n_ep
        aux_scale = (moe.aux_loss_weight / cfg.n_layers / loss_norm
                     if moe is not None else 0.0)

        if cfg.pad_token_id is not None:
            # the scale absorbs the WHOLE normalization (incl. the seq- and
            # expert-shard sums), so the pad branches below skip /loss_norm
            shard_axes = tuple(
                ax for ax, n in ((SEQ_AXIS, n_seq), (EXPERT_AXIS, n_ep))
                if n > 1)
            pad_scale = global_pad_scale(
                targets, cfg.pad_token_id, M,
                data_axis=DATA_AXIS if n_data > 1 else None,
                shard_axes=shard_axes or None)

        def stage_objective(p_v, head_arg, x_in, vv, mm, last_stage, g_in):
            """-> (objective, loss_report). The objective's gradients are the
            stage VJP: the real loss through the head on the last stage, else
            the contraction of the stage output with the incoming cotangent —
            plus this stage's share of the MoE routing aux loss. loss_report
            is what the tick accumulates into the reported loss. ``(vv, mm)``
            select the dropout stream, so the rematerialized forward here
            draws exactly the masks the forward unit drew. Under tied
            embeddings ``head_arg`` is ``(head, embed)`` so the embedding
            receives its head-matmul gradient through this VJP."""
            head_arg = compute_cast(cfg, head_arg)
            if cfg.tie_embeddings:
                head_p, embed_p = head_arg
            else:
                head_p, embed_p = head_arg, None
            y, aux = stage_body(p_v, x_in, vv, mm)

            def loss_branch():
                return _stage_ce(
                    cfg, head_p, embed_p, y, targets_mb[mm],
                    tp_axis=tp_axis, T=T,
                    tp_vocab_parallel=tp_vocab_parallel,
                    pad_scale=pad_scale if cfg.pad_token_id is not None
                    else None,
                    loss_norm=loss_norm)

            main = jax.lax.cond(
                last_stage, loss_branch,
                lambda: jnp.sum(y.astype(jnp.float32)
                                * g_in.astype(jnp.float32)))
            aux_term = aux * aux_scale
            report = jnp.where(last_stage, main, 0.0) + aux_term
            return main + aux_term, report

        if use_stored:
            # Stored-activation backward: classify the stage body's vjp
            # residuals once (abstract trace; vv/mm/x are arguments so the
            # jaxpr matches the live units', where they are tracers) and
            # allocate slot buffers for the x-dependent leaves only — the
            # x-independent ones (casts of weights, RoPE tables, masks) are
            # re-derived live at backward. See stored_backward module doc.
            from .stored_backward import (check_residual_leaves,
                                          x_dependent_mask)

            def body_vjp_leaves(p_v, x_in, vv, mm):
                _, vjp_fn = jax.vjp(
                    lambda p, xi: stage_body(p, xi, vv, mm), p_v, x_in)
                return tuple(jax.tree.leaves(vjp_fn))

            _mask_args = (select_v(layers_local, 0),
                          jnp.zeros(mb_shape, dtype),
                          jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
            res_mask = x_dependent_mask(body_vjp_leaves, _mask_args, (1,))
            res_struct = jax.eval_shape(body_vjp_leaves, *_mask_args)
            stored_struct = tuple(
                s for s, m0 in zip(res_struct, res_mask) if m0)
        else:
            res_mask = stored_struct = res_struct = ()

        def run_unit(pred, unit, noop, operand, know=None):
            """Execute one schedule unit. Default: a lax.cond (idle devices
            take the cheap branch; psum/all_to_all inside are grouped, so a
            group that skips together is fine). Ring-attention stages: run
            the unit unconditionally and where-mask its outputs against the
            noop's — ppermute (flat-pair collective-permute) requires full
            participation, so every seq peer must execute the unit's ring
            collectives every tick (see docs/parallelism.md). ``know``
            (unrolled ticks): the concrete device-uniform predicate — the
            cond/mask disappears. Elision is uniform across seq/model/data
            peers because the table row is shared along those axes."""
            if know is True:
                return unit(operand)
            if know is False:
                return noop(operand)
            if not uniform_units:
                return jax.lax.cond(pred, unit, noop, operand)
            return jax.tree.map(lambda n, o: jnp.where(pred, n, o),
                                unit(operand), noop(operand))

        def transfers(fwd_send, bwd_send, next_concrete=None):
            """End-of-tick ring hops. Classic wrap placement: activations
            ride +1, cotangents -1. With reverse routes (vshape), the same
            send values ALSO ride the opposite rings — each consumer banks
            only from the channel its table entry names, so the extra
            copies are dead unless routed. Unrolled ticks pass the NEXT
            tick's concrete row block: a channel no device banks next tick
            is dead, so its ppermute is elided (zeros flow instead) — the
            last tick and e.g. GPipe's whole warmup lose their grad-ring
            hops this way."""
            def hop(send, perm, bank_col, name):
                if next_concrete is not None and (
                        next_concrete[:, bank_col] < 0).all():
                    return jnp.zeros(mb_shape, dtype)
                with jax.named_scope(name):
                    return jax.lax.ppermute(send, PIPE_AXIS, perm)

            fr = hop(fwd_send, fwd_perm, COL_STORE_F_SLOT, "pp/ring_fwd")
            br = hop(bwd_send, bwd_perm, COL_STORE_B_SLOT, "pp/ring_bwd")
            if not reverse_routes:
                return (fr, br)
            return (fr, br,
                    hop(fwd_send, bwd_perm, COL_STORE_F_NEG_SLOT,
                        "pp/ring_fwd_rev"),
                    hop(bwd_send, fwd_perm, COL_STORE_B_POS_SLOT,
                        "pp/ring_bwd_rev"))

        def _sq_tree(t):
            """Sum of squared elements over a pytree, f32 (dynamics: one
            unit's share of its microbatch's squared grad norm)."""
            return sum((jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in jax.tree.leaves(t)), jnp.float32(0.0))

        def tick(carry, row_all, concrete=None, next_concrete=None,
                 bank_stages=None):
            if dyn:
                (act_buf, grad_buf, res_bufs, recvs,
                 g_layers, g_embed, g_head, loss_acc, sq_mb) = carry
            else:
                (act_buf, grad_buf, res_bufs, recvs,
                 g_layers, g_embed, g_head, loss_acc) = carry
                sq_mb = None
            row = row_all[d]

            def ccol(col):
                return None if concrete is None else concrete[:, col]

            # What the concrete row says of each unit across the pipe axis
            # (True: every device takes it, so its cond goes). A packed
            # tick can hold one unit every device takes beside one only
            # some take; where units hold collectives of their own (tensor,
            # sequence or expert axes, fsdp's gathers) the first then keeps
            # its cond as well: XLA:CPU runs a conditional whose branch
            # holds collectives concurrently with top-level collectives
            # that do not depend on it, and its in-process rendezvous then
            # deadlocks (every Ulysses 1F1B test of tests/test_sp_pipeline.py
            # did, most runs). One-unit-a-tick tables never mixed the two.
            knows = {c: _concrete_know(ccol(c))
                     for c in (COL_FWD_M, COL_BWD_M, COL_W_M)}
            if units_hold_collectives and None in knows.values():
                knows = {c: None if k else k for c, k in knows.items()}

            def store(buf, val, col):
                # unrolled: a row block that banks nowhere skips the
                # masked dynamic-update-slice entirely
                if concrete is not None and (concrete[:, col] < 0).all():
                    return buf
                return masked_store(buf, val, row[col])

            # 1. bank arrivals from last tick's ppermute channels — each at
            # its bank stage (comm_overlap='ring': the recv register IS the
            # second edge-slot buffer of the double-buffered discipline, so
            # deferring the edge-slot commit past units that don't touch the
            # slot removes the data dependency that fences the hop against
            # this tick's compute). ``bank_stages=None`` — the default and
            # the scan path — is the all-stage-0 lockstep program,
            # bit-identical to the pre-overlap executor.
            stages = (0, 0, 0, 0) if bank_stages is None else tuple(bank_stages)

            def bank_now(k, act_buf, grad_buf):
                if stages[0] == k:
                    act_buf = store(act_buf, recvs[0], COL_STORE_F_SLOT)
                if stages[1] == k:
                    grad_buf = store(grad_buf, recvs[1], COL_STORE_B_SLOT)
                if reverse_routes:
                    if stages[2] == k:
                        act_buf = store(act_buf, recvs[2],
                                        COL_STORE_F_NEG_SLOT)
                    if stages[3] == k:
                        grad_buf = store(grad_buf, recvs[3],
                                         COL_STORE_B_POS_SLOT)
                return act_buf, grad_buf

            act_buf, grad_buf = bank_now(BANK_BEFORE_F, act_buf, grad_buf)

            # 2. forward unit
            fv, fm, fslot = row[COL_FWD_V], row[COL_FWD_M], row[COL_FWD_SLOT]

            if use_stored:
                # Buffer discipline (measured on v5e): the slot-buffer
                # writes live INSIDE the cond — only the taken branch
                # touches them, so idle ticks cost nothing. (The
                # alternative — cond returns the leaves, masked_store
                # outside — materializes slot-sized zeros every idle tick
                # and re-writes every active tick: measured 1.4x slower.)
                def fwd_unit(op):
                    act_buf, res_bufs, loss_acc = op
                    vv, mm = jnp.maximum(fv, 0), jnp.maximum(fm, 0)
                    ss = jnp.maximum(fslot, 0)
                    first_stage = is_first_dev & (vv == 0)
                    x_emb = stage_embed(embed, tokens_mb[mm],
                                        mm).astype(dtype)
                    x = jnp.where(first_stage, x_emb, act_buf[ss])
                    (y, aux), vjp_fn = jax.vjp(
                        lambda p, xi: stage_body(p, xi, vv, mm),
                        stage_params(vv), x)
                    leaves, _ = jax.tree.flatten(vjp_fn)
                    check_residual_leaves(leaves, res_struct, "forward")
                    stored = (l for l, m0 in zip(leaves, res_mask) if m0)
                    res_bufs = tuple(
                        b.at[ss].set(l) for b, l in zip(res_bufs, stored))
                    # the slot banks the body OUTPUT (the backward's head
                    # input on the last stage); x is spent — same lifetime,
                    # same slot, no extra buffer
                    act_buf = act_buf.at[ss].set(y)
                    # the MoE routing aux share of the reported loss is
                    # known at forward time here (the CE share lands in the
                    # backward unit); the remat path accumulates both at
                    # backward — the totals are identical
                    return (act_buf, res_bufs,
                            loss_acc + aux * aux_scale), y

                def fwd_noop(op):
                    return op, jnp.zeros(mb_shape, dtype)

                with jax.named_scope("pp/fwd"):
                    (act_buf, res_bufs, loss_acc), fwd_send = run_unit(
                        fm >= 0, fwd_unit, fwd_noop,
                        (act_buf, res_bufs, loss_acc),
                        know=knows[COL_FWD_M])
            else:
                def fwd_unit(act_buf):
                    vv, mm = jnp.maximum(fv, 0), jnp.maximum(fm, 0)
                    ss = jnp.maximum(fslot, 0)
                    first_stage = is_first_dev & (vv == 0)
                    x_emb = stage_embed(embed, tokens_mb[mm],
                                        mm).astype(dtype)
                    x = jnp.where(first_stage, x_emb, act_buf[ss])
                    act_buf = act_buf.at[ss].set(x)  # saved for remat bwd
                    y, _ = stage_body(stage_params(vv), x, vv, mm)
                    return act_buf, y

                def fwd_noop(act_buf):
                    return act_buf, jnp.zeros(mb_shape, dtype)

                with jax.named_scope("pp/fwd"):
                    act_buf, fwd_send = run_unit(
                        fm >= 0, fwd_unit, fwd_noop, act_buf,
                        know=knows[COL_FWD_M])
            if reverse_routes:
                # same-device hop (vshape's V turning point): the output IS
                # the next chunk's input — bank it locally, no ring transit
                act_buf = store(act_buf, fwd_send, COL_FWD_LOCAL_SLOT)
            act_buf, grad_buf = bank_now(BANK_BEFORE_B, act_buf, grad_buf)
            if (knows[COL_FWD_M] is not False
                    and knows[COL_BWD_M] is not False):
                # A packed tick runs its forward, THEN its backward: the two
                # units are independent (F(m+k) beside B(m)), and where
                # neither sits in a cond the compiler interleaves them and
                # keeps both units' temporaries live together — gpt2-xl
                # D=4 then needs 16.53 GB of a v5e's 15.75 and is refused
                # (14.88 GB with the fence). The gradient accumulators every
                # backward-side unit adds into pass the fence with the
                # forward's outputs, so no such unit starts before the
                # forward has finished.
                ((act_buf, fwd_send, res_bufs),
                 (g_layers, g_embed, g_head, loss_acc)) = (
                    jax.lax.optimization_barrier(
                        ((act_buf, fwd_send, res_bufs),
                         (g_layers, g_embed, g_head, loss_acc))))

            # 3. backward unit (rematerializing)
            bv, bm = row[COL_BWD_V], row[COL_BWD_M]

            if split:
                # Split backward (ZB-H1): B computes only the input cotangent
                # (the half on the inter-stage critical path — upstream's
                # stage_backward_input, _backward.py:177); W later redoes the
                # stage VJP for parameter grads (stage_backward_weight,
                # _backward.py:281) in ticks that would otherwise be bubble.
                def dgrad_unit(loss_acc):
                    vv, mm = jnp.maximum(bv, 0), jnp.maximum(bm, 0)
                    last_stage = is_last_dev & (vv == last_chunk)
                    x = act_buf[jnp.maximum(row[COL_BWD_ASLOT], 0)]
                    g_in = grad_buf[jnp.maximum(row[COL_BWD_GSLOT], 0)]
                    params_v = stage_params(vv)
                    (_, report), gx = jax.value_and_grad(
                        lambda x_in: stage_objective(params_v, head_bundle, x_in, vv,
                                                     mm, last_stage, g_in),
                        has_aux=True)(x)
                    return loss_acc + report, gx

                def dgrad_noop(loss_acc):
                    return loss_acc, jnp.zeros(mb_shape, dtype)

                with jax.named_scope("pp/bwd_dgrad"):
                    loss_acc, bwd_send = run_unit(
                        bm >= 0, dgrad_unit, dgrad_noop, loss_acc,
                        know=knows[COL_BWD_M])
                if reverse_routes:
                    grad_buf = store(grad_buf, bwd_send, COL_BWD_LOCAL_SLOT)
                act_buf, grad_buf = bank_now(BANK_BEFORE_W, act_buf,
                                             grad_buf)

                wv, wm = row[COL_W_V], row[COL_W_M]

                def wgrad_unit(operand):
                    if dyn:
                        g_layers, g_embed, g_head, sq_mb = operand
                    else:
                        g_layers, g_embed, g_head = operand
                    vv, mm = jnp.maximum(wv, 0), jnp.maximum(wm, 0)
                    last_stage = is_last_dev & (vv == last_chunk)
                    first_stage = is_first_dev & (vv == 0)
                    x_slot = act_buf[jnp.maximum(row[COL_W_ASLOT], 0)]
                    g_in = grad_buf[jnp.maximum(row[COL_W_GSLOT], 0)]
                    params_v = stage_params(vv)
                    (gp, gh, gx), _ = jax.grad(
                        lambda p_v, head_p, x_in: stage_objective(
                            p_v, head_p, x_in, vv, mm, last_stage, g_in),
                        argnums=(0, 1, 2), has_aux=True)(params_v, head_bundle, x_slot)
                    if cfg.tie_embeddings:
                        # fold the tied head's embed grad into the ONE
                        # g_embed accumulator (a bundle-shaped g_head carry
                        # would duplicate the [vocab, dim] buffer per device)
                        gh, gh_embed = gh
                        g_embed = jax.tree.map(jnp.add, g_embed, gh_embed)
                    gp = scatter_chunk_grads(gp)
                    g_layers = jax.tree.map(lambda a, g: a.at[vv].add(g),
                                            g_layers, gp)
                    g_head = jax.tree.map(jnp.add, g_head, gh)
                    # Embedding wgrad only on the first stage (its saved input
                    # IS the embed output, so gx is the embed cotangent).
                    if dyn:
                        # dynamics restructures the cond to return the
                        # grad-or-zeros tree so its norm is observable;
                        # the off path keeps the original trace untouched
                        eg = jax.lax.cond(
                            first_stage,
                            lambda: jax.grad(lambda e: jnp.vdot(
                                stage_embed(e, tokens_mb[mm],
                                            mm).astype(jnp.float32),
                                gx.astype(jnp.float32)))(embed),
                            lambda: jax.tree.map(jnp.zeros_like, embed))
                        g_embed = jax.tree.map(jnp.add, g_embed, eg)
                        sq_mb = sq_mb.at[mm].add(
                            _sq_tree(gp) + _sq_tree(gh) + _sq_tree(eg))
                        return (g_layers, g_embed, g_head, sq_mb)
                    g_embed = jax.lax.cond(
                        first_stage,
                        lambda: jax.tree.map(
                            jnp.add, g_embed,
                            jax.grad(lambda e: jnp.vdot(
                                stage_embed(e, tokens_mb[mm], mm).astype(jnp.float32),
                                gx.astype(jnp.float32)))(embed)),
                        lambda: g_embed)
                    return (g_layers, g_embed, g_head)

                with jax.named_scope("pp/wgrad"):
                    w_op = (g_layers, g_embed, g_head) + (
                        (sq_mb,) if dyn else ())
                    w_out = run_unit(
                        wm >= 0, wgrad_unit, lambda operand: operand, w_op,
                        know=knows[COL_W_M])
                    if dyn:
                        g_layers, g_embed, g_head, sq_mb = w_out
                    else:
                        g_layers, g_embed, g_head = w_out

                act_buf, grad_buf = bank_now(BANK_END, act_buf, grad_buf)
                return (act_buf, grad_buf, res_bufs,
                        transfers(fwd_send, bwd_send, next_concrete),
                        g_layers, g_embed, g_head, loss_acc) + (
                            (sq_mb,) if dyn else ()), None

            def bwd_unit_stored(operand):
                """Stored-activation backward: head+CE grads from live
                weights and the banked body output y; body grads by
                replaying the banked vjp residuals (x-independent leaves
                re-derived live — the dummy-x forward chain is dead code
                XLA eliminates). No stage forward is recomputed."""
                if dyn:
                    g_layers, g_embed, g_head, loss_acc, sq_mb = operand
                else:
                    g_layers, g_embed, g_head, loss_acc = operand
                vv, mm = jnp.maximum(bv, 0), jnp.maximum(bm, 0)
                last_stage = is_last_dev & (vv == last_chunk)
                first_stage = is_first_dev & (vv == 0)
                aslot = jnp.maximum(row[COL_BWD_ASLOT], 0)
                y = act_buf[aslot]
                g_in = grad_buf[jnp.maximum(row[COL_BWD_GSLOT], 0)]
                params_v = stage_params(vv)

                def head_obj(head_arg, yy):
                    head_arg = compute_cast(cfg, head_arg)
                    if cfg.tie_embeddings:
                        head_p, embed_p = head_arg
                    else:
                        head_p, embed_p = head_arg, None
                    return _stage_ce(
                        cfg, head_p, embed_p, yy, targets_mb[mm],
                        tp_axis=tp_axis, T=T,
                        tp_vocab_parallel=tp_vocab_parallel,
                        pad_scale=pad_scale if cfg.pad_token_id is not None
                        else None,
                        loss_norm=loss_norm)

                def last_branch():
                    ce, (gh_d, ct_y) = jax.value_and_grad(
                        head_obj, argnums=(0, 1))(head_bundle, y)
                    return gh_d, ct_y, ce

                def other_branch():
                    return (jax.tree.map(jnp.zeros_like, head_bundle),
                            g_in, jnp.zeros((), jnp.float32))

                gh, ct_y, ce = jax.lax.cond(last_stage, last_branch,
                                            other_branch)
                # replay the banked residuals: re-trace the SAME vjp with a
                # dummy x, take x-independent leaves fresh, banked otherwise
                _, vjp2 = jax.vjp(
                    lambda p, xi: stage_body(p, xi, vv, mm), params_v,
                    jnp.zeros(mb_shape, dtype))
                fresh, treedef2 = jax.tree.flatten(vjp2)
                check_residual_leaves(fresh, res_struct, "backward")
                banked = iter(res_bufs)
                sel = [next(banked)[aslot] if m0 else f
                       for m0, f in zip(res_mask, fresh)]
                gp, gx = jax.tree.unflatten(treedef2, sel)(
                    (ct_y, jnp.asarray(aux_scale, jnp.float32)))

                if cfg.tie_embeddings:
                    gh, gh_embed = gh
                    g_embed = jax.tree.map(jnp.add, g_embed, gh_embed)
                g_layers = jax.tree.map(lambda a, g: a.at[vv].add(g),
                                        g_layers, gp)
                g_head = jax.tree.map(jnp.add, g_head, gh)
                if dyn:
                    eg = jax.lax.cond(
                        first_stage,
                        lambda: jax.grad(lambda e: jnp.vdot(
                            stage_embed(e, tokens_mb[mm],
                                        mm).astype(jnp.float32),
                            gx.astype(jnp.float32)))(embed),
                        lambda: jax.tree.map(jnp.zeros_like, embed))
                    g_embed = jax.tree.map(jnp.add, g_embed, eg)
                    sq_mb = sq_mb.at[mm].add(
                        _sq_tree(gp) + _sq_tree(gh) + _sq_tree(eg))
                    loss_acc = loss_acc + ce
                    return (g_layers, g_embed, g_head, loss_acc, sq_mb), gx
                g_embed = jax.lax.cond(
                    first_stage,
                    lambda: jax.tree.map(
                        jnp.add, g_embed,
                        jax.grad(lambda e: jnp.vdot(
                            stage_embed(e, tokens_mb[mm],
                                        mm).astype(jnp.float32),
                            gx.astype(jnp.float32)))(embed)),
                    lambda: g_embed)
                loss_acc = loss_acc + ce
                return (g_layers, g_embed, g_head, loss_acc), gx

            def bwd_unit_remat(operand):
                if dyn:
                    g_layers, g_embed, g_head, loss_acc, sq_mb = operand
                else:
                    g_layers, g_embed, g_head, loss_acc = operand
                vv, mm = jnp.maximum(bv, 0), jnp.maximum(bm, 0)
                last_stage = is_last_dev & (vv == last_chunk)
                first_stage = is_first_dev & (vv == 0)
                x = act_buf[jnp.maximum(row[COL_BWD_ASLOT], 0)]
                g_in = grad_buf[jnp.maximum(row[COL_BWD_GSLOT], 0)]
                params_v = stage_params(vv)
                (_, report), (gp, gh, gx) = jax.value_and_grad(
                    lambda p_v, head_p, x_in: stage_objective(
                        p_v, head_p, x_in, vv, mm, last_stage, g_in),
                    argnums=(0, 1, 2), has_aux=True)(params_v, head_bundle, x)

                if cfg.tie_embeddings:
                    # fold the tied head's embed grad into the ONE g_embed
                    # accumulator (see wgrad_unit note)
                    gh, gh_embed = gh
                    g_embed = jax.tree.map(jnp.add, g_embed, gh_embed)
                gp = scatter_chunk_grads(gp)
                g_layers = jax.tree.map(lambda a, g: a.at[vv].add(g),
                                        g_layers, gp)
                g_head = jax.tree.map(jnp.add, g_head, gh)
                if dyn:
                    eg = jax.lax.cond(
                        first_stage,
                        lambda: jax.grad(lambda e: jnp.vdot(
                            stage_embed(e, tokens_mb[mm],
                                        mm).astype(jnp.float32),
                            gx.astype(jnp.float32)))(embed),
                        lambda: jax.tree.map(jnp.zeros_like, embed))
                    g_embed = jax.tree.map(jnp.add, g_embed, eg)
                    sq_mb = sq_mb.at[mm].add(
                        _sq_tree(gp) + _sq_tree(gh) + _sq_tree(eg))
                    loss_acc = loss_acc + report
                    return (g_layers, g_embed, g_head, loss_acc, sq_mb), gx
                g_embed = jax.lax.cond(
                    first_stage,
                    lambda: jax.tree.map(
                        jnp.add, g_embed,
                        jax.grad(lambda e: jnp.vdot(
                            stage_embed(e, tokens_mb[mm], mm).astype(jnp.float32),
                            gx.astype(jnp.float32)))(embed)),
                    lambda: g_embed)
                loss_acc = loss_acc + report
                return (g_layers, g_embed, g_head, loss_acc), gx

            def bwd_noop(operand):
                return operand, jnp.zeros(mb_shape, dtype)

            with jax.named_scope("pp/bwd"):
                b_op = (g_layers, g_embed, g_head, loss_acc) + (
                    (sq_mb,) if dyn else ())
                b_out, bwd_send = run_unit(
                    bm >= 0,
                    bwd_unit_stored if use_stored else bwd_unit_remat,
                    bwd_noop, b_op,
                    know=knows[COL_BWD_M])
                if dyn:
                    g_layers, g_embed, g_head, loss_acc, sq_mb = b_out
                else:
                    g_layers, g_embed, g_head, loss_acc = b_out
            if reverse_routes:
                grad_buf = store(grad_buf, bwd_send, COL_BWD_LOCAL_SLOT)
            # non-split: no W unit, so the BEFORE_W and END bank points
            # coincide here (both after B, before the hops)
            act_buf, grad_buf = bank_now(BANK_BEFORE_W, act_buf, grad_buf)
            act_buf, grad_buf = bank_now(BANK_END, act_buf, grad_buf)

            # 4. ring transfer: activations +1, gradients -1 (ICI hops);
            # vshape placements add the two reverse channels
            return (act_buf, grad_buf, res_bufs,
                    transfers(fwd_send, bwd_send, next_concrete),
                    g_layers, g_embed, g_head, loss_acc) + (
                        (sq_mb,) if dyn else ()), None

        n_chan = 4 if reverse_routes else 2
        carry0 = (
            jnp.zeros((cs.n_act_slots,) + mb_shape, dtype),
            jnp.zeros((cs.n_grad_slots,) + mb_shape, dtype),
            tuple(jnp.zeros((cs.n_act_slots,) + s.shape, s.dtype)
                  for s in stored_struct),
            tuple(jnp.zeros(mb_shape, dtype) for _ in range(n_chan)),
            jax.tree.map(jnp.zeros_like, layers_local),
            jax.tree.map(jnp.zeros_like, embed),
            jax.tree.map(jnp.zeros_like, head),
            jnp.zeros((), jnp.float32),
        ) + ((jnp.zeros((M,), jnp.float32),) if dyn else ())
        if unroll_ticks == "phases":
            # phase-compressed: one specialized scan body per unique row
            # pattern, each phase driven as a lax.scan over its real rows
            carry = _phase_compressed_ticks(tick, carry0, table, phases,
                                            bank_stages=bank_stages_tab)
        elif unroll_ticks:
            # straight-line tick program: the Python loop IS the schedule,
            # each tick specialized against its concrete table row block
            # (cond/ppermute/store elision — see the tick helpers above)
            carry = carry0
            n_rows = cs.table.shape[0]
            # after the final tick nothing banks: an all-dead pseudo-row
            # elides the last hops (None means "no knowledge" — scan path)
            end_row = np.full_like(cs.table[0], -1)
            for t in range(n_rows):
                nxt = cs.table[t + 1] if t + 1 < n_rows else end_row
                bs = (None if bank_stages_tab is None
                      else tuple(int(v) for v in bank_stages_tab[t]))
                with jax.named_scope(f"pp/tick{t:03d}"):
                    carry, _ = tick(carry, table[t], concrete=cs.table[t],
                                    next_concrete=nxt, bank_stages=bs)
        else:
            carry, _ = jax.lax.scan(tick, carry0, table)
        if dyn:
            (_, _, _, _, g_layers, g_embed, g_head, loss_acc,
             sq_mb) = carry
        else:
            (_, _, _, _, g_layers, g_embed, g_head, loss_acc) = carry

        # Reductions: loss lives on the last stage only; embed/head grads on
        # one device each — psum replicates them across 'pipe'. Scale by 1/M
        # (upstream scale_grads semantics) and mean over data replicas.
        inv = 1.0 / M
        loss = jax.lax.psum(loss_acc, PIPE_AXIS) * inv
        if n_seq > 1:
            # each shard accumulated local_mean/n_seq -> sum = global mean
            loss = jax.lax.psum(loss, SEQ_AXIS)
        if n_ep > 1:
            loss = jax.lax.psum(loss, EXPERT_AXIS)
        g_layers = jax.tree.map(lambda x: x[None] * inv, g_layers)
        g_embed = jax.tree.map(lambda x: jax.lax.psum(x * inv, PIPE_AXIS), g_embed)
        g_head = jax.tree.map(lambda x: jax.lax.psum(x * inv, PIPE_AXIS), g_head)
        if n_data > 1:
            nd = 1.0 / n_data
            loss = jax.lax.psum(loss * nd, DATA_AXIS)
            if fsdp:
                # sharded layer leaves were already cross-replica summed by
                # the per-tick psum_scatter — only the scale remains; a
                # second psum here would n_data-fold them
                g_layers = jax.tree.map(
                    lambda x, dm: x * nd if dm >= 0
                    else jax.lax.psum(x * nd, DATA_AXIS),
                    g_layers, fsdp_dims)
                g_embed, g_head = jax.tree.map(
                    lambda x: jax.lax.psum(x * nd, DATA_AXIS),
                    (g_embed, g_head))
            else:
                g_layers, g_embed, g_head = jax.tree.map(
                    lambda x: jax.lax.psum(x * nd, DATA_AXIS),
                    (g_layers, g_embed, g_head))
        if n_seq > 1:
            # each seq shard holds its local-token share of d(global mean
            # loss)/d(params); the full grad is their unscaled sum (loss is
            # already the global mean and replicated across 'seq')
            g_layers, g_embed, g_head = jax.tree.map(
                lambda x: jax.lax.psum(x, SEQ_AXIS),
                (g_layers, g_embed, g_head))
        if n_ep > 1:
            # 'expert' doubles as a batch axis: replicated params sum their
            # per-shard local contributions; expert-sharded stacks (the
            # w1/b1/w2/b2 leaves under "moe") are already complete per shard
            # (every token reached its expert via the all_to_all), so they
            # stay local
            from .expert_parallel import is_expert_leaf

            def ep_reduce(path, g):
                return g if is_expert_leaf(path) else \
                    jax.lax.psum(g, EXPERT_AXIS)

            g_layers = jax.tree_util.tree_map_with_path(ep_reduce, g_layers)
            g_embed, g_head = jax.tree.map(
                lambda x: jax.lax.psum(x, EXPERT_AXIS), (g_embed, g_head))
        if dyn:
            # stages partition the (untied) params, so the pipe psum
            # completes each microbatch's |g_m|^2; data replicas hold
            # DIFFERENT microbatches — average their norms (each is one
            # sample of E|g_small|^2, the GNS small-batch moment)
            sq_mb = jax.lax.psum(sq_mb, PIPE_AXIS)
            if n_data > 1:
                sq_mb = jax.lax.psum(sq_mb * (1.0 / n_data), DATA_AXIS)
            return loss, g_layers, g_embed, g_head, sq_mb
        return loss, g_layers, g_embed, g_head

    layer_spec, head_spec = _stage_param_specs(cfg, moe, T, n_ep, fsdp,
                                               fsdp_dims, tp_vocab_parallel)
    batch_spec = _batch_spec(n_seq, n_ep)
    in_specs = (layer_spec, P(), head_spec, batch_spec, batch_spec)
    if use_dropout:
        in_specs = in_specs + (P(),)  # step rng: replicated raw key data
    sharded = _shard_map(
        spmd_fn, mesh,
        in_specs=in_specs,
        out_specs=(P(), layer_spec, P(), head_spec) + (
            (P(),) if dyn else ()),
    )

    def unpack(loss, g_layers, g_embed, g_head, *extras):
        grads = {
            "embed": g_embed,
            "layers": unstack_stage_layers(g_layers, placement),
            "head": g_head,
        }
        if dyn:
            return loss, grads, extras[0]
        return loss, grads

    if use_dropout:
        # Train-mode step: the caller supplies a per-step PRNG key; passing
        # the key's raw data through shard_map sidesteps typed-key sharding.
        def step(params, tokens, targets, rng):
            stacked = stack_stage_layers(params["layers"], D, V, placement)
            return unpack(*sharded(
                stacked, params["embed"], params["head"], tokens, targets,
                jax.random.key_data(rng)))

        return step

    def step(params, tokens, targets):
        stacked = stack_stage_layers(params["layers"], D, V, placement)
        return unpack(*sharded(
            stacked, params["embed"], params["head"], tokens, targets))

    return step


def make_pipeline_step(cfg: ModelConfig, mesh: Mesh, sched: ScheduleConfig,
                       force_tick_executor: bool = False, moe=None,
                       sp_attn_impl: str = "ring",
                       tp_vocab_parallel: bool = False,
                       fsdp: bool = False,
                       remat_backward=None,
                       unroll_ticks=None,
                       dynamics=None,
                       comm_overlap: str = "none",
                       ) -> Callable[[Pytree, jax.Array, jax.Array],
                                     Tuple[jax.Array, Pytree]]:
    """Jitted ``(params, tokens, targets) -> (loss, grads)`` pipeline step.

    Matching the reference's measurement semantics (SURVEY.md §3.3 note): the
    step computes loss and gradients only — no optimizer update — so it can be
    timed exactly like ``schedule.step``. ``force_tick_executor`` disables
    the degenerate 1-device fast path (a single fused full-batch step that
    ignores microbatching), so the step really executes the compiled
    schedule's microbatch program; WHICH executor formulation runs it is
    chosen by ``remat_backward`` (see :func:`make_pipeline_grad_fn` — at
    D == 1 the default is the unrolled stored program; pass
    ``remat_backward=True`` for the rematerializing tick scan).

    ``unroll_ticks`` picks the tick-loop form (full detail and measured
    compile-time economics in :func:`make_pipeline_grad_fn`): ``True``
    unrolls the table into straight-line specialized ticks (1.05-1.2x
    throughput, ~2.2 s compile per row), ``"phases"`` scans per-pattern
    specialized bodies (the same specialization at a compile cost that
    scales with UNIQUE tick patterns — O(1) in M for steady-state 1F1B),
    ``False`` is the bounded-compile cond-dispatched scan (~7 s), and
    ``None`` (default) auto-selects ``True`` up to ``_UNROLL_TICKS_LIMIT``
    rows and ``"phases"`` beyond — a one-line ``logging.info`` announces
    when a large table triggers that auto phase-compression. If compile
    time still hurts (or you are bisecting an executor-formulation
    difference), the ESCAPE HATCHES are explicit ``unroll_ticks=False``
    (bounded-compile scan) or ``unroll_ticks="phases"`` — both run the
    identical tick program, bit-exact against the unrolled form.

    ``dynamics`` (truthy) returns ``(loss, grads, sq_mb)`` instead — the
    per-microbatch squared grad norms feeding the gradient-noise-scale
    estimator (see :func:`make_pipeline_grad_fn`; falsy compiles a
    byte-identical program without the accumulator).

    ``comm_overlap`` (``"none"``/``"ring"``/``"auto"``) selects the
    double-buffered ring-hop discipline — bit-identical outputs, hops
    overlapped with the next tick's F/B compute (see
    :func:`make_pipeline_grad_fn`).
    """
    return jax.jit(make_pipeline_grad_fn(
        cfg, mesh, sched, force_tick_executor=force_tick_executor, moe=moe,
        sp_attn_impl=sp_attn_impl, tp_vocab_parallel=tp_vocab_parallel,
        fsdp=fsdp, remat_backward=remat_backward, unroll_ticks=unroll_ticks,
        dynamics=dynamics, comm_overlap=comm_overlap))


def aot_memory_analysis(step, *args) -> Dict[str, Any]:
    """XLA's memory accounting for a jitted step, ahead of time.

    ``lower(*args).compile()`` the step (the compile cache makes this
    free when the step already ran) and extract
    ``compiled.memory_analysis()``'s byte counters — the *compiled*
    accounting ``analysis.memory_model`` reconciles against its analytic
    slot model. Sizes are per addressable shard: a pipe-sharded
    parameter tree counts as layers/D plus the replicated operands per
    device (the reconciliation pin relies on this). Degrades to
    ``{"error": ...}`` on backends whose runtime exposes no memory
    analysis rather than failing the run."""
    try:
        compiled = step.lower(*args).compile()
        ma = compiled.memory_analysis()
        if ma is None:
            return {"error": "memory_analysis unavailable on this backend"}
        return {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "generated_code_bytes": int(ma.generated_code_size_in_bytes),
        }
    except Exception as e:  # a backend may expose no AOT memory analysis
        return {"error": str(e)}


def fsdp_shard_params(params: Pytree, cfg: ModelConfig, mesh: Mesh,
                      moe=None) -> Pytree:
    """Place a full-model pytree for pp x fsdp: :func:`param_shardings`'
    resting layout with ``fsdp=True`` — layer leaves sharded over 'pipe' on
    the layer dim (each pipe device keeps only its stages) AND over 'data'
    on the first free weight dim for matrix leaves — the placement the
    executor's grads come back in, so params, grads, and optimizer state
    all rest at ~1/(D * n_data) of the model's layer weights per device.
    Embed/head stay replicated (O(vocab*dim), a few percent of a
    Llama-class model)."""
    if mesh.shape.get(DATA_AXIS, 1) <= 1:
        raise ValueError("fsdp_shard_params needs a 'data' mesh axis to "
                         "shard parameters over (make_mesh(n_data=...))")
    return jax.device_put(params,
                          param_shardings(cfg, mesh, moe=moe, fsdp=True))


def _fwd_tick_table(D: int, V: int, M: int):
    """Forward-only tick table for the eval/inference executors: the
    F actions of the breadth-first (BFS) order — fill-drain generalized to
    V wrap-placed chunks — tick-scheduled and slot-allocated with the same
    machinery as the training tables. Returns (table [T, D, 4] int32 with
    columns (store_slot, fv, fm, src_slot), n_slots); store_slot banks the
    previous tick's +1-ring arrival, src_slot is where this tick's F reads
    its input (-1 = first stage: embed)."""
    import numpy as np

    from .schedules import (Action, F, _allocate_slots, bfs_order,
                            schedule_ticks)
    forders = [[a for a in order if a.op == F]
               for order in bfs_order(D, V, M)]
    ticks, T_compute = schedule_ticks(forders, D, V)
    # no +1: a store at t+1 always has a consumer at most at T_compute-1,
    # so the final compute tick is also the final row
    T = T_compute
    S = D * V
    # arrival of F(s, m)'s output at device (s+1) % D: store at tick+1,
    # consumed by F(s+1, m)'s tick
    events = {d: [] for d in range(D)}
    for a, t in ticks.items():
        if a.stage + 1 < S:
            nxt = Action(a.stage + 1, F, a.microbatch)
            events[(a.stage + 1) % D].append((t + 1, ticks[nxt], nxt))
    slot_of, n_slots = {}, 0
    for d in range(D):
        assign, n = _allocate_slots(events[d])
        slot_of.update(assign)
        n_slots = max(n_slots, n)
    table = np.full((T, D, 4), -1, dtype=np.int32)
    for a, t in ticks.items():
        d = a.stage % D
        table[t, d, 1] = a.stage // D
        table[t, d, 2] = a.microbatch
        if a.stage > 0:
            table[t, d, 3] = slot_of[a]
    for d in range(D):
        for arrive, _, key in events[d]:
            table[arrive, d, 0] = slot_of[key]
    n_slots = max(n_slots, 1)
    from ..analysis import maybe_verify_forward_table
    maybe_verify_forward_table(table, D, V, M, n_slots)
    return table, n_slots


def _build_forward_program(cfg: ModelConfig, mesh: Mesh,
                           sched: ScheduleConfig, sp_attn_impl: str,
                           tp_vocab_parallel: bool, fsdp: bool,
                           train_dropout: bool = False,
                           unroll=False, moe=None):
    """The forward-only tick program (BFS fill-drain over
    ``sched.n_virtual`` wrap-placed chunks; every schedule's forward order
    is fill-drain) shared by the eval loss (:func:`make_pipeline_loss_fn`)
    and the phase-separated stored backward (autodiff THROUGH this scan —
    see :func:`make_pipeline_grad_fn`). The last stage computes the
    token-mean CE per microbatch and accumulates it; [B, S, V] logits never
    materialize.

    ``unroll``: emit the ticks as a static Python loop instead of a
    ``lax.scan``. At D == 1 the table is device-symmetric, so every row is
    compile-time concrete and the program is pure straight-line code — no
    slot buffers, no conds, no self-loop ppermute; measured 148k vs 107k
    tok/s for the same 4-microbatch program on one v5e chip (scan
    boundaries force every residual through HBM, the dominant cost of
    microbatched training at small per-microbatch shapes,
    docs/performance.md). At D > 1 (round 4) slot buffers and per-device
    column reads stay dynamic, but the scan boundary still disappears and
    device-uniform ticks lose their conds and dead ring hops — autodiff
    residuals become per-tick SSA values instead of stacked scan outputs.

    Returns ``(spmd_fn, in_specs, D, V)`` where ``spmd_fn(layers_stacked,
    embed, head, tokens, targets[, rng_data])`` -> per-device partial loss
    (the PIPE/SEQ/DATA reductions are left to the caller so its gradient —
    taken inside shard_map — comes out as per-device partials, mirroring
    the tick executor's epilogue). With ``train_dropout`` the function
    takes the step key's raw data and draws the executor's exact mask
    streams (fold_in(step key, microbatch) then global-layer offsets), so
    a phase-separated stored-backward step equals the slot-buffer
    executor's bit-for-tolerance."""
    _check_patterned_stack(cfg, mesh, sched, moe, fsdp)
    D = mesh.shape[PIPE_AXIS]
    n_data = mesh.shape.get(DATA_AXIS, 1)
    T = mesh.shape.get(MODEL_AXIS, 1)
    n_seq = mesh.shape.get(SEQ_AXIS, 1)
    n_ep = mesh.shape.get(EXPERT_AXIS, 1)
    ep_axis = EXPERT_AXIS if n_ep > 1 else None
    if n_ep > 1 and moe is None:
        raise ValueError("mesh has an 'expert' axis but no MoEConfig given")
    if moe is not None:
        # MoE eval convention (VERDICT r2 item 4): the reported eval loss
        # is the CE term ONLY. The routing load-balance aux is a training
        # regularizer, not a model-quality quantity — perplexity comes
        # from CE — so the forward program drops each stage's aux scalar
        # (docs/parallelism.md "MoE evaluation").
        _check_moe_mesh(cfg, moe, T, n_seq, n_ep)
        if train_dropout:
            raise NotImplementedError(
                "the phase-stored/forward program does not plumb dropout "
                "rng into MoE stage bodies (the tick executor does, via "
                "moe_layer_apply's per-layer rng); use the tick executor "
                "for MoE training with dropout")
    if fsdp and n_data <= 1:
        raise ValueError("fsdp eval needs a 'data' mesh axis (matching "
                         "the training-side pp x fsdp support)")
    fsdp_dims = _resolve_fsdp_dims(cfg, moe, n_data, T, n_ep, fsdp)
    V = sched.n_virtual
    M = sched.n_microbatches
    tp_axis = MODEL_AXIS if T > 1 else None
    sp_axis = SEQ_AXIS if n_seq > 1 else None
    if sp_attn_impl not in ("ring", "ulysses"):
        raise ValueError(f"sp_attn_impl must be 'ring' or 'ulysses', "
                         f"got {sp_attn_impl!r}")
    if tp_vocab_parallel:
        if T <= 1:
            raise ValueError("tp_vocab_parallel needs a 'model' mesh axis")
        if cfg.vocab_size % T:
            raise ValueError(f"vocab_size={cfg.vocab_size} must divide over "
                             f"the model-axis size {T}")
    _check_tp_divisibility(cfg, T)
    S = D * V
    if cfg.n_layers % S:
        raise ValueError(f"n_layers={cfg.n_layers} must divide over {S} stages")
    lps = cfg.n_layers // S
    uniform_units = sp_axis is not None and sp_attn_impl == "ring"
    table_np, n_slots = _fwd_tick_table(D, V, M)
    if unroll is None:
        # auto: D == 1 always unrolls (measured fastest); D > 1 up to the
        # forward executor's OWN row budget — round 5 raised the training
        # executor's _UNROLL_TICKS_LIMIT to 64 from measurements of the
        # train-step economics (docs/performance.md "Unroll-vs-scan
        # crossover"); forward
        # ticks are ~1/3 of a train tick's compute, so the unroll win per
        # compile-second is unmeasured here and the round-4 budget stays.
        # Beyond the budget the phase-compressed form replaces the plain
        # whole-table scan (same default flip as the training executor).
        unroll = (True if (D == 1
                           or table_np.shape[0] <= _UNROLL_FWD_TICKS_LIMIT)
                  else "phases")
    if unroll not in (True, False, "phases"):
        raise ValueError(f"unroll must be True, False, 'phases', or None "
                         f"(auto), got {unroll!r}")
    if unroll == "phases":
        from .schedules import compress_schedule
        fwd_phases = compress_schedule(table_np)
    else:
        fwd_phases = None
    table = jnp.asarray(table_np)
    dtype = jnp.dtype(cfg.dtype)
    fwd_perm = [(i, (i + 1) % D) for i in range(D)]
    loss_norm = n_seq * n_ep  # each shard contributes its local-mean share

    def spmd_fn(layers_stacked, embed, head, tokens, targets,
                rng_data=None):
        d = jax.lax.axis_index(PIPE_AXIS)
        layers_local = compute_cast(
            cfg, jax.tree.map(lambda x: x[0], layers_stacked))
        embed_c = compute_cast(cfg, embed)
        head_c = compute_cast(cfg, head)
        b_local, seq = tokens.shape
        assert b_local % M == 0, (
            f"local batch {b_local} not divisible by n_microbatches={M}")
        mb = b_local // M
        tokens_mb = tokens.reshape(M, mb, seq)
        targets_mb = targets.reshape(M, mb, seq)
        mb_shape = (mb, seq, cfg.dim)

        if train_dropout:
            base_rng = jax.random.wrap_key_data(rng_data)
            if n_data > 1:
                base_rng = jax.random.fold_in(
                    base_rng, jax.lax.axis_index(DATA_AXIS))
        else:
            base_rng = None

        def mb_rng(mm):
            return (None if base_rng is None
                    else jax.random.fold_in(base_rng, mm))

        def stage_body(vv, x, mm=0):
            layer_p = jax.tree.map(
                lambda t: jax.lax.dynamic_index_in_dim(t, vv, 0,
                                                       keepdims=False),
                layers_local)
            if fsdp:
                # JIT all-gather of just this chunk's weights (the same
                # per-tick residency bound as the training executor)
                layer_p = jax.tree.map(
                    lambda x_, dm: jax.lax.all_gather(
                        x_, DATA_AXIS, axis=dm, tiled=True) if dm >= 0
                    else x_,
                    layer_p, fsdp_dims)
            if moe is not None:
                from ..models.moe import moe_layer_apply

                def mstep(h, lp):
                    # aux dropped: eval reports CE only (module docstring)
                    h, _aux = moe_layer_apply(cfg, moe, lp, h, ep_axis,
                                              tp_axis=tp_axis, tp_size=T,
                                              sp_axis=sp_axis,
                                              sp_attn_impl=sp_attn_impl,
                                              sp_size=n_seq)
                    return h, None

                y, _ = jax.lax.scan(mstep, x, layer_p)
                return y
            offset = (vv * D + d) * lps  # wrap placement's global layer
            if sp_axis is None:
                return body_apply(cfg, layer_p, x, tp_axis=tp_axis,
                                  tp_size=T, rng=mb_rng(mm),
                                  layer_offset=offset)
            from .seq_parallel import sp_body_apply
            return sp_body_apply(cfg, layer_p, x, sp_axis,
                                 attn_impl=sp_attn_impl,
                                 tp_axis=tp_axis, tp_size=T,
                                 rng=mb_rng(mm), layer_offset=offset,
                                 sp_size=n_seq)

        def stage_embed(toks, mm=0):
            rng_mb = mb_rng(mm)
            rng_e = (None if rng_mb is None
                     else jax.random.fold_in(rng_mb, cfg.n_layers))
            if sp_axis is None:
                return embed_apply(cfg, embed_c, toks, rng=rng_e)
            from .seq_parallel import sp_embed_apply
            return sp_embed_apply(cfg, embed_c, toks, sp_axis, rng=rng_e,
                                  sp_size=n_seq)

        if cfg.pad_token_id is not None:
            shard_axes = tuple(
                ax for ax, n in ((SEQ_AXIS, n_seq), (EXPERT_AXIS, n_ep))
                if n > 1)
            pad_scale = global_pad_scale(
                targets, cfg.pad_token_id, M,
                data_axis=DATA_AXIS if n_data > 1 else None,
                shard_axes=shard_axes or None)

        def mb_loss(y, mm):
            return _stage_ce(
                cfg, head_c, embed_c, y, targets_mb[mm], tp_axis=tp_axis,
                T=T, tp_vocab_parallel=tp_vocab_parallel,
                pad_scale=pad_scale if cfg.pad_token_id is not None
                else None,
                loss_norm=loss_norm)

        if unroll is True and D == 1:
            # D == 1: every table row is concrete, so the tick loop lowers
            # to straight-line code — slots become Python variables, conds
            # become Python ifs, the self-loop ppermute disappears
            saved: dict = {}
            recv = None
            loss = jnp.zeros((), jnp.float32)
            for t in range(table_np.shape[0]):
                s0, fv_, fm_, src = (int(v) for v in table_np[t, 0])
                if s0 >= 0:
                    assert recv is not None, "forward table banks a value " \
                        "no prior tick sent"
                    saved[s0] = recv
                if fm_ < 0:
                    recv = None
                    continue
                if fv_ == 0:
                    x = stage_embed(tokens_mb[fm_], fm_).astype(dtype)
                else:
                    x = saved[src]
                y = stage_body(fv_, x, fm_)
                if fv_ == V - 1:
                    loss = loss + mb_loss(y, fm_)
                recv = y
            return loss / M

        masked_store = _masked_store

        def run_unit(pred, unit, noop, operand, know=None):
            if know is True:
                return unit(operand)
            if know is False:
                return noop(operand)
            if not uniform_units:
                return jax.lax.cond(pred, unit, noop, operand)
            return jax.tree.map(lambda n, o: jnp.where(pred, n, o),
                                unit(operand), noop(operand))

        def tick(carry, row_all, concrete=None, next_concrete=None):
            act_buf, recv, loss_acc = carry
            row = row_all[d]
            if concrete is None or (concrete[:, 0] >= 0).any():
                act_buf = masked_store(act_buf, recv, row[0])
            fv, fm, src = row[1], row[2], row[3]

            def fwd_unit(act_buf):
                vv, mm = jnp.maximum(fv, 0), jnp.maximum(fm, 0)
                first_stage = (d == 0) & (vv == 0)
                x_emb = stage_embed(tokens_mb[mm], mm).astype(dtype)
                x = jnp.where(first_stage, x_emb,
                              act_buf[jnp.maximum(src, 0)])
                y = stage_body(vv, x, mm)
                last_stage = (d == D - 1) & (vv == V - 1)
                l = jax.lax.cond(last_stage, lambda: mb_loss(y, mm),
                                 lambda: jnp.zeros((), jnp.float32))
                return y, l

            def fwd_noop(act_buf):
                return (jnp.zeros(mb_shape, dtype),
                        jnp.zeros((), jnp.float32))

            y, l = run_unit(fm >= 0, fwd_unit, fwd_noop, act_buf,
                            know=_concrete_know(
                                None if concrete is None else concrete[:, 2]))
            if next_concrete is not None and (next_concrete[:, 0] < 0).all():
                nxt_recv = jnp.zeros(mb_shape, dtype)  # hop elided: dead
            else:
                nxt_recv = jax.lax.ppermute(y, PIPE_AXIS, fwd_perm)
            return (act_buf, nxt_recv, loss_acc + l), None

        carry0 = (jnp.zeros((n_slots,) + mb_shape, dtype),
                  jnp.zeros(mb_shape, dtype),
                  jnp.zeros((), jnp.float32))
        if unroll == "phases":
            # phase-compressed ticks (same core as the training executor)
            carry = _phase_compressed_ticks(tick, carry0, table, fwd_phases)
        elif unroll:
            # D > 1 unrolled: the tick loop is a Python loop over concrete
            # rows — slot buffers and per-device column reads stay dynamic,
            # but the scan boundary disappears and device-uniform ticks
            # lose their conds/hops (mirrors the training executor's
            # unroll_ticks; VERDICT r3 item 2)
            carry = carry0
            n_rows = table_np.shape[0]
            end_row = np.full_like(table_np[0], -1)
            for t in range(n_rows):
                nxt = table_np[t + 1] if t + 1 < n_rows else end_row
                carry, _ = tick(carry, table[t], concrete=table_np[t],
                                next_concrete=nxt)
        else:
            carry, _ = jax.lax.scan(tick, carry0, table)
        (_, _, loss) = carry
        return loss / M  # per-device partial (non-last stages: 0)

    layer_spec, head_spec = _stage_param_specs(cfg, moe, T, n_ep, fsdp,
                                               fsdp_dims, tp_vocab_parallel)
    batch_spec = _batch_spec(n_seq, n_ep)
    in_specs = (layer_spec, P(), head_spec, batch_spec, batch_spec)
    return spmd_fn, in_specs, D, V


def make_pipeline_loss_fn(cfg: ModelConfig, mesh: Mesh, sched: ScheduleConfig,
                          sp_attn_impl: str = "ring",
                          tp_vocab_parallel: bool = False,
                          fsdp: bool = False, moe=None,
                          unroll_ticks=False,
                          ) -> Callable[[Pytree, jax.Array, jax.Array],
                                        jax.Array]:
    """Jitted forward-only eval loss: ``(params, tokens, targets) -> loss``.

    The evaluation twin of :func:`make_pipeline_grad_fn` — the forward
    tick program of :func:`_build_forward_program` (eval mode: no dropout)
    with the cross-device loss reductions applied. The mean over
    microbatches equals the single-device full-batch ``transformer_loss``
    exactly (asserted in tests/test_eval.py), at forward-only cost — no
    backward, no rematerialization.

    Covers the full training-mesh space (VERDICT r1 item 7 / r2 item 4):
    data x pipe x model x seq meshes, V >= 1, Megatron TP inside stages,
    ring/Ulysses sequence parallelism, the vocab-parallel CE
    (``tp_vocab_parallel`` — incl. tied embeddings), pp x fsdp resting
    layouts (``fsdp=True``: params arrive pipe x data sharded and each
    chunk is gathered just in time, preserving the ZeRO-3 residency bound
    during eval), and MoE stages (``moe=`` a MoEConfig, experts sharded
    over an 'expert' axis when present). **MoE aux convention**: the eval
    loss is the CE term only — the routing load-balance aux is a training
    regularizer, so the forward program drops it and the comparison
    target is the training loss minus its aux term (asserted in
    tests/test_eval.py::test_moe_pipeline_eval_loss).

    ``unroll_ticks`` picks the forward tick-loop form — ``True``
    (straight-line), ``"phases"`` (per-pattern specialized scan bodies),
    ``False`` (cond-dispatched scan, the default: eval compiles once and
    runs rarely, so bounded compile wins), or ``None`` (the training-side
    auto rule with the forward budget ``_UNROLL_FWD_TICKS_LIMIT``).
    """
    spmd_fn, in_specs, D, V = _build_forward_program(
        cfg, mesh, sched, sp_attn_impl, tp_vocab_parallel, fsdp, moe=moe,
        unroll=unroll_ticks)
    n_data = mesh.shape.get(DATA_AXIS, 1)
    n_seq = mesh.shape.get(SEQ_AXIS, 1)
    n_ep = mesh.shape.get(EXPERT_AXIS, 1)

    def reduced(layers_stacked, embed, head, tokens, targets):
        loss = jax.lax.psum(
            spmd_fn(layers_stacked, embed, head, tokens, targets),
            PIPE_AXIS)  # lives on the last stage
        if n_seq > 1:
            loss = jax.lax.psum(loss, SEQ_AXIS)
        if n_ep > 1:
            # 'expert' doubles as a batch axis; the objective already
            # divided by n_ep, so the psum completes the global mean
            loss = jax.lax.psum(loss, EXPERT_AXIS)
        if n_data > 1:
            loss = jax.lax.psum(loss / n_data, DATA_AXIS)
        return loss

    sharded = _shard_map(reduced, mesh, in_specs=in_specs, out_specs=P())

    @jax.jit
    def loss_fn(params, tokens, targets):
        stacked = stack_stage_layers(params["layers"], D, V)
        return sharded(stacked, params["embed"], params["head"],
                       tokens, targets)

    return loss_fn


def _make_phase_stored_grad_fn(cfg: ModelConfig, mesh: Mesh,
                               sched: ScheduleConfig, sp_attn_impl: str,
                               tp_vocab_parallel: bool):
    """Stored-activation backward for phase-separated schedules (GPipe,
    BFS — and ANY non-split schedule at D == 1): differentiate THROUGH
    the forward tick program.

    These schedules run, per device, every forward before any backward —
    so the backward tick order is exactly the time-reversal of the forward
    program, which is precisely what ``jax.value_and_grad`` produces: XLA
    banks each tick's residuals (ordinary fused SSA values in the unrolled
    program — D == 1's straight-line form or round 4's D > 1 Python tick
    loop — static scan outputs only beyond the unroll budget), the
    generated backward replays them in reverse, and the transposed
    ``ppermute`` IS the gradient ring (+1 forward ring transposes to the
    -1 grad ring). This matches the reference's torch-autograd semantics
    exactly (GPipe's backward stashes per-microbatch saved tensors and
    never recomputes — upstream ``schedules.py:872-992`` over
    ``stage.py:857/937``). Activation residency is O(M) microbatches —
    GPipe's own requirement; schedules whose point is O(D) residency
    (1F1B/Interleaved) interleave B among F and cannot use this path at
    D > 1 (their stored backward is the slot-banked tick executor, which
    round 4 also unrolls — ``unroll_ticks``). Single-chip measurements
    (v5e, docs/performance.md): the unrolled D == 1 form is the FASTEST
    executor formulation (~1.25x over the remat tick scan); the scanned
    D > 1 form measures SLOWER than remat (scan-boundary residual
    traffic), hence stored remains opt-in via ``remat_backward=False`` —
    now served by the unrolled form wherever the tick budget allows.
    """
    use_dropout = cfg.dropout > 0.0
    spmd_fn, in_specs, D, V = _build_forward_program(
        cfg, mesh, sched, sp_attn_impl, tp_vocab_parallel, False,
        train_dropout=use_dropout, unroll=None)
    n_data = mesh.shape.get(DATA_AXIS, 1)
    n_seq = mesh.shape.get(SEQ_AXIS, 1)

    def grad_prog(layers_stacked, embed, head, tokens, targets,
                  rng_data=None):
        def obj(ls, e, h):
            if use_dropout:
                return spmd_fn(ls, e, h, tokens, targets, rng_data)
            return spmd_fn(ls, e, h, tokens, targets)

        loss, (g_l, g_e, g_h) = jax.value_and_grad(
            obj, argnums=(0, 1, 2))(layers_stacked, embed, head)
        # same reduction epilogue as the tick executor: loss lives on the
        # last stage; replicated embed/head grads are per-device partials
        loss = jax.lax.psum(loss, PIPE_AXIS)
        g_e = jax.tree.map(lambda x: jax.lax.psum(x, PIPE_AXIS), g_e)
        g_h = jax.tree.map(lambda x: jax.lax.psum(x, PIPE_AXIS), g_h)
        if n_seq > 1:
            loss = jax.lax.psum(loss, SEQ_AXIS)
            g_l, g_e, g_h = jax.tree.map(
                lambda x: jax.lax.psum(x, SEQ_AXIS), (g_l, g_e, g_h))
        if n_data > 1:
            nd = 1.0 / n_data
            loss = jax.lax.psum(loss * nd, DATA_AXIS)
            g_l, g_e, g_h = jax.tree.map(
                lambda x: jax.lax.psum(x * nd, DATA_AXIS),
                (g_l, g_e, g_h))
        return loss, g_l, g_e, g_h

    grad_specs = in_specs + ((P(),) if use_dropout else ())
    sharded = _shard_map(
        grad_prog, mesh, in_specs=grad_specs,
        out_specs=(P(), in_specs[0], P(), in_specs[2]))

    def unpack(loss, g_l, g_e, g_h):
        return loss, {"embed": g_e,
                      "layers": unstack_stage_layers(g_l),
                      "head": g_h}

    if use_dropout:
        def step(params, tokens, targets, rng):
            stacked = stack_stage_layers(params["layers"], D, V)
            return unpack(*sharded(stacked, params["embed"],
                                   params["head"], tokens, targets,
                                   jax.random.key_data(rng)))
        return step

    def step(params, tokens, targets):
        stacked = stack_stage_layers(params["layers"], D, V)
        return unpack(*sharded(stacked, params["embed"], params["head"],
                               tokens, targets))

    return step


def make_pipeline_forward(cfg: ModelConfig, mesh: Mesh, sched: ScheduleConfig,
                          ) -> Callable[[Pytree, jax.Array], jax.Array]:
    """Jitted forward-only pipeline: ``(params, tokens) -> logits [B, S, V]``.

    The parity twin of upstream's ``PipelineScheduleSingle.step`` return
    value — per-microbatch last-stage outputs merged back into the
    full-batch logits (``merge_chunks``, ``schedules.py:794-798``). Runs a
    BFS fill-drain forward over ``sched.n_virtual`` wrap-placed chunks
    (every schedule's forward order is fill-drain; no backward), so it
    doubles as pipelined batch inference.

    Meshes: data x pipe x model (VERDICT r2 item 6) — with a 'model' axis
    the stage bodies run Megatron-TP (weight leaves are local shards, the
    row-parallel projections complete with a psum) while the head stays
    replicated, so every model rank materializes the same full [B, S, V]
    logits and a TP-pipeline-trained checkpoint scores/samples without
    any resharding (tests/test_tp_pipeline.py). Seq/expert axes remain
    scope cuts because the CONTRACT here is materialized full-batch
    logits — under those meshes use :func:`make_pipeline_loss_fn` (which
    never materializes logits) for eval.
    """
    D = mesh.shape[PIPE_AXIS]
    T = mesh.shape.get(MODEL_AXIS, 1)
    tp_axis = MODEL_AXIS if T > 1 else None
    for axis in (SEQ_AXIS, EXPERT_AXIS):
        if mesh.shape.get(axis, 1) > 1:
            raise NotImplementedError(
                f"make_pipeline_forward supports data x pipe x model meshes "
                f"(got a '{axis}' axis); for eval losses on SP/MoE meshes "
                f"use make_pipeline_loss_fn")
    _check_tp_divisibility(cfg, T)
    M = sched.n_microbatches
    V = sched.n_virtual
    if M < 1:
        raise ValueError(f"n_microbatches={M} must be >= 1")
    # No schedule compilation: every schedule's *forward* order is the same
    # fill-drain, so training-only constraints (e.g. 1F1B's M >= D) do not
    # apply to batch inference. ScheduleConfig already validates the name.
    if cfg.n_layers % (D * V):
        raise ValueError(f"n_layers={cfg.n_layers} must divide over "
                         f"{D * V} stages")
    dtype = jnp.dtype(cfg.dtype)
    fwd_perm = [(i, (i + 1) % D) for i in range(D)]
    table_np, n_slots = _fwd_tick_table(D, V, M)
    table = jnp.asarray(table_np)

    def spmd_fn(layers_stacked, embed, head, tokens):
        d = jax.lax.axis_index(PIPE_AXIS)
        layers_local = compute_cast(
            cfg, jax.tree.map(lambda x: x[0], layers_stacked))
        embed = compute_cast(cfg, embed)
        head = compute_cast(cfg, head)
        b_local, seq = tokens.shape
        assert b_local % M == 0, (
            f"local batch {b_local} not divisible by n_microbatches={M}")
        mb = b_local // M
        tokens_mb = tokens.reshape(M, mb, seq)
        mb_shape = (mb, seq, cfg.dim)

        masked_store = _masked_store

        def tick(carry, row_all):
            act_buf, recv, out = carry
            row = row_all[d]
            act_buf = masked_store(act_buf, recv, row[0])
            fv, fm, src = row[1], row[2], row[3]

            def fwd_unit(act_buf):
                vv, mm = jnp.maximum(fv, 0), jnp.maximum(fm, 0)
                first_stage = (d == 0) & (vv == 0)
                x_emb = embed_apply(cfg, embed, tokens_mb[mm]).astype(dtype)
                x = jnp.where(first_stage, x_emb,
                              act_buf[jnp.maximum(src, 0)])
                layer_p = jax.tree.map(
                    lambda t: jax.lax.dynamic_index_in_dim(
                        t, vv, 0, keepdims=False), layers_local)
                y = body_apply(cfg, layer_p, x, tp_axis=tp_axis, tp_size=T)
                last = (d == D - 1) & (vv == V - 1)
                logits_mb = jax.lax.cond(
                    last,
                    lambda: head_apply(cfg, head, y,
                                       embed=embed).astype(jnp.float32),
                    lambda: jnp.zeros((mb, seq, cfg.vocab_size),
                                      jnp.float32))
                return y, logits_mb, last

            def fwd_noop(act_buf):
                return (jnp.zeros(mb_shape, dtype),
                        jnp.zeros((mb, seq, cfg.vocab_size), jnp.float32),
                        jnp.asarray(False))

            y, logits_mb, last = jax.lax.cond(fm >= 0, fwd_unit, fwd_noop,
                                              act_buf)
            mm = jnp.maximum(fm, 0)
            out = out.at[mm].set(jnp.where(last, logits_mb, out[mm]))
            return (act_buf, jax.lax.ppermute(y, PIPE_AXIS, fwd_perm),
                    out), None

        out0 = jnp.zeros((M, mb, seq, cfg.vocab_size), jnp.float32)
        carry0 = (jnp.zeros((n_slots,) + mb_shape, dtype),
                  jnp.zeros(mb_shape, dtype), out0)
        (_, _, out), _ = jax.lax.scan(tick, carry0, table)
        # logits live on the last-stage device; replicate via psum of zeros
        out = jax.lax.psum(jnp.where(d == D - 1, out, 0.0), PIPE_AXIS)
        return out.reshape(b_local, seq, cfg.vocab_size)

    if T > 1:
        # Megatron per-leaf shards for the stacked layers; the head (and
        # tied embedding) stay replicated, so the full logits fall out of
        # every model rank identically — no gather, no resharding
        from .tensor_parallel import pipeline_layer_specs
        layer_spec = pipeline_layer_specs(cfg, PIPE_AXIS)
    else:
        layer_spec = P(PIPE_AXIS)
    sharded = _shard_map(
        spmd_fn, mesh,
        in_specs=(layer_spec, P(), P(), P(DATA_AXIS)),
        out_specs=P(DATA_AXIS),
    )

    @jax.jit
    def forward(params, tokens):
        stacked = stack_stage_layers(params["layers"], D, V)
        return sharded(stacked, params["embed"], params["head"], tokens)

    return forward
