"""Optimizer-coupled training on top of the pipeline executor.

The reference *measures* forward+backward only — no ``optim.step()`` exists
anywhere in it (SURVEY.md §3.3 note) — so the benchmark path
(:func:`..parallel.pipeline.make_pipeline_step`) stays optimizer-free for
parity. Real training on the model ladder (GPT-2 / Llama configs) composes
the same pipeline gradients with an optax optimizer under a single jit here.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import logging
import math
import os
import time
from typing import Any, Callable, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh

from ..ops.layers import chip_room, remat_room
from ..parallel.mesh import PIPE_AXIS
from ..parallel.pipeline import (make_pipeline_grad_fn, model_init,
                                 param_shardings)
from .checkpoint import restore_checkpoint, save_checkpoint
from .config import ModelConfig, ScheduleConfig
from .dynamics import as_dynamics_config, nonfinite_per_stage, stage_stats
from .profiling import (annotate, annotated_steps, format_setup,
                        setup_section, trace)

Pytree = Any

# The update is in place: params and opt_state are donated to the step, so
# the program holds ONE copy of weights + moments, not input and output
# side by side (gpt2-medium bs8 seq1024 on a v5e, by the chip compiler's
# count: 17.1 GB without the aliasing, 15.4 GB with it — XLA spends some of
# the freed room on temp — against 15.75 GB of HBM). A caller that wants
# the state it passed in must copy it first.
_jit_step = functools.partial(jax.jit, donate_argnums=(0, 1))


@annotate("setup/build_step")
def make_train_step(cfg: ModelConfig, mesh: Mesh, sched: ScheduleConfig,
                    optimizer: optax.GradientTransformation, moe=None,
                    sp_attn_impl: str = "ring",
                    tp_vocab_parallel: bool = False,
                    fsdp: bool = False, remat_backward=None,
                    unroll_ticks=None,
                    guard=None, fault_plan=None, dynamics=None,
                    ) -> Callable[[Pytree, Any, jax.Array, jax.Array],
                                  Tuple[Pytree, Any, jax.Array]]:
    """Jitted ``(params, opt_state, tokens, targets) ->
    (params, opt_state, loss)``: pipeline grads + optax update in one XLA
    program (so the update fuses with the grad psum epilogue). The
    incoming ``params`` and ``opt_state`` are DONATED — the update happens
    in their buffers and the arrays passed in are dead afterwards — and
    the new params are pinned to :func:`..parallel.pipeline.param_shardings`'
    resting layout, so state born in it (:func:`init_train_state`) stays
    in it step after step. ``moe``
    (a MoEConfig) selects MoE pipeline stages — see
    :func:`..parallel.pipeline.make_pipeline_grad_fn`. ``fsdp`` runs
    ZeRO-3 inside the pipeline (params placed via ``fsdp_shard_params``;
    grads come back in the same pipe x data layout, so the optax update —
    elementwise — runs shard-local and moments are born sharded).
    ``remat_backward`` picks the backward's activation policy (None = auto:
    stored where supported; True = rematerialize for minimal activation
    memory — see :func:`..parallel.pipeline.make_pipeline_grad_fn`).
    ``unroll_ticks`` picks the tick-executor formulation (None = auto:
    unrolled up to 64 table rows, phase-compressed scan beyond; also
    ``True``/``False``/``"phases"`` — compile-time economics in
    :func:`..parallel.pipeline.make_pipeline_grad_fn`).

    ``guard`` (a ``utils.resilience.AnomalyGuard``) switches to the
    *guarded* step: ``(params, opt_state, tokens, targets[, rng],
    guard_state) -> (params, opt_state, loss, guard_state)``. Inside the
    same XLA program it checks the loss and a PER-STAGE non-finite
    reduction over the gradients (stages partition the layer stack, so
    the poisoned stage is identified without a host round-trip) and, on
    failure, SELECTS the incoming params/opt_state (the anomalous step
    is skipped, the optimizer clock does not advance) and bumps
    device-resident anomaly counters (``resilience.init_guard_state``)
    including ``last_bad_stage`` — the first non-finite stage index, -2
    when only the loss was non-finite, -1 when no anomaly has fired.
    Everything stays on device — the counters ride the loss fetch at
    the caller's existing sync points, so the happy path costs zero
    extra host syncs. ``fault_plan.nan_grad_steps`` (requires
    ``guard``) poisons the gradients at those global step indices with
    NaN, baked into the traced program as a step-index compare — the
    deterministic blowup the guard tests recover from; with
    ``fault_plan.nan_grad_stage`` set, only that stage's layer-grad
    rows are poisoned (the loss stays finite), exercising the per-stage
    attribution path specifically.

    ``dynamics`` (True or a ``utils.dynamics.DynamicsConfig``) appends a
    device-resident stat dict to the step's outputs — per-stage/
    per-layer grad norms, param RMS, update ratios, non-finite counts
    (:func:`utils.dynamics.stage_stats`) plus, when the pipeline
    supports it (``DynamicsConfig.gns``), the per-microbatch squared
    grad norms feeding the gradient-noise-scale estimator. Like the
    guard counters the dict is read only at the caller's log syncs;
    with ``dynamics`` falsy the traced program is byte-identical to a
    build without the argument.

    Kept as the host span ``setup/build_step``: the Python that BUILDS the
    function (the executor's schedule table among it, ``setup/schedule``),
    not its tracing — that happens at the first call or ``lower()`` and is
    filed by ``utils.profiling.programs`` under the function's name."""
    dcfg = as_dynamics_config(dynamics)
    want_gns = dcfg is not None and dcfg.gns
    grad_fn = make_pipeline_grad_fn(cfg, mesh, sched, moe=moe,
                                    sp_attn_impl=sp_attn_impl,
                                    tp_vocab_parallel=tp_vocab_parallel,
                                    fsdp=fsdp, remat_backward=remat_backward,
                                    unroll_ticks=unroll_ticks,
                                    dynamics=want_gns)
    n_stages = mesh.shape[PIPE_AXIS] * sched.n_virtual
    resting = param_shardings(cfg, mesh, moe=moe, fsdp=fsdp,
                              tp_vocab_parallel=tp_vocab_parallel)

    def update(grads, opt_state, params):
        """(new params, new opt_state, updates): the optax update and its
        application, the step's ``train/optimizer`` region."""
        with jax.named_scope("train/optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            new_params = jax.lax.with_sharding_constraint(
                optax.apply_updates(params, updates), resting)
        return new_params, opt_state, updates

    nan_steps = tuple(getattr(fault_plan, "nan_grad_steps", ()) or ())
    nan_stage = getattr(fault_plan, "nan_grad_stage", None)
    if nan_steps and guard is None:
        raise ValueError(
            "fault_plan.nan_grad_steps requires an AnomalyGuard — injected "
            "NaN grads without the guard would corrupt the params forever")
    if nan_stage is not None and not 0 <= nan_stage < n_stages:
        raise ValueError(f"fault_plan.nan_grad_stage={nan_stage} out of "
                         f"range for {n_stages} stages")

    def step_room(params, opt_state) -> float:
        """What a chip of the mesh has left for the activations of the step
        being traced (:func:`..ops.layers.chip_room`), beside what it holds
        whatever a layer keeps: the donated parameters and optimizer state,
        the gradients (the parameters' bytes again) and, under mixed
        precision, ``compute_cast``'s copies. Whole trees: an upper bound
        where they rest sharded."""
        def nbytes(tree):
            return sum(x.size * x.dtype.itemsize
                       for x in jax.tree.leaves(tree))
        copies = (sum(x.size for x in jax.tree.leaves(params))
                  * jnp.dtype(cfg.dtype).itemsize if cfg.mixed_precision
                  else 0)
        return chip_room(mesh, 2 * nbytes(params) + nbytes(opt_state)
                         + copies)

    def run_grads(params, opt_state, tokens, targets, rng):
        """(loss, grads, sq_mb|None) — arity bridge over the dynamics
        pipeline variant, traced inside the room ``remat_layers`` may keep
        named products in."""
        args = (params, tokens, targets) + (() if rng is None else (rng,))
        with remat_room(step_room(params, opt_state)):
            if want_gns:
                return grad_fn(*args)
            loss, grads = grad_fn(*args)
        return loss, grads, None

    def dyn_stats(grads, params, updates, sq_mb):
        stats = stage_stats(cfg.n_layers, n_stages, grads, params=params,
                            updates=updates)
        if sq_mb is not None:
            stats["sq_mb"] = sq_mb
        return stats

    if guard is None:
        if cfg.dropout > 0.0:
            # train-mode dropout: the step takes a per-step PRNG key
            if dcfg is not None:
                @_jit_step
                def train_step_dropout_dyn(params, opt_state, tokens,
                                           targets, rng):
                    loss, grads, sq_mb = run_grads(params, opt_state, tokens,
                                                   targets, rng)
                    new_params, opt_state, updates = update(
                        grads, opt_state, params)
                    dyn = dyn_stats(grads, params, updates, sq_mb)
                    return new_params, opt_state, loss, dyn

                return train_step_dropout_dyn

            @_jit_step
            def train_step_dropout(params, opt_state, tokens, targets, rng):
                loss, grads, _ = run_grads(params, opt_state, tokens,
                                           targets, rng)
                params, opt_state, _ = update(grads, opt_state, params)
                return params, opt_state, loss

            return train_step_dropout

        if dcfg is not None:
            @_jit_step
            def train_step_dyn(params, opt_state, tokens, targets):
                loss, grads, sq_mb = run_grads(params, opt_state, tokens,
                                               targets, None)
                new_params, opt_state, updates = update(grads, opt_state,
                                                        params)
                dyn = dyn_stats(grads, params, updates, sq_mb)
                return new_params, opt_state, loss, dyn

            return train_step_dyn

        @_jit_step
        def train_step(params, opt_state, tokens, targets):
            loss, grads, _ = run_grads(params, opt_state, tokens, targets,
                                       None)
            params, opt_state, _ = update(grads, opt_state, params)
            return params, opt_state, loss

        return train_step

    def guarded(params, opt_state, tokens, targets, guard_state, rng=None):
        loss, grads, sq_mb = run_grads(params, opt_state, tokens, targets,
                                       rng)
        step = guard_state["step"]
        if nan_steps:
            bad = functools.reduce(
                jnp.logical_or, [step == k for k in nan_steps])
            if nan_stage is None:
                poison = jnp.where(bad, jnp.float32(jnp.nan),
                                   jnp.float32(1.0))
                grads = jax.tree.map(lambda g: g * poison.astype(g.dtype),
                                     grads)
                loss = loss * poison.astype(loss.dtype)
            else:
                # stage-targeted fault: poison only that stage's layer
                # rows and leave the loss finite — ONLY the per-stage
                # reduction can catch and attribute it. Multiplicative
                # (NaN*g) like the global path, not a select: a
                # where(mask, nan, g) per leaf interacts pathologically
                # with XLA:CPU's fusion when max-reductions consume the
                # result (observed 140s vs 50s compiles on the smoke
                # config).
                lps = cfg.n_layers // n_stages
                in_stage = (jnp.arange(cfg.n_layers) // lps) == nan_stage
                row = jnp.where(bad & in_stage, jnp.float32(jnp.nan),
                                jnp.float32(1.0))

                def poison_layer(g):
                    m = row.reshape((cfg.n_layers,) + (1,) * (g.ndim - 1))
                    return g * m.astype(g.dtype)

                grads = dict(grads, layers=jax.tree.map(
                    poison_layer, grads["layers"]))
        # fused per-stage predicate: loss finite AND every stage's grads
        # finite. Computed on device; no host readback here (the caller
        # fetches the guard counters only where it already fetches the
        # loss). The per-stage counts replace the old all-or-nothing
        # global-norm isfinite — same verdict, now attributable.
        nf = nonfinite_per_stage(cfg.n_layers, n_stages, grads)
        loss_ok = jnp.isfinite(loss)
        stage_ok = nf == 0
        grads_ok = stage_ok.all()
        ok = loss_ok & grads_ok
        first_bad = jnp.where(
            grads_ok,
            jnp.where(loss_ok, jnp.int32(-1), jnp.int32(-2)),
            jnp.argmax(~stage_ok).astype(jnp.int32))
        new_params, new_opt, updates = update(grads, opt_state, params)
        dyn = (dyn_stats(grads, params, updates, sq_mb)
               if dcfg is not None else None)

        def keep(new, old):
            return jnp.where(ok, new, old)

        params = jax.tree.map(keep, new_params, params)
        opt_state = jax.tree.map(keep, new_opt, opt_state)
        anom = (~ok).astype(jnp.int32)
        guard_state = {
            "step": step + 1,
            "consec": jnp.where(ok, 0, guard_state["consec"] + 1),
            "total": guard_state["total"] + anom,
            "last_anomaly_step": jnp.where(
                ok, guard_state["last_anomaly_step"], step),
            "last_bad_stage": jnp.where(
                ok, guard_state["last_bad_stage"], first_bad),
        }
        if dcfg is not None:
            return params, opt_state, loss, guard_state, dyn
        return params, opt_state, loss, guard_state

    if cfg.dropout > 0.0:
        @_jit_step
        def guarded_step_dropout(params, opt_state, tokens, targets, rng,
                                 guard_state):
            return guarded(params, opt_state, tokens, targets, guard_state,
                           rng)

        return guarded_step_dropout

    @_jit_step
    def guarded_step(params, opt_state, tokens, targets, guard_state):
        return guarded(params, opt_state, tokens, targets, guard_state)

    return guarded_step


@annotate("setup/init_params")
def init_params(cfg: ModelConfig, mesh: Mesh, key: jax.Array, moe=None,
                fsdp: bool = False,
                tp_vocab_parallel: bool = False) -> Pytree:
    """``transformer_init`` (``moe_lm_init`` with ``moe``) born in the
    resting layout (:func:`..parallel.pipeline.param_shardings`): the init
    is jitted with those ``out_shardings``, so each device only ever
    materializes its own stages' weights — the whole model never sits on
    the first device, which for gpt2-xl (6.2 GB fp32) plus its Adam
    moments would not fit a 16 GB chip. Kept as the host span
    ``setup/init_params``: getting the init program (traced, lowered,
    compiled or read back) and enqueueing it, not the device's work."""
    return jax.jit(model_init(cfg, moe), out_shardings=param_shardings(
        cfg, mesh, moe=moe, fsdp=fsdp,
        tp_vocab_parallel=tp_vocab_parallel))(key)


def opt_state_shardings(optimizer: optax.GradientTransformation,
                        params: Pytree, mesh: Mesh,
                        zero1: bool = False) -> Pytree:
    """Where ``optimizer.init(params)`` rests: one ``NamedSharding`` per
    state leaf. Every leaf that mirrors a parameter — its tree path ends in
    that parameter's path and the shapes agree: Adam's mu/nu,
    ``MultiSteps``' accumulated grads — rests in that parameter's sharding;
    leaves that belong to no parameter (step counts) are replicated.
    ``zero1`` with a 'data' axis instead shards every leaf over 'data' on
    its largest divisible dim (the FSDP placement rule). ``params`` may be
    arrays or ``ShapeDtypeStruct``s carrying shardings."""
    from jax.sharding import NamedSharding, PartitionSpec

    from ..parallel.fsdp import fsdp_specs
    from ..parallel.mesh import DATA_AXIS

    shapes = jax.eval_shape(optimizer.init, params)
    n_data = mesh.shape.get(DATA_AXIS, 1)
    if zero1 and n_data > 1:
        return jax.tree.map(lambda s: NamedSharding(mesh, s),
                            fsdp_specs(shapes, n_data),
                            is_leaf=lambda x: not isinstance(
                                x, (dict, list, tuple)))
    of_param = dict(jax.tree_util.tree_flatten_with_path(params)[0])
    replicated = NamedSharding(mesh, PartitionSpec())

    def rest(path, leaf):
        for n in range(len(path)):
            p = of_param.get(path[n:])
            if p is not None and p.shape == leaf.shape:
                return p.sharding
        return replicated

    return jax.tree_util.tree_map_with_path(rest, shapes)


@annotate("setup/init_opt_state")
def init_opt_state(optimizer: optax.GradientTransformation, params: Pytree,
                   mesh: Mesh, zero1: bool = False) -> Pytree:
    """``optimizer.init`` jitted INTO :func:`opt_state_shardings`: the
    state is born where it rests, so no replicated (or first-device) peak
    ever materializes. Kept as the host span ``setup/init_opt_state``."""
    return jax.jit(optimizer.init, out_shardings=opt_state_shardings(
        optimizer, params, mesh, zero1=zero1))(params)


def shard_opt_state(opt_state: Pytree, mesh: Mesh) -> Pytree:
    """ZeRO-1: place optimizer-state leaves (Adam moments etc.) sharded over
    the mesh's 'data' axis, each on its largest divisible dimension
    (reusing the FSDP placement rule). Parameters stay replicated; the
    train step's elementwise update computes on local shards and XLA
    all-gathers the (sharded) updates back onto the replicated params —
    the ZeRO-1 dataflow from sharding annotations alone. Committed input
    shardings propagate through jit — the returned state keeps its data
    sharding across steps (asserted in tests/test_fsdp.py). Composes with
    every pipeline configuration (the grad function runs under its own
    shard_map; only the optax update is affected)."""
    from ..parallel.fsdp import shard_params_fsdp
    from ..parallel.mesh import DATA_AXIS

    if mesh.shape.get(DATA_AXIS, 1) <= 1:
        return opt_state
    return shard_params_fsdp(opt_state, mesh)


def adamw(learning_rate: float = 3e-4, weight_decay: float = 0.01,
          warmup_steps: int = 100, total_steps: int = 10000,
          max_grad_norm: float = 1.0) -> optax.GradientTransformation:
    """Standard LM recipe: global-norm clip + AdamW + linear-warmup cosine.

    Weight decay applies to projection matrices only — biases, norm
    scales/biases, and embeddings are excluded, the standard LM practice
    (decaying LayerNorm scales toward zero actively hurts). Leaf ndim
    cannot distinguish these in the stacked-layer layout (a per-layer bias
    stack is 2-D), so the mask keys off this framework's naming
    convention: matrices live under "w" (linear/attention/router, and the
    nemotron_h layers' projections and convolution, latent attention's five
    matrices) and "w1"/"w2"/"w3" (expert stacks, ``models/moe.py``'s and
    ``ops/experts.py``'s; ``w3`` is the gated form's linear branch). The nemotron_h
    leaves that are no matrices — ``A_log``, ``D``, ``dt_bias``, the
    router's ``bias`` buffer, norm scales — carry other names and are not
    decayed."""
    lr = optax.warmup_cosine_decay_schedule(
        init_value=0.0, peak_value=learning_rate, warmup_steps=warmup_steps,
        decay_steps=max(total_steps, warmup_steps + 1))

    def decay_mask(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, _: getattr(path[-1], "key", None) in ("w", "w1", "w2",
                                                             "w3"),
            params)

    return optax.chain(
        optax.clip_by_global_norm(max_grad_norm),
        optax.adamw(lr, weight_decay=weight_decay, mask=decay_mask),
    )


def make_eval_fn(cfg: ModelConfig, mesh: Mesh, sched: ScheduleConfig,
                 moe=None, sp_attn_impl: str = "ring",
                 tp_vocab_parallel: bool = False, fsdp: bool = False,
                 ) -> Callable[[Pytree, jax.Array, jax.Array], jax.Array]:
    """Jitted eval-mode loss over the mesh. Every training mesh (data x
    pipe x model x seq x expert, any n_virtual, incl. vocab-parallel CE
    and MoE stages) uses the forward-only pipelined loss — no backward,
    no rematerialization. **MoE convention**: the eval loss is the CE
    term only (the routing load-balance aux is a training regularizer,
    not a model-quality quantity — perplexity comes from CE), so an MoE
    eval loss is directly comparable across capacity/aux settings. Any
    configuration the training step accepts evaluates here (both require
    n_layers to divide the stage count); dropout configs evaluate in
    eval mode (dropout off)."""
    from ..parallel.pipeline import make_pipeline_loss_fn

    eval_cfg = (dataclasses.replace(cfg, dropout=0.0)
                if cfg.dropout else cfg)
    return make_pipeline_loss_fn(eval_cfg, mesh, sched,
                                 sp_attn_impl=sp_attn_impl,
                                 tp_vocab_parallel=tp_vocab_parallel,
                                 fsdp=fsdp, moe=moe)


def evaluate(eval_fn, params, data: Iterator[Tuple[jax.Array, jax.Array]],
             num_batches: int) -> dict:
    """Mean eval loss and perplexity over ``num_batches`` from ``data``.

    The reference has no evaluation path at all (SURVEY.md §5: loss values
    are never asserted, data is random tokens); this is the standard LM eval
    the model ladder needs. Returns ``{"eval_loss", "perplexity",
    "num_batches"}``; perplexity = exp(mean token CE).
    """
    total = 0.0
    n = 0
    for _ in range(num_batches):
        try:
            tokens, targets = next(data)
        except StopIteration:
            break
        total += float(eval_fn(params, tokens, targets))
        n += 1
    if n == 0:
        raise ValueError("evaluate: data iterator yielded no batches")
    mean = total / n
    return {"eval_loss": mean, "perplexity": math.exp(min(mean, 700.0)),
            "num_batches": n}


def _latest_step_dir(checkpoint_dir: str) -> Optional[Tuple[int, str]]:
    """Find the newest *committed* ``step_{n}`` checkpoint under
    ``checkpoint_dir``. Picking the newest dir by number alone would
    hand resume a partially-written async save that died mid-flush;
    :func:`.resilience.latest_committed_step_dir` skips uncommitted
    shells (warning on fallback) and only trusts a marker-less tree
    when NO dir has a marker (legacy checkpoints)."""
    from .resilience import latest_committed_step_dir
    return latest_committed_step_dir(checkpoint_dir)


def fit(cfg: ModelConfig, mesh: Mesh, sched: ScheduleConfig, params: Pytree,
        data: Iterator[Tuple[jax.Array, jax.Array]], num_steps: int,
        optimizer: Optional[optax.GradientTransformation] = None,
        log_every: int = 10, verbose: bool = True,
        checkpoint_dir: Optional[str] = None, checkpoint_every: int = 0,
        resume: bool = False, skip_data_on_resume: bool = True,
        metrics_path: Optional[str] = None, moe=None,
        sp_attn_impl: str = "ring", tp_vocab_parallel: bool = False,
        zero1: bool = False, fsdp: bool = False, remat_backward=None,
        unroll_ticks=None,
        dropout_seed: int = 0,
        eval_data: Optional[Callable[[], Iterator]] = None,
        eval_every: int = 0, eval_batches: int = 8,
        profile_dir: Optional[str] = None,
        profile_steps: Tuple[int, int] = (2, 5),
        grad_accum: int = 1,
        report_dir: Optional[str] = None,
        keep_last: Optional[int] = None,
        guard=None, fault_plan=None,
        handle_preemption: bool = False,
        stall_timeout_s: Optional[float] = None,
        dynamics=None,
        on_log: Optional[Callable[[int, Pytree, jax.Array], None]] = None):
    """Training loop over a ``(tokens, targets)`` iterator.

    Returns (params, list of (step, loss)). ``params`` is CONSUMED: it is
    placed in the resting layout (:func:`..parallel.pipeline.
    param_shardings` — build it there with :func:`init_params`) and donated
    to the step, so use the returned tree; a caller that still needs the
    tree it passed in copies it first. The data contract matches the
    reference's synthetic setup (random token batches,
    ``LLMsDistributedTrainingHelper.py:191-194``) but accepts any iterator.

    Beyond the minimal loop (capabilities the reference lacks, SURVEY.md §5):

    - ``checkpoint_dir`` + ``checkpoint_every``: save
      ``{'params', 'opt_state', 'step'}`` to ``step_{n}/`` via Orbax every n
      steps (and at the end); ``resume=True`` restores the newest one and
      continues counting from it. With ``skip_data_on_resume`` (default) the
      completed steps' batches are drained from ``data`` first, so re-running
      an interrupted job with the same (deterministic) data stream reproduces
      the uninterrupted run instead of double-training early batches. Pass
      ``False`` only if the caller re-positions the iterator itself.
    - ``metrics_path``: append one JSON line per log point —
      ``{"step", "loss", "tokens_per_sec", "elapsed_s"}`` — the streaming
      twin of the sweep's metrics dict (same tokens/sec definition:
      batch*seq*steps / wall-clock between log points).
    - ``eval_data`` + ``eval_every``: every n steps (and at the end), run
      :func:`evaluate` over ``eval_batches`` batches from a FRESH iterator
      (``eval_data`` is a zero-arg callable returning one, so the same
      held-out batches are scored every time); results go to the metrics
      stream and (``verbose``) stdout. Eval runs in eval mode
      (no dropout) on the forward-only pipelined loss where the mesh allows.
    - ``profile_dir``: capture a ``jax.profiler`` trace (XProf/TensorBoard)
      of steps ``profile_steps`` = [start, end) — default (2, 5): past the
      compile step, three steady-state steps.
    - ``grad_accum``: average gradients over k data batches before each
      optimizer update (``optax.MultiSteps``) — accumulation ACROSS steps,
      on top of the within-step microbatch accumulation the pipeline
      schedule already performs. k accumulated steps on batch B step the
      optimizer exactly as one step on batch k*B would. ``num_steps``
      counts data batches, so optimizer updates = num_steps / k.
    - ``report_dir``: write a structured :class:`.telemetry.RunReport` —
      ``events.jsonl`` streamed as the run progresses (every train-log and
      eval point) plus a final ``report.json`` manifest (config, mesh
      shape, schedule, compile_s, jax/jaxlib versions, final metrics) in
      the schema ``telemetry.validate_report`` checks — the same schema
      sweep rows emit (docs/observability.md).

    Resilience (docs/resilience.md; all opt-in, off by default):

    - Checkpoints go through ``resilience.CheckpointManager``: every save
      is committed via an atomic marker (step, config fingerprint, pytree
      digest) once its flush lands, resume restores the newest *committed*
      checkpoint (skipping shells a killed async save left behind), and
      ``keep_last`` garbage-collects older committed ones.
    - ``guard`` (``True`` or a ``resilience.AnomalyGuard``): the jitted
      step skips non-finite steps (see :func:`make_train_step`); the
      device-resident counters are read only at log points (zero extra
      syncs per step), anomalies land as report events/counters, and
      exceeding the consecutive-anomaly budget checkpoints the last good
      state and raises ``resilience.AnomalyBudgetExceeded``.
    - ``handle_preemption``: SIGTERM/SIGINT finish the in-flight step,
      write a synchronous committed checkpoint, emit a ``preempted``
      event and return normally — the resumed run continues bit-exact.
    - ``stall_timeout_s``: a wall-clock watchdog thread logs (and
      reports) a ``stall`` diagnostic when no step completes in time.
    - Any other crash banks the last completed step in a committed
      checkpoint before the exception propagates.
    - ``fault_plan`` (``resilience.FaultPlan``) injects deterministic
      faults — NaN grads, data-iterator failure, kill-during-save,
      simulated preemption — for the resilience tests and smoke.

    Training dynamics (docs/observability.md §7; opt-in, off by default):

    - ``dynamics`` (``True`` or a ``dynamics.DynamicsConfig``): per-stage /
      per-layer gradient statistics computed inside the jitted step and
      read only at log points (riding the loss sync — zero extra syncs), a
      gradient-noise-scale estimate from the per-microbatch squared norms
      the pipeline accumulates anyway, a host-side ring buffer of recent
      step stats + batch digests, and — on an anomaly or a z-score loss
      spike — a forensic bundle written next to the manifest (requires
      ``report_dir``). With ``guard`` set, skipped steps additionally emit
      an ``anomaly_attributed`` event naming the first non-finite stage.
      ``dynamics=None`` (default) leaves the compiled step byte-identical.
    - ``on_log``: called as ``on_log(step, params, tokens)`` at every log
      point, after the loss is read — for what a caller wants said about
      its own model there (``scripts/train.py`` logs an expert model's
      routing). It must not keep or donate ``params``.
    """
    from .resilience import (AnomalyBudgetExceeded, AnomalyGuard,
                             CheckpointManager, PreemptionHandler,
                             SimulatedKill, StepWatchdog,
                             config_fingerprint, init_guard_state)
    if guard is True:
        guard = AnomalyGuard()
    if optimizer is None:
        # the LR schedule advances once per OPTIMIZER update, which under
        # grad_accum happens every k data batches — size its horizon in
        # updates, not batches, or warmup/decay stretch k times too long
        optimizer = adamw(total_steps=max(1, num_steps // grad_accum))
    if grad_accum > 1:
        optimizer = optax.MultiSteps(optimizer, every_k_schedule=grad_accum)
    dcfg = as_dynamics_config(dynamics)
    step_fn = make_train_step(cfg, mesh, sched, optimizer, moe=moe,
                              sp_attn_impl=sp_attn_impl,
                              tp_vocab_parallel=tp_vocab_parallel,
                              fsdp=fsdp, remat_backward=remat_backward,
                              unroll_ticks=unroll_ticks,
                              guard=guard, fault_plan=fault_plan,
                              dynamics=dcfg)
    report = None
    if report_dir is not None:
        from .telemetry import RunReport
        report = RunReport(out_dir=report_dir, name="fit")
        # artifact-backed schedules record their certification pin (table
        # digest + fingerprint + source) so the manifest names exactly
        # which certified table the run executed
        from ..parallel.schedules import registered_artifact_info
        art_info = registered_artifact_info(sched.name)
        report.set_meta(config=dataclasses.asdict(cfg),
                        schedule=dataclasses.asdict(sched),
                        mesh_shape=dict(mesh.shape),
                        num_steps=num_steps, grad_accum=grad_accum,
                        backend=jax.devices()[0].platform,
                        **({"schedule_artifact": art_info}
                           if art_info else {}))
    if fsdp and zero1:
        raise ValueError("fsdp already shards optimizer state (ZeRO-3 "
                         "subsumes ZeRO-1) — drop --zero1")
    # Params rest where the executor takes them (a no-op for params born
    # there by init_params; with fsdp that is pipe x data sharded) and the
    # moments are born in their parameter's layout — or, zero1, sharded
    # over 'data' — so neither ever materializes whole on one device.
    params = jax.device_put(params, param_shardings(
        cfg, mesh, moe=moe, fsdp=fsdp, tp_vocab_parallel=tp_vocab_parallel))
    opt_state = init_opt_state(optimizer, params, mesh, zero1=zero1)

    mgr = None
    if checkpoint_dir:
        mgr = CheckpointManager(
            checkpoint_dir, keep_last=keep_last,
            fingerprint=config_fingerprint(cfg, sched, dict(mesh.shape)),
            fault_plan=fault_plan)
    if fault_plan is not None:
        data = fault_plan.wrap_data(data)

    start_step = 0
    if resume and mgr is not None:
        with annotate("setup/restore"):
            restored = mgr.restore_latest({
                "params": params, "opt_state": opt_state,
                "step": jnp.asarray(0)})
        if restored is not None:
            n, path, state = restored
            # the restore template carries the live shardings (see
            # checkpoint.restore_checkpoint), so a zero1 run restores its
            # moments directly into the sharded layout
            params, opt_state = state["params"], state["opt_state"]
            start_step = int(state["step"]) + 1
            if skip_data_on_resume:
                for _ in range(start_step):
                    next(data)
            if verbose:
                print(f"resumed from {path} (step {n})", flush=True)
            if report is not None:
                report.event("resumed", step=n, path=path)

    def _save(i, wait=True):
        with annotate("checkpoint_save"):
            mgr.save(i, {"params": params, "opt_state": opt_state,
                         "step": jnp.asarray(i)}, wait=wait)

    guard_state = init_guard_state(start_step) if guard is not None else None
    guard_seen = 0  # anomalies already surfaced (host high-water mark)

    # Training-dynamics host state: GNS estimator over the per-microbatch
    # squared norms, ring buffer + spike detector, and the latest device
    # stats (fetched only at log syncs). All None when dynamics is off.
    gns_est = None
    recorder = None
    dyn_latest = None  # device-resident stats from the newest step
    dyn_host = None    # host copy fetched at the last log sync
    n_skipped_attributed = 0
    if dcfg is not None:
        from .dynamics import (GNSEstimator, ForensicRecorder, batch_digest,
                               dynamics_section)
        recorder = ForensicRecorder(out_dir=report_dir, ring=dcfg.ring,
                                    spike_z=dcfg.spike_z,
                                    warmup=dcfg.spike_warmup)

    def _checkpoint_pointer():
        """Last committed checkpoint step/path for forensic bundles."""
        if mgr is None:
            return None
        s = mgr.stats()
        return {k: s[k] for k in ("last_committed_step",) if k in s}

    # Per-step dropout keys fold the step index from one base key, so a
    # resumed run draws the same masks the uninterrupted run would have.
    drop_key = jax.random.key(dropout_seed) if cfg.dropout > 0.0 else None

    eval_fn = None
    if eval_data is not None and eval_every:
        eval_fn = make_eval_fn(cfg, mesh, sched, moe=moe,
                               sp_attn_impl=sp_attn_impl,
                               tp_vocab_parallel=tp_vocab_parallel,
                               fsdp=fsdp)

    def _eval(i):
        with annotate("eval"):
            m = evaluate(eval_fn, params, eval_data(), eval_batches)
        if verbose:
            print(f"step {i}: eval_loss {m['eval_loss']:.4f} "
                  f"ppl {m['perplexity']:.2f}", flush=True)
        if metrics_path:
            with open(metrics_path, "a") as f:
                f.write(json.dumps({"step": i, **m}) + "\n")
        if report is not None:
            report.event("eval", step=i, **m)
        return m

    preempt = PreemptionHandler(enabled=handle_preemption)
    watchdog = None
    if stall_timeout_s:
        def _on_stall(info):
            logging.getLogger(__name__).warning(
                "fit: no step completed in %.1fs (last completed step %s) "
                "— stalled collective or dead input pipeline?",
                info["stalled_s"], info["step"])
            if report is not None:
                report.count("stalls")
                report.event("stall", **info)
        watchdog = StepWatchdog(stall_timeout_s, _on_stall)

    history = []
    startup_shown = False
    # the name JAX's compile events carry for the step (train_step,
    # guarded_step, ...): the recorder's first request under it is the
    # program this run waits for
    step_name = getattr(step_fn, "__name__", "train_step")
    window_start = time.perf_counter()
    window_tokens = 0
    profile = contextlib.ExitStack()  # the open profiler session, if any
    profiling = False
    preempted = False
    last_done = start_step - 1  # newest step whose outputs params hold
    data_shape = None  # (batch, seq) of the first batch, for the memory model

    def _finalize_report():
        if report is None:
            return
        report.count("steps", max(last_done - start_step + 1, 0))
        if history:
            report.gauge("final_loss", history[-1][1])
        if data_shape is not None:
            # analytic HBM from the verifier's slot peaks (+ AdamW's two
            # fp32 moments); an accounting error never takes down the
            # run's report
            try:
                from ..analysis.memory_model import memory_model_section
                from ..parallel.schedules import compile_schedule
                cs = compile_schedule(sched.name, mesh.shape["pipe"],
                                      sched.n_virtual, sched.n_microbatches)
                report.attach_memory(memory_model_section(
                    cs, cfg, batch_size=data_shape[0],
                    seq_length=data_shape[1],
                    remat_backward=remat_backward,
                    optimizer_slots=2))
            except Exception as e:
                report.event("memory_model_error", error=str(e))
        if dcfg is not None:
            report.attach_dynamics(dynamics_section(
                mesh.shape[PIPE_AXIS] * sched.n_virtual,
                last_stats=dyn_host,
                gns=gns_est.value() if gns_est is not None else None,
                gns_updates=gns_est.n_updates if gns_est is not None else 0,
                n_skipped_attributed=n_skipped_attributed,
                forensic_bundles=recorder.bundles))
        res = {}
        if mgr is not None:
            res.update(mgr.stats())
        if guard is not None:
            res["anomaly_budget"] = guard.max_consecutive
            res["anomalies"] = guard_seen
        if handle_preemption or (fault_plan is not None
                                 and fault_plan.preempt_at_step is not None):
            res["preempted"] = preempted
        if watchdog is not None:
            res["stalls"] = watchdog.stalls
        if res:
            report.attach_resilience(res)
        report.attach_setup(setup_section(step_name))
        report.write()

    # profile_steps counts from the first step THIS run executes, so a
    # resumed job still captures a window instead of silently skipping it
    prof_start = start_step + profile_steps[0]
    prof_stop = start_step + max(profile_steps[1], profile_steps[0] + 1)
    try:
        with preempt:
            # Host spans for a --profile-dir trace, on the device trace's
            # clock: input_wait / dispatch / wait_loss here (the names the
            # benchmark's own loop uses, so an idle gap of the device is
            # named the same way in either), eval and checkpoint_save above;
            # each iteration under a StepTraceAnnotation. All no-ops
            # without a profiler session.
            for i in annotated_steps(range(start_step, num_steps)):
                if fault_plan is not None and fault_plan.preempt_at_step == i:
                    preempt.trigger()  # deterministic stand-in for SIGTERM
                if profile_dir is not None:
                    if i == prof_start and not profiling:
                        profile.enter_context(trace(profile_dir))
                        profiling = True
                    elif i == prof_stop and profiling:
                        profile.close()
                        profiling = False
                        if verbose:
                            print(f"profile trace written to {profile_dir}",
                                  flush=True)
                with annotate("input_wait"):
                    tokens, targets = next(data)
                if data_shape is None:
                    data_shape = (int(tokens.shape[0]), int(tokens.shape[1]))
                if recorder is not None:
                    # inputs are host-visible already — hashing adds no sync
                    recorder.note_batch(i, batch_digest(tokens, targets))
                # first executed step = trace + compile + run; the report's
                # compile_s timer brackets it (forced, so the timer is honest)
                # and so does the setup/first_step span, which forces nothing:
                # without a report it holds getting the program, not its run
                first = report is not None and i == start_step
                with (report.timer("compile_s") if first
                      else contextlib.nullcontext()), \
                        (annotate("setup/first_step") if i == start_step
                         else contextlib.nullcontext()), annotate("dispatch"):
                    args = (params, opt_state, tokens, targets)
                    if drop_key is not None:
                        args += (jax.random.fold_in(drop_key, i),)
                    if guard_state is not None and dcfg is not None:
                        (params, opt_state, loss, guard_state,
                         dyn_latest) = step_fn(*args, guard_state)
                    elif guard_state is not None:
                        params, opt_state, loss, guard_state = step_fn(
                            *args, guard_state)
                    elif dcfg is not None:
                        params, opt_state, loss, dyn_latest = step_fn(*args)
                    else:
                        params, opt_state, loss = step_fn(*args)
                    if first:
                        jax.block_until_ready(loss)
                last_done = i
                if watchdog is not None:
                    watchdog.beat(i)
                window_tokens += tokens.shape[0] * tokens.shape[1]
                if i % log_every == 0 or i == num_steps - 1:
                    with annotate("wait_loss"):
                        # device sync: closes the timing window
                        loss_f = float(loss)
                    elapsed = time.perf_counter() - window_start
                    history.append((i, loss_f))
                    if verbose:
                        print(f"step {i}: loss {loss_f:.4f}", flush=True)
                        if not startup_shown:  # once, at the first log point
                            print(format_setup(setup_section(step_name)),
                                  flush=True)
                            startup_shown = True
                    if on_log is not None:
                        on_log(i, params, tokens)
                    if metrics_path:
                        with open(metrics_path, "a") as f:
                            f.write(json.dumps({
                                "step": i, "loss": loss_f,
                                "tokens_per_sec": round(window_tokens / elapsed,
                                                        2),
                                "elapsed_s": round(elapsed, 4)}) + "\n")
                    if report is not None:
                        report.event("train_log", step=i, loss=loss_f,
                                     tokens_per_sec=round(window_tokens / elapsed,
                                                          2),
                                     elapsed_s=round(elapsed, 4))
                    if dyn_latest is not None:
                        # same program as the loss just fetched — this read
                        # rides that sync, it does not add one
                        dyn_host = jax.device_get(dyn_latest)
                        if (dyn_host.get("sq_mb") is not None
                                and data_shape is not None):
                            if gns_est is None:
                                nd = dict(mesh.shape).get("data", 1)
                                toks = data_shape[0] * data_shape[1]
                                small = toks / (nd * sched.n_microbatches)
                                if small < toks:  # M*data==1: no norm pair
                                    gns_est = GNSEstimator(
                                        batch_small=small, batch_big=toks,
                                        ema=dcfg.ema)
                            if gns_est is not None:
                                gns_est.update(
                                    float(dyn_host["sq_mb"].mean()),
                                    float(dyn_host["grad_norm"]) ** 2)
                        gns_val = (gns_est.value() if gns_est is not None
                                   else None)
                        if report is not None:
                            report.event(
                                "dynamics", step=i,
                                grad_norm=float(dyn_host["grad_norm"]),
                                grad_norm_per_stage=[
                                    float(x) for x in
                                    dyn_host["grad_norm_per_stage"]],
                                nonfinite_per_stage=[
                                    int(x) for x in
                                    dyn_host["nonfinite_per_stage"]],
                                gns=gns_val)
                        spike_z = recorder.observe(i, loss_f, stats=dyn_host,
                                                   gns=gns_val)
                        if spike_z is not None:
                            path = recorder.dump(
                                i, "loss_spike", loss=loss_f, z=spike_z,
                                stats={k: v for k, v in dyn_host.items()
                                       if k != "sq_mb"},
                                checkpoint=_checkpoint_pointer())
                            if verbose:
                                print(f"step {i}: loss spike (z={spike_z:.1f})"
                                      + (f" — forensics at {path}"
                                         if path else ""), flush=True)
                            if report is not None:
                                report.count("loss_spikes")
                                report.event("loss_spike", step=i,
                                             loss=loss_f,
                                             z=round(float(spike_z), 2),
                                             bundle=path)
                    if guard_state is not None:
                        # the counters were computed by the same program as the
                        # loss just fetched — this read rides that sync, it
                        # does not add one
                        gs = {k: int(v)
                              for k, v in jax.device_get(guard_state).items()}
                        if gs["total"] > guard_seen:
                            delta = gs["total"] - guard_seen
                            guard_seen = gs["total"]
                            bad = gs.get("last_bad_stage", -1)
                            where = (f" in stage {bad}" if bad >= 0
                                     else " (loss only)" if bad == -2 else "")
                            if verbose:
                                print(f"step {i}: anomaly guard skipped {delta} "
                                      f"step(s) (total {gs['total']}, last at "
                                      f"step {gs['last_anomaly_step']}{where})",
                                      flush=True)
                            if report is not None:
                                report.count("anomalies", delta)
                                report.event(
                                    "anomaly", step=i, total=gs["total"],
                                    consec=gs["consec"],
                                    last_anomaly_step=gs["last_anomaly_step"],
                                    last_bad_stage=bad)
                            if dcfg is not None:
                                # explainable verdict: which stage first went
                                # non-finite, and on what statistic
                                n_skipped_attributed += delta
                                statistic = ("nonfinite_grad" if bad >= 0
                                             else "nonfinite_loss")
                                attribution = {
                                    "stage": bad, "statistic": statistic,
                                    "last_anomaly_step":
                                        gs["last_anomaly_step"]}
                                if dyn_host is not None:
                                    attribution["nonfinite_per_stage"] = [
                                        int(x) for x in
                                        dyn_host["nonfinite_per_stage"]]
                                if report is not None:
                                    report.event("anomaly_attributed", step=i,
                                                 **attribution)
                                path = recorder.dump(
                                    i, "anomaly", loss=loss_f,
                                    stats=None if dyn_host is None else {
                                        k: v for k, v in dyn_host.items()
                                        if k != "sq_mb"},
                                    attribution=attribution,
                                    checkpoint=_checkpoint_pointer())
                                if report is not None and path is not None:
                                    report.event("forensic_bundle", step=i,
                                                 trigger="anomaly",
                                                 bundle=path)
                        if gs["consec"] >= guard.max_consecutive:
                            # params/opt_state are the last GOOD state — every
                            # anomalous update was selected away in the step
                            if report is not None:
                                report.count("anomaly_aborts")
                                report.event("anomaly_abort", step=i,
                                             consec=gs["consec"],
                                             budget=guard.max_consecutive)
                            if mgr is not None:
                                _save(i, wait=True)
                            _finalize_report()
                            raise AnomalyBudgetExceeded(
                                f"{gs['consec']} consecutive anomalous steps at "
                                f"step {i} (budget {guard.max_consecutive})"
                                + (" — last good state checkpointed"
                                   if mgr is not None else ""))
                    window_start = time.perf_counter()
                    window_tokens = 0
                if (eval_fn is not None and (i + 1) % eval_every == 0
                        and i != num_steps - 1):
                    _eval(i)
                    # eval time isn't train time: restart the whole timing
                    # window (tokens too, else tokens_per_sec over-reports)
                    window_start = time.perf_counter()
                    window_tokens = 0
                if preempt.triggered:
                    # the in-flight step already finished (the handler only
                    # sets a flag): bank it synchronously and exit resumable
                    preempted = True
                    sig = preempt.signum
                    if verbose:
                        print(f"step {i}: preemption ({sig}) — checkpointing "
                              "and exiting resumable", flush=True)
                    if report is not None:
                        report.count("preemptions")
                        report.event("preempted", step=i,
                                     signal=int(sig) if sig is not None
                                     else None)
                    if mgr is not None:
                        _save(i, wait=True)
                    break
                if (mgr is not None and checkpoint_every
                        and (i + 1) % checkpoint_every == 0
                        and i != num_steps - 1):
                    _save(i, wait=False)  # flush in background; training goes on
    except (SimulatedKill, AnomalyBudgetExceeded):
        raise  # injected death / already-handled abort: no crash save
    except BaseException as e:
        # crash-safe exit: params/opt_state are step last_done's outputs —
        # bank them committed so the run resumes instead of restarting
        if mgr is not None and last_done >= start_step:
            try:
                _save(last_done, wait=True)
                if verbose:
                    print(f"crash at step {last_done + 1}: banked committed "
                          f"checkpoint at step {last_done}", flush=True)
            except Exception:
                logging.getLogger(__name__).exception(
                    "fit: crash checkpoint at step %d failed", last_done)
        if report is not None:
            report.event("crash", step=last_done, error=repr(e))
            with contextlib.suppress(Exception):
                _finalize_report()
        raise
    finally:
        if watchdog is not None:
            watchdog.stop()
        profile.close()  # the profile window ran past the last executed step
    if eval_fn is not None and num_steps > start_step and not preempted:
        _eval(num_steps - 1)
    if (mgr is not None and checkpoint_every and num_steps > start_step
            and not preempted):
        _save(num_steps - 1)
    if mgr is not None:
        mgr.commit_pending()
    _finalize_report()
    return params, history


def synthetic_data(cfg: ModelConfig, batch_size: int, seq_length: int,
                   seed: int = 0) -> Iterator[Tuple[jax.Array, jax.Array]]:
    """Random-token batches, the reference's data regime. Targets are the
    inputs shifted by one (next-token prediction), unlike the reference's
    independent random targets — random targets make loss a constant-entropy
    floor, which is useless for verifying that optimization works.

    Thin wrapper over :func:`.data.synthetic_batches` (the single
    implementation of the regime) with the model config supplying vocab."""
    from .data import synthetic_batches
    return synthetic_batches(cfg.vocab_size, batch_size, seq_length, seed=seed)
