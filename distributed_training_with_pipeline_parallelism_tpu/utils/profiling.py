"""Profiling and pipeline-bubble measurement.

The reference's only instrumentation is ``time.time()`` around the timed loop
(SURVEY.md §5 tracing row; upstream's ``record_function`` blocks are never
collected). Here:

- :func:`trace` wraps ``jax.profiler.trace`` — traces open in
  XProf/TensorBoard with per-op device timelines (the honest way to see
  bubbles on real hardware).
- :func:`measure_bubble` derives an end-to-end *measured* bubble fraction
  from wall-clocks, no profiler needed: a perfectly pipelined D-stage run
  would take ``t_single / D`` per step (same total FLOPs, spread over D
  chips); the measured bubble is the shortfall from that ideal,
  ``1 - t_single / (D * t_pipe)``. Comparable to the analytic
  ``(D-1)/(M+D-1)`` and the tick-simulated fraction
  (:func:`..parallel.schedules.simulated_bubble`) — the BASELINE.json
  north-star asks for measured-vs-analytic agreement.

Note the measured number also absorbs communication and remat overhead, so
it upper-bounds the pure schedule bubble; the gap between measured and
simulated (w_b=3) is the transport+overhead cost.

Caveat for simulated (CPU) meshes: the measurement assumes the D mesh
devices actually run in parallel. On a host with fewer cores than devices
the "parallel" ticks serialize and ``bubble_measured`` degenerates toward
``1 - 1/D`` regardless of schedule (docs/performance.md §bubbles) — use
the tick simulation for schedule comparisons there, and reserve this
function for real multi-chip slices.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, Optional

import jax


@contextlib.contextmanager
def trace(log_dir: str):
    """Profile a block: ``with trace('/tmp/prof'): step(...)`` then inspect
    in TensorBoard/XProf. The pipeline executors label their compute with
    ``pp/...`` named scopes (``pp/phase3``, ``pp/fwd``, ``pp/ring_bwd``,
    ...), so trace rows group by schedule structure — see
    docs/observability.md for the reading guide."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@contextlib.contextmanager
def annotate(name: str):
    """Label HOST-side spans of a traced block in XProf:
    ``with annotate("step3"): step(...)``. Complements the executors'
    ``jax.named_scope`` labels, which name DEVICE-side ops at trace time:
    ``TraceAnnotation`` marks wall-clock regions of the host timeline
    (e.g. which bench rung or train step issued the work). No-op cost when
    no profiler session is active."""
    with jax.profiler.TraceAnnotation(name):
        yield


def _time_fn(fn, *args, iters: int = 5, warmup: int = 2) -> float:
    from .metrics import force_completion
    out = None
    for _ in range(warmup):
        out = fn(*args)
    force_completion(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    force_completion(out)
    return (time.perf_counter() - t0) / iters


def measure_bubble(cfg, mesh, sched, batch_size: int = 32,
                   seq_length: int = 128, iters: int = 5,
                   seed: int = 0) -> Dict[str, float]:
    """Measured vs analytic vs simulated bubble for one config.

    Runs the pipeline step on the mesh and an equivalent single-device step
    (same model, same microbatch gradient accumulation via a GPipe program on
    a 1-device mesh, so remat costs cancel out of the comparison), then
    reports ``bubble_measured = 1 - t_single / (D * t_pipe)``.
    """
    from ..models.transformer import transformer_init
    from ..parallel.mesh import make_mesh
    from ..parallel.pipeline import make_pipeline_step
    from ..parallel.schedules import (analytic_bubble_fraction,
                                      compile_schedule, simulated_bubble)
    from ..utils.config import ScheduleConfig

    D = mesh.shape["pipe"]
    params = transformer_init(jax.random.key(seed), cfg)
    kx, ky = jax.random.split(jax.random.key(seed + 1))
    tokens = jax.random.randint(kx, (batch_size, seq_length), 0, cfg.vocab_size)
    targets = jax.random.randint(ky, (batch_size, seq_length), 0, cfg.vocab_size)

    pipe_step = make_pipeline_step(cfg, mesh, sched)
    t_pipe = _time_fn(pipe_step, params, tokens, targets, iters=iters)

    single_mesh = make_mesh(n_pipe=1, devices=list(mesh.devices.flat)[:1])
    single_sched = ScheduleConfig(name="GPipe",
                                  n_microbatches=sched.n_microbatches)
    # force the tick executor AND the rematerializing backward so the
    # comparator pays the same per-unit costs as the D-device pipeline run
    # (the degenerate fast path skips remat entirely, and the D=1 default
    # is the unrolled stored program — either would skew the ratio)
    single_step = make_pipeline_step(cfg, single_mesh, single_sched,
                                     force_tick_executor=True,
                                     remat_backward=True)
    t_single = _time_fn(single_step, params, tokens, targets, iters=iters)

    cs = compile_schedule(sched.name, D, sched.n_virtual, sched.n_microbatches)
    return {
        "t_pipeline": t_pipe,
        "t_single_device": t_single,
        "bubble_measured": 1.0 - t_single / (D * t_pipe),
        "bubble_analytic": analytic_bubble_fraction(
            sched.name, D, sched.n_virtual, sched.n_microbatches, cs=cs),
        "bubble_simulated": simulated_bubble(cs, w_f=1.0, w_b=3.0)[
            "bubble_fraction"],
    }
