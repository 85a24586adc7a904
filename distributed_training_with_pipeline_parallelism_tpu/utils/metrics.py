"""Timed training iterations + the reference's metrics dict.

Parity with ``run_train_iterations`` (SURVEY.md C4,
``LLMsDistributedTrainingHelper.py:98-143``): 2 untimed warmup iterations,
``num_iterations`` timed schedule steps (forward + backward + inter-stage
transfer, **no optimizer** — the reference never creates one, SURVEY.md §3.3
note), throughput = batch * seq * iters / elapsed, and the same result dict
``{"elapsed_time", "throughput", "tokens_processed"}``.

In SPMD there is no rank-role dispatch (the reference feeds x on rank 0 and
target=y on the last rank): every device runs the same program. Honest
wall-clock (the reference gets it from process joins) comes from closing
every timed window with :func:`force_completion`.
"""

from __future__ import annotations

import time
from typing import Callable, Dict

import jax


def force_completion(out) -> None:
    """The one barrier that closes a timed window: wait until every array
    in ``out`` has been computed. JAX dispatches asynchronously — a step
    returns in a fraction of a millisecond — and a device runs its programs
    in order, so waiting on the last step's outputs waits for all the work
    enqueued before them. ``jax.block_until_ready`` does wait on this
    installation's TPU runtime: ``chip_smoke.py`` times it against a host
    fetch of the same result on every run (PR 24, one v5e: 143.85 ms vs
    144.17 ms for a 144 ms dispatch that returned in 0.35 ms) and fails if
    it ever returns early."""
    jax.block_until_ready(out)


def run_train_iterations(step: Callable, params, tokens, targets,
                         num_iterations: int = 10,
                         warmup_iterations: int = 2,
                         report=None) -> Dict[str, float]:
    """Time ``num_iterations`` pipeline steps after untimed warmup.

    ``report`` (opt-in :class:`.telemetry.RunReport`) records the warmup
    (compile-inclusive) and timed-loop wall clocks as timers plus the
    returned metrics as gauges."""
    total_toks = tokens.shape[0] * tokens.shape[1] * num_iterations

    warm0 = time.perf_counter()
    out = None
    for _ in range(warmup_iterations):
        out = step(params, tokens, targets)
    if out is not None:
        force_completion(out)
    if report is not None:
        report.timers["warmup_s"] = time.perf_counter() - warm0

    start = time.perf_counter()
    for _ in range(num_iterations):
        out = step(params, tokens, targets)
    force_completion(out)
    elapsed = time.perf_counter() - start

    metrics = {
        "elapsed_time": elapsed,
        "throughput": total_toks / elapsed,
        "tokens_processed": total_toks,
    }
    if report is not None:
        report.timers["timed_loop_s"] = elapsed
        report.count("timed_iterations", num_iterations)
        for k, v in metrics.items():
            report.gauge(k, v)
    return metrics
