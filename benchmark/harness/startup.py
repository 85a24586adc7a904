"""What the program's host recorder kept about start-up, for the
``setup.*_s`` readers: ``utils/profiling.py``'s table of host spans
(``setup/<what>``) and its list of requests for programs, filed from JAX's
compile events by function name.

The readers run in the runner's process after the run, so the recorder holds
the whole run. A program without a recorder (a checkout from before it)
gives ``None`` everywhere and the readers return ``None``.

The step program is the one whose name holds :data:`PROGRAM`, matched as
``trace_reduce.step_window`` matches the XLA module. JAX 0.9.0 spells the
trace event ``train_step`` and the lowering and backend events
``jit(train_step)``; the recorder files all three under ``train_step``. Its
FIRST request is the one the run waits for before its window; later ones
(``train_scoped`` lowers the step again after the window) are left out of
every sum here and logged on their own.
"""

from __future__ import annotations

PROGRAM = "train_step"
STAGES = ("trace_s", "lower_s", "backend_s")


def recorder():
    """``utils.profiling`` where it records, else ``None``."""
    try:
        from distributed_training_with_pipeline_parallelism_tpu.utils import (
            profiling)
    except ImportError:
        return None
    if not all(hasattr(profiling, name) for name in
               ("host_seconds", "host_spans", "programs")):
        return None
    return profiling


def span_seconds(*names: str):
    """Summed seconds of the named host spans; ``None`` without a recorder
    or where the table lacks one of them."""
    rec = recorder()
    if rec is None:
        return None
    seconds = [rec.host_seconds(name) for name in names]
    return None if None in seconds else float(sum(seconds))


def seconds_of(request: dict, stages=STAGES) -> float:
    return float(sum(request[k] or 0.0 for k in stages))


def split(requests: list) -> tuple:
    """(the step program's first request or None, its later requests, every
    other request), each in the order filed."""
    mine = [r for r in requests if PROGRAM in r["name"]]
    others = [r for r in requests if PROGRAM not in r["name"]]
    return (mine[0] if mine else None), mine[1:], others


def requests():
    """The recorder's list, oldest first; ``None`` without a recorder."""
    rec = recorder()
    return None if rec is None else rec.programs()


def step_seconds(stages) -> float | None:
    """The step program's first request: the sum of ``stages`` it has;
    ``None`` without a recorder, without such a request, or where the
    request has none of them."""
    filed = requests()
    if filed is None:
        return None
    step, _, _ = split(filed)
    if step is None or all(step[k] is None for k in stages):
        return None
    return seconds_of(step, stages)


def how(request: dict) -> str:
    if request["backend_s"] is None:
        return "lowered only" if request["lower_s"] is not None else "traced only"
    return {"hit": "read from the cache", "miss": "compiled, written",
            "uncached": "compiled, not written",
            None: "compiled, no cache key"}[request["cache"]]


def line(request: dict, t0: float) -> str:
    def s(x):
        return "-" if x is None else f"{x:.3f}"
    return (f"{seconds_of(request):9.3f} s  {request['name']}  at "
            f"+{request['start'] - t0:.2f} s  {how(request)}  trace "
            f"{s(request['trace_s'])}, lowering {s(request['lower_s'])}, "
            f"backend {s(request['backend_s'])}"
            + (f" (read {s(request['retrieval_s'])})"
               if request["retrieval_s"] is not None else "")
            + (f"  {request['inlined']} inlined" if request["inlined"] else "")
            + (f"  inside {request['inside']}" if request["inside"] else ""))


def origin() -> float:
    """The clock's zero for the log: where the package's import started
    (``setup/import``), a moment after the process's own start."""
    spans = recorder().host_spans()
    return spans["setup/import"]["longest_start"] if "setup/import" in spans \
        else 0.0


def log_spans(log) -> None:
    """Every host span the recorder kept, in the order first met."""
    rec = recorder()
    log("host spans kept by the program's recorder (seconds; a span inside "
        "another is part of it):")
    for name, row in rec.host_spans().items():
        inside = "".join(f", {v:.3f} inside {k}"
                         for k, v in row["inside"].items())
        notes = "".join(f", {k}={v}" for k, v in row["notes"].items())
        log(f"  {name}: {row['seconds']:.3f} over {row['count']}, longest "
            f"{row['longest_s']:.3f}{inside}{notes}")
    cost = rec.recorder_cost()
    log(f"  the recorder itself: {cost['annotate_s'] * 1e3:.3f} ms in "
        f"{cost['spans']} spans, {cost['listener_s'] * 1e3:.3f} ms in "
        f"{cost['events']} events, {cost['listener_errors']} listener "
        f"errors, {cost['programs_dropped']} requests dropped")


def log_programs(log) -> None:
    """The table of programs by name: when each was requested, how it was
    obtained, its three durations, dearest first; then the step program's
    requests."""
    step, later, others = split(requests())
    t0 = origin()
    whole = [r for r in others if r["lower_s"] is not None
             or r["backend_s"] is not None]
    traced = [r for r in others if r not in whole]
    log(f"programs besides the step program: {len(whole)} lowered or "
        f"compiled, {sum(map(seconds_of, whole)):.3f} s; {len(traced)} "
        f"functions only traced, {sum(map(seconds_of, traced)):.3f} s "
        "(clock: seconds since the package's import began)")
    for r in sorted(whole, key=seconds_of, reverse=True):
        log("  " + line(r, t0))
    if later:
        since = later[0]["start"]
        after = [r for r in others if r["start"] >= since]
        log(f"  of these, {len(after)} requests and "
            f"{sum(map(seconds_of, after)):.3f} s came after the step "
            "program was asked for again (after the window)")
    if step is not None:
        log("the step program, first request: " + line(step, t0))
    for r in later:
        log("the step program, asked for again: " + line(r, t0))
