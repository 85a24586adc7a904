"""Profiling: the host-span recorder, JAX's compile events filed by program,
and the names of the step's regions.

The reference's only instrumentation is ``time.time()`` around the timed loop
(SURVEY.md §5 tracing row; upstream's ``record_function`` blocks are never
collected). Here:

- :class:`annotate` is THE host span: a ``jax.profiler.TraceAnnotation`` (so
  with a profiler session open the span is on the device trace's clock, and
  an idle gap of the device can be named by it) that also keeps its
  ``perf_counter`` seconds in a table of this module, session or not:
  :func:`host_spans`, :func:`host_seconds`, :func:`reset_host_spans`.
  ``fit``'s loop spans (``input_wait``, ``dispatch``, ``wait_loss``, ``eval``,
  ``checkpoint_save``) and the start-up path's ``setup/<what>`` spans
  (:data:`SETUP_SPANS`) all go through it. :func:`annotated_steps` puts a
  step number around a loop's iterations; :func:`trace` opens a profiler
  session around a block (``fit(profile_dir=...)`` uses it).
- On import this module registers ONE duration listener and ONE event
  listener with ``jax.monitoring`` and files what JAX 0.9.0 reports about
  every program it is asked for — tracing, lowering, the backend (compiling,
  or the persistent cache handing the program back), hit or miss — by the
  program's name, request by request: :func:`programs`.
- :func:`setup_section` reads both into the ``setup`` section of a
  ``RunReport`` and :func:`format_setup` into the "start-up" block ``fit``
  prints; ``benchmark/metrics/setup.*.py`` read the same table.
- :data:`REGIONS` is the fixed vocabulary of ``jax.named_scope`` names the
  program sets where the work is written (``models/transformer.py``,
  ``utils/train.py``; the executors' ``pp/...`` beside them), and
  :func:`classify` reads an instruction's ``op_name`` — as the compiled
  step's text carries it in ``metadata={op_name="..."}`` — back into
  ``(phase, region)``. A device-trace event is named by its instruction, so
  instruction -> compiled text -> ``op_name`` -> :func:`classify` splits a
  trace of any step by forward / backward / recompute / optimizer and by
  attention / MLP / head (``benchmark/harness/scopes.py`` does the join;
  docs/observability.md has the reading guide).
"""

from __future__ import annotations

import sys
import time

# The package's ``__init__`` imports this module FIRST, so this stamp is the
# top of the package's import: taken before ``import jax`` below, which is
# inside the ``setup/import`` span whenever jax was not loaded yet.
# (A reload keeps the first import's stamp: that import happened once.)
_IMPORT_START = globals().get("_IMPORT_START") or time.perf_counter()
_JAX_WAS_LOADED = globals().get("_JAX_WAS_LOADED", "jax" in sys.modules)

import collections  # noqa: E402
import contextlib  # noqa: E402
import functools  # noqa: E402
import re  # noqa: E402
import threading  # noqa: E402
from typing import Any, Dict, Iterable, Iterator, List, Optional, Tuple  # noqa: E402

import jax  # noqa: E402
import jax.monitoring  # noqa: E402

# --------------------------------------------------------------------------
# The host-span table

#: Recent ``(start, seconds)`` pairs kept per span name: a week-long ``fit``
#: adds to four numbers and overwrites this ring, it grows nothing.
RING = 64
#: Distinct places a span name is recorded as having run inside; more are
#: lumped as ``"elsewhere"``.
INSIDE_KEPT = 8
#: Requests :func:`programs` keeps; beyond it the oldest go and are counted
#: (``recorder_cost()["programs_dropped"]``).
PROGRAMS_KEPT = 4096

#: The start-up path's spans, in the order a run meets them, with where each
#: is taken. What lies inside what is not fixed here: the table records it
#: per reading (``inside``), because it differs by entry point —
#: ``setup/schedule`` lies inside ``setup/build_step`` (the executor compiles
#: its table when the step is BUILT), ``setup/init_params`` holds its own
#: program's trace, lowering and backend seconds, and in ``fit``
#: ``setup/first_step`` holds the step program's three. Never add spans up
#: without reading ``inside``.
SETUP_SPANS = (
    ("setup/import", "the package's __init__.py, top to bottom"),
    ("setup/mesh", "parallel/mesh.py:make_mesh"),
    ("setup/native_build", "parallel/native.py: make -C csrc, first use"),
    ("setup/schedule", "parallel/schedules.py:compile_schedule and "
                       "parallel/native.py:compile_schedule_native"),
    ("setup/init_params", "utils/train.py:init_params"),
    ("setup/init_opt_state", "utils/train.py:init_opt_state"),
    ("setup/build_step", "utils/train.py:make_train_step"),
    ("setup/remat_keep", "models/nemotron_h.py:kept_names, while the step "
                         "is traced: what remat_layers keeps, in its notes"),
    ("setup/restore", "utils/train.py:fit, CheckpointManager.restore_latest"),
    ("setup/first_step", "utils/train.py:fit, the first step's call"),
)

_spans: Dict[str, Dict[str, Any]] = {}
# set once, by import_done(); a reload keeps it, as it keeps the stamp
_import_seconds: Optional[float] = globals().get("_import_seconds")
_closed: collections.deque = collections.deque(maxlen=256)
_local = threading.local()  # .stack: open span names; .cache, .retrieval_s
_cost = {"annotate_s": 0.0, "spans": 0, "listener_s": 0.0, "events": 0,
         "listener_errors": 0, "programs_dropped": 0}


def _add_inside(row: Dict[str, Any], where: str, seconds: float) -> None:
    inside = row["inside"]
    if where not in inside and len(inside) >= INSIDE_KEPT:
        where = "elsewhere"
    inside[where] = inside.get(where, 0.0) + seconds


def _keep_span(name: str, start: float, seconds: float,
               inside: Optional[str], notes: Dict[str, Any]) -> None:
    row = _spans.get(name)
    if row is None:
        row = _spans[name] = {
            "count": 0, "seconds": 0.0, "longest_s": -1.0,
            "longest_start": start, "inside": {}, "notes": {},
            "recent": collections.deque(maxlen=RING)}
    row["count"] += 1
    row["seconds"] += seconds
    if seconds > row["longest_s"]:
        row["longest_s"], row["longest_start"] = seconds, start
    if inside is not None:
        _add_inside(row, inside, seconds)
    if notes:
        row["notes"].update(notes)
    row["recent"].append((start, seconds))
    _closed.append([start, name, threading.get_ident(), seconds, inside])


class annotate(jax.profiler.TraceAnnotation):
    """A host span, kept twice: ``with annotate("wait_loss"): ...``.

    It IS a ``jax.profiler.TraceAnnotation``: while a profiler session is
    open the span lands on the host plane of the trace, on the clock the
    device's events are on (which is how ``trace_reduce.attribute_gaps``
    names an idle gap of the device). Session or not, its ``perf_counter``
    seconds go into this module's table: per name the count, the summed
    seconds, the longest reading and when it started, how many of its
    seconds ran inside which other span (or inside the tracing of which
    program: ``{"setup/build_step": 0.8}``, empty for a span that always
    ran on its own), and the last :data:`RING` ``(start, seconds)`` pairs.
    Keyword ``notes`` are kept with the name
    (``annotate("setup/mesh", backend_was_up=False)``; :meth:`note` adds
    what is learned inside the span).
    ``@annotate("setup/build_step")`` wraps a function in a fresh span per
    call.

    A span times the HOST's call and adds no ``block_until_ready``: JAX
    dispatches asynchronously, so device work a call started and did not
    wait for lands in whoever waits next (``setup/init_params`` returns when
    the init program is compiled and enqueued, not when the weights exist).
    The cost is three clock reads and one dict update; the third read feeds
    :func:`recorder_cost`.
    """

    def __init__(self, name: str, **notes: Any) -> None:
        super().__init__(name)
        self._name, self._notes = name, notes

    def __call__(self, fn):
        name, notes = self._name, self._notes

        @functools.wraps(fn)
        def spanned(*args, **kwargs):
            with annotate(name, **notes):
                return fn(*args, **kwargs)
        return spanned

    def __enter__(self) -> "annotate":
        super().__enter__()
        try:
            stack = _local.stack
        except AttributeError:
            stack = _local.stack = []
        stack.append(self._name)
        self._start = time.perf_counter()
        return self

    def note(self, **notes: Any) -> None:
        """More notes, learned while the span is open; kept at its end."""
        self._notes = {**self._notes, **notes}

    def __exit__(self, *exc) -> Optional[bool]:
        end = time.perf_counter()
        stack = getattr(_local, "stack", None)  # None: entered on another thread
        if stack:
            stack.pop()
        _keep_span(self._name, self._start, end - self._start,
                   stack[-1] if stack else None, self._notes)
        _cost["spans"] += 1
        _cost["annotate_s"] += time.perf_counter() - end
        return super().__exit__(*exc)


@contextlib.contextmanager
def trace(log_dir: str):
    """Profile a block: ``with trace('/tmp/prof'): step(...)`` then inspect
    in TensorBoard/XProf (``fit(profile_dir=...)`` holds its window open
    through this). The pipeline executors label their compute with
    ``pp/...`` named scopes (``pp/phase3``, ``pp/fwd``, ``pp/ring_bwd``,
    ...), so trace rows group by schedule structure — see
    docs/observability.md for the reading guide."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def import_done() -> None:
    """Close ``setup/import``: called by the package's ``__init__`` as its
    last statement. The span started at the top of this module (no profiler
    session can be open then, so it is kept in the table only); its notes
    say whether ``jax`` was loaded already — if not, its import is inside."""
    global _import_seconds
    if _import_seconds is None:
        _import_seconds = time.perf_counter() - _IMPORT_START
        _keep_import_span()


def _keep_import_span() -> None:
    _keep_span("setup/import", _IMPORT_START, _import_seconds, None,
               {"jax_was_loaded": _JAX_WAS_LOADED})


def host_spans() -> Dict[str, Dict[str, Any]]:
    """The table, copied: ``{name: {count, seconds, longest_s,
    longest_start, inside, notes, recent}}`` — ``recent`` a list of
    ``(start, seconds)``, starts on ``time.perf_counter``'s clock."""
    return {name: {**row, "notes": dict(row["notes"]),
                   "inside": dict(row["inside"]),
                   "recent": list(row["recent"])}
            for name, row in _spans.items()}


def host_seconds(name: str) -> Optional[float]:
    """Summed seconds of every span of this name; ``None`` if none ran."""
    row = _spans.get(name)
    return None if row is None else row["seconds"]


def reset_host_spans() -> None:
    """Forget every span, every filed program and the recorder's own cost
    (tests; a second run in one process). ``setup/import`` is kept: it
    happened once and cannot happen again."""
    _spans.clear()
    _closed.clear()
    if _import_seconds is not None:
        _keep_import_span()
    _programs.clear()
    _open.clear()
    for key in _cost:
        _cost[key] = 0 if isinstance(_cost[key], int) else 0.0


def recorder_cost() -> Dict[str, Any]:
    """What the recorder itself took: seconds inside ``annotate``'s
    bookkeeping (``annotate_s`` over ``spans``), seconds inside the two
    listeners (``listener_s`` over ``events``), listener errors swallowed,
    requests dropped from the bounded list."""
    return dict(_cost)


def backend_is_up() -> bool:
    """Whether this process has initialised a JAX backend yet (the first
    ``jax.devices()`` does, and on a TPU takes seconds)."""
    from jax._src import xla_bridge
    return xla_bridge.backends_are_initialized()


# --------------------------------------------------------------------------
# JAX's compile events, filed by program
#
# What JAX 0.9.0 fires, read off on this installation
# (tests/test_host_recorder.py holds it): a call of a jitted function that has no program yet fires
# ``jaxpr_trace_duration`` with ``fun_name="train_step"``, then
# ``jaxpr_to_mlir_module_duration`` and ``backend_compile_duration`` with
# ``fun_name="jit(train_step)"`` — the function's name against the XLA
# module's, so names are filed with the ``jit(...)`` taken off. The trace
# event also fires for every jitted function traced INSIDE another (jnp's
# own ``_where``, ``multiply``, ...; 1e-5 s when cached): those are no
# programs, they are folded into the enclosing request as ``inlined``.
# ``backend_compile_duration`` brackets ``compile_or_get_cached`` WHOLE: with
# the persistent cache on, a hit fires ``cache_hits``, then
# ``cache_retrieval_time_sec`` (no ``fun_name``), then the backend event
# holding the retrieval; a miss fires ``cache_misses`` when the entry is
# written, then the backend event holding the compile and the write. So
# "backend seconds" is the compile on a miss and the read on a hit, and the
# events without a name are matched to the backend event that follows them
# on the same thread. A second ``lower()`` of the same function with the same
# shapes fires one trace event of microseconds and nothing else (lowering and
# compile are cached in the process); a rebuilt function fires all three.

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_DURATIONS = frozenset({_TRACE, _LOWER, _BACKEND, _RETRIEVAL})
#: event -> how the request that follows got its program. ``uncached``: JAX
#: made a cache key, the program compiled and no entry was written (under
#: JAX's thresholds, or no directory set: the key is made all the same); no
#: event at all (``None``): no key, or a request that never reached the
#: backend.
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "uncached",
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}

_programs: collections.deque = collections.deque(maxlen=PROGRAMS_KEPT)
_open: Dict[Tuple[int, str], Dict[str, Any]] = {}
_MODULE_NAME = re.compile(r"^\w+\((.*)\)$")


def program_name(fun_name: str) -> str:
    """The name a request is filed under: ``jit(train_step)`` (the XLA
    module, on the lowering and backend events) -> ``train_step`` (the
    function, on the trace event)."""
    m = _MODULE_NAME.match(fun_name)
    return m.group(1) if m else fun_name


def _request(name: str, start: float, thread: int) -> Dict[str, Any]:
    stack = getattr(_local, "stack", None)
    req = {"name": name, "start": start, "thread": thread,
           "trace_s": None, "lower_s": None, "backend_s": None,
           "cache": None, "retrieval_s": None, "inlined": 0,
           "inside": stack[-1] if stack else None}
    if len(_programs) == PROGRAMS_KEPT:
        _cost["programs_dropped"] += 1
    _programs.append(req)
    return req


def _is_program(req: Dict[str, Any]) -> bool:
    return req["lower_s"] is not None or req["backend_s"] is not None


def _fold_since(start: float, me: int, where: str) -> int:
    """What this thread filed since ``start`` ran inside the tracing or the
    lowering (``where``) that just ended: jitted functions traced inline —
    jnp's own, and what a lowering rule traces (``lower_fun``: threefry) —
    are taken off the list and counted; whole programs (eager work in
    between) stay and say where they ran."""
    inlined, kept = 0, []
    while _programs and _programs[-1]["start"] >= start:
        req = _programs.pop()
        if req["thread"] != me:
            kept.append(req)
        elif _is_program(req):
            req["inside"] = where
            kept.append(req)
        else:
            inlined += 1 + req["inlined"]
            if _open.get((me, req["name"])) is req:
                del _open[(me, req["name"])]
    _programs.extend(reversed(kept))
    return inlined


def _spans_since(start: float, me: int, where: str) -> None:
    """Host spans this thread closed since ``start`` ran while ``where`` was
    traced: their seconds move from the span they were opened in to it (the
    outermost trace ends last, so it is the one that stays)."""
    for closed in reversed(_closed):
        at, span, thread, span_s, was_inside = closed
        if at < start:
            break
        if thread != me or span not in _spans:
            continue
        inside = _spans[span]["inside"]
        if was_inside in inside:
            inside[was_inside] -= span_s
            if inside[was_inside] < 1e-9:
                del inside[was_inside]
        closed[4] = where
        _add_inside(_spans[span], where, span_s)


def _file_trace(name: str, start: float, seconds: float, me: int) -> None:
    where = f"trace of {name}"
    inlined = _fold_since(start, me, where)
    req = _request(name, start, me)
    req["trace_s"], req["inlined"] = seconds, inlined
    _open[(me, name)] = req
    _spans_since(start, me, where)


def _file(event: str, seconds: float, fun_name: Optional[str],
          now: float) -> None:
    if event == _RETRIEVAL:
        _local.retrieval_s = seconds
        return
    me = threading.get_ident()
    start = now - seconds
    if event == _TRACE:  # the function's own name, nothing to take off
        _file_trace(fun_name or "?", start, seconds, me)
        return
    name = program_name(fun_name or "?")
    req = _open.get((me, name))
    if event == _LOWER:
        inlined = _fold_since(start, me, f"lowering of {name}")
        if req is None or _is_program(req):
            req = _open[(me, name)] = _request(name, start, me)
        req["lower_s"] = seconds
        req["inlined"] += inlined
        return
    if req is None or req["backend_s"] is not None:
        req = _request(name, start, me)
    _open.pop((me, name), None)
    req["backend_s"] = seconds
    req["cache"] = getattr(_local, "cache", None)
    req["retrieval_s"] = getattr(_local, "retrieval_s", None)
    _local.cache = _local.retrieval_s = None


def _on_duration(event: str, duration: float, **kwargs: Any) -> None:
    if event not in _DURATIONS:
        return
    now = time.perf_counter()
    try:  # a recorder that fails must not fail a compile
        _file(event, duration, kwargs.get("fun_name"), now)
    except Exception:
        _cost["listener_errors"] += 1
    _cost["events"] += 1
    _cost["listener_s"] += time.perf_counter() - now


def _on_event(event: str, **kwargs: Any) -> None:
    how = _CACHE_EVENTS.get(event)
    if how is not None:
        _local.cache = how
        _cost["events"] += 1


_on_duration._dtpp_host_recorder = True
_on_event._dtpp_host_recorder = True


def _register_listeners() -> None:
    """Once a process, however often this file is imported (a reload, a
    second copy under another module name): a listener of this recorder
    that is registered already stays the only one."""
    def mine(listeners):
        return any(getattr(f, "_dtpp_host_recorder", False)
                   for f in listeners)

    # the public module has no way to list what is registered
    from jax._src import monitoring
    if not mine(monitoring.get_event_duration_listeners()):
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
    if not mine(monitoring.get_event_listeners()):
        jax.monitoring.register_event_listener(_on_event)


_register_listeners()
if _import_seconds is not None:  # a reload emptied the table
    _keep_import_span()


def programs() -> List[Dict[str, Any]]:
    """Every request for a program this process filed, oldest first:
    ``{name, start, trace_s, lower_s, backend_s, cache, retrieval_s,
    inlined, inside}``. ``start`` is on ``time.perf_counter``'s clock; a
    stage JAX did not run for this request (the trace found in its cache,
    a program only traced) is ``None``; ``cache`` is ``"hit"``, ``"miss"``,
    ``"uncached"`` (compiled, nothing written) or ``None`` (not compiled);
    ``inlined`` counts the jitted functions traced inside this one's trace
    or lowering; ``inside`` names the host span, or ``"trace of
    <program>"``, the request ran in."""
    return sorted(({k: v for k, v in req.items() if k != "thread"}
                   for req in list(_programs)), key=lambda r: r["start"])


def program_seconds(req: Dict[str, Any]) -> float:
    """Trace + lowering + backend seconds of one request."""
    return sum(req[k] or 0.0 for k in ("trace_s", "lower_s", "backend_s"))


def split_programs(requests: List[Dict[str, Any]], step_name: str
                   ) -> Tuple[Optional[Dict[str, Any]],
                              List[Dict[str, Any]], List[Dict[str, Any]]]:
    """``(the step program's first request, its later requests, all other
    requests)``: a request is the step program's when its name holds
    ``step_name`` (as ``trace_reduce.step_window`` matches the XLA module).
    Later requests are rebuilds (the benchmark's ``train_scoped`` lowers the
    step again after its window) and are kept out of both other groups."""
    mine = [r for r in requests if step_name in r["name"]]
    others = [r for r in requests if step_name not in r["name"]]
    return (mine[0] if mine else None), mine[1:], others


def setup_section(step_name: str = "train_step",
                  dearest: int = 5) -> Dict[str, Any]:
    """The ``setup`` section of a ``RunReport`` (``telemetry.
    validate_report`` checks it): the ``setup/*`` spans so far, the step
    program's first request, the count and summed seconds of every other
    request (``count`` lowered or compiled, ``traced_only`` traced outside
    any jitted function's trace and never lowered: ``eval_shape`` and the
    like) with the ``dearest`` few by name, and the recorder's own cost.
    Seconds of different rows overlap wherever ``inside`` says so."""
    spans = {name: {"count": row["count"], "seconds": row["seconds"],
                    "longest_s": row["longest_s"],
                    "inside": dict(row["inside"]),
                    **({"notes": dict(row["notes"])} if row["notes"] else {})}
             for name, row in _spans.items() if name.startswith("setup/")}
    step, later, others = split_programs(programs(), step_name)
    keys = ("name", "trace_s", "lower_s", "backend_s", "cache",
            "retrieval_s", "inlined", "inside")
    others.sort(key=program_seconds, reverse=True)
    n_programs = sum(map(_is_program, others))
    return {
        "spans": spans,
        "step_program": (None if step is None else
                         {**{k: step[k] for k in keys},
                          "later_requests": len(later)}),
        "other_programs": {
            "count": n_programs,
            "traced_only": len(others) - n_programs,
            "seconds": sum(program_seconds(r) for r in others),
            "dearest": [{"name": r["name"], "seconds": program_seconds(r),
                         "cache": r["cache"], "inside": r["inside"]}
                        for r in others[:dearest]]},
        "recorder_cost": recorder_cost(),
    }


def format_setup(section: Dict[str, Any]) -> str:
    """The "start-up" block ``fit`` prints at its first log point: one line
    a row of :func:`setup_section`."""
    def s(x):
        return "   -  " if x is None else f"{x:6.2f}"

    lines = ["start-up (host seconds; a row inside another is part of it):"]
    order = [name for name, _ in SETUP_SPANS if name in section["spans"]]
    order += sorted(set(section["spans"]) - set(order))
    for name in order:
        row = section["spans"][name]
        notes = ", ".join(f"{k}={v}" for k, v in row.get("notes", {}).items())
        lines.append(
            f"  {name:<22}{s(row['seconds'])} s"
            + (f" x{row['count']}" if row["count"] > 1 else "")
            + "".join(f"  {s(v).strip()} s inside {k}"
                      for k, v in row["inside"].items())
            + (f"  ({notes})" if notes else ""))
    step = section["step_program"]
    if step is not None:
        lines.append(
            f"  step program {step['name']}: trace{s(step['trace_s'])} s "
            f"({step['inlined']} functions inlined), lowering"
            f"{s(step['lower_s'])} s, backend{s(step['backend_s'])} s "
            f"(cache: {step['cache'] or 'off'})"
            + (f"  inside {step['inside']}" if step["inside"] else ""))
    other = section["other_programs"]
    lines.append(f"  {other['count']} other programs and "
                 f"{other['traced_only']} functions only traced: "
                 f"{other['seconds']:.2f} s" + ("; dearest: " + ", ".join(
                     f"{r['name']} {r['seconds']:.2f} s"
                     + (f" in {r['inside']}" if r["inside"] else "")
                     for r in other["dearest"]) if other["dearest"] else ""))
    return "\n".join(lines)


def annotated_steps(steps: Iterable[int],
                    name: str = "train") -> Iterator[int]:
    """Iterate ``steps`` with the body of each iteration under
    ``jax.profiler.StepTraceAnnotation(name, step_num=i)``, so a trace's
    host plane (and XProf's step view) knows which step issued what. The
    annotation closes when the loop asks for the next step, breaks, or drops
    the iterator. No-op cost when no profiler session is active."""
    for i in steps:
        with jax.profiler.StepTraceAnnotation(name, step_num=i):
            yield i


# --------------------------------------------------------------------------
# The step's regions, and how an op_name is read back into one

#: ``jax.named_scope`` names set where the work is written:
#: ``models/transformer.py`` (the ``model/`` ones, at the function every
#: path shares: ``embed_apply``, ``body_apply`` — the layer stack's own
#: weight slices, stacked residuals and residual adds —, ``layer_apply``'s
#: attention half, ``mlp_block``, ``head_apply`` and the loss) and
#: ``utils/train.py:make_train_step`` (the optax update). The executors'
#: ``pp/...`` scopes (``parallel/pipeline.py``) stay as they are; they are
#: regions too, wherever no name of this list is further in.
REGIONS = ("model/embed", "model/layers", "model/attn", "model/mlp",
           "model/head_loss", "train/optimizer")
#: The patterned stack's own six, beside ``model/attn`` for its attention
#: layers (an LFM2-style layer's q/k norms and rotation inside it) and
#: ``model/mlp`` for its dense MLP, disjoint by the
#: innermost-wins rule: ``model/ssm``
#: (``models/nemotron_h.py``: a Mamba-2 mixer but its scan — norm, both
#: projections, convolution, gate, group norm), ``model/ssm_scan``
#: (``ops/mamba2.py:ssd_chunked``), ``model/moe`` (norm, router, top-k,
#: the gate, shared expert), ``model/moe_experts``
#: (``ops/experts.py:held_experts``, the held experts' gated products) and
#: ``model/mla_latent`` (``ops/attention.py:mla_project``, inside a latent-
#: attention layer's ``model/attn``: both down-projections, the latent
#: norms, both up-projections, RoPE, building ``k`` — everything between the
#: normed input and the attention core's operands) and ``model/shortconv``
#: (a gated short-convolution layer whole: norm, both projections, both
#: gates, the convolution). Kept apart from :data:`REGIONS`, which every dense step names
#: whole (``benchmark/tests/test_bench_scopes.py`` holds it to that).
HYBRID_REGIONS = ("model/ssm", "model/ssm_scan", "model/moe",
                  "model/moe_experts", "model/mla_latent", "model/shortconv")
UNSCOPED = "unscoped"

#: In this order, the first mark an op_name holds gives its phase. JAX writes
#: the last two itself (``jax.checkpoint``'s second run of a function, and
#: the transposed half of a ``jvp``); anything else under a region is
#: ``forward``.
PHASE_MARKS = (("train/optimizer", "optimizer"),
               ("rematted_computation", "recompute"),
               ("transpose(", "backward"))

# longest first: ``model/ssm_scan`` is not ``model/ssm`` with a tail
_REGION = re.compile("|".join(
    re.escape(r) for r in sorted(REGIONS + HYBRID_REGIONS, key=len,
                                 reverse=True)))
_PP = re.compile(r"pp/[A-Za-z_]+")  # pp/tick003 -> pp/tick, pp/phase2 -> pp/phase
# The tick executors' backward units: each re-runs its stage's forward by
# hand (``jax.vjp`` inside the tick), which JAX marks ``jvp(``, not
# ``rematted_computation``. Read off the D=2 executor's compiled text: under
# these scopes ``jvp(pp/stage_body)/..`` and ``jvp(pp/embed)/..`` are the
# second forward, ``transpose(jvp(..))`` the backward. The head and its loss
# are the exception: their ONLY forward run is inside the last stage's
# backward unit (``stage_objective``), so it stays ``forward``.
_BACKWARD_UNIT = re.compile(r"pp/(bwd_dgrad|bwd|wgrad)(?![A-Za-z_])")


def _region(part: str):
    found = _REGION.findall(part)
    if found:
        return found[-1]  # the innermost
    found = _PP.findall(part)
    return found[-1] if found else None


def _phase(part: str, region) -> str:
    for mark, phase in PHASE_MARKS:
        if mark in part:
            return phase
    if region != "model/head_loss" and _BACKWARD_UNIT.search(part):
        return "recompute"
    if region is not None or "jvp(" in part:
        return "forward"
    return "other"


def classify(op_name: str) -> Tuple[str, str]:
    """``(phase, region)`` of one instruction, from the ``op_name`` its
    compiled HLO carries (``jit(train_step)/transpose(jvp())/while/body/
    closed_call/checkpoint/model/mlp/dot_general``).

    ``region`` is the innermost name of :data:`REGIONS` or
    :data:`HYBRID_REGIONS` in it, else the
    innermost ``pp/...`` scope (tick and phase numbers dropped), else
    ``"unscoped"``. ``phase`` is ``optimizer``, ``recompute``, ``backward``
    or ``forward`` by :data:`PHASE_MARKS` and the rule for the executors'
    backward units above; an op outside every region still gets the phase
    JAX's own marks give it (``jvp(`` alone is forward), and ``other`` only
    where the name holds no mark at all. A fusion that merged several
    sources carries ``a;b``: the first part that has a region is read."""
    parts = op_name.split(";")
    for part in parts:
        region = _region(part)
        if region is not None:
            return _phase(part, region), region
    return _phase(parts[0], None), UNSCOPED
