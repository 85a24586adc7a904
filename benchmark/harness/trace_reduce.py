"""From a ``jax.profiler`` trace to numbers, the same way for every PR.

The profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData`` reads it
with nothing but JAX. :func:`load` turns it into plain data — a dict of
planes, each a dict of lines, each a list of :class:`Event` — and every
reduction below works on that, so the tests feed it hand-made intervals.

What the reductions hold to (``PERF.md`` section 3 has the names found in a
real trace):

* a device plane is ``/device:TPU:<n>``; its ``XLA Ops`` line has one event
  per executed HLO instruction, named by the instruction's whole HLO text
  (``%fusion.3 = bf16[..] fusion(.. %copy-done.6), ..``), and its ``XLA
  Modules`` line one per executed program (``jit_train_step(<id>)``). As
  ``ProfileData`` shows them the events carry no ``hlo_category`` or
  ``tf_op``, so containers and collectives are known by instruction name;
* busy time is the UNION of op intervals, never their sum: ops on a plane
  can overlap (async collectives run beside compute), and a sum would count
  that time twice;
* ``while`` / ``conditional`` / ``call`` events contain their children, who
  have events of their own, so containers are skipped (the list is
  ``scripts/profile_breakdown.py``'s);
* collectives are kept apart from compute: a pipeline stage with nothing to
  do waits INSIDE its ``collective-permute``, so that time is idle time of
  the stage although an op is running;
* every device plane is reduced by itself — the planes of a pipeline are
  not alike, and the difference is the point.
"""

from __future__ import annotations

import collections
import glob
import os
import re
from typing import Iterable, NamedTuple

from benchmark.harness.kernels import instruction

CONTAINER_CATEGORIES = {"while", "conditional", "call"}
CONTAINER_NAME = re.compile(r"^%?(while|conditional|call)([.\d]*)( |=|$)")
COLLECTIVE = re.compile(
    r"(collective-permute|all-reduce|all-gather|all-to-all|reduce-scatter"
    r"|collective-broadcast)")
PERMUTE = re.compile(r"collective-permute")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
OP_STATS = ("hlo_category", "tf_op")  # the stats of an op that are read


class Event(NamedTuple):
    name: str
    start_ns: float
    end_ns: float
    stats: dict

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


Trace = dict  # {plane name: {line name: [Event, ...]}}


def newest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    """The planes this benchmark reads, as plain data: every device plane
    with its events' stats, and (for the gaps) the host plane's lines."""
    from jax.profiler import ProfileData
    trace: Trace = {}
    for plane in ProfileData.from_file(path).planes:
        is_device = DEVICE_PLANE.match(plane.name)
        if not is_device and plane.name != HOST_PLANE:
            continue
        lines = trace.setdefault(plane.name, {})
        for line in plane.lines:
            if is_device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                stats = ({k: v for k, v in ev.stats if k in OP_STATS}
                         if line.name == OPS_LINE else {})
                events.append(Event(ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns, stats))
    return trace


# --------------------------------------------------------------------------
# intervals


def merge(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, disjoint intervals covering the same points."""
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` inside [lo, hi)."""
    return sum(b - a for a, b in merge(clip(intervals, lo, hi)))


def gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    """What [lo, hi) has outside the disjoint, sorted ``merged``."""
    out, at = [], lo
    for a, b in clip(merged, lo, hi):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


# --------------------------------------------------------------------------
# events


def device_planes(trace: Trace) -> list[str]:
    """Device plane names, by ordinal."""
    found = [(int(m.group(1)), name) for name in trace
             if (m := DEVICE_PLANE.match(name))]
    return [name for _, name in sorted(found)]


def is_container(ev: Event) -> bool:
    cat = ev.stats.get("hlo_category")
    if cat is not None:
        return str(cat) in CONTAINER_CATEGORIES
    return bool(CONTAINER_NAME.match(ev.name))


def is_collective(ev: Event) -> bool:
    """By the instruction's own name: an event's text is the whole HLO
    line, operands included, so a fusion that CONSUMES a permute's result
    carries ``%collective-permute-done.3`` in its text too."""
    return bool(COLLECTIVE.search(instruction(ev.name)))


def is_permute(ev: Event) -> bool:
    return bool(PERMUTE.search(instruction(ev.name)))


def ops(trace: Trace, plane: str) -> list[Event]:
    """The plane's executed instructions, containers left out."""
    return [ev for ev in trace[plane].get(OPS_LINE, ())
            if not is_container(ev)]


def op_label(ev: Event, kernel_of: dict | None = None) -> str:
    """A name that survives renumbering. A Pallas call is named by its
    kernel function (``kernel_of``: instruction -> function, from
    ``kernels.pallas_calls``); any other op by its instruction without the
    number (``fusion.123`` -> ``fusion``), under the program's ``pp/...``
    named scope where the event carries one."""
    name = instruction(ev.name)
    if kernel_of and name in kernel_of:
        return kernel_of[name]
    m = re.search(r"pp/\w+", str(ev.stats.get("tf_op", "")))
    base = re.sub(r"[.\d]+$", "", name)
    return f"{m.group(0)}:{base}" if m else base


def step_window(trace: Trace, plane: str, program: str,
                n_steps: int) -> tuple[float, float, int]:
    """(lo, hi, steps): from the start of one execution of ``program`` on
    this plane to the start of the execution ``steps`` later — whole
    periods, each with the gap that follows it. The LAST ``n_steps + 1``
    executions in the trace are used (the first one after the profiler
    starts may be cut). Without a modules line the window is the span of
    the ops line and ``steps`` is what the caller said."""
    runs = sorted(ev.start_ns for ev in trace[plane].get(MODULES_LINE, ())
                  if program in ev.name)
    if len(runs) >= 2:
        runs = runs[-(n_steps + 1):]
        return runs[0], runs[-1], len(runs) - 1
    events = trace[plane].get(OPS_LINE, ())
    if not events:
        raise ValueError(f"plane {plane} has no {OPS_LINE!r} events")
    return (min(ev.start_ns for ev in events),
            max(ev.end_ns for ev in events), n_steps)


def reduce_plane(trace: Trace, plane: str, lo: float, hi: float,
                 kernel_of: dict | None = None) -> dict:
    """One device plane inside [lo, hi): seconds busy (union of all ops),
    computing (union of the ops that are no collective), in permutes and in
    collectives of any kind; per label — and per instruction, for the
    Pallas calls of ``kernel_of`` — the summed seconds and calls."""
    spans = {"busy": [], "compute": [], "permute": [], "collective": []}
    by_label: dict = collections.defaultdict(lambda: [0.0, 0])
    by_call: dict = collections.defaultdict(lambda: [0.0, 0])
    for ev in ops(trace, plane):
        a, b = max(ev.start_ns, lo), min(ev.end_ns, hi)
        if b <= a:
            continue
        spans["busy"].append((a, b))
        if is_collective(ev):
            spans["collective"].append((a, b))
            if is_permute(ev):
                spans["permute"].append((a, b))
        else:
            spans["compute"].append((a, b))
        entries = [by_label[op_label(ev, kernel_of)]]
        if kernel_of and (name := instruction(ev.name)) in kernel_of:
            entries.append(by_call[name])
        for entry in entries:
            entry[0] += (b - a) * 1e-9
            entry[1] += 1
    out = {k + "_s": union_ns(v, lo, hi) * 1e-9 for k, v in spans.items()}
    out["window_s"] = (hi - lo) * 1e-9
    out["by_label"] = {k: tuple(v) for k, v in by_label.items()}
    out["by_call"] = {k: tuple(v) for k, v in by_call.items()}
    out["busy_intervals"] = merge(spans["busy"])
    return out


def host_spans(trace: Trace, names: Iterable[str]) -> list[Event]:
    names = set(names)
    return [ev for line in trace.get(HOST_PLANE, {}).values()
            for ev in line if ev.name in names]


def attribute_gaps(busy_merged, lo: float, hi: float, spans: list[Event],
                   top: int = 10) -> list[list]:
    """The ``top`` longest stretches of [lo, hi) in which no op ran, each
    named by the host span that covers most of it (``none`` where no span
    does): ``[[name, seconds], ...]``, longest first."""
    out = []
    for a, b in sorted(gaps(busy_merged, lo, hi),
                       key=lambda g: g[0] - g[1])[:top]:
        cover: dict = collections.defaultdict(float)
        for ev in spans:
            both = min(b, ev.end_ns) - max(a, ev.start_ns)
            if both > 0:
                cover[ev.name] += both
        name = max(cover, key=cover.get) if cover else "none"
        out.append([name, (b - a) * 1e-9])
    return out


def reduce(trace: Trace, program: str, n_steps: int,
           span_names: Iterable[str] = (),
           kernel_of: dict | None = None) -> dict:
    """Everything the metric readers and the result line take from a trace.

    ``planes`` has one :func:`reduce_plane` dict per device, by ordinal,
    each over its own window of whole steps. ``busy_s`` and ``window_s`` are
    their means (what the result line's ``device`` carries). ``device_ops``
    and ``idle_gaps`` are the ``breakdown``: per step and per device, the
    labels that took most time; and the longest gaps of device 0."""
    names = device_planes(trace)
    if not names:
        raise ValueError(f"no device plane in the trace; planes: "
                         f"{sorted(trace)}")
    planes, totals = [], collections.Counter()
    for name in names:
        lo, hi, steps = step_window(trace, name, program, n_steps)
        p = reduce_plane(trace, name, lo, hi, kernel_of)
        p.update(name=name, lo_ns=lo, hi_ns=hi, steps=steps)
        planes.append(p)
        for label, (seconds, _) in p["by_label"].items():
            totals[label] += seconds
    first = planes[0]
    n = len(planes)
    return {
        "planes": planes,
        "steps": first["steps"],
        "busy_s": sum(p["busy_s"] for p in planes) / n,
        "window_s": sum(p["window_s"] for p in planes) / n,
        "device_ops": [[label, seconds / n] for label, seconds
                       in totals.most_common(10)],
        "idle_gaps": attribute_gaps(
            first["busy_intervals"], first["lo_ns"], first["hi_ns"],
            host_spans(trace, span_names)),
    }


def label_seconds(reduced: dict, pattern: str,
                  table: str = "by_label") -> tuple[float, int]:
    """Summed device seconds and calls, over all planes, of the labels (or,
    with ``table="by_call"``, the Pallas instructions) matching ``pattern``."""
    rx = re.compile(pattern)
    seconds, calls = 0.0, 0
    for p in reduced["planes"]:
        for label, (s, c) in p[table].items():
            if rx.search(label):
                seconds += s
                calls += c
    return seconds, calls
