"""Structured run reports, serving summaries and Perfetto tracks.

The device's time is not measured here. It is read from the profiler's
trace by the names the program gives its regions (``utils/profiling.py``
scopes and the executors' ``pp/...`` scopes, reduced by
``benchmark/harness/trace_reduce.py``); step time comes from the host clock
around a loop closed with ``utils.metrics.force_completion``. This module
keeps what a run writes down about itself:

- :class:`RunReport` — a structured run recorder (counters, timers,
  gauges, JSONL event stream + a single JSON manifest carrying config,
  mesh shape, schedule, compile time and jax/jaxlib versions) with a
  dependency-free :func:`validate_report`, so sweeps, ``fit`` and the
  serving scripts all emit the same schema instead of ad-hoc dicts.
- :func:`serving_summary` — per-request latency (in ticks) and throughput
  of one serving run.
- :func:`write_perfetto_trace` and the three ``perfetto_*_events``
  builders — serving-request slices, the tick-clock serving-load process
  and training-dynamics counter tracks as Chrome-trace JSON, loadable in
  ui.perfetto.dev or chrome://tracing.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

SCHEMA_VERSION = 1


# ---------------------------------------------------------------------------
# Perfetto tracks
# ---------------------------------------------------------------------------


def perfetto_request_events(serving_events: List[Dict[str, Any]],
                            pid: int = 1) -> List[Dict[str, Any]]:
    """Per-request async slices from ``serve_admit``/``serve_finish``
    RunReport event rows: one ``"b"``→``"e"`` pair per request id on a
    "requests" process track, laid out on the events' wall clock
    (normalized to the first admit). The slice args carry the on-device
    tick stamps — ``admit_tick``, prompt length / budget from the admit
    row, ``finish_tick``/``n_tokens``/``ttft_ticks`` from the finish row
    — so a TTFT/TPOT outlier in the UI names the exact ticks to inspect
    on the pipeline timeline. Slices land on a per-slot tid, so slot
    reuse reads as a row of back-to-back requests."""
    admits = {}
    finishes = {}
    for row in serving_events or []:
        if row.get("kind") == "serve_admit" and "rid" in row:
            admits[row["rid"]] = row
        elif row.get("kind") == "serve_finish" and "rid" in row:
            finishes[row["rid"]] = row
    if not admits:
        return []
    us = 1e6
    origin = min(r["t"] for r in admits.values())
    out: List[Dict[str, Any]] = [{
        "ph": "M", "name": "process_name", "pid": pid, "tid": 0, "ts": 0.0,
        "args": {"name": "serving requests"}}]
    slots = sorted({int(r.get("slot", 0)) for r in admits.values()})
    for slot in slots:
        out.append({"ph": "M", "name": "thread_name", "pid": pid,
                    "tid": slot, "ts": 0.0,
                    "args": {"name": f"slot {slot}"}})
    for rid, adm in sorted(admits.items(), key=lambda kv: kv[1]["t"]):
        fin = finishes.get(rid)
        slot = int(adm.get("slot", 0))
        ts = (adm["t"] - origin) * us
        args = {"rid": rid, "slot": slot,
                "admit_tick": adm.get("tick"),
                "prompt_len": adm.get("prompt_len"),
                "budget": adm.get("budget")}
        if fin is not None:
            args.update({"finish_tick": fin.get("tick"),
                         "n_tokens": fin.get("n_tokens"),
                         "ttft_ticks": fin.get("ttft_ticks")})
        common = {"cat": "request", "id": int(rid), "name": f"req {rid}",
                  "pid": pid, "tid": slot}
        out.append({"ph": "b", "ts": ts, "args": args, **common})
        # unfinished requests (failed / still in flight) close zero-width
        end_ts = (fin["t"] - origin) * us if fin is not None else ts
        out.append({"ph": "e", "ts": end_ts, "args": {}, **common})
    return out


def perfetto_serving_load_events(serving_events: List[Dict[str, Any]],
                                 occupancy: Optional[List[Any]] = None,
                                 queue_depth: Optional[List[Any]] = None,
                                 s_per_tick: Optional[float] = None,
                                 pages_used: Optional[List[Any]] = None,
                                 page_fragmentation: Optional[List[Any]] = None,
                                 acceptance: Optional[List[Any]] = None,
                                 pid: int = 3) -> List[Dict[str, Any]]:
    """The serving-load debugging surface on the **tick clock**: per-slot
    request slices split into *queue wait* vs *execution* sub-spans, plus
    queue-depth and slot-occupancy counter tracks.

    Rides the same ``serve_admit``/``serve_finish`` RunReport rows as
    :func:`perfetto_request_events`, but lays everything out in ticks —
    the exact on-device stamps (``arrival``/``tick`` on the admit row,
    ``tick`` on the finish row) rather than host wall-clock, so a
    latency outlier decomposes visually: a long ``wait`` slice is
    queueing (saturation), a long ``serve`` slice is the ring itself.
    ``occupancy``/``queue_depth`` are ``(tick, n)`` block-boundary
    samples (``ServeResult.occupancy``/``.queue_depth``); each becomes a
    ``"C"`` counter track right under the request rows, so the queue
    ramp that precedes a TTFT blow-up is on screen with it.
    ``s_per_tick`` scales ticks to real time when known (1 tick = 1 us
    otherwise — relative layout is what matters). Admit rows without an
    ``arrival`` field (pre-SLO-observatory streams) degrade to a
    zero-width wait slice. Paged-engine runs add ``pages used`` and
    ``page fragmentation`` counter tracks from the same block-boundary
    samples (``ServeResult.pages_used``/``.page_fragmentation``), so a
    TTFT blow-up under prefix traffic decomposes into queue pressure vs
    page-pool pressure on one screen. Speculative runs add an
    ``acceptance rate`` counter track from ``(tick, alpha)`` samples
    (``ServeResult.acceptance_series``) and nest a ``verify`` sub-span
    under each finished request's serve slice carrying its
    draft-verify gauges (``spec_verify_visits``/``spec_accepted``/
    ``accepted_len_mean`` from the finish row), so an acceptance-rate
    sag lines up with the exact requests it slowed."""
    admits: Dict[Any, Dict[str, Any]] = {}
    finishes: Dict[Any, Dict[str, Any]] = {}
    for row in serving_events or []:
        if row.get("kind") == "serve_admit" and "rid" in row:
            admits[row["rid"]] = row
        elif row.get("kind") == "serve_finish" and "rid" in row:
            finishes[row["rid"]] = row
    if not admits and not occupancy and not queue_depth and not pages_used:
        return []
    tick_us = (s_per_tick * 1e6) if s_per_tick else 1.0
    out: List[Dict[str, Any]] = [{
        "ph": "M", "name": "process_name", "pid": pid, "tid": 0, "ts": 0.0,
        "args": {"name": "serving load (ticks)"}}]
    slots = sorted({int(r.get("slot", 0)) for r in admits.values()})
    for slot in slots:
        out.append({"ph": "M", "name": "thread_name", "pid": pid,
                    "tid": slot + 1, "ts": 0.0,
                    "args": {"name": f"slot {slot}"}})
    for rid, adm in sorted(admits.items(),
                           key=lambda kv: kv[1].get("tick", 0)):
        slot = int(adm.get("slot", 0))
        admit_tick = float(adm.get("tick", 0))
        arrival = adm.get("arrival")
        arrival = float(arrival) if isinstance(arrival, (int, float)) \
            else admit_tick
        args = {"rid": rid, "slot": slot, "arrival": arrival,
                "admit_tick": adm.get("tick"),
                "prompt_len": adm.get("prompt_len"),
                "budget": adm.get("budget")}
        if arrival < admit_tick:
            out.append({"ph": "X", "name": f"wait r{rid}",
                        "cat": "queue_wait", "pid": pid, "tid": slot + 1,
                        "ts": arrival * tick_us,
                        "dur": (admit_tick - arrival) * tick_us,
                        "args": args})
        fin = finishes.get(rid)
        end_tick = (float(fin["tick"]) if fin is not None
                    and isinstance(fin.get("tick"), (int, float))
                    else admit_tick)
        fargs = dict(args)
        if fin is not None:
            fargs.update({"finish_tick": fin.get("tick"),
                          "n_tokens": fin.get("n_tokens"),
                          "ttft_ticks": fin.get("ttft_ticks")})
        out.append({"ph": "X", "name": f"serve r{rid}", "cat": "execution",
                    "pid": pid, "tid": slot + 1,
                    "ts": admit_tick * tick_us,
                    "dur": max(end_tick - admit_tick, 0.0) * tick_us,
                    "args": fargs})
        # draft-verify sub-span: equal-duration slice emitted after the
        # serve slice nests under it in the UI; args carry the
        # per-request speculative gauges from the finish row
        if fin is not None and fin.get("spec_verify_visits"):
            out.append({
                "ph": "X", "name": f"verify r{rid} "
                f"x{int(fin['spec_verify_visits'])}",
                "cat": "spec_verify", "pid": pid, "tid": slot + 1,
                "ts": admit_tick * tick_us,
                "dur": max(end_tick - admit_tick, 0.0) * tick_us,
                "args": {"rid": rid,
                         "spec_verify_visits": fin.get("spec_verify_visits"),
                         "spec_accepted": fin.get("spec_accepted"),
                         "accepted_len_mean": fin.get("accepted_len_mean")}})
    for name, series in (("slot occupancy", occupancy),
                         ("queue depth", queue_depth),
                         ("pages used", pages_used)):
        for t, n in series or []:
            out.append({"ph": "C", "name": name, "cat": "serving_load",
                        "pid": pid, "tid": 0, "ts": float(t) * tick_us,
                        "args": {name.replace(" ", "_"): int(n)}})
    for t, f in page_fragmentation or []:
        out.append({"ph": "C", "name": "page fragmentation",
                    "cat": "serving_load", "pid": pid, "tid": 0,
                    "ts": float(t) * tick_us,
                    "args": {"page_fragmentation": float(f)}})
    for t, a in acceptance or []:
        if a is None:
            continue  # pre-first-verify samples carry no rate yet
        out.append({"ph": "C", "name": "acceptance rate",
                    "cat": "serving_load", "pid": pid, "tid": 0,
                    "ts": float(t) * tick_us,
                    "args": {"acceptance_rate": float(a)}})
    return out


def perfetto_dynamics_events(dynamics_events: List[Dict[str, Any]],
                             pid: int = 2) -> List[Dict[str, Any]]:
    """Per-stage grad-norm counter tracks from RunReport ``dynamics``
    event rows (the rows ``fit`` streams at every log sync), one ``"C"``
    counter per (log point, stage) plus global grad-norm and GNS tracks.
    The rows carry the event stream's wall clock, so they land on their
    own "training dynamics" process, normalized to the first dynamics
    row; within the process, step ordering is exact."""
    rows = [r for r in (dynamics_events or [])
            if r.get("kind") == "dynamics" and "t" in r]
    if not rows:
        return []
    us = 1e6
    origin = min(r["t"] for r in rows)
    out: List[Dict[str, Any]] = [{
        "ph": "M", "name": "process_name", "pid": pid, "tid": 0, "ts": 0.0,
        "args": {"name": "training dynamics"}}]

    def finite(x):
        return isinstance(x, (int, float)) and np.isfinite(x)

    for r in sorted(rows, key=lambda r: r["t"]):
        ts = (r["t"] - origin) * us
        if finite(r.get("grad_norm")):
            out.append({"ph": "C", "name": "grad_norm", "cat": "dynamics",
                        "pid": pid, "tid": 0, "ts": ts,
                        "args": {"grad_norm": float(r["grad_norm"])}})
        if finite(r.get("gns")):
            out.append({"ph": "C", "name": "gns", "cat": "dynamics",
                        "pid": pid, "tid": 0, "ts": ts,
                        "args": {"gns": float(r["gns"])}})
        for s, v in enumerate(r.get("grad_norm_per_stage") or []):
            if finite(v):
                out.append({
                    "ph": "C", "name": f"grad_norm stage {s}",
                    "cat": "dynamics", "pid": pid, "tid": 0, "ts": ts,
                    "args": {"grad_norm": float(v)}})
    return out


def write_perfetto_trace(path: str,
                         serving_events: Optional[List[Dict[str, Any]]] = None,
                         dynamics_events: Optional[List[Dict[str, Any]]] = None,
                         serving_load_tracks: Optional[Dict[str, Any]] = None
                         ) -> str:
    """Write the requests / dynamics / serving-load tracks to ``path`` as
    Chrome-trace JSON; returns the path.
    ``serving_load_tracks`` (optional) adds the tick-clock serving-load
    process (:func:`perfetto_serving_load_events`): a dict with any of
    ``occupancy``/``queue_depth`` (block-boundary ``(tick, n)`` samples)
    and ``s_per_tick``; the request sub-spans come from
    ``serving_events``."""
    rows = perfetto_request_events(serving_events or [])
    rows.extend(perfetto_dynamics_events(dynamics_events or []))
    if serving_load_tracks is not None:
        rows.extend(perfetto_serving_load_events(
            serving_events or [],
            occupancy=serving_load_tracks.get("occupancy"),
            queue_depth=serving_load_tracks.get("queue_depth"),
            s_per_tick=serving_load_tracks.get("s_per_tick"),
            pages_used=serving_load_tracks.get("pages_used"),
            page_fragmentation=serving_load_tracks.get(
                "page_fragmentation"),
            acceptance=serving_load_tracks.get("acceptance")))
    trace = {"traceEvents": rows, "displayTimeUnit": "ms"}
    with open(path, "w") as fh:
        json.dump(trace, fh)
    return path


# ---------------------------------------------------------------------------
# Serving latency summaries
# ---------------------------------------------------------------------------


def _pct(xs: Sequence[float]) -> Dict[str, float]:
    """p50/p95/p99/mean of a latency sample (empty-safe)."""
    if not len(xs):
        return {"p50": None, "p95": None, "p99": None, "mean": None, "n": 0}
    a = np.asarray(xs, np.float64)
    return {"p50": float(np.percentile(a, 50)),
            "p95": float(np.percentile(a, 95)),
            "p99": float(np.percentile(a, 99)),
            "mean": float(a.mean()), "n": int(a.size)}


def serving_summary(result) -> Dict[str, Any]:
    """Per-request latency + throughput summary of one serving run.

    ``result`` is a :class:`...serving.engine.ServeResult` (duck-typed —
    anything with ``completions`` carrying ``ttft_ticks``/``tpot_ticks``,
    plus ``tokens_out``/``ticks``/``wall_s``/``n_slots``/``policy``/
    ``occupancy``). Latencies are reported in *ticks* (exact, stamped
    on-device at token-banking time) with the measured ``s_per_tick``
    factor alongside, so wall-clock latencies are one multiply away and
    the tick numbers stay comparable across hosts.
    """
    # failed completions (serving hardening: rejected/poisoned requests
    # retired with status="failed") carry no latency stamps — count them
    # separately, keep the percentile samples clean
    ok = [c for c in result.completions
          if getattr(c, "status", "ok") == "ok"]
    ttfts = [c.ttft_ticks for c in ok]
    tpots = [c.tpot_ticks for c in ok if c.tpot_ticks is not None]
    # TTFT split: admission wait (admit - arrival, pure queueing) vs
    # service TTFT (first token - admit, the ring's own latency). Older
    # ServeResult-likes without the stamps degrade to empty samples.
    waits = [c.admit_wait_ticks for c in ok
             if getattr(c, "admit_wait_ticks", None) is not None]
    service = [c.service_ttft_ticks for c in ok
               if getattr(c, "service_ttft_ticks", None) is not None]
    occ = [int(n) for _, n in result.occupancy]
    qd_series = list(getattr(result, "queue_depth", []) or [])
    qd = [int(n) for _, n in qd_series]
    busy = getattr(result, "busy_ticks", None)
    return {
        "policy": result.policy,
        "n_requests": len(ok),
        "n_failed": len(result.completions) - len(ok),
        "n_slots": int(result.n_slots),
        "ticks": int(result.ticks),
        "busy_ticks": int(busy) if busy is not None else None,
        "wall_s": float(result.wall_s),
        "s_per_tick": (float(result.wall_s) / result.ticks
                       if result.ticks else None),
        "tokens_out": int(result.tokens_out),
        "tokens_per_sec": float(result.tokens_per_sec),
        "goodput": float(result.goodput),
        "goodput_busy": (float(result.goodput_busy)
                         if hasattr(result, "goodput_busy") else None),
        "ttft_ticks": _pct(ttfts),
        "tpot_ticks": _pct(tpots),
        "admit_wait_ticks": _pct(waits),
        "service_ttft_ticks": _pct(service),
        "occupancy_mean": float(np.mean(occ)) if occ else 0.0,
        "occupancy": [[int(t), int(n)] for t, n in result.occupancy],
        "queue_depth_mean": float(np.mean(qd)) if qd else 0.0,
        "queue_depth_max": int(max(qd)) if qd else 0,
        "queue_depth": [[int(t), int(n)] for t, n in qd_series],
        **_paged_summary_fields(result),
        **_spec_summary_fields(result),
    }


def _paged_summary_fields(result) -> Dict[str, Any]:
    """Paged-KV gauges for :func:`serving_summary` — empty dict for
    contiguous runs, so their summaries are byte-identical to before the
    paged engine existed."""
    if not getattr(result, "paged", False):
        return {}
    pages = [int(n) for _, n in (result.pages_used or [])]
    frag = [float(f) for _, f in (result.page_fragmentation or [])]
    return {
        "paged": True,
        "pages_capacity": int(result.pages_capacity),
        "pages_used_mean": float(np.mean(pages)) if pages else 0.0,
        "pages_used_max": int(max(pages)) if pages else 0,
        "pages_used": [[int(t), int(n)] for t, n in result.pages_used],
        "page_fragmentation_mean": (float(np.mean(frag)) if frag else 0.0),
        "page_fragmentation": [[int(t), float(f)]
                               for t, f in result.page_fragmentation],
        "prefix_hit_rate": (float(result.prefix_hit_rate)
                            if result.prefix_hit_rate is not None else 0.0),
        "prefill_skipped_tokens": int(result.prefill_skipped_tokens),
        "n_cow": int(result.n_cow),
        "n_backpressure": int(result.n_backpressure),
    }


def _spec_summary_fields(result) -> Dict[str, Any]:
    """Speculative-decoding gauges for :func:`serving_summary` — empty
    dict for non-speculative runs (their summaries stay byte-identical).
    ``acceptance_rate``/``accepted_len_mean`` are ``None`` rather than a
    division error when a run finished before its first verify tick
    (zero-finished sweep points included)."""
    if not getattr(result, "speculative", False):
        return {}
    series = list(getattr(result, "acceptance_series", []) or [])
    rate = result.acceptance_rate
    alm = result.accepted_len_mean
    return {
        "speculative": True,
        "gamma": int(result.gamma),
        "spec_verify_visits": int(result.spec_verify_visits),
        "spec_accepted_tokens": int(result.spec_accepted_tokens),
        "acceptance_rate": float(rate) if rate is not None else None,
        "accepted_len_mean": float(alm) if alm is not None else None,
        "acceptance_series": [[int(t), (float(a) if a is not None else None)]
                              for t, a in series],
    }


# ---------------------------------------------------------------------------
# Structured run reports
# ---------------------------------------------------------------------------


class RunReport:
    """Counters / timers / gauges + JSONL events + a single JSON manifest.

    One instance per run (a ``fit`` call, a sweep row, a serving
    script). With ``out_dir`` set, :meth:`event` streams to
    ``events.jsonl`` as it happens (crash-safe partial record) and
    :meth:`write` drops ``report.json``; without it everything stays
    in-memory and :meth:`manifest` returns the same schema for embedding.
    """

    def __init__(self, out_dir: Optional[str] = None,
                 name: str = "run") -> None:
        import jax
        import jaxlib
        self.meta: Dict[str, Any] = {
            "name": name,
            "created_unix": time.time(),
            "jax_version": jax.__version__,
            "jaxlib_version": getattr(jaxlib, "__version__", "unknown"),
        }
        self.counters: Dict[str, int] = {}
        self.gauges: Dict[str, Any] = {}
        self.timers: Dict[str, float] = {}
        self.events: List[Dict[str, Any]] = []
        self.serving: List[Dict[str, Any]] = []
        self.serving_load: Optional[Dict[str, Any]] = None
        self.resilience: Optional[Dict[str, Any]] = None
        self.static_analysis: Optional[Dict[str, Any]] = None
        self.cost_model: Optional[Dict[str, Any]] = None
        self.memory: Optional[Dict[str, Any]] = None
        self.dynamics: Optional[Dict[str, Any]] = None
        self.calibration: Optional[Dict[str, Any]] = None
        self.setup: Optional[Dict[str, Any]] = None
        self.out_dir = out_dir
        self._events_fh = None
        # the event stream is written from the training loop AND from
        # background threads (resilience.StepWatchdog stall diagnostics)
        self._events_lock = threading.Lock()
        if out_dir is not None:
            os.makedirs(out_dir, exist_ok=True)

    # -- recording ------------------------------------------------------

    def set_meta(self, **fields: Any) -> None:
        """Merge run-identifying fields (config, mesh_shape, schedule,
        phase_stats, backend, ...) into the manifest's ``meta`` block."""
        self.meta.update(fields)

    def count(self, name: str, n: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + int(n)

    def gauge(self, name: str, value: Any) -> None:
        self.gauges[name] = value

    @contextlib.contextmanager
    def timer(self, name: str):
        """Accumulating wall-clock timer: ``with report.timer("compile_s"):``."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.timers[name] = (self.timers.get(name, 0.0)
                                 + time.perf_counter() - t0)

    def event(self, kind: str, **fields: Any) -> None:
        """Append one timestamped event; streamed to ``events.jsonl`` when
        the report has an output directory."""
        row = {"t": time.time(), "kind": kind, **fields}
        with self._events_lock:  # watchdog threads stream events too
            self.events.append(row)
            if self.out_dir is not None:
                if self._events_fh is None:
                    self._events_fh = open(
                        os.path.join(self.out_dir, "events.jsonl"), "a")
                self._events_fh.write(json.dumps(row, default=_jsonable)
                                      + "\n")
                self._events_fh.flush()

    def attach_serving(self, summary: Dict[str, Any]) -> None:
        """Append one serving-run latency summary
        (:func:`serving_summary`) to the manifest's ``serving`` list —
        a benchmark that runs continuous and static policies back to
        back attaches both."""
        self.serving.append(summary)

    def attach_serving_load(self, section: Dict[str, Any]) -> None:
        """Embed an offered-load sweep
        (:func:`...serving.loadgen.sweep_offered_load` /
        :func:`...serving.slo.serving_load_section`: latency-vs-load
        curve rows, the saturation knee, the SLOSpec and the regression
        reference point) as the manifest's ``serving_load`` block — the
        record ``scripts/regress.py`` guards ``max_sustainable_load``
        and reference p99 TTFT from."""
        self.serving_load = dict(section)

    def attach_resilience(self, section: Dict[str, Any]) -> None:
        """Embed the run's resilience summary (anomaly / preemption /
        stall counters, checkpoint-commit stats — assembled by
        ``utils.train.fit`` from ``resilience.CheckpointManager.stats``
        and the guard counters) as the manifest's ``resilience`` block."""
        self.resilience = dict(section)

    def attach_static_analysis(self, section: Dict[str, Any]) -> None:
        """Embed the static-verification digest
        (:func:`analysis.table_check.static_analysis_section`: verifier
        version, schedules checked, hazard count, slot high-water marks)
        as the manifest's ``static_analysis`` block."""
        self.static_analysis = dict(section)

    def attach_cost_model(self, section: Dict[str, Any]) -> None:
        """Embed the roofline accounting
        (:func:`analysis.cost_model.cost_model_section`: predicted vs
        measured step time, bubble fractions, ppermute hops, MFU/HFU)
        as the manifest's ``cost_model``
        block — the record ``scripts/regress.py`` reads."""
        self.cost_model = dict(section)

    def attach_dynamics(self, section: Dict[str, Any]) -> None:
        """Embed the training-dynamics summary
        (:func:`utils.dynamics.dynamics_section`: final grad norm,
        gradient-noise scale, per-stage stat rows, attributed-skip count
        and the run's forensic bundles) as the manifest's ``dynamics``
        block — the model-health record ``scripts/regress.py`` tracks."""
        self.dynamics = dict(section)

    def attach_memory(self, section: Dict[str, Any]) -> None:
        """Embed the HBM accounting
        (:func:`analysis.memory_model.memory_model_section` /
        ``serving_memory_section``: analytic per-device bytes from the
        verifier's slot peaks, AOT-compiled ``memory_analysis()`` and
        their reconciliation) as the manifest's
        ``memory`` block — the bytes-domain record ``scripts/regress.py``
        guards."""
        self.memory = dict(section)

    def attach_calibration(self, section: Dict[str, Any]) -> None:
        """Embed the predicted-vs-measured calibration record
        (:func:`analysis.calibration.calibration_section`: compact
        per-config probe rows, the raw-vs-corrected median error
        summary, the fitted per-hardware correction factors and the
        ledger path) as the manifest's ``calibration`` block — the
        model-trust record ``scripts/regress.py`` guards and the PR-19
        planner search will consume."""
        self.calibration = dict(section)

    def attach_setup(self, section: Dict[str, Any]) -> None:
        """Embed where start-up went
        (:func:`utils.profiling.setup_section`: the ``setup/*`` host spans,
        the step program's trace / lowering / backend seconds, every other
        program's count and seconds with the dearest few by name, the
        recorder's own cost) as the manifest's ``setup`` block, beside the
        ``compile_s`` timer, which brackets the first step whole."""
        self.setup = dict(section)

    # -- output ---------------------------------------------------------

    def manifest(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "schema_version": SCHEMA_VERSION,
            "meta": _jsonable(self.meta),
            "counters": dict(self.counters),
            "gauges": _jsonable(self.gauges),
            "timers": dict(self.timers),
            "n_events": len(self.events),
        }
        if self.out_dir is not None:
            out["events_path"] = os.path.join(self.out_dir, "events.jsonl")
        else:
            out["events"] = _jsonable(self.events)
        if self.serving:
            out["serving"] = _jsonable(self.serving)
        if self.serving_load is not None:
            out["serving_load"] = _jsonable(self.serving_load)
        if self.resilience is not None:
            out["resilience"] = _jsonable(self.resilience)
        if self.static_analysis is not None:
            out["static_analysis"] = _jsonable(self.static_analysis)
        if self.cost_model is not None:
            out["cost_model"] = _jsonable(self.cost_model)
        if self.memory is not None:
            out["memory"] = _jsonable(self.memory)
        if self.dynamics is not None:
            out["dynamics"] = _jsonable(self.dynamics)
        if self.calibration is not None:
            out["calibration"] = _jsonable(self.calibration)
        if self.setup is not None:
            out["setup"] = _jsonable(self.setup)
        return out

    def write(self, path: Optional[str] = None) -> Dict[str, Any]:
        """Validate + write the manifest (``report.json`` under ``out_dir``
        by default); returns the manifest dict."""
        m = self.manifest()
        validate_report(m)
        if path is None:
            if self.out_dir is None:
                raise ValueError("RunReport has no out_dir; pass a path")
            path = os.path.join(self.out_dir, "report.json")
        with open(path, "w") as fh:
            json.dump(m, fh, indent=2, default=_jsonable)
            fh.write("\n")
        if self._events_fh is not None:
            self._events_fh.close()
            self._events_fh = None
        return m


def _jsonable(x: Any) -> Any:
    """Best-effort conversion to JSON-serializable primitives (numpy
    scalars/arrays, dataclass-likes, tuples)."""
    if isinstance(x, dict):
        return {str(k): _jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    if isinstance(x, (np.integer,)):
        return int(x)
    if isinstance(x, (np.floating,)):
        return float(x)
    if isinstance(x, np.ndarray):
        return x.tolist()
    if isinstance(x, (str, int, float, bool)) or x is None:
        return x
    if hasattr(x, "__dataclass_fields__"):
        import dataclasses
        return _jsonable(dataclasses.asdict(x))
    return str(x)


def validate_report(manifest: Dict[str, Any]) -> None:
    """Schema check for a RunReport manifest (hand-rolled: the container
    has no jsonschema). Raises ``ValueError`` on the first violation.
    Keys it does not know are ignored, so a manifest written before PR 32
    (with a ``telemetry`` section, a ``memory.live`` leg or a
    ``cost_model.attribution`` table) still validates."""
    def fail(msg: str):
        raise ValueError(f"invalid run report: {msg}")

    if not isinstance(manifest, dict):
        fail("manifest must be a dict")
    if manifest.get("schema_version") != SCHEMA_VERSION:
        fail(f"schema_version must be {SCHEMA_VERSION}, got "
             f"{manifest.get('schema_version')!r}")
    meta = manifest.get("meta")
    if not isinstance(meta, dict):
        fail("meta must be a dict")
    for key in ("name", "jax_version", "jaxlib_version"):
        if not isinstance(meta.get(key), str):
            fail(f"meta.{key} must be a string")
    if not isinstance(meta.get("created_unix"), (int, float)):
        fail("meta.created_unix must be a number")
    counters = manifest.get("counters")
    if not isinstance(counters, dict) or not all(
            isinstance(v, int) for v in counters.values()):
        fail("counters must be a dict of ints")
    if not isinstance(manifest.get("gauges"), dict):
        fail("gauges must be a dict")
    timers = manifest.get("timers")
    if not isinstance(timers, dict) or not all(
            isinstance(v, (int, float)) for v in timers.values()):
        fail("timers must be a dict of numbers")
    if not isinstance(manifest.get("n_events"), int):
        fail("n_events must be an int")
    events = manifest.get("events")
    if events is not None:
        if not isinstance(events, list):
            fail("events must be a list")
        for row in events:
            if not isinstance(row, dict) or not isinstance(
                    row.get("kind"), str) or not isinstance(
                    row.get("t"), (int, float)):
                fail("each event needs a str 'kind' and numeric 't'")
    elif not isinstance(manifest.get("events_path"), str):
        fail("manifest needs either inline 'events' or an 'events_path'")
    serving = manifest.get("serving")
    if serving is not None:
        if not isinstance(serving, list):
            fail("serving must be a list of run summaries")
        for row in serving:
            if not isinstance(row, dict):
                fail("each serving summary must be a dict")
            if not isinstance(row.get("policy"), str):
                fail("serving summary needs a str 'policy'")
            for key in ("tokens_out", "ticks", "n_requests"):
                if not isinstance(row.get(key), int):
                    fail(f"serving summary needs an int {key!r}")
            for key in ("wall_s", "tokens_per_sec", "goodput"):
                if not isinstance(row.get(key), (int, float)):
                    fail(f"serving summary needs a numeric {key!r}")
            for key in ("ttft_ticks", "tpot_ticks"):
                if not isinstance(row.get(key), dict):
                    fail(f"serving summary needs a dict {key!r} "
                         "(p50/p95/p99/mean)")
            if "n_failed" in row and not isinstance(row["n_failed"], int):
                fail("serving summary n_failed must be an int")
    sl = manifest.get("serving_load")
    if sl is not None:
        if not isinstance(sl, dict):
            fail("serving_load must be a dict")
        if not isinstance(sl.get("policy"), str):
            fail("serving_load.policy must be a string")
        wl = sl.get("workload")
        if not isinstance(wl, dict) or not isinstance(
                wl.get("mix"), str) or not isinstance(
                wl.get("n_requests"), int):
            fail("serving_load.workload needs a str 'mix' and int "
                 "'n_requests'")
        slo = sl.get("slo")
        if not isinstance(slo, dict) or not isinstance(
                slo.get("ttft_p99_ticks"), (int, float)):
            fail("serving_load.slo needs a numeric ttft_p99_ticks")
        curve = sl.get("curve")
        if not isinstance(curve, list) or not curve:
            fail("serving_load.curve must be a non-empty list")
        loads = []
        for row in curve:
            if not isinstance(row, dict) or not isinstance(
                    row.get("offered_load"), (int, float)):
                fail("serving_load curve rows need a numeric "
                     "'offered_load'")
            loads.append(float(row["offered_load"]))
            for key in ("ticks", "tokens_out"):
                if not isinstance(row.get(key), int):
                    fail(f"serving_load curve rows need an int {key!r}")
            for key in ("ttft_ticks", "tpot_ticks"):
                pct = row.get(key)
                if not isinstance(pct, dict) or "p99" not in pct:
                    fail(f"serving_load curve row {key!r} must be a "
                         "percentile dict carrying p99")
                if pct["p99"] is not None and not isinstance(
                        pct["p99"], (int, float)):
                    fail(f"serving_load curve row {key}.p99 must be a "
                         "number or null")
            # paged-engine gauge columns are optional (contiguous runs
            # omit them) but typed when present
            for key in ("goodput", "queue_depth_mean", "prefix_hit_rate",
                        "pages_used_mean", "page_fragmentation_mean"):
                if key in row and row[key] is not None and not isinstance(
                        row[key], (int, float)):
                    fail(f"serving_load curve row {key!r} must be numeric")
            for key in ("pages_capacity", "pages_used_max", "n_cow",
                        "n_backpressure", "prefill_skipped_tokens"):
                if key in row and row[key] is not None and not isinstance(
                        row[key], int):
                    fail(f"serving_load curve row {key!r} must be an int")
        if any(b <= a for a, b in zip(loads, loads[1:])):
            fail(f"serving_load offered loads must be strictly "
                 f"increasing, got {loads}")
        knee = sl.get("knee")
        if not isinstance(knee, dict) or not isinstance(
                knee.get("detected"), bool):
            fail("serving_load.knee must be a dict with a bool 'detected'")
        for key in ("knee_load", "max_sustainable_load"):
            v = knee.get(key)
            if v is not None and not isinstance(v, (int, float)):
                fail(f"serving_load.knee.{key} must be a number or null")
        if knee["detected"] and not isinstance(
                knee.get("knee_load"), (int, float)):
            fail("serving_load.knee.detected without a numeric knee_load")
        ref = sl.get("reference")
        if ref is not None:
            if not isinstance(ref, dict) or not isinstance(
                    ref.get("offered_load"), (int, float)):
                fail("serving_load.reference needs a numeric "
                     "'offered_load'")
    res = manifest.get("resilience")
    if res is not None:
        if not isinstance(res, dict):
            fail("resilience must be a dict")
        for key in ("anomalies", "anomaly_budget", "stalls", "n_committed",
                    "n_saved", "gc_removed"):
            if key in res and not isinstance(res[key], int):
                fail(f"resilience.{key} must be an int")
        if "preempted" in res and not isinstance(res["preempted"], bool):
            fail("resilience.preempted must be a bool")
    sa = manifest.get("static_analysis")
    if sa is not None:
        if not isinstance(sa, dict):
            fail("static_analysis must be a dict")
        if not isinstance(sa.get("verifier_version"), int):
            fail("static_analysis.verifier_version must be an int")
        if not isinstance(sa.get("schedules"), list) or not all(
                isinstance(s, str) for s in sa["schedules"]):
            fail("static_analysis.schedules must be a list of strings")
        if not isinstance(sa.get("hazards"), int):
            fail("static_analysis.hazards must be an int")
        shw = sa.get("slot_high_water")
        if not isinstance(shw, dict) or not all(
                isinstance(v, dict) and isinstance(v.get("act"), int)
                and isinstance(v.get("grad"), int) for v in shw.values()):
            fail("static_analysis.slot_high_water must map schedule labels "
                 "to {'act': int, 'grad': int}")
    cm = manifest.get("cost_model")
    if cm is not None:
        if not isinstance(cm, dict):
            fail("cost_model must be a dict")
        if not isinstance(cm.get("schedule"), str):
            fail("cost_model.schedule must be a string")
        hw = cm.get("hardware")
        if not isinstance(hw, dict) or not isinstance(
                hw.get("name"), str) or not isinstance(
                hw.get("peak_flops"), (int, float)):
            fail("cost_model.hardware needs a str name and numeric "
                 "peak_flops")
        pred = cm.get("predicted")
        if not isinstance(pred, dict):
            fail("cost_model.predicted must be a dict")
        for key in ("step_s", "step_s_comm_overlap", "bubble_table_exact",
                    "bubble_closed_form"):
            if not isinstance(pred.get(key), (int, float)):
                fail(f"cost_model.predicted.{key} must be a number")
        comm = cm.get("comm")
        if not isinstance(comm, dict) or not isinstance(
                comm.get("hops"), int):
            fail("cost_model.comm needs an int 'hops'")
        measured = cm.get("measured")
        if measured is not None:
            if not isinstance(measured, dict):
                fail("cost_model.measured must be a dict")
            for key in ("step_s", "mfu"):
                if not isinstance(measured.get(key), (int, float)):
                    fail(f"cost_model.measured.{key} must be a number")
    mem = manifest.get("memory")
    if mem is not None:
        if not isinstance(mem, dict):
            fail("memory must be a dict")
        if not isinstance(mem.get("schedule"), str):
            fail("memory.schedule must be a string")
        hw = mem.get("hardware")
        if not isinstance(hw, dict) or not isinstance(hw.get("name"), str):
            fail("memory.hardware needs a str name")
        ana = mem.get("analytic")
        if not isinstance(ana, dict):
            fail("memory.analytic must be a dict")
        for key in ("act_slot_bytes", "grad_slot_bytes", "peak_bytes",
                    "params_per_device_bytes"):
            if not isinstance(ana.get(key), (int, float)):
                fail(f"memory.analytic.{key} must be a number")
        devs = ana.get("per_device")
        if not isinstance(devs, list) or not devs:
            fail("memory.analytic.per_device must be a non-empty list")
        for row in devs:
            if not isinstance(row, dict) or not isinstance(
                    row.get("device"), int):
                fail("memory.analytic.per_device rows need an int 'device'")
            for key in ("act_bytes", "grad_bytes", "total_bytes"):
                if not isinstance(row.get(key), (int, float)):
                    fail(f"memory.analytic.per_device.{key} must be a "
                         "number")
        comp = mem.get("compiled")
        if comp is not None:
            if not isinstance(comp, dict):
                fail("memory.compiled must be a dict")
            if "error" not in comp:
                for key in ("argument_bytes", "output_bytes", "temp_bytes"):
                    if not isinstance(comp.get(key), (int, float)):
                        fail(f"memory.compiled.{key} must be a number")
    dyn = manifest.get("dynamics")
    if dyn is not None:
        if not isinstance(dyn, dict):
            fail("dynamics must be a dict")
        if not isinstance(dyn.get("n_stages"), int):
            fail("dynamics.n_stages must be an int")
        for key in ("gns_updates", "n_skipped_attributed"):
            if not isinstance(dyn.get(key), int):
                fail(f"dynamics.{key} must be an int")
        # grad_norm_final / gns may be None (no log sync ran / estimator
        # unarmed) or a number; a poisoned final step serializes as the
        # string repr ("nan") — still a valid record of what happened
        for key in ("grad_norm_final", "gns"):
            if key in dyn and not isinstance(
                    dyn[key], (int, float, str, type(None))):
                fail(f"dynamics.{key} must be a number, string or null")
        rows = dyn.get("per_stage")
        if not isinstance(rows, list):
            fail("dynamics.per_stage must be a list")
        for row in rows:
            if not isinstance(row, dict) or not isinstance(
                    row.get("stage"), int):
                fail("dynamics.per_stage rows need an int 'stage'")
            if not isinstance(row.get("nonfinite"), int):
                fail("dynamics.per_stage rows need an int 'nonfinite'")
            for key in ("grad_norm", "grad_max", "param_rms",
                        "update_ratio"):
                if key in row and not isinstance(
                        row[key], (int, float, str)):
                    fail(f"dynamics.per_stage.{key} must be a number "
                         "(or a non-finite repr string)")
        bundles = dyn.get("forensic_bundles")
        if not isinstance(bundles, list) or not all(
                isinstance(b, str) for b in bundles):
            fail("dynamics.forensic_bundles must be a list of filenames")
    cal = manifest.get("calibration")
    if cal is not None:
        if not isinstance(cal, dict):
            fail("calibration must be a dict")
        if not isinstance(cal.get("schema_version"), int):
            fail("calibration.schema_version must be an int")
        rows = cal.get("rows")
        if not isinstance(rows, list):
            fail("calibration.rows must be a list")
        if cal.get("n_rows") != len(rows):
            fail(f"calibration.n_rows ({cal.get('n_rows')!r}) must equal "
                 f"len(rows) ({len(rows)})")
        for row in rows:
            if not isinstance(row, dict):
                fail("calibration.rows entries must be dicts")
            for key in ("schedule", "schedule_family", "backward_policy",
                        "comm_overlap"):
                if not isinstance(row.get(key), str):
                    fail(f"calibration row {key!r} must be a string")
            for key in ("n_devices", "n_microbatches"):
                if not isinstance(row.get(key), int):
                    fail(f"calibration row {key!r} must be an int")
            # predicted/measured/rel_err may be null (backfilled rows with
            # only one side of the comparison) but must be present
            for key in ("predicted_step_s", "measured_step_s", "rel_err"):
                if key not in row:
                    fail(f"calibration row missing {key!r}")
                if row[key] is not None and not isinstance(
                        row[key], (int, float)):
                    fail(f"calibration row {key!r} must be a number or null")
        summary = cal.get("summary")
        if not isinstance(summary, dict):
            fail("calibration.summary must be a dict")
        for key in ("median_abs_rel_err_raw", "median_abs_rel_err_corrected"):
            if key not in summary:
                fail(f"calibration.summary missing {key!r}")
            if summary[key] is not None and not isinstance(
                    summary[key], (int, float)):
                fail(f"calibration.summary.{key} must be a number or null")
        if not isinstance(summary.get("groups"), dict):
            fail("calibration.summary.groups must be a dict")
        corr = cal.get("correction")
        if corr is not None:
            if not isinstance(corr, dict):
                fail("calibration.correction must be a dict")
            for hw_name, factors in corr.items():
                if not isinstance(factors, dict):
                    fail(f"calibration.correction[{hw_name!r}] must be "
                         "a dict")
                for key in ("flops_efficiency", "bandwidth_efficiency"):
                    if not isinstance(factors.get(key), (int, float)):
                        fail(f"calibration.correction[{hw_name!r}].{key} "
                             "must be a number")
        lp = cal.get("ledger_path")
        if lp is not None and not isinstance(lp, str):
            fail("calibration.ledger_path must be a string or null")
    setup = manifest.get("setup")
    if setup is not None:
        def seconds_or_null(x):
            return x is None or (isinstance(x, (int, float))
                                 and not isinstance(x, bool) and x >= 0)

        if not isinstance(setup, dict):
            fail("setup must be a dict")
        spans = setup.get("spans")
        if not isinstance(spans, dict):
            fail("setup.spans must be a dict")
        for name, row in spans.items():
            if not name.startswith("setup/"):
                fail(f"setup.spans key {name!r} must start with 'setup/'")
            if not isinstance(row, dict) or not isinstance(
                    row.get("count"), int) or row["count"] < 1:
                fail(f"setup.spans[{name!r}] needs an int count >= 1")
            for key in ("seconds", "longest_s"):
                if row.get(key) is None or not seconds_or_null(row[key]):
                    fail(f"setup.spans[{name!r}].{key} must be a number "
                         ">= 0")
            if row["longest_s"] > row["seconds"] + 1e-9:
                fail(f"setup.spans[{name!r}]: longest_s above seconds")
            inside = row.get("inside")
            if not isinstance(inside, dict) or not all(
                    isinstance(k, str) and seconds_or_null(v)
                    and v is not None for k, v in inside.items()):
                fail(f"setup.spans[{name!r}].inside must be a dict of "
                     "seconds by where the span ran")
        step = setup.get("step_program", "missing")
        if step is not None:
            if not isinstance(step, dict) or not isinstance(
                    step.get("name"), str):
                fail("setup.step_program must be null or a dict with a "
                     "str 'name'")
            for key in ("trace_s", "lower_s", "backend_s"):
                if key not in step or not seconds_or_null(step[key]):
                    fail(f"setup.step_program.{key} must be a number >= 0 "
                         "or null")
            if step.get("cache") not in (None, "hit", "miss", "uncached"):
                fail("setup.step_program.cache must be hit, miss, "
                     "uncached or null")
        other = setup.get("other_programs")
        if not isinstance(other, dict) or not isinstance(
                other.get("count"), int):
            fail("setup.other_programs needs an int 'count'")
        if other.get("seconds") is None or not seconds_or_null(
                other["seconds"]):
            fail("setup.other_programs.seconds must be a number >= 0")
        dearest = other.get("dearest")
        if not isinstance(other.get("traced_only"), int):
            fail("setup.other_programs needs an int 'traced_only'")
        if not isinstance(dearest, list) or len(dearest) > (
                other["count"] + other["traced_only"]):
            fail("setup.other_programs.dearest must be a list no longer "
                 "than count + traced_only")
        for row in dearest:
            if not isinstance(row, dict) or not isinstance(
                    row.get("name"), str) or row.get(
                    "seconds") is None or not seconds_or_null(
                    row["seconds"]):
                fail("each setup.other_programs.dearest row needs a str "
                     "'name' and numeric 'seconds'")
