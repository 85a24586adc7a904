"""Perf-regression sentinel: compare a run report against its history.

Reads one or more RunReport manifests (``report.json`` from ``fit``, a
sweep row, ``scripts/probe.py`` or a serving script — anything carrying
gauges and/or a ``cost_model`` section), extracts the headline perf
numbers, appends them as one JSON line each to ``results/history.jsonl``,
and fails when a number regresses against the median of prior runs of
the same (name, backend, schedule) group:

- ``tokens_per_sec`` drops by more than ``--threshold`` (default 10%),
- ``mfu`` drops by more than the threshold,
- ``bubble`` (the table-exact prediction) rises by more than the
  threshold,
- ``peak_temp_bytes`` (XLA's compiled scratch high-water mark from the
  report's ``memory`` section) grows by more than the threshold — the
  HBM guard: a schedule or remat change that silently inflates memory
  fails here before it OOMs a real chip,
- ``max_sustainable_load`` (from the report's ``serving_load`` section:
  the highest offered load that sustained the SLO before the saturation
  knee) drops by more than the threshold, or ``serve_ttft_p99_ref``
  (p99 TTFT in ticks at the sweep's reference load) rises by more than
  the threshold — the serving SLO guard: a scheduler change that moves
  the knee left or inflates uncontended tail latency fails here before
  a deployment notices. Paged-KV runs add ``prefix_hit_rate`` (drop by
  more than the threshold) to the same guard: a radix-cache or
  admission change that quietly stops sharing prefixes fails here even
  while correctness tests still pass (the hit rate is deterministic on
  the seeded prefix mix, so off-cpu it gates hard; cpu-proxy stays
  warn-only like everything else). Speculative runs add
  ``acceptance_rate`` (drop), ``spec_tokens_per_sec`` (drop) and
  ``spec_tick_gain`` (drop — the tick-domain capacity headline of the
  serve_spec leg) under the same discipline,
- ``abs_rel_err`` (|predicted - measured| / measured step time, from the
  ``cost_model`` section or the ``rel_err`` gauge) or
  ``calib_abs_err_corrected`` (the ``calibration`` section's corrected
  median |relative error| — docs/observability.md §9) rises by more
  than the threshold — the model-trust guard: a change that quietly
  makes the cost model (or its fitted corrections) worse at predicting
  reality fails here before the auto-planner starts trusting bad
  numbers. ``calib_abs_err_raw`` rides the history rows uncorrected
  for comparison but is not gated (raw error is allowed to be bad —
  that is what the corrections are for).

Model-health metrics from the report's ``dynamics`` section (or sweep
gauges) — ``grad_norm_final`` and ``gns`` — get WARN-only two-sided
*drift* guards (``--drift-threshold``, default 50% either way): they
are expected to move across legitimate changes (init, data, LR), so a
drift never fails the run, but two runs of "the same" config quietly
diverging prints a warning naming the metric. An empty or missing
history file, a torn tail line, and single-sample groups are all fine:
the first run of a group establishes the baseline and always passes.

CPU-proxy runs (backend == "cpu") are always warn-only: a simulated-CPU
host serializes every "parallel" tick, so its wall-clock jitters with
machine load and a hard gate would flake (docs/results.md §2). Pass
``--warn-only`` to force the same behavior elsewhere (the tier-1/CI leg
does: CI hosts are shared). The first run of a group establishes the
baseline and always passes.

Stdlib only — no jax, no numpy: the sentinel must run even when the
accelerator stack is the thing that broke.

Usage::

    python scripts/regress.py --report /tmp/probe_smoke/report.json \
        [--history results/history.jsonl] [--threshold 0.1] \
        [--window 20] [--warn-only]
"""

import argparse
import json
import math
import os
import sys
import time


def _get(d, *path):
    """Nested dict lookup; None on any missing hop."""
    for key in path:
        if not isinstance(d, dict):
            return None
        d = d.get(key)
    return d


def _num(x):
    """Finite number or None (dynamics sections serialize NaN losses as
    repr strings; json may also yield literal NaN floats)."""
    if isinstance(x, bool) or not isinstance(x, (int, float)):
        return None
    return float(x) if math.isfinite(x) else None


def extract_metrics(manifest) -> dict:
    """One history row from a RunReport manifest (missing metrics -> None).

    Also accepts a certified schedule artifact (``kind ==
    "schedule_artifact"``, from ``scripts/search_schedule.py``): its
    predicted cost becomes the row, so searched schedules accumulate the
    same regression history as measured runs (backend ``"static"`` — no
    execution happened)."""
    if manifest.get("kind") == "schedule_artifact":
        pred = manifest.get("predicted") or {}
        return {
            "t": time.time(),
            "name": "schedule_search",
            "backend": "static",
            "schedule": "{}[D={},V={},M={}]".format(
                manifest.get("name", "Searched"),
                manifest.get("n_devices"), manifest.get("n_virtual"),
                manifest.get("n_microbatches")),
            "tokens_per_sec": None,
            "mfu": None,
            "bubble": pred.get("bubble_table_exact"),
            "predicted_step_s": pred.get("step_s"),
            "measured_step_s": None,
            "peak_temp_bytes": None,
            "grad_norm_final": None,
            "gns": None,
            "n_skipped_attributed": None,
            "max_sustainable_load": None,
            "serve_ttft_p99_ref": None,
            "prefix_hit_rate": None,
            "acceptance_rate": None,
            "spec_tokens_per_sec": None,
            "spec_tick_gain": None,
            "rel_err": None,
            "abs_rel_err": None,
            "calib_abs_err_raw": None,
            "calib_abs_err_corrected": None,
        }
    gauges = manifest.get("gauges") or {}
    cm = manifest.get("cost_model")
    tokens_per_sec = None
    for key in ("throughput", "headline_tokens_per_sec", "tokens_per_sec",
                "serve_continuous_tokens_per_sec"):
        if isinstance(gauges.get(key), (int, float)):
            tokens_per_sec = float(gauges[key])
            break
    if tokens_per_sec is None:
        tokens_per_sec = _get(cm, "measured", "tokens_per_sec")
    mfu = _get(cm, "measured", "mfu")
    if mfu is None and isinstance(gauges.get("headline_mfu"), (int, float)):
        mfu = float(gauges["headline_mfu"])
    if mfu is None and isinstance(gauges.get("mfu"), (int, float)):
        mfu = float(gauges["mfu"])
    bubble = _get(cm, "predicted", "bubble_table_exact")
    mem = manifest.get("memory")
    peak_temp = _get(mem, "compiled", "temp_bytes")
    # model-health metrics: the fit manifest's dynamics section, else the
    # sweep-row gauges (both carry the same column names)
    dyn = manifest.get("dynamics")
    grad_norm_final = _num(_get(dyn, "grad_norm_final"))
    if grad_norm_final is None:
        grad_norm_final = _num(gauges.get("grad_norm_final"))
    gns = _num(_get(dyn, "gns"))
    if gns is None:
        gns = _num(gauges.get("gns"))
    n_skipped = _get(dyn, "n_skipped_attributed")
    if n_skipped is None:
        n_skipped = gauges.get("n_skipped_attributed")
    # serving SLO observatory: the knee's sustainable-load headline and
    # the reference point's p99 TTFT (ticks — deterministic, so these
    # gate hard off-cpu unlike the wall-clock numbers)
    sl = manifest.get("serving_load")
    max_sustainable = _num(_get(sl, "knee", "max_sustainable_load"))
    ttft_ref = _num(_get(sl, "reference", "ttft_p99_ticks"))
    # paged-KV sharing gauge: best hit rate across the sweep's curve
    # rows (deterministic on a seeded mix), falling back to the serving
    # summaries / gauges for single-point bench reports. None on
    # contiguous runs -> no prior -> never gated.
    prefix_hit = None
    if isinstance(sl, dict):
        for r in sl.get("curve") or []:
            v = _num(r.get("prefix_hit_rate")) if isinstance(r, dict) \
                else None
            if v is not None:
                prefix_hit = v if prefix_hit is None else max(prefix_hit, v)
    if prefix_hit is None:
        for r in manifest.get("serving") or []:
            v = _num(r.get("prefix_hit_rate")) if isinstance(r, dict) \
                else None
            if v is not None:
                prefix_hit = v
    if prefix_hit is None:
        prefix_hit = _num(gauges.get("prefix_hit_rate"))
    # speculative-decoding gauges (docs/serving.md "Speculative
    # decoding"): acceptance rate via the same cascade as the prefix hit
    # rate — sweep curve rows, then serving summaries, then gauges.
    # Deterministic on a seeded trace, so it gates hard off-cpu; the
    # spec-on throughput / tick-gain headlines ride the gauges the
    # serve_spec leg records. None on non-speculative runs -> no prior
    # -> never gated.
    acceptance = None
    if isinstance(sl, dict):
        for r in sl.get("curve") or []:
            v = _num(r.get("acceptance_rate")) if isinstance(r, dict) \
                else None
            if v is not None:
                acceptance = v if acceptance is None else max(acceptance, v)
    if acceptance is None:
        for r in manifest.get("serving") or []:
            v = _num(r.get("acceptance_rate")) if isinstance(r, dict) \
                else None
            if v is not None:
                acceptance = v
    if acceptance is None:
        acceptance = _num(gauges.get("acceptance_rate"))
    spec_tps = _num(gauges.get("spec_on_tokens_per_sec"))
    spec_tick_gain = _num(gauges.get("spec_tick_gain"))
    # calibration observatory (docs/observability.md §9): the model-trust
    # axes — per-run signed error from the cost_model section (or the
    # first-class sweep gauge), plus the probe grid's raw and
    # corrected medians from the calibration section
    rel_err = _num(_get(cm, "measured", "rel_err"))
    if rel_err is None:
        rel_err = _num(gauges.get("rel_err"))
    cal = manifest.get("calibration")
    predicted_step_s = _get(cm, "predicted", "step_s")
    if predicted_step_s is None:
        predicted_step_s = _num(gauges.get("predicted_step_s"))
    return {
        "t": time.time(),
        "name": _get(manifest, "meta", "name") or "unknown",
        "backend": _get(manifest, "meta", "backend") or "unknown",
        "schedule": (_get(cm, "schedule")
                     or _get(mem, "schedule")
                     or _get(manifest, "meta", "schedule", "name")
                     or "unknown"),
        "tokens_per_sec": tokens_per_sec,
        "mfu": mfu,
        "bubble": bubble,
        "predicted_step_s": predicted_step_s,
        "measured_step_s": _get(cm, "measured", "step_s"),
        "peak_temp_bytes": peak_temp,
        "grad_norm_final": grad_norm_final,
        "gns": gns,
        "n_skipped_attributed": (int(n_skipped)
                                 if isinstance(n_skipped, (int, float))
                                 else None),
        "max_sustainable_load": max_sustainable,
        "serve_ttft_p99_ref": ttft_ref,
        "prefix_hit_rate": prefix_hit,
        "acceptance_rate": acceptance,
        "spec_tokens_per_sec": spec_tps,
        "spec_tick_gain": spec_tick_gain,
        "rel_err": rel_err,
        "abs_rel_err": abs(rel_err) if rel_err is not None else None,
        "calib_abs_err_raw": _num(_get(cal, "summary",
                                       "median_abs_rel_err_raw")),
        "calib_abs_err_corrected": _num(_get(cal, "summary",
                                             "median_abs_rel_err_corrected")),
    }


def load_history(path):
    """History rows (missing file -> []). Torn tail lines and rows that
    are not JSON objects (a hand-edited file, a stray string) are dropped
    rather than crashing the sentinel — history is best-effort evidence,
    not a source of truth."""
    rows = []
    if os.path.exists(path):
        with open(path) as fh:
            for line in fh:
                line = line.strip()
                if line:
                    try:
                        row = json.loads(line)
                    except json.JSONDecodeError:
                        continue  # a torn tail line never blocks the sentinel
                    if isinstance(row, dict):
                        rows.append(row)
    return rows


def _median(xs):
    xs = sorted(xs)
    n = len(xs)
    return xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])


def _group(row, history, window):
    group = [r for r in history
             if r.get("name") == row["name"]
             and r.get("backend") == row["backend"]
             and r.get("schedule") == row["schedule"]]
    return group[-window:]


def check(row, history, threshold, window) -> list:
    """Regression messages for ``row`` vs the same group's history."""
    group = _group(row, history, window)
    if not group:
        return []
    problems = []
    for key, direction in (("tokens_per_sec", "down"), ("mfu", "down"),
                           ("bubble", "up"), ("peak_temp_bytes", "up"),
                           ("max_sustainable_load", "down"),
                           ("serve_ttft_p99_ref", "up"),
                           ("prefix_hit_rate", "down"),
                           # speculative guards: a draft/verify change
                           # that quietly rejects more proposals or
                           # shrinks the tick-domain capacity win fails
                           # here (cpu-proxy: warn-only as always)
                           ("acceptance_rate", "down"),
                           ("spec_tokens_per_sec", "down"),
                           ("spec_tick_gain", "down"),
                           # model-trust guards: prediction error may not
                           # quietly grow (missing in pre-calibration
                           # history rows -> no prior -> skip)
                           ("abs_rel_err", "up"),
                           ("calib_abs_err_corrected", "up")):
        val = row.get(key)
        prior = [r[key] for r in group
                 if isinstance(r.get(key), (int, float))
                 and not isinstance(r.get(key), bool)]
        if not isinstance(val, (int, float)) or isinstance(val, bool) \
                or not prior:
            continue
        base = _median(prior)
        if direction == "down" and val < base * (1.0 - threshold):
            problems.append(
                f"{key} regressed: {val:.6g} < {base:.6g} "
                f"(median of {len(prior)}) - {threshold:.0%}")
        elif direction == "up" and base >= 0 and (
                val > base * (1.0 + threshold) + 1e-9):
            problems.append(
                f"{key} regressed: {val:.6g} > {base:.6g} "
                f"(median of {len(prior)}) + {threshold:.0%}")
    return problems


DRIFT_KEYS = ("grad_norm_final", "gns")


def drift_check(row, history, drift_threshold, window) -> list:
    """WARN-only two-sided drift messages for the model-health metrics:
    ``|val - median| > drift_threshold * max(|median|, eps)``. Never
    gates — training dynamics legitimately move when the run changes —
    but silent divergence between "identical" runs becomes visible."""
    group = _group(row, history, window)
    msgs = []
    for key in DRIFT_KEYS:
        val = _num(row.get(key))
        prior = [v for v in (_num(r.get(key)) for r in group)
                 if v is not None]
        if val is None or not prior:
            continue
        base = _median(prior)
        tol = drift_threshold * max(abs(base), 1e-12)
        if abs(val - base) > tol:
            msgs.append(
                f"{key} drifted: {val:.6g} vs median {base:.6g} of "
                f"{len(prior)} prior run(s) (±{drift_threshold:.0%})")
    return msgs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--report", action="append", required=True,
                    help="RunReport manifest path (repeatable)")
    ap.add_argument("--history", default="results/history.jsonl")
    ap.add_argument("--threshold", type=float, default=0.1,
                    help="relative regression tolerance (default 0.1)")
    ap.add_argument("--window", type=int, default=20,
                    help="prior runs per group the median is taken over")
    ap.add_argument("--drift-threshold", type=float, default=0.5,
                    help="two-sided WARN band for grad_norm_final/gns "
                         "drift (default 0.5 = ±50%%; never fails)")
    ap.add_argument("--warn-only", action="store_true",
                    help="report regressions but exit 0")
    args = ap.parse_args(argv)

    history = load_history(args.history)
    rc = 0
    new_rows = []
    for path in args.report:
        try:
            with open(path) as fh:
                manifest = json.load(fh)
        except (OSError, json.JSONDecodeError) as e:
            print(f"regress: cannot read {path}: {e}", file=sys.stderr)
            rc = max(rc, 2 if not args.warn_only else 0)
            continue
        row = extract_metrics(manifest)
        problems = check(row, history, args.threshold, args.window)
        label = f"{row['name']}/{row['schedule']}@{row['backend']}"
        cpu_proxy = row["backend"] == "cpu"
        if not problems:
            n_prior = sum(1 for r in history
                          if r.get("name") == row["name"]
                          and r.get("backend") == row["backend"]
                          and r.get("schedule") == row["schedule"])
            verdict = ("baseline established" if n_prior == 0
                       else f"OK vs {n_prior} prior run(s)")
            print(f"regress: {label}: {verdict} "
                  f"(tokens/s={row['tokens_per_sec']}, mfu={row['mfu']}, "
                  f"bubble={row['bubble']}, "
                  f"temp_bytes={row['peak_temp_bytes']})")
        else:
            soft = args.warn_only or cpu_proxy
            tag = ("WARN (cpu proxy)" if cpu_proxy and not args.warn_only
                   else "WARN" if soft else "FAIL")
            for p in problems:
                print(f"regress: {tag}: {label}: {p}",
                      file=sys.stderr if not soft else sys.stdout)
            if not soft:
                rc = 1
        for p in drift_check(row, history, args.drift_threshold,
                             args.window):
            print(f"regress: WARN (drift): {label}: {p}")
        new_rows.append(row)
        history.append(row)

    if new_rows:
        hist_dir = os.path.dirname(args.history)
        if hist_dir:
            os.makedirs(hist_dir, exist_ok=True)
        with open(args.history, "a") as fh:
            for row in new_rows:
                fh.write(json.dumps(row) + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
