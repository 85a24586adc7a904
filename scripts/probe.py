"""Calibration probe driver: measured micro-probes → ledger → corrections.

The measured leg of the calibration observatory (docs/observability.md
§9; ``analysis.calibration`` is the library). Two modes:

- **Grid** (default): run the seeded deterministic probe grid on the
  live mesh — one short measured run per (schedule family x microbatch
  count x backward policy x comm_overlap) point — fit per-hardware
  correction factors from the fresh measurements, re-price every row
  under the fit, append the rows to ``results/calibration.jsonl``,
  persist the fitted corrections as a versioned fingerprinted artifact
  (``results/calibration_corrections.json``), and write a RunReport
  whose ``calibration`` section passes ``validate_report``.
  ``--check`` turns the report into a gate: the corrected
  predictions must beat the raw ones (median |relative error|) — a
  hard failure on real hardware, a warning on the CPU proxy (tier-1
  runs it warn-only; a sim mesh measures the host, not the model).
- **Backfill** (``--backfill``): ingest the pre-ledger history —
  ``results/history.jsonl`` — into the ledger. Rows that carry a
  measurement but no prediction are kept
  with ``predicted: null``; rows that carry nothing calibratable are
  skipped with a printed, per-row reason. Nothing is dropped silently,
  and re-running is idempotent (exact duplicate lines are skipped).

Runs standalone (``python scripts/probe.py /tmp/probe_smoke --grid
smoke --check``) and as the tier-1 PROBE leg (scripts/tier1.sh).
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("out_dir", nargs="?", default="/tmp/probe_smoke",
                   help="report output directory")
    p.add_argument("--grid", default="smoke",
                   help="probe grid name (analysis.calibration._GRIDS)")
    p.add_argument("--seed", type=int, default=0,
                   help="grid-order + data seed (same seed => "
                        "byte-identical rows modulo measured fields)")
    p.add_argument("--iterations", type=int, default=2,
                   help="timed steps per probe")
    p.add_argument("--warmup", type=int, default=1,
                   help="untimed warmup steps per probe (compile+pages)")
    p.add_argument("--ledger", default=None,
                   help="calibration ledger path (default "
                        "results/calibration.jsonl under the repo root)")
    p.add_argument("--corrections", default=None,
                   help="correction artifact output path (default "
                        "results/calibration_corrections.json)")
    p.add_argument("--check", action="store_true",
                   help="gate: corrected must beat raw error (warn-only "
                        "on the cpu backend), artifact must byte-roundtrip")
    p.add_argument("--backfill", action="store_true",
                   help="ingest results/history.jsonl into the ledger "
                        "instead of probing")
    return p.parse_args(argv)


def _resolve(path, default_rel):
    """Relative paths resolve against the repo root, so the script works
    from any cwd (tier1.sh runs it from the checkout root, humans run it
    from anywhere)."""
    if path is None:
        path = default_rel
    return path if os.path.isabs(path) else os.path.join(ROOT, path)


def run_backfill(args) -> int:
    # host-side only: the calibration module imports no jax at module
    # scope, so backfill works on a box with no accelerator stack at all
    from distributed_training_with_pipeline_parallelism_tpu.analysis import (
        calibration as cal)

    ledger = _resolve(args.ledger, cal.DEFAULT_LEDGER_PATH)
    existing, bad = cal.load_ledger(ledger)
    for b in bad:
        print(f"probe --backfill: WARNING malformed ledger line: {b}")
    seen = {cal.canonical_row_line(r) for r in existing}
    rows, n_skipped = [], 0

    def keep(row, label, reason_none):
        nonlocal n_skipped
        if row is None:
            print(f"probe --backfill: skip {label}: {reason_none}")
            n_skipped += 1
        elif cal.canonical_row_line(row) in seen:
            print(f"probe --backfill: skip {label}: already in ledger")
            n_skipped += 1
        else:
            seen.add(cal.canonical_row_line(row))
            rows.append(row)

    hist = os.path.join(ROOT, "results", "history.jsonl")
    if os.path.exists(hist):
        with open(hist) as fh:
            for lineno, line in enumerate(fh, start=1):
                line = line.strip()
                if not line:
                    continue
                label = f"history.jsonl:{lineno}"
                try:
                    hrow = json.loads(line)
                except json.JSONDecodeError as e:
                    print(f"probe --backfill: skip {label}: bad JSON ({e})")
                    n_skipped += 1
                    continue
                keep(cal.backfill_row_from_history(hrow, path=label), label,
                     "no measured or predicted step time")
    else:
        print(f"probe --backfill: skip {hist}: no such file")

    n = cal.append_ledger_rows(ledger, rows)
    print(f"probe --backfill: OK — {n} rows appended to {ledger}, "
          f"{n_skipped} skipped (reasons above), "
          f"{len(existing)} rows were already present")
    return 0


def run_grid(args) -> int:
    import time

    # JAX_PLATFORMS is read when jax is imported, and importing the package
    # imports jax (its host recorder, utils/profiling.py): default it first.
    # It is only defaulted, so a TPU probe run just sets JAX_PLATFORMS=tpu
    # in the environment.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from distributed_training_with_pipeline_parallelism_tpu.analysis import (
        calibration as cal)

    specs = cal.probe_grid(args.grid, seed=args.seed)
    need = max(s.n_devices for s in specs)
    # must precede the first backend initialisation: the simulated mesh
    # needs `need` host devices. Forcing the *host* platform count is
    # harmless on a real accelerator.
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={need} "
        + os.environ.get("XLA_FLAGS", ""))

    import jax

    from distributed_training_with_pipeline_parallelism_tpu.utils.telemetry import (
        RunReport, validate_report)

    backend = jax.devices()[0].platform
    ledger = _resolve(args.ledger, cal.DEFAULT_LEDGER_PATH)
    corrections_path = _resolve(args.corrections,
                                cal.DEFAULT_CORRECTIONS_PATH)

    print(f"probe: {len(specs)} probes ({args.grid} grid, seed "
          f"{args.seed}) on backend={backend}")
    measured = []
    for i, spec in enumerate(specs):
        t_start = time.time()
        row = cal.run_probe(spec, seed=args.seed,
                            num_iterations=args.iterations,
                            warmup_iterations=args.warmup,
                            t=t_start)
        measured.append((spec, row))
        err = (row.get("rel_err") or {}).get("step_s")
        print(f"probe [{i + 1}/{len(specs)}] {spec.label}: measured "
              f"{row['measured']['step_s']:.3e}s, raw rel_err "
              f"{'n/a' if err is None else format(err, '+.3f')} "
              f"({time.time() - t_start:.1f}s)")

    raw_rows = [row for _, row in measured]
    corrections = cal.fit_corrections(raw_rows)
    for hw, cf in sorted(corrections.items()):
        print(f"probe: fitted {hw}: e_flops={cf.flops_efficiency:.4g}, "
              f"e_bw={cf.bandwidth_efficiency:.4g} over {cf.n_rows} rows "
              f"(residual rms {cf.residual_rms:.3e}s)")

    # the measurement is the expensive part — re-price the same rows
    # under the fit instead of re-running the grid
    rows = [cal.reprice_row(row, spec, corrections)
            for spec, row in measured]
    n_appended = cal.append_ledger_rows(ledger, rows)
    art = cal.correction_artifact(corrections)
    cal.save_correction_artifact(art, corrections_path)

    section = cal.calibration_section(rows, correction=corrections,
                                      ledger_path=ledger)
    report = RunReport(out_dir=args.out_dir, name="calibration_probe")
    report.set_meta(backend=backend, grid=args.grid, seed=args.seed,
                    ledger=ledger, corrections=corrections_path)
    report.count("probes", len(rows))
    report.attach_calibration(section)

    manifest = report.write()
    validate_report(manifest)

    summary = section["summary"]
    raw_err = summary["median_abs_rel_err_raw"]
    cor_err = summary["median_abs_rel_err_corrected"]
    print(f"probe: OK — {n_appended} rows -> {ledger}, corrections -> "
          f"{corrections_path}, median |rel err| raw="
          f"{'n/a' if raw_err is None else format(raw_err, '.4f')} "
          f"corrected="
          f"{'n/a' if cor_err is None else format(cor_err, '.4f')}, "
          f"report at {os.path.join(args.out_dir, 'report.json')}")

    if not args.check:
        return 0

    # --- the gate -----------------------------------------------------
    failures = []
    if raw_err is None or cor_err is None:
        failures.append("probe rows produced no raw/corrected error "
                        "medians — the grid measured nothing")
    elif not cor_err < raw_err:
        failures.append(f"corrected median |rel err| {cor_err:.4f} is not "
                        f"below raw {raw_err:.4f}")

    # artifact byte-roundtrip: load -> rebuild -> identical bytes on disk
    loaded = cal.load_correction_artifact(corrections_path)
    rebuilt = cal.correction_artifact_bytes(cal.correction_artifact(loaded))
    with open(corrections_path, "rb") as fh:
        on_disk = fh.read()
    if rebuilt != on_disk:
        failures.append("correction artifact does not byte-roundtrip")

    # our freshly appended rows must read back verbatim
    reread, bad = cal.load_ledger(ledger)
    if bad:
        failures.append(f"ledger has {len(bad)} malformed lines: {bad[:2]}")
    tail = reread[-len(rows):]
    if [cal.canonical_row_line(r) for r in tail] != \
            [cal.canonical_row_line(r) for r in rows]:
        failures.append("appended ledger rows did not read back verbatim")

    if failures:
        for f in failures:
            print(f"probe --check: {f}", file=sys.stderr)
        if backend == "cpu":
            # the CPU proxy measures the host, not the model — the gate
            # reports but does not fail (tier-1 policy; real-hardware
            # probe runs fail hard)
            print("probe --check: WARN-ONLY on cpu backend "
                  f"({len(failures)} finding(s) above)", file=sys.stderr)
            return 0
        return 1
    print("probe --check: all gates passed")
    return 0


def main(argv=None) -> int:
    args = parse_args(sys.argv[1:] if argv is None else argv)
    if args.backfill:
        return run_backfill(args)
    return run_grid(args)


if __name__ == "__main__":
    sys.exit(main())
