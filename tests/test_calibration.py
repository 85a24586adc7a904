"""Calibration observatory: probes, ledger, corrections, guards.

The contract under test (docs/observability.md §9):

- the probe grid is a pure function of (name, seed): same seed is
  byte-identical, the smoke grid spans >= 3 schedule families, all
  three backward policies, and both comm_overlap modes;
- the deterministic least-squares fit recovers known synthetic
  (flops, bandwidth) efficiencies exactly, and falls back to a
  flops-only fit (e_bw = 1) when the comm column is degenerate;
- re-pricing a compiled table under a positive correction preserves
  the overlap sandwich (overlapped <= comm_overlap <= serial);
- the ledger appends canonical one-line JSON rows that read back
  verbatim; malformed lines are *counted*, never silently dropped,
  and ``strict=True`` raises a located error;
- the correction artifact byte-roundtrips (build -> save -> load ->
  rebuild is the identity on bytes) and its fingerprint rejects any
  payload tamper;
- ``scripts/regress.py`` guards ``abs_rel_err`` and
  ``calib_abs_err_corrected``: a quiet growth in prediction error
  fails on a real backend, warns on cpu, and history rows from before
  the calibration era (missing keys) establish no prior;
- an end-to-end CPU-proxy probe produces a row whose ``calibration``
  RunReport section survives ``validate_report``, and a same-run fit
  reprices it to a strictly smaller |rel err|;
- the ``raw-step-timing`` lint rule flags raw host-clock calls outside
  the sanctioned timing surfaces and stays silent inside them.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

from distributed_training_with_pipeline_parallelism_tpu.analysis import (
    calibration as cal,
)
from distributed_training_with_pipeline_parallelism_tpu.analysis.cli import (
    run_calibration_checks,
)
from distributed_training_with_pipeline_parallelism_tpu.analysis.cost_model import (
    cost_model_section,
)
from distributed_training_with_pipeline_parallelism_tpu.analysis.repo_lint import (
    lint_source,
)
from distributed_training_with_pipeline_parallelism_tpu.parallel.schedules import (
    compile_schedule,
)
from distributed_training_with_pipeline_parallelism_tpu.utils.config import (
    ModelConfig,
)
from distributed_training_with_pipeline_parallelism_tpu.utils.telemetry import (
    RunReport, validate_report,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_script(name):
    """Import a scripts/ module by path (scripts/ is not a package)."""
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _synthetic_row(i=0, *, hardware="syn_hw", compute_s=1e-3, comm_s=1e-4,
                   measured_step_s=0.05, **over):
    row = {
        "schema_version": cal.CALIBRATION_SCHEMA_VERSION,
        "kind": cal.LEDGER_KIND, "source": "synthetic", "t": 0.0,
        "name": f"syn{i}", "backend": "cpu", "hardware": hardware,
        "cpu_proxy": True, "schedule": "GPipe",
        "schedule_family": "GPipe", "backward_policy": "remat",
        "comm_overlap": "none", "n_devices": 2, "n_virtual": 1,
        "n_microbatches": 4, "batch_size": 8, "seq_length": 16,
        "predicted": {"compute_s": compute_s, "comm_s": comm_s,
                      "step_s": compute_s + comm_s},
        "measured": {"step_s": measured_step_s},
        "rel_err": {"step_s": cal.signed_rel_err(compute_s + comm_s,
                                                 measured_step_s)},
        "corrected": None,
    }
    row.update(over)
    return cal.validate_ledger_row(row)


# ---------------------------------------------------------------------------
# Probe grid: seeded determinism + coverage contract
# ---------------------------------------------------------------------------


def test_probe_grid_deterministic():
    a, b = cal.probe_grid(seed=0), cal.probe_grid(seed=0)
    assert a == b
    assert [s.to_dict() for s in a] == [s.to_dict() for s in b]


def test_probe_grid_seed_permutes_not_reshapes():
    a, b = cal.probe_grid(seed=0), cal.probe_grid(seed=7)
    # different seed may reorder, never changes the set of configs
    key = lambda s: json.dumps(s.to_dict(), sort_keys=True)
    assert sorted(map(key, a)) == sorted(map(key, b))


def test_probe_grid_coverage():
    grid = cal.probe_grid("smoke", seed=0)
    assert len(grid) >= 8
    families = {cal.schedule_family(s.schedule) for s in grid}
    assert {"GPipe", "1F1B", "Interleaved"} <= families
    policies = {cal._policy_of(s.schedule, s.remat_backward, s.n_devices)
                for s in grid}
    assert policies == {"stored", "remat", "split"}
    assert {s.comm_overlap for s in grid} == {"none", "ring"}


def test_probe_grid_unknown_name():
    with pytest.raises(cal.CalibrationError):
        cal.probe_grid("nope")


# ---------------------------------------------------------------------------
# Least-squares correction fit
# ---------------------------------------------------------------------------


def test_fit_recovers_synthetic_efficiencies():
    e_f, e_b = 0.01, 0.5
    rows = []
    for i, (c, k) in enumerate(((1e-3, 1e-4), (2e-3, 5e-4),
                                (3e-3, 2e-4), (5e-3, 8e-4))):
        rows.append(_synthetic_row(i, compute_s=c, comm_s=k,
                                   measured_step_s=c / e_f + k / e_b))
    fit = cal.fit_correction(rows, "syn_hw")
    assert fit is not None
    assert fit.flops_efficiency == pytest.approx(e_f, abs=1e-12)
    assert fit.bandwidth_efficiency == pytest.approx(e_b, abs=1e-12)
    assert fit.n_rows == 4
    assert fit.residual_rms == pytest.approx(0.0, abs=1e-12)


def test_fit_is_row_order_invariant():
    rows = [_synthetic_row(i, compute_s=c, comm_s=k,
                           measured_step_s=c / 0.02 + k / 0.4)
            for i, (c, k) in enumerate(((1e-3, 1e-4), (2e-3, 5e-4),
                                        (3e-3, 2e-4)))]
    assert cal.fit_correction(rows, "syn_hw") == \
        cal.fit_correction(list(reversed(rows)), "syn_hw")


def test_fit_flops_only_fallback_on_degenerate_comm():
    e_f = 0.05
    rows = [_synthetic_row(i, compute_s=c, comm_s=0.0,
                           measured_step_s=c / e_f)
            for i, c in enumerate((1e-3, 2e-3, 4e-3))]
    fit = cal.fit_correction(rows, "syn_hw")
    assert fit.bandwidth_efficiency == 1.0
    assert fit.flops_efficiency == pytest.approx(e_f, abs=1e-12)


def test_fit_none_without_measurements():
    rows = [_synthetic_row(0, measured=None, rel_err=None)]
    assert cal.fit_correction(rows, "syn_hw") is None
    assert cal.fit_correction([], "syn_hw") is None


def test_fit_corrections_keyed_by_hardware():
    rows = [_synthetic_row(0, hardware="hw_a"),
            _synthetic_row(1, hardware="hw_b")]
    fits = cal.fit_corrections(rows)
    assert sorted(fits) == ["hw_a", "hw_b"]
    assert all(f.n_rows == 1 for f in fits.values())


# ---------------------------------------------------------------------------
# Corrected pricing preserves the overlap sandwich
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,D,V,M", [("GPipe", 2, 1, 4),
                                        ("1F1B", 4, 1, 8),
                                        ("ZBH1", 4, 1, 8)])
def test_corrected_sandwich(name, D, V, M):
    cfg = ModelConfig(dim=16, n_layers=4, n_heads=2, vocab_size=64,
                      ffn_dim=32, max_seq_len=16)
    cs = compile_schedule(name, D, V, M)
    fit = cal.CorrectionFactors(hardware="any", flops_efficiency=0.02,
                                bandwidth_efficiency=0.5, n_rows=4,
                                residual_rms=0.0)
    sec = cost_model_section(cs, cfg, batch_size=8, seq_length=16,
                             correction=fit)
    corr = sec["predicted"]["corrected"]
    assert corr["step_s_overlapped"] \
        <= corr["step_s_comm_overlap"] + 1e-12 \
        <= corr["step_s"] + 1e-12
    # de-rating by < 1 efficiencies can only slow the prediction down
    assert corr["step_s"] > sec["predicted"]["step_s"]


# ---------------------------------------------------------------------------
# Ledger: canonical rows, verbatim roundtrip, located rejection
# ---------------------------------------------------------------------------


def test_ledger_roundtrip_verbatim(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    rows = [_synthetic_row(i) for i in range(3)]
    assert cal.append_ledger_rows(path, rows) == 3
    loaded, bad = cal.load_ledger(path)
    assert not bad
    assert [cal.canonical_row_line(r) for r in loaded] == \
        [cal.canonical_row_line(r) for r in rows]
    # append is append-only
    cal.append_ledger_rows(path, [_synthetic_row(9)])
    loaded2, _ = cal.load_ledger(path)
    assert len(loaded2) == 4


def test_ledger_malformed_lines_counted_not_dropped(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    cal.append_ledger_rows(path, [_synthetic_row(0)])
    with open(path, "a") as fh:
        fh.write("{not json\n")
        fh.write(json.dumps({"schema_version": 1}) + "\n")
    rows, bad = cal.load_ledger(path)
    assert len(rows) == 1
    assert len(bad) == 2
    assert all(f"{path}:" in msg for msg in bad)
    with pytest.raises(cal.CalibrationError):
        cal.load_ledger(path, strict=True)


def test_validate_row_rejects_with_location():
    with pytest.raises(cal.CalibrationError, match="missing required"):
        cal.validate_ledger_row({}, "here")
    with pytest.raises(cal.CalibrationError, match="kind"):
        cal.validate_ledger_row(dict(_synthetic_row(0), kind="x"))
    with pytest.raises(cal.CalibrationError, match="step_s"):
        cal.validate_ledger_row(dict(_synthetic_row(0),
                                     predicted={"compute_s": 1.0}))


def test_deterministic_fields_excludes_measured_side():
    row = _synthetic_row(0)
    det = cal.deterministic_fields(row)
    for key in ("t", "measured", "rel_err", "corrected"):
        assert key not in det
    assert det["predicted"] == row["predicted"]


# ---------------------------------------------------------------------------
# Correction artifact: byte determinism + tamper rejection
# ---------------------------------------------------------------------------


def test_artifact_roundtrip_byte_deterministic(tmp_path):
    rows = [_synthetic_row(i, compute_s=c, comm_s=k,
                           measured_step_s=c / 0.02 + k / 0.4)
            for i, (c, k) in enumerate(((1e-3, 1e-4), (2e-3, 5e-4),
                                        (3e-3, 2e-4)))]
    art = cal.correction_artifact(cal.fit_corrections(rows))
    path = str(tmp_path / "corrections.json")
    cal.save_correction_artifact(art, path)
    loaded = cal.load_correction_artifact(path)
    rebuilt = cal.correction_artifact(loaded)
    assert cal.correction_artifact_bytes(rebuilt) == \
        open(path, "rb").read()


def test_artifact_rejects_tamper(tmp_path):
    art = cal.correction_artifact(cal.fit_corrections(
        [_synthetic_row(0)]))
    bad = dict(art)
    bad["corrections"] = {
        hw: dict(blob, flops_efficiency=1.0)
        for hw, blob in art["corrections"].items()}
    with pytest.raises(cal.CalibrationError, match="fingerprint"):
        cal.load_correction_artifact(bad)
    path = str(tmp_path / "corrupt.json")
    with open(path, "w") as fh:
        json.dump(bad, fh)
    with pytest.raises(cal.CalibrationError, match="fingerprint"):
        cal.load_correction_artifact(path)
    with pytest.raises(cal.CalibrationError, match="unreadable"):
        cal.load_correction_artifact(str(tmp_path / "missing.json"))


def test_maybe_load_default_corrections_env(tmp_path, monkeypatch):
    art = cal.correction_artifact(cal.fit_corrections([_synthetic_row(0)]))
    path = str(tmp_path / "c.json")
    cal.save_correction_artifact(art, path)
    monkeypatch.setenv(cal.CORRECTIONS_ENV, path)
    loaded = cal.maybe_load_default_corrections()
    assert loaded and "syn_hw" in loaded
    # a broken artifact degrades to None, never raises into the run
    (tmp_path / "c.json").write_text("{broken")
    assert cal.maybe_load_default_corrections() is None


# ---------------------------------------------------------------------------
# Calibration section: schema roundtrip through validate_report
# ---------------------------------------------------------------------------


def test_calibration_section_validates(tmp_path):
    rows = [_synthetic_row(i) for i in range(3)]
    section = cal.calibration_section(
        rows, correction=cal.fit_corrections(rows), ledger_path="x.jsonl")
    report = RunReport(str(tmp_path), name="unit")
    report.attach_calibration(section)
    validate_report(report.manifest())
    assert section["n_rows"] == 3
    assert section["summary"]["median_abs_rel_err_raw"] is not None
    assert "cpu|GPipe|remat" in section["summary"]["groups"]


def test_validate_report_rejects_malformed_calibration(tmp_path):
    rows = [_synthetic_row(0)]
    report = RunReport(str(tmp_path), name="unit")
    report.attach_calibration(cal.calibration_section(rows))
    manifest = report.manifest()
    manifest["calibration"]["n_rows"] = 99
    with pytest.raises(ValueError, match="n_rows"):
        validate_report(manifest)
    manifest["calibration"]["n_rows"] = 1
    del manifest["calibration"]["rows"][0]["rel_err"]
    with pytest.raises(ValueError, match="rel_err"):
        validate_report(manifest)


# ---------------------------------------------------------------------------
# Backfill: history rows become ledger rows
# ---------------------------------------------------------------------------


def test_backfill_from_history_row():
    hrow = {"t": 1.0, "name": "bench", "backend": "cpu",
            "schedule": "1F1B", "predicted_step_s": 0.01,
            "measured_step_s": 0.012, "tokens_per_sec": 1000.0}
    row = cal.backfill_row_from_history(hrow, path="history.jsonl")
    assert row["schedule_family"] == "1F1B"
    assert row["rel_err"]["step_s"] == pytest.approx(
        (0.01 - 0.012) / 0.012)
    # rows with a measurement but no prediction keep predicted: null
    row2 = cal.backfill_row_from_history(
        dict(hrow, predicted_step_s=None), path="history.jsonl")
    assert row2["predicted"] is None
    assert row2["measured"]["step_s"] == pytest.approx(0.012)


def test_committed_ledger_reads_with_its_stale_comm_axis():
    """``results/calibration.jsonl`` holds probe rows from before PR 32,
    whose ``measured.comm_s`` was read off the executors' host stamps. The
    field is optional on read and no malformed line is counted; a row built
    today never writes it."""
    rows, bad = cal.load_ledger(os.path.join(_REPO, "results",
                                             "calibration.jsonl"))
    assert not bad
    stale = [r for r in rows if "comm_s" in (r["measured"] or {})]
    assert stale and all(r["source"] == "probe" for r in stale)
    # such a row still summarises and fits
    cal.calibration_section(rows)
    cal.fit_corrections(rows)

    cm = cost_model_section(
        compile_schedule("1F1B", 2, 1, 2), ModelConfig(**cal._PROBE_MODEL),
        batch_size=8, seq_length=16, measured_step_s=0.01)
    row = cal.row_from_cost_model(cm, source="probe", name="now",
                                  backend="cpu")
    assert sorted(row["measured"]) == ["step_s", "tokens_per_sec"]
    assert "comm_s" not in row["rel_err"]


# ---------------------------------------------------------------------------
# scripts/regress.py: the model-trust guard
# ---------------------------------------------------------------------------


def _calib_report(tmp_path, i, abs_err_corrected, *, backend="tpu",
                  rel_err=None):
    manifest = {"meta": {"name": "unit_probe", "backend": backend},
                "cost_model": {"schedule": "GPipe",
                               "predicted": {"step_s": 0.01},
                               "measured": {"step_s": 0.01,
                                            "rel_err": rel_err}},
                "calibration": {"summary": {
                    "median_abs_rel_err_raw": 0.9,
                    "median_abs_rel_err_corrected": abs_err_corrected}}}
    path = tmp_path / f"calib{i}.json"
    path.write_text(json.dumps(manifest))
    return str(path)


def test_regress_guards_corrected_error(tmp_path):
    regress = _load_script("regress")
    hist = str(tmp_path / "history.jsonl")
    # baseline, then steady state
    assert regress.main(["--report",
                         _calib_report(tmp_path, 0, 0.05, rel_err=-0.04),
                         "--history", hist]) == 0
    assert regress.main(["--report",
                         _calib_report(tmp_path, 1, 0.052, rel_err=-0.04),
                         "--history", hist]) == 0
    # corrected error quietly doubling fails on a real backend
    assert regress.main(["--report",
                         _calib_report(tmp_path, 2, 0.12, rel_err=-0.04),
                         "--history", hist]) == 1
    # |rel err| growth on the run's own cost model also fails
    assert regress.main(["--report",
                         _calib_report(tmp_path, 3, 0.05, rel_err=-0.5),
                         "--history", hist]) == 1
    # cpu backends only warn
    assert regress.main(["--report",
                         _calib_report(tmp_path, 4, 0.5, backend="cpu",
                                       rel_err=-0.9),
                         "--history", hist]) == 0
    rows = [json.loads(l) for l in open(hist).read().splitlines()]
    assert rows[0]["calib_abs_err_corrected"] == pytest.approx(0.05)
    assert rows[0]["calib_abs_err_raw"] == pytest.approx(0.9)
    assert rows[0]["abs_rel_err"] == pytest.approx(0.04)


def test_regress_skips_precalibration_history(tmp_path):
    regress = _load_script("regress")
    hist = tmp_path / "history.jsonl"
    # a pre-calibration history row for the same group: no calib keys
    hist.write_text(json.dumps(
        {"name": "unit_probe", "backend": "tpu", "schedule": "GPipe",
         "tokens_per_sec": 1000.0}) + "\n")
    # new-era report with large corrected error: no prior -> no gate
    assert regress.main(["--report",
                         _calib_report(tmp_path, 0, 0.9),
                         "--history", str(hist)]) == 0


# ---------------------------------------------------------------------------
# Host-side structural pass (scripts/check.py --calibration)
# ---------------------------------------------------------------------------


def test_run_calibration_checks_all_green():
    out = run_calibration_checks()
    assert out["ok"], [c for c in out["cases"] if not c["ok"]]
    assert out["n_bad"] == 0
    assert {c["case"] for c in out["cases"]} >= {
        "grid_deterministic", "grid_coverage", "fit_recovers_synthetic",
        "artifact_roundtrip_and_tamper", "corrected_sandwich",
        "malformed_rows_rejected"}


# ---------------------------------------------------------------------------
# raw-step-timing lint rule
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("call", ["time.time()", "time.perf_counter()",
                                  "time.monotonic()",
                                  "time.perf_counter_ns()"])
def test_lint_flags_raw_timing_outside_allowlist(call):
    src = f"import time\nt0 = {call}\n"
    findings = lint_source("x.py", src, package_relpath="utils/data.py")
    assert any(f.rule == "raw-step-timing" for f in findings)


@pytest.mark.parametrize("rel", ["utils/metrics.py", "utils/telemetry.py",
                                 "analysis/calibration.py",
                                 "serving/engine.py"])
def test_lint_allows_sanctioned_timing_surfaces(rel):
    src = "import time\nt0 = time.perf_counter()\n"
    findings = lint_source("x.py", src, package_relpath=rel)
    assert not [f for f in findings if f.rule == "raw-step-timing"]


def test_lint_ignores_non_call_mentions():
    src = "TIMERS = ['time.perf_counter']\nx = 'time.time'\n"
    findings = lint_source("x.py", src, package_relpath="utils/data.py")
    assert not [f for f in findings if f.rule == "raw-step-timing"]


# ---------------------------------------------------------------------------
# End-to-end CPU-proxy probe
# ---------------------------------------------------------------------------


def test_probe_end_to_end(tmp_path):
    spec = cal.ProbeSpec(schedule="1F1B", n_devices=2, n_virtual=1,
                         n_microbatches=2)
    row = cal.run_probe(spec, seed=0, num_iterations=2,
                        warmup_iterations=1)
    cal.validate_ledger_row(row)
    assert row["source"] == "probe"
    assert row["measured"]["step_s"] > 0
    assert row["rel_err"]["step_s"] is not None

    # same-run fit reprices the row to a strictly smaller |rel err|
    fits = cal.fit_corrections([row])
    assert row["hardware"] in fits
    corrected = cal.reprice_row(row, spec, fits[row["hardware"]])
    assert corrected["measured"]["step_s"] == row["measured"]["step_s"]
    assert abs(corrected["corrected"]["rel_err_step_s"]) < \
        abs(row["rel_err"]["step_s"])

    # determinism contract: everything but the measured fields is a pure
    # function of (spec, seed)
    assert cal.deterministic_fields(row)["predicted"]["step_s"] == \
        pytest.approx(row["predicted"]["step_s"])

    # the section built from the measured rows survives validate_report
    section = cal.calibration_section([row, corrected], correction=fits)
    report = RunReport(str(tmp_path), name="probe_e2e")
    report.attach_calibration(section)
    report.write()
    validate_report(json.load(open(os.path.join(str(tmp_path),
                                                "report.json"))))
