"""Cost-model observatory: roofline accounting, trace export, sentinel.

The contract under test (docs/observability.md "Cost model & MFU"):

- the *table-exact* bubble prediction is identical — same integer idle
  count, not approximately — to the static verifier's simulated
  timeline (``table_check.check_table``), and the predicted hop count
  equals the verifier's dead-hop-elided ppermute count;
- the *weighted* bubble equals ``schedules.simulated_bubble`` under the
  resolved backward policy's weights, and the *closed-form* bubble
  equals ``schedules.analytic_bubble_fraction``;
- MFU divides by the same chip peaks ``bench.chip_peak_flops`` uses
  (the tool and the benchmark can never disagree about utilization);
- the Perfetto exporter emits valid Chrome-trace JSON: sorted
  timestamps, complete X slices for every table cell, one s->f flow
  pair per ring-hop store with unique matched ids;
- the critical-path walker's compute/comm/bubble seconds tile the
  measured window;
- the ``cost_model`` manifest section round-trips ``validate_report``;
- ``scripts/regress.py`` fails on a regression, warn-only on CPU proxy;
- ``scripts/profile_breakdown.py --from-report`` degrades gracefully on
  reports missing sections;
- ``bench.py`` measures a TPU or nothing: no TPU is a non-zero exit
  with no result, a backend that fails to come up fails the run, and an
  unknown device kind has no peak.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

import jax

import distributed_training_with_pipeline_parallelism_tpu as dtpp
from distributed_training_with_pipeline_parallelism_tpu.analysis.cost_model import (
    CPU_PROXY, TPU_PRESETS, HardwareSpec, backward_weights,
    cost_model_section, fwd_flops_per_token, hardware_spec_for,
    resolve_backward_policy, serving_cost_model_section,
    train_flops_per_token)
from distributed_training_with_pipeline_parallelism_tpu.analysis.table_check import (
    check_table)
from distributed_training_with_pipeline_parallelism_tpu.parallel.schedules import (
    analytic_bubble_fraction, compile_schedule, compress_schedule,
    simulated_bubble, table_unit_activity)
from distributed_training_with_pipeline_parallelism_tpu.utils.telemetry import (
    PHASE_END, PHASE_START, PipelineTelemetry, RunReport, critical_path,
    perfetto_trace, validate_report, write_perfetto_trace)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = dict(dim=32, n_layers=4, n_heads=4, vocab_size=64, ffn_dim=64,
           max_seq_len=16)

# (name, D, V, M) — one config per schedule family the observatory prices
GRID = [("GPipe", 4, 1, 4), ("1F1B", 4, 1, 8),
        ("Interleaved1F1B", 4, 2, 8), ("ZBH1", 4, 1, 8)]


def _load_script(name):
    """Import a scripts/ module by path (scripts/ is not a package)."""
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# Roofline accounting vs the static verifier and the closed forms
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,D,V,M", GRID)
def test_bubbles_agree_with_verifier_and_closed_form(name, D, V, M):
    cs = compile_schedule(name, D, V, M)
    cfg = dtpp.ModelConfig(**CFG)
    report = check_table(cs)
    assert report.ok
    sec = cost_model_section(cs, cfg, batch_size=8, seq_length=16,
                             hardware=CPU_PROXY, table_report=report)

    # table-exact: the SAME integer idle-cell count as the verifier, so
    # equality is exact, not approximate (the ISSUE acceptance bar)
    n_cells = cs.table.shape[0] * cs.n_devices
    assert sec["predicted"]["bubble_table_exact"] == (
        report.unit_counts["idle"] / n_cells)

    # predicted hops = the verifier's dead-hop-elided ppermute count
    assert sec["comm"]["hops"] == report.predicted_ppermutes

    # closed form delegates to the schedule library's analytic formula
    assert sec["predicted"]["bubble_closed_form"] == pytest.approx(
        analytic_bubble_fraction(name, D, V, M, cs=cs))

    # weighted bubble == the lockstep simulation under the same weights
    policy = resolve_backward_policy(cs)
    assert sec["backward_policy"] == policy
    w_b, w_w = backward_weights(policy)
    sim = simulated_bubble(cs, 1.0, w_b, w_w)
    assert sec["predicted"]["bubble_weighted"] == pytest.approx(
        sim["bubble_fraction"])


def test_policy_resolution_matches_executor_rules():
    assert resolve_backward_policy(compile_schedule("ZBH1", 4, 1, 8)) == \
        "split"
    gp = compile_schedule("GPipe", 4, 1, 4)
    assert resolve_backward_policy(gp) == "remat"
    assert resolve_backward_policy(gp, remat_backward=False) == "stored"
    assert resolve_backward_policy(gp, n_devices=1) == "stored"


def test_hardware_presets_match_bench_peaks(monkeypatch):
    import bench
    for key, peak in bench._PEAK_FLOPS.items():
        assert hardware_spec_for(key).peak_flops == peak
    assert hardware_spec_for("cpu") is CPU_PROXY
    assert hardware_spec_for("TPU v5 lite").peak_flops == 197e12
    # a device that is not in the table is an error, not a default —
    # here and in bench alike
    for unknown in ("tpu v99", ""):
        with pytest.raises(ValueError, match="no hardware preset"):
            hardware_spec_for(unknown)

    class FakeDevice:
        platform = "tpu"
        device_kind = "TPU v99"

    monkeypatch.setattr(jax, "devices", lambda *a, **kw: [FakeDevice()])
    with pytest.raises(ValueError, match="no bf16 peak on record"):
        bench.chip_peak_flops()


def test_bench_flops_delegates_to_cost_model():
    import bench
    cfg = dtpp.ModelConfig(**CFG)
    assert bench.train_flops_per_token(cfg, 16) == \
        train_flops_per_token(cfg, 16)
    assert train_flops_per_token(cfg, 16) == 3.0 * fwd_flops_per_token(
        cfg, 16)


def test_measured_block_mfu_and_report_roundtrip(tmp_path):
    cs = compile_schedule("GPipe", 4, 1, 4)
    cfg = dtpp.ModelConfig(**CFG)
    hw = HardwareSpec("unit", peak_flops=1e12, ici_bytes_per_s=1e9,
                      hbm_bytes_per_s=1e10)
    sec = cost_model_section(cs, cfg, batch_size=8, seq_length=16,
                             hardware=hw, measured_step_s=0.5)
    meas = sec["measured"]
    assert meas["tokens_per_sec"] == pytest.approx(8 * 16 / 0.5)
    assert meas["mfu"] == pytest.approx(
        sec["flops"]["model_per_step"] / (0.5 * 4 * hw.peak_flops))
    assert meas["hfu"] == pytest.approx(
        sec["flops"]["hardware_per_step"] / (0.5 * 4 * hw.peak_flops))
    # remat recomputes, and idle cells burn no FLOPs: HFU > MFU here
    assert meas["hfu"] > meas["mfu"]

    report = RunReport(out_dir=str(tmp_path), name="unit")
    report.attach_cost_model(sec)
    manifest = report.write()
    on_disk = json.loads((tmp_path / "report.json").read_text())
    validate_report(on_disk)
    assert on_disk["cost_model"]["schedule"] == "GPipe"
    assert manifest["cost_model"]["measured"]["mfu"] == meas["mfu"]


def test_validate_report_rejects_bad_cost_model():
    report = RunReport(name="unit")
    manifest = report.manifest()
    bad = dict(manifest, cost_model={"schedule": 7})
    with pytest.raises(ValueError, match="cost_model.schedule"):
        validate_report(bad)
    bad = dict(manifest, cost_model={
        "schedule": "GPipe", "hardware": {"name": "x", "peak_flops": 1.0},
        "predicted": {"step_s": 1.0, "step_s_comm_overlap": 0.9,
                      "bubble_table_exact": 0.1,
                      "bubble_closed_form": 0.1},
        "comm": {"hops": "many"}})
    with pytest.raises(ValueError, match="hops"):
        validate_report(bad)


def test_serving_section_schema():
    cfg = dtpp.ModelConfig(**CFG)
    sec = serving_cost_model_section(
        cfg, 4, 8, {"ticks": 100, "wall_s": 2.0, "tokens_out": 400},
        hardware=CPU_PROXY)
    assert sec["schedule"] == "serving_ring"
    assert sec["comm"]["hops"] == 100
    assert sec["measured"]["tokens_per_sec"] == pytest.approx(200.0)
    report = RunReport(name="serve")
    report.attach_cost_model(sec)
    validate_report(report.manifest())


# ---------------------------------------------------------------------------
# Perfetto export + critical path (satellite c): synthetic stamps over
# real compiled tables — deterministic, no jax execution
# ---------------------------------------------------------------------------


def _synthetic_telemetry(cs):
    """A phase-executor telemetry with fabricated monotonic stamps: one
    PHASE_START/PHASE_END pair per compressed phase, 1 ms per tick."""
    tel = PipelineTelemetry()
    phases = compress_schedule(cs.table)
    tel.attach(cs.table, phases, "phases")
    t = 0.0
    for j, ph in enumerate(phases):
        tel.events.append((PHASE_START, j, t))
        t += 1e-3 * ph.length
        tel.events.append((PHASE_END, j, t))
    return tel


def _expected_trace_shape(table):
    """(n_X_slices, n_flow_pairs) the exporter must emit for a table."""
    activity = table_unit_activity(table)
    n_x = int(activity.sum())  # unit cells + one idle slice per empty cell
    from distributed_training_with_pipeline_parallelism_tpu.parallel.schedules import (
        COL_STORE_B_POS_SLOT, COL_STORE_B_SLOT, COL_STORE_F_NEG_SLOT,
        COL_STORE_F_SLOT)
    cols = [COL_STORE_F_SLOT, COL_STORE_B_SLOT, COL_STORE_F_NEG_SLOT,
            COL_STORE_B_POS_SLOT]
    n_flows = int((table[1:][:, :, cols] >= 0).sum())
    return n_x, n_flows


@pytest.mark.parametrize("name,D,V,M",
                         [("GPipe", 4, 1, 4), ("Interleaved1F1B", 4, 2, 8)])
def test_perfetto_trace_schema(name, D, V, M):
    cs = compile_schedule(name, D, V, M)
    tel = _synthetic_telemetry(cs)
    trace = json.loads(json.dumps(perfetto_trace(tel)))  # JSON round-trip

    events = trace["traceEvents"]
    assert trace["displayTimeUnit"] == "ms"
    assert [e["ts"] for e in events] == sorted(e["ts"] for e in events)

    by_ph = {}
    for e in events:
        by_ph.setdefault(e["ph"], []).append(e)
    # track metadata: one process name + one thread name per device
    names = {e["args"]["name"] for e in by_ph["M"]}
    assert {f"device {d}" for d in range(D)} <= names
    # complete slices: every table cell accounted for, durations >= 0
    n_x, n_flows = _expected_trace_shape(cs.table)
    assert len(by_ph["X"]) == n_x
    assert all(e["dur"] >= 0 and 0 <= e["tid"] < D for e in by_ph["X"])
    cats = {e["cat"] for e in by_ph["X"]}
    assert "F" in cats and "B" in cats
    if V > 1:  # virtual stage visible in slice names
        assert any(" v1 " in e["name"] for e in by_ph["X"])
    # flow arrows: one s->f pair per ring-hop store, ids matched 1:1
    s_ids = sorted(e["id"] for e in by_ph.get("s", []))
    f_ids = sorted(e["id"] for e in by_ph.get("f", []))
    assert len(s_ids) == n_flows and s_ids == f_ids
    assert len(set(s_ids)) == n_flows
    assert trace["otherData"]["n_flows"] == n_flows
    assert all(e["cat"] == "ppermute" for e in by_ph.get("s", []))


def test_write_perfetto_trace_roundtrip(tmp_path):
    cs = compile_schedule("GPipe", 4, 1, 4)
    tel = _synthetic_telemetry(cs)
    path = write_perfetto_trace(tel, str(tmp_path / "trace.json"))
    trace = json.loads(open(path).read())
    assert trace["traceEvents"]


def test_critical_path_tiles_the_window():
    cs = compile_schedule("1F1B", 4, 1, 8)
    tel = _synthetic_telemetry(cs)
    cp = critical_path(tel)
    T = cs.table.shape[0]
    assert cp["n_ticks"] == T and len(cp["per_tick"]) == T
    assert {r["class"] for r in cp["per_tick"]} <= \
        {"compute", "comm", "bubble"}
    assert cp["compute_s"] + cp["comm_s"] + cp["bubble_s"] == \
        pytest.approx(cp["total_s"])
    assert cp["total_s"] == pytest.approx(1e-3 * T)
    assert 0 <= cp["straggler_device"] < 4
    # a pipeline schedule computes on some ticks — never all-bubble
    assert cp["compute_s"] > 0


def test_cost_model_attribution_from_telemetry():
    cs = compile_schedule("GPipe", 4, 1, 4)
    cfg = dtpp.ModelConfig(**CFG)
    tel = _synthetic_telemetry(cs)
    sec = cost_model_section(cs, cfg, batch_size=8, seq_length=16,
                             hardware=CPU_PROXY, telemetry=tel)
    attr = sec["attribution"]
    assert attr["n_ticks"] == cs.table.shape[0]
    # measured_step_s defaulted from the telemetry timeline
    assert sec["measured"]["step_s"] == pytest.approx(
        1e-3 * cs.table.shape[0])
    assert "bubble_measured_mean" in sec["measured"]
    report = RunReport(name="attr")
    report.attach_cost_model(sec)
    validate_report(report.manifest())


# ---------------------------------------------------------------------------
# scripts/regress.py: the perf-regression sentinel
# ---------------------------------------------------------------------------


def _sentinel_report(tmp_path, i, tps, mfu, bubble, backend="tpu"):
    manifest = {"meta": {"name": "unit_bench", "backend": backend},
                "gauges": {"throughput": tps},
                "cost_model": {"schedule": "GPipe",
                               "measured": {"mfu": mfu, "step_s": 0.1},
                               "predicted": {"bubble_table_exact": bubble,
                                             "step_s": 0.1}}}
    path = tmp_path / f"report{i}.json"
    path.write_text(json.dumps(manifest))
    return str(path)


def test_regress_sentinel(tmp_path):
    regress = _load_script("regress")
    hist = str(tmp_path / "history.jsonl")
    r0 = _sentinel_report(tmp_path, 0, 1000.0, 0.5, 0.2)
    # first run: baseline established
    assert regress.main(["--report", r0, "--history", hist]) == 0
    # steady state passes
    r1 = _sentinel_report(tmp_path, 1, 990.0, 0.5, 0.2)
    assert regress.main(["--report", r1, "--history", hist]) == 0
    # >10% tokens/sec drop on a real backend fails
    r2 = _sentinel_report(tmp_path, 2, 500.0, 0.5, 0.2)
    assert regress.main(["--report", r2, "--history", hist]) == 1
    # ... unless warn-only
    assert regress.main(["--report", r2, "--history", hist,
                         "--warn-only"]) == 0
    # bubble rising past the threshold also fails
    r3 = _sentinel_report(tmp_path, 3, 1000.0, 0.5, 0.5)
    assert regress.main(["--report", r3, "--history", hist]) == 1
    # the history carries every attempted row (append-only log)
    rows = [json.loads(l) for l in
            open(hist).read().splitlines()]
    assert len(rows) == 5
    assert all(r["name"] == "unit_bench" for r in rows)


def test_regress_cpu_proxy_is_warn_only(tmp_path):
    regress = _load_script("regress")
    hist = str(tmp_path / "history.jsonl")
    r0 = _sentinel_report(tmp_path, 0, 1000.0, 0.5, 0.2, backend="cpu")
    assert regress.main(["--report", r0, "--history", hist]) == 0
    r1 = _sentinel_report(tmp_path, 1, 10.0, 0.01, 0.9, backend="cpu")
    assert regress.main(["--report", r1, "--history", hist]) == 0


def test_regress_missing_report(tmp_path):
    regress = _load_script("regress")
    hist = str(tmp_path / "history.jsonl")
    rc = regress.main(["--report", str(tmp_path / "nope.json"),
                       "--history", hist])
    assert rc == 2
    assert regress.main(["--report", str(tmp_path / "nope.json"),
                         "--history", hist, "--warn-only"]) == 0


# ---------------------------------------------------------------------------
# scripts/profile_breakdown.py --from-report degrades gracefully
# (satellite b): missing sections are a message, not a traceback
# ---------------------------------------------------------------------------


def test_profile_breakdown_graceful_degradation(capsys):
    pb = _load_script("profile_breakdown")
    with pytest.raises(SystemExit, match="neither"):
        pb.report_breakdown({"meta": {"name": "empty"}})
    # partial telemetry (no timeline, no stage_breakdown): prints a note
    pb.report_breakdown({"meta": {"name": "p"},
                         "telemetry": {"executor": "phases"}})
    assert "no measured timeline" in capsys.readouterr().out
    # cost_model only (e.g. a sweep row without instrumented stamps)
    cs = compile_schedule("GPipe", 4, 1, 4)
    sec = cost_model_section(cs, dtpp.ModelConfig(**CFG), batch_size=8,
                             seq_length=16, hardware=CPU_PROXY)
    pb.report_breakdown({"meta": {"name": "cm"}, "cost_model": sec})
    out = capsys.readouterr().out
    assert "cost model: GPipe" in out and "bubble" in out


def test_profile_breakdown_renders_full_report(tmp_path, capsys):
    cs = compile_schedule("1F1B", 4, 1, 8)
    cfg = dtpp.ModelConfig(**CFG)
    tel = _synthetic_telemetry(cs)
    report = RunReport(out_dir=str(tmp_path), name="full")
    report.set_meta(backend="cpu")
    report.attach_telemetry(tel)
    report.attach_cost_model(cost_model_section(
        cs, cfg, batch_size=8, seq_length=16, hardware=CPU_PROXY,
        telemetry=tel))
    report.write()
    pb = _load_script("profile_breakdown")
    pb.report_breakdown(json.loads((tmp_path / "report.json").read_text()))
    out = capsys.readouterr().out
    assert "critical path" in out and "MFU" in out


# ---------------------------------------------------------------------------
# bench measures a TPU or nothing: no CPU fallback, no swallowed init error
# ---------------------------------------------------------------------------


def test_bench_without_tpu_exits_nonzero_with_no_result(capsys):
    """The suite runs on the CPU backend — exactly the case that used to
    switch to a proxy headline and exit 0."""
    import bench
    assert jax.devices()[0].platform == "cpu"
    for mode in (bench.run, bench.run_serve):
        with pytest.raises(SystemExit) as exit_info:
            mode()
        assert exit_info.value.code not in (0, None)
        assert "needs a TPU" in str(exit_info.value.code)
    assert capsys.readouterr().out == ""  # no result line


def test_bench_backend_init_errors_reraise(monkeypatch):
    """A backend that fails to come up fails the run, whatever it says —
    UNAVAILABLE used to be retried and then answered from the CPU."""
    import bench

    for msg in ("UNAVAILABLE: TPU backend setup/compile error (transient)",
                "something unrelated exploded"):
        def broken_devices(*a, msg=msg, **kw):
            raise RuntimeError(msg)

        monkeypatch.setattr(jax, "devices", broken_devices)
        with pytest.raises(RuntimeError, match=msg.split(":")[0]):
            bench.run()
