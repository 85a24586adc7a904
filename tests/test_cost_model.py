"""Cost-model observatory: roofline accounting, sentinel.

The contract under test (docs/observability.md "Cost model & MFU"):

- the *table-exact* bubble prediction is identical — same integer idle
  count, not approximately — to the static verifier's simulated
  timeline (``table_check.check_table``), and the predicted hop count
  equals the verifier's dead-hop-elided ppermute count;
- the *weighted* bubble equals ``schedules.simulated_bubble`` under the
  resolved backward policy's weights, and the *closed-form* bubble
  equals ``schedules.analytic_bubble_fraction``;
- a device that is not in the table of peaks is an error, not a default;
- the ``cost_model`` manifest section round-trips ``validate_report``;
- the requests/dynamics Perfetto writer emits loadable Chrome-trace JSON;
- ``scripts/regress.py`` fails on a regression, warn-only on CPU proxy.
"""

import importlib.util
import json
import os

import pytest


import distributed_training_with_pipeline_parallelism_tpu as dtpp
from distributed_training_with_pipeline_parallelism_tpu.analysis.cost_model import (
    CPU_PROXY, TPU_PRESETS, HardwareSpec, backward_weights,
    cost_model_section, fwd_flops_per_token, hardware_spec_for,
    resolve_backward_policy, serving_cost_model_section,
    train_flops_per_token)
from distributed_training_with_pipeline_parallelism_tpu.analysis.table_check import (
    check_table)
from distributed_training_with_pipeline_parallelism_tpu.parallel.schedules import (
    analytic_bubble_fraction, compile_schedule, simulated_bubble)
from distributed_training_with_pipeline_parallelism_tpu.utils.telemetry import (
    RunReport, validate_report, write_perfetto_trace)

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


def test_hardware_presets():
    for key, spec in TPU_PRESETS.items():
        assert hardware_spec_for(key) is spec
    assert hardware_spec_for("cpu") is CPU_PROXY
    assert hardware_spec_for("TPU v5 lite").peak_flops == 197e12
    # a device that is not in the table is an error, not a default
    for unknown in ("tpu v99", ""):
        with pytest.raises(ValueError, match="no hardware preset"):
            hardware_spec_for(unknown)


def test_train_flops_is_three_forwards():
    cfg = dtpp.ModelConfig(**CFG)
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
# Perfetto export: the requests and dynamics tracks
# ---------------------------------------------------------------------------


def test_write_perfetto_trace_roundtrip(tmp_path):
    serving = [
        {"kind": "serve_admit", "t": 10.0, "rid": 0, "slot": 1, "tick": 2,
         "prompt_len": 4, "budget": 8},
        {"kind": "serve_finish", "t": 10.5, "rid": 0, "tick": 9,
         "n_tokens": 8, "ttft_ticks": 3}]
    dynamics = [{"kind": "dynamics", "t": 11.0, "grad_norm": 1.5,
                 "grad_norm_per_stage": [1.0, 0.5]}]
    path = write_perfetto_trace(str(tmp_path / "trace.json"),
                                serving_events=serving,
                                dynamics_events=dynamics)
    trace = json.loads(open(path).read())
    by_ph = {}
    for e in trace["traceEvents"]:
        by_ph.setdefault(e["ph"], []).append(e)
    # one async slice per request, closed by its finish row
    (begin,), (end,) = by_ph["b"], by_ph["e"]
    assert begin["id"] == end["id"] == 0 and begin["tid"] == 1
    assert end["ts"] - begin["ts"] == pytest.approx(0.5e6)
    assert begin["args"]["ttft_ticks"] == 3
    # one counter per global norm and per stage
    assert sorted(e["name"] for e in by_ph["C"]) == [
        "grad_norm", "grad_norm stage 0", "grad_norm stage 1"]


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
