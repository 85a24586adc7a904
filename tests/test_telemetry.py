"""Run reports, and the names the one clock reads.

The contract under test (docs/observability.md):

- no step function holds a host callback (``tests/test_pipeline.py::
  test_no_host_callback_in_any_executor``): the device's time is read from
  the profiler's trace, and what makes a trace readable is that every
  executor form's lowering carries the ``pp/...`` scopes and the
  ``utils/profiling.py:REGIONS`` names — named scopes are metadata, so the
  check reads the debug asm;
- ``RunReport`` manifests round-trip through JSON and pass
  ``validate_report``; ``fit`` and sweeps emit the same schema, with no
  ``telemetry`` section, and a manifest from before PR 32 that carries one
  still validates.
"""

import json

import numpy as np
import pytest

import jax

import distributed_training_with_pipeline_parallelism_tpu as dtpp
from distributed_training_with_pipeline_parallelism_tpu.models import (
    transformer as tfm)
from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import (
    make_mesh)
from distributed_training_with_pipeline_parallelism_tpu.utils import profiling
from distributed_training_with_pipeline_parallelism_tpu.utils.telemetry import (
    RunReport, validate_report)

from test_pipeline import build_executor_form

CFG = dict(dim=32, n_layers=4, n_heads=4, vocab_size=64, ffn_dim=64,
           max_seq_len=16)


# ---------------------------------------------------------------------------
# RunReport schema
# ---------------------------------------------------------------------------


def test_run_report_roundtrip(tmp_path):
    report = RunReport(out_dir=str(tmp_path), name="unit")
    report.set_meta(backend="cpu", mesh_shape={"pipe": 4})
    report.count("steps", 3)
    report.gauge("final_loss", 1.25)
    with report.timer("compile_s"):
        pass
    report.event("train_log", step=0, loss=2.0)
    report.event("train_log", step=1, loss=1.5)
    manifest = report.write()

    on_disk = json.loads((tmp_path / "report.json").read_text())
    validate_report(on_disk)
    assert on_disk["schema_version"] == manifest["schema_version"]
    assert on_disk["counters"] == {"steps": 3}
    assert on_disk["gauges"]["final_loss"] == 1.25
    assert on_disk["meta"]["mesh_shape"] == {"pipe": 4}
    assert "jax_version" in on_disk["meta"]
    assert on_disk["n_events"] == 2
    # out_dir reports stream events to JSONL instead of inlining them
    assert "events" not in on_disk
    lines = [json.loads(l) for l in
             (tmp_path / "events.jsonl").read_text().splitlines()]
    assert [l["step"] for l in lines] == [0, 1]


def test_run_report_inline_events_and_jsonable():
    report = RunReport(name="unit")  # no out_dir: events inline
    report.event("metric", value=np.float32(1.5), arr=np.arange(2))
    report.gauge("np_scalar", np.int64(7))
    manifest = report.manifest()
    validate_report(manifest)
    json.dumps(manifest)  # numpy leaves must have been converted
    assert manifest["events"][0]["value"] == 1.5
    assert manifest["gauges"]["np_scalar"] == 7


def test_validate_report_rejects():
    report = RunReport(name="unit")
    manifest = report.manifest()
    validate_report(manifest)
    bad = dict(manifest, schema_version=99)
    with pytest.raises(ValueError, match="schema_version"):
        validate_report(bad)
    bad = {k: v for k, v in manifest.items() if k != "events"}
    with pytest.raises(ValueError, match="events"):
        validate_report(bad)


def _stale_telemetry(manifest):
    manifest["telemetry"] = {
        "executor": "phases", "n_events": 8,
        "timeline": [{"kind": "phase", "phase": 0, "start_tick": 0}]}


def _stale_live_and_attribution(manifest):
    from distributed_training_with_pipeline_parallelism_tpu.analysis.cost_model import (
        CPU_PROXY, cost_model_section)
    from distributed_training_with_pipeline_parallelism_tpu.analysis.memory_model import (
        memory_model_section)
    from distributed_training_with_pipeline_parallelism_tpu.parallel.schedules import (
        compile_schedule)
    cs = compile_schedule("GPipe", 2, 1, 4)
    kw = dict(batch_size=8, seq_length=16, hardware=CPU_PROXY)
    manifest["cost_model"] = dict(
        cost_model_section(cs, dtpp.ModelConfig(**CFG), **kw),
        attribution={"compute_s": "n/a"})
    manifest["memory"] = dict(
        memory_model_section(cs, dtpp.ModelConfig(**CFG), **kw),
        live={"available": "no"})


@pytest.mark.parametrize("add_stale", [_stale_telemetry,
                                       _stale_live_and_attribution])
def test_validate_report_ignores_sections_of_the_stamps(add_stale):
    """Manifests on disk from before PR 32 carry what the executors' host
    stamps fed — a ``telemetry`` section, ``memory.live``,
    ``cost_model.attribution`` — in shapes nothing checks any more: a key
    ``validate_report`` does not know is no violation."""
    manifest = RunReport(name="old").manifest()
    add_stale(manifest)
    validate_report(json.loads(json.dumps(manifest)))


# ---------------------------------------------------------------------------
# The names the trace reader rests on, in every executor form's lowering
# ---------------------------------------------------------------------------

# the model's regions: every grad program names them (train/optimizer is
# the train step's own, tests/test_profiling.py)
_MODEL_REGIONS = tuple(r for r in profiling.REGIONS if r.startswith("model/"))
_TICK_UNITS = ("pp/fwd", "pp/bwd", "pp/stage_body", "pp/embed", "pp/loss",
               "pp/ring_fwd", "pp/ring_bwd")


# (form, schedule) -> the executor's own scopes its lowering must carry
_FORM_SCOPES = {
    ("fused", "GPipe"): (),
    ("unrolled", "1F1B"): _TICK_UNITS + ("pp/tick000", "pp/tick001"),
    ("unrolled", "ZBH1"): ("pp/fwd", "pp/bwd_dgrad", "pp/wgrad",
                           "pp/tick000"),
    ("phases", "1F1B"): _TICK_UNITS + ("pp/tick_body", "pp/phase0"),
    ("scan", "1F1B"): _TICK_UNITS,
    ("phase_stored", "GPipe"): ("pp/loss",),
    ("slot_stored", "1F1B"): _TICK_UNITS + ("pp/tick000",),
}


@pytest.mark.parametrize("form,name", list(_FORM_SCOPES))
def test_named_scopes_in_lowering(form, name):
    # named scopes are trace-time metadata: they appear as MLIR locations
    # (debug info), never as ops — so the check reads the debug asm
    fn, _, args = build_executor_form(form, name)
    ir = jax.jit(fn).lower(*args).compiler_ir(dialect="stablehlo")
    asm = ir.operation.get_asm(enable_debug_info=True)
    for scope in _FORM_SCOPES[form, name] + _MODEL_REGIONS:
        assert scope in asm, f"named scope {scope} missing from lowering"
    # ... and classify reads a model region back into a phase and itself
    for region in _MODEL_REGIONS:
        assert profiling.classify(f"jit(step)/{region}/dot_general") == \
            ("forward", region)


# ---------------------------------------------------------------------------
# Plumbing: sweep rows and fit runs emit the same schema
# ---------------------------------------------------------------------------


def test_sweep_emits_report_rows(tmp_path):
    from distributed_training_with_pipeline_parallelism_tpu.utils.sweep import (
        run_one_experiment)
    metrics = run_one_experiment(4, 4, 2, "GPipe", batch_size=8,
                                 seq_length=16, num_iterations=1, dim=32,
                                 vocab_size=64, report_dir=str(tmp_path))
    assert "error" not in metrics
    lines = (tmp_path / "sweep_reports.jsonl").read_text().splitlines()
    row = json.loads(lines[-1])
    validate_report(row)
    assert "telemetry" not in row
    assert row["gauges"]["throughput"] == metrics["throughput"]
    assert row["meta"]["mesh_shape"]["pipe"] == 2
    assert "timed_loop_s" in row["timers"]


def test_fit_writes_report(tmp_path):
    from distributed_training_with_pipeline_parallelism_tpu.utils import train
    cfg = dtpp.ModelConfig(**CFG)
    mesh = make_mesh(n_pipe=2)
    sched = dtpp.ScheduleConfig(name="GPipe", n_microbatches=4)
    params = tfm.transformer_init(jax.random.key(0), cfg)
    data = train.synthetic_data(cfg, 8, 16, seed=1)
    train.fit(cfg, mesh, sched, params, data, num_steps=2, verbose=False,
              report_dir=str(tmp_path))
    manifest = json.loads((tmp_path / "report.json").read_text())
    validate_report(manifest)
    assert "telemetry" not in manifest
    assert manifest["counters"]["steps"] == 2
    assert manifest["timers"]["compile_s"] > 0
    assert manifest["meta"]["mesh_shape"]["pipe"] == 2
    assert (tmp_path / "events.jsonl").exists()
