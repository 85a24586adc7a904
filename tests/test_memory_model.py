"""Memory observatory: analytic and compiled HBM accounting.

The contract under test (docs/observability.md "Memory observatory"):

- the *integer identity*: per-device analytic activation/grad bytes are
  exactly the static verifier's slot live peaks times one slot's slab
  bytes, for every schedule family and every backward policy — the tick
  executors bank one ``[mb, seq, dim]`` boundary slab per slot, nothing
  else, so this is equality, not tolerance;
- the backward policy enters only through the separately-reported
  stored-residual estimate: 'stored' prices per-layer residuals per
  in-flight microbatch, 'remat'/'split' keep none;
- XLA's AOT ``memory_analysis()`` argument bytes reconcile with the
  analytic per-device params + inputs (exact on the unpadded CPU-mesh
  layout; documented tolerance 10% for padded real-chip layouts);
- the ``memory`` RunReport section round-trips ``validate_report`` and
  malformed sections are rejected;
- the default step traces with zero host callbacks;
- the sweep's OOM preflight prices a config *before* compiling and
  returns a ``skip_reason="predicted_oom"`` row instead of crashing;
- ``schedule_search`` accepts bytes-denominated budgets and resolves
  them to the same winner as the equivalent slot budget;
- the Perfetto exporter emits a per-request async-span track;
- ``scripts/regress.py`` guards peak HBM per (name, backend, schedule).
"""

import importlib.util
import os
import types

import pytest

import jax

import distributed_training_with_pipeline_parallelism_tpu as dtpp
from distributed_training_with_pipeline_parallelism_tpu.analysis.cli import (
    default_grid, run_memory_checks)
from distributed_training_with_pipeline_parallelism_tpu.analysis.cost_model import (
    CPU_PROXY, HardwareSpec, dtype_bytes, resolve_backward_policy)
from distributed_training_with_pipeline_parallelism_tpu.analysis.memory_model import (
    activation_slot_bytes, memory_model_section, oom_preflight, params_bytes,
    reconcile_memory, serving_memory_section)
from distributed_training_with_pipeline_parallelism_tpu.analysis.table_check import (
    check_table)
from distributed_training_with_pipeline_parallelism_tpu.parallel.schedules import (
    ScheduleError, compile_schedule)
from distributed_training_with_pipeline_parallelism_tpu.utils.telemetry import (
    RunReport, perfetto_request_events, validate_report)

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
# The integer identity: analytic bytes == live peaks x slot bytes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,D,V,M", GRID)
def test_integer_identity_per_schedule_family(name, D, V, M):
    cfg = dtpp.ModelConfig(**CFG)
    cs = compile_schedule(name, D, V, M)
    report = check_table(cs)
    batch, seq = 8, 16
    sec = memory_model_section(cs, cfg, batch_size=batch, seq_length=seq,
                               table_report=report)
    slot_b = sec["analytic"]["act_slot_bytes"]
    # the slab is one microbatch's stage-boundary activation
    assert slot_b == (batch // M) * seq * cfg.dim * dtype_bytes(cfg.dtype)
    assert slot_b == activation_slot_bytes(cfg, batch, seq, M)
    assert len(sec["analytic"]["per_device"]) == D
    for pd in sec["analytic"]["per_device"]:
        d = pd["device"]
        assert pd["act_bytes"] == report.act_live_peak[d] * slot_b
        assert pd["grad_bytes"] == report.grad_live_peak[d] * slot_b
        assert isinstance(pd["act_bytes"], int)
        assert isinstance(pd["grad_bytes"], int)
    assert sec["analytic"]["activation_peak_bytes"] == max(
        (report.act_live_peak[d] + report.grad_live_peak[d]) * slot_b
        for d in range(D))


@pytest.mark.parametrize("remat_backward,name",
                         [(None, "1F1B"),    # resolves 'remat' at D=4
                          (True, "1F1B"),    # explicit 'remat'
                          (False, "1F1B"),   # 'stored'
                          (None, "ZBH1")])   # 'split'
def test_integer_identity_per_backward_policy(remat_backward, name):
    cfg = dtpp.ModelConfig(**CFG)
    cs = compile_schedule(name, 4, 1, 8)
    report = check_table(cs)
    sec = memory_model_section(cs, cfg, batch_size=8, seq_length=16,
                               remat_backward=remat_backward,
                               table_report=report)
    policy = resolve_backward_policy(cs, remat_backward)
    assert sec["backward_policy"] == policy
    slot_b = sec["analytic"]["act_slot_bytes"]
    for pd in sec["analytic"]["per_device"]:
        d = pd["device"]
        # the identity is policy-independent...
        assert pd["act_bytes"] == report.act_live_peak[d] * slot_b
        assert pd["grad_bytes"] == report.grad_live_peak[d] * slot_b
        # ...the policy enters only via the stored-residual estimate
        if policy == "stored":
            assert pd["stored_residual_bytes"] == pytest.approx(
                report.act_live_peak[d]
                * sec["analytic"]["stored_residual_bytes_per_mb"])
            if report.act_live_peak[d]:
                assert pd["stored_residual_bytes"] > 0
        else:
            assert pd["stored_residual_bytes"] == 0.0
    if policy == "stored":
        tokens_mb = (8 // cs.n_microbatches) * 16
        assert sec["analytic"]["stored_residual_bytes_per_mb"] == (
            cfg.n_layers / cs.n_stages * tokens_mb
            * (2 * cfg.dim + cfg.ffn_dim) * dtype_bytes(cfg.dtype))


def test_full_grid_identity_holds():
    # the acceptance pin: every entry of the static-analysis grid (the
    # same 44 the table verifier walks) satisfies the identity
    out = run_memory_checks()
    assert out["ok"], [r for r in out["reports"] if not r["ok"]]
    assert out["n_checked"] == len(default_grid()) + 6  # +forward/serving


def test_optimizer_and_params_accounting():
    cfg = dtpp.ModelConfig(**CFG)
    cs = compile_schedule("1F1B", 4, 1, 8)
    sec0 = memory_model_section(cs, cfg, batch_size=8, seq_length=16)
    sec2 = memory_model_section(cs, cfg, batch_size=8, seq_length=16,
                                optimizer_slots=2)
    pb = params_bytes(cfg, 4)
    assert sec0["analytic"]["params_per_device_bytes"] == pb["per_device_bytes"]
    # two fp32 moments per parameter, sharded like the params
    dev0 = sec2["analytic"]["per_device"][0]
    assert dev0["opt_state_bytes"] == 2 * pb["n_params"] * 4.0 / 4
    assert sec2["analytic"]["peak_bytes"] > sec0["analytic"]["peak_bytes"]


# ---------------------------------------------------------------------------
# Compiled reconciliation on the CPU mesh (the one compile in this file)
# ---------------------------------------------------------------------------


def test_compiled_reconciles_with_analytic():
    import jax.numpy as jnp

    from distributed_training_with_pipeline_parallelism_tpu.models import (
        transformer as tfm)
    from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import (
        make_mesh)
    from distributed_training_with_pipeline_parallelism_tpu.parallel.pipeline import (
        aot_memory_analysis, make_pipeline_step)

    cfg = dtpp.ModelConfig(**CFG)
    mesh = make_mesh(n_pipe=4)
    sched = dtpp.ScheduleConfig(name="1F1B", n_microbatches=8)
    step = make_pipeline_step(cfg, mesh, sched, unroll_ticks="phases")
    params = tfm.transformer_init(jax.random.key(0), cfg)
    tokens = jnp.zeros((8, 16), jnp.int32)
    targets = jnp.zeros((8, 16), jnp.int32)
    stats = aot_memory_analysis(step, params, tokens, targets)
    assert "error" not in stats, stats
    cs = compile_schedule("1F1B", 4, 1, 8)
    sec = memory_model_section(cs, cfg, batch_size=8, seq_length=16,
                               compiled=stats)
    rec = sec["reconciliation"]
    # XLA's argument accounting is per addressable shard: each device's
    # layers/D slice plus the replicated embed/head and int32 inputs.
    # Unpadded CPU layout -> exact; the documented tolerance is 10%.
    assert rec["ok"]
    assert rec["argument_rel_err"] <= 0.10
    assert rec["expected_argument_bytes"] == (
        sec["analytic"]["params_per_device_bytes"]
        + sec["analytic"]["input_bytes"])
    assert sec["compiled"]["temp_bytes"] > 0


def test_reconcile_memory_flags_drift():
    analytic = {"params_per_device_bytes": 1000.0, "input_bytes": 0.0,
                "activation_peak_bytes": 0.0}
    ok = reconcile_memory(analytic, {"argument_bytes": 1050.0,
                                     "temp_bytes": 1.0})
    assert ok["ok"] and ok["argument_rel_err"] == pytest.approx(0.05)
    bad = reconcile_memory(analytic, {"argument_bytes": 2000.0})
    assert not bad["ok"]
    assert reconcile_memory(analytic, {"error": "no backend"}) is None
    assert reconcile_memory(analytic, None) is None


# ---------------------------------------------------------------------------
# Manifest schema
# ---------------------------------------------------------------------------


def test_memory_section_roundtrips_validate_report(tmp_path):
    cfg = dtpp.ModelConfig(**CFG)
    cs = compile_schedule("GPipe", 4, 1, 4)
    sec = memory_model_section(cs, cfg, batch_size=8, seq_length=16)
    report = RunReport(out_dir=str(tmp_path), name="mem_test")
    report.set_meta(backend="cpu")
    report.attach_memory(sec)
    manifest = report.write()
    validate_report(manifest)
    assert manifest["memory"]["schedule"] == "GPipe"
    assert manifest["memory"]["analytic"]["per_device"][0]["act_bytes"] >= 0


def test_validate_report_rejects_malformed_memory(tmp_path):
    report = RunReport(out_dir=str(tmp_path), name="mem_bad")
    report.set_meta(backend="cpu")
    report.attach_memory({"schedule": "GPipe"})  # no analytic section
    with pytest.raises(ValueError):
        report.write()


def test_serving_memory_section_prices_kv_cache():
    cfg = dtpp.ModelConfig(**CFG, arch="gpt2")
    program = types.SimpleNamespace(n_stages=2, n_slots=3, prefill_chunk=2,
                                    max_len=32, mlen_alloc=33)
    sec = serving_memory_section(cfg, program)
    n_kv = cfg.n_kv_heads or cfg.n_heads
    want_kv = (2.0 * (cfg.n_layers // 2) * 3 * 33 * n_kv * cfg.head_dim
               * dtype_bytes(cfg.dtype))
    assert sec["analytic"]["kv_cache_bytes_per_device"] == want_kv
    assert sec["schedule"] == "serving_ring"
    assert len(sec["analytic"]["per_device"]) == 2
    for pd in sec["analytic"]["per_device"]:
        assert pd["kv_cache_bytes"] == want_kv
        assert pd["total_bytes"] >= want_kv


# ---------------------------------------------------------------------------
# No host callbacks; the requests track
# ---------------------------------------------------------------------------


def test_default_step_has_zero_callbacks():
    import jax.numpy as jnp

    from distributed_training_with_pipeline_parallelism_tpu.models import (
        transformer as tfm)
    from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import (
        make_mesh)
    from distributed_training_with_pipeline_parallelism_tpu.parallel.pipeline import (
        make_pipeline_step)

    cfg = dtpp.ModelConfig(**CFG)
    mesh = make_mesh(n_pipe=4)
    sched = dtpp.ScheduleConfig(name="GPipe", n_microbatches=4)
    step = make_pipeline_step(cfg, mesh, sched)
    params = tfm.transformer_init(jax.random.key(0), cfg)
    tokens = jnp.zeros((8, 16), jnp.int32)
    targets = jnp.zeros((8, 16), jnp.int32)
    jaxpr = jax.make_jaxpr(step)(params, tokens, targets)
    # the jaxpr-audit contract, read off the text
    assert "callback" not in str(jaxpr)


def test_perfetto_requests_track():
    events = [
        {"kind": "serve_admit", "rid": 0, "slot": 1, "t": 10.0, "tick": 3,
         "prompt_len": 4, "budget": 6},
        {"kind": "serve_finish", "rid": 0, "slot": 1, "t": 10.5, "tick": 19,
         "n_tokens": 6, "ttft_ticks": 4},
        {"kind": "serve_admit", "rid": 1, "slot": 0, "t": 10.2, "tick": 5,
         "prompt_len": 2, "budget": 3},  # still in flight: no finish row
        {"kind": "other", "t": 0.0},
    ]
    out = perfetto_request_events(events)
    begins = [e for e in out if e["ph"] == "b"]
    ends = [e for e in out if e["ph"] == "e"]
    assert len(begins) == 2 and len(ends) == 2
    by_rid = {e["id"]: e for e in begins}
    assert by_rid[0]["args"]["admit_tick"] == 3
    assert by_rid[0]["args"]["finish_tick"] == 19
    assert by_rid[0]["args"]["ttft_ticks"] == 4
    assert by_rid[0]["tid"] == 1  # per-slot thread row
    assert "finish_tick" not in by_rid[1]["args"]
    # unfinished requests close zero-width at their admit timestamp
    end_by_rid = {e["id"]: e for e in ends}
    assert end_by_rid[1]["ts"] == by_rid[1]["ts"]
    assert perfetto_request_events([]) == []


# ---------------------------------------------------------------------------
# OOM preflight and byte-denominated search budgets
# ---------------------------------------------------------------------------


def test_oom_preflight_verdicts():
    cfg = dtpp.ModelConfig(**CFG)
    cs = compile_schedule("GPipe", 4, 1, 4)
    sec = memory_model_section(cs, cfg, batch_size=8, seq_length=16)
    assert oom_preflight(sec, hardware=CPU_PROXY)["ok"]
    tiny = HardwareSpec("tiny", 1e12, 1e9, 1e11, hbm_bytes=1024.0)
    verdict = oom_preflight(sec, hardware=tiny)
    assert not verdict["ok"]
    assert verdict["predicted_peak_bytes"] == sec["analytic"]["peak_bytes"]
    # unknown capacity never vetoes
    unknown = HardwareSpec("unknown", 1e12, 1e9, 1e11)
    assert oom_preflight(sec, hardware=unknown)["ok"]


def test_sweep_preflight_skips_predicted_oom():
    from distributed_training_with_pipeline_parallelism_tpu.utils.sweep import (
        run_one_experiment)
    # a config whose params alone dwarf the CPU proxy's 16 GB stand-in
    # capacity: priced and skipped before any mesh or compile exists
    row = run_one_experiment(n_layers=8, n_heads=8, num_devices=4,
                             schedule_type="GPipe", dim=16384,
                             vocab_size=50000, batch_size=8, seq_length=128,
                             num_iterations=1)
    assert row["skip_reason"] == "predicted_oom"
    assert row["predicted_peak_bytes"] > row["hbm_bytes"] > 0


def test_search_bytes_budget_matches_slot_budget():
    from distributed_training_with_pipeline_parallelism_tpu.analysis.schedule_search import (
        SearchSpec, search_schedule)
    slot_b = 4096
    s_slots = SearchSpec(n_devices=4, n_microbatches=8, iterations=30,
                         act_slot_budget=8)
    s_bytes = SearchSpec(n_devices=4, n_microbatches=8, iterations=30,
                         act_bytes_budget=float(8 * slot_b + 100),
                         act_slot_bytes=slot_b)
    assert s_slots.resolved_slot_budgets() == (8, None)
    assert s_bytes.resolved_slot_budgets() == (8, None)
    r1, r2 = search_schedule(s_slots), search_schedule(s_bytes)
    assert max(r1.report.act_slots_used) <= 8
    assert r1.cs.table.tobytes() == r2.cs.table.tobytes()
    assert r2.stats["effective_act_slot_budget"] == 8
    assert r2.stats["act_bytes_budget"] == 8 * slot_b + 100
    # when both budgets are given the tighter one wins
    both = SearchSpec(n_devices=2, n_microbatches=4, act_slot_budget=5,
                      act_bytes_budget=float(2 * slot_b),
                      act_slot_bytes=slot_b)
    assert both.resolved_slot_budgets()[0] == 2


def test_search_validates_bytes_budgets():
    from distributed_training_with_pipeline_parallelism_tpu.analysis.schedule_search import (
        SearchSpec)
    with pytest.raises(ScheduleError):
        SearchSpec(n_devices=2, n_microbatches=4,
                   act_bytes_budget=1e6).validate()  # no slot_bytes
    with pytest.raises(ScheduleError):
        SearchSpec(n_devices=2, n_microbatches=4, grad_bytes_budget=10.0,
                   grad_slot_bytes=4096).validate()  # holds zero slots


# ---------------------------------------------------------------------------
# The regression sentinel's HBM guard
# ---------------------------------------------------------------------------


def test_regress_guards_peak_hbm():
    regress = _load_script("regress")
    manifest = {
        "meta": {"name": "fit", "backend": "tpu",
                 "schedule": {"name": "1F1B"}},
        "memory": {"schedule": "1F1B",
                   "compiled": {"temp_bytes": 1000.0}},
    }
    row = regress.extract_metrics(manifest)
    assert row["peak_temp_bytes"] == 1000.0
    history = [dict(row) for _ in range(3)]
    grown = dict(row, peak_temp_bytes=1200.0)
    problems = regress.check(grown, history, 0.1, 20)
    assert any("peak_temp_bytes" in p for p in problems)
    # shrinking memory is an improvement, not a regression
    assert not regress.check(dict(row, peak_temp_bytes=900.0),
                             history, 0.1, 20)
    # reports without a memory section degrade to None, never fire
    bare = regress.extract_metrics({"meta": {"name": "fit",
                                             "backend": "tpu"}})
    assert bare["peak_temp_bytes"] is None
    assert not regress.check(bare, [dict(bare)] * 3, 0.1, 20)
