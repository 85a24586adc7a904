"""Training-dynamics observatory: stats parity, GNS, forensics, lint.

The contract under test (docs/observability.md §7):

- dynamics OFF is free at trace time: the grad program's jaxpr is
  byte-identical with ``dynamics=None`` / ``dynamics=False`` / the kwarg
  omitted, contains no host callbacks, and the unguarded train step's
  jaxpr is equally unchanged;
- dynamics ON yields per-stage gradient norms that match a single-device
  oracle partitioned the same way the pipeline partitions the layer
  stack (stage ``s`` owns layers ``[s*lps, (s+1)*lps)``, embed rides
  stage 0, head the last stage) across schedule families and both
  backward policies;
- the per-microbatch ``sq_mb`` accumulator feeds the McCandlish
  small/large-batch GNS pair: exact on algebraic inputs, consistent on
  a synthetic stochastic-gradient problem;
- the anomaly guard attributes a stage-targeted NaN fault to the
  injected stage via ``last_bad_stage`` while the loss stays finite;
- forensic bundles round-trip through JSON and are rejected when
  malformed; the spike detector arms only after warmup and triggers on
  jumps, not noise;
- the ``dynamics-sync-read`` lint rule flags host fetches of dynamics
  stats outside the log-sync modules;
- ``scripts/regress.py`` survives empty/torn history and warns (never
  fails) on model-health drift.
"""

import importlib.util
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import distributed_training_with_pipeline_parallelism_tpu as dtpp
from distributed_training_with_pipeline_parallelism_tpu.models import (
    transformer as tfm)
from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import (
    make_mesh)
from distributed_training_with_pipeline_parallelism_tpu.parallel.pipeline import (
    make_pipeline_grad_fn)
from distributed_training_with_pipeline_parallelism_tpu.utils import train
from distributed_training_with_pipeline_parallelism_tpu.utils.dynamics import (
    DynamicsConfig, ForensicRecorder, GNSEstimator, as_dynamics_config,
    batch_digest, dynamics_section, gns_estimates, nonfinite_per_stage,
    stage_stats, validate_forensic_bundle)
from distributed_training_with_pipeline_parallelism_tpu.utils.resilience import (
    AnomalyGuard, FaultPlan, init_guard_state)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = dtpp.ModelConfig(dim=32, n_layers=4, n_heads=4, vocab_size=64,
                       ffn_dim=64, max_seq_len=16)
S = 4  # stages on the 4-way pipe mesh below


def _load_script(name):
    """Import a scripts/ module by path (scripts/ is not a package)."""
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def problem():
    params = tfm.transformer_init(jax.random.key(0), CFG)
    tokens = jax.random.randint(jax.random.key(1), (8, 16), 0,
                                CFG.vocab_size)
    targets = jax.random.randint(jax.random.key(2), (8, 16), 0,
                                 CFG.vocab_size)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: tfm.transformer_loss(CFG, p, tokens, targets))(params)
    return params, tokens, targets, ref_loss, ref_grads


def _oracle_stage_norms(grads, n_layers, n_stages):
    """Per-stage grad norms from a single-device grad tree, partitioned
    exactly like the pipeline partitions the layer stack."""
    sq = np.zeros((n_stages,), np.float64)
    for leaf in jax.tree.leaves(grads["layers"]):
        x = np.asarray(leaf, np.float32).reshape(n_stages, -1)
        sq += (x.astype(np.float64) ** 2).sum(axis=1)
    for key, idx in (("embed", 0), ("head", n_stages - 1)):
        for leaf in jax.tree.leaves(grads[key]):
            x = np.asarray(leaf, np.float32).astype(np.float64)
            sq[idx] += (x ** 2).sum()
    return np.sqrt(sq)


# ---------------------------------------------------------------------------
# Zero-cost-when-off: byte-identical jaxprs, no callbacks
# ---------------------------------------------------------------------------


def test_dynamics_off_jaxpr_byte_identical(problem):
    params, tokens, targets, _, _ = problem
    mesh = make_mesh(n_pipe=4)
    sched = dtpp.ScheduleConfig(name="1F1B", n_microbatches=8)
    kw = dict(remat_backward=True, unroll_ticks=True)
    base = make_pipeline_grad_fn(CFG, mesh, sched, **kw)
    jp = str(jax.make_jaxpr(base)(params, tokens, targets))
    for off in (None, False):
        fn = make_pipeline_grad_fn(CFG, mesh, sched, dynamics=off, **kw)
        assert str(jax.make_jaxpr(fn)(params, tokens, targets)) == jp
    for banned in ("io_callback", "callback", "outside_call"):
        assert banned not in jp


def test_dynamics_off_train_step_jaxpr_identical(problem):
    params, tokens, targets, _, _ = problem
    mesh = make_mesh(n_pipe=4)
    sched = dtpp.ScheduleConfig(name="1F1B", n_microbatches=8)
    opt = train.adamw(total_steps=4, warmup_steps=1)
    opt_state = opt.init(params)
    args = (params, opt_state, tokens, targets)
    plain = train.make_train_step(CFG, mesh, sched, opt)
    off = train.make_train_step(CFG, mesh, sched, opt, dynamics=None)
    assert str(jax.make_jaxpr(plain)(*args)) == str(jax.make_jaxpr(off)(*args))


def test_as_dynamics_config_coercion():
    assert as_dynamics_config(None) is None
    assert as_dynamics_config(False) is None
    assert as_dynamics_config(True) == DynamicsConfig()
    dc = DynamicsConfig(gns=False, ring=4)
    assert as_dynamics_config(dc) is dc
    with pytest.raises(TypeError, match="dynamics must be"):
        as_dynamics_config("yes")


# ---------------------------------------------------------------------------
# Per-stage stats parity vs the single-device oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,M", [
    ("GPipe", 4),
    ("1F1B", 4),
    ("ZBH1", 4),     # split backward (B/W units)
])
def test_per_stage_norms_match_oracle(problem, name, M):
    params, tokens, targets, ref_loss, ref_grads = problem
    mesh = make_mesh(n_pipe=4)
    sched = dtpp.ScheduleConfig(name=name, n_microbatches=M)
    fn = make_pipeline_grad_fn(CFG, mesh, sched, remat_backward=True,
                               unroll_ticks=True, dynamics=True)
    # jitted: called bare, the unjitted unrolled tick program runs op by
    # op through an eager shard_map (minutes, not seconds)
    loss, grads, sq_mb = jax.jit(fn)(params, tokens, targets)
    assert float(jnp.abs(loss - ref_loss)) < 1e-5
    assert sq_mb.shape == (M,)

    st = stage_stats(CFG.n_layers, S, grads, params=params)
    want = _oracle_stage_norms(ref_grads, CFG.n_layers, S)
    np.testing.assert_allclose(np.asarray(st["grad_norm_per_stage"]), want,
                               rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(float(st["grad_norm"]),
                               math.sqrt(float((want ** 2).sum())),
                               rtol=2e-4)
    # layer norms tile the stage norms minus the embed/head contributions
    assert np.asarray(st["grad_norm_per_layer"]).shape == (CFG.n_layers,)
    assert int(np.asarray(st["nonfinite_per_stage"]).sum()) == 0
    # the whole-step |G|^2 equals the accumulated microbatch mean's
    # counterpart only statistically; sanity: every |g_m|^2 is positive
    assert np.all(np.asarray(sq_mb) > 0)


def test_dynamics_rejects_stored_backward():
    # the stored-activation program differentiates through its forward
    # tick scan and never materializes per-microbatch gradients — the
    # accumulator cannot ride it, and the error must say what to pass
    mesh = make_mesh(n_pipe=4)
    sched = dtpp.ScheduleConfig(name="GPipe", n_microbatches=4)
    with pytest.raises(ValueError, match="remat_backward=True"):
        make_pipeline_grad_fn(CFG, mesh, sched, remat_backward=False,
                              unroll_ticks=True, dynamics=True)


def test_stage_stats_update_ratio_and_param_rms(problem):
    params, _, _, _, ref_grads = problem
    updates = jax.tree.map(lambda p: 0.01 * jnp.ones_like(p), params)
    st = stage_stats(CFG.n_layers, S, ref_grads, params=params,
                     updates=updates)
    assert st["param_rms_per_stage"].shape == (S,)
    assert st["update_ratio_per_stage"].shape == (S,)
    assert np.all(np.asarray(st["param_rms_per_stage"]) > 0)
    st_min = stage_stats(CFG.n_layers, S, ref_grads)
    assert "param_rms_per_stage" not in st_min
    with pytest.raises(ValueError, match="must divide"):
        stage_stats(CFG.n_layers, 3, ref_grads)


def test_nonfinite_per_stage_attribution(problem):
    _, _, _, _, ref_grads = problem
    clean = np.asarray(nonfinite_per_stage(CFG.n_layers, S, ref_grads))
    assert clean.tolist() == [0] * S

    # poison one layer row owned by stage 2 (layers [2, 3) at lps=1)
    leaves = jax.tree.leaves(ref_grads["layers"])
    poisoned = jax.tree.map(lambda g: g, ref_grads)
    first = jax.tree.leaves(poisoned["layers"])[0]
    bad = first.at[2].set(jnp.nan)
    poisoned["layers"] = jax.tree.map(
        lambda g: bad if g is jax.tree.leaves(poisoned["layers"])[0] else g,
        poisoned["layers"])
    # simpler: rebuild with tree_map over paths is overkill — patch in place
    flat, treedef = jax.tree.flatten(ref_grads["layers"])
    flat = [flat[0].at[2].set(jnp.nan)] + flat[1:]
    poisoned = dict(ref_grads, layers=jax.tree.unflatten(treedef, flat))
    nf = np.asarray(nonfinite_per_stage(CFG.n_layers, S, poisoned))
    assert nf[2] == 1 and nf.sum() == 1

    # a poisoned embed leaf lands on stage 0, head on the last stage
    eflat, etd = jax.tree.flatten(ref_grads["embed"])
    bad_embed = dict(ref_grads,
                     embed=jax.tree.unflatten(
                         etd, [eflat[0].at[0].set(jnp.inf)] + eflat[1:]))
    assert np.asarray(
        nonfinite_per_stage(CFG.n_layers, S, bad_embed))[0] == 1
    hflat, htd = jax.tree.flatten(ref_grads["head"])
    bad_head = dict(ref_grads,
                    head=jax.tree.unflatten(
                        htd, [hflat[0].at[0].set(jnp.nan)] + hflat[1:]))
    assert np.asarray(
        nonfinite_per_stage(CFG.n_layers, S, bad_head))[S - 1] == 1
    assert len(leaves) > 0  # the fixture tree really is layer-stacked


# ---------------------------------------------------------------------------
# Gradient noise scale
# ---------------------------------------------------------------------------


def test_gns_algebraic_exact():
    # E|g_b|^2 = |G|^2 + tr(Sigma)/b: feed the exact expectations and the
    # unbiased pair must recover |G|^2 and tr(Sigma) to float precision
    g2_true, s_true, b, B = 4.0, 32.0, 2.0, 16.0
    g2, s = gns_estimates(g2_true + s_true / b, g2_true + s_true / B, b, B)
    assert abs(g2 - g2_true) < 1e-9
    assert abs(s - s_true) < 1e-9

    est = GNSEstimator(batch_small=b, batch_big=B, ema=0.5)
    assert est.value() is None
    for _ in range(5):
        v = est.update(g2_true + s_true / b, g2_true + s_true / B)
    assert abs(v - s_true / g2_true) < 1e-9
    assert est.n_updates == 5

    # a poisoned sync must not wedge the EMA
    v2 = est.update(float("nan"), g2_true + s_true / B)
    assert v2 == v and est.n_updates == 5

    with pytest.raises(ValueError, match="batch_big > batch_small"):
        GNSEstimator(batch_small=8, batch_big=8)
    with pytest.raises(ValueError, match="batch_big > batch_small"):
        gns_estimates(1.0, 1.0, 4.0, 4.0)


def test_gns_synthetic_stochastic_gradients():
    # g_i = G + eps, eps ~ N(0, sigma^2 I): the simple noise scale is
    # tr(Sigma)/|G|^2 = dim*sigma^2/|G|^2. Microbatch grads are means of
    # `b` samples; the full batch is the mean of all of them.
    rng = np.random.default_rng(0)
    dim, sigma, n, b = 8, 0.5, 4096, 32
    G = np.full((dim,), 2.0)
    samples = G + sigma * rng.standard_normal((n, dim))
    micro = samples.reshape(n // b, b, dim).mean(axis=1)
    mean_sq_small = float((micro ** 2).sum(axis=1).mean())
    sq_big = float((samples.mean(axis=0) ** 2).sum())
    est = GNSEstimator(batch_small=b, batch_big=n)
    got = est.update(mean_sq_small, sq_big)
    want = dim * sigma ** 2 / float(G @ G)
    assert got == pytest.approx(want, rel=0.2)


# ---------------------------------------------------------------------------
# Guarded attribution: stage-targeted fault -> last_bad_stage
# ---------------------------------------------------------------------------


def test_guard_attributes_stage_targeted_fault(problem):
    params, tokens, targets, _, _ = problem
    mesh = make_mesh(n_pipe=4)
    sched = dtpp.ScheduleConfig(name="GPipe", n_microbatches=4)
    opt = train.adamw(total_steps=4, warmup_steps=1)
    BAD = 2
    step = train.make_train_step(
        CFG, mesh, sched, opt, guard=AnomalyGuard(), dynamics=True,
        fault_plan=FaultPlan(nan_grad_steps=(1,), nan_grad_stage=BAD))
    p, s, gs = params, opt.init(params), init_guard_state(0)
    losses = []
    for _ in range(3):
        p, s, loss, gs, dyn = step(p, s, tokens, targets, gs)
        losses.append(float(loss))
    host = jax.device_get(gs)
    # the loss stayed finite on the poisoned step — only the per-stage
    # reduction saw the fault — yet the skip is attributed to the stage
    assert all(math.isfinite(x) for x in losses)
    assert int(host["total"]) == 1
    assert int(host["last_anomaly_step"]) == 1
    assert int(host["last_bad_stage"]) == BAD
    dyn_host = jax.device_get(dyn)
    assert dyn_host["grad_norm_per_stage"].shape == (S,)
    assert "sq_mb" in dyn_host

    with pytest.raises(ValueError, match="out of range"):
        train.make_train_step(
            CFG, mesh, sched, opt, guard=AnomalyGuard(),
            fault_plan=FaultPlan(nan_grad_steps=(1,), nan_grad_stage=7))


# ---------------------------------------------------------------------------
# Forensics: bundles, spike detector
# ---------------------------------------------------------------------------


def test_forensic_bundle_roundtrip(tmp_path):
    rec = ForensicRecorder(out_dir=str(tmp_path), ring=8, spike_z=6.0,
                           warmup=3)
    for i in range(6):
        rec.note_batch(i, batch_digest(np.arange(4) + i))
        rec.observe(i, 2.0 - 0.1 * i,
                    stats={"grad_norm": np.float32(1.0)}, gns=8.0)
    path = rec.dump(5, "anomaly", loss=float("nan"), z=None,
                    stats={"grad_norm_per_stage": [1.0, float("inf")]},
                    attribution={"stage": 1, "statistic": "nonfinite_grad"},
                    checkpoint={"last_committed_step": 4})
    assert path is not None and os.path.exists(path)
    assert rec.bundles == [path]
    with open(path) as fh:
        bundle = json.load(fh)  # NaN/inf were serialized as repr strings
    validate_forensic_bundle(bundle)
    assert bundle["trigger"] == "anomaly"
    assert bundle["attribution"]["stage"] == 1
    assert bundle["loss"] == "nan"
    assert bundle["stats"]["grad_norm_per_stage"][1] == "inf"
    assert len(bundle["ring"]) == 6
    assert len(bundle["batch_digests"]) == 6
    assert bundle["checkpoint"]["last_committed_step"] == 4

    # no out_dir: the ring still works, dump returns None
    rec2 = ForensicRecorder()
    rec2.observe(0, 1.0)
    assert rec2.dump(0, "loss_spike", loss=1.0) is None
    with pytest.raises(ValueError, match="trigger must be"):
        rec.dump(6, "oops", loss=1.0)


@pytest.mark.parametrize("mutate,msg", [
    (lambda b: b.update(kind="nope"), "kind"),
    (lambda b: b.update(schema_version=99), "schema_version"),
    (lambda b: b.update(trigger="panic"), "trigger"),
    (lambda b: b.update(step="five"), "step"),
    (lambda b: b.update(ring={"not": "a list"}), "ring"),
    (lambda b: b.update(ring=[{"loss": 1.0}]), "ring"),
    (lambda b: b.update(batch_digests=[{"digest": 7}]), "batch_digests"),
    (lambda b: b.update(attribution={"stage": "one",
                                     "statistic": "x"}), "attribution"),
    (lambda b: b.update(attribution={"stage": 1}), "attribution"),
])
def test_forensic_bundle_malformed_rejected(mutate, msg):
    rec = ForensicRecorder()
    rec.observe(0, 1.0)
    # build a valid in-memory bundle, then break one field
    bundle = {
        "schema_version": 1, "kind": "forensic_bundle",
        "trigger": "anomaly", "step": 0, "loss": 1.0, "z": None,
        "stats": None, "attribution": None,
        "ring": [{"step": 0, "loss": 1.0}],
        "batch_digests": [], "checkpoint": None,
    }
    validate_forensic_bundle(bundle)
    mutate(bundle)
    with pytest.raises(ValueError, match=msg):
        validate_forensic_bundle(bundle)


def test_spike_detector_matrix():
    rec = ForensicRecorder(spike_z=6.0, warmup=5)
    # during warmup nothing triggers, however large the jump
    for i in range(4):
        assert rec.observe(i, 1.0) is None
    assert rec.observe(4, 1000.0) is None  # 4 priors < warmup=5
    rec2 = ForensicRecorder(spike_z=6.0, warmup=5)
    for i in range(6):
        assert rec2.observe(i, 1.0) is None
    # flat plateau (sd == 0): the mean-scaled epsilon still lets a real
    # jump through...
    assert rec2.observe(6, 2.0) is not None
    # ...and a NaN loss never arms or crashes the detector
    assert rec2.observe(7, float("nan")) is None
    rec3 = ForensicRecorder(spike_z=6.0, warmup=3)
    losses = [1.0, 1.1, 0.9, 1.05, 0.95]
    for i, l in enumerate(losses):
        rec3.observe(i, l)
    assert rec3.observe(5, 1.12) is None   # within-noise move: no trigger
    z = rec3.observe(6, 5.0)               # genuine spike
    assert z is not None and z >= 6.0


# ---------------------------------------------------------------------------
# Manifest section + schema
# ---------------------------------------------------------------------------


def test_dynamics_section_schema(problem, tmp_path):
    from distributed_training_with_pipeline_parallelism_tpu.utils.telemetry import (  # noqa: E501
        RunReport, validate_report)
    _, _, _, _, ref_grads = problem
    st = jax.device_get(stage_stats(CFG.n_layers, S, ref_grads))
    sec = dynamics_section(S, last_stats=st, gns=12.5, gns_updates=3,
                           n_skipped_attributed=1,
                           forensic_bundles=["/x/forensics_a.json"])
    assert sec["n_stages"] == S
    assert len(sec["per_stage"]) == S
    assert sec["forensic_bundles"] == ["forensics_a.json"]  # basenames
    report = RunReport(out_dir=str(tmp_path), name="dyn-unit")
    report.set_meta(backend="cpu")
    report.attach_dynamics(sec)
    manifest = report.write()
    validate_report(manifest)
    on_disk = json.loads((tmp_path / "report.json").read_text())
    validate_report(on_disk)
    assert on_disk["dynamics"]["gns"] == 12.5

    broken = dict(manifest, dynamics=dict(sec, per_stage=[{"stage": "x"}]))
    with pytest.raises(ValueError):
        validate_report(broken)


# ---------------------------------------------------------------------------
# Lint: dynamics stats stay device-resident outside the sync boundary
# ---------------------------------------------------------------------------


def test_lint_flags_dynamics_sync_reads():
    from distributed_training_with_pipeline_parallelism_tpu.analysis.repo_lint import (  # noqa: E501
        lint_source)
    bad = ("import jax\n"
           "def log(dyn_latest, stats):\n"
           "    a = jax.device_get(dyn_latest)\n"
           "    b = float(stats['grad_norm_per_stage'][0])\n")
    findings = lint_source("x.py", bad,
                           package_relpath="parallel/pipeline_extras.py")
    assert [f.rule for f in findings] == ["dynamics-sync-read"] * 2
    # the sync-boundary owners are allowlisted
    assert lint_source("x.py", bad, package_relpath="utils/train.py") == []
    # reads of non-dynamics names are not the lint's business
    ok = "def f(loss):\n    return float(loss)\n"
    assert lint_source("x.py", ok,
                       package_relpath="parallel/whatever.py") == []


# ---------------------------------------------------------------------------
# regress.py: robustness + drift guards (stdlib-only module)
# ---------------------------------------------------------------------------


def test_regress_history_robustness(tmp_path):
    regress = _load_script("regress")
    missing = str(tmp_path / "nope.jsonl")
    assert regress.load_history(missing) == []
    hist = tmp_path / "history.jsonl"
    hist.write_text('{"name": "a", "tokens_per_sec": 1.0}\n'
                    '"just a string"\n'
                    '{"torn": \n')
    rows = regress.load_history(str(hist))
    assert rows == [{"name": "a", "tokens_per_sec": 1.0}]

    # single-sample groups and a fresh group never fail
    row = {"name": "a", "backend": "cpu", "schedule": "1F1B",
           "tokens_per_sec": 100.0, "mfu": 0.1, "bubble": 0.2,
           "peak_temp_bytes": 10, "peak_live_bytes": None,
           "grad_norm_final": 1.0, "gns": 8.0}
    assert regress.check(row, [], threshold=0.1, window=20) == []
    assert regress.drift_check(row, [], 0.5, 20) == []


def test_regress_drift_warns_only(tmp_path, capsys):
    regress = _load_script("regress")
    base = {"name": "a", "backend": "tpu", "schedule": "1F1B",
            "tokens_per_sec": 100.0, "mfu": 0.5, "bubble": 0.1,
            "peak_temp_bytes": 10, "peak_live_bytes": None}
    history = [dict(base, grad_norm_final=1.0, gns=8.0) for _ in range(3)]
    drifted = dict(base, grad_norm_final=3.0, gns=8.1)
    msgs = regress.drift_check(drifted, history, 0.5, 20)
    assert len(msgs) == 1 and "grad_norm_final" in msgs[0]
    # inside the band, or non-numeric (a NaN serialized as a string): quiet
    assert regress.drift_check(dict(base, grad_norm_final=1.2, gns="nan"),
                               history, 0.5, 20) == []

    # end to end: a drifted report exits 0 (drift never gates)
    report = {"meta": {"name": "a", "backend": "tpu",
                       "schedule": {"name": "1F1B"}},
              "gauges": {"tokens_per_sec": 100.0},
              "dynamics": {"n_stages": 2, "grad_norm_final": 3.0,
                           "gns": 8.0, "gns_updates": 1,
                           "n_skipped_attributed": 0, "per_stage": [],
                           "forensic_bundles": []}}
    rpath = tmp_path / "report.json"
    rpath.write_text(json.dumps(report))
    hist = tmp_path / "history.jsonl"
    with open(hist, "w") as fh:
        for r in history:
            fh.write(json.dumps(dict(r, t=0.0)) + "\n")
    rc = regress.main(["--report", str(rpath), "--history", str(hist)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "WARN (drift)" in out and "grad_norm_final" in out


def test_regress_extracts_dynamics_metrics():
    regress = _load_script("regress")
    manifest = {"meta": {"name": "x", "backend": "cpu",
                         "schedule": {"name": "GPipe"}},
                "dynamics": {"grad_norm_final": 2.5, "gns": float("nan"),
                             "n_skipped_attributed": 2}}
    row = regress.extract_metrics(manifest)
    assert row["grad_norm_final"] == 2.5
    assert row["gns"] is None  # non-finite never enters the history math
    assert row["n_skipped_attributed"] == 2
    # sweep rows carry the same names as gauges
    row2 = regress.extract_metrics(
        {"meta": {"name": "s", "backend": "cpu"},
         "gauges": {"grad_norm_final": 1.5, "gns": 4.0}})
    assert row2["grad_norm_final"] == 1.5 and row2["gns"] == 4.0
