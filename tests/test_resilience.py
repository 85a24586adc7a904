"""Resilience layer (ISSUE 8): crash-safe checkpoint commit protocol,
anomaly guard, preemption-safe fit, fault injection, and the hardened
serving scheduler. The load-bearing properties: an interrupted + resumed
run **bit-matches** the uninterrupted one; the anomaly guard skips a
poisoned step without touching params and adds **zero** host syncs or
jaxpr changes when off; a poisoned serving request is retired ``failed``
without wedging its slot or the other requests' oracle parity."""

import json
import os
import signal
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import distributed_training_with_pipeline_parallelism_tpu as dtpp
from distributed_training_with_pipeline_parallelism_tpu.models import (
    transformer as tfm)
from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import (
    make_mesh)
from distributed_training_with_pipeline_parallelism_tpu.utils import train
from distributed_training_with_pipeline_parallelism_tpu.utils.checkpoint import (
    COMMIT_MARKER, is_committed, read_commit_marker, save_checkpoint,
    write_commit_marker)
from distributed_training_with_pipeline_parallelism_tpu.utils.resilience import (
    AnomalyBudgetExceeded, AnomalyGuard, CheckpointManager, FaultPlan,
    InjectedDataFault, PreemptionHandler, SimulatedKill, StepWatchdog,
    config_fingerprint, gc_checkpoints, init_guard_state,
    latest_committed_step_dir, pytree_digest)


def _tiny():
    cfg = dtpp.ModelConfig(dim=16, n_layers=2, n_heads=2, vocab_size=32,
                           ffn_dim=32, max_seq_len=16)
    mesh = make_mesh(n_pipe=2)
    sched = dtpp.ScheduleConfig(name="GPipe", n_microbatches=2)
    return cfg, mesh, sched


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Commit protocol + retention (host-only: no compiles)
# ---------------------------------------------------------------------------


def _fake_ckpt(root, n, committed=True, **meta):
    path = os.path.join(root, f"step_{n}")
    os.makedirs(path)
    if committed:
        write_commit_marker(path, {"step": n, **meta})
    return path


def test_latest_committed_skips_shells_and_mismatches(tmp_path, caplog):
    root = str(tmp_path)
    _fake_ckpt(root, 1, fingerprint="aaaa")
    _fake_ckpt(root, 3, fingerprint="bbbb")
    _fake_ckpt(root, 5, committed=False)  # killed mid-flush
    with caplog.at_level("WARNING"):
        got = latest_committed_step_dir(root)
    assert got == (3, os.path.join(root, "step_3"))
    assert "step_5 (uncommitted)" in caplog.text
    # config-fingerprint mismatch falls back one more
    assert latest_committed_step_dir(root, fingerprint="aaaa")[0] == 1
    # nothing matches -> None, not a bad restore
    assert latest_committed_step_dir(root, fingerprint="cccc") is None


def test_latest_committed_legacy_fallback(tmp_path, caplog):
    # a marker-less tree predates the protocol: newest dir, loudly
    root = str(tmp_path)
    _fake_ckpt(root, 2, committed=False)
    _fake_ckpt(root, 4, committed=False)
    with caplog.at_level("WARNING"):
        got = latest_committed_step_dir(root)
    assert got == (4, os.path.join(root, "step_4"))
    assert "legacy" in caplog.text
    # corrupt marker == no marker
    with open(os.path.join(root, "step_4", COMMIT_MARKER), "w") as fh:
        fh.write("{truncated")
    assert read_commit_marker(os.path.join(root, "step_4")) is None


def test_gc_keeps_newest_k_committed(tmp_path):
    root = str(tmp_path)
    for n in (1, 3, 5, 7):
        _fake_ckpt(root, n)
    _fake_ckpt(root, 2, committed=False)   # dead shell below newest committed
    _fake_ckpt(root, 9, committed=False)   # maybe in-flight: must survive
    removed = gc_checkpoints(root, keep_last=2)
    left = sorted(d for d in os.listdir(root))
    assert left == ["step_5", "step_7", "step_9"], removed
    assert is_committed(os.path.join(root, "step_5"))
    with pytest.raises(ValueError):
        gc_checkpoints(root, keep_last=0)


def test_fingerprint_and_digest():
    cfg, _, sched = _tiny()
    fp = config_fingerprint(cfg, sched)
    assert fp == config_fingerprint(cfg, sched) and len(fp) == 16
    assert fp != config_fingerprint(
        dtpp.ModelConfig(dim=32, n_layers=2, n_heads=2, vocab_size=32,
                         ffn_dim=32, max_seq_len=16), sched)
    tree = {"a": jnp.zeros((2, 3)), "b": jnp.zeros((4,), jnp.int32)}
    assert pytree_digest(tree) == pytree_digest(
        {"a": jnp.ones((2, 3)), "b": jnp.zeros((4,), jnp.int32)})  # structural
    assert pytree_digest(tree) != pytree_digest(
        {"a": jnp.zeros((2, 3)), "b": jnp.zeros((5,), jnp.int32)})


def test_save_checkpoint_overwrite_rules(tmp_path, caplog):
    state = {"w": jnp.arange(4.0)}
    path = str(tmp_path / "step_0")
    save_checkpoint(path, state)
    # an uncommitted existing dir (died between flush and commit) is
    # removed and re-saved...
    with caplog.at_level("WARNING"):
        save_checkpoint(path, state)
    assert "removing and re-saving" in caplog.text
    # ...but a committed one is refused
    write_commit_marker(path, {"step": 0})
    with pytest.raises(ValueError, match="refusing to overwrite committed"):
        save_checkpoint(path, state)


def test_manager_kill_between_flush_and_commit(tmp_path):
    state = {"w": jnp.arange(4.0)}
    mgr = CheckpointManager(str(tmp_path), keep_last=2,
                            fault_plan=FaultPlan(kill_in_save_step=2))
    mgr.save(0, state)
    mgr.save(1, state, wait=False)        # commit left pending
    with pytest.raises(SimulatedKill):
        mgr.save(2, state)                # commits 1, flushes 2, "dies"
    assert os.path.isdir(mgr.step_path(2))
    assert not is_committed(mgr.step_path(2))
    assert is_committed(mgr.step_path(1))  # pending commit landed first
    # a new manager (the restarted process) resumes from the last commit
    mgr2 = CheckpointManager(str(tmp_path))
    got = mgr2.restore_latest(state)
    assert got is not None and got[0] == 1
    _assert_trees_equal(got[2], state)
    # idempotent re-save of an already-committed identical step
    mgr2.save(1, state)
    assert mgr2.stats()["n_committed"] == 2


def test_fault_plan_wrap_data():
    plan = FaultPlan(data_fail_step=2)
    it = plan.wrap_data(iter([0, 1, 2, 3]))
    assert [next(it), next(it)] == [0, 1]
    with pytest.raises(InjectedDataFault):
        next(it)
    # identity when no fault is scheduled
    assert list(FaultPlan().wrap_data(iter([5]))) == [5]


def test_watchdog_and_preemption_handler():
    fired = []
    dog = StepWatchdog(0.05, fired.append, poll_s=0.01)
    try:
        dog.beat(7)
        deadline = time.monotonic() + 5.0
        while not fired and time.monotonic() < deadline:
            time.sleep(0.01)
        assert fired and fired[0]["step"] == 7
        assert fired[0]["stalled_s"] >= 0.05 and dog.stalls == 1
        n = len(fired)
        time.sleep(0.1)
        assert len(fired) == n  # fires once per stall, not per poll
    finally:
        dog.stop()
    with pytest.raises(ValueError):
        StepWatchdog(0.0, fired.append)

    h = PreemptionHandler(enabled=True)
    with h:
        assert not h.triggered
        h.trigger()
        assert h.triggered and h.signum == signal.SIGTERM
    disabled = PreemptionHandler(enabled=False)
    with disabled:
        assert not disabled._old  # no handlers installed


# ---------------------------------------------------------------------------
# Guarded train step (traces + a couple of tiny compiles)
# ---------------------------------------------------------------------------


def test_guard_off_jaxpr_identical_and_guard_adds_no_callbacks():
    """The resilience layer must be free when off: the unguarded step's
    jaxpr is byte-identical with/without an (empty) FaultPlan, has no
    finite-check, and the guarded step adds selects — not host
    callbacks or syncs."""
    cfg, mesh, sched = _tiny()
    opt = train.adamw(total_steps=4, warmup_steps=1)
    params = tfm.transformer_init(jax.random.key(0), cfg)
    opt_state = opt.init(params)
    tok = jnp.zeros((4, 8), jnp.int32)
    args = (params, opt_state, tok, tok)

    plain = train.make_train_step(cfg, mesh, sched, opt)
    with_plan = train.make_train_step(cfg, mesh, sched, opt,
                                      fault_plan=FaultPlan())
    jp_plain = str(jax.make_jaxpr(plain)(*args))
    assert jp_plain == str(jax.make_jaxpr(with_plan)(*args))
    assert "is_finite" not in jp_plain

    guarded = train.make_train_step(cfg, mesh, sched, opt,
                                    guard=AnomalyGuard())
    jp_guard = str(jax.make_jaxpr(guarded)(*args, init_guard_state()))
    assert "is_finite" in jp_guard
    for banned in ("io_callback", "callback", "outside_call"):
        assert banned not in jp_guard

    with pytest.raises(ValueError, match="requires an AnomalyGuard"):
        train.make_train_step(cfg, mesh, sched, opt,
                              fault_plan=FaultPlan(nan_grad_steps=(1,)))


def test_nan_step_skipped_bitwise():
    """A NaN-poisoned step must be a no-op: the run with the poisoned
    batch skipped by the guard ends bitwise equal to the run that never
    saw it (same compiled program, so the comparison is exact)."""
    cfg, mesh, sched = _tiny()
    opt = train.adamw(total_steps=8, warmup_steps=1)
    params0 = tfm.transformer_init(jax.random.key(0), cfg)
    toks = [jax.random.randint(jax.random.key(i), (4, 8), 0, cfg.vocab_size)
            for i in range(8)]
    data = [(toks[2 * i], toks[2 * i + 1]) for i in range(4)]
    step = train.make_train_step(cfg, mesh, sched, opt, guard=AnomalyGuard(),
                                 fault_plan=FaultPlan(nan_grad_steps=(2,)))

    # run A: batches 0..3, step 2 poisoned -> skipped (the step donates
    # its state, so run A trains a copy and run B gets params0 itself)
    p, s, gs = (jax.tree.map(jnp.copy, params0), opt.init(params0),
                init_guard_state(0))
    losses_a = []
    for tok, tgt in data[:4]:
        p, s, loss, gs = step(p, s, tok, tgt, gs)
        losses_a.append(loss)
    gs = {k: int(v) for k, v in jax.device_get(gs).items()}
    assert gs == {"step": 4, "consec": 0, "total": 1, "last_anomaly_step": 2,
                  "last_bad_stage": 0}  # all-stage poison: argmax picks 0
    assert not np.isfinite(float(losses_a[2]))  # the poison was real

    # run B: SAME compiled fn, guard clock started past every nan step,
    # fed only the batches run A actually applied
    p2, s2, gs2 = params0, opt.init(params0), init_guard_state(100)
    losses_b = []
    for tok, tgt in [data[0], data[1], data[3]]:
        p2, s2, loss, gs2 = step(p2, s2, tok, tgt, gs2)
        losses_b.append(loss)
    assert int(jax.device_get(gs2)["total"]) == 0
    _assert_trees_equal(p, p2)
    _assert_trees_equal(s, s2)
    # history shifts across the skipped step, bitwise
    for a, b in zip([losses_a[0], losses_a[1], losses_a[3]], losses_b):
        assert float(a) == float(b)


# ---------------------------------------------------------------------------
# fit(): kill -> resume bit-match, crash banking, preemption, abort
# ---------------------------------------------------------------------------


def _fit(tmpdir, steps=6, seed=3, ckpt=True, **kw):
    cfg, mesh, sched = _tiny()
    params = tfm.transformer_init(jax.random.key(0), cfg)
    opt = train.adamw(total_steps=6, warmup_steps=1)
    return train.fit(cfg, mesh, sched, params,
                     train.synthetic_data(cfg, 4, 8, seed=seed), steps,
                     optimizer=opt, verbose=False, log_every=1,
                     checkpoint_dir=str(tmpdir) if ckpt else None,
                     checkpoint_every=2 if ckpt else 0, **kw)


def test_kill_during_async_save_then_resume_bitmatch(tmp_path):
    clean, _ = _fit(tmp_path / "unused", ckpt=False)
    ck = tmp_path / "ck"
    with pytest.raises(SimulatedKill):
        _fit(ck, fault_plan=FaultPlan(kill_in_save_step=3))
    # the kill left step_3 uncommitted; step_1's async save was committed
    assert not is_committed(str(ck / "step_3"))
    assert latest_committed_step_dir(str(ck))[0] == 1
    resumed, hist = _fit(ck, resume=True)
    assert [s for s, _ in hist] == [2, 3, 4, 5]
    _assert_trees_equal(resumed, clean)


def test_data_fault_banks_crash_checkpoint(tmp_path):
    with pytest.raises(InjectedDataFault):
        _fit(tmp_path, fault_plan=FaultPlan(data_fail_step=2))
    # steps 0 and 1 completed; the crash path banked step 1 committed
    got = latest_committed_step_dir(str(tmp_path))
    assert got is not None and got[0] == 1


def test_sigterm_leaves_resumable_committed_checkpoint(tmp_path):
    """A real SIGTERM delivered mid-run (from the data iterator, so the
    timing is deterministic) finishes the in-flight step, writes a
    committed checkpoint, and returns normally."""
    cfg, mesh, sched = _tiny()
    params = tfm.transformer_init(jax.random.key(0), cfg)

    def killing_data():
        src = train.synthetic_data(cfg, 4, 8, seed=3)
        for i, batch in enumerate(src):
            if i == 3:
                os.kill(os.getpid(), signal.SIGTERM)
            yield batch

    prev = signal.getsignal(signal.SIGTERM)
    _, hist = train.fit(cfg, mesh, sched, params, killing_data(), 6,
                        optimizer=train.adamw(total_steps=6, warmup_steps=1),
                        verbose=False, log_every=1,
                        checkpoint_dir=str(tmp_path), checkpoint_every=100,
                        handle_preemption=True)
    assert hist[-1][0] == 3  # stopped after the in-flight step finished
    assert latest_committed_step_dir(str(tmp_path))[0] == 3
    # fit restored the previous signal disposition on exit
    assert signal.getsignal(signal.SIGTERM) == prev


def test_anomaly_budget_abort_checkpoints_and_reports(tmp_path):
    report_dir = tmp_path / "report"
    with pytest.raises(AnomalyBudgetExceeded, match="2 consecutive"):
        _fit(tmp_path / "ck", guard=AnomalyGuard(max_consecutive=2),
             fault_plan=FaultPlan(nan_grad_steps=(2, 3)),
             report_dir=str(report_dir))
    # the abort checkpointed the last GOOD params (every poisoned update
    # was selected away) and wrote the report before raising
    assert latest_committed_step_dir(str(tmp_path / "ck")) is not None
    events = [json.loads(ln) for ln in open(report_dir / "events.jsonl")]
    kinds = [e["kind"] for e in events]
    assert "anomaly" in kinds and "anomaly_abort" in kinds
    manifest = json.load(open(report_dir / "report.json"))
    assert manifest["counters"]["anomalies"] == 2
    assert manifest["resilience"]["anomaly_budget"] == 2
    assert manifest["resilience"]["anomalies"] == 2


# ---------------------------------------------------------------------------
# Serving: poisoned / invalid requests retire failed, slots survive
# ---------------------------------------------------------------------------


def test_serving_poisoned_and_overlong_requests_fail_soft(tmp_path):
    from distributed_training_with_pipeline_parallelism_tpu.models.generate import (
        generate)
    from distributed_training_with_pipeline_parallelism_tpu.serving import (
        Request, ServingEngine, make_serving_step_fn)
    from distributed_training_with_pipeline_parallelism_tpu.utils.telemetry import (
        RunReport, serving_summary, validate_report)

    cfg = dtpp.ModelConfig(dim=32, n_layers=4, n_heads=4, vocab_size=64,
                           ffn_dim=64, max_seq_len=64, arch="gpt2")
    params = tfm.transformer_init(jax.random.key(0), cfg)
    program = make_serving_step_fn(cfg, make_mesh(n_pipe=2), n_slots=2,
                                   max_len=12, prompt_max=8, out_max=8,
                                   prefill_chunk=2, eos_id=7)
    report = RunReport(out_dir=str(tmp_path), name="serve")
    engine = ServingEngine(program, params, report=report,
                           fault_plan=FaultPlan(serve_poison_rids=(1,),
                                                serve_delay={2: 3.0}))
    requests = [
        Request(rid=0, prompt=[5, 11, 2], max_new_tokens=4, arrival=0.0),
        Request(rid=1, prompt=[3, 4], max_new_tokens=4, arrival=1.0),
        # prompt + budget overflows max_len=12: must fail, not raise
        Request(rid=2, prompt=list(range(8)), max_new_tokens=8, arrival=2.0),
        Request(rid=3, prompt=[9, 1], max_new_tokens=4, arrival=3.0),
    ]
    res = engine.run(requests, policy="continuous")
    assert len(res.completions) == len(requests)
    status = {c.rid: c.status for c in res.completions}
    assert status[1] == "failed" and status[2] == "failed"
    assert status[0] == "ok" and status[3] == "ok"
    assert res.n_failed == 2
    # the survivors still bit-match the single-device oracle
    for c in res.completions:
        if c.status != "ok":
            continue
        req = requests[c.rid]
        want_toks, want_len = generate(cfg, params,
                                       np.asarray([req.prompt], np.int32),
                                       max_new_tokens=req.max_new_tokens,
                                       eos_id=7, return_lengths=True,
                                       max_len=program.mlen_alloc)
        n = int(want_len[0])
        assert c.tokens == [int(t) for t in
                            np.asarray(want_toks)[0][len(req.prompt):
                                                     len(req.prompt) + n]]
    # report surfaces the failures: events + serving summary row
    assert report.counters.get("serve_failed") == 2
    report.attach_serving(serving_summary(res))
    manifest = report.write()
    validate_report(manifest)
    (row,) = manifest["serving"]
    assert row["n_failed"] == 2 and row["n_requests"] == 2
