"""Pipeline-executor correctness: (loss, grads) vs single-device autodiff.

This is the verification the reference never performs (SURVEY.md §4: its only
integration signal is 'a metrics dict arrives on the queue') — a PP run must
match a single-device full-batch run numerically.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import distributed_training_with_pipeline_parallelism_tpu as dtpp
from distributed_training_with_pipeline_parallelism_tpu.models import transformer as tfm
from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import make_mesh
from distributed_training_with_pipeline_parallelism_tpu.parallel.pipeline import (
    make_pipeline_step, stack_stage_layers, unstack_stage_layers)

CFG = dtpp.ModelConfig(dim=32, n_layers=8, n_heads=4, vocab_size=50, ffn_dim=64)


@pytest.fixture(scope="module")
def problem():
    params = tfm.transformer_init(jax.random.key(0), CFG)
    tokens = jax.random.randint(jax.random.key(1), (16, 6), 0, CFG.vocab_size)
    targets = jax.random.randint(jax.random.key(2), (16, 6), 0, CFG.vocab_size)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: tfm.transformer_loss(CFG, p, tokens, targets))(params)
    return params, tokens, targets, ref_loss, ref_grads


def assert_matches_reference(loss, grads, ref_loss, ref_grads, tol=1e-5):
    assert float(jnp.abs(loss - ref_loss)) < tol
    err = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), grads, ref_grads)
    worst = max(jax.tree.leaves(err))
    assert worst < tol, f"max grad err {worst}"


@pytest.mark.parametrize("name,D,V,M", [
    ("GPipe", 2, 1, 4),
    ("GPipe", 4, 1, 4),
    ("GPipe", 8, 1, 8),
    ("1F1B", 2, 1, 4),
    ("1F1B", 4, 1, 8),
    ("1F1B", 8, 1, 8),
    ("Interleaved1F1B", 2, 2, 4),
    ("Interleaved1F1B", 4, 2, 8),
    ("Interleaved1F1B", 2, 4, 4),
    ("Interleaved1F1B", 4, 1, 4),  # degenerate: falls back to 1F1B layout
    ("BFS", 2, 2, 4),
    ("BFS", 4, 2, 4),
    ("BFS", 2, 4, 2),
    ("ZBV", 2, 2, 4),
    ("ZBV", 4, 2, 8),
])
def test_pipeline_matches_single_device(problem, name, D, V, M):
    params, tokens, targets, ref_loss, ref_grads = problem
    mesh = make_mesh(n_pipe=D)
    step = make_pipeline_step(
        CFG, mesh, dtpp.ScheduleConfig(name=name, n_microbatches=M, n_virtual=V))
    loss, grads = step(params, tokens, targets)
    assert_matches_reference(loss, grads, ref_loss, ref_grads)


def test_data_parallel_mesh(problem):
    params, tokens, targets, ref_loss, ref_grads = problem
    mesh = make_mesh(n_pipe=2, n_data=2)
    step = make_pipeline_step(
        CFG, mesh, dtpp.ScheduleConfig(name="1F1B", n_microbatches=2, n_virtual=1))
    # DP=2 x M=2 microbatches of 4 == the same 16-sample batch
    loss, grads = step(params, tokens, targets)
    assert_matches_reference(loss, grads, ref_loss, ref_grads)


def test_single_device_pipeline_degenerate(problem):
    params, tokens, targets, ref_loss, ref_grads = problem
    mesh = make_mesh(n_pipe=1)
    # force_tick_executor: exercise the real 1-stage tick program (the
    # default path lowers D=1 to plain value_and_grad, which would make this
    # test compare the reference against itself)
    step = make_pipeline_step(
        CFG, mesh, dtpp.ScheduleConfig(name="GPipe", n_microbatches=4),
        force_tick_executor=True)
    loss, grads = step(params, tokens, targets)
    assert_matches_reference(loss, grads, ref_loss, ref_grads)


def test_single_device_fast_path_matches_and_checks_batch(problem):
    params, tokens, targets, ref_loss, ref_grads = problem
    mesh = make_mesh(n_pipe=1)
    step = make_pipeline_step(
        CFG, mesh, dtpp.ScheduleConfig(name="GPipe", n_microbatches=4))
    loss, grads = step(params, tokens, targets)
    assert_matches_reference(loss, grads, ref_loss, ref_grads)
    with pytest.raises(AssertionError):  # batch 10 % M=4 != 0, like shard_map
        step(params, tokens[:10], targets[:10])


def test_stack_roundtrip():
    params = tfm.transformer_init(jax.random.key(0), CFG)
    for D, V in [(2, 1), (2, 2), (4, 2), (8, 1)]:
        stacked = stack_stage_layers(params["layers"], D, V)
        back = unstack_stage_layers(stacked)
        assert jax.tree.all(jax.tree.map(
            lambda a, b: bool(jnp.all(a == b)), params["layers"], back))


def test_stack_wrap_placement():
    # stage s = v*D + d must land at [d, v]; layers are contiguous per stage
    layers = {"w": jnp.arange(8.0)}
    stacked = stack_stage_layers(layers, 2, 2)  # D=2, V=2, S=4, 2 layers/stage
    # stage 0 = layers 0,1 -> device 0 v 0 ; stage 1 = layers 2,3 -> device 1 v 0
    # stage 2 = layers 4,5 -> device 0 v 1 ; stage 3 = layers 6,7 -> device 1 v 1
    np.testing.assert_array_equal(np.asarray(stacked["w"]),
                                  [[[0, 1], [4, 5]], [[2, 3], [6, 7]]])


def test_indivisible_layers_raises():
    mesh = make_mesh(n_pipe=2)
    cfg = dtpp.ModelConfig(dim=32, n_layers=5, n_heads=4, vocab_size=50, ffn_dim=64)
    params = tfm.transformer_init(jax.random.key(0), cfg)
    step = make_pipeline_step(cfg, mesh, dtpp.ScheduleConfig(name="GPipe"))
    with pytest.raises(ValueError):
        step(params, jnp.zeros((8, 4), jnp.int32), jnp.zeros((8, 4), jnp.int32))


def test_gpt2_and_llama_through_pipeline():
    for arch, kw in [("gpt2", {}), ("llama", dict(n_kv_heads=2))]:
        cfg = dtpp.ModelConfig(dim=32, n_layers=4, n_heads=4, vocab_size=50,
                               ffn_dim=64, max_seq_len=16, arch=arch, **kw)
        params = tfm.transformer_init(jax.random.key(0), cfg)
        tokens = jax.random.randint(jax.random.key(1), (8, 6), 0, cfg.vocab_size)
        targets = jax.random.randint(jax.random.key(2), (8, 6), 0, cfg.vocab_size)
        ref_loss, ref_grads = jax.value_and_grad(
            lambda p: tfm.transformer_loss(cfg, p, tokens, targets))(params)
        mesh = make_mesh(n_pipe=2)
        step = make_pipeline_step(
            cfg, mesh, dtpp.ScheduleConfig(name="1F1B", n_microbatches=4))
        loss, grads = step(params, tokens, targets)
        assert_matches_reference(loss, grads, ref_loss, ref_grads, tol=2e-5)


def test_pipeline_forward_returns_merged_logits(problem):
    """U5 parity: the forward-only pipeline returns the merged full-batch
    last-stage logits (upstream merge_chunks semantics), equal to the
    single-device forward."""
    from distributed_training_with_pipeline_parallelism_tpu.parallel.pipeline import (
        make_pipeline_forward)

    params, tokens, _, _, _ = problem
    want = tfm.transformer_apply(CFG, params, tokens)
    fwd = make_pipeline_forward(CFG, make_mesh(n_pipe=4),
                                dtpp.ScheduleConfig(name="GPipe",
                                                    n_microbatches=4))
    got = fwd(params, tokens)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_pipeline_forward_with_data_axis(problem):
    from distributed_training_with_pipeline_parallelism_tpu.parallel.pipeline import (
        make_pipeline_forward)

    params, tokens, _, _, _ = problem
    want = tfm.transformer_apply(CFG, params, tokens)
    fwd = make_pipeline_forward(CFG, make_mesh(n_pipe=2, n_data=2),
                                dtpp.ScheduleConfig(name="1F1B",
                                                    n_microbatches=2))
    np.testing.assert_allclose(np.asarray(fwd(params, tokens)),
                               np.asarray(want), atol=1e-5, rtol=1e-5)


def test_pipeline_forward_ignores_training_only_constraints(problem):
    """Batch inference with fewer microbatches than stages is legal: the
    forward order is fill-drain for every schedule."""
    from distributed_training_with_pipeline_parallelism_tpu.parallel.pipeline import (
        make_pipeline_forward)

    params, tokens, _, _, _ = problem
    want = tfm.transformer_apply(CFG, params, tokens)
    fwd = make_pipeline_forward(CFG, make_mesh(n_pipe=4),
                                dtpp.ScheduleConfig(name="1F1B",
                                                    n_microbatches=2))
    np.testing.assert_allclose(np.asarray(fwd(params, tokens)),
                               np.asarray(want), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("name,V,M", [
    ("1F1B", 1, 4), ("Interleaved1F1B", 2, 4), ("ZBV", 2, 4),
])
def test_unrolled_ticks_match_scan(problem, name, V, M):
    """Round 4 (VERDICT r3 item 2): the unrolled straight-line tick
    program (Python loop, cond/hop elision against the concrete table)
    and the lax.scan form are the same executor — identical loss/grads.
    Small tables auto-unroll, so the scan path needs this explicit
    exercise; both are also held to the single-device oracle."""
    params, tokens, targets, ref_loss, ref_grads = problem
    mesh = make_mesh(n_pipe=2)
    sched = dtpp.ScheduleConfig(name=name, n_microbatches=M, n_virtual=V)
    remats = (None,) if name == "ZBV" else (None, False)  # ZBV: split bwd
    for remat in remats:
        lu, gu = make_pipeline_step(CFG, mesh, sched, unroll_ticks=True,
                                    remat_backward=remat)(
            params, tokens, targets)
        ls, gs = make_pipeline_step(CFG, mesh, sched, unroll_ticks=False,
                                    remat_backward=remat)(
            params, tokens, targets)
        assert float(jnp.abs(lu - ls)) < 1e-6, (name, remat)
        err = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                           gu, gs)
        assert max(jax.tree.leaves(err)) < 1e-5, (name, remat)
        assert_matches_reference(lu, gu, ref_loss, ref_grads)


def test_auto_unroll_past_32_rows_matches_scan(problem):
    """Round 5 (VERDICT r4 item 1): _UNROLL_TICKS_LIMIT was raised 32->64
    from chip measurements (docs/performance.md "Unroll-vs-scan crossover"), so
    ladder-scale tables (>32 rows) now AUTO-unroll. The auto path must
    equal the explicit scan form and the single-device oracle at a table
    size the old limit would have scanned. GPipe D=2 M=16 is 33 rows; the
    1F1B table this test used until PR 29 packs into 18."""
    from distributed_training_with_pipeline_parallelism_tpu.parallel.pipeline import (
        _UNROLL_TICKS_LIMIT, _compile)

    params, tokens, targets, ref_loss, ref_grads = problem
    M = 16
    rows = _compile("GPipe", 2, 1, M).table.shape[0]
    assert 32 < rows <= _UNROLL_TICKS_LIMIT, rows
    mesh = make_mesh(n_pipe=2)
    sched = dtpp.ScheduleConfig(name="GPipe", n_microbatches=M)
    # oracle-only: unroll==scan equivalence is already asserted at smaller
    # tables (test_unrolled_ticks_match_scan); compiling the scan twin of
    # this 33-row program would double an already-heavy 1-core-CI test
    la, ga = make_pipeline_step(CFG, mesh, sched,
                                remat_backward=True)(params, tokens, targets)
    assert_matches_reference(la, ga, ref_loss, ref_grads)


def test_packed_tick_logs_its_table_and_fences_forward_from_backward(
        problem, caplog):
    """PR 29: the build logs rows, work cells and packed cells once, and a
    tick that holds a forward AND a backward unit runs them one after the
    other — an ``optimization_barrier`` between the two, without which the
    v5e's compiler interleaves them and gpt2-xl D=4 no longer fits its HBM
    (PERF.md, PR 29). A tick with one kind of unit needs no fence."""
    import logging

    from distributed_training_with_pipeline_parallelism_tpu.parallel.pipeline import (
        make_pipeline_grad_fn)
    params, tokens, targets, _, _ = problem
    mesh = make_mesh(n_pipe=4)
    with caplog.at_level(logging.INFO):
        fn = make_pipeline_grad_fn(
            CFG, mesh, dtpp.ScheduleConfig(name="1F1B", n_microbatches=8))
    lines = [r.getMessage() for r in caplog.records if "tick table" in r.getMessage()]
    assert len(lines) == 1, lines
    assert "14 rows, 44 of 56 cells work, 20 of them packed" in lines[0]
    jaxpr = str(jax.make_jaxpr(fn)(params, tokens, targets))
    # ticks 3..10 of the 14 hold both kinds of unit on some stage
    assert jaxpr.count("optimization_barrier") == 8


def test_phase_executor_matches_scan_light(problem):
    """The phase-compressed executor (unroll_ticks="phases") is the same
    program as the cond-dispatched scan — identical loss/grads — and both
    match the unrolled form and the single-device oracle. Light config for
    tier-1; the full six-schedule grid is the slow-marked test below."""
    params, tokens, targets, ref_loss, ref_grads = problem
    mesh = make_mesh(n_pipe=2)
    sched = dtpp.ScheduleConfig(name="1F1B", n_microbatches=4)
    outs = {}
    for mode in ("phases", False, True):
        outs[mode] = make_pipeline_step(
            CFG, mesh, sched, remat_backward=True, unroll_ticks=mode)(
            params, tokens, targets)
    lp, gp = outs["phases"]
    for other in (False, True):
        lo, go = outs[other]
        assert float(jnp.abs(lp - lo)) == 0.0, other
        err = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                           gp, go)
        assert max(jax.tree.leaves(err)) == 0.0, other
    assert_matches_reference(lp, gp, ref_loss, ref_grads)


@pytest.mark.slow
@pytest.mark.parametrize("name,D,V,M,kw", [
    ("GPipe", 2, 1, 4, {}),
    ("1F1B", 4, 1, 8, {}),
    ("1F1B", 2, 1, 4, {"remat_backward": False}),  # stored (slot-banked vjp)
    ("Interleaved1F1B", 2, 2, 4, {}),
    ("BFS", 2, 2, 4, {}),
    ("ZBH1", 4, 1, 8, {}),
    ("ZBV", 2, 2, 4, {}),
])
def test_phase_executor_matches_scan_all_schedules(problem, name, D, V, M, kw):
    """Acceptance grid: bit-exact phases-vs-scan parity on every builtin
    schedule family (incl. split-backward ZB and the stored policy)."""
    params, tokens, targets, ref_loss, ref_grads = problem
    mesh = make_mesh(n_pipe=D)
    sched = dtpp.ScheduleConfig(name=name, n_microbatches=M, n_virtual=V)
    kw = dict({"remat_backward": True}, **kw)
    lp, gp = make_pipeline_step(CFG, mesh, sched, unroll_ticks="phases",
                                **kw)(params, tokens, targets)
    ls, gs = make_pipeline_step(CFG, mesh, sched, unroll_ticks=False,
                                **kw)(params, tokens, targets)
    assert float(jnp.abs(lp - ls)) == 0.0
    err = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), gp, gs)
    assert max(jax.tree.leaves(err)) == 0.0
    assert_matches_reference(lp, gp, ref_loss, ref_grads)


@pytest.mark.slow
def test_phase_executor_matches_scan_custom_schedule(problem):
    """register_schedule tables run the phase executor too (acceptance:
    one custom schedule in the parity grid)."""
    from distributed_training_with_pipeline_parallelism_tpu.parallel.schedules import (
        Action, B, F, register_schedule, unregister_schedule)

    def reverse_drain(D, V, M):
        del V
        return [[Action(d, F, m) for m in range(M)]
                + [Action(d, B, m) for m in reversed(range(M))]
                for d in range(D)]

    params, tokens, targets, ref_loss, ref_grads = problem
    register_schedule("PhaseRevDrain", reverse_drain)
    try:
        mesh = make_mesh(n_pipe=2)
        sched = dtpp.ScheduleConfig(name="PhaseRevDrain", n_microbatches=4)
        lp, gp = make_pipeline_step(CFG, mesh, sched, remat_backward=True,
                                    unroll_ticks="phases")(
            params, tokens, targets)
        ls, gs = make_pipeline_step(CFG, mesh, sched, remat_backward=True,
                                    unroll_ticks=False)(
            params, tokens, targets)
        assert float(jnp.abs(lp - ls)) == 0.0
        err = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                           gp, gs)
        assert max(jax.tree.leaves(err)) == 0.0
        assert_matches_reference(lp, gp, ref_loss, ref_grads)
    finally:
        unregister_schedule("PhaseRevDrain")


def test_phase_executor_trace_count(problem):
    """Acceptance: the number of PYTHON TRACES of phase bodies (each trace
    = one compiled tick body; lax.scan caches body jaxprs per function
    object) is bounded by unique patterns + 2, and is INDEPENDENT of M for
    steady-state-periodic 1F1B — the whole point of the formulation.
    Trace-only (jit lower, no XLA compile) keeps this test cheap."""
    from distributed_training_with_pipeline_parallelism_tpu.parallel import (
        pipeline as pl)
    from distributed_training_with_pipeline_parallelism_tpu.parallel.schedules import (
        compress_schedule, phase_stats)

    params, tokens, targets, _, _ = problem
    mesh = make_mesh(n_pipe=4)
    counts = {}
    for M in (8, 16):
        sched = dtpp.ScheduleConfig(name="1F1B", n_microbatches=M)
        n = 0

        def hook():
            nonlocal n
            n += 1

        fn = pl.make_pipeline_grad_fn(CFG, mesh, sched, remat_backward=True,
                                      unroll_ticks="phases")
        pl._PHASE_TRACE_HOOK = hook
        try:
            jax.jit(fn).lower(params, tokens, targets)
        finally:
            pl._PHASE_TRACE_HOOK = None
        assert n > 0
        st = phase_stats(compress_schedule(pl._compile("1F1B", 4, 1, M).table))
        assert n <= st["n_unique_patterns"] + 2, (M, n, st)
        counts[M] = n
    # the compile-cost invariant: more microbatches = more ticks but the
    # SAME set of tick bodies (steady state grows in reps, not patterns)
    assert counts[8] == counts[16], counts


# ---------------------------------------------------------------------------
# Every executor form: no host callback, ever
# ---------------------------------------------------------------------------

# form -> (pipe degree, make_pipeline_grad_fn kwargs, the schedules it
# accepts). The device's time is read from the profiler's trace by the
# names of the program's regions (tests/test_telemetry.py holds each form
# to those names), never stamped from inside the program.
EXECUTOR_FORMS = {
    "fused": (1, {}, ("GPipe", "1F1B")),
    "unrolled": (2, {"unroll_ticks": True},
                 ("GPipe", "1F1B", "Interleaved1F1B", "BFS", "ZBH1", "ZBV")),
    "phases": (2, {"unroll_ticks": "phases"},
               ("GPipe", "1F1B", "Interleaved1F1B", "BFS", "ZBH1", "ZBV")),
    "scan": (2, {"unroll_ticks": False},
             ("GPipe", "1F1B", "Interleaved1F1B", "BFS", "ZBH1", "ZBV")),
    # remat_backward=False: all-F-then-all-B schedules differentiate
    # through the forward tick scan; a one-stage pipe forced onto the
    # tick path takes the same program by default
    "phase_stored": (2, {"remat_backward": False}, ("GPipe", "BFS")),
    "phase_stored_d1": (1, {"force_tick_executor": True}, ("1F1B",)),
    # ... every other non-split schedule banks vjp residuals in slots
    "slot_stored": (2, {"remat_backward": False},
                    ("1F1B", "Interleaved1F1B")),
}
_FORM_CFG = dtpp.ModelConfig(dim=16, n_layers=4, n_heads=2, vocab_size=32,
                             ffn_dim=32, max_seq_len=8)
_TWO_CHUNK = ("Interleaved1F1B", "BFS", "ZBV")


def build_executor_form(form, name):
    """``(grad_fn, mesh, args)`` of one executor form at a tiny size, for
    tests that trace or lower it and run nothing."""
    from distributed_training_with_pipeline_parallelism_tpu.parallel.pipeline import (
        make_pipeline_grad_fn)
    D, kw, _ = EXECUTOR_FORMS[form]
    mesh = make_mesh(n_pipe=D)
    sched = dtpp.ScheduleConfig(
        name=name, n_microbatches=4,
        n_virtual=2 if D > 1 and name in _TWO_CHUNK else 1)
    fn = make_pipeline_grad_fn(_FORM_CFG, mesh, sched, **kw)
    params = tfm.transformer_init(jax.random.key(0), _FORM_CFG)
    tokens = jnp.zeros((4, 8), jnp.int32)
    return fn, mesh, (params, tokens, tokens)


@pytest.mark.parametrize("form,name", [
    (form, name) for form, (_, _, names) in EXECUTOR_FORMS.items()
    for name in names])
def test_no_host_callback_in_any_executor(form, name):
    from distributed_training_with_pipeline_parallelism_tpu.analysis.jaxpr_audit import (
        audit_fn)
    fn, mesh, args = build_executor_form(form, name)
    audit = audit_fn(fn, *args, mesh_axes=tuple(mesh.axis_names),
                     expect_no_callbacks=True)
    assert audit.n_callbacks == 0
    assert audit.ok, audit.problems
