"""Comm/compute overlap: double-buffered executors + collective matmuls.

Pins the three legs of the overlap contract (docs/performance.md
"Comm/compute overlap"):

- **Executor bit-parity**: the double-buffered ring executor
  (``comm_overlap="ring"``) defers each edge-slot commit to its bank
  stage so last tick's ppermute overlaps this tick's compute — and must
  produce BIT-IDENTICAL loss and grads to the lockstep program on every
  schedule family (the static proof is ``table_check``'s overlap
  discipline; this is the dynamic witness).
- **Collective-matmul parity**: the ring ``all_gather_matmul`` /
  ``matmul_reduce_scatter`` TP kernels (``tp_overlap="ring"``) match the
  unfused gather-then-matmul Megatron MLP in forward AND grads (ring
  gather is bit-exact per block; ring reduce-scatter reassociates the
  sum, so numerical tolerance there).
- **Census + cost model**: traced ppermutes stay equal to the table's
  predicted comm volume under deferral (the hop never moves, only the
  commit), the ring MLP traces exactly ``(T-1)`` hops per collective,
  and ``comm_overlap_step_time`` sits inside the
  ``step_s_overlapped <= step_s_comm_overlap <= step_s`` sandwich.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import distributed_training_with_pipeline_parallelism_tpu as dtpp
from distributed_training_with_pipeline_parallelism_tpu.analysis.cost_model import (
    comm_overlap_step_time, predicted_step_time)
from distributed_training_with_pipeline_parallelism_tpu.analysis.jaxpr_audit import (
    audit_fn, collective_matmul_ppermutes)
from distributed_training_with_pipeline_parallelism_tpu.analysis.table_check import (
    check_table)
from distributed_training_with_pipeline_parallelism_tpu.models import transformer as tfm
from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import make_mesh
from distributed_training_with_pipeline_parallelism_tpu.parallel.pipeline import (
    _compile, make_pipeline_step)
from distributed_training_with_pipeline_parallelism_tpu.parallel.schedules import (
    BANK_BEFORE_F, overlap_bank_stages)
from distributed_training_with_pipeline_parallelism_tpu.parallel.tensor_parallel import (
    resolve_tp_overlap)

CFG = dtpp.ModelConfig(dim=32, n_layers=8, n_heads=4, vocab_size=50,
                       ffn_dim=64)


@pytest.fixture(scope="module")
def problem():
    params = tfm.transformer_init(jax.random.key(0), CFG)
    tokens = jax.random.randint(jax.random.key(1), (16, 6), 0,
                                CFG.vocab_size)
    targets = jax.random.randint(jax.random.key(2), (16, 6), 0,
                                 CFG.vocab_size)
    return params, tokens, targets


# ---------------------------------------------------------------------------
# executor bit-parity: overlapped vs lockstep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,D,V,M,ut", [
    # the D=2 rows witness the bit-parity contract for every schedule
    # family inside the tier-1 OVERLAP budget; the D=4 twins and ZBV
    # (heavier unrolled programs, same code paths) ride the slow lane
    ("GPipe", 2, 1, 4, True),
    pytest.param("GPipe", 4, 1, 4, True, marks=pytest.mark.slow),
    ("1F1B", 2, 1, 4, True),
    pytest.param("1F1B", 4, 1, 4, True, marks=pytest.mark.slow),
    ("Interleaved1F1B", 2, 2, 4, True),
    pytest.param("Interleaved1F1B", 4, 2, 4, True,
                 marks=pytest.mark.slow),
    # phase-compressed executor (remat: the phase-STORED backward has no
    # per-tick bank sites and rejects ring, pinned below)
    ("1F1B", 2, 1, 4, "phases"),
    # split-backward families: W units read the banked act/grad slots,
    # so their bank stages exercise the BEFORE_W deferral leg
    ("ZBH1", 2, 1, 4, True),
    pytest.param("ZBV", 2, 2, 4, True, marks=pytest.mark.slow),
])
def test_ring_executor_bit_parity(problem, name, D, V, M, ut):
    params, tokens, targets = problem
    mesh = make_mesh(n_pipe=D)
    sched = dtpp.ScheduleConfig(name=name, n_microbatches=M, n_virtual=V)
    remat = True if ut == "phases" else None
    base = make_pipeline_step(CFG, mesh, sched, unroll_ticks=ut,
                              remat_backward=remat, comm_overlap="none")
    ring = make_pipeline_step(CFG, mesh, sched, unroll_ticks=ut,
                              remat_backward=remat, comm_overlap="ring")
    l0, g0 = jax.block_until_ready(base(params, tokens, targets))
    l1, g1 = jax.block_until_ready(ring(params, tokens, targets))
    assert jnp.array_equal(l0, l1), (float(l0), float(l1))
    mismatch = [k for (k, a), (_, b) in
                zip(jax.tree_util.tree_leaves_with_path(g0),
                    jax.tree_util.tree_leaves_with_path(g1))
                if not bool(jnp.array_equal(a, b))]
    assert not mismatch, f"grads not bit-identical: {mismatch}"


def test_ring_rejects_scan_executor(problem):
    mesh = make_mesh(n_pipe=2)
    sched = dtpp.ScheduleConfig(name="1F1B", n_microbatches=4)
    with pytest.raises(ValueError, match="unroll_ticks"):
        make_pipeline_step(CFG, mesh, sched, unroll_ticks=False,
                           comm_overlap="ring")


def test_ring_rejects_phase_stored_backward(problem):
    # GPipe at D>1 with remat_backward=False selects the phase-stored
    # program (pipeline.py backward-policy table) — the one executor with
    # no per-tick bank sites for the deferred edge-slot commits
    mesh = make_mesh(n_pipe=2)
    sched = dtpp.ScheduleConfig(name="GPipe", n_microbatches=4)
    with pytest.raises(ValueError, match="phase-stored"):
        make_pipeline_step(CFG, mesh, sched, unroll_ticks="phases",
                           remat_backward=False, comm_overlap="ring")


def test_auto_falls_back_to_lockstep_on_scan(problem):
    # auto must never raise: the scan executor silently keeps lockstep
    params, tokens, targets = problem
    mesh = make_mesh(n_pipe=2)
    sched = dtpp.ScheduleConfig(name="GPipe", n_microbatches=4)
    step = make_pipeline_step(CFG, mesh, sched, unroll_ticks=False,
                              comm_overlap="auto")
    loss, _ = step(params, tokens, targets)
    assert bool(jnp.isfinite(loss))


# ---------------------------------------------------------------------------
# traced-hop census: deferral moves the commit, never the hop
# ---------------------------------------------------------------------------

def test_ring_executor_traces_predicted_ppermutes(problem):
    params, tokens, targets = problem
    D, M = 4, 4
    predicted = check_table(_compile("1F1B", D, 1, M)).predicted_ppermutes
    mesh = make_mesh(n_pipe=D)
    sched = dtpp.ScheduleConfig(name="1F1B", n_microbatches=M)
    counts = {}
    for mode in ("none", "ring"):
        step = make_pipeline_step(CFG, mesh, sched, unroll_ticks=True,
                                  comm_overlap=mode)
        audit = audit_fn(step, params, tokens, targets,
                         mesh_axes=tuple(mesh.axis_names),
                         expected_ppermutes=predicted)
        assert audit.ok, audit.problems
        counts[mode] = audit.ppermute_count
    assert counts["none"] == counts["ring"] == predicted


def test_overlap_discipline_in_table_reports():
    # every registered family: the verifier's independent re-derivation
    # finds no overlap hazards, and per channel the exposed/overlappable
    # split partitions that channel's live hop ticks exactly
    for name, D, V, M in (("GPipe", 4, 1, 8), ("1F1B", 4, 1, 8),
                          ("Interleaved1F1B", 4, 2, 8), ("ZBH1", 4, 1, 8),
                          ("ZBV", 4, 2, 8), ("BFS", 4, 2, 8)):
        report = check_table(_compile(name, D, V, M))
        assert report.ok, (name, report.hazards)
        assert not [h for h in report.hazards
                    if h.kind.startswith("overlap-")], (name, report.hazards)
        assert report.overlap, name
        total = 0
        for key, row in report.overlap.items():
            live = report.comm[key]["hop_ticks"]
            split = row["exposed_hop_ticks"] + row["overlappable_hop_ticks"]
            assert split == live, (name, key, row, live)
            total += split
        assert total == report.predicted_ppermutes, name
        st = overlap_bank_stages(report.table if hasattr(report, "table")
                                 else _compile(name, D, V, M).table)
        # at least one hop must actually defer on a real pipeline — a
        # discipline that never defers would make the whole mode a no-op
        assert (st > BANK_BEFORE_F).any(), name


# ---------------------------------------------------------------------------
# collective-matmul TP kernels: parity + census
# ---------------------------------------------------------------------------

_TP = 4


def _tp_problem(arch):
    cfg = dtpp.ModelConfig(vocab_size=64, dim=32, n_heads=4, n_layers=2,
                           ffn_dim=64, max_seq_len=16, dtype="float32",
                           arch=arch)
    params = tfm.layer_init(jax.random.key(0), cfg)
    h = jax.random.normal(jax.random.key(1), (2, 8, cfg.dim))
    if arch == "gpt2":
        specs = {"lin1": {"w": P(None, "model"), "b": P("model")},
                 "lin2": {"w": P("model", None), "b": P(None)}}
    else:
        specs = {"w1": {"w": P(None, "model")}, "w3": {"w": P(None, "model")},
                 "w2": {"w": P("model", None)}}
    full = {k: specs.get(k, jax.tree.map(lambda _: P(), params[k]))
            for k in params}
    return cfg, params, h, full


def _tp_loss_fn(cfg, full_specs, mesh):
    def inner(p, x):
        return tfm.mlp_block(cfg, p, x, tp_axis="model", tp_size=_TP)
    f = jax.shard_map(inner, mesh=mesh, in_specs=(full_specs, P()),
                      out_specs=P(), check_vma=False)
    return lambda p, x: jnp.sum(f(p, x) ** 2)


@pytest.mark.parametrize("arch", ["gpt2", "llama"])
def test_collective_matmul_matches_unfused(arch):
    cfg, params, h, full = _tp_problem(arch)
    mesh = Mesh(np.array(jax.devices()[:_TP]), ("model",))
    vals, grads = {}, {}
    for mode in ("none", "ring"):
        mcfg = dataclasses.replace(cfg, tp_overlap=mode)
        vals[mode], grads[mode] = jax.value_and_grad(
            _tp_loss_fn(mcfg, full, mesh))(params, h)
    np.testing.assert_allclose(vals["none"], vals["ring"],
                               rtol=2e-5, atol=2e-5)
    for (kp, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(grads["none"]),
            jax.tree_util.tree_leaves_with_path(grads["ring"])):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5,
                                   err_msg=str(kp))


def test_collective_matmul_census():
    cfg, params, h, full = _tp_problem("gpt2")
    mesh = Mesh(np.array(jax.devices()[:_TP]), ("model",))
    rcfg = dataclasses.replace(cfg, tp_overlap="ring")
    fwd = jax.shard_map(
        lambda p, x: tfm.mlp_block(rcfg, p, x, tp_axis="model", tp_size=_TP),
        mesh=mesh, in_specs=(full, P()), out_specs=P(), check_vma=False)
    # gpt2 ring MLP: up-proj gather-matmul + down-proj matmul-scatter +
    # the residual's seq_all_gather = 2 gathers + 1 scatter
    expected = collective_matmul_ppermutes(_TP, n_gathers=2, n_scatters=1)
    audit = audit_fn(fwd, params, h, mesh_axes=("model",),
                     expected_ppermutes=expected)
    assert audit.ok, audit.problems
    # no bare all_gather/psum_scatter may appear on the ring path
    assert not any(k.startswith(("all_gather", "psum_scatter"))
                   for k in audit.collectives), audit.collectives


def test_resolve_tp_overlap_modes():
    assert resolve_tp_overlap("none", 4, 16) == "none"
    assert resolve_tp_overlap("ring", 4, 16) == "ring"
    with pytest.raises(ValueError, match="divis"):
        resolve_tp_overlap("ring", 4, 6)
    with pytest.raises(ValueError, match="tp_overlap"):
        resolve_tp_overlap("bogus", 4, 16)
    # auto on a cpu backend falls back to the unfused XLA collectives
    assert resolve_tp_overlap("auto", 4, 16) == "none"
    assert resolve_tp_overlap("auto", 4, 6) == "none"


def test_model_config_validates_tp_overlap():
    with pytest.raises(ValueError, match="tp_overlap"):
        dtpp.ModelConfig(tp_overlap="sidecar")


# ---------------------------------------------------------------------------
# cost model: the overlap sandwich
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,D,V,M", [
    ("GPipe", 4, 1, 8), ("1F1B", 4, 1, 8), ("Interleaved1F1B", 4, 2, 8),
    ("ZBH1", 4, 1, 8), ("ZBV", 4, 2, 8),
])
def test_comm_overlap_step_time_sandwich(name, D, V, M):
    cs = _compile(name, D, V, M)
    unit_s, hop_s = (1.0, 2.0, 1.0), 0.25
    hops = check_table(cs).predicted_ppermutes
    base = predicted_step_time(cs.table, unit_s, hop_s, hops)
    ov = comm_overlap_step_time(cs.table, unit_s, hop_s)
    mid = ov["step_s_comm_overlap"]
    assert base["step_s_overlapped"] <= mid + 1e-9, (name, base, ov)
    assert mid <= base["step_s"] + 1e-9, (name, base, ov)
    # hops exist on any D>1 pipeline, so pure-lockstep must cost MORE
    # than the overlapped mode at a nonzero hop price
    assert mid < base["step_s"], (name, base, ov)
