"""Stored-activation pipeline backward (remat_backward) correctness.

``remat_backward=False`` banks the stage body's vjp residuals per slot and
replays them (no forward recompute) — matching the reference's
torch-autograd semantics (its backward stashes saved tensors, never
recomputes: ``LLMsDistributedTrainingHelper.py:98-143`` via upstream
``stage.py:857/937``). Past one device the default is the rematerialising
backward, which tests/test_pipeline.py's schedule grid holds to the oracle.
These tests pin:

- oracle equality of the stored policy against single-device autodiff
  across schedules and depths, and that the default IS the remat program,
- the residual taint classification (weights are never slot-stored),
- the compiled-FLOP ordering (remat pays the recompute, stored does not),
- the unsupported-configuration errors (split-backward schedules, fsdp).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import distributed_training_with_pipeline_parallelism_tpu as dtpp
from distributed_training_with_pipeline_parallelism_tpu.models import transformer as tfm
from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import make_mesh
from distributed_training_with_pipeline_parallelism_tpu.parallel.pipeline import (
    make_pipeline_step)
from distributed_training_with_pipeline_parallelism_tpu.parallel.stored_backward import (
    x_dependent_mask)

CFG = dtpp.ModelConfig(dim=32, n_layers=8, n_heads=4, vocab_size=50,
                       ffn_dim=64)


@pytest.fixture(scope="module")
def problem():
    params = tfm.transformer_init(jax.random.key(0), CFG)
    tokens = jax.random.randint(jax.random.key(1), (16, 6), 0, CFG.vocab_size)
    targets = jax.random.randint(jax.random.key(2), (16, 6), 0,
                                 CFG.vocab_size)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: tfm.transformer_loss(CFG, p, tokens, targets))(params)
    return params, tokens, targets, ref_loss, ref_grads


def assert_matches(loss, grads, ref_loss, ref_grads, tol=1e-5):
    assert float(jnp.abs(loss - ref_loss)) < tol
    err = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                       grads, ref_grads)
    worst = max(jax.tree.leaves(err))
    assert worst < tol, f"max grad err {worst}"


@pytest.mark.parametrize("name,D,V,M,remat", [
    ("GPipe", 2, 1, 4, False),
    ("1F1B", 4, 1, 8, False),
    ("Interleaved1F1B", 2, 2, 4, False),
    ("BFS", 4, 2, 4, False),
])
def test_policy_matches_single_device(problem, name, D, V, M, remat):
    params, tokens, targets, ref_loss, ref_grads = problem
    mesh = make_mesh(n_pipe=D)
    step = make_pipeline_step(
        CFG, mesh,
        dtpp.ScheduleConfig(name=name, n_microbatches=M, n_virtual=V),
        remat_backward=remat)
    loss, grads = step(params, tokens, targets)
    assert_matches(loss, grads, ref_loss, ref_grads)


def _jaxpr_text(name, V, **kw):
    """The tiny two-stage grad program's jaxpr, as text."""
    from test_pipeline import _FORM_CFG
    from distributed_training_with_pipeline_parallelism_tpu.parallel.pipeline import (
        make_pipeline_grad_fn)
    fn = make_pipeline_grad_fn(
        _FORM_CFG, make_mesh(n_pipe=2),
        dtpp.ScheduleConfig(name=name, n_microbatches=4, n_virtual=V), **kw)
    params = tfm.transformer_init(jax.random.key(0), _FORM_CFG)
    tokens = jnp.zeros((4, 8), jnp.int32)
    return str(jax.make_jaxpr(fn)(params, tokens, tokens))


@pytest.mark.parametrize("name,V", [
    ("GPipe", 1), ("1F1B", 1), ("Interleaved1F1B", 2)])
def test_default_policy_is_remat_past_one_device(name, V):
    """So the schedule grid of tests/test_pipeline.py, which runs the
    default, is the remat policy's oracle grid."""
    assert _jaxpr_text(name, V) == _jaxpr_text(name, V, remat_backward=True)


def test_stored_rejects_split_backward():
    mesh = make_mesh(n_pipe=2)
    with pytest.raises(ValueError, match="split-backward"):
        make_pipeline_step(
            CFG, mesh, dtpp.ScheduleConfig(name="ZBH1", n_microbatches=4),
            remat_backward=False)


def test_stored_rejects_fsdp():
    mesh = make_mesh(n_pipe=2, n_data=2)
    with pytest.raises(ValueError, match="fsdp"):
        make_pipeline_step(
            CFG, mesh, dtpp.ScheduleConfig(name="GPipe", n_microbatches=4),
            fsdp=True, remat_backward=False)


def test_split_backward_auto_falls_back():
    # auto policy on a ZB schedule silently keeps remat (the program
    # tests/test_zero_bubble.py holds to the oracle)
    assert _jaxpr_text("ZBH1", 1) == _jaxpr_text("ZBH1", 1,
                                                 remat_backward=True)


def test_taint_mask_excludes_weights():
    """The stage body's parameter-derived residuals (incl. their bf16
    casts) must classify as recomputable — only x-dependent activations
    get slot buffers. A regression here is silent memory blowup, not a
    wrong answer, so pin it structurally."""
    from distributed_training_with_pipeline_parallelism_tpu.models.transformer import (
        body_apply, compute_cast, transformer_init)
    cfg = dtpp.ModelConfig(dim=32, n_layers=2, n_heads=4, vocab_size=50,
                           ffn_dim=64, dtype="bfloat16")
    layers = transformer_init(jax.random.key(0), cfg)["layers"]
    x = jnp.zeros((2, 8, cfg.dim), jnp.bfloat16)

    def f_body(p, xi):
        return body_apply(cfg, compute_cast(cfg, p), xi)

    def vjp_leaves(p, xi):
        _, vjp_fn = jax.vjp(f_body, p, xi)
        return tuple(jax.tree.leaves(vjp_fn))

    mask = x_dependent_mask(vjp_leaves, (layers, x), (1,))
    structs = jax.eval_shape(vjp_leaves, layers, x)
    # every weight-matrix-shaped residual (>= dim*dim elements per layer,
    # no microbatch axis) must be recomputed, not stored
    stored = [s for m, s in zip(mask, structs) if m]
    assert stored, "no residuals classified as stored at all"
    for s in stored:
        # stored activations carry the microbatch axis (size 2 here) right
        # after the per-layer stack axis; weight stacks ([L, dim, ...]) do
        # not — dim 32 != mb 2 makes the check unambiguous
        assert s.shape[1] == 2, f"weight-like residual stored: {s.shape}"
    # and the split must be non-trivial in both directions
    assert any(not m for m in mask)


def test_stored_fewer_flops_than_remat(problem):
    """The feature's point: the stored backward's compiled program must do
    materially fewer FLOPs (no stage-forward recompute; the dummy-x
    re-trace is dead-code-eliminated)."""
    params, tokens, targets, *_ = problem
    mesh = make_mesh(n_pipe=2)
    sched = dtpp.ScheduleConfig(name="GPipe", n_microbatches=4)

    def flops(remat):
        step = make_pipeline_step(CFG, mesh, sched, remat_backward=remat)
        c = step.lower(params, tokens, targets).compile()
        ca = c.cost_analysis()
        ca = ca[0] if isinstance(ca, list) else ca
        return float(ca["flops"])

    f_stored, f_remat = flops(False), flops(True)
    # remat recomputes every stage forward in backward: expect >= 15% more
    # work even on this tiny config (head/CE recompute narrows the gap)
    assert f_remat > 1.15 * f_stored, (f_stored, f_remat)


def test_stored_with_dropout(problem):
    """Dropout masks ride the stored residuals — bitwise the forward's own
    draw, so the stored run equals the manual microbatched oracle (the
    executor's dropout contract: rng = fold_in(step_key, m) per microbatch,
    tests/test_dropout.py)."""
    cfg = dtpp.ModelConfig(dim=32, n_layers=4, n_heads=4, vocab_size=50,
                           ffn_dim=64, dropout=0.1)
    params = tfm.transformer_init(jax.random.key(3), cfg)
    tokens = jax.random.randint(jax.random.key(4), (8, 6), 0, 50)
    targets = jax.random.randint(jax.random.key(5), (8, 6), 0, 50)
    rng = jax.random.key(7)
    M = 2
    tokens_mb = tokens.reshape(M, -1, tokens.shape[1])
    targets_mb = targets.reshape(M, -1, targets.shape[1])

    def manual(p):
        return sum(
            tfm.transformer_loss(cfg, p, tokens_mb[m], targets_mb[m],
                                 rng=jax.random.fold_in(rng, m))
            for m in range(M)) / M

    ref_loss, ref_grads = jax.value_and_grad(manual)(params)
    mesh = make_mesh(n_pipe=2)
    step = make_pipeline_step(
        cfg, mesh, dtpp.ScheduleConfig(name="1F1B", n_microbatches=M),
        remat_backward=False)
    loss, grads = step(params, tokens, targets, rng)
    assert_matches(loss, grads, ref_loss, ref_grads, tol=2e-5)
