"""Mixed-precision master weights and cross-step gradient accumulation.

Both are beyond-reference capabilities (the reference runs fp32 CPU with no
optimizer at all, SURVEY.md §3.3). Contracts: with
``dtype="bfloat16", param_dtype="float32"`` the parameters, gradients, and
optimizer moments stay fp32 while compute runs bf16; ``grad_accum=k`` steps
the optimizer exactly as one k-times-larger batch would.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import distributed_training_with_pipeline_parallelism_tpu as dtpp
from distributed_training_with_pipeline_parallelism_tpu.models import transformer as tfm
from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import make_mesh
from distributed_training_with_pipeline_parallelism_tpu.parallel.pipeline import (
    make_pipeline_step)

MIXED = dtpp.ModelConfig(dim=32, n_layers=4, n_heads=4, vocab_size=50,
                         ffn_dim=64, dtype="bfloat16", param_dtype="float32")
BF16 = dtpp.ModelConfig(dim=32, n_layers=4, n_heads=4, vocab_size=50,
                        ffn_dim=64, dtype="bfloat16")


def test_params_stored_fp32():
    params = tfm.transformer_init(jax.random.key(0), MIXED)
    assert all(x.dtype == jnp.float32 for x in jax.tree.leaves(params))
    # no mixing configured -> storage == compute dtype
    p16 = tfm.transformer_init(jax.random.key(0), BF16)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(p16))


def test_single_device_grads_fp32_and_close_to_bf16_loss():
    params = tfm.transformer_init(jax.random.key(0), MIXED)
    tokens = jax.random.randint(jax.random.key(1), (4, 8), 0, 50)
    loss, grads = jax.value_and_grad(
        lambda p: tfm.transformer_loss(MIXED, p, tokens, tokens))(params)
    assert all(g.dtype == jnp.float32 for g in jax.tree.leaves(grads))
    # compute ran in bf16: loss should match the all-bf16 model's loss far
    # more closely than fp32-vs-bf16 rounding could explain being different
    p16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    loss16 = tfm.transformer_loss(BF16, p16, tokens, tokens)
    assert abs(float(loss) - float(loss16)) < 0.05


def test_pipeline_mixed_precision_grads_fp32():
    params = tfm.transformer_init(jax.random.key(0), MIXED)
    tokens = jax.random.randint(jax.random.key(1), (8, 8), 0, 50)
    step = make_pipeline_step(
        MIXED, make_mesh(n_pipe=2),
        dtpp.ScheduleConfig(name="1F1B", n_microbatches=4))
    loss, grads = step(params, tokens, tokens)
    assert jnp.isfinite(loss)
    assert all(g.dtype == jnp.float32 for g in jax.tree.leaves(grads))
    # oracle: the single-device mixed-precision model (same bf16 compute,
    # same fp32 cast-vjp grads), microbatched the same way
    tokens_mb = tokens.reshape(4, 2, -1)

    def manual(p):
        return sum(tfm.transformer_loss(MIXED, p, tokens_mb[m], tokens_mb[m])
                   for m in range(4)) / 4

    ref_loss, ref_grads = jax.jit(jax.value_and_grad(manual))(params)
    assert abs(float(loss) - float(ref_loss)) < 2e-2
    # per-leaf error measured against the GLOBAL gradient scale (a per-leaf
    # relative metric explodes on near-zero-gradient leaves)
    gmax = max(float(jnp.max(jnp.abs(g))) for g in jax.tree.leaves(ref_grads))
    err = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                       grads, ref_grads)
    assert max(jax.tree.leaves(err)) < 0.05 * gmax, err


def test_mixed_precision_eval_and_forward():
    from distributed_training_with_pipeline_parallelism_tpu.parallel.pipeline import (
        make_pipeline_forward, make_pipeline_loss_fn)

    params = tfm.transformer_init(jax.random.key(0), MIXED)
    tokens = jax.random.randint(jax.random.key(1), (4, 8), 0, 50)
    mesh = make_mesh(n_pipe=2)
    sched = dtpp.ScheduleConfig(name="GPipe", n_microbatches=2)
    ref = float(tfm.transformer_loss(MIXED, params, tokens, tokens))
    loss = float(make_pipeline_loss_fn(MIXED, mesh, sched)(params, tokens, tokens))
    assert abs(loss - ref) < 1e-2  # both bf16 compute; small path-order noise
    logits = make_pipeline_forward(MIXED, mesh, sched)(params, tokens)
    assert logits.shape == (4, 8, 50) and bool(jnp.all(jnp.isfinite(logits)))


def test_grad_accum_equals_big_batch():
    """k accumulation steps on batch B == one step on batch k*B (grads are
    means over the batch, so averaging k half-batch grads is exact)."""
    from distributed_training_with_pipeline_parallelism_tpu.utils.train import fit

    cfg = dtpp.ModelConfig(dim=32, n_layers=4, n_heads=4, vocab_size=50,
                           ffn_dim=64)
    mesh = make_mesh(n_pipe=2)
    sched = dtpp.ScheduleConfig(name="GPipe", n_microbatches=2)
    params0 = tfm.transformer_init(jax.random.key(0), cfg)
    big = jax.random.randint(jax.random.key(1), (8, 8), 0, 50)
    opt = optax.sgd(0.1)

    def halves():
        yield big[:4], big[:4]
        yield big[4:], big[4:]

    # fit consumes the params it is given: the first run trains a copy
    accum_params, _ = fit(cfg, mesh, sched, jax.tree.map(jnp.copy, params0),
                          halves(), num_steps=2, optimizer=opt,
                          verbose=False, grad_accum=2)

    def whole():
        yield big, big

    big_params, _ = fit(cfg, mesh, sched, params0, whole(), num_steps=1,
                        optimizer=opt, verbose=False)
    err = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                       accum_params, big_params)
    assert max(jax.tree.leaves(err)) < 1e-5, err


def test_grad_accum_with_mixed_precision_smoke():
    from distributed_training_with_pipeline_parallelism_tpu.utils.train import (
        fit, synthetic_data)

    params = tfm.transformer_init(jax.random.key(0), MIXED)
    params, history = fit(
        MIXED, make_mesh(n_pipe=2),
        dtpp.ScheduleConfig(name="GPipe", n_microbatches=2),
        params, synthetic_data(MIXED, 8, 8), num_steps=4, verbose=False,
        grad_accum=2)
    assert all(np.isfinite(loss) for _, loss in history)
    assert all(x.dtype == jnp.float32 for x in jax.tree.leaves(params))
