"""Training-loop, optimizer, model-registry, and checkpoint tests."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import distributed_training_with_pipeline_parallelism_tpu as dtpp
from distributed_training_with_pipeline_parallelism_tpu.models import transformer as tfm
from distributed_training_with_pipeline_parallelism_tpu.models.gpt2 import gpt2_config
from distributed_training_with_pipeline_parallelism_tpu.models.llama import llama_config
from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import make_mesh
from distributed_training_with_pipeline_parallelism_tpu.utils import train
from distributed_training_with_pipeline_parallelism_tpu.utils.checkpoint import (
    restore_checkpoint, save_checkpoint)


def test_model_registry():
    small = gpt2_config("small")
    assert (small.dim, small.n_layers, small.vocab_size) == (768, 12, 50257)
    l3 = llama_config("llama3-8b")
    assert l3.n_kv_heads == 8 and l3.rope_theta == 5e5
    with pytest.raises(ValueError):
        gpt2_config("tiny")
    with pytest.raises(ValueError):
        llama_config("llama9")
    # overrides for pipeline divisibility
    assert gpt2_config("small", n_layers=8).n_layers == 8


@pytest.mark.parametrize("where,name", [
    ("utils.train", "fit"), ("utils.train", "make_train_step"),
    ("parallel.pipeline", "make_pipeline_step"),
    ("parallel.pipeline", "make_pipeline_grad_fn")])
def test_training_path_takes_no_telemetry(where, name):
    """One clock: the device's time is read from the profiler's trace
    (docs/observability.md §1). The option that planted host stamps inside
    the executors went in PR 32 and does not grow back."""
    import importlib
    import inspect
    fn = getattr(importlib.import_module(f"{dtpp.__name__}.{where}"), name)
    assert "telemetry" not in inspect.signature(fn).parameters


def test_training_reduces_loss():
    # A pipelined model must actually learn on a fixed batch.
    cfg = dtpp.ModelConfig(dim=32, n_layers=4, n_heads=4, vocab_size=64,
                           ffn_dim=64, max_seq_len=32, arch="gpt2")
    mesh = make_mesh(n_pipe=2)
    sched = dtpp.ScheduleConfig(name="1F1B", n_microbatches=4)
    params = tfm.transformer_init(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab_size)
    targets = jax.random.randint(jax.random.key(2), (8, 16), 0, cfg.vocab_size)

    opt = train.adamw(learning_rate=1e-2, warmup_steps=0, total_steps=100,
                      weight_decay=0.0)
    step_fn = train.make_train_step(cfg, mesh, sched, opt)
    opt_state = opt.init(params)
    losses = []
    for _ in range(30):
        params, opt_state, loss = step_fn(params, opt_state, tokens, targets)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 1.0, losses[:3] + losses[-3:]


def test_fit_loop_runs():
    cfg = dtpp.ModelConfig(dim=32, n_layers=2, n_heads=4, vocab_size=64,
                           ffn_dim=64, max_seq_len=32, arch="gpt2")
    mesh = make_mesh(n_pipe=2)
    sched = dtpp.ScheduleConfig(name="GPipe", n_microbatches=2)
    params = tfm.transformer_init(jax.random.key(0), cfg)
    data = train.synthetic_data(cfg, batch_size=4, seq_length=8)
    params, history = train.fit(cfg, mesh, sched, params, data, num_steps=3,
                                verbose=False, log_every=1)
    assert len(history) == 3
    assert all(np.isfinite(l) for _, l in history)


def test_checkpoint_roundtrip(tmp_path):
    cfg = dtpp.ModelConfig(dim=16, n_layers=2, n_heads=2, vocab_size=32,
                           ffn_dim=32)
    params = tfm.transformer_init(jax.random.key(0), cfg)
    path = tmp_path / "ckpt"
    save_checkpoint(str(path), params)
    restored = restore_checkpoint(str(path), template=params)
    flat_a = jax.tree.leaves(params)
    flat_b = jax.tree.leaves(restored)
    assert len(flat_a) == len(flat_b)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fit_checkpoint_resume_and_metrics(tmp_path):
    """Interrupt-and-resume: a run checkpointed at step k and resumed to N
    produces the same params as an uninterrupted N-step run; metrics JSONL
    has the expected schema."""
    import json

    cfg = dtpp.ModelConfig(dim=16, n_layers=2, n_heads=2, vocab_size=32,
                           ffn_dim=32)
    mesh = make_mesh(n_pipe=2)
    sched = dtpp.ScheduleConfig(name="GPipe", n_microbatches=2)
    params = tfm.transformer_init(jax.random.key(0), cfg)
    opt = train.adamw(total_steps=6, warmup_steps=1)
    ckdir = str(tmp_path / "ck")
    metrics = str(tmp_path / "metrics.jsonl")

    # uninterrupted 6-step run (fresh data iterator each time: deterministic;
    # fit consumes the params it is given, so each run gets its own copy)
    def copy(tree):
        return jax.tree.map(jnp.copy, tree)

    full_params, _ = train.fit(cfg, mesh, sched, copy(params),
                               train.synthetic_data(cfg, 4, 8, seed=3),
                               num_steps=6, optimizer=opt, verbose=False)

    # interrupted: run to a checkpoint at step 3 by stopping at num_steps=4...
    train.fit(cfg, mesh, sched, copy(params),
              train.synthetic_data(cfg, 4, 8, seed=3), num_steps=4,
              optimizer=opt, verbose=False, checkpoint_dir=ckdir,
              checkpoint_every=4, log_every=2, metrics_path=metrics)
    # ...then resume to 6 with the same fresh data stream: fit drains the
    # 4 already-consumed batches itself (skip_data_on_resume), so the resumed
    # run replays the same stream positions as the uninterrupted one.
    resumed_params, _ = train.fit(cfg, mesh, sched, params,
                                  train.synthetic_data(cfg, 4, 8, seed=3),
                                  num_steps=6, optimizer=opt, verbose=False,
                                  checkpoint_dir=ckdir, checkpoint_every=4,
                                  resume=True)

    err = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                       resumed_params, full_params)
    assert max(jax.tree.leaves(err)) < 1e-6

    lines = [json.loads(ln) for ln in open(metrics)]
    assert lines and all(
        set(ln) == {"step", "loss", "tokens_per_sec", "elapsed_s"}
        for ln in lines)
    assert [ln["step"] for ln in lines] == [0, 2, 3]


def test_adamw_decays_matrices_only():
    """Weight decay must not touch biases/norm scales (standard LM
    practice): with zero gradients, only ndim>=2 leaves shrink."""
    cfg = dtpp.ModelConfig(dim=16, n_layers=2, n_heads=2, vocab_size=32,
                           ffn_dim=32)
    params = tfm.transformer_init(jax.random.key(0), cfg)
    opt = train.adamw(learning_rate=1e-2, weight_decay=0.1, warmup_steps=0,
                      total_steps=10)
    zero_grads = jax.tree.map(jnp.zeros_like, params)
    updates, _ = jax.jit(lambda g, p: opt.update(g, opt.init(p), p))(
        zero_grads, params)
    moved = jax.tree.map(lambda u: float(jnp.max(jnp.abs(u))) > 0, updates)
    for path, did_move in jax.tree_util.tree_leaves_with_path(moved):
        is_matrix = getattr(path[-1], "key", None) in ("w", "w1", "w2", "w3")
        assert did_move == is_matrix, path


def test_adamw_decay_set_matches_golden_list():
    """Independent of the mask's own predicate: the exact set of decayed
    leaves for a gpt2 tree, written out by hand."""
    cfg = dtpp.ModelConfig(dim=16, n_layers=2, n_heads=2, vocab_size=32,
                           ffn_dim=32, arch="gpt2")
    params = tfm.transformer_init(jax.random.key(0), cfg)
    opt = train.adamw(learning_rate=1e-2, weight_decay=0.1, warmup_steps=0,
                      total_steps=10)
    updates, _ = jax.jit(lambda g, p: opt.update(g, opt.init(p), p))(
        jax.tree.map(jnp.zeros_like, params), params)
    decayed = {jax.tree_util.keystr(p)
               for p, u in jax.tree_util.tree_leaves_with_path(updates)
               if float(jnp.max(jnp.abs(u))) > 0}
    assert decayed == {
        "['layers']['attn']['q']['w']", "['layers']['attn']['k']['w']",
        "['layers']['attn']['v']['w']", "['layers']['attn']['o']['w']",
        "['layers']['lin1']['w']", "['layers']['lin2']['w']",
        "['head']['out']['w']",
    }, sorted(decayed)


def test_compile_cache_is_placed_from_outside(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR places the entry points' compile cache and
    then nothing is set in code; unset, it is the fixed <repo>/.jax_cache.
    (The real config is never touched here: the suite runs without a
    persistent cache — see conftest.py.)"""
    from distributed_training_with_pipeline_parallelism_tpu.utils import (
        compile_cache)
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/kept")
    assert compile_cache.enable_compile_cache() == "/somewhere/kept"
    assert updates == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.enable_compile_cache() == os.path.join(
        repo, ".jax_cache")
    assert updates == [("jax_compilation_cache_dir",
                        os.path.join(repo, ".jax_cache"))]


def test_state_is_born_and_stays_in_the_resting_layout():
    """Params and AdamW moments are initialised INTO the layout the
    executor takes them in — layer leaves 'pipe'-sharded on the layer
    axis, embedding and head replicated, never whole on one device — and
    the donated step hands them back in it, in their own buffers."""
    cfg = dtpp.ModelConfig(dim=16, n_layers=4, n_heads=2, vocab_size=32,
                           ffn_dim=32, arch="gpt2", max_seq_len=8)
    mesh = make_mesh(n_pipe=2)
    sched = dtpp.ScheduleConfig(name="GPipe", n_microbatches=2)
    opt = train.adamw(total_steps=4, warmup_steps=1)
    params = train.init_params(cfg, mesh, jax.random.key(0))
    opt_state = train.init_opt_state(opt, params, mesh)
    # the plain init's values, only placed (to an ulp: under jit the
    # embeddings' scale multiply fuses with the draw)
    plain = tfm.transformer_init(jax.random.key(0), cfg)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(plain)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)

    def check(params, opt_state):
        adam = opt_state[1][0]
        for tree in (params, adam.mu, adam.nu):
            for leaf in jax.tree.leaves(tree["layers"]):
                assert leaf.sharding.spec[0] == "pipe", leaf.sharding
                assert leaf.addressable_shards[0].data.shape[0] == 2
            for leaf in jax.tree.leaves((tree["embed"], tree["head"])):
                assert leaf.sharding.is_fully_replicated
                assert len(leaf.sharding.device_set) == 2
        assert adam.count.sharding.is_fully_replicated

    check(params, opt_state)
    step = train.make_train_step(cfg, mesh, sched, opt)
    tokens, targets = next(train.synthetic_data(cfg, 4, 8))
    old = jax.tree.leaves((params, opt_state))
    params, opt_state, loss = step(params, opt_state, tokens, targets)
    assert np.isfinite(float(loss))
    check(params, opt_state)
    assert all(x.is_deleted() for x in old)  # donated: updated in place
