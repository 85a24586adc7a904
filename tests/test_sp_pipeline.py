"""Sequence parallelism composed inside the pipeline executor (pp x sp).

Activations are sequence-sharded over a 'seq' mesh axis; each stage runs
ring attention across it while the schedule's ppermute rings run over
'pipe'. Oracle: single-device autodiff, as for every other composition.
"""

import pytest

import jax
import jax.numpy as jnp

import distributed_training_with_pipeline_parallelism_tpu as dtpp
from distributed_training_with_pipeline_parallelism_tpu.models import transformer as tfm
from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import make_mesh
from distributed_training_with_pipeline_parallelism_tpu.parallel.pipeline import (
    make_pipeline_step)


def _problem(cfg, seed=0, batch=4, seq=16):
    params = tfm.transformer_init(jax.random.key(seed), cfg)
    tokens = jax.random.randint(jax.random.key(1), (batch, seq), 0, cfg.vocab_size)
    targets = jax.random.randint(jax.random.key(2), (batch, seq), 0, cfg.vocab_size)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: tfm.transformer_loss(cfg, p, tokens, targets)))(params)
    return params, tokens, targets, ref_loss, ref_grads


def _check(step, params, tokens, targets, ref_loss, ref_grads, tol=2e-5):
    loss, grads = step(params, tokens, targets)
    assert float(jnp.abs(loss - ref_loss)) < tol
    err = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                       grads, ref_grads)
    worst = max(jax.tree.leaves(err))
    assert worst < tol, f"max grad err {worst}"


@pytest.mark.parametrize("arch,kw", [
    ("ref_decoder", {}),
    ("gpt2", {}),                      # learned positions offset per shard
    ("llama", dict(n_kv_heads=2)),     # RoPE local angles per shard
])
def test_pp_sp_matches_single_device(arch, kw):
    cfg = dtpp.ModelConfig(dim=32, n_layers=4, n_heads=4, vocab_size=64,
                           ffn_dim=64, max_seq_len=32, arch=arch, **kw)
    prob = _problem(cfg)
    mesh = make_mesh(n_pipe=2, n_seq=4)
    step = make_pipeline_step(
        cfg, mesh, dtpp.ScheduleConfig(name="GPipe", n_microbatches=2))
    _check(step, *prob)


def test_pp_sp_gemma_knobs():
    """Gemma-family knobs through seq-parallel stages (VERDICT r1 item 4
    guard lift): embed_scale, GeGLU MLP, decoupled head_dim, tied head."""
    cfg = dtpp.ModelConfig(dim=32, n_layers=4, n_heads=4, vocab_size=64,
                           ffn_dim=64, max_seq_len=32, arch="llama",
                           mlp_act="gelu", embed_scale=True,
                           head_dim_override=16, tie_embeddings=True)
    prob = _problem(cfg)
    mesh = make_mesh(n_pipe=2, n_seq=4)
    step = make_pipeline_step(
        cfg, mesh, dtpp.ScheduleConfig(name="GPipe", n_microbatches=2))
    _check(step, *prob)


def test_dp_pp_sp_1f1b():
    cfg = dtpp.ModelConfig(dim=32, n_layers=4, n_heads=4, vocab_size=64,
                           ffn_dim=64, max_seq_len=32, arch="gpt2")
    prob = _problem(cfg)
    mesh = make_mesh(n_pipe=2, n_data=2, n_seq=2)
    step = make_pipeline_step(
        cfg, mesh, dtpp.ScheduleConfig(name="1F1B", n_microbatches=2))
    _check(step, *prob)


def test_sp_with_virtual_stages():
    cfg = dtpp.ModelConfig(dim=32, n_layers=8, n_heads=4, vocab_size=64,
                           ffn_dim=64, max_seq_len=32, arch="llama")
    prob = _problem(cfg)
    mesh = make_mesh(n_pipe=2, n_seq=2)
    step = make_pipeline_step(
        cfg, mesh, dtpp.ScheduleConfig(name="Interleaved1F1B",
                                       n_microbatches=4, n_virtual=2))
    _check(step, *prob)


def test_tp_and_sp_together_gpipe():
    cfg = dtpp.ModelConfig(dim=32, n_layers=4, n_heads=4, vocab_size=64,
                           ffn_dim=64)
    prob = _problem(cfg)
    mesh = make_mesh(n_pipe=2, n_model=2, n_seq=2)
    step = make_pipeline_step(cfg, mesh, dtpp.ScheduleConfig(name="GPipe",
                                                             n_microbatches=2))
    _check(step, *prob)


def test_sp_with_zero_bubble_schedule():
    cfg = dtpp.ModelConfig(dim=32, n_layers=4, n_heads=4, vocab_size=64,
                           ffn_dim=64, max_seq_len=32, arch="gpt2")
    prob = _problem(cfg)
    mesh = make_mesh(n_pipe=2, n_seq=2)
    step = make_pipeline_step(
        cfg, mesh, dtpp.ScheduleConfig(name="ZBH1", n_microbatches=4))
    _check(step, *prob)


def test_4d_dp_pp_tp_sp():
    """The full composition: data x pipe x model x seq in one step (8 devs
    would need 16 for data=2, so data=1 here: pipe=2 x model=2 x seq=2)."""
    cfg = dtpp.ModelConfig(dim=32, n_layers=4, n_heads=4, vocab_size=64,
                           ffn_dim=64, max_seq_len=32, arch="llama",
                           n_kv_heads=2)
    prob = _problem(cfg)
    mesh = make_mesh(n_pipe=2, n_model=2, n_seq=2)
    step = make_pipeline_step(
        cfg, mesh, dtpp.ScheduleConfig(name="1F1B", n_microbatches=2))
    _check(step, *prob)


@pytest.fixture(scope="module")
def tp_sp_ulysses():
    cfg = dtpp.ModelConfig(dim=32, n_layers=4, n_heads=4, vocab_size=64,
                           ffn_dim=64, max_seq_len=32, arch="gpt2")
    mesh = make_mesh(n_pipe=2, n_model=2, n_seq=2)
    step = make_pipeline_step(cfg, mesh,
                              dtpp.ScheduleConfig(name="GPipe",
                                                  n_microbatches=2),
                              sp_attn_impl="ulysses")
    return step, _problem(cfg)


def test_tp_sp_ulysses_composes(tp_sp_ulysses):
    """Round-5 guard closure: Megatron TP nests with Ulysses — each model
    column all-to-alls its own head shard over 'seq' (4 heads / T=2 / D=2
    -> 1 head per device post-scatter), the o projection completes
    row-parallel. Loss/grads equal single-device autodiff."""
    step, prob = tp_sp_ulysses
    _check(step, *prob)


def test_tp_sp_ulysses_runs_ten_steps_in_one_process(tp_sp_ulysses):
    """The tick's units hold collectives over 'model' and 'seq' beside the
    pipe ring's permute. Under XLA:CPU's default schedule ten calls of this
    step hung in most runs; tests/conftest.py says why and what cures it."""
    step, (params, tokens, targets, _, _) = tp_sp_ulysses
    first = step(params, tokens, targets)
    for _ in range(9):
        again = step(params, tokens, targets)
    assert jax.tree.all(jax.tree.map(
        lambda a, b: bool(jnp.array_equal(a, b)), first, again))


def test_4d_dp_free_pp_tp_sp_ulysses_llama():
    """The 4-D llama composition on the Ulysses transport (GQA: 8 q heads
    / 4 kv heads, both dividing T*D = 4)."""
    cfg = dtpp.ModelConfig(dim=32, n_layers=4, n_heads=8, n_kv_heads=4,
                           vocab_size=64, ffn_dim=64, max_seq_len=32,
                           arch="llama")
    prob = _problem(cfg)
    mesh = make_mesh(n_pipe=2, n_model=2, n_seq=2)
    step = make_pipeline_step(cfg, mesh,
                              dtpp.ScheduleConfig(name="1F1B",
                                                  n_microbatches=2),
                              sp_attn_impl="ulysses")
    _check(step, *prob)


@pytest.mark.parametrize("arch,kw", [
    ("gpt2", {}),
    ("llama", dict(n_heads=8, n_kv_heads=4, dim=32)),  # GQA unexpanded a2a
])
def test_pp_sp_ulysses(arch, kw):
    """Ulysses all-to-all as the pipeline's sequence-parallel transport
    (cond units stay: all_to_all is a grouped collective)."""
    base = dict(dim=32, n_layers=4, n_heads=4, vocab_size=64,
                ffn_dim=64, max_seq_len=32, arch=arch)
    base.update(kw)
    cfg = dtpp.ModelConfig(**base)
    prob = _problem(cfg)
    mesh = make_mesh(n_pipe=2, n_seq=4)
    step = make_pipeline_step(
        cfg, mesh, dtpp.ScheduleConfig(name="1F1B", n_microbatches=2),
        sp_attn_impl="ulysses")
    _check(step, *prob)


def test_bad_sp_attn_impl_rejected():
    cfg = dtpp.ModelConfig(dim=32, n_layers=4, n_heads=4, vocab_size=64,
                           ffn_dim=64)
    mesh = make_mesh(n_pipe=2, n_seq=2)
    with pytest.raises(ValueError, match="sp_attn_impl"):
        make_pipeline_step(cfg, mesh,
                           dtpp.ScheduleConfig(name="GPipe", n_microbatches=2),
                           sp_attn_impl="flash")


def test_fsdp_sp_ulysses_and_moe():
    """Round-5 fsdp x seq coverage on the remaining legs: the Ulysses
    transport under ZeRO-3 (head all_to_all vs just-in-time chunk
    gathers — orthogonal axes), and MoE stages under fsdp x seq (expert
    per-tick psum_scatter over 'data' composing with the unconditional
    seq psum). Both exact vs their oracles. Lives here (not
    test_fsdp.py) to stay under that file's XLA:CPU per-process
    compilation crash threshold."""
    from distributed_training_with_pipeline_parallelism_tpu.models.moe import (
        MoEConfig, moe_lm_init, moe_lm_loss)
    from distributed_training_with_pipeline_parallelism_tpu.parallel.pipeline import (
        fsdp_shard_params)

    cfg = dtpp.ModelConfig(dim=32, n_layers=4, n_heads=4, vocab_size=64,
                           ffn_dim=64, max_seq_len=32, arch="gpt2")
    params, tokens, targets, ref_loss, ref_grads = _problem(cfg, batch=8)
    mesh = make_mesh(n_pipe=2, n_data=2, n_seq=2)
    placed = fsdp_shard_params(params, cfg, mesh)
    step = make_pipeline_step(cfg, mesh,
                              dtpp.ScheduleConfig(name="GPipe",
                                                  n_microbatches=2),
                              fsdp=True, sp_attn_impl="ulysses")
    _check(step, placed, tokens, targets, ref_loss, ref_grads)

    moe = MoEConfig(n_experts=4, top_k=2, capacity_factor=4.0,
                    aux_loss_weight=0.0)
    mcfg = dtpp.ModelConfig(dim=32, n_layers=4, n_heads=4, vocab_size=64,
                            ffn_dim=64, max_seq_len=16, arch="gpt2")
    M = 2
    mp = moe_lm_init(jax.random.key(0), mcfg, moe)
    mtok = jax.random.randint(jax.random.key(1), (8, 8), 0,
                              mcfg.vocab_size)
    mtgt = jax.random.randint(jax.random.key(2), (8, 8), 0,
                              mcfg.vocab_size)

    def mb_loss(p):
        t = mtok.reshape(M, -1, 8)
        g = mtgt.reshape(M, -1, 8)
        return sum(moe_lm_loss(mcfg, moe, p, t[m], g[m])
                   for m in range(M)) / M

    mref_loss, mref_grads = jax.value_and_grad(mb_loss)(mp)
    mplaced = fsdp_shard_params(mp, mcfg, mesh, moe=moe)
    mstep = make_pipeline_step(mcfg, mesh,
                               dtpp.ScheduleConfig(name="GPipe",
                                                   n_microbatches=M),
                               moe=moe, fsdp=True)
    _check(mstep, mplaced, mtok, mtgt, mref_loss, mref_grads)
