"""Profiling utilities: the trace/annotate wrappers, the region vocabulary
(``REGIONS``, ``classify``) pinned against what JAX really writes into a
compiled step's ``op_name``s, and the host spans of ``fit``'s loop."""

import glob
import re

import jax
import jax.numpy as jnp
import pytest

import distributed_training_with_pipeline_parallelism_tpu as dtpp
from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import make_mesh
from distributed_training_with_pipeline_parallelism_tpu.utils import train
from distributed_training_with_pipeline_parallelism_tpu.utils.profiling import (
    REGIONS, annotate, classify, trace)

TINY = dict(arch="gpt2", dim=32, n_layers=2, n_heads=4, vocab_size=64,
            ffn_dim=64, max_seq_len=16)


# the strings are what jax 0.9.0 wrote for a toy step (ISSUE 28) and for the
# tiny D=2 executor (read off its compiled text)
@pytest.mark.parametrize("op_name,expected", [
    ("jit(train_step)/jvp()/while/body/closed_call/model/mlp/dot_general",
     ("forward", "model/mlp")),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "model/mlp/dot_general", ("backward", "model/mlp")),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/model/attn/exp", ("recompute", "model/attn")),
    ("jit(train_step)/train/optimizer/mul", ("optimizer", "train/optimizer")),
    # the scope can sit inside the jvp( ) brackets
    ("jit(train_step)/jvp(model/head_loss)/reduce_sum",
     ("forward", "model/head_loss")),
    ("jit(train_step)/transpose(jvp(model/head_loss))/mul",
     ("backward", "model/head_loss")),
    # a fusion that merged two sources: the first part with a region
    ("jit(train_step)/reduce_sum;jit(train_step)/jvp()/model/attn/add",
     ("forward", "model/attn")),
    # no scope: a parameter, a bare op, and an op with only JAX's marks
    ("params['layers']['attn']['q']['w']", ("other", "unscoped")),
    ("jit(train_step)/reduce_sum", ("other", "unscoped")),
    ("jit(train_step)/transpose(jvp())/while/body/dynamic_update_slice",
     ("backward", "unscoped")),
    # executors: the innermost name wins, model/ over pp/
    ("jit(train_step)/shard_map/pp/tick003/pp/fwd/pp/stage_body/while/body/"
     "closed_call/model/attn/dot_general", ("forward", "model/attn")),
    ("jit(train_step)/shard_map/pp/tick003/pp/ring_fwd/ppermute",
     ("forward", "pp/ring_fwd")),
    # a backward unit re-runs its stage's forward by hand: jvp( there is the
    # second forward, transpose(jvp( the backward
    ("jit(train_step)/shard_map/pp/tick005/pp/bwd/cond/branch_1_fun/"
     "jvp(pp/stage_body)/while/body/closed_call/model/mlp/dot_general",
     ("recompute", "model/mlp")),
    ("jit(train_step)/shard_map/pp/tick005/pp/bwd/cond/branch_1_fun/"
     "transpose(jvp(pp/stage_body))/while/body/closed_call/model/mlp/"
     "dot_general", ("backward", "model/mlp")),
    ("jit(train_step)/shard_map/pp/tick005/pp/bwd_dgrad/jvp(pp/stage_body)/"
     "while/body/add", ("recompute", "pp/stage_body")),
    # ... but the head and its loss run forward only there
    ("jit(train_step)/shard_map/pp/tick005/pp/bwd/cond/branch_1_fun/jvp()/"
     "cond/branch_1_fun/pp/loss/model/head_loss/dot_general",
     ("forward", "model/head_loss")),
])
def test_classify(op_name, expected):
    assert classify(op_name) == expected


def _lowered_tiny_step(n_pipe=1):
    cfg = dtpp.ModelConfig(**TINY)
    mesh = make_mesh(n_pipe=n_pipe, devices=jax.devices()[:n_pipe])
    sched = dtpp.ScheduleConfig(name="1F1B", n_microbatches=2)
    opt = train.adamw(total_steps=10)
    params = jax.eval_shape(
        lambda k: train.init_params(cfg, mesh, k), jax.random.key(0))
    opt_state = jax.eval_shape(opt.init, params)
    tokens = jax.ShapeDtypeStruct((4, 16), jnp.int32)
    return train.make_train_step(cfg, mesh, sched, opt).lower(
        params, opt_state, tokens, tokens)


@pytest.fixture(scope="module")
def tiny_step():
    return _lowered_tiny_step()


def _op_names(hlo_text):
    return re.findall(r'op_name="([^"]*)"', hlo_text)


def test_compiled_step_names_every_region(tiny_step):
    names = _op_names(tiny_step.compile().as_text())
    read = {classify(n) for n in names}
    for region in REGIONS:
        assert any(region in n for n in names), region
    # JAX's own marks sit where classify looks for them
    assert any("transpose(jvp(" in n and "model/mlp" in n for n in names)
    assert ("backward", "model/mlp") in read
    assert ("forward", "model/attn") in read
    assert ("recompute", "model/mlp") in read  # jax.checkpoint(gelu)
    assert ("optimizer", "train/optimizer") in read
    assert {phase for phase, _ in read} <= {
        "forward", "backward", "recompute", "optimizer", "other"}


def test_scopes_change_nothing_of_the_computation(tiny_step):
    """Scopes are locations: every region is in the lowered step's debug
    info, and the same text without debug info — the computation — holds
    none of them."""
    asm = tiny_step.compiler_ir(dialect="stablehlo").operation.get_asm(
        enable_debug_info=True)
    computation = tiny_step.as_text()
    for region in REGIONS:
        assert region in asm, f"named scope {region} missing from lowering"
        assert region not in computation, region


def test_executor_backward_units_rerun_the_forward():
    """The rule for ``pp/bwd``: in the D=2 executor's text the ops of a
    backward unit that JAX does not mark ``transpose(`` are the stage's
    second forward run, and the head's only one."""
    names = _op_names(_lowered_tiny_step(n_pipe=2).compile().as_text())
    bwd_unit = [n for n in names if "pp/bwd" in n]
    assert any("jvp(pp/stage_body)" in n and "transpose(" not in n
               for n in bwd_unit)
    assert any("transpose(jvp(pp/stage_body))" in n for n in bwd_unit)
    heads = [n for n in names if "model/head_loss" in n]
    assert heads and all("pp/bwd" in n for n in heads)
    read = {classify(n) for n in names}
    for key in (("forward", "model/mlp"), ("recompute", "model/mlp"),
                ("backward", "model/mlp"), ("forward", "model/head_loss"),
                ("forward", "pp/ring_fwd")):
        assert key in read, key


def test_fit_leaves_its_spans_on_the_host_plane(tmp_path):
    from jax.profiler import ProfileData
    cfg = dtpp.ModelConfig(**TINY)
    mesh = make_mesh(n_pipe=1, devices=jax.devices()[:1])
    params = train.init_params(cfg, mesh, jax.random.key(0))
    train.fit(cfg, mesh, dtpp.ScheduleConfig(name="1F1B", n_microbatches=2),
              params, train.synthetic_data(cfg, 4, 16), num_steps=4,
              log_every=1, verbose=False, profile_dir=str(tmp_path),
              profile_steps=(1, 4))
    paths = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    assert paths, "fit(profile_dir=...) wrote no trace"
    planes = ProfileData.from_file(paths[-1]).planes
    host = [p for p in planes if p.name == "/host:CPU"]
    if not host:
        pytest.skip("the CPU profiler gave no host plane: "
                    f"{[p.name for p in planes]}")
    seen = {ev.name for line in host[0].lines for ev in line.events}
    assert {"input_wait", "dispatch", "wait_loss"} <= seen, sorted(seen)[:40]
    assert "train" in seen  # the StepTraceAnnotation


def test_trace_contextmanager(tmp_path):
    cfg = dtpp.ModelConfig(dim=16, n_layers=2, n_heads=2, vocab_size=32,
                           ffn_dim=32)
    from distributed_training_with_pipeline_parallelism_tpu.models import transformer as tfm
    params = tfm.transformer_init(jax.random.key(0), cfg)
    with trace(str(tmp_path)):
        jax.block_until_ready(
            tfm.transformer_apply(cfg, params, jnp.zeros((1, 4), jnp.int32)))
    assert any(tmp_path.iterdir())  # a trace directory was written


def test_annotate_contextmanager():
    # TraceAnnotation with no active profiler session is a cheap no-op;
    # the contract here is only that the wrapper nests and re-raises
    with annotate("outer"):
        with annotate("inner"):
            x = jax.numpy.ones(2) * 2
    assert float(x.sum()) == 4.0


def test_pipeline_named_scopes_label_lowering():
    """Executor compute is labeled with pp/ scopes in the lowered module's
    debug info (what XProf trace rows group by — docs/observability.md).
    Scopes are locations, not ops: asserting on the debug asm also pins
    that they add nothing to the computation itself."""
    from distributed_training_with_pipeline_parallelism_tpu.models import (
        transformer as tfm)
    from distributed_training_with_pipeline_parallelism_tpu.parallel.pipeline import (
        make_pipeline_step)
    cfg = dtpp.ModelConfig(dim=32, n_layers=4, n_heads=4, vocab_size=64,
                           ffn_dim=64, max_seq_len=16)
    mesh = make_mesh(n_pipe=2)
    sched = dtpp.ScheduleConfig(name="GPipe", n_microbatches=4)
    step = make_pipeline_step(cfg, mesh, sched, force_tick_executor=True)
    params = tfm.transformer_init(jax.random.key(0), cfg)
    tokens = jax.numpy.zeros((8, 16), dtype="int32")
    ir = step.lower(params, tokens, tokens).compiler_ir(dialect="stablehlo")
    asm = ir.operation.get_asm(enable_debug_info=True)
    for scope in ("pp/fwd", "pp/ring_fwd", "pp/embed", "pp/loss"):
        assert scope in asm, f"named scope {scope} missing from lowering"
