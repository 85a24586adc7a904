"""The join from a trace event to the region the program named: the compiled
text's instruction -> ``op_name`` map, the split of hand-made intervals, the
readers, and the wrapping runner's own two steps without a device."""

import gzip
import json
import os
import re
import types

import jax
import jax.numpy as jnp
import pytest

import distributed_training_with_pipeline_parallelism_tpu as dtpp
from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import (
    make_mesh)
from distributed_training_with_pipeline_parallelism_tpu.utils import train
from distributed_training_with_pipeline_parallelism_tpu.utils.data import (
    batch_sharding)
from distributed_training_with_pipeline_parallelism_tpu.utils.profiling import (
    REGIONS, classify)

from benchmark.harness import manifest as mf
from benchmark.harness import scopes
from benchmark.harness import trace_reduce as tr
from benchmark.harness.trace_reduce import Event

US = 1e3  # the synthetic traces below are written in microseconds
DEV = "/device:TPU:0"
runner = mf.load_runner("train_scoped")

NEW_READERS = {
    "step.forward_share_pct": ("phases", "forward"),
    "step.backward_share_pct": ("phases", "backward"),
    "step.recompute_share_pct": ("phases", "recompute"),
    "step.optimizer_share_pct": ("regions", "train/optimizer"),
    "model.attn_share_pct": ("regions", "model/attn"),
    "model.mlp_share_pct": ("regions", "model/mlp"),
    "model.head_loss_share_pct": ("regions", "model/head_loss"),
    "step.unscoped_share_pct": ("regions", "unscoped"),
}


def ev(name, start_us, dur_us):
    return Event(name, start_us * US, (start_us + dur_us) * US, {})


def tiny():
    cfg = dtpp.ModelConfig(arch="gpt2", dim=32, n_layers=2, n_heads=4,
                           vocab_size=64, ffn_dim=64, max_seq_len=16)
    mesh = make_mesh(n_pipe=1, devices=jax.devices()[:1])
    sched = dtpp.ScheduleConfig(name="1F1B", n_microbatches=2)
    return cfg, mesh, sched, train.adamw(total_steps=10)


@pytest.fixture(scope="module")
def real_text():
    """The tiny step compiled from real arrays, as ``runners/train.py``
    compiles a cell's."""
    cfg, mesh, sched, opt = tiny()
    params = train.init_params(cfg, mesh, jax.random.key(0))
    opt_state = train.init_opt_state(opt, params, mesh)
    tokens = jax.device_put(jnp.zeros((4, 16), jnp.int32),
                            batch_sharding(mesh))
    return train.make_train_step(cfg, mesh, sched, opt).lower(
        params, opt_state, tokens, tokens).compile().as_text()


def test_scope_map_reads_a_real_compiled_text(real_text):
    found = scopes.scope_map(real_text)
    read = {classify(op) for op in found.values()}
    assert {region for _, region in read} >= set(REGIONS)
    for key in (("forward", "model/attn"), ("backward", "model/mlp"),
                ("optimizer", "train/optimizer")):
        assert key in read
    # ROOT instructions are in it, and the compiler's own (no op_name) too
    roots = [line.split(" = ")[0].split()[-1].lstrip("%")
             for line in real_text.splitlines()
             if line.lstrip().startswith("ROOT ")]
    assert roots and all(name in found for name in roots)
    assert "" in found.values()


def test_scope_map_on_the_lines_a_tpu_text_has():
    text = (
        "%fused_computation.1 (p: bf16[8]) -> bf16[8] {\n"
        '  ROOT %multiply.5 = bf16[8]{0} multiply(%p, %p), metadata={op_type='
        '"mul" op_name="jit(train_step)/jvp()/model/mlp/mul"}\n}\n'
        "ENTRY %main {\n"
        '  %fusion.357 = bf16[8]{0:T(8,128)} fusion(bf16[8] %copy-done.67), '
        'kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit('
        'train_step)/transpose(jvp())/model/mlp/mul;jit(train_step)/x"}\n'
        "  %copy.3 = bf16[8]{0} copy(%fusion.357)\n"
        '  ROOT %attn.17 = (bf16[8]) custom-call(%copy.3), custom_call_target'
        '="tpu_custom_call", metadata={op_name="jit(train_step)/jvp()/model/'
        'attn/pallas_call"}\n}\n')
    found = scopes.scope_map(text)
    assert found["fusion.357"].startswith("jit(train_step)/transpose(jvp())")
    assert found["copy.3"] == "" and "attn.17" in found
    assert classify(found["fusion.357"]) == ("backward", "model/mlp")


SCOPES = {
    "fusion.1": "jit(train_step)/jvp()/model/mlp/dot_general",
    "fusion.2": "jit(train_step)/transpose(jvp())/model/mlp/dot_general",
    "fusion.3": "jit(train_step)/transpose(jvp())/model/attn/checkpoint/"
                "rematted_computation/exp",
    "fusion.4": "jit(train_step)/train/optimizer/mul",
    "copy.5": "",
    "collective-permute-done.6":
        "jit(train_step)/shard_map/pp/tick003/pp/ring_fwd/ppermute",
    "attn.17": "jit(train_step)/jvp()/model/attn/pallas_call",
}


def hand_made_plane():
    return {tr.OPS_LINE: [
        ev("%fusion.1 = bf16[8] fusion(..)", 0, 40),
        ev("%fusion.1 = bf16[8] fusion(..)", 30, 20),    # overlaps: 0-50 once
        ev("%while.9 = (..) while(..)", 0, 200),          # container
        ev("%fusion.2 = bf16[8] fusion(bf16[8] %fusion.1)", 50, 30),
        ev("%fusion.3 = bf16[8] fusion(..)", 80, 10),
        ev("%attn.17 = (bf16[8]) custom-call(..)", 90, 10),
        ev("%collective-permute-done.6 = bf16[8] collective-permute-done(..)",
           100, 20),
        ev("%fusion.4 = f32[8] fusion(..)", 120, 20),
        ev("%copy.5 = bf16[8] copy(..)", 140, 10),
        ev("%fusion.77 = bf16[8] fusion(..)", 150, 10),   # not in the text
        ev("%fusion.4 = f32[8] fusion(..)", 190, 30),     # cut at the window
    ], tr.MODULES_LINE: [ev("jit_train_step(7)", 0, 195),
                         ev("jit_train_step(7)", 200, 195)]}


def test_by_region_on_hand_made_intervals():
    p = scopes.by_region({DEV: hand_made_plane()}, DEV, 0, 200 * US, SCOPES,
                         {"attn.17": "_flash_fwd_kernel_packed"})
    s = {k: round(v * 1e6, 6) for k, v in p["seconds"].items()}
    assert s == {
        ("forward", "model/mlp"): 50,            # the overlap counted once
        ("backward", "model/mlp"): 30,
        ("recompute", "model/attn"): 10,
        ("forward", "model/attn"): 10,
        ("collective", "pp/ring_fwd"): 20,       # apart, whatever its region
        ("optimizer", "train/optimizer"): 30,    # 20 + the 10 inside the window
        ("other", "unscoped"): 20,               # the copy and the stranger
    }
    assert p["busy_s"] == pytest.approx(170e-6)  # the while is not in it
    assert p["window_s"] == pytest.approx(200e-6)
    assert p["regions"]["model/mlp"] == pytest.approx(80e-6)
    assert p["regions"]["model/attn"] == pytest.approx(20e-6)
    assert "pp/ring_fwd" not in p["regions"]     # a wait is no region's work
    assert p["phases"]["collective"] == pytest.approx(20e-6)
    assert sum(p["phases"].values()) == pytest.approx(p["busy_s"])
    # fusion.77 is not an instruction of the text: 10 of 170 us uncovered
    assert p["coverage"] == pytest.approx(160 / 170)
    assert p["by_label"]["forward:model/mlp:fusion"] == pytest.approx(60e-6)
    assert p["by_label"]["forward:model/attn:_flash_fwd_kernel_packed"] == (
        pytest.approx(10e-6))


def test_a_collective_is_known_by_name_or_by_opcode():
    assert scopes.collective(ev("%collective-permute-start.7 = (bf16[2,1024,"
                                "1600]{2,1,0:T(8,128)(2,1)}, bf16[2,1024,1600]) "
                                "collective-permute-start(%fusion.3)", 0, 1))
    # named after its op_name's tail, as the XL step's gradient sum is
    assert scopes.collective(ev("%psum.44 = f32[1600,6400]{1,0:T(8,128)} "
                                "all-reduce(f32[1600,6400] %fusion.9), "
                                "replica_groups={{0,1,2,3}}", 0, 1))
    # a fusion that consumes a collective's result is compute
    assert not scopes.collective(ev(
        "%fusion.2 = (bf16[8]{0:T(8,128)S(1)}, bf16[8]) fusion(bf16[8] "
        "%collective-permute-done.4, f32[] %all-reduce.1)", 0, 1))
    assert not scopes.collective(ev("%copy.5 = bf16[8] copy(..)", 0, 1))


def test_readers_take_their_share_and_know_when_there_is_none():
    man = mf.load_manifest()
    entries = {m["name"]: m for m in man["per_layer"]}
    plane = scopes.by_region({DEV: hand_made_plane()}, DEV, 0, 200 * US,
                             SCOPES)
    run = {"regions": scopes.summarize([plane, plane])}
    low = {"regions": dict(run["regions"], coverage=0.89)}
    for name, (table, key) in NEW_READERS.items():
        reader = mf.load_metric(name)
        entry = entries[name]
        assert (reader.LAYER, reader.UNIT, reader.BETTER, reader.MOVES,
                reader.SOURCE) == (entry["layer"], "%", "lower",
                                   "train.tokens_per_s", "device_trace")
        # every cell it is listed for runs through the runner that fills it
        for cell in entry["workloads"]:
            assert mf.load_workload(cell)["runner"] == "train_scoped"
        assert reader.read(run) == pytest.approx(
            100 * plane[table].get(key, 0.0) / plane["busy_s"])
        assert reader.read(low) is None          # the text is another program's
        assert reader.read({"trace": None}) is None   # the base runner's dict
    assert mf.load_metric("step.forward_share_pct").read(run) == (
        pytest.approx(100 * 60 / 170))
    assert mf.load_metric("step.unscoped_share_pct").read(run) == (
        pytest.approx(100 * 20 / 170))


def test_text_from_abstract_arguments_is_the_text_that_ran(real_text):
    """Instruction for instruction the same program with the same op_names;
    only the table of source files and stack frames differs (the two are
    lowered from different call sites), which no reader looks at."""
    cfg, mesh, sched, opt = tiny()
    rebuilt = runner.abstract_step_text(cfg, mesh, sched, opt, 4, 16)
    assert scopes.scope_map(rebuilt) == scopes.scope_map(real_text)

    def instructions(text):
        return [re.sub(r"stack_frame_id=\d+", "", line)
                for line in text.splitlines() if " = " in line]

    assert instructions(rebuilt) == instructions(real_text)


class FakeBase:
    """Stands for ``runners/train.py``: leaves a kept trace where it is
    told to, and a result without regions."""
    PROGRAM = "train_step"

    def __init__(self):
        self.seen = None

    def run(self, ctx):
        self.seen = ctx
        traced = ctx.trace
        if traced:
            with gzip.open(os.path.join(
                    ctx.keep_trace, ctx.cell + ".xplane.pb.gz"), "wb") as fh:
                fh.write(b"hand-made")
        return {"correct": True, "attempted": 12, "failed": 0,
                "end_to_end": {"train.tokens_per_s": 1.0, "setup_s": 2.0},
                "run": {"trace": {"planes": []} if traced else None,
                        "pallas_calls": {}, "config": ctx.config},
                **({"device": {"busy_s": 1.0, "window_s": 1.0},
                    "breakdown": {"device_ops": [["fusion", 1.0]],
                                  "idle_gaps": [["wait_loss", 8e-6]]}}
                   if traced else {})}


def wrapped(monkeypatch, trace):
    base = FakeBase()
    monkeypatch.setattr(mf, "load_runner", lambda name: base)
    monkeypatch.setattr(runner, "step_text", lambda ctx: "ENTRY %main {\n" + "".join(
        f'  %{name} = bf16[8] fusion(), metadata={{op_name="{op}"}}\n'
        for name, op in SCOPES.items()) + "}\n")
    monkeypatch.setattr(
        tr, "load", lambda path: {DEV: hand_made_plane()}
        if open(path, "rb").read() == b"hand-made" else {})
    ctx = types.SimpleNamespace(
        cell="tiny.cell", trace=trace, keep_trace="", log=lambda msg: None,
        workload={"trace_steps": 3}, config={"numerics": {}})
    return base, ctx, runner.run(ctx)


def test_wrapper_adds_regions_and_relabels_the_breakdown(monkeypatch):
    base, ctx, out = wrapped(monkeypatch, trace=True)
    assert base.seen.keep_trace and not os.path.exists(base.seen.keep_trace)
    assert ctx.keep_trace == ""                  # the caller's is not touched
    regions = out["run"]["regions"]
    assert regions["coverage"] == pytest.approx(160 / 170)
    assert len(regions["planes"]) == 1 and regions["after_window_s"] >= 0
    labels = [label for label, _ in out["breakdown"]["device_ops"]]
    assert labels[0] == "forward:model/mlp:fusion"
    assert all(label.count(":") >= 2 for label in labels)
    json.dumps(out["breakdown"])
    assert out["breakdown"]["idle_gaps"] == [["wait_loss", 8e-6]]
    # the timed window's numbers pass through untouched
    assert out["end_to_end"] == {"train.tokens_per_s": 1.0, "setup_s": 2.0}
    assert (out["correct"], out["attempted"], out["failed"]) == (True, 12, 0)
    assert out["device"] == {"busy_s": 1.0, "window_s": 1.0}


def test_wrapper_leaves_an_untraced_run_alone(monkeypatch):
    base, _, out = wrapped(monkeypatch, trace=False)
    assert "regions" not in out["run"] and "breakdown" not in out
    assert base.seen.keep_trace == ""


def test_wrapper_refuses_a_text_of_another_program(monkeypatch):
    base = FakeBase()
    run = base.run

    def with_a_kernel(ctx):
        out = run(ctx)
        out["run"]["pallas_calls"] = {"attn.17": {"kernel": "_flash_fwd",
                                                  "out_elements": 8}}
        return out

    base.run = with_a_kernel
    monkeypatch.setattr(mf, "load_runner", lambda name: base)
    monkeypatch.setattr(runner, "step_text", lambda ctx: "ENTRY %main {\n}\n")
    ctx = types.SimpleNamespace(
        cell="tiny.cell", trace=True, keep_trace="", log=lambda msg: None,
        workload={"trace_steps": 3}, config={"numerics": {}})
    with pytest.raises(RuntimeError, match="not the program that ran"):
        runner.run(ctx)
