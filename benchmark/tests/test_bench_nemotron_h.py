"""The ``nemotron-twotower-30b-a3b`` configuration on the CPU: its file
against the catalog and against itself, the adapter, the plain reference
against the program at a reduced width — equal in float32, and, through the
cell's own comparison (the loss, then layer by layer), correct in bf16 and
NOT correct under each control of ``reference/nemotron_h.py:CONTROLS`` —
the FLOP count against a hand count, and the four new readers."""

import dataclasses
import json
import os
import types

import jax
import numpy as np
import pytest

from benchmark.harness import manifest as mf

NAME = "nemotron-twotower-30b-a3b"
CELL = NAME + ".train-b2s8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the catalog row's ``source_url`` and ``config``, copied (PR 30): the
# driver's checkout has no /opt/skills
CATALOG_COPY = os.path.join(os.path.dirname(__file__), NAME + ".catalog.json")
REDUCED = ["hybrid_override_pattern", "n_routed_experts", "vocab_size"]

# every width a quarter to a tenth of the published one, the pattern whole
SIZES = dict(
    hidden_size=256, num_attention_heads=8, num_key_value_heads=2,
    head_dim=32, vocab_size=2048, hybrid_override_pattern="MEMEM*EME",
    max_position_embeddings=4096, layer_norm_epsilon=1e-5, rope_theta=10000,
    mamba_num_heads=8, mamba_head_dim=32, n_groups=2, ssm_state_size=32,
    conv_kernel=4, chunk_size=32, time_step_min=0.001, time_step_max=0.1,
    time_step_floor=1e-4, router_width=32, experts_held=[0, 1, 2, 3],
    num_experts_per_tok=3, moe_intermediate_size=128,
    moe_shared_expert_intermediate_size=256, routed_scaling_factor=2.5)
SEQ = 256


@pytest.fixture(scope="module")
def config():
    return mf.load_config(mf.load_manifest(), NAME)


@pytest.fixture(scope="module")
def family():
    return mf.load_reference("nemotron_h")


def test_file_holds_the_source_keys_twice_and_equal(config):
    """The source's keys stand at the top level (what the contract compares
    with the catalog) and under ``sizes`` (what the runner hands the
    adapter): the same values, the three reduced keys cut in both."""
    sizes = config["sizes"]
    extra = {"router_width", "experts_held"}
    assert set(sizes) - extra == {k for k in config if k in sizes}
    for key in set(sizes) - extra:
        assert config[key] == sizes[key], key
    assert config["reduced"] == REDUCED
    entry = next(c for c in mf.load_manifest()["configs"] if c["name"] == NAME)
    assert entry["reduced"] == REDUCED and entry["source"] == config["source"]
    assert (sizes["router_width"], sizes["experts_held"]) == (
        config["published"]["n_routed_experts"], list(range(8)))
    assert len(sizes["experts_held"]) == sizes["n_routed_experts"] == 8
    published = config["published"]["hybrid_override_pattern"]
    assert len(published) == config["published"]["num_hidden_layers"] == 52
    assert published.startswith(sizes["hybrid_override_pattern"])
    assert sizes["vocab_size"] * 8 == config["published"]["vocab_size"]


def test_every_number_but_the_reduced_is_the_catalogs(config):
    with open(CATALOG_COPY) as fh:
        row = json.load(fh)
    assert row["source_url"] == config["source"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    if os.path.exists(CATALOG):  # where the catalog is, the copy is its row
        with open(CATALOG) as fh:
            theirs = next(r for r in map(json.loads, fh)
                          if r["source_url"] == config["source"])
        assert theirs["config"] == row["config"]


def test_adapter_maps_the_published_keys(config, family):
    cfg = family.model_config(config["sizes"], config["numerics"])
    assert (cfg.arch, cfg.dim, cfg.n_layers, cfg.hybrid_override_pattern,
            cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.vocab_size) == (
                "nemotron_h", 2688, 9, "MEMEM*EME", 32, 2, 128, 16384)
    assert (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
            cfg.ssm_state_size, cfg.conv_kernel, cfg.chunk_size) == (
                64, 64, 8, 128, 4, 128)
    assert (cfg.n_routed_experts, cfg.held_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.moe_shared_expert_intermediate_size,
            cfg.routed_scaling_factor) == (
                128, tuple(range(8)), 6, 1856, 3712, 2.5)
    assert (cfg.dtype, cfg.param_dtype, cfg.use_flash_attention,
            cfg.use_fused_xent, cfg.remat_layers, cfg.tie_embeddings) == (
                "bfloat16", "float32", "auto", True, True, False)
    assert family.flash_call_shape(config["sizes"], 2, 8192) == (
        2, 8192, 32, 128)


def test_parameter_count_is_the_files_arithmetic(config, family):
    from distributed_training_with_pipeline_parallelism_tpu.models.transformer import (
        transformer_init)
    cfg = family.model_config(config["sizes"], config["numerics"])
    shapes = jax.eval_shape(lambda: transformer_init(jax.random.key(0), cfg))

    def count(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    d, layers = 2688, shapes["layers"]
    mamba = (d + d * 10304 + (4 + 1) * 6144 + 3 * 64 + 4096 + 4096 * d)
    attn = d + d * 128 * (2 * 32 + 2 * 2)
    moe = (d + d * 128 + 128 + 8 * 2 * d * 1856 + 2 * d * 3712)
    assert count(layers["mamba"]) == 4 * mamba and round(mamba / 1e4) == 3874
    assert count(layers["attn"]) == attn and round(attn / 1e4) == 2340
    assert count(layers["moe"]) == 4 * moe and round(moe / 1e4) == 10013
    assert count(shapes) == 4 * mamba + attn + 4 * moe + d + 2 * d * 16384
    assert abs(count(shapes) - 667e6) < 1e6


def test_flops_equal_a_hand_count(config, family):
    """Forward FLOPs a token, by hand from the published widths."""
    d = 2688
    mamba = (2 * d * 10304 + 2 * 4096 * d      # in- and out-projection
             + 8 * 128 * 128                    # C B^T: 8 groups, causal half
             + 64 * 128 * 64                    # its product with x, half
             + 2 * 2 * 64 * 64 * 128)           # the chunk's state; C S
    attn = 2 * d * 128 * (2 * 32 + 2 * 2) + 2 * 32 * 128 * 8192
    expert = 2 * d * 128 + 4 * d * 3712 + (6 * 8 / 128) * 4 * d * 1856
    head = 2 * d * 16384
    want = 3 * (4 * mamba + attn + 4 * expert + head)
    got = family.train_flops_per_token(config["sizes"], 8192)
    assert got == pytest.approx(want, rel=1e-12)
    assert 2.1e9 < got < 2.2e9                  # the issue sized 2.15 GFLOP


NUMERICS = dict(dtype="bfloat16", param_dtype="float32",
                use_flash_attention=False)


@pytest.fixture(scope="module")
def setting(family):
    from distributed_training_with_pipeline_parallelism_tpu.models.transformer import (
        transformer_init, transformer_loss)
    cfg = family.model_config(SIZES, NUMERICS)
    params = transformer_init(jax.random.key(3), cfg)
    toks = np.random.default_rng(0).integers(
        0, SIZES["vocab_size"], (4, SEQ + 1), dtype=np.int32)
    x, y = toks[:, :-1], toks[:, 1:]

    def reference(**faults):
        return float(jax.jit(
            lambda p: family.loss(p, x, y, SIZES, **faults))(params))

    want = reference()

    def program(cfg):
        got = float(jax.jit(lambda p: transformer_loss(cfg, p, x, y))(params))
        return abs(got - want) / want

    return cfg, program, reference, want


def test_reference_is_the_programs_mathematics(setting, family):
    cfg, program, _, want = setting
    assert abs(want - np.log(SIZES["vocab_size"])) < 0.5
    assert program(dataclasses.replace(cfg, dtype="float32")) < 1e-6
    assert program(cfg) < family.LOSS_TOL * 5  # 1024 tokens average less out


# What ONE scalar, the mean loss at random init, sees at this width over 1024
# tokens (readings of PR 30, CPU): a dropped Mamba-2 layer 1.2e-3, a dropped
# expert layer 3.2e-4, one held expert dropped 3.0e-4, the matrices rounded
# to fp8 e4m3 6.9e-4. Not the router in bf16 (9.5e-5 here, 9.3e-6 at the
# cell's size on the chip) nor the scan's state in bf16 (6e-8 here, 5.7e-6
# there): those are the layer-by-layer comparison's, below.
@pytest.mark.parametrize("control", [
    "dropped-mamba-layer", "dropped-expert-layer", "dropped-held-expert",
    "fp8-matrices"])
def test_the_loss_sees_what_it_can(setting, family, control):
    _, _, reference, want = setting
    got = reference(**family.CONTROLS[control])
    assert abs(got - want) / want > 2 * family.LOSS_TOL


@pytest.fixture(scope="module")
def layerwise():
    """``runners/train_layerwise.py:check`` as the cell calls it, at the
    reduced width but with the published 64 heads in 8 groups and 1 sequence
    of 1024: a state kept in bf16 shows on the heads that remember longest,
    so the reading needs the heads to draw from and the steps to remember."""
    sizes = dict(SIZES, mamba_num_heads=64, mamba_head_dim=8, n_groups=8)
    ctx = types.SimpleNamespace(
        workload=dict(mesh=dict(pipe=1), chips=1, check_sequences=1, batch=1,
                      seq=1024),
        config=dict(sizes=sizes, reference="nemotron_h", numerics=NUMERICS),
        devices=jax.devices(), seed=7, log=lambda msg: None)
    check = mf.load_runner("train_layerwise").check
    return lambda control: check(ctx, control)


def test_layer_by_layer_the_program_is_correct(layerwise, family):
    result = layerwise(None)
    assert result["failed_by"] == []
    assert set(result["readings"]) == set(family.LAYER_TOL) == {
        "out", "tokens_off", "scan"}
    # readings of PR 30 at this width (CPU): out 4.9e-3, scan 3.1e-3, no token
    assert result["readings"]["tokens_off"] == 0
    for key in ("out", "scan"):
        assert result["readings"][key] < family.LAYER_TOL[key] / 2


# Each control is NOT correct, and by the reading that is there for it. At
# this width (PR 30, CPU): the router in bf16 moves 5 tokens of 1024 to
# another held expert (tokens_off 2.9e-3 to 4.9e-3); the state in bf16 reads
# 6.8e-2 on the worst head at 1024 steps (1.2e-1 at 2048); one held expert of
# four left out 1.2e-1 of the tokens; fp8 matrices 2.8e-1 on a layer's output.
@pytest.mark.parametrize("control,by", [
    ("bf16-router", "tokens_off"), ("bf16-scan-state", "scan"),
    ("dropped-held-expert", "tokens_off"), ("fp8-matrices", "out"),
    ("dropped-mamba-layer", "out"), ("dropped-expert-layer", "out")])
def test_layer_by_layer_each_control_is_not_correct(layerwise, family, control,
                                                    by):
    result = layerwise(family.CONTROLS[control])
    assert by in result["failed_by"]
    assert not result["readings"][by] < 2 * family.LAYER_TOL[by]
    if control == "bf16-scan-state":  # and by nothing else: the loss and a
        assert result["failed_by"] == ["scan"]  # layer's output cannot see it


def test_controls_name_faults_the_reference_knows(family):
    known = {"weights_dtype", "router_dtype", "scan_dtype", "skip_held",
             "skip_layers"}
    assert all(set(f) <= known for f in family.CONTROLS.values())
    assert {"bf16-router", "bf16-scan-state"} <= set(family.CONTROLS)


def test_readers_read_their_regions():
    plane = {"busy_s": 2.0, "regions": {"model/ssm": 0.5, "model/ssm_scan": 0.6,
                                        "model/moe": 0.3,
                                        "model/moe_experts": 0.1}}
    run = {"regions": {"coverage": 0.99, "planes": [plane]}}
    want = {"model.ssm_share_pct": 25.0, "model.ssm_scan_share_pct": 30.0,
            "model.moe_share_pct": 15.0, "model.moe_experts_share_pct": 5.0}
    man = mf.load_manifest()
    for name, value in want.items():
        entry = next(m for m in man["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert mf.load_metric(name).read(run) == pytest.approx(value)
        assert mf.load_metric(name).read({}) is None  # a program without them
    # the accepted readers the cell is appended to, and the one it is not
    listed = {m["name"] for m in mf.metrics_of(man, "per_layer", CELL)}
    assert {"kernels.flash_roofline_pct", "kernels.flash_share_pct",
            "step.forward_share_pct", "step.backward_share_pct",
            "step.recompute_share_pct", "step.optimizer_share_pct",
            "step.unscoped_share_pct", "model.attn_share_pct",
            "model.head_loss_share_pct", "step.mfu_pct"} <= listed
    assert "model.mlp_share_pct" not in listed
    assert [m["name"] for m in mf.metrics_of(man, "end_to_end", CELL)] == [
        "train.tokens_per_s", "setup_s"]
