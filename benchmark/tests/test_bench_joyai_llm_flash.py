"""The ``joyai-llm-flash`` configuration on the CPU: its file against the
catalog and against itself, the adapter, the plain reference against the
program at a reduced width — equal in float32, and, through the cell's own
comparison (the loss, then sublayer by sublayer), correct in bf16 and NOT
correct under each control of ``reference/joyai_llm_flash.py:CONTROLS`` — the
two FLOP counts against hand counts, and the two new readers."""

import dataclasses
import json
import os
import types

import jax
import numpy as np
import pytest

from benchmark.harness import manifest as mf

NAME = "joyai-llm-flash"
CELL = NAME + ".train-b2s8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the catalog row's ``source_url`` and ``config``, copied (as PR 30 did): the
# driver's checkout has no /opt/skills
CATALOG_COPY = os.path.join(os.path.dirname(__file__), NAME + ".catalog.json")
REDUCED = ["hybrid_override_pattern", "n_routed_experts", "vocab_size"]

# every width a quarter to a sixteenth of the published one; the ratio of the
# rotary to the plain columns (1 : 2) and of q, k to v heads (3 : 2) kept
SIZES = dict(
    hidden_size=256, num_attention_heads=8, vocab_size=2048,
    hybrid_override_pattern="L-LELELE", max_position_embeddings=4096,
    rms_norm_eps=1e-6, rope_theta=32000000, q_lora_rank=192, kv_lora_rank=64,
    qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
    intermediate_size=512, hidden_act="silu", router_width=32,
    experts_held=[0, 1, 2, 3], num_experts_per_tok=4, n_shared_experts=1,
    moe_intermediate_size=96, routed_scaling_factor=2.5)
SEQ = 256


@pytest.fixture(scope="module")
def config():
    return mf.load_config(mf.load_manifest(), NAME)


@pytest.fixture(scope="module")
def family():
    return mf.load_reference("joyai_llm_flash")


def test_file_holds_the_source_keys_twice_and_equal(config):
    """The source's keys stand at the top level (what the contract compares
    with the catalog) and under ``sizes`` (what the runner hands the
    adapter): the same values, the reduced keys cut in both. The published
    pattern is what ``num_hidden_layers`` and ``first_k_dense_replace``
    declare, and the cut is its beginning."""
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
    dense, layers = sizes["first_k_dense_replace"], sizes["num_hidden_layers"]
    assert (dense, layers, sizes["moe_layer_freq"]) == (1, 40, 1)
    assert published == "L-" * dense + "LE" * (layers - dense)
    cut = sizes["hybrid_override_pattern"]
    assert published.startswith(cut) and cut == "L-" + "LE" * 7
    assert sizes["vocab_size"] * 8 == config["published"]["vocab_size"]


def test_every_number_but_the_reduced_is_the_catalogs(config):
    with open(CATALOG_COPY) as fh:
        row = json.load(fh)
    assert row["source_url"] == config["source"]
    assert "hybrid_override_pattern" not in row["config"]  # the program's key
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
            cfg.n_heads, cfg.vocab_size, cfg.rms_eps, cfg.rope_theta) == (
                "nemotron_h", 2048, 16, "L-" + "LE" * 7, 32, 16160, 1e-6,
                32e6)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.ffn_dim,
            cfg.mlp_hidden_act) == (1536, 512, 128, 64, 128, 7168, "silu")
    assert (cfg.n_routed_experts, cfg.held_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.moe_shared_expert_intermediate_size,
            cfg.routed_scaling_factor) == (
                256, tuple(range(8)), 8, 768, 768, 2.5)
    assert (cfg.dtype, cfg.param_dtype, cfg.use_flash_attention,
            cfg.use_fused_xent, cfg.remat_layers, cfg.tie_embeddings) == (
                "bfloat16", "float32", "auto", True, True, False)
    assert family.flash_call_shape(config["sizes"], 2, 8192) == (
        2, 8192, 32, 192)


def test_parameter_count_is_the_files_arithmetic(config, family):
    from distributed_training_with_pipeline_parallelism_tpu.models.transformer import (
        transformer_init)
    cfg = family.model_config(config["sizes"], config["numerics"])
    shapes = jax.eval_shape(lambda: transformer_init(jax.random.key(0), cfg))

    def count(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    d, layers = 2048, shapes["layers"]
    mla = (d + d * 1536 + 1536 + 1536 * 32 * 192 + d * (512 + 64) + 512
           + 512 * 32 * (128 + 128) + 32 * 128 * d)
    mlp = d + 3 * d * 7168
    moe = d + d * 256 + 256 + 8 * 3 * d * 768 + 3 * d * 768
    assert count(layers["mla"]) == 8 * mla and round(mla / 1e4) == 2635
    assert count(layers["mlp"]) == mlp and round(mlp / 1e4) == 4404
    assert count(layers["moe"]) == 7 * moe and round(moe / 1e4) == 4299
    assert count(shapes) == 8 * mla + mlp + 7 * moe + d + 2 * d * 16160
    assert count(shapes) == 621_989_632  # cut.parameters_here: 622.0 M


def test_flops_equal_a_hand_count(config, family):
    """Forward FLOPs a token, by hand from the published widths."""
    d = 2048
    mla = (2 * (d * 1536 + 1536 * 6144 + d * 576 + 512 * 8192 + 4096 * d)
           + 32 * 8192 * (192 + 128))          # Q K^T and P V, causal halves
    dense = 6 * d * 7168
    expert = 2 * d * 256 + 6 * d * 768 + (8 * 8 / 256) * 6 * d * 768
    head = 2 * d * 16160
    want = 3 * (8 * mla + dense + 7 * expert + head)
    got = family.train_flops_per_token(config["sizes"], 8192)
    assert got == pytest.approx(want, rel=1e-12)
    assert 4.0e9 < got < 4.02e9                 # 3 x 1.337 GFLOP


def test_two_width_flash_costs_equal_a_hand_count():
    from benchmark.flops import flash, flash_two_widths as two
    # 2 rows x 32 heads x 8192^2 under the mask: 2.147e9 score elements
    pairs = 0.5 * 2 * 32 * 8192 * 8192
    f, b = two.fwd(2, 8192, 32, 192, 128)
    assert f == 2 * pairs * (192 + 128)
    element = 2 * 8192 * 32 * 2
    assert b == element * (192 + 192 + 128 + 128) + 2 * 32 * 8192 * 4
    f, b = two.bwd(2, 8192, 32, 192, 128)
    assert f == 2 * pairs * (3 * 192 + 2 * 128)
    assert b == element * (4 * 192 + 4 * 128) + 2 * 32 * 8192 * 4
    # at equal widths it is the one-width count
    for kind in ("fwd", "bwd"):
        assert getattr(two, kind)(2, 1024, 16, 64, 64) == getattr(
            flash, kind)(2, 1024, 16, 64)


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
# tokens: fp8 matrices (PR 34, CPU). Not a dropped expert sublayer (5.1e-5:
# at random init an expert sublayer adds little to the stream), nor the
# router in bf16, nor the rotary angles in bf16: those are the comparison's
# sublayer by sublayer.
def test_the_loss_sees_what_it_can(setting, family):
    _, _, reference, want = setting
    got = reference(**family.CONTROLS["fp8-matrices"])
    assert abs(got - want) / want > 2 * family.LOSS_TOL
    got = reference(**family.CONTROLS["dropped-expert-layer"])
    assert abs(got - want) / want < family.LOSS_TOL


@pytest.fixture(scope="module")
def layerwise():
    """``runners/train_layerwise.py:check`` as the cell calls it, at the
    reduced width on 1 sequence of 1024: positions far enough out for
    rotary angles kept in bf16 to show."""
    ctx = types.SimpleNamespace(
        workload=dict(mesh=dict(pipe=1), chips=1, check_sequences=1, batch=1,
                      seq=1024),
        config=dict(sizes=SIZES, reference="joyai_llm_flash",
                    numerics=NUMERICS),
        devices=jax.devices(), seed=7, log=lambda msg: None)
    check = mf.load_runner("train_layerwise").check
    return lambda control: check(ctx, control)


def test_layer_by_layer_the_program_is_correct(layerwise, family):
    result = layerwise(None)
    assert result["failed_by"] == []
    assert set(result["readings"]) == set(family.LAYER_TOL) == {
        "out", "tokens_off"}
    assert result["readings"]["tokens_off"] == 0
    assert result["readings"]["out"] < family.LAYER_TOL["out"] / 2


# Each control is NOT correct, and by the reading that is there for it. At
# this width (PR 34, CPU; the program reads out 5.6e-3, no token off): the
# router in bf16 moves 6 tokens of 1024 to another held expert (tokens_off
# 5.9e-3); one held expert of four left out 1.9e-1 of the tokens; fp8
# matrices 2.7e-1 on a sublayer's output; rotary angles in bf16 2.5e-2 at
# 1024 positions and 16 rotary columns (a bf16 angle is off by up to 2 rad
# there; the cell has 8192 positions and 64 columns).
@pytest.mark.parametrize("control,by,times", [
    ("bf16-router", "tokens_off", 2), ("bf16-rope-angles", "out", 1.5),
    ("dropped-held-expert", "tokens_off", 2), ("fp8-matrices", "out", 2),
    ("dropped-expert-layer", "out", 2)])
def test_layer_by_layer_each_control_is_not_correct(layerwise, family, control,
                                                    by, times):
    result = layerwise(family.CONTROLS[control])
    assert by in result["failed_by"]
    assert not result["readings"][by] < times * family.LAYER_TOL[by]
    if control == "bf16-rope-angles":  # the routing cannot see it
        assert result["failed_by"] == ["out"]


def test_controls_name_faults_the_reference_knows(family):
    known = {"weights_dtype", "router_dtype", "angle_dtype", "skip_held",
             "skip_layers"}
    assert all(set(f) <= known for f in family.CONTROLS.values())
    assert {"bf16-router", "bf16-rope-angles", "dropped-held-expert",
            "fp8-matrices"} <= set(family.CONTROLS)
    # the runner special-cases the letters E and M: expert sublayers keep E,
    # and no kind of this family takes M
    assert family.STACK == {"L": "mla", "-": "mlp", "E": "moe"}


def test_new_readers_equal_their_manifest_entries_and_read_their_sources():
    man = mf.load_manifest()
    for name in ("model.mla_latent_share_pct", "kernels.mla_flash_roofline_pct"):
        entry = next(m for m in man["per_layer"] if m["name"] == name)
        reader = mf.load_metric(name)
        assert (entry["layer"], entry["unit"], entry["better"], entry["moves"],
                entry["source"]) == (reader.LAYER, reader.UNIT, reader.BETTER,
                                     reader.MOVES, reader.SOURCE)
        assert entry["workloads"] == [CELL]
    plane = {"busy_s": 2.0, "regions": {"model/attn": 0.9,
                                        "model/mla_latent": 0.5}}
    run = {"regions": {"coverage": 0.99, "planes": [plane]}}
    latent = mf.load_metric("model.mla_latent_share_pct")
    assert latent.read(run) == pytest.approx(25.0)
    assert latent.read({}) is None          # a run without regions
    # a program that names no such region (the parent): nothing, not 0
    del plane["regions"]["model/mla_latent"]
    assert latent.read(run) is None


def test_mla_flash_roofline_reader(config):
    """Two calls a kernel, from a hand-made trace: the forward's O is 128
    wide and the backward's dQ 192; both are flops-bound on the v5e."""
    from benchmark.flops import flash_two_widths as two
    reader = mf.load_metric("kernels.mla_flash_roofline_pct")
    rows, seq, heads = 2, 8192, 32
    calls = {"attn.1": {"kernel": "_flash_fwd_kernel",
                        "out_elements": rows * seq * heads * 128},
             "attn.2": {"kernel": "_flash_bwd_kernel",
                        "out_elements": rows * seq * heads * 192},
             "head_loss_.1": {"kernel": "_xent_fwd_kernel",
                              "out_elements": 16384}}
    trace = {"planes": [{"by_call": {"attn.1": (0.040, 2),
                                     "attn.2": (0.080, 2),
                                     "head_loss_.1": (0.010, 2)}}]}
    said = []
    run = {"trace": trace, "config": config, "workload": {"seq": seq},
           "pallas_calls": calls, "device_kind": "TPU v5 lite",
           "log": said.append}
    least = 2 * (two.fwd(rows, seq, heads, 192, 128)[0]
                 + two.bwd(rows, seq, heads, 192, 128)[0]) / 197e12
    assert reader.read(run) == pytest.approx(100 * least / 0.120)
    assert len(said) == 2 and all("flops-bound" in line for line in said)
    assert reader.read(dict(run, trace=None)) is None
    # a configuration of one head width: the accepted reader's, not this one's
    one_width = mf.load_config(mf.load_manifest(), "nemotron-twotower-30b-a3b")
    assert reader.read(dict(run, config=one_width)) is None


def test_the_cell_lists_the_accepted_readers_the_issue_names():
    man = mf.load_manifest()
    listed = {m["name"] for m in mf.metrics_of(man, "per_layer", CELL)}
    assert {"kernels.flash_share_pct", "kernels.mla_flash_roofline_pct",
            "model.mla_latent_share_pct", "step.forward_share_pct",
            "step.backward_share_pct", "step.recompute_share_pct",
            "step.optimizer_share_pct", "step.unscoped_share_pct",
            "model.attn_share_pct", "model.mlp_share_pct",
            "model.moe_share_pct", "model.moe_experts_share_pct",
            "model.head_loss_share_pct", "step.mfu_pct",
            "step.compiled_hbm_gb", "device.idle_pct"} <= listed
    # its count takes one head width
    assert "kernels.flash_roofline_pct" not in listed
    assert [m["name"] for m in mf.metrics_of(man, "end_to_end", CELL)] == [
        "train.tokens_per_s", "setup_s"]
    cell = mf.load_workload(CELL)
    entry = mf.cell_entry(man, CELL)
    assert all(cell[k] == entry[k] for k in ("config", "traffic", "chips",
                                             "why"))
