"""The ``lfm2-8b-a1b`` configuration on the CPU: its file against the catalog
and against itself, the adapter, the parameter count and the FLOP count
against hand counts, the plain reference against the program at a reduced
width — equal in float32, and, through the cell's own comparison (the loss,
then sublayer by sublayer), correct in bf16 and NOT correct under each control
of ``reference/lfm2_moe.py:CONTROLS`` — and the new reader."""

import dataclasses
import json
import os
import types

import jax
import numpy as np
import pytest

from benchmark.harness import manifest as mf

NAME = "lfm2-8b-a1b"
CELL = NAME + ".train-b2s8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the catalog row's ``source_url`` and ``config``, copied (as PR 30 did): the
# driver's checkout has no /opt/skills
CATALOG_COPY = os.path.join(os.path.dirname(__file__), NAME + ".catalog.json")
REDUCED = ["hybrid_override_pattern", "num_experts", "vocab_size"]

# every width an eighth of the published one (the head 32 wide), the ratios of
# query to key-value heads (4 : 1) and of held to routed experts (1 : 4) kept
SIZES = dict(
    hidden_size=256, num_attention_heads=8, num_key_value_heads=2,
    vocab_size=2048, hybrid_override_pattern="C-*ECE*E",
    max_position_embeddings=128000, norm_eps=1e-5, rope_theta=1000000,
    conv_L_cache=3, conv_bias=False, intermediate_size=896, router_width=16,
    experts_held=[0, 1, 2, 3], num_experts_per_tok=4,
    moe_intermediate_size=224, routed_scaling_factor=1, norm_topk_prob=True,
    use_expert_bias=True)
SEQ = 256


@pytest.fixture(scope="module")
def config():
    return mf.load_config(mf.load_manifest(), NAME)


@pytest.fixture(scope="module")
def family():
    return mf.load_reference("lfm2_moe")


def test_file_holds_the_source_keys_twice_and_equal(config, family):
    """The source's keys stand at the top level (what the contract compares
    with the catalog) and under ``sizes`` (what the runner hands the
    adapter): the same values, the reduced keys cut in both. The published
    pattern is what ``layer_types`` and ``num_dense_layers`` declare, and the
    cut is its layers 0 and 2-6."""
    sizes = config["sizes"]
    extra = {"router_width", "experts_held"}
    assert set(sizes) - extra == {k for k in config if k in sizes}
    for key in set(sizes) - extra:
        assert config[key] == sizes[key], key
    assert config["reduced"] == REDUCED
    entry = next(c for c in mf.load_manifest()["configs"] if c["name"] == NAME)
    assert entry["reduced"] == REDUCED and entry["source"] == config["source"]
    assert (sizes["router_width"], sizes["experts_held"]) == (
        config["published"]["num_experts"], list(range(8)))
    assert len(sizes["experts_held"]) == sizes["num_experts"] == 8
    assert (sizes["num_hidden_layers"], sizes["num_dense_layers"],
            len(sizes["layer_types"])) == (24, 2, 24)
    blocks = family.published_pattern(sizes)
    assert "".join(blocks) == config["published"]["hybrid_override_pattern"]
    assert (blocks.count("C-"), blocks.count("CE"), blocks.count("*E"),
            blocks.count("*-")) == (2, 16, 6, 0)
    assert [i for i, b in enumerate(blocks) if b[0] == "*"] == [
        2, 6, 10, 14, 18, 21]
    cut = sizes["hybrid_override_pattern"]
    assert cut == "".join(blocks[i] for i in (0, 2, 3, 4, 5, 6))
    assert cut == "C-" + "*E" + "CE" * 3 + "*E"
    assert sizes["vocab_size"] * 4 == config["published"]["vocab_size"]


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
            cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.vocab_size,
            cfg.rms_eps, cfg.rope_theta) == (
                "nemotron_h", 2048, 12, "C-*ECECECE*E", 32, 8, 64, 16384,
                1e-5, 1e6)
    assert (cfg.conv_L_cache, cfg.conv_bias, cfg.qk_layernorm, cfg.attn_rope,
            cfg.ffn_dim, cfg.mlp_hidden_act) == (3, False, True, True, 7168,
                                                 "silu")
    assert (cfg.n_routed_experts, cfg.held_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.moe_shared_expert_intermediate_size,
            cfg.routed_scaling_factor, cfg.router_norm_eps) == (
                32, tuple(range(8)), 4, 1792, 0, 1, 1e-6)
    assert (cfg.dtype, cfg.param_dtype, cfg.use_flash_attention,
            cfg.use_fused_xent, cfg.tie_embeddings) == (
                "bfloat16", "float32", "auto", True, False)
    assert family.flash_call_shape(config["sizes"], 2, 8192) == (
        2, 8192, 32, 64)
    # the program's own preset is this configuration
    from distributed_training_with_pipeline_parallelism_tpu.models.nemotron_h import (
        nemotron_h_config)
    assert nemotron_h_config("lfm2-stage", **config["numerics"]) == cfg


def test_parameter_count_is_the_files_arithmetic(config, family):
    from distributed_training_with_pipeline_parallelism_tpu.models.transformer import (
        transformer_init)
    cfg = family.model_config(config["sizes"], config["numerics"])
    shapes = jax.eval_shape(lambda: transformer_init(jax.random.key(0), cfg))

    def count(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    d, layers = 2048, shapes["layers"]
    conv = d + d * 3 * d + 3 * d + d * d
    attn = d + 2 * d * 2048 + 2 * d * 512 + 2 * 64
    mlp = d + 3 * d * 7168
    moe = d + d * 32 + 32 + 8 * 3 * d * 1792
    assert count(layers["shortconv"]) == 4 * conv and round(conv / 1e4) == 1679
    assert count(layers["attn"]) == 2 * attn and round(attn / 1e4) == 1049
    assert count(layers["mlp"]) == mlp and round(mlp / 1e4) == 4404
    assert count(layers["moe"]) == 5 * moe and round(moe / 1e4) == 8815
    assert "shared" not in layers["moe"]
    total = 4 * conv + 2 * attn + mlp + 5 * moe + d + 2 * d * 16384
    assert count(shapes) == total == 640_010_656  # cut.parameters_here
    # the whole model by the same arithmetic: the published 8.3 B
    whole = 18 * conv + 6 * attn + 2 * mlp + 22 * (
        d + d * 32 + 32 + 32 * 3 * d * 1792) + d + d * 65536
    assert 8.3e9 < whole < 8.4e9


def test_flops_equal_a_hand_count(config, family):
    """Forward FLOPs a token, by hand from the published widths."""
    d = 2048
    conv = 2 * (d * 3 * d + d * d)
    attn = (2 * (2 * d * 2048 + 2 * d * 512)
            + 32 * 8192 * (64 + 64))            # Q K^T and P V, causal halves
    dense = 6 * d * 7168
    expert = 2 * d * 32 + (4 * 8 / 32) * 6 * d * 1792
    head = 2 * d * 16384
    want = 3 * (4 * conv + 2 * attn + dense + 5 * expert + head)
    got = family.train_flops_per_token(config["sizes"], 8192)
    assert got == pytest.approx(want, rel=1e-12)
    assert 1.52e9 < got < 1.53e9                # 3 x 509.2 MFLOP


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


def test_the_loss_sees_what_it_can(setting, family):
    """ONE scalar, the mean loss at random init over 1024 tokens, sees fp8
    matrices; the faults of single sublayers are the comparison's sublayer
    by sublayer."""
    _, _, reference, want = setting
    got = reference(**family.CONTROLS["fp8-matrices"])
    assert abs(got - want) / want > 2 * family.LOSS_TOL


@pytest.fixture(scope="module")
def layerwise():
    """``runners/train_layerwise.py:check`` as the cell calls it, at the
    reduced width on 1 sequence of 1024: positions far enough out for
    rotary angles kept in bf16 to show."""
    ctx = types.SimpleNamespace(
        workload=dict(mesh=dict(pipe=1), chips=1, check_sequences=1, batch=1,
                      seq=1024),
        config=dict(sizes=SIZES, reference="lfm2_moe", numerics=NUMERICS),
        devices=jax.devices(), seed=7, log=lambda msg: None)
    check = mf.load_runner("train_layerwise").check
    return lambda control: check(ctx, control)


def test_layer_by_layer_the_program_is_correct(layerwise, family):
    result = layerwise(None)
    assert result["failed_by"] == []
    assert set(result["readings"]) == set(family.LAYER_TOL) == {
        "out", "tokens_off"}
    assert result["readings"]["tokens_off"] == 0
    assert result["readings"]["out"] < family.LAYER_TOL["out"] / 1.5


# Each control is NOT correct, and by the reading that is there for it (the
# readings at this width are in the test's assertion messages when one fails).
@pytest.mark.parametrize("control,by,times", [
    ("dropped-conv-tap", "out", 2), ("dropped-qk-norms", "out", 2),
    ("bf16-rope-angles", "out", 1.5), ("bf16-router", "tokens_off", 2),
    ("dropped-held-expert", "tokens_off", 2), ("fp8-matrices", "out", 2),
    ("dropped-expert-layer", "out", 2)])
def test_layer_by_layer_each_control_is_not_correct(layerwise, family, control,
                                                    by, times):
    faults = family.CONTROLS[control]
    if control == "dropped-expert-layer":  # this stack's third sublayer
        faults = dict(skip_layers=(3,))
    result = layerwise(faults)
    assert by in result["failed_by"], result
    assert not result["readings"][by] < times * family.LAYER_TOL[by], result
    if control in ("bf16-rope-angles", "dropped-conv-tap", "dropped-qk-norms"):
        assert result["failed_by"] == ["out"]  # the routing cannot see it


def test_controls_name_faults_the_reference_knows(config, family):
    known = {"weights_dtype", "router_dtype", "angle_dtype", "skip_held",
             "skip_layers", "skip_tap", "skip_qk_norm"}
    assert all(set(f) <= known for f in family.CONTROLS.values())
    assert {"dropped-conv-tap", "dropped-qk-norms", "bf16-rope-angles",
            "bf16-router", "dropped-held-expert", "dropped-expert-layer",
            "fp8-matrices"} == set(family.CONTROLS)
    # the dropped sublayer of the cell's stack is an expert sublayer
    dropped, = family.CONTROLS["dropped-expert-layer"]["skip_layers"]
    assert config["sizes"]["hybrid_override_pattern"][dropped] == "E"
    # the runner special-cases the letters E and M: expert sublayers keep E,
    # and no kind of this family takes M
    assert family.STACK == {"C": "shortconv", "*": "attn", "-": "mlp",
                            "E": "moe"}


def test_a_program_without_the_kind_is_a_named_error(config, family,
                                                     monkeypatch):
    """What the parent commit does with this cell: fail at once, by name."""
    from distributed_training_with_pipeline_parallelism_tpu.models import (
        nemotron_h)
    monkeypatch.setattr(nemotron_h, "KINDS", {
        k: v for k, v in nemotron_h.KINDS.items() if v != "shortconv"})
    with pytest.raises(NotImplementedError, match="shortconv"):
        family.model_config(config["sizes"], config["numerics"])


def test_new_reader_equals_its_manifest_entry_and_reads_its_source():
    man = mf.load_manifest()
    name = "model.shortconv_share_pct"
    entry = next(m for m in man["per_layer"] if m["name"] == name)
    reader = mf.load_metric(name)
    assert (entry["layer"], entry["unit"], entry["better"], entry["moves"],
            entry["source"]) == (reader.LAYER, reader.UNIT, reader.BETTER,
                                 reader.MOVES, reader.SOURCE)
    assert entry["workloads"] == [CELL]
    plane = {"busy_s": 2.0, "regions": {"model/attn": 0.9,
                                        "model/shortconv": 0.5}}
    run = {"regions": {"coverage": 0.99, "planes": [plane]}}
    assert reader.read(run) == pytest.approx(25.0)
    assert reader.read({}) is None          # a run without regions
    # a program that names no such region (the parent): nothing, not 0
    del plane["regions"]["model/shortconv"]
    assert reader.read(run) is None


def test_the_cell_lists_the_accepted_readers_the_issue_names():
    man = mf.load_manifest()
    listed = {m["name"] for m in mf.metrics_of(man, "per_layer", CELL)}
    assert {"kernels.flash_share_pct", "kernels.flash_roofline_pct",
            "model.shortconv_share_pct", "step.forward_share_pct",
            "step.backward_share_pct", "step.recompute_share_pct",
            "step.optimizer_share_pct", "step.unscoped_share_pct",
            "model.attn_share_pct", "model.mlp_share_pct",
            "model.moe_share_pct", "model.moe_experts_share_pct",
            "model.head_loss_share_pct", "step.mfu_pct",
            "step.compiled_hbm_gb", "device.idle_pct"} <= listed
    assert not {"model.ssm_share_pct", "model.mla_latent_share_pct",
                "kernels.mla_flash_roofline_pct"} & listed
    assert [m["name"] for m in mf.metrics_of(man, "end_to_end", CELL)] == [
        "train.tokens_per_s", "setup_s"]
    cell = mf.load_workload(CELL)
    entry = mf.cell_entry(man, CELL)
    assert all(cell[k] == entry[k] for k in ("config", "traffic", "chips",
                                             "why"))
    assert man["workloads"][-1] == entry and man["configs"][-1]["name"] == NAME
