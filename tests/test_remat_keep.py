"""What ``remat_layers`` keeps beside the flash pair (PR 39): named product
outputs, granted from a byte budget, dearest to recompute per byte first
(``ops/layers.py``: ``named_product``, ``offers_of``, ``choose_kept``,
``chip_room`` / ``remat_room``, ``remat_layer``; the walk over shapes is
``models/nemotron_h.py:kept_names``). The CPU's room is zero, so budgets are
passed explicitly here."""

import dataclasses
import functools
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distributed_training_with_pipeline_parallelism_tpu as dtpp
from distributed_training_with_pipeline_parallelism_tpu.models import (
    nemotron_h, transformer as tfm)
from distributed_training_with_pipeline_parallelism_tpu.models.gpt2 import (
    gpt2_config)
from distributed_training_with_pipeline_parallelism_tpu.ops import (
    attention, experts, layers, mamba2, shortconv)
from distributed_training_with_pipeline_parallelism_tpu.ops.layers import (
    Offer, choose_kept, chip_room, offers_of, remat_layer, remat_room)
from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import (
    make_mesh)
from distributed_training_with_pipeline_parallelism_tpu.utils import (
    profiling, train)

MB = 10 ** 6


def _offer(name, mb, contraction, dtype="bfloat16"):
    return Offer(name, (mb * MB // jnp.dtype(dtype).itemsize,), dtype,
                 contraction)


#: three layers: products over 2048 columns, one over 512, a float32 one
OFFERS = [[_offer("a", 400, 2048), _offer("b", 100, 512)],
          [_offer("c", 300, 2048), _offer("d", 200, 4096, "float32")],
          [_offer("e", 50, 2048)]]


@pytest.mark.parametrize("budget_mb,granted", [
    (0, []), (-5, []), (49, []),
    (50, ["2:e"]),                      # the first that fits, not the largest
    # float32 over 4096 columns spares what bf16 over 2048 does per byte: the
    # four tie, so the later layer first, and a layer's own in its order
    (349, ["2:e", "1:d"]), (350, ["2:e", "1:c"]), (400, ["2:e", "1:c"]),
    (550, ["2:e", "1:c", "1:d"]),
    (650, ["2:e", "1:c", "1:d", "0:b"]),   # the cheaper one last, if it fits
    (950, ["2:e", "1:c", "1:d", "0:a"]),
    (1049, ["2:e", "1:c", "1:d", "0:a"]),
    (1050, ["2:e", "1:c", "1:d", "0:a", "0:b"]),
    (10 ** 6, ["2:e", "1:c", "1:d", "0:a", "0:b"]),
    # a number an instant: what the layers before L may keep while L's
    # backward runs. The last layer's own is held at none of them but the
    # end of the forward; the first layer's at every one
    ([0, 100, 450, 10 ** 4], ["2:e", "1:c", "0:b"]),
    ([0, 0, 0, 10 ** 4], ["2:e"]),
    ([0, 500, 500, 500], ["2:e", "1:c", "0:b"]),
    ([0, 500, 500, 40], []),
])
def test_grants_follow_flops_per_byte_within_the_budget(caplog, budget_mb,
                                                        granted):
    several = isinstance(budget_mb, list)
    budget = [b * MB for b in budget_mb] if several else budget_mb * MB
    with caplog.at_level("INFO"):
        keep, record = choose_kept(OFFERS, budget)
    assert record["granted"] == granted
    nbytes = {f"{l}:{o.name}": o.nbytes for l, mine in enumerate(OFFERS)
              for o in mine}
    assert record["granted_bytes"] == sum(nbytes[n] for n in granted)
    for L, room in enumerate(budget if several else [budget] * 4):
        # never more held at an instant than it has room for
        assert sum(nbytes[n] for n in granted if int(n[0]) < L) <= max(0, room)
    assert record["budget_bytes"] == max(0, min(budget[1:]) if several
                                         else budget)
    assert sorted(record["granted"] + record["refused_for_room"]) == sorted(
        nbytes)
    assert keep == [tuple(n.split(":")[1] for n in granted
                          if n.startswith(f"{l}:")) for l in range(3)]
    # dearest per byte first: the product over 512 columns only after all
    # the others had their turn
    assert "0:b" not in granted[:-1] and record["names_offered"] == 5
    line, = [r.message for r in caplog.records if "budget" in r.message]
    assert f"granted {len(granted)} of them" in line
    assert (" ".join(granted) or "none") in line


def test_an_offer_counts_bytes_and_flops_from_its_shape():
    o = Offer("experts_w1", (16384, 8, 1792), "bfloat16", 2048)
    assert o.nbytes == 16384 * 8 * 1792 * 2 == 469_762_048
    assert o.flops_per_byte == 2048.0
    assert Offer("x", (4,), "float32", 2048).flops_per_byte == 1024.0


def test_a_chip_the_table_does_not_know_has_no_room():
    def mesh(kind):
        return types.SimpleNamespace(devices=np.array(
            [types.SimpleNamespace(device_kind=kind)], dtype=object))

    assert chip_room(make_mesh(n_pipe=1, devices=jax.devices()[:1]), 0) == 0
    assert chip_room(mesh("TPU v9"), 0) == 0
    v5e = layers.COMPILER_HBM_BYTES["TPU v5 lite"]
    assert v5e == 15.75e9 and layers.ROOM_MARGIN >= 0.03
    assert chip_room(mesh("TPU v5 lite"), 11.52e9) == pytest.approx(
        15.75e9 * (1 - layers.ROOM_MARGIN) - 11.52e9)
    assert chip_room(mesh("TPU v5 lite"), 16e9) == 0
    assert layers.current_room() == 0
    with remat_room(3e9):
        assert layers.current_room() == 3e9
    assert layers.current_room() == 0


#: preset -> (kind, the names its mixer offers)
SUBLAYERS = {
    "lfm2-moe": ("lfm2-debug", "moe", ("experts_w1", "experts_w3")),
    "lfm2-mlp": ("lfm2-debug", "mlp", ("mlp_up", "mlp_gate")),
    "lfm2-shortconv": ("lfm2-debug", "shortconv", ("shortconv_in",)),
    "lfm2-attn": ("lfm2-debug", "attn", ("attn_q", "attn_k", "attn_v")),
    "joyai-moe": ("joyai-debug", "moe", ("experts_w1", "experts_w3",
                                         "mlp_up", "mlp_gate")),
    "joyai-mla": ("joyai-debug", "mla", ("mla_q_a", "mla_q_b", "mla_kv_a",
                                         "mla_kv_b")),
    "nemotron-moe": ("debug", "moe", ("experts_w1", "mlp_up")),
    "nemotron-mamba": ("debug", "mamba", ("mamba_in",)),
}


#: XLA:CPU fuses the gated form's silu into other neighbours in a layer's
#: second run: a rematerialised gated expert sublayer reads 2e-7 - 5e-7 off
#: the plain one in three leaves, under the flash pair alone (PR 35's
#: policy, the parent's) as with every name granted. Every other sublayer
#: is bit for bit the plain one's.
GATED_EXPERTS = ("lfm2-moe", "joyai-moe")


def _same(a, b, exact):
    if exact:
        np.testing.assert_array_equal(a, b)
    else:
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-6)


def _sublayer(preset, kind, seq=32):
    cfg = nemotron_h.nemotron_h_config(preset)
    params = nemotron_h.mixer_init(jax.random.key(39), cfg, kind)
    h = jax.random.normal(jax.random.key(40), (2, seq, cfg.dim))
    return cfg, params, h


@pytest.mark.parametrize("case", list(SUBLAYERS))
def test_a_granted_name_spares_one_product_and_changes_no_gradient(case):
    """The lowered ``value_and_grad`` of one rematerialised sublayer holds
    one ``dot_general`` fewer for every granted name than under the flash
    pair alone (PR 35's policy), and every gradient leaf is the
    un-rematerialised layer's in float32 (bit for bit wherever the parent's
    policy is: :data:`GATED_EXPERTS`)."""
    preset, kind, names = SUBLAYERS[case]
    cfg, params, h = _sublayer(preset, kind)
    one = functools.partial(nemotron_h.mixer_apply, cfg, kind)
    assert tuple(o.name for o in offers_of(one, params, h)) == names

    def grads(f):
        return jax.jit(jax.value_and_grad(
            lambda p, x: jnp.sum(f(p, x)[0] ** 2), argnums=(0, 1)))

    forms = {"granted": remat_layer(one, 1, names), "pair": remat_layer(one, 1),
             "none": one}
    dots = {k: grads(f).lower(params, h).as_text().count("dot_general")
            for k, f in forms.items()}
    # (the router's product, a norm's or a latent's may still run twice)
    assert dots["none"] <= dots["granted"] == dots["pair"] - len(names), dots
    # one name at a time too: each spares exactly its own product
    for name in names:
        one_name = grads(remat_layer(one, 1, (name,))).lower(
            params, h).as_text().count("dot_general")
        assert dots["pair"] - one_name == 1, (name, dots, one_name)
    got = {k: grads(f)(params, h) for k, f in forms.items()}
    for k in ("granted", "pair"):
        for a, b in zip(jax.tree.leaves(got[k]), jax.tree.leaves(got["none"])):
            assert a.dtype == jnp.float32
            _same(a, b, exact=case not in GATED_EXPERTS)


def _strip_names(monkeypatch):
    """The parent's program: no product is named."""
    for mod in (attention, experts, mamba2, shortconv):
        monkeypatch.setattr(mod, "named_product", lambda y, *a: y)


def _step_text(cfg, seq=32, batch=4):
    mesh = make_mesh(n_pipe=1, devices=jax.devices()[:1])
    opt = train.adamw(total_steps=10)
    shapes = jax.eval_shape(lambda: tfm.transformer_init(jax.random.key(0),
                                                         cfg))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    step = train.make_train_step(
        cfg, mesh, dtpp.ScheduleConfig(name="1F1B", n_microbatches=2), opt)
    text = step.lower(shapes, jax.eval_shape(opt.init, shapes), tokens,
                      tokens).as_text()
    # the number the lowering puts on a private function's name counts the
    # process's lowerings, not the program's
    return re.sub(r"@(\w+?)_\d+\b", r"@\1", text)


@pytest.mark.parametrize("make", [
    lambda: nemotron_h.nemotron_h_config("lfm2-debug", remat_layers=True),
    lambda: nemotron_h.nemotron_h_config(
        "joyai-debug", remat_layers=True, dtype="bfloat16",
        param_dtype="float32"),
    lambda: gpt2_config("small", n_layers=2, dim=64, n_heads=4, vocab_size=256,
                        max_seq_len=64),
    lambda: gpt2_config("small", n_layers=2, dim=64, n_heads=4, vocab_size=256,
                        max_seq_len=64, remat_layers=True),
], ids=["lfm2-debug", "joyai-debug-bf16", "gpt2", "gpt2-remat"])
def test_a_budget_of_zero_lowers_to_the_parents_text(monkeypatch, make):
    """On the CPU the room is zero: the whole train step lowers to the text
    it has with no product named at all (the parent's program) — a name
    outside a policy, or not granted inside one, lowers to nothing. A GPT-2
    step (``remat_layers`` false in every GPT-2 cell) too."""
    cfg = make()
    ours = _step_text(cfg)
    if cfg.arch == "nemotron_h":
        notes = profiling.host_spans()["setup/remat_keep"]["notes"]
        assert notes["room_bytes"] == 0 and notes["granted"] == []
        assert notes["names_offered"] > 0
    _strip_names(monkeypatch)
    assert ours == _step_text(cfg)


def _toy(preset):
    cfg = nemotron_h.nemotron_h_config(preset)
    params = tfm.transformer_init(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, cfg.vocab_size)

    def grads(c):
        return jax.jit(jax.value_and_grad(
            lambda p: tfm.transformer_loss(c, p, tokens, tokens)))

    return cfg, params, grads


@pytest.mark.parametrize("preset", ["lfm2-debug", "joyai-debug", "debug"])
def test_a_patterned_model_keeps_every_gradient_with_everything_granted(
        preset, caplog):
    """A toy patterned model traced inside a room that grants every name:
    loss and every gradient leaf are the un-rematerialised program's in
    float32 (bit for bit without gated experts: :data:`GATED_EXPERTS`), and
    the summary line says what the record says."""
    cfg, params, grads = _toy(preset)
    remat = dataclasses.replace(cfg, remat_layers=True)
    want = grads(cfg)(params)
    with caplog.at_level("INFO"), remat_room(1e9):
        got = grads(remat)(params)
    notes = profiling.host_spans()["setup/remat_keep"]["notes"]
    assert notes["names_granted"] == notes["names_offered"] > 0
    assert notes["refused_for_room"] == []
    assert notes["room_bytes"] == 10 ** 9
    assert notes["budget_bytes"] == min(notes["instant_bytes"][1:])
    line = [r.message for r in caplog.records if "budget" in r.message][-1]
    said = re.search(r"budget ([\d.]+) GB; (\d+) named products offered, "
                     r"([\d.]+) GB; granted (\d+) of them, ([\d.]+) GB: (.*); "
                     r"refused for room: (.*)$", line)
    assert (int(said[2]), int(said[4])) == (notes["names_offered"],
                                            notes["names_granted"])
    assert said[6].split() == notes["granted"] and said[7] == "none"
    assert float(said[1]) == round(notes["budget_bytes"] / 1e9, 3)
    assert float(said[5]) == round(notes["granted_bytes"] / 1e9, 3)
    for name in {n.split(":")[1] for n in notes["granted"]}:
        assert f"a layer keeps {name} " in caplog.text
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == jnp.float32
        _same(a, b, exact=preset == "debug")


def test_room_at_every_instant_grants_the_later_layers_first():
    """lfm2's toy pattern ``C-*ECE``: every product reads the 64-wide
    stream, so all tie and the later layer goes first. What a layer keeps is
    held until its own backward: the last layer's pair costs nothing at any
    layer's backward, the in-projection before it is held at layer 5's
    only, and with room at layer 4's backward for just the expert pair
    before it, the first three layers' products are refused."""
    cfg, params, grads = _toy("lfm2-debug")
    remat = dataclasses.replace(cfg, remat_layers=True)
    plan = nemotron_h.layer_plan(cfg)
    h = jax.ShapeDtypeStruct((2, 32, cfg.dim), jnp.float32)
    offers = [offers_of(functools.partial(nemotron_h.mixer_apply, cfg, kind),
                        jax.tree.map(lambda x: x[0], params["layers"][kind]),
                        h) for kind, _ in plan]
    free = nemotron_h.instants(cfg, params["layers"], h, offers, 0.0)
    nbytes = [sum(o.nbytes for o in mine) for mine in offers]
    size = {k: sum(x.size * 4 for x in jax.tree.leaves(v))
            for k, v in params["layers"].items()}
    h_bytes = 2 * 32 * 64 * 4
    # layer 5's backward (an expert layer, the stack's last): the other
    # three stacks' gradients are not made yet; six inputs, no flash pair
    # (dense attention on the CPU), its own working set
    assert free[5] == (size["shortconv"] + size["mlp"] + size["attn"]
                       - 6 * h_bytes - 0.75 * nbytes[5])
    # layer 0's: only its own stack's gradient is not there yet... and it is
    assert free[0] == -h_bytes - 0.75 * nbytes[0]
    assert free[6] == sum(size.values()) - 6 * h_bytes
    assert len(free) == 7
    assert free[4] + nbytes[4] < free[5]    # layer 4's backward is tighter
    room = -free[4] + nbytes[3]
    with remat_room(room):
        grads(remat).lower(params)
    notes = profiling.host_spans()["setup/remat_keep"]["notes"]
    assert notes["room_bytes"] == int(room)
    assert notes["instant_bytes"] == [int(room + f) for f in free]
    assert notes["granted"] == [
        "5:experts_w1", "5:experts_w3", "4:shortconv_in", "3:experts_w1",
        "3:experts_w3"]
    assert notes["refused_for_room"] == [
        "2:attn_q", "2:attn_k", "2:attn_v", "1:mlp_up", "1:mlp_gate",
        "0:shortconv_in"]
    assert notes["granted_bytes"] == sum(nbytes[3:])


def test_a_train_step_hands_its_room_to_the_stack(monkeypatch):
    """``make_train_step`` declares the room while its step is traced —
    from the parameter and optimizer trees it is given — and the stack
    walker reads it: with a chip that has room, the step keeps products
    and trains to the numbers of the step that keeps none."""
    cfg = nemotron_h.nemotron_h_config(
        "lfm2-debug", remat_layers=True, dtype="bfloat16",
        param_dtype="float32")
    mesh = make_mesh(n_pipe=1, devices=jax.devices()[:1])
    sched = dtpp.ScheduleConfig(name="1F1B", n_microbatches=2)
    opt = train.adamw(total_steps=10)
    params = train.init_params(cfg, mesh, jax.random.key(0))
    state = train.init_opt_state(opt, params, mesh)
    tokens = jax.random.randint(jax.random.key(1), (4, 32), 0, cfg.vocab_size)
    n = sum(x.size for x in jax.tree.leaves(params))
    held = []

    def run():
        step = train.make_train_step(cfg, mesh, sched, opt)
        p, s = jax.tree.map(jnp.copy, (params, state))  # the step donates
        for _ in range(2):  # the first step's learning rate is 0
            p, s, loss = step(p, s, tokens, tokens)
        return p, loss, profiling.host_spans()["setup/remat_keep"]["notes"]

    none = run()
    assert none[2]["room_bytes"] == 0 and none[2]["names_granted"] == 0

    def room(mesh_, held_bytes):
        held.append(held_bytes)
        return 1e9

    monkeypatch.setattr(train, "chip_room", room)
    kept = run()
    # parameters, gradients and two moments in float32, the bf16 copies,
    # and AdamW's step count
    assert held and 0 <= held[0] - n * (4 * 4 + 2) <= 64
    assert kept[2]["room_bytes"] == 10 ** 9
    assert kept[2]["names_granted"] == kept[2]["names_offered"] == 11
    assert float(kept[1]) == float(none[1])
    for a, b in zip(jax.tree.leaves(kept[0]), jax.tree.leaves(none[0])):
        np.testing.assert_array_equal(a, b)
