"""The five ``setup.*_s`` readers and ``harness/startup.py``: what they read
from the program's host recorder, and that they read nothing — without
raising — from a program that has none."""

import types

import pytest

from benchmark.harness import manifest as mf
from benchmark.harness import startup

READERS = {
    "setup.import_s": ("start-up", "program_span"),
    "setup.init_s": ("start-up", "program_span"),
    "setup.step_trace_lower_s": ("compile cache", "program_counter"),
    "setup.step_backend_s": ("compile cache", "program_counter"),
    "setup.other_programs_s": ("compile cache", "program_counter"),
}


def req(name, start, trace=None, lower=None, backend=None, cache=None,
        retrieval=None, inlined=0, inside=None):
    return {"name": name, "start": start, "trace_s": trace, "lower_s": lower,
            "backend_s": backend, "cache": cache, "retrieval_s": retrieval,
            "inlined": inlined, "inside": inside}


# a run as train_scoped leaves it: the init programs, the step, an eager
# operation, then - after the window - the step lowered again with what that
# traces on the way
TABLE = [
    req("<lambda>", 12.0, 0.5, 0.25, 2.0, "hit", 1.5, 40, "setup/init_params"),
    req("init_fn", 15.0, 0.125, 0.125, 0.5, "hit", 0.25, 3,
        "setup/init_opt_state"),
    req("train_step", 16.0, 8.0, 2.0, 20.0, "hit", 19.0, 9000),
    req("convert_element_type", 40.0, 0.0625, 0.0625, 0.125, "miss"),
    req("train_step", 90.0, 7.0, 2.0, 21.0, "hit", 20.0, 9000),
    req("<lambda>", 91.0, 0.25),  # an eval_shape: traced only
]
SPANS = {
    "setup/import": {"count": 1, "seconds": 4.0, "longest_s": 4.0,
                     "longest_start": 10.0, "inside": {},
                     "notes": {"jax_was_loaded": False}, "recent": [(10., 4.)]},
    "setup/init_params": {"count": 2, "seconds": 3.0, "longest_s": 2.0,
                          "longest_start": 12.0, "inside": {}, "notes": {},
                          "recent": [(12.0, 2.0), (14.0, 1.0)]},
    "setup/init_opt_state": {"count": 1, "seconds": 0.75, "longest_s": 0.75,
                             "longest_start": 15.0, "inside": {}, "notes": {},
                             "recent": [(15.0, 0.75)]},
    "setup/schedule": {"count": 1, "seconds": 0.5, "longest_s": 0.5,
                       "longest_start": 15.9,
                       "inside": {"setup/build_step": 0.5}, "notes": {},
                       "recent": [(15.9, 0.5)]},
}


def fake_recorder(table=TABLE, spans=SPANS):
    return types.SimpleNamespace(
        programs=lambda: [dict(r) for r in table],
        host_spans=lambda: spans,
        host_seconds=lambda name: spans[name]["seconds"]
        if name in spans else None,
        recorder_cost=lambda: {"annotate_s": 1e-5, "spans": 9,
                               "listener_s": 0.05, "events": 18000,
                               "listener_errors": 0, "programs_dropped": 0})


@pytest.fixture
def recorded(monkeypatch):
    monkeypatch.setattr(startup, "recorder", fake_recorder)


def read(name, log=lambda msg: None):
    return mf.load_metric(name).read({"log": log})


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_constants_equal_the_manifests_entry(name):
    entry = next(m for m in mf.load_manifest()["per_layer"]
                 if m["name"] == name)
    reader = mf.load_metric(name)
    assert (reader.LAYER, reader.SOURCE) == READERS[name]
    assert (reader.UNIT, reader.BETTER, reader.MOVES) == ("s", "lower",
                                                          "setup_s")
    assert entry == {"name": name, "unit": "s", "better": "lower",
                     "source": reader.SOURCE, "layer": reader.LAYER,
                     "moves": "setup_s"}  # no workloads list: every cell


def test_the_five_are_appended_and_nothing_else_moved():
    per_layer = mf.load_manifest()["per_layer"]
    assert [m["name"] for m in per_layer[-5:]] == list(READERS)
    assert [m["name"] for m in per_layer if m["moves"] == "setup_s"] == [
        "setup.cache_misses", "setup.compile_s", *READERS]


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_finds_nothing_without_a_recorder(name, monkeypatch):
    logged = []
    monkeypatch.setattr(startup, "recorder", lambda: None)
    assert read(name, logged.append) is None
    assert not logged


def test_a_program_from_before_the_recorder_has_none(monkeypatch):
    """The parent's ``utils/profiling.py`` has ``annotate`` and ``classify``
    and no table: that is no recorder."""
    from distributed_training_with_pipeline_parallelism_tpu.utils import (
        profiling)
    assert startup.recorder() is profiling
    for gone in ("programs", "host_seconds", "host_spans"):
        with monkeypatch.context() as m:
            m.delattr(profiling, gone)
            assert startup.recorder() is None
            assert read("setup.other_programs_s") is None


def test_span_readers_sum_the_named_spans(recorded):
    assert read("setup.import_s") == 4.0
    assert read("setup.init_s") == 3.75  # both calls of init_params


def test_a_span_the_table_lacks_reads_as_nothing(monkeypatch):
    spans = {k: v for k, v in SPANS.items() if k != "setup/init_opt_state"}
    monkeypatch.setattr(startup, "recorder",
                        lambda: fake_recorder(spans=spans))
    assert read("setup.init_s") is None
    assert read("setup.import_s") == 4.0


def test_step_readers_take_the_first_request(recorded):
    assert read("setup.step_trace_lower_s") == 10.0  # 8 + 2, not 7 + 2
    assert read("setup.step_backend_s") == 20.0      # not 21


def test_step_readers_match_the_name_as_step_window_does(monkeypatch):
    table = [req("guarded_train_step_dyn", 1.0, 1.0, 0.5, 2.0)]
    monkeypatch.setattr(startup, "recorder", lambda: fake_recorder(table))
    assert read("setup.step_trace_lower_s") == 1.5
    monkeypatch.setattr(startup, "recorder", lambda: fake_recorder(
        [req("init_fn", 1.0, 1.0, 0.5, 2.0)]))
    assert read("setup.step_trace_lower_s") is None
    assert read("setup.step_backend_s") is None
    # traced and lowered, never compiled: the first has something, the
    # second nothing to read
    monkeypatch.setattr(startup, "recorder", lambda: fake_recorder(
        [req("train_step", 1.0, 1.0, 0.5)]))
    assert read("setup.step_trace_lower_s") == 1.5
    assert read("setup.step_backend_s") is None


def test_other_programs_leave_every_step_request_out(recorded):
    # 2.75 + 0.75 + 0.25 + 0.25; neither 30 s nor the rebuild's 30 s
    assert read("setup.other_programs_s") == 4.0


def test_other_programs_logs_the_table_dearest_first(recorded):
    lines = []
    read("setup.other_programs_s", lines.append)
    text = "\n".join(lines)
    rows = [ln for ln in lines if ln.startswith("  ") and " at +" in ln]
    assert [r.split()[2] for r in rows] == ["<lambda>", "init_fn",
                                            "convert_element_type"]
    assert "at +2.00 s" in rows[0]  # since the package's import began
    assert "read from the cache" in rows[0] and "(read 1.500)" in rows[0]
    assert "inside setup/init_params" in rows[0]
    assert "compiled, written" in rows[2]
    assert "3 lowered or compiled, 3.750 s; 1 functions only traced" in text
    assert "1 requests and 0.250 s came after the step program was asked " \
           "for again" in text
    assert sum("the step program, first request" in ln for ln in lines) == 1
    assert sum("the step program, asked for again" in ln for ln in lines) == 1


def test_import_reader_logs_every_span_and_the_recorders_cost(recorded):
    lines = []
    read("setup.import_s", lines.append)
    text = "\n".join(lines)
    for name in SPANS:
        assert f"  {name}: " in text
    assert "0.500 inside setup/build_step" in text
    assert "jax_was_loaded=False" in text
    assert "50.000 ms in 18000 events" in text


def test_the_real_recorder_feeds_the_readers():
    """No fake: a jitted ``train_step`` and two spans through the program's
    own ``annotate``, read by the files the manifest names."""
    import jax
    import jax.numpy as jnp
    from distributed_training_with_pipeline_parallelism_tpu.utils import (
        profiling)
    profiling.reset_host_spans()

    def train_step(x):
        return jnp.cos(x) + 1
    with profiling.annotate("setup/init_params"):
        jax.jit(lambda x: x * 2)(jnp.ones(3))
    with profiling.annotate("setup/init_opt_state"):
        pass
    jax.jit(train_step).lower(jnp.ones(3)).compile()
    jax.jit(train_step).lower(jnp.ones(3)).compile()  # a rebuild
    values = {name: read(name) for name in READERS}
    step, later, others = startup.split(profiling.programs())
    assert step["name"] == "train_step" and len(later) == 1
    assert values["setup.step_trace_lower_s"] == pytest.approx(
        step["trace_s"] + step["lower_s"])
    assert values["setup.step_backend_s"] == pytest.approx(step["backend_s"])
    assert values["setup.other_programs_s"] == pytest.approx(
        sum(map(startup.seconds_of, others)))
    assert values["setup.init_s"] == pytest.approx(
        profiling.host_seconds("setup/init_params")
        + profiling.host_seconds("setup/init_opt_state"))
    assert values["setup.import_s"] > 0
    profiling.reset_host_spans()
