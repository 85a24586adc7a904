"""The host recorder of ``utils/profiling.py``: ``annotate`` keeps what it
times, JAX's compile events are filed by program name, ``fit``'s report has
a ``setup`` section — and none of it touches a compiled program."""

import copy
import importlib
import importlib.util
import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import distributed_training_with_pipeline_parallelism_tpu as dtpp
from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import make_mesh
from distributed_training_with_pipeline_parallelism_tpu.utils import profiling, train
from distributed_training_with_pipeline_parallelism_tpu.utils.profiling import annotate
from distributed_training_with_pipeline_parallelism_tpu.utils.telemetry import (
    validate_report)

TINY = dict(arch="gpt2", dim=32, n_layers=2, n_heads=4, vocab_size=64,
            ffn_dim=64, max_seq_len=16)


@pytest.fixture
def fresh():
    profiling.reset_host_spans()
    yield profiling
    profiling.reset_host_spans()


# ---- annotate ------------------------------------------------------------

def test_annotate_keeps_count_sum_and_longest(fresh):
    import time
    for pause in (0.0, 0.02, 0.0):
        with annotate("unit/span"):
            time.sleep(pause)
    row = fresh.host_spans()["unit/span"]
    assert row["count"] == 3
    assert row["seconds"] == pytest.approx(sum(s for _, s in row["recent"]))
    assert 0.02 <= row["longest_s"] <= row["seconds"]
    # the longest reading is the second one, and says when it began
    assert row["longest_start"] == row["recent"][1][0]
    assert fresh.host_seconds("unit/span") == row["seconds"]
    assert fresh.host_seconds("unit/never") is None
    cost = fresh.recorder_cost()
    assert cost["spans"] == 3 and 0 < cost["annotate_s"] < 0.01


def test_annotate_ring_is_bounded(fresh):
    for _ in range(fresh.RING + 10):
        with annotate("unit/many"):
            pass
    row = fresh.host_spans()["unit/many"]
    assert row["count"] == fresh.RING + 10
    assert len(row["recent"]) == fresh.RING
    starts = [start for start, _ in row["recent"]]
    assert starts == sorted(starts)  # the newest are the ones kept


def test_annotate_is_still_a_trace_annotation(fresh):
    span = annotate("unit/kind", why="a note")
    assert isinstance(span, jax.profiler.TraceAnnotation)
    with span:
        pass
    assert fresh.host_spans()["unit/kind"]["notes"] == {"why": "a note"}

    @annotate("unit/decorated")
    def twice(x):
        """doc kept"""
        return 2 * x

    assert twice(2) == 4 and twice(3) == 6
    assert twice.__name__ == "twice" and twice.__doc__ == "doc kept"
    assert fresh.host_spans()["unit/decorated"]["count"] == 2


def test_annotate_says_what_ran_inside_what_and_reraises(fresh):
    with pytest.raises(KeyError):
        with annotate("unit/outer"):
            with annotate("unit/inner"):
                pass
            raise KeyError("passed on")
    spans = fresh.host_spans()
    assert spans["unit/outer"]["count"] == 1 and not spans["unit/outer"]["inside"]
    assert spans["unit/inner"]["inside"] == {
        "unit/outer": pytest.approx(spans["unit/inner"]["seconds"])}
    with annotate("unit/inner"):  # and once on its own
        pass
    inner = fresh.host_spans()["unit/inner"]
    assert inner["count"] == 2
    assert inner["inside"]["unit/outer"] < inner["seconds"] or \
        inner["recent"][1][1] == 0


def test_the_import_span_survives_a_reset(fresh):
    row = fresh.host_spans()["setup/import"]
    assert row["count"] == 1 and row["seconds"] > 0
    # conftest imported jax before the package, and the span says so
    assert row["notes"] == {"jax_was_loaded": True}


def test_package_import_is_stamped_before_jax_is_imported():
    """In a fresh interpreter that has not imported jax, ``setup/import``
    holds jax's import: the stamp is taken before it."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, time, json\n"
         "t = time.perf_counter()\n"
         "import distributed_training_with_pipeline_parallelism_tpu\n"
         "whole = time.perf_counter() - t\n"
         "from distributed_training_with_pipeline_parallelism_tpu.utils "
         "import profiling\n"
         "row = profiling.host_spans()['setup/import']\n"
         "print(json.dumps([row['seconds'], whole, row['notes']]))\n"],
        check=True, capture_output=True, text=True).stdout
    seconds, whole, notes = json.loads(out.strip().splitlines()[-1])
    assert notes == {"jax_was_loaded": False}
    # top to bottom of __init__: all but the interpreter finding the package
    assert 0.8 * whole < seconds <= whole


# ---- JAX's compile events, by program ---------------------------------------

def _named(name):
    def fn(x):
        return jnp.sin(x) * 2 + 1
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def _filed(recorder, name):
    return [r for r in recorder.programs() if r["name"] == name]


def test_jitting_a_named_function_files_one_request(fresh):
    fn = _named("unit_program_a")
    x = jnp.ones((4,))
    fn(x)
    (req,) = _filed(fresh, "unit_program_a")
    # the trace event says ``unit_program_a``, the lowering and backend
    # events ``jit(unit_program_a)``: one request all the same
    for stage in ("trace_s", "lower_s", "backend_s"):
        assert req[stage] is not None and req[stage] > 0, stage
    # the suite runs with no cache directory: compiled, nothing written
    assert req["cache"] in (None, "uncached") and req["retrieval_s"] is None
    assert req["inlined"] >= 1  # jnp.sin is a jitted function traced inside
    assert not _filed(fresh, "sin")
    y = x + 1  # an eager operation is a program of its own: get it first
    events = fresh.recorder_cost()["events"]
    fn(x)
    fn(y)
    assert len(_filed(fresh, "unit_program_a")) == 1
    assert fresh.recorder_cost()["events"] == events  # a steady call: nothing


def test_a_second_lower_and_a_rebuild_file_later_requests(fresh):
    fn = _named("unit_program_b")
    x = jnp.ones((4,))
    fn.lower(x).compile()
    fn.lower(x)  # same function, same shapes: JAX fires a trace event only
    filed = _filed(fresh, "unit_program_b")
    assert len(filed) == 2
    assert filed[1]["lower_s"] is None and filed[1]["backend_s"] is None
    _named("unit_program_b").lower(x).compile()  # rebuilt: all three again
    filed = _filed(fresh, "unit_program_b")
    assert len(filed) == 3 and filed[2]["backend_s"] is not None
    step, later, others = fresh.split_programs(fresh.programs(),
                                               "unit_program_b")
    assert step == filed[0] and later == filed[1:]
    assert all("unit_program_b" not in r["name"] for r in others)
    assert [r["start"] for r in filed] == sorted(r["start"] for r in filed)


def test_a_request_says_which_span_it_ran_in(fresh):
    with annotate("unit/holder"):
        _named("unit_program_c")(jnp.ones((4,)))
    (req,) = _filed(fresh, "unit_program_c")
    assert req["inside"] == "unit/holder"


def test_a_span_closed_while_tracing_says_so(fresh):
    def fn(x):
        with annotate("unit/at_trace_time"):
            return x + 1
    fn.__name__ = "unit_program_d"
    jax.jit(fn)(jnp.ones((4,)))
    row = fresh.host_spans()["unit/at_trace_time"]
    assert list(row["inside"]) == ["trace of unit_program_d"]


_HIT = ("/jax/compilation_cache/compile_requests_use_cache",
        "/jax/compilation_cache/cache_hits")
_MISS = ("/jax/compilation_cache/compile_requests_use_cache",
         "/jax/compilation_cache/cache_misses")


@pytest.mark.parametrize("events,retrieval,cache", [
    (_HIT, 0.25, "hit"),
    (_MISS, None, "miss"),
    (_HIT[:1], None, "uncached"),
    ((), None, None),
], ids=["hit", "miss", "compiled-not-written", "cache-off"])
def test_cache_events_are_matched_to_the_backend_event(fresh, events,
                                                       retrieval, cache):
    """The cache's events carry no name: they belong to the backend event
    that follows them on the same thread (the order JAX 0.9.0 fires them
    in, read off with a cache directory set)."""
    fresh._on_duration("/jax/core/compile/jaxpr_trace_duration", 0.5,
                       fun_name="unit_program_e")
    fresh._on_duration("/jax/core/compile/jaxpr_to_mlir_module_duration",
                       0.125, fun_name="jit(unit_program_e)")
    for event in events:
        fresh._on_event(event)
    if retrieval is not None:
        fresh._on_duration("/jax/compilation_cache/compile_time_saved_sec",
                           3.0)
        fresh._on_duration("/jax/compilation_cache/cache_retrieval_time_sec",
                           retrieval)
    fresh._on_duration("/jax/core/compile/backend_compile_duration", 1.0,
                       fun_name="jit(unit_program_e)")
    (req,) = _filed(fresh, "unit_program_e")
    assert (req["trace_s"], req["lower_s"], req["backend_s"]) == (0.5, 0.125,
                                                                  1.0)
    assert req["cache"] == cache and req["retrieval_s"] == retrieval
    assert fresh.program_seconds(req) == 1.625
    # the next request starts clean
    fresh._on_duration("/jax/core/compile/backend_compile_duration", 1.0,
                       fun_name="jit(unit_program_f)")
    (nxt,) = _filed(fresh, "unit_program_f")
    assert nxt["cache"] is None and nxt["retrieval_s"] is None
    assert nxt["trace_s"] is None and nxt["lower_s"] is None


def test_the_list_of_requests_is_bounded(fresh, monkeypatch):
    for n in range(fresh.PROGRAMS_KEPT + 5):
        fresh._on_duration("/jax/core/compile/backend_compile_duration",
                           0.0, fun_name=f"jit(p{n})")
    assert len(fresh.programs()) == fresh.PROGRAMS_KEPT
    assert fresh.recorder_cost()["programs_dropped"] == 5


def test_a_listener_that_raises_does_not_break_a_compile(fresh, monkeypatch):
    def broken(*args, **kwargs):
        raise RuntimeError("the recorder is broken")
    monkeypatch.setattr(fresh, "_file", broken)
    out = _named("unit_program_g")(jnp.ones((4,)))
    assert float(out.sum()) == pytest.approx(4 * (jnp.sin(1.0) * 2 + 1))
    assert fresh.recorder_cost()["listener_errors"] >= 3
    assert not _filed(fresh, "unit_program_g")


def _mine(listeners):
    return [f for f in listeners
            if getattr(f, "_dtpp_host_recorder", False)]


def test_importing_the_module_twice_registers_one_listener():
    from jax._src import monitoring
    path = profiling.__file__
    spec = importlib.util.spec_from_file_location("profiling_again", path)
    again = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(again)  # a second copy under another name
    importlib.reload(profiling)     # and the module itself once more
    assert len(_mine(monitoring.get_event_duration_listeners())) == 1
    assert len(_mine(monitoring.get_event_listeners())) == 1
    # the one that stayed still files into the module everyone imports
    profiling.reset_host_spans()
    _named("unit_program_h")(jnp.ones((4,)))
    assert _filed(profiling, "unit_program_h")
    assert not again.programs()
    profiling.reset_host_spans()


# ---- fit ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def fit_report(tmp_path_factory):
    out = tmp_path_factory.mktemp("fit_report")
    profiling.reset_host_spans()
    cfg = dtpp.ModelConfig(**TINY)
    mesh = make_mesh(n_pipe=1, devices=jax.devices()[:1])
    params = train.init_params(cfg, mesh, jax.random.key(0))
    train.fit(cfg, mesh, dtpp.ScheduleConfig(name="1F1B", n_microbatches=2),
              params, train.synthetic_data(cfg, 4, 16), num_steps=3,
              log_every=1, verbose=False, report_dir=str(out))
    with open(out / "report.json") as fh:
        return json.load(fh), profiling.host_spans(), profiling.programs()


def test_fit_writes_a_setup_section(fit_report):
    manifest, _, _ = fit_report
    validate_report(manifest)
    setup = manifest["setup"]
    for name in ("setup/import", "setup/mesh", "setup/init_params",
                 "setup/init_opt_state", "setup/build_step",
                 "setup/first_step"):
        assert setup["spans"][name]["count"] >= 1, name
    assert setup["spans"]["setup/mesh"]["notes"] == {"backend_was_up": True}
    step = setup["step_program"]
    # fit's second call finds the trace in JAX's cache (an event of
    # microseconds, nothing lowered): a later request, left out
    assert step["name"] == "train_step" and step["later_requests"] <= 1
    assert min(step["trace_s"], step["lower_s"], step["backend_s"]) > 0
    # compile_s stays, and brackets what setup/first_step brackets
    first = setup["spans"]["setup/first_step"]["seconds"]
    assert manifest["timers"]["compile_s"] == pytest.approx(first, abs=0.05)
    assert (step["trace_s"] + step["lower_s"] + step["backend_s"]) < first
    other = setup["other_programs"]
    assert other["count"] >= 2  # the two init programs at least
    assert len(other["dearest"]) <= 5
    assert other["seconds"] >= sum(r["seconds"] for r in other["dearest"]) \
        - 1e-9
    assert setup["recorder_cost"]["listener_errors"] == 0


def _mutations():
    def drop(path):
        def go(setup):
            node = setup
            for key in path[:-1]:
                node = node[key]
            del node[path[-1]]
        return go

    def put(path, value):
        def go(setup):
            node = setup
            for key in path[:-1]:
                node = node[key]
            node[path[-1]] = value
        return go

    return [
        ("spans-gone", drop(["spans"]), "setup.spans"),
        ("span-name", lambda s: s["spans"].update(
            {"dispatch": s["spans"]["setup/mesh"]}), "must start with"),
        ("negative-seconds", put(["spans", "setup/mesh", "seconds"], -1.0),
         "seconds"),
        ("count-a-float", put(["spans", "setup/mesh", "count"], 1.5),
         "count"),
        ("inside-a-string", put(["spans", "setup/mesh", "inside"], "x"),
         "inside"),
        ("step-stage-gone", drop(["step_program", "lower_s"]), "lower_s"),
        ("step-cache", put(["step_program", "cache"], "maybe"), "cache"),
        ("others-gone", drop(["other_programs"]), "other_programs"),
        ("dearest-unnamed", put(["other_programs", "dearest"],
                                [{"seconds": 1.0}]), "dearest"),
        ("not-a-dict", None, "setup must be a dict"),
    ]


@pytest.mark.parametrize("mutate,message", [m[1:] for m in _mutations()],
                         ids=[m[0] for m in _mutations()])
def test_validate_report_rejects_a_mutated_setup_section(fit_report, mutate,
                                                         message):
    manifest = copy.deepcopy(fit_report[0])
    if mutate is None:
        manifest["setup"] = ["not", "a", "dict"]
    else:
        mutate(manifest["setup"])
    with pytest.raises(ValueError, match=message):
        validate_report(manifest)


def test_a_report_without_a_setup_section_still_validates(fit_report):
    manifest = copy.deepcopy(fit_report[0])
    del manifest["setup"]
    validate_report(manifest)  # a report from before the recorder
    manifest["setup"] = {**fit_report[0]["setup"], "step_program": None}
    validate_report(manifest)  # a run that never got to its step


def test_fits_spans_reach_the_table(fit_report):
    _, spans, programs = fit_report
    assert spans["input_wait"]["count"] == 3
    assert spans["dispatch"]["count"] == 3
    assert spans["wait_loss"]["count"] == 3  # log_every=1
    # the first dispatch ran inside setup/first_step, the others on their own
    assert list(spans["dispatch"]["inside"]) == ["setup/first_step"]
    assert spans["dispatch"]["inside"]["setup/first_step"] == pytest.approx(
        spans["dispatch"]["longest_s"])
    # the step program was requested inside that first dispatch
    step, *later = [r for r in programs if r["name"] == "train_step"]
    assert step["inside"] == "dispatch" and step["backend_s"] > 0
    assert all(r["lower_s"] is None and r["backend_s"] is None
               and r["trace_s"] < 0.01 for r in later)
    # and the init programs inside their spans
    assert any(r["inside"] == "setup/init_params" for r in programs)
    assert any(r["inside"] == "setup/init_opt_state" for r in programs)


def test_format_setup_prints_every_row(fit_report):
    text = profiling.format_setup(fit_report[0]["setup"])
    assert text.startswith("start-up")
    for name in fit_report[0]["setup"]["spans"]:
        assert name in text
    assert "step program train_step: trace" in text
    assert "other programs" in text


def test_fit_prints_the_startup_block_once(capsys):
    cfg = dtpp.ModelConfig(**TINY)
    mesh = make_mesh(n_pipe=1, devices=jax.devices()[:1])
    params = train.init_params(cfg, mesh, jax.random.key(0))
    train.fit(cfg, mesh, dtpp.ScheduleConfig(name="1F1B", n_microbatches=2),
              params, train.synthetic_data(cfg, 4, 16), num_steps=3,
              log_every=1, verbose=True)
    out = capsys.readouterr().out
    assert out.count("start-up (host seconds") == 1
    assert out.index("step 0: loss") < out.index("start-up") < out.index(
        "step 1: loss")


# ---- and nothing of it reaches a program ----------------------------------------

def _step_jaxpr():
    cfg = dtpp.ModelConfig(**TINY)
    mesh = make_mesh(n_pipe=1, devices=jax.devices()[:1])
    sched = dtpp.ScheduleConfig(name="1F1B", n_microbatches=2)
    opt = train.adamw(total_steps=10)
    params = jax.eval_shape(
        lambda: train.init_params(cfg, mesh, jax.random.key(0)))
    opt_state = jax.eval_shape(opt.init, params)
    tokens = jax.ShapeDtypeStruct((4, 16), jnp.int32)
    step = train.make_train_step(cfg, mesh, sched, opt)
    return str(jax.make_jaxpr(step)(params, opt_state, tokens, tokens))


def test_the_train_steps_jaxpr_does_not_know_the_recorder():
    profiling.reset_host_spans()
    before = _step_jaxpr()
    with annotate("unit/around"):
        during = _step_jaxpr()
    profiling.reset_host_spans()
    after = _step_jaxpr()
    assert before == during == after
    assert "callback" not in before
