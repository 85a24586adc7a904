"""The reduction from a trace to numbers, on hand-made intervals."""

import pytest

from benchmark.harness import trace_reduce as tr
from benchmark.harness.trace_reduce import Event

US = 1e3  # the synthetic traces below are written in microseconds


def ev(name, start_us, dur_us, **stats):
    return Event(name, start_us * US, (start_us + dur_us) * US, stats)


def plane(ops, modules=()):
    return {tr.OPS_LINE: list(ops), tr.MODULES_LINE: list(modules)}


def test_merge_and_union_count_overlap_once():
    spans = [(0, 10), (5, 15), (15, 20), (30, 40), (32, 35)]
    assert tr.merge(spans) == [(0, 20), (30, 40)]
    assert tr.union_ns(spans, 0, 100) == 30          # the sum would be 48
    assert tr.union_ns(spans, 8, 33) == 12 + 3        # clipped to the window
    assert tr.gaps(tr.merge(spans), 0, 50) == [(20, 30), (40, 50)]


def test_busy_is_a_union_containers_skipped_collectives_apart():
    ops = [
        ev("%fusion.1 = ...", 0, 40, hlo_category="convolution fusion"),
        # overlaps .1, and consumes a permute's result: still compute
        ev("%fusion.2 = bf16[8] fusion(bf16[8] %collective-permute-done.4)",
           30, 30),
        ev("%while.3", 0, 100, hlo_category="while"),           # container
        ev("%collective-permute-start.4", 60, 5,
           hlo_category="collective-permute"),
        ev("%collective-permute-done.4", 65, 25,
           hlo_category="collective-permute"),
        ev("%all-reduce.7", 90, 5, hlo_category="all-reduce"),
    ]
    p = tr.reduce_plane({"/device:TPU:0": plane(ops)}, "/device:TPU:0",
                        0, 100 * US)
    assert p["window_s"] == pytest.approx(100e-6)
    assert p["compute_s"] == pytest.approx(60e-6)     # 0-60; the sum is 70
    assert p["permute_s"] == pytest.approx(30e-6)
    assert p["collective_s"] == pytest.approx(35e-6)
    assert p["busy_s"] == pytest.approx(95e-6)        # the while is not in it
    assert p["by_label"]["fusion"] == (pytest.approx(70e-6), 2)


def test_containers_without_a_category_are_known_by_name():
    assert tr.is_container(ev("%while.12", 0, 1))
    assert tr.is_container(ev("call.3 = f32[] call(...)", 0, 1))
    assert not tr.is_container(ev("%while_body_fusion.3", 0, 1))
    assert not tr.is_container(ev("%fusion.1", 0, 1, hlo_category="fusion"))


def test_pallas_call_is_labelled_by_its_kernel_function():
    kernel_of = {"closed_call.43": "_flash_fwd_kernel_packed"}
    e = ev("%closed_call.43 = (bf16[8,1024,1024]) custom-call(...)", 0, 1,
           tf_op="jit(train_step)/jvp()/while/body/closed_call/pallas_call")
    assert tr.op_label(e, kernel_of) == "_flash_fwd_kernel_packed"
    assert tr.op_label(e) == "closed_call"
    assert tr.op_label(ev("%fusion.77", 0, 1, tf_op="jit(f)/pp/bwd/dot")) \
        == "pp/bwd:fusion"


def hlo_call(name, out, kernel):
    import base64
    body = base64.b64encode(b"ML\xefR\x00mosaic\x00" + kernel.encode()
                            + b"\x00func\x00").decode()
    return (f'  %{name} = {out} custom-call(%a, %b), '
            f'custom_call_target="tpu_custom_call", metadata={{op_name='
            f'"jit(train_step)/jvp()/pallas_call"}}, backend_config='
            f'{{"custom_call_config":{{"body":"{body}"}}}}\n')


def test_kernels_are_read_from_the_compiled_text():
    from benchmark.harness import kernels
    text = ("HloModule jit_train_step\n  %fusion.1 = f32[8] fusion(%x)\n"
            + hlo_call("closed_call.43", "(bf16[8,1024,1024]{2,1,0}, "
                       "f32[8,8,2,1024]{3,2,1,0})", "_flash_fwd_kernel_packed")
            + hlo_call("jvp__.1", "f32[8192,1]{1,0}", "_xent_fwd_kernel")
            + '  %cc = f32[2] custom-call(%y), custom_call_target="Sharding"\n')
    assert kernels.pallas_calls(text) == {
        "closed_call.43": {"kernel": "_flash_fwd_kernel_packed",
                           "out_elements": 8 * 1024 * 1024},
        "jvp__.1": {"kernel": "_xent_fwd_kernel", "out_elements": 8192}}
    assert kernels.instruction("%closed_call.43 = (bf16[8]) custom-call()") \
        == "closed_call.43"


def test_flash_readers_take_kernels_rows_and_time_from_program_and_trace():
    """8 rows a call (read from the call's output, not from the workload),
    each call taking twice its roofline time: 50%."""
    from benchmark.flops import flash
    from benchmark.harness import manifest as mf
    from benchmark.harness.peaks import peak
    man = mf.load_manifest()
    config = mf.load_config(man, "gpt2-medium")
    calls = {"closed_call.43": {"kernel": "_flash_fwd_kernel_packed",
                                "out_elements": 8 * 1024 * 16 * 64},
             "closed_call.44": {"kernel": "_flash_bwd_kernel_packed",
                                "out_elements": 8 * 1024 * 16 * 64},
             "jvp__.1": {"kernel": "_xent_fwd_kernel", "out_elements": 8192}}
    chip = peak("TPU v5 lite")
    t_fwd = flash.least_seconds(*flash.fwd(8, 1024, 16, 64), chip)[0] * 1e9
    t_bwd = flash.least_seconds(*flash.bwd(8, 1024, 16, 64), chip)[0] * 1e9
    ops, t = [], 0.0
    for _ in range(3):
        for name, dur in (("closed_call.43", 2 * t_fwd), ("fusion.5", 1e6),
                          ("closed_call.44", 2 * t_bwd), ("jvp__.1", 1e5)):
            ops.append(Event(f"%{name} = ...", t, t + dur, {}))
            t += dur
    trace = {"/device:TPU:0": plane(ops)}
    kernel_of = {k: v["kernel"] for k, v in calls.items()}
    reduced = tr.reduce(trace, "train_step", 3, kernel_of=kernel_of)
    lines = []
    run = {"trace": reduced, "pallas_calls": calls, "config": config,
           "workload": {"seq": 1024}, "device_kind": "TPU v5 lite",
           "family": mf.load_reference("gpt2"), "log": lines.append}
    assert mf.load_metric("kernels.flash_roofline_pct").read(run) \
        == pytest.approx(50.0)
    assert len(lines) == 2 and "flops-bound" in lines[0]
    share = mf.load_metric("kernels.flash_share_pct").read(run)
    flash_ns = 3 * 2 * (t_fwd + t_bwd)
    assert share == pytest.approx(100 * flash_ns / t)
    # no trace, or a program without the kernels: nothing to read
    assert mf.load_metric("kernels.flash_roofline_pct").read(
        dict(run, trace=None)) is None
    assert mf.load_metric("kernels.flash_roofline_pct").read(
        dict(run, pallas_calls={})) is None


def two_stage_trace():
    """Two device planes, three executions of the step each; stage 1 starts
    20 us late and waits in a permute while stage 0 computes."""
    def stage(shift, compute, permute):
        ops, modules = [], []
        for i in range(3):
            t = shift + 100 * i
            modules.append(ev("jit_train_step(123)", t, 95))
            ops.append(ev(f"%fusion.{i}", t, compute,
                          hlo_category="fusion"))
            ops.append(ev("%collective-permute-done.1", t + compute, permute,
                          hlo_category="collective-permute"))
        modules.append(ev("jit_other(9)", shift + 300, 5))
        return plane(ops, modules)
    host = {"main": [ev("dispatch", 80, 5), ev("wait_loss", 85, 30),
                     ev("noise", 0, 1000)]}
    return {"/device:TPU:1": stage(20, 50, 40), "/device:TPU:0": stage(0, 80, 10),
            "/host:CPU": host}


def test_reduce_takes_each_plane_over_its_own_whole_steps():
    r = tr.reduce(two_stage_trace(), "train_step", n_steps=2,
                  span_names=("dispatch", "wait_loss"))
    p0, p1 = r["planes"]
    assert [p["name"] for p in r["planes"]] == ["/device:TPU:0", "/device:TPU:1"]
    assert r["steps"] == 2
    # start of execution 0 to start of execution 2: two whole periods
    assert (p0["lo_ns"], p0["hi_ns"]) == (0, 200 * US)
    assert (p1["lo_ns"], p1["hi_ns"]) == (20 * US, 220 * US)
    assert p0["compute_s"] == pytest.approx(160e-6)
    assert p1["compute_s"] == pytest.approx(100e-6)
    assert p1["permute_s"] == pytest.approx(80e-6)
    assert r["busy_s"] == pytest.approx(180e-6)       # both planes: 90 of 100
    assert r["window_s"] == pytest.approx(200e-6)
    # device 0 idles 90-100 and 190-200; the first is under wait_loss
    assert r["idle_gaps"][0][1] == pytest.approx(10e-6)
    assert {g[0] for g in r["idle_gaps"]} == {"wait_loss", "none"}
    assert r["device_ops"][0][0] == "fusion"
    seconds, calls = tr.label_seconds(r, "collective-permute")
    assert calls == 4 and seconds == pytest.approx(100e-6)


def test_reduce_refuses_a_trace_without_a_device_plane():
    with pytest.raises(ValueError, match="no device plane"):
        tr.reduce({"/host:CPU": {}}, "train_step", 3)


def test_load_reads_a_real_profile(tmp_path):
    """``ProfileData`` needs nothing but JAX; on the CPU there is no device
    plane, so only the host plane with the runner's spans comes back."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("dispatch"):
        jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    trace = tr.load(tr.newest_xplane(str(tmp_path)))
    assert tr.device_planes(trace) == []
    assert [e.name for e in tr.host_spans(trace, ["dispatch"])] == ["dispatch"]
