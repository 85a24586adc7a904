"""The training cells: AdamW steps on seeded random tokens for ``--seconds``.

The runner calls what ``train.fit`` calls — ``init_params``,
``init_opt_state``, ``make_train_step``, ``synthetic_data``,
``prefetch_to_device``, ``batch_sharding`` — and owns the loop, because
``fit`` counts steps, not seconds, holds its compile in its first window and
syncs at every log point (``PERF.md``, inventory).

One run:

  mesh -> weights from the seed, born in their resting layout -> the plain
  reference's loss on the check batch -> moments -> compile the step (timed;
  the compiler's memory count) -> warm-up steps, the first on the check batch
  and its loss held to the reference -> WINDOW -> result.

The window keeps ONE step in flight: dispatch step i+1, then wait for step
i's loss and stamp the clock. So the device always has its next program
queued, the stamps follow the device's cadence, and every loss is looked at.
With ``--trace 1`` the window is split: an untraced half, from which the
host, compiler and arithmetic metrics are read, then the profiler on for
``trace_steps`` + 1 steps.
"""

from __future__ import annotations

import contextlib
import gzip
import math
import os
import shutil
import statistics
import tempfile
import time

SPAN_NAMES = ("input_wait", "dispatch", "wait_loss")
PROGRAM = "train_step"  # the jitted step's name inside the XLA module's


class Spans:
    """Host spans around the runner's calls into the program: seconds by
    name, and the same spans as ``TraceAnnotation``s so that a profiler
    trace has them on its own clock."""

    def __init__(self):
        self.seconds = {name: [] for name in SPAN_NAMES}

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        with jax.profiler.TraceAnnotation(name):
            t = time.perf_counter()
            yield
            self.seconds[name].append(time.perf_counter() - t)


def drive(step, state, data, until, spans):
    """Steps with one in flight until ``until(dispatched, elapsed_s)``.
    Returns (state, window start, completion stamps, losses, error)."""
    params, opt_state = state
    stamps, losses, pending, error = [], [], None, None
    start = time.perf_counter()
    try:
        while True:
            with spans("input_wait"):
                tokens, targets = next(data)
            with spans("dispatch"):
                params, opt_state, loss = step(params, opt_state, tokens,
                                               targets)
            losses.append(loss)
            if pending is not None:
                with spans("wait_loss"):
                    pending.block_until_ready()
                stamps.append(time.perf_counter())
            pending = loss
            if until(len(losses), time.perf_counter() - start):
                break
        with spans("wait_loss"):
            pending.block_until_ready()
        stamps.append(time.perf_counter())
    except Exception as e:  # a failed step ends the window; the run reports it
        import traceback
        traceback.print_exc()
        error = e
    return (params, opt_state), start, stamps, losses, error


def check_batch(vocab: int, n_sequences: int, batch: int, seq: int, seed: int):
    """``n_sequences`` seeded sequences, and the same tiled to the batch:
    the step's mean loss over the tiled batch is the mean over the few."""
    import numpy as np
    if batch % n_sequences:
        raise ValueError(f"batch {batch} is no multiple of the "
                         f"{n_sequences} check sequences")
    toks = np.random.default_rng([seed, 0xC4EC]).integers(
        0, vocab, (n_sequences, seq + 1), dtype=np.int32)
    few = (toks[:, :-1], toks[:, 1:])
    return few, tuple(np.tile(x, (batch // n_sequences, 1)) for x in few)


def table_idle(name: str, n_devices: int, n_microbatches: int):
    """(idle cells, all cells) of the schedule's tick table, exactly."""
    from distributed_training_with_pipeline_parallelism_tpu.analysis.table_check import (
        check_table)
    from distributed_training_with_pipeline_parallelism_tpu.parallel.schedules import (
        compile_schedule)
    cs = compile_schedule(name, n_devices, 1, n_microbatches)
    return check_table(cs).unit_counts["idle"], cs.table.shape[0] * n_devices


def run(ctx) -> dict:
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    import distributed_training_with_pipeline_parallelism_tpu as dtpp
    from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import (
        make_mesh)
    from distributed_training_with_pipeline_parallelism_tpu.utils import train
    from distributed_training_with_pipeline_parallelism_tpu.utils.data import (
        batch_sharding, prefetch_to_device)

    from benchmark.harness import kernels, manifest, trace_reduce

    w, log, cache = ctx.workload, ctx.log, ctx.cache
    sizes = ctx.config["sizes"]
    family = manifest.load_reference(ctx.config["reference"])
    cfg = family.model_config(sizes, ctx.config["numerics"])
    batch, seq, n_pipe = w["batch"], w["seq"], w["mesh"]["pipe"]
    log(f"attention at seq {seq}: "
        + ("the Pallas flash kernel" if cfg.flash_for(True, seq) else "dense"))
    mesh = make_mesh(n_pipe=n_pipe, devices=ctx.devices[:w["chips"]])
    sched = dtpp.ScheduleConfig(name=w["schedule"]["name"],
                                n_microbatches=w["schedule"]["microbatches"])
    if n_pipe > 1:
        idle, cells = table_idle(sched.name, n_pipe, sched.n_microbatches)
        log(f"table: {sched.name} D={n_pipe} M={sched.n_microbatches}: {idle} "
            f"of {cells} cells idle = {100 * idle / cells:.2f}%")

    # weights from the seed, one jitted call, born where they rest
    params = train.init_params(cfg, mesh, jax.random.key(ctx.seed))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    few, tiled = check_batch(cfg.vocab_size, w["check_sequences"], batch, seq,
                             ctx.seed)

    # (a) the plain reference, on the same weights copied whole to device 0,
    # before the moments are born
    t = time.perf_counter()
    dev0 = SingleDeviceSharding(ctx.devices[0])
    whole = jax.device_put(params, dev0)
    ref_loss = float(jax.jit(lambda p, x, y: family.loss(p, x, y, sizes))(
        whole, *jax.device_put(few, dev0)))
    del whole
    log(f"reference: float32 loss {ref_loss:.6f} on {w['check_sequences']} "
        f"sequences of {seq}, {n_params / 1e6:.1f}M parameters "
        f"({time.perf_counter() - t:.1f}s)")

    optimizer = train.adamw(total_steps=w["optimizer"]["total_steps"])
    opt_state = train.init_opt_state(optimizer, params, mesh)
    placed = jax.device_put(tiled, batch_sharding(mesh))
    step_fn = train.make_train_step(cfg, mesh, sched, optimizer)
    t = time.perf_counter()
    step = step_fn.lower(params, opt_state, *placed).compile()
    compile_s = time.perf_counter() - t
    ma = step.memory_analysis()
    memory = {k: getattr(ma, k + "_size_in_bytes")
              for k in ("argument", "output", "alias", "temp")}
    log(f"step program: got in {compile_s:.1f}s; the compiler counts "
        + ", ".join(f"{k} {v / 1e9:.3f}" for k, v in memory.items()) + " GB")

    # warm-up: every shape the window uses; the first step on the check batch
    data = prefetch_to_device(
        train.synthetic_data(cfg, batch, seq, seed=ctx.seed),
        depth=w["prefetch_depth"], sharding=batch_sharding(mesh))
    params, opt_state, loss = step(params, opt_state, *placed)
    check_loss = float(loss)
    for _ in range(w["warmup_steps"] - 1):
        params, opt_state, loss = step(params, opt_state, *next(data))
    jax.block_until_ready(loss)
    rel = abs(check_loss - ref_loss) / abs(ref_loss)
    log(f"check: the program's loss {check_loss:.6f} vs the reference's "
        f"{ref_loss:.6f}: {rel:.2e} apart (tolerance {family.LOSS_TOL})")

    # ---- the window
    spans = Spans()
    seconds = ctx.seconds / 2 if ctx.trace else ctx.seconds
    programs_before = cache.programs_obtained
    setup_s = time.perf_counter() - ctx.t0
    state, start, stamps, losses, error = drive(
        step, (params, opt_state), data, lambda n, s: s >= seconds, spans)
    host_spans = {k: list(v) for k, v in spans.seconds.items()}

    reduced, pallas = None, {}
    if ctx.trace and error is None:
        k = w["trace_steps"]
        pallas = kernels.pallas_calls(step.as_text())
        log("Pallas calls of the step program: " + ", ".join(
            f"{name} = {c['kernel']}" for name, c in pallas.items()))
        with tempfile.TemporaryDirectory() as tmp:
            jax.profiler.start_trace(tmp)
            state, _, _, more, error = drive(
                step, state, data, lambda n, s: n >= k + 1, spans)
            jax.profiler.stop_trace()
            losses += more
            path = trace_reduce.newest_xplane(tmp)
            if ctx.keep_trace:
                os.makedirs(ctx.keep_trace, exist_ok=True)
                with open(path, "rb") as src, gzip.open(os.path.join(
                        ctx.keep_trace, ctx.cell + ".xplane.pb.gz"),
                        "wb") as dst:
                    shutil.copyfileobj(src, dst)
            reduced = trace_reduce.reduce(
                trace_reduce.load(path), PROGRAM, k, SPAN_NAMES,
                {name: c["kernel"] for name, c in pallas.items()})
    programs_in_window = cache.programs_obtained - programs_before

    values = np.asarray(jax.device_get(losses), np.float64)
    bad = int((~np.isfinite(values)).sum()) + (error is not None)
    n_done = len(stamps)
    window_s = stamps[-1] - start if stamps else float("nan")
    tokens_per_s = batch * seq * n_done / window_s if stamps else float("nan")
    intervals = np.diff(stamps)
    log(f"window: {n_done} steps in {window_s:.3f}s; losses "
        f"{values[:1]} .. {values[-1:]}; {programs_in_window} programs "
        f"obtained inside it; cache {cache.hits} hits, {cache.misses} "
        f"misses in the whole run")

    correct = (rel < family.LOSS_TOL                       # (a)
               and bad == 0 and len(values) > 0            # (b)
               and abs(check_loss - math.log(cfg.vocab_size)) < 0.5
               and programs_in_window == 0)                # (c)
    end_to_end = {"train.tokens_per_s": tokens_per_s, "setup_s": setup_s}
    if len(intervals) >= 20:
        end_to_end["train.step_p95_ms"] = float(
            np.percentile(intervals, 95)) * 1e3
        log(f"step intervals: median "
            f"{statistics.median(intervals) * 1e3:.3f} ms, p95 "
            f"{end_to_end['train.step_p95_ms']:.3f} ms, max "
            f"{intervals.max() * 1e3:.3f} ms over {len(intervals)}")

    out = {
        "correct": bool(correct), "attempted": len(losses), "failed": bad,
        "end_to_end": end_to_end,
        "run": {
            "config": ctx.config, "workload": w, "family": family,
            "device_kind": ctx.devices[0].device_kind, "chips": w["chips"],
            "spans": host_spans, "tokens_per_s": tokens_per_s,
            "compile_s": compile_s, "compiled_memory": memory,
            "cache_misses": cache.misses, "cache_hits": cache.hits,
            "trace": reduced, "pallas_calls": pallas, "log": log,
        },
    }
    if reduced is not None:
        out["device"] = {"busy_s": reduced["busy_s"],
                         "window_s": reduced["window_s"]}
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
        for p in reduced["planes"]:
            log(f"{p['name']}: over {p['steps']} steps, {p['window_s']:.4f}s: "
                f"busy {p['busy_s']:.4f}s, computing {p['compute_s']:.4f}s "
                f"(idle of compute {100 * (1 - p['compute_s'] / p['window_s']):.2f}%), "
                f"in permutes {p['permute_s']:.4f}s, in collectives "
                f"{p['collective_s']:.4f}s")
    return out
