"""The training cells, with the device's time split by the regions the
program names (``utils/profiling.py:REGIONS``).

This runner WRAPS ``runners/train.py``: the weights, the check against the
reference, the compile, the warm-up and both windows are that runner's, to
the letter, and so are ``end_to_end``, ``correct``, ``attempted``, ``failed``
and ``device``. What it adds happens after a traced run has ended:

  the kept ``.xplane.pb.gz`` is read back -> the step's compiled text is
  rebuilt from abstract arguments (the same program: a cache hit) -> every
  device plane is split by ``harness/scopes.py:by_region`` ->
  ``run["regions"]``, which the ``step.*_share_pct`` and ``model.*_share_pct``
  readers take, and ``breakdown.device_ops`` relabelled
  ``"<phase>:<region>:<label>"``.

With a program that names no regions (one from before ``classify``) the base
runner's result is passed on as it is and those readers find nothing.
"""

from __future__ import annotations

import contextlib
import gzip
import os
import shutil
import tempfile
import time
import types


def abstract_step_text(cfg, mesh, sched, optimizer, batch: int, seq: int) -> str:
    """The compiled text of ``make_train_step`` lowered from shapes alone:
    parameters and optimizer state as ``ShapeDtypeStruct``s in their resting
    shardings, the batch in ``batch_sharding``. The same text as the program
    compiled from the arrays themselves (tested), without holding any."""
    import jax
    import jax.numpy as jnp

    from distributed_training_with_pipeline_parallelism_tpu.parallel.pipeline import (
        model_init, param_shardings)
    from distributed_training_with_pipeline_parallelism_tpu.utils import train
    from distributed_training_with_pipeline_parallelism_tpu.utils.data import (
        batch_sharding)

    def abstract(shapes, shardings):
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes, shardings)

    params = abstract(jax.eval_shape(model_init(cfg, None), jax.random.key(0)),
                      param_shardings(cfg, mesh))
    opt_state = abstract(jax.eval_shape(optimizer.init, params),
                         train.opt_state_shardings(optimizer, params, mesh))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                  sharding=batch_sharding(mesh))
    step = train.make_train_step(cfg, mesh, sched, optimizer)
    return step.lower(params, opt_state, tokens, tokens).compile().as_text()


def step_text(ctx) -> str:
    """The cell's step program as ``runners/train.py`` builds it."""
    import distributed_training_with_pipeline_parallelism_tpu as dtpp
    from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import (
        make_mesh)
    from distributed_training_with_pipeline_parallelism_tpu.utils import train

    from benchmark.harness import manifest
    w = ctx.workload
    family = manifest.load_reference(ctx.config["reference"])
    cfg = family.model_config(ctx.config["sizes"], ctx.config["numerics"])
    mesh = make_mesh(n_pipe=w["mesh"]["pipe"], devices=ctx.devices[:w["chips"]])
    sched = dtpp.ScheduleConfig(name=w["schedule"]["name"],
                                n_microbatches=w["schedule"]["microbatches"])
    optimizer = train.adamw(total_steps=w["optimizer"]["total_steps"])
    return abstract_step_text(cfg, mesh, sched, optimizer, w["batch"], w["seq"])


def read_regions(xplane_gz: str, hlo_text: str, program: str, n_steps: int,
                 pallas: dict) -> dict:
    """``run["regions"]`` from a kept trace and the step's compiled text."""
    from benchmark.harness import scopes, trace_reduce
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "kept.xplane.pb")
        with gzip.open(xplane_gz, "rb") as src, open(path, "wb") as dst:
            shutil.copyfileobj(src, dst)
        trace = trace_reduce.load(path)
    found = scopes.scope_map(hlo_text)
    kernel_of = {name: c["kernel"] for name, c in pallas.items()}
    planes = []
    for plane in trace_reduce.device_planes(trace):
        lo, hi, _ = trace_reduce.step_window(trace, plane, program, n_steps)
        planes.append(dict(scopes.by_region(trace, plane, lo, hi, found,
                                            kernel_of), name=plane))
    return scopes.summarize(planes)


def run(ctx) -> dict:
    from benchmark.harness import kernels, manifest
    base = manifest.load_runner("train")
    try:
        from distributed_training_with_pipeline_parallelism_tpu.utils.profiling import (  # noqa: F401
            classify)
    except ImportError:
        ctx.log("regions: this program names none (no utils.profiling."
                "classify); this run is the base runner's")
        return base.run(ctx)
    with contextlib.ExitStack() as stack:
        if ctx.trace and not ctx.keep_trace:
            ctx = types.SimpleNamespace(**dict(
                vars(ctx), keep_trace=stack.enter_context(
                    tempfile.TemporaryDirectory())))
        out = base.run(ctx)
        run_ = out["run"]
        if run_["trace"] is None or out["failed"]:
            return out
        t = time.perf_counter()
        text = step_text(ctx)
        rebuilt = kernels.pallas_calls(text)
        if rebuilt != run_["pallas_calls"]:
            raise RuntimeError(
                "the step rebuilt from abstract arguments is not the program "
                f"that ran: Pallas calls {sorted(rebuilt)} against "
                f"{sorted(run_['pallas_calls'])}")
        relowered_s = time.perf_counter() - t
        regions = read_regions(
            os.path.join(ctx.keep_trace, ctx.cell + ".xplane.pb.gz"), text,
            base.PROGRAM, ctx.workload["trace_steps"], rebuilt)
        regions["after_window_s"] = time.perf_counter() - t
    run_["regions"] = regions
    out["breakdown"]["device_ops"] = regions["device_ops"]
    ctx.log(f"regions: text rebuilt in {relowered_s:.1f}s, trace read again "
            f"and split in {regions['after_window_s'] - relowered_s:.1f}s; "
            f"the text covers {100 * regions['coverage']:.2f}% of the busy "
            "time")

    def shares(plane, table):
        return ", ".join(f"{k} {100 * s / plane['busy_s']:.2f}%"
                         for k, s in sorted(plane[table].items(),
                                            key=lambda kv: -kv[1]))

    for p in regions["planes"]:
        ctx.log(f"{p['name']}: {shares(p, 'phases')} of busy; "
                f"{shares(p, 'regions')}")
    return out
