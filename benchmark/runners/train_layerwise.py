"""A training cell of a patterned stack: ``runners/train_scoped.py``, and
before it every layer's mixer held to the plain reference, one by one.

Why: ``runners/train.py`` decides ``correct`` on ONE scalar, the first
step's mean loss at random init. Over 16 384 tokens that mean averages a
layer's faults away: at this family's cell a router computed in bf16, a
recurrence state kept in bf16 and a held expert left out all move it by
less than the program's own bf16 noise (``PERF.md``, PR 30). So this runner
WRAPS ``train_scoped`` — weights, loss check, compile, warm-up, windows and
regions are that runner's, to the letter — and first, as part of set-up,
walks the stack on the cell's check batch with the cell's weights:

  the program's compute copies of the weights (``compute_cast``) -> embed ->
  for every layer: ``x`` = the program's normed input, so both sides read the
  same bf16 numbers -> the program's mixer (``models/nemotron_h.py:mixer``,
  the configuration's numerics) against the reference's
  (``family.mixer``, float32) -> ``h + the program's output`` goes on.

Three readings, each the largest over its layers, each with a limit in the
family's ``LAYER_TOL`` (set between the program's readings and a control's):

- ``out``: ``|program - reference| / |reference|`` over a layer's whole
  output (every layer);
- ``tokens_off``: the share of tokens whose own output is further than
  ``family.TOKEN_OFF`` from the reference's (expert layers): a token that
  took another expert, or missed one, is off by tens of percent and the
  rest by the arithmetic's few tenths of a percent;
- ``scan``: the recurrence alone — the program's ``ops/mamba2.py:
  ssd_chunked`` against ``family.recurrence`` on the same ``x``, ``B``,
  ``C`` (rounded to the compute dtype) and ``dt``, ``A`` — as the worst
  head's relative error (Mamba-2 layers): the long-memory heads show a
  state kept in less.

``correct`` is ``train_scoped``'s AND every reading under its limit.
``benchmark/controls.py`` runs the same comparison with the reference
computing in less than the configuration states; each control has to come
out as not correct.
"""

from __future__ import annotations

import time


def make_compare(cfg, family, sizes: dict, faults: dict):
    """-> jitted ``compare(letter, skip, p_cast, p, h)``: one layer of kind
    ``letter`` (``skip``: a control left it out of the reference) with the
    program's compute copies ``p_cast`` and the masters ``p`` on the
    residual stream ``h`` -> (``h`` after the program's layer, readings)."""
    import jax
    import jax.numpy as jnp

    from distributed_training_with_pipeline_parallelism_tpu.models import (
        nemotron_h as program)
    from distributed_training_with_pipeline_parallelism_tpu.ops import mamba2
    from distributed_training_with_pipeline_parallelism_tpu.ops.layers import (
        rms_norm_apply)

    f32 = jnp.float32

    def rel(err, want, axes):
        return jnp.sqrt(jnp.square(err).sum(axes) / jnp.square(want).sum(axes))

    def compare(letter, skip, p_cast, p, h):
        x = rms_norm_apply(p_cast["norm"], h, cfg.rms_eps)
        got, _ = program.mixer(cfg, family.STACK[letter], p_cast, x)
        want = family.mixer(letter, p, x.astype(f32), sizes, **faults)
        want = jnp.zeros_like(want) if skip else want
        err = got.astype(f32) - want
        read = {"out": rel(err, want, None)}
        if letter == "E":
            read["tokens_off"] = (rel(err, want, -1) > family.TOKEN_OFF).mean()
        if letter == "M":
            with jax.default_matmul_precision("highest"):
                _, xs, B, C, dt, A = family.mamba_inputs(
                    jax.tree.map(lambda w: w.astype(f32), p), x.astype(f32),
                    sizes)
            xs, B, C = (m.astype(x.dtype) for m in (xs, B, C))
            y = mamba2.ssd_chunked(xs, dt, A, B, C, cfg.chunk_size)
            y_ref = family.recurrence(
                *(m.astype(f32) for m in (xs, B, C)), dt, A,
                faults.get("scan_dtype", f32))
            read["scan"] = rel(y.astype(f32) - y_ref, y_ref, (0, 1, 3)).max()
        return h + got, read

    return jax.jit(compare, static_argnums=(0, 1))


def layerwise(cfg, family, sizes: dict, params, tokens, faults: dict,
              log) -> dict:
    """The three readings on ``tokens`` [rows, seq] with ``params`` (the
    masters); ``faults`` are the reference's (``family.mixer``)."""
    import jax

    from distributed_training_with_pipeline_parallelism_tpu.models import (
        transformer as tfm)

    compare = make_compare(cfg, family, sizes, faults)
    skipped = faults.get("skip_layers", ())
    cast = jax.jit(lambda p: tfm.compute_cast(cfg, p))(params)
    h = jax.jit(lambda p, t: tfm.embed_apply(cfg, p, t))(cast["embed"], tokens)
    worst = {}
    for n, ((letter, p), (_, p_cast)) in enumerate(zip(
            family.layers_of(params, sizes), family.layers_of(cast, sizes))):
        h, read = compare(letter, n in skipped, p_cast, p, h)
        read = {k: float(v) for k, v in read.items()}
        log(f"layer {n} {letter}: " + ", ".join(
            f"{k} {v:.3e}" for k, v in read.items()))
        for k, v in read.items():  # a NaN is the largest, and stays
            worst[k] = max(worst.get(k, v), v, key=lambda r: (r != r, r))
    return worst


def verdict(readings: dict, limits: dict) -> list:
    """The readings not under their limits (a NaN is not under anything)."""
    return [k for k, v in readings.items() if not v < limits[k]]


def check(ctx, faults: dict | None = None) -> dict:
    """The cell's weights and check batch as ``runners/train.py`` makes
    them, through :func:`layerwise` -> readings, limits, what failed."""
    import jax

    from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import (
        make_mesh)
    from distributed_training_with_pipeline_parallelism_tpu.utils import train

    from benchmark.harness import manifest
    w, sizes = ctx.workload, ctx.config["sizes"]
    family = manifest.load_reference(ctx.config["reference"])
    cfg = family.model_config(sizes, ctx.config["numerics"])
    mesh = make_mesh(n_pipe=w["mesh"]["pipe"], devices=ctx.devices[:w["chips"]])
    t = time.perf_counter()
    params = train.init_params(cfg, mesh, jax.random.key(ctx.seed))
    (tokens, _), _ = manifest.load_runner("train").check_batch(
        cfg.vocab_size, w["check_sequences"], w["batch"], w["seq"], ctx.seed)
    readings = layerwise(cfg, family, sizes, params, tokens, faults or {},
                         ctx.log)
    failed = verdict(readings, family.LAYER_TOL)
    ctx.log("layer by layer: " + ", ".join(
        f"{k} {v:.3e} (limit {family.LAYER_TOL[k]})"
        for k, v in readings.items())
        + (f": {failed} NOT under the limit" if failed else ": all under")
        + f" ({time.perf_counter() - t:.1f}s)")
    return {"readings": readings, "limits": dict(family.LAYER_TOL),
            "failed_by": failed}


def run(ctx) -> dict:
    from benchmark.harness import manifest
    layers = check(ctx)
    out = manifest.load_runner("train_scoped").run(ctx)
    out["correct"] = bool(out["correct"] and not layers["failed_by"])
    out["run"]["layerwise"] = layers
    return out
