"""The controls of a cell's ``correct``: the reference computing in less than
the configuration states, through the comparison the cell's runner makes.

    python3 benchmark/controls.py --workload <cell> --seed <n> [--only a,b] [--loss 1]

For a cell whose runner has ``check(ctx, faults)`` (``runners/
train_layerwise.py``) and whose reference lists ``CONTROLS``: first the
program against the reference as it is — which has to come out as correct —
then against the reference with each control's faults, which has to come out
as NOT correct. One JSON line each on standard output: the readings, their
limits, and which limits failed. With ``--loss 1`` also the scalar that
``runners/train.py`` compares: the program's loss on the check batch against
the (faulted) reference's, beside ``LOSS_TOL`` — it shows what one mean over
the batch can and cannot see. The exit code is 0 only if every line came out
as it has to. Like ``run.py`` it runs on the TPU only.
"""

import argparse
import json
import os
import sys
import time
import types

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T0:7.2f}s] {msg}", file=sys.stderr,
          flush=True)


def loss_apart(ctx, faults: dict) -> float:
    """``|program - reference| / reference`` on the check batch: the
    program's forward loss (what the step's first loss is) against the
    reference's with ``faults``."""
    import jax

    from distributed_training_with_pipeline_parallelism_tpu.models.transformer import (
        transformer_loss)
    from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import (
        make_mesh)
    from distributed_training_with_pipeline_parallelism_tpu.utils import train

    from benchmark.harness import manifest as mf
    w, sizes = ctx.workload, ctx.config["sizes"]
    family = mf.load_reference(ctx.config["reference"])
    cfg = family.model_config(sizes, ctx.config["numerics"])
    mesh = make_mesh(n_pipe=w["mesh"]["pipe"], devices=ctx.devices[:w["chips"]])
    params = train.init_params(cfg, mesh, jax.random.key(ctx.seed))
    few, _ = mf.load_runner("train").check_batch(
        cfg.vocab_size, w["check_sequences"], w["batch"], w["seq"], ctx.seed)
    got = float(jax.jit(lambda p, x, y: transformer_loss(cfg, p, x, y))(
        params, *few))
    want = float(jax.jit(lambda p, x, y: family.loss(p, x, y, sizes, **faults))(
        params, *few))
    return abs(got - want) / abs(want)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--only", default="", help="comma-separated controls")
    ap.add_argument("--loss", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark.harness import manifest as mf
    manifest = mf.load_manifest()
    workload = mf.load_workload(args.workload)
    config = mf.load_config(manifest, workload["config"])
    family = mf.load_reference(config["reference"])
    runner = mf.load_runner(workload["runner"])

    from distributed_training_with_pipeline_parallelism_tpu.utils.compile_cache import (
        enable_compile_cache)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < workload["chips"]:
        print(f"controls: {args.workload} needs {workload['chips']} TPU "
              f"chip(s); JAX sees {len(devices)} x {devices[0].platform}",
              file=sys.stderr)
        return 1
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    ctx = types.SimpleNamespace(cell=args.workload, seed=args.seed, log=log,
                                workload=workload, config=config,
                                devices=devices)

    wanted = [c for c in args.only.split(",") if c] or list(family.CONTROLS)
    as_it_has_to = True
    for name in ["program"] + wanted:
        faults = family.CONTROLS[name] if name != "program" else {}
        log(f"== {name}: {faults}")
        result = runner.check(ctx, faults)
        if args.loss:
            result["readings"]["loss"] = loss_apart(ctx, faults)
            result["limits"]["loss"] = family.LOSS_TOL
            if not result["readings"]["loss"] < family.LOSS_TOL:
                result["failed_by"].append("loss")
        correct = not result["failed_by"]
        as_it_has_to &= correct == (name == "program")
        print(json.dumps({"control": name, "seed": args.seed,
                          "correct": correct, **result}), flush=True)
    return 0 if as_it_has_to else 1


if __name__ == "__main__":
    sys.exit(main())
