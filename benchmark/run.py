"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's files by name (``BENCHMARK.json``, ``benchmark/workloads/``,
``benchmark/configs/``, ``benchmark/runners/``, ``benchmark/metrics/``), runs
it on the TPU this process sees, and prints ONE line on standard output: the
result as a JSON object. Everything else goes to standard error. On anything
but a TPU, with fewer chips than the cell asks for, or when any part fails,
the exit code is not 0 and standard output stays empty.
"""

import time

T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T0:7.2f}s] {msg}", file=sys.stderr,
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="", metavar="DIR",
                    help="with --trace 1, also leave the profiler's "
                         ".xplane.pb (gzipped) in DIR, to be read by hand")
    args = ap.parse_args(argv)

    from benchmark.harness import manifest as mf
    manifest = mf.load_manifest()
    workload = mf.load_workload(args.workload)
    try:
        entry = mf.cell_entry(manifest, args.workload)
    except KeyError:
        # a cell whose files are here but which BENCHMARK.json does not list
        # (kept for a later PR) still runs, and reports what a cell with its
        # chips would
        entry = {k: workload[k] for k in ("name", "config", "traffic", "chips")}
        log(f"{args.workload} is not listed in BENCHMARK.json: running it "
            "from its files")
    for key in ("config", "traffic", "chips"):
        if workload[key] != entry[key]:
            raise SystemExit(f"{args.workload}: its file says {key} "
                             f"{workload[key]!r}, BENCHMARK.json {entry[key]!r}")
    config = mf.load_config(manifest, entry["config"])

    # the program first: where it is absent there is nothing to measure
    from distributed_training_with_pipeline_parallelism_tpu.utils.compile_cache import (
        enable_compile_cache)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < entry["chips"]:
        print(f"benchmark: {args.workload} needs {entry['chips']} TPU "
              f"chip(s); JAX sees {len(devices)} x {devices[0].platform} - "
              "no result", file=sys.stderr)
        return 1

    from benchmark.harness.cache_counter import CacheCounter
    cache_dir = enable_compile_cache()
    # every program, however quick to compile: a warm run then compiles none
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    log(f"{args.workload} seed {args.seed} on {len(devices)} x "
        f"{devices[0].device_kind}; compile cache {cache_dir}")

    ctx = types.SimpleNamespace(
        cell=args.workload, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), keep_trace=args.keep_trace, t0=T0, log=log,
        manifest=manifest, workload=workload, config=config,
        devices=devices, cache=CacheCounter())
    out = mf.load_runner(workload["runner"]).run(ctx)

    metrics = {}
    if args.trace:
        for m in mf.metrics_of(manifest, "per_layer", args.workload):
            value = mf.load_metric(m["name"]).read(out["run"])
            if value is not None:  # a reader that found nothing to read
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in mf.metrics_of(manifest, "end_to_end", args.workload):
            metrics[m["name"]] = {"value": out["end_to_end"][m["name"]],
                                  "unit": m["unit"]}

    used = devices[:entry["chips"]]
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used),
              "memory_peak_bytes": max(
                  d.memory_stats()["peak_bytes_in_use"] for d in used)}
    device.update(out.get("device", {}))
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    if "breakdown" in out:
        line["breakdown"] = out["breakdown"]
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
