"""Run the reference's full 54-config sweep and write tables + plots.

Usage:
    python scripts/run_sweep.py --simulate-devices 8        # CPU-simulated mesh
    python scripts/run_sweep.py                             # real chips
    python scripts/run_sweep.py --quick                     # 6-config smoke run

Produces results/sweep.csv, results/speedup.csv, results/speedup.png,
results/throughput_grid.png — the same tables/plots as notebook cells 25-30.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--simulate-devices", type=int, default=0,
                    help="simulate N CPU devices (the JAX analog of the "
                         "reference's gloo-on-localhost trick)")
    ap.add_argument("--quick", action="store_true",
                    help="small model / fewer configs for a smoke run")
    ap.add_argument("--out", default="results")
    ap.add_argument("--iterations", type=int, default=5)
    ap.add_argument("--dim", type=int, default=None,
                    help="model width (default: reference's 768, or 64 under "
                         "--quick; smaller widths keep full sweeps tractable "
                         "on simulated CPU meshes)")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--schedules", nargs="+", default=None,
                    help="override the schedule list, e.g. "
                         "--schedules GPipe 1F1B ZBH1 BFS (default: the "
                         "reference's three; ZBH1/BFS are beyond-parity)")
    args = ap.parse_args()

    if args.simulate_devices:
        from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import (
            simulate_cpu_devices)
        simulate_cpu_devices(args.simulate_devices)
    from distributed_training_with_pipeline_parallelism_tpu.utils.compile_cache import (
        enable_compile_cache)
    enable_compile_cache()

    from distributed_training_with_pipeline_parallelism_tpu.utils.plotting import (
        plot_speedup_and_efficiency, plot_throughput_grid)
    from distributed_training_with_pipeline_parallelism_tpu.utils.sweep import (
        compute_speedup_and_efficiency, pivot_throughput, run_all_experiments)

    dim = args.dim or (64 if args.quick else 768)
    kwargs = dict(dim=dim, dtype=args.dtype)
    if args.schedules:
        if "GPipe" not in args.schedules:
            print("note: GPipe not in --schedules; speedup/efficiency "
                  "tables need it as the baseline and will be empty",
                  flush=True)
        kwargs["schedules"] = tuple(args.schedules)
    if args.quick:
        kwargs.update(layers=(4,), heads=(4, 8), devices=(2,),
                      batch_size=8, seq_length=32, vocab_size=256)
    df = run_all_experiments(num_iterations=args.iterations, **kwargs)

    os.makedirs(args.out, exist_ok=True)
    df.to_csv(os.path.join(args.out, "sweep.csv"), index=False)
    sp = compute_speedup_and_efficiency(df)
    sp.to_csv(os.path.join(args.out, "speedup.csv"), index=False)
    print("\n== Throughput pivot (tokens/sec) ==")
    print(pivot_throughput(df).round(2).to_string())
    print("\n== Speedup / efficiency ==")
    print(sp.round(3).to_string(index=False))
    if not sp.empty:
        plot_speedup_and_efficiency(sp, os.path.join(args.out, "speedup.png"))
    plot_throughput_grid(df, os.path.join(args.out, "throughput_grid.png"))
    print(f"\nwrote {args.out}/sweep.csv, speedup.csv, *.png")


if __name__ == "__main__":
    main()
