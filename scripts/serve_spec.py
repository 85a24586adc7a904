"""Speculative-decoding observatory driver: paired spec-off/on bench.

The tier-1 leg for speculative serving (scripts/tier1.sh runs it after
the paged load observatory; CI uploads the comparison as an artifact):
run :func:`serving.bench.run_spec_bench` on an 8-device simulated CPU
mesh — the SAME trace through a plain engine and a draft-verify engine
sharing weights and geometry — and require

- bit-identical completions across the pair (greedy acceptance makes
  speculative decoding exact by construction; any divergence is an
  engine bug, not a perf trade),
- both tick blocks compiled exactly once (asserted inside the bench),
- a tick-domain capacity win: ``ticks_spec_off / ticks_spec_on > 1``.
  Self-draft (the default here — the target model drafts for itself)
  pins acceptance near 1, so the win is deterministic on the CPU proxy
  where wall-clock FLOPs are meaningless but ticks are exact,
- a measured acceptance rate > 0 riding the summary/curve gauges,
- a ``RunReport`` manifest that passes ``validate_report``, with the
  speculative gauges recorded for ``scripts/regress.py``
  (``acceptance_rate``, ``spec_on_tokens_per_sec``, ``spec_tick_gain``
  — all warn-only on the cpu backend) and the spec-on offered-load
  sweep attached so the knee guard tracks ``max_sustainable_load``.

Writes ``report.json``, ``spec_compare.json`` (the paired row) and
``requests_trace.json`` (Perfetto: request sub-spans plus the
acceptance-rate counter track) into the output directory (argv[1],
default ``/tmp/serve_spec``). Exits 0 on success, 1 with a reason on
any violation. Four small compiles (bench pair + the ramp reuses them);
target a couple of minutes on a CI host.

Usage::

    python scripts/serve_spec.py [OUT_DIR] [--gamma 2]
        [--n-requests 16] [--seed 0] [--loads 0.5,1.0,1.5] [--paged]
"""

import argparse
import os
import sys

# must precede the first jax import: 8 simulated devices, CPU backend
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("out_dir", nargs="?", default="/tmp/serve_spec")
    ap.add_argument("--gamma", type=int, default=2)
    ap.add_argument("--n-requests", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--loads", default="0.5,1.0,1.5",
                    help="offered-load ramp for the knee comparison "
                         "(comma-separated, strictly increasing; "
                         "'none' skips the sweep)")
    ap.add_argument("--paged", action="store_true",
                    help="run the pair through the paged-KV engine "
                         "(page pool + committed-frontier rollback)")
    args = ap.parse_args(argv)
    from distributed_training_with_pipeline_parallelism_tpu.utils.compile_cache import (
        enable_compile_cache)
    enable_compile_cache()
    out_dir = args.out_dir
    loads = (None if args.loads == "none"
             else [float(x) for x in args.loads.split(",")])

    import json

    from distributed_training_with_pipeline_parallelism_tpu.serving import (
        run_spec_bench)
    from distributed_training_with_pipeline_parallelism_tpu.utils.telemetry import (  # noqa: E501
        RunReport, validate_report, write_perfetto_trace)

    name = "serve_spec_paged" if args.paged else "serve_spec"
    report = RunReport(out_dir=out_dir, name=name)
    row = run_spec_bench(n_slots=3, prefill_chunk=3, gamma=args.gamma,
                         max_len=32, prompt_max=10, out_max=12,
                         n_requests=args.n_requests, load=1.5,
                         seed=args.seed, paged=args.paged,
                         loads=loads, reps=1, report=report)
    report.set_meta(backend=jax.devices()[0].platform,
                    n_slots=3, prefill_chunk=3, gamma=args.gamma,
                    paged=args.paged, self_draft=row["self_draft"],
                    n_requests=args.n_requests, seed=args.seed)

    if not row["outputs_match"]:
        print("serve_spec: speculative completions diverged from the "
              "plain engine — greedy acceptance must be exact",
              file=sys.stderr)
        return 1
    tick_gain = row["tick_gain"]
    if tick_gain is None or tick_gain <= 1.0:
        print(f"serve_spec: no tick-domain win (tick_gain={tick_gain}; "
              f"ticks {row['ticks_spec_off']} -> {row['ticks_spec_on']})",
              file=sys.stderr)
        return 1
    alpha = row["acceptance_rate"]
    if not alpha or alpha <= 0:
        print(f"serve_spec: acceptance rate {alpha} — the verify path "
              f"never accepted a draft", file=sys.stderr)
        return 1

    report.gauge("acceptance_rate", round(float(alpha), 6))
    report.gauge("accepted_len_mean",
                 round(float(row["accepted_len_mean"]), 6))
    report.gauge("spec_tick_gain", round(float(tick_gain), 6))
    report.gauge("spec_on_tokens_per_sec",
                 round(float(row["spec_on_tokens_per_sec"]), 3))
    report.gauge("spec_off_tokens_per_sec",
                 round(float(row["spec_off_tokens_per_sec"]), 3))
    knee_note = ""
    if loads is not None:
        k_off = row["max_sustainable_load_spec_off"]
        k_on = row["max_sustainable_load_spec_on"]
        if k_on is not None:
            report.gauge("spec_on_max_sustainable_load", float(k_on))
        if k_off is not None:
            report.gauge("spec_off_max_sustainable_load", float(k_off))
        knee_note = f", knee {k_off} -> {k_on}"

    manifest = report.write()
    validate_report(manifest)  # write() validates too; belt and suspenders
    if loads is not None and "serving_load" not in manifest:
        print("serve_spec: manifest lost the serving_load section",
              file=sys.stderr)
        return 1

    compare_path = os.path.join(out_dir, "spec_compare.json")
    with open(compare_path, "w") as fh:
        json.dump(row, fh, indent=1)

    # Perfetto: request spans + the acceptance-rate counter track (from
    # the last — over-capacity — ramp point's summary, where verify
    # traffic is densest; single-point runs fall back to no track)
    tracks = {}
    if loads is not None:
        last = row["serving_load"]["spec_on"]["curve"][-1]["summary"]
        tracks = {"occupancy": last.get("occupancy"),
                  "queue_depth": last.get("queue_depth"),
                  "s_per_tick": last.get("s_per_tick"),
                  "acceptance": last.get("acceptance_series")}
    trace_path = write_perfetto_trace(
        os.path.join(out_dir, "requests_trace.json"),
        serving_events=report.events, serving_load_tracks=tracks)

    print(f"serve_spec: OK — gamma={args.gamma}, "
          f"alpha={alpha:.3f}, accepted_len_mean="
          f"{row['accepted_len_mean']:.2f}, ticks "
          f"{row['ticks_spec_off']} -> {row['ticks_spec_on']} "
          f"(gain {tick_gain:.3f}x), bit-identical completions"
          f"{knee_note}; row at {compare_path}; report at "
          f"{os.path.join(out_dir, 'report.json')}; trace at {trace_path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
