"""Serving SLO observatory driver: offered-load ramp on a CPU mesh.

The tier-1 leg for the serving load observatory (scripts/tier1.sh runs
it after the serve smoke; CI uploads the curve + trace as artifacts):
build ONE small serving engine on an 8-device simulated CPU mesh, sweep
a ramp of offered loads (default 0.4 / 0.8 / 1.2x ring capacity — under,
near, and over saturation) through the SAME compiled tick block, and
require

- the one-compilation invariant sweep-wide: ``program.step`` compiled
  exactly once across the whole ramp (every offered load replays the
  same static-shape block; a recompile would be a shape leak),
- a saturation knee: the over-capacity point must blow the SLO, and the
  knee must sit at or below the top of the ramp,
- monotone tail latency: p99 TTFT non-decreasing in offered load — true
  by construction because every point reuses the same workload seed
  (arrival gaps scale exactly 1/load), so a violation is an engine
  scheduling bug, not sampling noise,
- a ``serving_load`` RunReport section that passes ``validate_report``.

Writes ``report.json`` (manifest with the ``serving_load`` section),
``curve.json`` (the section alone, for plotting/regress consumers) and
``requests_trace.json`` (Perfetto: per-request queue-wait vs execution
sub-spans on the tick clock plus queue-depth / slot-occupancy counter
tracks) into the output directory (argv[1], default
``/tmp/serve_load``). Exits 0 on success, 1 with a reason on any
violation. One compile; target well under two minutes on a CI host.

``--paged`` runs the same observatory through the paged-KV engine
(page-pool + radix prefix cache; pair it with ``--mix prefix`` for the
shared-prefix traffic the cache exists for): the curve rows grow the
page gauges (prefix hit rate, pages used, fragmentation, backpressure),
the report name becomes ``serve_load_paged`` so the regression history
groups paged and contiguous knees separately, and the Perfetto trace
gains page-pool counter tracks. Before building anything the pool
config is priced by ``oom_preflight``; an over-budget pool writes a
``skip_reason="predicted_oom"`` row to ``curve.json`` and exits 0
instead of compiling (``--n-pages`` overrides the default
full-capacity pool; ``--headroom`` tightens the budget).

Usage::

    python scripts/serve_load.py [OUT_DIR] [--loads 0.4,0.8,1.2]
        [--n-requests 24] [--mix mixed] [--seed 0]
        [--paged] [--page-size 4] [--n-pages N] [--headroom 1.0]
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


def _p99(pct):
    v = (pct or {}).get("p99")
    return float(v) if isinstance(v, (int, float)) else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("out_dir", nargs="?", default="/tmp/serve_load")
    ap.add_argument("--loads", default="0.4,0.8,1.2",
                    help="comma-separated offered loads in units of ring "
                         "capacity, strictly increasing; the last one "
                         "should be over capacity so the knee exists")
    ap.add_argument("--n-requests", type=int, default=24)
    ap.add_argument("--mix", default="mixed")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--paged", action="store_true",
                    help="run the paged-KV engine (page pool + radix "
                         "prefix cache) instead of contiguous slots")
    ap.add_argument("--page-size", type=int, default=4)
    ap.add_argument("--n-pages", type=int, default=None,
                    help="pool size (default: full contiguous-parity "
                         "capacity); priced by oom_preflight before "
                         "any compile")
    ap.add_argument("--headroom", type=float, default=1.0,
                    help="fraction of detected HBM the preflight may "
                         "budget (paged only)")
    args = ap.parse_args(argv)
    from distributed_training_with_pipeline_parallelism_tpu.utils.compile_cache import (
        enable_compile_cache)
    enable_compile_cache()
    out_dir = args.out_dir
    loads = [float(x) for x in args.loads.split(",")]

    import json

    import distributed_training_with_pipeline_parallelism_tpu as dtpp
    from distributed_training_with_pipeline_parallelism_tpu.models import (
        transformer as tfm)
    from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import (
        make_mesh)
    from distributed_training_with_pipeline_parallelism_tpu.serving import (
        ServingEngine, make_serving_step_fn, sweep_offered_load)
    from distributed_training_with_pipeline_parallelism_tpu.utils.telemetry import (
        RunReport, validate_report, write_perfetto_trace)

    # CPU-proxy shape: big enough for the stock workload mixes
    # (long_doc prompts reach 12, short_chat outputs reach 16)
    prefill_chunk = 2
    cfg = dtpp.ModelConfig(dim=32, n_layers=4, n_heads=4, vocab_size=64,
                           ffn_dim=64, max_seq_len=48 + prefill_chunk - 1,
                           arch="gpt2")
    params = tfm.transformer_init(jax.random.key(0), cfg)
    mesh = make_mesh(n_pipe=2)
    paged_kw = ({"paged": True, "page_size": args.page_size}
                if args.paged else {})
    if args.paged and args.n_pages is not None:
        paged_kw["n_pages"] = args.n_pages
    program = make_serving_step_fn(cfg, mesh, n_slots=3, max_len=48,
                                   prompt_max=12, out_max=16,
                                   prefill_chunk=prefill_chunk, eos_id=None,
                                   **paged_kw)
    name = "serve_load_paged" if args.paged else "serve_load"
    report = RunReport(out_dir=out_dir, name=name)
    report.set_meta(config=cfg, mesh_shape=dict(mesh.shape),
                    backend=jax.devices()[0].platform,
                    n_slots=3, prefill_chunk=prefill_chunk,
                    loads=loads, mix=args.mix, n_requests=args.n_requests,
                    seed=args.seed, paged=args.paged)
    if args.paged:
        # price the pool BEFORE compiling anything: an over-budget page
        # pool becomes a skip row, not an OOM mid-ramp (building the
        # program is lazy — no trace has happened yet)
        from distributed_training_with_pipeline_parallelism_tpu.analysis.memory_model import (  # noqa: E501
            oom_preflight, serving_memory_section)
        pf = oom_preflight(serving_memory_section(cfg, program),
                           headroom=args.headroom)
        if not pf["ok"]:
            os.makedirs(out_dir, exist_ok=True)
            row = {"skip_reason": "predicted_oom", **pf,
                   "n_pages": int(program.n_pages),
                   "page_size": int(program.page_size)}
            with open(os.path.join(out_dir, "curve.json"), "w") as fh:
                json.dump(row, fh, indent=1)
            print(f"serve_load: SKIPPED (predicted_oom): "
                  f"{program.n_pages}-page pool prices at "
                  f"{pf['predicted_peak_bytes']:.3g} B/device vs "
                  f"{pf['hbm_bytes']:.3g} x {args.headroom} HBM — "
                  f"skip row at {os.path.join(out_dir, 'curve.json')}")
            return 0
    engine = ServingEngine(program, params, report=report)

    section = sweep_offered_load(engine, loads, mix=args.mix,
                                 n_requests=args.n_requests, seed=args.seed)
    report.attach_serving_load(section)

    # one-compilation invariant, sweep-wide: every ramp point replayed
    # the same jitted static-shape block; a second cache entry means a
    # shape leaked into the traced signature
    n_compiles = program.step._cache_size()
    if n_compiles != 1:
        print(f"serve_load: tick block compiled {n_compiles} times across "
              f"the ramp (want exactly 1)", file=sys.stderr)
        return 1

    knee = section["knee"]
    if not knee["detected"]:
        print(f"serve_load: no saturation knee on ramp {loads} — the "
              f"over-capacity point sustained the SLO", file=sys.stderr)
        return 1
    if knee["knee_load"] > loads[-1]:
        print(f"serve_load: knee at {knee['knee_load']} above the ramp top "
              f"{loads[-1]}", file=sys.stderr)
        return 1

    p99s = [_p99(row.get("ttft_ticks")) for row in section["curve"]]
    if any(v is None for v in p99s):
        print(f"serve_load: missing p99 TTFT on the curve: {p99s}",
              file=sys.stderr)
        return 1
    if any(b < a for a, b in zip(p99s, p99s[1:])):
        print(f"serve_load: p99 TTFT not monotone in offered load: {p99s} "
              f"— same-seed ramps share arrival order, so this is a "
              f"scheduling bug", file=sys.stderr)
        return 1

    manifest = report.write()
    validate_report(manifest)  # write() validates too; belt and suspenders
    if "serving_load" not in manifest:
        print("serve_load: manifest lost the serving_load section",
              file=sys.stderr)
        return 1

    curve_path = os.path.join(out_dir, "curve.json")
    with open(curve_path, "w") as fh:
        json.dump(section, fh, indent=1)

    # Perfetto: request async spans (wall-clock pid) + the tick-clock
    # serving-load process — queue-wait vs execution sub-spans per slot,
    # queue-depth and occupancy counters from the LAST ramp point (the
    # over-capacity one: that is where the queue ramp is worth looking
    # at; engine.run resets the series each replay)
    last = section["curve"][-1]["summary"]
    trace_path = write_perfetto_trace(
        os.path.join(out_dir, "requests_trace.json"),
        serving_events=report.events,
        serving_load_tracks={"occupancy": last.get("occupancy"),
                             "queue_depth": last.get("queue_depth"),
                             "s_per_tick": last.get("s_per_tick"),
                             "pages_used": last.get("pages_used"),
                             "page_fragmentation":
                                 last.get("page_fragmentation")})

    paged_note = ""
    if args.paged:
        hit = section["curve"][-1].get("prefix_hit_rate")
        paged_note = (f", paged ({program.n_pages} pages x "
                      f"{program.page_size}), prefix hit rate {hit}")
    print(f"serve_load: OK — ramp {loads} ({args.mix}, "
          f"{args.n_requests} req/point), knee at {knee['knee_load']} "
          f"({knee['reason']}), max sustainable "
          f"{knee['max_sustainable_load']}, p99 TTFT {p99s} ticks, "
          f"1 compile{paged_note}; report at "
          f"{os.path.join(out_dir, 'report.json')}; "
          f"curve at {curve_path}; trace at {trace_path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
