"""Serving smoke: continuous batching on a CPU mesh, oracle-checked.

The tier-1 liveness check for the serving layer (scripts/tier1.sh runs
it ahead of the suite; CI uploads the resulting report as an
artifact): drive a small request mix through the slot-level
:class:`ServingEngine` on an 8-device simulated CPU mesh and require

- every request completes, and its greedy tokens BIT-MATCH the
  single-device ``models.generate`` oracle (mid-flight admissions into
  recycled slots included),
- the static fill-drain policy emits the same per-request tokens and
  needs at least as many ticks as continuous,
- the paged-KV engine (page-pool gather + host-side radix/COW
  admission, same geometry) emits tokens bit-identical to the
  contiguous run on one compiled block,
- the speculative draft-verify engine (self-draft, gamma=1) emits
  tokens bit-identical to the contiguous run on one compiled block,
  with at least one verify visit measured,
- the paged-vs-contiguous comparison at a matched per-device HBM
  budget (``run_paged_bench`` on the shared-prefix mix) admits at
  least as many slots, matches completions across engines, and shows a
  nonzero prefix hit rate — the ISSUE 19 headline, uploaded as
  ``paged_compare.json``,
- a ``RunReport`` manifest with a populated ``serving`` section (TTFT /
  TPOT percentiles) that passes ``validate_report``.

Writes ``report.json`` (+ ``events.jsonl``) and ``paged_compare.json``
into the output directory (argv[1], default ``/tmp/serve_smoke``) and
exits 0 on success, 1 with a reason on any violation. Six small
compiles (contiguous + paged + speculative serving blocks, oracle, the
comparison's two engines): target a couple of minutes on a CI host.
"""

import os
import sys

# must precede the first jax import: 8 simulated devices, CPU backend
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def main() -> int:
    out_dir = sys.argv[1] if len(sys.argv) > 1 else "/tmp/serve_smoke"
    from distributed_training_with_pipeline_parallelism_tpu.utils.compile_cache import (
        enable_compile_cache)
    enable_compile_cache()

    import numpy as np

    import distributed_training_with_pipeline_parallelism_tpu as dtpp
    from distributed_training_with_pipeline_parallelism_tpu.models import (
        transformer as tfm)
    from distributed_training_with_pipeline_parallelism_tpu.models.generate import (
        generate)
    from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import (
        make_mesh)
    from distributed_training_with_pipeline_parallelism_tpu.serving import (
        Request, ServingEngine, make_serving_step_fn)
    from distributed_training_with_pipeline_parallelism_tpu.utils.telemetry import (
        RunReport, serving_summary, validate_report)

    EOS = 7
    cfg = dtpp.ModelConfig(dim=32, n_layers=4, n_heads=4, vocab_size=64,
                           ffn_dim=64, max_seq_len=64, arch="gpt2")
    params = tfm.transformer_init(jax.random.key(0), cfg)
    mesh = make_mesh(n_pipe=2)
    program = make_serving_step_fn(cfg, mesh, n_slots=3, max_len=32,
                                   prompt_max=8, out_max=10,
                                   prefill_chunk=2, eos_id=EOS)
    report = RunReport(out_dir=out_dir, name="serve_smoke")
    report.set_meta(config=cfg, mesh_shape=dict(mesh.shape),
                    backend=jax.devices()[0].platform,
                    n_slots=3, prefill_chunk=2, eos_id=EOS)
    engine = ServingEngine(program, params, report=report)

    rng = np.random.RandomState(0)
    requests = [Request(rid=i,
                        prompt=rng.randint(0, cfg.vocab_size,
                                           size=int(rng.randint(1, 9)))
                        .tolist(),
                        max_new_tokens=int(rng.randint(1, 11)),
                        arrival=float(i))
                for i in range(5)]

    res = engine.run(requests, policy="continuous")
    if len(res.completions) != len(requests):
        print(f"serve_smoke: {len(res.completions)} completions for "
              f"{len(requests)} requests", file=sys.stderr)
        return 1
    budgets = {r.rid: r.max_new_tokens for r in requests}
    for c in res.completions:
        want_toks, want_len = generate(
            cfg, params, np.asarray([c.prompt], np.int32),
            max_new_tokens=budgets[c.rid], eos_id=EOS, return_lengths=True,
            max_len=program.mlen_alloc)
        n = int(want_len[0])
        want = [int(t) for t in np.asarray(want_toks)[0]
                [len(c.prompt):len(c.prompt) + n]]
        if c.tokens != want:
            print(f"serve_smoke: rid {c.rid} diverged from the "
                  f"single-device oracle: {c.tokens} != {want}",
                  file=sys.stderr)
            return 1
    report.attach_serving(serving_summary(res))

    static = engine.run(requests, policy="static")
    by_rid = {c.rid: c.tokens for c in static.completions}
    if any(by_rid.get(c.rid) != c.tokens for c in res.completions):
        print("serve_smoke: static policy emitted different tokens",
              file=sys.stderr)
        return 1
    if static.ticks < res.ticks:
        print(f"serve_smoke: static finished in fewer ticks "
              f"({static.ticks} < {res.ticks})", file=sys.stderr)
        return 1
    report.attach_serving(serving_summary(static))

    # paged-KV parity: the page-pool engine on the same geometry must be
    # bit-identical to the contiguous run (the gather through the page
    # table reconstructs exactly the contiguous per-slot view)
    paged_prog = make_serving_step_fn(cfg, mesh, n_slots=3, max_len=32,
                                      prompt_max=8, out_max=10,
                                      prefill_chunk=2, eos_id=EOS,
                                      paged=True, page_size=4)
    paged_engine = ServingEngine(paged_prog, params, report=report)
    paged_res = paged_engine.run(requests, policy="continuous")
    cont_by_rid = {c.rid: c.tokens for c in res.completions}
    if any(cont_by_rid.get(c.rid) != c.tokens
           for c in paged_res.completions):
        print("serve_smoke: paged engine emitted different tokens than "
              "contiguous", file=sys.stderr)
        return 1
    if paged_prog.step._cache_size() != 1:
        print(f"serve_smoke: paged block compiled "
              f"{paged_prog.step._cache_size()}x (want 1)", file=sys.stderr)
        return 1
    paged_engine.paging.check_invariants()  # raises on any page leak
    report.attach_serving(serving_summary(paged_res))

    # speculative parity: the draft-verify engine (self-draft, gamma=1 —
    # the widest draft this geometry's prefill_chunk=2 admits) on the
    # same geometry must be bit-identical to the contiguous run — greedy
    # acceptance only ever banks tokens the target itself argmaxed
    spec_prog = make_serving_step_fn(cfg, mesh, n_slots=3, max_len=32,
                                     prompt_max=8, out_max=10,
                                     prefill_chunk=2, eos_id=EOS,
                                     speculative=True, gamma=1,
                                     draft_cfg=cfg)
    spec_engine = ServingEngine(spec_prog, params, draft_params=params,
                                report=report)
    spec_res = spec_engine.run(requests, policy="continuous")
    if any(cont_by_rid.get(c.rid) != c.tokens
           for c in spec_res.completions):
        print("serve_smoke: speculative engine emitted different tokens "
              "than plain", file=sys.stderr)
        return 1
    if spec_prog.step._cache_size() != 1:
        print(f"serve_smoke: speculative block compiled "
              f"{spec_prog.step._cache_size()}x (want 1)", file=sys.stderr)
        return 1
    if not spec_res.spec_verify_visits:
        print("serve_smoke: speculative run never reached a verify visit",
              file=sys.stderr)
        return 1
    report.attach_serving(serving_summary(spec_res))

    # the ISSUE 19 headline: paged vs contiguous at a matched HBM budget
    # on the shared-prefix mix, reusing this smoke's weights (two more
    # small compiles); the row is the CI artifact regress/plot consumers
    # read
    from distributed_training_with_pipeline_parallelism_tpu.serving.bench import (
        run_paged_bench)
    compare = run_paged_bench(cfg=cfg, params=params, mesh=mesh,
                              n_slots=4, max_len=32, prompt_max=12,
                              out_max=16, page_size=4, n_requests=12,
                              load=1.2, seed=0)
    if not compare["outputs_match"]:
        print("serve_smoke: paged-vs-contiguous completions diverged at "
              "matched budget", file=sys.stderr)
        return 1
    if compare["paged_slots"] < compare["contiguous_slots"]:
        print(f"serve_smoke: paged admitted fewer slots "
              f"({compare['paged_slots']} < {compare['contiguous_slots']}) "
              f"at the same budget", file=sys.stderr)
        return 1
    if not compare["prefix_hit_rate"]:
        print("serve_smoke: zero prefix hit rate on the prefix mix",
              file=sys.stderr)
        return 1
    report.gauge("prefix_hit_rate", compare["prefix_hit_rate"])
    report.gauge("paged_slot_gain", compare["slot_gain"])
    report.gauge("paged_goodput_gain", compare["goodput_gain"])

    # memory observatory: analytic KV/params accounting + XLA's numbers
    # for the already-compiled serving block (docs/observability.md)
    from distributed_training_with_pipeline_parallelism_tpu.analysis.memory_model import (
        serving_memory_section)
    from distributed_training_with_pipeline_parallelism_tpu.parallel.pipeline import (
        aot_memory_analysis)
    report.attach_memory(serving_memory_section(
        cfg, program,
        compiled=aot_memory_analysis(program.step, *engine.weights,
                                     program.init_state())))

    manifest = report.write()
    validate_report(manifest)  # write() validates too; belt and suspenders
    rows = manifest.get("serving", [])
    if len(rows) != 4 or rows[0]["ttft_ticks"]["p50"] is None:
        print("serve_smoke: serving section missing or empty",
              file=sys.stderr)
        return 1
    if not rows[2].get("paged") or "prefix_hit_rate" not in rows[2]:
        print("serve_smoke: paged serving row lost its page gauges",
              file=sys.stderr)
        return 1
    if not rows[3].get("speculative") \
            or rows[3].get("acceptance_rate") is None:
        print("serve_smoke: speculative serving row lost its acceptance "
              "gauges", file=sys.stderr)
        return 1
    if "memory" not in manifest or not manifest["memory"]["analytic"].get(
            "kv_cache_bytes_per_device"):
        print("serve_smoke: memory section missing or without KV bytes",
              file=sys.stderr)
        return 1

    # per-request async spans (serve_admit -> serve_finish, with the
    # on-device tick stamps in the args) on a "requests" Perfetto track
    from distributed_training_with_pipeline_parallelism_tpu.utils.telemetry import (
        write_perfetto_trace)
    trace_path = write_perfetto_trace(
        os.path.join(out_dir, "requests_trace.json"),
        serving_events=report.events)
    import json

    compare_path = os.path.join(out_dir, "paged_compare.json")
    with open(compare_path, "w") as fh:
        json.dump(compare, fh, indent=1)
    with open(trace_path) as fh:
        tr = json.load(fh)
    n_b = sum(1 for e in tr["traceEvents"] if e.get("ph") == "b")
    if n_b != len(requests):
        print(f"serve_smoke: requests trace has {n_b} spans for "
              f"{len(requests)} requests", file=sys.stderr)
        return 1

    print(f"serve_smoke: OK — {len(requests)} requests bit-matched the "
          f"oracle; continuous {res.ticks} ticks vs static {static.ticks}; "
          f"paged bit-matched contiguous; matched-budget comparison "
          f"{compare['paged_slots']} vs {compare['contiguous_slots']} "
          f"slots, prefix hit rate {compare['prefix_hit_rate']:.3f} "
          f"({compare_path}); report at "
          f"{os.path.join(out_dir, 'report.json')}; request spans at "
          f"{trace_path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
