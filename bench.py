"""Headline benchmark: pipeline training-step throughput on real hardware.

Reproduces the reference's measurement semantics (SURVEY.md C4,
``LLMsDistributedTrainingHelper.py:98-143``): timed full schedule steps
(forward + backward + inter-stage transfer, no optimizer) after 2 untimed
warmup iterations; throughput = batch * seq * iters / elapsed in tokens/sec.

The HEADLINE is the pipeline machinery itself (VERDICT r2 item 3): the
executor program compiled from the schedule table on the reference's
canonical mid config (ref_decoder L8/H8, batch 32, seq 128, 4
microbatches, ``force_tick_executor=True`` so the degenerate fused
full-batch path is disabled). On one chip the default executor
formulation is the UNROLLED stored program (table ticks as straight-line
microbatch code, autodiff backward — docs/performance.md "Backward
policy"); on a multi-chip pipe mesh it is the rematerializing tick scan,
and the metric label states which ran. Also timed, under "extra":

1. ``fused_ceiling`` — the same config on the degenerate 1-chip fast path
   (one fused full-batch step, identical loss/grads, tested): the model+
   loss compute ceiling. ceiling/headline IS the executor overhead
   (reported as ``tick_executor_overhead``, > 1).
2. ``tick_executor_remat`` — the remat tick program under the AUTO
   executor formulation (unrolled at this table size; the D>1 default
   policy). ``stored_backward_speedup`` (headline/remat) is reported only
   where the headline actually ran the stored form (1 chip).
3. ``phase_executor`` / ``tick_executor_scan`` — the same remat tick
   program under ``unroll_ticks="phases"`` (per-pattern specialized scan
   bodies) and ``unroll_ticks=False`` (cond-dispatched whole-table scan):
   with the per-row ``compile_s`` column this captures the executor-
   formulation trade (throughput vs compile time) the phase-compressed
   mode exists to close.
4. ``gpt2_small_1024`` / ``gpt2_medium_1024`` — GPT-2 124M/355M at
   seq 1024, bf16: real model families at a real sequence length
   (flash-attention kernel active per the "auto" policy).

Each row reports MFU (model-FLOP utilization): train FLOPs/token =
6*N_params + 12*L*dim*seq (PaLM appendix-B accounting, causal factored),
against the chip's advertised bf16 peak.

Baseline: the reference's GPipe L8/H8 2-process run on 10-core CPU/gloo =
1671.32 tok/s (BASELINE.md, notebook cell 25).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}
— the headline metric up front, the other runs and MFU under "extra",
plus a structured RunReport manifest (utils.telemetry schema) embedded as
``extra.run_report`` (or written to ``$BENCH_REPORT_PATH``). Every mode
measures the TPU or nothing: with no TPU visible it exits non-zero and
prints no result, a mesh is built from the devices that are there (too few
is an error), and a rung that fails fails the run.
"""

import json
import math
import os
import sys
import time

import jax

import distributed_training_with_pipeline_parallelism_tpu as dtpp
from distributed_training_with_pipeline_parallelism_tpu.models import transformer as tfm
from distributed_training_with_pipeline_parallelism_tpu.models.gpt2 import gpt2_config
from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import make_mesh
from distributed_training_with_pipeline_parallelism_tpu.parallel.pipeline import (
    make_pipeline_step)

BASELINE_TOKS_PER_SEC = 1671.32  # GPipe L8/H8 2 procs, reference cell 25

# advertised bf16 dense peak per chip, keyed by a substring of
# ``device_kind`` (a v5e reports "TPU v5 lite"). v5e is 197 TFLOP/s bf16
# (394 is its INT8 TOPS — a 2x MFU-understating trap this repo fell into
# until round 3)
_PEAK_FLOPS = {"v5 lite": 197e12, "v5e": 197e12, "v5p": 459e12,
               "v4": 275e12, "v6": 918e12}


def _require_tpu() -> dict:
    """The devices this run measures. A rate from anything but a TPU is not
    a result of this benchmark, so there is no fallback: exit non-zero,
    print nothing on stdout."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU; JAX sees {len(devices)} x "
                         f"{devices[0].platform} — no result")
    return {"backend": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "n_devices": len(devices)}


def chip_peak_flops() -> float:
    kind = jax.devices()[0].device_kind.lower()
    for key, peak in _PEAK_FLOPS.items():
        if key in kind:
            return peak
    raise ValueError(f"no bf16 peak on record for device kind {kind!r}: add "
                     f"it to _PEAK_FLOPS with its source — an MFU against a "
                     f"guessed peak is not a measurement")


def train_flops_per_token(cfg, seq: int) -> float:
    """6*N + 12*L*dim*seq: fwd 2N + attention 2*2*L*dim*s per token (QK^T
    and PV each 2*dim*s per layer), bwd 2x fwd — the standard dense-LM
    accounting (PaLM appendix B). The formula lives in
    ``analysis.cost_model`` (the roofline needs the same numbers); this
    delegate keeps bench's historical entry point."""
    from distributed_training_with_pipeline_parallelism_tpu.analysis.cost_model import (
        train_flops_per_token as _train_flops_per_token)
    return _train_flops_per_token(cfg, seq)


def _time_step(step, params, tokens, targets, num_iterations):
    from distributed_training_with_pipeline_parallelism_tpu.utils.metrics import (
        force_completion)
    # First warmup call = trace + XLA compile (+ one execution, negligible
    # next to compile at these sizes): the executor-formulation compile
    # economics the phase-compressed mode exists to fix, reported per row
    # as compile_s.
    start = time.perf_counter()
    force_completion(step(params, tokens, targets))
    compile_s = time.perf_counter() - start
    force_completion(step(params, tokens, targets))  # second warmup, untimed
    # Median of 3 measurement windows, each closed by force_completion on
    # the last step's loss (a device executes its programs in order).
    elapsed_runs = []
    for _ in range(3):
        start = time.perf_counter()
        for _ in range(num_iterations):
            loss, grads = step(params, tokens, targets)
        force_completion(loss)
        elapsed_runs.append(time.perf_counter() - start)
    # report the last loss so a diverged/NaN config is flagged instead of
    # publishing a throughput number for garbage math
    return sorted(elapsed_runs)[1], compile_s, float(loss)


def run_config(cfg, batch_size, seq_length, num_iterations=20,
               schedule="GPipe", n_microbatches=4, n_virtual=1,
               force_tick_executor=False, remat_backward=None,
               unroll_ticks=None, n_pipe=None, comm_overlap=None) -> dict:
    if n_pipe is None:  # 1-D pipeline mesh over every visible chip
        n_pipe = len(jax.devices())
    sched = dtpp.ScheduleConfig(name=schedule, n_microbatches=n_microbatches,
                                n_virtual=n_virtual)
    mesh = make_mesh(n_pipe=n_pipe)
    step = make_pipeline_step(cfg, mesh, sched,
                              force_tick_executor=force_tick_executor,
                              remat_backward=remat_backward,
                              unroll_ticks=unroll_ticks,
                              comm_overlap=comm_overlap or "none")
    params = tfm.transformer_init(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (batch_size, seq_length),
                                0, cfg.vocab_size)
    targets = jax.random.randint(jax.random.key(2), (batch_size, seq_length),
                                 0, cfg.vocab_size)
    elapsed, compile_s, last_loss = _time_step(step, params, tokens, targets,
                                               num_iterations)
    tokens_processed = batch_size * seq_length * num_iterations
    throughput = tokens_processed / elapsed
    flops_tok = train_flops_per_token(cfg, seq_length)
    mfu = throughput * flops_tok / (chip_peak_flops() * n_pipe)
    row = {"tokens_per_sec": round(throughput, 2),
           "mfu": round(mfu, 4),
           "elapsed_s": round(elapsed, 3),
           "compile_s": round(compile_s, 2),
           "overlap": comm_overlap or "none"}
    if not math.isfinite(last_loss):
        # a benchmark number for a program computing NaNs is meaningless —
        # flag it loudly in the row rather than failing the whole sweep
        row["anomaly"] = f"non-finite loss ({last_loss}) after timed window"
    return row


def _cost_model(cfg, batch_size, seq_length, n_pipe, headline,
                num_iterations, n_microbatches=4) -> dict:
    """Roofline section for the headline config (analysis.cost_model):
    predicted vs measured step time, bubble fractions, MFU/HFU — attached
    to the RunReport manifest and consumed by scripts/regress.py."""
    from distributed_training_with_pipeline_parallelism_tpu.analysis.calibration import (
        maybe_load_default_corrections)
    from distributed_training_with_pipeline_parallelism_tpu.analysis.cost_model import (
        cost_model_section)
    from distributed_training_with_pipeline_parallelism_tpu.parallel.schedules import (
        compile_schedule)
    cs = compile_schedule("GPipe", n_pipe, 1, n_microbatches)
    return cost_model_section(
        cs, cfg, batch_size=batch_size, seq_length=seq_length,
        measured_step_s=headline["elapsed_s"] / max(num_iterations, 1),
        correction=maybe_load_default_corrections())


def _memory_model(cfg, batch_size, seq_length, n_pipe, n_microbatches=4,
                  schedule="GPipe") -> dict:
    """Bytes-domain section for a bench config (analysis.memory_model):
    analytic per-device HBM from the verifier's slot peaks — attached to
    the RunReport manifest, consulted by the rung OOM preflight, and
    guarded by scripts/regress.py."""
    from distributed_training_with_pipeline_parallelism_tpu.analysis.memory_model import (
        memory_model_section)
    from distributed_training_with_pipeline_parallelism_tpu.parallel.schedules import (
        compile_schedule)
    cs = compile_schedule(schedule, n_pipe, 1, n_microbatches)
    return memory_model_section(cs, cfg, batch_size=batch_size,
                                seq_length=seq_length)


def _rung_preflight(cfg, batch_size, seq_length, n_pipe,
                    n_microbatches) -> dict:
    """Price a rung before compiling it. Returns the ``oom_preflight``
    verdict ({"ok": True} on any pricing failure — the preflight must
    never veto a rung it could not price)."""
    from distributed_training_with_pipeline_parallelism_tpu.analysis.memory_model import (
        oom_preflight)
    try:
        return oom_preflight(_memory_model(cfg, batch_size, seq_length,
                                           n_pipe, n_microbatches))
    except Exception:  # pragma: no cover - pricing must not veto rungs
        return {"ok": True}


def _result(headline, extra, n_pipe) -> dict:
    """Assemble the printed JSON line + the embedded RunReport manifest
    (same schema as sweep rows and ``fit`` reports — utils.telemetry)."""
    from distributed_training_with_pipeline_parallelism_tpu.utils.telemetry import (
        RunReport, validate_report)
    report = RunReport(name="bench")
    report.set_meta(n_devices=n_pipe,
                    **{k: extra[k] for k in
                       ("backend", "device_kind", "chip_peak_flops")
                       if k in extra})
    for k, v in headline.items():
        report.gauge(f"headline_{k}", v)
    # the overlap pair gets first-class gauges so scripts/regress.py can
    # guard overlap-on throughput per (name, backend, schedule) group
    for ov_key in ("overlap_on", "overlap_off"):
        ov_row = extra.get(ov_key)
        if isinstance(ov_row, dict) and "tokens_per_sec" in ov_row:
            report.gauge(f"{ov_key}_tokens_per_sec",
                         ov_row["tokens_per_sec"])
    if isinstance(extra.get("overlap_speedup"), (int, float)):
        report.gauge("overlap_speedup", extra["overlap_speedup"])
    cm = extra.get("cost_model")
    if isinstance(cm, dict) and "schedule" in cm:  # not an error stub
        report.attach_cost_model(cm)
        # predicted-vs-measured as first-class gauges + a calibration
        # section, so scripts/regress.py guards model error the same way
        # it guards throughput (docs/observability.md §9)
        report.gauge("predicted_step_s", cm["predicted"]["step_s"])
        measured = cm.get("measured") or {}
        # ...and as first-class headline-row columns in the printed JSON
        headline["predicted_step_s"] = cm["predicted"]["step_s"]
        headline["rel_err"] = measured.get("rel_err")
        if measured.get("rel_err") is not None:
            report.gauge("rel_err", measured["rel_err"])
        if measured.get("rel_err_corrected") is not None:
            report.gauge("rel_err_corrected", measured["rel_err_corrected"])
        from distributed_training_with_pipeline_parallelism_tpu.analysis.calibration import (
            calibration_section_from_cost_model, maybe_load_default_corrections)
        cal_section = calibration_section_from_cost_model(
            cm, backend=str(extra.get("backend", "unknown")), name="bench",
            correction=maybe_load_default_corrections())
        if cal_section is not None:
            report.attach_calibration(cal_section)
    mem = extra.get("memory")
    if isinstance(mem, dict) and "analytic" in mem:  # not an error stub
        report.attach_memory(mem)
    for key, row in extra.items():
        if isinstance(row, dict) and key not in ("cost_model", "memory"):
            report.event("rung", name=key, **row)
    manifest = report.manifest()
    validate_report(manifest)
    path = os.environ.get("BENCH_REPORT_PATH")
    if path:
        with open(path, "w") as fh:
            json.dump(manifest, fh, indent=2)
            fh.write("\n")
        extra["run_report_path"] = path
    else:
        extra["run_report"] = manifest
    backward = ("unrolled stored backward" if n_pipe == 1
                else "rematerializing backward")
    metric = extra.pop("metric_override", None) or (
        f"pipeline-executor train-step throughput (GPipe, L8/H8, "
        f"batch 32, seq 128, 4 microbatches, {n_pipe}-stage, "
        f"bfloat16, fused-CE, {backward})")
    return {
        "metric": metric,
        "value": headline["tokens_per_sec"],
        "unit": "tokens/sec",
        "vs_baseline": round(headline["tokens_per_sec"]
                             / BASELINE_TOKS_PER_SEC, 3),
        "extra": extra,
    }


def run(num_iterations: int = 20) -> dict:
    backend = _require_tpu()
    n_pipe = backend["n_devices"]
    # reference defaults (dim 768, L8, H8, vocab 10k) in the MXU-native
    # dtype; fused cross-entropy (our Pallas kernel) on: measured ~+1% here
    ref_cfg = dtpp.ModelConfig(dtype="bfloat16", use_fused_xent=True,
                               max_seq_len=128)
    # THE headline: the real tick-table executor (stored-activation
    # backward, 4 microbatches) — the machinery this framework exists to
    # provide, not the degenerate fused path
    headline = run_config(ref_cfg, 32, 128, num_iterations,
                          force_tick_executor=True, n_pipe=n_pipe)
    extra = {"headline": headline, "chip_peak_flops": chip_peak_flops(),
             **backend}
    extra["cost_model"] = _cost_model(ref_cfg, 32, 128, n_pipe, headline,
                                      num_iterations)
    extra["memory"] = _memory_model(ref_cfg, 32, 128, n_pipe)
    fused = run_config(ref_cfg, 32, 128, num_iterations, n_pipe=n_pipe)
    extra["fused_ceiling"] = fused
    extra["tick_executor_overhead"] = round(
        fused["tokens_per_sec"] / headline["tokens_per_sec"], 3)
    remat = run_config(ref_cfg, 32, 128, num_iterations,
                       force_tick_executor=True, remat_backward=True,
                       n_pipe=n_pipe)
    extra["tick_executor_remat"] = remat
    if n_pipe == 1:  # headline ran the unrolled stored form
        extra["stored_backward_speedup"] = round(
            headline["tokens_per_sec"] / remat["tokens_per_sec"], 3)
    # executor-formulation triangle on the same remat tick program
    # (docs/performance.md "Executor formulations"): the auto row above
    # unrolls at this table size (~2.2 s/row compile), phase_executor
    # scans per-pattern specialized bodies (compile ~ unique patterns),
    # tick_executor_scan is the cond-dispatched whole-table scan — each
    # row's compile_s is the column that captures the trade
    extra["phase_executor"] = run_config(
        ref_cfg, 32, 128, num_iterations, force_tick_executor=True,
        remat_backward=True, unroll_ticks="phases", n_pipe=n_pipe)
    extra["tick_executor_scan"] = run_config(
        ref_cfg, 32, 128, num_iterations, force_tick_executor=True,
        remat_backward=True, unroll_ticks=False, n_pipe=n_pipe)
    # comm/compute overlap pair (docs/performance.md "Comm/compute
    # overlap"): the SAME unrolled remat tick program with each tick's ring
    # hops issued at their deferred bank points (comm_overlap="ring",
    # bit-identical by the table_check overlap discipline) vs the
    # lockstep baseline. remat_backward=True is what a multi-chip mesh
    # takes by default and what one chip must be told: its default, the
    # phase-stored backward, has no bank sites and refuses "ring". On a
    # multi-chip mesh overlap_speedup >= 1 is the bar scripts/regress.py
    # guards; one chip serializes every tick, so there the pair proves the
    # staged program dispatches and stays parity, not that it is faster.
    off = run_config(ref_cfg, 32, 128, num_iterations,
                     force_tick_executor=True, remat_backward=True,
                     unroll_ticks=True, n_pipe=n_pipe, comm_overlap="none")
    on = run_config(ref_cfg, 32, 128, num_iterations,
                    force_tick_executor=True, remat_backward=True,
                    unroll_ticks=True, n_pipe=n_pipe, comm_overlap="ring")
    extra["overlap_off"] = off
    extra["overlap_on"] = on
    extra["overlap_speedup"] = round(
        on["tokens_per_sec"] / off["tokens_per_sec"], 3)
    # tie_embeddings=True is the real GPT-2 124M (and keeps the MFU's 6*N
    # honest: the tied table is the head matmul); unroll_layers +
    # batch 16/8 are the measured round-3 MFU levers (docs/performance.md)
    from distributed_training_with_pipeline_parallelism_tpu.models.llama import (
        llama_config)
    rungs = [
        # bs24 became the small rung's sweet spot when the head-packed
        # kernels stopped materializing transposed q/k/v copies (round 4:
        # bs16 53.9%, bs24 55.0%, bs32 54.8% MFU — bs32 only FITS since)
        (gpt2_config("small", dtype="bfloat16", use_fused_xent=True,
                     tie_embeddings=True, unroll_layers=True),
         24, 4, 1024, "gpt2_small_seq1024_bs24"),
        (gpt2_config("medium", dtype="bfloat16", use_fused_xent=True,
                     tie_embeddings=True, unroll_layers=True),
         8, 4, 1024, "gpt2_medium_seq1024_bs8"),
        # rung 4's model family (GQA + RoPE + SwiGLU + tied 128k vocab):
        # bs6 is the largest that fits next to its own grads on one chip
        # (VERDICT r3 item 5 measurements, same unroll_layers lever on
        # both sides: bs8 only fits WITH remat_layers and its 1.33x
        # recompute FLOPs land it at ~15.3k tok/s — SLOWER than the
        # stored-activation bs4/bs6 runs at ~18.9k, so more batch does
        # not pay at a model already near the MXU roof; bs8-remat is
        # reported below so the answer stays measured, not assumed)
        (llama_config("llama3.2-1b", dtype="bfloat16", use_fused_xent=True,
                      unroll_layers=True),
         6, 2, 1024, "llama32_1b_seq1024_bs6"),
        (llama_config("llama3.2-1b", dtype="bfloat16", use_fused_xent=True,
                      remat_layers=True, unroll_layers=True),
         8, 4, 1024, "llama32_1b_seq1024_bs8_remat"),
    ]
    # Long-context rungs (round 5, VERDICT r4 item 5): the sequences where
    # dense attention cannot even compile (8192: 18 GB of scores vs
    # 15.75 GB HBM) — the flash kernels' clearest TPU-native win, now with
    # committed numbers. Batch = the measured per-chip ceiling (4096: bs12
    # OOMs; 8192: bs2 is the compile ceiling, docs/performance.md round-5
    # long-context section). seq overrides the default 1024 below.
    rungs += [
        (gpt2_config("small", dtype="bfloat16", use_fused_xent=True,
                     tie_embeddings=True, unroll_layers=True,
                     max_seq_len=4096),
         8, 1, 4096, "gpt2_small_seq4096_bs8"),
        (gpt2_config("small", dtype="bfloat16", use_fused_xent=True,
                     tie_embeddings=True, unroll_layers=True,
                     max_seq_len=8192),
         2, 1, 8192, "gpt2_small_seq8192_bs2"),
    ]
    for rung_cfg, batch, n_mb, seq, key in rungs:
        if rung_cfg.n_layers % n_pipe != 0:
            extra[key] = {"skipped": f"{n_pipe} devices do not divide "
                                     f"{rung_cfg.n_layers} layers"}
            continue
        # OOM preflight: a rung the memory model prices over the chip's
        # HBM becomes a labelled skip row instead of a mid-bench crash
        pf = _rung_preflight(rung_cfg, batch, seq, n_pipe, n_mb)
        if not pf["ok"]:
            extra[key] = {
                "skipped": "predicted_oom",
                "predicted_peak_bytes": pf["predicted_peak_bytes"],
                "hbm_bytes": pf["hbm_bytes"]}
            continue
        extra[key] = run_config(rung_cfg, batch, seq, num_iterations,
                                n_microbatches=n_mb, n_pipe=n_pipe)
    return _result(headline, extra, n_pipe)


def run_serve() -> dict:
    """``--serve``: the continuous-vs-static serving comparison.

    Replays one synthetic Poisson trace through the slot-level serving
    executor (``serving/``) under both admission policies and prints the
    comparison row. Its pipe mesh is built from the TPU devices that are
    there; with fewer than its stage count ``make_mesh`` raises."""
    from distributed_training_with_pipeline_parallelism_tpu.serving.bench import (
        run_serve_bench)
    from distributed_training_with_pipeline_parallelism_tpu.utils.telemetry import (
        RunReport, validate_report)
    backend = _require_tpu()
    report = RunReport(name="serve_bench")
    report.set_meta(**backend)
    row = run_serve_bench(report=report)
    for k in ("continuous_tokens_per_sec", "static_tokens_per_sec",
              "throughput_gain", "tick_gain", "ttft_p50_ticks",
              "ttft_p99_ticks"):
        if row.get(k) is not None:
            report.gauge(f"serve_{k}", row[k])
    manifest = report.manifest()
    validate_report(manifest)
    extra = {**row, **backend}
    path = (os.environ.get("SERVE_REPORT_PATH")
            or os.environ.get("BENCH_REPORT_PATH"))
    if path:
        with open(path, "w") as fh:
            json.dump(manifest, fh, indent=2)
            fh.write("\n")
        extra["run_report_path"] = path
    else:
        extra["run_report"] = manifest
    return {
        "metric": (f"continuous-batching serving throughput vs static "
                   f"fill-drain (Poisson trace, {row['n_requests']} "
                   f"requests, load {row['load']}, {row['n_pipe']}-stage "
                   f"ring, {row['n_slots']} slots)"),
        "value": row["continuous_tokens_per_sec"],
        "unit": "tokens/sec",
        "vs_static": row["throughput_gain"],
        "extra": extra,
    }


def run_searched(artifact_path: str, num_iterations: int = 5) -> dict:
    """``--schedule-artifact PATH``: benchmark a certified searched
    schedule as a first-class citizen.

    Registers the artifact (full re-certification + pin — a tampered
    table never reaches the executor), runs a small proxy model through
    the real tick executor under the searched schedule AND under 1F1B on
    the same shape, and reports both rows plus the artifact's predicted
    cost. The mesh takes the artifact's certified device count from the
    TPU devices that are there; with too few ``make_mesh`` raises."""
    from distributed_training_with_pipeline_parallelism_tpu.parallel.schedules import (
        register_schedule_artifact, registered_artifact_info)
    from distributed_training_with_pipeline_parallelism_tpu.utils.telemetry import (
        RunReport, validate_report)
    backend = _require_tpu()
    cs = register_schedule_artifact(artifact_path)
    D, V, M = cs.n_devices, cs.n_virtual, cs.n_microbatches
    # smallest model the shape admits: layers divisible by D*V stages,
    # test-suite-scale width — the row compares schedules, not hardware
    proxy_cfg = dtpp.ModelConfig(dim=64, n_layers=2 * D * V, n_heads=4,
                                 vocab_size=256, ffn_dim=128, max_seq_len=64)
    headline = run_config(proxy_cfg, 4 * M, 64, num_iterations,
                          schedule=cs.name, n_microbatches=M, n_virtual=V,
                          force_tick_executor=True, n_pipe=D)
    extra = {"headline": headline, **backend,
             "schedule_artifact": {"path": artifact_path,
                                   **(registered_artifact_info(cs.name)
                                      or {})}}
    with open(artifact_path) as fh:  # certified above, so it parses
        art = json.load(fh)
    extra["predicted"] = art.get("predicted")
    extra["baselines"] = art.get("baselines")
    extra["one_f_one_b"] = run_config(
        proxy_cfg, 4 * M, 64, num_iterations, schedule="1F1B",
        n_microbatches=M, force_tick_executor=True, n_pipe=D)
    report = RunReport(name="bench_searched")
    report.set_meta(n_devices=D, backend=backend["backend"],
                    schedule={"name": cs.name, "n_microbatches": M,
                              "n_virtual": V},
                    schedule_artifact=extra["schedule_artifact"])
    for k, v in headline.items():
        report.gauge(f"headline_{k}", v)
    manifest = report.manifest()
    validate_report(manifest)
    path = os.environ.get("BENCH_REPORT_PATH")
    if path:
        with open(path, "w") as fh:
            json.dump(manifest, fh, indent=2)
            fh.write("\n")
        extra["run_report_path"] = path
    else:
        extra["run_report"] = manifest
    extra["metric_override"] = (
        f"searched-schedule executor throughput ({cs.name}, certified "
        f"artifact, D={D}, V={V}, M={M}, proxy model L{proxy_cfg.n_layers})")
    return _result(headline, extra, D)


if __name__ == "__main__":
    from distributed_training_with_pipeline_parallelism_tpu.utils.compile_cache import (
        enable_compile_cache)
    enable_compile_cache()
    if "--schedule-artifact" in sys.argv:
        i = sys.argv.index("--schedule-artifact")
        if i + 1 >= len(sys.argv):
            raise SystemExit("bench: --schedule-artifact needs a PATH")
        print(json.dumps(run_searched(sys.argv[i + 1])))
    elif "--serve" in sys.argv:
        print(json.dumps(run_serve()))
    else:
        print(json.dumps(run()))
