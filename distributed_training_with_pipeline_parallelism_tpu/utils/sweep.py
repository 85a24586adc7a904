"""Experiment sweep driver: the reference's 54-config harness, TPU-native.

Parity targets (SURVEY.md C6-C9):

- ``run_one_experiment`` (notebook cell 19, ``.ipynb:296-335``) — one config,
  one metrics dict. The reference spawns ``num_processes`` fresh interpreters
  rendezvousing over gloo; here a config is one jitted SPMD program over a
  ``num_devices``-wide pipeline mesh, so "launch" is just compile + run.
- ``run_all_experiments`` (cell 20, ``.ipynb:337-394``) — the full cross
  product layers {4,8,12} x heads {4,8,12} x devices {2,4} x schedules
  {GPipe, 1F1B, Interleaved1F1B} = 54 experiments, 5 timed iterations each,
  batch 32, seq 128; per-experiment progress printing; errors logged and
  skipped (same contract: a failed config contributes an ``error`` row and
  the sweep continues).
- ``compute_speedup_and_efficiency`` (cell 21, ``.ipynb:396-435``) —
  ``speedup = throughput / GPipe throughput`` per (layers, heads, devices)
  group; ``efficiency = speedup / devices * 100``.

Additions over the reference (SURVEY.md §5 metrics row): analytic and
simulated pipeline-bubble columns, and tokens/sec/chip.
"""

from __future__ import annotations

import traceback
from typing import Dict, Iterable, Optional, Sequence

import pandas as pd

from .config import ModelConfig, RunConfig, ScheduleConfig, virtual_stages_for
from .metrics import run_train_iterations

SCHEDULES = ("GPipe", "1F1B", "Interleaved1F1B")


def run_one_experiment(n_layers: int, n_heads: int, num_devices: int,
                       schedule_type: str, batch_size: int = 32,
                       seq_length: int = 128, num_iterations: int = 5,
                       dim: int = 768, vocab_size: int = 10000,
                       n_microbatches: int = 4, seed: int = 0,
                       arch: str = "ref_decoder",
                       dtype: str = "float32",
                       remat_backward=None,
                       unroll_ticks=None,
                       report_dir: Optional[str] = None,
                       schedule_artifact: Optional[str] = None,
                       oom_preflight: bool = True,
                       dynamics: bool = False
                       ) -> Dict[str, float]:
    """Run one pipeline experiment; returns the reference's metrics dict plus
    bubble analytics, or ``{"error": ...}`` on failure.

    ``report_dir``: also emit the row as a structured
    :class:`.telemetry.RunReport` manifest — config/mesh/schedule meta,
    the metrics as gauges, timed-loop timers — appended as one JSON line
    to ``{report_dir}/sweep_reports.jsonl`` (validated against the shared
    schema before writing), so sweep rows and ``fit`` runs speak the
    same report format (docs/observability.md).

    Self-describing columns (so the artifact cannot be misread without its
    docs): ``backward_policy`` records which backward the executor compiled
    ('stored', 'remat' or 'split' — ``analysis.cost_model``'s shared
    resolution), ``tick_executor`` which tick-loop formulation
    ('unrolled', 'scan', or 'phases' — the ``unroll_ticks`` resolution),
    ``bubble_sim_w_b`` the matching per-tick backward
    weight the ``bubble_simulated`` column was computed under, and
    ``host_serialized`` whether the mesh was CPU-simulated on a host — where
    every "parallel" tick serializes, wall-clock measures total work plus
    per-tick overhead, and the throughput columns must NOT be read as
    pipeline-overlap measurements (schedule-ordering claims come from the
    bubble/cost-model columns; docs/results.md §2).

    ``schedule_artifact``: path to a certified schedule artifact
    (``scripts/search_schedule.py``). It is registered and re-certified
    on load, and overrides ``schedule_type``/``n_microbatches``/the
    virtual-stage rule with the artifact's own certified config, so a
    searched schedule is a first-class sweep row (the row records the
    pinned table digest in ``schedule_artifact_digest``).

    ``oom_preflight``: price the config with ``analysis.memory_model``
    against the detected chip's HBM capacity BEFORE compiling anything;
    a predicted overflow returns a ``skip_reason="predicted_oom"`` row
    (with the predicted bytes) instead of crashing mid-sweep. Pass
    ``False`` to force the compile anyway.

    ``dynamics``: also run one dynamics-instrumented gradient pass after
    the timed loop (off the clock — the timed throughput columns are
    unaffected) and fill the ``grad_norm_final`` / ``gns`` /
    ``n_skipped_attributed`` model-health columns
    (docs/observability.md §7). Off by default; the columns are present
    either way (None when off) so DataFrames concatenate cleanly."""
    import jax

    from ..models.transformer import transformer_init
    from ..parallel.mesh import make_mesh
    from ..parallel.pipeline import make_pipeline_step
    from ..parallel.schedules import (analytic_bubble_fraction,
                                      compile_schedule, simulated_bubble)

    try:
        artifact_info = None
        if schedule_artifact is not None:
            from ..parallel.schedules import (register_schedule_artifact,
                                              registered_artifact_info)
            cs_art = register_schedule_artifact(schedule_artifact)
            schedule_type = cs_art.name
            n_microbatches = cs_art.n_microbatches
            n_virtual = cs_art.n_virtual
            artifact_info = registered_artifact_info(schedule_type)
        else:
            n_virtual = virtual_stages_for(schedule_type, n_layers,
                                           num_devices)
        if schedule_type == "ZBV":
            # ZBV's steady state needs M >= 2D; lift the reference's fixed 4
            # where required (recorded in the row's n_microbatches column)
            n_microbatches = max(n_microbatches, 2 * num_devices)
        cfg = ModelConfig(dim=dim, n_layers=n_layers, n_heads=n_heads,
                          vocab_size=vocab_size, arch=arch, dtype=dtype)
        sched = ScheduleConfig(name=schedule_type,
                               n_microbatches=n_microbatches,
                               n_virtual=n_virtual)
        cs = compile_schedule(schedule_type, num_devices, n_virtual,
                              n_microbatches)
        # OOM preflight: price the config BEFORE the (expensive, possibly
        # fatal) compile; a predicted overflow becomes a skipped row
        from ..analysis.memory_model import (memory_model_section,
                                             oom_preflight as _preflight)
        mem_section = memory_model_section(
            cs, cfg, batch_size=batch_size, seq_length=seq_length,
            remat_backward=remat_backward)
        if oom_preflight:
            pf = _preflight(mem_section)
            if not pf["ok"]:
                return {
                    "skip_reason": "predicted_oom",
                    "predicted_peak_bytes": pf["predicted_peak_bytes"],
                    "hbm_bytes": pf["hbm_bytes"],
                    "n_virtual": n_virtual,
                    "n_microbatches": n_microbatches,
                }
        mesh = make_mesh(n_pipe=num_devices)
        step = make_pipeline_step(cfg, mesh, sched,
                                  remat_backward=remat_backward,
                                  unroll_ticks=unroll_ticks)

        params = transformer_init(jax.random.key(seed), cfg)
        kx, ky = jax.random.split(jax.random.key(seed + 1))
        tokens = jax.random.randint(kx, (batch_size, seq_length), 0, vocab_size)
        targets = jax.random.randint(ky, (batch_size, seq_length), 0, vocab_size)

        report = None
        if report_dir is not None:
            from .telemetry import RunReport
            report = RunReport(name=f"sweep_L{n_layers}_H{n_heads}_"
                                    f"D{num_devices}_{schedule_type}")
            meta_extra = ({"schedule_artifact": artifact_info}
                          if artifact_info else {})
            report.set_meta(config=cfg, schedule=sched,
                            mesh_shape=dict(mesh.shape),
                            batch_size=batch_size, seq_length=seq_length,
                            backend=jax.devices()[0].platform,
                            **meta_extra)
        metrics = run_train_iterations(step, params, tokens, targets,
                                       num_iterations=num_iterations,
                                       report=report)
        # bubble_simulated uses the weights of the backward the executor
        # actually compiled, mirroring make_pipeline_grad_fn's resolution
        # (shared with the roofline in analysis.cost_model): stored
        # (w_b=2, ~2 fwd-equivalents of grad work) at D==1 by default or
        # on explicit remat_backward=False; otherwise remat (w_b=3: +1
        # recompute). Split-backward schedules always rematerialize:
        # B = recompute + dgrad ~ 2, W = recompute + wgrad ~ 2.
        from ..analysis.cost_model import (backward_weights,
                                           cost_model_section,
                                           resolve_backward_policy)
        policy = resolve_backward_policy(cs, remat_backward, num_devices)
        w_b, w_w = backward_weights(policy)
        sim = simulated_bubble(cs, w_f=1.0, w_b=w_b, w_w=w_w)
        # the full roofline section (predicted vs measured step time,
        # table-exact bubble, MFU) — its headline numbers also land as
        # sweep columns so schedule comparisons stay one-DataFrame reads;
        # fitted calibration corrections (scripts/probe.py) apply when
        # the artifact is present
        from ..analysis.calibration import maybe_load_default_corrections
        corrections = maybe_load_default_corrections()
        cost_model = cost_model_section(
            cs, cfg, batch_size=batch_size, seq_length=seq_length,
            remat_backward=remat_backward,
            measured_step_s=metrics["elapsed_time"] / num_iterations,
            correction=corrections)
        metrics.update({
            "throughput_per_chip": metrics["throughput"] / num_devices,
            "n_virtual": n_virtual,
            "n_microbatches": n_microbatches,
            # first-class predicted-vs-measured columns (the calibration
            # ledger's headline axis; scripts/regress.py extracts them)
            "predicted_step_s": cost_model["predicted"]["step_s"],
            "rel_err": cost_model.get("measured", {}).get("rel_err"),
            "rel_err_corrected": cost_model.get("measured", {}).get(
                "rel_err_corrected"),
            "bubble_analytic": analytic_bubble_fraction(
                schedule_type, num_devices, n_virtual, n_microbatches, cs=cs),
            "bubble_simulated": sim["bubble_fraction"],
            "bubble_sim_w_b": w_b,
            "bubble_table_exact": cost_model["predicted"][
                "bubble_table_exact"],
            "mfu": cost_model.get("measured", {}).get("mfu"),
            "backward_policy": policy,
            # which tick-loop formulation compiled (mirrors the auto
            # resolution in make_pipeline_grad_fn; 'unrolled' also covers
            # the D==1 stored program, which is unrolled by construction)
            "tick_executor": (
                {True: "unrolled", False: "scan", "phases": "phases"}
                [unroll_ticks] if unroll_ticks is not None
                else ("unrolled" if cs.table.shape[0] <= 64 else "phases")),
            "host_serialized": jax.devices()[0].platform == "cpu",
        })
        # model-health columns: present on every row (None when dynamics
        # is off) so sweeps with and without them concatenate cleanly
        dyn_cols: Dict[str, object] = {"grad_norm_final": None, "gns": None,
                                       "n_skipped_attributed": None}
        if dynamics:
            from ..parallel.pipeline import make_pipeline_grad_fn
            from .dynamics import GNSEstimator, stage_stats
            # one instrumented pass off the clock; the tick executor with
            # remat is the configuration the GNS accumulator supports.
            # make_pipeline_grad_fn returns an UNJITTED step: called bare,
            # the unrolled tick program runs op by op through an eager
            # shard_map (minutes on the CPU where the jitted one takes
            # seconds)
            dyn_grad = jax.jit(make_pipeline_grad_fn(
                cfg, mesh, sched, remat_backward=True, unroll_ticks=True,
                dynamics=True))
            _, grads_d, sq_mb = dyn_grad(params, tokens, targets)
            st = stage_stats(cfg.n_layers, num_devices * n_virtual, grads_d)
            dyn_cols["grad_norm_final"] = float(st["grad_norm"])
            dyn_cols["n_skipped_attributed"] = 0  # no guard in a sweep row
            if n_microbatches > 1:
                est = GNSEstimator(
                    batch_small=batch_size * seq_length / n_microbatches,
                    batch_big=batch_size * seq_length)
                est.update(float(sq_mb.mean()),
                           float(st["grad_norm"]) ** 2)
                dyn_cols["gns"] = est.value()
        metrics.update(dyn_cols)
        if artifact_info is not None:
            metrics["schedule_artifact_digest"] = \
                artifact_info["table_digest"]
        if report is not None:
            import json
            import os

            from .telemetry import validate_report
            for k, v in metrics.items():
                report.gauge(k, v)
            report.attach_cost_model(cost_model)
            # the run's own predicted-vs-measured point as a calibration
            # section (docs/observability.md §9)
            from ..analysis.calibration import (
                calibration_section_from_cost_model)
            cal_section = calibration_section_from_cost_model(
                cost_model, backend=jax.devices()[0].platform,
                name=f"sweep_{schedule_type}", correction=corrections)
            if cal_section is not None:
                report.attach_calibration(cal_section)
            # bytes-domain section: the preflight's analytic model plus
            # XLA's own accounting (free — the step is already compiled)
            from ..parallel.pipeline import aot_memory_analysis
            mem_section = memory_model_section(
                cs, cfg, batch_size=batch_size, seq_length=seq_length,
                remat_backward=remat_backward,
                compiled=aot_memory_analysis(step, params, tokens, targets))
            report.attach_memory(mem_section)
            if dynamics and dyn_cols["grad_norm_final"] is not None:
                from .dynamics import dynamics_section
                report.attach_dynamics(dynamics_section(
                    num_devices * n_virtual, last_stats=st,
                    gns=dyn_cols["gns"],
                    gns_updates=0 if dyn_cols["gns"] is None else 1))
            manifest = report.manifest()
            validate_report(manifest)
            os.makedirs(report_dir, exist_ok=True)
            with open(os.path.join(report_dir, "sweep_reports.jsonl"),
                      "a") as fh:
                fh.write(json.dumps(manifest) + "\n")
        return metrics
    except Exception as e:  # same catch-all contract as the reference worker
        traceback.print_exc()
        return {"error": str(e)}


def run_all_experiments(layers: Sequence[int] = (4, 8, 12),
                        heads: Sequence[int] = (4, 8, 12),
                        devices: Sequence[int] = (2, 4),
                        schedules: Sequence[str] = SCHEDULES,
                        batch_size: int = 32, seq_length: int = 128,
                        num_iterations: int = 5,
                        verbose: bool = True,
                        **kwargs) -> pd.DataFrame:
    """The reference's full cross-product sweep -> DataFrame (54 rows by
    default). Failed configs are reported and skipped, not fatal."""
    configs = [(L, H, D, s) for L in layers for H in heads
               for D in devices for s in schedules]
    rows = []
    for k, (L, H, D, s) in enumerate(configs, 1):
        if verbose:
            print(f"[{k}/{len(configs)}] Running: layers={L} heads={H} "
                  f"devices={D} schedule={s}", flush=True)
        result = run_one_experiment(L, H, D, s, batch_size=batch_size,
                                    seq_length=seq_length,
                                    num_iterations=num_iterations, **kwargs)
        if "error" in result:
            if verbose:
                print(f"    ERROR: {result['error']}", flush=True)
            continue
        if "skip_reason" in result:
            # a priced-out config is a row, not a crash: the DataFrame
            # records WHY it was skipped and how far over budget it was
            if verbose:
                print(f"    SKIPPED ({result['skip_reason']}): predicted "
                      f"{result.get('predicted_peak_bytes', 0) / 1e9:.2f} GB "
                      f"> {result.get('hbm_bytes', 0) / 1e9:.2f} GB HBM",
                      flush=True)
            rows.append({
                "n_layers": L, "n_heads": H, "num_processes": D,
                "schedule": s, **result,
            })
            continue
        if verbose:
            print(f"    throughput: {result['throughput']:.2f} tokens/sec",
                  flush=True)
            if result.get("grad_norm_final") is not None:
                gns = result.get("gns")
                print(f"    dynamics: grad_norm "
                      f"{result['grad_norm_final']:.4f}, gns "
                      + (f"{gns:.1f}" if gns is not None else "n/a"),
                      flush=True)
        rows.append({
            "n_layers": L, "n_heads": H, "num_processes": D, "schedule": s,
            **result,
        })
    return pd.DataFrame(rows)


def compute_speedup_and_efficiency(df: pd.DataFrame) -> pd.DataFrame:
    """Per (layers, heads, devices) group: speedup of each schedule over
    GPipe; scaling efficiency = speedup / devices * 100 (the problem-set
    formula, notebook cell 21)."""
    rows = []
    for (L, H, D), g in df.groupby(["n_layers", "n_heads", "num_processes"]):
        gp = g[g["schedule"] == "GPipe"]
        if gp.empty:
            continue
        base = float(gp["throughput"].iloc[0])
        # every non-GPipe schedule present (the reference's two, plus any
        # beyond-parity/custom schedules the sweep was run with)
        for schedule in [s for s in g["schedule"].unique() if s != "GPipe"]:
            row = g[g["schedule"] == schedule]
            speedup = float(row["throughput"].iloc[0]) / base
            rows.append({
                "n_layers": L, "n_heads": H, "num_processes": D,
                "schedule": schedule, "speedup": speedup,
                "efficiency": speedup / D * 100.0,
            })
    return pd.DataFrame(rows)


def summarize_dynamics(df: pd.DataFrame) -> pd.DataFrame:
    """Per-schedule model-health summary over a ``dynamics=True`` sweep:
    median ``grad_norm_final`` / ``gns`` and total attributed skips.
    Rows the dynamics pass did not run for (column absent or None) are
    excluded; an all-None sweep summarizes to an empty frame."""
    empty = pd.DataFrame(
        columns=["schedule", "n", "grad_norm_final_median",
                 "gns_median", "n_skipped_attributed"])
    if "grad_norm_final" not in df.columns:
        return empty
    d = df[df["grad_norm_final"].notna()]
    if d.empty:  # all-None: same schema as the column-absent case
        return empty
    rows = []
    for schedule, g in d.groupby("schedule"):
        gns = g["gns"].dropna() if "gns" in g.columns else []
        skipped = (g["n_skipped_attributed"].dropna().sum()
                   if "n_skipped_attributed" in g.columns else 0)
        rows.append({
            "schedule": schedule,
            "n": len(g),
            "grad_norm_final_median": float(g["grad_norm_final"].median()),
            "gns_median": (float(pd.Series(gns).median())
                           if len(gns) else None),
            "n_skipped_attributed": int(skipped),
        })
    return pd.DataFrame(rows)


def pivot_throughput(df: pd.DataFrame) -> pd.DataFrame:
    """Cell-25-style pivot: throughput by (layers, heads) x (schedule, devices)."""
    return df.pivot_table(index=["n_layers", "n_heads"],
                          columns=["schedule", "num_processes"],
                          values="throughput")
