"""Static analysis passes over the pipeline framework (docs/static_analysis.md).

Three passes plus a CLI (``python -m
distributed_training_with_pipeline_parallelism_tpu.analysis``):

- :mod:`.table_check` — symbolic interpreter over compiled tick tables:
  RAW/WAR/WAW slot hazards with exact (device, tick, column) locations,
  ppermute send/recv pairing per ring direction, route consistency,
  compression roundtrips, unit counts, slot high-water marks (a static
  activation-memory bound), and per-channel comm volume (the unrolled
  executor's predicted ppermute count).
- :mod:`.jaxpr_audit` — walks traced step functions: zero host
  callbacks, collective counts/axes vs the mesh and the table verifier's
  prediction, dtype drift.
- :mod:`.repo_lint` — ast rules: no host calls in tick/scan bodies,
  lazy-export discipline in ``__init__.py``, no bare ``jax.jit`` without
  a named scope in ``parallel/``, no raw host-clock step timing outside
  the sanctioned timing surfaces (``raw-step-timing``).
- :mod:`.cost_model` — analytical roofline accounting over compiled tick
  tables (FLOPs per F/B/W unit, bytes per ring hop, predicted step time
  under a :class:`~.cost_model.HardwareSpec`, table-exact/closed-form
  bubble fractions, MFU/HFU from measured step time) — the predicted
  side of the predicted↔measured comparison (docs/observability.md
  "Cost model & MFU").
- :mod:`.memory_model` — the bytes-domain twin of the cost model:
  per-device HBM priced two ways (analytic slot-peaks x slot-bytes +
  params/optimizer/KV, AOT-compiled ``memory_analysis()``) and
  reconciled; source of the sweep's OOM preflight and the
  byte-denominated search budgets
  (docs/observability.md "Memory observatory").
- :mod:`.calibration` — the measured-probe leg that closes the loop on
  both models: a deterministic micro-probe harness
  (``scripts/probe.py``), the predicted-vs-measured ledger
  (``results/calibration.jsonl``, per-axis signed relative error grouped
  by backend/schedule family/backward policy), and least-squares
  per-hardware correction factors the cost model applies when available
  (docs/observability.md "Calibration observatory").
- :mod:`.schedule_search` — the certifying schedule compiler: seeded,
  deterministic search over per-device action orders whose objective is
  the cost model's predicted step time and whose hard constraints are
  the static proofs above (every emitted artifact is certified
  hazard-free and budget-bounded; docs/static_analysis.md "Schedule
  compiler").

The builders call the table passes at table-build time behind the
``DTPP_VERIFY_TABLES`` env flag (on in tests, off by default in
production runs — the checks are pure numpy but nonzero).
"""

import os
from typing import Optional

VERIFIER_VERSION = 1


def verify_tables_enabled() -> bool:
    """True when ``DTPP_VERIFY_TABLES`` asks for build-time verification."""
    return os.environ.get("DTPP_VERIFY_TABLES", "").lower() not in (
        "", "0", "false", "off", "no")


def maybe_verify_schedule(cs) -> None:
    """Build-time hook (``parallel.pipeline._compile``): verify a compiled
    schedule's table when ``DTPP_VERIFY_TABLES`` is set; raise
    ``ScheduleError`` naming every hazard location otherwise stay silent."""
    if not verify_tables_enabled():
        return
    from ..parallel.schedules import ScheduleError
    from .table_check import check_table
    report = check_table(cs)
    if not report.ok:
        raise ScheduleError(
            f"static table verification failed for {cs.name} "
            f"(D={cs.n_devices}, V={cs.n_virtual}, M={cs.n_microbatches}, "
            f"{cs.placement}): "
            + "; ".join(str(h) for h in report.hazards[:8]))


def maybe_verify_forward_table(table, n_devices: int, n_virtual: int,
                               n_microbatches: int, n_slots: int) -> None:
    """Build-time hook for the forward-only executors
    (``pipeline._fwd_tick_table``)."""
    if not verify_tables_enabled():
        return
    from ..parallel.schedules import ScheduleError
    from .table_check import check_forward_table
    report = check_forward_table(table, n_devices, n_virtual,
                                 n_microbatches, n_slots)
    if not report.ok:
        raise ScheduleError(
            f"static forward-table verification failed "
            f"(D={n_devices}, V={n_virtual}, M={n_microbatches}): "
            + "; ".join(str(h) for h in report.hazards[:8]))


def maybe_verify_serving(n_devices: int, n_slots: int,
                         gamma: Optional[int] = None,
                         prefill_chunk: Optional[int] = None) -> None:
    """Build-time hook for the serving executor's round-robin ring
    (``serving.engine.make_serving_step_fn``). Speculative programs pass
    ``gamma``/``prefill_chunk`` so the widened-metadata checks (verify
    chunk fits the channel, acceptance bounds well-formed) run at build
    time too."""
    if not verify_tables_enabled():
        return
    from .table_check import check_serving_ring
    spec = (dict(gamma=gamma, prefill_chunk=prefill_chunk)
            if gamma is not None else None)
    report = check_serving_ring(n_devices, n_slots, speculative=spec)
    if not report.ok:
        raise ValueError(
            f"serving ring verification failed (D={n_devices}, "
            f"n_slots={n_slots}): "
            + "; ".join(str(h) for h in report.hazards[:8]))


def maybe_verify_page_table(pages, *, refcount, n_pages: int,
                            page_size: int, write_lo: int, write_hi: int,
                            cow_dst: int = -1, slot: int = -1) -> None:
    """Admission-time hook for the paged serving engine
    (``serving.engine.ServingEngine._admit``): verify one slot's planned
    page-table row against the pool's refcounts when
    ``DTPP_VERIFY_TABLES`` is set (in-bounds, refcount-live, no aliased
    or shared-page writes without COW)."""
    if not verify_tables_enabled():
        return
    from .table_check import page_table_hazards
    hazards = page_table_hazards(
        pages, refcount=refcount, n_pages=n_pages, page_size=page_size,
        write_lo=write_lo, write_hi=write_hi, cow_dst=cow_dst, slot=slot)
    if hazards:
        raise ValueError(
            f"page-table discipline verification failed (slot={slot}): "
            + "; ".join(str(h) for h in hazards[:8]))


_LAZY = {
    "Hazard": ("table_check", "Hazard"),
    "TableReport": ("table_check", "TableReport"),
    "check_table": ("table_check", "check_table"),
    "check_table_cached": ("table_check", "check_table_cached"),
    "check_table_baseline": ("table_check", "check_table_baseline"),
    "recheck_after_swap": ("table_check", "recheck_after_swap"),
    "TableCheckBaseline": ("table_check", "TableCheckBaseline"),
    "check_forward_table": ("table_check", "check_forward_table"),
    "check_serving_ring": ("table_check", "check_serving_ring"),
    "check_page_table": ("table_check", "check_page_table"),
    "page_table_hazards": ("table_check", "page_table_hazards"),
    "speculative_hazards": ("table_check", "speculative_hazards"),
    "static_analysis_section": ("table_check", "static_analysis_section"),
    "JaxprAudit": ("jaxpr_audit", "JaxprAudit"),
    "audit_jaxpr": ("jaxpr_audit", "audit_jaxpr"),
    "audit_fn": ("jaxpr_audit", "audit_fn"),
    "LintFinding": ("repo_lint", "LintFinding"),
    "lint_repo": ("repo_lint", "lint_repo"),
    "lint_source": ("repo_lint", "lint_source"),
    "main": ("cli", "main"),
    "run_checks": ("cli", "run_checks"),
    "default_grid": ("cli", "default_grid"),
    "HardwareSpec": ("cost_model", "HardwareSpec"),
    "hardware_spec_for": ("cost_model", "hardware_spec_for"),
    "detect_hardware": ("cost_model", "detect_hardware"),
    "cost_model_section": ("cost_model", "cost_model_section"),
    "serving_cost_model_section": ("cost_model",
                                   "serving_cost_model_section"),
    "expected_tokens_per_verify": ("cost_model",
                                   "expected_tokens_per_verify"),
    "train_flops_per_token": ("cost_model", "train_flops_per_token"),
    "fwd_flops_per_token": ("cost_model", "fwd_flops_per_token"),
    "resolve_backward_policy": ("cost_model", "resolve_backward_policy"),
    "backward_weights": ("cost_model", "backward_weights"),
    "predicted_step_time": ("cost_model", "predicted_step_time"),
    "memory_model_section": ("memory_model", "memory_model_section"),
    "serving_memory_section": ("memory_model", "serving_memory_section"),
    "activation_slot_bytes": ("memory_model", "activation_slot_bytes"),
    "params_bytes": ("memory_model", "params_bytes"),
    "compiled_memory_section": ("memory_model", "compiled_memory_section"),
    "reconcile_memory": ("memory_model", "reconcile_memory"),
    "oom_preflight": ("memory_model", "oom_preflight"),
    "size_page_pool": ("memory_model", "size_page_pool"),
    "kv_page_bytes": ("memory_model", "kv_page_bytes"),
    "kv_slot_bytes": ("memory_model", "kv_slot_bytes"),
    "contiguous_slots_for_budget": ("memory_model",
                                    "contiguous_slots_for_budget"),
    "comm_overlap_step_time": ("cost_model", "comm_overlap_step_time"),
    "memory_probe_axes": ("memory_model", "memory_probe_axes"),
    "CalibrationError": ("calibration", "CalibrationError"),
    "ProbeSpec": ("calibration", "ProbeSpec"),
    "probe_grid": ("calibration", "probe_grid"),
    "run_probe": ("calibration", "run_probe"),
    "reprice_row": ("calibration", "reprice_row"),
    "schedule_family": ("calibration", "schedule_family"),
    "load_ledger": ("calibration", "load_ledger"),
    "append_ledger_rows": ("calibration", "append_ledger_rows"),
    "group_errors": ("calibration", "group_errors"),
    "CorrectionFactors": ("calibration", "CorrectionFactors"),
    "fit_corrections": ("calibration", "fit_corrections"),
    "correction_artifact": ("calibration", "correction_artifact"),
    "load_correction_artifact": ("calibration", "load_correction_artifact"),
    "maybe_load_default_corrections": ("calibration",
                                       "maybe_load_default_corrections"),
    "calibration_section": ("calibration", "calibration_section"),
    "calibration_section_from_cost_model":
        ("calibration", "calibration_section_from_cost_model"),
    "SearchSpec": ("schedule_search", "SearchSpec"),
    "SearchResult": ("schedule_search", "SearchResult"),
    "search_schedule": ("schedule_search", "search_schedule"),
    "seed_orders": ("schedule_search", "seed_orders"),
    "run_search": ("cli", "run_search"),
}


def __getattr__(name):
    if name in _LAZY:
        import importlib
        mod, attr = _LAZY[name]
        value = getattr(importlib.import_module(f".{mod}", __name__), attr)
        globals()[name] = value
        return value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_LAZY))


__all__ = ["VERIFIER_VERSION", "verify_tables_enabled",
           "maybe_verify_schedule", "maybe_verify_forward_table",
           "maybe_verify_serving", "maybe_verify_page_table",
           *sorted(_LAZY)]
