"""Analytical HBM model: the bytes-domain twin of :mod:`.cost_model`.

The tick table prices *time* through :func:`.cost_model.cost_model_section`;
this module prices *memory*, two ways, and reconciles them:

1. **analytic** — per-device bytes built from the static verifier's exact
   slot high-water marks (:class:`.table_check.TableReport`'s
   ``act_live_peak`` / ``grad_live_peak``): the tick executors bank one
   stage-boundary activation slab per slot (``[mb, seq, dim]`` in the
   compute dtype — the same slab the cost model prices a ring hop with),
   so per-device activation bytes are *exactly*
   ``live_peak x slot_bytes`` — an integer identity the test-suite and
   ``analysis.cli --memory`` pin over the whole schedule grid. On top
   ride parameters (pipe-sharded layers + replicated embed/head, shapes
   from ``jax.eval_shape`` so dtype mixes are honest), optimizer state,
   the grads output, and — under the 'stored' backward policy
   (:func:`.cost_model.resolve_backward_policy`) — a first-order
   estimate of the per-layer residuals autodiff keeps live per in-flight
   microbatch (remat/split rematerialize and keep none).
2. **compiled** — XLA's own accounting from an AOT
   ``lower().compile().memory_analysis()`` of the jitted step
   (:func:`..parallel.pipeline.aot_memory_analysis` /
   the serving-block analog): argument / output / temp / alias bytes.
   :func:`reconcile_memory` pins analytic parameter+input bytes against
   the compiled argument bytes (documented tolerance: 10% — layout
   padding and donation are XLA's business, wholesale drift is ours).

Both land in the schema-validated ``memory`` RunReport section
(``attach_memory``) that fit/sweep/serving auto-attach, and the
analytic peak against :attr:`.cost_model.HardwareSpec.hbm_bytes` is the
OOM preflight the sweep consults *before* compiling a config
(:func:`oom_preflight`).

Host-side only: ``jax.eval_shape`` for shapes/dtypes, numpy for sums —
no arrays are materialized and no backend is required.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from ..parallel.schedules import CompiledSchedule
from .cost_model import (HardwareSpec, detect_hardware, dtype_bytes,
                         resolve_backward_policy)

__all__ = [
    "activation_slot_bytes", "params_bytes", "stored_residual_bytes",
    "memory_model_section", "serving_memory_section",
    "compiled_memory_section", "reconcile_memory", "oom_preflight",
    "memory_probe_axes",
]


def memory_probe_axes(section: Dict[str, Any]
                      ) -> Dict[str, Optional[float]]:
    """The (predicted, measured) peak-bytes pair a calibration ledger row
    records, extracted from a ``memory_model_section`` dict: analytic
    per-device peak on the predicted side, XLA's compiled ``temp_bytes``
    on the measured side (None when the AOT analysis was unavailable or
    degraded to an error row)."""
    analytic = section.get("analytic") or {}
    compiled = section.get("compiled") or {}
    predicted = analytic.get("peak_bytes")
    measured = (compiled.get("temp_bytes")
                if "error" not in compiled else None)
    return {
        "predicted_peak_bytes":
            None if predicted is None else float(predicted),
        "measured_peak_bytes":
            None if measured is None else float(measured),
    }


def _tree_bytes(shapes) -> int:
    """Total bytes of an ``eval_shape`` pytree, per-leaf dtype-aware."""
    import jax
    return sum(int(x.size) * dtype_bytes(str(x.dtype))
               for x in jax.tree.leaves(shapes))


def activation_slot_bytes(cfg, batch_size: int, seq_length: int,
                          n_microbatches: int) -> int:
    """Bytes one activation/grad slot holds: the stage-boundary slab.

    The tick executors' slot buffers are literally ``[n_slots, mb, seq,
    dim]`` arrays in the compute dtype — one microbatch's boundary
    activation (or its cotangent, same shape) per slot. Shaped via
    ``jax.eval_shape`` on the stage partition so the dtype accounting
    cannot drift from the model config. Equal to the cost model's
    ``bytes_per_hop`` (a ring hop moves exactly one slot's contents)."""
    import jax
    import jax.numpy as jnp
    mb = batch_size // n_microbatches
    slab = jax.eval_shape(
        lambda: jnp.zeros((mb, seq_length, cfg.dim), dtype=cfg.dtype))
    return int(slab.size) * dtype_bytes(str(slab.dtype))


def params_bytes(cfg, n_devices: int) -> Dict[str, float]:
    """Per-device parameter bytes under the pipeline sharding.

    Layer stacks are sharded over the pipe axis (one ``L/D`` slice per
    device); embed and head are replicated onto every device (the
    ``fsdp_shard_params`` contract). Shapes and dtypes come from
    ``jax.eval_shape`` of ``transformer_init`` — storage dtype, tied
    embeddings and per-arch head layouts are all honest."""
    import jax

    from ..models import transformer as tfm
    shapes = jax.eval_shape(
        lambda: tfm.transformer_init(jax.random.key(0), cfg))
    layer_b = _tree_bytes(shapes["layers"])
    embed_b = _tree_bytes(shapes["embed"]) + _tree_bytes(shapes["head"])
    n_params = sum(int(x.size) for x in jax.tree.leaves(shapes))
    return {
        "total_bytes": float(layer_b + embed_b),
        "per_device_bytes": float(layer_b) / n_devices + embed_b,
        "layer_bytes": float(layer_b),
        "replicated_bytes": float(embed_b),
        "n_params": int(n_params),
    }


def stored_residual_bytes(cfg, n_stages: int, tokens_per_mb: float) -> float:
    """First-order per-microbatch residual bytes the 'stored' backward
    keeps live per stage: per layer, the residual-stream input/output
    pair plus the MLP hidden (``2*dim + ffn_dim`` values per token, in
    the compute dtype). Remat/split policies recompute these inside the
    backward and keep none. An estimate, not an identity — XLA's
    ``temp_bytes`` is the ground truth it is reconciled against."""
    layers_per_stage = cfg.n_layers / float(n_stages)
    per_layer = tokens_per_mb * (2 * cfg.dim + cfg.ffn_dim)
    return layers_per_stage * per_layer * dtype_bytes(cfg.dtype)


def compiled_memory_section(stats: Optional[Dict[str, Any]]
                            ) -> Optional[Dict[str, Any]]:
    """Normalize an :func:`..parallel.pipeline.aot_memory_analysis`
    result into the manifest's ``compiled`` subsection (pass-through for
    ``{"error": ...}`` degradation rows)."""
    if not stats:
        return None
    if "error" in stats:
        return {"error": str(stats["error"])}
    out = {k: float(stats[k]) for k in
           ("argument_bytes", "output_bytes", "temp_bytes", "alias_bytes",
            "generated_code_bytes") if k in stats}
    out["total_bytes"] = (out.get("argument_bytes", 0.0)
                          + out.get("output_bytes", 0.0)
                          + out.get("temp_bytes", 0.0)
                          - out.get("alias_bytes", 0.0))
    return out


def reconcile_memory(analytic: Dict[str, Any],
                     compiled: Optional[Dict[str, Any]],
                     tolerance: float = 0.10) -> Optional[Dict[str, Any]]:
    """Pin analytic vs compiled where both account the same thing.

    XLA's ``argument_bytes`` is the program's *per-shard* input
    footprint: each device's slice of the parameter tree (layers/D under
    the pipe sharding) plus the replicated token/target (or
    serving-state) operands — the analytic
    ``params_per_device_bytes + input_bytes``. On an unpadded layout the
    two agree to the integer (the CPU-mesh test pins this); layout
    padding gives XLA a few percent of slack on real chips, so ``ok``
    flags relative error within ``tolerance`` (documented at 10%).
    ``temp_bytes`` is reported alongside the analytic activation peak
    for reading, not pinned — XLA fuses/rematerializes inside a tick at
    will."""
    if not compiled or "error" in compiled:
        return None
    expected = float(analytic.get("params_per_device_bytes", 0.0)
                     + analytic.get("input_bytes", 0.0))
    got = float(compiled.get("argument_bytes", 0.0))
    rel = abs(got - expected) / expected if expected > 0 else 0.0
    return {
        "expected_argument_bytes": expected,
        "compiled_argument_bytes": got,
        "argument_rel_err": rel,
        "tolerance": float(tolerance),
        "ok": bool(rel <= tolerance),
        "compiled_temp_bytes": float(compiled.get("temp_bytes", 0.0)),
        "analytic_activation_peak_bytes": float(
            analytic.get("activation_peak_bytes", 0.0)),
    }


def memory_model_section(cs: CompiledSchedule, cfg, *, batch_size: int,
                         seq_length: int,
                         hardware: Optional[HardwareSpec] = None,
                         remat_backward=None,
                         optimizer_slots: int = 0,
                         table_report=None,
                         compiled: Optional[Dict[str, Any]] = None
                         ) -> Dict[str, Any]:
    """Price one compiled schedule's per-device HBM; reconcile with the
    compiled accounting when supplied.

    ``optimizer_slots``: fp32 moment buffers per parameter the training
    loop keeps (2 for the ``fit`` AdamW path; 0 for the bare
    loss-and-grads step the sweep times). ``table_report``: precomputed
    :class:`.table_check.TableReport` (verified fresh when absent) —
    the source of the exact slot live peaks. ``compiled``: an
    ``aot_memory_analysis`` dict.
    Returns the plain dict ``RunReport.attach_memory`` embeds."""
    D = int(cs.table.shape[1])
    hw = hardware if hardware is not None else detect_hardware()
    policy = resolve_backward_policy(cs, remat_backward)
    if table_report is None:
        from .table_check import check_table
        table_report = check_table(cs)

    slot_b = activation_slot_bytes(cfg, batch_size, seq_length,
                                   cs.n_microbatches)
    tokens_per_mb = (batch_size // cs.n_microbatches) * seq_length
    stored_mb_b = (stored_residual_bytes(cfg, cs.n_stages, tokens_per_mb)
                   if policy == "stored" else 0.0)
    pb = params_bytes(cfg, D)
    # sweep and fit steps both return a grads pytree shaped like params;
    # optimizer moments are fp32 regardless of the storage dtype
    grads_dev_b = pb["per_device_bytes"]
    opt_dev_b = optimizer_slots * pb["n_params"] * 4.0 / D \
        if optimizer_slots else 0.0
    # int32 tokens + targets, replicated onto the mesh
    input_b = 2.0 * batch_size * seq_length * 4.0

    act_peaks = [int(p) for p in table_report.act_live_peak]
    grad_peaks = [int(p) for p in table_report.grad_live_peak]
    per_device = []
    for d in range(D):
        act_b = act_peaks[d] * slot_b          # the integer identity
        grad_b = grad_peaks[d] * slot_b
        stored_b = act_peaks[d] * stored_mb_b  # residuals per in-flight mb
        total = (act_b + grad_b + stored_b + pb["per_device_bytes"]
                 + grads_dev_b + opt_dev_b)
        per_device.append({
            "device": d,
            "act_live_peak": act_peaks[d],
            "grad_live_peak": grad_peaks[d],
            "act_bytes": int(act_b),
            "grad_bytes": int(grad_b),
            "stored_residual_bytes": float(stored_b),
            "params_bytes": pb["per_device_bytes"],
            "grads_bytes": grads_dev_b,
            "opt_state_bytes": opt_dev_b,
            "total_bytes": float(total),
        })
    peak = max(pd["total_bytes"] for pd in per_device)
    analytic: Dict[str, Any] = {
        "act_slot_bytes": int(slot_b),
        "grad_slot_bytes": int(slot_b),
        "stored_residual_bytes_per_mb": float(stored_mb_b),
        "params_total_bytes": pb["total_bytes"],
        "params_per_device_bytes": pb["per_device_bytes"],
        "n_params": pb["n_params"],
        "optimizer_slots": int(optimizer_slots),
        "input_bytes": input_b,
        "activation_peak_bytes": float(
            max(a["act_bytes"] + a["grad_bytes"] for a in per_device)),
        "per_device": per_device,
        "peak_bytes": float(peak),
    }
    if hw.hbm_bytes:
        analytic["hbm_frac"] = peak / hw.hbm_bytes

    section: Dict[str, Any] = {
        "schedule": cs.name,
        "n_devices": D,
        "n_virtual": int(cs.n_virtual),
        "n_microbatches": int(cs.n_microbatches),
        "batch_size": int(batch_size),
        "seq_length": int(seq_length),
        "dtype": str(cfg.dtype),
        "param_dtype": str(cfg.storage_dtype),
        "backward_policy": policy,
        "hardware": hw.summary(),
        "analytic": analytic,
    }
    comp = compiled_memory_section(compiled)
    if comp is not None:
        section["compiled"] = comp
        rec = reconcile_memory(analytic, comp)
        if rec is not None:
            section["reconciliation"] = rec
    return section


def kv_page_bytes(cfg, *, n_devices: int, page_size: int) -> float:
    """Bytes one K+V page pair costs per device (the paged pool's unit
    price): ``2 x layers/D x page_size x n_kv x head_dim x dtype``."""
    lps = cfg.n_layers // n_devices
    n_kv = cfg.n_kv_heads or cfg.n_heads
    return (2.0 * lps * page_size * n_kv * cfg.head_dim
            * dtype_bytes(cfg.dtype))


def kv_slot_bytes(cfg, *, n_devices: int, mlen_alloc: int) -> float:
    """Bytes one contiguous slot's K+V cache costs per device — what
    every slot reserves up front in non-paged serving."""
    lps = cfg.n_layers // n_devices
    n_kv = cfg.n_kv_heads or cfg.n_heads
    return (2.0 * lps * mlen_alloc * n_kv * cfg.head_dim
            * dtype_bytes(cfg.dtype))


def size_page_pool(cfg, *, n_devices: int, page_size: int,
                   budget_bytes: float) -> int:
    """Largest ``n_pages`` (null page 0 included) whose per-device pool
    fits ``budget_bytes`` — the ROADMAP's "oom_preflight bounds
    page-pool sizing" knob. Returns 0 when not even two pages fit (a
    pool needs the null page plus one usable page)."""
    pg_b = kv_page_bytes(cfg, n_devices=n_devices, page_size=page_size)
    n = int(budget_bytes // pg_b)
    return n if n >= 2 else 0


def contiguous_slots_for_budget(cfg, *, n_devices: int, mlen_alloc: int,
                                budget_bytes: float) -> int:
    """How many worst-case contiguous slots the same budget buys — the
    paged-vs-contiguous comparison's matched-budget twin of
    :func:`size_page_pool`."""
    slot_b = kv_slot_bytes(cfg, n_devices=n_devices, mlen_alloc=mlen_alloc)
    return int(budget_bytes // slot_b)


def serving_memory_section(cfg, program, *,
                           hardware: Optional[HardwareSpec] = None,
                           compiled: Optional[Dict[str, Any]] = None,
                           prefix_stats: Optional[Dict[str, Any]] = None
                           ) -> Dict[str, Any]:
    """Memory section for a serving run (same manifest schema).

    Activation state is the ``[D, 1, C, dim]`` ring payload — one slab
    per device, priced as one ``act`` slot of ``C`` tokens. The dominant
    term is the KV cache: contiguous mode prices ``2 x layers/D x
    n_slots x mlen_alloc x n_kv_heads x head_dim`` per device; paged
    mode (``program.paged``) prices the pool ``n_pages x page_size``
    rows instead plus the int32 page table, sized from the same
    expressions ``ServingProgram.init_state`` allocates with.

    ``prefix_stats`` (paged runs; e.g. ``{"hit_rate": h,
    "mean_prompt_len": p, "mean_budget": b}`` from a workload or a
    measured run) adds the expected *demand* discount from prefix
    sharing: a fraction ``h`` of prompt rows is served from shared
    pages, so per-request page demand shrinks by ``h * p / (p + b)`` —
    the pool does not get smaller, it admits proportionally more
    requests."""
    hw = hardware if hardware is not None else detect_hardware()
    D = int(program.n_stages)
    M = int(program.n_slots)
    C = int(program.prefill_chunk)
    lps = cfg.n_layers // D
    n_kv = cfg.n_kv_heads or cfg.n_heads
    dt_b = dtype_bytes(cfg.dtype)
    paged = bool(getattr(program, "paged", False))
    paged_info: Optional[Dict[str, Any]] = None
    if paged:
        pg_b = kv_page_bytes(cfg, n_devices=D, page_size=program.page_size)
        kv_dev_b = program.n_pages * pg_b
        # int32 table + COW command pair, replicated on every device
        tbl_b = 4.0 * M * (program.max_pages_per_slot + 2)
        kv_dev_b += tbl_b
        paged_info = {
            "page_size": int(program.page_size),
            "n_pages": int(program.n_pages),
            "max_pages_per_slot": int(program.max_pages_per_slot),
            "page_bytes_per_device": float(pg_b),
            "pool_bytes_per_device": float(program.n_pages * pg_b),
            "page_table_bytes_per_device": float(tbl_b),
            # what the same bytes would have bought as contiguous slots
            "contiguous_slot_bytes": float(kv_slot_bytes(
                cfg, n_devices=D, mlen_alloc=program.mlen_alloc)),
        }
        if prefix_stats:
            h = float(prefix_stats.get("hit_rate", 0.0))
            p_len = float(prefix_stats.get("mean_prompt_len", 0.0))
            b_len = float(prefix_stats.get("mean_budget", 0.0))
            disc = (h * p_len / (p_len + b_len)
                    if (p_len + b_len) > 0 else 0.0)
            paged_info["expected_sharing_discount"] = round(disc, 6)
            paged_info["effective_capacity_factor"] = (
                round(1.0 / (1.0 - disc), 6) if disc < 1.0 else None)
    else:
        kv_dev_b = (2.0 * lps * M * program.mlen_alloc * n_kv
                    * cfg.head_dim * dt_b)
    slot_b = C * cfg.dim * dt_b
    pb = params_bytes(cfg, D)
    per_device = []
    for d in range(D):
        total = slot_b + kv_dev_b + pb["per_device_bytes"]
        per_device.append({
            "device": d, "act_live_peak": 1, "grad_live_peak": 0,
            "act_bytes": int(slot_b), "grad_bytes": 0,
            "kv_cache_bytes": float(kv_dev_b),
            "params_bytes": pb["per_device_bytes"],
            "opt_state_bytes": 0.0,
            "total_bytes": float(total),
        })
    peak = max(pd["total_bytes"] for pd in per_device)
    analytic: Dict[str, Any] = {
        "act_slot_bytes": int(slot_b),
        "grad_slot_bytes": 0,
        "kv_cache_bytes_per_device": float(kv_dev_b),
        "params_total_bytes": pb["total_bytes"],
        "params_per_device_bytes": pb["per_device_bytes"],
        "n_params": pb["n_params"],
        "optimizer_slots": 0,
        # the serving step takes the state pytree as an operand; the
        # per-device KV slice dominates it, so that is what the
        # (per-shard) argument accounting sees
        "input_bytes": float(kv_dev_b),
        "activation_peak_bytes": float(slot_b),
        "per_device": per_device,
        "peak_bytes": float(peak),
    }
    if hw.hbm_bytes:
        analytic["hbm_frac"] = peak / hw.hbm_bytes
    if paged_info is not None:
        analytic["paged"] = paged_info
    section: Dict[str, Any] = {
        "schedule": "serving_ring",
        "n_devices": D,
        "n_virtual": 1,
        "n_microbatches": M,
        "batch_size": M,
        "seq_length": int(program.max_len),
        "dtype": str(cfg.dtype),
        "param_dtype": str(cfg.storage_dtype),
        "backward_policy": "none",
        "hardware": hw.summary(),
        "analytic": analytic,
    }
    comp = compiled_memory_section(compiled)
    if comp is not None:
        section["compiled"] = comp
        # serving-state aliasing/donation makes the argument pin too
        # loose to assert; report the raw numbers without a verdict
        section["reconciliation"] = {
            "expected_argument_bytes": analytic["params_per_device_bytes"]
            + analytic["input_bytes"],
            "compiled_argument_bytes": comp.get("argument_bytes", 0.0),
        }
    return section


def oom_preflight(section: Dict[str, Any],
                  hardware: Optional[HardwareSpec] = None,
                  headroom: float = 1.0) -> Dict[str, Any]:
    """Price a memory section against the chip's HBM capacity.

    ``ok=False`` means the analytic per-device peak exceeds
    ``headroom x HardwareSpec.hbm_bytes`` — the sweep's preflight
    then emits a ``skip_reason="predicted_oom"`` row *before* compiling.
    Unknown capacity (``hbm_bytes == 0``) always passes."""
    hw = hardware if hardware is not None else detect_hardware()
    peak = float(section["analytic"]["peak_bytes"])
    cap = float(hw.hbm_bytes) * headroom
    return {
        "ok": bool(cap <= 0 or peak <= cap),
        "predicted_peak_bytes": peak,
        "hbm_bytes": float(hw.hbm_bytes),
        "headroom": float(headroom),
        "hbm_frac": peak / cap if cap > 0 else None,
    }
