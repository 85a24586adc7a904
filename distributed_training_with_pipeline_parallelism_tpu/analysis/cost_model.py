"""Analytical cost model: roofline accounting over the compiled tick table.

The tick table already *is* the program (docs/schedules.md): every F/B/W
unit and every ring hop a step will execute appears as a cell. This
module prices those cells — FLOPs per unit from the model config, bytes
per hop from the microbatch activation shape — against a
:class:`HardwareSpec` roofline (peak dense FLOP/s + per-link ICI
bandwidth) and produces the *predicted* side of the predicted↔measured
comparison (the measured side is a host-clock step time):

- per-unit FLOPs (F, and B/W under the backward policy the executor
  actually compiles: stored / remat / split — the same resolution
  ``utils.sweep`` records as ``backward_policy``),
- bytes moved per ring hop and total predicted ppermute hops (the
  dead-hop-elided count from :class:`.table_check.TableReport`),
- ideal step time under the roofline (serial and compute/comm-overlapped
  bounds),
- bubble fractions three ways: *table-exact* (idle cells over the
  ``[T, D]`` grid, identical by construction to the static verifier's
  ``unit_counts['idle'] / (T*D)``), *weighted* (per-tick lockstep
  simulation under the backward-policy weights, equal to
  ``schedules.simulated_bubble``), and *closed-form*
  (``schedules.analytic_bubble_fraction``),
- MFU/HFU once a measured step time is supplied (model FLOPs use the
  standard ``6N + attention`` accounting; hardware FLOPs charge the
  recompute the chosen backward policy actually executes).

Everything here is host-side numpy over a handful of ``[T, D, 17]``
tables — no jax execution (``jax.eval_shape`` only, for the parameter
count). The output of :func:`cost_model_section` is a plain dict that
rides the RunReport manifest (``attach_cost_model``; schema enforced by
``utils.telemetry.validate_report``) and feeds the
``scripts/regress.py`` perf-regression sentinel.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ..parallel.schedules import (BANK_BEFORE_F, COL_STORE_B_POS_SLOT,
                                  COL_STORE_B_SLOT,
                                  COL_STORE_F_NEG_SLOT, COL_STORE_F_SLOT,
                                  CompiledSchedule, analytic_bubble_fraction,
                                  overlap_bank_stages, table_unit_activity)

__all__ = [
    "HardwareSpec", "CPU_PROXY", "TPU_PRESETS", "hardware_spec_for",
    "detect_hardware", "fwd_flops_per_token", "train_flops_per_token",
    "resolve_backward_policy", "backward_weights", "dtype_bytes",
    "predicted_step_time", "comm_overlap_step_time",
    "cost_model_section",
    "serving_cost_model_section",
]

# The ring columns a hop can bank into, with the offset the sender sits
# at: a store at (t, d) was ppermuted during tick t-1 by device
# (d - offset) % D. Mirrors table_check.RING_CHANNELS (kept literal here
# so the cost model never imports the verifier just for four constants).
_STORE_CHANNELS = (
    ("fwd_ring_pos", COL_STORE_F_SLOT, +1),
    ("bwd_ring_neg", COL_STORE_B_SLOT, -1),
    ("fwd_ring_neg", COL_STORE_F_NEG_SLOT, -1),
    ("bwd_ring_pos", COL_STORE_B_POS_SLOT, +1),
)

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "float64": 8}


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Roofline parameters for one chip of the pipeline mesh.

    ``peak_flops``: advertised dense bf16 peak per chip.
    ``ici_bytes_per_s``: usable unidirectional bandwidth of the one ICI
    link a ring hop crosses. ``hbm_bytes_per_s``: per-chip HBM bandwidth
    (the second roofline ceiling, reported for context). ``hbm_bytes``:
    per-chip HBM *capacity* — the denominator of
    ``analysis.memory_model``'s OOM preflight and the unit byte-valued
    ``schedule_search`` budgets are quoted in (0.0 = unknown, preflight
    disabled). ``cpu_proxy``: the numbers are order-of-magnitude
    placeholders for a simulated-CPU host — predictions keep their
    *structure* (relative schedule ranking, bubble fractions are
    hardware-free) but absolute seconds are not accelerator claims, and
    downstream consumers (regress.py) treat the run as warn-only."""

    name: str
    peak_flops: float
    ici_bytes_per_s: float
    hbm_bytes_per_s: float
    hbm_bytes: float = 0.0
    cpu_proxy: bool = False

    def summary(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


# Peaks are dense bf16 (v5e is 197 TFLOP/s — not its INT8 TOPS). ICI:
# one link of v4/v5e 3D/2D torus ~45-50 GB/s usable each way; v5p
# ~100 GB/s; v6e ~90 GB/s. HBM: v5e 819 GB/s, v4 1228, v5p 2765,
# v6e 1640.
# Capacity: v5e/v6e 16 GiB-class (16e9), v4 32, v5p 95.
TPU_PRESETS: Dict[str, HardwareSpec] = {
    "v5 lite": HardwareSpec("v5e", 197e12, 5.0e10, 8.19e11, 16e9),
    "v5e": HardwareSpec("v5e", 197e12, 5.0e10, 8.19e11, 16e9),
    "v5p": HardwareSpec("v5p", 459e12, 1.0e11, 2.765e12, 95e9),
    "v4": HardwareSpec("v4", 275e12, 5.0e10, 1.228e12, 32e9),
    "v6": HardwareSpec("v6e", 918e12, 9.0e10, 1.64e12, 32e9),
}

# One host CPU core-ish matmul throughput and loopback "interconnect":
# honest only about orders of magnitude, flagged cpu_proxy=True. The
# 16e9 "HBM" stands in for a host-RAM slice so the memory-model OOM
# preflight stays exercisable (and testable) on the simulated mesh.
CPU_PROXY = HardwareSpec("cpu_proxy", 5e10, 1e9, 5e10, 16e9,
                         cpu_proxy=True)


def hardware_spec_for(device_kind: str) -> HardwareSpec:
    """Map a ``device_kind``/platform string to a preset.

    Substring match over the TPU presets; a caller that asks for the
    CPU gets the labelled :data:`CPU_PROXY`. A device that is not in the
    table is an error, not a default: a roofline against another chip's
    peaks is not a prediction."""
    kind = device_kind.lower()
    for key, spec in TPU_PRESETS.items():
        if key in kind:
            return spec
    if "cpu" in kind:
        return CPU_PROXY
    raise ValueError(f"no hardware preset for device kind {device_kind!r}: "
                     f"add it to TPU_PRESETS with its source")


def detect_hardware() -> HardwareSpec:
    """Spec for the first visible device: :data:`CPU_PROXY` on the CPU
    backend (the test suite, the simulated mesh), the preset of its
    ``device_kind`` otherwise — an unknown kind raises."""
    import jax
    dev = jax.devices()[0]
    return hardware_spec_for(
        "cpu" if dev.platform == "cpu" else dev.device_kind)


def dtype_bytes(dtype: str) -> int:
    return _DTYPE_BYTES.get(dtype, 4)


def fwd_flops_per_token(cfg, seq: int) -> float:
    """Forward FLOPs per token: ``2N + 4*L*dim*seq`` attention term.

    ``N`` counts matmul-participating params only (lookup-only embedding
    tables excluded; a tied table IS the head matmul so it stays in) via
    ``jax.eval_shape`` — no arrays are materialized. Causal attention
    halves the live score matrix; ``ref_decoder`` runs two unmasked
    attentions per layer (self + cross), doubling it instead. This is the
    canonical accounting: :func:`train_flops_per_token` is 3x this."""
    import jax

    from ..models import transformer as tfm
    shapes = jax.eval_shape(
        lambda: tfm.transformer_init(jax.random.key(0), cfg))
    n_params = sum(x.size for x in jax.tree.leaves(shapes))
    if not cfg.tie_embeddings:
        n_params -= shapes["embed"]["tok"].size  # lookup only, zero matmuls
    if "pos" in shapes["embed"]:
        n_params -= shapes["embed"]["pos"].size  # additive lookup
    attn_fwd_per_tok = 2 * 2 * cfg.n_layers * cfg.dim * seq
    attn_fwd_per_tok *= 2 if cfg.arch == "ref_decoder" else 0.5
    return 2.0 * n_params + attn_fwd_per_tok


def train_flops_per_token(cfg, seq: int) -> float:
    """``6N + 12*L*dim*seq``-family model FLOPs per trained token (fwd +
    2x bwd — PaLM appendix B)."""
    return 3.0 * fwd_flops_per_token(cfg, seq)


def resolve_backward_policy(cs: CompiledSchedule, remat_backward=None,
                            n_devices: Optional[int] = None) -> str:
    """Which backward the executor compiles for this schedule.

    Mirrors ``make_pipeline_grad_fn``'s resolution (the rule
    ``utils.sweep`` inlined until this module became the shared home):
    split-backward schedules always rematerialize into separate
    B (recompute + dgrad) and W (recompute + wgrad) units; otherwise
    'stored' at D==1 by default or on explicit ``remat_backward=False``,
    else 'remat'."""
    if cs.split_backward:
        return "split"
    D = cs.n_devices if n_devices is None else n_devices
    stored = remat_backward is False or (remat_backward is None and D == 1)
    return "stored" if stored else "remat"


def backward_weights(policy: str):
    """Per-tick cost of (B, W) units in forward-unit equivalents.

    stored: B = dgrad + wgrad ~ 2F, no W unit. remat: +1F recompute.
    split: B = recompute + dgrad ~ 2F, W = recompute + wgrad ~ 2F."""
    return {"stored": (2.0, 1.0), "remat": (3.0, 1.0),
            "split": (2.0, 2.0)}[policy]


def _hops_per_tick(table: np.ndarray) -> np.ndarray:
    """Live ring hops launched at the end of each tick.

    A store at ``(t, d, channel)`` banks data ppermuted during tick
    ``t-1``, and one ppermute per channel serves every device that tick —
    so hops[t-1] = number of channels with >= 1 store at t. Summed over
    ticks this equals ``TableReport.predicted_ppermutes`` (channels with
    zero cells contribute zero hop ticks)."""
    T = table.shape[0]
    hops = np.zeros(T, dtype=np.int64)
    for t in range(1, T):
        n_live = sum(1 for _, col, _ in _STORE_CHANNELS
                     if (table[t, :, col] >= 0).any())
        hops[t - 1] = n_live
    return hops


def predicted_step_time(table: np.ndarray, unit_s: Tuple[float, float, float],
                        hop_s: float, hops_total: int) -> Dict[str, float]:
    """The exact time model ``cost_model_section`` prices ``predicted``
    with, factored out so the schedule search's objective is *identical*
    to the reported cost: lockstep per-tick max across devices (every
    device waits for the tick's straggler), ring hops serialized after
    compute (``step_s``) or overlapped with the launching tick
    (``step_s_overlapped``). ``unit_s`` is (F, B, W) seconds per unit —
    absolute (unit FLOPs / peak) or abstract forward-unit equivalents;
    the argmin over candidate tables is scale-invariant either way."""
    activity = table_unit_activity(table)          # [T, D, (F,B,W,idle)]
    vec = np.array([unit_s[0], unit_s[1], unit_s[2], 0.0], dtype=np.float64)
    per_dev_tick_s = activity.astype(np.float64) @ vec          # [T, D]
    compute_tick_s = per_dev_tick_s.max(axis=1)                 # [T]
    t_compute_s = float(compute_tick_s.sum())
    t_comm_s = float(hops_total) * hop_s
    hops_per_tick = _hops_per_tick(table)
    idle_cells = int(activity[:, :, 3].sum())
    T, D = int(table.shape[0]), int(table.shape[1])
    return {
        "compute_s": t_compute_s,
        "comm_s": t_comm_s,
        "step_s": t_compute_s + t_comm_s,
        "step_s_overlapped": float(
            np.maximum(compute_tick_s, hops_per_tick * hop_s).sum()),
        "bubble_table_exact": idle_cells / float(T * D),
    }


def comm_overlap_step_time(table: np.ndarray,
                           unit_s: Tuple[float, float, float],
                           hop_s: float,
                           bank_stages: Optional[np.ndarray] = None,
                           correction=None,
                           ) -> Dict[str, float]:
    """Predicted step time under the DOUBLE-BUFFERED executor
    (``comm_overlap="ring"``) — the first-class mode between the lockstep
    ``step_s`` (hops serialized after compute) and the fully optimistic
    ``step_s_overlapped`` lower bound.

    Attribution follows the executor's actual dataflow: a hop launched at
    the end of tick ``u-1`` lands in a recv register and is committed at
    tick ``u``'s bank stage (:func:`..parallel.schedules.
    overlap_bank_stages`, the same classifier the executor banks by). A
    stage-0 bank means the first unit of tick ``u`` consumes the arrival —
    the hop is EXPOSED, serialized exactly as in lockstep. A later stage
    means the hop overlaps tick ``u``'s earlier compute, so the tick costs
    ``max(compute_u, overlappable_comm_u)`` instead of the sum:

        time_u = exposed_hops_u * hop_s
                 + max(compute_u, overlappable_hops_u * hop_s)

    Per tick this is >= ``max(compute_u, all_hops_u * hop_s)`` and
    <= ``compute_u + all_hops_u * hop_s``, so summed it sits within the
    [overlapped, serial] envelope the existing bounds quote (the
    ``overlapped`` bound attributes hops to the LAUNCH tick, so the
    orderings can differ tick-by-tick, but hold summed on real schedule
    tables — ``scripts/check.py --overlap`` asserts the grid-wide
    ``<= step_s`` invariant and the search smoke pins the strict
    sandwich on searched artifacts).

    ``correction``: an ``analysis.calibration.CorrectionFactors`` (or
    any object with ``flops_efficiency``/``bandwidth_efficiency``) — the
    per-hardware efficiency scalars fitted from measured probes; when
    present the inputs are de-rated (``unit_s / e_flops``,
    ``hop_s / e_bw``) before pricing, which preserves the envelope
    ordering (both scalings are positive)."""
    if correction is not None:
        e_f = float(correction.flops_efficiency)
        e_b = float(correction.bandwidth_efficiency)
        unit_s = (unit_s[0] / e_f, unit_s[1] / e_f, unit_s[2] / e_f)
        hop_s = hop_s / e_b
    table = np.asarray(table)
    if bank_stages is None:
        bank_stages = overlap_bank_stages(table)
    activity = table_unit_activity(table)
    vec = np.array([unit_s[0], unit_s[1], unit_s[2], 0.0], dtype=np.float64)
    compute_tick_s = (activity.astype(np.float64) @ vec).max(axis=1)  # [T]
    T = table.shape[0]
    exposed = np.zeros(T, dtype=np.int64)
    deferred = np.zeros(T, dtype=np.int64)
    for u in range(1, T):
        for ci, (_, col, _) in enumerate(_STORE_CHANNELS):
            if (table[u, :, col] >= 0).any():
                if bank_stages[u, ci] == BANK_BEFORE_F:
                    exposed[u] += 1
                else:
                    deferred[u] += 1
    tick_s = exposed * hop_s + np.maximum(compute_tick_s, deferred * hop_s)
    return {
        "step_s_comm_overlap": float(tick_s.sum()),
        "exposed_hops": int(exposed.sum()),
        "overlappable_hops": int(deferred.sum()),
        "exposed_comm_s": float(exposed.sum() * hop_s),
        "hidden_comm_s": float(
            (np.minimum(deferred * hop_s, compute_tick_s)).sum()),
    }


def _resolve_correction(correction, hw_name: str):
    """Accept a CorrectionFactors, a {hardware_name: CorrectionFactors}
    mapping (the :func:`..analysis.calibration.load_correction_artifact`
    shape), or None; return the factors for ``hw_name`` or None."""
    if correction is None:
        return None
    if hasattr(correction, "flops_efficiency"):
        return correction
    if hasattr(correction, "get"):
        return correction.get(hw_name)
    return None


def cost_model_section(cs: CompiledSchedule, cfg, *, batch_size: int,
                       seq_length: int,
                       hardware: Optional[HardwareSpec] = None,
                       remat_backward=None,
                       measured_step_s: Optional[float] = None,
                       table_report=None,
                       comm_overlap: str = "none",
                       correction=None) -> Dict[str, Any]:
    """Price one compiled schedule against a roofline; reconcile with a
    measured run when one is supplied.

    ``measured_step_s``: a host-clock step time (a loop closed with
    ``utils.metrics.force_completion``); adds the ``measured`` block.
    ``table_report``: a precomputed :class:`.table_check.TableReport`;
    verified fresh via ``check_table`` when absent. ``comm_overlap``
    records the ring-hop discipline the run's executor compiled
    ("none"/"ring") — the ``step_s_comm_overlap`` prediction itself is
    always reported (it prices the table, not the run).
    ``correction``: calibration-fitted efficiency scalars (a
    ``CorrectionFactors`` or the per-hardware mapping
    ``analysis.calibration.load_correction_artifact`` returns) — when
    one matches this run's hardware, ``predicted`` additionally carries
    a ``corrected`` block (every step-time variant re-priced under the
    de-rated roofline) and the measured reconciliation reports both
    ``rel_err`` and ``rel_err_corrected``. Returns the plain dict that
    ``RunReport.attach_cost_model`` embeds."""
    table = cs.table
    T, D = int(table.shape[0]), int(table.shape[1])
    hw = hardware if hardware is not None else detect_hardware()
    policy = resolve_backward_policy(cs, remat_backward)
    w_b, w_w = backward_weights(policy)

    # --- FLOPs per unit: one F unit = one microbatch through one stage
    fwd_tok = fwd_flops_per_token(cfg, seq_length)
    tokens_per_step = float(batch_size) * float(seq_length)
    tokens_per_mb = tokens_per_step / cs.n_microbatches
    unit_f = fwd_tok * tokens_per_mb / cs.n_stages
    unit_b, unit_w = w_b * unit_f, w_w * unit_f
    model_per_step = 3.0 * fwd_tok * tokens_per_step

    activity = table_unit_activity(table)          # [T, D, (F,B,W,idle)]
    counts = activity.sum(axis=(0, 1))             # cells per unit kind
    # hardware FLOPs are table-exact: ZB variants elide stage-0 dgrad,
    # remat recomputes — both show up in the cell counts / weights
    hardware_per_step = (float(counts[0]) * unit_f
                         + float(counts[1]) * unit_b
                         + float(counts[2]) * unit_w)

    # --- comm: activation slab one microbatch moves per ring hop
    bytes_per_hop = (tokens_per_mb * cfg.dim * dtype_bytes(cfg.dtype))
    if table_report is None:
        from .table_check import check_table
        table_report = check_table(cs)
    hops_total = int(table_report.predicted_ppermutes)
    hop_s = bytes_per_hop / hw.ici_bytes_per_s

    # --- roofline: lockstep per-tick max across devices, hops serialized
    # or overlapped — the shared time model (predicted_step_time) the
    # schedule search optimizes, so search objective == reported cost
    unit_sec = (unit_f / hw.peak_flops, unit_b / hw.peak_flops,
                unit_w / hw.peak_flops)
    tm = predicted_step_time(table, unit_sec, hop_s, hops_total)
    ov = comm_overlap_step_time(table, unit_sec, hop_s)
    t_compute_s = tm["compute_s"]
    t_comm_s = tm["comm_s"]
    ideal_compute_s = hardware_per_step / (D * hw.peak_flops)
    step_s_overlapped = tm["step_s_overlapped"]

    # --- bubbles three ways (see module docstring)
    bubble_table_exact = tm["bubble_table_exact"]
    bubble_weighted = (1.0 - ideal_compute_s / t_compute_s
                       if t_compute_s > 0 else 0.0)
    bubble_closed_form = float(analytic_bubble_fraction(
        cs.name, D, cs.n_virtual, cs.n_microbatches, cs=cs))

    section: Dict[str, Any] = {
        "schedule": cs.name,
        "n_devices": D,
        "n_virtual": int(cs.n_virtual),
        "n_microbatches": int(cs.n_microbatches),
        "n_ticks": T,
        "batch_size": int(batch_size),
        "seq_length": int(seq_length),
        "backward_policy": policy,
        "comm_overlap": comm_overlap,
        "hardware": hw.summary(),
        "flops": {
            "fwd_per_token": fwd_tok,
            "train_per_token": 3.0 * fwd_tok,
            "unit": {"F": unit_f, "B": unit_b, "W": unit_w},
            "model_per_step": model_per_step,
            "hardware_per_step": hardware_per_step,
        },
        "comm": {
            "bytes_per_hop": float(bytes_per_hop),
            "hops": hops_total,
            "bytes_total": float(bytes_per_hop) * hops_total,
            "exposed_hops": ov["exposed_hops"],
            "overlappable_hops": ov["overlappable_hops"],
        },
        "predicted": {
            "compute_s": t_compute_s,
            "comm_s": t_comm_s,
            "step_s": t_compute_s + t_comm_s,
            "step_s_overlapped": step_s_overlapped,
            "step_s_comm_overlap": ov["step_s_comm_overlap"],
            "exposed_comm_s": ov["exposed_comm_s"],
            "hidden_comm_s": ov["hidden_comm_s"],
            "ideal_compute_s": ideal_compute_s,
            "bubble_table_exact": bubble_table_exact,
            "bubble_weighted": bubble_weighted,
            "bubble_closed_form": bubble_closed_form,
        },
    }

    corr = _resolve_correction(correction, hw.name)
    if corr is not None:
        # re-price every variant under the de-rated roofline; positive
        # scalings preserve the serial/comm_overlap/overlapped envelope
        e_f = float(corr.flops_efficiency)
        unit_sec_c = tuple(u / e_f for u in unit_sec)
        tm_c = predicted_step_time(
            table, unit_sec_c, hop_s / float(corr.bandwidth_efficiency),
            hops_total)
        ov_c = comm_overlap_step_time(table, unit_sec, hop_s,
                                      correction=corr)
        section["predicted"]["corrected"] = {
            "flops_efficiency": e_f,
            "bandwidth_efficiency": float(corr.bandwidth_efficiency),
            "compute_s": tm_c["compute_s"],
            "comm_s": tm_c["comm_s"],
            "step_s": tm_c["step_s"],
            "step_s_overlapped": tm_c["step_s_overlapped"],
            "step_s_comm_overlap": ov_c["step_s_comm_overlap"],
        }

    if measured_step_s is not None and measured_step_s > 0:
        chip_s = measured_step_s * D * hw.peak_flops
        measured: Dict[str, Any] = {
            "step_s": float(measured_step_s),
            "tokens_per_sec": tokens_per_step / measured_step_s,
            "mfu": model_per_step / chip_s,
            "hfu": hardware_per_step / chip_s,
            "predicted_over_measured":
                section["predicted"]["step_s"] / measured_step_s,
            # signed relative error, the calibration ledger's headline
            # axis: negative = the roofline is optimistic
            "rel_err": (section["predicted"]["step_s"] - measured_step_s)
                / measured_step_s,
        }
        corrected = section["predicted"].get("corrected")
        if corrected is not None:
            measured["rel_err_corrected"] = \
                (corrected["step_s"] - measured_step_s) / measured_step_s
        section["measured"] = measured

    return section


def expected_tokens_per_verify(alpha: float, gamma: int) -> float:
    """Expected emitted tokens per verify forward under greedy
    speculative decoding with per-position acceptance rate ``alpha``
    and draft length ``gamma`` (Leviathan et al., arXiv:2211.17192):

        E[tokens] = (1 - alpha^(gamma+1)) / (1 - alpha)

    i.e. the run-length of i.i.d. accepts plus the free token the
    verify forward always yields. Continuous at the endpoints:
    ``gamma + 1`` as ``alpha -> 1`` and ``1`` at ``alpha = 0``."""
    g = int(gamma)
    if g < 0:
        raise ValueError(f"gamma must be >= 0, got {gamma}")
    a = min(max(float(alpha), 0.0), 1.0)
    if a >= 1.0:
        return float(g + 1)
    return (1.0 - a ** (g + 1)) / (1.0 - a)


def serving_cost_model_section(cfg, n_pipe: int, n_slots: int,
                               summary: Dict[str, Any],
                               hardware: Optional[HardwareSpec] = None,
                               draft_cfg=None, correction=None,
                               ) -> Dict[str, Any]:
    """Cost-model section for a serving run (same manifest schema).

    A decode tick moves one token-slot through each stage and rolls the
    ring once; predicted per-tick time is the roofline on one token's
    stage slice plus one hop of a ``dim``-wide activation row. Measured
    MFU uses forward FLOPs only (decoding trains nothing). ``summary``:
    a ``serving_summary`` dict (ticks, wall_s, tokens_out...).

    When ``summary`` carries the speculative gauges
    (``speculative``/``gamma``/``acceptance_rate``) a ``speculative``
    subsection prices the draft-verify tick: target verify FLOPs over
    ``gamma+1`` rows, draft FLOPs (``draft_cfg``, replicated so not
    divided by the pipe degree) for ``gamma`` proposals, expected
    tokens/tick from the measured acceptance rate, and the predicted
    saturation-knee shift — de-rated through ``correction``
    (calibration-fitted efficiency scalars, same contract as
    :func:`cost_model_section`) when available."""
    hw = hardware if hardware is not None else detect_hardware()
    seq = cfg.max_seq_len
    fwd_tok = fwd_flops_per_token(cfg, seq)
    bytes_per_hop = float(cfg.dim * dtype_bytes(cfg.dtype))
    per_tick_compute_s = fwd_tok / n_pipe / hw.peak_flops
    hop_s = bytes_per_hop / hw.ici_bytes_per_s
    ticks = int(summary.get("ticks") or 0)
    wall_s = float(summary.get("wall_s") or 0.0)
    tokens_out = int(summary.get("tokens_out") or 0)
    section: Dict[str, Any] = {
        "schedule": "serving_ring",
        "n_devices": int(n_pipe),
        "n_virtual": 1,
        "n_microbatches": int(n_slots),
        "n_ticks": ticks,
        "batch_size": int(n_slots),
        "seq_length": int(seq),
        "backward_policy": "none",
        "hardware": hw.summary(),
        "flops": {
            "fwd_per_token": fwd_tok,
            "train_per_token": 0.0,
            "unit": {"F": fwd_tok / n_pipe, "B": 0.0, "W": 0.0},
            "model_per_step": fwd_tok,        # per decoded token
            "hardware_per_step": fwd_tok,
        },
        "comm": {
            "bytes_per_hop": bytes_per_hop,
            # the ring rolls every tick regardless of slot occupancy
            "hops": ticks,
            "bytes_total": bytes_per_hop * ticks,
        },
        "predicted": {
            "compute_s": per_tick_compute_s,
            "comm_s": hop_s,
            "step_s": per_tick_compute_s + hop_s,   # per tick
            "step_s_overlapped": max(per_tick_compute_s, hop_s),
            # the serving ring is still lockstep (arrival consumed at the
            # tick top), so its comm_overlap prediction equals serial
            "step_s_comm_overlap": per_tick_compute_s + hop_s,
            "ideal_compute_s": per_tick_compute_s,
            "bubble_table_exact": 0.0,
            "bubble_weighted": 0.0,
            "bubble_closed_form": 0.0,
        },
    }
    if ticks > 0 and wall_s > 0:
        chip_s = wall_s * n_pipe * hw.peak_flops
        section["measured"] = {
            "step_s": wall_s / ticks,                # per tick
            "tokens_per_sec": tokens_out / wall_s,
            "mfu": tokens_out * fwd_tok / chip_s,
            "hfu": tokens_out * fwd_tok / chip_s,
            "predicted_over_measured":
                section["predicted"]["step_s"] / (wall_s / ticks),
        }

    if summary.get("speculative"):
        gamma = int(summary.get("gamma") or 0)
        alpha = summary.get("acceptance_rate")
        exp_tok = expected_tokens_per_verify(
            alpha if alpha is not None else 0.0, gamma)
        draft_tok = (fwd_flops_per_token(draft_cfg, seq)
                     if draft_cfg is not None else 0.0)
        # verify widens the target forward to gamma+1 rows; the draft is
        # replicated (stage 0 runs it for every slot), so its FLOPs are
        # NOT divided by the pipe degree
        verify_s = (gamma + 1) * fwd_tok / n_pipe / hw.peak_flops
        draft_s = gamma * draft_tok / hw.peak_flops
        base_tick_s = per_tick_compute_s + hop_s
        spec_tick_s = verify_s + draft_s + hop_s
        # tokens/s scale = (tokens per tick gain) / (tick cost gain);
        # offered-load capacity is tokens/s-limited at saturation, so
        # the knee is predicted to shift by the same factor
        knee_scale = (exp_tok / (spec_tick_s / base_tick_s)
                      if base_tick_s > 0 else None)
        spec: Dict[str, Any] = {
            "gamma": gamma,
            "acceptance_rate": alpha,
            "expected_tokens_per_tick": exp_tok,
            "draft_flops_per_token": draft_tok,
            "flops_per_tick": {
                "verify": (gamma + 1) * fwd_tok,
                "draft": gamma * draft_tok,
            },
            "predicted": {
                "tick_s": spec_tick_s,
                "s_per_token": spec_tick_s / exp_tok,
                "baseline_s_per_token": base_tick_s,
                "tokens_per_sec_scale": knee_scale,
                "knee_scale": knee_scale,
            },
        }
        corr = _resolve_correction(correction, hw.name)
        if corr is not None:
            e_f = float(corr.flops_efficiency)
            e_b = float(corr.bandwidth_efficiency)
            c_base = per_tick_compute_s / e_f + hop_s / e_b
            c_tick = (verify_s + draft_s) / e_f + hop_s / e_b
            spec["predicted"]["corrected"] = {
                "tick_s": c_tick,
                "s_per_token": c_tick / exp_tok,
                "knee_scale": (exp_tok / (c_tick / c_base)
                               if c_base > 0 else None),
            }
        section["speculative"] = spec
    return section
