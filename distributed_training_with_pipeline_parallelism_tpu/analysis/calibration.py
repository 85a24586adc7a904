"""Calibration observatory: measured micro-probes vs. the analytical models.

``analysis.cost_model`` and ``analysis.memory_model`` *predict*; a timed
loop and the compiler's memory accounting *measure*. Nothing in between
tracked the error —
``results/history.jsonl`` accumulates points but nobody computes, groups
or guards the model residual, and the ROADMAP's auto-planner search
("validated by measured probes") needs exactly that layer. This module
closes the loop:

- **Probes**: :func:`run_probe` executes one short measured run (a few
  warm steps of a tiny model on the live mesh) for one
  :class:`ProbeSpec` — schedule family x microbatch count x backward
  policy x comm_overlap mode — and records the measured step time and
  the compiled peak HBM side-by-side
  with every prediction variant the models quote (lockstep serial,
  optimistically overlapped, double-buffered comm_overlap, table-exact
  bubble, analytic peak bytes). :func:`probe_grid` builds the seeded
  deterministic grid ``scripts/probe.py`` sweeps.
- **Ledger**: probe rows append to ``results/calibration.jsonl`` — one
  canonical (sorted-key) JSON line per probe, validated on write *and*
  on read (:func:`validate_ledger_row`; malformed lines are counted and
  surfaced, never silently dropped). Signed relative error is computed
  per axis and grouped by (backend, schedule family, backward policy)
  so "where can the model be trusted" is a one-dict read
  (:func:`group_errors`).
- **Corrections**: :func:`fit_corrections` least-squares fits
  per-:class:`~.cost_model.HardwareSpec` efficiency scalars — an
  effective-FLOPs factor and an effective-bandwidth factor — from the
  ledger (deterministic float64 normal equations over sorted rows), and
  persists them as a versioned, fingerprinted artifact exactly like the
  schedule artifacts of ``parallel.schedules``
  (:func:`correction_artifact` / :func:`load_correction_artifact`).
  ``cost_model_section(..., correction=...)`` applies them, so predicted
  step time carries both raw and corrected values and
  ``scripts/regress.py`` can guard the corrected error.

Everything except :func:`run_probe` is host-side stdlib+numpy — no jax
at import, so the ledger/fit/artifact layer works in any analysis
context (CI, notebooks, the regression sentinel).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import re
from typing import Any, Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "CALIBRATION_SCHEMA_VERSION", "LEDGER_KIND",
    "CORRECTION_ARTIFACT_VERSION", "CORRECTION_ARTIFACT_KIND",
    "DEFAULT_LEDGER_PATH", "DEFAULT_CORRECTIONS_PATH", "CORRECTIONS_ENV",
    "CalibrationError", "ProbeSpec", "probe_grid", "schedule_family",
    "signed_rel_err", "validate_ledger_row", "canonical_row_line",
    "deterministic_fields", "append_ledger_rows", "load_ledger",
    "group_errors", "CorrectionFactors", "fit_correction", "fit_corrections",
    "correction_artifact", "correction_artifact_bytes",
    "save_correction_artifact", "load_correction_artifact",
    "maybe_load_default_corrections", "row_from_cost_model",
    "backfill_row_from_history",
    "run_probe", "reprice_row", "calibration_section",
    "calibration_section_from_cost_model",
]

CALIBRATION_SCHEMA_VERSION = 1
LEDGER_KIND = "calibration_probe"
CORRECTION_ARTIFACT_VERSION = 1
CORRECTION_ARTIFACT_KIND = "calibration_correction"
DEFAULT_LEDGER_PATH = os.path.join("results", "calibration.jsonl")
DEFAULT_CORRECTIONS_PATH = os.path.join("results",
                                        "calibration_corrections.json")
CORRECTIONS_ENV = "DTPP_CALIBRATION_CORRECTIONS"

# Fitted efficiencies are clamped into a physically readable band: a
# scalar below the floor means the probe measured pure overhead (the
# fit is still recorded — the floor only stops a zero/negative divide),
# above 1.0 means the model *under*-prices work; 10x is a generous cap
# for model error before the fit itself should be distrusted.
EFFICIENCY_CLAMP = (1e-6, 10.0)


class CalibrationError(ValueError):
    """Located validation failure in a ledger row or correction artifact."""


# ---------------------------------------------------------------------------
# Probe specs and grids
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ProbeSpec:
    """One point of the probe grid.

    ``remat_backward`` is the executor's tri-state knob (None = auto →
    'remat' at D>1, False = force 'stored', True = force 'remat');
    split-backward schedules (ZBH1/ZBV) resolve to 'split' regardless.
    ``comm_overlap`` is the ring-hop discipline ("none"/"ring"); the
    double-buffered executor requires the unrolled tick loop, which
    :func:`run_probe` selects automatically."""

    schedule: str
    n_devices: int = 2
    n_virtual: int = 1
    n_microbatches: int = 4
    remat_backward: Optional[bool] = None
    comm_overlap: str = "none"

    @property
    def label(self) -> str:
        return (f"{self.schedule}[D={self.n_devices},V={self.n_virtual},"
                f"M={self.n_microbatches}]"
                f"/{_policy_of(self.schedule, self.remat_backward, self.n_devices)}"
                f"/{self.comm_overlap}")

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def _policy_of(schedule: str, remat_backward, n_devices: int) -> str:
    if schedule in ("ZBH1", "ZBV"):
        return "split"
    stored = remat_backward is False or (remat_backward is None
                                         and n_devices == 1)
    return "stored" if stored else "remat"


# The smoke grid: >= 8 configs spanning GPipe/1F1B/Interleaved x
# stored/remat/split x overlap on/off. A 2-device mesh keeps the probes
# micro (the unrolled tick loop's compile time scales with T; a probe
# measures model error, not scale). 'stored' at D>1 pairs only with
# comm_overlap="none": the double-buffered executor rejects the
# stored-residual program (docs/performance.md), and the probe harness
# honors the same constraint rather than papering over it.
_SMOKE_GRID: Tuple[ProbeSpec, ...] = (
    ProbeSpec("GPipe", n_microbatches=2, remat_backward=False),
    ProbeSpec("GPipe", n_microbatches=4, remat_backward=True),
    ProbeSpec("GPipe", n_microbatches=2, remat_backward=True,
              comm_overlap="ring"),
    ProbeSpec("1F1B", n_microbatches=2, remat_backward=False),
    ProbeSpec("1F1B", n_microbatches=2, remat_backward=True,
              comm_overlap="ring"),
    ProbeSpec("Interleaved1F1B", n_virtual=2, n_microbatches=4,
              remat_backward=True),
    ProbeSpec("Interleaved1F1B", n_virtual=2, n_microbatches=2,
              remat_backward=True, comm_overlap="ring"),
    ProbeSpec("ZBH1", n_microbatches=4),
    ProbeSpec("ZBH1", n_microbatches=2, comm_overlap="ring"),
)

_GRIDS: Dict[str, Tuple[ProbeSpec, ...]] = {"smoke": _SMOKE_GRID}


def probe_grid(name: str = "smoke", seed: int = 0) -> List[ProbeSpec]:
    """The named grid in a seeded deterministic order.

    The permutation decorrelates probe order from grid-definition order
    (so steady-state host effects — page cache, turbo — don't bias one
    schedule family), while same seed → same order → byte-identical
    ledger rows modulo measured fields (the determinism contract
    ``tests/test_calibration.py`` pins)."""
    try:
        grid = _GRIDS[name]
    except KeyError:
        raise CalibrationError(
            f"unknown probe grid {name!r}; available: {sorted(_GRIDS)}")
    perm = np.random.default_rng(seed).permutation(len(grid))
    return [grid[int(i)] for i in perm]


_FAMILIES = (
    (re.compile(r"^GPipe"), "GPipe"),
    (re.compile(r"^1F1B"), "1F1B"),
    (re.compile(r"^Interleaved"), "Interleaved"),
    (re.compile(r"^BFS"), "BFS"),
    (re.compile(r"^ZB"), "ZB"),
    (re.compile(r"^Searched"), "searched"),
)


def schedule_family(name: str) -> str:
    """Coarse family key for error grouping ("other" when unrecognized)."""
    for pat, fam in _FAMILIES:
        if pat.match(name or ""):
            return fam
    return "other"


# ---------------------------------------------------------------------------
# Ledger rows
# ---------------------------------------------------------------------------


def signed_rel_err(predicted, measured) -> Optional[float]:
    """(predicted - measured) / measured; None when either side is
    missing or the measurement is non-positive. Negative = the model
    under-predicts (optimistic), positive = over-predicts."""
    if predicted is None or measured is None:
        return None
    measured = float(measured)
    if measured <= 0.0 or not np.isfinite(measured):
        return None
    return (float(predicted) - measured) / measured


def _rel_err_block(predicted: Optional[Dict[str, Any]],
                   measured: Optional[Dict[str, Any]]) -> Optional[Dict[str, Any]]:
    """Per-axis signed error between matching keys of the two blocks."""
    if not predicted or not measured:
        return None
    out: Dict[str, Any] = {}
    for axis in ("step_s", "step_s_overlapped", "step_s_comm_overlap",
                 "peak_bytes"):
        m_axis = "step_s" if axis.startswith("step_s") else axis
        err = signed_rel_err(predicted.get(axis), measured.get(m_axis))
        if err is not None:
            out[axis] = err
    return out or None


# required key -> allowed types. "predicted"/"measured"/"rel_err"/
# "corrected" are dict-or-None; a missing *required* key or a wrong type
# is a hard CalibrationError so a truncated write can't masquerade as
# a probe.
_ROW_SCHEMA: Tuple[Tuple[str, tuple], ...] = (
    ("schema_version", (int,)),
    ("kind", (str,)),
    ("source", (str,)),
    ("t", (int, float)),
    ("name", (str,)),
    ("backend", (str,)),
    ("hardware", (str,)),
    ("cpu_proxy", (bool,)),
    ("schedule", (str,)),
    ("schedule_family", (str,)),
    ("backward_policy", (str,)),
    ("comm_overlap", (str,)),
    ("n_devices", (int,)),
    ("n_virtual", (int,)),
    ("n_microbatches", (int,)),
    ("batch_size", (int,)),
    ("seq_length", (int,)),
    ("predicted", (dict, type(None))),
    ("measured", (dict, type(None))),
    ("rel_err", (dict, type(None))),
    ("corrected", (dict, type(None))),
)

# Fields excluded from the determinism contract: everything measured
# (and everything derived from a measurement) plus the wall-clock stamp.
_MEASURED_FIELDS = ("t", "measured", "rel_err", "corrected")


def validate_ledger_row(row: Any, where: str = "row") -> Dict[str, Any]:
    """Schema-check one ledger row; returns it. Raises
    :class:`CalibrationError` naming the offending field."""
    if not isinstance(row, dict):
        raise CalibrationError(f"{where}: not a JSON object "
                               f"({type(row).__name__})")
    for key, types in _ROW_SCHEMA:
        if key not in row:
            raise CalibrationError(f"{where}: missing required field {key!r}")
        if not isinstance(row[key], types):
            raise CalibrationError(
                f"{where}: field {key!r} has type "
                f"{type(row[key]).__name__}, expected "
                f"{'/'.join(t.__name__ for t in types)}")
    if row["schema_version"] != CALIBRATION_SCHEMA_VERSION:
        raise CalibrationError(
            f"{where}: schema_version {row['schema_version']} != "
            f"{CALIBRATION_SCHEMA_VERSION}")
    if row["kind"] != LEDGER_KIND:
        raise CalibrationError(f"{where}: kind {row['kind']!r} != "
                               f"{LEDGER_KIND!r}")
    pred = row["predicted"]
    if pred is not None and "step_s" not in pred:
        raise CalibrationError(f"{where}: predicted block has no step_s")
    meas = row["measured"]
    if meas is not None and "step_s" not in meas:
        raise CalibrationError(f"{where}: measured block has no step_s")
    return row


def canonical_row_line(row: Dict[str, Any]) -> str:
    """The canonical (byte-deterministic) one-line encoding the ledger
    stores: sorted keys, minimal separators, no trailing spaces."""
    return json.dumps(row, sort_keys=True, separators=(",", ":"))


def deterministic_fields(row: Dict[str, Any]) -> Dict[str, Any]:
    """The row minus its measured-side fields — the part the determinism
    test requires to be byte-identical across same-seed probe runs."""
    return {k: v for k, v in row.items() if k not in _MEASURED_FIELDS}


def append_ledger_rows(path: str, rows: Iterable[Dict[str, Any]]) -> int:
    """Validate and append rows to the ledger; returns the count."""
    rows = [validate_ledger_row(r, f"append[{i}]")
            for i, r in enumerate(rows)]
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    with open(path, "a", encoding="utf-8") as fh:
        for row in rows:
            fh.write(canonical_row_line(row) + "\n")
    return len(rows)


def load_ledger(path: str, strict: bool = False
                ) -> Tuple[List[Dict[str, Any]], List[str]]:
    """Read the ledger: (valid rows, malformed-line descriptions).

    Malformed lines — bad JSON or schema violations — are never silently
    dropped: each contributes a located description (``strict=True``
    raises on the first instead). A missing file is an empty ledger."""
    rows: List[Dict[str, Any]] = []
    bad: List[str] = []
    if not os.path.exists(path):
        return rows, bad
    with open(path, "r", encoding="utf-8") as fh:
        for lineno, line in enumerate(fh, start=1):
            line = line.strip()
            if not line:
                continue
            where = f"{path}:{lineno}"
            try:
                rows.append(validate_ledger_row(json.loads(line), where))
            except (json.JSONDecodeError, CalibrationError) as e:
                if strict:
                    raise CalibrationError(f"{where}: {e}") from e
                bad.append(f"{where}: {e}")
    return rows, bad


def group_errors(rows: Sequence[Dict[str, Any]]) -> Dict[str, Dict[str, Any]]:
    """Signed step-time error grouped by backend|family|policy.

    Rows without both a prediction and a measurement contribute to the
    group's ``n`` (the ledger's coverage is part of the answer) but not
    to its medians."""
    groups: Dict[str, List[Optional[float]]] = {}
    for row in rows:
        key = "|".join((row["backend"], row["schedule_family"],
                        row["backward_policy"]))
        err = (row.get("rel_err") or {}).get("step_s")
        groups.setdefault(key, []).append(err)
    out: Dict[str, Dict[str, Any]] = {}
    for key in sorted(groups):
        errs = [e for e in groups[key] if e is not None]
        out[key] = {
            "n": len(groups[key]),
            "n_with_err": len(errs),
            "median_rel_err": float(np.median(errs)) if errs else None,
            "median_abs_rel_err":
                float(np.median(np.abs(errs))) if errs else None,
        }
    return out


# ---------------------------------------------------------------------------
# Correction factors: deterministic least squares + signed artifact
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CorrectionFactors:
    """Per-hardware efficiency scalars the roofline divides by.

    ``measured_step ~= compute_s / flops_efficiency
    + comm_s / bandwidth_efficiency`` — so a factor of 1.0 means the
    roofline was exact, 0.01 means the hardware delivered 1% of the
    modeled rate on these probes. ``n_rows``/``residual_rms`` record the
    fit's evidence so a consumer can weigh it."""

    hardware: str
    flops_efficiency: float
    bandwidth_efficiency: float
    n_rows: int
    residual_rms: float

    def summary(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def _clamp_eff(x: float) -> float:
    lo, hi = EFFICIENCY_CLAMP
    return float(min(max(x, lo), hi))


def fit_correction(rows: Sequence[Dict[str, Any]], hardware: str
                   ) -> Optional[CorrectionFactors]:
    """Least-squares fit of (flops, bandwidth) efficiency for one
    hardware name over its ledger rows.

    Model: ``measured = a * compute_s + b * comm_s`` with
    ``a = 1/e_flops``, ``b = 1/e_bw`` — solved by explicit 2x2 normal
    equations in float64 over *sorted* canonical rows, so the result is
    bit-deterministic for a given ledger regardless of row order. When
    the comm column is degenerate (all ~0, or collinear with compute,
    or the solve lands non-positive) it falls back to a pure-FLOPs fit
    with ``e_bw = 1.0``. None when no row has both sides."""
    pts: List[Tuple[str, float, float, float]] = []
    for row in rows:
        if row.get("hardware") != hardware:
            continue
        pred, meas = row.get("predicted"), row.get("measured")
        if not pred or not meas:
            continue
        c = pred.get("compute_s")
        k = pred.get("comm_s")
        m = meas.get("step_s")
        if c is None or m is None or float(m) <= 0 or float(c) <= 0:
            continue
        pts.append((canonical_row_line(deterministic_fields(row)),
                    float(c), 0.0 if k is None else float(k), float(m)))
    if not pts:
        return None
    pts.sort()
    comp = np.array([p[1] for p in pts], dtype=np.float64)
    comm = np.array([p[2] for p in pts], dtype=np.float64)
    meas = np.array([p[3] for p in pts], dtype=np.float64)

    def _flops_only() -> Tuple[float, float]:
        return float((comp * meas).sum() / (comp * comp).sum()), 1.0

    scc = float((comp * comp).sum())
    skk = float((comm * comm).sum())
    sck = float((comp * comm).sum())
    det = scc * skk - sck * sck
    if skk <= 0.0 or det <= 1e-12 * scc * max(skk, 1e-300):
        a, b = _flops_only()
    else:
        rhs_c = float((comp * meas).sum())
        rhs_k = float((comm * meas).sum())
        a = (rhs_c * skk - rhs_k * sck) / det
        b = (rhs_k * scc - rhs_c * sck) / det
        if a <= 0.0 or b <= 0.0:
            a, b = _flops_only()
    resid = a * comp + b * comm - meas
    return CorrectionFactors(
        hardware=hardware,
        flops_efficiency=_clamp_eff(1.0 / a),
        bandwidth_efficiency=_clamp_eff(1.0 / b),
        n_rows=len(pts),
        residual_rms=float(np.sqrt(np.mean(resid * resid))),
    )


def fit_corrections(rows: Sequence[Dict[str, Any]]
                    ) -> Dict[str, CorrectionFactors]:
    """One :class:`CorrectionFactors` per hardware name in the rows."""
    out: Dict[str, CorrectionFactors] = {}
    for hw in sorted({r.get("hardware") for r in rows
                      if isinstance(r.get("hardware"), str)}):
        fit = fit_correction(rows, hw)
        if fit is not None:
            out[hw] = fit
    return out


_CORRECTION_FIELDS = ("hardware", "flops_efficiency", "bandwidth_efficiency",
                      "n_rows", "residual_rms")


def _corrections_fingerprint(art: Dict[str, Any]) -> str:
    payload = {k: art.get(k) for k in
               ("artifact_version", "kind", "schema_version", "corrections")}
    blob = json.dumps(payload, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


def correction_artifact(factors: Mapping[str, CorrectionFactors]
                        ) -> Dict[str, Any]:
    """Versioned, fingerprinted JSON artifact for a set of fitted
    corrections — the same interchange discipline as the schedule
    artifacts (``parallel.schedules``): the fingerprint signs the
    payload, the loader re-derives and rejects any tamper."""
    art: Dict[str, Any] = {
        "artifact_version": CORRECTION_ARTIFACT_VERSION,
        "kind": CORRECTION_ARTIFACT_KIND,
        "schema_version": CALIBRATION_SCHEMA_VERSION,
        "corrections": {hw: cf.summary() for hw, cf in sorted(factors.items())},
    }
    art["fingerprint"] = _corrections_fingerprint(art)
    return art


def correction_artifact_bytes(art: Dict[str, Any]) -> bytes:
    """Canonical (byte-deterministic) encoding of a correction artifact."""
    return (json.dumps(art, sort_keys=True) + "\n").encode()


def save_correction_artifact(art: Dict[str, Any], path: str) -> None:
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    with open(path, "wb") as fh:
        fh.write(correction_artifact_bytes(art))


def load_correction_artifact(source) -> Dict[str, CorrectionFactors]:
    """Load + verify a correction artifact (path or dict) into
    per-hardware :class:`CorrectionFactors`. Every failure is a located
    :class:`CalibrationError`."""
    if isinstance(source, dict):
        art, label = source, "<dict>"
    else:
        label = str(source)
        try:
            with open(source, "r", encoding="utf-8") as fh:
                art = json.load(fh)
        except OSError as e:
            raise CalibrationError(f"correction artifact {label}: "
                                   f"unreadable: {e}")
        except json.JSONDecodeError as e:
            raise CalibrationError(f"correction artifact {label}: "
                                   f"invalid JSON: {e}")
    if not isinstance(art, dict):
        raise CalibrationError(f"correction artifact {label}: not an object")
    if art.get("kind") != CORRECTION_ARTIFACT_KIND:
        raise CalibrationError(f"correction artifact {label}: kind "
                               f"{art.get('kind')!r} != "
                               f"{CORRECTION_ARTIFACT_KIND!r}")
    if art.get("artifact_version") != CORRECTION_ARTIFACT_VERSION:
        raise CalibrationError(
            f"correction artifact {label}: artifact_version "
            f"{art.get('artifact_version')!r} != "
            f"{CORRECTION_ARTIFACT_VERSION}")
    if art.get("fingerprint") != _corrections_fingerprint(art):
        raise CalibrationError(f"correction artifact {label}: fingerprint "
                               "mismatch (payload was modified)")
    corr = art.get("corrections")
    if not isinstance(corr, dict):
        raise CalibrationError(f"correction artifact {label}: corrections "
                               "is not an object")
    out: Dict[str, CorrectionFactors] = {}
    for hw, blob in corr.items():
        if not isinstance(blob, dict):
            raise CalibrationError(f"correction artifact {label}: "
                                   f"corrections[{hw!r}] is not an object")
        for field in _CORRECTION_FIELDS:
            if field not in blob:
                raise CalibrationError(
                    f"correction artifact {label}: corrections[{hw!r}] "
                    f"missing {field!r}")
        lo, hi = EFFICIENCY_CLAMP
        for field in ("flops_efficiency", "bandwidth_efficiency"):
            v = blob[field]
            if not isinstance(v, (int, float)) or not (lo <= v <= hi):
                raise CalibrationError(
                    f"correction artifact {label}: corrections[{hw!r}]"
                    f".{field}={v!r} outside clamp {EFFICIENCY_CLAMP}")
        out[hw] = CorrectionFactors(
            hardware=str(blob["hardware"]),
            flops_efficiency=float(blob["flops_efficiency"]),
            bandwidth_efficiency=float(blob["bandwidth_efficiency"]),
            n_rows=int(blob["n_rows"]),
            residual_rms=float(blob["residual_rms"]))
    return out


def maybe_load_default_corrections() -> Optional[Dict[str, CorrectionFactors]]:
    """Corrections from ``$DTPP_CALIBRATION_CORRECTIONS`` or the default
    ``results/calibration_corrections.json`` — None when neither exists
    or the artifact fails verification. Never raises: a bad artifact
    must degrade to uncorrected predictions, not break a training run
    (the probe/regress legs are where a bad artifact is a hard error)."""
    path = os.environ.get(CORRECTIONS_ENV) or DEFAULT_CORRECTIONS_PATH
    if not os.path.exists(path):
        return None
    try:
        return load_correction_artifact(path)
    except CalibrationError:
        return None


# ---------------------------------------------------------------------------
# Row builders: probe, cost-model reports, backfill
# ---------------------------------------------------------------------------


def row_from_cost_model(cm: Dict[str, Any], *, source: str, name: str,
                        backend: str, t: float = 0.0,
                        seed: Optional[int] = None,
                        predicted_peak_bytes: Optional[float] = None,
                        measured_peak_bytes: Optional[float] = None
                        ) -> Dict[str, Any]:
    """Build one validated ledger row from a ``cost_model_section`` dict
    (which already pairs a predicted block with a measured one). Rows
    written before PR 32 may carry a ``measured.comm_s`` (read off the
    executors' host stamps, which are gone); it is never written now and
    nothing reads it."""
    hw = cm.get("hardware") or {}
    pred_src = cm.get("predicted") or {}
    meas_src = cm.get("measured")
    predicted: Optional[Dict[str, Any]] = None
    if pred_src:
        predicted = {k: pred_src.get(k) for k in
                     ("compute_s", "comm_s", "step_s", "step_s_overlapped",
                      "step_s_comm_overlap", "bubble_table_exact")}
        if predicted_peak_bytes is not None:
            predicted["peak_bytes"] = float(predicted_peak_bytes)
    measured: Optional[Dict[str, Any]] = None
    if meas_src and meas_src.get("step_s"):
        measured = {"step_s": float(meas_src["step_s"]),
                    "tokens_per_sec": meas_src.get("tokens_per_sec")}
        if measured_peak_bytes is not None:
            measured["peak_bytes"] = float(measured_peak_bytes)
    corrected = None
    corr_src = pred_src.get("corrected")
    if corr_src and measured:
        corrected = dict(corr_src)
        corrected["rel_err_step_s"] = signed_rel_err(
            corr_src.get("step_s"), measured["step_s"])
    row: Dict[str, Any] = {
        "schema_version": CALIBRATION_SCHEMA_VERSION,
        "kind": LEDGER_KIND,
        "source": source,
        "t": float(t),
        "name": name,
        "backend": backend,
        "hardware": str(hw.get("name", "unknown")),
        "cpu_proxy": bool(hw.get("cpu_proxy", False)),
        "schedule": str(cm.get("schedule", "unknown")),
        "schedule_family": schedule_family(str(cm.get("schedule", ""))),
        "backward_policy": str(cm.get("backward_policy", "unknown")),
        "comm_overlap": str(cm.get("comm_overlap", "none")),
        "n_devices": int(cm.get("n_devices", 0)),
        "n_virtual": int(cm.get("n_virtual", 1)),
        "n_microbatches": int(cm.get("n_microbatches", 0)),
        "batch_size": int(cm.get("batch_size", 0)),
        "seq_length": int(cm.get("seq_length", 0)),
        "predicted": predicted,
        "measured": measured,
        "rel_err": _rel_err_block(predicted, measured),
        "corrected": corrected,
    }
    if seed is not None:
        row["seed"] = int(seed)
    return validate_ledger_row(row, f"row_from_cost_model[{name}]")


def backfill_row_from_history(hrow: Dict[str, Any], *, path: str = "history"
                              ) -> Optional[Dict[str, Any]]:
    """One ``results/history.jsonl`` row → a ledger row, or None with a
    reason attached when the row carries nothing calibratable.

    History rows predate the ledger and carry only headline scalars;
    rows with a measured step but no prediction are kept with
    ``predicted: null`` (the ISSUE's never-drop-silently contract —
    the *caller* prints the reason for the ones that return None)."""
    meas_step = hrow.get("measured_step_s")
    pred_step = hrow.get("predicted_step_s")
    if meas_step is None and pred_step is None:
        return None
    schedule = str(hrow.get("schedule") or "unknown")
    backend = str(hrow.get("backend") or "unknown")
    predicted = None
    if pred_step is not None:
        predicted = {"step_s": float(pred_step), "compute_s": None,
                     "comm_s": None}
    measured = None
    if meas_step is not None:
        measured = {"step_s": float(meas_step),
                    "tokens_per_sec": hrow.get("tokens_per_sec")}
        if hrow.get("peak_temp_bytes") is not None:
            measured["peak_bytes"] = float(hrow["peak_temp_bytes"])
    row = {
        "schema_version": CALIBRATION_SCHEMA_VERSION,
        "kind": LEDGER_KIND,
        "source": f"backfill:{path}",
        "t": float(hrow.get("t") or 0.0),
        "name": str(hrow.get("name") or "history"),
        "backend": backend,
        "hardware": "cpu_proxy" if backend == "cpu" else "unknown",
        "cpu_proxy": backend == "cpu",
        "schedule": schedule,
        "schedule_family": schedule_family(schedule),
        "backward_policy": "unknown",
        "comm_overlap": "none",
        "n_devices": 0,
        "n_virtual": 1,
        "n_microbatches": 0,
        "batch_size": 0,
        "seq_length": 0,
        "predicted": predicted,
        "measured": measured,
        "rel_err": _rel_err_block(predicted, measured),
        "corrected": None,
    }
    return validate_ledger_row(row, f"backfill:{path}")


# ---------------------------------------------------------------------------
# The measured micro-probe
# ---------------------------------------------------------------------------

# Tiny probe model: 4 layers divide both the 2-stage (V=1) and 4-stage
# (V=2) placements of the 2-device smoke mesh; batch 8 divides every
# grid microbatch count.
_PROBE_MODEL = dict(dim=16, n_layers=4, n_heads=2, vocab_size=64,
                    ffn_dim=32, max_seq_len=16)
_PROBE_BATCH = 8
_PROBE_SEQ = 16


def run_probe(spec: ProbeSpec, *, seed: int = 0, num_iterations: int = 2,
              warmup_iterations: int = 1, correction=None,
              t: float = 0.0) -> Dict[str, Any]:
    """Execute one measured micro-probe on the live mesh → a validated
    ledger row.

    A few warm steps of a tiny model (warmup compiles + pages, then
    ``num_iterations`` timed steps via ``utils.metrics.
    run_train_iterations`` — the only sanctioned step clock), with
    XLA's AOT accounting for the measured peak-HBM axis.
    Deterministic modulo the measured fields:
    the spec, seeds, model and every predicted number are pure
    functions of (spec, seed). ``t`` stamps the row (pass
    ``time.time()`` from the driver; defaults to 0 so library callers
    stay deterministic)."""
    import jax

    from ..models.transformer import transformer_init
    from ..parallel.mesh import make_mesh
    from ..parallel.pipeline import aot_memory_analysis, make_pipeline_step
    from ..parallel.schedules import compile_schedule
    from ..utils.config import ModelConfig, ScheduleConfig
    from ..utils.metrics import run_train_iterations
    from .cost_model import cost_model_section, resolve_backward_policy
    from .memory_model import memory_model_section, memory_probe_axes

    cfg = ModelConfig(**_PROBE_MODEL)
    sched = ScheduleConfig(name=spec.schedule,
                           n_microbatches=spec.n_microbatches,
                           n_virtual=spec.n_virtual)
    cs = compile_schedule(spec.schedule, spec.n_devices, spec.n_virtual,
                          spec.n_microbatches)
    mesh = make_mesh(n_pipe=spec.n_devices)
    # the double-buffered executor requires the unrolled tick loop; every
    # other probe takes the scan executor, whose once-compiled tick body
    # keeps a 9-point grid's compile bill in CI budget (the probe measures
    # steady-state step time, which executor formulation doesn't change —
    # and the choice is a pure function of the row's comm_overlap field)
    unroll = True if spec.comm_overlap == "ring" else False
    step = make_pipeline_step(cfg, mesh, sched,
                              remat_backward=spec.remat_backward,
                              unroll_ticks=unroll,
                              comm_overlap=spec.comm_overlap)
    params = transformer_init(jax.random.key(seed), cfg)
    kx, ky = jax.random.split(jax.random.key(seed + 1))
    tokens = jax.random.randint(kx, (_PROBE_BATCH, _PROBE_SEQ), 0,
                                cfg.vocab_size)
    targets = jax.random.randint(ky, (_PROBE_BATCH, _PROBE_SEQ), 0,
                                 cfg.vocab_size)
    metrics = run_train_iterations(step, params, tokens, targets,
                                   num_iterations=num_iterations,
                                   warmup_iterations=warmup_iterations)
    measured_step_s = metrics["elapsed_time"] / num_iterations

    cm = cost_model_section(cs, cfg, batch_size=_PROBE_BATCH,
                            seq_length=_PROBE_SEQ,
                            remat_backward=spec.remat_backward,
                            measured_step_s=measured_step_s,
                            comm_overlap=spec.comm_overlap,
                            correction=correction)
    mem = memory_model_section(
        cs, cfg, batch_size=_PROBE_BATCH, seq_length=_PROBE_SEQ,
        remat_backward=spec.remat_backward,
        compiled=aot_memory_analysis(step, params, tokens, targets))
    peaks = memory_probe_axes(mem)

    backend = jax.devices()[0].platform
    policy = resolve_backward_policy(cs, spec.remat_backward, spec.n_devices)
    name = (f"probe_{spec.schedule}_D{spec.n_devices}V{spec.n_virtual}"
            f"M{spec.n_microbatches}_{policy}_{spec.comm_overlap}")
    return row_from_cost_model(
        cm, source="probe", name=name, backend=backend, t=t, seed=seed,
        predicted_peak_bytes=peaks["predicted_peak_bytes"],
        measured_peak_bytes=peaks["measured_peak_bytes"])


def reprice_row(row: Dict[str, Any], spec: ProbeSpec, correction
                ) -> Dict[str, Any]:
    """Re-price one probe row under fitted corrections WITHOUT
    re-measuring: recompile the schedule table (pure numpy) and re-run
    the cost model with the correction applied, keeping the row's
    measured fields verbatim. This is how ``scripts/probe.py`` reports
    corrected error from the same run that fitted the correction — the
    measurement is the expensive part; the pricing is host math."""
    from ..parallel.schedules import compile_schedule
    from ..utils.config import ModelConfig
    from .cost_model import cost_model_section

    cfg = ModelConfig(**_PROBE_MODEL)
    cs = compile_schedule(spec.schedule, spec.n_devices, spec.n_virtual,
                          spec.n_microbatches)
    meas = row.get("measured") or {}
    pred_old = row.get("predicted") or {}
    cm = cost_model_section(cs, cfg, batch_size=row["batch_size"],
                            seq_length=row["seq_length"],
                            remat_backward=spec.remat_backward,
                            measured_step_s=meas.get("step_s"),
                            comm_overlap=spec.comm_overlap,
                            correction=correction)
    return row_from_cost_model(
        cm, source=row["source"], name=row["name"], backend=row["backend"],
        t=row["t"], seed=row.get("seed"),
        predicted_peak_bytes=pred_old.get("peak_bytes"),
        measured_peak_bytes=meas.get("peak_bytes"))


# ---------------------------------------------------------------------------
# RunReport section
# ---------------------------------------------------------------------------


def _compact_row(row: Dict[str, Any]) -> Dict[str, Any]:
    pred = row.get("predicted") or {}
    meas = row.get("measured") or {}
    corr = row.get("corrected") or {}
    return {
        "schedule": row["schedule"],
        "schedule_family": row["schedule_family"],
        "backward_policy": row["backward_policy"],
        "comm_overlap": row["comm_overlap"],
        "n_devices": row["n_devices"],
        "n_microbatches": row["n_microbatches"],
        "predicted_step_s": pred.get("step_s"),
        "predicted_step_s_corrected": corr.get("step_s"),
        "measured_step_s": meas.get("step_s"),
        "rel_err": (row.get("rel_err") or {}).get("step_s"),
        "rel_err_corrected": corr.get("rel_err_step_s"),
    }


def calibration_section(rows: Sequence[Dict[str, Any]], *,
                        correction: Optional[Mapping[str, Any]] = None,
                        ledger_path: Optional[str] = None) -> Dict[str, Any]:
    """The schema-validated ``calibration`` RunReport section: compact
    per-config rows plus the raw-vs-corrected error summary the regress
    sentinel guards."""
    compact = [_compact_row(validate_ledger_row(r, f"section[{i}]"))
               for i, r in enumerate(rows)]
    raw = [abs(c["rel_err"]) for c in compact if c["rel_err"] is not None]
    cor = [abs(c["rel_err_corrected"]) for c in compact
           if c["rel_err_corrected"] is not None]
    section: Dict[str, Any] = {
        "schema_version": CALIBRATION_SCHEMA_VERSION,
        "n_rows": len(compact),
        "rows": compact,
        "summary": {
            "n_with_predictions":
                sum(1 for c in compact if c["predicted_step_s"] is not None),
            "median_abs_rel_err_raw":
                float(np.median(raw)) if raw else None,
            "median_abs_rel_err_corrected":
                float(np.median(cor)) if cor else None,
            "groups": group_errors(rows),
        },
        "correction": None,
        "ledger_path": ledger_path,
    }
    if correction:
        section["correction"] = {
            hw: (cf.summary() if isinstance(cf, CorrectionFactors)
                 else dict(cf))
            for hw, cf in sorted(correction.items())}
    return section


def calibration_section_from_cost_model(cm: Dict[str, Any], *, backend: str,
                                        name: str = "run",
                                        correction: Optional[Mapping[str, Any]]
                                        = None) -> Optional[Dict[str, Any]]:
    """Single-run calibration section from a measured
    ``cost_model_section`` — how the sweep reports its own
    predicted-vs-measured point without running a probe grid. None when
    the section carries no measurement (nothing to calibrate)."""
    if not (cm.get("measured") or {}).get("step_s"):
        return None
    row = row_from_cost_model(cm, source="run", name=name, backend=backend)
    return calibration_section([row], correction=correction)
