"""Training-dynamics observatory: per-stage/per-layer statistics, the
gradient-noise-scale estimator, and loss-spike forensics.

The systems observatories (telemetry / cost model / memory) watch the
*hardware*; this module watches the *model*. Three pieces, all opt-in
and all zero-cost when off (the dynamics-off jaxpr is byte-identical to
a build without the feature — tests/test_dynamics.py pins it, the same
discipline as the telemetry and guard counters):

- **In-jit statistics** (:func:`stage_stats`, :func:`nonfinite_per_stage`):
  computed inside the jitted train step from the full-model pytrees the
  step already holds. Pipeline stages partition the layer stack into
  contiguous blocks (``stack_stage_layers``: global stage ``s`` owns
  layers ``[s*lps, (s+1)*lps)``; the embedding rides stage 0, the head
  the last stage), so per-stage attribution is a reshape, not a
  collective. The resulting stat dict is device-resident; ``fit`` reads
  it only at log syncs, riding the ``float(loss)`` fetch — no extra
  host round-trips.

- **Gradient noise scale** (:class:`GNSEstimator`): the pipeline's
  accumulation loop already materializes one gradient per microbatch
  (the B/W units' ``gp``/``gh``); ``make_pipeline_grad_fn(...,
  dynamics=True)`` accumulates their squared norms per microbatch into
  an ``[M]`` carry — stages partition the (untied) parameters, so a
  pipe-axis psum completes each microbatch's ``|g_m|^2`` — and the
  classic small/large-batch pair (McCandlish et al., "An Empirical
  Model of Large-Batch Training") gives ``B_noise ~ S/|G|^2`` with no
  extra backward pass.

- **Forensics** (:class:`ForensicRecorder`): a host-side ring buffer of
  recent step stats plus batch content digests; on an anomaly-guard
  skip or a z-score loss spike it dumps a schema-versioned bundle
  (offending per-stage stats, microbatch digests, pointer to the last
  committed checkpoint) next to the run's manifest.

Stat definitions, the zero-cost-when-off contract, and the bundle
format are documented in docs/observability.md §7.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import json
import math
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

# Bundle files are versioned independently of the RunReport manifest:
# they are read by humans mid-incident and by regress/forensics tooling
# long after the run that wrote them is gone.
FORENSIC_SCHEMA_VERSION = 1
FORENSIC_TRIGGERS = ("anomaly", "loss_spike")


@dataclasses.dataclass(frozen=True)
class DynamicsConfig:
    """Opt-in knobs for the training-dynamics observatory.

    ``gns``: accumulate per-microbatch squared grad norms in the pipeline
    executor (needs the tick executor on a dense pipe x data mesh —
    ``make_pipeline_grad_fn`` raises otherwise; set False to keep the
    per-stage stats on configs the accumulator does not support).
    ``ema``: smoothing factor for the GNS estimate (per log sync).
    ``ring``: forensic ring length (log-sync entries and batch digests).
    ``spike_z``/``spike_warmup``: loss-spike trigger — z-score of the
    current loss against the ring's prior entries, armed only once the
    ring holds ``spike_warmup`` finite losses.
    """
    gns: bool = True
    ema: float = 0.9
    ring: int = 16
    spike_z: float = 6.0
    spike_warmup: int = 5


def as_dynamics_config(dynamics) -> Optional[DynamicsConfig]:
    """None | True | DynamicsConfig -> Optional[DynamicsConfig]."""
    if dynamics is None or dynamics is False:
        return None
    if dynamics is True:
        return DynamicsConfig()
    if isinstance(dynamics, DynamicsConfig):
        return dynamics
    raise TypeError(f"dynamics must be None, True, or a DynamicsConfig, "
                    f"got {dynamics!r}")


# ---------------------------------------------------------------------------
# In-jit per-stage / per-layer statistics
# ---------------------------------------------------------------------------


def _stage_view(leaf, n_layers: int, n_stages: int):
    """[L, ...] layer-stacked leaf -> [S, per-stage-elements] f32 view."""
    if leaf.shape[0] != n_layers:
        raise ValueError(
            f"layer leaf leading dim {leaf.shape[0]} != n_layers="
            f"{n_layers}; dynamics stats need the stacked dense layout")
    return leaf.astype(jnp.float32).reshape(n_stages, -1)


def nonfinite_per_stage(n_layers: int, n_stages: int, grads) -> jax.Array:
    """[S] int32: non-finite (leaf, layer) slots per stage, in-jit.

    The unit counted is one layer-row of one stacked leaf (plus one unit
    per whole embed/head leaf, charged to the first/last stage): fine
    enough to name the poisoned tensor class, cheap enough to run on
    every guarded step. Zero everywhere == the step is clean.
    """
    S, lps = n_stages, n_layers // n_stages
    nf = jnp.zeros((S,), jnp.int32)
    for leaf in jax.tree.leaves(grads["layers"]):
        bad = ~jnp.isfinite(leaf.astype(jnp.float32)).reshape(n_layers, -1)
        nf = nf + bad.any(axis=1).reshape(S, lps).sum(axis=1,
                                                      dtype=jnp.int32)
    for leaf in jax.tree.leaves(grads["embed"]):
        bad = ~jnp.isfinite(leaf.astype(jnp.float32))
        nf = nf.at[0].add(bad.any().astype(jnp.int32))
    for leaf in jax.tree.leaves(grads["head"]):
        bad = ~jnp.isfinite(leaf.astype(jnp.float32))
        nf = nf.at[S - 1].add(bad.any().astype(jnp.int32))
    return nf


def _per_stage_sq(n_layers: int, n_stages: int, tree_
                  ) -> Tuple[jax.Array, np.ndarray]:
    """Per-stage sum of squares [S] plus the (static) element counts."""
    S = n_stages
    sq = jnp.zeros((S,), jnp.float32)
    counts = np.zeros((S,), np.int64)
    for leaf in jax.tree.leaves(tree_["layers"]):
        x = _stage_view(leaf, n_layers, S)
        sq = sq + jnp.sum(x * x, axis=1)
        counts += int(np.prod(leaf.shape)) // S
    for key, idx in (("embed", 0), ("head", S - 1)):
        for leaf in jax.tree.leaves(tree_[key]):
            x = leaf.astype(jnp.float32)
            sq = sq.at[idx].add(jnp.sum(x * x))
            counts[idx] += int(np.prod(leaf.shape))
    return sq, counts


def stage_stats(n_layers: int, n_stages: int, grads, params=None,
                updates=None) -> Dict[str, jax.Array]:
    """Per-stage / per-layer dynamics statistics, computed in-jit.

    Always present: ``grad_norm`` (global, pre-clipping), ``grad_norm_
    per_stage`` [S], ``grad_max_per_stage`` [S] (max |g|),
    ``nonfinite_per_stage`` [S], ``grad_norm_per_layer`` [L] (layer
    stack only — embed/head norms live in their stages' entries). With
    ``params``: ``param_rms_per_stage`` [S]. With both ``params`` and
    ``updates``: ``update_ratio_per_stage`` [S] (||update|| / ||param||
    per stage — the update-to-weight ratio LR sanity check).

    Non-finite values are NOT masked out of the norms: a poisoned stage
    reports a non-finite norm (honest) alongside its non-zero
    ``nonfinite_per_stage`` count (attributable).
    """
    if n_layers % n_stages:
        raise ValueError(f"n_layers={n_layers} must divide into "
                         f"{n_stages} stages")
    S = n_stages
    g_sq, _ = _per_stage_sq(n_layers, S, grads)
    mx = jnp.zeros((S,), jnp.float32)
    for leaf in jax.tree.leaves(grads["layers"]):
        mx = jnp.maximum(mx, jnp.max(
            jnp.abs(_stage_view(leaf, n_layers, S)), axis=1))
    for key, idx in (("embed", 0), ("head", S - 1)):
        for leaf in jax.tree.leaves(grads[key]):
            mx = mx.at[idx].max(jnp.max(jnp.abs(leaf.astype(jnp.float32))))
    l_sq = jnp.zeros((n_layers,), jnp.float32)
    for leaf in jax.tree.leaves(grads["layers"]):
        x = leaf.astype(jnp.float32).reshape(n_layers, -1)
        l_sq = l_sq + jnp.sum(x * x, axis=1)
    out = {
        "grad_norm": jnp.sqrt(jnp.sum(g_sq)),
        "grad_norm_per_stage": jnp.sqrt(g_sq),
        "grad_max_per_stage": mx,
        "nonfinite_per_stage": nonfinite_per_stage(n_layers, S, grads),
        "grad_norm_per_layer": jnp.sqrt(l_sq),
    }
    if params is not None:
        p_sq, n_elems = _per_stage_sq(n_layers, S, params)
        out["param_rms_per_stage"] = jnp.sqrt(
            p_sq / jnp.asarray(n_elems, jnp.float32))
        if updates is not None:
            u_sq, _ = _per_stage_sq(n_layers, S, updates)
            out["update_ratio_per_stage"] = jnp.sqrt(u_sq) / (
                jnp.sqrt(p_sq) + 1e-12)
    return out


# ---------------------------------------------------------------------------
# Gradient noise scale
# ---------------------------------------------------------------------------


def gns_estimates(mean_sq_small: float, sq_big: float, batch_small: float,
                  batch_big: float) -> Tuple[float, float]:
    """Unbiased ``(|G|^2, tr(Sigma))`` pair from a small/large-batch norm
    pair (McCandlish et al. appendix A):

    ``E|g_B|^2 = |G|^2 + tr(Sigma)/B`` for a batch of B samples, so two
    batch sizes solve for both unknowns. Here the small batch is one
    microbatch (per data shard) and the large batch is the full step —
    gradients the accumulation loop materializes anyway.
    """
    b, B = float(batch_small), float(batch_big)
    if not B > b:
        raise ValueError(f"need batch_big > batch_small, got {B} <= {b}")
    g2 = (B * sq_big - b * mean_sq_small) / (B - b)
    s = (mean_sq_small - sq_big) / (1.0 / b - 1.0 / B)
    return g2, s


class GNSEstimator:
    """EMA-smoothed gradient-noise-scale tracker (host side).

    Feed it one ``(mean_m |g_m|^2, |G|^2)`` pair per log sync; ``value()``
    is ``tr(Sigma)/|G|^2`` — the "simple noise scale" whose magnitude
    is the batch size beyond which data parallelism stops paying.
    Numerator and denominator are smoothed separately (their ratio is
    biased; the smoothed ratio of smoothed moments is the standard
    estimator). Returns None until the first finite update, or when the
    smoothed ``|G|^2`` is non-positive (noise dominates signal and the
    ratio is meaningless).
    """

    def __init__(self, batch_small: float, batch_big: float,
                 ema: float = 0.9):
        if not batch_big > batch_small > 0:
            raise ValueError(
                f"need batch_big > batch_small > 0, got "
                f"small={batch_small}, big={batch_big} (GNS needs at "
                f"least two microbatches per step)")
        self.batch_small = float(batch_small)
        self.batch_big = float(batch_big)
        self.ema = float(ema)
        self.g2_ema: Optional[float] = None
        self.s_ema: Optional[float] = None
        self.n_updates = 0

    def update(self, mean_sq_small: float, sq_big: float) -> Optional[float]:
        g2, s = gns_estimates(mean_sq_small, sq_big, self.batch_small,
                              self.batch_big)
        if not (math.isfinite(g2) and math.isfinite(s)):
            return self.value()  # a poisoned step must not wedge the EMA
        if self.g2_ema is None:
            self.g2_ema, self.s_ema = g2, s
        else:
            a = self.ema
            self.g2_ema = a * self.g2_ema + (1.0 - a) * g2
            self.s_ema = a * self.s_ema + (1.0 - a) * s
        self.n_updates += 1
        return self.value()

    def value(self) -> Optional[float]:
        if self.g2_ema is None or self.g2_ema <= 0.0:
            return None
        return self.s_ema / self.g2_ema


# ---------------------------------------------------------------------------
# Forensics: batch digests, spike detection, bundle dump
# ---------------------------------------------------------------------------


def batch_digest(*arrays) -> str:
    """Content digest of a batch (shape/dtype/bytes), for "which data did
    the bad step eat" forensics without storing the data itself."""
    h = hashlib.sha256()
    for a in arrays:
        x = np.asarray(a)
        h.update(repr((x.shape, str(x.dtype))).encode())
        h.update(x.tobytes())
    return h.hexdigest()[:16]


def _jsonable(obj):
    """Numpy/jax leaves -> plain JSON types (bundles must load anywhere,
    including hosts without jax)."""
    if isinstance(obj, dict):
        return {k: _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, (np.ndarray, jax.Array)):
        return np.asarray(obj).tolist()
    if isinstance(obj, np.generic):
        return obj.item()
    if isinstance(obj, float) and not math.isfinite(obj):
        return repr(obj)  # json has no NaN/inf; keep them readable
    return obj


class ForensicRecorder:
    """Host-side ring buffer + bundle writer for loss-spike forensics.

    ``note_batch`` runs every step (a content digest of the input batch —
    the arrays are already host-visible inputs, so hashing adds no device
    sync); ``observe`` runs at log syncs with the fetched loss and the
    dynamics stat dict, appends a ring entry, and returns the z-score
    when the loss spikes against the ring's history (None otherwise).
    ``dump`` writes the bundle next to the manifest and remembers the
    path so the run report can list it.
    """

    def __init__(self, out_dir: Optional[str] = None, ring: int = 16,
                 spike_z: float = 6.0, warmup: int = 5):
        self.out_dir = out_dir
        self.spike_z = float(spike_z)
        self.warmup = int(warmup)
        self.ring: collections.deque = collections.deque(maxlen=ring)
        self.digests: collections.deque = collections.deque(maxlen=ring)
        self.bundles: List[str] = []

    def note_batch(self, step: int, digest: str) -> None:
        self.digests.append({"step": int(step), "digest": digest})

    def observe(self, step: int, loss: float, stats: Optional[dict] = None,
                gns: Optional[float] = None) -> Optional[float]:
        prior = [r["loss"] for r in self.ring
                 if isinstance(r["loss"], float) and math.isfinite(r["loss"])]
        z = None
        loss = float(loss)
        if len(prior) >= self.warmup and math.isfinite(loss):
            mu = sum(prior) / len(prior)
            var = sum((x - mu) ** 2 for x in prior) / len(prior)
            # the epsilon scales with the mean so a flat loss plateau
            # (sd == 0) still triggers on any real jump, not on noise
            z = (loss - mu) / (math.sqrt(var) + 1e-9 * (1.0 + abs(mu)))
        entry = {"step": int(step), "loss": loss, "gns": gns}
        if stats is not None:
            entry["grad_norm"] = float(np.asarray(stats["grad_norm"]))
        self.ring.append(entry)
        if z is not None and z >= self.spike_z:
            return z
        return None

    def dump(self, step: int, trigger: str, *, loss=None, z=None,
             stats: Optional[dict] = None, attribution: Optional[dict] = None,
             checkpoint: Optional[dict] = None) -> Optional[str]:
        """Write one forensic bundle; returns its path (None without an
        ``out_dir`` — recorder still tracks the ring for tests)."""
        if trigger not in FORENSIC_TRIGGERS:
            raise ValueError(f"trigger must be one of {FORENSIC_TRIGGERS}, "
                             f"got {trigger!r}")
        bundle = {
            "schema_version": FORENSIC_SCHEMA_VERSION,
            "kind": "forensic_bundle",
            "trigger": trigger,
            "step": int(step),
            "loss": _jsonable(loss),
            "z": _jsonable(z),
            "stats": _jsonable(stats),
            "attribution": _jsonable(attribution),
            "ring": _jsonable(list(self.ring)),
            "batch_digests": _jsonable(list(self.digests)),
            "checkpoint": _jsonable(checkpoint),
        }
        validate_forensic_bundle(bundle)
        if self.out_dir is None:
            return None
        os.makedirs(self.out_dir, exist_ok=True)
        path = os.path.join(self.out_dir,
                            f"forensics_step{int(step):06d}_{trigger}.json")
        with open(path, "w") as fh:
            json.dump(bundle, fh, indent=1)
        self.bundles.append(path)
        return path


def validate_forensic_bundle(bundle) -> None:
    """Structural validation of a forensic bundle; raises ValueError
    naming the offending field (mirrors ``telemetry.validate_report``'s
    hand-rolled style — no external schema dependency)."""

    def fail(msg):
        raise ValueError(f"invalid forensic bundle: {msg}")

    if not isinstance(bundle, dict):
        fail(f"expected dict, got {type(bundle).__name__}")
    if bundle.get("kind") != "forensic_bundle":
        fail(f"kind must be 'forensic_bundle', got {bundle.get('kind')!r}")
    if bundle.get("schema_version") != FORENSIC_SCHEMA_VERSION:
        fail(f"schema_version must be {FORENSIC_SCHEMA_VERSION}, got "
             f"{bundle.get('schema_version')!r}")
    if bundle.get("trigger") not in FORENSIC_TRIGGERS:
        fail(f"trigger must be one of {FORENSIC_TRIGGERS}, got "
             f"{bundle.get('trigger')!r}")
    if not isinstance(bundle.get("step"), int):
        fail(f"step must be an int, got {bundle.get('step')!r}")
    ring = bundle.get("ring")
    if not isinstance(ring, list):
        fail(f"ring must be a list, got {type(ring).__name__}")
    for i, row in enumerate(ring):
        if not isinstance(row, dict) or "step" not in row or "loss" not in row:
            fail(f"ring[{i}] must be a dict with step/loss, got {row!r}")
    digests = bundle.get("batch_digests")
    if not isinstance(digests, list):
        fail(f"batch_digests must be a list, got {type(digests).__name__}")
    for i, row in enumerate(digests):
        if (not isinstance(row, dict)
                or not isinstance(row.get("digest"), str)):
            fail(f"batch_digests[{i}] must carry a string digest, "
                 f"got {row!r}")
    attr = bundle.get("attribution")
    if attr is not None:
        if not isinstance(attr, dict):
            fail(f"attribution must be a dict or None, got "
                 f"{type(attr).__name__}")
        if not isinstance(attr.get("stage"), int):
            fail(f"attribution.stage must be an int, got "
                 f"{attr.get('stage')!r}")
        if not isinstance(attr.get("statistic"), str):
            fail(f"attribution.statistic must be a string, got "
                 f"{attr.get('statistic')!r}")


# ---------------------------------------------------------------------------
# RunReport section
# ---------------------------------------------------------------------------


def dynamics_section(n_stages: int, last_stats: Optional[dict] = None,
                     gns: Optional[float] = None, gns_updates: int = 0,
                     n_skipped_attributed: int = 0,
                     forensic_bundles=()) -> dict:
    """The manifest's ``dynamics`` section from host-fetched stats
    (``validate_report`` checks this shape)."""
    section = {
        "n_stages": int(n_stages),
        "grad_norm_final": None,
        "gns": None if gns is None else float(gns),
        "gns_updates": int(gns_updates),
        "n_skipped_attributed": int(n_skipped_attributed),
        "per_stage": [],
        "forensic_bundles": [os.path.basename(p) for p in forensic_bundles],
    }
    if last_stats is not None:
        sv = {k: np.asarray(v) for k, v in last_stats.items()
              if k != "sq_mb"}
        section["grad_norm_final"] = float(sv["grad_norm"])
        for s in range(int(n_stages)):
            row = {"stage": s,
                   "grad_norm": float(sv["grad_norm_per_stage"][s]),
                   "grad_max": float(sv["grad_max_per_stage"][s]),
                   "nonfinite": int(sv["nonfinite_per_stage"][s])}
            if "param_rms_per_stage" in sv:
                row["param_rms"] = float(sv["param_rms_per_stage"][s])
            if "update_ratio_per_stage" in sv:
                row["update_ratio"] = float(
                    sv["update_ratio_per_stage"][s])
            section["per_stage"].append(row)
        section["grad_norm_per_layer"] = [
            float(x) for x in sv["grad_norm_per_layer"]]
    return section
