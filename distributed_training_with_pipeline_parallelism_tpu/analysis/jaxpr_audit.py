"""Jaxpr auditor: host-callback, collective, and dtype-drift checks.

The table verifier (:mod:`.table_check`) proves what the *schedule* says
should happen; this pass checks what the *traced program* actually
contains. It walks a closed jaxpr recursively (through pjit/shard_map
calls, scan bodies with their trip counts, cond branches, custom-vjp
wrappers) and accumulates:

- ``n_callbacks``: host callbacks (``io_callback`` / ``pure_callback`` /
  debug prints). No step function of this repo holds one
  (docs/observability.md): the device's time is read from the profiler's
  trace, never stamped from inside the program.
- ``collectives``: weighted counts per collective primitive. Scan bodies
  multiply by the scan ``length``; cond contributes the elementwise MAX
  over its branches (the executor's worst-case tick); a while loop makes
  the counts lower bounds (``unbounded`` is set). For an unrolled tick
  executor the traced ``ppermute`` count must equal
  ``TableReport.predicted_ppermutes`` — the dead-hop elision contract.
- ``psum_axes`` / ``unknown_axes``: every axis name a collective reduces
  over, and those not present in the declared mesh axes.
- dtype drift: ``f64_values`` (any float64 output — unintended x64
  promotion) and ``bf16_upcasts`` (bf16 -> f32 ``convert_element_type``;
  legitimate sites — loss accumulators, RoPE tables — are bounded by the
  caller's allowlist budget, not matched by name).

Only :func:`audit_fn` imports jax (lazily): the module itself stays
importable in jax-free tooling contexts.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

_CALLBACK_MARKERS = ("callback", "outside_call", "debug_print")
_COLLECTIVE_PREFIXES = ("ppermute", "pbroadcast", "psum", "pmax", "pmin",
                        "all_gather", "all_to_all", "reduce_scatter",
                        "psum_scatter")


@dataclasses.dataclass
class JaxprAudit:
    """Aggregated facts about one traced step function."""

    n_callbacks: int = 0
    collectives: Dict[str, int] = dataclasses.field(default_factory=dict)
    psum_axes: Tuple[str, ...] = ()
    unknown_axes: Tuple[str, ...] = ()
    f64_values: int = 0
    bf16_upcasts: int = 0
    unbounded: bool = False  # a while loop made counts lower bounds
    problems: List[str] = dataclasses.field(default_factory=list)

    @property
    def ppermute_count(self) -> int:
        return sum(n for name, n in self.collectives.items()
                   if name.startswith("ppermute"))

    @property
    def ok(self) -> bool:
        return not self.problems

    def summary(self) -> Dict[str, Any]:
        return {
            "n_callbacks": self.n_callbacks,
            "collectives": dict(self.collectives),
            "ppermute_count": self.ppermute_count,
            "psum_axes": list(self.psum_axes),
            "unknown_axes": list(self.unknown_axes),
            "f64_values": self.f64_values,
            "bf16_upcasts": self.bf16_upcasts,
            "unbounded": self.unbounded,
            "problems": list(self.problems),
        }


class _Acc:
    def __init__(self):
        self.callbacks = 0
        self.collectives: Dict[str, int] = {}
        self.axes: Dict[str, bool] = {}  # axis name -> seen on a psum-like
        self.f64 = 0
        self.upcasts = 0
        self.unbounded = False

    def merge_max(self, others: Sequence["_Acc"]) -> None:
        """Elementwise max across cond branches, added into self."""
        if not others:
            return
        self.callbacks += max(o.callbacks for o in others)
        for name in {n for o in others for n in o.collectives}:
            self.collectives[name] = self.collectives.get(name, 0) + max(
                o.collectives.get(name, 0) for o in others)
        for o in others:
            self.axes.update(o.axes)
            self.unbounded |= o.unbounded
        self.f64 += max(o.f64 for o in others)
        self.upcasts += max(o.upcasts for o in others)


def _inner_jaxpr(obj: Any) -> Optional[Any]:
    """Duck-typed unwrap: ClosedJaxpr -> Jaxpr, Jaxpr -> itself."""
    if hasattr(obj, "eqns"):
        return obj
    if hasattr(obj, "jaxpr") and hasattr(getattr(obj, "jaxpr"), "eqns"):
        return obj.jaxpr
    return None


def _axis_names(params: Dict[str, Any]) -> List[str]:
    names: List[str] = []
    for key in ("axis_name", "axes", "axis_index_groups_axis"):
        v = params.get(key)
        if v is None:
            continue
        for item in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(item, str):
                names.append(item)
    return names


def _walk(jaxpr: Any, mult: int, acc: _Acc) -> None:
    import numpy as np

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if any(m in name for m in _CALLBACK_MARKERS):
            acc.callbacks += mult
        if name.startswith(_COLLECTIVE_PREFIXES):
            acc.collectives[name] = acc.collectives.get(name, 0) + mult
            for ax in _axis_names(eqn.params):
                acc.axes[ax] = True
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is not None and dt == np.dtype("float64"):
                acc.f64 += mult
        if name == "convert_element_type":
            src = getattr(getattr(eqn.invars[0], "aval", None), "dtype",
                          None)
            dst = eqn.params.get("new_dtype")
            if (src is not None and dst is not None
                    and str(src) == "bfloat16" and str(dst) == "float32"):
                acc.upcasts += mult
        # recurse into sub-jaxprs with the right multiplier
        if name == "scan":
            length = int(eqn.params.get("length", 1))
            sub = _inner_jaxpr(eqn.params.get("jaxpr"))
            if sub is not None:
                _walk(sub, mult * length, acc)
            continue
        if name == "while":
            acc.unbounded = True
            for key in ("body_jaxpr", "cond_jaxpr"):
                sub = _inner_jaxpr(eqn.params.get(key))
                if sub is not None:
                    _walk(sub, mult, acc)
            continue
        if name == "cond":
            branch_accs = []
            for br in eqn.params.get("branches", ()):
                sub = _inner_jaxpr(br)
                if sub is not None:
                    b = _Acc()
                    _walk(sub, mult, b)
                    branch_accs.append(b)
            acc.merge_max(branch_accs)
            continue
        for v in eqn.params.values():
            for item in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = _inner_jaxpr(item)
                if sub is not None:
                    _walk(sub, mult, acc)


def audit_jaxpr(closed_jaxpr: Any, mesh_axes: Sequence[str] = (),
                expect_no_callbacks: bool = False,
                expected_ppermutes: Optional[int] = None,
                upcast_budget: Optional[int] = None) -> JaxprAudit:
    """Audit a (closed) jaxpr. Facts are always collected; ``problems`` is
    populated only for the contracts the caller opted into (plus unknown
    collective axes whenever ``mesh_axes`` is given)."""
    acc = _Acc()
    jaxpr = _inner_jaxpr(closed_jaxpr)
    if jaxpr is None:
        raise TypeError(f"not a jaxpr: {type(closed_jaxpr)!r}")
    _walk(jaxpr, 1, acc)

    audit = JaxprAudit(
        n_callbacks=acc.callbacks,
        collectives=dict(sorted(acc.collectives.items())),
        psum_axes=tuple(sorted(acc.axes)),
        f64_values=acc.f64,
        bf16_upcasts=acc.upcasts,
        unbounded=acc.unbounded,
    )
    if mesh_axes:
        unknown = tuple(a for a in audit.psum_axes if a not in mesh_axes)
        audit.unknown_axes = unknown
        if unknown:
            audit.problems.append(
                f"collectives reduce over undeclared axes {unknown} "
                f"(mesh declares {tuple(mesh_axes)})")
    if expect_no_callbacks and audit.n_callbacks:
        audit.problems.append(
            f"{audit.n_callbacks} host callback(s) traced (must be zero)")
    if expected_ppermutes is not None \
            and audit.ppermute_count != expected_ppermutes:
        audit.problems.append(
            f"traced ppermute count {audit.ppermute_count} != table-"
            f"predicted comm volume {expected_ppermutes}")
    if audit.f64_values:
        audit.problems.append(
            f"{audit.f64_values} float64 value(s) traced (unintended x64 "
            f"promotion)")
    if upcast_budget is not None and audit.bf16_upcasts > upcast_budget:
        audit.problems.append(
            f"{audit.bf16_upcasts} bf16->f32 upcasts exceed the allowlist "
            f"budget {upcast_budget}")
    return audit


def audit_fn(fn: Any, *args: Any, mesh_axes: Sequence[str] = (),
             **kwargs: Any) -> JaxprAudit:
    """Trace ``fn(*args)`` with ``jax.make_jaxpr`` (abstract — nothing
    executes) and audit the result. Keyword arguments are forwarded to
    :func:`audit_jaxpr`."""
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    return audit_jaxpr(closed, mesh_axes=mesh_axes, **kwargs)


def collective_matmul_ppermutes(axis_size: int, n_gathers: int,
                                n_scatters: int = 0) -> int:
    """Chunked-permute census for the ring collective-matmul forms
    (:mod:`..ops.collectives`): every ring gather (``all_gather_matmul``,
    ``seq_all_gather``) and ring scatter (``matmul_reduce_scatter``)
    traces exactly ``axis_size - 1`` ppermutes. Add this to a program's
    ``expected_ppermutes`` when auditing a ``tp_overlap="ring"`` forward
    — the double-buffered pipeline executors themselves keep the table's
    ``predicted_ppermutes`` unchanged (deferred banking moves the store
    commit, never the hop)."""
    return (int(axis_size) - 1) * (int(n_gathers) + int(n_scatters))
