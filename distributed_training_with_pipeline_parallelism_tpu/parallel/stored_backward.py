"""Residual taint analysis for the stored-activation pipeline backward.

The tick executor's stored-activation mode (``remat_backward=False`` on
:func:`.pipeline.make_pipeline_grad_fn`) banks the stage body's ``jax.vjp``
residuals in slot-addressed buffers at forward time and replays them at
backward time — the TPU-native analog of how the reference's torch autograd
stashes saved tensors per microbatch and never recomputes the forward
(``LLMsDistributedTrainingHelper.py:98-143`` via upstream
``stage.py:857/937``).

``jax.vjp``'s returned pullback is a pytree whose leaves are *all* values the
backward needs — which includes the stage *weights* (a matmul's input
cotangent needs W) and cheap derived values (bf16 casts, RoPE tables, causal
masks). Storing those per in-flight microbatch would replicate parameters
per slot. This module answers, mechanically, "which residual leaves actually
depend on the stage input x?":

- **x-dependent leaves** are the true activations (layer inputs, attention
  statistics, FFN intermediates, dropout bits) — these get slot buffers.
- **x-independent leaves** are pure functions of (params, chunk index,
  microbatch index) — the backward unit re-traces the same vjp with a dummy
  x and takes these leaves from the fresh trace; the dummy trace's
  x-dependent chain feeds nothing (the stored leaves replace it) and XLA's
  dead-code elimination removes it, so no forward matmul is recomputed.

**Slot-buffer lifetime under the phase-compressed executor** (``unroll_
ticks="phases"``): the residual slot buffers live in the tick carry, and
:func:`.pipeline._phase_compressed_ticks` threads ONE carry through every
per-phase ``lax.scan`` — a residual banked by a forward tick in one phase
(e.g. the warmup) survives phase boundaries untouched until the backward
tick that consumes it, possibly several scans later (1F1B's last warmup
residuals are read deep into the cooldown). Nothing about slot lifetime is
phase-local: slots are allocated against the WHOLE table
(``schedules._allocate_slots``), phases only re-group the iteration order
of the same rows, and the per-phase scans neither reset nor re-shape the
carry. The one interaction to keep in mind is memory, not correctness:
each scan boundary materializes the full carry — including every slot
buffer — in HBM, so the stored policy pays the buffer HBM round-trip once
per phase transition rather than once per tick (cheaper than the plain
scan, more than the fully unrolled form, where XLA may keep residuals in
registers across ticks).

The analysis is a conservative taint propagation over the jaxpr of the
residual extraction, descending into scan (with carry-feedback fixpoint),
cond (union over branches), and single-subjaxpr call primitives
(pjit/remat/custom_vjp); unknown higher-order primitives fall back to
"any tainted input taints every output", which can only over-store, never
under-store — correctness does not depend on the classification, only
memory does (tests/test_stored_backward.py pins both).
"""

from __future__ import annotations

from typing import Callable, List, Sequence, Tuple

import jax
import numpy as np
from jax.extend.core import Var


def _eqn_out_taint(eqn, in_taint: List[bool]) -> List[bool]:
    """Taint of one equation's outputs given its inputs' taint."""
    prim = eqn.primitive.name
    if prim == "scan":
        body = eqn.params["jaxpr"].jaxpr
        n_consts = eqn.params["num_consts"]
        n_carry = eqn.params["num_carry"]
        # fixpoint over the carry feedback loop (monotone, so it terminates
        # in <= n_carry iterations)
        t_in = list(in_taint)
        while True:
            out_t = _jaxpr_out_taint(body, t_in)
            new_in = list(t_in)
            for i in range(n_carry):
                if out_t[i]:
                    new_in[n_consts + i] = True
            if new_in == t_in:
                break
            t_in = new_in
        return _jaxpr_out_taint(body, t_in)
    if prim == "cond":
        op_taint = in_taint[1:]  # invars = [branch index, *operands]
        outs: List[bool] | None = None
        for br in eqn.params["branches"]:
            o = _jaxpr_out_taint(br.jaxpr, op_taint)
            outs = o if outs is None else [a or b for a, b in zip(outs, o)]
        assert outs is not None
        return outs
    if prim == "while":
        # conservative: loop-carried mixing
        return [any(in_taint)] * len(eqn.outvars)
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = eqn.params.get(key)
        if sub is None:
            continue
        body = sub.jaxpr if hasattr(sub, "jaxpr") else sub
        pad = len(body.invars) - len(in_taint)
        if pad < 0:  # unexpected arity: be conservative
            return [any(in_taint)] * len(eqn.outvars)
        # custom_vjp/jvp prepend rule operands; padding with False is safe
        # because those extra invars are not the traced x
        out_t = _jaxpr_out_taint(body, [False] * pad + list(in_taint))
        if len(out_t) >= len(eqn.outvars):
            return out_t[: len(eqn.outvars)]
        return [any(in_taint)] * len(eqn.outvars)
    return [any(in_taint)] * len(eqn.outvars)


def _jaxpr_out_taint(jaxpr, in_taint: Sequence[bool]) -> List[bool]:
    tainted = {v for v, t in zip(jaxpr.invars, in_taint) if t}
    for eqn in jaxpr.eqns:
        eqn_in = [isinstance(v, Var) and v in tainted for v in eqn.invars]
        if not any(eqn_in):
            continue
        for v, t in zip(eqn.outvars, _eqn_out_taint(eqn, eqn_in)):
            if t:
                tainted.add(v)
    return [isinstance(v, Var) and v in tainted for v in jaxpr.outvars]


def x_dependent_mask(fn: Callable, args: Tuple, x_argnums: Sequence[int],
                     ) -> List[bool]:
    """Per-output bool: does output i of ``fn(*args)`` depend on any of
    ``args[j] for j in x_argnums``?  ``fn`` must return a flat tuple of
    arrays (use it on the flattened-vjp-leaf extraction). Closure values of
    ``fn`` become jaxpr constants — untainted by construction, which is
    exactly right: they are live at backward time."""
    closed = jax.make_jaxpr(fn)(*args)
    jaxpr = closed.jaxpr
    flat_sizes = [len(jax.tree.leaves(a)) for a in args]
    starts = np.cumsum([0] + flat_sizes)
    in_taint = [False] * len(jaxpr.invars)
    for i in x_argnums:
        for k in range(int(starts[i]), int(starts[i + 1])):
            in_taint[k] = True
    return _jaxpr_out_taint(jaxpr, in_taint)


def check_residual_leaves(leaves, struct, where: str) -> None:
    """Trace-time invariant: the live vjp trace must produce the same
    residual list (count, shapes, dtypes, order) as the abstract trace the
    slot buffers were allocated from. A mismatch means the two traces of
    the stage body diverged — raise before silent corruption."""
    if len(leaves) != len(struct):
        raise RuntimeError(
            f"stored-activation backward: residual count diverged at "
            f"{where} ({len(leaves)} leaves vs {len(struct)} at "
            f"allocation); the stage body traced differently between "
            f"forward and allocation — please report this configuration")
    for i, (l, s) in enumerate(zip(leaves, struct)):
        if tuple(l.shape) != tuple(s.shape) or l.dtype != s.dtype:
            raise RuntimeError(
                f"stored-activation backward: residual {i} diverged at "
                f"{where}: {l.shape}/{l.dtype} vs allocated "
                f"{s.shape}/{s.dtype}")
