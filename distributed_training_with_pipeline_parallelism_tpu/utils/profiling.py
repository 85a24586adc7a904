"""Profiling: the profiler's context managers, and the names of the step's
regions.

The reference's only instrumentation is ``time.time()`` around the timed loop
(SURVEY.md §5 tracing row; upstream's ``record_function`` blocks are never
collected). Here:

- :func:`trace` wraps ``jax.profiler.trace`` — traces open in
  XProf/TensorBoard with per-op device timelines (the honest way to see
  bubbles on real hardware); :func:`annotate` puts a host span on the same
  clock, :func:`annotated_steps` a step number around a loop's iterations.
- :data:`REGIONS` is the fixed vocabulary of ``jax.named_scope`` names the
  program sets where the work is written (``models/transformer.py``,
  ``utils/train.py``; the executors' ``pp/...`` beside them), and
  :func:`classify` reads an instruction's ``op_name`` — as the compiled
  step's text carries it in ``metadata={op_name="..."}`` — back into
  ``(phase, region)``. A device-trace event is named by its instruction, so
  instruction -> compiled text -> ``op_name`` -> :func:`classify` splits a
  trace of any step by forward / backward / recompute / optimizer and by
  attention / MLP / head (``benchmark/harness/scopes.py`` does the join;
  docs/observability.md has the reading guide).
"""

from __future__ import annotations

import contextlib
import re
from typing import Iterable, Iterator, Tuple

import jax


@contextlib.contextmanager
def trace(log_dir: str):
    """Profile a block: ``with trace('/tmp/prof'): step(...)`` then inspect
    in TensorBoard/XProf. The pipeline executors label their compute with
    ``pp/...`` named scopes (``pp/phase3``, ``pp/fwd``, ``pp/ring_bwd``,
    ...), so trace rows group by schedule structure — see
    docs/observability.md for the reading guide."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@contextlib.contextmanager
def annotate(name: str):
    """Label HOST-side spans of a traced block in XProf:
    ``with annotate("step3"): step(...)``. Complements the executors'
    ``jax.named_scope`` labels, which name DEVICE-side ops at trace time:
    ``TraceAnnotation`` marks wall-clock regions of the host timeline
    (e.g. which train step issued the work). No-op cost when
    no profiler session is active."""
    with jax.profiler.TraceAnnotation(name):
        yield


def annotated_steps(steps: Iterable[int],
                    name: str = "train") -> Iterator[int]:
    """Iterate ``steps`` with the body of each iteration under
    ``jax.profiler.StepTraceAnnotation(name, step_num=i)``, so a trace's
    host plane (and XProf's step view) knows which step issued what. The
    annotation closes when the loop asks for the next step, breaks, or drops
    the iterator. No-op cost when no profiler session is active."""
    for i in steps:
        with jax.profiler.StepTraceAnnotation(name, step_num=i):
            yield i


# --------------------------------------------------------------------------
# The step's regions, and how an op_name is read back into one

#: ``jax.named_scope`` names set where the work is written:
#: ``models/transformer.py`` (the ``model/`` ones, at the function every
#: path shares: ``embed_apply``, ``body_apply`` — the layer stack's own
#: weight slices, stacked residuals and residual adds —, ``layer_apply``'s
#: attention half, ``mlp_block``, ``head_apply`` and the loss) and
#: ``utils/train.py:make_train_step`` (the optax update). The executors'
#: ``pp/...`` scopes (``parallel/pipeline.py``) stay as they are; they are
#: regions too, wherever no name of this list is further in.
REGIONS = ("model/embed", "model/layers", "model/attn", "model/mlp",
           "model/head_loss", "train/optimizer")
#: The patterned stack's own six, beside ``model/attn`` for its attention
#: layers (an LFM2-style layer's q/k norms and rotation inside it) and
#: ``model/mlp`` for its dense MLP, disjoint by the
#: innermost-wins rule: ``model/ssm``
#: (``models/nemotron_h.py``: a Mamba-2 mixer but its scan — norm, both
#: projections, convolution, gate, group norm), ``model/ssm_scan``
#: (``ops/mamba2.py:ssd_chunked``), ``model/moe`` (norm, router, top-k,
#: the gate, shared expert), ``model/moe_experts``
#: (``ops/experts.py:held_experts``, the held experts' gated products) and
#: ``model/mla_latent`` (``ops/attention.py:mla_project``, inside a latent-
#: attention layer's ``model/attn``: both down-projections, the latent
#: norms, both up-projections, RoPE, building ``k`` — everything between the
#: normed input and the attention core's operands) and ``model/shortconv``
#: (a gated short-convolution layer whole: norm, both projections, both
#: gates, the convolution). Kept apart from :data:`REGIONS`, which every dense step names
#: whole (``benchmark/tests/test_bench_scopes.py`` holds it to that).
HYBRID_REGIONS = ("model/ssm", "model/ssm_scan", "model/moe",
                  "model/moe_experts", "model/mla_latent", "model/shortconv")
UNSCOPED = "unscoped"

#: In this order, the first mark an op_name holds gives its phase. JAX writes
#: the last two itself (``jax.checkpoint``'s second run of a function, and
#: the transposed half of a ``jvp``); anything else under a region is
#: ``forward``.
PHASE_MARKS = (("train/optimizer", "optimizer"),
               ("rematted_computation", "recompute"),
               ("transpose(", "backward"))

# longest first: ``model/ssm_scan`` is not ``model/ssm`` with a tail
_REGION = re.compile("|".join(
    re.escape(r) for r in sorted(REGIONS + HYBRID_REGIONS, key=len,
                                 reverse=True)))
_PP = re.compile(r"pp/[A-Za-z_]+")  # pp/tick003 -> pp/tick, pp/phase2 -> pp/phase
# The tick executors' backward units: each re-runs its stage's forward by
# hand (``jax.vjp`` inside the tick), which JAX marks ``jvp(``, not
# ``rematted_computation``. Read off the D=2 executor's compiled text: under
# these scopes ``jvp(pp/stage_body)/..`` and ``jvp(pp/embed)/..`` are the
# second forward, ``transpose(jvp(..))`` the backward. The head and its loss
# are the exception: their ONLY forward run is inside the last stage's
# backward unit (``stage_objective``), so it stays ``forward``.
_BACKWARD_UNIT = re.compile(r"pp/(bwd_dgrad|bwd|wgrad)(?![A-Za-z_])")


def _region(part: str):
    found = _REGION.findall(part)
    if found:
        return found[-1]  # the innermost
    found = _PP.findall(part)
    return found[-1] if found else None


def _phase(part: str, region) -> str:
    for mark, phase in PHASE_MARKS:
        if mark in part:
            return phase
    if region != "model/head_loss" and _BACKWARD_UNIT.search(part):
        return "recompute"
    if region is not None or "jvp(" in part:
        return "forward"
    return "other"


def classify(op_name: str) -> Tuple[str, str]:
    """``(phase, region)`` of one instruction, from the ``op_name`` its
    compiled HLO carries (``jit(train_step)/transpose(jvp())/while/body/
    closed_call/checkpoint/model/mlp/dot_general``).

    ``region`` is the innermost name of :data:`REGIONS` or
    :data:`HYBRID_REGIONS` in it, else the
    innermost ``pp/...`` scope (tick and phase numbers dropped), else
    ``"unscoped"``. ``phase`` is ``optimizer``, ``recompute``, ``backward``
    or ``forward`` by :data:`PHASE_MARKS` and the rule for the executors'
    backward units above; an op outside every region still gets the phase
    JAX's own marks give it (``jvp(`` alone is forward), and ``other`` only
    where the name holds no mark at all. A fusion that merged several
    sources carries ``a;b``: the first part that has a region is read."""
    parts = op_name.split(";")
    for part in parts:
        region = _region(part)
        if region is not None:
            return _phase(part, region), region
    return _phase(parts[0], None), UNSCOPED
