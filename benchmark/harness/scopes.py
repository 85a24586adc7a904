"""Device time by the regions the program names.

A profiler trace names a device op by its HLO instruction (``%fusion.357 =
..``) and, as ``ProfileData`` shows it, carries nothing else. The program's
``jax.named_scope`` names are in the COMPILED step's text instead: every
instruction that came from user code has ``metadata={op_name="jit(train_step)
/transpose(jvp())/../model/mlp/dot_general"}``. So: event -> instruction name
-> :func:`scope_map` of the compiled text -> ``op_name`` -> the program's own
``utils/profiling.py:classify`` -> ``(phase, region)``. The vocabulary and
its reading live in the program (an operator reading a ``--profile-dir``
trace needs the same); this file only joins.

Seconds are UNIONS of intervals, as everywhere in ``trace_reduce``;
containers are skipped; a collective goes under the phase ``collective``
whatever region it carries, because a stage with nothing to do waits inside
its permute.
"""

from __future__ import annotations

import collections
import re

from benchmark.harness.trace_reduce import (COLLECTIVE, instruction,
                                            is_collective, op_label, ops,
                                            union_ns)

# every instruction line of every computation, ROOT or not (the pattern of
# ``kernels.CALL``); the line's op_name where it has one
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?(?P<name>[\w.\-]+) = [^\n]*", re.M)
OP_NAME = re.compile(r'op_name="([^"]*)"')
# the opcode of an event's HLO text: the first `` word(`` after `` = ``
# (shapes and layouts hold no such token; operands come after it)
OPCODE = re.compile(r" = .*? ([a-z][a-z\-]*)\(")
MIN_COVERAGE = 0.9  # under it the text is not the traced program's


def scope_map(hlo_text: str) -> dict[str, str]:
    """``{instruction: op_name}`` for every instruction of the compiled
    program's text; ``""`` where the compiler gave it no ``op_name`` (its
    own copies and bitcasts). XLA keeps instruction names unique in a
    module; should one repeat, the first is kept."""
    found: dict[str, str] = {}
    for m in INSTRUCTION.finditer(hlo_text):
        op = OP_NAME.search(m.group(0))
        found.setdefault(m.group("name"), op.group(1) if op else "")
    return found


def collective(ev) -> bool:
    """A collective by its instruction's name (``trace_reduce``'s rule) or
    by its opcode: XLA names an instruction after the tail of its
    ``op_name``, so the gradient all-reduce is ``%psum.44 = f32[..]
    all-reduce(..)`` and the name alone misses it."""
    if is_collective(ev):
        return True
    m = OPCODE.search(ev.name)
    return bool(m and COLLECTIVE.search(m.group(1)))


def by_region(trace, plane: str, lo: float, hi: float, scopes: dict,
              kernel_of: dict | None = None) -> dict:
    """One device plane inside [lo, hi), split by what the program named.

    ``seconds``: ``{(phase, region): union seconds}``; ``phases`` and
    ``regions``: the same unions over one key alone (a phase over all its
    regions, a region over all its phases — collectives are in ``phases``
    only); ``busy_s``: union of all ops; ``coverage``: the share of busy
    time whose instruction is in ``scopes`` at all; ``by_label``: summed
    seconds per ``"<phase>:<region>:<label>"`` (``trace_reduce.op_label``).
    An instruction that ``scopes`` lacks counts as ``other`` / ``unscoped``.
    """
    from distributed_training_with_pipeline_parallelism_tpu.utils.profiling import (
        classify)
    spans: dict = collections.defaultdict(list)
    phases: dict = collections.defaultdict(list)
    regions: dict = collections.defaultdict(list)
    busy, known = [], []
    by_label: dict = collections.Counter()
    for ev in ops(trace, plane):
        a, b = max(ev.start_ns, lo), min(ev.end_ns, hi)
        if b <= a:
            continue
        name = instruction(ev.name)
        phase, region = classify(scopes.get(name, ""))
        busy.append((a, b))
        if name in scopes:
            known.append((a, b))
        if collective(ev):
            phase = "collective"
        else:
            regions[region].append((a, b))
        spans[phase, region].append((a, b))
        phases[phase].append((a, b))
        by_label[f"{phase}:{region}:{op_label(ev, kernel_of)}"] += (b - a) * 1e-9

    def seconds(groups):
        return {k: union_ns(v, lo, hi) * 1e-9 for k, v in groups.items()}

    busy_s = union_ns(busy, lo, hi) * 1e-9
    return {"seconds": seconds(spans), "phases": seconds(phases),
            "regions": seconds(regions), "busy_s": busy_s,
            "window_s": (hi - lo) * 1e-9,
            "coverage": union_ns(known, lo, hi) * 1e-9 / busy_s if busy_s else 0.0,
            "by_label": dict(by_label)}


def summarize(planes: list[dict], top: int = 10) -> dict:
    """What the runner puts under ``run["regions"]``: the planes, their
    busy-weighted ``coverage``, and ``device_ops`` — per device, the ``top``
    labels by seconds, for the result line's ``breakdown``."""
    busy = sum(p["busy_s"] for p in planes)
    totals: collections.Counter = collections.Counter()
    for p in planes:
        totals.update(p["by_label"])
    return {"planes": planes,
            "coverage": (sum(p["coverage"] * p["busy_s"] for p in planes) / busy
                         if busy else 0.0),
            "device_ops": [[label, s / len(planes)]
                           for label, s in totals.most_common(top)]}


def share_pct(run: dict, table: str, key: str):
    """Union seconds of one phase (``table="phases"``) or one region
    (``"regions"``) over the planes' summed busy seconds, in %. ``None``
    where the run has no regions (an untraced run, a runner or a program
    that names none) or the text did not cover the trace."""
    regions = run.get("regions")
    if not regions or regions["coverage"] < MIN_COVERAGE:
        return None
    busy = sum(p["busy_s"] for p in regions["planes"])
    if not busy:
        return None
    return 100.0 * sum(p[table].get(key, 0.0) for p in regions["planes"]) / busy
