"""Pipeline-schedule IR: per-device action lists, tick scheduling, validation.

The reference delegates scheduling to ``torch.distributed.pipelining``
(SURVEY.md U2-U4): ``ScheduleGPipe`` (fill-drain, ``schedules.py:872``),
``Schedule1F1B`` (warmup/steady/cooldown, ``schedules.py:995``), and
``ScheduleInterleaved1F1B`` (explicit per-rank action-list IR over virtual
stages, ``schedules.py:2891``, after Megatron-LM arXiv:2104.04473).

This module re-expresses all three as a host-side IR compiled for a
single-program SPMD executor:

1. **Action lists** — for each device, an ordered list of
   ``Action(stage, op, microbatch)`` (``op`` in {F, B}; ``stage`` is the
   *global* stage index; device(stage) = stage % n_devices, virtual index
   v = stage // n_devices — the reference's wrap placement
   ``stage_idx = rank + world_size * i``, ``LLMsDistributedTrainingHelper.py:208``).
2. **Tick scheduling** — an ASAP list scheduler assigns each action to a
   discrete tick: a device takes its actions in list order, its next
   forward and its next full backward in the same tick where both are ready
   — a row's F and B slots, so a steady 1F1B stage runs its forward and its
   backward in ONE tick; split-backward (F/B/W) orders keep one unit a tick
   — and a cross-device data dependency costs one tick of transfer latency
   (the ``ppermute`` hop).
3. **Tick tables** — dense int32 arrays the SPMD executor scans over; every
   entry is static, so the whole schedule compiles into one XLA program with
   no data-dependent control flow.

Under jit the ticks become real lockstep super-steps separated by
``ppermute`` collectives, so the tick abstraction here *is* the runtime
model, not just an analysis.
"""

from __future__ import annotations

import dataclasses
import hashlib
import heapq
import json
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from ..utils.profiling import annotate

F = "F"
B = "B"  # full backward — or input-grad (dgrad) only under a split schedule
W = "W"  # weight-grad (wgrad) — split schedules (ZB-H1) only

SPLIT_BACKWARD_SCHEDULES = frozenset({"ZBH1", "ZBV"})

# User-registered schedules: name -> (order_fn, split_backward).
# ``order_fn(n_devices, n_virtual, n_microbatches) -> List[List[Action]]``.
_CUSTOM_SCHEDULES: Dict[str, Tuple[object, bool]] = {}


def register_schedule(name: str, order_fn, split_backward: bool = False,
                      overwrite: bool = False) -> None:
    """Register a custom pipeline schedule under ``name``.

    ``order_fn(n_devices, n_virtual, n_microbatches)`` returns per-device
    action lists using this module's :class:`Action` (wrap placement:
    device(stage) = stage % n_devices). The order is validated, deadlock-
    checked, tick-scheduled, slot-allocated, and symbolically verified by
    the same machinery as the built-ins, then runs on the unmodified SPMD
    executor — the whole point of keeping the schedule as data
    (upstream torch gates this behind ``_PipelineScheduleRuntime``'s CSV
    loader, ``schedules.py:2279``; here it is a first-class API, tested in
    tests/test_custom_schedule.py). With ``split_backward`` the order must
    emit dgrad ``B`` + wgrad ``W`` pairs per ZB-H1 conventions (no ``B``
    on stage 0).
    """
    if not overwrite and (name in BUILTIN_SCHEDULE_NAMES
                          or name in _CUSTOM_SCHEDULES):
        raise ScheduleError(f"schedule {name!r} already exists")
    if name in BUILTIN_SCHEDULE_NAMES:
        raise ScheduleError(f"cannot overwrite built-in schedule {name!r}")
    _CUSTOM_SCHEDULES[name] = (order_fn, split_backward)


def unregister_schedule(name: str) -> None:
    _CUSTOM_SCHEDULES.pop(name, None)
    _ARTIFACT_PINS.pop(name, None)


def is_split_backward(name: str) -> bool:
    if name in _CUSTOM_SCHEDULES:
        return _CUSTOM_SCHEDULES[name][1]
    return name in SPLIT_BACKWARD_SCHEDULES


def is_custom(name: str) -> bool:
    return name in _CUSTOM_SCHEDULES


def schedule_names() -> Tuple[str, ...]:
    return BUILTIN_SCHEDULE_NAMES + tuple(_CUSTOM_SCHEDULES)


BUILTIN_SCHEDULE_NAMES = ("GPipe", "1F1B", "Interleaved1F1B", "ZBH1", "BFS",
                          "ZBV")


def schedule_placement(name: str) -> str:
    return "vshape" if name == "ZBV" else "wrap"


@dataclasses.dataclass(frozen=True)
class Action:
    stage: int  # global stage index in [0, n_stages)
    op: str  # F, B, or W
    microbatch: int


class ScheduleError(ValueError):
    pass


# ---------------------------------------------------------------------------
# Per-device action-order generators
# ---------------------------------------------------------------------------


def gpipe_order(n_devices: int, n_microbatches: int) -> List[List[Action]]:
    """Fill-drain: all forwards in microbatch order, then all backwards.

    Mirrors upstream ScheduleGPipe semantics (SURVEY.md U2): per stage, M
    forwards then M backwards, both in increasing microbatch order.
    """
    orders = []
    for d in range(n_devices):
        acts = [Action(d, F, m) for m in range(n_microbatches)]
        acts += [Action(d, B, m) for m in range(n_microbatches)]
        orders.append(acts)
    return orders


def one_f_one_b_order(n_devices: int, n_microbatches: int) -> List[List[Action]]:
    """1F1B: per-device warmup of ``2 * (D-1-d)`` forwards, steady-state
    alternating F/B, cooldown backwards (SURVEY.md U3; upstream requires
    M >= D, ``schedules.py:1020-1024`` — enforced here too).

    The warm-up is twice upstream's ``D-1-d`` because of what a hop costs
    on the tick executor: one tick each way. A microbatch's round trip from
    stage ``d`` to the last stage and back takes ``2 * (D-1-d)`` ticks, so
    that many forwards must be in flight before ``B(d, 0)`` can run — the
    depth :func:`interleaved_order` already uses. With it
    :func:`schedule_ticks` packs every steady-state ``F, B`` pair into one
    tick, all stages are in steady state together, and a lock-step tick
    costs f + b on every device: ``M + 2(D-1)`` rows at the async runtime's
    cost ``(M + D - 1)(f + b)``. The price is ``min(M, 2D-1)`` stage inputs
    in flight on stage 0 instead of ``D``."""
    D, M = n_devices, n_microbatches
    if M < D:
        raise ScheduleError(f"1F1B requires n_microbatches >= n_devices ({M} < {D})")
    orders = []
    for d in range(D):
        warmup = min(M, 2 * (D - 1 - d))
        acts = [Action(d, F, m) for m in range(warmup)]
        nf, nb = warmup, 0
        while nf < M:  # steady state: one forward, one backward
            acts.append(Action(d, F, nf))
            nf += 1
            acts.append(Action(d, B, nb))
            nb += 1
        acts += [Action(d, B, m) for m in range(nb, M)]
        orders.append(acts)
    return orders


def interleaved_order(n_devices: int, n_virtual: int,
                      n_microbatches: int) -> List[List[Action]]:
    """Interleaved 1F1B over V virtual stages per device (Megatron-LM style,
    upstream ``ScheduleInterleaved1F1B``, SURVEY.md U4).

    Global stage v * D + d lives on device d (wrap placement). Forwards are
    issued in rounds of ``mb_per_round`` microbatches per virtual stage;
    warmup depth is ``(V-1) * mb_per_round + 2 * (D-1-d)``; steady state is
    one-forward-one-backward; backward virtual-stage order is reversed.
    Upstream requires ``n_mb % num_rounds == 0`` with
    ``num_rounds = max(1, n_mb // D)`` (``schedules.py:2935-2942``).

    With V == 1 this degenerates to the plain 1F1B layout — matching the
    reference's fallback when ``n_layers % (world_size*2) != 0``
    (``LLMsDistributedTrainingHelper.py:181-185``).
    """
    D, V, M = n_devices, n_virtual, n_microbatches
    if V == 1:
        return one_f_one_b_order(D, M)
    num_rounds = max(1, M // D)
    if M % num_rounds != 0:
        raise ScheduleError(
            f"Interleaved1F1B requires n_microbatches % num_rounds == 0 "
            f"(M={M}, num_rounds={num_rounds})")
    mbpr = M // num_rounds  # microbatches per round

    def fwd_vm(i: int) -> Tuple[int, int]:
        v = (i // mbpr) % V
        m = (i // (mbpr * V)) * mbpr + (i % mbpr)
        return v, m

    def bwd_vm(j: int) -> Tuple[int, int]:
        v = V - 1 - ((j // mbpr) % V)
        m = (j // (mbpr * V)) * mbpr + (j % mbpr)
        return v, m

    total = M * V
    orders = []
    for d in range(D):
        warmup = min(total, (V - 1) * mbpr + 2 * (D - 1 - d))
        acts = []
        nf = nb = 0
        for _ in range(warmup):
            v, m = fwd_vm(nf)
            acts.append(Action(v * D + d, F, m))
            nf += 1
        while nf < total:  # steady state
            v, m = fwd_vm(nf)
            acts.append(Action(v * D + d, F, m))
            nf += 1
            v, m = bwd_vm(nb)
            acts.append(Action(v * D + d, B, m))
            nb += 1
        while nb < total:  # cooldown
            v, m = bwd_vm(nb)
            acts.append(Action(v * D + d, B, m))
            nb += 1
        orders.append(acts)
    return orders


def bfs_order(n_devices: int, n_virtual: int,
              n_microbatches: int) -> List[List[Action]]:
    """BFS (breadth-first) pipeline: GPipe generalized to V virtual stages
    per device with wrap placement (Lamy-Poirier, arXiv:2211.05953).

    Per device: all forwards in (virtual, microbatch) lexicographic order —
    every microbatch sweeps virtual stage v before any touches v+1 — then
    all backwards with the virtual order reversed. With V == 1 this *is*
    GPipe's fill-drain. Versus Interleaved-1F1B it keeps GPipe's simple
    all-F-then-all-B structure (activation memory O(M*V), no steady-state
    interleaving) while shrinking the bubble the same way: per-device work
    grows to 2MV unit ticks against the same ~2(D-1) ramp.

    Beyond-parity: the reference's three schedules (SURVEY.md U2-U4) do not
    include BFS; it completes the depth-first (interleaved) vs breadth-first
    axis of the virtual-stage design space.
    """
    D, V, M = n_devices, n_virtual, n_microbatches
    orders = []
    for d in range(D):
        acts = [Action(v * D + d, F, m)
                for v in range(V) for m in range(M)]
        acts += [Action(v * D + d, B, m)
                 for v in reversed(range(V)) for m in range(M)]
        orders.append(acts)
    return orders


def _zb_greedy_order(D: int, M: int, S: int, device_of,
                     live_cap_of, label: str) -> List[List[Action]]:
    """Greedy priority synthesis shared by the zero-bubble schedules.

    At each tick every device picks its highest-priority READY action:
    dgrad ``B`` first (it unblocks a neighbor), then ``F``, then ``W`` —
    so weight-grad work sinks into exactly the ticks that would otherwise
    be bubbles (warmup for late devices, cooldown for early ones). This
    is what makes the compiled tables meet the papers' makespans instead
    of approximating them (asserted against the closed forms in
    :func:`analytic_bubble_fraction` by tests/test_zero_bubble.py).
    Stage 0 elides ``B`` (no upstream to send a cotangent to; its ``W``
    carries the full parameter+embedding backward), and ``live_cap_of``
    bounds each device's in-flight forwards (F count minus W count — W is
    the releasing read of the saved input) so the greedy cannot front-load
    toward GPipe-class memory.
    """
    remaining = {(s, F, m) for s in range(S) for m in range(M)}
    remaining |= {(s, W, m) for s in range(S) for m in range(M)}
    remaining |= {(s, B, m) for s in range(1, S) for m in range(M)}
    done: Dict[Tuple[int, str, int], int] = {}
    orders: List[List[Action]] = [[] for _ in range(D)]
    t = 0
    limit = 8 * len(remaining) + 64

    def ready(s, op, m, now):
        if op == F:
            if s == 0:
                return True
            d = done.get((s - 1, F, m))
            return d is not None and d + 1 <= now
        if (s, F, m) not in done:
            return False
        if op == W:
            if s == 0:
                d = done.get((1, B, m))
                return d is not None and d + 1 <= now
            if s == S - 1:
                return True
            return (s, B, m) in done
        # dgrad B
        if s == S - 1:
            return True
        d = done.get((s + 1, B, m))
        return d is not None and d + 1 <= now

    def priority(s, op, m):
        # smaller sorts first: B before F before W; within an op, deeper
        # stages first (the return leg drains eagerly under multi-chunk
        # placements); then older microbatches
        op_rank = {B: 0, F: 1, W: 2}[op]
        return (op_rank, -s, m)

    n_f = [0] * D
    n_w = [0] * D
    while remaining:
        if t > limit:
            raise ScheduleError(f"{label} synthesis deadlocked")
        for d in range(D):
            cands = sorted(
                ((s, op, m) for (s, op, m) in remaining
                 if device_of(s) == d and ready(s, op, m, t)
                 and not (op == F and n_f[d] - n_w[d] >= live_cap_of(d))),
                key=lambda a: priority(*a))
            if cands:
                s, op, m = cands[0]
                remaining.discard((s, op, m))
                done[(s, op, m)] = t
                orders[d].append(Action(s, op, m))
                if op == F:
                    n_f[d] += 1
                elif op == W:
                    n_w[d] += 1
        t += 1
    return orders


def zb_h1_order(n_devices: int, n_microbatches: int) -> List[List[Action]]:
    """ZB-H1 zero-bubble schedule (Qi et al., arXiv:2401.10241): the full
    backward is split into an input-grad half ``B`` (on the critical path —
    it unblocks the upstream stage) and a weight-grad half ``W`` (off the
    critical path — it fills what would otherwise be bubble ticks).

    Upstream torch.distributed.pipelining exposes exactly this split as
    ``stage_backward_input`` / ``stage_backward_weight``
    (``_backward.py:177,281`` — SURVEY.md U5); the reference's three
    schedules never exercise it, so this schedule is beyond-parity.

    Orders come from the shared greedy synthesis (V=1, stage == device).
    The in-flight cap is ``2D - d``: eliding stage 0's dgrad means the
    first W (the releasing read) cannot exist before the first cotangent
    makes the full ~2D-tick round trip, so hitting the paper's makespan
    requires stage 0 to front-run up to 2D forwards — a deliberate
    memory-for-makespan trade (the paper's uniform-work H1 peaks at ~D
    in-flight but runs M more actions; ours runs fewer actions and banks
    deeper on the first stage). Tighter caps (e.g. ``D - d + 1``) stall
    device 0's forwards during the ramp and sit 1..(D-3) ticks over the
    ``3M + D - 1`` optimum, which the compiled table now meets exactly
    (asserted against :func:`analytic_bubble_fraction`'s closed form).
    """
    D, M = n_devices, n_microbatches
    if D < 2:
        raise ScheduleError("ZBH1 requires n_devices >= 2 (loss lives on the "
                            "last stage's dgrad unit, which stage 0 elides)")
    if M < D:
        raise ScheduleError(f"ZBH1 requires n_microbatches >= n_devices ({M} < {D})")
    return _zb_greedy_order(D, M, D, lambda s: s,
                            lambda d: 2 * D - d, "ZBH1")


def zb_v_order(n_devices: int, n_microbatches: int) -> List[List[Action]]:
    """ZB-V (Qi et al., arXiv:2401.10241 §4): 2 chunks per device in the
    V-shaped placement — device d holds stages d and 2D-1-d, so the last
    forward stage and the first backward stage share device 0 and cotangents
    begin flowing with no cross-device turnaround. Combined with the
    dgrad/wgrad split, the warm pipeline has (near-)zero bubble at 1F1B's
    activation memory.

    The per-device order is synthesized by a greedy priority simulation
    rather than transcribed from the paper's figure: at each tick every
    device picks its highest-priority READY action (dgrad B first — it
    unblocks a neighbor — then F, then W to fill leftover ticks), with
    chunk-1 work preferred over chunk-0 so the V's return leg drains
    eagerly. The validator/tick-scheduler then re-checks the result like
    any other order. Stage 0 elides B per the ZB-H1 convention (no upstream
    to send a cotangent to; its W carries the full parameter backward).
    """
    D, M = n_devices, n_microbatches
    if D < 2:
        raise ScheduleError("ZBV requires n_devices >= 2")
    if M < 2 * D:
        raise ScheduleError(
            f"ZBV requires n_microbatches >= 2 * n_devices ({M} < {2 * D}); "
            f"fewer microbatches cannot fill the V's steady state")
    # Activation-memory cap ~2D+2 live stage inputs per device: without it
    # the greedy front-loads every forward and peak memory degrades to
    # GPipe's O(M·V); with it the slot allocator recovers 1F1B-class O(D)
    # buffers (asserted in tests). The cap never deadlocks: B/W chains are
    # always schedulable once their forwards ran.
    return _zb_greedy_order(D, M, 2 * D,
                            lambda s: placement_device_of("vshape", s, D),
                            lambda d: 2 * D + 2, "ZBV")


def build_order(name: str, n_devices: int, n_virtual: int,
                n_microbatches: int) -> List[List[Action]]:
    if name in _CUSTOM_SCHEDULES:
        return _CUSTOM_SCHEDULES[name][0](n_devices, n_virtual, n_microbatches)
    if name == "ZBV":
        if n_virtual != 2:
            raise ScheduleError("ZBV runs exactly 2 chunks per device "
                                "(set n_virtual=2)")
        return zb_v_order(n_devices, n_microbatches)
    if name == "ZBH1":
        if n_virtual != 1:
            raise ScheduleError("ZBH1 supports a single stage per device")
        return zb_h1_order(n_devices, n_microbatches)
    if name == "GPipe":
        if n_virtual != 1:
            raise ScheduleError("GPipe supports a single stage per device")
        return gpipe_order(n_devices, n_microbatches)
    if name == "1F1B":
        if n_virtual != 1:
            raise ScheduleError("1F1B supports a single stage per device")
        return one_f_one_b_order(n_devices, n_microbatches)
    if name == "Interleaved1F1B":
        return interleaved_order(n_devices, n_virtual, n_microbatches)
    if name == "BFS":
        return bfs_order(n_devices, n_virtual, n_microbatches)
    raise ScheduleError(f"unknown schedule {name!r}")


# ---------------------------------------------------------------------------
# Stage placements
# ---------------------------------------------------------------------------
#
# "wrap" (the reference's ``stage = rank + world_size * v``): device(s) = s % D.
# Inter-stage transfers always travel +1 (fwd) / -1 (bwd) on the device ring.
#
# "vshape" (ZB-V, Qi et al. arXiv:2401.10241): V=2 chunks per device laid out
# as a V — device(s) = s for s < D, else 2D-1-s. The s=D-1 -> D transfer stays
# on-device; chunk-1 forwards travel -1 on the ring (and their cotangents +1).


def placement_device_of(placement: str, stage: int, D: int) -> int:
    if placement == "wrap":
        return stage % D
    if placement == "vshape":
        return stage if stage < D else 2 * D - 1 - stage
    raise ScheduleError(f"unknown placement {placement!r}")


def placement_chunk_of(placement: str, stage: int, D: int) -> int:
    """The local chunk index v such that stage_of(device, v) == stage."""
    if placement == "wrap":
        return stage // D
    if placement == "vshape":
        return 0 if stage < D else 1
    raise ScheduleError(f"unknown placement {placement!r}")


def placement_stage_of(placement: str, d: int, v: int, D: int) -> int:
    if placement == "wrap":
        return v * D + d
    if placement == "vshape":
        return d if v == 0 else 2 * D - 1 - d
    raise ScheduleError(f"unknown placement {placement!r}")


# ---------------------------------------------------------------------------
# Tick scheduling (ASAP list scheduler)
# ---------------------------------------------------------------------------


def schedule_ticks(orders: List[List[Action]], n_devices: int, n_virtual: int,
                   placement: str = "wrap") -> Tuple[Dict[Action, int], int]:
    """Assign each action a tick. Returns (action -> tick, makespan).

    Rules: per-device actions are placed in list order; a device takes, in
    one tick, its next forward and its next full backward — the F and the B
    slot every row of the tick table has, which the executor runs in that
    order whatever the list order was (a *packed* tick). F(s, m) needs
    F(s-1, m) completed >= 1 tick earlier (ppermute latency; a same-device
    inter-stage transfer — vshape's s=D-1 -> D hop — is held to the same
    rule), B(s, m) needs F(s, m) (same device, input saved locally: the
    same tick will do, the F slot runs first — the last stage's F(m) and
    B(m) share a tick) and B(s+1, m) >= 1 tick earlier. A slot is reusable
    only from ``release_tick + 1``, so a forward packed beside a backward
    listed before it never overwrites what that backward reads.

    Split-backward orders (any ``W`` in them) keep ONE unit per device per
    tick. Their synthesis (:func:`_zb_greedy_order`) already fills every
    unit tick of every device — that is what meets the papers' makespans —
    and a hop costs a tick however long the tick is, so packing them
    lengthens the ticks along the dependency chain and costs more under
    lock-step than it saves (ZBV D=4 M=8, weights F, B, W = 1, 2, 2:
    94 unpacked, 153 packed; ZBH1 D=8 M=16: 102 and 118). A W unit needs
    its dgrad twin B(s, m) done, the same tick not included then.

    This is the deadlock-freedom analog of upstream's ``_validate_schedule``
    (``schedules.py:1619``) plus gloo's peer-sorted P2P batching
    (SURVEY.md §5 race-detection row): here deadlocks surface as a scheduling
    error at compile time rather than a hang at run time.
    """
    D = n_devices
    S = D * n_virtual
    n_actions = sum(len(o) for o in orders)
    done: Dict[Action, int] = {}
    ptr = [0] * D
    t = 0
    limit = 4 * n_actions + 4 * S + 16

    split = any(a.op == W for o in orders for a in o)

    def device_of(stage: int) -> int:
        return placement_device_of(placement, stage, D)

    def ready(a: Action, now: int) -> bool:
        if a.op == F:
            if a.stage == 0:
                return True
            dep = Action(a.stage - 1, F, a.microbatch)
            # one tick of ppermute latency (for D == 1 too: the next stage's
            # F slot of the same tick has already run)
            return dep in done and done[dep] + 1 <= now
        if Action(a.stage, F, a.microbatch) not in done:
            return False
        if a.op == W:
            # wgrad: needs the incoming cotangent. Stage 0 (no B of its own)
            # waits for the ppermute arrival from B(1, m); other stages'
            # same-device B already proved the cotangent is banked.
            if a.stage == 0:
                dep = Action(1, B, a.microbatch)
                return dep in done and done[dep] + 1 <= now
            if a.stage == S - 1:
                return True  # CE recompute needs no incoming cotangent
            return Action(a.stage, B, a.microbatch) in done
        # backward (full or dgrad)
        if a.stage == S - 1:
            return True
        dep = Action(a.stage + 1, B, a.microbatch)
        return dep in done and done[dep] + 1 <= now

    while any(ptr[d] < len(orders[d]) for d in range(D)):
        if t > limit:
            raise ScheduleError("schedule deadlocked: no progress within tick limit")
        for d in range(D):
            taken = set()  # kinds this device already runs in tick t
            while ptr[d] < len(orders[d]):
                a = orders[d][ptr[d]]
                if device_of(a.stage) != d:
                    raise ScheduleError(f"action {a} listed on device {d}")
                if a.op in taken or not ready(a, t):
                    break
                done[a] = t
                taken.add(a.op)
                ptr[d] += 1
                if split:
                    break
        t += 1
    return done, t


def validate_order(orders: List[List[Action]], n_devices: int, n_virtual: int,
                   n_microbatches: int, split_backward: bool = False,
                   placement: str = "wrap") -> None:
    """Structural validation: every (stage, microbatch) has exactly one F and
    one full B (or, under a split schedule, one W plus one dgrad B for every
    stage except 0), F precedes B/W per device, W follows its dgrad twin
    (whose saved slots it aliases), and the tick scheduler completes.
    Error messages carry a (device, index) location prefix — the device and
    per-device order position of the offending action."""
    S = n_devices * n_virtual
    seen: Dict[Action, int] = {}
    for d, order in enumerate(orders):
        pos = {}
        for i, a in enumerate(order):
            if a in seen:
                raise ScheduleError(
                    f"(device {d}, index {i}): duplicate action {a} "
                    f"(first listed on device {seen[a]})")
            seen[a] = d
            pos[a] = i
        for a in order:
            if a.op in (B, W):
                fa = Action(a.stage, F, a.microbatch)
                if fa not in pos or pos[fa] > pos[a]:
                    raise ScheduleError(
                        f"(device {d}, index {pos[a]}): backward before "
                        f"forward: {a}")
            if a.op == W and a.stage >= 1:
                # split-backward W reuses the dgrad B unit's saved slots
                # (COL_W_ASLOT/COL_W_GSLOT alias COL_BWD_ASLOT/GSLOT, see
                # analysis.table_check's w-slot-alias hazard) — so B(s, m)
                # must precede W(s, m) in the same device order or the
                # aliased slots would not exist yet. Stage 0 has no B; its
                # W reads F(0, m)'s own slot.
                ba = Action(a.stage, B, a.microbatch)
                if ba not in pos or pos[ba] > pos[a]:
                    raise ScheduleError(
                        f"(device {d}, index {pos[a]}): {a} precedes its "
                        f"dgrad twin {ba}, whose saved slots it aliases")
    want = {Action(s, F, m) for s in range(S) for m in range(n_microbatches)}
    if split_backward:
        want |= {Action(s, W, m) for s in range(S) for m in range(n_microbatches)}
        want |= {Action(s, B, m) for s in range(1, S) for m in range(n_microbatches)}
    else:
        want |= {Action(s, B, m) for s in range(S) for m in range(n_microbatches)}
    if set(seen) != want:
        raise ScheduleError(
            f"action set mismatch: {len(seen)} actions vs expected {len(want)} "
            f"(missing {list(want - set(seen))[:4]}, "
            f"extra {list(set(seen) - want)[:4]})")
    schedule_ticks(orders, n_devices, n_virtual,
                   placement=placement)  # raises on deadlock


# ---------------------------------------------------------------------------
# Tick tables for the SPMD executor
# ---------------------------------------------------------------------------

# Columns of the per-(tick, device) table. -1 means "no-op this tick".
# Buffers are slot-addressed: slots are allocated from actual activation
# lifetimes, so 1F1B keeps its O(in-flight) activation-memory advantage over
# GPipe's O(M) instead of always allocating M microbatch buffers.
COL_STORE_F_SLOT = 0  # store +1-channel fwd arrival -> act_buf[slot]
COL_FWD_V, COL_FWD_M, COL_FWD_SLOT = 1, 2, 3  # forward unit: (v, m), input slot
COL_STORE_B_SLOT = 4  # store -1-channel grad arrival -> grad_buf[slot]
COL_BWD_V, COL_BWD_M = 5, 6  # backward unit: (v, m)
COL_BWD_ASLOT, COL_BWD_GSLOT = 7, 8  # saved-input slot, incoming-grad slot
COL_W_V, COL_W_M = 9, 10  # weight-grad unit (split schedules): (v, m)
COL_W_ASLOT, COL_W_GSLOT = 11, 12  # its saved-input slot, incoming-grad slot
# vshape-placement routes (always -1 under wrap placement, so wrap tables
# are bit-identical to the 13-column era):
N_COLS_CLASSIC = 13  # the wrap-placement-only column count
COL_FWD_LOCAL_SLOT = 13  # fwd output -> OWN act_buf[slot] (same-device hop)
COL_STORE_F_NEG_SLOT = 14  # store -1-channel fwd arrival -> act_buf[slot]
COL_BWD_LOCAL_SLOT = 15  # bwd cotangent -> OWN grad_buf[slot]
COL_STORE_B_POS_SLOT = 16  # store +1-channel grad arrival -> grad_buf[slot]
N_COLS = 17


def fwd_route(placement: str, s: int, D: int) -> str:
    """Where F(s)'s output travels to reach stage s+1: '+1' ring, '-1' ring,
    or 'local' (same device)."""
    if placement == "wrap":
        return "+1"
    if s == D - 1:
        return "local"  # the V's turning point
    return "+1" if s < D - 1 else "-1"


def bwd_route(placement: str, s: int, D: int) -> str:
    """Where B(s)'s cotangent travels to reach stage s-1."""
    if placement == "wrap":
        return "-1"
    if s == D:
        return "local"
    return "+1" if s > D else "-1"


@dataclasses.dataclass(frozen=True)
class CompiledSchedule:
    name: str
    n_devices: int
    n_virtual: int
    n_microbatches: int
    table: np.ndarray  # [T, D, N_COLS] int32
    makespan: int
    ticks: Dict[Action, int]
    n_act_slots: int
    n_grad_slots: int
    # True when B actions are dgrad-only and W actions carry the parameter
    # gradients (ZB-H1 family; custom schedules declare it at registration).
    # Captured at compile time — a live registry lookup would let a later
    # unregister/overwrite silently change an already-compiled schedule's
    # semantics.
    split_backward: bool = False
    # "wrap" (stage = v*D + d) or "vshape" (ZB-V: device d holds stages d
    # and 2D-1-d; some transfers ride the -1 ring or stay on-device).
    placement: str = "wrap"

    @property
    def n_stages(self) -> int:
        return self.n_devices * self.n_virtual

    @property
    def uses_reverse_routes(self) -> bool:
        """True when the table uses the -1 fwd / +1 bwd channels or local
        hops — the executor then issues the two extra ppermutes."""
        return bool(np.any(self.table[:, :, N_COLS_CLASSIC:] >= 0))

    @property
    def work_cells(self) -> int:
        """(tick, device) cells that run at least one unit."""
        return int((1 - table_unit_activity(self.table)[..., 3]).sum())

    @property
    def packed(self) -> int:
        """(tick, device) cells that run more than one unit — how often
        :func:`schedule_ticks`'s packing engages (20 of 1F1B D=4 M=8's 56
        cells)."""
        return int((table_unit_activity(self.table)[..., :3].sum(-1) > 1).sum())


def _allocate_slots(events: List[Tuple[int, int, object]]) -> Tuple[Dict[object, int], int]:
    """Greedy interval slot allocation.

    ``events`` is a list of (store_tick, release_tick, key): the slot is
    written at ``store_tick`` and may be reused for stores at
    ``release_tick + 1`` onwards (release_tick is the tick whose compute
    reads it last). Returns (key -> slot, n_slots).
    """
    by_store = sorted(events, key=lambda e: (e[0], e[1]))
    free: List[int] = []
    in_use: List[Tuple[int, int]] = []  # (release_tick, slot)
    n_slots = 0
    assign: Dict[object, int] = {}
    for store, release, key in by_store:
        while in_use and in_use[0][0] < store:
            _, slot = heapq.heappop(in_use)
            heapq.heappush(free, slot)
        if free:
            slot = heapq.heappop(free)
        else:
            slot = n_slots
            n_slots += 1
        assign[key] = slot
        heapq.heappush(in_use, (release, slot))
    return assign, n_slots


@annotate("setup/schedule")
def compile_schedule(name: str, n_devices: int, n_virtual: int,
                     n_microbatches: int) -> CompiledSchedule:
    """Generate, validate, and lower a schedule to executor tick tables.
    Kept as the host span ``setup/schedule`` (order generation, table,
    self-check), as its native twin is.

    The lowering is the SPMD analog of upstream's comm insertion
    (``_add_send_recv`` / ``_prepare_schedule_with_comms``,
    ``schedules.py:1406, 2279`` — SURVEY.md U5): instead of SEND/RECV actions,
    every tick ends with a fwd ``ppermute`` (+1 ring) and a bwd ``ppermute``
    (-1 ring), and the table records which arrivals carry real data and which
    buffer slot holds each live value. The compiled table is self-checked by
    :func:`verify_table` (a symbolic interpreter) before being returned.
    """
    D, V, M = n_devices, n_virtual, n_microbatches
    split = is_split_backward(name)
    placement = schedule_placement(name)
    orders = build_order(name, D, V, M)
    cs = compile_order(name, orders, D, V, M, split_backward=split,
                       placement=placement)
    verify_artifact_pin(cs)
    return cs


def compile_order(name: str, orders: List[List[Action]], n_devices: int,
                  n_virtual: int, n_microbatches: int, *,
                  split_backward: bool = False, placement: str = "wrap",
                  verify: bool = True) -> CompiledSchedule:
    """Lower explicit per-device action orders to a verified tick table.

    This is :func:`compile_schedule` minus the order *generation* step: the
    caller supplies the per-device :class:`Action` lists directly, which is
    what the schedule-search pass (``analysis.schedule_search``) and the
    artifact loader need — both own their orders and must compile thousands
    of candidate permutations without registering each one. ``verify=False``
    skips the :func:`verify_table` self-check (the search certifies
    candidates with the richer ``analysis.check_table`` instead); validation
    of the action set and deadlock-freedom always runs.
    """
    D, V, M = n_devices, n_virtual, n_microbatches
    split = split_backward
    validate_order(orders, D, V, M, split_backward=split,
                   placement=placement)
    ticks, T_compute = schedule_ticks(orders, D, V, placement=placement)
    S = D * V

    def device_of(s):
        return placement_device_of(placement, s, D)

    # +1: arrivals land one tick after the producing compute; the final
    # backward of stage 0 produces no arrival, but a last-tick forward of a
    # non-final stage (never happens in practice) would need T_compute + 1.
    T = T_compute + 1

    # Activation lifetimes per device: input of stage s for microbatch m is
    # written at the producer's tick + 1 (ring arrival) — at the producer's
    # tick itself for a same-device hop, or at the forward tick for global
    # stage 0 (the embed is computed in place) — and last read by B(s, m),
    # or by W(s, m) under a split schedule (W runs after B by list order, so
    # W is the releasing read). Grad lifetimes mirror this for B(s+1, m).
    act_events: List[List[Tuple[int, int, object]]] = [[] for _ in range(D)]
    grad_events: List[List[Tuple[int, int, object]]] = [[] for _ in range(D)]
    for a, t in ticks.items():
        if a.op != F:
            continue
        d = device_of(a.stage)
        if a.stage == 0:
            store = t
        else:
            pt = ticks[Action(a.stage - 1, F, a.microbatch)]
            local = fwd_route(placement, a.stage - 1, D) == "local"
            store = pt if local else pt + 1
        release = max(ticks[r] for r in (Action(a.stage, B, a.microbatch),
                                         Action(a.stage, W, a.microbatch))
                      if r in ticks)
        act_events[d].append((store, release, (a.stage, a.microbatch)))
    for s in range(S - 1):
        d = device_of(s)
        for m in range(M):
            pt = ticks[Action(s + 1, B, m)]
            local = bwd_route(placement, s + 1, D) == "local"
            store = pt if local else pt + 1
            release = max(ticks[r] for r in (Action(s, B, m), Action(s, W, m))
                          if r in ticks)
            grad_events[d].append((store, release, (s, m)))

    act_assign, n_act = [], 0
    grad_assign, n_grad = [], 0
    for d in range(D):
        assign, n = _allocate_slots(act_events[d])
        act_assign.append(assign)
        n_act = max(n_act, n)
        assign, n = _allocate_slots(grad_events[d])
        grad_assign.append(assign)
        n_grad = max(n_grad, n)
    n_grad = max(n_grad, 1)  # executor buffers cannot be zero-sized

    table = np.full((T, D, N_COLS), -1, dtype=np.int32)
    for a, t in ticks.items():
        d = device_of(a.stage)
        v = placement_chunk_of(placement, a.stage, D)
        if a.op == F:
            slot = act_assign[d][(a.stage, a.microbatch)]
            table[t, d, COL_FWD_V] = v
            table[t, d, COL_FWD_M] = a.microbatch
            table[t, d, COL_FWD_SLOT] = slot
            if a.stage < S - 1:
                nd = device_of(a.stage + 1)
                nslot = act_assign[nd][(a.stage + 1, a.microbatch)]
                route = fwd_route(placement, a.stage, D)
                if route == "local":
                    table[t, d, COL_FWD_LOCAL_SLOT] = nslot
                elif route == "+1":
                    table[t + 1, nd, COL_STORE_F_SLOT] = nslot
                else:  # "-1"
                    table[t + 1, nd, COL_STORE_F_NEG_SLOT] = nslot
        elif a.op == B:
            table[t, d, COL_BWD_V] = v
            table[t, d, COL_BWD_M] = a.microbatch
            table[t, d, COL_BWD_ASLOT] = act_assign[d][(a.stage, a.microbatch)]
            if a.stage < S - 1:
                table[t, d, COL_BWD_GSLOT] = grad_assign[d][(a.stage, a.microbatch)]
            if a.stage > 0:
                pd = device_of(a.stage - 1)
                pslot = grad_assign[pd][(a.stage - 1, a.microbatch)]
                route = bwd_route(placement, a.stage, D)
                if route == "local":
                    table[t, d, COL_BWD_LOCAL_SLOT] = pslot
                elif route == "-1":
                    table[t + 1, pd, COL_STORE_B_SLOT] = pslot
                else:  # "+1"
                    table[t + 1, pd, COL_STORE_B_POS_SLOT] = pslot
        else:  # W (wgrad)
            table[t, d, COL_W_V] = v
            table[t, d, COL_W_M] = a.microbatch
            table[t, d, COL_W_ASLOT] = act_assign[d][(a.stage, a.microbatch)]
            if a.stage < S - 1:
                table[t, d, COL_W_GSLOT] = grad_assign[d][(a.stage, a.microbatch)]
    # Trim trailing all-empty ticks (keeps the executor scan minimal).
    while T > 1 and np.all(table[T - 1] == -1):
        T -= 1
    cs = CompiledSchedule(name, D, V, M, table[:T], T, ticks, n_act, n_grad,
                          split_backward=split, placement=placement)
    if verify:
        verify_table(cs)
    return cs


def verify_table(cs: CompiledSchedule) -> None:
    """Symbolic interpreter over the compiled table: executes the exact
    store/compute/permute contract the SPMD executor uses — four transfer
    channels (+1/-1 for each direction) plus same-device hops — and checks
    that every forward reads the right stage input and every backward reads
    the right saved input and incoming cotangent. Raises ScheduleError on
    any stale read, overwrite of a live value, or missing data."""
    D, V, S = cs.n_devices, cs.n_virtual, cs.n_stages
    pl = cs.placement
    act = [dict() for _ in range(D)]   # slot -> ("act", stage, mb)
    grad = [dict() for _ in range(D)]  # slot -> ("gout", stage, mb)
    fwd_in = [None] * D  # value delivered by last tick's +1 fwd ppermute
    fwd_in_neg = [None] * D  # ... -1 fwd channel (vshape chunk-1 forwards)
    bwd_in = [None] * D  # -1 bwd channel
    bwd_in_pos = [None] * D  # +1 bwd channel (vshape chunk-1 cotangents)
    fwd_done = set()
    bwd_done = set()
    w_done = set()
    for t in range(cs.table.shape[0]):
        fwd_send = [None] * D  # routed to +1, -1, or local per fwd_route
        fwd_send_neg = [None] * D
        bwd_send = [None] * D
        bwd_send_pos = [None] * D
        for d in range(D):
            row = cs.table[t, d]
            if row[COL_STORE_F_SLOT] >= 0:
                if fwd_in[d] is None:
                    raise ScheduleError(f"(device {d}, tick {t}): fwd store of empty register")
                act[d][int(row[COL_STORE_F_SLOT])] = fwd_in[d]
            if row[COL_STORE_F_NEG_SLOT] >= 0:
                if fwd_in_neg[d] is None:
                    raise ScheduleError(
                        f"(device {d}, tick {t}): fwd-neg store of empty register")
                act[d][int(row[COL_STORE_F_NEG_SLOT])] = fwd_in_neg[d]
            if row[COL_STORE_B_SLOT] >= 0:
                if bwd_in[d] is None:
                    raise ScheduleError(f"(device {d}, tick {t}): bwd store of empty register")
                grad[d][int(row[COL_STORE_B_SLOT])] = bwd_in[d]
            if row[COL_STORE_B_POS_SLOT] >= 0:
                if bwd_in_pos[d] is None:
                    raise ScheduleError(
                        f"(device {d}, tick {t}): bwd-pos store of empty register")
                grad[d][int(row[COL_STORE_B_POS_SLOT])] = bwd_in_pos[d]
            if row[COL_FWD_M] >= 0:
                s = placement_stage_of(pl, d, int(row[COL_FWD_V]), D)
                m = int(row[COL_FWD_M])
                slot = int(row[COL_FWD_SLOT])
                if s == 0:
                    act[d][slot] = ("act", 0, m)  # embed computed in place
                got = act[d].get(slot)
                if got != ("act", s, m):
                    raise ScheduleError(
                        f"(device {d}, tick {t}): F(stage={s}, mb={m}) read slot {slot} "
                        f"holding {got}")
                if s < S - 1:
                    route = fwd_route(pl, s, D)
                    if route == "local":
                        if row[COL_FWD_LOCAL_SLOT] < 0:
                            raise ScheduleError(
                                f"(device {d}, tick {t}): F(stage={s}) local route "
                                f"without COL_FWD_LOCAL_SLOT")
                        act[d][int(row[COL_FWD_LOCAL_SLOT])] = ("act", s + 1, m)
                    elif route == "+1":
                        fwd_send[d] = ("act", s + 1, m)
                    else:
                        fwd_send_neg[d] = ("act", s + 1, m)
                fwd_done.add((s, m))
            if row[COL_BWD_M] >= 0:
                s = placement_stage_of(pl, d, int(row[COL_BWD_V]), D)
                m = int(row[COL_BWD_M])
                aslot = int(row[COL_BWD_ASLOT])
                got = act[d].get(aslot)
                if got != ("act", s, m):
                    raise ScheduleError(
                        f"(device {d}, tick {t}): B(stage={s}, mb={m}) saved-input slot "
                        f"{aslot} holds {got}")
                if s < S - 1:
                    gslot = int(row[COL_BWD_GSLOT])
                    gg = grad[d].get(gslot)
                    if gg != ("gout", s, m):
                        raise ScheduleError(
                            f"(device {d}, tick {t}): B(stage={s}, mb={m}) grad slot "
                            f"{gslot} holds {gg}")
                if s > 0:
                    route = bwd_route(pl, s, D)
                    if route == "local":
                        if row[COL_BWD_LOCAL_SLOT] < 0:
                            raise ScheduleError(
                                f"(device {d}, tick {t}): B(stage={s}) local route "
                                f"without COL_BWD_LOCAL_SLOT")
                        grad[d][int(row[COL_BWD_LOCAL_SLOT])] = ("gout", s - 1, m)
                    elif route == "-1":
                        bwd_send[d] = ("gout", s - 1, m)
                    else:
                        bwd_send_pos[d] = ("gout", s - 1, m)
                bwd_done.add((s, m))
            if row[COL_W_M] >= 0:
                s = placement_stage_of(pl, d, int(row[COL_W_V]), D)
                m = int(row[COL_W_M])
                aslot = int(row[COL_W_ASLOT])
                got = act[d].get(aslot)
                if got != ("act", s, m):
                    raise ScheduleError(
                        f"(device {d}, tick {t}): W(stage={s}, mb={m}) saved-input slot "
                        f"{aslot} holds {got}")
                if s < S - 1:
                    gslot = int(row[COL_W_GSLOT])
                    gg = grad[d].get(gslot)
                    if gg != ("gout", s, m):
                        raise ScheduleError(
                            f"(device {d}, tick {t}): W(stage={s}, mb={m}) grad slot "
                            f"{gslot} holds {gg}")
                w_done.add((s, m))
        fwd_in = [fwd_send[(d - 1) % D] for d in range(D)]
        fwd_in_neg = [fwd_send_neg[(d + 1) % D] for d in range(D)]
        bwd_in = [bwd_send[(d + 1) % D] for d in range(D)]
        bwd_in_pos = [bwd_send_pos[(d - 1) % D] for d in range(D)]
    want = {(s, m) for s in range(S) for m in range(cs.n_microbatches)}
    if cs.split_backward:
        want_b = {(s, m) for s in range(1, S) for m in range(cs.n_microbatches)}
        ok = fwd_done == want and bwd_done == want_b and w_done == want
    else:
        ok = fwd_done == want and bwd_done == want and not w_done
    if not ok:
        raise ScheduleError("table does not execute every (stage, microbatch)")


# ---------------------------------------------------------------------------
# Schedule artifacts: certified, versioned JSON interchange for searched
# (or otherwise externally produced) schedules. An artifact carries the
# per-device action orders, the compiled [T, D, 17] table, a config
# fingerprint over its metadata, and (when emitted by the search) the
# embedded TableReport summary plus predicted cost. Loading recompiles the
# orders and certifies the stored table cell-by-cell, so a tampered or
# stale artifact fails with an exact (device, tick, column) location.
# ---------------------------------------------------------------------------

SCHEDULE_ARTIFACT_VERSION = 1
SCHEDULE_ARTIFACT_KIND = "schedule_artifact"

# Artifact-backed registered schedules: name -> pin. compile_schedule and
# pipeline._compile re-check the pin (verify_artifact_pin) so a re-registered
# order function can never silently swap a certified table.
_ARTIFACT_PINS: Dict[str, Dict[str, str]] = {}


def table_digest(table: np.ndarray) -> str:
    """Content digest of a tick table (shape + little-endian int32 cells)."""
    arr = np.ascontiguousarray(np.asarray(table, dtype="<i4"))
    h = hashlib.sha256()
    h.update(repr(arr.shape).encode())
    h.update(arr.tobytes())
    return h.hexdigest()


_FINGERPRINT_FIELDS = (
    "artifact_version", "kind", "name", "n_devices", "n_virtual",
    "n_microbatches", "placement", "split_backward", "n_act_slots",
    "n_grad_slots", "makespan", "verifier_version", "table_digest")


def _artifact_fingerprint(art: Dict[str, object]) -> str:
    payload = {k: art.get(k) for k in _FINGERPRINT_FIELDS}
    blob = json.dumps(payload, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


def _orders_from_ticks(cs: CompiledSchedule) -> List[List[Action]]:
    """Recover per-device action orders from a Python-compiled schedule's
    tick assignment. :func:`schedule_ticks` fills the map as it places —
    tick by tick and, within a device, in list order — so the map's own
    order, split by device, IS the order that was compiled (a tick may hold
    several of a device's actions, and sorting them would not reproduce the
    table)."""
    if not cs.ticks:
        raise ScheduleError(
            f"schedule {cs.name!r} has no tick map (natively compiled?); "
            "cannot recover per-device orders for an artifact")
    orders: List[List[Action]] = [[] for _ in range(cs.n_devices)]
    for a in cs.ticks:
        orders[placement_device_of(cs.placement, a.stage, cs.n_devices)].append(a)
    return orders


def schedule_artifact(cs: CompiledSchedule, *,
                      orders: Optional[List[List[Action]]] = None,
                      seed: Optional[int] = None,
                      table_report: Optional[Dict[str, object]] = None,
                      predicted: Optional[Dict[str, object]] = None,
                      baselines: Optional[Dict[str, object]] = None,
                      search: Optional[Dict[str, object]] = None) -> Dict[str, object]:
    """Build the versioned JSON-serializable artifact for ``cs``.

    ``table_report`` is a ``TableReport.summary()`` dict (the caller runs
    ``check_table`` — this module stays import-clean of ``analysis``);
    ``predicted`` is the cost-model dict; both are embedded verbatim.
    The ``config_fingerprint`` signs the metadata fields only — table cells
    are covered separately by ``table_digest`` plus the loader's
    recompile-and-diff, which reports the exact mutated cell.
    """
    if orders is None:
        orders = _orders_from_ticks(cs)
    art: Dict[str, object] = {
        "artifact_version": SCHEDULE_ARTIFACT_VERSION,
        "kind": SCHEDULE_ARTIFACT_KIND,
        "name": cs.name,
        "n_devices": int(cs.n_devices),
        "n_virtual": int(cs.n_virtual),
        "n_microbatches": int(cs.n_microbatches),
        "placement": cs.placement,
        "split_backward": bool(cs.split_backward),
        "n_act_slots": int(cs.n_act_slots),
        "n_grad_slots": int(cs.n_grad_slots),
        "makespan": int(cs.makespan),
        "orders": [[[int(a.stage), a.op, int(a.microbatch)] for a in order]
                   for order in orders],
        "table": np.asarray(cs.table, dtype=np.int32).tolist(),
        "table_digest": table_digest(cs.table),
    }
    from ..analysis import VERIFIER_VERSION  # lazy: analysis imports us
    art["verifier_version"] = VERIFIER_VERSION
    if seed is not None:
        art["seed"] = int(seed)
    if table_report is not None:
        art["table_report"] = table_report
    if predicted is not None:
        art["predicted"] = predicted
    if baselines is not None:
        art["baselines"] = baselines
    if search is not None:
        art["search"] = search
    art["config_fingerprint"] = _artifact_fingerprint(art)
    return art


def schedule_artifact_bytes(art: Dict[str, object]) -> bytes:
    """Canonical (byte-deterministic) JSON encoding of an artifact."""
    return (json.dumps(art, sort_keys=True) + "\n").encode()


def save_schedule_artifact(art: Dict[str, object], path) -> None:
    with open(path, "wb") as fh:
        fh.write(schedule_artifact_bytes(art))


def _art_err(label: str, field: str, msg: str) -> ScheduleError:
    return ScheduleError(f"schedule artifact {label}: field {field!r}: {msg}")


def _load_artifact_dict(source) -> Tuple[Dict[str, object], str]:
    if isinstance(source, dict):
        return source, "<dict>"
    label = str(source)
    try:
        with open(source, "r", encoding="utf-8") as fh:
            art = json.load(fh)
    except OSError as e:
        raise ScheduleError(f"schedule artifact {label}: unreadable: {e}")
    except json.JSONDecodeError as e:
        raise ScheduleError(f"schedule artifact {label}: invalid JSON: {e}")
    if not isinstance(art, dict):
        raise ScheduleError(
            f"schedule artifact {label}: top level must be a JSON object, "
            f"got {type(art).__name__}")
    return art, label


def _validated_int(art: Dict[str, object], label: str, key: str,
                   minimum: int) -> int:
    v = art.get(key)
    if not isinstance(v, int) or isinstance(v, bool) or v < minimum:
        raise _art_err(label, key, f"must be an int >= {minimum}, got {v!r}")
    return v


def _load_schedule_artifact_impl(source, verify: bool,
                                 ) -> Tuple[CompiledSchedule, Dict[str, object],
                                            List[List[Action]], str]:
    art, label = _load_artifact_dict(source)
    # --- schema: every mismatch is a located ScheduleError, never a numpy
    # broadcasting error (tested with truncated columns / float cells).
    ver = art.get("artifact_version")
    if ver != SCHEDULE_ARTIFACT_VERSION:
        raise _art_err(label, "artifact_version",
                       f"unsupported version {ver!r} "
                       f"(this build reads {SCHEDULE_ARTIFACT_VERSION})")
    if art.get("kind") != SCHEDULE_ARTIFACT_KIND:
        raise _art_err(label, "kind",
                       f"expected {SCHEDULE_ARTIFACT_KIND!r}, got "
                       f"{art.get('kind')!r}")
    name = art.get("name")
    if not isinstance(name, str) or not name:
        raise _art_err(label, "name", f"must be a non-empty string, got {name!r}")
    D = _validated_int(art, label, "n_devices", 1)
    V = _validated_int(art, label, "n_virtual", 1)
    M = _validated_int(art, label, "n_microbatches", 1)
    n_act = _validated_int(art, label, "n_act_slots", 1)
    n_grad = _validated_int(art, label, "n_grad_slots", 1)
    makespan = _validated_int(art, label, "makespan", 1)
    placement = art.get("placement")
    if placement not in ("wrap", "vshape"):
        raise _art_err(label, "placement",
                       f"must be 'wrap' or 'vshape', got {placement!r}")
    split = art.get("split_backward")
    if not isinstance(split, bool):
        raise _art_err(label, "split_backward", f"must be a bool, got {split!r}")
    if not isinstance(art.get("table_digest"), str):
        raise _art_err(label, "table_digest", "must be a hex string")
    # --- stale-fingerprint check over the metadata fields, before any
    # numpy work: an edited field (say n_microbatches) fails here.
    fp = art.get("config_fingerprint")
    want_fp = _artifact_fingerprint(art)
    if fp != want_fp:
        raise _art_err(
            label, "config_fingerprint",
            "stale fingerprint: metadata was edited after the artifact was "
            f"signed (stored {str(fp)[:12]!r}, recomputed {want_fp[:12]!r})")
    # --- table structure: shape / dtype / column count.
    raw = art.get("table")
    if not isinstance(raw, list) or not raw:
        raise _art_err(label, "table",
                       f"must be a non-empty [T][D][{N_COLS}] nested list")
    try:
        arr = np.asarray(raw)
    except Exception as e:  # ragged nesting
        raise _art_err(label, "table", f"not a rectangular array: {e}")
    if arr.dtype == object or arr.ndim != 3:
        raise _art_err(label, "table",
                       f"must be rank-3 [T, D, {N_COLS}], got shape "
                       f"{arr.shape} ({arr.dtype})")
    if not np.issubdtype(arr.dtype, np.integer):
        raise _art_err(label, "table",
                       f"dtype mismatch: cells must be integers, got {arr.dtype}")
    if arr.shape[2] != N_COLS:
        raise _art_err(label, "table",
                       f"column-count mismatch: {arr.shape[2]} columns != "
                       f"N_COLS {N_COLS}")
    if arr.shape[1] != D:
        raise _art_err(label, "table",
                       f"shape mismatch: {arr.shape[1]} device rows != "
                       f"n_devices {D}")
    if arr.shape[0] != makespan:
        raise _art_err(label, "table",
                       f"shape mismatch: {arr.shape[0]} ticks != makespan "
                       f"{makespan}")
    if (arr < -1).any():
        t, d, c = (int(x) for x in np.argwhere(arr < -1)[0])
        raise _art_err(label, "table",
                       f"cell (device {d}, tick {t}, col {c}) = "
                       f"{int(arr[t, d, c])} is below -1")
    table = arr.astype(np.int32)
    # --- orders.
    raw_orders = art.get("orders")
    if not isinstance(raw_orders, list) or len(raw_orders) != D:
        raise _art_err(label, "orders",
                       f"must be a list of {D} per-device action lists, got "
                       f"{type(raw_orders).__name__} of length "
                       f"{len(raw_orders) if isinstance(raw_orders, list) else '?'}")
    orders: List[List[Action]] = []
    for d, dev in enumerate(raw_orders):
        if not isinstance(dev, list):
            raise _art_err(label, f"orders[{d}]", "must be a list")
        out: List[Action] = []
        for i, item in enumerate(dev):
            if (not isinstance(item, (list, tuple)) or len(item) != 3
                    or not isinstance(item[0], int) or isinstance(item[0], bool)
                    or item[1] not in (F, B, W)
                    or not isinstance(item[2], int) or isinstance(item[2], bool)):
                raise _art_err(label, f"orders[{d}][{i}]",
                               f"must be [stage:int, op in 'FBW', mb:int], "
                               f"got {item!r}")
            out.append(Action(int(item[0]), str(item[1]), int(item[2])))
        orders.append(out)
    # --- recompile the orders (the authoritative source) and certify the
    # stored table against the result, cell by cell.
    try:
        cs = compile_order(name, orders, D, V, M, split_backward=split,
                           placement=placement)
    except ScheduleError as e:
        raise ScheduleError(
            f"schedule artifact {label}: orders do not compile: {e}")
    if cs.n_act_slots != n_act:
        raise _art_err(label, "n_act_slots",
                       f"{n_act} != recompiled {cs.n_act_slots}")
    if cs.n_grad_slots != n_grad:
        raise _art_err(label, "n_grad_slots",
                       f"{n_grad} != recompiled {cs.n_grad_slots}")
    if cs.table.shape != table.shape or not np.array_equal(cs.table, table):
        k = min(cs.table.shape[0], table.shape[0])
        diff = np.argwhere(cs.table[:k] != table[:k])
        if diff.size:
            t, d, c = (int(x) for x in diff[0])
            col = _column_label(c)
            raise ScheduleError(
                f"schedule artifact {label}: certification failed at "
                f"(device {d}, tick {t}, {col}): stored cell "
                f"{int(table[t, d, c])} != certified value "
                f"{int(cs.table[t, d, c])} (table tampered or stale)")
        raise _art_err(label, "table",
                       f"tick count {table.shape[0]} != recompiled "
                       f"{cs.table.shape[0]}")
    if art["table_digest"] != table_digest(table):
        raise _art_err(label, "table_digest",
                       "digest does not match the stored table")
    # --- full static certification (and embedded-report consistency).
    if verify:
        from ..analysis.table_check import check_table
        report = check_table(cs)
        if report.hazards:
            h = report.hazards[0]
            raise ScheduleError(
                f"schedule artifact {label}: certification failed: {h}")
        emb = art.get("table_report")
        if emb is not None:
            if not isinstance(emb, dict):
                raise _art_err(label, "table_report", "must be an object")
            if emb.get("ok") is False or emb.get("n_hazards", 0):
                raise _art_err(label, "table_report",
                               "embeds a non-clean TableReport; refusing to "
                               "load an uncertified artifact")
            summary = report.summary()
            for key in ("makespan", "predicted_ppermutes"):
                if key in emb and emb[key] != summary[key]:
                    raise _art_err(label, f"table_report.{key}",
                                   f"{emb[key]!r} != recomputed "
                                   f"{summary[key]!r}")
    else:
        from ..analysis import maybe_verify_schedule  # DTPP_VERIFY_TABLES hook
        maybe_verify_schedule(cs)
    return cs, art, orders, label


def _column_label(c: int) -> str:
    try:
        from ..analysis.table_check import COLUMN_NAMES
        return COLUMN_NAMES.get(c, f"col {c}")
    except Exception:
        return f"col {c}"


def load_schedule_artifact(source, *, verify: bool = True) -> CompiledSchedule:
    """Load a schedule artifact (path or dict) into a CompiledSchedule.

    Validation order: JSON/schema (shape, dtype, column count) → metadata
    ``config_fingerprint`` → recompile-from-orders diff (any mutated table
    cell fails with its exact (device, tick, column)) → ``check_table``
    certification. Every failure is a located :class:`ScheduleError` naming
    the artifact and field. With ``verify=False`` the full ``check_table``
    pass is skipped but the structural checks still run and
    ``DTPP_VERIFY_TABLES`` re-verifies via the build-time hook.
    """
    cs, _art, _orders, _label = _load_schedule_artifact_impl(source, verify)
    return cs


def register_schedule_artifact(source, *, name: Optional[str] = None,
                               overwrite: bool = True) -> CompiledSchedule:
    """Load, certify, and register an artifact as a named schedule.

    After this, ``compile_schedule(name, D, V, M)`` (and therefore
    ``ScheduleConfig``/fit/sweep) resolves the searched schedule like
    any built-in — but pinned: the compile path re-checks the table digest
    against the artifact, so the certified table cannot drift.
    """
    cs, art, orders, label = _load_schedule_artifact_impl(source, True)
    reg_name = name if name is not None else cs.name
    if cs.placement != "wrap":
        raise ScheduleError(
            f"schedule artifact {label}: only wrap-placement artifacts can "
            "be registered (vshape placement is reserved for the ZBV builtin)")

    def order_fn(D: int, V: int, M: int) -> List[List[Action]]:
        want = (cs.n_devices, cs.n_virtual, cs.n_microbatches)
        if (D, V, M) != want:
            raise ScheduleError(
                f"schedule {reg_name!r} was certified for n_devices={want[0]}, "
                f"n_virtual={want[1]}, n_microbatches={want[2]}; requested "
                f"({D}, {V}, {M}) — re-run the search for this config")
        return [list(order) for order in orders]

    register_schedule(reg_name, order_fn, split_backward=cs.split_backward,
                      overwrite=overwrite)
    _ARTIFACT_PINS[reg_name] = {
        "table_digest": str(art["table_digest"]),
        "config_fingerprint": str(art["config_fingerprint"]),
        "source": label,
    }
    if reg_name != cs.name:
        cs = dataclasses.replace(cs, name=reg_name)
    return cs


def registered_artifact_info(name: str) -> Optional[Dict[str, str]]:
    """Pin metadata (table digest / fingerprint / source) for an
    artifact-backed schedule name, or None."""
    info = _ARTIFACT_PINS.get(name)
    return dict(info) if info is not None else None


def verify_artifact_pin(cs: CompiledSchedule) -> None:
    """For artifact-backed schedule names, re-check the compiled table's
    digest against the certified pin. Called on every compile/ingest path
    so a re-registered order function (or a mutated registry) can never
    swap in an uncertified table under a certified name."""
    pin = _ARTIFACT_PINS.get(cs.name)
    if pin is None:
        return
    got = table_digest(cs.table)
    if got != pin["table_digest"]:
        raise ScheduleError(
            f"schedule {cs.name!r}: compiled table digest {got[:12]}... does "
            f"not match the certified artifact pin "
            f"{pin['table_digest'][:12]}... (source {pin['source']}) — the "
            "registered orders no longer produce the certified table")


# ---------------------------------------------------------------------------
# Phase compression: the periodic-steady-state structure of a tick table
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Phase:
    """One maximal periodic run of tick-table rows.

    Covers rows ``[start, start + period * reps)``. ``base`` is the first
    repetition's row block ``[period, D, n_cols]``; repetition ``k``
    (``0 <= k < reps``) is exactly ``base + k * stride`` — active entries
    (``>= 0``) advance affinely per repetition (microbatch counters step,
    slot indices step or, over a period spanning a full slot-reuse cycle,
    stay put), inactive entries stay ``-1`` (``stride`` is 0 there). The
    *pattern* — which units run and which transfer channels are live on
    each device at each period position — is ``base >= 0`` and is constant
    across repetitions by construction, which is what lets the executor
    compile ONE specialized body per pattern and drive the run as a
    ``lax.scan`` (``unroll_ticks="phases"``). Rows that match no period
    fall out of :func:`compress_schedule` as ``period=1, reps=1`` phases.
    """

    start: int
    period: int
    reps: int
    base: np.ndarray    # [period, D, n_cols] int32
    stride: np.ndarray  # [period, D, n_cols] int32; 0 on inactive entries

    @property
    def length(self) -> int:
        return self.period * self.reps

    def pattern_key(self) -> Tuple[int, bytes]:
        """Hashable identity of the active/idle structure: the executor
        compiles one tick body per distinct key (slot/microbatch VALUES are
        scanned inputs, only this mask shapes the program)."""
        return (self.period, (self.base >= 0).tobytes())


def rows_of(phase: Phase) -> np.ndarray:
    """Materialize one phase's rows ``[length, D, n_cols]`` from its
    descriptor alone (base + per-rep stride; no table reference)."""
    ks = np.arange(phase.reps, dtype=phase.base.dtype)
    blocks = phase.base[None] + ks[:, None, None, None] * phase.stride[None]
    return blocks.reshape(phase.reps * phase.period, *phase.base.shape[1:])


def replay_phases(phases: Sequence[Phase]) -> np.ndarray:
    """Reconstruct the full tick table from phase descriptors —
    :func:`compress_schedule`'s inverse, and the property the compression
    self-check (and tests/test_schedules.py) assert bit-exactly."""
    return np.concatenate([rows_of(p) for p in phases], axis=0)


def compress_schedule(table: np.ndarray,
                      max_period: Optional[int] = None) -> Tuple[Phase, ...]:
    """Segment a tick table into maximal periodic runs (:class:`Phase`).

    Every schedule we execute is warmup + a periodic steady state +
    cooldown (arXiv:2401.10241's zero-bubble family makes the periodicity
    explicit; the tabular view of arXiv:2605.24006 makes it statically
    detectable from the rows). A run of period ``p`` starting at ``t``
    requires, for each repetition ``k``: the active/idle mask of rows
    ``table[t+k*p : t+(k+1)*p]`` equals the first repetition's, and active
    entries advance affinely (``base + k * stride``). Mask-alternating
    steady states (1F1B's F/B interleave) land at ``p >= 2``; cyclic slot
    reuse is absorbed by a period spanning the whole reuse cycle (slot
    stride 0, microbatch stride = slots per cycle). Greedy: at each row
    take the (period, reps) with maximal coverage, smallest period on
    ties; rows matching no period become ``period=1, reps=1`` phases
    (warmup/cooldown transients). The result is self-checked against
    :func:`replay_phases` before being returned.
    """
    table = np.asarray(table)
    T = table.shape[0]
    if max_period is None:
        max_period = min(T // 2, 64)
    phases: List[Phase] = []
    t = 0
    while t < T:
        best = None  # (coverage, -period, period, reps, stride)
        rem = T - t
        for p in range(1, min(max_period, rem // 2) + 1):
            base = table[t:t + p]
            mask = base >= 0
            nxt = table[t + p:t + 2 * p]
            if ((nxt >= 0) != mask).any():
                continue
            stride = np.where(mask, nxt - base, 0).astype(table.dtype)
            if not np.array_equal(base + stride, nxt):
                continue  # inactive entries drifted (non -1 sentinel)
            reps = 2
            while t + (reps + 1) * p <= T:
                blk = table[t + reps * p:t + (reps + 1) * p]
                # mask equality is checked separately: an active entry
                # walking onto -1 by arithmetic coincidence must NOT count
                # as a match — the executor's per-position specialization
                # relies on the mask being constant across repetitions
                if (((blk >= 0) == mask).all()
                        and np.array_equal(blk, base + reps * stride)):
                    reps += 1
                else:
                    break
            cand = (p * reps, -p, p, reps, stride)
            if best is None or cand[:2] > best[:2]:
                best = cand
        if best is not None:
            _, _, p, reps, stride = best
            phases.append(Phase(t, p, reps, table[t:t + p].copy(), stride))
            t += p * reps
        else:
            phases.append(Phase(t, 1, 1, table[t:t + 1].copy(),
                                np.zeros((1,) + table.shape[1:],
                                         dtype=table.dtype)))
            t += 1
    out = tuple(phases)
    if not np.array_equal(replay_phases(out), table):  # pragma: no cover
        raise ScheduleError("phase compression self-check failed: replay "
                            "does not reconstruct the tick table")
    return out


def phase_stats(phases: Sequence[Phase]) -> Dict[str, int]:
    """Compression summary: total rows, phase count, and the number of
    distinct patterns (= tick bodies the phase executor compiles, before
    the successor-mask refinement that may add a couple more)."""
    return {
        "n_rows": sum(p.length for p in phases),
        "n_phases": len(phases),
        "n_unique_patterns": len({p.pattern_key() for p in phases}),
    }


def phase_spans(phases: Sequence[Phase]) -> List[Tuple[int, int]]:
    """``[(start_tick, n_ticks)]`` per phase. Spans tile ``[0, makespan)``
    contiguously (the compression invariant ``check_table`` verifies)."""
    return [(p.start, p.length) for p in phases]


def table_unit_activity(table: np.ndarray) -> np.ndarray:
    """Classify every (tick, device) cell of a tick table as F/B/W/idle.

    Returns ``[T, D, 4]`` 0/1 with the last axis ordered (F, B, W, idle).
    Works on both the 4-column forward-only table (col 2 is the forward
    microbatch) and the >=13-column training table (``COL_FWD_M`` /
    ``COL_BWD_M`` / ``COL_W_M``). A cell doing several units in one tick
    (e.g. B and W fused on non-split schedules' backward) counts each
    active op; ``idle`` is set only when no unit runs.
    """
    table = np.asarray(table)
    if table.ndim != 3:
        raise ScheduleError(f"expected [T, D, n_cols] table, got shape "
                            f"{table.shape}")
    n_cols = table.shape[2]
    f = table[:, :, COL_FWD_M] >= 0 if n_cols > COL_FWD_M else (
        table[:, :, n_cols - 2] >= 0)
    b = (table[:, :, COL_BWD_M] >= 0 if n_cols > COL_BWD_M
         else np.zeros(table.shape[:2], bool))
    w = (table[:, :, COL_W_M] >= 0 if n_cols > COL_W_M
         else np.zeros(table.shape[:2], bool))
    idle = ~(f | b | w)
    return np.stack([f, b, w, idle], axis=-1).astype(np.int64)


# Ring channels in the executor's recv-register order: (bank column,
# buffer kind). The recv register itself is the "second edge-slot buffer"
# of the double-buffered discipline — an arrival rides it across the tick
# until its bank stage, so the hop that produced it overlaps compute.
OVERLAP_CHANNELS: Tuple[Tuple[int, str], ...] = (
    (COL_STORE_F_SLOT, "act"),
    (COL_STORE_B_SLOT, "grad"),
    (COL_STORE_F_NEG_SLOT, "act"),
    (COL_STORE_B_POS_SLOT, "grad"),
)

# Bank stages: where within a tick a channel's arrival is committed from
# its recv register into the edge slot. Stage k means "immediately before
# unit k" with units ordered F(0), B(1), W(2); stage 3 is end-of-tick
# (just before the next hops replace the registers). Stage 0 is the
# lockstep discipline; later stages let the producing ppermute overlap
# this tick's earlier units.
BANK_BEFORE_F, BANK_BEFORE_B, BANK_BEFORE_W, BANK_END = 0, 1, 2, 3


def overlap_bank_stages(table: np.ndarray) -> np.ndarray:
    """Latest-safe bank stage per (tick, ring channel): ``[T, 4]`` int8.

    For each tick and each of the four ring channels (order =
    :data:`OVERLAP_CHANNELS`, matching the executor's recv registers),
    computes the latest point in the tick at which the arrival can be
    committed to its edge slot without changing any unit's inputs or the
    final buffer state — i.e. the earliest same-tick *conflict* with the
    banked slot, minimized across devices (SPMD: one program, one bank
    site per channel per tick). Conflicts, per device, against the
    device's banked slot ``s``:

    - the F unit (stage 0) reads AND writes ``act_buf[COL_FWD_SLOT]``
      and (vshape routes) writes ``act_buf[COL_FWD_LOCAL_SLOT]``;
      banking must precede a write so the unit's write lands last
      (write-last ordering of the lockstep tick is preserved).
    - the B unit (stage 1) reads ``act_buf[COL_BWD_ASLOT]`` and
      ``grad_buf[COL_BWD_GSLOT]``, and (vshape) writes
      ``grad_buf[COL_BWD_LOCAL_SLOT]``.
    - the W unit (stage 2) reads ``act_buf[COL_W_ASLOT]`` and
      ``grad_buf[COL_W_GSLOT]``.

    No conflict => stage 3 (end of tick). Banking EARLIER than the
    returned stage is always lockstep-correct, so the cross-device min is
    conservative and the staged executor is bit-identical to the lockstep
    one by construction. This classifier is the single source of truth:
    the executor banks at these stages, ``analysis.table_check`` verifies
    the register lifetime under them, and ``analysis.cost_model``'s
    ``comm_overlap`` mode derives per-tick overlappable hop time from
    them.
    """
    table = np.asarray(table)
    if table.ndim != 3 or table.shape[2] < N_COLS:
        raise ScheduleError(
            f"overlap_bank_stages needs a [T, D, {N_COLS}] training table, "
            f"got shape {table.shape}")
    T, D, _ = table.shape
    out = np.full((T, len(OVERLAP_CHANNELS)), BANK_END, dtype=np.int8)
    f_on = table[:, :, COL_FWD_M] >= 0
    b_on = table[:, :, COL_BWD_M] >= 0
    w_on = table[:, :, COL_W_M] >= 0
    # (stage, active-mask, slot-column, buffer kind); writes behave like
    # reads here — both pin the bank before the unit that touches the slot.
    touches = (
        (BANK_BEFORE_F, f_on, COL_FWD_SLOT, "act"),
        (BANK_BEFORE_F, table[:, :, COL_FWD_LOCAL_SLOT] >= 0,
         COL_FWD_LOCAL_SLOT, "act"),
        (BANK_BEFORE_B, b_on, COL_BWD_ASLOT, "act"),
        (BANK_BEFORE_B, b_on, COL_BWD_GSLOT, "grad"),
        (BANK_BEFORE_B, table[:, :, COL_BWD_LOCAL_SLOT] >= 0,
         COL_BWD_LOCAL_SLOT, "grad"),
        (BANK_BEFORE_W, w_on, COL_W_ASLOT, "act"),
        (BANK_BEFORE_W, w_on, COL_W_GSLOT, "grad"),
    )
    for ci, (bank_col, kind) in enumerate(OVERLAP_CHANNELS):
        slots = table[:, :, bank_col]          # [T, D]; -1 = no bank
        banked = slots >= 0
        if not banked.any():
            continue
        stage = np.full((T, D), BANK_END, dtype=np.int8)
        for st, on, slot_col, k in touches:
            if k != kind:
                continue
            hit = banked & on & (table[:, :, slot_col] == slots)
            stage = np.where(hit, np.minimum(stage, st), stage)
        stage = np.where(banked, stage, BANK_END)
        out[:, ci] = stage.min(axis=1)
    # Two channels of the same buffer landing in the SAME slot on the same
    # tick must keep their lockstep write order; forcing equal stages makes
    # the in-stage channel order (= lockstep order) decide.
    for i, j in ((0, 2), (1, 3)):
        si = table[:, :, OVERLAP_CHANNELS[i][0]]
        sj = table[:, :, OVERLAP_CHANNELS[j][0]]
        clash = ((si >= 0) & (sj >= 0) & (si == sj)).any(axis=1)
        if clash.any():
            m = np.minimum(out[:, i], out[:, j])
            out[:, i] = np.where(clash, m, out[:, i])
            out[:, j] = np.where(clash, m, out[:, j])
    return out


def phase_bank_stages(phase: Phase,
                      bank_stages: np.ndarray) -> np.ndarray:
    """Fold a table-wide ``[T, 4]`` bank-stage map onto one phase's period
    positions: ``[period, 4]``, min across repetitions AND across every
    tick the table maps to the position (conservative => lockstep-correct
    for all of them). The phase executor compiles one body per (pattern,
    successor-mask, bank-stage) triple and banks at these stages."""
    rows = bank_stages[phase.start:phase.start + phase.length]
    return rows.reshape(phase.reps, phase.period, -1).min(axis=0)


# ---------------------------------------------------------------------------
# Bubble analytics
# ---------------------------------------------------------------------------


def analytic_bubble_fraction(name: str, n_devices: int, n_virtual: int,
                             n_microbatches: int,
                             cs: "CompiledSchedule" = None) -> float:
    """Ideal bubble fraction in unit-cost ticks.

    GPipe / 1F1B: (D-1)/(M + D - 1) — the classic fill/drain bubble (1F1B
    matches GPipe's bubble; its win is activation memory, SURVEY.md §6 note).
    Interleaved / BFS: warmup/cooldown offsets stay proportional to D-1 while
    per-device work grows to 2MV ticks -> (D-1)/(M*V + D-1).

    ZB-H1 / ZB-V (closed forms, derived for THIS executor's work model —
    stage 0's dgrad ``B`` is elided, so device 0 genuinely runs M fewer
    actions than the papers' uniform-work accounting):

    - makespan at the papers' optimum, with our explicit 1-tick ppermute
      transit: ``3M + D - 1`` (H1) / ``6M + D - 1`` (V — the first
      microbatch pays the ramp once; the V placement returns the cotangent
      chain to device 0 with no extra turnaround).
    - mean per-device busy work: ``3M - M/D`` (H1) / ``6M - M/D`` (V).
    - mean bubble = 1 - busy/makespan. Note this *mean* counts device 0's
      elided-dgrad idle ticks as bubble even though they are a work
      *saving*, so it exceeds the papers' (D-1)/(3M + D-1)-style numbers
      by construction; the makespan factor is the apples-to-apples check.

    tests/test_zero_bubble.py asserts the compiled tables MEET these
    closed forms (north star: measured == analytic), which makes the
    check meaningful for exactly the schedules claiming the lowest
    bubbles (VERDICT r2 item 5).
    """
    D, M = n_devices, n_microbatches
    if name in _CUSTOM_SCHEDULES:
        # no closed form for arbitrary registered orders: report the
        # unit-cost tick simulation, which IS the executor's time model
        # (pass the caller's already-compiled ``cs`` to skip a recompile)
        if cs is None:
            cs = compile_schedule(name, D, n_virtual, M)
        return simulated_bubble(cs, w_f=1.0, w_b=1.0, w_w=1.0)[
            "bubble_fraction"]
    if name == "ZBH1":
        return 1.0 - (3 * M - M / D) / (3 * M + D - 1)
    if name == "ZBV":
        return 1.0 - (6 * M - M / D) / (6 * M + D - 1)
    V = n_virtual if name in ("Interleaved1F1B", "BFS") else 1
    return (D - 1) / (M * V + D - 1)


def paper_bubble_fraction(name: str, n_devices: int, n_virtual: int,
                          n_microbatches: int) -> float:
    """The PAPER-comparable bubble under uniform-work accounting.

    :func:`analytic_bubble_fraction`'s ZB numbers price device 0's elided
    dgrad as idle (this executor genuinely skips it — a work saving the
    per-device mean counts as bubble), so they are NOT comparable to the
    zero-bubble paper's figures or to this repo's pre-round-3 reports.
    This twin reports the classic ``1 - uniform_busy/makespan`` form on the
    same makespans — ``(D-1)/(3M+D-1)`` for ZB-H1, ``(D-1)/(6M+D-1)`` for
    ZB-V — and equals :func:`analytic_bubble_fraction` for every other
    builtin. Sweep CSVs / docs citing a ZB bubble should say which form
    they use (docs/schedules.md shows both)."""
    D, M = n_devices, n_microbatches
    if name == "ZBH1":
        return (D - 1) / (3 * M + D - 1)
    if name == "ZBV":
        return (D - 1) / (6 * M + D - 1)
    return analytic_bubble_fraction(name, n_devices, n_virtual,
                                    n_microbatches)


def simulated_bubble(cs: CompiledSchedule, w_f: float = 1.0,
                     w_b: float = 2.0, w_w: float = 1.0) -> Dict[str, float]:
    """Bubble measured on the compiled tick schedule under a cost model where
    a forward tick costs ``w_f``, a backward tick ``w_b`` and a wgrad tick
    ``w_w``. The default ``w_b=2`` is the STORED-backward cost model (~2
    grad-work forward-equivalents, no recompute) — the same per-action
    weight as the reference's torch-autograd runtime and as
    :func:`async_makespan`'s default, so the two models compare like for
    like. NOTE the executor's own D>1 default is the REMATERIALIZING
    backward (``pipeline.make_pipeline_grad_fn``), whose model is
    ``w_b=3`` (1 recompute + ~2 grad-work) — pass it explicitly when
    modeling a default multi-device run (``utils.sweep`` does, recording
    the weight used in its ``bubble_sim_w_b`` column). ``w_b=1`` is the
    unit-cost textbook model (= :func:`analytic_bubble_fraction`);
    ``w_b~=w_f`` fits split schedules whose B is dgrad-only. Lockstep
    SPMD: a device's units of one tick run one after the other, and each
    tick lasts as long as its most expensive device — what the v5e
    measures, every tick ending in the ring's ``ppermute`` (PERF.md §6,
    PR 28 and PR 29). Packed 1F1B reads ``(D-1)/(M+D-1)`` at every
    ``w_b``: all stages are in steady state together, so a tick costs
    ``w_f + w_b`` on each, and the makespan is :func:`async_makespan`'s."""
    weight = np.array([w_f, w_b, w_w, 0.0])
    per_dev_tick = table_unit_activity(cs.table) @ weight  # [T, D]
    busy = per_dev_tick.sum(axis=0)
    makespan = float(per_dev_tick.max(axis=1).sum())
    per_device = 1.0 - busy / makespan
    return {
        "makespan": makespan,
        "bubble_fraction": float(per_device.mean()),
        "bubble_fraction_max": float(per_device.max()),
    }


def async_makespan(name: str, n_devices: int, n_virtual: int,
                   n_microbatches: int, w_f: float = 1.0, w_b: float = 2.0,
                   w_w: float = 1.0, comm: float = 0.0) -> float:
    """Makespan of a schedule's per-device action orders under an **async**
    runtime model: each device advances through its own action list as soon
    as that action's dependencies have arrived — no lockstep tick barrier.

    This is the execution model of the reference's
    ``torch.distributed.pipelining`` runtime (async batched P2P, activation
    stash — so ``w_b=2``, a plain backward), as opposed to this framework's
    lockstep scan executor (``simulated_bubble``, ``w_b=3`` remat). Costs
    are per *action*; with V virtual chunks each action covers 1/V of the
    per-device layers, so cross-V comparisons scale weights by 1/V (see
    ``predicted_throughput``). Used to reconcile the reference's published
    schedule orderings with this executor's (docs/results.md).
    """
    D, V, M = n_devices, n_virtual, n_microbatches
    S = D * V
    # NOTE: comm is charged on every inter-stage hop; a vshape (ZBV)
    # placement's same-device chunk boundary would need placement-aware
    # exemption if comm > 0 matters there.
    orders = build_order(name, D, V, M)
    end: Dict[Action, float] = {}
    free = [0.0] * D
    ptr = [0] * D
    scale = 1.0 / V
    weight = {F: w_f * scale, B: w_b * scale, W: w_w * scale}

    def dep_ends(a: Action):
        if a.op == F:
            if a.stage == 0:
                return [0.0]
            dep = Action(a.stage - 1, F, a.microbatch)
            return [end[dep] + comm] if dep in end else None
        if a.op == W:
            # wgrad needs its own dgrad's cotangent (stage 0 has no B under
            # the split convention: it takes the B(1, m) arrival instead)
            dep = (Action(1, B, a.microbatch) if a.stage == 0
                   else Action(a.stage, B, a.microbatch))
            if dep not in end:
                return None
            return [end[dep] + (comm if a.stage == 0 else 0.0)]
        # B: forward stashed on-device + upstream cotangent arrival
        fw = Action(a.stage, F, a.microbatch)
        if fw not in end:
            return None
        needs = [end[fw]]
        if a.stage < S - 1:
            up = Action(a.stage + 1, B, a.microbatch)
            if up not in end:
                return None
            needs.append(end[up] + comm)
        return needs

    remaining = sum(len(o) for o in orders)
    while remaining:
        progressed = False
        for d in range(D):
            while ptr[d] < len(orders[d]):
                a = orders[d][ptr[d]]
                deps = dep_ends(a)
                if deps is None:
                    break
                start = max([free[d]] + deps)
                end[a] = start + weight[a.op]
                free[d] = end[a]
                ptr[d] += 1
                remaining -= 1
                progressed = True
        if not progressed:
            raise ScheduleError(f"async simulation deadlocked for {name} "
                                f"(D={D}, V={V}, M={M})")
    return max(free)


def predicted_throughput(name: str, n_devices: int, n_virtual: int,
                         n_microbatches: int, tokens_per_step: int,
                         w_f: float = 1.0, w_b: float = 2.0,
                         comm: float = 0.0) -> float:
    """Relative throughput prediction from :func:`async_makespan` (async /
    stash cost model — the reference runtime's): tokens per unit time where
    one unit = one full-model microbatch forward. Comparable across
    schedules and V at fixed (D, M, model)."""
    ms = async_makespan(name, n_devices, n_virtual, n_microbatches,
                        w_f=w_f, w_b=w_b, comm=comm)
    return tokens_per_step / ms
