"""Slot-level continuous batching over the pipelined round-robin decoder.

The static decoder (:mod:`..parallel.pipelined_decode`) keeps every pipe
stage busy by round-robining ``M >= D`` independent streams, but all M
streams start together and drain together — mixed-length requests waste
slots exactly the way a fill-drain schedule wastes bubbles. This module
makes each stream a *slot* an open request queue feeds:

- ``make_serving_step_fn`` builds ONE jitted SPMD program that advances
  the ring by a fixed ``block_ticks`` ticks. Every shape in it is
  static: per-slot caches ``[lps, M, max_len + C - 1, Hkv, hd]``, a
  ``[1, C, dim]`` ring channel (C = prefill chunk), int32 slot-state
  vectors. A slot's whole lifecycle — chunked prefill, decode, EOS /
  budget retirement, sitting idle — is data, not shape, so the program
  compiles once and serves forever.
- tick ``u``, device ``d`` serves slot ``(u - d) mod M``, exactly the
  decoder's schedule. Stage 0 owns the authoritative slot state; a small
  int32 metadata vector ``(offset, s_valid, sample?, live?)`` rides the
  same ``ppermute`` as the activations, so stages ``d > 0`` need no slot
  knowledge at all — they apply their layer slice at the offset the
  metadata names, and the last stage samples only when the metadata says
  this chunk ends in a sampling position.
- *chunked prefill*: a newly admitted request's prompt enters C tokens
  per visit while every other slot keeps decoding — admission never
  stalls the ring. Rows past ``s_valid`` in a chunk are garbage but
  provably invisible: the band mask hides cache keys beyond the query's
  position, and the next chunk's write covers the garbage rows before
  the valid frontier reaches them (same argument for the C-1 junk rows a
  decode step writes).
- :class:`ServingEngine` drives the program from the host *between*
  blocks: retire slots whose ``finished`` flag is set (EOS or per-request
  budget — by then nothing of that slot is in flight, because a slot's
  next visit comes ``M >= D`` ticks after its token lands), refill them
  from the pending queue, fast-forward ``u`` across fully-idle gaps.
  ``policy="continuous"`` refills per slot; ``policy="static"`` admits
  only when ALL slots have drained — the fill-drain baseline the
  benchmark compares against, on the *same compiled program*.

Per-request latency stamps (``t_first``/``t_finish``, in ticks) are
written on-device at banking time, so TTFT and per-output-token time are
exact even though the host only observes block boundaries. Sampling is
greedy (temperature 0): continuous batching interleaves requests into
one sequential token stream, and greedy is what the oracle-parity tests
pin against single-device :func:`...models.generate.generate`.

*Speculative decoding* (``speculative=True``, Leviathan et al.,
arXiv:2211.17192) multiplies decode tokens per visit without changing a
single shape: a small replicated draft model runs on stage 0 inside the
same compiled block and proposes ``gamma`` tokens per verify visit; the
target pipeline scores all ``gamma + 1`` positions in ONE forward by
reusing the C-wide chunked-prefill channel (``gamma + 1 <= C``), and the
longest matching prefix of proposals is accepted — ``n_accepted ∈
[1, gamma+1]`` tokens bank per visit. Everything data-dependent rides
the widened metadata ring (``isverify`` flag + the gamma draft tokens)
or the widened ``[gamma+2]`` token channel (per-row argmaxes +
``n_accepted``), so the block still compiles exactly once. Rejected
rows are *rolled back by overwrite*: they land past the accepted
frontier, the band mask keeps them invisible (masked scores contribute
exact zeros), and the slot's next C-wide write covers them before the
frontier arrives — the same junk-row discipline chunked prefill already
relies on. Greedy outputs are bit-identical to the non-speculative
engine by construction (an accepted token's context is exactly the
greedy context; tests/test_serving_spec.py pins it).
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..models.generate import _embed_at, layers_with_cache, rope_slice_at
from ..models.transformer import compute_cast
from ..parallel.mesh import MODEL_AXIS, PIPE_AXIS
from ..parallel.pipeline import (_check_tp_divisibility, _dense_layer_specs,
                                 _shard_map, stack_stage_layers)
from ..parallel.pipelined_decode import (_head_token, _slot_cache_apply,
                                         spec_accept_len)
from ..utils.config import ModelConfig

# state leaves the host scheduler reads back after every block (small:
# O(M) ints plus the [M, out_max] output buffer — never the caches)
_HOST_KEYS = ("u", "finished", "emitted", "pos", "prefill_left",
              "t_first", "t_finish", "out_buf", "tok")
# leaves the host may write between blocks (numpy mirrors re-uploaded with
# their pinned sharding only when dirty, so admission costs one transfer,
# not a cascade of per-slot jitted updates)
_SCHED_KEYS = _HOST_KEYS + ("budget", "plen", "live", "prompt_buf")
# paged mode adds the COW command pair to the per-block fetch (the step
# returns them cleared, which is exactly the reset the mirrors need) and
# the page table to the host-writable set
_PAGED_HOST_KEYS = _HOST_KEYS + ("cow_src", "cow_dst")
_PAGED_SCHED_KEYS = _PAGED_HOST_KEYS + ("budget", "plen", "live",
                                        "prompt_buf", "page_tbl")
# speculative mode adds the draft-model frontier plus the acceptance
# counters (verify visits / accepted proposals per slot) to both sets:
# the host resets them at admission and reads them back for the
# acceptance-rate gauges
_SPEC_KEYS = ("dpos", "spec_visits", "spec_accepted")


def _paged_cache_apply(cfg: ModelConfig, layers_d, h, kp, vp, pt_row,
                       offset, s: int, *, tp_axis: Optional[str] = None,
                       tp_size: int = 1):
    """Paged twin of :func:`..parallel.pipelined_decode._slot_cache_apply`:
    gather the slot's pages ``kp[:, pt_row]`` into a positionally-
    contiguous view (table entry ``i`` holds positions ``[i*ps,
    (i+1)*ps)``, so gathered row index == absolute position), run the
    stage's layers, scatter every page back.

    The whole-table scatter is value-safe: a visit only changes rows
    ``[offset, offset + C)`` and the host allocator guarantees those
    live in private (refcount == 1) pages — shared prefix pages are
    rewritten byte-identically, and duplicate null-page entries receive
    copies of their own unchanged content. The gathered view is longer
    than the contiguous cache (``P_max * ps >= mlen_alloc``) but the
    tail is band-masked, and masked scores contribute exact zeros to the
    softmax, so the paged and contiguous paths are bit-identical (the
    parity test in tests/test_serving_paging.py pins this)."""
    lps, n_pages, ps, n_kv, hd = kp.shape
    pmax = pt_row.shape[0]
    kg = kp[:, pt_row].reshape(lps, 1, pmax * ps, n_kv, hd)
    vg = vp[:, pt_row].reshape(lps, 1, pmax * ps, n_kv, hd)
    rope = rope_slice_at(cfg, pmax * ps, offset, s)
    h, (kg2, vg2) = layers_with_cache(cfg, layers_d, h, kg, vg, offset,
                                      rope, tp_axis=tp_axis,
                                      tp_size=tp_size)
    kp = kp.at[:, pt_row].set(kg2.reshape(lps, pmax, ps, n_kv, hd))
    vp = vp.at[:, pt_row].set(vg2.reshape(lps, pmax, ps, n_kv, hd))
    return h, kp, vp


@dataclasses.dataclass
class Request:
    """One serving request: ``prompt`` token ids, a per-request output
    budget, and an arrival time in *ticks* (0 = available immediately)."""
    rid: int
    prompt: Sequence[int]
    max_new_tokens: int
    arrival: float = 0.0


@dataclasses.dataclass
class Completion:
    """A finished request with its emitted tokens and tick-exact stamps.

    ``tokens`` includes the EOS token when the request ended on one.
    ``ttft_ticks`` counts from *arrival* (queue wait included);
    ``tpot_ticks`` is the mean tick gap between consecutive output
    tokens (None for single-token outputs). A request the scheduler
    retired without serving (over-budget prompt, poisoned admission)
    comes back with ``status="failed"``, a ``reason``, no tokens and
    ``-1`` stamps — per-request failure is an outcome, not an engine
    crash (docs/resilience.md)."""
    rid: int
    prompt: List[int]
    tokens: List[int]
    slot: int
    admit_tick: int
    first_token_tick: int
    finish_tick: int
    arrival: float
    status: str = "ok"
    reason: Optional[str] = None

    @property
    def ttft_ticks(self) -> float:
        return self.first_token_tick - self.arrival

    @property
    def admit_wait_ticks(self) -> float:
        """Queue wait: ticks between arrival and slot admission. TTFT =
        admit_wait + service TTFT, so a latency regression is immediately
        attributable to queueing vs the ring itself."""
        return self.admit_tick - self.arrival

    @property
    def service_ttft_ticks(self) -> float:
        """TTFT excluding queue wait: admission to first banked token —
        the ring's own latency (prefill visits + D hops), independent of
        offered load."""
        return self.first_token_tick - self.admit_tick

    @property
    def tpot_ticks(self) -> Optional[float]:
        n = len(self.tokens)
        if n < 2:
            return None
        return (self.finish_tick - self.first_token_tick) / (n - 1)


@dataclasses.dataclass
class ServeResult:
    """What :meth:`ServingEngine.run` returns: completions in finish
    order, the slot-occupancy timeline sampled at every block boundary
    (``(tick, n_active_slots)``), the admission-queue depth at the same
    boundaries (``(tick, n_waiting)`` — arrived but not yet admitted),
    total ticks the ring advanced, ticks the ring was actually busy, and
    the host wall-clock the run took. Both time series also carry a
    ``(tick, 0)`` sample at every idle fast-forward boundary, so
    time-integrals over the samples account for the skipped span instead
    of silently interpolating across it."""
    completions: List[Completion]
    occupancy: List[Any]
    ticks: int
    wall_s: float
    n_slots: int
    policy: str
    queue_depth: List[Any] = dataclasses.field(default_factory=list)
    busy_ticks: int = 0
    # paged-mode gauges (None/empty on contiguous runs): pages_used and
    # page_fragmentation are (tick, value) series sampled at the same
    # block boundaries as occupancy; prefix_hit_rate is token-weighted
    # over all admissions; n_backpressure counts admission attempts
    # deferred by pool exhaustion (deferred, never failed)
    paged: bool = False
    pages_capacity: int = 0
    pages_used: List[Any] = dataclasses.field(default_factory=list)
    page_fragmentation: List[Any] = dataclasses.field(default_factory=list)
    prefix_hit_rate: Optional[float] = None
    prefill_skipped_tokens: int = 0
    n_cow: int = 0
    n_backpressure: int = 0
    # speculative-mode gauges (zero/None on plain runs): verify visits
    # and accepted proposals summed over all completions, plus the
    # (tick, running acceptance rate) series sampled at block boundaries
    speculative: bool = False
    gamma: int = 0
    spec_verify_visits: int = 0
    spec_accepted_tokens: int = 0
    acceptance_series: List[Any] = dataclasses.field(default_factory=list)

    @property
    def acceptance_rate(self) -> Optional[float]:
        """Accepted proposals over offered proposals: ``sum(n_acc - 1) /
        (gamma * verify_visits)`` — the measured alpha the cost model's
        expected-tokens formula takes. None until a verify visit ran."""
        if not (self.speculative and self.gamma and self.spec_verify_visits):
            return None
        return self.spec_accepted_tokens / (self.gamma
                                            * self.spec_verify_visits)

    @property
    def accepted_len_mean(self) -> Optional[float]:
        """Mean tokens banked per verify visit (``1 + gamma * alpha`` in
        expectation, in ``[1, gamma+1]`` always)."""
        if not (self.speculative and self.spec_verify_visits):
            return None
        return 1.0 + self.spec_accepted_tokens / self.spec_verify_visits

    @property
    def tokens_out(self) -> int:
        return sum(len(c.tokens) for c in self.completions)

    @property
    def tokens_per_sec(self) -> float:
        return self.tokens_out / self.wall_s if self.wall_s > 0 else 0.0

    @property
    def goodput(self) -> float:
        """Emitted tokens per slot-visit — the schedule-quality number
        (1.0 would mean every slot emitted a token on every ring round),
        independent of host/hardware speed. Each slot gets ticks/M
        visits, so this is tokens_out / ticks.

        ``ticks`` includes idle fast-forwarded gaps, so under light load
        this measures *offered-load* utilization (it deflates toward the
        arrival rate); :attr:`goodput_busy` is the schedule-quality twin
        over busy ticks only."""
        return self.tokens_out / self.ticks if self.ticks else 0.0

    @property
    def goodput_busy(self) -> float:
        """Emitted tokens per *busy* tick: ``tokens_out / busy_ticks``
        where ``busy_ticks`` counts only ticks the ring actually
        advanced through the compiled block (>= 1 live slot at block
        entry) — idle fast-forwarded gaps are excluded. Under light load
        :attr:`goodput` is deflated by the gaps between arrivals (it
        answers "how loaded was the ring"); ``goodput_busy`` answers
        "how well did the schedule use the ticks it ran" and stays
        comparable across offered loads. At/over saturation there are no
        gaps and the two coincide. Busy time is accounted at block
        granularity (the host only observes block boundaries), so a
        drained tail inside the final block counts as busy."""
        return self.tokens_out / self.busy_ticks if self.busy_ticks else 0.0

    @property
    def n_failed(self) -> int:
        """Requests the scheduler retired with ``status="failed"``
        (over-budget prompts, poisoned admissions) instead of serving."""
        return sum(1 for c in self.completions if c.status == "failed")


class ServingProgram:
    """The compiled tick-block step + its static configuration.

    Built by :func:`make_serving_step_fn`; drive it through
    :class:`ServingEngine` (or call ``prepare(params)`` +
    ``step(stacked, embed, head, state)`` directly)."""

    def __init__(self, cfg: ModelConfig, mesh: Mesh, *, n_slots: int,
                 max_len: int, prompt_max: int, out_max: int,
                 prefill_chunk: int, block_ticks: int,
                 eos_id: Optional[int], step_fn, state_specs,
                 paged: bool = False, page_size: int = 0,
                 n_pages: int = 0, speculative: bool = False,
                 gamma: int = 0,
                 draft_cfg: Optional[ModelConfig] = None) -> None:
        self.cfg = cfg
        self.mesh = mesh
        self.n_slots = n_slots
        self.max_len = max_len
        self.prompt_max = prompt_max
        self.out_max = out_max
        self.prefill_chunk = prefill_chunk
        self.block_ticks = block_ticks
        self.eos_id = eos_id
        self.step = step_fn
        self.state_specs = state_specs
        self.n_stages = mesh.shape[PIPE_AXIS]
        self.tp = mesh.shape.get(MODEL_AXIS, 1)
        self.paged = paged
        self.page_size = page_size
        self.n_pages = n_pages
        self.speculative = speculative
        self.gamma = gamma
        self.draft_cfg = draft_cfg

    @property
    def max_pages_per_slot(self) -> int:
        """Static page-table width: pages to cover ``mlen_alloc`` rows."""
        if not self.paged:
            return 0
        return -(-self.mlen_alloc // self.page_size)

    @property
    def host_keys(self) -> tuple:
        base = _PAGED_HOST_KEYS if self.paged else _HOST_KEYS
        return base + _SPEC_KEYS if self.speculative else base

    @property
    def sched_keys(self) -> tuple:
        base = _PAGED_SCHED_KEYS if self.paged else _SCHED_KEYS
        return base + _SPEC_KEYS if self.speculative else base

    def sharding(self, key: str):
        from jax.sharding import NamedSharding
        return NamedSharding(self.mesh, self.state_specs[key])

    # cache rows past max_len absorb the junk tail of a C-wide write
    # starting at the last legal offset, so dynamic_update_slice never
    # clamps (clamping would silently shift valid rows)
    @property
    def mlen_alloc(self) -> int:
        return self.max_len + self.prefill_chunk - 1

    def prepare(self, params, draft_params=None) -> tuple:
        """Pre-stack the layer pytree for the pipe mesh (once per
        weights, not per block). Speculative programs additionally take
        the replicated draft model's params (same ``transformer_init``
        pytree for ``draft_cfg``)."""
        out = (stack_stage_layers(params["layers"], self.n_stages, 1),
               params["embed"], params["head"])
        if not self.speculative:
            return out
        if draft_params is None:
            raise ValueError("speculative programs need draft_params "
                             "(the draft model's weight pytree)")
        return out + (stack_stage_layers(draft_params["layers"], 1, 1),
                      draft_params["embed"], draft_params["head"])

    def init_state(self) -> Dict[str, jax.Array]:
        cfg, M, C, D = self.cfg, self.n_slots, self.prefill_chunk, \
            self.n_stages
        lps = cfg.n_layers // D
        n_kv = cfg.n_kv_heads or cfg.n_heads
        dt = jnp.dtype(cfg.dtype)
        i32 = jnp.int32
        if self.paged:
            # the page pool replaces the per-slot contiguous caches; the
            # [M, P_max] table rides the metadata ring (meta gains P_max
            # columns), the COW pair is the host's copy command queue
            pmax = self.max_pages_per_slot
            cache_shape = (D, lps, self.n_pages, self.page_size, n_kv,
                           cfg.head_dim)
            meta_w = 4 + pmax
            paged_state = {
                "page_tbl": jnp.zeros((M, pmax), i32),
                "cow_src": jnp.full((M,), -1, i32),
                "cow_dst": jnp.full((M,), -1, i32),
            }
        else:
            cache_shape = (D, lps, M, self.mlen_alloc, n_kv, cfg.head_dim)
            meta_w = 4
            paged_state = {}
        spec_state = {}
        tok_w = 1
        if self.speculative:
            # draft KV rides every stage's shard slot (uniform [None]
            # wrap), but only stage 0's shard ever holds data — the
            # draft runs replicated on stage 0. meta gains the isverify
            # flag + the gamma draft tokens; tok_chan widens to the
            # per-row argmaxes + n_accepted.
            dcfg = self.draft_cfg
            meta_w += 1 + self.gamma
            tok_w = self.gamma + 2
            n_kv_d = dcfg.n_kv_heads or dcfg.n_heads
            dshape = (D, dcfg.n_layers, M, self.mlen_alloc, n_kv_d,
                      dcfg.head_dim)
            ddt = jnp.dtype(dcfg.dtype)
            spec_state = {
                "dkc": jnp.zeros(dshape, ddt),
                "dvc": jnp.zeros(dshape, ddt),
                "dpos": jnp.zeros((M,), i32),
                "spec_visits": jnp.zeros((M,), i32),
                "spec_accepted": jnp.zeros((M,), i32),
            }
        state = {
            "u": jnp.zeros((), i32),
            "h": jnp.zeros((D, 1, C, cfg.dim), dt),
            "tok_chan": jnp.zeros((D, tok_w), i32),
            "meta": jnp.zeros((D, meta_w), i32),
            "kc": jnp.zeros(cache_shape, dt),
            "vc": jnp.zeros(cache_shape, dt),
            **paged_state,
            **spec_state,
            "tok": jnp.zeros((M,), i32),
            "pos": jnp.zeros((M,), i32),
            "prefill_left": jnp.zeros((M,), i32),
            "emitted": jnp.zeros((M,), i32),
            "budget": jnp.zeros((M,), i32),
            "plen": jnp.zeros((M,), i32),
            "live": jnp.zeros((M,), bool),
            "finished": jnp.zeros((M,), bool),
            "prompt_buf": jnp.zeros((M, self.prompt_max + C - 1), i32),
            "out_buf": jnp.zeros((M, self.out_max), i32),
            "t_first": jnp.full((M,), -1, i32),
            "t_finish": jnp.full((M,), -1, i32),
        }
        # commit every leaf to its pinned sharding so the step program
        # compiles exactly once — uncommitted inputs would give the first
        # call a different signature than steady state
        return {k: jax.device_put(v, self.sharding(k))
                for k, v in state.items()}


def make_serving_step_fn(cfg: ModelConfig, mesh: Mesh, *, n_slots: int,
                         max_len: int, prompt_max: int, out_max: int,
                         prefill_chunk: int = 1,
                         block_ticks: Optional[int] = None,
                         eos_id: Optional[int] = None,
                         paged: bool = False, page_size: int = 8,
                         n_pages: Optional[int] = None,
                         speculative: bool = False, gamma: int = 2,
                         draft_cfg: Optional[ModelConfig] = None
                         ) -> ServingProgram:
    """Build the serving tick-block program over ``mesh``'s pipe axis.

    ``n_slots`` is the ring's M (each slot carries one request);
    ``max_len`` bounds prompt+output per slot; ``prompt_max``/``out_max``
    size the static prompt/output buffers; ``prefill_chunk`` (C) is how
    many prompt tokens a slot ingests per visit; ``block_ticks`` is how
    many ticks one jitted step advances (default M — every slot visited
    once per block). ``eos_id`` retires a slot the moment it emits that
    token; budget retirement applies always.

    ``paged=True`` swaps the per-slot contiguous caches for a shared
    page pool ``[n_pages, page_size, Hkv, hd]`` per layer shard plus a
    static ``[M, P_max]`` int32 page table whose served row rides the
    metadata ring — every shape stays static, so the block still
    compiles exactly once. ``n_pages`` *includes* the reserved null
    page 0 and defaults to full parity capacity (every slot fully
    backed, ``1 + M * P_max``); size it tighter from an HBM budget with
    :func:`...analysis.memory_model.size_page_pool` to trade worst-case
    reservation for admission backpressure (docs/serving.md "Paged KV
    cache & prefix caching").

    ``speculative=True`` adds greedy draft-verify decoding: ``draft_cfg``
    names a small model (same vocab, any depth/width) whose replicated
    weights run on stage 0 inside the block; each decode visit proposes
    ``gamma`` draft tokens and the target verifies all ``gamma + 1``
    positions in one C-wide forward, so ``prefill_chunk`` must be at
    least ``gamma + 1``. Composes with ``paged=True`` — target rows past
    the accepted length stay uncommitted on the host allocator and are
    rolled back by overwrite (docs/serving.md "Speculative decoding").
    """
    from ..models.nemotron_h import not_served
    not_served("serving/engine.py", cfg)
    if cfg.arch not in ("gpt2", "llama"):
        raise ValueError(
            f"generation is undefined for arch {cfg.arch!r} (see "
            "models.generate)")
    D = mesh.shape[PIPE_AXIS]
    T = mesh.shape.get(MODEL_AXIS, 1)
    for ax, n in mesh.shape.items():
        if ax not in (PIPE_AXIS, MODEL_AXIS) and n > 1:
            raise NotImplementedError(
                f"the serving executor composes pipe x model meshes; axis "
                f"{ax!r} has size {n}")
    _check_tp_divisibility(cfg, T)
    tp_axis = MODEL_AXIS if T > 1 else None
    if cfg.n_layers % D:
        raise ValueError(f"n_layers={cfg.n_layers} must divide over {D} "
                         "stages")
    M = n_slots
    if M < D:
        raise ValueError(f"n_slots={M} must be >= the pipe degree {D} "
                         "(fewer slots than stages stalls the ring)")
    C = prefill_chunk
    if C < 1:
        raise ValueError(f"prefill_chunk must be >= 1, got {C}")
    if not speculative:
        gamma = 0
        draft_cfg = None
    else:
        if draft_cfg is None:
            raise ValueError("speculative=True needs draft_cfg (the "
                             "draft model's ModelConfig)")
        if gamma < 1:
            raise ValueError(f"gamma must be >= 1, got {gamma}")
        if gamma + 1 > C:
            raise ValueError(
                f"speculative verify scores gamma+1={gamma + 1} positions "
                f"through the C-wide chunk channel; set prefill_chunk >= "
                f"gamma+1 (got prefill_chunk={C})")
        if draft_cfg.arch not in ("gpt2", "llama"):
            raise ValueError(f"draft arch {draft_cfg.arch!r} is not "
                             "generable (see models.generate)")
        if draft_cfg.vocab_size != cfg.vocab_size:
            raise ValueError(
                f"draft vocab_size ({draft_cfg.vocab_size}) must match the "
                f"target's ({cfg.vocab_size}) — acceptance compares token "
                "ids")
        if draft_cfg.arch == "gpt2" \
                and max_len + C - 1 > draft_cfg.max_seq_len:
            raise ValueError(
                f"max_len + prefill_chunk - 1 ({max_len + C - 1}) exceeds "
                f"the gpt2 draft position table "
                f"(max_seq_len={draft_cfg.max_seq_len})")
    from ..analysis import maybe_verify_serving
    maybe_verify_serving(D, M, gamma=gamma if speculative else None,
                         prefill_chunk=C)
    if prompt_max < 1 or out_max < 1:
        raise ValueError("prompt_max and out_max must be >= 1")
    if prompt_max + 1 > max_len:
        raise ValueError(f"prompt_max ({prompt_max}) + 1 output token "
                         f"exceeds max_len ({max_len})")
    mlen_alloc = max_len + C - 1
    if cfg.arch == "gpt2" and mlen_alloc > cfg.max_seq_len:
        raise ValueError(f"max_len ({max_len}) + prefill_chunk - 1 "
                         f"({C - 1}) exceeds the gpt2 position table "
                         f"(max_seq_len={cfg.max_seq_len})")
    block = block_ticks or M
    if block < 1:
        raise ValueError(f"block_ticks must be >= 1, got {block}")
    vocab_parallel = tp_axis is not None and cfg.vocab_size % T == 0
    i32 = jnp.int32
    if paged:
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        pmax = -(-(max_len + C - 1) // page_size)
        if n_pages is None:
            n_pages = 1 + M * pmax  # null page + full parity capacity
        if n_pages < 2:
            raise ValueError(f"n_pages must be >= 2 (page 0 is the "
                             f"reserved null page), got {n_pages}")
    else:
        pmax = 0
        n_pages = 0

    # column index where the paged page-table row starts inside meta:
    # speculative mode inserts the isverify flag + gamma draft tokens
    # between the base 4 columns and the page row
    meta_pt = 4 + (1 + gamma if speculative else 0)
    tok_w = gamma + 2 if speculative else 1

    def spmd(*args):
        if speculative:
            (layers_stacked, embed, head,
             dlayers_stacked, dembed, dhead, state) = args
        else:
            layers_stacked, embed, head, state = args
            dlayers_stacked = dembed = dhead = None
        d = jax.lax.axis_index(PIPE_AXIS)
        layers_d = jax.tree.map(lambda x: x[0, 0], layers_stacked)
        layers_d = compute_cast(cfg, layers_d)
        embed_c = compute_cast(cfg, embed)
        head_c = compute_cast(cfg, head)
        dt = jnp.dtype(cfg.dtype)
        if speculative:
            # the draft is replicated: every stage traces it, only stage
            # 0's cond branch executes it (no collectives inside)
            dlayers = jax.tree.map(lambda x: x[0, 0], dlayers_stacked)
            dlayers = compute_cast(draft_cfg, dlayers)
            dembed_c = compute_cast(draft_cfg, dembed)
            dhead_c = compute_cast(draft_cfg, dhead)
            ddt = jnp.dtype(draft_cfg.dtype)
        perm = [(i, (i + 1) % D) for i in range(D)]

        def ring(tree):
            return jax.tree.map(
                lambda x: jax.lax.ppermute(x, PIPE_AXIS, perm), tree)

        def tick(carry, _):
            st = dict(carry)
            u = st["u"]
            h_chan, tok_chan, meta = st["h"], st["tok_chan"], st["meta"]
            kc, vc = st["kc"], st["vc"]
            is0 = d == 0

            # ---- bank the token(s) that rode in (meta came with them, so
            # a dead or mid-prefill hop banks nothing). Banking runs
            # BEFORE the serve so the M == D same-tick arrive/serve case
            # sees fresh state.
            bank = is0 & (meta[2] == 1) & (meta[3] == 1)
            ga = jnp.mod(u - D, M)
            if speculative:
                # a verify visit delivers up to gamma+1 accepted tokens at
                # once; a prefill/catch-up visit delivers one (n_acc == 1
                # rode the channel). The static gamma+1 loop banks token
                # j only while j < n_acc and neither budget nor EOS has
                # retired the slot mid-acceptance — the oracle stops at
                # EOS, so accepted tokens past it must never land.
                vflag = meta[4] == 1
                n_acc = jnp.clip(tok_chan[gamma + 1], 1, gamma + 1)
                em0 = st["emitted"][ga]
                em_run = em0
                fin_run = jnp.zeros((), bool)
                out_buf, t_first = st["out_buf"], st["t_first"]
                t_finish = st["t_finish"]
                for j in range(gamma + 1):
                    tk_j = tok_chan[j]
                    do = bank & (j < n_acc) & ~fin_run
                    out_buf = jnp.where(
                        do, out_buf.at[ga, em_run].set(tk_j), out_buf)
                    t_first = jnp.where(do & (em_run == 0),
                                        t_first.at[ga].set(u), t_first)
                    em_run = em_run + do.astype(i32)
                    fin_tok = em_run >= st["budget"][ga]
                    if eos_id is not None:
                        fin_tok = fin_tok | (tk_j == eos_id)
                    fin_now = do & fin_tok
                    t_finish = jnp.where(fin_now, t_finish.at[ga].set(u),
                                         t_finish)
                    fin_run = fin_run | fin_now
                st["out_buf"], st["t_first"] = out_buf, t_first
                st["t_finish"] = t_finish
                st["finished"] = jnp.where(
                    bank,
                    st["finished"].at[ga].set(st["finished"][ga] | fin_run),
                    st["finished"])
                st["emitted"] = jnp.where(
                    bank, st["emitted"].at[ga].set(em_run), st["emitted"])
                # the last banked token seeds the slot's next visit; a
                # retired slot's value is never read
                last = tok_chan[jnp.maximum(em_run - em0, 1) - 1]
                st["tok"] = jnp.where(bank, st["tok"].at[ga].set(last),
                                      st["tok"])
                # verify visits advance the target/draft frontiers HERE
                # (serve time could not know n_acc); rejected rows are
                # left past the frontier for the next write to cover
                padd = jnp.where(bank & vflag, n_acc, 0)
                st["pos"] = st["pos"].at[ga].add(padd)
                st["dpos"] = st["dpos"].at[ga].add(padd)
                st["spec_visits"] = st["spec_visits"].at[ga].add(
                    (bank & vflag).astype(i32))
                st["spec_accepted"] = st["spec_accepted"].at[ga].add(
                    jnp.where(bank & vflag, n_acc - 1, 0))
            else:
                tk = tok_chan[0]
                em = st["emitted"][ga]
                st["out_buf"] = jnp.where(
                    bank, st["out_buf"].at[ga, em].set(tk), st["out_buf"])
                st["t_first"] = jnp.where(
                    bank & (em == 0), st["t_first"].at[ga].set(u),
                    st["t_first"])
                em2 = em + 1
                fin_now = em2 >= st["budget"][ga]
                if eos_id is not None:
                    fin_now = fin_now | (tk == eos_id)
                st["finished"] = jnp.where(
                    bank,
                    st["finished"].at[ga].set(st["finished"][ga] | fin_now),
                    st["finished"])
                st["t_finish"] = jnp.where(
                    bank & fin_now, st["t_finish"].at[ga].set(u),
                    st["t_finish"])
                st["emitted"] = jnp.where(
                    bank, st["emitted"].at[ga].set(em2), st["emitted"])
                st["tok"] = jnp.where(bank, st["tok"].at[ga].set(tk),
                                      st["tok"])

            # ---- serve slot g = u mod M. Stage 0 builds the metadata
            # from its slot tables; later stages replay the copy that
            # rode in with the activations.
            g = jnp.mod(u, M)
            act0 = st["live"][g] & ~st["finished"][g]
            pleft = st["prefill_left"][g]
            ispre = pleft > 0
            off0 = st["pos"][g]
            if speculative:
                # three visit kinds: chunked prefill (as ever), draft
                # catch-up decode (the draft's frontier trails the
                # target's — after a paged prefix skip the draft holds no
                # KV for the matched tokens), and verify (frontiers
                # aligned: propose gamma, score gamma+1)
                dp0 = st["dpos"][g]
                isver = (~ispre) & (dp0 >= off0)
                sv0 = jnp.where(ispre, jnp.minimum(pleft, C),
                                jnp.where(isver, gamma + 1, 1))
            else:
                isver = None
                sv0 = jnp.where(ispre, jnp.minimum(pleft, C), 1)
            sf0 = jnp.where(ispre, (pleft <= C).astype(i32), 1)

            if speculative:
                # ---- the draft model's turn (stage 0 only). Catch-up
                # visits feed it one C-wide chunk at its own frontier —
                # token source spans the prompt then the already-banked
                # output, so it converges on the target within a few
                # visits. Verify visits run gamma sequential single-row
                # steps from the last banked token; the proposals ride
                # the metadata ring to the last stage for acceptance.
                def draft_run(op):
                    dk, dv = op

                    def catchup(op2):
                        dk, dv = op2
                        hi = jnp.where(ispre, st["plen"][g], off0 + 1)
                        dn = jnp.maximum(
                            jnp.minimum(C, hi - dp0), 0)
                        pp = dp0 + jnp.arange(C, dtype=i32)
                        plen_g = st["plen"][g]
                        from_prompt = jnp.take(
                            st["prompt_buf"][g],
                            jnp.clip(pp, 0,
                                     st["prompt_buf"].shape[1] - 1))
                        from_out = jnp.take(
                            st["out_buf"][g],
                            jnp.clip(pp - plen_g, 0, out_max - 1))
                        toks = jnp.where(pp < plen_g, from_prompt,
                                         from_out)[None]
                        xd = _embed_at(draft_cfg, dembed_c, toks,
                                       dp0).astype(ddt)
                        _, dk, dv = _slot_cache_apply(
                            draft_cfg, dlayers, xd, dk, dv, g, 1, dp0, C)
                        return (dk, dv), jnp.zeros((gamma,), i32), dn

                    def propose(op2):
                        dk, dv = op2
                        t = st["tok"][g]
                        toks = []
                        for i in range(gamma):
                            xd = _embed_at(draft_cfg, dembed_c,
                                           t[None, None],
                                           dp0 + i).astype(ddt)
                            yd, dk, dv = _slot_cache_apply(
                                draft_cfg, dlayers, xd, dk, dv, g, 1,
                                dp0 + i, 1)
                            t = _head_token(draft_cfg, dhead_c, dembed_c,
                                            yd, None)[0]
                            toks.append(t)
                        return ((dk, dv), jnp.stack(toks),
                                jnp.zeros((), i32))

                    return jax.lax.cond(ispre | (dp0 < off0), catchup,
                                        propose, op)

                def draft_noop(op):
                    return op, jnp.zeros((gamma,), i32), jnp.zeros((), i32)

                ((dkc_n, dvc_n), draft_toks, dadv) = jax.lax.cond(
                    is0 & act0, draft_run, draft_noop,
                    (st["dkc"], st["dvc"]))
                st["dkc"], st["dvc"] = dkc_n, dvc_n
                st["dpos"] = jnp.where(
                    is0 & act0, st["dpos"].at[g].add(dadv), st["dpos"])
                meta0 = jnp.concatenate([
                    jnp.stack([off0, sv0, sf0, act0.astype(i32),
                               isver.astype(i32)]), draft_toks])
            else:
                draft_toks = None
                meta0 = jnp.stack([off0, sv0, sf0, act0.astype(i32)])
            if paged:
                # the served slot's page-table row rides the ring with
                # the metadata: stages d > 0 gather/scatter through the
                # copy that arrived with the activations and need no
                # slot knowledge, exactly like the offset
                meta0 = jnp.concatenate([meta0, st["page_tbl"][g]])
            meta_eff = jnp.where(is0, meta0, meta)
            offset, s_valid = meta_eff[0], meta_eff[1]
            active = meta_eff[3] == 1

            # stage 0 consumes the slot's frontier for this visit (verify
            # visits advance at banking instead — n_acc is data there)
            upd = is0 & act0
            if speculative:
                adv = jnp.where(ispre, sv0, 1)
                st["pos"] = jnp.where(upd & ~isver,
                                      st["pos"].at[g].set(off0 + adv),
                                      st["pos"])
            else:
                st["pos"] = jnp.where(upd, st["pos"].at[g].set(off0 + sv0),
                                      st["pos"])
            st["prefill_left"] = jnp.where(
                upd & ispre,
                st["prefill_left"].at[g].set(pleft - sv0),
                st["prefill_left"])

            # the C-token input: next prompt chunk while prefilling, the
            # last banked token (plus C-1 junk rows) while decoding, or
            # [t0, d_1..d_gamma] on a verify visit. The junk rows' cache
            # writes land past the valid frontier and are overwritten
            # before the frontier reaches them.
            pstart = st["plen"][g] - pleft
            chunk = jax.lax.dynamic_slice(st["prompt_buf"][g],
                                          (jnp.maximum(pstart, 0),), (C,))
            dec = jnp.zeros((C,), i32).at[0].set(st["tok"][g])
            if speculative:
                ver = jax.lax.dynamic_update_slice(dec, draft_toks, (1,))
                toks_in = jnp.where(
                    ispre, chunk, jnp.where(isver, ver, dec))[None]
            else:
                toks_in = jnp.where(ispre, chunk, dec)[None]  # [1, C]
            x0 = _embed_at(cfg, embed_c, toks_in, offset).astype(dt)
            x = jnp.where(is0, x0, h_chan)

            def unit(op):
                kc, vc = op
                if paged:
                    y, kc, vc = _paged_cache_apply(cfg, layers_d, x, kc, vc,
                                                   meta_eff[meta_pt:],
                                                   offset, C,
                                                   tp_axis=tp_axis, tp_size=T)
                else:
                    y, kc, vc = _slot_cache_apply(cfg, layers_d, x, kc, vc,
                                                  g, 1, offset, C,
                                                  tp_axis=tp_axis, tp_size=T)
                if speculative:
                    # score every chunk row in one batched head call (rows
                    # become the batch dim, so the vocab-parallel
                    # shard/all_gather path is reused unchanged), then
                    # take the longest matching prefix of the proposals:
                    # d_i is accepted while d_i == y_{i-1}, and y_n_acc-1
                    # is the bonus token the target emits for free
                    def head_all():
                        return _head_token(cfg, head_c, embed_c,
                                           jnp.swapaxes(y, 0, 1), None,
                                           tp_axis=tp_axis, tp_size=T,
                                           vocab_parallel=vocab_parallel)

                    y_all = jax.lax.cond(
                        (d == D - 1) & (meta_eff[2] == 1),
                        head_all, lambda: jnp.zeros((C,), i32))
                    isv = meta_eff[4] == 1
                    drafts = meta_eff[5:5 + gamma]
                    n_acc = jnp.where(isv, spec_accept_len(drafts, y_all),
                                      1)
                    dec_tok = jnp.take(y_all,
                                       jnp.maximum(s_valid - 1, 0))
                    ver_vec = jnp.concatenate([y_all[:gamma + 1],
                                               n_acc[None]])
                    dec_vec = jnp.zeros((tok_w,), i32) \
                        .at[0].set(dec_tok).at[gamma + 1].set(1)
                    tok = jnp.where(isv, ver_vec, dec_vec)
                else:
                    y_last = jax.lax.dynamic_slice_in_dim(y, s_valid - 1, 1,
                                                          axis=1)
                    tok = jax.lax.cond(
                        (d == D - 1) & (meta_eff[2] == 1),
                        lambda: _head_token(cfg, head_c, embed_c, y_last,
                                            None, tp_axis=tp_axis, tp_size=T,
                                            vocab_parallel=vocab_parallel),
                        lambda: jnp.zeros((1,), i32))
                return (kc, vc), y, tok

            def noop(op):
                return op, jnp.zeros_like(h_chan), jnp.zeros((tok_w,), i32)

            (kc, vc), y, tok = jax.lax.cond(active, unit, noop, (kc, vc))
            st["h"], st["tok_chan"], st["meta"] = ring((y, tok, meta_eff))
            st["kc"], st["vc"] = kc, vc
            st["u"] = u + 1
            return st, None

        # per-device leaves arrive with a leading singleton shard dim
        shard_keys = ("h", "tok_chan", "meta", "kc", "vc") + \
            (("dkc", "dvc") if speculative else ())
        inner = dict(state)
        for k in shard_keys:
            inner[k] = state[k][0]
        if paged:
            # execute the host's queued copy-on-write commands before any
            # tick runs: divergence pages become private so the block's
            # writes never touch a shared (refcount > 1) page. Vectorized
            # over slots; -1 entries degenerate to rewriting the null
            # page with its own content. At most one copy per admission.
            cs, cd = inner["cow_src"], inner["cow_dst"]
            m = cd > 0
            ss = jnp.where(m, cs, 0)
            sd = jnp.where(m, cd, 0)
            mb = m[None, :, None, None, None]
            for key in ("kc", "vc"):
                pool = inner[key]
                vals = jnp.where(mb, pool[:, ss], pool[:, sd])
                inner[key] = pool.at[:, sd].set(vals)
        inner, _ = jax.lax.scan(tick, inner, None, length=block)
        if paged:
            # the copies ran: return the command pair cleared, so the
            # host's post-block fetch resets its mirrors and a stale
            # re-upload can never re-execute a copy over fresh writes
            inner["cow_src"] = jnp.full((M,), -1, i32)
            inner["cow_dst"] = jnp.full((M,), -1, i32)

        # stage 0's slot tables are authoritative; replicate them so the
        # host (and the next block on every stage) sees one truth
        out = dict(inner)
        rep_keys = ("tok", "pos", "prefill_left", "emitted", "finished",
                    "out_buf", "t_first", "t_finish") + \
            (_SPEC_KEYS if speculative else ())
        for k in rep_keys:
            v = inner[k]
            rep = jax.lax.psum(jnp.where(d == 0, v.astype(i32), 0), PIPE_AXIS)
            out[k] = rep.astype(v.dtype)
        for k in shard_keys:
            out[k] = out[k][None]
        return out

    layer_spec = (_dense_layer_specs(cfg, T, None) if T > 1
                  else P(PIPE_AXIS))
    cache_spec = (P(PIPE_AXIS, None, None, None, MODEL_AXIS) if T > 1
                  else P(PIPE_AXIS))
    state_spec = {
        "u": P(), "h": P(PIPE_AXIS), "tok_chan": P(PIPE_AXIS),
        "meta": P(PIPE_AXIS), "kc": cache_spec, "vc": cache_spec,
        "tok": P(), "pos": P(), "prefill_left": P(), "emitted": P(),
        "budget": P(), "plen": P(), "live": P(), "finished": P(),
        "prompt_buf": P(), "out_buf": P(), "t_first": P(), "t_finish": P(),
    }
    if paged:
        # table + COW commands are replicated host-written scalars/rows;
        # the pool itself reuses the kc/vc cache spec (same rank, the
        # model axis still shards the n_kv dim)
        state_spec.update({"page_tbl": P(), "cow_src": P(), "cow_dst": P()})
    if speculative:
        # the draft cache rides the pipe-axis shard slot like the target
        # cache (only stage 0's shard holds data — the draft never runs
        # under TP, so no model-axis dim); frontiers/counters are
        # replicated stage-0-authoritative vectors like pos/emitted
        state_spec.update({"dkc": P(PIPE_AXIS), "dvc": P(PIPE_AXIS),
                           "dpos": P(), "spec_visits": P(),
                           "spec_accepted": P()})
        in_specs = (layer_spec, P(), P(), P(), P(), P(), state_spec)
        donate = 6
    else:
        in_specs = (layer_spec, P(), P(), state_spec)
        donate = 3
    sharded = _shard_map(spmd, mesh, in_specs=in_specs,
                         out_specs=state_spec)

    # donate the state (caches included): the block is state -> state', so
    # XLA reuses the cache buffers instead of double-allocating them. The
    # new state is pinned to the specs the old one came in with: left to
    # itself jit drops a size-1 'pipe' axis from the outputs' specs, and on
    # a one-device mesh the second block then compiled a second time.
    from jax.sharding import NamedSharding
    step = jax.jit(sharded, donate_argnums=(donate,),
                   out_shardings={k: NamedSharding(mesh, spec)
                                  for k, spec in state_spec.items()})

    return ServingProgram(cfg, mesh, n_slots=M, max_len=max_len,
                          prompt_max=prompt_max, out_max=out_max,
                          prefill_chunk=C, block_ticks=block, eos_id=eos_id,
                          step_fn=step, state_specs=state_spec,
                          paged=paged, page_size=page_size if paged else 0,
                          n_pages=n_pages, speculative=speculative,
                          gamma=gamma, draft_cfg=draft_cfg)


class ServingEngine:
    """Host-side scheduler driving a :class:`ServingProgram`.

    ``submit`` queues requests; ``run`` (or repeated ``run_block``)
    advances the ring in jitted blocks, retiring finished slots and
    admitting queued requests between blocks. ``report`` (optional
    :class:`...utils.telemetry.RunReport`) receives one event per
    admission/completion for the crash-safe JSONL stream.

    The scheduler loop is exception-safe per request: ``submit`` raises
    on an invalid request (the direct-API contract), but ``run`` retires
    an invalid or poisoned request with a ``status="failed"``
    :class:`Completion` plus a ``serve_failed`` report event and keeps
    serving — one bad request must not wedge the live slots.
    ``fault_plan`` (``...utils.resilience.FaultPlan``) injects
    deterministic admission faults (``serve_poison_rids``) and per-rid
    arrival delays (``serve_delay``) for the resilience tests.
    """

    def __init__(self, program: ServingProgram, params, *,
                 draft_params=None, report=None, fault_plan=None,
                 prefix_cache: bool = True) -> None:
        self.program = program
        self.weights = program.prepare(params, draft_params)
        self.report = report
        self.fault_plan = fault_plan
        self.prefix_cache = prefix_cache
        self.reset()

    def reset(self) -> None:
        self.state = self.program.init_state()
        # numpy mirrors of the scheduler-owned leaves: the host mutates
        # THESE (plain array writes — no per-slot jitted updates to
        # compile), and only dirty keys get re-uploaded before a block
        self.host: Dict[str, np.ndarray] = {
            k: np.array(self.state[k]) for k in self.program.sched_keys}
        self._dirty: set = set()
        self.pending: deque = deque()
        self.waiting: deque = deque()
        self.completions: List[Completion] = []
        self.occupancy: List[Any] = []
        self.queue_depth: List[Any] = []
        self._slot_req: Dict[int, Request] = {}
        self._slot_admit: Dict[int, int] = {}
        self._tick = 0
        self._busy_ticks = 0
        self.paging = None
        self.pages_used: List[Any] = []
        self.page_fragmentation: List[Any] = []
        self._n_backpressure = 0
        self._spec_visits = 0
        self._spec_accepted = 0
        self.acceptance_series: List[Any] = []
        if self.program.paged:
            from .paging import PagedKVAllocator
            p = self.program
            self.paging = PagedKVAllocator(
                n_pages=p.n_pages, page_size=p.page_size,
                max_pages_per_slot=p.max_pages_per_slot,
                prefill_chunk=p.prefill_chunk,
                prefix_cache=self.prefix_cache)

    # -- request intake --------------------------------------------------

    def submit(self, req: Request) -> None:
        """Validate and queue one request (ordered by ``arrival``)."""
        p = self.program
        plen = len(req.prompt)
        if plen < 1 or plen > p.prompt_max:
            raise ValueError(f"request {req.rid}: prompt length {plen} "
                             f"outside [1, prompt_max={p.prompt_max}]")
        if req.max_new_tokens < 1 or req.max_new_tokens > p.out_max:
            raise ValueError(f"request {req.rid}: max_new_tokens="
                             f"{req.max_new_tokens} outside [1, out_max="
                             f"{p.out_max}]")
        if plen + req.max_new_tokens > p.max_len:
            raise ValueError(
                f"request {req.rid}: prompt ({plen}) + budget "
                f"({req.max_new_tokens}) overflows the slot max_len "
                f"({p.max_len})")
        self.pending.append(req)

    # -- scheduling ------------------------------------------------------

    def _admit(self, slot: int, req: Request, plan=None) -> None:
        # plain numpy writes on the host mirrors: per-slot jnp ``.at[]``
        # updates would each compile a one-off XLA program per
        # (field, slot) pair and dominate CPU wall-clock
        h, p = self.host, self.program
        plen = len(req.prompt)
        h["prompt_buf"][slot] = 0
        h["prompt_buf"][slot, :plen] = np.asarray(req.prompt, np.int32)
        h["plen"][slot] = plen
        if plan is not None:
            # paged admission: map the planned pages, queue the COW copy,
            # and start the frontier past the cached prefix — prefill for
            # the matched tokens is skipped outright
            from ..analysis import maybe_verify_page_table
            maybe_verify_page_table(
                plan.pages, refcount=self.paging.pool.refcount,
                n_pages=p.n_pages, page_size=p.page_size,
                write_lo=plan.matched_len,
                write_hi=plen + req.max_new_tokens + p.prefill_chunk - 1,
                cow_dst=plan.cow_dst)
            h["page_tbl"][slot] = 0
            h["page_tbl"][slot, :plan.n_pages] = np.asarray(plan.pages,
                                                            np.int32)
            h["cow_src"][slot] = plan.cow_src
            h["cow_dst"][slot] = plan.cow_dst
            self._dirty.update(("page_tbl", "cow_src", "cow_dst"))
            self.paging.bind(slot, plan)
            h["prefill_left"][slot] = plen - plan.matched_len
            h["pos"][slot] = plan.matched_len
        else:
            h["prefill_left"][slot] = plen
            h["pos"][slot] = 0
        h["emitted"][slot] = 0
        h["budget"][slot] = req.max_new_tokens
        h["tok"][slot] = 0
        h["out_buf"][slot] = 0
        h["t_first"][slot] = -1
        h["t_finish"][slot] = -1
        h["finished"][slot] = False
        h["live"][slot] = True
        self._dirty.update(("prompt_buf", "plen", "prefill_left", "pos",
                            "emitted", "budget", "tok", "out_buf", "t_first",
                            "t_finish", "finished", "live"))
        if p.speculative:
            # the draft starts cold even after a paged prefix skip (its
            # KV was never cached) — catch-up visits close the gap
            h["dpos"][slot] = 0
            h["spec_visits"][slot] = 0
            h["spec_accepted"][slot] = 0
            self._dirty.update(_SPEC_KEYS)
        self._slot_req[slot] = req
        self._slot_admit[slot] = self._tick
        if self.report is not None:
            paged_kv = ({"matched_len": plan.matched_len,
                         "n_pages": plan.n_pages}
                        if plan is not None else {})
            self.report.event("serve_admit", rid=req.rid, slot=slot,
                              tick=self._tick, prompt_len=plen,
                              budget=req.max_new_tokens,
                              arrival=req.arrival,
                              wait_ticks=self._tick - req.arrival,
                              **paged_kv)

    def _scrub_slot(self, slot: int) -> None:
        # a failed admission may have left partial mirror writes: park the
        # slot dead (live=False masks every other field) and drop any
        # scheduler bookkeeping so the slot goes straight back to free
        h = self.host
        h["live"][slot] = False
        h["finished"][slot] = False
        self._dirty.update(("live", "finished"))
        if self.paging is not None:
            # return the slot's pages uncached and cancel any queued COW
            # (the copy must never run into a page that just went free)
            self.paging.release(slot)
            h["page_tbl"][slot] = 0
            h["cow_src"][slot] = -1
            h["cow_dst"][slot] = -1
            self._dirty.update(("page_tbl", "cow_src", "cow_dst"))
        self._slot_req.pop(slot, None)
        self._slot_admit.pop(slot, None)

    def _fail_request(self, req: Request, reason: str) -> None:
        """Retire ``req`` unserved with a ``failed`` completion + event."""
        self.completions.append(Completion(
            rid=req.rid, prompt=list(map(int, req.prompt)), tokens=[],
            slot=-1, admit_tick=-1, first_token_tick=-1, finish_tick=-1,
            arrival=req.arrival, status="failed", reason=reason))
        if self.report is not None:
            self.report.event("serve_failed", rid=req.rid, tick=self._tick,
                              reason=reason)
            self.report.count("serve_failed")

    def _harvest(self) -> None:
        host = self.host
        for slot, req in list(self._slot_req.items()):
            if not host["finished"][slot]:
                continue
            n = int(host["emitted"][slot])
            comp = Completion(
                rid=req.rid, prompt=list(map(int, req.prompt)),
                tokens=[int(t) for t in host["out_buf"][slot][:n]],
                slot=slot, admit_tick=self._slot_admit[slot],
                first_token_tick=int(host["t_first"][slot]),
                finish_tick=int(host["t_finish"][slot]),
                arrival=req.arrival)
            self.completions.append(comp)
            host["live"][slot] = False
            self._dirty.add("live")
            if self.paging is not None:
                # decref the slot's pages and cache the prompt-covered
                # ones for future prefix hits; clear the stale table row
                # (a dead slot's row is never gathered, but a zeroed row
                # keeps the page-table discipline check trivially green)
                self.paging.retire(slot, req.prompt)
                host["page_tbl"][slot] = 0
                self._dirty.add("page_tbl")
            del self._slot_req[slot]
            del self._slot_admit[slot]
            spec_kv = {}
            if self.program.speculative:
                sv = int(host["spec_visits"][slot])
                sa = int(host["spec_accepted"][slot])
                self._spec_visits += sv
                self._spec_accepted += sa
                spec_kv = {"spec_verify_visits": sv, "spec_accepted": sa,
                           "accepted_len_mean": (round(1 + sa / sv, 4)
                                                 if sv else None)}
            if self.report is not None:
                self.report.event("serve_finish", rid=req.rid, slot=slot,
                                  tick=self._tick, n_tokens=n,
                                  ttft_ticks=comp.ttft_ticks, **spec_kv)

    def run(self, requests: Sequence[Request], *,
            policy: str = "continuous",
            max_blocks: int = 200_000) -> ServeResult:
        """Serve ``requests`` to completion and return the
        :class:`ServeResult`. ``policy="continuous"`` refills freed
        slots immediately; ``policy="static"`` admits a fresh batch only
        once every slot has drained (the fill-drain baseline)."""
        if policy not in ("continuous", "static"):
            raise ValueError(f"unknown policy {policy!r} (continuous|static)")
        self.reset()
        plan = self.fault_plan
        delay = dict(getattr(plan, "serve_delay", None) or {})
        poison = set(getattr(plan, "serve_poison_rids", ()) or ())
        # injected stragglers shift arrival BEFORE the sort — the pending
        # queue's pop loop relies on arrival order
        retimed = [dataclasses.replace(r, arrival=r.arrival + delay[r.rid])
                   if r.rid in delay else r for r in requests]
        for r in sorted(retimed, key=lambda r: r.arrival):
            try:
                self.submit(r)
            except ValueError as e:
                # over-budget prompt etc.: a per-request outcome, not a
                # scheduler crash — the live slots keep serving
                self._fail_request(r, str(e))
        p = self.program
        free = list(range(p.n_slots))
        wall0 = time.perf_counter()
        for _ in range(max_blocks):
            while self.pending and self.pending[0].arrival <= self._tick:
                self.waiting.append(self.pending.popleft())
            if policy == "continuous" or len(free) == p.n_slots:
                while free and self.waiting:
                    req = self.waiting[0]
                    plan = None
                    if self.paging is not None:
                        if not self.paging.admissible(len(req.prompt),
                                                      req.max_new_tokens):
                            # needs more pages than the pool has: no
                            # amount of waiting fixes it — per-request
                            # failure, not backpressure
                            self.waiting.popleft()
                            self._fail_request(
                                req, f"request needs "
                                f"{self.paging.pages_needed(len(req.prompt), req.max_new_tokens)} "
                                f"pages but the pool holds "
                                f"{self.paging.pool.capacity}")
                            continue
                        plan = self.paging.try_admit(req.prompt,
                                                     req.max_new_tokens)
                        if plan is None:
                            # pool exhausted: backpressure. The request
                            # stays at the head of the queue; if slots
                            # are active the block below retires them
                            # and frees pages. With nothing active every
                            # page is trie-held and evictable, so
                            # try_admit cannot fail — defend anyway.
                            self._n_backpressure += 1
                            if not self._slot_req:
                                self.waiting.popleft()
                                self._fail_request(
                                    req, "page pool exhausted with no "
                                    "active slots to retire")
                                continue
                            break
                    self.waiting.popleft()
                    slot = free[0]
                    try:
                        if req.rid in poison:
                            from ..utils.resilience import SimulatedFault
                            raise SimulatedFault(
                                f"injected admission fault for rid "
                                f"{req.rid}")
                        self._admit(slot, req, plan)
                    except Exception as e:  # noqa: BLE001 — quarantine,
                        # retire the request, keep the slot free and the
                        # ring serving (wedging all slots is the failure
                        # mode this loop exists to prevent)
                        if (plan is not None
                                and self.paging.plan_for(slot) is not plan):
                            # admission died before the slot bound the
                            # plan: return its pages directly
                            self.paging.release_plan(plan)
                        self._scrub_slot(slot)
                        self._fail_request(req, f"admission failed: {e}")
                        continue
                    free.pop(0)
            if not self._slot_req:
                if not self.waiting and not self.pending:
                    break  # drained
                if not self.waiting:
                    # idle gap before the next arrival: nothing is in
                    # flight (all slots dead => all ring hops dead), so
                    # jumping the tick counter is observationally the
                    # same as spinning empty blocks. The jump skips the
                    # block-boundary sampling below, so bank an explicit
                    # zero sample at the jump target — otherwise
                    # occupancy/queue-depth time-integrals silently
                    # interpolate across the idle span.
                    nxt = int(np.ceil(self.pending[0].arrival))
                    self._tick = max(self._tick, nxt)
                    self.host["u"] = np.asarray(self._tick, np.int32)
                    self._dirty.add("u")
                    self.occupancy.append((self._tick, 0))
                    self.queue_depth.append((self._tick, 0))
                    if self.paging is not None:
                        # pages may still be trie-held across an idle gap
                        self.pages_used.append(
                            (self._tick, self.paging.pages_used))
                        self.page_fragmentation.append((self._tick, 0.0))
                    continue
            # upload only the leaves the scheduler touched, in one batched
            # transfer, each pinned to its spec so the jitted block sees
            # one stable signature
            if self._dirty:
                dirty = sorted(self._dirty)
                vals = jax.device_put([self.host[k] for k in dirty],
                                      [p.sharding(k) for k in dirty])
                self.state.update(zip(dirty, vals))
                self._dirty.clear()
            tick_before = self._tick
            self.state = p.step(*self.weights, self.state)
            fetched = jax.device_get({k: self.state[k]
                                      for k in p.host_keys})
            self.host.update(  # np.array: device_get views can be read-only
                {k: np.array(v) for k, v in fetched.items()})
            if self.paging is not None:
                # the block executed any queued COW copies (and the fetch
                # above reset the cow mirrors to the cleared -1s): the
                # source pages no longer need their safety hold
                self.paging.cow_flush()
            self._tick = int(self.host["u"])
            # every executed block had >= 1 live slot at entry (the empty
            # cases break or fast-forward above), so its ticks are busy
            self._busy_ticks += self._tick - tick_before
            n_active = int((self.host["live"] & ~self.host["finished"]).sum())
            self.occupancy.append((self._tick, n_active))
            # admission-queue depth at the same boundary: requests that
            # have arrived by now but hold no slot yet (the waiting deque
            # plus the pending head the next loop iteration will move)
            n_wait = len(self.waiting)
            for r in self.pending:  # arrival-sorted: stop at the future
                if r.arrival > self._tick:
                    break
                n_wait += 1
            self.queue_depth.append((self._tick, n_wait))
            if self.paging is not None:
                # the committed-frontier ledger follows pos, which only
                # ever advances by ACCEPTED rows (speculative overshoot
                # lands past it and is rolled back by overwrite), so
                # commits, fragmentation and later trie inserts all see
                # the accepted frontier only
                frontier = {s: int(self.host["pos"][s])
                            for s in self._slot_req}
                for s, f in frontier.items():
                    self.paging.advance(s, f)
                self.pages_used.append((self._tick, self.paging.pages_used))
                self.page_fragmentation.append(
                    (self._tick,
                     round(self.paging.fragmentation(frontier), 6)))
            if p.speculative:
                # running acceptance rate at this boundary: harvested
                # totals plus the still-bound slots' live counters
                tv = self._spec_visits + sum(
                    int(self.host["spec_visits"][s]) for s in self._slot_req)
                ta = self._spec_accepted + sum(
                    int(self.host["spec_accepted"][s])
                    for s in self._slot_req)
                self.acceptance_series.append(
                    (self._tick,
                     round(ta / (p.gamma * tv), 6) if tv else None))
            self._harvest()
            free = [g for g in range(p.n_slots) if g not in self._slot_req]
        else:
            raise RuntimeError(f"serving did not drain within {max_blocks} "
                               "blocks — check arrivals/budgets")
        wall = time.perf_counter() - wall0
        paged_kv: Dict[str, Any] = {}
        if self.paging is not None:
            self.paging.cow_flush()  # a scrubbed final admission's hold
            paged_kv = dict(
                paged=True, pages_capacity=self.paging.pool.capacity,
                pages_used=self.pages_used,
                page_fragmentation=self.page_fragmentation,
                prefix_hit_rate=round(self.paging.prefix_hit_rate(), 6),
                prefill_skipped_tokens=self.paging.matched_tokens,
                n_cow=self.paging.n_cow,
                n_backpressure=self._n_backpressure)
        spec_kv: Dict[str, Any] = {}
        if p.speculative:
            spec_kv = dict(speculative=True, gamma=p.gamma,
                           spec_verify_visits=self._spec_visits,
                           spec_accepted_tokens=self._spec_accepted,
                           acceptance_series=self.acceptance_series)
        result = ServeResult(completions=self.completions,
                             occupancy=self.occupancy, ticks=self._tick,
                             wall_s=wall, n_slots=p.n_slots, policy=policy,
                             queue_depth=self.queue_depth,
                             busy_ticks=self._busy_ticks, **paged_kv,
                             **spec_kv)
        if self.report is not None:
            # one event per run with the measured tick rate — the factor
            # the cost model's predicted per-tick time reconciles against
            self.report.event(
                "serve_run", policy=policy, ticks=result.ticks,
                busy_ticks=result.busy_ticks,
                wall_s=round(wall, 4), tokens_out=result.tokens_out,
                s_per_tick=(round(wall / result.ticks, 6)
                            if result.ticks else None),
                **({"prefix_hit_rate": result.prefix_hit_rate,
                    "n_backpressure": result.n_backpressure,
                    "n_cow": result.n_cow} if self.paging is not None
                   else {}),
                **({"gamma": p.gamma,
                    "acceptance_rate": result.acceptance_rate,
                    "accepted_len_mean": result.accepted_len_mean}
                   if p.speculative else {}))
        return result
