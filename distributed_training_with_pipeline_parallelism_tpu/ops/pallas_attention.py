"""Fused flash-attention (forward + backward) in Pallas (Mosaic) for TPU.

This is the framework's native-kernel layer — the TPU analog of the C++/ATen
kernels the reference leans on through torch (SURVEY.md §2.3: "if a custom
native kernel layer is wanted ... it is Pallas (Mosaic) kernels"). The
forward computes softmax(QK^T/sqrt(d))V one query block at a time with the
online softmax recurrence (Dao et al., arXiv:2205.14135), so the [s, s]
score matrix never hits HBM: per grid step it lives in VMEM as a
[block_q, block_k] tile feeding the MXU. The forward also emits the per-row
logsumexp (lse), which is what makes the backward flash too.

Backward (the real flash backward, not dense recompute): with o and lse
saved, ``delta = rowsum(do * o)`` and the probabilities rebuild blockwise as
``p = exp(s - lse)`` — no second online-softmax pass and no [s, s]
materialization anywhere:

- ``dq`` kernel: grid (batch*heads, q blocks); each instance loops over the
  live k blocks accumulating ``dq += (p * (do v^T - delta)) k``.
- ``dk/dv`` kernel: grid (batch*heads, k blocks); each instance loops over
  the live q blocks accumulating ``dv += p^T do`` and
  ``dk += (p * (do v^T - delta))^T q``.

Causal masking prunes both loops to live blocks (at/below the diagonal for
dq, at/right of it for dk/dv), and a sliding ``window`` tightens both
bounds, so backward compute scales the same way forward does.

Measured kernel disciplines (rounds 3-4, one v5e chip — docs/profiles/):

- **MXU**: every dot keeps its inputs in the storage dtype (bf16 on the
  ladder configs) with f32 accumulation via ``preferred_element_type`` —
  f32 matmul inputs run the v5e MXU at a fraction of bf16 throughput.
  Softmax statistics (m, l, lse) stay f32.
- **VPU**: at head_dim 64 these kernels are vector-unit-bound (~256 MXU
  FLOPs but ~10 vector ops per score element against a ~50:1 MXU:VPU
  peak ratio at the corrected 197 TFLOP/s bf16 peak), so per-score-element
  vector work is minimized three ways (round 4):
  1. **exp2 domain**: the softmax scale and the ``log2(e)`` factor inside
     every ``exp`` fold into ONE constant applied to the [block_q, head_dim]
     q tile (``qc = q * scale*log2e``), so the per-element path is
     ``exp2(s2 - m2)`` with no multiply — the saved lse is log2-domain
     (internal: it only ever feeds these backward kernels).
  2. **static diagonal split**: on the plain causal training path
     (bq == bk, no padding/window) the one diagonal block per loop is
     peeled out STATICALLY — interior blocks run with no mask arithmetic
     at all, and the diagonal applies a precomputed additive 0/NEG_INF
     tile (one add/elem instead of compare+select). A scalar `lax.cond`
     gate was measured SLOWER (it costs Mosaic its k-loop software
     pipelining: fwd 1.16 -> 1.66 ms at gpt2-small shapes); the static
     peel has no branch. Other paths keep the k-block-invariant
     difference-tile mask (one compare per edge, scalar-broadcast).
     Masked scores go to NEG_INF so ``exp2`` underflows dead elements to
     exactly 0.0; dead-row guards are only paid where a fully-dead first
     block is reachable (a sliding window's left edge).
  3. **one-sweep backward**: dq, dk and dv come out of a single kernel
     gridded over k blocks. The q-block loop accumulates dk/dv in
     registers and dq into a grid-revisited f32 VMEM output block
     (index map ignores the k-grid axis; zeroed at k==0), so the scores,
     probabilities and dp are computed ONCE per (q, k) block pair instead
     of twice (the round-3 form ran separate dq and dk/dv kernels, each
     redoing s, exp and dp — 7 block matmuls and ~2x the VPU work per
     pair vs 5 matmuls here).
- **Dead tiles** (PR 31): with ONE block a row (every s <= 1024,
  :func:`_auto_block`) the diagonal block of (2) is the whole [s, s]
  square, so the one-tile body did twice the causal work: at 8 x 16 heads
  x 1024 x 64 the forward ran at 30% and the backward at 46% of the bf16
  peak on what they EXECUTED, 15% and 23% on what the mask keeps. Every
  product here has the 64 in its contraction or its output width and fills
  half of the 128 x 128 array (two heads stacked in one product stream the
  same rows through the same weight tiles: no lever), so about half the
  peak is the ceiling of the geometry and the square was most of the
  distance to it. Where the row is a whole number of strips both kernels
  now cut the triangle statically inside the one block (the section
  "Static causal strips" below: forward 590 -> 368 us, backward 958 -> 605
  us a call; the classic form at 2 x 25 heads 215 -> 134 and 358 -> 228).

Layout (round 4): the training hot path (plain causal, full-length,
head_dim 64/128) runs the HEAD-PACKED kernels — inputs stay [b, s, h*dh]
exactly as the projection matmul wrote them, each grid instance owns a
128-lane-aligned slab of 128//head_dim heads, and the body unrolls the
slab's heads with static lane slices. That removes the
[b,s,h,dh] -> [b*h,s,dh] relayouts around every kernel (~10% of a GPT-2
step) AND the fusion barrier they imposed: gpt2-small device step
126.2 -> 117.2 ms. (The r3 full-head-per-instance attempt was slower
because its per-head BlockSpecs made lane-MISALIGNED strided reads; the
aligned slab is a clean DMA.) Other shapes (windows, ragged tails,
bq != bk, odd head dims) fall back to the classic [b*h, s, dh] form
plus explicit transposes.

On non-TPU backends the kernels run in interpreter mode so CPU CI exercises
the same code paths.
"""

from __future__ import annotations

import functools
import logging
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

logger = logging.getLogger(__name__)

NEG_INF = -1e30
LOG2E = 1.4426950408889634


def _use_interpret() -> bool:
    """Interpret mode is for the CPU tests only: on a TPU the kernels are
    always compiled by Mosaic."""
    return jax.devices()[0].platform != "tpu"


def _block_index(axis: int, n_blocks):
    """This grid instance's block index along ``axis``. When ONE block spans
    the whole row the axis has a single program, so the index is the static
    0: Mosaic must prove every lane-dim slice offset a multiple of 128, and
    it cannot for ``program_id * s`` at a ragged ``s`` (s=1000 was refused
    by the v5e compiler, "cannot statically prove that index in dimension
    3 is a multiple of 128") — a static 0 needs no proof."""
    return 0 if n_blocks == 1 else pl.program_id(axis)


def _make_block_mask(qi_base, block_shape, causal: bool, true_len: int,
                     seq_len: int, window: Optional[int]):
    """Per-grid-instance score-mask factory (or None if nothing masks).
    See the module docstring's VPU discipline for why it is shaped this
    way."""
    if not causal and true_len == seq_len and window is None:
        return None
    rows = jax.lax.broadcasted_iota(jnp.int32, block_shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, block_shape, 1)
    rc = rows - cols  # = (abs_row - abs_col) - (qi_base - ki_base)

    def mask(s, ki_base):
        off = ki_base - qi_base
        keep = None
        if causal:
            keep = rc >= off  # abs_row >= abs_col
        if window is not None:
            w = rc < off + window  # abs_row - abs_col < window
            keep = w if keep is None else keep & w
        if true_len != seq_len:
            pad = cols < true_len - ki_base  # abs_col < true_len
            keep = pad if keep is None else keep & pad
        return jnp.where(keep, s, NEG_INF)

    return mask


def _causal_tile(n: int, keys_down: bool = False):
    """The additive mask of a diagonal [n, n] tile of scores [queries, keys]
    (``keys_down``: [keys, queries]): 0 where the key is at or before the
    query, NEG_INF elsewhere (one add an element, no compare + select)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.where(rows <= cols if keys_down else rows >= cols, 0.0,
                     NEG_INF)


# ---------------------------------------------------------------------------
# Static causal strips (PR 31). Where ONE block spans the row (every
# s <= 1024, see _auto_block) the "diagonal block" of the static split is the
# whole [s, s] square: the one-tile body forms every score, exponential and
# product of it, and the mask zeroes half. The whole row's q, k, v (and do)
# are resident in that program anyway, so the triangle is cut into s/t strips
# of t query rows by a Python loop: static slices, no fori_loop, no cond, no
# program_id in a bound, no online rescale, the grid unchanged. Only the
# [t, t] tiles on the diagonal are masked; above them nothing is formed.
#
# What the chip said of the ways to cut it (v5e, kernel microseconds a call,
# PERF.md section 6, PR 31): a product streams its left operand's rows through
# the weight tiles its right operand makes, and a strip that streams only t
# rows a tile pays for the tiles, not for the area (key strips of 256 rows in
# the backward: 921 us against the square's 958; query strips of 256 rows in
# an untransposed forward: 471 against 590). So both kernels form a strip's
# scores TRANSPOSED, [keys, queries]: ``k[0:r1] @ q.T`` streams the strip's
# keys, the long side; the softmax statistics, lse and delta are [1, t] lane
# rows, which is what they are stored as (no lane-to-sublane relayout), and
# the reductions run down the sublanes. The forward then takes
# ``o.T = v.T @ p.T``, whose weights ARE p.T (no transpose of the
# probabilities): 590 -> 368 us with two strips of 512 (432 untransposed
# strips, 433 transposed but uncut). The backward's ``p.T @ do`` and
# ``ds.T @ q`` are plain products, one transpose (``ds @ k``) is left of two:
# 958 -> 605 us with eight strips of 128, 36 of 64 tiles.
# ---------------------------------------------------------------------------

# Strip heights in the order they are taken, per kernel: the first that cuts
# the row into >= 2 whole strips. Multiples of 128, so that the lse / delta
# rows are cut at whole lane tiles. Measured at s = 1024, 512 and 256.
_STRIP_ROWS = {"fwd": (512, 256, 128), "bwd": (128,)}

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b

# What the strip bodies are told of each head of a block: its lane slice and
# the index of its lse / delta row, kept [1, s]. The classic blocks hold one
# head.
_ONE_HEAD = ((slice(None), (0, slice(0, 1))),)


def _slab_heads(hp: int, dh: int):
    """The ``hp`` heads of a packed 128-lane slab."""
    return [(slice(p * dh, (p + 1) * dh), (0, 0, slice(p, p + 1)))
            for p in range(hp)]


def causal_strips(s: int, t: int) -> tuple[int, int]:
    """``(live_tiles, square_tiles)``: of the ``(s/t)**2`` [t, t] tiles of
    the causal square, those at or below the diagonal — what the strip form
    computes against what the one-tile form does (10 of 16 at s/t = 4)."""
    n = s // t
    return n * (n + 1) // 2, n * n


def _strip_rows(s: int, block_q: int, block_k: int, causal: bool,
                window: Optional[int], kernel: str) -> Optional[int]:
    """Strip height of the ``kernel`` ("fwd" / "bwd") for a call, or None
    where it keeps the form it had: a window, a non-causal call, unequal
    blocks, blocks that do not span the row (every s > 1024: those skip dead
    blocks already), or a row that is no whole number >= 2 of strips (a
    ragged s). A function of what the call can see, nothing to tune."""
    if not (causal and window is None and block_q == block_k == s):
        return None
    return next((t for t in _STRIP_ROWS[kernel]
                 if s % t == 0 and s // t >= 2), None)


def _fwd_strips(q_ref, k_ref, v_ref, o_ref, lse_ref, heads, t: int,
                scale: float):
    """The one-block causal forward on strips of ``t`` query rows, scores
    TRANSPOSED: strip [r0, r1) forms ``s.T = k @ q.T`` against keys [0, r1)
    only — the diagonal [t, t] tile, masked, and the interior [r0, t],
    unmasked — takes ONE softmax pass down the trapezoid's columns (joint
    max, exp2, column sum: [1, t] rows, as lse is stored), and
    ``o.T = v.T @ p.T`` with p.T as the product's weights. ``heads``: per
    head its lane slice of the blocks and the index of its [1, s] lse row
    (the packed kernels pass a slab's heads). A head's q, k, v leave the
    blocks once, as in the one-tile form; the strips slice those values,
    which moves nothing."""
    s = q_ref.shape[1]
    diag_add = _causal_tile(t, keys_down=True)
    for lane, row in heads:
        q = q_ref[0, :, lane]
        q = (q.astype(jnp.float32) * (scale * LOG2E)).astype(q.dtype)
        k = k_ref[0, :, lane]
        v_t = v_ref[0, :, lane].T  # [dh, s]
        for r0 in range(0, s, t):
            r1 = r0 + t
            keys = [slice(r0, r1)] + ([slice(0, r0)] if r0 else [])
            scores = [jax.lax.dot_general(k[ks], q[r0:r1], _NT,
                                          preferred_element_type=jnp.float32)
                      for ks in keys]
            scores[0] = scores[0] + diag_add
            m = functools.reduce(jnp.maximum, [
                jnp.max(x, axis=0, keepdims=True) for x in scores])
            l = o_t = 0.0
            for ks, x in zip(keys, scores):
                p = jnp.exp2(x - m)
                l = l + jnp.sum(p, axis=0, keepdims=True)
                o_t = o_t + jax.lax.dot(v_t[:, ks], p.astype(v_t.dtype),
                                        preferred_element_type=jnp.float32)
            # l >= 1: every query's own key is live
            o_ref[0, r0:r1, lane] = (o_t / l).T.astype(o_ref.dtype)
            lse_ref[row + (slice(r0, r1),)] = m + jnp.log2(l)


def _bwd_strips(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                dk_ref, dv_ref, heads, t: int, scale: float):
    """The one-block causal backward on strips of ``t`` query rows, scores
    transposed as in :func:`_fwd_strips`: strip [r0, r1) forms ``s.T =
    k[0:r1] @ q.T`` and ``dp.T = v[0:r1] @ do.T`` as [r1, t] (its last
    [t, t] tile masked), lse and delta subtract as [1, t] rows, ``p.T @ do``
    and ``ds.T @ q`` are plain products whose row strips add into dv and dk
    of keys [0, r1), and the strip's dq = ``ds @ k`` comes out whole (the
    one transpose; nothing is zeroed, nothing accumulates in the block).
    ``heads`` as in :func:`_fwd_strips`."""
    s = q_ref.shape[1]
    n = s // t
    diag_add = _causal_tile(t, keys_down=True)
    for lane, row in heads:
        q = q_ref[0, :, lane]  # unscaled
        # exp2-domain fold, matching the forward's lse
        qc = (q.astype(jnp.float32) * (scale * LOG2E)).astype(q.dtype)
        do = do_ref[0, :, lane]
        k = k_ref[0, :, lane]
        v = v_ref[0, :, lane]
        dk = [0.0] * n
        dv = [0.0] * n
        for i in range(n):
            r0, r1 = i * t, (i + 1) * t
            cols = row + (slice(r0, r1),)
            x = jax.lax.dot_general(k[:r1], qc[r0:r1], _NT,
                                    preferred_element_type=jnp.float32)
            x = (jnp.concatenate([x[:r0], x[r0:] + diag_add]) if r0
                 else x + diag_add)
            p = jnp.exp2(x - lse_ref[cols])
            dp = jax.lax.dot_general(v[:r1], do[r0:r1], _NT,
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_ref[cols])).astype(q.dtype)
            dv_i = jax.lax.dot(p.astype(do.dtype), do[r0:r1],
                               preferred_element_type=jnp.float32)
            dk_i = jax.lax.dot(ds, q[r0:r1],
                               preferred_element_type=jnp.float32)
            for j in range(i + 1):
                dv[j] = dv[j] + dv_i[j * t:(j + 1) * t]
                dk[j] = dk[j] + dk_i[j * t:(j + 1) * t]
            # dq rides unscaled f32; the caller applies `scale`
            dq_ref[0, r0:r1, lane] = jax.lax.dot_general(
                ds, k[:r1], _TN, preferred_element_type=jnp.float32)
        for j in range(n):
            rows = slice(j * t, (j + 1) * t)
            # q was unscaled in the dk product, so the scale applies once here
            dk_ref[0, rows, lane] = (dk[j] * scale).astype(dk_ref.dtype)
            dv_ref[0, rows, lane] = dv[j].astype(dv_ref.dtype)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                      causal: bool, scale: float, seq_len: int,
                      true_len: int, window: Optional[int],
                      strip: Optional[int]):
    if strip is not None:
        _fwd_strips(q_ref, k_ref, v_ref, o_ref, lse_ref, _ONE_HEAD, strip,
                    scale)
        return
    qi = _block_index(1, seq_len // q_ref.shape[1])
    # exp2-domain scores: scale*log2e folds into the [block_q, dh] q tile
    # so the per-element softmax path has no multiplies (module docstring)
    q = (q_ref[0].astype(jnp.float32) * (scale * LOG2E)).astype(q_ref.dtype)
    block_q = q.shape[0]
    dv = v_ref.shape[2]  # the values' width: o's, and the accumulator's

    n_kv = pl.cdiv(seq_len, block_k)  # seq_len is padded to a block multiple
    if causal:
        # highest k block that the last query row of this block can see
        n_kv_live = jax.lax.min(n_kv, ((qi + 1) * block_q + block_k - 1) // block_k)
    else:
        n_kv_live = n_kv
    if window is not None:
        # lowest k block the FIRST query row of this block can still see:
        # its oldest visible key is qi*block_q - (window - 1)
        kv_start = jax.lax.max(0, (qi * block_q - (window - 1)) // block_k)
    else:
        kv_start = 0

    mask = _make_block_mask(qi * block_q, (block_q, block_k), causal,
                            true_len, seq_len, window)
    # A fully-dead row in a block is only a correctness hazard while its
    # running max is still NEG_INF (exp2(s - m) = exp2(0) = 1 instead of 0).
    # The first visited block always has a live element in every row —
    # causal's block 0 contains column 0; padding keeps column 0 live —
    # EXCEPT at a sliding window's left edge, where the top rows of the
    # q block may open strictly later than kv_start. Only that case pays
    # the dead-row guards.
    guard_dead_rows = window is not None
    # Static diagonal split (the plain causal/full training path,
    # bq == bk, no padding/window): interior blocks are fully live — NO
    # mask arithmetic at all — and the single diagonal block applies a
    # precomputed ADDITIVE tile (one add/elem instead of compare+select).
    diag_split = (causal and block_q == block_k and true_len == seq_len
                  and window is None)

    def make_body(msk):
        def body(ki, carry):
            m, l, acc = carry
            k = k_ref[0, pl.ds(ki * block_k, block_k), :]
            v = v_ref[0, pl.ds(ki * block_k, block_k), :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [bq, bk] log2-domain
            if msk is not None:
                s = msk(s, ki * block_k)
            m_blk = jnp.max(s, axis=1)
            m_new = jnp.maximum(m, m_blk)
            p = jnp.exp2(s - m_new[:, None])
            alpha = jnp.exp2(m - m_new)
            if guard_dead_rows:
                p = jnp.where(s <= NEG_INF / 2, 0.0, p)
                alpha = jnp.where(m <= NEG_INF / 2, 0.0, alpha)
            l_new = l * alpha + jnp.sum(p, axis=1)
            acc_new = acc * alpha[:, None] + jax.lax.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new
        return body

    carry0 = (jnp.full((block_q,), NEG_INF, jnp.float32),
              jnp.zeros((block_q,), jnp.float32),
              jnp.zeros((block_q, dv), jnp.float32))
    if diag_split:
        # diagonal tile: rc >= 0 is instance-invariant at bq == bk
        diag_add = _causal_tile(block_q)
        m, l, acc = jax.lax.fori_loop(0, qi, make_body(None), carry0)
        m, l, acc = make_body(lambda s, _: s + diag_add)(qi, (m, l, acc))
    else:
        m, l, acc = jax.lax.fori_loop(kv_start, n_kv_live, make_body(mask),
                                      carry0)
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l[:, None]).astype(o_ref.dtype)
    # per-row logsumexp of the (scaled, masked) scores, in LOG2 domain
    # (= log2 sum_j 2^{s2_j}; only the backward kernels consume it). Rides
    # as [bh, 1, s_pad] (rank-3) because Mosaic requires the last two block
    # dims to tile (8, 128) or equal the array dims
    lse_ref[0, 0] = m + jnp.log2(l)


def _pad_to_blocks(s: int, block_q: int, block_k: int) -> int:
    blk = math.lcm(block_q, block_k)
    return -(-s // blk) * blk


# The compiler gives a kernel 16 MiB of VMEM unless told otherwise.
_DEFAULT_SCOPED_VMEM_BYTES = 16 * 1024 * 1024


def _lanes(width: int) -> int:
    """What a row ``width`` wide takes in VMEM, whose minor dimension is
    tiled to 128 lanes: heads of 64 take what heads of 128 take, heads of
    192 take 256 (the compiler's own count for a described v5e: 8.29 MiB of
    forward operands at 8192 x 64 as at 8192 x 128, 12.41 at 192 | 128)."""
    return -(-width // 128) * 128


def _vmem(operands, body_bytes: int) -> dict:
    """``pallas_call`` keywords for the scoped VMEM of a classic kernel,
    forward or backward. ``operands``: ``(rows, width, itemsize)`` of every
    block the pipeline keeps there, double-buffered; the whole-row ones (k
    and v forward; q, do and the float32 dq accumulator backward) are what
    grows with the sequence: 16 MiB backward at 8192 rows of 128 in bf16 and
    28 at 192 | 128, 8 and 12 forward. ``body_bytes``: what the loop's body
    holds beside them, its [block_q, block_k] float32 tiles (forward:
    scores, probabilities in float32 and cast, the mask tile; backward: dp
    and ds too) and its float32 carries.

    Where the operands alone pass three quarters of the default limit the
    kernel asks for operands plus body and a quarter more (a v5e core has
    128 MiB). Everywhere else NOTHING is passed and the program is the one
    it was: every row of one block (at most 1024 rows: 2 MiB of operands,
    and a body without a loop, which streams its one tile: 4.2 and 5.8 MiB
    wanted in all at 1000 rows), the forward at 8192 x 64 and 8192 x 128
    (8.5 MiB), the windowed 4096 x 128 call. The quarter left under the
    default is what the bodies take at blocks of 512 (3.1-3.9 MiB). What the
    described v5e's compiler wanted in all, found by raising a too-small
    limit until it compiled, and what this asks, MiB (PR 37):

    =================  =============  =============  =============
    8192 rows          64             128            192 | 128
    =================  =============  =============  =============
    forward, 256       9.36, -        9.36, -        13.55, 16.9
    forward, 512       11.68, -       11.70, -       16.20, 21.2
    backward, 256      17.73, 22.5    17.73, 22.5    30.35, 38.0
    backward, 512      20.83, 28.1    20.65, 28.1    33.39, 44.1
    =================  =============  =============  =============

    Until PR 37 the forward asked for nothing (refused at 192 | 128 in
    blocks of 512: 16.20 of 16) and the backward for 1.5 x its whole rows
    with 192 counted as 192 lanes: 33.00 MiB, 0.39 short at 512 — a factor
    that fitted blocks of 256 and did not follow them."""
    held = 2 * sum(rows * _lanes(width) * size
                   for rows, width, size in operands)
    if held <= 0.75 * _DEFAULT_SCOPED_VMEM_BYTES:
        return {}
    from jax.experimental.pallas import tpu as pltpu
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=int(1.25 * (held + body_bytes)))}


def _flash_fwd(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
               block_q: int, block_k: int,
               window: Optional[int] = None):
    """q, k: [bh, s, dh], v: [bh, s, dv] -> (out [bh, s, dv], lse [bh, 1,
    s_pad]); ``dv`` is ``dh`` everywhere but under latent attention. Ragged s
    (not a block multiple) is zero-padded up front; padded key columns are
    masked dead in-kernel and padded query rows are sliced off the output
    (the lse stays padded — it only feeds the backward kernels, which slice
    consistently)."""
    bh, s, dh = q.shape
    dv, size = v.shape[-1], q.dtype.itemsize
    scale = 1.0 / (dh ** 0.5)
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    s_pad = _pad_to_blocks(s, block_q, block_k)
    if s_pad != s:
        pad = ((0, 0), (0, s_pad - s), (0, 0))
        q, k, v = (jnp.pad(x, pad) for x in (q, k, v))
    grid = (bh, s_pad // block_q)
    kernel = functools.partial(
        _flash_fwd_kernel, block_k=block_k, causal=causal, scale=scale,
        seq_len=s_pad, true_len=s, window=window,
        strip=_strip_rows(s, block_q, block_k, causal, window, "fwd"))
    out, lse = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(v.shape, q.dtype),
                   jax.ShapeDtypeStruct((bh, 1, s_pad), jnp.float32)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, s_pad, dh), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, s_pad, dv), lambda i, j: (i, 0, 0)),
        ],
        out_specs=(pl.BlockSpec((1, block_q, dv), lambda i, j: (i, j, 0)),
                   pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j))),
        interpret=_use_interpret(),
        # q and o blocks, a whole row's k and v; the body's scores,
        # probabilities (float32 and cast) and mask tile, and its accumulator
        **_vmem([(block_q, dh, size), (s_pad, dh, size), (s_pad, dv, size),
                 (block_q, dv, size)],
                4 * (4 * block_q * block_k + block_q * _lanes(dv))),
    )(q, k, v)
    return out[:, :s, :], lse


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, *, block_q: int, causal: bool,
                      scale: float, seq_len: int, true_len: int,
                      window: Optional[int], strip: Optional[int]):
    """One-sweep backward: grid (batch*heads, k blocks). Each instance owns
    one k block, loops over its live q blocks, accumulates dk/dv in f32
    carries, and accumulates dq into a grid-revisited f32 VMEM output block
    (its index map ignores the k-grid axis, so the block stays resident
    across the sweep; zeroed when the sweep starts). Scores, probabilities
    and dp are computed once per (q, k) block pair — the round-3 two-kernel
    form computed each twice."""
    if strip is not None:
        _bwd_strips(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                    dk_ref, dv_ref, _ONE_HEAD, strip, scale)
        return
    ki = _block_index(1, seq_len // k_ref.shape[1])
    k = k_ref[0]  # [block_k, dh], storage dtype
    v = v_ref[0]
    block_k = k.shape[0]
    dh = k.shape[1]
    c = scale * LOG2E  # exp2-domain fold, matching the forward's lse

    @pl.when(ki == 0)
    def _zero_dq():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    n_q = pl.cdiv(seq_len, block_q)
    if causal:
        # first q block whose last row can see this k block's first key
        q_start = (ki * block_k) // block_q
    else:
        q_start = 0
    if window is not None:
        # last q row that still sees this block's newest key is
        # ki*block_k + block_k - 1 + window - 1
        q_stop = jax.lax.min(
            n_q, (ki * block_k + block_k - 1 + window - 1) // block_q + 1)
    else:
        q_stop = n_q

    mask_needed = causal or true_len != seq_len or window is not None
    if mask_needed:
        # this kernel's grid walks ki (fixed per instance), so the
        # loop-invariant tile is rc_k = row_iota - abs_col; each edge is
        # then one scalar-broadcast compare against the varying qi offset
        shape = (block_q, block_k)
        col_abs = (ki * block_k
                   + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
        rc_k = jax.lax.broadcasted_iota(jnp.int32, shape, 0) - col_abs
        pad_cols = col_abs < true_len if true_len != seq_len else None

    def apply_mask(s, qi):
        keep = None
        if causal:
            keep = rc_k >= -qi * block_q  # abs_row >= abs_col
        if window is not None:
            w = rc_k < window - qi * block_q
            keep = w if keep is None else keep & w
        if pad_cols is not None:
            keep = pad_cols if keep is None else keep & pad_cols
        return jnp.where(keep, s, NEG_INF)

    def make_body(msk):
        def body(qi, carry):
            dk_acc, dv_acc = carry
            qs = q_ref[0, pl.ds(qi * block_q, block_q), :]  # unscaled
            qc = (qs.astype(jnp.float32) * c).astype(qs.dtype)
            do = do_ref[0, pl.ds(qi * block_q, block_q), :]
            lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q)]  # log2-domain
            delta = delta_ref[0, 0, pl.ds(qi * block_q, block_q)]
            s = jax.lax.dot_general(
                qc, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [bq, bk] log2-domain
            if msk is not None:
                s = msk(s, qi)
            # padded q rows carry do = 0, so their (finite-garbage) p rows
            # contribute exactly 0 everywhere; dead elements underflow to 0
            # (every live row's lse is finite — its diagonal is always live)
            p = jnp.exp2(s - lse[:, None])
            dv_new = dv_acc + jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [bq, bk] f32
            ds = p * (dp - delta[:, None])
            dsb = ds.astype(qs.dtype)
            dk_new = dk_acc + jax.lax.dot_general(
                dsb, qs, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            # dq rides unscaled f32; the caller applies `scale` (fused by
            # XLA into the cast/transpose that follows the kernel)
            dq_ref[0, pl.ds(qi * block_q, block_q), :] += jax.lax.dot(
                dsb, k, preferred_element_type=jnp.float32)
            return dk_new, dv_new
        return body

    dk0 = jnp.zeros((block_k, dh), jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    # Static diagonal split, mirroring the forward: with bq == bk on the
    # plain causal/full path this instance's FIRST live q block (qi == ki)
    # is the diagonal — an instance-invariant additive tile — and every
    # later q block is fully live with no mask arithmetic at all.
    diag_split = (causal and block_q == block_k and true_len == seq_len
                  and window is None)
    if diag_split:
        diag_add = jnp.where(rc_k >= -ki * block_q, 0.0, NEG_INF)
        carry = make_body(lambda s, _: s + diag_add)(q_start, (dk0, dv0))
        dk, dv = jax.lax.fori_loop(q_start + 1, q_stop, make_body(None),
                                   carry)
    else:
        dk, dv = jax.lax.fori_loop(
            q_start, q_stop,
            make_body(apply_mask if mask_needed else None), (dk0, dv0))
    # qs was unscaled in the dk dot, so the scale applies once here
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, g, causal, block_q, block_k, window):
    """Blockwise dq/dk/dv from saved (o, lse): the [s, s] matrix never
    materializes. Inputs unpadded, q and k [bh, s, dh], v, o and g [bh, s,
    dv]; lse [bh, 1, s_pad] (padded, log2-domain, from the forward). One
    fused kernel produces all three grads (see _flash_bwd_kernel)."""
    bh, s, dh = q.shape
    dv, size = v.shape[-1], q.dtype.itemsize
    scale = 1.0 / (dh ** 0.5)
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    s_pad = _pad_to_blocks(s, block_q, block_k)
    # delta_i = rowsum(do_i * o_i) in f32 — O(s*dh), the only non-kernel work
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]  # [bh, 1, s] (rank-3, see lse note)
    if s_pad != s:
        pad3 = ((0, 0), (0, s_pad - s), (0, 0))
        q, k, v, g = (jnp.pad(x, pad3) for x in (q, k, v, g))
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, s_pad - s)))
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_kernel, block_q=block_q, causal=causal, scale=scale,
            seq_len=s_pad, true_len=s, window=window,
            strip=_strip_rows(s, block_q, block_k, causal, window, "bwd")),
        out_shape=(jax.ShapeDtypeStruct(q.shape, jnp.float32),  # dq, f32
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        grid=(bh, s_pad // block_k),
        in_specs=[
            pl.BlockSpec((1, s_pad, dh), lambda i, j: (i, 0, 0)),     # q
            pl.BlockSpec((1, block_k, dh), lambda i, j: (i, j, 0)),   # k
            pl.BlockSpec((1, block_k, dv), lambda i, j: (i, j, 0)),   # v
            pl.BlockSpec((1, s_pad, dv), lambda i, j: (i, 0, 0)),     # do
            pl.BlockSpec((1, 1, s_pad), lambda i, j: (i, 0, 0)),      # lse
            pl.BlockSpec((1, 1, s_pad), lambda i, j: (i, 0, 0)),      # delta
        ],
        out_specs=(
            # dq: revisited across the k-grid axis (accumulator)
            pl.BlockSpec((1, s_pad, dh), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_k, dh), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda i, j: (i, j, 0)),
        ),
        interpret=_use_interpret(),
        # a whole row's q, do and float32 dq, the k, v, dk and dv blocks;
        # the body's five tiles (scores, probabilities, dp, ds, the mask)
        # and its dk and dv accumulators
        **_vmem([(s_pad, dh, size), (s_pad, dv, size), (s_pad, dh, 4)]
                + 2 * [(block_k, dh, size), (block_k, dv, size)],
                4 * (5 * block_q * block_k
                     + block_k * (_lanes(dh) + _lanes(dv)))),
    )(q, k, v, g, lse, delta)
    # the deferred `scale` fold (see kernel docstring); XLA fuses it into
    # the cast + transpose that follow
    dq = (dq * scale).astype(q.dtype)
    return dq[:, :s, :], dk[:, :s, :], dv[:, :s, :]


# ---------------------------------------------------------------------------
# Head-packed (transpose-free) kernels — round 4.
#
# The classic form above wants [b*h, s, dh] inputs, which costs explicit
# [b,s,h,dh] -> [b*h,s,dh] relayouts around every kernel (~10% of a GPT-2
# train step at 77% HBM; docs/profiles/). Here the heads STAY where the
# projection matmul wrote them: inputs are [b, s, h*dh] (a free reshape),
# each grid instance owns a 128-lane-ALIGNED slab of HP = 128//dh heads
# (the r3 full-head variant was slow because its per-head BlockSpecs were
# lane-misaligned strided reads; a 128-lane slab is a clean DMA), and the
# kernel unrolls the HP heads in its body with per-head lane slices.
# Plain-causal full-length path only (the training hot path); everything
# else falls back to the transpose form.
# ---------------------------------------------------------------------------


# The packed kernels keep whole [s, h*dh] head-slabs resident in VMEM per
# batch grid cell (q, k, v, o, do plus the f32 dq accumulator in the
# backward). Measured cliff on v5e (round 5, h*dh = 768 bf16): the
# backward compiles at s = 5120 (7.9 MB/slab) and fails at s = 6144
# (9.4 MB/slab), so cap the slab at 8 MB and fall back to the classic
# per-(batch, head) form — whose K/V residency is [s, dh], 1 MB at
# s = 8192 — beyond it. The fallback pays the head transpose relayouts
# (~10% at GPT-2 shapes) but compiles at any sequence length.
_PACKED_SLAB_LIMIT_BYTES = 8 * 1024 * 1024


def _packed_ok(s, h, dh, causal, window, block_q, block_k, itemsize=2):
    hp = 128 // dh if dh in (64, 128) else 0
    return (causal and window is None and hp > 0 and h % max(hp, 1) == 0
            and block_q == block_k and s % block_q == 0
            and s * h * dh * itemsize <= _PACKED_SLAB_LIMIT_BYTES
            # Mosaic lowering constraint on the packed-lse BlockSpec
            # (1, 1, hp, block_q): its last block dim must tile 128 lanes
            # or span the whole array dim
            and (block_q % 128 == 0 or block_q == s))


def _flash_fwd_kernel_packed(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                             block_k: int, dh: int, hp: int, scale: float,
                             seq_len: int, strip: Optional[int]):
    if strip is not None:
        _fwd_strips(q_ref, k_ref, v_ref, o_ref, lse_ref, _slab_heads(hp, dh),
                    strip, scale)
        return
    qi = _block_index(1, seq_len // q_ref.shape[1])
    q2 = q_ref[0]  # [block_q, hp*dh]
    block_q = q2.shape[0]
    c = scale * LOG2E
    diag_add = _causal_tile(block_q)

    for p in range(hp):
        sl = slice(p * dh, (p + 1) * dh)
        qh = (q2[:, sl].astype(jnp.float32) * c).astype(q2.dtype)

        def body(ki, carry, msk=None):
            m, l, acc = carry
            k = k_ref[0, pl.ds(ki * block_k, block_k), sl]
            v = v_ref[0, pl.ds(ki * block_k, block_k), sl]
            s = jax.lax.dot_general(
                qh, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if msk is not None:
                s = s + msk
            m_blk = jnp.max(s, axis=1)
            m_new = jnp.maximum(m, m_blk)
            pr = jnp.exp2(s - m_new[:, None])
            alpha = jnp.exp2(m - m_new)
            l_new = l * alpha + jnp.sum(pr, axis=1)
            acc_new = acc * alpha[:, None] + jax.lax.dot(
                pr.astype(v.dtype), v, preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new

        carry0 = (jnp.full((block_q,), NEG_INF, jnp.float32),
                  jnp.zeros((block_q,), jnp.float32),
                  jnp.zeros((block_q, dh), jnp.float32))
        m, l, acc = jax.lax.fori_loop(0, qi, body, carry0)
        m, l, acc = body(qi, (m, l, acc), msk=diag_add)
        l = jnp.maximum(l, 1e-30)
        o_ref[0, :, sl] = (acc / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0, p, :] = m + jnp.log2(l)


def _flash_bwd_kernel_packed(q_ref, k_ref, v_ref, do_ref, lse_ref,
                             delta_ref, dq_ref, dk_ref, dv_ref, *,
                             block_q: int, dh: int, hp: int, scale: float,
                             seq_len: int, strip: Optional[int]):
    if strip is not None:
        _bwd_strips(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                    dk_ref, dv_ref, _slab_heads(hp, dh), strip, scale)
        return
    block_k = k_ref.shape[1]
    ki = _block_index(1, seq_len // block_k)
    n_q = seq_len // block_q
    c = scale * LOG2E

    @pl.when(ki == 0)
    def _zero_dq():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    diag_add = _causal_tile(block_q)

    for p in range(hp):
        sl = slice(p * dh, (p + 1) * dh)
        k = k_ref[0, :, sl]
        v = v_ref[0, :, sl]

        def body(qi, carry, msk=None):
            dk_acc, dv_acc = carry
            qs = q_ref[0, pl.ds(qi * block_q, block_q), sl]
            qc = (qs.astype(jnp.float32) * c).astype(qs.dtype)
            do = do_ref[0, pl.ds(qi * block_q, block_q), sl]
            lse = lse_ref[0, 0, p, pl.ds(qi * block_q, block_q)]
            delta = delta_ref[0, 0, p, pl.ds(qi * block_q, block_q)]
            s = jax.lax.dot_general(
                qc, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if msk is not None:
                s = s + msk
            pr = jnp.exp2(s - lse[:, None])
            dv_new = dv_acc + jax.lax.dot_general(
                pr.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = pr * (dp - delta[:, None])
            dsb = ds.astype(qs.dtype)
            dk_new = dk_acc + jax.lax.dot_general(
                dsb, qs, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dq_ref[0, pl.ds(qi * block_q, block_q), sl] += jax.lax.dot(
                dsb, k, preferred_element_type=jnp.float32)
            return dk_new, dv_new

        carry0 = (jnp.zeros((block_k, dh), jnp.float32),
                  jnp.zeros((block_k, dh), jnp.float32))
        carry = body(ki, carry0, msk=diag_add)  # diagonal (q_start == ki)
        dk, dv = jax.lax.fori_loop(ki + 1, n_q, body, carry)
        dk_ref[0, :, sl] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[0, :, sl] = dv.astype(dv_ref.dtype)


def _flash_fwd_packed(q, k, v, h, block_q, block_k):
    """q, k, v: [b, s, h*dh] -> (out [b, s, h*dh], lse [b, nhp, HP, s])."""
    b, s, hd = q.shape
    dh = hd // h
    hp = 128 // dh
    nhp = h // hp
    scale = 1.0 / (dh ** 0.5)
    grid = (b * nhp, s // block_q)
    kernel = functools.partial(
        _flash_fwd_kernel_packed, block_k=block_k, dh=dh, hp=hp, scale=scale,
        seq_len=s, strip=_strip_rows(s, block_q, block_k, True, None, "fwd"))
    slab = hp * dh  # = 128 lanes

    out, lse = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, nhp, hp, s), jnp.float32)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, slab),
                         lambda i, j: (i // nhp, j, i % nhp)),
            pl.BlockSpec((1, s, slab), lambda i, j: (i // nhp, 0, i % nhp)),
            pl.BlockSpec((1, s, slab), lambda i, j: (i // nhp, 0, i % nhp)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, slab),
                         lambda i, j: (i // nhp, j, i % nhp)),
            pl.BlockSpec((1, 1, hp, block_q),
                         lambda i, j: (i // nhp, i % nhp, 0, j)),
        ),
        interpret=_use_interpret(),
    )(q, k, v)
    return out, lse


def _flash_bwd_packed(q, k, v, o, lse, g, h, block_q, block_k):
    b, s, hd = q.shape
    dh = hd // h
    hp = 128 // dh
    nhp = h // hp
    scale = 1.0 / (dh ** 0.5)
    slab = hp * dh
    # per-head delta = rowsum(do_h * o_h): [b, s, h] -> [b, nhp, hp, s]
    delta = jnp.sum((g.astype(jnp.float32) * o.astype(jnp.float32))
                    .reshape(b, s, h, dh), axis=-1)
    delta = delta.reshape(b, s, nhp, hp).transpose(0, 2, 3, 1)
    kernel = functools.partial(
        _flash_bwd_kernel_packed, block_q=block_q, dh=dh, hp=hp, scale=scale,
        seq_len=s, strip=_strip_rows(s, block_q, block_k, True, None, "bwd"))
    dq, dk, dv = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(q.shape, jnp.float32),  # dq f32
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        grid=(b * nhp, s // block_k),
        in_specs=[
            pl.BlockSpec((1, s, slab), lambda i, j: (i // nhp, 0, i % nhp)),
            pl.BlockSpec((1, block_k, slab),
                         lambda i, j: (i // nhp, j, i % nhp)),
            pl.BlockSpec((1, block_k, slab),
                         lambda i, j: (i // nhp, j, i % nhp)),
            pl.BlockSpec((1, s, slab), lambda i, j: (i // nhp, 0, i % nhp)),
            pl.BlockSpec((1, 1, hp, s), lambda i, j: (i // nhp, i % nhp,
                                                      0, 0)),
            pl.BlockSpec((1, 1, hp, s), lambda i, j: (i // nhp, i % nhp,
                                                      0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, s, slab), lambda i, j: (i // nhp, 0, i % nhp)),
            pl.BlockSpec((1, block_k, slab),
                         lambda i, j: (i // nhp, j, i % nhp)),
            pl.BlockSpec((1, block_k, slab),
                         lambda i, j: (i // nhp, j, i % nhp)),
        ),
        interpret=_use_interpret(),
    )(q, k, v, g, lse, delta)
    return (dq * scale).astype(q.dtype), dk, dv


#: what both forward rules call the two residuals that only the forward kernel
#: can give back: a ``jax.checkpoint`` policy that keeps these names
#: (:func:`..ops.layers.remat_layer`) spares a rematerialised layer the
#: kernel's second run. Outside such a policy the name lowers to nothing.
FLASH_OUT = "flash_out"
FLASH_LSE = "flash_lse"


def _named(out, lse):
    return checkpoint_name(out, FLASH_OUT), checkpoint_name(lse, FLASH_LSE)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_packed(q, k, v, h, block_q, block_k):
    out, _ = _flash_fwd_packed(q, k, v, h, block_q, block_k)
    return out


def _flash_packed_vjp_fwd(q, k, v, h, block_q, block_k):
    out, lse = _named(*_flash_fwd_packed(q, k, v, h, block_q, block_k))
    return out, (q, k, v, out, lse)


def _flash_packed_vjp_bwd(h, block_q, block_k, res, g):
    q, k, v, o, lse = res
    return _flash_bwd_packed(q, k, v, o, lse, g, h, block_q, block_k)


_flash_packed.defvjp(_flash_packed_vjp_fwd, _flash_packed_vjp_bwd)


def _dense_attention(q, k, v, causal, window=None):
    """Reference path in plain XLA (f32 accumulation) for tests/benchmarks."""
    dh = q.shape[-1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (dh ** 0.5)
    if causal:
        from .attention import band_mask
        s = jnp.where(band_mask(s.shape[-2], s.shape[-1], window)[None],
                      s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block_q, block_k, window):
    out, _ = _flash_fwd(q, k, v, causal, block_q, block_k, window)
    return out


def _flash_vjp_fwd(q, k, v, causal, block_q, block_k, window):
    out, lse = _named(*_flash_fwd(q, k, v, causal, block_q, block_k, window))
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, block_q, block_k, window, res, g):
    q, k, v, o, lse = res
    return _flash_bwd(q, k, v, o, lse, g, causal, block_q, block_k, window)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _auto_block(s: int) -> int:
    """Default kernel block, from the row length alone (v5e measurements,
    docs/performance.md).

    s <= 1024: ONE block covers the whole row — one grid step a row, no
    interior k loop with a `program_id` trip count (round 4 measured that
    loop SLOWER at blocks of 512, fwd 0.94 against 0.83 ms at gpt2-small
    shapes, although it skips a tile in four: a dynamic bound costs Mosaic
    its software pipelining), and `min(block, s)` keeps short rows
    unpadded. Under a causal mask the one [s, s] tile costs the whole
    square for half of it (8 x 16 heads x 1024: forward 590 us, backward
    958 us a call; chip, PR 31); where the row is a whole number of
    strips the kernels therefore cut the triangle STATICALLY inside the
    one block (:func:`_strip_rows`: forward 368 us, backward 605 us),
    and a ragged row keeps the square.

    Beyond 1024 rows: 512, at every length. Blocks of 1024 x 1024 want more
    VMEM than the kernels ask for (20.5 MiB forward at 8192 x 64) and 512
    measured up to ~20% (fwd) / ~34% (grad) faster per row than 256 at
    2048-4096 rows; estimated time ~ padded_length / per-row-speed, so 256
    wins only where its padding saving exceeds 512's ~1.2x per-row
    advantage (s=1280: 1280 vs 1536/1.2 -> 256; s=2600: -> 512). At 8192
    rows the advantage is 2x (chip, PR 37: kernels alone, 2 x 8192 x 32
    heads, bf16, device us a call forward / backward; the relative error of
    out, dq, dk, dv against float32 attention is the same to three digits
    in every column):

    ===========  ===============  ===============  ===============
    heads        256              512              1024 x 512
    ===========  ===============  ===============  ===============
    64           18 574 / 37 939  9 862 / 19 763   11 649 / 18 153
    128          18 532 / 38 187  9 822 / 19 765   11 758 / 18 079
    192 | 128    21 450 / 38 644  12 611 / 29 358  14 269 / 28 368
    ===========  ===============  ===============  ===============

    The kernels are bound by per-block and per-score vector work (heads of
    64 take what heads of 128 take) and a row in blocks of 512 has a quarter
    as many block pairs. Every width gains, so the rule does not read the
    width; 1024 x 512 (unequal blocks: the masked body, no static diagonal)
    wins 3-9% backward and loses 13-20% forward, 0.6-1.6% slower in sum.

    Until PR 37 256 was FORCED where the padded row reached 8192, for two
    walls: composed train steps (the backward call beside the weight-grad
    dots) had crashed the v5e compiler at 512 x 8192 in round 5, and PR 34
    saw 512 refused there at 192 | 128 for VMEM. Neither stands: the
    second was two missing requests (:func:`_vmem`), and with them the
    compiler takes the three composed 8k steps of the benchmark at 512
    (12.503, 14.654 and 13.121 GB counted, as at 256;
    ``tests/test_chip_compile.py -m slow``) and the chip runs them."""
    if s <= 1024:
        return 1024
    if -(-s // 256) * 256 * 1.2 <= -(-s // 512) * 512:
        return 256
    return 512


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    window: Optional[int] = None) -> jax.Array:
    """Fused attention: q, k, v [batch, seq, heads, head_dim] -> same shape.
    ``v`` may have a head width of its own (latent attention multiplies
    scores over 192 and values over 128): the output has ``v``'s, the
    softmax scale is the queries' ``1/sqrt(head_dim)``, and the classic
    kernels run the two widths as they are — no zero columns in HBM or in
    a product. Equal widths compile the programs they compiled before.

    Drop-in replacement for the dense attention inside
    ``ops.attention.mha_apply`` (GQA repeat must happen before the call);
    differentiable with a fully-blockwise Pallas backward (see module
    docstring). EXPLICIT blocks below 128 lower on real TPUs only when
    the block spans the whole (padded) sequence — the rank-3 lse
    BlockSpec's last dim must tile 128 lanes or equal the array dim
    (Mosaic constraint; :func:`_auto_block`'s 256/512/1024 are always
    safe, and CPU interpret mode takes any block, which is what the
    small-block unit tests use). ``block_q``/``block_k`` default to :func:`_auto_block`
    (the whole row up to 1024; beyond it 512, at 8192 rows too since PR 37,
    or 256 where it avoids a dead padding block); both kernels keep their
    [block_q, block_k] f32 tiles plus the full per-(batch, head) K/V (the
    backward: q, do and a float32 dq) in VMEM and ask for what that takes
    where it passes the compiler's default (:func:`_vmem`), so block size
    trades per-block overhead against grid parallelism. Where one block spans a plain
    causal row of a whole number of strips (every ``s`` <= 1024 that is a
    multiple of 128, from 256), both kernels leave the dead half of the
    square unformed (:func:`_strip_rows`, :func:`causal_strips`): nothing
    to pass, and one ``logging.INFO`` line a traced call says which form
    runs, at what strip heights, on how many tiles of the square.
    ``window`` (requires ``causal``) applies the Mistral sliding-window
    band: both directions skip K/V (resp. Q) blocks entirely outside
    ``[i - window + 1, i]``, so long-sequence *compute* scales with the
    window. K/V VMEM residency still scales with the sequence (the
    whole [s, dh] K/V maps in per (batch, head)); truly long sequences
    should shard over a 'seq' mesh axis instead (ring attention).
    """
    if window is not None and (not causal or window < 1):
        raise ValueError("window requires causal attention and window >= 1")
    b, s, h, dh = q.shape
    dv = v.shape[-1]
    # AUTO blocks clamp to the sequence so short full-length rows
    # (s <= 1024, where _auto_block returns 1024) still satisfy
    # _packed_ok's s % block_q == 0 and take the transpose-free packed
    # path (block_q == s is an admissible packed-lse config under the
    # Mosaic lane constraint). EXPLICIT blocks are taken literally: a
    # caller-tuned block larger than the sequence is a config error, and
    # silently clamping it made "why is my tuned block ignored?"
    # undiagnosable (ADVICE r5) — raise instead.
    for name, blk in (("block_q", block_q), ("block_k", block_k)):
        if blk is not None and blk > s:
            raise ValueError(
                f"explicit {name}={blk} exceeds the sequence length {s}; "
                f"pass {name}=None to let _auto_block pick (auto blocks "
                f"clamp to the sequence)")
    block_q = block_q or min(_auto_block(s), s)
    block_k = block_k or min(_auto_block(s), s)
    packed = dv == dh and _packed_ok(s, h, dh, causal, window, block_q,
                                     block_k, q.dtype.itemsize)
    strips = {kernel: _strip_rows(s, block_q, block_k, causal, window, kernel)
              for kernel in ("fwd", "bwd")}
    logger.info(
        "flash_attention: %s kernels, %d x %d x %d x %s, blocks %d x %d, %s",
        "packed" if packed else "classic", b, s, h,
        dh if dv == dh else "%d (values %d)" % (dh, dv), block_q, block_k,
        "; ".join("%s strips of %d rows, %d of %d tiles of the causal square"
                  % (kernel, t, *causal_strips(s, t))
                  for kernel, t in strips.items() if t) or "no strips")
    if packed:
        # transpose-free path: heads stay packed in the lane dimension
        # (see _flash_packed) — the [b,s,h,dh]->[b*h,s,dh] relayouts this
        # call otherwise pays were ~10% of a GPT-2 train step
        def pack(x):
            return x.reshape(b, s, h * dh)

        out = _flash_packed(pack(q), pack(k), pack(v), h, block_q, block_k)
        return out.reshape(b, s, h, dh)

    def flat(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, x.shape[-1])

    out = _flash(flat(q), flat(k), flat(v), causal, block_q, block_k, window)
    return out.reshape(b, h, s, dv).transpose(0, 2, 1, 3)
