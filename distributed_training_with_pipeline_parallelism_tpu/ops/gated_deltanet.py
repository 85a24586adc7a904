"""The Gated DeltaNet mixer (Yang, Kautz, Hatamizadeh, "Gated Delta
Networks", arXiv:2412.06464) as a pure function, as the ``qwen3_next``
family's ``config.json`` declares it (``linear_num_key_heads`` Hk,
``linear_num_value_heads`` Hv, ``linear_key_head_dim`` dk,
``linear_value_head_dim`` dv, ``linear_conv_kernel_dim``)::

    [q | k | v | z] = u W_qkvz,  [b | a] = u W_ba     d -> 2 Hk dk + 2 Hv dv; 2 Hv
    [q | k | v] <- silu(causal_depthwise_conv1d([q | k | v]))    no bias
    q <- l2norm(q) / sqrt(dk),  k <- l2norm(k)       per head; key head h
                                                      serves value heads
                                                      h Hv/Hk .. (h+1) Hv/Hk - 1
    beta = sigmoid(b),  g = -exp(A_log) softplus(a + dt_bias)      per value head
    S_t = e^{g_t} S_{t-1} + beta_t k_t (v_t - e^{g_t} S_{t-1}^T k_t)^T    [dk, dv]
    o_t = S_t^T q_t
    out = (RMSNorm(o) * silu(z)) W_out            norm over a head's dv columns

The norm comes BEFORE the gate (Mamba-2's ``gated_group_rms_norm`` gates
first). The program's layout of ``in_proj_qkvz`` is the four blocks one after
the other, ``in_proj_ba`` the two (the source groups them by key head;
``benchmark/configs/qwen3-next-80b-a3b.json`` gives the map).

:func:`chunked_delta_rule` computes the recurrence in the chunked WY form
(chunks of :data:`CHUNK`) as a pair of Pallas TPU kernels under a custom VJP
whose backward is written by hand (autodiff's no longer). Both sweep a grid
(row, step of :data:`HEADS_PER_STEP` value heads, chunk) with the chunk axis
sequential; a step stacks its two heads' chunks in the 128 rows of one tile,
so every [n, n] product serves both (zero between heads), and keeps each
head's [dk, dv] float32 state in VMEM scratch across the chunks:

- forward (``_gdn_fwd_kernel``): per chunk ``gamma = cumsum(g)``, the
  masked ``A``, its ``T = (I + A)^{-1}`` by forward substitution by halves,
  the updates, ``o`` and the next state; the call the backward needs also
  writes every chunk's entering state and its ``T``;
- backward (``_gdn_bwd_kernel``): the chunks in reverse, each chunk's
  forward again from its saved entering state and ``T`` up to the updates
  (no inverse, no ``o``, no leaving state: only what the gradients read),
  then ``dq, dk, dv, dg, dbeta`` (``dg`` takes each exponent's gradient by
  the ``g`` it sums, not as a difference of cumulative sums) and the
  entering state's gradient, carried in VMEM scratch.

The backward saves the inputs, the entering states (b x H x T / 64 x dk x
dv float32, 537 MB a layer at 2 x 8192 x 32 heads x 128 x 128) and every
grid step's ``T`` (b x H / 2 x T / 64 x 128 x 128 float32, 268 MB there),
alive only inside that layer's backward (``remat_layers`` recomputes the
layer, so the recompute builds the inverses the backward reads). ``T``
depends on a chunk's ``k``, ``g`` and ``beta`` alone, not on the state
carried between chunks. A key head is read once for the value heads of a
step it serves. Everything is float32 with its products at
``Precision.HIGHEST`` (a TPU otherwise rounds float32 operands to bf16): the
state is carried whole and read whole.

Two profiler regions (``utils/profiling.py:HYBRID_REGIONS``):
``model/gdn_scan`` is :func:`chunked_delta_rule`, both kernels in every
phase; the rest of the mixer is the caller's ``model/gdn``.
"""

from __future__ import annotations

import logging
import math
import types
from typing import Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .layers import linear_apply, linear_init, named_product, rms_norm_init
from .mamba2 import causal_conv1d
from .pallas_attention import _use_interpret

logger = logging.getLogger(__name__)

#: positions a chunk of the chunked form
CHUNK = 64
#: value heads a grid step of the kernels takes, their chunks stacked in
#: the rows of one tile (TPU v5e, 2 x 8192 x 32 heads of 128: the forward
#: 23.7 ms a call at one, 17.6 at two, 32.8 at four, whose 256-row inverse
#: costs four times one of 128 rows)
HEADS_PER_STEP = 2
_HI = jax.lax.Precision.HIGHEST


def gdn_init(key: jax.Array, dim: int, n_k_heads: int, n_v_heads: int,
             dk: int, dv: int, conv_kernel: int) -> Dict:
    """The source's initialisers where they are its own: ``A_log = log U[0,
    16]``, ``dt_bias = 1``, the gated norm's scale 1, the convolution as
    ``torch.nn.Conv1d`` (fan-in ``conv_kernel``); the three projections as
    this repo's linears."""
    k_qkvz, k_ba, k_conv, k_a, k_out = jax.random.split(key, 5)
    key_dim, value_dim = n_k_heads * dk, n_v_heads * dv
    bound = 1.0 / math.sqrt(conv_kernel)
    return {
        "in_proj_qkvz": linear_init(k_qkvz, dim, 2 * key_dim + 2 * value_dim,
                                    bias=False),
        "in_proj_ba": linear_init(k_ba, dim, 2 * n_v_heads, bias=False),
        "conv": {"w": jax.random.uniform(
            k_conv, (conv_kernel, 2 * key_dim + value_dim), minval=-bound,
            maxval=bound)},
        "dt_bias": jnp.ones((n_v_heads,)),
        "A_log": jnp.log(jax.random.uniform(k_a, (n_v_heads,), minval=0.0,
                                            maxval=16.0)),
        "gate_norm": rms_norm_init(dv),
        "out_proj": linear_init(k_out, value_dim, dim, bias=False),
    }


def l2norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """``x / sqrt(sum x^2 + eps)`` over the last axis (the source's kernel
    option ``use_qk_l2norm_in_kernel``)."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _mm(a: jax.Array, b: jax.Array, contract) -> jax.Array:
    """``a`` times ``b`` over the axes ``contract`` names: float32 at
    ``HIGHEST`` (Mosaic's fp32 contraction; the default rounds to bf16)."""
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _ab(a, b):    # a b
    return _mm(a, b, ((1,), (0,)))


def _abt(a, b):   # a b^T
    return _mm(a, b, ((1,), (1,)))


def _atb(a, b):   # a^T b
    return _mm(a, b, ((0,), (0,)))


def _total(x: jax.Array) -> jax.Array:
    """The sum of a 2-D tile as a [1, 1] tile."""
    return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _col(row: jax.Array, eye: jax.Array) -> jax.Array:
    """[1, n] -> [n, 1] through the diagonal (no transpose in the kernel)."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(col: jax.Array, eye: jax.Array) -> jax.Array:
    """[n, 1] -> [1, n] through the diagonal."""
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _unit_lower_inverse(a: jax.Array, i: jax.Array,
                        j: jax.Array) -> jax.Array:
    """``(I + A)^{-1}`` for the strictly lower ``a`` [n, n] whose nonzeros
    lie in diagonal blocks of :data:`CHUNK` (``i``, ``j`` its row and column
    indices): forward substitution by halves. The inverse of a unit lower
    triangular ``[[L11, 0], [L21, L22]]`` is ``[[T11, 0], [-T22 L21 T11,
    T22]]`` with ``T11``, ``T22`` the halves' own; with ``inv`` the block
    diagonal of the inverses of the ``m``-blocks, ``inv A_low inv`` is ``T22
    L21 T11`` in every lower-left quarter of a ``2m``-block (``A_low``: ``a``
    there, zero elsewhere), so ``log2 CHUNK`` levels of two [n, n] products
    build the inverse from blocks of one row up (the first level's products
    are by ``I``)."""
    m = 1
    inv = jnp.where(i == j, 1.0, 0.0)
    while m < CHUNK:
        low = jnp.where(((i ^ j) < 2 * m) & ((i & m) != 0) & ((j & m) == 0),
                        a, 0.0)
        inv = inv - (low if m == 1 else _ab(_ab(inv, low), inv))
        m *= 2
    return inv


def _rows(x: jax.Array, h: int) -> jax.Array:
    """Head ``h``'s rows of a tile that stacks the heads' chunks."""
    return x[h * CHUNK:(h + 1) * CHUNK]


def _stacked(xs) -> jax.Array:
    return jnp.concatenate(xs, axis=0)


def _chunk(q, k, v, g, beta, states, t=None) -> types.SimpleNamespace:
    """One chunk of ``len(states)`` value heads at once, each head's
    :data:`CHUNK` rows after the previous head's (two heads fill the MXU's
    128 rows: one product where each head would take one of half the
    depth): ``q``, ``k`` [n, dk], ``v`` [n, dv] float32 tiles, ``g``,
    ``beta`` [1, n] rows, the heads' entering states [dk, dv] and, where the
    caller has it, ``t`` = ``(I + A)^{-1}`` [n, n] (built here otherwise).
    Every [n, n] quantity is zero between heads (``same``), so the products
    over the stack are each head's own. Returns every quantity the backward
    reads again, up to the updates ``upd``; :func:`_leaving` makes ``o`` and
    the leaving states from them. Exponents are masked BEFORE ``exp``
    (``gamma_i - gamma_j`` for ``i < j`` is positive and overflows)."""
    n = k.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    same = (i ^ j) < CHUNK                               # one head's rows
    eye, below, strict = i == j, same & (j <= i), same & (j < i)
    gamma = jnp.sum(jnp.where(below, g, 0.0), axis=1, keepdims=True)  # [n,1]
    beta = _col(beta, eye)
    grow = _row(gamma, eye)
    decay = jnp.where(below, jnp.exp(jnp.where(below, gamma - grow, 0.0)),
                      0.0)
    kk = _abt(k, k)
    if t is None:
        t = _unit_lower_inverse(jnp.where(strict, beta * decay * kk, 0.0), i,
                                j)
    eg = jnp.exp(gamma)
    vb, kb = beta * v, beta * eg * k
    w, u = _ab(t, kb), _ab(t, vb)
    # each row's head's total log-decay: gamma at the head's last row
    total = jnp.sum(jnp.where(same & ((j & (CHUNK - 1)) == CHUNK - 1), grow,
                              0.0), axis=1, keepdims=True)
    tail = jnp.exp(total - gamma)                         # e^{total - gamma}
    ke = tail * k
    qk = _abt(q, k)
    p, qg = decay * qk, eg * q
    upd = _stacked([_rows(u, h) - _ab(_rows(w, h), states[h])
                    for h in range(len(states))])         # U = u - w S0
    return types.SimpleNamespace(
        same=same, eye=eye, below=below, strict=strict, upper=i < j,
        beta=beta, decay=decay, kk=kk, t=t, eg=eg, vb=vb, kb=kb, w=w,
        upd=upd, qk=qk, p=p, qg=qg, total=total, tail=tail, ke=ke)


def _leaving(x: types.SimpleNamespace, states):
    """``o`` = ``qg S0 + p U`` and each head's leaving state ``e^{total} S0
    + ke^T U`` from :func:`_chunk`'s ``x`` and the entering ``states``."""
    heads = range(len(states))
    o = _stacked([_ab(_rows(x.qg, h), states[h]) for h in heads]) + _ab(
        x.p, x.upd)
    return o, [jnp.exp(_rows(x.total, h)[:1]) * states[h]
               + _atb(_rows(x.ke, h), _rows(x.upd, h)) for h in heads]


def _chunk_grads(q, k, v, g, beta, states, do, dsn, t):
    """:func:`_leaving`'s gradients through :func:`_chunk`: ``dq``, ``dk``,
    ``dv`` tiles, ``dg``, ``dbeta`` rows and each head's entering state's
    ``ds``, from the chunk's inputs, the entering states, ``do``, the
    leaving states' ``dsn`` and the chunk's ``T`` the forward built."""
    x = _chunk(q, k, v, g, beta, states, t)
    heads = range(len(states))
    # S' = e^{total} S + ke^T U,  o = qg S + p U (each head's S)
    et = [jnp.exp(_rows(x.total, h)[:1]) for h in heads]
    dtotal = _stacked([jnp.broadcast_to(et[h] * _total(states[h] * dsn[h]),
                                        (CHUNK, 1)) for h in heads])
    dke = _stacked([_abt(_rows(x.upd, h), dsn[h]) for h in heads])
    dqg = _stacked([_abt(_rows(do, h), states[h]) for h in heads])
    dupd = _stacked([_ab(_rows(x.ke, h), dsn[h]) for h in heads]) + _atb(
        x.p, do)
    ds = [et[h] * dsn[h] + _atb(_rows(x.qg, h), _rows(do, h)) for h in heads]
    # p = decay * q k^T,  qg = e^gamma q
    dp = _abt(do, x.upd)
    deg = jnp.sum(q * dqg, axis=1, keepdims=True)
    dqk = x.decay * dp
    ddecay = x.qk * dp
    dq = x.eg * dqg + _ab(dqk, k)
    dk = _atb(dqk, q)
    # U = T vb - w S,  w = T kb
    dw = _stacked([-_abt(_rows(dupd, h), states[h]) for h in heads])
    ds = [ds[h] - _atb(_rows(x.w, h), _rows(dupd, h)) for h in heads]
    dt = _abt(dupd, x.vb) + _abt(dw, x.kb)
    dvb, dkb = _atb(x.t, dupd), _atb(x.t, dw)
    dbeg = jnp.sum(k * dkb, axis=1, keepdims=True)       # d(beta e^gamma)
    dk = dk + x.beta * x.eg * dkb
    dbeta = jnp.sum(v * dvb, axis=1, keepdims=True) + x.eg * dbeg
    deg = deg + x.beta * dbeg
    # T = (I + A)^{-1}: dA = -T^T dT T^T on A's strictly lower support
    da = jnp.where(x.strict, -_abt(_atb(x.t, dt), x.t), 0.0)
    dkk = da * x.beta * x.decay
    ddecay = ddecay + da * x.beta * x.kk
    dbeta = dbeta + jnp.sum(da * x.decay * x.kk, axis=1, keepdims=True)
    dk = dk + _ab(dkk, k) + _atb(dkk, k) + x.tail * dke
    # g_t's gradient, each exponent by the g it sums (no difference of two
    # sums: with a strong decay they cancel to far less than they are):
    # e^{gamma_i} by t <= i; e^{total - gamma_i} (ke) by t > i; e^{total}
    # by every t; e^{gamma_i - gamma_j}, j < i, by j < t <= i (all in the
    # head of t)
    dtail = x.tail * jnp.sum(k * dke, axis=1, keepdims=True)
    dlag = jnp.where(x.strict, ddecay * x.decay, 0.0)
    lag = _ab(dlag, jnp.where(x.upper, 1.0, 0.0))         # sum over j < t
    dg = _row(dtotal, x.eye) + jnp.sum(
        jnp.where(x.below, lag + deg * x.eg, jnp.where(x.same, dtail, 0.0)),
        axis=0, keepdims=True)
    return dq, dk, x.beta * dvb, dg, _row(dbeta, x.eye), ds


def _stack(ref, heads: int) -> jax.Array:
    """A [1, CHUNK, heads * d] block as [heads * CHUNK, d] float32: each
    head's columns (static slices of whole 128-lane tiles on a TPU) below
    the previous head's."""
    d = ref.shape[-1] // heads
    return _stacked([ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)
                     for h in range(heads)])


def _unstack(ref, x: jax.Array, heads: int) -> None:
    d = ref.shape[-1] // heads
    for h in range(heads):
        ref[0, :, h * d:(h + 1) * d] = _rows(x, h).astype(ref.dtype)


def _tiles(c, heads, dk, q_ref, k_ref, v_ref, g_ref, beta_ref):
    """Chunk ``c`` of the step's heads: stacked tiles and rows. Where the
    step's heads share one key head, its ``q``, ``k`` tile serves each."""
    keys = q_ref.shape[-1] // dk
    return (*(_stacked([_stack(ref, keys)] * (heads // keys))
              for ref in (q_ref, k_ref)), _stack(v_ref, heads),
            g_ref[0, 0, pl.ds(c, 1), :], beta_ref[0, 0, pl.ds(c, 1), :])


def _gdn_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest):
    """One chunk of the step's value heads in one row, chunks in order:
    ``o`` and, where asked, the entering states and the chunk's ``T``; the
    states stay in the float32 scratch."""
    *kept_refs, state_ref = rest
    c, heads = pl.program_id(2), state_ref.shape[0]

    @pl.when(c == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    states = [state_ref[h] for h in range(heads)]
    x = _chunk(*_tiles(c, heads, state_ref.shape[1], q_ref, k_ref, v_ref,
                       g_ref, beta_ref), states)
    o, leaving = _leaving(x, states)
    _unstack(o_ref, o, heads)
    if kept_refs:
        entering_ref, t_ref = kept_refs
        t_ref[0, 0, 0] = x.t
        for h in range(heads):
            entering_ref[0, h, 0] = states[h]
    for h in range(heads):
        state_ref[h] = leaving[h]


def _gdn_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s_ref, do_ref,
                    t_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, ds_ref):
    """One chunk of the step's value heads in one row, chunks in reverse:
    the chunk's forward again from the saved entering states and ``T`` up
    to the updates, then its gradients; the gradients of the states leaving
    the chunk stay in the float32 scratch and become the entering
    states'."""
    step, heads = pl.program_id(2), ds_ref.shape[0]
    c = pl.num_programs(2) - 1 - step

    @pl.when(step == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    dk_width = ds_ref.shape[1]
    dq, dk, dv, dg, dbeta, ds = _chunk_grads(
        *_tiles(c, heads, dk_width, q_ref, k_ref, v_ref, g_ref, beta_ref),
        [s_ref[0, h, 0] for h in range(heads)], _stack(do_ref, heads),
        [ds_ref[h] for h in range(heads)], t_ref[0, 0, 0])
    keys = dq_ref.shape[-1] // dk_width
    for ref, x in ((dq_ref, dq), (dk_ref, dk)):
        if keys < heads:   # one key head: the sum over the heads it serves
            x = sum(_rows(x, h) for h in range(heads))
        _unstack(ref, x, keys)
    _unstack(dv_ref, dv, heads)
    dg_ref[0, 0, pl.ds(c, 1), :] = dg
    dbeta_ref[0, 0, pl.ds(c, 1), :] = dbeta
    for h in range(heads):
        ds_ref[h] = ds[h]


def _specs(b: int, T: int, heads: int, keys: int, dk: int, dv: int,
           reverse: bool):
    """Block specs over the grid (row, step of ``heads`` value heads,
    chunk), chunks in order or in reverse: ``v``/``o`` tiles [CHUNK, heads
    * dv] of ``[b, T, H * dv]`` and ``q``/``k`` tiles [CHUNK, keys * dk] of
    ``[b, T, Hk * dk]`` (``keys``: the step's key heads, one per value head
    or one for them all; the heads' columns are their block: no relayout),
    ``g``/``beta`` rows of ``[b, H / heads, nc, heads * CHUNK]`` (the step's
    whole sequence resident: one row a chunk), the entering states
    ``[b, H, nc, dk, dv]`` and each step's ``T`` tile of ``[b, H / heads,
    nc, heads * CHUNK, heads * CHUNK]``."""
    nc = T // CHUNK

    def chunk(c):
        return nc - 1 - c if reverse else c

    def tile(width):
        return pl.BlockSpec((1, CHUNK, width),
                            lambda r, h, c: (r, chunk(c), h))

    return dict(
        k=tile(keys * dk), v=tile(heads * dv),
        row=pl.BlockSpec((1, 1, nc, heads * CHUNK),
                         lambda r, h, c: (r, h, 0, 0)),
        state=pl.BlockSpec((1, heads, 1, dk, dv),
                           lambda r, h, c: (r, h, chunk(c), 0, 0)),
        t=pl.BlockSpec((1, 1, 1, heads * CHUNK, heads * CHUNK),
                       lambda r, h, c: (r, h, chunk(c), 0, 0)))


def _heads(H: int) -> int:
    """Value heads a grid step takes: :data:`HEADS_PER_STEP` or the most of
    it that divides ``H``."""
    return math.gcd(H, HEADS_PER_STEP)


def _keys(q: jax.Array, v: jax.Array) -> int:
    """Key heads a grid step reads: one a value head, or one for all of the
    step's value heads where each key head serves that many."""
    return _heads(v.shape[2]) if q.shape[2] == v.shape[2] else 1


def _call(kernel, q, v, in_specs, out_specs, out_shape, aliases=None):
    """The ``pallas_call`` both kernels share: the grid (row, step of value
    heads, chunk), the chunk axis sequential, a [dk, dv] float32 scratch a
    head carried along it."""
    from jax.experimental.pallas import tpu as pltpu
    b, T, _, dk = q.shape
    H = v.shape[2]
    heads = _heads(H)
    return pl.pallas_call(
        kernel, grid=(b, H // heads, T // CHUNK), in_specs=in_specs,
        out_specs=out_specs, out_shape=out_shape,
        input_output_aliases=aliases or {},
        scratch_shapes=[pltpu.VMEM((heads, dk, v.shape[-1]), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_use_interpret())


def _operands(q, k, v, g, beta):
    """The layouts the kernels read: ``[b, T, H * d]`` views, and ``g``,
    ``beta`` float32 as ``[b, H / heads, nc, heads * CHUNK]``: a grid
    step's heads' chunk rows side by side."""
    b, T, H = g.shape
    heads, nc = _heads(H), T // CHUNK
    return (*(x.reshape(b, T, -1) for x in (q, k, v)),
            *(x.astype(jnp.float32).reshape(b, nc, CHUNK, H // heads, heads)
              .transpose(0, 3, 1, 4, 2).reshape(b, H // heads, nc, -1)
              for x in (g, beta)))


def _t_shape(b: int, T: int, H: int) -> jax.ShapeDtypeStruct:
    """Every grid step's ``T``: ``[b, H / heads, nc, heads * CHUNK, heads *
    CHUNK]`` float32, block diagonal (each head's inverse on the
    diagonal)."""
    heads = _heads(H)
    return jax.ShapeDtypeStruct(
        (b, H // heads, T // CHUNK, heads * CHUNK, heads * CHUNK),
        jnp.float32)


def _forward(q, k, v, g, beta, keep_states: bool):
    """``o`` [b, T, H, dv] float32 and, where ``keep_states``, the state
    entering every chunk, [b, H, nc, dk, dv] float32, and every grid step's
    ``T`` (:func:`_t_shape`)."""
    b, T, _, dk = q.shape
    H, dv = v.shape[2:]
    s = _specs(b, T, _heads(H), _keys(q, v), dk, dv, reverse=False)
    out_shape = [jax.ShapeDtypeStruct((b, T, H * dv), jnp.float32)]
    out_specs = [s["v"]]
    if keep_states:
        out_shape += [jax.ShapeDtypeStruct((b, H, T // CHUNK, dk, dv),
                                           jnp.float32), _t_shape(b, T, H)]
        out_specs += [s["state"], s["t"]]
    outs = _call(_gdn_fwd_kernel, q, v,
                 [s["k"], s["k"], s["v"], s["row"], s["row"]], out_specs,
                 out_shape)(*_operands(q, k, v, g, beta))
    o = outs[0].reshape(b, T, H, dv)
    return (o, *outs[1:]) if keep_states else o


def _backward(q, k, v, g, beta, states, t, do):
    """The gradients of :func:`_forward`'s ``o`` by ``do``, from its
    entering states and ``T``: ``q``, ``k``, ``v`` are dead after it, so
    their buffers take ``dq``, ``dk``, ``dv``."""
    b, T, _, dk = q.shape
    H, dv = v.shape[2:]
    heads, nc = _heads(H), T // CHUNK
    s = _specs(b, T, heads, _keys(q, v), dk, dv, reverse=True)
    rows = jax.ShapeDtypeStruct((b, H // heads, nc, heads * CHUNK),
                                jnp.float32)
    dq, dk_, dv_, dg, dbeta = _call(
        _gdn_bwd_kernel, q, v,
        [s["k"], s["k"], s["v"], s["row"], s["row"], s["state"], s["v"],
         s["t"]],
        [s["k"], s["k"], s["v"], s["row"], s["row"]],
        [jax.ShapeDtypeStruct((b, T, x.shape[2] * x.shape[3]), x.dtype)
         for x in (q, k, v)] + [rows, rows], aliases={0: 0, 1: 1, 2: 2})(
            *_operands(q, k, v, g, beta), states, do.reshape(b, T, H * dv),
            t)

    def back(x, like):  # [b, H / heads, nc, heads * CHUNK] -> [b, T, H]
        return x.reshape(b, H // heads, nc, heads, CHUNK).transpose(
            0, 2, 4, 1, 3).reshape(b, T, H).astype(like.dtype)

    return (dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
            back(dg, g), back(dbeta, beta))


@jax.custom_vjp
def _rule(q, k, v, g, beta):
    return _forward(q, k, v, g, beta, keep_states=False)


def _rule_fwd(q, k, v, g, beta):
    o, states, t = _forward(q, k, v, g, beta, keep_states=True)
    return o, (q, k, v, g, beta, states, t)


def _rule_bwd(res, do):
    return _backward(*res, do)


_rule.defvjp(_rule_fwd, _rule_bwd, optimize_remat=True)


@jax.named_scope("model/gdn_scan")
def chunked_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array,
                       g: jax.Array, beta: jax.Array) -> jax.Array:
    """The gated delta rule in chunked form, ``S_0 = 0``, as the kernel pair.

    ``q``, ``k`` [b, T, Hk, dk] (normed, ``q`` scaled; key head ``h`` serves
    value heads ``h H / Hk`` .. ``(h + 1) H / Hk - 1``, ``Hk = H`` where they
    are already repeated to the value heads), ``v`` [b, T, H, dv], ``g``
    (log-decay, <= 0) and ``beta`` [b, T, H]; ``T`` a multiple of
    :data:`CHUNK`. Returns ``o`` [b, T, H, dv], float32.

    Inside a chunk, with ``gamma`` the chunk's cumulative log-decay and
    ``S0`` the entering state, the update ``U`` (``S_t = e^{g_t} S_{t-1} +
    k_t U_t^T``) solves ``(I + A) U = beta V - diag(beta e^gamma) K S0``,
    ``A_ij = beta_i k_i.k_j e^{gamma_i - gamma_j}`` for ``j < i``: with
    ``T = (I + A)^{-1}``, ``U = T beta V - (T beta e^gamma K) S0``; then
    ``o_t = e^{gamma_t} S0^T q_t + sum_{j <= t} e^{gamma_t - gamma_j}
    (q_t.k_j) U_j`` and ``S <- e^{total} S0 + sum_j e^{total - gamma_j} k_j
    U_j^T``. On a TPU both head widths must be multiples of 128 (the lanes
    of a tile)."""
    b, T, Hk, dk = q.shape
    H, dv = v.shape[2:]
    if T % CHUNK or H % Hk:
        raise ValueError(
            f"chunked_delta_rule: positions must be a multiple of {CHUNK} and "
            f"value heads of key heads; got {T} positions, {Hk} key heads, "
            f"{H} value heads")
    if not _use_interpret() and (dk % 128 or dv % 128):
        raise NotImplementedError(
            f"chunked_delta_rule: Gated DeltaNet kernels need head widths "
            f"that are multiples of 128 on a TPU, got dk {dk}, dv {dv}")
    if H // Hk not in (1, _heads(H)):   # a step reads one key head or each
        q, k = (jnp.repeat(x, H // Hk, axis=2) for x in (q, k))
    heads = _heads(H)
    keys = _keys(q, v)
    blocks = 4 * (2 * CHUNK * (2 * keys * dk + 2 * heads * dv)
                  + 2 * 2 * T * heads + heads * dk * dv)
    t = _t_shape(b, T, H)
    logger.info(
        "chunked_delta_rule: Pallas kernels, %d x %d x %d heads x %d | %d "
        "(%d key heads), grid %d x %d x %d: %d heads a step in %d rows, "
        "%d key head(s) read; VMEM asked: the compiler's default (forward "
        "blocks and state %.2f MiB); the backward reads the recompute's "
        "inverses, %.1f MB a call", b, T, H, dk, dv, q.shape[2], b,
        H // heads, T // CHUNK, heads, heads * CHUNK, keys, blocks / 2 ** 20,
        math.prod(t.shape) * t.dtype.itemsize / 1e6)
    return _rule(q, k, v, g, beta)


def gated_rms_norm(scale: jax.Array, o: jax.Array, z: jax.Array,
                   eps: float) -> jax.Array:
    """``RMSNorm(o) * scale * silu(z)`` over the last axis (a head's
    columns), float32 — the source's ``RMSNormGated``: norm, THEN gate."""
    def core(scale, o, z):
        o = o.astype(jnp.float32)
        normed = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
        return (normed * scale.astype(jnp.float32)
                * jax.nn.silu(z.astype(jnp.float32)))

    # checkpointed like ops.layers' norms: backward keeps o and z only
    return jax.checkpoint(core)(scale, o, z)


def gdn_inputs(params: Dict, u: jax.Array, n_k_heads: int, n_v_heads: int,
               dk: int, dv: int):
    """What the delta rule reads, from the normed ``u`` [b, T, d]: ``q``,
    ``k`` [b, T, Hk, dk] (normed, ``q`` scaled; the kernels read each key
    head for the value heads it serves, so nothing is repeated), ``v`` [b,
    T, Hv, dv], ``g``, ``beta`` [b, T, Hv] (float32 but ``v``'s source
    dtype), and the gate ``z`` [b, T, Hv, dv]."""
    b, t, d = u.shape
    key_dim, value_dim = n_k_heads * dk, n_v_heads * dv
    qkvz = named_product(linear_apply(params["in_proj_qkvz"], u), "gdn_qkvz",
                         d)
    ba = named_product(linear_apply(params["in_proj_ba"], u), "gdn_ba", d)
    qkv, z = jnp.split(qkvz, [2 * key_dim + value_dim], axis=-1)

    def conv_and_norms(qkv, w):  # float32 from the convolution on
        q, k, v = jnp.split(jax.nn.silu(causal_conv1d(qkv, w)),
                            [key_dim, 2 * key_dim], axis=-1)
        q = l2norm(q.reshape(b, t, n_k_heads, dk)) / math.sqrt(dk)
        k = l2norm(k.reshape(b, t, n_k_heads, dk))
        return q, k, v.reshape(b, t, n_v_heads, dv)

    # checkpointed like ops.layers' norms: the backward keeps the product's
    # bf16 columns, not four float32 copies of them
    q, k, v = jax.checkpoint(conv_and_norms)(qkv, params["conv"]["w"])
    beta_in, a = jnp.split(ba.astype(jnp.float32), 2, axis=-1)
    g = -jnp.exp(params["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        a + params["dt_bias"].astype(jnp.float32))
    return q, k, v, g, jax.nn.sigmoid(beta_in), z.reshape(b, t, n_v_heads, dv)


def gdn_apply(params: Dict, u: jax.Array, n_k_heads: int, n_v_heads: int,
              dk: int, dv: int, eps: float) -> jax.Array:
    """The mixer on ``u`` [b, T, d] (already normed) -> [b, T, d]. ``T`` is
    padded to a multiple of :data:`CHUNK` here and cut back: the pad follows
    the real positions, which a causal recurrence never shows them."""
    b, t, _ = u.shape
    pad = -t % CHUNK
    if pad:
        u = jnp.pad(u, ((0, 0), (0, pad), (0, 0)))
    q, k, v, g, beta, z = gdn_inputs(params, u, n_k_heads, n_v_heads, dk, dv)
    o = gated_rms_norm(params["gate_norm"]["scale"],
                       chunked_delta_rule(q, k, v, g, beta), z, eps)
    return linear_apply(params["out_proj"],
                        o.reshape(b, t + pad, -1).astype(u.dtype))[:, :t]
