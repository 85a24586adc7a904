"""The Mamba-2 state-space recurrence in chunked matrix form (Dao & Gu 2024,
arXiv:2405.21060, "SSD") as a pair of Pallas TPU kernels under a custom VJP
whose backward is written by hand.

Per head ``h`` of group ``g`` (the ``J = H / G`` heads of a group read one
``B``, ``C``), with ``a_t = dt_t A`` the log-decay and, inside a chunk,
``cum_t = sum_{s <= t} a_s``, ``xd_t = dt_t x_t`` and ``S0`` the entering
state ([N, P] a head)::

    y_t  = sum_{s <= t} (C_t . B_s) exp(cum_t - cum_s) xd_s + exp(cum_t) C_t S0
    S    = exp(cum_last) S0 + sum_s B_s (x) exp(cum_last - cum_s) xd_s

Both kernels sweep a grid (row, group, chunk) with the chunk axis
sequential and keep the group's state, [N, J P] float32 (head ``h``'s
``S^T`` in columns ``h P .. (h + 1) P``), in VMEM scratch across the chunks:

- forward (``_ssd_fwd_kernel``): per chunk ``C B^T`` once for the group, per
  head the masked decay and its ``[chunk, chunk]`` product, ``exp(cum) C S0``
  and the next state; the call the backward needs also writes every chunk's
  entering state;
- backward (``_ssd_bwd_kernel``): the chunks in reverse, each chunk's forward
  quantities again from its saved entering state, then ``dx``, ``dB``,
  ``dC``, the gradient of each position's ``x dt`` product by ``dt`` and of
  its log-decay ``a``, and the entering state's gradient, carried in VMEM
  scratch. ``dt``'s and ``A``'s gradients are assembled from the last two.

A group's columns are multiplied in windows of whole 128-lane tiles: a head
narrower than a tile (64 in the ``nemotron`` cell) takes a product over its
window with the other heads' columns zeroed, so every slice of a tile is
aligned. The products read the compute dtype (``x``'s) and accumulate in
float32, rounding where the plain chunked form rounds: ``xd``, the decayed
scores, ``xd`` times the decay to the chunk's end, and the entering state
(float32 at ``HIGHEST`` where ``x`` is float32). The cumulative log-decay,
every exponent and the carried state are float32. The backward saves the
inputs and the entering states: b x G x T / chunk x N x J P float32, 268 MB
a layer at 2 x 8192 x 64 heads x 64 in 8 groups of 128, alive only inside
that layer's backward.
"""

from __future__ import annotations

import functools
import logging
import types

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pallas_attention import _use_interpret

logger = logging.getLogger(__name__)

#: the lanes of a TPU tile: a head's columns are multiplied in the window of
#: whole tiles that holds them
LANES = 128
_F32 = jnp.float32


def _mm(a: jax.Array, b: jax.Array, contract, dtype) -> jax.Array:
    """``a`` times ``b`` over the axes ``contract`` names, both read in
    ``dtype`` and accumulated in float32 (at ``HIGHEST`` for float32: a TPU
    otherwise rounds float32 operands to bf16)."""
    hi = jax.lax.Precision.HIGHEST if dtype == _F32 else None
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype),
                               (contract, ((), ())), precision=hi,
                               preferred_element_type=_F32)


def _ab(a, b, dtype):    # a b
    return _mm(a, b, ((1,), (0,)), dtype)


def _abt(a, b, dtype):   # a b^T
    return _mm(a, b, ((1,), (1,)), dtype)


def _atb(a, b, dtype):   # a^T b
    return _mm(a, b, ((0,), (0,)), dtype)


def _col(row: jax.Array, eye: jax.Array) -> jax.Array:
    """[1, n] -> [n, 1] through the diagonal (no transpose in the kernel)."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(col: jax.Array, eye: jax.Array) -> jax.Array:
    """[n, 1] -> [1, n] through the diagonal."""
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _windows(width: int, p: int):
    """``(lo, hi, heads)`` of each window of whole tiles over a group's
    ``width`` columns: the heads of ``p`` columns that lie in it."""
    return [(lo, min(lo + LANES, width),
             range(lo // p, (min(lo + LANES, width) - 1) // p + 1))
            for lo in range(0, width, LANES)]


def _masks(lo: int, hi: int, heads, p: int):
    """Head ``h``'s columns of the window ``lo .. hi`` as a [1, hi - lo]
    mask, for each of ``heads``."""
    col = lo + jax.lax.broadcasted_iota(jnp.int32, (1, hi - lo), 1)
    return {h: (col >= h * p) & (col < (h + 1) * p) for h in heads}


def _spread(values, masks) -> jax.Array:
    """Each column of a window takes its head's value: ``values[h]`` [r, 1]
    -> [r, width]."""
    out = None
    for h, m in masks.items():
        out = jnp.where(m, values[h], 0.0 if out is None else out)
    return out


def _chunk(c, J, p, b_ref, c_ref, dt_ref, a_ref) -> types.SimpleNamespace:
    """Chunk ``c``'s quantities that do not depend on the state: ``C B^T``
    [n, n] and per head (``J`` of ``p`` columns) the cumulative log-decay
    (a column), the masked decay ``exp(cum_t - cum_s)``, ``s <= t``, the
    decayed scores in the compute dtype, and ``dt``, ``exp(cum)``,
    ``exp(total - cum)`` (columns) and ``exp(total)`` ([1, 1]). Exponents are masked BEFORE ``exp``
    (``cum_t - cum_s`` for ``t < s`` is positive and overflows)."""
    dtype = b_ref.dtype
    n = b_ref.shape[1]
    i = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    eye, below = i == j, j <= i
    cb = _abt(c_ref[0], b_ref[0], dtype)
    heads = []
    for h in range(J):
        a = a_ref[0, 0, pl.ds(c * J + h, 1), :]
        cum = jnp.sum(jnp.where(below, a, 0.0), axis=1, keepdims=True)
        decay = jnp.where(below, jnp.exp(jnp.where(
            below, cum - _row(cum, eye), 0.0)), 0.0)
        total = cum[n - 1:, :]
        heads.append(types.SimpleNamespace(
            cum=cum, decay=decay, scores=(cb * decay).astype(dtype),
            dt=_col(dt_ref[0, 0, pl.ds(c * J + h, 1), :], eye),
            ecum=jnp.exp(cum), to_end=jnp.exp(total - cum),
            etotal=jnp.exp(total)))
    return types.SimpleNamespace(i=i, j=j, eye=eye, cb=cb, heads=heads,
                                 dtype=dtype, p=p)


def _window(k, x_ref, lo, hi, heads):
    """A window's column-spread quantities: its masks, ``x``, ``dt``, the
    rounded ``xd``, ``exp(cum)``, ``exp(total - cum)`` and ``exp(total)``
    (a row), float32."""
    m = _masks(lo, hi, heads, k.p)
    hs = k.heads
    dt = _spread({h: hs[h].dt for h in m}, m)
    x = x_ref[0, :, lo:hi].astype(_F32)
    return types.SimpleNamespace(
        masks=m, x=x, dt=dt,
        xd=(x * dt).astype(k.dtype).astype(_F32),
        ecum=_spread({h: hs[h].ecum for h in m}, m),
        to_end=_spread({h: hs[h].to_end for h in m}, m),
        etotal=_spread({h: hs[h].etotal for h in m}, m))


def _ssd_fwd_kernel(J, x_ref, b_ref, c_ref, dt_ref, a_ref, y_ref, *rest):
    """One chunk of a group in one row, chunks in order: ``y`` and, where
    asked, the entering state; the state stays in the float32 scratch."""
    *entering_ref, s_ref = rest
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    p = x_ref.shape[-1] // J
    k = _chunk(c, J, p, b_ref, c_ref, dt_ref, a_ref)
    s0 = s_ref[...]
    if entering_ref:
        entering_ref[0][0, 0, 0] = s0
    for lo, hi, heads in _windows(x_ref.shape[-1], p):
        w = _window(k, x_ref, lo, hi, heads)
        y = w.ecum * _ab(c_ref[0], s0[:, lo:hi], k.dtype)
        for h, m in w.masks.items():
            y = y + _ab(k.heads[h].scores, jnp.where(m, w.xd, 0.0), k.dtype)
        y_ref[0, :, lo:hi] = y.astype(y_ref.dtype)
        s_ref[:, lo:hi] = w.etotal * s0[:, lo:hi] + _atb(
            b_ref[0], w.xd * w.to_end, k.dtype)


def _ssd_bwd_kernel(J, x_ref, b_ref, c_ref, dt_ref, a_ref, s_ref, dy_ref,
                    dx_ref, db_ref, dc_ref, ddt_ref, da_ref, ds_ref):
    """One chunk of a group in one row, chunks in reverse: the chunk's
    forward quantities again from the saved entering state, then its
    gradients; the gradient of the state leaving the chunk stays in the
    float32 scratch and becomes the entering state's."""
    step = pl.program_id(2)
    c = pl.num_programs(2) - 1 - step

    @pl.when(step == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    n = b_ref.shape[1]
    p = x_ref.shape[-1] // J
    k = _chunk(c, J, p, b_ref, c_ref, dt_ref, a_ref)
    b, cc, dtype = b_ref[0], c_ref[0], k.dtype
    s0, ds1 = s_ref[0, 0, 0], ds_ref[...]
    zero_col = jnp.zeros((n, 1), _F32)
    dcum = [zero_col] * len(k.heads)      # by cum_t, a column a head
    dxdx = [zero_col] * len(k.heads)      # sum over a head's columns of x dxd
    dtotal = [jnp.zeros((1, 1), _F32)] * len(k.heads)
    dcb = jnp.zeros((n, n), _F32)
    db = dc = jnp.zeros((n, b_ref.shape[-1]), _F32)

    def head_sum(v, m):  # [r, width] -> [r, 1]: the sum over m's columns
        return jnp.sum(jnp.where(m, v, 0.0), axis=1, keepdims=True)

    for lo, hi, heads in _windows(x_ref.shape[-1], p):
        w = _window(k, x_ref, lo, hi, heads)
        s0w, ds1w = s0[:, lo:hi], ds1[:, lo:hi]
        dy = dy_ref[0, :, lo:hi].astype(_F32)
        # y += exp(cum) C S0;  S' = exp(total) S0 + B^T (xd exp(total - cum))
        dye = dy * w.ecum
        ds_ref[:, lo:hi] = w.etotal * ds1w + _atb(cc, dye, dtype)
        dc = dc + _abt(dye, s0w, dtype)
        inter = dye * _ab(cc, s0w, dtype)
        wd = (w.xd * w.to_end).astype(dtype).astype(_F32)
        db = db + _abt(wd, ds1w, dtype)
        dwd = _ab(b, ds1w, dtype)
        dxd = dwd * w.to_end
        dend = dwd * w.xd * w.to_end          # by exp(total - cum), times it
        kept = jnp.sum(s0w * ds1w, axis=0, keepdims=True)
        # y += (C B^T * decay) xd, a head at a time
        for h, m in w.masks.items():
            x = k.heads[h]
            dscores = _abt(jnp.where(m, dy, 0.0), w.xd, dtype)
            dxd = dxd + jnp.where(m, _atb(x.scores, dy, dtype), 0.0)
            dcb = dcb + dscores * x.decay
            lag = dscores * k.cb * x.decay    # by cum_t - cum_s, times decay
            de = head_sum(dend, m)
            dcum[h] = (dcum[h] + jnp.sum(lag, axis=1, keepdims=True)
                       - _col(jnp.sum(lag, axis=0, keepdims=True), k.eye)
                       + head_sum(inter, m) - de)
            dtotal[h] = (dtotal[h] + jnp.sum(de, axis=0, keepdims=True)
                         + x.etotal * jnp.sum(jnp.where(m, kept, 0.0),
                                              axis=1, keepdims=True))
        for h, m in w.masks.items():
            dxdx[h] = dxdx[h] + head_sum(dxd * w.x, m)
        dx_ref[0, :, lo:hi] = (dxd * w.dt).astype(dx_ref.dtype)
    # C B^T
    dc_ref[0] = (dc + _ab(dcb, b, dtype)).astype(dc_ref.dtype)
    db_ref[0] = (db + _atb(dcb, cc, dtype)).astype(db_ref.dtype)
    last = k.i[:, :1] == n - 1
    heads = range(len(k.heads))
    # total = cum at the chunk's last position; a_t enters every cum_u,
    # u >= t: its gradient is the sum of theirs
    for h in heads:
        row = pl.ds(c * len(heads) + h, 1)
        da_ref[0, 0, row, :] = jnp.sum(jnp.where(
            k.i >= k.j, dcum[h] + jnp.where(last, dtotal[h], 0.0), 0.0),
            axis=0, keepdims=True)
        ddt_ref[0, 0, row, :] = _row(dxdx[h], k.eye)


def _specs(chunk: int, nc: int, heads: int, p: int, n: int, reverse: bool):
    """Block specs over the grid (row, group, chunk), chunks in order or in
    reverse: ``x``/``y`` tiles [chunk, J P] of ``[b, T, H P]`` and ``B``/``C``
    tiles [chunk, N] of ``[b, T, G N]`` (a group's columns are its block: no
    relayout), ``dt``/``a`` rows of ``[b, G, nc J, chunk]`` (the group's
    whole sequence resident: one row a head and chunk), and the entering
    states
    ``[b, G, nc, N, J P]``."""
    def at(c):
        return nc - 1 - c if reverse else c

    return types.SimpleNamespace(
        x=pl.BlockSpec((1, chunk, heads * p), lambda r, g, c: (r, at(c), g)),
        bc=pl.BlockSpec((1, chunk, n), lambda r, g, c: (r, at(c), g)),
        row=pl.BlockSpec((1, 1, nc * heads, chunk),
                         lambda r, g, c: (r, g, 0, 0)),
        state=pl.BlockSpec((1, 1, 1, n, heads * p),
                           lambda r, g, c: (r, g, at(c), 0, 0)))


def _rows(v: jax.Array, chunk: int, groups: int) -> jax.Array:
    """[b, T, H] -> float32 ``[b, G, nc J, chunk]``: row ``c J + h`` is
    head ``h``'s chunk ``c`` (a whole row of lanes: a row is read and
    written at a dynamic row index only)."""
    b, T, H = v.shape
    return (v.astype(_F32).reshape(b, T // chunk, chunk, groups, H // groups)
            .transpose(0, 3, 1, 4, 2).reshape(b, groups, -1, chunk))


def _unrows(v: jax.Array, heads: int) -> jax.Array:
    """:func:`_rows`' inverse."""
    b, groups, rows, chunk = v.shape
    return (v.reshape(b, groups, rows // heads, heads, chunk)
            .transpose(0, 2, 4, 1, 3).reshape(b, rows // heads * chunk, -1))


def _call(kernel, x, B, chunk, in_specs, out_specs, out_shape, aliases=None):
    """The ``pallas_call`` both kernels share: the grid (row, group,
    chunk), the chunk axis sequential, a group's [N, J P] float32 scratch
    carried along it; the kernel is told the group's ``J`` heads."""
    from jax.experimental.pallas import tpu as pltpu
    b, T, H, p = x.shape
    G, n = B.shape[2:]
    return pl.pallas_call(
        functools.partial(kernel, H // G), grid=(b, G, T // chunk),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        input_output_aliases=aliases or {},
        scratch_shapes=[pltpu.VMEM((n, H // G * p), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_use_interpret())


def _operands(x, dt, A, B, C, chunk):
    """The layouts the kernels read: ``[b, T, H P]`` and ``[b, T, G N]``
    views, and ``dt`` and the log-decay ``dt A`` as rows."""
    b, T = x.shape[:2]
    G = B.shape[2]
    return (x.reshape(b, T, -1), *(m.reshape(b, T, -1) for m in (B, C)),
            _rows(dt, chunk, G), _rows(dt * A, chunk, G))


def _forward(chunk, x, dt, A, B, C, keep_states: bool):
    """``y`` [b, T, H, P] in ``x``'s dtype and, where ``keep_states``, the
    state entering every chunk, [b, G, nc, N, J P] float32."""
    b, T, H, p = x.shape
    G, n = B.shape[2:]
    nc = T // chunk
    s = _specs(chunk, nc, H // G, p, n, reverse=False)
    out_shape = [jax.ShapeDtypeStruct((b, T, H * p), x.dtype)]
    out_specs = [s.x]
    if keep_states:
        out_shape.append(jax.ShapeDtypeStruct((b, G, nc, n, H // G * p),
                                              _F32))
        out_specs.append(s.state)
    outs = _call(_ssd_fwd_kernel, x, B, chunk,
                 [s.x, s.bc, s.bc, s.row, s.row], out_specs, out_shape)(
                     *_operands(x, dt, A, B, C, chunk))
    y = outs[0].reshape(x.shape)
    return (y, outs[1]) if keep_states else y


def _backward(chunk, x, dt, A, B, C, states, dy):
    """The gradients of :func:`_forward`'s ``y`` by ``dy``: ``x``, ``B``,
    ``C`` are dead after it, so their buffers take ``dx``, ``dB``, ``dC``.
    ``dt`` reaches ``y`` through ``x dt`` and through the log-decay ``dt
    A``, ``A`` through the log-decay alone."""
    b, T, H, p = x.shape
    G, n = B.shape[2:]
    nc = T // chunk
    s = _specs(chunk, nc, H // G, p, n, reverse=True)
    rows = jax.ShapeDtypeStruct((b, G, nc * (H // G), chunk), _F32)
    dx, dB, dC, ddt, da = _call(
        _ssd_bwd_kernel, x, B, chunk,
        [s.x, s.bc, s.bc, s.row, s.row, s.state, s.x],
        [s.x, s.bc, s.bc, s.row, s.row],
        [jax.ShapeDtypeStruct((b, T, m.shape[2] * m.shape[3]), m.dtype)
         for m in (x, B, C)] + [rows, rows], aliases={0: 0, 1: 1, 2: 2})(
            *_operands(x, dt, A, B, C, chunk), states, dy.reshape(b, T, -1))
    da = _unrows(da, H // G)
    return (dx.reshape(x.shape), _unrows(ddt, H // G) + da * A,
            jnp.sum(da * dt, axis=(0, 1)), dB.reshape(B.shape),
            dC.reshape(C.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _scan(chunk, x, dt, A, B, C):
    return _forward(chunk, x, dt, A, B, C, keep_states=False)


def _scan_fwd(chunk, x, dt, A, B, C):
    y, states = _forward(chunk, x, dt, A, B, C, keep_states=True)
    return y, (x, dt, A, B, C, states)


def _scan_bwd(chunk, res, dy):
    return _backward(chunk, *res, dy)


_scan.defvjp(_scan_fwd, _scan_bwd, optimize_remat=True)


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, chunk: int) -> jax.Array:
    """``y`` [b, T, H, P] in ``x``'s dtype, without the ``D x`` skip, from
    ``x`` [b, T, H, P], ``dt`` [b, T, H] (float32, after softplus), ``A``
    [H] (float32, negative) and ``B``/``C`` [b, T, G, N], ``S_0 = 0``; ``T``
    a multiple of ``chunk``. On a TPU a group's ``J P`` columns, ``N`` and
    ``chunk`` must be multiples of 128 (the lanes of a tile)."""
    b, T, H, p = x.shape
    G, n = B.shape[2:]
    if T % chunk or H % G:
        raise ValueError(
            f"ssd_chunked: positions must be a multiple of the chunk and "
            f"heads of groups; got {T} positions, chunk {chunk}, {H} heads, "
            f"{G} groups")
    width = H // G * p
    if not _use_interpret() and (width % LANES or n % LANES
                                 or chunk % LANES):
        raise NotImplementedError(
            f"ssd_chunked: the Mamba-2 scan kernels need a group's columns "
            f"(heads x head width), the state size and the chunk to be "
            f"multiples of {LANES} on a TPU, got {H // G} x {p} = {width}, "
            f"state {n}, chunk {chunk}")
    nc = T // chunk
    item = x.dtype.itemsize
    blocks = (2 * 2 * chunk * width * item + 2 * 2 * chunk * n * item
              + 2 * 2 * nc * H // G * chunk * 4 + n * width * 4)
    logger.info(
        "ssd_chunked: Pallas kernels, %d x %d x %d heads x %d, %d groups x "
        "%d, chunk %d, grid %d x %d x %d: %d heads a step (%d columns, %d "
        "windows of %d lanes); VMEM asked: the compiler's default (forward "
        "blocks and state %.2f MiB)", b, T, H, p, G, n, chunk, b, G, nc,
        H // G, width, len(_windows(width, p)), LANES, blocks / 2 ** 20)
    return _scan(chunk, x, dt, A, B, C)
