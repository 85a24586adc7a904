"""The Mamba-2 mixer (Dao & Gu 2024, arXiv:2405.21060) as a pure function.

One mixer, as the ``nemotron_h`` family's ``config.json`` declares it
(``mamba_num_heads`` H, ``mamba_head_dim`` P, ``n_groups`` G,
``ssm_state_size`` N, ``conv_kernel``, ``chunk_size``)::

    [z | xBC | dt] = u W_in                      d -> HP + (HP + 2GN) + H
    xBC <- silu(causal_depthwise_conv1d(xBC) + b_conv)
    x [T,H,P], B [T,G,N], C [T,G,N] <- xBC       head h reads group h // (H/G)
    dt_t = softplus(dt_t + dt_bias),  A = -exp(A_log)        per head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t             [H,P,N], S_0 = 0
    y_t = S_t C_t + D x_t
    out = RMSNorm_groups(y * silu(z)) W_out      groups of HP / G channels

:func:`ssd_chunked` computes the recurrence in the chunked matrix form, a
pair of Pallas TPU kernels with a hand-written backward (:mod:`.pallas_ssd`):
inside a chunk a masked ``C B^T`` product weighted by the decay between the
two positions, between chunks the float32 state carried in VMEM. The matrix
products take the activations' dtype and accumulate in float32.

Two profiler regions (``utils/profiling.py:HYBRID_REGIONS``): ``model/ssm_scan``
is :func:`ssd_chunked`, both kernels in every phase; the rest of the mixer is
the caller's ``model/ssm``.
"""

from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from .layers import (linear_apply, linear_init, named_product,
                     rms_norm_init)
from .pallas_ssd import ssd_scan


def mamba2_init(key: jax.Array, dim: int, n_heads: int, head_dim: int,
                n_groups: int, state_size: int, conv_kernel: int,
                dt_min: float, dt_max: float, dt_floor: float) -> Dict:
    """Initialisers of the reference implementation (``mamba_ssm``'s
    ``Mamba2``): ``A_log = log U[1, 16]``, ``dt`` log-uniform in
    ``[dt_min, dt_max]`` floored at ``dt_floor`` and stored through the
    inverse softplus, ``D = 1``; the convolution as ``torch.nn.Conv1d``
    (fan-in ``conv_kernel``); the two projections as this repo's linears."""
    k_in, k_conv, k_cb, k_dt, k_a, k_out = jax.random.split(key, 6)
    d_inner = n_heads * head_dim
    conv_dim = d_inner + 2 * n_groups * state_size
    bound = 1.0 / math.sqrt(conv_kernel)
    dt = jnp.exp(jax.random.uniform(k_dt, (n_heads,))
                 * (math.log(dt_max) - math.log(dt_min)) + math.log(dt_min))
    dt = jnp.maximum(dt, dt_floor)
    return {
        "in_proj": linear_init(k_in, dim, 2 * d_inner + 2 * n_groups
                               * state_size + n_heads, bias=False),
        "conv": {"w": jax.random.uniform(k_conv, (conv_kernel, conv_dim),
                                         minval=-bound, maxval=bound),
                 "b": jax.random.uniform(k_cb, (conv_dim,), minval=-bound,
                                         maxval=bound)},
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "A_log": jnp.log(jax.random.uniform(k_a, (n_heads,), minval=1.0,
                                            maxval=16.0)),
        "D": jnp.ones((n_heads,)),
        "gate_norm": rms_norm_init(d_inner),
        "out_proj": linear_init(k_out, d_inner, dim, bias=False),
    }


def causal_conv1d(x: jax.Array, w: jax.Array,
                  b: Optional[jax.Array] = None) -> jax.Array:
    """Depthwise causal convolution over time: ``y_t = b + sum_i w[i] *
    x_{t-(k-1)+i}`` with ``x`` [B, T, C], ``w`` [k, C]; float32 sums. ``b``
    [C] where the convolution has a bias (Mamba-2's; the short-convolution
    mixer of :mod:`.shortconv` has none)."""
    k, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0))).astype(jnp.float32)
    w = w.astype(jnp.float32)
    y = sum(w[i] * padded[:, i:i + t] for i in range(k))
    return y if b is None else y + b.astype(jnp.float32)


@jax.named_scope("model/ssm_scan")
def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                C: jax.Array, chunk: int) -> jax.Array:
    """The state-space recurrence in chunked matrix form, ``S_0 = 0``, as the
    kernel pair (:func:`.pallas_ssd.ssd_scan`).

    ``x`` [b, T, H, P], ``dt`` [b, T, H] (float32, after softplus), ``A`` [H]
    (float32, negative), ``B``/``C`` [b, T, G, N]; ``T`` a multiple of
    ``chunk``. Returns ``y`` [b, T, H, P] in ``x``'s dtype, without the
    ``D x`` skip."""
    return ssd_scan(x, dt, A, B, C, chunk)


def gated_group_rms_norm(scale: jax.Array, y: jax.Array, z: jax.Array,
                         n_groups: int, eps: float) -> jax.Array:
    """``RMSNorm_groups(y * silu(z)) * scale`` over groups of ``C /
    n_groups`` channels, in float32; returns ``y``'s dtype."""
    def core(scale, y, z):
        g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        grouped = g.reshape(*g.shape[:-1], n_groups, -1)
        ms = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
        normed = (grouped * jax.lax.rsqrt(ms + eps)).reshape(g.shape)
        return (normed * scale.astype(jnp.float32)).astype(y.dtype)

    # checkpointed like ops.layers' norms: backward keeps y and z only
    return jax.checkpoint(core)(scale, y, z)


def mamba2_apply(params: Dict, u: jax.Array, n_heads: int, head_dim: int,
                 n_groups: int, state_size: int, chunk: int,
                 eps: float) -> jax.Array:
    """The mixer on ``u`` [B, T, d] (already normed) -> [B, T, d]. ``T`` is
    padded to a multiple of ``chunk`` here and cut back: the pad follows the
    real positions, which a causal recurrence never shows them."""
    b, t, _ = u.shape
    d_inner, gn = n_heads * head_dim, n_groups * state_size
    pad = -t % chunk
    if pad:
        u = jnp.pad(u, ((0, 0), (0, pad), (0, 0)))
    zxbcdt = named_product(linear_apply(params["in_proj"], u), "mamba_in",
                           u.shape[-1])
    z, xBC, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * gn], axis=-1)
    xBC = jax.nn.silu(causal_conv1d(
        xBC, params["conv"]["w"], params["conv"]["b"])).astype(u.dtype)
    x, B, C = jnp.split(xBC, [d_inner, d_inner + gn], axis=-1)
    x = x.reshape(b, t + pad, n_heads, head_dim)
    B, C = (m.reshape(b, t + pad, n_groups, state_size) for m in (B, C))
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    y = ssd_chunked(x, dt, A, B, C, chunk)
    y = y + (params["D"].astype(jnp.float32)[:, None]
             * x.astype(jnp.float32)).astype(y.dtype)
    y = gated_group_rms_norm(params["gate_norm"]["scale"],
                             y.reshape(b, t + pad, d_inner), z, n_groups, eps)
    return linear_apply(params["out_proj"], y)[:, :t]
