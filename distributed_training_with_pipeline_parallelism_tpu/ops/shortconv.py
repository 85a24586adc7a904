"""The gated short-convolution mixer (LFM2's ``conv`` operator; Liquid AI,
``model_type`` ``lfm2`` / ``lfm2_moe``) as a pure function.

One mixer, as the source's ``config.json`` declares it (``conv_L_cache`` taps,
``conv_bias``)::

    [B | C | x] = u W_in                         d -> 3 d
    y = C * causal_depthwise_conv1d(B * x)       conv_L_cache taps a channel
    out = y W_out                                d -> d

Both gates are plain products, no activation; the convolution is
:func:`.mamba2.causal_conv1d`, Mamba-2's, with the bias optional
(``conv_bias`` puts one on the convolution and on both projections, as the
source's module does). The two gates and the tap sum are float32 whatever the
activations' dtype; the projections take the activations' dtype.

The whole mixer is the caller's profiler region ``model/shortconv``
(``utils/profiling.py:HYBRID_REGIONS``).
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from .layers import linear_apply, linear_init, named_product
from .mamba2 import causal_conv1d


def shortconv_init(key: jax.Array, dim: int, taps: int, bias: bool) -> Dict:
    """The projections as this repo's linears; the convolution as
    ``torch.nn.Conv1d`` (fan-in ``taps``), as :func:`.mamba2.mamba2_init`'s."""
    k_in, k_conv, k_cb, k_out = jax.random.split(key, 4)
    bound = 1.0 / math.sqrt(taps)
    conv = {"w": jax.random.uniform(k_conv, (taps, dim), minval=-bound,
                                    maxval=bound)}
    if bias:
        conv["b"] = jax.random.uniform(k_cb, (dim,), minval=-bound,
                                       maxval=bound)
    return {"in_proj": linear_init(k_in, dim, 3 * dim, bias=bias),
            "conv": conv,
            "out_proj": linear_init(k_out, dim, dim, bias=bias)}


def shortconv_apply(params: Dict, u: jax.Array) -> jax.Array:
    """The mixer on ``u`` [B, T, d] (already normed) -> [B, T, d]."""
    B, C, x = jnp.split(named_product(
        linear_apply(params["in_proj"], u), "shortconv_in", u.shape[-1]),
        3, axis=-1)
    f32 = jnp.float32
    y = C.astype(f32) * causal_conv1d(
        B.astype(f32) * x.astype(f32), params["conv"]["w"],
        params["conv"].get("b"))
    return linear_apply(params["out_proj"], y.astype(u.dtype))
