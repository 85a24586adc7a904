"""A routed-expert layer as ONE expert-parallel rank holds it.

The patterned stack's expert layer (``models/nemotron_h.py``; DeepSeek-V3-
style routing, as the sources' ``config.json``s declare it): sigmoid scores
over ALL ``n_routed_experts``, the ``top_k`` largest of ``score + bias``
chosen (the bias is ``e_score_correction_bias`` / LFM2's ``expert_bias``, a
buffer that no gradient reaches), weights ``scale * s_e / (sum over the
chosen of s + eps)`` (``eps`` the source's: ``ModelConfig.router_norm_eps``),
plus, where the source has one (a ``shared`` leaf), one shared expert that
every token takes::

    out = sum_{e chosen and held here} w_e f_e(x)  [+  f_shared(x)]

The expert function ``f`` comes in the two forms the benchmark's
configurations have (``ModelConfig.mlp_hidden_act``): ``"relu2"``,
``relu(x W_up)^2 W_down`` (two matrices: ``w1``, ``w2``; Nemotron-H), and
the ``gated`` ``"silu"``, ``(silu(x W_gate) * (x W_up)) W_down`` (three:
``w1`` the gate, ``w3`` the linear branch, ``w2``; DeepSeek-V3-style
families); the parameters say which (a ``w3``, a ``gate``).
Routing, the gate between the products and the worst-case reasoning below
are one code for both; :func:`mlp_init` / :func:`mlp_apply` are the same
function as a plain layer (the shared expert, and the stack's dense MLP).

The layer is told which experts it holds (``held``: ids of the router's
width). It routes over all of them and computes its own experts' part —
what expert parallelism asks of a rank (``parallel/expert_parallel.py`` is
the later consumer); nothing here stands in for the absent ranks or their
exchange.

Unlike ``models/moe.py`` (softmax top-k with a capacity that drops tokens,
a dense ``[tokens, k, experts, capacity]`` one-hot) nothing is dispatched and
so nothing can be dropped: every held expert multiplies every token, and a
``[tokens, held]`` gate — an expert's weight where the token chose it, zero
elsewhere — is applied between its products. That is one MLP of width
``held * width`` with its hidden columns gated by expert: two (three) plain
matrix products, no sort, gather, scatter or buffer, and a step costs the
same whatever is routed.

It pays for ``held`` expert passes a token where the routing asks for
``top_k * held / n_routed`` (8 against 0.375 in the benchmark's ``nemotron``
cell: 95% of the gated columns are zeros; 8 against 0.25, 97%, in its
``joyai-llm-flash`` cell; 8 against 1, 87.5%, in its ``lfm2-8b-a1b`` cell).
That is the price of the worst case, and the worst case is what one rank's
share of training meets (PR 30, on the chip,
with ``lax.ragged_dot`` over a sorted buffer): on random tokens AdamW moved a
layer's local assignments 7876 -> 12757 in 20 steps and the whole batch came
to pick the same experts within ~30, so every buffer short of a row a token
overflowed; the compiler's grouped-product kernels skip empty tiles, so the
step time followed the routing (3% between seeds); what they leave in rows
outside every group is unspecified (NaN included, forward and in every
cotangent); and given the worst-case buffer whole they ran at 15% of the
MXU's peak. A bounded buffer needs the source's load balancing
(``e_score_correction_bias`` updated outside the loss, here a buffer held at
zero) before it can hold: ROADMAP Queue 2.

The router is float32 end to end (its product at ``Precision.HIGHEST``: a
TPU otherwise rounds float32 operands to bf16).

Profiler regions (``utils/profiling.py:HYBRID_REGIONS``): the held experts'
products and their gate are ``model/moe_experts``; router, top-k and the
shared expert are the caller's ``model/moe``.
"""

from __future__ import annotations

import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp

from .layers import linear_apply, linear_init, named_product


def mlp_init(keys, dim: int, width: int, gated: bool) -> Dict:
    """One MLP of ``width`` from three keys: ``up`` and ``down``, and a
    ``gate`` where the form is ``gated``."""
    ku, kd, kg = keys
    params = {"up": linear_init(ku, dim, width, bias=False),
              "down": linear_init(kd, width, dim, bias=False)}
    if gated:
        params["gate"] = linear_init(kg, dim, width, bias=False)
    return params


def experts_init(key: jax.Array, dim: int, n_routed: int, n_held: int,
                 width: int, shared_width: int, gated: bool = False) -> Dict:
    """``router.w`` is ``n_routed`` wide whatever is held; ``experts.w1`` /
    ``w2`` (and ``w3`` where ``gated``) stack the ``n_held`` held experts
    (the names AdamW's decay mask knows, ``utils/train.py:adamw``)."""
    kr, k1, k2, ku, kd = jax.random.split(key, 5)
    # the gated form's two further draws, beside the five every form takes
    k3, kg = jax.random.split(jax.random.fold_in(key, 5))
    b1, b2 = 1.0 / math.sqrt(dim), 1.0 / math.sqrt(width)
    experts = {"w1": jax.random.uniform(k1, (n_held, dim, width),
                                        minval=-b1, maxval=b1),
               "w2": jax.random.uniform(k2, (n_held, width, dim),
                                        minval=-b2, maxval=b2)}
    if gated:
        experts["w3"] = jax.random.uniform(k3, (n_held, dim, width),
                                           minval=-b1, maxval=b1)
    params = {
        "router": {"w": jax.random.uniform(kr, (dim, n_routed), minval=-b1,
                                           maxval=b1),
                   "bias": jnp.zeros((n_routed,))},
        "experts": experts,
    }
    if shared_width:  # a source without a shared expert: no such leaf
        params["shared"] = mlp_init((ku, kd, kg), dim, shared_width, gated)
    return params


def relu2(x: jax.Array) -> jax.Array:
    return jnp.square(jax.nn.relu(x))


def silu_gated(gate: jax.Array, up: jax.Array) -> jax.Array:
    return jax.nn.silu(gate) * up


def mlp_apply(params: Dict, x: jax.Array) -> jax.Array:
    """``down(relu2(up x))``, or ``down(silu(gate x) * up x)`` where the
    parameters hold a gate. The activation is checkpointed: the backward
    keeps the pre-activations and recomputes the pointwise chain."""
    d = x.shape[-1]
    up = named_product(linear_apply(params["up"], x), "mlp_up", d)
    if "gate" in params:
        h = jax.checkpoint(silu_gated)(named_product(
            linear_apply(params["gate"], x), "mlp_gate", d), up)
    else:
        h = jax.checkpoint(relu2)(up)
    return linear_apply(params["down"], h)


def route(router: Dict, x: jax.Array, top_k: int, scale: float,
          norm_eps: float = 1e-20):
    """``x`` [T, d] -> (ids [T, k] of the chosen experts, weights [T, k]),
    float32. The weights are normalised over all ``k`` chosen, wherever they
    live, their sum guarded by the source's ``norm_eps``."""
    logits = jnp.dot(x.astype(jnp.float32), router["w"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    bias = jax.lax.stop_gradient(router["bias"].astype(jnp.float32))
    _, ids = jax.lax.top_k(scores + bias, top_k)
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    weights = scale * chosen / (chosen.sum(-1, keepdims=True) + norm_eps)
    return ids, weights


@jax.named_scope("model/moe_experts")
def held_experts(experts: Dict, x: jax.Array, gate: jax.Array) -> jax.Array:
    """``sum_e gate[t, e] * f_e(x[t])`` for ``x`` [T, d] and ``gate``
    [T, held] (float32): every held expert on every token, the gate between
    the hidden activation (``relu(x W1)^2``, or ``silu(x W1) * (x W3)`` where
    the stacks hold a ``w3``) and the product with ``W2``."""
    d = x.shape[-1]
    h = named_product(jnp.einsum("td,edf->tef", x, experts["w1"]),
                      "experts_w1", d)
    if "w3" in experts:
        h = jax.checkpoint(silu_gated)(h, named_product(
            jnp.einsum("td,edf->tef", x, experts["w3"]), "experts_w3", d))
    else:
        h = jax.checkpoint(relu2)(h)
    h = (h.astype(jnp.float32) * gate[..., None]).astype(x.dtype)
    return jnp.einsum("tef,efd->td", h, experts["w2"])


def experts_apply(params: Dict, x: jax.Array, held: Sequence[int],
                  top_k: int, scale: float, norm_eps: float = 1e-20):
    """``x`` [T, d] (already normed) -> (out [T, d], the assignments each
    held expert got [held]).

    ``held``: static ids of the experts whose weights ``params["experts"]``
    stacks, in that order. The shared expert is added where the parameters
    hold one."""
    ids, weights = route(params["router"], x, top_k, scale, norm_eps)
    chose = ids[:, :, None] == jnp.asarray(held, ids.dtype)    # [T, k, held]
    gate = jnp.where(chose, weights[:, :, None], 0.0).sum(1)
    out = held_experts(params["experts"], x, gate)
    if "shared" in params:
        out = out + mlp_apply(params["shared"], x)
    return out, chose.sum((0, 1))
