"""Elementary neural-net ops as pure functions over parameter pytrees.

All ops take a params dict and return arrays; initializers mirror torch's
defaults closely enough for healthy training (the reference never asserts loss
values — SURVEY.md §0 — so distributional parity, not bit parity, is the bar;
bit-level parity against torch is established in tests by copying weights).
"""

from __future__ import annotations

import contextlib
import logging
import math
from typing import (Any, Callable, Dict, List, NamedTuple, Sequence, Tuple,
                    Union)

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

_log = logging.getLogger(__name__)


def linear_init(key: jax.Array, in_dim: int, out_dim: int, bias: bool = True) -> Dict:
    """Kaiming-uniform weight + uniform bias, matching ``torch.nn.Linear.reset_parameters``."""
    wkey, bkey = jax.random.split(key)
    bound = 1.0 / math.sqrt(in_dim)
    params = {"w": jax.random.uniform(wkey, (in_dim, out_dim), minval=-bound, maxval=bound)}
    if bias:
        params["b"] = jax.random.uniform(bkey, (out_dim,), minval=-bound, maxval=bound)
    return params


def linear_apply(params: Dict, x: jax.Array) -> jax.Array:
    y = x @ params["w"]
    if "b" in params:
        y = y + params["b"]
    return y


def layer_norm_init(dim: int) -> Dict:
    return {"scale": jnp.ones((dim,)), "bias": jnp.zeros((dim,))}


def layer_norm_apply(params: Dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    # checkpoint: backward saves only (x, scale, bias) and recomputes the
    # stats — without it autodiff banks the f32 normalized copy (2-4x the
    # input bytes at bf16 compute), the single largest residual class in
    # the stored-activation profiles (docs/performance.md)
    def core(scale, bias, x):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        xn = (x - mean) * jax.lax.rsqrt(var + eps)
        return xn * scale + bias

    return jax.checkpoint(core)(params["scale"], params["bias"], x)


def rms_norm_init(dim: int) -> Dict:
    return {"scale": jnp.ones((dim,))}


def rms_norm_apply(params: Dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    # checkpointed for the same residual-traffic reason as layer_norm_apply
    def core(scale, x):
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + eps) * scale

    return jax.checkpoint(core)(params["scale"], x)


#: what the chip's COMPILER lets one program hold, by ``device_kind`` (the
#: v5e's own "Used .. of 15.75G hbm"; the data sheet's 16e9 is
#: ``analysis/cost_model.py``'s). A kind that is not here — the CPU — has no
#: room: nothing but the flash pair is ever kept there.
COMPILER_HBM_BYTES = {"TPU v5 lite": 15.75e9}
#: the share of the chip that a budget never touches
ROOM_MARGIN = 0.04

_room = 0.0      # bytes, set by remat_room() while a step is traced
_offers = None   # a list while offers_of() walks a layer's shapes


class Offer(NamedTuple):
    """A named product output a rematerialised layer could keep: what
    keeping it costs and what it spares (2 x ``contraction`` FLOPs an
    element, so per byte the contraction width over the item size)."""
    name: str
    shape: Tuple[int, ...]
    dtype: str
    contraction: int

    @property
    def nbytes(self) -> int:
        return math.prod(self.shape) * jnp.dtype(self.dtype).itemsize

    @property
    def flops_per_byte(self) -> float:
        return 2 * self.contraction / jnp.dtype(self.dtype).itemsize


def named_product(y: jax.Array, name: str, contraction: int) -> jax.Array:
    """``y``, the output of a product over ``contraction`` columns that
    reads a sublayer's normed input, under a ``checkpoint_name``: a
    :func:`remat_layer` that was granted ``name`` keeps it and the backward
    reads it instead of multiplying again. Outside such a policy the name
    lowers to nothing."""
    if _offers is not None:
        _offers.append(Offer(name, tuple(y.shape), str(y.dtype), contraction))
    return checkpoint_name(y, name)


def offers_of(fn: Callable, *args) -> List[Offer]:
    """What ``fn(*args)`` names through :func:`named_product`, from shapes
    alone (``jax.eval_shape``: nothing is computed)."""
    global _offers
    was, _offers = _offers, []
    try:
        jax.eval_shape(fn, *args)
        return _offers
    finally:
        _offers = was


def chip_room(mesh, held_bytes: float) -> float:
    """Bytes a chip of ``mesh`` has left beside ``held_bytes`` (what a step
    holds whatever its layers keep) and the margin; 0 where the table does
    not know the device."""
    limit = COMPILER_HBM_BYTES.get(mesh.devices.flat[0].device_kind)
    if limit is None:
        return 0.0
    return max(0.0, limit * (1 - ROOM_MARGIN) - held_bytes)


@contextlib.contextmanager
def remat_room(room: float):
    """While a step is TRACED inside this, ``room`` bytes of a chip are left
    beside what the step holds whatever its layers keep
    (:func:`chip_room`): the way ``make_train_step``, which sees the
    parameter and optimizer trees, hands its number to the stack walker,
    which sees the activations' shapes and takes its own share off
    (``models/nemotron_h.py:kept_names`` is the reader)."""
    global _room
    was, _room = _room, room
    try:
        yield
    finally:
        _room = was


def current_room() -> float:
    return _room


def choose_kept(offers: Sequence[Sequence[Offer]],
                budget: Union[float, Sequence[float]],
                ) -> Tuple[List[Tuple[str, ...]], Dict[str, Any]]:
    """Which of ``offers[l]`` (layer ``l``'s named products, in order) to
    keep -> (the names granted to every layer, the record of the choice).

    ``budget``: bytes, one number for everything kept together — or one
    number an INSTANT, ``budget[L]`` for what the layers before ``L`` may
    keep together, ``L`` = 0 .. the number of layers: what a layer keeps is
    held from its forward until its own backward has run, so while layer
    ``L``'s backward runs the layers after it hold nothing any more (and
    ``L``'s own products are there kept or recomputed).

    Dearest to recompute per byte first (2 x contraction FLOPs an element);
    ties to the LATER layer, whose bytes are held for the shorter time;
    each granted whole while it fits what is left at every instant it is
    held, else refused."""
    n = len(offers)
    left = ([max(0.0, budget)] * (n + 1) if isinstance(budget, (int, float))
            else [max(0.0, b) for b in budget])
    assert len(left) == n + 1, (len(left), n)
    tightest = min(left[1:])
    order = sorted(((l, o) for l, mine in enumerate(offers) for o in mine),
                   key=lambda lo: (-lo[1].flops_per_byte, -lo[0]))
    keep: List[List[str]] = [[] for _ in offers]
    granted, refused, kept_bytes = [], [], 0
    for l, o in order:
        if all(o.nbytes <= left[i] for i in range(l + 1, n + 1)):
            for i in range(l + 1, n + 1):
                left[i] -= o.nbytes
            kept_bytes += o.nbytes
            keep[l].append(o.name)
            granted.append(f"{l}:{o.name}")
        else:
            refused.append(f"{l}:{o.name}")
    record = {"budget_bytes": int(tightest),
              "offered_bytes": sum(o.nbytes for _, o in order),
              "granted_bytes": kept_bytes,
              "names_offered": len(order), "names_granted": len(granted),
              "granted": granted, "refused_for_room": refused}
    _log.info("remat_layers: budget %.3f GB; %d named products offered, "
              "%.3f GB; granted %d of them, %.3f GB: %s; refused for room: %s",
              tightest / 1e9, len(order), record["offered_bytes"] / 1e9,
              len(granted), kept_bytes / 1e9,
              " ".join(granted) or "none", " ".join(refused) or "none")
    return [tuple(k) for k in keep], record


def remat_layer(fn: Callable, layers: int, keep: Sequence[str] = (),
                **kw) -> Callable:
    """``jax.checkpoint(fn, **kw)`` for ONE layer of a stack of ``layers``
    under ``cfg.remat_layers``: the backward recomputes the layer from its
    input, but for what one policy keeps — the flash pair always, and the
    names in ``keep``.

    The flash kernels' output ``o`` [b*h, s, d_v] and float32 log-sum-exp
    [b*h, s] are the two residuals the backward kernel reads that only the
    forward kernel can give back, and per byte kept the dearest thing a
    layer recomputes (batch 2 x 8192, 32 heads of 192 | 128: 21.45 ms of
    kernel for 136 MB; chip, PR 34). A layer without the kernels (dense,
    ring or Ulysses attention; Mamba-2; MLPs and experts) has no such names
    in its trace.

    ``keep``: names of :func:`named_product` outputs this layer was granted
    (:func:`choose_kept`, from a byte budget): kept whole, read back by the
    backward instead of multiplied again. ONE helper, one policy and one
    algorithm for every ``remat_layers`` site; the parameter is zero-length
    for the callers that scan their layers or cut them into pipeline stages
    (``models/transformer.py:body_apply``, ``parallel/seq_parallel.py``,
    ``parallel/pipeline.py``'s MoE stage body), where a name would be kept
    for every layer of the scan at once — a different sum, and no budget is
    computed there. q, k and v, and every product not granted, are still
    recomputed.

    Says at ``logging.INFO`` how many layers are rematerialised and, when
    the backward is traced, what the policy kept, with its bytes from the
    traced shapes (each distinct line once a call)."""
    from .pallas_attention import FLASH_LSE, FLASH_OUT
    named = jax.checkpoint_policies.save_only_these_names(
        FLASH_OUT, FLASH_LSE, *keep)
    said = set()

    def policy(prim, *avals, **params):
        keeps = named(prim, *avals, **params)
        if keeps:
            a, = avals
            line = (f"remat_layers: a layer keeps {params['name']} "
                    f"{a.str_short()}, {a.size * a.dtype.itemsize / 1e6:.1f} "
                    "MB")
            if line not in said:
                said.add(line)
                _log.info(line)
        return keeps

    _log.info("remat_layers: %d layers recomputed in the backward from their "
              "input; where the flash kernels run, %s and %s are kept%s",
              layers, FLASH_OUT, FLASH_LSE,
              "; granted: " + ", ".join(keep) if keep else "")
    return jax.checkpoint(fn, policy=policy, **kw)


def embedding_init(key: jax.Array, vocab: int, dim: int) -> jax.Array:
    """N(0, 1) like ``torch.nn.Embedding``."""
    return jax.random.normal(key, (vocab, dim))


def embedding_apply(table: jax.Array, tokens: jax.Array) -> jax.Array:
    return jnp.take(table, tokens, axis=0)


def dropout_apply(x: jax.Array, rate: float, rng) -> jax.Array:
    """Inverted dropout: zero each element with probability ``rate`` and scale
    survivors by 1/(1-rate), matching ``torch.nn.functional.dropout`` train
    semantics. ``rng=None`` (eval mode) or ``rate=0`` is the identity.
    ``rate`` must be a static Python float (it selects the compiled program).
    """
    if rng is None or rate == 0.0:
        return x
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), jnp.zeros((), x.dtype))


def sharded_dropout_apply(x: jax.Array, rate: float, rng,
                          axis: str = None, n_shards: int = 1,
                          shard_dim: int = -1) -> jax.Array:
    """Dropout on a tensor whose ``shard_dim`` is this device's 1/n_shards
    slice of a larger tensor (tensor-parallel attention heads / FFN hidden,
    sequence-parallel positions). The mask is drawn at the FULL shape from
    the replicated ``rng`` and the local block sliced out by
    ``lax.axis_index(axis)`` — so every shard's mask is exactly the
    single-device mask restricted to its slice, and a sharded run matches
    the unsharded oracle bit-for-bit (the axis-aware mask folding of
    VERDICT r1 item 5). Mask bits are threefry ALU work, cheap next to the
    matmuls the mask sits between; no [full] tensor is materialized beyond
    the mask itself.
    """
    if rng is None or rate == 0.0:
        return x
    if axis is None or n_shards == 1:
        return dropout_apply(x, rate, rng)
    shard_dim = shard_dim % x.ndim
    full_shape = list(x.shape)
    full_shape[shard_dim] *= n_shards
    keep_full = jax.random.bernoulli(rng, 1.0 - rate, tuple(full_shape))
    idx = jax.lax.axis_index(axis)
    keep = jax.lax.dynamic_slice_in_dim(
        keep_full, idx * x.shape[shard_dim], x.shape[shard_dim], shard_dim)
    return jnp.where(keep, x / (1.0 - rate), jnp.zeros((), x.dtype))


def _token_nll(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Per-position NLL (fp32 log-softmax), the core shared by the masked
    and unmasked loss paths so they cannot diverge."""
    logz = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logz, targets[..., None], axis=-1)[..., 0]


def cross_entropy_loss(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean token-wise cross entropy over all positions.

    Matches the reference's ``tokenwise_loss_fn`` — ``nn.CrossEntropyLoss`` over
    flattened ``(B*S, V)`` logits (``LLMsDistributedTrainingHelper.py:197-201``).
    """
    return jnp.mean(_token_nll(logits, targets))


def masked_xent_sum(logits: jax.Array, targets: jax.Array,
                    pad_id: int) -> Tuple[jax.Array, jax.Array]:
    """Cross-entropy SUM over non-pad positions plus the valid-token count.

    The building block for ignore-index losses (torch's
    ``CrossEntropyLoss(ignore_index=...)``): the caller divides the summed
    NLL by the (possibly globally reduced) count, so microbatched/sharded
    runs can normalize by the GLOBAL valid count instead of a per-chunk
    mean-of-means (which would weight short sequences more).
    """
    nll = _token_nll(logits, targets)
    valid = targets != pad_id
    return jnp.sum(jnp.where(valid, nll, 0.0)), jnp.sum(valid)


def global_pad_scale(targets: jax.Array, pad_id: int, n_micro: int,
                     data_axis=None, shard_axes=None) -> jax.Array:
    """The factor that turns per-microbatch masked NLL sums into the
    globally normalized ignore-index mean under the pipeline executor's
    standard reductions: the executor later multiplies accumulated loss by
    ``1/n_micro`` and means over ``data_axis`` replicas (``shard_axes`` —
    an axis name or tuple of them, e.g. seq/expert — are summed unscaled),
    so pre-multiplying each sum by ``n_micro * n_data / n_valid_global``
    cancels everything into ``total_nll / global_valid_count``. The valid
    count psums over every given axis. Must be called OUTSIDE the schedule
    scan."""
    n_valid = jnp.sum(targets != pad_id).astype(jnp.float32)
    n_data = 1
    if data_axis is not None:
        n_valid = jax.lax.psum(n_valid, data_axis)
        n_data = jax.lax.axis_size(data_axis)
    axes = (shard_axes,) if isinstance(shard_axes, str) else (shard_axes or ())
    for axis in axes:
        n_valid = jax.lax.psum(n_valid, axis)
    return n_micro * n_data / jnp.maximum(n_valid, 1.0)


def select_masked_xent_sum(use_fused: bool):
    """Pick the ignore-index loss core: the XLA :func:`masked_xent_sum` or
    its fused-kernel twin. Same (sum, count) contract, identical values."""
    if use_fused:
        from .pallas_xent import fused_masked_xent_sum
        return fused_masked_xent_sum
    return masked_xent_sum


def select_xent(use_fused: bool):
    """Pick the loss implementation: the XLA formulation above, or the Pallas
    fused kernel (``ops.pallas_xent``) which never materializes the [N, V]
    log-softmax. Both compute identical values (tested)."""
    if use_fused:
        from .pallas_xent import fused_cross_entropy_loss
        return fused_cross_entropy_loss
    return cross_entropy_loss
