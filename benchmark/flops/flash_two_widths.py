"""What one call of the causal flash-attention kernels needs when queries
and keys are ``qk_dim`` wide and values ``v_dim`` (latent attention: 192 and
128), from its shapes ``(rows, seq, heads, qk_dim, v_dim)`` and the bytes of
an element. ``flops/flash.py`` is the one-width count, and this one equals it
where the widths are equal.

Forward: ``S = Q K^T`` over ``qk_dim`` and ``O = P V`` over ``v_dim``, of
which a causal mask needs half. Reads Q, K (``qk_dim``) and V (``v_dim``);
writes O (``v_dim``) and the row log-sum-exp (float32).

Backward, given Q, K, V, O, dO and the log-sum-exp: ``S`` again, ``dQ = dS
K`` and ``dK = dS^T Q`` over ``qk_dim``; ``dP = dO V^T`` and ``dV = P^T dO``
over ``v_dim`` — three products at one width and two at the other, half of
each under the mask. Reads Q, K, V, O, dO, lse; writes dQ, dK (``qk_dim``)
and dV (``v_dim``).

The exponentials and the running maxima are VPU/EUP work that the MXU peak
does not describe; they are not counted, so a share of this roofline cannot
pass 100% through them.
"""

from __future__ import annotations


def _product_flops(rows: int, seq: int, heads: int, width: int,
                   causal: bool) -> float:
    full = 2.0 * rows * heads * seq * seq * width
    return 0.5 * full if causal else full


def fwd(rows: int, seq: int, heads: int, qk_dim: int, v_dim: int,
        causal: bool = True, itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one forward call."""
    element = rows * seq * heads * itemsize
    lse = rows * heads * seq * 4
    return (_product_flops(rows, seq, heads, qk_dim, causal)
            + _product_flops(rows, seq, heads, v_dim, causal),
            element * (2 * qk_dim + 2 * v_dim) + lse)


def bwd(rows: int, seq: int, heads: int, qk_dim: int, v_dim: int,
        causal: bool = True, itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one backward call."""
    element = rows * seq * heads * itemsize
    lse = rows * heads * seq * 4
    return (3 * _product_flops(rows, seq, heads, qk_dim, causal)
            + 2 * _product_flops(rows, seq, heads, v_dim, causal),
            element * (4 * qk_dim + 4 * v_dim) + lse)
