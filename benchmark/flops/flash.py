"""What one call of the causal flash-attention kernels needs, from its
shapes: ``(rows, seq, heads, head_dim)`` and the bytes of an element.

Forward: ``S = Q K^T`` and ``O = P V`` — two matrix products of
``2 * seq * seq * head_dim`` a head, of which a causal mask needs half.
Reads Q, K, V; writes O and the row log-sum-exp (float32).

Backward, given Q, K, V, O, dO and the log-sum-exp: the probabilities are
not an input, so ``S`` is formed again, then ``dV = P^T dO``,
``dP = dO V^T``, ``dQ = dS K``, ``dK = dS^T Q`` — five products, half of
each under the mask. Reads Q, K, V, O, dO, lse; writes dQ, dK, dV.

The exponentials and the running maxima are VPU/EUP work that the MXU peak
does not describe; they are not counted, so a share of this roofline cannot
pass 100% through them.
"""

from __future__ import annotations


def _matmul_flops(rows: int, seq: int, heads: int, head_dim: int,
                  causal: bool) -> float:
    full = 2.0 * rows * heads * seq * seq * head_dim
    return 0.5 * full if causal else full


def fwd(rows: int, seq: int, heads: int, head_dim: int, causal: bool = True,
        itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one forward call."""
    tensor = rows * seq * heads * head_dim * itemsize
    lse = rows * heads * seq * 4
    return (2 * _matmul_flops(rows, seq, heads, head_dim, causal),
            4 * tensor + lse)


def bwd(rows: int, seq: int, heads: int, head_dim: int, causal: bool = True,
        itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one backward call."""
    tensor = rows * seq * heads * head_dim * itemsize
    lse = rows * heads * seq * 4
    return (5 * _matmul_flops(rows, seq, heads, head_dim, causal),
            8 * tensor + lse)


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak sets it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
