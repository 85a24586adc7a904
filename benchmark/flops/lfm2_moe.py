"""Model FLOPs per trained token of an ``lfm2_moe`` stack (gated short
convolutions and grouped-query attention over a dense gated MLP, then gated
experts without a shared expert) as ONE expert-parallel rank computes it,
from the configuration's ``sizes``. Nothing here imports the program.

The convention is ``flops/nemotron_h.py``'s:

* training = 3 x forward; recomputed operations are not counted;
* only matrix products count, 2 per multiply-add: the short convolution's two
  projections (``d -> 3d`` and ``d -> d``), attention's four projections
  (queries and the output over ``num_attention_heads`` heads, keys and values
  over ``num_key_value_heads``), its ``Q K^T`` and ``P V`` over the head width
  (a causal mask needs half, and half is counted), the gated MLPs' three
  products, the output head over the vocabulary held. Norms (the q/k norms
  too), the rotary turn, both gates and the ``conv_L_cache`` taps of the
  depthwise convolution, the router's sigmoid and top-k and the embedding
  look-up count nothing;
* an expert layer: the router at its published width and the routed experts at
  the share of a token's ``num_experts_per_tok`` choices that lands on the
  experts held here under uniform routing: ``k * held / router_width`` expert
  passes a token (4 * 8 / 32 = 1 in the benchmark's configuration; the
  program multiplies 8). There is no shared expert. What the absent ranks
  would compute is no work of this chip and is not counted.
"""

from __future__ import annotations


def head_dim(s: dict) -> int:
    return s["hidden_size"] // s["num_attention_heads"]


def shortconv_fwd_flops(s: dict) -> float:
    d = s["hidden_size"]
    return 2.0 * (d * 3 * d + d * d)


def attention_fwd_flops(s: dict, seq: int) -> float:
    d, heads, hd = s["hidden_size"], s["num_attention_heads"], head_dim(s)
    projections = 2.0 * d * hd * (2 * heads + 2 * s["num_key_value_heads"])
    causal = 0.5 * 2.0 * heads * seq * 2 * hd   # Q K^T and P V
    return projections + causal


def gated_mlp_fwd_flops(d: int, width: int) -> float:
    return 3.0 * 2.0 * d * width


def expert_fwd_flops(s: dict) -> float:
    d = s["hidden_size"]
    passes = (s["num_experts_per_tok"] * len(s["experts_held"])
              / s["router_width"])
    return (2.0 * d * s["router_width"]
            + passes * gated_mlp_fwd_flops(d, s["moe_intermediate_size"]))


def fwd_flops_per_token(s: dict, seq: int) -> float:
    per_kind = {"C": shortconv_fwd_flops(s),
                "*": attention_fwd_flops(s, seq),
                "-": gated_mlp_fwd_flops(s["hidden_size"],
                                         s["intermediate_size"]),
                "E": expert_fwd_flops(s)}
    layers = sum(per_kind[letter] for letter in s["hybrid_override_pattern"])
    return layers + 2.0 * s["hidden_size"] * s["vocab_size"]


def train_flops_per_token(s: dict, seq: int) -> float:
    return 3.0 * fwd_flops_per_token(s, seq)
