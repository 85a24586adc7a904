"""Model FLOPs per trained token of a ``nemotron_h`` stack as ONE
expert-parallel rank computes it, from the configuration's ``sizes``.
Nothing here imports the program.

The convention (beside ``model.py``'s for a dense decoder):

* training = 3 x forward; recomputed operations are not counted;
* only matrix products count, 2 per multiply-add: projections, attention's
  ``Q K^T`` and ``P V`` (a causal mask needs half, and half is counted), the
  output head over the vocabulary held. Norms, the depthwise convolution,
  gates, the router's sigmoid and top-k and the embedding look-up count
  nothing;
* a Mamba-2 layer's recurrence is counted in the chunked matrix form at the
  source's ``chunk_size`` Q: per token and head ``C B^T`` (per group, half
  under the causal mask inside a chunk), its product with ``x`` (half), the
  chunk's own state ``B^T x`` and the entering state's ``C S``;
* an expert layer: the router at its published width, the shared expert
  whole, and the routed experts at the share of a token's
  ``num_experts_per_tok`` choices that lands on the experts held here under
  uniform routing: ``k * held / router_width`` expert passes a token (6 * 8 /
  128 = 0.375 in the benchmark's configuration). What the absent ranks would
  compute is no work of this chip and is not counted.
"""

from __future__ import annotations


def mamba_fwd_flops(s: dict) -> float:
    d, H, P = s["hidden_size"], s["mamba_num_heads"], s["mamba_head_dim"]
    G, N, Q = s["n_groups"], s["ssm_state_size"], s["chunk_size"]
    d_inner = H * P
    in_proj = 2.0 * d * (2 * d_inner + 2 * G * N + H)
    out_proj = 2.0 * d_inner * d
    scores = 0.5 * 2.0 * G * Q * N          # C B^T inside the chunk, causal
    mix = 0.5 * 2.0 * H * Q * P             # (C B^T . decay) x, causal
    own_state = 2.0 * H * P * N             # B^T x at the chunk's end
    entering = 2.0 * H * P * N              # C S for the entering state
    return in_proj + out_proj + scores + mix + own_state + entering


def attention_fwd_flops(s: dict, seq: int) -> float:
    d, heads, kv = s["hidden_size"], s["num_attention_heads"], s["num_key_value_heads"]
    hd = s["head_dim"]
    projections = 2.0 * d * hd * (2 * heads + 2 * kv)   # q, o; k, v
    causal = 0.5 * 2.0 * 2.0 * heads * hd * seq          # Q K^T and P V
    return projections + causal


def expert_fwd_flops(s: dict) -> float:
    d = s["hidden_size"]
    router = 2.0 * d * s["router_width"]
    shared = 2.0 * 2.0 * d * s["moe_shared_expert_intermediate_size"]
    passes = (s["num_experts_per_tok"] * len(s["experts_held"])
              / s["router_width"])
    routed = passes * 2.0 * 2.0 * d * s["moe_intermediate_size"]
    return router + shared + routed


def fwd_flops_per_token(s: dict, seq: int) -> float:
    per_kind = {"M": mamba_fwd_flops(s), "*": attention_fwd_flops(s, seq),
                "E": expert_fwd_flops(s)}
    layers = sum(per_kind[letter] for letter in s["hybrid_override_pattern"])
    return layers + 2.0 * s["hidden_size"] * s["vocab_size"]


def train_flops_per_token(s: dict, seq: int) -> float:
    return 3.0 * fwd_flops_per_token(s, seq)
