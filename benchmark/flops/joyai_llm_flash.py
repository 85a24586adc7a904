"""Model FLOPs per trained token of a ``joyai_llm_flash`` stack (latent
attention in every block; a dense gated MLP, then gated experts) as ONE
expert-parallel rank computes it, from the configuration's ``sizes``.
Nothing here imports the program.

The convention is ``flops/nemotron_h.py``'s:

* training = 3 x forward; recomputed operations are not counted;
* only matrix products count, 2 per multiply-add: latent attention's five
  projections (both down-projections, both up-projections, the output),
  its ``Q K^T`` over ``qk_nope_head_dim + qk_rope_head_dim`` columns and
  ``P V`` over ``v_head_dim`` (a causal mask needs half, and half is
  counted), the gated MLPs' three products, the output head over the
  vocabulary held. Norms, the rotary turn, gates, the router's sigmoid and
  top-k and the embedding look-up count nothing;
* an expert layer: the router at its published width, the shared experts
  whole, and the routed experts at the share of a token's
  ``num_experts_per_tok`` choices that lands on the experts held here under
  uniform routing: ``k * held / router_width`` expert passes a token (8 * 8 /
  256 = 0.25 in the benchmark's configuration; the program multiplies 8).
  What the absent ranks would compute is no work of this chip and is not
  counted.
"""

from __future__ import annotations


def mla_fwd_flops(s: dict, seq: int) -> float:
    d, heads = s["hidden_size"], s["num_attention_heads"]
    qk = s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
    projections = 2.0 * (
        d * s["q_lora_rank"] + s["q_lora_rank"] * heads * qk
        + d * (s["kv_lora_rank"] + s["qk_rope_head_dim"])
        + s["kv_lora_rank"] * heads * (s["qk_nope_head_dim"] + s["v_head_dim"])
        + heads * s["v_head_dim"] * d)
    causal = 0.5 * 2.0 * heads * seq * (qk + s["v_head_dim"])  # Q K^T, P V
    return projections + causal


def gated_mlp_fwd_flops(d: int, width: int) -> float:
    return 3.0 * 2.0 * d * width


def expert_fwd_flops(s: dict) -> float:
    d, width = s["hidden_size"], s["moe_intermediate_size"]
    router = 2.0 * d * s["router_width"]
    shared = s["n_shared_experts"] * gated_mlp_fwd_flops(d, width)
    passes = (s["num_experts_per_tok"] * len(s["experts_held"])
              / s["router_width"])
    return router + shared + passes * gated_mlp_fwd_flops(d, width)


def fwd_flops_per_token(s: dict, seq: int) -> float:
    per_kind = {"L": mla_fwd_flops(s, seq),
                "-": gated_mlp_fwd_flops(s["hidden_size"],
                                         s["intermediate_size"]),
                "E": expert_fwd_flops(s)}
    layers = sum(per_kind[letter] for letter in s["hybrid_override_pattern"])
    return layers + 2.0 * s["hidden_size"] * s["vocab_size"]


def train_flops_per_token(s: dict, seq: int) -> float:
    return 3.0 * fwd_flops_per_token(s, seq)
