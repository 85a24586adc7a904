"""Model FLOPs per trained token of a dense decoder, from its published
sizes. Nothing here imports the program.

The convention (the same as ``analysis/cost_model.py``'s, so the numbers
agree with what the repo has printed before):

* forward = ``2 N + 2 L dim seq``; training = 3 x forward =
  ``6 N + 6 L dim seq`` (backward costs twice the forward);
* ``N`` is every parameter but the token and position embedding tables —
  the matrices of the layers and of the untied output head, and their
  biases and norm scales (0.08% of N; kept so that ``6 N`` is the usual
  non-embedding count of Kaplan et al. 2020). An embedding look-up is no
  matrix multiplication and counts nothing;
* attention scores and their use, ``Q K^T`` and ``P V``, are
  ``2 * 2 * dim * seq`` a token a layer unmasked; a causal mask needs half
  of them, and only that half is counted;
* recomputed operations (a rematerialising backward, checkpointed
  activations) are not counted: utilisation is against what the
  mathematics needs, not against what the program chose to do.
"""

from __future__ import annotations


def gpt2_sizes(sizes: dict) -> dict:
    """dim, layers, ffn, vocab from a GPT-2 ``config.json``."""
    dim = sizes["n_embd"]
    return {"dim": dim, "layers": sizes["n_layer"],
            "ffn": sizes.get("n_inner") or 4 * dim,
            "vocab": sizes["vocab_size"]}


def non_embedding_params(sizes: dict) -> int:
    """GPT-2 block: LN, q/k/v/o with biases, LN, two MLP matrices with
    biases; then the final LN and the untied, bias-free output matrix."""
    s = gpt2_sizes(sizes)
    dim, ffn = s["dim"], s["ffn"]
    attn = 4 * (dim * dim + dim)
    mlp = dim * ffn + ffn + ffn * dim + dim
    norms = 2 * 2 * dim
    return s["layers"] * (attn + mlp + norms) + 2 * dim + dim * s["vocab"]


def fwd_flops_per_token(sizes: dict, seq: int) -> float:
    s = gpt2_sizes(sizes)
    causal_attention = 0.5 * 2 * 2 * s["layers"] * s["dim"] * seq
    return 2.0 * non_embedding_params(sizes) + causal_attention


def train_flops_per_token(sizes: dict, seq: int) -> float:
    return 3.0 * fwd_flops_per_token(sizes, seq)
