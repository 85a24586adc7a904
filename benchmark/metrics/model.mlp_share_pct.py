"""Share of the device's busy time spent in the MLP blocks, all phases (region
``model/mlp``, set in ``models/transformer.py:mlp_block``: norm, two matmuls,
activation). Union seconds over the planes' summed busy seconds
(``harness/scopes.py``). A place to look, not a verdict: only
``train.tokens_per_s`` says a change helped."""

LAYER = "model"
UNIT = "%"
BETTER = "lower"
MOVES = "train.tokens_per_s"
SOURCE = "device_trace"


def read(run):
    from benchmark.harness.scopes import share_pct
    return share_pct(run, "regions", "model/mlp")
