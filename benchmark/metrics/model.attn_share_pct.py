"""Share of the device's busy time spent in the attention blocks, all phases
(region ``model/attn``, set in ``models/transformer.py:layer_apply``: norm,
qkv, attention or the flash call, output projection). Union seconds over the
planes' summed busy seconds (``harness/scopes.py``). A place to look, not a
verdict: only ``train.tokens_per_s`` says a change helped."""

LAYER = "model"
UNIT = "%"
BETTER = "lower"
MOVES = "train.tokens_per_s"
SOURCE = "device_trace"


def read(run):
    from benchmark.harness.scopes import share_pct
    return share_pct(run, "regions", "model/attn")
