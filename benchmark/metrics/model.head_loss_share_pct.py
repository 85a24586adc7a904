"""Share of the device's busy time spent in the final norm, the output head
and the cross-entropy, all phases (region ``model/head_loss``, set in
``models/transformer.py:head_apply`` and around the loss, ``pallas_xent``
included). Union seconds over the planes' summed busy seconds
(``harness/scopes.py``). A place to look, not a verdict: only
``train.tokens_per_s`` says a change helped."""

LAYER = "model"
UNIT = "%"
BETTER = "lower"
MOVES = "train.tokens_per_s"
SOURCE = "device_trace"


def read(run):
    from benchmark.harness.scopes import share_pct
    return share_pct(run, "regions", "model/head_loss")
