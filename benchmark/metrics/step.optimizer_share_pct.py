"""Share of the device's busy time spent in the optax update and its
application (region ``train/optimizer``, set in
``utils/train.py:make_train_step``). Union seconds over the planes' summed
busy seconds (``harness/scopes.py``). A place to look, not a verdict: only
``train.tokens_per_s`` says a change helped."""

LAYER = "optimizer step"
UNIT = "%"
BETTER = "lower"
MOVES = "train.tokens_per_s"
SOURCE = "device_trace"


def read(run):
    from benchmark.harness.scopes import share_pct
    return share_pct(run, "regions", "train/optimizer")
