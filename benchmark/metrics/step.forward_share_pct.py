"""Share of the device's busy time spent in the forward pass (phase
``forward``: ops JAX marks neither ``transpose(`` nor
``rematted_computation``, under a ``model/`` or ``pp/`` region or a bare
``jvp(``). Union seconds over the planes' summed busy seconds
(``harness/scopes.py``). A place to look, not a verdict: only
``train.tokens_per_s`` says a change helped."""

LAYER = "optimizer step"
UNIT = "%"
BETTER = "lower"
MOVES = "train.tokens_per_s"
SOURCE = "device_trace"


def read(run):
    from benchmark.harness.scopes import share_pct
    return share_pct(run, "phases", "forward")
