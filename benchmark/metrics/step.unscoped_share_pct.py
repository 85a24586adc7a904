"""Share of the device's busy time spent in ops that no region of the
program's covers (region ``unscoped``): what the naming still misses, the
compiler's own copies included. Union seconds over the planes' summed busy
seconds (``harness/scopes.py``). A place to look, not a verdict: only
``train.tokens_per_s`` says a change helped."""

LAYER = "optimizer step"
UNIT = "%"
BETTER = "lower"
MOVES = "train.tokens_per_s"
SOURCE = "device_trace"


def read(run):
    from benchmark.harness.scopes import share_pct
    return share_pct(run, "regions", "unscoped")
