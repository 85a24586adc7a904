"""Share of the device's busy time spent in the routed experts' two batched
matrix products, all phases (region ``model/moe_experts``,
``ops/experts.py:batched_experts``: every held expert multiplies a row a token,
the rows no assignment fills being zeros). Union seconds over the planes'
summed busy seconds (``harness/scopes.py``); nothing to read, and no metric,
where the program names no such region. A place to look, not a verdict: only
``train.tokens_per_s`` says a change helped."""

LAYER = "model"
UNIT = "%"
BETTER = "lower"
MOVES = "train.tokens_per_s"
SOURCE = "device_trace"


def read(run):
    from benchmark.harness.scopes import share_pct
    return share_pct(run, "regions", "model/moe_experts")
