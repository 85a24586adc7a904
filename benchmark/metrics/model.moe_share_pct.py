"""Share of the device's busy time spent in the expert layers outside the routed experts' products, all
phases (region ``model/moe``, set in ``models/nemotron_h.py:mixer_apply``: norm,
router, top-k, sort and gather into the assignment buffer, combine, the shared
expert). Union seconds over the planes'
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
    return share_pct(run, "regions", "model/moe")
