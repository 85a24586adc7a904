"""Share of the device's busy time spent in the Mamba-2 mixers outside their scan, all phases (region
``model/ssm``, set in ``models/nemotron_h.py:mixer_apply``: norm, in- and
out-projection, causal convolution, gate and group norm). Union seconds over the planes'
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
    return share_pct(run, "regions", "model/ssm")
