"""Share of the device's busy time spent in recomputation (phase
``recompute``: ``jax.checkpoint``'s ``rematted_computation``, and the forward
that a tick executor's backward unit runs again by hand under ``pp/bwd``,
``pp/bwd_dgrad``, ``pp/wgrad``). Union seconds over the planes' summed busy
seconds (``harness/scopes.py``). A place to look, not a verdict: only
``train.tokens_per_s`` says a change helped."""

LAYER = "optimizer step"
UNIT = "%"
BETTER = "lower"
MOVES = "train.tokens_per_s"
SOURCE = "device_trace"


def read(run):
    from benchmark.harness.scopes import share_pct
    return share_pct(run, "phases", "recompute")
