"""Largest share of the step window in which a stage computed nothing: per
device plane, 1 - union(ops that are no collective) / window. Time inside a
``collective-permute`` counts as idle: every tick ends in two permutes, so a
stage with an empty cell waits inside the collective. The runner's log has
every stage's share."""

LAYER = "pipeline executor"
UNIT = "%"
BETTER = "lower"
MOVES = "train.tokens_per_s"
SOURCE = "device_trace"


def read(run):
    trace = run["trace"]
    if trace is None or len(trace["planes"]) < 2:
        return None
    return max(100.0 * (1.0 - p["compute_s"] / p["window_s"])
               for p in trace["planes"])
