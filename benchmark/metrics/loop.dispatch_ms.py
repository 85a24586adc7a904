"""Median time for the step call to return in the untraced window: the host's
cost of handing the device one program."""

LAYER = "train loop"
UNIT = "ms"
BETTER = "lower"
MOVES = "train.tokens_per_s"
SOURCE = "host_clock"


def read(run):
    import statistics
    calls = run["spans"]["dispatch"]
    return statistics.median(calls) * 1e3 if calls else None
