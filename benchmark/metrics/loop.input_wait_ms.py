"""Median time a step of the untraced window spent in ``next(data)``: what the
loop waits for the input pipeline (``utils/data.py:prefetch_to_device``)."""

LAYER = "train loop"
UNIT = "ms"
BETTER = "lower"
MOVES = "train.tokens_per_s"
SOURCE = "host_clock"


def read(run):
    import statistics
    waits = run["spans"]["input_wait"]
    return statistics.median(waits) * 1e3 if waits else None
