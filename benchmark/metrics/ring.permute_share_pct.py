"""Share of the step window a stage spends inside ``collective-permute`` ops
(union of their intervals), mean over the stages: transfer and waiting for
the neighbour together."""

LAYER = "ring hops"
UNIT = "%"
BETTER = "lower"
MOVES = "train.tokens_per_s"
SOURCE = "device_trace"


def read(run):
    trace = run["trace"]
    if trace is None or len(trace["planes"]) < 2:
        return None
    shares = [p["permute_s"] / p["window_s"] for p in trace["planes"]]
    return 100.0 * sum(shares) / len(shares)
