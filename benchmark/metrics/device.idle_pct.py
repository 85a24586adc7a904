"""Share of the traced window in which no op ran on a device: 1 - union(all op
intervals) / window, mean over the device planes."""

LAYER = "device"
UNIT = "%"
BETTER = "lower"
MOVES = "train.tokens_per_s"
SOURCE = "device_trace"


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
