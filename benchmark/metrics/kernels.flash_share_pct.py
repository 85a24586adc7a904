"""Device time of the flash-attention forward and backward Pallas calls over
the device's busy time, all planes together."""

LAYER = "kernels"
UNIT = "%"
BETTER = "lower"
MOVES = "train.tokens_per_s"
SOURCE = "device_trace"


FLASH = r"_flash_(fwd|bwd)_kernel"


def read(run):
    from benchmark.harness.trace_reduce import label_seconds
    trace = run["trace"]
    if trace is None:
        return None
    seconds, calls = label_seconds(trace, FLASH)
    busy = sum(p["busy_s"] for p in trace["planes"])
    return 100.0 * seconds / busy if calls and busy else None
