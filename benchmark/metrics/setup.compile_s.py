"""Seconds around ``step.lower(...).compile()``: tracing and lowering the step
(the schedule tables are built here) and compiling it, or reading it from
the persistent cache."""

LAYER = "compile cache"
UNIT = "s"
BETTER = "lower"
MOVES = "setup_s"
SOURCE = "host_clock"


def read(run):
    return run["compile_s"]
