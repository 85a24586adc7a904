"""What the chip's compiler counts for the step program on one device:
argument + output - alias + temp (``memory_analysis()`` of the program that
runs). ``memory_stats()``' peak leaves temps out on this runtime, so this is
the number that says whether a program fits."""

LAYER = "optimizer step"
UNIT = "GB"
BETTER = "lower"
MOVES = "train.tokens_per_s"
SOURCE = "program_counter"


def read(run):
    m = run["compiled_memory"]
    return (m["argument"] + m["output"] - m["alias"] + m["temp"]) / 1e9
