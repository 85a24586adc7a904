"""Programs the run compiled because the persistent cache did not have them
(JAX's ``/jax/compilation_cache/cache_misses`` events, whole run)."""

LAYER = "compile cache"
UNIT = "count"
BETTER = "lower"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(run):
    return float(run["cache_misses"])
