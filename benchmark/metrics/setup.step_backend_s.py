"""Seconds of JAX's ``backend_compile_duration`` event for the FIRST request
of the program whose name holds ``train_step``: XLA compiling the step, or,
on a hit, the persistent cache reading it back and loading it (the event
brackets both on this JAX; the runner's log says which). Nothing to read from
a program without the recorder."""

LAYER = "compile cache"
UNIT = "s"
BETTER = "lower"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(run):
    from benchmark.harness import startup
    return startup.step_seconds(("backend_s",))
