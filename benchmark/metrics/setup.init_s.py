"""Seconds the host spent in ``train.init_params`` and
``train.init_opt_state`` (host spans ``setup/init_params`` +
``setup/init_opt_state``, every call of the run): getting the two init
programs and enqueueing them, not the device's work. A runner that builds
the weights twice (``train_layerwise``'s check, then the run) counts both.
Nothing to read from a program without the recorder."""

LAYER = "start-up"
UNIT = "s"
BETTER = "lower"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    from benchmark.harness import startup
    return startup.span_seconds("setup/init_params", "setup/init_opt_state")
