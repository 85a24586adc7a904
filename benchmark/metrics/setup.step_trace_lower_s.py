"""Seconds JAX spent tracing the step function in Python and lowering it to
an MLIR module, from its own compile events (``jaxpr_trace_duration`` +
``jaxpr_to_mlir_module_duration``) for the FIRST request of the program whose
name holds ``train_step``: the part of ``setup.compile_s`` no cache can
take away. Nothing to read from a program without the recorder."""

LAYER = "compile cache"
UNIT = "s"
BETTER = "lower"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(run):
    from benchmark.harness import startup
    return startup.step_seconds(("trace_s", "lower_s"))
