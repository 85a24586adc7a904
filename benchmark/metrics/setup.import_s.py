"""Seconds the program's package took to import, top to bottom of its
``__init__.py`` (host span ``setup/import``): with ``jax`` not loaded before
it, as in ``benchmark/run.py``, that holds ``import jax``. Logs every host
span the program's recorder kept, with what ran inside what, and the
recorder's own cost. Nothing to read from a program without the recorder."""

LAYER = "start-up"
UNIT = "s"
BETTER = "lower"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    from benchmark.harness import startup
    value = startup.span_seconds("setup/import")
    if value is not None:
        startup.log_spans(run["log"])
    return value
