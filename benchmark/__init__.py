"""The repo's benchmark: ``BENCHMARK.json`` at the root names what is here.

``run.py`` is the one command. Everything that belongs to one configuration,
one cell or one per-layer metric is a file of its own, found by its name in
``BENCHMARK.json`` — ``README.md`` has the recipe for adding one.
"""
