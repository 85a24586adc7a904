"""Where JAX's persistent compilation cache lives for this repo's entry points.

Every run on the chip machine starts with no compiled code, and a whole
train step takes tens of seconds to compile, so the entry points
(``scripts/*.py``, ``chip_smoke.py``) call
:func:`enable_compile_cache` before their first compile. It is NOT called
on package import and not by the test suite: a persistent cache crashed
XLA:CPU under full-suite volume (tests/conftest.py).
"""

from __future__ import annotations

import os

import jax

# A fixed path inside the checkout: the directory is part of every cache
# key's lookup, so one built from a temp name, a pid or the time never hits.
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    nothing is set in code — whoever runs the program places the cache.
    Otherwise it is ``<repo>/.jax_cache`` (in ``.gitignore``)."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
