"""JAX's persistent compilation cache, placed from outside.

Whoever runs the program says where compiled code is kept, by setting
``JAX_COMPILATION_CACHE_DIR``: JAX reads that variable itself, so then
nothing is set here.  Unset, the cache goes to one fixed directory inside
the checkout — the path is part of the cache's key, so a directory named
after a pid, a time or a temp dir would never hit.

Called by the programs (``chip_smoke.py``, ``benchmark/run.py``, the train
and serve CLIs, the worker) before their first compile, never by a library
module and never by ``tests/conftest.py``.
"""

from __future__ import annotations

import os

#: the fixed place, listed in ``.gitignore``
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory it uses."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
