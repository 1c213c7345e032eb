"""Where the entry points keep JAX's persistent compilation cache.

A run finds only what earlier runs wrote to the same directory, so the
directory never moves between runs: ``$JAX_COMPILATION_CACHE_DIR`` when
the environment sets it, otherwise the fixed ``<checkout>/.jax_cache``
(git-ignored; the test suite's ``conftest.py`` uses the same default).
"""

from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Point the persistent compilation cache at its directory (see the
    module notes) and return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
