"""Placement of JAX's persistent compilation cache.

Called from the program mains only (chip_smoke.py, bench.py,
testing/tester.py:main, examples/run_all.py) — never on
``import slate_tpu`` and never under pytest. The directory is part of
the cache key's usefulness: a path that moves never hits, so the
default is fixed by the package's own location.
"""

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"


def enable() -> str:
    """Returns the cache directory in use. With ``$JAX_COMPILATION_CACHE_DIR``
    set nothing at all is configured in code (JAX reads the variable
    itself); otherwise the cache goes to ``<checkout>/.jax_cache`` and
    every program is cached, however quick its compile: one streamed
    factorization is dozens of small programs."""
    placed = os.environ.get(ENV)
    if placed:
        return placed
    import jax
    placed = str(pathlib.Path(__file__).resolve().parents[2]
                 / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", placed)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return placed
