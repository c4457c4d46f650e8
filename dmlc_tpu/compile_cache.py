"""Where JAX's persistent compilation cache lives.

One rule for every entry point that compiles for the chip
(``bin/dmlc-serve``, ``examples/train_lm_recordio.py``, ``bench.py``,
each ``chip_smoke.py`` child): whoever runs the program may place the
cache with ``JAX_COMPILATION_CACHE_DIR`` — JAX reads that variable
itself and this module then sets nothing; otherwise the cache is
``<checkout>/.jax_cache``.  The path is part of the cache key, so it is
fixed: never a temp dir, a pid or a timestamp, or no second process
would ever hit.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def place_compile_cache() -> str:
    """Point JAX at the persistent cache (call before the first
    compile); returns the directory in use."""
    placed = os.environ.get(ENV)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
