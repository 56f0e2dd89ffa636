"""Where XLA's persistent compilation cache lives: one rule, one place.

Every entry point that compiles for the chip (``benchmarks/run.py``,
``chip_smoke.py``, ``__graft_entry__``'s ``__main__``) calls
:func:`configure_compile_cache` before its first compile.  The
directory is part of the cache key, so it must not move between runs:
no temp names, pids or times in it.
"""

from __future__ import annotations

import os

import jax

# <checkout>/.xla_cache (listed in .gitignore).
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".xla_cache",
)


def configure_compile_cache() -> str:
    """Returns the cache directory in effect.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing
    is set in code; otherwise the fixed in-checkout directory."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
