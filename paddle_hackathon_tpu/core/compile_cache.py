"""Where jax's persistent compilation cache lives for this checkout.

A cold compile of the gpt2-small train step is tens of seconds on a v5e
and every fresh process pays it again, so the entry points that drive
the chip (``chip_smoke.py``, ``bench.py``, ``__graft_entry__.py``) share
one on-disk cache.  Nothing calls this at import time, and tests do not
call it (``tests/conftest.py``).

The directory is part of the cache's key, so it must not move between
runs: it is either the one the environment names or one fixed path
inside the checkout — never a temp name, a pid or a timestamp.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_compile_cache — listed in .gitignore and .chiprunignore
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory in effect.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself and this sets
    nothing in code, so a cache placed from outside is found again by the
    next process that is handed the same variable.  Unset: the fixed
    in-checkout directory."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
