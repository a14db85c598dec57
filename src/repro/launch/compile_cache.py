"""The one place that decides where JAX's persistent compilation cache
lives, shared by every entry point (``chip_smoke.py``, the launchers and
the benches).

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; nothing is
  set in code.
* otherwise — ``<checkout>/.jax_cache`` (git-ignored).  The path is part
  of the cache key, so it is fixed: never a temp name, pid or time.

Call :func:`enable` from an entry point's ``main``, never at import.
"""

from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> None:
    """Turn the persistent cache on (see the module docstring)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
