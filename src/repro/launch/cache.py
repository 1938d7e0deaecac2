"""JAX's persistent compilation cache, one rule for every entry point.

Entry points (the serving and training CLIs, ``chip_smoke.py``) call
:func:`enable_compile_cache` once at start-up; importing this module does
nothing.  The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and no other
  directory is set in code.
* unset: the cache lives at ``<checkout>/.jax_cache`` (listed in
  ``.gitignore``).  The path is fixed — never derived from a temporary
  name, a process id or the time — because the directory is part of what
  a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the checkout root: ``src/repro/launch/cache.py`` -> three levels up
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax

    env = os.environ.get(ENV_VAR)
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
