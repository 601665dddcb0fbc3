"""JAX's persistent compilation cache for this repository's entry points.

The entry points (``launch/serve``, ``launch/train``, ``chip_smoke.py``) call
:func:`use_compile_cache` before their first compile; importing this module
changes nothing.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it
itself and no other path is set.  Otherwise, on an accelerator, the cache
lives in ``.jax_cache`` at the root of the checkout (listed in
``.gitignore``): a fixed path, never built from a temporary name, a PID or
the time, so every run from this checkout finds the programs earlier runs
compiled.  On the CPU backend (tests, host rehearsals) no default is set:
its programs compile in moments, and XLA:CPU warns on every cached
executable it loads.
"""
from __future__ import annotations

import pathlib
from typing import Optional

import jax

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> Optional[str]:
    """Turn on the persistent compilation cache; return its directory
    (None: no cache, on the CPU backend without the variable)."""
    path = jax.config.jax_compilation_cache_dir   # JAX_COMPILATION_CACHE_DIR
    if not path and jax.default_backend() != "cpu":
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
