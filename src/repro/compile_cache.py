"""Persistent compilation cache for the repo's entry points.

``use_compile_cache()`` is called by ``chip_smoke.py``,
``python -m repro.launch.solve``, ``python -m benchmarks.run`` and
``python -m repro.obs`` before their first compile — never on
``import repro``, so library users and the tests keep JAX's own
settings.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; no other
  directory is set here.
* Otherwise the cache lives at ``.jax_cache/`` in the checkout (listed
  in ``.gitignore``).  The path is fixed, so the next run finds what
  this one wrote; a directory named after a pid, a time or a temporary
  name would never be read again.

Either way every compiled program is written, however short its
compile: the size and compile-time floors are set to zero.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
