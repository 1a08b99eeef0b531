"""Where the entry points keep JAX's persistent compilation cache.

Compiling the serving programs at a published model width takes minutes on
a TPU, so the launchers (``chip_smoke.py``, ``repro.launch.serve``) keep
compiled programs across runs. The cache directory is part of every entry's
key, so it must not move between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and nothing
  else is set here;
* unset: the cache goes to ``.jax_cache/`` at the root of the checkout
  (listed in ``.gitignore``).

Library code and tests never call this: only a process's entry point
decides where its cache lives.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
