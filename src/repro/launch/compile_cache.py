"""JAX's persistent compilation cache for the program's entry points.

A full-width model step compiles for tens of seconds, and a process that
restarts would pay that again. Every entry point (``chip_smoke.py``,
``launch/serve.py``, ``launch/train.py``, ``benchmarks/run.py``) calls
``enable_compile_cache()`` once, before its first compile:

* with ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads that variable,
  and this sets nothing: the cache lives there and only there;
* without it, the cache goes to ``.jax_cache/`` at the root of the
  checkout (listed in ``.gitignore``). The path is fixed so that every
  run from this checkout finds what the runs before it stored.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory it uses."""
    import jax

    env = os.environ.get(ENV_VAR)
    if env:
        return env
    DEFAULT_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
