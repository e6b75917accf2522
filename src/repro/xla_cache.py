"""Where JAX keeps its persistent compilation cache.

The entry points (``launch/train.py``, ``launch/serve.py``, ``chip_smoke.py``)
call ``enable_persistent_cache()`` before their first compile:

* with ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads the variable itself and
  nothing is set here;
* otherwise the cache goes to ``<checkout>/.jax_cache``. The path is fixed
  (no temporary name, pid or time in it) because it is part of what a later
  run must find again; ``.gitignore`` lists it.

Tests do not enable the cache.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Optional, Tuple

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def resolve_cache_dir(environ: Optional[Mapping[str, str]] = None
                      ) -> Tuple[str, bool]:
    """(directory, set_by_environment) for this process."""
    env = os.environ if environ is None else environ
    if env.get(ENV_VAR):
        return env[ENV_VAR], True
    return str(CHECKOUT_CACHE_DIR), False


def enable_persistent_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    path, from_env = resolve_cache_dir()
    if not from_env:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
