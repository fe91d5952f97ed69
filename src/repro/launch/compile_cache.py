"""JAX's persistent compilation cache for the command-line entry points.

:func:`enable` is called by the ``repro.launch.serve`` and
``repro.launch.train`` mains and by ``chip_smoke.py``, never on import.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is set here.  Otherwise the cache lives in ``.jax_cache/`` at the
checkout root: a fixed path, because a cache that moves is never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["ENV_VAR", "DEFAULT_DIR", "enable"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on and return the directory it uses."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
