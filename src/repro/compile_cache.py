"""JAX's persistent compilation cache, kept at one fixed place.

Entry points (``main()`` of the CLIs, ``chip_smoke.py``) call
:func:`enable` before their first compile; importing this module changes
nothing. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and this module sets nothing. Otherwise the cache lives at
``<checkout>/.jax_cache``, a fixed path, so that a later process finds
what an earlier one compiled.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
