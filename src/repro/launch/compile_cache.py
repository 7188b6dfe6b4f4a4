"""Persistent compilation cache placement for the entry points.

Called by the programs a user runs (``chip_smoke.py``,
``repro.launch.im_solve``, ``repro.serve.net``, ``examples/``) and never on
library import or in tests.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and nothing is set here.  Otherwise the cache goes to
``.jax_cache/`` at the checkout root: a fixed path, so a rerun from the
same checkout finds the programs the last run compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def configure_compile_cache() -> str:
    """Turn the persistent cache on; return the directory it uses."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
