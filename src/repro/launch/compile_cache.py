"""Where the program's entry points keep JAX's persistent compilation cache."""
from __future__ import annotations

import os
import pathlib

import jax

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_ROOT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    it: the one ``JAX_COMPILATION_CACHE_DIR`` names when that is set (JAX
    reads the variable itself, and nothing here overrides it), else
    ``.jax_cache/`` at the checkout root. The path is fixed, never drawn
    from a temporary name, a pid or the time, so a later process finds
    what an earlier one compiled."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
