"""Pallas kernels: ``ops`` holds the jit'd entry points, ``ref`` the
pure-jnp oracles each kernel is tested against."""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """The Pallas ``interpret`` flag of every kernel in this package: as
    given, else True exactly when JAX's default backend is the CPU. This
    is the one place the choice is made, so on an accelerator every kernel
    compiles for it, and on the CPU it runs in the Pallas interpreter."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)
