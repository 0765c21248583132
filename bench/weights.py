"""Random weights from a seed, made on the device in one jitted call.

The tree has the program's parameter layout (leaf names and shapes are read
from its schema); the values and their scales are this module's own, so the
reference and the program are handed the same arrays and neither makes
them. Every leaf name must be in ``DEFAULT_LEAVES`` or in the table the
configuration's family adds (its ``LEAVES``): a new kind of leaf fails here
rather than being drawn at a guessed scale.
"""
from __future__ import annotations

import zlib
from typing import Any, Dict, Mapping, Tuple

import jax
import jax.numpy as jnp

# the leaves of every decoder the program builds, outside its attention
DEFAULT_LEAVES = {"tok": "embedding", "unembed": "matrix",
                  "final_ln": "norm", "ln1": "norm", "ln2": "norm",
                  "w_up": "matrix", "w_gate": "matrix", "w_down": "matrix"}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole seed, also one above 32 bits."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def _leaf(key, path: Tuple[str, ...], shape, kinds: Mapping[str, str],
          dtype):
    kind = kinds.get(path[-1])
    k = jax.random.fold_in(key, zlib.crc32("/".join(path).encode()) % (2**31))
    z = jax.random.normal(k, shape, jnp.float32)
    if kind == "norm":
        v = 1.0 + 0.1 * z
    elif kind == "bias":
        v = 0.1 * z
    elif kind == "embedding":
        v = z
    elif kind == "matrix":
        # (in, out), stacked (L, in, out) or experts' (L, E, in, out)
        v = z / jnp.sqrt(jnp.float32(shape[-2]))
    else:
        raise KeyError(f"no rule for parameter leaf {'/'.join(path)!r} "
                       f"(kind {kind!r})")
    return v.astype(dtype)


def make(shapes: Dict[str, Any], seed: int, leaves: Mapping[str, str],
         dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Weights for ``shapes`` (a tree of ``jax.ShapeDtypeStruct`` in the
    program's layout), each leaf drawn by its kind in ``leaves`` (the
    family's) or else in ``DEFAULT_LEAVES``."""
    kinds = {**DEFAULT_LEAVES, **leaves}

    def build(key):
        def walk(tree, path):
            if isinstance(tree, dict):
                return {k: walk(v, path + (k,)) for k, v in tree.items()}
            return _leaf(key, path, tree.shape, kinds, dtype)
        return walk(shapes, ())

    return jax.jit(build)(seed_key(seed))
