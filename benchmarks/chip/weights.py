"""Initial weights from the seed, leaf by leaf, for the program and the reference.

Each leaf is drawn from its own key, ``fold_in(root_key(seed), crc32(name))``,
so any one leaf can be made again alone, on any device, with the same values.
The harness fills the program's parameter tree with these leaves in one jitted
call; the reference makes the same leaves by name.  Neither takes weights from
the other.

Distributions follow the usual initialisation of a llama-style decoder:
RMSNorm weights are ones, the embedding is a truncated normal of scale 0.02,
and every other matrix a truncated normal of scale ``fan_in ** -0.5``, where
``fan_in`` is the next-to-last dimension.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

NORMS = ("ln1", "ln2", "final_norm")


def root_key(seed: int):
    """The key of a run.  Seeds up to 2**64 - 1 keep all their bits."""
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def leaf(key, name: str, shape: tuple, dtype):
    """Initial value of the leaf ``name`` (a ``/``-joined path)."""
    last = name.rsplit("/", 1)[-1]
    if last in NORMS:
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, np.uint32(zlib.crc32(name.encode())))
    scale = 0.02 if last == "embed" else shape[-2] ** -0.5
    draw = jax.random.truncated_normal(k, -3.0, 3.0, shape, jnp.float32)
    return (draw * scale).astype(dtype)
