"""The corpus of a configuration, made on the device from the seed.

Rows are standard normal, L2-normalised in float32 on the device, one
block of :data:`BLOCK_ROWS` rows per call of one jitted program, and each
block is copied into one host array. The device never holds more than two
blocks of it; the program is then handed the host array as a deployment
hands it an index it has loaded (``DenseIndex(rows, passages,
assume_normalized=True)``) and places its own padded copy.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.traffic import rng_for

BLOCK_ROWS = 1 << 17


def jax_key(seed: int, stream: str) -> jax.Array:
    """A jax PRNG key from any whole seed (jax keys take 32 bits)."""
    return jax.random.PRNGKey(int(rng_for(seed, stream).integers(0, 1 << 31)))


@functools.partial(jax.jit, static_argnums=(2,))
def _block(key: jax.Array, index: jax.Array, dim: int) -> jax.Array:
    x = jax.random.normal(jax.random.fold_in(key, index), (BLOCK_ROWS, dim), jnp.float32)
    return x / jnp.maximum(jnp.linalg.norm(x, axis=1, keepdims=True), 1e-9)


def make_rows(rows: int, dim: int, seed: int) -> np.ndarray:
    """``(rows, dim)`` float32 unit rows on the host, made on the device."""
    key = jax_key(seed, "corpus")
    host = np.empty((rows, dim), np.float32)
    starts = list(range(0, rows, BLOCK_ROWS))
    pending = _block(key, jnp.int32(0), dim)
    for b, s in enumerate(starts):
        current = pending
        if b + 1 < len(starts):  # the next block computes while this one copies
            pending = _block(key, jnp.int32(b + 1), dim)
        e = min(s + BLOCK_ROWS, rows)
        host[s:e] = np.asarray(current)[: e - s]
        del current
    return host
