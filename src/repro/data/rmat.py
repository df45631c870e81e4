"""Graph500-style R-MAT power-law edge-stream generator (paper Section IV:
"simulated Graph500.org R-Mat power-law network data", 100 M connections
inserted in groups of 100 K).

Fully vectorized in JAX: per scale-bit quadrant sampling.  The stream API
yields fixed-size groups device-side so benchmarks measure *update* cost,
not host data movement.
"""
from __future__ import annotations

import functools
from typing import Iterator, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("n_edges", "scale", "a", "b", "c"))
def rmat_edges(
    key,
    n_edges: int,
    scale: int,
    a: float = 0.57,
    b: float = 0.19,
    c: float = 0.19,
) -> Tuple[jax.Array, jax.Array]:
    """Sample ``n_edges`` edges of a 2**scale-vertex R-MAT graph.

    Returns (src, dst) int32 arrays.  Quadrant probabilities (a, b, c, d)
    follow Graph500 (d = 1 - a - b - c = 0.05).
    """
    src = jnp.zeros((n_edges,), jnp.int32)
    dst = jnp.zeros((n_edges,), jnp.int32)
    for bit in range(scale):
        key, sub = jax.random.split(key)
        r = jax.random.uniform(sub, (n_edges,))
        src_bit = (r >= a + b).astype(jnp.int32)  # quadrants c, d
        dst_bit = (((r >= a) & (r < a + b)) | (r >= a + b + c)).astype(jnp.int32)
        src = src * 2 + src_bit
        dst = dst * 2 + dst_bit
    return src, dst


def rmat_edges_np(
    rng: np.random.Generator,
    n_edges: int,
    scale: int,
    a: float = 0.57,
    b: float = 0.19,
    c: float = 0.19,
) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side :func:`rmat_edges`: the same quadrant sampling in numpy.

    For load generators that must stay off the accelerator (a process that
    touches JAX on a TPU host claims the chip).  Returns (src, dst) int32.
    """
    r = rng.random((scale, n_edges))
    src_bits = r >= a + b  # quadrants c, d
    dst_bits = ((r >= a) & (r < a + b)) | (r >= a + b + c)
    weights = (1 << np.arange(scale - 1, -1, -1, dtype=np.int64))[:, None]
    src = (src_bits * weights).sum(axis=0).astype(np.int32)
    dst = (dst_bits * weights).sum(axis=0).astype(np.int32)
    return src, dst


def edge_stream(
    seed: int,
    total_edges: int,
    group_size: int,
    scale: int,
    a: float = 0.57,
    b: float = 0.19,
    c: float = 0.19,
) -> Iterator[Tuple[jax.Array, jax.Array, jax.Array]]:
    """Yield ``total_edges // group_size`` groups of (src, dst, val=1)."""
    key = jax.random.PRNGKey(seed)
    n_groups = total_edges // group_size
    for g in range(n_groups):
        key, sub = jax.random.split(key)
        s, d = rmat_edges(sub, group_size, scale, a, b, c)
        yield s, d, jnp.ones((group_size,), jnp.float32)


def stream_tensor(
    seed: int, n_groups: int, group_size: int, scale: int, **kw
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Materialize a [n_groups, group_size] stream for lax.scan ingestion."""
    key = jax.random.PRNGKey(seed)
    keys = jax.random.split(key, n_groups)
    gen = jax.vmap(lambda k: rmat_edges(k, group_size, scale, **kw))
    src, dst = gen(keys)
    return src, dst, jnp.ones((n_groups, group_size), jnp.float32)
