"""Batched Lloyd's k-means in pure JAX.

Used for PQ codebook training (vmapped over sub-spaces) and for IVF coarse
centroids. Fully jit-able: fixed iteration count, dead clusters re-seeded
deterministically from the data.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp


class KMeansResult(NamedTuple):
    centroids: jax.Array  # (k, d)
    assignments: jax.Array  # (n,) int32
    inertia: jax.Array  # () float32 — sum of squared distances


def pairwise_sqdist(x: jax.Array, c: jax.Array,
                    precision: jax.lax.Precision | None = None) -> jax.Array:
    """Squared L2 distances (n, k) between rows of x (n, d) and c (k, d).

    Uses the ||x||^2 - 2 x.c + ||c||^2 expansion so the inner term is a
    single matmul (MXU-friendly on TPU). ``precision`` is the matmul's: the
    default may run bf16 passes on a TPU, which is fine for k-means and
    probe selection; exact ground truth asks for ``Precision.HIGHEST``.
    """
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)  # (n, 1)
    c2 = jnp.sum(c * c, axis=-1)  # (k,)
    # clamp: the expansion can go slightly negative in float32
    d = x2 - 2.0 * jnp.matmul(x, c.T, precision=precision) + c2[None, :]
    return jnp.maximum(d, 0.0)


def _assign(x: jax.Array, c: jax.Array) -> tuple[jax.Array, jax.Array]:
    d = pairwise_sqdist(x, c)
    a = jnp.argmin(d, axis=-1).astype(jnp.int32)
    return a, jnp.min(d, axis=-1)


def _update(x: jax.Array, a: jax.Array, k: int, key: jax.Array) -> jax.Array:
    """Mean per cluster; empty clusters re-seeded from random data points."""
    n = x.shape[0]
    counts = jax.ops.segment_sum(jnp.ones((n,), x.dtype), a, num_segments=k)
    sums = jax.ops.segment_sum(x, a, num_segments=k)
    means = sums / jnp.maximum(counts, 1.0)[:, None]
    # deterministic re-seed for empty clusters
    reseed_idx = jax.random.randint(key, (k,), 0, n)
    reseed = x[reseed_idx]
    return jnp.where(counts[:, None] > 0, means, reseed)


@functools.partial(jax.jit, static_argnames=("k", "iters"))
def kmeans(key: jax.Array, x: jax.Array, k: int, iters: int = 25) -> KMeansResult:
    """Lloyd's algorithm. x: (n, d) float32. Returns KMeansResult."""
    n = x.shape[0]
    init_key, *iter_keys = jax.random.split(key, iters + 1)
    # k-means|| style cheap init: random distinct-ish sample
    perm = jax.random.permutation(init_key, n)[:k]
    c0 = x[perm]

    def body(c, it_key):
        a, _ = _assign(x, c)
        c = _update(x, a, k, it_key)
        return c, None

    c, _ = jax.lax.scan(body, c0, jnp.stack(iter_keys))
    a, dmin = _assign(x, c)
    return KMeansResult(centroids=c, assignments=a, inertia=jnp.sum(dmin))


@functools.partial(jax.jit, static_argnames=("k", "iters"))
def kmeans_multi(key: jax.Array, x: jax.Array, k: int, iters: int = 25) -> KMeansResult:
    """vmapped k-means over a leading batch axis: x (m, n, d) -> (m, k, d).

    This is the PQ training primitive: one independent k-means per sub-space.
    """
    m = x.shape[0]
    keys = jax.random.split(key, m)
    return jax.vmap(lambda kk, xx: kmeans(kk, xx, k=k, iters=iters))(keys, x)
