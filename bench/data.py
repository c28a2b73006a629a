"""Seeded synthetic vectors at a deployment's published shapes, made on the
device in one jitted call.

The generator is a Gaussian mixture with the geometry of the paper's data
sets (arXiv:2203.02505 Fig. 2): SIFT-like rows are non-negative and scaled to
SIFT's [0, ~218] range, Deep-like rows are L2-normalised. Queries are base
rows perturbed by Gaussian noise (the standard synthetic-ANN protocol), drawn
without replacement from the base.

The base, training and query rows are the deployment's, as SIFT1M is one
fixed set with one fixed query set: they come from the configuration's
``distribution_seed``. The run's ``--seed`` draws the order the base rows are
stored in, and so every row's id (and the traffic draws the order the pool's
queries are asked in, ``traffic.py``). Every seed therefore builds the same
index up to ids and slot order, with the same list sizes, and asks the same
queries. The program under test only ever sees the generated arrays.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp


class Vectors(NamedTuple):
    base: jax.Array   # (n_base, dim) f32
    train: jax.Array  # (n_train, dim) f32
    pool: jax.Array   # (n_pool, dim) f32, the queries traffic draws from


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also one wider than 32 bits."""
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(0)
    while True:  # fold the seed in 31 bits at a time
        key = jax.random.fold_in(key, seed & 0x7FFFFFFF)
        seed >>= 31
        if not seed:
            return key


def data_key(spec: dict) -> jax.Array:
    """The deployment's own key, which its rows come from."""
    return jax.random.PRNGKey(spec["distribution_seed"])


def build_key(spec: dict) -> jax.Array:
    """The key the deployment's index is built with, the same every run."""
    return jax.random.fold_in(data_key(spec), 1)


@functools.partial(jax.jit, static_argnames=(
    "n_base", "n_train", "n_pool", "dim", "clusters", "spread", "scale",
    "normalize", "query_noise"))
def _gmm(fixed_key, key, *, n_base: int, n_train: int, n_pool: int, dim: int,
         clusters: int, spread: float, scale: float, normalize: bool,
         query_noise: float):
    k_c, k_w, k_x = jax.random.split(fixed_key, 3)
    k_q, k_n = jax.random.split(jax.random.fold_in(fixed_key, 2))
    n = n_base + n_train
    centers = jax.random.normal(k_c, (clusters, dim), jnp.float32)
    which = jax.random.randint(k_w, (n,), 0, clusters)
    x = centers[which] + spread * jax.random.normal(k_x, (n, dim), jnp.float32)
    if normalize:
        x = x / jnp.maximum(jnp.linalg.norm(x, axis=1, keepdims=True), 1e-9)
    else:
        x = jnp.abs(x) * scale
    rows = jax.random.permutation(k_q, n_base)[:n_pool]
    noise = jax.random.normal(k_n, (n_pool, dim), jnp.float32)
    pool = x[rows] + jnp.std(x[:n_base]) * query_noise * noise
    base = x[:n_base][jax.random.permutation(key, n_base)]
    train = x[n_base:]
    if normalize:
        pool = pool / jnp.maximum(jnp.linalg.norm(pool, axis=1, keepdims=True),
                                  1e-9)
    return base, train, pool


def make(spec: dict, key: jax.Array) -> Vectors:
    """The data block of a configuration file -> device arrays."""
    if spec["generator"] != "gmm":
        raise ValueError(f"unknown data generator {spec['generator']!r}")
    base, train, pool = _gmm(
        data_key(spec), key,
        n_base=spec["n_base"], n_train=spec["n_train"],
        n_pool=spec["n_pool"], dim=spec["dim"], clusters=spec["clusters"],
        spread=spec["spread"], scale=spec.get("scale", 1.0),
        normalize=spec["normalize"], query_noise=spec["query_noise"])
    return Vectors(base, train, pool)


@functools.partial(jax.jit, static_argnames=("block",))
def _nearest(base: jax.Array, queries: jax.Array, block: int) -> jax.Array:
    """Exact nearest base row of each query: f32 distances at
    ``Precision.HIGHEST``, the base taken in blocks of ``block`` rows so the
    (Q, block) distance tile stays small."""
    n = base.shape[0]
    q2 = jnp.sum(queries * queries, axis=1, keepdims=True)

    def body(i, carry):
        best_d, best_i = carry
        x = jax.lax.dynamic_slice_in_dim(base, i * block, block)
        d = (q2 - 2.0 * jnp.matmul(queries, x.T,
                                   precision=jax.lax.Precision.HIGHEST)
             + jnp.sum(x * x, axis=1)[None, :])
        j = jnp.argmin(d, axis=1)
        dj = jnp.take_along_axis(d, j[:, None], axis=1)[:, 0]
        take = dj < best_d
        return (jnp.where(take, dj, best_d),
                jnp.where(take, (j + i * block).astype(jnp.int32), best_i))

    init = (jnp.full((queries.shape[0],), jnp.inf, jnp.float32),
            jnp.zeros((queries.shape[0],), jnp.int32))
    return jax.lax.fori_loop(0, n // block, body, init)[1]


def nearest_neighbour(base: jax.Array, queries: jax.Array) -> jax.Array:
    """(Q,) i32: the exact nearest base row of each query (ground truth for
    recall), queries taken 1024 at a time."""
    n = base.shape[0]
    block = next(b for b in (65536, 50000, 40000, 32768, 25000, 20000, 16384,
                             10000, 8192, 5000, 4096, 2000, 1000, n)
                 if n % b == 0)
    out = []
    for s in range(0, queries.shape[0], 1024):
        out.append(_nearest(base, queries[s:s + 1024], block))
    return jnp.concatenate(out)
