"""Synthetic ANN datasets with the paper's dataset geometry.

Offline container => no SIFT1M/Deep1B downloads. We generate clustered
Gaussian-mixture data whose dimensionality matches the paper's datasets
(SIFT-like: 128-D non-negative ints; Deep-like: 96-D L2-normalized floats)
and compute exact ground truth by brute force. Cluster structure matters:
PQ recall curves are meaningless on isotropic noise.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kmeans import pairwise_sqdist
from repro.core.topk import smallest_k


class ANNDataset(NamedTuple):
    base: jax.Array    # (N, D)
    train: jax.Array   # (Nt, D)
    queries: jax.Array  # (Q, D)
    gt_ids: jax.Array  # (Q, G) exact nearest neighbor ids (ascending)

    @property
    def d(self) -> int:
        return self.base.shape[1]


def _gmm(rng: np.random.Generator, n: int, d: int, ncl: int, spread: float):
    centers = rng.normal(0.0, 1.0, (ncl, d)).astype(np.float32)
    which = rng.integers(0, ncl, n)
    x = centers[which] + spread * rng.normal(0.0, 1.0, (n, d)).astype(np.float32)
    return x.astype(np.float32)


def exact_ground_truth(base: jax.Array, queries: jax.Array, g: int = 10,
                       chunk: int = 512) -> jax.Array:
    """Brute-force g nearest base ids per query, ascending (Q, g) i32.

    The distance matmul runs at ``Precision.HIGHEST``: recall is scored
    against these ids, so they must be the f32 neighbours even on a TPU,
    whose default matmul precision may use bf16 passes.
    """
    outs = []
    for s in range(0, queries.shape[0], chunk):
        d = pairwise_sqdist(queries[s:s + chunk], base,
                            precision=jax.lax.Precision.HIGHEST)
        _, ids = smallest_k(d, g)
        outs.append(ids)
    return jnp.concatenate(outs, axis=0)


def _make_queries(rng, base: np.ndarray, nq: int, rel_noise: float) -> np.ndarray:
    """Queries = perturbed base vectors (standard synthetic-ANN protocol):
    the true NN is at a controlled margin, so recall curves measure ADC
    fidelity rather than the degenerate geometry of isotropic mixtures."""
    idx = rng.choice(base.shape[0], size=nq, replace=False)
    scale = np.std(base) * rel_noise
    return (base[idx] + scale * rng.normal(0, 1, (nq, base.shape[1]))
            ).astype(np.float32)


def make_sift_like(n: int = 100_000, nt: int = 20_000, nq: int = 256,
                   d: int = 128, ncl: int = 256, seed: int = 0,
                   gt: int = 10, query_noise: float = 0.5) -> ANNDataset:
    """128-D SIFT-like: non-negative, heavy cluster structure (paper Fig. 2a)."""
    rng = np.random.default_rng(seed)
    x = _gmm(rng, n + nt, d, ncl, spread=0.35)
    x = np.abs(x) * 64.0  # SIFT histograms are non-negative with ~[0,218] range
    base, train = x[:n], x[n:]
    queries = _make_queries(rng, base, nq, query_noise)
    base_j, queries_j = jnp.asarray(base), jnp.asarray(queries)
    return ANNDataset(base_j, jnp.asarray(train), queries_j,
                      exact_ground_truth(base_j, queries_j, g=gt))


def make_deep_like(n: int = 100_000, nt: int = 20_000, nq: int = 256,
                   d: int = 96, ncl: int = 256, seed: int = 1,
                   gt: int = 10, query_noise: float = 0.5) -> ANNDataset:
    """96-D Deep1B-like: L2-normalized CNN-ish features (paper Fig. 2b/Table 1)."""
    rng = np.random.default_rng(seed)
    x = _gmm(rng, n + nt, d, ncl, spread=0.25)
    x /= np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-9)
    base, train = x[:n], x[n:]
    queries = _make_queries(rng, base, nq, query_noise)
    queries /= np.maximum(np.linalg.norm(queries, axis=1, keepdims=True), 1e-9)
    base_j, queries_j = jnp.asarray(base), jnp.asarray(queries)
    return ANNDataset(base_j, jnp.asarray(train), queries_j,
                      exact_ground_truth(base_j, queries_j, g=gt))
