"""Exact re-ranking stage: refine quantized-scan candidates with true floats.

Quicker ADC (André et al.) and KScaNN both stack an exact refinement pass on
top of the fast quantized scan: the 4-bit ADC orders candidates *almost*
right, so recomputing true distances for only the top r·k survivors recovers
nearly all the recall lost to quantization at a tiny fraction of brute-force
cost. This module is that pass, batched and jit-friendly (static shapes,
-1-padded candidate sets).

Two implementations, selected by ``rerank_impl`` (registry:
``kernels.ops.RERANK_IMPLS``), bit-identical through every search path:

  'gathered'  gather the candidate rows to a (Q, R, D) copy and compute
              ``Σ(q − x)²`` over it;
  'stream'    gather-free: the Pallas kernel ``kernels.rerank_kernel``
              DMAs only the candidate rows out of the in-place HBM base
              (double-buffered) and reduces to the final top-k in VMEM, so
              only (Q, k) survivors reach HBM;
  'auto'      timed dispatch between the two, cached alongside the scan
              verdicts (``kernels.ops.resolve_rerank_impl``).

Both use the shared distance helper ``rerank_kernel.sq_l2_dists``, which
is what keeps them bit-identical (see that module's docstring for the
rounding argument, and for why the subtraction form rather than the
expanded ``(‖q‖² − 2·q·x) + ‖x‖²``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import topk as topk_mod
from repro.kernels import ops
from repro.kernels.rerank_kernel import sq_l2_dists


@jax.jit
def exact_distances(base: jax.Array, q: jax.Array, cand_ids: jax.Array
                    ) -> jax.Array:
    """True squared-L2 ``Σ(q − x)²`` from each query to its candidates.

    base: (N, D); q: (Q, D); cand_ids: (Q, R) int32, -1 = padding.
    Returns (Q, R) f32 with +inf at padded slots. The row gather is this
    path's memory traffic; the 'stream' impl removes it.
    """
    safe = jnp.maximum(cand_ids, 0)
    vecs = base[safe]                                      # (Q, R, D)
    d = sq_l2_dists(q, vecs)                               # (Q, R)
    return jnp.where(cand_ids >= 0, d, jnp.inf)


def finalize_candidates(flat_d: jax.Array, flat_ids: jax.Array,
                        base: jax.Array | None, q: jax.Array, k: int, r: int,
                        *, rerank_impl: str = "gathered"
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Stages 3+4 for one candidate pool: optional exact re-rank, final top-k.

    flat_d/flat_ids: (Q, C) quantized candidate distances/ids (-1 = padding).
    r > 0 refines the top r*k candidates with true distances from ``base``
    via ``rerank_impl`` ('gathered' | 'stream' | 'auto' — resolved here at
    trace time, like the scan dispatch).
    Returns (dists (Q, k), ids (Q, k), reranked (Q,) i32 work counter).
    Shared by the single-host engine and the per-shard pipeline so the two
    paths cannot drift.
    """
    if r:
        rr = min(r * k, flat_d.shape[1])
        _, pos = topk_mod.masked_topk(flat_d, flat_ids >= 0, rr)
        cand_ids = topk_mod.gather_ids(flat_ids, pos)
        impl, tile_r = ops.resolve_rerank_dispatch(
            rerank_impl, flat_d.shape[0], rr, q.shape[-1], k, base.shape[0])
        if impl == "stream":
            vals, out_ids = ops.rerank_stream_topk(base, q, cand_ids, k=k,
                                                   tile_r=tile_r)
        else:
            vals, out_ids = exact_rerank(base, q, cand_ids, k)
        reranked = jnp.sum((cand_ids >= 0).astype(jnp.int32), axis=1)
    else:
        vals, pos = topk_mod.masked_topk(flat_d, flat_ids >= 0, k)
        out_ids = topk_mod.gather_ids(flat_ids, pos)
        reranked = jnp.zeros((flat_d.shape[0],), jnp.int32)
    return vals, out_ids, reranked


@functools.partial(jax.jit, static_argnames=("k",))
def exact_rerank(base: jax.Array, q: jax.Array, cand_ids: jax.Array, k: int
                 ) -> tuple[jax.Array, jax.Array]:
    """Re-rank candidates by true distance, keep the best k (gathered impl).

    Returns (dists (Q, k) f32 ascending, ids (Q, k) i32, -1 past the valid
    candidate count). Candidate ids are unique by construction (each base
    vector lives in exactly one IVF list), so no dedup pass is needed. The
    semantic oracle the streaming kernel is held bit-identical to.
    """
    d = exact_distances(base, q, cand_ids)
    vals, pos = topk_mod.masked_topk(d, cand_ids >= 0, k)
    return vals, topk_mod.gather_ids(cand_ids, pos)
