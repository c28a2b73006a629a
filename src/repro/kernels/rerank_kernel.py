"""Gather-free streaming exact re-rank: Pallas kernel for stage 3.

The gathered re-rank (``engine/rerank.py exact_distances``) materializes a
``(Q, R, D)`` f32 copy of every candidate base row plus a full ``(Q, R)``
distance tensor before top-k — after PR 4 made the scan stage gather-free,
that copy is the dominant memory-traffic term of the pipeline. This kernel
is the same move applied to stage 3: candidate ids are scalar-prefetched,
each grid step DMAs only its candidate rows out of the in-place HBM base
into VMEM scratch (double-buffered, two slots + two semaphores, so chunk
t+1's rows stream in while chunk t's distances compute), distances are the
subtraction form ``d(q, x) = Σ(q − x)²``, and a running top-k folds each
chunk in VMEM — only the ``(Q, k)`` survivors ever reach HBM.

Exactness. Both re-rank paths compute the distance through the same
``sq_l2_dists`` helper below: an elementwise difference, square and
``axis=-1`` sum, whose per-row reduction order XLA keeps identical across
the two batching shapes, so on the CPU the kernel is *bit-identical* to the
gathered ``exact_rerank`` (asserted in ``tests/test_stream_rerank.py``).
On a TPU, Mosaic and XLA may sum the D lanes in different orders; every
summand is >= 0, so the two results stay within a few ulp of the distance
itself. (The expanded form ``(‖q‖² − 2·q·x) + ‖x‖²`` does not: its terms
are ~25x the distance of a near neighbour on SIFT-like data, so one ulp of
difference in the dot is ~1e-5 of the distance.) The running top-k
reproduces ``masked_topk``'s
lowest-flat-index tie-break: the running candidates (all from earlier
chunks, i.e. lower flat positions) are merged *ahead of* the current
chunk's entries and min-extraction takes the first occurrence, so an equal
value always resolves to the lowest candidate position; non-finite
distances get position -1 exactly like ``masked_topk``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pipeline import double_buffered_dma

# Default candidate-chunk size: r*k candidate rows per query are typically a
# few dozen, so one or two chunks cover a query while keeping the (2, tile_r,
# D) f32 scratch small.
TILE_R = 64


def sq_l2_dists(qv: jax.Array, vecs: jax.Array) -> jax.Array:
    """Squared L2 ``Σ(q − x)²`` of qv (..., D) against the row blocks vecs
    (..., R, D); returns (..., R) f32. The ONE distance expression both
    re-rank impls share (see module docstring)."""
    diff = qv[..., None, :] - vecs                       # (..., R, D)
    return jnp.sum(diff * diff, axis=-1)


def _merge_topk(run_vals, run_pos, chunk_vals, chunk_pos, k: int):
    """Fold one chunk into the running top-k by iterative min-extraction.

    run_vals/run_pos: (1, k) f32/i32 running selection (+inf / -1 absent),
    ascending, equal values ordered by position. chunk_vals/chunk_pos:
    (1, tn). Returns the updated (1, k) pair with the same invariants.
    Running entries are concatenated FIRST: they hold strictly lower flat
    positions than any current-chunk entry, so first-occurrence argmin
    reproduces ``masked_topk``'s lowest-flat-index tie-break.
    """
    vals = jnp.concatenate([run_vals, chunk_vals], axis=1)   # (1, k + tn)
    pos = jnp.concatenate([run_pos, chunk_pos], axis=1)
    width = vals.shape[1]
    iota_w = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)

    def body(j, carry):
        v, out_v, out_p = carry
        mn = jnp.min(v, axis=-1, keepdims=True)                   # (1, 1)
        # first occurrence of the min, as a masked min (see
        # fastscan_kernel._first_argmin)
        am = jnp.min(jnp.where(v == mn, iota_w, width), axis=-1,
                     keepdims=True)                               # (1, 1)
        sel = iota_w == am
        out_v = jnp.where(iota_k == j, mn, out_v)
        out_p = jnp.where(iota_k == j,
                          jnp.sum(jnp.where(sel, pos, 0), axis=-1,
                                  keepdims=True), out_p)
        v = jnp.where(sel, jnp.inf, v)
        return v, out_v, out_p

    init = (vals,
            jnp.full((1, k), jnp.inf, jnp.float32),
            jnp.full((1, k), -1, jnp.int32))
    _, out_v, out_p = jax.lax.fori_loop(0, k, body, init)
    # masked_topk marks non-finite selections with position -1
    out_p = jnp.where(out_v < jnp.inf, out_p, -1)
    return out_v, out_p


def _rerank_kernel(cand_ref, q_ref, cids_ref, base_hbm,
                   vals_ref, pos_ref, scratch, sem, *,
                   tile_r: int, k: int, n_chunks: int, q: int, d: int):
    """One query x one candidate chunk; base rows DMA'd from HBM in place.

    cand_ref: (Q*Rp,) i32 scalar-prefetched flat candidate ids (-1 = pad)
    q_ref:    (1, 1, D) f32 block — this query's row
    cids_ref: (1, n_chunks, tile_r) i32 block — the same candidate ids,
              vector-readable (validity mask; the scalar copy drives the
              DMA); chunk ``ci`` is row ``ci``
    base_hbm: (N, D) f32, memory space ANY — the base, untouched in place
    vals_ref/pos_ref: (1, 1, k) output blocks, revisited across the chunk
              grid (index map ignores the chunk dim) — the running top-k
              lives in VMEM and is written back once per query
    scratch:  (2, tile_r, D) f32 — double-buffered row landing pads
    sem:      (2,) DMA semaphores, one per slot

    Each chunk issues ``tile_r`` single-row copies (a true gather has no
    contiguous HBM slice to DMA); invalid ids skip their copy, and the
    whole next chunk streams into the other slot while this one computes.
    """
    qi = pl.program_id(0)
    ci = pl.program_id(1)
    step = qi * n_chunks + ci
    total = q * n_chunks
    rp = n_chunks * tile_r

    def row_copies(s, slot, op):
        sq, sc = s // n_chunks, s % n_chunks

        def body(j, _):
            cid = cand_ref[sq * rp + sc * tile_r + j]

            @pl.when(cid >= 0)
            def _copy():
                dma = pltpu.make_async_copy(
                    base_hbm.at[pl.ds(cid, 1)],
                    scratch.at[slot, pl.ds(j, 1)], sem.at[slot])
                if op == "start":
                    dma.start()
                else:
                    dma.wait()
            return 0
        jax.lax.fori_loop(0, tile_r, body, 0)

    double_buffered_dma(step, total,
                        lambda s, slot: row_copies(s, slot, "start"),
                        lambda s, slot: row_copies(s, slot, "wait"))

    @pl.when(ci == 0)
    def _init():  # fresh query: empty running selection
        vals_ref[...] = jnp.full_like(vals_ref, jnp.inf)
        pos_ref[...] = jnp.full_like(pos_ref, -1)

    cids = cids_ref[0, pl.ds(ci, 1), :]                # (1, tile_r)
    rows = scratch[step % 2]                           # (tile_r, D)
    dists = sq_l2_dists(q_ref[0], rows[None])         # (1, tile_r)
    dists = jnp.where(cids >= 0, dists, jnp.inf)       # pad/-1 -> absent
    chunk_pos = (jax.lax.broadcasted_iota(jnp.int32, (1, tile_r), 1)
                 + ci * tile_r)
    vals, pos = _merge_topk(vals_ref[0], pos_ref[0], dists, chunk_pos, k)
    vals_ref[0] = vals
    pos_ref[0] = pos


def rerank_stream_topk(base: jax.Array, q: jax.Array, cand_ids: jax.Array,
                       *, k: int, tile_r: int = TILE_R,
                       interpret: bool
                       ) -> tuple[jax.Array, jax.Array]:
    """Gather-free exact re-rank: (N, D) f32 base *in place* + (Q, Rp) i32
    candidate ids -> (vals (Q, k) f32 ascending, pos (Q, k) i32).

    Rp must be a ``tile_r`` multiple
    (pad with -1; padded slots come back +inf / -1). ``pos`` indexes into
    ``cand_ids`` exactly like ``masked_topk``'s positions: the caller maps
    positions to ids with ``topk.gather_ids``. Bit-identical to the
    gathered ``engine.rerank.exact_rerank`` off the TPU (same
    ``sq_l2_dists`` expression, same tie-breaks).
    """
    n, d = base.shape
    qq, rp = cand_ids.shape
    assert rp % tile_r == 0, (rp, tile_r)
    assert q.shape == (qq, d)
    n_chunks = rp // tile_r
    # per-query blocks whose last two dims equal the array's (Mosaic's
    # block rule), resident while the grid walks the query's chunks
    per_query = lambda qi, ci, cd: (qi, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(qq, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, d), per_query),
            pl.BlockSpec((1, n_chunks, tile_r), per_query),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, k), per_query),
            pl.BlockSpec((1, 1, k), per_query),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, tile_r, d), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = functools.partial(_rerank_kernel, tile_r=tile_r, k=k,
                               n_chunks=n_chunks, q=qq, d=d)
    vals, pos = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((qq, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((qq, 1, k), jnp.int32),
        ],
        interpret=interpret,
    )(cand_ids.reshape(-1), q[:, None, :],
      cand_ids.reshape(qq, n_chunks, tile_r), base)
    return vals[:, 0], pos[:, 0]
