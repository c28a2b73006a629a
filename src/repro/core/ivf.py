"""Inverted-file index (IVF) with 4-bit PQ fast-scan distance estimation.

Paper §4: split the database into n_list subsets around k-means centroids;
at query time scan only the n_probe nearest subsets with the 4-bit ADC.

TPU adaptation of the data structure: lists are *padded* to a fixed capacity
so every shape is static and the whole probe+scan+merge pipeline lowers under
jit/pjit on a 512-device mesh (no dynamic shapes anywhere — the brief's rule).
Encoding is by-residual (faiss IVFPQ default): codes quantize x - centroid.

List storage/gather lives in ``repro.core.lists.ListStore`` — a reusable
component shared with the unified engine (``repro.engine``) and the
shard-parallel path. ``scan_probes`` is the quantized-scan stage on its own:
(query, probe_ids) -> per-candidate ADC distances, reused verbatim by the
engine so ``SearchEngine.search`` and hand-composition are identical.

Conventions (shared across ``repro.core``, see docs/architecture.md):
  shapes  all static — lists padded to ``cap``, probe sets to ``nprobe``;
          queries (Q, D) or (D,) auto-promoted to (1, D)
  dtypes  queries/centroids/distances float32; packed codes uint8;
          ids and probe ids int32
  -1 id   sentinel everywhere — probe_ids entry -1 = no probe (yields a
          fully-padded list), candidate/result id -1 = padding/no candidate
          (distance +inf); consumers mask on ``id >= 0``
  filter  optional packed per-row bitmap (nlist, W) u8 (layout:
          ``core.lists.pack_filter_mask``, docs/filtering.md); bit 0 = the
          row is excluded from the scan exactly as if it were padding (id
          -1, distance +inf). ``scan_probes_stream`` applies it inside the
          kernel's pre-selection mask; gathered paths post-mask the full
          pool — both bit-identical
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fastscan as fs
from repro.core import pq as pq_mod
from repro.core import topk as topk_mod
from repro.core.kmeans import kmeans, pairwise_sqdist
from repro.core.lists import ListStore, build_lists
from repro.core.pq import PQCodebook


class IVFIndex(NamedTuple):
    centroids: jax.Array  # (nlist, D) coarse quantizer
    codebook: PQCodebook  # residual PQ codebooks, K=16
    lists: ListStore      # padded posting lists (codes/ids/sizes)

    @property
    def nlist(self) -> int:
        return self.centroids.shape[0]

    @property
    def cap(self) -> int:
        return self.lists.cap

    # back-compat accessors for the pre-ListStore field layout
    @property
    def list_codes(self) -> jax.Array:
        return self.lists.codes

    @property
    def list_ids(self) -> jax.Array:
        return self.lists.ids

    @property
    def list_sizes(self) -> jax.Array:
        return self.lists.sizes


def build_ivf(key: jax.Array, train_x: jax.Array, base_x: jax.Array, *,
              m: int, nlist: int, cap: int | None = None,
              coarse_iters: int = 20, pq_iters: int = 25) -> IVFIndex:
    """Train coarse centroids + residual PQ, bucket base into padded lists.

    Host-side bucketing (numpy) — index build is offline; search is jit'd.
    """
    k_coarse, k_pq, k_assign = jax.random.split(key, 3)
    res = kmeans(k_coarse, train_x, k=nlist, iters=coarse_iters)
    centroids = res.centroids

    # assign base vectors to lists, in chunks to bound memory
    n = base_x.shape[0]
    assign = np.empty((n,), np.int32)
    chunk = 65536
    for s in range(0, n, chunk):
        d = pairwise_sqdist(base_x[s:s + chunk], centroids)
        assign[s:s + chunk] = np.asarray(jnp.argmin(d, axis=-1), np.int32)

    # residual PQ training on train residuals
    d_train = pairwise_sqdist(train_x, centroids)
    train_assign = jnp.argmin(d_train, axis=-1)
    train_res = train_x - centroids[train_assign]
    cb = pq_mod.train_pq(k_pq, train_res, m=m, k=16, iters=pq_iters)

    # encode base residuals
    base_res = base_x - centroids[assign]
    codes = np.asarray(pq_mod.encode(cb, base_res), np.int32)  # (n, M)
    packed = np.asarray(fs.pack_codes(jnp.asarray(codes)), np.uint8)

    return IVFIndex(
        centroids=centroids,
        codebook=cb,
        lists=build_lists(assign, packed, nlist=nlist, cap=cap),
    )


# fixed encode batch shape for the mutation path (docs/mutability.md): a
# row's assignment + code bytes must be bitwise independent of who shares
# its upsert batch, so every encode runs at this exact padded shape
_ENCODE_CHUNK = 256


@jax.jit
def _encode_chunk(centroids: jax.Array, cb: PQCodebook, chunk: jax.Array
                  ) -> tuple[jax.Array, jax.Array]:
    d = pairwise_sqdist(chunk, centroids)
    assign = jnp.argmin(d, axis=-1).astype(jnp.int32)
    codes = pq_mod.encode(cb, chunk - centroids[assign])
    return assign, fs.pack_codes(codes)


def encode_rows(centroids: jax.Array, cb: PQCodebook, vecs: np.ndarray
                ) -> tuple[np.ndarray, np.ndarray]:
    """Deterministic list assignment + residual PQ encode of raw rows.

    vecs: (B, D) f32. Returns (assign (B,) i32, packed (B, M//2) u8) — the
    nearest coarse centroid per row and the nibble-packed 4-bit PQ codes of
    the residual, exactly what ``build_ivf`` computes for the initial build.

    Every call runs the same jitted program at a FIXED zero-padded batch
    shape (``_ENCODE_CHUNK``), so a given row encodes to bitwise-identical
    bytes no matter how it is batched. That is the property the mutation
    oracle rests on (docs/mutability.md): an upserted row's codes equal the
    codes a from-scratch rebuild assigns it, so a mutated engine and a
    rebuilt one score it identically.
    """
    vecs = np.asarray(vecs, np.float32)
    b, d = vecs.shape
    assign = np.empty((b,), np.int32)
    packed = np.empty((b, cb.m // 2), np.uint8)
    for s in range(0, b, _ENCODE_CHUNK):
        chunk = vecs[s:s + _ENCODE_CHUNK]
        c = chunk.shape[0]
        if c < _ENCODE_CHUNK:
            chunk = np.concatenate(
                [chunk, np.zeros((_ENCODE_CHUNK - c, d), np.float32)])
        a, p = _encode_chunk(centroids, cb, jnp.asarray(chunk))
        assign[s:s + c] = np.asarray(a)[:c]
        packed[s:s + c] = np.asarray(p)[:c]
    return assign, packed


def probe_tables(index: IVFIndex, q: jax.Array, probe_ids: jax.Array
                  ) -> fs.QuantizedLUT:
    """Residual ADC LUTs for each (query, probe): (Q, P, M, 16) u8, with the
    per-(query, probe) dequantization affine. The scan kernels' operand."""
    mu = index.centroids[jnp.maximum(probe_ids, 0)]  # (Q, P, D)
    resid = q[:, None, :] - mu                 # (Q, P, D)
    qq, p, d = resid.shape
    t = pq_mod.adc_table(index.codebook, resid.reshape(qq * p, d))  # (QP, M, 16)
    qlut = fs.quantize_lut(t)
    return fs.QuantizedLUT(
        table_q8=qlut.table_q8.reshape(qq, p, *qlut.table_q8.shape[1:]),
        scale=qlut.scale.reshape(qq, p),
        bias=qlut.bias.reshape(qq, p, -1),
    )


@functools.partial(jax.jit, static_argnames=("impl",))
def scan_probes(index: IVFIndex, q: jax.Array, probe_ids: jax.Array, *,
                impl: str = "ref") -> tuple[jax.Array, jax.Array]:
    """Quantized fine-scan stage: 4-bit ADC over the probed lists.

    q: (Q, D); probe_ids: (Q, P) (-1 = no probe). Returns
    (dists (Q, P, cap) f32, ids (Q, P, cap) i32, -1 = padding).

    Each (query, probe) pair gets its own residual u8 LUT, so the scan is the
    *grouped* kernel formulation: impl 'ref' is the vectorized jnp gather,
    'select' the register-resident Pallas select-tree, 'mxu' the per-group
    one-hot GEMM on the MXU, 'stream' the gather-free in-kernel list DMA
    (codes scanned in place in ``index.lists`` — the (Q, P, cap, M//2)
    gathered copy never exists), and 'auto' the autotuned dispatch
    (``kernels.ops.SCAN_IMPLS``; resolution happens at trace time since all
    shapes here are static, and may itself pick 'stream'). All bit-identical
    on every real candidate (invalid probes yield unmasked garbage distances
    under any impl; consumers mask on ``ids >= 0``).
    """
    from repro.kernels import ops  # local import: kernels depend on nothing here

    qlut = probe_tables(index, q, probe_ids)          # (Q, P, M, 16)
    qq, p = probe_ids.shape
    cap = index.lists.cap
    m = qlut.table_q8.shape[-2]
    impl, tile_n = ops.resolve_scan_impl(impl, qq * p, cap, m,
                                         nlist=index.lists.nlist)
    tables = qlut.table_q8.reshape(qq * p, *qlut.table_q8.shape[2:])
    if impl == "stream":
        # in-place calling convention: the ListStore never gets copied —
        # only the probed tiles cross into VMEM, and only the ids (needed
        # downstream for masking/re-rank) are gathered
        acc = ops.fastscan_stream_grouped(
            tables, index.lists.codes, probe_ids.reshape(-1),
            tile_n=tile_n).reshape(qq, p, cap)
        ids = index.lists.gather_ids(probe_ids)        # (Q, P, cap)
    else:
        codes, ids = index.lists.gather(probe_ids)     # (Q,P,cap,Mh), (Q,P,cap)
        acc = ops.fastscan_grouped(
            tables, codes.reshape(qq * p, cap, -1),
            impl=impl, tile_n=tile_n).reshape(qq, p, cap)
    dists = (qlut.scale[..., None] * acc.astype(jnp.float32)
             + jnp.sum(qlut.bias, axis=-1)[..., None])  # (Q, P, cap)
    return dists, ids


@functools.partial(jax.jit, static_argnames=("keep", "tile_n", "early_exit"))
def scan_probes_stream(index: IVFIndex, q: jax.Array, probe_ids: jax.Array, *,
                       keep: int, tile_n: int = 0,
                       filter_bits: jax.Array | None = None,
                       early_exit: bool = False
                       ) -> tuple[jax.Array, ...]:
    """Gather-free fine scan with fused candidate reduction (+ filtering).

    The ``impl='stream'`` serving hot path: ADC runs over ``index.lists``
    *in place* and the kernel reduces each cap tile to its ``kc =
    min(keep, tile)`` best candidates in VMEM, so neither the gathered
    (Q, P, cap, M//2) code copy nor the full (Q, P, cap) distance tensor
    ever reaches HBM. ``filter_bits`` — optional (nlist, W) u8 packed
    per-row bitmap (docs/filtering.md) — excludes rows whose bit is 0
    inside the kernel's pre-selection mask, so filtering costs no recall
    at fixed ``keep``: excluded rows free their candidate slots instead of
    occupying them the way a post-filter would. Returns a *reduced*
    candidate pool (dists (Q, C') f32, ids (Q, C') i32, -1 = absent) with
    C' = P * n_tiles * kc, and two (Q,) i32 work counters:
    ``tiles_skipped`` (early exit, below; zeros without it) and
    ``tiles_scanned`` — the cap tiles the kernel ran ADC and selection on:
    the live tiles (``fastscan_kernel.live_tiles``, the rule the kernel's
    fetch plan applies) of the valid probes, less those early exit pruned.

    Exactness: any final selection of <= ``keep`` candidates per query over
    (dists, ids) — e.g. ``rerank.finalize_candidates`` with
    ``r*k <= keep`` — is bit-identical to the same selection over the full
    ``scan_probes`` pool (post-masked by the same filter): every true
    survivor is within its own tile's top-kc (i32 ADC scores are exact),
    the pool preserves (probe, tile, slot) order, and in-tile ties resolve
    lowest-slot-first, matching ``masked_topk``'s lowest-flat-index
    tie-break.

    ``early_exit`` arms the kernel's anytime tile pruning (docs/anytime.md):
    ``tiles_skipped`` then counts, per query, the valid-probe tiles whose
    scan the lower bound proved irrelevant. The final <= ``keep``
    selection stays bit-identical; the raw pool does not (pruned tiles
    surface as absent candidates).
    """
    from repro.kernels import fastscan_kernel as fk
    from repro.kernels import ops

    qlut = probe_tables(index, q, probe_ids)          # (Q, P, M, 16)
    qq, p = probe_ids.shape
    bias_sum = jnp.sum(qlut.bias, axis=-1)             # (Q, P)
    if early_exit:
        vals, slots, skipped = ops.fastscan_stream_topk(
            qlut.table_q8.reshape(qq * p, *qlut.table_q8.shape[2:]),
            index.lists.codes, probe_ids.reshape(-1), index.lists.sizes,
            keep=keep, tile_n=tile_n, filter_bits=filter_bits,
            early_exit=True, groups_per_query=p,
            scales=qlut.scale.reshape(-1),
            biases=bias_sum.reshape(-1))               # + (G, n_tiles)
    else:
        vals, slots = ops.fastscan_stream_topk(
            qlut.table_q8.reshape(qq * p, *qlut.table_q8.shape[2:]),
            index.lists.codes, probe_ids.reshape(-1), index.lists.sizes,
            keep=keep, tile_n=tile_n,
            filter_bits=filter_bits)                   # (G, n_tiles, kc) x2
        skipped = jnp.zeros(vals.shape[:2], jnp.int32)
    n_tiles, kc = vals.shape[1], vals.shape[2]
    live = fk.live_tiles(probe_ids.reshape(-1), index.lists.sizes, n_tiles,
                         index.lists.cap // n_tiles)      # (G, n_tiles)
    scanned = live & (skipped == 0)
    tiles_skipped = jnp.sum(skipped.reshape(qq, -1), axis=1)
    tiles_scanned = jnp.sum(scanned.reshape(qq, -1), axis=1, dtype=jnp.int32)
    vals = vals.reshape(qq, p, n_tiles * kc)
    slots = slots.reshape(qq, p, n_tiles * kc)
    valid = slots >= 0
    # same affine dequantization expression as scan_probes -> f32-identical
    # (and the same expression the early-exit kernel thresholds with)
    dists = (qlut.scale[..., None] * vals.astype(jnp.float32)
             + bias_sum[..., None])
    dists = jnp.where(valid, dists, jnp.inf)
    # ids only for the kept candidates: a (Q, P, n_tiles*kc) gather instead
    # of the full (Q, P, cap) one
    lids = jnp.maximum(probe_ids, 0)[..., None]
    ids = index.lists.ids[lids, jnp.maximum(slots, 0)]
    ids = jnp.where(valid & (probe_ids >= 0)[..., None], ids, -1)
    return (dists.reshape(qq, -1), ids.reshape(qq, -1), tiles_skipped,
            tiles_scanned)


@functools.partial(jax.jit, static_argnames=("nprobe", "topk"))
def search_ivf(index: IVFIndex, q: jax.Array, *, nprobe: int = 8,
               topk: int = 10) -> tuple[jax.Array, jax.Array]:
    """IVF + 4-bit fast-scan search.

    q: (Q, D). Returns (dists (Q, topk) f32, ids (Q, topk) i32, -1 padding).
    """
    if q.ndim == 1:
        q = q[None]
    coarse_d = pairwise_sqdist(q, index.centroids)            # (Q, nlist)
    _, probe_ids = topk_mod.smallest_k(coarse_d, nprobe)      # (Q, P)
    dists, ids = scan_probes(index, q, probe_ids)             # (Q, P, cap)
    qq = dists.shape[0]
    flat_d = dists.reshape(qq, -1)
    flat_ids = ids.reshape(qq, -1)
    vals, pos = topk_mod.masked_topk(flat_d, flat_ids >= 0, topk)
    return vals, topk_mod.gather_ids(flat_ids, pos)


@functools.partial(jax.jit, static_argnames=("nprobe", "topk"))
def search_ivf_precomputed_probes(index: IVFIndex, q: jax.Array,
                                  probe_ids: jax.Array, *, nprobe: int = 8,
                                  topk: int = 10) -> tuple[jax.Array, jax.Array]:
    """Fine stage only — probes come from an external coarse quantizer (HNSW).

    This is the paper's Table 1 pipeline: HNSW for coarse, fast-scan for fine.
    """
    if q.ndim == 1:
        q = q[None]
    probe_ids = probe_ids[:, :nprobe]
    dists, ids = scan_probes(index, q, probe_ids)
    qq = dists.shape[0]
    flat_d = dists.reshape(qq, -1)
    flat_ids = ids.reshape(qq, -1)
    vals, pos = topk_mod.masked_topk(flat_d, flat_ids >= 0, topk)
    return vals, topk_mod.gather_ids(flat_ids, pos)
