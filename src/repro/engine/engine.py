"""Unified batched search engine: coarse -> 4-bit fast-scan -> exact re-rank.

The single query path a server calls, composing the pieces that previously
lived disconnected across ``core``:

  1. coarse: pluggable probe selection over the IVF centroids — flat
     brute-force, HNSW graph routing (paper Table 1), or k-means tree;
  2. scan: the 4-bit fast-scan ADC over the probed posting lists
     (``core.ivf.scan_probes``, grouped Pallas kernel underneath; with
     ``scan_impl='stream'`` the codes are scanned *in place* with fused
     candidate reduction — no gathered copy, no full distance writeback);
  3. re-rank: exact float refinement of the top ``rerank_mult * k``
     quantized candidates (``engine.rerank``), Quicker-ADC style;
  4. merge: final masked top-k (single host) or the distributed 2k-scalar
     shard merge (``engine.sharded`` over ``core.topk.distributed_topk``).

Every stage is a *pure function* of (coarse pytree, index pytree, arrays) —
see ``coarse_probes`` / ``scan_candidates`` / ``make_stats`` — and the engine
offers two compositions of the same stage functions:

  - ``SearchEngine.search``      staged: each stage dispatches on its own
    (stages are individually jit'd); convenient for debugging and for
    composing custom pipelines by hand.
  - ``SearchEngine.search_jit``  fused: the whole pipeline in ONE ``jax.jit``
    with ``(k, nprobe, rerank_mult, scan_impl, ef)`` static. One XLA program,
    one dispatch — the serving path (``repro.serving``). Results are
    bit-identical to the staged path (tested).

Because the fused jit lives at module level, its compile cache is shared by
every engine in the process and keyed only on static knobs + input shapes:
steady-state serving over a fixed set of batch-shape buckets never
recompiles. ``fused_cache_size()`` exposes the cache occupancy so tests and
serving metrics can assert "at most one compile per shape bucket".

A ``QueryStats`` record rides along for observability: how many lists were
probed, codes scanned, candidates re-ranked, rows filtered out — per query.

Filtered & namespaced search (docs/filtering.md): ``search``/``search_jit``
accept an optional packed per-row predicate bitmap (``filter_bits``) that the
stream kernels apply inside their per-tile VMEM reductions, and optional
per-query ``namespaces`` that restrict coarse probe *selection* to the
tenant's own lists. Both are traced arguments — predicate/tenant churn never
recompiles the fused pipeline.
"""
from __future__ import annotations

import functools
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import coarse as coarse_mod
from repro.core import ivf as ivf_mod
from repro.core import lists as lists_mod
from repro.core import topk as topk_mod
from repro.core.kmeans import pairwise_sqdist
from repro.core.lists import (filter_pass_sizes, filter_words,
                              unpack_filter_mask)
from repro.engine import rerank as rerank_mod
# single source of truth for both registries (kernels.ops)
from repro.kernels import ops as ops_mod
from repro.kernels.ops import RERANK_IMPLS, SCAN_IMPLS

COARSE_KINDS = ("flat", "hnsw", "tree")


class EngineConfig(NamedTuple):
    """Static search-time knobs (all shapes derive from these => jit-stable)."""

    nprobe: int = 8         # lists scanned per query (the MAX under 'margin')
    rerank_mult: int = 0    # refine rerank_mult*k candidates exactly; 0 = off
    scan_impl: str = "ref"  # grouped ADC impl: 'ref' | 'select' | 'mxu' |
    #                         'stream' (gather-free in-kernel list DMA) |
    #                         'auto' (autotuned; see kernels.ops.SCAN_IMPLS)
    ef: int = 64            # HNSW beam width (hnsw coarse only)
    rerank_impl: str = "gathered"  # exact re-rank impl: 'gathered' |
    #                         'stream' (gather-free in-kernel row DMA) |
    #                         'auto' (see kernels.ops.RERANK_IMPLS)
    probe_policy: str = "fixed"  # 'fixed' (always nprobe lists) | 'margin'
    #                         (adaptive nprobe, docs/anytime.md: drop probes
    #                         whose coarse distance exceeds (1 + tau) x the
    #                         query's best — nprobe becomes a per-query MAX)
    margin_tau: float = float("inf")  # 'margin' width; traced at search time
    #                         (per-request overrides never recompile); +inf
    #                         keeps every probe (bit-identical to 'fixed')
    early_exit: bool = False  # anytime tile pruning inside the stream scan
    #                         kernel (docs/anytime.md); lossless for the
    #                         final top-k, no-op on gathered impls


_EF_DEFAULT = EngineConfig._field_defaults["ef"]
PROBE_POLICIES = ("fixed", "margin")
# valid-probe fraction the autotune sweep assumes under a margin policy: the
# 'auto' verdict for an adaptive workload is timed (and cached) against a
# probe set with this fill instead of a dense one (kernels.ops).
MARGIN_PROBE_FILL = 0.5


class QueryStats(NamedTuple):
    """Per-query work counters threaded through the pipeline."""

    lists_probed: jax.Array   # (Q,) i32  valid probes issued
    codes_scanned: jax.Array  # (Q,) i32  true occupancy of scanned lists
    reranked: jax.Array       # (Q,) i32  candidates refined exactly
    rows_filtered: jax.Array  # (Q,) i32  probed LIVE rows the user filter
    #                           excluded (0 when no filter was supplied;
    #                           namespace-excluded LISTS never appear in any
    #                           counter — their probes are -1, so nothing was
    #                           scanned)
    rows_tombstoned: jax.Array  # (Q,) i32  probed slots inside the watermark
    #                           holding deleted rows (docs/mutability.md);
    #                           always 0 on an unmutated engine
    lists_pruned: jax.Array   # (Q,) i32  coarse probes the margin policy
    #                           dropped (docs/anytime.md); 0 under 'fixed'
    tiles_skipped: jax.Array  # (Q,) i32  valid-probe cap tiles the stream
    #                           kernel's early exit proved irrelevant; 0
    #                           without early_exit or on gathered impls
    tiles_scanned: jax.Array  # (Q,) i32  cap tiles the stream kernel ran
    #                           ADC and selection on: the valid probes'
    #                           tiles that hold a row, sum ceil(size/tile),
    #                           less those early exit pruned (tiles past a
    #                           list's occupancy cost no copy and no scan);
    #                           0 on gathered impls. ServingLoop never
    #                           fetches it


class SearchResult(NamedTuple):
    dists: jax.Array  # (Q, k) f32 ascending
    ids: jax.Array    # (Q, k) i32 global ids, -1 = no candidate
    stats: QueryStats


def validate_config(config: EngineConfig, *, coarse_kind: str,
                    has_base: bool) -> None:
    """Reject nonsense config/coarse combinations at construction time.

    Raises ``ValueError`` on knobs that would otherwise be silently ignored
    (``ef`` without HNSW coarse) or blow up on the first search
    (``rerank_mult > 0`` without base vectors, unknown ``scan_impl``).
    """
    if config.nprobe < 1:
        raise ValueError(f"EngineConfig.nprobe must be >= 1, got {config.nprobe}")
    if config.rerank_mult < 0:
        raise ValueError(
            f"EngineConfig.rerank_mult must be >= 0, got {config.rerank_mult}")
    if config.scan_impl not in SCAN_IMPLS:
        raise ValueError(f"EngineConfig.scan_impl {config.scan_impl!r} unknown; "
                         f"want one of {SCAN_IMPLS}")
    if config.rerank_impl not in RERANK_IMPLS:
        raise ValueError(
            f"EngineConfig.rerank_impl {config.rerank_impl!r} unknown; "
            f"want one of {RERANK_IMPLS}")
    if config.probe_policy not in PROBE_POLICIES:
        raise ValueError(
            f"EngineConfig.probe_policy {config.probe_policy!r} unknown; "
            f"want one of {PROBE_POLICIES}")
    if config.margin_tau is None or not config.margin_tau >= 0:  # rejects NaN
        raise ValueError(
            f"EngineConfig.margin_tau must be >= 0, got {config.margin_tau}")
    if config.ef < 1:
        raise ValueError(f"EngineConfig.ef must be >= 1, got {config.ef}")
    if config.ef != _EF_DEFAULT and coarse_kind != "hnsw":
        raise ValueError(
            f"EngineConfig.ef={config.ef} is set but coarse={coarse_kind!r}; "
            "ef is the HNSW beam width and is ignored by every other coarse "
            "quantizer — drop it or build with coarse='hnsw'")
    if config.rerank_mult > 0 and not has_base:
        raise ValueError(
            f"EngineConfig.rerank_mult={config.rerank_mult} requires the raw "
            "base vectors for exact re-rank, but the engine holds none "
            "(build with keep_base=True or pass base=...)")


# ---------------------------------------------------------------------------
# stages — pure functions of (coarse/index pytrees, arrays, static ints).
# ``search`` composes them eagerly stage-by-stage; ``_fused_pipeline`` traces
# the very same functions into one XLA program.
# ---------------------------------------------------------------------------

def coarse_probes(coarse, q: jax.Array, *, nprobe: int, ef: int,
                  ns_member: jax.Array | None = None,
                  namespaces: jax.Array | None = None,
                  probe_policy: str = "fixed",
                  margin_tau: jax.Array | float | None = None
                  ) -> tuple[jax.Array, jax.Array]:
    """Stage 1 — coarse: pick the most promising lists, up to nprobe.

    coarse: any of the ``core.coarse`` quantizer pytrees (or a custom object
    with ``.search(q, nprobe)``). q: (Q, D) f32. Returns
    (probes (Q, nprobe) i32 list ids with -1 = no probe,
    lists_pruned (Q,) i32 — probes the adaptive policy dropped).

    Namespacing (docs/filtering.md): ``ns_member`` is the engine-held
    (n_ns, nlist) bool membership table and ``namespaces`` the per-query
    (Q,) i32 namespace ids (-1 = unrestricted). For flat coarse the
    restriction is fused into probe *selection* (``masked_topk`` over the
    allowed lists), so a tenant scan only ever touches its own lists; graph/
    tree coarse post-masks the routed probes to -1 (they may under-fill
    nprobe, never over-reach). With every query unrestricted the flat path
    is exactly ``smallest_k`` — bit-identical to the namespace-free engine.

    Adaptive nprobe (docs/anytime.md): with ``probe_policy='margin'`` the
    coarse distances every quantizer already returns feed
    ``core.topk.margin_prune_probes`` — a probe survives only while its
    centroid distance is within ``(1 + margin_tau) x`` the query's best, so
    ``nprobe`` becomes a per-query *maximum* and easy (large-margin) queries
    scan fewer lists. ``margin_tau`` is traced (scalar or (Q,)):
    per-request budgets never recompile. ``margin_tau=None`` or ``+inf``
    keeps every probe — bit-identical to ``'fixed'``. The probe mask keeps
    its static (Q, nprobe) shape; pruned slots are the ``-1`` sentinel the
    stream kernels skip without touching HBM.
    """
    restrict = ns_member is not None and namespaces is not None
    if restrict:
        # (Q, nlist) bool: True where query may probe the list
        allow = ((namespaces < 0)[:, None]
                 | ns_member[jnp.maximum(namespaces, 0)])
    if isinstance(coarse, coarse_mod.FlatCoarse) and restrict:
        coarse_d = pairwise_sqdist(q, coarse.centroids)
        vals, probes = topk_mod.masked_topk(coarse_d, allow, nprobe)
    else:
        if isinstance(coarse, coarse_mod.HNSWCoarse):
            vals, probes = coarse.search(q, nprobe, ef=max(ef, nprobe))
        else:
            vals, probes = coarse.search(q, nprobe)
        if restrict:
            ok = jnp.take_along_axis(allow, jnp.maximum(probes, 0), axis=1)
            probes = jnp.where(ok & (probes >= 0), probes, -1)
    if probe_policy == "margin":
        tau = jnp.inf if margin_tau is None else margin_tau
        return topk_mod.margin_prune_probes(vals, probes, tau)
    return probes, jnp.zeros((probes.shape[0],), jnp.int32)


def scan_candidates(index: ivf_mod.IVFIndex, q: jax.Array, probes: jax.Array,
                    *, scan_impl: str, keep: int | None = None,
                    filter_bits: jax.Array | None = None,
                    early_exit: bool = False, probe_fill: float = 1.0
                    ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Stage 2 — quantized scan, flattened to one candidate pool per query.

    Returns (dists (Q, C) f32, ids (Q, C) i32 with -1 = pad,
    tiles_skipped (Q,) i32 — early-exit counter, zeros unless the stream
    path ran with ``early_exit=True`` — and tiles_scanned (Q,) i32, the
    stream kernel's scanned cap tiles, zeros on the gathered impls). With
    the gathered impls
    C = nprobe*cap. ``keep`` is the per-query candidate budget the
    downstream selection will take (r*k, or k without re-rank): when the
    resolved impl is 'stream' and ``keep`` is given, the scan runs gather-
    free over the in-place ListStore with fused per-tile reduction
    (``core.ivf.scan_probes_stream``) and C shrinks to
    nprobe*n_tiles*min(keep, tile) — bit-identical through any final
    selection of <= keep candidates. ``keep=None`` always yields the full
    pool (hand-composition back-compat).

    ``filter_bits`` is the (nlist, W) packed per-row predicate bitmap
    (``core.lists.pack_filter_mask``; docs/filtering.md). The stream path
    applies it *inside* the per-tile VMEM reduction (excluded rows hit the
    same sentinel as padding, before candidate selection — so the keep
    budget is spent on passing rows only). The gathered path here is the
    reference post-filter oracle: scan everything, then mask excluded rows
    to (inf, -1). The two are bit-identical through any final selection of
    <= keep candidates (tested at 0/1/50/100% selectivity).

    ``early_exit`` arms the stream kernel's anytime tile pruning
    (docs/anytime.md) — lossless for the final top-``keep``; a no-op (zeros
    counter) whenever the resolved impl is gathered. ``probe_fill`` is the
    valid-probe fraction the 'auto' sweep should assume (< 1 under a margin
    policy, where many probes arrive as -1).
    """
    if keep is not None:
        from repro.kernels import ops
        qq, p = probes.shape
        impl, tile_n = ops.resolve_scan_impl(
            scan_impl, qq * p, index.lists.cap,
            2 * index.lists.codes.shape[-1], nlist=index.lists.nlist,
            probe_fill=probe_fill)
        if impl == "stream":
            return ivf_mod.scan_probes_stream(index, q, probes, keep=keep,
                                              tile_n=tile_n,
                                              filter_bits=filter_bits,
                                              early_exit=early_exit)
    dists, ids = ivf_mod.scan_probes(index, q, probes, impl=scan_impl)
    if filter_bits is not None:
        # post-filter oracle: (Q, P, cap) bool of rows that pass
        ok = unpack_filter_mask(filter_bits, index.lists.cap)[
            jnp.maximum(probes, 0)]
        ok = ok & (ids >= 0)
        dists = jnp.where(ok, dists, jnp.inf)
        ids = jnp.where(ok, ids, -1)
    qq = dists.shape[0]
    zeros = jnp.zeros((qq,), jnp.int32)
    return dists.reshape(qq, -1), ids.reshape(qq, -1), zeros, zeros


def combine_filter_bits(filter_bits: jax.Array | None,
                        live_bits: jax.Array | None) -> jax.Array | None:
    """AND the user predicate bitmap with the engine's live-row bitmap.

    The effective filter the scan stage applies: a row is scannable iff it
    passes the user predicate AND is not tombstoned (docs/mutability.md).
    Either side may be None (no predicate / no tombstones) and simply drops
    out; both None returns None, keeping the unfiltered-unmutated trace
    byte-identical to the pre-mutation engine.
    """
    if live_bits is None:
        return filter_bits
    if filter_bits is None:
        return live_bits
    return filter_bits & live_bits


def _probe_sum(probes: jax.Array, per_list: jax.Array) -> jax.Array:
    """Sum a (nlist,) per-list counter over each query's valid probes."""
    return jnp.sum(jnp.where(probes >= 0, per_list[jnp.maximum(probes, 0)], 0),
                   axis=1)


def count_rows_filtered(index: ivf_mod.IVFIndex, probes: jax.Array,
                        filter_bits: jax.Array | None,
                        live_bits: jax.Array | None = None) -> jax.Array:
    """(Q,) i32: probed LIVE rows the user filter excluded.

    Zero without a filter. Tombstoned slots are counted by
    ``count_rows_tombstoned``, never here — the two partition the probed
    non-passing occupancy. Namespace-excluded lists contribute nothing:
    their probes are already -1, so they were never scanned at all.
    """
    qq = probes.shape[0]
    if filter_bits is None:
        return jnp.zeros((qq,), jnp.int32)
    live = (index.lists.sizes if live_bits is None
            else filter_pass_sizes(index.lists, live_bits))
    eff = combine_filter_bits(filter_bits, live_bits)
    return _probe_sum(probes, live - filter_pass_sizes(index.lists, eff))


def count_rows_tombstoned(index: ivf_mod.IVFIndex, probes: jax.Array,
                          live_bits: jax.Array | None) -> jax.Array:
    """(Q,) i32: probed watermark slots holding tombstones. Zero when the
    engine carries none (``live_bits`` is None)."""
    qq = probes.shape[0]
    if live_bits is None:
        return jnp.zeros((qq,), jnp.int32)
    tomb = index.lists.sizes - filter_pass_sizes(index.lists, live_bits)
    return _probe_sum(probes, tomb)


def make_stats(index: ivf_mod.IVFIndex, probes: jax.Array,
               reranked: jax.Array,
               filter_bits: jax.Array | None = None,
               live_bits: jax.Array | None = None,
               lists_pruned: jax.Array | None = None,
               tiles_skipped: jax.Array | None = None,
               tiles_scanned: jax.Array | None = None) -> QueryStats:
    """Work counters from the probe set + the re-rank stage's counter.

    ``lists_pruned``/``tiles_skipped`` are the anytime counters
    (docs/anytime.md), ``tiles_scanned`` the scan stage's own; None (the
    hand-composition default) records zeros.
    """
    qq = probes.shape[0]
    zeros = jnp.zeros((qq,), jnp.int32)
    return QueryStats(
        lists_probed=jnp.sum((probes >= 0).astype(jnp.int32), axis=1),
        codes_scanned=jnp.sum(index.lists.probed_sizes(probes), axis=1),
        reranked=reranked,
        rows_filtered=count_rows_filtered(index, probes, filter_bits,
                                          live_bits),
        rows_tombstoned=count_rows_tombstoned(index, probes, live_bits),
        lists_pruned=zeros if lists_pruned is None else lists_pruned,
        tiles_skipped=zeros if tiles_skipped is None else tiles_skipped,
        tiles_scanned=zeros if tiles_scanned is None else tiles_scanned,
    )


def _pipeline(coarse, index: ivf_mod.IVFIndex, base: jax.Array | None,
              ns_member: jax.Array | None,
              q: jax.Array, filter_bits: jax.Array | None,
              namespaces: jax.Array | None,
              live_bits: jax.Array | None = None,
              margin_tau: jax.Array | None = None, *, k: int, nprobe: int,
              r: int, scan_impl: str, rerank_impl: str, ef: int,
              probe_policy: str = "fixed", early_exit: bool = False
              ) -> SearchResult:
    """The whole engine as one pure function (stages 1-4 + stats).

    Each stage runs under a ``jax.named_scope`` (``coarse``, ``scan``, and
    in ``finalize_candidates`` ``rerank`` and ``merge``, then ``stats``):
    the scopes reach only the HLO metadata, where a profiler trace reads
    them as each device op's ``tf_op`` path (docs/serving.md).

    ``filter_bits``/``namespaces``/``live_bits``/``margin_tau`` are *traced*
    arguments (None simply drops out of the trace): changing the predicate,
    tenant mix, tombstone set, or per-request margin budget between requests
    never recompiles — only presence/absence does, giving a handful of
    compile-cache entries per shape bucket instead of one per predicate.

    ``live_bits`` is the engine-held live-row bitmap
    (``core.lists.live_filter_bits``), present only while the store carries
    tombstones. It is ANDed into the scan's effective filter so the stream
    kernel's per-tile candidate budget skips deleted rows *before*
    selection — the condition for mutated results to stay bit-identical to
    a rebuilt engine's (docs/mutability.md). Gathered impls mask tombstones
    by id anyway; for them the AND only changes the stats, not the math.

    ``probe_policy``/``early_exit`` are the static anytime knobs
    (docs/anytime.md): the policy picks which coarse branch traces and the
    sweep fill the autotuner should time against; early exit changes the
    stream kernel variant. ``margin_tau`` itself stays traced.
    """
    with jax.named_scope("coarse"):
        probes, lists_pruned = coarse_probes(
            coarse, q, nprobe=nprobe, ef=ef, ns_member=ns_member,
            namespaces=namespaces, probe_policy=probe_policy,
            margin_tau=margin_tau)
    # the selection budget stage 3+4 will take — under 'stream' this lets
    # the scan kernel reduce candidates in VMEM instead of writing the full
    # (Q, nprobe*cap) pool to HBM
    with jax.named_scope("scan"):
        flat_d, flat_ids, tiles_skipped, tiles_scanned = scan_candidates(
            index, q, probes, scan_impl=scan_impl, keep=(r * k) if r else k,
            filter_bits=combine_filter_bits(filter_bits, live_bits),
            early_exit=early_exit,
            probe_fill=(MARGIN_PROBE_FILL if probe_policy == "margin"
                        else 1.0))
    vals, out_ids, reranked = rerank_mod.finalize_candidates(
        flat_d, flat_ids, base, q, k, r, rerank_impl=rerank_impl)
    with jax.named_scope("stats"):
        stats = make_stats(index, probes, reranked, filter_bits, live_bits,
                           lists_pruned, tiles_skipped, tiles_scanned)
    return SearchResult(dists=vals, ids=out_ids, stats=stats)


# ONE process-wide jit: cache is keyed on static knobs + pytree structure +
# leaf shapes/dtypes, so N engines serving the same bucket shapes share
# compiles. This is the serving fast path.
_fused_pipeline = jax.jit(
    _pipeline,
    static_argnames=("k", "nprobe", "r", "scan_impl", "rerank_impl", "ef",
                     "probe_policy", "early_exit"))


def fused_cache_size() -> int:
    """Number of compiled entries in the fused-pipeline jit cache.

    Serving tests assert the delta of this across a request stream: at most
    one new entry per (shape bucket x static-knob combination).
    """
    return _fused_pipeline._cache_size()


class EngineState(NamedTuple):
    """One immutable snapshot of everything a search reads.

    The mutable engine's atomicity primitive (docs/mutability.md): mutation
    never edits what a reader sees — ``upsert``/``delete``/``compact`` build
    a complete replacement snapshot and install it with a single attribute
    store on ``SearchEngine._state`` (atomic under the GIL). A search grabs
    the snapshot exactly once, so an in-flight batch keeps computing on a
    consistent retiring epoch while every later search sees the new one —
    there is no window where a query can mix lists from one epoch with base
    rows or live bits from another.
    """

    index: ivf_mod.IVFIndex
    base: jax.Array | None
    live_bits: jax.Array | None  # packed live-row bitmap; None = no tombstones
    epoch: int                   # bumped on every swap (monotonic, starts 0)
    n_tombstones: int            # tombstoned slots currently held across lists


class SearchEngine:
    """IVF + fast-scan + exact re-rank behind one ``search(queries, k)``.

    ``base`` (the raw float vectors) is optional: without it the engine
    degrades gracefully to pure quantized search (re-rank requests are
    rejected loudly rather than silently skipped).

    Config/coarse combinations are validated at construction
    (``validate_config``): a nonsense knob raises here, not on first search.

    The engine is *live-mutable* (docs/mutability.md): ``upsert`` PQ-encodes
    new rows and appends them into spare list slots, ``delete`` tombstones
    rows in place (a mask write — the kernels already treat id -1 as
    padding), and ``compact`` rebuilds the lists tombstone-free into a
    fresh epoch. Everything a search reads lives in one ``EngineState``
    snapshot swapped atomically per mutation, so readers never see a torn
    epoch; ``engine.epoch`` counts the swaps. ``index``/``base``/
    ``live_bits`` are read-only views of the current snapshot.
    """

    def __init__(self, index: ivf_mod.IVFIndex, *, base: jax.Array | None = None,
                 coarse: str | object = "flat",
                 config: EngineConfig | None = None, hnsw_m: int = 16,
                 ef_construction: int = 64,
                 namespaces: jax.Array | None = None):
        # A store built with tombstones already present (unusual, but
        # partition/compact round-trips allow it) derives its live bitmap
        # here so the first search is already exact.
        n_tomb = int(jnp.sum(lists_mod.tombstone_counts(index.lists)))
        self._state = EngineState(
            index=index, base=base,
            live_bits=(lists_mod.live_filter_bits(index.lists)
                       if n_tomb else None),
            epoch=0, n_tombstones=n_tomb)
        self._mutate_lock = threading.RLock()
        self._locator: dict[int, tuple[int, int]] | None = None  # lazy
        # optional persist.WALWriter (attach_wal): every mutation is logged
        # and fsync'd BEFORE its state swap, so an acknowledged mutation
        # survives kill-9 (docs/persistence.md)
        self._wal = None
        # retained so a snapshot can record how to rebuild the coarse
        # structure deterministically from the centroids alone
        self.hnsw_m = int(hnsw_m)
        self.ef_construction = int(ef_construction)
        # (n_ns, nlist) bool membership: row t = the lists holding tenant
        # t's vectors. None = engine is namespace-free (docs/filtering.md).
        if namespaces is not None:
            namespaces = jnp.asarray(namespaces, dtype=bool)
            if namespaces.ndim != 2 or namespaces.shape[1] != index.lists.nlist:
                raise ValueError(
                    f"namespaces must be (n_ns, nlist={index.lists.nlist}) "
                    f"bool membership, got shape {namespaces.shape}")
        self.ns_member = namespaces
        self.config = config or EngineConfig()
        if isinstance(coarse, str):
            if coarse == "flat":
                self.coarse = coarse_mod.build_flat(index.centroids)
            elif coarse == "hnsw":
                self.coarse = coarse_mod.build_hnsw_coarse(
                    index.centroids, m=hnsw_m, ef_construction=ef_construction)
            elif coarse == "tree":
                self.coarse = coarse_mod.build_tree(jax.random.PRNGKey(0),
                                                    index.centroids)
            else:
                raise ValueError(
                    f"unknown coarse kind {coarse!r}; want one of {COARSE_KINDS}")
            kind = coarse
        else:
            self.coarse = coarse  # prebuilt object with .search(q, nprobe)
            kind = _coarse_kind_of(coarse)
        self.coarse_kind = kind
        validate_config(self.config, coarse_kind=kind,
                        has_base=base is not None)

    # -- state snapshot views (docs/mutability.md) --------------------------
    # All reads go through the current EngineState so a mutation can never
    # tear what a caller composes by hand; mutators replace the whole tuple.

    @property
    def index(self) -> ivf_mod.IVFIndex:
        return self._state.index

    @property
    def base(self) -> jax.Array | None:
        return self._state.base

    @property
    def live_bits(self) -> jax.Array | None:
        """Packed live-row bitmap; None while the store holds no tombstones."""
        return self._state.live_bits

    @property
    def epoch(self) -> int:
        """Monotonic state-swap counter: bumps on every upsert/delete/compact.

        After a mutation call returns with ``epoch == e``, every search
        *started* afterwards reflects at least epoch ``e`` (searches in
        flight during the swap finish on the epoch they snapshotted)."""
        return self._state.epoch

    @property
    def n_tombstones(self) -> int:
        """Tombstoned slots currently held (0 right after ``compact``)."""
        return self._state.n_tombstones

    def attach_wal(self, wal) -> None:
        """Attach a ``persist.WALWriter``: every later ``upsert``/``delete``/
        ``compact`` appends a checksummed, fsync'd record *before* installing
        its state swap, making the mutation durable the moment the call
        returns (docs/persistence.md). Pass ``None`` to detach (replay must
        not re-log)."""
        with self._mutate_lock:
            self._wal = wal

    def locate(self, gid: int) -> tuple[int, int] | None:
        """(list, slot) of a live row by global id, None if absent/deleted."""
        with self._mutate_lock:
            return self._locate(self._state).get(int(gid))

    def _locate(self, st: EngineState) -> dict[int, tuple[int, int]]:
        # callers hold _mutate_lock; the locator tracks st.index.lists
        if self._locator is None:
            self._locator = lists_mod.locate_rows(st.index.lists)
        return self._locator

    # -- live mutation (docs/mutability.md) ---------------------------------

    def upsert(self, ids, vecs, *, attrs=None) -> np.ndarray:
        """Insert or replace rows: PQ-encode, route, append into spare slots.

        ids: (B,) int global ids (>= 0, unique within the batch); vecs:
        (B, D) f32; attrs: optional (B,) i32 filter attributes (requires the
        store to carry an attrs column). Returns the (B,) i32 list each row
        was routed to (its nearest coarse centroid).

        A re-upserted existing id is tombstoned first, then appended like a
        new row — one atomic swap covers both, so no reader ever sees the
        id twice or not at all. Encoding is bitwise batch-independent
        (``core.ivf.encode_rows``), which is what keeps a mutated engine's
        codes identical to a from-scratch rebuild's. When a target list
        lacks spare capacity the store is compacted in place (reclaiming
        tombstones) and, if still short, grown to a larger cap — both under
        the same swap; autotune verdicts keyed to the retired cap are
        dropped. ``base`` grows and updates incrementally
        (zero-padded to 256-row multiples); the engine's namespace table is
        deliberately NOT touched — membership is a list-level property the
        caller owns.
        """
        ids = np.asarray(ids, np.int64)
        vecs = np.asarray(vecs, np.float32)
        if ids.ndim != 1 or vecs.ndim != 2 or ids.shape[0] != vecs.shape[0]:
            raise ValueError(
                f"upsert wants ids (B,) + vecs (B, D), got {ids.shape} and "
                f"{vecs.shape}")
        if ids.size == 0:
            return np.empty((0,), np.int32)
        if (ids < 0).any():
            raise ValueError("upsert ids must be >= 0 (-1 is the padding "
                             "sentinel)")
        if np.unique(ids).size != ids.size:
            raise ValueError("duplicate ids within one upsert batch — the "
                             "per-batch slot order would be ambiguous; "
                             "dedupe to the latest value first")
        avals = None if attrs is None else np.asarray(attrs, np.int32)
        with self._mutate_lock:
            st = self._state
            if vecs.shape[1] != st.index.centroids.shape[1]:
                raise ValueError(
                    f"upsert vecs have D={vecs.shape[1]}, engine expects "
                    f"D={st.index.centroids.shape[1]}")
            assign, packed = ivf_mod.encode_rows(
                st.index.centroids, st.index.codebook, vecs)
            loc = dict(self._locate(st))
            store = st.index.lists
            n_tomb = st.n_tombstones
            hit = [int(g) for g in ids if int(g) in loc]
            if hit:
                store = lists_mod.tombstone_rows(
                    store, np.array([loc[g][0] for g in hit], np.int32),
                    np.array([loc[g][1] for g in hit], np.int32))
                for g in hit:
                    del loc[g]
                n_tomb += len(hit)
            incoming = np.bincount(assign, minlength=store.nlist)
            if (np.asarray(store.sizes) + incoming > store.cap).any():
                # compact-then-grow: reclaiming tombstones may already free
                # enough spare slots; only grow cap when live rows + the
                # batch genuinely exceed it (to an aligned_cap, so the
                # filter-bitmap width stays exact and the stream kernels
                # keep a lane-aligned tile)
                live = np.asarray(lists_mod.live_counts(store))
                need = int((live + incoming).max())
                old_cap = store.cap
                new_cap = max(old_cap, lists_mod.aligned_cap(need))
                store = lists_mod.compact_lists(store, cap=new_cap)
                n_tomb = 0
                loc = lists_mod.locate_rows(store)
                if new_cap != old_cap:
                    ops_mod.clear_autotune_cache(nlist=store.nlist,
                                                 cap=old_cap)
            store, slots = lists_mod.append_rows(
                store, assign, packed, ids.astype(np.int32), avals)
            for g, l, s in zip(ids.tolist(), assign.tolist(), slots.tolist()):
                loc[int(g)] = (int(l), int(s))
            base = st.base
            if base is not None:
                need_rows = int(ids.max()) + 1
                n0 = base.shape[0]
                if need_rows > n0:
                    grown = -(-need_rows // 256) * 256
                    base = jnp.concatenate(
                        [base, jnp.zeros((grown - n0, base.shape[1]),
                                         base.dtype)])
                    ops_mod.clear_autotune_cache(kind="rerank", n=n0)
                base = base.at[jnp.asarray(ids.astype(np.int32))].set(
                    jnp.asarray(vecs))
            if self._wal is not None:
                # durable before visible: fsync the record, then swap
                self._wal.log_upsert(ids, vecs, avals)
            self._locator = loc
            self._state = EngineState(
                index=st.index._replace(lists=store), base=base,
                live_bits=(lists_mod.live_filter_bits(store)
                           if n_tomb else None),
                epoch=st.epoch + 1, n_tombstones=n_tomb)
        return assign

    def delete(self, ids) -> int:
        """Tombstone rows by global id; unknown/already-deleted ids are
        ignored. Returns the number of rows actually deleted.

        A delete is a mask write (ids/attrs at the slot become -1 — the
        padding convention every scan path masks); code bytes and the base
        row stay in place until ``compact``, unreachable because no list
        references them. After this returns, no later-started search can
        return the deleted ids.
        """
        ids = np.unique(np.asarray(ids, np.int64))
        with self._mutate_lock:
            st = self._state
            loc = dict(self._locate(st))
            found = [int(g) for g in ids if int(g) in loc]
            if not found:
                return 0
            store = lists_mod.tombstone_rows(
                st.index.lists,
                np.array([loc[g][0] for g in found], np.int32),
                np.array([loc[g][1] for g in found], np.int32))
            for g in found:
                del loc[g]
            if self._wal is not None:
                # a no-op delete returned above without logging; replaying
                # the full id batch re-derives the same `found` set
                self._wal.log_delete(ids)
            self._locator = loc
            self._state = EngineState(
                index=st.index._replace(lists=store), base=st.base,
                live_bits=lists_mod.live_filter_bits(store),
                epoch=st.epoch + 1,
                n_tombstones=st.n_tombstones + len(found))
            return len(found)

    def compact(self, cap: int | None = None) -> int:
        """Rebuild every list tombstone-free into a fresh epoch.

        Survivors keep their relative slot order; ``cap`` may grow (spare
        headroom for upserts) or shrink to fit. The rebuild happens off to
        the side and swaps in atomically — in-flight searches finish on the
        retiring epoch (this is what ``ServingLoop.compact`` runs under its
        dispatch lock). Autotune verdicts keyed to a retired cap are
        dropped so a stale (impl, tile) can't be served or re-persisted.
        Returns the number of tombstoned slots reclaimed.
        """
        with self._mutate_lock:
            st = self._state
            old_cap = st.index.lists.cap
            store = lists_mod.compact_lists(st.index.lists, cap=cap)
            if store.cap != old_cap:
                ops_mod.clear_autotune_cache(nlist=store.nlist, cap=old_cap)
            reclaimed = st.n_tombstones
            if self._wal is not None:
                self._wal.log_compact(cap)
            self._locator = lists_mod.locate_rows(store)
            self._state = EngineState(
                index=st.index._replace(lists=store), base=st.base,
                live_bits=None,
                epoch=st.epoch + 1, n_tombstones=0)
            return reclaimed

    # -- construction -------------------------------------------------------

    @classmethod
    def build(cls, key: jax.Array, train_x: jax.Array, base_x: jax.Array, *,
              m: int, nlist: int, coarse: str = "flat",
              config: EngineConfig | None = None, cap: int | None = None,
              coarse_iters: int = 20, pq_iters: int = 25,
              keep_base: bool = True, **coarse_kw) -> "SearchEngine":
        """Train + bucket + wrap: one call from raw vectors to a live engine."""
        index = ivf_mod.build_ivf(key, train_x, base_x, m=m, nlist=nlist,
                                  cap=cap, coarse_iters=coarse_iters,
                                  pq_iters=pq_iters)
        return cls(index, base=base_x if keep_base else None, coarse=coarse,
                   config=config, **coarse_kw)

    # -- stages (kept as methods for hand-composition; each delegates to the
    #    pure stage functions above) ----------------------------------------

    def select_probes(self, q: jax.Array, nprobe: int) -> jax.Array:
        """Stage 1 — coarse: pick up to nprobe promising lists (-1 = none).

        Applies the config's probe policy; the pruned-count counter is
        dropped here (hand-composition back-compat) — use ``coarse_probes``
        directly to observe it.
        """
        probes, _ = coarse_probes(
            self.coarse, q, nprobe=nprobe, ef=self.config.ef,
            probe_policy=self.config.probe_policy,
            margin_tau=self.config.margin_tau)
        return probes

    def scan(self, q: jax.Array, probe_ids: jax.Array
             ) -> tuple[jax.Array, jax.Array]:
        """Stage 2 — quantized scan: flattened ADC candidates per query."""
        dists, ids, _, _ = scan_candidates(self.index, q, probe_ids,
                                           scan_impl=self.config.scan_impl)
        return dists, ids

    # -- the unified entry points ------------------------------------------

    def _resolve(self, queries, nprobe, rerank_mult, filter_bits, namespaces,
                 st: EngineState, margin_tau=None):
        q = queries[None] if queries.ndim == 1 else queries
        nprobe = self.config.nprobe if nprobe is None else nprobe
        r = self.config.rerank_mult if rerank_mult is None else rerank_mult
        if margin_tau is not None and self.config.probe_policy != "margin":
            raise ValueError(
                "margin_tau override given but probe_policy is "
                f"{self.config.probe_policy!r}; build the engine with "
                "EngineConfig(probe_policy='margin')")
        if self.config.probe_policy == "margin":
            tau = (self.config.margin_tau if margin_tau is None
                   else margin_tau)
            tau = jnp.asarray(tau, jnp.float32)
            if tau.ndim not in (0, 1) or (tau.ndim == 1
                                          and tau.shape != (q.shape[0],)):
                raise ValueError(
                    f"margin_tau must be a scalar or ({q.shape[0]},) per-"
                    f"query widths, got shape {tau.shape}")
        else:
            tau = None
        if r and st.base is None:
            raise ValueError("exact re-rank requested but engine holds no "
                             "base vectors (build with keep_base=True)")
        if filter_bits is not None:
            nlist, cap = st.index.lists.nlist, st.index.lists.cap
            if (filter_bits.ndim != 2
                    or filter_bits.shape[0] != nlist
                    or filter_bits.shape[1] * 8 < cap):
                raise ValueError(
                    f"filter_bits must be (nlist={nlist}, "
                    f"W>=ceil(cap/8)={filter_words(cap)}) packed "
                    f"u8 (core.lists.pack_filter_mask), got shape "
                    f"{filter_bits.shape} — note a compaction/grow may have "
                    "changed cap; re-derive filters from the live store")
            # normalize to the exact W of this epoch's cap so the bitmap
            # broadcasts against live_bits (extra words carry no slots)
            filter_bits = filter_bits[:, :filter_words(cap)].astype(jnp.uint8)
        if namespaces is not None:
            if self.ns_member is None:
                raise ValueError(
                    "per-query namespaces given but the engine was built "
                    "without a namespace table (pass namespaces=(n_ns, nlist) "
                    "bool membership to SearchEngine)")
            namespaces = jnp.asarray(namespaces, jnp.int32)
            if namespaces.ndim == 0:
                namespaces = namespaces[None]
            if namespaces.shape != (q.shape[0],):
                raise ValueError(
                    f"namespaces must be ({q.shape[0]},) i32 (one per query, "
                    f"-1 = unrestricted), got shape {namespaces.shape}")
        return q, nprobe, r, filter_bits, namespaces, tau

    def search(self, queries: jax.Array, k: int = 10, *,
               nprobe: int | None = None, rerank_mult: int | None = None,
               filter_bits: jax.Array | None = None,
               namespaces: jax.Array | None = None,
               margin_tau: jax.Array | float | None = None) -> SearchResult:
        """Batched ANN search, staged. queries: (Q, D) or (D,).

        ``rerank_mult`` overrides the config: r > 0 refines the top r*k
        quantized candidates with exact float distances before the final
        merge (requires ``base``); 0 returns pure fast-scan results.

        ``filter_bits`` is an optional (nlist, W) packed per-row predicate
        bitmap (``core.lists.pack_filter_mask`` / ``filter_from_attrs``);
        ``namespaces`` an optional (Q,) i32 of per-query namespace ids into
        the engine's membership table, -1 = unrestricted. Both restrict
        which rows can appear in results — see docs/filtering.md for the
        exact contract.

        ``margin_tau`` (scalar or (Q,)) overrides the config's margin width
        for this request — the anytime latency/recall dial
        (docs/anytime.md). Only legal under ``probe_policy='margin'``.
        """
        st = self._state  # ONE snapshot read: the whole search is one epoch
        q, nprobe, r, fb, ns, tau = self._resolve(
            queries, nprobe, rerank_mult, filter_bits, namespaces, st,
            margin_tau)
        return _pipeline(self.coarse, st.index, st.base,
                         self.ns_member if ns is not None else None,
                         q, fb, ns, st.live_bits, tau, k=k, nprobe=nprobe,
                         r=r, scan_impl=self.config.scan_impl,
                         rerank_impl=self.config.rerank_impl,
                         ef=self.config.ef,
                         probe_policy=self.config.probe_policy,
                         early_exit=self.config.early_exit)

    def search_jit(self, queries: jax.Array, k: int = 10, *,
                   nprobe: int | None = None, rerank_mult: int | None = None,
                   filter_bits: jax.Array | None = None,
                   namespaces: jax.Array | None = None,
                   margin_tau: jax.Array | float | None = None
                   ) -> SearchResult:
        """Batched ANN search, fused: the whole pipeline in one ``jax.jit``.

        Same semantics and bit-identical results to ``search``, but a single
        XLA dispatch with ``(k, nprobe, rerank_mult)`` static — the serving
        path. Steady-state traffic over fixed shape buckets hits the shared
        process-wide compile cache (``fused_cache_size``) and never
        recompiles. Requires the coarse quantizer to be a jax pytree (all of
        ``core.coarse``'s are; a custom non-pytree object falls back to
        ``search``).

        ``filter_bits``/``namespaces``/``margin_tau`` (see ``search``) are
        traced, not static: the predicate/budget VALUES never key the
        compile cache — only their presence does (a None is absent from the
        pytree), so a stream of distinct filters or per-request tau dials
        compiles at most once per presence combination.
        """
        st = self._state  # ONE snapshot read: the whole search is one epoch
        q, nprobe, r, fb, ns, tau = self._resolve(
            queries, nprobe, rerank_mult, filter_bits, namespaces, st,
            margin_tau)
        if self.coarse_kind == "custom":
            # unknown coarse objects may not be jax pytrees => not traceable
            return self.search(queries, k, nprobe=nprobe, rerank_mult=r,
                               filter_bits=fb, namespaces=ns,
                               margin_tau=margin_tau)
        return _fused_pipeline(self.coarse, st.index, st.base,
                               self.ns_member if ns is not None else None,
                               q, fb, ns, st.live_bits, tau, k=k,
                               nprobe=nprobe, r=r,
                               scan_impl=self.config.scan_impl,
                               rerank_impl=self.config.rerank_impl,
                               ef=self.config.ef,
                               probe_policy=self.config.probe_policy,
                               early_exit=self.config.early_exit)


def _coarse_kind_of(coarse) -> str:
    if isinstance(coarse, coarse_mod.FlatCoarse):
        return "flat"
    if isinstance(coarse, coarse_mod.HNSWCoarse):
        return "hnsw"
    if isinstance(coarse, coarse_mod.TreeCoarse):
        return "tree"
    return "custom"
