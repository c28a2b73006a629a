"""Gather-free probe-streaming scan: parity, DMA-skip semantics, fused
reduction exactness, memory traffic, and autotune-cache persistence.

The 'stream' impl must be bit-identical to 'ref' on every real candidate —
through the raw kernels, ``scan_probes``, the reduced-pool
``scan_probes_stream``, and the whole engine (``search`` / ``search_jit``).
Integer ADC accumulation is exact, so every comparison here is
``assert_array_equal``, never allclose.
"""
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ivf
from repro.core.lists import ListStore
from repro.core.pq import PQCodebook
from repro.data import vectors
from repro.engine import (EngineConfig, QueryStats, SearchEngine,
                          ShardedEngine)
from repro.engine.engine import scan_candidates
from repro.kernels import fastscan_kernel as fk
from repro.kernels import ops, ref
from repro.launch.hlo_analysis import xla_cost_dict


def _synth_index(nlist, cap, m, *, d=None, seed=0, occupancy="ragged"):
    """An IVFIndex from raw random arrays — no k-means, instant to build.

    occupancy: 'ragged' (random sizes incl. empty lists), 'full', or an
    explicit (nlist,) array of sizes.
    """
    d = d or 4 * m
    assert d % m == 0
    rng = np.random.default_rng(seed)
    if isinstance(occupancy, str):
        sizes = (np.full(nlist, cap) if occupancy == "full"
                 else rng.integers(0, cap + 1, nlist))
    else:
        sizes = np.asarray(occupancy)
    codes = np.zeros((nlist, cap, m // 2), np.uint8)
    ids = np.full((nlist, cap), -1, np.int32)
    nxt = 0
    for li in range(nlist):
        s = int(sizes[li])
        codes[li, :s] = rng.integers(0, 256, (s, m // 2), np.uint8)
        ids[li, :s] = np.arange(nxt, nxt + s, dtype=np.int32)
        nxt += s
    index = ivf.IVFIndex(
        centroids=jnp.asarray(rng.normal(size=(nlist, d)).astype(np.float32)),
        codebook=PQCodebook(jnp.asarray(
            rng.normal(size=(m, 16, d // m)).astype(np.float32))),
        lists=ListStore(codes=jnp.asarray(codes), ids=jnp.asarray(ids),
                        sizes=jnp.asarray(sizes.astype(np.int32))),
    )
    base = rng.normal(size=(max(nxt, 1), d)).astype(np.float32)
    return index, jnp.asarray(base)


def _queries(index, q, seed=1):
    rng = np.random.default_rng(seed)
    d = index.centroids.shape[1]
    return jnp.asarray(rng.normal(size=(q, d)).astype(np.float32))


# ---------------------------------------------------------------------------
# kernel-level parity (gathered calling convention)
# ---------------------------------------------------------------------------

STREAM_SHAPES = [
    (1, 64, 4),     # G=1 (single query x single probe)
    (3, 100, 4),    # cap with no pow2 divisor >= 8 -> padded-copy path
    (4, 129, 3),    # ragged cap AND odd M//2
    (2, 300, 1),    # minimal M
    (5, 1024, 8),   # exact tile
]


@pytest.mark.parametrize("g,cap,mh", STREAM_SHAPES)
def test_stream_gathered_signature_matches_ref(g, cap, mh):
    rng = np.random.default_rng(g * 31 + cap + mh)
    table = jnp.asarray(rng.integers(0, 256, (g, 2 * mh, 16), np.uint8))
    codes = jnp.asarray(rng.integers(0, 256, (g, cap, mh), np.uint8))
    want = ref.fastscan_grouped_ref(table, codes)
    got = ops.fastscan_grouped(table, codes, impl="stream")
    assert got.dtype == jnp.int32 and got.shape == (g, cap)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_stream_multi_tile_grid():
    """tile_n < cap drives >1 DMA per group; results must be seamless."""
    rng = np.random.default_rng(5)
    table = jnp.asarray(rng.integers(0, 256, (3, 8, 16), np.uint8))
    codes = jnp.asarray(rng.integers(0, 256, (3, 256, 4), np.uint8))
    want = np.asarray(ref.fastscan_grouped_ref(table, codes))
    got = ops.fastscan_grouped(table, codes, impl="stream", tile_n=64)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_stream_inplace_skips_invalid_and_handles_duplicates():
    """In-place entry: duplicate probes scan the same list twice; invalid
    probes (-1) skip the DMA and emit zeros."""
    rng = np.random.default_rng(7)
    nlist, cap, mh = 6, 96, 4
    store = jnp.asarray(rng.integers(0, 256, (nlist, cap, mh), np.uint8))
    probes = jnp.asarray(np.array([2, 2, -1, 5, 0, -1], np.int32))
    table = jnp.asarray(rng.integers(0, 256, (6, 2 * mh, 16), np.uint8))
    got = np.asarray(ops.fastscan_stream_grouped(table, store, probes,
                                                 tile_n=32))
    want = np.asarray(ref.fastscan_grouped_ref(
        table, store[jnp.maximum(probes, 0)]))
    valid = np.asarray(probes) >= 0
    np.testing.assert_array_equal(got[valid], want[valid])
    assert (got[~valid] == 0).all()


def test_stream_topk_exact_with_occupancy_and_ties():
    """Fused per-tile selection == numpy stable-sort oracle, including
    occupancy masking and lowest-slot tie-breaks (u8 codes collide often
    at these sizes, so ties genuinely occur)."""
    rng = np.random.default_rng(11)
    nlist, cap, mh, tile, kc = 5, 64, 2, 32, 6
    store = jnp.asarray(rng.integers(0, 4, (nlist, cap, mh), np.uint8))
    sizes = jnp.asarray(np.array([64, 40, 0, 33, 1], np.int32))
    probes = jnp.asarray(np.array([0, 1, 2, 3, 4, -1], np.int32))
    g = probes.shape[0]
    table = jnp.asarray(rng.integers(0, 3, (g, 2 * mh, 16), np.uint8))
    vals, slots = ops.fastscan_stream_topk(table, store, probes, sizes,
                                           keep=kc, tile_n=tile)
    assert vals.shape == (g, cap // tile, kc)
    vals, slots = np.asarray(vals), np.asarray(slots)
    acc = np.asarray(ref.fastscan_grouped_ref(
        table, store[jnp.maximum(probes, 0)]))
    for gi in range(g):
        lid = int(probes[gi])
        if lid < 0:
            assert (slots[gi] == -1).all()
            continue
        for ti in range(cap // tile):
            lo = ti * tile
            n_valid = int(np.clip(int(sizes[lid]) - lo, 0, tile))
            seg = acc[gi, lo:lo + n_valid]
            order = np.argsort(seg, kind="stable")[:kc]  # ties: lowest slot
            k_real = min(kc, n_valid)
            np.testing.assert_array_equal(vals[gi, ti, :k_real], seg[order])
            np.testing.assert_array_equal(slots[gi, ti, :k_real], order + lo)
            assert (slots[gi, ti, k_real:] == -1).all()


# ---------------------------------------------------------------------------
# tiles past a list's occupancy: no copy, no scan, the same output
# ---------------------------------------------------------------------------

# tile 16, cap 64 (4 tiles): list sizes 0, 1, tile-1, tile, tile+1 and cap
DEAD_TILE, DEAD_CAP = 16, 64
DEAD_SIZES = np.array([0, 1, 15, 16, 17, 64], np.int32)
# Q=4 queries x P=4 probes: every list, duplicates, -1s, an all-invalid row
DEAD_PROBES = np.array([[0, 1, 2, 3], [4, 5, 5, -1],
                        [-1, -1, -1, -1], [5, 3, 4, 1]], np.int32)


def _scan_every_tile_oracle(table, store, probes, sizes, kc, tile, *,
                            fbits=None, scales=None, biases=None, gpq=0):
    """What the stream top-k kernel emits when every (group, tile) step of
    a valid probe is scanned, dead tiles included, in NumPy.

    Step by step in grid order: mask slots past the occupancy (and, with
    ``fbits`` (G, cap) bool, filtered rows) to the sentinel, keep the kc
    smallest (lowest slot first on ties), sentinel vals and -1 slots where
    fewer exist. With ``scales`` the early-exit bound prunes as in
    ``_stream_topk_prune_kernel``: a step is scanned iff its probe is valid
    and its group's bound beats the query's running kc-th best, and
    ``skipped`` is 1 for every other valid-probe step.
    """
    sent = np.iinfo(np.int32).max
    g = probes.shape[0]
    n_tiles = store.shape[1] // tile
    acc = np.asarray(ref.fastscan_grouped_ref(
        table, store[jnp.maximum(probes, 0)]))
    tq = np.asarray(table).astype(np.int64)
    vals = np.full((g, n_tiles, kc), sent, np.int32)
    slots = np.full((g, n_tiles, kc), -1, np.int32)
    skipped = np.zeros((g, n_tiles), np.int32)
    run = thr = None
    for gi in range(g):
        lid = int(probes[gi])
        if scales is not None:
            if gi % gpq == 0:
                run = np.full(kc, np.inf, np.float32)
                thr = np.float32(np.inf)
            bound = (scales[gi] * np.float32(tq[gi].min(-1).sum())
                     + biases[gi])
        for ti in range(n_tiles):
            if lid < 0:
                continue
            if scales is not None and not bound < thr:
                skipped[gi, ti] = 1
                continue
            lo = ti * tile
            seg = acc[gi, lo:lo + tile].astype(np.int64)
            ok = np.arange(lo, lo + tile) < int(sizes[lid])
            if fbits is not None:
                ok &= fbits[gi, lo:lo + tile]
            seg = np.where(ok, seg, sent)
            order = np.argsort(seg, kind="stable")[:kc]
            real = seg[order] != sent
            vals[gi, ti, :] = seg[order]
            slots[gi, ti, :] = np.where(real, order + lo, -1)
            if scales is not None:
                d = np.where(slots[gi, ti] >= 0,
                             scales[gi] * vals[gi, ti].astype(np.float32)
                             + biases[gi], np.float32(np.inf))
                run = np.sort(np.concatenate([run, d.astype(np.float32)]))[:kc]
                thr = run.max()
    return vals, slots, skipped


@pytest.mark.parametrize("filtered", [False, True],
                         ids=["unfiltered", "filtered"])
@pytest.mark.parametrize("early_exit", [False, True],
                         ids=["full", "early_exit"])
def test_stream_topk_dead_tiles_bitidentical(filtered, early_exit):
    """Raw (vals, slots) — and ``skipped`` under early exit — equal the
    every-tile formulation on a ragged store: skipping the tiles past a
    list's occupancy changes no output bit."""
    from repro.core.lists import pack_filter_mask
    rng = np.random.default_rng(41 + 2 * filtered + early_exit)
    nlist, mh, kc = DEAD_SIZES.shape[0], 2, 4
    probes = DEAD_PROBES.reshape(-1)
    g = probes.shape[0]
    store = jnp.asarray(rng.integers(0, 256, (nlist, DEAD_CAP, mh), np.uint8))
    table = jnp.asarray(rng.integers(0, 64, (g, 2 * mh, 16), np.uint8))
    kw, oracle_kw = {}, {}
    if filtered:
        mask = rng.random((nlist, DEAD_CAP)) < 0.7
        kw["filter_bits"] = pack_filter_mask(jnp.asarray(mask))
        oracle_kw["fbits"] = mask[np.maximum(probes, 0)]
    if early_exit:
        # dyadic scales and integer biases keep every f32 product and sum
        # exact, so the oracle's threshold compares bit for bit; the spread
        # of biases across a query's probes makes the bound prune
        scales = (2.0 ** -rng.integers(0, 3, g)).astype(np.float32)
        biases = rng.integers(0, 3000, g).astype(np.float32)
        kw.update(early_exit=True, groups_per_query=DEAD_PROBES.shape[1],
                  scales=jnp.asarray(scales), biases=jnp.asarray(biases))
        oracle_kw.update(scales=scales, biases=biases,
                         gpq=DEAD_PROBES.shape[1])
    out = ops.fastscan_stream_topk(table, store, jnp.asarray(probes),
                                   jnp.asarray(DEAD_SIZES), keep=kc,
                                   tile_n=DEAD_TILE, **kw)
    want_v, want_s, want_skip = _scan_every_tile_oracle(
        table, store, probes, DEAD_SIZES, kc, DEAD_TILE, **oracle_kw)
    np.testing.assert_array_equal(np.asarray(out[0]), want_v)
    np.testing.assert_array_equal(np.asarray(out[1]), want_s)
    if early_exit:
        np.testing.assert_array_equal(np.asarray(out[2]), want_skip)
        assert 0 < want_skip.sum() < (probes >= 0).sum() * 4  # it pruned


def _blocks(probes, plan, n_tiles):
    """The code block index each sequential grid step lands."""
    src = np.asarray(plan)
    return [(max(int(probes[s // n_tiles]), 0), int(s % n_tiles)) for s in src]


@pytest.mark.parametrize("probes", [
    DEAD_PROBES.reshape(-1),
    np.array([-1, 3, -1, 0, 5, 2, -1], np.int32),  # leading -1, empty list
], ids=["grid", "leading_invalid"])
def test_fetch_plan_copies_each_live_tile_once(probes):
    """With sizes, each live (probe, tile) step lands its own block, once;
    every dead or invalid step repeats its predecessor's block (the first
    live block, before any), so the pipeline copies exactly the live tiles."""
    n_tiles = DEAD_CAP // DEAD_TILE
    live = np.asarray(fk.live_tiles(jnp.asarray(probes),
                                    jnp.asarray(DEAD_SIZES), n_tiles,
                                    DEAD_TILE)).reshape(-1)
    want_live = np.concatenate([
        np.arange(n_tiles) * DEAD_TILE < (DEAD_SIZES[p] if p >= 0 else 0)
        for p in probes])
    np.testing.assert_array_equal(live, want_live)
    blocks = _blocks(probes, fk._fetch_plan(
        jnp.asarray(probes), n_tiles, jnp.asarray(DEAD_SIZES), DEAD_TILE),
        n_tiles)
    first = int(np.argmax(live))
    for s, b in enumerate(blocks):
        if live[s]:
            assert b == (int(probes[s // n_tiles]), s % n_tiles), s
        else:
            assert b == (blocks[s - 1] if s else blocks[first]), s
    copies = 1 + sum(blocks[s] != blocks[s - 1] for s in range(1, len(blocks)))
    assert copies == live.sum()
    # without sizes the plan is the probe-only one: every tile of a valid
    # probe is fetched
    plain = _blocks(probes, fk._fetch_plan(jnp.asarray(probes), n_tiles),
                    n_tiles)
    valid = np.repeat(probes >= 0, n_tiles)
    assert sum(plain[s] != plain[s - 1]
               for s in range(1, len(plain))) + 1 == valid.sum()


def test_tiles_scanned_counts_live_tiles():
    """``tiles_scanned`` = sum of ceil(size / tile) over valid probes, 0 for
    an all-invalid row; under early exit the pruned live tiles come off it;
    0 on the gathered impls."""
    index, _ = _synth_index(DEAD_SIZES.shape[0], DEAD_CAP, 4,
                            occupancy=DEAD_SIZES, seed=43)
    q = _queries(index, DEAD_PROBES.shape[0], seed=44)
    probes = jnp.asarray(DEAD_PROBES)
    want = _want_tiles_scanned(index, probes, DEAD_TILE)
    assert want[2] == 0 and (want[[0, 1, 3]] > 0).all()
    _, _, skipped, scanned = ivf.scan_probes_stream(index, q, probes, keep=4,
                                                    tile_n=DEAD_TILE)
    np.testing.assert_array_equal(np.asarray(scanned), want)
    np.testing.assert_array_equal(np.asarray(skipped), 0)
    _, _, _, scanned_ref = scan_candidates(index, q, probes, scan_impl="ref",
                                           keep=4)
    np.testing.assert_array_equal(np.asarray(scanned_ref), 0)
    # early exit: push lists 3.. far from the queries so the bound prunes
    cen = np.array(index.centroids)
    cen[3:] += 50.0
    far = index._replace(centroids=jnp.asarray(cen))
    _, _, skipped, scanned = ivf.scan_probes_stream(
        far, q, probes, keep=4, tile_n=DEAD_TILE, early_exit=True)
    skipped, scanned = np.asarray(skipped), np.asarray(scanned)
    valid_tiles = (DEAD_PROBES >= 0).sum(axis=1) * (DEAD_CAP // DEAD_TILE)
    assert skipped.sum() > 0
    assert (scanned <= want).all() and scanned.sum() < want.sum()
    assert (scanned + skipped <= valid_tiles).all()
    assert scanned[2] == 0 and skipped[2] == 0
    # exact: the live tiles less the live ones the bound pruned
    qlut = ivf.probe_tables(far, q, probes)
    g = probes.size
    _, _, raw_skip = ops.fastscan_stream_topk(
        qlut.table_q8.reshape(g, 4, 16), far.lists.codes, probes.reshape(-1),
        far.lists.sizes, keep=4, tile_n=DEAD_TILE, early_exit=True,
        groups_per_query=probes.shape[1], scales=qlut.scale.reshape(-1),
        biases=jnp.sum(qlut.bias, axis=-1).reshape(-1))
    live = np.asarray(fk.live_tiles(probes.reshape(-1), far.lists.sizes,
                                    DEAD_CAP // DEAD_TILE, DEAD_TILE))
    pruned_live = (live & (np.asarray(raw_skip) > 0)).reshape(4, -1).sum(1)
    np.testing.assert_array_equal(scanned, want - pruned_live)
    np.testing.assert_array_equal(skipped, np.asarray(raw_skip).reshape(
        4, -1).sum(1))


def test_serving_loop_does_not_fetch_tiles_scanned():
    """ServingLoop's per-batch device-to-host fetch stays the nine arrays it
    was (ids, dists and seven counters): ``tiles_scanned`` is left on the
    device."""
    from repro.serving import ServingLoop
    ds, eng = trained_engine()
    eng_s = SearchEngine(eng.index, base=ds.base,
                         config=EngineConfig(scan_impl="stream"))
    fetched = []

    class Recorder:
        def __init__(self, name, arr):
            self.name, self.arr = name, arr

        def __array__(self, dtype=None, copy=None):
            fetched.append(self.name)
            return np.asarray(self.arr, dtype=dtype)

    loop = ServingLoop(eng_s, rerank_mult=2, buckets=(4,), max_wait_s=0.005)
    call = loop._call_engine

    def recording_call(*a, **kw):
        res = call(*a, **kw)
        stats = res.stats._replace(**{
            f: Recorder(f, getattr(res.stats, f)) for f in QueryStats._fields})
        return res._replace(dists=Recorder("dists", res.dists),
                            ids=Recorder("ids", res.ids), stats=stats)

    loop._call_engine = recording_call
    loop.start(warmup=False)
    try:
        futs = [loop.submit(ds.queries[i], k=10) for i in range(3)]
        for f in futs:
            f.result(timeout=60)
    finally:
        loop.stop()
    assert fetched, "no batch was served"
    per_batch = set(QueryStats._fields) - {"tiles_scanned"} | {"dists", "ids"}
    assert set(fetched) == per_batch and len(per_batch) == 9
    assert len(fetched) % 9 == 0


def test_stream_registered_in_impl_registries():
    assert "stream" in ops.GROUPED_IMPLS
    assert "stream" in ops.SCAN_IMPLS
    assert "stream" not in ops.IMPLS  # flat scan has no probe indirection


def test_autotune_sweep_times_stream():
    ops.clear_autotune_cache()
    try:
        tuned = ops.resolve_grouped_impl(2, 64, 8)
        swept = {name.split("@")[0] for name, _ in tuned.timings_us}
        assert "stream" in swept
        # stream tiles in the sweep must divide cap (in-place constraint),
        # so the verdict's pair is exactly what scan_probes will execute
        for name, _ in tuned.timings_us:
            impl, tile = name.split("@")
            if impl == "stream":
                assert 64 % int(tile) == 0
    finally:
        ops.clear_autotune_cache()


# ---------------------------------------------------------------------------
# gather early-mask bugfix
# ---------------------------------------------------------------------------

def test_gather_masks_codes_for_invalid_probes():
    """An invalid probe must gather ZERO codes, not list 0's real codes —
    otherwise the gathered impls scan work that QueryStats.codes_scanned
    never counted and that the stream kernel (which skips the DMA) never
    does."""
    index, _ = _synth_index(4, 32, 8, occupancy="full")
    probes = jnp.asarray(np.array([[0, -1], [-1, 3]], np.int32))
    codes, ids = index.lists.gather(probes)
    codes, ids = np.asarray(codes), np.asarray(ids)
    assert (codes[0, 1] == 0).all() and (codes[1, 0] == 0).all()
    assert (ids[0, 1] == -1).all() and (ids[1, 0] == -1).all()
    np.testing.assert_array_equal(codes[0, 0], np.asarray(index.lists.codes[0]))
    np.testing.assert_array_equal(
        np.asarray(index.lists.gather_ids(probes)), ids)


# ---------------------------------------------------------------------------
# scan_probes / scan_probes_stream parity
# ---------------------------------------------------------------------------

def _assert_scan_parity(index, q, probes):
    d_ref, i_ref = ivf.scan_probes(index, q, probes, impl="ref")
    d_s, i_s = ivf.scan_probes(index, q, probes, impl="stream")
    i_ref, i_s = np.asarray(i_ref), np.asarray(i_s)
    np.testing.assert_array_equal(i_s, i_ref)
    valid = i_ref >= 0
    np.testing.assert_array_equal(np.asarray(d_s)[valid],
                                  np.asarray(d_ref)[valid])
    return d_ref, i_ref


def test_scan_probes_stream_impl_parity_ragged():
    index, _ = _synth_index(6, 100, 8, occupancy="ragged")
    q = _queries(index, 3)
    probes = jnp.asarray(np.array([[0, 1], [5, 5], [2, 4]], np.int32))
    _assert_scan_parity(index, q, probes)  # incl. duplicate probes (row 1)


def test_scan_probes_stream_impl_parity_invalid_rows():
    index, _ = _synth_index(4, 64, 4)
    q = _queries(index, 3)
    probes = jnp.asarray(np.array([[-1, -1], [0, -1], [3, 1]], np.int32))
    _assert_scan_parity(index, q, probes)  # incl. an all-invalid row


def test_scan_probes_stream_reduced_pool_selection_parity():
    """The reduced (P*n_tiles*kc) pool must yield the exact same top-keep
    selection as the full (P*cap) pool — multi-tile, ragged occupancy,
    duplicate + invalid probes all at once."""
    from repro.core import topk as topk_mod
    index, _ = _synth_index(6, 128, 8, occupancy="ragged", seed=3)
    q = _queries(index, 4)
    probes = jnp.asarray(np.array(
        [[0, 1, 2], [3, 3, -1], [-1, -1, -1], [5, 4, 0]], np.int32))
    keep = 10
    d_full, i_full = ivf.scan_probes(index, q, probes, impl="ref")
    qq = d_full.shape[0]
    fd, fi = d_full.reshape(qq, -1), i_full.reshape(qq, -1)
    want_v, want_pos = topk_mod.masked_topk(fd, fi >= 0, keep)
    want_i = topk_mod.gather_ids(fi, want_pos)

    rd, ri, _, _ = ivf.scan_probes_stream(index, q, probes, keep=keep,
                                          tile_n=32)
    assert rd.shape[1] < fd.shape[1]  # the pool genuinely shrank
    got_v, got_pos = topk_mod.masked_topk(rd, ri >= 0, keep)
    got_i = topk_mod.gather_ids(ri, got_pos)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))


def test_scan_candidates_keep_routes_stream_to_reduced_pool():
    index, _ = _synth_index(5, 64, 8, seed=9)
    q = _queries(index, 2)
    probes = jnp.asarray(np.array([[0, 2], [4, 1]], np.int32))
    full_d, full_i, full_ts, _ = scan_candidates(index, q, probes,
                                                 scan_impl="ref", keep=5)
    red_d, red_i, _, _ = scan_candidates(index, q, probes,
                                         scan_impl="stream", keep=5)
    assert full_d.shape[1] == 2 * 64
    assert red_d.shape[1] < full_d.shape[1]
    # the tiles-skipped counter is all zeros without early_exit
    np.testing.assert_array_equal(np.asarray(full_ts), 0)
    # both pools contain the same top-5 (checked end-to-end elsewhere);
    # keep=None falls back to the full pool under every impl
    s_d, s_i, _, _ = scan_candidates(index, q, probes, scan_impl="stream")
    assert s_d.shape == full_d.shape
    valid = np.asarray(full_i) >= 0
    np.testing.assert_array_equal(np.asarray(s_i), np.asarray(full_i))
    np.testing.assert_array_equal(np.asarray(s_d)[valid],
                                  np.asarray(full_d)[valid])


# ---------------------------------------------------------------------------
# engine end-to-end: bit-identical search/search_jit, multi-tile cap
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def trained_engine():
    ds = vectors.make_sift_like(n=5000, nt=2000, nq=16, d=32, ncl=32, seed=3)
    eng = SearchEngine.build(jax.random.PRNGKey(0), ds.train, ds.base,
                             m=8, nlist=32, coarse_iters=6, pq_iters=6)
    return ds, eng


def _want_tiles_scanned(index, probes, tile):
    """sum over valid probes of ceil(size / tile): the cap tiles holding a
    row, counted in NumPy from the store's occupancy."""
    sizes = np.asarray(index.lists.sizes)
    p = np.asarray(probes)
    per = np.where(p >= 0, -(-sizes[np.maximum(p, 0)] // tile), 0)
    return per.sum(axis=1)


def assert_stats_match_but_tiles_scanned(stats, stats_ref):
    """Every QueryStats counter equal, except ``tiles_scanned``: the stream
    scan's own counter, 0 on the gathered impls."""
    for name in QueryStats._fields:
        if name != "tiles_scanned":
            np.testing.assert_array_equal(np.asarray(getattr(stats, name)),
                                          np.asarray(getattr(stats_ref, name)),
                                          err_msg=name)


@pytest.mark.parametrize("rerank_mult", [0, 2])
def test_search_stream_bitidentical_to_ref(rerank_mult):
    ds, eng = trained_engine()
    eng_s = SearchEngine(eng.index, base=ds.base,
                         config=EngineConfig(scan_impl="stream"))
    q = ds.queries[:6]
    res_ref = eng.search(q, 10, nprobe=6, rerank_mult=rerank_mult)
    np.testing.assert_array_equal(np.asarray(res_ref.stats.tiles_scanned), 0)
    want_ts = _want_tiles_scanned(eng.index, eng.select_probes(q, 6),
                                  ops._stream_tile(eng.index.cap))
    assert (want_ts > 0).all()
    for res in (eng_s.search(q, 10, nprobe=6, rerank_mult=rerank_mult),
                eng_s.search_jit(q, 10, nprobe=6, rerank_mult=rerank_mult)):
        np.testing.assert_array_equal(np.asarray(res.ids),
                                      np.asarray(res_ref.ids))
        np.testing.assert_array_equal(np.asarray(res.dists),
                                      np.asarray(res_ref.dists))
        assert_stats_match_but_tiles_scanned(res.stats, res_ref.stats)
        np.testing.assert_array_equal(np.asarray(res.stats.tiles_scanned),
                                      want_ts)


def test_search_stream_multi_tile_cap():
    """cap > TILE_N forces a multi-tile stream grid through the engine."""
    index, base = _synth_index(4, 2048, 8, occupancy="ragged", seed=13)
    q = _queries(index, 3)
    eng_r = SearchEngine(index, base=base, config=EngineConfig(scan_impl="ref"))
    eng_s = SearchEngine(index, base=base,
                         config=EngineConfig(scan_impl="stream"))
    res_r = eng_r.search(q, 5, nprobe=3, rerank_mult=2)
    res_s = eng_s.search(q, 5, nprobe=3, rerank_mult=2)
    np.testing.assert_array_equal(np.asarray(res_s.ids), np.asarray(res_r.ids))
    np.testing.assert_array_equal(np.asarray(res_s.dists),
                                  np.asarray(res_r.dists))


def test_sharded_stream_matches_sharded_ref():
    ds, eng = trained_engine()
    eng_s = SearchEngine(eng.index, base=ds.base,
                         config=EngineConfig(scan_impl="stream"))
    q = ds.queries[:4]
    res_r = ShardedEngine(eng, 3).search(q, 10, nprobe=4, rerank_mult=2)
    res_s = ShardedEngine(eng_s, 3).search(q, 10, nprobe=4, rerank_mult=2)
    np.testing.assert_array_equal(np.asarray(res_s.ids), np.asarray(res_r.ids))
    np.testing.assert_array_equal(np.asarray(res_s.dists),
                                  np.asarray(res_r.dists))
    assert_stats_match_but_tiles_scanned(res_s.stats, res_r.stats)
    np.testing.assert_array_equal(np.asarray(res_r.stats.tiles_scanned), 0)
    # summed over shards: every scanned tile holds at least one row, and
    # every probed row lies in a scanned tile
    ts = np.asarray(res_s.stats.tiles_scanned)
    cs = np.asarray(res_s.stats.codes_scanned)
    assert ((ts > 0) == (cs > 0)).all() and (ts <= cs).all()


# ---------------------------------------------------------------------------
# memory traffic: the point of the whole exercise
# ---------------------------------------------------------------------------

def test_stream_scan_stage_bytes_accessed_4x_below_gathered():
    """cost_analysis bytes-accessed of the scan stage: the gather-free path
    must come in at least 4x under the gathered path at the acceptance
    shape (Q=32, P=16, cap=1024, M=16)."""
    index, _ = _synth_index(64, 1024, 16, d=32, occupancy="full", seed=17)
    q = _queries(index, 32)
    probes = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, (32, 16), np.int32))

    gathered = jax.jit(lambda i, qq, p: ivf.scan_probes(i, qq, p, impl="ref"))
    streamed = jax.jit(functools.partial(ivf.scan_probes_stream, keep=40))
    b_gather = xla_cost_dict(
        gathered.lower(index, q, probes).compile()).get("bytes accessed", 0.0)
    b_stream = xla_cost_dict(
        streamed.lower(index, q, probes).compile()).get("bytes accessed", 0.0)
    assert b_gather > 0 and b_stream > 0
    assert b_stream * 4 <= b_gather, (b_stream, b_gather)


# ---------------------------------------------------------------------------
# autotune-cache persistence
# ---------------------------------------------------------------------------

def test_autotune_cache_save_load_roundtrip(tmp_path):
    path = str(tmp_path / "tuned.json")
    ops.clear_autotune_cache()
    try:
        tuned = ops.resolve_grouped_impl(2, 32, 4)
        assert ops.save_autotune_cache(path) == 1
        ops.clear_autotune_cache()
        assert ops.autotune_cache_size() == 0
        assert ops.load_autotune_cache(path) == 1
        (got,) = ops.autotune_cache().values()
        assert got == tuned
        # a loaded verdict is a cache hit: resolving again runs no sweep
        # (it would append a new entry only on a miss)
        assert ops.resolve_grouped_impl(2, 32, 4) == tuned
        assert ops.autotune_cache_size() == 1
        # loading again is idempotent (in-process verdicts win)
        assert ops.load_autotune_cache(path) == 0
    finally:
        ops.clear_autotune_cache()


def test_autotune_cache_load_tolerates_garbage(tmp_path):
    assert ops.load_autotune_cache(str(tmp_path / "missing.json")) == 0
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert ops.load_autotune_cache(str(bad)) == 0
    stale = tmp_path / "stale.json"
    stale.write_text(json.dumps({
        "schema": "repro.autotune/v1",
        "entries": [{"backend": "cpu", "interpret": True, "g": 1, "cap": 8,
                     "m": 2, "impl": "gone-impl", "tile_n": 0,
                     "timings_us": []}]}))
    assert ops.load_autotune_cache(str(stale)) == 0  # unknown impl skipped
    assert ops.autotune_cache_size() == 0


def test_serving_loop_warmup_cache_skips_resweep(tmp_path):
    from repro.serving import ServingLoop
    path = str(tmp_path / "fleet.json")
    # an index shape no other test uses, so the process-wide fused-jit cache
    # cannot already hold this signature and the first warmup MUST trace
    # (and therefore sweep)
    index, base = _synth_index(10, 48, 6, d=24, seed=23)
    eng_a = SearchEngine(index, base=base,
                         config=EngineConfig(scan_impl="auto"))
    ops.clear_autotune_cache()
    try:
        loop = ServingLoop(eng_a, rerank_mult=2, buckets=(2,),
                           warmup_cache=path)
        loop.start(warmup=True, warmup_ks=(7,))
        loop.stop()
        assert loop.metrics().autotuned >= 1  # first boot paid the sweep
        with open(path) as f:
            assert len(json.load(f)["entries"]) >= 1
        ops.clear_autotune_cache()  # "new replica"
        loop2 = ServingLoop(eng_a, rerank_mult=2, buckets=(2,),
                            warmup_cache=path)
        loop2.start(warmup=True, warmup_ks=(7,))
        loop2.stop()
        # the hook re-populated the table from the fleet file (the roundtrip
        # test proves a loaded verdict short-circuits the sweep) and no new
        # sweeps ran during warmup
        assert ops.autotune_cache_size() >= 1
        assert loop2.metrics().autotuned == 0
    finally:
        ops.clear_autotune_cache()
