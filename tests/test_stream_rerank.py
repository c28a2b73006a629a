"""Gather-free streaming exact re-rank + double-buffered DMA pipeline.

The 'stream' re-rank impl must be *bit-identical* to the gathered
``exact_rerank`` — through the raw kernel wrapper, ``finalize_candidates``,
and the whole engine (``search`` / ``search_jit`` / ``ShardedEngine`` on
both top-k drivers). Both impls share one distance expression
(``rerank_kernel.sq_l2_dists``), so every comparison here is
``assert_array_equal``, never allclose. Also covers: the gathered
``exact_distances`` against an independent subtraction-form oracle
(tolerance zero), the
double-buffered DMA refactor of the stream *scan* kernels (bit-identity
across multi-tile grids that exercise the two-slot rotation), re-rank
autotune dispatch, the v2 persistence schema + v1 migration, and the
memory-traffic acceptance (rerank-stage bytes >= 4x below gathered).
"""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import topk as topk_mod
from repro.data import vectors
from repro.engine import EngineConfig, SearchEngine, ShardedEngine
from repro.engine import rerank as rerank_mod
from repro.kernels import ops, ref
from repro.launch.hlo_analysis import xla_cost_dict


def _case(n=300, d=16, q=4, r=24, seed=0, ties=False):
    """(base, queries, cand) — ``ties=True`` draws base rows from a
    tiny integer lattice so duplicate rows (hence exactly-equal distances)
    genuinely occur and the lowest-position tie-break is exercised."""
    rng = np.random.default_rng(seed)
    if ties:
        base = rng.integers(-2, 3, (n, d)).astype(np.float32)
    else:
        base = rng.normal(size=(n, d)).astype(np.float32)
    base = jnp.asarray(base)
    queries = jnp.asarray(rng.normal(size=(q, d)).astype(np.float32))
    cand = rng.integers(0, n, (q, r)).astype(np.int32)
    return base, queries, cand


def _assert_rerank_parity(base, queries, cand, k, **kw):
    want_v, want_i = rerank_mod.exact_rerank(base, queries, jnp.asarray(cand),
                                             k)
    got_v, got_i = ops.rerank_stream_topk(base, queries, jnp.asarray(cand),
                                          k=k, **kw)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))


# ---------------------------------------------------------------------------
# kernel-level parity vs the gathered exact_rerank
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tile_r", [0, 8, 16])
def test_stream_rerank_matches_gathered(tile_r):
    base, q, cand = _case()
    _assert_rerank_parity(base, q, cand, 10, tile_r=tile_r)


def test_stream_rerank_ragged_and_all_invalid_rows():
    """-1 padding mid-pool, a fully-invalid query, and R < r*k raggedness:
    absent slots come back (+inf, -1) exactly like masked_topk's."""
    base, q, cand = _case(q=4, r=24)
    cand[0, 5:] = -1          # ragged: only 5 live candidates (< k)
    cand[1, :] = -1           # all invalid -> whole row absent
    cand[2, ::2] = -1         # interleaved padding
    _assert_rerank_parity(base, q, cand, 10, tile_r=8)
    vals, ids = ops.rerank_stream_topk(base, q, jnp.asarray(cand), k=10,
                                       tile_r=8)
    assert (np.asarray(ids)[1] == -1).all()
    assert np.isinf(np.asarray(vals)[1]).all()
    assert (np.asarray(ids)[0, 5:] == -1).all()


def test_stream_rerank_single_candidate_and_single_query():
    # k == R == 1: the smallest legal selection (k > R is rejected by the
    # gathered oracle's lax.top_k, so the contract floor is k <= R)
    base, q, cand = _case(q=1, r=1)
    _assert_rerank_parity(base, q, cand, 1)


def test_stream_rerank_ties_resolve_like_masked_topk():
    """Duplicate base rows => exactly equal f32 distances; the kernel's
    running-merge must pick the lowest candidate position, byte-for-byte
    like masked_topk — across chunk boundaries too (tile_r=4 splits the
    pool into 6 chunks)."""
    base, q, cand = _case(n=40, d=4, q=5, r=24, ties=True)
    _assert_rerank_parity(base, q, cand, 8, tile_r=4)


def test_stream_rerank_multi_chunk_shapes():
    """R >> tile_r drives many double-buffered chunks per query."""
    base, q, cand = _case(n=800, d=24, q=3, r=160)
    _assert_rerank_parity(base, q, cand, 10, tile_r=16)


def test_stream_rerank_duplicate_candidates_behave_like_gathered():
    """Candidate ids are unique by construction in the engine (each base
    vector lives in exactly one IVF list), so neither impl dedups — but a
    hand-composed pool CAN contain duplicates, and the two impls must then
    misbehave identically (the duplicate id may appear twice in the top-k,
    positions still lowest-first)."""
    base, q, cand = _case(q=3, r=16)
    cand[:, 8:] = cand[:, :8]          # every candidate duplicated
    _assert_rerank_parity(base, q, cand, 10, tile_r=8)


def test_stream_rerank_k_exceeds_live_candidates():
    """k > live candidates: exactly the live ones come back, then -1s."""
    base, q, cand = _case(q=2, r=6)
    cand[:, 3:] = -1
    _assert_rerank_parity(base, q, cand, 6, tile_r=8)


# ---------------------------------------------------------------------------
# the gathered fallback against an independent oracle
# ---------------------------------------------------------------------------

def test_exact_distances_norms_gemm_equals_subtraction_form_exactly():
    """Tolerance-ZERO parity of the gathered ``exact_distances`` against an
    independently written subtraction form ``Σ(x−q)²`` — on integer-valued
    f32 data, where every difference, square and partial sum is an
    exactly-representable integer (all magnitudes << 2^24), so no reduction
    order can make them differ. (The f64-anchored accuracy test on generic
    float data lives in tests/test_engine.py.)"""
    rng = np.random.default_rng(7)
    n, d, q, r = 200, 16, 6, 30
    base = jnp.asarray(rng.integers(-9, 10, (n, d)).astype(np.float32))
    queries = jnp.asarray(rng.integers(-9, 10, (q, d)).astype(np.float32))
    cand = rng.integers(0, n, (q, r)).astype(np.int32)
    cand[0, 10:] = -1
    cand = jnp.asarray(cand)
    got = rerank_mod.exact_distances(base, queries, cand)
    want = jax.jit(lambda b, qq, c: jnp.where(
        c >= 0,
        jnp.sum((b[jnp.maximum(c, 0)] - qq[:, None, :]) ** 2, axis=-1),
        jnp.inf))(base, queries, cand)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_finalize_candidates_routes_impls_identically():
    """finalize_candidates under 'gathered' vs 'stream' (and an unknown
    impl raising) — same (vals, ids, reranked) bit-for-bit."""
    base, q, cand = _case(q=3, r=20)
    rng = np.random.default_rng(3)
    flat_d = jnp.asarray(rng.normal(size=(3, 64)).astype(np.float32)) ** 2
    flat_ids = jnp.asarray(rng.permutation(300)[:64][None, :].repeat(3, 0)
                           .astype(np.int32))
    out = {}
    for impl in ("gathered", "stream"):
        out[impl] = rerank_mod.finalize_candidates(
            flat_d, flat_ids, base, q, 10, 3, rerank_impl=impl)
    for a, b in zip(out["gathered"], out["stream"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="unknown rerank impl"):
        rerank_mod.finalize_candidates(flat_d, flat_ids, base, q, 10, 3,
                                       rerank_impl="simd")


# ---------------------------------------------------------------------------
# engine end-to-end: search / search_jit / sharded (both drivers)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def trained_engine():
    ds = vectors.make_sift_like(n=5000, nt=2000, nq=16, d=32, ncl=32, seed=5)
    eng = SearchEngine.build(jax.random.PRNGKey(0), ds.train, ds.base,
                             m=8, nlist=32, coarse_iters=6, pq_iters=6)
    return ds, eng


@pytest.mark.parametrize("scan_impl", ["ref", "stream"])
def test_search_stream_rerank_bitidentical(scan_impl):
    ds, eng = trained_engine()
    eng_s = SearchEngine(eng.index, base=ds.base,
                         config=EngineConfig(scan_impl=scan_impl,
                                             rerank_impl="stream"))
    q = ds.queries[:6]
    res_ref = eng.search(q, 10, nprobe=6, rerank_mult=4)
    for res in (eng_s.search(q, 10, nprobe=6, rerank_mult=4),
                eng_s.search_jit(q, 10, nprobe=6, rerank_mult=4)):
        np.testing.assert_array_equal(np.asarray(res.ids),
                                      np.asarray(res_ref.ids))
        np.testing.assert_array_equal(np.asarray(res.dists),
                                      np.asarray(res_ref.dists))
        for a, b in zip(res.stats, res_ref.stats):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sharded_stream_rerank_matches_gathered_vmap_driver():
    """Stream re-rank on shard-local base partitions (local candidate ids,
    gids remap before the merge) == gathered, on the vmap named-axis
    driver."""
    ds, eng = trained_engine()
    eng_s = SearchEngine(eng.index, base=ds.base,
                         config=EngineConfig(rerank_impl="stream"))
    q = ds.queries[:4]
    res_g = ShardedEngine(eng, 3).search(q, 10, nprobe=4, rerank_mult=2)
    res_s = ShardedEngine(eng_s, 3).search(q, 10, nprobe=4, rerank_mult=2)
    np.testing.assert_array_equal(np.asarray(res_s.ids), np.asarray(res_g.ids))
    np.testing.assert_array_equal(np.asarray(res_s.dists),
                                  np.asarray(res_g.dists))
    for a, b in zip(res_s.stats, res_g.stats):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sharded_stream_rerank_matches_on_shard_map_mesh_driver():
    ds, eng = trained_engine()
    eng_s = SearchEngine(eng.index, base=ds.base,
                         config=EngineConfig(rerank_impl="stream"))
    n_dev = jax.device_count()
    mesh = jax.make_mesh((n_dev,), ("shards",))
    q = ds.queries[:4]
    res_g = ShardedEngine(eng, n_dev).search(q, 10, nprobe=4, rerank_mult=2,
                                             mesh=mesh)
    res_s = ShardedEngine(eng_s, n_dev).search(q, 10, nprobe=4, rerank_mult=2,
                                               mesh=mesh)
    np.testing.assert_array_equal(np.asarray(res_s.ids), np.asarray(res_g.ids))
    np.testing.assert_array_equal(np.asarray(res_s.dists),
                                  np.asarray(res_g.dists))


def test_engine_validates_rerank_impl():
    ds, eng = trained_engine()
    with pytest.raises(ValueError, match="rerank_impl"):
        SearchEngine(eng.index, base=ds.base,
                     config=EngineConfig(rerank_impl="simd"))


# ---------------------------------------------------------------------------
# double-buffered DMA pipeline: stream scan kernels stay bit-identical
# ---------------------------------------------------------------------------

def test_double_buffered_stream_scan_bitidentical_to_ref():
    """The two-slot pipeline refactor must not change a single bit of the
    stream scan outputs: multi-tile grids (>= 3 tiles per group, exercising
    both slot reuses), invalid probes interleaved mid-sequence (their
    skipped DMA must not desync the rotation), and duplicate probes."""
    rng = np.random.default_rng(11)
    nlist, cap, mh, tile = 6, 128, 4, 32   # 4 tiles/group
    store = jnp.asarray(rng.integers(0, 256, (nlist, cap, mh), np.uint8))
    probes = jnp.asarray(np.array([2, -1, 2, 5, -1, 0, 3, -1], np.int32))
    g = probes.shape[0]
    table = jnp.asarray(rng.integers(0, 256, (g, 2 * mh, 16), np.uint8))
    got = np.asarray(ops.fastscan_stream_grouped(table, store, probes,
                                                 tile_n=tile))
    want = np.asarray(ref.fastscan_grouped_ref(
        table, store[jnp.maximum(probes, 0)]))
    valid = np.asarray(probes) >= 0
    np.testing.assert_array_equal(got[valid], want[valid])
    assert (got[~valid] == 0).all()
    # and the result is tile-size invariant (different pipeline depths)
    got_1tile = np.asarray(ops.fastscan_stream_grouped(table, store, probes,
                                                       tile_n=cap))
    np.testing.assert_array_equal(got[valid], got_1tile[valid])


def test_double_buffered_stream_topk_bitidentical():
    """Same refactor check for the fused-reduction kernel: per-tile top-kc
    against the numpy stable-sort oracle across a multi-tile pipeline."""
    rng = np.random.default_rng(13)
    nlist, cap, mh, tile, kc = 4, 96, 2, 32, 5
    store = jnp.asarray(rng.integers(0, 4, (nlist, cap, mh), np.uint8))
    sizes = jnp.asarray(np.array([96, 50, 0, 33], np.int32))
    probes = jnp.asarray(np.array([0, -1, 1, 3, 2], np.int32))
    g = probes.shape[0]
    table = jnp.asarray(rng.integers(0, 3, (g, 2 * mh, 16), np.uint8))
    vals, slots = ops.fastscan_stream_topk(table, store, probes, sizes,
                                           keep=kc, tile_n=tile)
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
            order = np.argsort(seg, kind="stable")[:kc]
            k_real = min(kc, n_valid)
            np.testing.assert_array_equal(vals[gi, ti, :k_real], seg[order])
            np.testing.assert_array_equal(slots[gi, ti, :k_real], order + lo)
            assert (slots[gi, ti, k_real:] == -1).all()


# ---------------------------------------------------------------------------
# autotune: re-rank dispatch + v2 persistence + v1 migration
# ---------------------------------------------------------------------------

def test_rerank_impls_registered():
    assert ops.RERANK_IMPLS == ("gathered", "stream", "auto")
    from repro.engine import engine as engine_mod
    assert engine_mod.RERANK_IMPLS is ops.RERANK_IMPLS


def test_resolve_rerank_impl_sweeps_both_and_caches():
    ops.clear_autotune_cache()
    try:
        tuned = ops.resolve_rerank_impl(2, 12, 16, 5, 300)
        assert tuned.impl in ops.RERANK_CONCRETE
        swept = {name.split("@")[0] for name, _ in tuned.timings_us}
        assert swept == set(ops.RERANK_CONCRETE)
        assert ops.resolve_rerank_impl(2, 12, 16, 5, 300) is tuned  # cache hit
        assert ops.autotune_cache_size() == 1
        (key,) = ops.autotune_cache().keys()
        assert key[0] == "rerank" and key[3:] == (2, 12, 16, 5, 300)
        # N is part of the key: the gathered path's gather cost scales with
        # the table, so a verdict must never be shared across base sizes
        ops.resolve_rerank_impl(2, 12, 16, 5, 5000)
        assert ops.autotune_cache_size() == 2
        # 'auto' through the engine path is bit-identical to both concretes
        base, q, cand = _case(q=2, r=12, d=16)
        want = rerank_mod.finalize_candidates(
            jnp.abs(jnp.asarray(np.random.default_rng(0).normal(
                size=(2, 40)).astype(np.float32))),
            jnp.asarray(np.arange(80, dtype=np.int32).reshape(2, 40)),
            base, q, 5, 2, rerank_impl="gathered")
        got = rerank_mod.finalize_candidates(
            jnp.abs(jnp.asarray(np.random.default_rng(0).normal(
                size=(2, 40)).astype(np.float32))),
            jnp.asarray(np.arange(80, dtype=np.int32).reshape(2, 40)),
            base, q, 5, 2, rerank_impl="auto")
        for a, b in zip(want, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    finally:
        ops.clear_autotune_cache()


def test_autotune_roundtrips_scan_and_rerank_entries(tmp_path):
    path = str(tmp_path / "tuned.json")
    ops.clear_autotune_cache()
    try:
        t_scan = ops.resolve_grouped_impl(2, 32, 4, nlist=10)
        t_rr = ops.resolve_rerank_impl(2, 8, 8, 4, 100)
        assert ops.save_autotune_cache(path) == 2
        with open(path) as f:
            data = json.load(f)
        assert data["schema"] == "repro.autotune/v3"
        kinds = {e["kind"] for e in data["entries"]}
        assert kinds == {"scan", "rerank"}
        assert all("nlist" in e for e in data["entries"]
                   if e["kind"] == "scan")
        ops.clear_autotune_cache()
        assert ops.load_autotune_cache(path) == 2
        assert ops.resolve_grouped_impl(2, 32, 4, nlist=10) == t_scan
        assert ops.resolve_rerank_impl(2, 8, 8, 4, 100) == t_rr
        assert ops.autotune_cache_size() == 2  # both were cache hits
    finally:
        ops.clear_autotune_cache()


def test_autotune_v1_files_migrate_gracefully(tmp_path):
    """A v1 file (no kind/nlist) still loads: its scan verdicts re-key to
    nlist=g — the G-list store that sweep actually timed — and satisfy
    exactly those lookups; unknown impls are still skipped."""
    v1 = tmp_path / "v1.json"
    v1.write_text(json.dumps({
        "schema": "repro.autotune/v1",
        "entries": [
            {"backend": jax.default_backend(), "interpret": True, "g": 3,
             "cap": 64, "m": 4, "impl": "ref", "tile_n": 0,
             "timings_us": [["ref@0", 12.5]]},
            {"backend": "cpu", "interpret": True, "g": 1, "cap": 8, "m": 2,
             "impl": "gone-impl", "tile_n": 0, "timings_us": []},
        ]}))
    ops.clear_autotune_cache()
    try:
        assert ops.load_autotune_cache(str(v1)) == 1
        (key,) = ops.autotune_cache().keys()
        assert key == ("scan", jax.default_backend(), True, 3, 64, 4, 3, 1.0)
        # the migrated verdict is a hit for the shape it measured...
        tuned = ops.resolve_grouped_impl(3, 64, 4, interpret=True)
        assert tuned.impl == "ref" and ops.autotune_cache_size() == 1
        # ...but NOT for the same (G, cap, M) against a different store size
        ops.resolve_grouped_impl(3, 64, 4, nlist=20, interpret=True)
        assert ops.autotune_cache_size() == 2
    finally:
        ops.clear_autotune_cache()


# ---------------------------------------------------------------------------
# memory traffic: the point of the whole exercise
# ---------------------------------------------------------------------------

def test_stream_rerank_stage_bytes_accessed_4x_below_gathered():
    """cost_analysis bytes-accessed of the re-rank stage: the gather-free
    kernel must come in at least 4x under the gathered path at the
    acceptance shape (Q=32, k=10, r=4, D=128)."""
    rng = np.random.default_rng(17)
    n, d, q, k, r = 4096, 128, 32, 10, 4
    base = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    queries = jnp.asarray(rng.normal(size=(q, d)).astype(np.float32))
    cand = jnp.asarray(rng.integers(0, n, (q, r * k)).astype(np.int32))
    gathered = jax.jit(functools.partial(rerank_mod.exact_rerank, k=k))
    streamed = jax.jit(functools.partial(ops.rerank_stream_topk, k=k))
    b_gather = xla_cost_dict(gathered.lower(
        base, queries, cand).compile()).get("bytes accessed", 0.0)
    b_stream = xla_cost_dict(streamed.lower(
        base, queries, cand).compile()).get("bytes accessed", 0.0)
    assert b_gather > 0 and b_stream > 0
    assert b_stream * 4 <= b_gather, (b_stream, b_gather)
