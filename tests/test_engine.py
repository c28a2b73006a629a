"""Unified engine tests: stage-composition parity, exact re-rank, QueryStats,
grouped-kernel agreement, and the shard-parallel merge."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import coarse, ivf, metrics
from repro.core import topk as topk_mod
from repro.core.kmeans import pairwise_sqdist
from repro.core.lists import ListStore, partition_lists
from repro.data import vectors
from repro.engine import (EngineConfig, SearchEngine, ShardedEngine,
                          exact_distances, exact_rerank)


@functools.lru_cache(maxsize=None)
def small_ds():
    return vectors.make_sift_like(n=5000, nt=2000, nq=16, d=32, ncl=32, seed=3)


@functools.lru_cache(maxsize=None)
def small_engine():
    ds = small_ds()
    return SearchEngine.build(jax.random.PRNGKey(0), ds.train, ds.base,
                              m=8, nlist=32, coarse_iters=6, pq_iters=6)


@functools.lru_cache(maxsize=None)
def hard_ds():
    """Coarse PQ (M=4) + noisy queries: quantization visibly costs recall,
    so the exact re-rank stage has something to win back."""
    return vectors.make_deep_like(n=12000, nt=4000, nq=64, d=32, ncl=256,
                                  seed=5, query_noise=1.0)


@functools.lru_cache(maxsize=None)
def hard_engine():
    ds = hard_ds()
    return SearchEngine.build(jax.random.PRNGKey(0), ds.train, ds.base,
                              m=4, nlist=64, coarse_iters=8, pq_iters=8)


# ---------------------------------------------------------------------------
# stage-composition parity (the engine is exactly its stages)
# ---------------------------------------------------------------------------

def test_search_matches_hand_composed_flat_pipeline():
    ds, eng = small_ds(), small_engine()
    res = eng.search(ds.queries, 10, nprobe=8, rerank_mult=0)
    _, ids_hand = ivf.search_ivf(eng.index, ds.queries, nprobe=8, topk=10)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ids_hand))


def test_search_matches_hand_composed_hnsw_pipeline():
    ds, eng = small_ds(), small_engine()
    eng_h = SearchEngine(eng.index, base=ds.base, coarse="hnsw",
                         hnsw_m=8, ef_construction=32)
    res = eng_h.search(ds.queries, 10, nprobe=8, rerank_mult=0)
    _, probes = eng_h.coarse.search(ds.queries, 8, ef=max(eng_h.config.ef, 8))
    _, ids_hand = ivf.search_ivf_precomputed_probes(
        eng.index, ds.queries, probes, nprobe=8, topk=10)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ids_hand))


@pytest.mark.parametrize("impl", ["select", "mxu", "stream", "auto"])
def test_scan_impl_matches_ref_through_engine(impl):
    """Every grouped kernel formulation — select-tree VPU, one-hot MXU, the
    gather-free stream DMA, and the autotuned dispatch — produces results
    identical to the jnp gather end-to-end, through both the staged and the
    fused pipeline."""
    ds, eng = small_ds(), small_engine()
    eng_i = SearchEngine(eng.index, base=ds.base,
                         config=EngineConfig(scan_impl=impl))
    q = ds.queries[:4]
    res_ref = eng.search(q, 10, nprobe=4, rerank_mult=2)
    res_i = eng_i.search(q, 10, nprobe=4, rerank_mult=2)
    np.testing.assert_array_equal(np.asarray(res_ref.ids), np.asarray(res_i.ids))
    np.testing.assert_array_equal(np.asarray(res_ref.dists),
                                  np.asarray(res_i.dists))
    res_j = eng_i.search_jit(q, 10, nprobe=4, rerank_mult=2)
    np.testing.assert_array_equal(np.asarray(res_ref.ids), np.asarray(res_j.ids))
    np.testing.assert_array_equal(np.asarray(res_ref.dists),
                                  np.asarray(res_j.dists))


# ---------------------------------------------------------------------------
# exact re-rank
# ---------------------------------------------------------------------------

def test_rerank_bitmatches_brute_force_on_candidate_set():
    """Stage 3 distances == brute-force float distances, bit-for-bit."""
    ds, eng = small_ds(), small_engine()
    q = ds.queries[:8]
    probes = eng.select_probes(q, 8)
    flat_d, flat_ids = eng.scan(q, probes)
    _, pos = topk_mod.masked_topk(flat_d, flat_ids >= 0, 40)
    cand = jnp.where(pos >= 0,
                     jnp.take_along_axis(flat_ids, jnp.maximum(pos, 0), axis=1),
                     -1)
    got = exact_distances(ds.base, q, cand)

    # candidate-restricted brute force, written independently in the same
    # subtraction form exact_distances uses (Σ(q−x)², one sum over D): same
    # math, same shapes -> must agree bit-for-bit. jit'd so both sides get
    # XLA's fused reduction order.
    def bf(b, qq, c):
        return jnp.sum((b[jnp.maximum(c, 0)] - qq[:, None, :]) ** 2, -1)
    want = jax.jit(bf)(ds.base, q, cand)
    valid = np.asarray(cand >= 0)
    np.testing.assert_array_equal(np.asarray(got)[valid], np.asarray(want)[valid])
    assert np.all(np.isinf(np.asarray(got)[~valid]))

    # and anchor against float64 numpy ground truth: every summand is >= 0,
    # so f32 rounding stays within a few ulp of the distance itself (the
    # expanded (‖q‖² − 2q·x) + ‖x‖² form cancels and misses this by ~10x)
    base64 = np.asarray(ds.base, np.float64)
    q64 = np.asarray(q, np.float64)
    want64 = ((base64[np.maximum(np.asarray(cand), 0)]
               - q64[:, None, :]) ** 2).sum(-1)
    np.testing.assert_allclose(np.asarray(got)[valid], want64[valid], rtol=1e-6)

    # and the re-ranked top-k is the brute-force order on that set
    vals, ids = exact_rerank(ds.base, q, cand, 10)
    masked = jnp.where(cand >= 0, want, jnp.inf)
    bf_vals, bf_pos = topk_mod.smallest_k(masked, 10)
    bf_ids = jnp.take_along_axis(cand, bf_pos, axis=1)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(bf_vals))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(bf_ids))


def test_rerank_improves_recall_over_pure_fastscan():
    """Acceptance: re-rank strictly improves (or ties) recall@10 — here it
    improves by a wide margin because M=4 quantization is deliberately lossy."""
    ds, eng = hard_ds(), hard_engine()
    r_pure = float(metrics.recall_at_r(
        eng.search(ds.queries, 10, nprobe=8, rerank_mult=0).ids, ds.gt_ids, r=10))
    r_rr = float(metrics.recall_at_r(
        eng.search(ds.queries, 10, nprobe=8, rerank_mult=4).ids, ds.gt_ids, r=10))
    assert r_rr >= r_pure
    assert r_rr > r_pure + 0.05, (r_pure, r_rr)


def test_full_pipeline_recall_not_below_raw_ivf_fastscan():
    """Acceptance: engine recall@k >= raw IVF fast-scan recall@k."""
    ds, eng = hard_ds(), hard_engine()
    _, ids_raw = ivf.search_ivf(eng.index, ds.queries, nprobe=8, topk=10)
    r_raw = float(metrics.recall_at_r(ids_raw, ds.gt_ids, r=10))
    res = eng.search(ds.queries, 10, nprobe=8, rerank_mult=4)
    r_eng = float(metrics.recall_at_r(res.ids, ds.gt_ids, r=10))
    assert r_eng >= r_raw, (r_raw, r_eng)


def test_rerank_without_base_raises():
    ds, eng = small_ds(), small_engine()
    bare = SearchEngine(eng.index, base=None)
    with pytest.raises(ValueError, match="re-rank"):
        bare.search(ds.queries, 10, rerank_mult=2)


# ---------------------------------------------------------------------------
# QueryStats
# ---------------------------------------------------------------------------

def test_query_stats_match_nprobe_and_list_sizes():
    ds, eng = small_ds(), small_engine()
    k, nprobe, r = 10, 6, 3
    res = eng.search(ds.queries, k, nprobe=nprobe, rerank_mult=r)

    np.testing.assert_array_equal(np.asarray(res.stats.lists_probed),
                                  np.full((ds.queries.shape[0],), nprobe))
    # recompute the probe set by hand and sum true occupancies
    d = pairwise_sqdist(ds.queries, eng.index.centroids)
    _, probes = topk_mod.smallest_k(d, nprobe)
    want_scanned = np.asarray(eng.index.lists.sizes)[np.asarray(probes)].sum(axis=1)
    np.testing.assert_array_equal(np.asarray(res.stats.codes_scanned),
                                  want_scanned)
    # every candidate in a probed list is valid, so the re-rank pool is
    # min(r*k, codes actually scanned)
    np.testing.assert_array_equal(np.asarray(res.stats.reranked),
                                  np.minimum(r * k, want_scanned))


def test_query_stats_zero_rerank_when_disabled():
    ds, eng = small_ds(), small_engine()
    res = eng.search(ds.queries, 10, nprobe=4, rerank_mult=0)
    assert int(np.asarray(res.stats.reranked).sum()) == 0


# ---------------------------------------------------------------------------
# list store
# ---------------------------------------------------------------------------

def test_liststore_gather_masks_invalid_probes():
    eng = small_engine()
    store = eng.index.lists
    probes = jnp.asarray([[0, -1], [2, 3]], jnp.int32)
    codes, ids = store.gather(probes)
    assert codes.shape == (2, 2, store.cap, store.codes.shape[-1])
    assert int((np.asarray(ids[0, 1]) != -1).sum()) == 0  # invalid probe
    sizes = store.probed_sizes(probes)
    assert int(sizes[0, 1]) == 0
    assert int(sizes[0, 0]) == int(store.sizes[0])


def test_partition_lists_preserves_every_vector_once():
    eng = small_engine()
    cen_s, lists_s, real_s = partition_lists(eng.index.lists,
                                             eng.index.centroids, 3)
    all_ids = np.asarray(lists_s.ids).reshape(-1)
    valid = np.sort(all_ids[all_ids >= 0])
    orig = np.asarray(eng.index.lists.ids).reshape(-1)
    np.testing.assert_array_equal(valid, np.sort(orig[orig >= 0]))
    assert cen_s.shape[0] == 3 and lists_s.ids.shape[0] == 3
    # real mask covers exactly the original lists; padding is marked False
    assert int(np.asarray(real_s).sum()) == eng.index.nlist


def test_partition_base_covers_every_row_once_without_replication():
    """The sharded re-rank base: every base row lands on exactly one shard,
    shard slices are ~N/S (not a replicated full copy), and the local-id
    remap round-trips through gids back to the global posting-list ids."""
    from repro.core.lists import partition_base
    ds, eng = small_ds(), small_engine()
    s = 4
    cen_s, lists_s, real_s = partition_lists(eng.index.lists,
                                             eng.index.centroids, s)
    base_s, gids_s, local_ids = partition_base(lists_s, ds.base)
    n, d = ds.base.shape
    # each global id appears exactly once across all shards' gids
    g = np.asarray(gids_s).reshape(-1)
    np.testing.assert_array_equal(np.sort(g[g >= 0]), np.arange(n))
    # per-shard slices are a partition, not replicas: R < N for S > 1
    assert base_s.shape[0] == s and base_s.shape[2] == d
    assert base_s.shape[1] < n
    # local ids point at the right rows: base_s[shard, local] == base[global]
    li = np.asarray(local_ids)
    gi = np.asarray(lists_s.ids)
    bs = np.asarray(base_s)
    b = np.asarray(ds.base)
    valid = gi >= 0
    np.testing.assert_array_equal(valid, li >= 0)
    for j in range(s):
        np.testing.assert_array_equal(bs[j][li[j][valid[j]]], b[gi[j][valid[j]]])
        np.testing.assert_array_equal(np.asarray(gids_s)[j][li[j][valid[j]]],
                                      gi[j][valid[j]])
    # padding rows of each slice are zero and map to no global id
    gv = g >= 0
    assert (bs.reshape(-1, d)[~gv] == 0).all()


def test_sharded_rerank_on_local_base_matches_replicated_semantics():
    """Single shard + re-rank: local-base slicing and the id remap must be
    invisible — results identical to the unsharded engine's."""
    ds, eng = small_ds(), small_engine()
    sh = ShardedEngine(eng, 1)
    res = eng.search(ds.queries, 10, nprobe=8, rerank_mult=3)
    res_s = sh.search(ds.queries, 10, nprobe=8, rerank_mult=3)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(res_s.ids))
    np.testing.assert_array_equal(np.asarray(res.dists), np.asarray(res_s.dists))


def test_sharded_rerank_multi_shard_returns_global_ids():
    """S > 1 with re-rank: result ids are global (valid range, deduped) and
    the re-ranked distances are the true float distances to those ids."""
    ds, eng = small_ds(), small_engine()
    sh = ShardedEngine(eng, 3)
    res = sh.search(ds.queries, 10, nprobe=4, rerank_mult=2)
    ids = np.asarray(res.ids)
    d = np.asarray(res.dists)
    b = np.asarray(ds.base)
    q = np.asarray(ds.queries)
    assert ids.max() < b.shape[0]
    for qi in range(ids.shape[0]):
        row = ids[qi][ids[qi] >= 0]
        assert len(row) == len(set(row.tolist()))
        want = ((b[row] - q[qi][None, :]) ** 2).sum(-1)
        np.testing.assert_allclose(d[qi][ids[qi] >= 0], want, rtol=1e-5)


def test_sharded_stats_exclude_padding_lists():
    """nlist=32, S=5 -> L=7 with 3 padding lists; probing all 7 local lists
    per shard must report exactly the 32 real lists, not 35."""
    ds, eng = small_ds(), small_engine()
    sh = ShardedEngine(eng, 5)
    res = sh.search(ds.queries, 10, nprobe=7, rerank_mult=0)
    np.testing.assert_array_equal(np.asarray(res.stats.lists_probed),
                                  np.full((ds.queries.shape[0],), 32))


# ---------------------------------------------------------------------------
# shard-parallel execution
# ---------------------------------------------------------------------------

def test_sharded_single_shard_matches_unsharded():
    ds, eng = small_ds(), small_engine()
    res = eng.search(ds.queries, 10, nprobe=8, rerank_mult=0)
    sh = ShardedEngine(eng, 1)
    res_s = sh.search(ds.queries, 10, nprobe=8, rerank_mult=0)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(res_s.ids))
    np.testing.assert_array_equal(np.asarray(res.dists), np.asarray(res_s.dists))


def test_sharded_recall_and_stats():
    """Each of S shards probes nprobe of its own lists => >= recall of the
    single-shard engine at the same nprobe, and stats aggregate via psum."""
    ds, eng = hard_ds(), hard_engine()
    nprobe = 4
    r_single = float(metrics.recall_at_r(
        eng.search(ds.queries, 10, nprobe=nprobe, rerank_mult=4).ids,
        ds.gt_ids, r=10))
    sh = ShardedEngine(eng, 4)
    res = sh.search(ds.queries, 10, nprobe=nprobe, rerank_mult=4)
    r_sharded = float(metrics.recall_at_r(res.ids, ds.gt_ids, r=10))
    assert r_sharded >= r_single - 1e-6, (r_single, r_sharded)
    np.testing.assert_array_equal(np.asarray(res.stats.lists_probed),
                                  np.full((ds.queries.shape[0],), 4 * nprobe))


def test_sharded_results_are_sorted_and_deduped():
    ds, eng = small_ds(), small_engine()
    sh = ShardedEngine(eng, 4)
    res = sh.search(ds.queries, 10, nprobe=4, rerank_mult=0)
    d = np.asarray(res.dists)
    assert np.all(np.diff(d, axis=1) >= 0)
    ids = np.asarray(res.ids)
    for row in ids:
        row = row[row >= 0]
        assert len(row) == len(set(row.tolist()))


def test_sharded_shard_map_on_device_mesh():
    """The shard_map driver (one shard per device) agrees with the vmap one."""
    ds, eng = small_ds(), small_engine()
    n_dev = jax.device_count()
    sh = ShardedEngine(eng, n_dev)
    mesh = jax.make_mesh((n_dev,), ("shards",))
    res_m = sh.search(ds.queries, 10, nprobe=4, rerank_mult=2, mesh=mesh)
    res_v = sh.search(ds.queries, 10, nprobe=4, rerank_mult=2)
    np.testing.assert_array_equal(np.asarray(res_m.ids), np.asarray(res_v.ids))
    np.testing.assert_array_equal(np.asarray(res_m.dists), np.asarray(res_v.dists))
