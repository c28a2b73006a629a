"""Anytime search: margin probe pruning + in-kernel early-exit tile pruning.

Two claims are load-bearing (docs/anytime.md) and everything here drives
at them with exact oracles, never allclose:

  1. ``margin_prune_probes`` with ``tau=inf`` is the identity, the best
     probe always survives, and the pruned counter is exact — so
     ``probe_policy='margin'`` at ``tau=inf`` is bit-identical to 'fixed'
     through the whole engine (staged, fused, sharded, serving).
  2. The stream kernel's early-exit bound is admissible: the final
     top-``keep`` selection over the pruned pool is bit-identical to the
     unpruned kernel's for every shape/occupancy/filter combination, even
     when the skewed-data path genuinely skips tiles.

Hypothesis drives the probe-pruning property (gracefully skipped when the
package is absent — see conftest); deterministic seed-swept twins cover
the same oracles in the tier-1 container.
"""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as hst

from repro.core import ivf
from repro.core.lists import ListStore, pack_filter_mask
from repro.core.pq import PQCodebook
from repro.core.topk import gather_ids, margin_prune_probes, masked_topk
from repro.data import vectors
from repro.engine import EngineConfig, SearchEngine, ShardedEngine
from repro.engine.engine import coarse_probes, scan_candidates
from repro.kernels import ops
from repro.serving.loop import ServingLoop

_SETTINGS = dict(max_examples=25, deadline=None,
                 suppress_health_check=[HealthCheck.too_slow,
                                        HealthCheck.data_too_large])


# ---------------------------------------------------------------------------
# margin_prune_probes: unit + property
# ---------------------------------------------------------------------------

def _check_margin_invariants(vals, probes, tau):
    out, pruned = margin_prune_probes(jnp.asarray(vals), jnp.asarray(probes),
                                      tau)
    out = np.asarray(out)
    pruned = np.asarray(pruned)
    vals = np.asarray(vals)
    probes = np.asarray(probes)
    present = probes >= 0
    taus = np.broadcast_to(np.asarray(tau, np.float32).reshape(-1, 1)
                           if np.ndim(tau) == 1 else np.float32(tau),
                           probes.shape)
    for qi in range(probes.shape[0]):
        kept = out[qi] >= 0
        # pruning only ever clears slots, never invents them, and a kept
        # slot keeps its probe id
        assert not (kept & ~present[qi]).any()
        np.testing.assert_array_equal(out[qi][kept], probes[qi][kept])
        if present[qi].any():
            d0 = vals[qi][present[qi]].min()
            # the best probe always survives (ties included)
            best = present[qi] & (vals[qi] <= d0)
            assert kept[best].all(), "a best-distance probe was pruned"
            # the margin rule, slot by slot
            want = present[qi] & (
                (vals[qi] <= d0 * (1.0 + taus[qi]))
                | np.isposinf(taus[qi]) | (vals[qi] <= d0))
            np.testing.assert_array_equal(kept, want)
        assert pruned[qi] == int((present[qi] & ~kept).sum())
    return out, pruned


@given(qn=hst.integers(1, 5), pn=hst.integers(1, 8),
       tau=hst.floats(0.0, 4.0), frac=hst.floats(0.0, 1.0),
       seed=hst.integers(0, 2**31 - 1))
@settings(**_SETTINGS)
def test_margin_prune_property(qn, pn, tau, frac, seed):
    rng = np.random.default_rng(seed)
    vals = rng.random((qn, pn)).astype(np.float32) * 10
    probes = np.where(rng.random((qn, pn)) < frac,
                      rng.integers(0, 64, (qn, pn)), -1).astype(np.int32)
    _check_margin_invariants(vals, probes, tau)


@pytest.mark.parametrize("seed", range(8))
def test_margin_prune_seeds(seed):
    rng = np.random.default_rng(seed)
    qn, pn = int(rng.integers(1, 5)), int(rng.integers(1, 8))
    vals = rng.random((qn, pn)).astype(np.float32) * 10
    probes = np.where(rng.random((qn, pn)) < rng.random(),
                      rng.integers(0, 64, (qn, pn)), -1).astype(np.int32)
    for tau in (0.0, 0.3, float(seed), np.inf):
        _check_margin_invariants(vals, probes, tau)


def test_margin_prune_tau_inf_is_identity():
    rng = np.random.default_rng(0)
    vals = rng.random((3, 6)).astype(np.float32)
    probes = rng.integers(-1, 20, (3, 6)).astype(np.int32)
    out, pruned = margin_prune_probes(jnp.asarray(vals), jnp.asarray(probes),
                                      np.inf)
    np.testing.assert_array_equal(np.asarray(out), probes)
    np.testing.assert_array_equal(np.asarray(pruned), 0)


def test_margin_prune_all_absent_row_stays_absent():
    vals = jnp.full((2, 4), jnp.inf, jnp.float32)
    probes = jnp.full((2, 4), -1, jnp.int32)
    out, pruned = margin_prune_probes(vals, probes, 0.0)
    np.testing.assert_array_equal(np.asarray(out), -1)
    np.testing.assert_array_equal(np.asarray(pruned), 0)


def test_margin_prune_per_query_tau_and_monotonicity():
    rng = np.random.default_rng(3)
    vals = rng.random((4, 8)).astype(np.float32)
    probes = rng.integers(0, 32, (4, 8)).astype(np.int32)
    taus = np.array([0.0, 0.2, 1.0, np.inf], np.float32)
    out_vec, pruned_vec = _check_margin_invariants(vals, probes, taus)
    # each row of the vector call == the scalar call at that row's tau
    for qi, t in enumerate(taus):
        out_s, pruned_s = margin_prune_probes(
            jnp.asarray(vals[qi:qi + 1]), jnp.asarray(probes[qi:qi + 1]),
            float(t))
        np.testing.assert_array_equal(out_vec[qi], np.asarray(out_s)[0])
        assert pruned_vec[qi] == int(np.asarray(pruned_s)[0])
    # widening tau never prunes more
    prev = None
    for t in (0.0, 0.1, 0.5, 2.0, np.inf):
        _, pruned = margin_prune_probes(jnp.asarray(vals),
                                        jnp.asarray(probes), float(t))
        tot = int(np.asarray(pruned).sum())
        assert prev is None or tot <= prev
        prev = tot


# ---------------------------------------------------------------------------
# early-exit stream scan vs the unpruned oracle (kernel grid)
# ---------------------------------------------------------------------------

def _synth_index(nlist, cap, m, *, d=None, seed=0, occupancy="ragged"):
    """IVFIndex from raw random arrays — no k-means, instant to build."""
    d = d or 4 * m
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
    return ivf.IVFIndex(
        centroids=jnp.asarray(rng.normal(size=(nlist, d)).astype(np.float32)),
        codebook=PQCodebook(jnp.asarray(
            rng.normal(size=(m, 16, d // m)).astype(np.float32))),
        lists=ListStore(codes=jnp.asarray(codes), ids=jnp.asarray(ids),
                        sizes=jnp.asarray(sizes.astype(np.int32))),
    )


def _skewed_index(nlist, cap, m, *, d=None, seed=0):
    """An index whose later lists sit far from the origin: queries near the
    origin get a huge ADC bias on those probes, so the early-exit bound can
    genuinely beat the running threshold and skip their tiles."""
    idx = _synth_index(nlist, cap, m, d=d, seed=seed, occupancy="full")
    cen = np.array(idx.centroids)
    cen[nlist // 2:] += 200.0  # push half the lists far away
    return idx._replace(centroids=jnp.asarray(cen))


def _topk_oracle(dists, ids, keep):
    v, pos = masked_topk(dists, ids >= 0, keep)
    return np.asarray(v), np.asarray(gather_ids(ids, pos))


def _assert_early_exit_lossless(index, q, probes, keep, tile_n,
                                filter_bits=None):
    base_d, base_i, _, _ = ivf.scan_probes_stream(
        index, q, probes, keep=keep, tile_n=tile_n, filter_bits=filter_bits)
    ee_d, ee_i, skipped, _ = ivf.scan_probes_stream(
        index, q, probes, keep=keep, tile_n=tile_n, filter_bits=filter_bits,
        early_exit=True)
    want_v, want_i = _topk_oracle(base_d, base_i, keep)
    got_v, got_i = _topk_oracle(ee_d, ee_i, keep)
    np.testing.assert_array_equal(got_i, want_i)
    np.testing.assert_array_equal(got_v, want_v)
    return np.asarray(skipped)


EE_GRID = [
    # (nlist, cap, m, tile_n, keep, p, occupancy)
    (6, 64, 4, 32, 8, 3, "ragged"),     # multi-tile, ragged
    (6, 64, 4, 64, 8, 3, "full"),       # single tile per probe
    (4, 100, 8, 32, 5, 4, "ragged"),    # non-pow2 cap, p == nlist
    (8, 48, 4, 16, 16, 2, "ragged"),    # keep == tile_n (kc == keep, armed)
    (5, 32, 2, 8, 1, 5, "full"),        # keep=1, many tiny tiles
    (3, 64, 4, 16, 32, 3, "full"),      # keep > tile_n -> prune DISARMED
]


@pytest.mark.parametrize("nlist,cap,m,tile_n,keep,p,occ", EE_GRID)
def test_early_exit_scan_lossless_grid(nlist, cap, m, tile_n, keep, p, occ):
    rng = np.random.default_rng(nlist * 7 + cap + keep)
    index = _synth_index(nlist, cap, m, seed=nlist + cap, occupancy=occ)
    q = jnp.asarray(rng.normal(size=(3, 4 * m)).astype(np.float32))
    probes = np.where(rng.random((3, p)) < 0.8,
                      rng.integers(0, nlist, (3, p)), -1).astype(np.int32)
    probes[1, :] = -1  # one fully-pruned query (all-sentinel probe row)
    probes[2, :2] = probes[2, 0]  # duplicate probes
    skipped = _assert_early_exit_lossless(index, q, jnp.asarray(probes),
                                          keep, tile_n)
    assert (skipped >= 0).all()
    assert skipped[1] == 0  # no valid probes -> nothing to count as skipped


def test_early_exit_actually_skips_on_skewed_data():
    """The lossless grid can pass with zero pruning; this construction makes
    the bound genuinely fire so the skip path itself is exercised."""
    nlist, cap, m, tile_n, keep = 8, 64, 8, 16, 4
    index = _skewed_index(nlist, cap, m, seed=11)
    rng = np.random.default_rng(12)
    q = jnp.asarray(rng.normal(size=(2, 4 * m)).astype(np.float32))
    probes = jnp.asarray(np.tile(np.arange(nlist, dtype=np.int32), (2, 1)))
    skipped = _assert_early_exit_lossless(index, q, probes, keep, tile_n)
    assert skipped.sum() > 0, "skewed construction never pruned a tile"


def test_early_exit_lossless_with_filters_and_tombstones():
    """Filter bits (and the tombstone bitmap that rides the same path) must
    compose with the bound: the pre-selection mask shrinks candidates, the
    bound only ever skips tiles that cannot matter."""
    nlist, cap, m, tile_n, keep = 6, 64, 4, 16, 6
    index = _skewed_index(nlist, cap, m, seed=21)
    rng = np.random.default_rng(22)
    q = jnp.asarray(rng.normal(size=(2, 4 * m)).astype(np.float32))
    probes = jnp.asarray(np.tile(np.arange(nlist, dtype=np.int32), (2, 1)))
    for selectivity in (0.0, 0.5, 1.0):
        mask = rng.random((nlist, cap)) < selectivity
        fb = pack_filter_mask(jnp.asarray(mask))
        _assert_early_exit_lossless(index, q, probes, keep, tile_n,
                                    filter_bits=fb)


def test_early_exit_disarmed_keep_exceeds_tile_reports_zero():
    """keep > tile_n means the kernel cannot hold a full top-keep per tile,
    so pruning silently disarms: results identical, counter all zeros."""
    index = _synth_index(4, 64, 4, seed=31, occupancy="full")
    rng = np.random.default_rng(32)
    q = jnp.asarray(rng.normal(size=(2, 16)).astype(np.float32))
    probes = jnp.asarray(rng.integers(0, 4, (2, 3)).astype(np.int32))
    skipped = _assert_early_exit_lossless(index, q, probes, keep=40,
                                          tile_n=16)
    np.testing.assert_array_equal(skipped, 0)


# ---------------------------------------------------------------------------
# engine end-to-end: margin policy + early exit
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _dataset():
    return vectors.make_sift_like(n=4000, nt=1500, nq=8, d=32, ncl=16, seed=5)


@functools.lru_cache(maxsize=None)
def _engines(probe_policy, early_exit, rerank_mult=0):
    ds = _dataset()
    cfg = EngineConfig(nprobe=8, scan_impl="stream", rerank_mult=rerank_mult,
                       probe_policy=probe_policy, early_exit=early_exit)
    return ds, SearchEngine.build(jax.random.PRNGKey(0), ds.train, ds.base,
                                  m=8, nlist=16, config=cfg,
                                  coarse_iters=5, pq_iters=5)


@pytest.mark.parametrize("rerank_mult", [0, 2])
def test_margin_tau_inf_bit_identical_to_fixed(rerank_mult):
    ds, e_fix = _engines("fixed", False, rerank_mult)
    _, e_adp = _engines("margin", True, rerank_mult)
    q = jnp.asarray(ds.queries)
    rf = e_fix.search_jit(q, 10)
    ra = e_adp.search_jit(q, 10, margin_tau=float("inf"))
    np.testing.assert_array_equal(np.asarray(ra.ids), np.asarray(rf.ids))
    np.testing.assert_array_equal(np.asarray(ra.dists), np.asarray(rf.dists))
    np.testing.assert_array_equal(np.asarray(ra.stats.lists_pruned), 0)
    # staged == fused under the adaptive config too
    rs = e_adp.search(q, 10, margin_tau=float("inf"))
    np.testing.assert_array_equal(np.asarray(ra.ids), np.asarray(rs.ids))
    np.testing.assert_array_equal(np.asarray(ra.dists), np.asarray(rs.dists))


def test_margin_tau_prunes_and_early_exit_stays_lossless_at_fixed_probes():
    """At any tau the adaptive engine must equal a fixed engine given the
    SAME pruned probe set — early exit never costs recall at fixed probes.
    (Smaller tau may change the probe set and hence results; that recall
    trade is the point of the dial, measured in serve_bench.)"""
    ds, e_adp = _engines("margin", True)
    _, e_noee = _engines("margin", False)
    q = jnp.asarray(ds.queries)
    for tau in (0.0, 0.25, 1.0):
        ra = e_adp.search_jit(q, 10, margin_tau=tau)
        rb = e_noee.search_jit(q, 10, margin_tau=tau)
        np.testing.assert_array_equal(np.asarray(ra.ids), np.asarray(rb.ids))
        np.testing.assert_array_equal(np.asarray(ra.dists),
                                      np.asarray(rb.dists))
        np.testing.assert_array_equal(np.asarray(ra.stats.lists_pruned),
                                      np.asarray(rb.stats.lists_pruned))
    r0 = e_adp.search_jit(q, 10, margin_tau=0.0)
    assert (np.asarray(r0.stats.lists_pruned) > 0).any()
    # probes shrink with tau: lists_probed + lists_pruned == nprobe-selected
    probed = np.asarray(r0.stats.lists_probed)
    pruned = np.asarray(r0.stats.lists_pruned)
    full = np.asarray(e_adp.search_jit(
        q, 10, margin_tau=float("inf")).stats.lists_probed)
    np.testing.assert_array_equal(probed + pruned, full)


def test_margin_policy_with_tombstones_and_filters():
    ds, _ = _engines("margin", True)
    cfg = EngineConfig(nprobe=8, scan_impl="stream", probe_policy="margin",
                       early_exit=True)
    eng = SearchEngine.build(jax.random.PRNGKey(0), ds.train, ds.base,
                             m=8, nlist=16, config=cfg,
                             coarse_iters=5, pq_iters=5)
    cfg_f = EngineConfig(nprobe=8, scan_impl="stream")
    eng_f = SearchEngine.build(jax.random.PRNGKey(0), ds.train, ds.base,
                               m=8, nlist=16, config=cfg_f,
                               coarse_iters=5, pq_iters=5)
    dead = np.arange(0, 400)
    assert eng.delete(dead) == 400
    assert eng_f.delete(dead) == 400
    q = jnp.asarray(ds.queries)
    fb = pack_filter_mask(
        jnp.asarray(np.random.default_rng(7).random(
            (16, eng.index.lists.cap)) < 0.6))
    ra = eng.search_jit(q, 10, margin_tau=float("inf"), filter_bits=fb)
    rf = eng_f.search_jit(q, 10, filter_bits=fb)
    np.testing.assert_array_equal(np.asarray(ra.ids), np.asarray(rf.ids))
    np.testing.assert_array_equal(np.asarray(ra.dists), np.asarray(rf.dists))
    assert not np.isin(np.asarray(ra.ids), dead).any()
    # tight tau still never returns a tombstoned or filtered-out row
    rt = eng.search_jit(q, 10, margin_tau=0.0, filter_bits=fb)
    assert not np.isin(np.asarray(rt.ids), dead).any()


def test_margin_tau_rejected_under_fixed_policy():
    ds, e_fix = _engines("fixed", False)
    with pytest.raises(ValueError, match="probe_policy"):
        e_fix.search_jit(jnp.asarray(ds.queries), 10, margin_tau=0.5)
    from repro.engine.engine import validate_config
    with pytest.raises(ValueError, match="margin_tau"):
        validate_config(EngineConfig(probe_policy="margin", margin_tau=-1.0),
                        coarse_kind="flat", has_base=False)
    with pytest.raises(ValueError, match="probe_policy"):
        validate_config(EngineConfig(probe_policy="bogus"),
                        coarse_kind="flat", has_base=False)


def test_coarse_probes_policy_with_namespaces():
    """The flat+restricted branch (masked_topk) must feed the margin prune
    the same distances it selected by — a tenant's pruned set is a subset
    of its own lists and the best allowed probe survives."""
    ds, eng = _engines("margin", True)
    member = np.zeros((2, 16), bool)
    member[0, :8] = True
    member[1, 8:] = True
    q = jnp.asarray(ds.queries[:4])
    ns = jnp.asarray(np.array([0, 1, 0, -1], np.int32))
    probes, pruned = coarse_probes(
        eng.coarse, q, nprobe=8, ef=64, ns_member=jnp.asarray(member),
        namespaces=ns, probe_policy="margin", margin_tau=0.3)
    probes = np.asarray(probes)
    assert (probes[0][probes[0] >= 0] < 8).all()
    assert (probes[1][probes[1] >= 0] >= 8).all()
    assert (probes[0] >= 0).any() and (probes[1] >= 0).any()
    assert (np.asarray(pruned) >= 0).all()


# ---------------------------------------------------------------------------
# sharded driver (vmap; the 8-device shard_map twin lives in
# tests/_multidevice_harness.py)
# ---------------------------------------------------------------------------

def test_sharded_margin_tau_inf_matches_fixed_and_counters_psum():
    ds, e_adp = _engines("margin", True)
    _, e_fix = _engines("fixed", False)
    sh_a = ShardedEngine(e_adp, 4)
    sh_f = ShardedEngine(e_fix, 4)
    q = jnp.asarray(ds.queries)
    ra = sh_a.search(q, 10, margin_tau=float("inf"))
    rf = sh_f.search(q, 10)
    np.testing.assert_array_equal(np.asarray(ra.ids), np.asarray(rf.ids))
    np.testing.assert_array_equal(np.asarray(ra.dists), np.asarray(rf.dists))
    np.testing.assert_array_equal(np.asarray(ra.stats.lists_pruned), 0)
    rt = sh_a.search(q, 10, margin_tau=0.0)
    assert (np.asarray(rt.stats.lists_pruned) > 0).any()
    # per-shard prune: probed + pruned == tau=inf probed (psum'd totals)
    np.testing.assert_array_equal(
        np.asarray(rt.stats.lists_probed) + np.asarray(rt.stats.lists_pruned),
        np.asarray(ra.stats.lists_probed))
    with pytest.raises(ValueError, match="probe_policy"):
        sh_f.search(q, 10, margin_tau=0.5)


# ---------------------------------------------------------------------------
# serving loop: margin_tau plumb-through + auto-compaction satellite
# ---------------------------------------------------------------------------

def test_serving_loop_margin_counters_and_auto_compaction():
    ds, _ = _engines("margin", True)
    cfg = EngineConfig(nprobe=8, scan_impl="stream", probe_policy="margin",
                       early_exit=True)
    eng = SearchEngine.build(jax.random.PRNGKey(0), ds.train, ds.base,
                             m=8, nlist=16, config=cfg,
                             coarse_iters=5, pq_iters=5)
    loop = ServingLoop(eng, margin_tau=0.0, compact_at=0.001)
    with loop:
        res = loop.submit(np.asarray(ds.queries[0]), k=5,
                          tenant="t0").result(timeout=60)
        assert res.lists_pruned > 0
        assert res.tiles_skipped >= 0
        # push tombstones over the ratio; the NEXT dispatch auto-compacts
        assert loop.delete(np.asarray(res.ids[res.ids >= 0])) > 0
        assert eng.n_tombstones > 0
        loop.submit(np.asarray(ds.queries[1]), k=5,
                    tenant="t0").result(timeout=60)
        # compaction runs on the dispatch thread between batches; give it a
        # generous deadline — a full-suite run can starve this thread for
        # seconds on a loaded CPU
        deadline = 600
        while (eng.n_tombstones or not loop.metrics().auto_compactions) \
                and deadline:
            import time
            time.sleep(0.05)
            deadline -= 1
        m = loop.metrics()
        assert m.auto_compactions >= 1
        assert eng.n_tombstones == 0
        assert m.lists_pruned > 0
        st = loop.stats.get("t0")
        assert st.lists_pruned > 0
        assert st.tiles_skipped >= 0


def test_serving_loop_auto_compaction_default_off():
    ds, _ = _engines("margin", True)
    cfg = EngineConfig(nprobe=4, scan_impl="stream")
    eng = SearchEngine.build(jax.random.PRNGKey(0), ds.train, ds.base,
                             m=8, nlist=16, config=cfg,
                             coarse_iters=5, pq_iters=5)
    loop = ServingLoop(eng)
    assert loop.compact_at is None
    with loop:
        res = loop.submit(np.asarray(ds.queries[0]), k=5).result(timeout=60)
        loop.delete(np.asarray(res.ids[res.ids >= 0]))
        n_tomb = eng.n_tombstones
        assert n_tomb > 0
        loop.submit(np.asarray(ds.queries[1]), k=5).result(timeout=60)
        assert loop.metrics().auto_compactions == 0
        assert eng.n_tombstones == n_tomb  # nothing compacted behind our back


def test_serving_loop_rejects_bad_anytime_config():
    ds, _ = _engines("margin", True)
    cfg = EngineConfig(nprobe=4, scan_impl="stream")
    eng = SearchEngine.build(jax.random.PRNGKey(0), ds.train, ds.base,
                             m=8, nlist=16, config=cfg,
                             coarse_iters=5, pq_iters=5)
    with pytest.raises(ValueError, match="probe_policy"):
        ServingLoop(eng, margin_tau=0.1)
    with pytest.raises(ValueError, match="compact_at"):
        ServingLoop(eng, compact_at=1.5)
    with pytest.raises(ValueError, match="compact_at"):
        ServingLoop(eng, compact_at=0.0)


# ---------------------------------------------------------------------------
# autotune: probe_fill keys + schema migration + re-rank sweep cap
# ---------------------------------------------------------------------------

def test_autotune_probe_fill_keys_distinct_entries():
    ops.clear_autotune_cache()
    try:
        t_dense = ops.resolve_grouped_impl(8, 32, 8, nlist=16)
        t_half = ops.resolve_grouped_impl(8, 32, 8, nlist=16, probe_fill=0.5)
        assert ops.autotune_cache_size() == 2  # distinct keys, both cached
        # cached on repeat: no third entry
        ops.resolve_grouped_impl(8, 32, 8, nlist=16, probe_fill=0.5)
        assert ops.autotune_cache_size() == 2
        assert t_dense.impl in ("ref", "select", "mxu", "stream")
        assert t_half.impl in ("ref", "select", "mxu", "stream")
        with pytest.raises(ValueError, match="probe_fill"):
            ops.resolve_grouped_impl(8, 32, 8, nlist=16, probe_fill=0.0)
        with pytest.raises(ValueError, match="probe_fill"):
            ops.resolve_grouped_impl(8, 32, 8, nlist=16, probe_fill=1.5)
    finally:
        ops.clear_autotune_cache()


def test_autotune_cache_v3_roundtrip_and_v2_v1_migration(tmp_path):
    ops.clear_autotune_cache()
    try:
        ops.resolve_grouped_impl(8, 32, 8, nlist=16, probe_fill=0.5)
        path = str(tmp_path / "tuned.json")
        assert ops.save_autotune_cache(path) == 1
        data = json.loads(open(path).read())
        assert data["schema"].endswith("/v3")
        assert data["entries"][0]["probe_fill"] == 0.5
        ops.clear_autotune_cache()
        assert ops.load_autotune_cache(path) == 1
        # the reloaded verdict satisfies the same fill-keyed lookup with no
        # re-sweep (cache size stays 1)
        ops.resolve_grouped_impl(8, 32, 8, nlist=16, probe_fill=0.5)
        assert ops.autotune_cache_size() == 1

        # v2 file (no probe_fill): migrates to fill=1.0
        e2 = dict(data["entries"][0])
        e2.pop("probe_fill")
        v2 = {"schema": "repro.autotune/v2", "entries": [e2]}
        p2 = str(tmp_path / "v2.json")
        open(p2, "w").write(json.dumps(v2))
        ops.clear_autotune_cache()
        assert ops.load_autotune_cache(p2) == 1
        ops.resolve_grouped_impl(8, 32, 8, nlist=16)  # fill=1.0 lookup hits
        assert ops.autotune_cache_size() == 1

        # v1 file (no kind/nlist/probe_fill): re-keys to nlist=g, fill=1.0
        e1 = {k: e2[k] for k in ("backend", "interpret", "g", "cap", "m",
                                 "impl", "tile_n", "timings_us")}
        v1 = {"schema": "repro.autotune/v1", "entries": [e1]}
        p1 = str(tmp_path / "v1.json")
        open(p1, "w").write(json.dumps(v1))
        ops.clear_autotune_cache()
        assert ops.load_autotune_cache(p1) == 1
        ops.resolve_grouped_impl(8, 32, 8, nlist=8)  # nlist=g=8 lookup hits
        assert ops.autotune_cache_size() == 1
    finally:
        ops.clear_autotune_cache()


def test_rerank_sweep_cap_env_and_kwarg(monkeypatch):
    from repro.kernels.ops import _RERANK_SWEEP_N_CAP, _rerank_sweep_n_cap
    monkeypatch.delenv("REPRO_RERANK_SWEEP_N_CAP", raising=False)
    assert _rerank_sweep_n_cap() == _RERANK_SWEEP_N_CAP
    monkeypatch.setenv("REPRO_RERANK_SWEEP_N_CAP", "2048")
    assert _rerank_sweep_n_cap() == 2048
    monkeypatch.setenv("REPRO_RERANK_SWEEP_N_CAP", "not-a-number")
    assert _rerank_sweep_n_cap() == _RERANK_SWEEP_N_CAP
    monkeypatch.setenv("REPRO_RERANK_SWEEP_N_CAP", "0")
    assert _rerank_sweep_n_cap() == _RERANK_SWEEP_N_CAP
    # the kwarg shapes the sweep without touching the cache key
    ops.clear_autotune_cache()
    try:
        t = ops.resolve_rerank_impl(2, 4, 16, 2, 512, sweep_n_cap=64)
        assert t.impl in ("gathered", "stream")
        assert ops.autotune_cache_size() == 1
        # same signature, different cap: the cached verdict is returned
        # (documented: clear first to re-time at a new cap)
        ops.resolve_rerank_impl(2, 4, 16, 2, 512, sweep_n_cap=128)
        assert ops.autotune_cache_size() == 1
    finally:
        ops.clear_autotune_cache()


# ---------------------------------------------------------------------------
# scan_candidates: gathered impls ignore early_exit (zeros counter)
# ---------------------------------------------------------------------------

def test_scan_candidates_gathered_early_exit_is_noop():
    index = _synth_index(5, 64, 8, seed=9)
    rng = np.random.default_rng(10)
    q = jnp.asarray(rng.normal(size=(2, 32)).astype(np.float32))
    probes = jnp.asarray(np.array([[0, 2], [4, 1]], np.int32))
    d_ref, i_ref, ts_ref, _ = scan_candidates(
        index, q, probes, scan_impl="ref", keep=5, early_exit=True)
    np.testing.assert_array_equal(np.asarray(ts_ref), 0)
    d_st, i_st, ts_st, _ = scan_candidates(
        index, q, probes, scan_impl="stream", keep=5, early_exit=True)
    assert np.asarray(ts_st).shape == (2,)
    want_v, want_i = _topk_oracle(d_ref, i_ref, 5)
    got_v, got_i = _topk_oracle(d_st, i_st, 5)
    np.testing.assert_array_equal(got_i, want_i)
    np.testing.assert_array_equal(got_v, want_v)
