"""Filtered & namespaced search: bitmap helpers, oracle parity, isolation.

The contract under test is docs/filtering.md: a filtered search returns
exactly what an unfiltered search over only the passing rows would return —
the stream kernels' in-VMEM predicate masking must be bit-identical to the
gathered post-filter oracle at every selectivity; namespaces must confine a
query to its own lists end to end (single host, sharded, serving). Integer
ADC accumulation is exact, so scan comparisons are ``assert_array_equal``.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ivf
from repro.core.lists import (ListStore, build_lists, filter_from_attrs,
                              filter_pass_sizes, filter_words,
                              pack_filter_mask, partition_filter,
                              round_robin_perm, unpack_filter_mask)
from repro.core.pq import PQCodebook
from repro.core.topk import gather_ids, masked_topk
from repro.engine import EngineConfig, SearchEngine, ShardedEngine
from repro.engine.engine import fused_cache_size

SELECTIVITIES = (0.0, 0.01, 0.5, 1.0)


def _synth_index(nlist, cap, m, *, d=None, seed=0, occupancy="ragged"):
    """IVFIndex from raw random arrays (same shape contract as the build)."""
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


def _random_mask(index, selectivity, seed=7):
    """(nlist, cap) bool predicate over occupied slots only."""
    rng = np.random.default_rng(seed)
    nlist, cap = index.lists.ids.shape
    mask = rng.random((nlist, cap)) < selectivity
    return mask & np.asarray(index.lists.ids >= 0)


def _oracle_select(index, q, probes, mask, keep):
    """Gathered scan -> post-filter -> masked top-keep: the reference."""
    dg, ig = ivf.scan_probes(index, q, probes, impl="ref")
    ok = jnp.asarray(mask)[jnp.maximum(probes, 0)] & (ig >= 0)
    dg = jnp.where(ok, dg, jnp.inf).reshape(q.shape[0], -1)
    ig = jnp.where(ok, ig, -1).reshape(q.shape[0], -1)
    vals, pos = masked_topk(dg, ig >= 0, keep)
    return vals, gather_ids(ig, pos)


# ---------------------------------------------------------------------------
# bitmap helpers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cap", [1, 7, 8, 9, 129, 512])
def test_pack_unpack_roundtrip(cap):
    rng = np.random.default_rng(cap)
    mask = jnp.asarray(rng.random((5, cap)) < 0.5)
    bits = pack_filter_mask(mask)
    assert bits.dtype == jnp.uint8
    assert bits.shape == (5, filter_words(cap))
    np.testing.assert_array_equal(np.asarray(unpack_filter_mask(bits, cap)),
                                  np.asarray(mask))


def test_bit_layout_lsb_first():
    # slot w*8 + j  <->  bit j of word w
    mask = np.zeros((1, 16), bool)
    mask[0, 0] = True   # word 0 bit 0
    mask[0, 9] = True   # word 1 bit 1
    bits = np.asarray(pack_filter_mask(jnp.asarray(mask)))
    assert bits[0, 0] == 1 and bits[0, 1] == 2


def test_padded_slot_bits_are_zero_via_filter_from_attrs():
    rng = np.random.default_rng(0)
    n, nlist, cap = 50, 4, 32
    assign = rng.integers(0, nlist, n)
    packed = rng.integers(0, 256, (n, 2), np.uint8)
    attrs = rng.integers(0, 100, n).astype(np.int32)
    store = build_lists(assign, packed, nlist=nlist, cap=cap, attrs=attrs)
    assert store.attrs is not None and store.attrs.shape == (nlist, cap)
    bits = filter_from_attrs(store, lambda a: a >= 0)  # passes every real row
    got = np.asarray(unpack_filter_mask(bits, cap))
    np.testing.assert_array_equal(got, np.asarray(store.ids >= 0))
    # a store built without attrs refuses loudly
    bare = build_lists(assign, packed, nlist=nlist, cap=cap)
    with pytest.raises(ValueError):
        filter_from_attrs(bare, lambda a: a >= 0)


def test_filter_pass_sizes_ignores_stale_bits_past_occupancy():
    index, _ = _synth_index(6, 40, 4, occupancy="ragged")
    all_ones = pack_filter_mask(jnp.ones_like(index.lists.ids, dtype=bool))
    np.testing.assert_array_equal(
        np.asarray(filter_pass_sizes(index.lists, all_ones)),
        np.asarray(index.lists.sizes))


def test_partition_filter_matches_round_robin_layout():
    nlist, cap, shards = 10, 24, 4  # non-divisible -> padded layout
    rng = np.random.default_rng(3)
    bits = pack_filter_mask(jnp.asarray(rng.random((nlist, cap)) < 0.5))
    sharded = np.asarray(partition_filter(bits, shards))
    l = -(-nlist // shards)
    assert sharded.shape == (shards, l, filter_words(cap))
    perm = round_robin_perm(nlist, shards)
    flat = sharded.reshape(shards * l, -1)
    for padded_pos, global_list in enumerate(perm):
        if global_list < nlist:
            np.testing.assert_array_equal(flat[padded_pos],
                                          np.asarray(bits)[global_list])
        else:
            assert not flat[padded_pos].any()  # padding passes nothing


# ---------------------------------------------------------------------------
# stream-kernel parity vs the post-filter oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("selectivity", SELECTIVITIES)
def test_stream_scan_filter_parity_vs_oracle(selectivity):
    index, _ = _synth_index(12, 96, 4, occupancy="full", seed=2)
    q = _queries(index, 4)
    probes = jnp.asarray(
        np.random.default_rng(5).integers(0, 12, (4, 5)).astype(np.int32))
    mask = _random_mask(index, selectivity)
    fb = pack_filter_mask(jnp.asarray(mask))
    keep = 20
    ds, ids_s, _, _ = ivf.scan_probes_stream(index, q, probes, keep=keep,
                                             tile_n=32, filter_bits=fb)
    vals_s, pos_s = masked_topk(ds, ids_s >= 0, keep)
    got_ids = gather_ids(ids_s, pos_s)
    want_vals, want_ids = _oracle_select(index, q, probes, mask, keep)
    np.testing.assert_array_equal(np.asarray(got_ids), np.asarray(want_ids))
    np.testing.assert_array_equal(np.asarray(vals_s), np.asarray(want_vals))


def test_filter_with_ragged_occupancy_and_invalid_probes():
    # filters must compose with occupancy padding AND -1 probes
    index, _ = _synth_index(10, 64, 4, occupancy="ragged", seed=9)
    q = _queries(index, 3)
    probes = jnp.asarray(np.array([[0, 3, -1, 7], [9, -1, -1, 2],
                                   [-1, -1, -1, -1]], np.int32))
    mask = _random_mask(index, 0.5, seed=11)
    fb = pack_filter_mask(jnp.asarray(mask))
    keep = 12
    ds, ids_s, _, _ = ivf.scan_probes_stream(index, q, probes, keep=keep,
                                             tile_n=16, filter_bits=fb)
    vals_s, pos_s = masked_topk(ds, ids_s >= 0, keep)
    got_ids = gather_ids(ids_s, pos_s)
    want_vals, want_ids = _oracle_select(index, q, probes, mask, keep)
    np.testing.assert_array_equal(np.asarray(got_ids), np.asarray(want_ids))
    np.testing.assert_array_equal(np.asarray(vals_s), np.asarray(want_vals))


def test_all_filtered_lists_return_only_sentinels():
    index, _ = _synth_index(8, 48, 4, occupancy="full", seed=4)
    q = _queries(index, 2)
    probes = jnp.asarray(np.array([[0, 1, 2], [3, 4, 5]], np.int32))
    fb = pack_filter_mask(jnp.zeros_like(index.lists.ids, dtype=bool))
    ds, ids_s, _, _ = ivf.scan_probes_stream(index, q, probes, keep=10,
                                             tile_n=16, filter_bits=fb)
    assert np.all(np.asarray(ids_s) == -1)


# ---------------------------------------------------------------------------
# engine end to end: stream engine == gathered-oracle engine, jit == staged
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _engines():
    rng = np.random.default_rng(0)
    base = rng.standard_normal((1500, 32)).astype(np.float32)
    train = rng.standard_normal((1500, 32)).astype(np.float32)
    key = jax.random.PRNGKey(0)
    mk = lambda impl: SearchEngine.build(
        key, train, base, m=8, nlist=16,
        config=EngineConfig(nprobe=6, rerank_mult=4, scan_impl=impl))
    return mk("stream"), mk("ref"), base


@pytest.mark.parametrize("selectivity", SELECTIVITIES)
def test_engine_filtered_search_parity(selectivity):
    eng_s, eng_g, _ = _engines()
    q = _queries(eng_s.index, 5, seed=8)
    mask = _random_mask(eng_s.index, selectivity, seed=13)
    fb = pack_filter_mask(jnp.asarray(mask))
    rs = eng_s.search(q, 10, filter_bits=fb)
    rg = eng_g.search(q, 10, filter_bits=fb)
    np.testing.assert_array_equal(np.asarray(rs.ids), np.asarray(rg.ids))
    np.testing.assert_allclose(np.asarray(rs.dists), np.asarray(rg.dists),
                               rtol=1e-6)
    # every surfaced id passes the predicate
    passing = set()
    ids_np, mk = np.asarray(eng_s.index.lists.ids), mask
    for li in range(ids_np.shape[0]):
        for sl in range(ids_np.shape[1]):
            if ids_np[li, sl] >= 0 and mk[li, sl]:
                passing.add(int(ids_np[li, sl]))
    for gid in np.asarray(rs.ids).ravel():
        assert gid < 0 or gid in passing
    # rows_filtered counts the complement of the pass set over probed lists
    rf = np.asarray(rs.stats.rows_filtered)
    if selectivity == 1.0:
        np.testing.assert_array_equal(rf, 0)
    else:
        assert (rf > 0).all()


def test_all_ones_filter_bit_identical_to_unfiltered():
    eng_s, _, _ = _engines()
    q = _queries(eng_s.index, 4, seed=21)
    fb = pack_filter_mask(eng_s.index.lists.ids >= 0)
    r_f = eng_s.search(q, 10, filter_bits=fb)
    r_u = eng_s.search(q, 10)
    np.testing.assert_array_equal(np.asarray(r_f.ids), np.asarray(r_u.ids))
    np.testing.assert_array_equal(np.asarray(r_f.dists), np.asarray(r_u.dists))
    np.testing.assert_array_equal(np.asarray(r_f.stats.rows_filtered), 0)


def test_search_jit_filter_is_traced_not_static():
    eng_s, _, _ = _engines()
    q = _queries(eng_s.index, 3, seed=30)
    fb1 = pack_filter_mask(jnp.asarray(_random_mask(eng_s.index, 0.5, seed=1)))
    fb2 = pack_filter_mask(jnp.asarray(_random_mask(eng_s.index, 0.3, seed=2)))
    r1 = eng_s.search_jit(q, 10, filter_bits=fb1)
    n0 = fused_cache_size()
    r2 = eng_s.search_jit(q, 10, filter_bits=fb2)  # new VALUES, same shapes
    assert fused_cache_size() == n0, "filter values must not recompile"
    e1 = eng_s.search(q, 10, filter_bits=fb1)
    e2 = eng_s.search(q, 10, filter_bits=fb2)
    np.testing.assert_array_equal(np.asarray(r1.ids), np.asarray(e1.ids))
    np.testing.assert_array_equal(np.asarray(r2.ids), np.asarray(e2.ids))


def test_filter_shape_validation():
    eng_s, _, _ = _engines()
    q = _queries(eng_s.index, 1)
    with pytest.raises(ValueError, match="filter_bits"):
        eng_s.search(q, 5, filter_bits=jnp.zeros((3, 2), jnp.uint8))
    with pytest.raises(ValueError, match="namespace"):
        eng_s.search(q, 5, namespaces=jnp.zeros((1,), jnp.int32))


# ---------------------------------------------------------------------------
# namespaces: single host + ShardedEngine, both drivers
# ---------------------------------------------------------------------------

def _ns_setup():
    rng = np.random.default_rng(17)
    base = rng.standard_normal((1200, 32)).astype(np.float32)
    train = rng.standard_normal((1200, 32)).astype(np.float32)
    member = np.zeros((2, 12), bool)
    member[0, :6] = True
    member[1, 6:] = True
    eng = SearchEngine.build(
        jax.random.PRNGKey(1), train, base, m=8, nlist=12,
        config=EngineConfig(nprobe=4, rerank_mult=4, scan_impl="stream"),
        namespaces=jnp.asarray(member))
    ids_np = np.asarray(eng.index.lists.ids)
    owner = np.full(1200, -1)
    for li in range(12):
        for sl in range(ids_np.shape[1]):
            if ids_np[li, sl] >= 0:
                owner[ids_np[li, sl]] = 0 if li < 6 else 1
    q = _queries(eng.index, 5, seed=23)
    ns = jnp.asarray([0, 1, -1, 0, 1], jnp.int32)
    return eng, owner, q, ns


def _assert_isolated(ids, ns, owner):
    for qi, t in enumerate(np.asarray(ns)):
        for gid in np.asarray(ids)[qi]:
            if gid >= 0 and t >= 0:
                assert owner[gid] == t, f"namespace leak: q{qi} got {gid}"


def test_namespace_isolation_single_host():
    eng, owner, q, ns = _ns_setup()
    r = eng.search(q, 10, namespaces=ns)
    rj = eng.search_jit(q, 10, namespaces=ns)
    _assert_isolated(r.ids, ns, owner)
    np.testing.assert_array_equal(np.asarray(r.ids), np.asarray(rj.ids))
    # unrestricted query is bit-identical to a namespace-free search
    r_free = eng.search(q, 10)
    np.testing.assert_array_equal(np.asarray(r.ids[2]),
                                  np.asarray(r_free.ids[2]))


@pytest.mark.parametrize("num_shards", [1, 3])
def test_namespace_isolation_sharded_vmap(num_shards):
    eng, owner, q, ns = _ns_setup()
    sh = ShardedEngine(eng, num_shards)
    r = sh.search(q, 10, namespaces=ns)
    _assert_isolated(r.ids, ns, owner)
    # filter composes on top of namespaces in the sharded path too
    mask = _random_mask(eng.index, 0.5, seed=31)
    fb = pack_filter_mask(jnp.asarray(mask))
    rc = sh.search(q, 10, namespaces=ns, filter_bits=fb)
    _assert_isolated(rc.ids, ns, owner)
    assert (np.asarray(rc.stats.rows_filtered) > 0).all()


def test_namespace_isolation_sharded_shard_map():
    eng, owner, q, ns = _ns_setup()
    sh = ShardedEngine(eng, 1)  # one shard per device; CI has one device
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("shards",))
    mask = _random_mask(eng.index, 0.5, seed=37)
    fb = pack_filter_mask(jnp.asarray(mask))
    rm = sh.search(q, 10, namespaces=ns, filter_bits=fb, mesh=mesh)
    rv = sh.search(q, 10, namespaces=ns, filter_bits=fb)
    _assert_isolated(rm.ids, ns, owner)
    np.testing.assert_array_equal(np.asarray(rm.ids), np.asarray(rv.ids))
    np.testing.assert_array_equal(np.asarray(rm.stats.rows_filtered),
                                  np.asarray(rv.stats.rows_filtered))


def test_sharded_unfiltered_unchanged_by_namespace_support():
    # building a ShardedEngine from a namespace-capable engine and searching
    # without namespaces must match a namespace-free engine exactly
    eng, _, q, _ = _ns_setup()
    sh = ShardedEngine(eng, 3)
    bare = SearchEngine(eng.index, base=None if eng.base is None else eng.base,
                        config=eng.config)
    sh_bare = ShardedEngine(bare, 3)
    r1 = sh.search(q, 10)
    r2 = sh_bare.search(q, 10)
    np.testing.assert_array_equal(np.asarray(r1.ids), np.asarray(r2.ids))
    np.testing.assert_array_equal(np.asarray(r1.dists), np.asarray(r2.dists))


# ---------------------------------------------------------------------------
# serving: per-request namespaces + rows_filtered accounting
# ---------------------------------------------------------------------------

def test_serving_namespaces_and_filter_accounting():
    from repro.serving import ServingLoop

    eng, owner, _, _ = _ns_setup()
    mask = _random_mask(eng.index, 0.5, seed=41)
    fb = pack_filter_mask(jnp.asarray(mask))
    loop = ServingLoop(eng, buckets=(1, 4), filter_bits=fb)
    loop.start(warmup=True)
    try:
        compiles0 = loop.metrics().compiles
        rng = np.random.default_rng(43)
        futs = [loop.submit(rng.standard_normal(32).astype(np.float32), k=10,
                            tenant=f"t{i % 2}", namespace=i % 2)
                for i in range(6)]
        results = [f.result(timeout=60) for f in futs]
        assert loop.metrics().compiles == compiles0, \
            "filtered/namespaced steady-state traffic recompiled"
        for i, r in enumerate(results):
            assert r.rows_filtered > 0
            for gid in r.ids:
                if gid >= 0:
                    assert owner[gid] == i % 2
                    assert mask.ravel()[
                        np.flatnonzero(
                            np.asarray(eng.index.lists.ids).ravel() == gid)[0]]
        for t in ("t0", "t1"):
            st = loop.stats.get(t)
            assert st.queries == 3 and st.rows_filtered > 0
        with pytest.raises(ValueError, match="out of range"):
            loop.submit(np.zeros(32, np.float32), namespace=99)
    finally:
        loop.stop()
