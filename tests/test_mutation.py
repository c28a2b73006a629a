"""Live mutable index: epoch-versioned upserts/deletes under an oracle.

The contract under test is docs/mutability.md: after ANY interleaving of
upserts, deletes and compactions, ``search`` / ``search_jit`` return results
bit-identical to a from-scratch engine rebuilt over the surviving rows (same
centroids, codebook and cap) — across every scan/rerank impl, the filtered
and namespaced paths, and both ShardedEngine drivers. ADC accumulation is
integer-exact and the fixed-shape encoder makes codes batch-independent, so
every comparison here is ``assert_array_equal``, not allclose.

Plus: mutation primitives (watermark/tombstone/live-bits invariants),
epoch/stats accounting, selective autotune invalidation, serving entry
points, a hypothesis sweep over random mutation programs, and a threaded
stress test hammering the ServingLoop with queries during mutation.
"""
import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as hst

from repro.core import ivf
from repro.core.lists import (ListStore, aligned_cap, append_rows,
                              build_lists, compact_lists, filter_from_attrs,
                              filter_words, grow_cap, live_counts,
                              live_filter_bits, locate_rows, pack_filter_mask,
                              tombstone_counts, tombstone_rows)
from repro.data import vectors
from repro.engine import EngineConfig, SearchEngine, ShardedEngine
from repro.kernels import ops as ops_mod
from repro.serving.loop import ServingLoop

# ---------------------------------------------------------------------------
# shared build (immutable jax arrays: engines wrapping it never alias state)
# ---------------------------------------------------------------------------

NLIST = 16
D = 32
M = 8


@functools.lru_cache(maxsize=None)
def _built():
    ds = vectors.make_sift_like(n=3000, nt=1500, nq=8, d=D, ncl=16, seed=3)
    index = ivf.build_ivf(jax.random.PRNGKey(0), jnp.asarray(ds.train),
                          jnp.asarray(ds.base), m=M, nlist=NLIST,
                          coarse_iters=4, pq_iters=4)
    return ds, index


def _attr_of(gids):
    return (np.asarray(gids, np.int64) % 5).astype(np.int32)


def _with_attrs(store: ListStore) -> ListStore:
    """Attach a deterministic attrs column derived from each row's gid."""
    ids = np.asarray(store.ids)
    attrs = np.where(ids >= 0, _attr_of(np.maximum(ids, 0)), -1).astype(np.int32)
    return store._replace(attrs=jnp.asarray(attrs))


def mk_engine(cfg: EngineConfig, *, attrs=False, namespaces=None) -> SearchEngine:
    ds, index = _built()
    store = index.lists
    if attrs:
        store = _with_attrs(store)
    return SearchEngine(index._replace(lists=store), base=jnp.asarray(ds.base),
                        config=cfg, namespaces=namespaces)


class Model:
    """Host-side mirror of the live row set: gid -> vector."""

    def __init__(self, base: np.ndarray):
        self.rows = {g: np.asarray(base[g]) for g in range(base.shape[0])}

    def delete(self, gids):
        for g in np.asarray(gids).ravel():
            self.rows.pop(int(g), None)

    def upsert(self, gids, vecs):
        for g, v in zip(np.asarray(gids).ravel(), np.asarray(vecs)):
            self.rows[int(g)] = np.asarray(v, np.float32)

    def survivors(self):
        surv = np.array(sorted(self.rows), np.int64)
        vecs = (np.stack([self.rows[int(g)] for g in surv])
                if surv.size else np.zeros((0, D), np.float32))
        return surv, vecs


def rebuild_oracle(model: Model, cap: int, cfg: EngineConfig, *, attrs=False,
                   namespaces=None):
    """From-scratch engine over the surviving rows: the ground truth.

    Same centroids/codebook as the live engine, same cap (the layout knob a
    grow can change), rows encoded through the same fixed-shape encoder —
    so a correct mutable engine must match it bitwise. The oracle's ids are
    positions into the survivor array; ``surv`` maps them back to gids.
    """
    _, index = _built()
    surv, vecs = model.survivors()
    assign, packed = ivf.encode_rows(index.centroids, index.codebook,
                                     jnp.asarray(vecs))
    store = build_lists(np.asarray(assign), np.asarray(packed),
                        ids=np.arange(surv.size, dtype=np.int32),
                        nlist=NLIST, cap=cap,
                        attrs=_attr_of(surv) if attrs else None)
    eng = SearchEngine(index._replace(lists=store), config=cfg,
                       base=jnp.asarray(vecs) if surv.size else
                       jnp.zeros((1, D), jnp.float32),
                       namespaces=namespaces)
    return eng, surv


def _to_gids(ids, surv):
    ids = np.asarray(ids)
    return np.where(ids >= 0, surv[np.maximum(ids, 0)] if surv.size else -1, -1)


def assert_matches_oracle(eng, model, q, *, k=10, filter_fn=None,
                          namespaces=None, ns_table=None):
    """search AND search_jit of the live engine vs the rebuilt oracle."""
    cfg = eng.config
    cap = eng.index.lists.cap
    oracle, surv = rebuild_oracle(model, cap, cfg,
                                  attrs=filter_fn is not None,
                                  namespaces=ns_table)
    fb_live = fb_oracle = None
    if filter_fn is not None:
        # filters are derived from each engine's OWN live store — a grow
        # may have changed cap, so the caller can't share one bitmap
        fb_live = filter_from_attrs(eng.index.lists, filter_fn)
        fb_oracle = filter_from_attrs(oracle.index.lists, filter_fn)
    for call in ("search", "search_jit"):
        r_mut = getattr(eng, call)(q, k, filter_bits=fb_live,
                                   namespaces=namespaces)
        r_orc = getattr(oracle, call)(q, k, filter_bits=fb_oracle,
                                      namespaces=namespaces)
        np.testing.assert_array_equal(np.asarray(r_mut.dists),
                                      np.asarray(r_orc.dists), err_msg=call)
        np.testing.assert_array_equal(np.asarray(r_mut.ids),
                                      _to_gids(r_orc.ids, surv), err_msg=call)
        # live stats must partition: filtered counts only live rows, the
        # oracle (tombstone-free by construction) reports zero tombstoned
        assert (np.asarray(r_orc.stats.rows_tombstoned) == 0).all()
    return oracle, surv


def _mutate(eng, model, *, seed=7, n_delete=200, n_new=150, n_re=50,
            id_base=3000):
    """The canonical program: delete a slab, insert new ids, re-upsert."""
    rng = np.random.default_rng(seed)
    dead = rng.choice(3000, size=n_delete, replace=False)
    assert eng.delete(dead) == n_delete
    model.delete(dead)
    new_ids = np.arange(id_base, id_base + n_new)
    new_vecs = rng.normal(size=(n_new, D)).astype(np.float32)
    eng.upsert(new_ids, new_vecs)
    model.upsert(new_ids, new_vecs)
    re_ids = np.setdiff1d(np.arange(3000), dead)[:n_re]
    re_vecs = rng.normal(size=(n_re, D)).astype(np.float32)
    eng.upsert(re_ids, re_vecs)
    model.upsert(re_ids, re_vecs)


# ---------------------------------------------------------------------------
# mutation primitives (core.lists)
# ---------------------------------------------------------------------------

def _tiny_store(nlist=4, cap=8, m=4, rows_per_list=(3, 0, 5, 2), seed=0):
    rng = np.random.default_rng(seed)
    assign = np.repeat(np.arange(nlist), rows_per_list)
    packed = rng.integers(0, 256, (assign.size, m // 2), np.uint8)
    return build_lists(assign, packed, nlist=nlist, cap=cap)


def test_append_rows_slots_watermark_and_overflow():
    st = _tiny_store()
    packed = np.full((3, 2), 9, np.uint8)
    st2, slots = append_rows(st, np.array([0, 2, 0]), packed,
                             np.array([100, 101, 102], np.int32))
    # slot = list watermark + stable rank within the batch
    np.testing.assert_array_equal(slots, [3, 5, 4])
    assert int(st2.sizes[0]) == 5 and int(st2.sizes[2]) == 6
    np.testing.assert_array_equal(np.asarray(st2.ids[0, 3:5]), [100, 102])
    assert int(st2.ids[2, 5]) == 101
    # original store untouched (jax arrays are immutable)
    assert int(st.sizes[0]) == 3
    with pytest.raises(ValueError, match="spare capacity"):
        append_rows(st2, np.full(4, 2), np.zeros((4, 2), np.uint8),
                    np.arange(200, 204, dtype=np.int32))


def test_append_rows_attrs_contract():
    st = _tiny_store()
    with pytest.raises(ValueError, match="attrs"):
        append_rows(st, np.array([0]), np.zeros((1, 2), np.uint8),
                    np.array([7], np.int32), attrs=np.array([1], np.int32))
    st_a = _with_attrs(st)
    st2, slots = append_rows(st_a, np.array([1]), np.zeros((1, 2), np.uint8),
                             np.array([7], np.int32),
                             attrs=np.array([42], np.int32))
    assert int(st2.attrs[1, slots[0]]) == 42


def test_tombstone_marks_ids_attrs_and_live_counts():
    st = _with_attrs(_tiny_store())
    st2 = tombstone_rows(st, np.array([0, 2]), np.array([1, 4]))
    assert int(st2.ids[0, 1]) == -1 and int(st2.attrs[0, 1]) == -1
    assert int(st2.ids[2, 4]) == -1
    # watermark unchanged, live shrinks, tombstones appear
    np.testing.assert_array_equal(np.asarray(st2.sizes), np.asarray(st.sizes))
    np.testing.assert_array_equal(np.asarray(live_counts(st2)), [2, 0, 4, 2])
    np.testing.assert_array_equal(np.asarray(tombstone_counts(st2)),
                                  [1, 0, 1, 0])
    # live bitmap has exactly the live slots set
    bits = live_filter_bits(st2)
    from repro.core.lists import unpack_filter_mask
    np.testing.assert_array_equal(
        np.asarray(unpack_filter_mask(bits, st2.cap)),
        np.asarray(st2.ids >= 0))


def test_grow_cap_pads_and_refuses_shrink():
    st = _with_attrs(_tiny_store())
    g = grow_cap(st, 16)
    assert g.cap == 16 and g.codes.shape == (4, 16, 2)
    np.testing.assert_array_equal(np.asarray(g.ids[:, 8:]), -1)
    np.testing.assert_array_equal(np.asarray(g.attrs[:, 8:]), -1)
    np.testing.assert_array_equal(np.asarray(g.ids[:, :8]), np.asarray(st.ids))
    assert grow_cap(st, 8) is st
    with pytest.raises(ValueError):
        grow_cap(st, 4)


@pytest.mark.parametrize("fullest,cap", [
    (1, 128), (5, 128), (128, 128), (129, 256), (1000, 1024), (1024, 1024),
    (1025, 2048), (4000, 4096)])
def test_default_cap_is_a_stream_tile_multiple(fullest, cap):
    """Without an explicit cap, build_lists rounds the fullest list up so the
    stream kernels always get a lane-aligned tile: 128-row granules up to
    1024, whole 1024-row tiles beyond."""
    assert aligned_cap(fullest) == cap
    st = _tiny_store(nlist=2, cap=None, rows_per_list=(fullest, 3))
    assert st.cap == cap and st.codes.shape == (2, cap, 2)
    assert int(st.sizes[0]) == fullest


def test_compact_lists_preserves_survivor_order():
    st = _tiny_store()
    st2 = tombstone_rows(st, np.array([2, 2, 0]), np.array([0, 3, 1]))
    st3 = compact_lists(st2)
    np.testing.assert_array_equal(np.asarray(st3.sizes), [2, 0, 3, 2])
    # list 2 held gids 3..7; slots 0 and 3 died -> survivors 4, 5, 7 in order
    np.testing.assert_array_equal(np.asarray(st3.ids[2, :3]), [4, 5, 7])
    np.testing.assert_array_equal(
        np.asarray(st3.codes[2, :3]),
        np.asarray(st2.codes)[2][np.array([1, 2, 4])])
    # shrink below the largest live list refuses
    with pytest.raises(ValueError):
        compact_lists(st2, cap=2)
    small = compact_lists(st2, cap=4)
    assert small.cap == 4


def test_locate_rows_live_only():
    st = _tiny_store()
    st2 = tombstone_rows(st, np.array([0]), np.array([0]))
    loc = locate_rows(st2)
    assert 0 not in loc            # gid 0 was (list 0, slot 0)
    assert loc[1] == (0, 1)
    assert loc[3] == (2, 0)
    assert len(loc) == 9


# ---------------------------------------------------------------------------
# the headline: oracle bit-identity across impls
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scan_impl", ["ref", "stream"])
@pytest.mark.parametrize("rerank_impl", ["gathered", "stream"])
def test_mutation_oracle_bit_identity(scan_impl, rerank_impl):
    ds, _ = _built()
    cfg = EngineConfig(nprobe=8, rerank_mult=4, scan_impl=scan_impl,
                       rerank_impl=rerank_impl)
    eng = mk_engine(cfg)
    model = Model(np.asarray(ds.base))
    _mutate(eng, model)
    assert_matches_oracle(eng, model, jnp.asarray(ds.queries))


def test_mutation_oracle_no_rerank():
    ds, _ = _built()
    cfg = EngineConfig(nprobe=8, rerank_mult=0)
    eng = mk_engine(cfg)
    model = Model(np.asarray(ds.base))
    _mutate(eng, model)
    assert_matches_oracle(eng, model, jnp.asarray(ds.queries))


@pytest.mark.parametrize("scan_impl", ["ref", "stream"])
def test_mutation_oracle_filtered(scan_impl):
    ds, _ = _built()
    cfg = EngineConfig(nprobe=8, rerank_mult=4, scan_impl=scan_impl)
    eng = mk_engine(cfg, attrs=True)
    model = Model(np.asarray(ds.base))
    rng = np.random.default_rng(11)
    dead = rng.choice(3000, size=150, replace=False)
    eng.delete(dead)
    model.delete(dead)
    new_ids = np.arange(3000, 3100)
    new_vecs = rng.normal(size=(100, D)).astype(np.float32)
    # upserting into an attrs-bearing store requires attrs for the rows
    eng.upsert(new_ids, new_vecs, attrs=_attr_of(new_ids))
    model.upsert(new_ids, new_vecs)
    assert_matches_oracle(eng, model, jnp.asarray(ds.queries),
                          filter_fn=lambda a: (a % 5) != 2)


def test_mutation_oracle_namespaced():
    ds, index = _built()
    member = np.zeros((2, NLIST), bool)
    member[0, :NLIST // 2] = True
    member[1, NLIST // 2:] = True
    ns_table = jnp.asarray(member)
    cfg = EngineConfig(nprobe=8, rerank_mult=4, scan_impl="stream")
    eng = mk_engine(cfg, namespaces=ns_table)
    model = Model(np.asarray(ds.base))
    _mutate(eng, model, seed=13)
    ns = jnp.asarray([0, 1, -1, 0, 1, -1, 0, 1], jnp.int32)
    _, surv = assert_matches_oracle(eng, model, jnp.asarray(ds.queries),
                                    namespaces=ns, ns_table=ns_table)
    # isolation survives mutation: a restricted query only sees its lists
    r = eng.search(jnp.asarray(ds.queries), 10, namespaces=ns)
    ids = np.asarray(r.ids)
    loc = {g: eng.locate(g) for row in ids for g in row if g >= 0}
    for qi, n in enumerate(np.asarray(ns)):
        if n < 0:
            continue
        for g in ids[qi]:
            if g >= 0:
                assert member[int(n), loc[int(g)][0]]


def test_post_compact_bit_identity_and_shrink():
    ds, _ = _built()
    cfg = EngineConfig(nprobe=8, rerank_mult=4, scan_impl="stream",
                       rerank_impl="stream")
    eng = mk_engine(cfg)
    model = Model(np.asarray(ds.base))
    _mutate(eng, model)
    n_tomb = eng.n_tombstones
    assert n_tomb > 0
    assert eng.compact() == n_tomb
    assert eng.n_tombstones == 0 and eng.live_bits is None
    assert_matches_oracle(eng, model, jnp.asarray(ds.queries))
    # compaction with an explicit smaller cap still matches its oracle
    max_live = int(np.asarray(live_counts(eng.index.lists)).max())
    tight = -(-max_live // 8) * 8
    if tight < eng.index.lists.cap:
        eng.compact(cap=tight)
        assert eng.index.lists.cap == tight
        assert_matches_oracle(eng, model, jnp.asarray(ds.queries))


def test_capacity_growth_keeps_oracle_parity():
    ds, _ = _built()
    cfg = EngineConfig(nprobe=8, rerank_mult=4)
    eng = mk_engine(cfg)
    model = Model(np.asarray(ds.base))
    cap0 = eng.index.lists.cap
    # slam one list with enough rows to overflow its spare slots
    target = int(np.argmax(np.asarray(eng.index.lists.sizes)))
    cvec = np.asarray(eng.index.centroids[target])
    n_new = int(cap0)  # guaranteed overflow for the fullest list
    new_ids = np.arange(4000, 4000 + n_new)
    new_vecs = (cvec[None, :]
                + 0.01 * np.random.default_rng(5).normal(size=(n_new, D))
                ).astype(np.float32)
    eng.upsert(new_ids, new_vecs)
    model.upsert(new_ids, new_vecs)
    assert eng.index.lists.cap > cap0
    assert eng.index.lists.cap % 8 == 0
    assert_matches_oracle(eng, model, jnp.asarray(ds.queries))


def test_upsert_replaces_vector_exactly():
    ds, _ = _built()
    eng = mk_engine(EngineConfig(nprobe=NLIST, rerank_mult=8))
    probe = np.asarray(ds.base[42]) * 0.0 + 7.5  # far from everything
    eng.upsert(np.array([42]), probe[None, :])
    r = eng.search(jnp.asarray(probe), 1)
    assert int(r.ids[0, 0]) == 42
    assert float(r.dists[0, 0]) == 0.0


def test_delete_everything_returns_sentinels():
    ds, _ = _built()
    eng = mk_engine(EngineConfig(nprobe=8, rerank_mult=4))
    assert eng.delete(np.arange(3000)) == 3000
    r = eng.search(jnp.asarray(ds.queries), 10)
    assert (np.asarray(r.ids) == -1).all()
    assert np.isinf(np.asarray(r.dists)).all()
    # and reinsertion brings rows back
    eng.upsert(np.array([7]), np.asarray(ds.base[7])[None, :])
    r2 = eng.search(jnp.asarray(ds.base[7]), 1)
    assert int(r2.ids[0, 0]) == 7


def test_epoch_counters_and_noop_mutations():
    ds, _ = _built()
    eng = mk_engine(EngineConfig(nprobe=8, rerank_mult=4))
    assert eng.epoch == 0 and eng.n_tombstones == 0 and eng.live_bits is None
    assert eng.delete([99999]) == 0      # unknown id: no-op, no epoch bump
    assert eng.epoch == 0
    assert eng.upsert(np.empty(0, np.int64), np.empty((0, D))).size == 0
    assert eng.epoch == 0
    assert eng.delete([5, 5, 6]) == 2    # duplicates collapse
    assert eng.epoch == 1 and eng.n_tombstones == 2
    assert eng.live_bits is not None
    assert eng.locate(5) is None and eng.locate(7) is not None
    eng.upsert(np.array([5]), np.asarray(ds.base[5])[None, :])
    assert eng.epoch == 2
    assert eng.locate(5) is not None


def test_upsert_validation():
    ds, _ = _built()
    eng = mk_engine(EngineConfig(nprobe=8, rerank_mult=4))
    with pytest.raises(ValueError):
        eng.upsert(np.array([1, 2]), np.zeros((3, D)))
    with pytest.raises(ValueError):
        eng.upsert(np.array([-1]), np.zeros((1, D)))
    with pytest.raises(ValueError):
        eng.upsert(np.array([1, 1]), np.zeros((2, D)))
    with pytest.raises(ValueError, match="attrs"):
        eng.upsert(np.array([1]), np.zeros((1, D)),
                   attrs=np.array([3], np.int32))


def test_stats_partition_filtered_vs_tombstoned():
    ds, _ = _built()
    eng = mk_engine(EngineConfig(nprobe=NLIST, rerank_mult=4), attrs=True)
    q = jnp.asarray(ds.queries)
    dead = np.arange(0, 600)
    eng.delete(dead)
    # all-pass filter: rows_filtered must be 0, tombstones all visible
    fb_all = filter_from_attrs(eng.index.lists, lambda a: a >= 0)
    r = eng.search(q, 10, filter_bits=fb_all)
    assert (np.asarray(r.stats.rows_filtered) == 0).all()
    assert (np.asarray(r.stats.rows_tombstoned) == 600).all()
    # restrictive filter drops only LIVE rows; the partition is disjoint
    fb = filter_from_attrs(eng.index.lists, lambda a: (a % 5) == 0)
    r2 = eng.search(q, 10, filter_bits=fb)
    live_total = 3000 - 600
    pass_total = int(np.asarray(
        jnp.sum((jnp.asarray(_attr_of(np.arange(3000))) % 5 == 0)
                & (jnp.arange(3000) >= 600))))
    assert (np.asarray(r2.stats.rows_filtered)
            == live_total - pass_total).all()
    assert (np.asarray(r2.stats.rows_tombstoned) == 600).all()
    # unfiltered search still reports zero filtered
    r3 = eng.search(q, 10)
    assert (np.asarray(r3.stats.rows_filtered) == 0).all()
    assert (np.asarray(r3.stats.rows_tombstoned) == 600).all()


def test_stale_filter_width_rejected_after_growth():
    ds, _ = _built()
    eng = mk_engine(EngineConfig(nprobe=8, rerank_mult=4), attrs=True)
    fb = filter_from_attrs(eng.index.lists, lambda a: a >= 0)
    cap0 = eng.index.lists.cap
    # force a cap grow, then the pre-grow bitmap must be refused loudly
    target = int(np.argmax(np.asarray(eng.index.lists.sizes)))
    cvec = np.asarray(eng.index.centroids[target])
    n_new = int(cap0)
    vecs = (cvec[None, :] + 0.01 * np.random.default_rng(6)
            .normal(size=(n_new, D))).astype(np.float32)
    eng.upsert(np.arange(5000, 5000 + n_new), vecs,
               attrs=_attr_of(np.arange(5000, 5000 + n_new)))
    assert eng.index.lists.cap > cap0
    if fb.shape[1] < filter_words(eng.index.lists.cap):
        with pytest.raises(ValueError, match="cap"):
            eng.search(jnp.asarray(ds.queries), 10, filter_bits=fb)


# ---------------------------------------------------------------------------
# sharded: mutation threads through both drivers
# ---------------------------------------------------------------------------

def _assert_sharded_matches_oracle(sh, model, q, cfg, num_shards, *,
                                   mesh=None):
    oracle, surv = rebuild_oracle(model, sh.cap, cfg)
    sh_oracle = ShardedEngine(oracle, num_shards)
    r_mut = sh.search(q, 10, mesh=mesh)
    r_orc = sh_oracle.search(q, 10, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(r_mut.dists),
                                  np.asarray(r_orc.dists))
    np.testing.assert_array_equal(np.asarray(r_mut.ids),
                                  _to_gids(r_orc.ids, surv))
    return r_mut


@pytest.mark.parametrize("num_shards", [2, 3])
def test_sharded_mutation_oracle_vmap(num_shards):
    ds, _ = _built()
    cfg = EngineConfig(nprobe=8, rerank_mult=4, scan_impl="stream",
                       rerank_impl="stream")
    eng = mk_engine(cfg)
    sh = ShardedEngine(eng, num_shards)
    model = Model(np.asarray(ds.base))
    rng = np.random.default_rng(21)
    dead = rng.choice(3000, size=200, replace=False)
    # routing and bookkeeping agree with the single-host engine exactly
    assert sh.delete(dead) == eng.delete(dead) == dead.size
    model.delete(dead)
    new_ids = np.arange(3000, 3150)
    new_vecs = rng.normal(size=(150, D)).astype(np.float32)
    a_s = sh.upsert(new_ids, new_vecs)
    a_e = eng.upsert(new_ids, new_vecs)
    np.testing.assert_array_equal(np.asarray(a_s), np.asarray(a_e))
    model.upsert(new_ids, new_vecs)
    assert sh.epoch == eng.epoch == 2
    assert sh.n_tombstones == eng.n_tombstones
    q = jnp.asarray(ds.queries)
    r = _assert_sharded_matches_oracle(sh, model, q, cfg, num_shards)
    assert (np.asarray(r.stats.rows_tombstoned) > 0).all()
    # compaction reclaims and stays on the oracle
    assert sh.compact() == dead.size
    assert sh.n_tombstones == 0 and sh.live_s is None
    _assert_sharded_matches_oracle(sh, model, q, cfg, num_shards)


def test_sharded_mutation_oracle_shard_map():
    ds, _ = _built()
    cfg = EngineConfig(nprobe=8, rerank_mult=4)
    eng = mk_engine(cfg)
    sh = ShardedEngine(eng, 1)  # one shard per device; CI has one device
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("shards",))
    model = Model(np.asarray(ds.base))
    rng = np.random.default_rng(23)
    dead = rng.choice(3000, size=120, replace=False)
    sh.delete(dead)
    model.delete(dead)
    new_ids = np.arange(3000, 3080)
    new_vecs = rng.normal(size=(80, D)).astype(np.float32)
    sh.upsert(new_ids, new_vecs)
    model.upsert(new_ids, new_vecs)
    q = jnp.asarray(ds.queries)
    rm = _assert_sharded_matches_oracle(sh, model, q, cfg, 1, mesh=mesh)
    # both drivers agree with each other too
    rv = sh.search(q, 10)
    np.testing.assert_array_equal(np.asarray(rm.ids), np.asarray(rv.ids))
    np.testing.assert_array_equal(np.asarray(rm.stats.rows_tombstoned),
                                  np.asarray(rv.stats.rows_tombstoned))


def test_sharded_locate_and_reupsert():
    ds, _ = _built()
    cfg = EngineConfig(nprobe=8, rerank_mult=4)
    sh = ShardedEngine(mk_engine(cfg), 3)
    loc = sh.locate(42)
    assert loc is not None
    sh.delete([42])
    assert sh.locate(42) is None
    v = np.asarray(ds.base[42])[None, :]
    sh.upsert(np.array([42]), v)
    j, l, s = sh.locate(42)
    # re-routed to the same global list -> same shard/local by round robin
    assert (j, l) == (loc[0], loc[1])


# ---------------------------------------------------------------------------
# autotune invalidation (docs/mutability.md: no stale verdicts)
# ---------------------------------------------------------------------------

def test_clear_autotune_cache_selective():
    saved = dict(ops_mod._AUTOTUNE_CACHE)
    try:
        ops_mod._AUTOTUNE_CACHE.clear()
        ops_mod._AUTOTUNE_CACHE.update({
            ("scan", "cpu", False, 8, 512, 8, 64, 1.0): "a",
            ("scan", "cpu", False, 8, 1024, 8, 64, 1.0): "b",
            ("scan", "cpu", False, 8, 512, 8, 128, 0.5): "c",
            ("rerank", "cpu", False, 8, 40, 32, 10, 3000): "d",
            ("rerank", "cpu", False, 8, 40, 32, 10, 4096): "e",
        })
        # cap matcher touches only scan keys with that cap
        assert ops_mod.clear_autotune_cache(cap=512) == 2
        assert ("scan", "cpu", False, 8, 1024, 8, 64, 1.0) in \
            ops_mod._AUTOTUNE_CACHE
        assert len(ops_mod._AUTOTUNE_CACHE) == 3
        # n matcher touches only rerank keys with that N
        assert ops_mod.clear_autotune_cache(n=3000) == 1
        assert ("rerank", "cpu", False, 8, 40, 32, 10, 4096) in \
            ops_mod._AUTOTUNE_CACHE
        # nlist matcher
        assert ops_mod.clear_autotune_cache(nlist=128) == 0  # cap dropped it
        assert ops_mod.clear_autotune_cache(nlist=64) == 1
        # kind + no dims clears that kind
        assert ops_mod.clear_autotune_cache(kind="rerank") == 1
        assert ops_mod.autotune_cache_size() == 0
    finally:
        ops_mod._AUTOTUNE_CACHE.clear()
        ops_mod._AUTOTUNE_CACHE.update(saved)


def test_compaction_cap_change_retriggers_autotune_sweep():
    """Regression: a post-compaction shape change must re-run the sweep —
    a stale verdict for the old (G, cap, M, nlist) signature must not
    survive to serve the new shape."""
    ds, _ = _built()
    cfg = EngineConfig(nprobe=8, rerank_mult=0, scan_impl="auto")
    eng = mk_engine(cfg)
    q = jnp.asarray(ds.queries)
    cap0 = eng.index.lists.cap
    eng.search(q, 10)  # resolves the (..., cap0, ...) scan signature
    sig_hit = [k for k in ops_mod.autotune_cache()
               if k[0] == "scan" and k[4] == cap0 and k[6] == NLIST]
    assert sig_hit, "expected the sweep to have resolved this shape"
    eng.delete(np.arange(500))
    max_live = int(np.asarray(live_counts(eng.index.lists)).max())
    tight = -(-max_live // 8) * 8
    assert tight < cap0, "test needs the compaction to actually shrink cap"
    eng.compact(cap=tight)
    snap = ops_mod.autotune_cache()
    for k in sig_hit:
        assert k not in snap, "stale verdict survived the cap change"
    a0 = ops_mod.autotune_cache_size()
    eng.search(q, 10)  # must re-sweep for the new cap
    assert ops_mod.autotune_cache_size() == a0 + 1
    new_key = [k for k in ops_mod.autotune_cache()
               if k[0] == "scan" and k[4] == tight and k[6] == NLIST]
    assert new_key


# ---------------------------------------------------------------------------
# serving: mutation entry points + threaded stress
# ---------------------------------------------------------------------------

def _serving_engine():
    ds, _ = _built()
    return ds, mk_engine(EngineConfig(nprobe=8, rerank_mult=2))


def test_serving_mutation_entry_points():
    ds, eng = _serving_engine()
    loop = ServingLoop(eng, buckets=(4,), max_wait_s=0.001)
    with loop:
        r0 = loop.submit(ds.queries[0], k=5, tenant="t").result(timeout=60)
        assert r0.rows_tombstoned == 0
        assert loop.metrics().epoch == 0
        assert loop.delete(np.arange(300)) == 300
        r1 = loop.submit(ds.queries[0], k=5, tenant="t").result(timeout=60)
        assert r1.rows_tombstoned > 0
        m = loop.metrics()
        assert m.epoch == 1
        assert m.rows_tombstoned == r1.rows_tombstoned
        assert loop.stats.get("t").rows_tombstoned == r1.rows_tombstoned
        loop.upsert(np.array([9000]), np.asarray(ds.base[0])[None, :])
        reclaimed = loop.compact()
        # the upsert may itself have compacted while growing a full list;
        # either way every tombstone is gone afterwards
        assert reclaimed >= 0 and eng.n_tombstones == 0
        r2 = loop.submit(ds.queries[0], k=5, tenant="t").result(timeout=60)
        assert r2.rows_tombstoned == 0
        assert loop.metrics().epoch == eng.epoch >= 3


def test_serving_stress_queries_during_mutation():
    """Hammer the loop with queries while a mutator thread upserts, deletes
    and compacts: zero failed futures, zero stale-epoch results (a gid
    deleted before the run never reappears), epochs advance."""
    ds, eng = _serving_engine()
    pre_dead = np.arange(0, 100)
    eng.delete(pre_dead)
    eng.compact()
    pre_dead_set = set(pre_dead.tolist())
    # mutator only touches this disjoint pool, so base/cap shapes stay
    # stable and queries never see a mid-run recompile storm
    pool = np.arange(100, 400)
    stop = threading.Event()
    mut_err = []

    def mutate():
        rng = np.random.default_rng(31)
        try:
            while not stop.is_set():
                sel = rng.choice(pool, size=40, replace=False)
                eng.delete(sel)
                vecs = rng.normal(size=(sel.size, D)).astype(np.float32)
                eng.upsert(np.sort(sel), vecs)
                eng.compact()
        except Exception as e:  # surface in the main thread
            mut_err.append(e)

    loop = ServingLoop(eng, buckets=(4,), max_wait_s=0.001)
    with loop:
        # compile the bucket before the clock starts
        loop.submit(ds.queries[0], k=5).result(timeout=120)
        epoch0 = loop.metrics().epoch
        t = threading.Thread(target=mutate, daemon=True)
        t.start()
        futures = []
        try:
            for i in range(120):
                q = np.asarray(ds.queries[i % ds.queries.shape[0]])
                futures.append(loop.submit(q, k=5, tenant=f"t{i % 3}"))
        finally:
            stop.set()
            t.join(timeout=30)
        results = [f.result(timeout=120) for f in futures]  # zero failures
    assert not mut_err, mut_err
    for r in results:
        for g in r.ids:
            g = int(g)
            assert g not in pre_dead_set, "stale-epoch result leaked"
            assert g == -1 or g < 3000
    assert loop.metrics().epoch > epoch0
    # quiesced index agrees with its oracle: the interleaving left no damage
    model = Model(np.asarray(ds.base))
    model.delete(pre_dead)
    live = np.asarray(eng.index.lists.ids)
    live_gids = set(int(g) for g in live[live >= 0])
    for g in list(model.rows):
        if g not in live_gids:
            del model.rows[g]
    # re-upserted vectors: read them back out of the engine's base
    locs = {g: eng.locate(g) for g in live_gids}
    base_np = np.asarray(eng.base)
    for g in live_gids:
        model.rows[g] = base_np[np.asarray(eng.index.lists.ids)[
            locs[g][0], locs[g][1]]]
    assert_matches_oracle(eng, model, jnp.asarray(ds.queries))


# ---------------------------------------------------------------------------
# hypothesis: random mutation programs vs the oracle (tie-aware on ids)
# ---------------------------------------------------------------------------

def _assert_tie_aware_equal(d_a, i_a, d_b, i_b):
    """Distances must match bitwise; ids must match except inside exact
    distance ties, where any permutation of the tied ids is legal (layout
    differences legitimately reorder equal keys in masked_topk)."""
    d_a, i_a = np.asarray(d_a), np.asarray(i_a)
    d_b, i_b = np.asarray(d_b), np.asarray(i_b)
    np.testing.assert_array_equal(d_a, d_b)
    for qi in range(d_a.shape[0]):
        row = d_a[qi]
        for v in np.unique(row):
            grp = row == v
            assert (sorted(i_a[qi][grp].tolist())
                    == sorted(i_b[qi][grp].tolist()))


_PROGRAM = hst.lists(
    hst.tuples(hst.integers(min_value=0, max_value=3),
               hst.integers(min_value=0, max_value=2**31 - 1)),
    min_size=1, max_size=6)


@pytest.mark.slow
@given(program=_PROGRAM)
@settings(max_examples=10, deadline=None,
          suppress_health_check=[HealthCheck.too_slow,
                                 HealthCheck.data_too_large,
                                 HealthCheck.filter_too_much])
def test_random_mutation_programs_match_oracle(program):
    ds, _ = _built()
    cfg = EngineConfig(nprobe=8, rerank_mult=4, scan_impl="stream")
    eng = mk_engine(cfg)
    model = Model(np.asarray(ds.base))
    next_gid = 3000
    for op, seed in program:
        rng = np.random.default_rng(seed)
        if op == 0:      # delete a random slab
            gids = list(model.rows)
            if gids:
                sel = rng.choice(gids, size=min(100, len(gids)),
                                 replace=False)
                assert eng.delete(sel) == np.unique(sel).size
                model.delete(sel)
        elif op == 1:    # insert brand-new ids
            n = int(rng.integers(1, 80))
            gids = np.arange(next_gid, next_gid + n)
            next_gid += n
            vecs = rng.normal(size=(n, D)).astype(np.float32)
            eng.upsert(gids, vecs)
            model.upsert(gids, vecs)
        elif op == 2:    # re-upsert existing ids with new vectors
            gids = sorted(model.rows)
            if gids:
                sel = np.unique(rng.choice(gids, size=min(50, len(gids))))
                vecs = rng.normal(size=(sel.size, D)).astype(np.float32)
                eng.upsert(sel, vecs)
                model.upsert(sel, vecs)
        else:            # compact
            eng.compact()
            assert eng.n_tombstones == 0
    oracle, surv = rebuild_oracle(model, eng.index.lists.cap, cfg)
    q = jnp.asarray(ds.queries)
    r_mut = eng.search(q, 10)
    r_orc = oracle.search(q, 10)
    _assert_tie_aware_equal(r_mut.dists, r_mut.ids,
                            r_orc.dists, _to_gids(r_orc.ids, surv))


# ---------------------------------------------------------------------------
# durability: background snapshots during the mutation storm
# ---------------------------------------------------------------------------

def test_serving_stress_with_background_snapshots(tmp_path):
    """Queries + upserts/deletes/compactions while the loop's checkpoint
    thread snapshots concurrently (docs/persistence.md): zero failed
    futures, zero checkpoint errors, and every durable state the run
    leaves behind — mid-run directory copies and the final directory —
    recovers bit-identical to a from-scratch engine replaying the same
    acknowledged mutation prefix (or fails loudly on a torn copy)."""
    import shutil

    from repro import persist
    from repro.persist import CorruptSnapshotError, CorruptWALError

    ds, eng = _serving_engine()
    d = str(tmp_path / "dur")
    pool = np.arange(100, 400)
    stop = threading.Event()
    mut_err = []
    applied = []  # ops in WAL-seq order (single mutator => issue order)

    def mutate():
        rng = np.random.default_rng(37)
        try:
            rounds = 0
            while not stop.is_set():
                sel = rng.choice(pool, size=40, replace=False)
                if eng.delete(sel):
                    applied.append(("delete", np.sort(np.asarray(sel))))
                vecs = rng.normal(size=(sel.size, D)).astype(np.float32)
                gids = np.sort(sel)
                eng.upsert(gids, vecs)
                applied.append(("upsert", gids, vecs))
                rounds += 1
                if rounds % 5 == 0:
                    eng.compact()
                    applied.append(("compact",))
        except Exception as e:  # surface in the main thread
            mut_err.append(e)

    loop = ServingLoop(eng, buckets=(4,), max_wait_s=0.001,
                       snapshot_dir=d, snapshot_every=0.05)
    frozen = []
    with loop:
        loop.submit(ds.queries[0], k=5).result(timeout=120)
        t = threading.Thread(target=mutate, daemon=True)
        t.start()
        futures = []
        try:
            for i in range(80):
                q = np.asarray(ds.queries[i % ds.queries.shape[0]])
                futures.append(loop.submit(q, k=5, tenant=f"t{i % 3}"))
                if i % 25 == 20:  # freeze a mid-run durable state
                    fz = str(tmp_path / f"frozen{i}")
                    shutil.copytree(d, fz)
                    frozen.append(fz)
        finally:
            stop.set()
            t.join(timeout=60)
        results = [f.result(timeout=120) for f in futures]  # zero failures
        # The state is dirty here, so the checkpoint thread is guaranteed to
        # fire; under load its 0.05 s cadence can lag the (fast) submit
        # window, so wait for the first snapshot rather than racing it.
        deadline = time.monotonic() + 120.0
        while (loop.metrics().checkpoints == 0
               and loop.checkpoint_error is None
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert loop.checkpoint_error is None
        ckpts = loop.metrics().checkpoints
    assert not mut_err, mut_err
    assert ckpts >= 1, "background checkpointing never fired"
    assert len(results) == 80

    def replay_reference(n_ops):
        ref = mk_engine(EngineConfig(nprobe=8, rerank_mult=2))
        for op in applied[:n_ops]:
            if op[0] == "delete":
                ref.delete(op[1])
            elif op[0] == "upsert":
                ref.upsert(op[1], op[2])
            else:
                ref.compact()
        return ref

    q = jnp.asarray(ds.queries)
    # final state: recovery == live engine == from-scratch replay of ALL ops
    rec, info = persist.open_engine(d, attach=False)
    assert info.last_seq == len(applied)
    ref = replay_reference(len(applied))
    for other in (eng, ref):
        ra, rb = rec.search(q, 10), other.search(q, 10)
        np.testing.assert_array_equal(np.asarray(ra.dists),
                                      np.asarray(rb.dists))
        np.testing.assert_array_equal(np.asarray(ra.ids),
                                      np.asarray(rb.ids))
    # mid-run copies: prefix-or-loud (a copy racing the checkpointer may
    # have caught a GC'd segment — loud is correct; silent damage is not)
    opened = 0
    for fz in frozen:
        try:
            rec_f, info_f = persist.open_engine(fz, attach=False)
        except (CorruptSnapshotError, CorruptWALError):
            continue
        opened += 1
        assert info_f.last_seq <= len(applied)
        ref_f = replay_reference(info_f.last_seq)
        ra, rb = rec_f.search(q, 10), ref_f.search(q, 10)
        np.testing.assert_array_equal(np.asarray(ra.dists),
                                      np.asarray(rb.dists))
        np.testing.assert_array_equal(np.asarray(ra.ids),
                                      np.asarray(rb.ids))
    assert opened >= 1, "every mid-run copy was torn; expected >=1 clean"
