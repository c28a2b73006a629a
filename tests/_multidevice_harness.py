"""Subprocess body for test_multidevice.py — runs with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` set by the parent
BEFORE jax is imported (the flag is read at backend init, so it cannot be
flipped inside an already-running test process).

Asserts on a real 8-device host mesh:
  - the shard_map driver is bit-identical to the vmap driver (which needs
    no devices and is tested everywhere else), unfiltered and filtered;
  - mutation (delete/upsert/compact) threads through the multi-device
    path: both drivers agree after every epoch and tombstones never leak;
  - after a mesh search, and after compaction, each device holds exactly
    one shard of the engine's state.
Exits 0 and prints OK on success; any assertion kills the process.
"""
import os
import sys

assert "--xla_force_host_platform_device_count=8" in \
    os.environ.get("XLA_FLAGS", ""), "harness must run with forced devices"

import jax  # noqa: E402  (import order is the point)
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

assert jax.device_count() >= 8, f"got {jax.device_count()} devices"

from repro.core.lists import filter_words  # noqa: E402
from repro.data import vectors  # noqa: E402
from repro.engine import EngineConfig, SearchEngine, ShardedEngine  # noqa: E402

S = 8
ds = vectors.make_sift_like(n=2400, nt=1200, nq=6, d=32, ncl=16, seed=3)
cfg = EngineConfig(nprobe=2, rerank_mult=4)
eng = SearchEngine.build(jax.random.PRNGKey(0), jnp.asarray(ds.train),
                         jnp.asarray(ds.base), m=8, nlist=16, config=cfg,
                         coarse_iters=4, pq_iters=4)
sh = ShardedEngine(eng, S)
mesh = jax.sharding.Mesh(np.array(jax.devices()[:S]), ("shards",))
q = jnp.asarray(ds.queries)


def drivers_agree(tag):
    rm = sh.search(q, 10, mesh=mesh)
    rv = sh.search(q, 10)
    np.testing.assert_array_equal(np.asarray(rm.dists), np.asarray(rv.dists),
                                  err_msg=tag)
    np.testing.assert_array_equal(np.asarray(rm.ids), np.asarray(rv.ids),
                                  err_msg=tag)
    for a, b in zip(rm.stats, rv.stats):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=tag)
    return rm


def one_shard_per_device(tag):
    """Every shard-partitioned array lives on the mesh, split so that each
    device holds exactly one shard — no whole copy left on device 0."""
    leaves = jax.tree.leaves((sh._state, sh.member_s))
    arrays = [x for x in leaves if isinstance(x, jax.Array)]
    assert arrays, tag
    for x in arrays:
        shards = x.addressable_shards
        assert {s.device for s in shards} == set(mesh.devices.flat), tag
        assert all(s.data.shape == (1,) + x.shape[1:] for s in shards), (
            tag, x.shape, [s.data.shape for s in shards])


r0 = drivers_agree("pristine")
one_shard_per_device("pristine")
assert (np.asarray(r0.stats.rows_tombstoned) == 0).all()

# mutation over the 8-way mesh: delete / upsert / compact, re-checking the
# driver pair after every epoch
rng = np.random.default_rng(41)
dead = rng.choice(2400, size=160, replace=False)
assert sh.delete(dead) == 160
r1 = drivers_agree("post-delete")
assert (np.asarray(r1.stats.rows_tombstoned) > 0).all()
assert not np.isin(np.asarray(r1.ids), dead).any(), "tombstone leaked"

new_ids = np.arange(2400, 2480)
sh.upsert(new_ids, rng.normal(size=(80, 32)).astype(np.float32))
drivers_agree("post-upsert")

assert sh.compact() == 160
assert sh.n_tombstones == 0
r3 = drivers_agree("post-compact")
one_shard_per_device("post-compact")
assert (np.asarray(r3.stats.rows_tombstoned) == 0).all()
assert not np.isin(np.asarray(r3.ids), dead).any()

# filtered path over the mesh: an arbitrary bitmap at the LIVE width (the
# upsert above grew cap, so a pristine-width bitmap would be refused)
fb = jnp.asarray(
    rng.integers(0, 256, (16, filter_words(sh.cap)), dtype=np.uint8))
rf_m = sh.search(q, 10, filter_bits=fb, mesh=mesh)
rf_v = sh.search(q, 10, filter_bits=fb)
np.testing.assert_array_equal(np.asarray(rf_m.ids), np.asarray(rf_v.ids))

# anytime path over the mesh (docs/anytime.md): margin policy + in-kernel
# early exit through the stream scan — drivers must agree on results AND the
# pruned/skipped counters, and tau=inf must match a fixed-policy engine
cfg_any = EngineConfig(nprobe=2, rerank_mult=4, scan_impl="stream",
                       probe_policy="margin", early_exit=True)
eng_any = SearchEngine.build(jax.random.PRNGKey(0), jnp.asarray(ds.train),
                             jnp.asarray(ds.base), m=8, nlist=16,
                             config=cfg_any, coarse_iters=4, pq_iters=4)
sh_any = ShardedEngine(eng_any, S)
for tau in (float("inf"), 0.2):
    ra_m = sh_any.search(q, 10, margin_tau=tau, mesh=mesh)
    ra_v = sh_any.search(q, 10, margin_tau=tau)
    np.testing.assert_array_equal(np.asarray(ra_m.ids), np.asarray(ra_v.ids),
                                  err_msg=f"anytime tau={tau}")
    np.testing.assert_array_equal(np.asarray(ra_m.dists),
                                  np.asarray(ra_v.dists),
                                  err_msg=f"anytime tau={tau}")
    for a, b in zip(ra_m.stats, ra_v.stats):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"anytime stats tau={tau}")
cfg_fix = EngineConfig(nprobe=2, rerank_mult=4, scan_impl="stream")
eng_fix = SearchEngine.build(jax.random.PRNGKey(0), jnp.asarray(ds.train),
                             jnp.asarray(ds.base), m=8, nlist=16,
                             config=cfg_fix, coarse_iters=4, pq_iters=4)
sh_fix = ShardedEngine(eng_fix, S)
r_fix = sh_fix.search(q, 10, mesh=mesh)
r_inf = sh_any.search(q, 10, margin_tau=float("inf"), mesh=mesh)
np.testing.assert_array_equal(np.asarray(r_inf.ids), np.asarray(r_fix.ids))
np.testing.assert_array_equal(np.asarray(r_inf.dists),
                              np.asarray(r_fix.dists))
assert (np.asarray(r_inf.stats.lists_pruned) == 0).all()
r_tight = sh_any.search(q, 10, margin_tau=0.0, mesh=mesh)
assert (np.asarray(r_tight.stats.lists_pruned) > 0).any(), \
    "tau=0 pruned nothing across 8 shards"

print("OK")
sys.exit(0)
