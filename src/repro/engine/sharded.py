"""Shard-parallel engine execution over a device mesh.

The database's posting lists are partitioned round-robin into S shards
(``core.lists.partition_lists``); every shard runs the same local pipeline —
flat coarse over *its* centroids, grouped 4-bit scan, optional exact re-rank —
and the shard-local top-k results meet in ``core.topk.distributed_topk``:
an all-gather of 2k scalars per device, then one final re-top-k.

Base vectors for the exact re-rank are sharded too (``core.lists.
partition_base``): each shard holds only the (R, D) rows of the lists it
owns, with posting-list ids remapped to shard-local rows; results map back
to global ids via the shard's ``gids`` table just before the merge, so the
2k-scalar merge still needs no re-mapping.

Two drivers over the same per-shard function:
  - ``mesh=None``: ``jax.vmap`` with a named axis — S arbitrary, runs on one
    host; this is also how the merge is unit-tested.
  - ``mesh=...``: ``shard_map`` over a 1-D device mesh (axis ``"shards"``),
    one shard per device — the production layout. The first such search
    moves the shard state onto the mesh for good (each device then holds
    only its shard); the vmap driver reads that same placed state.

Live mutation (docs/mutability.md) is threaded through the shards:
``upsert`` routes rows with the *global* centroid table (bitwise the same
assignment the single-host engine makes), appends into the owning shard's
spare slots, and maintains the local-id remap (``gids_s`` grows in
place); ``delete`` tombstones slots; ``compact`` rebuilds every shard's
lists and base slice tombstone-free. All shard arrays live in one
``_ShardState`` snapshot swapped atomically per mutation, mirroring the
single-host ``EngineState``.
"""
from __future__ import annotations

import functools
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import ivf as ivf_mod
from repro.core import topk as topk_mod
from repro.core.kmeans import pairwise_sqdist
from repro.core.lists import (ListStore, aligned_cap, filter_pass_sizes,
                              pack_filter_mask, partition_base,
                              partition_filter, partition_lists,
                              round_robin_perm)
from repro.engine import rerank as rerank_mod
from repro.engine.engine import (MARGIN_PROBE_FILL, EngineConfig, QueryStats,
                                 SearchEngine, SearchResult,
                                 combine_filter_bits, scan_candidates)
from repro.kernels import ops as ops_mod

AXIS = "shards"


def _local_search(centroids, lists: ListStore, real, gids, codebook, base,
                  member, q, fbits, live, ns, tau, *, k: int,
                  nprobe: int, r: int, scan_impl: str, rerank_impl: str,
                  remap: bool, probe_policy: str = "fixed",
                  early_exit: bool = False):
    """One shard's pipeline + the cross-shard merge. Runs under a named axis.

    With ``remap=True`` the shard's list ids are *local* rows into its own
    ``base`` slice (see ``partition_base``): the scan and re-rank both work
    on local ids and ``gids`` translates back to global just before the
    distributed merge. With ``remap=False`` (no base held) ids are global
    throughout and ``gids`` is an unused dummy.

    ``member`` is the shard's (n_ns, L) slice of the namespace table,
    ``fbits`` its (L, W) slice of the per-request filter bitmap, ``live``
    its (L, W) slice of the engine-held live-row bitmap (None while the
    shard set carries no tombstones — docs/mutability.md), ``ns`` the
    replicated (Q,) namespace ids — any may be None (docs/filtering.md).
    A restricted query selects probes with ``masked_topk`` over its own
    lists only; padding lists are member-False everywhere, and with every
    query unrestricted the mask is all-True so the selection is exactly
    ``smallest_k`` — bit-identical to the namespace-free driver.

    Anytime knobs (docs/anytime.md): under ``probe_policy='margin'`` each
    shard prunes against the best centroid among *its own* lists (``tau``
    is the replicated traced margin width) — the margin is shard-local, so
    a shard holding none of the query's near lists prunes almost
    everything, which is exactly the work-skipping the policy wants.
    ``early_exit`` arms the stream kernel's tile pruning per shard; both
    counters are psum'd so the merged stats read as totals across shards,
    like every other counter.
    """
    index = ivf_mod.IVFIndex(centroids=centroids, codebook=codebook, lists=lists)
    nprobe_local = min(nprobe, centroids.shape[0])
    coarse_d = pairwise_sqdist(q, centroids)
    if member is not None and ns is not None:
        allow = (ns < 0)[:, None] | member[jnp.maximum(ns, 0)]
        cvals, probes = topk_mod.masked_topk(coarse_d, allow, nprobe_local)
    else:
        cvals, probes = topk_mod.smallest_k(coarse_d, nprobe_local)
    if probe_policy == "margin":
        probes, lists_pruned = topk_mod.margin_prune_probes(
            cvals, probes, jnp.inf if tau is None else tau)
    else:
        lists_pruned = jnp.zeros((q.shape[0],), jnp.int32)
    # same stage function as the single-host engine, including its stream
    # routing: each shard's local ListStore already has the
    # (nlist_local, cap, M//2) layout the stream kernel scans in place, so a
    # 'stream' (or 'auto'-resolved-to-stream) shard never materializes its
    # gathered code copy either. Tombstones ride the same path as the user
    # filter: ANDed in so the stream kernel's candidate budget skips them
    # before selection.
    eff = combine_filter_bits(fbits, live)
    flat_d, flat_ids, tiles_skipped, tiles_scanned = scan_candidates(
        index, q, probes, scan_impl=scan_impl, keep=(r * k) if r else k,
        filter_bits=eff, early_exit=early_exit,
        probe_fill=(MARGIN_PROBE_FILL if probe_policy == "margin" else 1.0))
    # re-rank (either impl) runs on the shard-local (R, D) base slice; local
    # candidate ids map back to global through gids only after the top-k,
    # just before the merge
    vals, out_ids, reranked = rerank_mod.finalize_candidates(
        flat_d, flat_ids, base, q, k, r, rerank_impl=rerank_impl)
    if remap:
        out_ids = jnp.where(out_ids >= 0, gids[jnp.maximum(out_ids, 0)], -1)
    mvals, mids = topk_mod.distributed_topk(vals, out_ids, k, AXIS)
    valid = probes >= 0
    safe = jnp.maximum(probes, 0)
    zeros = jnp.zeros((q.shape[0],), jnp.int32)
    live_sizes = (lists.sizes if live is None
                  else filter_pass_sizes(lists, live))
    if fbits is None:
        rows_filtered = zeros
    else:
        dropped = live_sizes - filter_pass_sizes(lists, eff)
        rows_filtered = jnp.sum(jnp.where(valid, dropped[safe], 0), axis=1)
    if live is None:
        rows_tombstoned = zeros
    else:
        tomb = lists.sizes - live_sizes
        rows_tombstoned = jnp.sum(jnp.where(valid, tomb[safe], 0), axis=1)
    stats = QueryStats(
        # count only probes of real lists — a shard with fewer real lists
        # than nprobe inevitably "probes" padding, which is zero work
        lists_probed=jax.lax.psum(
            jnp.sum((real[safe] & valid).astype(jnp.int32), axis=1), AXIS),
        codes_scanned=jax.lax.psum(
            jnp.sum(lists.probed_sizes(probes), axis=1), AXIS),
        reranked=jax.lax.psum(reranked, AXIS),
        rows_filtered=jax.lax.psum(rows_filtered, AXIS),
        rows_tombstoned=jax.lax.psum(rows_tombstoned, AXIS),
        lists_pruned=jax.lax.psum(lists_pruned, AXIS),
        tiles_skipped=jax.lax.psum(tiles_skipped, AXIS),
        tiles_scanned=jax.lax.psum(tiles_scanned, AXIS),
    )
    return mvals, mids, stats


@functools.lru_cache(maxsize=32)
def _mesh_driver(mesh: jax.sharding.Mesh, **static):
    """``_local_search`` with the ``static`` knobs as one jitted
    ``shard_map`` program over ``mesh``, one shard per device — built once
    per (mesh, knobs), so a stream of searches compiles once instead of
    once per call."""
    fn = functools.partial(_local_search, **static)

    def per_device(cen, lists, real, gids, cb, base, mem, qq, fb, lv, nss,
                   tt):
        # each device owns exactly one shard => leading block dim is 1
        out_v, out_i, stt = fn(cen[0], jax.tree.map(lambda x: x[0], lists),
                               real[0], gids[0], cb,
                               None if base is None else base[0],
                               None if mem is None else mem[0], qq,
                               None if fb is None else fb[0],
                               None if lv is None else lv[0], nss, tt)
        return (out_v[None], out_i[None],
                jax.tree.map(lambda x: x[None], stt))

    base_spec = P(AXIS) if static["remap"] else P()
    return jax.jit(jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(), base_spec,
                  P(AXIS), P(), P(AXIS), P(AXIS), P(), P()),
        out_specs=(P(AXIS), P(AXIS), P(AXIS)),
        # jax has no replication rule for pallas_call (the 'stream'
        # scan/re-rank kernels); the merge replicates results itself via
        # all_gather, so skipping the static replication check is sound
        check_vma=False,
    ))


class _ShardState(NamedTuple):
    """One immutable snapshot of every shard-partitioned array a search
    reads — the sharded twin of ``engine.EngineState`` (docs/mutability.md).
    Mutators build a replacement and install it with a single attribute
    store, so a search never sees lists from one epoch next to base rows or
    live bits from another."""

    centroids_s: jax.Array        # (S, L, D)
    lists_s: ListStore            # leading shard dim S; ids local when base_s
    real_s: jax.Array             # (S, L) bool — False on padding lists
    base_s: jax.Array | None      # (S, R, D) or None
    gids_s: jax.Array             # (S, R) i32 local row -> global id
    live_s: jax.Array | None      # (S, L, W) u8 live bitmap; None = no tombs
    rows_used: tuple              # per-shard base rows in use (len S)
    epoch: int
    n_tombstones: int


class ShardedEngine:
    """A ``SearchEngine`` whose lists are partitioned across S shards.

    Note: every shard selects probes with *flat* brute-force coarse over its
    local centroids (each shard holds only nlist/S of them, so the wrapped
    engine's HNSW/tree coarse structure does not partition); the wrapped
    engine's coarse quantizer is intentionally not carried over.

    When the wrapped engine holds base vectors, they are partitioned by
    shard list-membership (``partition_base``): each shard's re-rank reads
    only its own (R, D) slice, R ~= N/S, instead of a replicated (N, D)
    copy, so a 'stream' re-rank shard DMAs candidate rows straight out of
    its local slice. Shard-local ListStore
    ids become local row indices; ``gids_s`` maps them back to global ids
    after the per-shard pipeline.

    Mutation (docs/mutability.md): ``upsert``/``delete``/``compact`` mirror
    the single-host engine. Routing uses the retained *global* centroid
    table through the same fixed-shape encoder, so a row lands in the same
    global list (hence the same shard, ``g % S``, local list ``g // S``)
    and gets bitwise-identical codes on both engines.
    """

    def __init__(self, engine: SearchEngine, num_shards: int):
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        self.num_shards = int(num_shards)
        self.codebook = engine.index.codebook
        self.config = engine.config
        # retained for mutation routing: identical assignment + codes to the
        # single-host engine by construction (core.ivf.encode_rows)
        self.centroids = engine.index.centroids
        self.nlist_global = engine.index.lists.nlist
        centroids_s, lists_s, real_s = partition_lists(
            engine.index.lists, engine.index.centroids, self.num_shards)
        if engine.base is not None:
            base_s, gids_s, local_ids = partition_base(lists_s, engine.base)
            lists_s = lists_s._replace(ids=local_ids)
            rows_used = tuple(int(c) for c in
                              np.asarray(jnp.sum(gids_s >= 0, axis=1)))
        else:
            base_s = None
            # unused dummies so both vmap and shard_map see a uniform arity
            gids_s = jnp.full((self.num_shards, 1), -1, jnp.int32)
            rows_used = (0,) * self.num_shards
        # a wrapped engine may already carry tombstones — count them so the
        # first sharded search is already exact
        n_tomb = int(np.asarray(jnp.sum(lists_s.sizes))
                     - np.asarray(jnp.sum(lists_s.ids >= 0)))
        self._state = _ShardState(
            centroids_s=centroids_s, lists_s=lists_s, real_s=real_s,
            base_s=base_s, gids_s=gids_s,
            live_s=pack_filter_mask(lists_s.ids >= 0) if n_tomb else None,
            rows_used=rows_used, epoch=0, n_tombstones=n_tomb)
        self._mutate_lock = threading.RLock()
        self._locator: dict[int, tuple[int, int, int]] | None = None
        # optional persist.WALWriter, mirroring SearchEngine.attach_wal:
        # mutations are logged + fsync'd before the state swap
        self._wal = None
        # the device mesh the state lives on (_place); None until the first
        # mesh search
        self._mesh = None
        # namespace membership sharded with the same round-robin permutation
        # as the lists: shard j's (n_ns, L) slice covers exactly its lists;
        # padding lists are member-False for every namespace
        if engine.ns_member is None:
            self.member_s = None
        else:
            member = jnp.asarray(engine.ns_member, bool)
            nlist = member.shape[1]
            s = self.num_shards
            l = -(-nlist // s)
            pad = s * l - nlist
            if pad:
                member = jnp.concatenate(
                    [member, jnp.zeros((member.shape[0], pad), bool)], axis=1)
            perm = jnp.asarray(round_robin_perm(nlist, s))
            self.member_s = (member[:, perm]
                             .reshape(member.shape[0], s, l)
                             .transpose(1, 0, 2))  # (S, n_ns, L)

    # -- state snapshot views (mirror SearchEngine's) -----------------------

    @property
    def centroids_s(self) -> jax.Array:
        return self._state.centroids_s

    @property
    def lists_s(self) -> ListStore:
        return self._state.lists_s

    @property
    def real_s(self) -> jax.Array:
        return self._state.real_s

    @property
    def base_s(self) -> jax.Array | None:
        return self._state.base_s

    @property
    def gids_s(self) -> jax.Array:
        return self._state.gids_s

    @property
    def live_s(self) -> jax.Array | None:
        """Sharded live-row bitmap; None while no tombstones are held."""
        return self._state.live_s

    @property
    def cap(self) -> int:
        """Slot capacity of every (shard, list) — NB ``lists_s.cap`` would
        read the wrong axis on the 3-D store."""
        return self._state.lists_s.ids.shape[-1]

    @property
    def base(self) -> jax.Array | None:
        """Sharded base slices (S, R, D), or None when no base is held."""
        return self._state.base_s

    @property
    def epoch(self) -> int:
        return self._state.epoch

    @property
    def n_tombstones(self) -> int:
        return self._state.n_tombstones

    def attach_wal(self, wal) -> None:
        """Attach a ``persist.WALWriter`` (same contract as
        ``SearchEngine.attach_wal``): every later mutation appends a
        checksummed, fsync'd record before its state swap. ``None``
        detaches — replay must not re-log (docs/persistence.md)."""
        with self._mutate_lock:
            self._wal = wal

    def locate(self, gid: int) -> tuple[int, int, int] | None:
        """(shard, local list, slot) of a live row, None if absent."""
        with self._mutate_lock:
            return self._locate(self._state).get(int(gid))

    def _locate(self, st: _ShardState) -> dict[int, tuple[int, int, int]]:
        if self._locator is None:
            lids = np.asarray(st.lists_s.ids)
            if st.base_s is None:
                gid_at = lids                      # ids are global already
            else:
                g = np.asarray(st.gids_s)
                gid_at = np.where(
                    lids >= 0,
                    np.take_along_axis(
                        g, np.maximum(lids, 0).reshape(g.shape[0], -1),
                        axis=1).reshape(lids.shape),
                    -1)
            js, ls, ss = np.nonzero(gid_at >= 0)
            self._locator = {int(gid_at[j, l, s]): (int(j), int(l), int(s))
                             for j, l, s in zip(js, ls, ss)}
        return self._locator

    # -- live mutation (docs/mutability.md) ---------------------------------

    def upsert(self, ids, vecs, *, attrs=None) -> np.ndarray:
        """Shard-local insert/replace. Same contract as
        ``SearchEngine.upsert``; returns the (B,) i32 *global* list per row.

        Routing runs on the retained global centroids through the
        fixed-shape encoder, so assignment and code bytes are bitwise what
        the single-host engine produces; the owning shard is ``g % S``.
        When a target list is out of spare slots the whole shard set grows
        ``cap`` (shard compaction only happens in ``compact``); when a
        shard's base slice is out of rows it grows R — both retire autotune
        signatures, which are invalidated here.
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
            raise ValueError("upsert ids must be >= 0")
        if np.unique(ids).size != ids.size:
            raise ValueError("duplicate ids within one upsert batch")
        avals = None if attrs is None else np.asarray(attrs, np.int32)
        with self._mutate_lock:
            st = self._state
            assign, packed = ivf_mod.encode_rows(self.centroids,
                                                 self.codebook, vecs)
            shard = assign % self.num_shards
            local = assign // self.num_shards
            loc = dict(self._locate(st))
            lists_s = st.lists_s
            n_tomb = st.n_tombstones
            hit = [int(g) for g in ids if int(g) in loc]
            if hit:
                js = np.array([loc[g][0] for g in hit], np.int32)
                ls = np.array([loc[g][1] for g in hit], np.int32)
                ss = np.array([loc[g][2] for g in hit], np.int32)
                lists_s = lists_s._replace(
                    ids=lists_s.ids.at[js, ls, ss].set(-1),
                    attrs=(None if lists_s.attrs is None
                           else lists_s.attrs.at[js, ls, ss].set(-1)))
                for g in hit:
                    del loc[g]
                n_tomb += len(hit)
            # spare capacity: watermark + incoming per (shard, local list)
            # (NB ListStore.cap reads axis 1, which is L on this 3-D store)
            sizes = np.asarray(lists_s.sizes)
            inc = np.zeros(sizes.shape, np.int64)
            np.add.at(inc, (shard, local), 1)
            if (sizes + inc > lists_s.ids.shape[-1]).any():
                old_cap = lists_s.ids.shape[-1]
                need = int((sizes + inc).max())
                new_cap = aligned_cap(need)
                pad = new_cap - old_cap
                s_n = lists_s.ids.shape[0]
                l_n = lists_s.ids.shape[1]
                lists_s = ListStore(
                    codes=jnp.concatenate(
                        [lists_s.codes,
                         jnp.zeros((s_n, l_n, pad, lists_s.codes.shape[-1]),
                                   lists_s.codes.dtype)], axis=2),
                    ids=jnp.concatenate(
                        [lists_s.ids,
                         jnp.full((s_n, l_n, pad), -1, jnp.int32)], axis=2),
                    sizes=lists_s.sizes,
                    attrs=None if lists_s.attrs is None else jnp.concatenate(
                        [lists_s.attrs,
                         jnp.full((s_n, l_n, pad), -1, jnp.int32)], axis=2))
                ops_mod.clear_autotune_cache(nlist=l_n, cap=old_cap)
            # slot per row: list watermark + rank within the batch (same
            # order the single-host append uses — global-list batch order)
            b = ids.shape[0]
            order = np.argsort(assign, kind="stable")
            rank = np.empty(b, np.int64)
            sa = assign[order]
            rank[order] = np.arange(b) - np.searchsorted(sa, sa, side="left")
            slots = sizes[shard, local] + rank
            counts = np.zeros(sizes.shape, np.int32)
            np.add.at(counts, (shard, local), 1)

            base_s, gids_s = st.base_s, st.gids_s
            rows_used = st.rows_used
            if base_s is not None:
                # shard-local base rows: next free row per shard, in batch
                # order within the shard
                order_j = np.argsort(shard, kind="stable")
                rank_j = np.empty(b, np.int64)
                sj = shard[order_j]
                rank_j[order_j] = (np.arange(b)
                                   - np.searchsorted(sj, sj, side="left"))
                used = np.array(rows_used, np.int64)
                rows = used[shard] + rank_j
                r_cap = base_s.shape[1]
                if rows.max() >= r_cap:
                    old_r = r_cap
                    grown = -(-(int(rows.max()) + 1) // 256) * 256
                    pad_r = grown - r_cap
                    base_s = jnp.concatenate(
                        [base_s, jnp.zeros((base_s.shape[0], pad_r,
                                            base_s.shape[2]), base_s.dtype)],
                        axis=1)
                    gids_s = jnp.concatenate(
                        [gids_s, jnp.full((gids_s.shape[0], pad_r), -1,
                                          jnp.int32)], axis=1)
                    ops_mod.clear_autotune_cache(kind="rerank", n=old_r)
                vj = jnp.asarray(shard.astype(np.int32))
                vr = jnp.asarray(rows.astype(np.int32))
                base_s = base_s.at[vj, vr].set(jnp.asarray(vecs))
                gids_s = gids_s.at[vj, vr].set(
                    jnp.asarray(ids.astype(np.int32)))
                np.add.at(used, shard, 1)
                rows_used = tuple(int(c) for c in used)
                slot_ids = rows.astype(np.int32)       # local row indices
            else:
                slot_ids = ids.astype(np.int32)        # global ids directly
            js = jnp.asarray(shard.astype(np.int32))
            ls = jnp.asarray(local.astype(np.int32))
            ks = jnp.asarray(slots.astype(np.int32))
            new_attrs = lists_s.attrs
            if new_attrs is not None:
                aa = (np.full(b, -1, np.int32) if avals is None else avals)
                new_attrs = new_attrs.at[js, ls, ks].set(jnp.asarray(aa))
            elif avals is not None:
                raise ValueError("attrs given but the store holds no attrs "
                                 "column")
            lists_s = ListStore(
                codes=lists_s.codes.at[js, ls, ks].set(jnp.asarray(packed)),
                ids=lists_s.ids.at[js, ls, ks].set(jnp.asarray(slot_ids)),
                sizes=lists_s.sizes + jnp.asarray(counts),
                attrs=new_attrs)
            for g, j, l, s in zip(ids.tolist(), shard.tolist(),
                                  local.tolist(), slots.tolist()):
                loc[int(g)] = (int(j), int(l), int(s))
            if self._wal is not None:
                # durable before visible (docs/persistence.md)
                self._wal.log_upsert(ids, vecs, avals)
            self._locator = loc
            self._state = self._place(_ShardState(
                centroids_s=st.centroids_s, lists_s=lists_s,
                real_s=st.real_s, base_s=base_s, gids_s=gids_s,
                live_s=(pack_filter_mask(lists_s.ids >= 0)
                        if n_tomb else None),
                rows_used=rows_used, epoch=st.epoch + 1,
                n_tombstones=n_tomb))
        return assign

    def delete(self, ids) -> int:
        """Tombstone rows by global id across shards; unknown ids ignored.
        Returns the number of rows deleted. Same contract as
        ``SearchEngine.delete``."""
        ids = np.unique(np.asarray(ids, np.int64))
        with self._mutate_lock:
            st = self._state
            loc = dict(self._locate(st))
            found = [int(g) for g in ids if int(g) in loc]
            if not found:
                return 0
            js = np.array([loc[g][0] for g in found], np.int32)
            ls = np.array([loc[g][1] for g in found], np.int32)
            ss = np.array([loc[g][2] for g in found], np.int32)
            lists_s = st.lists_s._replace(
                ids=st.lists_s.ids.at[js, ls, ss].set(-1),
                attrs=(None if st.lists_s.attrs is None
                       else st.lists_s.attrs.at[js, ls, ss].set(-1)))
            for g in found:
                del loc[g]
            if self._wal is not None:
                # no-op deletes returned above unlogged; replay re-derives
                # the same `found` set from the full batch
                self._wal.log_delete(ids)
            self._locator = loc
            self._state = self._place(st._replace(
                lists_s=lists_s,
                live_s=pack_filter_mask(lists_s.ids >= 0),
                epoch=st.epoch + 1,
                n_tombstones=st.n_tombstones + len(found)))
            return len(found)

    def compact(self, cap: int | None = None) -> int:
        """Rebuild every shard's lists (and base slice) tombstone-free.

        Host-side like ``core.lists.compact_lists``, swapped in atomically.
        Survivors keep their relative slot order per list; when a base is
        held the shard's rows re-pack in order of appearance — exactly the
        ``partition_base`` convention — and R shrinks to the new max.
        Returns the number of tombstoned slots reclaimed.
        """
        with self._mutate_lock:
            st = self._state
            lids = np.asarray(st.lists_s.ids)          # (S, L, cap)
            codes = np.asarray(st.lists_s.codes)
            attrs = (None if st.lists_s.attrs is None
                     else np.asarray(st.lists_s.attrs))
            s_n, l_n, old_cap = lids.shape
            live = lids >= 0
            counts = live.sum(axis=2)                  # (S, L)
            new_cap = int(cap if cap is not None else old_cap)
            if new_cap < int(counts.max(initial=0)):
                raise ValueError(
                    f"compact: cap {new_cap} below the largest live list "
                    f"({int(counts.max(initial=0))})")
            n_codes = np.zeros((s_n, l_n, new_cap, codes.shape[-1]),
                               codes.dtype)
            n_ids = np.full((s_n, l_n, new_cap), -1, np.int32)
            n_attrs = (None if attrs is None
                       else np.full((s_n, l_n, new_cap), -1, np.int32))
            if st.base_s is not None:
                base = np.asarray(st.base_s)
                gids = np.asarray(st.gids_s)
                r_cap = max(1, -(-int(counts.sum(axis=1).max(initial=1))
                                 // 256) * 256)
                n_base = np.zeros((s_n, r_cap, base.shape[-1]), base.dtype)
                n_gids = np.full((s_n, r_cap), -1, np.int32)
            rows_used = []
            for j in range(s_n):
                cursor = 0
                for l in range(l_n):
                    m = live[j, l]
                    c = int(counts[j, l])
                    n_codes[j, l, :c] = codes[j, l, m]
                    if attrs is not None:
                        n_attrs[j, l, :c] = attrs[j, l, m]
                    if st.base_s is None:
                        n_ids[j, l, :c] = lids[j, l, m]
                    else:
                        old_rows = lids[j, l, m]       # old local rows
                        new_rows = np.arange(cursor, cursor + c, dtype=np.int32)
                        n_ids[j, l, :c] = new_rows
                        n_base[j, new_rows] = base[j, old_rows]
                        n_gids[j, new_rows] = gids[j, old_rows]
                        cursor += c
                rows_used.append(cursor)
            lists_s = ListStore(
                codes=jnp.asarray(n_codes), ids=jnp.asarray(n_ids),
                sizes=jnp.asarray(counts.astype(np.int32)),
                attrs=None if n_attrs is None else jnp.asarray(n_attrs))
            if new_cap != old_cap:
                ops_mod.clear_autotune_cache(nlist=l_n, cap=old_cap)
            if st.base_s is not None and r_cap != np.asarray(st.base_s).shape[1]:
                ops_mod.clear_autotune_cache(kind="rerank",
                                             n=st.base_s.shape[1])
            reclaimed = st.n_tombstones
            if self._wal is not None:
                self._wal.log_compact(cap)
            self._locator = None
            self._state = self._place(st._replace(
                lists_s=lists_s,
                base_s=None if st.base_s is None else jnp.asarray(n_base),
                gids_s=st.gids_s if st.base_s is None else jnp.asarray(n_gids),
                live_s=None, rows_used=tuple(rows_used),
                epoch=st.epoch + 1, n_tombstones=0))
            return reclaimed

    def _place(self, st: _ShardState) -> _ShardState:
        """``st`` with every shard-partitioned array split over the engine's
        mesh, one shard per device (a no-op for arrays already split so);
        ``st`` as it is while the engine has no mesh."""
        if self._mesh is None:
            return st
        sh = NamedSharding(self._mesh, P(AXIS))
        return st._replace(
            centroids_s=jax.device_put(st.centroids_s, sh),
            lists_s=jax.device_put(st.lists_s, sh),
            real_s=jax.device_put(st.real_s, sh),
            base_s=jax.device_put(st.base_s, sh),
            gids_s=jax.device_put(st.gids_s, sh),
            live_s=jax.device_put(st.live_s, sh))

    def _move_to(self, mesh: jax.sharding.Mesh) -> None:
        """Move the state and the namespace table onto ``mesh`` for good:
        the arrays are replaced, not copied, so each device holds only its
        own shard, and every later mutation is placed there too. Both
        drivers read the one placed state."""
        if mesh.shape[AXIS] != self.num_shards:
            raise ValueError(
                f"mesh axis {AXIS!r} has {mesh.shape[AXIS]} devices but the "
                f"engine holds {self.num_shards} shards")
        # Auto axes: arrays on an Explicit-axis mesh (jax.make_mesh's
        # default) carry their sharding in their type, and the vmap driver's
        # gathers then refuse them (test_sharded_shard_map_on_device_mesh)
        auto = Mesh(mesh.devices, mesh.axis_names,
                    axis_types=(AxisType.Auto,) * len(mesh.axis_names))
        with self._mutate_lock:
            if self._mesh == auto:
                return
            self._mesh = auto
            self._state = self._place(self._state)
            self.member_s = jax.device_put(self.member_s,
                                           NamedSharding(auto, P(AXIS)))

    def search(self, queries: jax.Array, k: int = 10, *,
               nprobe: int | None = None, rerank_mult: int | None = None,
               filter_bits: jax.Array | None = None,
               namespaces: jax.Array | None = None,
               margin_tau: jax.Array | float | None = None,
               mesh: jax.sharding.Mesh | None = None) -> SearchResult:
        """Batched search with the distributed shard merge.

        Semantics note vs the unsharded engine: each shard probes ``nprobe``
        of *its own* lists, so up to S*nprobe lists are scanned in total —
        recall at a given nprobe is >= the single-shard engine's.

        ``filter_bits`` is the (nlist, W) bitmap over *global* list ids —
        it is resharded here per request (``partition_filter``, pure jnp) so
        callers never track the round-robin layout. ``namespaces`` (Q,) i32
        is replicated: each shard masks its own coarse selection with its
        slice of the membership table, so a tenant's query only ever probes
        (and only ever DMAs) the tenant's lists on every shard. See
        docs/filtering.md.

        ``margin_tau`` (scalar or (Q,), replicated) overrides the config's
        margin width for this request — only legal under the wrapped
        engine's ``probe_policy='margin'`` (docs/anytime.md). Each shard
        prunes against its own best centroid distance.
        """
        st = self._state  # ONE snapshot read: the whole search is one epoch
        q = queries[None] if queries.ndim == 1 else queries
        nprobe = self.config.nprobe if nprobe is None else nprobe
        r = self.config.rerank_mult if rerank_mult is None else rerank_mult
        if r and st.base_s is None:
            raise ValueError("exact re-rank requested but engine holds no "
                             "base vectors (build with keep_base=True)")
        if margin_tau is not None and self.config.probe_policy != "margin":
            raise ValueError(
                "margin_tau override given but probe_policy is "
                f"{self.config.probe_policy!r}; build the wrapped engine "
                "with EngineConfig(probe_policy='margin')")
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
        if namespaces is not None:
            if self.member_s is None:
                raise ValueError(
                    "per-query namespaces given but the wrapped engine was "
                    "built without a namespace table")
            namespaces = jnp.asarray(namespaces, jnp.int32)
        cap = st.lists_s.ids.shape[-1]
        if filter_bits is not None:
            if filter_bits.shape[1] * 8 < cap:
                raise ValueError(
                    f"filter_bits W={filter_bits.shape[1]} too narrow for "
                    f"cap={cap} — a grow may have changed cap; "
                    "re-derive filters from the live store")
            w = -(-cap // 8)
            fbits_s = partition_filter(
                jnp.asarray(filter_bits, jnp.uint8)[:, :w], self.num_shards)
        else:
            fbits_s = None
        member_s = self.member_s if namespaces is not None else None
        static = dict(k=k, nprobe=nprobe, r=r,
                      scan_impl=self.config.scan_impl,
                      rerank_impl=self.config.rerank_impl,
                      remap=st.base_s is not None,
                      probe_policy=self.config.probe_policy,
                      early_exit=self.config.early_exit)
        fn = functools.partial(_local_search, **static)
        base_ax = 0 if st.base_s is not None else None

        if mesh is None:
            # None args are empty pytrees: their in_axes entries are inert
            mvals, mids, stats = jax.vmap(
                fn, in_axes=(0, 0, 0, 0, None, base_ax, 0, None, 0, 0, None,
                             None),
                axis_name=AXIS,
            )(st.centroids_s, st.lists_s, st.real_s, st.gids_s,
              self.codebook, st.base_s, member_s, q, fbits_s, st.live_s,
              namespaces, tau)
            # merge output is replicated across the shard axis; take shard 0
            return SearchResult(mvals[0], mids[0],
                                QueryStats(*(s[0] for s in stats)))

        self._move_to(mesh)
        st = self._place(st)  # a no-op unless this search did the move
        member_s = self.member_s if namespaces is not None else None
        mvals, mids, stats = _mesh_driver(self._mesh, **static)(
            st.centroids_s, st.lists_s, st.real_s, st.gids_s, self.codebook,
            st.base_s, member_s, q, fbits_s, st.live_s, namespaces, tau)
        return SearchResult(mvals[0], mids[0], QueryStats(*(s[0] for s in stats)))
