"""Padded inverted-list storage: the reusable half of the IVF index.

Extracted from ``core/ivf.py`` so that any component — the IVF index, the
unified ``repro.engine`` search pipeline, shard-parallel serving — can own,
gather, and slice posting lists without going through IVF-specific code.

Layout (TPU rule: every shape static, no raggedness):
  codes: (nlist, cap, M//2) uint8   nibble-packed PQ codes, zero-padded
  ids:   (nlist, cap)       int32   global vector ids, -1 = padding
  sizes: (nlist,)           int32   true occupancy per list (<= cap)
  attrs: (nlist, cap)       int32   optional per-row metadata attribute,
                                    -1 = padding (None when unused)

Bucketing is host-side numpy (index build is offline); ``gather`` is pure
jnp and lowers under jit/pjit.

Filter bitmaps (docs/filtering.md): a predicate over the rows is carried as
a *packed* bitmap ``(nlist, W) u8`` with ``W = ceil(cap / 8)``, bit ``j`` of
word ``w`` = slot ``w*8 + j`` (LSB-first), 1 = the row passes. Packing keeps
the filter at ~1.5% of the code bytes at M=16, so streaming it next to the
codes costs almost nothing (``pack_filter_mask`` / ``unpack_filter_mask`` /
``filter_from_attrs`` / ``filter_pass_sizes`` below). The bitmap is sliced
and permuted exactly like the codes by ``partition_lists`` /
``partition_filter``, so it stays epoch-consistent with the codes on every
shard.

Conventions (shared across ``repro.core``, see docs/architecture.md):
  shapes  all static — every list padded to ``cap``; gathers preserve the
          leading probe-set shape; filter bitmaps padded to W words
  dtypes  packed codes uint8; ids/sizes/attrs int32; filter bitmaps uint8
          (LSB-first within each word)
  -1 id   sentinel — a padded list slot or an invalid (negative) probe id
          gathers to id -1; code bytes at padded slots are zero and must be
          masked by the id, never interpreted; attrs at padded slots are -1
  filter  bit 0 = row excluded (scans treat the slot exactly like padding:
          id -1, distance +inf / ACC_SENTINEL before selection); bits at
          padded slots must be 0 (``filter_from_attrs`` guarantees it)
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class ListStore(NamedTuple):
    codes: jax.Array  # (nlist, cap, M//2) uint8
    ids: jax.Array    # (nlist, cap) int32, -1 = padding
    sizes: jax.Array  # (nlist,) int32
    # optional per-row metadata column (filtering contract, docs/filtering.md):
    # one i32 attribute per slot, -1 at padding. None = no attributes — the
    # field vanishes from the pytree, so vmap/shard_map arities are unchanged.
    attrs: jax.Array | None = None

    @property
    def nlist(self) -> int:
        return self.ids.shape[0]

    @property
    def cap(self) -> int:
        return self.ids.shape[1]

    def gather(self, probe_ids: jax.Array) -> tuple[jax.Array, jax.Array]:
        """Gather probed lists: probe_ids (..., P) -> codes (..., P, cap, M//2),
        ids (..., P, cap). Negative probe ids yield fully-padded lists: ids
        all -1 AND codes all zero (the early mask) — without it an invalid
        probe hands list 0's real codes to the scan, which then does work
        that ``probed_sizes`` (and ``QueryStats.codes_scanned``) never
        counted, and which the gather-free stream kernel — skipping the DMA
        outright — would disagree with."""
        valid = probe_ids >= 0
        safe = jnp.maximum(probe_ids, 0)
        codes = jnp.where(valid[..., None, None], self.codes[safe], 0)
        ids = jnp.where(valid[..., None], self.ids[safe], -1)
        return codes, ids

    def gather_ids(self, probe_ids: jax.Array) -> jax.Array:
        """ids of ``gather`` alone: probe_ids (..., P) -> (..., P, cap) i32.

        The gather-free scan path (``core.ivf.scan_probes`` with
        impl='stream') reads codes in place and only needs this — the
        (..., P, cap, M//2) code copy never exists."""
        return jnp.where((probe_ids >= 0)[..., None],
                         self.ids[jnp.maximum(probe_ids, 0)], -1)

    def probed_sizes(self, probe_ids: jax.Array) -> jax.Array:
        """True occupancy of each probed list (0 for invalid probes)."""
        return jnp.where(probe_ids >= 0, self.sizes[jnp.maximum(probe_ids, 0)], 0)


# ---------------------------------------------------------------------------
# packed filter bitmaps (the filtering contract — docs/filtering.md)
# ---------------------------------------------------------------------------

def filter_words(cap: int) -> int:
    """Words per list of a packed filter bitmap: W = ceil(cap / 8)."""
    return -(-int(cap) // 8)


@jax.jit
def pack_filter_mask(mask: jax.Array) -> jax.Array:
    """Pack a per-slot boolean mask into the filter bitmap layout.

    mask: (..., cap) bool (1 = row passes). Returns (..., W) u8 with
    W = ceil(cap/8); bit j of word w = slot w*8 + j (LSB-first). Bits past
    ``cap`` in the last word are 0.
    """
    cap = mask.shape[-1]
    pad = (-cap) % 8
    m = mask.astype(jnp.int32)
    if pad:
        widths = [(0, 0)] * (m.ndim - 1) + [(0, pad)]
        m = jnp.pad(m, widths)
    m = m.reshape(*mask.shape[:-1], -1, 8)
    weights = (1 << jnp.arange(8, dtype=jnp.int32))
    return jnp.sum(m * weights, axis=-1).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("cap",))
def unpack_filter_mask(bits: jax.Array, cap: int) -> jax.Array:
    """Inverse of ``pack_filter_mask``: (..., W) u8 -> (..., cap) bool."""
    b = bits.astype(jnp.int32)
    u = ((b[..., None] >> jnp.arange(8, dtype=jnp.int32)) & 1)
    return u.reshape(*bits.shape[:-1], -1)[..., :cap].astype(jnp.bool_)


def filter_from_attrs(store: ListStore, predicate) -> jax.Array:
    """Evaluate a per-row predicate over ``store.attrs`` into a packed bitmap.

    predicate: elementwise fn (nlist, cap) i32 attrs -> bool (pure jnp, so
    the whole thing jits). Returns (nlist, W) u8. Padded slots (id -1) are
    forced to 0 regardless of what the predicate says about the -1 attr
    sentinel — a filter bit may only ever be set on a real row.
    """
    if store.attrs is None:
        raise ValueError("ListStore holds no attrs column; build with "
                         "build_lists(..., attrs=...)")
    return pack_filter_mask(predicate(store.attrs) & (store.ids >= 0))


@jax.jit
def filter_pass_sizes(store: ListStore, filter_bits: jax.Array) -> jax.Array:
    """Rows per list that pass the filter: (nlist, W) u8 -> (nlist,) i32.

    Occupancy-aware: bits at slots past ``sizes`` never count even if a
    stale bitmap left them set. ``sizes - filter_pass_sizes`` is the
    per-list row count a filtered scan excludes (``QueryStats.rows_filtered``
    sums this over the probed lists).
    """
    cap = store.cap
    m = unpack_filter_mask(filter_bits, cap)
    slot = jnp.arange(cap, dtype=jnp.int32)[None, :]
    return jnp.sum((m & (slot < store.sizes[:, None])).astype(jnp.int32),
                   axis=-1)


# Granules of a derived list capacity. The stream kernels scan the store in
# place with the largest power-of-two tile (<= 1024, kernels'
# fastscan_kernel.TILE_N) that divides cap, so cap is rounded to keep that
# tile lane-aligned (128 = TPU lanes) and, past 1024 rows, a full 1024.
CAP_ALIGN = 128
CAP_TILE = 1024


def aligned_cap(rows: int) -> int:
    """Capacity for a list of ``rows`` rows: a multiple of ``CAP_ALIGN`` up
    to ``CAP_TILE``, a multiple of ``CAP_TILE`` beyond it."""
    rows = max(1, int(rows))
    granule = CAP_ALIGN if rows <= CAP_TILE else CAP_TILE
    return -(-rows // granule) * granule


def build_lists(assign: np.ndarray, packed_codes: np.ndarray, *, nlist: int,
                cap: int | None = None, ids: np.ndarray | None = None,
                attrs: np.ndarray | None = None) -> ListStore:
    """Bucket packed codes into padded lists (host-side, offline).

    assign: (n,) list assignment per vector; packed_codes: (n, M//2) uint8;
    ids: optional global id per vector (defaults to arange — shards pass
    their own offsets); attrs: optional (n,) i32 per-vector metadata
    attribute, bucketed alongside the codes (-1 at padded slots) so filter
    bitmaps derived from it stay epoch-consistent with the codes. Overflow
    beyond ``cap`` is dropped, reflected in ``sizes`` (same semantics the
    IVF build always had). Without ``cap`` the capacity is the fullest
    list's count rounded up by ``aligned_cap``.
    """
    assign = np.asarray(assign, np.int64)
    packed = np.asarray(packed_codes, np.uint8)
    n, mh = packed.shape
    gids = np.arange(n, dtype=np.int32) if ids is None else np.asarray(ids, np.int32)
    avals = None if attrs is None else np.asarray(attrs, np.int32)
    counts = np.bincount(assign, minlength=nlist)
    cap_ = int(cap or aligned_cap(counts.max()))
    list_codes = np.zeros((nlist, cap_, mh), np.uint8)
    list_ids = np.full((nlist, cap_), -1, np.int32)
    list_attrs = None if avals is None else np.full((nlist, cap_), -1, np.int32)
    cursor = np.zeros((nlist,), np.int64)
    order = np.argsort(assign, kind="stable")
    for i in order:
        li = assign[i]
        c = cursor[li]
        if c < cap_:
            list_codes[li, c] = packed[i]
            list_ids[li, c] = gids[i]
            if list_attrs is not None:
                list_attrs[li, c] = avals[i]
            cursor[li] += 1
    return ListStore(
        codes=jnp.asarray(list_codes),
        ids=jnp.asarray(list_ids),
        sizes=jnp.asarray(np.minimum(counts, cap_).astype(np.int32)),
        attrs=None if list_attrs is None else jnp.asarray(list_attrs),
    )


# ---------------------------------------------------------------------------
# live mutation primitives (docs/mutability.md)
#
# A ListStore mutates under three invariants:
#   watermark   ``sizes[l]`` counts slots EVER written this epoch (appends go
#               at the watermark; it only moves on append or compaction)
#   tombstone   a deleted row keeps its slot: ``ids[l, s] = -1`` (and attrs
#               -1) while its stale code bytes stay in place — exactly the
#               padding convention every scan path already masks
#   live bits   ``live_filter_bits`` = the packed bitmap of rows with
#               ``id >= 0``; engines AND it into the per-request filter so
#               the stream kernels' candidate budget is spent on live rows
#               only (a tombstone inside the watermark would otherwise pass
#               the occupancy mask with its stale distance)
#
# All three are derivable from (ids, sizes) alone, so mutation helpers return
# plain new ListStores — no parallel bookkeeping structure to desync.
# ---------------------------------------------------------------------------

def locate_rows(store: ListStore) -> dict[int, tuple[int, int]]:
    """Host-side id -> (list, slot) map of every live row.

    The mutable engine's locator: built once (one device->host sync), then
    maintained incrementally by upsert/delete/compact.
    """
    ids = np.asarray(store.ids)
    ls, ss = np.nonzero(ids >= 0)
    return {int(ids[l, s]): (int(l), int(s)) for l, s in zip(ls, ss)}


def live_counts(store: ListStore) -> jax.Array:
    """(nlist,) i32 rows per list that are live (id >= 0, inside watermark)."""
    return jnp.sum((store.ids >= 0).astype(jnp.int32), axis=1)


def tombstone_counts(store: ListStore) -> jax.Array:
    """(nlist,) i32 tombstoned slots per list: watermark minus live rows."""
    return store.sizes - live_counts(store)


def live_filter_bits(store: ListStore) -> jax.Array:
    """Packed (nlist, W) u8 bitmap of live rows (``pack_filter_mask`` layout).

    Bit 1 exactly where ``ids >= 0`` — padding beyond the watermark and
    tombstones inside it are both 0, so ANDing this into any per-request
    filter makes the stream kernels treat tombstones like padding *before*
    candidate selection (the exactness condition for the mutation oracle:
    a deleted row must never occupy a per-tile candidate slot).
    """
    return pack_filter_mask(store.ids >= 0)


def grow_cap(store: ListStore, new_cap: int) -> ListStore:
    """Pad every list with spare slots: cap -> ``new_cap`` (ids -1, codes 0,
    attrs -1). Watermarks are untouched; gathers/scans behave identically
    (the new slots are past every watermark). Shape change — compiled
    pipelines re-key, and scan autotune verdicts for the old cap are stale
    (``kernels.ops.clear_autotune_cache(cap=...)``)."""
    cap = store.cap
    if new_cap < cap:
        raise ValueError(f"grow_cap: new_cap {new_cap} < current cap {cap}")
    if new_cap == cap:
        return store
    pad = new_cap - cap
    nlist = store.nlist
    return ListStore(
        codes=jnp.concatenate(
            [store.codes,
             jnp.zeros((nlist, pad, store.codes.shape[-1]), store.codes.dtype)],
            axis=1),
        ids=jnp.concatenate(
            [store.ids, jnp.full((nlist, pad), -1, store.ids.dtype)], axis=1),
        sizes=store.sizes,
        attrs=None if store.attrs is None else jnp.concatenate(
            [store.attrs, jnp.full((nlist, pad), -1, store.attrs.dtype)],
            axis=1),
    )


def tombstone_rows(store: ListStore, list_ids: np.ndarray,
                   slots: np.ndarray) -> ListStore:
    """Delete rows in place: ids/attrs at each (list, slot) become -1.

    Codes stay (masked by the id like padding); watermarks stay (the slot is
    not reusable until compaction). A pure functional update — callers swap
    the returned store atomically.
    """
    l = jnp.asarray(list_ids, jnp.int32)
    s = jnp.asarray(slots, jnp.int32)
    return store._replace(
        ids=store.ids.at[l, s].set(-1),
        attrs=None if store.attrs is None else store.attrs.at[l, s].set(-1),
    )


def append_rows(store: ListStore, list_ids: np.ndarray, packed: np.ndarray,
                gids: np.ndarray, attrs: np.ndarray | None = None
                ) -> tuple[ListStore, np.ndarray]:
    """Append rows into spare slots at each target list's watermark.

    list_ids (B,) target list per row; packed (B, M//2) u8 PQ codes;
    gids (B,) i32 global ids; attrs optional (B,) i32 (required -1-free when
    the store carries an attrs column — pass -1 explicitly to mean "no
    attribute" at your own risk: -1 is the padding sentinel).

    Returns (new store, slots (B,) the rows landed in). Raises when any
    target list lacks spare capacity (callers compact/grow first — this
    helper never drops rows the way ``build_lists`` overflow does).
    Slot assignment is deterministic: batch order within each list.
    """
    list_ids = np.asarray(list_ids, np.int64)
    packed = np.asarray(packed, np.uint8)
    gids = np.asarray(gids, np.int32)
    b = list_ids.shape[0]
    sizes = np.asarray(store.sizes, np.int64)
    # slot = watermark + rank of the row among batch rows targeting its list
    order = np.argsort(list_ids, kind="stable")
    rank = np.empty(b, np.int64)
    sorted_lists = list_ids[order]
    rank[order] = np.arange(b) - np.searchsorted(sorted_lists, sorted_lists,
                                                 side="left")
    slots = sizes[list_ids] + rank
    if b and slots.max() >= store.cap:
        full = int(list_ids[slots.argmax()])
        raise ValueError(
            f"append_rows: list {full} is out of spare capacity "
            f"(cap={store.cap}); compact or grow_cap first")
    l = jnp.asarray(list_ids, jnp.int32)
    s = jnp.asarray(slots, jnp.int32)
    counts = np.bincount(list_ids, minlength=store.nlist).astype(np.int32)
    new_attrs = store.attrs
    if new_attrs is not None:
        avals = (np.full(b, -1, np.int32) if attrs is None
                 else np.asarray(attrs, np.int32))
        new_attrs = new_attrs.at[l, s].set(jnp.asarray(avals))
    elif attrs is not None:
        raise ValueError("append_rows: attrs given but the store holds no "
                         "attrs column (build with attrs=...)")
    return store._replace(
        codes=store.codes.at[l, s].set(jnp.asarray(packed)),
        ids=store.ids.at[l, s].set(jnp.asarray(gids)),
        sizes=store.sizes + jnp.asarray(counts),
        attrs=new_attrs,
    ), slots.astype(np.int32)


def compact_lists(store: ListStore, cap: int | None = None) -> ListStore:
    """Rebuild every list without tombstones: the fresh-epoch store.

    Survivors keep their relative slot order (stable shift-down), watermarks
    become live counts, and ``cap`` may change (grow for headroom, shrink to
    fit — must cover the largest live list). Host-side numpy like
    ``build_lists`` — compaction is the offline half of mutation; the swap
    into a serving engine is what must be atomic, not the rebuild.
    """
    ids = np.asarray(store.ids)
    codes = np.asarray(store.codes)
    attrs = None if store.attrs is None else np.asarray(store.attrs)
    nlist, old_cap = ids.shape
    live = ids >= 0
    counts = live.sum(axis=1)
    new_cap = int(cap if cap is not None else old_cap)
    if new_cap < int(counts.max(initial=0)):
        raise ValueError(
            f"compact_lists: cap {new_cap} below the largest live list "
            f"({int(counts.max(initial=0))} rows)")
    new_codes = np.zeros((nlist, new_cap, codes.shape[-1]), codes.dtype)
    new_ids = np.full((nlist, new_cap), -1, ids.dtype)
    new_attrs = None if attrs is None else np.full((nlist, new_cap), -1,
                                                   attrs.dtype)
    for li in range(nlist):
        m = live[li]
        c = int(counts[li])
        new_codes[li, :c] = codes[li, m]
        new_ids[li, :c] = ids[li, m]
        if new_attrs is not None:
            new_attrs[li, :c] = attrs[li, m]
    return ListStore(
        codes=jnp.asarray(new_codes),
        ids=jnp.asarray(new_ids),
        sizes=jnp.asarray(counts.astype(np.int32)),
        attrs=None if new_attrs is None else jnp.asarray(new_attrs),
    )


def store_arrays(store: ListStore) -> dict[str, np.ndarray]:
    """The store as plain host arrays — the persistence wire format.

    Works on 2-D (single-host) and 3-D (shard-stacked) stores alike; the
    ``attrs`` key is simply absent when the store carries no attribute
    column, so ``store_from_arrays(store_arrays(s))`` round-trips the
    pytree arity exactly (docs/persistence.md)."""
    out = {"codes": np.asarray(store.codes),
           "ids": np.asarray(store.ids),
           "sizes": np.asarray(store.sizes)}
    if store.attrs is not None:
        out["attrs"] = np.asarray(store.attrs)
    return out


def store_from_arrays(arrays: dict[str, np.ndarray]) -> ListStore:
    """Inverse of ``store_arrays``: rebuild the ListStore pytree."""
    return ListStore(
        codes=jnp.asarray(arrays["codes"]),
        ids=jnp.asarray(arrays["ids"]),
        sizes=jnp.asarray(arrays["sizes"]),
        attrs=jnp.asarray(arrays["attrs"]) if "attrs" in arrays else None,
    )


def round_robin_perm(nlist: int, num_shards: int) -> np.ndarray:
    """The list permutation ``partition_lists`` applies: shard j owns lists
    j, j+S, j+2S, ... of the (padded to S*L) id space. Exposed so per-request
    sidecars — filter bitmaps (``partition_filter``), namespace membership
    rows — can be sharded consistently with a store partitioned earlier."""
    s = int(num_shards)
    l = -(-int(nlist) // s)
    return np.arange(s * l).reshape(l, s).T.reshape(-1)


def partition_lists(store: ListStore, centroids: jax.Array, num_shards: int
                    ) -> tuple[jax.Array, ListStore, jax.Array]:
    """Round-robin partition of lists into shards for shard-parallel search.

    Returns (centroids (S, L, D), ListStore with leading shard dim S,
    real (S, L) bool), where L = ceil(nlist / S). Padding lists — marked
    False in ``real`` — get a far-away centroid (probed only when a shard
    holds fewer real lists than nprobe), size 0, and all-(-1) ids, so every
    shard sees identical static shapes. ids stay *global* — the distributed
    top-k merge needs no re-mapping.
    """
    nlist = store.nlist
    s = int(num_shards)
    l = -(-nlist // s)
    pad = s * l - nlist
    cen = np.asarray(centroids, np.float32)
    codes = np.asarray(store.codes)
    ids = np.asarray(store.ids)
    sizes = np.asarray(store.sizes)
    attrs = None if store.attrs is None else np.asarray(store.attrs)
    if pad:
        far = np.full((pad, cen.shape[1]), 1e30, np.float32)
        cen = np.concatenate([cen, far], axis=0)
        codes = np.concatenate(
            [codes, np.zeros((pad,) + codes.shape[1:], codes.dtype)], axis=0)
        ids = np.concatenate([ids, np.full((pad,) + ids.shape[1:], -1, ids.dtype)],
                             axis=0)
        sizes = np.concatenate([sizes, np.zeros((pad,), sizes.dtype)], axis=0)
        if attrs is not None:
            attrs = np.concatenate(
                [attrs, np.full((pad,) + attrs.shape[1:], -1, attrs.dtype)],
                axis=0)
    # round-robin: shard j owns lists j, j+S, j+2S, ... — balances sizes when
    # k-means produces a long tail of small clusters
    perm = round_robin_perm(nlist, s)
    real = (perm < nlist).reshape(s, l)
    return (
        jnp.asarray(cen[perm].reshape(s, l, -1)),
        ListStore(
            codes=jnp.asarray(codes[perm].reshape((s, l) + codes.shape[1:])),
            ids=jnp.asarray(ids[perm].reshape(s, l, -1)),
            sizes=jnp.asarray(sizes[perm].reshape(s, l)),
            attrs=None if attrs is None else jnp.asarray(
                attrs[perm].reshape(s, l, -1)),
        ),
        jnp.asarray(real),
    )


def partition_filter(filter_bits: jax.Array, num_shards: int) -> jax.Array:
    """Shard a packed filter bitmap like ``partition_lists`` shards the codes.

    filter_bits: (nlist, W) u8 over the *global* list ids. Returns
    (S, L, W) u8 aligned with the partitioned store — shard j's row i is the
    bitmap of the list ``partition_lists`` placed at (j, i); padding lists
    get all-zero words (nothing passes — they hold no rows anyway). Pure jnp
    (the permutation is a compile-time constant), so it composes under jit;
    per-request filters go through here on every sharded search.
    """
    nlist, w = filter_bits.shape
    s = int(num_shards)
    l = -(-nlist // s)
    pad = s * l - nlist
    bits = filter_bits
    if pad:
        bits = jnp.concatenate(
            [bits, jnp.zeros((pad, w), filter_bits.dtype)], axis=0)
    perm = jnp.asarray(round_robin_perm(nlist, s))
    return bits[perm].reshape(s, l, w)


def partition_base(lists_s: ListStore, base: jax.Array
                   ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-shard base-vector slices + the id->row remap for sharded re-rank.

    Each base vector lives in exactly one posting list, hence on exactly one
    shard — so the exact-re-rank stage only ever needs the rows whose lists
    that shard owns. This builds those slices (host-side, offline, like
    ``build_lists``) so ``ShardedEngine`` stops replicating the full (N, D)
    base to every device.

    lists_s: ListStore with leading shard dim S (from ``partition_lists``,
    ids still global); base: (N, D) f32.

    Returns:
      base_s    (S, R, D) f32 — shard-local base rows, zero-padded;
      gids_s    (S, R)    i32 — global id of each local row (-1 = padding);
      local_ids (S, L, cap) i32 — ``lists_s.ids`` remapped to shard-local
                row indices into ``base_s`` (-1 where ids was -1).

    R = max over shards of the shard's vector count (static shapes — the
    round-robin list partition keeps shards balanced, so the padding slack
    is small). Search runs on local ids end-to-end and maps back to global
    via ``gids_s`` just before the distributed merge.
    """
    ids = np.asarray(lists_s.ids)              # (S, L, cap) global ids
    s = ids.shape[0]
    base_np = np.asarray(base, np.float32)
    flat = ids.reshape(s, -1)
    mask = flat >= 0
    r_cap = max(1, int(mask.sum(axis=1).max()))
    base_s = np.zeros((s, r_cap, base_np.shape[1]), np.float32)
    gids_s = np.full((s, r_cap), -1, np.int32)
    local_flat = np.full(flat.shape, -1, np.int32)
    for j in range(s):
        g = flat[j][mask[j]]                   # globals in order of appearance
        base_s[j, :g.size] = base_np[g]
        gids_s[j, :g.size] = g
        local_flat[j][mask[j]] = np.arange(g.size, dtype=np.int32)
    return (jnp.asarray(base_s), jnp.asarray(gids_s),
            jnp.asarray(local_flat.reshape(ids.shape)))
