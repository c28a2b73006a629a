"""Pallas TPU kernels for the 4-bit PQ fast-scan ADC (paper §3, TPU-adapted).

The paper emulates AVX2's 256-bit in-register shuffle with two NEON 128-bit
``vqtbl1q_u8`` table lookups. A TPU has no cross-lane shuffle at all, so we
re-express the register-resident 16-entry LUT gather in the units the TPU
*does* have, keeping the LUT pinned in VMEM/VREGs (the TPU analogue of the
SIMD register file):

Variant A — ``select-tree`` (VPU, paper-faithful analogue):
    A 16-way LUT lookup is decomposed into log2(16) = 4 levels of 2-way
    vector selects over statically-sliced halves of the LUT, exactly as the
    paper decomposes one 256-bit shuffle into two 128-bit shuffles. All
    operands live in vector registers; the only memory traffic is the code
    tile stream.

Variant B — ``one-hot MXU`` (beyond-paper):
    The ADC gather for a *batch* of queries is algebraically a matmul:
    ``acc[q, n] = T_flat[q] . onehot(codes[n])`` with ``T_flat`` the stacked
    (M*16) LUT. On TPU the systolic MXU is the throughput unit, so we convert
    the gather into a dense bf16 GEMM. Exactness: all u8 LUT entries (0..255)
    and one-hot 0/1 are exactly representable in bf16; products and the f32
    accumulation of <= M*16 terms (<= 32640 for M <= 128) are exact in f32,
    so the result is still bit-identical to the int oracle.

    Both the flat (shared database, ``fastscan_onehot_mxu``) and the grouped
    (gathered IVF lists, ``fastscan_onehot_mxu_grouped``) scans have MXU
    forms; the grouped one is the serving hot path (``core.ivf.scan_probes``)
    where each (query, probe) pair owns its own residual LUT.

Variant C — ``fused block-min``: variant B plus an in-kernel per-tile
    min/argmin reduction, the TPU stand-in for faiss' SIMD top-k candidate
    filtering via ``_mm256_movemask_epi8`` (which has no Pallas equivalent).

Variant D — ``stream`` (gather-free probe streaming):
    The grouped variants above consume a *gathered* ``(G, cap, M//2)`` copy
    of every probed list — an O(G·cap) HBM round trip that exists only to
    feed the kernel. The stream kernels instead take ``ListStore.codes``
    **in place** (``(nlist, cap, M//2)`` u8) plus scalar-prefetched probe
    ids, and each grid step's BlockSpec index map picks the probed list's
    ``(tile_n, M//2)`` tile, which Pallas copies into VMEM — the gathered
    copy never exists, and invalid probes (id -1) reuse the previous
    step's block, so the pipeline issues no copy for them.
    ``fastscan_stream_topk_grouped`` additionally fuses the candidate
    reduction: instead of writing the full ``(G, cap)`` accumulation back to
    HBM it keeps a per-tile partial selection in VMEM and emits only
    ``(G, n_tiles, kc)`` (quantized dist, slot) candidate pairs — shrinking
    the scan-stage writeback by ~cap/kc. Pallas double-buffers the block
    copies: tile t+1 streams in while tile t is scanned.

All kernels are tiled with explicit BlockSpecs, laid out for Mosaic (the
TPU kernel compiler), which lowers only blocks whose last two dims are
(8, 128)-aligned or equal to the array's:

  codes    nibble-packed ``(tile_n, M//2) u8`` tiles, split into lo/hi
           nibble planes in registers (no interleave, no lane reshape);
  LUTs     ``_lut_planes`` layout ``(32, M//2)`` — one row per (nibble,
           code value), broadcast along sublanes against the code tile;
  outputs  one row per (group, tile) of a per-group ``(1, n_tiles, X)``
           block that stays resident while the grid walks the group's
           tiles (``_store_row``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Default tile sizes. Lane dim multiples of 128, sublane multiples of 8
# (f32/i32 VREG tile is 8x128). N tile of 1024 keeps the code tile
# (1024 x M/2 u8) well under VMEM while amortizing LUT residency.
TILE_N = 1024
TILE_Q = 128
# Queries per grid step of the flat select-tree scan: its (8, tile_n) i32
# output block is one whole sublane tile.
ROWS_PER_STEP = 8


def _lut_planes(table_q8: jax.Array) -> jax.Array:
    """(..., M, 16) u8 LUT -> (..., 32, M//2) u8 nibble planes.

    Row ``h*16 + v``, column ``j`` holds ``T[2j + h, v]``: rows 0..15 serve
    the low nibble of packed byte j (even sub-space 2j), rows 16..31 its
    high nibble (odd sub-space 2j+1). One plane row broadcasts along
    sublanes against a whole (tile_n, M//2) code tile, so no kernel ever
    interleaves nibbles or reshapes across lanes — both of which Mosaic
    refuses. ``planes.reshape(..., 16*M)`` is the flat LUT row the one-hot
    MXU kernels contract against (``_onehot_codes`` lane order).
    """
    *lead, m, k = table_q8.shape
    t = table_q8.reshape(*lead, m // 2, 2, k)
    return jnp.moveaxis(t, -3, -1).reshape(*lead, 2 * k, m // 2)


def _nibbles(packed_u8: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(tn, M//2) u8 -> (lo, hi) (tn, M//2) i32: codes of sub-spaces 2j, 2j+1."""
    p = packed_u8.astype(jnp.int32)
    return p & 0xF, (p >> 4) & 0xF


def _store_row(ref, row_idx, row: jax.Array) -> None:
    """Write one (1, X) row into a per-group (1, R, X) output block.

    Per-group outputs are shaped (G, R, X) with block (1, R, X): the block's
    last two dims equal the array's, which Mosaic accepts for any R and X,
    and the block stays resident while the grid walks the group's R tiles.
    """
    ref[0, pl.ds(row_idx, 1), :] = row


def _first_argmin(a: jax.Array, mn: jax.Array) -> jax.Array:
    """Lowest lane index holding the row minimum ``mn``: (r, n) -> (r, 1).

    Spelled as a masked min because Mosaic lowers ``argmin`` for f32 only;
    the first-occurrence tie-break is the one ``masked_topk`` uses.
    """
    iota = jax.lax.broadcasted_iota(jnp.int32, a.shape, a.ndim - 1)
    return jnp.min(jnp.where(a == mn, iota, a.shape[-1]), axis=-1,
                   keepdims=True)


# ---------------------------------------------------------------------------
# Variant A: select-tree (VPU)
# ---------------------------------------------------------------------------

def _select16(planes: jax.Array, base: int, codes: jax.Array) -> jax.Array:
    """out[n, j] = planes[base + codes[n, j], j] by a 4-level select tree.

    planes (32, M//2) i32, codes (tn, M//2) i32 in [0, 16). A 16-way LUT
    lookup decomposed into log2(16) = 4 levels of 2-way vector selects,
    most significant bit first (the paper's 256-bit shuffle via 2x128-bit
    shuffles, one level deeper on TPU).
    """
    rows = [planes[base + v: base + v + 1, :] for v in range(16)]
    for bit in (8, 4, 2, 1):
        half = len(rows) // 2
        take_hi = (codes & bit) != 0
        rows = [jnp.where(take_hi, rows[half + i], rows[i])
                for i in range(half)]
    return rows[0]


def _select_tree_acc(planes: jax.Array, lo: jax.Array, hi: jax.Array
                     ) -> jax.Array:
    """Select-tree ADC: (32, M//2) i32 planes x nibbles (tn, M//2) -> (1, tn)
    i32 sums. The per-code sums land along sublanes; one transpose turns
    them into the lane-dense row every output block stores."""
    per = _select16(planes, 0, lo) + _select16(planes, 16, hi)
    return jnp.sum(per, axis=1, keepdims=True).T


def _select_tree_kernel(table_ref, codes_ref, out_ref):
    """ROWS_PER_STEP queries x one N tile.

    table_ref: (R, 32, M//2) u8 block — R queries' LUT planes
    codes_ref: (tn, M//2) u8 block  — nibble-packed codes, split once
    out_ref:   (R, tn) i32 block
    """
    lo, hi = _nibbles(codes_ref[...])
    for r in range(out_ref.shape[0]):
        out_ref[r:r + 1, :] = _select_tree_acc(
            table_ref[r].astype(jnp.int32), lo, hi)


def fastscan_select_tree(table_q8: jax.Array, packed_codes: jax.Array, *,
                         tile_n: int = TILE_N, interpret: bool) -> jax.Array:
    """(Q, M, 16) u8 x (N, M//2) u8 -> (Q, N) i32. Q pre-padded to a
    ROWS_PER_STEP multiple, N to a tile_n multiple."""
    q, m, k = table_q8.shape
    n, mh = packed_codes.shape
    assert k == 16 and mh * 2 == m and n % tile_n == 0
    assert q % ROWS_PER_STEP == 0, (q, ROWS_PER_STEP)
    grid = (q // ROWS_PER_STEP, n // tile_n)
    return pl.pallas_call(
        _select_tree_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROWS_PER_STEP, 2 * k, mh),
                         lambda qi, ni: (qi, 0, 0)),
            pl.BlockSpec((tile_n, mh), lambda qi, ni: (ni, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS_PER_STEP, tile_n), lambda qi, ni: (qi, ni)),
        out_shape=jax.ShapeDtypeStruct((q, n), jnp.int32),
        interpret=interpret,
    )(_lut_planes(table_q8), packed_codes)


def _select_tree_grouped_kernel(table_ref, codes_ref, out_ref):
    """One (query, probe) group x one N tile — each group has its OWN LUT
    *and* its own code tile (gathered IVF lists), unlike the shared-database
    variant above.

    table_ref: (1, 32, M//2) u8 planes; codes_ref: (1, tn, M//2) u8 block;
    out_ref: (1, n_tiles, tn) i32, this group's row ``ni`` written.
    """
    lo, hi = _nibbles(codes_ref[0])
    _store_row(out_ref, pl.program_id(1),
               _select_tree_acc(table_ref[0].astype(jnp.int32), lo, hi))


def fastscan_select_tree_grouped(table_q8: jax.Array, packed_codes: jax.Array,
                                 *, tile_n: int = TILE_N, interpret: bool
                                 ) -> jax.Array:
    """Grouped ADC: (G, M, 16) u8 x (G, N, M//2) u8 -> (G, N) i32.

    The IVF 'memory path' made register-resident: group g = one
    (query, probed-list) pair whose residual LUT scans only that list's code
    tile. N (the padded list capacity) must be a tile_n multiple.
    """
    g, m, k = table_q8.shape
    gc, n, mh = packed_codes.shape
    assert k == 16 and mh * 2 == m and gc == g and n % tile_n == 0
    n_tiles = n // tile_n
    return pl.pallas_call(
        _select_tree_grouped_kernel,
        grid=(g, n_tiles),
        in_specs=[
            pl.BlockSpec((1, 2 * k, mh), lambda gi, ni: (gi, 0, 0)),
            pl.BlockSpec((1, tile_n, mh), lambda gi, ni: (gi, ni, 0)),
        ],
        out_specs=pl.BlockSpec((1, n_tiles, tile_n), lambda gi, ni: (gi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g, n_tiles, tile_n), jnp.int32),
        interpret=interpret,
    )(_lut_planes(table_q8), packed_codes).reshape(g, n)


# ---------------------------------------------------------------------------
# Variant B: one-hot MXU
# ---------------------------------------------------------------------------

def _onehot_codes(packed_u8: jax.Array) -> jax.Array:
    """(tn, M//2) u8 -> (tn, 16*M) bf16 one-hot planes.

    Lane ``h*8M + v*(M//2) + j`` is 1 iff nibble h of byte j equals v — the
    lane order of ``_lut_planes(...).reshape(16*M)``. Built by repeating the
    nibble columns along lanes and comparing against a lane iota, so the
    (tn, M, 16) -> (tn, M*16) reshape Mosaic refuses never happens.
    """
    lo, hi = _nibbles(packed_u8)
    rep = jnp.concatenate([lo] * 16 + [hi] * 16, axis=1)
    v = (jax.lax.broadcasted_iota(jnp.int32, rep.shape, 1) // lo.shape[1]) % 16
    return (rep == v).astype(jnp.bfloat16)


def _mxu_acc(tflat: jax.Array, packed_u8: jax.Array) -> jax.Array:
    """(r, 16*M) bf16 flat LUT rows x (tn, M//2) u8 codes -> (r, tn) i32.

    Exact: u8 LUT entries and one-hot 0/1 are exact in bf16, and the f32
    accumulation of M nonzero terms (<= 255*M) is exact.
    """
    acc = jax.lax.dot_general(
        tflat, _onehot_codes(packed_u8),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return acc.astype(jnp.int32)


def _mxu_table(table_q8: jax.Array) -> jax.Array:
    """(..., M, 16) u8 -> (..., 16*M) bf16 flat LUT rows (u8 -> bf16 is a
    cast Mosaic lacks, so it happens here, outside the kernel)."""
    planes = _lut_planes(table_q8)
    return planes.reshape(*planes.shape[:-2], -1).astype(jnp.bfloat16)


def _onehot_mxu_kernel(table_ref, codes_ref, out_ref):
    """table_ref: (tq, 16*M) bf16; codes_ref: (tn, M//2) u8; out_ref: (tq, tn) i32."""
    out_ref[...] = _mxu_acc(table_ref[...], codes_ref[...])


def fastscan_onehot_mxu(table_q8: jax.Array, packed_codes: jax.Array, *,
                        tile_n: int = TILE_N, tile_q: int = TILE_Q,
                        interpret: bool) -> jax.Array:
    """(Q, M, 16) u8 x (N, M//2) u8 -> (Q, N) i32. Q, N pre-padded to tiles."""
    q, m, k = table_q8.shape
    n, mh = packed_codes.shape
    assert k == 16 and mh * 2 == m
    assert q % tile_q == 0 and n % tile_n == 0, (q, tile_q, n, tile_n)
    grid = (q // tile_q, n // tile_n)
    return pl.pallas_call(
        _onehot_mxu_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_q, m * 16), lambda qi, ni: (qi, 0)),
            pl.BlockSpec((tile_n, mh), lambda qi, ni: (ni, 0)),
        ],
        out_specs=pl.BlockSpec((tile_q, tile_n), lambda qi, ni: (qi, ni)),
        out_shape=jax.ShapeDtypeStruct((q, n), jnp.int32),
        interpret=interpret,
    )(_mxu_table(table_q8), packed_codes)


def _onehot_mxu_grouped_kernel(table_ref, codes_ref, out_ref):
    """One (query, probe) group x one cap tile, on the MXU.

    table_ref: (1, 1, 16*M) bf16 — this group's flat LUT row
    codes_ref: (1, tn, M//2) u8 block — this group's gathered code tile
    out_ref:   (1, n_tiles, tn) i32, this group's row ``ni`` written

    The grouped ADC gather is a per-group matvec: expand the nibble codes
    to one-hot lanes and contract against the group's own LUT row on the
    MXU with f32 accumulation (exactness as in ``_mxu_acc``).
    """
    _store_row(out_ref, pl.program_id(1),
               _mxu_acc(table_ref[0], codes_ref[0]))


def fastscan_onehot_mxu_grouped(table_q8: jax.Array, packed_codes: jax.Array,
                                *, tile_n: int = TILE_N, interpret: bool
                                ) -> jax.Array:
    """Grouped one-hot MXU ADC: (G, M, 16) u8 x (G, cap, M//2) u8 -> (G, cap) i32.

    The MXU formulation of the gathered-list scan — the path every real IVF
    search takes (``scan_probes``). Group g = one (query, probed-list) pair
    with its OWN residual LUT and its own gathered code tile; the grid runs
    over (group, cap tile) and each program does one LUT-row x one-hot-codes
    contraction on the MXU. cap must be a tile_n multiple (pre-padded).
    Bit-identical to the ref/select formulations.
    """
    g, m, k = table_q8.shape
    gc, n, mh = packed_codes.shape
    assert k == 16 and mh * 2 == m and gc == g and n % tile_n == 0
    n_tiles = n // tile_n
    return pl.pallas_call(
        _onehot_mxu_grouped_kernel,
        grid=(g, n_tiles),
        in_specs=[
            pl.BlockSpec((1, 1, m * 16), lambda gi, ni: (gi, 0, 0)),
            pl.BlockSpec((1, tile_n, mh), lambda gi, ni: (gi, ni, 0)),
        ],
        out_specs=pl.BlockSpec((1, n_tiles, tile_n), lambda gi, ni: (gi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g, n_tiles, tile_n), jnp.int32),
        interpret=interpret,
    )(_mxu_table(table_q8)[:, None, :], packed_codes).reshape(g, n)


# ---------------------------------------------------------------------------
# Variant C: fused scan + per-tile min/argmin (top-1 candidate filter)
# ---------------------------------------------------------------------------

def _blockmin_kernel(table_ref, codes_ref, min_ref, arg_ref, *, tile_n: int):
    """table_ref (tq, 16*M) bf16; codes_ref (tn, M//2) u8; min_ref/arg_ref
    (1, tq, 1) i32 — this N tile's column of the (n_tiles, Q, 1) outputs."""
    acc = _mxu_acc(table_ref[...], codes_ref[...])  # (tq, tn)
    # in-register reduction: the movemask/top-k filter analogue
    mn = jnp.min(acc, axis=-1, keepdims=True)
    min_ref[0] = mn
    arg_ref[0] = _first_argmin(acc, mn) + pl.program_id(1) * tile_n


def fastscan_blockmin(table_q8: jax.Array, packed_codes: jax.Array, *,
                      tile_n: int = TILE_N, tile_q: int = TILE_Q,
                      interpret: bool) -> tuple[jax.Array, jax.Array]:
    """Fused ADC + per-N-tile min: (Q, N/tile_n) i32 mins and global argmin ids."""
    q, m, k = table_q8.shape
    n, mh = packed_codes.shape
    assert k == 16 and mh * 2 == m
    assert q % tile_q == 0 and n % tile_n == 0
    nb = n // tile_n
    kernel = functools.partial(_blockmin_kernel, tile_n=tile_n)
    mins, args = pl.pallas_call(
        kernel,
        grid=(q // tile_q, nb),
        in_specs=[
            pl.BlockSpec((tile_q, m * 16), lambda qi, ni: (qi, 0)),
            pl.BlockSpec((tile_n, mh), lambda qi, ni: (ni, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tile_q, 1), lambda qi, ni: (ni, qi, 0)),
            pl.BlockSpec((1, tile_q, 1), lambda qi, ni: (ni, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, q, 1), jnp.int32),
            jax.ShapeDtypeStruct((nb, q, 1), jnp.int32),
        ],
        interpret=interpret,
    )(_mxu_table(table_q8), packed_codes)
    return mins[..., 0].T, args[..., 0].T


# ---------------------------------------------------------------------------
# Variant D: gather-free probe streaming (in-place list tiles)
# ---------------------------------------------------------------------------

# Larger than any reachable ADC sum (<= 128 sub-spaces * 255 = 32640), used
# to mark padded/invalid candidate slots inside the fused selection.
ACC_SENTINEL = jnp.iinfo(jnp.int32).max


def _fetch_plan(probe_ids: jax.Array, n_tiles: int) -> jax.Array:
    """(G,) probe ids -> (G*n_tiles,) i32 source step per sequential step.

    Grid steps run group-major: step ``s`` is group ``s // n_tiles``, cap
    tile ``s % n_tiles``. A step whose probe is invalid (-1) borrows the
    block of the nearest earlier valid step (or, before the first valid
    step, of that first one), so its code block index equals its
    neighbour's and the block pipeline issues no copy for it: invalid
    probes cost no HBM traffic.
    """
    steps = jnp.arange(probe_ids.shape[0] * n_tiles, dtype=jnp.int32)
    valid = jnp.repeat(probe_ids >= 0, n_tiles)
    last = jax.lax.cummax(jnp.where(valid, steps, -1))
    first = jnp.argmax(valid).astype(jnp.int32)
    return jnp.where(last >= 0, last, first)


def _codes_spec(tile_n: int, n_tiles: int, mh: int) -> pl.BlockSpec:
    """The in-place code tile of a step, fetched by the block pipeline.

    The index map reads the scalar-prefetched probe ids (first prefetch
    operand) and fetch plan (last prefetch operand): step (gi, ni) lands
    tile ``src % n_tiles`` of list ``probe[src // n_tiles]`` with ``src``
    from ``_fetch_plan``. Pallas double-buffers the copies (tile t+1
    streams in while tile t is scanned) and skips a copy whose block index
    repeats the previous step's. Manual DMAs of this (tile_n, M//2) slice
    are refused by Mosaic (the M//2 lane dim is not 128-aligned); the
    block pipeline takes it.
    """
    def index(gi, ni, probe_ref, *rest):
        src = rest[-1][gi * n_tiles + ni]
        return (jnp.maximum(probe_ref[src // n_tiles], 0), src % n_tiles, 0)
    return pl.BlockSpec((1, tile_n, mh), index)


def _stream_grouped_kernel(probe_ref, src_ref, table_ref, codes_ref,
                           out_ref, *, tile_n: int):
    """One (query, probe) group x one cap tile, codes fetched in place.

    probe_ref: (G,) i32 scalar-prefetched flat probe ids (-1 = no probe)
    src_ref:   (G*n_tiles,) i32 scalar-prefetched fetch plan (index maps)
    table_ref: (1, 32, M//2) u8 block — this group's LUT planes (VMEM)
    codes_ref: (1, tile_n, M//2) u8 block — the probed list's tile, copied
               out of the untouched ListStore by the block pipeline
    out_ref:   (1, n_tiles, tile_n) i32 — this group's row ``ni`` written
    """
    del src_ref  # read by the index maps only
    ni = pl.program_id(1)
    lid = probe_ref[pl.program_id(0)]

    @pl.when(lid >= 0)
    def _scan():
        lo, hi = _nibbles(codes_ref[0])
        _store_row(out_ref, ni, _select_tree_acc(
            table_ref[0].astype(jnp.int32), lo, hi))

    @pl.when(lid < 0)
    def _skip():  # no copy, no scan: invalid probes cost nothing
        _store_row(out_ref, ni, jnp.zeros((1, tile_n), jnp.int32))


def fastscan_stream_grouped(table_q8: jax.Array, list_codes: jax.Array,
                            probe_ids: jax.Array, *, tile_n: int = TILE_N,
                            interpret: bool) -> jax.Array:
    """Gather-free grouped ADC: (G, M, 16) u8 LUTs x (nlist, cap, M//2) u8
    codes *in place* + (G,) i32 probe ids -> (G, cap) i32.

    Semantically ``fastscan_select_tree_grouped(table, codes[probe_ids])``
    without the gathered copy ever existing: a PrefetchScalarGridSpec makes
    ``probe_ids`` available before the grid runs, and each (group, cap-tile)
    step copies only that probed list's tile from HBM into VMEM. Invalid
    probes (id -1) issue no copy and emit zeros (their
    output is id-masked downstream, like gathered padding). cap must be a
    ``tile_n`` multiple — the store is scanned in place, never padded.
    """
    g, m, k = table_q8.shape
    nlist, cap, mh = list_codes.shape
    assert k == 16 and mh * 2 == m and probe_ids.shape == (g,)
    assert cap % tile_n == 0, (cap, tile_n)
    n_tiles = cap // tile_n
    probes = probe_ids.astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(g, n_tiles),
        in_specs=[
            pl.BlockSpec((1, 2 * k, mh), lambda gi, ni, *_: (gi, 0, 0)),
            _codes_spec(tile_n, n_tiles, mh),
        ],
        out_specs=pl.BlockSpec((1, n_tiles, tile_n),
                               lambda gi, ni, *_: (gi, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_stream_grouped_kernel, tile_n=tile_n),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g, n_tiles, tile_n), jnp.int32),
        interpret=interpret,
    )(probes, _fetch_plan(probes, n_tiles), _lut_planes(table_q8),
      list_codes).reshape(g, cap)


def _tile_topk(acc: jax.Array, slot_base: jax.Array, kc: int
               ) -> tuple[jax.Array, jax.Array]:
    """Smallest kc of acc (1, tn) i32 by iterative min-extraction, in VMEM.

    Entries equal to ACC_SENTINEL are treated as absent. Returns
    (vals (1, kc) i32 ascending, slots (1, kc) i32 global slot ids, -1 where
    fewer than kc real entries exist). Ties resolve to the lowest slot
    (first occurrence), matching ``masked_topk``'s lowest-flat-index
    tie-break on the full array.
    """
    tn = acc.shape[-1]
    iota_n = jax.lax.broadcasted_iota(jnp.int32, (1, tn), 1)
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (1, kc), 1)

    def body(j, carry):
        a, vals, slots = carry
        mn = jnp.min(a, axis=-1, keepdims=True)                    # (1, 1)
        am = _first_argmin(a, mn)                                  # (1, 1)
        vals = jnp.where(iota_k == j, mn, vals)
        slots = jnp.where(iota_k == j, am, slots)
        a = jnp.where(iota_n == am, ACC_SENTINEL, a)
        return a, vals, slots

    init = (acc,
            jnp.full((1, kc), ACC_SENTINEL, jnp.int32),
            jnp.zeros((1, kc), jnp.int32))
    _, vals, slots = jax.lax.fori_loop(0, kc, body, init)
    slots = jnp.where(vals == ACC_SENTINEL, -1, slots + slot_base)
    return vals, slots


def _tile_filter_bits(words_u8: jax.Array, tile_n: int) -> jax.Array:
    """(1, W) u8 packed filter words -> (1, tile_n) i32 bits, LSB-first.

    Bit j of word w is slot w*8 + j (core/lists.py layout). Each word is
    repeated over its 8 lanes by a 0/1 matmul — exact, since bytes are
    bf16-exact — then shifted by its lane's bit index: the lane-dense
    unpack Mosaic accepts in place of a stack + reshape.
    """
    w = words_u8.astype(jnp.int32)
    nw = w.shape[-1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (nw, 8 * nw), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (nw, 8 * nw), 1)
    spread = (cols // 8 == rows).astype(jnp.bfloat16)
    rep = jax.lax.dot_general(
        w.astype(jnp.float32).astype(jnp.bfloat16), spread,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, rep.shape, 1)
    return ((rep >> (lane % 8)) & 1)[:, :tile_n]


def _masked_tile_acc(table_ref, codes_ref, sizes_ref, lid, fbits_ref, *,
                     tile_n: int, ni):
    """Scan the landed tile and mask non-candidates to ACC_SENTINEL: slots
    past the list's occupancy and, with a filter, rows whose bit is 0 — a
    filtered row is indistinguishable from padding before selection."""
    lo, hi = _nibbles(codes_ref[0])
    acc = _select_tree_acc(table_ref[0].astype(jnp.int32), lo, hi)  # (1, tn)
    slot_ids = (jax.lax.broadcasted_iota(jnp.int32, (1, tile_n), 1)
                + ni * tile_n)
    acc = jnp.where(slot_ids < sizes_ref[lid], acc, ACC_SENTINEL)
    if fbits_ref is not None:
        bits = _tile_filter_bits(fbits_ref[0], tile_n)
        acc = jnp.where(bits > 0, acc, ACC_SENTINEL)
    return acc


def _stream_topk_kernel(probe_ref, sizes_ref, src_ref, table_ref, *rest,
                        tile_n: int, kc: int, has_filter: bool):
    """Stream kernel + fused per-tile candidate selection (+ optional
    per-row predicate mask).

    Outputs per (group, cap-tile): the kc smallest quantized dists and their
    global slot ids within the list (-1 = absent), as row ``ni`` of the
    group's (1, n_tiles, kc) blocks. Slots past the list's true occupancy
    (``sizes_ref``) are masked to ACC_SENTINEL *before* the selection, so
    padding can never displace a real candidate. With ``has_filter`` this
    tile's packed filter words (``fbits_ref``, (1, 1, W_t) u8, LSB-first —
    see core/lists.py) ride into VMEM next to the LUT; rows whose bit is 0
    are masked through the *same* pre-selection path as the occupancy mask,
    so the fused selection stays bit-identical to a post-filtered oracle.
    Codes arrive as in ``_stream_grouped_kernel``: tile t+1's copy overlaps
    tile t's scan+selection.
    """
    del src_ref  # read by the index maps only
    if has_filter:
        fbits_ref, codes_ref, vals_ref, slots_ref = rest
    else:
        codes_ref, vals_ref, slots_ref = rest
        fbits_ref = None
    ni = pl.program_id(1)
    lid = probe_ref[pl.program_id(0)]

    @pl.when(lid >= 0)
    def _scan():
        acc = _masked_tile_acc(table_ref, codes_ref, sizes_ref, lid,
                               fbits_ref, tile_n=tile_n, ni=ni)
        vals, slots = _tile_topk(acc, ni * tile_n, kc)
        _store_row(vals_ref, ni, vals)
        _store_row(slots_ref, ni, slots)

    @pl.when(lid < 0)
    def _skip():
        _store_row(vals_ref, ni, jnp.full((1, kc), ACC_SENTINEL, jnp.int32))
        _store_row(slots_ref, ni, jnp.full((1, kc), -1, jnp.int32))


def _merge_smallest(cat: jax.Array, kc: int) -> jax.Array:
    """Smallest kc of cat (1, W) f32 ascending, +inf = absent. Same iterative
    min-extraction as ``_tile_topk`` but in the dequantized f32 domain the
    early-exit threshold lives in."""
    w = cat.shape[-1]
    iota_n = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (1, kc), 1)

    def body(j, carry):
        a, vals = carry
        mn = jnp.min(a, axis=-1, keepdims=True)
        am = _first_argmin(a, mn)
        vals = jnp.where(iota_k == j, mn, vals)
        a = jnp.where(iota_n == am, jnp.float32(jnp.inf), a)
        return a, vals

    init = (cat, jnp.full((1, kc), jnp.inf, jnp.float32))
    _, vals = jax.lax.fori_loop(0, kc, body, init)
    return vals


def _stream_topk_prune_kernel(probe_ref, sizes_ref, src_ref, table_ref,
                              bounds_ref, scales_ref, biases_ref, *rest,
                              tile_n: int, kc: int, n_tiles: int, gpq: int,
                              has_filter: bool):
    """Early-exit variant of ``_stream_topk_kernel``: anytime tile pruning.

    Extra operands (all (G,), SMEM — read as scalars, never tiled):
      bounds_ref  f32 — per-group lower bound on any candidate's dequantized
                  distance (``scale * sum_m min_j LUT[m, j] + bias``), the
                  min possible ADC sum made comparable across a query's
                  probes. Admissible by construction: the per-subquantizer
                  minimum undercuts every real code, and ``a*x + b`` with
                  ``a >= 0`` is monotone under f32 rounding.
      scales_ref / biases_ref — the group's dequantization affine, the SAME
                  expression downstream selection applies to the emitted
                  quantized vals, so in-kernel threshold comparisons agree
                  bitwise with the host-side ordering.

    Extra scratch: ``run_ref`` (1, kc) f32 VMEM — running top-kc dequantized
    distances of the *current query* (groups arrive query-major, ``gpq``
    groups per query); ``thr_ref`` (1,) f32 SMEM — its kc-th best as a
    scalar.

    A tile is skipped when its group's bound can't beat the running kc-th
    best: every candidate it could emit is >= bound >= threshold, and the
    running set already holds kc candidates from earlier flat positions, so
    with downstream's lowest-index tie-break the final top-kc is unchanged
    (bit-identical for kc == keep). A skipped tile saves its scan and
    selection; its copy was already issued by the block pipeline, whose
    index maps cannot see the running threshold.

    Third output ``skip_ref`` (1, n_tiles, 1) i32: row ``ni`` is 1 iff this
    (group, tile) held a valid probe but was pruned (its emitted candidates
    are sentinels).
    """
    del src_ref  # read by the index maps only
    if has_filter:
        (fbits_ref, codes_ref, vals_ref, slots_ref, skip_ref,
         run_ref, thr_ref) = rest
    else:
        codes_ref, vals_ref, slots_ref, skip_ref, run_ref, thr_ref = rest
        fbits_ref = None
    gi = pl.program_id(0)
    ni = pl.program_id(1)
    lid = probe_ref[gi]

    @pl.when((gi * n_tiles + ni) % (gpq * n_tiles) == 0)
    def _reset():  # first tile of a new query: no candidates seen yet
        run_ref[...] = jnp.full_like(run_ref, jnp.inf)
        thr_ref[0] = jnp.float32(jnp.inf)

    do_scan = (lid >= 0) & (bounds_ref[gi] < thr_ref[0])

    @pl.when(do_scan)
    def _scan():
        acc = _masked_tile_acc(table_ref, codes_ref, sizes_ref, lid,
                               fbits_ref, tile_n=tile_n, ni=ni)
        vals, slots = _tile_topk(acc, ni * tile_n, kc)
        _store_row(vals_ref, ni, vals)
        _store_row(slots_ref, ni, slots)
        _store_row(skip_ref, ni, jnp.zeros((1, 1), jnp.int32))
        # fold this tile's candidates into the query's running top-kc and
        # tighten the threshold (the same affine downstream applies)
        d = scales_ref[gi] * vals.astype(jnp.float32) + biases_ref[gi]
        d = jnp.where(slots < 0, jnp.float32(jnp.inf), d)
        merged = _merge_smallest(
            jnp.concatenate([run_ref[...], d], axis=-1), kc)
        run_ref[...] = merged
        thr_ref[0] = jnp.max(merged)  # ascending: the kc-th best

    @pl.when(~do_scan)
    def _skip():  # invalid probe, or a tile the bound proved irrelevant
        _store_row(vals_ref, ni, jnp.full((1, kc), ACC_SENTINEL, jnp.int32))
        _store_row(slots_ref, ni, jnp.full((1, kc), -1, jnp.int32))
        _store_row(skip_ref, ni,
                   jnp.full((1, 1), 1, jnp.int32) * (lid >= 0).astype(jnp.int32))


def fastscan_stream_topk_grouped(table_q8: jax.Array, list_codes: jax.Array,
                                 probe_ids: jax.Array, sizes: jax.Array, *,
                                 kc: int, tile_n: int = TILE_N,
                                 filter_bits: jax.Array | None = None,
                                 interpret: bool,
                                 early_exit: bool = False,
                                 groups_per_query: int = 0,
                                 scales: jax.Array | None = None,
                                 biases: jax.Array | None = None
                                 ) -> tuple[jax.Array, ...]:
    """Gather-free grouped ADC with fused candidate reduction + filtering.

    table_q8 (G, M, 16) u8; list_codes (nlist, cap, M//2) u8 in place;
    probe_ids (G,) i32 (-1 = no probe); sizes (nlist,) i32 true occupancy;
    filter_bits optional (G, W) u8 — each group's *pre-gathered* packed
    filter-bitmap row (W = ceil(cap/8), LSB-first; callers gather
    ``bitmap[max(probe_ids, 0)]`` — ~W bytes/group next to cap*M//2 code
    bytes, so the extra VMEM traffic is ~1.5% at M=16).
    Returns (vals (G, n_tiles, kc) i32, slots (G, n_tiles, kc) i32): per
    (group, cap-tile) the kc smallest quantized distances and their slot
    position inside the probed list, -1 slot = absent (padding past the
    list's occupancy, a filtered-out row, or an invalid probe — whose copy
    is skipped outright).

    The full (G, cap) accumulation never reaches HBM: selection happens in
    VMEM on the tile just copied in, so scan-stage writeback shrinks
    by ~cap/kc. Keeping the per-tile top-kc is exact for any final
    selection of <= kc candidates (every survivor is within its own tile's
    top-kc), with ties resolved identically to ``masked_topk`` over the
    full array (lowest slot wins) — and the predicate mask joins the
    occupancy mask *before* selection, so the filtered result is
    bit-identical to filtering the full accumulation after the fact.

    With ``early_exit`` (anytime search, docs/anytime.md) the kernel also
    prunes tiles whose group-level lower bound on any dequantized distance
    can't beat the query's running kc-th best — skipping the tile's scan
    and selection (its copy is already issued by then). Requires
    ``groups_per_query`` (consecutive groups per query, > 0, dividing G) and
    the per-group dequantization affine ``scales``/``biases`` ((G,) f32,
    exactly what downstream selection applies). Returns a third array
    ``skipped`` (G, n_tiles) i32, 1 per pruned valid-probe tile. The final
    top-kc per query is bit-identical to the unpruned kernel; the raw
    candidate pool is not (pruned tiles emit sentinels).
    """
    g, m, k = table_q8.shape
    nlist, cap, mh = list_codes.shape
    assert k == 16 and mh * 2 == m and probe_ids.shape == (g,)
    assert sizes.shape == (nlist,)
    assert cap % tile_n == 0, (cap, tile_n)
    assert 1 <= kc <= tile_n, (kc, tile_n)
    n_tiles = cap // tile_n
    probes = probe_ids.astype(jnp.int32)
    in_specs = [
        pl.BlockSpec((1, 2 * k, mh), lambda gi, ni, *_: (gi, 0, 0)),
    ]
    operands = [probes, sizes, _fetch_plan(probes, n_tiles),
                _lut_planes(table_q8)]
    if early_exit:
        assert groups_per_query > 0 and g % groups_per_query == 0, (
            g, groups_per_query)
        assert scales is not None and biases is not None
        assert scales.shape == (g,) and biases.shape == (g,), (
            scales.shape, biases.shape, g)
        scales = scales.astype(jnp.float32)
        biases = biases.astype(jnp.float32)
        # Admissible per-group lower bound: the min possible ADC sum (each
        # subquantizer contributes its smallest LUT entry), dequantized with
        # the group's own affine so it is comparable across a query's probes.
        acc_min = jnp.sum(jnp.min(table_q8.astype(jnp.int32), axis=-1),
                          axis=-1)  # (G,)
        bounds = scales * acc_min.astype(jnp.float32) + biases
        for arr in (bounds, scales, biases):
            in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
            operands.append(arr)
    if filter_bits is not None:
        w = filter_bits.shape[-1]
        assert filter_bits.shape == (g, w) and w * 8 >= cap, (
            filter_bits.shape, g, cap)
        # one (1, 1, W_t) block of words per (group, tile): every block's
        # last two dims equal the array's, whatever tile_n is
        if n_tiles == 1:
            wt = w
        else:
            assert tile_n % 8 == 0, tile_n
            wt = tile_n // 8
        tiles = filter_bits[:, :n_tiles * wt].reshape(g * n_tiles, 1, wt)
        in_specs.append(pl.BlockSpec(
            (1, 1, wt), lambda gi, ni, *_: (gi * n_tiles + ni, 0, 0)))
        operands.append(tiles)
    in_specs.append(_codes_spec(tile_n, n_tiles, mh))
    operands.append(list_codes)
    per_group = lambda gi, ni, *_: (gi, 0, 0)  # noqa: E731
    out_specs = [
        pl.BlockSpec((1, n_tiles, kc), per_group),
        pl.BlockSpec((1, n_tiles, kc), per_group),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((g, n_tiles, kc), jnp.int32),
        jax.ShapeDtypeStruct((g, n_tiles, kc), jnp.int32),
    ]
    scratch_shapes = []
    has_filter = filter_bits is not None
    if early_exit:
        out_specs.append(pl.BlockSpec((1, n_tiles, 1), per_group))
        out_shape.append(jax.ShapeDtypeStruct((g, n_tiles, 1), jnp.int32))
        scratch_shapes = [
            pltpu.VMEM((1, kc), jnp.float32),   # running top-kc (dequant)
            pltpu.SMEM((1,), jnp.float32),      # threshold
        ]
        kernel = functools.partial(
            _stream_topk_prune_kernel, tile_n=tile_n, kc=kc,
            n_tiles=n_tiles, gpq=groups_per_query, has_filter=has_filter)
    else:
        kernel = functools.partial(_stream_topk_kernel, tile_n=tile_n, kc=kc,
                                   has_filter=has_filter)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(g, n_tiles),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)
    if early_exit:
        vals, slots, skipped = out
        return vals, slots, skipped[..., 0]
    return tuple(out)
