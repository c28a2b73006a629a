"""jit'd dispatch wrappers around the fast-scan kernels, plus autotuning.

Handles padding (queries to the Q tile, database to the N tile), backend
selection (compiled Pallas on TPU, interpret mode elsewhere), and the
pure-jnp reference fallback. All variants are bit-identical; see ref.py.

Impl registries — ONE source of truth, everything else derives from it:

  ``GROUPED_IMPLS``  concrete grouped-scan formulations ('ref' jnp gather /
                     'select' VPU select-tree / 'mxu' one-hot GEMM /
                     'stream' gather-free in-place list tiles);
  ``IMPLS``          the flat (shared-database) scan: the gathered subset
                     (no probe indirection exists in the flat layout);
  ``SCAN_IMPLS``     what callers may request: GROUPED_IMPLS + 'auto'.

``impl='auto'`` resolves to a concrete (impl, tile_n) via a one-time timed
micro-sweep per ``('scan', backend, interpret, G, cap, M, nlist,
probe_fill)`` signature (``resolve_grouped_impl``; ``nlist`` is in the key
because the 'stream' candidate is timed against a real nlist-sized
ListStore — its HBM strides, not an arange-probed G-list stand-in;
``probe_fill`` because an adaptive-nprobe workload presents sparse probe
sets whose skipped DMAs change the verdict), cached process-wide — the analogue of
the paper picking the widest SIMD unit per target CPU, done empirically per
shape instead of hard-coded per arch. The exact re-rank stage has the same
dispatch problem and shares the machinery: ``RERANK_IMPLS`` ('gathered' |
'stream' | 'auto'), ``rerank_stream_topk`` (the gather-free Pallas re-rank),
and ``resolve_rerank_impl`` (verdicts keyed ``('rerank', backend,
interpret, Q, R, D, k, N)`` in the same cache).
``autotune_cache()`` / ``autotune_cache_size()`` expose the cache for
inspection, mirroring ``engine.fused_cache_size``;
``save_autotune_cache()`` / ``load_autotune_cache()`` persist the resolved
table to JSON so a serving fleet stops re-timing identical signatures on
every boot (``ServingLoop(warmup_cache=...)``) — schema v3; v1/v2 files
load with their scan verdicts re-keyed to the store / probe density they
actually timed (v1: nlist=g; v1+v2: probe_fill=1.0).
"""
from __future__ import annotations

import concurrent.futures
import functools
import json
import os
import threading
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import topk as topk_mod
from repro.kernels import fastscan_kernel as fk
from repro.kernels import ref as ref_mod
from repro.kernels import rerank_kernel as rk

# Concrete grouped-scan kernel formulations. The flat scan supports the
# gathered three; the engine additionally accepts 'auto' (autotuned dispatch
# below).
GROUPED_IMPLS = ("ref", "select", "mxu", "stream")
IMPLS = ("ref", "select", "mxu")
SCAN_IMPLS = GROUPED_IMPLS + ("auto",)
# Exact re-rank (stage 3) formulations: 'gathered' (jnp Σ(q−x)² over a
# gathered (Q, R, D) row copy), 'stream' (gather-free in-kernel row DMA +
# fused top-k, kernels/rerank_kernel.py), 'auto' (timed dispatch, below).
RERANK_CONCRETE = ("gathered", "stream")
RERANK_IMPLS = RERANK_CONCRETE + ("auto",)


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _auto_tile(size: int, cap: int) -> int:
    """Largest power-of-two tile <= cap covering size (min 8, VREG sublane)."""
    pow2 = 1 << max(size - 1, 1).bit_length()
    return max(8, min(cap, pow2))


def _stream_tile(cap: int, tile_n: int = 0) -> int:
    """A cap tile for the in-place stream kernels: must DIVIDE cap (the
    ListStore is scanned where it lives — there is nothing to pad). Honors
    ``tile_n`` when it divides cap, otherwise falls back to the largest
    power-of-two divisor <= TILE_N, then to cap itself (one tile per list).
    """
    if tile_n and cap % tile_n == 0:
        return tile_n
    t = fk.TILE_N
    while t >= 8:
        if cap % t == 0:
            return t
        t //= 2
    return cap


def _pad_to(x: jax.Array, axis: int, mult: int, value: int = 0) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.jit, static_argnames=("impl", "tile_n", "tile_q", "interpret"))
def fastscan_distances(table_q8: jax.Array, packed_codes: jax.Array, *,
                       impl: str = "mxu", tile_n: int = 0, tile_q: int = 0,
                       interpret: bool | None = None) -> jax.Array:
    """ADC accumulation: (Q, M, 16) u8 x (N, M//2) u8 -> (Q, N) i32.

    impl: 'ref' (pure jnp oracle) | 'select' (VPU select-tree, paper-faithful)
          | 'mxu' (one-hot GEMM, beyond-paper). All bit-identical.
    """
    if table_q8.ndim == 2:
        table_q8 = table_q8[None]
    q, m, k = table_q8.shape
    n = packed_codes.shape[0]
    assert k == 16, f"4-bit PQ requires K=16, got {k}"
    if impl == "ref":
        return ref_mod.fastscan_distances_ref(table_q8, packed_codes)

    interp = _default_interpret() if interpret is None else interpret
    tn = tile_n or _auto_tile(n, fk.TILE_N)
    codes_p = _pad_to(packed_codes, 0, tn)

    if impl == "select":
        table_p = _pad_to(table_q8, 0, fk.ROWS_PER_STEP)
        acc = fk.fastscan_select_tree(table_p, codes_p, tile_n=tn,
                                      interpret=interp)
    elif impl == "mxu":
        tq = tile_q or _auto_tile(q, fk.TILE_Q)
        table_p = _pad_to(table_q8, 0, tq)
        acc = fk.fastscan_onehot_mxu(table_p, codes_p, tile_n=tn, tile_q=tq,
                                     interpret=interp)
    else:
        raise ValueError(f"unknown impl {impl!r}; want one of {IMPLS}")
    return acc[:q, :n]


# ---------------------------------------------------------------------------
# grouped scan (the IVF hot path) + autotuned dispatch
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("impl", "tile_n", "interpret"))
def _fastscan_grouped_pallas(table_q8: jax.Array, packed_codes: jax.Array, *,
                             impl: str, tile_n: int,
                             interpret: bool | None) -> jax.Array:
    """Pallas half of the grouped dispatch ('select' | 'mxu'), pre-validated."""
    cap = packed_codes.shape[1]
    interp = _default_interpret() if interpret is None else interpret
    tn = tile_n or _auto_tile(cap, fk.TILE_N)
    codes_p = _pad_to(packed_codes, 1, tn)
    if impl == "select":
        acc = fk.fastscan_select_tree_grouped(table_q8, codes_p, tile_n=tn,
                                              interpret=interp)
    else:
        acc = fk.fastscan_onehot_mxu_grouped(table_q8, codes_p, tile_n=tn,
                                             interpret=interp)
    return acc[:, :cap]


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def _fastscan_grouped_stream(table_q8: jax.Array, packed_codes: jax.Array, *,
                             tile_n: int, interpret: bool | None) -> jax.Array:
    """Stream impl under the *gathered* calling convention: treat the
    (G, cap, M//2) codes as an in-place store of G lists probed by
    arange(G). Exists so 'stream' slots into the same registry/sweep as the
    gathered impls; the gather-free payoff comes from calling
    ``fastscan_stream_grouped`` on the real ListStore instead."""
    g, cap = packed_codes.shape[0], packed_codes.shape[1]
    interp = _default_interpret() if interpret is None else interpret
    # padding a copy is fine here (this is the parity/sweep path, not the
    # in-place hot path), so any tile works — pad cap up to it
    tn = tile_n if (tile_n and cap % tile_n == 0) else _auto_tile(cap, fk.TILE_N)
    codes_p = _pad_to(packed_codes, 1, tn)
    probes = jnp.arange(g, dtype=jnp.int32)
    acc = fk.fastscan_stream_grouped(table_q8, codes_p, probes, tile_n=tn,
                                     interpret=interp)
    return acc[:, :cap]


def fastscan_grouped(table_q8: jax.Array, packed_codes: jax.Array, *,
                     impl: str = "ref", tile_n: int = 0,
                     interpret: bool | None = None) -> jax.Array:
    """Grouped ADC for gathered IVF lists: (G, M, 16) u8 x (G, cap, M//2) u8
    -> (G, cap) i32. Group g = one (query, probed-list) pair.

    impl: 'ref' (vectorized jnp gather — fastest off-TPU) | 'select'
    (register-resident Pallas select-tree) | 'mxu' (per-group one-hot GEMM on
    the MXU) | 'stream' (in-kernel DMA of one cap tile per grid step; under
    this gathered signature it scans the codes as a G-list store probed by
    arange — see ``fastscan_stream_grouped`` for the true in-place entry) |
    'auto' (timed micro-sweep picks the (impl, tile_n) pair per
    (backend, interpret, G, cap, M) signature, cached process-wide; an
    explicit ``tile_n`` is ignored under 'auto' since the sweep timed pairs).
    Bit-identical.

    Shapes are static under jit, so 'auto' resolves at trace time: the sweep
    runs once per signature and the chosen concrete impl is what gets staged
    into the XLA program.
    """
    g, m, k = table_q8.shape
    cap = packed_codes.shape[1]
    assert k == 16, f"4-bit PQ requires K=16, got {k}"
    if impl not in SCAN_IMPLS:
        raise ValueError(f"unknown grouped impl {impl!r}; "
                         f"want one of {SCAN_IMPLS}")
    if impl == "auto":
        tuned = resolve_grouped_impl(g, cap, m, interpret=interpret)
        # the sweep timed (impl, tile) PAIRS — honoring a caller tile_n here
        # could pair the winning impl with a tile it never won with, so an
        # explicit tile_n is ignored under 'auto' (pass a concrete impl to
        # control tiling by hand)
        impl, tile_n = tuned.impl, tuned.tile_n
    if impl == "ref":
        return _fastscan_grouped_ref_jit(table_q8, packed_codes)
    if impl == "stream":
        return _fastscan_grouped_stream(table_q8, packed_codes, tile_n=tile_n,
                                        interpret=interpret)
    return _fastscan_grouped_pallas(table_q8, packed_codes, impl=impl,
                                    tile_n=tile_n, interpret=interpret)


_fastscan_grouped_ref_jit = jax.jit(ref_mod.fastscan_grouped_ref)


def resolve_scan_impl(impl: str, g: int, cap: int, m: int, *,
                      nlist: int | None = None,
                      interpret: bool | None = None,
                      probe_fill: float = 1.0) -> tuple[str, int]:
    """Resolve a requested scan impl to a concrete ``(impl, tile_n)``.

    Concrete impls pass through with tile 0 (shape-fit default); ``'auto'``
    consults the autotune table (``resolve_grouped_impl``) — which may pick
    ``'stream'``, letting callers that hold the codes in place
    (``core.ivf.scan_probes``) route to the gather-free path; such callers
    pass their store's ``nlist`` so the stream candidate is timed against
    the strides it will really see. ``probe_fill`` is the expected fraction
    of *valid* probe slots: under adaptive pruning (docs/anytime.md) a
    margin policy leaves many ``-1`` slots whose DMA the stream kernel
    skips outright, so a sweep timed on dense probes would overstate the
    stream cost — the sweep masks ``1 - probe_fill`` of its probes and the
    verdict is keyed by the fill. Shared by the single-host and sharded
    pipelines so dispatch cannot drift.
    """
    if impl not in SCAN_IMPLS:
        raise ValueError(f"unknown grouped impl {impl!r}; "
                         f"want one of {SCAN_IMPLS}")
    if impl != "auto":
        return impl, 0
    tuned = resolve_grouped_impl(g, cap, m, nlist=nlist, interpret=interpret,
                                 probe_fill=probe_fill)
    return tuned.impl, tuned.tile_n


def resolve_rerank_dispatch(impl: str, q: int, r: int, d: int, k: int,
                            n: int, *,
                            interpret: bool | None = None) -> tuple[str, int]:
    """Resolve a requested re-rank impl to a concrete ``(impl, tile_r)``.

    The re-rank twin of ``resolve_scan_impl``: concrete impls pass through
    with tile 0 (shape-fit default), ``'auto'`` consults the autotune table
    (``resolve_rerank_impl``). Shared by ``rerank.finalize_candidates`` on
    the single-host and sharded pipelines.
    """
    if impl not in RERANK_IMPLS:
        raise ValueError(f"unknown rerank impl {impl!r}; "
                         f"want one of {RERANK_IMPLS}")
    if impl != "auto":
        return impl, 0
    tuned = resolve_rerank_impl(q, r, d, k, n, interpret=interpret)
    return tuned.impl, tuned.tile_n


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def fastscan_stream_grouped(table_q8: jax.Array, list_codes: jax.Array,
                            probe_ids: jax.Array, *, tile_n: int = 0,
                            interpret: bool | None = None) -> jax.Array:
    """Gather-free grouped ADC over an in-place ListStore.

    table_q8: (G, M, 16) u8 per-group LUTs; list_codes: (nlist, cap, M//2)
    u8 — ``ListStore.codes``, scanned where it lives (no gathered copy);
    probe_ids: (G,) i32, -1 = no probe (DMA skipped, zeros emitted).
    Returns (G, cap) i32, identical at every real slot to
    ``fastscan_grouped(table, list_codes[probe_ids])``.
    """
    g, m, k = table_q8.shape
    cap = list_codes.shape[1]
    assert k == 16, f"4-bit PQ requires K=16, got {k}"
    assert probe_ids.shape == (g,), (probe_ids.shape, g)
    interp = _default_interpret() if interpret is None else interpret
    tn = _stream_tile(cap, tile_n)
    return fk.fastscan_stream_grouped(table_q8, list_codes,
                                      probe_ids.astype(jnp.int32),
                                      tile_n=tn, interpret=interp)


@functools.partial(jax.jit,
                   static_argnames=("keep", "tile_n", "interpret",
                                    "early_exit", "groups_per_query"))
def fastscan_stream_topk(table_q8: jax.Array, list_codes: jax.Array,
                         probe_ids: jax.Array, sizes: jax.Array, *,
                         keep: int, tile_n: int = 0,
                         filter_bits: jax.Array | None = None,
                         interpret: bool | None = None,
                         early_exit: bool = False,
                         groups_per_query: int = 0,
                         scales: jax.Array | None = None,
                         biases: jax.Array | None = None
                         ) -> tuple[jax.Array, ...]:
    """Gather-free scan + fused candidate reduction over an in-place store.

    Like ``fastscan_stream_grouped`` but the full (G, cap) accumulation
    never reaches HBM: each cap tile keeps only its ``kc = min(keep,
    tile_n)`` smallest entries, so any final selection of <= ``keep``
    candidates per query is exact (see the kernel docstring for the
    tie-break argument). ``sizes`` (nlist,) i32 masks slots past each
    list's true occupancy before selection, and a cap tile that starts at
    or past it (``fastscan_kernel.live_tiles``) costs no copy and no
    scan: it emits the all-absent row an invalid probe emits.
    ``filter_bits`` — optional (nlist, W) u8 packed filter bitmap
    (``core.lists.pack_filter_mask`` layout) — masks rows whose bit is 0
    through the same pre-selection path; only the probed groups' rows (a
    (G, W) u8 gather, ~1.5% of the code bytes at M=16) ever reach the
    kernel. Returns
    (vals (G, n_tiles, kc) i32, slots (G, n_tiles, kc) i32, -1 = absent —
    padding, filtered-out, or invalid probe).

    With ``early_exit`` (plus ``groups_per_query`` > 0 dividing G and the
    per-group dequantization affine ``scales``/``biases``, both (G,) f32)
    the kernel additionally prunes tiles whose lower bound can't beat the
    query's running kc-th best, and a third ``skipped`` (G, n_tiles) i32
    array is returned (docs/anytime.md). Pruning is only armed when the
    per-tile candidate width covers the full selection (``kc == keep``,
    i.e. ``keep <= tile_n``) — otherwise the running kc-th best would be
    tighter than the keep-th best the caller selects and the skip would
    stop being lossless, so the kernel silently falls back to the unpruned
    path (``skipped`` all zeros).
    """
    g, m, k = table_q8.shape
    cap = list_codes.shape[1]
    assert k == 16, f"4-bit PQ requires K=16, got {k}"
    assert probe_ids.shape == (g,), (probe_ids.shape, g)
    interp = _default_interpret() if interpret is None else interpret
    tn = _stream_tile(cap, tile_n)
    kc = max(1, min(keep, tn))
    probes = probe_ids.astype(jnp.int32)
    fb = None
    if filter_bits is not None:
        assert filter_bits.shape[0] == list_codes.shape[0], (
            filter_bits.shape, list_codes.shape)
        # pre-gather each group's bitmap row; invalid probes (-1) clamp to
        # row 0 but their whole group is skipped inside the kernel anyway
        fb = filter_bits.astype(jnp.uint8)[jnp.maximum(probes, 0)]
    if early_exit:
        assert scales is not None and biases is not None, (
            "early_exit requires the per-group dequantization affine")
        if kc == keep and groups_per_query > 0 and g % groups_per_query == 0:
            vals, slots, skipped = fk.fastscan_stream_topk_grouped(
                table_q8, list_codes, probes, sizes.astype(jnp.int32), kc=kc,
                tile_n=tn, filter_bits=fb, interpret=interp, early_exit=True,
                groups_per_query=groups_per_query, scales=scales,
                biases=biases)
            return vals, slots, skipped
        vals, slots = fk.fastscan_stream_topk_grouped(
            table_q8, list_codes, probes, sizes.astype(jnp.int32), kc=kc,
            tile_n=tn, filter_bits=fb, interpret=interp)
        return vals, slots, jnp.zeros(vals.shape[:2], jnp.int32)
    return fk.fastscan_stream_topk_grouped(
        table_q8, list_codes, probes, sizes.astype(jnp.int32), kc=kc,
        tile_n=tn, filter_bits=fb, interpret=interp)


def _rerank_tile(r: int, tile_r: int = 0) -> int:
    """Candidate-chunk size for the stream re-rank: honor an explicit
    ``tile_r``, else the smallest power-of-two >= min(r, TILE_R) (floor 8) —
    candidate ids are padded with -1, so any tile is realizable."""
    if tile_r:
        return tile_r
    return max(8, min(rk.TILE_R, 1 << max(r - 1, 1).bit_length()))


@functools.partial(jax.jit, static_argnames=("k", "tile_r", "interpret"))
def rerank_stream_topk(base: jax.Array, q: jax.Array, cand_ids: jax.Array,
                       *, k: int, tile_r: int = 0,
                       interpret: bool | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """Gather-free exact re-rank over the in-place base (stage 3 hot path).

    base (N, D) f32 stays in HBM — the kernel DMAs only each query's
    candidate rows; q (Q, D) f32; cand_ids (Q, R) i32, -1 = padding.
    Returns (vals (Q, k) f32 ascending, ids (Q, k) i32, -1 = absent), the
    same as ``engine.rerank.exact_rerank`` (same distance expression, same
    ``masked_topk`` tie-breaks — see kernels/rerank_kernel.py).
    """
    qq, r = cand_ids.shape
    interp = _default_interpret() if interpret is None else interpret
    tr = _rerank_tile(r, tile_r)
    cand_p = _pad_to(cand_ids.astype(jnp.int32), 1, tr, value=-1)
    vals, pos = rk.rerank_stream_topk(base, q, cand_p, k=k, tile_r=tr,
                                      interpret=interp)
    # pos follows masked_topk's position contract, so the shared sentinel-
    # preserving mapper applies as-is
    return vals, topk_mod.gather_ids(cand_p, pos)


class TunedScan(NamedTuple):
    """Autotune verdict for one scan/re-rank shape signature."""

    impl: str          # winning concrete impl (GROUPED_IMPLS / RERANK_CONCRETE)
    tile_n: int        # winning tile (0 = impl has no tiling knob)
    timings_us: tuple  # ((f"{impl}@{tile}", median_us), ...) — full sweep


_AUTOTUNE_CACHE: dict[tuple, TunedScan] = {}
# serializes first resolutions: without it, two threads racing on the same
# signature would pay the sweep twice and could cache divergent verdicts
_AUTOTUNE_LOCK = threading.Lock()


def _grouped_tile_candidates(cap: int) -> tuple[int, ...]:
    """Cap-tile sizes worth timing: the shape-fit auto tile plus smaller
    power-of-two tiles (more grid parallelism / smaller VMEM blocks)."""
    fit = _auto_tile(cap, fk.TILE_N)
    cands = {fit}
    for t in (128, 512):
        if t < fit:
            cands.add(t)
    return tuple(sorted(cands))


# Scoped VMEM a Pallas TPU kernel may use by default (16 MiB on v5e). The
# autotune sweeps leave out tiles whose estimated working set exceeds it.
_VMEM_BUDGET = 16 * 2**20


def _lanes(n: int) -> int:
    """A minor dim as VMEM lays it out: padded to whole 128-lane rows."""
    return -(-n // 128) * 128


def _scan_vmem_bytes(impl: str, tile_n: int, m: int) -> int:
    """Largest live VMEM of one grouped-scan grid step (0 for 'ref').

    Two buffered (tile_n, M//2) u8 code tiles, plus the i32 values the
    formulation keeps live: 'mxu' its (tile_n, 16*M) repeated nibbles and
    bf16 one-hot, the select-tree impls their two nibble planes and up to
    16 (tile_n, M//2) tree temporaries.
    """
    if impl == "ref":
        return 0
    codes = 2 * tile_n * _lanes(m // 2)
    if impl == "mxu":
        return codes + tile_n * _lanes(16 * m) * (4 + 2)
    return codes + 18 * tile_n * _lanes(m // 2) * 4


def _rerank_vmem_bytes(tile_r: int, d: int) -> int:
    """Stream re-rank scratch: two (tile_r, D) f32 row slots plus the
    chunk's (q - x)² values."""
    return 3 * tile_r * _lanes(d) * 4


def _median_time_us(fn, iters: int = 3) -> float:
    out = fn()  # warmup: compile (or first interpret pass)
    if isinstance(out, jax.core.Tracer):
        # Staged into an ambient trace instead of executed — the "timing"
        # would measure tracing overhead, not the kernel. resolve_grouped_impl
        # escapes to a worker thread precisely to prevent this.
        raise RuntimeError("autotune sweep ran under an ambient jax trace")
    jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def _resolve_cached(sig: tuple, sweep_fn, *args) -> TunedScan:
    """Shared resolve-or-sweep path for the scan and re-rank autotuners.

    One sweep per signature per process. The sweep must EXECUTE even when
    resolution happens at trace time (scan_probes, finalize_candidates and
    the fused pipeline are jit'd, so that is the normal case): under an
    ambient trace every jax call made here would be staged into the
    caller's jaxpr instead of run, and the "timings" would measure tracing
    overhead. JAX trace state is thread-local, so a worker thread is a
    clean escape hatch — everything it runs dispatches eagerly on concrete
    arrays.
    """
    hit = _AUTOTUNE_CACHE.get(sig)
    if hit is not None:
        return hit
    with _AUTOTUNE_LOCK:
        hit = _AUTOTUNE_CACHE.get(sig)  # racing thread may have resolved it
        if hit is not None:
            return hit
        with concurrent.futures.ThreadPoolExecutor(max_workers=1) as ex:
            tuned = ex.submit(sweep_fn, *args).result()
        _AUTOTUNE_CACHE[sig] = tuned
    return tuned


def resolve_grouped_impl(g: int, cap: int, m: int, *, nlist: int | None = None,
                         interpret: bool | None = None,
                         probe_fill: float = 1.0) -> TunedScan:
    """Resolve ``impl='auto'`` for the grouped scan at one shape signature.

    Times every concrete impl (x its tile candidates) on synthetic data of
    the exact workload shape and caches the winner per
    ``('scan', backend, interpret, G, cap, M, nlist, probe_fill)`` — one
    sweep per signature per process (interpret mode is part of the key: a
    verdict timed on the Pallas interpreter must never be reused for
    compiled execution, or vice versa). ``nlist`` is the size of the
    in-place ListStore the 'stream' candidate would scan: the sweep times
    it against a store of that many lists with random probes, so the
    verdict reflects real list-store strides rather than the arange-probed
    G-list stand-in (``nlist=None`` keeps the gathered calling convention's
    G-list store — what ``fastscan_grouped`` itself executes).
    ``probe_fill`` in (0, 1] is the expected valid-probe fraction: the
    sweep masks ``1 - probe_fill`` of its probes to ``-1`` (evenly across
    the sweep's queries), the workload an adaptive-nprobe policy actually
    presents — the stream kernel skips those groups' DMAs while the
    gathered impls still pay full freight, so a dense-probe sweep would
    overstate the stream advantage's denominator. The fixed-seed synthetic
    data makes the sweep reproducible; the cache makes resolution
    deterministic for the life of the process (asserted in
    tests/test_kernels.py). Tiles whose working set would not fit the
    kernel VMEM budget (``_scan_vmem_bytes``) are left out of the sweep up
    front; any other failure of a candidate raises — a kernel
    that does not build on this backend is a fault to see, not a verdict.
    """
    interp = _default_interpret() if interpret is None else interpret
    nl = int(g if nlist is None else nlist)
    fill = round(float(probe_fill), 4)
    if not 0.0 < fill <= 1.0:
        raise ValueError(f"probe_fill must be in (0, 1], got {probe_fill}")
    sig = ("scan", jax.default_backend(), interp, int(g), int(cap), int(m),
           nl, fill)
    return _resolve_cached(sig, _run_grouped_sweep, int(g), int(cap), int(m),
                           nl, fill, interp)


def _run_grouped_sweep(g: int, cap: int, m: int, nlist: int, fill: float,
                       interp: bool) -> TunedScan:
    rng = np.random.default_rng(0)
    # plain numpy on purpose: jnp.asarray under an ambient trace would make
    # these tracers; as numpy they only become device arrays inside the
    # worker thread's eager calls
    table = rng.integers(0, 256, (g, m, 16), dtype=np.uint8)
    codes = rng.integers(0, 256, (g, cap, m // 2), dtype=np.uint8)
    # the stream impl's real operand: an nlist-sized in-place store with
    # random probes — the strides scan_probes actually drives it with
    store = rng.integers(0, 256, (nlist, cap, m // 2), dtype=np.uint8)
    probes = rng.integers(0, nlist, (g,), dtype=np.int32)
    if fill < 1.0:
        # representative adaptive-probe mix: prune a deterministic
        # 1-fill fraction of slots to the -1 sentinel, spread evenly so
        # the stream kernel's skip pattern matches a margin policy's
        # (some groups per query dropped) rather than a dead prefix
        n_prune = min(g - 1, int(round(g * (1.0 - fill))))
        if n_prune > 0:
            pruned_idx = np.linspace(0, g - 1, n_prune).astype(np.int64)
            probes[pruned_idx] = -1
    sweep = []
    for impl in GROUPED_IMPLS:
        if impl == "ref":
            tiles = (0,)
        elif impl == "stream":
            # stream scans the store in place, so only cap-dividing tiles
            # are realizable — map each candidate to its realizable tile so
            # the verdict's (impl, tile) pair is exactly what executes
            tiles = tuple(sorted({_stream_tile(cap, t)
                                  for t in _grouped_tile_candidates(cap)}))
        else:
            tiles = _grouped_tile_candidates(cap)
        for tn in tiles:
            if _scan_vmem_bytes(impl, tn, m) > _VMEM_BUDGET:
                continue
            if impl == "stream":
                fn = functools.partial(fastscan_stream_grouped, table, store,
                                       probes, tile_n=tn, interpret=interp)
            else:
                fn = functools.partial(fastscan_grouped, table, codes,
                                       impl=impl, tile_n=tn, interpret=interp)
            sweep.append((impl, tn, _median_time_us(fn)))
    best = min(sweep, key=lambda r: r[2])
    tuned = TunedScan(
        impl=best[0], tile_n=best[1],
        timings_us=tuple((f"{i}@{tn}", us) for i, tn, us in sweep))
    return tuned


# Default cap on the synthetic base built for the re-rank sweep. The real N
# stays in the verdict KEY (two engines with identical (Q, R, D, k) but
# different base sizes must never share a verdict), but building a
# multi-million-row synthetic copy would cost more than the sweep measures,
# so beyond the cap the timing runs on a 64k-row stand-in. What actually
# varies with N for fixed R is row-gather cache locality, and at 64k x 128
# f32 (~32 MB) the stand-in already misses on-chip caches like a large table
# does. Real-TPU deployments that want the sweep to touch genuine multi-
# million-row strides raise the cap via the REPRO_RERANK_SWEEP_N_CAP env var
# or the ``sweep_n_cap`` kwarg (docs/kernels.md).
_RERANK_SWEEP_N_CAP = 65536


def _rerank_sweep_n_cap() -> int:
    """Effective sweep cap: ``REPRO_RERANK_SWEEP_N_CAP`` env override (>= 1)
    falling back to ``_RERANK_SWEEP_N_CAP``. Read at resolve time, so tests
    and long-lived servers can retarget without a restart."""
    raw = os.environ.get("REPRO_RERANK_SWEEP_N_CAP", "")
    try:
        cap = int(raw)
    except ValueError:
        return _RERANK_SWEEP_N_CAP
    return cap if cap >= 1 else _RERANK_SWEEP_N_CAP


def resolve_rerank_impl(q: int, r: int, d: int, k: int, n: int, *,
                        interpret: bool | None = None,
                        sweep_n_cap: int | None = None) -> TunedScan:
    """Resolve ``rerank_impl='auto'`` at one (Q, R, D, k, N) re-rank
    signature (N = base-row count).

    Times the gathered fallback against the streaming kernel
    (x its chunk-tile candidates) on synthetic data of the workload shape
    (base rows capped at ``sweep_n_cap``, defaulting to the
    ``REPRO_RERANK_SWEEP_N_CAP`` env var then ``_RERANK_SWEEP_N_CAP``) and
    caches the verdict per ``('rerank', backend, interpret, Q, R, D, k, N)``
    in the same process-wide table (and the same persisted JSON) as the
    scan verdicts. The cap shapes only the synthetic stand-in's size, never
    the key, so re-resolving with a bigger cap requires clearing the cached
    verdict first (``clear_autotune_cache(n=...)``). Both candidates are
    bit-identical, so the verdict is purely a performance choice. Chunk
    tiles whose scratch would not fit the VMEM budget are left out up
    front; any other failure of a candidate raises.
    """
    interp = _default_interpret() if interpret is None else interpret
    cap = _rerank_sweep_n_cap() if sweep_n_cap is None else max(1, int(sweep_n_cap))
    sig = ("rerank", jax.default_backend(), interp, int(q), int(r), int(d),
           int(k), int(n))
    return _resolve_cached(sig, _run_rerank_sweep, int(q), int(r), int(d),
                           int(k), int(n), cap, interp)


def _rerank_tile_candidates(r: int) -> tuple[int, ...]:
    """Chunk sizes worth timing: the shape-fit default plus smaller
    power-of-two chunks (more DMA overlap, smaller scratch)."""
    fit = _rerank_tile(r)
    return tuple(sorted({fit} | {t for t in (16, 32) if t < fit}))


def _run_rerank_sweep(q: int, r: int, d: int, k: int, n: int, n_cap: int,
                      interp: bool) -> TunedScan:
    from repro.engine import rerank as rerank_mod  # lazy: engine -> ops

    rng = np.random.default_rng(0)
    n_sweep = max(r, min(n, n_cap))
    base = rng.standard_normal((n_sweep, d), dtype=np.float32)
    queries = rng.standard_normal((q, d), dtype=np.float32)
    cand = rng.integers(0, n_sweep, (q, r), dtype=np.int32)
    sweep = []
    for impl in RERANK_CONCRETE:
        tiles = (0,) if impl == "gathered" else _rerank_tile_candidates(r)
        for tr in tiles:
            if impl == "gathered":
                fn = functools.partial(rerank_mod.exact_rerank, base, queries,
                                       cand, k)
            elif _rerank_vmem_bytes(tr, d) > _VMEM_BUDGET:
                continue
            else:
                fn = functools.partial(rerank_stream_topk, base, queries,
                                       cand, k=k, tile_r=tr,
                                       interpret=interp)
            sweep.append((impl, tr, _median_time_us(fn)))
    best = min(sweep, key=lambda rec: rec[2])
    return TunedScan(
        impl=best[0], tile_n=best[1],
        timings_us=tuple((f"{i}@{tn}", us) for i, tn, us in sweep))


def autotune_cache() -> dict[tuple, TunedScan]:
    """Snapshot of the process-wide autotune cache, keyed by
    ('scan', backend, interpret, G, cap, M, nlist, probe_fill) and
    ('rerank', backend, interpret, Q, R, D, k, N). For inspection/metrics —
    mutations don't stick."""
    return dict(_AUTOTUNE_CACHE)


def autotune_cache_size() -> int:
    """Number of resolved signatures (mirrors ``engine.fused_cache_size``)."""
    return len(_AUTOTUNE_CACHE)


def clear_autotune_cache(kind: str | None = None, *, nlist: int | None = None,
                         cap: int | None = None, n: int | None = None) -> int:
    """Drop resolved verdicts; with no arguments, all of them.

    Selective form (the mutation path, docs/mutability.md): ``kind``
    restricts to 'scan' or 'rerank' keys; ``nlist``/``cap`` match scan keys
    on the ListStore dimensions a compaction can change; ``n`` matches
    rerank keys on the base-row count an upsert can grow. The mutable
    engine calls this when an epoch swap retires a shape signature, so the
    retired epoch's verdicts can neither serve a lookup (the new shape
    re-keys anyway) nor be re-persisted by ``save_autotune_cache`` into a
    warmup file that outlives them. Returns the number of entries dropped.

    ``nlist``/``cap`` only ever match scan keys and ``n`` only rerank keys,
    so e.g. ``clear_autotune_cache(cap=1024)`` leaves every rerank verdict
    alone without needing ``kind='scan'`` spelled out.
    """
    with _AUTOTUNE_LOCK:
        if kind is None and nlist is None and cap is None and n is None:
            dropped = len(_AUTOTUNE_CACHE)
            _AUTOTUNE_CACHE.clear()
            return dropped
        doomed = []
        for key in _AUTOTUNE_CACHE:
            if kind is not None and key[0] != kind:
                continue
            if key[0] == "scan":
                # ('scan', backend, interpret, G, cap, M, nlist, probe_fill)
                if n is not None:
                    continue
                if nlist is not None and key[6] != nlist:
                    continue
                if cap is not None and key[4] != cap:
                    continue
            else:
                # ('rerank', backend, interpret, Q, R, D, k, N)
                if nlist is not None or cap is not None:
                    continue
                if n is not None and key[7] != n:
                    continue
            doomed.append(key)
        for key in doomed:
            del _AUTOTUNE_CACHE[key]
        return len(doomed)


_AUTOTUNE_SCHEMA = "repro.autotune/v3"
_AUTOTUNE_SCHEMA_V2 = "repro.autotune/v2"
_AUTOTUNE_SCHEMA_V1 = "repro.autotune/v1"


def save_autotune_cache(path: str) -> int:
    """Serialize the resolved TunedScan table to JSON at ``path``.

    Returns the number of entries written. Schema v3: each entry carries a
    ``kind`` ('scan' | 'rerank') plus its kind's full key dims (scan:
    backend/interpret/g/cap/m/nlist/probe_fill; rerank:
    backend/interpret/q/r/d/k/n), so one file can hold both stages'
    verdicts for several backends; ``load_autotune_cache`` re-keys them
    verbatim and lookups still only ever hit the running backend's
    signatures. A serving fleet saves after its first warmup and ships the
    file to every replica (``ServingLoop(warmup_cache=...)``).
    """
    with _AUTOTUNE_LOCK:  # a concurrent sweep may be inserting its verdict
        snapshot = dict(_AUTOTUNE_CACHE)
    entries = []
    for key, t in snapshot.items():
        timings = [[name, us] for name, us in t.timings_us]
        if key[0] == "scan":
            _, b, i, g, c, m, nl, fill = key
            entries.append({"kind": "scan", "backend": b, "interpret": bool(i),
                            "g": g, "cap": c, "m": m, "nlist": nl,
                            "probe_fill": fill,
                            "impl": t.impl, "tile_n": t.tile_n,
                            "timings_us": timings})
        else:
            _, b, i, q, r, d, k, n = key
            entries.append({"kind": "rerank", "backend": b,
                            "interpret": bool(i), "q": q, "r": r, "d": d,
                            "k": k, "n": n, "impl": t.impl,
                            "tile_n": t.tile_n, "timings_us": timings})
    with open(path, "w") as f:
        json.dump({"schema": _AUTOTUNE_SCHEMA, "entries": entries}, f,
                  indent=2)
    return len(entries)


def load_autotune_cache(path: str) -> int:
    """Merge a ``save_autotune_cache`` file into the process-wide table.

    Returns the number of entries adopted. Missing file, wrong schema, or
    malformed JSON load nothing (0) — a stale or absent warmup cache must
    never stop a boot, it just means the sweeps run again. Older schemas
    migrate gracefully: v1 files (no ``kind``, no ``nlist``) re-key their
    scan verdicts to ``nlist=g`` — the arange-probed G-list store that
    sweep actually timed — and both v1 and v2 files (no ``probe_fill``)
    re-key to ``probe_fill=1.0``, the dense-probe sweep they ran, so they
    only ever satisfy lookups for the workloads they measured. Entries
    naming an impl that no longer exists are skipped (stale file from an
    older build); entries already resolved in this process keep their
    in-process verdict.
    """
    if not os.path.exists(path):
        return 0
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        return 0
    if not isinstance(data, dict) or data.get("schema") not in (
            _AUTOTUNE_SCHEMA, _AUTOTUNE_SCHEMA_V2, _AUTOTUNE_SCHEMA_V1):
        return 0
    loaded = 0
    with _AUTOTUNE_LOCK:
        for e in data.get("entries", ()):
            try:
                kind = str(e.get("kind", "scan"))
                if kind == "scan":
                    g = int(e["g"])
                    key = ("scan", str(e["backend"]), bool(e["interpret"]),
                           g, int(e["cap"]), int(e["m"]),
                           int(e.get("nlist", g)),  # v1: the G-list store
                           round(float(e.get("probe_fill", 1.0)), 4))
                    known = GROUPED_IMPLS
                elif kind == "rerank":
                    key = ("rerank", str(e["backend"]), bool(e["interpret"]),
                           int(e["q"]), int(e["r"]), int(e["d"]),
                           int(e["k"]), int(e["n"]))
                    known = RERANK_CONCRETE
                else:
                    continue
                tuned = TunedScan(
                    impl=str(e["impl"]), tile_n=int(e["tile_n"]),
                    timings_us=tuple((str(n), float(us))
                                     for n, us in e["timings_us"]))
            except (KeyError, TypeError, ValueError):
                continue
            if tuned.impl not in known or key in _AUTOTUNE_CACHE:
                continue
            _AUTOTUNE_CACHE[key] = tuned
            loaded += 1
    return loaded


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fastscan_blockmin(table_q8: jax.Array, packed_codes: jax.Array, *,
                      block: int = 1024, interpret: bool | None = None
                      ) -> tuple[jax.Array, jax.Array]:
    """Fused ADC + per-block min/argmin. Pads N with +inf-like sentinel codes.

    Returns (min_dists (Q, ceil(N/block)) i32, global argmin ids).
    Padded tail rows use code 15 in every sub-space; callers who need exact
    semantics on ragged N should mask via the returned ids (< N check).
    """
    if table_q8.ndim == 2:
        table_q8 = table_q8[None]
    q, m, k = table_q8.shape
    n = packed_codes.shape[0]
    assert k == 16
    interp = _default_interpret() if interpret is None else interpret
    tq = _auto_tile(q, fk.TILE_Q)
    table_p = _pad_to(table_q8, 0, tq)
    codes_p = _pad_to(packed_codes, 0, block, value=0xFF)
    mins, args = fk.fastscan_blockmin(table_p, codes_p, tile_n=block, tile_q=tq,
                                      interpret=interp)
    nb = -(-n // block)
    return mins[:q, :nb], args[:q, :nb]
