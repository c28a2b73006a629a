"""Kernel-level benchmark: register-resident LUT vs memory LUT (paper §3).

Wall-clock on this container reflects the Pallas *interpreter* on CPU, so we
report it only as a correctness-path cost. The TPU claim is made with the
roofline model: bytes-per-code of each formulation at the VMEM/HBM boundary,
which is the structural content of the paper's 10x (in-register shuffle
eliminates the per-code random LUT load).

  naive PQ (K=256, u8 codes, f32 LUT in HBM/L2): per code-subspace lookup
    reads 1 code byte + one 4 B random table entry -> gather-bound.
  4-bit fast-scan (K=16, u8 LUT in VMEM/registers): per code-subspace 0.5
    byte of codes streams through; the LUT never leaves the register file.
"""
from __future__ import annotations

import functools
import json
import os

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks import common
from repro.core import ivf
from repro.core.lists import ListStore, pack_filter_mask
from repro.core.pq import PQCodebook
from repro.engine import rerank as rerank_mod
from repro.kernels import ops, ref
from repro.launch import roofline as rl
from repro.launch.hlo_analysis import xla_cost_dict

# machine-readable grouped-kernel sweep artifact (CI uploads it; the perf
# trajectory across PRs reads it). Override the path with REPRO_BENCH_KERNELS.
KERNELS_JSON = os.environ.get("REPRO_BENCH_KERNELS", "BENCH_kernels.json")


def roofline_model(m: int = 16, n: int = 10**6, q: int = 1) -> dict:
    """Analytic time-per-query on a v5e chip for both formulations."""
    # naive PQ: N*M random gathers of 4 B each (table too big for registers;
    # scalar pipeline ~1 lookup/cycle/core analogue: we charge HBM latency-
    # amortized random access at cacheline granularity / 8 useful bytes)
    naive_bytes = n * m * (1 + 4)          # code byte + table entry
    # fast-scan: codes stream 0.5 B/subspace; LUT resident; accum in-reg
    fast_bytes = n * m * 0.5
    # MXU formulation: onehot(codes) @ LUT = N * (M*16) * Q MACs
    mxu_flops = 2 * n * m * 16 * q
    return {
        "naive_t": naive_bytes / rl.HBM_BW,
        "fast_t": max(fast_bytes / rl.HBM_BW, mxu_flops / rl.PEAK_FLOPS / 8),
        "mxu_t": max(fast_bytes / rl.HBM_BW, mxu_flops / rl.PEAK_FLOPS),
    }


def grouped_sweep(m: int = 16) -> list[dict]:
    """Time every grouped impl (incl. the autotuned dispatch) over (G, cap)
    points of the IVF hot path: G = Q*nprobe gathered lists of capacity cap.

    Returns one record per (shape, impl) for BENCH_kernels.json; each record
    carries cost_analysis ``bytes_accessed`` alongside wall time so the perf
    trajectory tracks HBM traffic, not just clock.
    """
    rng = np.random.default_rng(0)
    points = ([(8, 128), (32, 256), (8, 1024)] if common.SMOKE else
              [(8, 256), (64, 256), (8, 1024), (256, 512)])
    records = []
    for g, cap in points:
        table = jnp.asarray(rng.integers(0, 256, (g, m, 16), np.uint8))
        codes = jnp.asarray(rng.integers(0, 256, (g, cap, m // 2), np.uint8))
        for impl in ops.SCAN_IMPLS:  # ref / select / mxu / stream / auto
            t = common.time_call(ops.fastscan_grouped, table, codes, impl=impl)
            cost = xla_cost_dict(jax.jit(functools.partial(
                ops.fastscan_grouped, impl=impl)).lower(table, codes).compile())
            rec = {"kernel": "fastscan_grouped", "impl": impl, "G": g,
                   "cap": cap, "M": m, "us_per_call": t * 1e6,
                   "bytes_accessed": cost.get("bytes accessed", 0.0),
                   "backend": jax.default_backend()}
            if impl == "auto":
                tuned = ops.resolve_grouped_impl(g, cap, m)
                rec["resolved"] = {"impl": tuned.impl, "tile_n": tuned.tile_n}
            records.append(rec)
            common.emit(f"kernel_grouped_{impl}_G{g}_cap{cap}_M{m}", t,
                        "grouped IVF-hot-path scan (interpret mode off-TPU)")
    return records


def scan_stage_traffic(q: int = 32, p: int = 16, cap: int = 1024,
                       m: int = 16, nlist: int = 64) -> list[dict]:
    """HBM bytes-accessed of the whole scan STAGE, gathered vs gather-free.

    The gathered path is ``core.ivf.scan_probes(impl='ref')``: gather the
    probed lists, scan, write the full (Q, P, cap) distances + ids back.
    The streamed path is ``scan_probes_stream``: in-kernel list DMA + fused
    per-tile reduction — only (Q, P, n_tiles, kc) candidates return to HBM.
    Both are *compiled only* (cost_analysis needs no execution), so this
    runs at the acceptance shape (Q=32, P=16, cap=1024, M=16) even in the
    CI smoke job. ``keep=40`` is the serving default's selection budget
    (rerank_mult=4 x k=10).
    """
    rng = np.random.default_rng(0)
    d = 32
    codes = rng.integers(0, 256, (nlist, cap, m // 2), np.uint8)
    ids = np.arange(nlist * cap, dtype=np.int32).reshape(nlist, cap)
    index = ivf.IVFIndex(
        centroids=jnp.asarray(rng.normal(size=(nlist, d)).astype(np.float32)),
        codebook=PQCodebook(jnp.asarray(
            rng.normal(size=(m, 16, d // m)).astype(np.float32))),
        lists=ListStore(codes=jnp.asarray(codes), ids=jnp.asarray(ids),
                        sizes=jnp.asarray(np.full(nlist, cap, np.int32))),
    )
    qs = jnp.asarray(rng.normal(size=(q, d)).astype(np.float32))
    probes = jnp.asarray(rng.integers(0, nlist, (q, p)).astype(np.int32))
    # all-ones (100% selectivity) filter bitmap: measures the pure bitmap
    # overhead of the filtered stream scan — docs/filtering.md promises it
    # within 10% of the unfiltered stream record
    fbits = pack_filter_mask(index.lists.ids >= 0)
    stages = (
        ("gathered", jax.jit(
            lambda i, qq, pr: ivf.scan_probes(i, qq, pr, impl="ref")),
         (index, qs, probes)),
        ("stream", jax.jit(functools.partial(ivf.scan_probes_stream,
                                             keep=40)),
         (index, qs, probes)),
        ("stream_filtered", jax.jit(
            lambda i, qq, pr, fb: ivf.scan_probes_stream(
                i, qq, pr, keep=40, filter_bits=fb)),
         (index, qs, probes, fbits)),
    )
    records = []
    for name, fn, args in stages:
        cost = xla_cost_dict(fn.lower(*args).compile())
        rec = {"kernel": "scan_stage", "impl": name, "Q": q, "P": p,
               "cap": cap, "M": m, "nlist": nlist,
               "bytes_accessed": cost.get("bytes accessed", 0.0),
               "backend": jax.default_backend()}
        records.append(rec)
        common.emit(f"scan_stage_bytes_{name}", 0.0,
                    f"bytes_accessed={rec['bytes_accessed']:.0f}")
    if records[1]["bytes_accessed"]:
        ratio = records[0]["bytes_accessed"] / records[1]["bytes_accessed"]
        common.emit("scan_stage_traffic_ratio", 0.0,
                    f"gathered/stream={ratio:.1f}x (acceptance: >= 4x)")
        overhead = (records[2]["bytes_accessed"]
                    / records[1]["bytes_accessed"] - 1.0)
        common.emit("scan_stage_filter_overhead", 0.0,
                    f"filtered/unfiltered-1={overhead:+.1%} "
                    "(acceptance: within 10%)")
    return records


def anytime_scan_traffic(q: int = 32, p: int = 16, cap: int = 1024,
                         m: int = 16, nlist: int = 64, tile: int = 256,
                         tau: float = 2.0) -> list[dict]:
    """Scan-stage traffic under the anytime policy on a margin-skewed mix.

    XLA's static cost model cannot see data-dependent work, so this record
    models the stream scan's dominant HBM term directly from the kernel's
    own counters: codes-DMA bytes = tiles actually scanned x ``tile x M/2``
    plus one (M, 16) LUT per valid group. The "margin-skewed mix" is the
    regime docs/anytime.md targets — clustered data, every query near one
    centroid — so the coarse margins are real: the margin policy drops
    whole probes (their groups' DMAs never issue) and the early-exit bound
    skips surviving far groups' tiles in-kernel. Recall is matched by
    construction *and checked*: the adaptive pool's final top-10 against
    the fixed-nprobe pool's (acceptance: recall@10 >= 0.99 with >= 25%
    fewer modeled bytes).
    """
    from repro.core.topk import (gather_ids, margin_prune_probes, masked_topk,
                                 smallest_k)

    rng = np.random.default_rng(0)
    d = 32
    # well-separated centroids + near-centroid queries = real coarse margins
    centroids = rng.normal(size=(nlist, d)).astype(np.float32) * 4.0
    codes = rng.integers(0, 256, (nlist, cap, m // 2), np.uint8)
    ids = np.arange(nlist * cap, dtype=np.int32).reshape(nlist, cap)
    index = ivf.IVFIndex(
        centroids=jnp.asarray(centroids),
        codebook=PQCodebook(jnp.asarray(
            rng.normal(size=(m, 16, d // m)).astype(np.float32))),
        lists=ListStore(codes=jnp.asarray(codes), ids=jnp.asarray(ids),
                        sizes=jnp.asarray(np.full(nlist, cap, np.int32))),
    )
    # half the queries sit on a centroid (tight margin: the policy prunes
    # all but the home probe), half sit between two clusters (wide margin:
    # both survive the prune and the early-exit bound skips the farther
    # group's tiles in-kernel) — both anytime mechanisms show up in the
    # counters below
    home = rng.integers(0, nlist, q)
    mate = (home + 1) % nlist
    w = np.where(np.arange(q) < q // 2, 0.0, 0.42).astype(np.float32)[:, None]
    qs = jnp.asarray((1.0 - w) * centroids[home] + w * centroids[mate]
                     + 0.3 * rng.normal(size=(q, d)).astype(np.float32))
    cd = jnp.sum((qs[:, None, :] - index.centroids[None]) ** 2, axis=-1)
    cvals, probes = smallest_k(cd, p)
    adp_probes, lists_pruned = margin_prune_probes(cvals, probes, tau)

    keep = 40
    fix_d, fix_i, _, _ = ivf.scan_probes_stream(index, qs, probes,
                                                keep=keep, tile_n=tile)
    adp_d, adp_i, skipped, _ = ivf.scan_probes_stream(
        index, qs, adp_probes, keep=keep, tile_n=tile, early_exit=True)

    def _final10(dd, ii):
        v, pos = masked_topk(dd, ii >= 0, 10)
        return np.asarray(gather_ids(ii, pos))

    want, got = _final10(fix_d, fix_i), _final10(adp_d, adp_i)
    recall10 = float(np.mean([np.isin(got[i], want[i]).mean()
                              for i in range(q)]))

    n_tiles = cap // tile
    group_lut = m * 16                      # (M, 16) u8 LUT per valid group
    tile_bytes = tile * (m // 2)            # packed-codes DMA per tile
    pruned = int(np.asarray(lists_pruned).sum())
    n_skip = int(np.asarray(skipped).sum())
    fixed_bytes = q * p * (n_tiles * tile_bytes + group_lut)
    valid_groups = q * p - pruned
    adp_bytes = (valid_groups * group_lut
                 + (valid_groups * n_tiles - n_skip) * tile_bytes)
    reduction = 1.0 - adp_bytes / fixed_bytes
    base = {"kernel": "anytime_scan", "Q": q, "P": p, "cap": cap, "M": m,
            "nlist": nlist, "tile_n": tile, "modeled": True,
            "backend": jax.default_backend()}
    records = [
        dict(base, impl="fixed", bytes_accessed=float(fixed_bytes)),
        dict(base, impl="adaptive", bytes_accessed=float(adp_bytes),
             margin_tau=tau, lists_pruned=pruned, tiles_skipped=n_skip,
             reduction_pct=reduction * 100.0, recall10_vs_fixed=recall10),
    ]
    common.emit("anytime_scan_bytes_fixed", 0.0,
                f"modeled_bytes={fixed_bytes}")
    common.emit("anytime_scan_bytes_adaptive", 0.0,
                f"modeled_bytes={adp_bytes};lists_pruned={pruned};"
                f"tiles_skipped={n_skip};reduction={reduction:.1%};"
                f"recall10_vs_fixed={recall10:.3f} "
                "(acceptance: >= 25% fewer bytes at matched recall)")
    assert reduction >= 0.25, f"anytime reduction {reduction:.1%} < 25%"
    assert recall10 >= 0.99, f"anytime recall@10 {recall10:.3f} < 0.99"
    return records


def rerank_stage_traffic(q: int = 32, k: int = 10, r: int = 4,
                         d: int = 128, n: int = 4096) -> list[dict]:
    """HBM bytes-accessed of the exact re-rank STAGE, gathered vs stream.

    The gathered path materializes a (Q, R, D) f32 copy of the candidate
    base rows before top-k; the streamed path
    (``ops.rerank_stream_topk``) DMAs only the candidate rows out of the
    in-place base and reduces to (Q, k) in VMEM. Compiled-only
    (cost_analysis needs no execution), so this runs at the acceptance
    shape (Q=32, k=10, r=4, D=128) even in the CI smoke job. The gathered
    number grows with N (XLA charges the row gather against the whole
    table); the stream number does not — the base is an in-place operand
    the kernel only ever touches R rows of.
    """
    rng = np.random.default_rng(0)
    rr = r * k
    base = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    qs = jnp.asarray(rng.normal(size=(q, d)).astype(np.float32))
    cand = jnp.asarray(rng.integers(0, n, (q, rr)).astype(np.int32))
    stages = (
        ("gathered", jax.jit(functools.partial(rerank_mod.exact_rerank, k=k)),
         (base, qs, cand)),
        ("stream", jax.jit(functools.partial(ops.rerank_stream_topk, k=k)),
         (base, qs, cand)),
    )
    records = []
    for name, fn, args in stages:
        cost = xla_cost_dict(fn.lower(*args).compile())
        rec = {"kernel": "rerank_stage", "impl": name, "Q": q, "k": k,
               "r": r, "D": d, "N": n,
               "bytes_accessed": cost.get("bytes accessed", 0.0),
               "backend": jax.default_backend()}
        records.append(rec)
        common.emit(f"rerank_stage_bytes_{name}", 0.0,
                    f"bytes_accessed={rec['bytes_accessed']:.0f}")
    if records[1]["bytes_accessed"]:
        ratio = records[0]["bytes_accessed"] / records[1]["bytes_accessed"]
        common.emit("rerank_stage_traffic_ratio", 0.0,
                    f"gathered/stream={ratio:.1f}x (acceptance: >= 4x)")
    return records


def main() -> None:
    rng = np.random.default_rng(0)
    q_, n_, m_ = 8, 65536, 16
    table = jnp.asarray(rng.integers(0, 256, (q_, m_, 16), np.uint8))
    packed = jnp.asarray(rng.integers(0, 256, (n_, m_ // 2), np.uint8))

    for impl in ("ref", "select", "mxu"):
        t = common.time_call(ops.fastscan_distances, table, packed, impl=impl)
        common.emit(f"kernel_{impl}_Q{q_}_N{n_}_M{m_}", t / q_,
                    "interpret-mode wall clock (CPU correctness path)")

    records = (grouped_sweep() + scan_stage_traffic()
               + anytime_scan_traffic() + rerank_stage_traffic())
    with open(KERNELS_JSON, "w") as f:
        json.dump({"schema": "repro.kernel_bench/v1", "records": records}, f,
                  indent=2)
    common.emit("kernel_grouped_json", 0.0,
                f"wrote {len(records)} records to {KERNELS_JSON}")

    t_min = common.time_call(ops.fastscan_blockmin, table, packed, block=1024)
    common.emit(f"kernel_blockmin_Q{q_}_N{n_}_M{m_}", t_min / q_,
                "fused scan+min (movemask analogue)")

    model = roofline_model(m=m_, n=10**6)
    common.emit("kernel_roofline_naivePQ_1M", model["naive_t"],
                "v5e model: memory-LUT gather path")
    common.emit("kernel_roofline_fastscan_1M", model["fast_t"],
                f"v5e model: register LUT; speedup={model['naive_t']/model['fast_t']:.1f}x")
    common.emit("kernel_roofline_mxu_1M", model["mxu_t"],
                f"v5e model: one-hot MXU; speedup={model['naive_t']/model['mxu_t']:.1f}x")


if __name__ == "__main__":
    main()
