"""Bring-up smoke of the 4-bit fast-scan serving path on a TPU.

Drives the system once through the entry points a user calls, on a
SIFT1M-sized index built from seeded synthetic data:

    SearchEngine.build -> ServingLoop -> stream scan kernel
                       -> stream re-rank kernel -> merge

and checks what comes out against the plain jax.numpy engine (``ref`` scan,
``gathered`` re-rank) over the same index and base. Phases, one line each:

  device        jax.devices(): platform, device_kind, count
  build         1M x 128-D base, M=16 (8 B/code), nlist=1024; two engines
                sharing one index: stream/stream (kernels) and ref/gathered
                (reference); bytes on the device and peak device memory
  stage parity  one Q=32 batch: the select, mxu and stream grouped scans
                through kernels.ops at the index's (nlist, cap, M), each
                equal to 'ref' exactly (ADC sums are integers)
  serve         ServingLoop(stream engine) warmed up, 1000 queries (k=10)
                from three tenants; ids identical to the reference engine's,
                distances bit-identical or within 1e-6 relative, 0 compiles
                after warmup, recall@1 / recall@10 against brute force

The serve phase also holds the served distances to a float64 NumPy
sum((q - x)^2) over the returned ids, within the same 1e-6 relative.

With ``--chips 4`` only the sharded phase runs (after device + build):
ShardedEngine(stream engine, 4) through its shard_map driver on a 4-device
mesh, compared with the same engine's vmap driver on one device; then the
bytes of every live array on each device, which must show one shard each.

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
A failed phase raises (exit code 1, no JSON line). Off a TPU the full size
is refused after the device line (exit code 1, no JSON line): the kernels
would run in interpret mode for hours. A smaller run that finds no TPU
runs every phase, then prints ``"ok": false`` and exits 1 — so the script
can be rehearsed on the CPU:

    JAX_PLATFORMS=cpu python chip_smoke.py --n 4000
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python chip_smoke.py --n 4000 --chips 4
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_BASE = 1_000_000     # SIFT1M
D = 128
M = 16                 # 16 sub-spaces x 4 bits = 8 bytes per code
NLIST = 1024
NPROBE = 16
RERANK_MULT = 4
K = 10
N_QUERIES = 1000
PARITY_Q = 32
BATCH = 128            # the serving loop's largest shape bucket
TENANTS = ("alice", "bob", "carol")
# Largest relative distance gap admitted between the stream kernels and the
# jax.numpy reference when they are not bit-identical on a chip.
MAX_REL_GAP = 1e-6
# Largest --n run off a TPU, where the kernels run in interpret mode.
MAX_N_OFF_TPU = 100_000
# recall@10 floor. The CPU rehearsal at n=100_000 (nlist=1024, nprobe=16,
# rerank 4x10) measured 1.0; the 1M index scans the same 16 lists holding
# ten times the rows, so its recall is expected lower — the floor catches a
# broken path, not a regression.
RECALL10_FLOOR = 0.85


def _nlist(n: int) -> int:
    """1024 lists at full size; fewer for a small rehearsal (>= 64 rows
    per list on average), so every list is trainable."""
    return min(NLIST, max(16, n // 64))


def _device_line(jax) -> dict:
    devs = jax.devices()
    d0 = devs[0]
    info = {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}
    print(f"device: {info['count']} x {info['kind']} ({info['platform']}): "
          f"{devs}", flush=True)
    return info


def _peak_bytes(device) -> str:
    stats = device.memory_stats()
    return ("not reported" if not stats
            else f"{stats.get('peak_bytes_in_use', 'not reported')}")


def _build(n: int):
    import jax

    from repro.data import vectors
    from repro.engine import EngineConfig, SearchEngine

    t0 = time.perf_counter()
    ds = vectors.make_sift_like(n=n, nt=max(n // 10, 4 * _nlist(n)),
                                nq=N_QUERIES, d=D)
    t_data = time.perf_counter() - t0
    cfg = EngineConfig(nprobe=NPROBE, rerank_mult=RERANK_MULT)
    engine_stream = SearchEngine.build(
        jax.random.PRNGKey(0), ds.train, ds.base, m=M, nlist=_nlist(n),
        config=cfg._replace(scan_impl="stream", rerank_impl="stream"))
    # the reference: same index and base, plain jax.numpy stages
    engine_ref = SearchEngine(engine_stream.index, base=engine_stream.base,
                              config=cfg)
    lists = engine_stream.index.lists
    jax.block_until_ready((lists.codes, engine_stream.base))
    print(f"build: n={n} D={D} M={M} nlist={lists.nlist} cap={lists.cap} "
          f"data {t_data:.1f}s build {time.perf_counter() - t0 - t_data:.1f}s"
          f" | device bytes: base {engine_stream.base.nbytes} "
          f"codes {lists.codes.nbytes} ids {lists.ids.nbytes} "
          f"| peak_bytes_in_use {_peak_bytes(jax.devices()[0])}", flush=True)
    return ds, engine_stream, engine_ref


def _stage_parity(ds, engine) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core import ivf
    from repro.kernels import ops

    index = engine.index
    q = ds.queries[:PARITY_Q]
    probes = engine.select_probes(q, NPROBE)
    if not bool(jnp.all(probes >= 0)):
        raise AssertionError("stage parity needs every probe valid")
    g = PARITY_Q * NPROBE
    tables = ivf.probe_tables(index, q, probes).table_q8.reshape(g, M, 16)
    codes, _ = index.lists.gather(probes)
    codes = codes.reshape(g, index.cap, M // 2)
    ref = np.asarray(ops.fastscan_grouped(tables, codes, impl="ref"))
    got = {impl: ops.fastscan_grouped(tables, codes, impl=impl)
           for impl in ("select", "mxu")}
    got["stream"] = ops.fastscan_stream_grouped(tables, index.lists.codes,
                                                probes.reshape(-1))
    bad = [impl for impl, acc in got.items()
           if not np.array_equal(np.asarray(acc), ref)]
    print(f"stage parity: G={g} (nlist={index.nlist}, cap={index.cap}, M={M})"
          f" select/mxu/stream == ref: {not bad}", flush=True)
    if bad:
        raise AssertionError(f"grouped scans differ from ref: {bad}")


def _reference(engine, queries):
    """engine.search_jit over ``queries`` in BATCH-row chunks (the last one
    zero-padded), the shape the serving loop dispatches."""
    import numpy as np

    dists, ids = [], []
    for s in range(0, queries.shape[0], BATCH):
        chunk = queries[s:s + BATCH]
        n = chunk.shape[0]
        if n < BATCH:
            chunk = np.concatenate(
                [chunk, np.zeros((BATCH - n, chunk.shape[1]), chunk.dtype)])
        res = engine.search_jit(chunk, K)
        dists.append(np.asarray(res.dists)[:n])
        ids.append(np.asarray(res.ids)[:n])
    return np.concatenate(dists), np.concatenate(ids)


def _compare(name: str, dists, ids, ref_dists, ref_ids) -> None:
    """ids identical; distances bit-identical or within MAX_REL_GAP."""
    import numpy as np

    if not np.array_equal(ids, ref_ids):
        rows = int(np.sum(np.any(ids != ref_ids, axis=1)))
        raise AssertionError(f"{name}: ids differ from the reference in "
                             f"{rows} of {ids.shape[0]} queries")
    exact = np.array_equal(dists, ref_dists)
    real = ids >= 0  # absent results are +inf on both sides
    gap = float(np.max(np.abs(dists - ref_dists)[real]
                       / np.maximum(np.abs(ref_dists[real]), 1e-30),
                       initial=0.0))
    print(f"{name}: ids identical; dists bit-identical: {exact}"
          + ("" if exact else f", largest relative gap {gap!r}"), flush=True)
    if gap > MAX_REL_GAP:
        raise AssertionError(f"{name}: relative distance gap {gap} > "
                             f"{MAX_REL_GAP}")


def _check_exact(name: str, dists, ids, base, queries) -> None:
    """Served distances against NumPy float64 sum((q - x)^2) over the
    returned ids — a reference that shares no code with the engine."""
    import jax.numpy as jnp
    import numpy as np

    real = ids >= 0
    x = np.asarray(jnp.take(base, np.maximum(ids, 0), axis=0), np.float64)
    exact = np.sum((queries[:, None, :].astype(np.float64) - x) ** 2, axis=-1)
    gap = float(np.max(np.abs(dists - exact)[real]
                       / np.maximum(exact[real], 1e-30), initial=0.0))
    print(f"{name}: largest relative gap to float64 {gap!r}", flush=True)
    if gap > MAX_REL_GAP:
        raise AssertionError(f"{name}: relative distance gap {gap} > "
                             f"{MAX_REL_GAP}")


def _serve(ds, engine_stream, engine_ref) -> None:
    import numpy as np

    from repro.core import metrics
    from repro.serving import ServingLoop

    queries = np.asarray(ds.queries, np.float32)
    loop = ServingLoop(engine_stream)
    try:
        t0 = time.perf_counter()
        loop.start(warmup=True)
        t_warm = time.perf_counter() - t0
        warm = loop.metrics()
        t0 = time.perf_counter()
        futs = [loop.submit(queries[i], k=K, tenant=TENANTS[i % len(TENANTS)])
                for i in range(queries.shape[0])]
        results = [f.result(timeout=600) for f in futs]
        t_serve = time.perf_counter() - t0
        after = loop.metrics()
    finally:
        loop.close()
    compiles = after.compiles - warm.compiles
    dists = np.stack([r.dists for r in results])
    ids = np.stack([r.ids for r in results])
    ref_dists, ref_ids = _reference(engine_ref, queries)
    r1 = float(metrics.recall_at_r(ids, ds.gt_ids, r=1))
    r10 = float(metrics.recall_at_r(ids, ds.gt_ids, r=10))
    print(f"serve: {len(results)} queries from {len(TENANTS)} tenants in "
          f"{after.batches - warm.batches} batches, warmup {t_warm:.1f}s "
          f"({warm.compiles} compiles), serve wall {t_serve:.2f}s, "
          f"{compiles} compiles after warmup, recall@1 {r1!r} "
          f"recall@10 {r10!r} (floor {RECALL10_FLOOR})", flush=True)
    _compare("serve vs reference", dists, ids, ref_dists, ref_ids)
    _check_exact("serve vs float64", dists, ids, ds.base, queries)
    if compiles:
        raise AssertionError(f"{compiles} compiles after warmup")
    if r10 < RECALL10_FLOOR:
        raise AssertionError(f"recall@10 {r10} below {RECALL10_FLOOR}")


def _bytes_per_device(jax) -> dict:
    """Bytes of every live array on each device, shard by shard."""
    import gc

    gc.collect()
    held = {d: 0 for d in jax.devices()}
    for arr in jax.live_arrays():
        for shard in arr.addressable_shards:
            held[shard.device] += shard.data.nbytes
    return held


def _sharded(n: int, chips: int) -> None:
    """ShardedEngine's shard_map driver on ``chips`` devices against its
    vmap driver on one. Only the sharded engine is kept alive, so the
    bytes each device holds afterwards are its shard and little else."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.engine import ShardedEngine

    devs = jax.devices()
    if len(devs) < chips:
        raise AssertionError(f"--chips {chips} but JAX sees {len(devs)} "
                             "devices")
    ds, engine_stream = _build(n)[:2]
    queries = np.asarray(ds.queries, np.float32)
    sharded = ShardedEngine(engine_stream, chips)
    del ds, engine_stream
    batches = [queries[s:s + BATCH] for s in range(0, len(queries), BATCH)]

    def run(**kw):
        t0 = time.perf_counter()
        res = [sharded.search(b, K, **kw) for b in batches]
        dists = np.concatenate([np.asarray(r.dists) for r in res])
        ids = np.concatenate([np.asarray(r.ids) for r in res])
        return dists, ids, time.perf_counter() - t0

    ref_dists, ref_ids, t_vmap = run()                      # vmap, one device
    dists, ids, t_mesh = run(mesh=Mesh(np.array(devs[:chips]), ("shards",)))
    held = _bytes_per_device(jax)
    total = sum(held.values())
    in_use = {str(d): (d.memory_stats() or {}).get("bytes_in_use",
                                                   "not reported")
              for d in devs[:chips]}
    print(f"sharded: {chips} shards, shard_map on {chips} devices "
          f"({t_mesh:.1f}s) vs vmap on one ({t_vmap:.1f}s) | live array bytes"
          f" per device: { {str(d): b for d, b in held.items()} } of {total}"
          f" | bytes_in_use {in_use}", flush=True)
    # one shard each, plus the small replicated tables (global centroids,
    # codebook) and results; a whole copy left on one device is ~chips x
    if max(held[d] for d in devs[:chips]) > 2 * total / chips:
        raise AssertionError("shard state is not split one shard per device")
    _compare("shard_map vs vmap", dists, ids, ref_dists, ref_ids)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=N_BASE,
                    help="base rows (smaller only to rehearse off the chip)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded shard_map-vs-vmap phase")
    args = ap.parse_args(argv)

    import jax

    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    device = _device_line(jax)
    if device["platform"] != "tpu" and args.n > MAX_N_OFF_TPU:
        print(f"no TPU found: --n {args.n} needs the chip; rehearse off it "
              f"with --n <= {MAX_N_OFF_TPU}", file=sys.stderr)
        return 1
    if args.chips == 1:
        ds, engine_stream, engine_ref = _build(args.n)
        _stage_parity(ds, engine_stream)
        _serve(ds, engine_stream, engine_ref)
    else:
        _sharded(args.n, args.chips)
    print(f"peak_bytes_in_use after phases: {_peak_bytes(jax.devices()[0])}",
          flush=True)
    ok = device["platform"] == "tpu"
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
