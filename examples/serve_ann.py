"""Serve ANN queries through the dynamic micro-batching loop, end to end:

build an IVF + 4-bit-PQ engine, start ``repro.serving.ServingLoop`` (fused
single-jit pipeline underneath), fire a ragged multi-tenant request stream
at it, and print per-tenant accounting + loop metrics.

    PYTHONPATH=src python examples/serve_ann.py [--n 50000] [--requests 200]
"""
import argparse
import asyncio
import math
import time

import jax
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import metrics
from repro.data import vectors
from repro.engine import SearchEngine
from repro.serving import ServingLoop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=50_000)
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--rerank-mult", type=int, default=4)
    ap.add_argument("--max-wait-ms", type=float, default=2.0,
                    help="batching window: how long a request waits for co-riders")
    args = ap.parse_args()
    use_compile_cache()

    print("== build engine ==")
    ds = vectors.make_sift_like(n=args.n, nt=max(5_000, args.n // 10), nq=256)
    engine = SearchEngine.build(jax.random.PRNGKey(0), ds.train, ds.base,
                                m=8, nlist=int(math.sqrt(args.n)),
                                coarse_iters=10, pq_iters=10)

    loop = ServingLoop(engine, rerank_mult=args.rerank_mult,
                       max_wait_s=args.max_wait_ms / 1e3)
    loop.start(warmup=True)  # pre-compile every shape bucket
    print(f"warmed up: {loop.metrics().compiles} compiles "
          f"(one per shape bucket {loop.batcher.buckets})")

    print(f"\n== serve {args.requests} requests from 3 tenants ==")
    rng = np.random.default_rng(0)
    queries = np.asarray(ds.queries, np.float32)
    t0 = time.monotonic()
    futs, rows = [], []
    for i in range(args.requests):
        qi = i % queries.shape[0]
        tenant = ("alice", "bob", "carol")[i % 3]
        futs.append(loop.submit(queries[qi], k=10, tenant=tenant))
        rows.append(qi)
        if rng.random() < 0.3:               # ragged arrivals: bursty stream
            time.sleep(float(rng.exponential(0.002)))
    results = [f.result(timeout=60) for f in futs]
    wall = time.monotonic() - t0

    got = np.stack([r.ids for r in results])
    r1 = float(metrics.recall_at_r(got, ds.gt_ids[np.asarray(rows)], r=1))
    m = loop.metrics()
    print(f"{args.requests} requests in {wall:.2f}s "
          f"({args.requests / wall:.0f} qps), recall@1={r1:.3f}")
    print(f"batches={m.batches}, occupancy={m.occupancy:.2f}, "
          f"buckets={m.bucket_counts}, compiles after warmup="
          f"{m.compiles - len(loop.batcher.buckets)}")

    print("\n== per-tenant accounting ==")
    for tenant, st in sorted(loop.stats.snapshot().items()):
        print(f"  {tenant:8s} queries={st.queries:4d} "
              f"codes_scanned={st.codes_scanned:8d} "
              f"reranked={st.reranked:6d} "
              f"mean_latency={st.mean_latency_s * 1e3:6.2f}ms "
              f"max={st.latency_max_s * 1e3:6.2f}ms")

    print("\n== asyncio entry point ==")

    async def one():
        res = await loop.asearch(queries[0], k=5, tenant="async")
        return res.ids

    print("await loop.asearch(...) ->", asyncio.run(one()))
    loop.stop()


if __name__ == "__main__":
    main()
