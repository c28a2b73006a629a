"""One traced run of a cell that also reads what the program records for
its spans: the serving loop's host spans, its queue-wait counter and the
fused pipeline's stage scopes (``bench/spans.py``).

    python3 bench/spans_run.py --workload <cell> --seed <n> --seconds <s>

runs ``run.run_cell`` with ``--trace 1`` exactly as ``run.py`` does and
prints its result line with one entry added, ``spans``: the readings of
``spans.queue_wait_ms``, ``spans.host_ms_per_batch`` and
``spans.scan_prep_ms_per_batch``, the end-to-end ``p50_ms``/``qps`` of the
traced window, and ``spans.breakdown`` (device time per stage, host spans,
idle time per span, the longest idle gaps named by span). It stands in
for ``run.per_layer`` reading these itself: it wraps that function for the
length of the run, so it follows ``per_layer``'s signature, and goes once
``run.py`` reports the readings as per-layer metrics.

    python3 bench/spans_run.py --fixture <file.xplane.pb> [--seed <n>]

records the small trace ``bench/tests`` reads: SIFT1M built as
``sift1m.steady`` builds it, then two batches of 128 requests and four lone
requests 20 ms apart through the serving loop. Both need a TPU.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1] / "src"),
                str(Path(__file__).resolve().parents[1])]

from bench import cells, run, spans  # noqa: E402

FIXTURE_CELL = "sift1m.steady"


def traced_run(cell, seed: int, seconds: float) -> dict:
    """``run.run_cell`` traced, with the span readings taken from the same
    trace and loop counters its per-layer metrics read."""
    inner = run.per_layer
    extra: dict = {}

    def per_layer(cell, sent, m0, m1, trace_dir, window_s, device):
        out = inner(cell, sent, m0, m1, trace_dir, window_s, device)
        trace = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
        t = time.perf_counter()
        red = spans.reduce(trace)
        run.log(f"spans: reduced {trace.stat().st_size} B in "
                f"{time.perf_counter() - t:.1f}s")
        wait0 = getattr(m0, "queue_wait_s", None)
        loop = {"rows_served": m1.rows_served - m0.rows_served,
                "queue_wait_s": (None if wait0 is None
                                 else m1.queue_wait_s - wait0)}
        e2e, _ = run.end_to_end(sent, seconds)
        extra.update({
            "batcher.queue_wait_ms": spans.queue_wait_ms(loop),
            "serving.host_ms_per_batch": spans.host_ms_per_batch(red),
            "scan.prep_ms_per_batch": spans.scan_prep_ms_per_batch(red),
            "batches": len(red.batches),
            "busy_s": red.busy_s, "window_s": window_s,
            "end_to_end": e2e,
            **spans.breakdown(red)})
        return out

    run.per_layer = per_layer
    try:
        result = run.run_cell(cell, seed, seconds, True)
    finally:
        run.per_layer = inner
    result["spans"] = extra
    return result


def record_fixture(path: str, seed: int) -> None:
    import jax

    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = cells.load(FIXTURE_CELL)
    run.device_info(jax, cell.chips)
    _, _, loop, pool = run.setup(cell, seed)
    k = cell.config["k"]
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=run._profile_options(jax))
        for rnd in range(2):
            futs = [loop.submit(q, k=k) for q in pool[rnd * 128:][:128]]
            for f in futs:
                f.result()
        for q in pool[256:260]:
            loop.submit(q, k=k).result()
            time.sleep(0.02)
        jax.profiler.stop_trace()
        shutil.copy(sorted(Path(d).rglob("*.xplane.pb"))[-1], path)
    m = loop.metrics()
    loop.close()
    run.log(f"fixture: {m.batches} batches, buckets {m.bucket_counts}, "
            f"{Path(path).stat().st_size} B -> {path}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--fixture")
    args = ap.parse_args(argv)
    try:
        if args.fixture:
            record_fixture(args.fixture, args.seed)
            return 0
        if not args.workload:
            ap.error("give --workload or --fixture")
        result = traced_run(cells.load(args.workload), args.seed,
                            args.seconds)
    except run.NoChip as e:
        run.log(f"refused: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
