"""One run of one benchmark cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Makes the cell's data on the device from the seed, builds the index with
``SearchEngine.build``, starts ``ServingLoop(engine).start(warmup=True)``,
offers the cell's traffic for ``--seconds``, checks every answer sampled from
the window against the plain reference (``bench/reference.py``) and prints
one JSON line last on stdout:

    {"correct", "attempted", "failed", "metrics", "device"[, "breakdown"],
     "readings", "checks"}

With ``--trace 0`` the metrics are the cell's end-to-end metrics, taken on
the host clock; with ``--trace 1`` a profiler trace of the same window gives
its per-layer metrics (``bench/metrics/<name>.py``). Off a TPU, with fewer
chips than the cell asks for, or on a chip kind missing from
``bench/peaks.json``, it prints no result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import NamedTuple  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import numpy as np  # noqa: E402

from bench import cells, reference, traffic  # noqa: E402

# answered requests compared with the reference per run, drawn from the seed
CHECK_SAMPLE = 1000
# stored rows whose list and codes the reference re-derives per run
BUILD_SAMPLE = 8192
# The program's own names this harness calls (a change that renames one
# needs a change to the benchmark): SearchEngine.build, EngineConfig,
# ServingLoop(...).start / .submit / .metrics / .close, ServeResult fields,
# and the built engine's stored index (engine.index.centroids / .codebook /
# .lists) for the reference.

# the TPU's default matmul precision, which the configurations' coarse,
# table and build matmuls run at: one bfloat16 pass
MATMUL = "bf16"


class NoChip(RuntimeError):
    """The run found no accelerator it may measure."""


class Run(NamedTuple):
    """What a per-layer metric reader gets (``bench/metrics/*.py``)."""

    config: dict
    sums: dict       # work counts summed over the window's answered requests
    loop: dict       # LoopMetrics deltas over the traced window
    trace: object    # trace_reduce.Summary
    peaks: dict
    window_s: float

    def kernel_seconds(self, patterns) -> float:
        from bench import trace_reduce
        return trace_reduce.kernel_seconds(self.trace, patterns)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_info(jax, chips: int) -> dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX runs on {info['platform']}; the "
                     "benchmark measures only on the chip")
    if info["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{info['count']}")
    cells.peaks(info["kind"])  # an unlisted chip kind is refused here
    return info


@contextlib.contextmanager
def full_collections():
    """Log the process's full garbage collections inside the block: how
    many, and the longest and total pause (a pause holds every thread)."""
    pauses: list = []
    start = [0.0]

    def on_gc(phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            start[0] = time.perf_counter()
        else:
            pauses.append(time.perf_counter() - start[0])

    gc.callbacks.append(on_gc)
    try:
        yield
    finally:
        gc.callbacks.remove(on_gc)
        log(f"full collections during the window: {len(pauses)}, longest "
            f"{max(pauses, default=0.0) * 1e3:.1f} ms, total "
            f"{sum(pauses) * 1e3:.1f} ms")


def memory_peak(jax) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def build(config: dict, seed: int):
    """The seed's data and the deployment's index, as every run builds
    them: (vectors, engine, data seconds, build seconds)."""
    import jax

    from bench import data
    from repro.engine import EngineConfig, SearchEngine

    t = time.perf_counter()
    vec = data.make(config["data"], data.seed_key(seed))
    jax.block_until_ready(vec)
    t_data = time.perf_counter() - t
    args = dict(config["build"])
    t = time.perf_counter()
    engine = SearchEngine.build(
        data.build_key(config["data"]), vec.train, vec.base, m=args.pop("m"),
        nlist=args.pop("nlist"), config=EngineConfig(**config["engine"]),
        **args)
    lists = engine.index.lists
    held = int(np.asarray(lists.sizes).sum())
    if held != config["data"]["n_base"]:
        raise RuntimeError(
            f"the lists hold {held} of {config['data']['n_base']} rows: a "
            f"list overflowed its capacity {lists.cap}")
    return vec, engine, t_data, time.perf_counter() - t


def setup(cell: cells.Cell, seed: int):
    """Data, index, warmed-up loop: what every run of the cell needs."""
    from repro.serving import ServingLoop

    vec, engine, t_data, t_build = build(cell.config, seed)
    t = time.perf_counter()
    loop = ServingLoop(engine).start(warmup=True)
    t_warm = time.perf_counter() - t
    lists = engine.index.lists
    sizes = np.asarray(lists.sizes)
    log(f"setup: data {t_data:.2f}s build {t_build:.2f}s warmup "
        f"{t_warm:.2f}s ({loop.metrics().compiles} compiles) | nlist "
        f"{lists.nlist} cap {lists.cap} rows per list max {sizes.max()} "
        f"mean {sizes.mean():.1f}")
    return vec._replace(train=None), engine, loop, np.asarray(vec.pool)


def is_answered(sent: traffic.Sent) -> np.ndarray:
    """(R,) bool: requests answered without an error."""
    return sent.ok & np.isfinite(sent.done)


def end_to_end(sent: traffic.Sent, seconds: float) -> tuple[dict, int]:
    answered = is_answered(sent)
    start = sent.due if sent.open_loop else sent.sent
    lat = np.where(answered, sent.done - start, np.inf) * 1e3
    p50, p99 = np.percentile(lat, [50, 99])
    in_window = int(np.sum(answered & (sent.done <= sent.t1)))
    late = np.percentile(sent.sent - sent.due, 99) * 1e3
    pending = int(np.sum(~(sent.done <= sent.t1)))
    log(f"window: {len(sent)} sent, {int(answered.sum())} answered,"
        f" {in_window} in the window; p50 {float(p50)!r} ms, p99 "
        f"{float(p99)!r} ms; generator lateness p99 {float(late)!r} ms; "
        f"pending at the window's end {pending}")
    # an open loop's latency counts from the due time, a closed loop's from
    # the send: two quantities, so two names
    p50_name = "p50_ms" if sent.open_loop else "p50_ms.closed"
    return ({p50_name: float(p50), "p99_ms": float(p99),
             "qps": in_window / seconds}, int((~answered).sum()))


def check(cell: cells.Cell, sent: traffic.Sent, view, base, pool,
          seed: int, missing: int) -> dict:
    """The compared numbers over a seeded sample of answered requests and
    a seeded sample of the stored rows."""
    cfg = cell.config
    ok = np.nonzero(is_answered(sent))[0]
    rng = np.random.default_rng([seed, 0xC4EC])
    pick = (np.sort(rng.choice(ok, CHECK_SAMPLE, replace=False))
            if len(ok) > CHECK_SAMPLE else ok)
    ids = sent.ids[pick].astype(np.int64)
    dists = sent.dists[pick].astype(np.float64)
    t = time.perf_counter()
    nums = reference.judge(
        view, base, pool[sent.pool_idx[pick]], ids, dists,
        nprobe=cfg["engine"]["nprobe"],
        rerank_mult=cfg["engine"].get("rerank_mult", 0), k=cfg["k"],
        n_base=cfg["data"]["n_base"])
    log(f"check: {len(pick)} answers against the reference in "
        f"{time.perf_counter() - t:.1f}s")
    t = time.perf_counter()
    rows = build_sample(base.shape[0], seed)
    nums.update(reference.judge_build(view, base, rows,
                                      *reference.stored(view, rows)))
    log(f"check: {len(rows)} stored rows re-derived in "
        f"{time.perf_counter() - t:.1f}s")
    nums["missing"] = missing
    return nums


def build_sample(n_base: int, seed: int) -> np.ndarray:
    """The stored rows whose filing the check re-derives, drawn from the
    seed."""
    rng = np.random.default_rng([seed, 0xB11D])
    return np.sort(rng.choice(n_base, min(BUILD_SAMPLE, n_base),
                              replace=False))


def verdict(nums: dict, limits: dict) -> tuple[bool, dict]:
    """Each number the configuration sets a limit for, beside its limit;
    the others are readings and not compared (PERF.md says why)."""
    checks = {n: {"value": nums[n], "limit": limits[n]}
              for n in reference.NUMBERS if n in limits}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks


def log_checks(nums: dict, checks: dict) -> None:
    """The readings, then each compared number beside its limit: the last
    lines a run writes to stderr."""
    for n in reference.NUMBERS:
        if n not in checks:
            log(f"reading {n}: {nums[n]!r} (not compared)")
    for n, c in checks.items():
        log(f"check {n}: {c['value']!r} (limit {c['limit']!r})")


def recall(sent: traffic.Sent, nn: np.ndarray) -> float:
    ok = is_answered(sent)
    hits = np.any(sent.ids[ok, :10] == nn[sent.pool_idx[ok]][:, None],
                  axis=1)
    return float(np.mean(hits)) if hits.size else 0.0


def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool
             ) -> dict:
    import jax

    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    # every program of a run goes to the persistent cache, so only a cell's
    # first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    device = device_info(jax, cell.chips)
    vec, engine, loop, pool = setup(cell, seed)
    k = cell.config["k"]
    trace_dir = tempfile.TemporaryDirectory() if trace else None
    try:
        if trace:
            jax.profiler.start_trace(
                trace_dir.name, profiler_options=_profile_options(jax))
            tr0 = time.perf_counter()
        m0 = loop.metrics()
        with full_collections():
            sent = traffic.drive(loop, pool, cell.traffic, seconds, seed, k)
        m1 = loop.metrics()
        if trace:
            window_s = time.perf_counter() - tr0
            jax.profiler.stop_trace()
        setup_s = sent.t0 - T_START
        log(f"compiles during the window: {m1.compiles - m0.compiles}; "
            f"batches {m1.batches - m0.batches} buckets "
            f"{ {b: m1.bucket_counts.get(b, 0) - m0.bucket_counts.get(b, 0) for b in m1.bucket_counts} }")
        loop.close()
        device["memory_peak_bytes"] = memory_peak(jax)
        view = reference.view_of(engine, MATMUL)
        del engine, loop
        metrics, missing = end_to_end(sent, seconds)
        base = np.asarray(vec.base)
        nums = check(cell, sent, view, base, pool, seed, missing)
        correct, checks = verdict(nums, cell.config["limits"])
        result = {"correct": correct, "attempted": len(sent),
                  "failed": missing}
        if trace:
            result.update(per_layer(cell, sent, m0, m1, trace_dir.name,
                                    window_s, device))
        else:
            from bench import data
            nn = np.asarray(data.nearest_neighbour(vec.base,
                                                   jax.numpy.asarray(pool)))
            metrics["recall1_at_10"] = recall(sent, nn)
            metrics["setup_s"] = setup_s
            result["metrics"] = {
                m["name"]: {"value": metrics[m["name"]], "unit": m["unit"]}
                for m in cell.end_to_end}
        result["device"] = device
        result["readings"] = nums
        result["checks"] = checks
        log_checks(nums, checks)
        return result
    finally:
        if trace_dir is not None:
            trace_dir.cleanup()


def _profile_options(jax):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # keep the host's cost of tracing low
    return opts


def per_layer(cell, sent, m0, m1, trace_dir, window_s, device) -> dict:
    from bench import trace_reduce

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise RuntimeError("the profiler wrote no trace")
    summary = trace_reduce.reduce(files[-1])
    peaks = cells.peaks(device["kind"])
    work = sent.work[is_answered(sent)]
    sums = {"requests": work.shape[0],
            **{w: int(c) for w, c in zip(traffic.WORK, work.sum(axis=0))},
            "refining_queries": int(np.sum(
                work[:, traffic.WORK.index("reranked")] > 0))}
    loop = {"batches": m1.batches - m0.batches,
            "rows_served": m1.rows_served - m0.rows_served,
            "rows_padded": m1.rows_padded - m0.rows_padded}
    run = Run(cell.config, sums, loop, summary, peaks, window_s)
    metrics = {}
    for entry in cell.per_layer:
        value = cells.metric(entry["name"]).read(run)
        if value is not None:
            metrics[entry["name"]] = {"value": float(value),
                                      "unit": entry["unit"]}
    log(f"trace: {summary.chips} chip(s), busy {summary.busy_s!r}s of "
        f"{window_s!r}s, sums {sums}, loop {loop}")
    device["busy_s"] = summary.busy_s
    device["window_s"] = window_s
    return {"metrics": metrics,
            "breakdown": {"device_ops": trace_reduce.top_ops(summary),
                          "idle_gaps": [[n, s] for n, s in summary.gaps]}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.load(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"refused: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
