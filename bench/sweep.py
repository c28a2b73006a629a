"""The one-time knee sweep of a configuration on the chip.

    python bench/sweep.py --config <name> --rates 600,650,700 --seconds 10

Builds the configuration once (seed ``--seed``), then offers open-loop
Poisson traffic at each rate in turn, ascending, and prints one line per
rate: offered and completed q/s, p50 and p99 latency from the due time, the
requests still pending when the window closed, and the median latency of
the window's second and last quarters. The knee is the highest rate, below
the first that fails, at which the backlog does not grow across the window:
the last quarter's median latency is at most ``GROWTH`` times the second's
(the first quarter is the ramp from an empty queue). The rate a mix needs
is written by hand into its traffic file as a fixed ``rate_qps``; no
benchmark run recomputes it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import numpy as np  # noqa: E402

from bench import cells, run, traffic  # noqa: E402

GROWTH = 1.25  # late / early median latency past which the backlog grows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--rates", required=True, help="q/s, comma-separated")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import jax

    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = cells.benchmark()
    entry = {c["name"]: c for c in bench["configs"]}[args.config]
    config = json.loads((ROOT / entry["file"]).read_text())
    cell = cells.Cell(f"sweep.{args.config}", config, {}, [], [], 1)
    device = run.device_info(jax, 1)
    _, _, loop, pool = run.setup(cell, args.seed)
    knee, failed = None, False
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = {"kind": "open_poisson", "rate_qps": rate}
        sent = traffic.drive(loop, pool, mix, args.seconds, args.seed + i,
                             config["k"])
        lat = (sent.done - sent.due) * 1e3
        done_in = int(np.sum(sent.done <= sent.t1))
        pending = int(np.sum(~(sent.done <= sent.t1)))
        offered = len(sent) / args.seconds
        completed = done_in / args.seconds
        p50, p99 = np.percentile(lat, [50, 99])
        quarters = np.array_split(lat[np.argsort(sent.due)], 4)
        early, late = (float(np.median(q)) for q in (quarters[1],
                                                       quarters[3]))
        holds = late <= GROWTH * early
        if holds and not failed:
            knee = rate
        failed = failed or not holds
        rows.append({"rate_qps": rate, "offered_qps": offered,
                     "completed_qps": completed, "p50_ms": float(p50),
                     "p99_ms": float(p99), "pending_at_close": pending,
                     "early_p50_ms": early, "late_p50_ms": late,
                     "keeps_up": holds})
        print(json.dumps(rows[-1]), flush=True)
    loop.close()
    print(json.dumps({"config": args.config, "device": device,
                      "seconds": args.seconds, "knee_qps": knee}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
