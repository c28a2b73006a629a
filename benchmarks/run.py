"""Benchmark harness: one entry per paper table/figure + kernel + roofline.

Prints ``name,us_per_call,derived`` CSV lines (see common.emit). Scaled-down
dataset sizes by default (CPU container); REPRO_BENCH_FULL=1 for paper scale,
REPRO_BENCH_SMOKE=1 for the even smaller CI smoke job.

Exit status: non-zero when any job raised, so CI and scripts can gate on it.
``--out FILE`` tees the CSV to a file (the CI artifact), ``--jobs a,b``
selects a subset.
"""
from __future__ import annotations

import argparse
import contextlib
import pathlib
import sys
import time
import traceback

# allow `python benchmarks/run.py` from anywhere (repo root on sys.path)
_ROOT = pathlib.Path(__file__).resolve().parent.parent
for p in (str(_ROOT), str(_ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


class _Tee:
    def __init__(self, *streams):
        self.streams = streams

    def write(self, s):
        for st in self.streams:
            st.write(s)

    def flush(self):
        for st in self.streams:
            st.flush()


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="", help="also write the CSV to this file")
    ap.add_argument("--jobs", default="",
                    help="comma-separated job subset (default: all)")
    args = ap.parse_args(argv)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()

    from benchmarks import (fig2, kernel_bench, mutation_bench, persist_bench,
                            serve_bench, table1)

    jobs = [
        ("kernel_bench", kernel_bench.main),
        ("fig2", fig2.main),
        ("table1", table1.main),
        ("serve_bench", serve_bench.main),
        # after kernel_bench: these append their records into BENCH_kernels.json
        ("mutation_bench", mutation_bench.main),
        ("persist_bench", persist_bench.main),
    ]
    if args.jobs:
        want = {j.strip() for j in args.jobs.split(",") if j.strip()}
        unknown = want - {n for n, _ in jobs}
        if unknown:
            print(f"unknown jobs: {sorted(unknown)}", file=sys.stderr)
            return 2
        jobs = [(n, f) for n, f in jobs if n in want]

    try:
        out_f = open(args.out, "w") if args.out else None
    except OSError as e:
        print(f"cannot open --out file: {e}", file=sys.stderr)
        return 2
    stack = contextlib.ExitStack()
    if out_f is not None:
        stack.enter_context(out_f)
        stack.enter_context(contextlib.redirect_stdout(_Tee(sys.stdout, out_f)))

    failures = []
    with stack:
        print("name,us_per_call,derived")
        for name, fn in jobs:
            t0 = time.time()
            try:
                fn()
            except Exception:
                traceback.print_exc()
                failures.append(name)
            print(f"# {name} finished in {time.time() - t0:.1f}s", file=sys.stderr)
    if failures:
        print(f"# FAILED: {failures}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
