"""The control: the reference at the precision below the one the
configuration states, put in the program's place and judged as the program
is. It has to come out not correct; its readings set the upper end of each
limit (``limits`` in ``bench/configs/*.json``). The benchmark's own runs
never run it.

    python bench/control.py --workload <cell> --seeds 11,12,13 [--program 3]

builds the cell's index on the chip for each seed, answers a seeded sample
of pool queries as the control does (``rerank_bf16``: the re-rank on
bfloat16 rows; ``lut_int4``: 4-bit ADC tables), files a seeded sample of the
base rows as its build does (``build_fp8``: float8 operands) and prints one
JSON line per seed with the compared numbers. With ``--program S`` it first reads the
program's numbers on the same seed, from a run of the cell itself with an
S-second window at the cell's own load, in the same process: the lower
readings the limits are set from.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import numpy as np  # noqa: E402

from bench import cells, reference, run  # noqa: E402


def readings(cell: cells.Cell, seed: int) -> dict:
    """The control's compared numbers for one seed, on the sample of pool
    queries and of stored rows a run of the cell would check."""
    import jax

    run.device_info(jax, cell.chips)
    cfg = cell.config
    vec, engine, _, _ = run.build(cfg, seed)
    view = reference.view_of(engine, run.MATMUL)
    del engine
    base = np.asarray(vec.base)
    pool = np.asarray(vec.pool)
    rng = np.random.default_rng([seed, 0xC4EC])
    queries = pool[rng.integers(0, pool.shape[0],
                                min(run.CHECK_SAMPLE, pool.shape[0]))]
    eng = cfg["engine"]
    kw = dict(nprobe=eng["nprobe"], rerank_mult=eng.get("rerank_mult", 0),
              k=cfg["k"])
    ids, dists = reference.control_answers(view, base, queries,
                                           control=cfg["control"], **kw)
    nums = reference.judge(view, base, queries, ids, dists,
                           n_base=cfg["data"]["n_base"], **kw)
    rows = run.build_sample(base.shape[0], seed)
    nums.update(reference.judge_build(
        view, base, rows, *reference.control_build(view, base, rows,
                                                   control=cfg["control"])))
    nums["missing"] = 0
    return nums


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one reading each")
    ap.add_argument("--program", type=float, default=0.0,
                    help="also read the program's numbers from a run with "
                    "a window of this many seconds")
    args = ap.parse_args(argv)
    cell = cells.load(args.workload)
    import jax

    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for seed in (int(s) for s in args.seeds.split(",")):
        if args.program:
            res = run.run_cell(cell, seed, args.program, False)
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "control": None, "correct": res["correct"],
                              **res["readings"]}), flush=True)
        nums = readings(cell, seed)
        correct, _ = run.verdict(nums, cell.config["limits"])
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control": cell.config["control"],
                          "correct": correct, **nums}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
