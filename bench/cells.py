"""Everything the harness knows about a cell comes from files found by name.

``BENCHMARK.json`` (the checkout's root) names each cell's configuration and
traffic mix; ``bench/configs/<config>.json`` holds the deployment,
``bench/traffic/<mix>.json`` the mix, ``bench/metrics/<metric>.py`` each
per-layer metric's reader and ``bench/peaks.json`` the chips' peaks. Adding a
cell, a mix or a metric is adding files; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import NamedTuple

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class Cell(NamedTuple):
    name: str
    config: dict
    traffic: dict
    end_to_end: list   # BENCHMARK.json end_to_end entries this cell reports
    per_layer: list    # BENCHMARK.json per_layer entries this cell reports
    chips: int


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load(name: str, root: Path = ROOT) -> Cell:
    bench = benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name, config, traffic,
                [m for m in bench["end_to_end"] if _reports(m, name)],
                [m for m in bench["per_layer"] if _reports(m, name)],
                int(w["chips"]))


def metric(name: str, root: Path = ROOT):
    """The reader module ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    """The peaks of one chip kind; an unlisted kind is an error."""
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if device_kind not in table:
        raise LookupError(f"no peaks for device kind {device_kind!r} in "
                          f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]
