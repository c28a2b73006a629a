"""A configuration, a traffic mix and a per-layer metric are added as files
and entries alone: the harness finds each by its name, with no edit."""
import json
import shutil
from pathlib import Path

import pytest

from bench import cells, run, trace_reduce

ROOT = Path(__file__).resolve().parents[2]


def test_every_declared_piece_has_its_file():
    bench = cells.benchmark()
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert set(cfg["limits"]) <= set(run.reference.NUMBERS)
    for w in bench["workloads"]:
        cell = cells.load(w["name"])
        assert cell.traffic["kind"] in run.traffic.KINDS
    for m in bench["per_layer"]:
        mod = cells.metric(m["name"])
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
            m["name"], m["unit"], m["layer"], m["moves"], m["source"])
        e2e = {e["name"] for e in bench["end_to_end"]}
        assert m["moves"] in e2e


def test_new_config_mix_and_metric_are_found(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    bench = cells.benchmark()
    # a new deployment: a copy of one with another probe count
    old = bench["configs"][0]
    cfg = json.loads((ROOT / old["file"]).read_text())
    cfg["name"] = "added-config"
    cfg["engine"]["nprobe"] = 8
    (tmp_path / "bench" / "configs" / "added-config.json").write_text(
        json.dumps(cfg))
    bench["configs"].append({**old, "name": "added-config",
                             "file": "bench/configs/added-config.json"})
    # a new mix
    (tmp_path / "bench" / "traffic" / "added_mix.json").write_text(
        json.dumps({"kind": "open_poisson", "rate_qps": 123.0}))
    # a new per-layer metric: padding rows per batch
    (tmp_path / "bench" / "metrics" / "batcher.pad_rows.py").write_text(
        'NAME = "batcher.pad_rows"\nUNIT = "rows"\n'
        'LAYER = "serving batcher (serving/batcher.py)"\n'
        'MOVES = "p99_ms"\nSOURCE = "program_counter"\n\n\n'
        'def read(run):\n'
        '    return run.loop["rows_padded"] / run.loop["batches"]\n')
    bench["per_layer"].append({
        "name": "batcher.pad_rows", "unit": "rows", "better": "lower",
        "source": "program_counter",
        "layer": "serving batcher (serving/batcher.py)", "moves": "p99_ms",
        "workloads": ["added.cell"]})
    bench["workloads"].append({"name": "added.cell", "config": "added-config",
                               "traffic": "added_mix", "chips": 1,
                               "why": "added by files alone"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = cells.load("added.cell", root=tmp_path)
    assert cell.config["engine"]["nprobe"] == 8
    assert cell.traffic == {"kind": "open_poisson", "rate_qps": 123.0}
    assert [m["name"] for m in cell.per_layer] == ["batcher.pad_rows"]
    reader = cells.metric("batcher.pad_rows", root=tmp_path)
    view = run.Run(cell.config, {}, {"batches": 4, "rows_served": 100,
                                     "rows_padded": 28},
                   trace_reduce.Summary(0, 0.0, 0.0, {}, []), {}, 1.0)
    assert reader.read(view) == 7.0
    # an existing cell is untouched by the additions
    assert cells.load("sift1m.steady", root=tmp_path) == cells.load(
        "sift1m.steady")


def test_unknown_names_fail_loudly(tmp_path):
    with pytest.raises(KeyError):
        cells.load("no.such.cell")
    with pytest.raises(FileNotFoundError):
        cells.metric("no.such.metric")
    with pytest.raises(LookupError):
        cells.peaks("cpu")
