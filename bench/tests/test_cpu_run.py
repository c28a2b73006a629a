"""Every cell end to end on the CPU at a toy size: traffic, serving,
reference check and end-to-end metrics run, and the device metrics refuse to
be read off a chip rather than fall back to the CPU."""
import json

import pytest

from bench import cells, run
from bench.tests.tiny import shrink

CELLS = [w["name"] for w in cells.benchmark()["workloads"]]
SEED = 2**31 + 17  # wider than 31 bits, as a benchmark seed may be


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_checks_on_cpu(name, off_chip):
    res = run.run_cell(shrink(cells.load(name)), SEED, 0.5, False)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    metrics = res["metrics"]
    assert set(metrics) == {m["name"] for m in cells.load(name).end_to_end}
    assert all(m["value"] > 0 for m in metrics.values())
    assert res["device"]["platform"] == "cpu"


def test_device_metrics_fail_off_the_chip(off_chip):
    cell = shrink(cells.load(CELLS[0]))
    with pytest.raises(LookupError, match="no peaks for device kind"):
        run.run_cell(cell, SEED, 0.3, True)


def test_command_refuses_without_a_chip(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", str(SEED),
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert "no TPU" in out.err
    for line in out.out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
