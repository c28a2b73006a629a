"""What decides ``correct`` can fail: the control (the reference at the
precision below the configuration's) and a broken timed path or build both
come out not correct, at a toy size on the CPU, where the stated matmul
precision is the CPU's exact one. The limits are the configurations' own,
set from chip readings (PERF.md)."""
import numpy as np
import pytest

from bench import cells, control, run
from bench.tests.tiny import shrink
from repro.core import ivf, pq
from repro.engine import SearchEngine

CONFIG_CELLS = {}
for w in cells.benchmark()["workloads"]:
    CONFIG_CELLS.setdefault(w["config"], w["name"])
SEEDS = (3, 2**31 + 29, 77)


def _failing(checks: dict) -> set:
    return {n for n, c in checks.items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("name", sorted(CONFIG_CELLS.values()))
@pytest.mark.parametrize("seed", SEEDS)
def test_control_is_not_correct(name, seed, off_chip):
    cell = shrink(cells.load(name))
    nums = control.readings(cell, seed)
    correct, checks = run.verdict(nums, cell.config["limits"])
    assert not correct, checks
    # the control's float8 build gives the build's numbers an upper reading
    assert nums["misfiled"] > 0 and nums["miscoded"] > 0, nums


def _alter_one_answer(search_jit):
    def broken(self, queries, k=10, **kw):
        res = search_jit(self, queries, k, **kw)
        # the first row's best id becomes another stored row, not a repeat
        n = self.index.lists.ids.max() + 1
        ids = res.ids.at[0, 0].set((res.ids[0, 0] + 1) % n)
        return res._replace(ids=ids)
    return broken


def _drop_half_the_batch(search_jit):
    def broken(self, queries, k=10, **kw):
        q = np.asarray(queries)
        half = max(1, q.shape[0] // 2)
        q = np.concatenate([q[:half]] * 2 + [q[:1]] * (q.shape[0] % 2))
        return search_jit(self, q[:queries.shape[0]], k, **kw)
    return broken


def _one_slot_one_probe(search_jit):
    """The first slot of every batch scans only its nearest list: its
    answer is a self-consistent one (true ids with their true distances),
    just not the best."""
    def broken(self, queries, k=10, **kw):
        res = search_jit(self, queries, k, **kw)
        few = search_jit(self, queries, k, **{**kw, "nprobe": 1})
        return res._replace(ids=res.ids.at[0].set(few.ids[0]),
                            dists=res.dists.at[0].set(few.dists[0]))
    return broken


FAULTS = {"answer_altered": _alter_one_answer,
          "half_batch_left_out": _drop_half_the_batch,
          "one_slot_wrong_probes": _one_slot_one_probe}


@pytest.mark.parametrize("name", sorted(CONFIG_CELLS.values()))
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(name, fault, off_chip,
                                          monkeypatch):
    monkeypatch.setattr(SearchEngine, "search_jit",
                        FAULTS[fault](SearchEngine.search_jit))
    cell = shrink(cells.load(name))
    # full Q=128 batches, as at the knee: a fault in one slot touches under
    # 1% of the answers. Only `correct` is read, so no end-to-end metric is
    # asked of the closed loop.
    cell = cell._replace(traffic={"kind": "closed", "outstanding": 128},
                         end_to_end=[])
    res = run.run_cell(cell, 5, 1.0, False)
    assert res["correct"] is False, res["checks"]
    if fault == "one_slot_wrong_probes":
        assert res["readings"]["worse_share"] < 0.02, res["readings"]
        assert "rank_gap" in _failing(res["checks"]), res["checks"]


def _misfile_rows(build_lists):
    """Every 50th row goes to the next list."""
    def broken(assign, packed, *, nlist, cap):
        assign = np.array(assign)
        assign[::50] = (assign[::50] + 1) % nlist
        return build_lists(assign, packed, nlist=nlist, cap=cap)
    return broken


def _encode_bf16(encode):
    """The codes computed from bfloat16 operands, a precision below the
    CPU's exact one."""
    import jax.numpy as jnp

    def low(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    def broken(cb, x):
        return encode(cb._replace(codewords=low(cb.codewords)), low(x))
    return broken


BUILD_FAULTS = {"rows_misfiled": (ivf, "build_lists", _misfile_rows,
                                  "misfiled"),
                "codes_low_precision": (pq, "encode", _encode_bf16,
                                        "miscoded")}


@pytest.mark.parametrize("name", sorted(CONFIG_CELLS.values()))
@pytest.mark.parametrize("fault", sorted(BUILD_FAULTS))
def test_broken_build_is_not_correct(name, fault, off_chip, monkeypatch):
    module, attr, make, number = BUILD_FAULTS[fault]
    monkeypatch.setattr(module, attr, make(getattr(module, attr)))
    res = run.run_cell(shrink(cells.load(name)), 5, 0.5, False)
    assert res["correct"] is False, res["checks"]
    assert number in _failing(res["checks"]), res["checks"]
