"""The trace reduction on a small trace recorded on one TPU v5 lite: eight
SIFT1M-sized batches (three Q=128, five Q=1) through the serving loop."""
from pathlib import Path

import pytest

from bench import trace_reduce

TRACE = Path(__file__).parent / "data" / "sift1m_q128.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce(TRACE)


def test_one_chip_busy_within_span(summary):
    assert summary.chips == 1
    assert 0 < summary.busy_s <= summary.span_s


def test_kernel_time_by_name(summary):
    scan = trace_reduce.kernel_seconds(summary, ("fastscan_stream_topk",))
    rerank = trace_reduce.kernel_seconds(summary, ("rerank_stream_topk",))
    # eight scan and eight re-rank calls, as recorded
    assert scan == pytest.approx(0.381055948, rel=1e-9)
    assert rerank == pytest.approx(0.002305925, rel=1e-6)
    assert scan + rerank < summary.busy_s
    assert trace_reduce.kernel_seconds(summary, ("no_such_kernel",)) == 0


def test_top_ops_and_gaps(summary):
    ops = trace_reduce.top_ops(summary)
    assert ops[0][0] == "fastscan_stream_topk"
    assert len(ops) <= trace_reduce.TOP
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    gaps = [s for _, s in summary.gaps]
    assert 0 < len(gaps) <= trace_reduce.TOP
    assert gaps == sorted(gaps, reverse=True) and gaps[-1] >= 0
    # busy time and the gaps between busy intervals tile the span
    assert summary.busy_s + sum(gaps) <= summary.span_s + 1e-9


def test_op_name_and_merge():
    assert trace_reduce.op_name(
        "%fastscan_stream_topk.1 = (s32[2048,4,40]) custom-call(...)"
    ) == "fastscan_stream_topk"
    assert trace_reduce.op_name("%fusion = f32[8] fusion(...)") == "fusion"
    assert trace_reduce.merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [
        [0, 3], [5, 9]]
