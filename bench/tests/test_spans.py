"""The span reduction (``bench/spans.py``) on two small traces recorded on
one TPU v5 lite: ``sift1m_q128`` from before the program had spans or stage
scopes (three Q=128 and five Q=1 SIFT1M batches), and ``sift1m_spans`` with
both (``spans_run.py --fixture``: two rounds of 128 requests, then four lone
requests 20 ms apart)."""
from pathlib import Path

import pytest

from bench import spans, trace_reduce

DATA = Path(__file__).parent / "data"
OLD = DATA / "sift1m_q128.xplane.pb"
NEW = DATA / "sift1m_spans.xplane.pb"
CHILDREN = ("repro.serve.form", "repro.serve.enqueue", "repro.serve.fetch",
            "repro.serve.resolve", "repro.serve.account")


@pytest.fixture(scope="module", params=[OLD, NEW], ids=["old", "new"])
def both(request):
    return (request.param, trace_reduce.reduce(request.param),
            spans.reduce(request.param))


@pytest.fixture(scope="module")
def new():
    return spans.reduce(NEW)


@pytest.fixture(scope="module")
def old():
    return trace_reduce.reduce(OLD), spans.reduce(OLD)


def test_decoder_reads_what_profile_data_reads(both):
    _, summary, red = both
    assert red.op_s.keys() == summary.op_s.keys()
    for name, t in summary.op_s.items():
        assert red.op_s[name] == pytest.approx(t, rel=1e-12)
    assert (red.chips, red.busy_s, red.span_s) == (
        summary.chips, summary.busy_s, summary.span_s)


def test_stages_sum_to_busy_and_idle_tiles_the_span(both):
    _, _, red = both
    assert sum(red.stages.values()) == pytest.approx(red.busy_s, rel=1e-9)
    assert set(red.stages) <= {*spans.STAGES, spans.UNATTRIBUTED}
    idle = sum(red.idle_by_span.values())
    assert idle + red.busy_s == pytest.approx(red.traced_s, rel=1e-9)
    assert red.traced_s >= red.span_s


def test_trace_without_spans_reads_nothing_new(old):
    summary, red = old
    assert red.stages == {spans.UNATTRIBUTED: pytest.approx(red.busy_s)}
    assert red.batches == [] and red.spans == {}
    assert set(red.idle_by_span) == {spans.NO_SPAN}
    assert red.traced_s == red.span_s
    assert red.gaps == summary.gaps  # named as trace_reduce names them
    assert spans.host_ms_per_batch(red) is None
    assert spans.scan_prep_ms_per_batch(red) is None


def test_every_op_has_a_stage(new):
    assert new.stages.get(spans.UNATTRIBUTED, 0.0) < 0.02 * new.busy_s
    assert new.stages["scan"] > 0.9 * new.busy_s
    assert {"coarse", "scan", "rerank", "merge", "stats"} <= set(new.stages)
    kernel = trace_reduce.kernel_seconds(new, (spans.SCAN_KERNEL,))
    assert new.scan_kernel_s == pytest.approx(kernel)


def test_code_store_relayout_lands_in_scan():
    """XLA's copy of the whole code store to the layout the scan kernel
    takes has no stage of its own: it follows its operand or its user."""
    (plane,) = [p for p in spans.read_xspace(NEW)
                if p.name == "/device:TPU:0"]
    stage = spans.op_stages(plane.meta)
    relayout = [mid for mid, (text, _) in plane.meta.items()
                if text.startswith("%copy") and " u8[1024,5120,8]" in text]
    assert relayout
    for mid in relayout:
        assert spans.stage_of_path(plane.meta[mid][1].get("tf_op")) is None
        assert stage[mid] == "scan"


def test_one_batch_span_per_batch_with_its_children(new):
    assert [(b.rows, b.bucket) for b in new.batches] == [
        (128, 128), (128, 128), (1, 1), (1, 1), (1, 1), (1, 1)]
    for child in CHILDREN:
        assert new.spans[child]["count"] == len(new.batches)
    batch = new.spans[spans.BATCH_SPAN]
    children = sum(new.spans[c]["total_s"] for c in CHILDREN)
    assert batch["self_s"] == pytest.approx(batch["total_s"] - children)
    for b in new.batches:
        assert 0 < b.device_s < (b.end_ns - b.start_ns) * 1e-9
        assert b.host_s == pytest.approx((b.end_ns - b.start_ns) * 1e-9
                                         - b.device_s)


def test_idle_named_by_the_dispatch_thread_span(new):
    assert new.idle_by_span["repro.batcher.next_batch"] > 0
    assert set(new.idle_by_span) <= {*new.spans, spans.NO_SPAN}
    # the lone requests' 20 ms arrival gaps are the longest idle gaps
    assert [n for n, _ in new.gaps[:3]] == ["repro.batcher.next_batch"] * 3


def test_trace_read_metrics_read_the_new_trace(new):
    host = spans.host_ms_per_batch(new)
    prep = spans.scan_prep_ms_per_batch(new)
    assert host is not None and prep is not None
    assert 0 < host < 1e3 * new.span_s / len(new.batches)
    assert 0 < prep < 1e3 * new.stages["scan"] / len(new.batches)
    out = spans.breakdown(new)
    assert set(out["host_ms_per_batch_by_bucket"]) == {"1", "128"}


def test_queue_wait_reads_the_loop_counter():
    assert spans.queue_wait_ms({"queue_wait_s": 0.5, "rows_served": 100}
                               ) == pytest.approx(5.0)
    assert spans.queue_wait_ms({"queue_wait_s": None, "rows_served": 9}
                               ) is None  # a loop without the counter
    assert spans.queue_wait_ms({"queue_wait_s": 0.0, "rows_served": 0}
                               ) is None


def test_innermost_split_and_self_times():
    nested = [(0, 100, "batch"), (10, 20, "form"), (20, 90, "fetch"),
              (120, 150, "wait")]
    assert spans.innermost(nested) == [
        (0, 10, "batch"), (10, 20, "form"), (20, 90, "fetch"),
        (90, 100, "batch"), (120, 150, "wait")]
    parts = spans.split([(5, 15), (95, 130)], spans.innermost(nested))
    assert parts == pytest.approx({"batch": 10e-9, "form": 5e-9,
                                   "wait": 10e-9, spans.NO_SPAN: 20e-9})
    times = spans.self_times(nested)
    assert times["batch"] == {"count": 1, "total_s": pytest.approx(100e-9),
                              "self_s": pytest.approx(20e-9)}
    assert times["fetch"]["self_s"] == pytest.approx(70e-9)


def test_stage_of_path():
    assert spans.stage_of_path(
        "jit(_pipeline)/rerank/jit(rerank_stream_topk)/jit(gather_ids)"
    ) == "rerank"
    assert spans.stage_of_path("jit(_pipeline)/jit(smallest_k)/top_k") is None
    assert spans.stage_of_path(None) is None
