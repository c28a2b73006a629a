"""What the serving loop and the fused pipeline record for a profiler: the
queue-wait counter, the dispatch thread's ``repro.*`` spans in a
``jax.profiler`` trace, and the stage scopes in the pipeline's HLO metadata
(docs/serving.md, "Tracing a live loop")."""
import functools
import re
import time
from pathlib import Path

import jax

from repro.data import vectors
from repro.engine import SearchEngine
from repro.engine import engine as engine_mod
from repro.serving import ServingLoop

STAGES = ("coarse", "scan", "rerank", "merge", "stats")
CHILDREN = ("repro.serve.form", "repro.serve.enqueue", "repro.serve.fetch",
            "repro.serve.resolve", "repro.serve.account")


@functools.lru_cache(maxsize=None)
def small():
    ds = vectors.make_sift_like(n=3000, nt=1000, nq=16, d=32, ncl=16, seed=5)
    eng = SearchEngine.build(jax.random.PRNGKey(0), ds.train, ds.base, m=8,
                             nlist=16, coarse_iters=4, pq_iters=4)
    return ds, eng


def settle(loop, rows):
    """Wait until the loop has accounted ``rows`` served rows: a future
    resolves before its batch's counters are updated."""
    deadline = time.monotonic() + 30
    while loop.metrics().rows_served < rows:
        assert time.monotonic() < deadline
        time.sleep(0.005)


def test_queue_wait_counts_the_co_rider_wait_per_row():
    ds, eng = small()
    max_wait = 0.05
    loop = ServingLoop(eng, buckets=(1, 8), max_wait_s=max_wait,
                       rerank_mult=2).start(warmup=True)
    try:
        assert loop.metrics().queue_wait_s == 0.0
        for i in range(3):  # lone requests wait out max_wait_s for co-riders
            m0 = loop.metrics()
            loop.submit(ds.queries[i], k=10).result(timeout=60)
            settle(loop, m0.rows_served + 1)
            assert loop.metrics().queue_wait_s - m0.queue_wait_s >= max_wait
        # a batch that fills at once leaves without waiting: a long
        # max_wait_s is cut short when the largest bucket fills
        loop.batcher.max_wait_s = 2.0
        m0 = loop.metrics()
        futs = [loop.submit(ds.queries[i], k=10) for i in range(8)]
        for f in futs:
            f.result(timeout=60)
        settle(loop, m0.rows_served + 8)
        m1 = loop.metrics()
        assert m1.batches - m0.batches == 1
        assert 0 <= (m1.queue_wait_s - m0.queue_wait_s) / 8 < 0.1 * 2.0
    finally:
        loop.stop()


def _host_events(trace_dir: Path):
    """[(line index, name, start, end, stats)] of the host plane's events."""
    from jax.profiler import ProfileData

    path = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    data = ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                out.append((i, ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return out


def test_trace_holds_one_batch_span_per_batch_with_its_children(tmp_path):
    ds, eng = small()
    loop = ServingLoop(eng, buckets=(1, 8), max_wait_s=0.002,
                       rerank_mult=2).start(warmup=True)
    m0 = loop.metrics()
    jax.profiler.start_trace(str(tmp_path))
    try:
        futs = [loop.submit(ds.queries[i], k=10) for i in range(8)]
        for f in futs:
            f.result(timeout=60)
        for i in range(3):
            loop.submit(ds.queries[i], k=10).result(timeout=60)
            time.sleep(0.01)
        settle(loop, m0.rows_served + 11)
        m1 = loop.metrics()
        loop.stop()  # every dispatch-thread span has closed
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    batches = [e for e in events if e[1] == "repro.serve.batch"]
    assert len(batches) == m1.batches - m0.batches >= 2
    rows = m1.rows_served - m0.rows_served
    assert sum(e[4]["rows"] for e in batches) == rows
    assert sorted(e[4]["batch"] for e in batches) == list(
        range(len(batches)))
    assert {e[0] for e in batches} == {batches[0][0]}  # one thread
    line = batches[0][0]
    for _, _, s, e, stats in batches:
        assert stats["bucket"] in (1, 8) and stats["rows"] <= stats["bucket"]
        inside = [ev[1] for ev in events
                  if ev[0] == line and s <= ev[2] and ev[3] <= e
                  and ev[1] != "repro.serve.batch"]
        for child in CHILDREN:
            assert inside.count(child) == 1, (child, inside)
    assert any(ev[0] == line and ev[1] == "repro.batcher.next_batch"
               for ev in events)


def test_fused_pipeline_metadata_names_every_stage():
    ds, eng = small()
    st = eng._state
    compiled = engine_mod._fused_pipeline.lower(
        eng.coarse, st.index, st.base, None, ds.queries, None, None, None,
        None, k=10, nprobe=4, r=2, scan_impl=eng.config.scan_impl,
        rerank_impl=eng.config.rerank_impl, ef=eng.config.ef).compile()
    names = re.findall(r'op_name="([^"]+)"', compiled.as_text())
    scopes = {part for n in names if n.startswith("jit(_pipeline)/")
              for part in n.split("/")[1:2]}
    assert set(STAGES) <= scopes, scopes

