"""Profiler trace (``.xplane.pb``) -> device time per pipeline stage, the
serving loop's host spans, and device idle time split by the span the
dispatch thread was in.

    python3 bench/spans.py <trace.xplane.pb>

prints the reduction as one JSON line. It reads what the program writes into
the trace (``docs/serving.md``, "Tracing a live loop"):

- **Stages.** The fused pipeline runs each stage under a ``jax.named_scope``
  (``STAGES``); the scope reaches each device op's ``tf_op`` stat in the
  trace's event metadata, which ``jax.profiler.ProfileData`` does not expose,
  so this module decodes the XSpace protobuf itself. An op takes the first
  stage named in its ``tf_op`` path. An op XLA inserted (a copy, a layout
  change) has no stage there: it takes the stage of the op that produced its
  first operand that has one (the operands are in the HLO text of the event's
  name), else of the first op that consumes it, else ``unattributed``.
- **Host spans.** The ``repro.*`` spans (``jax.profiler.TraceAnnotation``)
  on the host threads that hold a ``repro.serve.batch`` span: the serving
  loop's dispatch thread. Per span name: count, total and self time (less
  the time of its direct children).
- **Idle split.** Every device-idle interval of chip 0, from the first to
  the last event traced on chip 0 or in a dispatch-thread span, cut by the
  innermost dispatch-thread span in effect; time outside every span is
  ``NO_SPAN``. The parts and the busy time sum to that traced time.
"""
from __future__ import annotations

import bisect
import json
import re
import struct
import sys
from pathlib import Path
from typing import NamedTuple

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace_reduce  # noqa: E402

STAGES = ("coarse", "scan", "rerank", "merge", "stats")
UNATTRIBUTED = "unattributed"
SPAN_PREFIX = "repro."
BATCH_SPAN = "repro.serve.batch"
NO_SPAN = "no span"
SCAN_KERNEL = "fastscan_stream_topk"
OPERAND = re.compile(r"%([\w.\-]+)")


# -- the protobuf wire format, as far as XSpace needs it ---------------------

def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one message: an int for a
    varint, a memoryview for a length-delimited field, bytes for fixed."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire == 1:
            value, i = bytes(buf[i:i + 8]), i + 8
        elif wire == 5:
            value, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _map_entry(buf) -> tuple[int, object]:
    key = value = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _stats(bufs, stat_names: dict) -> dict:
    """XStat messages -> {stat name: value}; a ``ref_value`` is the name of
    the stat metadata it points at (how XPlane stores repeated strings)."""
    out = {}
    for buf in bufs:
        sid, value = None, None
        for f, v in _fields(buf):
            if f == 1:
                sid = v
            elif f == 2:
                value = struct.unpack("<d", v)[0]
            elif f in (3, 4):
                value = _signed(v) if f == 4 else v
            elif f in (5, 6):
                value = bytes(v).decode("utf-8", "replace")
            elif f == 7:
                value = stat_names.get(v, "")
        out[stat_names.get(sid, str(sid))] = value
    return out


class Event(NamedTuple):
    name: str
    start_ns: int
    duration_ns: int
    stats: list       # its own XStat messages, undecoded (``Plane.stats``)
    meta: int         # its XEventMetadata id


class Line(NamedTuple):
    name: str
    events: list


class Plane(NamedTuple):
    name: str
    lines: list
    meta: dict        # event metadata id -> (name, stats)
    stat_names: dict  # stat metadata id -> name

    def stats(self, event: Event) -> dict:
        """An event's own stats (a span's arguments), decoded."""
        return _stats(event.stats, self.stat_names)


def _event(buf, t0: int, names: dict) -> Event:
    """One XEvent; the hot loop of a large trace, so decoded inline."""
    i, end = 0, len(buf)
    mid = off = dur = 0
    stats = []
    while i < end:
        key = buf[i]  # every XEvent field number and wire type fits a byte
        i += 1
        if key & 7 == 0:
            value, i = _varint(buf, i)
            f = key >> 3
            if f == 1:
                mid = value
            elif f == 2:
                off = value
            elif f == 3:
                dur = value
        elif key & 7 == 2:
            n, i = _varint(buf, i)
            if key == 0x22:  # field 4, length-delimited: an XStat
                stats.append(buf[i:i + n])
            i += n
        else:
            raise ValueError(f"unexpected XEvent wire type {key & 7}")
    return Event(names.get(mid, ""), t0 + off // 1000, dur // 1000, stats,
                 mid)


def read_xspace(path) -> list:
    """Every plane of a trace file, with its lines' events and its event
    metadata. Times are whole ns, ``timestamp_ns + offset_ps // 1000`` and
    ``duration_ps // 1000``, as ``ProfileData`` reports them."""
    planes = []
    for f, pbuf in _fields(memoryview(Path(path).read_bytes())):
        if f != 1:
            continue
        name, line_bufs, meta_bufs, stat_names = "", [], [], {}
        for pf, v in _fields(pbuf):
            if pf == 2:
                name = bytes(v).decode()
            elif pf == 3:
                line_bufs.append(v)
            elif pf == 4:
                meta_bufs.append(v)
            elif pf == 5:
                sid, sbuf = _map_entry(v)
                for sf, sv in _fields(sbuf):
                    if sf == 2:
                        stat_names[sid] = bytes(sv).decode()
        meta = {}
        for buf in meta_bufs:
            mid, mbuf = _map_entry(buf)
            mname, mstats = "", []
            for mf, mv in _fields(mbuf):
                if mf == 2:
                    mname = bytes(mv).decode("utf-8", "replace")
                elif mf == 5:
                    mstats.append(mv)
            meta[mid] = (mname, _stats(mstats, stat_names))
        names = {mid: m[0] for mid, m in meta.items()}
        lines = []
        for lbuf in line_bufs:
            lname, t0, events = "", 0, []
            for lf, lv in _fields(lbuf):
                if lf == 2:
                    lname = bytes(lv).decode()
                elif lf == 3:
                    t0 = lv
                elif lf == 4:
                    events.append(lv)
            lines.append(Line(lname, [_event(ebuf, t0, names)
                                      for ebuf in events]))
        planes.append(Plane(name, lines, meta, stat_names))
    return planes


# -- stages -----------------------------------------------------------------

def stage_of_path(tf_op) -> str | None:
    """The first stage scope in a ``tf_op`` path, or None."""
    for part in str(tf_op or "").split("/"):
        if part in STAGES:
            return part
    return None


def op_stages(meta: dict) -> dict:
    """Event metadata id -> stage, for one device plane's ops."""
    by_name: dict = {}   # (program, HLO name) -> metadata id
    operands: dict = {}  # metadata id -> [metadata id of each operand]
    for mid, (text, stats) in meta.items():
        head, _, rest = text.partition(" = ")
        by_name[(stats.get("program_id"), head.lstrip("%"))] = mid
        operands[mid] = rest
    users: dict = {mid: [] for mid in meta}
    for mid, rest in operands.items():
        prog = meta[mid][1].get("program_id")
        found = [by_name[(prog, n)] for n in OPERAND.findall(rest)
                 if (prog, n) in by_name]
        operands[mid] = found
        for o in found:
            users[o].append(mid)
    own = {mid: stage_of_path(stats.get("tf_op"))
           for mid, (_, stats) in meta.items()}

    def follow(mid, edges, seen):
        if own[mid] is not None:
            return own[mid]
        seen.add(mid)
        for nxt in edges[mid]:
            if nxt not in seen:
                stage = follow(nxt, edges, seen)
                if stage is not None:
                    return stage
        return None

    return {mid: (follow(mid, operands, set()) or follow(mid, users, set())
                  or UNATTRIBUTED) for mid in meta}


# -- host spans and the idle split ------------------------------------------

def innermost(spans: list) -> list:
    """Properly nested (start, end, name) spans of one thread -> disjoint
    (start, end, name) segments, each named by the innermost span."""
    out: list = []
    stack: list = []   # (end, name)
    cur = float("-inf")

    def emit(upto):
        nonlocal cur
        if stack and upto > cur:
            out.append((cur, upto, stack[-1][1]))
        cur = max(cur, upto)

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        emit(s)
        stack.append((min(e, stack[-1][0]) if stack else e, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


def split(intervals: list, segments: list) -> dict:
    """Seconds of the disjoint, ascending ``intervals`` under each segment's
    name; time under no segment goes to ``NO_SPAN``."""
    parts: dict = {}
    j = 0
    for s, e in intervals:
        covered = 0.0
        while j < len(segments) and segments[j][1] <= s:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < e:
            c = min(e, segments[k][1]) - max(s, segments[k][0])
            if c > 0:
                parts[segments[k][2]] = parts.get(segments[k][2], 0.0) + c
                covered += c
            k += 1
        if e - s - covered > 0:
            parts[NO_SPAN] = parts.get(NO_SPAN, 0.0) + (e - s - covered)
    return {n: ns * 1e-9 for n, ns in parts.items()}


def self_times(spans: list) -> dict:
    """name -> {count, total_s, self_s} over one thread's nested spans."""
    out: dict = {}
    stack: list = []   # [end, name, ns, children's ns]

    def close(_end, name, dur, children):
        rec = out.setdefault(name, {"count": 0, "total_s": 0.0,
                                    "self_s": 0.0})
        rec["count"] += 1
        rec["total_s"] += dur * 1e-9
        rec["self_s"] += (dur - children) * 1e-9

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            close(*stack.pop())
        if stack:
            e = min(e, stack[-1][0])
            stack[-1][3] += e - s
        stack.append([e, name, e - s, 0.0])
    while stack:
        close(*stack.pop())
    return out


class Batch(NamedTuple):
    start_ns: float
    end_ns: float
    rows: int
    bucket: int
    device_s: float   # chip 0's busy time inside the span
    host_s: float     # the span less that


class Reduction(NamedTuple):
    chips: int
    busy_s: float       # union of op intervals, mean over chips
    span_s: float       # chip 0's first op start -> last op end
    traced_s: float     # the first to the last of those ops and the
    #                     dispatch thread's spans
    op_s: dict          # op name -> seconds, as trace_reduce.reduce
    stages: dict        # stage (or UNATTRIBUTED) -> seconds, all chips
    scan_kernel_s: float  # stage ``scan`` time of the scan kernel's ops
    spans: dict         # repro.* span name -> {count, total_s, self_s}
    batches: list       # [Batch] one per repro.serve.batch span
    idle_by_span: dict  # innermost dispatch-thread span -> idle seconds
    gaps: list          # [(label, seconds)] longest idle gaps of chip 0


def reduce(path) -> Reduction:
    planes = read_xspace(path)
    per_chip: dict = {}
    op_s: dict = {}
    stages: dict = {}
    scan_kernel = 0.0
    span_lines: list = []
    host: list = []
    for plane in planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m:
            stage = op_stages(plane.meta)
            for line in plane.lines:
                if line.name != trace_reduce.OPS_LINE:
                    continue
                ivs = per_chip.setdefault(int(m.group(1)), [])
                for ev in line.events:
                    ivs.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                    name = trace_reduce.op_name(ev.name)
                    op_s[name] = op_s.get(name, 0.0) + ev.duration_ns * 1e-9
                    st = stage.get(ev.meta, UNATTRIBUTED)
                    stages[st] = stages.get(st, 0.0) + ev.duration_ns * 1e-9
                    if st == "scan" and SCAN_KERNEL in name:
                        scan_kernel += ev.duration_ns * 1e-9
        elif plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                host += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                         for ev in line.events]
                if any(ev.name == BATCH_SPAN for ev in line.events):
                    span_lines.append((plane, [
                        ev for ev in line.events
                        if ev.name.startswith(SPAN_PREFIX)]))
    chips = sorted(c for c, ivs in per_chip.items() if ivs)
    first = trace_reduce.merge(per_chip[chips[0]]) if chips else []
    busy = [sum(e - s for s, e in trace_reduce.merge(per_chip[c])) * 1e-9
            for c in chips]
    ends = [e for _, e in first]

    def busy_within(s, e):
        """chip 0's busy ns inside [s, e]."""
        i = bisect.bisect_right(ends, s)
        total = 0.0
        while i < len(first) and first[i][0] < e:
            total += min(e, first[i][1]) - max(s, first[i][0])
            i += 1
        return total

    spans: dict = {}
    segments: list = []
    batches: list = []
    for plane, events in span_lines:
        nested = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                  for ev in events]
        for name, rec in self_times(nested).items():
            agg = spans.setdefault(name, {"count": 0, "total_s": 0.0,
                                          "self_s": 0.0})
            for key in agg:
                agg[key] += rec[key]
        segments += innermost(nested)
        for ev in events:
            if ev.name == BATCH_SPAN:
                dev = busy_within(ev.start_ns, ev.start_ns + ev.duration_ns)
                args = plane.stats(ev)
                batches.append(Batch(
                    ev.start_ns, ev.start_ns + ev.duration_ns,
                    int(args.get("rows", 0)), int(args.get("bucket", 0)),
                    dev * 1e-9, (ev.duration_ns - dev) * 1e-9))
    segments.sort()
    batches.sort()
    holes = [(a[1], b[0]) for a, b in zip(first, first[1:])]
    # the idle split covers all that was traced: chip 0's ops and the
    # dispatch thread's spans, which begin and end with the trace
    outer = first[:1] + first[-1:] + segments[:1] + segments[-1:]
    ends_at = [t for iv in outer for t in iv[:2]]
    lo, hi = (min(ends_at), max(ends_at)) if ends_at else (0, 0)
    edges = [(lo, first[0][0]), (first[-1][1], hi)] if first else [(lo, hi)]
    idle = split(sorted(g for g in holes + edges if g[1] > g[0]), segments)
    longest = sorted(holes, key=lambda g: g[0] - g[1])[:trace_reduce.TOP]
    gaps = []
    for g in longest:
        parts = split([g], segments)
        parts.pop(NO_SPAN, None)
        label = (max(parts, key=parts.get) if parts
                 else trace_reduce._label(g, host))
        gaps.append((label, (g[1] - g[0]) * 1e-9))
    return Reduction(
        len(chips), sum(busy) / len(busy) if busy else 0.0,
        (first[-1][1] - first[0][0]) * 1e-9 if first else 0.0,
        (hi - lo) * 1e-9, op_s, stages, scan_kernel, spans, batches, idle,
        gaps)


# -- what the per-layer metrics read -----------------------------------------

def host_ms_per_batch(red: Reduction) -> float | None:
    """Mean over ``repro.serve.batch`` spans of the span less chip 0's busy
    time inside it, in ms: the host's share of a batch."""
    if not red.batches:
        return None
    return 1e3 * sum(b.host_s for b in red.batches) / len(red.batches)


def scan_prep_ms_per_batch(red: Reduction) -> float | None:
    """Device time of stage ``scan`` outside the scan kernel per batch, in
    ms: the tables, the code store's relayout and the candidate pool."""
    if not red.batches or "scan" not in red.stages:
        return None
    return 1e3 * (red.stages["scan"] - red.scan_kernel_s) / len(red.batches)


def queue_wait_ms(loop: dict) -> float | None:
    """Mean wait from submit to dequeue per served row, in ms, from the
    loop's ``queue_wait_s`` and ``rows_served`` deltas (None where the loop
    has no such counter)."""
    if loop.get("queue_wait_s") is None or not loop.get("rows_served"):
        return None
    return 1e3 * loop["queue_wait_s"] / loop["rows_served"]


def breakdown(red: Reduction) -> dict:
    """The reduction as a result line's ``breakdown`` entries."""
    by_bucket: dict = {}
    for b in red.batches:
        by_bucket.setdefault(b.bucket, []).append(b.host_s)
    return {
        "device_stages": dict(sorted(red.stages.items(),
                                     key=lambda kv: -kv[1])),
        "scan_kernel_s": red.scan_kernel_s,
        "host_spans": red.spans,
        "host_ms_per_batch_by_bucket": {
            str(k): 1e3 * sum(v) / len(v)
            for k, v in sorted(by_bucket.items())},
        "idle_by_span": dict(sorted(red.idle_by_span.items(),
                                    key=lambda kv: -kv[1])),
        "idle_gaps": [[n, s] for n, s in red.gaps],
    }


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__.split("\n\n")[1].strip(), file=sys.stderr)
        return 2
    red = reduce(args[0])
    print(json.dumps({
        "chips": red.chips, "busy_s": red.busy_s, "span_s": red.span_s,
        "traced_s": red.traced_s,
        "batches": len(red.batches),
        "host_ms_per_batch": host_ms_per_batch(red),
        "scan_prep_ms_per_batch": scan_prep_ms_per_batch(red),
        **breakdown(red)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
