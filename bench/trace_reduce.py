"""Profiler trace (``.xplane.pb``) -> device busy time, time per device
operation, and the longest idle gaps with what the host was doing in them.

Reads the planes JAX's profiler writes: each ``/device:TPU:<n>`` plane has an
``XLA Ops`` line whose events are the operations that ran on that chip (the
``Async XLA Ops`` line holds copies in flight beside them and is not busy
time). An event's name is its HLO instruction, ``%<op>.<n> = ...``; a Pallas
kernel's op is named after the kernel's entry point
(``fastscan_stream_topk``, ``rerank_stream_topk``). Host threads are the
lines of the ``/host:CPU`` plane.
"""
from __future__ import annotations

import re
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
TOP = 10


class Summary(NamedTuple):
    chips: int                # device planes with operations on them
    busy_s: float             # union of operation intervals, mean over chips
    span_s: float             # first operation start -> last end, chip 0
    op_s: dict                # op name (".<n>" suffix dropped) -> seconds,
    #                           summed over chips
    gaps: list                # [(label, seconds)] longest idle gaps, chip 0


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def merge(intervals: list) -> list:
    """Union of (start, end) intervals, ascending and disjoint."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _label(gap: tuple, host: list) -> str:
    """The host event that covers most of the gap (ns intervals)."""
    best, cover = "no host event", 0.0
    gs, ge = gap
    for name, s, e in host:
        c = min(ge, e) - max(gs, s)
        if c > cover:
            best, cover = name, c
    return best


def reduce(path: str) -> Summary:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    per_chip: dict = {}
    op_s: dict = {}
    host: list = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ivs = per_chip.setdefault(int(m.group(1)), [])
                for ev in line.events:
                    s, d = ev.start_ns, ev.duration_ns
                    ivs.append((s, s + d))
                    name = op_name(ev.name)
                    op_s[name] = op_s.get(name, 0.0) + d * 1e-9
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    host.append((ev.name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns))
    chips = sorted(c for c, ivs in per_chip.items() if ivs)
    if not chips:
        return Summary(0, 0.0, 0.0, {}, [])
    busy = [sum(e - s for s, e in merge(per_chip[c])) * 1e-9 for c in chips]
    first = merge(per_chip[chips[0]])
    span = (first[-1][1] - first[0][0]) * 1e-9
    holes = [(a[1], b[0]) for a, b in zip(first, first[1:])]
    holes.sort(key=lambda g: g[0] - g[1])
    gaps = [(_label(g, host), (g[1] - g[0]) * 1e-9) for g in holes[:TOP]]
    return Summary(len(chips), sum(busy) / len(busy), span, op_s, gaps)


def kernel_seconds(summary: Summary, patterns) -> float:
    """Seconds of every operation whose name contains one of ``patterns``."""
    return sum(t for name, t in summary.op_s.items()
               if any(p in name for p in patterns))


def top_ops(summary: Summary) -> list:
    return [[n, t] for n, t in sorted(summary.op_s.items(),
                                      key=lambda kv: -kv[1])[:TOP]]
