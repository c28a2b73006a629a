"""Bytes each kernel needs for the requests it served, from the program's
per-request work counts (``ServeResult``) and the configuration's shapes.

They count the work the algorithm needs for real queries: the true
occupancy of the probed lists and the candidates actually refined, not the
padding rows or tiles a kernel happens to touch, so they read the same
whatever implements the stage. Both kernels are bound by HBM bytes on the
v5e's peaks: a code row is M/2 bytes against M int8 table lookups and adds,
a refined row D*4 bytes against 3*D float operations.
"""
from __future__ import annotations


def scan_bytes(sums: dict, config: dict) -> float:
    """4-bit codes of every live row scanned, plus each (query, list) u8
    ADC table (M x 16 bytes)."""
    m = config["build"]["m"]
    return sums["codes_scanned"] * m / 2 + sums["lists_probed"] * m * 16


def rerank_bytes(sums: dict, config: dict) -> float:
    """The f32 rows refined plus each refining query's own row."""
    d = config["data"]["dim"]
    queries = sums["refining_queries"]
    return (sums["reranked"] + queries) * d * 4


def share(nbytes: float, seconds: float, peaks: dict) -> float | None:
    """Percent of the HBM roofline: the least time those bytes could take
    over the kernel's measured device time."""
    if not seconds or not nbytes:
        return None
    return 100.0 * nbytes / peaks["hbm_bytes_per_s"] / seconds
