"""The one traffic generator: a mix file's parameters -> requests on a
``ServingLoop``, with the due, send and done time of every request.

Kinds (``bench/traffic/<mix>.json``):

  open_poisson  independent users: Poisson arrivals at ``rate_qps``
  open_bursty   on/off Poisson: the mean is ``rate_qps``; each ``period_s``
                starts with ``burst_s`` at ``burst_mult`` x the mean, the rest
                runs at the multiplier that keeps the mean
  closed        ``outstanding`` callers, each sending its next request when
                the last one returns

Every seed gets the same set of inter-arrival gaps (the quantiles of the
exponential distribution, mapped through the mix's rate profile) in an order
drawn from the seed, and asks the pool's queries in rounds, each round every
query once in an order drawn from the seed. So seeds differ in order, not in
how much work arrives or which queries it holds. An open-loop request is timed from when
it was due; a closed-loop one from when it was sent.

A result's numbers are copied into preallocated arrays as it arrives and the
result itself is dropped, so the generator holds no Python objects per
request inside the serving process, as a deployment's clients would not.
"""
from __future__ import annotations

import functools
import queue
import time
from typing import NamedTuple

import numpy as np

KINDS = ("open_poisson", "open_bursty", "closed")
# the most requests a closed loop may send in one window
CLOSED_CAP = 1 << 18
# the ServeResult work counts kept per request, in ``Sent.work``'s columns
WORK = ("lists_probed", "codes_scanned", "reranked")


class Sent(NamedTuple):
    """What ``drive`` recorded, one row per request sent in the window."""

    pool_idx: np.ndarray  # (R,) i64 which pool query
    due: np.ndarray       # (R,) f64 perf_counter seconds it was due
    sent: np.ndarray      # (R,) f64 when submit returned
    done: np.ndarray      # (R,) f64 when its result arrived (nan = never)
    ok: np.ndarray        # (R,) bool answered without an error
    ids: np.ndarray       # (R, k) i32 the answer's ids (rows with ok)
    dists: np.ndarray     # (R, k) f32 the answer's distances
    work: np.ndarray      # (R, len(WORK)) i64 the answer's work counts
    t0: float             # window start (perf_counter)
    t1: float             # window end
    open_loop: bool

    def __len__(self) -> int:
        return self.pool_idx.shape[0]


def rate_profile(mix: dict) -> tuple[np.ndarray, np.ndarray]:
    """(edges, rates): one period of the piecewise-constant offered rate."""
    kind = mix["kind"]
    rate = float(mix["rate_qps"])
    if kind == "open_poisson":
        return np.array([0.0, 1.0]), np.array([rate])
    if kind == "open_bursty":
        period, burst = float(mix["period_s"]), float(mix["burst_s"])
        hi = float(mix["burst_mult"])
        lo = (period - hi * burst) / (period - burst)
        if not 0 < burst < period or lo <= 0:
            raise ValueError(f"bursty mix cannot keep its mean: {mix}")
        return np.array([0.0, burst, period]), rate * np.array([hi, lo])
    raise ValueError(f"{kind!r} has no rate profile")


def arrivals(mix: dict, seconds: float, rng: np.random.Generator
             ) -> np.ndarray:
    """Due offsets in [0, seconds) of an open-loop mix, ascending."""
    edges, rates = rate_profile(mix)
    period = edges[-1]
    per_period = float(np.sum(np.diff(edges) * rates))
    total = per_period * seconds / period   # expected arrivals
    n = max(1, int(round(total)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)   # Exp(1) quantiles
    unit = np.cumsum(rng.permutation(gaps))        # unit-rate arrivals
    unit *= total / unit[-1] * (n - 0.5) / n       # the last lands in-window
    # invert the cumulative intensity, period by period
    whole, rest = np.divmod(unit, per_period)
    cum = np.concatenate([[0.0], np.cumsum(np.diff(edges) * rates)])
    seg = np.clip(np.searchsorted(cum, rest, side="right") - 1, 0,
                  len(rates) - 1)
    t = whole * period + edges[seg] + (rest - cum[seg]) / rates[seg]
    return t[t < seconds]


def query_order(n_pool: int, n: int, rng: np.random.Generator
                ) -> np.ndarray:
    """The pool query of each of ``n`` requests: rounds of the whole pool,
    each in an order of its own."""
    rounds = -(-n // n_pool)
    return np.concatenate([rng.permutation(n_pool)
                           for _ in range(rounds)])[:n]


def drive(loop, pool: np.ndarray, mix: dict, seconds: float, seed: int,
          k: int, wait_s: float = 60.0) -> Sent:
    """Offer the mix to ``loop`` for ``seconds``, then wait up to ``wait_s``
    for every request sent in the window. The calling thread is the only
    sender; results are stamped by a done-callback."""
    kind = mix["kind"]
    if kind not in KINDS:
        raise ValueError(f"unknown traffic kind {kind!r}; want one of {KINDS}")
    rng = np.random.default_rng([seed, 0x7A1F])
    n_pool = pool.shape[0]
    open_loop = kind != "closed"
    if open_loop:
        offsets = arrivals(mix, seconds, rng)
        cap = offsets.shape[0]
    else:
        offsets = None
        cap = CLOSED_CAP
    pool_idx = query_order(n_pool, cap, rng)
    due = np.full(cap, np.nan)
    sent = np.full(cap, np.nan)
    done = np.full(cap, np.nan)
    ok = np.zeros(cap, bool)
    ids = np.full((cap, k), -1, np.int32)
    dists = np.full((cap, k), np.nan, np.float32)
    work = np.zeros((cap, len(WORK)), np.int64)
    finished: queue.SimpleQueue = queue.SimpleQueue()

    def on_done(i, fut):
        t = time.perf_counter()
        try:
            r = fut.result()
        except Exception:  # recorded as not ok; judged as failed
            pass
        else:
            ids[i] = r.ids
            dists[i] = r.dists
            work[i] = [getattr(r, w) for w in WORK]
            ok[i] = True
        done[i] = t  # last: a stamped request is fully recorded
        finished.put(i)

    def send(i, t_due):
        due[i] = t_due
        fut = loop.submit(pool[pool_idx[i]], k=k)
        sent[i] = time.perf_counter()
        fut.add_done_callback(functools.partial(on_done, i))

    t0 = time.perf_counter() + 0.05
    t1 = t0 + seconds
    n_sent = 0
    if open_loop:
        for i, off in enumerate(offsets):
            t_due = t0 + off
            lead = t_due - time.perf_counter()
            if lead > 0:
                time.sleep(lead)
            send(i, t_due)
        n_sent = cap
    else:
        while time.perf_counter() < t0:
            pass
        for i in range(int(mix["outstanding"])):
            send(i, t0)
        n_sent = int(mix["outstanding"])
        while n_sent < cap:
            left = t1 - time.perf_counter()
            if left <= 0:
                break
            try:
                finished.get(timeout=left)
            except queue.Empty:
                break
            if time.perf_counter() >= t1:
                break
            send(n_sent, time.perf_counter())
            n_sent += 1
        if n_sent == cap:
            raise RuntimeError(f"the closed loop sent {cap} requests, all "
                               "it can record; raise CLOSED_CAP")
    deadline = max(time.perf_counter(), t1) + wait_s
    while (np.isnan(done[:n_sent]).any()
           and time.perf_counter() < deadline):
        time.sleep(0.005)
    # copies: a callback that comes after the wait writes into the originals
    return Sent(pool_idx[:n_sent], due[:n_sent], sent[:n_sent],
                done[:n_sent].copy(), ok[:n_sent].copy(),
                ids[:n_sent].copy(), dists[:n_sent].copy(),
                work[:n_sent].copy(), t0, t1, open_loop)
