"""Plain reference of the served search, and the comparison that decides
``correct``.

The reference answers a query the way IVF + 4-bit PQ fast-scan is defined
(arXiv:2203.02505 §2-§4, faiss ``IVFxxx,PQ16x4fs[,RFlat]``), in float64 NumPy
on the host, sharing no code with the program:

  1. coarse: squared L2 to every centroid, the ``nprobe`` nearest lists;
  2. per (query, list): the residual's ADC table ``T[m, j]`` (squared L2 of
     each sub-vector to each of the 16 codewords), quantised to u8 per table
     (bias = row minimum, one scale mapping the largest shifted entry to
     255, round half to even), the score of a row is
     ``scale * sum_m T8[m, code_m] + sum_m bias_m``;
  3. the ``keep`` best scores over every live row of the probed lists
     (``keep = rerank_mult * k``, or ``k`` without refinement);
  4. with refinement, the exact float64 squared L2 of those candidates, and
     the ``k`` smallest of that.

Steps 1 and 2 take squared L2 as ``|a|^2 - 2 a.b + |b|^2`` (clamped at 0)
with the cross term at the matmul precision the configuration states: the
platform's default, which on a TPU is one bfloat16 pass (both operands
rounded to bfloat16, products summed exactly) and elsewhere exact
(``matmul`` = ``"bf16"`` or ``"exact"``). Everything else is float64.

It reads the deployment's stored index: the centroids and PQ codebook the
build trained, and each list's row ids and packed 4-bit codes (unpacked
here, low nibble first). It reads none of the program's per-query work (no
probe set, table, scale or candidate), and the base rows are the
benchmark's own. What it trusts of the stored index is checked too: over a
sample of the base rows it re-derives each row's list (the nearest
centroid) and codes (the nearest codeword of each sub-vector of the row's
float32 residual to its list's centroid) from the trained centroids and
codewords, so the only program-made inputs it takes on trust are those two
tables, whose quality recall measures.

The compared numbers, per cell, over a sample of the requests answered:

  missing    requests due in the window that never got an answer
  malformed  answers with an id out of range or repeated, distances not
             ascending, or fewer ids than the reference found
  dist_err   the widest gap between a served distance and the reference's
             value of the same id (the exact distance with refinement, its
             u8 ADC score without), over the reference's k-th value
  rank_gap   the widest gap by which the served ids, ranked by the
             reference's value, lie above the reference's own answer at the
             same rank, over the reference's k-th value
  worse_share  the share of answers with such a gap above ``WORSE_TOL``
  misfiled   the share of sampled rows not stored, or stored in a list
             whose centroid is not the nearest
  miscoded   the share of the sampled rows' codes (one per sub-vector)
             whose codeword is not the nearest

"The nearest" is taken twice, with the distance's cross term at the stated
matmul precision (one bfloat16 pass: operands rounded to bfloat16, products
summed exactly) and exactly; a choice passes if it is the nearest by either,
to within float32 sums (``dim * 2**-23`` of the terms' magnitude). So a
build at the stated precision or above passes, and a lower one, or a row
filed or coded wrongly, does not.

The control is this reference at the precision below the one each stage's
guarantee states, the step a later change would be tempted by: the re-rank
on bfloat16 rows (``rerank_bf16``), 4-bit ADC tables (``lut_int4``), and
the build's assignment and encoding with float8 (e4m3) operands
(``build_fp8``), some or all.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

NUMBERS = ("missing", "malformed", "dist_err", "rank_gap", "worse_share",
           "misfiled", "miscoded")
CONTROLS = ("rerank_bf16", "lut_int4", "build_fp8")
MATMULS = ("bf16", "exact")
# a rank gap above this share of the k-th value makes an answer worse
WORSE_TOL = 1e-5


class IndexView(NamedTuple):
    """Host copies of the index state a search reads."""

    centroids: np.ndarray  # (nlist, D) f64
    codewords: np.ndarray  # (M, 16, dsub) f64
    codes: np.ndarray      # (nlist, cap, M) u8, one 4-bit code per byte
    ids: np.ndarray        # (nlist, cap) i64, -1 = empty slot
    where: np.ndarray      # (n_ids, 2) i64: global id -> (list, slot),
    #                        -1 where no stored row holds the id
    matmul: str            # the coarse and table matmuls' precision


def view_of(engine, matmul: str) -> IndexView:
    """Read the deployment's stored index out of a built ``SearchEngine``;
    ``matmul`` is the precision its platform runs a default matmul at."""
    if matmul not in MATMULS:
        raise ValueError(f"unknown matmul precision {matmul!r}")
    index = engine.index
    packed = np.asarray(index.lists.codes)
    codes = np.empty(packed.shape[:2] + (packed.shape[2] * 2,), np.uint8)
    codes[..., 0::2] = packed & 0x0F
    codes[..., 1::2] = packed >> 4
    ids = np.asarray(index.lists.ids).astype(np.int64)
    lst, slot = np.nonzero(ids >= 0)
    where = np.full((int(ids.max()) + 1, 2), -1, np.int64)
    where[ids[lst, slot]] = np.stack([lst, slot], axis=1)
    return IndexView(np.asarray(index.centroids, np.float64),
                     np.asarray(index.codebook.codewords, np.float64),
                     codes, ids, where, matmul)


def _bf16(x: np.ndarray) -> np.ndarray:
    import ml_dtypes
    return x.astype(np.float32).astype(ml_dtypes.bfloat16).astype(np.float64)


def _fp8(x: np.ndarray) -> np.ndarray:
    import ml_dtypes
    return (x.astype(np.float32).astype(ml_dtypes.float8_e4m3fn)
            .astype(np.float64))


def _sqdist(a: np.ndarray, b: np.ndarray, matmul: str) -> np.ndarray:
    """(..., n) squared L2 of rows ``a`` (..., d) to rows ``b`` (..., n, d),
    the cross term at ``matmul`` precision, clamped at 0."""
    ca, cb = (_bf16(a), _bf16(b)) if matmul == "bf16" else (a, b)
    cross = np.einsum("...d,...nd->...n", ca, cb)
    d = (np.sum(a * a, axis=-1)[..., None] - 2.0 * cross
         + np.sum(b * b, axis=-1))
    return np.maximum(d, 0.0)


def _tables(view: IndexView, q: np.ndarray, lists: np.ndarray, bits: int):
    """u8 (or ``bits``-bit) ADC tables of ``q`` against ``lists``:
    (levels (P, M, 16) i64, scale (P,), bias_sum (P,))."""
    m, _, dsub = view.codewords.shape
    resid = (q[None, :] - view.centroids[lists]).reshape(len(lists), m, dsub)
    t = _sqdist(resid, view.codewords[None], view.matmul)   # (P, M, 16)
    bias = t.min(axis=-1)
    shifted = t - bias[..., None]
    top = 2 ** bits - 1
    scale = np.maximum(shifted.max(axis=(1, 2)), 1e-20) / top
    levels = np.clip(np.rint(shifted / scale[:, None, None]), 0, top)
    return levels.astype(np.int64), scale, bias.sum(axis=-1)


def _scores(view: IndexView, q, lists, slots_per_list, bits: int):
    """ADC scores of the given slots of the given lists."""
    levels, scale, bias = _tables(view, q, lists, bits)
    m = view.codes.shape[-1]
    out = []
    for p, (lst, slots) in enumerate(zip(lists, slots_per_list)):
        c = view.codes[lst, slots].astype(np.int64)          # (R, M)
        acc = levels[p, np.arange(m)[None, :], c].sum(axis=1)
        out.append(scale[p] * acc + bias[p])
    return out


def _exact(base: np.ndarray, q: np.ndarray, ids: np.ndarray,
           bf16: bool = False) -> np.ndarray:
    x = np.asarray(base[ids], np.float64)
    if bf16:
        import ml_dtypes
        x = x.astype(ml_dtypes.bfloat16).astype(np.float32)
        qq = q.astype(ml_dtypes.bfloat16).astype(np.float32)
        return np.sum((qq[None, :] - x) ** 2, axis=-1, dtype=np.float32
                      ).astype(np.float64)
    return np.sum((q[None, :] - x) ** 2, axis=-1)


class Answer(NamedTuple):
    ids: np.ndarray    # (k,) i64, -1 past the rows found
    vals: np.ndarray   # (k,) f64 distance (refined) or ADC score


def search(view: IndexView, base: np.ndarray, q: np.ndarray, *, nprobe: int,
           rerank_mult: int, k: int, control: tuple = ()) -> Answer:
    """The reference's answer for one query (or the control's)."""
    q = np.asarray(q, np.float64)
    bits = 4 if "lut_int4" in control else 8
    dc = _sqdist(q, view.centroids, view.matmul)
    lists = np.argsort(dc, kind="stable")[:nprobe]
    slots = [np.nonzero(view.ids[lst] >= 0)[0] for lst in lists]
    scores = np.concatenate(_scores(view, q, lists, slots, bits))
    ids = np.concatenate([view.ids[lst, s] for lst, s in zip(lists, slots)])
    keep = rerank_mult * k if rerank_mult else k
    order = np.argsort(scores, kind="stable")[:keep]
    ids, vals = ids[order], scores[order]
    if rerank_mult:
        vals = _exact(base, q, ids, bf16="rerank_bf16" in control)
        order = np.argsort(vals, kind="stable")[:k]
        ids, vals = ids[order], vals[order]
    pad = k - ids.shape[0]
    if pad > 0:
        ids = np.concatenate([ids, np.full(pad, -1, np.int64)])
        vals = np.concatenate([vals, np.full(pad, np.inf)])
    return Answer(ids, vals)


def value_of(view: IndexView, base: np.ndarray, q: np.ndarray,
             ids: np.ndarray, rerank_mult: int) -> np.ndarray:
    """The reference's value of arbitrary stored ids for ``q``: the exact
    distance with refinement, else the u8 ADC score in the id's own list."""
    q = np.asarray(q, np.float64)
    if rerank_mult:
        return _exact(base, q, ids)
    lists, slots = view.where[ids].T
    uniq = np.unique(lists)
    got = _scores(view, q, uniq, [slots[lists == lst] for lst in uniq], 8)
    out = np.empty(ids.shape[0])
    for lst, sc in zip(uniq, got):
        out[lists == lst] = sc
    return out


def judge(view: IndexView, base: np.ndarray, queries: np.ndarray,
          served_ids: np.ndarray, served_dists: np.ndarray, *, nprobe: int,
          rerank_mult: int, k: int, n_base: int) -> dict:
    """The compared numbers (except ``missing``) over answered requests.

    queries (R, D); served_ids (R, k) i64; served_dists (R, k) f64.
    """
    malformed = worse = checked = 0
    dist_err = 0.0
    rank_gap = 0.0
    cache: dict = {}
    for q, sid, sd in zip(queries, served_ids, served_dists):
        key = q.tobytes()
        if key not in cache:
            cache[key] = search(view, base, q, nprobe=nprobe,
                                rerank_mult=rerank_mult, k=k)
        ref = cache[key]
        n_ref = int(np.sum(ref.ids >= 0))
        real = sid >= 0
        n = int(real.sum())
        ok = (n >= n_ref and bool(np.all(real[:n]))
              and bool(np.all((sid[:n] >= 0) & (sid[:n] < n_base)))
              and np.unique(sid[:n]).shape[0] == n
              and bool(np.all(np.isfinite(sd[:n])))
              and bool(np.all(np.diff(sd[:n]) >= 0))
              and bool(np.all(sid[:n] < view.where.shape[0]))
              and bool(np.all(view.where[np.minimum(
                  sid[:n], view.where.shape[0] - 1), 0] >= 0)))
        if not ok:
            malformed += 1
            continue
        if n == 0:
            continue
        kth = max(float(ref.vals[n_ref - 1]), 1e-30)
        mine = value_of(view, base, q, sid[:n], rerank_mult)
        dist_err = max(dist_err, float(np.max(np.abs(sd[:n] - mine))) / kth)
        gap = float(np.max(np.sort(mine)[:n_ref] - ref.vals[:n_ref],
                           initial=0.0)) / kth
        rank_gap = max(rank_gap, gap)
        worse += gap > WORSE_TOL
        checked += 1
    return {"malformed": malformed, "dist_err": dist_err,
            "rank_gap": rank_gap,
            "worse_share": worse / checked if checked else 0.0}


def stored(view: IndexView, rows: np.ndarray
           ) -> tuple[np.ndarray, np.ndarray]:
    """What the index stores for base rows ``rows``: (list (n,) i64, -1
    where no list holds the row; codes (n, M) i64)."""
    where = np.full((rows.shape[0], 2), -1, np.int64)
    inside = rows < view.where.shape[0]
    where[inside] = view.where[rows[inside]]
    lst, slot = where.T
    codes = view.codes[np.maximum(lst, 0), np.maximum(slot, 0)]
    return lst, codes.astype(np.int64)


def _dot(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """(..., n): each row of ``a`` (..., d) against the rows of ``b``
    ((n, d), or (..., n, d) broadcast against ``a``)."""
    if b.ndim == 2:
        return a @ b.T
    return np.einsum("...d,...nd->...n", a, b)


def _nearest(a: np.ndarray, b: np.ndarray, round_to=None) -> np.ndarray:
    """(...,) index of the nearest row of ``b`` to each row of ``a``
    (``_dot``'s shapes), the cross term on operands rounded by
    ``round_to``."""
    ca, cb = (round_to(a), round_to(b)) if round_to else (a, b)
    return np.argmin(np.sum(b * b, axis=-1) - 2.0 * _dot(ca, cb), axis=-1)


def _residual(view: IndexView, x: np.ndarray, lists: np.ndarray
              ) -> np.ndarray:
    """(n, M, dsub): the float32 residual of rows ``x`` to their lists'
    centroids, split into sub-vectors, as float64."""
    m, _, dsub = view.codewords.shape
    r = (x.astype(np.float32)
         - view.centroids[lists].astype(np.float32)).astype(np.float64)
    return r.reshape(x.shape[0], m, dsub)


def control_build(view: IndexView, base: np.ndarray, rows: np.ndarray, *,
                  control: list) -> tuple[np.ndarray, np.ndarray]:
    """The list and codes the control files rows ``rows`` under: the
    nearest centroid and codewords with float8 operands where ``control``
    holds ``build_fp8``, else as the reference does."""
    rnd = _fp8 if "build_fp8" in control else None
    x = np.asarray(base[rows], np.float64)
    lists = _nearest(x, view.centroids, rnd)
    codes = _nearest(_residual(view, x, lists), view.codewords[None], rnd)
    return lists, codes


def _misplaced(a: np.ndarray, b: np.ndarray, chosen: np.ndarray,
               matmul: str) -> np.ndarray:
    """(...,) bool: ``chosen`` (an index into ``b``'s rows) is not the
    nearest row of ``b`` to ``a``, exactly nor at ``matmul`` precision
    (module docstring); ``_dot``'s shapes."""
    a2 = np.sum(a * a, axis=-1)
    norm = np.sum(b * b, axis=-1) + np.zeros(a.shape[:-1] + (1,))
    cross = _dot(np.abs(a), np.abs(b))
    f32 = a.shape[-1] * 2.0 ** -23

    def at(v, i):
        return np.take_along_axis(v, i[..., None], axis=-1)[..., 0]

    def farther(dot):
        d = a2[..., None] - 2.0 * dot + norm
        best = np.argmin(d, axis=-1)
        slack = f32 * (at(cross, chosen) + at(cross, best) + 2.0 * a2
                       + at(norm, chosen) + at(norm, best))
        return at(d, chosen) - at(d, best) > slack

    far = farther(_dot(a, b))
    if matmul == "bf16":
        far &= farther(_dot(_bf16(a), _bf16(b)))
    return far


def judge_build(view: IndexView, base: np.ndarray, rows: np.ndarray,
                lists: np.ndarray, codes: np.ndarray) -> dict:
    """``misfiled`` and ``miscoded`` of base rows ``rows`` filed under
    ``lists`` (n,) (-1 = not stored) with ``codes`` (n, M)."""
    x = np.asarray(base[rows], np.float64)
    held = lists >= 0
    lists = np.maximum(lists, 0)
    misfiled = ~held | _misplaced(x, view.centroids, lists, view.matmul)
    miscoded = _misplaced(_residual(view, x, lists), view.codewords[None],
                          codes, view.matmul)
    return {"misfiled": float(np.mean(misfiled)),
            "miscoded": float(np.mean(miscoded))}


def control_answers(view: IndexView, base: np.ndarray, queries: np.ndarray,
                    *, control: list, nprobe: int, rerank_mult: int, k: int):
    """The control put in the program's place: (ids (R, k), dists (R, k)).
    ``control`` lists the stages computed a precision lower."""
    control = tuple(control)
    if set(control) - set(CONTROLS):
        raise ValueError(f"unknown control {control!r}; want some of "
                         f"{CONTROLS}")
    out = [search(view, base, q, nprobe=nprobe, rerank_mult=rerank_mult,
                  k=k, control=control) for q in queries]
    return (np.stack([a.ids for a in out]), np.stack([a.vals for a in out]))
