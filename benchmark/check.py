"""The comparison that decides ``correct``.

Every answer that the window returned is compared with the plain exact
reference over the same corpus and queries. The numbers compared:

* ``dist_err``: the widest gap between a returned distance and the exact
  distance of the row it names, as a share of the query's exact k-th
  distance;
* ``incomplete``: the share of answers that leave out one or more of the
  query's exact k nearest rows (a row at the reference's k-th distance
  counts, whichever row the reference named). It sees which rows were
  chosen: IVF's probing leaves a few answers short, a scan whose order is
  off at the k-th distance leaves most of them short;
* ``bad_ids``: returned ids outside the corpus, or named twice in one
  answer (an exact count, limit 0);
* ``missing``: answers due in the window that never came or failed (an
  exact count, limit 0).

Recall@k over the same answers is returned beside them: a returned row is a
hit when its exact distance is no larger than the reference's k-th distance
(ties at the k-th distance count, whichever row the reference named), and
an answer that never came counts k misses. It is an end-to-end metric, not
a compared number: the control (the reference on float8 inputs) loses about
twice the recall that IVF's probing does, too little for a limit between
them. ``incomplete`` separates them, since the control's losses are spread
over most answers and the probing's gather in a few.

Answers repeated unchanged across batches (the same query, the same rows
and distances) are evaluated once.
"""

from __future__ import annotations

import numpy as np

ORDER = ("dist_err", "incomplete", "bad_ids", "missing")


def _row_stats(d, ids, exact, kth):
    """Per answer row: hits, widest relative distance gap, bad ids."""
    n_rows = exact.shape[0]
    valid = np.isfinite(exact)
    srt = np.sort(ids, axis=1)
    dup = np.zeros(ids.shape, bool)
    dup[:, 1:] = srt[:, 1:] == srt[:, :-1]
    bad = (~valid).sum(axis=1) + dup.sum(axis=1)
    hits = (valid & (exact <= kth[:, None])).sum(axis=1) - dup.sum(axis=1)
    scale = np.maximum(kth, 1.0)[:, None]
    with np.errstate(invalid="ignore"):
        gap = np.where(valid, np.abs(d.astype(np.float64) - exact) / scale,
                       0.0)
    err = gap.max(axis=1) if n_rows else np.zeros(0)
    return np.maximum(hits, 0), err, bad


def compare(qidx, d, ids, missing: int, ref_d, pair_dist,
            limits: dict) -> tuple:
    """({name: {"value", "limit"}} in ``ORDER``, recall@k).

    ``qidx`` (m,), ``d``/``ids`` (m, k): the answers the window returned;
    ``missing``: answers due that never came; ``ref_d`` (n_queries, k): the
    reference's exact distances, ascending; ``pair_dist(qidx, ids)``: exact
    distances of the named rows (inf for an id outside the corpus).
    """
    k = ref_d.shape[1]
    m = qidx.shape[0]
    if m:
        if ids.shape[1] != k or d.shape[1] != k:
            raise ValueError(f"answers have {ids.shape[1]} columns, k={k}")
        # evaluate each distinct (query, answer) once
        _, first = np.unique(qidx, return_index=True)
        first_of = np.empty(ref_d.shape[0], np.int64)
        first_of[qidx[first]] = first
        f = first_of[qidx]
        same = (ids == ids[f]).all(axis=1) & (d == d[f]).all(axis=1)
        evalr = np.flatnonzero(~same | (f == np.arange(m)))
        exact = pair_dist(qidx[evalr], ids[evalr])
        kth = ref_d[qidx[evalr], k - 1].astype(np.float64)
        hits_e, err_e, bad_e = _row_stats(d[evalr], ids[evalr], exact, kth)
        pos = np.full(m, -1, np.int64)
        pos[evalr] = np.arange(evalr.size)
        src = np.where(same, pos[f], pos)
        hits, err, bad = hits_e[src], err_e[src], bad_e[src]
        recall = hits.sum() / float(k * (m + missing))
        dist_err = float(err.max())
        incomplete = float((hits < k).mean())
        bad_ids = int(bad.sum())
    else:
        recall, dist_err, incomplete, bad_ids = 0.0, 0.0, 0.0, 0
    values = {"dist_err": dist_err, "incomplete": incomplete,
              "bad_ids": bad_ids, "missing": int(missing)}
    return ({name: {"value": values[name], "limit": limits[name]}
             for name in ORDER}, float(recall))


def passed(numbers: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in numbers.values())
