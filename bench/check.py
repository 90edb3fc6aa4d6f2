"""The comparison that decides ``correct``.

Over a sample of the answers the timed path gave (drawn from the seed),
against the reference (``reference.py``):

* ``dist_err``: the widest gap between a served distance and the exact
  float64 distance of the id it was served with, over the reference's k-th
  distance of that query. Catches distances computed in a lower precision
  than the configuration's float32 and ids altered after their distance was
  taken. Limit from the configuration's ``check.dist_err_max``, set from
  readings (PERF.md).
* ``bad_ids``: answers that break a guarantee: an id that is no object
  (``-1`` marks an empty slot, a miss that recall counts), an object
  outside the query's range, or an id twice in one answer. Exact: limit 0.
* ``errors``: requests that resolved with an exception other than the
  deadline and admission policy's (rejected, shed, timed out). Limit 0.
* ``recall_at_10``: mean recall@k of the sample against the exact top-k.
  The configuration states its floor (``check.recall_at_10_min``).
"""
from __future__ import annotations

import numpy as np

from traffic import ERROR, OK


def sample(log, seed: int, size: int) -> np.ndarray:
    """Indices of up to ``size`` answered requests, drawn from the seed."""
    ok = np.flatnonzero(log.outcome == OK)
    if len(ok) <= size:
        return ok
    rng = np.random.default_rng([seed, 3])
    return np.sort(rng.choice(ok, size, replace=False))


def compare(coll, reqs, log, idx: np.ndarray, limits: dict) -> dict:
    """Numbers compared, each as {"value", "limit", "ok"}; plus the sample
    size."""
    k = reqs.k
    ref_ids, ref_d = coll.topk(reqs.vectors[idx], reqs.L[idx], reqs.R[idx],
                               k)
    dist_err = 0.0
    bad = 0
    hits = 0
    due = 0
    for row, j in enumerate(idx):
        got = log.ids[j]
        want = ref_ids[row]
        in_range = int(reqs.R[j] - reqs.L[j] + 1)
        valid = got >= 0
        ids = got[valid]
        known = ids < coll.n
        a = coll.attr[ids[known]]
        bad += int(not known.all() or len(np.unique(ids)) < len(ids)
                   or not np.all((a >= reqs.lo[j]) & (a <= reqs.hi[j])))
        valid[valid] = known
        ids = ids[known]
        hits += len(set(ids.tolist()) & set(want[want >= 0].tolist()))
        due += min(k, in_range)
        if len(ids):
            exact = coll.exact_dist(reqs.vectors[j], ids)
            scale = max(float(ref_d[row, min(k, in_range) - 1]), 1e-30)
            gap = np.abs(log.dists[j][valid].astype(np.float64) - exact)
            dist_err = max(dist_err, float(gap.max()) / scale)
    recall = hits / max(due, 1)
    errors = int((log.outcome[:log.sent] == ERROR).sum())
    return {
        "dist_err": _le(dist_err, limits["dist_err_max"]),
        "bad_ids": _le(bad, 0),
        "errors": _le(errors, 0),
        "recall_at_10": _ge(recall, limits["recall_at_10_min"]),
        "_sample": len(idx),
    }


def _le(v, limit):
    return {"value": v, "limit": limit, "ok": bool(v <= limit)}


def _ge(v, limit):
    return {"value": v, "limit": limit, "ok": bool(v >= limit)}
