"""The comparison that decides ``correct``.

Every answer the clients received is judged against the plain
reference the configuration names (``references/<name>.py``) by what
it says:

- ``dist_err``: the widest gap, over every returned (query, id) pair,
  between the distance the program reported and the float64 distance
  of that id from that query, as a share of the query's k-th exact
  distance. An id outside the corpus, a repeated id or a distance that
  is not finite reads infinity.
- ``miss``: one less the mean tie-aware recall@k over the queries
  answered in the window: a returned id counts when it is among the
  exact k or lies no farther than the exact k-th.
- ``failed``: requests that raised or never came back.

Answers repeat (every query of the pool is asked many times), so each
distinct answer is judged once and its verdict shared with its copies;
a copy is only a row whose ids and distances are bit-equal.
"""

from __future__ import annotations

import numpy as np


def judge_rows(x, pool, ref_d, ref_i, qids, dist, ids, true_distances):
    """Per answered row: ``(gap (m,), recall (m,))``; ``true_distances``
    is the reference's (``(x, queries, ids) -> float64 distances``)."""
    m, k = ids.shape
    n = int(x.shape[0])
    uq, first = np.unique(qids, return_index=True)
    first_of = np.zeros(int(pool.shape[0]), np.int64)
    first_of[uq] = first
    f = first_of[qids]
    same_d = (dist == dist[f]) | (np.isnan(dist) & np.isnan(dist[f]))
    same = (ids == ids[f]).all(axis=1) & same_d.all(axis=1)
    rep = np.where(same, f, np.arange(m))
    todo = np.unique(rep)

    i_t, d_t, q_t = ids[todo].astype(np.int64), dist[todo], qids[todo]
    s = np.sort(i_t, axis=1)
    dup = np.zeros_like(i_t, bool)
    dup_sorted = np.concatenate(
        [np.zeros((len(s), 1), bool), s[:, 1:] == s[:, :-1]], axis=1)
    # a repeated id marks every copy of it invalid
    for j in range(k):
        dup[:, j] = (dup_sorted & (s == i_t[:, j:j + 1])).any(axis=1)
    valid = (i_t >= 0) & (i_t < n) & ~dup & np.isfinite(d_t)
    true = true_distances(x, pool[q_t], np.where(valid, i_t, 0))
    kth = ref_d[q_t, k - 1]
    gap = np.where(valid, np.abs(d_t - true)
                   / np.maximum(kth, 1e-30)[:, None], np.inf).max(axis=1)
    in_ref = (i_t[:, :, None] == ref_i[q_t][:, None, :]).any(axis=2)
    hit = valid & (in_ref | (true <= kth[:, None]))
    recall = hit.sum(axis=1) / k

    pos = np.searchsorted(todo, rep)
    return gap[pos], recall[pos]


def judge(x, pool, ref, answers, limits: dict, true_distances) -> dict:
    """The numbers compared, each beside its limit, and the recall.

    ``x``: the corpus (the sharded ``jax.Array``); ``ref``: the
    reference's ``(distances, ids)``; ``answers``: ``(qids (m,), dist
    (m, k), ids (m, k), in_window (m,), failed)`` of every answered row;
    ``limits``: the configuration's ``limits``; ``true_distances``: the
    reference's.
    Returns ``{"checks": {name: {"value", "limit"}}, "recall": float,
    "correct": bool}``."""
    qids, dist, ids, in_window, failed = answers
    ref_d, ref_i = ref
    if len(qids):
        gap, recall = judge_rows(x, pool, ref_d, ref_i, qids, dist, ids,
                                 true_distances)
        dist_err = float(gap.max())
        win = recall[in_window]
        rec = float(win.mean()) if len(win) else 0.0
    else:
        dist_err, rec = float("inf"), 0.0
    checks = {
        "dist_err": {"value": dist_err, "limit": limits.get("dist_err")},
        "miss": {"value": 1.0 - rec, "limit": limits.get("miss")},
        "failed": {"value": int(failed), "limit": 0},
    }
    correct = all(c["limit"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    return {"checks": checks, "recall": rec, "correct": correct}
