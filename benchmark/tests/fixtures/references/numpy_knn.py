"""Fixture reference, found by name: exact kNN under squared L2 in
plain NumPy (int64 for byte rows, float64 otherwise), over the whole
corpus fetched to the host. For test sizes only."""

from __future__ import annotations

import numpy as np


def _host(a) -> np.ndarray:
    a = np.asarray(a)
    return a.astype(np.int64 if a.dtype.kind in "iu" else np.float64)


def knn(x, queries, k: int):
    xh, qh = _host(x), _host(queries)
    full = ((qh[:, None, :] - xh[None]) ** 2).sum(-1)
    ids = np.argsort(full, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(full, ids, axis=1).astype(np.float64), ids


def true_distances(x, queries, ids):
    rows = _host(x)[ids]
    return ((rows - _host(queries)[:, None, :]) ** 2).sum(-1).astype(
        np.float64)
