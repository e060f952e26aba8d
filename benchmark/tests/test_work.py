"""Work functions and the peak table, against counts made by hand."""

import json

import numpy as np
import pytest

from benchmark import peaks
from benchmark.work import fused_knn, ivf_scan


def test_fused_knn_dispatch_by_hand():
    # 3 queries over 5 rows of dim 4, f32: 5*4*4 corpus + 3*4*4 queries
    assert fused_knn.dispatch(3, 5, 4) == (80 + 48, 2 * 3 * 5 * 4)


def test_fused_knn_counts_real_queries_not_buckets():
    inputs = {"n": 1000, "dim": 8, "itemsize": 4}
    b, f = fused_knn.totals(inputs, [np.arange(10), np.arange(3)])
    assert b == 2 * 1000 * 8 * 4 + 13 * 8 * 4
    assert f == 2 * 13 * 1000 * 8


def test_ivf_scan_dispatch_by_hand():
    sizes = np.array([5, 0, 7, 2])
    # query 0 probes lists 0, 2; query 1 probes 2, 3: distinct 0, 2, 3
    rows = np.array([[0, 2], [2, 3]])
    b, f = ivf_scan.dispatch(rows, sizes, dim=4)
    assert b == (5 + 7 + 2) * (4 * 4 + 4) + 2 * 4 * 4
    assert f == 2 * 4 * ((5 + 7) + (7 + 2))


def _padded(sizes, slots):
    ids = np.full((len(sizes), slots), -1, np.int32)
    nxt = 0
    for l, s in enumerate(sizes):
        ids[l, :s] = np.arange(nxt, nxt + s)
        nxt += s
    return ids


def test_padding_never_counts():
    sizes = [3, 1, 4, 1, 5]
    tight = ivf_scan.list_sizes(_padded(sizes, 5))
    loose = ivf_scan.list_sizes(_padded(sizes, 64))
    np.testing.assert_array_equal(tight, sizes)
    np.testing.assert_array_equal(loose, sizes)
    rows = np.array([[0, 4], [1, 2], [4, 3]])
    assert (ivf_scan.dispatch(rows, tight, 8)
            == ivf_scan.dispatch(rows, loose, 8))


def test_probes_rank_nearest_centers():
    centers = np.array([[0.0, 0.0], [10.0, 0.0], [0.0, 10.0]], np.float32)
    q = np.array([[9.0, 1.0], [1.0, 8.0]], np.float32)
    p = ivf_scan.probes(q, centers, 2)
    assert p.tolist() == [[1, 0], [2, 0]]


def test_totals_sum_dispatches():
    inputs = {"pool_probes": np.array([[0, 1], [1, 2], [2, 3]]),
              "sizes": np.array([1, 2, 3, 4]), "dim": 2, "itemsize": 4}
    b, f = ivf_scan.totals(inputs, [np.array([0, 1]), np.array([2])])
    b0, f0 = ivf_scan.dispatch(inputs["pool_probes"][[0, 1]],
                               inputs["sizes"], 2)
    b1, f1 = ivf_scan.dispatch(inputs["pool_probes"][[2]],
                               inputs["sizes"], 2)
    assert (b, f) == (b0 + b1, f0 + f1)


def test_peaks_table_v5e_and_unknown_kind(tmp_path):
    v5e = peaks.lookup("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["flops_per_s"] == 197e12
    with open(peaks.PATH) as fh:
        assert "Google Cloud" in json.load(fh)["source"]
    with pytest.raises(KeyError):
        peaks.lookup("TPU v99")


def test_ideal_seconds_takes_the_larger_bound():
    peak = {"hbm_bytes_per_s": 100.0, "flops_per_s": 1000.0}
    assert peaks.ideal_seconds(200, 1000, peak) == 2.0      # bytes bound
    assert peaks.ideal_seconds(100, 5000, peak) == 5.0      # flops bound
