"""The comparison that decides ``correct`` fails what it must.

- the control (the reference computed in bfloat16, put in the
  program's place) fails the configurations' own limits, at a size a
  test holds (the byte control: ``test_sharded.py``);
- a whole run, past the look for a chip, with the timed path broken
  underneath, reports ``correct: false`` for each fault a search cell
  can have: an answer altered where it is produced, and half of each
  batch left out (its rows answered with the other half's results);
  and, in IVF cells, a search cut to a quarter of its probes, the
  speed a change could buy with accuracy (``miss`` catches it); and, in
  the four-chip fixture cell, the exchange between chips left out (each
  chip's own top-k returned unmerged). A step returning its state
  unchanged has no place in a search cell.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from benchmark import check, data, run, spec

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures")
FIX_BENCH = os.path.join(FIXTURES, "BENCHMARK.json")
DIRS = (FIXTURES, spec.BENCH_DIR)
reference = spec.load_module((spec.BENCH_DIR,), "references", "exact_knn")


def _real_limits(config):
    with open(os.path.join(spec.BENCH_DIR, "configs",
                           config + ".json")) as fh:
        return json.load(fh)["limits"]


@pytest.mark.parametrize("config", ["ivf_flat-sift1m", "brute_force-sift1m"])
@pytest.mark.parametrize("seed", [5, 2**40 + 7, 3000000001])
def test_control_fails_the_limits(config, seed):
    x, pool = data.make_data(seed, seed + 1, n=8192, dim=128, n_queries=256,
                             n_clusters=64, intrinsic_dim=16,
                             center_scale=1.0, noise=0.05)
    ref = reference.knn(x, pool, 10)
    d, i = reference.control(x, pool, 10)
    answers = (np.arange(256), d, i, np.ones(256, bool), 0)
    out = check.judge(x, np.asarray(pool), ref, answers,
                      _real_limits(config), reference.true_distances)
    assert out["correct"] is False
    assert out["checks"]["dist_err"]["value"] > (
        _real_limits(config)["dist_err"])


def test_reference_matches_brute_numpy():
    x, q = data.make_data(11, 12, n=3000, dim=16, n_queries=50, n_clusters=8,
                          intrinsic_dim=8, center_scale=1.0, noise=0.05)
    d, i = reference.knn(x, q, 10, q_block=16)
    xh, qh = np.asarray(x, np.float64), np.asarray(q, np.float64)
    full = ((qh[:, None, :] - xh[None]) ** 2).sum(-1)
    np.testing.assert_array_equal(i, np.argsort(full, axis=1)[:, :10])
    np.testing.assert_allclose(d, np.sort(full, axis=1)[:, :10], rtol=1e-12)


def test_judge_reads_invalid_answers_as_infinite():
    x, q = data.make_data(3, 4, n=2000, dim=8, n_queries=4, n_clusters=4,
                          intrinsic_dim=4, center_scale=1.0, noise=0.05)
    ref = reference.knn(x, q, 3)
    ids = ref[1].copy()
    dist = ref[0].astype(np.float32)
    ids[1, 2] = ids[1, 0]                      # a repeated id
    ids[2, 0] = -1                             # no id
    answers = (np.arange(4), dist, ids, np.ones(4, bool), 0)
    out = check.judge(x, np.asarray(q), ref, answers,
                      {"dist_err": 1.0, "miss": 1.0}, reference.true_distances)
    assert out["checks"]["dist_err"]["value"] == np.inf
    assert out["correct"] is False


def _break(monkeypatch, request, fault):
    import jax

    from raft_tpu.core.executor import SearchExecutor
    from raft_tpu.distributed import ivf as dist_ivf

    if fault == "exchange_left_out":
        monkeypatch.setattr(dist_ivf, "merge_results_sharded",
                            lambda best_d, best_i, *a, **kw: (best_d, best_i))
        jax.clear_caches()                  # no program traced before
        request.addfinalizer(jax.clear_caches)   # nor kept after
    real = SearchExecutor.search_blocks

    def broken(self, index, blocks, k, params=None, **kw):
        if fault == "probes_cut":
            params = dataclasses.replace(params,
                                         n_probes=max(1, params.n_probes // 4))
        outs = real(self, index, blocks, k, params=params, **kw)
        d = np.concatenate([np.asarray(o[0]) for o in outs])
        i = np.concatenate([np.asarray(o[1]) for o in outs])
        if fault == "altered":
            i[:, 0] = (i[:, 0] + 1) % index_rows(index)
        elif fault == "half_left_out":
            half = (len(i) + 1) // 2
            i[half:] = i[:len(i) - half]
            d[half:] = d[:len(d) - half]
        res, s = [], 0
        for b in blocks:
            m = len(b)
            res.append((d[s:s + m], i[s:s + m]))
            s += m
        return res

    monkeypatch.setattr(SearchExecutor, "search_blocks", broken)


def index_rows(index):
    return int(getattr(index, "size", 0)) or int(index.dataset.shape[0])


@pytest.mark.parametrize("fault,workload", [
    ("altered", "tiny_ivf.bulk"), ("altered", "tiny_bf.b10"),
    ("half_left_out", "tiny_ivf.bulk"), ("half_left_out", "tiny_bf.b10"),
    ("probes_cut", "tiny_ivf.bulk"), ("probes_cut", "tiny_ivf.single"),
    ("altered", "tiny_u8_mesh.bulk"), ("half_left_out", "tiny_u8_mesh.bulk"),
    ("probes_cut", "tiny_u8_mesh.bulk"),
    ("exchange_left_out", "tiny_u8_mesh.bulk")])
def test_broken_timed_path_is_not_correct(capsys, monkeypatch, request,
                                          fault, workload):
    _break(monkeypatch, request, fault)
    rc = run.main(["--workload", workload, "--seed", "77", "--seconds",
                   "0.5", "--trace", "0"], bench_path=FIX_BENCH, dirs=DIRS)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert res["correct"] is False
