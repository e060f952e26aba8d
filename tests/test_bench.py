"""Benchmark harness tests — dataset tree, runner, export, plot
(reference ``python/raft-ann-bench`` CLI behavior)."""

import json

import numpy as np
import pytest

from raft_tpu.bench.datasets import convert_hdf5, make_dataset
from raft_tpu.bench.runner import export_csv, plot_results, run_benchmark
from raft_tpu.io import read_bin


@pytest.fixture(scope="module")
def dataset_dir(tmp_path_factory):
    out = tmp_path_factory.mktemp("data")
    return make_dataset(out, "tiny", n=3000, dim=16, n_queries=50, k=20)


class TestDatasets:
    def test_tree_layout(self, dataset_dir):
        assert (dataset_dir / "base.fbin").exists()
        assert (dataset_dir / "query.fbin").exists()
        base = read_bin(dataset_dir / "base.fbin")
        gt = read_bin(dataset_dir / "groundtruth.neighbors.ibin")
        assert base.shape == (3000, 16)
        assert gt.shape == (50, 20)
        # groundtruth sanity: ids in range, first column is true NN
        assert gt.min() >= 0 and gt.max() < 3000

    def test_hdf5_conversion(self, tmp_path, rng_np):
        import h5py

        h5 = tmp_path / "toy.hdf5"
        train = rng_np.standard_normal((200, 8)).astype(np.float32)
        test = rng_np.standard_normal((10, 8)).astype(np.float32)
        with h5py.File(h5, "w") as f:
            f["train"] = train
            f["test"] = test
            f.attrs["distance"] = "euclidean"
        root = convert_hdf5(h5, tmp_path / "out")
        np.testing.assert_allclose(read_bin(root / "base.fbin"), train)
        assert (root / "metric.txt").read_text().strip() == "euclidean"


class TestRunner:
    def test_run_export_plot(self, dataset_dir, tmp_path):
        config = {
            "algos": [
                {"name": "raft_brute_force", "search": [{}]},
                {
                    "name": "raft_ivf_flat",
                    "build": {"n_lists": 32},
                    "search": [{"n_probes": 4}, {"n_probes": 32}],
                },
            ]
        }
        rows = run_benchmark(dataset_dir, config, tmp_path / "res",
                             k=10, search_iters=1)
        assert len(rows) == 3
        bf = rows[0]
        assert bf["algo"] == "raft_brute_force"
        assert bf["recall"] > 0.999          # exact search
        assert bf["qps"] > 0
        # sweeping n_probes to all lists reaches ~exact recall
        assert rows[2]["recall"] >= rows[1]["recall"]
        assert rows[2]["recall"] > 0.95

        csv_path = export_csv(tmp_path / "res")
        text = csv_path.read_text()
        assert "raft_ivf_flat" in text and "qps" in text

        png = plot_results(tmp_path / "res")
        assert png.exists() and png.stat().st_size > 1000

    def test_index_cache_round_trip(self, dataset_dir, tmp_path):
        """Second run on the same out_dir reloads the saved index
        (reference benchmark.hpp build/search phase separation) with
        identical search quality; --force-rebuild rebuilds."""
        config = {
            "algos": [
                {"name": "raft_ivf_flat", "build": {"n_lists": 32},
                 "search": [{"n_probes": 32}]},
                {"name": "raft_cagra",
                 "build": {"graph_degree": 16,
                           "intermediate_graph_degree": 24,
                           "build_algo": "cluster_join"},
                 "search": [{"itopk_size": 32}]},
            ]
        }
        out = tmp_path / "res"
        first = run_benchmark(dataset_dir, config, out, k=10,
                              search_iters=1)
        assert all(not r["build_cached"] for r in first)
        idx_files = sorted((out / "indexes").glob("*.bin"))
        assert len(idx_files) == 2, idx_files

        second = run_benchmark(dataset_dir, config, out, k=10,
                               search_iters=1)
        assert all(r["build_cached"] for r in second)
        for a, b in zip(first, second):
            assert a["recall"] == b["recall"], (a, b)

        third = run_benchmark(dataset_dir, config, out, k=10,
                              search_iters=1, force_rebuild=True)
        assert all(not r["build_cached"] for r in third)

    def test_resume_and_algo_filter(self, dataset_dir, tmp_path):
        """resume=True skips combinations already in results.jsonl and
        appends the rest (the interrupted-TPU-sweep recovery path);
        only_algos restricts the sweep to the named families."""
        config = {
            "algos": [
                {"name": "raft_brute_force", "search": [{}]},
                {"name": "raft_ivf_flat", "build": {"n_lists": 32},
                 "search": [{"n_probes": 4}, {"n_probes": 32}]},
            ]
        }
        out = tmp_path / "res"
        only = run_benchmark(dataset_dir, config, out, k=10,
                             search_iters=1,
                             only_algos=["raft_brute_force"])
        assert [r["algo"] for r in only] == ["raft_brute_force"]

        # simulate the interrupted sweep: results.jsonl holds only the
        # brute-force row; resume must keep it and add the ivf rows
        resumed = run_benchmark(dataset_dir, config, out, k=10,
                                search_iters=1, resume=True)
        assert [r["algo"] for r in resumed] == [
            "raft_brute_force", "raft_ivf_flat", "raft_ivf_flat"]
        lines = [json.loads(line) for line in
                 (out / "results.jsonl").read_text().splitlines()]
        assert len(lines) == 3

        # resuming a complete sweep is a no-op that reports every row
        again = run_benchmark(dataset_dir, config, out, k=10,
                              search_iters=1, resume=True)
        assert len(again) == 3
        assert len((out / "results.jsonl").read_text()
                   .splitlines()) == 3

        # a resumed per-family step returns only that family's rows
        one = run_benchmark(dataset_dir, config, out, k=10,
                            search_iters=1, resume=True,
                            only_algos=["raft_brute_force"])
        assert [r["algo"] for r in one] == ["raft_brute_force"]

        # rows measured at a different search_iters don't satisfy the
        # resume (they re-measure and append)
        deeper = run_benchmark(dataset_dir, config, out, k=10,
                               search_iters=2, resume=True,
                               only_algos=["raft_brute_force"])
        assert len(deeper) == 1
        assert len((out / "results.jsonl").read_text()
                   .splitlines()) == 4

    def test_resume_rewrites_legacy_backendless_rows(self, dataset_dir,
                                                     tmp_path):
        """A row written before the backend field existed cannot prove
        it was measured on THIS backend: resume re-measures the combo
        and drops the stale row from the file (keeping both would
        double up the export/plot)."""
        config = {"algos": [
            {"name": "raft_brute_force", "search": [{}]},
            {"name": "raft_ivf_flat", "build": {"n_lists": 16},
             "search": [{"n_probes": 4}]},
        ]}
        out = tmp_path / "res"
        first = run_benchmark(dataset_dir, config, out, k=10,
                              search_iters=1)
        out_file = out / "results.jsonl"
        bf_row, ivf_row = [json.loads(line) for line in
                           out_file.read_text().splitlines()]
        del bf_row["backend"]
        # a backend-less row from some OTHER dataset must survive
        foreign = dict(bf_row, dataset="other-ds")
        out_file.write_text("\n".join(json.dumps(r) for r in
                                      (bf_row, ivf_row, foreign)) + "\n")

        # a combo the resumed invocation will NOT re-measure (filtered
        # out by only_algos) must keep its legacy row: dropping without
        # replacing would lose measured data
        run_benchmark(dataset_dir, config, out, k=10, search_iters=1,
                      resume=True, only_algos=["raft_ivf_flat"])
        rows = [json.loads(line) for line in
                out_file.read_text().splitlines()]
        assert sum("backend" not in r for r in rows) == 2  # bf + foreign

        resumed = run_benchmark(dataset_dir, config, out, k=10,
                                search_iters=1, resume=True)
        assert len(resumed) == 2
        rows = [json.loads(line) for line in
                out_file.read_text().splitlines()]
        # this sweep's legacy brute-force row was replaced (with the
        # backend field); the foreign dataset's stayed as-is
        by_ds = {}
        for r in rows:
            by_ds.setdefault(r.get("dataset"), []).append(r)
        assert len(by_ds["other-ds"]) == 1
        assert "backend" not in by_ds["other-ds"][0]
        this_ds = by_ds[dataset_dir.name]
        assert len(this_ds) == 2
        assert all(r["backend"] == first[0]["backend"] for r in this_ds)

    def test_require_cached_index(self, dataset_dir, tmp_path):
        """require_cached_index fails fast (host-side) when a saveable
        algo's cache misses, instead of building on the measurement
        device; saveless brute_force is exempt; a cached family runs."""
        config = {
            "algos": [
                {"name": "raft_brute_force", "search": [{}]},
                {"name": "raft_ivf_flat", "build": {"n_lists": 32},
                 "search": [{"n_probes": 4}]},
            ]
        }
        out = tmp_path / "res"
        with pytest.raises(RuntimeError, match="require_cached_index"):
            run_benchmark(dataset_dir, config, out, k=10, search_iters=1,
                          require_cached_index=True)
        # brute force (no index file) ran and flushed before the raise
        lines = (out / "results.jsonl").read_text().splitlines()
        assert [json.loads(line)["algo"] for line in lines] == [
            "raft_brute_force"]

        # populate the cache, then the guarded run succeeds
        run_benchmark(dataset_dir, config, out, k=10, search_iters=1,
                      only_algos=["raft_ivf_flat"])
        rows = run_benchmark(dataset_dir, config, out, k=10,
                             search_iters=1, require_cached_index=True)
        assert [r["algo"] for r in rows] == [
            "raft_brute_force", "raft_ivf_flat"]
        assert rows[1]["build_cached"]

    def test_cli(self, dataset_dir, tmp_path):
        from raft_tpu.bench.__main__ import main

        cfg = tmp_path / "cfg.json"
        cfg.write_text(json.dumps(
            {"algos": [{"name": "raft_brute_force", "search": [{}]}]}
        ))
        rc = main([
            "run", "--dataset", str(dataset_dir), "--config", str(cfg),
            "--out-dir", str(tmp_path / "res2"), "-k", "5",
            "--search-iters", "1",
        ])
        assert rc == 0
        assert (tmp_path / "res2" / "results.jsonl").exists()


class TestReferenceConfigSchema:
    def test_normalize_reference_config(self):
        from raft_tpu.bench.runner import normalize_config

        ref = {
            "dataset": {"name": "x", "distance": "euclidean"},
            "index": [
                {"algo": "raft_bfknn", "build_param": {},
                 "search_params": [{"probe": 1}]},
                {"algo": "hnswlib", "build_param": {"M": 12},
                 "search_params": [{"ef": 10}]},
                {"algo": "raft_ivf_pq",
                 "build_param": {"niter": 25, "nlist": 1000, "pq_dim": 64,
                                 "pq_bits": 8, "ratio": 2},
                 "search_params": [{"nprobe": 20,
                                    "internalDistanceDtype": "float"}]},
                {"algo": "raft_cagra", "build_param": {"graph_degree": 32},
                 "search_params": [{"itopk": 32}, {"itopk": 64}]},
                # competitor with no wrapper here: must be dropped
                {"algo": "faiss_gpu_ivf_flat", "build_param": {"nlist": 64},
                 "search_params": [{"nprobe": 4}]},
            ],
        }
        cfg = normalize_config(ref)
        names = [a["name"] for a in cfg["algos"]]
        # hnswlib has a wrapper (the native C++ baseline), so a
        # reference conf naming it runs the competitor series; faiss/
        # ggnn wrap other libraries and are dropped.
        assert names == ["raft_brute_force", "hnswlib", "raft_ivf_pq",
                         "raft_cagra"]
        hnsw = cfg["algos"][1]
        assert hnsw["build"] == {"M": 12}
        assert hnsw["search"] == [{"ef": 10}]
        pq = cfg["algos"][2]
        assert pq["build"] == {"kmeans_n_iters": 25, "n_lists": 1000,
                               "pq_dim": 64, "pq_bits": 8,
                               "kmeans_trainset_fraction": 0.5}
        assert pq["search"] == [{"n_probes": 20}]
        assert cfg["algos"][3]["search"] == [{"itopk_size": 32},
                                             {"itopk_size": 64}]
        # native schema passes through untouched
        native = {"algos": [{"name": "raft_brute_force"}]}
        assert normalize_config(native) is native

    def test_runs_with_reference_schema(self, tmp_path):
        import json

        from raft_tpu.bench.datasets import make_dataset
        from raft_tpu.bench.runner import run_benchmark

        root = make_dataset(tmp_path, "tiny", n=2000, dim=16, n_queries=50,
                            k=10)
        ref_cfg = {"index": [
            {"algo": "raft_ivf_flat", "build_param": {"nlist": 16},
             "search_params": [{"nprobe": 8}, {"nprobe": 16}]},
        ]}
        rows = run_benchmark(root, ref_cfg, tmp_path / "out", k=10,
                             search_iters=1)
        assert len(rows) == 2
        assert rows[1]["recall"] >= 0.99


class TestPrims:
    def test_suite_runs_and_reports(self):
        from raft_tpu.bench.prims import run_prims

        recs = run_prims(size="tiny", name_filter="pairwise", budget_s=0.5)
        assert len(recs) == 1
        rec = recs[0]
        assert rec["prim"] == "pairwise_l2"
        for field in ("ms", "gbps", "bw_frac", "mfu", "shape", "backend"):
            assert field in rec
        assert rec["ms"] > 0 and rec["gbps"] > 0

    def test_out_jsonl(self, tmp_path):
        import json

        from raft_tpu.bench.prims import run_prims

        out = tmp_path / "prims.jsonl"
        run_prims(size="tiny", name_filter="select_k_xla", budget_s=0.5,
                  out_path=str(out))
        lines = out.read_text().strip().splitlines()
        assert len(lines) == 1
        assert json.loads(lines[0])["prim"] == "select_k_xla"


class TestCagraBundleRefine:
    def test_refine_uses_raw_base(self, rng_np):
        """Regression (review r3): with storage_dtype the index holds a
        quantized copy — refine must re-rank against the RAW f32 base,
        and refined distances must therefore be exact f32 L2."""
        import jax.numpy as jnp

        from raft_tpu.bench.runner import _cagra_build, _cagra_search
        from raft_tpu.distance.types import DistanceType

        c = rng_np.standard_normal((6, 128)) * 5
        x = (c[rng_np.integers(0, 6, 1200)]
             + rng_np.standard_normal((1200, 128))).astype(np.float32)
        q = (c[rng_np.integers(0, 6, 8)]
             + rng_np.standard_normal((8, 128))).astype(np.float32)
        bundle = _cagra_build(x, DistanceType.L2Expanded,
                              graph_degree=16,
                              intermediate_graph_degree=32,
                              build_algo="NN_DESCENT",
                              storage_dtype="bfloat16")
        assert bundle["index"].dataset.dtype == jnp.bfloat16
        assert np.asarray(bundle["base"]).dtype == np.float32
        d, i = _cagra_search(bundle, q, 5, itopk_size=32,
                             search_width=4, refine_ratio=2.0)
        ref = np.sum((q[:, None] - x[np.asarray(i)]) ** 2, axis=2)
        np.testing.assert_allclose(np.asarray(d), ref, rtol=1e-4,
                                   atol=1e-3)


class TestHnswCpuBaseline:
    """The native C++ HNSW competitor wrapper (the reference's hnswlib
    comparison role, ``cpp/bench/ann/src/hnswlib/hnswlib_wrapper.h``)."""

    def test_build_search_recall(self, dataset_dir, tmp_path):
        pytest.importorskip("ctypes")
        from raft_tpu.bench import hnsw_cpu

        if not hnsw_cpu.available():
            pytest.skip("native HNSW library could not be built")
        config = {
            "algos": [{
                "name": "hnswlib",
                "build": {"M": 8, "ef_construction": 100},
                "search": [{"ef": 10}, {"ef": 100}],
            }]
        }
        rows = run_benchmark(dataset_dir, config, tmp_path / "res",
                             k=10, search_iters=1)
        assert len(rows) == 2
        assert all(r["algo"] == "hnswlib" for r in rows)
        # higher ef -> higher recall; ef=100 on a 3000-row set is ~exact
        assert rows[1]["recall"] >= rows[0]["recall"]
        assert rows[1]["recall"] > 0.9
        assert rows[1]["qps"] > 0

    def test_index_cache_round_trip(self, dataset_dir, tmp_path):
        from raft_tpu.bench import hnsw_cpu

        if not hnsw_cpu.available():
            pytest.skip("native HNSW library could not be built")
        config = {
            "algos": [{
                "name": "hnswlib",
                "build": {"M": 8, "ef_construction": 100},
                "search": [{"ef": 50}],
            }]
        }
        r1 = run_benchmark(dataset_dir, config, tmp_path / "res",
                           k=10, search_iters=1)
        assert not r1[0]["build_cached"]
        r2 = run_benchmark(dataset_dir, config, tmp_path / "res",
                           k=10, search_iters=1)
        assert r2[0]["build_cached"]
        assert abs(r2[0]["recall"] - r1[0]["recall"]) < 1e-6

    def test_ivf_flat_cpu_cache_round_trip(self, rng_np, tmp_path):
        """Second competitor's index cache: save -> load -> identical
        search; mismatched/corrupt caches are refused (the hnsw_cpu
        contract)."""
        from raft_tpu.bench import ivf_flat_cpu
        from raft_tpu.distance.types import DistanceType

        base = rng_np.standard_normal((500, 16)).astype(np.float32)
        q = rng_np.standard_normal((20, 16)).astype(np.float32)
        idx = ivf_flat_cpu.build(base, DistanceType.L2Expanded,
                                 n_lists=16, trainset_fraction=1.0)
        d1, i1 = ivf_flat_cpu.search(idx, q, 5, n_probes=4)
        path = tmp_path / "ivf.bin"
        ivf_flat_cpu.save(idx, path)
        idx2 = ivf_flat_cpu.load(path, 16, DistanceType.L2Expanded)
        d2, i2 = ivf_flat_cpu.search(idx2, q, 5, n_probes=4)
        assert np.array_equal(i1, i2) and np.allclose(d1, d2)
        with pytest.raises(ValueError, match="dim"):
            ivf_flat_cpu.load(path, 32, DistanceType.L2Expanded)
        with pytest.raises(ValueError, match="metric"):
            ivf_flat_cpu.load(path, 16, DistanceType.InnerProduct)
        raw = bytearray(path.read_bytes())
        raw[len(raw) // 2] ^= 0xFF  # flip a byte mid-payload
        bad = tmp_path / "bad.bin"
        bad.write_bytes(bytes(raw[:len(raw) // 2]))  # truncate too
        with pytest.raises((ValueError, OSError, EOFError)):
            ivf_flat_cpu.load(bad, 16, DistanceType.L2Expanded)

    def test_load_rejects_mismatched_cache(self, rng_np, tmp_path):
        """A cache file whose recorded dim/metric differ from the
        caller's must be refused — the native side strides queries by
        the FILE's dim, so accepting it reads past the query buffer."""
        from raft_tpu.bench import hnsw_cpu
        from raft_tpu.distance.types import DistanceType

        if not hnsw_cpu.available():
            pytest.skip("native HNSW library could not be built")
        base = rng_np.standard_normal((64, 16)).astype(np.float32)
        idx = hnsw_cpu.build(base, DistanceType.L2Expanded, M=8,
                             ef_construction=50)
        path = tmp_path / "idx.bin"
        hnsw_cpu.save(idx, path)
        with pytest.raises(RuntimeError, match="dim"):
            hnsw_cpu.load(path, 32, DistanceType.L2Expanded)
        with pytest.raises(RuntimeError, match="metric"):
            hnsw_cpu.load(path, 16, DistanceType.InnerProduct)
        ok = hnsw_cpu.load(path, 16, DistanceType.L2Expanded)
        assert ok.dim == 16

    def test_load_rejects_corrupt_max_level(self, rng_np, tmp_path):
        """max_level above the entry node's level list would index past
        upper[entry] at search time; the loader must reject it."""
        from raft_tpu.bench import hnsw_cpu
        from raft_tpu.distance.types import DistanceType

        if not hnsw_cpu.available():
            pytest.skip("native HNSW library could not be built")
        base = rng_np.standard_normal((64, 16)).astype(np.float32)
        idx = hnsw_cpu.build(base, DistanceType.L2Expanded, M=8,
                             ef_construction=50)
        path = tmp_path / "idx.bin"
        hnsw_cpu.save(idx, path)
        # header: magic u32, dim i64, M i64, ef_construction i64,
        # metric i32, n i64, max_level i32 — corrupt max_level
        raw = bytearray(path.read_bytes())
        off = 4 + 8 + 8 + 8 + 4 + 8
        raw[off:off + 4] = (10 ** 6).to_bytes(4, "little")
        path.write_bytes(bytes(raw))
        with pytest.raises(RuntimeError, match="corrupt"):
            hnsw_cpu.load(path, 16, DistanceType.L2Expanded)

    def test_reference_schema_spellings(self):
        from raft_tpu.bench.runner import normalize_config

        cfg = normalize_config({
            "index": [{
                "algo": "hnswlib",
                "build_param": {"M": 12, "efConstruction": 150},
                "search_params": [{"ef": 20}],
            }]
        })
        assert cfg["algos"][0]["name"] == "hnswlib"
        assert cfg["algos"][0]["build"] == {"M": 12,
                                            "ef_construction": 150}
        assert cfg["algos"][0]["search"] == [{"ef": 20}]

    def test_two_competitor_series(self, dataset_dir, tmp_path):
        """The pareto needs a second non-raft series (the reference
        benches FAISS beside hnswlib): both competitors must produce
        rows in one sweep."""
        from raft_tpu.bench import hnsw_cpu

        algos = [{"name": "ivf_flat_cpu",
                  "build": {"n_lists": 64, "trainset_fraction": 0.5},
                  "search": [{"n_probes": 4}, {"n_probes": 64}]}]
        if hnsw_cpu.available():
            algos.append({"name": "hnswlib", "build": {"M": 8},
                          "search": [{"ef": 50}]})
        rows = run_benchmark(dataset_dir, {"algos": algos},
                             tmp_path / "res", k=10, search_iters=1)
        by_algo = {}
        for r in rows:
            by_algo.setdefault(r["algo"], []).append(r)
        ivf = by_algo["ivf_flat_cpu"]
        assert len(ivf) == 2
        # more probes -> higher recall; n_probes=64 of 64 lists = exact
        assert ivf[1]["recall"] >= ivf[0]["recall"]
        assert ivf[1]["recall"] > 0.99
        assert all(r["qps"] > 0 for r in rows)

    def test_sweep_survives_missing_toolchain(self, dataset_dir, tmp_path,
                                              monkeypatch):
        """A host without g++ must lose the hnswlib comparison series,
        not the whole sweep (the raft algos still run)."""
        from raft_tpu.bench import hnsw_cpu

        monkeypatch.setattr(hnsw_cpu, "available", lambda: False)
        config = {
            "algos": [
                {"name": "raft_brute_force", "search": [{}]},
                {"name": "hnswlib", "build": {"M": 8},
                 "search": [{"ef": 10}]},
            ]
        }
        rows = run_benchmark(dataset_dir, config, tmp_path / "res",
                             k=10, search_iters=1)
        assert [r["algo"] for r in rows] == ["raft_brute_force"]


class TestBenchCompare:
    """The CI perf-regression gate (graftscope v2): ``ci/bench_compare``
    must pass a record against itself, exit nonzero on an injected
    throughput/latency regression beyond tolerance, and floor-check the
    metrics snapshot's modeled-throughput counters."""

    @pytest.fixture(scope="class")
    def bc(self):
        import importlib.util
        import pathlib

        path = (pathlib.Path(__file__).parent.parent / "ci"
                / "bench_compare.py")
        spec = importlib.util.spec_from_file_location(
            "bench_compare", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    @pytest.fixture
    def record(self):
        return {
            "value": 1000.0,
            "serving": {
                "qps": 800.0,
                "baseline_one_per_call_qps": 400.0,
                "p99_ms": 20.0,
                "requests_per_batch": 4.0,
                "completed": 96.0,
                "backend_compiles_during_load": 22.0,
                "modeled_exec_bytes": 7e6,
                "modeled_exec_flops": 3e7,
            },
        }

    def test_identical_records_pass(self, bc, record):
        assert bc.compare(record, record) == []

    def test_injected_throughput_regression_fails(self, bc, record):
        import copy

        slow = copy.deepcopy(record)
        slow["serving"]["qps"] = record["serving"]["qps"] * 0.1
        msgs = bc.compare(record, slow)
        assert any("serving.qps" in m for m in msgs)
        # within the band: a 2x slowdown on a 0.30 min_ratio passes
        ok = copy.deepcopy(record)
        ok["serving"]["qps"] = record["serving"]["qps"] * 0.5
        assert bc.compare(record, ok) == []

    def test_injected_latency_and_compile_regressions_fail(self, bc,
                                                           record):
        import copy

        bad = copy.deepcopy(record)
        bad["serving"]["p99_ms"] = 200.0        # > 4x and > base + 50
        msgs = bc.compare(record, bad)
        assert any("p99_ms" in m for m in msgs)
        rec = copy.deepcopy(record)
        rec["serving"]["backend_compiles_during_load"] = 100.0
        msgs = bc.compare(record, rec)
        assert any("backend_compiles_during_load" in m for m in msgs)

    def test_missing_fresh_column_is_a_regression(self, bc, record):
        import copy

        gone = copy.deepcopy(record)
        del gone["serving"]["modeled_exec_bytes"]
        msgs = bc.compare(record, gone)
        assert any("modeled_exec_bytes" in m and "missing" in m
                   for m in msgs)
        # the converse — a column only the FRESH run has — is fine
        # (old baselines must not fail new code)
        extra = copy.deepcopy(record)
        del extra["serving"]["modeled_exec_bytes"]
        assert bc.compare(extra, record) == []

    def test_ragged_family_bands_gate(self, bc, record):
        """graftragged: the per-family ragged legs gate structurally —
        an executable-count or pad-waste regression in any family leg
        fails the gate; identical records pass."""
        import copy

        base = copy.deepcopy(record)
        base["serving"]["ragged_families"] = {
            "pq": {"completed": 24.0, "qps": 5.0, "p99_ms": 20.0,
                   "pad_waste_fraction": 0.01,
                   "backend_compiles_during_load": 0.0,
                   "executables": 2.0},
            "mesh": {"completed": 24.0, "qps": 15.0, "p99_ms": 10.0,
                     "pad_waste_fraction": 0.01,
                     "backend_compiles_during_load": 0.0,
                     "executables": 2.0, "shards": 4.0},
        }
        assert bc.compare(base, base) == []
        worse = copy.deepcopy(base)
        worse["serving"]["ragged_families"]["pq"]["executables"] = 5.0
        msgs = bc.compare(base, worse)
        assert any("ragged_families.pq.executables" in m for m in msgs)
        padded = copy.deepcopy(base)
        padded["serving"]["ragged_families"]["mesh"][
            "pad_waste_fraction"] = 0.2
        msgs = bc.compare(base, padded)
        assert any("ragged_families.mesh.pad_waste_fraction" in m
                   for m in msgs)
        # a lost mesh shard is a measurement regression, not noise
        fewer = copy.deepcopy(base)
        fewer["serving"]["ragged_families"]["mesh"]["shards"] = 1.0
        msgs = bc.compare(base, fewer)
        assert any("ragged_families.mesh.shards" in m for m in msgs)

    def test_snapshot_floors(self, bc):
        ok = {"counters": {"serving.execute.calls": 5.0,
                           "serving.execute.modeled_bytes": 1e6,
                           "serving.execute.modeled_flops": 1e7,
                           "index.probe.dispatches": 2.0,
                           "index.probe_freq.accounted": 64.0,
                           "profiling.captures": 1.0,
                           "incident.bundles": 1.0,
                           "profiling.rolling.folds": 2.0,
                           "fleet.scrapes": 1.0,
                           "memory.samples": 8.0,
                           "tier.swaps": 2.0,
                           "tier.swap_bytes": 1e5,
                           "fleet.route.requests": 4.0,
                           "fleet.plan.builds": 2.0}}
        assert bc.check_snapshot(ok) == []
        dark = {"counters": {"serving.execute.calls": 5.0,
                             "serving.execute.modeled_bytes": 0.0}}
        msgs = bc.check_snapshot(dark)
        assert any("modeled_bytes" in m for m in msgs)
        assert any("modeled_flops" in m and "missing" in m
                   for m in msgs)

    def test_snapshot_floors_prefer_lifetime_ledger(self, bc):
        """The floors read ``counters_lifetime`` when present: the live
        ``counters`` view only holds what ran after the session's LAST
        ``reset_counters()`` — ordering-dependent — while the lifetime
        ledger accumulates across resets (conftest writes both)."""
        snap = {
            "counters": {},  # a late test reset the live registry
            "counters_lifetime": {
                "serving.execute.calls": 5.0,
                "serving.execute.modeled_bytes": 1e6,
                "serving.execute.modeled_flops": 1e7,
                "index.probe.dispatches": 2.0,
                "index.probe_freq.accounted": 64.0,
                "profiling.captures": 2.0,
                "incident.bundles": 1.0,
                "profiling.rolling.folds": 2.0,
                "fleet.scrapes": 1.0,
            "memory.samples": 8.0,
                "tier.swaps": 2.0,
                "tier.swap_bytes": 1e5,
                "fleet.route.requests": 4.0,
                "fleet.plan.builds": 2.0,
            },
        }
        assert bc.check_snapshot(snap) == []

    def test_main_exits_nonzero_on_injected_regression(self, bc, record,
                                                       tmp_path):
        """End-to-end through ``main()``: the gate's exit code is the
        CI contract — 0 within bands, 1 on regression."""
        import copy

        baseline = {"record": record,
                    "tolerances": bc.DEFAULT_TOLERANCES,
                    "snapshot_floors": bc.SNAPSHOT_FLOORS}
        bpath = tmp_path / "baseline.json"
        bpath.write_text(json.dumps(baseline))
        good = tmp_path / "fresh_ok.json"
        good.write_text(json.dumps(record))
        assert bc.main(["--baseline", str(bpath),
                        "--fresh", str(good)]) == 0
        slow = copy.deepcopy(record)
        slow["serving"]["qps"] = 1.0
        bad = tmp_path / "fresh_bad.json"
        bad.write_text(json.dumps(slow))
        assert bc.main(["--baseline", str(bpath),
                        "--fresh", str(bad)]) == 1
        # missing baseline without --update is a usage error, not a pass
        assert bc.main(["--baseline", str(tmp_path / "absent.json"),
                        "--fresh", str(good)]) == 2

    def test_update_writes_baseline(self, bc, record, tmp_path):
        bpath = tmp_path / "baseline.json"
        fresh = tmp_path / "fresh.json"
        fresh.write_text(json.dumps(record))
        assert bc.main(["--baseline", str(bpath), "--fresh", str(fresh),
                        "--update"]) == 0
        out = json.loads(bpath.read_text())
        assert out["record"] == record
        assert out["tolerances"] == bc.DEFAULT_TOLERANCES
        # and the freshly written baseline gates against itself
        assert bc.main(["--baseline", str(bpath),
                        "--fresh", str(fresh)]) == 0

    # -- PR 8: multi-baseline support + probe-accounting floors -------------

    def test_snapshot_floors_include_probe_accounting(self, bc):
        """graftgauge satellite: the gate floor-checks the device-side
        probe-frequency ledger — a refactor that disconnects the
        scatter-add (or the scrape fetch) zeroes these and fails."""
        assert "index.probe_freq.accounted" in bc.SNAPSHOT_FLOORS
        assert "index.probe.dispatches" in bc.SNAPSHOT_FLOORS
        dark = {"counters_lifetime": {
            "serving.execute.calls": 5.0,
            "serving.execute.modeled_bytes": 1e6,
            "serving.execute.modeled_flops": 1e7,
            "index.probe.dispatches": 3.0,
            "index.probe_freq.accounted": 0.0,     # went dark
            "profiling.captures": 1.0,
            "incident.bundles": 1.0,
            "profiling.rolling.folds": 2.0,
            "fleet.scrapes": 1.0,
            "memory.samples": 8.0,
            "tier.swaps": 2.0,
            "tier.swap_bytes": 1e5,
            "fleet.route.requests": 4.0,
            "fleet.plan.builds": 2.0,
        }}
        msgs = bc.check_snapshot(dark)
        assert any("index.probe_freq.accounted" in m for m in msgs)
        dark["counters_lifetime"]["index.probe_freq.accounted"] = 96.0
        assert bc.check_snapshot(dark) == []

    # -- PR 11: graftflight ingestion / incident-capture floors -------------

    def test_snapshot_floors_include_graftflight(self, bc):
        """graftflight satellite: the gate floor-checks trace
        ingestion and incident capture — a refactor that disconnects
        the parser pipeline or the flight-recorder triggers zeroes
        these and fails structurally."""
        assert "profiling.captures" in bc.SNAPSHOT_FLOORS
        assert "incident.bundles" in bc.SNAPSHOT_FLOORS
        dark = {"counters_lifetime": {
            "serving.execute.calls": 5.0,
            "serving.execute.modeled_bytes": 1e6,
            "serving.execute.modeled_flops": 1e7,
            "index.probe.dispatches": 3.0,
            "index.probe_freq.accounted": 96.0,
            "profiling.captures": 0.0,             # ingestion dark
            "incident.bundles": 1.0,
            "profiling.rolling.folds": 2.0,
            "fleet.scrapes": 1.0,
            "memory.samples": 8.0,
            "tier.swaps": 2.0,
            "tier.swap_bytes": 1e5,
            "fleet.route.requests": 4.0,
            "fleet.plan.builds": 2.0,
        }}
        msgs = bc.check_snapshot(dark)
        assert any("profiling.captures" in m for m in msgs)
        dark["counters_lifetime"]["profiling.captures"] = 3.0
        assert bc.check_snapshot(dark) == []
        # the committed baseline carries the new floors too
        import os

        base_path = os.path.join(os.path.dirname(bc.__file__),
                                 "bench_baseline.json")
        with open(base_path) as f:
            committed = json.load(f)
        assert "profiling.captures" in committed["snapshot_floors"]
        assert "incident.bundles" in committed["snapshot_floors"]

    # -- PR 12: graftfleet rolling-attribution / federation floors ----------

    def test_snapshot_floors_include_graftfleet(self, bc):
        """graftfleet satellite: the gate floor-checks the
        continuous-capture -> rolling-EWMA pipeline and the
        federation scrape loop — disconnecting either zeroes these
        and fails structurally — and carries the tight
        continuous-overhead tolerance bands."""
        assert "profiling.rolling.folds" in bc.SNAPSHOT_FLOORS
        assert "fleet.scrapes" in bc.SNAPSHOT_FLOORS
        dark = {"counters_lifetime": {
            "serving.execute.calls": 5.0,
            "serving.execute.modeled_bytes": 1e6,
            "serving.execute.modeled_flops": 1e7,
            "index.probe.dispatches": 3.0,
            "index.probe_freq.accounted": 96.0,
            "profiling.captures": 1.0,
            "incident.bundles": 1.0,
            "profiling.rolling.folds": 0.0,        # rolling dark
            "fleet.scrapes": 1.0,
            "memory.samples": 8.0,
            "tier.swaps": 2.0,
            "tier.swap_bytes": 1e5,
            "fleet.route.requests": 4.0,
            "fleet.plan.builds": 2.0,
        }}
        msgs = bc.check_snapshot(dark)
        assert any("profiling.rolling.folds" in m for m in msgs)
        dark["counters_lifetime"]["profiling.rolling.folds"] = 4.0
        assert bc.check_snapshot(dark) == []
        # the continuous-capture overhead bands are gated, ratio tight
        assert bc.DEFAULT_TOLERANCES[
            "serving.continuous.p99_ratio"] == {"max_increase": 1.0}
        assert "serving.continuous.capture_attempts" in \
            bc.DEFAULT_TOLERANCES
        import os

        base_path = os.path.join(os.path.dirname(bc.__file__),
                                 "bench_baseline.json")
        with open(base_path) as f:
            committed = json.load(f)
        assert "profiling.rolling.folds" in committed["snapshot_floors"]
        assert "fleet.scrapes" in committed["snapshot_floors"]

    # -- PR 13: graftledger watermark floor ---------------------------------

    def test_snapshot_floors_include_graftledger(self, bc):
        """graftledger satellite: the gate floor-checks the
        dispatch-time watermark heartbeat — a refactor that
        disconnects ``MemoryLedger.sample_dispatch()`` from the
        executor's dispatch core zeroes this and fails
        structurally."""
        assert "memory.samples" in bc.SNAPSHOT_FLOORS
        dark = {"counters_lifetime": {
            "serving.execute.calls": 5.0,
            "serving.execute.modeled_bytes": 1e6,
            "serving.execute.modeled_flops": 1e7,
            "index.probe.dispatches": 3.0,
            "index.probe_freq.accounted": 96.0,
            "profiling.captures": 1.0,
            "incident.bundles": 1.0,
            "profiling.rolling.folds": 2.0,
            "fleet.scrapes": 1.0,
            "memory.samples": 0.0,                 # watermark dark
            "tier.swaps": 2.0,
            "tier.swap_bytes": 1e5,
            "fleet.route.requests": 4.0,
            "fleet.plan.builds": 2.0,
        }}
        msgs = bc.check_snapshot(dark)
        assert any("memory.samples" in m for m in msgs)
        dark["counters_lifetime"]["memory.samples"] = 8.0
        assert bc.check_snapshot(dark) == []
        import os

        base_path = os.path.join(os.path.dirname(bc.__file__),
                                 "bench_baseline.json")
        with open(base_path) as f:
            committed = json.load(f)
        assert "memory.samples" in committed["snapshot_floors"]

    # -- PR 14: grafttier swap floor + tiered tolerance bands ---------------

    def test_snapshot_floors_include_grafttier(self, bc):
        """grafttier satellite: the gate floor-checks the placement
        swap executor — a refactor that disconnects apply_plan's
        block swaps (or their byte accounting) zeroes these and
        fails structurally — and carries the tight tiered bands."""
        assert "tier.swaps" in bc.SNAPSHOT_FLOORS
        assert "tier.swap_bytes" in bc.SNAPSHOT_FLOORS
        dark = {"counters_lifetime": {
            "serving.execute.calls": 5.0,
            "serving.execute.modeled_bytes": 1e6,
            "serving.execute.modeled_flops": 1e7,
            "index.probe.dispatches": 3.0,
            "index.probe_freq.accounted": 96.0,
            "profiling.captures": 1.0,
            "incident.bundles": 1.0,
            "profiling.rolling.folds": 2.0,
            "fleet.scrapes": 1.0,
            "memory.samples": 8.0,
            "tier.swaps": 0.0,                     # swaps dark
            "tier.swap_bytes": 1e5,
            "fleet.route.requests": 4.0,
            "fleet.plan.builds": 2.0,
        }}
        msgs = bc.check_snapshot(dark)
        assert any("tier.swaps" in m for m in msgs)
        dark["counters_lifetime"]["tier.swaps"] = 2.0
        assert bc.check_snapshot(dark) == []
        # the correctness + zero-recompile columns are gated TIGHT
        assert bc.DEFAULT_TOLERANCES["tiered.bit_identical"] == \
            {"min_ratio": 1.0}
        assert bc.DEFAULT_TOLERANCES[
            "tiered.compiles_during_epochs"] == {"max_increase": 0}
        assert "tiered.swap_bytes_total" in bc.DEFAULT_TOLERANCES
        import os

        base_path = os.path.join(os.path.dirname(bc.__file__),
                                 "bench_baseline.json")
        with open(base_path) as f:
            committed = json.load(f)
        assert "tier.swaps" in committed["snapshot_floors"]
        # the committed baseline's tiered record holds the contract
        # values the bands pin against
        tiered = committed["record"]["tiered"]
        assert tiered["bit_identical"] == 1
        assert tiered["compiles_during_epochs"] == 0

    def test_multi_baseline_gates_each(self, bc, record, tmp_path):
        import copy

        b1 = tmp_path / "bench_baseline.json"
        b2 = tmp_path / "bench_baseline_other.json"
        b1.write_text(json.dumps({"record": record}))
        tight = copy.deepcopy(record)
        tight["serving"]["qps"] = record["serving"]["qps"] * 4
        b2.write_text(json.dumps({"record": tight}))
        fresh = tmp_path / "fresh.json"
        fresh.write_text(json.dumps(record))
        # passes against itself, fails against the tighter second
        assert bc.main(["--baseline", str(b1),
                        "--fresh", str(fresh)]) == 0
        assert bc.main(["--baseline", str(b1), "--baseline", str(b2),
                        "--fresh", str(fresh)]) == 1

    def test_requires_backend_skips_when_absent(self, bc, record,
                                                tmp_path, capsys):
        import copy

        impossible = copy.deepcopy(record)
        impossible["serving"]["qps"] = record["serving"]["qps"] * 100
        tpu = tmp_path / "bench_baseline_tpu.json"
        tpu.write_text(json.dumps({"record": impossible,
                                   "requires_backend": "tpu"}))
        fresh = tmp_path / "fresh.json"
        fresh.write_text(json.dumps(record))
        # the (on CPU CI, unmeetable) TPU baseline is skipped with a
        # note instead of failing the gate
        assert bc.main(["--baseline", str(tpu),
                        "--fresh", str(fresh)]) == 0
        assert "SKIP" in capsys.readouterr().out
        cpu_spelled = tmp_path / "bench_baseline_cpu.json"
        cpu_spelled.write_text(json.dumps({"record": record,
                                           "requires_backend": "cpu"}))
        # a baseline whose backend IS present gates normally
        assert bc.main(["--baseline", str(cpu_spelled),
                        "--fresh", str(fresh)]) == 0

    def test_update_rejects_multiple_baselines(self, bc, record,
                                               tmp_path):
        fresh = tmp_path / "fresh.json"
        fresh.write_text(json.dumps(record))
        assert bc.main(["--baseline", str(tmp_path / "a.json"),
                        "--baseline", str(tmp_path / "b.json"),
                        "--fresh", str(fresh), "--update"]) == 2

    def test_default_baselines_glob(self, bc):
        """With no --baseline the gate picks up every committed
        ci/bench_baseline*.json — how a recorded TPU baseline joins
        CI without touching test.sh."""
        import os

        paths = bc.default_baselines()
        assert any(p.endswith("bench_baseline.json") for p in paths)
        assert all(os.path.basename(p).startswith("bench_baseline")
                   for p in paths)
