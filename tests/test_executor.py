"""Serving-path tests: SearchExecutor bucketing, AOT executable cache,
compile-count regression (the steady-state-never-compiles guarantee),
stateless call signatures, and bit-identity with the direct search
paths."""

import jax
import numpy as np
import pytest

from raft_tpu import SearchExecutor
from raft_tpu.core import tracing
from raft_tpu.neighbors import brute_force, cagra, ivf_bq, ivf_flat, ivf_pq
from raft_tpu.neighbors.filters import BitmapFilter, BitsetFilter
from raft_tpu.core.bitset import Bitset


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(7)
    x = rng.standard_normal((600, 16)).astype(np.float32)
    q = rng.standard_normal((16, 16)).astype(np.float32)
    return x, q


@pytest.fixture(scope="module")
def indexes(data):
    x, _ = data
    return {
        "brute_force": brute_force.build(None, x),
        "ivf_flat": ivf_flat.build(
            None, ivf_flat.IvfFlatIndexParams(n_lists=8), x),
        "ivf_pq": ivf_pq.build(
            None, ivf_pq.IvfPqIndexParams(n_lists=8, pq_dim=8), x),
        "ivf_bq": ivf_bq.build(
            None, ivf_bq.IvfBqIndexParams(n_lists=8), x),
        "cagra": cagra.build(None, cagra.CagraIndexParams(
            graph_degree=8, intermediate_graph_degree=16,
            build_algo=cagra.BuildAlgo.NN_DESCENT), x),
    }


def _direct(name, index, q, k):
    if name == "brute_force":
        return brute_force.search(None, index, q, k)
    if name == "ivf_flat":
        return ivf_flat.search(
            None, ivf_flat.IvfFlatSearchParams(n_probes=8), index, q, k)
    if name == "ivf_pq":
        return ivf_pq.search(
            None, ivf_pq.IvfPqSearchParams(n_probes=8), index, q, k)
    if name == "ivf_bq":
        return ivf_bq.search(
            None, ivf_bq.IvfBqSearchParams(n_probes=8), index, q, k)
    return cagra.search(
        None, cagra.CagraSearchParams(itopk_size=16), index, q, k)


def _params(name):
    return {
        "brute_force": None,
        "ivf_flat": ivf_flat.IvfFlatSearchParams(n_probes=8),
        "ivf_pq": ivf_pq.IvfPqSearchParams(n_probes=8),
        "ivf_bq": ivf_bq.IvfBqSearchParams(n_probes=8),
        "cagra": cagra.CagraSearchParams(itopk_size=16),
    }[name]


class TestBitIdentity:
    """Acceptance: bucketed serving results are bit-identical to the
    direct search path for every index family, at batch sizes that do
    and do not fill their bucket."""

    @pytest.mark.parametrize(
        "name", ["brute_force", "ivf_flat", "ivf_pq", "ivf_bq", "cagra"])
    @pytest.mark.parametrize("q_rows", [3, 11, 16])
    def test_matches_direct(self, data, indexes, name, q_rows):
        _, q = data
        ex = SearchExecutor()
        d0, i0 = _direct(name, indexes[name], q[:q_rows], 5)
        d1, i1 = ex.search(indexes[name], q[:q_rows], 5,
                           params=_params(name))
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))

    def test_oversized_batch_tiles(self, data, indexes):
        x, _ = data
        rng = np.random.default_rng(3)
        big = rng.standard_normal((70, 16)).astype(np.float32)
        ex = SearchExecutor(min_bucket=8, max_bucket=32)
        d0, i0 = brute_force.search(None, indexes["brute_force"], big, 5)
        d1, i1 = ex.search(indexes["brute_force"], big, 5)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))

    def test_oversized_batch_cagra_seeds_stay_aligned(self, data, indexes):
        """CAGRA seeds are a pure function of query content
        (graftbeam), so oversized batches tile through the shared
        bucketed executable with rows bit-identical to the direct
        path — no row-offset plumbing."""
        rng = np.random.default_rng(4)
        big = rng.standard_normal((70, 16)).astype(np.float32)
        p = cagra.CagraSearchParams(itopk_size=16)
        ex = SearchExecutor(min_bucket=8, max_bucket=32)
        d0, i0 = cagra.search(None, p, indexes["cagra"], big, 5)
        d1, i1 = ex.search(indexes["cagra"], big, 5, params=p)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))

    def test_filtered_search(self, data, indexes):
        x, q = data
        # shared bitset filter: ban the first half of the ids
        bs = Bitset.from_mask(
            np.arange(x.shape[0]) >= x.shape[0] // 2)
        ex = SearchExecutor()
        p = ivf_flat.IvfFlatSearchParams(n_probes=8)
        d0, i0 = ivf_flat.search(None, p, indexes["ivf_flat"], q[:9], 5,
                                 sample_filter=BitsetFilter(bs))
        d1, i1 = ex.search(indexes["ivf_flat"], q[:9], 5, params=p,
                           sample_filter=BitsetFilter(bs))
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
        # per-query bitmap filter: pad rows get all-zero words
        mask = np.ones((9, x.shape[0]), bool)
        mask[:, ::3] = False
        bm = BitmapFilter.from_mask(mask)
        d0, i0 = ivf_flat.search(None, p, indexes["ivf_flat"], q[:9], 5,
                                 sample_filter=bm)
        d1, i1 = ex.search(indexes["ivf_flat"], q[:9], 5, params=p,
                           sample_filter=bm)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))


class TestCompileRegression:
    """Tier-1 guarantee: within one bucket, steady-state serving
    triggers ZERO new XLA compilations — asserted against jax's own
    backend-compile monitoring events, not just the executor's
    bookkeeping."""

    def test_zero_recompiles_within_bucket(self, data, indexes):
        _, q = data
        tracing.install_xla_compile_listener()
        ex = SearchExecutor()
        # prime: each batch size once (the search executable compiles
        # once per bucket; tiny pad/slice programs compile per size)
        for n in (16, 13, 9):
            ex.search(indexes["brute_force"], q[:n], 5)
        compiles0 = ex.stats.compile_count
        assert compiles0 == 1  # one bucket -> one search executable
        backend0 = tracing.get_counter(tracing.XLA_COMPILE_COUNT)
        # steady state: repeats at varying batch sizes within the bucket
        for n in (16, 13, 9, 13, 16, 9):
            d, i = ex.search(indexes["brute_force"], q[:n], 5)
        assert ex.stats.compile_count == compiles0
        assert tracing.get_counter(tracing.XLA_COMPILE_COUNT) == backend0
        assert ex.stats.cache_hits >= 8

    def test_counters_exported_via_tracing(self, data, indexes):
        _, q = data
        base = tracing.get_counter("serving.compile_count")
        ex = SearchExecutor()
        ex.search(indexes["ivf_bq"], q, 5)
        assert tracing.get_counter("serving.compile_count") >= base + 1


class TestWarmup:
    def test_warmup_precompiles(self, data, indexes):
        _, q = data
        ex = SearchExecutor()
        secs = ex.warmup(indexes["ivf_flat"], buckets=(8, 16), k=5,
                         params=ivf_flat.IvfFlatSearchParams(n_probes=8))
        assert secs > 0 and ex.stats.warmup_seconds == secs
        assert ex.stats.compile_count == 2
        # first real traffic is a cache hit, not a compile
        d, i = ex.search(indexes["ivf_flat"], q[:5], 5,
                         params=ivf_flat.IvfFlatSearchParams(n_probes=8))
        assert ex.stats.compile_count == 2
        assert ex.stats.cache_hits == 1
        assert np.isfinite(np.asarray(d)).all()

    def test_warmup_rejects_unknown_bucket(self, indexes):
        from raft_tpu.core.validation import RaftError

        ex = SearchExecutor(min_bucket=8, max_bucket=32)
        with pytest.raises(RaftError):
            ex.warmup(indexes["brute_force"], buckets=(7,), k=5)


class TestCacheAndState:
    def test_lru_eviction(self, data, indexes):
        _, q = data
        ex = SearchExecutor(max_entries=1)
        ex.search(indexes["brute_force"], q[:4], 5)
        ex.search(indexes["ivf_flat"], q[:4], 5,
                  params=ivf_flat.IvfFlatSearchParams(n_probes=8))
        assert ex.stats.evictions == 1
        # the evicted brute-force entry recompiles on return
        ex.search(indexes["brute_force"], q[:4], 5)
        assert ex.stats.compile_count == 3

    def test_results_survive_the_next_dispatch(self, data, indexes):
        """Results returned from call N must survive call N+1 of the
        same executable."""
        _, q = data
        ex = SearchExecutor()
        d1, i1 = ex.search(indexes["brute_force"], q[:16], 5)
        d1c, i1c = np.asarray(d1).copy(), np.asarray(i1).copy()
        d2, i2 = ex.search(indexes["brute_force"], q[:9], 5)
        np.testing.assert_array_equal(np.asarray(d1), d1c)
        np.testing.assert_array_equal(np.asarray(i1), i1c)
        d0, i0 = brute_force.search(None, indexes["brute_force"],
                                    q[:9], 5)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i2))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d2))

    @pytest.mark.parametrize("plan", ["pallas", "xla", "rank", "bf_scan"])
    def test_call_carries_no_running_state(self, data, indexes, plan):
        """Each plan's executable takes the queries and the index arrays
        only: no (bucket, k) operand is among its inputs, since the
        serving function allocates its own top-k carry. The results
        equal the family's direct search."""
        _, q = data
        k, rows, bucket = 7, 11, 16
        ex = SearchExecutor()
        if plan == "bf_scan":
            index, params = indexes["brute_force"], None
            d0, i0 = brute_force.search(None, index, q[:rows], k)
        else:
            index = indexes["ivf_flat"]
            params = ivf_flat.IvfFlatSearchParams(n_probes=4,
                                                  scan_engine=plan)
            d0, i0 = ivf_flat.search(None, params, index, q[:rows], k)
        d, i = ex.search(index, q[:rows], k, params=params)
        (info,) = ex.executable_costs().values()
        assert info["bucket"] == bucket
        if plan == "bf_scan":
            assert info["family"] == "bf_scan"
        else:
            assert info["family"] == "ivf_flat" and info["engine"] == plan
        (entry,) = ex._cache.values()
        shapes = [tuple(a.shape) for a in
                  jax.tree_util.tree_leaves(entry.compiled.in_avals)]
        assert (bucket, 16) in shapes           # the padded queries
        assert (bucket, k) not in shapes
        np.testing.assert_array_equal(np.asarray(i), np.asarray(i0))
        np.testing.assert_array_equal(np.asarray(d), np.asarray(d0))

    def test_empty_batch(self, indexes):
        ex = SearchExecutor()
        d, i = ex.search(indexes["brute_force"], np.zeros((0, 16),
                                                          np.float32), 5)
        assert d.shape == (0, 5) and i.shape == (0, 5)

    def test_unsupported_index_type(self):
        ex = SearchExecutor()
        with pytest.raises(TypeError):
            ex.search(object(), np.zeros((2, 4), np.float32), 1)


class TestCostIntrospection:
    """graftscope (PR 6): compile-time cost_analysis/memory_analysis
    capture, per-executable gauges, and the modeled-work counters the
    live achieved-GB/s derivation stands on."""

    def test_cost_table_and_gauges_after_compile(self, data, indexes):
        _, q = data
        ex = SearchExecutor()
        ex.search(indexes["ivf_flat"], q, 5,
                  params=ivf_flat.IvfFlatSearchParams(n_probes=4))
        costs = ex.executable_costs()
        assert len(costs) == 1
        digest, info = next(iter(costs.items()))
        assert info["family"] == "ivf_flat"
        assert info["bucket"] == 16 and info["k"] == 5
        assert info["bytes_accessed"] > 0
        assert info["peak_hbm_bytes"] > 0
        assert info["compile_seconds"] > 0
        base = f"serving.executable.{digest}."
        assert tracing.get_gauge(base + "bytes_accessed") == (
            info["bytes_accessed"])
        assert tracing.get_gauge(base + "peak_hbm_bytes") == (
            info["peak_hbm_bytes"])
        assert tracing.get_gauge(
            "serving.executor.cached_executables") == 1.0

    def test_modeled_counters_advance_per_call_not_per_compile(
            self, data, indexes):
        _, q = data
        ex = SearchExecutor()
        tracing.reset_counters("serving.execute.")
        ex.warmup(indexes["brute_force"], buckets=(16,), k=5)
        # warmup compiles but dispatches nothing
        assert tracing.get_counter("serving.execute.calls") == 0
        ex.search(indexes["brute_force"], q, 5)
        ex.search(indexes["brute_force"], q, 5)
        assert tracing.get_counter("serving.execute.calls") == 2
        per_call = ex.executable_costs()
        (info,) = per_call.values()
        assert tracing.get_counter(
            "serving.execute.modeled_bytes") == pytest.approx(
                2 * info["bytes_accessed"])
        assert tracing.get_counter(
            "serving.execute.rows") == 2 * q.shape[0]

    def test_publish_cost_gauges_survives_gauge_reset(self, data, indexes):
        """``metrics.reset()`` clears the serving gauge namespace while
        the AOT cache keeps its executables; ``publish_cost_gauges()``
        (the exporter's scrape-time refresh) restores the per-executable
        gauges so /metrics and executable_costs() agree again."""
        _, q = data
        ex = SearchExecutor()
        ex.search(indexes["brute_force"], q, 5)
        (digest,) = ex.executable_costs()
        base = f"serving.executable.{digest}."
        tracing.reset_gauges("serving.")
        assert tracing.gauges(base) == {}
        ex.publish_cost_gauges()
        info = ex.executable_costs()[digest]
        assert tracing.get_gauge(base + "bytes_accessed") == (
            info["bytes_accessed"])
        assert tracing.get_gauge(
            "serving.executor.cached_executables") == 1.0

    def test_eviction_retires_cost_gauges(self, data, indexes):
        _, q = data
        ex = SearchExecutor(max_entries=1)
        ex.search(indexes["brute_force"], q, 5)
        first = set(ex.executable_costs())
        ex.search(indexes["brute_force"], q, 7)   # evicts k=5 entry
        second = set(ex.executable_costs())
        assert len(second) == 1 and first != second
        gone = first.pop()
        assert tracing.gauges(f"serving.executable.{gone}.") == {}
        assert tracing.get_gauge(
            f"serving.executable.{second.pop()}.bytes_accessed") > 0


class TestProbeAccounting:
    """graftgauge (PR 8): device-side probe-frequency accounting —
    a donated int32 counter plane scatter-added inside the jitted IVF
    search bodies. Acceptance: bit-identity and zero-recompile stay
    green with accounting ON, counts are exact (inert bucket-pad rows
    masked), and the counters surface only at scrape time."""

    IVF = ("ivf_flat", "ivf_pq", "ivf_bq")

    @pytest.mark.parametrize("name", IVF)
    def test_bit_identity_with_accounting_on(self, data, indexes, name):
        _, q = data
        ex = SearchExecutor(probe_accounting=True)
        d1, i1 = ex.search(indexes[name], q, 5, params=_params(name))
        d0, i0 = _direct(name, indexes[name], q, 5)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))

    @pytest.mark.parametrize("name", IVF)
    def test_exact_counts_and_pad_masking(self, data, indexes, name):
        """Every dispatch adds exactly rows * n_probes to the plane —
        13 rows pad to the 16-bucket, and the 3 phantom rows' probe
        selections must NOT pollute the histogram."""
        _, q = data
        ex = SearchExecutor(probe_accounting=True)
        for _ in range(3):
            ex.search(indexes[name], q[:13], 5, params=_params(name))
        (plane,) = ex.probe_frequencies().values()
        assert plane.shape == (8,)          # n_lists
        assert plane.sum() == 3 * 13 * 8    # calls * rows * n_probes
        assert (plane >= 0).all()

    def test_zero_recompile_steady_state(self, data, indexes):
        _, q = data
        tracing.install_xla_compile_listener()
        ex = SearchExecutor(probe_accounting=True)
        sp = ivf_flat.IvfFlatSearchParams(n_probes=4)
        for n in (16, 13, 9):
            ex.search(indexes["ivf_flat"], q[:n], 5, params=sp)
        compiles0 = ex.stats.compile_count
        backend0 = tracing.get_counter(tracing.XLA_COMPILE_COUNT)
        for n in (16, 13, 9, 13, 16, 9):
            ex.search(indexes["ivf_flat"], q[:n], 5, params=sp)
        assert ex.stats.compile_count == compiles0
        assert tracing.get_counter(tracing.XLA_COMPILE_COUNT) == backend0

    def test_accounting_is_a_distinct_executable(self, data, indexes):
        """The counter plane changes the compiled signature, so the
        flag joins the cache key — an accounting executor and a plain
        one must not collide in the persistent compile cache."""
        _, q = data
        ex_on = SearchExecutor(probe_accounting=True)
        ex_off = SearchExecutor(probe_accounting=False)
        sp = ivf_flat.IvfFlatSearchParams(n_probes=4)
        kon = ex_on._plan(indexes["ivf_flat"], sp, 5, 16, None, {}).key
        koff = ex_off._plan(indexes["ivf_flat"], sp, 5, 16, None, {}).key
        assert kon != koff
        assert "probe_accounting" in kon

    def test_off_by_default_no_planes(self, data, indexes):
        _, q = data
        ex = SearchExecutor()
        ex.search(indexes["ivf_flat"], q, 5,
                  params=ivf_flat.IvfFlatSearchParams(n_probes=4))
        assert ex.probe_frequencies() == {}
        assert ex.probe_label(indexes["ivf_flat"]) is None

    def test_non_ivf_families_unaffected(self, data, indexes):
        _, q = data
        ex = SearchExecutor(probe_accounting=True)
        d1, i1 = ex.search(indexes["brute_force"], q, 5)
        d0, i0 = _direct("brute_force", indexes["brute_force"], q, 5)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        assert ex.probe_frequencies() == {}

    def test_publish_probe_gauges_and_lifetime_counter(
            self, data, indexes):
        _, q = data
        tracing.reset_counters("index.")
        tracing.reset_gauges("index.")
        ex = SearchExecutor(probe_accounting=True)
        sp = ivf_flat.IvfFlatSearchParams(n_probes=4)
        ex.search(indexes["ivf_flat"], q, 5, params=sp)
        label = ex.probe_label(indexes["ivf_flat"])
        assert label is not None and "." not in label
        stats = ex.publish_probe_gauges(top_n=3)[label]
        base = f"index.probe_freq.{label}."
        assert tracing.get_gauge(base + "total") == 16 * 4
        assert 0.0 < tracing.get_gauge(base + "probed_fraction") <= 1.0
        assert (tracing.get_gauge(base + "coverage_p01")
                <= tracing.get_gauge(base + "coverage_p10"))
        assert len(stats["top"]) <= 3
        for lid, c in stats["top"]:
            assert tracing.get_gauge(f"{base}list.{lid}") == float(c)
        # the monotone counter mirror — what the CI snapshot floors
        # check — reflects exactly what came off the device
        assert tracing.get_counter(
            "index.probe_freq.accounted") == 16 * 4
        ex.search(indexes["ivf_flat"], q, 5, params=sp)
        ex.publish_probe_gauges(top_n=3)
        assert tracing.get_counter(
            "index.probe_freq.accounted") == 2 * 16 * 4
        # the per-dispatch host heartbeat
        assert tracing.get_counter("index.probe.dispatches") == 2
        assert tracing.get_counter("index.probe.rows") == 32

    def test_stale_topn_samples_retire(self, data, indexes):
        _, q = data
        ex = SearchExecutor(probe_accounting=True)
        ex.search(indexes["ivf_flat"], q, 5,
                  params=ivf_flat.IvfFlatSearchParams(n_probes=4))
        label = ex.probe_label(indexes["ivf_flat"])
        base = f"index.probe_freq.{label}.list."
        ex.publish_probe_gauges(top_n=8)
        # fake a stale sample, then republishing must retire it
        tracing.set_gauge(base + "9999", 123.0)
        ex.publish_probe_gauges(top_n=8)
        assert base + "9999" not in tracing.gauges(base)


class TestRaggedPlans:
    """The ragged packed-batch plan family: one AOT entry per (index
    shapes, params class, tile) serves every load shape — bit-identical
    per request to the bucketed path, zero-recompile steady state."""

    @pytest.fixture(scope="class")
    def ragged_setup(self):
        rng = np.random.default_rng(11)
        x = rng.standard_normal((2000, 24)).astype(np.float32)
        index = ivf_flat.build(
            None, ivf_flat.IvfFlatIndexParams(n_lists=16), x)
        return x, index, rng

    @pytest.mark.parametrize("engine", ["pallas", "xla"])
    def test_bit_identical_to_bucketed_per_engine(self, ragged_setup,
                                                  engine):
        """pallas ≡ xla ≡ bucketed per packed request, with mixed
        per-request n_probes AND k in one params class."""
        _, index, rng = ragged_setup
        ex = SearchExecutor(ragged_tile=16)
        blocks = [rng.standard_normal((m, 24)).astype(np.float32)
                  for m in (3, 2, 4, 1)]
        nps, ks = [5, 8, 2, 7], [3, 7, 5, 8]
        ps = [ivf_flat.IvfFlatSearchParams(n_probes=n,
                                           scan_engine=engine)
              for n in nps]
        keys = {ex.ragged_key(index, k, params=p)
                for k, p in zip(ks, ps)}
        assert len(keys) == 1 and None not in keys
        res = ex.search_ragged(index, blocks, ks, params_list=ps)
        for b, k, p, (d, i) in zip(blocks, ks, ps, res):
            dd, ii = ex.search(index, b, k, params=p)
            np.testing.assert_array_equal(np.asarray(i), np.asarray(ii))
            np.testing.assert_array_equal(np.asarray(d), np.asarray(dd))

    def test_single_request_batch(self, ragged_setup):
        _, index, rng = ragged_setup
        ex = SearchExecutor(ragged_tile=16)
        p = ivf_flat.IvfFlatSearchParams(n_probes=5, scan_engine="xla")
        b = rng.standard_normal((5, 24)).astype(np.float32)
        (d, i), = ex.search_ragged(index, [b], 4, params_list=p)
        dd, ii = ex.search(index, b, 4, params=p)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ii))
        np.testing.assert_array_equal(np.asarray(d), np.asarray(dd))
        assert d.shape == (5, 4)

    def test_one_executable_zero_recompile(self, ragged_setup):
        """warmup_ragged compiles the ONE executable; mixed load
        shapes then never compile again (asserted against the XLA
        backend counter) and the cache holds exactly one ragged
        entry."""
        _, index, rng = ragged_setup
        ex = SearchExecutor(ragged_tile=16)
        p = ivf_flat.IvfFlatSearchParams(n_probes=6, scan_engine="xla")
        ex.warmup_ragged(index, k=8, params=p)
        assert ex.ragged_executables() == 1
        tracing.install_xla_compile_listener()
        # first call set: each distinct total-row count pays its tiny
        # pad/concat program once (the bucketed small print), so churn
        # the shapes once before measuring
        shapes = [(1,), (3, 2), (5, 7, 4), (16,), (2, 2, 2)]
        for sizes in shapes:
            blocks = [rng.standard_normal((m, 24)).astype(np.float32)
                      for m in sizes]
            ex.search_ragged(index, blocks, 8, params_list=p)
        before = tracing.get_counter(tracing.XLA_COMPILE_COUNT)
        for sizes in shapes:
            blocks = [rng.standard_normal((m, 24)).astype(np.float32)
                      for m in sizes]
            ex.search_ragged(index, blocks, 8, params_list=p)
        assert tracing.get_counter(tracing.XLA_COMPILE_COUNT) == before
        assert ex.ragged_executables() == 1

    def test_distinct_params_class_distinct_key(self, ragged_setup):
        _, index, _ = ragged_setup
        ex = SearchExecutor(ragged_tile=16)
        p_small = ivf_flat.IvfFlatSearchParams(n_probes=5,
                                               scan_engine="xla")
        p_big = ivf_flat.IvfFlatSearchParams(n_probes=16,
                                             scan_engine="xla")
        k1 = ex.ragged_key(index, 4, params=p_small)
        k2 = ex.ragged_key(index, 4, params=p_big)     # np class 8 vs 16
        k3 = ex.ragged_key(index, 40, params=p_small)  # k class 8 vs 64
        assert k1 != k2 and k1 != k3

    def test_not_raggable_falls_back(self, ragged_setup, indexes):
        _, index, _ = ragged_setup
        ex = SearchExecutor()
        # rank engine / approx coarse / other families: bucketed only
        assert ex.ragged_key(index, 4, params=ivf_flat.IvfFlatSearchParams(
            n_probes=5, scan_engine="rank")) is None
        assert ex.ragged_key(index, 4, params=ivf_flat.IvfFlatSearchParams(
            n_probes=5, coarse_algo="approx")) is None
        # CAGRA packs since graftbeam (content-pure seeds) — only a k
        # class cap past itopk_size refuses
        assert ex.ragged_key(indexes["cagra"], 4,
                             params=cagra.CagraSearchParams()) is not None
        assert ex.ragged_key(
            indexes["cagra"], 40,
            params=cagra.CagraSearchParams(itopk_size=16)) is None
        assert ex.ragged_key(indexes["brute_force"], 4) is None

    def test_tile_overflow_streams_chunks(self, ragged_setup):
        """Totals past one tile stream through the SAME executable —
        results identical, no second specialization."""
        _, index, rng = ragged_setup
        ex = SearchExecutor(ragged_tile=8)
        p = ivf_flat.IvfFlatSearchParams(n_probes=5, scan_engine="xla")
        blocks = [rng.standard_normal((m, 24)).astype(np.float32)
                  for m in (6, 7, 9)]           # 22 rows -> 3 chunks
        res = ex.search_ragged(index, blocks, 6, params_list=p)
        assert ex.ragged_executables() == 1
        for b, (d, i) in zip(blocks, res):
            dd, ii = ex.search(index, b, 6, params=p)
            np.testing.assert_array_equal(np.asarray(i), np.asarray(ii))
            np.testing.assert_array_equal(np.asarray(d), np.asarray(dd))

    def test_empty_total_returns_empties(self, ragged_setup):
        _, index, _ = ragged_setup
        ex = SearchExecutor(ragged_tile=8)
        p = ivf_flat.IvfFlatSearchParams(n_probes=5, scan_engine="xla")
        res = ex.search_ragged(
            index, [np.zeros((0, 24), np.float32)], 4, params_list=p)
        assert res[0][0].shape == (0, 4)

    def test_2d_filter_rows_pack_adjacently(self, ragged_setup):
        """Per-request 2-D filter rows concatenate to the packed rows
        and mask exactly as the bucketed path does per request."""
        x, index, rng = ragged_setup
        ex = SearchExecutor(ragged_tile=16)
        p = ivf_flat.IvfFlatSearchParams(n_probes=8, scan_engine="xla")
        sizes = (3, 5)
        blocks = [rng.standard_normal((m, 24)).astype(np.float32)
                  for m in sizes]
        mask = rng.random((sum(sizes), len(x))) < 0.5
        bm = BitmapFilter.from_mask(mask)
        res = ex.search_ragged(index, blocks, 6, params_list=p,
                               sample_filter=bm)
        row = 0
        for b, m, (d, i) in zip(blocks, sizes, res):
            bm_j = BitmapFilter.from_mask(mask[row:row + m])
            dd, ii = ex.search(index, b, 6, params=p, sample_filter=bm_j)
            np.testing.assert_array_equal(np.asarray(i), np.asarray(ii))
            np.testing.assert_array_equal(np.asarray(d), np.asarray(dd))
            ids = np.asarray(i)
            valid = ids >= 0
            rows_of = np.repeat(np.arange(m), 6).reshape(m, 6)
            assert mask[row:row + m][rows_of[valid], ids[valid]].all()
            row += m

    def test_shared_1d_filter(self, ragged_setup):
        x, index, rng = ragged_setup
        ex = SearchExecutor(ragged_tile=16)
        p = ivf_flat.IvfFlatSearchParams(n_probes=8, scan_engine="xla")
        filt = BitsetFilter(Bitset.from_mask(np.arange(len(x)) % 2 == 0))
        blocks = [rng.standard_normal((m, 24)).astype(np.float32)
                  for m in (4, 3)]
        res = ex.search_ragged(index, blocks, 5, params_list=p,
                               sample_filter=filt)
        for b, (d, i) in zip(blocks, res):
            dd, ii = ex.search(index, b, 5, params=p, sample_filter=filt)
            np.testing.assert_array_equal(np.asarray(i), np.asarray(ii))
            ids = np.asarray(i)
            assert (ids[ids >= 0] % 2 == 0).all()

    def test_probe_accounting_counts_exactly(self, ragged_setup):
        """The donated probe plane counts each packed request's OWN
        n_probes per row — pad rows and masked slots contribute
        nothing, and the plane is shared with the bucketed plans."""
        _, index, rng = ragged_setup
        ex = SearchExecutor(ragged_tile=16, probe_accounting=True)
        p1 = ivf_flat.IvfFlatSearchParams(n_probes=5, scan_engine="xla")
        p2 = ivf_flat.IvfFlatSearchParams(n_probes=8, scan_engine="xla")
        blocks = [rng.standard_normal((m, 24)).astype(np.float32)
                  for m in (3, 2)]
        ex.search_ragged(index, blocks, 4, params_list=[p1, p2])
        planes = ex.probe_frequencies()
        total = sum(int(v.sum()) for v in planes.values())
        assert total == 3 * 5 + 2 * 8
        # bucketed dispatch folds into the SAME plane
        ex.search(index, blocks[0], 4, params=p1)
        planes = ex.probe_frequencies()
        assert sum(int(v.sum()) for v in planes.values()) == \
            total + 3 * 5


class TestBqEngines:
    """RaBitQ IVF-BQ through the executor: the resolved scan engine
    is in the AOT cache key (engine switch = distinct executable),
    each fused engine is bit-identical to the direct search at every
    bucket occupancy, and steady state stays zero-recompile."""

    @pytest.mark.parametrize("engine", ["pallas", "xla", "rank"])
    @pytest.mark.parametrize("rows", [16, 13, 9])
    def test_bit_identity_per_engine(self, data, indexes, engine, rows):
        _, q = data
        sp = ivf_bq.IvfBqSearchParams(n_probes=8, scan_engine=engine)
        ex = SearchExecutor()
        d1, i1 = ex.search(indexes["ivf_bq"], q[:rows], 5, params=sp)
        d0, i0 = ivf_bq.search(None, sp, indexes["ivf_bq"], q[:rows], 5)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))

    def test_engine_in_cache_key_and_zero_recompile(self, data, indexes):
        _, q = data
        tracing.install_xla_compile_listener()
        ex = SearchExecutor()
        sp_x = ivf_bq.IvfBqSearchParams(n_probes=8, scan_engine="xla")
        for n in (16, 13, 9):
            ex.search(indexes["ivf_bq"], q[:n], 5, params=sp_x)
        assert ex.stats.compile_count == 1
        backend0 = tracing.get_counter(tracing.XLA_COMPILE_COUNT)
        for n in (16, 13, 9, 13, 16):
            ex.search(indexes["ivf_bq"], q[:n], 5, params=sp_x)
        assert ex.stats.compile_count == 1
        assert tracing.get_counter(tracing.XLA_COMPILE_COUNT) == backend0
        # engine switch compiles a DISTINCT executable (engine is in
        # the key); epsilon is a static too — both fork deliberately
        ex.search(indexes["ivf_bq"], q, 5,
                  params=ivf_bq.IvfBqSearchParams(n_probes=8,
                                                  scan_engine="pallas"))
        assert ex.stats.compile_count == 2
        ex.search(indexes["ivf_bq"], q, 5,
                  params=ivf_bq.IvfBqSearchParams(n_probes=8,
                                                  scan_engine="rank"))
        assert ex.stats.compile_count == 3

    def test_codes_only_index_degrades_to_rank(self, data):
        """An index without the rerank plane serves through the
        executor on the estimate-only path (auto resolves to rank),
        bit-identical to the direct search."""
        x, q = data
        idx = ivf_bq.build(None, ivf_bq.IvfBqIndexParams(
            n_lists=8, store_vectors=False), x)
        sp = ivf_bq.IvfBqSearchParams(n_probes=8)
        ex = SearchExecutor()
        d1, i1 = ex.search(idx, q[:9], 5, params=sp)
        d0, i0 = ivf_bq.search(None, sp, idx, q[:9], 5)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))


class TestRaggedFamilies:
    """graftragged: the PQ and BQ fronts serve through the SAME
    ragged plan family — bit-identical per request to the bucketed
    path, one executable per (index shapes, params class, tile), and
    the documented non-raggable residue falls back with an explicit
    reason."""

    @pytest.fixture(scope="class")
    def fam_setup(self):
        rng = np.random.default_rng(23)
        x = rng.standard_normal((2000, 32)).astype(np.float32)
        return (
            ivf_pq.build(None, ivf_pq.IvfPqIndexParams(
                n_lists=16, pq_dim=8), x),
            ivf_bq.build(None, ivf_bq.IvfBqIndexParams(
                n_lists=16, bits=2), x),
            rng,
        )

    def test_pq_bit_identical_and_zero_recompile(self, fam_setup):
        pq_index, _, rng = fam_setup
        ex = SearchExecutor(ragged_tile=16)
        p1 = ivf_pq.IvfPqSearchParams(n_probes=5, scan_engine="xla")
        p2 = ivf_pq.IvfPqSearchParams(n_probes=8, scan_engine="xla")
        # mixed n_probes AND k inside one pow2 class share the key
        assert (ex.ragged_key(pq_index, 4, params=p1)
                == ex.ragged_key(pq_index, 7, params=p2))
        ex.warmup_ragged(pq_index, k=7, params=p1)
        assert ex.ragged_executables("ivf_pq") == 1
        tracing.install_xla_compile_listener()
        blocks = [rng.standard_normal((m, 32)).astype(np.float32)
                  for m in (3, 5, 2, 9)]
        c0 = tracing.get_counter(tracing.XLA_COMPILE_COUNT)
        res = ex.search_ragged(pq_index, blocks, [4, 7, 6, 5],
                               params_list=[p1, p2, p1, p2])
        assert (tracing.get_counter(tracing.XLA_COMPILE_COUNT)
                - c0 == 0)
        assert ex.ragged_executables("ivf_pq") == 1
        for b, (d, i), kj, pj in zip(blocks, res, [4, 7, 6, 5],
                                     [p1, p2, p1, p2]):
            sd, si = ex.search(pq_index, b, kj, params=pj)
            np.testing.assert_array_equal(i, np.asarray(si))
            np.testing.assert_array_equal(d, np.asarray(sd))

    def test_bq_bit_identical_and_zero_recompile(self, fam_setup):
        _, bq_index, rng = fam_setup
        ex = SearchExecutor(ragged_tile=16)
        pb = ivf_bq.IvfBqSearchParams(n_probes=6, scan_engine="xla")
        pb2 = ivf_bq.IvfBqSearchParams(n_probes=3, scan_engine="xla")
        ex.warmup_ragged(bq_index, k=5, params=pb)
        assert ex.ragged_executables("ivf_bq") == 1
        tracing.install_xla_compile_listener()
        blocks = [rng.standard_normal((m, 32)).astype(np.float32)
                  for m in (4, 2, 7)]
        c0 = tracing.get_counter(tracing.XLA_COMPILE_COUNT)
        res = ex.search_ragged(bq_index, blocks, [5, 3, 4],
                               params_list=[pb, pb2, pb])
        assert (tracing.get_counter(tracing.XLA_COMPILE_COUNT)
                - c0 == 0)
        for b, (d, i), kj, pj in zip(blocks, res, [5, 3, 4],
                                     [pb, pb2, pb]):
            sd, si = ex.search(bq_index, b, kj, params=pj)
            np.testing.assert_array_equal(i, np.asarray(si))
            np.testing.assert_array_equal(d, np.asarray(sd))

    def test_probe_accounting_shared_with_bucketed(self, fam_setup):
        """The PQ ragged plan threads the SAME donated probe plane as
        the bucketed plans — one cumulative histogram per index,
        exact across the path split."""
        pq_index, _, rng = fam_setup
        ex = SearchExecutor(ragged_tile=16, probe_accounting=True)
        p1 = ivf_pq.IvfPqSearchParams(n_probes=4, scan_engine="xla")
        b1 = rng.standard_normal((3, 32)).astype(np.float32)
        b2 = rng.standard_normal((2, 32)).astype(np.float32)
        ex.search_ragged(pq_index, [b1, b2], 4, params_list=p1)
        ex.search(pq_index, b1, 4, params=p1)        # bucketed leg
        planes = ex.probe_frequencies()
        (label,) = planes.keys()
        assert label.startswith("ivf_pq-")
        # every dispatched row probed exactly n_probes=4 lists
        assert planes[label].sum() == (3 + 2 + 3) * 4

    def test_residue_reasons(self, fam_setup, indexes, data):
        pq_index, bq_index, _ = fam_setup
        x, _ = data
        ex = SearchExecutor()
        assert ex.ragged_fallback_reason(
            pq_index, 4, params=ivf_pq.IvfPqSearchParams(
                scan_engine="rank")).startswith("scan_engine")
        assert ex.ragged_fallback_reason(
            pq_index, 4, params=ivf_pq.IvfPqSearchParams(
                coarse_algo="approx")).startswith("coarse_algo")
        # CAGRA's only residue since graftbeam: a k class cap the beam
        # buffer cannot carry
        assert ex.ragged_fallback_reason(
            indexes["cagra"], 20,
            params=cagra.CagraSearchParams(
                itopk_size=16)).startswith("cagra")
        assert ex.ragged_fallback_reason(
            indexes["cagra"], 4,
            params=cagra.CagraSearchParams(itopk_size=16)) is None
        assert ex.ragged_fallback_reason(
            indexes["brute_force"], 4).startswith("brute_force")
        # codes-only BQ resolves to the rank estimate scan
        codes_only = ivf_bq.build(None, ivf_bq.IvfBqIndexParams(
            n_lists=8, store_vectors=False), x)
        assert ex.ragged_key(codes_only, 4) is None
        assert "rank" in ex.ragged_fallback_reason(codes_only, 4)
        # raggable combinations report no reason
        assert ex.ragged_fallback_reason(
            bq_index, 4, params=ivf_bq.IvfBqSearchParams(
                scan_engine="xla")) is None


class TestRaggedDualTile:
    """The opt-in small/large tile pair: tile selection happens at
    dispatch by packed-row count, the packing key never forks, and
    steady state holds at ≤ 2 executables per params class."""

    @pytest.fixture(scope="class")
    def dual_setup(self):
        rng = np.random.default_rng(29)
        x = rng.standard_normal((1500, 24)).astype(np.float32)
        index = ivf_flat.build(
            None, ivf_flat.IvfFlatIndexParams(n_lists=16), x)
        return x, index, rng

    def test_warmup_compiles_both_tiles(self, dual_setup):
        _, index, _ = dual_setup
        p = ivf_flat.IvfFlatSearchParams(n_probes=6)
        ex = SearchExecutor(ragged_tile=32, ragged_tile_small=8)
        ex.warmup_ragged(index, k=4, params=p)
        assert ex.ragged_executables() == 2
        assert ex.ragged_executables("ivf_flat") == 2

    def test_dispatch_selects_tile_and_stays_compiled(self, dual_setup):
        _, index, rng = dual_setup
        p = ivf_flat.IvfFlatSearchParams(n_probes=6)
        ex = SearchExecutor(ragged_tile=32, ragged_tile_small=8)
        ex.warmup_ragged(index, k=4, params=p)
        tracing.install_xla_compile_listener()
        small = [rng.standard_normal((3, 24)).astype(np.float32)]
        big = [rng.standard_normal((9, 24)).astype(np.float32)
               for _ in range(5)]
        c0 = tracing.get_counter(tracing.XLA_COMPILE_COUNT)
        tracing.reset_counters("serving.execute.")
        res_s = ex.search_ragged(index, small, 4, params_list=p)
        res_b = ex.search_ragged(index, big, 4, params_list=p)
        assert (tracing.get_counter(tracing.XLA_COMPILE_COUNT)
                - c0 == 0)
        assert ex.ragged_executables() == 2
        # the split counters attribute the dispatches per tile
        assert tracing.get_counter(
            "serving.execute.padded_rows.p8.t8") == 8.0
        assert tracing.get_counter(
            "serving.execute.padded_rows.p8.t32") == 64.0
        from raft_tpu.serving import metrics as sv_metrics

        by_class = sv_metrics.derived()["pad_waste_by_class"]
        assert set(by_class) >= {"p8.t8", "p8.t32"}
        # both tiles are bit-identical to the bucketed path
        sd, si = ex.search(index, small[0], 4, params=p)
        np.testing.assert_array_equal(res_s[0][1], np.asarray(si))
        for b, (d, i) in zip(big, res_b):
            _, si = ex.search(index, b, 4, params=p)
            np.testing.assert_array_equal(i, np.asarray(si))

    def test_tile_never_joins_the_key(self, dual_setup):
        _, index, _ = dual_setup
        p = ivf_flat.IvfFlatSearchParams(n_probes=6)
        ex1 = SearchExecutor(ragged_tile=32)
        ex2 = SearchExecutor(ragged_tile=32, ragged_tile_small=8)
        assert (ex1.ragged_key(index, 4, params=p)
                == ex2.ragged_key(index, 4, params=p))
