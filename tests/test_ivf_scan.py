"""List-major IVF scan engine tests (ops/ivf_scan): interpret-mode
parity of the Pallas kernel and the XLA list-major scan against the
rank-major scan across metrics and filters; bucketing/query-tile
invariance through SearchExecutor; engine-keyed AOT cache with the
zero-recompile guarantee.

Parity contract: the two list-major engines are bit-identical to EACH
OTHER (same contraction, and zero-padding is reduction-invariant);
against the rank-major scan the returned indices are bit-identical and
distances agree to XLA's dot-reassociation tolerance — the batched
(q, m, d) matvec and the (q, d)x(d, m) GEMM reassociate the f32
reduction differently (1-2 ulp), the same caveat as ``beam_search``'s
two lowerings.
"""

import numpy as np
import pytest

from raft_tpu import SearchExecutor
from raft_tpu.core import tracing
from raft_tpu.core.bitset import Bitset
from raft_tpu.distance.types import DistanceType
from raft_tpu.neighbors import ivf_flat, ivf_pq
from raft_tpu.neighbors.filters import BitmapFilter
from raft_tpu.neighbors.ivf_flat import IvfFlatIndexParams, IvfFlatSearchParams
from raft_tpu.neighbors.ivf_pq import IvfPqIndexParams, IvfPqSearchParams

METRICS = [DistanceType.L2Expanded, DistanceType.L2SqrtExpanded,
           DistanceType.InnerProduct]


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(7)
    x = rng.standard_normal((2000, 24)).astype(np.float32)
    q = rng.standard_normal((33, 24)).astype(np.float32)
    return x, q


@pytest.fixture(scope="module")
def indexes(data):
    x, _ = data
    return {m: ivf_flat.build(
        None, IvfFlatIndexParams(n_lists=16, metric=m), x)
        for m in METRICS}


def _run(index, q, k, engine, n_probes=5, sample_filter=None):
    sp = IvfFlatSearchParams(n_probes=n_probes, scan_engine=engine)
    d, i = ivf_flat.search(None, sp, index, q, k,
                           sample_filter=sample_filter)
    return np.asarray(d), np.asarray(i)


def _assert_engine_parity(index, q, k, n_probes=5, sample_filter=None):
    """pallas == xla bit-identical; both vs rank: ids bit-identical,
    distances to reassociation tolerance."""
    ref_d, ref_i = _run(index, q, k, "rank", n_probes, sample_filter)
    out = {e: _run(index, q, k, e, n_probes, sample_filter)
           for e in ("pallas", "xla")}
    np.testing.assert_array_equal(out["pallas"][1], out["xla"][1])
    np.testing.assert_array_equal(out["pallas"][0], out["xla"][0])
    for e in ("pallas", "xla"):
        np.testing.assert_array_equal(out[e][1], ref_i)
        np.testing.assert_allclose(out[e][0], ref_d, rtol=1e-5, atol=1e-5)


class TestEngineParity:
    @pytest.mark.parametrize("metric", METRICS)
    def test_matches_rank_major(self, data, indexes, metric):
        _, q = data
        _assert_engine_parity(indexes[metric], q, 10)

    @pytest.mark.parametrize("metric", METRICS)
    def test_bitset_filter(self, data, indexes, metric):
        x, q = data
        filt = Bitset.from_mask(np.arange(len(x)) % 3 != 0)
        _assert_engine_parity(indexes[metric], q, 10, n_probes=8,
                              sample_filter=filt)
        # filtered-out ids must never surface
        _, i = _run(indexes[metric], q, 10, "pallas", 8, filt)
        valid = i[i >= 0]
        assert (valid % 3 != 0).all()

    def test_bitmap_filter_falls_back(self, data, indexes):
        """Per-query (2-D) filters route the pallas engine to the XLA
        list-major scan — results still match rank-major ids."""
        x, q = data
        mask = np.ones((len(q), len(x)), bool)
        mask[:, ::2] = False
        bm = BitmapFilter.from_mask(mask)
        index = indexes[DistanceType.L2Expanded]
        ref_d, ref_i = _run(index, q, 10, "rank", 8, bm)
        for engine in ("pallas", "xla"):
            d, i = _run(index, q, 10, engine, 8, bm)
            np.testing.assert_array_equal(i, ref_i)
            np.testing.assert_allclose(d, ref_d, rtol=1e-5, atol=1e-5)

    def test_ragged_k_exceeds_probed(self, data, indexes):
        """k larger than the probed candidate pool: the -1/inf fill
        pattern must match the rank-major scan exactly."""
        _, q = data
        index = indexes[DistanceType.L2Expanded]
        ref_d, ref_i = _run(index, q[:4], 400, "rank", n_probes=1)
        assert (ref_i == -1).any()
        for engine in ("pallas", "xla"):  # pallas falls back (k > cap)
            d, i = _run(index, q[:4], 400, engine, n_probes=1)
            np.testing.assert_array_equal(i, ref_i)
            np.testing.assert_allclose(d, ref_d, rtol=1e-5, atol=1e-5)
            assert not np.isfinite(d[i == -1]).any() or (
                d[i == -1] == np.inf).all()

    def test_exhaustive_probes_all_lists(self, data, indexes):
        """n_probes == n_lists: the union is every list — the dense
        degenerate case (brute force as list-major GEMMs)."""
        _, q = data
        _assert_engine_parity(indexes[DistanceType.L2Expanded], q, 10,
                              n_probes=16)

    def test_bf16_storage(self, data):
        """bf16 lists stream half-width; the kernel upcasts in VMEM and
        must match the rank-major scan's f32 math."""
        import jax.numpy as jnp

        x, q = data
        index = ivf_flat.build(None, IvfFlatIndexParams(n_lists=16),
                               jnp.asarray(x, jnp.bfloat16))
        assert index.data.dtype == jnp.bfloat16
        _assert_engine_parity(index, q, 10)

    def test_int8_falls_back_to_xla(self, data):
        rng = np.random.default_rng(0)
        x8 = rng.integers(-100, 100, (1000, 16)).astype(np.int8)
        q = x8[:8].astype(np.float32)
        index = ivf_flat.build(None, IvfFlatIndexParams(n_lists=8), x8)
        ref_d, ref_i = _run(index, q, 3, "rank", 8)
        d, i = _run(index, q, 3, "pallas", 8)  # resolves to xla
        np.testing.assert_array_equal(i, ref_i)
        np.testing.assert_allclose(d, ref_d, rtol=1e-5, atol=1e-5)

    def test_exact_ties_smallest_id(self, data):
        """Exact duplicate vectors produce genuinely tied distances;
        both list-major engines break ties by smallest dataset id (the
        ``_extract_topk`` order), so they stay bit-identical to each
        other even on ties — the property ``merge_topk``'s positional
        tie-break would not give."""
        x, q = data
        x = x.copy()
        x[1000:1200] = x[:200]  # 200 exact duplicate pairs
        index = ivf_flat.build(None, IvfFlatIndexParams(n_lists=16), x)
        queries = x[:40]        # self-queries guarantee tied top hits
        out = {e: _run(index, queries, 10, e, n_probes=16)
               for e in ("pallas", "xla")}
        np.testing.assert_array_equal(out["pallas"][1], out["xla"][1])
        np.testing.assert_array_equal(out["pallas"][0], out["xla"][0])
        # both members of a duplicate pair must surface among the
        # top hits of their self-query (distance 0 twice)
        ids = out["pallas"][1]
        for r in range(40):
            assert r in ids[r] and (r + 1000) in ids[r]

    def test_multiple_query_tiles_in_kernel(self, data, indexes,
                                            monkeypatch):
        """A tiny VMEM budget forces the kernel's query-tile grid
        dimension > 1; results must not depend on the tiling."""
        _, q = data
        index = indexes[DistanceType.L2Expanded]
        want_d, want_i = _run(index, q, 10, "pallas")
        monkeypatch.setenv("RAFT_TPU_VMEM_MB", "1")
        got_d, got_i = _run(index, q, 10, "pallas")
        np.testing.assert_array_equal(got_i, want_i)
        np.testing.assert_array_equal(got_d, want_d)


class TestUniqueLists:
    def test_union_sorted_sentinel_padded(self):
        import jax.numpy as jnp

        from raft_tpu.ops.ivf_scan import unique_lists

        probes = jnp.asarray([[3, 1, 3], [7, 1, 0], [7, 7, 7]], jnp.int32)
        u = np.asarray(unique_lists(probes, 16))
        assert u.shape == (9,)  # min(16, 3*3)
        np.testing.assert_array_equal(u[:4], [0, 1, 3, 7])
        assert (u[4:] == 16).all()  # sentinel

    def test_cap_at_n_lists(self):
        import jax.numpy as jnp

        from raft_tpu.ops.ivf_scan import unique_lists

        rng = np.random.default_rng(0)
        probes = jnp.asarray(rng.integers(0, 8, (64, 4)), jnp.int32)
        u = np.asarray(unique_lists(probes, 8))
        assert u.shape == (8,)
        np.testing.assert_array_equal(np.sort(u), np.arange(8))


class TestResolveEngine:
    def test_auto_off_tpu_is_xla_list_major(self):
        from raft_tpu.ops.ivf_scan import resolve_scan_engine

        assert resolve_scan_engine("auto") == "xla"
        assert resolve_scan_engine("rank") == "rank"
        assert resolve_scan_engine("xla") == "xla"

    def test_pallas_precondition_fallbacks(self, caplog):
        import jax.numpy as jnp

        from raft_tpu.ops.ivf_scan import _warn_degrade, resolve_scan_engine

        # a degrade is never silent: the first per reason logs
        _warn_degrade.cache_clear()
        data = jnp.zeros((4, 8, 16), jnp.float32)
        assert resolve_scan_engine("pallas", data=data) == "pallas"
        # 2-D per-query filter words
        fw = jnp.zeros((3, 4), jnp.uint32)
        assert resolve_scan_engine("pallas", data=data,
                                   filter_words=fw) == "xla"
        # shared 1-D words are fine
        assert resolve_scan_engine(
            "pallas", data=data, filter_words=fw[0]) == "pallas"
        # byte storage is the kernel's; float16 storage is not
        assert resolve_scan_engine(
            "pallas", data=data.astype(jnp.int8)) == "pallas"
        assert resolve_scan_engine(
            "pallas", data=data.astype(jnp.float16)) == "xla"
        # k beyond the unrolled-merge budget
        assert resolve_scan_engine("pallas", data=data, k=512) == "xla"
        # a single list block that cannot fit VMEM
        big = jnp.zeros((2, 65536, 256), jnp.float32)
        assert resolve_scan_engine("pallas", data=big, vmem_mb=16) == "xla"
        warned = [r.getMessage() for r in caplog.records
                  if "serving it with the xla engine" in r.getMessage()]
        assert len(warned) == 4, warned

    def test_rejects_unknown_engine(self):
        from raft_tpu.core.validation import RaftError
        from raft_tpu.ops.ivf_scan import resolve_scan_engine

        with pytest.raises(RaftError):
            resolve_scan_engine("mosaic")


class TestDirectKernelEntry:
    def test_list_major_scan_direct(self, data, indexes):
        """Drive ops.list_major_scan directly (the guard-test anchor:
        interpret=True reference for the ivf_scan pallas_call)."""
        import jax.numpy as jnp

        from raft_tpu.neighbors._batching import coarse_select
        from raft_tpu.ops.ivf_scan import list_major_scan

        _, q = data
        index = indexes[DistanceType.L2Expanded]
        qf = jnp.asarray(q)
        ip = qf @ index.centers.T
        score = -(index.center_norms[None, :] - 2.0 * ip)
        probes = coarse_select(score, 5, "exact")
        outs = {}
        for engine in ("pallas", "xla"):
            d, i = list_major_scan(
                qf, index.data, index.data_norms, index.indices, probes,
                k=10, metric=DistanceType.L2Expanded, engine=engine,
                interpret=True)
            outs[engine] = (np.asarray(d), np.asarray(i))
        np.testing.assert_array_equal(outs["pallas"][1], outs["xla"][1])
        np.testing.assert_array_equal(outs["pallas"][0], outs["xla"][0])


class TestExecutorIntegration:
    @pytest.mark.parametrize("engine", ["pallas", "xla"])
    def test_bucketing_invariance(self, data, indexes, engine):
        """Query-tile / bucket invariance: the probed-list union grows
        with pad rows and tile boundaries move, but per-query masking
        keeps every real row bit-stable."""
        _, q = data
        index = indexes[DistanceType.L2Expanded]
        p = IvfFlatSearchParams(n_probes=8, scan_engine=engine)
        want_d, want_i = ivf_flat.search(None, p, index, q, 10)
        # direct path, small query tiles (ragged tail padded into tile)
        d, i = ivf_flat.search(None, p, index, q, 10, query_tile=16)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(want_i))
        np.testing.assert_array_equal(np.asarray(d), np.asarray(want_d))
        # serving path at two bucket ladders (pad rows + tiling)
        for ex in (SearchExecutor(),
                   SearchExecutor(min_bucket=8, max_bucket=16)):
            d, i = ex.search(index, q, 10, params=p)
            np.testing.assert_array_equal(np.asarray(i),
                                          np.asarray(want_i))
            np.testing.assert_array_equal(np.asarray(d),
                                          np.asarray(want_d))

    def test_engine_keyed_aot_cache_zero_recompile(self, data, indexes):
        """The resolved scan engine is part of the AOT cache key: the
        pallas engine compiles once per bucket, steady state triggers
        ZERO backend compiles (asserted against jax's own monitoring),
        and switching engines compiles a distinct executable."""
        _, q = data
        index = indexes[DistanceType.L2Expanded]
        tracing.install_xla_compile_listener()
        ex = SearchExecutor()
        p = IvfFlatSearchParams(n_probes=8, scan_engine="pallas")
        for n in (16, 13, 9):  # prime the bucket + pad/slice programs
            ex.search(index, q[:n], 5, params=p)
        assert ex.stats.compile_count == 1
        backend0 = tracing.get_counter(tracing.XLA_COMPILE_COUNT)
        for n in (16, 13, 9, 13, 16, 9):
            ex.search(index, q[:n], 5, params=p)
        assert ex.stats.compile_count == 1
        assert tracing.get_counter(tracing.XLA_COMPILE_COUNT) == backend0
        # a different engine is a different executable, not a reuse
        p2 = IvfFlatSearchParams(n_probes=8, scan_engine="xla")
        d_x, i_x = ex.search(index, q[:16], 5, params=p2)
        assert ex.stats.compile_count == 2
        d_p, i_p = ex.search(index, q[:16], 5, params=p)
        assert ex.stats.compile_count == 2  # both entries live
        np.testing.assert_array_equal(np.asarray(i_x), np.asarray(i_p))
        np.testing.assert_array_equal(np.asarray(d_x), np.asarray(d_p))

    def test_executor_matches_direct_per_engine(self, data, indexes):
        _, q = data
        index = indexes[DistanceType.InnerProduct]
        for engine in ("pallas", "xla", "rank"):
            p = IvfFlatSearchParams(n_probes=8, scan_engine=engine)
            d0, i0 = ivf_flat.search(None, p, index, q[:11], 5)
            d1, i1 = SearchExecutor().search(index, q[:11], 5, params=p)
            np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
            np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))


class TestIvfPqListMajor:
    """The same list-major union formulation on the PQ gathered-codes
    scan: per-list code planes stream once and score the whole tile;
    bit-identical to the rank-major PQ scan on tie-free data (scoring
    is per-element LUT sums — no contraction reassociation in play;
    exact cross-list ADC ties resolve smallest-id in the list-major
    engine vs probe-order in rank-major)."""

    @pytest.fixture(scope="class")
    def pq_setup(self):
        rng = np.random.default_rng(3)
        x = rng.standard_normal((1500, 32)).astype(np.float32)
        q = rng.standard_normal((21, 32)).astype(np.float32)
        return x, q

    @pytest.mark.parametrize("metric", [DistanceType.L2Expanded,
                                        DistanceType.InnerProduct])
    def test_matches_rank_major(self, pq_setup, metric):
        x, q = pq_setup
        index = ivf_pq.build(None, IvfPqIndexParams(
            n_lists=12, pq_dim=8, metric=metric), x)
        filt = Bitset.from_mask(np.arange(len(x)) % 3 != 0)
        for sf in (None, filt):
            ref_d, ref_i = ivf_pq.search(
                None, IvfPqSearchParams(n_probes=4, scan_engine="rank"),
                index, q, 7, sample_filter=sf)
            d, i = ivf_pq.search(
                None, IvfPqSearchParams(n_probes=4, scan_engine="xla"),
                index, q, 7, sample_filter=sf)
            np.testing.assert_array_equal(np.asarray(i), np.asarray(ref_i))
            np.testing.assert_array_equal(np.asarray(d), np.asarray(ref_d))

    def test_executor_engine_keyed(self, pq_setup):
        x, q = pq_setup
        index = ivf_pq.build(None, IvfPqIndexParams(n_lists=12, pq_dim=8),
                             x)
        ex = SearchExecutor()
        for engine in ("rank", "xla"):
            p = IvfPqSearchParams(n_probes=4, scan_engine=engine)
            d0, i0 = ivf_pq.search(None, p, index, q[:9], 5)
            d1, i1 = ex.search(index, q[:9], 5, params=p)
            np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
            np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
        assert ex.stats.compile_count == 2  # one executable per engine


class TestRaggedFront:
    """The ragged query-tile front (ops/ivf_scan.ragged_row_probes /
    ragged_probes + ivf_flat._search_ragged_fn): per-request probe
    budgets resolve through the engines' membership mask, so one
    packed tile is bit-identical per request to solo searches."""

    def test_row_probes_descriptor(self):
        from raft_tpu.ops.ivf_scan import ragged_row_probes

        rp = ragged_row_probes([3, 2, 4], [5, 9, 2], tile=12)
        np.testing.assert_array_equal(
            rp, [5, 5, 5, 9, 9, 2, 2, 2, 2, 0, 0, 0])
        with pytest.raises(Exception):
            ragged_row_probes([8, 8], [1, 1], tile=12)  # overflow

    def test_ragged_probes_masks_to_sentinel(self):
        import jax.numpy as jnp

        from raft_tpu.ops.ivf_scan import ragged_probes

        probes = jnp.arange(12, dtype=jnp.int32).reshape(3, 4)
        rp = jnp.asarray([2, 0, 4], jnp.int32)
        out = np.asarray(ragged_probes(probes, rp, n_lists=99))
        np.testing.assert_array_equal(out[0], [0, 1, 99, 99])
        np.testing.assert_array_equal(out[1], [99] * 4)  # pad row
        np.testing.assert_array_equal(out[2], [8, 9, 10, 11])

    @pytest.mark.parametrize("engine", ["pallas", "xla"])
    @pytest.mark.parametrize("metric", METRICS)
    def test_packed_tile_bit_identical_to_solo(self, data, indexes,
                                               metric, engine):
        """pallas ≡ xla ≡ solo per packed request, mixed n_probes/k."""
        import jax.numpy as jnp

        from raft_tpu.ops.ivf_scan import ragged_row_probes

        _, q = data
        index = indexes[metric]
        sizes, nps, ks = [3, 2, 4, 1], [5, 9, 2, 16], [3, 7, 5, 10]
        tile, np_cap, k_cap = 16, 16, 16
        packed = np.zeros((tile, q.shape[1]), np.float32)
        row = 0
        for m in sizes:
            packed[row:row + m] = q[row:row + m]
            row += m
        rp = ragged_row_probes(sizes, nps, tile)
        # jitted like the serving path compiles it: eager-vs-jit is
        # NOT bit-stable (XLA fuses/reassociates), the contract is
        # jitted-ragged ≡ jitted-solo
        import functools

        import jax

        ragged_jit = jax.jit(functools.partial(
            ivf_flat._search_ragged_fn, n_probes=np_cap, k=k_cap,
            metric=index.metric, scan_engine=engine))
        d, i = ragged_jit(
            jnp.asarray(packed), jnp.asarray(rp), index.centers,
            index.center_norms, index.data, index.data_norms,
            index.indices, None)
        d, i = np.asarray(d), np.asarray(i)
        row = 0
        for m, npb, k in zip(sizes, nps, ks):
            sd, si = _run(index, q[row:row + m], k, engine,
                          n_probes=npb)
            np.testing.assert_array_equal(i[row:row + m, :k], si)
            np.testing.assert_array_equal(d[row:row + m, :k], sd)
            row += m
        # tile pad rows (budget 0) probe nothing: empty results
        assert (i[row:] == -1).all()

    def test_pad_rows_never_pollute_probe_histogram(self, data, indexes):
        import jax.numpy as jnp

        from raft_tpu.ops.ivf_scan import (
            probe_histogram,
            ragged_probes,
            ragged_row_probes,
        )

        index = indexes[DistanceType.L2Expanded]
        _, q = data
        qf = jnp.asarray(q[:8])
        import jax

        ip = qf @ index.centers.T
        _, probes = jax.lax.top_k(-(index.center_norms[None, :] - 2 * ip),
                                  8)
        rp = jnp.asarray(ragged_row_probes([3, 2], [4, 8], tile=8))
        masked = ragged_probes(probes.astype(jnp.int32), rp,
                               index.n_lists)
        counts = probe_histogram(masked,
                                 jnp.zeros((index.n_lists,), jnp.int32))
        assert int(np.asarray(counts).sum()) == 3 * 4 + 2 * 8
