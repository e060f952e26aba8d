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
        """A tiny VMEM budget forces the kernel's smallest query group
        (8 rows); results must not depend on the group size."""
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


# ---------------------------------------------------------------------------
# the grouped Pallas scan: (list, query-group) steps
# ---------------------------------------------------------------------------

SCAN_DTYPES = ["float32", "bfloat16", "uint8", "int8"]
N_LISTS, M_SLOTS, DIM = 12, 40, 24


def _scan_inputs(dtype, q=21, p=4, seed=0):
    """Lists of ``dtype`` with pad slots, and ``q`` (not a multiple of
    8) queries probing ``p`` distinct lists each. Byte lists get
    byte-valued queries, whose distances both engines give exactly."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    if dtype in ("uint8", "int8"):
        lo, hi = (0, 256) if dtype == "uint8" else (-128, 128)
        data = rng.integers(lo, hi, (N_LISTS, M_SLOTS, DIM)).astype(dtype)
        queries = rng.integers(lo, hi, (q, DIM)).astype(np.float32)
    else:
        data = np.asarray(jnp.asarray(
            rng.standard_normal((N_LISTS, M_SLOTS, DIM)), dtype))
        queries = rng.standard_normal((q, DIM)).astype(np.float32)
    ids = np.arange(N_LISTS * M_SLOTS, dtype=np.int32).reshape(
        N_LISTS, M_SLOTS)
    ids[:, 35:] = -1                                     # pad slots
    norms = np.where(ids >= 0, (np.asarray(data, np.float64) ** 2).sum(-1),
                     np.inf).astype(np.float32)
    probes = np.stack([rng.choice(N_LISTS, p, replace=False)
                       for _ in range(q)]).astype(np.int32)
    return queries, data, norms, ids, probes


def _both_engines(queries, data, norms, ids, probes, k, metric,
                  filter_words=None):
    """``{engine: (distances, ids)}`` of the jitted list-major scan."""
    import functools

    import jax
    import jax.numpy as jnp

    from raft_tpu.ops.ivf_scan import list_major_scan

    out = {}
    for engine in ("pallas", "xla"):
        fn = jax.jit(functools.partial(
            list_major_scan, k=k, metric=metric, engine=engine,
            interpret=True))
        d, i = fn(jnp.asarray(queries), jnp.asarray(data),
                  jnp.asarray(norms), jnp.asarray(ids), jnp.asarray(probes),
                  filter_words)
        out[engine] = (np.asarray(d), np.asarray(i))
    return out


def _assert_bit_identical(out):
    np.testing.assert_array_equal(out["pallas"][1], out["xla"][1])
    np.testing.assert_array_equal(out["pallas"][0], out["xla"][0])


def _real_steps(probes, n_lists, g, s):
    from raft_tpu.ops.ivf_scan import _schedule

    _, steps, rows, src = _schedule(probes, n_lists, g, s)
    steps = np.asarray(steps)
    return steps[:s], int(steps[2 * s]), np.asarray(rows), np.asarray(src)


class TestGroupedScan:
    @pytest.mark.parametrize("k", [1, 10, 128])
    @pytest.mark.parametrize("metric", [DistanceType.L2Expanded,
                                        DistanceType.InnerProduct])
    @pytest.mark.parametrize("dtype", SCAN_DTYPES)
    def test_bit_identical_to_xla(self, dtype, metric, k):
        """Ids and distances equal the XLA engine's, bit for bit, for
        every list dtype, both metrics and k from 1 to the unrolled cap
        (k = 128 exceeds the 3 x 35 rows a query probes: -1 fills)."""
        out = _both_engines(*_scan_inputs(dtype, p=3), k, metric)
        _assert_bit_identical(out)
        if k == 128:
            assert (out["pallas"][1] == -1).any()

    @pytest.mark.parametrize("q", [21, 64])
    def test_one_list_probed_by_every_query(self, q):
        """A list every query probes takes ceil(q / G) consecutive steps
        of that list, and the results still equal the XLA engine's."""
        from raft_tpu.ops.ivf_scan import group_shape

        queries, data, norms, ids, probes = _scan_inputs("float32", q=q)
        probes[:, 0] = 3
        probes[:, 1:] = np.where(probes[:, 1:] == 3, 4, probes[:, 1:])
        probes[:, 1:] = np.where(probes[:, 1:] == 4,
                                 (probes[:, 1:] + 1) % N_LISTS,
                                 probes[:, 1:])
        g, s = group_shape(q, 4, data.shape, data.dtype, 10)
        lists, n_real, _, _ = _real_steps(probes, N_LISTS, g, s)
        assert (lists[:n_real] == 3).sum() == -(-q // g)
        _assert_bit_identical(_both_engines(
            queries, data, norms, ids, probes, 10,
            DistanceType.L2Expanded))

    @pytest.mark.parametrize("case", ["ragged_budget_0", "not_owned"])
    def test_sentinel_rows(self, case):
        """Rows whose every probe is the sentinel (a ragged row of
        budget 0) or some of whose probes are (probes another shard of
        a list-sharded index owns) take no pair for those probes; an
        all-sentinel row comes back empty."""
        from raft_tpu.ops.ivf_scan import ragged_probes

        queries, data, norms, ids, probes = _scan_inputs("uint8")
        if case == "ragged_budget_0":
            budget = np.resize([4, 0, 2], len(probes)).astype(np.int32)
            probes = np.asarray(ragged_probes(probes, budget, N_LISTS))
            empty = budget == 0
        else:
            rng = np.random.default_rng(5)
            owned = rng.random(probes.shape) < 0.5
            owned[0] = False
            probes = np.where(owned, probes, N_LISTS).astype(np.int32)
            empty = ~owned.any(axis=1)
        out = _both_engines(queries, data, norms, ids, probes, 10,
                            DistanceType.L2Expanded)
        _assert_bit_identical(out)
        assert empty.any()
        assert (out["pallas"][1][empty] == -1).all()
        assert (out["pallas"][0][empty] == np.inf).all()

    @pytest.mark.parametrize("metric", [DistanceType.L2Expanded,
                                        DistanceType.InnerProduct])
    def test_shared_bitset(self, metric):
        """A shared 1-D bitset folds into the id planes; filtered ids
        never surface and the results equal the XLA engine's."""
        from raft_tpu.neighbors.filters import resolve_filter_words

        queries, data, norms, ids, probes = _scan_inputs("float32")
        keep = np.arange(N_LISTS * M_SLOTS) % 3 != 0
        words = resolve_filter_words(Bitset.from_mask(keep))
        out = _both_engines(queries, data, norms, ids, probes, 10, metric,
                            filter_words=words)
        _assert_bit_identical(out)
        got = out["pallas"][1]
        assert (got[got >= 0] % 3 != 0).all()

    @pytest.mark.parametrize("seed", range(6))
    def test_real_steps_within_the_static_cap(self, seed):
        """On random, skewed probe blocks (sentinels and repeats mixed
        in) the real steps never exceed S, every step holds at most G
        rows of one list, and every kept pair's slot is in a real step
        of its own list and row."""
        from raft_tpu.ops.ivf_scan import group_shape

        rng = np.random.default_rng(seed)
        n_lists = int(rng.choice([4, 16, 64]))
        q, p = int(rng.integers(1, 70)), int(rng.integers(1, 9))
        # skew: most probes land on a few lists
        hot = rng.integers(0, n_lists, 3)
        probes = np.where(rng.random((q, p)) < 0.6,
                          rng.choice(hot, (q, p)),
                          rng.integers(0, n_lists + 1, (q, p)))
        probes = probes.astype(np.int32)
        g, s = group_shape(q, p, (n_lists, 40, 24), np.float32, 10)
        lists, n_real, rows, src = _real_steps(probes, n_lists, g, s)
        assert 0 <= n_real <= s
        flat = probes.reshape(-1)
        for j in np.flatnonzero(src >= 0):
            step, row = divmod(int(src[j]), g)
            assert step < n_real
            assert lists[step] == flat[j]
            assert rows[src[j]] == j // p
        # kept pairs: each real (row, list) probe once, sentinels none
        want = {(j // p, int(flat[j])) for j in range(flat.size)
                if flat[j] < n_lists}
        assert len(want) == int((src >= 0).sum())
        assert len(set(src[src >= 0].tolist())) == len(want)

    @pytest.mark.parametrize("where", ["single_chip", "mesh"])
    def test_bucket_pad_rows_add_no_pair(self, data, indexes, monkeypatch,
                                         where):
        """The executor hands the search its real row count, so the
        probes of bucket-pad rows reach the grouped scan as sentinels:
        13 rows in a 16-row bucket, three pad rows, no pad pair — on one
        chip and on every shard of a list-sharded index."""
        import jax

        from raft_tpu.comms import local_comms
        from raft_tpu.distributed import ivf as dist_ivf
        from raft_tpu.ops import ivf_scan

        seen = []
        module = ivf_scan if where == "single_chip" else dist_ivf
        scan = module.list_major_scan

        def spy(qf, data_, norms, ids, probes, *a, **kw):
            jax.debug.callback(lambda pr: seen.append(np.asarray(pr)),
                               probes)
            return scan(qf, data_, norms, ids, probes, *a, **kw)

        monkeypatch.setattr(module, "list_major_scan", spy)
        x, q = data
        index = indexes[DistanceType.L2Expanded]
        n_lists = index.n_lists
        if where == "mesh":
            comms = local_comms()
            index = dist_ivf.build(None, comms,
                                   IvfFlatIndexParams(n_lists=16), x)
            n_lists = index.n_lists // comms.size
        p = IvfFlatSearchParams(n_probes=5, scan_engine="pallas")
        ex = SearchExecutor(min_bucket=16, max_bucket=16)
        d, i = ex.search(index, q[:13], 10, params=p)
        jax.effects_barrier()
        assert seen
        for probes in seen:
            assert probes.shape == (16, 5)
            assert (probes[13:] == n_lists).all()
        if where == "single_chip":
            assert (seen[0][:13] < n_lists).all()
        want_d, want_i = _run(indexes[DistanceType.L2Expanded], q[:13],
                              10, "xla")
        np.testing.assert_array_equal(np.asarray(i), want_i)
        np.testing.assert_array_equal(np.asarray(d), want_d)
        (info,) = ex.executable_costs().values()
        assert info["engine"] == "pallas"
        shape = (n_lists,) + tuple(index.data.shape[1:])
        assert (info["scan_group_rows"], info["scan_steps"]) == \
            ivf_scan.group_shape(16, 5, shape, index.data.dtype, 10)

    @pytest.mark.parametrize("engine,grouped", [("pallas", 1), ("xla", 0)])
    def test_grouped_dispatch_counter(self, data, indexes, engine,
                                      grouped):
        """``serving.scan.grouped_dispatches`` counts the dispatches of
        executables that run the grouped Pallas scan, and only those."""
        _, q = data
        index = indexes[DistanceType.L2Expanded]
        p = IvfFlatSearchParams(n_probes=5, scan_engine=engine)
        ex = SearchExecutor()
        ex.search(index, q[:9], 10, params=p)
        before = tracing.get_counter("serving.scan.grouped_dispatches")
        calls = tracing.get_counter("serving.execute.calls")
        for n in (9, 16, 33):
            ex.search(index, q[:n], 10, params=p)
        calls = tracing.get_counter("serving.execute.calls") - calls
        after = tracing.get_counter("serving.scan.grouped_dispatches")
        assert calls >= 3
        assert after - before == grouped * calls
