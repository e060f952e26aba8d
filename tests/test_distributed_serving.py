"""Mesh-native serving tests — the PR-3 acceptance suite.

- Engine parity: the list-sharded IVF search (probe_mode=global) is
  bit-identical (ids AND distances) to the single-device index for
  every ``scan_engine``, on the 8-virtual-CPU-device mesh.
- Lean collectives: the lean probe-candidate exchange selects the same
  probe set as the dense coarse-block gather; ``wire_dtype="bf16"``
  result compression keeps ids exact-ranked (smallest-id ties) and
  shard-count deterministic.
- Mesh-aware SearchExecutor: bucketing invariance (bit-identity with
  the direct distributed search at batch sizes that do and do not fill
  their bucket) and the zero-recompile steady-state guarantee, asserted
  against jax's backend-compile monitoring events.
- Streamed build deal: the per-shard placement produces the same index
  as the dealt layout contract requires, and the peak build-device
  staging counter stays at one block.
"""

import numpy as np
import pytest

from raft_tpu import SearchExecutor
from raft_tpu.comms import local_comms
from raft_tpu.core import tracing
from raft_tpu.distributed import bq as dist_bq, ivf as dist_ivf
from raft_tpu.neighbors import ivf_bq, ivf_flat, ivf_pq
from raft_tpu.neighbors.ivf_flat import (
    IvfFlatIndexParams,
    IvfFlatSearchParams,
)
from raft_tpu.neighbors.ivf_pq import IvfPqIndexParams, IvfPqSearchParams

N_DEV = 8


@pytest.fixture(scope="module")
def comms():
    return local_comms()


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(11)
    x = rng.standard_normal((4096, 32)).astype(np.float32)
    q = rng.standard_normal((16, 32)).astype(np.float32)
    return x, q


@pytest.fixture(scope="module")
def flat_pair(comms, data):
    """The same dataset built as a single-device index and as the
    list-sharded distributed index (same params/resources, so the
    quantizer and packed lists are identical — only the deal differs)."""
    x, _ = data
    params = IvfFlatIndexParams(n_lists=32)
    return (ivf_flat.build(None, params, x),
            dist_ivf.build(None, comms, params, x))


class TestEngineParity:
    """probe_mode=global must be bit-identical to the single-device
    index for every scan engine — the tentpole acceptance criterion."""

    @pytest.mark.parametrize("engine", ["rank", "xla", "pallas", "auto"])
    @pytest.mark.parametrize("n_probes", [4, 12, 32])
    def test_flat_bit_identical(self, data, flat_pair, engine, n_probes):
        _, q = data
        single, dist = flat_pair
        sp = IvfFlatSearchParams(n_probes=n_probes, scan_engine=engine)
        d0, i0 = ivf_flat.search(None, sp, single, q, 10)
        d1, i1 = dist_ivf.search(None, sp, dist, q, 10)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))

    def test_flat_inner_product(self, comms, data):
        x, q = data
        from raft_tpu.distance.types import DistanceType

        params = IvfFlatIndexParams(n_lists=32,
                                    metric=DistanceType.InnerProduct)
        single = ivf_flat.build(None, params, x)
        dist = dist_ivf.build(None, comms, params, x)
        for engine in ("rank", "xla"):
            sp = IvfFlatSearchParams(n_probes=8, scan_engine=engine)
            d0, i0 = ivf_flat.search(None, sp, single, q, 10)
            d1, i1 = dist_ivf.search(None, sp, dist, q, 10)
            np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
            np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))

    @pytest.mark.parametrize("engine", ["xla", "rank"])
    def test_pq_engines(self, comms, data, engine):
        """PQ union scan per shard: the xla engine must match the
        single-chip xla engine bit-for-bit (shared smallest-id ADC
        tie-break); the rank engine tracks it on id sets (positional
        ties may legitimately order differently across layouts)."""
        x, q = data
        params = IvfPqIndexParams(n_lists=16, pq_dim=16)
        single = ivf_pq.build(None, params, x)
        dist = dist_ivf.build_pq(None, comms, params, x)
        sp = IvfPqSearchParams(n_probes=8, scan_engine=engine)
        d0, i0 = ivf_pq.search(None, sp, single, q, 10)
        d1, i1 = dist_ivf.search_pq(None, sp, dist, q, 10)
        if engine == "xla":
            np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
            np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
        else:
            for a, b in zip(np.asarray(i0), np.asarray(i1)):
                assert set(a.tolist()) == set(b.tolist())

    def test_full_probes_equal_brute_force(self, comms, data):
        x, q = data
        dist = dist_ivf.build(None, comms, IvfFlatIndexParams(n_lists=16),
                              x)
        d2 = ((q[:, None, :] - x[None, :, :]) ** 2).sum(-1)
        gt = np.argsort(d2, axis=1, kind="stable")[:, :5]
        for engine in ("rank", "xla", "pallas"):
            sp = IvfFlatSearchParams(n_probes=16, scan_engine=engine)
            _, i = dist_ivf.search(None, sp, dist, q, 5)
            np.testing.assert_array_equal(np.asarray(i), gt)


class TestLeanCollectives:
    def test_lean_probe_select_matches_dense(self, data, flat_pair):
        """n_probes small enough to take the lean candidate exchange
        (2·local_k < n_local) must return the same results as the
        single-device probe set — the lean path is exact."""
        _, q = data
        single, dist = flat_pair
        # n_local = 32/8 = 4 -> lean needs local_k < 2: n_probes=1
        sp = IvfFlatSearchParams(n_probes=1, scan_engine="xla")
        d0, i0 = ivf_flat.search(None, sp, single, q, 5)
        d1, i1 = dist_ivf.search(None, sp, dist, q, 5)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))

    def test_lean_vs_dense_larger_mesh_budget(self, comms, data):
        """With more lists per shard, a mid-size probe budget rides the
        lean branch; it must match the dense branch bit-for-bit
        (synthesized by a probe budget that forces the dense path on
        the same index)."""
        x, q = data
        dist = dist_ivf.build(None, comms, IvfFlatIndexParams(n_lists=128),
                              x)
        # n_local = 16: n_probes=4 -> local_k=4, lean; compare against
        # the single-device search (the exactness oracle)
        single = ivf_flat.build(None, IvfFlatIndexParams(n_lists=128), x)
        sp = IvfFlatSearchParams(n_probes=4, scan_engine="xla")
        d0, i0 = ivf_flat.search(None, sp, single, q, 5)
        d1, i1 = dist_ivf.search(None, sp, dist, q, 5)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))

    def test_wire_dtype_bf16(self, data, flat_pair):
        """bf16 wire compression: ids stay int32-exact and the result
        ranking follows the compressed distances with smallest-id
        ties; against the f32 wire the id sets stay near-identical on
        well-separated data."""
        _, q = data
        _, dist = flat_pair
        sp = IvfFlatSearchParams(n_probes=8, scan_engine="xla")
        d32, i32 = dist_ivf.search(None, sp, dist, q, 10)
        d16, i16 = dist_ivf.search(None, sp, dist, q, 10,
                                   wire_dtype="bf16")
        assert np.asarray(i16).dtype == np.int32
        agree = (np.asarray(i32) == np.asarray(i16)).mean()
        assert agree >= 0.9, agree
        # compressed distances within bf16 relative tolerance
        np.testing.assert_allclose(np.asarray(d16), np.asarray(d32),
                                   rtol=1e-2, atol=1e-2)

    def test_wire_dtype_validates(self, data, flat_pair):
        _, q = data
        _, dist = flat_pair
        with pytest.raises(ValueError, match="wire_dtype"):
            dist_ivf.search(None, IvfFlatSearchParams(n_probes=4), dist,
                            q, 5, wire_dtype="f16")

    def test_payload_model_is_lean(self):
        """Acceptance: global-mode probe selection and result merge move
        O(q · n_probes) and O(q · k) payloads, not the O(q · n_lists/R)
        coarse block."""
        m = dist_ivf.collective_payload_model(
            q=64, k=10, n_probes=32, n_lists=4096, r=8,
            wire_dtype="bf16")
        assert m["coarse_bytes"] == 64 * 32 * 8      # (d, id) candidates
        assert m["coarse_bytes"] < m["dense_coarse_bytes"]
        assert m["merge_bytes"] == 64 * 10 * (2 + 4)  # bf16 wire + ids


def _sharded_flat(n_shards):
    """A flat index list-sharded over ``n_shards`` devices + queries —
    the quantized-wire recall study's fixture builder."""
    import jax

    from raft_tpu.comms.comms import Comms
    from raft_tpu.comms.bootstrap import make_mesh

    rng = np.random.default_rng(23)
    x = rng.standard_normal((4096, 32)).astype(np.float32)
    q = rng.standard_normal((64, 32)).astype(np.float32)
    comms = Comms(make_mesh(("data",),
                            devices=jax.devices()[:n_shards]), "data")
    dist = dist_ivf.build(None, comms, IvfFlatIndexParams(n_lists=64), x)
    return dist, q


class TestQuantizedProbeExchange:
    """ROADMAP item: the probe-candidate exchange rides the
    ``probe_wire_dtype`` quantized wire (bf16, and int8 with
    block-independent per-row affine scales) — recall swept vs shard
    count against the exact f32 exchange (graftwire satellite: the
    16-shard point is the slow-marked tail of the study)."""

    @pytest.fixture(scope="class")
    def four_shard(self):
        return _sharded_flat(4)

    @pytest.mark.parametrize("probe_wire", ["bf16", "int8"])
    @pytest.mark.parametrize("n_shards", [
        4, 8,
        pytest.param(16, marks=pytest.mark.slow),
    ])
    def test_recall_vs_shards(self, n_shards, probe_wire):
        import jax

        if len(jax.devices()) < n_shards:
            pytest.skip(f"needs {n_shards} devices")
        dist, q = _sharded_flat(n_shards)
        # n_local = 64 / n_shards, n_probes = 4 -> the exchange goes
        # lean at 4/8 shards and dense at 16 (2*4 >= 64/16) — the
        # sweep covers both wire layouts
        sp = IvfFlatSearchParams(n_probes=4, scan_engine="xla")
        _, i_exact = dist_ivf.search(None, sp, dist, q, 10)
        _, i_q = dist_ivf.search(None, sp, dist, q, 10,
                                 probe_wire_dtype=probe_wire)
        exact = np.asarray(i_exact)
        got = np.asarray(i_q)
        recall = np.mean([
            len(set(got[r]) & set(exact[r])) / 10
            for r in range(exact.shape[0])])
        floor = 0.99 if probe_wire == "bf16" else 0.95
        assert recall >= floor, (n_shards, probe_wire, recall)

    def test_dense_fallback_also_quantizes(self, four_shard):
        """Probing most of the index takes the dense coarse-block
        gather; the quantized wire applies there too and recall holds
        (at a probe budget this wide the probe sets barely move)."""
        dist, q = four_shard
        sp = IvfFlatSearchParams(n_probes=48, scan_engine="xla")
        _, i_exact = dist_ivf.search(None, sp, dist, q, 10)
        _, i_q = dist_ivf.search(None, sp, dist, q, 10,
                                 probe_wire_dtype="int8")
        exact, got = np.asarray(i_exact), np.asarray(i_q)
        recall = np.mean([
            len(set(got[r]) & set(exact[r])) / 10
            for r in range(exact.shape[0])])
        assert recall >= 0.99, recall

    def test_executor_serves_quantized_probe_wire(self, four_shard):
        """The mesh-aware executor plans the quantized exchange as a
        distinct static (own AOT executable) and matches the direct
        entry bit-for-bit."""
        dist, q = four_shard
        sp = IvfFlatSearchParams(n_probes=4, scan_engine="xla")
        d0, i0 = dist_ivf.search(None, sp, dist, q, 10,
                                 probe_wire_dtype="int8")
        ex = SearchExecutor()
        d1, i1 = ex.search(dist, q, 10, params=sp,
                           probe_wire_dtype="int8")
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))

    def test_probe_wire_validates(self, data, flat_pair):
        _, q = data
        _, dist = flat_pair
        with pytest.raises(ValueError, match="probe wire_dtype"):
            dist_ivf.search(None, IvfFlatSearchParams(n_probes=4), dist,
                            q, 5, probe_wire_dtype="f16")

    def test_payload_model_prices_quantized_probes(self):
        f32 = dist_ivf.collective_payload_model(
            q=64, k=10, n_probes=32, n_lists=4096, r=8)
        bf16 = dist_ivf.collective_payload_model(
            q=64, k=10, n_probes=32, n_lists=4096, r=8,
            probe_wire_dtype="bf16")
        i8 = dist_ivf.collective_payload_model(
            q=64, k=10, n_probes=32, n_lists=4096, r=8,
            probe_wire_dtype="int8")
        assert f32["coarse_bytes"] == 64 * 32 * 8
        assert bf16["coarse_bytes"] == 64 * 32 * 6
        # + per-row (min, range) f32 affine scale pair — the
        # block-independent scheme that lets int8 ride ragged
        assert i8["coarse_bytes"] == 64 * (32 * 5 + 8)
        assert i8["coarse_bytes"] < bf16["coarse_bytes"] \
            < f32["coarse_bytes"]


class TestMeshExecutor:
    """Mesh-aware SearchExecutor: bucketing invariance + the
    zero-recompile steady state, per engine."""

    @pytest.mark.parametrize("engine", ["rank", "xla", "pallas"])
    @pytest.mark.parametrize("q_rows", [3, 11, 16])
    def test_bucketing_invariance(self, data, flat_pair, engine, q_rows):
        _, q = data
        _, dist = flat_pair
        sp = IvfFlatSearchParams(n_probes=8, scan_engine=engine)
        ex = SearchExecutor()
        d0, i0 = dist_ivf.search(None, sp, dist, q[:q_rows], 5)
        d1, i1 = ex.search(dist, q[:q_rows], 5, params=sp)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))

    def test_zero_recompiles_within_bucket(self, data, flat_pair):
        _, q = data
        _, dist = flat_pair
        tracing.install_xla_compile_listener()
        sp = IvfFlatSearchParams(n_probes=8, scan_engine="xla")
        ex = SearchExecutor()
        # prime each batch size (search compiles once per bucket; the
        # tiny pad/place programs compile per distinct size)
        for n in (16, 13, 9):
            ex.search(dist, q[:n], 5, params=sp)
        compiles0 = ex.stats.compile_count
        assert compiles0 == 1
        backend0 = tracing.get_counter(tracing.XLA_COMPILE_COUNT)
        for n in (16, 13, 9, 13, 16, 9):
            ex.search(dist, q[:n], 5, params=sp)
        assert ex.stats.compile_count == compiles0
        assert tracing.get_counter(tracing.XLA_COMPILE_COUNT) == backend0
        assert ex.stats.cache_hits >= 8

    def test_engine_switch_is_distinct_executable(self, data, flat_pair):
        _, q = data
        _, dist = flat_pair
        ex = SearchExecutor()
        ex.search(dist, q, 5,
                  params=IvfFlatSearchParams(n_probes=8, scan_engine="xla"))
        c0 = ex.stats.compile_count
        ex.search(dist, q, 5,
                  params=IvfFlatSearchParams(n_probes=8,
                                             scan_engine="rank"))
        assert ex.stats.compile_count == c0 + 1

    def test_warmup_then_serve(self, data, flat_pair):
        _, q = data
        _, dist = flat_pair
        sp = IvfFlatSearchParams(n_probes=8, scan_engine="xla")
        ex = SearchExecutor()
        secs = ex.warmup(dist, buckets=(16,), k=5, params=sp)
        assert secs > 0 and ex.stats.compile_count == 1
        d, i = ex.search(dist, q, 5, params=sp)
        assert ex.stats.compile_count == 1
        assert ex.stats.cache_hits == 1
        d0, i0 = dist_ivf.search(None, sp, dist, q, 5)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i))

    def test_results_survive_the_next_dispatch(self, data, flat_pair):
        """Results returned from call N must survive call N+1 of the
        same mesh executable."""
        _, q = data
        _, dist = flat_pair
        sp = IvfFlatSearchParams(n_probes=8, scan_engine="xla")
        ex = SearchExecutor()
        d1, i1 = ex.search(dist, q[:16], 5, params=sp)
        d1c, i1c = np.asarray(d1).copy(), np.asarray(i1).copy()
        ex.search(dist, q[:9], 5, params=sp)
        np.testing.assert_array_equal(np.asarray(d1), d1c)
        np.testing.assert_array_equal(np.asarray(i1), i1c)

    def test_pq_and_bq_through_executor(self, comms, data):
        x, q = data
        pqi = dist_ivf.build_pq(
            None, comms, IvfPqIndexParams(n_lists=16, pq_dim=16), x)
        sp = IvfPqSearchParams(n_probes=8)
        ex = SearchExecutor()
        d0, i0 = dist_ivf.search_pq(None, sp, pqi, q[:9], 5)
        d1, i1 = ex.search(pqi, q[:9], 5, params=sp)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))

        bqi = dist_bq.build_bq(
            None, comms, ivf_bq.IvfBqIndexParams(n_lists=16), x)
        spb = ivf_bq.IvfBqSearchParams(n_probes=8)
        d0, i0 = dist_bq.search_bq(None, spb, bqi, q[:9], 10)
        d1, i1 = ex.search(bqi, q[:9], 10, params=spb)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))

    def test_rejects_filter_and_bad_query_axis(self, data, flat_pair):
        from raft_tpu.core.bitset import Bitset
        from raft_tpu.core.validation import RaftError
        from raft_tpu.neighbors.filters import BitsetFilter

        x, q = data
        _, dist = flat_pair
        ex = SearchExecutor()
        bs = Bitset.from_mask(np.ones(x.shape[0], bool))
        with pytest.raises(RaftError, match="sample_filter"):
            ex.search(dist, q, 5, params=IvfFlatSearchParams(n_probes=4),
                      sample_filter=BitsetFilter(bs))
        # query_axis must name ANOTHER axis of the index's mesh — a
        # 1-D mesh has none to offer
        with pytest.raises(RaftError, match="query_axis"):
            ex.search(dist, q, 5, params=IvfFlatSearchParams(n_probes=4),
                      query_axis="queries")


def _grid_pair(data):
    """The same dataset list-sharded over a 1-D 4-device mesh and over
    the lists axis of a 4×2 (lists × queries) grid — built with the
    same params so the quantizer and deal are identical."""
    import jax
    from jax.sharding import Mesh

    from raft_tpu.comms.comms import Comms

    x, _ = data
    params = IvfFlatIndexParams(n_lists=32)
    devs = np.array(jax.devices()[:8]).reshape(4, 2)
    c2 = Comms(Mesh(devs, ("lists", "queries")), "lists")
    c1 = Comms(Mesh(np.array(jax.devices()[:4]), ("data",)), "data")
    return (dist_ivf.build(None, c1, params, x),
            dist_ivf.build(None, c2, params, x))


class Test2DMeshExecutor:
    """graftwire: 2-D query×list grids join the zero-recompile world —
    the executor's bucketed plans shard the padded query block over
    ``query_axis``, scatter-merge within the list axis, and key the
    AOT cache on the full 2-D mesh identity."""

    @pytest.fixture(scope="class")
    def grid_pair(self, data):
        return _grid_pair(data)

    @pytest.mark.parametrize("engine", ["rank", "xla", "pallas"])
    def test_bit_identical_to_1d(self, data, grid_pair, engine):
        _, q = data
        d1, d2 = grid_pair
        sp = IvfFlatSearchParams(n_probes=8, scan_engine=engine)
        ex = SearchExecutor()
        a_d, a_i = ex.search(d1, q, 5, params=sp)
        b_d, b_i = ex.search(d2, q, 5, params=sp,
                             query_axis="queries")
        np.testing.assert_array_equal(np.asarray(a_i), np.asarray(b_i))
        np.testing.assert_array_equal(np.asarray(a_d), np.asarray(b_d))

    def test_quantized_wires_bit_identical_to_1d(self, data, grid_pair):
        _, q = data
        d1, d2 = grid_pair
        sp = IvfFlatSearchParams(n_probes=8, scan_engine="xla")
        ex = SearchExecutor()
        kw = dict(wire_dtype="bf16", probe_wire_dtype="int8")
        a_d, a_i = ex.search(d1, q, 5, params=sp, **kw)
        b_d, b_i = ex.search(d2, q, 5, params=sp,
                             query_axis="queries", **kw)
        np.testing.assert_array_equal(np.asarray(a_i), np.asarray(b_i))
        np.testing.assert_array_equal(np.asarray(a_d), np.asarray(b_d))

    def test_zero_recompiles_under_load(self, data, grid_pair):
        """Warm the ladder once, then prime-sized batches serve with
        ZERO backend compiles — the recompile hole the 2-D mesh used
        to have (the per-query-shard block is bucketed and the plan
        key carries the 2-D mesh)."""
        rng = np.random.default_rng(31)
        _, d2 = grid_pair
        sp = IvfFlatSearchParams(n_probes=8, scan_engine="xla")
        dist = d2
        ex = SearchExecutor(min_bucket=16, max_bucket=64)
        ex.warmup(dist, k=5, params=sp, query_axis="queries")
        # primer: one dispatch per bucket compiles nothing new but
        # creates the tiny per-size pad programs
        for n in (16, 13, 9, 64, 33):
            ex.search(dist, rng.standard_normal(
                (n, 32)).astype(np.float32), 5, params=sp,
                query_axis="queries")
        tracing.install_xla_compile_listener()
        c0 = tracing.get_counter(tracing.XLA_COMPILE_COUNT)
        compiles0 = ex.stats.compile_count
        for n in (16, 13, 9, 64, 33, 9, 13):
            ex.search(dist, rng.standard_normal(
                (n, 32)).astype(np.float32), 5, params=sp,
                query_axis="queries")
        assert ex.stats.compile_count == compiles0
        assert tracing.get_counter(tracing.XLA_COMPILE_COUNT) == c0

    def test_auto_wire_selection(self, data, grid_pair):
        """``wire_dtype="auto"``/``probe_wire_dtype="auto"`` close the
        loop on the payload model: the resolved plan serves, and the
        model's argmin picks the narrowest wire at this shape."""
        _, q = data
        d1, d2 = grid_pair
        sp = IvfFlatSearchParams(n_probes=8, scan_engine="xla")
        ex = SearchExecutor()
        b_d, b_i = ex.search(d2, q, 5, params=sp, query_axis="queries",
                             wire_dtype="auto", probe_wire_dtype="auto")
        assert np.asarray(b_d).shape == (16, 5)
        # at this tiny grid int8's scale plane ties bf16's dense block
        # — the tie prefers the wider (less lossy) wire
        wd, pwd = dist_ivf.resolve_auto_wires(
            16, 5, 8, 32, 4, "auto", "global", "auto")
        assert wd == "bf16" and pwd == "bf16"
        # at a serving-scale candidate shape the int8 codes dwarf
        # their scale plane and the argmin flips to int8
        _, pwd_big = dist_ivf.resolve_auto_wires(
            64, 10, 32, 4096, 8, "auto", "global", "auto")
        assert pwd_big == "int8"
        # concrete dtypes pass through untouched
        assert dist_ivf.resolve_auto_wires(
            16, 5, 8, 32, 4, "f32", "global", "bf16") == ("f32", "bf16")


class TestStreamedBuildDeal:
    def test_peak_staging_is_one_block(self, comms, data):
        x, _ = data
        tracing.reset_counters("distributed.build.")
        index = dist_ivf.build(None, comms, IvfFlatIndexParams(n_lists=32),
                               x)
        peak = tracing.get_counter(
            "distributed.build.peak_deal_block_bytes")
        total = tracing.get_counter("distributed.build.deal_bytes_total")
        data_bytes = index.data.size * index.data.dtype.itemsize
        assert 0 < peak <= data_bytes // N_DEV + 1
        assert total >= data_bytes
        # and the dealt index still searches exactly
        q = x[:4]
        sp = IvfFlatSearchParams(n_probes=32)
        _, i = dist_ivf.search(None, sp, index, q, 1)
        np.testing.assert_array_equal(np.asarray(i)[:, 0],
                                      np.arange(4))


class TestPayloadGauges:
    """graftscope (PR 6): compiling a mesh executable publishes its
    modeled collective payload as live gauges — the same accounting
    the bench rider emits, scrapeable while serving."""

    def test_mesh_compile_publishes_collective_gauges(self, data,
                                                      flat_pair):
        _, q = data
        _, dist = flat_pair
        tracing.reset_gauges("serving.collective.")
        sp = IvfFlatSearchParams(n_probes=8)
        ex = SearchExecutor()
        ex.search(dist, q, 5, params=sp, wire_dtype="bf16")
        got = tracing.gauges("serving.collective.dist_ivf_flat.bf16.f32.")
        assert set(n.rsplit(".", 1)[1] for n in got) == {
            "coarse_bytes", "dense_coarse_bytes", "merge_bytes"}
        model = dist_ivf.collective_payload_model(
            16, 5, 8, dist.n_lists, N_DEV, "bf16")
        base = "serving.collective.dist_ivf_flat.bf16.f32."
        assert got[base + "merge_bytes"] == model["merge_bytes"]
        assert got[base + "coarse_bytes"] == model["coarse_bytes"]
        # the executor's cost table carries the same model per entry
        (info,) = ex.executable_costs().values()
        assert info["collective_payload"]["merge_bytes"] == (
            model["merge_bytes"])
        # a gauge wipe (metrics.reset) heals at scrape time: the
        # resident mesh entry re-publishes its collective gauges too
        tracing.reset_gauges("serving.")
        assert tracing.gauges(base) == {}
        ex.publish_cost_gauges()
        assert tracing.gauges(base)[base + "merge_bytes"] == (
            model["merge_bytes"])


class TestMeshSpans:
    """graftscope v2: trace_id propagation into the distributed search
    — phase spans with modeled wire bytes, per-shard straggler spans,
    and the regressions (bit-identity + zero-recompile) re-asserted
    with mesh tracing fully enabled."""

    def test_executor_mesh_span_tree(self, data, flat_pair):
        _, q = data
        single, dist = flat_pair
        tracing.reset_spans()
        tracing.reset_gauges("serving.mesh.")
        sp = IvfFlatSearchParams(n_probes=8, scan_engine="xla")
        ex = SearchExecutor(mesh_trace=True)
        tid = tracing.new_trace_id()
        d1, i1 = ex.search(dist, q, 5, params=sp, trace_ids=(tid,))
        # tracing changes nothing about the results
        d0, i0 = ivf_flat.search(None, sp, single, q, 5)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
        rec = tracing.span_recorder()
        # the three mesh phases, each carrying the trace id AND the
        # modeled wire bytes of the entry's collective_payload_model
        model = dist_ivf.collective_payload_model(
            16, 5, 8, dist.n_lists, N_DEV)
        (cs,) = rec.spans(trace_id=tid,
                          name="serving.mesh.coarse_select")
        assert cs.attrs["wire_bytes"] == model["coarse_bytes"]
        (mg,) = rec.spans(trace_id=tid, name="serving.mesh.merge")
        assert mg.attrs["wire_bytes"] == model["merge_bytes"]
        assert rec.spans(trace_id=tid, name="serving.mesh.scan")
        # one readiness span per shard of the 8-device mesh, and the
        # straggler gauges reduced from those timings
        shards = rec.spans(trace_id=tid, name="serving.mesh.shard")
        assert [s.attrs["shard"] for s in shards] == list(range(N_DEV))
        assert all(s.attrs["family"] == "dist_ivf_flat" for s in shards)
        slowest = tracing.get_gauge(tracing.MESH_SLOWEST_SHARD)
        times = [s.duration for s in shards]
        assert times[int(slowest)] == max(times)
        assert tracing.get_gauge(
            tracing.MESH_SHARD_SKEW) == pytest.approx(
                max(times) - min(times))

    def test_zero_recompiles_with_mesh_tracing_enabled(self, data,
                                                       flat_pair):
        _, q = data
        _, dist = flat_pair
        tracing.install_xla_compile_listener()
        sp = IvfFlatSearchParams(n_probes=8, scan_engine="xla")
        ex = SearchExecutor(mesh_trace=True)
        tid = tracing.new_trace_id()
        for n in (16, 13, 9):
            ex.search(dist, q[:n], 5, params=sp, trace_ids=(tid,))
        compiles0 = ex.stats.compile_count
        assert compiles0 == 1
        backend0 = tracing.get_counter(tracing.XLA_COMPILE_COUNT)
        for n in (16, 13, 9, 13, 16):
            ex.search(dist, q[:n], 5, params=sp, trace_ids=(tid,))
        assert ex.stats.compile_count == compiles0
        assert tracing.get_counter(tracing.XLA_COMPILE_COUNT) == backend0

    def test_direct_search_trace_id(self, data, flat_pair):
        _, q = data
        single, dist = flat_pair
        tracing.reset_spans()
        sp = IvfFlatSearchParams(n_probes=8, scan_engine="xla")
        tid = tracing.new_trace_id()
        d1, i1 = dist_ivf.search(None, sp, dist, q, 5, trace_id=tid)
        d0, i0 = ivf_flat.search(None, sp, single, q, 5)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
        rec = tracing.span_recorder()
        # the timed-dispatch wrapper's span + the three phase spans
        (disp,) = rec.spans(trace_id=tid,
                            name="comms.dispatch.dist_ivf_flat")
        assert disp.duration > 0
        assert disp.attrs["modeled_bytes"] > 0
        assert rec.spans(trace_id=tid, name="serving.mesh.merge")
        assert tracing.get_counter(
            "comms.dispatch.dist_ivf_flat.calls") >= 1.0
        # untraced calls record nothing new (opt-in contract)
        n0 = len(rec.spans())
        dist_ivf.search(None, sp, dist, q, 5)
        assert len(rec.spans()) == n0

    def test_bq_direct_search_trace_id(self, comms, data):
        x, q = data
        from raft_tpu.neighbors.ivf_bq import (
            IvfBqIndexParams,
            IvfBqSearchParams,
        )

        tracing.reset_spans()
        dist = dist_bq.build_bq(None, comms,
                                IvfBqIndexParams(n_lists=16), x)
        tid = tracing.new_trace_id()
        dist_bq.search_bq(None, IvfBqSearchParams(n_probes=8), dist,
                          q, 5, trace_id=tid)
        rec = tracing.span_recorder()
        assert rec.spans(trace_id=tid,
                         name="comms.dispatch.dist_ivf_bq")
        assert rec.spans(trace_id=tid, name="serving.mesh.merge")

    def test_collective_trace_counters_inventory(self, data, flat_pair):
        """The comms veneer's trace-time accounting: tracing a mesh
        program bumps per-family calls/bytes counters, and repeat
        dispatches of the compiled program add nothing."""
        _, q = data
        _, dist = flat_pair
        sp = IvfFlatSearchParams(n_probes=8, scan_engine="xla")
        ex = SearchExecutor()
        ex.warmup(dist, buckets=(16,), k=5, params=sp)
        calls0 = tracing.get_counter("comms.allgather.calls")
        assert calls0 >= 1.0            # the id gather traced at least once
        assert tracing.get_counter("comms.allgather.modeled_bytes") > 0
        ex.search(dist, q, 5, params=sp)
        ex.search(dist, q, 5, params=sp)
        # steady state: no re-traces, so the inventory is unchanged
        assert tracing.get_counter("comms.allgather.calls") == calls0


class TestShardedAnnStraggler:
    """The per-shard-dispatch path measures REAL per-shard readiness:
    a trace_id-carrying search feeds the straggler detector (opt-in —
    untraced steady traffic must not fill the span ring)."""

    def test_sharded_search_records_shard_timings(self):
        import jax

        from raft_tpu.distributed import sharded_ann
        from raft_tpu.neighbors import brute_force

        rng = np.random.default_rng(7)
        x = rng.standard_normal((512, 16)).astype(np.float32)
        q = rng.standard_normal((8, 16)).astype(np.float32)
        idx = sharded_ann.build_sharded(
            None,
            lambda res, part: brute_force.build(res, part),
            lambda res, ix, qs, k: brute_force.search(res, ix, qs, k),
            x, devices=jax.devices()[:4])
        tracing.reset_spans()
        tracing.reset_gauges("serving.mesh.")
        tid = tracing.new_trace_id()
        d, i = idx.search(None, q, 5, trace_id=tid)
        assert np.asarray(i).shape == (8, 5)
        rec = tracing.span_recorder()
        shards = rec.spans(trace_id=tid, name="serving.mesh.shard")
        assert len(shards) == 4
        assert tracing.get_gauge(tracing.MESH_SHARD_TIME_MAX) > 0
        assert 0 <= tracing.get_gauge(tracing.MESH_SLOWEST_SHARD) < 4
        # opt-in: an untraced search records NO shard spans — steady
        # traffic must not churn the bounded span ring
        n_before = len(rec.spans(name="serving.mesh.shard"))
        idx.search(None, q, 5)
        assert len(rec.spans(name="serving.mesh.shard")) == n_before


class TestMeshProbeAccounting:
    """graftgauge (PR 8): the sharded IVF families scatter-add their
    selected probes into a LIST-SHARDED donated counter plane — each
    shard counts only the probes it owns, so a probe lands exactly
    once mesh-wide and the gathered plane is the same global histogram
    the single-chip index would have recorded."""

    def test_mesh_bit_identity_and_exact_counts(self, data, flat_pair):
        _, q = data
        single, dist = flat_pair
        sp = IvfFlatSearchParams(n_probes=8, scan_engine="xla")
        ex = SearchExecutor(probe_accounting=True)
        d1, i1 = ex.search(dist, q, 5, params=sp)
        d0, i0 = dist_ivf.search(None, sp, dist, q, 5)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
        (plane,) = ex.probe_frequencies().values()
        assert plane.shape == (dist.n_lists,)
        assert plane.sum() == q.shape[0] * 8

    def test_mesh_histogram_in_own_list_id_space(self, data, flat_pair):
        """The gathered mesh plane must be the exact probe histogram
        in the DIST index's own list-id space (the deal permutes list
        ids, so that space — the one ``dist.list_sizes`` and the drift
        baseline live in — is the meaningful one): bin for bin equal
        to a host-side bincount of the coarse selection over the dist
        quantizer, and a permutation of nothing lost."""
        _, q = data
        _, dist = flat_pair
        import jax.numpy as jnp
        from raft_tpu.neighbors._batching import coarse_select

        ex = SearchExecutor(probe_accounting=True)
        ex.search(dist, q, 5,
                  params=IvfFlatSearchParams(n_probes=8,
                                             scan_engine="xla"))
        (p_mesh,) = ex.probe_frequencies().values()
        c = jnp.asarray(np.asarray(dist.centers))
        ip = jnp.asarray(q) @ c.T
        score = -(jnp.sum(jnp.square(c), axis=1)[None, :] - 2.0 * ip)
        probes = np.asarray(coarse_select(score, 8, "exact"))
        expected = np.bincount(probes.reshape(-1),
                               minlength=dist.n_lists)
        np.testing.assert_array_equal(expected, p_mesh)

    def test_mesh_zero_recompile_with_accounting(self, data, flat_pair):
        _, q = data
        _, dist = flat_pair
        tracing.install_xla_compile_listener()
        sp = IvfFlatSearchParams(n_probes=8, scan_engine="xla")
        ex = SearchExecutor(probe_accounting=True)
        for n in (16, 13, 9):
            ex.search(dist, q[:n], 5, params=sp)
        compiles0 = ex.stats.compile_count
        backend0 = tracing.get_counter(tracing.XLA_COMPILE_COUNT)
        for n in (16, 13, 9, 13, 16, 9):
            ex.search(dist, q[:n], 5, params=sp)
        assert ex.stats.compile_count == compiles0
        assert tracing.get_counter(tracing.XLA_COMPILE_COUNT) == backend0
        # pad rows masked on the mesh too: 6 + 3 dispatches of
        # (16+13+9)=38 rows x 8 probes each plane-wide
        (plane,) = ex.probe_frequencies().values()
        assert plane.sum() == 3 * (16 + 13 + 9) * 8

    def test_mesh_pq_and_bq_accounting(self, comms, data):
        x, q = data
        ex = SearchExecutor(probe_accounting=True)
        pq = dist_ivf.build_pq(
            None, comms, IvfPqIndexParams(n_lists=32, pq_dim=8), x)
        sp = IvfPqSearchParams(n_probes=8)
        d1, i1 = ex.search(pq, q, 5, params=sp)
        d0, i0 = dist_ivf.search_pq(None, sp, pq, q, 5)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
        bq = dist_bq.build_bq(
            None, comms, ivf_bq.IvfBqIndexParams(n_lists=32), x)
        bp = ivf_bq.IvfBqSearchParams(n_probes=8)
        d3, i3 = ex.search(bq, q, 5, params=bp)
        d2, i2 = dist_bq.search_bq(None, bp, bq, q, 5)
        np.testing.assert_array_equal(np.asarray(i2), np.asarray(i3))
        np.testing.assert_array_equal(np.asarray(d2), np.asarray(d3))
        planes = ex.probe_frequencies()
        assert len(planes) == 2
        for plane in planes.values():
            assert plane.sum() == q.shape[0] * 8


class TestBqFusedMesh:
    """RaBitQ IVF-BQ on the mesh (this PR's tentpole): the fused
    estimate-then-rerank engines run shard-locally, probe_mode=global
    stays bit-identical to the single-chip index per engine, and the
    variance-corrected merge hits the 0.99 recall bar at (or under)
    the budget the flat 2x over-fetch used to burn."""

    @pytest.fixture(scope="class")
    def bq_pair(self, comms, data):
        x, _ = data
        params = ivf_bq.IvfBqIndexParams(n_lists=32)
        return (ivf_bq.build(None, params, x),
                dist_bq.build_bq(None, comms, params, x))

    @pytest.mark.parametrize("engine", ["rank", "xla", "pallas", "auto"])
    def test_bq_bit_identical(self, data, bq_pair, engine):
        _, q = data
        single, dist = bq_pair
        sp = ivf_bq.IvfBqSearchParams(n_probes=8, scan_engine=engine)
        d0, i0 = ivf_bq.search(None, sp, single, q, 10)
        d1, i1 = dist_bq.search_bq(None, sp, dist, q, 10)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))

    def test_four_shard_recall_at_old_half_budget(self):
        """Acceptance: sharded recall >= 0.99 at <= the old 2x merge
        over-fetch budget on the 4-shard config. The fused engines
        exchange EXACT distances, so merge_k collapses to k — half
        the old 2x wire depth — and recall is limited only by the
        probe set."""
        import jax

        from raft_tpu.comms.bootstrap import make_mesh
        from raft_tpu.comms.comms import Comms
        from raft_tpu.distributed.bq import merge_overfetch
        from raft_tpu.neighbors import brute_force
        from raft_tpu.utils import eval_recall

        rng = np.random.default_rng(23)
        x = rng.standard_normal((4096, 32)).astype(np.float32)
        q = rng.standard_normal((64, 32)).astype(np.float32)
        comms4 = Comms(make_mesh(("data",),
                                 devices=jax.devices()[:4]), "data")
        dist = dist_bq.build_bq(
            None, comms4, ivf_bq.IvfBqIndexParams(n_lists=64), x)
        merge_k = merge_overfetch(dist, 10)
        assert merge_k <= 20, merge_k          # old budget was 2x k
        sp = ivf_bq.IvfBqSearchParams(n_probes=48)
        _, i = dist_bq.search_bq(None, sp, dist, q, 10)
        _, gt = brute_force.knn(None, x, q, 10)
        r, _, _ = eval_recall(np.asarray(gt), np.asarray(i))
        assert r >= 0.99, r

    def test_estimate_only_variance_corrected_merge(self, comms, data):
        """A codes-only mesh index over-fetches the merge by the
        MEASURED per-shard estimator variance (not a flat 2x): the
        derived depth is recorded per shard at build, and the merged
        estimate candidates rescue the exact top-k through refine."""
        from raft_tpu.distributed.bq import merge_overfetch
        from raft_tpu.neighbors import brute_force
        from raft_tpu.neighbors.refine import refine
        from raft_tpu.utils import eval_recall

        x, q = data
        dist = dist_bq.build_bq(
            None, comms, ivf_bq.IvfBqIndexParams(
                n_lists=32, store_vectors=False), x)
        assert len(dist.shard_rel_err) == N_DEV
        assert all(v > 0 for v in dist.shard_rel_err)
        merge_k = merge_overfetch(dist, 10)
        assert 10 < merge_k <= 240    # bound-derived, not hand-tuned
        # exhaustive probes isolate the merge budget: recall measures
        # the candidate depth, not the probe set
        sp = ivf_bq.IvfBqSearchParams(n_probes=32)
        _, gt = brute_force.knn(None, x, q, 10)

        def recall_at(depth):
            _, cand = dist_bq.search_bq(None, sp, dist, q, depth)
            assert np.asarray(cand).shape[1] == depth
            _, i = refine(None, x, q, cand, 10)
            r, _, _ = eval_recall(np.asarray(gt), np.asarray(i))
            return float(r)

        r_derived = recall_at(merge_k)
        r_flat2x = recall_at(20)
        # the measured-variance depth beats the flat 2x it replaced by
        # a wide margin on the estimator's hardest case (1-bit codes,
        # unclustered gaussians — residual ≈ the whole vector)
        assert r_derived >= r_flat2x + 0.2, (r_derived, r_flat2x)
        assert r_derived >= 0.7, r_derived

    @pytest.mark.parametrize("engine", ["xla", "pallas"])
    @pytest.mark.parametrize("q_rows", [3, 11, 16])
    def test_executor_bucketing_invariance(self, data, bq_pair, engine,
                                           q_rows):
        _, q = data
        _, dist = bq_pair
        sp = ivf_bq.IvfBqSearchParams(n_probes=8, scan_engine=engine)
        ex = SearchExecutor()
        d0, i0 = dist_bq.search_bq(None, sp, dist, q[:q_rows], 5)
        d1, i1 = ex.search(dist, q[:q_rows], 5, params=sp)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))

    def test_executor_engine_in_cache_key_zero_recompile(self, data,
                                                         bq_pair):
        """Engine switch = distinct executable; steady state on one
        engine = zero recompiles (the new engine static is in the AOT
        cache key)."""
        _, q = data
        _, dist = bq_pair
        tracing.install_xla_compile_listener()
        ex = SearchExecutor()
        sp_x = ivf_bq.IvfBqSearchParams(n_probes=8, scan_engine="xla")
        for n in (16, 13, 9):
            ex.search(dist, q[:n], 5, params=sp_x)
        c0 = ex.stats.compile_count
        assert c0 == 1
        backend0 = tracing.get_counter(tracing.XLA_COMPILE_COUNT)
        for n in (16, 13, 9, 13):
            ex.search(dist, q[:n], 5, params=sp_x)
        assert ex.stats.compile_count == c0
        assert tracing.get_counter(tracing.XLA_COMPILE_COUNT) == backend0
        sp_p = ivf_bq.IvfBqSearchParams(n_probes=8,
                                        scan_engine="pallas")
        ex.search(dist, q, 5, params=sp_p)
        assert ex.stats.compile_count == c0 + 1


class TestMeshRagged:
    """graftragged: the list-sharded families serve through the SAME
    ragged plan family — one replicated-tile executable per (mesh,
    params class) replaces the distributed bucket ladder. Bit-identity
    per request vs the bucketed mesh dispatch, zero-recompile mixed
    load, probe accounting exact, and the mesh-specific residue
    (query_axis grids) falls back with an explicit reason; the int8
    probe wire rides ragged since graftwire's block-independent
    scales."""

    @pytest.fixture(scope="class")
    def mesh_indexes(self, comms, data):
        x, _ = data
        return {
            "flat": dist_ivf.build(
                None, comms, IvfFlatIndexParams(n_lists=32), x),
            "pq": dist_ivf.build_pq(
                None, comms,
                IvfPqIndexParams(n_lists=32, pq_dim=8), x),
            "bq": dist_bq.build_bq(
                None, comms,
                ivf_bq.IvfBqIndexParams(n_lists=32, bits=2), x),
        }

    def _blocks(self, seed=3):
        rng = np.random.default_rng(seed)
        return [rng.standard_normal((m, 32)).astype(np.float32)
                for m in (3, 5, 2, 9)]

    # three combos cover both axes (engine × probe mode) without the
    # fourth's near-duplicate compile cost — tier-1 wall-time budget
    @pytest.mark.parametrize("probe_mode,engine", [
        ("global", "pallas"), ("global", "xla"), ("local", "xla")])
    def test_flat_bit_identical_per_engine(self, mesh_indexes, engine,
                                           probe_mode):
        index = mesh_indexes["flat"]
        ex = SearchExecutor(ragged_tile=16)
        p1 = IvfFlatSearchParams(n_probes=5, scan_engine=engine)
        p2 = IvfFlatSearchParams(n_probes=8, scan_engine=engine)
        assert (ex.ragged_key(index, 4, params=p1,
                              probe_mode=probe_mode)
                == ex.ragged_key(index, 7, params=p2,
                                 probe_mode=probe_mode))
        blocks = self._blocks()
        res = ex.search_ragged(index, blocks, [4, 7, 6, 5],
                               params_list=[p1, p2, p1, p2],
                               probe_mode=probe_mode)
        for b, (d, i), kj, pj in zip(blocks, res, [4, 7, 6, 5],
                                     [p1, p2, p1, p2]):
            sd, si = ex.search(index, b, kj, params=pj,
                               probe_mode=probe_mode)
            np.testing.assert_array_equal(i, np.asarray(si))
            np.testing.assert_array_equal(d, np.asarray(sd))
        assert ex.ragged_executables("dist_ivf_flat") == 1

    @pytest.mark.parametrize("fam", ["pq", "bq"])
    def test_pq_bq_bit_identical(self, mesh_indexes, fam):
        index = mesh_indexes[fam]
        mk = (IvfPqSearchParams if fam == "pq"
              else ivf_bq.IvfBqSearchParams)
        p1 = mk(n_probes=5, scan_engine="xla")
        p2 = mk(n_probes=8, scan_engine="xla")
        ex = SearchExecutor(ragged_tile=16)
        blocks = self._blocks(seed=5)
        res = ex.search_ragged(index, blocks, [4, 7, 6, 5],
                               params_list=[p1, p2, p1, p2])
        for b, (d, i), kj, pj in zip(blocks, res, [4, 7, 6, 5],
                                     [p1, p2, p1, p2]):
            sd, si = ex.search(index, b, kj, params=pj)
            np.testing.assert_array_equal(i, np.asarray(si))
            np.testing.assert_array_equal(d, np.asarray(sd))
        assert ex.ragged_executables("dist_ivf_" + fam) == 1

    def test_zero_recompile_mixed_load(self, mesh_indexes):
        """Warm the one executable, then mixed per-request n_probes/k
        load — with probe accounting ON — serves with ZERO backend
        compiles (after the one-time lazily-created probe plane)."""
        index = mesh_indexes["flat"]
        ex = SearchExecutor(ragged_tile=16, probe_accounting=True)
        p1 = IvfFlatSearchParams(n_probes=5, scan_engine="xla")
        p2 = IvfFlatSearchParams(n_probes=8, scan_engine="xla")
        ex.warmup_ragged(index, k=7, params=p1)
        blocks = self._blocks(seed=7)
        # primer dispatch creates the donated probe plane (one jnp
        # broadcast compile, same one-time cost as the bucketed path)
        ex.search_ragged(index, blocks[:1], 4, params_list=p1)
        tracing.install_xla_compile_listener()
        c0 = tracing.get_counter(tracing.XLA_COMPILE_COUNT)
        for _ in range(3):
            ex.search_ragged(index, blocks, [4, 7, 6, 5],
                             params_list=[p1, p2, p1, p2])
        assert (tracing.get_counter(tracing.XLA_COMPILE_COUNT)
                - c0 == 0)
        assert ex.ragged_executables() == 1
        # probe accounting: every dispatched row lands exactly its own
        # budget, counted once mesh-wide on the owning shard
        planes = ex.probe_frequencies()
        (label,) = planes.keys()
        assert label.startswith("dist_ivf_flat-")
        # primer: 3 rows at n_probes=5; then 3 rounds of the mixed
        # stream (rows x that request's OWN budget)
        rows_by_budget = 3 * 5 + 3 * (3 * 5 + 5 * 8 + 2 * 5 + 9 * 8)
        assert planes[label].sum() == rows_by_budget

    def test_mesh_residue_reasons(self, mesh_indexes):
        index = mesh_indexes["flat"]
        ex = SearchExecutor()
        p = IvfFlatSearchParams(n_probes=5, scan_engine="xla")
        # graftwire: the int8 probe wire went block-independent
        # (per-row affine scales over the FULL local coarse block), so
        # its ragged pin is retired — int8 is raggable now
        assert ex.ragged_key(index, 4, params=p,
                             probe_wire_dtype="int8") is not None
        assert ex.ragged_fallback_reason(
            index, 4, params=p, probe_wire_dtype="int8") is None
        assert ex.ragged_key(index, 4, params=p,
                             query_axis="q") is None
        assert "query_axis" in ex.ragged_fallback_reason(
            index, 4, params=p, query_axis="q")
        # bf16 wires stay raggable (per-element rounding keeps the
        # budget-prefix property)
        assert ex.ragged_key(index, 4, params=p, wire_dtype="bf16",
                             probe_wire_dtype="bf16") is not None

    def test_int8_probe_wire_bit_identical(self, mesh_indexes):
        """The retired pin's acceptance: an int8-probe-wire ragged
        dispatch is bit-identical to the solo bucketed search — the
        block-independent scales make codes independent of what else
        shares the tile (cap-vs-solo)."""
        index = mesh_indexes["flat"]
        ex = SearchExecutor(ragged_tile=16)
        p1 = IvfFlatSearchParams(n_probes=5, scan_engine="xla")
        p2 = IvfFlatSearchParams(n_probes=8, scan_engine="xla")
        blocks = self._blocks(seed=13)[:2]
        res = ex.search_ragged(index, blocks, 4, params_list=[p1, p2],
                               probe_wire_dtype="int8")
        for b, (d, i), pj in zip(blocks, res, [p1, p2]):
            sd, si = ex.search(index, b, 4, params=pj,
                               probe_wire_dtype="int8")
            np.testing.assert_array_equal(i, np.asarray(si))
            np.testing.assert_array_equal(d, np.asarray(sd))

    def test_bf16_wire_bit_identical(self, mesh_indexes):
        index = mesh_indexes["flat"]
        ex = SearchExecutor(ragged_tile=16)
        p1 = IvfFlatSearchParams(n_probes=5, scan_engine="xla")
        p2 = IvfFlatSearchParams(n_probes=8, scan_engine="xla")
        blocks = self._blocks(seed=9)[:2]
        res = ex.search_ragged(index, blocks, 4, params_list=[p1, p2],
                               wire_dtype="bf16",
                               probe_wire_dtype="bf16")
        for b, (d, i), pj in zip(blocks, res, [p1, p2]):
            sd, si = ex.search(index, b, 4, params=pj,
                               wire_dtype="bf16",
                               probe_wire_dtype="bf16")
            np.testing.assert_array_equal(i, np.asarray(si))
            np.testing.assert_array_equal(d, np.asarray(sd))

    def test_batcher_serves_mesh_ragged(self, mesh_indexes):
        """BatcherConfig(ragged=True) covers the mesh families in
        continuous admission: submissions group by the mesh ragged
        key and complete bit-identical to the executor path."""
        from raft_tpu.serving import BatcherConfig, DynamicBatcher

        index = mesh_indexes["flat"]
        ex = SearchExecutor(ragged_tile=16)
        p = IvfFlatSearchParams(n_probes=5, scan_engine="xla")
        blocks = self._blocks(seed=13)
        with DynamicBatcher(ex, BatcherConfig(max_wait_s=0.002,
                                              ragged=True)) as b:
            hs = [b.submit(index, blk, 5, params=p,
                           probe_mode="global") for blk in blocks]
            for h, blk in zip(hs, blocks):
                got = h.result(timeout=120)
                want = ex.search(index, blk, 5, params=p,
                                 probe_mode="global")
                np.testing.assert_array_equal(
                    np.asarray(got[1]), np.asarray(want[1]))
        assert ex.ragged_executables("dist_ivf_flat") == 1
