"""grafttier (PR 14): tiered hot/cold IVF storage.

The serving contracts under test:

- **Bit-identity**: with tiering enabled (half the lists cold), search
  results are bit-identical to the all-HBM index per engine — direct
  and through the executor, before and after placement swaps, with
  shared and per-row filters, for L2/sqrt-L2/IP.
- **Zero-recompile across epochs**: placement only permutes which
  lists occupy the fixed hot slots (fixed-width drop-mode swaps), so
  steady-state serving runs zero backend compiles across ≥2
  promote/demote epochs.
- **Determinism**: the epoch function is pure (ties to the smaller
  list id), so scripted traffic under a ManualClock reproduces the
  exact same swap sequence run-to-run.
- **Probe-plane exactness**: graftgauge's accounting stays exact with
  tiering on (the plane threads the tiered plan like any IVF plan).
"""

import json
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.core import memwatch, tracing
from raft_tpu.core.executor import SearchExecutor
from raft_tpu.distance.types import DistanceType
from raft_tpu.neighbors import ivf_flat, tiered
from raft_tpu.core.bitset import Bitset
from raft_tpu.neighbors.tiered import TieredSearchParams, build_tiered
from raft_tpu.ops.tier_scan import (
    resolve_tier_engine,
    tier_fetch_plan,
    tiered_list_major_scan,
)
from raft_tpu.serving.harness import ManualClock
from raft_tpu.serving.placement import (
    PlacementConfig,
    TierManager,
    plan_epoch,
)

ENGINES = ("xla", "pallas")


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(21)
    x = rng.standard_normal((4096, 32)).astype(np.float32)
    q = rng.standard_normal((24, 32)).astype(np.float32)
    return x, q


@pytest.fixture(scope="module")
def flat_index(data):
    x, _ = data
    return ivf_flat.build(
        None, ivf_flat.IvfFlatIndexParams(n_lists=32,
                                          kmeans_n_iters=6), x)


@pytest.fixture()
def tiered_index(flat_index):
    # fresh split per test (the container is mutable — placement
    # tests would otherwise leak layout into each other)
    return build_tiered(flat_index, hot_fraction=0.5)


@pytest.fixture(autouse=True)
def clean_gate():
    yield
    memwatch.remove_gate()


def _search_pair(flat_index, t, q, k=10, engine="xla", n_probes=8,
                 flt=None, metric_params=None):
    pf = ivf_flat.IvfFlatSearchParams(n_probes=n_probes,
                                      scan_engine=engine)
    pt = TieredSearchParams(n_probes=n_probes, scan_engine=engine)
    d0, i0 = ivf_flat.search(None, pf, flat_index, q, k,
                             sample_filter=flt)
    d1, i1 = tiered.search(None, pt, t, q, k, sample_filter=flt)
    return (np.asarray(d0), np.asarray(i0),
            np.asarray(d1), np.asarray(i1))


class TestBitIdentity:
    """Tiered results ≡ all-HBM results, bit for bit, per engine."""

    @pytest.mark.parametrize("engine", ENGINES)
    def test_half_cold_bit_identical(self, data, flat_index,
                                     tiered_index, engine):
        _, q = data
        assert tiered_index.n_cold >= tiered_index.n_lists // 2
        d0, i0, d1, i1 = _search_pair(flat_index, tiered_index, q,
                                      engine=engine)
        assert (d0 == d1).all() and (i0 == i1).all()

    @pytest.mark.parametrize("engine", ENGINES)
    def test_bit_identical_after_swaps(self, data, flat_index,
                                       tiered_index, engine):
        _, q = data
        promo = [int(x) for x in tiered_index.cold_lists[:3]]
        demo = [int(x) for x in tiered_index.hot_lists[:3]]
        moved = tiered.apply_plan(tiered_index, promo, demo, width=8)
        assert moved == 2 * 3 * tiered_index.block_bytes
        d0, i0, d1, i1 = _search_pair(flat_index, tiered_index, q,
                                      engine=engine)
        assert (d0 == d1).all() and (i0 == i1).all()

    @pytest.mark.parametrize("engine", ENGINES)
    def test_shared_filter_through_cold_blocks(self, data, flat_index,
                                               tiered_index, engine):
        """A 1-D shared bitset that knocks out rows living in COLD
        lists must filter identically — the id-fold rides the
        resident id plane, so the cold tier needs no filter
        plumbing of its own."""
        x, q = data
        # forbid every odd id — guaranteed to hit rows in both tiers
        mask = np.ones(x.shape[0], bool)
        mask[1::2] = False
        bs = Bitset.from_mask(mask)
        d0, i0, d1, i1 = _search_pair(flat_index, tiered_index, q,
                                      engine=engine, flt=bs)
        assert (d0 == d1).all() and (i0 == i1).all()
        assert (i1[i1 >= 0] % 2 == 0).all()

    def test_per_row_filter_through_cold_blocks(self, data, flat_index,
                                                tiered_index):
        """2-D per-query filters degrade pallas→xla (same contract as
        ivf_scan) and stay bit-identical through cold blocks."""
        x, q = data
        rng = np.random.default_rng(3)
        words = x.shape[0] // 32 + 1
        fw = jnp.asarray(
            rng.integers(0, 2**31, size=(q.shape[0], words),
                         dtype=np.int32).astype(np.uint32))
        assert resolve_tier_engine(
            "pallas", hot_data=tiered_index.hot_data,
            filter_words=fw, k=10) == "xla"
        d0, i0, d1, i1 = _search_pair(flat_index, tiered_index, q,
                                      flt=fw)
        assert (d0 == d1).all() and (i0 == i1).all()

    @pytest.mark.parametrize("engine", ENGINES)
    def test_inner_product_and_sqrt_metrics(self, data, engine):
        x, q = data
        for metric in (DistanceType.InnerProduct,
                       DistanceType.L2SqrtExpanded):
            idx = ivf_flat.build(
                None, ivf_flat.IvfFlatIndexParams(
                    n_lists=16, kmeans_n_iters=4, metric=metric), x)
            t = build_tiered(idx, hot_fraction=0.5)
            d0, i0, d1, i1 = _search_pair(idx, t, q, engine=engine,
                                          n_probes=6)
            assert (d0 == d1).all() and (i0 == i1).all()

    def test_interpret_mode_kernel_reference(self, data, flat_index,
                                             tiered_index):
        """The R6 interpret-coverage reference: the tiered Pallas
        kernel itself, driven directly with interpret=True, matches
        the XLA twin bit-for-bit (the ops-guard contract every
        pallas_call in ops/ must keep)."""
        x, q = data
        t = tiered_index
        qf = jnp.asarray(q)
        ip = qf @ np.asarray(t.centers).T
        score = -(np.asarray(t.center_norms)[None, :] - 2.0 * ip)
        probes = jnp.asarray(
            np.argsort(-np.asarray(score), axis=1)[:, :8]
            .astype(np.int32))
        outs = {}
        for eng in ENGINES:
            outs[eng] = tiered_list_major_scan(
                qf, t.hot_data, t.cold_data, t.hot_slot_map,
                t.cold_slot_map, t.data_norms, t.indices, probes,
                k=10, metric=t.metric, engine=eng, interpret=True)
        assert (np.asarray(outs["pallas"][0])
                == np.asarray(outs["xla"][0])).all()
        assert (np.asarray(outs["pallas"][1])
                == np.asarray(outs["xla"][1])).all()


class TestFetchPlan:
    """tier_fetch_plan: the per-step dual-tier fetch descriptor."""

    def test_hot_hold_and_cold_sequence(self):
        # lists: 0 hot(slot 0), 1 cold(slot 0), 2 hot(slot 1),
        # 3 cold(slot 1), 4 cold(slot 2)
        hot_map = jnp.asarray([0, -1, 1, -1, -1], jnp.int32)
        cold_map = jnp.asarray([-1, 0, -1, 1, 2], jnp.int32)
        uniq = jnp.asarray([0, 1, 2, 3, 4, 5], jnp.int32)  # 5 = sentinel
        hf, cf, cs = tier_fetch_plan(uniq, hot_map, cold_map, 5)
        # hot fetch holds across cold + sentinel steps
        assert list(np.asarray(hf)) == [0, 0, 1, 1, 1, 1]
        assert list(np.asarray(cf)) == [-1, 0, -1, 1, 2, -1]
        # exclusive cold count -> alternating buffer slots 0,1,0
        assert list(np.asarray(cs)) == [0, 0, 1, 1, 2, 3]

    def test_leading_cold_clamps_to_slot_zero(self):
        hot_map = jnp.asarray([-1, 0], jnp.int32)
        cold_map = jnp.asarray([0, -1], jnp.int32)
        hf, cf, _ = tier_fetch_plan(
            jnp.asarray([0, 1], jnp.int32), hot_map, cold_map, 2)
        assert list(np.asarray(hf)) == [0, 0]
        assert list(np.asarray(cf)) == [0, -1]


class TestResolveEngine:
    def test_auto_is_xla_off_tpu(self, tiered_index):
        assert resolve_tier_engine(
            "auto", hot_data=tiered_index.hot_data, k=10) == "xla"

    def test_big_k_degrades(self, tiered_index):
        assert resolve_tier_engine(
            "pallas", hot_data=tiered_index.hot_data, k=256) == "xla"

    def test_non_f32_degrades(self, tiered_index):
        bf = tiered_index.hot_data.astype(jnp.bfloat16)
        assert resolve_tier_engine("pallas", hot_data=bf,
                                   k=10) == "xla"

    def test_bad_engine_rejected(self):
        with pytest.raises(Exception, match="tiered scan_engine"):
            resolve_tier_engine("rank")


class TestHotSizing:
    """resolve_hot_slots: the graftledger byte half of placement."""

    def test_ledger_headroom_sizes_the_hot_tier(self, flat_index):
        block = (flat_index.max_list_size * flat_index.dim * 4)
        # capacity for exactly 5 blocks after the 10% safety reserve
        ledger = memwatch.MemoryLedger(
            capacity_bytes=block * 5 / 0.9 + 1)
        h = tiered.resolve_hot_slots(flat_index, ledger=ledger)
        assert h == 5
        t = build_tiered(flat_index, ledger=ledger)
        assert t.n_hot == 5 and t.n_cold == flat_index.n_lists - 5

    def test_unknown_headroom_falls_back_to_fraction(self, flat_index):
        ledger = memwatch.MemoryLedger()   # no stats, no capacity
        h = tiered.resolve_hot_slots(flat_index, ledger=ledger,
                                     hot_fraction=0.25)
        assert h == flat_index.n_lists // 4

    def test_clamped_to_a_real_split(self, flat_index):
        assert tiered.resolve_hot_slots(flat_index,
                                        hot_slots=10**9) \
            == flat_index.n_lists - 1
        assert tiered.resolve_hot_slots(flat_index, hot_slots=0) == 1

    def test_probe_counts_seed_the_initial_placement(self, flat_index):
        counts = np.zeros((flat_index.n_lists,), np.int64)
        hot_lids = [3, 7, 11, 20]
        counts[hot_lids] = [40, 30, 20, 10]
        t = build_tiered(flat_index, hot_slots=4, probe_counts=counts)
        assert sorted(t.hot_lists.tolist()) == hot_lids


class TestPlanEpoch:
    """The pure epoch function: deterministic, hysteretic, bounded."""

    def test_promotes_hot_cold_pairs(self):
        counts = np.asarray([0, 100, 5, 50, 2, 0])
        plan = plan_epoch(counts, hot_lists=[0, 2], cold_lists=[1, 3, 4, 5],
                          max_swaps=8, min_heat_ratio=1.5)
        # cold 1 (100) beats hot 0 (0); cold 3 (50) beats hot 2 (5)
        assert plan.promotions == (1, 3)
        assert plan.demotions == (0, 2)
        assert plan.window_total == 157
        assert plan.hot_window_fraction == pytest.approx(5 / 157)

    def test_hysteresis_blocks_border_swaps(self):
        counts = np.asarray([10, 14, 0, 0])
        plan = plan_epoch(counts, hot_lists=[0], cold_lists=[1, 2, 3],
                          min_heat_ratio=1.5)
        assert plan.promotions == ()        # 14 < 1.5 * 10
        plan = plan_epoch(counts, hot_lists=[0], cold_lists=[1, 2, 3],
                          min_heat_ratio=1.2)
        assert plan.promotions == (1,) and plan.demotions == (0,)

    def test_zero_traffic_cold_never_promotes(self):
        plan = plan_epoch(np.zeros(4, np.int64), hot_lists=[0, 1],
                          cold_lists=[2, 3])
        assert plan.promotions == ()

    def test_max_swaps_bounds_the_plan(self):
        counts = np.asarray([0, 0, 0, 9, 9, 9])
        plan = plan_epoch(counts, hot_lists=[0, 1, 2],
                          cold_lists=[3, 4, 5], max_swaps=2)
        assert len(plan.promotions) == 2

    def test_ties_break_to_smaller_lid(self):
        counts = np.asarray([0, 0, 7, 7])
        plan = plan_epoch(counts, hot_lists=[0, 1], cold_lists=[2, 3],
                          max_swaps=1)
        assert plan.promotions == (2,) and plan.demotions == (0,)

    def test_pure_function_determinism(self):
        rng = np.random.default_rng(5)
        counts = rng.integers(0, 100, size=32)
        hot, cold = list(range(16)), list(range(16, 32))
        a = plan_epoch(counts, hot, cold)
        b = plan_epoch(counts.copy(), list(hot), list(cold))
        assert a == b


class TestApplyPlan:
    def test_layout_mirrors_and_maps_agree(self, tiered_index):
        t = tiered_index
        promo = [int(t.cold_lists[1])]
        demo = [int(t.hot_lists[2])]
        tiered.apply_plan(t, promo, demo, width=4)
        assert promo[0] in t.hot_lists and demo[0] in t.cold_lists
        hot_map = np.asarray(t.hot_slot_map)
        cold_map = np.asarray(t.cold_slot_map)
        # every list in exactly one tier; maps mirror the host truth
        assert ((hot_map >= 0) ^ (cold_map >= 0)).all()
        for slot, lid in enumerate(t.hot_lists):
            assert hot_map[lid] == slot
        for slot, lid in enumerate(t.cold_lists):
            assert cold_map[lid] == slot

    def test_rejects_wrong_tier_pairs(self, tiered_index):
        t = tiered_index
        with pytest.raises(Exception, match="currently-cold"):
            tiered.apply_plan(t, [int(t.hot_lists[0])],
                              [int(t.hot_lists[1])], width=4)
        with pytest.raises(Exception, match="currently-hot"):
            tiered.apply_plan(t, [int(t.cold_lists[0])],
                              [int(t.cold_lists[1])], width=4)

    def test_empty_plan_is_a_noop(self, tiered_index):
        before = tiered_index.hot_lists.copy()
        assert tiered.apply_plan(tiered_index, [], [], width=4) == 0
        assert (tiered_index.hot_lists == before).all()


class TestServingEpochs:
    """The executor contract: zero backend compiles across epochs,
    probe-plane exactness, deterministic ManualClock placement."""

    def _targeted_queries(self, flat_index, lid, rows=16, seed=7):
        rng = np.random.default_rng(seed)
        c = np.asarray(flat_index.centers)[lid]
        return (np.tile(c, (rows, 1))
                + 0.01 * rng.standard_normal((rows, c.size))
                ).astype(np.float32)

    def test_zero_recompile_across_epochs(self, data, flat_index):
        _, q = data
        t = build_tiered(flat_index, hot_fraction=0.5)
        p = TieredSearchParams(n_probes=8)
        ex = SearchExecutor(probe_accounting=True)
        ex.warmup(t, buckets=(32,), k=10, params=p)
        clock = ManualClock()
        mgr = TierManager(t, ex, clock=clock, config=PlacementConfig(
            epoch_every_s=10.0, max_swaps_per_epoch=4))
        qh = self._targeted_queries(flat_index, int(t.cold_lists[0]))
        d_ref, i_ref = ex.search(t, qh, 10, params=p)
        d_ref, i_ref = np.asarray(d_ref), np.asarray(i_ref)
        # warm everything the epoch path compiles (the fixed-width
        # swap programs specialize once), then demand silence
        mgr.epoch()
        ex.search(t, qh, 10, params=p)
        tracing.install_xla_compile_listener()
        c0 = tracing.counters().get(tracing.XLA_COMPILE_COUNT, 0)
        for _ in range(2):
            ex.search(t, qh, 10, params=p)
            plan = mgr.epoch()
            d2, i2 = ex.search(t, qh, 10, params=p)
        c1 = tracing.counters().get(tracing.XLA_COMPILE_COUNT, 0)
        assert c1 - c0 == 0, "re-placement must not recompile"
        # and the results stayed bit-identical through re-placement
        assert (np.asarray(d2) == d_ref).all()
        assert (np.asarray(i2) == i_ref).all()
        del plan

    def test_epoch_promotes_hot_traffic(self, flat_index):
        t = build_tiered(flat_index, hot_fraction=0.5)
        p = TieredSearchParams(n_probes=4)
        ex = SearchExecutor(probe_accounting=True)
        mgr = TierManager(t, ex, clock=ManualClock())
        lid = int(t.cold_lists[0])
        qh = self._targeted_queries(flat_index, lid)
        for _ in range(3):
            ex.search(t, qh, 10, params=p)
        plan = mgr.epoch()
        assert lid in plan.promotions
        assert lid in t.hot_lists

    def test_epoch_determinism_under_manual_clock(self, flat_index):
        """Two identical runs — same traffic script, same clock
        script — produce the exact same swap sequence."""
        def run():
            t = build_tiered(flat_index, hot_fraction=0.5)
            p = TieredSearchParams(n_probes=4)
            ex = SearchExecutor(probe_accounting=True)
            clock = ManualClock()
            mgr = TierManager(t, ex, clock=clock, config=PlacementConfig(
                epoch_every_s=5.0, max_swaps_per_epoch=2))
            plans = []
            for step, lid_pos in enumerate((0, 3, 5)):
                lid = int(build_tiered(flat_index,
                                       hot_fraction=0.5)
                          .cold_lists[lid_pos])
                qh = self._targeted_queries(flat_index, lid,
                                            seed=step)
                for _ in range(2):
                    ex.search(t, qh, 10, params=p)
                plans.append(mgr.epoch())
            return [(pl.promotions, pl.demotions) for pl in plans]

        assert run() == run()

    def test_tick_pacing(self, flat_index):
        t = build_tiered(flat_index, hot_fraction=0.5)
        ex = SearchExecutor(probe_accounting=True)
        clock = ManualClock()
        mgr = TierManager(t, ex, clock=clock, config=PlacementConfig(
            epoch_every_s=10.0))
        assert mgr.tick() is None          # first tick stamps only
        clock.advance(9.0)
        assert mgr.tick() is None          # not due yet
        clock.advance(2.0)
        assert mgr.tick() is not None      # due
        # elapsed multiples never stack into more than one epoch
        clock.advance(100.0)
        assert mgr.tick() is not None
        assert mgr.tick() is None

    def test_probe_plane_exact_with_tiering_on(self, data, flat_index):
        _, q = data
        t = build_tiered(flat_index, hot_fraction=0.5)
        p = TieredSearchParams(n_probes=8)
        ex = SearchExecutor(probe_accounting=True)
        n_dispatch = 3
        for _ in range(n_dispatch):
            ex.search(t, q, 10, params=p)
        planes = ex.probe_frequencies()
        label = ex.probe_label(t)
        assert label is not None and label.startswith("tiered_ivf-")
        total = int(planes[label].sum())
        assert total == n_dispatch * q.shape[0] * 8
        # and the plane matches the all-HBM index's own accounting
        # (same coarse selection -> identical histograms)
        ex2 = SearchExecutor(probe_accounting=True)
        for _ in range(n_dispatch):
            ex2.search(flat_index, q, 10,
                       params=ivf_flat.IvfFlatSearchParams(n_probes=8))
        ref = ex2.probe_frequencies()[ex2.probe_label(flat_index)]
        assert (planes[label] == ref).all()

    def test_executor_bit_identity_both_engines(self, data, flat_index):
        _, q = data
        t = build_tiered(flat_index, hot_fraction=0.5)
        ex = SearchExecutor()
        for eng in ENGINES:
            p = TieredSearchParams(n_probes=8, scan_engine=eng)
            d1, i1 = ex.search(t, q, 10, params=p)
            d0, i0 = ivf_flat.search(
                None, ivf_flat.IvfFlatSearchParams(n_probes=8,
                                                   scan_engine=eng),
                flat_index, q, 10)
            assert (np.asarray(d0) == np.asarray(d1)).all()
            assert (np.asarray(i0) == np.asarray(i1)).all()
        # the resolved engine keys distinct executables
        fams = [key for key in ex._cache if key[0] == "tiered_ivf"]
        assert len(fams) == 2

    def test_manager_requires_probe_accounting(self, tiered_index):
        with pytest.raises(Exception, match="probe-accounting"):
            TierManager(tiered_index, SearchExecutor(),
                        clock=ManualClock())


class TestTierSurface:
    """/tier.json + gauges + host-tier memory accounting."""

    def test_tier_json_and_gauges(self, data, flat_index):
        from raft_tpu.serving import MetricsExporter

        _, q = data
        t = build_tiered(flat_index, hot_fraction=0.5)
        p = TieredSearchParams(n_probes=8)
        ex = SearchExecutor(probe_accounting=True)
        clock = ManualClock()
        mgr = TierManager(t, ex, clock=clock, config=PlacementConfig(
            epoch_every_s=5.0))
        for _ in range(2):
            ex.search(t, q, 10, params=p)
        exp = MetricsExporter(executor=ex, tier=mgr)
        port = exp.start()
        try:
            body = urllib.request.urlopen(
                exp.url("/tier.json")).read()
            snap = json.loads(body)
            assert snap["layout"]["n_hot"] == t.n_hot
            assert snap["layout"]["n_cold"] == t.n_cold
            assert snap["layout"]["host_resident"] is t.host_resident
            assert snap["epochs"] == 0
            # two scrapes with the clock advanced drive one epoch
            urllib.request.urlopen(exp.url("/metrics")).read()
            clock.advance(6.0)
            urllib.request.urlopen(exp.url("/metrics")).read()
            snap = json.loads(urllib.request.urlopen(
                exp.url("/tier.json")).read())
            assert snap["epochs"] == 1
            assert snap["last_plan"] is not None
            g = tracing.gauges()
            assert g["tier.hot_lists"] == float(t.n_hot)
            assert g["tier.hot_bytes"] == float(t.hot_bytes)
            assert g["tier.cold_bytes"] == float(t.cold_bytes)
            assert "tier.hot_window_fraction" in g
            text = urllib.request.urlopen(
                exp.url("/metrics")).read().decode()
            assert "tier_hot_bytes" in text
        finally:
            exp.close()

    def test_tier_json_404_unattached(self):
        from raft_tpu.serving import MetricsExporter

        exp = MetricsExporter()
        port = exp.start()
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(exp.url("/tier.json"))
            assert e.value.code == 404
        finally:
            exp.close()
        del port

    def test_memwatch_models_the_tiers(self, tiered_index):
        """The resident model accounts the hot plane as device bytes;
        on CPU the cold plane honestly stays device (host and device
        are one pool — host_resident is False), while the numpy
        layout mirrors count host."""
        m = memwatch.index_memory_model(tiered_index)
        comps = m["components"]
        assert comps["hot_data"]["tier"] == "device"
        assert comps["hot_data"]["bytes"] == tiered_index.hot_bytes
        assert comps["cold_data"]["bytes"] == tiered_index.cold_bytes
        if tiered_index.host_resident:
            assert comps["cold_data"]["tier"] == "host"
            assert m["host_resident_bytes"] >= tiered_index.cold_bytes
        else:
            assert comps["cold_data"]["tier"] == "device"
        assert comps["hot_lists"]["tier"] == "host"

    def test_host_put_fallback_is_honest(self):
        arr, resident = tiered.host_put(np.zeros((4, 4), np.float32))
        if jax.default_backend() == "cpu":
            assert resident is False
        assert arr.shape == (4, 4)


class TestMultiTileKernel:
    """The Pallas kernel's cold-DMA discipline across QUERY TILES:
    cbuf/semaphore state persists across grid steps, and each tile's
    j==0 warm-up must re-fetch its own first cold block — force a
    small q_tile via the VMEM budget so several tiles actually run,
    and demand bit-parity with the XLA twin."""

    def test_multi_tile_bit_parity(self, flat_index, tiered_index):
        from raft_tpu.ops.tier_scan import (
            _tier_scan_pallas,
            _tier_scan_xla,
            _tier_vmem_plan,
        )

        t = tiered_index
        rng = np.random.default_rng(9)
        q = rng.standard_normal((192, t.dim)).astype(np.float32)
        qf = jnp.asarray(q)
        ip = qf @ np.asarray(t.centers).T
        score = -(np.asarray(t.center_norms)[None, :] - 2.0 * ip)
        probes = jnp.asarray(
            np.argsort(-np.asarray(score), axis=1)[:, :8]
            .astype(np.int32))
        # size the budget so the tile is a fraction of the batch —
        # the SAME arithmetic the kernel uses, so the tile count
        # assertion below can't silently degrade to one tile
        m_pad = -(-t.max_list_size // 8) * 8
        d_pad = -(-t.dim // 128) * 128
        fixed, per_q = _tier_vmem_plan(m_pad, d_pad, 10)
        vmem_mb = int(fixed + 96 * per_q) >> 20
        budget = (vmem_mb << 20) - fixed
        q_tile = min(max(8, (budget // per_q) // 8 * 8), 192)
        assert 192 // q_tile >= 2, "budget did not force multiple tiles"
        pd, pi = _tier_scan_pallas(
            qf, t.hot_data, t.cold_data, t.hot_slot_map,
            t.cold_slot_map, t.data_norms, t.indices, probes, None,
            k=10, metric=t.metric, interpret=True, vmem_mb=vmem_mb)
        xd, xi = _tier_scan_xla(
            qf, t.hot_data, t.cold_data, t.hot_slot_map,
            t.cold_slot_map, t.data_norms, t.indices, probes, None,
            k=10, metric=t.metric)
        assert (np.asarray(pd) == np.asarray(xd)).all()
        assert (np.asarray(pi) == np.asarray(xi)).all()


class TestLivePlacementRace:
    """The donation race the verify drive surfaced: an epoch swap
    donates the old hot plane while a concurrent search thread holds
    the pre-swap generation — the executor must absorb it with one
    rebuild-and-retry (jax spells the deleted-buffer error as
    RuntimeError OR ValueError), never surface it to the caller."""

    def test_concurrent_epochs_and_searches(self, data, flat_index):
        import threading

        _, q = data
        t = build_tiered(flat_index, hot_fraction=0.5)
        p = TieredSearchParams(n_probes=8)
        ex = SearchExecutor(probe_accounting=True)
        ex.warmup(t, buckets=(32,), k=10, params=p)
        d_ref, i_ref = np.asarray(ivf_flat.search(
            None, ivf_flat.IvfFlatSearchParams(n_probes=8),
            flat_index, q, 10)[0]), None
        errors = []
        stop = threading.Event()

        def pump():
            while not stop.is_set():
                try:
                    d, i = ex.search(t, q, 10, params=p)
                    np.asarray(d)
                except Exception as e:  # noqa: BLE001 — the assertion
                    errors.append(e)
                    return

        th = threading.Thread(target=pump, daemon=True)
        th.start()
        try:
            for step in range(20):
                promo = [int(t.cold_lists[step % t.n_cold])]
                demo = [int(t.hot_lists[step % t.n_hot])]
                tiered.apply_plan(t, promo, demo, width=4,
                                  executor=ex)
        finally:
            stop.set()
            th.join(timeout=30)
        assert not errors, errors[:1]
        d2, i2 = ex.search(t, q, 10, params=p)
        assert (np.asarray(d2) == d_ref).all()
        del i_ref, i2


class TestRaggedTiered:
    """graftcast retires the tiered ragged refusal (PR 15 pinned it;
    this is the flip that pin documented): the tiered plan key
    carries only shapes + statics — the placement generation never
    enters it — so an epoch swap can't invalidate the one packed
    ragged executable, and tiered serving rides the same packed-tile
    path as every other IVF family, bit-identical to its bucketed
    dispatch."""

    def test_fallback_pin_retired(self, tiered_index):
        ex = SearchExecutor()
        p = TieredSearchParams(n_probes=8)
        assert ex.ragged_key(tiered_index, 5, params=p) is not None
        assert ex.ragged_fallback_reason(tiered_index, 5,
                                         params=p) is None

    def test_ragged_batcher_serves_tiered(self, data, tiered_index):
        from raft_tpu.serving import BatcherConfig, DynamicBatcher

        _, q = data
        ex = SearchExecutor()
        p = TieredSearchParams(n_probes=8)
        want_d, want_i = ex.search(tiered_index, q[:7], 5, params=p)
        with DynamicBatcher(ex, BatcherConfig(max_wait_s=0.002,
                                              ragged=True)) as b:
            h = b.submit(tiered_index, q[:7], 5, params=p)
            got_d, got_i = h.result(timeout=120)
        np.testing.assert_array_equal(np.asarray(got_i),
                                      np.asarray(want_i))
        np.testing.assert_array_equal(np.asarray(got_d),
                                      np.asarray(want_d))
        assert ex.ragged_executables() == 1

    def test_ragged_stable_across_epochs(self, data, flat_index):
        """The generation-stable packing contract: ONE ragged
        executable serves across a placement swap, and its results
        track the (bit-identical) bucketed path on both sides."""
        _, q = data
        t = build_tiered(flat_index, hot_fraction=0.5)
        ex = SearchExecutor(probe_accounting=True)
        p = TieredSearchParams(n_probes=8)
        for _ in range(2):
            want_d, want_i = ex.search(t, q[:7], 5, params=p)
            (got_d, got_i), = ex.search_ragged(t, [q[:7]], 5,
                                               params_list=p)
            np.testing.assert_array_equal(np.asarray(got_i),
                                          np.asarray(want_i))
            np.testing.assert_array_equal(np.asarray(got_d),
                                          np.asarray(want_d))
            tiered.apply_plan(t, [int(t.cold_lists[0])],
                              [int(t.hot_lists[0])], width=4,
                              executor=ex)
        assert ex.ragged_executables() == 1


# ---------------------------------------------------------------------------
# graftcast (PR 18): tiered PQ/BQ planes
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def pq_index(data):
    from raft_tpu.neighbors import ivf_pq

    x, _ = data
    return ivf_pq.build(None, ivf_pq.IvfPqIndexParams(
        n_lists=32, pq_dim=8, kmeans_n_iters=6), x)


@pytest.fixture(scope="module")
def bq_index(data):
    from raft_tpu.neighbors import ivf_bq

    x, _ = data
    return ivf_bq.build(None, ivf_bq.IvfBqIndexParams(
        n_lists=32, kmeans_n_iters=6), x)


class TestTieredCompressed:
    """Tiered PQ codes plane and BQ record planes: bit-identical to
    the all-HBM index with half the lists cold — direct, through the
    executor, through the ragged tile, and across placement swaps
    (the ONE shared scan body guarantees it by construction; these
    pin that the tier steering doesn't perturb it)."""

    def _pq_pair(self, pq_index, t, q, k=10, n_probes=8):
        from raft_tpu.neighbors import ivf_pq

        p = ivf_pq.IvfPqSearchParams(n_probes=n_probes,
                                     scan_engine="xla")
        d0, i0 = ivf_pq.search(None, p, pq_index, q, k)
        d1, i1 = tiered.search_pq(None, p, t, q, k)
        return (np.asarray(d0), np.asarray(i0),
                np.asarray(d1), np.asarray(i1))

    def _bq_pair(self, bq_index, t, q, k=10, n_probes=8):
        from raft_tpu.neighbors import ivf_bq

        p = ivf_bq.IvfBqSearchParams(n_probes=n_probes,
                                     scan_engine="xla")
        d0, i0 = ivf_bq.search(None, p, bq_index, q, k)
        d1, i1 = tiered.search_bq(None, p, t, q, k)
        return (np.asarray(d0), np.asarray(i0),
                np.asarray(d1), np.asarray(i1))

    def test_pq_half_cold_bit_identical(self, data, pq_index):
        _, q = data
        t = tiered.build_tiered_pq(pq_index, hot_fraction=0.5)
        d0, i0, d1, i1 = self._pq_pair(pq_index, t, q)
        np.testing.assert_array_equal(i0, i1)
        np.testing.assert_array_equal(d0, d1)

    def test_bq_half_cold_bit_identical(self, data, bq_index):
        _, q = data
        t = tiered.build_tiered_bq(bq_index, hot_fraction=0.5)
        d0, i0, d1, i1 = self._bq_pair(bq_index, t, q)
        np.testing.assert_array_equal(i0, i1)
        np.testing.assert_array_equal(d0, d1)

    def test_pq_packed_half_cold_bit_identical(self, data):
        from raft_tpu.neighbors import ivf_pq

        x, q = data
        idx = ivf_pq.build(None, ivf_pq.IvfPqIndexParams(
            n_lists=16, pq_dim=16, pq_bits=4, kmeans_n_iters=4), x)
        t = tiered.build_tiered_pq(idx, hot_fraction=0.5)
        d0, i0, d1, i1 = self._pq_pair(idx, t, q)
        np.testing.assert_array_equal(i0, i1)
        np.testing.assert_array_equal(d0, d1)

    def test_bit_identical_after_swaps(self, data, pq_index, bq_index):
        _, q = data
        tpq = tiered.build_tiered_pq(pq_index, hot_fraction=0.5)
        tbq = tiered.build_tiered_bq(bq_index, hot_fraction=0.5)
        for t in (tpq, tbq):
            promo = [int(t.cold_lists[0]), int(t.cold_lists[1])]
            demo = [int(t.hot_lists[0]), int(t.hot_lists[1])]
            tiered.apply_plan(t, promo, demo, width=4)
            assert t.generation == 1
        d0, i0, d1, i1 = self._pq_pair(pq_index, tpq, q)
        np.testing.assert_array_equal(i0, i1)
        np.testing.assert_array_equal(d0, d1)
        d0, i0, d1, i1 = self._bq_pair(bq_index, tbq, q)
        np.testing.assert_array_equal(i0, i1)
        np.testing.assert_array_equal(d0, d1)

    def test_executor_and_ragged_paths(self, data, pq_index, bq_index):
        from raft_tpu.neighbors import ivf_bq, ivf_pq

        _, q = data
        tpq = tiered.build_tiered_pq(pq_index, hot_fraction=0.5)
        tbq = tiered.build_tiered_bq(bq_index, hot_fraction=0.5)
        ex = SearchExecutor()
        for t, p in ((tpq, ivf_pq.IvfPqSearchParams(n_probes=8)),
                     (tbq, ivf_bq.IvfBqSearchParams(n_probes=8))):
            assert ex.ragged_key(t, 5, params=p) is not None
            assert ex.ragged_fallback_reason(t, 5, params=p) is None
            want_d, want_i = ex.search(t, q[:7], 5, params=p)
            (got_d, got_i), = ex.search_ragged(t, [q[:7]], 5,
                                               params_list=p)
            np.testing.assert_array_equal(np.asarray(got_i),
                                          np.asarray(want_i))
            np.testing.assert_array_equal(np.asarray(got_d),
                                          np.asarray(want_d))

    def test_rank_engine_rejected_for_tiered_pq(self, pq_index):
        from raft_tpu.ops.tier_scan import resolve_tier_pq_engine

        with pytest.raises(Exception):
            resolve_tier_pq_engine("rank")

    def test_block_bytes_prices_all_planes(self, pq_index, bq_index):
        tpq = tiered.build_tiered_pq(pq_index, hot_fraction=0.5)
        tbq = tiered.build_tiered_bq(bq_index, hot_fraction=0.5)
        assert tpq.block_bytes == (
            int(np.prod(tpq.hot_codes.shape[1:]))
            * tpq.hot_codes.dtype.itemsize)
        per_plane = sum(
            int(np.prod(getattr(tbq, h).shape[1:]))
            * getattr(tbq, h).dtype.itemsize
            for h, _ in type(tbq)._PLANE_PAIRS)
        assert tbq.block_bytes == per_plane


# ---------------------------------------------------------------------------
# graftcast (PR 18): forecast-driven prefetch
# ---------------------------------------------------------------------------


class TestPrefetch:
    """The predictive tiering loop: forecast = the epoch policy over
    (rolling window + EWMA prior); staged promotions hit at the
    epoch; stale stages are refused by the generation check; the miss
    cache respects the ledger's capacity gate and shrinking
    headroom."""

    def _manager(self, flat_index, clock, capacity=1 << 30,
                 lead=10.0, **pf_kw):
        from raft_tpu.serving.prefetch import PrefetchConfig

        t = build_tiered(flat_index, hot_fraction=0.5)
        ex = SearchExecutor(probe_accounting=True)
        ledger = memwatch.MemoryLedger(executor=ex,
                                       capacity_bytes=capacity)
        mgr = TierManager(t, ex, config=PlacementConfig(
            epoch_every_s=60.0, max_swaps_per_epoch=4,
            prefetch_lead_s=lead), clock=clock)
        pf = mgr.enable_prefetch(
            config=PrefetchConfig(alpha=0.5, **pf_kw), ledger=ledger)
        return t, ex, mgr, pf, ledger

    def _drive(self, ex, t, mgr, clock, q, ticks, flat_index=None):
        p = TieredSearchParams(n_probes=4)
        for _ in range(ticks):
            d, i = ex.search(t, q, 10, params=p)
            if flat_index is not None:
                d2, i2 = ex.search(
                    flat_index, q, 10,
                    params=ivf_flat.IvfFlatSearchParams(n_probes=4))
                np.testing.assert_array_equal(np.asarray(i),
                                              np.asarray(i2))
                np.testing.assert_array_equal(np.asarray(d),
                                              np.asarray(d2))
            clock.advance(11.0)
            mgr.tick()

    @staticmethod
    def _near(flat_index, lids, n=64, seed=7):
        rng = np.random.default_rng(seed)
        centers = np.asarray(jax.device_get(flat_index.centers))
        qs = centers[np.asarray(lids)[rng.integers(0, len(lids), n)]]
        qs = qs + 0.01 * rng.standard_normal(qs.shape)
        return qs.astype(np.float32)

    def test_forecast_is_the_epoch_policy(self):
        from raft_tpu.serving.prefetch import forecast_plan

        window = np.array([0, 50, 1, 40, 2, 3], np.int64)
        hot = np.array([0, 1, 2])
        cold = np.array([3, 4, 5])
        want = plan_epoch(window, hot, cold, max_swaps=2)
        got = forecast_plan(np.zeros(6), hot, cold, max_swaps=2,
                            window=window)
        assert got.promotions == want.promotions
        assert got.demotions == want.demotions

    def test_prefetch_hits_and_zero_recompile(self, flat_index):
        """Drifting hot set under a ManualClock: the lead-time stage
        hits at the epoch, cold bytes leave the epoch path, and —
        after one warm drift cycle — further epochs with the
        prefetcher on add ZERO backend compiles (bit-identity to the
        flat index asserted on every dispatch)."""
        clock = ManualClock()
        t, ex, mgr, pf, _ = self._manager(flat_index, clock)
        assert pf.enabled
        hot0 = [int(lid) for lid in t.hot_lists[:8]]
        cold0 = [int(lid) for lid in t.cold_lists[:8]]
        tracing.install_xla_compile_listener()
        # warm: settle on hot0, then one full drift cycle compiles
        # the stage/mix executables exactly once
        self._drive(ex, t, mgr, clock, self._near(flat_index, hot0),
                    12, flat_index)
        self._drive(ex, t, mgr, clock, self._near(flat_index, cold0),
                    14, flat_index)
        base = dict(tracing.counters())
        n0 = base.get(tracing.XLA_COMPILE_COUNT, 0)
        # measured: drift BACK — prefetch stages ahead, zero compiles
        self._drive(ex, t, mgr, clock, self._near(flat_index, hot0),
                    14, flat_index)
        c = tracing.counters()
        assert c.get(tracing.XLA_COMPILE_COUNT, 0) - n0 == 0
        assert c.get("tier.prefetch.issued", 0) > base.get(
            "tier.prefetch.issued", 0)
        assert c.get("tier.prefetch.hits", 0) > base.get(
            "tier.prefetch.hits", 0)
        # a hit's bytes moved at stage time: the epoch path charged
        # fewer cold bytes than its promotions would cost reactively
        promoted = (c.get("tier.promotions", 0)
                    - base.get("tier.promotions", 0))
        cold_bytes = (c.get("tier.promote_cold_bytes", 0)
                      - base.get("tier.promote_cold_bytes", 0))
        assert cold_bytes < promoted * t.block_bytes

    def test_stale_promotion_cancelled(self, flat_index):
        """A prefetch that lands after the placement moved under it
        (the list was promoted/demoted by a racing epoch) is refused
        by the generation check and counted cancelled — never mixed
        into a swap."""
        clock = ManualClock()
        t, ex, mgr, pf, _ = self._manager(flat_index, clock)
        lid = int(t.cold_lists[0])
        window = np.zeros((t.n_lists,), np.int64)
        window[lid] = 100
        assert pf.prefetch(max_swaps=4, window=window) == 1
        gen0 = t.generation
        # racing epoch: promote lid reactively, then demote it again
        tiered.apply_plan(t, [lid], [int(t.hot_lists[0])], width=4)
        tiered.apply_plan(t, [int(t.cold_lists[0])], [lid], width=4)
        assert t.generation == gen0 + 2
        base = dict(tracing.counters())
        staged = pf.take([lid], t.generation)
        assert staged is None
        c = tracing.counters()
        assert (c.get("tier.prefetch.cancelled", 0)
                == base.get("tier.prefetch.cancelled", 0) + 1)
        assert (c.get("tier.prefetch.hits", 0)
                == base.get("tier.prefetch.hits", 0))

    def test_epoch_mid_prefetch_generation_wins(self, data,
                                                flat_index):
        """Epoch fires between stage and take: the stale row is
        cancelled, the epoch streams reactively, and serving stays
        bit-identical to the flat index across the whole exchange."""
        _, q = data
        clock = ManualClock()
        t, ex, mgr, pf, _ = self._manager(flat_index, clock)
        p = TieredSearchParams(n_probes=8)
        lid = int(t.cold_lists[0])
        window = np.zeros((t.n_lists,), np.int64)
        window[lid] = 100
        assert pf.prefetch(max_swaps=4, window=window) == 1
        # the mid-prefetch epoch (another list's traffic wins)
        tiered.apply_plan(t, [int(t.cold_lists[1])],
                          [int(t.hot_lists[0])], width=4, executor=ex)
        staged = pf.take([lid], t.generation)
        assert staged is None                 # stale: refused
        tiered.apply_plan(t, [int(t.cold_lists[0])],
                          [int(t.hot_lists[1])], width=4, executor=ex)
        d1, i1 = ex.search(t, q, 10, params=p)
        d0, i0 = ivf_flat.search(
            None, ivf_flat.IvfFlatSearchParams(n_probes=8),
            flat_index, q, 10)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d0))

    def test_miss_cache_evicts_under_shrinking_headroom(self,
                                                        flat_index):
        clock = ManualClock()
        t, ex, mgr, pf, ledger = self._manager(flat_index, clock)
        window = np.zeros((t.n_lists,), np.int64)
        lids = [int(lid) for lid in t.cold_lists[:3]]
        window[lids] = (300, 200, 100)
        assert pf.prefetch(max_swaps=4, window=window) == 3
        assert pf.snapshot()["staged"] == 3
        assert ledger.reserved_bytes() == 3 * t.block_bytes
        # headroom collapses: everything but one block's worth goes
        ledger.capacity_bytes = (
            ledger.forecast()["peak_bytes"] + ledger.reserved_bytes()
            - 1.5 * t.block_bytes)
        before = tracing.counters().get("tier.prefetch.cancelled", 0)
        evicted = pf.maintain()
        assert evicted >= 2
        assert pf.snapshot()["staged"] == 3 - evicted
        assert tracing.counters().get("tier.prefetch.cancelled",
                                      0) == before + evicted
        assert ledger.reserved_bytes() == (
            (3 - evicted) * t.block_bytes)

    def test_capacity_exceeded_degrades_to_reactive(self, data,
                                                    flat_index):
        """The gate refusing a stage never surfaces: the prefetcher
        cancels, the epoch promotes reactively, searches succeed."""
        _, q = data
        clock = ManualClock()
        t, ex, mgr, pf, ledger = self._manager(flat_index, clock)
        # collapse headroom BEFORE any stage: every reserve refuses
        ledger.capacity_bytes = ledger.forecast()["peak_bytes"] + 1.0
        window = np.zeros((t.n_lists,), np.int64)
        window[int(t.cold_lists[0])] = 100
        before = tracing.counters().get("tier.prefetch.cancelled", 0)
        assert pf.prefetch(max_swaps=4, window=window) == 0
        assert tracing.counters().get("tier.prefetch.cancelled",
                                      0) == before + 1
        # serving and the reactive epoch are untouched
        p = TieredSearchParams(n_probes=8)
        d, i = ex.search(t, q, 10, params=p)
        plan = mgr.epoch()
        assert plan is not None
        d, i = ex.search(t, q, 10, params=p)
        assert np.asarray(d).shape == (q.shape[0], 10)

    def test_window_claimed_once_per_epoch(self, flat_index):
        """The satellite-6 lock fix: one epoch claims the probe
        window EXACTLY once, and the same single claim feeds both the
        plan and the prefetcher's EWMA — a racing scrape can't
        double-fold (the DriftDetector.update locking model)."""
        import threading

        clock = ManualClock()
        t, ex, mgr, pf, _ = self._manager(flat_index, clock)
        calls = []
        orig = ex.probe_frequencies

        def counting():
            calls.append(threading.get_ident())
            return orig()

        ex.probe_frequencies = counting
        p = TieredSearchParams(n_probes=4)
        ex.search(t, self._near(flat_index, [0, 1, 2]), 10, params=p)
        mgr.tick()                            # baseline stamp: no claim
        base_calls = len(calls)
        clock.advance(61.0)
        n_threads = 4
        barrier = threading.Barrier(n_threads)
        plans = []

        def racer():
            barrier.wait()
            plans.append(mgr.tick())

        threads = [threading.Thread(target=racer)
                   for _ in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        ran = [pl for pl in plans if pl is not None]
        assert len(ran) == 1                  # one epoch, n racers
        # exactly one ledger claim for that epoch (the lead-time
        # peek is read-only and did not run here)
        assert len(calls) == base_calls + 1
        assert pf._epochs_observed == 1

    def test_disabled_prefetcher_is_reactive(self, flat_index):
        from raft_tpu.serving.prefetch import PrefetchConfig

        clock = ManualClock()
        t = build_tiered(flat_index, hot_fraction=0.5)
        ex = SearchExecutor(probe_accounting=True)
        mgr = TierManager(t, ex, clock=clock)
        pf = mgr.enable_prefetch(config=PrefetchConfig(capacity=0))
        assert not pf.enabled
        assert pf.prefetch(max_swaps=4) == 0
        assert pf.take([1], t.generation) is None
        assert mgr.epoch() is not None
