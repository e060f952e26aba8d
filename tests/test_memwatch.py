"""graftledger tests (PR 13) — the memory-truth plane.

The acceptance criteria this file carries: the resident-bytes model is
pinned BYTE-EXACT against at least one real index per family (flat /
PQ / BQ, the BQ one with AND without its rerank plane) and per shard
on the mesh; ``memory_stats()``-unsupported backends (CPU — the tier-1
environment) degrade honestly to ``supported: False`` instead of
invented numbers; the reservation forecast's arithmetic is pinned
against the executor's real donated-state/temp reservations; the
opt-in capacity gate refuses a build host-side with a typed
:class:`~raft_tpu.core.memwatch.CapacityExceeded` BEFORE any device
allocation; zero-recompile and bit-identity stay green with the
ledger fully enabled, single-chip AND mesh; and the exporter /
flight-recorder surfaces (``/memory.json``, ``/memory_profile``, the
low-headroom incident trigger) serve the same numbers.
"""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from raft_tpu.comms import local_comms
from raft_tpu.core import memwatch, tracing
from raft_tpu.core.executor import SearchExecutor
from raft_tpu.core.memwatch import CapacityExceeded, MemoryLedger
from raft_tpu.distributed import ivf as dist_ivf
from raft_tpu.neighbors import brute_force, ivf_bq, ivf_flat, ivf_pq
from raft_tpu.serving import metrics
from raft_tpu.serving.harness import ManualClock


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(7)
    x = rng.standard_normal((512, 32)).astype(np.float32)
    q = rng.standard_normal((16, 32)).astype(np.float32)
    return x, q


@pytest.fixture(scope="module")
def flat_index(data):
    x, _ = data
    return ivf_flat.build(None, ivf_flat.IvfFlatIndexParams(n_lists=8), x)


@pytest.fixture(autouse=True)
def _reset():
    metrics.reset()
    memwatch.remove_gate()
    yield
    memwatch.remove_gate()


def model_vs_nbytes(index):
    """The byte-exact pin: the model must equal the arrays' own
    ``nbytes``, component by component and in total."""
    import dataclasses

    model = memwatch.index_memory_model(index)
    total = 0
    for f in dataclasses.fields(index):
        v = getattr(index, f.name, None)
        if v is None or not hasattr(v, "nbytes"):
            continue
        assert model["components"][f.name]["bytes"] == v.nbytes, f.name
        total += v.nbytes
    assert model["resident_bytes"] == total
    return model


class TestResidentModel:
    """Byte-exact pins of the resident-bytes model, per family."""

    def test_flat_byte_exact(self, flat_index):
        model = model_vs_nbytes(flat_index)
        # single-chip: per-shard == global (nothing is sharded)
        assert model["shard_resident_bytes"] == model["resident_bytes"]
        assert set(model["components"]) == {
            "centers", "center_norms", "data", "data_norms",
            "indices", "list_sizes"}

    def test_pq_byte_exact(self, data):
        x, _ = data
        idx = ivf_pq.build(
            None, ivf_pq.IvfPqIndexParams(n_lists=8, pq_dim=8), x)
        model = model_vs_nbytes(idx)
        assert "codes" in model["components"]
        assert "codebooks" in model["components"]

    def test_bq_byte_exact_with_and_without_rerank_plane(self, data):
        x, _ = data
        with_plane = ivf_bq.build(
            None, ivf_bq.IvfBqIndexParams(n_lists=8), x)
        codes_only = ivf_bq.build(
            None, ivf_bq.IvfBqIndexParams(n_lists=8,
                                          store_vectors=False), x)
        m1 = model_vs_nbytes(with_plane)
        m0 = model_vs_nbytes(codes_only)
        # the rerank plane is exactly the raw-vector + norm planes:
        # the codes-only model must be smaller by exactly their bytes
        assert "data" in m1["components"]
        assert "data" not in m0["components"]
        plane = (m1["components"]["data"]["bytes"]
                 + m1["components"]["data_norms"]["bytes"])
        assert m1["resident_bytes"] - m0["resident_bytes"] == plane
        # the correction scalars and packed words are all modeled
        for comp in ("codes", "rnorm", "cfac", "errw"):
            assert comp in m0["components"]

    def test_brute_force_byte_exact(self, data):
        x, _ = data
        idx = brute_force.build(None, x)
        model = model_vs_nbytes(idx)
        assert model["components"]["dataset"]["bytes"] == x.nbytes

    def test_known_layout_pinned(self):
        """The model against hand-computed numbers for a fixed
        layout — a model change must move THIS pin, not only the
        nbytes identity."""
        x = np.zeros((256, 32), np.float32)
        idx = ivf_flat.build(
            None, ivf_flat.IvfFlatIndexParams(n_lists=4), x)
        n_lists, mls, d = 4, idx.max_list_size, 32
        expected = (
            n_lists * d * 4            # centers f32
            + n_lists * 4              # center_norms f32
            + n_lists * mls * d * 4    # data f32
            + n_lists * mls * 4        # data_norms f32
            + n_lists * mls * 4        # indices i32
            + n_lists * 4)             # list_sizes i32
        model = memwatch.index_memory_model(idx)
        assert model["resident_bytes"] == expected

    def test_mesh_per_shard(self, data):
        """Mesh-sharded index: global bytes match nbytes; per-shard
        bytes follow each array's own sharding (list-sharded planes
        are 1/R of global on the 8-virtual-device mesh)."""
        x, _ = data
        comms = local_comms()
        didx = dist_ivf.build(
            None, comms, ivf_flat.IvfFlatIndexParams(n_lists=32), x)
        model = model_vs_nbytes(didx)
        r = comms.size
        comp = model["components"]
        # list-sharded planes shard 1/R per device
        assert comp["data"]["shard_bytes"] == comp["data"]["bytes"] // r
        assert comp["indices"]["shard_bytes"] == \
            comp["indices"]["bytes"] // r
        assert model["shard_resident_bytes"] < model["resident_bytes"]
        # per-device map covers every mesh device, and sums to the
        # global total (each byte lands on exactly one device for
        # fully-sharded planes)
        assert set(model["per_device_bytes"]) == {
            int(d.id) for d in comms.mesh.devices.flat}


class TestLiveStatsFallback:
    """The memory_stats()-unsupported path — CPU is the tier-1
    backend, so this IS the honest-fallback proof."""

    def test_supported_false_on_cpu(self):
        stats = memwatch.device_memory_stats()
        assert stats["supported"] is False
        assert stats["devices"] == {}

    def test_snapshot_degrades_honestly(self, flat_index):
        ledger = MemoryLedger()
        ledger.watch("flat", flat_index)
        snap = ledger.snapshot()
        assert snap["supported"] is False
        assert snap["devices"] == {}
        # no live truth -> no divergence, no invented headroom
        assert snap["divergence_bytes"] is None
        assert snap["headroom_bytes"] is None
        # ... but the MODEL keeps working
        assert snap["resident_total_bytes"] > 0
        ledger.publish()
        assert tracing.get_gauge("memory.live.supported") == 0.0
        assert tracing.get_gauge("memory.hbm.headroom_bytes") == -1.0
        assert tracing.get_gauge(
            "memory.index.flat.resident_bytes") > 0

    def test_capacity_override_restores_headroom(self, flat_index):
        model = memwatch.index_memory_model(flat_index)
        cap = model["resident_bytes"] + 10_000
        ledger = MemoryLedger(capacity_bytes=cap)
        ledger.watch("flat", flat_index)
        room = ledger.headroom_bytes()
        assert room == pytest.approx(
            cap - ledger.forecast()["peak_bytes"])


class TestLiveArithmetic:
    """The supported-backend arithmetic (headroom, divergence,
    watermark), pinned with injected stats — CPU cannot produce them
    live, but the formulas must not wait for a TPU to be wrong."""

    def fake_stats(self):
        return {"supported": True, "devices": {
            0: {"in_use_bytes": 6e6, "peak_bytes": 7e6,
                "limit_bytes": 8e6},
            1: {"in_use_bytes": 5e6, "peak_bytes": 6e6,
                "limit_bytes": 8e6},
        }}

    def test_headroom_divergence_watermark(self, flat_index,
                                           monkeypatch):
        monkeypatch.setattr(memwatch, "device_memory_stats",
                            lambda devices=None: self.fake_stats())
        ledger = MemoryLedger()
        ledger.watch("flat", flat_index)
        # headroom = min over devices of limit - in_use (device 0)
        assert ledger.headroom_bytes() == 8e6 - 6e6
        snap = ledger.snapshot()
        assert snap["supported"] is True
        # divergence = total live in-use - modeled residency terms
        model = memwatch.index_memory_model(flat_index)
        assert snap["divergence_bytes"] == \
            (6e6 + 5e6) - model["resident_bytes"]
        # the dispatch watermark folds the in-use total
        ledger.sample_dispatch()
        assert ledger.snapshot()["watermark"]["in_use_peak_bytes"] \
            == 6e6 + 5e6
        ledger.publish()
        assert tracing.get_gauge("memory.live.supported") == 1.0
        assert tracing.get_gauge(
            "memory.device.0.in_use_bytes") == 6e6
        assert tracing.get_gauge("memory.hbm.headroom_bytes") == 2e6

    def test_live_limit_beats_configured_capacity(self, flat_index,
                                                  monkeypatch):
        monkeypatch.setattr(memwatch, "device_memory_stats",
                            lambda devices=None: self.fake_stats())
        # a configured capacity is the fallback, not an override:
        # measured truth wins when the backend provides it
        ledger = MemoryLedger(capacity_bytes=1.0)
        assert ledger.headroom_bytes() == 2e6

    def test_snapshot_reads_backend_once(self, flat_index,
                                         monkeypatch):
        """Review hardening: one snapshot = one backend stats read +
        one model walk — headroom/divergence derive from the same
        inputs instead of re-reading per field."""
        calls = {"n": 0}

        def counting(devices=None):
            calls["n"] += 1
            return self.fake_stats()

        monkeypatch.setattr(memwatch, "device_memory_stats", counting)
        ledger = MemoryLedger(capacity_bytes=1e9)
        ledger.watch("flat", flat_index)
        ledger.snapshot()
        assert calls["n"] == 1


class TestForecast:
    """The reservation forecast pinned against the executor's real
    reservations — byte-exact arithmetic, no tolerance."""

    def test_terms_pinned(self, data, flat_index):
        _, q = data
        ex = SearchExecutor(min_bucket=16, max_bucket=16)
        ledger = MemoryLedger(executor=ex)
        label = ledger.watch("flat", flat_index)
        assert label == "flat"
        ex.search(flat_index, q, 5,
                  ivf_flat.IvfFlatSearchParams(scan_engine="xla"))
        res = ex.memory_reservations()
        # ONE xla-engine entry at bucket 16, k 5; no probe plane
        assert set(res) == {"probe_plane_bytes", "max_temp_bytes",
                            "executables"}
        assert res["probe_plane_bytes"] == {}
        assert res["executables"] == 1
        costs = ex.executable_costs()
        max_temp = max(c.get("temp_bytes", 0.0) for c in costs.values())
        assert res["max_temp_bytes"] == max_temp
        fc = ledger.forecast()
        model = memwatch.index_memory_model(flat_index)
        assert fc["resident_bytes"] == model["resident_bytes"]
        assert fc["probe_plane_bytes"] == 0.0
        assert fc["max_temp_bytes"] == max_temp
        # single chip: everything lands on device 0, and the peak is
        # exactly the sum of the resident bytes and the max temp
        assert fc["peak_bytes"] == model["resident_bytes"] + max_temp

    def test_probe_plane_term(self, data, flat_index):
        _, q = data
        ex = SearchExecutor(min_bucket=16, max_bucket=16,
                            probe_accounting=True)
        ledger = MemoryLedger(executor=ex)
        ledger.watch("flat", flat_index)
        ex.search(flat_index, q, 5)
        fc = ledger.forecast()
        # one int32 plane of n_lists entries
        assert fc["probe_plane_bytes"] == flat_index.n_lists * 4

    def test_dead_index_drops_from_model(self, data):
        x, _ = data
        ledger = MemoryLedger()
        idx = ivf_flat.build(
            None, ivf_flat.IvfFlatIndexParams(n_lists=4), x)
        ledger.watch("tmp", idx)
        assert "tmp" in ledger.resident()
        del idx
        import gc

        gc.collect()
        assert "tmp" not in ledger.resident()


class TestCapacityGate:
    """fits() + the opt-in CapacityExceeded gate on build/extend."""

    def test_fits_unknown_is_distinguishable(self, flat_index):
        ledger = MemoryLedger()          # no live stats, no capacity
        verdict = ledger.fits(flat_index)
        assert verdict["fits"] is True and verdict["unknown"] is True
        assert verdict["headroom_bytes"] is None

    def test_fits_against_capacity(self, flat_index):
        model = memwatch.index_memory_model(flat_index)
        # room for one-and-a-half copies: the first fits, a second
        # copy next to it does not
        ledger = MemoryLedger(
            capacity_bytes=1.5 * model["resident_bytes"])
        assert ledger.fits(flat_index)["fits"] is True
        ledger.watch("flat", flat_index)
        assert ledger.fits(flat_index)["fits"] is False
        # safety_fraction tightens the verdict further: with the full
        # capacity free, reserving 60% refuses what 0% admits
        empty = MemoryLedger(
            capacity_bytes=1.5 * model["resident_bytes"])
        assert empty.fits(flat_index,
                          safety_fraction=0.6)["fits"] is False

    def test_gate_refuses_build_host_side(self, data, flat_index):
        x, _ = data
        ledger = MemoryLedger(capacity_bytes=1000)
        memwatch.install_gate(ledger)
        with pytest.raises(CapacityExceeded) as e:
            ivf_flat.build(
                None, ivf_flat.IvfFlatIndexParams(n_lists=8), x)
        assert e.value.required_bytes > 1000
        assert e.value.headroom_bytes == 1000
        assert "ivf_flat.extend" in str(e.value)
        assert tracing.get_counter("memory.gate.refused") >= 1
        # gate removed -> same build admits again
        memwatch.remove_gate()
        ivf_flat.build(None, ivf_flat.IvfFlatIndexParams(n_lists=8), x)

    def test_gate_covers_every_family(self, data):
        x, _ = data
        memwatch.install_gate(MemoryLedger(capacity_bytes=100))
        with pytest.raises(CapacityExceeded, match="ivf_pq.extend"):
            ivf_pq.build(
                None, ivf_pq.IvfPqIndexParams(n_lists=8, pq_dim=8), x)
        with pytest.raises(CapacityExceeded, match="ivf_bq.extend"):
            ivf_bq.build(None, ivf_bq.IvfBqIndexParams(n_lists=8), x)
        with pytest.raises(CapacityExceeded,
                           match="brute_force.build"):
            brute_force.build(None, x)

    def test_gate_covers_build_streaming(self, data, tmp_path):
        """Review hardening: the streaming builds allocate the full
        padded layout directly — the gate must see them too (the
        'corpus ≫ headroom' path is exactly what streaming serves)."""
        from raft_tpu.io import BinDataset, write_bin

        x, _ = data
        path = tmp_path / "d.fbin"
        write_bin(path, x)
        memwatch.install_gate(MemoryLedger(capacity_bytes=100))
        with BinDataset(path) as ds:
            with pytest.raises(CapacityExceeded,
                               match="ivf_flat.build_streaming"):
                ivf_flat.build_streaming(
                    None, ivf_flat.IvfFlatIndexParams(n_lists=8), ds,
                    chunk_rows=256)
            with pytest.raises(CapacityExceeded,
                               match="ivf_pq.build_streaming"):
                ivf_pq.build_streaming(
                    None, ivf_pq.IvfPqIndexParams(n_lists=8, pq_dim=8),
                    ds, chunk_rows=256)
            with pytest.raises(CapacityExceeded,
                               match="ivf_bq.build_streaming"):
                ivf_bq.build_streaming(
                    None, ivf_bq.IvfBqIndexParams(n_lists=8), ds,
                    chunk_rows=256)

    def test_gate_admits_within_capacity(self, data):
        x, _ = data
        memwatch.install_gate(MemoryLedger(capacity_bytes=10**9))
        admitted0 = tracing.get_counter("memory.gate.admitted")
        idx = ivf_flat.build(
            None, ivf_flat.IvfFlatIndexParams(n_lists=8), x)
        assert idx.size == x.shape[0]
        assert tracing.get_counter("memory.gate.admitted") > admitted0

    def test_extend_gated_on_growth(self, data):
        """An extend that must grow the padded extent re-allocates —
        the gate sees exactly that repack."""
        x, _ = data
        idx = ivf_flat.build(
            None, ivf_flat.IvfFlatIndexParams(n_lists=8), x)
        model = memwatch.index_memory_model(idx)
        # capacity admits the index once but not a grown repack
        memwatch.install_gate(
            MemoryLedger(capacity_bytes=model["resident_bytes"]))
        rng = np.random.default_rng(3)
        with pytest.raises(CapacityExceeded):
            ivf_flat.extend(
                None, idx,
                rng.standard_normal((512, 32)).astype(np.float32))


class TestLedgerOnIdentity:
    """The acceptance criterion: zero-recompile + bit-identity stay
    green with the memory ledger fully enabled (watermark sampling on
    every dispatch), single-chip and mesh."""

    def test_single_chip(self, data, flat_index):
        _, q = data
        tracing.install_xla_compile_listener()
        params = ivf_flat.IvfFlatSearchParams(n_probes=4)
        bare = SearchExecutor(min_bucket=16, max_bucket=16)
        d0, i0 = bare.search(flat_index, q, 5, params)
        ex = SearchExecutor(min_bucket=16, max_bucket=16)
        ledger = MemoryLedger(executor=ex)
        ledger.watch("flat", flat_index)
        samples0 = tracing.get_counter(memwatch.SAMPLES)
        d1, i1 = ex.search(flat_index, q, 5, params)
        # bit-identity vs the ledger-free executor
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
        # zero-recompile in steady state with sampling live
        backend0 = tracing.get_counter(tracing.XLA_COMPILE_COUNT)
        compiles0 = ex.stats.compile_count
        for _ in range(4):
            ex.search(flat_index, q, 5, params)
            ledger.publish()
        assert ex.stats.compile_count == compiles0
        assert tracing.get_counter(
            tracing.XLA_COMPILE_COUNT) == backend0
        # the heartbeat the CI snapshot floor checks: one sample per
        # dispatch, even on a backend without live stats
        assert tracing.get_counter(memwatch.SAMPLES) == samples0 + 5

    def test_mesh(self, data):
        x, q = data
        comms = local_comms()
        didx = dist_ivf.build(
            None, comms, ivf_flat.IvfFlatIndexParams(n_lists=32), x)
        tracing.install_xla_compile_listener()
        params = ivf_flat.IvfFlatSearchParams(n_probes=8)
        bare = SearchExecutor(min_bucket=16, max_bucket=16)
        d0, i0 = bare.search(didx, q, 5, params)
        ex = SearchExecutor(min_bucket=16, max_bucket=16)
        ledger = MemoryLedger(executor=ex)
        ledger.watch("dist-flat", didx)
        d1, i1 = ex.search(didx, q, 5, params)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
        backend0 = tracing.get_counter(tracing.XLA_COMPILE_COUNT)
        compiles0 = ex.stats.compile_count
        for _ in range(3):
            ex.search(didx, q, 5, params)
            ledger.publish()
        assert ex.stats.compile_count == compiles0
        assert tracing.get_counter(
            tracing.XLA_COMPILE_COUNT) == backend0
        # the mesh model reached the gauges per shard
        assert tracing.get_gauge(
            "memory.index.dist-flat.shard_bytes") < tracing.get_gauge(
            "memory.index.dist-flat.resident_bytes")


class TestExporterSurface:
    """/memory.json + /memory_profile + the labeled families."""

    def test_memory_json_and_labeled_families(self, data, flat_index):
        from raft_tpu.serving import MetricsExporter

        _, q = data
        ex = SearchExecutor(min_bucket=16, max_bucket=16)
        ledger = MemoryLedger(executor=ex)
        ledger.watch("flat", flat_index)
        ex.search(flat_index, q, 5)
        with MetricsExporter(executor=ex, memory=ledger) as exp:
            body = json.loads(urllib.request.urlopen(
                exp.url("/memory.json"), timeout=10).read())
            text = urllib.request.urlopen(
                exp.url("/metrics"), timeout=10).read().decode()
            snap = json.loads(urllib.request.urlopen(
                exp.url("/snapshot.json"), timeout=10).read())
        model = memwatch.index_memory_model(flat_index)
        assert body["supported"] is False
        assert body["indexes"]["flat"]["resident_bytes"] == \
            model["resident_bytes"]
        assert body["forecast"]["peak_bytes"] >= model["resident_bytes"]
        lines = text.splitlines()
        assert any(l.startswith(
            'memory_index_resident_bytes{index="flat"} ')
            for l in lines)
        assert "# TYPE memory_index_resident_bytes gauge" in lines
        # the federation block rides /snapshot.json
        assert snap["memory"]["resident"]["flat"] == \
            model["resident_bytes"]
        assert snap["memory"]["headroom_bytes"] is None

    def test_memory_json_404_without_ledger(self):
        from raft_tpu.serving import MetricsExporter

        with MetricsExporter() as exp:
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(exp.url("/memory.json"),
                                       timeout=10)
            assert e.value.code == 404

    def test_memory_profile_gated_and_armed(self, tmp_path):
        from raft_tpu.serving import MetricsExporter

        # unarmed: 403, same gate as /profile
        with MetricsExporter() as exp:
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(exp.url("/memory_profile"),
                                       timeout=10)
            assert e.value.code == 403
        # armed: the pprof bytes land under profile_dir and the
        # response names the file
        with MetricsExporter(profile_dir=str(tmp_path)) as exp:
            out = json.loads(urllib.request.urlopen(
                exp.url("/memory_profile"), timeout=10).read())
        assert out["bytes"] > 0
        import os

        assert os.path.exists(out["path"])
        assert out["path"].startswith(str(tmp_path))

    def test_memory_profile_shares_profile_lock(self, tmp_path):
        """One profiler customer at a time, both directions: a held
        profile lock 409s /memory_profile."""
        from raft_tpu.serving import MetricsExporter

        exp = MetricsExporter(profile_dir=str(tmp_path))
        assert exp._profile_lock.acquire(blocking=False)
        try:
            with pytest.raises(RuntimeError):
                exp.memory_profile()
        finally:
            exp._profile_lock.release()
        # released -> works
        assert exp.memory_profile()["bytes"] > 0

    def test_memory_profile_never_overwrites_across_restarts(
            self, tmp_path):
        """Review hardening: the capture sequence restarts with the
        process — a 'restarted' exporter must skip existing names,
        never overwrite the pre-crash evidence."""
        from raft_tpu.serving import MetricsExporter

        first = MetricsExporter(profile_dir=str(tmp_path))
        p1 = first.memory_profile()["path"]
        restarted = MetricsExporter(profile_dir=str(tmp_path))
        p2 = restarted.memory_profile()["path"]
        assert p1 != p2
        import os

        assert os.path.exists(p1) and os.path.exists(p2)


class TestLowHeadroomIncident:
    """The graftledger -> graftflight wiring: low headroom arms an
    incident bundle carrying the memory snapshot; ManualClock pins
    the cooldown."""

    def make_flight(self, ledger, clock, **cfg):
        from raft_tpu.serving.flight import FlightConfig, FlightRecorder

        config = FlightConfig(cooldown_s=60.0, latency=None,
                              low_headroom_bytes=10_000, **cfg)
        return FlightRecorder(config=config, clock=clock,
                              capture_fn=lambda: None, memory=ledger)

    def test_trigger_and_bundle(self, flat_index):
        model = memwatch.index_memory_model(flat_index)
        # capacity barely above residency -> headroom under threshold
        ledger = MemoryLedger(
            capacity_bytes=model["resident_bytes"] + 100)
        ledger.watch("flat", flat_index)
        assert ledger.headroom_bytes() <= 10_000
        clock = ManualClock()
        flight = self.make_flight(ledger, clock)
        bundle = flight.check(clock.now())
        assert bundle is not None
        assert bundle["triggers"] == ["low_headroom"]
        # the bundle carries the evidence: the full memory snapshot
        assert bundle["memory"]["headroom_bytes"] == \
            ledger.headroom_bytes()
        assert bundle["memory"]["indexes"]["flat"]["resident_bytes"] \
            == model["resident_bytes"]
        assert tracing.get_counter(
            "incident.trigger.low_headroom") == 1

    def test_cooldown_rate_limits(self, flat_index):
        model = memwatch.index_memory_model(flat_index)
        ledger = MemoryLedger(
            capacity_bytes=model["resident_bytes"] + 100)
        ledger.watch("flat", flat_index)
        clock = ManualClock()
        flight = self.make_flight(ledger, clock)
        assert flight.check(clock.now()) is not None
        clock.advance(1.0)
        assert flight.check(clock.now()) is None    # suppressed
        assert tracing.get_counter("incident.suppressed") == 1
        clock.advance(120.0)
        assert flight.check(clock.now()) is not None

    def test_unknown_headroom_never_fires(self, flat_index):
        # CPU, no capacity configured: headroom is None — ignorance
        # is not an incident
        ledger = MemoryLedger()
        ledger.watch("flat", flat_index)
        assert ledger.headroom_bytes() is None
        clock = ManualClock()
        flight = self.make_flight(ledger, clock)
        assert flight.check(clock.now()) is None


class TestHostTierModel:
    """PR 14 (grafttier): host-tier bytes join the resident model —
    components living OFF their device's default memory (or in plain
    numpy) fold into ``host_resident_bytes`` and OUT of the device
    totals the forecast/headroom/divergence arithmetic runs on."""

    class _StubSharding:
        def __init__(self, kind):
            self.memory_kind = kind
            self.device_set = ()

    class _StubArray:
        def __init__(self, shape, dtype, kind=None):
            self.shape = shape
            self.dtype = np.dtype(dtype)
            self.sharding = (TestHostTierModel._StubSharding(kind)
                            if kind else None)

    def test_memory_tier_classification(self):
        assert memwatch.memory_tier(np.zeros((2, 2))) == "host"
        assert memwatch.memory_tier(
            self._StubArray((2, 2), np.float32)) == "device"
        assert memwatch.memory_tier(
            self._StubArray((2, 2), np.float32,
                            kind="pinned_host")) == "host"

    def test_cpu_default_host_kind_counts_as_device(self):
        """The CPU backend's default memory IS unpinned_host — an
        ordinary CPU array must never be misclassified off-device
        (that would zero the whole resident model on tier-1)."""
        import jax.numpy as jnp

        a = jnp.zeros((4, 4), jnp.float32)
        assert memwatch.memory_tier(a) == "device"

    def test_host_bytes_split_pinned(self):
        """Byte-exact pin of the device/host split on a stub index
        with one pinned-host component."""
        import dataclasses as dc

        @dc.dataclass(frozen=True)
        class StubIndex:
            hot: object
            cold: object

        idx = StubIndex(
            hot=self._StubArray((8, 16, 4), np.float32),
            cold=self._StubArray((24, 16, 4), np.float32,
                                 kind="pinned_host"))
        m = memwatch.index_memory_model(idx)
        assert m["resident_bytes"] == 8 * 16 * 4 * 4
        assert m["host_resident_bytes"] == 24 * 16 * 4 * 4
        assert m["components"]["hot"]["tier"] == "device"
        assert m["components"]["cold"]["tier"] == "host"
        # the forecast's device peak excludes the host tier
        ledger = MemoryLedger()
        ledger.watch("stub", idx)
        fc = ledger.forecast()
        assert fc["resident_bytes"] == 8 * 16 * 4 * 4
        snap = ledger.snapshot()
        assert snap["host_resident_total_bytes"] == 24 * 16 * 4 * 4
        ledger.publish()
        g = tracing.gauges()
        assert g["memory.host.resident_bytes"] == 24 * 16 * 4 * 4
        assert g["memory.index.stub.host_bytes"] == 24 * 16 * 4 * 4
        # the federation block carries it too
        assert ledger.federation_payload()[
            "host_resident_total_bytes"] == 24 * 16 * 4 * 4


class TestDistributedDealGate:
    """PR 14 satellite (ROADMAP graftledger follow-on (b)): the mesh
    deal's per-shard staging is ADMITTED, not just modeled."""

    def test_dealt_shard_bytes_arithmetic(self):
        import jax
        import jax.numpy as jnp

        arrays = (
            jax.ShapeDtypeStruct((32, 64, 8), jnp.float32),
            jax.ShapeDtypeStruct((32, 64), jnp.int32),
            jax.ShapeDtypeStruct((32,), jnp.int32),
        )
        # 8 shards: 4 rows each of every tensor
        want = 4 * 64 * 8 * 4 + 4 * 64 * 4 + 4 * 4
        assert memwatch.dealt_shard_bytes(arrays, 8) == want
        # non-dividing row counts round up (the last shard's slack
        # must not make the model optimistic), None entries skip
        assert memwatch.dealt_shard_bytes(
            (jax.ShapeDtypeStruct((10, 2), jnp.float32), None), 4) \
            == 3 * 2 * 4

    def test_deal_admission_refuses_per_shard_model(self, data):
        """admit_deal is the distributed builds' gate seam: a
        capacity below the per-shard slot model refuses with the
        exact per-shard byte count, host-side."""
        x, _ = data
        idx = ivf_flat.build(
            None, ivf_flat.IvfFlatIndexParams(n_lists=8), x)
        arrays = (idx.centers, idx.data, idx.data_norms, idx.indices,
                  idx.list_sizes)
        per_shard = memwatch.dealt_shard_bytes(arrays, 8)
        memwatch.install_gate(MemoryLedger(
            capacity_bytes=per_shard - 1))
        with pytest.raises(CapacityExceeded,
                           match="ivf_flat.build.deal") as e:
            dist_ivf.admit_deal(arrays, 8,
                                "distributed.ivf_flat.build.deal")
        assert e.value.required_bytes == per_shard

    def test_distributed_build_is_gated_end_to_end(self, data):
        """The whole mesh build path refuses under a tiny gate —
        typed CapacityExceeded, never a backend OOM."""
        from raft_tpu.comms import local_comms

        x, _ = data
        memwatch.install_gate(MemoryLedger(capacity_bytes=100))
        with pytest.raises(CapacityExceeded):
            dist_ivf.build(None, local_comms(),
                           ivf_flat.IvfFlatIndexParams(n_lists=8), x)

    def test_deal_admissions_counted(self, data):
        """With ample capacity the mesh build's deal admission rides
        the same decision ledger as the single-chip gates."""
        from raft_tpu.comms import local_comms

        x, _ = data
        memwatch.install_gate(MemoryLedger(capacity_bytes=10**12))
        before = tracing.get_counter(memwatch.GATE_ADMITTED)
        dist_ivf.build(None, local_comms(),
                       ivf_flat.IvfFlatIndexParams(n_lists=8), x)
        after = tracing.get_counter(memwatch.GATE_ADMITTED)
        # at least the single-chip extend admit AND the deal admit
        assert after - before >= 2


def _encode_varint(v):
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _encode_field(fnum, wtype, payload):
    tag = _encode_varint((fnum << 3) | wtype)
    if wtype == 0:
        return tag + _encode_varint(payload)
    return tag + _encode_varint(len(payload)) + payload


def _make_pprof(samples, strings):
    """Minimal pprof Profile: sample_type (objects, bytes), samples
    with labels, a string table. ``samples`` is a list of
    (label_pairs, objects, bytes) with label_pairs as (key_idx,
    str_idx) tuples."""
    body = bytearray()
    # sample_type: {type, unit} — unit indices point at "objects"/"bytes"
    for unit_idx in (strings.index("objects"), strings.index("bytes")):
        vt = _encode_field(1, 0, 0) + _encode_field(2, 0, unit_idx)
        body += _encode_field(1, 2, vt)
    for label_pairs, n_obj, n_bytes in samples:
        sample = bytearray()
        sample += _encode_field(2, 0, n_obj)
        sample += _encode_field(2, 0, n_bytes)
        for key_idx, str_idx in label_pairs:
            lab = (_encode_field(1, 0, key_idx)
                   + _encode_field(2, 0, str_idx))
            sample += _encode_field(3, 2, bytes(lab))
        body += _encode_field(2, 2, bytes(sample))
    for s in strings:
        body += _encode_field(6, 2, s.encode())
    return bytes(body)


class TestMemoryProfileDiff:
    """PR 14 satellite (ROADMAP graftledger follow-on (c)): two
    sequence-numbered pprof captures diff into per-buffer divergence
    attribution."""

    STRINGS = ["", "objects", "bytes", "kind", "buffer", "shape",
               "f32[128,64]", "f32[8,8]"]

    def _profile(self, buf_bytes, small_bytes):
        s = self.STRINGS
        return _make_pprof(
            [([(s.index("kind"), s.index("buffer")),
               (s.index("shape"), s.index("f32[128,64]"))], 1,
              buf_bytes),
             ([(s.index("kind"), s.index("buffer")),
               (s.index("shape"), s.index("f32[8,8]"))], 1,
              small_bytes)],
            s)

    def test_parse_aggregates_by_label_set(self):
        parsed = memwatch.parse_memory_profile(
            self._profile(32768, 256))
        assert parsed == {
            "kind=buffer,shape=f32[128,64]": 32768,
            "kind=buffer,shape=f32[8,8]": 256,
        }

    def test_parse_handles_gzip(self):
        import gzip

        raw = self._profile(1024, 64)
        assert memwatch.parse_memory_profile(gzip.compress(raw)) \
            == memwatch.parse_memory_profile(raw)

    def test_parse_picks_bytes_value_column(self):
        """The bytes-typed sample value is summed — not the objects
        column sitting before it."""
        parsed = memwatch.parse_memory_profile(self._profile(500, 7))
        assert sum(parsed.values()) == 507

    def test_diff_attributes_per_buffer_group(self):
        before = memwatch.parse_memory_profile(
            self._profile(32768, 256))
        after = memwatch.parse_memory_profile(
            self._profile(65536, 256))
        diff = memwatch.diff_memory_profiles(before, after)
        assert diff["total_delta_bytes"] == 32768
        assert diff["deltas"] == [{
            "label": "kind=buffer,shape=f32[128,64]",
            "from_bytes": 32768, "to_bytes": 65536,
            "delta_bytes": 32768,
        }]
        # disappearing and appearing groups both attribute
        gone = memwatch.diff_memory_profiles(before, {})
        assert gone["total_delta_bytes"] == -(32768 + 256)
        assert len(gone["deltas"]) == 2
        assert gone["deltas"][0]["delta_bytes"] == -32768

    def test_http_diff_round_trip(self, tmp_path):
        """?diff=<seq> over HTTP: capture, capture-with-diff, and the
        400 contract for unknown/malformed sequence numbers."""
        from raft_tpu.serving import MetricsExporter

        exp = MetricsExporter(profile_dir=str(tmp_path))
        exp.start()
        try:
            r1 = json.loads(urllib.request.urlopen(
                exp.url("/memory_profile")).read())
            assert r1["seq"] == 1 and r1["bytes"] > 0
            r2 = json.loads(urllib.request.urlopen(
                exp.url("/memory_profile?diff=1")).read())
            assert r2["seq"] == 2
            d = r2["diff"]
            assert d["from_seq"] == 1 and d["to_seq"] == 2
            assert isinstance(d["deltas"], list)
            assert d["total_delta_bytes"] == (
                d["total_after_bytes"] - d["total_before_bytes"])
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(
                    exp.url("/memory_profile?diff=99"))
            assert e.value.code == 400
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(
                    exp.url("/memory_profile?diff=abc"))
            assert e.value.code == 400
        finally:
            exp.close()


class TestNamedReservations:
    """graftcast: named byte holds (the prefetcher's staged miss
    cache) subtract from headroom and pass growth through the
    capacity gate."""

    def test_reserve_subtracts_from_headroom(self):
        ledger = MemoryLedger(capacity_bytes=1000)
        assert ledger.headroom_bytes() == 1000
        ledger.reserve("tier.prefetch", 300)
        assert ledger.reserved_bytes() == 300
        assert ledger.headroom_bytes() == 700
        # a second named hold stacks; same-name re-reserve replaces
        ledger.reserve("other", 100)
        assert ledger.headroom_bytes() == 600
        ledger.reserve("tier.prefetch", 200)
        assert ledger.reserved_bytes() == 300
        assert ledger.headroom_bytes() == 700

    def test_release_is_idempotent(self):
        ledger = MemoryLedger(capacity_bytes=1000)
        ledger.reserve("tier.prefetch", 400)
        ledger.release("tier.prefetch")
        assert ledger.reserved_bytes() == 0
        assert ledger.headroom_bytes() == 1000
        ledger.release("tier.prefetch")   # no such hold: no error
        ledger.release("never-held")
        assert ledger.headroom_bytes() == 1000

    def test_growth_gated_refusal_restores_prior_hold(self):
        ledger = MemoryLedger(capacity_bytes=1000)
        ledger.reserve("tier.prefetch", 400)
        refused0 = tracing.get_counter("memory.gate.refused")
        # growth is judged WITHOUT the prior hold: 900 <= 1000 - 0
        # admits even though 900 > headroom-with-hold (600)
        ledger.reserve("tier.prefetch", 900)
        assert ledger.reserved_bytes() == 900
        # but beyond capacity refuses and keeps the 900 hold intact
        with pytest.raises(CapacityExceeded) as e:
            ledger.reserve("tier.prefetch", 1200)
        assert e.value.required_bytes == 1200
        assert ledger.reserved_bytes() == 900
        assert (tracing.get_counter("memory.gate.refused")
                == refused0 + 1)

    def test_shrink_always_admitted(self):
        ledger = MemoryLedger(capacity_bytes=1000)
        ledger.reserve("tier.prefetch", 800)
        # other pressure appears: even with zero headroom, shrinking
        # (and zeroing) the hold must never raise
        ledger.reserve("other", 200)
        assert ledger.headroom_bytes() == 0
        ledger.reserve("tier.prefetch", 100)
        assert ledger.reserved_bytes() == 300
        ledger.reserve("tier.prefetch", 0)
        assert ledger.reserved_bytes() == 200

    def test_gate_admission_sees_holds(self, data):
        """A build racing the prefetcher's hold is refused the bytes
        the hold already claimed."""
        x, _ = data
        model_bytes = memwatch.index_memory_model(
            ivf_flat.build(
                None, ivf_flat.IvfFlatIndexParams(n_lists=8), x)
        )["resident_bytes"]
        ledger = MemoryLedger(capacity_bytes=1.5 * model_bytes)
        memwatch.install_gate(ledger)
        ledger.reserve("tier.prefetch", model_bytes)
        with pytest.raises(CapacityExceeded):
            ivf_flat.build(
                None, ivf_flat.IvfFlatIndexParams(n_lists=8), x)
        ledger.release("tier.prefetch")
        ivf_flat.build(None, ivf_flat.IvfFlatIndexParams(n_lists=8), x)

    def test_snapshot_and_gauge_publish_holds(self, flat_index):
        ledger = MemoryLedger(capacity_bytes=10**9)
        ledger.watch("flat", flat_index)
        ledger.reserve("tier.prefetch", 12345)
        snap = ledger.publish()
        assert snap["reserved_held_bytes"] == 12345
        assert (tracing.gauges().get("memory.reserved.held_bytes")
                == 12345)

    def test_unknown_headroom_stays_unknown(self, flat_index):
        """No capacity + no live stats: holds don't invent a number —
        headroom stays None and growth is un-gateable (admitted)."""
        ledger = MemoryLedger()
        ledger.reserve("tier.prefetch", 500)
        assert ledger.headroom_bytes() is None
        assert ledger.reserved_bytes() == 500
        verdict = ledger.fits(flat_index)
        assert verdict["fits"] is True and verdict["unknown"] is True
