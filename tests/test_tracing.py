"""The graftscope observability core (``core/tracing.py``): histogram
thread safety + cumulative buckets, gauges, and the span flight
recorder with its Chrome trace-event round trip."""

import json
import threading

import pytest

from raft_tpu.core import tracing


class TestHistogramConcurrency:
    def test_concurrent_observe_loses_nothing(self):
        """PR 5's ``get_histogram`` handed out live objects whose
        ``observe`` ran unlocked — racing increments could drop
        counts. Hammer one instance from many threads and assert
        exact totals."""
        h = tracing.Histogram()
        n_threads, per_thread = 8, 5000
        start = threading.Barrier(n_threads)

        def worker(seed):
            start.wait()
            for i in range(per_thread):
                h.observe(1e-6 * ((seed + i) % 50 + 1))

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        snap = h.snapshot()
        assert snap["count"] == n_threads * per_thread
        assert sum(h.counts) == n_threads * per_thread
        assert snap["bucket_counts"][-1] == n_threads * per_thread

    def test_concurrent_snapshot_is_consistent(self):
        """A snapshot taken mid-storm must be internally consistent:
        its cumulative bucket total equals its count."""
        h = tracing.Histogram()
        stop = threading.Event()

        def writer():
            i = 0
            while not stop.is_set():
                h.observe(1e-6 * (i % 30 + 1))
                i += 1

        w = threading.Thread(target=writer)
        w.start()
        try:
            for _ in range(200):
                snap = h.snapshot()
                assert snap["bucket_counts"][-1] == snap["count"]
        finally:
            stop.set()
            w.join()


class TestHistogramBuckets:
    def test_cumulative_buckets_shape_and_monotonicity(self):
        h = tracing.Histogram()
        for v in (0.5e-6, 3e-6, 3e-6, 1.0):
            h.observe(v)
        snap = h.snapshot()
        bounds, cum = snap["bucket_bounds"], snap["bucket_counts"]
        assert len(cum) == len(bounds) + 1      # +Inf overflow bucket
        assert cum == sorted(cum)               # cumulative => monotone
        assert cum[-1] == snap["count"] == 4
        # first bucket (le 1e-6) holds exactly the 0.5 µs observation
        assert cum[0] == 1

    def test_empty_histogram(self):
        h = tracing.Histogram()
        snap = h.snapshot()
        assert snap["count"] == 0 and snap["sum"] == 0.0
        assert h.quantile(0.5) == 0.0
        assert snap["p50"] == snap["p95"] == snap["p99"] == 0.0
        assert snap["bucket_counts"][-1] == 0

    def test_single_observation_quantile(self):
        """Every quantile of a single observation lands inside that
        observation's bucket (linear interpolation within it)."""
        h = tracing.Histogram()
        h.observe(5e-6)                          # bucket (4e-6, 8e-6]
        for q in (0.01, 0.5, 0.99):
            assert 4e-6 < h.quantile(q) <= 8e-6, q

    def test_overflow_bucket_estimate(self):
        """Observations past the last bound interpolate inside the
        synthetic overflow bucket (last bound, 2 × last bound] — a
        bounded estimate, not garbage — and q→1 hits the 2× cap."""
        h = tracing.Histogram()
        h.observe(1e9)
        top = h.bounds[-1]
        assert top < h.quantile(0.5) <= 2.0 * top
        assert h.quantile(1.0) == pytest.approx(2.0 * top)
        snap = h.snapshot()
        assert snap["bucket_counts"][-2] == 0    # nothing below +Inf
        assert snap["bucket_counts"][-1] == 1


class TestGauges:
    def test_set_get_prefix_reset(self):
        tracing.reset_gauges("t_gauge.")
        tracing.set_gauge("t_gauge.a", 3.0)
        tracing.set_gauge("t_gauge.a", 1.5)      # last write wins
        tracing.set_gauges({"t_gauge.b": 2.0, "other.c": 7.0})
        try:
            assert tracing.get_gauge("t_gauge.a") == 1.5
            assert tracing.get_gauge("t_gauge.missing", -1.0) == -1.0
            assert tracing.gauges("t_gauge.") == {"t_gauge.a": 1.5,
                                                  "t_gauge.b": 2.0}
            tracing.reset_gauges("t_gauge.")
            assert tracing.gauges("t_gauge.") == {}
            assert tracing.get_gauge("other.c") == 7.0
        finally:
            tracing.reset_gauges("t_gauge.")
            tracing.reset_gauges("other.c")

    def test_inc_counters_batch(self):
        tracing.reset_counters("t_batch.")
        try:
            tracing.inc_counters({"t_batch.x": 2.0, "t_batch.y": 1.0})
            tracing.inc_counters({"t_batch.x": 3.0})
            assert tracing.get_counter("t_batch.x") == 5.0
            assert tracing.get_counter("t_batch.y") == 1.0
        finally:
            tracing.reset_counters("t_batch.")

    def test_reset_folds_into_lifetime_ledger(self):
        """``reset_counters`` moves counts into the process-lifetime
        ledger instead of discarding them: the session-end CI snapshot
        floors read :func:`lifetime_counters`, so a mid-session test
        reset must not blank the session's accounting."""
        tracing.reset_counters("t_life.")
        base = tracing.lifetime_counters("t_life.")
        tracing.inc_counter("t_life.a", 2.0)
        tracing.reset_counters("t_life.")        # folds, not discards
        tracing.inc_counter("t_life.a", 3.0)     # live again
        life = tracing.lifetime_counters("t_life.")
        assert life["t_life.a"] - base.get("t_life.a", 0.0) == 5.0
        # the LIVE view only sees what ran after the reset
        assert tracing.get_counter("t_life.a") == 3.0
        tracing.reset_counters("t_life.")


class TestSpanRecorder:
    def test_record_filter_and_trace_ids(self):
        r = tracing.SpanRecorder(capacity=16)
        a, b = tracing.new_trace_id(), tracing.new_trace_id()
        assert a != b
        r.record("stage.one", 0.0, 1.0, trace_ids=(a,))
        r.record("stage.two", 1.0, 2.0, trace_ids=(a, b))
        r.event("mark", 1.5, trace_ids=(b,), attrs={"reason": "x"})
        assert len(r) == 3
        assert [s.name for s in r.spans(trace_id=a)] == ["stage.one",
                                                         "stage.two"]
        only_b = r.spans(trace_id=b)
        assert [s.name for s in only_b] == ["stage.two", "mark"]
        assert r.spans(name="mark")[0].duration == 0.0
        assert r.spans(name="mark")[0].attrs["reason"] == "x"

    def test_ring_bounds_and_drop_accounting(self):
        """The flight recorder is bounded: old spans fall off, and the
        overwrite count is visible (a post-mortem must know whether it
        sees the whole story)."""
        r = tracing.SpanRecorder(capacity=4)
        for i in range(10):
            r.record(f"s{i}", float(i), float(i) + 0.5)
        assert len(r) == 4
        assert r.dropped == 6
        assert [s.name for s in r.spans()] == ["s6", "s7", "s8", "s9"]
        r.clear()
        assert len(r) == 0 and r.dropped == 0

    def test_chrome_trace_round_trip(self):
        """Export → json.dumps → json.loads → import reproduces the
        exact span list (timestamps ride in args as float seconds, so
        µs conversion lossiness cannot corrupt a post-mortem)."""
        r = tracing.SpanRecorder(capacity=8)
        tid = tracing.new_trace_id()
        r.record("serving.execute", 0.1, 0.25, trace_ids=(tid,),
                 attrs={"rows": 17},
                 events=((0.2, "failed", {"error": "ValueError"}),))
        r.event("serving.shed", 0.3, trace_ids=(tid,),
                attrs={"reason": "deadline"})
        data = json.loads(json.dumps(r.to_chrome_trace()))
        assert data["traceEvents"], data
        xs = [e for e in data["traceEvents"] if e["ph"] == "X"]
        assert {e["name"] for e in xs} == {"serving.execute",
                                           "serving.shed"}
        # event marks surface as instant events for Perfetto
        instants = [e for e in data["traceEvents"] if e["ph"] == "i"]
        assert any(e["name"] == "serving.execute.failed"
                   for e in instants)
        # zero-duration spans (shed/cancel/reject reasons) surface as
        # clickable instant marks too, not just invisible dur=0 slices
        shed_marks = [e for e in instants if e["name"] == "serving.shed"]
        assert shed_marks and shed_marks[0]["args"]["reason"] == "deadline"
        back = tracing.SpanRecorder.from_chrome_trace(data)
        assert back == r.spans()

    def test_chrome_trace_reserved_keys_win_over_attrs(self):
        """A span attr named like a reserved arg key (``t0_s`` etc.)
        must not corrupt the export: the reserved keys win, so the
        rebuilt span keeps exact timing/ids and only the colliding
        attr itself is shadowed."""
        r = tracing.SpanRecorder(capacity=4)
        r.record("x", 1.0, 2.0, trace_ids=(7,),
                 attrs={"t0_s": "label", "trace_ids": "oops", "rows": 3})
        (back,) = tracing.SpanRecorder.from_chrome_trace(
            json.loads(json.dumps(r.to_chrome_trace())))
        assert (back.start, back.end) == (1.0, 2.0)
        assert back.trace_ids == (7,)
        assert back.attrs == {"rows": 3}

    def test_process_ring_helpers(self):
        tracing.reset_spans()
        try:
            tid = tracing.new_trace_id()
            tracing.record_span("stage", 1.0, 2.0, trace_ids=(tid,))
            tracing.span_event("mark", 1.5, trace_ids=(tid,))
            assert len(tracing.span_recorder().spans(trace_id=tid)) == 2
        finally:
            tracing.reset_spans()

    def test_host_span_context_manager(self):
        """The stage helper: on the caller's clock, its duration goes
        into the named histogram and (asked) the ring; a stage that
        raises observes nothing and records a ``failed`` event; an
        early ``close`` ends the stage and the exit is a no-op."""
        tracing.reset_spans()
        tracing.reset_histograms("test.stage")
        now = iter([1.0, 3.5, 10.0, 12.0, 20.0, 21.0]).__next__
        try:
            with tracing.host_span("build.extend", clock=now,
                                   hist="test.stage_seconds", ring=True,
                                   trace_ids=(4,), attrs={"n": 3}) as sp:
                pass
            assert (sp.start, sp.end, sp.duration) == (1.0, 3.5, 2.5)
            (s,) = tracing.span_recorder().spans(name="build.extend")
            assert (s.start, s.end, s.trace_ids) == (1.0, 3.5, (4,))
            assert s.attrs == {"n": 3} and s.events == ()
            with pytest.raises(KeyError):
                with tracing.host_span("build.fail", clock=now,
                                       hist="test.stage_seconds",
                                       ring=True):
                    raise KeyError("x")
            (f,) = tracing.span_recorder().spans(name="build.fail")
            assert f.events == ((12.0, "failed", {"error": "KeyError"}),)
            with tracing.host_span("build.early", clock=now,
                                   hist="test.stage_seconds") as sp:
                sp.close()
            assert sp.duration == 1.0
            h = tracing.histograms("test.stage")["test.stage_seconds"]
            assert (h["count"], h["sum"]) == (2, 3.5)
            assert not tracing.span_recorder().spans(name="build.early")
        finally:
            tracing.reset_spans()
            tracing.reset_histograms("test.stage")


class TestStragglerDetector:
    """graftscope v2: per-shard timings reduce into exact straggler
    attribution — gauges, phase spans, and the trace_id-filtered
    Chrome export."""

    def test_straggler_stats_exact(self):
        stats = tracing.straggler_stats([0.010, 0.004, 0.025, 0.007])
        assert stats["shards"] == 4
        assert stats["slowest_shard"] == 2
        assert stats["shard_skew"] == pytest.approx(0.021)
        assert stats["max_s"] == 0.025
        assert stats["mean_s"] == pytest.approx(0.0115)
        empty = tracing.straggler_stats([])
        assert empty["slowest_shard"] == -1
        assert empty["shard_skew"] == 0.0

    def test_record_mesh_spans_spans_and_gauges(self):
        tracing.reset_spans()
        tracing.reset_gauges("serving.mesh.")
        tracing.reset_counters("serving.mesh.")
        try:
            tid = tracing.new_trace_id()
            stats = tracing.record_mesh_spans(
                "dist_ivf_flat", 10.0, 10.5, trace_ids=(tid,),
                phases={"coarse_select": {"wire_bytes": 256},
                        "merge": {"wire_bytes": 1280}},
                shard_timings=[0.1, 0.5, 0.2])
            rec = tracing.span_recorder()
            (cs,) = rec.spans(trace_id=tid,
                              name="serving.mesh.coarse_select")
            assert cs.attrs["wire_bytes"] == 256
            assert cs.attrs["family"] == "dist_ivf_flat"
            assert (cs.start, cs.end) == (10.0, 10.5)
            shards = rec.spans(trace_id=tid, name="serving.mesh.shard")
            assert [s.attrs["shard"] for s in shards] == [0, 1, 2]
            assert shards[1].end == pytest.approx(10.5)
            # gauges pin to the scripted timings exactly
            assert tracing.get_gauge(
                tracing.MESH_SHARD_SKEW) == pytest.approx(0.4)
            assert tracing.get_gauge(tracing.MESH_SLOWEST_SHARD) == 1.0
            assert tracing.get_gauge(
                tracing.MESH_SHARD_TIME_MAX) == pytest.approx(0.5)
            assert tracing.get_counter("serving.mesh.dispatches") == 1.0
            assert stats["shard_skew"] == pytest.approx(0.4)
        finally:
            tracing.reset_spans()
            tracing.reset_gauges("serving.mesh.")
            tracing.reset_counters("serving.mesh.")

    def test_chrome_trace_trace_id_filter(self):
        tracing.reset_spans()
        try:
            t1, t2 = tracing.new_trace_id(), tracing.new_trace_id()
            tracing.record_span("a", 1.0, 2.0, trace_ids=(t1,))
            tracing.record_span("b", 1.0, 2.0, trace_ids=(t2,))
            tracing.record_span("both", 2.0, 3.0, trace_ids=(t1, t2))
            rec = tracing.span_recorder()
            names = {e["name"]
                     for e in rec.to_chrome_trace(
                         trace_id=t1)["traceEvents"]}
            assert names == {"a", "both"}
            # unknown id: empty but VALID trace, not an error
            empty = rec.to_chrome_trace(trace_id=10**9)
            assert empty["traceEvents"] == []
            # the unfiltered export is unchanged
            assert len(rec.to_chrome_trace()["traceEvents"]) == 3
        finally:
            tracing.reset_spans()


class TestSpanRecorderConcurrentOverflow:
    """PR 8 satellite: the ring's overwrite accounting stays exact
    with MULTIPLE recorders overflowing under concurrent writers —
    recorders share nothing (each has its own lock, deque, and drop
    counter), so parallel flight recorders (per-test rings next to the
    process ring) cannot cross-pollute each other's story."""

    def test_concurrent_recorders_exact_drop_accounting(self):
        recorders = [tracing.SpanRecorder(capacity=32)
                     for _ in range(3)]
        threads_per = 4
        spans_per = 500
        errs = []

        def writer(r, tid):
            try:
                for i in range(spans_per):
                    r.record(f"t{tid}.s{i}", float(i), float(i) + 0.1)
            except Exception as e:          # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=writer,
                                    args=(r, t), daemon=True)
                   for r in recorders for t in range(threads_per)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        for r in recorders:
            # every record either resides in the ring or was counted
            # dropped — nothing vanishes silently
            assert len(r) == 32
            assert r.dropped == threads_per * spans_per - 32
            # the ring holds whole spans (no torn writes)
            for s in r.spans():
                assert s.end == pytest.approx(s.start + 0.1)

    def test_process_ring_isolated_from_local_recorders(self):
        tracing.reset_spans()
        local = tracing.SpanRecorder(capacity=2)
        for i in range(5):
            local.record(f"local{i}", float(i), float(i) + 1)
        assert tracing.span_recorder().dropped == 0
        tracing.record_span("process.one", 0.0, 1.0)
        assert local.dropped == 3
        assert len(tracing.span_recorder()) == 1


class TestGraftgaugeReducers:
    """graftgauge (PR 8): the pure index-health / probe-frequency /
    drift reducers — host-array functions whose every output a
    scripted test pins exactly."""

    def test_index_health_exact(self):
        sizes = [0, 10, 10, 10, 10, 40, 0, 10]
        h = tracing.index_health(sizes, max_list_size=40, shards=2)
        assert h["n_lists"] == 8
        assert h["rows"] == 90
        assert h["max_list_size"] == 40
        assert h["mean_list_size"] == pytest.approx(90 / 8)
        assert h["dead_lists"] == 2
        assert h["overflow_lists"] == 1
        assert h["fill_fraction"] == pytest.approx(90 / (8 * 40))
        # shards: [0,10,10,10]=30 vs [10,40,0,10]=60 -> max/mean
        assert h["shard_imbalance"] == pytest.approx(60 / 45)
        assert 0.0 < h["gini"] < 1.0

    def test_index_health_gini_edges(self):
        even = tracing.index_health([5, 5, 5, 5])
        assert even["gini"] == pytest.approx(0.0)
        skewed = tracing.index_health([0, 0, 0, 20])
        # all rows in one of n lists -> (n-1)/n
        assert skewed["gini"] == pytest.approx(3 / 4)
        assert tracing.index_health([])["gini"] == 0.0
        assert tracing.index_health([0, 0])["rows"] == 0

    def test_probe_freq_stats_exact(self):
        # 100 lists: list 0 takes 90 probes, list 1 takes 6, 4 lists
        # take 1 each -> total 100
        counts = [0] * 100
        counts[0] = 90
        counts[1] = 6
        for lid in (10, 20, 30, 40):
            counts[lid] = 1
        s = tracing.probe_freq_stats(counts, top_n=3)
        assert s["total"] == 100
        assert s["probed_fraction"] == pytest.approx(6 / 100)
        # hottest 1% (1 list) absorbs 90%; hottest 10% everything
        assert s["coverage_p01"] == pytest.approx(0.90)
        assert s["coverage_p10"] == pytest.approx(1.0)
        assert s["top"] == [(0, 90), (1, 6), (10, 1)]

    def test_probe_freq_stats_empty(self):
        s = tracing.probe_freq_stats([0, 0, 0])
        assert s["total"] == 0 and s["top"] == []
        assert s["coverage_p01"] == 0.0
        assert tracing.probe_freq_stats([])["n_lists"] == 0

    def test_js_divergence_properties(self):
        assert tracing.js_divergence([1, 2, 3], [1, 2, 3]) == (
            pytest.approx(0.0))
        assert tracing.js_divergence([2, 4, 6], [1, 2, 3]) == (
            pytest.approx(0.0))      # scale-invariant
        # disjoint support is maximal drift (base-2 JSD bound)
        assert tracing.js_divergence([1, 0], [0, 1]) == (
            pytest.approx(1.0))
        a, b = [5, 1, 1], [1, 1, 5]
        assert tracing.js_divergence(a, b) == pytest.approx(
            tracing.js_divergence(b, a))   # symmetric
        assert 0.0 < tracing.js_divergence(a, b) < 1.0
        # zero-mass edges
        assert tracing.js_divergence([0, 0], [0, 0]) == 0.0
        assert tracing.js_divergence([0, 0], [1, 1]) == 1.0
