"""The served path's host stages as spans: each stage of a dispatch
(the batcher's hold and idle; the executor's prepare, enqueue, device
wait and slice) lands on the profiler's host timeline and in its
latency histogram, and the executor counts the eager device programs
a dispatch launches beside its compiled executable."""

import glob
import threading

import numpy as np
import pytest

from raft_tpu import SearchExecutor
from raft_tpu.core import tracing
from raft_tpu.neighbors import ivf_flat
from raft_tpu.serving import BatcherConfig, DynamicBatcher, metrics
from raft_tpu.serving.harness import FakeExecutor, ManualClock

K = 5
PARAMS = ivf_flat.IvfFlatSearchParams(n_probes=4)

STAGE_SPANS = (metrics.HOLD_SPAN, metrics.IDLE_SPAN,
               metrics.DEVICE_WAIT_SPAN, metrics.PREPARE_SPAN,
               metrics.ENQUEUE_SPAN, metrics.SLICE_SPAN)
# one observation each per (untiled, bucketed) dispatch
PER_DISPATCH = (metrics.PREPARE, metrics.ENQUEUE, metrics.SLICE,
                metrics.DEVICE_WAIT)


@pytest.fixture(scope="module")
def served():
    rng = np.random.default_rng(5)
    x = rng.standard_normal((400, 16)).astype(np.float32)
    q = rng.standard_normal((32, 16)).astype(np.float32)
    index = ivf_flat.build(None, ivf_flat.IvfFlatIndexParams(n_lists=8), x)
    ex = SearchExecutor()
    ex.warmup(index, buckets=(8, 16), k=K, params=PARAMS)
    for blocks in ([q[:1]], [q[i:i + 1] for i in range(10)],
                   [q[i:i + 1] for i in range(16)]):
        ex.search_blocks(index, blocks, K, params=PARAMS)
    return index, q, ex


def _hist(name):
    return tracing.histograms(name).get(name, {"count": 0, "sum": 0.0})


def _batches():
    return tracing.get_counter(metrics.PREFIX + "batches")


def _host_event_names(profile_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{profile_dir}/**/*.xplane.pb", recursive=True)
    pd = ProfileData.from_file(path)
    return {e.name for plane in pd.planes
            if plane.name.startswith("/host:CPU")
            for line in plane.lines for e in line.events}


class TestProfilerTimeline:
    def test_stage_spans_on_the_host_line_and_counted_per_dispatch(
            self, served, tmp_path):
        """A threaded batcher on the real clock under the profiler:
        every stage span is on a host line of the trace, each executor
        stage and the device wait is observed once per dispatch, and
        the four stages lie inside the batcher's execute span."""
        import jax

        index, q, ex = served
        b = DynamicBatcher(ex, BatcherConfig(max_wait_s=0.002))
        try:
            b.submit(index, q[:1], K, params=PARAMS).result(timeout=60)
            metrics.reset()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
            try:
                for j in range(6):     # lone requests: held, then idle
                    b.submit(index, q[j:j + 1], K,
                             params=PARAMS).result(timeout=60)
            finally:
                jax.profiler.stop_trace()
        finally:
            b.close()
        names = _host_event_names(tmp_path)
        assert set(STAGE_SPANS) <= names, set(STAGE_SPANS) - names
        assert _batches() == 6
        for h in PER_DISPATCH:
            assert _hist(h)["count"] == 6, h
        assert _hist(metrics.HOLD)["count"] >= 6
        stages = sum(_hist(h)["sum"] for h in PER_DISPATCH)
        assert 0 < stages <= _hist(metrics.EXECUTE)["sum"]


class _WaitSignalClock(ManualClock):
    """A manual clock that reports each timed wait the worker enters:
    the test advances time only once the worker is parked in it."""

    def __init__(self):
        super().__init__()
        self.timed_wait = threading.Event()

    def wait(self, cond, timeout):
        if timeout is not None:
            self.timed_wait.set()
        super().wait(cond, timeout)


class _Index:
    """Opaque index token for FakeExecutor tests."""


class TestHold:
    def test_lone_request_holds_exactly_max_wait(self):
        metrics.reset()
        clock = _WaitSignalClock()
        b = DynamicBatcher(FakeExecutor(), BatcherConfig(max_wait_s=0.002),
                           clock=clock)
        try:
            h = b.submit(_Index(), np.zeros((1, 4), np.float32), 3)
            assert clock.timed_wait.wait(timeout=30)
            clock.advance(0.002)
            h.result(timeout=30)
        finally:
            b.close()
        hold = _hist(metrics.HOLD)
        assert hold["count"] == 1
        assert hold["sum"] == 0.002

    def test_full_bucket_holds_nothing(self):
        metrics.reset()
        clock = _WaitSignalClock()
        b = DynamicBatcher(FakeExecutor(),
                           BatcherConfig(max_wait_s=10.0, full_batch_rows=4),
                           clock=clock)
        try:
            b.submit(_Index(), np.zeros((4, 4), np.float32),
                     3).result(timeout=30)
        finally:
            b.close()
        assert _hist(metrics.HOLD)["count"] == 0
        assert not clock.timed_wait.is_set()
        assert _batches() == 1


class TestEagerPrograms:
    # requests of one row each, in buckets 8, 16 and 32, short of the
    # bucket or filling it: the bucket's outputs come to the host in one
    # fetch and are cut there, so no dispatch launches a program beside
    # its executable
    @pytest.mark.parametrize("requests", [1, 10, 15, 16, 17])
    def test_count_per_dispatch(self, served, requests):
        index, q, ex = served
        clock = ManualClock()
        b = DynamicBatcher(ex, BatcherConfig(max_wait_s=0.01),
                           clock=clock, start=False)
        metrics.reset()
        handles = [b.submit(index, q[j:j + 1], K, params=PARAMS)
                   for j in range(requests)]
        clock.advance(0.01)
        assert b.pump() == 1
        for h in handles:
            d, i = h.result(timeout=0)
            assert isinstance(d, np.ndarray) and isinstance(i, np.ndarray)
        b.close()
        assert tracing.get_counter(metrics.EAGER_PROGRAMS) == 0
        assert _batches() == 1

    # (rows, programs): the library entry keeps its results on the
    # device, so a batch short of its bucket (1 row in bucket 8) is cut
    # there, a slice of distances and one of ids; a full bucket is the
    # outputs themselves
    @pytest.mark.parametrize("rows,programs", [(1, 2), (8, 0)])
    def test_search_keeps_device_arrays(self, served, rows, programs):
        import jax

        index, q, ex = served
        metrics.reset()
        d, i = ex.search(index, q[:rows], K, params=PARAMS)
        assert isinstance(d, jax.Array) and isinstance(i, jax.Array)
        assert d.shape == i.shape == (rows, K)
        assert tracing.get_counter(metrics.EAGER_PROGRAMS) == programs

    def test_results_unchanged_by_the_split(self, served):
        index, q, ex = served
        want_d, want_i = ex.search(index, q[:10], K, params=PARAMS)
        parts = ex.search_blocks(index, [q[:3], q[3:4], q[4:10]], K,
                                 params=PARAMS)
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(i) for _, i in parts]),
            np.asarray(want_i))
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(d) for d, _ in parts]),
            np.asarray(want_d))
