"""The readers of the served path's stage spans: each is a histogram
sum or a counter over the window's dispatches, from hand-made
before/after snapshots, and reads nothing from a program that records
no stage spans or dispatched nothing."""

import pytest

from benchmark import run, spec

BATCHES = "serving.batcher.batches"
DEVICE_WAIT = "serving.batcher.device_wait_seconds"

# (metric, histogram it reads; None: the eager-program counter)
TIME_METRICS = [
    ("frontend.hold_ms", "serving.batcher.hold_seconds"),
    ("executor.prepare_ms", "serving.executor.prepare_seconds"),
    ("executor.enqueue_ms", "serving.executor.enqueue_seconds"),
    ("executor.slice_ms", "serving.executor.slice_seconds"),
    ("executor.device_wait_ms", DEVICE_WAIT),
]
ALL = [m for m, _ in TIME_METRICS] + ["executor.eager_programs_per_dispatch"]


def _reader(name):
    return spec.load_module((spec.BENCH_DIR,), "metrics", name)


def _snap(counters, hists):
    return {"counters": counters,
            "hists": {k: {"count": c, "sum": s}
                      for k, (c, s) in hists.items()},
            "compiles": 0, "xla_compiles": 0}


def _window(before, after):
    return run.Window(None, before, after, None, None, 0, None)


def _stage_window(hist, count, seconds, batches=8.0, eager=16.0):
    """A window of ``batches`` dispatches over which ``hist`` grew by
    ``count`` observations and ``seconds``, on top of earlier ones."""
    before = _snap({BATCHES: 2.0, "serving.execute.eager_programs": 4.0},
                   {DEVICE_WAIT: (2, 0.25), hist: (2, 0.5)})
    after = _snap({BATCHES: 2.0 + batches,
                   "serving.execute.eager_programs": 4.0 + eager},
                  {DEVICE_WAIT: (2 + batches, 0.25 + 0.016),
                   hist: (2 + count, 0.5 + seconds)})
    return _window(before, after)


@pytest.mark.parametrize("metric,hist", TIME_METRICS)
def test_time_per_dispatch(metric, hist):
    """Sum over dispatches, not over observations: a stage observed
    twice per dispatch (the tiles of an oversized batch) still reads
    the time a dispatch spent in it."""
    w = _stage_window(hist, count=16, seconds=0.024)
    assert _reader(metric).read(w) == pytest.approx(3.0)


def test_stage_never_observed_reads_zero():
    """No hold in the window (every dispatch filled its bucket) is a
    reading of 0, not a missing one."""
    before = _snap({BATCHES: 0.0}, {})
    after = _snap({BATCHES: 5.0}, {DEVICE_WAIT: (5, 0.01)})
    w = _window(before, after)
    assert _reader("frontend.hold_ms").read(w) == 0.0
    assert _reader("executor.eager_programs_per_dispatch").read(w) == 0.0


def test_eager_programs_per_dispatch():
    w = _stage_window("serving.executor.slice_seconds", 8, 0.001,
                      batches=8.0, eager=256.0)
    assert _reader("executor.eager_programs_per_dispatch").read(w) == 32.0


@pytest.mark.parametrize("metric", ALL)
def test_nothing_without_dispatches(metric):
    snap = _snap({BATCHES: 3.0}, {DEVICE_WAIT: (3, 0.1)})
    assert _reader(metric).read(_window(snap, snap)) is None


@pytest.mark.parametrize("metric", ALL)
def test_nothing_from_a_program_without_stage_spans(metric):
    """A program that predates the spans has dispatches but no
    device-wait histogram: every reader returns nothing and raises
    nothing."""
    before = _snap({BATCHES: 1.0}, {"serving.batcher.execute_seconds":
                                    (1, 0.003)})
    after = _snap({BATCHES: 9.0}, {"serving.batcher.execute_seconds":
                                   (9, 0.03)})
    assert _reader(metric).read(_window(before, after)) is None


def test_every_reader_is_in_the_benchmark():
    cell = spec.Cell("ivf_flat-sift1m.single")
    entries = {e["name"]: e for e, _ in cell.per_layer()}
    for metric in ALL:
        assert metric in entries
        assert "workloads" not in entries[metric]
    assert entries["frontend.hold_ms"]["moves"] == "latency_p95_ms"
