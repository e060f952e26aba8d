"""``scan.grouped_share``: the share of a window's batches whose
executable ran the grouped Pallas list scan, from hand-made
before/after counter snapshots."""

import pytest

from benchmark import run, spec

BATCHES = "serving.batcher.batches"
GROUPED = "serving.scan.grouped_dispatches"
IVF_CELLS = ["ivf_flat-sift1m.bulk", "ivf_flat-sift1m.online",
             "ivf_flat-sift1m.single", "ivf_flat-bigann-mesh4.bulk"]


def _reader():
    return spec.load_module((spec.BENCH_DIR,), "metrics",
                            "scan.grouped_share")


def _window(before, after):
    def snap(counters):
        return {"counters": counters, "hists": {}, "compiles": 0,
                "xla_compiles": 0}
    return run.Window(None, snap(before), snap(after), None, None, 0, None)


@pytest.mark.parametrize("grouped,want", [(12.0, 1.0), (6.0, 0.5),
                                          (0.0, 0.0)])
def test_share_of_the_windows_batches(grouped, want):
    """Counted over the window only; a degrade to the XLA scan adds 0
    to the counter, so it reads below 1.0, not nothing."""
    w = _window({BATCHES: 3.0, GROUPED: 3.0},
                {BATCHES: 15.0, GROUPED: 3.0 + grouped})
    assert _reader().read(w) == pytest.approx(want)


@pytest.mark.parametrize("counters", [
    {BATCHES: 9.0},                      # a program without the counter
    {BATCHES: 3.0, GROUPED: 3.0},        # no batch in the window
])
def test_nothing_to_read(counters):
    w = _window({BATCHES: 3.0, GROUPED: 3.0} if GROUPED in counters
                else {BATCHES: 1.0}, counters)
    assert _reader().read(w) is None


def test_reported_in_the_ivf_cells_only():
    for name in IVF_CELLS + ["brute_force-sift1m.b10"]:
        entries = {e["name"]: e for e, _ in spec.Cell(name).per_layer()}
        assert ("scan.grouped_share" in entries) == (name in IVF_CELLS)
    entry = {e["name"]: e for e, _ in
             spec.Cell(IVF_CELLS[0]).per_layer()}["scan.grouped_share"]
    assert entry["layer"] == "scan kernels"
    assert entry["moves"] == "qps"
