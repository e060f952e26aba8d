"""The trace reduction, on hand-made intervals and on a trace recorded
on the chip in PR 22 (``benchmark/data/single_chip.xplane.pb``: a
half-second traced run of ``ivf_flat-sift1m.single``, 225 dispatches)."""

import os

import pytest

from benchmark import spec, trace
from benchmark.work import ivf_scan

CHIP_TRACE = os.path.join(spec.BENCH_DIR, "data", "single_chip.xplane.pb")


def test_union_clips_and_merges():
    iv = [(5, 8), (0, 2), (1, 3), (7, 12), (20, 30)]
    assert trace.union(iv, 1, 25) == [(1, 3), (5, 12), (20, 25)]


def test_gaps_between_busy():
    busy = [(1, 3), (5, 12)]
    assert trace.gaps(busy, 0, 15) == [(0, 1), (3, 5), (12, 15)]
    assert trace.gaps([], 0, 4) == [(0, 4)]


def test_label_takes_innermost_span():
    spans = [("bench.window", 0, 100), ("bench.result", 10, 60),
             ("PjitFunction", 20, 30)]
    assert trace.label(spans, 25) == "PjitFunction"
    assert trace.label(spans, 50) == "bench.result"
    assert trace.label(spans, 80) == "no host span"


@pytest.fixture(scope="module")
def chip():
    if not os.path.exists(CHIP_TRACE):
        pytest.skip("no recorded chip trace")
    from jax.profiler import ProfileData

    return ProfileData.from_file(CHIP_TRACE)


def test_chip_trace_reduces(chip):
    red = trace.reduce(chip, {"ivf_scan": ivf_scan.TRACE_PATTERNS})
    assert 0 < red["busy_s"] < red["window_s"]
    n, secs = red["kernels"]["ivf_scan"]
    assert n > 0 and secs > 0
    ops = red["breakdown"]["device_ops"]
    assert 0 < len(ops) <= trace.TOP
    assert ops == sorted(ops, key=lambda o: -o[1])
    gaps = red["breakdown"]["idle_gaps"]
    assert 0 < len(gaps) <= trace.TOP
    assert all(g[1] > 0 for g in gaps)
    # busy + idle covers the window exactly
    ops_by_plane = trace.device_ops(chip)
    spans = trace.host_spans(chip)
    lo, hi = next((s, e) for n, s, e in spans if n == trace.WINDOW_SPAN)
    for evs in ops_by_plane.values():
        if not evs:
            continue
        busy = trace.union(((s, e) for _, s, e in evs), lo, hi)
        idle = trace.gaps(busy, lo, hi)
        total = sum(e - s for s, e in busy) + sum(e - s for s, e in idle)
        assert total == pytest.approx(hi - lo)


def test_kernel_patterns_match_the_chip_trace_names(chip):
    import re

    names = [n for evs in trace.device_ops(chip).values() for n, _, _ in evs]
    for p in ivf_scan.TRACE_PATTERNS:
        hits = [n for n in names if re.match(p, n)]
        assert hits and all("tpu_custom_call" in n for n in hits)


def test_short_name_keeps_name_and_opcode():
    text = ('%rt_ivf_flat_fa78935e9f07.1 = (f32[8,10]{1,0:T(8,128)S(1)}, '
            's32[8,10]{1,0:T(8,128)}) custom-call(s32[256]{0} %fusion.2), '
            'custom_call_target="tpu_custom_call"')
    assert (trace.short_name(text)
            == "%rt_ivf_flat_fa78935e9f07.1 custom-call tpu_custom_call")
    assert (trace.short_name("%fusion.2 = s32[1024]{0:T(1024)S(1)} "
                             "fusion(s32[32768]{0} %bitcast.14)")
            == "%fusion.2 fusion")
