"""The four-chip byte cell's files at a tiny size on the CPU: the
streamed byte build through ``dist_ivf_flat``'s ``build_on`` and the
served path under the harness, the three mesh readers, and the byte
scan's trace pattern."""

import json
import os
import re

import pytest

from benchmark import run, spec

HERE = os.path.dirname(os.path.abspath(__file__))
MESH_FIX = os.path.join(HERE, "fixtures_mesh_bytes")
FIX_BENCH = os.path.join(MESH_FIX, "BENCHMARK.json")
DIRS = (MESH_FIX, os.path.join(HERE, "fixtures"), spec.BENCH_DIR)
CHIP_TRACE = os.path.join(spec.BENCH_DIR, "data", "single_chip.xplane.pb")
CELL = "tiny_u8_stream.bulk"
NEW = ("mesh_ivf_scan_roofline", "mesh.scan_ms",
       "mesh.wire_bytes_per_dispatch")

# the byte scan's event as the v5e compiler names it in the served
# four-chip executable (compiled HLO for a described v5e:2x2)
BYTE_EVENT = (
    '%scan.1 = (f32[1032,10]{1,0:T(8,128)S(1)}, s32[1032,10]{1,0:T(8,128)'
    'S(1)}) custom-call(s32[8192]{0} %copy-done.5, s32[1032,128]{1,0} '
    '%pad.12, bf16[1032,128]{1,0} %copy-done.6, bf16[1032,128]{1,0} '
    '%copy-done.7, u8[8192,6208,128]{2,1,0:T(8,128)(4,1)} %param.6, '
    '/*index=5*/f32[8192,1,6208]{2,1,0} %broadcast_select_fusion.1, '
    's32[8192,1,6208]{2,1,0} %broadcast_select_fusion), '
    'custom_call_target="tpu_custom_call"')


def _main(capsys, trace_flag=0, seed=2600000001):
    rc = run.main(["--workload", CELL, "--seed", str(seed),
                   "--seconds", "0.5", "--trace", str(trace_flag)],
                  bench_path=FIX_BENCH, dirs=DIRS)
    out = capsys.readouterr()
    return rc, json.loads(out.out.strip().splitlines()[-1]), out.err


def test_streamed_byte_cell_runs_correct_on_cpu(capsys):
    rc, res, err = _main(capsys)
    assert rc == 0
    assert res["correct"] is True, err
    assert res["checks"]["dist_err"]["value"] == 0.0
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"qps", "latency_p95_ms",
                                   "recall_at_10", "setup_s"}
    assert "over 4 chips" in err and "uint8" in err
    for stage in ("sample", "quantizer", "labels", "scatter", "norms"):
        assert f"build stage {stage} " in err


def test_traced_run_reports_the_wire_bytes(capsys, monkeypatch):
    """``--trace 1`` with the CPU's trace (no device plane) replaced by
    an empty device reduction: the program counter's reader reports,
    the device readers find nothing and stay silent."""
    from benchmark import trace

    monkeypatch.setattr(trace, "reduce_dir", lambda d, kernels: {
        "busy_s": 1.0, "window_s": 2.0,
        "kernels": {k: (0, 0.0) for k in kernels},
        "breakdown": {"device_ops": [], "idle_gaps": []}})
    rc, res, _ = _main(capsys, trace_flag=1)
    assert rc == 0 and res["correct"] is True
    m = res["metrics"]
    assert m["mesh.wire_bytes_per_dispatch"]["value"] > 0
    assert "mesh_ivf_scan_roofline" not in m and "mesh.scan_ms" not in m
    assert m["executor.compiles_in_window"]["value"] == 0


class _Cell:
    chips = 4
    conf = {"kernel": "mesh_ivf_scan"}


class _Window:
    """What a reader sees of one traced run."""

    def __init__(self, events, seconds, batches=3, requests=3,
                 wire=None):
        self.cell, self.n_requests = _Cell(), 3
        self.trace = {"kernels": {"mesh_ivf_scan": (events, seconds)}}
        self.work = (8.19e9, 1.97e12)          # 10 ms + 10 ms ideal
        self.peak = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}
        self._c = {"serving.batcher.batches": batches,
                   "serving.batcher.requests": requests}
        self.after = {"counters": {}}
        if wire is not None:
            self._c["serving.mesh.wire_bytes"] = wire
            self.after["counters"]["serving.mesh.wire_bytes"] = wire

    def counter(self, name):
        return self._c.get(name, 0.0)


def _reader(name):
    return spec.load_module((spec.BENCH_DIR,), "metrics", name)


def test_mesh_readers():
    roof, scan, wire = (_reader(n) for n in NEW)
    # 12 events (4 chips x 3 dispatches), 0.4 s summed: 10 ms ideal
    assert roof.read(_Window(12, 0.4)) == pytest.approx(2.5)
    assert scan.read(_Window(12, 0.4)) == pytest.approx(1e3 * 0.4 / 12)
    # a trace that kept half the events reads the same share
    assert roof.read(_Window(6, 0.2)) == pytest.approx(2.5)
    assert roof.read(_Window(13, 0.4)) is None      # more than 4 x 3
    assert roof.read(_Window(12, 0.4, batches=2)) is None   # coalesced
    assert scan.read(_Window(0, 0.0)) is None
    assert wire.read(_Window(12, 0.4, wire=3 * 4096.0)) == 4096.0
    assert wire.read(_Window(12, 0.4)) is None      # a program without it


def test_byte_scan_pattern_matches_only_the_byte_kernel():
    work = spec.load_module((spec.BENCH_DIR,), "work", "mesh_ivf_scan")
    rx = [re.compile(p) for p in work.TRACE_PATTERNS]
    assert any(r.match(BYTE_EVENT) for r in rx)
    float_event = BYTE_EVENT.replace("u8[8192,6208,128]",
                                     "f32[8192,6208,128]")
    assert not any(r.match(float_event) for r in rx)
    if os.path.exists(CHIP_TRACE):       # the float32 single-chip scan
        from jax.profiler import ProfileData

        from benchmark import trace

        red = trace.reduce(ProfileData.from_file(CHIP_TRACE),
                           {"mesh_ivf_scan": work.TRACE_PATTERNS})
        assert red["kernels"]["mesh_ivf_scan"] == (0, 0.0)
