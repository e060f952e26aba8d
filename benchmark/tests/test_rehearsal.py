"""Rehearsals that cost no chip time: each cell's path at a tiny size
through the harness's own functions on the CPU, the data-driven lookup,
and the command's refusal off the chip."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import run, spec, trace

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures")
FIX_BENCH = os.path.join(FIXTURES, "BENCHMARK.json")
DIRS = (FIXTURES, spec.BENCH_DIR)
CHIP_TRACE = os.path.join(spec.BENCH_DIR, "data", "single_chip.xplane.pb")

CELLS = ["tiny_ivf.bulk", "tiny_bf.b10", "tiny_ivf.online",
         "tiny_ivf.single"]


def _main(capsys, workload, trace_flag=0, seed=3000000001):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "0.5", "--trace", str(trace_flag)],
                  bench_path=FIX_BENCH, dirs=DIRS)
    out = capsys.readouterr()
    return rc, json.loads(out.out.strip().splitlines()[-1]), out.err


@pytest.mark.parametrize("workload", CELLS)
def test_cell_path_tiny_on_cpu(capsys, workload):
    rc, res, err = _main(capsys, workload)
    assert rc == 0
    assert res["correct"] is True, err
    assert res["failed"] == 0 and res["attempted"] > 0
    m = res["metrics"]
    assert set(m) == {"qps", "latency_p95_ms", "recall_at_10", "setup_s"}
    assert all(v["value"] > 0 for v in m.values())
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"dist_err", "miss", "failed"}
    assert err.strip().splitlines()[-1].startswith("check failed 0")


def test_fixture_files_found_by_name_only():
    """A configuration, a traffic mix and a per-layer metric that exist
    only as fixture files resolve by the names a BENCHMARK.json gives."""
    cell = spec.Cell("tiny_ivf.online", FIX_BENCH, DIRS)
    assert cell.conf["name"] == "tiny_ivf"
    assert cell.traffic["clients"] == 8
    names = [e["name"] for e, _ in cell.per_layer()]
    assert "fixture.dispatches" in names
    assert "ivf_scan_roofline" not in names          # not listed for it
    with pytest.raises(spec.SpecError):
        spec.Cell("no_such.cell", FIX_BENCH, DIRS)


def test_traced_path_reads_every_listed_metric(capsys, monkeypatch):
    """``--trace 1`` through the harness, with the CPU's trace (which
    has no device plane) replaced by the chip trace kept here."""
    if not os.path.exists(CHIP_TRACE):
        pytest.skip("no recorded chip trace")
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(CHIP_TRACE)
    monkeypatch.setattr(trace, "reduce_dir",
                        lambda d, kernels: trace.reduce(pd, kernels))
    rc, res, _ = _main(capsys, "tiny_ivf.single", trace_flag=1)
    assert rc == 0 and res["correct"] is True
    m = res["metrics"]
    assert m["fixture.dispatches"]["value"] > 0
    assert m["executor.compiles_in_window"]["value"] == 0
    assert 0 <= m["device.idle_share"]["value"] <= 100
    for key in ("frontend.queue_wait_ms", "frontend.rows_per_dispatch",
                "executor.execute_ms"):
        assert m[key]["value"] > 0
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def _command(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "benchmark/run.py"] + args,
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_command_refuses_cpu():
    p = _command(["--workload", "ivf_flat-sift1m.bulk", "--seed", "1",
                  "--seconds", "1", "--trace", "0"], spec.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_command_fails_with_benchmark_files_alone(tmp_path):
    """In a directory holding only BENCHMARK.json and ``benchmark/``
    (no program) the command exits non-zero and prints no result."""
    shutil.copy(spec.BENCHMARK_JSON, tmp_path / "BENCHMARK.json")
    shutil.copytree(spec.BENCH_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(["--workload", "ivf_flat-sift1m.bulk", "--seed", "1",
                  "--seconds", "1", "--trace", "0"], tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    # past the look for a chip, too: the program is not there
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    code = ("import sys; sys.path.insert(0, '.'); import jax;"
            " from benchmark import run;"
            " run.require_chips = lambda chips: jax.devices();"
            " sys.exit(run.main(['--workload', 'ivf_flat-sift1m.bulk',"
            " '--seed', '1', '--seconds', '1']))")
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "raft_tpu" in p.stderr
