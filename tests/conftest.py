"""Test configuration: force a clean 8-virtual-device CPU JAX.

Multi-chip sharding is validated the way the reference validates MNMG
logic without a cluster (SURVEY.md §4: LocalCUDACluster of local
processes) — here a single process exposing 8 virtual CPU devices via
``xla_force_host_platform_device_count``.

Tests run on the CPU (``JAX_PLATFORMS=cpu``); the config update below
pins that even when the variable is unset, before any backend
initializes. The chip is reached only through ``chip_smoke.py``.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)
assert jax.devices()[0].platform == "cpu", "tests must run on CPU devices"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: beyond the tier-1 budget (e.g. the 16-shard point of "
        "the quantized-wire recall study) — deselected by -m 'not "
        "slow'")


@pytest.fixture
def rng_np():
    return np.random.default_rng(42)


@pytest.fixture
def res():
    from raft_tpu import Resources

    return Resources(seed=42)


def pytest_sessionfinish(session, exitstatus):
    """Drop a metrics-snapshot artifact after the run when CI asks
    (``RAFT_TPU_METRICS_SNAPSHOT=<path>``, set by ``ci/test.sh``): the
    full tracing registries — counters, gauges, histogram summaries
    with cumulative buckets, span-ring stats — accumulated over the
    test session. A CI browser then sees the same accounting a live
    ``/metrics`` scrape would show, next to the bench JSONs."""
    path = os.environ.get("RAFT_TPU_METRICS_SNAPSHOT")
    if not path:
        return
    import json

    from raft_tpu.core import tracing

    rec = tracing.span_recorder()
    snap = {
        "exit_status": int(exitstatus),
        "counters": tracing.counters(),
        # session totals surviving per-test reset_counters() isolation —
        # what ci/bench_compare.py floors check (the live view above
        # only carries whatever ran after the LAST reset)
        "counters_lifetime": tracing.lifetime_counters(),
        "gauges": tracing.gauges(),
        "histograms": tracing.histograms(),
        "spans": {"recorded": len(rec), "dropped": rec.dropped,
                  "capacity": rec.capacity},
    }
    with open(path, "w") as f:
        json.dump(snap, f, indent=2, sort_keys=True, default=str)
        f.write("\n")
