"""The benchmark's own tests run on the CPU at tiny sizes, on four
virtual devices (``xla_force_host_platform_device_count``, set before
JAX starts) so the four-chip cells' sharded paths run too."""

import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture(autouse=True)
def _cpu_compile_cache(tmp_path_factory, monkeypatch):
    """CPU executables stay out of the checkout's cache, which the
    chip's runs use."""
    from benchmark import run

    monkeypatch.setattr(run, "CACHE_DIR",
                        str(tmp_path_factory.getbasetemp() / "jax_cache"))


@pytest.fixture(autouse=True)
def _any_device(monkeypatch):
    """The harness's look for a chip is skipped: the rest of a run is
    driven on the CPU's devices."""
    import jax

    from benchmark import run

    monkeypatch.setattr(run, "require_chips", lambda chips: jax.devices())
