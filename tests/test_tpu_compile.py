"""Compile the served path's Pallas kernels for a described TPU v5e.

Interpret mode (every other kernel test) runs the kernels' numerics on
the CPU but never meets Mosaic, so a block shape or VMEM budget the
TPU compiler refuses passes there and fails only on the chip. These
tests hand each kernel real-width shapes on a described — not attached
— v5e device and compile it; a refusal raises here at no chip cost.
Each must leave a ``tpu_custom_call`` in the compiled module (the
kernel really is in the program).

Geometry: SIFT-1M (1,000,000 x 128 f32) — brute force over the whole
corpus, and the IVF families at ``n_lists=1024`` whose padded lists
hold 1536 rows, probed by a 16-query bucket at 64 probes.

The topology is described inside a module fixture, never at import:
only one process may load libtpu, and xdist workers all import this
file (on-chip-measurement guide §2).
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from raft_tpu.core.chips import CHIPS
from raft_tpu.distance.types import DistanceType

N, D, K = 1_000_000, 128, 10
N_LISTS, M, Q, PROBES = 1024, 1536, 16, 64
VMEM_MB = CHIPS["TPU v5 lite"].vmem_budget_mb


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """``sds(shape, dtype, memory_kind=None)`` on one described chip."""
    from jax.sharding import SingleDeviceSharding

    dev = topo.devices[0]
    assert dev.device_kind in CHIPS

    def make(shape, dtype, memory_kind=None):
        return jax.ShapeDtypeStruct(
            shape, dtype,
            sharding=SingleDeviceSharding(dev, memory_kind=memory_kind))
    return make


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_knn(sds, dtype):
    from raft_tpu.ops.fused_topk import fused_knn

    fn = functools.partial(fused_knn, k=K, metric=DistanceType.L2Expanded,
                           vmem_mb=VMEM_MB)
    text = _compile_text(
        lambda q, x, xn: fn(q, x, dataset_norms=xn),
        sds((Q, D), dtype), sds((N, D), dtype), sds((N,), jnp.float32))
    assert "tpu_custom_call" in text


def test_select_k_tiles(sds):
    from raft_tpu.ops.fused_topk import _select_k_tiles_impl

    text = _compile_text(
        functools.partial(_select_k_tiles_impl, k=K, vmem_mb=VMEM_MB),
        sds((Q, N), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.uint8])
def test_ivf_scan(sds, dtype):
    from raft_tpu.ops.ivf_scan import _scan_pallas

    fn = functools.partial(_scan_pallas, filter_words=None, k=K,
                           metric=DistanceType.L2Expanded,
                           interpret=False, vmem_mb=VMEM_MB)
    text = _compile_text(
        fn, sds((Q, D), jnp.float32), sds((N_LISTS, M, D), dtype),
        sds((N_LISTS, M), jnp.float32), sds((N_LISTS, M), jnp.int32),
        sds((Q, PROBES), jnp.int32))
    assert "tpu_custom_call" in text


def test_bq_scan(sds):
    from raft_tpu.ops.bq_scan import _bq_scan_pallas

    bits, dim_ext = 1, D
    words = bits * dim_ext // 32
    fn = functools.partial(_bq_scan_pallas, filter_words=None, k=K,
                           metric=DistanceType.L2Expanded, epsilon=1.9,
                           query_bits=4, interpret=False, vmem_mb=VMEM_MB)
    f32, i32 = jnp.float32, jnp.int32
    text = _compile_text(
        fn, sds((Q, D), f32), sds((Q, dim_ext), f32),
        sds((N_LISTS, dim_ext), f32), sds((N_LISTS, M, words), i32),
        sds((N_LISTS, M), f32), sds((N_LISTS, M, bits), f32),
        sds((N_LISTS, M), f32), sds((N_LISTS, M), i32),
        sds((N_LISTS, M, D), f32), sds((N_LISTS, M), f32),
        sds((Q, PROBES), i32))
    assert "tpu_custom_call" in text


def test_tier_scan(sds):
    from raft_tpu.ops.tier_scan import _tier_scan_pallas

    n_hot = N_LISTS // 2
    fn = functools.partial(_tier_scan_pallas, filter_words=None, k=K,
                           metric=DistanceType.L2Expanded,
                           interpret=False, vmem_mb=VMEM_MB)
    f32, i32 = jnp.float32, jnp.int32
    text = _compile_text(
        fn, sds((Q, D), f32), sds((n_hot, M, D), f32),
        sds((N_LISTS - n_hot, M, D), f32),
        sds((N_LISTS,), i32), sds((N_LISTS,), i32),
        sds((N_LISTS, M), f32), sds((N_LISTS, M), i32),
        sds((Q, PROBES), i32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", [100_000, N])
def test_beam_search(sds, n):
    from raft_tpu.ops.beam_search import beam_search

    deg, itopk, width = 32, 64, 4
    fn = functools.partial(beam_search, k=K, L=itopk, w=width,
                           max_iters=32, metric=DistanceType.L2Expanded,
                           interpret=False, vmem_mb=VMEM_MB)
    text = _compile_text(
        fn, sds((Q, D), jnp.float32), sds((n, D), jnp.float32),
        sds((n, deg), jnp.int32), sds((Q, width * deg), jnp.int32))
    assert "tpu_custom_call" in text
