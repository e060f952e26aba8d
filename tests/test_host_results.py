"""The serving entry's results on the host: ``search_blocks`` fetches a
call's outputs in one batch and cuts each block's rows in numpy. Every
block's result is a numpy array bit-identical to the library entry
``search()`` of that block alone — for a stateless plan and one whose
outputs alias donated state, short of the bucket and filling it, tiled
past the top bucket, and on a mesh through the batcher."""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from raft_tpu import SearchExecutor
from raft_tpu.comms import Comms
from raft_tpu.core import tracing
from raft_tpu.distributed import ivf as dist_ivf
from raft_tpu.neighbors import ivf_flat
from raft_tpu.serving import BatcherConfig, DynamicBatcher, metrics

K = 5
DIM = 16


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(24)
    x = rng.standard_normal((512, DIM)).astype(np.float32)
    q = rng.standard_normal((48, DIM)).astype(np.float32)
    return x, q


@pytest.fixture(scope="module")
def index(data):
    return ivf_flat.build(None, ivf_flat.IvfFlatIndexParams(n_lists=8),
                          data[0])


def _assert_solo(ex, index, blocks, got, params):
    """Each block's host result equals ``search()`` of the block alone."""
    assert len(got) == len(blocks)
    for b, (d, i) in zip(blocks, got):
        assert isinstance(d, np.ndarray) and isinstance(i, np.ndarray)
        want_d, want_i = ex.search(index, b, K, params=params)
        np.testing.assert_array_equal(i, np.asarray(want_i))
        np.testing.assert_array_equal(d, np.asarray(want_d))


# (scan engine, donate): the Pallas scan keeps no state; the XLA scan's
# outputs alias its donated top-k state when donation is on, and are
# then fetched before the executor lock releases
PLANS = [("pallas", False), ("xla", True), ("xla", False)]


@pytest.mark.parametrize("engine,donate", PLANS)
class TestBucketed:
    def test_short_and_full_bucket(self, index, data, engine, donate):
        _, q = data
        params = ivf_flat.IvfFlatSearchParams(n_probes=4,
                                              scan_engine=engine)
        ex = SearchExecutor(donate=donate, max_bucket=16)
        for sizes in ([2, 3], [3, 1, 4], [16]):     # 5 of 8, 8, 16
            edges = np.cumsum([0] + sizes)
            blocks = [q[a:b] for a, b in zip(edges[:-1], edges[1:])]
            metrics.reset()
            got = ex.search_blocks(index, blocks, K, params=params)
            assert tracing.get_counter(metrics.EAGER_PROGRAMS) == 0
            _assert_solo(ex, index, blocks, got, params)

    def test_back_to_back_dispatches_of_one_plan(self, index, data,
                                                 engine, donate):
        """The second dispatch re-donates the first's output buffers
        (one executable, one state): the first results, already on the
        host, stay as they were."""
        _, q = data
        params = ivf_flat.IvfFlatSearchParams(n_probes=4,
                                              scan_engine=engine)
        ex = SearchExecutor(donate=donate, max_bucket=16)
        first_blocks = [q[:3], q[3:6]]
        first = ex.search_blocks(index, first_blocks, K, params=params)
        kept = [(d.copy(), i.copy()) for d, i in first]
        second_blocks = [q[6:8], q[8:12]]
        second = ex.search_blocks(index, second_blocks, K, params=params)
        for (d, i), (kd, ki) in zip(first, kept):
            np.testing.assert_array_equal(d, kd)
            np.testing.assert_array_equal(i, ki)
        _assert_solo(ex, index, first_blocks, first, params)
        _assert_solo(ex, index, second_blocks, second, params)

    def test_oversized_batch_tiles_at_the_top_bucket(self, index, data,
                                                     engine, donate):
        """40 rows over a top bucket of 16: three tiles of one
        executable, joined and cut on the host."""
        _, q = data
        params = ivf_flat.IvfFlatSearchParams(n_probes=4,
                                              scan_engine=engine)
        ex = SearchExecutor(donate=donate, max_bucket=16)
        blocks = [q[:7], q[7:20], q[20:21], q[21:40]]
        metrics.reset()
        got = ex.search_blocks(index, blocks, K, params=params)
        assert tracing.get_counter(metrics.EAGER_PROGRAMS) == 0
        assert tracing.get_counter("serving.execute.calls") == 3
        _assert_solo(ex, index, blocks, got, params)


def test_empty_blocks_come_back_empty_on_the_host(index, data):
    ex = SearchExecutor()
    got = ex.search_blocks(index, [data[1][:0], data[1][:0]], K)
    for d, i in got:
        assert isinstance(d, np.ndarray) and d.shape == (0, K)
        assert i.dtype == np.int32 and i.shape == (0, K)


def test_mesh_plan_through_the_batcher(data):
    """A list-sharded index on a 4-device mesh: requests coalesced by
    the batcher come back as host arrays equal to the direct search."""
    x, q = data
    comms = Comms(Mesh(np.array(jax.devices()[:4]), ("data",)), "data")
    dist = dist_ivf.build(None, comms,
                          ivf_flat.IvfFlatIndexParams(n_lists=8), x)
    params = ivf_flat.IvfFlatSearchParams(n_probes=4)
    ex = SearchExecutor(max_bucket=16)
    ex.warmup(dist, buckets=(8, 16), k=K, params=params)
    b = DynamicBatcher(ex, BatcherConfig(max_wait_s=0.05))
    try:
        blocks = [q[j:j + m] for j, m in ((0, 1), (1, 3), (4, 2), (6, 5))]
        metrics.reset()
        handles = [b.submit(dist, blk, K, params=params) for blk in blocks]
        got = [h.result(timeout=120) for h in handles]
    finally:
        b.close()
    assert tracing.get_counter(metrics.EAGER_PROGRAMS) == 0
    _assert_solo(ex, dist, blocks, got, params)
