"""Core runtime tests (analog of reference cpp/test/core/)."""

import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import Resources
from raft_tpu.core import (
    Bitset,
    deserialize_array,
    deserialize_scalar,
    serialize_array,
    serialize_scalar,
)
from raft_tpu.core import interruptible
from raft_tpu.core.serialize import check_version
from raft_tpu.core.validation import RaftError, check_matrix, expect


class TestResources:
    def test_next_key_unique(self):
        res = Resources(seed=1)
        k1, k2 = res.next_key(), res.next_key()
        assert not np.array_equal(jax.random.key_data(k1), jax.random.key_data(k2))

    def test_next_key_batch(self):
        res = Resources(seed=1)
        keys = res.next_key(4)
        assert keys.shape[0] == 4

    def test_reproducible(self):
        a = Resources(seed=7).next_key()
        b = Resources(seed=7).next_key()
        assert np.array_equal(jax.random.key_data(a), jax.random.key_data(b))

    def test_sync(self):
        res = Resources()
        x = jnp.ones((8,))
        res.sync(x)
        res.sync()

    def test_subcomm(self):
        res = Resources()
        res.set_subcomm("row", "fake")
        assert res.get_subcomm("row") == "fake"


class TestSerialize:
    def test_array_roundtrip(self, rng_np):
        buf = io.BytesIO()
        arr = rng_np.standard_normal((5, 3)).astype(np.float32)
        serialize_array(buf, jnp.asarray(arr))
        buf.seek(0)
        out = deserialize_array(buf)
        np.testing.assert_array_equal(out, arr)

    @pytest.mark.parametrize("dtype_name", ["bfloat16", "float8_e4m3fn"])
    def test_extension_dtype_roundtrip(self, rng_np, dtype_name):
        """ml_dtypes arrays (bf16 datasets, fp8) have no .npy descr —
        they ride as a marker record + uint view and come back typed."""
        dtype = getattr(jnp, dtype_name)
        buf = io.BytesIO()
        arr = jnp.asarray(rng_np.standard_normal((6, 4)), dtype)
        serialize_array(buf, arr)
        buf.seek(0)
        out = deserialize_array(buf)
        assert out.dtype == np.dtype(dtype_name)
        np.testing.assert_array_equal(out, np.asarray(arr))

    def test_bf16_brute_force_index_roundtrip(self, rng_np):
        """The end-to-end case that was broken: a bf16-storage index
        must save/load (previously died with 'Dtype |V2')."""
        from raft_tpu.neighbors import brute_force

        x = rng_np.standard_normal((64, 16)).astype(np.float32)
        idx = brute_force.build(None, x, storage_dtype=jnp.bfloat16)
        buf = io.BytesIO()
        brute_force.save(idx, buf)
        buf.seek(0)
        idx2 = brute_force.load(None, buf)
        assert idx2.dataset.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(idx2.dataset),
                                      np.asarray(idx.dataset))

    def test_scalar_roundtrip(self):
        buf = io.BytesIO()
        serialize_scalar(buf, 42, np.int64)
        serialize_scalar(buf, 2.5, np.float32)
        buf.seek(0)
        assert deserialize_scalar(buf) == 42
        assert deserialize_scalar(buf) == np.float32(2.5)

    def test_stream_of_records(self, rng_np):
        buf = io.BytesIO()
        serialize_scalar(buf, 4, np.int32)  # version
        a = rng_np.random((4, 4)).astype(np.float32)
        serialize_array(buf, a)
        buf.seek(0)
        assert deserialize_scalar(buf) == 4
        np.testing.assert_array_equal(deserialize_array(buf), a)

    def test_check_version(self):
        check_version(3, 3, "x")
        with pytest.raises(ValueError):
            check_version(2, 3, "x")


class TestBitset:
    def test_default_all_set(self):
        bs = Bitset.create(70)
        assert int(bs.count()) == 70
        assert bool(bs.test(69))

    def test_from_mask_roundtrip(self, rng_np):
        mask = rng_np.random(100) < 0.5
        bs = Bitset.from_mask(mask)
        np.testing.assert_array_equal(np.asarray(bs.to_mask()), mask)
        assert int(bs.count()) == mask.sum()

    def test_vectorized_test(self, rng_np):
        mask = rng_np.random(64) < 0.5
        bs = Bitset.from_mask(mask)
        idx = jnp.array([0, 5, 63])
        np.testing.assert_array_equal(np.asarray(bs.test(idx)), mask[[0, 5, 63]])

    def test_set_flip(self):
        bs = Bitset.create(40, default=False)
        bs = bs.set(jnp.array([1, 3]))
        assert int(bs.count()) == 2
        flipped = bs.flip()
        assert int(flipped.count()) == 38

    def test_jit_through(self):
        bs = Bitset.from_mask(jnp.array([True, False, True]))

        @jax.jit
        def f(b):
            return b.count()

        assert int(f(bs)) == 2


class TestValidation:
    def test_expect(self):
        expect(True, "ok")
        with pytest.raises(RaftError):
            expect(False, "bad")

    def test_check_matrix(self):
        check_matrix(jnp.ones((3, 4)), cols=4)
        with pytest.raises(RaftError):
            check_matrix(jnp.ones((3,)))


class TestInterruptible:
    def test_yield_no_flag(self):
        interruptible.yield_()  # no-op

    def test_cancel_then_yield(self):
        interruptible.cancel()
        with pytest.raises(interruptible.InterruptedException):
            interruptible.yield_()
        interruptible.yield_()  # flag cleared

    def test_synchronize(self):
        interruptible.synchronize(jnp.ones((4,)))


class TestOperators:
    """core/operators.hpp functor vocabulary."""

    def test_basic_ops(self):
        import jax.numpy as jnp

        from raft_tpu.core import operators as op

        x = jnp.float32(-3.0)
        assert float(op.sq_op(x)) == 9.0
        assert float(op.abs_op(x)) == 3.0
        assert float(op.nz_op(jnp.float32(0.0))) == 0.0
        assert float(op.compose_op(op.sqrt_op, op.sq_op)(x)) == 3.0
        assert float(op.div_checkzero_op(jnp.float32(4), jnp.float32(0))) == 0
        assert float(op.plug_const_op(2.0, op.pow_op)(jnp.float32(3))) == 9.0
        assert op.key_op((1, 2.5)) == 1 and op.value_op((1, 2.5)) == 2.5
        add3 = op.map_args_op(op.add_op, op.sq_op, op.identity_op)
        assert float(add3(jnp.float32(2), jnp.float32(1))) == 5.0


class TestSpatialAlias:
    def test_deprecated_forwarding(self):
        import warnings

        import numpy as np

        from raft_tpu.spatial import knn as spatial_knn

        x = np.random.default_rng(0).standard_normal((50, 8)).astype(np.float32)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            d, i = spatial_knn.brute_force_knn(None, x, x[:4], 3)
            assert any(issubclass(x.category, DeprecationWarning) for x in w)
        assert np.asarray(i)[:, 0].tolist() == [0, 1, 2, 3]


class TestTracingCapture:
    def test_capture_writes_trace(self, tmp_path):
        import jax.numpy as jnp

        from raft_tpu.core import tracing

        with tracing.capture(str(tmp_path)):
            with tracing.range("test.block"):
                jnp.square(jnp.arange(16.0)).block_until_ready()
        # a plugins/profile dir with at least one artifact appears
        found = list(tmp_path.rglob("*"))
        assert any(p.is_file() for p in found), found


class TestInterop:
    """Array-interop parity with pylibraft.common's cai/ai wrappers
    (``common/cai_wrapper.py:21,43``): any ``__array_interface__`` /
    dlpack producer — numpy, torch (cpu) — is accepted by the public
    APIs without copies being forced on the caller."""

    def test_torch_tensor_inputs(self):
        torch = pytest.importorskip("torch")
        import numpy as np

        from raft_tpu.neighbors import brute_force

        t = torch.randn(64, 8, dtype=torch.float32)
        q = t[:4]
        d, i = brute_force.knn(None, t, q, 3)
        assert np.asarray(i)[:, 0].tolist() == [0, 1, 2, 3]

    def test_numpy_and_jax_mixed(self):
        import numpy as np
        import jax.numpy as jnp

        from raft_tpu.distance import pairwise_distance

        x = np.random.default_rng(0).standard_normal((8, 4)).astype(np.float32)
        out = pairwise_distance(None, x, jnp.asarray(x))
        assert np.allclose(np.asarray(out).diagonal(), 0.0, atol=1e-4)


class TestChipTable:
    """One per-device_kind table: a TPU it does not know is an error,
    never a default; off the TPU only the interpret budget applies."""

    def test_unknown_tpu_kind_raises(self):
        import types

        from raft_tpu.core.chips import chip_spec

        dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
        with pytest.raises(ValueError, match="unknown TPU device_kind"):
            chip_spec(dev)
        v5e = types.SimpleNamespace(platform="tpu",
                                    device_kind="TPU v5 lite")
        assert chip_spec(v5e).hbm_bytes_per_s == 819e9
        with pytest.raises(ValueError, match="platform"):
            chip_spec(jax.devices()[0])

    def test_vmem_budget_off_tpu(self, monkeypatch):
        from raft_tpu.core.chips import INTERPRET_VMEM_MB, vmem_budget_mb

        monkeypatch.delenv("RAFT_TPU_VMEM_MB", raising=False)
        assert vmem_budget_mb() == INTERPRET_VMEM_MB
        monkeypatch.setenv("RAFT_TPU_VMEM_MB", "8")
        assert vmem_budget_mb() == 8
