"""The corpus laid out over the cell's chips, in the configuration's
dtype, and the reference run where the rows are, on four CPU devices.

- a byte corpus (uint8, int8) is the same bytes on one device and on
  four, and is never whole on one device; a float32 corpus keeps the
  bytes it had before the harness could lay it out (digests pinned from
  that version of ``benchmark/data.py``) and is the same on four;
- the sharded reference is exact against a NumPy int64 brute force;
- on bytes the bfloat16 control reads ``dist_err`` 0, and the control
  one bit coarser fails the limit;
- a four-chip uint8 cell made of fixture files alone runs through
  ``run.main`` with a mesh index and reads ``correct: true``.
"""

import hashlib
import json
import os

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding

from benchmark import calibrate, check, data, run, spec

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures")
FIX_BENCH = os.path.join(FIXTURES, "BENCHMARK.json")
DIRS = (FIXTURES, spec.BENCH_DIR)
reference = spec.load_module((spec.BENCH_DIR,), "references", "exact_knn")

# sha256 of the corpus and of the pool, made by benchmark/data.py as it
# was before the byte and sharded paths (one call on the default device)
CORPUS_SHA = "394ceddfcab05dcd514aadf8b979bf31ab85ca8c2af91514a900bfb00818e0db"
POOL_SHA = {
    3000000001: "fcccb96422e0a1b26b31e972af09ee1913786f069b799f372edaa9474a0b3b25",
    77: "9dca524ffe5bd2e3f0852c9d136276151429f6b8fb7dbc389fa2e39ee9710d2f",
    2**40 + 7: "a1a07cf4f427b680d0bf22ff4c9e2bb35bf0bf494758874523796fde944f93a3",
}

BYTES = {"uint8": (40.0, 128.0), "int8": (40.0, 0.0)}


def _sha(a) -> str:
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()


def _conf(name):
    with open(os.path.join(FIXTURES, "configs", name + ".json")) as fh:
        return json.load(fh)


def _bytes_ds(dtype, scale=None, offset=None, n=4096, dim=32):
    s, o = BYTES[dtype]
    return dict(n=n, dim=dim, dtype=dtype, n_clusters=16, intrinsic_dim=8,
                center_scale=1.0, noise=0.05, data_seed=2**33 + 3,
                byte_scale=s if scale is None else scale,
                byte_offset=o if offset is None else offset)


def test_four_devices():
    assert len(jax.devices()) >= 4


@pytest.mark.parametrize("config,seed", [
    ("tiny_ivf", 3000000001), ("tiny_bf", 77), ("tiny_ivf", 2**40 + 7)])
def test_float32_corpus_and_pool_unchanged(config, seed):
    corpus, pool = data.for_dataset(_conf(config)["dataset"], seed, 200)
    assert corpus.dtype == np.float32
    assert _sha(corpus.array) == CORPUS_SHA
    assert _sha(pool) == POOL_SHA[seed]


def test_float32_corpus_same_over_four_devices():
    ds = _conf("tiny_ivf")["dataset"]
    corpus, pool = data.for_dataset(ds, 77, 200, jax.devices()[:4])
    assert _sha(corpus.array) == CORPUS_SHA
    assert _sha(pool) == POOL_SHA[77]
    assert len({s.device for s in corpus.array.addressable_shards}) == 4


@pytest.mark.parametrize("block", [data.BLOCK_ROWS, 384])
@pytest.mark.parametrize("dtype", ["uint8", "int8"])
def test_byte_corpus_same_over_one_and_four_devices(monkeypatch, dtype,
                                                    block):
    monkeypatch.setattr(data, "BLOCK_ROWS", block)
    ds = _bytes_ds(dtype)
    one, q1 = data.for_dataset(ds, 2**40 + 11, 100, jax.devices()[:1])
    four, q4 = data.for_dataset(ds, 2**40 + 11, 100, jax.devices()[:4])
    assert one.dtype == four.dtype == np.dtype(dtype)
    np.testing.assert_array_equal(np.asarray(one.array),
                                  np.asarray(four.array))
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q4))
    assert np.asarray(q4).dtype == np.dtype(dtype)
    # never whole on one device: a row-sharded array, a quarter a device
    sh = four.array.sharding
    assert isinstance(sh, NamedSharding) and sh.spec[0] == data.AXIS
    shards = four.array.addressable_shards
    assert len({s.device for s in shards}) == 4
    assert all(s.data.shape == (1024, 32) for s in shards)
    x = np.asarray(one.array).astype(np.int64)
    lo, hi = data.BYTE_RANGES[dtype]
    assert lo <= x.min() and x.max() <= hi and x.std() > 10


def test_byte_rows_depend_only_on_seed_and_row():
    """A bigger corpus from the same data seed starts with the same
    rows: a row is a function of its number, not of the layout."""
    small, _ = data.for_dataset(_bytes_ds("uint8", n=1024), 1, 10)
    big, _ = data.for_dataset(_bytes_ds("uint8", n=4096), 1, 10,
                              jax.devices()[:4])
    np.testing.assert_array_equal(np.asarray(small.array),
                                  np.asarray(big.array)[:1024])


def test_iter_chunks_lie_on_their_chip():
    corpus, _ = data.for_dataset(_bytes_ds("uint8"), 3, 10,
                                 jax.devices()[:4])
    assert (corpus.n_rows, corpus.dim) == (4096, 32)
    owner = {}
    for s in corpus.array.addressable_shards:
        owner[s.index[0].start or 0] = s.device
    seen, parts = 0, []
    for first, rows in corpus.iter_chunks(300):
        assert first == seen
        (dev,) = rows.devices()
        start = max(f for f in owner if f <= first)
        assert dev == owner[start]
        assert first + rows.shape[0] <= start + 1024   # one chip each
        seen += rows.shape[0]
        parts.append(np.asarray(rows))
    assert seen == 4096
    np.testing.assert_array_equal(np.concatenate(parts),
                                  np.asarray(corpus.array))


def test_layout_refusals():
    with pytest.raises(ValueError, match="divide"):
        data.for_dataset(_bytes_ds("uint8", n=4098), 1, 10,
                         jax.devices()[:4])
    with pytest.raises(ValueError, match="dtype"):
        data.for_dataset(dict(_bytes_ds("uint8"), dtype="float16"), 1, 10)


def _brute_int64(x, q, k):
    xh, qh = np.asarray(x, np.int64), np.asarray(q, np.int64)
    full = ((qh[:, None, :] - xh[None]) ** 2).sum(-1)
    ids = np.argsort(full, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(full, ids, axis=1), ids


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("dtype,scale", [("uint8", None), ("int8", None),
                                         ("uint8", 0.5)])
def test_sharded_reference_exact_against_numpy_int64(dtype, scale, chips):
    """``scale`` 0.5 puts the rows on a few byte levels: exact ties far
    past the shortlist, which both break by the smaller id."""
    corpus, q = data.for_dataset(_bytes_ds(dtype, scale), 2**35 + 1, 120,
                                 jax.devices()[:chips])
    d, i = reference.knn(corpus.array, q, 10, q_block=64)
    want_d, want_i = _brute_int64(corpus.array, q, 10)
    np.testing.assert_array_equal(i, want_i)
    np.testing.assert_array_equal(d, want_d.astype(np.float64))
    ids = want_i[:, ::3]
    np.testing.assert_array_equal(
        reference.true_distances(corpus.array, np.asarray(q), ids),
        np.take_along_axis(want_d, np.arange(0, 10, 3)[None], axis=1))


def _judge(x, q, ref, answers, limits):
    p = len(q)
    out = check.judge(x, np.asarray(q), ref,
                      (np.arange(p), answers[0], answers[1],
                       np.ones(p, bool), 0), limits,
                      reference.true_distances)
    return {n: c["value"] for n, c in out["checks"].items()}, out["correct"]


@pytest.mark.parametrize("dtype", ["uint8", "int8"])
@pytest.mark.parametrize("seed", [7, 2**40 + 9, 3000000011])
def test_byte_controls(dtype, seed):
    """bfloat16 holds every byte, so the float control reads 0 on bytes
    and cannot bound a limit; the control one bit coarser fails it."""
    corpus, q = data.for_dataset(_bytes_ds(dtype, dim=128), seed, 200,
                                 jax.devices()[:4])
    x = corpus.array
    limits = _conf("tiny_u8_mesh")["limits"]
    ref = reference.knn(x, q, 10)
    bf16 = [(d.astype(np.float32), i.astype(np.int32))
            for _, d, i in reference._merged(x, q, 10, "bf16", 1024)][0]
    got, ok = _judge(x, q, ref, bf16, limits)
    assert got["dist_err"] == 0 and ok
    got, ok = _judge(x, q, ref, reference.control(x, q, 10), limits)
    assert got["dist_err"] > limits["dist_err"] and not ok


def test_calibrate_reads_reference_and_control_on_a_mesh_cell(capsys):
    """The rehearsal's readings, at a fixture size: the reference's own
    answers read 0, the control fails, each phase with four peaks."""
    cell = spec.Cell("tiny_u8_mesh.bulk", FIX_BENCH, DIRS)
    cell.reference = reference                 # the sharded one
    out = calibrate.control_reading(cell, 2**40 + 13, jax.devices()[:4])
    assert out["reference"]["dist_err"] == 0
    assert out["reference"]["miss"] == 0
    assert out["control"]["dist_err"] > cell.conf["limits"]["dist_err"]
    err = capsys.readouterr().err
    for phase in ("data", "reference", "control", "judge_control"):
        line = [ln for ln in err.splitlines()
                if ln.startswith(f"phase {phase} ")]
        assert line and line[0].endswith("]")
        assert len(json.loads(line[0].split("peak_bytes ")[1])) == 4


def test_four_chip_uint8_cell_through_run_main(capsys, monkeypatch):
    made = []
    real = data.for_dataset

    def spy(*a, **kw):
        made.append(real(*a, **kw))
        return made[-1]

    monkeypatch.setattr(data, "for_dataset", spy)
    rc = run.main(["--workload", "tiny_u8_mesh.bulk", "--seed", "3000000001",
                   "--seconds", "0.5", "--trace", "0"],
                  bench_path=FIX_BENCH, dirs=DIRS)
    out = capsys.readouterr()
    res = json.loads(out.out.strip().splitlines()[-1])
    assert rc == 0
    assert res["correct"] is True, out.err
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["dist_err"]["value"] == 0
    corpus = made[0][0]
    assert corpus.dtype == np.uint8 and corpus.n_rows == 4096
    shards = corpus.array.addressable_shards
    assert len({s.device for s in shards}) == 4
    assert all(s.data.shape[0] == 1024 for s in shards)
    assert "16 lists" in out.err and "over 4 chips" in out.err
    peaks = [ln for ln in out.err.splitlines()
             if ln.startswith("memory peak_bytes by chip ")]
    assert len(json.loads(peaks[0].split("chip ")[1])) == 4
