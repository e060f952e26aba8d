"""Byte-typed IVF-Flat lists end to end on the CPU: the sublane
rounding of the padded layout, the Pallas list scan over uint8/int8
storage (interpret mode) against the XLA engine and numpy's exact
integer distances, the list-sharded streaming build over chunks that
live on different devices of a 4-device mesh against the in-memory
build, its stage spans, and the served mesh path (``SearchExecutor`` +
``DynamicBatcher``) against the benchmark's plain exact reference."""

import glob
import hashlib
import importlib.util
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from raft_tpu import SearchExecutor
from raft_tpu.comms import Comms
from raft_tpu.core import tracing
from raft_tpu.distance.types import DistanceType
from raft_tpu.distributed import ivf as dist_ivf
from raft_tpu.neighbors.ivf_flat import IvfFlatIndexParams, IvfFlatSearchParams
from raft_tpu.serving import BatcherConfig, DynamicBatcher

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, D, N_LISTS, K = 2048, 64, 16, 10
BYTES = ("uint8", "int8")


def _load(rel: str):
    """A benchmark module by path (the plain reference and the
    comparison that decides ``correct``)."""
    spec = importlib.util.spec_from_file_location(
        rel.replace("/", "_")[:-3], os.path.join(ROOT, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _byte_data(dtype: str, n: int = N, seed: int = 0) -> np.ndarray:
    """Clustered byte rows: 16 centers plus noise, clipped to range."""
    rng = np.random.default_rng(seed)
    lo, hi = (0, 255) if dtype == "uint8" else (-128, 127)
    centers = rng.integers(lo + 30, hi - 30, (16, D))
    rows = centers[rng.integers(0, 16, n)] + rng.integers(-25, 26, (n, D))
    return np.clip(rows, lo, hi).astype(dtype)


class Sharded:
    """A corpus sharded by rows over devices, streamed as chunks that
    each live on the device holding them (the benchmark's ``Corpus``
    interface)."""

    def __init__(self, x: np.ndarray, devices):
        mesh = Mesh(np.asarray(devices), ("rows",))
        self.array = jax.device_put(x, NamedSharding(mesh, P("rows")))
        self.n_rows, self.dim = x.shape
        self.dtype = x.dtype

    def iter_chunks(self, chunk_rows: int):
        for sh in sorted(self.array.addressable_shards,
                         key=lambda s: s.index[0].start or 0):
            first = sh.index[0].start or 0
            for s in range(0, sh.data.shape[0], chunk_rows):
                yield first + s, sh.data[s:s + chunk_rows]


class Host(Sharded):
    """The same rows as host arrays (a ``BinDataset``'s chunks)."""

    def __init__(self, x: np.ndarray):
        self.x, self.array = x, None
        self.n_rows, self.dim = x.shape
        self.dtype = x.dtype

    def iter_chunks(self, chunk_rows: int):
        for s in range(0, self.n_rows, chunk_rows):
            yield s, self.x[s:s + chunk_rows].copy()


@pytest.fixture(scope="module")
def devs():
    return jax.devices()[:4]


@pytest.fixture(scope="module")
def comms(devs):
    return Comms(Mesh(np.asarray(devs), ("lists",)), "lists")


def _arrays(index):
    return {f: np.asarray(getattr(index, f)) for f in
            ("centers", "data", "data_norms", "indices", "list_sizes")}


# ---------------------------------------------------------------------------
# the padded layout
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype,multiple", [
    ("float32", 8), ("bfloat16", 16), ("uint8", 32), ("int8", 32)])
def test_padded_extent_rounds_to_the_dtypes_sublane_multiple(dtype,
                                                             multiple):
    from raft_tpu.neighbors._packing import padded_extent

    dt = jnp.dtype(dtype)
    assert padded_extent(np.array([3, 1]), dt) == multiple
    assert padded_extent(np.array([multiple + 1, 5]), dt) == 2 * multiple
    assert padded_extent(np.array([2 * multiple]), dt) == 2 * multiple


# ---------------------------------------------------------------------------
# the list scan over byte storage
# ---------------------------------------------------------------------------


def _scan_case(dtype: str, float_queries: bool = False):
    rng = np.random.default_rng(3)
    lo, hi = (0, 256) if dtype == "uint8" else (-128, 128)
    n_lists, m, q, p = 12, 40, 24, 4
    data = rng.integers(lo, hi, (n_lists, m, D)).astype(dtype)
    ids = np.arange(n_lists * m, dtype=np.int32).reshape(n_lists, m)
    ids[:, 35:] = -1                                     # padding slots
    norms = np.where(ids >= 0, (data.astype(np.int64) ** 2).sum(-1),
                     np.inf).astype(np.float32)
    queries = rng.integers(lo, hi, (q, D)).astype(np.float32)
    if float_queries:
        queries = queries + rng.standard_normal((q, D)).astype(np.float32)
    probes = np.stack([rng.choice(n_lists, p, replace=False)
                       for _ in range(q)]).astype(np.int32)
    return data, norms, ids, queries, probes


def _scan(engine, data, norms, ids, queries, probes, metric):
    from raft_tpu.ops.ivf_scan import list_major_scan

    d, i = list_major_scan(
        jnp.asarray(queries), jnp.asarray(data), jnp.asarray(norms),
        jnp.asarray(ids), jnp.asarray(probes), k=K, metric=metric,
        engine=engine, interpret=True)
    return np.asarray(d), np.asarray(i)


@pytest.mark.parametrize("dtype", BYTES)
@pytest.mark.parametrize("metric", [DistanceType.L2Expanded,
                                    DistanceType.InnerProduct])
def test_pallas_byte_scan_is_exact(dtype, metric):
    """The kernel on byte lists returns the XLA engine's ids, and its
    distances are numpy's exact integers (L2 in min-space
    ``norm - 2 x.q``, IP the raw inner product)."""
    data, norms, ids, queries, probes = _scan_case(dtype)
    dp, ip_ = _scan("pallas", data, norms, ids, queries, probes, metric)
    dx, ix = _scan("xla", data, norms, ids, queries, probes, metric)
    np.testing.assert_array_equal(ip_, ix)
    rows = data.reshape(-1, D).astype(np.int64)[ip_]      # (q, k, D)
    dot = np.einsum("qkd,qd->qk", rows, queries.astype(np.int64))
    want = (dot if metric == DistanceType.InnerProduct
            else (rows ** 2).sum(-1) - 2 * dot)
    np.testing.assert_array_equal(dp, want.astype(np.float64))
    np.testing.assert_array_equal(dx, want.astype(np.float64))


def test_float_queries_on_byte_lists_keep_the_stated_bound():
    """Float queries: the two-pass bfloat16 split keeps the inner
    product within 2**-16 sum |q||x| (plus float32 accumulation)."""
    data, norms, ids, queries, probes = _scan_case("uint8",
                                                   float_queries=True)
    dp, ip_ = _scan("pallas", data, norms, ids, queries, probes,
                    DistanceType.InnerProduct)
    rows = data.reshape(-1, D).astype(np.float64)[ip_]
    q64 = queries.astype(np.float64)
    exact = np.einsum("qkd,qd->qk", rows, q64)
    bound = np.einsum("qkd,qd->qk", rows, np.abs(q64)) * (2.0 ** -16
                                                          + 2.0 ** -20)
    assert (np.abs(dp - exact) <= bound).all()


def _resolve_on_tpu(monkeypatch, data, **kw):
    from raft_tpu.ops import ivf_scan

    monkeypatch.setattr(ivf_scan.jax, "default_backend", lambda: "tpu")
    return ivf_scan.resolve_scan_engine("pallas", data=data, vmem_mb=64,
                                        **kw)


@pytest.mark.parametrize("dtype", BYTES)
def test_byte_lists_resolve_to_the_kernel_on_tpu(monkeypatch, dtype):
    """Whole 32-row tiles of byte lists are served by the kernel on a
    TPU backend; an 8-row-aligned byte layout is not whole tiles."""
    from raft_tpu.ops.ivf_scan import _warn_degrade

    _warn_degrade.cache_clear()
    aligned = jax.ShapeDtypeStruct((8, 4160, 128), jnp.dtype(dtype))
    assert _resolve_on_tpu(monkeypatch, aligned, k=K) == "pallas"
    ragged = jax.ShapeDtypeStruct((8, 4168, 128), jnp.dtype(dtype))
    assert _resolve_on_tpu(monkeypatch, ragged, k=K) == "xla"


def test_degrade_still_warns_for_storage_the_kernel_lacks(caplog):
    """float16 and int32 lists still fall to the XLA engine, out loud,
    once per reason; byte and float lists say nothing."""
    from raft_tpu.ops.ivf_scan import _warn_degrade, resolve_scan_engine

    _warn_degrade.cache_clear()
    with caplog.at_level(logging.WARNING):
        for dtype in ("uint8", "int8", "float32", "bfloat16"):
            data = jnp.zeros((4, 32, 16), dtype)
            assert resolve_scan_engine("pallas", data=data) == "pallas"
        for dtype in ("float16", "int32", "float16"):
            data = jnp.zeros((4, 32, 16), dtype)
            assert resolve_scan_engine("pallas", data=data) == "xla"
    warned = [r.getMessage() for r in caplog.records
              if "serving it with the xla engine" in r.getMessage()]
    assert len(warned) == 2, warned
    assert any("float16 storage" in w for w in warned)
    assert any("int32 storage" in w for w in warned)


# ---------------------------------------------------------------------------
# the list-sharded streaming build
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", BYTES)
def test_streamed_bytes_equal_the_in_memory_build(comms, devs, dtype):
    """Chunks on four devices, each labelled where it lives, give the
    in-memory build's centers, lists, ids, bytes and norms."""
    x = _byte_data(dtype)
    params = IvfFlatIndexParams(n_lists=N_LISTS)
    mem = _arrays(dist_ivf.build(None, comms, params, x))
    streamed = dist_ivf.build_streaming(
        None, comms, params, Sharded(x, devs), chunk_rows=300, train_rows=N)
    got = _arrays(streamed)
    assert got["data"].dtype == np.dtype(dtype)
    assert got["data"].shape[1] % 32 == 0
    for name, want in mem.items():
        np.testing.assert_array_equal(got[name], want, err_msg=name)
    # norms: float32 sums of squares of the bytes, exact integers
    sq = (got["data"].astype(np.int64) ** 2).sum(-1).astype(np.float64)
    np.testing.assert_array_equal(
        got["data_norms"], np.where(got["indices"] >= 0, sq, np.inf))


@pytest.mark.parametrize("where", ["devices", "host"])
def test_label_pass_fetches_device_labels_after_every_dispatch(
        monkeypatch, comms, devs, where):
    """Device chunks: the predictions go round-robin over the devices
    (the source yields one device's chunks after another's) and every
    one is dispatched before any labels are fetched, so the devices
    label at once. Host chunks: one chunk's labels are fetched after
    the next chunk's prediction is dispatched. The labels are the same
    either way."""
    from raft_tpu.cluster import kmeans_balanced
    from raft_tpu.cluster.kmeans_balanced import KMeansBalancedParams
    from raft_tpu.neighbors import _streaming

    x = _byte_data("uint8", n=1200)
    source = Sharded(x, devs) if where == "devices" else Host(x)
    centers = jax.device_put(jnp.asarray(x[::150], jnp.float32),
                             comms.replicated())
    events, on = [], []
    predict, device_get = kmeans_balanced.predict, _streaming.jax.device_get

    def logged_predict(res, km, c, chunk):
        events.append("predict")
        on.append(_streaming.chunk_device(chunk))
        return predict(res, km, c, chunk)

    def logged_get(tree):
        events.append(f"fetch {len(tree)}")
        return device_get(tree)

    monkeypatch.setattr(kmeans_balanced, "predict", logged_predict)
    monkeypatch.setattr(_streaming.jax, "device_get", logged_get)
    labels, sizes = _streaming.label_pass(None, KMeansBalancedParams(),
                                          centers, source, 100, 8)
    n_chunks = 12
    if where == "devices":
        assert events == ["predict"] * n_chunks + [f"fetch {n_chunks}"]
        assert on == list(devs) * 3
    else:
        assert events == (["predict", "fetch 0"]
                          + ["predict", "fetch 1"] * (n_chunks - 1)
                          + ["fetch 1"])
    d2 = ((x[:, None, :].astype(np.int64)
           - x[::150][None].astype(np.int64)) ** 2).sum(-1)
    np.testing.assert_array_equal(labels, d2.argmin(1))
    np.testing.assert_array_equal(sizes, np.bincount(labels, minlength=8))


# SHA-1 of the float32 streaming build's arrays below, as the build
# gave them before it learned byte storage (host chunks, train_rows
# 4096 then 1000): the float32 path is unchanged bit for bit
FLOAT32_DIGEST = "8a866d9ee5b7fed3c7a6f88f3b0a0bc3b29a0a1b"


def test_float32_streaming_build_unchanged(comms, devs):
    x = np.random.default_rng(0).standard_normal((4096, 32)).astype(
        np.float32)
    digests = []
    for source in (Host(x), Sharded(x, devs)):
        h = hashlib.sha1()
        for train_rows in (4096, 1000):
            index = dist_ivf.build_streaming(
                None, comms, IvfFlatIndexParams(n_lists=N_LISTS), source,
                chunk_rows=300, train_rows=train_rows)
            for a in _arrays(index).values():
                h.update(a.tobytes())
        digests.append(h.hexdigest())
    assert digests == [FLOAT32_DIGEST, FLOAT32_DIGEST]


def _host_event_names(profile_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{profile_dir}/**/*.xplane.pb", recursive=True)
    pd = ProfileData.from_file(path)
    return {e.name for plane in pd.planes
            if plane.name.startswith("/host:CPU")
            for line in plane.lines for e in line.events}


def test_build_stages_are_spans_and_histograms(comms, devs, tmp_path):
    x = _byte_data("uint8", n=512)
    before = {s: tracing.histograms(_span(s) + "_seconds").get(
        _span(s) + "_seconds", {"count": 0})["count"]
        for s in dist_ivf.STREAM_STAGES}
    jax.profiler.start_trace(str(tmp_path))
    try:
        dist_ivf.build_streaming(None, comms, IvfFlatIndexParams(n_lists=8),
                                 Sharded(x, devs), chunk_rows=100)
    finally:
        jax.profiler.stop_trace()
    names = _host_event_names(tmp_path)
    for stage in dist_ivf.STREAM_STAGES:
        assert _span(stage) in names, stage
        h = tracing.histograms(_span(stage) + "_seconds")[
            _span(stage) + "_seconds"]
        assert h["count"] == before[stage] + 1 and h["sum"] > 0


def _span(stage: str) -> str:
    return dist_ivf.STREAM_SPAN.format(stage)


# ---------------------------------------------------------------------------
# the served mesh path
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served(comms, devs):
    x = _byte_data("uint8", seed=1)
    source = Sharded(x, devs)
    index = dist_ivf.build_streaming(
        None, comms, IvfFlatIndexParams(n_lists=N_LISTS), source,
        chunk_rows=256)
    pool = _byte_data("uint8", n=64, seed=2)
    return source.array, index, pool


def _serve(index, pool, params, blocks=2):
    """Answers for the pool through the batcher's default config, in
    ``blocks`` requests."""
    ex = SearchExecutor()
    ex.warmup(index, buckets=(32,), k=K, params=params)
    batcher = DynamicBatcher(ex, BatcherConfig())
    try:
        handles = [batcher.submit(index, b, K, params=params)
                   for b in np.array_split(pool, blocks)]
        out = [h.result(timeout=600) for h in handles]
    finally:
        batcher.close()
    return (np.concatenate([np.asarray(d) for d, _ in out]),
            np.concatenate([np.asarray(i) for _, i in out]), ex)


@pytest.mark.parametrize("engine", ["pallas", "xla"])
def test_served_full_probes_read_exact(served, engine):
    """At n_probes = n_lists every list is scanned: the served answers
    are the plain reference's, recall 1 and ``dist_err`` 0."""
    x, index, pool = served
    ref_mod, check = (_load("benchmark/references/exact_knn.py"),
                      _load("benchmark/check.py"))
    params = IvfFlatSearchParams(n_probes=N_LISTS, scan_engine=engine)
    d, i, ex = _serve(index, pool, params)
    assert {c["engine"] for c in ex.executable_costs().values()} == {engine}
    ref = ref_mod.knn(x, pool, K)
    answers = (np.arange(len(pool)), d, i, np.ones(len(pool), bool), 0)
    verdict = check.judge(x, pool, ref, answers,
                          {"dist_err": 0.0, "miss": 0.0},
                          ref_mod.true_distances)
    assert verdict["checks"]["dist_err"]["value"] == 0.0
    assert verdict["recall"] == 1.0 and verdict["correct"]


def _by_distance_then_id(d, i):
    order = np.lexsort((i, d), axis=1)
    return (np.take_along_axis(d, order, 1), np.take_along_axis(i, order, 1))


def test_served_small_probes_match_the_rank_engine(served):
    """At 3 of 16 probes the served kernel returns the rank engine's
    answers: the same distances, and the same ids once equal (integer)
    distances are ordered by id, the list-major engines' tie-break."""
    x, index, pool = served
    params = IvfFlatSearchParams(n_probes=3, scan_engine="pallas")
    d, i, _ = _serve(index, pool, params, blocks=3)
    dr, ir = dist_ivf.search(None, IvfFlatSearchParams(
        n_probes=3, scan_engine="rank"), index, pool, K)
    np.testing.assert_array_equal(d, np.asarray(dr))
    for a, b in zip(_by_distance_then_id(d, i),
                    _by_distance_then_id(np.asarray(dr), np.asarray(ir))):
        np.testing.assert_array_equal(a, b)


def test_wire_bytes_counter_per_sharded_dispatch(served):
    """Each sharded dispatch adds its entry's modeled coarse + merge
    bytes; the executable, its digest and the compile count stay as
    they were, and nothing compiles in the steady state."""
    _, index, pool = served
    params = IvfFlatSearchParams(n_probes=4)
    ex = SearchExecutor()
    ex.warmup(index, buckets=(32,), k=K, params=params)
    (digest, info), = ex.executable_costs().items()
    model = info["collective_payload"]
    per = float(model["coarse_bytes"] + model["merge_bytes"])
    assert per > 0
    ex.search_blocks(index, [pool[:20]], K, params=params)   # prime
    compiles = ex.stats.compile_count
    tracing.install_xla_compile_listener()
    xla = tracing.get_counter(tracing.XLA_COMPILE_COUNT)
    wire = tracing.get_counter("serving.mesh.wire_bytes")
    for n in (20, 31, 17):
        ex.search_blocks(index, [pool[:n]], K, params=params)
    assert tracing.get_counter("serving.mesh.wire_bytes") - wire == 3 * per
    assert list(ex.executable_costs()) == [digest]
    assert ex.stats.compile_count == compiles
    assert tracing.get_counter(tracing.XLA_COMPILE_COUNT) == xla
