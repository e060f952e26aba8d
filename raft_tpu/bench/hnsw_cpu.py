"""CPU HNSW competitor baseline — the role of the reference's hnswlib
wrapper (``cpp/bench/ann/src/hnswlib/hnswlib_wrapper.h:1``): the
benchmark harness's non-RAFT comparison point on the recall-vs-QPS
pareto plot (``docs/source/raft_ann_benchmarks.md:229``).

This environment has no hnswlib, so the baseline is a from-scratch
C++17 HNSW (``native/hnsw.cpp``, Malkov & Yashunin arXiv:1603.09320)
loaded via ctypes — a real graph-search competitor measured on the
same host the way the reference measures hnswlib on CPU.
"""

from __future__ import annotations

import ctypes
import pathlib
import subprocess
import threading

import numpy as np

from raft_tpu.distance.types import DistanceType
from raft_tpu.io.binfile import native_stale

_REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
_NATIVE_DIR = _REPO_ROOT / "native"
_SO_PATH = _NATIVE_DIR / "libraft_tpu_hnsw.so"

_lib = None
_lib_lock = threading.Lock()
_build_attempted = False

_METRIC_CODES = {
    DistanceType.L2Expanded: 0,
    DistanceType.L2SqrtExpanded: 0,   # same graph; sqrt applied on top
    DistanceType.L2Unexpanded: 0,
    DistanceType.InnerProduct: 1,
}


def _load():
    global _lib, _build_attempted
    with _lib_lock:
        if _lib is not None:
            return _lib
        if (native_stale(_SO_PATH, _NATIVE_DIR / "hnsw.cpp",
                         _NATIVE_DIR / "Makefile")
                and not _build_attempted):
            _build_attempted = True
            try:
                subprocess.run(["make", "-s"], cwd=_NATIVE_DIR, check=True,
                               capture_output=True, timeout=300)
            except (OSError, subprocess.SubprocessError):
                pass  # an existing (possibly stale) .so may still do
        if not _SO_PATH.exists():
            return None
        try:
            lib = ctypes.CDLL(str(_SO_PATH))
        except OSError:
            return None
        # a stale prebuilt .so (toolchain missing, make failed) must
        # degrade to available() == False, not AttributeError out of
        # every caller that relies on it to skip the baseline
        for sym in ("hnsw_create", "hnsw_add", "hnsw_size", "hnsw_dim",
                    "hnsw_metric", "hnsw_search", "hnsw_save",
                    "hnsw_load", "hnsw_free", "hnsw_last_error"):
            if not hasattr(lib, sym):
                return None
        lib.hnsw_create.restype = ctypes.c_void_p
        lib.hnsw_create.argtypes = [ctypes.c_int64, ctypes.c_int64,
                                    ctypes.c_int64, ctypes.c_int,
                                    ctypes.c_uint64]
        lib.hnsw_add.restype = ctypes.c_int
        lib.hnsw_add.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                 ctypes.c_int64]
        lib.hnsw_size.restype = ctypes.c_int64
        lib.hnsw_size.argtypes = [ctypes.c_void_p]
        lib.hnsw_dim.restype = ctypes.c_int64
        lib.hnsw_dim.argtypes = [ctypes.c_void_p]
        lib.hnsw_metric.restype = ctypes.c_int
        lib.hnsw_metric.argtypes = [ctypes.c_void_p]
        lib.hnsw_search.restype = ctypes.c_int
        lib.hnsw_search.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                    ctypes.c_int64, ctypes.c_int64,
                                    ctypes.c_int64, ctypes.c_void_p,
                                    ctypes.c_void_p]
        lib.hnsw_save.restype = ctypes.c_int
        lib.hnsw_save.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.hnsw_load.restype = ctypes.c_void_p
        lib.hnsw_load.argtypes = [ctypes.c_char_p]
        lib.hnsw_free.argtypes = [ctypes.c_void_p]
        lib.hnsw_last_error.restype = ctypes.c_char_p
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def _err(lib) -> str:
    return lib.hnsw_last_error().decode(errors="replace")


class HnswCpuIndex:
    """Owns the native handle; frees it on GC."""

    def __init__(self, handle, dim: int, metric: DistanceType):
        self._h = handle
        self._free = _load().hnsw_free  # bound now: _load() and module
        self.dim = dim                  # globals may be gone at GC time
        self.metric = metric

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            try:
                self._free(h)
            except TypeError:  # interpreter teardown already unloaded it
                pass
            self._h = None


def build(base, metric: DistanceType, *, M: int = 16,
          ef_construction: int = 200, seed: int = 0) -> HnswCpuIndex:
    """Insert every base row (single-threaded, like a 1-thread hnswlib
    build). ``base`` must be float32 (n, dim)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native HNSW library unavailable (g++/make "
                           "missing?); cannot run the CPU baseline")
    base = np.ascontiguousarray(base, np.float32)
    n, dim = base.shape
    code = _METRIC_CODES.get(metric)
    if code is None:
        raise ValueError(f"hnsw_cpu: unsupported metric {metric}")
    h = lib.hnsw_create(dim, M, ef_construction, code, seed)
    if not h:
        raise RuntimeError(f"hnsw_create failed: {_err(lib)}")
    if lib.hnsw_add(h, base.ctypes.data_as(ctypes.c_void_p), n) != 0:
        lib.hnsw_free(h)
        raise RuntimeError(f"hnsw_add failed: {_err(lib)}")
    return HnswCpuIndex(h, dim, metric)


def search(index: HnswCpuIndex, queries, k: int, *, ef: int = 64):
    """(q, k) distances + ids. L2 metrics return squared L2 (sqrt for
    L2SqrtExpanded); InnerProduct returns the (positive) similarity."""
    lib = _load()
    queries = np.ascontiguousarray(queries, np.float32)
    q = queries.shape[0]
    if queries.ndim != 2 or queries.shape[1] != index.dim:
        raise ValueError("queries must be (q, dim)")
    out_d = np.empty((q, k), np.float32)
    out_i = np.empty((q, k), np.int64)
    rc = lib.hnsw_search(index._h,
                         queries.ctypes.data_as(ctypes.c_void_p), q, k,
                         max(ef, k),
                         out_d.ctypes.data_as(ctypes.c_void_p),
                         out_i.ctypes.data_as(ctypes.c_void_p))
    if rc != 0:
        raise RuntimeError(f"hnsw_search failed: {_err(lib)}")
    if index.metric == DistanceType.L2SqrtExpanded:
        out_d = np.sqrt(np.maximum(out_d, 0.0))
    elif index.metric == DistanceType.InnerProduct:
        out_d = -out_d  # native stores min-form
    return out_d, out_i.astype(np.int32)


def save(index: HnswCpuIndex, path: str) -> None:
    lib = _load()
    if lib.hnsw_save(index._h, str(path).encode()) != 0:
        raise RuntimeError(f"hnsw_save failed: {_err(lib)}")


def load(path: str, dim: int, metric: DistanceType) -> HnswCpuIndex:
    lib = _load()
    if lib is None:
        raise RuntimeError("native HNSW library unavailable")
    h = lib.hnsw_load(str(path).encode())
    if not h:
        raise RuntimeError(f"hnsw_load failed: {_err(lib)}")
    # cross-check the file's recorded geometry/metric against the
    # caller's: search() validates queries against the caller-supplied
    # dim while the native side strides by the FILE's dim, so accepting
    # a mismatched cache (stale, hand-placed, name collision) would read
    # past the query buffer or score under the wrong metric
    stored_dim = lib.hnsw_dim(h)
    stored_metric = lib.hnsw_metric(h)
    want_metric = _METRIC_CODES.get(metric)
    if stored_dim != dim or stored_metric != want_metric:
        lib.hnsw_free(h)
        raise RuntimeError(
            f"hnsw_load: cache {path} holds dim={stored_dim} "
            f"metric_code={stored_metric}, caller expects dim={dim} "
            f"metric_code={want_metric} ({metric.name}) — stale or "
            f"mismatched cache file")
    return HnswCpuIndex(h, dim, metric)
