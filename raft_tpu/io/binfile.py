"""``.fbin`` / ``.u8bin`` / ``.i8bin`` readers and writers.

Primary path: the native C++ library (``native/io.cpp`` — mmap +
threaded reads, the ``BinFile<T>`` analog of the reference's
``bench/ann/src/common/dataset.hpp:45-145``), loaded via ctypes and
compiled on demand with the in-repo Makefile. Fallback: numpy memmap,
so the package works where no toolchain exists.
"""

from __future__ import annotations

import ctypes
import os
import pathlib
import subprocess
import threading
from typing import Optional

import numpy as np

_SUFFIX_DTYPES = {
    ".fbin": np.float32,
    ".u8bin": np.uint8,
    ".i8bin": np.int8,
    ".ibin": np.int32,   # groundtruth index files
}

_REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]
_NATIVE_DIR = _REPO_ROOT / "native"
_SO_PATH = _NATIVE_DIR / "libraft_tpu_io.so"

_lib = None
_lib_lock = threading.Lock()
_build_attempted = False


def _dtype_for(path: str):
    suffix = pathlib.Path(path).suffix
    if suffix not in _SUFFIX_DTYPES:
        raise ValueError(
            f"unknown dataset suffix {suffix!r}; expected one of "
            f"{sorted(_SUFFIX_DTYPES)}"
        )
    return np.dtype(_SUFFIX_DTYPES[suffix])


def native_stale(so_path: pathlib.Path, *sources: pathlib.Path) -> bool:
    """Missing, or older than a source that produces it (the ``.so``
    is gitignored, so a checkout may carry a stale one). Decided by
    mtime BEFORE dlopen: rebuilding after a dlopen would truncate a
    mapped file."""
    if not so_path.exists():
        return True
    so_m = so_path.stat().st_mtime
    return any(src.exists() and src.stat().st_mtime > so_m
               for src in sources)


def _load_native():
    """Load (rebuilding if missing or stale) the native IO library;
    None if impossible."""
    global _lib, _build_attempted
    with _lib_lock:
        if _lib is not None:
            return _lib
        if (native_stale(_SO_PATH, _NATIVE_DIR / "io.cpp",
                         _NATIVE_DIR / "Makefile")
                and not _build_attempted):
            _build_attempted = True
            try:
                subprocess.run(
                    ["make", "-s", _SO_PATH.name], cwd=_NATIVE_DIR,
                    check=True, capture_output=True, timeout=120,
                )
            except (OSError, subprocess.SubprocessError):
                return None
        if not _SO_PATH.exists():
            return None
        try:
            lib = ctypes.CDLL(str(_SO_PATH))
        except OSError:
            return None
        lib.rt_io_open.restype = ctypes.c_void_p
        lib.rt_io_open.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        lib.rt_io_rows.restype = ctypes.c_int64
        lib.rt_io_rows.argtypes = [ctypes.c_void_p]
        lib.rt_io_dim.restype = ctypes.c_int64
        lib.rt_io_dim.argtypes = [ctypes.c_void_p]
        lib.rt_io_last_error.restype = ctypes.c_char_p
        lib.rt_io_read_rows.restype = ctypes.c_int
        lib.rt_io_read_rows.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int,
        ]
        lib.rt_io_close.argtypes = [ctypes.c_void_p]
        lib.rt_io_create.restype = ctypes.c_void_p
        lib.rt_io_create.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ]
        lib.rt_io_append_rows.restype = ctypes.c_int
        lib.rt_io_append_rows.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
        ]
        lib.rt_io_close_writer.restype = ctypes.c_int
        lib.rt_io_close_writer.argtypes = [ctypes.c_void_p]
        lib.rt_io_pipeline_start.restype = ctypes.c_void_p
        lib.rt_io_pipeline_start.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int,
        ]
        lib.rt_io_pipeline_next.restype = ctypes.c_int
        lib.rt_io_pipeline_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ]
        lib.rt_io_pipeline_close.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def native_available() -> bool:
    return _load_native() is not None


class BinDataset:
    """Windowed access to a big-ann bin file — the ``BinFile<T>`` +
    subset view combination the reference bench uses for 100M+ row
    datasets (``dataset.hpp`` subset ctor)."""

    def __init__(self, path, *, use_native: Optional[bool] = None):
        self.path = str(path)
        self.dtype = _dtype_for(self.path)
        if use_native is None:
            use_native = native_available()
        elif use_native and not native_available():
            raise IOError(
                "use_native=True but the native IO library is unavailable "
                "(build failed or no toolchain); pass use_native=None to "
                "allow the numpy fallback"
            )
        self._native = use_native
        if self._native:
            lib = _load_native()
            handle = lib.rt_io_open(
                self.path.encode(), self.dtype.itemsize
            )
            if not handle:
                raise IOError(
                    f"native open failed: "
                    f"{lib.rt_io_last_error().decode()}"
                )
            self._handle = handle
            self.n_rows = int(lib.rt_io_rows(handle))
            self.dim = int(lib.rt_io_dim(handle))
        else:
            self._handle = None
            header = np.fromfile(self.path, np.int32, 2)
            if header.size != 2 or header[1] <= 0 or header[0] < 0:
                raise IOError(f"bad bin header in {self.path}")
            self.n_rows, self.dim = int(header[0]), int(header[1])
            expected = 8 + self.n_rows * self.dim * self.dtype.itemsize
            actual = os.path.getsize(self.path)
            if expected > actual:
                raise IOError(
                    f"truncated bin file {self.path}: header promises "
                    f"{expected} bytes, file has {actual}"
                )

    @property
    def shape(self):
        return (self.n_rows, self.dim)

    def read(self, row_start: int = 0, n_rows: Optional[int] = None,
             n_threads: int = 0) -> np.ndarray:
        """Copy rows [row_start, row_start + n_rows) into a fresh array."""
        if n_rows is None:
            n_rows = self.n_rows - row_start
        if row_start < 0 or n_rows < 0 or row_start + n_rows > self.n_rows:
            raise IndexError("read out of bounds")
        out = np.empty((n_rows, self.dim), self.dtype)
        if self._native:
            lib = _load_native()
            rc = lib.rt_io_read_rows(
                self._handle, row_start, n_rows,
                out.ctypes.data_as(ctypes.c_void_p), n_threads,
            )
            if rc != 0:
                raise IOError(lib.rt_io_last_error().decode())
        else:
            mm = np.memmap(self.path, self.dtype, mode="r", offset=8,
                           shape=(self.n_rows, self.dim))
            out[:] = mm[row_start : row_start + n_rows]
            del mm
        return out

    def iter_chunks(self, chunk_rows: int, n_threads: int = 0,
                    copy: bool = True):
        """Yield ``(first_row, array)`` chunks in order.

        On the native path a background C++ thread prefetches chunk i+1
        while chunk i is being consumed (double-buffered) — the streaming
        ingestion path for datasets far larger than memory. With
        ``copy=False`` the yielded array is a view into the prefetch
        buffer and is only valid until the next iteration (fine when the
        next step is an immediate ``jax.device_put``).
        """
        if chunk_rows <= 0:
            raise ValueError("chunk_rows must be positive")
        if not self._native:
            for start in range(0, self.n_rows, chunk_rows):
                n = min(chunk_rows, self.n_rows - start)
                yield start, self.read(start, n)
            return
        lib = _load_native()
        pipe = lib.rt_io_pipeline_start(self._handle, chunk_rows, n_threads)
        if not pipe:
            raise IOError(lib.rt_io_last_error().decode())
        try:
            data_p = ctypes.c_void_p()
            first = ctypes.c_int64()
            nrows = ctypes.c_int64()
            while True:
                rc = lib.rt_io_pipeline_next(
                    pipe, ctypes.byref(data_p), ctypes.byref(first),
                    ctypes.byref(nrows),
                )
                if rc == 1:
                    return
                if rc != 0:
                    raise IOError(lib.rt_io_last_error().decode())
                n = int(nrows.value)
                buf = (ctypes.c_char * (n * self.dim
                                        * self.dtype.itemsize)
                       ).from_address(data_p.value)
                arr = np.frombuffer(buf, self.dtype).reshape(n, self.dim)
                yield int(first.value), (arr.copy() if copy else arr)
        finally:
            lib.rt_io_pipeline_close(pipe)

    def close(self):
        if self._native and self._handle is not None:
            _load_native().rt_io_close(self._handle)
            self._handle = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def read_bin(path, row_start: int = 0, n_rows: Optional[int] = None,
             **kwargs) -> np.ndarray:
    with BinDataset(path, **kwargs) as ds:
        return ds.read(row_start, n_rows)


def write_bin(path, data: np.ndarray, *,
              use_native: Optional[bool] = None) -> None:
    """Write a (n, d) array in big-ann bin layout (dtype from suffix)."""
    data = np.ascontiguousarray(data, dtype=_dtype_for(str(path)))
    if data.ndim != 2:
        raise ValueError("write_bin expects (n, d) data")
    if use_native is None:
        use_native = native_available()
    elif use_native and not native_available():
        raise IOError(
            "use_native=True but the native IO library is unavailable "
            "(build failed or no toolchain); pass use_native=None to "
            "allow the numpy fallback"
        )
    if use_native:
        lib = _load_native()
        h = lib.rt_io_create(str(path).encode(), data.shape[0],
                             data.shape[1], data.dtype.itemsize)
        if not h:
            raise IOError(lib.rt_io_last_error().decode())
        if lib.rt_io_append_rows(
            h, data.ctypes.data_as(ctypes.c_void_p), data.shape[0]
        ) != 0:
            lib.rt_io_close_writer(h)
            raise IOError(lib.rt_io_last_error().decode())
        if lib.rt_io_close_writer(h) != 0:
            raise IOError(lib.rt_io_last_error().decode())
    else:
        with open(path, "wb") as fh:
            np.asarray(data.shape, np.int32).tofile(fh)
            data.tofile(fh)
