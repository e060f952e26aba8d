"""Resources handle — the TPU-native analog of ``raft::resources``.

The reference threads a type-indexed lazy resource container through every
API (``core/resources.hpp:47``) whose CUDA specialization
(``core/device_resources.hpp:61``) carries stream, stream pool, cuBLAS /
cuSOLVER handles, comms and a workspace allocator. On TPU almost all of
that is owned by XLA: there are no user-visible streams, no BLAS handles,
and memory is managed by the runtime. What genuinely remains shared state
across algorithm calls is:

- the **device / mesh** an algorithm should target (replaces device id +
  comms clique; multi-chip sharding is expressed with ``jax.sharding.Mesh``)
- a **PRNG key stream** (replaces ``rngState_t`` seeds threaded by hand)
- **tunables**: default matmul precision, batch/tile sizes, VMEM budget
  hints for Pallas kernels
- an injected **comms** object for multi-process runs (SURVEY.md §2.6)

``Resources`` is deliberately cheap, immutable-ish, and never traced: it is
host-side configuration, passed as the first argument of every public
function exactly like the reference's ``resources const&``.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from typing import Any, Optional, Sequence

import jax
import numpy as np


def _default_device() -> jax.Device:
    return jax.devices()[0]


# the one in-checkout cache location (gitignored): a fixed path, since
# the path is part of the cache key — a moving directory never hits
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def init_compile_cache() -> str:
    """Turn on XLA's persistent compilation cache for this process and
    return its directory — the ONE helper every entry point calls
    before its first compile, so a restarted process's warmup loads
    executables instead of compiling them.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache (jax reads
    it itself; nothing here overrides it). Otherwise the cache lives
    at :data:`COMPILE_CACHE_DIR` inside the checkout. Every executable
    is persisted, however quick its compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


@dataclasses.dataclass
class Resources:
    """Shared execution context threaded through every raft_tpu call.

    Analog of ``raft::resources`` / ``raft::device_resources``
    (reference ``core/device_resources.hpp:61-237``): where the reference
    hands out streams and vendor-library handles, this hands out devices,
    meshes, PRNG keys and kernel tunables.

    Attributes:
      device: preferred device for single-chip execution. ``None`` means
        JAX default placement.
      mesh: optional ``jax.sharding.Mesh`` for multi-chip algorithms; the
        analog of the comms clique injected into the reference handle
        (``core/device_resources.hpp:214`` ``get_comms``).
      seed: base seed for the handle-owned PRNG stream.
      matmul_precision: default ``jax.lax`` precision for distance GEMMs
        ("default" | "float32" | "bfloat16" | "highest"...).
      workspace_limit_bytes: soft budget that batching heuristics use when
        deciding tile sizes (analog of the workspace memory resource,
        ``core/device_resources.hpp`` workspace accessors).

    The persistent compilation cache is process-wide, not per handle:
    entry points turn it on with :func:`init_compile_cache`.
    """

    device: Optional[jax.Device] = None
    mesh: Optional[jax.sharding.Mesh] = None
    seed: int = 0
    matmul_precision: str = "highest"
    workspace_limit_bytes: int = 2 * 1024**3
    comms: Optional[Any] = None

    def __post_init__(self):
        self._lock = threading.Lock()
        self._key = jax.random.key(self.seed)
        self._subcomms: dict[str, Any] = {}

    # -- PRNG ---------------------------------------------------------------
    def next_key(self, n: Optional[int] = None):
        """Split and return fresh PRNG key(s) from the handle-owned stream.

        Replaces the reference pattern of threading ``random::RngState``
        (``random/rng_state.hpp:38``) through algorithms by hand.
        """
        with self._lock:
            if n is None:
                self._key, out = jax.random.split(self._key)
            else:
                keys = jax.random.split(self._key, n + 1)
                self._key, out = keys[0], keys[1:]
        return out

    # -- placement ----------------------------------------------------------
    def put(self, x, sharding: Optional[jax.sharding.Sharding] = None):
        """Place an array on this handle's device (or an explicit sharding)."""
        if sharding is not None:
            return jax.device_put(x, sharding)
        if self.device is not None:
            return jax.device_put(x, self.device)
        return jax.device_put(x)

    @property
    def default_device(self) -> jax.Device:
        return self.device if self.device is not None else _default_device()

    # -- comms (multi-process / multi-chip) ----------------------------------
    def get_comms(self):
        """Return the injected comms object (analog of
        ``resource::get_comms``, ``core/device_resources.hpp:214``)."""
        if self.comms is None:
            raise RuntimeError(
                "no comms injected into Resources; construct raft_tpu.comms."
                "Comms and pass it via Resources(comms=...)"
            )
        return self.comms

    def set_subcomm(self, key: str, comm) -> None:
        """Register a sub-communicator (analog of ``resource::set_subcomm``,
        ``core/resource/sub_comms.hpp``)."""
        self._subcomms[key] = comm

    def get_subcomm(self, key: str):
        return self._subcomms[key]

    # -- sync ---------------------------------------------------------------
    def sync(self, *arrays) -> None:
        """Block until given arrays (or all pending work) are ready.

        Analog of ``device_resources::sync_stream``
        (``core/device_resources.hpp:137-201``); XLA dispatch is async the
        same way CUDA streams are.
        """
        if arrays:
            for a in arrays:
                jax.block_until_ready(a)
        else:
            # effectively a fence: a trivial transfer on the target device
            jax.block_until_ready(jax.device_put(np.zeros(()), self.default_device))


# Legacy-flavored alias, mirroring ``raft::handle_t`` == device_resources
# (reference ``core/handle.hpp``).
DeviceResources = Resources


class ResourcesManager:
    """Process-wide per-device pool of ``Resources`` handles — the analog
    of ``raft::device_resources_manager`` (``core/
    device_resources_manager.hpp:49-154``), which hands multi-threaded
    servers a shared, pre-configured handle per GPU.

    Defaults set via ``set_*`` before first use apply to every handle the
    manager creates (mirroring the reference's set-then-freeze params);
    later calls simply return the cached handle.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._handles: dict[Optional[int], Resources] = {}
        self._defaults: dict[str, Any] = {}

    def set_seed(self, seed: int) -> None:
        self._defaults["seed"] = seed

    def set_matmul_precision(self, precision: str) -> None:
        self._defaults["matmul_precision"] = precision

    def set_workspace_limit_bytes(self, n: int) -> None:
        self._defaults["workspace_limit_bytes"] = n

    def get_device_resources(
        self, device: "Optional[jax.Device | int]" = None
    ) -> Resources:
        """The shared handle for ``device`` (an int id, a device object, or
        None for default placement) — ``get_device_resources()``."""
        if isinstance(device, int):
            device = jax.devices()[device]
        key = None if device is None else device.id
        with self._lock:
            if key not in self._handles:
                self._handles[key] = Resources(device=device,
                                               **self._defaults)
            return self._handles[key]


resources_manager = ResourcesManager()


def get_default_resources() -> Resources:
    """Process-wide default handle: callers that do not care about
    placement share one lazily-created ``Resources``."""
    return resources_manager.get_device_resources(None)


def ensure_resources(res: Optional[Resources]) -> Resources:
    return res if res is not None else get_default_resources()


def make_local_mesh(
    axis_names: Sequence[str] = ("data",),
    shape: Optional[Sequence[int]] = None,
) -> jax.sharding.Mesh:
    """Build a mesh over all local devices.

    Convenience for tests and single-host multi-chip runs; the analog of
    raft-dask's one-process-per-GPU clique bootstrap collapsed to a single
    call (reference ``raft_dask/common/comms.py:39-250``).
    """
    devs = jax.devices()
    if shape is None:
        shape = (len(devs),) + (1,) * (len(axis_names) - 1)
    arr = np.array(devs).reshape(tuple(shape))
    return jax.sharding.Mesh(arr, tuple(axis_names))
