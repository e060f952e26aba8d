"""graftledger — the memory-truth plane (PR 13).

Every plane so far answered "what is the service *doing*" (spans,
probes, recall, device time); none answered "what does it *hold*".
The ROADMAP's tiered-storage direction needs exactly that signal:
hot/cold placement is traffic (graftgauge's probe planes) **times
bytes**, and today bytes exist only as compile-time
``memory_analysis()`` numbers per executable — the serving plane
cannot say "does this index fit?", "how close is this replica to
OOM?", or "which replica has headroom for the hot tier?" without
crashing a device to find out. This module is the byte accounting the
TPU-KNN roofline methodology (PAPERS.md) presumes and the
distributed-linalg paper's binding constraint (per-host footprint at
mesh scale) makes operational:

- **Resident-bytes model** (:func:`index_memory_model`) — a pure
  host-side model of one index's device-resident arrays: codes,
  packed sign words, correction scalars, the optional rerank plane,
  centroids — every array field of the (frozen-dataclass) index,
  byte-exact against ``arr.nbytes`` by construction
  (``prod(shape) * itemsize``; the tier-1 suite pins this per family).
  Mesh-sharded indexes model **per shard** through the arrays' own
  shardings (``sharding.shard_shape`` — host-side, no device sync).
- **Live backend truth** (:func:`device_memory_stats`) —
  ``device.memory_stats()`` (bytes_in_use / peak / limit) per local
  device, with an honest ``supported: False`` fallback on backends
  that don't expose it (CPU): the model keeps working, the live
  column reads absent rather than fake.
- **Reservation forecast** (:meth:`MemoryLedger.forecast`) — resident
  indexes + the executor's probe planes + the max compile-time
  ``temp_bytes`` over its cached executables (any dispatch may be the
  one that peaks) → a per-device modeled peak. The divergence gauge
  (live in-use minus modeled resident) is the fragmentation/
  untracked-allocation signal — when it grows, the model is missing
  something real.
- **Capacity planning** (:meth:`MemoryLedger.fits`, :func:`admit`) —
  "would N more bytes fit?" answered host-side, and an **opt-in**
  typed :class:`CapacityExceeded` gate on the index build/extend
  paths (:func:`install_gate`) so admission fails in Python BEFORE a
  device OOM takes the replica down. Without an installed gate every
  build/extend admits exactly as before — the gate is a deployment
  decision, not a default.
- **Watermark sampling at dispatch**
  (:meth:`MemoryLedger.sample_dispatch`) — the executor folds a
  live high-water mark per dispatch. ``memory_stats()`` is a
  host-only backend call (no device sync, nothing traced): the
  zero-recompile and bit-identity regressions run with the ledger
  fully enabled and stay green (tested, single-chip and mesh). On
  unsupported backends the sample degrades to the heartbeat counter
  (``memory.samples`` — the CI snapshot floor) and the modeled
  watermark.

Gauges (published by :meth:`MemoryLedger.publish`, scrape-time):

- ``memory.index.<label>.resident_bytes`` (+ ``.shard_bytes`` on the
  mesh) — per watched index; rendered labeled
  (``memory_index_resident_bytes{index="..."}``)
- ``memory.device.<ordinal>.{in_use_bytes,peak_bytes,limit_bytes}``
  — live truth per device (only when supported); rendered labeled
  (``memory_device_in_use_bytes{device="0"}``)
- ``memory.resident.total_bytes`` / ``memory.reserved.
  {probe_planes,max_temp}_bytes`` — the forecast's modeled terms
- ``memory.forecast.peak_bytes`` — max per-device modeled peak
- ``memory.hbm.headroom_bytes`` — live headroom (min over devices of
  limit − in_use); −1 when unknowable (no live stats, no configured
  capacity)
- ``memory.divergence_bytes`` — live in-use total minus modeled
  total (fragmentation / untracked allocations); only when live is
  supported
- ``memory.live.supported`` — 1/0
- ``memory.watermark.{in_use,forecast}_peak_bytes`` — dispatch-time
  high-water marks
- ``memory.samples`` / ``memory.gate.{admitted,refused}`` —
  lifetime counters (``memory.samples`` is the snapshot-floor
  heartbeat: watermark sampling staying wired into dispatch)

Host-sync discipline (graftlint R5 — this module is IN scope, like
``core/executor.py``): everything here is shape/dtype arithmetic and
backend introspection; nothing fetches a device array. Clock
discipline (R7 — also in scope): the ledger keeps no timestamps at
all; if one is ever needed it must come from an injected clock.
"""

from __future__ import annotations

import dataclasses
import math
import re
import threading
import weakref
from typing import Any, Dict, Optional

import jax
import numpy as np

from raft_tpu.core import tracing
from raft_tpu.core.validation import expect

SAMPLES = "memory.samples"
GATE_ADMITTED = "memory.gate.admitted"
GATE_REFUSED = "memory.gate.refused"

# gauge labels must stay ONE dot-delimited segment of the registry
# name so the exporter's labeled-family regexes can lift them into
# {index="..."} labels (same contract as graftgauge's probe labels)
_LABEL_SUB = re.compile(r"[^A-Za-z0-9_:-]").sub


class CapacityExceeded(RuntimeError):
    """Typed admission failure of the capacity gate: the planned
    allocation does not fit the device's remaining headroom. Raised
    HOST-SIDE, before any device allocation happens — the caller gets
    a catchable Python error instead of a backend OOM abort. Carries
    the numbers the refusal was computed from."""

    def __init__(self, what: str, required_bytes: int,
                 headroom_bytes: float):
        self.what = what
        self.required_bytes = int(required_bytes)
        self.headroom_bytes = float(headroom_bytes)
        super().__init__(
            f"{what}: planned allocation of {self.required_bytes} bytes "
            f"exceeds the remaining device headroom of "
            f"{int(self.headroom_bytes)} bytes (graftledger capacity "
            "gate — see raft_tpu.core.memwatch.install_gate)")


def _is_array(v: Any) -> bool:
    """Device/host arrays only — the index dataclasses also carry
    enums, bools and the mesh ``comms`` handle."""
    return hasattr(v, "shape") and hasattr(v, "dtype") \
        and not isinstance(v, (int, float, bool))


def array_bytes(a) -> int:
    """GLOBAL byte size of one array from shape × itemsize — pure
    host metadata, byte-exact against ``a.nbytes`` for the dense
    layouts every index family uses (pinned per family in tier-1)."""
    shape = tuple(a.shape)
    return int(math.prod(shape)) * int(a.dtype.itemsize)


def shard_bytes(a) -> int:
    """PER-DEVICE byte size: the array's own sharding says what one
    device actually holds (``shard_shape`` is host-side metadata —
    no placement query touches the device). Unsharded / replicated
    arrays resolve to their full size."""
    sharding = getattr(a, "sharding", None)
    if sharding is None:
        return array_bytes(a)
    try:
        shape = sharding.shard_shape(tuple(a.shape))
    except Exception:  # noqa: BLE001 — unknown sharding kinds fall back honest
        return array_bytes(a)
    return int(math.prod(shape)) * int(a.dtype.itemsize)


def per_device_bytes(a, acc: Optional[Dict[int, int]] = None
                     ) -> Dict[int, int]:
    """Fold one array's per-device residency into ``acc`` (ordinal →
    bytes): each device in the array's sharding holds one shard
    (replicated shardings hold the full array on every device). The
    forecast sums these maps across every resident array so the peak
    is per-DEVICE — the unit a device OOM is measured in."""
    acc = {} if acc is None else acc
    sb = shard_bytes(a)
    sharding = getattr(a, "sharding", None)
    devices = getattr(sharding, "device_set", None)
    if not devices:
        acc[0] = acc.get(0, 0) + array_bytes(a)
        return acc
    for d in devices:
        o = int(d.id)
        acc[o] = acc.get(o, 0) + sb
    return acc


# memory kinds that mean "this array's bytes live in HOST memory, not
# HBM" — the grafttier cold plane's placement (an array committed via
# jax.device_put(..., memory_kind="pinned_host")). Plain numpy arrays
# are host-side by construction.
_HOST_MEMORY_KINDS = ("pinned_host", "unpinned_host")


def memory_tier(a) -> str:
    """Which memory an array's bytes occupy: ``"host"`` for numpy
    arrays and device arrays committed OFF their device's default
    memory into a host kind (the grafttier cold tier), ``"device"``
    otherwise. Pure metadata — reads the array's own sharding, never
    the backend.

    A host memory KIND alone does not mean off-device: the CPU
    backend's default memory is itself ``unpinned_host`` (host and
    device are one pool there), so classification compares against
    the device's DEFAULT memory — only an array deliberately moved
    off it counts as host-tier."""
    if isinstance(a, np.ndarray):
        return "host"
    sharding = getattr(a, "sharding", None)
    kind = getattr(sharding, "memory_kind", None)
    if kind not in _HOST_MEMORY_KINDS:
        return "device"
    for d in getattr(sharding, "device_set", None) or ():
        try:
            if d.default_memory().kind == kind:
                return "device"
        except Exception:  # noqa: BLE001 — no memories API: kind decides
            break
    return "host"


def index_memory_model(index) -> dict:
    """The resident-bytes model of one index: per-component (array
    field) global and per-shard bytes, plus the totals. Works for
    every frozen-dataclass index family — single-chip and mesh-
    sharded (``shard_bytes`` reads each array's own sharding) — and
    skips optional fields that are ``None`` (a codes-only BQ index
    has no rerank plane, and models exactly that much smaller).

    Components whose bytes live in HOST memory (:func:`memory_tier` —
    the grafttier cold plane, numpy mirrors) fold into
    ``host_resident_bytes`` INSTEAD of the device totals: the device
    forecast, headroom arithmetic and divergence gauge must never
    count bytes that were deliberately moved off-HBM, while the host
    tier still shows up as its own accountable number."""
    expect(dataclasses.is_dataclass(index),
           f"index_memory_model needs an index dataclass, got "
           f"{type(index)!r}")
    components: dict = {}
    total = 0
    shard_total = 0
    host_total = 0
    per_device: Dict[int, int] = {}
    for f in dataclasses.fields(index):
        v = getattr(index, f.name, None)
        if v is None or not _is_array(v):
            continue
        b = array_bytes(v)
        tier = memory_tier(v)
        components[f.name] = {
            "bytes": b,
            "shard_bytes": shard_bytes(v),
            "shape": [int(s) for s in v.shape],
            "dtype": str(v.dtype),
            "tier": tier,
        }
        if tier == "host":
            host_total += b
            continue
        total += b
        shard_total += components[f.name]["shard_bytes"]
        per_device_bytes(v, per_device)
    return {
        "family": type(index).__name__,
        "components": components,
        "resident_bytes": total,
        "shard_resident_bytes": shard_total,
        "host_resident_bytes": host_total,
        "per_device_bytes": per_device,
    }


def packed_layout_bytes(n_lists: int, max_list_size: int,
                        row_bytes: int, *,
                        norms: bool = True,
                        indices: bool = True) -> int:
    """Planned bytes of one padded ``(n_lists, max_list_size, ...)``
    list layout BEFORE it is allocated — the number the build/extend
    capacity gate admits against. ``row_bytes`` is the per-slot
    payload (``dim * itemsize`` for flat data, ``pq_dim`` code bytes,
    packed-word + correction bytes for BQ); ``norms``/``indices`` add
    the f32 norm and int32 id planes most layouts carry."""
    slots = int(n_lists) * int(max_list_size)
    b = slots * int(row_bytes)
    if norms:
        b += slots * 4
    if indices:
        b += slots * 4
    return b


def dealt_shard_bytes(arrays, r: int) -> int:
    """Per-shard bytes of dealing these build-device tensors across
    ``r`` shards — the slot model the DISTRIBUTED build staging
    admits against (each mesh device receives ``ceil(rows / r)``
    list blocks of every dealt tensor; headroom is per-device, so
    per-shard bytes is the unit the gate must judge in). Pure shape
    arithmetic, computed BEFORE ``place_dealt`` moves anything."""
    total = 0
    for a in arrays:
        if a is None or not _is_array(a):
            continue
        rows = -(-int(a.shape[0]) // max(int(r), 1))
        rest = int(math.prod(tuple(a.shape)[1:]))
        total += rows * rest * int(a.dtype.itemsize)
    return total


def device_memory_stats(devices=None) -> dict:
    """Live backend truth: ``device.memory_stats()`` per local
    device. Returns ``{"supported": bool, "devices": {ordinal:
    {"in_use_bytes", "peak_bytes", "limit_bytes"}}}`` — a backend
    that exposes no stats (CPU) yields ``supported: False`` with an
    empty device map, never invented numbers. A host-only backend
    call: nothing is dispatched, nothing synced."""
    if devices is None:
        devices = jax.local_devices()
    out: Dict[str, Any] = {"supported": False, "devices": {}}
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 — introspection must never raise out
            stats = None
        if not stats:
            continue
        out["supported"] = True
        out["devices"][int(d.id)] = {
            "in_use_bytes": float(stats.get("bytes_in_use", 0)),
            "peak_bytes": float(stats.get("peak_bytes_in_use",
                                          stats.get("bytes_in_use", 0))),
            "limit_bytes": float(stats.get("bytes_limit", 0)),
        }
    return out


class MemoryLedger:
    """The memory-truth plane of one serving process.

    ``executor`` (optional) wires the two dispatch-path touchpoints:
    the executor calls :meth:`sample_dispatch` after every dispatch
    (host-only watermark fold), and the forecast reads the executor's
    probe-plane / compile-time-temp reservations
    through :meth:`~raft_tpu.core.executor.SearchExecutor
    .memory_reservations`. ``capacity_bytes`` is an explicit
    per-device capacity for backends without live ``memory_stats``
    (CPU tests, or an operator pinning a budget below the physical
    limit); live limits win when present.

    Example::

        ledger = MemoryLedger(executor=ex)
        ledger.watch("sift-flat", index)
        memwatch.install_gate(ledger)        # opt-in build/extend gate
        exp = MetricsExporter(executor=ex, memory=ledger)
        # /memory.json + memory_* families now serve the byte truth

    Thread-safety: one lock guards the watch map and watermarks;
    every read path (snapshot/publish/forecast) recomputes from live
    metadata — the ledger caches nothing an extend could invalidate.
    """

    def __init__(self, executor=None, *,
                 capacity_bytes: Optional[float] = None):
        self.capacity_bytes = capacity_bytes
        self._lock = threading.Lock()
        # label -> weakref(index): a dropped index must not be held
        # resident by its own accounting (mirrors the executor's
        # probe-plane death watch)
        self._watched: "dict[str, weakref.ref]" = {}  # guarded-by: _lock
        # memory_stats support is probed once: on unsupported
        # backends the per-dispatch sample degrades to the heartbeat
        # counter instead of paying a doomed backend call per dispatch
        self._live_supported: Optional[bool] = None
        # the per-dispatch sample runs inside the executor's locked
        # dispatch core: cache the device list once so the hot path
        # never re-enumerates backends, only reads their stats
        self._devices = None
        self._wm_in_use = 0.0     # guarded-by: _lock
        self._wm_forecast = 0.0   # guarded-by: _lock
        # named byte holds (graftcast prefetch and friends): bytes a
        # background channel has claimed but serving must still see
        # as spoken for — headroom subtracts them, so an admission
        # racing a prefetch can never both win the same bytes
        self._reservations: Dict[str, int] = {}  # guarded-by: _lock
        # the last snapshot publish() produced (the flight recorder's
        # low-headroom trigger reads it instead of recomputing the
        # whole truth the same scrape just published)
        self.last_snapshot: Optional[dict] = None
        self.executor = None
        if executor is not None:
            self.attach(executor)

    # -- wiring -------------------------------------------------------------

    def attach(self, executor) -> "MemoryLedger":
        """Wire ``executor`` both ways: its dispatches sample the
        watermark, the forecast reads its reservations."""
        self.executor = executor
        if hasattr(executor, "attach_memwatch"):
            executor.attach_memwatch(self)
        return self

    def watch(self, label: str, index) -> str:
        """Register ``index`` under ``label`` (sanitized to one
        dot-free gauge segment; returned). Re-watching a label
        replaces it — the rebuild/extend pattern."""
        label = _LABEL_SUB("-", str(label)) or "index"
        with self._lock:
            self._watched[label] = weakref.ref(index)
        return label

    def unwatch(self, label: str) -> None:
        with self._lock:
            self._watched.pop(label, None)
        tracing.reset_gauges(f"memory.index.{label}.")

    def _watched_models_locked(self) -> dict:
        out = {}
        dead = []
        for label, ref in self._watched.items():
            index = ref()
            if index is None:
                dead.append(label)
                continue
            out[label] = index_memory_model(index)
        for label in dead:
            self._watched.pop(label, None)
        return out

    # -- model + forecast ---------------------------------------------------

    def resident(self) -> dict:
        """``{label: index_memory_model(index)}`` for every watched
        index still alive — pure metadata, no device touch."""
        with self._lock:
            return self._watched_models_locked()

    def live(self) -> dict:
        """:func:`device_memory_stats`, support-probed once."""
        stats = device_memory_stats()
        self._live_supported = stats["supported"]
        return stats

    def forecast(self, models: Optional[dict] = None) -> dict:
        """The reservation forecast: watched resident bytes + the
        executor's probe planes + its max compile-time temp, folded
        per device; ``peak_bytes`` is the
        worst device's modeled peak (the unit an OOM happens in).
        ``models`` lets a caller that already walked the watched
        indexes (:meth:`snapshot` does) skip a second walk."""
        if models is None:
            with self._lock:
                models = self._watched_models_locked()
        per_device: Dict[int, float] = {}
        resident_total = 0
        for model in models.values():
            resident_total += model["resident_bytes"]
            for o, b in model["per_device_bytes"].items():
                per_device[o] = per_device.get(o, 0.0) + b
        probe = temp = 0.0
        if self.executor is not None and hasattr(
                self.executor, "memory_reservations"):
            res = self.executor.memory_reservations()
            probe = float(sum(res["probe_plane_bytes"].values()))
            temp = float(res["max_temp_bytes"])
            for o, b in res["probe_plane_bytes"].items():
                per_device[o] = per_device.get(o, 0.0) + b
            # any dispatch may be the one that peaks: the max temp
            # reserves on EVERY device holding state (or device 0
            # when nothing is resident yet)
            for o in list(per_device) or [0]:
                per_device[o] = per_device.get(o, 0.0) + temp
        peak = max(per_device.values()) if per_device else 0.0
        return {
            "resident_bytes": float(resident_total),
            "probe_plane_bytes": probe,
            "max_temp_bytes": temp,
            "per_device_bytes": {int(o): float(b)
                                 for o, b in per_device.items()},
            "peak_bytes": float(peak),
        }

    def _headroom_from(self, stats: dict,
                       fc: Optional[dict]) -> Optional[float]:
        """Headroom from already-computed inputs (``fc`` may be a
        thunkable None when live stats decide) — shared by the public
        :meth:`headroom_bytes` and :meth:`snapshot` so one scrape
        never re-reads the backend or re-walks the model for the same
        answer. Named holds (:meth:`reserve`) subtract LAST: reserved
        bytes are spoken for whichever source measured the room."""
        base: Optional[float] = None
        if stats["supported"] and stats["devices"]:
            rooms = [d["limit_bytes"] - d["in_use_bytes"]
                     for d in stats["devices"].values()
                     if d["limit_bytes"] > 0]
            if rooms:
                base = float(min(rooms))
        if base is None and self.capacity_bytes is not None:
            if fc is None:
                fc = self.forecast()
            base = float(self.capacity_bytes - fc["peak_bytes"])
        if base is None:
            return None
        return base - self.reserved_bytes()

    def headroom_bytes(self) -> Optional[float]:
        """Remaining per-device headroom: min over devices of
        ``limit − in_use`` from live stats; with no live support,
        ``capacity_bytes − forecast peak`` when a capacity was
        configured; ``None`` when genuinely unknowable (the gate then
        admits — refusing on ignorance would break every CPU test)."""
        return self._headroom_from(self.live(), None)

    # -- capacity planning --------------------------------------------------

    def fits(self, what, *, safety_fraction: float = 0.0) -> dict:
        """Capacity-planner verdict for ``what`` — an index (modeled
        through :func:`index_memory_model`; mesh indexes ask per
        shard), an index model dict, or a plain byte count. Returns
        ``{"fits", "required_bytes", "headroom_bytes", "unknown"}``;
        ``unknown: True`` (and ``fits: True``) when no headroom source
        exists — the honest answer, distinguishable from a measured
        yes. ``safety_fraction`` reserves that share of the headroom
        (0.1 = keep 10% free)."""
        if isinstance(what, (int, float)):
            required = int(what)
        elif isinstance(what, dict):
            required = int(what.get("shard_resident_bytes",
                                    what.get("resident_bytes", 0)))
        else:
            model = index_memory_model(what)
            required = int(model["shard_resident_bytes"])
        headroom = self.headroom_bytes()
        if headroom is None:
            return {"fits": True, "unknown": True,
                    "required_bytes": required, "headroom_bytes": None}
        usable = headroom * (1.0 - safety_fraction)
        return {"fits": required <= usable, "unknown": False,
                "required_bytes": required,
                "headroom_bytes": float(headroom)}

    def admit(self, nbytes: int, what: str) -> None:
        """Gate one planned allocation: raise :class:`CapacityExceeded`
        when ``nbytes`` exceeds the current headroom (known-headroom
        case only — see :meth:`fits`). Counts every decision
        (``memory.gate.admitted`` / ``.refused``)."""
        verdict = self.fits(nbytes)
        if not verdict["fits"]:
            tracing.inc_counter(GATE_REFUSED)
            raise CapacityExceeded(what, nbytes,
                                   verdict["headroom_bytes"])
        tracing.inc_counter(GATE_ADMITTED)

    # -- named reservations (graftcast prefetch) ----------------------------

    def reserve(self, what: str, nbytes: int) -> None:
        """Set the named hold ``what`` to ``nbytes``: the bytes a
        background channel (the tier prefetcher's staged miss cache)
        has claimed ahead of placement. Held bytes subtract from
        every subsequent :meth:`headroom_bytes` read, so a build /
        extend / sibling-prefetch admission racing this channel can
        never be granted the same bytes — a prefetch can never OOM
        what serving needs. GROWING a hold passes through the
        capacity gate (:class:`CapacityExceeded` on refusal, decision
        counted like :meth:`admit`; the prior hold is kept);
        shrinking — including to 0 — is always admissible."""
        nbytes = int(nbytes)
        expect(nbytes >= 0, "a reservation cannot hold negative bytes")
        with self._lock:
            prev = int(self._reservations.pop(what, 0))
            if nbytes <= prev:
                if nbytes > 0:
                    self._reservations[what] = nbytes
                return
        # growth: judged against headroom WITHOUT the prior hold
        # (popped above) — the gate prices the full new hold, not
        # the delta on top of bytes it already refused once
        verdict = self.fits(nbytes)
        if not verdict["fits"]:
            with self._lock:
                if prev > 0:
                    self._reservations[what] = prev
            tracing.inc_counter(GATE_REFUSED)
            raise CapacityExceeded(what, nbytes,
                                   verdict["headroom_bytes"])
        tracing.inc_counter(GATE_ADMITTED)
        with self._lock:
            self._reservations[what] = nbytes

    def release(self, what: str) -> None:
        """Drop the named hold entirely (idempotent)."""
        with self._lock:
            self._reservations.pop(what, None)

    def reserved_bytes(self) -> float:
        """Total bytes across all named holds."""
        with self._lock:
            return float(sum(self._reservations.values()))

    # -- dispatch-time watermark --------------------------------------------

    def sample_dispatch(self) -> None:
        """One watermark sample, called by the executor after each
        dispatch. Host-only: a backend ``memory_stats()`` read (never
        a device sync — nothing here enters or waits on the compiled
        program; the zero-recompile and bit-identity regressions run
        with this enabled). On unsupported backends (probed ONCE) it
        degrades to the heartbeat counter — the CI snapshot floor
        that proves sampling stayed wired into dispatch."""
        tracing.inc_counter(SAMPLES)
        if self._live_supported is False:
            return
        if self._devices is None:
            self._devices = jax.local_devices()
        stats = device_memory_stats(self._devices)
        if self._live_supported is None:
            self._live_supported = stats["supported"]
        if not stats["supported"]:
            return
        in_use = sum(d["in_use_bytes"]
                     for d in stats["devices"].values())
        with self._lock:
            self._wm_in_use = max(self._wm_in_use, in_use)

    # -- scrape surface -----------------------------------------------------

    def snapshot(self) -> dict:
        """The ``/memory.json`` body: model, live truth, forecast,
        headroom, divergence, watermarks — one structured view, all
        recomputed fresh (the ledger is stateless like the exporter:
        an extend between scrapes changes the next scrape)."""
        # each input computed exactly once per snapshot: one model
        # walk, one backend stats read, one executor-reservation read
        with self._lock:
            models = self._watched_models_locked()
        live = self.live()
        fc = self.forecast(models)
        headroom = self._headroom_from(live, fc)
        divergence = None
        if live["supported"] and live["devices"]:
            in_use = sum(d["in_use_bytes"]
                         for d in live["devices"].values())
            modeled = fc["resident_bytes"] + fc["probe_plane_bytes"]
            divergence = float(in_use - modeled)
        with self._lock:
            self._wm_forecast = max(self._wm_forecast, fc["peak_bytes"])
            wm_in_use, wm_forecast = self._wm_in_use, self._wm_forecast
        host_total = sum(m.get("host_resident_bytes", 0)
                         for m in models.values())
        return {
            "supported": live["supported"],
            "devices": live["devices"],
            "indexes": models,
            "resident_total_bytes": fc["resident_bytes"],
            "host_resident_total_bytes": float(host_total),
            "forecast": fc,
            "reserved_held_bytes": self.reserved_bytes(),
            "headroom_bytes": headroom,
            "divergence_bytes": divergence,
            "watermark": {"in_use_peak_bytes": wm_in_use,
                          "forecast_peak_bytes": wm_forecast},
            "capacity_bytes": self.capacity_bytes,
        }

    def publish(self) -> dict:
        """Publish the gauge surface from one :meth:`snapshot` (the
        exporter's scrape refresh calls this) and return the
        snapshot. Stale per-index gauges retire first — an unwatched
        or collected index must not linger at its old value."""
        snap = self.snapshot()
        tracing.reset_gauges("memory.index.")
        tracing.reset_gauges("memory.device.")
        vals: Dict[str, float] = {
            "memory.live.supported": 1.0 if snap["supported"] else 0.0,
            "memory.resident.total_bytes": snap["resident_total_bytes"],
            "memory.host.resident_bytes":
                snap["host_resident_total_bytes"],
            "memory.reserved.probe_planes_bytes":
                snap["forecast"]["probe_plane_bytes"],
            "memory.reserved.max_temp_bytes":
                snap["forecast"]["max_temp_bytes"],
            "memory.reserved.held_bytes": snap["reserved_held_bytes"],
            "memory.forecast.peak_bytes": snap["forecast"]["peak_bytes"],
            "memory.hbm.headroom_bytes":
                -1.0 if snap["headroom_bytes"] is None
                else float(snap["headroom_bytes"]),
            "memory.watermark.in_use_peak_bytes":
                snap["watermark"]["in_use_peak_bytes"],
            "memory.watermark.forecast_peak_bytes":
                snap["watermark"]["forecast_peak_bytes"],
        }
        if snap["divergence_bytes"] is not None:
            vals["memory.divergence_bytes"] = snap["divergence_bytes"]
        for label, model in snap["indexes"].items():
            base = f"memory.index.{label}."
            vals[base + "resident_bytes"] = float(
                model["resident_bytes"])
            vals[base + "shard_bytes"] = float(
                model["shard_resident_bytes"])
            if model.get("host_resident_bytes"):
                vals[base + "host_bytes"] = float(
                    model["host_resident_bytes"])
        for o, d in snap["devices"].items():
            base = f"memory.device.{o}."
            vals[base + "in_use_bytes"] = d["in_use_bytes"]
            vals[base + "peak_bytes"] = d["peak_bytes"]
            vals[base + "limit_bytes"] = d["limit_bytes"]
        tracing.set_gauges(vals)
        # same-scrape consumers (the flight recorder's low-headroom
        # trigger runs right after the exporter's publish) read this
        # instead of recomputing the truth that was just computed
        self.last_snapshot = snap
        return snap

    def federation_payload(self) -> dict:
        """The type-correct fleet-merge inputs (shipped inside
        ``/snapshot.json`` as the ``memory`` block): per-index
        resident bytes SUM fleet-side (each replica holds its own
        copy), headroom takes the fleet MIN (placement goes where the
        worst-off replica still fits), device truth rides per replica
        for the labeled exposition. A replica without live support
        ships ``headroom_bytes: null`` — the aggregator skips it in
        the min rather than treating ignorance as infinite room.

        Reuses the snapshot :meth:`publish` just produced when one
        exists: the exporter's scrape refresh publishes BEFORE the
        ``/snapshot.json`` body assembles, so recomputing here would
        double every model walk, backend stats read and executor-lock
        acquisition per scrape for identical data. Callers that never
        publish pay one fresh snapshot."""
        snap = (self.last_snapshot if self.last_snapshot is not None
                else self.snapshot())
        return {
            "supported": snap["supported"],
            "resident": {label: int(m["resident_bytes"])
                         for label, m in snap["indexes"].items()},
            "resident_total_bytes": int(snap["resident_total_bytes"]),
            "host_resident_total_bytes":
                int(snap["host_resident_total_bytes"]),
            "forecast_peak_bytes": snap["forecast"]["peak_bytes"],
            "headroom_bytes": snap["headroom_bytes"],
            "divergence_bytes": snap["divergence_bytes"],
            "devices": snap["devices"],
        }


# ---------------------------------------------------------------------------
# /memory_profile diffing — per-buffer divergence attribution
# ---------------------------------------------------------------------------


def parse_memory_profile(data: bytes) -> Dict[str, int]:
    """Aggregate one ``jax.profiler.device_memory_profile`` capture
    (pprof wire format, gzip or raw) into per-buffer-group byte
    totals: ``{label_key: bytes}`` where ``label_key`` renders each
    sample's pprof labels (``kind=buffer,shape=f32[...],...``) — the
    grouping the divergence gauge can point AT, instead of at the
    whole process. Pure stdlib: gzip + the protobuf wire reader
    :func:`raft_tpu.core.xplane.fields` (varints and length-delimited
    payloads only; unknown fields skipped per proto semantics).

    The summed value is the sample type whose unit string is
    ``bytes`` (pprof heap profiles carry ``(objects, bytes)`` pairs);
    captures exposing no byte-typed value fall back to the LAST value
    column, pprof's space convention."""
    import gzip

    from raft_tpu.core.xplane import _read_varint, fields

    if data[:2] == b"\x1f\x8b":
        data = gzip.decompress(data)

    strings: list = []
    sample_units: list = []
    samples: list = []
    for fnum, wtype, val in fields(data):
        if fnum == 6 and wtype == 2:          # string_table
            strings.append(val.decode("utf-8", "replace"))
        elif fnum == 1 and wtype == 2:        # sample_type: ValueType
            unit_idx = 0
            for f2, w2, v2 in fields(val):
                if f2 == 2 and w2 == 0:
                    unit_idx = v2
            sample_units.append(unit_idx)
        elif fnum == 2 and wtype == 2:        # sample
            samples.append(val)

    def string_at(i: int) -> str:
        return strings[i] if 0 <= i < len(strings) else ""

    value_idx = len(sample_units) - 1
    for i, unit in enumerate(sample_units):
        if string_at(unit) == "bytes":
            value_idx = i
            break

    out: Dict[str, int] = {}
    for raw in samples:
        values: list = []
        labels: list = []
        for fnum, wtype, val in fields(raw):
            if fnum == 2:                     # value: repeated int64
                if wtype == 0:
                    values.append(val)
                elif wtype == 2:              # packed
                    pos = 0
                    while pos < len(val):
                        v, pos = _read_varint(val, pos)
                        values.append(v)
            elif fnum == 3 and wtype == 2:    # label
                key = s = num = 0
                has_num = False
                for f2, w2, v2 in fields(val):
                    if f2 == 1 and w2 == 0:
                        key = v2
                    elif f2 == 2 and w2 == 0:
                        s = v2
                    elif f2 == 3 and w2 == 0:
                        num = v2
                        has_num = True
                kname = string_at(key)
                if not kname:
                    continue
                value = string_at(s) if s else (
                    str(num) if has_num else "")
                labels.append(f"{kname}={value}")
        if not values:
            continue
        v = values[value_idx] if value_idx < len(values) else values[-1]
        label_key = ",".join(sorted(labels)) or "(unlabeled)"
        out[label_key] = out.get(label_key, 0) + int(v)
    return out


def diff_memory_profiles(before: Dict[str, int],
                         after: Dict[str, int]) -> dict:
    """Per-buffer-group divergence between two parsed captures:
    ``deltas`` (largest |delta| first; ties by label) name which
    buffer groups grew or shrank across the window the two
    sequence-numbered captures bracket — the attribution that turns
    the process-wide divergence gauge into an answer."""
    keys = sorted(set(before) | set(after))
    deltas = []
    for key in keys:
        b = int(before.get(key, 0))
        a = int(after.get(key, 0))
        if a != b:
            deltas.append({"label": key, "from_bytes": b,
                           "to_bytes": a, "delta_bytes": a - b})
    deltas.sort(key=lambda d: (-abs(d["delta_bytes"]), d["label"]))
    return {
        "deltas": deltas,
        "total_before_bytes": int(sum(before.values())),
        "total_after_bytes": int(sum(after.values())),
        "total_delta_bytes": int(sum(after.values())
                                 - sum(before.values())),
    }


# ---------------------------------------------------------------------------
# the opt-in build/extend capacity gate
# ---------------------------------------------------------------------------

_GATE: Optional[MemoryLedger] = None  # guarded-by: _GATE_LOCK
_GATE_LOCK = threading.Lock()


def install_gate(ledger: MemoryLedger) -> None:
    """Arm the process-wide capacity gate: every index build/extend
    allocation point calls :func:`admit` with its planned bytes, and
    :class:`CapacityExceeded` is raised host-side when they don't
    fit. Opt-in by design — without this call, :func:`admit` is a
    no-op and build/extend behave exactly as before."""
    global _GATE
    with _GATE_LOCK:
        _GATE = ledger


def remove_gate() -> None:
    """Disarm the gate (tests; a deployment turning the gate off)."""
    global _GATE
    with _GATE_LOCK:
        _GATE = None


def gate() -> Optional[MemoryLedger]:
    """The armed ledger, or None."""
    with _GATE_LOCK:
        return _GATE


def admit(nbytes: int, what: str) -> None:
    """Module-level gate check the build/extend paths call: no-op
    unless a gate is installed (the opt-in), else
    :meth:`MemoryLedger.admit`."""
    g = gate()
    if g is not None:
        g.admit(int(nbytes), what)
