"""grafttier — billion-scale tiered IVF storage (PR 14; graftcast
extended it across the compressed families, PR 18).

Every index family so far is fully HBM-resident, which caps corpus
size at device memory — far below the SIFT-1B north star ("millions
of users, corpus ≫ HBM"). :class:`TieredIvf` splits an
:class:`~raft_tpu.neighbors.ivf_flat.IvfFlatIndex`'s lists into an
HBM-resident **hot tier** (fixed slot capacity, sized against
graftledger's live headroom via :func:`resolve_hot_slots`) and a
host-memory **cold tier** (committed via :func:`host_put` on a TPU;
on the CPU tier-1 backend it stays in the one memory pool there is),
and serves the probed-list union in one
pass through :mod:`raft_tpu.ops.tier_scan`: hot blocks ride the
existing scalar-prefetched BlockSpec pipeline, cold blocks stream
through a double-buffered manual-DMA pipeline from the host operand.

graftcast generalizes the split to the compressed families — the
actual billion-vector story: :class:`TieredIvfPq` tiers the PQ codes
plane, :class:`TieredIvfBq` tiers the five-plane RaBitQ record
(codes/scales/error/rerank vectors move as ONE unit per list so an
estimate and its re-rank can never split across tiers). Every
container declares its hot/cold plane pairs in ``_PLANE_PAIRS`` and
shares one placement executor (:func:`apply_plan`), one snapshot
discipline and one layout truth through :class:`_TieredPlanes`.

The split moves ONLY the heavy per-row planes: centers, norms, ids,
slot maps and list sizes (~2% of the bytes at serving dims) stay
resident, so coarse selection, membership masking, filters and
graftgauge's probe accounting are untouched — and search results are
**bit-identical** to the all-HBM index per engine.

**Shape stability is the serving contract.** The hot tier has a FIXED
slot count decided once at construction; a placement epoch
(:mod:`raft_tpu.serving.placement`) only PERMUTES which lists occupy
those slots, via :func:`apply_plan`'s fixed-width donated block swaps
(pad entries carry out-of-range slots — gathers clamp, scatters
``mode="drop"`` — so every epoch runs the same compiled programs).
Shapes never change ⇒ the ``SearchExecutor``'s AOT cache keys never
change ⇒ steady-state serving stays at zero backend compiles across
re-placement epochs (pinned in ``tests/test_tiered.py``). The
container is deliberately MUTABLE (unlike the frozen index
dataclasses): the arrays are re-placed in place across epochs while
``id(index)`` — the coalesce key's and probe plane's identity — stays
stable; the container itself never flows through jit, only its
arrays do.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.core import tracing
from raft_tpu.core.resources import Resources, ensure_resources
from raft_tpu.core.validation import expect
from raft_tpu.distance.types import DistanceType
from raft_tpu.neighbors._batching import coarse_select, tile_queries
from raft_tpu.neighbors.ann_types import SearchParams
from raft_tpu.neighbors.filters import resolve_filter_words
from raft_tpu.neighbors.ivf_flat import IvfFlatIndex


@dataclasses.dataclass(frozen=True)
class TieredSearchParams(SearchParams):
    """Search params of the tiered index. ``scan_engine`` selects the
    tiered engine pair (:mod:`raft_tpu.ops.tier_scan`): ``"auto"`` is
    the dual-source Pallas kernel on TPU and the tiered XLA scan
    elsewhere; ``"pallas"`` degrades per ``resolve_tier_engine``."""

    n_probes: int = 20
    coarse_algo: str = "exact"   # "exact" | "approx"
    scan_engine: str = "auto"    # "auto" | "pallas" | "xla"


class _TieredPlanes:
    """Shared tiered-container machinery (graftcast). Every tiered
    family declares its hot/cold plane name pairs in ``_PLANE_PAIRS``
    and inherits the geometry, byte accounting, atomic generation
    snapshot and layout truth from here — ONE implementation, so the
    flat/PQ/BQ containers cannot drift on the placement contract.

    ``generation`` is the placement-generation counter
    (:func:`apply_plan` bumps it under the swap lock): the
    prefetcher stamps staged blocks with it, so a block staged
    against an older placement is detectably stale, and the ragged
    packing contract is generation-STABLE — a packed tile's plan
    carries no placement arrays in its cache key, every dispatch
    re-snapshots the planes, so epochs permute placement without
    ever invalidating (or even touching) the one ragged
    executable."""

    _PLANE_PAIRS = ()          # ((hot_name, cold_name), ...)

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]

    @property
    def max_list_size(self) -> int:
        return getattr(self, self._PLANE_PAIRS[0][0]).shape[1]

    @property
    def n_hot(self) -> int:
        return getattr(self, self._PLANE_PAIRS[0][0]).shape[0]

    @property
    def n_cold(self) -> int:
        return getattr(self, self._PLANE_PAIRS[0][1]).shape[0]

    @property
    def block_bytes(self) -> int:
        """Bytes of ONE list's tiered planes (summed across plane
        pairs) — the unit every placement swap moves twice (one
        promotion + one demotion) and every prefetch stages once."""
        total = 0
        for hot_name, _ in self._PLANE_PAIRS:
            a = getattr(self, hot_name)
            total += int(np.prod(a.shape[1:])) * a.dtype.itemsize
        return total

    @property
    def hot_bytes(self) -> int:
        return self.n_hot * self.block_bytes

    @property
    def cold_bytes(self) -> int:
        return self.n_cold * self.block_bytes

    def tier_planes(self) -> tuple:
        """Atomic snapshot of the placement generation across EVERY
        tiered plane pair: ``(hot_planes, cold_planes, hot_slot_map,
        cold_slot_map, generation)`` read under the swap lock — the
        generic sibling of :meth:`TieredIvf.tier_arrays`
        (:func:`apply_plan` replaces all of them, and bumps the
        generation, under the same lock)."""
        with self._swap_lock:
            return (
                tuple(getattr(self, h) for h, _ in self._PLANE_PAIRS),
                tuple(getattr(self, c) for _, c in self._PLANE_PAIRS),
                self.hot_slot_map, self.cold_slot_map,
                self.generation)

    def layout(self) -> dict:
        """The host-side placement truth (the ``/tier.json`` body's
        core): which lists are hot, which cold, and the byte split.
        Read under the swap lock — a concurrent epoch must never show
        a scrape new hot mirrors against old cold mirrors (a list in
        both tiers, or neither)."""
        with self._swap_lock:
            return {
                "n_lists": self.n_lists,
                "n_hot": self.n_hot,
                "n_cold": self.n_cold,
                "hot_lists": [int(x) for x in self.hot_lists],
                "cold_lists": [int(x) for x in self.cold_lists],
                "hot_bytes": self.hot_bytes,
                "cold_bytes": self.cold_bytes,
                "block_bytes": self.block_bytes,
                "host_resident": self.host_resident,
                "generation": self.generation,
            }


@dataclasses.dataclass
class TieredIvf(_TieredPlanes):
    """Hot/cold tiered IVF container (MUTABLE — see module docstring;
    placement epochs re-place the arrays in place, shapes fixed)."""

    centers: jax.Array         # (n_lists, d) f32 — HBM
    center_norms: jax.Array    # (n_lists,) f32
    data_norms: jax.Array      # (n_lists, max_list_size) f32, full plane
    indices: jax.Array         # (n_lists, max_list_size) int32, full plane
    list_sizes: jax.Array      # (n_lists,) int32
    hot_data: jax.Array        # (n_hot, max_list_size, d) f32 — HBM
    cold_data: jax.Array       # (n_cold, max_list_size, d) f32 — host
    hot_slot_map: jax.Array    # (n_lists,) int32, hot slot or -1  # guarded-by: _swap_lock
    cold_slot_map: jax.Array   # (n_lists,) int32, cold slot or -1  # guarded-by: _swap_lock
    hot_lists: np.ndarray      # (n_hot,) list id occupying each hot slot  # guarded-by: _swap_lock
    cold_lists: np.ndarray     # (n_cold,) list id occupying each cold slot  # guarded-by: _swap_lock
    metric: DistanceType
    host_resident: bool        # did the cold tier land in host memory?
    generation: int = 0        # placement generation (apply_plan bumps)  # guarded-by: _swap_lock
    # serializes placement writes against serving reads: a search
    # must capture the placement-affected arrays as ONE consistent
    # generation (all pre-swap or all post-swap, never mixed — a new
    # hot plane against an old slot map would serve a list from the
    # wrong slot). Not an array field, so the memwatch model walk
    # skips it.
    _swap_lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)

    _PLANE_PAIRS = (("hot_data", "cold_data"),)

    def tier_arrays(self) -> tuple:
        """Atomic snapshot of the placement generation:
        ``(hot_data, cold_data, hot_slot_map, cold_slot_map)`` read
        under the swap lock — THE way the serving path must capture
        the tier arrays (:func:`apply_plan` replaces all four under
        the same lock). Flat-family convenience over the generic
        :meth:`_TieredPlanes.tier_planes`."""
        with self._swap_lock:
            return (self.hot_data, self.cold_data,
                    self.hot_slot_map, self.cold_slot_map)


@dataclasses.dataclass
class TieredIvfPq(_TieredPlanes):
    """Hot/cold tiered IVF-PQ container (graftcast): the codes plane
    — the only billion-scale plane of a PQ index — splits hot/cold
    under the same fixed-slot, fixed-shape contract as
    :class:`TieredIvf`; centers, rotation, codebooks and the id
    plane stay resident, so coarse selection, the LUT build,
    membership masking and probe accounting are untouched and the
    tiered search is bit-identical to the all-HBM index."""

    centers: jax.Array         # (n_lists, dim) f32 — HBM
    rotation: jax.Array        # (dim_ext, dim) f32
    codebooks: jax.Array       # PQ codebooks — resident
    indices: jax.Array         # (n_lists, max_list_size) int32, full
    list_sizes: jax.Array      # (n_lists,) int32
    hot_codes: jax.Array       # (n_hot, max, pq_bytes) u8 — HBM
    cold_codes: jax.Array      # (n_cold, max, pq_bytes) u8 — host
    hot_slot_map: jax.Array    # (n_lists,) int32, hot slot or -1  # guarded-by: _swap_lock
    cold_slot_map: jax.Array   # (n_lists,) int32, cold slot or -1  # guarded-by: _swap_lock
    hot_lists: np.ndarray  # guarded-by: _swap_lock
    cold_lists: np.ndarray  # guarded-by: _swap_lock
    metric: DistanceType
    codebook_kind: object      # ivf_pq.CodebookKind
    pq_bits: int
    packed: bool
    host_resident: bool
    generation: int = 0  # guarded-by: _swap_lock
    _swap_lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)

    _PLANE_PAIRS = (("hot_codes", "cold_codes"),)

    @property
    def pq_book_size(self) -> int:
        return self.codebooks.shape[1]

    @property
    def pq_dim(self) -> int:
        d = self.hot_codes.shape[2]
        return d * 2 if self.packed else d


@dataclasses.dataclass
class TieredIvfBq(_TieredPlanes):
    """Hot/cold tiered IVF-RaBitQ container (graftcast): the five
    per-row record planes — sign codes, residual norm, per-level
    scales, error weight and the raw re-rank vectors — tier as ONE
    unit per list (a single slot assignment covers all five), so the
    fused estimate-then-rerank can never read a list's estimate
    planes from one tier and its re-rank rows from another. Centers,
    rotation, ids and the norm plane stay resident. Requires the
    re-rank plane (``store_vectors=True``): a codes-only index
    serves through the rank-major scan, which has no per-list fetch
    step to tier."""

    centers: jax.Array         # (n_lists, dim) f32 — HBM
    rotation: jax.Array        # (dim_ext, dim) f32
    indices: jax.Array         # (n_lists, max) int32, full plane
    list_sizes: jax.Array      # (n_lists,) int32
    data_norms: jax.Array      # (n_lists, max) f32 — resident
    hot_codes: jax.Array       # (n_hot, max, bits·D/32) i32 — HBM
    cold_codes: jax.Array
    hot_rnorm: jax.Array       # (n_hot, max) f32
    cold_rnorm: jax.Array
    hot_cfac: jax.Array        # (n_hot, max, bits) f32
    cold_cfac: jax.Array
    hot_errw: jax.Array        # (n_hot, max) f32
    cold_errw: jax.Array
    hot_data: jax.Array        # (n_hot, max, dim) f32 — rerank rows
    cold_data: jax.Array
    hot_slot_map: jax.Array  # guarded-by: _swap_lock
    cold_slot_map: jax.Array  # guarded-by: _swap_lock
    hot_lists: np.ndarray  # guarded-by: _swap_lock
    cold_lists: np.ndarray  # guarded-by: _swap_lock
    metric: DistanceType
    host_resident: bool
    generation: int = 0  # guarded-by: _swap_lock
    _swap_lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)

    _PLANE_PAIRS = (
        ("hot_codes", "cold_codes"),
        ("hot_rnorm", "cold_rnorm"),
        ("hot_cfac", "cold_cfac"),
        ("hot_errw", "cold_errw"),
        ("hot_data", "cold_data"),
    )

    @property
    def dim_ext(self) -> int:
        return self.rotation.shape[0]

    @property
    def bits(self) -> int:
        return self.hot_cfac.shape[2]


def host_put(x) -> Tuple[jax.Array, bool]:
    """Commit ``x`` to host memory (``pinned_host``) on a TPU; returns
    ``(array, host_resident)``. Elsewhere the array stays committed to
    the default device and the flag says so: the CPU backend's host
    and device memory are one pool, and XLA:CPU implements no moves
    between memory kinds inside a program, so a host-committed plane
    could not be read there. Committed placement (explicit sharding):
    the cold plane presents the same committed-ness from its first
    epoch that the ``out_shardings``-pinned swap output carries ever
    after — an uncommitted first generation would re-specialize the
    swap program once."""
    x = jnp.asarray(x)
    dev = x.devices().pop()
    if dev.platform != "tpu":
        return jax.device_put(
            x, jax.sharding.SingleDeviceSharding(dev)), False
    sharding = jax.sharding.SingleDeviceSharding(
        dev, memory_kind="pinned_host")
    return jax.device_put(x, sharding), True


def resolve_hot_slots(index, *, hot_slots=None,
                      hot_fraction: float = 0.5, ledger=None,
                      safety_fraction: float = 0.1,
                      block_bytes: Optional[int] = None) -> int:
    """Decide the hot tier's FIXED slot capacity. Precedence:

    1. an explicit ``hot_slots``;
    2. a graftledger :class:`~raft_tpu.core.memwatch.MemoryLedger`
       with known headroom: the largest slot count whose hot-tier
       bytes fit ``headroom × (1 − safety_fraction)`` (the byte half
       of the placement signal — live truth beats any fraction);
    3. ``hot_fraction`` of the lists (the unknown-headroom default —
       CPU tier-1, or no ledger attached).

    Always clamped to [1, n_lists − 1]: an all-hot or all-cold split
    is not a tiered index. ``block_bytes`` overrides the per-list
    byte unit (the compressed-family builders pass their own — a PQ
    list block is codes bytes, a BQ block the five-plane sum);
    without it the flat raw-vector block is assumed."""
    n_lists = index.n_lists
    block = block_bytes if block_bytes is not None else (
        index.max_list_size * index.dim * index.data.dtype.itemsize)
    if hot_slots is None and ledger is not None:
        headroom = ledger.headroom_bytes()
        if headroom is not None:
            usable = max(float(headroom) * (1.0 - safety_fraction), 0.0)
            hot_slots = int(usable // max(block, 1))
    if hot_slots is None:
        hot_slots = int(n_lists * hot_fraction)
    return max(1, min(int(hot_slots), n_lists - 1))


def _slot_maps(hot_lists: np.ndarray, cold_lists: np.ndarray,
               n_lists: int):
    """The (hot_map, cold_map) numpy planes for one assignment: each
    list's slot in its tier, −1 in the other — ONE implementation
    shared by construction and the swap executor, so the two can
    never disagree about the map convention."""
    hot_map = np.full((n_lists,), -1, np.int32)
    cold_map = np.full((n_lists,), -1, np.int32)
    hot_map[hot_lists] = np.arange(len(hot_lists), dtype=np.int32)
    cold_map[cold_lists] = np.arange(len(cold_lists), dtype=np.int32)
    return hot_map, cold_map


def build_tiered(index: IvfFlatIndex, *, hot_slots=None,
                 hot_fraction: float = 0.5, ledger=None,
                 safety_fraction: float = 0.1,
                 probe_counts=None) -> TieredIvf:
    """Split a built :class:`IvfFlatIndex` into the tiered layout.

    ``probe_counts`` (optional ``(n_lists,)`` counts — graftgauge's
    claimed probe-frequency plane, or any traffic prior) decides the
    INITIAL placement: the hottest ``hot_slots`` lists by count (ties
    to the smaller list id — deterministic) go hot, the rest cold.
    Without counts, lists 0..H−1 go hot — the first placement epoch
    corrects it from live traffic. ``ledger`` sizes the hot tier from
    live headroom (see :func:`resolve_hot_slots`).

    The tiered path is f32-only (the cold DMA scratch and hot blocks
    must agree on layout); int8/bf16 tiering is a follow-on."""
    expect(index.max_list_size > 0, "index is empty — extend() it first")
    expect(index.data.dtype == jnp.float32,
           "tiered storage supports f32 list data only")
    n_lists = index.n_lists
    h = resolve_hot_slots(index, hot_slots=hot_slots,
                          hot_fraction=hot_fraction, ledger=ledger,
                          safety_fraction=safety_fraction)
    hot_lists, cold_lists = _split_lists(n_lists, h, probe_counts)

    hot_map, cold_map = _slot_maps(hot_lists, cold_lists, n_lists)

    # the placement-affected arrays are COMMITTED (explicit device)
    # from construction: the epoch swap's jit outputs are committed,
    # and a committed-ness flip between the first and second epoch
    # would re-specialize the swap programs once — committing here
    # makes epoch 0 already run the steady-state executables
    dev = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    hot_data = jax.device_put(
        _gather_blocks(index.data, jnp.asarray(hot_lists)), dev)
    cold_dev = _gather_blocks(index.data, jnp.asarray(cold_lists))
    cold_data, host_resident = host_put(cold_dev)
    return TieredIvf(
        centers=index.centers,
        center_norms=index.center_norms,
        data_norms=index.data_norms,
        indices=index.indices,
        list_sizes=index.list_sizes,
        hot_data=hot_data,
        cold_data=cold_data,
        hot_slot_map=jax.device_put(jnp.asarray(hot_map), dev),
        cold_slot_map=jax.device_put(jnp.asarray(cold_map), dev),
        hot_lists=hot_lists,
        cold_lists=cold_lists,
        metric=index.metric,
        host_resident=host_resident,
    )


@jax.jit
def _gather_blocks(plane, rows):
    """``plane[rows]`` in device memory. Block by block: a
    host-committed plane may only be sliced, and XLA turns each slice
    + move into one host-to-device copy of exactly that block."""
    return jax.lax.map(
        lambda r: jax.device_put(
            jax.lax.dynamic_index_in_dim(plane, r, 0, False),
            jax.memory.Space.Device),
        rows)


def _split_lists(n_lists: int, h: int, probe_counts):
    """Initial hot/cold list split shared by every builder: the
    hottest ``h`` lists by count go hot (ties to the smaller list id
    — argsort is stable on the already-ordered lid axis), the rest
    cold; no counts → lists 0..h−1 (the first placement epoch
    corrects it from live traffic)."""
    if probe_counts is None:
        counts = np.zeros((n_lists,), np.int64)
    else:
        counts = np.asarray(probe_counts, np.int64)
        expect(counts.shape == (n_lists,),
               "probe_counts must be one count per list")
    order = np.argsort(-counts, kind="stable").astype(np.int32)
    return np.sort(order[:h]), np.sort(order[h:])


def _tier_place(full_planes, hot_lists, cold_lists):
    """Gather each full ``(n_lists, ...)`` plane into a COMMITTED
    device hot plane and a host-committed cold plane (see
    :func:`build_tiered` on why committed-ness must hold from epoch
    0); returns ``(hot_planes, cold_planes, host_resident)``."""
    dev = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    hl = jnp.asarray(hot_lists)
    cl = jnp.asarray(cold_lists)
    # one batched placement covers the whole hot plane set (R5: no
    # per-iteration transfers, even at build time)
    hots = tuple(jax.device_put(
        [_gather_blocks(plane, hl) for plane in full_planes], dev))
    colds, resident = [], True
    for plane in full_planes:
        cold, hr = host_put(_gather_blocks(plane, cl))
        colds.append(cold)
        resident = resident and hr
    return hots, tuple(colds), resident


def build_tiered_pq(index, *, hot_slots=None, hot_fraction: float = 0.5,
                    ledger=None, safety_fraction: float = 0.1,
                    probe_counts=None) -> TieredIvfPq:
    """Split a built :class:`~raft_tpu.neighbors.ivf_pq.IvfPqIndex`
    into the tiered layout — same contract as :func:`build_tiered`,
    tiering the codes plane (the only billion-scale plane of a PQ
    index). The hot-slot budget prices a list block at its CODES
    bytes, so a ledger-sized hot tier holds ~32× the lists the flat
    tier would at the same headroom (the compression ratio is the
    point)."""
    expect(index.max_list_size > 0, "index is empty — extend() it first")
    n_lists = index.n_lists
    block = (int(np.prod(index.codes.shape[1:]))
             * index.codes.dtype.itemsize)
    h = resolve_hot_slots(index, hot_slots=hot_slots,
                          hot_fraction=hot_fraction, ledger=ledger,
                          safety_fraction=safety_fraction,
                          block_bytes=block)
    hot_lists, cold_lists = _split_lists(n_lists, h, probe_counts)
    hot_map, cold_map = _slot_maps(hot_lists, cold_lists, n_lists)
    (hot_codes,), (cold_codes,), host_resident = _tier_place(
        (index.codes,), hot_lists, cold_lists)
    dev = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    return TieredIvfPq(
        centers=index.centers,
        rotation=index.rotation,
        codebooks=index.codebooks,
        indices=index.indices,
        list_sizes=index.list_sizes,
        hot_codes=hot_codes,
        cold_codes=cold_codes,
        hot_slot_map=jax.device_put(jnp.asarray(hot_map), dev),
        cold_slot_map=jax.device_put(jnp.asarray(cold_map), dev),
        hot_lists=hot_lists,
        cold_lists=cold_lists,
        metric=index.metric,
        codebook_kind=index.codebook_kind,
        pq_bits=index.pq_bits,
        packed=index.packed,
        host_resident=host_resident,
    )


def build_tiered_bq(index, *, hot_slots=None, hot_fraction: float = 0.5,
                    ledger=None, safety_fraction: float = 0.1,
                    probe_counts=None) -> TieredIvfBq:
    """Split a built :class:`~raft_tpu.neighbors.ivf_bq.IvfBqIndex`
    into the tiered layout — the five per-row record planes move as
    one unit per list (see :class:`TieredIvfBq`). Requires the
    re-rank plane and f32 vectors (same f32-only rule as
    :func:`build_tiered`)."""
    expect(index.max_list_size > 0, "index is empty — extend() it first")
    expect(index.data is not None and index.data_norms is not None,
           "tiered BQ needs the re-rank plane "
           "(build with store_vectors=True)")
    expect(index.data.dtype == jnp.float32,
           "tiered storage supports f32 list data only")
    n_lists = index.n_lists
    planes = (index.codes, index.rnorm, index.cfac, index.errw,
              index.data)
    block = sum(int(np.prod(p.shape[1:])) * p.dtype.itemsize
                for p in planes)
    h = resolve_hot_slots(index, hot_slots=hot_slots,
                          hot_fraction=hot_fraction, ledger=ledger,
                          safety_fraction=safety_fraction,
                          block_bytes=block)
    hot_lists, cold_lists = _split_lists(n_lists, h, probe_counts)
    hot_map, cold_map = _slot_maps(hot_lists, cold_lists, n_lists)
    hots, colds, host_resident = _tier_place(planes, hot_lists,
                                             cold_lists)
    dev = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    return TieredIvfBq(
        centers=index.centers,
        rotation=index.rotation,
        indices=index.indices,
        list_sizes=index.list_sizes,
        data_norms=index.data_norms,
        hot_codes=hots[0], cold_codes=colds[0],
        hot_rnorm=hots[1], cold_rnorm=colds[1],
        hot_cfac=hots[2], cold_cfac=colds[2],
        hot_errw=hots[3], cold_errw=colds[3],
        hot_data=hots[4], cold_data=colds[4],
        hot_slot_map=jax.device_put(jnp.asarray(hot_map), dev),
        cold_slot_map=jax.device_put(jnp.asarray(cold_map), dev),
        hot_lists=hot_lists,
        cold_lists=cold_lists,
        metric=index.metric,
        host_resident=host_resident,
    )


# ---------------------------------------------------------------------------
# placement execution — fixed-width donated block swaps
# ---------------------------------------------------------------------------


@partial(jax.jit, donate_argnums=(0,))
def _swap_hot_fn(hot_data, hot_slots, promoted):
    """Hot half of one epoch's swap: scatter the promoted blocks
    into the freed hot slots, DONATED — the hot tier is the scarce
    HBM pool and must update in place (the ``place_dealt``
    discipline: stream blocks, never materialize a permuted copy).
    ``hot_slots`` is a FIXED-width int32 vector: live pairs carry
    real slots, pad entries carry out-of-range slots the scatter
    ``mode="drop"``s — every epoch runs this one compiled program
    regardless of how many swaps it planned (zero-recompile)."""
    return hot_data.at[hot_slots].set(promoted, mode="drop")


@functools.lru_cache(maxsize=8)
def _cold_scatter_for(sharding):
    """Cold half of the swap, specialized per cold-tier sharding:
    ``out_shardings`` pins the output to the cold plane's OWN
    placement, so a host-committed (``pinned_host``) tier STAYS
    host-committed across epochs — without it the first epoch's
    output would land in default device memory, both hauling the
    cold tier back into HBM and invalidating the executor's AOT
    executable that was lowered with the host-memory aval
    (``_Plan.keep_sharding``). Not donated: host RAM is the abundant
    pool, and pinned-host donation semantics are backend-dependent —
    a transient functional copy there is the safe trade. One cached
    jit per sharding; the sharding is stable across epochs, so this
    compiles once."""
    return jax.jit(
        lambda cold, slots, blocks: cold.at[slots].set(blocks,
                                                       mode="drop"),
        out_shardings=sharding)


@partial(jax.jit, donate_argnums=(0, 1))
def _swap_maps_fn(hot_map, cold_map, promo_lids, demo_lids, hot_slots,
                  cold_slots):
    """Slot-map half of the swap (same fixed width + drop-mode pad
    discipline): promoted lists take the freed hot slots, demoted
    lists the freed cold slots, each list's other-tier slot goes
    −1."""
    hot_map = hot_map.at[promo_lids].set(hot_slots, mode="drop")
    hot_map = hot_map.at[demo_lids].set(-1, mode="drop")
    cold_map = cold_map.at[demo_lids].set(cold_slots, mode="drop")
    cold_map = cold_map.at[promo_lids].set(-1, mode="drop")
    return hot_map, cold_map


@jax.jit
def _promote_mix_fn(staged_plane, cold_plane, st_rows, cg, hit):
    """Promotion-source mix (graftcast prefetch): rows the
    prefetcher already staged in HBM come from the staged plane, the
    rest gather from the cold plane. Fixed shapes (swap width ×
    staged capacity) — one compiled program per plane geometry, so a
    prefetch-assisted epoch runs the same executables as a reactive
    one plus exactly this mix. The per-row select is the accounting
    truth the bench gates on: a hit's bytes moved at STAGE time
    (background), off the serving-path epoch — a sparse cold gather
    that also skips the miss rows' neighbors on-chip is the ROADMAP
    follow-on."""
    a = jnp.take(staged_plane, jnp.maximum(st_rows, 0), axis=0)
    b = _gather_blocks(cold_plane, cg)
    shape = (hit.shape[0],) + (1,) * (a.ndim - 1)
    return jnp.where(jnp.reshape(hit, shape), a, b)


def apply_plan(tiered, promotions, demotions,
               width: int, executor=None, staged=None) -> int:
    """Execute a placement plan IN PLACE: ``promotions[i]`` (a cold
    list id) takes the hot slot ``demotions[i]`` frees, which takes
    the cold slot ``promotions[i]`` frees. ``width`` is the fixed
    compiled swap width (the policy's ``max_swaps_per_epoch``) — the
    pair vectors pad to it with out-of-range slots (gathers clamp,
    scatters drop), so every epoch reuses one executable per
    (shapes, width). Works on ANY tiered container — the plane
    pairs come from ``_PLANE_PAIRS`` (flat: one raw-vector pair;
    PQ: codes; BQ: all five record planes under one slot decision).
    Returns the bytes moved (2 × block per pair: one promotion + one
    demotion).

    ``staged`` (graftcast prefetch) optionally provides promotion
    blocks the prefetcher already copied into HBM: an object with
    ``rows`` (one staged-plane row per promotion, −1 = miss) and
    ``planes`` (hot plane name → fixed ``(K, ...)`` staged storage).
    Hit rows skip the epoch-time cold stream (their bytes moved in
    the background at stage time); only misses count into the
    ``tier.promote_cold_bytes`` serving-path counter, which the
    reactive path charges in full — the A/B surface
    ``BENCH_TIERED`` gates.

    Concurrency discipline: the hot planes and the slot maps are
    DONATED to the swap (in-place HBM update), which is only safe
    against live traffic when swap enqueues serialize with dispatch
    enqueues — pass the serving ``executor`` (the TierManager does)
    and the swap runs under its dispatch lock. A dispatch that
    captured the pre-swap generation and enqueues after the swap
    hits jax's deleted-array error once and is retried by the
    executor against the new generation (see
    ``SearchExecutor._run``); readers always see a CONSISTENT
    generation because the container's placement arrays replace —
    and the generation counter bumps — atomically under the swap
    lock (:meth:`_TieredPlanes.tier_planes`)."""
    n = len(promotions)
    expect(n == len(demotions), "promotions/demotions must pair up")
    expect(n <= width, f"plan has {n} swaps, width is {width}")
    if n == 0:
        return 0
    promo = np.asarray(promotions, np.int32)
    demo = np.asarray(demotions, np.int32)
    hot_map_np, cold_map_np = _slot_maps(
        tiered.hot_lists, tiered.cold_lists, tiered.n_lists)
    hot_slots = hot_map_np[demo]
    cold_slots = cold_map_np[promo]
    expect(bool((hot_slots >= 0).all()),
           "every demotion must name a currently-hot list")
    expect(bool((cold_slots >= 0).all()),
           "every promotion must name a currently-cold list")

    # fixed-width pad: out-of-range slots/lids — gathers clamp,
    # scatters drop (see _swap_blocks_fn)
    def pad_to(v, fill):
        out = np.full((width,), fill, np.int32)
        out[:n] = v
        return jnp.asarray(out)

    hs = pad_to(hot_slots, tiered.n_hot)
    cs = pad_to(cold_slots, tiered.n_cold)
    pl_ = pad_to(promo, tiered.n_lists)
    dl = pad_to(demo, tiered.n_lists)

    st_rows = hit = None
    misses = n
    if staged is not None:
        rows_np = np.full((width,), -1, np.int32)
        rows_np[:n] = np.asarray(staged.rows, np.int32)[:n]
        st_rows = jnp.asarray(rows_np)
        hit = jnp.asarray(rows_np >= 0)
        misses = int(n - int((rows_np[:n] >= 0).sum()))

    # contextlib.nullcontext would be cleaner, but the executor lock
    # is the point: with a live executor attached, the donation
    # enqueues below must not interleave with dispatch enqueues
    ex_lock = getattr(executor, "_lock", None) if executor is not None \
        else None
    if ex_lock is not None:
        ex_lock.acquire()
    try:
        updates = {}
        for hot_name, cold_name in type(tiered)._PLANE_PAIRS:
            old_hot = getattr(tiered, hot_name)
            old_cold = getattr(tiered, cold_name)
            hg = jnp.minimum(hs, old_hot.shape[0] - 1)
            cg = jnp.minimum(cs, old_cold.shape[0] - 1)
            # gathers BEFORE the donation consumes the hot plane;
            # the promoted gather out of a host-committed cold plane
            # lands in device memory (that copy IS the promotion
            # transfer), and the demoted blocks ride into the
            # sharding-pinned cold scatter (the demotion transfer)
            demoted = _gather_blocks(old_hot, hg)
            if st_rows is not None:
                promoted = _promote_mix_fn(
                    staged.planes[hot_name], old_cold, st_rows, cg,
                    hit)
            else:
                promoted = _gather_blocks(old_cold, cg)
            updates[hot_name] = _swap_hot_fn(old_hot, hs, promoted)
            updates[cold_name] = _cold_scatter_for(old_cold.sharding)(
                old_cold, cs, demoted)
        hot_map, cold_map = _swap_maps_fn(
            tiered.hot_slot_map, tiered.cold_slot_map, pl_, dl, hs, cs)
        # host-side mirrors (the layout truth /tier.json serves)
        hot_lists = tiered.hot_lists.copy()
        cold_lists = tiered.cold_lists.copy()
        hot_lists[hot_slots] = promo
        cold_lists[cold_slots] = demo
        # the new generation replaces atomically: a concurrent
        # tier_planes()/tier_arrays() sees all-old or all-new, never
        # a mix — and the generation bump makes any still-in-flight
        # prefetch against the old placement detectably stale
        with tiered._swap_lock:
            for name, arr in updates.items():
                setattr(tiered, name, arr)
            tiered.hot_slot_map = hot_map
            tiered.cold_slot_map = cold_map
            tiered.hot_lists = hot_lists
            tiered.cold_lists = cold_lists
            tiered.generation += 1
    finally:
        if ex_lock is not None:
            ex_lock.release()
    moved = 2 * n * tiered.block_bytes
    tracing.inc_counters({
        "tier.swaps": float(n),
        "tier.swap_bytes": float(moved),
        "tier.promote_cold_bytes": float(misses * tiered.block_bytes),
    })
    return moved


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------


def _tiered_search_fn(queries, centers, center_norms, hot_data,
                      cold_data, hot_slot_map, cold_slot_map,
                      data_norms, indices, filter_words,
                      probe_counts=None, n_valid=None,
                      row_probes=None, *,
                      n_probes: int, k: int, metric: DistanceType,
                      coarse_algo: str = "exact",
                      scan_engine: str = "xla"):
    """Coarse select + tiered probe scan — the serving body (the
    executor's ``tiered_ivf`` plan compiles this). Mirrors ivf_flat's
    ``_search_impl_fn`` contract: the coarse stage and metric epilog
    are char-identical, only the scan swaps in the tiered engines, so
    results are bit-identical to the all-HBM index per engine.
    ``probe_counts``/``n_valid`` thread graftgauge's donated plane
    exactly like the un-tiered body. ``row_probes`` (the ragged
    front — see :func:`_tiered_search_ragged_fn`) masks each packed
    row's probe slots past its own budget to the sentinel id, which
    the tiered engines' membership predicate already rejects.
    ``scan_engine`` must arrive resolved (``pallas``/``xla``) — it
    is a jit static."""
    from raft_tpu.ops.tier_scan import tiered_list_major_scan

    qf = queries.astype(jnp.float32)

    ip = jax.lax.dot_general(
        qf, centers, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    score = (ip if metric == DistanceType.InnerProduct
             else -(center_norms[None, :] - 2.0 * ip))
    probes = coarse_select(score, n_probes, coarse_algo)
    if row_probes is not None:
        from raft_tpu.ops.ivf_scan import ragged_probes

        probes = ragged_probes(probes, row_probes, centers.shape[0])
    if probe_counts is not None:
        from raft_tpu.ops.ivf_scan import probe_histogram

        probe_counts = probe_histogram(
            probes, probe_counts,
            None if row_probes is not None else n_valid)

    best_d, best_i = tiered_list_major_scan(
        qf, hot_data, cold_data, hot_slot_map, cold_slot_map,
        data_norms, indices, probes, filter_words,
        k=k, metric=metric, engine=scan_engine,
        interpret=jax.default_backend() != "tpu")

    if metric != DistanceType.InnerProduct:
        q_sq = jnp.sum(jnp.square(qf), axis=1, keepdims=True)
        best_d = jnp.where(jnp.isfinite(best_d),
                           jnp.maximum(best_d + q_sq, 0.0), best_d)
        if metric == DistanceType.L2SqrtExpanded:
            best_d = jnp.where(jnp.isfinite(best_d), jnp.sqrt(best_d),
                               best_d)
    if probe_counts is not None:
        return best_d, best_i, probe_counts
    return best_d, best_i


_tiered_search = partial(jax.jit, static_argnames=(
    "n_probes", "k", "metric", "coarse_algo",
    "scan_engine"))(_tiered_search_fn)


def _tiered_search_ragged_fn(queries, row_probes, centers,
                             center_norms, hot_data, cold_data,
                             hot_slot_map, cold_slot_map, data_norms,
                             indices, filter_words, probe_counts=None,
                             n_valid=None, *, n_probes: int, k: int,
                             metric: DistanceType,
                             scan_engine: str = "xla"):
    """Packed ragged-batch tiered search body — the tiered member of
    the serving executor's ragged plan family (see
    :func:`raft_tpu.neighbors.ivf_flat._search_ragged_fn` for the
    packing contract). The plan is placement-GENERATION-stable: its
    cache key carries only shapes and statics, never the placement
    arrays, and every dispatch re-snapshots one consistent
    generation (:meth:`_TieredPlanes.tier_planes`) into the same
    fixed avals — an epoch permutes the hot/cold slot maps without
    touching the one ragged executable, which is what retired the
    ``"tiered"`` ragged-fallback pin. Bit-identical per request to
    :func:`_tiered_search_fn` on that request alone (same body, same
    membership-masked engines)."""
    del n_valid
    expect(scan_engine in ("pallas", "xla"),
           "ragged tiered serving needs a membership-masked tier "
           f"engine (pallas|xla), got {scan_engine!r}")
    return _tiered_search_fn(
        queries, centers, center_norms, hot_data, cold_data,
        hot_slot_map, cold_slot_map, data_norms, indices,
        filter_words, probe_counts, None,
        row_probes=row_probes, n_probes=n_probes, k=k, metric=metric,
        coarse_algo="exact", scan_engine=scan_engine)


def _tiered_pq_search_fn(queries, centers, rotation, codebooks,
                         hot_codes, cold_codes, hot_slot_map,
                         cold_slot_map, indices, filter_words,
                         probe_counts=None, n_valid=None,
                         row_probes=None, *,
                         n_probes: int, k: int, metric: DistanceType,
                         codebook_kind, lut_dtype,
                         score_mode: str = "gather",
                         packed: bool = False,
                         coarse_algo: str = "exact",
                         scan_engine: str = "xla"):
    """Tiered PQ serving body — a thin reorder over
    :func:`raft_tpu.neighbors.ivf_pq._search_impl_fn` with the cold
    codes plane live: the LUT union scan is the SAME body (coarse
    select, LUT build, accumulate, merge are char-identical), only
    the per-list codes fetch steers through the tier slot maps, so
    tiered PQ results are bit-identical to the all-HBM index."""
    from raft_tpu.neighbors.ivf_pq import _search_impl_fn

    return _search_impl_fn(
        queries, centers, rotation, codebooks, hot_codes, indices,
        filter_words, probe_counts, n_valid,
        row_probes=row_probes, cold_codes=cold_codes,
        hot_slot_map=hot_slot_map, cold_slot_map=cold_slot_map,
        n_probes=n_probes, k=k, metric=metric,
        codebook_kind=codebook_kind, lut_dtype=lut_dtype,
        score_mode=score_mode, packed=packed,
        coarse_algo=coarse_algo, scan_engine=scan_engine)


_tiered_pq_search = partial(jax.jit, static_argnames=(
    "n_probes", "k", "metric", "codebook_kind", "lut_dtype",
    "score_mode", "packed", "coarse_algo",
    "scan_engine"))(_tiered_pq_search_fn)


def _tiered_pq_search_ragged_fn(queries, row_probes, centers,
                                rotation, codebooks, hot_codes,
                                cold_codes, hot_slot_map,
                                cold_slot_map, indices, filter_words,
                                probe_counts=None, n_valid=None, *,
                                n_probes: int, k: int,
                                metric: DistanceType, codebook_kind,
                                lut_dtype, score_mode: str = "gather",
                                packed: bool = False,
                                scan_engine: str = "xla"):
    """Packed ragged-batch tiered-PQ body (see
    :func:`_tiered_search_ragged_fn` for the generation-stable
    contract; XLA engine only, like the un-tiered PQ ragged twin)."""
    del n_valid
    expect(scan_engine == "xla",
           "ragged tiered PQ serving rides the list-major XLA scan, "
           f"got {scan_engine!r}")
    return _tiered_pq_search_fn(
        queries, centers, rotation, codebooks, hot_codes, cold_codes,
        hot_slot_map, cold_slot_map, indices, filter_words,
        probe_counts, None, row_probes=row_probes,
        n_probes=n_probes, k=k, metric=metric,
        codebook_kind=codebook_kind, lut_dtype=lut_dtype,
        score_mode=score_mode, packed=packed, coarse_algo="exact",
        scan_engine=scan_engine)


def _tiered_bq_search_fn(queries, centers, rotation, hot_codes,
                         hot_rnorm, hot_cfac, hot_errw, hot_data,
                         cold_codes, cold_rnorm, cold_cfac, cold_errw,
                         cold_data, hot_slot_map, cold_slot_map,
                         indices, data_norms, filter_words,
                         probe_counts=None, n_valid=None,
                         row_probes=None, *,
                         n_probes: int, k: int, metric: DistanceType,
                         coarse_algo: str = "exact",
                         scan_engine: str = "xla",
                         epsilon: float = 3.0, query_bits: int = 0):
    """Tiered BQ serving body — a thin reorder over
    :func:`raft_tpu.neighbors.ivf_bq._search_impl_fn` with the five
    cold record planes live (one slot decision per list covers the
    estimate planes AND the re-rank rows). Same fused
    estimate-then-rerank body ⇒ same prune decisions ⇒ bit-identical
    to the all-HBM index."""
    from raft_tpu.neighbors.ivf_bq import _search_impl_fn

    return _search_impl_fn(
        queries, centers, rotation, hot_codes, hot_rnorm, hot_cfac,
        hot_errw, indices, hot_data, data_norms, filter_words,
        probe_counts, n_valid,
        row_probes=row_probes,
        cold_planes=(cold_codes, cold_rnorm, cold_cfac, cold_errw,
                     cold_data),
        hot_slot_map=hot_slot_map, cold_slot_map=cold_slot_map,
        n_probes=n_probes, k=k, metric=metric,
        coarse_algo=coarse_algo, scan_engine=scan_engine,
        epsilon=epsilon, query_bits=query_bits)


_tiered_bq_search = partial(jax.jit, static_argnames=(
    "n_probes", "k", "metric", "coarse_algo", "scan_engine",
    "epsilon", "query_bits"))(_tiered_bq_search_fn)


def _tiered_bq_search_ragged_fn(queries, row_probes, centers,
                                rotation, hot_codes, hot_rnorm,
                                hot_cfac, hot_errw, hot_data,
                                cold_codes, cold_rnorm, cold_cfac,
                                cold_errw, cold_data, hot_slot_map,
                                cold_slot_map, indices, data_norms,
                                filter_words, probe_counts=None,
                                n_valid=None, *, n_probes: int,
                                k: int, metric: DistanceType,
                                scan_engine: str = "xla",
                                epsilon: float = 3.0,
                                query_bits: int = 0):
    """Packed ragged-batch tiered-BQ body (see
    :func:`_tiered_search_ragged_fn` for the generation-stable
    contract; the fused XLA engine's per-row prune threshold keeps
    each request's re-rank decisions independent of its tile
    mates)."""
    del n_valid
    expect(scan_engine == "xla",
           "ragged tiered BQ serving rides the fused XLA scan, got "
           f"{scan_engine!r}")
    return _tiered_bq_search_fn(
        queries, centers, rotation, hot_codes, hot_rnorm, hot_cfac,
        hot_errw, hot_data, cold_codes, cold_rnorm, cold_cfac,
        cold_errw, cold_data, hot_slot_map, cold_slot_map, indices,
        data_norms, filter_words, probe_counts, None,
        row_probes=row_probes, n_probes=n_probes, k=k, metric=metric,
        coarse_algo="exact", scan_engine=scan_engine,
        epsilon=epsilon, query_bits=query_bits)


def search(
    res: Optional[Resources],
    params: TieredSearchParams,
    tiered: TieredIvf,
    queries,
    k: int,
    sample_filter=None,
    query_tile: int = 4096,
) -> Tuple[jax.Array, jax.Array]:
    """ANN search over the tiered index — same contract as
    ``ivf_flat.search`` (and bit-identical to it on the same lists):
    returns (distances, indices) of shape (q, k), missing slots id
    −1. The probe scan follows ``params.scan_engine`` (resolved per
    backend/shape by :func:`raft_tpu.ops.tier_scan
    .resolve_tier_engine`)."""
    ensure_resources(res)
    queries = jnp.asarray(queries)
    expect(queries.ndim == 2 and queries.shape[1] == tiered.dim,
           "queries must be (q, dim)")
    expect(params.coarse_algo in ("exact", "approx"),
           f"coarse_algo must be 'exact' or 'approx', got "
           f"{params.coarse_algo!r}")
    n_probes = min(params.n_probes, tiered.n_lists)
    filter_words = resolve_filter_words(sample_filter)
    from raft_tpu.ops.tier_scan import resolve_tier_engine

    # one consistent placement generation for the whole call — a
    # concurrent epoch swap must never hand this search a new hot
    # plane against an old slot map
    hot_data, cold_data, hot_map, cold_map = tiered.tier_arrays()
    scan_engine = resolve_tier_engine(
        params.scan_engine, hot_data=hot_data,
        filter_words=filter_words, k=k)
    with tracing.range("raft_tpu.tiered.search"):
        def run(qt, fw):
            return _tiered_search(
                qt, tiered.centers, tiered.center_norms,
                hot_data, cold_data, hot_map, cold_map,
                tiered.data_norms, tiered.indices, fw,
                n_probes=n_probes, k=k, metric=tiered.metric,
                coarse_algo=params.coarse_algo,
                scan_engine=scan_engine,
            )

        return tile_queries(run, queries, filter_words, query_tile)


def search_pq(
    res: Optional[Resources],
    params,
    tiered: TieredIvfPq,
    queries,
    k: int,
    sample_filter=None,
    query_tile: int = 4096,
) -> Tuple[jax.Array, jax.Array]:
    """ANN search over the tiered PQ index — same contract as (and
    bit-identical to) ``ivf_pq.search`` with
    :class:`~raft_tpu.neighbors.ivf_pq.IvfPqSearchParams`, forced
    onto the list-major XLA scan (the only engine with a per-list
    fetch step to steer through the tier — see
    :func:`raft_tpu.ops.tier_scan.resolve_tier_pq_engine`)."""
    from raft_tpu.neighbors import ivf_pq as m
    from raft_tpu.ops.tier_scan import resolve_tier_pq_engine

    ensure_resources(res)
    queries = jnp.asarray(queries)
    expect(queries.ndim == 2 and queries.shape[1] == tiered.dim,
           "queries must be (q, dim)")
    n_probes = min(params.n_probes, tiered.n_lists)
    filter_words = resolve_filter_words(sample_filter)
    engine = resolve_tier_pq_engine(params.scan_engine)
    score_mode = m.resolve_score_mode(params.score_mode,
                                      tiered.pq_book_size)
    (hot_codes,), (cold_codes,), hot_map, cold_map, _ = \
        tiered.tier_planes()
    with tracing.range("raft_tpu.tiered.search_pq"):
        def run(qt, fw):
            return _tiered_pq_search(
                qt, tiered.centers, tiered.rotation, tiered.codebooks,
                hot_codes, cold_codes, hot_map, cold_map,
                tiered.indices, fw, n_probes=n_probes, k=k,
                metric=tiered.metric,
                codebook_kind=tiered.codebook_kind,
                lut_dtype=params.lut_dtype, score_mode=score_mode,
                packed=tiered.packed, coarse_algo=params.coarse_algo,
                scan_engine=engine,
            )

        return tile_queries(run, queries, filter_words, query_tile)


def search_bq(
    res: Optional[Resources],
    params,
    tiered: TieredIvfBq,
    queries,
    k: int,
    sample_filter=None,
    query_tile: int = 4096,
) -> Tuple[jax.Array, jax.Array]:
    """ANN search over the tiered BQ index — same contract as (and
    bit-identical to) ``ivf_bq.search`` with
    :class:`~raft_tpu.neighbors.ivf_bq.IvfBqSearchParams` on a
    store-vectors index: exact distances out of the fused
    estimate-then-rerank XLA engine, with each probed list's five
    record planes fetched from its tier."""
    from raft_tpu.ops.bq_scan import auto_query_bits
    from raft_tpu.ops.tier_scan import resolve_tier_bq_engine

    ensure_resources(res)
    queries = jnp.asarray(queries)
    expect(queries.ndim == 2 and queries.shape[1] == tiered.dim,
           "queries must be (q, dim)")
    n_probes = min(params.n_probes, tiered.n_lists)
    filter_words = resolve_filter_words(sample_filter)
    engine = resolve_tier_bq_engine(params.scan_engine)
    qb = params.query_bits or auto_query_bits(tiered.bits)
    hots, colds, hot_map, cold_map, _ = tiered.tier_planes()
    with tracing.range("raft_tpu.tiered.search_bq"):
        def run(qt, fw):
            return _tiered_bq_search(
                qt, tiered.centers, tiered.rotation, *hots, *colds,
                hot_map, cold_map, tiered.indices, tiered.data_norms,
                fw, n_probes=n_probes, k=k, metric=tiered.metric,
                coarse_algo=params.coarse_algo, scan_engine=engine,
                epsilon=params.epsilon, query_bits=qb,
            )

        return tile_queries(run, queries, filter_words, query_tile)
