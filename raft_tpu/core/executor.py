"""Shape-stable serving path: bucketed batching + AOT executable cache.

The reference ships ahead-of-time-compiled kernels (RAFT's L6 explicit
instantiation layer) so serving never compiles; under plain ``jax.jit``
this repo instead paid a full XLA trace+compile (seconds) for every new
query-batch shape — fatal for a frontend that sends varying batch
sizes. ``SearchExecutor`` is the TPU-native answer, per the TPU-KNN
peak-throughput recipe already cited in ``matrix/select_k.py``:

- **Bucketing**: query batches are padded up to power-of-two buckets,
  so every batch size in a bucket runs ONE compiled program. Search
  results are per-query-row independent in every index family, so pad
  rows cannot perturb real rows (their outputs are sliced away), and
  results are bit-identical to the direct search path.
- **AOT compilation**: each (index shapes, search params, bucket)
  specialization is compiled once via ``jit(...).lower().compile()``
  and cached; the steady-state hot path calls the compiled executable
  directly — no tracing, no dispatch-cache lookup, no recompiles.
  :meth:`warmup` builds the executables from abstract shapes before
  traffic arrives, and the persistent compilation cache
  (:func:`~raft_tpu.core.resources.init_compile_cache`) makes that
  warmup survive process restarts.
- **Stateless calls**: a serving call takes the queries and the
  index arrays and allocates its own running top-k carry, so its
  outputs alias nothing the next call reads; each dispatch enqueues
  under the executor lock and its results are fetched after it.

Counters (compile count, cache hits/misses, evictions, warmup seconds)
are exported through :mod:`raft_tpu.core.tracing` under the
``serving.`` prefix, and :func:`tracing.install_xla_compile_listener`
provides the backend-compile ground truth that the tier-1 recompile
regression test asserts on.

**Executable cost introspection (PR 6, graftscope).** AOT compilation
is the one moment the whole program is in hand, so that is where the
TPU-KNN roofline accounting moves from bench artifact to live metric:
each compiled entry captures XLA's ``cost_analysis()`` (flops, bytes
accessed) and ``memory_analysis()`` (argument/output/temp bytes → peak
HBM) once, publishes them as ``serving.executable.<digest>.*`` gauges,
and every dispatch bumps ``serving.execute.modeled_flops`` /
``.modeled_bytes`` by the entry's numbers — pure host-side dict work,
captured at compile time, so the steady state stays sync-free and
zero-recompile. Combined with the measured execute-latency histogram
(the batcher blocks on results anyway) a scrape derives live achieved
GB/s and FLOP/s. Mesh plans also publish their
``collective_payload_model`` bytes per wire dtype. :meth:`
SearchExecutor.executable_costs` is the JSON-snapshot view.

Supported index types: ``BruteForceIndex``, ``IvfFlatIndex``,
``IvfPqIndex``, ``IvfBqIndex``, ``CagraIndex``, and the mesh-sharded
``DistributedIvfFlat`` / ``DistributedIvfPq`` / ``DistributedIvfBq``
(AOT-compiled per (mesh, index shapes, params, resolved scan engine,
bucket): queries bucket exactly like the single-chip families and are
placed replicated on the mesh — steady-state multi-chip serving is
zero-recompile).

**Ragged packed-batch plans (PR 9).** The bucket ladder trades pad
compute for shape stability: every batch pow2-rounds (up to ~2x pad
on the query axis) and a micro-batch must assemble whole requests.
The ragged plan family (Ragged Paged Attention, PAPERS.md) collapses
the ladder to ONE executable per (index shapes, params class): a
fixed ``(ragged_tile, dim)`` packed query tensor carries several
requests adjacently, each row's probe budget rides a per-row plane
into the engines' membership mask, and per-request ``k`` is a column
slice of the class-cap top-k (both total orders, so results stay
bit-identical per request to the bucketed path). ``n_probes``/``k``
round up to power-of-two CLASSES instead of forking executables — the
pow2 ladder moved from the batch axis (paid per dispatch, in pad
rows) to the params axis (paid once, in compiles). See
:meth:`SearchExecutor.search_ragged` / :meth:`~SearchExecutor
.ragged_key`; the serving batcher's ``BatcherConfig(ragged=True)``
admits continuously into the open packed tile and splits requests at
tile boundaries.

**One ragged family for the whole index zoo (PR 15, graftragged).**
The ragged plan DERIVES from each family's bucketed plan
(:meth:`SearchExecutor._plan_ragged`): same arrays, statics, probe
plumbing and shardings, with the serving fn swapped
for a thin wrapper that turns on the ``row_probes`` budget hook in
the SAME search body. Every IVF family — flat, PQ, BQ, single-chip
and list-sharded mesh — serves ragged through the one shared
dispatch core; the per-family bucketed plan paths shrank to the
documented non-raggable residue (see :meth:`SearchExecutor
.ragged_fallback_reason`). An opt-in small/large dual tile
(``ragged_tile_small``) cuts partial-tile pad at light load without
forking the params-class ladder: the tile is selected per dispatch
by packed-row count and never joins :meth:`~SearchExecutor
.ragged_key`.

Small print: the serving entries (:meth:`SearchExecutor.search_blocks`,
:meth:`~SearchExecutor.search_ragged`) run no per-shape micro-programs:
host (numpy) blocks are padded or packed in numpy, and each call's
outputs come back in one batched fetch and are cut per request in
numpy, so their results are host arrays. The library entry
:meth:`~SearchExecutor.search` returns device arrays; cutting a batch
short of its bucket to its rows (or padding a device batch) executes
tiny device ops whose programs XLA caches per distinct batch size —
the *search* program itself never recompiles, and once a batch size
has been seen, repeats are entirely compile-free. Each such program is
counted in ``serving.execute.eager_programs``, and every dispatch's
host stages (prepare, enqueue, device wait, slice) are
:class:`~raft_tpu.core.tracing.host_span` spans: profiler annotations
beside the device ops, and latency histograms.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
import threading
import time
import weakref
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.core import tracing
from raft_tpu.core.resources import Resources, ensure_resources
from raft_tpu.core.validation import expect

# The host stages of one dispatch, back to back: prepare (block concat,
# bucket, plan, pad, placement, the wait for the lock, up to the
# enqueue), enqueue (the AOT executable's call, argument transfer
# included), the device wait (blocking until the outputs are computed)
# and slice (the outputs fetched to the host and cut into each block's
# result; search()'s cut on the device). Each is a span of that name
# and a latency histogram; serving.metrics re-exports them beside the
# batcher's stages. The device wait keeps its serving.batcher. name:
# the benchmark's readers and recorded traces key on it.
STAGE_PREFIX = "serving.executor."
PREPARE_SPAN = STAGE_PREFIX + "prepare"
PREPARE = STAGE_PREFIX + "prepare_seconds"
ENQUEUE_SPAN = STAGE_PREFIX + "enqueue"
ENQUEUE = STAGE_PREFIX + "enqueue_seconds"
SLICE_SPAN = STAGE_PREFIX + "slice"
SLICE = STAGE_PREFIX + "slice_seconds"
DEVICE_WAIT_SPAN = "serving.batcher.device_wait"
DEVICE_WAIT = "serving.batcher.device_wait_seconds"
# eager device programs a dispatch launches beside its compiled
# executable: partial slices and copies of device arrays, device-side
# concatenations, pads and casts, jnp.asarray of host values (implicit
# transfers of numpy arguments into the compiled call are not counted)
EAGER_PROGRAMS = "serving.execute.eager_programs"
# modeled collective bytes of the sharded dispatches: each adds its
# entry's collective_payload_model coarse + merge bytes (a per-shard
# payload, fixed when the executable compiles)
MESH_WIRE_BYTES = "serving.mesh.wire_bytes"
# dispatches whose executable runs the grouped Pallas list scan
# (ops/ivf_scan): the resolved engine of an IVF-Flat plan, single-chip
# or list-sharded, is "pallas". Every IVF-Flat dispatch adds 1 or 0,
# so a degrade to the XLA engine reads 0, not nothing
GROUPED_SCAN_DISPATCHES = "serving.scan.grouped_dispatches"
_GROUPED_FAMILIES = ("ivf_flat", "dist_ivf_flat")


def _count_eager(n: int) -> None:
    if n:
        tracing.inc_counter(EAGER_PROGRAMS, n)


def _take_rows(x, start: int, stop: int):
    """``(x[start:stop], eager programs it launched)``: a partial slice
    of a device array is one program; a whole one is the array itself,
    and a numpy slice stays on the host."""
    partial = start > 0 or stop < x.shape[0]
    return x[start:stop], int(partial and not isinstance(x, np.ndarray))


def _concat_blocks(blocks):
    """The blocks as one batch: numpy blocks join on the host, device
    blocks in one eager concatenation."""
    if len(blocks) == 1:
        return blocks[0]
    if all(isinstance(b, np.ndarray) for b in blocks):
        return np.concatenate(blocks)
    _count_eager(1)
    return jnp.concatenate([jnp.asarray(b) for b in blocks])


def _search_results(tiles, sizes, ks=None):
    """A call's per-block ``(distances, indices)`` as host numpy arrays,
    from its output tiles ``[(d, i, rows), ...]`` in row order, each of
    whose first ``rows`` rows are real.

    Two stages: the device wait (blocking until every tile is
    computed; nothing to wait for when all are on the host already),
    then the slice stage: one batched fetch of every tile, the real
    rows joined and cut into consecutive blocks of ``sizes`` rows —
    with ``ks``, each block's first ``ks[j]`` columns (the ragged
    path's per-request ``k``). The cuts are numpy views, so no device
    program runs and none compiles per shape."""
    outs = [(d, i) for d, i, _ in tiles]
    if not all(isinstance(d, np.ndarray) for d, _ in outs):
        with tracing.host_span(DEVICE_WAIT_SPAN, hist=DEVICE_WAIT):
            jax.block_until_ready(outs)
    with tracing.host_span(SLICE_SPAN, hist=SLICE):
        # graftlint: disable=R5(the serving split is host-side by design: one batched fetch of a call's output tiles replaces per-request device-slice programs; every serving caller reads its results on the host)
        host = jax.device_get(outs)
        parts = [(d[:r], i[:r]) for (d, i), (_, _, r) in zip(host, tiles)]
        if len(parts) == 1:
            d_all, i_all = parts[0]
        else:
            d_all = np.concatenate([d for d, _ in parts])
            i_all = np.concatenate([i for _, i in parts])
        out, row = [], 0
        for j, m in enumerate(sizes):
            cols = slice(None if ks is None else ks[j])
            out.append((d_all[row:row + m, cols], i_all[row:row + m, cols]))
            row += m
        return out


def _device_cut(out_d, out_i, q: int):
    """:meth:`SearchExecutor.search`'s slice stage: a bucket's outputs
    cut on the device to the ``q`` real rows — eager programs,
    counted."""
    with tracing.host_span(SLICE_SPAN, hist=SLICE):
        (d, nd), (i, ni) = _take_rows(out_d, 0, q), _take_rows(out_i, 0, q)
        _count_eager(nd + ni)
        return d, i


def _fused_entry_fn(queries, dataset, norms, *, k: int, metric):
    """Serving wrapper for the Pallas fused brute-force kernel."""
    from raft_tpu.ops.fused_topk import fused_knn

    return fused_knn(queries, dataset, k, metric, dataset_norms=norms)


@dataclasses.dataclass
class ExecutorStats:
    """Serving-path counters (also exported via ``tracing.counters``)."""

    compile_count: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    evictions: int = 0
    warmup_seconds: float = 0.0


@dataclasses.dataclass
class _Plan:
    """Everything needed to compile and call one bucket specialization.

    Call argument order is ``(*pre, queries, *post, [filter_words])``
    — matching each family's serving function signature."""

    key: tuple
    fn: Callable
    static: dict
    pre: tuple = ()
    post: tuple = ()
    use_filter: bool = False
    qdtype: Any = jnp.float32
    qdim: int = 0
    # mesh-sharded (distributed) plans: abstract avals carry the index
    # arrays' NamedShardings, and the padded queries (and the n_valid
    # scalar) are placed with this sharding before the call
    sharded: bool = False
    qsharding: Any = None
    # distributed plans carry their modeled per-shard collective
    # payload as (family, thunk returning the collective_payload_model
    # dict) — evaluated and published as gauges only on a compile miss,
    # so the cache-hit hot path never builds the dict
    payload: Any = None
    # graftgauge probe-frequency accounting (IVF families, opt-in via
    # SearchExecutor(probe_accounting=True)): (pkey, n_lists,
    # counts_sharding, family, label, index) describing the donated
    # int32 counter plane this plan's dispatches thread through the
    # call — None keeps the compiled signature (and the executable
    # cache key) exactly as before
    probe: Any = None
    # ragged packed-batch plans: the compiled signature carries the
    # per-row probe-budget plane ((tile,) int32) right after the
    # packed queries — the ragged query-tile front of ops/ivf_scan
    ragged: bool = False
    # grafttier: lower with each operand's OWN sharding even off the
    # mesh — the tiered cold plane is committed to host memory, and
    # an aval that dropped its memory kind would compile an
    # executable that hauls the whole cold tier back into HBM per
    # call (exactly the copy the tier exists to avoid)
    keep_sharding: bool = False
    # 2-D query-sharded mesh plans (graftwire): the padded row count
    # when it differs from the bucket — the bucket rounded up to a
    # multiple of the query×list grid extent, so the query shards
    # split evenly AND each list shard's scatter-merge slice stays
    # whole. Dispatch pads/compiles to this instead of the bucket.
    rows: Optional[int] = None
    # the grouped Pallas list scan (IVF-Flat plans whose engine
    # resolved to "pallas"): (n_probes, one chip's (n_lists, m, d) list
    # shape, list dtype, query shards) — the executable's G and S are
    # recorded from it, and its dispatches counted
    grouped_scan: Any = None

    @property
    def valid_rows(self) -> bool:
        """The compiled signature carries the real row count
        ``n_valid`` (traced int32 scalar), so bucket-pad rows probe
        nothing: grouped-scan plans with unsharded queries. Ragged
        plans zero pad rows by their budgets; plans with probe
        accounting carry ``n_valid`` already."""
        return (self.grouped_scan is not None and not self.ragged
                and self.grouped_scan[3] == 1)


class _Entry:
    __slots__ = ("compiled", "cost", "digest", "family",
                 "payload_model", "wire_bytes", "grouped")

    def __init__(self, compiled, cost=None, digest="",
                 family="", payload_model=None, grouped=None):
        self.compiled = compiled
        self.cost = cost or {}
        self.digest = digest
        self.family = family
        # IVF-Flat entries: whether the executable runs the grouped
        # Pallas scan; None for every other family
        self.grouped = grouped
        # mesh entries keep their collective_payload_model dict so the
        # per-dispatch mesh spans can attach modeled per-phase bytes
        # without rebuilding the model on the hot path
        self.payload_model = payload_model
        self.wire_bytes = 0.0 if payload_model is None else float(
            payload_model["coarse_bytes"] + payload_model["merge_bytes"])


# readiness-poll quantum for per-shard arrival timing (mesh_trace):
# also the straggler timings' resolution — 50 µs resolves sub-ms skew
# while keeping the poll loop's host cost negligible per dispatch
_MESH_POLL_S = 50e-6


def _executable_cost(compiled) -> dict:
    """XLA's static accounting for one compiled executable: flops and
    bytes accessed from ``cost_analysis()``, the HBM footprint split
    from ``memory_analysis()``. Best-effort — backends that implement
    neither simply yield an empty dict (the gauges then read 0)."""
    cost: dict = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if ca:
            cost["flops"] = float(ca.get("flops", 0.0))
            cost["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
    except Exception:  # noqa: BLE001 — introspection must never fail a compile
        pass
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            arg = float(getattr(ma, "argument_size_in_bytes", 0))
            out = float(getattr(ma, "output_size_in_bytes", 0))
            tmp = float(getattr(ma, "temp_size_in_bytes", 0))
            alias = float(getattr(ma, "alias_size_in_bytes", 0))
            cost["argument_bytes"] = arg
            cost["output_bytes"] = out
            cost["temp_bytes"] = tmp
            # aliased (donated) outputs reuse argument storage
            cost["peak_hbm_bytes"] = arg + out + tmp - alias
    except Exception:  # noqa: BLE001 — introspection must never fail a compile
        pass
    return cost


def _cost_gauge_values(digest: str, cost: dict) -> dict:
    """The ``serving.executable.<digest>.*`` gauge values for one
    executable's cost dict (compile-time publication and scrape-time
    re-publication read from the same mapping)."""
    base = f"serving.executable.{digest}."
    return {
        base + "flops": cost.get("flops", 0.0),
        base + "bytes_accessed": cost.get("bytes_accessed", 0.0),
        base + "peak_hbm_bytes": cost.get("peak_hbm_bytes", 0.0),
    }


def _named_fn(fn: Callable, name: str) -> Callable:
    """Wrap ``fn`` under a distinct ``__name__`` so jax names the HLO
    module after it (``jit_<name>``). Every AOT entry compiles through
    a digest-derived name (graftflight, PR 11): a profiler trace's
    ``hlo_module`` arg then maps to exactly ONE resident executable —
    without this, every bucket/engine specialization of one family
    shares ``jit__search_impl_fn`` and device time cannot be
    attributed per executable. ``functools.wraps`` keeps the original
    signature visible (``__wrapped__``), so static/donate argname
    resolution is untouched; the name is a pure function of the cache
    key, so the persistent compilation cache stays stable across
    restarts."""

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        return fn(*args, **kwargs)

    wrapper.__name__ = name
    wrapper.__qualname__ = name
    return wrapper


def _module_name(compiled, fallback: str) -> str:
    """The compiled executable's real HLO module name (what profiler
    trace events carry in ``hlo_module``); falls back to the
    ``jit_``-prefixed wrapper name when the backend exposes no module
    introspection."""
    try:
        mods = compiled.runtime_executable().hlo_modules()
        if mods:
            return str(mods[0].name)
    except Exception:  # noqa: BLE001 — introspection must never fail a compile
        pass
    return f"jit_{fallback}"


def _plan_engine(plan: "_Plan") -> str:
    """The engine a plan resolved to — ``"pallas"`` when a Pallas
    kernel serves it."""
    if plan.key[0] == "bf_fused":
        return "pallas"
    return plan.static.get("scan_engine", plan.static.get("engine", "xla"))


def _sds(x) -> Optional[jax.ShapeDtypeStruct]:
    # None passes through: optional plan operands (e.g. the BQ
    # rerank plane of a codes-only index) are empty pytree args
    if x is None:
        return None
    return jax.ShapeDtypeStruct(jnp.shape(x), x.dtype)


def _sds_sharded(x) -> Optional[jax.ShapeDtypeStruct]:
    """Abstract aval carrying the array's sharding — mesh-sharded plans
    must lower with the real NamedShardings so the compiled executable
    accepts (and keeps) the mesh placement."""
    if x is None:
        return None
    return jax.ShapeDtypeStruct(jnp.shape(x), x.dtype,
                                sharding=getattr(x, "sharding", None))


def _mesh_key(comms) -> tuple:
    """Cache-key component identifying a mesh precisely (axis, names,
    shape, device ids) — ``str(mesh)`` alone would collide across
    different device sets of the same shape. Covers 2-D grids whole:
    BOTH axis names, the full device-grid shape, and the flat device
    ordering are in the tuple, so a transposed or re-axed mesh can
    never reuse another grid's executable. Everything here is already
    a hashable static (graftlint R1 watches this function — no lossy
    coercions on the key path)."""
    mesh = comms.mesh
    return ("mesh", comms.axis, tuple(mesh.axis_names),
            tuple(mesh.devices.shape),
            tuple(d.id for d in mesh.devices.flat))


def _sig(*arrays) -> tuple:
    return tuple((tuple(a.shape), str(a.dtype)) for a in arrays)


def _pow2_at_least(n: int, floor: int) -> int:
    """Smallest power-of-two multiple of ``floor`` at/above ``n`` —
    the ragged params-class rounding (a pow2 ladder on the *params*
    axis replaces the old one on the *batch* axis, so the executable
    count stays logarithmic while the query tile carries no pad)."""
    b = floor
    while b < n:
        b *= 2
    return b


def _filter_spec(fw) -> tuple:
    if fw is None:
        return ("nofilter",)
    return ("filter", fw.ndim, fw.shape[-1], str(fw.dtype))


class SearchExecutor:
    """Compile-free steady-state search across all ANN index families.

    Example::

        ex = SearchExecutor(res)
        ex.warmup(index, buckets=(64, 256), k=10)   # cold-start, AOT
        d, i = ex.search(index, queries, 10)        # never traces again

    Constructor args:
      res: shared :class:`Resources` (placement, workspace budget).
      min_bucket/max_bucket: power-of-two bucket ladder bounds. Batches
        larger than ``max_bucket`` are tiled at ``max_bucket`` with the
        ragged tail padded into the bucket (all tiles dispatched before
        any result is fetched).
      max_entries: LRU capacity of the executable cache.
      mesh_trace: record graftscope-v2 mesh spans around every
        mesh-sharded dispatch — the three modeled phase spans
        (coarse select / scan / merge, bytes from the entry's
        ``collective_payload_model``) plus per-shard readiness timings
        through the straggler detector
        (``serving.mesh.{shard_skew,slowest_shard}``). Costs a
        host-side readiness wait per dispatch AFTER it is enqueued
        (the batcher blocks on results anyway — but an oversized
        batch's tiles serialize, since each tile's poll completes
        before the next dispatches), compiles nothing, and adds
        nothing inside the traced program; default off so
        latency-pipelined callers (the bench riders) keep fully async
        dispatch.
      probe_accounting: graftgauge device-side probe-frequency
        accounting for the IVF families (single-chip and mesh): each
        dispatch scatter-adds its selected probe ids into a donated
        per-index int32 counter plane inside the compiled program —
        the plane threads through calls, so steady state stays
        zero-recompile and search results stay bit-identical (the
        results never read the plane). The counters are fetched ONLY
        at scrape time
        (:meth:`probe_frequencies` / :meth:`publish_probe_gauges` —
        one device fetch per plane per scrape, never per dispatch).
        Default off: enabling changes the compiled signature, so it is
        part of the executable cache key.
      ragged_tile: row count of the ragged plan family's packed batch
        shape (:meth:`search_ragged`). Every ragged dispatch runs
        ``(ragged_tile, dim)`` queries — under load the serving
        batcher keeps the tile full via tile-boundary splits, so pad
        waste collapses to timer-fired partial tiles.
      ragged_tile_small: opt-in SMALL tile of the dual-tile pair
        (e.g. 64 next to a 512 large tile): a packed batch whose
        total rows fit it dispatches through the small executable,
        cutting partial-tile pad at light load. Tile selection is a
        dispatch-time row-count check — both tiles share one
        :meth:`ragged_key`, so the params-class ladder does not fork
        and steady state stays at ≤ 2 executables per (index shapes,
        params class). Default off (one tile).
    """

    def __init__(self, res: Optional[Resources] = None, *,
                 min_bucket: int = 8, max_bucket: int = 4096,
                 max_entries: int = 64, mesh_trace: bool = False,
                 probe_accounting: bool = False,
                 ragged_tile: int = 256,
                 ragged_tile_small: Optional[int] = None):
        self.res = ensure_resources(res)
        expect(0 < min_bucket <= max_bucket,
               f"need 0 < min_bucket <= max_bucket, got "
               f"({min_bucket}, {max_bucket})")
        buckets = []
        b = min_bucket
        while b < max_bucket:
            buckets.append(b)
            b *= 2
        buckets.append(max_bucket)
        self.buckets: Tuple[int, ...] = tuple(buckets)
        self.max_entries = max_entries
        expect(ragged_tile > 0, "ragged_tile must be > 0")
        expect(ragged_tile_small is None
               or 0 < ragged_tile_small < ragged_tile,
               "ragged_tile_small must be in (0, ragged_tile)")
        # the ragged plan family's packed-batch shape(s): every ragged
        # dispatch runs (tile, dim) queries, so one AOT entry per
        # (index shapes, params class, tile) serves every load shape —
        # the bucket ladder collapsed to one executable, or two with
        # the opt-in dual tile (ragged_tile_small): a packed batch
        # that fits the small tile dispatches through it, cutting
        # partial-tile pad at light load WITHOUT forking the params
        # class (both tiles share one ragged_key, so admission
        # grouping and warmup are tile-oblivious)
        self.ragged_tile = ragged_tile
        self.ragged_tile_small = ragged_tile_small
        self.mesh_trace = mesh_trace
        self.probe_accounting = probe_accounting
        # graftgauge probe-frequency planes: pkey -> device counter
        # array holding the CURRENT scrape window (threaded donated
        # through dispatches, so every bucket/engine entry of one
        # index shares ONE plane; reset to zero as each scrape claims
        # its window), the scrape-side descriptors, the host-side
        # int64 lifetime totals, and the pkeys whose index a weakref
        # finalizer reported garbage-collected (drained under the
        # lock — GC callbacks only append)
        self._probe_state: dict = {}   # guarded-by: _lock
        self._probe_info: dict = {}    # guarded-by: _lock
        self._probe_totals: dict = {}  # guarded-by: _lock
        # NOT lock-guarded: GC finalizers append without the lock
        # (GIL-atomic); the list drains under _lock
        self._probe_dead: list = []
        # graftledger (PR 13): an attached MemoryLedger samples a
        # live-memory watermark after every dispatch (host-only
        # backend call — nothing enters the compiled program, so the
        # cache keys and zero-recompile contract are untouched)
        self._memwatch = None
        self.stats = ExecutorStats()
        self._cache: "collections.OrderedDict[tuple, _Entry]" = (  # guarded-by: _lock
            collections.OrderedDict())
        # digest -> {family, bucket, flops, bytes_accessed, ...}: the
        # JSON-snapshot view of the per-executable cost gauges
        self._cost_table: dict = {}  # guarded-by: _lock
        # multi-threaded frontends share one executor: the cache and
        # the donated probe planes must hand off atomically (two
        # threads donating the same plane would hit jax's
        # deleted-array error). Dispatch is async, so holding the lock
        # through the executable call serializes only enqueueing.
        self._lock = threading.RLock()

    # -- bucketing ----------------------------------------------------------

    def bucket_for(self, q: int) -> int:
        """Smallest bucket >= q (the last bucket for anything larger)."""
        for b in self.buckets:
            if q <= b:
                return b
        return self.buckets[-1]

    # -- public API ---------------------------------------------------------

    def warmup(self, index, buckets=None, *, k: int, params=None,
               sample_filter=None, **kw) -> float:
        """AOT-compile the executables for ``buckets`` (default: the
        whole ladder) so first-traffic latency is a cache *call*, not a
        compile. Returns wall seconds spent (also accumulated into the
        ``serving.warmup_seconds`` counter). With a persistent
        compilation cache configured, a restarted process's warmup
        loads artifacts instead of re-compiling."""
        fw = self._resolve_filter(sample_filter)
        t0 = time.perf_counter()
        for b in (buckets if buckets is not None else self.buckets):
            expect(b in self.buckets, f"bucket {b} not in {self.buckets}")
            plan = self._plan(index, params, k, b, fw, kw)
            self._get_entry(plan, plan.rows or b, k)
        dt = time.perf_counter() - t0
        self.stats.warmup_seconds += dt
        tracing.inc_counter("serving.warmup_seconds", dt)
        return dt

    def search(self, index, queries, k: int, params=None,
               sample_filter=None, trace_ids: Tuple[int, ...] = (),
               **kw) -> Tuple[jax.Array, jax.Array]:
        """Bucketed, compile-free search. Returns (distances (q, k),
        indices (q, k) int32) as device arrays, bit-identical to the
        direct per-family ``search`` entry point: library callers
        compose them on the device, so the result is not waited for,
        and a batch short of its bucket pays one cut on the device.
        Extra ``kw`` are family-specific knobs
        (brute force: ``db_tile``, ``approx``). ``trace_ids`` tags the
        dispatch's flight-recorder spans (mesh plans with
        ``mesh_trace`` on) — the serving batcher passes its members'
        ids so mesh stragglers attribute back to requests."""
        expect(len(np.shape(queries)) == 2, "queries must be (q, dim)")
        fw = self._resolve_filter(sample_filter)
        return self._search(index, [queries], k, params, fw, kw,
                            trace_ids, host=False)

    def coalesce_key(self, index, k: int, params=None, sample_filter=None,
                     **kw) -> tuple:
        """Hashable compatibility key for request coalescing: two
        submissions may share one bucketed call iff their keys are
        equal. This is the executor's plan key with the bucket stripped
        (any bucket serves any compatible batch) and the index's
        identity mixed in (two indexes with equal shapes must never
        coalesce). The serving batcher groups its queues by this."""
        fw = self._resolve_filter(sample_filter)
        plan = self._plan(index, params, k, self.buckets[0], fw, kw)
        # plan.key is (family, bucket, *specialization) in every family
        return (id(index), plan.key[0]) + tuple(plan.key[2:])

    def search_blocks(self, index, blocks, k: int, params=None,
                      sample_filter=None, trace_ids: Tuple[int, ...] = (),
                      **kw):
        """Batch-handle entry point for the serving frontend: run the
        per-request query blocks of ONE coalesced micro-batch as a
        single bucketed call and split the results back per block.

        ``blocks`` is a sequence of (m_j, dim) query arrays that agreed
        on :meth:`coalesce_key`; a 2-D ``sample_filter`` must be the
        row-wise concatenation matching the blocks. Returns a list of
        per-block ``(distances, indices)`` pairs as HOST (numpy)
        arrays, each bit-identical to a direct :meth:`search` of that
        block alone (bucketing pads with inert rows, so coalescing
        cannot perturb results).

        The serving frontend hands every result to a caller that reads
        it on the host, so the call waits for the device and fetches
        the bucket's outputs in one batch, then cuts each block's rows
        in numpy: no per-shape slice, copy or concatenate program runs
        beside the executable.

        Every family concatenates — CAGRA's seeds became a pure
        function of query content (PR 16), which retired the last
        per-block dispatch special case."""
        expect(len(blocks) > 0, "search_blocks needs at least one block")
        fw = self._resolve_filter(sample_filter)
        return self._search(index, blocks, k, params, fw, kw, trace_ids,
                            host=True)

    def _search(self, index, blocks, k, params, fw, kw, trace_ids, host):
        """The bucketed path of :meth:`search` and :meth:`search_blocks`:
        the blocks as one batch, tiled at the top bucket. With ``host``,
        per-block ``(distances, indices)`` on the host; without (one
        block), its ``(distances, indices)`` on the device."""
        sizes = [int(np.shape(b)[0]) for b in blocks]
        q = sum(sizes)
        if q == 0:
            xp = np if host else jnp
            tiles = [(xp.zeros((0, k), np.float32),
                      xp.zeros((0, k), np.int32), 0)]
        elif q <= self.buckets[-1]:
            tiles = [self._run(index, blocks, k, params, fw, kw,
                               trace_ids=trace_ids, host=host)]
        else:
            # tile oversized batches at the top bucket; every tile runs
            # the same executable and all tiles dispatch before any
            # fetch
            max_b = self.buckets[-1]
            queries = _concat_blocks(blocks)
            tiles = []
            for start in range(0, q, max_b):
                qt, n = _take_rows(queries, start, start + max_b)
                fwt = fw
                if fw is not None and fw.ndim == 2:
                    fwt, n_fw = _take_rows(fw, start, start + max_b)
                    n += n_fw
                _count_eager(n)
                tiles.append(self._run(index, [qt], k, params, fwt, kw,
                                       trace_ids=trace_ids, host=host))
        if host:
            return _search_results(tiles, sizes)
        if len(tiles) == 1:
            return tiles[0][:2]
        with tracing.host_span(SLICE_SPAN, hist=SLICE):
            _count_eager(2)
            return (jnp.concatenate([d for d, _, _ in tiles]),
                    jnp.concatenate([i for _, i, _ in tiles]))

    # -- ragged packed-batch plan family ------------------------------------

    def ragged_key(self, index, k: int, params=None, sample_filter=None,
                   **kw) -> Optional[tuple]:
        """Hashable packing key for the ragged continuous-batching
        path, or ``None`` when this (index, params, k) combination is
        not servable ragged — the caller then falls back to
        :meth:`coalesce_key` and the bucketed path
        (:meth:`ragged_fallback_reason` names why).

        Raggable: every IVF family — flat, PQ, BQ, single-chip AND
        list-sharded mesh — through its membership-masked list-major
        engine with exact coarse select, and CAGRA (PR 16: seeds are
        a pure function of query content; the per-row plane carries
        iteration budgets and the params class rounds
        ``max_iterations``). The documented non-raggable residue:
        CAGRA whose ``k`` class cap exceeds ``itopk_size`` (the beam
        buffer is the result surface), ``coarse_algo="approx"`` (no
        prefix property at the class cap), the rank-major engines (no
        membership mask), codes-only BQ (resolves to the rank
        estimate scan), brute force (no probe plane), ``TieredIvf``
        (the dual-tier fetch plan is placement-epoch state — see
        :meth:`ragged_fallback_reason`), and 2-D query-sharded mesh
        grids (served zero-recompile by the bucketed 2-D plans
        instead). The int8 probe wire rides ragged since its scales
        went block-independent (per-row affine over the FULL local
        coarse block — codes no longer depend on the candidate set,
        so cap-vs-solo bit-identity holds).

        Two submissions may share one packed ragged batch iff their
        keys are equal. Unlike :meth:`coalesce_key`, ``n_probes`` and
        ``k`` do NOT fork the key directly — they round up to a
        power-of-two *params class* (``n_probes`` resolves per row
        through the engines' membership mask, ``k`` through a
        caller-side column slice), so mixed-``n_probes``/``k`` traffic
        under one class cap shares ONE executable (two with the
        opt-in dual tile — the tile is selected at dispatch and is
        deliberately NOT part of this key). The degradation ladder's
        params override feeds this key like any other params (the
        batcher applies it before keying), so a degraded
        specialization that changes only ``n_probes`` keeps packing
        with live traffic. Mesh keys fold the wire knobs in through
        ``kw`` — mesh devices and params-class tuples stay hashable
        statics (graftlint R1 covers this construction)."""
        fw = self._resolve_filter(sample_filter)
        spec, _ = self._ragged_resolve(index, k, params, fw, kw)
        if spec is None:
            return None
        return (id(index), spec["family"] + "_ragged",
                str(index.metric), spec["engine"], spec["np_class"],
                spec["k_class"], _filter_spec(fw),
                tuple(sorted((n, str(v)) for n, v in kw.items())))

    def ragged_fallback_reason(self, index, k: int, params=None,
                               sample_filter=None, **kw) -> Optional[str]:
        """Why this (index, params, k) combination is NOT servable by
        the ragged plan family (``None`` when it is) — the explicit
        plan-key reason the serving batcher's bucketed fallback can be
        pinned against. The strings are stable test surface: each
        names the residue class, not the call site."""
        fw = self._resolve_filter(sample_filter)
        _, reason = self._ragged_resolve(index, k, params, fw, kw)
        return reason

    def warmup_ragged(self, index, *, k: int, params=None,
                      sample_filter=None, **kw) -> float:
        """AOT-compile the ragged executable(s) of this (index,
        params-class) — one per configured tile (a single tile by
        default, the small+large pair with ``ragged_tile_small``) —
        the whole warmup the ragged path needs, where the bucketed
        ladder compiled one executable per bucket. Raises on
        combinations :meth:`ragged_key` would refuse."""
        fw = self._resolve_filter(sample_filter)
        spec, reason = self._ragged_resolve(index, k, params, fw, kw)
        expect(spec is not None,
               "index/params combination is not servable by the ragged "
               f"plan family: {reason}")
        t0 = time.perf_counter()
        for tile in self._ragged_tiles():
            plan = self._plan_ragged(index, fw, spec, tile)
            self._get_entry(plan, tile, spec["k_class"])
        dt = time.perf_counter() - t0
        self.stats.warmup_seconds += dt
        tracing.inc_counter("serving.warmup_seconds", dt)
        return dt

    def _place_ragged_chunk(self, plan: _Plan, qt, rpt):
        """One packed tile's operands, placed for the plan: mesh
        ragged plans put the tile and its budget plane replicated in
        ONE batched transfer (exactly one placement per dispatched
        tile — the same per-dispatch transfer the bucketed mesh path
        pays); single-chip plans pass host arrays straight through
        (the compiled call owns the transfer)."""
        eager = int(isinstance(rpt, np.ndarray))
        rpt = jnp.asarray(rpt)
        if plan.qsharding is None:
            _count_eager(eager)
            return qt, rpt
        _count_eager(eager + int(isinstance(qt, np.ndarray)
                                 or qt.dtype != plan.qdtype))
        return jax.device_put([jnp.asarray(qt, plan.qdtype), rpt],
                              [plan.qsharding, plan.qsharding])

    def _ragged_tiles(self) -> Tuple[int, ...]:
        """The configured packed-tile ladder, small first (≤ 2 — the
        dual-tile acceptance bound is structural)."""
        if self.ragged_tile_small is not None:
            return (self.ragged_tile_small, self.ragged_tile)
        return (self.ragged_tile,)

    def _ragged_tile_for(self, total: int) -> int:
        """Dispatch-time tile selection: the small tile iff the whole
        packed batch fits it — a host-side row-count check, so the
        choice costs nothing and never forks the packing key."""
        small = self.ragged_tile_small
        if small is not None and total <= small:
            return small
        return self.ragged_tile

    def search_ragged(self, index, blocks, ks, params_list=None,
                      sample_filter=None,
                      trace_ids: Tuple[int, ...] = (), **kw):
        """Packed ragged-batch entry point: run several requests'
        query blocks — possibly with DIFFERENT per-request ``k`` and
        ``params.n_probes`` — as packed ``(tile, dim)`` calls of ONE
        compiled executable (per configured tile), and split the
        results back per block. Serves every raggable family through
        the same locked dispatch core: single-chip IVF flat/PQ/BQ and
        the list-sharded mesh families (whose packed tile and budget
        plane place replicated, with the list-sharded probe plane
        threaded exactly as bucketed mesh plans thread it; ``kw``
        carries the mesh wire knobs). ``mesh_trace`` span recording is
        a bucketed-dispatch feature — ragged mesh dispatches skip it
        (the batcher's stage spans still cover the packed call).

        ``blocks`` is a sequence of (m_j, dim) query arrays; ``ks``
        and ``params_list`` give each block's ``k`` / search params (a
        scalar/single value is shared by all). Every block must agree
        on :meth:`ragged_key` — the serving batcher groups by it. A
        2-D ``sample_filter`` is the row-wise concatenation matching
        the blocks (1-D shared words pass through, exactly like
        :meth:`search_blocks`).

        Blocks pack adjacently into the tile (the tail padded with
        inert zero rows whose probe budget is 0); totals past one tile
        stream through the SAME executable in tile-sized chunks.
        Returns per-block ``(distances, indices)`` as HOST (numpy)
        arrays, each bit-identical to a direct bucketed :meth:`search`
        of that block alone (total-order coarse select +
        membership-masked probes + total-order merges make the packed
        results independent of what else shares the tile).

        The per-request split is deliberately host-side, as in
        :meth:`search_blocks`: one batched device fetch of the packed
        tiles replaces per-(offset, rows, k) device slices — whose
        tiny programs would otherwise compile per load shape,
        resurrecting through the back door the shape churn the ONE
        packed executable exists to kill. The serving batcher hands
        every result to a caller that reads it on the host, so the
        fetch costs what the caller was about to pay anyway."""
        expect(len(blocks) > 0, "search_ragged needs at least one block")
        # the prepare stage ends inside the locked core, where the
        # first tile's enqueue begins
        with tracing.host_span(PREPARE_SPAN, hist=PREPARE) as prepare:
            n = len(blocks)
            if not isinstance(ks, (list, tuple)):
                ks = [ks] * n
            if not isinstance(params_list, (list, tuple)):
                params_list = [params_list] * n
            expect(len(ks) == n and len(params_list) == n,
                   "ks/params_list must match blocks")
            fw = self._resolve_filter(sample_filter)
            # blocks repeat few distinct (params, k) pairs, and resolution
            # builds a base plan (one resolution authority — see
            # _ragged_resolve): memoize per distinct pair so a packed
            # dispatch of n blocks resolves once per pair, not n times
            memo: dict = {}
            specs = []
            for kj, pj in zip(ks, params_list):
                mk = (pj, kj)
                if mk not in memo:
                    memo[mk] = self._ragged_resolve(index, kj, pj, fw, kw)
                s, reason = memo[mk]
                expect(s is not None,
                       "a block is not servable by the ragged plan "
                       f"family: {reason}")
                specs.append(s)
            classes = {(s["family"], s["engine"], s["np_class"],
                        s["k_class"]) for s in specs}
            expect(len(classes) == 1,
                   "blocks must agree on the ragged params class — group "
                   "submissions by SearchExecutor.ragged_key")
            spec = specs[0]
            k_class = spec["k_class"]
            sizes = [int(np.shape(b)[0]) for b in blocks]
            for b in blocks:
                expect(int(np.shape(b)[1]) == index.dim,
                       "query dim mismatch")
            total = sum(sizes)
            if total == 0:
                return [(np.zeros((0, kj), np.float32),
                         np.zeros((0, kj), np.int32)) for kj in ks]
            if fw is not None and fw.ndim == 2:
                expect(int(fw.shape[0]) == total,
                       "2-D filter rows must match the packed query rows")
            tile = self._ragged_tile_for(total)
            plan = self._plan_ragged(index, fw, spec, tile)

            # host-side packing: adjacent blocks, zero pad rows, per-row
            # probe budgets (0 on pads). numpy blocks (the serving path)
            # pack with zero device ops; device arrays fall back to one
            # concat + pad program per distinct total
            from raft_tpu.ops.ivf_scan import ragged_row_probes

            padded_total = -(-total // tile) * tile
            row_probes = ragged_row_probes(
                sizes, [s["n_probes"] for s in specs], padded_total)
            if all(isinstance(b, np.ndarray) for b in blocks):
                packed = np.zeros((padded_total, index.dim), np.float32)
                r = 0
                for b, m in zip(blocks, sizes):
                    packed[r:r + m] = b
                    r += m
            else:
                from raft_tpu.neighbors._batching import pad_rows

                packed = pad_rows(
                    jnp.concatenate([jnp.asarray(b, jnp.float32)
                                     for b in blocks]), padded_total)
                _count_eager(2 if padded_total > total else 1)
            fwp = fw
            if fw is not None and fw.ndim == 2 and padded_total > total:
                fwp = self._pad(fw, padded_total, fw.dtype)

            # pad-waste attribution: the aggregate serving.execute.rows /
            # .padded_rows counters (bumped per dispatch in the locked
            # core) additionally split per (params class, tile) here, so
            # metrics.derived()["pad_waste_by_class"] and the exporter's
            # labeled family attribute waste to the small-vs-large tile
            # choice. Class labels are pow2-bounded, tiles ≤ 2 — the
            # counter-name cardinality is structural, not client-driven.
            split = (f"p{spec['np_class']}.t{tile}")
            outs = []
            with self._lock:
                for start in range(0, padded_total, tile):
                    q_real = min(total - start, tile)
                    chunk, eager = _take_rows(packed, start, start + tile)
                    qt, rpt = self._place_ragged_chunk(
                        plan, chunk, row_probes[start:start + tile])
                    args = [qt, rpt]
                    args.extend(plan.post)
                    if plan.use_filter:
                        fwt = fwp
                        if fwp is not None and fwp.ndim == 2:
                            fwt, n = _take_rows(fwp, start, start + tile)
                            eager += n
                        args.append(fwt)
                    _count_eager(eager)
                    _, out_d, out_i, _ = self._execute_entry_locked(
                        plan, tile, k_class, args, q_real,
                        prepare=prepare)
                    tracing.inc_counters({
                        f"serving.execute.rows.{split}": q_real,
                        f"serving.execute.padded_rows.{split}": tile,
                    })
                    # only ENQUEUE happens under the lock — every tile
                    # dispatches before anything is fetched, and
                    # concurrent searches/scrapes are not blocked for a
                    # device execution
                    outs.append((out_d, out_i, q_real))
        # per-request k: a column slice of the class-cap top-k — the
        # merge is a total order, so the first k_j columns ARE the solo
        # top-k_j
        return _search_results(outs, sizes, ks)

    # the documented non-raggable residue, as stable reason strings —
    # what ragged_fallback_reason returns and the fallback tests pin
    _RAGGED_RESIDUE = {
        "cagra_k": "cagra: the k class cap exceeds itopk_size, so the "
                   "class executable's beam buffer would differ from "
                   "the solo run's — bucketed path",
        "brute_force": "brute_force: no probe plane to budget per "
                       "row — bucketed path",
        "approx": "coarse_algo='approx' has no prefix property at "
                  "the class cap — bucketed path",
        "rank": "scan_engine resolved to the rank-major scan, which "
                "has no membership mask — bucketed path",
        "kw": "family-specific kwargs stay on the bucketed path",
        "empty": "empty index or k <= 0 — bucketed path",
        "query_axis": "query_axis grids serve through the bucketed "
                      "2-D plans (zero-recompile, scatter-merged) — "
                      "no ragged front yet",
        "dist_filter": "distributed searches have no sample_filter "
                       "support",
        "family": "index family has no ragged front — bucketed path",
    }

    def _ragged_resolve(self, index, k: int, params, fw, kw):
        """Resolve one request onto the ragged plan family:
        ``(spec, None)`` with the family tag, resolved engine and
        power-of-two class caps, or ``(None, reason)`` when the
        request must stay on the bucketed path. ONE resolver covers
        every raggable family — flat/PQ/BQ, single-chip and mesh —
        because the plan itself derives from the family's bucketed
        plan (:meth:`_plan_ragged`); only raggability and the class
        rounding live here."""
        from raft_tpu.distributed.bq import DistributedIvfBq
        from raft_tpu.distributed.ivf import (
            DistributedIvfFlat,
            DistributedIvfPq,
        )
        from raft_tpu.neighbors import ivf_bq, ivf_flat, ivf_pq
        from raft_tpu.neighbors import tiered as tiered_mod

        reasons = self._RAGGED_RESIDUE
        families = (
            # graftcast: the tiered containers joined the ragged
            # family — their plans are placement-generation-stable
            # (shape-keyed, re-snapshotted per dispatch), so epochs
            # permute placement without touching the one executable
            (tiered_mod.TieredIvf, "tiered_ivf",
             tiered_mod.TieredSearchParams, None),
            (tiered_mod.TieredIvfPq, "tiered_ivf_pq",
             ivf_pq.IvfPqSearchParams, None),
            (tiered_mod.TieredIvfBq, "tiered_ivf_bq",
             ivf_bq.IvfBqSearchParams, None),
            (DistributedIvfFlat, "dist_ivf_flat",
             ivf_flat.IvfFlatSearchParams, None),
            (DistributedIvfPq, "dist_ivf_pq",
             ivf_pq.IvfPqSearchParams, None),
            (DistributedIvfBq, "dist_ivf_bq",
             ivf_bq.IvfBqSearchParams, None),
            (ivf_flat.IvfFlatIndex, "ivf_flat",
             ivf_flat.IvfFlatSearchParams, None),
            (ivf_pq.IvfPqIndex, "ivf_pq",
             ivf_pq.IvfPqSearchParams, None),
            (ivf_bq.IvfBqIndex, "ivf_bq", ivf_bq.IvfBqSearchParams,
             None),
        )
        family = params_cls_type = None
        for typ, fam, pcls, refusal in families:
            if isinstance(index, typ):
                if refusal is not None:
                    return None, reasons[refusal]
                family, params_cls_type = fam, pcls
                break
        if family is None:
            from raft_tpu.neighbors.cagra import CagraIndex

            if isinstance(index, CagraIndex):
                return self._ragged_resolve_cagra(index, k, params, fw,
                                                  kw)
            from raft_tpu.neighbors.brute_force import BruteForceIndex

            if isinstance(index, BruteForceIndex):
                return None, reasons["brute_force"]
            return None, reasons["family"]
        mesh = family.startswith("dist_")
        if mesh:
            if kw.get("query_axis") is not None:
                return None, reasons["query_axis"]
            if not set(kw) <= {"probe_mode", "wire_dtype",
                               "probe_wire_dtype"}:
                return None, reasons["kw"]
            if fw is not None:
                return None, reasons["dist_filter"]
        elif kw:
            return None, reasons["kw"]
        params = params or params_cls_type()
        if params.coarse_algo != "exact":
            return None, reasons["approx"]
        if params.scan_engine == "rank":
            return None, reasons["rank"]
        # DistributedIvfBq carries no max_list_size property; its
        # packed-codes extent plays the same role
        extent = getattr(index, "max_list_size", None)
        if extent is None:
            extent = index.codes.shape[1]
        if extent <= 0 or k <= 0:
            return None, reasons["empty"]
        n_probes = min(params.n_probes, index.n_lists)
        np_class = min(_pow2_at_least(n_probes, 8), index.n_lists)
        k_class = _pow2_at_least(k, 8)
        # the resolved engine comes from the family's OWN bucketed
        # plan at the class caps — one resolution authority, so the
        # raggability decision and the compiled plan cannot disagree
        params_cls = dataclasses.replace(params, n_probes=np_class)
        base = self._plan(index, params_cls, k_class, self.buckets[0],
                          fw, kw)
        engine = base.static["scan_engine"]
        if engine not in (("xla",) if family.endswith("ivf_pq")
                          else ("pallas", "xla")):
            return None, reasons["rank"]
        return {"family": family, "engine": engine,
                "np_class": np_class, "k_class": k_class,
                "n_probes": n_probes, "params_cls": params_cls,
                "kw": kw}, None

    def _ragged_resolve_cagra(self, index, k: int, params, fw, kw):
        """CAGRA onto the ragged plan family (PR 16): seeds are a pure
        function of query content, so any split packs; the per-row
        budget plane carries each request's ITERATION budget (the role
        ``n_probes`` plays for the IVF families), and the params class
        rounds ``max_iterations`` up to a power of two — budget no-op
        iterations are bit-neutral in both engines, so each row equals
        its solo bucketed run. Only the class ``k`` cap must stay
        under ``itopk_size``: the beam buffer IS the result surface,
        and widening it would change the beam itself."""
        from raft_tpu.neighbors import cagra as m

        reasons = self._RAGGED_RESIDUE
        if kw:
            return None, reasons["kw"]
        params = params or m.CagraSearchParams()
        if index.graph.shape[0] == 0 or k <= 0:
            return None, reasons["empty"]
        k_class = _pow2_at_least(k, 8)
        if k_class > params.itopk_size:
            return None, reasons["cagra_k"]
        cfg = m.derive_search_config(params, index, k)
        iters_class = _pow2_at_least(cfg["max_iters"], 8)
        params_cls = dataclasses.replace(params,
                                         max_iterations=iters_class)
        base = self._plan(index, params_cls, k_class, self.buckets[0],
                          fw, kw)
        return {"family": "cagra", "engine": base.static["engine"],
                "np_class": iters_class, "k_class": k_class,
                "n_probes": cfg["max_iters"], "params_cls": params_cls,
                "kw": kw}, None

    # family tag -> (module, attr) of the packed ragged-batch twin of
    # that family's bucketed serving fn — each a thin wrapper over the
    # SAME search body with the per-row budget hook live, so the two
    # paths cannot drift. Module paths (not objects): the mapping must
    # not force the distributed imports at module load
    _RAGGED_FNS = {
        "ivf_flat": ("raft_tpu.neighbors.ivf_flat",
                     "_search_ragged_fn"),
        "ivf_pq": ("raft_tpu.neighbors.ivf_pq", "_search_ragged_fn"),
        "ivf_bq": ("raft_tpu.neighbors.ivf_bq", "_search_ragged_fn"),
        "tiered_ivf": ("raft_tpu.neighbors.tiered",
                       "_tiered_search_ragged_fn"),
        "tiered_ivf_pq": ("raft_tpu.neighbors.tiered",
                          "_tiered_pq_search_ragged_fn"),
        "tiered_ivf_bq": ("raft_tpu.neighbors.tiered",
                          "_tiered_bq_search_ragged_fn"),
        "cagra": ("raft_tpu.neighbors.cagra", "_search_ragged_fn"),
        "dist_ivf_flat": ("raft_tpu.distributed.ivf",
                          "_dist_search_ragged_fn"),
        "dist_ivf_pq": ("raft_tpu.distributed.ivf",
                        "_dist_search_ragged_pq_fn"),
        "dist_ivf_bq": ("raft_tpu.distributed.bq",
                        "_dist_search_ragged_bq_fn"),
    }

    def _ragged_fn(self, family: str) -> Callable:
        """Resolve one family's ragged serving fn (:data:`_RAGGED_FNS`
        — a missing family is a KeyError, the single point a new
        raggable family must register at)."""
        import importlib

        module, attr = self._RAGGED_FNS[family]
        return getattr(importlib.import_module(module), attr)

    def _plan_ragged(self, index, fw, spec, tile: int) -> _Plan:
        """One ragged plan builder for every raggable family — THE
        deletion this PR exists for: the plan DERIVES from the
        family's bucketed plan at the params-class caps (same arrays,
        same statics minus the pinned-exact ``coarse_algo``, same
        probe plumbing, same shardings/payload model), with
        the serving fn swapped for the family's ragged twin and the
        family tag marked ``_ragged``. No per-family ragged plan code
        paths remain — a family change lands in ONE builder and both
        path families inherit it. Probe planes are shared with the
        bucketed plans (same pkey), so one cumulative histogram
        covers an index however its traffic splits across the two
        path families."""
        base = self._plan(index, spec["params_cls"], spec["k_class"],
                          tile, fw, spec["kw"])
        # coarse_algo is pinned exact; query_axis is always None here
        # (2-D grids are refused upstream) and the ragged fns don't
        # take it
        statics = {n: v for n, v in base.static.items()
                   if n not in ("coarse_algo", "query_axis")}
        key = (base.key[0] + "_ragged",) + base.key[1:]
        return dataclasses.replace(
            base, key=key, fn=self._ragged_fn(base.key[0]),
            static=statics, ragged=True)

    def ragged_executables(self, family: Optional[str] = None) -> int:
        """Resident ragged-plan executables — the acceptance surface
        of the one-executable contract (steady state: at most one per
        (index shapes, params class) per configured tile — ≤ 2 per
        family with the dual tile). ``family`` filters to one family
        tag (e.g. ``"dist_ivf_bq"``)."""
        with self._lock:
            return sum(
                1 for key in self._cache
                if key and isinstance(key[0], str)
                and key[0].endswith("_ragged")
                and (family is None or key[0] == family + "_ragged"))

    # -- internals ----------------------------------------------------------

    def _resolve_filter(self, sample_filter):
        if sample_filter is None:
            return None
        from raft_tpu.neighbors.filters import resolve_filter_words

        return resolve_filter_words(sample_filter)

    def _run(self, index, blocks, k, params, fw, kw,
             trace_ids: Tuple[int, ...] = (), host: bool = False):
        # grafttier placement race: an epoch swap DONATES the old hot
        # plane / slot maps, and a dispatch that captured the
        # pre-swap generation but enqueued after the swap finds its
        # operands deleted (jax spells this RuntimeError or
        # INVALID_ARGUMENT ValueError depending on the path). The
        # swap serializes its enqueues with dispatch under the
        # executor lock, so each failure means a COMPLETE newer
        # generation is already in the container — rebuild and retry
        # against it. Bounded: every retry needs a fresh swap to have
        # landed in the capture→enqueue window, so under any sane
        # epoch cadence one retry is the norm; the bound guards
        # against a pathological swap storm (any other error
        # re-raises immediately). The final attempt runs WHOLLY under
        # the dispatch lock: plan capture and enqueue become atomic
        # against apply_plan (which swaps under this same RLock), so
        # a swap storm can starve at most four attempts — the fifth
        # cannot observe a donated plane. Lock order stays
        # executor._lock -> container._swap_lock, the order
        # apply_plan already established.
        for _ in range(4):
            try:
                return self._run_once(index, blocks, k, params, fw,
                                      kw, trace_ids=trace_ids, host=host)
            except (RuntimeError, ValueError) as e:
                if "deleted" not in str(e).lower():
                    raise
                tracing.inc_counter(
                    "serving.execute.placement_retries")
        with self._lock:
            return self._run_once(index, blocks, k, params, fw, kw,
                                  trace_ids=trace_ids, host=host)

    def _run_once(self, index, blocks, k, params, fw, kw,
                  trace_ids: Tuple[int, ...] = (), host: bool = False):
        """One bucketed dispatch of ``blocks`` (one batch of at most
        the top bucket's rows): the output tile ``(distances, indices,
        q)``, whose first ``q`` rows are the blocks' results. With
        ``host``, the outputs come back as the device arrays, possibly
        still computing (:func:`_search_results` reads them); without,
        cut on the device to the ``q`` rows."""
        # the prepare stage ends inside the locked core, where the
        # enqueue begins: waiting for the lock is part of preparing
        with tracing.host_span(PREPARE_SPAN, hist=PREPARE) as prepare:
            queries = _concat_blocks(blocks)
            q = int(np.shape(queries)[0])
            bucket = self.bucket_for(q)
            plan = self._plan(index, params, k, bucket, fw, kw)
            expect(int(np.shape(queries)[1]) == plan.qdim,
                   "query dim mismatch")

            # 2-D query-sharded plans round the padded block up to the
            # grid extent (plan.rows); every other plan pads to the
            # bucket
            rows = plan.rows or bucket
            qp = self._pad(queries, rows, plan.qdtype)
            if plan.qsharding is not None:
                qp = jax.device_put(qp, plan.qsharding)
            args = list(plan.pre) + [qp]
            args.extend(plan.post)
            if plan.use_filter:
                fwp = fw
                if fw is not None and fw.ndim == 2:
                    fwp = self._pad(fw, rows, fw.dtype)
                args.append(fwp)
            with self._lock:
                entry, out_d, out_i, t0 = self._execute_entry_locked(
                    plan, rows, k, args, q, prepare=prepare)
        # mesh recording AFTER the lock releases: the readiness poll
        # lasts as long as the slowest shard, and holding the executor
        # lock through it would stall OTHER threads — concurrent
        # searches and exporter scrapes (publish_cost_gauges takes the
        # same lock) — for a full device execution. The calling thread
        # itself still waits out the poll, so an oversized batch's
        # tiles DO serialize under mesh_trace (per-tile attribution is
        # the trade; see the mesh_trace docstring)
        if plan.sharded and self.mesh_trace:
            self._record_mesh_dispatch(entry, out_d, out_i, t0,
                                       trace_ids)
        if host:
            return out_d, out_i, q
        return _device_cut(out_d, out_i, q) + (q,)

    def _execute_entry_locked(self, plan: _Plan, rows: int, k: int,
                              args, q_real: int, prepare=None):
        """Shared locked dispatch core of the bucketed and ragged
        paths: entry fetch/compile, graftgauge probe-plane threading,
        and the modeled-work counters. The caller holds ``self._lock``
        (RLock) and has assembled the positional ``args``; its open
        ``prepare`` span ends where the enqueue span begins. Returns
        ``(entry, out_d, out_i, t0)``."""
        entry = self._get_entry_locked(plan, rows, k)
        kwargs = {}
        eager = 0
        if plan.probe is not None:
            # graftgauge: thread the per-index donated counter
            # plane + the valid-row count (traced scalar — inert
            # bucket-pad rows must not pollute the histogram).
            # Created lazily on first dispatch; the lock serializes
            # the donate-and-replace handoff.
            pkey, n_lists, csharding, family, label = plan.probe[:5]
            counts = self._probe_state.get(pkey)
            if counts is None:
                self._evict_dead_probe_planes_locked()
                counts = jnp.zeros((n_lists,), jnp.int32)
                eager += 1
                if csharding is not None:
                    counts = jax.device_put(counts, csharding)
                self._probe_info[pkey] = {
                    "family": family, "label": label,
                    "n_lists": n_lists, "sharding": csharding}
                try:
                    # report the index's death so the plane (and
                    # its label) cannot be inherited by a new
                    # index reusing the address; the callback may
                    # fire in GC context, so it only appends —
                    # never takes the executor lock
                    weakref.finalize(plan.probe[5],
                                     self._probe_dead.append, pkey)
                except TypeError:       # non-weakref-able index
                    pass
            nv = jnp.asarray(q_real, jnp.int32)
            eager += 1
            if plan.qsharding is not None:
                nv = jax.device_put(nv, plan.qsharding)
            kwargs = {"probe_counts": counts, "n_valid": nv}
        elif plan.valid_rows:
            # a host scalar: its transfer rides the call itself
            kwargs = {"n_valid": np.asarray(q_real, np.int32)}
        if prepare is not None:
            prepare.close()
        with tracing.host_span(ENQUEUE_SPAN, hist=ENQUEUE):
            t0 = time.perf_counter()
            out = entry.compiled(*args, **kwargs)
        if plan.probe is not None:
            out_d, out_i, new_counts = out
            self._probe_state[plan.probe[0]] = new_counts
        else:
            out_d, out_i = out
        # modeled per-dispatch work, from the compile-time capture:
        # a counter bump (one host lock), never a device sync. The
        # scrape divides these by the measured execute-latency sum
        # to publish live achieved GB/s / FLOP/s. Counted AFTER the
        # dispatch so a call that raises does not inflate the
        # achieved-bandwidth numerator its failed execution never
        # contributes latency for.
        amounts = {
            "serving.execute.calls": 1.0,
            "serving.execute.rows": float(q_real),
            # dispatched row capacity incl. bucket/tile pad — the
            # pad-waste denominator the ragged-vs-bucketed A/B reads
            "serving.execute.padded_rows": float(rows),
            "serving.execute.modeled_flops":
                entry.cost.get("flops", 0.0),
            "serving.execute.modeled_bytes":
                entry.cost.get("bytes_accessed", 0.0),
        }
        if entry.payload_model is not None:
            amounts[MESH_WIRE_BYTES] = entry.wire_bytes
        if entry.grouped is not None:
            amounts[GROUPED_SCAN_DISPATCHES] = float(entry.grouped)
        if plan.probe is not None:
            # the host-side heartbeat of the device accounting —
            # what the CI snapshot floors check (lifetime ledger)
            amounts["index.probe.dispatches"] = 1.0
            amounts["index.probe.rows"] = float(q_real)
            amounts[EAGER_PROGRAMS] = float(eager)
        tracing.inc_counters(amounts)
        if self._memwatch is not None:
            # graftledger watermark: a host-only memory_stats read
            # folded into the ledger's high-water mark — no device
            # sync, no traced op, degrades to a counter bump on
            # backends without live stats
            self._memwatch.sample_dispatch()
        return entry, out_d, out_i, t0

    def _record_mesh_dispatch(self, entry, out_d, out_i, t0: float,
                              trace_ids: Tuple[int, ...]) -> None:
        """Graftscope v2 mesh span recording around one sharded
        dispatch (``mesh_trace=True``): the three modeled phase spans
        (bytes from the entry's compile-time
        ``collective_payload_model``) plus per-shard readiness timings
        — each output shard's host-visible arrival offset — reduced by
        the straggler detector into ``serving.mesh.*`` gauges. All of
        it is host-side timing + dict work AFTER the dispatch; nothing
        enters the traced program, so zero-recompile is untouched (the
        regression test runs with this enabled).

        Arrival times come from the shared non-blocking poll
        (:func:`raft_tpu.core.tracing.poll_shard_timings` — see there
        for why sequential blocking would hide early-ordinal
        stragglers)."""
        shards = [(sd.data, si.data)
                  for sd, si in zip(out_d.addressable_shards,
                                    out_i.addressable_shards)]
        timings = tracing.poll_shard_timings(shards, t0,
                                             poll_s=_MESH_POLL_S)
        phases = None
        if entry.payload_model is not None:
            from raft_tpu.distributed.ivf import mesh_phases

            phases = mesh_phases(entry.payload_model)
        tracing.record_mesh_spans(
            entry.family or "mesh", t0,
            t0 + (max(timings) if timings else 0.0),
            trace_ids=trace_ids, phases=phases, shard_timings=timings)

    def _pad(self, arr, rows: int, dtype):
        """Pad to ``rows`` along axis 0. numpy inputs (the serving
        frontend case) are padded host-side — zero device ops; device
        arrays pad with one tiny cached concat program."""
        if isinstance(arr, np.ndarray):
            out = np.zeros((rows,) + arr.shape[1:], dtype)
            out[: arr.shape[0]] = arr
            return out
        from raft_tpu.neighbors._batching import pad_rows

        arr = jnp.asarray(arr)
        if arr.dtype != dtype:
            _count_eager(1)
            arr = arr.astype(dtype)
        _count_eager(int(arr.shape[0] < rows))
        return pad_rows(arr, rows)

    def _get_entry(self, plan: _Plan, bucket: int, k: int) -> _Entry:
        with self._lock:
            return self._get_entry_locked(plan, bucket, k)

    def _get_entry_locked(self, plan: _Plan, bucket: int, k: int) -> _Entry:
        ent = self._cache.get(plan.key)
        if ent is not None:
            self._cache.move_to_end(plan.key)
            self.stats.cache_hits += 1
            tracing.inc_counter("serving.cache_hits")
            return ent
        self.stats.cache_misses += 1
        tracing.inc_counter("serving.cache_misses")
        # digest BEFORE compile: the HLO module is named after it
        # (jit_rt_<family>_<digest>), so a profiler trace's hlo_module
        # events correlate back to exactly this entry (graftflight)
        digest = hashlib.sha1(repr(plan.key).encode()).hexdigest()[:12]
        t0 = time.perf_counter()
        compiled = self._compile(plan, bucket,
                                 module=f"rt_{plan.key[0]}_{digest}")
        dt = time.perf_counter() - t0
        self.stats.compile_count += 1
        tracing.inc_counter("serving.compile_count")
        tracing.inc_counter("serving.compile_seconds", dt)
        # cost introspection happens HERE — compile time, once per
        # executable — so the per-dispatch accounting below is a plain
        # dict read with zero device interaction
        cost = _executable_cost(compiled)
        # the compile-time identity graftflight correlates trace events
        # on: the real module name as the profiler will spell it
        cost["hlo_module"] = _module_name(
            compiled, f"rt_{plan.key[0]}_{digest}")
        info = {"family": plan.key[0], "bucket": bucket, "k": k,
                "engine": _plan_engine(plan), "compile_seconds": dt,
                **cost}
        if plan.grouped_scan is not None:
            from raft_tpu.ops.ivf_scan import group_shape

            n_probes, shape, dtype, shards = plan.grouped_scan
            info["scan_group_rows"], info["scan_steps"] = group_shape(
                bucket // shards, n_probes, shape, dtype, k)
        payload_model = None
        if plan.payload is not None:
            family, model_fn = plan.payload
            payload_model = dict(model_fn())
            info["collective_family"] = family
            info["collective_payload"] = payload_model
            from raft_tpu.distributed.ivf import publish_payload_gauges

            publish_payload_gauges(family, payload_model)
        self._cost_table[digest] = info
        tracing.set_gauges(_cost_gauge_values(digest, cost))
        grouped = None
        if plan.key[0].removesuffix("_ragged") in _GROUPED_FAMILIES:
            grouped = plan.grouped_scan is not None
        ent = _Entry(compiled, cost=cost, digest=digest,
                     family=plan.key[0], payload_model=payload_model,
                     grouped=grouped)
        self._cache[plan.key] = ent
        while len(self._cache) > self.max_entries:
            _, old = self._cache.popitem(last=False)
            self.stats.evictions += 1
            tracing.inc_counter("serving.evictions")
            if old.digest:
                self._cost_table.pop(old.digest, None)
                tracing.reset_gauges(f"serving.executable.{old.digest}.")
        tracing.set_gauge("serving.executor.cached_executables",
                          float(len(self._cache)))
        return ent

    def executable_costs(self) -> dict:
        """``{digest: {family, bucket, k, engine, flops,
        bytes_accessed, peak_hbm_bytes, ...}}`` for every cached
        executable — the JSON view of the ``serving.executable.*``
        gauges (one scrape shows which programs are resident, which
        engine each resolved to, and what each costs per call)."""
        with self._lock:
            return {d: dict(info) for d, info in self._cost_table.items()}

    def executable_text(self, digest: str) -> str:
        """Optimized HLO text of the cached executable ``digest`` (as
        keyed in :meth:`executable_costs`) — e.g. to check that a
        kernel (``tpu_custom_call``) is in the program."""
        with self._lock:
            for ent in self._cache.values():
                if ent.digest == digest:
                    return ent.compiled.as_text()
        raise KeyError(f"no cached executable {digest!r}")

    def attach_memwatch(self, ledger) -> None:
        """Wire a graftledger :class:`~raft_tpu.core.memwatch
        .MemoryLedger`: every dispatch then folds a live-memory
        watermark sample (host-only — see ``_execute_entry_locked``)
        and the ledger's reservation forecast reads
        :meth:`memory_reservations`."""
        self._memwatch = ledger

    def memory_reservations(self) -> dict:
        """The executor-owned terms of graftledger's reservation
        forecast, per device ordinal: the graftgauge probe planes, and
        the max compile-time ``temp_bytes`` over the resident
        executables (any dispatch may be the one that peaks). Pure
        host-side metadata read under the executor lock — shapes,
        dtypes and the compile-time cost table; no device fetch."""
        from raft_tpu.core.memwatch import per_device_bytes

        planes: dict = {}
        with self._lock:
            for arr in self._probe_state.values():
                per_device_bytes(arr, planes)
            max_temp = max(
                (float(info.get("temp_bytes", 0.0))
                 for info in self._cost_table.values()), default=0.0)
            n = len(self._cache)
        return {"probe_plane_bytes": planes,
                "max_temp_bytes": max_temp,
                "executables": n}

    def publish_cost_gauges(self) -> None:
        """Re-publish every resident executable's cost gauges plus the
        cache-size gauge from the live cache. ``metrics.reset()``
        clears the whole ``serving.`` gauge namespace while the cache
        keeps its entries; an attached exporter calls this at scrape
        time so ``/metrics`` and :meth:`executable_costs` never
        disagree about which programs are resident. Mesh entries'
        ``serving.collective.*`` payload gauges re-publish too (they
        are keyed by family + wire dtypes rather than digest, so one
        gauge can represent several resident executables)."""
        with self._lock:
            table = {d: dict(info) for d, info in self._cost_table.items()}
            n = len(self._cache)
        vals = {"serving.executor.cached_executables": float(n)}
        for digest, info in table.items():
            vals.update(_cost_gauge_values(digest, info))
            if "collective_payload" in info:
                from raft_tpu.distributed.ivf import publish_payload_gauges

                publish_payload_gauges(info["collective_family"],
                                       info["collective_payload"])
        tracing.set_gauges(vals)

    def _compile(self, plan: _Plan, bucket: int,
                 module: Optional[str] = None):
        # the probe plane is donated where the backend implements
        # donation (CPU does not)
        donate = ()
        if plan.probe is not None and jax.default_backend() != "cpu":
            donate = ("probe_counts",)
        fn = plan.fn if module is None else _named_fn(plan.fn, module)
        jitted = jax.jit(fn, static_argnames=tuple(plan.static),
                         donate_argnames=donate)
        sds = _sds_sharded if (plan.sharded or plan.keep_sharding) \
            else _sds
        args = [sds(a) for a in plan.pre]
        args.append(jax.ShapeDtypeStruct((bucket, plan.qdim), plan.qdtype,
                                         sharding=plan.qsharding))
        if plan.ragged:
            # per-row probe-budget plane of the packed ragged batch
            args.append(jax.ShapeDtypeStruct((bucket,), jnp.int32))
        args.extend(sds(a) for a in plan.post)
        if plan.use_filter:
            fw_spec = plan.key[-1]  # _filter_spec tuple
            if fw_spec[0] == "nofilter":
                args.append(None)
            else:
                _, ndim, width, dt = fw_spec
                shape = (bucket, width) if ndim == 2 else (width,)
                args.append(jax.ShapeDtypeStruct(shape, np.dtype(dt)))
        kwargs = {}
        if plan.probe is not None:
            # graftgauge counter plane + valid-row scalar ride as
            # KEYWORD avals: the mesh plans take no filter_words
            # positional, so a positional plane would slide into
            # another parameter slot
            _, n_lists, csharding = plan.probe[:3]
            kwargs["probe_counts"] = jax.ShapeDtypeStruct(
                (n_lists,), jnp.int32, sharding=csharding)
            kwargs["n_valid"] = jax.ShapeDtypeStruct(
                (), jnp.int32, sharding=plan.qsharding)
        elif plan.valid_rows:
            kwargs["n_valid"] = jax.ShapeDtypeStruct(
                (), jnp.int32, sharding=plan.qsharding)
        return jitted.lower(*args, **kwargs, **plan.static).compile()

    # -- graftgauge probe-frequency surface ---------------------------------

    def _probe_plumbing(self, index, family: str, key: tuple,
                        sharding=None):
        """(key', probe descriptor) for one IVF-family plan: appends
        the accounting marker to the executable cache key (enabling
        accounting changes the compiled signature — it must be a
        distinct executable) and names the per-index counter plane.
        No-op (key unchanged, None) when accounting is off."""
        if not self.probe_accounting:
            return key, None
        pkey = (id(index), index.n_lists)
        digest = hashlib.sha1(
            repr((family, id(index))).encode()).hexdigest()[:6]
        # dash, not dot: the label must stay ONE dot-delimited segment
        # of the gauge name so the exporter's labeled-family regexes
        # can lift it into an {index="..."} label
        label = f"{family}-{digest}"
        # marker slots in BEFORE the trailing _filter_spec tuple —
        # _compile reads the filter spec off key[-1]
        key = key[:-1] + ("probe_accounting", key[-1])
        # the index rides along (plans are per-dispatch descriptors,
        # not cached) so first-dispatch plane creation can register
        # the death-watch weakref
        return key, (pkey, index.n_lists, sharding, family, label,
                     index)

    def _evict_dead_probe_planes_locked(self) -> None:
        """Drop planes whose index was garbage-collected. The weakref
        finalizer only APPENDS the dead pkey (list.append is atomic —
        a GC-context callback must never try to take the executor
        lock); the actual eviction happens here, under the lock, on
        the next dispatch-create or scrape. This also closes the
        id-reuse hazard: a new index reusing a dead one's address
        cannot inherit its cumulative plane."""
        while self._probe_dead:
            pkey = self._probe_dead.pop()
            self._probe_state.pop(pkey, None)
            self._probe_info.pop(pkey, None)
            self._probe_totals.pop(pkey, None)

    def probe_frequencies(self) -> dict:
        """``{label: (n_lists,) int64 numpy plane}`` of cumulative
        per-list probe counts, one entry per index that has dispatched
        with ``probe_accounting`` on. ONE device fetch per plane —
        this is the scrape-time read; nothing on the dispatch path
        ever fetches. The fetch happens under the executor lock, which
        also serializes dispatch, so it atomically CLAIMS the window
        since the last scrape: the device plane resets to zero and the
        fetched counts fold into a host-side int64 lifetime ledger
        (per-window device counts stay far from int32 overflow on any
        realistic scrape interval, while the returned totals never
        wrap) — and the claimed window bumps the monotone
        ``index.probe_freq.accounted`` counter exactly once, however
        many scrapers run concurrently."""
        out = {}
        accounted = 0
        with self._lock:
            self._evict_dead_probe_planes_locked()
            reset_keys, reset_zeros, reset_shardings = [], [], []
            for pkey, arr in self._probe_state.items():
                info = self._probe_info.get(pkey)
                if info is None:
                    continue
                window = np.asarray(jax.device_get(arr), dtype=np.int64)
                if window.any():
                    # claim the window: queue the plane for reset
                    # (placed in ONE batched device_put below)
                    reset_keys.append(pkey)
                    reset_zeros.append(
                        np.zeros(arr.shape, dtype=np.int32))
                    reset_shardings.append(info["sharding"])
                    accounted += int(window.sum())
                total = self._probe_totals.get(pkey)
                total = window if total is None else total + window
                self._probe_totals[pkey] = total
                out[info["label"]] = total.copy()
            if reset_keys:
                fresh = jax.device_put(
                    reset_zeros,
                    [s if s is not None else jax.devices()[0]
                     for s in reset_shardings])
                for pkey, plane in zip(reset_keys, fresh):
                    self._probe_state[pkey] = plane
        if accounted:
            # the mirror the CI snapshot floors check: counts that
            # really came off the device, exactly once per window
            tracing.inc_counter("index.probe_freq.accounted",
                                float(accounted))
        return out

    def publish_probe_gauges(self, top_n: int = 8,
                             planes: Optional[dict] = None) -> dict:
        """Reduce every probe plane through
        :func:`raft_tpu.core.tracing.probe_freq_stats` and publish the
        ``index.probe_freq.<label>.*`` gauges: lifetime ``total``,
        ``probed_fraction`` (share of lists traffic ever touched),
        the hot/cold coverage fractions ``coverage_p01`` /
        ``coverage_p10`` (share of probes the hottest 1% / 10% of
        lists absorb — the signal a future HBM/host-RAM tier split
        keys on), and the top-``top_n`` lists as
        ``index.probe_freq.<label>.list.<lid>`` samples (a labeled
        Prometheus family on the exporter). The monotone
        ``index.probe_freq.accounted`` mirror — the CI snapshot
        floor's ledger of counts that really came off the device — is
        bumped by :meth:`probe_frequencies` as it claims each window.
        ``planes`` lets a caller that already fetched (the exporter's
        scrape does, to share one fetch with drift detection) skip a
        second device read. Returns ``{label: stats}``."""
        if planes is None:
            planes = self.probe_frequencies()
        out = {}
        for label, counts in planes.items():
            stats = tracing.probe_freq_stats(counts, top_n=top_n)
            out[label] = stats
            base = f"index.probe_freq.{label}."
            # retire stale top-N samples before republishing — a list
            # that fell out of the top set must not linger at its old
            # value
            tracing.reset_gauges(base + "list.")
            vals = {
                base + "total": float(stats["total"]),
                base + "probed_fraction": stats["probed_fraction"],
                base + "coverage_p01": stats["coverage_p01"],
                base + "coverage_p10": stats["coverage_p10"],
            }
            for lid, c in stats["top"]:
                vals[f"{base}list.{lid}"] = float(c)
            tracing.set_gauges(vals)
        return out

    def probe_label(self, index) -> Optional[str]:
        """The gauge label of ``index``'s probe plane (None until its
        first accounted dispatch) — how graftgauge's drift detector
        pairs a watched index with its live histogram."""
        with self._lock:
            info = self._probe_info.get((id(index), index.n_lists))
        return info["label"] if info else None

    # -- per-family plans ---------------------------------------------------

    def _plan(self, index, params, k: int, bucket: int, fw, kw) -> _Plan:
        from raft_tpu.distributed.bq import DistributedIvfBq
        from raft_tpu.distributed.ivf import (
            DistributedIvfFlat,
            DistributedIvfPq,
        )
        from raft_tpu.neighbors.brute_force import BruteForceIndex
        from raft_tpu.neighbors.cagra import CagraIndex
        from raft_tpu.neighbors.ivf_bq import IvfBqIndex
        from raft_tpu.neighbors.ivf_flat import IvfFlatIndex
        from raft_tpu.neighbors.ivf_pq import IvfPqIndex
        from raft_tpu.neighbors.tiered import (
            TieredIvf,
            TieredIvfBq,
            TieredIvfPq,
        )

        if isinstance(index, BruteForceIndex):
            return self._plan_brute_force(index, k, bucket, fw, kw)
        if isinstance(index, TieredIvf):
            return self._plan_tiered(index, params, k, bucket, fw, kw)
        if isinstance(index, TieredIvfPq):
            return self._plan_tiered_pq(index, params, k, bucket, fw,
                                        kw)
        if isinstance(index, TieredIvfBq):
            return self._plan_tiered_bq(index, params, k, bucket, fw,
                                        kw)
        if isinstance(index, IvfFlatIndex):
            return self._plan_ivf_flat(index, params, k, bucket, fw, kw)
        if isinstance(index, IvfPqIndex):
            return self._plan_ivf_pq(index, params, k, bucket, fw, kw)
        if isinstance(index, IvfBqIndex):
            return self._plan_ivf_bq(index, params, k, bucket, fw, kw)
        if isinstance(index, CagraIndex):
            return self._plan_cagra(index, params, k, bucket, fw, kw)
        if isinstance(index, (DistributedIvfFlat, DistributedIvfPq,
                              DistributedIvfBq)):
            return self._plan_dist(index, params, k, bucket, fw, kw)
        raise TypeError(f"SearchExecutor does not support {type(index)!r}")

    def _dist_statics(self, index, kw) -> tuple:
        """Shared mesh-plan pieces: (comms, probe_mode, wire_dtype,
        probe_wire_dtype, query_axis) — validated. ``query_axis``
        (graftwire) names a second mesh axis to shard the padded query
        block over: 2-D list×query grids serve through the same
        bucketed AOT plans as 1-D meshes — the bucket rounds up to the
        grid extent and the cache key carries the full 2-D mesh
        identity (:func:`_mesh_key`), so steady state is
        zero-recompile. ``"auto"`` wire dtypes resolve against the
        modeled payload in :meth:`_plan_dist` (after the probe budget
        is known)."""
        from raft_tpu.comms.comms import (
            resolve_probe_wire_dtype,
            resolve_wire_dtype,
        )

        comms = index.comms
        probe_mode = kw.get("probe_mode", "global")
        wire_dtype = kw.get("wire_dtype", "f32")
        probe_wire_dtype = kw.get("probe_wire_dtype", "f32")
        query_axis = kw.get("query_axis")
        expect(probe_mode in ("global", "local"),
               f"probe_mode must be 'global' or 'local', got {probe_mode!r}")
        if wire_dtype != "auto":
            resolve_wire_dtype(wire_dtype)
        if probe_wire_dtype != "auto":
            resolve_probe_wire_dtype(probe_wire_dtype)
        if query_axis is not None:
            expect(query_axis in comms.mesh.axis_names
                   and query_axis != comms.axis,
                   f"query_axis {query_axis!r} must be another mesh axis")
        return comms, probe_mode, wire_dtype, probe_wire_dtype, query_axis

    def _plan_dist(self, index, params, k, bucket, fw, kw) -> _Plan:
        """ONE plan builder for the three list-sharded families —
        they share everything but the per-family statics/arrays, so
        the shared mesh plumbing (probe budget, mesh key, replicated
        query sharding, list-sharded probe plane, payload
        model) lives exactly once. The ragged plan family derives
        from this same builder (:meth:`_plan_ragged`), which is what
        retired the per-family bucketed/ragged plan-path copies."""
        from raft_tpu.distributed import bq as dist_bq
        from raft_tpu.distributed import ivf as dist_ivf
        from raft_tpu.distributed.ivf import DistributedIvfFlat, \
            DistributedIvfPq
        from raft_tpu.neighbors import ivf_bq, ivf_flat, ivf_pq

        expect(fw is None,
               "distributed searches have no sample_filter support")
        (comms, probe_mode, wire_dtype, probe_wire_dtype,
         query_axis) = self._dist_statics(index, kw)
        grouped = None
        if isinstance(index, DistributedIvfFlat):
            from raft_tpu.ops.ivf_scan import resolve_scan_engine

            family, fn = "dist_ivf_flat", dist_ivf._dist_search_fn
            params = params or ivf_flat.IvfFlatSearchParams()
            n_probes = dist_ivf.resolve_probe_budget(
                params.n_probes, index.n_lists, comms.size, probe_mode)
            engine = resolve_scan_engine(params.scan_engine,
                                         data=index.data, k=k)
            if engine == "pallas":
                n_lists, m, d = index.data.shape
                shards = (1 if query_axis is None
                          else comms.mesh.shape[query_axis])
                grouped = (n_probes, (n_lists // comms.size, m, d),
                           index.data.dtype, shards)
            extra, key_extra = {}, ()
            arrays = (index.centers, index.data, index.data_norms,
                      index.indices)
        elif isinstance(index, DistributedIvfPq):
            family, fn = "dist_ivf_pq", dist_ivf._dist_search_pq_fn
            params = params or ivf_pq.IvfPqSearchParams()
            n_probes = dist_ivf.resolve_probe_budget(
                params.n_probes, index.n_lists, comms.size, probe_mode)
            engine = ivf_pq.resolve_scan_engine(params.scan_engine)
            extra = {"codebook_kind": index.codebook_kind,
                     "score_mode": ivf_pq.resolve_score_mode(
                         params.score_mode, index.codebooks.shape[1]),
                     "lut_dtype": params.lut_dtype}
            key_extra = ()
            arrays = (index.centers, index.rotation, index.codebooks,
                      index.codes, index.indices)
        else:
            from raft_tpu.ops.bq_scan import resolve_bq_engine

            family, fn = "dist_ivf_bq", dist_bq._dist_search_bq_fn
            params = params or ivf_bq.IvfBqSearchParams()
            n_probes = dist_ivf.resolve_probe_budget(
                params.n_probes, index.n_lists, comms.size, probe_mode)
            engine = resolve_bq_engine(
                params.scan_engine, data=index.data, filter_words=None,
                k=k, dim_ext=index.dim_ext, bits=index.bits,
                n_probes=n_probes)
            extra = {"epsilon": params.epsilon}
            key_extra = (("data", index.data is not None),)
            arrays = (index.centers, index.rotation, index.codes,
                      index.rnorm, index.cfac, index.errw,
                      index.indices, index.data, index.data_norms)
        rows = bucket
        if query_axis is not None:
            # the padded query block must divide the whole 2-D grid:
            # a multiple of the query-axis extent (even query shards)
            # × the list-axis extent (whole scatter-merge slices per
            # list shard) — the bucketed-block move that makes 2-D
            # grids zero-recompile like 1-D meshes
            grid = comms.mesh.shape[query_axis] * comms.size
            rows = -(-bucket // grid) * grid
        wire_dtype, probe_wire_dtype = dist_ivf.resolve_auto_wires(
            rows, k, n_probes, index.n_lists, comms.size, wire_dtype,
            probe_mode, probe_wire_dtype)
        static = {"axis": comms.axis, "mesh": comms.mesh,
                  "n_probes": n_probes, "k": k, "metric": index.metric,
                  "probe_mode": probe_mode,
                  "coarse_algo": params.coarse_algo,
                  "scan_engine": engine, "wire_dtype": wire_dtype,
                  "probe_wire_dtype": probe_wire_dtype,
                  "query_axis": query_axis, **extra}
        key = (family, rows, _mesh_key(comms),
               _sig(*(a for a in arrays if a is not None))) + key_extra \
            + (tuple(sorted((n, str(v)) for n, v in static.items())),
               _filter_spec(None))
        if query_axis is None:
            key, probe = self._probe_plumbing(
                index, family, key, sharding=comms.sharding(comms.axis))
            qsharding = comms.replicated()
        else:
            # a query-sharded dispatch would write divergent replicas
            # into the probe plane — 2-D plans skip the accounting
            probe = None
            qsharding = comms.sharding(query_axis, None)
        return _Plan(key=key, fn=fn, static=static, post=arrays,
                     qdim=index.dim, sharded=True, probe=probe,
                     grouped_scan=grouped, qsharding=qsharding,
                     rows=rows if query_axis is not None else None,
                     payload=(family,
                              lambda: dist_ivf.collective_payload_model(
                                  rows, k, n_probes, index.n_lists,
                                  comms.size, wire_dtype, probe_mode,
                                  probe_wire_dtype)))

    def _plan_brute_force(self, index, k, bucket, fw, kw) -> _Plan:
        from raft_tpu.neighbors import brute_force as bf

        expect(fw is None, "brute_force has no sample_filter support")
        expect(0 < k <= index.size, f"k must be in (0, {index.size}]")
        approx = bool(kw.get("approx", False))
        if not approx and bf._use_fused_kernel(index.metric, k, bucket):
            static = {"k": k, "metric": index.metric}
            key = ("bf_fused", bucket, _sig(index.dataset, index.norms),
                   tuple(sorted(static.items())), _filter_spec(None))
            return _Plan(key=key, fn=_fused_entry_fn, static=static,
                         post=(index.dataset, index.norms),
                         qdtype=index.dataset.dtype,
                         qdim=index.dim)
        db_tile = int(kw.get("db_tile", 32768))
        budget_cols = max(
            128, self.res.workspace_limit_bytes // (4 * bucket))
        db_tile = min(db_tile, budget_cols, max(128, index.size))
        precision = self.res.matmul_precision
        qdtype = jnp.float32
        if index.dataset.dtype == jnp.bfloat16:
            qdtype = jnp.bfloat16
            precision = "default"
        static = {"k": k, "metric": index.metric,
                  "metric_arg": index.metric_arg, "tile": db_tile,
                  "precision": precision, "approx": approx}
        key = ("bf_scan", bucket, _sig(index.dataset),
               tuple(sorted((n, str(v)) for n, v in static.items())),
               _filter_spec(None))
        return _Plan(key=key, fn=bf._knn_scan_fn, static=static,
                     post=(index.dataset,), qdtype=qdtype, qdim=index.dim)

    def _plan_ivf_flat(self, index, params, k, bucket, fw, kw) -> _Plan:
        from raft_tpu.neighbors import ivf_flat as m
        from raft_tpu.ops.ivf_scan import resolve_scan_engine

        params = params or m.IvfFlatSearchParams()
        expect(index.max_list_size > 0, "index is empty — extend() it first")
        n_probes = min(params.n_probes, index.n_lists)
        # the resolved engine is part of the static set and therefore of
        # the AOT cache key: switching engines compiles a new executable
        # instead of silently reusing the wrong one, and bucketing /
        # warmup behave per engine
        engine = resolve_scan_engine(params.scan_engine, data=index.data,
                                     filter_words=fw, k=k)
        static = {"n_probes": n_probes, "k": k, "metric": index.metric,
                  "coarse_algo": params.coarse_algo, "scan_engine": engine}
        arrays = (index.centers, index.center_norms, index.data,
                  index.data_norms, index.indices)
        key = ("ivf_flat", bucket, _sig(*arrays),
               tuple(sorted((n, str(v)) for n, v in static.items())),
               _filter_spec(fw))
        key, probe = self._probe_plumbing(index, "ivf_flat", key)
        grouped = None
        if engine == "pallas":
            grouped = (n_probes, index.data.shape, index.data.dtype, 1)
        return _Plan(key=key, fn=m._search_impl_fn, static=static,
                     post=arrays, use_filter=True, qdim=index.dim,
                     probe=probe, grouped_scan=grouped)

    def _plan_tiered(self, index, params, k, bucket, fw, kw) -> _Plan:
        from raft_tpu.neighbors import tiered as m
        from raft_tpu.ops.tier_scan import resolve_tier_engine

        params = params or m.TieredSearchParams()
        expect(index.max_list_size > 0, "tiered index is empty")
        n_probes = min(params.n_probes, index.n_lists)
        # ONE consistent placement generation for this dispatch —
        # tier_arrays() snapshots all four placement-affected arrays
        # under the container's swap lock, so a concurrent epoch can
        # never hand a plan a new hot plane against an old slot map
        hot_data, cold_data, hot_map, cold_map = index.tier_arrays()
        engine = resolve_tier_engine(params.scan_engine,
                                     hot_data=hot_data,
                                     cold_data=cold_data,
                                     filter_words=fw, k=k)
        static = {"n_probes": n_probes, "k": k, "metric": index.metric,
                  "coarse_algo": params.coarse_algo,
                  "scan_engine": engine}
        arrays = (index.centers, index.center_norms, hot_data,
                  cold_data, hot_map, cold_map, index.data_norms,
                  index.indices)
        # the cache key is SHAPES + statics, never array identity: a
        # placement epoch replaces hot_data/cold_data/slot maps with
        # same-shape arrays, so re-placed traffic keeps hitting this
        # exact executable — zero backend compiles across epochs (the
        # grafttier serving contract, pinned in tests)
        key = ("tiered_ivf", bucket, _sig(*arrays),
               tuple(sorted((n, str(v)) for n, v in static.items())),
               _filter_spec(fw))
        key, probe = self._probe_plumbing(index, "tiered_ivf", key)
        # keep_sharding: the cold plane's host memory kind must
        # survive into the lowered avals (see _Plan.keep_sharding)
        return _Plan(key=key, fn=m._tiered_search_fn, static=static,
                     post=arrays, use_filter=True, qdim=index.dim,
                     probe=probe, keep_sharding=True)

    def _plan_tiered_pq(self, index, params, k, bucket, fw,
                        kw) -> _Plan:
        """Tiered-PQ plan (graftcast) — the ``_plan_ivf_pq`` statics
        with the codes plane split hot/cold. Same
        generation-snapshot + shape-keyed discipline as
        :meth:`_plan_tiered`: the placement arrays never enter the
        cache key, every dispatch re-snapshots one consistent
        generation, so epochs are zero-recompile."""
        from raft_tpu.neighbors import ivf_pq
        from raft_tpu.neighbors import tiered as m
        from raft_tpu.ops.tier_scan import resolve_tier_pq_engine

        params = params or ivf_pq.IvfPqSearchParams()
        expect(index.max_list_size > 0, "tiered index is empty")
        score_mode = ivf_pq.resolve_score_mode(params.score_mode,
                                               index.pq_book_size)
        engine = resolve_tier_pq_engine(params.scan_engine)
        (hot_codes,), (cold_codes,), hot_map, cold_map, _ = \
            index.tier_planes()
        static = {"n_probes": min(params.n_probes, index.n_lists),
                  "k": k, "metric": index.metric,
                  "codebook_kind": index.codebook_kind,
                  "lut_dtype": params.lut_dtype,
                  "score_mode": score_mode, "packed": index.packed,
                  "coarse_algo": params.coarse_algo,
                  "scan_engine": engine}
        arrays = (index.centers, index.rotation, index.codebooks,
                  hot_codes, cold_codes, hot_map, cold_map,
                  index.indices)
        key = ("tiered_ivf_pq", bucket, _sig(*arrays),
               tuple(sorted((n, str(v)) for n, v in static.items())),
               _filter_spec(fw))
        key, probe = self._probe_plumbing(index, "tiered_ivf_pq", key)
        return _Plan(key=key, fn=m._tiered_pq_search_fn,
                     static=static, post=arrays, use_filter=True,
                     qdim=index.dim, probe=probe, keep_sharding=True)

    def _plan_tiered_bq(self, index, params, k, bucket, fw,
                        kw) -> _Plan:
        """Tiered-BQ plan (graftcast) — the ``_plan_ivf_bq`` statics
        with the five record planes split hot/cold under one slot
        decision. Generation-snapshot + shape-keyed like the other
        tiered plans."""
        from raft_tpu.neighbors import ivf_bq
        from raft_tpu.neighbors import tiered as m
        from raft_tpu.ops.bq_scan import auto_query_bits
        from raft_tpu.ops.tier_scan import resolve_tier_bq_engine

        params = params or ivf_bq.IvfBqSearchParams()
        expect(index.max_list_size > 0, "tiered index is empty")
        engine = resolve_tier_bq_engine(params.scan_engine)
        qb = params.query_bits or auto_query_bits(index.bits)
        hots, colds, hot_map, cold_map, _ = index.tier_planes()
        static = {"n_probes": min(params.n_probes, index.n_lists),
                  "k": k, "metric": index.metric,
                  "coarse_algo": params.coarse_algo,
                  "scan_engine": engine, "epsilon": params.epsilon,
                  "query_bits": qb}
        arrays = (index.centers, index.rotation) + hots + colds + (
            hot_map, cold_map, index.indices, index.data_norms)
        key = ("tiered_ivf_bq", bucket, _sig(*arrays),
               tuple(sorted((n, str(v)) for n, v in static.items())),
               _filter_spec(fw))
        key, probe = self._probe_plumbing(index, "tiered_ivf_bq", key)
        return _Plan(key=key, fn=m._tiered_bq_search_fn,
                     static=static, post=arrays, use_filter=True,
                     qdim=index.dim, probe=probe, keep_sharding=True)

    def _plan_ivf_pq(self, index, params, k, bucket, fw, kw) -> _Plan:
        from raft_tpu.neighbors import ivf_pq as m

        params = params or m.IvfPqSearchParams()
        expect(index.max_list_size > 0, "index is empty — extend() it first")
        score_mode = m.resolve_score_mode(params.score_mode,
                                          index.pq_book_size)
        engine = m.resolve_scan_engine(params.scan_engine)
        static = {"n_probes": min(params.n_probes, index.n_lists), "k": k,
                  "metric": index.metric,
                  "codebook_kind": index.codebook_kind,
                  "lut_dtype": params.lut_dtype, "score_mode": score_mode,
                  "packed": index.packed, "coarse_algo": params.coarse_algo,
                  "scan_engine": engine}
        arrays = (index.centers, index.rotation, index.codebooks,
                  index.codes, index.indices)
        key = ("ivf_pq", bucket, _sig(*arrays),
               tuple(sorted((n, str(v)) for n, v in static.items())),
               _filter_spec(fw))
        key, probe = self._probe_plumbing(index, "ivf_pq", key)
        return _Plan(key=key, fn=m._search_impl_fn, static=static,
                     post=arrays, use_filter=True, qdim=index.dim,
                     probe=probe)

    def _plan_ivf_bq(self, index, params, k, bucket, fw, kw) -> _Plan:
        from raft_tpu.neighbors import ivf_bq as m
        from raft_tpu.ops.bq_scan import resolve_bq_engine

        params = params or m.IvfBqSearchParams()
        expect(index.max_list_size > 0, "index is empty — extend() it first")
        # the resolved engine joins the static set and therefore the
        # AOT cache key (same contract as ivf_flat): engine switch =
        # distinct executable, never a silent reuse
        n_probes = min(params.n_probes, index.n_lists)
        engine = resolve_bq_engine(
            params.scan_engine, data=index.data, filter_words=fw, k=k,
            dim_ext=index.dim_ext, bits=index.bits, n_probes=n_probes)
        from raft_tpu.ops.bq_scan import auto_query_bits

        qb = params.query_bits or auto_query_bits(index.bits)
        static = {"n_probes": n_probes, "k": k,
                  "metric": index.metric, "coarse_algo": params.coarse_algo,
                  "scan_engine": engine, "epsilon": params.epsilon,
                  "query_bits": qb}
        arrays = (index.centers, index.rotation, index.codes, index.rnorm,
                  index.cfac, index.errw, index.indices, index.data,
                  index.data_norms)
        key = ("ivf_bq", bucket, _sig(*(a for a in arrays if a is not None)),
               ("data", index.data is not None),
               tuple(sorted((n, str(v)) for n, v in static.items())),
               _filter_spec(fw))
        key, probe = self._probe_plumbing(index, "ivf_bq", key)
        return _Plan(key=key, fn=m._search_impl_fn, static=static,
                     post=arrays, use_filter=True, qdim=index.dim,
                     probe=probe)

    def _plan_cagra(self, index, params, k, bucket, fw, kw) -> _Plan:
        from raft_tpu.neighbors import cagra as m
        from raft_tpu.ops.bq_scan import auto_query_bits

        params = params or m.CagraSearchParams()
        use_kernel = m._resolve_search_algo(params, index, fw)
        seed_mode = m._resolve_seed_mode(params, index)
        use_bq = m._resolve_bq_traversal(params, index, use_kernel)
        engine = "pallas" if use_kernel else "xla"
        # seeds are a pure function of query content (PR 16), so one
        # "cagra" family serves any block mix — the resolved engine and
        # plane presence join the statics/key exactly like ivf_bq's
        static = dict(m.derive_search_config(params, index, k),
                      metric=index.metric, engine=engine,
                      seed_mode=seed_mode, seed_pool=params.seed_pool,
                      bq_bits=index.bq_bits if use_bq else 0,
                      bq_query_bits=(auto_query_bits(index.bq_bits)
                                     if use_bq else 4),
                      bq_epsilon=params.bq_epsilon,
                      deg=index.graph_degree,
                      interpret=jax.default_backend() != "tpu")
        arrays = (index.dataset,
                  index.padded_graph if use_kernel else index.graph,
                  index.seed_centers, index.seed_members,
                  index.bq_rotation if use_bq else None,
                  index.bq_center_rot if use_bq else None,
                  index.bq_records if use_bq else None)
        key = ("cagra", bucket,
               _sig(*(a for a in arrays if a is not None)),
               ("planes", index.seed_centers is not None, use_bq),
               tuple(sorted((n, str(v)) for n, v in static.items())),
               _filter_spec(fw if not use_kernel else None))
        return _Plan(key=key, fn=m._serving_fn, static=static,
                     post=arrays, use_filter=not use_kernel,
                     qdim=index.dim)
