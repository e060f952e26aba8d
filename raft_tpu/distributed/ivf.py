"""SPMD distributed IVF-Flat — the index itself sharded over a mesh axis.

The reference scales IVF via raft-dask's index-per-worker pattern (host
orchestration + ``knn_merge_parts``). The TPU-native form keeps ONE
logical index whose inverted lists are block-sharded over the mesh
(``jax.sharding``): every chip owns ``n_lists / R`` lists, the coarse
quantizer is replicated, and a single jitted ``shard_map`` program does

    local coarse top-p  →  local probe scan  →  lean all_gather + merge

so the collectives ride ICI and no host round-trips happen per query
(SURVEY.md §5 "TPU equivalent" note; the merge is the
``knn_merge_parts`` pattern inside the program).

The shard-local probe scan is the SAME pluggable engine set as the
single-chip ``ivf_flat.search`` (``scan_engine: auto|pallas|xla|rank``,
:mod:`raft_tpu.ops.ivf_scan`): the list-major engines compute each
shard's probed-list union (not-owned probes masked to the sentinel id)
and stream every owned unique list once through one MXU GEMM. The
query hot path moves only lean payloads over ICI:

- probe selection (``"global"``): each shard contributes its top
  ``min(n_probes, n_local)`` (distance, id) candidates — an
  O(q · n_probes) collective, not the O(q · n_lists / R) coarse block;
- result merge: each shard's locally-reduced (q, k) top-k — O(q · k) —
  with an opt-in ``wire_dtype="bf16"`` low-precision wire format for
  the gathered distances (ids ride exact; ties re-rank by smallest id).

Probe semantics (``probe_mode``):

- ``"global"`` (default, exact): the global top-``n_probes`` lists are
  selected from the gathered per-shard candidates; each shard scans the
  probed lists it owns, masking the rest. Results match the
  single-device index exactly; per-chip wall-clock is ~the single-chip
  search, while HBM capacity scales with the mesh — the point of
  sharding at 1B rows.
- ``"local"`` (approximate, fast): each shard probes its own top
  ``ceil(n_probes / R)`` local lists. Lists are dealt round-robin by
  size at build time so relevant lists spread evenly; the union
  closely tracks the global top-``n_probes`` (the approximation
  sharded FAISS-IVF deployments make). Per-chip scan work drops by R.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from raft_tpu.comms.comms import (
    Comms,
    allgather,
    allgather_quantized,
    allgather_wire,
    alltoall,
    rank as comm_rank,
    reducescatter_quantized,
    resolve_probe_wire_dtype,
    resolve_wire_dtype,
    shard_map,
    size as comm_size,
)
from raft_tpu.core import interruptible, memwatch, tracing
from raft_tpu.core.resources import Resources, ensure_resources
from raft_tpu.core.validation import expect
from raft_tpu.distance.types import DistanceType, is_min_close
from raft_tpu.matrix.select_k import merge_topk
from raft_tpu.neighbors import ivf_flat as ivf_flat_mod
from raft_tpu.neighbors import ivf_pq as ivf_pq_mod
from raft_tpu.neighbors._batching import coarse_select
from raft_tpu.neighbors._packing import padded_extent, streaming_ranks
from raft_tpu.neighbors.ivf_flat import IvfFlatIndexParams, IvfFlatSearchParams
from raft_tpu.neighbors.ivf_pq import (
    CodebookKind,
    IvfPqIndexParams,
    IvfPqSearchParams,
)
from raft_tpu.ops.ivf_scan import list_major_scan


@dataclasses.dataclass(frozen=True)
class DistributedIvfFlat:
    """List-sharded IVF-Flat index.

    Arrays with a leading ``n_lists`` axis are sharded over ``comms``'s
    mesh axis; ``centers`` is replicated (every shard needs the full
    codebook only for its local slice — centers are stored sharded too,
    matching the list assignment).
    """

    comms: Comms
    centers: jax.Array        # (n_lists, d) sharded on axis 0
    data: jax.Array           # (n_lists, max_list_size, d) sharded
    data_norms: jax.Array     # (n_lists, max_list_size) sharded
    indices: jax.Array        # (n_lists, max_list_size) int32 sharded
    list_sizes: jax.Array     # (n_lists,) sharded
    metric: DistanceType

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]

    @property
    def max_list_size(self) -> int:
        return self.data.shape[1]

    @property
    def size(self) -> int:
        return int(jax.device_get(self.list_sizes).sum())


def deal_order(sizes: np.ndarray, r: int) -> np.ndarray:
    """Round-robin deal by descending population — THE list-to-shard
    layout policy, shared by build, build_pq and checkpoint restore:
    shard s gets every r-th list of the size-sorted order, so per-shard
    scan work and list relevance stay balanced at any shard count."""
    order = np.argsort(-np.asarray(sizes), kind="stable")
    return np.concatenate([order[s::r] for s in range(r)])


_gather_rows = jax.jit(lambda a, rows: jnp.take(a, rows, axis=0))


def admit_deal(arrays, r: int, what: str) -> None:
    """graftledger gate for the mesh deal (opt-in, no-op unless a
    gate is installed): the single-chip build admitted the BUILD
    device's packed layout, but the deal is a second allocation event
    — every SHARD device receives its ``1/r`` slice of each dealt
    tensor. Admit that per-shard slot model
    (:func:`raft_tpu.core.memwatch.dealt_shard_bytes` — headroom is
    per-device, so per-shard bytes is the unit) host-side BEFORE any
    block moves, so a mesh that cannot hold the sharded index fails
    as a typed ``CapacityExceeded`` instead of an OOM mid-deal.
    Accepts arrays or ``ShapeDtypeStruct``s (the streaming build
    admits its planned buffers before allocating them)."""
    memwatch.admit(memwatch.dealt_shard_bytes(arrays, r), what)


def place_dealt(a, perm: np.ndarray, comms: Comms):
    """Deal + place ONE build-device tensor onto the mesh, streaming
    per-shard blocks instead of materializing the fully-permuted tensor
    on the build device: each shard's list block (1/R of the tensor) is
    gathered on the build device, transferred to its device(s), and the
    global sharded array assembled from the per-device pieces. Peak
    extra build-device footprint drops from O(full tensor) to O(block);
    the high-water mark is recorded in the
    ``distributed.build.peak_deal_block_bytes`` tracing counter and the
    total moved in ``distributed.build.deal_bytes_total``."""
    perm = np.asarray(perm)
    shard = comms.sharding(comms.axis)
    shape = tuple(a.shape)
    imap = shard.devices_indices_map(shape)
    # group devices by their dim-0 block (a 2-D mesh replicates each
    # list block across the other axis — gather it once)
    groups: dict = {}
    order = []
    for dev, idx in imap.items():
        sl = idx[0]
        key = (sl.start or 0, sl.stop if sl.stop is not None else shape[0])
        groups.setdefault(key, []).append(dev)
        order.append((dev, key))
    pieces = {}
    for (start, stop), devs in groups.items():
        rows = jnp.asarray(perm[start:stop], jnp.int32)
        blk = _gather_rows(a, rows)          # ONE block on the build device
        blk_bytes = blk.size * blk.dtype.itemsize
        tracing.max_counter("distributed.build.peak_deal_block_bytes",
                            blk_bytes)
        tracing.inc_counter("distributed.build.deal_bytes_total",
                            blk_bytes * len(devs))
        # graftlint: disable=R5(streaming deal: per-block puts bound build staging to O(block))
        puts = [jax.device_put(blk, d) for d in devs]
        # block before gathering the next block so at most one block's
        # worth of staging lives on the build device at a time
        for p in puts:
            p.block_until_ready()
        for d, p in zip(devs, puts):
            pieces[d] = p
        del blk
    return jax.make_array_from_single_device_arrays(
        shape, shard, [pieces[dev] for dev, _ in order])


def select_probes_sharded(coarse, n_probes: int, axis: str,
                          probe_mode: str, coarse_algo: str = "exact",
                          probe_wire_dtype: str = "f32"):
    """Shared probe selection inside a shard_map body — THE
    probe-ownership arithmetic for every list-sharded index family.

    ``coarse`` is this shard's (q, n_local) min-close coarse distances.
    Returns ``(local, mine)``: per-(query, probe-rank) local list ids
    and a mask of the probes this shard owns.

    - ``"global"``: LEAN candidate exchange — each shard ranks only its
      own centers and contributes its top-``min(n_probes, n_local)``
      (distance, global id) pairs to the all_gather: an O(q · n_probes)
      payload instead of the O(q · n_local) coarse block (the global
      top-``n_probes`` provably lies inside the union of per-shard
      top-``n_probes``). The global probe set is the lexicographic
      (distance, id) top-``n_probes`` of the gathered candidates, so
      ties resolve deterministically at any shard count. When the
      candidate payload would NOT be leaner (probing most of the index:
      2 · min(n_probes, n_local) ≥ n_local), the dense coarse-block
      gather is used instead — same probe set, fewer bytes.
    - ``"local"``: each shard probes its own top-``n_probes`` lists.

    ``coarse_algo="approx"`` swaps the probe top-k for the TPU's
    native approximate top-k unit, via the same
    :func:`raft_tpu.neighbors._batching.coarse_select` dispatch the
    single-chip searches use (lean mode applies it to the local stage).

    ``probe_wire_dtype`` compresses the exchanged coarse *distances*
    on the wire (``f32|bf16|int8`` — int8 rides per-query affine
    scales, :func:`raft_tpu.comms.comms.allgather_quantized`);
    candidate ids stay exact int32, and the final probe select sorts
    (distance, id) so compression-induced ties resolve
    deterministically. The int8 scales derive from the FULL local
    coarse block (``scale_ref=coarse``), BEFORE candidate selection —
    each candidate's code is therefore independent of which (and how
    many) candidates were selected, which is what lets the int8 wire
    ride the ragged serving family's cap-vs-solo bit-identity
    contract. This trades probe-selection fidelity (hence a little
    recall) for 2-4x fewer coarse-exchange bytes — recall-checked in
    ``tests/test_distributed_serving.py``.
    """
    q, n_local = coarse.shape
    if probe_mode == "global":
        rank = comm_rank(axis)
        local_k = min(n_probes, n_local)
        if 2 * local_k < n_local:
            # lean candidate exchange: (distance, global id) pairs only
            loc = coarse_select(-coarse, local_k, coarse_algo)
            dloc = jnp.take_along_axis(coarse, loc, axis=1)
            gid = loc.astype(jnp.int32) + rank.astype(jnp.int32) * n_local
            # (R, q, local_k); distances optionally ride a quantized
            # wire format (scales from the full pre-selection block —
            # candidate-set-independent), ids always exact
            all_d = allgather_quantized(dloc, axis, probe_wire_dtype,
                                        scale_ref=coarse)
            all_g = allgather(gid, axis)
            r = all_d.shape[0]
            cand_d = jnp.moveaxis(all_d, 0, 1).reshape(q, r * local_k)
            cand_g = jnp.moveaxis(all_g, 0, 1).reshape(q, r * local_k)
            _, sg = jax.lax.sort((cand_d, cand_g), dimension=1,
                                 num_keys=2)
            probes = sg[:, :n_probes]
        else:
            coarse_all = allgather_quantized(
                coarse, axis, probe_wire_dtype)           # (R, q, L)
            r = coarse_all.shape[0]
            coarse_flat = jnp.moveaxis(coarse_all, 0, 1).reshape(
                q, r * n_local)
            probes = coarse_select(-coarse_flat, n_probes, coarse_algo)
        owner = probes // n_local
        local = probes - owner * n_local
        mine = owner == rank
        return local, mine
    probes = coarse_select(-coarse, n_probes, coarse_algo)
    return probes, jnp.ones(probes.shape, jnp.bool_)


def merge_results_sharded(best_d, best_i, axis: str, select_min: bool,
                          wire_dtype: str = "f32",
                          smallest_id_ties: bool = True,
                          scatter: bool = False):
    """All-gather each shard's locally-reduced (q, k) top-k and merge —
    the O(q · k) result collective of every list-sharded search (the
    ``knn_merge_parts`` pattern inside the program).

    ``wire_dtype="bf16"`` compresses the gathered *distances* on the
    wire (ids ride exact int32); ties — including the extra ties the
    compression creates — re-rank deterministically by smallest id, so
    the returned ids stay exact w.r.t. the wire-rounded ranking and
    shard-count invariant.

    ``smallest_id_ties=True`` merges by lexicographic (distance, id) —
    the list-major engines' order, bit-identical to the single-chip
    engines even on exact-duplicate ties. ``False`` keeps the legacy
    positional ``knn_merge_parts`` tie-break of the rank-major and BQ
    paths.

    ``scatter=True`` (the 2-D query×list grids) replaces the
    all-ranks gather — where every list shard redundantly merges the
    SAME (q, r·k) candidate table — with a scatter-merge: the
    distances ride
    :func:`raft_tpu.comms.comms.reducescatter_quantized`'s wire (fold
    = this sort-merge), so each list shard receives all ranks'
    candidates for a DISJOINT q/r query slice, merges only that
    slice, and one (q/r, k) allgather reassembles the rows in rank
    order — ~r/2× fewer merge bytes per shard. The received blocks
    stack in rank order, matching the gathered candidate order
    exactly, so the merged results are bit-identical to the
    allgather path (which stays the static fallback when r does not
    divide q)."""
    r = comm_size(axis)
    q, k = best_d.shape
    if scatter and q % r == 0 and q >= r:
        sub_i = alltoall(best_i, axis)                    # (R, q/r, k)
        merged = reducescatter_quantized(
            best_d, axis=axis, wire_dtype=wire_dtype,
            fold=lambda sub_d: _merge_candidates(
                sub_d, sub_i, k, select_min, smallest_id_ties))
        return (allgather(merged[0], axis, tiled=True),
                allgather(merged[1], axis, tiled=True))
    all_d = allgather_wire(best_d, axis, wire_dtype)      # (R, q, k)
    all_i = allgather(best_i, axis)
    return _merge_candidates(all_d, all_i, k, select_min,
                             smallest_id_ties)


def _merge_candidates(all_d, all_i, k: int, select_min: bool,
                      smallest_id_ties: bool):
    """Shared merge epilog of the gather and scatter wires: concat the
    (R, rows, k) rank stacks in rank order and reduce each row's r·k
    candidates to its top-k."""
    r, q, _ = all_d.shape
    cat_d = jnp.moveaxis(all_d, 0, 1).reshape(q, r * k)
    cat_i = jnp.moveaxis(all_i, 0, 1).reshape(q, r * k)
    if not smallest_id_ties:
        return merge_topk(cat_d[:, :k], cat_i[:, :k], cat_d[:, k:],
                          cat_i[:, k:], k, select_min)
    sd, si = jax.lax.sort((cat_d if select_min else -cat_d, cat_i),
                          dimension=1, num_keys=2)
    sd, si = sd[:, :k], si[:, :k]
    si = jnp.where(jnp.isfinite(sd), si, -1)
    return (sd if select_min else -sd), si


def collective_payload_model(q: int, k: int, n_probes: int, n_lists: int,
                             r: int, wire_dtype: str = "f32",
                             probe_mode: str = "global",
                             probe_wire_dtype: str = "f32") -> dict:
    """Modeled per-shard query-path collective payloads (bytes) — the
    accounting the bench rider emits next to measured throughput, and
    the contract the lean-collective tests assert on.

    ``coarse_bytes``/``merge_bytes`` are what the current implementation
    moves per shard; ``dense_coarse_bytes`` is the pre-lean coarse-block
    gather for comparison. ``probe_wire_dtype`` prices the quantized
    candidate exchange (int8 adds TWO f32 affine-scale planes — min and
    range — per (query, shard); the block-independent scheme the
    ragged family's bit-identity contract rides)."""
    n_local = max(n_lists // max(r, 1), 1)
    local_k = min(n_probes, n_local)
    wire_itemsize = 2 if wire_dtype == "bf16" else 4
    probe_itemsize = {"f32": 4, "bf16": 2, "int8": 1}[probe_wire_dtype]
    scale = 8 if probe_wire_dtype == "int8" else 0  # per-row (min, range)
    dense = q * (n_local * probe_itemsize + scale)
    lean = q * (local_k * (probe_itemsize + 4) + scale)  # + int32 ids
    coarse = 0
    if probe_mode == "global":
        coarse = lean if 2 * local_k < n_local else dense
    return {
        "coarse_bytes": coarse,
        "dense_coarse_bytes": q * n_local * 4
            if probe_mode == "global" else 0,
        "merge_bytes": q * k * (wire_itemsize + 4),
        "wire_dtype": wire_dtype,
        "probe_wire_dtype": probe_wire_dtype,
    }


def mesh_phases(model: dict) -> dict:
    """Map one :func:`collective_payload_model` result onto the three
    mesh query phases — the span attrs of the ``serving.mesh.*`` spans
    (PR 7 graftscope v2): ``coarse_select`` carries the probe-candidate
    exchange bytes, ``scan`` the shard-local probe scan (no wire
    bytes — it is the HBM-bound stage), ``merge`` the O(q · k) result
    collective. ``modeled: True`` marks the attribution as byte-model
    accounting over the shared dispatch window, not a device profile —
    the TPU-KNN methodology, machine-readable."""
    return {
        "coarse_select": {"modeled": True,
                          "wire_bytes": model["coarse_bytes"],
                          "dense_wire_bytes": model["dense_coarse_bytes"],
                          "probe_wire_dtype": model["probe_wire_dtype"]},
        "scan": {"modeled": True, "wire_bytes": 0},
        "merge": {"modeled": True, "wire_bytes": model["merge_bytes"],
                  "wire_dtype": model["wire_dtype"]},
    }


def record_dispatch(family: str, model, trace_id, thunk, *,
                    axis: str = "data",
                    phases: Optional[dict] = None,
                    modeled_bytes: Optional[float] = None,
                    attrs: Optional[dict] = None):
    """Shared traced-dispatch path of the direct distributed search
    entries: with ``trace_id=None`` (the default) the thunk dispatches
    untouched — fully async, zero instrumentation cost. With a
    ``trace_id`` the dispatch is timed through
    :func:`raft_tpu.comms.comms.timed_dispatch`, **blocks until the
    result is ready** (so the span duration covers the mesh execution,
    not just the enqueue — the one place tracing trades away async
    dispatch, opt-in per call), and the mesh phase spans are recorded
    with the modeled per-phase wire bytes attached.

    ``axis`` names the mesh axis the program's collectives ride (the
    caller's ``comms.axis`` — a span attr; hardcoding ``"data"`` would
    mislabel 2-D grids and renamed-axis meshes).
    ``phases``/``modeled_bytes`` default from ``model`` — a
    :func:`collective_payload_model` dict, or a zero-arg callable
    producing one so the untraced hot path (``trace_id=None``, every
    production call) never pays for building a model it immediately
    discards; callers with a different phase structure (the exact-kNN
    programs, which have no coarse phase) pass them explicitly and may
    leave ``model`` as None. ``attrs`` ride on the timed-dispatch
    span."""
    from raft_tpu.comms.comms import timed_dispatch

    if trace_id is None:
        return thunk()
    if callable(model):
        model = model()
    if phases is None:
        phases = mesh_phases(model)
    if modeled_bytes is None:
        modeled_bytes = float(model["coarse_bytes"] + model["merge_bytes"])
    ids = (trace_id,)
    t0 = time.perf_counter()
    out = timed_dispatch(
        family, lambda: jax.block_until_ready(thunk()), axis,
        modeled_bytes=modeled_bytes, trace_ids=ids, attrs=attrs)
    tracing.record_mesh_spans(family, t0, time.perf_counter(),
                              trace_ids=ids, phases=phases)
    return out


def publish_payload_gauges(family: str, model: dict) -> None:
    """Register one :func:`collective_payload_model` result as live
    ``serving.collective.*`` gauges — called once per compiled mesh
    executable by the executor (PR 6 graftscope), so a monitoring
    scrape sees the modeled per-shard wire bytes next to the achieved
    bandwidth counters instead of only in offline BENCH JSONs."""
    from raft_tpu.core import tracing

    base = (f"serving.collective.{family}."
            f"{model['wire_dtype']}.{model['probe_wire_dtype']}.")
    tracing.set_gauges({
        base + "coarse_bytes": float(model["coarse_bytes"]),
        base + "dense_coarse_bytes": float(model["dense_coarse_bytes"]),
        base + "merge_bytes": float(model["merge_bytes"]),
    })


def resolve_auto_wires(q: int, k: int, n_probes: int, n_lists: int,
                       r: int, wire_dtype: str, probe_mode: str,
                       probe_wire_dtype: str) -> Tuple[str, str]:
    """Resolve ``"auto"`` wire selections by argmin over the modeled
    per-shard payload (:func:`collective_payload_model`) — the byte
    accounting the comms ledger and bench riders publish, closing its
    own loop. The merge wire argmins ``merge_bytes`` over the
    result-wire formats, the probe wire ``coarse_bytes`` over the
    probe-wire formats (the candidate orderings differ: int8's affine
    scale planes can outweigh its code savings on tiny candidate
    sets). Ties prefer the wider (less lossy) wire; concrete dtypes
    pass through unchanged."""
    from raft_tpu.comms.comms import PROBE_WIRE_DTYPES, WIRE_DTYPES

    def bytes_for(wd: str, pwd: str) -> dict:
        return collective_payload_model(q, k, n_probes, n_lists, r,
                                        wd, probe_mode, pwd)

    if wire_dtype == "auto":
        wire_dtype = min(WIRE_DTYPES,
                         key=lambda wd: bytes_for(wd, "f32")["merge_bytes"])
    if probe_wire_dtype == "auto":
        probe_wire_dtype = min(
            PROBE_WIRE_DTYPES,
            key=lambda pwd: bytes_for("f32", pwd)["coarse_bytes"])
    return wire_dtype, probe_wire_dtype


def resolve_query_sharding(comms: Comms, queries, query_axis):
    """Shared ``query_axis`` validation + placement for the 2-D
    list×query grids: returns the sharding the replicated-or-sharded
    queries should be placed with."""
    if query_axis is not None:
        expect(query_axis in comms.mesh.axis_names
               and query_axis != comms.axis,
               f"query_axis {query_axis!r} must be another mesh axis")
        expect(queries.shape[0] % comms.mesh.shape[query_axis] == 0,
               "the query-axis size must divide the query count evenly")
        return comms.sharding(query_axis)
    return comms.replicated()


def resolve_probe_budget(n_probes: int, n_lists: int, mesh_size: int,
                         probe_mode: str) -> int:
    """Shared probe-budget clamp for the list-sharded search entries:
    validates ``probe_mode`` and converts the user's global probe count
    into this mode's per-program budget (local mode probes each shard's
    own ``ceil(n_probes / R)`` lists)."""
    expect(probe_mode in ("global", "local"),
           f"probe_mode must be 'global' or 'local', got {probe_mode!r}")
    local_lists = n_lists // mesh_size
    n_probes = min(n_probes, n_lists)
    if probe_mode == "local":
        n_probes = min(-(-n_probes // mesh_size), local_lists)
    return n_probes


def build(
    res: Optional[Resources],
    comms: Comms,
    params: IvfFlatIndexParams,
    dataset,
) -> DistributedIvfFlat:
    """Build a list-sharded index: global balanced-kmeans quantizer, then
    lists dealt round-robin by population and placed shard-local (the
    deal streams per shard block — :func:`place_dealt` — so the build
    device never holds a second fully-permuted copy of the index).

    ``params.n_lists`` is rounded up to a multiple of the mesh-axis size.
    """
    res = ensure_resources(res)
    r = comms.size
    n_lists = -(-params.n_lists // r) * r
    params = dataclasses.replace(params, n_lists=n_lists)

    with tracing.range("raft_tpu.distributed.ivf_flat.build"):
        # single-chip build (global quantizer + packed lists), then deal
        return shard_index(comms, ivf_flat_mod.build(res, params, dataset))


def shard_index(comms: Comms, index) -> DistributedIvfFlat:
    """Deal a built single-chip :class:`~raft_tpu.neighbors.ivf_flat
    .IvfFlatIndex` over ``comms``' mesh axis — the placement half of
    :func:`build`. Its ``n_lists`` must divide by the axis size. The
    blocked layout wants shard-contiguous rows: the deal streams per
    shard block per the shared layout policy."""
    expect(index.n_lists % comms.size == 0,
           f"n_lists {index.n_lists} must divide by the mesh axis size "
           f"{comms.size}")
    sizes = np.asarray(jax.device_get(index.list_sizes))
    perm = deal_order(sizes, comms.size)
    admit_deal((index.centers, index.data, index.data_norms,
                index.indices, index.list_sizes), comms.size,
               "distributed.ivf_flat.build.deal")

    def place(a):
        return place_dealt(a, perm, comms)

    return DistributedIvfFlat(
        comms=comms,
        centers=place(index.centers),
        data=place(index.data),
        data_norms=place(index.data_norms),
        indices=place(index.indices),
        list_sizes=place(index.list_sizes),
        metric=index.metric,
    )


def _dist_search_fn(queries, centers, data, data_norms, indices,
                    probe_counts=None, n_valid=None, row_probes=None, *,
                    axis: str, mesh,
                    n_probes: int, k: int, metric: DistanceType,
                    probe_mode: str, query_axis: Optional[str] = None,
                    coarse_algo: str = "exact", scan_engine: str = "rank",
                    wire_dtype: str = "f32",
                    probe_wire_dtype: str = "f32"):
    """One shard_map program: local coarse → (global|local) probe
    select → shard-local probe scan → lean O(q · k) result merge.

    ``scan_engine`` must arrive resolved (``rank``/``pallas``/``xla``,
    via :func:`raft_tpu.ops.ivf_scan.resolve_scan_engine`) — it is a
    jit static, and the mesh-aware serving path keys AOT executables on
    it. The list-major engines mask not-owned probes to the sentinel id
    ``n_local`` so each shard streams only the union of lists it owns.
    ``n_valid`` (traced scalar, replicated; 1-D meshes) is the real row
    count: bucket-pad rows own no probe, so they add the scan no pair.

    ``probe_counts`` (graftgauge) optionally provides the donated
    list-sharded (n_lists,) int32 probe-frequency plane: each shard
    scatter-adds only the probes it OWNS into its local slice (so a
    probe counts exactly once mesh-wide) and the updated plane returns
    as a third output. Replicated-query dispatches only (the mesh
    executor's mode; a ``query_axis`` grid would write divergent
    replicas).

    ``row_probes`` (the mesh ragged front, via
    :func:`_dist_search_ragged_fn`) optionally provides a packed
    ragged tile's per-row GLOBAL probe budgets (replicated ``(tile,)``
    int32, 0 on pad rows): the probe selection then runs at the class
    cap ``n_probes`` and each row's ownership columns past its own
    budget fold out of ``mine``
    (:func:`raft_tpu.ops.ivf_scan.ragged_owned`) — the scan's sentinel
    masking, the result merge, and the probe accounting all already
    consume that mask, so ONE replicated-tile executable serves every
    per-request ``n_probes`` in the class, bit-identical per request
    to the bucketed dispatch."""
    select_min = is_min_close(metric)
    pad_val = jnp.inf if select_min else -jnp.inf
    interpret = jax.default_backend() != "tpu"
    ragged = row_probes is not None

    def body(centers_l, data_l, norms_l, ids_l, qs, *rest):
        rest = list(rest)
        rp = rest.pop(0) if ragged else None
        cnt = rest.pop(0) if probe_counts is not None else None
        nv = rest.pop(0) if n_valid is not None else None
        q = qs.shape[0]
        n_local = centers_l.shape[0]
        qf = qs.astype(jnp.float32)

        # graftflight phase markers: each mesh phase runs under a
        # jax.named_scope so the HLO ops carry coarse_select/scan/
        # merge in their op paths — a profiler capture then attributes
        # MEASURED device time per phase (core/profiling.PHASE_MARKERS)
        # instead of only the modeled byte windows. Pure metadata:
        # zero ops added, bit-identity and zero-recompile untouched.
        with jax.named_scope("coarse_select"):
            # coarse distances to this shard's centers
            ip = jax.lax.dot_general(
                qf, centers_l, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )
            if metric == DistanceType.InnerProduct:
                coarse = -ip
            else:
                cn = jnp.sum(jnp.square(centers_l), axis=1)
                coarse = cn[None, :] - 2.0 * ip

            local, mine = select_probes_sharded(coarse, n_probes, axis,
                                                probe_mode, coarse_algo,
                                                probe_wire_dtype)
            if rp is not None:
                # ragged: a row owns only the probe columns below its
                # own budget (columns are rank-ordered — the prefix
                # property); local mode converts to per-shard budgets
                from raft_tpu.ops.ivf_scan import ragged_owned

                mine = ragged_owned(
                    mine, rp,
                    shards=(mesh.shape[axis]
                            if probe_mode == "local" else 1))
            if nv is not None:
                # bucket-pad rows own no probe, so the scan gives them
                # no (query, list) pair
                valid = jnp.arange(q, dtype=jnp.int32) < nv
                mine = jnp.logical_and(mine, valid[:, None])
        if cnt is not None:
            from raft_tpu.ops.ivf_scan import probe_histogram

            cnt = probe_histogram(local, cnt, nv, owned=mine)

        if scan_engine != "rank":
            # list-major: not-owned probes mask to the sentinel id
            # n_local (ops/ivf_scan mask plumbing); each owned probed
            # list streams from HBM once — the single-chip engines,
            # unchanged, running inside the shard_map body
            with jax.named_scope("scan"):
                masked = jnp.where(mine, local, n_local).astype(jnp.int32)
                best_d, best_i = list_major_scan(
                    qf, data_l, norms_l, ids_l, masked, None,
                    k=k, metric=metric, engine=scan_engine,
                    interpret=interpret)
        else:
            def step(carry, rank_i):
                best_d, best_i = carry
                lists = local[:, rank_i]
                valid = mine[:, rank_i]
                rows = jnp.take(data_l, lists, axis=0).astype(jnp.float32)
                row_norms = jnp.take(norms_l, lists, axis=0)
                row_ids = jnp.take(ids_l, lists, axis=0)
                ipr = jax.lax.dot_general(
                    rows, qf, (((2,), (1,)), ((0,), (0,))),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32,
                )
                if metric == DistanceType.InnerProduct:
                    dist = ipr
                else:
                    dist = row_norms - 2.0 * ipr
                dist = jnp.where((row_ids >= 0) & valid[:, None], dist,
                                 pad_val)
                return merge_topk(best_d, best_i, dist, row_ids, k,
                                  select_min), None

            init = (jnp.full((q, k), pad_val, jnp.float32),
                    jnp.full((q, k), -1, jnp.int32))
            with jax.named_scope("scan"):
                (best_d, best_i), _ = jax.lax.scan(
                    step, init, jnp.arange(local.shape[1]))

        with jax.named_scope("merge"):
            # 2-D grids scatter-merge: each list shard merges a
            # disjoint query slice instead of the whole replicated
            # candidate table (bit-identical — rank-order stacks)
            merged = merge_results_sharded(
                best_d, best_i, axis, select_min, wire_dtype,
                smallest_id_ties=scan_engine != "rank",
                scatter=query_axis is not None)
        if cnt is not None:
            return merged + (cnt,)
        return merged

    # 2-D grid: queries shard over a second mesh axis while lists shard
    # over the first — the reference's row/col process grid
    # (``sub_comms.hpp``). Each device handles its (list-block,
    # query-block) cell; merges stay within the list axis.
    qspec = P() if query_axis is None else P(query_axis, None)
    args = [centers, data, data_norms, indices, queries]
    in_specs = [P(axis, None), P(axis, None, None), P(axis, None),
                P(axis, None), qspec]
    out_specs = [qspec, qspec]
    if ragged:
        args += [row_probes]
        in_specs += [P()]           # replicated per-row budget plane
    if probe_counts is not None:
        args += [probe_counts]
        in_specs += [P(axis)]
        out_specs += [P(axis)]
    if n_valid is not None:
        args += [n_valid]
        in_specs += [P()]
    outs = shard_map(
        body, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=tuple(out_specs),
        check_vma=False,
    )(*args)
    out_d, out_i = outs[0], outs[1]

    if metric != DistanceType.InnerProduct:
        q_sq = jnp.sum(jnp.square(queries.astype(jnp.float32)), axis=1,
                       keepdims=True)
        out_d = jnp.where(jnp.isfinite(out_d),
                          jnp.maximum(out_d + q_sq, 0.0), out_d)
        if metric == DistanceType.L2SqrtExpanded:
            out_d = jnp.where(jnp.isfinite(out_d), jnp.sqrt(out_d), out_d)
    if probe_counts is not None:
        return out_d, out_i, outs[2]
    return out_d, out_i


_dist_search = partial(jax.jit, static_argnames=(
    "axis", "mesh", "n_probes", "k", "metric", "probe_mode", "query_axis",
    "coarse_algo", "scan_engine", "wire_dtype",
    "probe_wire_dtype"))(_dist_search_fn)


def _dist_search_ragged_fn(queries, row_probes, centers, data, data_norms,
                           indices, probe_counts=None, n_valid=None, *,
                           axis: str,
                           mesh, n_probes: int, k: int,
                           metric: DistanceType, probe_mode: str,
                           scan_engine: str = "xla",
                           wire_dtype: str = "f32",
                           probe_wire_dtype: str = "f32"):
    """Packed ragged-batch mesh search — the distributed IVF-flat
    member of the serving executor's ragged plan family: ONE
    replicated-tile executable per (mesh, params class) replaces the
    distributed bucket ladder. The packing contract is
    :func:`raft_tpu.neighbors.ivf_flat._search_ragged_fn`'s; the
    per-row budgets ride the replicated ``row_probes`` plane into
    :func:`_dist_search_fn`'s ownership mask
    (:func:`raft_tpu.ops.ivf_scan.ragged_owned`), so the sharded body
    — probe-ownership arithmetic, sentinel-masked shard-local scan,
    list-sharded probe plane, lean result merge — is char-identical
    to the bucketed dispatch. Exact coarse select only, list-major
    engines only (the rank-major scan's positional-tie merge is not
    budget-prefix-stable)."""
    expect(scan_engine in ("pallas", "xla"),
           "mesh ragged serving needs a membership-masked list-major "
           f"engine (pallas|xla), got {scan_engine!r}")
    return _dist_search_fn(
        queries, centers, data, data_norms, indices,
        probe_counts, n_valid, row_probes=row_probes, axis=axis,
        mesh=mesh, n_probes=n_probes, k=k, metric=metric,
        probe_mode=probe_mode, coarse_algo="exact",
        scan_engine=scan_engine, wire_dtype=wire_dtype,
        probe_wire_dtype=probe_wire_dtype)


def search(
    res: Optional[Resources],
    params: IvfFlatSearchParams,
    index: DistributedIvfFlat,
    queries,
    k: int,
    probe_mode: str = "global",
    query_axis: Optional[str] = None,
    wire_dtype: str = "f32",
    probe_wire_dtype: str = "f32",
    trace_id: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """One-program distributed search; returns replicated (q, k) results
    with global row ids. See the module docstring for ``probe_mode``.
    ``query_axis`` names a second mesh axis to shard queries over (2-D
    list × query grid); results come back sharded over that axis.
    ``wire_dtype="bf16"`` halves the result-merge collective payload
    (distances compressed on the wire; ids exact, smallest-id ties);
    ``probe_wire_dtype`` (``f32|bf16|int8``) additionally compresses
    the probe-candidate exchange — int8 rides a per-query scale and
    trades a little probe-selection fidelity for ~4x fewer coarse
    bytes (see :func:`select_probes_sharded`).
    The probe scan engine follows ``params.scan_engine`` exactly like
    the single-chip entry (resolved per backend/shape by
    :func:`raft_tpu.ops.ivf_scan.resolve_scan_engine`).
    ``trace_id`` (graftscope v2) opts this call into mesh span
    recording — the dispatch blocks, times, and lands the three phase
    spans with modeled wire bytes (:func:`record_dispatch`)."""
    ensure_resources(res)
    queries = jnp.asarray(queries)
    expect(queries.ndim == 2 and queries.shape[1] == index.dim,
           "queries must be (q, dim)")
    comms = index.comms
    qsharding = resolve_query_sharding(comms, queries, query_axis)
    n_probes = resolve_probe_budget(params.n_probes, index.n_lists,
                                    comms.size, probe_mode)
    expect(params.coarse_algo in ("exact", "approx"),
           f"coarse_algo must be 'exact' or 'approx', got "
           f"{params.coarse_algo!r}")
    wire_dtype, probe_wire_dtype = resolve_auto_wires(
        queries.shape[0], k, n_probes, index.n_lists, comms.size,
        wire_dtype, probe_mode, probe_wire_dtype)
    resolve_wire_dtype(wire_dtype)
    resolve_probe_wire_dtype(probe_wire_dtype)
    from raft_tpu.ops.ivf_scan import resolve_scan_engine

    scan_engine = resolve_scan_engine(params.scan_engine, data=index.data,
                                      k=k)
    queries = jax.device_put(queries, qsharding)
    with tracing.range("raft_tpu.distributed.ivf_flat.search"):
        # lazy: only a traced dispatch (trace_id=) builds the model
        model = lambda: collective_payload_model(  # noqa: E731
            queries.shape[0], k, n_probes, index.n_lists, comms.size,
            wire_dtype, probe_mode, probe_wire_dtype)
        return record_dispatch(
            "dist_ivf_flat", model, trace_id, axis=comms.axis,
            thunk=lambda: _dist_search(
                queries, index.centers, index.data, index.data_norms,
                index.indices, axis=comms.axis, mesh=comms.mesh,
                n_probes=n_probes, k=k, metric=index.metric,
                probe_mode=probe_mode, query_axis=query_axis,
                coarse_algo=params.coarse_algo, scan_engine=scan_engine,
                wire_dtype=wire_dtype, probe_wire_dtype=probe_wire_dtype,
            ))


_BYTE_STORAGE = (np.dtype(np.uint8), np.dtype(np.int8))

# one host span (and a ``<name>_seconds`` histogram) per pass of
# build_streaming, each ended once its results are ready
STREAM_STAGES = ("sample", "quantizer", "labels", "scatter", "norms")
STREAM_SPAN = "distributed.build.{}"


def _stage(name: str):
    span = STREAM_SPAN.format(name)
    return tracing.host_span(span, hist=span + "_seconds")


def _list_storage(source_dtype) -> np.dtype:
    """The dtype :func:`build_streaming` holds the lists in: a
    uint8/int8 source's own bytes, float32 for anything else."""
    src = np.dtype(source_dtype)
    return src if src in _BYTE_STORAGE else np.dtype(np.float32)


def _scatter_rows_fn(data, idx, rows, meta, *, axis: str, mesh):
    """Scatter one chunk (replicated over the mesh) into the
    list-sharded buffers. ``meta`` is ``(3, m)`` int32: each row's id,
    dealt list and rank within the list. Each shard keeps the rows of
    the lists it owns and drops the rest."""

    def body(data_l, idx_l, rows, meta):
        ids, pos, ranks = meta
        n_local = data_l.shape[0]
        local = pos - comm_rank(axis).astype(jnp.int32) * n_local
        local = jnp.where((local >= 0) & (local < n_local), local, n_local)
        return (data_l.at[local, ranks].set(rows, mode="drop"),
                idx_l.at[local, ranks].set(ids, mode="drop"))

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None, None), P(axis, None), P(), P()),
        out_specs=(P(axis, None, None), P(axis, None)),
        check_vma=False)(data, idx, rows, meta)


_scatter_rows = partial(jax.jit, donate_argnums=(0, 1),
                        static_argnames=("axis", "mesh"))(_scatter_rows_fn)


@jax.jit
def _stream_norms(data, indices):
    norms = jnp.sum(jnp.square(data.astype(jnp.float32)), axis=2)
    return jnp.where(indices >= 0, norms, jnp.inf)


def build_streaming(
    res: Optional[Resources],
    comms: Comms,
    params: IvfFlatIndexParams,
    source,
    chunk_rows: int = 1 << 20,
    train_rows: int = 1 << 18,
) -> DistributedIvfFlat:
    """Stream a dataset larger than any single chip's HBM directly into
    the list-sharded index: the quantizer trains on a strided sample,
    then every chunk is labelled and scattered into the ALREADY-SHARDED
    device buffers (donated, so updates stay in place on their shards).
    This is the capacity story of the distributed index — the dataset
    never materializes on one device or in host memory. A uint8/int8
    source stays bytes in the lists.

    ``source`` has ``n_rows``, ``dim``, ``dtype`` and ``iter_chunks``:
    a :class:`raft_tpu.io.BinDataset` (host chunks), or a corpus whose
    chunks are ``jax.Array`` s on any of the mesh's devices, each
    labelled on the device that holds it
    (:func:`raft_tpu.neighbors._streaming.label_pass`).

    The lists keep a uint8/int8 source's bytes (a quarter of float32's
    HBM; the list scan widens them in VMEM) and hold anything else as
    float32. ``data_norms`` are float32 sums of squares of the stored values
    (exact integers for bytes). The quantizer trains on a
    ``train_rows``-row sample (of which ``ivf_flat.build`` keeps its
    ``kmeans_trainset_fraction``): enough rows per list keep the lists
    even, and the padded extent — the largest list — sets the index's
    bytes. Each pass runs under a ``distributed.build.<stage>`` host
    span (:data:`STREAM_STAGES`) whose ``_seconds`` histogram records
    its duration.
    """
    res = ensure_resources(res)
    r = comms.size
    n_lists = -(-params.n_lists // r) * r
    params = dataclasses.replace(params, n_lists=n_lists,
                                 add_data_on_build=False)
    n, d = source.n_rows, source.dim
    storage = _list_storage(getattr(source, "dtype", np.float32))

    with tracing.range("raft_tpu.distributed.ivf_flat.build_streaming"):
        # quantizer on a strided sample + per-chunk labels: the SAME
        # passes as the single-chip streaming builds — shared helpers,
        # not a re-implementation (each chunk a cancellation point)
        from raft_tpu.cluster.kmeans_balanced import KMeansBalancedParams
        from raft_tpu.neighbors._streaming import (
            label_pass,
            sample_trainset,
        )

        with _stage("sample"):
            train_rows = max(n_lists, min(train_rows, n))
            trainset = sample_trainset(source, train_rows, chunk_rows,
                                       storage)
        with _stage("quantizer"):
            quant = ivf_flat_mod.build(res, params, trainset)
            jax.block_until_ready(quant.centers)

        km = KMeansBalancedParams(
            metric=(DistanceType.InnerProduct
                    if params.metric == DistanceType.InnerProduct
                    else DistanceType.L2Expanded))
        with _stage("labels"):
            labels_np, sizes_np = label_pass(
                res, km, jax.device_put(quant.centers, comms.replicated()),
                source, chunk_rows, n_lists)
        max_size = padded_extent(sizes_np, storage)

        # deal lists round-robin by population; dealt[i] = original list
        deal = deal_order(sizes_np, r)
        dealt_pos = np.empty((n_lists,), np.int32)
        dealt_pos[deal] = np.arange(n_lists, dtype=np.int32)

        shard = comms.sharding(comms.axis)
        repl = comms.replicated()
        # gate the per-shard staging BEFORE the sharded buffers (and
        # the norms plane derived later) allocate — planned shapes,
        # nothing materialized yet
        admit_deal(
            (jax.ShapeDtypeStruct((n_lists, max_size, d), storage),
             jax.ShapeDtypeStruct((n_lists, max_size), jnp.int32),
             jax.ShapeDtypeStruct((n_lists, max_size), jnp.float32)),
            r, "distributed.ivf_flat.build_streaming.deal")
        # each device allocates only its own shard of the buffers
        data = jax.jit(partial(jnp.zeros, (n_lists, max_size, d), storage),
                       out_shardings=shard)()
        indices = jax.jit(partial(jnp.full, (n_lists, max_size), -1,
                                  jnp.int32), out_shardings=shard)()

        with _stage("scatter"):
            fill = np.zeros((n_lists,), np.int64)
            for first, chunk in source.iter_chunks(chunk_rows):
                interruptible.yield_()  # cancellation point per chunk
                m = chunk.shape[0]
                lab = labels_np[first : first + m]
                ranks = streaming_ranks(lab, fill, n_lists)
                meta = np.stack([first + np.arange(m, dtype=np.int32),
                                 dealt_pos[lab], ranks])
                # graftlint: disable=R5(streaming scatter: one replicated put per chunk bounds build staging to O(chunk))
                rows, meta = jax.device_put((chunk.astype(storage), meta),
                                            repl)
                data, indices = _scatter_rows(data, indices, rows, meta,
                                              axis=comms.axis,
                                              mesh=comms.mesh)
            jax.block_until_ready((data, indices))
        with _stage("norms"):
            norms = jax.block_until_ready(_stream_norms(data, indices))

        return DistributedIvfFlat(
            comms=comms,
            centers=place_dealt(quant.centers, deal, comms),
            data=data,
            data_norms=norms,
            indices=indices,
            list_sizes=jax.device_put(
                jnp.asarray(sizes_np[deal], jnp.int32), shard),
            metric=DistanceType(params.metric),
        )


# ---------------------------------------------------------------------------
# distributed IVF-PQ — the SIFT-1B-scale configuration: compressed codes
# sharded over the mesh, per-subspace codebooks replicated
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DistributedIvfPq:
    """List-sharded IVF-PQ index (codes + ids sharded on the list axis,
    rotation replicated). PER_SUBSPACE codebooks are replicated;
    PER_CLUSTER codebooks are per-list data and shard with the lists."""

    comms: Comms
    centers: jax.Array        # (n_lists, dim) sharded on axis 0
    rotation: jax.Array       # (dim_ext, dim) replicated
    codebooks: jax.Array      # PER_SUBSPACE: (pq_dim, 2^bits, pq_len) repl.
                              # PER_CLUSTER:  (n_lists, 2^bits, pq_len) shard.
    codes: jax.Array          # (n_lists, max_list_size, pq_dim) u8 sharded
    indices: jax.Array        # (n_lists, max_list_size) int32 sharded
    list_sizes: jax.Array     # (n_lists,) sharded
    metric: DistanceType
    pq_bits: int
    codebook_kind: CodebookKind = CodebookKind.PER_SUBSPACE

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]

    @property
    def max_list_size(self) -> int:
        return self.codes.shape[1]

    @property
    def pq_dim(self) -> int:
        return self.codes.shape[2]

    @property
    def pq_len(self) -> int:
        return self.codebooks.shape[2]

    @property
    def size(self) -> int:
        return int(jax.device_get(self.list_sizes).sum())


def build_pq(
    res: Optional[Resources],
    comms: Comms,
    params: IvfPqIndexParams,
    dataset,
) -> DistributedIvfPq:
    """Build + deal, like :func:`build`. PER_SUBSPACE codebooks are
    replicated; PER_CLUSTER codebooks are per-list data and are dealt +
    sharded together with the lists they describe."""
    res = ensure_resources(res)
    r = comms.size
    n_lists = -(-params.n_lists // r) * r
    params = dataclasses.replace(params, n_lists=n_lists)

    with tracing.range("raft_tpu.distributed.ivf_pq.build"):
        index = ivf_pq_mod.build(res, params, dataset)
        codes = index.codes
        if index.packed:
            # the distributed scan uses the unpacked layout
            from raft_tpu.neighbors.ivf_pq import _unpack_nibbles

            codes = _unpack_nibbles(codes)
            index = dataclasses.replace(index, codes=codes, packed=False)

        sizes = np.asarray(jax.device_get(index.list_sizes))
        perm = deal_order(sizes, r)
        per_cluster = params.codebook_kind == CodebookKind.PER_CLUSTER
        admit_deal(
            (index.centers, index.codes, index.indices,
             index.list_sizes)
            + ((index.codebooks,) if per_cluster else ()),
            r, "distributed.ivf_pq.build.deal")

        def place(a):
            return place_dealt(a, perm, comms)

        rep = comms.replicated()
        return DistributedIvfPq(
            comms=comms,
            centers=place(index.centers),
            rotation=jax.device_put(index.rotation, rep),
            codebooks=(place(index.codebooks) if per_cluster
                       else jax.device_put(index.codebooks, rep)),
            codes=place(index.codes),
            indices=place(index.indices),
            list_sizes=place(index.list_sizes),
            metric=index.metric,
            pq_bits=index.pq_bits,
            codebook_kind=params.codebook_kind,
        )


def _dist_search_pq_fn(queries, centers, rotation, codebooks, codes,
                       indices, probe_counts=None, n_valid=None,
                       row_probes=None, *, axis: str,
                       mesh, n_probes: int, k: int, metric: DistanceType,
                       probe_mode: str, query_axis: Optional[str] = None,
                       codebook_kind: CodebookKind = (
                           CodebookKind.PER_SUBSPACE),
                       score_mode: str = "gather", lut_dtype=jnp.float32,
                       coarse_algo: str = "exact",
                       scan_engine: str = "rank",
                       wire_dtype: str = "f32",
                       probe_wire_dtype: str = "f32"):
    """Distributed ADC probe scan — same engine plumbing as
    :func:`_dist_search_fn` (``scan_engine: xla`` is the list-major
    union scan of :mod:`raft_tpu.neighbors.ivf_pq`, run per shard with
    not-owned probes masked to the sentinel id), including the optional
    donated list-sharded ``probe_counts`` plane (owned probes only)
    and the optional ragged ``row_probes`` budget plane (see
    :func:`_dist_search_fn`)."""
    select_min = is_min_close(metric)
    pad_val = jnp.inf if select_min else -jnp.inf
    pq_dim = codes.shape[2]
    pq_len = codebooks.shape[2]
    ip_metric = metric == DistanceType.InnerProduct
    per_cluster = codebook_kind == CodebookKind.PER_CLUSTER
    score = ivf_pq_mod.score_fn(score_mode, codebooks.shape[1])
    ragged = row_probes is not None

    def body(centers_l, books_l, codes_l, ids_l, qs, *rest):
        rest = list(rest)
        rp = rest.pop(0) if ragged else None
        cnt, nv = rest if rest else (None, None)
        q = qs.shape[0]
        n_local = centers_l.shape[0]
        qf = qs.astype(jnp.float32)

        # graftflight phase markers (see _dist_search_fn): pure HLO
        # op-path metadata for measured per-phase device attribution
        with jax.named_scope("coarse_select"):
            ip = jax.lax.dot_general(
                qf, centers_l, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )
            if ip_metric:
                coarse = -ip
            else:
                cn = jnp.sum(jnp.square(centers_l), axis=1)
                coarse = cn[None, :] - 2.0 * ip

            local, mine = select_probes_sharded(coarse, n_probes, axis,
                                                probe_mode, coarse_algo,
                                                probe_wire_dtype)
            if rp is not None:
                from raft_tpu.ops.ivf_scan import ragged_owned

                mine = ragged_owned(
                    mine, rp,
                    shards=(mesh.shape[axis]
                            if probe_mode == "local" else 1))
        if cnt is not None:
            from raft_tpu.ops.ivf_scan import probe_histogram

            cnt = probe_histogram(local, cnt, nv, owned=mine)

        qsub_fixed = (qf @ rotation.T).reshape(q, pq_dim, pq_len)
        lut_fixed = (jnp.einsum("qsl,sjl->qsj", qsub_fixed, books_l)
                     if ip_metric and not per_cluster else None)

        def probe_dist(lists, rows, row_ids):
            c = jnp.take(centers_l, lists, axis=0)        # (q, dim)
            lut, base = ivf_pq_mod._probe_lut(
                qf, c, qsub_fixed, lut_fixed, rotation, books_l, lists,
                ip_metric, per_cluster)
            lut, lut_scale = ivf_pq_mod.quantize_lut(lut, lut_dtype)
            dist = score(lut, rows)
            if lut_scale is not None:
                dist = dist * lut_scale
            dist = dist + base[:, None]
            return jnp.where(row_ids >= 0, dist, pad_val)

        if scan_engine != "rank":
            # list-major union scan (the single-chip ivf_pq "xla"
            # engine inside the shard body): min-space with the
            # smallest-id tie-break, not-owned probes masked out
            from raft_tpu.ops.ivf_scan import (
                _merge_smallest_id,
                unique_lists,
            )

            masked = jnp.where(mine, local, n_local).astype(jnp.int32)

            def step(carry, lid):
                best_d, best_i = carry
                lidc = jnp.minimum(lid, n_local - 1)       # sentinel-safe
                lists = jnp.full((q,), lidc, jnp.int32)
                rows1 = jax.lax.dynamic_index_in_dim(codes_l, lidc, 0,
                                                     False)
                ids1 = jax.lax.dynamic_index_in_dim(ids_l, lidc, 0, False)
                rows = jnp.broadcast_to(rows1[None], (q,) + rows1.shape)
                row_ids = jnp.broadcast_to(ids1[None], (q, ids1.shape[0]))
                dist = probe_dist(lists, rows, row_ids)
                if not select_min:
                    dist = -dist                           # to min-space
                probed = (jnp.any(masked == lid, axis=1)
                          & (lid < n_local))               # membership
                dist = jnp.where(probed[:, None], dist, jnp.inf)
                return _merge_smallest_id(best_d, best_i, dist, row_ids,
                                          k), None

            init = (jnp.full((q, k), jnp.inf, jnp.float32),
                    jnp.full((q, k), -1, jnp.int32))
            with jax.named_scope("scan"):
                (best_d, best_i), _ = jax.lax.scan(
                    step, init, unique_lists(masked, n_local))
            if not select_min:
                best_d = -best_d
        else:
            def step(carry, rank_i):
                best_d, best_i = carry
                lists = local[:, rank_i]
                valid = mine[:, rank_i]
                rows = jnp.take(codes_l, lists, axis=0)    # (q, m, s) u8
                row_ids = jnp.take(ids_l, lists, axis=0)
                dist = probe_dist(lists, rows, row_ids)
                dist = jnp.where(valid[:, None], dist, pad_val)
                return merge_topk(best_d, best_i, dist, row_ids, k,
                                  select_min), None

            init = (jnp.full((q, k), pad_val, jnp.float32),
                    jnp.full((q, k), -1, jnp.int32))
            with jax.named_scope("scan"):
                (best_d, best_i), _ = jax.lax.scan(
                    step, init, jnp.arange(local.shape[1]))

        with jax.named_scope("merge"):
            # 2-D grids scatter-merge: each list shard merges a
            # disjoint query slice instead of the whole replicated
            # candidate table (bit-identical — rank-order stacks)
            merged = merge_results_sharded(
                best_d, best_i, axis, select_min, wire_dtype,
                smallest_id_ties=scan_engine != "rank",
                scatter=query_axis is not None)
        if cnt is not None:
            return merged + (cnt,)
        return merged

    qspec = P() if query_axis is None else P(query_axis, None)
    bspec = P(axis, None, None) if per_cluster else P(None, None, None)
    args = [centers, codebooks, codes, indices, queries]
    in_specs = [P(axis, None), bspec, P(axis, None, None), P(axis, None),
                qspec]
    out_specs = [qspec, qspec]
    if ragged:
        args += [row_probes]
        in_specs += [P()]           # replicated per-row budget plane
    if probe_counts is not None:
        args += [probe_counts, n_valid]
        in_specs += [P(axis), P()]
        out_specs += [P(axis)]
    outs = shard_map(
        body, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=tuple(out_specs),
        check_vma=False,
    )(*args)
    out_d, out_i = outs[0], outs[1]

    if metric == DistanceType.L2SqrtExpanded:
        out_d = jnp.where(jnp.isfinite(out_d),
                          jnp.sqrt(jnp.maximum(out_d, 0.0)), out_d)
    if probe_counts is not None:
        return out_d, out_i, outs[2]
    return out_d, out_i


_dist_search_pq = partial(jax.jit, static_argnames=(
    "axis", "mesh", "n_probes", "k", "metric", "probe_mode", "query_axis",
    "codebook_kind", "score_mode", "lut_dtype", "coarse_algo",
    "scan_engine", "wire_dtype", "probe_wire_dtype"))(_dist_search_pq_fn)


def _dist_search_ragged_pq_fn(queries, row_probes, centers, rotation,
                              codebooks, codes, indices,
                              probe_counts=None, n_valid=None, *,
                              axis: str, mesh,
                              n_probes: int, k: int,
                              metric: DistanceType, probe_mode: str,
                              codebook_kind: CodebookKind = (
                                  CodebookKind.PER_SUBSPACE),
                              score_mode: str = "gather",
                              lut_dtype=jnp.float32,
                              scan_engine: str = "xla",
                              wire_dtype: str = "f32",
                              probe_wire_dtype: str = "f32"):
    """Packed ragged-batch mesh PQ search — see
    :func:`_dist_search_ragged_fn` for the replicated-tile contract;
    per-row budgets fold into the shard body's ownership mask and the
    LUT union scan serves the packed tile unchanged."""
    expect(scan_engine == "xla",
           "mesh ragged PQ serving needs the membership-masked "
           f"list-major engine ('xla'), got {scan_engine!r}")
    return _dist_search_pq_fn(
        queries, centers, rotation, codebooks, codes, indices,
        probe_counts, n_valid, row_probes=row_probes, axis=axis,
        mesh=mesh, n_probes=n_probes, k=k, metric=metric,
        probe_mode=probe_mode, codebook_kind=codebook_kind,
        score_mode=score_mode, lut_dtype=lut_dtype,
        coarse_algo="exact", scan_engine=scan_engine,
        wire_dtype=wire_dtype, probe_wire_dtype=probe_wire_dtype)


def search_pq(
    res: Optional[Resources],
    params: IvfPqSearchParams,
    index: DistributedIvfPq,
    queries,
    k: int,
    probe_mode: str = "global",
    query_axis: Optional[str] = None,
    wire_dtype: str = "f32",
    probe_wire_dtype: str = "f32",
    trace_id: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """One-program distributed PQ search (LUT scoring per shard, lean
    global merge); semantics of :func:`search` incl. the 2-D
    ``query_axis``, the ``wire_dtype`` result compression, the
    ``probe_wire_dtype`` quantized probe-candidate exchange, and the
    opt-in ``trace_id`` mesh span recording. The probe
    scan follows ``params.scan_engine`` (``auto|xla|rank``, resolved by
    :func:`raft_tpu.neighbors.ivf_pq.resolve_scan_engine`)."""
    ensure_resources(res)
    queries = jnp.asarray(queries)
    expect(queries.ndim == 2 and queries.shape[1] == index.dim,
           "queries must be (q, dim)")
    comms = index.comms
    qsharding = resolve_query_sharding(comms, queries, query_axis)
    n_probes = resolve_probe_budget(params.n_probes, index.n_lists,
                                    comms.size, probe_mode)
    expect(params.coarse_algo in ("exact", "approx"),
           f"coarse_algo must be 'exact' or 'approx', got "
           f"{params.coarse_algo!r}")
    wire_dtype, probe_wire_dtype = resolve_auto_wires(
        queries.shape[0], k, n_probes, index.n_lists, comms.size,
        wire_dtype, probe_mode, probe_wire_dtype)
    resolve_wire_dtype(wire_dtype)
    resolve_probe_wire_dtype(probe_wire_dtype)
    scan_engine = ivf_pq_mod.resolve_scan_engine(params.scan_engine)
    queries = jax.device_put(queries, qsharding)
    with tracing.range("raft_tpu.distributed.ivf_pq.search"):
        # lazy: only a traced dispatch (trace_id=) builds the model
        model = lambda: collective_payload_model(  # noqa: E731
            queries.shape[0], k, n_probes, index.n_lists, comms.size,
            wire_dtype, probe_mode, probe_wire_dtype)
        return record_dispatch(
            "dist_ivf_pq", model, trace_id, axis=comms.axis,
            thunk=lambda: _dist_search_pq(
                queries, index.centers, index.rotation, index.codebooks,
                index.codes, index.indices, axis=comms.axis,
                mesh=comms.mesh, n_probes=n_probes, k=k,
                metric=index.metric, probe_mode=probe_mode,
                query_axis=query_axis, codebook_kind=index.codebook_kind,
                score_mode=params.score_mode, lut_dtype=params.lut_dtype,
                coarse_algo=params.coarse_algo, scan_engine=scan_engine,
                wire_dtype=wire_dtype, probe_wire_dtype=probe_wire_dtype,
            ))
