"""SPMD distributed IVF-BQ — the RaBitQ residual sign-code index (1-4
bits/dim) list-sharded over a mesh axis (same layout policy as
:mod:`raft_tpu.distributed.ivf`: lists dealt round-robin by
population, coarse quantizer sharded with its lists, rotation
replicated, raw-vector rerank plane sharded with its lists). Search is
one jitted ``shard_map`` program: local coarse top-p → shard-local
scan → all_gather + merge.

The shard-local scan runs the single-chip engine family
(:mod:`raft_tpu.ops.bq_scan`): the fused estimate-then-rerank
list-major engines (``scan_engine: auto|pallas|xla`` — exact
distances, probes the shard does not own masked to the sentinel the
same way the flat/PQ paths do) or the legacy rank-major estimate scan
(``"rank"``, and every codes-only index).

**Variance-corrected merge** (the ROADMAP residual): the per-shard
estimator error bound is measured at build time (``shard_rel_err``,
from the dealt layout) and :func:`merge_overfetch` derives the fetch
depth the caller needs from it — instead of the flat 2× over-fetch
the estimate-only merge used to burn (recall 0.95 vs 0.99 at equal
budget). With the fused engines the exchanged distances are exact,
the merge is lossless, and the derived depth collapses to ``k``
outright. The wire discipline ((distance, id) candidates at the
requested depth, ``collective_payload_model`` accounting) is
unchanged — only how the depth is chosen moved, from a hand constant
to the measured bound.

Probe semantics (``probe_mode``) match the IVF-Flat/PQ paths:
``"global"`` ranks all centers for exact list selection; ``"local"``
probes each shard's own top lists.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from raft_tpu.comms.comms import (
    Comms,
    resolve_probe_wire_dtype,
    resolve_wire_dtype,
    shard_map,
)
from raft_tpu.core import tracing
from raft_tpu.core.resources import Resources, ensure_resources
from raft_tpu.core.validation import expect
from raft_tpu.distance.types import DistanceType, is_min_close
from raft_tpu.matrix.select_k import merge_topk
from raft_tpu.neighbors import ivf_bq as ivf_bq_mod
from raft_tpu.neighbors._batching import tile_queries
from raft_tpu.neighbors.ivf_bq import (
    _OVERFETCH_KAPPA,
    IvfBqIndexParams,
    IvfBqSearchParams,
    score_probe,
)
from raft_tpu.distributed.ivf import (
    admit_deal,
    collective_payload_model,
    deal_order,
    merge_results_sharded,
    place_dealt,
    record_dispatch,
    resolve_probe_budget,
    resolve_query_sharding,
    select_probes_sharded,
)


@dataclasses.dataclass(frozen=True)
class DistributedIvfBq:
    """List-sharded IVF-BQ index (RaBitQ construction)."""

    comms: Comms
    centers: jax.Array        # (n_lists, dim) sharded on axis 0
    rotation: jax.Array       # (dim_ext, dim) replicated
    codes: jax.Array          # (n_lists, max, bits·D/32) i32 sharded
    rnorm: jax.Array          # (n_lists, max) f32 sharded — ‖r‖
    cfac: jax.Array           # (n_lists, max, bits) f32 sharded
    errw: jax.Array           # (n_lists, max) f32 sharded — ‖r−recon‖
    indices: jax.Array        # (n_lists, max) int32 sharded
    list_sizes: jax.Array     # (n_lists,) sharded
    metric: DistanceType
    # measured per-shard relative estimator error (host tuple, from
    # the dealt layout at build time) — the variance-corrected merge's
    # input; () means "unmeasured" and the merge falls back to the
    # most conservative shard-free bound
    shard_rel_err: tuple = ()
    # optional rerank plane (sharded with the lists)
    data: Optional[jax.Array] = None         # (n_lists, max, dim) f32
    data_norms: Optional[jax.Array] = None   # (n_lists, max) f32

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]

    @property
    def dim_ext(self) -> int:
        return self.rotation.shape[0]

    @property
    def bits(self) -> int:
        return self.cfac.shape[2]

    @property
    def size(self) -> int:
        return int(jax.device_get(self.list_sizes).sum())


def shard_rel_err_from_arrays(errw, rnorm, indices, dim_ext: int,
                              perm, r: int) -> tuple:
    """Measured per-shard relative estimator error of a dealt layout:
    shard s owns lists ``perm[s·L:(s+1)·L]``, and its error statistic
    is the same ``rel_err`` knob :func:`raft_tpu.neighbors.ivf_bq
    .estimator_stats` measures index-wide — THE one implementation
    (``_OVERFETCH_KAPPA`` was calibrated against this exact
    statistic); build time and checkpoint restore both call it over
    host arrays in the pre-deal (global list id) order."""
    perm = np.asarray(perm)
    valid = np.asarray(indices) >= 0
    errw = np.asarray(errw)
    rn2 = np.square(np.asarray(rnorm))
    n_local = len(perm) // r
    out = []
    for s in range(r):
        lists = perm[s * n_local : (s + 1) * n_local]
        v = valid[lists]
        cnt = max(int(v.sum()), 1)
        mean_e = float(errw[lists][v].sum()) / cnt
        mean_rn2 = float(rn2[lists][v].sum()) / cnt
        rel = (2.0 * mean_e / (math.sqrt(dim_ext)
                               * math.sqrt(max(mean_rn2, 1e-20)))
               if mean_rn2 > 0 else 0.0)
        out.append(rel)
    return tuple(out)


def _shard_rel_err(index, perm: np.ndarray, r: int) -> tuple:
    """Build-time wrapper: ONE small device fetch of the single-chip
    planes, then the shared per-shard reduction."""
    return shard_rel_err_from_arrays(
        jax.device_get(index.errw), jax.device_get(index.rnorm),
        jax.device_get(index.indices), index.dim_ext, perm, r)


def merge_overfetch(index: DistributedIvfBq, k: int, *,
                    confidence: float = 1.0) -> int:
    """Variance-corrected merge budget: how deep to fetch through the
    sharded merge so the true top-k survives the exact re-rank at the
    stated confidence — the bound-derived replacement for the flat 2×
    caller-side over-fetch.

    An index carrying the rerank plane exchanges **exact** distances —
    the merge is lossless (the global top-k restricted to a shard lies
    inside that shard's top-k), so the budget is ``k`` outright.
    Estimate-only indexes over-fetch by the worst *measured* per-shard
    relative estimator error (the same bound-derived budget as the
    single-chip :func:`raft_tpu.neighbors.ivf_bq.overfetch_budget`,
    per shard — searched at this depth and refined host-side)."""
    expect(k >= 1, "k must be >= 1")
    if index.data is not None:
        return k
    worst = max(index.shard_rel_err) if index.shard_rel_err else 1.0
    return int(math.ceil(
        k * (1.0 + confidence * _OVERFETCH_KAPPA * worst)))


def build_bq(
    res: Optional[Resources],
    comms: Comms,
    params: IvfBqIndexParams,
    dataset,
) -> DistributedIvfBq:
    """Single-chip build, then deal + shard (the shared layout policy).
    ``params.n_lists`` is rounded up to a multiple of the mesh axis."""
    res = ensure_resources(res)
    r = comms.size
    n_lists = -(-params.n_lists // r) * r
    params = dataclasses.replace(params, n_lists=n_lists)

    with tracing.range("raft_tpu.distributed.ivf_bq.build"):
        index = ivf_bq_mod.build(res, params, dataset)
        sizes = np.asarray(jax.device_get(index.list_sizes))
        perm = deal_order(sizes, r)
        rel = _shard_rel_err(index, perm, r)
        # graftledger gate for the mesh deal (opt-in): per-shard slot
        # model of every dealt plane, incl. the optional rerank plane
        admit_deal(
            (index.centers, index.codes, index.rnorm, index.cfac,
             index.errw, index.indices, index.list_sizes, index.data,
             index.data_norms), r, "distributed.ivf_bq.build.deal")

        def place(a):
            # streamed per-shard deal — no fully-permuted build-device copy
            return place_dealt(a, perm, comms)

        return DistributedIvfBq(
            comms=comms,
            centers=place(index.centers),
            rotation=jax.device_put(index.rotation, comms.replicated()),
            codes=place(index.codes),
            rnorm=place(index.rnorm),
            cfac=place(index.cfac),
            errw=place(index.errw),
            indices=place(index.indices),
            list_sizes=place(index.list_sizes),
            metric=index.metric,
            shard_rel_err=rel,
            data=place(index.data) if index.data is not None else None,
            data_norms=(place(index.data_norms)
                        if index.data_norms is not None else None),
        )


def _dist_search_bq_fn(queries, centers, rotation, codes, rnorm, cfac,
                       errw, indices, data, data_norms,
                       probe_counts=None, n_valid=None,
                       row_probes=None, *,
                       axis: str, mesh, n_probes: int, k: int,
                       metric: DistanceType,
                       probe_mode: str, query_axis=None,
                       coarse_algo: str = "exact",
                       scan_engine: str = "rank",
                       epsilon: float = 3.0,
                       wire_dtype: str = "f32",
                       probe_wire_dtype: str = "f32"):
    """Distributed BQ probe scan: lean probe selection + shard-local
    scan (fused estimate-then-rerank engines or the legacy rank-major
    estimate scan) + O(q · merge_k) result merge. ``merge_k`` is the
    variance-corrected per-shard contribution (:func:`merge_overfetch`
    — ``wire_dtype`` compresses the gathered distances on the wire).
    ``probe_counts`` optionally provides the donated
    list-sharded (n_lists,) int32 probe-frequency plane (graftgauge —
    owned probes only, returned as a third output) and the optional
    ragged ``row_probes`` budget plane (see
    :func:`raft_tpu.distributed.ivf._dist_search_fn`). ``scan_engine``
    must arrive resolved (:func:`raft_tpu.ops.bq_scan
    .resolve_bq_engine`) — it is a jit static."""
    select_min = is_min_close(metric)
    pad_val = jnp.inf if select_min else -jnp.inf
    ip_metric = metric == DistanceType.InnerProduct
    ragged = row_probes is not None
    with_data = data is not None

    def body(centers_l, codes_l, rn_l, cf_l, ew_l, ids_l, *rest):
        rest = list(rest)
        if with_data:
            data_l, dn_l = rest[0], rest[1]
            rest = rest[2:]
        else:
            data_l, dn_l = None, None
        qs = rest.pop(0)
        rp = rest.pop(0) if ragged else None
        cnt, nv = rest if rest else (None, None)
        q = qs.shape[0]
        qf = qs.astype(jnp.float32)
        n_local = centers_l.shape[0]

        # graftflight phase markers (see ivf._dist_search_fn): pure
        # HLO op-path metadata for measured per-phase attribution
        with jax.named_scope("coarse_select"):
            ip = jax.lax.dot_general(
                qf, centers_l, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )
            if ip_metric:
                coarse = -ip
                cn = None
                qnorm = None
            else:
                cn = jnp.sum(jnp.square(centers_l), axis=1)
                coarse = cn[None, :] - 2.0 * ip
                qnorm = jnp.sum(jnp.square(qf), axis=1)

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

        qrot = qf @ rotation.T
        centers_rot = centers_l @ rotation.T

        if scan_engine != "rank":
            # fused estimate-then-rerank on the shard's own lists:
            # not-owned probes mask to the sentinel id n_local — the
            # engines' shared membership predicate rejects them, the
            # exact machinery the flat/PQ shard bodies already use
            from raft_tpu.ops.bq_scan import bq_list_major_scan

            masked = jnp.where(mine, local, n_local)
            with jax.named_scope("scan"):
                best_d, best_i = bq_list_major_scan(
                    qf, qrot, centers_rot, codes_l, rn_l, cf_l, ew_l,
                    ids_l, data_l, dn_l, masked, None,
                    k=k, metric=metric, epsilon=epsilon,
                    engine=scan_engine,
                    interpret=jax.default_backend() != "tpu")
        else:
            def step(carry, rank_i):
                best_d, best_i = carry
                dist, row_ids = score_probe(
                    local[:, rank_i], qrot,
                    None if ip_metric else centers_rot, ip, cn, qnorm,
                    codes_l, rn_l, cf_l, ids_l, ip_metric, pad_val,
                    valid=mine[:, rank_i])
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
    args = [centers, codes, rnorm, cfac, errw, indices]
    in_specs = [P(axis, None), P(axis, None, None), P(axis, None),
                P(axis, None, None), P(axis, None), P(axis, None)]
    if with_data:
        args += [data, data_norms]
        in_specs += [P(axis, None, None), P(axis, None)]
    args += [queries]
    in_specs += [qspec]
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


_dist_search_bq = partial(jax.jit, static_argnames=(
    "axis", "mesh", "n_probes", "k", "metric", "probe_mode",
    "query_axis", "coarse_algo", "scan_engine", "epsilon", "wire_dtype",
    "probe_wire_dtype"))(_dist_search_bq_fn)


def _dist_search_ragged_bq_fn(queries, row_probes, centers, rotation,
                              codes, rnorm, cfac, errw, indices, data,
                              data_norms, probe_counts=None,
                              n_valid=None, *,
                              axis: str, mesh, n_probes: int, k: int,
                              metric: DistanceType, probe_mode: str,
                              scan_engine: str = "xla",
                              epsilon: float = 3.0,
                              wire_dtype: str = "f32",
                              probe_wire_dtype: str = "f32"):
    """Packed ragged-batch mesh BQ search — see
    :func:`raft_tpu.distributed.ivf._dist_search_ragged_fn` for the
    replicated-tile contract. The fused estimate-then-rerank engines
    carry exact distances, so the lean merge stays lossless at the
    class-cap ``k`` and per-request ``k`` is the usual column slice.
    Fused engines only (a codes-only index resolves to the rank
    estimate scan and stays bucketed)."""
    expect(scan_engine in ("pallas", "xla"),
           "mesh ragged BQ serving needs a fused membership-masked "
           f"engine (pallas|xla), got {scan_engine!r}")
    return _dist_search_bq_fn(
        queries, centers, rotation, codes, rnorm, cfac, errw, indices,
        data, data_norms, probe_counts, n_valid,
        row_probes=row_probes, axis=axis, mesh=mesh, n_probes=n_probes,
        k=k, metric=metric, probe_mode=probe_mode, coarse_algo="exact",
        scan_engine=scan_engine, epsilon=epsilon, wire_dtype=wire_dtype,
        probe_wire_dtype=probe_wire_dtype)


def search_bq(
    res: Optional[Resources],
    params: IvfBqSearchParams,
    index: DistributedIvfBq,
    queries,
    k: int,
    probe_mode: str = "global",
    query_axis: Optional[str] = None,
    query_tile: int = 4096,
    wire_dtype: str = "f32",
    probe_wire_dtype: str = "f32",
    trace_id: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """One-program distributed BQ search at depth ``k``. With the
    fused engines (the default on an index carrying the rerank plane)
    the returned distances are **exact** and the merge is lossless —
    ask for the ``k`` you want. A codes-only index returns
    estimate-ranked candidates: pass ``k = merge_overfetch(index,
    want_k)`` (the variance-corrected merge budget derived from the
    measured per-shard estimator error) and re-rank host-side with
    :func:`raft_tpu.neighbors.refine`. Large query sets
    run in ``query_tile`` batches, bounding the per-shard
    intermediates like the single-chip path. ``query_axis`` names a
    second mesh axis to shard queries over; ``wire_dtype="bf16"``
    compresses the merge collective's distances; ``probe_wire_dtype``
    (``f32|bf16|int8``) compresses the probe-candidate exchange;
    ``trace_id`` opts into graftscope-v2 mesh span recording."""
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
    from raft_tpu.distributed.ivf import resolve_auto_wires

    wire_dtype, probe_wire_dtype = resolve_auto_wires(
        queries.shape[0], k, n_probes, index.n_lists, comms.size,
        wire_dtype, probe_mode, probe_wire_dtype)
    resolve_wire_dtype(wire_dtype)
    resolve_probe_wire_dtype(probe_wire_dtype)
    from raft_tpu.ops.bq_scan import resolve_bq_engine

    scan_engine = resolve_bq_engine(
        params.scan_engine, data=index.data, filter_words=None,
        k=k, dim_ext=index.dim_ext, bits=index.bits,
        n_probes=n_probes)
    queries = jax.device_put(queries, qsharding)
    with tracing.range("raft_tpu.distributed.ivf_bq.search"):
        def run(qt, _fw):
            return _dist_search_bq(
                qt, index.centers, index.rotation, index.codes,
                index.rnorm, index.cfac, index.errw, index.indices,
                index.data, index.data_norms,
                axis=comms.axis, mesh=comms.mesh, n_probes=n_probes,
                k=k, metric=index.metric,
                probe_mode=probe_mode, query_axis=query_axis,
                coarse_algo=params.coarse_algo, scan_engine=scan_engine,
                epsilon=params.epsilon, wire_dtype=wire_dtype,
                probe_wire_dtype=probe_wire_dtype,
            )

        # lazy: only a traced dispatch (trace_id=) builds the model
        model = lambda: collective_payload_model(  # noqa: E731
            queries.shape[0], k, n_probes, index.n_lists,
            comms.size, wire_dtype, probe_mode, probe_wire_dtype)
        if query_axis is not None:
            # already query-sharded: tiling would slice across the
            # shard layout and force a reshard per tile — run whole
            # (the 2-D grid is itself the large-batch mechanism)
            return record_dispatch("dist_ivf_bq", model, trace_id,
                                   lambda: run(queries, None),
                                   axis=comms.axis)
        return record_dispatch(
            "dist_ivf_bq", model, trace_id,
            lambda: tile_queries(run, queries, None, query_tile),
            axis=comms.axis)
