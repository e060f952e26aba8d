"""CAGRA — graph-based ANN, TPU-native re-design of
``raft::neighbors::cagra`` (``cagra_types.hpp:131`` index, params
``:54-111``; build ``detail/cagra/cagra_build.cuh:44-123``; optimize
``detail/cagra/graph_core.cuh:320``; search ``detail/cagra/cagra_search.cuh:105``).

Reference architecture: k-NN graph from batched IVF-PQ searches (+refine)
or NN-descent; graph *optimize* = 2-hop detour counting (``kern_prune``,
``graph_core.cuh:128``) + reverse-edge augmentation (``kern_make_rev_graph
:191``); search = persistent CUDA kernels walking the graph with a
random-hash visited table, per-CTA bitonic top-M and three kernel
families (single-cta / multi-cta / multi-kernel).

TPU re-design:

- **build**: same two graph sources (IVF-PQ batches + refine, or the
  dense NN-descent in :mod:`raft_tpu.neighbors.nn_descent`).
- **optimize**: detour counting is a *dense batched tensor op* — for a
  node tile, gather the neighbor-of-neighbor id cube (t, K, K) and count
  rank-lower 2-hop matches with one broadcast compare; no atomics. The
  reverse graph uses sort-and-rank packing.
- **search**: one jitted ``lax.while_loop`` per query batch ("beam
  search" formulation): an itopk buffer (ids, dists, explored flags) is
  expanded ``search_width`` parents at a time; candidate scoring is a
  batched gather + MXU contraction over all queries at once. Instead of
  the GPU's visited hashmap, merging deduplicates ids with
  buffer-copy-priority, which both dedups and preserves explored flags —
  re-proposed candidates can never re-enter unexplored, so termination
  ("all buffer entries explored") is exact. Queries are tiled host-side;
  every shape is static.
- **seeding**: every beam starts from a build-time IVF-coarse *seed
  plane* (balanced k-means centers + a padded member table, serialized
  with the index): a query probes its nearest centroids and the beam
  opens from the best member rows — a pure function of query CONTENT,
  never of batch position, so blocks concatenate and CAGRA serves
  through the executor's batched + ragged plans like every other
  family. Indexes without the plane (``from_graph``, hnswlib loads)
  fall back to the query-aware strided pool, which is content-pure too.
- **BQ-coded traversal** (opt-in ``bq_bits`` at build): gathered graph
  neighbors are first scored by the RaBitQ XOR+popcount estimate
  against a packed per-row code plane and only estimate-survivors are
  exactly reranked — ``ops/bq_scan``'s estimate-then-rerank discipline
  on the beam's neighbor-gather path, in BOTH engines (the Pallas
  kernel skips the raw-row DMA for survivor-free batches).
"""

from __future__ import annotations

import dataclasses
import enum
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.core import tracing
from raft_tpu.core.logger import warn as _log_warn
from raft_tpu.core.resources import Resources, ensure_resources
from raft_tpu.core.serialize import (
    check_version,
    deserialize_array,
    deserialize_scalar,
    open_maybe_path,
    serialize_array,
    serialize_scalar,
)
from raft_tpu.core.validation import expect
from raft_tpu.distance.types import DistanceType
from raft_tpu.neighbors import ivf_pq as ivf_pq_mod
from raft_tpu.neighbors import nn_descent as nn_descent_mod
from raft_tpu.neighbors._exact import dedup_candidate_mask, gathered_distances
from raft_tpu.neighbors.filters import resolve_filter_words, test_filter
from raft_tpu.neighbors.nn_descent import _reverse_sample
from raft_tpu.neighbors.refine import refine

_SERIALIZATION_VERSION = 5


class BuildAlgo(enum.Enum):
    """Mirrors ``cagra::graph_build_algo`` (``cagra_types.hpp``), plus
    the TPU-first CLUSTER_JOIN builder (merged within-cluster brute
    force — see :mod:`raft_tpu.neighbors.cluster_join`)."""

    IVF_PQ = "ivf_pq"
    NN_DESCENT = "nn_descent"
    CLUSTER_JOIN = "cluster_join"


@dataclasses.dataclass(frozen=True)
class CagraIndexParams:
    """Mirrors ``cagra::index_params`` (``cagra_types.hpp:54-111``)."""

    metric: DistanceType = DistanceType.L2Expanded
    intermediate_graph_degree: int = 128
    graph_degree: int = 64
    build_algo: BuildAlgo = BuildAlgo.IVF_PQ
    nn_descent_niter: int = 20
    # IVF-PQ graph-build knobs (reference auto-derives; exposed here)
    ivf_pq_n_lists: int = 0       # 0 → auto sqrt(n)
    ivf_pq_n_probes: int = 0      # 0 → auto
    refine_rate: float = 2.0      # gpu_top_k = degree * refine_rate
    # dataset storage dtype for the built index: bf16 halves both the
    # per-iteration gather bytes (XLA engine) and the VMEM residency
    # (Pallas engine: 500k×128 bf16 fits where f32 does not); build
    # math stays f32. Same contract as brute_force.build's
    # storage_dtype: None keeps the input dtype; accepts a dtype or
    # its name (JSON configs pass "bfloat16").
    storage_dtype: Optional[Any] = None
    # coarse seed plane: number of balanced-k-means lists trained at
    # build time for IVF-coarse beam seeding. 0 → auto (≈ sqrt(n),
    # capped at 1024). The plane is always built — it is the batching-
    # invariant seed source — and serializes with the index.
    seed_n_lists: int = 0
    # BQ-coded traversal plane: RaBitQ code bits per dimension level
    # (1..4) packed into the per-row record plane the beam's
    # estimate-then-rerank phase scores against. 0 (default) skips the
    # plane; traversal then always reranks exactly.
    bq_bits: int = 0


@dataclasses.dataclass(frozen=True)
class CagraSearchParams:
    """Mirrors ``cagra::search_params`` (``cagra_types.hpp``): ``itopk_size``
    is the retained candidate buffer, ``search_width`` the number of
    parents expanded per iteration, ``max_iterations`` 0 → auto."""

    itopk_size: int = 64
    search_width: int = 1
    max_iterations: int = 0
    num_random_samplings: int = 1
    query_tile: int = 256
    # Rows scored per query before the beam opens: in "coarse" mode the
    # member rows of ~ceil(seed_pool / list_cap) probed lists, in
    # "pool" mode a strided dataset sample of this width. 0 → auto
    # (max(256, 4·n_seeds)). The coarse plane reaches the pool-mode
    # entry quality at ~8× smaller pools — the probed lists are the
    # query's own neighborhoods, not a blind stride.
    seed_pool: int = 0
    # "coarse": IVF-coarse seeding from the build-time seed plane
    # (requires it); "pool": the query-aware strided pool; "auto":
    # coarse when the index carries the plane, else pool. Every mode is
    # a pure function of query content — batching-invariant.
    seed_mode: str = "auto"
    # "on": estimate-then-rerank neighbor scoring against the build-time
    # BQ record plane (requires bq_bits ≥ 1 at build); "off": always
    # rerank exactly; "auto": on when the plane exists (and, on the
    # kernel path, fits the VMEM budget).
    bq_traversal: str = "auto"
    # RaBitQ margin multiplier for the traversal prune — same role as
    # IvfBqSearchParams.epsilon (3σ of the estimator error model).
    bq_epsilon: float = 3.0
    # "pallas": the one-dispatch VMEM-resident beam-search kernel
    # (ops/beam_search, role of the reference's persistent single-CTA
    # kernel); "xla": the lax.while_loop path; "auto": pallas on TPU
    # when its constraints hold (supported metric, no filter,
    # dim % 128 == 0, dataset fits the VMEM budget), else xla.
    algo: str = "auto"


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class CagraIndex:
    """Dataset + fixed-degree neighbor graph (``cagra::index``,
    ``cagra_types.hpp:131``; the dataset is stored padded/strided in the
    reference — on TPU a plain dense (n, d) array)."""

    dataset: jax.Array      # (n, d)
    graph: jax.Array        # (n, graph_degree) int32
    metric: DistanceType
    # IVF-coarse seed plane (built by :func:`build`, None on directly
    # assembled indexes): balanced-k-means centers + the -1-padded
    # member table mapping each list to its dataset rows
    seed_centers: Optional[jax.Array] = None    # (n_lists, d) f32
    seed_members: Optional[jax.Array] = None    # (n_lists, cap) int32
    # BQ traversal plane (built when CagraIndexParams.bq_bits ≥ 1):
    # the pinned rotation, the rotated global center row, and the
    # packed per-row record plane of ops/bq_scan.pack_bq_records
    bq_rotation: Optional[jax.Array] = None     # (dim_ext, d) f32
    bq_center_rot: Optional[jax.Array] = None   # (1, dim_ext) f32
    bq_records: Optional[jax.Array] = None      # (T, PW) int32
    bq_bits: int = 0

    def tree_flatten(self):
        return ((self.dataset, self.graph, self.seed_centers,
                 self.seed_members, self.bq_rotation, self.bq_center_rot,
                 self.bq_records),
                (self.metric, self.bq_bits))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux[0], *children[2:],
                   bq_bits=aux[1])

    @property
    def size(self) -> int:
        return self.dataset.shape[0]

    @property
    def dim(self) -> int:
        return self.dataset.shape[1]

    @property
    def graph_degree(self) -> int:
        return self.graph.shape[1]

    @property
    def padded_graph(self) -> jax.Array:
        """Adjacency rows padded to the Pallas kernel's 128-lane DMA
        unit, computed lazily and cached on the index so repeated
        ``search()`` calls don't re-copy the graph."""
        cached = self.__dict__.get("_padded_graph")
        if cached is None:
            from raft_tpu.ops.beam_search import pad_graph

            cached = pad_graph(self.graph)
            object.__setattr__(self, "_padded_graph", cached)
        return cached


# ---------------------------------------------------------------------------
# build
# ---------------------------------------------------------------------------


def build_knn_graph(
    res: Optional[Resources],
    dataset,
    k: int,
    metric: DistanceType = DistanceType.L2Expanded,
    n_lists: int = 0,
    n_probes: int = 0,
    refine_rate: float = 2.0,
    batch: int = 1024,
) -> jax.Array:
    """Intermediate k-NN graph via batched IVF-PQ self-search + refine —
    ``detail/cagra/cagra_build.cuh:44-123`` (1024-query batches at
    ``:105``). Self-matches are dropped; returns (n, k) int32."""
    res = ensure_resources(res)
    dataset = jnp.asarray(dataset)
    n, dim = dataset.shape
    n_lists = n_lists or max(8, min(n // 39 + 1, int(np.sqrt(n) * 2)))
    n_probes = n_probes or max(8, n_lists // 10)
    gpu_k = max(k + 1, int((k + 1) * refine_rate))

    # 4-bit codes at doubled pq_dim: equal code bytes and measured-equal
    # graph recall vs the 8-bit default, but the scoring rides the
    # masked-sum select path (~6x faster on TPU) — and refine re-ranks
    # with exact distances anyway
    params = ivf_pq_mod.IvfPqIndexParams(
        metric=metric, n_lists=n_lists,
        pq_bits=4,
        pq_dim=min(dim, 2 * ivf_pq_mod._auto_pq_dim(dim)),
        kmeans_trainset_fraction=min(1.0, 10240 / max(n, 1) + 0.1),
    )
    index = ivf_pq_mod.build(res, params, dataset)
    sp = ivf_pq_mod.IvfPqSearchParams(n_probes=n_probes)

    out = []
    for start in range(0, n, batch):
        q = dataset[start : start + batch]
        _, cand = ivf_pq_mod.search(res, sp, index, q, gpu_k)
        _, idx = refine(res, dataset, q, cand, k + 1, metric)
        # drop self-hits: mask rows equal to the query's own id
        own = jnp.arange(start, start + q.shape[0], dtype=jnp.int32)[:, None]
        keep = idx != own
        # stable-compact each row to k entries (self-hit, if found, removed)
        pos = jnp.where(keep, jnp.cumsum(keep, axis=1) - 1, k + 1)
        row = jnp.full((q.shape[0], k + 2), -1, jnp.int32)
        row = row.at[jnp.arange(q.shape[0])[:, None], pos].set(idx, mode="drop")
        out.append(row[:, :k])
    return jnp.concatenate(out, axis=0)


@partial(jax.jit, static_argnames=("tile", "method"))
def _detour_counts(graph, tile: int, method: str = "auto"):
    """2-hop detour count per edge (role of ``kern_prune``,
    ``graph_core.cuh:128``): edge (i → g[i,r]) is detourable through the
    higher-ranked neighbor g[i,l] (l < r) when g[i,r] ∈ graph[g[i,l]].

    Two membership tests, picked per backend (the reference amortizes
    the same lookup with shared-memory hashing):

    - ``compare``: O(k³)-per-node broadcast equality — pure VPU
      compares, no gathers/sorts; the right trade on TPU where lane
      gathers serialize onto the scalar core.
    - ``search``: sort each neighbor row once + binary-search all edges
      into it — O(k² log k) per node; wins on CPU/GPU where gathers
      are cheap.
    """
    if method == "auto":
        method = "compare" if jax.default_backend() == "tpu" else "search"
    n, k = graph.shape
    pad = (-n) % tile
    node_ids = jnp.arange(n + pad, dtype=jnp.int32) % n
    sentinel = jnp.iinfo(jnp.int32).max
    rank = jnp.arange(k, dtype=jnp.int32)

    def step(_, t):
        nid = jax.lax.dynamic_slice_in_dim(node_ids, t * tile, tile)
        g = jnp.take(graph, nid, axis=0)                       # (t, k)
        nbrs = jnp.take(graph, jnp.clip(g, 0), axis=0)         # (t, k, k)
        # rows of invalid parents (or invalid entries) can match nothing
        nbrs = jnp.where((g >= 0)[:, :, None] & (nbrs >= 0), nbrs,
                         sentinel)
        if method == "search":
            snbrs = jnp.sort(nbrs, axis=2)
            pos = jax.vmap(jax.vmap(jnp.searchsorted, (0, None)))(snbrs, g)
            hit = jnp.take_along_axis(
                snbrs, jnp.clip(pos, 0, k - 1), axis=2
            ) == g[:, None, :]                                 # (t, l, r)
            ok = ((rank[None, :, None] < rank[None, None, :])
                  & (g >= 0)[:, None, :])
            return None, jnp.sum((hit & ok).astype(jnp.int32), axis=1)

        # "compare": accumulate over l so the intermediate stays
        # (t, k, k) instead of a (t, k, k, k) broadcast cube
        def count_l(l, counts):
            eq = nbrs[:, l, :, None] == g[:, None, :]          # (t, m, r)
            match = jnp.any(eq, axis=1) & (g >= 0)             # (t, r)
            return counts + (match & (rank > l)[None, :]).astype(jnp.int32)

        counts = jax.lax.fori_loop(
            0, k, count_l, jnp.zeros((tile, k), jnp.int32)
        )
        return None, counts

    n_tiles = (n + pad) // tile
    _, out = jax.lax.scan(step, None, jnp.arange(n_tiles))
    return out.reshape(-1, k)[:n]


@partial(jax.jit, static_argnames=("fwd_keep",))
def _select_forward(graph, detours, fwd_keep: int):
    """The fwd_keep lowest-detour edges per node, rank-order preserved
    (ties broken toward closer neighbors)."""
    k = graph.shape[1]
    rank = jnp.arange(k, dtype=jnp.int32)[None, :]
    score = jnp.where(graph >= 0, detours * k + rank, jnp.iinfo(jnp.int32).max)
    _, pos = jax.lax.top_k(-score, fwd_keep)
    return jnp.take_along_axis(graph, jnp.sort(pos, axis=1), axis=1)


@partial(jax.jit, static_argnames=("out_degree",))
def _merge_forward_reverse(graph, fwd, rev, out_degree: int):
    """Merge the kept forward edges with reverse edges and leftover
    forward edges, dedup'd by priority (role of ``graph_core.cuh``
    ``optimize:320`` + ``kern_make_rev_graph:191``)."""
    n, k = graph.shape

    # candidates in priority order: kept-forward, reverse, remaining-forward
    cand = jnp.concatenate([fwd, rev, graph], axis=1)
    c = cand.shape[1]
    prio = jnp.arange(c, dtype=jnp.int32)[None, :]
    prio = jnp.where(cand >= 0, prio, c)
    order = jnp.argsort(cand, axis=1, stable=True)      # groups equal ids
    sid = jnp.take_along_axis(cand, order, axis=1)
    sprio = jnp.take_along_axis(prio, order, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros((n, 1), bool), sid[:, 1:] == sid[:, :-1]], axis=1
    )
    sprio = jnp.where(dup | (sid < 0), c, sprio)
    _, best = jax.lax.top_k(-sprio, out_degree)
    keep_ids = jnp.take_along_axis(sid, best, axis=1)
    keep_prio = jnp.take_along_axis(sprio, best, axis=1)
    # order final rows by priority so closest-first ordering survives
    reorder = jnp.argsort(keep_prio, axis=1, stable=True)
    out = jnp.take_along_axis(keep_ids, reorder, axis=1)
    return jnp.where(jnp.take_along_axis(keep_prio, reorder, axis=1) < c,
                     out, -1)


def optimize(
    res: Optional[Resources],
    knn_graph,
    out_degree: int,
    tile: int = 128,
) -> jax.Array:
    """Prune an intermediate k-NN graph to a fixed-degree search graph —
    ``cagra::optimize`` (``graph_core.cuh:320``)."""
    ensure_resources(res)
    knn_graph = jnp.asarray(knn_graph, jnp.int32)
    n, k = knn_graph.shape
    expect(out_degree <= k, "out_degree must be <= input graph degree")
    with tracing.range("raft_tpu.cagra.optimize"):
        detours = _detour_counts(knn_graph, tile)
        fwd = _select_forward(knn_graph, detours, out_degree // 2)
        rev = _reverse_sample(fwd, n, out_degree - out_degree // 2)
        return _merge_forward_reverse(knn_graph, fwd, rev, out_degree)


def _auto_seed_lists(n: int) -> int:
    """Default coarse-plane list count: ≈ sqrt(n) puts ~sqrt(n) rows in
    each list, so one probed list already carries a beam's worth of
    entry candidates; 1024 caps the center-scoring GEMM."""
    return max(1, min(1024, int(round(np.sqrt(max(n, 1))))))


def _build_seed_plane(res, dataset, metric: DistanceType, n_lists: int):
    """Train the IVF-coarse seed plane: balanced-k-means centers plus a
    dense -1-padded member table (list → dataset rows). Always built by
    :func:`build` — it is the batching-invariant seed source the
    serving path's block-concatenation rests on."""
    from raft_tpu.cluster import kmeans_balanced

    x = jnp.asarray(dataset).astype(jnp.float32)
    n = x.shape[0]
    n_lists = min(n_lists or _auto_seed_lists(n), n)
    km = kmeans_balanced.KMeansBalancedParams(
        metric=DistanceType(metric), seed=res.seed)
    centers, labels, sizes = kmeans_balanced.build_clusters(
        res, km, x, n_lists)
    labels_np = np.asarray(labels)
    cap = max(1, int(np.asarray(sizes).max()))
    members = np.full((n_lists, cap), -1, np.int32)
    order = np.argsort(labels_np, kind="stable")
    sl = labels_np[order]
    ranks = np.arange(n) - np.searchsorted(sl, sl)
    members[sl, ranks] = order
    # drop empty lists (degenerate data collapses k-means): a probed
    # empty list would contribute zero valid seeds, and a query whose
    # every probe lands empty would open the beam with no entries
    keep = np.flatnonzero(np.asarray(sizes) > 0)
    if keep.size < n_lists:
        centers = jnp.asarray(np.asarray(centers)[keep])
        members = members[keep]
    return centers.astype(jnp.float32), jnp.asarray(members)


def _build_bq_plane(dataset, bits: int, seed: int):
    """Encode the dataset into the packed BQ traversal plane: the
    ivf_bq pinned rotation + per-row RaBitQ codes about the GLOBAL
    dataset mean (one center, so the beam estimator needs no per-list
    bookkeeping), packed per-row by
    :func:`raft_tpu.ops.bq_scan.pack_bq_records`."""
    from raft_tpu.neighbors.ivf_bq import _encode, _pinned_rotation
    from raft_tpu.ops.bq_scan import pack_bq_records

    x = jnp.asarray(dataset).astype(jnp.float32)
    d = x.shape[1]
    dim_ext = -(-d // 32) * 32
    rotation = _pinned_rotation(seed, dim_ext, d)
    center = jnp.mean(x, axis=0, keepdims=True)
    center_rot = jnp.einsum("od,ed->oe", center, rotation,
                            precision=jax.lax.Precision.HIGHEST)
    rot = jnp.einsum("nd,ed->ne", x - center, rotation,
                     precision=jax.lax.Precision.HIGHEST)
    codes, rnorm, cfac, errw = _encode(rot, bits)
    return rotation, center_rot, pack_bq_records(codes, rnorm, cfac, errw)


def build(
    res: Optional[Resources],
    params: CagraIndexParams,
    dataset,
) -> CagraIndex:
    """knn-graph + optimize — ``cagra::build`` (``cagra.cuh:296-331``).

    Examples
    --------
    >>> import numpy as np
    >>> from raft_tpu.neighbors import cagra
    >>> x = np.random.default_rng(0).standard_normal(
    ...     (128, 16)).astype(np.float32)
    >>> idx = cagra.build(None, cagra.CagraIndexParams(
    ...     graph_degree=8, intermediate_graph_degree=16,
    ...     build_algo=cagra.BuildAlgo.NN_DESCENT), x)
    >>> _, i = cagra.search(None, cagra.CagraSearchParams(itopk_size=16),
    ...                     idx, x[:4], 1)
    >>> np.asarray(i).ravel().tolist()   # each point is its own NN
    [0, 1, 2, 3]
    """
    res = ensure_resources(res)
    dataset = jnp.asarray(dataset)
    expect(dataset.ndim == 2, "dataset must be (n, d)")
    expect(params.metric in (DistanceType.L2Expanded,
                             DistanceType.L2SqrtExpanded,
                             DistanceType.InnerProduct),
           f"cagra supports L2/InnerProduct, got {params.metric!r}")
    if params.storage_dtype is not None:   # fail fast, before the build
        expect(jnp.dtype(params.storage_dtype) in
               (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)),
               f"storage_dtype must be float32/bfloat16, got "
               f"{params.storage_dtype!r}")
        params = dataclasses.replace(
            params, storage_dtype=jnp.dtype(params.storage_dtype))
    n = dataset.shape[0]
    ideg = min(params.intermediate_graph_degree, n - 1)
    if ideg < params.intermediate_graph_degree:
        _log_warn(
            "Intermediate graph degree cannot be larger than dataset "
            "size, reducing it to %d", ideg)
    odeg = min(params.graph_degree, ideg)
    if odeg < params.graph_degree:
        _log_warn(
            "Graph degree (%d) cannot be larger than intermediate graph "
            "degree (%d), reducing graph_degree", params.graph_degree, ideg)

    with tracing.range("raft_tpu.cagra.build"):
        if params.build_algo == BuildAlgo.CLUSTER_JOIN:
            from raft_tpu.neighbors import cluster_join

            cj = cluster_join.ClusterJoinParams(
                graph_degree=ideg,
                metric=params.metric,
                seed=res.seed,
            )
            knn_graph = cluster_join.build(res, cj, dataset)
        elif params.build_algo == BuildAlgo.NN_DESCENT:
            nnd = nn_descent_mod.NNDescentParams(
                graph_degree=ideg,
                intermediate_graph_degree=min(int(ideg * 1.5), n - 1),
                max_iterations=params.nn_descent_niter,
                metric=params.metric,
                seed=res.seed,
            )
            knn_graph = nn_descent_mod.build(res, nnd, dataset)
        else:
            knn_graph = build_knn_graph(
                res, dataset, ideg, params.metric,
                params.ivf_pq_n_lists, params.ivf_pq_n_probes,
                params.refine_rate,
            )
        graph = optimize(res, knn_graph, odeg)
        seed_centers, seed_members = _build_seed_plane(
            res, dataset, params.metric, params.seed_n_lists)
        bq_rotation = bq_center_rot = bq_records = None
        if params.bq_bits:
            expect(1 <= params.bq_bits <= 4,
                   f"bq_bits must be 0 (off) or 1..4, got {params.bq_bits}")
            bq_rotation, bq_center_rot, bq_records = _build_bq_plane(
                dataset, params.bq_bits, res.seed)
        stored = dataset
        if params.storage_dtype is not None:
            stored = jnp.asarray(dataset).astype(params.storage_dtype)
        return CagraIndex(
            dataset=res.put(stored), graph=graph,
            metric=DistanceType(params.metric),
            seed_centers=res.put(seed_centers),
            seed_members=res.put(seed_members),
            bq_rotation=None if bq_rotation is None else res.put(bq_rotation),
            bq_center_rot=(None if bq_center_rot is None
                           else res.put(bq_center_rot)),
            bq_records=None if bq_records is None else res.put(bq_records),
            bq_bits=params.bq_bits)


def from_graph(res, dataset, graph,
               metric: DistanceType = DistanceType.L2Expanded) -> CagraIndex:
    """Assemble an index from a prebuilt graph (reference's index
    constructor taking dataset + knn_graph views)."""
    res = ensure_resources(res)
    return CagraIndex(res.put(jnp.asarray(dataset)),
                      res.put(jnp.asarray(graph, jnp.int32)),
                      DistanceType(metric))


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------


def _buffer_merge(ids, dists, explored, cand_ids, cand_d, L: int):
    """Merge candidates into the itopk buffer with id-dedup where the
    buffer copy wins — preserving explored flags (the hash-free visited
    mechanism; see module docstring).

    Dedup is a broadcast equality mask (candidate-vs-buffer (C, L) +
    candidate-vs-earlier-candidate (C, C)) feeding one ``top_k`` — no
    argsort in the search hot loop (TPU sorts have poor constants; the
    masks are cheap VPU compares)."""
    # buffer copy wins over duplicates; first proposal wins among
    # candidates (shared helper — the Pallas engine uses the same one)
    buf_ids = jnp.where(ids >= 0, ids, -2)               # -2 ≠ any cand -1
    dup = dedup_candidate_mask(cand_ids, buf_ids)
    cd = jnp.where(dup | (cand_ids < 0), jnp.inf, cand_d)

    all_d = jnp.concatenate([dists, cd], axis=1)
    all_i = jnp.concatenate([ids, cand_ids], axis=1)
    all_e = jnp.concatenate(
        [explored, jnp.zeros(cand_ids.shape, bool)], axis=1
    )
    neg, pos = jax.lax.top_k(-all_d, L)
    return (
        jnp.take_along_axis(all_i, pos, axis=1),
        -neg,
        jnp.take_along_axis(all_e, pos, axis=1),
    )


@partial(jax.jit, static_argnames=("pool", "n_seeds", "metric"))
def _pooled_seeds(dataset, queries, pool: int, n_seeds: int,
                  metric: DistanceType):
    """Best ``n_seeds`` of a strided ``pool``-row sample per query — a
    one-GEMM routing stage replacing uniform-random seeding."""
    n = dataset.shape[0]
    stride = -(-n // pool)  # ceil: the pool must span the whole id range
    cand = (jnp.arange(pool, dtype=jnp.int32) * stride) % n
    qf = queries.astype(jnp.float32)
    d = gathered_distances(
        qf, dataset, jnp.broadcast_to(cand, (qf.shape[0], pool)), metric)
    _, pos = jax.lax.top_k(-d, min(n_seeds, pool))
    return cand[pos]


@partial(jax.jit, static_argnames=("n_probes", "n_seeds", "metric"))
def _coarse_seeds(dataset, centers, members, queries, *, n_probes: int,
                  n_seeds: int, metric: DistanceType):
    """IVF-coarse seeding: each query probes its ``n_probes`` nearest
    seed-plane centers, gathers their member rows, and the beam opens
    from the ``n_seeds`` best of them. Strictly row-wise (one GEMM on
    the center plane + one gathered-distance tile), hence a pure
    function of query content — the batching-invariance contract."""
    qf = queries.astype(jnp.float32)
    ip = jnp.einsum("qd,cd->qc", qf, centers,
                    precision=jax.lax.Precision.HIGHEST)
    if metric == DistanceType.InnerProduct:
        cdist = -ip
    else:
        cdist = jnp.sum(jnp.square(centers), axis=1)[None, :] - 2.0 * ip
    _, probes = jax.lax.top_k(-cdist, n_probes)          # (q, n_probes)
    cand = jnp.take(members, probes, axis=0).reshape(qf.shape[0], -1)
    d = gathered_distances(qf, dataset, cand, metric)    # -1 pads → inf
    _, pos = jax.lax.top_k(-d, n_seeds)
    seeds = jnp.take_along_axis(cand, pos, axis=1)
    return jnp.where(
        jnp.isfinite(jnp.take_along_axis(d, pos, axis=1)), seeds, -1)


def derive_search_config(params: "CagraSearchParams",
                         index: "CagraIndex", k: int) -> dict:
    """THE beam-search shape derivation (L, w, max_iters, n_seeds),
    shared by :func:`search` and the serving path
    (``core/executor.py``) — their bit-identity depends on these
    values agreeing, so they are derived in exactly one place.

    One seed-count formula for both engines (their parity depends on
    drawing identical seed sets): the XLA width, rounded up to a
    multiple of the kernel's chunk width C = w*graph_degree. Duplicate
    draws are harmless — the merge dedups them."""
    L = max(params.itopk_size, k)
    w = max(1, params.search_width)
    C = w * index.graph_degree
    n_seeds = max(L, C) * max(1, params.num_random_samplings)
    n_seeds = -(-n_seeds // C) * C
    return {
        "k": k,
        "L": L,
        "w": w,
        "max_iters": params.max_iterations or (L // w + 24),
        "n_seeds": n_seeds,
    }


def _resolve_seed_mode(params: CagraSearchParams,
                       index: CagraIndex) -> str:
    """Resolve ``params.seed_mode`` against what the index carries."""
    mode = params.seed_mode
    expect(mode in ("auto", "coarse", "pool"),
           f"seed_mode must be 'auto'/'coarse'/'pool', got {mode!r}")
    if mode == "coarse":
        expect(index.seed_centers is not None,
               "seed_mode='coarse' needs the build-time seed plane "
               "(cagra.build); this index was assembled without one")
        return "coarse"
    if mode == "auto" and index.seed_centers is not None:
        return "coarse"
    return "pool"


def _make_seeds(dataset, seed_centers, seed_members, qt, n_seeds: int,
                metric: DistanceType, seed_mode: str, seed_pool: int):
    """Shared seed policy for the direct and serving search paths:
    IVF-coarse seeds from the build-time plane, or the query-aware
    strided pool for plane-less indexes. Both are pure functions of
    query content (row-wise) — blocks concatenate, pad rows cannot
    perturb real rows, and the ragged family can pack any split."""
    n = dataset.shape[0]
    pool = seed_pool if seed_pool > 0 else max(256, 4 * n_seeds)
    if seed_mode == "coarse":
        cap = seed_members.shape[1]
        n_probes = max(1, min(-(-pool // cap), seed_centers.shape[0]))
        seeds = _coarse_seeds(
            dataset, seed_centers, seed_members, qt, n_probes=n_probes,
            n_seeds=min(n_seeds, n_probes * cap), metric=metric)
    else:
        pool = min(pool, n)
        seeds = _pooled_seeds(dataset, qt, pool, min(n_seeds, pool),
                              metric)
    if seeds.shape[1] < n_seeds:
        # pad to the shared width by repeating the best seeds
        # (dedup makes repeats free)
        reps = -(-n_seeds // seeds.shape[1])
        seeds = jnp.tile(seeds, (1, reps))[:, :n_seeds]
    return seeds


def _rotate_queries(queries, rotation):
    """Rotate queries into the BQ estimator basis — ONE implementation
    for both engines and both call paths, so the estimate inputs (and
    hence the prune decisions) are bit-identical everywhere."""
    return jnp.einsum("qd,ed->qe", queries.astype(jnp.float32), rotation,
                      precision=jax.lax.Precision.HIGHEST)


def _resolve_bq_traversal(params: CagraSearchParams, index: CagraIndex,
                          use_kernel: bool) -> bool:
    """Resolve ``params.bq_traversal`` against the index plane and (on
    the kernel path) the VMEM budget the record plane must co-reside
    in."""
    mode = params.bq_traversal
    expect(mode in ("auto", "on", "off"),
           f"bq_traversal must be 'auto'/'on'/'off', got {mode!r}")
    if mode == "off":
        return False
    if index.bq_records is None:
        expect(mode != "on",
               "bq_traversal='on' needs an index built with bq_bits >= 1")
        return False
    if use_kernel:
        from raft_tpu.core.chips import vmem_budget_mb

        # same rule the kernel wrapper enforces: the plane is
        # VMEM-resident in both dataset modes and must leave the ~8 MB
        # scratch headroom (the dataset then places around it)
        fits = (4 * index.bq_records.size
                <= (vmem_budget_mb() - 8) * 1024 * 1024)
        if mode == "on":
            expect(fits, "bq_traversal='on': the BQ record plane "
                   "exceeds the kernel VMEM budget")
        return fits
    return True


def _search_batch_fn(dataset, graph, queries, seed_ids, filter_words,
                     row_iters=None, bq_records=None, bq_qrot=None,
                     bq_center_rot=None, *,
                     k: int, L: int, w: int, max_iters: int,
                     metric: DistanceType, bq_bits: int = 0,
                     bq_query_bits: int = 4, bq_epsilon: float = 3.0):
    """The XLA beam engine. ``row_iters`` (q,) optionally caps each
    row's live iterations (the ragged-serving budget — iterations past
    it are bit-exact no-ops for that row). ``bq_records``/``bq_qrot``/
    ``bq_center_rot`` enable the estimate-then-prune candidate gate —
    the same shared :func:`raft_tpu.ops.bq_scan._block_estimate` math
    as the Pallas kernel, so prune decisions (and hence results) are
    engine-parity-exact. This engine still gathers every candidate row
    (it is the portable correctness engine); only the kernel converts
    the prune into skipped DMA traffic."""
    q, dim = queries.shape
    n, deg = graph.shape
    qf = queries.astype(jnp.float32)
    ip_metric = metric == DistanceType.InnerProduct
    use_bq = bq_records is not None

    def score(cand):                                     # (q, c) ids → dists
        d = gathered_distances(qf, dataset, cand, metric)
        if filter_words is not None:
            # filtered-out samples never enter the itopk buffer, so they
            # are neither returned nor expanded (the reference's
            # search_with_filtering greenlight semantics)
            d = jnp.where(test_filter(filter_words, cand), d, jnp.inf)
        return d

    if use_bq:
        from raft_tpu.ops.bq_scan import _block_estimate, bq_record_geometry

        words = bq_bits * ((dim + 31) // 32)
        dim_ext = ((dim + 31) // 32) * 32
        _, rec_pad, _, _ = bq_record_geometry(words, bq_bits)
        rows2d = bq_records.reshape(-1, rec_pad)

        def bq_survivors(cand, dists):
            """(q, C) candidate ids → bool survivor mask: estimate
            minus margin still beats the row's running L-th exact
            distance. Record extraction mirrors the kernel's lane
            split bit-for-bit."""
            r = jnp.take(rows2d, jnp.maximum(cand, 0), axis=0)
            codes_wb = r[..., :words]                    # (q, C, words)
            scal = jax.lax.bitcast_convert_type(
                r[..., words:words + bq_bits + 2], jnp.float32)

            def one(qr, codes_q, sc):
                rn = sc[:, 0][None, :]                   # (1, C)
                cf = jnp.transpose(sc[:, 1:1 + bq_bits])  # (bits, C)
                ew = sc[:, 1 + bq_bits][None, :]
                return _block_estimate(
                    qr[None, :], bq_center_rot, rn, ew, cf, codes_q,
                    dim_ext=dim_ext, bits=bq_bits,
                    query_bits=bq_query_bits, epsilon=bq_epsilon,
                    ip_metric=ip_metric)
            est, margin = jax.vmap(one)(bq_qrot, codes_wb, scal)
            kth = dists[:, L - 1:L]
            return ((est[:, 0, :] - margin[:, 0, :]) < kth) & (cand >= 0)

    ids = jnp.full((q, L), -1, jnp.int32)
    dists = jnp.full((q, L), jnp.inf)
    explored = jnp.zeros((q, L), bool)
    if use_bq:
        # seed rounds merge in C-wide chunks with the evolving buffer's
        # L-th distance as the prune bar — the kernel's exact order
        C = w * deg
        for chunk in range(seed_ids.shape[1] // C):
            cand = seed_ids[:, chunk * C:(chunk + 1) * C]
            cd = jnp.where(bq_survivors(cand, dists), score(cand),
                           jnp.inf)
            ids, dists, explored = _buffer_merge(ids, dists, explored,
                                                 cand, cd, L)
    else:
        # seeding (role of the reference's random_samplings)
        ids, dists, explored = _buffer_merge(
            ids, dists, explored, seed_ids, score(seed_ids), L)

    def cond(state):
        ids, dists, explored, it = state
        frontier = (~explored) & jnp.isfinite(dists)
        if row_iters is not None:
            frontier = frontier & (it < row_iters)[:, None]
        return (it < max_iters) & jnp.any(frontier)

    def body(state):
        ids, dists, explored, it = state
        masked = jnp.where(explored | (ids < 0), jnp.inf, dists)
        _, ppos = jax.lax.top_k(-masked, w)              # (q, w) parents
        valid = jnp.isfinite(jnp.take_along_axis(masked, ppos, axis=1))
        if row_iters is not None:
            # a row past its budget contributes no parents and marks
            # nothing explored — the whole iteration is a no-op for it
            valid = valid & (it < row_iters)[:, None]
        parents = jnp.where(valid,
                            jnp.take_along_axis(ids, ppos, axis=1), -1)
        explored = explored.at[
            jnp.arange(q)[:, None], ppos
        ].set(explored[jnp.arange(q)[:, None], ppos] | valid)
        cand = jnp.take(graph, jnp.clip(parents, 0), axis=0)  # (q, w, deg)
        cand = jnp.where((parents >= 0)[:, :, None], cand, -1)
        cand = cand.reshape(q, w * deg)
        cand_d = score(cand)
        if use_bq:
            cand_d = jnp.where(bq_survivors(cand, dists), cand_d,
                               jnp.inf)
        ids, dists, explored = _buffer_merge(ids, dists, explored, cand,
                                             cand_d, L)
        return ids, dists, explored, it + 1

    ids, dists, explored, _ = jax.lax.while_loop(
        cond, body, (ids, dists, explored, jnp.zeros((), jnp.int32))
    )

    # entries never scored finite (e.g. everything a filter rejected)
    # report index -1, like the ivf search paths
    out_d = dists[:, :k]
    out_i = jnp.where(jnp.isfinite(out_d), ids[:, :k], -1)
    if ip_metric:
        out_d = -out_d
    elif metric == DistanceType.L2SqrtExpanded:
        out_d = jnp.where(jnp.isfinite(out_d),
                          jnp.sqrt(jnp.maximum(out_d, 0.0)), out_d)
    return out_d, out_i


_search_batch = partial(jax.jit, static_argnames=(
    "k", "L", "w", "max_iters", "metric", "bq_bits", "bq_query_bits",
    "bq_epsilon"))(_search_batch_fn)


def _serve_impl(queries, row_iters, dataset, graph, seed_centers,
                seed_members, bq_rotation, bq_center_rot, bq_records,
                filter_words, *, engine: str, k: int, L: int, w: int,
                max_iters: int, n_seeds: int, metric: DistanceType,
                seed_mode: str, seed_pool: int, bq_bits: int,
                bq_query_bits: int, bq_epsilon: float, deg: int,
                interpret: bool):
    """Seeds + beam + metric epilog for BOTH engines — what
    ``core/executor.py`` AOT-compiles per bucket (``_serving_fn``) and
    per ragged params class (``_search_ragged_fn``). Seeds are a pure
    function of query content, so blocks concatenate and results for
    real rows are bit-identical to the direct :func:`search` path.
    ``graph`` arrives pre-padded (``pad_graph``) on the kernel
    engine."""
    seeds = _make_seeds(dataset, seed_centers, seed_members, queries,
                        n_seeds, metric, seed_mode, seed_pool)
    use_bq = bq_records is not None
    qrot = _rotate_queries(queries, bq_rotation) if use_bq else None
    if engine == "pallas":
        from raft_tpu.ops.beam_search import beam_search

        d, i = beam_search(
            queries, dataset, graph, seeds, k, L, w, max_iters, metric,
            row_iters=row_iters, bq_records=bq_records, bq_qrot=qrot,
            bq_crot=bq_center_rot, bq_bits=bq_bits if use_bq else 0,
            bq_query_bits=bq_query_bits, bq_epsilon=bq_epsilon,
            deg=deg, interpret=interpret)
        if metric == DistanceType.InnerProduct:
            d = -d
        elif metric == DistanceType.L2SqrtExpanded:
            d = jnp.where(jnp.isfinite(d),
                          jnp.sqrt(jnp.maximum(d, 0.0)), d)
        return d, i
    return _search_batch_fn(
        dataset, graph, queries, seeds, filter_words,
        row_iters=row_iters, bq_records=bq_records, bq_qrot=qrot,
        bq_center_rot=bq_center_rot, k=k, L=L, w=w,
        max_iters=max_iters, metric=metric,
        bq_bits=bq_bits if use_bq else 0,
        bq_query_bits=bq_query_bits, bq_epsilon=bq_epsilon)


def _serving_fn(queries, dataset, graph, seed_centers, seed_members,
                bq_rotation, bq_center_rot, bq_records,
                filter_words=None, *, engine: str, k: int, L: int,
                w: int, max_iters: int, n_seeds: int,
                metric: DistanceType, seed_mode: str, seed_pool: int,
                bq_bits: int, bq_query_bits: int, bq_epsilon: float,
                deg: int, interpret: bool):
    """Bucketed serving entry (see :func:`_serve_impl`)."""
    return _serve_impl(
        queries, None, dataset, graph, seed_centers, seed_members,
        bq_rotation, bq_center_rot, bq_records, filter_words,
        engine=engine, k=k, L=L, w=w, max_iters=max_iters,
        n_seeds=n_seeds, metric=metric, seed_mode=seed_mode,
        seed_pool=seed_pool, bq_bits=bq_bits,
        bq_query_bits=bq_query_bits, bq_epsilon=bq_epsilon, deg=deg,
        interpret=interpret)


def _search_ragged_fn(queries, row_iters, dataset, graph, seed_centers,
                      seed_members, bq_rotation, bq_center_rot,
                      bq_records, filter_words=None, *, engine: str,
                      k: int, L: int, w: int, max_iters: int,
                      n_seeds: int, metric: DistanceType,
                      seed_mode: str, seed_pool: int, bq_bits: int,
                      bq_query_bits: int, bq_epsilon: float, deg: int,
                      interpret: bool):
    """Ragged serving entry: one packed query tile, per-row iteration
    budgets (the per-request ``max_iterations``, resolved by the
    executor) folded into the beam as bit-exact no-op iterations —
    each row's columns equal a solo bucketed run at its own params."""
    return _serve_impl(
        queries, row_iters, dataset, graph, seed_centers, seed_members,
        bq_rotation, bq_center_rot, bq_records, filter_words,
        engine=engine, k=k, L=L, w=w, max_iters=max_iters,
        n_seeds=n_seeds, metric=metric, seed_mode=seed_mode,
        seed_pool=seed_pool, bq_bits=bq_bits,
        bq_query_bits=bq_query_bits, bq_epsilon=bq_epsilon, deg=deg,
        interpret=interpret)


def _resolve_search_algo(params: CagraSearchParams, index: CagraIndex,
                         filter_words) -> bool:
    """True → the one-dispatch Pallas beam kernel; False → XLA path."""
    from raft_tpu.ops import beam_search as bs

    if params.algo == "xla":
        return False
    expect(params.algo in ("auto", "pallas"),
           f"algo must be 'auto'/'pallas'/'xla', got {params.algo!r}")
    # any dataset size qualifies: the kernel streams candidate rows
    # from HBM when the dataset exceeds the VMEM budget (ds_mode auto)
    ok = (index.metric in bs._SUPPORTED
          and filter_words is None
          and index.dim % 128 == 0
          and index.dataset.dtype in (jnp.float32, jnp.bfloat16,
                                      jnp.int8))
    if params.algo == "pallas":
        expect(ok, "algo='pallas' needs: L2/IP metric, no sample_filter, "
               "dim % 128 == 0, f32/bf16/int8 dataset "
               f"(n={index.size}, dim={index.dim}, "
               f"dtype={index.dataset.dtype})")
        return True
    return ok and jax.default_backend() == "tpu"


def search(
    res: Optional[Resources],
    params: CagraSearchParams,
    index: CagraIndex,
    queries,
    k: int,
    sample_filter=None,
) -> Tuple[jax.Array, jax.Array]:
    """Graph beam search — ``cagra::search`` → ``search_main``
    (``detail/cagra/cagra_search.cuh:105``). With ``sample_filter``,
    only samples whose bit is set may be returned or expanded
    (``cagra::search_with_filtering``, ``cagra.cuh:430``).

    Two engines behind ``params.algo``: the ``lax.while_loop`` XLA path
    and the one-dispatch Pallas kernel with the dataset VMEM-resident
    (``ops/beam_search``, role of the reference's persistent
    single-CTA kernel)."""
    res = ensure_resources(res)
    queries = jnp.asarray(queries)
    expect(queries.ndim == 2 and queries.shape[1] == index.dim,
           "queries must be (q, dim)")
    if queries.shape[0] == 0:
        return (jnp.zeros((0, k), jnp.float32), jnp.zeros((0, k), jnp.int32))
    cfg = derive_search_config(params, index, k)
    L, w, max_iters, n_seeds = (cfg["L"], cfg["w"], cfg["max_iters"],
                                cfg["n_seeds"])
    filter_words = resolve_filter_words(sample_filter)
    use_kernel = _resolve_search_algo(params, index, filter_words)
    seed_mode = _resolve_seed_mode(params, index)
    use_bq = _resolve_bq_traversal(params, index, use_kernel)
    if use_bq:
        from raft_tpu.ops.bq_scan import auto_query_bits

        bq_query_bits = auto_query_bits(index.bq_bits)
    else:
        bq_query_bits = 4
    if filter_words is not None and filter_words.ndim == 2:
        expect(filter_words.shape[0] == queries.shape[0],
               "per-query BitmapFilter rows must match the query count")

    with tracing.range("raft_tpu.cagra.search"):
        outs_d, outs_i = [], []
        tile = max(1, params.query_tile)
        # padded once per index, not per search call or query tile
        # (the kernel DMAs whole 128-lane-aligned adjacency rows)
        padded_graph = index.padded_graph if use_kernel else None
        for start in range(0, queries.shape[0], tile):
            qt = queries[start : start + tile]
            fw = filter_words
            if fw is not None and fw.ndim == 2:
                fw = fw[start : start + tile]
            seeds = _make_seeds(index.dataset, index.seed_centers,
                                index.seed_members, qt, n_seeds,
                                index.metric, seed_mode, params.seed_pool)
            qrot = (_rotate_queries(qt, index.bq_rotation)
                    if use_bq else None)
            if use_kernel:
                from raft_tpu.ops.beam_search import beam_search

                d, i = beam_search(
                    qt, index.dataset, padded_graph, seeds, k, L, w,
                    max_iters, index.metric,
                    bq_records=index.bq_records if use_bq else None,
                    bq_qrot=qrot,
                    bq_crot=index.bq_center_rot if use_bq else None,
                    bq_bits=index.bq_bits if use_bq else 0,
                    bq_query_bits=bq_query_bits,
                    bq_epsilon=params.bq_epsilon,
                    deg=index.graph_degree,
                    interpret=jax.default_backend() != "tpu")
                if index.metric == DistanceType.InnerProduct:
                    d = -d
                elif index.metric == DistanceType.L2SqrtExpanded:
                    d = jnp.where(jnp.isfinite(d),
                                  jnp.sqrt(jnp.maximum(d, 0.0)), d)
            else:
                d, i = _search_batch(
                    index.dataset, index.graph, qt, seeds, fw, None,
                    index.bq_records if use_bq else None, qrot,
                    index.bq_center_rot if use_bq else None,
                    k=k, L=L, w=w, max_iters=max_iters,
                    metric=index.metric,
                    bq_bits=index.bq_bits if use_bq else 0,
                    bq_query_bits=bq_query_bits,
                    bq_epsilon=params.bq_epsilon)
            outs_d.append(d)
            outs_i.append(i)
        if len(outs_d) == 1:
            return outs_d[0], outs_i[0]
        return jnp.concatenate(outs_d), jnp.concatenate(outs_i)


# ---------------------------------------------------------------------------
# serialization
# ---------------------------------------------------------------------------


def save(index: CagraIndex, fh_or_path, include_dataset: bool = True) -> None:
    """``cagra::serialize`` (``detail/cagra/cagra_serialize.cuh``)."""
    fh, own = open_maybe_path(fh_or_path, "wb")
    try:
        serialize_scalar(fh, _SERIALIZATION_VERSION, np.int32)
        serialize_scalar(fh, int(index.metric), np.int32)
        serialize_scalar(fh, 1 if include_dataset else 0, np.int32)
        serialize_array(fh, index.graph)
        if include_dataset:
            serialize_array(fh, index.dataset)
        has_seed = index.seed_centers is not None
        serialize_scalar(fh, 1 if has_seed else 0, np.int32)
        if has_seed:
            serialize_array(fh, index.seed_centers)
            serialize_array(fh, index.seed_members)
        has_bq = index.bq_records is not None
        serialize_scalar(fh, 1 if has_bq else 0, np.int32)
        if has_bq:
            serialize_scalar(fh, index.bq_bits, np.int32)
            serialize_array(fh, index.bq_rotation)
            serialize_array(fh, index.bq_center_rot)
            serialize_array(fh, index.bq_records)
    finally:
        if own:
            fh.close()


def load(res: Optional[Resources], fh_or_path, dataset=None) -> CagraIndex:
    """Load an index; pass ``dataset`` when it was saved without one."""
    res = ensure_resources(res)
    fh, own = open_maybe_path(fh_or_path, "rb")
    try:
        check_version(deserialize_scalar(fh), _SERIALIZATION_VERSION, "cagra")
        metric = DistanceType(int(deserialize_scalar(fh)))
        has_ds = int(deserialize_scalar(fh)) != 0
        graph = res.put(deserialize_array(fh))
        if has_ds:
            dataset = res.put(deserialize_array(fh))
        seed_centers = seed_members = None
        if int(deserialize_scalar(fh)) != 0:
            seed_centers = res.put(deserialize_array(fh))
            seed_members = res.put(deserialize_array(fh))
        bq_rotation = bq_center_rot = bq_records = None
        bq_bits = 0
        if int(deserialize_scalar(fh)) != 0:
            bq_bits = int(deserialize_scalar(fh))
            bq_rotation = res.put(deserialize_array(fh))
            bq_center_rot = res.put(deserialize_array(fh))
            bq_records = res.put(deserialize_array(fh))
    finally:
        if own:
            fh.close()
    expect(dataset is not None, "index was saved without its dataset")
    return CagraIndex(jnp.asarray(dataset), jnp.asarray(graph), metric,
                      seed_centers=seed_centers, seed_members=seed_members,
                      bq_rotation=bq_rotation, bq_center_rot=bq_center_rot,
                      bq_records=bq_records, bq_bits=bq_bits)
