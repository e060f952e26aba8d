"""Exact brute-force kNN — analog of ``raft::neighbors::brute_force``
(``neighbors/brute_force-inl.cuh``; impl ``detail/knn_brute_force.cuh``).

Reference architecture: a tiled loop (row tiles × database tiles) running
``pairwise_distance`` then per-tile ``select_k``, with a global merge
(``tiled_brute_force_knn:57-260``), plus a fused L2 kernel for small k and
``knn_merge_parts`` for multi-shard merges.

TPU re-design: one jitted scan over database tiles that carries a running
(k-best values, indices) state and merges each tile's local top-k with a
single ``lax.top_k`` over the 2k concatenation. The pairwise tile rides
the MXU; the merge is the TPU-KNN-paper two-phase pattern. Queries are
tiled host-side only to bound the q×tile buffer; dataset tiling is inside
the scan so HBM traffic is streamed.

The index object precomputes database norms, mirroring
``brute_force_types.hpp``'s norm caching.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.core import memwatch, tracing
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
from raft_tpu.distance.pairwise import _pairwise_distance_impl
from raft_tpu.distance.types import DistanceType, is_min_close
from raft_tpu.matrix.select_k import merge_topk
from raft_tpu.neighbors._batching import tile_queries

_SERIALIZATION_VERSION = 1


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class BruteForceIndex:
    """Exact-search index: the dataset plus cached norms
    (``brute_force_types.hpp`` ``brute_force::index``)."""

    dataset: jax.Array          # (n, d)
    norms: jax.Array            # (n,) cached ||y||^2 for expanded metrics
    metric: DistanceType
    metric_arg: float

    def tree_flatten(self):
        return (self.dataset, self.norms), (self.metric, self.metric_arg)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux[0], aux[1])

    @property
    def size(self) -> int:
        return self.dataset.shape[0]

    @property
    def dim(self) -> int:
        return self.dataset.shape[1]


def build(
    res: Optional[Resources],
    dataset,
    metric: DistanceType = DistanceType.L2Expanded,
    metric_arg: float = 2.0,
    storage_dtype=None,
) -> BruteForceIndex:
    """Construct the index (norm caching only — exact search has no train
    step). Analog of ``brute_force::build``.

    ``storage_dtype=jnp.bfloat16`` stores the dataset half-width — the
    TPU analog of the reference's fp16 dataset support: HBM traffic (the
    search bottleneck) halves, and bf16×bf16 MXU products are exact in
    f32, so distances are exact *for the quantized dataset*."""
    res = ensure_resources(res)
    dataset = jnp.asarray(dataset)
    expect(dataset.ndim == 2, "dataset must be (n, d)")
    if storage_dtype is not None:
        dataset = dataset.astype(storage_dtype)
    # graftledger capacity gate (opt-in): the dataset copy plus its
    # f32 norm plane is the whole resident footprint of this family
    memwatch.admit(
        int(dataset.shape[0]) * int(dataset.shape[1])
        * dataset.dtype.itemsize + int(dataset.shape[0]) * 4,
        "brute_force.build")
    dataset = res.put(dataset)
    norms = jnp.sum(jnp.square(dataset.astype(jnp.float32)), axis=1)
    return BruteForceIndex(dataset, norms, DistanceType(metric), metric_arg)


def _knn_scan_fn(queries, dataset, *, k: int,
                 metric: DistanceType, metric_arg: float, tile: int,
                 precision: str = "highest", approx: bool = False):
    """Scan database tiles, carrying running top-k (the global-merge loop of
    ``tiled_brute_force_knn``). ``approx`` swaps the per-tile exact top-k
    for the TPU's native approximate top-k unit (the TPU-KNN-paper
    peak-FLOP/s recipe); the cross-tile merge stays exact."""
    n, d = dataset.shape
    q = queries.shape[0]
    select_min = is_min_close(metric)
    pad_val = jnp.inf if select_min else -jnp.inf

    tile = min(tile, n)
    n_tiles = -(-n // tile)

    def step(carry, t_idx):
        best_d, best_i = carry
        # slice the dataset in place — no padded copy of the whole
        # dataset per call; the ragged tail clamps to (n - tile, n) and
        # the rows already seen by the previous tile are masked out
        start = jnp.minimum(t_idx * tile, n - tile)
        yt = jax.lax.dynamic_slice_in_dim(dataset, start, tile)
        dist = _pairwise_distance_impl(queries, yt, metric, metric_arg,
                                       precision)
        col_ids = start + jnp.arange(tile)
        dist = jnp.where((col_ids >= t_idx * tile)[None, :], dist, pad_val)
        kk = min(k, tile)
        if approx:
            sel = (jax.lax.approx_min_k if select_min
                   else jax.lax.approx_max_k)
            tile_d, tile_i = sel(dist, kk, recall_target=0.95)
        elif select_min:
            tile_d, tile_i = jax.lax.top_k(-dist, kk)
            tile_d = -tile_d
        else:
            tile_d, tile_i = jax.lax.top_k(dist, kk)
        tile_gi = start + tile_i
        new_d, new_i = merge_topk(best_d, best_i, tile_d,
                                  tile_gi.astype(jnp.int32), k, select_min)
        return (new_d, new_i), None

    init = (jnp.full((q, k), pad_val, jnp.float32),
            jnp.full((q, k), -1, jnp.int32))
    (best_d, best_i), _ = jax.lax.scan(step, init, jnp.arange(n_tiles))
    return best_d, best_i


_knn_scan = partial(jax.jit, static_argnames=(
    "k", "metric", "metric_arg", "tile", "precision", "approx"))(_knn_scan_fn)


def _use_fused_kernel(metric: DistanceType, k: int, q: int) -> bool:
    """Dispatch to the Pallas fused scan (role of the reference's
    fused-vs-tiled choice, ``detail/knn_brute_force.cuh:324``): TPU
    hardware, an expanded metric the kernel supports, small-k (the
    VPU merge is O(k·tile)), and a VMEM-resident query block.
    ``RAFT_TPU_DISABLE_FUSED=1`` forces the XLA tile-scan path
    (A/B profiling knob)."""
    import os

    from raft_tpu.ops.fused_topk import _SUPPORTED_METRICS

    return (
        jax.default_backend() == "tpu"
        and os.environ.get("RAFT_TPU_DISABLE_FUSED") != "1"
        and metric in _SUPPORTED_METRICS
        and k <= 64
        and q <= 512
    )


def search(
    res: Optional[Resources],
    index: BruteForceIndex,
    queries,
    k: int,
    query_tile: int = 8192,
    db_tile: int = 32768,
    approx: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Exact kNN: returns (distances (q, k), indices (q, k) int32) —
    ``brute_force::knn`` / ``brute_force::search``. ``approx=True``
    trades exactness for the TPU's approximate top-k unit in the
    per-tile selection (recall ≈ 0.95 per tile; merge stays exact).

    For ``InnerProduct`` the returned "distances" are similarities sorted
    descending (``is_min_close`` semantics, matching the reference).

    On TPU with small k and an expanded metric this dispatches to the
    Pallas fused scan (``raft_tpu.ops.fused_knn`` — the ``fusedL2kNN``
    analog); otherwise the XLA tile-scan path runs."""
    res = ensure_resources(res)
    queries = jnp.asarray(queries)
    expect(queries.ndim == 2, "queries must be (q, d)")
    expect(queries.shape[1] == index.dim, "query dim mismatch")
    expect(0 < k <= index.size, f"k must be in (0, {index.size}]")
    # bound the (q_tile, db_tile) distance buffer by the handle's
    # workspace budget (the reference sizes its tiles from the workspace
    # memory resource the same way, ``knn_brute_force.cuh:57-90``)
    q_rows = min(queries.shape[0], query_tile)
    budget_cols = max(128, res.workspace_limit_bytes // (4 * max(q_rows, 1)))
    db_tile = min(db_tile, budget_cols, max(128, index.size))
    precision = res.matmul_precision
    if index.dataset.dtype == jnp.bfloat16:
        # bf16 products are exact in the f32 accumulator — extra MXU
        # passes would only re-derive the same bits
        queries = queries.astype(jnp.bfloat16)
        precision = "default"
    with tracing.range("raft_tpu.brute_force.search"):
        q = queries.shape[0]
        if not approx and _use_fused_kernel(index.metric, k, q):
            from raft_tpu.ops.fused_topk import fused_knn

            return fused_knn(queries, index.dataset, k, index.metric,
                             dataset_norms=index.norms)
        def run(qt, _fw):
            return _knn_scan(qt, index.dataset, k=k, metric=index.metric,
                             metric_arg=index.metric_arg, tile=db_tile,
                             precision=precision, approx=approx)

        return tile_queries(run, queries, None, query_tile)


def knn(
    res: Optional[Resources],
    dataset,
    queries,
    k: int,
    metric: DistanceType = DistanceType.L2Expanded,
    metric_arg: float = 2.0,
) -> Tuple[jax.Array, jax.Array]:
    """One-shot convenience matching ``brute_force::knn``.

    Examples
    --------
    >>> import numpy as np
    >>> from raft_tpu.neighbors import brute_force
    >>> x = np.eye(4, dtype=np.float32)
    >>> d, i = brute_force.knn(None, x, x[:2], 1)
    >>> np.asarray(i).ravel().tolist()
    [0, 1]
    """
    index = build(res, dataset, metric, metric_arg)
    return search(res, index, queries, k)


def knn_merge_parts(distances, indices, select_min: bool = True):
    """Merge per-shard kNN results — analog of ``knn_merge_parts``
    (``detail/knn_merge_parts.cuh``), the building block of distributed
    search (SURVEY.md §5 long-context equivalent).

    Args:
      distances: (n_parts, q, k); indices: (n_parts, q, k) with *global* ids.
    Returns merged (q, k) pair.
    """
    distances = jnp.asarray(distances)
    indices = jnp.asarray(indices)
    n_parts, q, k = distances.shape
    cat_d = jnp.moveaxis(distances, 0, 1).reshape(q, n_parts * k)
    cat_i = jnp.moveaxis(indices, 0, 1).reshape(q, n_parts * k)
    return merge_topk(cat_d[:, :k], cat_i[:, :k], cat_d[:, k:], cat_i[:, k:],
                      k, select_min)


# -- serialization ----------------------------------------------------------


def save(index: BruteForceIndex, fh_or_path) -> None:
    """Versioned npy-stream serialization (pattern of
    ``brute_force_serialize``)."""
    fh, own = open_maybe_path(fh_or_path, "wb")
    try:
        serialize_scalar(fh, _SERIALIZATION_VERSION, np.int32)
        serialize_scalar(fh, int(index.metric), np.int32)
        serialize_scalar(fh, index.metric_arg, np.float32)
        serialize_array(fh, index.dataset)
        serialize_array(fh, index.norms)
    finally:
        if own:
            fh.close()


def load(res: Optional[Resources], fh_or_path) -> BruteForceIndex:
    res = ensure_resources(res)
    fh, own = open_maybe_path(fh_or_path, "rb")
    try:
        check_version(deserialize_scalar(fh), _SERIALIZATION_VERSION, "brute_force")
        metric = DistanceType(int(deserialize_scalar(fh)))
        metric_arg = float(deserialize_scalar(fh))
        dataset = res.put(deserialize_array(fh))
        norms = res.put(deserialize_array(fh))
        return BruteForceIndex(dataset, norms, metric, metric_arg)
    finally:
        if own:
            fh.close()
