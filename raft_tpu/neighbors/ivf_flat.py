"""IVF-Flat — inverted-file index with raw vectors, TPU-native re-design
of ``raft::neighbors::ivf_flat`` (``neighbors/ivf_flat_types.hpp:131``,
build ``detail/ivf_flat_build.cuh:301``, search
``detail/ivf_flat_search-inl.cuh:38-210``).

Reference architecture: balanced-kmeans cluster centers; ragged per-list
device arrays with vectors interleaved in groups of 32
(``ivf_flat_types.hpp:163-176``); search = coarse GEMM + select_k over
centers, then a fused ``interleaved_scan`` kernel over probed lists.

TPU re-design (SURVEY.md §7.4): raggedness is the enemy of XLA, so lists
live in ONE dense padded tensor ``data[n_lists, max_list_size, dim]``
(max_list_size = padded max cluster population; balanced k-means keeps the
overhead ≈2× worst case). The probe scan is pluggable
(``IvfFlatSearchParams.scan_engine``): the default **list-major**
engines (:mod:`raft_tpu.ops.ivf_scan` — the grouped Pallas kernel on
TPU, which scores each probed list against the queries that probed it,
and the XLA scan elsewhere, which scores it against the whole query
tile under a per-query membership mask) stream each probed list once;
the legacy **rank-major** engine is a ``lax.scan``
over probe ranks gathering one probed list per query into a batched
GEMM. Per-slot squared norms are precomputed so every engine's scan is
a pure ``norms - 2 x·y`` epilog (the reference caches norms the same
way, ``ivf_flat_types.hpp``).

int8/uint8 datasets are stored packed and upcast inside the scan
(reference supports float/int8/uint8, ``ivf_flat_types.hpp:49-68``).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.cluster import kmeans_balanced
from raft_tpu.cluster.kmeans_balanced import KMeansBalancedParams
from raft_tpu.core import interruptible, memwatch, tracing
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
from raft_tpu.distance.types import DistanceType, is_min_close
from raft_tpu.matrix.select_k import merge_topk
from raft_tpu.neighbors._batching import coarse_select, tile_queries
from raft_tpu.neighbors._streaming import label_pass, sample_trainset
from raft_tpu.neighbors._packing import (
    pack_padded_lists,
    padded_extent,
    streaming_ranks,
)
from raft_tpu.neighbors.ann_types import IndexParams, SearchParams
from raft_tpu.neighbors.filters import resolve_filter_words, test_filter

_SERIALIZATION_VERSION = 4  # kept in step with the reference's v4 format id


@dataclasses.dataclass(frozen=True)
class IvfFlatIndexParams(IndexParams):
    """Mirrors ``ivf_flat::index_params`` (``ivf_flat_types.hpp:49-68``)."""

    n_lists: int = 1024
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    adaptive_centers: bool = False


@dataclasses.dataclass(frozen=True)
class IvfFlatSearchParams(SearchParams):
    """Mirrors ``ivf_flat::search_params``. ``coarse_algo="approx"``
    routes cluster selection through the TPU's native approximate top-k
    unit (``lax.approx_min_k``) — worthwhile at 10k+ lists where the
    exact sort dominates the coarse stage.

    ``scan_engine`` selects the probe-scan formulation
    (:mod:`raft_tpu.ops.ivf_scan`): ``"auto"`` is the fused list-major
    Pallas kernel on TPU and the list-major XLA scan elsewhere;
    ``"pallas"``/``"xla"`` force an engine (pallas degrades to xla when
    its preconditions fail — see ``resolve_scan_engine``); ``"rank"``
    is the legacy rank-major gather scan."""

    n_probes: int = 20
    coarse_algo: str = "exact"   # "exact" | "approx"
    scan_engine: str = "auto"    # "auto" | "pallas" | "xla" | "rank"


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class IvfFlatIndex:
    """Padded-dense IVF index (role of ``ivf_flat::index``,
    ``ivf_flat_types.hpp:131``)."""

    centers: jax.Array        # (n_lists, d) float32
    center_norms: jax.Array   # (n_lists,) float32 squared norms
    data: jax.Array           # (n_lists, max_list_size, d) storage dtype
    data_norms: jax.Array     # (n_lists, max_list_size) f32, +inf at padding
    indices: jax.Array        # (n_lists, max_list_size) int32, -1 at padding
    list_sizes: jax.Array     # (n_lists,) int32
    metric: DistanceType
    adaptive_centers: bool

    def tree_flatten(self):
        return (
            self.centers, self.center_norms, self.data, self.data_norms,
            self.indices, self.list_sizes,
        ), (self.metric, self.adaptive_centers)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, metric=aux[0], adaptive_centers=aux[1])

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
        return int(self.list_sizes.sum())


# ---------------------------------------------------------------------------
# build / extend
# ---------------------------------------------------------------------------


def _pack_lists(dataset, ids, labels, n_lists: int, max_list_size: int,
                sizes=None):
    """Scatter rows into the padded [n_lists, max_list_size] layout —
    the shared sort-and-rank packing (dense formulation of the
    reference's per-list packing, ``detail/ivf_flat_build.cuh:161``)."""
    (data, indices), sizes = pack_padded_lists(
        labels, n_lists, max_list_size,
        [(dataset, 0), (jnp.asarray(ids, jnp.int32), -1)], sizes=sizes)
    # per-slot norms; +inf on padding so padded slots never win the top-k
    norms = jnp.sum(jnp.square(data.astype(jnp.float32)), axis=2)
    norms = jnp.where(indices >= 0, norms, jnp.inf)
    return data, norms, indices, sizes


def build(
    res: Optional[Resources],
    params: IvfFlatIndexParams,
    dataset,
) -> IvfFlatIndex:
    """Train the coarse quantizer and (optionally) fill the lists —
    ``ivf_flat::build`` (``detail/ivf_flat_build.cuh:301``).

    Examples
    --------
    >>> import numpy as np
    >>> from raft_tpu.neighbors import ivf_flat
    >>> x = np.arange(32, dtype=np.float32).reshape(16, 2)
    >>> idx = ivf_flat.build(
    ...     None, ivf_flat.IvfFlatIndexParams(n_lists=2), x)
    >>> _, i = ivf_flat.search(
    ...     None, ivf_flat.IvfFlatSearchParams(n_probes=2), idx, x[:1], 1)
    >>> int(np.asarray(i)[0, 0])
    0
    """
    res = ensure_resources(res)
    dataset = jnp.asarray(dataset)
    expect(dataset.ndim == 2, "dataset must be (n, d)")
    n, d = dataset.shape
    expect(params.n_lists <= n, "n_lists > n_rows")
    expect(
        params.metric in (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded,
                          DistanceType.InnerProduct),
        f"ivf_flat supports L2Expanded/L2SqrtExpanded/InnerProduct, got {params.metric!r}",
    )
    with tracing.range("raft_tpu.ivf_flat.build"):
        # subsample trainset (``ivf_pq_build.cuh:1537`` pattern shared by IVF)
        frac = min(max(params.kmeans_trainset_fraction, 0.0), 1.0)
        n_train = max(params.n_lists, int(n * frac))
        if n_train < n:
            stride = n // n_train
            trainset = dataset[:: stride][:n_train].astype(jnp.float32)
        else:
            trainset = dataset.astype(jnp.float32)
        km_params = KMeansBalancedParams(
            n_iters=params.kmeans_n_iters,
            metric=(DistanceType.InnerProduct
                    if params.metric == DistanceType.InnerProduct
                    else DistanceType.L2Expanded),
            seed=res.seed,
        )
        centers = kmeans_balanced.fit(res, km_params, trainset, params.n_lists)
        center_norms = jnp.sum(jnp.square(centers), axis=1)

        empty = IvfFlatIndex(
            centers=centers,
            center_norms=center_norms,
            data=jnp.zeros((params.n_lists, 0, d), dataset.dtype),
            data_norms=jnp.zeros((params.n_lists, 0), jnp.float32),
            indices=jnp.full((params.n_lists, 0), -1, jnp.int32),
            list_sizes=jnp.zeros((params.n_lists,), jnp.int32),
            metric=DistanceType(params.metric),
            adaptive_centers=params.adaptive_centers,
        )
        if not params.add_data_on_build:
            return empty
        return extend(res, empty, dataset, jnp.arange(n, dtype=jnp.int32))


def _scatter_extend_fn(data, norms, indices, rows, row_norms, ids, list_ids,
                       ranks):
    """Scatter new rows into the padded list tensors — the incremental
    half of ``extend``. With the donating wrapper the big (n_lists,
    max_list_size, dim) tensor is updated in place: no full repack, no
    second HBM allocation."""
    return (data.at[list_ids, ranks].set(rows),
            norms.at[list_ids, ranks].set(row_norms),
            indices.at[list_ids, ranks].set(ids))


_scatter_extend = jax.jit(_scatter_extend_fn)
_scatter_extend_donated = jax.jit(_scatter_extend_fn, donate_argnums=(0, 1, 2))


def extend(
    res: Optional[Resources],
    index: IvfFlatIndex,
    new_vectors,
    new_indices=None,
    donate: bool = False,
) -> IvfFlatIndex:
    """Add vectors to the index — ``ivf_flat::extend``
    (``detail/ivf_flat_build.cuh:161``). Functional: returns a new index
    (XLA model; the reference mutates device lists in place).

    When the new rows fit inside the existing padding, they are
    scattered incrementally — O(new) work instead of a full O(total)
    repack. With ``donate=True`` the old index's list tensors are
    donated to that scatter, so the rebuild reuses their HBM in place —
    the serving-ingestion mode; the *old* index object must not be used
    afterwards. Only the incremental path can donate; a growing padded
    extent always falls back to the full functional repack.

    With ``adaptive_centers`` the centers drift toward the running mean of
    their list (``ivf_flat_types.hpp:57-68``)."""
    res = ensure_resources(res)
    new_vectors = jnp.asarray(new_vectors)
    expect(new_vectors.ndim == 2 and new_vectors.shape[1] == index.dim,
           "new_vectors must be (n, dim)")
    n_new = new_vectors.shape[0]
    if new_indices is None:
        start = index.size
        new_indices = jnp.arange(start, start + n_new, dtype=jnp.int32)
    else:
        new_indices = jnp.asarray(new_indices, jnp.int32)

    with tracing.range("raft_tpu.ivf_flat.extend"):
        km_params = KMeansBalancedParams(
            metric=(DistanceType.InnerProduct
                    if index.metric == DistanceType.InnerProduct
                    else DistanceType.L2Expanded))
        new_labels = kmeans_balanced.predict(res, km_params, index.centers,
                                             new_vectors.astype(jnp.float32))

        # -- incremental fast path: new rows fit the existing padding.
        # Slot assignment matches the full repack bit-for-bit (old rows
        # keep their slots; new rows land at the running fill ranks),
        # so the two paths produce identical tensors.
        if index.max_list_size > 0 and not index.adaptive_centers:
            sizes_new = index.list_sizes + jax.ops.segment_sum(
                jnp.ones((n_new,), jnp.int32), new_labels,
                num_segments=index.n_lists)
            if (padded_extent(sizes_new, index.data.dtype)
                    <= index.max_list_size):
                lab_np = np.asarray(new_labels)
                fill = np.asarray(index.list_sizes).astype(np.int64)
                ranks = streaming_ranks(lab_np, fill, index.n_lists)
                rows = new_vectors.astype(index.data.dtype)
                row_norms = jnp.sum(
                    jnp.square(rows.astype(jnp.float32)), axis=1)
                scatter = _scatter_extend_donated if donate else _scatter_extend
                data, norms, indices = scatter(
                    index.data, index.data_norms, index.indices, rows,
                    row_norms, new_indices, jnp.asarray(lab_np),
                    jnp.asarray(ranks))
                return dataclasses.replace(
                    index, data=data, data_norms=norms, indices=indices,
                    list_sizes=sizes_new)

        # gather existing rows back to flat form and re-pack everything
        if index.max_list_size > 0:
            old_rows = index.data.reshape(-1, index.dim)
            old_ids = index.indices.reshape(-1)
            old_labels = jnp.repeat(jnp.arange(index.n_lists, dtype=jnp.int32),
                                    index.max_list_size)
            keep = old_ids >= 0
            # compaction happens on host-side sizes; keep as dense select
            all_vecs = jnp.concatenate([old_rows[keep], new_vectors])
            all_ids = jnp.concatenate([old_ids[keep], new_indices])
            all_labels = jnp.concatenate([old_labels[keep], new_labels])
        else:
            all_vecs, all_ids, all_labels = new_vectors, new_indices, new_labels

        sizes = jax.ops.segment_sum(
            jnp.ones((all_vecs.shape[0],), jnp.int32), all_labels,
            num_segments=index.n_lists,
        )
        # one host sync at build/extend time to fix the padded extent
        max_size = padded_extent(sizes, all_vecs.dtype)

        # graftledger capacity gate (opt-in, no-op unless installed):
        # the repack is the allocation event — admit its padded layout
        # host-side BEFORE any device tensor materializes, so an index
        # that cannot fit fails as a typed CapacityExceeded instead of
        # a backend OOM
        memwatch.admit(
            memwatch.packed_layout_bytes(
                index.n_lists, int(max_size),
                index.dim * all_vecs.dtype.itemsize),
            "ivf_flat.extend")

        data, norms, indices, sizes = _pack_lists(
            all_vecs, all_ids, all_labels, index.n_lists, max_size,
            sizes=sizes,
        )

        centers = index.centers
        if index.adaptive_centers:
            sums = jax.ops.segment_sum(
                all_vecs.astype(jnp.float32), all_labels,
                num_segments=index.n_lists,
            )
            nonempty = sizes > 0
            centers = jnp.where(
                nonempty[:, None],
                sums / jnp.maximum(sizes, 1)[:, None].astype(jnp.float32),
                centers,
            )
        center_norms = jnp.sum(jnp.square(centers), axis=1)

        return IvfFlatIndex(
            centers=centers,
            center_norms=center_norms,
            data=data,
            data_norms=norms,
            indices=indices,
            list_sizes=sizes,
            metric=index.metric,
            adaptive_centers=index.adaptive_centers,
        )


def build_streaming(
    res: Optional[Resources],
    params: IvfFlatIndexParams,
    source,
    chunk_rows: int = 1 << 20,
    train_rows: int = 1 << 18,
) -> IvfFlatIndex:
    """Build from a dataset that never fully materializes in host memory
    — the 100M+-row ingestion path (role of the reference's
    managed-memory trainset spill, ``ivf_pq_build.cuh:1542-1554``, plus
    its batched extend).

    ``source`` is a :class:`raft_tpu.io.BinDataset` (or any object with
    ``n_rows``/``dim``/``iter_chunks``). Three streamed passes over the
    native prefetch pipeline:

    1. strided trainset sample → balanced-kmeans centers;
    2. per-chunk label predict (device) + list-size count (host);
    3. per-chunk scatter into the padded list tensor with **donated**
       device buffers, so the big tensor is updated in place.
    """
    res = ensure_resources(res)
    n, d = source.n_rows, source.dim
    expect(params.n_lists <= n, "n_lists > n_rows")

    with tracing.range("raft_tpu.ivf_flat.build_streaming"):
        # -- pass 1: trainset sample + centers
        train_rows = max(params.n_lists, min(train_rows, n))
        trainset = sample_trainset(source, train_rows, chunk_rows)
        km_params = KMeansBalancedParams(
            n_iters=params.kmeans_n_iters,
            metric=(DistanceType.InnerProduct
                    if params.metric == DistanceType.InnerProduct
                    else DistanceType.L2Expanded),
            seed=res.seed,
        )
        centers = kmeans_balanced.fit(res, km_params, jnp.asarray(trainset),
                                      params.n_lists)

        # -- pass 2: labels + sizes
        labels_np, sizes_np = label_pass(res, km_params, centers, source,
                                         chunk_rows, params.n_lists)
        max_size = padded_extent(sizes_np)

        # -- pass 3: scatter chunks into donated padded buffers. Indexing
        # is 2-D (list id, rank within list): a flat slot index would
        # overflow int32 (jax default) past 2^31 total slots, well within
        # the billion-row regime this path targets.
        @partial(jax.jit, donate_argnums=(0, 1))
        def scatter_chunk(data, idx, rows, ids, list_ids, ranks):
            return (data.at[list_ids, ranks].set(rows),
                    idx.at[list_ids, ranks].set(ids))

        # graftledger capacity gate (opt-in): the donated padded
        # buffers below are THE allocation of the streaming path —
        # admit them host-side like the repack path does
        memwatch.admit(
            memwatch.packed_layout_bytes(params.n_lists, int(max_size),
                                         d * 4),
            "ivf_flat.build_streaming")
        data = jnp.zeros((params.n_lists, max_size, d), jnp.float32)
        indices = jnp.full((params.n_lists, max_size), -1, jnp.int32)
        fill = np.zeros((params.n_lists,), np.int64)
        for first, chunk in source.iter_chunks(chunk_rows):
            interruptible.yield_()  # cancellation point per chunk
            m = chunk.shape[0]
            lab = labels_np[first : first + m]
            ranks = streaming_ranks(lab, fill, params.n_lists)
            data, indices = scatter_chunk(
                data, indices,
                jnp.asarray(chunk, jnp.float32),
                jnp.asarray(first + np.arange(m, dtype=np.int32)),
                jnp.asarray(lab),
                jnp.asarray(ranks),
            )

        norms = jnp.sum(jnp.square(data), axis=2)
        norms = jnp.where(indices >= 0, norms, jnp.inf)
        return IvfFlatIndex(
            centers=centers,
            center_norms=jnp.sum(jnp.square(centers), axis=1),
            data=data,
            data_norms=norms,
            indices=indices,
            list_sizes=jnp.asarray(sizes_np, jnp.int32),
            metric=DistanceType(params.metric),
            adaptive_centers=params.adaptive_centers,
        )


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------


def _search_impl_fn(queries, centers, center_norms, data, data_norms, indices,
                    filter_words, probe_counts=None, n_valid=None,
                    row_probes=None, *,
                    n_probes: int, k: int, metric: DistanceType,
                    coarse_algo: str = "exact",
                    scan_engine: str = "rank"):
    """Coarse select + probe scan with running top-k merge.

    ``probe_counts`` (graftgauge) optionally provides the donated
    (n_lists,) int32 cumulative probe-frequency plane: the selected
    probe ids scatter-add into it (:func:`raft_tpu.ops.ivf_scan
    .probe_histogram`, pad rows past ``n_valid`` masked out) and the
    updated plane returns as a third output. The search results never
    read it, so enabling accounting cannot perturb them.

    ``n_valid`` (traced scalar, the executor's real row count) also
    turns the probes of bucket-pad rows into sentinels before a
    list-major scan, so they cost the grouped Pallas scan no steps;
    pad rows' own results are never read.

    ``row_probes`` (the ragged query-tile front, via
    :func:`_search_ragged_fn`) optionally provides the per-ROW probe
    budget plane of a packed ragged batch: the coarse stage then
    selects at the class cap ``n_probes`` and each row's slots past
    its own budget mask to the sentinel id
    (:func:`raft_tpu.ops.ivf_scan.ragged_probes`) — the scan below is
    char-identical between the bucketed and ragged paths, which IS the
    bit-identity argument. Pad rows carry budget 0, so ``n_valid``
    masking is redundant on this path (every pad slot is already the
    sentinel, which :func:`~raft_tpu.ops.ivf_scan.probe_histogram`
    drops).

    ``scan_engine`` must arrive resolved (``rank``/``pallas``/``xla``,
    via :func:`raft_tpu.ops.ivf_scan.resolve_scan_engine`): it is a jit
    static, so an unresolved ``"auto"`` would fork the compile cache."""
    q, d = queries.shape
    n_lists, max_size, _ = data.shape
    select_min = is_min_close(metric)
    qf = queries.astype(jnp.float32)

    # ---- coarse: ``select_clusters`` (GEMM + select_k over centers)
    ip = jax.lax.dot_general(
        qf, centers, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    score = (ip if metric == DistanceType.InnerProduct
             else -(center_norms[None, :] - 2.0 * ip))          # larger=better
    probes = coarse_select(score, n_probes, coarse_algo)
    if row_probes is not None:
        from raft_tpu.ops.ivf_scan import ragged_probes

        probes = ragged_probes(probes, row_probes, n_lists)
    if probe_counts is not None:
        from raft_tpu.ops.ivf_scan import probe_histogram

        probe_counts = probe_histogram(
            probes, probe_counts,
            None if row_probes is not None else n_valid)
    if n_valid is not None and scan_engine != "rank":
        # bucket-pad rows probe nothing: the list-major scans skip
        # sentinel probes, so a pad row adds no (query, list) pair
        valid = jnp.arange(q, dtype=jnp.int32) < n_valid
        probes = jnp.where(valid[:, None], probes, n_lists)

    pad_val = jnp.inf if select_min else -jnp.inf

    if scan_engine != "rank":
        # ---- list-major probe scan (ops/ivf_scan): stream each unique
        # probed list once. The XLA engine scores it against the whole
        # tile; the grouped Pallas scan scores it against the rows that
        # probed it.
        from raft_tpu.ops.ivf_scan import list_major_scan

        best_d, best_i = list_major_scan(
            qf, data, data_norms, indices, probes, filter_words,
            k=k, metric=metric, engine=scan_engine,
            interpret=jax.default_backend() != "tpu")
    else:
        # ---- rank-major probe scan: one gathered list + one batched
        # GEMM per probe rank
        def step(carry, rank):
            best_d, best_i = carry
            lists = probes[:, rank]                              # (q,)
            rows = jnp.take(data, lists, axis=0).astype(
                jnp.float32)                                     # (q, m, d)
            row_norms = jnp.take(data_norms, lists, axis=0)      # (q, m)
            row_ids = jnp.take(indices, lists, axis=0)           # (q, m)
            ipr = jax.lax.dot_general(
                rows, qf, (((2,), (1,)), ((0,), (0,))),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )                                                    # (q, m)
            if metric == DistanceType.InnerProduct:
                dist = jnp.where(row_ids >= 0, ipr, pad_val)
            else:
                dist = row_norms - 2.0 * ipr                     # +||q||^2 later
                dist = jnp.where(row_ids >= 0, dist, pad_val)
            if filter_words is not None:
                bits = test_filter(filter_words, row_ids)
                dist = jnp.where(bits & (row_ids >= 0), dist, pad_val)

            new_d, new_i = merge_topk(best_d, best_i, dist, row_ids, k,
                                      select_min)
            return (new_d, new_i), None

        init = (jnp.full((q, k), pad_val, jnp.float32),
                jnp.full((q, k), -1, jnp.int32))
        (best_d, best_i), _ = jax.lax.scan(step, init, jnp.arange(n_probes))

    if metric != DistanceType.InnerProduct:
        q_sq = jnp.sum(jnp.square(qf), axis=1, keepdims=True)
        best_d = jnp.where(jnp.isfinite(best_d),
                           jnp.maximum(best_d + q_sq, 0.0), best_d)
        if metric == DistanceType.L2SqrtExpanded:
            best_d = jnp.where(jnp.isfinite(best_d), jnp.sqrt(best_d), best_d)
    if probe_counts is not None:
        return best_d, best_i, probe_counts
    return best_d, best_i


_search_impl = partial(jax.jit, static_argnames=(
    "n_probes", "k", "metric", "coarse_algo", "scan_engine"))(_search_impl_fn)


def _search_ragged_fn(queries, row_probes, centers, center_norms, data,
                      data_norms, indices, filter_words,
                      probe_counts=None, n_valid=None, *,
                      n_probes: int, k: int, metric: DistanceType,
                      scan_engine: str = "xla"):
    """Packed ragged-batch search body — the serving executor's
    one-executable-per-params-class entry (Ragged Paged Attention
    style; see :mod:`raft_tpu.ops.ivf_scan`'s ragged front).

    ``queries`` is a fixed ``(tile, d)`` packed tensor holding several
    requests' rows adjacently (pad rows zero); ``row_probes`` is the
    per-row probe budget (:func:`raft_tpu.ops.ivf_scan
    .ragged_row_probes` — 0 on pad rows). ``n_probes`` and ``k`` are
    the packed batch's CLASS CAPS: the coarse stage selects the top
    ``n_probes`` lists exactly (``lax.top_k`` is a total order, so a
    row's first ``b`` slots equal a solo ``n_probes=b`` selection) and
    each row masks its slots past ``row_probes`` to the sentinel —
    per-request ``n_probes`` resolves through the engines' existing
    membership mask, and per-request ``k`` is a caller-side column
    slice of the total-order top-``k``. Bit-identical per request to
    :func:`_search_impl_fn` on that request alone — structurally: this
    IS :func:`_search_impl_fn` with the ``row_probes`` hook live, so
    the scan code cannot drift between the two paths.

    ``coarse_algo`` is deliberately NOT a knob: only the exact coarse
    top-k has the prefix property the class cap relies on
    (``approx_max_k`` at the cap is not a solo ``approx_max_k`` at the
    request's budget), so approx-coarse requests stay on the bucketed
    path; likewise the rank-major engine has no membership mask to
    resolve per-row budgets through. ``n_valid`` is accepted for
    signature parity but unused — ``row_probes`` already zeroes pad
    rows out of the scan and the histogram."""
    del n_valid
    expect(scan_engine in ("pallas", "xla"),
           "ragged serving needs a membership-masked list-major engine "
           f"(pallas|xla), got {scan_engine!r}")
    return _search_impl_fn(
        queries, centers, center_norms, data, data_norms, indices,
        filter_words, probe_counts, None,
        row_probes=row_probes, n_probes=n_probes, k=k, metric=metric,
        coarse_algo="exact", scan_engine=scan_engine)


def search(
    res: Optional[Resources],
    params: IvfFlatSearchParams,
    index: IvfFlatIndex,
    queries,
    k: int,
    sample_filter=None,
    query_tile: int = 4096,
) -> Tuple[jax.Array, jax.Array]:
    """ANN search — ``ivf_flat::search``
    (``detail/ivf_flat_search-inl.cuh:38-210``).

    ``sample_filter``: a Bitset or any :mod:`raft_tpu.neighbors.filters`
    type. Large query sets are processed in ``query_tile`` batches (the
    reference's max_queries=4096 batching loop). The probe-scan engine
    follows ``params.scan_engine`` (resolved per backend/shape by
    :func:`raft_tpu.ops.ivf_scan.resolve_scan_engine`). Returns
    (distances, indices) of shape (q, k); missing slots (when fewer
    than k valid candidates were probed) have index -1."""
    ensure_resources(res)
    queries = jnp.asarray(queries)
    expect(queries.ndim == 2 and queries.shape[1] == index.dim,
           "queries must be (q, dim)")
    expect(index.max_list_size > 0, "index is empty — extend() it first")
    expect(params.coarse_algo in ("exact", "approx"),
           f"coarse_algo must be 'exact' or 'approx', got {params.coarse_algo!r}")
    n_probes = min(params.n_probes, index.n_lists)
    filter_words = resolve_filter_words(sample_filter)
    from raft_tpu.ops.ivf_scan import resolve_scan_engine

    scan_engine = resolve_scan_engine(
        params.scan_engine, data=index.data, filter_words=filter_words, k=k)
    with tracing.range("raft_tpu.ivf_flat.search"):
        def run(qt, fw):
            return _search_impl(
                qt, index.centers, index.center_norms, index.data,
                index.data_norms, index.indices, fw,
                n_probes=n_probes, k=k, metric=index.metric,
                coarse_algo=params.coarse_algo, scan_engine=scan_engine,
            )

        return tile_queries(run, queries, filter_words, query_tile)


# ---------------------------------------------------------------------------
# serialization (versioned npy stream, reference v4 layout analog)
# ---------------------------------------------------------------------------


def save(index: IvfFlatIndex, fh_or_path) -> None:
    """``ivf_flat::serialize`` (``detail/ivf_flat_serialize.cuh:37``)."""
    fh, own = open_maybe_path(fh_or_path, "wb")
    try:
        serialize_scalar(fh, _SERIALIZATION_VERSION, np.int32)
        serialize_scalar(fh, int(index.metric), np.int32)
        serialize_scalar(fh, int(index.adaptive_centers), np.int32)
        serialize_array(fh, index.centers)
        serialize_array(fh, index.data)
        serialize_array(fh, index.indices)
        serialize_array(fh, index.list_sizes)
    finally:
        if own:
            fh.close()


def load(res: Optional[Resources], fh_or_path) -> IvfFlatIndex:
    """``ivf_flat::deserialize``."""
    res = ensure_resources(res)
    fh, own = open_maybe_path(fh_or_path, "rb")
    try:
        check_version(deserialize_scalar(fh), _SERIALIZATION_VERSION, "ivf_flat")
        metric = DistanceType(int(deserialize_scalar(fh)))
        adaptive = bool(deserialize_scalar(fh))
        centers = res.put(deserialize_array(fh))
        data = res.put(deserialize_array(fh))
        indices = res.put(deserialize_array(fh))
        sizes = res.put(deserialize_array(fh))
    finally:
        if own:
            fh.close()
    centers = jnp.asarray(centers)
    data_f = jnp.asarray(data).astype(jnp.float32)
    indices = jnp.asarray(indices)
    norms = jnp.sum(jnp.square(data_f), axis=2)
    norms = jnp.where(indices >= 0, norms, jnp.inf)
    return IvfFlatIndex(
        centers=centers,
        center_norms=jnp.sum(jnp.square(centers), axis=1),
        data=jnp.asarray(data),
        data_norms=norms,
        indices=indices,
        list_sizes=jnp.asarray(sizes),
        metric=metric,
        adaptive_centers=adaptive,
    )
