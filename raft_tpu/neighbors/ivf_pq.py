"""IVF-PQ — inverted file with product quantization, TPU-native re-design
of ``raft::neighbors::ivf_pq`` (``neighbors/ivf_pq_types.hpp:219``, build
``detail/ivf_pq_build.cuh:1513``, search ``detail/ivf_pq_search.cuh:732``).

Reference architecture: balanced-kmeans coarse clusters; residuals rotated
by a (random orthogonal) matrix (``make_rotation_matrix``,
``detail/ivf_pq_build.cuh:122``); product codebooks trained per subspace or
per cluster (``:344``/``:421``); codes packed interleaved in 16-byte
chunks; search builds a per-(query, probe) lookup table and scores codes in
a fused kernel with fp8/fp16/fp32 LUTs
(``detail/ivf_pq_compute_similarity-inl.cuh:125-177``).

TPU re-design:

- codes live in ONE dense padded tensor ``codes[n_lists, max_list_size,
  pq_dim] uint8`` — no interleaving: the TPU reads codes in vectorized
  rows, and XLA lays out the trailing dims for the VPU. (The CUDA
  interleave exists to serve 32 threads striding a list; irrelevant here.)
- the LUT phase is a batched MXU GEMM (`q̃` rotation + pairwise-sq-dist
  against codebooks); scoring is a vectorized table gather per subspace,
  merged into a running top-k scan over probe ranks, identical in shape
  to the IVF-Flat scan.
- codebook training is a ``vmap``-ed fixed-iteration Lloyd EM over the
  pq_dim subspaces (one compiled kernel trains all codebooks at once,
  vs the reference's stream-parallel loop of kmeans launches).

Supported metrics: L2Expanded / L2SqrtExpanded / InnerProduct (reference
set, ``ivf_pq_types.hpp``).
"""

from __future__ import annotations

import dataclasses
import enum
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

_SERIALIZATION_VERSION = 4  # v4: adds the 4-bit nibble-packed codes flag


class CodebookKind(enum.IntEnum):
    """Mirrors ``ivf_pq::codebook_gen`` (``ivf_pq_types.hpp:42-46``)."""

    PER_SUBSPACE = 0
    PER_CLUSTER = 1


@dataclasses.dataclass(frozen=True)
class IvfPqIndexParams(IndexParams):
    """Mirrors ``ivf_pq::index_params`` (``ivf_pq_types.hpp:48-111``)."""

    n_lists: int = 1024
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    pq_bits: int = 8              # 4..8
    pq_dim: int = 0               # 0 → auto: dim/4 rounded to multiple of 8
    codebook_kind: CodebookKind = CodebookKind.PER_SUBSPACE
    force_random_rotation: bool = False


@dataclasses.dataclass(frozen=True)
class IvfPqSearchParams(SearchParams):
    """Mirrors ``ivf_pq::search_params`` — ``lut_dtype``/
    ``internal_distance_dtype`` select the scoring precision like the
    reference's fp32/fp16/fp8 LUT variants."""

    n_probes: int = 20
    # "approx" routes cluster selection through the TPU's native
    # approximate top-k unit — worthwhile at 10k+ lists (same knob as
    # IvfFlatSearchParams.coarse_algo)
    coarse_algo: str = "exact"
    # probe-scan formulation (same knob as IvfFlatSearchParams):
    # "rank" gathers one probed list per query per probe rank; "xla"
    # scans the *union* of probed lists list-major (ops/ivf_scan) —
    # each list's codes stream from HBM once and score against the
    # whole query tile. "auto" = list-major on TPU (the gather is the
    # scalar-core bottleneck there), rank-major elsewhere.
    scan_engine: str = "auto"
    # f32 / bf16 / float8_e4m3fn — the reference's fp32/fp16/fp8 LUT
    # ladder (ivf_pq_compute_similarity-inl.cuh:125-177). fp8 quarters
    # the LUT's VMEM footprint (the probe-tile bound); scoring upcasts
    # to bf16 on the fly, so only LUT entries round
    lut_dtype: jnp.dtype = jnp.float32
    # "gather": per-element LUT lookup; "onehot": gather-free MXU
    # contraction (J-fold more FLOPs, no dynamic gathers). "auto"
    # resolves per backend: measured on TPU v5e the one-hot path is
    # ~18x faster (dynamic gathers lower to the scalar core), while on
    # CPU the gather wins.
    score_mode: str = "auto"


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class IvfPqIndex:
    """PQ-compressed IVF index (role of ``ivf_pq::index``)."""

    centers: jax.Array        # (n_lists, dim) f32 cluster centers
    rotation: jax.Array       # (dim_ext, dim) f32 orthogonal-ish map
    codebooks: jax.Array      # PER_SUBSPACE: (pq_dim, 2^bits, pq_len)
                              # PER_CLUSTER:  (n_lists, 2^bits, pq_len)
    codes: jax.Array          # (n_lists, max_list_size, pq_dim) uint8 —
                              # or (…, pq_dim // 2) nibble-packed when
                              # ``packed`` (pq_bits == 4)
    indices: jax.Array        # (n_lists, max_list_size) int32, -1 pad
    list_sizes: jax.Array     # (n_lists,) int32
    metric: DistanceType
    codebook_kind: CodebookKind
    pq_bits: int
    packed: bool = False      # two 4-bit codes per byte (halves HBM)

    def tree_flatten(self):
        return (
            self.centers, self.rotation, self.codebooks, self.codes,
            self.indices, self.list_sizes,
        ), (self.metric, self.codebook_kind, self.pq_bits, self.packed)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, metric=aux[0], codebook_kind=aux[1],
                   pq_bits=aux[2], packed=aux[3])

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
    def pq_dim(self) -> int:
        return self.codes.shape[2] * 2 if self.packed else self.codes.shape[2]

    @property
    def pq_len(self) -> int:
        return self.codebooks.shape[2]

    @property
    def pq_book_size(self) -> int:
        return 1 << self.pq_bits

    @property
    def max_list_size(self) -> int:
        return self.codes.shape[1]

    @property
    def size(self) -> int:
        return int(self.list_sizes.sum())


# ---------------------------------------------------------------------------
# build helpers
# ---------------------------------------------------------------------------


def _auto_pq_dim(dim: int) -> int:
    """Reference heuristic: dim/4 rounded up to a multiple of 8
    (``ivf_pq_types.hpp`` pq_dim docs)."""
    pq = max(1, dim // 4)
    return max(8, -(-pq // 8) * 8) if dim >= 32 else max(1, pq)


def make_rotation_matrix(key, dim_ext: int, dim: int, force_random: bool):
    """Orthogonal projection dim → dim_ext
    (``detail/ivf_pq_build.cuh:122``): identity when dims align and
    randomness is not forced; otherwise QR of a gaussian."""
    if not force_random and dim_ext == dim:
        return jnp.eye(dim, dtype=jnp.float32)
    g = jax.random.normal(key, (dim_ext, max(dim_ext, dim)), jnp.float32)
    qmat, _ = jnp.linalg.qr(g.T)            # (max, dim_ext) orthonormal cols
    return qmat[:dim, :].T                  # (dim_ext, dim), R R^T = I on range


# codebook-training rows per codeword (reference
# ``ivf_pq::index_params::max_train_points_per_pq_code``)
_MAX_TRAIN_POINTS_PER_PQ_CODE = 256


@partial(jax.jit, static_argnames=("n_centers", "n_iters"))
def _vmapped_lloyd(trainsets, key, n_centers: int, n_iters: int):
    """Fixed-iteration Lloyd EM vmapped over leading axis — trains all
    pq_dim (or n_lists) codebooks in one compiled kernel
    (role of ``train_per_subset``/``train_per_cluster``,
    ``detail/ivf_pq_build.cuh:344,421``)."""

    def one(trainset, k):
        n = trainset.shape[0]
        idx = jax.random.choice(k, n, (n_centers,), replace=n < n_centers)
        centers = trainset[idx]

        def body(_, centers):
            d = (
                jnp.sum(jnp.square(trainset), 1)[:, None]
                - 2.0 * trainset @ centers.T
                + jnp.sum(jnp.square(centers), 1)[None, :]
            )
            labels = jnp.argmin(d, axis=1)
            sums = jax.ops.segment_sum(trainset, labels, num_segments=n_centers)
            counts = jax.ops.segment_sum(
                jnp.ones((n,), jnp.float32), labels, num_segments=n_centers
            )
            new = sums / jnp.maximum(counts, 1.0)[:, None]
            return jnp.where((counts > 0)[:, None], new, centers)

        return jax.lax.fori_loop(0, n_iters, body, centers)

    keys = jax.random.split(key, trainsets.shape[0])
    return jax.vmap(one)(trainsets, keys)


def _rotate_residuals(vectors, labels, centers, rotation):
    """R @ (x - c_label), reshaped to (n, pq_dim, pq_len)."""
    res = vectors.astype(jnp.float32) - centers[labels]
    rot = res @ rotation.T                     # (n, dim_ext)
    return rot


def _encode(rot_residuals, codebooks, labels, codebook_kind: CodebookKind,
            pq_dim: int, pq_len: int):
    """Nearest-codeword per subspace
    (role of ``process_and_fill_codes_kernel``, ``ivf_pq_build.cuh:946``).

    Scans over subspaces so the distance tensor is O(n · 2^bits) per
    step instead of the O(n · pq_dim · 2^bits) a one-shot form needs
    (13 GB at n=200k, pq_dim=64, 8 bits — over HBM). PER_CLUSTER
    additionally keeps the gathered per-row codebooks,
    O(n · 2^bits · pq_len), alive across the scan. The constant
    ``||sub||²`` term is dropped: it does not move the argmin."""
    n = rot_residuals.shape[0]
    sub = rot_residuals.reshape(n, pq_dim, pq_len)
    if codebook_kind == CodebookKind.PER_CLUSTER:
        cb_rows = codebooks[labels]            # (n, 2^bits, pq_len)
        cb_norms = jnp.sum(jnp.square(cb_rows), -1)

        def step(_, s):
            v = jax.lax.dynamic_index_in_dim(sub, s, 1, False)   # (n, L)
            scores = cb_norms - 2.0 * jnp.einsum("nl,njl->nj", v, cb_rows)
            return _, jnp.argmin(scores, axis=1).astype(jnp.uint8)
    else:

        def step(_, s):
            v = jax.lax.dynamic_index_in_dim(sub, s, 1, False)   # (n, L)
            cb = jax.lax.dynamic_index_in_dim(codebooks, s, 0, False)
            scores = jnp.sum(jnp.square(cb), -1)[None, :] - 2.0 * (v @ cb.T)
            return _, jnp.argmin(scores, axis=1).astype(jnp.uint8)

    _, codes = jax.lax.scan(step, None, jnp.arange(pq_dim))
    return codes.T                              # (n, pq_dim)


def _pack_nibbles(codes):
    """Two 4-bit codes per byte along the last axis: even subspaces in
    the low nibble (role of the reference's bit-packed 4-bit code
    planes, ``ivf_pq_types.hpp`` list_spec)."""
    return (codes[..., 0::2] | (codes[..., 1::2] << 4)).astype(jnp.uint8)


def _unpack_nibbles(packed):
    """Inverse of :func:`_pack_nibbles` → (..., 2 * packed.shape[-1])."""
    lo = packed & jnp.uint8(0x0F)
    hi = packed >> 4
    stacked = jnp.stack([lo, hi], axis=-1)          # (..., s/2, 2)
    return stacked.reshape(*packed.shape[:-1], packed.shape[-1] * 2)


def _pack_codes(codes, ids, labels, n_lists: int, max_list_size: int,
                sizes=None):
    """Scatter code rows into the padded [n_lists, max_list_size] layout
    (the shared sort-and-rank packing)."""
    (packed, indices), sizes = pack_padded_lists(
        labels, n_lists, max_list_size, [(codes, 0), (ids, -1)],
        sizes=sizes)
    return packed, indices, sizes


# ---------------------------------------------------------------------------
# build / extend
# ---------------------------------------------------------------------------


def build(
    res: Optional[Resources],
    params: IvfPqIndexParams,
    dataset,
) -> IvfPqIndex:
    """Train coarse centers, rotation, codebooks; encode the dataset —
    ``ivf_pq::build`` (``detail/ivf_pq_build.cuh:1513-1723``).

    Examples
    --------
    >>> import numpy as np
    >>> from raft_tpu.neighbors import ivf_pq
    >>> x = np.random.default_rng(1).standard_normal(
    ...     (256, 8)).astype(np.float32)
    >>> idx = ivf_pq.build(
    ...     None, ivf_pq.IvfPqIndexParams(n_lists=4, pq_dim=4), x)
    >>> (idx.n_lists, idx.pq_dim, idx.size)
    (4, 4, 256)
    """
    res = ensure_resources(res)
    dataset = jnp.asarray(dataset)
    expect(dataset.ndim == 2, "dataset must be (n, d)")
    n, dim = dataset.shape
    expect(4 <= params.pq_bits <= 8, "pq_bits must be in [4, 8]")
    expect(params.n_lists <= n, "n_lists > n_rows")
    expect(
        params.metric in (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded,
                          DistanceType.InnerProduct),
        f"ivf_pq supports L2/L2Sqrt/InnerProduct, got {params.metric!r}",
    )
    pq_dim = params.pq_dim if params.pq_dim > 0 else _auto_pq_dim(dim)
    pq_len = -(-dim // pq_dim)                 # ceil
    dim_ext = pq_dim * pq_len

    with tracing.range("raft_tpu.ivf_pq.build"):
        frac = min(max(params.kmeans_trainset_fraction, 0.0), 1.0)
        # trainset must cover both the coarse clusters and the codebooks
        n_train = max(params.n_lists * 2, 1 << params.pq_bits, int(n * frac))
        n_train = min(n, n_train)
        stride = max(1, n // n_train)
        trainset = dataset[::stride][:n_train].astype(jnp.float32)

        km = KMeansBalancedParams(
            n_iters=params.kmeans_n_iters,
            metric=(DistanceType.InnerProduct
                    if params.metric == DistanceType.InnerProduct
                    else DistanceType.L2Expanded),
            seed=res.seed,
        )
        centers = kmeans_balanced.fit(res, km, trainset, params.n_lists)

        rotation = make_rotation_matrix(
            jax.random.fold_in(jax.random.key(res.seed), 7),
            dim_ext, dim,
            params.force_random_rotation or (dim != dim_ext),
        )

        # codebook training on rotated trainset residuals
        train_labels = kmeans_balanced.predict(res, km, centers, trainset)
        rot = _rotate_residuals(trainset, train_labels, centers, rotation)
        book_size = 1 << params.pq_bits
        key = jax.random.fold_in(jax.random.key(res.seed), 11)
        if params.codebook_kind == CodebookKind.PER_SUBSPACE:
            # at most _MAX_TRAIN_POINTS_PER_PQ_CODE rows per codeword
            # (the reference's max_train_points_per_pq_code): the
            # (pq_dim, rows, pq_len) trainset tiles its short pq_len
            # axis to 128 lanes on TPU, so the whole 1M-row trainset
            # would not fit HBM
            cap = _MAX_TRAIN_POINTS_PER_PQ_CODE * book_size
            if rot.shape[0] > cap:
                rot = rot[::rot.shape[0] // cap][:cap]
            sub = jnp.moveaxis(rot.reshape(-1, pq_dim, pq_len), 1, 0)
            codebooks = _vmapped_lloyd(sub, key, book_size, 25)
        else:
            # per cluster: train on that cluster's OWN subvectors (all
            # subspaces pooled); rows are drawn modulo the cluster's segment
            # length so no foreign-cluster residuals leak in
            per = max(book_size * 4 // pq_dim + 1, 64)
            order = jnp.argsort(train_labels, stable=True)
            sorted_lab = train_labels[order]
            firsts = jnp.searchsorted(sorted_lab, jnp.arange(params.n_lists))
            ends = jnp.append(firsts[1:], trainset.shape[0])
            seg_len = jnp.maximum(ends - firsts, 1)
            take = firsts[:, None] + (jnp.arange(per)[None, :] % seg_len[:, None])
            rows = rot[order][take]            # (n_lists, per, dim_ext)
            pooled = rows.reshape(params.n_lists, per * pq_dim, pq_len)
            codebooks = _vmapped_lloyd(pooled, key, book_size, 25)

        empty = IvfPqIndex(
            centers=centers,
            rotation=rotation,
            codebooks=codebooks,
            codes=jnp.zeros((params.n_lists, 0, pq_dim), jnp.uint8),
            indices=jnp.full((params.n_lists, 0), -1, jnp.int32),
            list_sizes=jnp.zeros((params.n_lists,), jnp.int32),
            metric=DistanceType(params.metric),
            codebook_kind=params.codebook_kind,
            pq_bits=params.pq_bits,
        )
        if not params.add_data_on_build:
            return empty
        return extend(res, empty, dataset, jnp.arange(n, dtype=jnp.int32))


def build_streaming(
    res: Optional[Resources],
    params: IvfPqIndexParams,
    source,
    chunk_rows: int = 1 << 20,
    train_rows: int = 1 << 18,
) -> IvfPqIndex:
    """Streamed PQ build over a :class:`raft_tpu.io.BinDataset` — the
    dataset never fully materializes host-side (role of the reference's
    managed-memory trainset spill, ``ivf_pq_build.cuh:1542-1554``).

    Passes: (1) strided trainset sample → centers + rotation +
    codebooks via the in-memory trainer; (2) per-chunk label predict +
    size count; (3) per-chunk encode + scatter into donated code
    buffers. Only the compressed codes live on device, so datasets many
    times HBM fit."""
    res = ensure_resources(res)
    expect(params.codebook_kind == CodebookKind.PER_SUBSPACE,
           "build_streaming supports PER_SUBSPACE codebooks")
    n, dim = source.n_rows, source.dim
    expect(params.n_lists <= n, "n_lists > n_rows")
    pq_dim = params.pq_dim if params.pq_dim > 0 else _auto_pq_dim(dim)
    pq_len = -(-dim // pq_dim)

    with tracing.range("raft_tpu.ivf_pq.build_streaming"):
        # -- pass 1: trainset sample → full training via build()
        train_rows = max(params.n_lists * 2, 1 << params.pq_bits,
                         min(train_rows, n))
        trainset = sample_trainset(source, train_rows, chunk_rows)
        empty = build(res, dataclasses.replace(params,
                                               add_data_on_build=False),
                      trainset)

        km = KMeansBalancedParams(
            metric=(DistanceType.InnerProduct
                    if params.metric == DistanceType.InnerProduct
                    else DistanceType.L2Expanded))

        # -- pass 2: labels + sizes
        labels_np, sizes_np = label_pass(res, km, empty.centers, source,
                                         chunk_rows, params.n_lists)
        max_size = padded_extent(sizes_np)

        # -- pass 3: encode + scatter with donated buffers. 2-D
        # (list, rank) indexing: flat slots would overflow int32 past
        # 2^31 total slots (the billion-row regime this path targets).
        @partial(jax.jit, donate_argnums=(0, 1))
        def encode_scatter(codes_buf, idx_buf, rows, labels, ids, ranks):
            rot = _rotate_residuals(rows, labels, empty.centers,
                                    empty.rotation)
            codes = _encode(rot, empty.codebooks, labels,
                            CodebookKind.PER_SUBSPACE, pq_dim, pq_len)
            return (codes_buf.at[labels, ranks].set(codes),
                    idx_buf.at[labels, ranks].set(ids))

        # graftledger capacity gate (opt-in): admit the streaming
        # path's padded code planes before they allocate (no norms
        # plane in the PQ layout; this path never nibble-packs)
        memwatch.admit(
            memwatch.packed_layout_bytes(params.n_lists, int(max_size),
                                         pq_dim, norms=False),
            "ivf_pq.build_streaming")
        codes_buf = jnp.zeros((params.n_lists, max_size, pq_dim), jnp.uint8)
        idx_buf = jnp.full((params.n_lists, max_size), -1, jnp.int32)
        fill = np.zeros((params.n_lists,), np.int64)
        for first, chunk in source.iter_chunks(chunk_rows):
            interruptible.yield_()  # cancellation point per chunk
            m = chunk.shape[0]
            lab = labels_np[first : first + m]
            ranks = streaming_ranks(lab, fill, params.n_lists)
            codes_buf, idx_buf = encode_scatter(
                codes_buf, idx_buf,
                jnp.asarray(chunk, jnp.float32),
                jnp.asarray(lab),
                jnp.asarray(first + np.arange(m, dtype=np.int32)),
                jnp.asarray(ranks),
            )

        return IvfPqIndex(
            centers=empty.centers,
            rotation=empty.rotation,
            codebooks=empty.codebooks,
            codes=codes_buf,
            indices=idx_buf,
            list_sizes=jnp.asarray(sizes_np, jnp.int32),
            metric=DistanceType(params.metric),
            codebook_kind=params.codebook_kind,
            pq_bits=params.pq_bits,
        )


def _scatter_codes_fn(codes, indices, new_codes, ids, list_ids, ranks):
    """Incremental ``extend`` scatter (see ivf_flat._scatter_extend_fn):
    new code rows land at the running fill ranks of their lists."""
    return (codes.at[list_ids, ranks].set(new_codes),
            indices.at[list_ids, ranks].set(ids))


_scatter_codes = jax.jit(_scatter_codes_fn)
_scatter_codes_donated = jax.jit(_scatter_codes_fn, donate_argnums=(0, 1))


def extend(
    res: Optional[Resources],
    index: IvfPqIndex,
    new_vectors,
    new_indices=None,
    donate: bool = False,
) -> IvfPqIndex:
    """Encode + add vectors — ``ivf_pq::extend``. Functional rebuild of the
    padded code planes. When the new rows fit the existing padding they
    are scattered incrementally (O(new), not O(total)); ``donate=True``
    additionally donates the old code planes to that scatter so the
    rebuild reuses their HBM in place (the old index object must not be
    used afterwards)."""
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

    with tracing.range("raft_tpu.ivf_pq.extend"):
        km = KMeansBalancedParams(
            metric=(DistanceType.InnerProduct
                    if index.metric == DistanceType.InnerProduct
                    else DistanceType.L2Expanded))
        labels = kmeans_balanced.predict(res, km, index.centers,
                                         new_vectors.astype(jnp.float32))
        rot = _rotate_residuals(new_vectors, labels, index.centers, index.rotation)
        new_codes = _encode(rot, index.codebooks, labels, index.codebook_kind,
                            index.pq_dim, index.pq_len)

        # -- incremental fast path: new codes fit the existing padding.
        # Slot assignment matches the full repack bit-for-bit.
        if index.max_list_size > 0:
            sizes_new = index.list_sizes + jax.ops.segment_sum(
                jnp.ones((n_new,), jnp.int32), labels,
                num_segments=index.n_lists)
            if padded_extent(sizes_new) <= index.max_list_size:
                lab_np = np.asarray(labels)
                fill = np.asarray(index.list_sizes).astype(np.int64)
                ranks = streaming_ranks(lab_np, fill, index.n_lists)
                rows = (_pack_nibbles(new_codes) if index.packed
                        else new_codes)
                scatter = _scatter_codes_donated if donate else _scatter_codes
                codes, indices = scatter(
                    index.codes, index.indices, rows, new_indices,
                    jnp.asarray(lab_np), jnp.asarray(ranks))
                return dataclasses.replace(index, codes=codes,
                                           indices=indices,
                                           list_sizes=sizes_new)

        if index.max_list_size > 0:
            stored = (_unpack_nibbles(index.codes) if index.packed
                      else index.codes)
            old_codes = stored.reshape(-1, index.pq_dim)
            old_ids = index.indices.reshape(-1)
            old_labels = jnp.repeat(jnp.arange(index.n_lists, dtype=jnp.int32),
                                    index.max_list_size)
            keep = old_ids >= 0
            all_codes = jnp.concatenate([old_codes[keep], new_codes])
            all_ids = jnp.concatenate([old_ids[keep], new_indices])
            all_labels = jnp.concatenate([old_labels[keep], labels])
        else:
            all_codes, all_ids, all_labels = new_codes, new_indices, labels

        sizes = jax.ops.segment_sum(
            jnp.ones((all_codes.shape[0],), jnp.int32), all_labels,
            num_segments=index.n_lists,
        )
        max_size = padded_extent(sizes)
        # graftledger capacity gate (opt-in): admit the padded code
        # planes host-side before the repack allocates them. The
        # repack always materializes UNPACKED (pq_dim-wide) planes;
        # a nibble-packed index then allocates the half-width copy
        # BEFORE the unpacked one frees — the transient peak is what
        # must fit, not the stored width. No norms plane in the PQ
        # layout.
        slot_width = index.pq_dim
        if index.pq_bits == 4 and index.pq_dim % 2 == 0:
            slot_width += index.pq_dim // 2
        memwatch.admit(
            memwatch.packed_layout_bytes(
                index.n_lists, int(max_size), slot_width, norms=False),
            "ivf_pq.extend")
        codes, indices, sizes = _pack_codes(all_codes, all_ids, all_labels,
                                            index.n_lists, max_size,
                                            sizes=sizes)
        should_pack = index.pq_bits == 4 and index.pq_dim % 2 == 0
        if should_pack:
            codes = _pack_nibbles(codes)
        return dataclasses.replace(index, codes=codes, indices=indices,
                                   list_sizes=sizes, packed=should_pack)


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------


def resolve_scan_engine(engine: str) -> str:
    """Resolve the PQ probe-scan formulation. ``auto`` is the
    list-major union scan on TPU (per-query list gathers bottleneck on
    the scalar core there) and the rank-major gather scan elsewhere.
    There is no Pallas PQ engine (yet) — see ARCHITECTURE.md "IVF scan
    engines" for the measured reasoning."""
    expect(engine in ("auto", "xla", "rank"),
           f"scan_engine must be auto|xla|rank, got {engine!r}")
    if engine == "auto":
        return "xla" if jax.default_backend() == "tpu" else "rank"
    return engine


def resolve_score_mode(score_mode: str, book_size: int = 256) -> str:
    """Resolve "auto" per backend: dynamic per-element gathers lower to
    the TPU scalar core (measured ~18x slower than the one-hot MXU
    contraction on v5e), while on CPU/GPU the direct gather wins. For
    small codebooks (pq_bits <= 5) the masked-sum "select" path beats
    the one-hot contraction on TPU — J compare/select/add VPU ops per
    element with no J-fold matmul inflation."""
    expect(score_mode in ("auto", "gather", "onehot", "select"),
           f"score_mode must be auto|gather|onehot|select, got {score_mode!r}")
    if score_mode == "auto":
        if jax.default_backend() == "tpu":
            return "select" if book_size <= 32 else "onehot"
        return "gather"
    return score_mode


def score_fn(score_mode: str, book_size: int = 256):
    """Resolve a score_mode string (incl. "auto") to its scoring
    function — the single place mapping modes to implementations."""
    mode = resolve_score_mode(score_mode, book_size)
    return {"onehot": _score_onehot, "gather": _score_gather,
            "select": _score_select}[mode]


def _score_gather(lut, rows):
    """dist contributions via per-element LUT gather —
    O(q·m·s) dynamic gathers (the GPU's shared-mem LUT access pattern)."""
    gathered = jnp.take_along_axis(
        lut[:, None, :, :],                            # (q, 1, s, J)
        rows.astype(jnp.int32)[:, :, :, None],         # (q, m, s, 1)
        axis=3,
    )[..., 0]                                          # (q, m, s)
    return jnp.sum(gathered.astype(jnp.float32), axis=2)


def _score_onehot(lut, rows):
    """dist contributions via one-hot × LUT MXU contraction: trades a
    J-fold FLOP inflation for gather-free systolic throughput — the
    profitable trade on TPU when q is small (the VPU executes XLA
    gathers element-at-a-time; the MXU does 256 MACs/cycle/lane).
    dist[q, m] = Σ_{s} lut[q, s, rows[q, m, s]].

    The LUT keeps its dtype (``lut_dtype``): bf16 LUTs get the native
    one-pass MXU path; f32 LUTs stay f32, with internal matmul
    precision governed by the platform default (wrap in
    ``jax.default_matmul_precision('float32')`` for full-width f32 on
    TPU). The one-hot operand is always bf16 — 0/1 are exact there, so
    it carries no rounding and the dominant (q, m, s, J) intermediate
    stays half-width; the only rounding is of the LUT entries
    themselves, and accumulation is always f32 via
    ``preferred_element_type``."""
    q, s, J = lut.shape
    # bf16/fp8 LUTs contract in bf16 (fp8 -> bf16 is exact; rounding
    # already happened at the lut_dtype cast); f32 stays f32
    ctype = (jnp.float32 if lut.dtype == jnp.float32 else jnp.bfloat16)
    oh = jax.nn.one_hot(rows.astype(jnp.int32), J,
                        dtype=jnp.bfloat16)            # (q, m, s, J)
    return jnp.einsum("qmsj,qsj->qm", oh,
                      lut.astype(ctype),
                      preferred_element_type=jnp.float32)


def _score_select(lut, rows):
    """dist contributions via a masked sum over codewords:
    ``acc[q, m, s] = Σ_j lut[q, s, j] · (rows[q, m, s] == j)`` — J
    unrolled compare/select/add terms, entirely elementwise so XLA
    fuses the whole chain (no per-element gathers, no one-hot
    materialization, no J-fold MXU FLOP inflation). The profitable
    TPU path for small codebooks (pq_bits <= 5)."""
    q, s, J = lut.shape
    expect(J <= 32, "score_mode='select' unrolls J terms — use "
           f"onehot/gather for book_size {J} > 32")
    lutf = lut.astype(jnp.float32)
    acc = jnp.zeros(rows.shape, jnp.float32)           # (q, m, s)
    for j in range(J):
        plane = lutf[:, :, j][:, None, :]              # (q, 1, s)
        acc = acc + jnp.where(rows == jnp.uint8(j), plane, 0.0)
    return jnp.sum(acc, axis=2)


def _probe_lut(qf, c, qsub_fixed, lut_fixed, rotation, codebooks, lists,
               ip_metric: bool, per_cluster: bool):
    """Per-probe LUT + base score — the LUT-build half of the reference's
    fused similarity kernel (``detail/ivf_pq_compute_similarity-inl.cuh:
    125-177``), shared by the single-chip and distributed search paths.

    ``qsub_fixed``/``lut_fixed`` are the probe-invariant precomputations
    (rotated query; and, for replicated-codebook IP, the full LUT).
    Returns ``(lut (q, pq_dim, book), base (q,))`` with
    ``score = sum_s lut[q, s, code] + base``.
    """
    q = qf.shape[0]
    pq_len = codebooks.shape[2]
    cb = jnp.take(codebooks, lists, axis=0) if per_cluster else codebooks
    if ip_metric:
        base = jnp.sum(qf * c, axis=1)
        lut = (jnp.einsum("qsl,qjl->qsj", qsub_fixed, cb) if per_cluster
               else lut_fixed)
    else:
        qsub = ((qf - c) @ rotation.T).reshape(q, -1, pq_len)
        base = jnp.zeros((q,), jnp.float32)
        if per_cluster:
            lut = (
                jnp.sum(jnp.square(qsub), -1)[:, :, None]
                - 2.0 * jnp.einsum("qsl,qjl->qsj", qsub, cb)
                + jnp.sum(jnp.square(cb), -1)[:, None, :]
            )
        else:
            lut = (
                jnp.sum(jnp.square(qsub), -1)[:, :, None]
                - 2.0 * jnp.einsum("qsl,sjl->qsj", qsub, cb)
                + jnp.sum(jnp.square(cb), -1)[None, :, :]
            )
    return lut, base


_FP8_DTYPES = tuple(
    getattr(jnp, name) for name in ("float8_e4m3fn", "float8_e5m2")
    if hasattr(jnp, name))
_FP8_MAX = {"float8_e4m3fn": 448.0, "float8_e5m2": 57344.0}


def quantize_lut(lut, lut_dtype):
    """Cast the per-probe LUT to ``lut_dtype`` — the reference's
    fp32/fp16/fp8 LUT ladder (``ivf_pq_compute_similarity-inl.cuh:125-177``).
    fp8's ±448 range can't hold raw squared-distance contributions, so
    (like the reference's fp8 path) entries are scaled per query into
    range; returns ``(lut, scale)`` where ``scale`` is ``(q, 1)`` to
    multiply back into the summed scores, or ``None`` when no scaling
    happened. Scaling is per *query*, not per subspace, so the
    Σ_s lut[q, s, code_s] accumulation stays a plain sum."""
    expect(lut_dtype in (jnp.float32, jnp.bfloat16) + _FP8_DTYPES,
           f"lut_dtype must be float32/bfloat16/float8, got {lut_dtype}")
    if lut_dtype in _FP8_DTYPES:
        fmax = _FP8_MAX[jnp.dtype(lut_dtype).name]
        scale = jnp.max(jnp.abs(lut), axis=(1, 2), keepdims=True) / fmax
        scale = jnp.maximum(scale, 1e-30)
        return (lut / scale).astype(lut_dtype), scale[:, :, 0]
    return lut.astype(lut_dtype), None


def _search_impl_fn(queries, centers, rotation, codebooks, codes, indices,
                    filter_words, init_d=None, init_i=None,
                    probe_counts=None, n_valid=None, row_probes=None,
                    cold_codes=None, hot_slot_map=None,
                    cold_slot_map=None, *,
                    n_probes: int, k: int, metric: DistanceType,
                    codebook_kind: CodebookKind, lut_dtype,
                    score_mode: str = "gather", packed: bool = False,
                    coarse_algo: str = "exact", scan_engine: str = "rank"):
    """ADC probe scan. ``init_d``/``init_i`` optionally provide the
    (q, k) running-state storage (values are reset here); the serving
    path donates them so the scan state reuses one HBM allocation.
    ``probe_counts`` optionally provides the donated (n_lists,) int32
    probe-frequency plane (graftgauge): selected probe ids scatter-add
    into it (rows past ``n_valid`` masked) and the updated plane
    returns as a third output — the results never read it.
    ``row_probes`` (the ragged front — see :func:`_search_ragged_fn`)
    optionally provides a packed batch's per-row probe budgets: the
    coarse stage selects at the class cap and masks each row's slots
    past its own budget to the sentinel id, which the list-major
    engine's membership predicate already rejects.

    ``scan_engine`` must arrive resolved (``rank``/``xla`` via
    :func:`resolve_scan_engine` — it is a jit static). ``rank`` scans
    probe ranks with per-query gathered code rows; ``xla`` scans the
    union of probed lists list-major (``ops/ivf_scan`` formulation):
    each unique list's code plane streams once, scores against every
    query in the tile, and a per-query membership predicate masks
    queries that did not probe it.

    ``cold_codes``/``hot_slot_map``/``cold_slot_map`` (graftcast —
    the tiered PQ cold engine) optionally split the codes plane:
    ``codes`` is then the HOT plane ``(n_hot, m, pq_dim)`` and each
    list-major step selects its block from its tier
    (:func:`raft_tpu.ops.tier_scan.tier_block_select`). Everything
    downstream of the fetch is THIS same body, so the tiered LUT
    union scan is bit-identical to the all-HBM scan by construction.
    List-major only: the rank-major gather has no per-list fetch
    step to steer (``resolve_tier_pq_engine`` rejects it)."""
    q, dim = queries.shape
    tiered_codes = cold_codes is not None
    assert not (tiered_codes and scan_engine == "rank"), \
        "tiered PQ codes need the list-major engine"
    # with a tiered codes plane, codes.shape[0] is the HOT slot count,
    # not the list count — the resident centers plane is the authority
    n_lists = centers.shape[0]
    max_size, pq_dim = codes.shape[1], codes.shape[2]
    if packed:
        pq_dim = pq_dim * 2
    book_size = codebooks.shape[1]
    pq_len = codebooks.shape[2]
    select_min = is_min_close(metric)
    qf = queries.astype(jnp.float32)

    # ---- coarse cluster selection (``select_clusters``,
    #      detail/ivf_pq_search.cuh:70-156)
    ip = jax.lax.dot_general(
        qf, centers, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    score = (ip if metric == DistanceType.InnerProduct
             else -(jnp.sum(jnp.square(centers), axis=1)[None, :] - 2.0 * ip))
    probes = coarse_select(score, n_probes, coarse_algo)
    if row_probes is not None:
        from raft_tpu.ops.ivf_scan import ragged_probes

        probes = ragged_probes(probes, row_probes, n_lists)
    if probe_counts is not None:
        from raft_tpu.ops.ivf_scan import probe_histogram

        probe_counts = probe_histogram(
            probes, probe_counts,
            None if row_probes is not None else n_valid)

    pad_val = jnp.inf if select_min else -jnp.inf

    # ---- probe-invariant precomputation (hoisted out of the scan)
    ip_query = metric == DistanceType.InnerProduct
    if ip_query:
        # score = q·y = q·c + (Rq)·ỹ — the rotated query never changes
        qsub_fixed = (qf @ rotation.T).reshape(q, pq_dim, pq_len)
        if codebook_kind == CodebookKind.PER_SUBSPACE:
            lut_fixed = jnp.einsum("qsl,sjl->qsj", qsub_fixed, codebooks)
        else:
            lut_fixed = None
    else:
        qsub_fixed = None
        lut_fixed = None

    # ---- shared per-probe scoring: LUT build + ADC code scan
    score = score_fn(score_mode, book_size)

    def probe_dist(lists, rows, row_ids):
        """(q,) list ids + unpacked (q, m, pq_dim) code rows + (q, m)
        ids -> masked (q, m) dist."""
        c = centers[lists]                             # (q, dim)
        lut, base = _probe_lut(
            qf, c, qsub_fixed, lut_fixed, rotation, codebooks, lists,
            ip_query, codebook_kind == CodebookKind.PER_CLUSTER)
        lut, lut_scale = quantize_lut(lut, lut_dtype)  # (q, pq_dim, J)
        # score codes: dist[q, m] = sum_s lut[q, s, rows[q, m, s]]
        dist = score(lut, rows)
        if lut_scale is not None:
            dist = dist * lut_scale
        dist = dist + base[:, None]
        dist = jnp.where(row_ids >= 0, dist, pad_val)
        if filter_words is not None:
            bits = test_filter(filter_words, row_ids)
            dist = jnp.where(bits & (row_ids >= 0), dist, pad_val)
        return dist

    if scan_engine != "rank":
        # list-major: scan the union of probed lists; one streamed
        # code plane per unique list scores the whole query tile. The
        # scan runs in min-space with the smallest-id tie-break merge
        # (shared with the ivf_flat engines), so exact ADC ties — easy
        # to hit after quantization — resolve deterministically and
        # independently of the list visitation order; IP negates back
        # after the scan (exact for floats).
        from raft_tpu.ops.ivf_scan import _merge_smallest_id, unique_lists

        def step(carry, lid):
            best_d, best_i = carry
            lidc = jnp.minimum(lid, n_lists - 1)       # sentinel-safe
            lists = jnp.full((q,), lidc, jnp.int32)
            if tiered_codes:
                from raft_tpu.ops.tier_scan import (
                    tier_block_select,
                    tier_slot_pair,
                )

                hs, cs = tier_slot_pair(hot_slot_map, cold_slot_map,
                                        lidc)
                rows1 = tier_block_select(codes, cold_codes, hs, cs)
            else:
                rows1 = jax.lax.dynamic_index_in_dim(codes, lidc, 0,
                                                     False)
            ids1 = jax.lax.dynamic_index_in_dim(indices, lidc, 0, False)
            if packed:
                rows1 = _unpack_nibbles(rows1)  # once, before broadcast
            rows = jnp.broadcast_to(rows1[None], (q,) + rows1.shape)
            row_ids = jnp.broadcast_to(ids1[None], (q, ids1.shape[0]))
            dist = probe_dist(lists, rows, row_ids)
            if not select_min:
                dist = -dist                           # to min-space
            # membership (sentinel steps — and sentinel-valued masked
            # probe slots — match nothing, as in ops/ivf_scan)
            probed = jnp.any(probes == lid, axis=1) & (lid < n_lists)
            dist = jnp.where(probed[:, None], dist, jnp.inf)
            return _merge_smallest_id(best_d, best_i, dist, row_ids,
                                      k), None

        init = (
            jnp.full((q, k), jnp.inf, jnp.float32) if init_d is None
            else jnp.full_like(init_d, jnp.inf),
            jnp.full((q, k), -1, jnp.int32) if init_i is None
            else jnp.full_like(init_i, -1),
        )
        (best_d, best_i), _ = jax.lax.scan(step, init,
                                           unique_lists(probes, n_lists))
        if not select_min:
            best_d = -best_d       # inf (unfilled) -> -inf, like rank
    else:

        def step(carry, rank):
            best_d, best_i = carry
            lists = probes[:, rank]                    # (q,)
            rows = jnp.take(codes, lists, axis=0)      # (q, m, pq_dim) u8
            if packed:
                # nibble-unpack right after the HBM gather — the
                # stream stays half-width end to end
                rows = _unpack_nibbles(rows)
            row_ids = jnp.take(indices, lists, axis=0)  # (q, m)
            dist = probe_dist(lists, rows, row_ids)
            new_d, new_i = merge_topk(best_d, best_i, dist, row_ids, k,
                                      select_min)
            return (new_d, new_i), None

        init = (
            jnp.full((q, k), pad_val, jnp.float32) if init_d is None
            else jnp.full_like(init_d, pad_val),
            jnp.full((q, k), -1, jnp.int32) if init_i is None
            else jnp.full_like(init_i, -1),
        )
        (best_d, best_i), _ = jax.lax.scan(step, init,
                                           jnp.arange(n_probes))

    if metric == DistanceType.L2SqrtExpanded:
        best_d = jnp.where(jnp.isfinite(best_d),
                           jnp.sqrt(jnp.maximum(best_d, 0.0)), best_d)
    if probe_counts is not None:
        return best_d, best_i, probe_counts
    return best_d, best_i


_search_impl = partial(jax.jit, static_argnames=(
    "n_probes", "k", "metric", "codebook_kind", "lut_dtype", "score_mode",
    "packed", "coarse_algo", "scan_engine"))(_search_impl_fn)


def _search_ragged_fn(queries, row_probes, centers, rotation, codebooks,
                      codes, indices, filter_words, init_d=None,
                      init_i=None, probe_counts=None, n_valid=None, *,
                      n_probes: int, k: int, metric: DistanceType,
                      codebook_kind: CodebookKind, lut_dtype,
                      score_mode: str = "gather", packed: bool = False,
                      scan_engine: str = "xla"):
    """Packed ragged-batch ADC search body — the PQ member of the
    serving executor's ragged plan family (see
    :func:`raft_tpu.neighbors.ivf_flat._search_ragged_fn` for the
    packing contract; this is the same wrapper over the same hook).
    ``n_probes``/``k`` are the packed batch's CLASS CAPS; per-row
    budgets ride ``row_probes`` into the list-major engine's
    membership mask, and each per-probe LUT depends only on its own
    (query row, list) pair, so a row's scores are independent of what
    else shares the tile — bit-identical per request to
    :func:`_search_impl_fn` on that request alone. Exact coarse
    select only (the prefix-property argument), list-major engine
    only (the rank-major scan has no membership mask)."""
    del n_valid
    expect(scan_engine == "xla",
           "ragged PQ serving needs the membership-masked list-major "
           f"engine ('xla'), got {scan_engine!r}")
    return _search_impl_fn(
        queries, centers, rotation, codebooks, codes, indices,
        filter_words, init_d, init_i, probe_counts, None,
        row_probes=row_probes, n_probes=n_probes, k=k, metric=metric,
        codebook_kind=codebook_kind, lut_dtype=lut_dtype,
        score_mode=score_mode, packed=packed, coarse_algo="exact",
        scan_engine=scan_engine)


def search(
    res: Optional[Resources],
    params: IvfPqSearchParams,
    index: IvfPqIndex,
    queries,
    k: int,
    sample_filter=None,
    query_tile: int = 4096,
) -> Tuple[jax.Array, jax.Array]:
    """ANN search — ``ivf_pq::search`` (``detail/ivf_pq_search.cuh:732``).
    Large query sets run in ``query_tile`` batches (the reference's
    max_queries=4096 loop, ``ivf_pq_search.cuh:790``).

    For L2 metrics the returned distances are approximate (residual-PQ)
    squared L2 (or sqrt thereof); use :func:`raft_tpu.neighbors.refine`
    to re-rank with exact distances, as the reference does."""
    ensure_resources(res)
    queries = jnp.asarray(queries)
    expect(queries.ndim == 2 and queries.shape[1] == index.dim,
           "queries must be (q, dim)")
    expect(index.max_list_size > 0, "index is empty — extend() it first")
    n_probes = min(params.n_probes, index.n_lists)
    expect(params.coarse_algo in ("exact", "approx"),
           f"coarse_algo must be 'exact' or 'approx', got "
           f"{params.coarse_algo!r}")
    expect(params.lut_dtype in (jnp.float32, jnp.bfloat16) + _FP8_DTYPES,
           f"lut_dtype must be float32/bfloat16/float8, got "
           f"{params.lut_dtype}")
    filter_words = resolve_filter_words(sample_filter)
    score_mode = resolve_score_mode(params.score_mode, index.pq_book_size)
    scan_engine = resolve_scan_engine(params.scan_engine)
    with tracing.range("raft_tpu.ivf_pq.search"):
        def run(qt, fw):
            return _search_impl(
                qt, index.centers, index.rotation, index.codebooks,
                index.codes, index.indices, fw,
                n_probes=n_probes, k=k, metric=index.metric,
                codebook_kind=index.codebook_kind,
                lut_dtype=params.lut_dtype, score_mode=score_mode,
                packed=index.packed, coarse_algo=params.coarse_algo,
                scan_engine=scan_engine,
            )

        return tile_queries(run, queries, filter_words, query_tile)


# ---------------------------------------------------------------------------
# serialization
# ---------------------------------------------------------------------------


def save(index: IvfPqIndex, fh_or_path) -> None:
    """``ivf_pq::serialize`` (``detail/ivf_pq_serialize.cuh:39``)."""
    fh, own = open_maybe_path(fh_or_path, "wb")
    try:
        serialize_scalar(fh, _SERIALIZATION_VERSION, np.int32)
        serialize_scalar(fh, int(index.packed), np.int32)
        serialize_scalar(fh, int(index.metric), np.int32)
        serialize_scalar(fh, int(index.codebook_kind), np.int32)
        serialize_scalar(fh, index.pq_bits, np.int32)
        serialize_array(fh, index.centers)
        serialize_array(fh, index.rotation)
        serialize_array(fh, index.codebooks)
        serialize_array(fh, index.codes)
        serialize_array(fh, index.indices)
        serialize_array(fh, index.list_sizes)
    finally:
        if own:
            fh.close()


def load(res: Optional[Resources], fh_or_path) -> IvfPqIndex:
    res = ensure_resources(res)
    fh, own = open_maybe_path(fh_or_path, "rb")
    try:
        check_version(deserialize_scalar(fh), _SERIALIZATION_VERSION, "ivf_pq")
        packed = bool(int(deserialize_scalar(fh)))
        metric = DistanceType(int(deserialize_scalar(fh)))
        kind = CodebookKind(int(deserialize_scalar(fh)))
        pq_bits = int(deserialize_scalar(fh))
        arrays = [res.put(deserialize_array(fh)) for _ in range(6)]
    finally:
        if own:
            fh.close()
    centers, rotation, codebooks, codes, indices, sizes = map(jnp.asarray, arrays)
    return IvfPqIndex(
        centers=centers, rotation=rotation, codebooks=codebooks,
        codes=codes, indices=indices, list_sizes=sizes,
        metric=metric, codebook_kind=kind, pq_bits=pq_bits, packed=packed,
    )
