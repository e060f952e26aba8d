"""IVF-BQ — inverted file with RaBitQ-grade binary quantization, a
TPU-first compression family (quantizer follows RaBitQ, arXiv
2405.12497, and the IVF-RaBitQ build in PAPERS.md: sign codes of the
per-vector residual under a pinned random rotation, with per-vector
scalar correction factors that make the distance estimator *unbiased*
and give it a *known per-candidate error bound*).

Why this exists on TPU: PQ scoring needs per-code LUT lookups — gathers
(scalar-core serialized) or one-hot/masked-sum workarounds (J-fold FLOP
inflation). A sign code has no lookup at all. The geometry-aware
construction (all in the rotated space, ``R`` orthonormal):

    r = x − c            (residual against the list centroid)
    s_l = sign(resid_l)  (level l encodes what levels < l left over)
    a_l = per-level scale, globally rescaled so ⟨r, Σ a_l s_l⟩ = ‖r‖²

stored per vector as the packed sign words plus three scalars:

    rnorm = ‖r‖          (residual norm)
    cfac_l = a_l / ‖r‖   (dimensionless code/residual alignment — for
                          one level this is 1/(√D·⟨r̂, û⟩), the
                          reciprocal code/residual inner product of
                          the RaBitQ estimator)
    errw = ‖r − Σ a_l s_l‖   (unexplained residual norm — the whole
                              error budget of the estimator)

The estimator  ‖q − x‖² ≈ ‖q − c‖² − 2·Σ_l a_l·⟨q̃, s_l⟩ + ‖r‖²
(``q̃ = R(q−c)``) is unbiased with per-candidate error
``2·⟨q̃, r − recon⟩``; under the random rotation the error's standard
deviation is ``≈ 2·‖q̃‖·errw/√D`` — a *measurable* quantity
(:func:`estimator_stats`), which is what retires the hand-calibrated
over-fetch constants (:func:`overfetch_budget`) and powers the fused
estimate-then-rerank scan (:mod:`raft_tpu.ops.bq_scan`): candidates
whose estimate minus the bound cannot beat the running k-th exact
distance are pruned *before* their raw vector is ever read.

Two search modes:

- **fused** (``scan_engine: auto|pallas|xla``, index built with
  ``store_vectors=True`` — the default): list-major scan that scores
  packed codes by XOR+popcount and re-ranks surviving rows against the
  raw vectors of the *same resident block* — returns **exact**
  distances, no separate ``refine`` pass needed.
- **estimate-only** (``scan_engine: "rank"``, or any index without the
  vector plane — e.g. a codes-only streaming build): today's
  rank-major estimate scan; over-fetch by :func:`overfetch_budget` and
  re-rank with :func:`raft_tpu.neighbors.refine`.

Supported metrics: L2Expanded / L2SqrtExpanded / InnerProduct.
"""

from __future__ import annotations

import dataclasses
import math
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

# v3: RaBitQ corrections (rnorm/cfac/errw), int32 sign words, optional
# raw-vector rerank plane
_SERIALIZATION_VERSION = 3

# entangled into the pinned rotation stream; bumping it redraws every
# rotation (and re-derives the estimator-quality expectations)
_ROTATION_STREAM = 0

# ONE calibration constant for the bound-derived over-fetch budgets —
# candidates displaced per unit of relative estimator error (measured
# once against the pinned rotation stream; replaces the three
# hand-calibrated constants 40/240/60 retired in this PR: derived
# budgets land at ~38 on the self-hit config, ~41 on the streamed
# 2-bit config, and k on every index carrying the rerank plane)
_OVERFETCH_KAPPA = 25.0


def _pinned_rotation(seed: int, dim_ext: int, dim: int) -> jax.Array:
    """Random orthogonal rotation dim → dim_ext from a **pinned**
    generator: numpy's PCG64 stream is stable across numpy versions,
    where ``jax.random`` draws shift across jax releases (threefry
    partitionable default, key layout). The estimator-quality contracts
    in ``tests/test_ivf_bq.py`` are calibrated against this exact
    stream — a jax upgrade must not silently redraw the rotation every
    saved BQ index and recall bound was derived under."""
    rng = np.random.default_rng(
        np.random.SeedSequence([seed, _ROTATION_STREAM]))
    g = rng.standard_normal((max(dim_ext, dim), dim_ext))
    q, r = np.linalg.qr(g)          # orthonormal columns
    # LAPACK backends disagree on QR column signs — normalize so the
    # rotation (not just the stream) is backend-invariant
    d = np.sign(np.diag(r))
    d[d == 0] = 1.0
    q = q * d
    return jnp.asarray(q[:dim, :].T, jnp.float32)  # (dim_ext, dim)


@dataclasses.dataclass(frozen=True)
class IvfBqIndexParams(IndexParams):
    n_lists: int = 1024
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    # residual sign-quantization levels (bits/dim, 1..4): level l
    # encodes the residual left by levels < l. Each level adds D bits
    # and one f32 scale per vector and one more popcount term to the
    # score; 2 bits roughly halves the estimator noise of 1 bit.
    bits: int = 1
    # keep the raw vectors in list layout next to the codes — the
    # rerank plane of the fused estimate-then-rerank scan. False =
    # codes-only (the many-times-HBM streaming regime): searches are
    # estimate-only and re-rank host-side via neighbors.refine.
    store_vectors: bool = True


@dataclasses.dataclass(frozen=True)
class IvfBqSearchParams(SearchParams):
    n_probes: int = 20
    # "approx" routes cluster selection through the TPU's native
    # approximate top-k unit (same knob as the flat/PQ params)
    coarse_algo: str = "exact"
    # probe-scan engine (ops/bq_scan): auto = fused Pallas kernel on
    # TPU / fused XLA scan elsewhere when the index carries the
    # rerank plane; "rank" = the legacy rank-major estimate-only scan
    scan_engine: str = "auto"    # "auto" | "pallas" | "xla" | "rank"
    # error-bound confidence multiplier for the fused prune (est −
    # epsilon·sigma must beat the running k-th exact distance to
    # trigger a re-rank): 3.0 covers ≥ 99% of estimator errors —
    # measured in tests/test_ivf_bq.py::TestEstimatorContract
    epsilon: float = 3.0
    # query-side quantization grid width for the popcount estimate
    # (RaBitQ's asymmetric query treatment). 0 resolves per code
    # ladder (raft_tpu.ops.bq_scan.auto_query_bits): 4 below 3 code
    # bits, 8 at bits >= 3 — where the code estimate is sharp enough
    # that the 4-bit query grid becomes the dominant noise source
    query_bits: int = 0


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class IvfBqIndex:
    """Binary-quantized IVF index (RaBitQ construction)."""

    centers: jax.Array        # (n_lists, dim) f32
    rotation: jax.Array       # (dim_ext, dim) f32 random orthogonal
    codes: jax.Array          # (n_lists, max_list_size, bits·D/32) i32
    rnorm: jax.Array          # (n_lists, max_list_size) f32 — ‖r‖
    cfac: jax.Array           # (n_lists, max_list_size, bits) f32
    errw: jax.Array           # (n_lists, max_list_size) f32 — ‖r−recon‖
    indices: jax.Array        # (n_lists, max_list_size) int32, -1 pad
    list_sizes: jax.Array     # (n_lists,) int32
    metric: DistanceType
    # optional rerank plane (store_vectors=True): raw vectors in list
    # layout + per-slot squared norms (+inf at padding, like ivf_flat)
    data: Optional[jax.Array] = None         # (n_lists, max, dim) f32
    data_norms: Optional[jax.Array] = None   # (n_lists, max) f32

    def tree_flatten(self):
        return (self.centers, self.rotation, self.codes, self.rnorm,
                self.cfac, self.errw, self.indices, self.list_sizes,
                self.data, self.data_norms), (self.metric,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children[:8], metric=aux[0], data=children[8],
                   data_norms=children[9])

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
    def max_list_size(self) -> int:
        return self.codes.shape[1]

    @property
    def size(self) -> int:
        return int(self.list_sizes.sum())


def _pack_words(signs):
    """(..., dim_ext) bool (sign >= 0) → (..., dim_ext // 32) int32
    sign words, bit b of word w = component 32w + b. int32 words (not
    the old uint8 bytes) so the fused kernel's XOR+popcount scoring
    runs on native VPU lanes."""
    d = signs.shape[-1]
    b = signs.reshape(*signs.shape[:-1], d // 32, 32).astype(jnp.int32)
    weights = jnp.left_shift(
        jnp.int32(1), jnp.arange(32, dtype=jnp.int32))
    return jnp.sum(b * weights, axis=-1, dtype=jnp.int32)


def _unpack_pm1(words, dtype=jnp.bfloat16):
    """(..., n_words) int32 → (..., 32·n_words) ±1 in ``dtype``."""
    bits = (words[..., None] >> jnp.arange(32, dtype=jnp.int32)) & 1
    pm1 = bits.astype(dtype) * 2 - 1
    return pm1.reshape(*words.shape[:-1], words.shape[-1] * 32)


def _encode(rot_residuals, bits: int = 1):
    """residual r → (packed sign words per level, ‖r‖, per-level
    dimensionless scales, unexplained-residual norm).

    Level 0 sign-encodes r with the least-squares scale ⟨r,s⟩/D; each
    further level encodes what the previous levels left over (residual
    sign quantization). A final global rescale γ = ‖r‖² / ⟨r, recon⟩
    is folded into every level's scale so that ⟨r, Σ a_l s_l⟩ = ‖r‖²
    EXACTLY — the collinearity correction of the RaBitQ estimator,
    which makes the distance estimate of a vector to itself 0 (with a
    single level a = ‖r‖²/⟨r, s⟩ = ‖r‖/(√D·⟨r̂, û⟩) — the
    reciprocal code/residual inner product). The stored scale is
    ``cfac_l = a_l/‖r‖``; ``errw = ‖r − γ·recon‖`` is the residual
    the code fails to explain — the estimator's entire error budget
    (per-candidate error std ≈ 2·‖q̃‖·errw/√D under the rotation).

    Returns codes (..., bits·D/32) i32, rnorm, cfac (..., bits),
    errw."""
    d = rot_residuals.shape[-1]
    rn2 = jnp.sum(jnp.square(rot_residuals), axis=-1)
    rnorm = jnp.sqrt(rn2)
    level_codes, level_scales = [], []
    resid = rot_residuals
    recon = jnp.zeros_like(rot_residuals)
    for _ in range(bits):
        signs = resid >= 0
        s = jnp.where(signs, 1.0, -1.0)
        a = jnp.sum(resid * s, axis=-1) / d           # LS scale per level
        level_codes.append(_pack_words(signs))
        level_scales.append(a)
        recon = recon + a[..., None] * s
        resid = resid - a[..., None] * s
    gamma = rn2 / jnp.maximum(
        jnp.sum(rot_residuals * recon, axis=-1), 1e-20)
    codes = jnp.concatenate(level_codes, axis=-1)
    scales = jnp.stack(level_scales, axis=-1) * gamma[..., None]
    errw = jnp.linalg.norm(
        rot_residuals - recon * gamma[..., None], axis=-1)
    cfac = scales / jnp.maximum(rnorm, 1e-20)[..., None]
    return (codes, rnorm.astype(jnp.float32), cfac.astype(jnp.float32),
            errw.astype(jnp.float32))


def _pack_lists(codes, rnorm, cfac, errw, ids, labels, n_lists,
                max_size, vectors=None, sizes=None):
    """Scatter rows into the padded [n_lists, max_list_size] layout
    (the shared sort-and-rank packing). ``vectors`` optionally rides
    along as the rerank plane."""
    payloads = [(codes, 0), (rnorm, 0.0), (cfac, 0.0), (errw, 0.0),
                (ids, -1)]
    if vectors is not None:
        payloads.append((vectors, 0.0))
    packed, sizes = pack_padded_lists(labels, n_lists, max_size,
                                      payloads, sizes=sizes)
    return packed, sizes


def _vector_norms(data, indices):
    """Per-slot squared norms, +inf at padding so padded slots never
    win the exact re-rank (the ivf_flat convention)."""
    norms = jnp.sum(jnp.square(data.astype(jnp.float32)), axis=2)
    return jnp.where(indices >= 0, norms, jnp.inf)


def build(
    res: Optional[Resources],
    params: IvfBqIndexParams,
    dataset,
) -> IvfBqIndex:
    """Train coarse centers + random rotation, RaBitQ-encode the
    dataset (and, by default, keep the raw vectors as the fused
    re-rank plane)."""
    res = ensure_resources(res)
    dataset = jnp.asarray(dataset)
    expect(dataset.ndim == 2, "dataset must be (n, d)")
    n, dim = dataset.shape
    expect(params.n_lists <= n, "n_lists > n_rows")
    expect(params.metric in (DistanceType.L2Expanded,
                             DistanceType.L2SqrtExpanded,
                             DistanceType.InnerProduct),
           f"ivf_bq supports L2/L2Sqrt/InnerProduct, got {params.metric!r}")
    expect(1 <= params.bits <= 4, "bits must be in [1, 4]")
    dim_ext = -(-dim // 32) * 32

    with tracing.range("raft_tpu.ivf_bq.build"):
        frac = min(max(params.kmeans_trainset_fraction, 0.0), 1.0)
        n_train = min(n, max(params.n_lists * 2, int(n * frac)))
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
        # the random rotation is what makes sign codes informative —
        # always random, never identity; pinned so recall contracts
        # survive jax upgrades
        rotation = _pinned_rotation(res.seed, dim_ext, dim)

        empty = IvfBqIndex(
            centers=centers, rotation=rotation,
            codes=jnp.zeros((params.n_lists, 0,
                             params.bits * dim_ext // 32), jnp.int32),
            rnorm=jnp.zeros((params.n_lists, 0), jnp.float32),
            cfac=jnp.zeros((params.n_lists, 0, params.bits),
                           jnp.float32),
            errw=jnp.zeros((params.n_lists, 0), jnp.float32),
            indices=jnp.full((params.n_lists, 0), -1, jnp.int32),
            list_sizes=jnp.zeros((params.n_lists,), jnp.int32),
            metric=DistanceType(params.metric),
            data=(jnp.zeros((params.n_lists, 0, dim), jnp.float32)
                  if params.store_vectors else None),
            data_norms=(jnp.zeros((params.n_lists, 0), jnp.float32)
                        if params.store_vectors else None),
        )
        if not params.add_data_on_build:
            return empty
        return extend(res, empty, dataset, jnp.arange(n, dtype=jnp.int32))


def build_streaming(
    res: Optional[Resources],
    params: IvfBqIndexParams,
    source,
    chunk_rows: int = 1 << 20,
    train_rows: int = 1 << 18,
) -> IvfBqIndex:
    """Streamed BQ build over a :class:`raft_tpu.io.BinDataset` — the
    dataset never fully materializes host-side (same three passes as
    the flat/PQ streaming builds: trainset sample → label count →
    encode + scatter into donated buffers). With
    ``store_vectors=False`` only the sign codes and per-vector scalars
    live in HBM, so datasets many times HBM fit (searches are then
    estimate-only — over-fetch by :func:`overfetch_budget` and refine
    host-side); the default keeps the rerank plane and streams the raw
    rows into it chunk-by-chunk."""
    res = ensure_resources(res)
    n, dim = source.n_rows, source.dim
    expect(params.n_lists <= n, "n_lists > n_rows")

    with tracing.range("raft_tpu.ivf_bq.build_streaming"):
        # -- pass 1: trainset sample → centers + rotation via build()
        train_rows = max(params.n_lists * 2, min(train_rows, n))
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

        # -- pass 3: encode + scatter with donated buffers (the code
        # planes and, when kept, the rerank plane each thread through
        # their own donated scatter — state = step(state) discipline)
        @partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
        def encode_scatter(codes_buf, rn_buf, cf_buf, ew_buf, idx_buf,
                           rows, labels, ids, ranks):
            resid = rows - empty.centers[labels]
            rot = resid @ empty.rotation.T
            codes, rnorm, cfac, errw = _encode(rot, params.bits)
            return (codes_buf.at[labels, ranks].set(codes),
                    rn_buf.at[labels, ranks].set(rnorm),
                    cf_buf.at[labels, ranks].set(cfac),
                    ew_buf.at[labels, ranks].set(errw),
                    idx_buf.at[labels, ranks].set(ids))

        @partial(jax.jit, donate_argnums=(0,))
        def scatter_rows(data_buf, rows, labels, ranks):
            return data_buf.at[labels, ranks].set(rows)

        dim_ext = empty.dim_ext
        # graftledger capacity gate (opt-in): one slot = packed words
        # + the three correction scalars + the id plane (+ the raw
        # vector when the rerank plane streams too) — the same slot
        # model the extend gate admits against
        slot = (params.bits * dim_ext // 32) * 4 + 4 + params.bits * 4 \
            + 4 + 4
        if params.store_vectors:
            # raw vector plane + the f32 data_norms plane the
            # store_vectors epilog materializes (_vector_norms)
            slot += dim * 4 + 4
        memwatch.admit(params.n_lists * int(max_size) * slot,
                       "ivf_bq.build_streaming")
        codes_buf = jnp.zeros(
            (params.n_lists, max_size, params.bits * dim_ext // 32),
            jnp.int32)
        rn_buf = jnp.zeros((params.n_lists, max_size), jnp.float32)
        cf_buf = jnp.zeros((params.n_lists, max_size, params.bits),
                           jnp.float32)
        ew_buf = jnp.zeros((params.n_lists, max_size), jnp.float32)
        idx_buf = jnp.full((params.n_lists, max_size), -1, jnp.int32)
        data_buf = (jnp.zeros((params.n_lists, max_size, dim),
                              jnp.float32)
                    if params.store_vectors else None)
        fill = np.zeros((params.n_lists,), np.int64)
        for first, chunk in source.iter_chunks(chunk_rows):
            interruptible.yield_()  # cancellation point per chunk
            m = chunk.shape[0]
            lab = labels_np[first : first + m]
            ranks = streaming_ranks(lab, fill, params.n_lists)
            rows = jnp.asarray(chunk, jnp.float32)
            lab_d = jnp.asarray(lab)
            ranks_d = jnp.asarray(ranks)
            codes_buf, rn_buf, cf_buf, ew_buf, idx_buf = encode_scatter(
                codes_buf, rn_buf, cf_buf, ew_buf, idx_buf, rows, lab_d,
                jnp.asarray(first + np.arange(m, dtype=np.int32)),
                ranks_d,
            )
            if params.store_vectors:
                data_buf = scatter_rows(data_buf, rows, lab_d, ranks_d)

        return IvfBqIndex(
            centers=empty.centers,
            rotation=empty.rotation,
            codes=codes_buf,
            rnorm=rn_buf,
            cfac=cf_buf,
            errw=ew_buf,
            indices=idx_buf,
            list_sizes=jnp.asarray(sizes_np, jnp.int32),
            metric=DistanceType(params.metric),
            data=data_buf,
            data_norms=(_vector_norms(data_buf, idx_buf)
                        if params.store_vectors else None),
        )


def extend(
    res: Optional[Resources],
    index: IvfBqIndex,
    new_vectors,
    new_indices=None,
) -> IvfBqIndex:
    """Encode + add vectors (functional rebuild of the padded lists)."""
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

    with tracing.range("raft_tpu.ivf_bq.extend"):
        km = KMeansBalancedParams(
            metric=(DistanceType.InnerProduct
                    if index.metric == DistanceType.InnerProduct
                    else DistanceType.L2Expanded))
        labels = kmeans_balanced.predict(res, km, index.centers,
                                         new_vectors.astype(jnp.float32))
        newf = new_vectors.astype(jnp.float32)
        resid = newf - index.centers[labels]
        rot = resid @ index.rotation.T                   # (n, dim_ext)
        codes, rnorm, cfac, errw = _encode(rot, index.bits)
        with_vectors = index.data is not None

        if index.max_list_size > 0:
            keep = index.indices.reshape(-1) >= 0
            old_labels = jnp.repeat(
                jnp.arange(index.n_lists, dtype=jnp.int32),
                index.max_list_size)
            nw = index.codes.shape[2]
            all_codes = jnp.concatenate(
                [index.codes.reshape(-1, nw)[keep], codes])
            all_rn = jnp.concatenate(
                [index.rnorm.reshape(-1)[keep], rnorm])
            all_cf = jnp.concatenate(
                [index.cfac.reshape(-1, index.bits)[keep], cfac])
            all_ew = jnp.concatenate(
                [index.errw.reshape(-1)[keep], errw])
            all_ids = jnp.concatenate(
                [index.indices.reshape(-1)[keep], new_indices])
            all_labels = jnp.concatenate([old_labels[keep], labels])
            all_vecs = None
            if with_vectors:
                all_vecs = jnp.concatenate(
                    [index.data.reshape(-1, index.dim)[keep], newf])
        else:
            all_codes, all_rn, all_cf, all_ew = codes, rnorm, cfac, errw
            all_ids, all_labels = new_indices, labels
            all_vecs = newf if with_vectors else None

        sizes = jax.ops.segment_sum(
            jnp.ones((all_codes.shape[0],), jnp.int32), all_labels,
            num_segments=index.n_lists)
        max_size = padded_extent(sizes)
        # graftledger capacity gate (opt-in): one slot carries the
        # packed sign words (i32), the three correction scalars
        # (rnorm + per-level cfac + errw, f32), the id plane, and —
        # with the rerank plane — the raw f32 vector + its norm
        slot = (all_codes.shape[1] * 4 + 4 + index.bits * 4 + 4 + 4)
        if with_vectors:
            slot += index.dim * 4 + 4
        memwatch.admit(index.n_lists * int(max_size) * slot,
                       "ivf_bq.extend")
        packed, sizes = _pack_lists(all_codes, all_rn, all_cf, all_ew,
                                    all_ids, all_labels, index.n_lists,
                                    max_size, vectors=all_vecs,
                                    sizes=sizes)
        c, rn, cf, ew, ids = packed[:5]
        data = packed[5] if with_vectors else None
        return dataclasses.replace(
            index, codes=c, rnorm=rn, cfac=cf, errw=ew, indices=ids,
            list_sizes=sizes, data=data,
            data_norms=(_vector_norms(data, ids) if with_vectors
                        else None))


# ---------------------------------------------------------------------------
# estimator statistics and bound-derived over-fetch budgets
# ---------------------------------------------------------------------------


def estimator_stats(index) -> dict:
    """Measured estimator-error statistics of one (shard of an) index
    — the quantities the bound-derived budgets consume. ONE small
    device fetch; build/plan-time only, never on the dispatch path.

    - ``mean_errw``: mean unexplained-residual norm ‖r − recon‖
    - ``mean_rnorm2``: mean squared residual norm (the distance scale)
    - ``rel_err``: 2·mean_errw / (√D · √mean_rnorm2) — the
      per-candidate distance-error std over the distance scale, the
      dimensionless knob every budget below is monotone in
    """
    ids = index.indices
    valid = (ids >= 0).astype(jnp.float32)
    cnt = jnp.maximum(jnp.sum(valid), 1.0)
    mean_e = jnp.sum(index.errw * valid) / cnt
    mean_rn2 = jnp.sum(jnp.square(index.rnorm) * valid) / cnt
    mean_e, mean_rn2 = jax.device_get((mean_e, mean_rn2))
    mean_e = float(mean_e)
    mean_rn2 = float(mean_rn2)
    rel = (2.0 * mean_e / (math.sqrt(index.dim_ext)
                           * math.sqrt(max(mean_rn2, 1e-20)))
           if mean_rn2 > 0 else 0.0)
    return {"mean_errw": mean_e, "mean_rnorm2": mean_rn2,
            "rel_err": rel, "dim_ext": index.dim_ext}


def overfetch_budget(index, k: int, *, confidence: float = 1.0,
                     query_bits: int = 4) -> int:
    """Bound-derived candidate budget for the estimate-only path: how
    many estimate-ranked candidates to fetch so the true top-k survive
    the exact re-rank (:func:`raft_tpu.neighbors.refine`).

    ``budget = ceil(k · (1 + confidence·κ_eff·ρ))`` where ``ρ`` is the
    index's measured relative estimator error
    (:func:`estimator_stats`) and ``κ_eff`` scales the one calibration
    constant ``_OVERFETCH_KAPPA`` (displacement per unit relative
    error, measured against the pinned rotation stream at the 4-bit
    query grid) by the query grid actually searched with:
    ``κ_eff = κ·(2^(4−query_bits) + 1)/2`` — the quantization noise
    term halves per extra query bit while the rotation term stays, so
    the identity holds at ``query_bits=4`` and an 8-bit grid
    (``auto_query_bits`` at a bits≥3 ladder) buys ~47% less
    over-fetch. Replaces the three hand-calibrated constants
    (self-hit 40, sharded merge 240, streamed-bits2 60;
    ``tests/test_ivf_bq.py`` pins derived ≤ old at equal recall
    targets, and pins the ladder's monotone budget drop). An index
    carrying the rerank plane needs no over-fetch at all: the fused
    scan already returns exact distances, so the budget is ``k``."""
    expect(k >= 1, "k must be >= 1")
    expect(1 <= query_bits <= 8,
           f"query_bits must be 1..8, got {query_bits}")
    if index.data is not None:
        return k
    stats = estimator_stats(index)
    kappa_eff = _OVERFETCH_KAPPA * (2.0 ** (4 - query_bits) + 1.0) / 2.0
    budget = math.ceil(
        k * (1.0 + confidence * kappa_eff * stats["rel_err"]))
    return max(k, min(budget, index.size))


def estimator_margin(qc_norm, rnorm, errw, delta, dim_ext: int,
                     epsilon: float):
    """Per-candidate distance-error bound at confidence ``epsilon``
    (the fused prune's margin; shared with the engines in
    :mod:`raft_tpu.ops.bq_scan` and the estimator-contract tests).

    Two independent noise sources add in quadrature: the rotation
    part (the unexplained residual ``errw`` projected on the query
    direction — std ``‖q̃‖·errw/√D`` under the random rotation) and
    the query-quantization part (uniform rounding noise of width
    ``delta`` against the reconstruction, whose squared norm is
    ``rnorm² + errw²`` by the collinearity rescale). The factor 2 is
    the cross term's weight in the squared-distance estimator."""
    recon2 = jnp.square(rnorm) + jnp.square(errw)
    return 2.0 * epsilon * jnp.sqrt(
        jnp.square(qc_norm * errw) / dim_ext
        + jnp.square(delta) * recon2 / 12.0)


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------


def score_probe(lists, qrot, centers_rot, ip, cn, qnorm, codes, rnorm,
                cfac, indices, ip_metric: bool, pad_val, valid=None):
    """THE per-probe scoring step of the rank-major estimate-only
    engine, shared by the single-chip and distributed searches: gather
    one probed list per query, unpack the sign words, one MXU GEMM
    cross term, estimator assembly. Rows that are padding (or,
    distributed, probes this shard does not own via ``valid``) score
    ``pad_val``. Returns ``(dist (q, m), row_ids)``.

    Inputs are the probe-invariant precomputations: ``qrot = R q``,
    ``centers_rot = R c`` (L2 only), the coarse-stage ``ip = q·c``
    matrix and norms (L2 only)."""
    q = qrot.shape[0]
    qidx = jnp.arange(q)
    words = jnp.take(codes, lists, axis=0)       # (q, m, bits·D/32)
    cf = jnp.take(cfac, lists, axis=0)           # (q, m, bits)
    rn = jnp.take(rnorm, lists, axis=0)          # (q, m)
    bits = cf.shape[-1]
    a = rn[..., None] * cf                       # per-level scales
    pm1 = _unpack_pm1(words)                     # (q, m, bits·D) bf16
    m = pm1.shape[1]
    pm1 = pm1.reshape(q, m, bits, -1)            # (q, m, L, D)
    row_ids = jnp.take(indices, lists, axis=0)   # (q, m)
    if ip_metric:
        # similarity (select_min is False for IP — no negation)
        crosses = jnp.einsum("qd,qmld->qml", qrot.astype(jnp.bfloat16),
                             pm1, preferred_element_type=jnp.float32)
        base = ip[qidx, lists]                   # q·c from coarse
        dist = base[:, None] + jnp.sum(a * crosses, axis=-1)
    else:
        qsub = qrot - centers_rot[lists]         # (q, dim_ext)
        crosses = jnp.einsum("qd,qmld->qml", qsub.astype(jnp.bfloat16),
                             pm1, preferred_element_type=jnp.float32)
        r2 = jnp.square(rn)
        # ||q−c||² from the coarse-stage terms (R is an isometry, so
        # this equals Σ qsub² without re-reducing per probe)
        qc2 = qnorm + cn[lists] - 2.0 * ip[qidx, lists]
        dist = (jnp.maximum(qc2, 0.0)[:, None]
                - 2.0 * jnp.sum(a * crosses, axis=-1) + r2)
    ok = row_ids >= 0
    if valid is not None:
        ok = ok & valid[:, None]
    return jnp.where(ok, dist, pad_val), row_ids


def _search_impl_fn(queries, centers, rotation, codes, rnorm, cfac,
                    errw, indices, data, data_norms, filter_words,
                    probe_counts=None, n_valid=None, row_probes=None,
                    cold_planes=None,
                    hot_slot_map=None, cold_slot_map=None, *,
                    n_probes: int,
                    k: int, metric: DistanceType,
                    coarse_algo: str = "exact",
                    scan_engine: str = "rank", epsilon: float = 3.0,
                    query_bits: int = 0):
    """BQ probe scan: coarse select, then either the fused
    estimate-then-rerank list-major engines (``pallas``/``xla`` —
    :mod:`raft_tpu.ops.bq_scan`, exact output distances) or the legacy
    rank-major estimate-only scan (``rank``). ``probe_counts``
    optionally provides the donated (n_lists,) int32 probe-frequency
    plane (graftgauge): selected probe ids scatter-add into it (rows
    past ``n_valid`` masked) and the updated plane returns as a third
    output. ``row_probes`` (the ragged front — see
    :func:`_search_ragged_fn`) optionally provides a packed batch's
    per-row probe budgets: the coarse stage selects at the class cap
    and masks each row's slots past its own budget to the sentinel id,
    which the fused engines' membership predicate already rejects.
    ``scan_engine`` must arrive resolved (via
    :func:`raft_tpu.ops.bq_scan.resolve_bq_engine`): it is a jit
    static, so an unresolved ``"auto"`` would fork the compile cache.
    ``cold_planes`` (graftcast) optionally carries the cold halves of
    the five per-row record planes — ``codes``/``rnorm``/``cfac``/
    ``errw``/``data`` are then the HOT halves and the fused XLA
    engine selects each list's planes from one tier through
    ``(hot_slot_map, cold_slot_map)`` (same body, same estimates,
    same prune decisions ⇒ bit-identical to all-HBM)."""
    q, dim = queries.shape
    if cold_planes is not None:
        assert scan_engine == "xla", \
            "tiered BQ record planes need the fused XLA engine"
    select_min = is_min_close(metric)
    qf = queries.astype(jnp.float32)
    ip_metric = metric == DistanceType.InnerProduct

    # coarse cluster selection (shared shape with ivf_flat/pq)
    ip = jax.lax.dot_general(
        qf, centers, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    if ip_metric:
        score = ip
        c_norms = None
        qnorm = None
    else:
        c_norms = jnp.sum(jnp.square(centers), axis=1)
        score = -(c_norms[None, :] - 2.0 * ip)
        qnorm = jnp.sum(jnp.square(qf), axis=1)
    probes = coarse_select(score, n_probes, coarse_algo)
    if row_probes is not None:
        from raft_tpu.ops.ivf_scan import ragged_probes

        probes = ragged_probes(probes, row_probes, centers.shape[0])
    if probe_counts is not None:
        from raft_tpu.ops.ivf_scan import probe_histogram

        probe_counts = probe_histogram(
            probes, probe_counts,
            None if row_probes is not None else n_valid)
    pad_val = jnp.inf if select_min else -jnp.inf

    # probe-invariant precomputation: the rotated query never changes,
    # and q̃ = R(q−c) = Rq − (Rc) needs only a rotated-centers table
    qrot = qf @ rotation.T                             # (q, dim_ext)
    centers_rot = centers @ rotation.T

    if scan_engine != "rank":
        # fused estimate-then-rerank (ops/bq_scan): stream each unique
        # probed list's codes once, XOR+popcount estimates, exact f32
        # re-rank of surviving rows from the same resident block
        from raft_tpu.ops.bq_scan import auto_query_bits, bq_list_major_scan

        qb = query_bits if query_bits else auto_query_bits(
            int(cfac.shape[2]))
        best_d, best_i = bq_list_major_scan(
            qf, qrot, centers_rot, codes, rnorm, cfac, errw, indices,
            data, data_norms, probes, filter_words, cold_planes,
            hot_slot_map, cold_slot_map,
            k=k, metric=metric, epsilon=epsilon, engine=scan_engine,
            query_bits=qb, interpret=jax.default_backend() != "tpu")
    else:
        def step(carry, rank):
            best_d, best_i = carry
            lists = probes[:, rank]                    # (q,)
            dist, row_ids = score_probe(
                lists, qrot, None if ip_metric else centers_rot, ip,
                c_norms, qnorm, codes, rnorm, cfac, indices, ip_metric,
                pad_val)
            if filter_words is not None:
                bits = test_filter(filter_words, row_ids)
                dist = jnp.where(bits & (row_ids >= 0), dist, pad_val)
            return merge_topk(best_d, best_i, dist, row_ids, k,
                              select_min), None

        init = (jnp.full((q, k), pad_val, jnp.float32),
                jnp.full((q, k), -1, jnp.int32))
        (best_d, best_i), _ = jax.lax.scan(step, init,
                                           jnp.arange(n_probes))

    if metric == DistanceType.L2SqrtExpanded:
        best_d = jnp.where(jnp.isfinite(best_d),
                           jnp.sqrt(jnp.maximum(best_d, 0.0)), best_d)
    if probe_counts is not None:
        return best_d, best_i, probe_counts
    return best_d, best_i


_search_impl = partial(jax.jit, static_argnames=(
    "n_probes", "k", "metric", "coarse_algo", "scan_engine",
    "epsilon", "query_bits"))(_search_impl_fn)


def _search_ragged_fn(queries, row_probes, centers, rotation, codes,
                      rnorm, cfac, errw, indices, data, data_norms,
                      filter_words, probe_counts=None, n_valid=None, *,
                      n_probes: int,
                      k: int, metric: DistanceType,
                      scan_engine: str = "xla", epsilon: float = 3.0,
                      query_bits: int = 0):
    """Packed ragged-batch BQ search body — the BQ member of the
    serving executor's ragged plan family (see
    :func:`raft_tpu.neighbors.ivf_flat._search_ragged_fn` for the
    packing contract). ``n_probes``/``k`` are the packed batch's
    CLASS CAPS; per-row budgets ride ``row_probes`` into the fused
    estimate-then-rerank engines' membership mask (the sentinel
    machinery the list-sharded BQ bodies already use for not-owned
    probes), and the running k-th-distance prune threshold is
    per-row, so a row's prune decisions — and its exact reranked
    output — are independent of what else shares the tile.
    Bit-identical per request to :func:`_search_impl_fn` on that
    request alone. Fused engines only: the rank-major estimate-only
    scan has no membership mask (and a codes-only index resolves to
    it, so codes-only BQ stays on the bucketed path)."""
    del n_valid
    expect(scan_engine in ("pallas", "xla"),
           "ragged BQ serving needs a fused membership-masked engine "
           f"(pallas|xla), got {scan_engine!r}")
    return _search_impl_fn(
        queries, centers, rotation, codes, rnorm, cfac, errw, indices,
        data, data_norms, filter_words, probe_counts, None,
        row_probes=row_probes, n_probes=n_probes, k=k,
        metric=metric, coarse_algo="exact", scan_engine=scan_engine,
        epsilon=epsilon, query_bits=query_bits)


def search(
    res: Optional[Resources],
    params: IvfBqSearchParams,
    index: IvfBqIndex,
    queries,
    k: int,
    sample_filter=None,
    query_tile: int = 4096,
) -> Tuple[jax.Array, jax.Array]:
    """ANN search over RaBitQ codes. With the fused engines (the
    default on an index carrying the rerank plane) the returned
    distances are **exact** — estimate-then-rerank happens inside one
    list-major pass, no separate :func:`raft_tpu.neighbors.refine`
    needed. On a codes-only index (or ``scan_engine="rank"``) the
    distances are unbiased estimates: over-fetch by
    :func:`overfetch_budget` and refine host-side."""
    ensure_resources(res)
    queries = jnp.asarray(queries)
    expect(queries.ndim == 2 and queries.shape[1] == index.dim,
           "queries must be (q, dim)")
    expect(index.max_list_size > 0, "index is empty — extend() it first")
    n_probes = min(params.n_probes, index.n_lists)
    expect(params.coarse_algo in ("exact", "approx"),
           f"coarse_algo must be 'exact' or 'approx', got "
           f"{params.coarse_algo!r}")
    expect(params.query_bits == 0 or 1 <= params.query_bits <= 8,
           "query_bits must be 0 (auto) or 1..8, got "
           f"{params.query_bits}")
    filter_words = resolve_filter_words(sample_filter)
    from raft_tpu.ops.bq_scan import resolve_bq_engine

    scan_engine = resolve_bq_engine(
        params.scan_engine, data=index.data, filter_words=filter_words,
        k=k, dim_ext=index.dim_ext, bits=index.bits,
        n_probes=n_probes)
    with tracing.range("raft_tpu.ivf_bq.search"):
        def run(qt, fw):
            return _search_impl(
                qt, index.centers, index.rotation, index.codes,
                index.rnorm, index.cfac, index.errw, index.indices,
                index.data, index.data_norms, fw,
                n_probes=n_probes, k=k, metric=index.metric,
                coarse_algo=params.coarse_algo, scan_engine=scan_engine,
                epsilon=params.epsilon, query_bits=params.query_bits)

        return tile_queries(run, queries, filter_words, query_tile)


def save(index: IvfBqIndex, fh_or_path) -> None:
    fh, own = open_maybe_path(fh_or_path, "wb")
    try:
        serialize_scalar(fh, _SERIALIZATION_VERSION, np.int32)
        serialize_scalar(fh, int(index.metric), np.int32)
        serialize_scalar(fh, index.bits, np.int32)
        serialize_scalar(fh, int(index.data is not None), np.int32)
        serialize_array(fh, index.centers)
        serialize_array(fh, index.rotation)
        serialize_array(fh, index.codes)
        serialize_array(fh, index.rnorm)
        serialize_array(fh, index.cfac)
        serialize_array(fh, index.errw)
        serialize_array(fh, index.indices)
        serialize_array(fh, index.list_sizes)
        if index.data is not None:
            serialize_array(fh, index.data)
    finally:
        if own:
            fh.close()


def load(res: Optional[Resources], fh_or_path) -> IvfBqIndex:
    res = ensure_resources(res)
    fh, own = open_maybe_path(fh_or_path, "rb")
    try:
        check_version(deserialize_scalar(fh), _SERIALIZATION_VERSION,
                      "ivf_bq")
        metric = DistanceType(int(deserialize_scalar(fh)))
        int(deserialize_scalar(fh))  # bits — recorded; shape-derivable
        has_data = bool(deserialize_scalar(fh))
        arrays = [res.put(deserialize_array(fh)) for _ in range(8)]
        data = res.put(deserialize_array(fh)) if has_data else None
    finally:
        if own:
            fh.close()
    (centers, rotation, codes, rnorm, cfac, errw, indices,
     sizes) = map(jnp.asarray, arrays)
    data = jnp.asarray(data) if has_data else None
    return IvfBqIndex(
        centers=centers, rotation=rotation, codes=codes, rnorm=rnorm,
        cfac=cfac, errw=errw, indices=indices, list_sizes=sizes,
        metric=metric, data=data,
        data_norms=_vector_norms(data, indices) if has_data else None,
    )
