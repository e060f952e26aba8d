"""Fused distance + top-k Pallas kernels — the TPU re-design of the
reference's two hottest kernels:

- ``fusedL2kNN`` (``spatial/knn/detail/fused_l2_knn-inl.cuh:198``): exact
  kNN that never materializes the (q, n) distance matrix. The CUDA
  version keeps a warp-level register top-k; here a VMEM-resident
  (q, k) running state persists across a 1-D grid over database tiles —
  each step does one MXU contraction (the distance core) and a VPU
  extract-min merge, so the dataset streams through HBM exactly once.

- ``matrix::select_k`` (``matrix/detail/select_radix.cuh``,
  ``select_warpsort.cuh``): batched k-selection over a wide matrix,
  expressed as the same tiled merge without the distance core.

The merge primitive is k rounds of (min, first-argmin, mask) over the
lane axis — O(k·tile) VPU work per tile, negligible next to the O(d·tile)
MXU distance work, and free of gathers/sorts that Mosaic lowers poorly.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.core.chips import vmem_budget_mb
from raft_tpu.core.validation import expect
from raft_tpu.distance.types import DistanceType

_SUPPORTED_METRICS = (
    DistanceType.L2Expanded,
    DistanceType.L2SqrtExpanded,
    DistanceType.L2Unexpanded,
    DistanceType.L2SqrtUnexpanded,
    DistanceType.InnerProduct,
    DistanceType.CosineExpanded,
)


def _extract_topk(dist, ids, k: int):
    """k smallest of (q, m) with smallest-id tie-break, by k rounds of
    min / min-id / mask — the in-register merge network of the
    reference's warp-sort restated for the VPU (min reductions only:
    Mosaic has no cumsum/sort lowering)."""
    big = jnp.int32(jnp.iinfo(jnp.int32).max)
    outs_d, outs_i = [], []
    for _ in range(k):
        m = jnp.min(dist, axis=1, keepdims=True)                 # (q, 1)
        is_min = dist == m
        idx = jnp.min(jnp.where(is_min, ids, big), axis=1, keepdims=True)
        outs_d.append(m)
        outs_i.append(jnp.where(jnp.isfinite(m), idx, -1))
        dist = jnp.where(is_min & (ids == idx), jnp.inf, dist)
    return (jnp.concatenate(outs_d, axis=1),
            jnp.concatenate(outs_i, axis=1))


def _knn_kernel(q_ref, qn_ref, x_ref, xn_ref, outd_ref, outi_ref,
                bestd, besti, *, k: int, n: int, tile: int,
                steps: int, metric: DistanceType):
    # position within the current pass — the grid runs `passes` full
    # dataset streams back-to-back (pass > 1 only for slope timing:
    # per-pass cost = d wall / d passes, immune to dispatch overhead)
    step = pl.program_id(0) % steps

    @pl.when(step == 0)
    def _():
        bestd[:] = jnp.full_like(bestd, jnp.inf)
        besti[:] = jnp.full_like(besti, -1)

    xt = x_ref[:]                                                # (t, d)
    qt = q_ref[:]                                                # (q, d)
    # f32 inputs: HIGHEST — exact-kNN semantics need full f32 products
    # (default single-pass bf16 loses ~8 mantissa bits), and the stream
    # is HBM-bound so the extra passes hide behind the loads. bf16
    # inputs: their products are already exact in the f32 accumulator.
    prec = (jax.lax.Precision.DEFAULT if xt.dtype == jnp.bfloat16
            else jax.lax.Precision.HIGHEST)
    ip = jax.lax.dot_general(qt, xt, (((1,), (1,)), ((), ())),
                             precision=prec,
                             preferred_element_type=jnp.float32)  # (q, t)
    xn = xn_ref[:]                                               # (1, t)
    qn = qn_ref[:]                                               # (q, 1)
    if metric in (DistanceType.InnerProduct,):
        dist = -ip
    elif metric == DistanceType.CosineExpanded:
        inv = jax.lax.rsqrt(jnp.maximum(qn * xn, 1e-30))
        dist = 1.0 - ip * inv
    else:  # L2 expanded family
        dist = jnp.maximum(qn + xn - 2.0 * ip, 0.0)

    col = jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1) + step * tile
    dist = jnp.where(col < n, dist, jnp.inf)

    # filtered merge (the reference's ``warp_sort_filtered`` idea,
    # ``matrix/detail/select_warpsort.cuh``): most tiles cannot improve
    # the running top-k — one VPU compare detects that and skips the
    # k-round extraction entirely
    kth = bestd[:, k - 1 : k]                                    # (q, 1)
    any_better = jnp.any(dist < kth)

    @pl.when(any_better)
    def _():
        cat_d = jnp.concatenate([bestd[:], dist], axis=1)
        cat_i = jnp.concatenate([besti[:], col], axis=1)
        new_d, new_i = _extract_topk(cat_d, cat_i, k)
        bestd[:] = new_d
        besti[:] = new_i

    @pl.when(step == steps - 1)
    def _():
        out = bestd[:]
        if metric in (DistanceType.L2SqrtExpanded,
                      DistanceType.L2SqrtUnexpanded):
            out = jnp.sqrt(out)
        elif metric == DistanceType.InnerProduct:
            out = -out
        outd_ref[:] = out
        outi_ref[:] = besti[:]


def fused_knn(
    queries,
    dataset,
    k: int,
    metric: DistanceType = DistanceType.L2Expanded,
    *,
    dataset_norms=None,
    tile: int = 0,
    vmem_mb: int = 0,
    passes: int = 1,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Exact kNN in one streamed Pallas pass: (q, k) distances + indices.

    Queries must be modest (they stay VMEM-resident: q·d + q·tile floats);
    the caller tiles large query sets. Any n — the ragged tail rides a
    partial final block, masked with +inf in the kernel.

    ``dataset_norms`` (f32 ``(n,)`` cached ||y||² as built by the
    brute-force index) skips the per-call norm pass; without it one extra
    full read of the dataset happens per call. The dataset itself is
    consumed in place when its dim is lane-aligned (d % 128 == 0) —
    per-call HBM traffic is then exactly one dataset stream.

    ``tile=0`` auto-sizes database blocks to the VMEM budget
    (``vmem_mb``, default :func:`~raft_tpu.core.chips.vmem_budget_mb`). Measured on
    v5e the stream is per-grid-step bound (~16 us/step) far below the
    HBM roofline, so the right tile is the largest that fits — fewer,
    bigger DMAs — not a fixed 8k.

    ``passes > 1`` repeats the full dataset stream that many times in
    ONE dispatch (the grid wraps around) — a benchmarking aid: per-pass
    time from the slope between two pass counts cancels the per-call
    dispatch overhead. Results are identical to passes=1."""
    if vmem_mb <= 0:
        vmem_mb = vmem_budget_mb()
    return _fused_knn_impl(queries, dataset, k, metric,
                           dataset_norms=dataset_norms, tile=tile,
                           vmem_mb=vmem_mb, passes=passes,
                           interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("k", "metric", "tile", "vmem_mb",
                                    "passes", "interpret"))
def _fused_knn_impl(
    queries,
    dataset,
    k: int,
    metric: DistanceType,
    *,
    dataset_norms,
    tile: int,
    vmem_mb: int,
    passes: int,
    interpret: bool,
) -> Tuple[jax.Array, jax.Array]:
    expect(metric in _SUPPORTED_METRICS,
           f"fused_knn: unsupported metric {metric}")
    q, d = queries.shape
    n = dataset.shape[0]
    expect(dataset.shape[1] == d, "fused_knn: dim mismatch")
    expect(0 < k <= n, "fused_knn: bad k")

    # sublane multiple: 8 for f32 blocks, 16 for bf16
    pad_q = (-q) % (16 if dataset.dtype == jnp.bfloat16 else 8)
    pad_d = (-d) % 128
    d_pad = d + pad_d
    q_pad = q + pad_q
    # VMEM budget per database row: double-buffered (tile, d) dataset
    # block + (1, tile) norms (f32, x2 buffers) + the kernel's live
    # (q_pad, tile) intermediates — ip/dist f32, col iota i32, and the
    # cat_d/cat_i concatenations in the merge — ~24 B per q_pad row.
    # 2 MB flat margin covers queries, out/scratch (q_pad, k) pairs and
    # compiler slack; cap at 65536 rows (past ~32 MB blocks the stream
    # is byte-bound and bigger tiles stop paying).
    itemsize = 2 if dataset.dtype == jnp.bfloat16 else 4
    budget = vmem_mb * 1024 * 1024 - q_pad * d_pad * itemsize - (2 << 20)
    per_row = 2 * (d_pad * itemsize + 4) + 24 * q_pad
    vmem_cap = max(512, (budget // per_row) // 128 * 128)
    if tile <= 0:
        tile = vmem_cap
    tile = min(tile, vmem_cap, 65536, max(128, ((n + 127) // 128) * 128))
    # bf16 datasets stay bf16 through HBM (the point of half storage);
    # everything else runs f32
    if dataset.dtype == jnp.bfloat16:
        qs = jnp.pad(queries.astype(jnp.bfloat16), ((0, pad_q), (0, pad_d)))
        xs = jnp.pad(dataset, ((0, 0), (0, pad_d)))
    else:
        qs = jnp.pad(queries.astype(jnp.float32), ((0, pad_q), (0, pad_d)))
        xs = jnp.pad(dataset.astype(jnp.float32), ((0, 0), (0, pad_d)))
    qn = jnp.sum(jnp.square(qs.astype(jnp.float32)), axis=1,
                 keepdims=True)                                   # (Q, 1)
    if dataset_norms is None:
        xn = jnp.sum(jnp.square(xs.astype(jnp.float32)), axis=1)[None, :]
    else:
        xn = jnp.asarray(dataset_norms, jnp.float32).reshape(1, n)
    qp = qs.shape[0]
    steps = -(-n // tile)

    kernel = functools.partial(_knn_kernel, k=k, n=n, tile=tile,
                               steps=steps, metric=metric)
    outd, outi = pl.pallas_call(
        kernel,
        grid=(steps * passes,),
        in_specs=[
            pl.BlockSpec((qp, qs.shape[1]), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((qp, 1), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, xs.shape[1]), lambda i, s=steps: (i % s, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile), lambda i, s=steps: (0, i % s),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((qp, k), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((qp, k), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((qp, k), jnp.float32),
            jax.ShapeDtypeStruct((qp, k), jnp.int32),
        ),
        scratch_shapes=[
            pltpu.VMEM((qp, k), jnp.float32),
            pltpu.VMEM((qp, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_mb * 1024 * 1024),
        interpret=interpret,
    )(qs, qn, xs, xn)
    return outd[:q], outi[:q]


def _select_kernel(v_ref, outd_ref, outi_ref, bestd, besti,
                   *, k: int, n: int, tile: int, select_min: bool):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _():
        bestd[:] = jnp.full_like(bestd, jnp.inf)
        besti[:] = jnp.full_like(besti, -1)

    vals = v_ref[:].astype(jnp.float32)
    if not select_min:
        vals = -vals
    col = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1) + step * tile
    vals = jnp.where(col < n, vals, jnp.inf)

    kth = bestd[:, k - 1 : k]
    any_better = jnp.any(vals < kth)

    @pl.when(any_better)
    def _():
        cat_d = jnp.concatenate([bestd[:], vals], axis=1)
        cat_i = jnp.concatenate([besti[:], col], axis=1)
        new_d, new_i = _extract_topk(cat_d, cat_i, k)
        bestd[:] = new_d
        besti[:] = new_i

    @pl.when(step == pl.num_programs(0) - 1)
    def _():
        outd_ref[:] = bestd[:] if select_min else -bestd[:]
        outi_ref[:] = besti[:]


def select_k_tiles(
    values,
    k: int,
    select_min: bool = True,
    *,
    tile: int = 4096,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Batched k-selection over a wide (batch, n) matrix as a streamed
    Pallas merge — the radix/warpsort-select analog. Exact, first-
    occurrence tie-break like the reference's stable warpsort.

    The VMEM budget is resolved OUTSIDE the jitted impl (like
    ``fused_knn``) so ``RAFT_TPU_VMEM_MB`` is honored per call instead
    of being frozen into the first trace."""
    return _select_k_tiles_impl(values, k, select_min, tile=tile,
                                interpret=interpret,
                                vmem_mb=vmem_budget_mb())


@functools.partial(jax.jit,
                   static_argnames=("k", "select_min", "tile",
                                    "interpret", "vmem_mb"))
def _select_k_tiles_impl(
    values,
    k: int,
    select_min: bool = True,
    *,
    tile: int = 4096,
    interpret: bool = False,
    vmem_mb: int = 64,
) -> Tuple[jax.Array, jax.Array]:
    b, n = values.shape
    expect(0 < k <= n, "select_k_tiles: bad k")
    tile = min(tile, max(128, ((n + 127) // 128) * 128))
    pad_n = (-n) % tile
    pad_b = (-b) % 8
    vs = jnp.pad(values.astype(jnp.float32), ((0, pad_b), (0, pad_n)))
    bp, npad = vs.shape
    grid = npad // tile

    kernel = functools.partial(_select_kernel, k=k, n=n, tile=tile,
                               select_min=select_min)
    outd, outi = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((bp, tile), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((bp, k), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bp, k), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bp, k), jnp.float32),
            jax.ShapeDtypeStruct((bp, k), jnp.int32),
        ),
        scratch_shapes=[
            pltpu.VMEM((bp, k), jnp.float32),
            pltpu.VMEM((bp, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_mb << 20),
        interpret=interpret,
    )(vs)
    return outd[:b], outi[:b]


# ---------------------------------------------------------------------------
# stream probe
# ---------------------------------------------------------------------------


def _stream_kernel(x_ref, o_ref, acc):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)

    acc[:] += jnp.sum(x_ref[:].astype(jnp.float32), axis=0, keepdims=True)

    @pl.when(step == pl.num_programs(0) - 1)
    def _():
        o_ref[:] = acc[:]


def stream_read_sum(x, tile: int = 0, vmem_mb: int = 0,
                    interpret: bool = False):
    """Column-sum of ``x`` as a pure streamed read — the HBM-bandwidth
    ceiling probe every bandwidth-bound kernel is judged against (the
    prims micro-bench and roofline claims in BASELINE.md use it).
    Touches each element exactly once; compute is one VPU add per
    element, far under the bandwidth bound. Ragged shapes are handled
    by a zero-pad (padding adds 0 to the sum) — but the pad is a full
    materialized copy INSIDE this jitted call, so for bandwidth
    measurements use tile- and lane-aligned shapes (n % tile == 0,
    d % 128 == 0), where the input streams in place.

    ``tile=0`` auto-sizes blocks to the VMEM budget (``vmem_mb``,
    default :func:`~raft_tpu.core.chips.vmem_budget_mb`): the stream is per-grid-step
    bound (~16 us/step on v5e) well below the HBM roofline, so the
    probe uses the biggest block that fits — a small-block probe
    measures step overhead, not bandwidth."""
    if vmem_mb <= 0:
        vmem_mb = vmem_budget_mb()
    return _stream_read_impl(x, tile, vmem_mb, interpret)


@functools.partial(jax.jit, static_argnames=("tile", "vmem_mb", "interpret"))
def _stream_read_impl(x, tile: int, vmem_mb: int, interpret: bool):
    n, d = x.shape
    dpad_cols = d + ((-d) % 128)
    itemsize = x.dtype.itemsize
    budget = vmem_mb * 1024 * 1024 - (1 << 20)
    # per element: double-buffered input block + an f32-widened strip
    # for the astype inside the kernel (sub-f32 inputs upcast to sum)
    per_elem = 2 * itemsize + (4 if itemsize < 4 else 0)
    cap = max(8, budget // (dpad_cols * per_elem))
    # power-of-two tile: the probe shapes are powers of two, so the
    # auto tile divides n exactly and the pad-copy path (which would
    # corrupt the bandwidth measurement) never triggers
    cap = 1 << (cap.bit_length() - 1)
    if tile <= 0:
        tile = cap
    tile = min(tile, cap, max(8, ((n + 7) // 8) * 8))
    pad_n = (-n) % tile
    pad_d = (-d) % 128
    if pad_n or pad_d:
        x = jnp.pad(x, ((0, pad_n), (0, pad_d)))
    npad, dpad = x.shape
    return pl.pallas_call(
        _stream_kernel,
        grid=(npad // tile,),
        in_specs=[pl.BlockSpec((tile, dpad), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, dpad), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, dpad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, dpad), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_mb * 1024 * 1024),
        interpret=interpret,
    )(x)[:, :d]
