"""Balanced k-means — analog of ``raft::cluster::kmeans_balanced``
(``cluster/kmeans_balanced.cuh:76``), the trainer behind IVF coarse
quantizers and PQ codebooks.

Reference semantics mirrored from ``detail/kmeans_balanced.cuh``:

- EM iterations (``balancing_em_iters:618``): predict → recompute centers
  (``calc_centers_and_sizes:257``) with a **balancing step** between
  iterations (``adjust_centers:524``): any cluster smaller than
  ``avg_size * balancing_threshold`` (0.25) is pulled toward a random
  sample from a large (≥ average) cluster with weight
  ``wc = min(size, 7)`` vs ``wd = 1`` (``kAdjustCentersWeight``,
  ``detail/kmeans_balanced.cuh:61,473``).
- For InnerProduct/Cosine/Correlation metrics centers are L2-normalized
  every iteration to avoid collapse to zero (``:655-670``).

TPU re-design: the predict step is the fused GEMM+argmin; the center
update is a ``segment_sum``; the adjust step is fully vectorized (one
weighted random point drawn per cluster instead of the CUDA atomic-counter
walk — same distributional intent, deterministic under a PRNG key). The
whole trainer is one jitted ``fori_loop``.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from raft_tpu.core import tracing
from raft_tpu.core.resources import Resources, ensure_resources
from raft_tpu.core.validation import expect
from raft_tpu.distance.fused_l2_nn import _fused_l2_nn
from raft_tpu.distance.types import DistanceType

_ADJUST_CENTERS_WEIGHT = 7.0  # kAdjustCentersWeight
_BALANCING_THRESHOLD = 0.25   # default balancing_threshold

_NORMALIZED_METRICS = (
    DistanceType.InnerProduct,
    DistanceType.CosineExpanded,
    DistanceType.CorrelationExpanded,
)


@dataclasses.dataclass(frozen=True)
class KMeansBalancedParams:
    """Mirrors ``raft::cluster::kmeans_balanced_params``
    (``cluster/kmeans_balanced_types.hpp:38``)."""

    n_iters: int = 20
    metric: DistanceType = DistanceType.L2Expanded
    seed: int = 0


def _predict_impl(x, centroids, metric: DistanceType):
    """Nearest center under L2 or (normalized-center) inner product —
    ``detail/kmeans_balanced.cuh:371`` ``predict``."""
    if metric in _NORMALIZED_METRICS:
        sims = jax.lax.dot_general(
            x.astype(jnp.float32), centroids, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        labels = jnp.argmax(sims, axis=1).astype(jnp.int32)
        return labels
    c_sq = jnp.sum(jnp.square(centroids), axis=1)
    _, labels = _fused_l2_nn(x, centroids, c_sq, False,
                             min(2048, max(64, centroids.shape[0])))
    return labels


def _calc_centers_and_sizes(x, labels, n_clusters: int):
    sums = jax.ops.segment_sum(x, labels, num_segments=n_clusters)
    sizes = jax.ops.segment_sum(
        jnp.ones((x.shape[0],), jnp.float32), labels, num_segments=n_clusters
    )
    centers = sums / jnp.maximum(sizes, 1.0)[:, None]
    return centers, sizes


def _normalize_rows(c):
    n = jnp.linalg.norm(c, axis=1, keepdims=True)
    return c / jnp.maximum(n, 1e-12)


def _adjust_centers(key, centers, sizes, x, labels, n_clusters: int):
    """Vectorized balancing step (``adjust_centers_kernel``,
    ``detail/kmeans_balanced.cuh:438-483``)."""
    n = x.shape[0]
    average = n / n_clusters
    small = sizes < average * _BALANCING_THRESHOLD
    # draw one candidate point per cluster, weighted toward rows whose own
    # cluster is at least average-sized (the reference's do/while walk)
    weights = (sizes[labels] >= average).astype(jnp.float32) + 1e-6
    cand = jax.random.choice(key, n, (n_clusters,), replace=True, p=weights / weights.sum())
    points = x[cand]
    wc = jnp.minimum(sizes, _ADJUST_CENTERS_WEIGHT)[:, None]
    pulled = (wc * centers + points) / (wc + 1.0)
    return jnp.where(small[:, None], pulled, centers), jnp.any(small)


@partial(jax.jit, static_argnames=("n_clusters", "n_iters", "metric"))
def _fit_impl(x, key, n_clusters: int, n_iters: int, metric: DistanceType):
    n = x.shape[0]
    k_init, k_adjust = jax.random.split(key)
    # init: uniform subsample of the dataset (reference seeds from a strided
    # subsample of the trainset)
    idx = jax.random.choice(k_init, n, (n_clusters,), replace=False)
    centers = x[idx]
    if metric in _NORMALIZED_METRICS:
        centers = _normalize_rows(centers)

    def body(it, state):
        centers, sizes, labels = state
        # balancing step (not on the first iteration)
        def do_adjust(c):
            adjusted, _ = _adjust_centers(
                jax.random.fold_in(k_adjust, it), c, sizes, x, labels, n_clusters
            )
            return adjusted

        centers = jax.lax.cond(it > 0, do_adjust, lambda c: c, centers)
        if metric in _NORMALIZED_METRICS:
            centers = _normalize_rows(centers)
        labels = _predict_impl(x, centers, metric)
        new_centers, sizes = _calc_centers_and_sizes(x, labels, n_clusters)
        new_centers = jnp.where((sizes > 0)[:, None], new_centers, centers)
        return new_centers, sizes, labels

    init = (
        centers,
        jnp.zeros((n_clusters,), jnp.float32),
        jnp.zeros((n,), jnp.int32),
    )
    centers, sizes, labels = jax.lax.fori_loop(0, n_iters, body, init)
    if metric in _NORMALIZED_METRICS:
        centers = _normalize_rows(centers)
    return centers, labels, sizes


def fit(
    res: Optional[Resources],
    params: KMeansBalancedParams,
    x,
    n_clusters: int,
) -> jax.Array:
    """Train balanced k-means; returns centroids (n_clusters, d) float32
    (``kmeans_balanced::fit``, ``cluster/kmeans_balanced.cuh:76``)."""
    res = ensure_resources(res)
    x = jnp.asarray(x, jnp.float32)
    expect(x.ndim == 2, "x must be 2-D")
    expect(n_clusters <= x.shape[0], "n_clusters > n_samples")
    key = jax.random.key(params.seed)
    with tracing.range("raft_tpu.kmeans_balanced.fit"):
        centers, _, _ = _fit_impl(x, key, n_clusters, params.n_iters, params.metric)
    return centers


def predict(
    res: Optional[Resources],
    params: KMeansBalancedParams,
    centroids,
    x,
) -> jax.Array:
    """Label each row with its nearest centroid
    (``kmeans_balanced::predict``). ``x`` may hold any real dtype: it
    is widened to float32 inside the compiled program, on the device
    that holds it, so byte rows cross to the device as bytes."""
    ensure_resources(res)
    x = jnp.asarray(x)
    centroids = jnp.asarray(centroids, jnp.float32)
    with tracing.range("raft_tpu.kmeans_balanced.predict"):
        return _predict_impl(x, centroids, params.metric)


def fit_predict(res, params: KMeansBalancedParams, x, n_clusters: int):
    centroids = fit(res, params, x, n_clusters)
    return centroids, predict(res, params, centroids, x)


def build_clusters(
    res: Optional[Resources],
    params: KMeansBalancedParams,
    x,
    n_clusters: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Train + label + sizes in one call — the IVF build entry
    (``kmeans_balanced::helpers::build_clusters``,
    ``cluster/kmeans_balanced.cuh:258``)."""
    res = ensure_resources(res)
    x = jnp.asarray(x, jnp.float32)
    key = jax.random.key(params.seed)
    centers, labels, sizes = _fit_impl(x, key, n_clusters, params.n_iters, params.metric)
    return centers, labels, sizes.astype(jnp.int32)


def calc_centers_and_sizes(x, labels, n_clusters: int):
    """Public helper mirroring ``kmeans_balanced::helpers::
    calc_centers_and_sizes`` (``cluster/kmeans_balanced.cuh:337``)."""
    x = jnp.asarray(x, jnp.float32)
    labels = jnp.asarray(labels, jnp.int32)
    centers, sizes = _calc_centers_and_sizes(x, labels, n_clusters)
    return centers, sizes.astype(jnp.int32)
