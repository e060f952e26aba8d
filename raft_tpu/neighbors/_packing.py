"""Shared padded-list packing — THE sort-and-rank scatter used by every
IVF index type (role of the reference's per-list packing,
``detail/ivf_flat_build.cuh:161`` extend; dense re-design per
SURVEY.md §7.4: ragged ``ivf::list`` → one padded tensor).

Stable-sort rows by label, compute each row's rank within its list,
scatter into ``label * max_size + rank`` slots.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def streaming_ranks(labels_chunk, fill, n_lists: int):
    """Host-side within-list rank assignment for the streaming builds:
    given a chunk's list labels and the running per-list fill counts
    (np.int64, updated IN PLACE), return each row's destination rank
    within its padded list."""
    lab = np.asarray(labels_chunk)
    m = lab.shape[0]
    order = np.argsort(lab, kind="stable")
    sl = lab[order]
    first_pos = np.searchsorted(sl, np.arange(n_lists))
    rank_sorted = np.arange(m) - first_pos[sl] + fill[sl]
    ranks = np.empty((m,), np.int32)
    ranks[order] = rank_sorted.astype(np.int32)
    np.add.at(fill, lab, 1)
    return ranks


def sublane_multiple(dtype=jnp.float32) -> int:
    """Rows of one TPU tile of ``dtype`` storage: 8 for 4-byte
    elements, 16 for 2-byte, 32 for 1-byte (an (8, 128) tile of 32-bit
    words packs narrower elements along the sublanes)."""
    return max(8, 32 // np.dtype(dtype).itemsize)


def padded_extent(sizes, dtype=jnp.float32) -> int:
    """Shared max-list-size rounding: the largest list, rounded up to
    the sublane multiple of the ``dtype`` the lists are stored in
    (:func:`sublane_multiple`; 8 for the default float32), so the
    Pallas list scan reads each list as whole tiles. One host sync per
    build/extend."""
    sub = sublane_multiple(dtype)
    return max(sub, -(-int(jnp.max(jnp.asarray(sizes))) // sub) * sub)


def pack_padded_lists(
    labels,
    n_lists: int,
    max_size: int,
    payloads: Sequence[Tuple[object, object]],
    sizes=None,
):
    """Scatter per-row payloads into padded ``[n_lists, max_size]``
    layouts.

    Args:
      labels: (n,) int list assignment per row.
      payloads: sequence of ``(array, fill)`` — each array is (n, ...)
        and lands in a ``(n_lists, max_size, ...)`` output initialized
        to ``fill``.
      sizes: optional precomputed per-list populations (callers usually
        have them already — they sized ``max_size`` from them); when
        omitted they are recomputed here.

    Returns ``([packed...], sizes)`` with sizes (n_lists,) int32.
    """
    labels = jnp.asarray(labels, jnp.int32)
    n = labels.shape[0]
    order = jnp.argsort(labels, stable=True)
    sorted_labels = labels[order]
    first = jnp.searchsorted(sorted_labels, jnp.arange(n_lists),
                             side="left")
    rank = jnp.arange(n) - first[sorted_labels]
    slot = sorted_labels * max_size + rank

    outs = []
    for arr, fill in payloads:
        arr = jnp.asarray(arr)
        flat = jnp.full((n_lists * max_size,) + arr.shape[1:], fill,
                        arr.dtype)
        flat = flat.at[slot].set(arr[order])
        outs.append(flat.reshape((n_lists, max_size) + arr.shape[1:]))
    if sizes is None:
        sizes = jax.ops.segment_sum(jnp.ones((n,), jnp.int32), labels,
                                    num_segments=n_lists)
    return outs, jnp.asarray(sizes, jnp.int32)
