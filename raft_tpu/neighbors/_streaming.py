"""Shared passes for the streaming index builds (flat / PQ / BQ) —
the three-pass structure over a :class:`raft_tpu.io.BinDataset`:
strided trainset sample, per-chunk label predict + size count, then
each index's own encode+scatter pass (whose rank bookkeeping is
:func:`raft_tpu.neighbors._packing.streaming_ranks`).

A chunk is a host array (a ``BinDataset`` read) or a ``jax.Array``
already on one device, e.g. one shard of a corpus laid out over a
mesh: the passes then work on the device that holds it and move only
their results (the sample's rows, the labels) to the host."""

from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.cluster import kmeans_balanced
from raft_tpu.core import interruptible

_take_rows = jax.jit(lambda a, rows: jnp.take(a, rows, axis=0))


def chunk_device(chunk):
    """The one device a ``jax.Array`` chunk lives on; None for a host
    array (or an array spread over several devices)."""
    if isinstance(chunk, jax.Array):
        devs = chunk.devices()
        if len(devs) == 1:
            return next(iter(devs))
    return None


def sample_trainset(source, train_rows: int, chunk_rows: int,
                    dtype=np.float32) -> np.ndarray:
    """Pass 1: a strided ``train_rows``-row sample spanning the whole
    dataset, as ``dtype`` (float32 by default; a byte source may keep
    its bytes), assembled chunk by chunk (the stride keeps phase
    across chunk boundaries). A device chunk's rows are picked on its
    device, so only the sample crosses to the host. Each chunk is a
    cancellation point (``interruptible.yield_``,
    ``core/interruptible.hpp:83`` role). Device picks are fetched
    together at the end, so every device picks at once."""
    n = source.n_rows
    stride = max(1, n // train_rows)
    parts = []
    for first, chunk in source.iter_chunks(chunk_rows):
        interruptible.yield_()
        offset = (-first) % stride
        if chunk_device(chunk) is not None:
            rows = np.arange(offset, chunk.shape[0], stride, dtype=np.int32)
            parts.append(_take_rows(chunk, rows))
        else:
            parts.append(np.asarray(chunk[offset::stride], dtype))
    parts = jax.device_get(parts)
    return np.concatenate([np.asarray(p, dtype) for p in parts])[:train_rows]


def label_pass(res, km_params, centers, source, chunk_rows: int,
               n_lists: int):
    """Pass 2: per-chunk nearest-center labels (device) + per-list
    population counts (host). Returns ``(labels_np, sizes_np)``.

    ``centers`` may be replicated over several devices (a mesh's): a
    device chunk is then labelled on the device that holds it, against
    that device's copy; host chunks use the first device's copy.

    A host chunk's labels are fetched once the next chunk's prediction
    has been dispatched, so the fetch overlaps the device work and one
    staged chunk at a time waits on the device. Device chunks are
    resident already: their predictions are dispatched round-robin over
    the devices, whatever order the source yields them in, and their
    labels (4 bytes a row) fetched after the last dispatch, so all
    devices label at once. Each chunk is a cancellation point."""
    n = source.n_rows
    labels_np = np.empty((n,), np.int32)
    copies = {s.device: s.data for s in centers.addressable_shards}
    first_copy = copies[min(copies, key=lambda d: d.id)]
    pending, resident = [], {}

    def fetch():
        labs = jax.device_get([lab for _, lab in pending])
        for (first, _), lab in zip(pending, labs):
            labels_np[first : first + lab.shape[0]] = lab
        pending.clear()

    for first, chunk in source.iter_chunks(chunk_rows):
        interruptible.yield_()
        dev = chunk_device(chunk)
        if dev is not None:
            resident.setdefault(dev, collections.deque()).append(
                (first, chunk))
            continue
        lab = kmeans_balanced.predict(res, km_params, first_copy, chunk)
        fetch()
        pending.append((first, lab))
    while resident:
        for dev in list(resident):
            interruptible.yield_()
            first, chunk = resident[dev].popleft()
            if not resident[dev]:
                del resident[dev]
            pending.append((first, kmeans_balanced.predict(
                res, km_params, copies.get(dev, first_copy), chunk)))
    fetch()
    sizes_np = np.bincount(labels_np, minlength=n_lists)
    return labels_np, sizes_np
