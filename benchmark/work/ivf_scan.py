"""Work an IVF-Flat probe scan needs, whatever implements it.

Bytes: for each dispatch, the rows actually stored in the distinct
lists its queries probe, times ``dim * itemsize + 4`` (the vector and
its norm), plus the queries. Operations: ``2 * dim`` for every probed
(query, row) pair. Padding slots never count, so a scan that stops
streaming padding shows as a roofline gain. The probed lists are the
index's centers ranked for the benchmark's own queries in plain jnp.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# the scan kernel's events, as a v5e trace names them (read by hand in
# PR 22): the Pallas call carries the executor's module name, e.g.
# ``%rt_ivf_flat_fa78935e9f07.1 = (f32[1216,10]..., s32[1216,10]...)
# custom-call(...), custom_call_target="tpu_custom_call"`` -- the only
# Mosaic kernel of the served IVF-Flat executable
TRACE_PATTERNS = (
    r'%rt_ivf_flat_[0-9a-f]+(\.\d+)? = .*custom_call_target="tpu_custom_call"',
)


@jax.jit
def _center_scores(queries, centers):
    ip = jnp.dot(queries, centers.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.sum(jnp.square(centers), axis=1)[None, :] - 2.0 * ip


def probes(queries, centers, n_probes: int, block: int = 4096) -> np.ndarray:
    """``(q, n_probes)`` ids of each query's nearest centers (L2)."""
    out = []
    for s in range(0, int(queries.shape[0]), block):
        sc = _center_scores(jnp.asarray(queries[s:s + block]), centers)
        out.append(np.asarray(jax.lax.top_k(-sc, n_probes)[1]))
    return np.concatenate(out)


def list_sizes(indices) -> np.ndarray:
    """Rows stored in each list of a padded ``(n_lists, slots)`` id
    plane (padding carries id -1)."""
    return np.asarray(jnp.sum(jnp.asarray(indices) >= 0, axis=1), np.int64)


def dispatch(probe_rows: np.ndarray, sizes: np.ndarray, dim: int,
             itemsize: int = 4):
    """``(bytes, flops)`` of one dispatch whose queries probe
    ``probe_rows`` ``(q, n_probes)``."""
    q = probe_rows.shape[0]
    distinct = np.unique(probe_rows)
    n_bytes = (int(sizes[distinct].sum()) * (dim * itemsize + 4)
               + q * dim * 4)
    n_flops = 2 * dim * int(sizes[probe_rows].sum())
    return n_bytes, n_flops


def totals(inputs: dict, dispatches) -> tuple:
    """Summed ``(bytes, flops)`` over ``dispatches``, each an array of
    pool rows. ``inputs``: ``pool_probes`` (pool, n_probes), ``sizes``,
    ``dim``, ``itemsize``."""
    b = f = 0
    for rows in dispatches:
        db, df = dispatch(inputs["pool_probes"][rows], inputs["sizes"],
                          inputs["dim"], inputs["itemsize"])
        b += db
        f += df
    return b, f
