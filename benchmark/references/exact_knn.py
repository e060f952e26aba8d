"""The plain reference: exact k-nearest neighbours under squared L2.

Found by the configuration's ``"reference": "exact_knn"``. Independent
of the code under test: it imports nothing of ``raft_tpu`` and reads
only the corpus and queries the benchmark made from the seed.

The corpus is a ``jax.Array`` sharded by rows over the cell's chips (or
on one device). Each chip keeps, for each query, the ``shortlist``
nearest of its own rows in one float32 pass (``Precision.HIGHEST``:
full float32 products), its ids offset by the shard's first row; the
shortlists are merged on the host, and the merged shortlist is ranked
again in float64 from rows gathered out of the sharded corpus. Byte
rows are cast to float32 before any product: every byte distance is an
integer below 2**24 (128 * 255**2 = 8,323,200), so the float32 pass is
exact on them. On float rows float32's error is ~1e-6 of a distance,
far inside the gap between the k-th and the shortlist-th neighbour.
Ties are broken by the smaller row id, as a stable sort would.

:func:`control` is the control: the reference one step coarser than
the configuration's data, put in the program's place to show that the
comparison in :mod:`benchmark.check` fails it. For float32 data the
corpus and the queries are rounded to bfloat16 and multiplied at the
default MXU precision. For byte data bfloat16 would change nothing (its
8 significant bits hold every byte exactly, and the byte distances are
exact), so each value is instead rounded to an even number, one bit
coarser, and the distances of those values are exact.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def _blocks(n: int, target: int = 65536) -> int:
    """Number of equal corpus blocks: the fewest whose size divides
    ``n`` and is at most ``target`` (so no padded copy is made)."""
    for nb in range(max(1, -(-n // target)), n + 1):
        if n % nb == 0:
            return nb
    return n


def _row_layout(x):
    """``(mesh, axis)`` the corpus's rows are sharded over: its own
    mesh, or a one-device mesh for a corpus on one device."""
    sh = x.sharding
    if isinstance(sh, NamedSharding) and len(sh.spec) and sh.spec[0]:
        return sh.mesh, sh.spec[0]
    return Mesh(np.asarray(list(x.devices())), ("rows",)), "rows"


def _coarse(v):
    """Each byte value rounded to an even number, in float32; values
    that would leave the byte range step back into it."""
    lo, hi = (0, 254) if v.dtype == jnp.uint8 else (-128, 126)
    return jnp.clip(2.0 * jnp.round(v.astype(jnp.float32) / 2.0), lo, hi)


def _values(v, mode: str):
    if mode == "bf16":
        return v.astype(jnp.bfloat16)
    if mode == "coarse":
        return _coarse(v)
    return v.astype(jnp.float32)


def _local_shortlist(qb, xl, *, nb: int, s: int, mode: str):
    """(qb, s) smallest squared-L2 distances to the rows ``xl`` of one
    chip, and their local row ids."""
    n, d = xl.shape
    b = n // nb
    prec = (jax.lax.Precision.DEFAULT if mode == "bf16"
            else jax.lax.Precision.HIGHEST)
    qb = _values(qb, mode)
    qn = jnp.sum(jnp.square(qb.astype(jnp.float32)), axis=1, keepdims=True)

    def step(carry, t):
        best_v, best_i = carry
        xb = _values(jax.lax.dynamic_slice_in_dim(xl, t * b, b), mode)
        xn = jnp.sum(jnp.square(xb.astype(jnp.float32)), axis=1)
        ip = jax.lax.dot_general(qb, xb, (((1,), (1,)), ((), ())),
                                 precision=prec,
                                 preferred_element_type=jnp.float32)
        dist = qn + xn[None, :] - 2.0 * ip
        v, i = jax.lax.top_k(-dist, s)
        cat_v = jnp.concatenate([best_v, v], axis=1)
        cat_i = jnp.concatenate([best_i, i + t * b], axis=1)
        v, j = jax.lax.top_k(cat_v, s)
        return (v, jnp.take_along_axis(cat_i, j, axis=1)), None

    init = (jnp.full((qb.shape[0], s), -jnp.inf, jnp.float32),
            jnp.full((qb.shape[0], s), -1, jnp.int32))
    (v, i), _ = jax.lax.scan(step, init, jnp.arange(nb))
    return -v, i


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "nb", "s",
                                             "mode"))
def _shortlists(qb, x, *, mesh, axis: str, nb: int, s: int, mode: str):
    """Every chip's shortlist side by side: ``(qb, s * chips)``
    distances and global row ids, chip by chip in row order."""

    def body(qb, xl):
        d, i = _local_shortlist(qb, xl, nb=nb, s=s, mode=mode)
        return d, i + jax.lax.axis_index(axis) * xl.shape[0]

    return jax.shard_map(body, mesh=mesh, in_specs=(P(), P(axis)),
                         out_specs=(P(None, axis), P(None, axis)),
                         check_vma=False)(qb, x)


@functools.partial(jax.jit, static_argnames=("mesh", "axis"))
def _gather(x, ids, *, mesh, axis: str):
    """``x[ids]`` in float32 on every chip, from the rows sharded over
    ``axis``: each chip gives the rows it holds and zeros elsewhere,
    and the sum over chips is exact (one term is not zero)."""

    def body(xl, ids):
        r = xl.shape[0]
        loc = ids - jax.lax.axis_index(axis) * r
        mine = (loc >= 0) & (loc < r)
        rows = xl[jnp.clip(loc, 0, r - 1)].astype(jnp.float32)
        return jax.lax.psum(jnp.where(mine[..., None], rows, 0.0), axis)

    return jax.shard_map(body, mesh=mesh, in_specs=(P(axis), P()),
                         out_specs=P())(x, ids)


def _merged(x, queries, s: int, mode: str, q_block: int):
    """Per block of queries: ``(host queries, distances (qb, s) float32,
    ids (qb, s) int64)``, the ``s`` best of every chip's shortlist by
    distance, then by id."""
    mesh, axis = _row_layout(x)
    chips = mesh.shape[axis]
    nb = _blocks(int(x.shape[0]) // chips)
    q_all = np.asarray(queries)
    rep = NamedSharding(mesh, P())
    for start in range(0, len(q_all), q_block):
        qh = q_all[start:start + q_block]
        d, i = _shortlists(jax.device_put(qh, rep), x, mesh=mesh, axis=axis,
                           nb=nb, s=min(s, int(x.shape[0]) // chips),
                           mode=mode)
        d, i = np.asarray(d), np.asarray(i, np.int64)
        order = np.lexsort((i, d), axis=1)[:, :s]
        yield (qh, np.take_along_axis(d, order, axis=1),
               np.take_along_axis(i, order, axis=1))


def _rows64(x, ids) -> np.ndarray:
    """Rows ``ids`` (any shape of valid ids) in float64 on the host."""
    mesh, axis = _row_layout(x)
    ids = jax.device_put(np.asarray(ids, np.int32), NamedSharding(mesh, P()))
    return np.asarray(_gather(x, ids, mesh=mesh, axis=axis), np.float64)


def knn(x, queries, k: int, *, shortlist: int = 32, q_block: int = 1024):
    """Exact kNN of every query: ``(d64 (q, k) float64, ids (q, k)
    int64)``, ascending by distance, then by id, ranked in float64."""
    out_d, out_i = [], []
    for qh, _, cand in _merged(x, queries, shortlist, "exact", q_block):
        diff = _rows64(x, cand) - np.asarray(qh, np.float64)[:, None, :]
        d64 = np.einsum("qsd,qsd->qs", diff, diff)
        order = np.lexsort((cand, d64), axis=1)[:, :k]
        out_d.append(np.take_along_axis(d64, order, axis=1))
        out_i.append(np.take_along_axis(cand, order, axis=1))
    return np.concatenate(out_d), np.concatenate(out_i)


def control(x, queries, k: int, *, q_block: int = 1024):
    """The control's answers, ``(d float32 (q, k), ids int32 (q, k))``
    as the program would return them: exact kNN on values one step
    coarser than the data's (bfloat16 for float data, even numbers for
    bytes)."""
    mode = "coarse" if jnp.issubdtype(x.dtype, jnp.integer) else "bf16"
    out_d, out_i = [], []
    for _, d, i in _merged(x, queries, k, mode, q_block):
        out_d.append(d.astype(np.float32))
        out_i.append(i.astype(np.int32))
    return np.concatenate(out_d), np.concatenate(out_i)


def true_distances(x, queries, ids, *, block: int = 4096) -> np.ndarray:
    """float64 squared-L2 distance of each (query row, id) pair;
    ``queries`` (r, d) host array, ``ids`` (r, k) valid row ids; the
    rows are gathered out of the sharded corpus."""
    out = []
    for s in range(0, len(ids), block):
        rows = _rows64(x, ids[s:s + block])
        diff = rows - np.asarray(queries[s:s + block], np.float64)[:, None]
        out.append(np.einsum("rkd,rkd->rk", diff, diff))
    return np.concatenate(out) if out else np.zeros(ids.shape)
