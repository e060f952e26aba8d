"""The plain reference: exact k-nearest neighbours under squared L2.

Independent of the code under test: it imports nothing of ``raft_tpu``
and reads only the corpus and queries the benchmark made from the seed.
A float32 pass on the device keeps each query's ``shortlist`` nearest
rows (``Precision.HIGHEST``: full float32 products); those are ranked
again in float64 on the host. float32's error is ~1e-6 of a distance,
far inside the gap between the k-th and the shortlist-th neighbour.

``low=True`` is the control: the same search with the corpus and the
queries rounded to bfloat16 (the precision below the float32 the
configurations state), default MXU precision, no float64 pass. Its
answers stand in the program's place to show that the comparison in
:mod:`benchmark.check` fails it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _blocks(n: int, target: int = 65536) -> int:
    """Number of equal corpus blocks: the fewest whose size divides
    ``n`` and is at most ``target`` (so no padded copy is made)."""
    for nb in range(max(1, -(-n // target)), n + 1):
        if n % nb == 0:
            return nb
    return n


@functools.partial(jax.jit, static_argnames=("nb", "s", "low"))
def _shortlist(qb, x, *, nb: int, s: int, low: bool):
    """(qb, s) smallest squared-L2 distances and their row ids."""
    n, d = x.shape
    b = n // nb
    if low:
        qb, x = qb.astype(jnp.bfloat16), x.astype(jnp.bfloat16)
        prec = jax.lax.Precision.DEFAULT
    else:
        prec = jax.lax.Precision.HIGHEST
    xs = x.reshape(nb, b, d)
    qn = jnp.sum(jnp.square(qb.astype(jnp.float32)), axis=1, keepdims=True)

    def step(carry, t):
        best_v, best_i = carry
        xb = xs[t]
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


@jax.jit
def _gather(x, ids):
    return x[ids]


def exact_knn(x, queries, k: int, *, shortlist: int = 32,
              q_block: int = 1024):
    """Exact kNN of every query: ``(d64 (q, k) float64, ids (q, k)
    int64)``, ascending, ranked in float64."""
    nb = _blocks(int(x.shape[0]))
    out_d, out_i = [], []
    for s in range(0, int(queries.shape[0]), q_block):
        qb = jnp.asarray(queries[s:s + q_block])
        _, cand = _shortlist(qb, x, nb=nb, s=shortlist, low=False)
        rows = np.asarray(_gather(x, cand), np.float64)      # (qb, s, d)
        diff = rows - np.asarray(qb, np.float64)[:, None, :]
        d64 = np.einsum("qsd,qsd->qs", diff, diff)
        cand = np.asarray(cand, np.int64)
        order = np.lexsort((cand, d64), axis=1)[:, :k]
        out_d.append(np.take_along_axis(d64, order, axis=1))
        out_i.append(np.take_along_axis(cand, order, axis=1))
    return np.concatenate(out_d), np.concatenate(out_i)


def control_knn(x, queries, k: int, *, q_block: int = 1024):
    """The control's answers: exact kNN computed in bfloat16,
    ``(d float32 (q, k), ids int32 (q, k))`` as the program would
    return them."""
    nb = _blocks(int(x.shape[0]))
    out_d, out_i = [], []
    for s in range(0, int(queries.shape[0]), q_block):
        d, i = _shortlist(jnp.asarray(queries[s:s + q_block]), x, nb=nb,
                          s=k, low=True)
        out_d.append(np.asarray(d))
        out_i.append(np.asarray(i))
    return np.concatenate(out_d), np.concatenate(out_i)


def true_distances(x, queries, ids, *, block: int = 4096) -> np.ndarray:
    """float64 squared-L2 distance of each (query row, id) pair;
    ``queries`` (r, d) host array, ``ids`` (r, k) valid row ids."""
    out = []
    for s in range(0, len(ids), block):
        rows = np.asarray(_gather(x, jnp.asarray(ids[s:s + block],
                                                 jnp.int32)), np.float64)
        diff = rows - np.asarray(queries[s:s + block], np.float64)[:, None]
        out.append(np.einsum("rkd,rkd->rk", diff, diff))
    return np.concatenate(out) if out else np.zeros(ids.shape)
