"""Corpus and query pool, made on the chips that hold them.

SIFT itself cannot be downloaded here, so the data stands in for it
with the property that decides IVF recall: a low intrinsic dimension
inside the 128 stored ones (SIFT's is estimated at 10-20). A row is a
point of an ``intrinsic_dim``-dimensional Gaussian mixture
(``n_clusters`` centers drawn at ``center_scale``, unit-normal spread),
mapped into ``dim`` dimensions by a fixed orthonormal basis, plus
isotropic noise of standard deviation ``noise``. The mixture fills its
low-dimensional space densely, so a query's ten nearest rows straddle
the borders of a few neighbouring IVF lists and recall rises with the
probe count in a knee, as it does on SIFT; the first version's
well-separated 128-d clusters read recall 1.0 from 16 probes up and
could not see a probe count cut. Queries come from the same
distribution.

The corpus comes from the configuration's ``data_seed`` and the query
pool from the run's ``--seed``: an IVF index's padded list length (its
executables' shapes) follows from the corpus, so a corpus drawn per run
would compile the served programs anew in every run. Every seed thus
serves the same index with other queries in another order.

The configuration's ``dataset.dtype`` picks one of two paths:

- ``float32``: one jitted call draws the corpus and the pool together
  (:func:`make_data`). On one chip it runs on the default device, as it
  always has; over several chips the same call writes each chip's rows
  there (JAX's threefry is partitionable, so the values do not depend
  on the layout).
- ``uint8`` or ``int8``: the same mixture, drawn in float32 a block of
  :data:`BLOCK_ROWS` rows at a time on the chip that holds the block,
  then mapped to the byte grid by the configuration's ``byte_scale``
  and ``byte_offset`` (``round(byte_scale * v + byte_offset)``, clipped
  to the dtype's range). Every row is a pure function of ``data_seed``
  and its row number, so one configuration gives the same bytes on one
  chip and on four; the float block is transient. The pool is mapped
  the same way (:func:`make_bytes`).

Either way the corpus is one ``jax.Array`` sharded by rows over the
cell's chips, never whole on one device or on the host, wrapped in a
:class:`Corpus`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

GENERATOR_KEYS = ("n", "dim", "n_clusters", "intrinsic_dim",
                  "center_scale", "noise", "data_seed")
BYTE_KEYS = ("byte_scale", "byte_offset")
BYTE_RANGES = {"uint8": (0, 255), "int8": (-128, 127)}
# rows drawn in float32 at once on a chip by the byte path: 64 MB of
# float rows at dim 128, whatever the corpus's size
BLOCK_ROWS = 1 << 17
AXIS = "rows"


def seed_key(seed: int) -> jax.Array:
    """A threefry key for any whole-number seed (taken mod 2**64):
    the same key ``jax.random.key(seed)`` gives for seeds below 2**63."""
    s = int(seed) % (1 << 64)
    return jax.random.wrap_key_data(
        jnp.array([s >> 32, s & 0xFFFFFFFF], jnp.uint32))


def _layout(data_key, center_scale, *, dim: int, n_clusters: int,
            intrinsic_dim: int):
    """The corpus's row keys, basis and centers, from its key."""
    kb, kc, ka, kz, ke = jax.random.split(data_key, 5)
    m = intrinsic_dim
    basis = jnp.linalg.qr(jax.random.normal(kb, (dim, m), jnp.float32))[0].T
    centers = center_scale * jax.random.normal(kc, (n_clusters, m),
                                               jnp.float32)
    return (ka, kz, ke), basis, centers


def _mixture(data_key, query_key, center_scale, noise, *, n: int, dim: int,
             n_queries: int, n_clusters: int, intrinsic_dim: int):
    (ka, kz, ke), basis, centers = _layout(
        data_key, center_scale, dim=dim, n_clusters=n_clusters,
        intrinsic_dim=intrinsic_dim)
    kqa, kqz, kqe = jax.random.split(query_key, 3)
    m = intrinsic_dim

    def draw(k_assign, k_latent, k_noise, rows):
        z = jax.random.normal(k_latent, (rows, m), jnp.float32)
        z = z + centers[jax.random.randint(k_assign, (rows,), 0, n_clusters)]
        e = noise * jax.random.normal(k_noise, (rows, dim), jnp.float32)
        return jnp.dot(z, basis, precision=jax.lax.Precision.HIGHEST) + e

    return draw(ka, kz, ke, n), draw(kqa, kqz, kqe, n_queries)


_MIXTURE_STATIC = ("n", "dim", "n_queries", "n_clusters", "intrinsic_dim")
_mixture_one = jax.jit(_mixture, static_argnames=_MIXTURE_STATIC)


@functools.lru_cache(maxsize=None)
def _mixture_sharded(mesh: Mesh):
    return jax.jit(_mixture, static_argnames=_MIXTURE_STATIC,
                   out_shardings=(NamedSharding(mesh, P(AXIS)),
                                  NamedSharding(mesh, P())))


def row_mesh(devices) -> Mesh:
    """A one-axis mesh over ``devices``, rows in device order."""
    return Mesh(np.asarray(list(devices)), (AXIS,))


def _rows_of(devices, n: int) -> int:
    if n % len(devices):
        raise ValueError(f"n {n} does not divide over {len(devices)} chips")
    return n // len(devices)


def make_data(data_seed: int, seed: int, *, n: int, dim: int,
              n_queries: int, n_clusters: int, intrinsic_dim: int,
              center_scale: float, noise: float, devices=None):
    """``(x (n, dim), queries (n_queries, dim))`` float32: the corpus a
    pure function of ``data_seed``, the queries of ``seed``, both of the
    sizes. On one device (the default: the default device) in one call;
    over several, the corpus sharded by rows and the queries
    replicated."""
    args = (seed_key(data_seed), seed_key(seed), jnp.float32(center_scale),
            jnp.float32(noise))
    sizes = dict(n=n, dim=dim, n_queries=n_queries, n_clusters=n_clusters,
                 intrinsic_dim=intrinsic_dim)
    devices = list(devices) if devices else jax.devices()[:1]
    if len(devices) == 1:
        with jax.default_device(devices[0]):
            x, q = _mixture_one(*args, **sizes)
    else:
        _rows_of(devices, n)
        x, q = _mixture_sharded(row_mesh(devices))(*args, **sizes)
    return jax.block_until_ready(x), jax.block_until_ready(q)


def _draw_rows(keys, basis, centers, noise, first, count: int):
    """Rows ``first .. first + count - 1`` of a mixture, each drawn
    from its own keys (``fold_in`` of its row number), so a row's
    values depend on nothing but the keys and its number."""
    ka, kz, ke = keys
    m, dim = basis.shape
    idx = first + jnp.arange(count, dtype=jnp.uint32)

    def per_row(k, draw):
        return jax.vmap(lambda i: draw(jax.random.fold_in(k, i)))(idx)

    z = per_row(kz, lambda k: jax.random.normal(k, (m,), jnp.float32))
    a = per_row(ka, lambda k: jax.random.randint(k, (), 0,
                                                 centers.shape[0]))
    e = per_row(ke, lambda k: jax.random.normal(k, (dim,), jnp.float32))
    return (jnp.dot(z + centers[a], basis,
                    precision=jax.lax.Precision.HIGHEST) + noise * e)


def _to_bytes(v, scale, offset, dtype: str):
    lo, hi = BYTE_RANGES[dtype]
    return jnp.clip(jnp.round(scale * v + offset), lo, hi).astype(dtype)


@functools.partial(jax.jit, static_argnames=(
    "mesh", "rows", "block", "dim", "n_clusters", "intrinsic_dim", "dtype"))
def _byte_corpus(key_data, center_scale, noise, scale, offset, *, mesh,
                 rows: int, block: int, dim: int, n_clusters: int,
                 intrinsic_dim: int, dtype: str):
    """Each chip's ``rows`` rows of the byte corpus, drawn there
    ``block`` rows at a time into its shard."""

    def shard(key_data, center_scale, noise, scale, offset):
        keys, basis, centers = _layout(
            jax.random.wrap_key_data(key_data), center_scale, dim=dim,
            n_clusters=n_clusters, intrinsic_dim=intrinsic_dim)
        first = jax.lax.axis_index(AXIS).astype(jnp.uint32) * rows

        def put(out, start, count):           # start: int32 local row
            v = _draw_rows(keys, basis, centers, noise,
                           first + start.astype(jnp.uint32), count)
            return jax.lax.dynamic_update_slice(
                out, _to_bytes(v, scale, offset, dtype), (start, 0))

        out = jax.lax.pcast(jnp.zeros((rows, dim), dtype), (AXIS,),
                            to="varying")
        out = jax.lax.fori_loop(0, rows // block,
                                lambda j, o: put(o, j * block, block), out)
        tail = rows % block
        if tail:
            out = put(out, jnp.int32(rows - tail), tail)
        return out

    return jax.shard_map(shard, mesh=mesh, in_specs=P(), out_specs=P(AXIS))(
        key_data, center_scale, noise, scale, offset)


@functools.partial(jax.jit, static_argnames=(
    "n_queries", "dim", "n_clusters", "intrinsic_dim", "dtype"))
def _byte_queries(data_key, query_key, center_scale, noise, scale, offset,
                  *, n_queries: int, dim: int, n_clusters: int,
                  intrinsic_dim: int, dtype: str):
    _, basis, centers = _layout(data_key, center_scale, dim=dim,
                                n_clusters=n_clusters,
                                intrinsic_dim=intrinsic_dim)
    v = _draw_rows(tuple(jax.random.split(query_key, 3)), basis, centers,
                   noise, jnp.uint32(0), n_queries)
    return _to_bytes(v, scale, offset, dtype)


def make_bytes(data_seed: int, seed: int, *, n: int, dim: int,
               n_queries: int, n_clusters: int, intrinsic_dim: int,
               center_scale: float, noise: float, dtype: str,
               byte_scale: float, byte_offset: float, devices=None):
    """``(x (n, dim), queries (n_queries, dim))`` of ``dtype`` (uint8 or
    int8): the corpus sharded by rows over ``devices`` (default: the
    default device), the queries on the first of them."""
    if dtype not in BYTE_RANGES:
        raise ValueError(f"no byte path for dtype {dtype!r}")
    devices = list(devices) if devices else jax.devices()[:1]
    rows = _rows_of(devices, n)
    if n >= 1 << 32:
        raise ValueError(f"n {n} does not fit 32-bit row numbers")
    f32 = [jnp.float32(v) for v in (center_scale, noise, byte_scale,
                                    byte_offset)]
    sizes = dict(dim=dim, n_clusters=n_clusters, intrinsic_dim=intrinsic_dim,
                 dtype=dtype)
    x = _byte_corpus(jax.random.key_data(seed_key(data_seed)), *f32,
                     mesh=row_mesh(devices), rows=rows,
                     block=min(BLOCK_ROWS, rows), **sizes)
    with jax.default_device(devices[0]):
        q = _byte_queries(seed_key(data_seed), seed_key(seed), *f32,
                          n_queries=n_queries, **sizes)
    return jax.block_until_ready(x), jax.block_until_ready(q)


class Corpus:
    """The corpus as the harness made it, for the family adapters and
    the reference: ``array``, one ``jax.Array`` sharded by rows over the
    cell's chips; ``n_rows``, ``dim``, ``dtype``; and
    :meth:`iter_chunks`, the streaming interface the program's
    ``raft_tpu.io.BinDataset`` offers its streaming builds."""

    def __init__(self, array: jax.Array):
        self.array = array
        self.n_rows, self.dim = (int(s) for s in array.shape)
        self.dtype = np.dtype(array.dtype)

    def shards(self):
        """``[(first_row, rows)]``: each chip's rows as a one-device
        array, in row order."""
        return sorted(((s.index[0].start or 0, s.data)
                       for s in self.array.addressable_shards),
                      key=lambda t: t[0])

    def iter_chunks(self, chunk_rows: int):
        """Yield ``(first_row, rows)`` in row order, at most
        ``chunk_rows`` rows each, each chunk on the chip that holds it
        (a chunk never spans two chips)."""
        if chunk_rows <= 0:
            raise ValueError("chunk_rows must be positive")
        for first, rows in self.shards():
            for s in range(0, rows.shape[0], chunk_rows):
                yield first + s, rows[s:s + chunk_rows]


def for_dataset(ds: dict, seed: int, n_queries: int, devices=None):
    """``(Corpus, queries)`` for a configuration's ``dataset`` keys, laid
    out over ``devices`` (the cell's chips; default: the default
    device)."""
    sizes = {k: ds[k] for k in GENERATOR_KEYS if k != "data_seed"}
    dtype = ds.get("dtype", "float32")
    if dtype == "float32":
        x, q = make_data(ds["data_seed"], seed, n_queries=n_queries,
                         devices=devices, **sizes)
    elif dtype in BYTE_RANGES:
        x, q = make_bytes(ds["data_seed"], seed, n_queries=n_queries,
                          dtype=dtype, devices=devices,
                          **{k: ds[k] for k in BYTE_KEYS}, **sizes)
    else:
        raise ValueError(f"dataset dtype {dtype!r}: the harness makes "
                         f"float32, {', '.join(BYTE_RANGES)}")
    return Corpus(x), q
