"""Corpus and query pool, made on the device.

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
distribution. It is one jitted call, so set-up pays one small program
and no host-side generation.

The corpus comes from the configuration's ``data_seed`` and the query
pool from the run's ``--seed``: an IVF index's padded list length (its
executables' shapes) follows from the corpus, so a corpus drawn per run
would compile the served programs anew in every run. Every seed thus
serves the same index with other queries in another order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

GENERATOR_KEYS = ("n", "dim", "n_clusters", "intrinsic_dim",
                  "center_scale", "noise", "data_seed")


def seed_key(seed: int) -> jax.Array:
    """A threefry key for any whole-number seed (taken mod 2**64):
    the same key ``jax.random.key(seed)`` gives for seeds below 2**63."""
    s = int(seed) % (1 << 64)
    return jax.random.wrap_key_data(
        jnp.array([s >> 32, s & 0xFFFFFFFF], jnp.uint32))


@functools.partial(jax.jit, static_argnames=(
    "n", "dim", "n_queries", "n_clusters", "intrinsic_dim"))
def _mixture(data_key, query_key, center_scale, noise, *, n: int, dim: int,
             n_queries: int, n_clusters: int, intrinsic_dim: int):
    kb, kc, ka, kz, ke = jax.random.split(data_key, 5)
    kqa, kqz, kqe = jax.random.split(query_key, 3)
    m = intrinsic_dim
    basis = jnp.linalg.qr(jax.random.normal(kb, (dim, m), jnp.float32))[0].T
    centers = center_scale * jax.random.normal(kc, (n_clusters, m),
                                               jnp.float32)

    def draw(k_assign, k_latent, k_noise, rows):
        z = jax.random.normal(k_latent, (rows, m), jnp.float32)
        z = z + centers[jax.random.randint(k_assign, (rows,), 0, n_clusters)]
        e = noise * jax.random.normal(k_noise, (rows, dim), jnp.float32)
        return jnp.dot(z, basis, precision=jax.lax.Precision.HIGHEST) + e

    return draw(ka, kz, ke, n), draw(kqa, kqz, kqe, n_queries)


def make_data(data_seed: int, seed: int, *, n: int, dim: int,
              n_queries: int, n_clusters: int, intrinsic_dim: int,
              center_scale: float, noise: float):
    """``(x (n, dim), queries (n_queries, dim))`` float32 on the
    default device: the corpus a pure function of ``data_seed``, the
    queries of ``seed``, both of the sizes."""
    x, q = _mixture(seed_key(data_seed), seed_key(seed),
                    jnp.float32(center_scale), jnp.float32(noise), n=n,
                    dim=dim, n_queries=n_queries, n_clusters=n_clusters,
                    intrinsic_dim=intrinsic_dim)
    return jax.block_until_ready(x), jax.block_until_ready(q)


def for_dataset(ds: dict, seed: int, n_queries: int):
    """:func:`make_data` with a configuration's ``dataset`` keys."""
    return make_data(ds["data_seed"], seed, n_queries=n_queries,
                     **{k: ds[k] for k in GENERATOR_KEYS if k != "data_seed"})
