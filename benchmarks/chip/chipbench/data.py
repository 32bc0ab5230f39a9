"""Corpus and query pool, made on the device from the run's seed.

The recipe is that of ``repro.data.synth.make_dataset``, redone with
``jax.random`` so that a corpus of millions of vectors is made in one
jitted call instead of on the host: a mixture of ``n_clusters`` Gaussians
on a random ``intrinsic_dim``-dimensional subspace, with per-cluster
scales, plus a little full-rank noise.  Queries are perturbed corpus
points, so they lie on the data manifold.  The same seed gives the same
corpus and queries; every seed gives the same sizes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any seed in [0, 2**62), also one wider than 32 bits.

    The ``rbg`` generator compiles the corpus in a third of threefry's
    time on the TPU; it is deterministic on one backend, which is what
    "the same seed gives the same inputs" needs.
    """
    if not 0 <= seed < 2 ** 62:
        raise ValueError(f"seed must be in [0, 2**62), got {seed}")
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, seed >> 31)


@functools.partial(jax.jit, static_argnames=(
    "n", "n_pool", "dim", "intrinsic_dim", "n_clusters", "cluster_std",
    "noise_std"))
def make_corpus(key, *, n, n_pool, dim, intrinsic_dim, n_clusters=64,
                cluster_std=0.35, noise_std=0.02):
    """(corpus (n, dim) f32, query pool (n_pool, dim) f32) from ``key``."""
    kb, kc, ks, ka, kz, kn, kq, kqz, kqn = jax.random.split(key, 9)
    r = min(intrinsic_dim, dim)
    basis = jax.random.normal(kb, (r, dim)) / jnp.sqrt(float(r))
    centers = jax.random.normal(kc, (n_clusters, r))
    scales = jax.random.uniform(ks, (n_clusters,), minval=0.3,
                                maxval=1.2) * cluster_std
    assign = jax.random.randint(ka, (n,), 0, n_clusters)
    z = centers[assign] + jax.random.normal(kz, (n, r)) \
        * scales[assign][:, None]
    data = jnp.dot(z, basis, precision=HIGHEST) \
        + noise_std * jax.random.normal(kn, (n, dim))
    qi = jax.random.randint(kq, (n_pool,), 0, n)
    qz = z[qi] + jax.random.normal(kqz, (n_pool, r)) \
        * (0.5 * scales[assign[qi]])[:, None]
    queries = jnp.dot(qz, basis, precision=HIGHEST) \
        + noise_std * jax.random.normal(kqn, (n_pool, dim))
    return data.astype(jnp.float32), queries.astype(jnp.float32)
