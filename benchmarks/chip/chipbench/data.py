"""Corpus and query pool, made on the device from the run's seed.

The recipe is that of ``repro.data.synth.make_dataset``, redone with
``jax.random`` so that a corpus of millions of vectors is made in one
jitted call instead of on the host: a mixture of ``n_clusters`` Gaussians
on a random ``intrinsic_dim``-dimensional subspace, with per-cluster
scales, plus a little full-rank noise.  Queries are perturbed corpus
points, so they lie on the data manifold.  The same seed gives the same
corpus and queries; every seed gives the same sizes.

The element type is the configuration's ``dtype`` (:data:`DTYPES`).  An
integer corpus is the same float mixture carried onto the type's integers
by one affine map, fixed from the corpus alone by a maximum or a minimum
(no sort), then rounded and clipped; the queries go through the corpus's
map:

* ``int8``: ``repro.data.synth``'s recipe, ``x * 127 / max|x|``, rounded,
  clipped to [-127, 127];
* ``uint8``: ``(x - min x) * 255 / (max x - min x)``, rounded, clipped to
  [0, 255].

No corpus coordinate clips; a query coordinate clips only beyond the
corpus's extremes, far under one in a thousand.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
#: the element types a configuration's ``dtype`` may name
DTYPES = ("float32", "int8", "uint8")
#: each integer type's range
_RANGE = {"int8": (-127, 127), "uint8": (0, 255)}


def element_type(config: dict) -> str:
    """The configuration's ``dtype``; ``ValueError`` for any other than
    :data:`DTYPES`, a missing one too."""
    dtype = config.get("dtype")
    if dtype not in DTYPES:
        raise ValueError(f"the configuration's dtype must be one of "
                         f"{', '.join(DTYPES)}, not {dtype!r}")
    return dtype


def affine_map(data, dtype: str):
    """(offset, scale) of the map ``x * scale + offset`` that carries the
    float corpus ``data`` onto the integers of ``dtype``."""
    if dtype == "int8":
        return 0.0, 127.0 / (jnp.max(jnp.abs(data)) + 1e-9)
    lo, hi = jnp.min(data), jnp.max(data)
    scale = 255.0 / (hi - lo + 1e-9)
    return -lo * scale, scale


def quantize(x, offset, scale, dtype: str):
    """``x`` through the map (:func:`affine_map`), rounded, clipped to the
    range of ``dtype`` and stored in it."""
    lo, hi = _RANGE[dtype]
    return jnp.clip(jnp.round(x * scale + offset), lo, hi).astype(dtype)


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
    "noise_std", "dtype"))
def make_corpus(key, *, n, n_pool, dim, intrinsic_dim, dtype="float32",
                n_clusters=64, cluster_std=0.35, noise_std=0.02):
    """(corpus (n, dim), query pool (n_pool, dim)) of ``dtype`` from
    ``key``."""
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
    if dtype == "float32":
        return data.astype(jnp.float32), queries.astype(jnp.float32)
    offset, scale = affine_map(data, dtype)
    return (quantize(data, offset, scale, dtype),
            quantize(queries, offset, scale, dtype))
