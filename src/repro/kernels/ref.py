"""Pure-jnp oracles for every Pallas kernel in this package.

Kernel tests sweep shapes/dtypes and assert_allclose against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def l2_distance_ref(q: jax.Array, x: jax.Array) -> jax.Array:
    """Squared L2: q (Q, D), x (N, D) -> (Q, N).

    f32 accumulation for float inputs; exact int32 accumulation for int8
    and uint8, widened to int32 before any product.
    """
    if jnp.issubdtype(q.dtype, jnp.integer):
        qi, xi = q.astype(jnp.int32), x.astype(jnp.int32)
        qn = jnp.sum(qi * qi, axis=-1)[:, None]
        xn = jnp.sum(xi * xi, axis=-1)[None, :]
        ip = jax.lax.dot_general(qi, xi, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.int32)
        return (qn + xn - 2 * ip).astype(jnp.float32)
    qf, xf = q.astype(jnp.float32), x.astype(jnp.float32)
    qn = jnp.sum(qf * qf, axis=-1)[:, None]
    xn = jnp.sum(xf * xf, axis=-1)[None, :]
    ip = jax.lax.dot_general(qf, xf, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    return jnp.maximum(qn + xn - 2.0 * ip, 0.0)


def adc_lookup_ref(codes: jax.Array, table: jax.Array) -> jax.Array:
    """PQ asymmetric distance: codes (N, m) int, table (m, 256) f32 -> (N,).

    out[n] = sum_m table[m, codes[n, m]]
    """
    m = table.shape[0]
    gathered = jnp.take_along_axis(
        table.T[None],                       # (1, 256, m)
        codes.astype(jnp.int32)[:, None, :], # (N, 1, m)
        axis=1,
    )[:, 0, :]                               # (N, m)
    return gathered.sum(axis=-1).astype(jnp.float32)


def l2_topk_ref(q: jax.Array, x: jax.Array, k: int
                ) -> tuple[jax.Array, jax.Array]:
    """Fused distance + top-k oracle: returns (dists (Q, k), ids (Q, k))."""
    d = l2_distance_ref(q, x)
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, idx


def list_scan_ref(queries: jax.Array, list_vecs: jax.Array,
                  list_ids: jax.Array, probe: jax.Array) -> jax.Array:
    """Probed-list distances: the gather-then-scan the list-scan kernel
    replaces.  queries (B, D), list_vecs (L, slots, D) of the queries' type,
    list_ids (L, slots) (-1 pad), probe (B, nprobe) -> (B, nprobe, slots),
    ``inf`` at padding; exact on int8 and uint8."""
    vecs = list_vecs[probe]                          # (B, nprobe, slots, D)
    d = jax.vmap(lambda q, v: l2_distance_ref(q[None], v.reshape(
        -1, v.shape[-1]))[0].reshape(v.shape[:2]))(queries, vecs)
    return jnp.where(list_ids[probe] < 0, jnp.inf, d)
