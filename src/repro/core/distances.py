"""Distance computation — the paper's dominant compute cost (Fig 2).

Two execution paths share one interface:

* ``pairwise_sq_l2`` / ``pairwise_neg_ip``: pure-jnp reference path, used by
  index build, the host-side (simulated-cloud) serving engine, and as the
  oracle for the Pallas kernels.
* ``repro.kernels.ops``: Pallas TPU kernels (MXU-tiled) used on the device
  serving path; they are validated against these functions in
  ``tests/test_kernels_*``.

TPU adaptation note: the paper's x86 SIMD distance loops become matmuls via
``‖a−b‖² = ‖a‖² − 2·a·b + ‖b‖²`` so that the 128×128 MXU does the heavy
lifting.  int8 datasets (MSSPACE/BIGANN analogues, §5.2) accumulate in int32
on the MXU integer path and are only widened at the end.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

#: Precision of every f32 distance dot.  The TPU's default rounds f32
#: operands to bf16 for a single MXU pass, which reorders near neighbours
#: against the host (numpy) reference; HIGHEST keeps f32 accuracy.  On the
#: CPU it changes nothing.
F32_DOT = jax.lax.Precision.HIGHEST


def _as_f32(x: Array) -> Array:
    return x.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=())
def pairwise_sq_l2(q: Array, x: Array) -> Array:
    """Squared L2 distances.  q: (Q, D), x: (N, D) -> (Q, N) float32.

    Supports float32/bfloat16/int8 inputs; accumulation is always f32
    (int8 inputs go through the int32 dot-product path first).
    """
    if q.dtype == jnp.int8 or x.dtype == jnp.int8:
        qi = q.astype(jnp.int32)
        xi = x.astype(jnp.int32)
        qn = jnp.sum(qi * qi, axis=-1, dtype=jnp.int32)[:, None]
        xn = jnp.sum(xi * xi, axis=-1, dtype=jnp.int32)[None, :]
        ip = jax.lax.dot_general(
            q, x,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        return (qn + xn - 2 * ip).astype(jnp.float32)
    qf, xf = _as_f32(q), _as_f32(x)
    qn = jnp.sum(qf * qf, axis=-1)[:, None]
    xn = jnp.sum(xf * xf, axis=-1)[None, :]
    ip = jax.lax.dot_general(
        qf, xf,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=F32_DOT,
        preferred_element_type=jnp.float32,
    )
    d = qn + xn - 2.0 * ip
    return jnp.maximum(d, 0.0)


@jax.jit
def pairwise_neg_ip(q: Array, x: Array) -> Array:
    """Negative inner product (smaller = closer), (Q, D)x(N, D) -> (Q, N)."""
    ip = jax.lax.dot_general(
        _as_f32(q), _as_f32(x),
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=F32_DOT,
        preferred_element_type=jnp.float32,
    )
    return -ip


def pairwise(q: Array, x: Array, metric: str = "l2") -> Array:
    if metric == "l2":
        return pairwise_sq_l2(q, x)
    if metric == "ip":
        return pairwise_neg_ip(q, x)
    raise ValueError(f"unknown metric {metric!r}")


@functools.partial(jax.jit, static_argnames=("k",))
def topk_smallest(d: Array, k: int) -> tuple[Array, Array]:
    """Top-k smallest along the last axis -> (values, indices)."""
    neg_vals, idx = jax.lax.top_k(-d, k)
    return -neg_vals, idx


# ---------------------------------------------------------------------------
# numpy host-path (used inside the discrete-event serving engine where data
# arrives as numpy objects from the simulated object store; keeping this in
# numpy avoids host<->device ping-pong for tiny per-round batches).
# ---------------------------------------------------------------------------

def np_sq_l2(q: np.ndarray, x: np.ndarray) -> np.ndarray:
    """q: (D,) or (Q, D); x: (N, D) -> (N,) or (Q, N), float32."""
    q = np.asarray(q, dtype=np.float32)
    x = np.asarray(x, dtype=np.float32)
    single = q.ndim == 1
    if single:
        q = q[None]
    qn = np.einsum("qd,qd->q", q, q)[:, None]
    xn = np.einsum("nd,nd->n", x, x)[None, :]
    d = qn + xn - 2.0 * (q @ x.T)
    np.maximum(d, 0.0, out=d)
    return d[0] if single else d
