"""Pallas TPU kernel: tiled squared-L2 distance matrix.

The paper's hottest compute loop (Fig 2: distance comps are 29–51% of
search cost) mapped onto the MXU: per (query-tile, database-tile) the
kernel accumulates

    out[i, j] = ‖q_i‖² + ‖x_j‖² − 2·q_i·x_j

over D-tiles streamed HBM→VMEM.  The inner product rides the systolic
array (jnp.dot with f32/int32 accumulation); the norm terms are computed
tile-locally and folded into the same accumulator, so the distance matrix
never materialises in more than one VMEM tile per grid cell.

Grid: (Q/BQ, N/BN, D/BD) with the last axis as the reduction loop
(out BlockSpec ignores it; accumulate in-place, zero-init at k==0).

dtypes: float32, bfloat16 (f32 accumulate), int8 (int32 accumulate —
exact, serving the paper's quantized-dataset studies §5.2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _dist_kernel(q_ref, x_ref, o_ref, *, acc_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    q = q_ref[...]          # (BQ, BD)
    x = x_ref[...]          # (BN, BD)
    if acc_dtype == jnp.int32:
        qa = q.astype(jnp.int32)
        xa = x.astype(jnp.int32)
    else:
        qa = q.astype(acc_dtype)
        xa = x.astype(acc_dtype)
    qn = jnp.sum(qa * qa, axis=-1)[:, None]      # (BQ, 1)
    xn = jnp.sum(xa * xa, axis=-1)[None, :]      # (1, BN)
    # f32 at HIGHEST: the TPU default would round f32 operands to bf16
    ip = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())),
        precision=(None if acc_dtype == jnp.int32
                   else jax.lax.Precision.HIGHEST),
        preferred_element_type=acc_dtype)        # (BQ, BN) on the MXU
    o_ref[...] += qn + xn - 2 * ip


def _pad_to(a: jax.Array, mult: int, axis: int) -> jax.Array:
    size = a.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, rem)
    return jnp.pad(a, widths)


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_n", "block_d", "interpret"))
def l2_distance(
    q: jax.Array,            # (Q, D)
    x: jax.Array,            # (N, D)
    *,
    block_q: int = 128,
    block_n: int = 256,
    block_d: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Squared-L2 distance matrix (Q, N), f32 (exact int32 path for int8).

    VMEM working set per grid cell:
      BQ*BD + BN*BD inputs + BQ*BN accumulator
      (defaults: 128*256 + 256*256 + 128*256 f32 ≈ 0.6 MB — well under
      the ~16 MB v5e VMEM budget, leaving room for double buffering).
    """
    Q, D = q.shape
    N, _ = x.shape
    is_int = q.dtype == jnp.int8
    acc_dtype = jnp.int32 if is_int else jnp.float32

    bq, bn, bd = min(block_q, Q), min(block_n, N), min(block_d, D)
    qp = _pad_to(_pad_to(q, bq, 0), bd, 1)
    xp = _pad_to(_pad_to(x, bn, 0), bd, 1)
    Qp, Dp = qp.shape
    Np, _ = xp.shape
    grid = (Qp // bq, Np // bn, Dp // bd)

    out = pl.pallas_call(
        functools.partial(_dist_kernel, acc_dtype=acc_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, bd), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bd), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Qp, Np), acc_dtype),
        interpret=interpret,
    )(qp, xp)
    out = out[:Q, :N].astype(jnp.float32)
    if not is_int:
        out = jnp.maximum(out, 0.0)
    return out
