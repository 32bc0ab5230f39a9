"""Pallas TPU kernel: fused squared-L2 distance + running top-k.

Cluster-index scanning is distance-then-top-k over every probed posting
list (§2.3.1).  Materialising the (Q, N) distance matrix in HBM makes the
scan memory-bound; this kernel keeps a running per-query top-k in the
output VMEM block while streaming database tiles, so HBM traffic is
O(Q·D + N·D + Q·k) instead of O(Q·N).

Top-k inside the kernel is k rounds of Mosaic-safe min-extraction
(min-reduce + id-tiebreak + mask) — no sort/argmin primitives, so it
lowers on both interpret mode and real TPU.

Grid: (Q/BQ, N/BN); the N axis is innermost and revisits the same output
block (zero-init at j==0, merge per tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_BIG = 3.4e38            # python scalars: Pallas kernels cannot capture
_BIG_ID = 2**31 - 1      # tracers/arrays from the enclosing scope


def _fused_kernel(q_ref, x_ref, vals_ref, ids_ref, *, k, bn, n_total):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        vals_ref[...] = jnp.full_like(vals_ref, _BIG)
        ids_ref[...] = jnp.full_like(ids_ref, -1)

    q = q_ref[...].astype(jnp.float32)            # (BQ, D)
    x = x_ref[...].astype(jnp.float32)            # (BN, D)
    qn = jnp.sum(q * q, axis=-1)[:, None]
    xn = jnp.sum(x * x, axis=-1)[None, :]
    ip = jax.lax.dot_general(q, x, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    d = jnp.maximum(qn + xn - 2.0 * ip, 0.0)      # (BQ, BN)

    tile_ids = j * bn + jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    d = jnp.where(tile_ids < n_total, d, _BIG)    # mask padding rows

    cand_vals = jnp.concatenate([vals_ref[...], d], axis=1)
    cand_ids = jnp.concatenate([ids_ref[...], tile_ids], axis=1)
    new_vals = []
    new_ids = []
    for _ in range(k):                            # static unroll, k small
        mv = jnp.min(cand_vals, axis=1, keepdims=True)          # (BQ, 1)
        sel = jnp.where(cand_vals <= mv, cand_ids, _BIG_ID)
        mid = jnp.min(sel, axis=1, keepdims=True)               # (BQ, 1)
        new_vals.append(mv)
        new_ids.append(mid)
        cand_vals = jnp.where(cand_ids == mid, _BIG, cand_vals)
    vals_ref[...] = jnp.concatenate(new_vals, axis=1)
    ids_ref[...] = jnp.concatenate(new_ids, axis=1).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("k", "block_q", "block_n", "interpret"))
def l2_topk(
    q: jax.Array,            # (Q, D)
    x: jax.Array,            # (N, D)
    k: int = 10,
    *,
    block_q: int = 128,
    block_n: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused top-k nearest: returns (dists (Q, k) f32, ids (Q, k) int32).

    VMEM per cell (defaults, D=1024): 128*1024 + 512*1024 f32 + merge
    buffers ≈ 2.7 MB.  D is taken whole per block (fine to D≈4k).
    """
    Q, D = q.shape
    N, _ = x.shape
    qf = q.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    bq, bn = min(block_q, Q), min(block_n, max(N, k))

    remq = (-Q) % bq
    qp = jnp.pad(qf, ((0, remq), (0, 0))) if remq else qf
    remn = (-N) % bn
    xp = jnp.pad(xf, ((0, remn), (0, 0))) if remn else xf
    Qp, Np = qp.shape[0], xp.shape[0]

    vals, ids = pl.pallas_call(
        functools.partial(_fused_kernel, k=k, bn=bn, n_total=N),
        grid=(Qp // bq, Np // bn),
        in_specs=[
            pl.BlockSpec((bq, D), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, D), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Qp, k), jnp.float32),
            jax.ShapeDtypeStruct((Qp, k), jnp.int32),
        ],
        interpret=interpret,
    )(qp, xp)
    return vals[:Q], ids[:Q]
