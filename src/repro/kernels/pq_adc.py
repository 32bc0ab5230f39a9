"""Pallas TPU kernel: PQ asymmetric-distance (ADC) lookup.

x86/GPU ADC gathers from a 256-entry LUT per subquantizer (L1/shared-memory
resident).  TPUs have no fast per-lane gather, so the TPU-native idiom is a
one-hot × LUT matmul:

    out[n] = Σ_m  table[m, codes[n, m]]
           = Σ_m  table[m, :] · onehot(codes[n, m])

The whole table (m × 256 f32, ≤ 128 KB for m ≤ 128) is pinned in VMEM for
every grid step — the VMEM analogue of the paper's cache-resident LUT —
while code tiles stream through.  Codes arrive transposed, (m, N), so a
subquantizer's codes for a tile are one lane-dense row.  The kernel loops
over the m subquantizers; each step builds a (256, BN) one-hot on the VPU
and contracts it with one LUT row on the MXU, a plain 2-D
(1, 256) @ (256, BN) matmul, accumulating into a lane-dense (1, BN) row.

Grid: (N/BN,) over code tiles; the m loop is a ``fori_loop`` inside the
kernel (m is a small compile-time constant: paper Table 3 uses 48–112).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANE = 128


def _adc_kernel(codes_ref, table_ref, o_ref):
    m, bn = codes_ref.shape                          # (m, BN) int32
    iota = jax.lax.broadcasted_iota(jnp.int32, (256, bn), 0)

    def one_subquantizer(j, acc):
        onehot = (iota == codes_ref[pl.ds(j, 1), :]).astype(jnp.float32)
        # HIGHEST keeps the f32 LUT entries exact through the MXU (the
        # default precision would round them to bf16)
        return acc + jax.lax.dot_general(
            table_ref[pl.ds(j, 1), :], onehot, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)      # (1, BN)

    o_ref[...] = jax.lax.fori_loop(0, m, one_subquantizer,
                                   jnp.zeros((1, bn), jnp.float32))


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def adc_lookup(
    codes: jax.Array,        # (N, m) uint8/int32
    table: jax.Array,        # (m, 256) f32
    *,
    block_n: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    """ADC distances (N,) f32.

    VMEM per grid cell: m*BN int32 codes + m*256 table (both double
    buffered) + a 256*BN f32 one-hot + a BN output row (defaults, m=120:
    2*480 KB + 2*120 KB + 1 MB + 2*32 KB ≈ 2.3 MB, within v5e's 16 MB
    scoped VMEM).
    """
    N, m = codes.shape
    if table.shape != (m, 256):
        raise ValueError(f"table shape {table.shape} != ({m}, 256)")
    bn = min(block_n, -(-N // _LANE) * _LANE)
    codes_t = codes.astype(jnp.int32).T                  # (m, N)
    rem = (-N) % bn
    if rem:
        codes_t = jnp.pad(codes_t, ((0, 0), (0, rem)))
    Np = codes_t.shape[1]

    out = pl.pallas_call(
        _adc_kernel,
        grid=(Np // bn,),
        in_specs=[
            pl.BlockSpec((m, bn), lambda i: (0, i)),
            pl.BlockSpec((m, 256), lambda i: (0, 0)),   # VMEM-pinned LUT
        ],
        out_specs=pl.BlockSpec((1, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Np), jnp.float32),
        interpret=interpret,
    )(codes_t, table.astype(jnp.float32))
    return out[0, :N]
