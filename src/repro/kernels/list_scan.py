"""Pallas TPU kernel: scan each probed posting list where it lies in HBM.

The resident search (``core.cluster_index.device_search_batch``) holds the
posting lists as one padded (L, slots, D) array.  Copying every probed list
out of it, a (B, nprobe, slots, D) tensor, and then reading the copy again
costs more than the whole rest of the search.  This kernel reads each
probed list once, from where it lies, and writes only the distances:

    out[b, p, s] = Σ_d (list_vecs[probe[b, p], s, d] − q[b, d])²

in float32 on the VPU (no MXU pass rounds an operand to bf16).

Layout.  TPU stores an (L, slots, D) array with the slots on the lanes
when that pads less than D on the lanes would (D = 96 or 960 against a
multiple of 128 slots), so the kernel reads the (L, D, slots) view of the
same bytes: a list is a (D, slots) matrix, a chunk of slots is a strided
DMA, and the sum over D runs down the sublanes into a lane-dense row.

Chunks.  The slots are cut into equal chunks of ``chunk`` slots; a bit per
(query, probe, chunk) says the chunk holds a real row.  Chunks without one
are neither fetched nor scanned, and read ``inf``, wherever they sit in the
list; padding inside a fetched chunk is the caller's to mask.

Grid: one step per ``group`` probes of one query, sequential.  Each step
starts the DMAs of the next step's real chunks into the other of two VMEM
buffers, waits for its own, and scans them, each in a loop over the
step's probes with the chunks unrolled inside.  The probe ids and chunk
bits are scalar-prefetched into SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128
#: bytes of one chunk's DMA the chunk size aims at
_CHUNK_BYTES = 128 * 1024
#: list bytes one grid step fetches at most (each of the two buffers)
_STEP_BYTES = 8 * 2**20
#: chunk bits per (query, probe) word
_MAX_CHUNKS = 31
#: probes a grid step scans at most: their distances are one tile of rows
_MAX_GROUP = 8


def chunking(slots: int, dim: int) -> tuple[int, int]:
    """(chunk, n_chunks) for lists of ``slots`` rows of ``dim`` floats.

    The chunks tile the slots rounded up to 128 (a DMA cuts the lanes of a
    tiled array only at multiples of 128) in equal parts of a multiple of
    128 slots: the most 128-slot tiles a part, dividing the tiles, whose
    DMA stays within ``_CHUNK_BYTES``, and at most ``_MAX_CHUNKS`` parts.
    """
    tiles = -(-slots // _LANE)
    want = _CHUNK_BYTES // (4 * dim * _LANE)
    fewest = -(-tiles // _MAX_CHUNKS)
    parts = [t for t in range(fewest, tiles + 1) if tiles % t == 0]
    per = max([t for t in parts if t <= want], default=parts[0])
    return per * _LANE, tiles // per


def _group(nprobe: int, list_bytes: int) -> int:
    """Probes a grid step scans: the largest divisor of ``nprobe``, at most
    ``_MAX_GROUP``, whose lists fit ``_STEP_BYTES`` (at least one)."""
    fits = [g for g in range(1, min(nprobe, _MAX_GROUP) + 1)
            if nprobe % g == 0 and g * list_bytes <= _STEP_BYTES]
    return max(fits, default=1)


def chunk_bits(ids: jax.Array, chunk: int, n_chunks: int) -> jax.Array:
    """(..., slots) int32 ids, -1 for padding -> (...) int32 whose bit c is
    set when chunk c holds a real row."""
    real = ids >= 0
    pad = n_chunks * chunk - ids.shape[-1]
    if pad:
        real = jnp.pad(real, [(0, 0)] * (real.ndim - 1) + [(0, pad)])
    held = jnp.any(real.reshape(*real.shape[:-1], n_chunks, chunk), axis=-1)
    weights = jnp.left_shift(1, jnp.arange(n_chunks, dtype=jnp.int32))
    return jnp.sum(held.astype(jnp.int32) * weights, axis=-1)


def chunk_counts(list_ids: np.ndarray, probe: np.ndarray,
                 dim: int) -> tuple[int, int]:
    """Chunks the kernel fetches and skips for ``probe`` (B, nprobe) over
    ``list_ids`` (L, slots), lists of ``dim`` floats a row."""
    chunk, n_chunks = chunking(list_ids.shape[1], dim)
    bits = np.asarray(chunk_bits(jnp.asarray(list_ids), chunk, n_chunks))
    held = (bits[np.asarray(probe)][..., None] >> np.arange(n_chunks)) & 1
    return int(held.sum()), int(held.size - held.sum())


def _scan_kernel(probe_ref, bits_ref, q_ref, x_hbm, out_ref, buf, sem, *,
                 group: int, chunk: int, n_chunks: int):
    step = pl.program_id(0)
    slot = step % 2

    def each_chunk(at, body):
        # body(g, lo, real) for chunk c of probe g of step ``at``: a loop
        # over the probes, the chunks unrolled in it.  The kernel's size,
        # and its tracing time, grow with the chunks but not the group.
        def one(g, carry):
            bits = bits_ref[at * group + g]
            for c in range(n_chunks):
                body(g, c * chunk, ((bits >> c) & 1) == 1)
            return carry
        jax.lax.fori_loop(0, group, one, 0)

    def copy(at, into, g, lo):
        return pltpu.make_async_copy(
            x_hbm.at[probe_ref[at * group + g], :, pl.ds(lo, chunk)],
            buf.at[into, g, :, pl.ds(lo, chunk)], sem.at[into])

    def start(at, into):
        each_chunk(at, lambda g, lo, real:
                   pl.when(real)(copy(at, into, g, lo).start))

    @pl.when(step == 0)
    def _():
        start(0, 0)

    @pl.when(step + 1 < pl.num_programs(0))
    def _():
        start(step + 1, 1 - slot)

    # one semaphore a buffer: every chunk is waited for before any is read
    each_chunk(step, lambda g, lo, real:
               pl.when(real)(copy(step, slot, g, lo).wait))

    # the step's distances: inf, then a row at a time in each real chunk.
    # A row's store is a select over the block's rows (at most 8, one tile
    # of sublanes): Mosaic stores no single row at a dynamic sublane.
    out_ref[...] = jnp.full(out_ref.shape, jnp.inf, jnp.float32)
    q = q_ref[...].astype(jnp.float32)                   # (D, 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (group, chunk), 0)

    def scan(g, lo, real):
        @pl.when(real)
        def _():
            d = buf[slot, g, :, pl.ds(lo, chunk)].astype(jnp.float32) - q
            dist = jnp.sum(d * d, axis=0, keepdims=True)     # (1, chunk)
            out_ref[:, pl.ds(lo, chunk)] = jnp.where(
                rows == g, dist, out_ref[:, pl.ds(lo, chunk)])

    each_chunk(step, scan)


@functools.partial(jax.jit, static_argnames=("interpret",))
def list_scan(
    queries: jax.Array,      # (B, D)
    list_vecs: jax.Array,    # (L, slots, D)
    probe: jax.Array,        # (B, nprobe) int32 list ids
    ids: jax.Array,          # (B, nprobe, slots) int32, list_ids[probe]
    *,
    interpret: bool = False,
) -> jax.Array:
    """Squared-L2 distances (B, nprobe, slots) f32 from each query to every
    slot of its probed lists; ``inf`` in chunks that hold no real row.

    VMEM: two buffers of ``group`` whole lists, (group, D, slots) f32 each
    (DEEP's 96 x 1,536 at group 8: 2 x 4.7 MB; GIST's 960 x 1,792 at group
    1: 2 x 6.9 MB), and two (group, slots) output blocks, the slots rounded
    up to 128.
    """
    B, D = queries.shape
    L, slots, _ = list_vecs.shape
    nprobe = probe.shape[1]
    chunk, n_chunks = chunking(slots, D)
    lanes = -(-slots // _LANE) * _LANE
    itemsize = jnp.dtype(list_vecs.dtype).itemsize
    group = _group(nprobe, D * lanes * itemsize)
    bits = chunk_bits(ids, chunk, n_chunks)
    buf_bytes = 2 * group * D * lanes * itemsize
    kernel = functools.partial(_scan_kernel, group=group, chunk=chunk,
                               n_chunks=n_chunks)
    # (L, D, slots): the bytes as TPU lays them out when the slots are on
    # the lanes.  A slot count that is not a multiple of 128 is padded to
    # one, which copies the lists on every call.
    lists = jnp.swapaxes(list_vecs, 1, 2)
    if lanes > slots:
        lists = jnp.pad(lists, ((0, 0), (0, 0), (0, lanes - slots)))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B * nprobe // group,),
            in_specs=[
                pl.BlockSpec((None, D, 1),
                             lambda s, *_: (s * group // nprobe, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, group, lanes),
                                   lambda s, *_: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, group, D, lanes), list_vecs.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=jax.ShapeDtypeStruct((B * nprobe // group, group, lanes),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=buf_bytes + 16 * 2**20),
        interpret=interpret,
    )(probe.reshape(-1).astype(jnp.int32), bits.reshape(-1),
      queries[:, :, None], lists)
    return out.reshape(B, nprobe, lanes)[..., :slots]
