"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles.

Kernels run in interpret mode on CPU (the kernel bodies execute exactly as
they would tile on TPU; Mosaic lowering is exercised on real hardware).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import list_scan, ops
from repro.kernels.ref import (adc_lookup_ref, l2_distance_ref, l2_topk_ref,
                               list_scan_ref)


def _mk(q, n, d, dtype, seed=0):
    rng = np.random.default_rng(seed)
    if dtype == "int8":
        qs = rng.integers(-127, 128, size=(q, d)).astype(np.int8)
        xs = rng.integers(-127, 128, size=(n, d)).astype(np.int8)
    elif dtype == "bfloat16":
        qs = rng.normal(size=(q, d)).astype(jnp.bfloat16)
        xs = rng.normal(size=(n, d)).astype(jnp.bfloat16)
    else:
        qs = rng.normal(size=(q, d)).astype(np.float32)
        xs = rng.normal(size=(n, d)).astype(np.float32)
    return jnp.asarray(qs), jnp.asarray(xs)


# ------------------------------------------------------------- distance --

@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("q,n,d", [
    (4, 16, 8),          # tiny, everything padded
    (128, 256, 256),     # exact tile multiples
    (100, 300, 96),      # deep-analog dims, ragged tiles
    (7, 513, 960),       # gist-analog dims, ragged everywhere
])
def test_l2_distance_matches_ref(dtype, q, n, d):
    qs, xs = _mk(q, n, d, dtype)
    got = ops.l2_distance(qs, xs, interpret=True)
    want = l2_distance_ref(qs, xs)
    assert got.shape == (q, n)
    if dtype == "int8":
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        rtol = 2e-2 if dtype == "bfloat16" else 1e-5
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=rtol, atol=1e-2)


@pytest.mark.parametrize("dtype", [np.uint8, np.int8])
def test_l2_distance_ref_is_exact_on_8bit_extremes(dtype):
    # 0 against 255 (uint8) or -127 against 127 (int8) in every coordinate:
    # widened before any product, so nothing wraps
    info = np.iinfo(dtype)
    lo, hi = max(info.min, -127), info.max
    q = jnp.full((1, 128), lo, dtype)
    x = jnp.stack([jnp.full((128,), hi, dtype), jnp.full((128,), lo, dtype)])
    got = np.asarray(l2_distance_ref(q, x))
    np.testing.assert_array_equal(got, [[128 * (hi - lo) ** 2, 0]])


@pytest.mark.parametrize("blocks", [(32, 32, 32), (64, 128, 64)])
def test_l2_distance_block_shape_independent(blocks):
    bq, bn, bd = blocks
    qs, xs = _mk(50, 130, 100, "float32")
    got = ops.l2_distance(qs, xs, interpret=True,
                          block_q=bq, block_n=bn, block_d=bd)
    want = l2_distance_ref(qs, xs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-3)


# ------------------------------------------------------------------ ADC --

@pytest.mark.parametrize("n,m", [(10, 8), (1024, 48), (2000, 112), (3, 120)])
def test_adc_lookup_matches_ref(n, m):
    rng = np.random.default_rng(0)
    codes = jnp.asarray(rng.integers(0, 256, size=(n, m)).astype(np.uint8))
    table = jnp.asarray(rng.random((m, 256)).astype(np.float32))
    got = ops.adc_lookup(codes, table, interpret=True)
    want = adc_lookup_ref(codes, table)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_adc_lookup_matches_pq_module():
    """Kernel agrees with the ProductQuantizer host path end-to-end."""
    from repro.core.pq import train_pq
    rng = np.random.default_rng(0)
    x = rng.normal(size=(500, 96)).astype(np.float32)
    pq = train_pq(x, m=48, iters=4, seed=0)
    codes = pq.encode(x)
    table = pq.adc_table(x[0])
    got = ops.adc_lookup(jnp.asarray(codes), jnp.asarray(table),
                         interpret=True)
    want = pq.adc_lookup(codes, table)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-3)


# ----------------------------------------------------------- fused topk --

@pytest.mark.parametrize("q,n,d,k", [
    (4, 64, 32, 5),
    (128, 1024, 96, 10),
    (33, 700, 960, 10),
    (1, 2048, 128, 20),
])
def test_l2_topk_matches_ref(q, n, d, k):
    qs, xs = _mk(q, n, d, "float32")
    vals, ids = ops.l2_topk(qs, xs, k, interpret=True)
    rvals, rids = l2_topk_ref(qs, xs, k)
    assert vals.shape == (q, k) and ids.shape == (q, k)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rvals),
                               rtol=1e-4, atol=1e-3)
    # ids may differ only on exact distance ties; check via distances
    d_by_id = np.take_along_axis(
        np.asarray(l2_distance_ref(qs, xs)), np.asarray(ids), axis=1)
    np.testing.assert_allclose(d_by_id, np.asarray(rvals),
                               rtol=1e-4, atol=1e-3)


def test_l2_topk_ids_unique_and_sorted():
    qs, xs = _mk(16, 512, 64, "float32", seed=3)
    vals, ids = ops.l2_topk(qs, xs, 10, interpret=True)
    vals, ids = np.asarray(vals), np.asarray(ids)
    for r in range(16):
        assert len(np.unique(ids[r])) == 10
        assert (np.diff(vals[r]) >= -1e-6).all()


def test_l2_topk_block_sweep():
    qs, xs = _mk(40, 333, 100, "float32", seed=4)
    rvals, _ = l2_topk_ref(qs, xs, 10)
    for bq, bn in [(16, 64), (64, 128), (128, 512)]:
        vals, _ = ops.l2_topk(qs, xs, 10, interpret=True,
                              block_q=bq, block_n=bn)
        np.testing.assert_allclose(np.asarray(vals), np.asarray(rvals),
                                   rtol=1e-4, atol=1e-3)


# ------------------------------------------------------------ list scan --

def _lists(n_lists, slots, d, seed=0, dtype="float32"):
    """Padded posting lists: random lengths, list 1 all padding, list 2
    with a hole in the middle of its first chunk, list 3 with a hole of
    whole chunks between real rows; padding rows zero.  Integer rows span
    their type's range, and list 0's first row is all at its maximum."""
    rng = np.random.default_rng(seed)
    if dtype == "float32":
        vecs = rng.normal(size=(n_lists, slots, d)).astype(np.float32)
    else:
        info = np.iinfo(dtype)
        vecs = rng.integers(info.min, info.max + 1,
                            size=(n_lists, slots, d)).astype(dtype)
        vecs[0, 0] = info.max
    ids = np.full((n_lists, slots), -1, np.int32)
    for li in range(n_lists):
        n = int(rng.integers(1, slots + 1))
        ids[li, :n] = li * slots + np.arange(n)
    ids[0, 0] = 0
    ids[1] = -1
    ids[2] = 2 * slots + np.arange(slots)
    ids[2, 3:11] = -1
    ids[3] = 3 * slots + np.arange(slots)
    ids[3, 128:slots - 64] = -1
    vecs[ids < 0] = 0
    return vecs, ids


def _queries(b, d, dtype, rng):
    """Queries of the rows' type; for integers, query 0 is all zeros."""
    if dtype == "float32":
        return rng.normal(size=(b, d)).astype(np.float32)
    info = np.iinfo(dtype)
    qs = rng.integers(info.min, info.max + 1, size=(b, d)).astype(dtype)
    qs[0] = 0
    return qs


@pytest.mark.parametrize("b,nprobe,n_lists,slots,d,dtype", [
    (3, 4, 9, 640, 96, "float32"),    # DEEP width; 5 chunks of 128, 3 steps of 4
    (2, 4, 6, 384, 200, "float32"),   # wider than a lane tile, not a multiple of it
    (2, 3, 5, 300, 96, "float32"),    # slots not a multiple of 128: padded to 384
    (1, 4, 6, 512, 96, "float32"),    # one query; 2 chunks of 256
    (1, 3, 4, 1152, 960, "float32"),  # GIST width: one whole list a step
    (2, 4, 6, 640, 256, "float32"),   # a multiple of 128: rows on the lanes
    (2, 4, 6, 512, 128, "float32"),   # SIFT's width in float32, likewise
    (2, 4, 6, 1280, 128, "uint8"),    # BIGANN width, rows on the lanes; a
                                      # row of 255s against a zero query
    (2, 3, 5, 640, 100, "int8"),      # 100-d int8, rows held at 128
])
def test_list_scan_matches_ref(b, nprobe, n_lists, slots, d, dtype):
    vecs, ids = _lists(n_lists, slots, d, dtype=dtype)
    rng = np.random.default_rng(1)
    probe = np.stack([rng.permutation(n_lists)[:nprobe] for _ in range(b)])
    probe[0, :min(nprobe, 4)] = [1, 2, 3, 0][:nprobe]   # the holed lists
    probe = probe.astype(np.int32)
    qs = jnp.asarray(_queries(b, d, dtype, rng))
    lv, li, pj = jnp.asarray(vecs), jnp.asarray(ids), jnp.asarray(probe)
    width = list_scan.row_width(d, np.dtype(dtype).itemsize)
    held = jnp.asarray(np.pad(vecs, ((0, 0), (0, 0), (0, width - d))))
    got = np.asarray(ops.list_scan(qs, held, pj, li[pj], interpret=True))
    assert got.shape == (b, nprobe, slots)
    # a chunk with no real row is skipped and reads inf; every other slot
    # holds its distance
    chunk, n_chunks = list_scan.chunking(slots, width,
                                         np.dtype(dtype).itemsize)
    real = np.zeros((b, nprobe, n_chunks * chunk), bool)
    real[..., :slots] = ids[probe] >= 0
    real = np.repeat(real.reshape(b, nprobe, n_chunks, chunk).any(-1),
                     chunk, axis=-1)[..., :slots]
    np.testing.assert_array_equal(np.isinf(got), ~real)
    want = np.asarray(list_scan_ref(qs, lv, li, pj))
    got = np.where(ids[probe] < 0, np.inf, got)
    if dtype == "float32":
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    else:
        np.testing.assert_array_equal(got, want)
        exact = ((vecs[probe].astype(np.int64)
                  - np.asarray(qs, np.int64)[:, None, None, :]) ** 2).sum(-1)
        np.testing.assert_array_equal(
            got, np.where(ids[probe] < 0, np.inf, exact))


def test_list_scan_pads_8bit_rows_off_the_lanes():
    # int8 rows of 100 are held padded to 128 (row_width); unpadded, the
    # kernel pads them itself and gives the same distances
    vecs, ids = _lists(5, 640, 100, dtype="int8")
    rng = np.random.default_rng(4)
    probe = jnp.asarray(np.stack([rng.permutation(5)[:3] for _ in range(2)])
                        .astype(np.int32))
    qs = jnp.asarray(_queries(2, 100, "int8", rng))
    li = jnp.asarray(ids)
    held = jnp.asarray(np.pad(vecs, ((0, 0), (0, 0), (0, 28))))
    got = np.asarray(ops.list_scan(qs, jnp.asarray(vecs), probe, li[probe],
                                   interpret=True))
    want = np.asarray(ops.list_scan(qs, held, probe, li[probe],
                                    interpret=True))
    np.testing.assert_array_equal(got, want)
    assert np.isfinite(got).any()


@pytest.mark.parametrize("slots,dim,itemsize", [
    (616, 96, 4), (1536, 96, 4), (1792, 960, 4), (4000, 96, 4),
    (10_000, 96, 4), (3072, 128, 1), (2560, 128, 1), (1280, 128, 1),
    (3072, 128, 4)])
def test_list_scan_chunks_tile_the_slots(slots, dim, itemsize):
    # equal chunks of whole 128-slot tiles, one bit each in an int32 word
    chunk, n_chunks = list_scan.chunking(slots, dim, itemsize)
    assert chunk % 128 == 0 and n_chunks <= 31
    assert chunk * n_chunks == -(-slots // 128) * 128


@pytest.mark.parametrize("slots,dim,itemsize,chunk", [
    (3072, 128, 1, 1024), (2560, 128, 1, 640), (1280, 128, 1, 640),
    (3072, 128, 4, 256), (1536, 96, 4, 256)])
def test_list_scan_chunks_count_bytes(slots, dim, itemsize, chunk):
    # a chunk's DMA stays within the byte target: a 1-byte row gets four
    # times the slots of a 4-byte one
    got, _ = list_scan.chunking(slots, dim, itemsize)
    assert got == chunk
    assert chunk * dim * itemsize <= list_scan._CHUNK_BYTES


@pytest.mark.parametrize("slots", [1024, 1000])
def test_list_scan_chunk_counts_match_a_count(slots):
    _, ids = _lists(12, slots, 96, seed=2)
    probe = np.random.default_rng(3).integers(0, 12, size=(5, 8))
    chunk, n_chunks = list_scan.chunking(slots, 96, 4)
    assert (chunk, n_chunks) == (256, 4)
    fetched = sum(
        int((ids[lst, c * chunk:(c + 1) * chunk] >= 0).any())
        for lst in probe.ravel() for c in range(n_chunks))
    assert list_scan.chunk_counts(ids, probe, 96, 4) == (
        fetched, probe.size * n_chunks - fetched)
