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

def _lists(n_lists, slots, d, seed=0):
    """Padded posting lists: random lengths, list 1 all padding, list 2
    with a hole in the middle of its first chunk, list 3 with a hole of
    whole chunks between real rows; padding rows zero."""
    rng = np.random.default_rng(seed)
    vecs = rng.normal(size=(n_lists, slots, d)).astype(np.float32)
    ids = np.full((n_lists, slots), -1, np.int32)
    for li in range(n_lists):
        n = int(rng.integers(1, slots + 1))
        ids[li, :n] = li * slots + np.arange(n)
    ids[1] = -1
    ids[2] = 2 * slots + np.arange(slots)
    ids[2, 3:11] = -1
    ids[3] = 3 * slots + np.arange(slots)
    ids[3, 128:slots - 64] = -1
    vecs[ids < 0] = 0.0
    return vecs, ids


@pytest.mark.parametrize("b,nprobe,n_lists,slots,d", [
    (3, 4, 9, 640, 96),      # DEEP width; 5 chunks of 128, 3 steps of 4
    (2, 4, 6, 384, 200),     # wider than a lane tile, not a multiple of it
    (2, 3, 5, 300, 96),      # slots not a multiple of 128: padded to 384
    (1, 4, 6, 512, 96),      # one query; 2 chunks of 256
    (1, 3, 4, 1152, 960),    # GIST width: one whole list a step
])
def test_list_scan_matches_ref(b, nprobe, n_lists, slots, d):
    vecs, ids = _lists(n_lists, slots, d)
    rng = np.random.default_rng(1)
    probe = np.stack([rng.permutation(n_lists)[:nprobe] for _ in range(b)])
    probe[0, :min(nprobe, 4)] = [1, 2, 3, 0][:nprobe]   # the holed lists
    probe = probe.astype(np.int32)
    qs = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    lv, li, pj = jnp.asarray(vecs), jnp.asarray(ids), jnp.asarray(probe)
    got = np.asarray(ops.list_scan(qs, lv, pj, li[pj], interpret=True))
    assert got.shape == (b, nprobe, slots)
    # a chunk with no real row is skipped and reads inf; every other slot
    # holds its distance
    chunk, n_chunks = list_scan.chunking(slots, d)
    held = np.zeros((b, nprobe, n_chunks * chunk), bool)
    held[..., :slots] = ids[probe] >= 0
    held = np.repeat(held.reshape(b, nprobe, n_chunks, chunk).any(-1),
                     chunk, axis=-1)[..., :slots]
    np.testing.assert_array_equal(np.isinf(got), ~held)
    want = np.asarray(list_scan_ref(qs, lv, li, pj))
    got = np.where(ids[probe] < 0, np.inf, got)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("slots,dim", [(616, 96), (1536, 96), (1792, 960),
                                       (4000, 96), (10_000, 96)])
def test_list_scan_chunks_tile_the_slots(slots, dim):
    # equal chunks of whole 128-slot tiles, one bit each in an int32 word
    chunk, n_chunks = list_scan.chunking(slots, dim)
    assert chunk % 128 == 0 and n_chunks <= 31
    assert chunk * n_chunks == -(-slots // 128) * 128


@pytest.mark.parametrize("slots", [1024, 1000])
def test_list_scan_chunk_counts_match_a_count(slots):
    _, ids = _lists(12, slots, 96, seed=2)
    probe = np.random.default_rng(3).integers(0, 12, size=(5, 8))
    chunk, n_chunks = list_scan.chunking(slots, 96)
    assert (chunk, n_chunks) == (256, 4)
    fetched = sum(
        int((ids[lst, c * chunk:(c + 1) * chunk] >= 0).any())
        for lst in probe.ravel() for c in range(n_chunks))
    assert list_scan.chunk_counts(ids, probe, 96) == (
        fetched, probe.size * n_chunks - fetched)
