import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cluster_index import ClusterIndex, device_search_batch
from repro.core.flat import exact_topk
from repro.core.types import ClusterIndexParams, SearchParams, recall_at_k
from repro.data.synth import DEEP_ANALOG, make_dataset, scaled


@pytest.fixture(scope="module")
def built():
    spec = scaled(DEEP_ANALOG, 2000, 20)
    data, queries = make_dataset(spec)
    gt, _ = exact_topk(data, queries, 10)
    idx = ClusterIndex.build(
        data, ClusterIndexParams(centroid_frac=0.16, num_replica=8, seed=0))
    return data, queries, gt, idx


def _mean_recall(idx, queries, gt, nprobe):
    recs = []
    for i, q in enumerate(queries):
        r = idx.search(q, SearchParams(k=10, nprobe=nprobe))
        recs.append(recall_at_k(r.ids, gt[i]))
    return float(np.mean(recs))


def test_recall_monotonic_in_nprobe(built):
    _, queries, gt, idx = built
    r8 = _mean_recall(idx, queries, gt, 8)
    r64 = _mean_recall(idx, queries, gt, 64)
    rmax = _mean_recall(idx, queries, gt, idx.meta.n_lists)
    assert r8 <= r64 + 0.05
    assert r64 <= rmax + 0.02
    assert rmax >= 0.99          # probing everything must be ~exact
    assert r64 >= 0.8


def test_no_duplicate_results(built):
    _, queries, gt, idx = built
    r = idx.search(queries[0], SearchParams(k=10, nprobe=32))
    valid = r.ids[r.ids >= 0]
    assert len(np.unique(valid)) == len(valid)


def test_metrics_consistency(built):
    _, queries, _, idx = built
    r = idx.search(queries[0], SearchParams(k=10, nprobe=16))
    m = r.metrics
    assert m.roundtrips == 1                   # dependency-free fetch
    assert m.requests == m.lists_visited == 16
    assert m.dist_comps > 0


def test_replication_increases_index_size():
    spec = scaled(DEEP_ANALOG, 1500, 10)
    data, _ = make_dataset(spec)
    i2 = ClusterIndex.build(data, ClusterIndexParams(num_replica=2, seed=0))
    i8 = ClusterIndex.build(data, ClusterIndexParams(num_replica=8, seed=0))
    assert i8.meta.index_bytes > i2.meta.index_bytes
    # paper Table 4: replication inflates size by <= ~3x vs 1-replica IVF
    assert i8.meta.index_bytes < 4 * i2.meta.index_bytes


def test_centroid_frac_controls_list_size():
    spec = scaled(DEEP_ANALOG, 1500, 10)
    data, _ = make_dataset(spec)
    i16 = ClusterIndex.build(
        data, ClusterIndexParams(centroid_frac=0.08, seed=0))
    i32 = ClusterIndex.build(
        data, ClusterIndexParams(centroid_frac=0.32, seed=0))
    assert i32.meta.n_lists > i16.meta.n_lists
    assert i32.meta.avg_list_bytes < i16.meta.avg_list_bytes


def test_device_search_matches_host(built):
    data, queries, gt, idx = built
    arrs = idx.device_arrays()
    ids, dists = device_search_batch(
        jnp.asarray(arrs["centroids"]), jnp.asarray(arrs["list_vecs"]),
        jnp.asarray(arrs["list_ids"]), jnp.asarray(queries, jnp.float32)[:8],
        nprobe=32, k=10)
    ids = np.asarray(ids)
    for i in range(8):
        host = idx.search(queries[i], SearchParams(k=10, nprobe=32))
        # same top-k set modulo centroid-selection (BKT vs flat) differences
        overlap = len(np.intersect1d(ids[i], host.ids)) / 10
        assert overlap >= 0.7, (i, ids[i], host.ids)
        assert recall_at_k(ids[i], gt[i]) >= 0.7


def test_device_search_dedups_points_replicated_in_every_probe():
    # 12 points sit in all 8 lists: the best 10 unique ids lie 80 deep in
    # the candidates, past a window of 4k
    L, ml, D, npt = 8, 16, 4, 12
    pts = np.zeros((npt, D), np.float32)
    pts[:, 0] = 0.1 * (np.arange(npt) + 1)
    vecs = np.zeros((L, ml, D), np.float32)
    vecs[:, :npt] = pts
    ids = np.full((L, ml), -1, np.int32)
    ids[:, :npt] = np.arange(npt)
    cents = np.random.default_rng(0).standard_normal((L, D)).astype(
        np.float32)
    got, vals = device_search_batch(
        jnp.asarray(cents), jnp.asarray(vecs), jnp.asarray(ids),
        jnp.zeros((1, D), jnp.float32), nprobe=L, k=10)
    np.testing.assert_array_equal(np.asarray(got)[0], np.arange(10))
    np.testing.assert_allclose(np.asarray(vals)[0], (pts[:10, 0]) ** 2,
                               rtol=1e-5)


def test_int8_dataset_build_and_search():
    from repro.data.synth import MSSPACE_ANALOG
    spec = scaled(MSSPACE_ANALOG, 1500, 10)
    data, queries = make_dataset(spec)
    assert data.dtype == np.int8
    gt, _ = exact_topk(data, queries, 10)
    idx = ClusterIndex.build(data, ClusterIndexParams(seed=0))
    r = _mean_recall(idx, queries, gt, 64)
    assert r >= 0.8
    # int8 posting lists are ~4x smaller than f32 would be
    assert idx.meta.avg_list_bytes < idx.meta.list_lengths.mean() * (
        spec.dim * 4 + 8)


def _uint8_corpus(n, nq, dim, seed):
    """A seeded uint8 corpus and queries: a Gaussian mixture carried onto
    [0, 255], as the chip benchmark's BIGANN analogue is."""
    data, queries = make_dataset(scaled(DEEP_ANALOG, n, nq, dim=dim,
                                        seed=seed))
    lo, hi = data.min(), data.max()

    def to_u8(x):
        return np.clip(np.round((x - lo) * 255 / (hi - lo)), 0, 255
                       ).astype(np.uint8)
    return to_u8(data), to_u8(queries)


@pytest.mark.parametrize("dtype,dim,width", [
    (np.uint8, 128, 128), (np.int8, 100, 128), (np.float32, 96, 96)])
def test_device_arrays_keep_the_element_type(dtype, dim, width):
    # 8-bit lists are held as bytes, padded with zeros to whole lane tiles
    data = np.random.default_rng(0).integers(
        -100, 100, size=(600, dim)).astype(dtype)
    idx = ClusterIndex.build(data, ClusterIndexParams(centroid_frac=0.05,
                                                      seed=0))
    arrs = idx.device_arrays()
    vecs, ids = arrs["list_vecs"], arrs["list_ids"]
    assert vecs.dtype == dtype and vecs.shape[2] == width
    assert arrs["centroids"].dtype == np.float32
    real = ids >= 0
    np.testing.assert_array_equal(vecs[real][:, :dim], data[ids[real]])
    assert not vecs[..., dim:].any() and not vecs[~real].any()


def test_device_search_uint8_matches_exact_host_search():
    # every distance an exact integer: the device search returns the ids
    # and distances of a float64 search over the same probed lists
    data, queries = _uint8_corpus(1500, 16, 128, seed=3)
    idx = ClusterIndex.build(data, ClusterIndexParams(centroid_frac=0.04,
                                                      seed=0))
    arrs = idx.device_arrays()
    assert arrs["list_vecs"].dtype == np.uint8
    nprobe, k = 8, 10
    ids, dists = device_search_batch(
        jnp.asarray(arrs["centroids"]), jnp.asarray(arrs["list_vecs"]),
        jnp.asarray(arrs["list_ids"]), jnp.asarray(queries),
        nprobe=nprobe, k=k)
    ids, dists = np.asarray(ids), np.asarray(dists)
    q64 = queries.astype(np.float64)
    c64 = arrs["centroids"].astype(np.float64)
    cd = ((q64[:, None, :] - c64[None, :, :]) ** 2).sum(-1)
    for i in range(len(queries)):
        probed = np.argsort(cd[i], kind="stable")[:nprobe]
        cand = arrs["list_ids"][probed].ravel()
        cand = np.unique(cand[cand >= 0])
        exact = ((data[cand].astype(np.int64) - queries[i].astype(np.int64))
                 ** 2).sum(-1)
        order = np.lexsort((cand, exact))[:k + 1]
        np.testing.assert_array_equal(dists[i], exact[order[:k]])
        served = ((data[ids[i]].astype(np.int64)
                   - queries[i].astype(np.int64)) ** 2).sum(-1)
        np.testing.assert_array_equal(served, dists[i])
        if exact[order[k - 1]] < exact[order[k]]:
            assert set(ids[i]) == set(cand[order[:k]])
