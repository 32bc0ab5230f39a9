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
