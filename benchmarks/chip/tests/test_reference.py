"""The device screen of ``rank_gap`` against the float64 host search."""
import numpy as np
import pytest

from chipbench import data, layout, reference

NPROBE, K = 4, 10


@pytest.fixture(scope="module")
def index():
    x, pool = data.make_corpus(data.seed_key(11), n=4000, n_pool=64, dim=16,
                               intrinsic_dim=6)
    arr, _ = layout.build_layout(x, n_lists=40, iters=3, num_replica=8,
                                 closure_eps=0.15, max_len=256,
                                 chunk=layout.chunk_for(4000, 1000))
    return arr, np.asarray(x), np.asarray(pool)


def served_by_host(arr, x, pool, qidx):
    """The true top-k over each query's clearly probed lists."""
    probes = reference.clear_probes(pool[qidx], np.asarray(arr["centroids"]),
                                    NPROBE)
    ids_h = np.asarray(arr["list_ids"])
    out = []
    for qi, lists in zip(qidx, probes):
        cand = ids_h[np.flatnonzero(lists)].ravel()
        cand = np.unique(cand[cand >= 0])
        d = ((x[cand].astype(np.float64) - pool[qi]) ** 2).sum(1)
        out.append(cand[np.argsort(d, kind="stable")[:K]])
    return np.asarray(out, np.int32)


def screened(arr, x, pool, qidx, ids):
    ref, scale = reference.served_sq(x, pool, qidx, ids)
    return reference.screen(arr, pool, qidx, ids, ref, scale, nprobe=NPROBE)


def test_true_answers_are_cleared(index):
    arr, x, pool = index
    qidx = np.arange(len(pool))
    ids = served_by_host(arr, x, pool, qidx)
    flagged = screened(arr, x, pool, qidx, ids)
    # only a near tie at the probe boundary may be left to the host search
    assert len(flagged) <= 2
    gap = reference.rank_gap(x, pool, np.asarray(arr["centroids"]),
                             np.asarray(arr["list_ids"]), qidx[flagged],
                             ids[flagged], NPROBE, K)
    assert gap <= reference.SCREEN_TOL


def test_a_missed_neighbour_is_flagged_and_read_by_the_host(index):
    arr, x, pool = index
    qidx = np.arange(len(pool))
    ids = served_by_host(arr, x, pool, qidx)
    # every third query loses its best row for a far one, still distinct
    worse = ids.copy()
    hit = qidx[::3]
    far = np.argmax(((x[None, :, :] - pool[hit][:, None, :]) ** 2).sum(-1),
                    axis=1)
    worse[hit, 0] = far
    flagged = screened(arr, x, pool, qidx, worse)
    assert set(hit) <= set(flagged)
    gap = reference.rank_gap(x, pool, np.asarray(arr["centroids"]),
                             np.asarray(arr["list_ids"]), qidx[flagged],
                             worse[flagged], NPROBE, K)
    assert gap > 100 * reference.SCREEN_TOL


def test_an_id_naming_no_row_is_flagged(index):
    arr, x, pool = index
    qidx = np.arange(8)
    ids = served_by_host(arr, x, pool, qidx)
    ids[2, 5] = len(x) + 3
    assert 2 in screened(arr, x, pool, qidx, ids)
