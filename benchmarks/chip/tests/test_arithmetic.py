"""Percentiles, open-loop arrivals, intervals and the byte count."""
import numpy as np
import pytest

from chipbench import stats, tracing


@pytest.mark.parametrize("p", [0, 1, 50, 95, 99, 99.9, 100])
def test_percentile_is_numpys_linear(p):
    x = np.random.default_rng(3).exponential(size=1001)
    assert stats.percentile(x, p) == pytest.approx(np.percentile(x, p),
                                                   rel=1e-15)


def test_poisson_arrivals_same_gaps_for_every_seed():
    a = stats.poisson_arrivals(500.0, 4.0, gap_seed=9, seed=1)
    b = stats.poisson_arrivals(500.0, 4.0, gap_seed=9, seed=2 ** 33 + 1)
    assert len(a) == len(b) == 2000
    assert not np.array_equal(a, b)
    ga, gb = np.diff(np.r_[0.0, a]), np.diff(np.r_[0.0, b])
    assert np.sort(ga)[:-1].sum() == pytest.approx(np.sort(gb)[:-1].sum(),
                                                   rel=1e-2)
    assert (a >= 0).all() and (a < 4.0).all() and (np.diff(a) >= 0).all()
    # exponential gaps: mean 1/rate, coefficient of variation ~1
    assert np.mean(ga) == pytest.approx(1 / 500, rel=0.05)
    assert np.std(ga) / np.mean(ga) == pytest.approx(1.0, abs=0.1)


def test_union_of_intervals():
    iv = [(5, 7), (0, 2), (1, 3), (6, 6.5), (10, 11)]
    assert stats.merged(iv) == [(0, 3), (5, 7), (10, 11)]
    assert stats.merged([]) == []


@pytest.mark.parametrize("dtype", ["float32", "int8", "uint8"])
def test_search_bytes_counts_real_list_lengths_not_padding(dtype):
    # 4 lists on the axes; query near list 0 probes lists 0 and (by tie
    # break of the nearer) one more.  Lengths 3, 5, 7, 11; padding unseen.
    # Rows and queries count at their type's size, centroids at 4 bytes.
    cents = np.eye(4, dtype=np.float32) * 10
    lens = np.array([3, 5, 7, 11])
    q = np.array([[10, 1, 0, 0], [0, 0, 10, 1]]).astype(dtype)
    got = tracing.search_bytes(q, cents, lens, n_batches=1, nprobe=2)
    item = np.dtype(dtype).itemsize
    rows = (3 + 5) + (7 + 11)
    assert got["rows"] == rows
    assert got["row_bytes"] == rows * 4 * item
    assert got["bytes"] == rows * (4 * item + 4) + 2 * 4 * item \
        + 1 * 4 * 4 * 4
    assert got["flops"] == 2 * 4 * (rows + 2 * 4)
