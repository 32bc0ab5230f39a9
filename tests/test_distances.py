import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.distances import (np_sq_l2, pairwise_neg_ip, pairwise_sq_l2,
                                  topk_smallest)


@pytest.mark.parametrize("dtype", [np.float32, np.int8])
@pytest.mark.parametrize("q,n,d", [(4, 64, 16), (1, 7, 960), (8, 128, 100)])
def test_pairwise_matches_numpy(dtype, q, n, d):
    rng = np.random.default_rng(0)
    if dtype == np.int8:
        qs = rng.integers(-127, 128, size=(q, d)).astype(np.int8)
        xs = rng.integers(-127, 128, size=(n, d)).astype(np.int8)
    else:
        qs = rng.normal(size=(q, d)).astype(np.float32)
        xs = rng.normal(size=(n, d)).astype(np.float32)
    got = np.asarray(pairwise_sq_l2(jnp.asarray(qs), jnp.asarray(xs)))
    want = np_sq_l2(qs, xs)
    rtol = 1e-5 if dtype == np.float32 else 0.0
    np.testing.assert_allclose(got, want, rtol=rtol, atol=1e-2)


def test_int8_exact_integer_arithmetic():
    # int8 path must be exact (int32 accumulation, no float rounding)
    rng = np.random.default_rng(1)
    qs = rng.integers(-127, 128, size=(3, 200)).astype(np.int8)
    xs = rng.integers(-127, 128, size=(50, 200)).astype(np.int8)
    got = np.asarray(pairwise_sq_l2(jnp.asarray(qs), jnp.asarray(xs)))
    want = ((qs.astype(np.int64)[:, None, :]
             - xs.astype(np.int64)[None, :, :]) ** 2).sum(-1)
    np.testing.assert_array_equal(got.astype(np.int64), want)


@pytest.mark.parametrize("dtype", [np.uint8, np.int8])
def test_integer_queries_against_float_centroids(dtype):
    # the device search's probe: 8-bit queries widen to f32 (exactly) and
    # meet the f32 centroids at HIGHEST, as float queries do
    rng = np.random.default_rng(5)
    info = np.iinfo(dtype)
    qs = rng.integers(info.min, info.max + 1, size=(6, 128)).astype(dtype)
    cs = rng.uniform(info.min, info.max, size=(40, 128)).astype(np.float32)
    got = np.asarray(pairwise_sq_l2(jnp.asarray(qs), jnp.asarray(cs)))
    q64, c64 = qs.astype(np.float64), cs.astype(np.float64)
    want = ((q64[:, None, :] - c64[None, :, :]) ** 2).sum(-1)
    scale = (q64 ** 2).sum(1)[:, None] + (c64 ** 2).sum(1)[None, :]
    assert np.abs(got - want).max() / scale.min() < 1e-6


def test_neg_ip():
    rng = np.random.default_rng(2)
    qs = rng.normal(size=(5, 32)).astype(np.float32)
    xs = rng.normal(size=(11, 32)).astype(np.float32)
    got = np.asarray(pairwise_neg_ip(jnp.asarray(qs), jnp.asarray(xs)))
    np.testing.assert_allclose(got, -(qs @ xs.T), rtol=1e-5, atol=1e-5)


def test_topk_smallest():
    rng = np.random.default_rng(3)
    d = rng.normal(size=(6, 40)).astype(np.float32)
    vals, idx = topk_smallest(jnp.asarray(d), 5)
    want = np.sort(d, axis=1)[:, :5]
    np.testing.assert_allclose(np.asarray(vals), want, rtol=1e-6)
    np.testing.assert_array_equal(
        np.take_along_axis(d, np.asarray(idx), axis=1), np.asarray(vals))


def test_self_distance_zero():
    rng = np.random.default_rng(4)
    x = rng.normal(size=(10, 64)).astype(np.float32)
    d = np.asarray(pairwise_sq_l2(jnp.asarray(x), jnp.asarray(x)))
    np.testing.assert_allclose(np.diag(d), 0.0, atol=1e-3)
    assert (d >= 0).all()
