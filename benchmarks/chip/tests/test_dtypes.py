"""The element type follows the configuration: float32 corpora and layouts
stay bit for bit what they were, integer ones are made, laid out, counted
and checked without wrapping, and a type the harness does not know ends a
run."""
import functools
import hashlib
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run as R
from chipbench import data, layout, reference, tracing

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
SEED = 2 ** 33 + 29
INTEGER = [("uint8", 128), ("int8", 100)]


def sha(a) -> str:
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()


def small_layout(x, max_len=96):
    return layout.build_layout(x, n_lists=30, iters=3, num_replica=8,
                               closure_eps=0.15, max_len=max_len,
                               chunk=layout.chunk_for(len(x), 1000))


#: sha256 of the float32 corpus, its layout and its byte count at a tiny
#: size on the CPU, as the harness made them before it took integer types
GOLDEN = dict(
    corpus="02bab9a561eac5e37d884811c3509253aa4322024390ca27b3569b2bfe91702d",
    pool="3dce73fa00eb0b8aad9cc044f3ab01f5a866b5291a150c745d58fd3374af4f2d",
    centroids="31a10b39576e9c39526dfb01b007f60b"
              "976cdf3b03cbd0f2945856fd30379192",
    list_ids="a2d101aa1cf3459f341e39791bfe6526a4df6e9558c2daab837bd15f1ca58400",
    list_vecs="b423a29ab4731c7d5f9ac5bce790d457"
              "0bfeea8ffcaa49a427c6ed3e9fe62222")


def test_float32_corpus_and_layout_are_unchanged():
    x, pool = data.make_corpus(data.seed_key(2 ** 32 + 3), n=3000, n_pool=64,
                               dim=16, intrinsic_dim=6)
    arr, st = small_layout(x)
    got = dict(corpus=sha(x), pool=sha(pool), centroids=sha(arr["centroids"]),
               list_ids=sha(arr["list_ids"]), list_vecs=sha(arr["list_vecs"]))
    assert got == GOLDEN
    assert st == dict(pairs=3639, longest=257, slots=512,
                      padding=0.7630859375)
    lens = np.asarray((arr["list_ids"] >= 0).sum(1))
    need = tracing.search_bytes(np.asarray(pool), np.asarray(arr["centroids"]),
                                lens, n_batches=3, nprobe=4)
    assert need == dict(bytes=2373876, flops=1173920, rows=34765, queries=64,
                        row_bytes=34765 * 16 * 4)


@pytest.mark.parametrize("dtype,dim", INTEGER)
def test_integer_corpus_in_range_repeatable_and_rarely_clipped(dtype, dim):
    kw = dict(n=20000, n_pool=4096, dim=dim, intrinsic_dim=24)
    x, q = data.make_corpus(data.seed_key(SEED), dtype=dtype, **kw)
    x2, q2 = data.make_corpus(data.seed_key(SEED), dtype=dtype, **kw)
    x3, _ = data.make_corpus(data.seed_key(SEED + 1), dtype=dtype, **kw)
    assert x.dtype == q.dtype == np.dtype(dtype)
    assert np.array_equal(x, x2) and np.array_equal(q, q2)
    assert not np.array_equal(x, x3)
    lo, hi = (-127, 127) if dtype == "int8" else (0, 255)
    x, q = np.asarray(x, np.int64), np.asarray(q, np.int64)
    assert lo <= x.min() and x.max() <= hi and lo <= q.min() and q.max() <= hi
    # the map uses the range: the corpus reaches an end of it
    assert x.min() == lo or x.max() == hi
    # the float mixture through the same map: what lands outside clips
    xf, qf = data.make_corpus(data.seed_key(SEED), **kw)
    offset, scale = data.affine_map(xf, dtype)
    outside = [np.mean((np.round(np.asarray(a * scale + offset)) < lo)
                       | (np.round(np.asarray(a * scale + offset)) > hi))
               for a in (xf, qf)]
    assert outside[0] == 0.0 and outside[1] < 1e-3
    # the integers are the mixture's, rounded: a coordinate moves by one
    # at most where the two programs round a half differently
    assert np.abs(x - np.round(np.asarray(xf * scale + offset))).max() <= 1


@pytest.mark.parametrize("dtype,dim", INTEGER)
def test_integer_layout_matches_host_reference(dtype, dim):
    x, _ = data.make_corpus(data.seed_key(2 ** 32 + 3), n=3000, n_pool=8,
                            dim=dim, intrinsic_dim=6, dtype=dtype)
    arr, st = small_layout(x, max_len=1024)
    xs = np.asarray(x)
    cents, ids = layout.build_layout_np(xs, n_lists=30, iters=3,
                                        num_replica=8, closure_eps=0.15,
                                        max_len=1024)
    got = np.asarray(arr["list_ids"])
    assert arr["centroids"].dtype == jnp.float32
    assert arr["list_vecs"].dtype == np.dtype(dtype)
    assert np.abs(np.asarray(arr["centroids"]) - cents).max() \
        < 1e-6 * np.abs(cents).max()
    assert np.array_equal(got, ids)
    vecs = np.asarray(arr["list_vecs"])
    assert np.array_equal(vecs[got >= 0], xs[got[got >= 0]])
    assert (vecs[got < 0] == 0).all()
    assert st["pairs"] == (got >= 0).sum()


def test_uint8_differences_do_not_wrap():
    """q = 0 and x = 255 in one coordinate: 255 - 0 and 0 - 255 both wrap
    in uint8; the distance is 65,025."""
    far = np.zeros((1, 4), np.uint8)
    far[0, 1] = 255
    near = np.zeros((1, 4), np.uint8)
    near[0, 2] = 1
    corpus = np.concatenate([far, near])
    pool = np.zeros((1, 4), np.uint8)
    dist, scale = reference.served_sq(corpus, pool, np.array([0]),
                                      np.array([[0, 1]]))
    assert dist.tolist() == [[65025.0, 1.0]]
    assert scale.tolist() == [[65025.0, 1.0]]
    lists = np.array([[0, 1]], np.int32)
    assert reference.host_search(pool[0], corpus, np.array([0]), lists,
                                 k=2).tolist() == [1.0, 65025.0]
    top = reference.exact_topk(jnp.asarray(corpus), jnp.asarray(pool), k=2)
    assert np.asarray(top).tolist() == [[1, 0]]
    # and from the query's side: q = 255, x = 0
    d, _ = reference.served_sq(np.zeros((1, 4), np.uint8),
                               np.full((1, 4), 255, np.uint8), np.array([0]),
                               np.array([[0]]))
    assert d.tolist() == [[4 * 65025.0]]


def plain_search(wrap: bool):
    """A plain search over the layout: the nearest ``nprobe`` centroids,
    every row of their lists, the k nearest distinct ids.  With ``wrap`` it
    computes differences and squares in the rows' own integer type, as a
    careless integer path would."""
    @functools.partial(jax.jit, static_argnames=("nprobe", "k"))
    def search_step(centroids, list_vecs, list_ids, queries, *, nprobe, k):
        qf = queries.astype(jnp.float32)
        ip = jax.lax.dot_general(qf, centroids, (((1,), (1,)), ((), ())),
                                 precision=jax.lax.Precision.HIGHEST)
        _, probe = jax.lax.top_k(
            2.0 * ip - jnp.sum(centroids * centroids, 1)[None, :], nprobe)
        x = list_vecs[probe]
        if wrap:
            diff = x - queries[:, None, None, :]
            d = jnp.sum(diff * diff, -1, dtype=x.dtype).astype(jnp.float32)
        else:
            diff = x.astype(jnp.float32) - qf[:, None, None, :]
            d = jnp.sum(diff * diff, -1)
        b = queries.shape[0]
        ids = list_ids[probe].reshape(b, -1)
        d = jnp.where(ids < 0, jnp.inf, d.reshape(b, -1))
        neg, order = jax.lax.top_k(-d, d.shape[1])
        cand = jnp.take_along_axis(ids, order, axis=1)
        same = cand[:, :, None] == cand[:, None, :]
        earlier = jnp.tril(jnp.ones(same.shape[-2:], bool), k=-1)[None]
        neg = jnp.where(jnp.any(same & earlier, -1), -jnp.inf, neg)
        neg, sel = jax.lax.top_k(neg, k)
        return jnp.take_along_axis(cand, sel, axis=1), -neg
    return search_step


def integer_cell(dtype: str, dim: int) -> tuple[dict, dict]:
    bench = R.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    _, config, traffic, _, _ = R.cell_spec(bench, "deep96-f32.bulk")
    config = dict(config, name=f"tiny-{dtype}", dtype=dtype, dim=dim,
                  n=6000, pool=2048, build_chunk=2000, batch=32,
                  recall_sample=10, nprobe=4, max_len=512)
    return config, traffic


@pytest.mark.parametrize("wrap", [False, True])
@pytest.mark.parametrize("dtype,dim", INTEGER)
def test_integer_run_is_correct_only_without_wrapping(dtype, dim, wrap):
    config, traffic = integer_cell(dtype, dim)
    res = R.run_cell(config, traffic, seed=SEED, seconds=0.5, trace=False,
                     device=jax.devices()[0], metric_names=[], t_start=0.0,
                     search_impl=plain_search(wrap), log=lambda *a: None)
    checks = {k: v["value"] for k, v in res["checks"].items()}
    assert res["attempted"] > 0 and res["failed"] == 0
    if wrap:
        assert not res["correct"], checks
    else:
        assert res["correct"], checks
        # integer distances are exact on both sides
        assert checks["dist_err"] == 0.0 and checks["rank_gap"] == 0.0


@pytest.mark.parametrize("dtype", ["float16", None])
def test_a_dtype_the_harness_does_not_know_ends_the_run(tmp_path, dtype):
    bench = R.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == "deep96-f32")
    config = R.load_json(os.path.join(ROOT, entry["file"]))
    config.pop("dtype")
    if dtype:
        config["dtype"] = dtype
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(tmp_path / entry["file"], "w") as f:
        json.dump(config, f)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "deep96-f32.bulk", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "dtype must be one of float32, int8, uint8" in p.stderr
    with pytest.raises(ValueError):
        R.make_corpus(config, data.seed_key(1))

