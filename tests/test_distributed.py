"""Distributed (sharded) vector search: correctness on a tiny real mesh.

The production-scale version is exercised by the dry-run (512 fake
devices); here the same shard_map code runs on a 1-device mesh and must
match flat exact search on the probed set.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cluster_index import SEARCH_STAGES
from repro.core.distributed import (MERGE, sharded_kmeans_step,
                                    sharded_search_step)
from repro.core.flat import exact_topk


@pytest.fixture(scope="module")
def mesh():
    return jax.make_mesh((1, 1), ("data", "model"))


def test_sharded_search_matches_flat(mesh):
    rng = np.random.default_rng(0)
    L, M, D, B = 64, 8, 16, 4
    cents = rng.normal(size=(L, D)).astype(np.float32)
    vecs = (cents[:, None, :]
            + rng.normal(0, 0.1, size=(L, M, D))).astype(np.float32)
    ids = np.arange(L * M, dtype=np.int32).reshape(L, M)
    queries = (cents[rng.choice(L, B)]
               + rng.normal(0, 0.05, size=(B, D))).astype(np.float32)

    norms = (vecs.astype(np.float32) ** 2).sum(-1)
    fn = jax.jit(sharded_search_step(mesh, nprobe_local=L, k=5))
    with mesh:
        got_ids, got_d = fn(jnp.asarray(cents), jnp.asarray(vecs),
                            jnp.asarray(ids), jnp.asarray(norms),
                            jnp.asarray(queries))
    flat = vecs.reshape(-1, D)
    want_ids, want_d = exact_topk(flat, queries, 5)
    # ids array maps row-major, so direct comparison works
    np.testing.assert_allclose(np.asarray(got_d), want_d, rtol=1e-4,
                               atol=1e-4)
    for b in range(B):
        assert len(np.intersect1d(np.asarray(got_ids)[b],
                                  want_ids[b])) >= 4


def test_sharded_search_names_its_stages(mesh):
    """The compiled step carries each shard's search stages and the merge
    as the first scope of its ops' ``op_name``."""
    L, M, D, B = 32, 4, 8, 2
    fn = jax.jit(sharded_search_step(mesh, nprobe_local=4, k=3))
    shapes = [jax.ShapeDtypeStruct(s, t) for s, t in (
        ((L, D), jnp.float32), ((L, M, D), jnp.float32), ((L, M), jnp.int32),
        ((L, M), jnp.float32), ((B, D), jnp.float32))]
    with mesh:
        text = fn.lower(*shapes).compile().as_text()
    scopes = {name.split("/")[1] for name in
              re.findall(r'op_name="(jit\([^"/]*\)/[^"]*/[^"]*)"', text)}
    assert scopes == set(SEARCH_STAGES) | {MERGE}


def test_sharded_search_respects_nprobe(mesh):
    rng = np.random.default_rng(1)
    L, M, D, B = 32, 4, 8, 2
    cents = rng.normal(size=(L, D)).astype(np.float32) * 10
    vecs = (cents[:, None, :]
            + rng.normal(0, 0.1, size=(L, M, D))).astype(np.float32)
    ids = np.arange(L * M, dtype=np.int32).reshape(L, M)
    q = (cents[:B] + 0.01).astype(np.float32)
    norms = (vecs.astype(np.float32) ** 2).sum(-1)
    fn = jax.jit(sharded_search_step(mesh, nprobe_local=1, k=3))
    with mesh:
        got_ids, _ = fn(jnp.asarray(cents), jnp.asarray(vecs),
                        jnp.asarray(ids), jnp.asarray(norms),
                        jnp.asarray(q))
    # probing only the nearest list still finds its members
    for b in range(B):
        assert set(np.asarray(got_ids)[b].tolist()) <= set(
            ids[b].tolist())


def test_sharded_kmeans_step_improves(mesh):
    rng = np.random.default_rng(2)
    true = rng.normal(size=(8, 8)).astype(np.float32) * 5
    x = (true[rng.integers(0, 8, 512)]
         + rng.normal(0, 0.3, size=(512, 8))).astype(np.float32)
    cents = x[rng.choice(512, 8, replace=False)]
    step = jax.jit(sharded_kmeans_step(mesh))

    def inertia(c):
        d = ((x[:, None, :] - c[None]) ** 2).sum(-1)
        return d.min(1).mean()

    with mesh:
        c1 = np.asarray(step(jnp.asarray(x), jnp.asarray(cents)))
        c2 = np.asarray(step(jnp.asarray(x), jnp.asarray(c1)))
    assert inertia(c2) <= inertia(np.asarray(cents)) + 1e-5
