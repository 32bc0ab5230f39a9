"""The device layout builder against its float64 host reference, and the
window arithmetic of the two loops with a stand-in server."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import data, layout, serve
import run as R


@pytest.mark.parametrize("max_len", [1024, 96])
def test_layout_matches_host_reference(max_len):
    x, _ = data.make_corpus(data.seed_key(2 ** 32 + 3), n=3000, n_pool=8,
                            dim=16, intrinsic_dim=6)
    arr, st = layout.build_layout(x, n_lists=30, iters=3, num_replica=8,
                                  closure_eps=0.15, max_len=max_len,
                                  chunk=layout.chunk_for(3000, 1000))
    cents, ids = layout.build_layout_np(np.asarray(x), n_lists=30, iters=3,
                                        num_replica=8, closure_eps=0.15,
                                        max_len=max_len)
    got = np.asarray(arr["list_ids"])
    assert got.shape == ids.shape == (30, st["slots"])
    assert np.abs(np.asarray(arr["centroids"]) - cents).max() < 1e-4
    # rounding may move a point across the closure boundary, rarely
    assert (got == ids).mean() > 0.999
    vecs = np.asarray(arr["list_vecs"])
    xs = np.asarray(x)
    assert np.array_equal(vecs[got >= 0], xs[got[got >= 0]])
    assert (vecs[got < 0] == 0).all()
    # no list is cut: every pair the closure made has its slot
    lens = (got >= 0).sum(1)
    assert st["pairs"] == lens.sum() >= 3000
    assert st["longest"] == lens.max() <= st["slots"]
    assert len(np.unique(got[got >= 0])) == 3000
    if max_len < st["longest"]:
        # a list longer than max_len raises the slots, in steps of ALIGN
        assert st["slots"] % layout.ALIGN == 0
        assert st["slots"] - layout.ALIGN < st["longest"]
    else:
        assert st["slots"] == max_len


def test_chunk_for():
    assert layout.chunk_for(2_500_000, 10_000) == 10_000
    assert layout.chunk_for(250_000, 5_000) == 5_000
    assert layout.chunk_for(6000, 1000) == 1000
    with pytest.raises(ValueError):
        layout.chunk_for(10007, 1000)


class StandIn:
    """A server whose search takes a fixed time and answers row indices."""

    def __init__(self, service_s: float, dim: int = 4, pool: int = 100_000):
        self.service_s = service_s
        self.calls = []

        def search(q):
            self.calls.append(q.shape[0])
            t = time.perf_counter() + service_s
            while time.perf_counter() < t:
                pass
            ids = jnp.zeros((q.shape[0], 3), jnp.int32)
            return ids, ids.astype(jnp.float32)
        self.server = serve.Server(device=jax.devices()[0], search=search,
                                   pool=np.zeros((pool, dim), np.float32),
                                   dim=dim)


def test_closed_loop_counts_every_query_in_the_window():
    s = StandIn(0.002)
    loop = R.load_part("loops", "closed")
    rec = loop.run(s.server, {"batch": 16}, {}, 0.3, seed=1)
    assert rec["answered"] == rec["attempted"] == 16 * len(rec["batches"])
    assert rec["window_s"] >= 0.3
    assert set(s.calls) == {16}
    # consecutive pool slices, no query twice
    assert len(np.unique(rec["qidx"])) == len(rec["qidx"])


def test_open_loop_latency_runs_from_due_time():
    s = StandIn(0.003)
    loop = R.load_part("loops", "open")
    traffic = dict(rate_qps=200.0, max_batch=64, gap_seed=3, drain_s=5.0)
    rec = loop.run(s.server, {}, traffic, 1.0, seed=4)
    assert rec["attempted"] == 200 == rec["answered"]
    lat = rec["latencies_s"]
    # never less than one service time, and light load leaves no backlog
    assert lat.min() >= 0.003
    assert np.median(lat) < 0.02
    assert rec["backlog_at_close"] <= 64
    assert set(s.calls) <= set(loop.buckets(64))
    assert sum(n for _, _, n in rec["batches"]) == 200


def test_open_loop_over_capacity_backs_up_and_batches_fill():
    s = StandIn(0.004)
    loop = R.load_part("loops", "open")
    traffic = dict(rate_qps=20000.0, max_batch=64, gap_seed=3, drain_s=10.0)
    rec = loop.run(s.server, {}, traffic, 0.5, seed=4)
    # capacity ~64 / 4 ms = 16k/s, offered 20k/s: the queue grows
    assert rec["backlog_at_close"] > 64
    assert max(s.calls) == 64
    assert rec["achieved_qps"] < rec["offered_qps"]
    # the latency of a query served late counts its wait
    assert rec["latencies_s"].max() > 0.05


def test_buckets():
    loop = R.load_part("loops", "open")
    assert loop.buckets(64) == [1, 2, 4, 8, 16, 32, 64]
    with pytest.raises(ValueError):
        loop.buckets(48)
