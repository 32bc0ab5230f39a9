"""Open loop: Poisson arrivals at a fixed rate, served by a greedy batcher.

Queries are due at the offsets of ``chipbench.stats.poisson_arrivals``
(``rate_qps`` over the window; the same gaps for every seed, in the
seed's order), each one the next query of the pool.  Whenever the device
is free, the batcher takes every query already due, up to ``max_batch``,
pads them to the next power of two, and sends them; one batch is in
flight at a time.  A query's latency runs from the moment it was due
until its ids are on the host, so a late dispatcher counts against the
system.  Queries due in the window are all served, also after it closes;
one still unanswered ``drain_s`` after the close is counted as failed.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from chipbench import stats
from chipbench.serve import ASSEMBLE, BATCH, WAIT, serve_batch

#: how long before a due time the batcher stops sleeping and spins
SPIN_S = 5e-4


def buckets(max_batch: int) -> list[int]:
    """The padded batch sizes: powers of two from 1 to ``max_batch``."""
    out = [1]
    while out[-1] < max_batch:
        out.append(out[-1] * 2)
    if out[-1] != max_batch:
        raise ValueError(f"max_batch must be a power of two: {max_batch}")
    return out


def warm_shapes(config: dict, traffic: dict) -> list[int]:
    return buckets(int(traffic["max_batch"]))


def run(server, config: dict, traffic: dict, seconds: float, seed: int
        ) -> dict:
    max_batch = int(traffic["max_batch"])
    sizes = buckets(max_batch)
    offsets = stats.poisson_arrivals(float(traffic["rate_qps"]), seconds,
                                     int(traffic["gap_seed"]), seed)
    n = len(offsets)
    pool = server.pool
    drain_s = float(traffic["drain_s"])
    lat = np.full(n, np.nan)
    qidx, ids, dists, spans = [], [], [], []
    backlog_at_close = None
    t0 = time.perf_counter()
    due = t0 + offsets
    close = t0 + seconds
    i = 0
    while i < n:
        now = time.perf_counter()
        if backlog_at_close is None and now >= close:
            backlog_at_close = n - i
        if now > close + drain_s:
            break
        if due[i] > now:
            with jax.profiler.TraceAnnotation(WAIT):
                if due[i] - now > SPIN_S:
                    time.sleep(due[i] - now - SPIN_S)
                while time.perf_counter() < due[i]:
                    pass
            continue
        j = min(int(np.searchsorted(due, now, side="right")), i + max_batch)
        t_b = time.perf_counter()
        with jax.profiler.TraceAnnotation(BATCH):
            with jax.profiler.TraceAnnotation(ASSEMBLE):
                b = next(s for s in sizes if s >= j - i)
                q = np.zeros((b, server.dim), pool.dtype)
                idx = np.arange(i, j) % len(pool)
                q[:j - i] = pool[idx]
            out_ids, out_d = serve_batch(server, q)
        t = time.perf_counter()
        lat[i:j] = t - due[i:j]
        qidx.append(idx)
        ids.append(out_ids[:j - i])
        dists.append(out_d[:j - i])
        spans.append((t_b, t, j - i))
        i = j
    answered = int(np.isfinite(lat).sum())
    last = spans[-1][1] if spans else t0
    worst = int(np.nanargmax(lat)) if answered else 0
    return dict(
        attempted=n, answered=answered, window_s=last - t0, batches=spans,
        latencies_s=lat[np.isfinite(lat)],
        offered_qps=n / seconds, achieved_qps=answered / max(last - t0, 1e-9),
        backlog_at_close=0 if backlog_at_close is None else backlog_at_close,
        worst_latency_s=float(lat[worst]), worst_due_s=float(offsets[worst]),
        wrapped=n > len(pool),
        qidx=np.concatenate(qidx) if qidx else np.zeros(0, np.int64),
        ids=np.concatenate(ids) if ids else np.zeros((0, 0), np.int32),
        dists=np.concatenate(dists) if dists else np.zeros((0, 0), np.float32))
