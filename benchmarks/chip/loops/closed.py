"""Closed loop: one batch of ``batch`` queries in flight, back to back.

The batch size comes from the configuration (``batch``): what a bulk
caller of this deployment sends at once.  Batches are consecutive slices
of the query pool, so no query is asked twice while the pool lasts.  The
window opens when the first batch is assembled and closes when the first
batch to finish after ``seconds`` is on the host; every query answered in
between counts.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from chipbench.serve import ASSEMBLE, BATCH, serve_batch


def warm_shapes(config: dict, traffic: dict) -> list[int]:
    """The batch shapes this loop sends."""
    return [int(config["batch"])]


def run(server, config: dict, traffic: dict, seconds: float, seed: int
        ) -> dict:
    b = int(config["batch"])
    pool = server.pool
    n_slices = len(pool) // b
    qidx, ids, dists, spans = [], [], [], []
    i = 0
    t0 = time.perf_counter()
    end = t0 + seconds
    while True:
        t_b = time.perf_counter()
        with jax.profiler.TraceAnnotation(BATCH):
            with jax.profiler.TraceAnnotation(ASSEMBLE):
                s = (i % n_slices) * b
                q = pool[s:s + b]
            out_ids, out_d = serve_batch(server, q)
        t = time.perf_counter()
        qidx.append(np.arange(s, s + b))
        ids.append(out_ids)
        dists.append(out_d)
        spans.append((t_b, t, b))
        i += 1
        if t >= end:
            break
    return dict(
        attempted=i * b, answered=i * b, window_s=t - t0, batches=spans,
        wrapped=i > n_slices,
        qidx=np.concatenate(qidx), ids=np.concatenate(ids),
        dists=np.concatenate(dists))
