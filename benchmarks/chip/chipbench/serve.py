"""One batch through the timed path, with a host span around each step.

The loops call :func:`serve_batch` for every batch they send.  The spans
(``jax.profiler.TraceAnnotation``) cost about a microsecond when no trace
is being taken, so traced and untraced runs drive the same code; in a
traced run they put the host's steps on the device's clock, where the
trace reduction attributes device idle time to them.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import numpy as np

#: span names, as the trace reduction reads them
BATCH, ASSEMBLE, PUT, SEARCH, COPY, WAIT = (
    "batch", "assemble", "device_put", "search", "result_copy",
    "wait_arrival")


@dataclasses.dataclass
class Server:
    """The system under test as the loops see it: a device, the search
    program bound to the resident layout, and the query pool."""

    device: jax.Device
    search: Callable            # device queries (b, D) -> (ids, dists)
    pool: np.ndarray            # (P, D) queries of the corpus's type, on the host
    dim: int


def serve_batch(server: Server, queries: np.ndarray
                ) -> tuple[np.ndarray, np.ndarray]:
    """Answer ``queries`` (b, D) on the device; returns host (ids, dists)."""
    with jax.profiler.TraceAnnotation(PUT):
        q = jax.device_put(queries, server.device)
    with jax.profiler.TraceAnnotation(SEARCH):
        ids, dists = server.search(q)
    with jax.profiler.TraceAnnotation(COPY):
        # one transfer of both results, which also waits for the search
        return jax.device_get((ids, dists))
