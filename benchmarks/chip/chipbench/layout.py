"""The HBM-resident posting-list layout, built on the device from the corpus.

``device_search_batch`` serves from a padded layout: centroids (L, D),
list vectors (L, max_len, D), list ids (L, max_len) with -1 padding.  The
program builds it through ``ClusterIndex.build``, whose host BKT costs
~0.3 ms a point; this builder makes the same kind of layout on the device
in seconds, so that every run can afford a fresh corpus from its seed:

* centroids: ``n_lists = round(centroid_frac * n)`` corpus rows at an even
  stride (the corpus is in random order, so a random sample), refined by
  ``iters`` Lloyd iterations (flat, not hierarchical);
* SPANN's closure rule, as in ``ClusterIndex.build``: a point joins the
  lists of its ``num_replica`` nearest centroids whose squared distance is
  within ``(1 + closure_eps)**2`` of its nearest;
* every list is padded to the same number of slots, as the program's
  ``ClusterIndex.device_arrays`` pads to its longest list, and no list is
  cut: the slots are the configuration's ``max_len``, a fixed size so
  that every seed compiles the same programs, or, on a seed whose longest
  list does not fit, that list's length rounded up to :data:`ALIGN`.  A
  list holds first the points whose nearest centroid it is, then the
  points for which it is the second nearest, and so on, each group in
  corpus order.

The corpus may be float32 or integer (``chipbench.data.DTYPES``).  The
centroids are float32, as the program's ``ClusterIndex`` holds them, and
Lloyd and the closure compute in float32 on one chunk of rows at a time,
each widened as it is read, so that no widened copy of a whole integer
corpus is ever held.  The list vectors keep the corpus's type.

The packing is a counting sort on the host: a sort on the TPU takes half
a minute to compile, at any size.  :func:`build_layout_np` is the same
algorithm in float64 numpy, the reference the tests compare the device
builder with at a tiny size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: the build's matmuls: three bf16 passes.  The layout is the benchmark's
#: own, and the references read the lists it made, so rounding in the
#: build changes which lists a point joins, never whether answers check.
BUILD_DOT = jax.lax.Precision.HIGH
#: slots are raised in steps of this many where a list outgrows ``max_len``
ALIGN = 256


def chunk_for(n: int, target: int) -> int:
    """The largest divisor of ``n`` that is at most ``target``."""
    for c in range(min(target, n), 0, -1):
        if n % c == 0:
            if c < max(1, min(target, n) // 8):
                raise ValueError(f"n={n} has no divisor near {target}")
            return c
    raise ValueError(f"n must be positive, got {n}")


def _ip(x, c):
    return jax.lax.dot_general(x.astype(jnp.float32), c,
                               (((1,), (1,)), ((), ())), precision=BUILD_DOT,
                               preferred_element_type=jnp.float32)


def init_rows(n: int, n_lists: int) -> np.ndarray:
    """The corpus rows that seed the centroids."""
    return np.arange(n_lists) * (n // n_lists)


@functools.partial(jax.jit, static_argnames=("n_lists", "iters", "chunk"))
def lloyd(data, *, n_lists, iters, chunk):
    """Centroids (n_lists, D) float32 from ``iters`` flat Lloyd
    iterations."""
    n, dim = data.shape
    blocks = data.reshape(n // chunk, chunk, dim)
    init = init_rows(n, n_lists)

    def sums_of(assign):
        if data.dtype == jnp.float32:
            return jax.ops.segment_sum(data, assign, n_lists)

        def add(sums, block):
            x, a = block
            return sums + jax.ops.segment_sum(x.astype(jnp.float32), a,
                                              n_lists), None
        zero = jnp.zeros((n_lists, dim), jnp.float32)
        return jax.lax.scan(add, zero,
                            (blocks, assign.reshape(n // chunk, chunk)))[0]

    def step(_, cents):
        cn = jnp.sum(cents * cents, axis=1)
        assign = jax.lax.map(
            lambda x: jnp.argmin(cn[None, :] - 2.0 * _ip(x, cents), axis=1),
            blocks).reshape(n)
        sums = sums_of(assign)
        counts = jax.ops.segment_sum(jnp.ones((n,), jnp.float32), assign,
                                     n_lists)
        return jnp.where(counts[:, None] > 0,
                         sums / jnp.maximum(counts, 1.0)[:, None], cents)

    return jax.lax.fori_loop(0, iters, step,
                             data[init].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("num_replica", "closure_eps",
                                              "chunk"))
def closure(data, cents, *, num_replica, closure_eps, chunk):
    """Per point, its lists (n, r) int32, nearest first: n_lists where it
    stays out of the r-th nearest."""
    n, dim = data.shape
    n_lists = cents.shape[0]
    r = min(num_replica, n_lists)
    thresh = (1.0 + closure_eps) ** 2
    cn = jnp.sum(cents * cents, axis=1)

    def one(x):
        x = x.astype(jnp.float32)
        xn = jnp.sum(x * x, axis=1)
        d = jnp.maximum(xn[:, None] + cn[None, :] - 2.0 * _ip(x, cents), 0.0)
        neg, idx = jax.lax.top_k(-d, r)
        dd = -neg
        d1 = dd[:, :1]
        keep = (dd <= thresh * d1 + 1e-12).at[:, 0].set(True)
        return jnp.where(keep, idx, n_lists).astype(jnp.int32)

    lists = jax.lax.map(one, data.reshape(n // chunk, chunk, dim))
    return lists.reshape(n, r)


def pack(lists: np.ndarray, n_lists: int, max_len: int
         ) -> tuple[np.ndarray, dict]:
    """(list_ids (L, slots) int32, -1 padded; stats) from the closure's
    (n, r) lists, by a stable counting sort of the (point, rank) pairs in
    rank-major order.  ``slots`` is ``max_len``, or the longest list
    rounded up to :data:`ALIGN` where that is longer."""
    n, r = lists.shape
    key = np.ascontiguousarray(lists.T).reshape(-1)
    point = np.tile(np.arange(n, dtype=np.int32), r)
    order = np.argsort(key.astype(np.uint16 if n_lists < 2 ** 16 else
                                  np.int32), kind="stable")
    key, point = key[order], point[order]
    counts = np.bincount(key, minlength=n_lists + 1)
    longest = int(counts[:n_lists].max())
    slots = max(max_len, -(-longest // ALIGN) * ALIGN)
    starts = np.cumsum(counts) - counts
    slot = np.arange(n * r) - starts[key]
    member = key < n_lists
    list_ids = np.full((n_lists, slots), -1, np.int32)
    list_ids[key[member], slot[member]] = point[member]
    pairs = int(member.sum())
    stats = dict(pairs=pairs, longest=longest, slots=slots,
                 padding=1.0 - pairs / (n_lists * slots))
    return list_ids, stats


@functools.partial(jax.jit, static_argnames=("block",))
def fill(data, list_ids, *, block):
    """List vectors (L, max_len, D) of the corpus's type: each slot's
    corpus row, 0 in padding.

    Filled ``block`` lists at a time: one gather of the whole layout
    would hold a second, relaid-out copy of it in HBM.
    """
    n_lists, max_len = list_ids.shape
    oob = jnp.where(list_ids < 0, data.shape[0], list_ids)

    def body(i, out):
        ids = jax.lax.dynamic_slice_in_dim(oob, i * block, block)
        vecs = jnp.take(data, ids, axis=0, mode="fill", fill_value=0)
        return jax.lax.dynamic_update_slice_in_dim(out, vecs, i * block, 0)

    out = jnp.zeros((n_lists, max_len, data.shape[1]), data.dtype)
    return jax.lax.fori_loop(0, n_lists // block, body, out)


def build_layout(data, *, n_lists, iters, num_replica, closure_eps,
                 max_len, chunk):
    """The whole layout on the device; returns (arrays, host stats)."""
    cents = lloyd(data, n_lists=n_lists, iters=iters, chunk=chunk)
    lists = closure(data, cents, num_replica=num_replica,
                    closure_eps=closure_eps, chunk=chunk)
    ids_h, stats = pack(np.asarray(lists), n_lists, max_len)
    del lists
    list_ids = jax.device_put(ids_h, data.sharding)
    list_vecs = fill(data, list_ids, block=chunk_for(n_lists, 128))
    return dict(centroids=cents, list_vecs=list_vecs, list_ids=list_ids), stats


def build_layout_np(data: np.ndarray, *, n_lists: int, iters: int,
                    num_replica: int, closure_eps: float, max_len: int
                    ) -> tuple[np.ndarray, np.ndarray]:
    """Float64 reference of :func:`build_layout`, for a float or an integer
    corpus: (centroids, list_ids)."""
    x = data.astype(np.float64)
    cents = x[init_rows(len(x), n_lists)].copy()
    for _ in range(iters):
        d = (cents * cents).sum(1)[None, :] - 2.0 * x @ cents.T
        assign = d.argmin(1)
        counts = np.bincount(assign, minlength=n_lists)
        sums = np.zeros_like(cents)
        np.add.at(sums, assign, x)
        full = counts > 0
        cents[full] = sums[full] / counts[full, None]
    d = ((x[:, None, :] - cents[None, :, :]) ** 2).sum(-1)
    r = min(num_replica, n_lists)
    thresh = (1.0 + closure_eps) ** 2
    by_rank: list[list[list[int]]] = [[[] for _ in range(r)]
                                      for _ in range(n_lists)]
    for p in range(len(x)):
        order = np.argsort(d[p], kind="stable")[:r]
        for j, c in enumerate(order):
            if j == 0 or d[p, c] <= thresh * d[p, order[0]] + 1e-12:
                by_rank[c][j].append(p)
    members = [[p for group in by_rank[c] for p in group]
               for c in range(n_lists)]
    longest = max(len(m) for m in members)
    slots = max(max_len, -(-longest // ALIGN) * ALIGN)
    list_ids = np.full((n_lists, slots), -1, np.int32)
    for c, ids in enumerate(members):
        list_ids[c, :len(ids)] = ids
    return cents, list_ids
