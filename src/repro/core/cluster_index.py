"""SPANN-style cluster index (paper §2.3.1, §3, §5.3).

Build: hierarchically balanced k-means partitions the dataset into posting
lists (leaf centers = centroids; the hierarchy is the in-memory BKT).
Boundary vectors are *closure-replicated* into up to ``num_replica`` lists
(a point joins list j iff d(p,c_j) <= (1+eps) * d(p,c_1)) — SPANN's key
data-read-per-query optimization, studied in Fig 16/24.

Search: BKT (or flat) centroid search picks the top-``nprobe`` lists; all
lists are fetched in ONE dependency-free roundtrip (paper §2.3.1 — cluster
indexes' big advantage on long-latency storage), then scanned with full-
precision distance computations.

Two serving paths:
* ``search_plan`` — generator yielding :class:`FetchBatch` for the
  discrete-event cloud simulator (the paper's setting).
* ``device_search_batch`` — resident-array pjit path (TPU-native serving /
  distributed dry-run), with padded posting lists.
"""
from __future__ import annotations

import dataclasses
from typing import Generator

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kmeans as km
from repro.core.distances import np_sq_l2, pairwise_sq_l2, topk_smallest
from repro.core.types import (ClusterIndexParams, FetchBatch, FetchRequest,
                              QueryMetrics, SearchParams, SearchResult)
from repro.kernels import list_scan
from repro.kernels import ops as kernel_ops
from repro.storage.object_store import ObjectStore


#: the stages of :func:`device_search_batch`, in order: each is a
#: ``jax.named_scope``, so every op of the compiled search carries its
#: stage as the first scope of its ``op_name``
SEARCH_STAGES = ("probe", "gather", "scan", "select")
PROBE, GATHER, SCAN, SELECT = SEARCH_STAGES


@dataclasses.dataclass
class ClusterIndexMeta:
    """Compute-node-resident metadata (what TurboPuffer caches, §2.1)."""

    tree: km.BKTree
    list_lengths: np.ndarray      # (n_lists,) int32
    list_nbytes: np.ndarray       # (n_lists,) int64 billable object sizes
    n_data: int
    dim: int
    dtype: np.dtype
    params: ClusterIndexParams

    @property
    def n_lists(self) -> int:
        return len(self.list_lengths)

    @property
    def index_bytes(self) -> int:
        return int(self.list_nbytes.sum())

    @property
    def avg_list_bytes(self) -> float:
        return float(self.list_nbytes.mean())


def dedup_topk(ids: np.ndarray, d: np.ndarray, k: int
               ) -> tuple[np.ndarray, np.ndarray]:
    """Top-``k`` of (ids, distances) with replica dedup, padded to k.

    Stable distance order + first-occurrence id dedup keeps the nearest
    copy of every closure-replicated point.  The one kernel behind both
    the single-node posting-list scan and the fleet's global merge of
    shard-local top-ks.
    """
    order = np.argsort(d, kind="stable")
    ids_sorted = ids[order]
    _, first = np.unique(ids_sorted, return_index=True)
    first.sort()
    sel = order[first[:k]]
    # re-sort final k by distance
    sel = sel[np.argsort(d[sel], kind="stable")]
    out_ids = ids[sel]
    out_d = d[sel].astype(np.float32)
    if len(out_ids) < k:
        out_ids = np.pad(out_ids, (0, k - len(out_ids)),
                         constant_values=-1)
        out_d = np.pad(out_d, (0, k - len(out_d)),
                       constant_values=np.inf)
    return out_ids, out_d


def scan_posting_lists(q: np.ndarray, payload_items, k: int,
                       metrics: QueryMetrics,
                       exclude: set | None = None) -> SearchResult:
    """Scan fetched posting lists and return the top-``k``.

    ``payload_items`` is an iterable of ``(ids, vecs)`` posting-list
    payloads.  Closure-replicated points are deduplicated by keeping the
    first (nearest) occurrence.  Shared by the single-node plan and the
    fleet's shard-local scan jobs — a shard scanning its own subset of the
    probed lists produces a local top-k whose global merge equals the
    single-node result.  ``exclude`` (a set or int64 array) drops
    tombstoned ids (live-ingest deletes not yet compacted out of the
    sealed lists).
    """
    all_ids = []
    all_vecs = []
    for ids, vecs in payload_items:
        if len(ids):
            all_ids.append(ids)
            all_vecs.append(vecs)
    if not all_ids:
        return SearchResult(np.full(k, -1, np.int64),
                            np.full(k, np.inf, np.float32), metrics)
    ids = np.concatenate(all_ids)
    vecs = np.concatenate(all_vecs)
    if exclude is not None and len(exclude):
        excl = exclude if isinstance(exclude, np.ndarray) else \
            np.fromiter(exclude, dtype=np.int64)
        keep = ~np.isin(ids, excl)
        ids, vecs = ids[keep], vecs[keep]
        if not len(ids):
            return SearchResult(np.full(k, -1, np.int64),
                                np.full(k, np.inf, np.float32), metrics)
    d = np_sq_l2(q, vecs)
    metrics.dist_comps += len(ids)
    out_ids, out_d = dedup_topk(ids, d, k)
    return SearchResult(out_ids, out_d, metrics)


class ClusterIndex:
    def __init__(self, meta: ClusterIndexMeta, store: ObjectStore,
                 use_bkt: bool = True):
        self.meta = meta
        self.store = store
        self.use_bkt = use_bkt

    # ------------------------------------------------------------- build --
    @staticmethod
    def build(data: np.ndarray, params: ClusterIndexParams,
              store: ObjectStore | None = None,
              chunk: int = 4096) -> "ClusterIndex":
        store = store if store is not None else ObjectStore()
        data = np.ascontiguousarray(data)
        n, dim = data.shape
        n_leaves = max(1, int(round(params.centroid_frac * n)))
        tree, _ = km.hierarchical_partition(
            data.astype(np.float32), n_leaves, branch=params.branch,
            iters=params.kmeans_iters,
            balance_penalty=max(params.balance_penalty, 1.0),
            seed=params.seed)
        cents = jnp.asarray(tree.centroids)
        n_lists = len(tree.centroids)
        r = min(params.num_replica, n_lists)

        # closure replication: top-r centroids per point, keep those within
        # (1+eps) of the nearest (squared distances -> (1+eps)^2).
        thresh = (1.0 + params.closure_eps) ** 2
        pair_list: list[np.ndarray] = []
        pair_point: list[np.ndarray] = []
        for s in range(0, n, chunk):
            end = min(s + chunk, n)
            xc = jnp.zeros((chunk, dim), dtype=jnp.float32
                           ).at[: end - s].set(data[s:end])
            d = pairwise_sq_l2(xc, cents)                   # (chunk, n_lists)
            dd, idx = topk_smallest(d, r)
            dd = np.asarray(dd)[: end - s]
            idx = np.asarray(idx)[: end - s]
            keep = dd <= (thresh * dd[:, :1] + 1e-12)
            keep[:, 0] = True
            rows, cols = np.nonzero(keep)
            pair_list.append(idx[rows, cols].astype(np.int64))
            pair_point.append((rows + s).astype(np.int64))
        lists_flat = np.concatenate(pair_list)
        points_flat = np.concatenate(pair_point)
        order = np.argsort(lists_flat, kind="stable")
        lists_flat, points_flat = lists_flat[order], points_flat[order]
        starts = np.searchsorted(lists_flat, np.arange(n_lists))
        ends = np.searchsorted(lists_flat, np.arange(n_lists) + 1)

        itemsize = data.dtype.itemsize
        lengths = (ends - starts).astype(np.int32)
        # billable size: raw vectors + 8-byte ids (paper's posting lists
        # store full vectors inline)
        nbytes = lengths.astype(np.int64) * (dim * itemsize + 8)
        for li in range(n_lists):
            ids_arr = points_flat[starts[li]:ends[li]]
            vecs = data[ids_arr] if len(ids_arr) else np.zeros(
                (0, dim), data.dtype)
            store.put(("list", li), (ids_arr, vecs), int(max(nbytes[li], 1)))

        meta = ClusterIndexMeta(
            tree=tree, list_lengths=lengths, list_nbytes=nbytes,
            n_data=n, dim=dim, dtype=data.dtype, params=params)
        return ClusterIndex(meta, store)

    # ------------------------------------------------------------ search --
    def select_lists(self, q: np.ndarray, nprobe: int
                     ) -> tuple[np.ndarray, int]:
        nprobe = min(nprobe, self.meta.n_lists)
        if self.use_bkt:
            return self.meta.tree.search(q, nprobe)
        ids = self.meta.tree.flat_search(q, nprobe)
        return ids, self.meta.n_lists

    def search_plan(
        self, q: np.ndarray, params: SearchParams,
        metrics: QueryMetrics | None = None,
    ) -> Generator[FetchBatch, dict, SearchResult]:
        """Generator protocol: yields one FetchBatch; engine sends back
        {key: payload}; returns SearchResult.  ``metrics`` may be supplied
        by the serving engine (it snapshots deltas to price compute)."""
        m = metrics if metrics is not None else QueryMetrics()
        lids, ndist = self.select_lists(q, params.nprobe)
        m.dist_comps += ndist                      # BKT centroid comps
        m.lists_visited = len(lids)
        reqs = [FetchRequest(("list", int(i)), int(self.meta.list_nbytes[i]))
                for i in lids]
        payloads = yield FetchBatch(reqs)
        m.roundtrips += 1
        m.requests += len(reqs)
        m.bytes_read += sum(r.nbytes for r in reqs)
        return scan_posting_lists(q, (payloads[rq.key] for rq in reqs),
                                  params.k, m)

    def search(self, q: np.ndarray, params: SearchParams) -> SearchResult:
        """Drive search_plan directly against the store (no timing)."""
        gen = self.search_plan(q, params)
        batch = next(gen)
        try:
            while True:
                payloads = {r.key: self.store.get(r.key)
                            for r in batch.requests}
                batch = gen.send(payloads)
        except StopIteration as stop:
            return stop.value

    # ----------------------------------------------------- device arrays --
    def device_arrays(self, max_len: int | None = None) -> dict[str, np.ndarray]:
        """Padded resident layout for the TPU serving path.

        Returns centroids (L, D) float32, list_vecs (L, maxlen, W) of the
        corpus's type, list_ids (L, maxlen) int32 (-1 pad), list_len (L,)
        int32.  ``maxlen`` defaults to the longest list rounded up to a
        multiple of 128, so that the list scan reads each list where it
        lies; ``W`` is D, or for 8-bit rows D rounded up to a multiple of
        128 with zeros (``repro.kernels.list_scan.row_width``).
        """
        L = self.meta.n_lists
        dim = self.meta.dim
        dtype = np.dtype(self.meta.dtype)
        ml = int(max_len or -(-self.meta.list_lengths.max() // 128) * 128)
        width = list_scan.row_width(dim, dtype.itemsize)
        vecs = np.zeros((L, ml, width), dtype=dtype)
        ids = np.full((L, ml), -1, dtype=np.int32)
        for li in range(L):
            pids, pv = self.store.get(("list", li))
            cnt = min(len(pids), ml)
            if cnt:
                vecs[li, :cnt, :dim] = pv[:cnt]
                ids[li, :cnt] = pids[:cnt]
        return dict(
            centroids=self.meta.tree.centroids.astype(np.float32),
            list_vecs=vecs, list_ids=ids,
            list_len=np.minimum(self.meta.list_lengths, ml).astype(np.int32))


def device_search_batch(
    centroids: jax.Array,     # (L, D) float32
    list_vecs: jax.Array,     # (L, maxlen, W)
    list_ids: jax.Array,      # (L, maxlen) int32, -1 padded
    queries: jax.Array,       # (B, D)
    *, nprobe: int, k: int,
) -> tuple[jax.Array, jax.Array]:
    """Resident-array batched cluster search (pjit/TPU path).

    ``queries`` and ``list_vecs`` are float32, int8 or uint8, of one type;
    an 8-bit corpus is held and read as bytes, and its distances are
    exact integers.  ``W`` is D, or for 8-bit rows D rounded up to a
    multiple of 128 with zeros (:meth:`ClusterIndex.device_arrays`).
    Returns ids (B, k) int32 and squared distances (B, k) float32.

    One pipeline: centroid matmul -> top-nprobe -> the probed lists' ids ->
    the list-scan kernel's distances, masked at padding -> global top-k.
    This is the device equivalent of the paper's fetch-then-scan: "fetch"
    is the kernel's DMA of each probed list's real chunks from where it
    lies in HBM (``repro.kernels.list_scan``).  Each step runs under its
    name in :data:`SEARCH_STAGES` (the dedup as ``select/dedup``); the
    scopes change only the ops' metadata.
    """
    B = queries.shape[0]
    with jax.named_scope(PROBE):
        cd = pairwise_sq_l2(queries, centroids)          # (B, L)
        _, probe = topk_smallest(cd, nprobe)             # (B, nprobe)
    with jax.named_scope(GATHER):
        ids = list_ids[probe]                            # (B, np, ml)
    with jax.named_scope(SCAN):
        d = kernel_ops.list_scan(queries, list_vecs, probe, ids)
        ids = ids.reshape(B, -1)
        d = jnp.where(ids < 0, jnp.inf, d.reshape(B, -1))  # (B, np*ml)
    with jax.named_scope(SELECT):
        # dedup replicas: mask repeated ids within the top window.  A point
        # sits at most once in each list, so it has at most ``nprobe``
        # copies among the candidates and the first k*nprobe hold k unique
        # ids.
        dd, ii = jax.lax.top_k(-d, min(k * nprobe, d.shape[-1]))
        dd = -dd
        cand_ids = jnp.take_along_axis(ids, ii, axis=1)  # (B, k*np)
        with jax.named_scope("dedup"):
            same = cand_ids[:, :, None] == cand_ids[:, None, :]
            earlier = jnp.tril(jnp.ones(same.shape[-2:], bool), k=-1)[None]
            dup = jnp.any(same & earlier, axis=-1)
            dd = jnp.where(dup, jnp.inf, dd)
        vals, sel = topk_smallest(dd, k)
        out_ids = jnp.take_along_axis(cand_ids, sel, axis=1)
    return out_ids, vals
