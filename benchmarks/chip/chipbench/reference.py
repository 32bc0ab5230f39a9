"""References that decide ``correct``, and the lower-precision control.

The references import nothing of the program.  They read the corpus, the
query pool and the posting lists that the benchmark itself made, and the
answers that the timed path returned, and check all three stages of a
cluster search:

* ``bad_answers``: answers that name no corpus row, name one row twice,
  or carry a distance that is not finite (the replica dedup and the
  padding mask);
* ``dist_err`` and ``dist_off_share``: over every answer of the window,
  the widest gap between a returned distance and the float64 distance of
  the row it names, over ``|q|^2 + |x|^2`` (the scale of the rounding of
  the L2 expansion), and the share of answers whose gap exceeds
  :data:`DIST_TOL` (the list scan);
* ``rank_gap``: over every answered query, the widest amount by which
  the r-th best returned row lies farther from the query than the r-th
  best row of a plain search over the same lists, on the same scale (the
  centroid probe and the top-k).  A float32 screen on the device
  (:func:`screen`) clears every query whose returned rows no other row of
  its lists beats by more than :data:`SCREEN_TOL`; the rest go to a
  float64 host search (:func:`rank_gap`) whose probe takes the lists
  whose centroid distance lies clearly inside the ``nprobe`` nearest,
  ``PROBE_TIE`` of the norms below the boundary, so that a near tie at
  the boundary cannot be read as a fault.

Rows may be float32 or integer (``chipbench.data.DTYPES``).  Every
function widens integer rows before any arithmetic: to float64 on the
host, to float32 on the device, where a squared distance of 8-bit rows
below 2**24 (128-d uint8, 100-d int8) is an exact integer too.  So the
checks read exact integer distances on integer data.

``recall`` (exact top-k over the whole corpus, on a smaller sample, by
:func:`exact_topk` on the device) is reported beside them but not
compared: it is a property of the index.

:func:`control_search` is the search of ``device_search_batch`` computed
on bfloat16-rounded vectors, the precision below the configuration's
float32, the same on every backend: the control that has to come out
not correct.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: the host probe's tie band, a share of |q|^2 + |c|^2 (f32 HIGHEST
#: rounds the centroid distances to ~1e-7 of it)
PROBE_TIE = 2e-6
#: candidates re-ranked in float64 after the float32 host scan
REFINE = 32
#: a served distance off by more than this share of |q|^2 + |x|^2 (about
#: 30 float32 ulps of it) counts towards ``dist_off_share``
DIST_TOL = 2e-6
#: the screen clears a query whose returned rows no unreturned row of its
#: lists beats by more than this share of |q|^2 + |x|^2, a tenth of the
#: ``rank_gap`` limits and ~15x the float32 rounding of the screen
SCREEN_TOL = 1e-6
#: device bytes of one screen batch's gathered candidates
SCREEN_BYTES = 3 << 30
#: at most this many queries, drawn from the seed, of those the screen
#: does not clear go to the float64 host search
HOST_CHECKS = 2000


def wide(a: np.ndarray) -> np.ndarray:
    """Host rows as the arithmetic reads them: float32 as they are, integer
    rows in float64, where every sum of their squares is exact."""
    return a if a.dtype == np.float32 else a.astype(np.float64)


def _sq64(q: np.ndarray, x: np.ndarray) -> np.ndarray:
    """Float64 squared distances of rows ``x`` (m, D) to ``q`` (D,)."""
    diff = x.astype(np.float64) - q.astype(np.float64)
    return np.einsum("nd,nd->n", diff, diff)


def bad_answers(ids: np.ndarray, dists: np.ndarray, n: int) -> int:
    """Rows with an id outside [0, n), an id twice, or a non-finite
    distance."""
    bad = (ids < 0).any(1) | (ids >= n).any(1) | ~np.isfinite(dists).all(1)
    s = np.sort(ids, axis=1)
    bad |= (s[:, 1:] == s[:, :-1]).any(1)
    return int(bad.sum())


def served_sq(data: np.ndarray, pool: np.ndarray, qidx: np.ndarray,
              ids: np.ndarray, chunk: int = 8192
              ) -> tuple[np.ndarray, np.ndarray]:
    """(distances, scales), each (Q, k) float32: the squared distance of
    every served row to its query, and |q|^2 + |x|^2; ``inf`` where an id
    names no corpus row.

    On float32 rows the distance is the float32 sum of squared
    differences, whose rounding is relative to the distance itself, far
    below the rounding of the L2 expansion relative to the norms that the
    checks measure; on integer rows it is exact.
    """
    n = len(data)
    dist = np.full(ids.shape, np.inf, np.float32)
    scale = np.full(ids.shape, np.inf, np.float32)
    for s in range(0, len(qidx), chunk):
        row = ids[s:s + chunk]
        ok = (row >= 0) & (row < n)
        q = wide(pool[qidx[s:s + chunk]])
        x = wide(data[np.where(ok, row, 0)])
        diff = x - q[:, None, :]
        d = np.einsum("mkd,mkd->mk", diff, diff)
        sc = np.einsum("md,md->m", q, q)[:, None] \
            + np.einsum("mkd,mkd->mk", x, x)
        dist[s:s + chunk] = np.where(ok, d, np.inf)
        scale[s:s + chunk] = np.where(ok, sc, np.inf)
    return dist, scale


def dist_errors(dists: np.ndarray, ref: np.ndarray, scale: np.ndarray
                ) -> dict:
    """How far every served distance ``dists`` lies from the distance
    ``ref`` of the row it names (:func:`served_sq`), over |q|^2 + |x|^2:
    the widest (``dist_err``), the share of answers off by more than
    :data:`DIST_TOL` (``dist_off_share``) and the root mean square
    (``dist_rms``).  An answer naming no row counts as off by ``inf``."""
    if not dists.size:
        return dict(dist_err=float("inf"), dist_off_share=1.0,
                    dist_rms=float("inf"))
    with np.errstate(invalid="ignore"):
        err = np.abs(dists.astype(np.float64) - ref) / scale
    err = np.where(np.isfinite(err) & np.isfinite(ref), err, np.inf)
    return dict(dist_err=float(err.max()),
                dist_off_share=float((err > DIST_TOL).mean()),
                dist_rms=float(np.sqrt(np.mean(err ** 2))))


def clear_probes(queries: np.ndarray, centroids: np.ndarray, nprobe: int
                 ) -> np.ndarray:
    """(Q, L) mask of the lists whose float64 centroid distance lies
    clearly, :data:`PROBE_TIE` of |q|^2 + |c|^2, inside the ``nprobe``
    nearest of each query: nearer than the next list by that much."""
    q = queries.astype(np.float64)
    c = centroids.astype(np.float64)
    qn, cn = (q * q).sum(1), (c * c).sum(1)
    cd = qn[:, None] + cn[None, :] - 2.0 * (q @ c.T)
    if nprobe >= cd.shape[1]:
        return np.ones(cd.shape, bool)
    bound = np.partition(cd, nprobe, axis=1)[:, nprobe]
    return cd < bound[:, None] - PROBE_TIE * (qn[:, None] + cn[None, :])


def host_search(q: np.ndarray, data: np.ndarray, lists: np.ndarray,
                list_ids: np.ndarray, k: int) -> np.ndarray:
    """Float64 distances of the k nearest rows of ``lists`` to ``q``."""
    cand = list_ids[lists].ravel()
    cand = np.unique(cand[cand >= 0])
    diff = wide(data[cand]) - wide(q)
    d32 = np.einsum("nd,nd->n", diff, diff)
    top = cand[np.argpartition(d32, min(k + REFINE, len(cand) - 1))
               [:k + REFINE]]
    return np.sort(_sq64(q, data[top]))[:k]


def rank_gap(data: np.ndarray, pool: np.ndarray, centroids: np.ndarray,
             list_ids: np.ndarray, qidx: np.ndarray, ids: np.ndarray,
             nprobe: int, k: int) -> float:
    """Widest gap, over |q|^2 + |x|^2, by which the r-th best served row
    is farther than the float64 host search's r-th best, over the given
    rows; 0 for none."""
    worst = 0.0
    n = len(data)
    probes = clear_probes(pool[qidx], centroids, nprobe)
    for qi, row, lists in zip(qidx, ids, probes):
        q = pool[qi]
        ref = host_search(q, data, np.flatnonzero(lists), list_ids, k)
        row = row[(row >= 0) & (row < n)]
        served = np.sort(_sq64(q, data[row]))
        if len(served) < k:
            return float("inf")
        scale = float(q.astype(np.float64) @ q) \
            + (data[row].astype(np.float64) ** 2).sum(1).max()
        worst = max(worst, float(((served - ref) / scale).max()))
    return worst


@functools.partial(jax.jit, static_argnames=("nprobe",))
def _screen(centroids, list_vecs, list_ids, queries, served, kth, tol, *,
            nprobe):
    """(B,) bool: some row of the query's ``nprobe`` nearest lists (float32
    at HIGHEST) that is not among ``served`` lies nearer than the served
    k-th best ``kth`` by more than ``tol``.  ``queries`` are float32; the
    list rows are widened to it as they are gathered."""
    qc = jax.lax.dot_general(queries, centroids, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST)
    cd = jnp.sum(centroids * centroids, 1)[None, :] - 2.0 * qc
    _, probe = jax.lax.top_k(-cd, nprobe)
    diff = list_vecs[probe].astype(jnp.float32) - queries[:, None, None, :]
    d = jnp.sum(diff * diff, axis=-1)                   # (B, nprobe, slots)
    ids = list_ids[probe]
    new = ~jnp.any(ids[..., None] == served[:, None, None, :], axis=-1)
    nearer = d < (kth - tol)[:, None, None]
    return jnp.any((ids >= 0) & new & nearer, axis=(1, 2))


def screen(layout: dict, pool: np.ndarray, qidx: np.ndarray,
           ids: np.ndarray, ref: np.ndarray, scale: np.ndarray, *,
           nprobe: int) -> np.ndarray:
    """Indices of the answered queries that the float32 screen does not
    clear: some unreturned row of the lists probed lies nearer than the
    k-th returned row by more than :data:`SCREEN_TOL` of the norms.

    ``ref`` and ``scale`` are :func:`served_sq` of the returned rows.
    Every other query's ``rank_gap`` is at most ``SCREEN_TOL`` (the
    r-th best row of the lists can beat the r-th returned row only with a
    row that beats the k-th).  Runs on the layout's device in batches of
    :data:`SCREEN_BYTES` of gathered candidates.
    """
    vecs = layout["list_vecs"]
    _, slots, dim = vecs.shape
    per_query = nprobe * slots * dim * 4
    b = 1 << max(0, (SCREEN_BYTES // per_query).bit_length() - 1)
    q_all = len(qidx)
    kth = ref.max(axis=1)
    tol = SCREEN_TOL * scale.max(axis=1)
    tol = np.where(np.isfinite(tol), tol, 0.0).astype(np.float32)
    out = []
    for s in range(0, q_all, b):
        m = min(b, q_all - s)
        q = np.zeros((b, dim), np.float32)
        q[:m] = pool[qidx[s:s + m]]
        sv = np.full((b, ids.shape[1]), -1, np.int32)
        sv[:m] = ids[s:s + m]
        kk = np.full(b, -np.inf, np.float32)
        kk[:m] = kth[s:s + m]
        tt = np.zeros(b, np.float32)
        tt[:m] = tol[s:s + m]
        flag = _screen(layout["centroids"], vecs, layout["list_ids"], q, sv,
                       kk, tt, nprobe=nprobe)
        out.append(np.flatnonzero(np.asarray(flag)[:m]) + s)
    return np.concatenate(out) if out else np.zeros(0, np.int64)


def recall(ids: np.ndarray, exact: np.ndarray) -> float:
    """Mean recall@k of served ``ids`` against ``exact`` top-k ids."""
    k = exact.shape[1]
    return float(np.mean([len(np.intersect1d(a, b)) / k
                          for a, b in zip(ids, exact)]))


@functools.partial(jax.jit, static_argnames=("k",))
def exact_topk(corpus, queries, *, k):
    """Ids of the exact k nearest corpus rows (ranked in float32 at
    HIGHEST precision; a near tie may swap, which recall hardly sees)."""
    corpus = corpus.astype(jnp.float32)
    queries = queries.astype(jnp.float32)
    ip = jax.lax.dot_general(queries, corpus, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST)
    d = jnp.sum(corpus * corpus, axis=1)[None, :] - 2.0 * ip
    return jax.lax.top_k(-d, k)[1]


# --------------------------------------------------------------- control --

def to_bf16(x):
    """``x`` (float32) rounded to bfloat16 precision, to nearest even, kept
    in float32.  Done on the bits, so that no compiler can keep the excess
    precision (XLA does, for a plain cast feeding a product on the VPU)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _sq_l2_bf16(q, x):
    """Squared L2 of bfloat16-rounded rows, in float32 arithmetic (integer
    rows widened first)."""
    q = to_bf16(q.astype(jnp.float32))
    x = to_bf16(x.astype(jnp.float32))
    qn = jnp.sum(q * q, axis=-1)[:, None]
    xn = jnp.sum(x * x, axis=-1)[None, :]
    ip = jax.lax.dot_general(q, x, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(qn + xn - 2.0 * ip, 0.0)


@functools.partial(jax.jit, static_argnames=("nprobe", "k"))
def control_search(centroids, list_vecs, list_ids, queries, *, nprobe, k):
    """The cluster search at the precision below the configuration's
    float32: bfloat16 distance dots.  On rows of 8-bit integers, which
    bfloat16 holds exactly, it is exact."""
    b = queries.shape[0]
    _, probe = jax.lax.top_k(-_sq_l2_bf16(queries, centroids), nprobe)
    vecs = list_vecs[probe].reshape(b, -1, list_vecs.shape[-1])
    ids = list_ids[probe].reshape(b, -1)
    d = jax.vmap(lambda q, v: _sq_l2_bf16(q[None], v)[0])(queries, vecs)
    d = jnp.where(ids < 0, jnp.inf, d)
    neg, ii = jax.lax.top_k(-d, min(k * nprobe, d.shape[-1]))
    cand_d = -neg
    cand = jnp.take_along_axis(ids, ii, axis=1)
    same = cand[:, :, None] == cand[:, None, :]
    earlier = jnp.tril(jnp.ones(same.shape[-2:], bool), k=-1)[None]
    cand_d = jnp.where(jnp.any(same & earlier, axis=-1), jnp.inf, cand_d)
    neg, sel = jax.lax.top_k(-cand_d, k)
    return jnp.take_along_axis(cand, sel, axis=1), -neg
