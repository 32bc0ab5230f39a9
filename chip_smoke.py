#!/usr/bin/env python3
"""Smoke run of the served vector-search path on a TPU.

Drives the system once, in one process, through the entry points a user
calls, at the paper's DEEP shape (96-d float32, squared L2):

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded search on a 4-chip mesh

One chip runs five phases: ``device`` (a TPU, and no Pallas
interpreter), ``kernels`` (the Mosaic-compiled kernels against float64
references, then ``repro.exec.calibrate.measure_table``, written to
``chiprun_out/chip_smoke/``), ``build`` (one ``ClusterIndex``, reused by
every later phase), ``search`` (its padded lists resident in HBM, batches
answered by a jitted ``device_search_batch`` and checked against the host
index and exact top-k) and ``fleet`` (the same index served by
``repro.fleet.run_fleet`` on the kernel backend, priced from the table the
``kernels`` phase measured).  ``--chips 4`` runs only ``device`` and
``sharded``: ``sharded_search_step`` over a 4-chip mesh against exhaustive
single-chip search on the same data.

Each phase prints one line: its name, wall seconds, XLA compiles (with
persistent-cache hits among them) and its result as JSON.  The last line
of stdout is ``{"ok": true, "device": {...}}``.  Without a TPU, or when
any phase fails, the script exits non-zero and never prints that line.
These are smoke numbers, not benchmark results: the fleet's latencies are
simulated seconds.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.cluster_index import ClusterIndex, device_search_batch  # noqa: E402,E501
from repro.core.flat import exact_topk  # noqa: E402
from repro.core.types import ClusterIndexParams, SearchParams  # noqa: E402
from repro.data.synth import DEEP_ANALOG, make_dataset, scaled  # noqa: E402

K = 10
N_QUERIES = 500
#: where the calibration table goes (``.gitignore`` lists it)
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
#: 1% of points are asked for as centroids: at 1M the build makes 15,491
#: lists of mean length 379 after closure replication, which fill whole
#: MXU tiles, where the paper's 16% would give lists of a few vectors
#: (16x the lists, 16x the build's tree nodes).
CENTROID_FRAC = 0.01
NPROBE = 32
BATCH = 64
#: Device answers may differ from the host reference's only through f32
#: near-ties (both sides compute in f32, the TPU's dots at HIGHEST
#: precision): a tie between the nprobe-th and the next centroid, or at
#: the k-th rank.  Each such tie moves one or two ids of one query, so at
#: most 1 in 200 ids may differ, and recall@10 by no more than 0.005.
MIN_ID_MATCH = 0.995
MAX_RECALL_GAP = 0.005
#: Sharded vs exhaustive distances: the f32 rounding of
#: ||q||^2 + ||x||^2 - 2 q.x is relative to those norms (~100 here), so
#: the bound is 4e-6 of them, ~64 f32 ulps over both sides.
F32_TIE = 4e-6


def _check(ok: bool, msg: str) -> None:
    """Fail the phase when its result disagrees with its reference."""
    if not ok:
        raise RuntimeError(msg)


class _Compiles:
    """XLA compiles and persistent-cache hits, from ``jax.monitoring``."""

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _run_phase(name: str, fn, counter: _Compiles):
    c0, h0 = counter.compiles, counter.cache_hits
    t0 = time.perf_counter()
    try:
        result = fn()
    except Exception as e:
        print(f"phase={name} FAILED: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        raise
    # a phase returns its summary, or (value, summary)
    summary = result[-1] if isinstance(result, tuple) else result
    print(f"phase={name} wall_s={time.perf_counter() - t0:.3f} "
          f"compiles={counter.compiles - c0} "
          f"cache_hits={counter.cache_hits - h0} "
          f"{json.dumps(summary, sort_keys=True)}", flush=True)
    return result


def _overlap(ids: np.ndarray, ref: np.ndarray) -> float:
    """Mean share of each row's k reference ids found in ``ids``: the id
    match rate, or recall@k when ``ref`` is the exact top-k."""
    return float(np.mean([len(np.intersect1d(a[a >= 0], b)) / K
                          for a, b in zip(ids, ref)]))


# ------------------------------------------------------------- phases --

def phase_device(chips: int) -> dict:
    """A TPU with ``chips`` devices, and kernels compiled by Mosaic."""
    from repro.kernels import ops
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found (JAX platform {d.platform!r}, "
            f"device {d.device_kind!r}); this script runs only on a TPU")
    _check(len(devs) >= chips,
           f"--chips {chips} needs {chips} TPU devices, JAX sees "
           f"{len(devs)}")
    _check(not ops.default_interpret(),
           "repro.kernels.ops.default_interpret() is True on a TPU: "
           "kernels would run in the Pallas interpreter")
    return dict(platform=d.platform, kind=d.device_kind, count=len(devs))


def _kernel_parity(interpret: bool) -> dict:
    """Each kernel against a float64 numpy reference on random inputs."""
    from repro.core.pq import default_pq_dims
    from repro.exec.batched import batched_topk
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    out = {}

    q = rng.standard_normal((64, 96)).astype(np.float32)
    x = rng.standard_normal((4096, 96)).astype(np.float32)
    ref = ((q.astype(np.float64)[:, None] - x[None]) ** 2).sum(-1)
    got = np.asarray(ops.l2_distance(q, x[:1000], interpret=interpret))
    err = float(np.max(np.abs(got - ref[:, :1000]) / (ref[:, :1000] + 1.0)))
    _check(err < 1e-4, f"l2_distance f32 D=96: max rel err {err:.3e}")
    out["l2_distance_f32_max_rel_err"] = err

    qi = rng.integers(-127, 128, (64, 100)).astype(np.int8)
    xi = rng.integers(-127, 128, (1000, 100)).astype(np.int8)
    refi = ((qi.astype(np.int64)[:, None] - xi[None]) ** 2).sum(-1)
    goti = np.asarray(ops.l2_distance(qi, xi, interpret=interpret))
    _check(np.array_equal(goti, refi), "l2_distance int8 D=100 not exact")
    out["l2_distance_int8_exact"] = True

    vals, ids = batched_topk(q, x, K, interpret=interpret)
    want = np.sort(ref, axis=1)[:, :K]
    # ids may differ only on ties: their true distances must be the top-k
    got_d = np.take_along_axis(ref, ids.astype(np.int64), axis=1)
    err = float(np.max(np.abs(np.sort(got_d, axis=1) - want) / (want + 1.0)))
    _check(err < 1e-5 and np.allclose(vals, want, rtol=1e-4, atol=1e-4),
           f"l2_topk D=96: top-k distances off by {err:.3e}")
    out["l2_topk_id_match"] = _overlap(ids, np.argsort(ref, axis=1)[:, :K])

    for m in (8, 16, default_pq_dims(960)):
        codes = rng.integers(0, 256, (5000, m), dtype=np.uint8)
        table = rng.standard_normal((m, 256)).astype(np.float32)
        refa = table.astype(np.float64)[np.arange(m), codes].sum(-1)
        gota = np.asarray(ops.adc_lookup(codes, table, interpret=interpret))
        err = float(np.max(np.abs(gota - refa)))
        _check(err < 1e-4 * m, f"adc_lookup m={m}: max abs err {err:.3e}")
        out[f"adc_m{m}_max_abs_err"] = err
    return out


def phase_kernels(out_dir: str, *, interpret: bool,
                  quick: bool = False) -> dict:
    """Kernel parity, then the calibration table that prices the fleet."""
    from repro.exec.calibrate import measure_table
    out = _kernel_parity(interpret)
    table = measure_table(quick=quick, interpret=interpret)
    _check(table.meta["interpret"] == interpret,
           f"calibration ran with interpret={table.meta['interpret']}")
    _check({e.op for e in table.entries} == {"dist", "adc"},
           "calibration table lacks dist or adc entries")
    path = os.path.join(out_dir, "calibration.json")
    table.save(path)
    fracs = [r["roofline_frac"] for r in table.meta["rooflines"]]
    out.update(table=path, entries=len(table.entries),
               interpret=table.meta["interpret"],
               backend=table.meta["backend"],
               device_kind=table.meta["device_kind"],
               max_roofline_frac=max(fracs) if fracs else None)
    return out


def make_deep(n: int, n_queries: int, seed: int):
    """The DEEP-shaped corpus: 96-d float32 vectors and queries."""
    return make_dataset(scaled(DEEP_ANALOG, n, n_queries, seed=seed))


def phase_build(data: np.ndarray, seed: int) -> tuple[ClusterIndex, dict]:
    t0 = time.perf_counter()
    index = ClusterIndex.build(
        data, ClusterIndexParams(centroid_frac=CENTROID_FRAC, seed=seed))
    ll = index.meta.list_lengths
    return index, dict(n=int(index.meta.n_data), dim=int(index.meta.dim),
                       build_s=time.perf_counter() - t0,
                       lists=int(index.meta.n_lists),
                       list_len_mean=float(ll.mean()),
                       list_len_max=int(ll.max()),
                       replication=float(ll.sum() / index.meta.n_data))


def phase_search(index: ClusterIndex, data: np.ndarray,
                 queries: np.ndarray) -> tuple[np.ndarray, dict]:
    """Device search over the HBM-resident lists vs the host index."""
    dev = jax.devices()[0]
    arrays = index.device_arrays()          # whole lists: no max_len cut
    resident = {name: jax.device_put(arrays[name], dev)
                for name in ("centroids", "list_vecs", "list_ids")}
    hbm_bytes = sum(int(a.nbytes) for a in resident.values())
    search = jax.jit(device_search_batch, static_argnames=("nprobe", "k"))

    nq = len(queries)
    padded = np.zeros((-(-nq // BATCH) * BATCH, queries.shape[1]),
                      np.float32)
    padded[:nq] = queries
    batches = [jax.device_put(padded[s:s + BATCH], dev)
               for s in range(0, len(padded), BATCH)]
    run = lambda qb: search(resident["centroids"], resident["list_vecs"],  # noqa: E731,E501
                            resident["list_ids"], qb, nprobe=NPROBE, k=K)
    jax.block_until_ready(run(batches[0]))  # compile
    t0 = time.perf_counter()
    outs = [run(qb) for qb in batches]
    jax.block_until_ready(outs)
    batch_s = (time.perf_counter() - t0) / len(batches)
    dev_ids = np.concatenate([np.asarray(i) for i, _ in outs])[:nq]
    dev_d = np.concatenate([np.asarray(d) for _, d in outs])[:nq]

    flat = ClusterIndex(index.meta, index.store, use_bkt=False)
    sp = SearchParams(k=K, nprobe=NPROBE)
    host_ids = np.stack([flat.search(q, sp).ids for q in queries])
    gt, _ = exact_topk(data, queries, K)

    match = _overlap(dev_ids, host_ids)
    rec_dev, rec_host = _overlap(dev_ids, gt), _overlap(host_ids, gt)
    dup_rows = int(sum(len(np.unique(r)) < K for r in dev_ids))
    _check(np.isfinite(dev_d).all() and dup_rows == 0,
           f"device answers: {dup_rows} rows with repeated ids or "
           f"non-finite distances")
    _check(match >= MIN_ID_MATCH,
           f"device vs host id match {match:.4f} < {MIN_ID_MATCH}")
    _check(abs(rec_dev - rec_host) <= MAX_RECALL_GAP,
           f"recall@10 device {rec_dev:.4f} vs host {rec_host:.4f}")
    stats = dev.memory_stats() or {}
    return gt, dict(hbm_list_bytes=hbm_bytes,
                max_len=int(arrays["list_vecs"].shape[1]),
                batches=len(batches), batch=BATCH, nprobe=NPROBE,
                batch_wall_s=batch_s, id_match=match,
                recall_device=rec_dev, recall_host=rec_host,
                peak_bytes_in_use=stats.get("peak_bytes_in_use"))


def phase_fleet(index: ClusterIndex, queries: np.ndarray, gt: np.ndarray,
                table_path: str) -> dict:
    """The same index behind run_fleet on the kernel backend."""
    from repro.fleet.router import FleetConfig, run_fleet
    cfg = FleetConfig(backend="kernel", calibration=table_path)
    sp = SearchParams(k=K, nprobe=NPROBE)
    rep = run_fleet(index, queries, sp, cfg)         # closed loop
    by_qid = {r.qid: r.ids for r in rep.records}
    fleet_ids = np.stack([by_qid[i] for i in range(len(queries))])
    host_ids = np.stack([index.search(q, sp).ids for q in queries])
    match = _overlap(fleet_ids, host_ids)
    _check(len(rep.records) == len(queries),
           f"fleet answered {len(rep.records)} of {len(queries)} queries")
    _check(match >= MIN_ID_MATCH,
           f"fleet vs single-node id match {match:.4f} < {MIN_ID_MATCH}")
    return dict(n_shards=cfg.n_shards, queries=len(rep.records),
                id_match_single_node=match,
                recall=rep.recall_against(gt),
                p50_simulated_s=rep.latency_percentile(50),
                p99_simulated_s=rep.latency_percentile(99))


def ivf_layout(data: np.ndarray, n_lists: int, seed: int) -> dict:
    """Padded posting lists around ``n_lists`` sampled centroids.

    Each point joins the list of its nearest centroid (no replication);
    the layout feeds ``sharded_search_step``.  It takes seconds where
    ``ClusterIndex.build`` takes minutes of host time, which a 4-chip
    run would pay four times over.
    """
    from repro.core.distances import pairwise_sq_l2
    rng = np.random.default_rng(seed)
    cents = data[rng.choice(len(data), n_lists, replace=False)]
    cj = jnp.asarray(cents)
    assign = np.concatenate([
        np.asarray(jnp.argmin(pairwise_sq_l2(jnp.asarray(
            data[s:s + 65536]), cj), axis=1))
        for s in range(0, len(data), 65536)])
    order = np.argsort(assign, kind="stable")
    counts = np.bincount(assign, minlength=n_lists)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    ml = int(counts.max())
    slot = np.arange(len(data)) - np.repeat(starts, counts)
    vecs = np.zeros((n_lists, ml, data.shape[1]), np.float32)
    ids = np.full((n_lists, ml), -1, np.int32)
    vecs[assign[order], slot] = data[order]
    ids[assign[order], slot] = order
    norms = (vecs.astype(np.float64) ** 2).sum(-1).astype(np.float32)
    return dict(centroids=cents.astype(np.float32), list_vecs=vecs,
                list_ids=ids, norms=norms)


def phase_sharded(data: np.ndarray, queries: np.ndarray, n_lists: int,
                  chips: int, seed: int, n_exhaustive: int = 64,
                  nprobe_small: int = 16) -> dict:
    """sharded_search_step on a ``chips``-device mesh vs exhaustive search.

    With every local list probed the answer is exact top-k; a smaller
    ``nprobe_small`` reports its recall.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.distributed import sharded_search_step
    mesh = jax.make_mesh((chips,), ("shard",),
                         devices=jax.devices()[:chips])
    lay = ivf_layout(data, n_lists, seed)
    shard = NamedSharding(mesh, P("shard"))
    args = [jax.device_put(lay[n], shard)
            for n in ("centroids", "list_vecs", "list_ids", "norms")]
    placement = sorted({f"{s.device.id}:{s.data.shape[0]}"
                        for s in args[1].addressable_shards})
    _check(len(placement) == chips,
           f"list shards on {placement}, want {chips} devices")
    repl = NamedSharding(mesh, P())

    def answer(nprobe_local: int, qs: np.ndarray, batch: int):
        step = jax.jit(sharded_search_step(
            mesh, nprobe_local=nprobe_local, k=K))
        ids, ds = [], []
        for s in range(0, len(qs), batch):
            i, d = step(*args, jax.device_put(qs[s:s + batch], repl))
            ids.append(np.asarray(i))
            ds.append(np.asarray(d))
        return np.concatenate(ids), np.concatenate(ds)

    gt_ids, gt_d = exact_topk(data, queries, K)     # one chip, exhaustive
    qx = queries[:n_exhaustive]
    ex_ids, ex_d = answer(n_lists // chips, qx, batch=4)
    q64 = qx.astype(np.float64)[:, None]

    def true_d(ids):                    # float64 distances of named ids
        return ((data[ids].astype(np.float64) - q64) ** 2).sum(-1)

    # f32 error of ||q||^2 + ||x||^2 - 2 q.x scales with the norms it
    # cancels, not with the distance: allow F32_TIE of them
    scale = ((q64 ** 2).sum(-1)
             + (data[gt_ids[:n_exhaustive]].astype(np.float64) ** 2).sum(-1))
    d_err = float(np.max(np.abs(ex_d - gt_d[:n_exhaustive]) / scale))
    _check(d_err <= F32_TIE,
           f"all-lists sharded distances off single-chip exact top-k by "
           f"{d_err:.3e} of the norms")
    # ids may differ only where distances tie within f32 resolution
    tie_err = float(np.max(np.abs(np.sort(true_d(ex_ids), axis=1)
                                  - true_d(gt_ids[:n_exhaustive])) / scale))
    _check(tie_err <= F32_TIE,
           f"all-lists sharded ids are not an exact top-k ({tie_err:.3e})")
    small_ids, _ = answer(nprobe_small, queries, batch=BATCH)
    return dict(chips=chips, lists=n_lists,
                max_len=int(lay["list_vecs"].shape[1]),
                shards=placement,
                exhaustive_queries=n_exhaustive,
                exhaustive_dist_err_of_norms=d_err,
                exhaustive_tie_err_of_norms=tie_err,
                exhaustive_id_match=_overlap(ex_ids, gt_ids[:n_exhaustive]),
                nprobe_local=nprobe_small,
                recall_at_nprobe_local=_overlap(small_ids, gt_ids))


# --------------------------------------------------------------- main --

def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="Smoke run of the vector-search path on a TPU.")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded search on a 4-chip mesh")
    ap.add_argument("--n", type=int, default=1_000_000,
                    help="corpus vectors (default: %(default)s, 10%% of "
                         "DEEP10M)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    counter = _Compiles()
    device = _run_phase("device", lambda: phase_device(args.chips), counter)
    print(f"compile cache: {cache_dir}", flush=True)

    data, queries = make_deep(args.n, N_QUERIES, args.seed)
    if args.chips > 1:
        _run_phase("sharded", lambda: phase_sharded(
            data, queries, n_lists=args.n // 256 // args.chips * args.chips,
            chips=args.chips,
            seed=args.seed), counter)
    else:
        os.makedirs(OUT_DIR, exist_ok=True)
        kern = _run_phase("kernels", lambda: phase_kernels(
            OUT_DIR, interpret=False), counter)
        index, _ = _run_phase("build", lambda: phase_build(data, args.seed),
                              counter)
        gt, _ = _run_phase("search", lambda: phase_search(
            index, data, queries), counter)
        _run_phase("fleet", lambda: phase_fleet(
            index, queries, gt, kern["table"]), counter)
    print(json.dumps(dict(ok=True, device=device)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
