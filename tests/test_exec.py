"""repro.exec: batched MXU execution, calibrated pricing, parity.

Three layers under test:

* **batched execution** (``repro.exec.batched``) — pad-to-tile
  correctness against the numpy oracles: result ids bit-identical on any
  input (the kernel's tie-break must match lexsort), distances
  bit-identical on integer-valued inputs (exact float32 sums);
* **coalescer + pricing** (``repro.exec.backend`` / ``table``) — batch
  window semantics on a bare event kernel, calibration-table
  interpolation and validation;
* **the parity contract** — a kernel-backend fleet run returns
  bit-identical per-query result ids and recall vs the analytic backend
  at every batch window, and is deterministic run to run.
"""
import dataclasses
import json
import types

import numpy as np
import pytest

from repro.core.cluster_index import ClusterIndex
from repro.core.flat import exact_topk
from repro.core.types import ClusterIndexParams, SearchParams
from repro.data.synth import DatasetSpec, make_dataset
from repro.exec import (CalibEntry, CalibrationTable, KernelBackend,
                        QUERY_TILE, batched_topk, coalesce_scan,
                        load_table, pad_amount, scan_topk_oracle)
from repro.fleet import FleetConfig, run_fleet
from repro.kernels import ops
from repro.sim.kernel import Kernel


# ---------------------------------------------------------------- setup --

def _mk(b, n, d, seed=0, integer=False):
    rng = np.random.default_rng(seed)
    if integer:      # small integers: float32 sums exact -> bit-exactness
        q = rng.integers(-8, 8, (b, d)).astype(np.float32)
        x = rng.integers(-8, 8, (n, d)).astype(np.float32)
    else:
        q = rng.standard_normal((b, d)).astype(np.float32)
        x = rng.standard_normal((n, d)).astype(np.float32)
    return q, x


@pytest.fixture(scope="module")
def fleet_setup():
    spec = DatasetSpec("exec-test", 32, "float32", 800, 32,
                       n_clusters=16, intrinsic_dim=16, seed=7)
    data, queries = make_dataset(spec)
    gt, _ = exact_topk(data, queries, 10)
    index = ClusterIndex.build(data, ClusterIndexParams(kmeans_iters=2,
                                                        seed=7))
    return index, queries, gt


# ------------------------------------------------------ pad-to-tile MXU --

@pytest.mark.parametrize("b", [1, 2, 5, 7, 8, 9])
def test_batched_topk_ragged_batch_ids_match_oracle(b):
    q, x = _mk(b, 200, 32, seed=b)
    vk, ik = batched_topk(q, x, 10)
    vo, io = scan_topk_oracle(q, x, 10)
    assert vk.shape == (b, 10) and ik.shape == (b, 10)
    np.testing.assert_array_equal(ik, io)
    np.testing.assert_allclose(vk, vo, rtol=1e-5, atol=1e-5)


def test_batched_topk_k_exceeds_candidates():
    q, x = _mk(3, 5, 16, seed=1)
    vk, ik = batched_topk(q, x, 8)
    vo, io = scan_topk_oracle(q, x, 8)
    assert ik.shape == (3, 8)
    # 5 real results, then -1 / +inf fill — identical to the oracle
    np.testing.assert_array_equal(ik, io)
    assert (ik[:, 5:] == -1).all() and np.isinf(vk[:, 5:]).all()
    np.testing.assert_allclose(vk[:, :5], vo[:, :5], rtol=1e-5, atol=1e-5)


def test_batched_topk_duplicate_distances_bit_exact():
    # duplicated candidate rows => exactly tied distances; integer-valued
    # vectors make the sums exact, so ids AND values must be bit-identical
    # (ties broken by candidate id, both sides canonicalized by lexsort)
    q, x = _mk(6, 80, 32, seed=2, integer=True)
    x = np.concatenate([x, x[:40]])          # 40 exact duplicates
    vk, ik = batched_topk(q, x, 10)
    vo, io = scan_topk_oracle(q, x, 10)
    np.testing.assert_array_equal(ik, io)
    np.testing.assert_array_equal(vk, vo)


def test_batched_topk_rows_independent_of_batchmates():
    # each query's result must not depend on what it was batched with
    q, x = _mk(5, 96, 16, seed=3, integer=True)
    vb, ib = batched_topk(q, x, 6)
    for i in range(len(q)):
        v1, i1 = batched_topk(q[i:i + 1], x, 6)
        np.testing.assert_array_equal(i1[0], ib[i])
        np.testing.assert_array_equal(v1[0], vb[i])


def test_batched_topk_empty_edges():
    q, x = _mk(2, 50, 16, seed=4)
    v, i = batched_topk(np.empty((0, 16), np.float32), x, 5)
    assert v.shape == (0, 5) and i.shape == (0, 5)
    v, i = batched_topk(q, x, 0)
    assert v.shape == (2, 0) and i.shape == (2, 0)
    v, i = batched_topk(q, np.empty((0, 16), np.float32), 5)
    assert (i == -1).all() and np.isinf(v).all()


def test_coalesce_scan_maps_global_ids():
    q, x = _mk(4, 60, 16, seed=5)
    gids = np.arange(1000, 1060, dtype=np.int64)
    out = coalesce_scan(list(q), x, gids, 7)    # one query per owner job
    assert len(out) == 4
    _, io = scan_topk_oracle(q, x, 7)
    for j, (dists, ids) in enumerate(out):
        np.testing.assert_array_equal(ids, gids[io[j]])


def test_pad_amount():
    assert pad_amount(0, 8) == 0
    assert pad_amount(1, 8) == 7
    assert pad_amount(8, 8) == 0
    assert pad_amount(9, 8) == 7
    assert pad_amount(120, 128) == 8


def test_default_interpret_cached_and_overridable():
    auto = ops.default_interpret()
    assert ops.default_interpret() is auto       # cached, not re-detected
    try:
        ops.set_default_interpret(True)
        assert ops.default_interpret() is True
        ops.set_default_interpret(False)
        assert ops.default_interpret() is False
    finally:
        ops.set_default_interpret(None)          # re-arm auto-detect
    assert ops.default_interpret() == auto


# ----------------------------------------------------- calibration table --

def _toy_table():
    return CalibrationTable([
        CalibEntry("dist", 32, 0, 100, "float32", 1e-6),
        CalibEntry("dist", 32, 0, 10000, "float32", 1e-8),
        CalibEntry("dist", 128, 0, 100, "float32", 4e-6),
        CalibEntry("adc", 0, 8, 1000, "uint8", 2e-8),
    ], meta={"backend": "test"})


def test_table_roundtrip(tmp_path):
    t = _toy_table()
    p = tmp_path / "cal.json"
    t.save(str(p))
    t2 = CalibrationTable.load(str(p))
    assert [e.to_dict() for e in t2.entries] == \
        [e.to_dict() for e in t.entries]
    assert t2.meta["backend"] == "test"
    assert t2.dist_unit_s(32, 100) == t.dist_unit_s(32, 100)


def test_table_log_interpolation_and_clamp():
    t = _toy_table()
    assert t.dist_unit_s(32, 100) == pytest.approx(1e-6)
    assert t.dist_unit_s(32, 10000) == pytest.approx(1e-8)
    # unit_s interpolates linearly in log(batch): the geometric midpoint
    # of the batch axis lands halfway between the endpoint unit costs
    mid = t.dist_unit_s(32, 1000)
    assert mid == pytest.approx((1e-6 + 1e-8) / 2)
    # outside the measured range: clamped, never extrapolated
    assert t.dist_unit_s(32, 1) == pytest.approx(1e-6)
    assert t.dist_unit_s(32, 1e9) == pytest.approx(1e-8)


def test_table_nearest_bucket():
    t = _toy_table()
    # dim 64 sits between 32 and 128 buckets; log-distance picks one
    assert t.dist_unit_s(64, 100) in (pytest.approx(1e-6),
                                      pytest.approx(4e-6))
    assert t.adc_unit_s(16, 1000) == pytest.approx(2e-8)   # nearest pq_m


def test_table_requires_dist_entries():
    with pytest.raises(ValueError):
        CalibrationTable([CalibEntry("adc", 0, 8, 100, "uint8", 1e-8)])


def test_plan_seconds_batching_amortizes():
    t = _toy_table()
    solo = t.plan_seconds(500, 0, 32, 0)
    # the same work charged at a 100x-bigger batch operating point
    batched = t.plan_seconds(500, 0, 32, 0, dist_batch=50000)
    assert 0 < batched < solo


def test_committed_table_loads_and_prices():
    t = load_table()
    assert t.meta.get("backend")
    assert len(t.entries) > 8
    s = t.plan_seconds(4096, 2048, 64, 8)
    assert 0 < s < 1.0
    # measured amortization: bulk unit cost strictly below batch-of-one
    assert t.dist_unit_s(32, 1e5) < t.dist_unit_s(32, 1)


# ----------------------------------------------------------- coalescer --

def _stub_engine():
    k = Kernel(seed=0)
    return types.SimpleNamespace(kernel=k), k


def _job(dim=32, pq_m=0):
    return types.SimpleNamespace(alive=True, coalesce=[], dim=dim,
                                 pq_m=pq_m)


def test_backend_zero_work_bypasses_window():
    eng, k = _stub_engine()
    be = KernelBackend(load_table(), window_s=1e-3).attach(eng)
    done = []
    be.submit(_job(), 5.0, 0, 0, done.append)
    assert done == [5.0]                     # immediate, no flush event
    assert be.batches == 0 and len(k.queue) == 0


def test_backend_window_zero_is_batch_of_one():
    eng, k = _stub_engine()
    t = load_table()
    be = KernelBackend(t, window_s=0.0).attach(eng)
    done = []
    be.submit(_job(), 1.0, 500, 0, done.append)
    assert be.batches == 1 and be.jobs_batched == 1
    assert done == [1.0 + t.plan_seconds(500, 0, 32, 0)]
    assert be.mean_occupancy == pytest.approx(1 / QUERY_TILE)


def test_backend_coalesces_within_window():
    eng, k = _stub_engine()
    t = load_table()
    be = KernelBackend(t, window_s=1e-4).attach(eng)
    done = []
    j1, j2 = _job(), _job()
    be.submit(j1, 0.0, 400, 0, lambda td: done.append(("a", td)))
    be.submit(j2, 0.0, 600, 0, lambda td: done.append(("b", td)))
    assert len(k.queue) == 1                 # one armed flush, not two
    k.run()
    assert be.batches == 1 and be.jobs_batched == 2
    # both continuations fire at the same fused completion time, in
    # submission order, and the flush happened at t + window
    assert [x[0] for x in done] == ["a", "b"]
    assert done[0][1] == done[1][1]
    expect = 1e-4 + sum(
        t.plan_seconds(d, 0, 32, 0, dist_batch=1000) for d in (400, 600))
    assert done[0][1] == pytest.approx(expect)
    # per-job coalesce intervals recorded for span tiling
    assert j1.coalesce == [[0.0, 1e-4]] and j2.coalesce == [[0.0, 1e-4]]


def test_backend_batching_is_cheaper():
    t = load_table()
    eng, k = _stub_engine()
    be = KernelBackend(t, window_s=1e-4).attach(eng)
    for _ in range(8):
        be.submit(_job(), 0.0, 500, 0, lambda td: None)
    k.run()
    batched_busy = be.busy_s
    assert be.mean_occupancy == 1.0          # full query tile
    solo = 8 * t.plan_seconds(500, 0, 32, 0)
    assert batched_busy < solo


def test_backend_dead_job_dropped_at_flush():
    eng, k = _stub_engine()
    be = KernelBackend(load_table(), window_s=1e-4).attach(eng)
    done = []
    j1, j2 = _job(), _job()
    be.submit(j1, 0.0, 500, 0, lambda td: done.append("a"))
    be.submit(j2, 0.0, 500, 0, lambda td: done.append("b"))
    j1.alive = False                         # aborted while waiting
    k.run()
    assert done == ["b"]
    assert be.batches == 1 and be.jobs_batched == 1


def test_backend_rejects_negative_window():
    with pytest.raises(ValueError):
        KernelBackend(load_table(), window_s=-1e-6)


# ------------------------------------------------------ parity contract --

def _run(index, queries, **cfg_kw):
    base = dict(n_shards=2, replication=1, concurrency=16,
                shard_concurrency=4, queue_depth=32, seed=3)
    base.update(cfg_kw)
    return run_fleet(index, queries, SearchParams(k=10, nprobe=8),
                     FleetConfig(**base))


@pytest.mark.parametrize("window_us", [0.0, 200.0])
def test_fleet_kernel_backend_parity(fleet_setup, window_us):
    index, queries, gt = fleet_setup
    ra = _run(index, queries)
    rk = _run(index, queries, backend="kernel",
              batch_window_s=window_us * 1e-6)
    by_qid = {r.qid: r for r in ra.records}
    assert len(rk.records) == len(ra.records)
    for r in rk.records:
        np.testing.assert_array_equal(r.ids, by_qid[r.qid].ids)
        np.testing.assert_array_equal(r.dists, by_qid[r.qid].dists)
    assert rk.recall_against(gt) == ra.recall_against(gt)


def test_fleet_kernel_backend_deterministic(fleet_setup):
    index, queries, _ = fleet_setup
    r1 = _run(index, queries, backend="kernel", batch_window_s=2e-4)
    r2 = _run(index, queries, backend="kernel", batch_window_s=2e-4)
    assert r1.to_json() == r2.to_json()


def test_fleet_window_grows_batches(fleet_setup):
    index, queries, _ = fleet_setup
    from repro.fleet.router import FleetRouter

    def stats(window_s):
        cfg = FleetConfig(n_shards=2, replication=1, concurrency=16,
                          shard_concurrency=4, queue_depth=32, seed=3,
                          backend="kernel", batch_window_s=window_s)
        router = FleetRouter(index, cfg)
        rep = router.run(queries, SearchParams(k=10, nprobe=8))
        be_stats = [srv.engine.backend for g in router.groups
                    for srv in g.all_servers()]
        jobs = sum(b.jobs_batched for b in be_stats)
        batches = sum(b.batches for b in be_stats)
        return rep, jobs / batches

    rep0, mean0 = stats(0.0)
    rep1, mean1 = stats(2e-3)
    assert mean0 == 1.0
    assert mean1 > 1.0                       # window actually coalesces
    # holding jobs a window can only delay completion
    assert rep1.latency_percentile(99) >= rep0.latency_percentile(99)


def test_fleet_config_validates_backend_knobs():
    with pytest.raises(ValueError, match="kernel-backend knobs"):
        FleetConfig(n_shards=2, batch_window_s=1e-4)
    with pytest.raises(ValueError, match="kernel-backend knobs"):
        FleetConfig(n_shards=2, calibration="x.json")
    with pytest.raises(ValueError, match="backend"):
        FleetConfig(n_shards=2, backend="mosaic")
    cfg = FleetConfig(n_shards=2, backend="kernel", batch_window_s=1e-4)
    d = cfg.to_dict()
    assert d["backend"] == "kernel"
    assert d["batch_window_us"] == pytest.approx(100.0)
    # analytic configs serialize exactly as before the backend axis
    assert "backend" not in FleetConfig(n_shards=2).to_dict()


def test_exec_cli_fields_validate():
    from repro.cli import exec_fields_from_args
    ns = types.SimpleNamespace(backend="analytic", batch_window_us=50.0,
                               calibration=None)
    with pytest.raises(ValueError, match="kernel-backend"):
        exec_fields_from_args(ns)
    ns = types.SimpleNamespace(backend="kernel", batch_window_us=50.0,
                               calibration=None)
    assert exec_fields_from_args(ns) == dict(
        backend="kernel", batch_window_s=pytest.approx(5e-5),
        calibration=None)


# ------------------------------------------------- calibration harness --

def test_calibrate_quick_produces_usable_table(tmp_path):
    from repro.exec.calibrate import measure_table
    t = measure_table(quick=True, iters=1)
    ops_seen = {e.op for e in t.entries}
    assert ops_seen == {"dist", "adc"}
    assert all(e.unit_s > 0 for e in t.entries)
    assert all(r["roofline_frac"] < 1.0 for r in t.meta["rooflines"])
    assert t.plan_seconds(1000, 500, 32, 8) > 0
    p = tmp_path / "t.json"
    t.save(str(p))
    # a measured-then-saved table is a valid --calibration input
    assert json.loads(p.read_text())["version"] == 1
    assert CalibrationTable.load(str(p)).dist_unit_s(32) > 0


@pytest.mark.parametrize("platform,kind,want", [
    ("cpu", "cpu", None),
    ("tpu", "TPU v5 lite", dict(bf16_flops=197e12, hbm_Bps=819e9)),
])
def test_device_peaks_by_kind(platform, kind, want):
    from repro.exec.peaks import device_peaks
    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    assert device_peaks(dev) == want


def test_device_peaks_refuses_unknown_tpu_kind():
    from repro.exec.peaks import device_peaks
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    with pytest.raises(KeyError, match="TPU v99"):
        device_peaks(dev)


# ------------------------------------------------------- window tuning --

def test_tune_batch_window_smoke():
    from repro.tuning import (WindowRecommendation, tune_batch_window,
                              EnvSpec, WorkloadSpec, resolve_storage)
    w = WorkloadSpec(n=2000, dim=32, dtype="float32", target_recall=0.9,
                     concurrency=8, k=10)
    env = EnvSpec(storage=resolve_storage("tos"), cache_bytes=0)
    rec = tune_batch_window(w, env, window_grid_us=(0.0, 500.0),
                            eval_n=400, nq=16, seed=0)
    assert isinstance(rec, WindowRecommendation)
    assert rec.window_us in (0.0, 500.0)
    assert len(rec.outcomes) == 2
    o0, o1 = rec.outcomes
    assert o0.mean_batch_jobs == 1.0 and o0.batches > 0
    assert o1.mean_batch_jobs >= o0.mean_batch_jobs
    assert {o.recall for o in rec.outcomes} == {o0.recall}
    d = rec.to_dict()
    assert d["recommendation"]["backend"] == "kernel"
    assert len(d["sweep"]) == 2
