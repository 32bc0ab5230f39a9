"""The reduction from trace events to idle share, program time and the
breakdown, on hand-made events and on a trace recorded here."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import readings, serve, tracing

MS = 1_000_000   # ns


def _window():
    # two batches: [0, 10) and [12, 20) ms on the host; device ops inside
    spans = [
        ("batch", 0, 10 * MS), ("assemble", 0, 1 * MS),
        ("device_put", 1 * MS, 2 * MS), ("search", 2 * MS, 3 * MS),
        ("result_copy", 3 * MS, 10 * MS),
        ("wait_arrival", 10 * MS, 12 * MS),
        ("batch", 12 * MS, 20 * MS), ("result_copy", 13 * MS, 19 * MS),
    ]
    ops = [("fusion.1", 3 * MS, 6 * MS), ("top-k", 5 * MS, 8 * MS),
           ("fusion.1", 13 * MS, 18 * MS), ("stray", 30 * MS, 31 * MS)]
    modules = [("jit_search_step(1)", 3 * MS, 8 * MS),
               ("jit_search_step(1)", 13 * MS, 18 * MS),
               ("jit_other(2)", 13 * MS, 14 * MS)]
    return ops, modules, spans


def test_reduce_busy_idle_and_program_time():
    t = tracing.reduce(*_window(), program="search_step")
    assert t["window_s"] == pytest.approx(0.020)
    # union of [3,8) and [13,18): 10 ms; the op at 30 ms is outside
    assert t["busy_s"] == pytest.approx(0.010)
    assert t["program_n"] == 2
    assert t["program_s"] == pytest.approx(0.010)
    assert t["batch_host_s"] == pytest.approx(0.018)
    assert t["n_batches"] == 2
    rec = {"trace": t}
    assert readings.idle_share(rec) == (pytest.approx(50.0), "%")
    assert readings.search_device_ms(rec) == (pytest.approx(5.0), "ms")
    assert readings.host_ms_per_batch(rec) == (pytest.approx(4.0), "ms")


def test_reduce_charges_idle_gaps_to_host_spans():
    t = tracing.reduce(*_window(), program="search_step")
    gaps = dict(t["breakdown"]["idle_gaps"])
    # idle: [0,3) [8,13) [18,20) = 10 ms
    assert gaps["idle in assemble"] == pytest.approx(0.001)
    assert gaps["idle in device_put"] == pytest.approx(0.001)
    assert gaps["idle in search"] == pytest.approx(0.001)
    assert gaps["idle in result_copy"] == pytest.approx(0.002 + 0.001)
    assert gaps["idle in wait_arrival"] == pytest.approx(0.002)
    assert sum(gaps.values()) == pytest.approx(0.010)
    ops = dict(t["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.008)
    assert ops["top-k"] == pytest.approx(0.003)


def test_roofline_reading_is_least_time_over_program_time():
    rec = {"trace": {"program_s": 0.010, "program_n": 2},
           "search_bytes": {"bytes": 819e9 * 0.004, "flops": 1.0},
           "peaks": {"hbm_Bps": 819e9, "bf16_flops": 197e12}}
    assert readings.search_roofline(rec) == (pytest.approx(40.0), "%")
    assert readings.search_roofline({"trace": None}) is None


def test_scan_roofline_reads_the_scan_stage_only():
    rec = dict(batches=[None] * 4, peaks=dict(hbm_Bps=1e9, bf16_flops=1e12),
               search_bytes=dict(row_bytes=4e6),
               trace=dict(stages=dict(executions=4, seconds=dict(
                   probe=1.0, gather=1.0, scan=0.008, select=1.0, other=0.0))))
    assert readings.scan_roofline(rec) == (pytest.approx(50.0), "%")
    rec["trace"]["stages"] = None
    assert readings.scan_roofline(rec) is None
    assert readings.scan_roofline(dict(rec, trace=None)) is None


def test_no_batch_no_reading():
    assert tracing.reduce([], [], [], program="search_step") is None
    assert readings.idle_share({"trace": None}) is None


def test_reduce_reads_a_trace_recorded_here(tmp_path):
    """The host spans of a real trace file are found; off the TPU no
    device plane exists, so the device is idle throughout."""
    f = jax.jit(lambda q: (jnp.argsort(q, axis=1), q * 2))
    srv = serve.Server(device=jax.devices()[0], search=f,
                       pool=np.ones((4, 8), np.float32), dim=8)
    serve.serve_batch(srv, srv.pool)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation(serve.BATCH):
            serve.serve_batch(srv, srv.pool)
    jax.profiler.stop_trace()
    t = tracing.reduce_dir(str(tmp_path), program="search_step")
    assert t["n_batches"] == 3
    assert t["window_s"] > 0 and t["busy_s"] == 0
    names = {n for n, _ in t["breakdown"]["idle_gaps"]}
    assert "idle in device_put" in names


RECORDED = os.path.join(os.path.dirname(__file__), "data")


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(RECORDED, "*.xplane.pb"))))
def test_reduce_reads_a_recorded_tpu_trace(path):
    """A few batches of the search, traced on a TPU v5e."""
    t = tracing.reduce(*tracing.load(path), program="search_step")
    assert t["n_batches"] == t["program_n"] > 0
    assert 0 < t["busy_s"] <= t["window_s"]
    # a program's span holds its ops and the short gaps between them
    assert 0.9 * t["program_s"] <= t["busy_s"] and t["program_s"] <= t["window_s"]
    assert t["breakdown"]["device_ops"]
