"""Whole runs at a tiny size on the CPU, the harness's look for a chip
skipped: the program passes, and the control and each planted fault of
the timed path come out not correct."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

import run as R
from chipbench import reference

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
SEED = 2 ** 33 + 17


def tiny(config_name: str, workload: str) -> tuple[dict, dict]:
    bench = R.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    _, config, traffic, _, _ = R.cell_spec(bench, workload)
    assert config["name"] == config_name
    config = dict(config, n=6000, pool=2048, build_chunk=2000,
                  batch=min(config["batch"], 32), recall_sample=10)
    if traffic["loop"] == "open":
        traffic = dict(traffic, rate_qps=150.0)
    return config, traffic


def run_tiny(config, traffic, impl, seconds=1.0):
    return R.run_cell(config, traffic, seed=SEED, seconds=seconds,
                      trace=False, device=jax.devices()[0], metric_names=[],
                      t_start=0.0, search_impl=impl, log=lambda *a: None)


@pytest.fixture(scope="module")
def program():
    return R.program_search()


CELLS = [("deep96-f32", "deep96-f32.bulk"), ("gist960-f32", "gist960-f32.bulk"),
         ("deep96-f32", "deep96-f32.online")]


@pytest.mark.parametrize("config_name,workload", CELLS)
def test_program_is_correct(program, config_name, workload):
    res = run_tiny(*tiny(config_name, workload), program)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("config_name,workload", CELLS)
def test_control_is_not_correct(config_name, workload):
    """The search with bf16 distance dots, the precision below float32."""
    res = run_tiny(*tiny(config_name, workload), reference.control_search)
    assert not res["correct"]
    c = res["checks"]
    assert any(v["value"] > v["limit"] for v in c.values())


def stale(program):
    """Each batch gets the answers of the batch before it."""
    last = {}

    def impl(c, v, i, q, *, nprobe, k):
        out = program(c, v, i, q, nprobe=nprobe, k=k)
        prev = last.get(q.shape, out)
        last[q.shape] = out
        return prev
    return impl


def half_batch(program):
    """The second half of every batch is never searched."""
    def impl(c, v, i, q, *, nprobe, k):
        ids, d = program(c, v, i, q, nprobe=nprobe, k=k)
        h = (q.shape[0] + 1) // 2
        return ids.at[h:].set(0), d.at[h:].set(0.0)
    return impl


def altered(program):
    """One answer of every batch names the wrong row."""
    def impl(c, v, i, q, *, nprobe, k):
        ids, d = program(c, v, i, q, nprobe=nprobe, k=k)
        return ids.at[0, 0].set((ids[0, 0] + 1) % 6000), d
    return impl


def short_probe(program):
    """The probe scans one list, the nearest, of the lists it should."""
    def impl(c, v, i, q, *, nprobe, k):
        return program(c, v, i, q, nprobe=1, k=k)
    return impl


@pytest.mark.parametrize("fault", [stale, half_batch, altered, short_probe])
@pytest.mark.parametrize("workload", ["deep96-f32.bulk", "deep96-f32.online"])
def test_planted_fault_is_not_correct(program, fault, workload):
    res = run_tiny(*tiny("deep96-f32", workload), fault(program))
    assert not res["correct"], res["checks"]


def test_unanswered_queries_are_failures(program):
    """Queries still unanswered ``drain_s`` after the close are failures."""
    config, traffic = tiny("deep96-f32", "deep96-f32.online")

    def slow(c, v, i, q, *, nprobe, k):
        time.sleep(0.05)
        return program(c, v, i, q, nprobe=nprobe, k=k)
    res = run_tiny(config, dict(traffic, rate_qps=2000.0, drain_s=0.2), slow)
    assert res["failed"] > 0
    assert not res["correct"]


def _run_script(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "deep96-f32.bulk", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    p = _run_script(ROOT)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = _run_script(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""


def test_every_entry_finds_its_files():
    bench = R.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for cell in bench["workloads"]:
        _, config, traffic, e2e, layer = R.cell_spec(bench, cell["name"])
        assert os.path.exists(os.path.join(BENCH, "loops",
                                           traffic["loop"] + ".py"))
        assert "setup_s" in e2e and len(e2e) >= 2 and layer
        for m in e2e + layer:
            assert hasattr(R.load_part("metrics", m), "read")
        for m in bench["per_layer"]:
            if cell["name"] in m.get("workloads", []):
                assert m["moves"] in e2e
    for entry in bench["configs"]:
        cfg = R.load_json(os.path.join(ROOT, entry["file"]))
        assert set(entry["reduced"]) == set(cfg["reduced"])
        assert json.dumps(cfg)


def test_seed_wider_than_32_bits_gives_its_own_corpus():
    from chipbench import data
    a, _ = data.make_corpus(data.seed_key(2 ** 31 + 5), n=64, n_pool=4,
                            dim=8, intrinsic_dim=4)
    b, _ = data.make_corpus(data.seed_key(5), n=64, n_pool=4, dim=8,
                            intrinsic_dim=4)
    c, _ = data.make_corpus(data.seed_key(2 ** 31 + 5), n=64, n_pool=4,
                            dim=8, intrinsic_dim=4)
    assert not np.array_equal(a, b)
    assert np.array_equal(a, c)
