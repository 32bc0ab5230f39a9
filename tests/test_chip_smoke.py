"""chip_smoke.py: its refusals off the TPU, and its phases at a tiny size.

The phases run here on the CPU with the Pallas interpreter, the way the
script's ``main`` runs them on the chip with compiled kernels.
"""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_script(path, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(path)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_refuses_without_tpu():
    r = _run_script(ROOT / "chip_smoke.py", ROOT)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU found" in r.stderr


def test_refuses_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    r = _run_script(tmp_path / "chip_smoke.py", tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_phases_at_tiny_size(smoke, tmp_path):
    kern = smoke.phase_kernels(str(tmp_path), interpret=True, quick=True)
    assert kern["interpret"] is True and kern["max_roofline_frac"] is None
    assert os.path.exists(kern["table"])
    data, queries = smoke.make_deep(6000, 40, seed=1)
    index, built = smoke.phase_build(data, seed=1)
    assert built["lists"] == index.meta.n_lists
    gt, found = smoke.phase_search(index, data, queries)
    assert found["id_match"] >= smoke.MIN_ID_MATCH
    assert found["recall_device"] > 0.9
    served = smoke.phase_fleet(index, queries, gt, kern["table"])
    assert served["queries"] == 40 and served["recall"] > 0.9


def test_sharded_phase_on_one_device(smoke):
    data, queries = smoke.make_deep(4000, 24, seed=2)
    out = smoke.phase_sharded(data, queries, n_lists=16, chips=1, seed=2,
                              n_exhaustive=8, nprobe_small=2)
    assert out["exhaustive_id_match"] == 1.0
    assert out["shards"] == [f"{jax.devices()[0].id}:16"]


def test_ivf_layout_holds_every_point_once(smoke):
    data = np.random.default_rng(0).standard_normal((500, 8)).astype(
        np.float32)
    lay = smoke.ivf_layout(data, 7, seed=0)
    ids = lay["list_ids"][lay["list_ids"] >= 0]
    assert sorted(ids.tolist()) == list(range(500))
    li, slot = np.nonzero(lay["list_ids"] >= 0)
    np.testing.assert_array_equal(lay["list_vecs"][li, slot],
                                  data[lay["list_ids"][li, slot]])


def test_compile_cache_respects_the_environment(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.use_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before
    assert jax.config.jax_persistent_cache_min_compile_time_secs == min_s
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert compile_cache.use_compile_cache() == \
            compile_cache.REPO_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == \
            str(ROOT / ".jax_cache")
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_s)
