"""The benchmarks' build cache is keyed on the ``repro`` sources."""
import importlib.util
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def common():
    spec = importlib.util.spec_from_file_location(
        "bench_common", ROOT / "benchmarks" / "common.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_source_files_are_the_package_sources(common):
    files = common._source_files(str(ROOT / "src" / "repro"))
    assert "core/cluster_index.py" in files
    assert not any("__pycache__" in f for f in files)


def test_key_changes_with_the_sources(common, monkeypatch):
    before = common._key("index", 1)
    assert common._key("index", 1) == before
    monkeypatch.setattr(common, "_source_digest", lambda: "edited")
    assert common._key("index", 1) != before
