"""Shared benchmark infrastructure.

* disk-cached index builds (builds are the expensive offline step — the
  paper also builds once on local disk and uploads);
* QPS–recall sweep helper following the paper's §5.1 protocol
  (power-of-2 nprobe / search_len sweeps, early-stop at recall > 0.995);
* CSV emission: every row is ``name,us_per_call,derived`` where
  ``us_per_call`` is mean per-query latency in microseconds under the
  simulated environment and ``derived`` packs the figure-specific fields.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import os
import pickle
import subprocess
import sys
import time

import numpy as np

import repro.core
from repro.core.cluster_index import ClusterIndex
from repro.core.flat import exact_topk
from repro.core.graph_index import GraphIndex
from repro.core.types import (ClusterIndexParams, GraphIndexParams,
                              SearchParams)
from repro.data.synth import (ANALOGS, BIGANN_ANALOG, DEEP_ANALOG,
                              GIST_ANALOG, MSSPACE_ANALOG, DatasetSpec,
                              make_dataset, scaled)
from repro.serving.engine import EngineConfig
from repro.serving.trace import record_traces, replay_workload
from repro.storage.spec import SSD, TOS, StorageSpec

CACHE_DIR = os.environ.get(
    "REPRO_BENCH_CACHE", os.path.join(os.path.dirname(__file__), ".cache"))

QUICK = os.environ.get("REPRO_BENCH_QUICK", "0") == "1"

# benchmark-scale dataset sizes (reduced-cardinality analogues — DESIGN.md
# assumption 3; QUICK mode shrinks further for smoke runs)
_SCALE = 0.2 if QUICK else 1.0


def bench_dataset(name: str) -> DatasetSpec:
    base = {
        "gist-analog": scaled(GIST_ANALOG, int(4000 * _SCALE), 40),
        "deep-analog": scaled(DEEP_ANALOG, int(15000 * _SCALE), 80),
        "msspace-analog": scaled(MSSPACE_ANALOG, int(15000 * _SCALE), 80),
        "bigann-analog": scaled(BIGANN_ANALOG, int(24000 * _SCALE), 80),
        # size-scaling variants for the Fig 13 study
        "bigann-analog-s": scaled(BIGANN_ANALOG, int(6000 * _SCALE), 50),
        "bigann-analog-m": scaled(BIGANN_ANALOG, int(12000 * _SCALE), 50),
    }
    return base[name]


def _source_files(pkg: str) -> list[str]:
    """The package's source files that git tracks (every file under it
    where the tree is not a git checkout)."""
    try:
        out = subprocess.run(["git", "ls-files", "-z", "--", "."], cwd=pkg,
                             capture_output=True, check=True).stdout
        return sorted(f for f in out.decode().split("\0") if f)
    except (OSError, subprocess.CalledProcessError):
        return sorted(
            os.path.relpath(os.path.join(d, f), pkg)
            for d, dirs, files in os.walk(pkg)
            if "__pycache__" not in d for f in files)


@functools.cache
def _source_digest() -> str:
    """Hash of the ``repro`` sources: a cached build is reused only by the
    code that made it."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(
        repro.core.__file__)))
    h = hashlib.sha256()
    for rel in _source_files(pkg):
        h.update(rel.encode())
        with open(os.path.join(pkg, rel), "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _key(*parts) -> str:
    raw = repr((_source_digest(),) + parts).encode()
    return hashlib.sha256(raw).hexdigest()[:24]


def _cache_path(key: str) -> str:
    os.makedirs(CACHE_DIR, exist_ok=True)
    return os.path.join(CACHE_DIR, key + ".pkl")


def cached(key_parts, builder):
    path = _cache_path(_key(*key_parts))
    if os.path.exists(path):
        with open(path, "rb") as f:
            return pickle.load(f)
    obj = builder()
    with open(path, "wb") as f:
        pickle.dump(obj, f, protocol=4)
    return obj


# ------------------------------------------------------------- datasets --

def get_dataset(name: str):
    spec = bench_dataset(name)
    def build():
        data, queries = make_dataset(spec)
        gt, _ = exact_topk(data, queries, 10)
        return data, queries, gt
    return cached(("dataset", spec), build)


# -------------------------------------------------------------- indexes --

def get_cluster_index(dataset: str, params: ClusterIndexParams
                      ) -> ClusterIndex:
    spec = bench_dataset(dataset)
    def build():
        data, _, _ = get_dataset(dataset)
        t0 = time.time()
        idx = ClusterIndex.build(data, params)
        print(f"# built cluster[{dataset},{params}] in {time.time()-t0:.0f}s",
              file=sys.stderr)
        return idx
    return cached(("cluster", spec, params), build)


def get_graph_index(dataset: str, params: GraphIndexParams) -> GraphIndex:
    spec = bench_dataset(dataset)
    def build():
        data, _, _ = get_dataset(dataset)
        t0 = time.time()
        idx = GraphIndex.build(data, params)
        print(f"# built graph[{dataset},{params}] in {time.time()-t0:.0f}s",
              file=sys.stderr)
        return idx
    return cached(("graph", spec, params), build)


DEFAULT_CLUSTER = ClusterIndexParams(centroid_frac=0.16, num_replica=8,
                                     seed=0)
DEFAULT_GRAPH = GraphIndexParams(R=48, L_build=96, build_passes=2, seed=0)


def default_graph_params(dataset: str) -> GraphIndexParams:
    from repro.core.pq import default_pq_dims
    dim = bench_dataset(dataset).dim
    return dataclasses.replace(DEFAULT_GRAPH, pq_dims=default_pq_dims(dim))


# --------------------------------------------------------------- sweeps --

NPROBE_SWEEP = [8, 16, 32, 64, 128, 256, 512, 1024]
SEARCHLEN_SWEEP = [10, 20, 40, 80, 160, 320, 640]


def get_traces(dataset: str, index_kind: str, index, params: SearchParams):
    """Record (and cache) per-query search traces."""
    spec = bench_dataset(dataset)
    def build():
        _, queries, _ = get_dataset(dataset)
        return record_traces(index, queries, params)
    ip = index.meta.params
    return cached(("traces", spec, index_kind, ip, params), build)


def replay(dataset: str, index_kind: str, index, sparams: SearchParams,
           storage: StorageSpec = TOS, concurrency: int = 1,
           cache_bytes: int = 0, seed: int = 0):
    traces = get_traces(dataset, index_kind, index, sparams)
    cfg = EngineConfig(storage=storage, concurrency=concurrency,
                       cache_bytes=cache_bytes, seed=seed)
    rep = replay_workload(index, traces, cfg)
    return rep


def sweep_recall_qps(dataset: str, index_kind: str, index,
                     storage: StorageSpec = TOS, concurrency: int = 1,
                     cache_bytes: int = 0, stop_recall: float = 0.995):
    """Paper §5.1 protocol: sweep the index's knob in powers of two,
    early-stopping once recall > stop_recall.  Returns rows of
    (knob, recall, report)."""
    _, _, gt = get_dataset(dataset)
    rows = []
    knobs = NPROBE_SWEEP if index_kind == "cluster" else SEARCHLEN_SWEEP
    for knob in knobs:
        if index_kind == "cluster":
            if knob > index.meta.n_lists:
                break
            sp = SearchParams(k=10, nprobe=knob)
        else:
            sp = SearchParams(k=10, search_len=knob, beamwidth=16)
        rep = replay(dataset, index_kind, index, sp, storage=storage,
                     concurrency=concurrency, cache_bytes=cache_bytes)
        recall = rep.recall_against(gt)
        rows.append((knob, recall, rep))
        if recall > stop_recall:
            break
    return rows


# ------------------------------------------------------------------ CSV --

def emit(name: str, us_per_call: float, **derived) -> None:
    kv = ";".join(f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
                  for k, v in derived.items())
    print(f"{name},{us_per_call:.2f},{kv}")
