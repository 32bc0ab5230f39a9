"""Run every benchmark harness; one CSV row per measurement:

    name,us_per_call,derived

Set REPRO_BENCH_QUICK=1 for a fast smoke pass (smaller datasets).
Index builds and search traces are cached under benchmarks/.cache.
"""
from __future__ import annotations

import os
import sys
import time
import traceback

# make both import styles work regardless of the caller's cwd:
# "benchmarks.<mod>" (package) and "from common import emit" (script)
_HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (_HERE, os.path.dirname(_HERE)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

MODULES = [
    "benchmarks.kernel_bench",
    "benchmarks.fig2_overheads",
    "benchmarks.fig7_qps_recall",
    "benchmarks.fig8_query_metrics",
    "benchmarks.fig10_datasets",
    "benchmarks.tab4_fig14_16_centroids_replicas",
    "benchmarks.fig17_19_graph_params",
    "benchmarks.fig20_25_caching",
    "benchmarks.tuner_bench",
    "benchmarks.fleet_bench",
    "benchmarks.ingest_bench",
    "benchmarks.tenancy_bench",
    "benchmarks.tier_bench",
]


def main() -> None:
    print("name,us_per_call,derived")
    failures = []
    for modname in MODULES:
        t0 = time.time()
        print(f"# === {modname} ===", file=sys.stderr)
        try:
            mod = __import__(modname, fromlist=["main"])
            if mod.main():                 # rule/fleet benches return 1 on
                failures.append(modname)   # failed hard checks
                print(f"# FAILED {modname} (hard check)", file=sys.stderr)
        except Exception:
            failures.append(modname)
            print(f"# FAILED {modname}", file=sys.stderr)
            traceback.print_exc()
        print(f"# {modname}: {time.time()-t0:.0f}s", file=sys.stderr)
    if failures:
        print(f"# failures: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()
