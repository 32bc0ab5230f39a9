#!/usr/bin/env python3
"""Readings of the compared numbers on the chip, for the program and for
the control, many seeds in one process (the set-up is long):

    python benchmarks/chip/control.py --workload deep96-f32.bulk \\
        --impl program --seeds 1,2,3 --seconds 3

``--impl``: ``program`` (the timed path as a benchmark run drives it),
``program-bf16`` (the program with its own precision switch,
``repro.core.distances.F32_DOT``, at ``Precision.DEFAULT``: one bf16 pass,
the precision below the configuration's float32; on the TPU it reaches
the centroid probe, which ``rank_gap`` reads) or ``control``
(``chipbench.reference.control_search``: the reference search on
bfloat16-rounded vectors, which ``dist_err`` reads).  Each is a process
of its own, so that nothing traced at another precision is reused.  Each
seed prints one JSON line with every number the run compares or reports;
the limits in ``configs/`` are set from these readings.  A benchmark run
never runs this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import run as R


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--impl", required=True, choices=(
        "program", "program-bf16", "control"))
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench = R.load_json(os.path.join(R.ROOT, "BENCHMARK.json"))
    cell, config, traffic, _, _ = R.cell_spec(bench, args.workload)
    R.use_cache()
    sys.path.insert(0, os.path.join(R.ROOT, "src"))
    import jax
    try:
        device = R.chip_devices(int(cell["chips"]))[0]
    except R.NoChip as e:
        print(f"control.py: {e}", file=sys.stderr)
        return 2
    if args.impl == "control":
        from chipbench.reference import control_search as impl
    else:
        if args.impl == "program-bf16":
            from repro.core import distances
            distances.F32_DOT = jax.lax.Precision.DEFAULT
        impl = R.program_search()
    for seed in (int(s) for s in args.seeds.split(",")):
        info = {}

        def log(line, info=info):
            if line.startswith("info "):
                info.update(json.loads(line[5:]))
        t = time.perf_counter()
        res = R.run_cell(config, traffic, seed=seed, seconds=args.seconds,
                         trace=False, device=device, metric_names=[],
                         t_start=t, search_impl=impl, log=log)
        print(json.dumps(dict(
            workload=args.workload, impl=args.impl, seed=seed,
            correct=res["correct"], run_s=time.perf_counter() - t,
            memory_peak_bytes=res["device"]["memory_peak_bytes"],
            checks={k: v["value"] for k, v in res["checks"].items()},
            info=info)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
