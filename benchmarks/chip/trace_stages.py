#!/usr/bin/env python3
"""Device time of each stage of the search step over one traced window of
a cell, and the host's own events in the slowest batch's result copy:

    python benchmarks/chip/trace_stages.py --workload deep96-f32.bulk \\
        --seed 7 --seconds 10

One set-up and one traced window, by the same code as ``run.py --trace
1`` (``run.set_up``, ``run.measure``, ``run.read_trace``), whose search
ops ``chipbench.stages`` charges stage by stage.  Prints one JSON line:
the ms a batch of each stage and of ``other``, the search program's
device ms a batch, the seconds the stage map and its reduction took after
the window, and the slowest batch's result copy.  Answers are not
checked: a benchmark run reports the stages, checked, as its
``<stage>_device_ms`` metrics; this shows the whole reduction.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

import run as R


def trace_cell(config: dict, traffic: dict, *, seed: int, seconds: float,
               device) -> dict:
    """One set-up and one traced window of the cell on ``device``."""
    from chipbench import readings, stages

    search = R.program_search()
    server, lay, build, loop = R.set_up(config, traffic, seed=seed,
                                        device=device, search_impl=search)
    R.settle_heap()
    trace_dir = tempfile.mkdtemp(prefix="chipbench-stages-")
    rec = R.measure(server, loop, config, traffic, seconds, seed, trace_dir)
    R.read_trace(rec, trace_dir, search_impl=search, lay=lay,
                 pool=server.pool, shapes=loop.warm_shapes(config, traffic),
                 config=config, device=device)
    t = rec["trace"] or {}
    found = t.get("stages")
    out = dict(batches=len(rec["batches"]), layout=build)
    for stage in stages.STAGES:
        reading = stages.device_ms(stage)(rec)
        out[f"{stage}_device_ms"] = reading and reading[0]
    out["other_device_ms"] = found and (
        1e3 * found["seconds"][stages.OTHER] / found["executions"])
    reading = readings.search_device_ms(rec)
    out["search_device_ms"] = reading and reading[0]
    out.update(stages=found, worst_copy=t.get("worst_copy"),
               stages_cost_s=t.get("stages_cost_s"))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench = R.load_json(os.path.join(R.ROOT, "BENCHMARK.json"))
    cell, config, traffic, _, _ = R.cell_spec(bench, args.workload)
    R.use_cache()
    sys.path.insert(0, os.path.join(R.ROOT, "src"))
    try:
        device = R.chip_devices(int(cell["chips"]))[0]
    except R.NoChip as e:
        print(f"trace_stages.py: {e}", file=sys.stderr)
        return 2
    out = trace_cell(config, traffic, seed=args.seed, seconds=args.seconds,
                     device=device)
    print(json.dumps(dict(workload=args.workload, seed=args.seed, **out)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
