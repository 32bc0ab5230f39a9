#!/usr/bin/env python3
"""Knee sweep of an open-loop cell: the offered rate the search sustains.

    python benchmarks/chip/sweep.py --workload deep96-f32.online \\
        --rates 2000,4000,6000,8000 --seconds 10 --seed 5

One set-up (corpus, layout, every bucket warm), then one window per rate,
from the lowest up, each with the cell's traffic at that rate.  Each rate
prints one JSON line: offered and achieved rate, the backlog when the
window closed, and the p50 and p99 latency.  The knee is the highest rate
whose achieved rate is within 2% of the offered one and whose backlog at
the close is at most one largest batch; the cell's rate is 0.8 of it,
rounded down to 100 queries/s, written into its traffic file by hand.
The benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import run as R


def sustained(line: dict, max_batch: int) -> bool:
    """Whether one sweep point kept up with its offered rate."""
    return (line["achieved_qps"] >= 0.98 * line["offered_qps"]
            and line["backlog_at_close"] <= max_batch
            and line["unanswered"] == 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    bench = R.load_json(os.path.join(R.ROOT, "BENCHMARK.json"))
    cell, config, traffic, _, _ = R.cell_spec(bench, args.workload)
    R.use_cache()
    sys.path.insert(0, os.path.join(R.ROOT, "src"))
    from chipbench.stats import percentile
    try:
        device = R.chip_devices(int(cell["chips"]))[0]
    except R.NoChip as e:
        print(f"sweep.py: {e}", file=sys.stderr)
        return 2
    server, _, build, loop = R.set_up(config, traffic, seed=args.seed,
                                      device=device,
                                      search_impl=R.program_search())
    print(json.dumps({"layout": build}), flush=True)
    R.settle_heap()
    knee = None
    max_batch = int(traffic["max_batch"])
    for rate in sorted(float(r) for r in args.rates.split(",")):
        rec = loop.run(server, config, dict(traffic, rate_qps=rate),
                       args.seconds, args.seed)
        lat = rec["latencies_s"]
        line = dict(offered_qps=rec["offered_qps"],
                    achieved_qps=rec["achieved_qps"],
                    backlog_at_close=rec["backlog_at_close"],
                    unanswered=rec["attempted"] - rec["answered"],
                    batches=len(rec["batches"]),
                    mean_batch=rec["answered"] / max(len(rec["batches"]), 1),
                    p50_ms=1e3 * percentile(lat, 50) if len(lat) else None,
                    p99_ms=1e3 * percentile(lat, 99) if len(lat) else None)
        line["sustained"] = sustained(line, max_batch)
        if line["sustained"]:
            knee = rate
        print(json.dumps(line), flush=True)
    print(json.dumps({"knee_qps": knee,
                      "cell_rate_qps": None if knee is None
                      else 100 * int(0.8 * knee // 100)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
