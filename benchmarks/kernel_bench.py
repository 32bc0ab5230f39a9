"""Kernel micro-benchmarks + calibrated-pricing regression gate.

Writes ``BENCH_kernels.json`` at the repo root (gated by
``check_regression.py`` like the fleet/ingest/tenancy benches).  Three
sections, split by what can be gated deterministically:

1. **parity** — the batched MXU execution path (``repro.exec.batched``)
   against the numpy oracles: result ids must be bit-identical on random
   floats, and ids *and* distances bit-identical on integer-valued
   vectors (exact float32 sums).  Hard checks; the booleans are gated.
2. **pricing** — ``plan_seconds`` rows computed from the *committed*
   CalibrationTable over a fixed (dim, pq_m, work, batch) grid.  Pure
   arithmetic on committed JSON, so identical on every machine; gated at
   the default tolerance.  Hard check: batching amortizes (large-batch
   unit cost below batch-of-one).
3. **meta.timings** — measured wall-clock rows for the XLA:CPU reference
   paths and a Pallas-interpret spot check.  Timing is per-host noise,
   so these live under ``meta`` which the regression gate never compares
   (they still land in the CSV stream for eyeballing).

On this CPU container the Pallas kernels run under the interpreter, so
interpret rows measure the interpreter, not TPU silicon; on TPU hardware
the same ops compile to Mosaic and ``repro.exec.calibrate`` re-measures
the table.
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import numpy as np

from common import QUICK, emit

from repro.exec import batched_topk, load_table, scan_topk_oracle
from repro.kernels import ops
from repro.kernels.ref import adc_lookup_ref, l2_distance_ref
from repro.obs import run_manifest

OUT_PATH = os.path.join(os.path.dirname(__file__), os.pardir,
                        "BENCH_kernels.json")

_failures: list[str] = []


def _check(name: str, ok: bool, detail: str) -> None:
    print(f"# [{name}] {'PASS' if ok else 'FAIL'}: {detail}",
          file=sys.stderr)
    if not ok:
        _failures.append(name)


def _time_us(fn, *args, iters=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


# ------------------------------------------------------------- parity --

def bench_parity() -> list[dict]:
    """Batched execution path vs the numpy oracles (see repro.exec)."""
    rng = np.random.default_rng(0)
    cases = [("b3.n200.d32.k10", 3, 200, 32, 10, False),
             ("b9.n300.d64.k10", 9, 300, 64, 10, False)]
    if not QUICK:
        cases += [("b1.n50.d16.k8", 1, 50, 16, 8, False),
                  ("b5.n160.d32.k10.int", 5, 160, 32, 10, True)]
    else:
        cases += [("b5.n160.d32.k10.int", 5, 160, 32, 10, True)]
    rows = []
    for name, b, n, d, k, integer in cases:
        if integer:     # small integers: float32 sums are exact, so the
            q = rng.integers(-8, 8, (b, d)).astype(np.float32)
            x = rng.integers(-8, 8, (n, d)).astype(np.float32)
        else:
            q = rng.standard_normal((b, d)).astype(np.float32)
            x = rng.standard_normal((n, d)).astype(np.float32)
        vk, ik = batched_topk(q, x, k)
        vo, io = scan_topk_oracle(q, x, k)
        ids_eq = bool(np.array_equal(ik, io))
        vals_eq = bool(np.array_equal(vk, vo))
        vals_close = bool(np.allclose(vk, vo, rtol=1e-5, atol=1e-5))
        rows.append(dict(case=name, batch=b, n=n, dim=d, k=k,
                         integer_valued=integer, ids_identical=ids_eq,
                         vals_identical=vals_eq, vals_close=vals_close))
        emit(f"kernel/parity-{name}", 0.0, ids_identical=int(ids_eq),
             vals_identical=int(vals_eq))
    _check("kernel-parity-ids",
           all(r["ids_identical"] for r in rows),
           "batched_topk result ids bit-identical to the numpy oracle "
           "on every case")
    _check("kernel-parity-vals-close",
           all(r["vals_close"] for r in rows),
           "batched_topk distances within float tolerance everywhere")
    _check("kernel-parity-int-exact",
           all(r["vals_identical"] for r in rows if r["integer_valued"]),
           "integer-valued inputs: distances bit-identical too")
    return rows


# ------------------------------------------------------------ pricing --

PRICING_GRID = [
    # (dim, pq_m, d_dist, d_pq, batch_jobs) — scan-only and PQ'd plans
    (32, 0, 4096, 0, 1), (32, 0, 4096, 0, 8), (32, 0, 4096, 0, 64),
    (128, 0, 4096, 0, 1), (128, 0, 4096, 0, 64),
    (64, 8, 512, 2048, 1), (64, 8, 512, 2048, 64),
    (128, 16, 512, 2048, 8),
]


def bench_pricing() -> dict:
    """Deterministic pricing rows from the committed CalibrationTable."""
    table = load_table()
    rows = []
    for dim, pq_m, d_dist, d_pq, batch in PRICING_GRID:
        lookups = d_pq * max(pq_m, 1)
        sec = table.plan_seconds(
            d_dist, d_pq, dim, pq_m,
            dist_batch=batch * d_dist, adc_batch=batch * lookups)
        rows.append(dict(dim=dim, pq_m=pq_m, d_dist=d_dist, d_pq=d_pq,
                         batch_jobs=batch, seconds=round(sec, 12)))
        emit(f"kernel/price-d{dim}m{pq_m}b{batch}", sec * 1e6,
             d_dist=d_dist, d_pq=d_pq)
    amort = {}
    for dim in (32, 128):
        solo, bulk = table.dist_unit_s(dim, 1), table.dist_unit_s(dim, 1e5)
        amort[str(dim)] = round(solo / bulk, 3)
        _check(f"kernel-pricing-amortizes-d{dim}", bulk < solo,
               f"dim={dim} unit cost {solo:.3e}s/comp at batch 1 vs "
               f"{bulk:.3e} at batch 1e5 (want batching cheaper)")
    # a table measured off the TPU records no roofline share
    frac = max((r["roofline_frac"] for r in table.meta["rooflines"]),
               default=0.0)
    _check("kernel-pricing-roofline-sane", frac < 1.0,
           f"max measured roofline fraction {frac:.2e} (want < 1)")
    return dict(table_entries=len(table.entries),
                backend=table.meta.get("backend"),
                amortization=amort, rows=rows)


# ------------------------------------------- measured timings (ungated) --

def bench_timings() -> list[dict]:
    rng = np.random.default_rng(0)
    iters, warmup = (1, 1) if QUICK else (5, 2)
    rows = []

    n, d = (2048, 96) if QUICK else (8192, 960)
    q = rng.standard_normal((64, d)).astype(np.float32)
    x = rng.standard_normal((n, d)).astype(np.float32)
    flops = 2.0 * q.shape[0] * n * d
    us = _time_us(jax.jit(l2_distance_ref), q, x, iters=iters,
                  warmup=warmup)
    rows.append(dict(name=f"dist.q64.n{n}.d{d}.ref", us=round(us, 2),
                     gflops=round(flops / us / 1e3, 3), interpret=0))
    us_k = _time_us(lambda a, b: ops.l2_distance(a, b, interpret=True),
                    q[:8], x[:512], iters=1, warmup=1)
    rows.append(dict(name=f"dist.q8.n512.d{d}.pallas_interp",
                     us=round(us_k, 2), interpret=1))

    nc = 2048 if QUICK else 65536
    codes = rng.integers(0, 256, (nc, 112)).astype(np.int32)
    tab = rng.random((112, 256)).astype(np.float32)
    us = _time_us(jax.jit(adc_lookup_ref), codes, tab, iters=iters,
                  warmup=warmup)
    rows.append(dict(name=f"adc.n{nc}.m112.ref", us=round(us, 2),
                     interpret=0))

    bq, bn = (8, 512) if QUICK else (32, 2048)
    q2 = rng.standard_normal((bq, 64)).astype(np.float32)
    x2 = rng.standard_normal((bn, 64)).astype(np.float32)
    us = _time_us(lambda a, b: batched_topk(a, b, 10)[0], q2, x2,
                  iters=iters, warmup=warmup)
    rows.append(dict(name=f"exec.batched_topk.q{bq}.n{bn}.d64",
                     us=round(us, 2),
                     unit_ns=round(us * 1e3 / (bq * bn), 3)))

    for r in rows:
        emit(f"kernel/{r['name']}", r["us"],
             **{k: v for k, v in r.items() if k not in ("name", "us")})
    return rows


def main() -> int:
    t0 = time.perf_counter()
    results = dict(
        bench="kernels",
        quick=QUICK,
        parity=bench_parity(),
        pricing=bench_pricing(),
        failures=_failures,
    )
    results["meta"] = run_manifest(
        seed=0, config=dict(bench="kernels", quick=QUICK),
        wall_s=time.perf_counter() - t0)
    # measured wall-clock: per-host noise, kept out of the gate's reach
    results["meta"]["timings"] = bench_timings()
    results["meta"]["wall_s"] = round(time.perf_counter() - t0, 3)
    with open(OUT_PATH, "w") as f:
        json.dump(results, f, indent=2)
        f.write("\n")
    print(f"# wrote {os.path.abspath(OUT_PATH)}", file=sys.stderr)
    if _failures:
        print(f"# kernel_bench: FAILED {_failures}", file=sys.stderr)
        return 1
    print("# kernel_bench: all kernel checks passed", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
