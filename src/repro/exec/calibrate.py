"""Calibration harness: measure the kernel backend, persist the table.

Times the *actual* execution backend — :func:`repro.exec.batched.batched_topk`
(fused distance + top-k, the serving scan op) and :func:`repro.kernels.ops.
adc_lookup` — over a grid of (dim, pq_m, batch size) points, converts each
point to a ``unit_s`` (seconds per distance computation / per ADC lookup)
and persists a :class:`~repro.exec.table.CalibrationTable` JSON.  On this
container the backend is Pallas interpret / XLA:CPU; on a TPU the same
calls compile to Mosaic and the measured numbers change accordingly —
which is the point: pricing follows the hardware, not hand-set constants.

On a TPU each dist point is cross-checked against the chip's published
peaks (:mod:`repro.exec.peaks`, keyed by ``device_kind``): a point
faster than its roofline would mean the timer is lying, so that fails
loudly; the roofline share is recorded in the table meta either way.  Off
the TPU no roofline share is recorded.

CLI::

    python -m repro.exec.calibrate --out calibration.json [--quick]

The committed default table (``calibration_default.json``) was generated
with this harness once; re-run to re-measure for your host.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from repro.exec.batched import batched_topk
from repro.exec.table import CalibEntry, CalibrationTable
from repro.kernels import ops

__all__ = ["measure_table", "main"]

#: (B queries, N candidates) points per dim — the batch axis is B*N pairs.
DIST_POINTS = [(1, 128), (4, 512), (8, 1024), (32, 2048)]
DIST_POINTS_QUICK = [(1, 128), (8, 1024)]
DIMS = [16, 32, 64, 128]
DIMS_QUICK = [32, 64]
#: (n codes, ) points per pq_m — the batch axis is n*m lookups.
ADC_POINTS = [256, 2048, 16384]
ADC_POINTS_QUICK = [256, 2048]
PQ_MS = [8, 16]
PQ_MS_QUICK = [8]
TOPK = 10


def _time(fn, iters: int, warmup: int) -> float:
    """Median wall-clock seconds per call (warmed; result synced)."""
    for _ in range(warmup):
        fn()
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        samples.append(time.perf_counter() - t0)
    samples.sort()
    return samples[len(samples) // 2]


def measure_table(quick: bool = False, *, iters: int | None = None,
                  seed: int = 0, verbose: bool = False,
                  interpret: bool | None = None) -> CalibrationTable:
    """Run the measurement grid and build a :class:`CalibrationTable`.

    ``interpret`` is passed to every kernel call (``None`` auto-detects:
    the Pallas interpreter off the TPU).
    """
    iters = iters or (2 if quick else 5)
    warmup = 1 if quick else 2
    dims = DIMS_QUICK if quick else DIMS
    dist_points = DIST_POINTS_QUICK if quick else DIST_POINTS
    pq_ms = PQ_MS_QUICK if quick else PQ_MS
    adc_points = ADC_POINTS_QUICK if quick else ADC_POINTS
    rng = np.random.default_rng(seed)

    import jax
    from repro.exec.peaks import device_peaks

    device = jax.devices()[0]
    peaks = device_peaks(device)
    entries: list[CalibEntry] = []
    rooflines: list[dict] = []
    for dim in dims:
        for bq, n in dist_points:
            q = rng.standard_normal((bq, dim)).astype(np.float32)
            x = rng.standard_normal((n, dim)).astype(np.float32)
            sec = _time(lambda: batched_topk(q, x, TOPK,
                                             interpret=interpret),
                        iters, warmup)
            pairs = bq * n
            achieved = 2.0 * dim * pairs / sec
            entries.append(CalibEntry(
                op="dist", dim=dim, pq_m=0, batch=pairs, dtype="float32",
                unit_s=sec / pairs, us_per_call=sec * 1e6))
            if peaks is not None:
                hbm_bytes = 4 * (bq + n) * dim + 8 * bq * TOPK
                floor_s = max(2.0 * dim * pairs / peaks["bf16_flops"],
                              hbm_bytes / peaks["hbm_Bps"])
                frac = floor_s / sec
                if frac > 1.0:
                    raise RuntimeError(
                        f"calibration point dim={dim} pairs={pairs} took "
                        f"{sec:.3e}s, under the roofline floor "
                        f"{floor_s:.3e}s of {device.device_kind} — timer "
                        f"is broken")
                rooflines.append(dict(dim=dim, batch=pairs,
                                      achieved_gflops=achieved / 1e9,
                                      roofline_frac=frac))
            if verbose:
                print(f"  dist dim={dim:<4} pairs={pairs:<6} "
                      f"{sec * 1e6:9.1f} us/call  "
                      f"{achieved / 1e9:8.3f} GFLOP/s", file=sys.stderr)
    for m in pq_ms:
        for n in adc_points:
            codes = rng.integers(0, 256, (n, m), dtype=np.uint8)
            table = rng.standard_normal((m, 256)).astype(np.float32)
            sec = _time(
                lambda: np.asarray(ops.adc_lookup(codes, table,
                                                  interpret=interpret)),
                iters, warmup)
            lookups = n * m
            entries.append(CalibEntry(
                op="adc", dim=0, pq_m=m, batch=lookups, dtype="uint8",
                unit_s=sec / lookups, us_per_call=sec * 1e6))
            if verbose:
                print(f"  adc  m={m:<6} codes={n:<6} "
                      f"{sec * 1e6:9.1f} us/call", file=sys.stderr)

    meta = dict(backend=device.platform,
                device_kind=device.device_kind,
                interpret=(ops.default_interpret() if interpret is None
                           else bool(interpret)),
                jax=jax.__version__,
                quick=bool(quick), iters=iters, topk=TOPK,
                rooflines=rooflines,
                generated_by="python -m repro.exec.calibrate")
    return CalibrationTable(entries, meta=meta)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.exec.calibrate",
        description="Measure the kernel backend and write a "
                    "CalibrationTable JSON.")
    ap.add_argument("--out", default="calibration.json",
                    help="output path (default: %(default)s)")
    ap.add_argument("--quick", action="store_true",
                    help="small grid, few iters (CI smoke)")
    ap.add_argument("--iters", type=int, default=None,
                    help="timing iterations per point")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--summary", action="store_true",
                    help="print the table summary JSON to stdout")
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    table = measure_table(quick=args.quick, iters=args.iters,
                          seed=args.seed, verbose=True)
    table.save(args.out)
    print(f"wrote {args.out}: {len(table.entries)} entries in "
          f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)
    if args.summary:
        print(json.dumps(table.describe(), indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    raise SystemExit(main())
