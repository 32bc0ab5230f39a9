#!/usr/bin/env python3
"""Chip benchmark of the HBM-resident cluster search (``device_search_batch``).

    python benchmarks/chip/run.py --workload deep96-f32.bulk --seed 7 \\
        --seconds 10 --trace 0

Everything a cell needs is found by name from ``BENCHMARK.json`` at the
root of the checkout: the cell's configuration in ``configs/<config>.json``,
its traffic in ``traffic/<traffic>.json``, the loop that traffic names in
``loops/<loop>.py``, and a reader per metric in ``metrics/<metric>.py``.
A new cell, mix or metric is new files and entries, never an edit.

A run makes its corpus and query pool on the device from ``--seed``,
builds the padded posting-list layout on the device (``chipbench.layout``),
compiles and warms every batch shape the loop will send (set-up ends
here), then drives the loop for ``--seconds``.  With ``--trace 1`` the
window runs under the profiler and the per-layer metrics are read from
the trace, the search's stage times among them (``chipbench.stages``);
otherwise the end-to-end ones are reported.

The element type is the configuration's ``dtype``: ``float32``, ``int8``
or ``uint8`` (``chipbench.data.DTYPES``); any other, or none, ends the
run with status 2 and no result.  The search under test is called as
``search(centroids, list_vecs, list_ids, queries, nprobe=, k=)`` with
``queries`` (b, D) and ``list_vecs`` (L, slots, D) in that type,
``centroids`` (L, D) float32 and ``list_ids`` (L, slots) int32, -1 in
padding; it returns (ids (b, k) int32, squared distances (b, k) float32).

After the window the device memory peak is read, the program's state is
freed, and every answer is checked against references that import
nothing of the program (``chipbench.reference``).  The last line of
stdout is one JSON object; the compared numbers, each beside its limit,
end both it and stderr.

Without a TPU, or with fewer chips than the cell asks for, the run exits
with status 2 and prints no result.
"""
from __future__ import annotations

T_START = __import__("time").perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import time  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
#: JAX's persistent compilation cache: a fixed path inside the checkout,
#: so that only a cell's first run there compiles
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: the name the search program carries in the trace
SEARCH_PROGRAM = "search_step"


def use_cache() -> None:
    """Keep every compiled program in :data:`CACHE_DIR`, before JAX has
    compiled anything: no size limit, so that no entry is evicted (a limit
    such as ``JAX_COMPILATION_CACHE_MAX_SIZE`` in the environment turns on
    eviction, which recompiles evicted programs in every run)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_part(kind: str, name: str):
    """The module ``<kind>/<name>.py`` beside this file."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"{kind}_{name}", path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(bench: dict, workload: str) -> tuple[dict, dict, dict, list, list]:
    """(workload entry, config, traffic, end-to-end names, per-layer names)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(os.path.join(ROOT, entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))

    def here(m):
        return workload in m.get("workloads", [workload])
    e2e = [m["name"] for m in bench["end_to_end"] if here(m)]
    layer = [m["name"] for m in bench["per_layer"]
             if workload in m.get("workloads", [])
             or ("workloads" not in m and m["moves"] in e2e)]
    return cell, config, traffic, e2e, layer


def chip_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return devs


class Compiles:
    """XLA compiles and persistent-cache loads, from ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.count += 1


def program_search():
    """The system under test: the program's ``device_search_batch``, jitted
    here under the fixed name :data:`SEARCH_PROGRAM`."""
    import jax
    from repro.core.cluster_index import device_search_batch

    def search_step(centroids, list_vecs, list_ids, queries, *, nprobe, k):
        return device_search_batch(centroids, list_vecs, list_ids, queries,
                                   nprobe=nprobe, k=k)
    assert search_step.__name__ == SEARCH_PROGRAM
    return jax.jit(search_step, static_argnames=("nprobe", "k"))


def set_up(config: dict, traffic: dict, *, seed: int, device, search_impl):
    """Corpus, layout and the server the loop drives, every shape warm.

    ``search_impl(centroids, list_vecs, list_ids, queries, *, nprobe, k)``
    is what the window drives: :func:`program_search` in a benchmark run,
    the control or a planted fault in the tests.  Returns (server, layout
    arrays, layout stats, loop module).
    """
    import jax
    import numpy as np

    from chipbench import data, layout, serve

    loop = load_part("loops", traffic["loop"])
    n, dim, k, nprobe = (int(config[x]) for x in ("n", "dim", "k", "nprobe"))
    t0 = time.perf_counter()
    with jax.default_device(device):
        corpus, pool = make_corpus(config, data.seed_key(seed))
        corpus.block_until_ready()
        t1 = time.perf_counter()
        lay, build = layout.build_layout(
            corpus, n_lists=int(round(config["centroid_frac"] * n)),
            iters=int(config["lloyd_iters"]),
            num_replica=int(config["num_replica"]),
            closure_eps=float(config["closure_eps"]),
            max_len=int(config["max_len"]),
            chunk=layout.chunk_for(n, int(config["build_chunk"])))
        del corpus
        lay["list_vecs"].block_until_ready()
        t2 = time.perf_counter()
        pool_h = np.asarray(pool)
        del pool
    c, v, i = lay["centroids"], lay["list_vecs"], lay["list_ids"]
    server = serve.Server(
        device=device, pool=pool_h, dim=dim,
        search=lambda q: search_impl(c, v, i, q, nprobe=nprobe, k=k))
    t3 = time.perf_counter()
    for b in loop.warm_shapes(config, traffic):
        for _ in range(2):
            serve.serve_batch(server, pool_h[:b])
    build["stages_s"] = dict(corpus=t1 - t0, layout=t2 - t1, pool=t3 - t2,
                             warm=time.perf_counter() - t3)
    return server, lay, build, loop


def settle_heap() -> None:
    """Collect once and freeze what set-up made.  A full collection walks
    every object of a JAX process (~0.1 s); without the freeze one lands
    in some windows and stalls the loop for that long."""
    gc.collect()
    gc.freeze()


def make_corpus(config: dict, key):
    from chipbench import data
    return data.make_corpus(
        key, n=int(config["n"]), n_pool=int(config["pool"]),
        dim=int(config["dim"]), intrinsic_dim=int(config["intrinsic_dim"]),
        dtype=data.element_type(config))


def measure(server, loop, config: dict, traffic: dict, seconds: float,
            seed: int, trace_dir: str | None = None) -> dict:
    """The measured window: the loop's record.  With ``trace_dir``, under
    the profiler, which writes its trace there."""
    import jax
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        return loop.run(server, config, traffic, seconds, seed)
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
        gc.unfreeze()


def read_trace(rec: dict, trace_dir: str, *, search_impl, lay: dict,
               pool, shapes: list[int], config: dict, device) -> None:
    """Reduce a traced window into ``rec``, then remove ``trace_dir``:
    ``trace`` (device and host times, with the search's ``stages``
    charged op by op from the programs of every warm shape),
    ``search_bytes`` and the device's ``peaks``."""
    import jax
    import numpy as np

    from chipbench import stages, tracing
    nprobe, k = int(config["nprobe"]), int(config["k"])
    try:
        rec["trace"] = tracing.reduce_dir(trace_dir, program=SEARCH_PROGRAM)
        stages.add_to_trace(
            rec, trace_dir, SEARCH_PROGRAM, search_impl,
            (lay["centroids"], lay["list_vecs"], lay["list_ids"]),
            [jax.device_put(pool[:b], device) for b in shapes],
            nprobe=nprobe, k=k)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    lens = np.asarray(jax.numpy.sum(lay["list_ids"] >= 0, axis=1))
    rec["search_bytes"] = tracing.search_bytes(
        pool[rec["qidx"]], np.asarray(lay["centroids"]), lens,
        n_batches=len(rec["batches"]), nprobe=nprobe)
    rec["peaks"] = tracing.peaks_of(device)


def run_cell(config: dict, traffic: dict, *, seed: int, seconds: float,
             trace: bool, device, metric_names: list[str], t_start: float,
             search_impl, log=None) -> dict:
    """One run of one cell on ``device``; returns the result object.
    ``search_impl`` as for :func:`set_up`."""
    import jax
    import numpy as np

    from chipbench import data, readings, reference, stages
    from chipbench.stats import percentile

    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    compiles = Compiles()
    n, k, nprobe = (int(config[x]) for x in ("n", "k", "nprobe"))
    server, lay, build, loop = set_up(config, traffic, seed=seed,
                                      device=device, search_impl=search_impl)
    pool_h = server.pool
    settle_heap()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s; layout {build}")

    # ---- the measured window
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    before = compiles.count
    rec = measure(server, loop, config, traffic, seconds, seed, trace_dir)
    in_window = compiles.count - before
    stats = device.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    rec.update(setup_s=setup_s, config=config, traffic=traffic,
               compiles_in_window=in_window)
    log(f"window {rec['window_s']:.3f} s, {rec['answered']} of "
        f"{rec['attempted']} answered, {len(rec['batches'])} batches, "
        f"compiles in window {in_window}, peak {peak} B")

    if trace:
        read_trace(rec, trace_dir, search_impl=search_impl, lay=lay,
                   pool=pool_h, shapes=loop.warm_shapes(config, traffic),
                   config=config, device=device)

    # ---- free the program's state, then the references
    t_ref = time.perf_counter()
    del server
    rng = np.random.default_rng([seed, 1])
    answered = len(rec["qidx"])
    few = rng.choice(answered, size=min(int(config["recall_sample"]),
                                        answered), replace=False)
    with jax.default_device(device):
        corpus, _ = make_corpus(config, data.seed_key(seed))
        exact = np.asarray(reference.exact_topk(
            corpus, pool_h[rec["qidx"][few]], k=k))
        corpus_h = np.asarray(corpus)
        del corpus, _
    ref, scale = reference.served_sq(corpus_h, pool_h, rec["qidx"],
                                     rec["ids"])
    flagged = reference.screen(lay, pool_h, rec["qidx"], rec["ids"], ref,
                               scale, nprobe=nprobe)
    cents_h = np.asarray(lay["centroids"])
    ids_h = np.asarray(lay["list_ids"])
    del lay
    host = flagged
    if len(host) > reference.HOST_CHECKS:
        host = np.sort(rng.choice(flagged, reference.HOST_CHECKS,
                                  replace=False))
    numbers = dict(
        unanswered=rec["attempted"] - rec["answered"],
        bad_answers=reference.bad_answers(rec["ids"], rec["dists"], n),
        **reference.dist_errors(rec["dists"], ref, scale),
        rank_gap=reference.rank_gap(
            corpus_h, pool_h, cents_h, ids_h, rec["qidx"][host],
            rec["ids"][host], nprobe, k),
        screened=answered, not_cleared=len(flagged))
    limits = dict(unanswered=0, bad_answers=0, **config["limits"])
    checks = {name: (numbers.pop(name), lim) for name, lim in limits.items()}
    info = dict(recall=reference.recall(rec["ids"][few], exact),
                setup_s=setup_s, reference_s=time.perf_counter() - t_ref,
                pool_wrapped=rec["wrapped"], compiles_in_window=in_window,
                layout=build, **numbers)
    for key_ in ("offered_qps", "achieved_qps", "backlog_at_close",
                 "worst_latency_s", "worst_due_s"):
        if key_ in rec:
            info[key_] = rec[key_]
    if rec.get("trace"):
        # what charging the stages cost after the window, the search's
        # device time that the stages split, and the part of it that no
        # stage explains
        info["stages_cost_s"] = rec["trace"]["stages_cost_s"]
        searched = readings.search_device_ms(rec)
        info["search_device_ms"] = searched and searched[0]
        found = rec["trace"]["stages"]
        if found:
            info["other_device_ms"] = \
                1e3 * found["seconds"][stages.OTHER] / found["executions"]
            info["unexplained_programs"] = len(found["unexplained"])
    if len(rec.get("latencies_s", ())):
        # the tail, printed for the record: a ~0.12 s stall of the shared
        # host lands in about half of all windows and moves it tenfold
        for p in (95, 99):
            info[f"p{p}_ms"] = 1e3 * percentile(rec["latencies_s"], p)
    log("info " + json.dumps(info, sort_keys=True))
    correct = all(v <= lim for v, lim in checks.values())

    metrics = {}
    for name in metric_names:
        value = load_part("metrics", name).read(rec)
        if value is not None:
            metrics[name] = {"value": float(value[0]), "unit": value[1]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": int(rec["attempted"]),
              "failed": int(rec["attempted"] - rec["answered"]),
              "metrics": metrics, "device": dev}
    if trace and rec["trace"]:
        dev["busy_s"] = rec["trace"]["busy_s"]
        dev["window_s"] = rec["trace"]["window_s"]
        result["breakdown"] = rec["trace"]["breakdown"]
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, (v, lim) in checks.items()}
    for name, (v, lim) in checks.items():
        log(f"check {name} {v!r} limit {lim!r}")
    return result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config, traffic, e2e, layer = cell_spec(bench, args.workload)
    use_cache()
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    from chipbench import data
    try:
        data.element_type(config)
    except ValueError as e:
        print(f"run.py: {e}; no result", file=sys.stderr)
        return 2
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    try:
        devices = chip_devices(int(cell["chips"]))
    except NoChip as e:
        print(f"run.py: {e}; no result", file=sys.stderr)
        return 2
    result = run_cell(config, traffic, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), device=devices[0],
                      metric_names=layer if args.trace else e2e,
                      t_start=T_START, search_impl=program_search())
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
