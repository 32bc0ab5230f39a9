"""Reduction of a profiler trace to the per-layer numbers, and the byte
count that a search step cannot do without.

The trace is read with ``jax.profiler.ProfileData`` alone.  The device is
the ``/device:TPU:0`` plane: its ``XLA Ops`` line holds every operation
that ran, its ``XLA Modules`` line one event per execution of a compiled
program (the search program among them, by its fixed name).  The host
plane holds the benchmark's own spans (``chipbench.serve``), on the same
clock.  The window is the stretch from the first span of the loop to the
end of its last batch.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

import numpy as np

from chipbench import stats
from chipbench.peaks import peaks_for
from chipbench.serve import ASSEMBLE, BATCH, COPY, PUT, SEARCH, WAIT

#: host spans a device gap can be charged to, innermost first
LEAF_SPANS = (ASSEMBLE, PUT, SEARCH, COPY, WAIT)
TOP = 10


def op_name(hlo: str) -> str:
    """A short name for an ``XLA Ops`` event, whose name is the HLO text:
    its name, opcode (or custom-call target) and result shape, as in
    ``fusion.5 fusion (f32[256,320], s32[256,320])``."""
    name, _, rest = hlo.partition(" = ")
    name = name.lstrip("%")
    opcode = re.search(r"\}? ([a-z][a-z0-9_-]*)\(", rest)
    if not opcode:
        return name
    shape = re.sub(r"\{[^}]*\}", "", rest[:opcode.start() + 1]).strip()
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    kind = target.group(1) if target else opcode.group(1)
    return f"{name} {kind} {shape}"


def _events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
    return []


def load(path: str):
    """(device op events, program events, host span events) of a trace
    file, each a list of (name, start_ns, end_ns)."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    planes = {p.name: p for p in pd.planes}
    dev = planes.get("/device:TPU:0")
    ops = _events(dev, "XLA Ops") if dev is not None else []
    modules = _events(dev, "XLA Modules") if dev is not None else []
    spans = []
    host = planes.get("/host:CPU")
    wanted = set(LEAF_SPANS) | {BATCH}
    if host is not None:
        for line in host.lines:
            spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events if e.name in wanted)
    return ops, modules, spans


def reduce(ops, modules, spans, program: str) -> dict | None:
    """The numbers of one traced window; ``None`` where it holds no batch."""
    batches = [(s, e) for n, s, e in spans if n == BATCH]
    if not batches:
        return None
    lo = min(min(s for s, _ in batches),
             min((s for n, s, _ in spans if n == WAIT), default=np.inf))
    hi = max(e for _, e in batches)
    window = hi - lo
    inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
              if e > lo and s < hi]
    busy = stats.merged([(s, e) for _, s, e in inside])
    busy_ns = sum(e - s for s, e in busy)
    prog = [(s, e) for n, s, e in modules
            if program in n and e > lo and s < hi]
    per_op: dict[str, float] = defaultdict(float)
    for n, s, e in inside:
        per_op[op_name(n)] += e - s
    gaps = np.asarray(_gaps(busy, lo, hi), dtype=np.float64).reshape(-1, 2)
    idle_by: dict[str, float] = defaultdict(float)
    charged = 0.0
    for n, s, e in spans:
        if n in LEAF_SPANS:
            ov = _overlap(gaps, s, e)
            idle_by["idle in " + n] += ov
            charged += ov
    idle_ns = window - busy_ns
    if idle_ns - charged > 0:
        idle_by["idle outside spans"] += idle_ns - charged

    def top(d):
        return [[k, v * 1e-9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    return dict(
        window_s=window * 1e-9, busy_s=busy_ns * 1e-9,
        program_s=sum(e - s for s, e in prog) * 1e-9, program_n=len(prog),
        batch_host_s=sum(e - s for s, e in batches) * 1e-9,
        n_batches=len(batches),
        breakdown=dict(device_ops=top(per_op), idle_gaps=top(idle_by)))


def _gaps(busy, lo, hi):
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _overlap(gaps: np.ndarray, s, e) -> float:
    """Length of (s, e) that falls in the sorted, disjoint ``gaps``."""
    i = max(0, int(np.searchsorted(gaps[:, 0], s, side="right")) - 1)
    total = 0.0
    while i < len(gaps) and gaps[i, 0] < e:
        total += max(0.0, min(e, gaps[i, 1]) - max(s, gaps[i, 0]))
        i += 1
    return total


def reduce_dir(trace_dir: str, program: str) -> dict | None:
    """:func:`reduce` of the one trace file under ``trace_dir``."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file, found {files}")
    return reduce(*load(files[0]), program=program)


def peaks_of(device) -> dict | None:
    """The device's peaks; ``None`` off the TPU, where no share is read."""
    return peaks_for(device.device_kind) if device.platform == "tpu" else None


def search_bytes(queries: np.ndarray, centroids: np.ndarray,
                 list_len: np.ndarray, *, n_batches: int, nprobe: int,
                 chunk: int = 4096) -> dict:
    """HBM bytes and FLOPs a cluster search needs for ``queries``.

    Per query: the real (unpadded) lengths of the ``nprobe`` lists nearest
    to it, each row D elements of the queries' type plus a 4-byte id, and
    the query itself; per batch, the float32 centroids once.  Padding and
    gather copies are not needed, so they are not counted.  ``row_bytes``
    is the list rows' elements alone, what a scan of the lists has to
    read.  FLOPs: 2·D per centroid and per candidate.  The probe is the
    exact float32 top-``nprobe`` (HIGHEST precision).
    """
    import jax
    import jax.numpy as jnp

    n_lists, dim = centroids.shape

    @jax.jit
    def probed_rows(q, c, lens):
        qc = jax.lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                                 precision=jax.lax.Precision.HIGHEST)
        d = jnp.sum(c * c, 1)[None, :] - 2.0 * qc
        _, probe = jax.lax.top_k(-d, nprobe)
        return jnp.sum(lens[probe], axis=1)

    c = jnp.asarray(centroids)
    lens = jnp.asarray(list_len.astype(np.int32))
    rows = 0
    for s in range(0, len(queries), chunk):
        q = np.zeros((chunk, dim), np.float32)
        part = queries[s:s + chunk]
        q[:len(part)] = part
        rows += int(np.asarray(probed_rows(q, c, lens))[:len(part)].sum())
    nq = len(queries)
    item = queries.dtype.itemsize
    row_bytes = rows * dim * item
    hbm = row_bytes + rows * 4 + nq * dim * item \
        + n_batches * n_lists * dim * 4
    flops = 2 * dim * (rows + nq * n_lists)
    return dict(bytes=hbm, flops=flops, rows=rows, queries=nq,
                row_bytes=row_bytes)
