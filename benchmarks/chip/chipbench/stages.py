"""Device time of each stage of the search step, from a traced window and
the search program's compiled HLO.

The program names the stages of its search with ``jax.named_scope``
(:data:`STAGES`); a scope reaches each HLO instruction as the first
element after ``jit(<program>)`` of its ``op_name`` metadata.  A trace
keeps no metadata: its ``XLA Ops`` events carry an instruction's name and
text alone.  So the stage of each instruction is read from the compiled
program (``compile().as_text()``) of every batch shape the loop sent, and
each op event of the search program is charged to its instruction's
stage.  Some instructions carry no ``op_name`` (the sorts XLA makes for a
top-k, layout copies, prefetches).  Such an instruction, or one whose
``op_name`` names no stage, takes:

1. the stage of its operands, in program order, so the stages it took
   already count; where they differ, the latest of :data:`STAGES`, since
   the instruction waits for the latest stage it reads;
2. else the stage of its users, where those that have one agree;
3. else :data:`OTHER`.

A trace names each execution of a compiled program with the program's id.
Each id is matched to the one compiled shape whose instructions hold every
op the id ran, by name and result shape; an id that no shape, or more than
one, explains is charged to :data:`OTHER`.  A name that means one thing in
one shape's program and another in the next is so never charged with the
wrong meaning.

Also here: the host's own events during the result copy of the slowest
batch (:func:`worst_copy`), for the record.  A ``--trace 1`` run of
``run.py`` reads both after its window (``run.read_trace``), and so does
``trace_stages.py``.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import time
from collections import defaultdict

from chipbench import tracing
from chipbench.serve import BATCH, COPY

#: the search step's stages, in the order its data flows through them, as
#: the program's ``jax.named_scope`` calls name them
STAGES = ("probe", "gather", "scan", "select")
#: where an op of the search program that no stage explains is charged
OTHER = "other"
#: host events listed for the slowest batch's result copy
TOP_HOST = 8

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+ = .*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*\bop_name="([^"]*)"')
_OPCODE = re.compile(r"\}? ([a-z][a-z0-9_-]*)\(")


def scope_of(op_name: str) -> str | None:
    """The stage an ``op_name`` names as its first scope: the element after
    the outermost ``jit(...)``, where a primitive's name follows it.
    ``jit(search_step)/gather/gather`` is in ``gather``;
    ``jit(search_step)/gather``, a gather outside any scope, is in none."""
    parts = op_name.split("/")
    if len(parts) < 3 or not parts[0].startswith("jit("):
        return None
    return parts[1] if parts[1] in STAGES else None


def _operands(instr: str) -> list[str]:
    """Names of the operands of one instruction's text."""
    m = _OPCODE.search(instr.partition(" = ")[2])
    if not m:
        return []
    rest = instr.partition(" = ")[2][m.end():]
    depth, end = 1, len(rest)
    for i, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            end = i
            break
    return re.findall(r"%([\w.\-]+)", rest[:end])


def stage_map(hlo_text: str) -> dict[str, str]:
    """``{op key: stage}`` for every instruction of a compiled program's
    text, by the rule of the module docstring; the key is
    :func:`chipbench.tracing.op_name`, which an ``XLA Ops`` event of the
    same instruction gives too."""
    keys, ops, stage = [], {}, {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        instr = m.group(1)
        name = instr.partition(" = ")[0].lstrip("%")
        keys.append((name, tracing.op_name(instr)))
        ops[name] = _operands(instr)
        meta = _OP_NAME.search(instr)
        stage[name] = scope_of(meta.group(1)) if meta else None
    users = defaultdict(list)
    for name, operands in ops.items():
        for o in operands:
            users[o].append(name)
    for name, _ in keys:
        if stage[name] is None:
            up = {stage.get(o) for o in ops[name]} - {None}
            if up:
                stage[name] = max(up, key=STAGES.index)
    for name, _ in reversed(keys):
        if stage[name] is None:
            down = {stage[u] for u in users[name]} - {OTHER}
            stage[name] = down.pop() if len(down) == 1 else OTHER
    return {key: stage[name] for name, key in keys}


def compiled_maps(search, arrays, queries, *, nprobe: int, k: int
                  ) -> list[dict[str, str]]:
    """:func:`stage_map` of the jitted ``search`` compiled for each of
    ``queries`` (device arrays of the batch shapes the loop sent), with the
    resident ``arrays`` (centroids, list vectors, list ids).  The programs
    ran already, so each compile is a load from the persistent cache."""
    return [stage_map(search.lower(*arrays, q, nprobe=nprobe, k=k)
                      .compile().as_text()) for q in queries]


def load(path: str):
    """(device op events, program events, host events) of a trace file, each
    a list of (name, start_ns, end_ns); the host's are every event of every
    thread of ``/host:CPU``."""
    import jax
    planes = {p.name: p for p in
              jax.profiler.ProfileData.from_file(path).planes}
    dev = planes.get("/device:TPU:0")
    ops = tracing._events(dev, "XLA Ops") if dev is not None else []
    modules = tracing._events(dev, "XLA Modules") if dev is not None else []
    host = planes.get("/host:CPU")
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for line in (host.lines if host is not None else ())
              for e in line.events]
    return ops, modules, events


def reduce(ops, modules, maps, program: str) -> dict | None:
    """Seconds of device time per stage (and :data:`OTHER`) over every
    execution of ``program`` in the trace, charged op by op through
    ``maps`` (one :func:`stage_map` per compiled shape).  ``None`` where
    no op was charged to a stage: a program without the scopes."""
    runs = sorted((s, e, n) for n, s, e in modules if program in n)
    if not runs:
        return None
    starts = [s for s, _, _ in runs]
    by_run: dict[str, list] = defaultdict(list)
    for text, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < runs[i][1]:
            by_run[runs[i][2]].append((tracing.op_name(text), e - s))
    seconds = dict.fromkeys(STAGES + (OTHER,), 0.0)
    other_ops: dict[str, float] = defaultdict(float)
    unexplained = []
    for run_id, charged in by_run.items():
        keys = {key for key, _ in charged}
        fits = [m for m in maps if keys <= m.keys()]
        if len(fits) != 1:
            unexplained.append(run_id)
        for key, ns in charged:
            stage = fits[0][key] if len(fits) == 1 else OTHER
            seconds[stage] += ns * 1e-9
            if stage == OTHER:
                other_ops[key] += ns * 1e-9
    if not any(seconds[s] for s in STAGES):
        return None
    top = sorted(other_ops.items(), key=lambda kv: -kv[1])[:tracing.TOP]
    return dict(seconds=seconds, executions=len(runs), other_ops=top,
                unexplained=sorted(unexplained))


def worst_copy(modules, events, program: str) -> dict | None:
    """The batch with the most host time beyond its search program's device
    time, and the host's own events that overlap its result copy, summed
    by name (clipped to the copy), the largest :data:`TOP_HOST`."""
    batches = sorted((s, e) for n, s, e in events if n == BATCH)
    runs = sorted((s, e) for n, s, e in modules if program in n)
    if not batches:
        return None
    starts = [s for s, _ in runs]

    def beyond(b):
        lo = bisect.bisect_left(starts, b[0])
        hi = bisect.bisect_left(starts, b[1])
        return (b[1] - b[0]) - sum(e - s for s, e in runs[lo:hi])
    worst = max(batches, key=beyond)
    copies = [(s, e) for n, s, e in events
              if n == COPY and worst[0] <= s and e <= worst[1]]
    if not copies:
        return None
    cs, ce = copies[0]
    ours = set(tracing.LEAF_SPANS) | {BATCH}
    by_name: dict[str, float] = defaultdict(float)
    for n, s, e in events:
        if n not in ours and s < ce and e > cs:
            by_name[n] += min(e, ce) - max(s, cs)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP_HOST]
    return dict(batch=batches.index(worst), beyond_ms=beyond(worst) * 1e-6,
                copy_ms=(ce - cs) * 1e-6,
                host_events_ms=[[n, v * 1e-6] for n, v in top])


def add_to_trace(rec: dict, trace_dir: str, program: str, search, arrays,
                 queries, *, nprobe: int, k: int) -> None:
    """Put the stage seconds (``rec["trace"]["stages"]``), the slowest
    batch's result copy (``["worst_copy"]``) and the seconds both took
    (``["stages_cost_s"]``) into a traced run's record."""
    t = rec.get("trace")
    if not t:
        return
    t0 = time.perf_counter()
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file, found {files}")
    ops, modules, events = load(files[0])
    maps = compiled_maps(search, arrays, queries, nprobe=nprobe, k=k)
    t["stages"] = reduce(ops, modules, maps, program)
    t["worst_copy"] = worst_copy(modules, events, program)
    t["stages_cost_s"] = time.perf_counter() - t0


def device_ms(stage: str):
    """A reader of a traced run's record, as ``metrics/<name>.py`` files
    hold: device time of ``stage`` per execution of the search program, in
    ms; ``None`` where the run charged no op to a stage."""
    def read(rec):
        t = rec.get("trace") or {}
        s = t.get("stages")
        if not s or not s["executions"]:
            return None
        return 1e3 * s["seconds"][stage] / s["executions"], "ms"
    read.__doc__ = f"Device time of the search's {stage} stage per batch, ms."
    return read
