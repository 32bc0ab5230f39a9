"""The stage map from compiled HLO text and the charge of a trace's ops to
the search's stages, on hand-written text and events and on a trace and
compiled program recorded on a TPU v5e."""
import os

import jax
import pytest

import run as R
import trace_stages
from chipbench import stages, tracing

MS = 1_000_000   # ns

#: a search program as the TPU compiler prints it, cut to the cases the
#: rule has to cover; a fused computation comes first, as in real text
HLO = """\
HloModule jit_search_step, is_scheduled=true

%fused_computation.1 (param_0: f32[4,8], param_1: f32[16,8]) -> f32[4,16] {
  %param_0 = f32[4,8]{1,0} parameter(0)
  %param_1 = f32[16,8]{1,0} parameter(1)
  ROOT %dot.1 = f32[4,16]{1,0} dot(%param_0, %param_1), lhs_contracting_dims={1}, rhs_contracting_dims={1}, metadata={op_name="jit(search_step)/probe/jit(pairwise_sq_l2)/dot_general" stack_frame_id=3}
}

ENTRY %main.9 (centroids.1: f32[16,8], list_ids.1: s32[16,4], queries.1: f32[4,8]) -> (s32[4,2], f32[4,2]) {
  %list_ids.1 = s32[16,4]{1,0:T(8,128)} parameter(1), metadata={op_name="list_ids"}
  %copy-start = (s32[16,4]{1,0:T(8,128)S(1)}, s32[16,4]{1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%list_ids.1), cross_program_prefetch_index=0
  %queries.1 = f32[4,8]{1,0} parameter(2), metadata={op_name="queries"}
  %centroids.1 = f32[16,8]{1,0} parameter(0), metadata={op_name="centroids"}
  %constant.3 = f32[] constant(0)
  %fusion.1 = f32[4,16]{1,0} fusion(%queries.1, %centroids.1), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(search_step)/probe/jit(pairwise_sq_l2)/dot_general" stack_frame_id=3}
  %copy-done = s32[16,4]{1,0:T(8,128)S(1)} copy-done(%copy-start)
  %fusion.2 = s32[4,8]{1,0} fusion(%copy-done, %fusion.1), kind=kCustom, calls=%fused_computation.2, metadata={op_name="jit(search_step)/gather/gather" stack_frame_id=4}
  %fusion.3 = f32[4,8]{1,0} fusion(%fusion.2, %queries.1, %constant.3), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(search_step)/scan/vmap(jit(pairwise_sq_l2))/dot_general" stack_frame_id=5}
  %fusion.4 = f32[4,8]{1,0} fusion(%fusion.3, %constant.3), kind=kLoop, calls=%fused_computation.4, metadata={op_name="jit(search_step)/select/neg" stack_frame_id=6}
  %iota.1 = s32[4,8]{1,0} iota(), iota_dimension=1
  %sort.1 = (f32[4,8]{1,0}, s32[4,8]{1,0}) sort(%fusion.4, %iota.1), dimensions={1}, to_apply=%compare-greater-than.1
  %get-tuple-element.1 = s32[4,8]{1,0} get-tuple-element(%sort.1), index=1
  %fusion.5 = s32[4,2]{1,0} fusion(%get-tuple-element.1, %fusion.2), kind=kCustom, calls=%fused_computation.5, metadata={op_name="jit(search_step)/select/dedup/eq" stack_frame_id=7}
  %get-tuple-element.2 = f32[4,8]{1,0} get-tuple-element(%sort.1), index=0
  %slice.1 = f32[4,2]{1,0} slice(%get-tuple-element.2), slice={[0:4], [0:2]}
  %after-all.1 = token[] after-all()
  ROOT %tuple.1 = (s32[4,2]{1,0}, f32[4,2]{1,0}) tuple(%fusion.5, %slice.1)
}
"""

#: the same program without scopes, as the program was before them: the
#: list gather's op_name is the primitive alone
UNSCOPED = HLO.replace("/probe/", "/").replace("/scan/", "/").replace(
    "/select/dedup/", "/").replace("/select/", "/").replace(
    'op_name="jit(search_step)/gather/gather"',
    'op_name="jit(search_step)/gather"')


def _line(name: str, text: str = HLO) -> str:
    """The instruction ``name`` of ``text`` as a trace's op event names it:
    its text without metadata."""
    for line in text.splitlines():
        line = line.strip().removeprefix("ROOT ")
        if line.startswith(f"%{name} = "):
            return line.split(", metadata=")[0]
    raise KeyError(name)


def _stage(name: str, text: str = HLO) -> str:
    return stages.stage_map(text)[tracing.op_name(_line(name, text))]


@pytest.mark.parametrize("op_name,stage", [
    ("jit(search_step)/probe/jit(pairwise_sq_l2)/dot_general", "probe"),
    ("jit(search_step)/select/dedup/eq", "select"),
    ("jit(local_search)/scan/lt", "scan"),
    ("jit(search_step)/gather", None),       # the gather primitive, no scope
    ("jit(search_step)/jit(topk_smallest)/top_k", None),
    ("queries", None),
])
def test_scope_of_reads_the_first_scope(op_name, stage):
    assert stages.scope_of(op_name) == stage


@pytest.mark.parametrize("name,stage", [
    ("fusion.1", "probe"),               # its own op_name
    ("fusion.2", "gather"),
    ("fusion.3", "scan"),
    ("fusion.5", "select"),              # select/dedup is in select
    ("sort.1", "select"),                # no op_name, downstream of select
    ("get-tuple-element.2", "select"),
    ("slice.1", "select"),
    ("copy-done", "gather"),             # prefetch: its only user gathers
    ("copy-start", "gather"),
    ("iota.1", "select"),                # no operand: its one user's stage
    ("constant.3", "other"),             # users in scan and select disagree
    ("after-all.1", "other"),            # no operand, no user
])
def test_stage_map_rule(name, stage):
    assert _stage(name) == stage


def test_stage_map_joins_two_stages_at_the_later():
    text = HLO.replace(
        "%fusion.4 = f32[4,8]{1,0} fusion(%fusion.3, %constant.3)",
        "%fusion.4 = f32[4,8]{1,0} fusion(%fusion.3, %fusion.1)").replace(
        ', metadata={op_name="jit(search_step)/select/neg" stack_frame_id=6}',
        "")
    assert _stage("fusion.4", text) == "scan"


def test_stage_map_without_scopes_is_all_other():
    assert set(stages.stage_map(UNSCOPED).values()) == {"other"}


def _run(names, start, text=HLO, step=MS):
    """Op events of one execution of the program in ``text``, back to back
    from ``start``: (events, end)."""
    out, t = [], start
    for n in names:
        out.append((_line(n, text), t, t + step))
        t += step
    return out, t


PROG_OPS = ["copy-start", "fusion.1", "copy-done", "fusion.2", "fusion.3",
            "fusion.4", "sort.1", "fusion.5", "slice.1"]


def test_reduce_charges_ops_inside_the_program_only():
    maps = [stages.stage_map(HLO)]
    ops1, end1 = _run(PROG_OPS, 0)
    ops2, end2 = _run(PROG_OPS, 20 * MS)
    stray = [("%fusion.1 = f32[4,16]{1,0} fusion()", 15 * MS, 16 * MS),
             ("%other.1 = f32[2]{0} add()", 12 * MS, 13 * MS)]
    modules = [("jit_search_step(7)", 0, end1),
               ("jit_search_step(7)", 20 * MS, end2),
               ("jit_other_step(8)", 12 * MS, 17 * MS)]
    got = stages.reduce(ops1 + stray + ops2, modules, maps, "search_step")
    s = got["seconds"]
    assert got["executions"] == 2 and got["unexplained"] == []
    # two executions of 9 one-ms ops; the strays of another program are out
    assert sum(s.values()) == pytest.approx(2 * 9e-3)
    assert s == pytest.approx(dict(probe=2e-3, gather=6e-3, scan=2e-3,
                                   select=8e-3, other=0.0))
    rec = {"trace": {"stages": got}}
    assert stages.device_ms("gather")(rec) == (pytest.approx(3.0), "ms")
    assert stages.device_ms("select")(rec) == (pytest.approx(4.0), "ms")


def test_reduce_tells_two_shapes_apart_by_program():
    """``fusion.3`` is a scan at batch 4 and a probe at batch 2: each
    execution is charged by the shape that explains all of its ops."""
    small = HLO.replace("f32[4,", "f32[2,").replace("s32[4,", "s32[2,")
    small = small.replace("jit(search_step)/scan/", "jit(search_step)/probe/")
    maps = [stages.stage_map(HLO), stages.stage_map(small)]
    big, end = _run(["fusion.3", "fusion.4"], 0)
    little, end2 = _run(["fusion.3", "fusion.4"], end, small)
    modules = [("jit_search_step(1)", 0, end),
               ("jit_search_step(2)", end, end2)]
    got = stages.reduce(big + little, modules, maps, "search_step")
    assert got["seconds"] == pytest.approx(dict(
        probe=1e-3, gather=0.0, scan=1e-3, select=2e-3, other=0.0))


def test_reduce_charges_an_unexplained_program_to_other():
    maps = [stages.stage_map(HLO)]
    ops, end = _run(["fusion.1", "fusion.3"], 0)
    ops.append(("%fusion.77 = f32[9]{0} fusion()", end, end + MS))
    modules = [("jit_search_step(1)", 0, end + MS)]
    got = stages.reduce(ops, modules, maps, "search_step")
    assert got is None           # nothing was charged to a stage
    ops2, end2 = _run(["fusion.1", "fusion.3"], 10 * MS)
    got = stages.reduce(ops + ops2, modules + [
        ("jit_search_step(2)", 10 * MS, end2)], maps, "search_step")
    assert got["unexplained"] == ["jit_search_step(1)"]
    assert got["seconds"]["other"] == pytest.approx(3e-3)
    assert got["seconds"]["probe"] == pytest.approx(1e-3)


def test_a_program_without_scopes_reads_none():
    maps = [stages.stage_map(UNSCOPED)]
    ops, end = _run(PROG_OPS, 0, UNSCOPED)
    got = stages.reduce(ops, [("jit_search_step(3)", 0, end)], maps,
                        "search_step")
    assert got is None
    for read in map(stages.device_ms, stages.STAGES):
        assert read({"trace": {"stages": got}}) is None
        assert read({"trace": None}) is None
    assert stages.reduce(ops, [], maps, "search_step") is None
    assert stages.reduce(ops, [("jit_search_step(3)", 0, end)], [],
                         "search_step") is None


def test_worst_copy_lists_the_host_during_the_slowest_batch():
    events = [
        ("batch", 0, 10 * MS), ("result_copy", 4 * MS, 10 * MS),
        ("batch", 12 * MS, 30 * MS), ("result_copy", 16 * MS, 30 * MS),
        ("TransferFromDevice", 17 * MS, 29 * MS),
        ("Delinearize", 20 * MS, 22 * MS), ("Delinearize", 24 * MS, 25 * MS),
        ("D2H Dispatch", 2 * MS, 3 * MS),       # in the first batch
        ("Spans the copy's end", 28 * MS, 40 * MS),
    ]
    modules = [("jit_search_step(1)", 1 * MS, 9 * MS),
               ("jit_search_step(1)", 13 * MS, 21 * MS)]
    got = stages.worst_copy(modules, events, "search_step")
    assert got["batch"] == 1
    assert got["beyond_ms"] == pytest.approx(10.0)
    assert got["copy_ms"] == pytest.approx(14.0)
    assert got["host_events_ms"] == [["TransferFromDevice", pytest.approx(12.0)],
                                     ["Delinearize", pytest.approx(3.0)],
                                     ["Spans the copy's end",
                                      pytest.approx(2.0)]]
    assert stages.worst_copy(modules, [], "search_step") is None


#: a few DEEP bulk batches of the scoped search (the cell's layout, B 256)
#: traced on a TPU v5e, and the program the TPU compiler made for them
#: (the file paths of its debug tables made relative to the repository)
SCOPED = os.path.join(os.path.dirname(__file__), "data",
                      "deep96-f32-scoped_b256_3batches")


def test_recorded_tpu_trace_leaves_no_op_unmatched():
    with open(SCOPED + ".hlo.txt") as f:
        smap = stages.stage_map(f.read())
    ops, modules, events = stages.load(SCOPED + ".xplane.pb")
    runs = [(s, e) for n, s, e in modules if "search_step" in n]
    inside = [(n, e - s) for n, s, e in ops
              if any(rs <= s < re for rs, re in runs)]
    assert len(runs) == 3 and inside
    assert all(tracing.op_name(n) in smap for n, _ in inside)
    got = stages.reduce(ops, modules, [smap], "search_step")
    assert got["unexplained"] == [] and got["executions"] == len(runs)
    total = sum(d for _, d in inside) * 1e-9
    assert sum(got["seconds"].values()) == pytest.approx(total)
    assert got["seconds"]["other"] < 0.02 * total
    assert all(got["seconds"][s] > 0 for s in stages.STAGES)
    program_s = sum(e - s for s, e in runs) * 1e-9
    assert sum(got["seconds"][s] for s in stages.STAGES) >= 0.95 * program_s
    # the largest ops land where the scopes put them
    by_op = {tracing.op_name(n).split()[0]: smap[tracing.op_name(n)]
             for n, _ in inside}
    assert by_op["sort.6"] == by_op["sort.5"] == "select"
    assert by_op["fusion"] == "gather"
    assert by_op["multiply_reduce_fusion.1"] == "scan"
    worst = stages.worst_copy(modules, events, "search_step")
    assert worst["copy_ms"] > 0 and worst["host_events_ms"]


def test_trace_stages_reads_no_stage_off_the_tpu():
    """A tiny window of the online cell on the CPU: the host's spans are
    read, but no device plane exists, so no stage is charged."""
    bench = R.load_json(os.path.join(R.ROOT, "BENCHMARK.json"))
    _, config, traffic, _, _ = R.cell_spec(bench, "deep96-f32.online")
    config = dict(config, n=6000, pool=2048, build_chunk=2000)
    traffic = dict(traffic, rate_qps=150.0, max_batch=4)
    out = trace_stages.trace_cell(config, traffic, seed=2 ** 33 + 5,
                                  seconds=0.5, device=jax.devices()[0])
    assert out["batches"] > 0
    assert out["stages"] is None and out["search_device_ms"] is None
    assert all(out[f"{s}_device_ms"] is None for s in stages.STAGES)
    assert out["worst_copy"]["copy_ms"] > 0
    assert out["stages_cost_s"] > 0


def test_traced_run_leaves_out_what_it_cannot_read_off_the_tpu():
    """A traced run of the bulk cell on the CPU: the stage metrics and the
    scan roofline find no device plane and are left out, not read as 0."""
    bench = R.load_json(os.path.join(R.ROOT, "BENCHMARK.json"))
    _, config, traffic, _, layer = R.cell_spec(bench, "deep96-f32.bulk")
    assert {f"{s}_device_ms.bulk" for s in stages.STAGES} \
        | {"scan_roofline.bulk"} <= set(layer)
    config = dict(config, n=6000, pool=2048, build_chunk=2000, batch=16,
                  recall_sample=10)
    res = R.run_cell(config, traffic, seed=2 ** 33 + 7, seconds=0.3,
                     trace=True, device=jax.devices()[0], metric_names=layer,
                     t_start=0.0, search_impl=R.program_search(),
                     log=lambda *a: None)
    assert res["correct"], res["checks"]
    assert res["metrics"]
    assert not any(m.endswith("_device_ms.bulk") or m == "scan_roofline.bulk"
                   for m in res["metrics"])
