"""The readers of the program's own counters (ISSUE 25) and
``perfbench/scopes.py``: on hand-made contexts, on a CPU rehearsal, on a
hand-encoded XSpace and on a trace cut from the chip run of PR 25. Nothing
here is a device number but what the recorded trace holds."""

import io
import json
import os
import struct
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import run, scopes, trace_reduce  # noqa: E402
from perfbench.readers import (programs_per_query, rebuild_ms,  # noqa: E402
                               retraces_per_query, sync_wait_ms)

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = {"programs_per_query": programs_per_query,
           "retraces_per_query": retraces_per_query,
           "rebuild_ms": rebuild_ms, "sync_wait_ms": sync_wait_ms}
TRACE = {"window_s": 1.0, "busy_s": 0.5, "devices": 1,
         "device_ops": [], "idle_gaps": []}


def _entry(dispatches=0, traces=0, trace_s=0.0, lower_s=0.0, compiles=0,
           compile_s=0.0, loads=0, load_s=0.0):
    return {"dispatches": dispatches, "traces": traces, "traceS": trace_s,
            "lowerS": lower_s, "compiles": compiles, "compileS": compile_s,
            "cacheLoads": loads, "loadS": load_s}


def _query(programs, wait_s):
    return {"programs": programs,
            "sync": {"hostSyncs": 2, "syncSites": {}, "syncWaitS": wait_s}}


def _ctx(query_metrics, trace=TRACE):
    return {"queries": len(query_metrics), "query_metrics": query_metrics,
            "trace": trace}


# -- the four readers ---------------------------------------------------------

def test_counter_readers_on_a_known_context():
    first = _query({
        "agg/update/sort": _entry(dispatches=1),
        "agg/final": _entry(dispatches=1, traces=1, trace_s=0.002,
                            lower_s=0.003, compiles=1, compile_s=0.25),
        "<eager>:scan": _entry(traces=1, trace_s=0.001, lower_s=0.002,
                               loads=1, load_s=0.004)}, 13.5)
    second = _query({"agg/update/sort": _entry(dispatches=1),
                     "agg/final": _entry(dispatches=1)}, 12.5)
    ctx = _ctx([first, second])
    assert programs_per_query.read(ctx) == 2.0
    assert retraces_per_query.read(ctx) == 1.0
    assert rebuild_ms.read(ctx) == pytest.approx(
        (0.002 + 0.003 + 0.25 + 0.001 + 0.002 + 0.004) * 1e3 / 2)
    assert sync_wait_ms.read(ctx) == pytest.approx(13000.0)


@pytest.mark.parametrize("name", sorted(READERS))
def test_counter_reader_finds_nothing_where_nothing_is(name):
    """No traced query; a program from before the counters existed (the
    parent commit: no ``programs`` map, no ``syncWaitS``); a run without a
    device trace: ``None`` each time, never 0 and never a raise."""
    read = READERS[name].read
    assert read(_ctx([])) is None
    parent = {"sync": {"hostSyncs": 2, "syncSites": {}}, "planTimeS": 0.001}
    assert read(_ctx([parent])) is None
    assert read(_ctx([_query({"a": _entry(dispatches=1)}, 1.0)],
                     trace=None)) is None


def test_benchmark_lists_the_four_under_their_layers():
    bench = run.load_benchmark()
    added = {m["name"]: m for m in bench["per_layer"] if m["name"] in READERS}
    assert set(added) == set(READERS)
    for m in added.values():
        assert m["workloads"] == ["tpch_sf1.q1"] and m["moves"] == "query_s"
        assert m["better"] == "lower"
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in READERS}
    assert {m["layer"] for m in added.values()} <= layers
    assert [m["name"] for m in bench["per_layer"]][-4:] == [
        "programs_per_query", "retraces_per_query", "rebuild_ms",
        "sync_wait_ms"]


def test_traced_rehearsal_prints_all_four_beside_a_trace(monkeypatch):
    """A CPU rehearsal of the traced run: with a (stand-in) device trace to
    read beside, the result line carries the four new metrics with the
    program's own counts; the accepted five stay."""
    monkeypatch.setattr(trace_reduce, "reduce_trace", lambda planes: TRACE)
    load_json = run.load_json
    monkeypatch.setattr(run, "load_json", lambda *parts: (
        {"cpu": load_json("peaks.json")["TPU v5 lite"]}
        if parts == ("peaks.json",) else load_json(*parts)))
    out = io.StringIO()
    result = run.run_cell("tpch_sf1.q1", 25, 0.0, 1, rows_scale=0.002,
                          out=out, err=io.StringIO())
    assert json.loads(out.getvalue().splitlines()[-1]) == result
    assert result["correct"] is True
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert {"plan_ms", "scan_ms", "host_syncs", "compiles_per_query",
            "compile_ms"} | set(READERS) <= set(got)
    units = {k: v["unit"] for k, v in result["metrics"].items()}
    assert [units[k] for k in sorted(READERS)] == ["count", "ms", "count",
                                                   "ms"]
    # the scan-cache served group-by: its update and its final program
    assert got["programs_per_query"] == 2.0
    assert got["retraces_per_query"] == int(got["retraces_per_query"]) >= 1
    assert got["rebuild_ms"] > 0 and got["sync_wait_ms"] > 0
    assert got["host_syncs"] == 2


# -- scopes.py: the XSpace reader ---------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    """One protobuf field: an int as a varint, bytes or str delimited, a
    float as a fixed64 (which the reader has to skip)."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, float):
        return _varint(number << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _entry_of_map(key, message):
    return _field(1, key) + _field(2, message)


def _xspace():
    """One device plane (two ops whose ``tf_op`` is kept as a string and
    as a reference, one module) and the host plane with the window."""
    stat_names = {1: "tf_op", 2: "flops",
                  3: "jit(agg_final)/TpuHashAggregateExec/gather/gather:"}
    stat_meta = b"".join(
        _field(5, _entry_of_map(k, _field(1, k) + _field(2, v)))
        for k, v in stat_names.items())
    op_a = _field(1, 10) + _field(2, "%fusion.1 = f32[8] fusion(...)") + \
        _field(5, _field(1, 2) + _field(2, 1024.0)) + \
        _field(5, _field(1, 1) + _field(
            5, "jit(agg_update_sort)/TpuHashAggregateExec/lexsort/while/"
               "body/sort:"))
    op_b = _field(1, 11) + _field(2, "%gather.2 = f32[8] gather(...)") + \
        _field(5, _field(1, 1) + _field(7, 3))
    module = _field(1, 12) + _field(2, "jit_agg_update_sort(123)")
    event_meta = b"".join(_field(4, _entry_of_map(k, m)) for k, m in
                          ((10, op_a), (11, op_b), (12, module)))
    ops = _field(2, "XLA Ops") + _field(3, 1000) + \
        _field(4, _field(1, 10) + _field(2, 2_000_000) + _field(3, 500_000)) \
        + _field(4, _field(1, 11) + _field(2, 3_000_000) + _field(3, 250_000))
    modules = _field(2, "XLA Modules") + _field(3, 1000) + \
        _field(4, _field(1, 12) + _field(2, 1_000_000) + _field(3, 3_000_000))
    device = _field(2, "/device:TPU:0") + _field(3, ops) + \
        _field(3, modules) + event_meta + stat_meta
    window = _field(1, 1) + _field(2, scopes.WINDOW)
    host = _field(2, scopes.HOST_PLANE) + _field(4, _entry_of_map(1, window)) \
        + _field(3, _field(2, "main") + _field(3, 0) + _field(
            4, _field(1, 1) + _field(2, 0) + _field(3, 5_000_000)))
    return _field(1, device) + _field(1, host)


def test_load_reads_names_times_and_op_names_off_the_wire(tmp_path, capsys):
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(_xspace())
    planes = scopes.load(str(path))
    assert [p["name"] for p in planes] == ["/device:TPU:0", scopes.HOST_PLANE]
    ops, modules = planes[0]["lines"]
    assert ops["events"] == [
        ["%fusion.1 = f32[8] fusion(...)", 3000.0, 500.0,
         "jit(agg_update_sort)/TpuHashAggregateExec/lexsort/while/body/"
         "sort:"],
        ["%gather.2 = f32[8] gather(...)", 4000.0, 250.0,
         "jit(agg_final)/TpuHashAggregateExec/gather/gather:"]]
    assert modules["events"] == [["jit_agg_update_sort(123)", 2000.0, 3000.0]]
    assert planes[1]["lines"][0]["events"] == [[scopes.WINDOW, 0.0, 5000.0]]
    assert scopes.modules(planes) == {"jit_agg_update_sort": 3e-6}
    assert scopes.by_scope(planes) == pytest.approx({
        "jit_agg_update_sort/TpuHashAggregateExec/lexsort": 0.5e-6,
        "jit_agg_update_sort/TpuHashAggregateExec/gather": 0.25e-6})
    assert scopes.main([str(path)]) == 0
    table = capsys.readouterr().out
    assert "jit_agg_update_sort/TpuHashAggregateExec/lexsort" in table
    assert "100.00% under a named scope" in table
    assert scopes.main([]) == 2


@pytest.mark.parametrize("op_name, scope", [
    ("jit(agg_update_sort)/TpuHashAggregateExec/lexsort/while/body/sort:",
     "TpuHashAggregateExec/lexsort"),
    ("jit(agg)/TpuHashAggregateExec/segment_sum_scatter/jit(_where)/"
     "select_n:", "TpuHashAggregateExec/segment_sum_scatter"),
    ("jit(stage)/TpuFilterExec/filter/cond/branch_1_fun/gt",
     "TpuFilterExec/filter"),
    ("jit(agg)/TpuWholeStageExec/compact/gather/gather",
     "TpuWholeStageExec/compact"),
    ("jit(agg)/TpuHashAggregateExec/jit(_where)/select_n:", None),
    ("jit(gather)/gather:", None),
    ("", None),
])
def test_scope_of_an_op_name(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_an_enclosing_op_keeps_only_what_its_body_leaves():
    """A ``while`` is an event round its body's: self times add up to the
    union, and the loop's scope gets only the instants no inner op ran."""
    host = {"name": scopes.HOST_PLANE, "lines": [
        {"name": "main", "events": [[scopes.WINDOW, 0.0, 100.0]]}]}
    device = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_p(77)", 5.0, 150.0]]},
        {"name": "XLA Ops", "events": [
            ["%while", 10.0, 60.0, ""],
            ["%sort.1", 12.0, 20.0, "jit(p)/Op/lexsort/while/body/sort"],
            ["%sort.2", 40.0, 25.0, "jit(p)/Op/lexsort/while/body/sort"],
            ["%add", 80.0, 40.0, "jit(p)/Op/project/add"]]}]}
    got = scopes.by_scope([host, device])
    assert got == pytest.approx({"jit_p/Op/lexsort": 45e-9,
                                 "jit_p/(unscoped)": 15e-9,
                                 "jit_p/Op/project": 20e-9})
    assert scopes.scoped_share(got) == pytest.approx(65 / 80)
    assert scopes.by_scope([device]) is None        # no window


def test_scopes_on_the_trace_cut_from_the_chip():
    """``tests/perfbench/data/q1_scopes_chip_trace.json``: the seconds per
    scope add up to the busy time ``trace_reduce`` finds in the same
    planes, nearly all of them under a named ``<module>/<operator>/
    <stage>``, and no program is called ``jit_fn``. What the recorded run
    holds (a TPU v5 lite, PR 25), not a measurement of this machine."""
    with open(os.path.join(HERE, "data", "q1_scopes_chip_trace.json")) as f:
        planes = json.load(f)["planes"]
    totals = scopes.by_scope(planes)
    plain = [{"name": p["name"], "lines": [
        {"name": line["name"], "events": [e[:3] for e in line["events"]]}
        for line in p["lines"]]} for p in planes]
    reduced = trace_reduce.reduce_trace(plain)
    assert sum(totals.values()) == pytest.approx(reduced["busy_s"], rel=1e-9)
    assert scopes.scoped_share(totals) > 0.995
    group_by = "jit_agg_update_complete_pre_stage_sort/TpuHashAggregateExec/"
    assert totals[group_by + "segment_sum_scatter"] == pytest.approx(
        9.3909, abs=1e-3)
    assert totals[group_by + "gather"] == pytest.approx(3.6722, abs=1e-3)
    assert totals[group_by + "lexsort"] == pytest.approx(0.5737, abs=1e-3)
    programs = scopes.modules(planes)
    assert programs and not any(n.startswith("jit_fn") for n in programs)
    assert max(programs, key=programs.get) == \
        "jit_agg_update_complete_pre_stage_sort"
    # the program's spans lie in the same trace, by the query's id or not
    host = {e[0] for p in planes if p["name"] == scopes.HOST_PLANE
            for line in p["lines"] for e in line["events"]}
    assert {"query", "plan", "fetch_to_host", "host_sync",
            "program:agg/update/complete/pre_stage/sort"} <= host
