"""Per-operator metrics, EXPLAIN ANALYZE, query listeners, and the
Chrome-trace timeline (ISSUE 6): the observability layer the reference
surfaces through SQLMetrics in the Spark UI (GpuExec.scala:27-56) plus
NVTX ranges (NvtxWithMetrics.scala:27), reproduced as exec-attributed
metric bags + a text EXPLAIN ANALYZE + trace.json export."""

import json
import threading

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.exec import metrics as em
from spark_rapids_tpu.exec.tracing import (SpanRecorder, SyncCounter,
                                           trace_span)


def _session(**conf):
    return TpuSession.builder.config(
        {"spark.rapids.tpu.sql.explain": "NONE", **conf}).getOrCreate()


def _q3_tables(s, n=8192):
    rng = np.random.default_rng(7)
    line = pd.DataFrame({
        "l_order": rng.integers(0, 1000, n).astype("int64"),
        "l_price": rng.normal(100.0, 10.0, n)})
    orders = pd.DataFrame({
        "o_key": np.arange(1000, dtype="int64"),
        "o_cust": rng.integers(0, 100, 1000).astype("int64"),
        "o_date": rng.integers(0, 1000, 1000).astype("int64")})
    cust = pd.DataFrame({
        "c_key": np.arange(100, dtype="int64"),
        "c_seg": rng.integers(0, 3, 100).astype("int64")})
    s.createDataFrame(line).createOrReplaceTempView("o_lineitem")
    s.createDataFrame(orders).createOrReplaceTempView("o_orders")
    s.createDataFrame(cust).createOrReplaceTempView("o_customer")
    exp = (line.merge(orders, left_on="l_order", right_on="o_key")
               .merge(cust, left_on="o_cust", right_on="c_key"))
    return exp[(exp.o_date < 700) & (exp.c_seg == 1)]


Q3_SQL = ("SELECT l_price, o_date, c_seg FROM o_lineitem "
          "JOIN o_orders ON l_order = o_key "
          "JOIN o_customer ON o_cust = c_key "
          "WHERE o_date < 700 AND c_seg = 1")


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE on the q3-shaped 3-way join
# ---------------------------------------------------------------------------

def test_q3_explain_analyze_rows_consistent_and_join_syncs_o1():
    s = _session(**{"spark.rapids.tpu.sql.reader.batchSizeRows": 1024})
    exp = _q3_tables(s)
    rows = s.sql(Q3_SQL).collect()
    assert len(rows) == len(exp)

    # the metrics tree's ROOT numOutputRows must equal the collected rows
    ops = s.last_query_metrics()["operators"]
    root = ops[0]
    assert root["metrics"].get("numOutputRows") == len(rows), root

    # every join node's attributed hostSyncs stays O(1) per stage: the
    # pipelined window batches its sizing readbacks (one per half-window),
    # so per-batch syncs would show ~8+ here
    joins = [o for o in ops if "JoinExec" in o["operator"]]
    assert joins, ops
    for j in joins:
        assert j["metrics"].get("hostSyncs", 0) <= 4, j

    # the rendered EXPLAIN ANALYZE names the join nodes with their
    # per-node metrics inline and carries the query-level summary
    text = s.explain_analyze()
    assert "== Executed Plan (analyzed) ==" in text
    assert "TpuSortMergeJoinExec" in text
    assert f"numOutputRows: {len(rows)}" in text
    assert "hostSyncs" in text and "executeTimeS=" in text


def test_df_explain_analyze_executes_and_prints(capsys):
    s = _session()
    df = s.createDataFrame(pd.DataFrame(
        {"k": [1, 2, 1, 3] * 16, "v": [1., 2., 3., 4.] * 16}))
    agg = df.groupBy("k").agg(F.sum("v").alias("sv"))
    agg.explain("analyze")          # executes the frame (Spark semantics)
    text = capsys.readouterr().out
    assert "== Executed Plan (analyzed) ==" in text
    assert "TpuHashAggregateExec" in text
    assert "numOutputRows: 3" in text


def test_contract_violation_attaches_to_analyzed_tree(capsys):
    """A seeded schema corruption must show on ITS node in EXPLAIN
    ANALYZE, not only in the flat warn log."""
    from spark_rapids_tpu.columnar import dtypes as dt
    s = _session(**{"spark.rapids.tpu.sql.analysis.validatePlan": "warn"})
    df = s.createDataFrame(pd.DataFrame({"a": [1.0, 2.0, 3.0]}))
    df = df.filter(F.col("a") > 0)
    df._execute()
    plan = s.last_plan()
    # corrupt the filter's passthrough schema after conversion, then
    # re-validate the way Overrides does and render
    from spark_rapids_tpu.analysis import contracts
    from spark_rapids_tpu.plan.physical import TpuFilterExec

    def find(node):
        if isinstance(node, TpuFilterExec):
            return node
        for c in node.children:
            got = find(c)
            if got is not None:
                return got
        return None

    filt = find(plan)
    assert filt is not None, plan
    filt._schema = dt.Schema([dt.Field(f.name, dt.INT64, f.nullable)
                              for f in filt._schema])
    violations = contracts.validate_plan(plan, None)
    assert violations
    s._last_overrides.last_violations = violations
    text = s.explain_analyze()
    assert "! contract:" in text


# ---------------------------------------------------------------------------
# Query-execution listener API
# ---------------------------------------------------------------------------

def test_listener_receives_executed_plan_and_reports():
    s = _session()
    captured = []
    s.register_query_listener(captured.append)
    try:
        df = s.createDataFrame(pd.DataFrame(
            {"k": [1, 2, 1] * 8, "v": [1., 2., 3.] * 8}))
        df.groupBy("k").agg(F.sum("v").alias("sv")).collect()
    finally:
        s.unregister_query_listener(captured.append)
    assert len(captured) == 1
    qe = captured[0]
    assert qe.plan is s.last_plan()
    assert qe.metrics_tree and qe.metrics_tree[0][0] == 0
    assert "hostSyncs" in qe.sync
    assert "wallS" in qe.spans
    assert isinstance(qe.recompiles, dict) and isinstance(qe.locks, dict)
    assert "TpuHashAggregateExec" in qe.explain_analyze()
    # unregistered: no further captures
    s.createDataFrame(pd.DataFrame({"x": [1]})).collect()
    assert len(captured) == 1


def test_listener_errors_never_fail_the_query():
    s = _session()

    def bad(_qe):
        raise RuntimeError("listener bug")

    s.register_query_listener(bad)
    try:
        out = s.createDataFrame(pd.DataFrame({"x": [1, 2]})).collect()
        assert [r[0] for r in out] == [1, 2]
    finally:
        s.unregister_query_listener(bad)


# ---------------------------------------------------------------------------
# Chrome-trace timeline exporter
# ---------------------------------------------------------------------------

def test_timeline_round_trips_valid_chrome_trace(tmp_path):
    s = _session(**{"spark.rapids.tpu.sql.tracing.timeline": "true"})
    try:
        df = s.createDataFrame(pd.DataFrame(
            {"k": [1, 2, 1, 3] * 64, "v": [1., 2., 3., 4.] * 64}))
        df.groupBy("k").agg(F.sum("v").alias("sv")).collect()
        rec = s._last_span_recorder
        path = rec.dump_chrome_trace(str(tmp_path / "trace.json"))
        tr = json.load(open(path))           # round-trips as valid JSON
        evs = tr["traceEvents"]
        xs = [e for e in evs if e["ph"] == "X"]
        assert xs, "timeline recorded no spans"
        named_tids = {e["tid"] for e in evs
                      if e["ph"] == "M" and e["name"] == "thread_name"}
        for e in xs:
            # event pairing: every complete event carries begin + duration
            assert e["ts"] >= 0.0 and e["dur"] >= 0.0, e
            assert e["name"] and e["tid"] in named_tids, e
        # the span names match the flat report's names
        rep_names = {n for n in rec.report()
                     if n not in ("wallS", "concurrency")}
        assert {e["name"] for e in xs} <= rep_names | {"process_name"}
    finally:
        from spark_rapids_tpu.exec import tracing
        tracing.reset_cache()


def test_timeline_off_by_default_records_no_events():
    s = _session()
    s.createDataFrame(pd.DataFrame({"x": [1, 2, 3]})).collect()
    rec = s._last_span_recorder
    assert rec.chrome_trace()["traceEvents"] == [
        {"ph": "M", "name": "process_name", "pid": 0, "tid": 0,
         "args": {"name": "spark-rapids-tpu query"}}]


def test_timeline_names_task_pool_threads(tmp_path):
    """Multi-partition drains run on the named task pool; the timeline's
    thread metadata must carry those names (PR 4 named them)."""
    rec = SpanRecorder(timeline=True)
    from spark_rapids_tpu.exec.tasks import run_partition_tasks
    with rec:
        def body(pid, part):
            with trace_span(f"part_{pid}"):
                return pid
        run_partition_tasks([1, 2, 3, 4], body, max_workers=4)
    names = {e["args"]["name"]
             for e in rec.chrome_trace()["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert any(n.startswith("tpu-task") for n in names), names


# ---------------------------------------------------------------------------
# SpanRecorder wallS + concurrency
# ---------------------------------------------------------------------------

def test_span_report_wall_and_concurrency():
    import time
    rec = SpanRecorder()
    with rec:
        with trace_span("outer"):
            time.sleep(0.02)
    rep = rec.report()
    assert rep["wallS"] >= 0.02
    assert rep["outer"]["selfS"] >= 0.02
    # single-threaded, no suspension: self-time ~ wall
    assert 0.5 <= rep["concurrency"] <= 1.5, rep


def test_span_report_concurrency_past_one_with_threads():
    import time
    rec = SpanRecorder()

    def worker():
        with trace_span("w"):
            time.sleep(0.05)

    with rec:
        ts = [threading.Thread(target=worker) for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    rep = rec.report()
    # 4 threads x 0.05s inside a ~0.05s wall: the ratio names the
    # parallelism instead of looking like double counting
    assert rep["concurrency"] > 1.5, rep


# ---------------------------------------------------------------------------
# Exec attribution (innermost open exec)
# ---------------------------------------------------------------------------

def test_attribute_routes_to_innermost_open_exec():
    inner = em.TpuMetrics()
    outer = em.TpuMetrics()
    with trace_span("o", outer):
        em.attribute("hostSyncs")
        with trace_span("i", inner):
            em.attribute("hostSyncs")
            em.attribute("spillBytes", 128)
    assert dict(inner) == {"hostSyncs": 1, "spillBytes": 128}
    assert dict(outer) == {"hostSyncs": 1}
    assert em.current() is None            # scopes unwound


def test_attribute_outside_any_exec_is_noop():
    em.attribute("hostSyncs")              # must not raise
    assert em.current() is None


def test_metrics_disabled_conf_stops_collection():
    s = _session(**{"spark.rapids.tpu.sql.metrics.enabled": "false"})
    try:
        s.createDataFrame(pd.DataFrame({"x": [1, 2, 3]})).collect()
        ops = s.last_query_metrics()["operators"]
        assert all(not o["metrics"] for o in ops), ops
    finally:
        em.reset_cache()
        _session()                          # restore default-conf session


# ---------------------------------------------------------------------------
# SyncCounter default stack under concurrent enter/exit
# ---------------------------------------------------------------------------

def test_sync_counter_stack_survives_concurrent_enter_exit():
    errs = []

    def hammer():
        try:
            for _ in range(200):
                with SyncCounter():
                    pass
        except Exception as e:              # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=hammer) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    assert SyncCounter._default_stack == []


# ---------------------------------------------------------------------------
# Bench preflight: no chip, no measurement
# ---------------------------------------------------------------------------

def test_preflight_without_a_chip_fails_with_the_probe_error():
    """A measurement entry point never falls back to the CPU: on this
    CPU-only backend the probe raises, naming the platform it found."""
    from benchmarks import preflight
    with pytest.raises(RuntimeError, match="'cpu'.*not a TPU"):
        preflight.require_chip()
    assert not hasattr(preflight, "force_cpu_backend")
    with pytest.raises(RuntimeError, match="not a TPU"):
        from benchmarks.runner import run_benchmark
        run_benchmark(sf=0.0005, query_names=["q6"], iterations=1)
