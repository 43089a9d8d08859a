"""Per-operator metrics, EXPLAIN ANALYZE, query listeners, and the
Chrome-trace timeline (ISSUE 6): the observability layer the reference
surfaces through SQLMetrics in the Spark UI (GpuExec.scala:27-56) plus
NVTX ranges (NvtxWithMetrics.scala:27), reproduced as exec-attributed
metric bags + a text EXPLAIN ANALYZE + trace.json export."""

import json
import os
import sys
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
                     if n not in ("wallS", "concurrency",
                                  "semaphoreHoldS")}
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


# ---------------------------------------------------------------------------
# ISSUE 25: named programs, scopes inside them, compile events charged
# from inside the engine, the spans of a whole query
# ---------------------------------------------------------------------------

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_NAMES_CHILD = r"""
import json, sys
sys.path.insert(0, sys.argv[1])
from benchmarks import datagen, queries
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.api import functions as F

def programs():
    from spark_rapids_tpu.columnar import batch
    from spark_rapids_tpu.parallel import mesh
    from spark_rapids_tpu.plan import physical
    from spark_rapids_tpu.shuffle import partitioning
    out = {}
    for cache in (physical._FUSED_CACHE, batch._UNPACK_CACHE,
                  partitioning._SPLIT_FN_CACHE, mesh._FN_CACHE):
        for prog in list(cache.values()):
            out[prog._family] = prog._fn.__wrapped__.__name__
    return out

s = TpuSession.builder.config(
    {"spark.rapids.tpu.sql.explain": "NONE"}).getOrCreate()
t = datagen.register_tables(s, 0.002)
for q in (queries.q1, queries.q6, queries.q3):
    q(t).collect()
names = programs()
# one SPMD stage and one shuffle split besides: the mesh group-by
m = TpuSession.builder.config(
    {"spark.rapids.tpu.sql.explain": "NONE",
     "spark.rapids.tpu.sql.mesh.enabled": "true",
     "spark.rapids.tpu.sql.shuffle.partitions": 4}).getOrCreate()
df = m.createDataFrame({"k": [i % 7 for i in range(2000)],
                        "v": [float(i) for i in range(2000)]})
df.groupBy("k").agg(F.sum("v").alias("s")).collect()
df.repartition(4, "k").collect()
names.update(programs())
print(json.dumps(names, sort_keys=True))
"""


def _names_child(hash_seed):
    import subprocess
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONHASHSEED": hash_seed,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "JAX_ENABLE_COMPILATION_CACHE": "false"}
    return subprocess.Popen(
        [sys.executable, "-c", _NAMES_CHILD, _ROOT], env=env, cwd=_ROOT,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


def test_every_program_compiles_under_its_family_name_in_every_process():
    """q1, q6 and q3 (plus one mesh stage and one shuffle split): every
    program a cache hands out is named after its kernel family — never
    the builder's ``fn`` / ``run`` / ``unpack`` — and a process with
    another hash seed arrives at the same names (the persistent compile
    cache keys on them)."""
    import re
    children = [_names_child("1"), _names_child("2")]
    outs = []
    for c in children:
        out, err = c.communicate(timeout=600)
        assert c.returncode == 0, err[-2000:]
        outs.append(json.loads(out.strip().splitlines()[-1]))
    first, second = outs
    assert first == second
    assert len(first) >= 6, first
    assert "scan_unpack" in first and any(
        f.startswith("mesh/") for f in first), sorted(first)
    for family, name in first.items():
        assert name not in ("fn", "run", "unpack", "per_worker",
                            "run_project", "run_filter"), (family, name)
        assert name == re.sub(r"\W", "_", family)
        assert re.fullmatch(r"[A-Za-z_]\w*", name), name


def _tiny_q1(session, rows=3000):
    from benchmarks import datagen, queries
    t = datagen.register_tables(session, rows / datagen.LINEITEM_PER_SF)
    return queries.q1(t)


def _group_by_program_text(conf, query=_tiny_q1):
    """The lowered text (with debug info) of the aggregate's update
    programs of ``query`` (q1's group-by), as the session under ``conf``
    builds them."""
    from spark_rapids_tpu.plan import physical as ph
    calls = {}
    orig = ph._fused_fn

    def recording(key, builder):
        prog = orig(key, builder)

        def call(*args):
            if key not in calls:
                import jax
                calls[key] = (prog, [
                    jax.ShapeDtypeStruct(a.shape, a.dtype)
                    if hasattr(a, "shape") else a for a in args])
            return prog(*args)
        return call

    ph._fused_fn = recording
    try:
        query(_session(**conf)).collect()
    finally:
        ph._fused_fn = orig
    texts = {}
    for key, (prog, structs) in calls.items():
        if prog._family.startswith("agg/update"):
            texts[prog._family] = prog._fn.lower(*structs).as_text(
                debug_info=True)
    assert texts, sorted(p._family for p, _ in calls.values())
    return texts


_RETIRED_MATMUL_KEY = "spark.rapids.tpu.sql.agg.matmul.enabled"
_RETIRED_MATMUL_ENV = "SPARK_RAPIDS_TPU_CONF__" + \
    _RETIRED_MATMUL_KEY.upper().replace(".", "__")


@pytest.mark.parametrize("matmul", ["false", "true"])
def test_group_by_program_names_its_stages_under_the_operator(
        monkeypatch, matmul):
    """q1's group-by: every stage of the vocabulary it runs lies under
    ``TpuHashAggregateExec`` in the lowered program's op names, both ways
    of the choice the device makes by the group count among them. The
    environment key that used to select float32 matmul sums (the
    benchmark still exports it) changes nothing of that."""
    from spark_rapids_tpu.exec.tracing import STAGES
    monkeypatch.setenv(_RETIRED_MATMUL_ENV, matmul)
    text = "\n".join(_group_by_program_text({}).values())
    import re
    for stage in ("lexsort", "gather", "segment_starts",
                  "segment_ids_to_rows", "segment_sum_masked",
                  "segment_sum_scatter"):
        assert stage in STAGES
        # jax's own ``cond/branch_<i>_fun`` may lie between the two: the
        # choice between the masked and the scatter sums is made there
        assert re.search(r"/TpuHashAggregateExec/(cond/branch_\d_fun/)?"
                         + stage + "/", text), stage
    # the folded filter and projection keep their own operators' scopes
    assert "/TpuFilterExec/filter/" in text
    assert "jit(agg_update_" in text


def test_retired_matmul_key_leaves_q1s_update_program_as_it_is(monkeypatch):
    """One group-by (PR 29): with the key unset, ``false`` or ``true``, in
    the environment and in the session, q1's update is ONE program of the
    ``sort`` family and its lowered text is the same to the byte; the key
    is no conf of the registry any more."""
    from spark_rapids_tpu import config as cfg
    assert not [k for k in cfg.REGISTRY.entries() if "agg.matmul" in k.key]
    texts = {}
    for value in (None, "false", "true"):
        conf = {}
        if value is None:
            monkeypatch.delenv(_RETIRED_MATMUL_ENV, raising=False)
        else:
            monkeypatch.setenv(_RETIRED_MATMUL_ENV, value)
            conf[_RETIRED_MATMUL_KEY] = value
        by_family = _group_by_program_text(conf)
        (family, text), = by_family.items()
        assert family.startswith("agg/update/") and \
            family.endswith("/sort"), family
        texts[value] = text
    assert texts[None] == texts["false"] == texts["true"]


def _ops_by_scope(text, op):
    """The name (scopes and all) jax gave every ``stablehlo.<op>`` of a
    lowered text with debug info."""
    import re
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    lines = text.splitlines()
    names = []
    for i, line in enumerate(lines):
        if f"stablehlo.{op}" not in line:
            continue
        if line.endswith("({"):         # an op with a region: its end
            line = next(after for after in lines[i + 1:]
                        if after.lstrip().startswith("})"))
        names.append(locs.get(re.search(r"loc\((#loc\d+)\)$", line)[1], ""))
    return names


def test_group_by_gathers_its_keys_and_no_aggregate_input():
    """The reductions of the sort-based group-by read their inputs in ROW
    order (PR 28): in q1's update program the gathers into sort order are
    those of the two string keys, with one aggregate or with eight, and
    the one scatter outside the branches carries the segment ids to the
    rows. q1's value gathers were 2.9 s of its 5.26 s on the chip."""
    aggs = [F.sum("l_quantity"), F.sum("l_extendedprice"),
            F.avg("l_discount"), F.min("l_tax"), F.max("l_quantity"),
            F.count("l_tax"), F.first("l_extendedprice"), F.sum("l_tax")]

    def grouped(n_aggs):
        def query(session):
            from benchmarks import datagen
            t = datagen.register_tables(session,
                                        3000 / datagen.LINEITEM_PER_SF)
            return t["lineitem"].groupBy("l_returnflag", "l_linestatus").agg(
                *[a.alias(f"a{i}") for i, a in enumerate(aggs[:n_aggs])])
        return query

    key_gathers = {}
    for n_aggs in (1, len(aggs)):
        text = "\n".join(_group_by_program_text({}, grouped(n_aggs)).values())
        gathers = _ops_by_scope(text, "gather")
        outside = [g for g in gathers if "/cond/" not in g
                   and "/TpuHashAggregateExec/" in g]
        assert all(g.endswith("/TpuHashAggregateExec/gather/gather")
                   for g in outside), outside
        # inside a branch: only first / last fetching each group's pick
        assert all("/segment_minmax/gather/" in g for g in gathers
                   if "/cond/" in g), gathers
        key_gathers[n_aggs] = len(outside)
        scatters = [s for s in _ops_by_scope(text, "scatter")
                    if "/cond/" not in s and "/segment_starts/" not in s]
        assert [s.split("/TpuHashAggregateExec/")[1] for s in scatters] == \
            ["segment_ids_to_rows/scatter"], scatters
    # two string keys (bytes, lengths, validity), into sort order and, of
    # each group's first row, to the front
    assert key_gathers == {1: 12, len(aggs): 12}


def test_grouping_free_reduction_emits_no_scatter(monkeypatch):
    """q6's one program: a one-slot segment reduction is a masked reduce.
    Until PR 26 it was a scatter-add of every row into slot 0, 929 ms of
    q6's 1004 ms on the chip."""
    def tiny_q6(session):
        from benchmarks import datagen, queries
        return queries.q6(datagen.register_tables(
            session, 3000 / datagen.LINEITEM_PER_SF))
    texts = _group_by_program_text({}, tiny_q6)
    assert any(f.endswith("reduce") for f in texts), sorted(texts)
    text = "\n".join(texts.values())
    assert "/TpuHashAggregateExec/reduce/segment_sum_masked/" in text
    assert "stablehlo.reduce" in text and "stablehlo.scatter" not in text


def _few_groups_max():
    from spark_rapids_tpu.ops.aggregates import FEW_GROUPS_MAX
    return FEW_GROUPS_MAX


@pytest.mark.parametrize("groups, counted", [
    (4, {"aggFewGroupBatches": 1}),
    (_few_groups_max() + 1, {"aggScatterBatches": 1}),
])
def test_aggregate_counts_which_reduction_each_batch_took(groups, counted):
    """The sort-based group-by chooses on the device; the operator says
    which way from the group count it reads back anyway — no sync more
    than before PR 26 (one: the shrink's)."""
    s = _session()
    n = 6000               # a batch above SHRINK_ABOVE_SLOTS: it is shrunk
    df = s.createDataFrame({"k": [f"g{i % groups}" for i in range(n)],
                            "v": [float(i) for i in range(n)]})
    rows = df.groupBy("k").agg(F.sum("v").alias("s")).collect()
    assert len(rows) == groups
    m = s.last_query_metrics()
    got = {k: sum(o["metrics"].get(k, 0) for o in m["operators"])
           for k in ("aggFewGroupBatches", "aggScatterBatches")}
    assert {k: v for k, v in got.items() if v} == counted
    assert m["sync"]["hostSyncs"] == 1
    assert f"{next(iter(counted))}: 1" in s.explain_analyze()


class _OutsideListener:
    """What an independent ``jax.monitoring`` listener counts: top-level
    traces, backend compilations and loads from the persistent cache."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    BACKEND = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.tls = threading.local()
        self.traces = self.builds = self.loads = 0
        self.build_s = 0.0

    def __enter__(self):
        import jax.monitoring as m
        m.register_event_listener(self.on_event)
        m.register_scalar_listener(self.on_scalar)
        m.register_event_duration_secs_listener(self.on_duration)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as m
        m.unregister_event_listener(self.on_event)
        m.unregister_scalar_listener(self.on_scalar)
        m.unregister_event_duration_listener(self.on_duration)

    def on_event(self, event, **_):
        if event == self.HIT:
            self.tls.hit = True

    def on_scalar(self, event, _value, **_):
        if event == self.TRACE:
            self.tls.depth = getattr(self.tls, "depth", 0) + 1

    def on_duration(self, event, seconds, **_):
        if event == self.TRACE:
            self.tls.depth -= 1
            if self.tls.depth == 0:
                self.traces += 1
        elif event == self.BACKEND:
            if getattr(self.tls, "hit", False):
                self.loads += 1
            else:
                self.builds += 1
                self.build_s += seconds
            self.tls.hit = False


def _program_totals(programs):
    return {f: sum(p[f] for p in programs.values())
            for f in ("dispatches", "traces", "compiles", "cacheLoads")}


def test_programs_map_agrees_with_an_outside_listener():
    """``last_query_metrics()["programs"]`` — present with no query
    listener registered — counts the builds and loads an independent
    listener counts; its traces are the outside count less the helpers
    traced while a program lowers; a repeat rebuilds no program of the
    funnel; an op outside it lands under ``<eager>:``."""
    s = _session()
    assert not s._query_listeners
    rng = np.random.default_rng(25)
    df = s.createDataFrame(pd.DataFrame({
        "k": rng.integers(0, 5, 700), "v": rng.normal(0, 1, 700)}))
    # literals unique to this test: the fused cache is process-wide
    q = (df.filter(F.col("v") > -7.03125).groupBy("k")
         .agg(F.sum(F.col("v") * 1.40625).alias("s")).orderBy("k"))
    with _OutsideListener() as outside:
        q.collect()
    programs = s.last_query_metrics()["programs"]
    totals = _program_totals(programs)
    assert totals["compiles"] == outside.builds > 0
    assert totals["cacheLoads"] == outside.loads
    assert 0 < totals["traces"] <= outside.traces
    assert totals["dispatches"] >= 2
    funnel = {k: v for k, v in programs.items()
              if not k.startswith("<eager>:")}
    built = {k for k, p in funnel.items() if p["traces"]}
    assert any(k.startswith("agg/update") for k in built), funnel
    for family, p in funnel.items():
        assert p["dispatches"] >= 1, family
        # one trace, one lowering, one build (or load) per new program;
        # a program an earlier query built (the scan unpack) costs none
        assert p["traces"] == p["compiles"] + p["cacheLoads"], (family, p)
        assert (p["traceS"] > 0 and p["lowerS"] > 0) == (family in built)
    # the final ORDER BY of a few rows runs as eager jnp ops
    eager = {k: v for k, v in programs.items() if k.startswith("<eager>:")}
    assert eager and all(p["dispatches"] == 0 for p in eager.values())
    compile_s = sum(p["compileS"] for p in programs.values())
    # (each entry is reported rounded to the microsecond)
    assert compile_s == pytest.approx(outside.build_s, abs=1e-4)
    # the same text again: every program is there already
    with _OutsideListener() as again:
        q.collect()
    repeat = s.last_query_metrics()["programs"]
    # (the repeat reads the table from the device scan cache: no unpack)
    again_funnel = set(funnel) - {"scan_unpack"}
    assert {k for k in repeat if not k.startswith("<eager>:")} == \
        again_funnel
    assert all(repeat[k]["dispatches"] == funnel[k]["dispatches"]
               and repeat[k]["traces"] == repeat[k]["compiles"] == 0
               for k in again_funnel)
    # what is still rebuilt is named: an eager op of the final sort
    # (its fori_loop is a fresh function, so a fresh build, every query)
    assert _program_totals(repeat)["compiles"] == again.builds
    assert {k for k, p in repeat.items() if p["compiles"]} <= \
        {"<eager>:scan"}


def test_listener_recompiles_come_from_the_query_programs_map():
    """``QueryExecution.recompiles`` keeps ``recompile.delta``'s shape,
    filled from the query's own ``programs`` map."""
    s = _session()
    got = []
    s.register_query_listener(got.append)
    try:
        df = s.createDataFrame(pd.DataFrame(
            {"k": [1, 2, 1, 3] * 40, "v": [1.5, 2.5, 3.5, 4.5] * 40}))
        df.groupBy("k").agg(F.max("v").alias("m")).collect()
    finally:
        s.unregister_query_listener(got.append)
    (qe,) = got
    programs = s.last_query_metrics()["programs"]
    assert set(qe.recompiles) == {
        k for k in programs if not k.startswith("<eager>:")}
    for family, ent in qe.recompiles.items():
        p = programs[family]
        assert set(ent) == {"compiles", "calls", "coldCompiles",
                            "diskHits", "compileS"}
        assert ent["calls"] == p["dispatches"]
        assert ent["compiles"] == p["compiles"] + p["cacheLoads"]


def test_spans_cover_the_whole_query_and_name_their_parents():
    """The root span ``query`` opens before planning; ``plan`` and the
    result's ``fetch_to_host`` are spans of the same query; every event
    carries the span that caused it and the query's id; the blocking
    readbacks are timed."""
    s = _session(**{"spark.rapids.tpu.sql.tracing.timeline": "true"})
    try:
        df = s.createDataFrame(pd.DataFrame(
            {"k": [1, 2, 1, 3] * 64, "v": [1., 2., 3., 4.] * 64}))
        batch = df.groupBy("k").agg(F.sum("v").alias("sv")) \
            .orderBy("k").collect_batch()
        before = s.last_query_metrics()["spans"]
        assert "fetch_to_host" not in before
        assert batch.fetch_to_host().num_rows == 3
        m = s.last_query_metrics()
        spans = m["spans"]
        for name in ("query", "plan", "analyze", "plan_cache",
                     "overrides", "fetch_to_host"):
            assert spans[name]["count"] == 1, name
        assert spans["wallS"] >= before["wallS"]
        assert m["planTimeS"] > 0
        rec = s._last_span_recorder
        qid = s._last_query_id
        events = [e for e in rec.chrome_trace()["traceEvents"]
                  if e["ph"] == "X"]
        by_name = {e["name"]: e for e in events}
        assert all(e["args"]["query"] == qid for e in events)
        assert "parent" not in by_name["query"]["args"]
        assert by_name["plan"]["args"]["parent"] == "query"
        assert by_name["analyze"]["args"]["parent"] == "plan"
        assert by_name["overrides"]["args"]["parent"] == "plan_cache"
        assert by_name["fetch_to_host"]["args"]["parent"] == "query"
        assert all(e["args"].get("parent") for e in events
                   if e["name"] != "query")
        # the compile events were charged to the span they fell under
        assert sum(v.get("rebuilds", 0) for v in spans.values()
                   if isinstance(v, dict)) > 0
        sync = m["sync"]
        assert sync["hostSyncs"] > 0 and sync["syncWaitS"] > 0
        assert sum(sync["syncSpanWaitS"].values()) == pytest.approx(
            sync["syncWaitS"], abs=1e-4)
        assert set(sync["syncSiteWaitS"]) == set(sync["syncSites"])
    finally:
        from spark_rapids_tpu.exec import tracing
        tracing.reset_cache()


def _synced_query(s):
    """A group-by whose ORDER BY reads a row count back: programs, spans
    and at least one blocking readback."""
    df = s.createDataFrame(pd.DataFrame(
        {"k": [1, 2, 1, 3] * 64, "v": [1., 2., 3., 4.] * 64}))
    return df.groupBy("k").agg(F.sum("v").alias("sv")).orderBy("k")


def _clean_tracing_env(monkeypatch):
    monkeypatch.delenv(
        "SPARK_RAPIDS_TPU_CONF__SPARK__RAPIDS__TPU__SQL__TRACING__ENABLED",
        raising=False)


def test_tracing_off_constructs_no_profiler_annotation(monkeypatch):
    """With ``tracing.enabled`` false a query builds not one
    ``TraceAnnotation``: programs, spans and readbacks cost no profiler
    call."""
    import jax
    _clean_tracing_env(monkeypatch)

    def refuse(*a, **k):
        raise AssertionError(f"TraceAnnotation{a} with tracing off")

    s = _session()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    assert len(_synced_query(s).collect()) == 3
    assert s.last_query_metrics()["sync"]["hostSyncs"] > 0


def test_session_conf_turns_the_annotations_on(monkeypatch):
    """``.config({"...tracing.enabled": "true"})`` with a clean
    environment is honoured: spans, program calls and readbacks are
    profiler annotations that carry the query's id (the root span and
    ``plan`` open before the plan, and so the id, exist)."""
    import contextlib
    import jax
    from spark_rapids_tpu.exec import tracing
    _clean_tracing_env(monkeypatch)
    seen = []

    def annotation(name, **kw):
        seen.append((name, kw))
        return contextlib.nullcontext()

    s = _session(**{"spark.rapids.tpu.sql.tracing.enabled": "true"})
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", annotation)
    try:
        _synced_query(s).collect()
        qid = s._last_query_id
    finally:
        _session()                      # a default session: tracing off
        tracing.reset_cache()
    names = [n for n, _ in seen]
    assert names[0] == "query" and "plan" in names
    assert "fetch_to_host" in names and "host_sync" in names
    programs = [(n, kw) for n, kw in seen if n.startswith("program:")]
    assert programs and all(kw["query"] == qid for _, kw in programs)
    assert any(kw.get("op", "").startswith("Tpu") for _, kw in programs)
    late = names.index("plan") + 1
    assert all(kw.get("query") == qid for n, kw in seen[late:]
               if n not in ("analyze", "plan_cache", "overrides"))
