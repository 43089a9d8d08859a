"""Query-lifecycle observability (docs/observability.md §8): query-id
propagation, stage-boundary exchange statistics on all three shuffle
planes, estimate-vs-actual drift, the structured query log + report CLI,
the merged multi-worker timeline, the flight-dump query filter, and the
durable-tier GC budget.

Plus query lifecycle CONTROL (ISSUE 20, docs/service.md §4a): the
CancelToken state machine, deterministic mid-execution cancel and
suspend/resume via the chaos points, deadline enforcement at poll
boundaries, weighted-fair scheduling, and two-OS-process cancel
propagation over the shuffle META round trip.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pandas as pd
import pytest

from procs import readline_bounded

from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.shuffle.exchange import (collect_stage_stats,
                                               compute_stage_stats)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _session(**conf):
    return TpuSession.builder.config(
        {"spark.rapids.tpu.sql.explain": "NONE", **conf}).getOrCreate()


# ---------------------------------------------------------------------------
# Units: skew/p50 math, query-id minting, timeline merging
# ---------------------------------------------------------------------------

def test_stage_stats_skew_and_p50_units():
    """Exact unit semantics: p50 = median partition BYTES, skew = max
    partition bytes over MEAN partition bytes (1.0 = balanced)."""
    st = compute_stage_stats(3, "dcn", rows=[10, 20, 30, 40],
                             bytes_=[100, 200, 300, 600])
    assert st["partitions"] == 4
    assert st["totalRows"] == 100 and st["totalBytes"] == 1200
    assert st["p50Bytes"] == 250.0          # median of 100,200,300,600
    assert st["maxBytes"] == 600
    assert st["skew"] == 2.0                # 600 / mean(300)
    assert st["stageId"] == 3 and st["plane"] == "dcn"
    # degenerate shapes never divide by zero
    empty = compute_stage_stats(None, "ici", [], [])
    assert empty["skew"] == 1.0 and empty["p50Bytes"] == 0.0
    zeros = compute_stage_stats(1, "dcn", [0, 0], [0, 0])
    assert zeros["skew"] == 1.0


def test_query_id_minting_is_structural_and_monotonic():
    from spark_rapids_tpu.exec import query_context as qc

    class _N:
        def __init__(self, *children):
            self.children = list(children)

    plan = _N(_N(), _N(_N()))
    a = qc.mint_query_id(plan)
    b = qc.mint_query_id(plan)
    c = qc.mint_query_id(_N())
    # counter advances, structural digest is stable for the same shape
    assert a != b
    assert a.split("-")[1] == b.split("-")[1]
    assert a.split("-")[1] != c.split("-")[1]
    # the ambient scope: pool-style threads see the driving default
    ctx = qc.QueryContext("q-test")
    with qc.query_scope(ctx):
        assert qc.current_query_id() == "q-test"
        assert ctx.next_stage_id() == 1 and ctx.next_stage_id() == 2
        import threading
        seen = []
        t = threading.Thread(
            target=lambda: seen.append(qc.current_query_id()))
        t.start()
        t.join()
        assert seen == ["q-test"]
    assert qc.current_query_id() is None


def test_merge_chrome_traces_filters_and_regroups():
    from spark_rapids_tpu.exec.tracing import merge_chrome_traces
    t0 = {"traceEvents": [
        {"ph": "X", "name": "a", "pid": 0, "tid": 1, "ts": 0, "dur": 5,
         "args": {"query": "q1"}},
        {"ph": "X", "name": "stale", "pid": 0, "tid": 1, "ts": 9,
         "dur": 1, "args": {"query": "q0"}},
        {"ph": "M", "name": "thread_name", "pid": 0, "tid": 1,
         "args": {"name": "tpu-task_0"}}]}
    t1 = {"traceEvents": [
        {"ph": "X", "name": "b", "pid": 0, "tid": 1, "ts": 2, "dur": 3,
         "args": {"query": "q1"}}]}
    merged = merge_chrome_traces([t0, t1], query_id="q1")
    xs = [e for e in merged["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"a", "b"}   # q0 filtered out
    assert {e["pid"] for e in xs} == {0, 1}        # per-source process
    assert all(e["args"]["query"] == "q1" for e in xs)
    assert merged["queryId"] == "q1" and merged["mergedSources"] == 2


# ---------------------------------------------------------------------------
# The q3-shaped acceptance query, on the local DCN and ICI planes
# ---------------------------------------------------------------------------

def _q3_tables(s):
    rng = np.random.default_rng(7)
    n = 8192
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
    s.createDataFrame(line).createOrReplaceTempView("p_lineitem")
    s.createDataFrame(orders).createOrReplaceTempView("p_orders")
    s.createDataFrame(cust).createOrReplaceTempView("p_customer")


_Q3 = ("SELECT l_price, o_date, c_seg FROM p_lineitem "
       "JOIN p_orders ON l_order = o_key "
       "JOIN p_customer ON o_cust = c_key "
       "WHERE o_date < 700 AND c_seg = 1")

_Q3_CONF = {
    "spark.rapids.tpu.sql.shuffle.partitions": "4",
    "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": "-1",
}


def _run_q3(s):
    _q3_tables(s)
    rows = s.sql(_Q3).collect()
    assert len(rows) > 0
    return rows


def _assert_q3_observability(s, plane):
    """The ISSUE acceptance surface, shared by the DCN and ICI runs:
    EXPLAIN ANALYZE shows, per exchange node, partition count + p50/max
    partition bytes + skew factor, and per plan node est vs actual rows
    with a drift ratio; last_stage_stats carries the programmatic
    shape."""
    stats = s.last_stage_stats()
    assert len(stats) == 4, stats              # 2 per shuffled join
    for st in stats:
        assert st["plane"] == plane
        assert st["partitions"] == 4
        assert st["stageId"] is not None
        assert st["queryId"] == s.last_query_id()
        assert len(st["rows"]) == 4 and len(st["bytes"]) == 4
        assert st["totalRows"] == sum(st["rows"]) > 0
        assert st["skew"] >= 1.0 and st["p50Bytes"] >= 0
        assert st["maxBytes"] == max(st["bytes"])
    # stage ids number the boundaries 1..4 deterministically
    assert sorted(st["stageId"] for st in stats) == [1, 2, 3, 4]
    ea = s.explain_analyze()
    for needle in (f"exchange [{plane}]", "partitions=4", "p50Bytes=",
                   "maxBytes=", "skew=", "rows: est=", "drift=",
                   "queryId="):
        assert needle in ea, (needle, ea)
    drift = s.last_drift_report()
    assert drift and all(
        {"operator", "estRows", "actualRows", "ratio",
         "flagged"} <= set(d) for d in drift)
    return stats


def test_q3_dcn_stage_stats_and_drift_in_explain_analyze():
    s = _session(**{"spark.rapids.tpu.sql.mesh.enabled": "false",
                    **_Q3_CONF})
    _run_q3(s)
    _assert_q3_observability(s, "dcn")


def test_q3_ici_stage_stats_parity_with_dcn():
    """The ICI plane derives the SAME per-partition row statistics from
    its single counts readback as the DCN plane measures from staged
    slices — exchange-statistics parity across planes on the q3-shaped
    3-way join."""
    s_dcn = _session(**{"spark.rapids.tpu.sql.mesh.enabled": "false",
                        **_Q3_CONF})
    _run_q3(s_dcn)
    dcn = {st["stageId"]: st["rows"]
           for st in _assert_q3_observability(s_dcn, "dcn")}
    s_ici = _session(**{
        "spark.rapids.tpu.sql.mesh.enabled": "true",
        "spark.rapids.tpu.sql.mesh.maxStageBytes": "1",
        "spark.rapids.tpu.sql.shuffle.plane": "ici",
        **_Q3_CONF})
    _run_q3(s_ici)
    ici = {st["stageId"]: st["rows"]
           for st in _assert_q3_observability(s_ici, "ici")}
    # identical hash partitioning => identical per-partition row vectors
    assert dcn == ici, (dcn, ici)


def test_stats_collection_overhead_within_coarse_factor():
    """Stage-stats collection rides the metrics gate; with metrics ON the
    exchange-heavy query stays within a coarse factor of metrics OFF
    (stats are derived once per exchange from already-host metadata —
    never per batch)."""
    from spark_rapids_tpu.api.functions import col

    def run(metrics_on):
        s = _session(**{
            "spark.rapids.tpu.sql.mesh.enabled": "false",
            "spark.rapids.tpu.sql.metrics.enabled":
                "true" if metrics_on else "false",
            "spark.rapids.tpu.sql.shuffle.partitions": "8"})
        rng = np.random.default_rng(3)
        df = pd.DataFrame({"k": rng.integers(0, 64, 20000).astype("int64"),
                           "v": rng.normal(0, 1, 20000)})
        frame = s.createDataFrame(df).repartition(8, col("k"))
        frame.collect()                      # warm compiles out of the timing
        t0 = time.perf_counter()
        for _ in range(3):
            frame.collect()
        return time.perf_counter() - t0, s

    off_s, s_off = run(False)
    assert not collect_stage_stats(s_off.last_plan()), \
        "metrics off must also gate stage stats"
    on_s, s_on = run(True)
    assert collect_stage_stats(s_on.last_plan())
    assert on_s < off_s * 5 + 1.0, (on_s, off_s)


def test_drift_threshold_flags_misestimates():
    """A filter whose selectivity is far from the 0.25 heuristic crosses
    the drift threshold and is flagged (report + EXPLAIN ANALYZE)."""
    from spark_rapids_tpu.api.functions import col
    s = _session(**{
        "spark.rapids.tpu.sql.observability.driftThreshold": "2.0",
        # keep the standalone filter visible as its own node
        "spark.rapids.tpu.sql.fusion.wholeStage": "false"})
    df = pd.DataFrame({"v": list(range(10000))})
    got = s.createDataFrame(df).filter(col("v") < 10).collect()
    assert len(got) == 10
    drift = s.last_drift_report()
    flagged = [d for d in drift if d["flagged"]]
    # est = 10000 * 0.25 = 2500 vs actual 10 -> ratio 0.004, flagged
    f = [d for d in flagged if d["operator"] == "TpuFilterExec"]
    assert f and f[0]["estRows"] == 2500 and f[0]["actualRows"] == 10, \
        drift
    assert "! drift" in s.explain_analyze()
    # widen the threshold past the miss: the same query stops flagging
    s2 = _session(**{
        "spark.rapids.tpu.sql.observability.driftThreshold": "100000",
        "spark.rapids.tpu.sql.fusion.wholeStage": "false"})
    s2.createDataFrame(df).filter(col("v") < 10).collect()
    assert not [d for d in s2.last_drift_report() if d["flagged"]]


def test_drift_perfectly_estimated_empty_node_not_flagged():
    """est=0 / actual=0 is a PERFECT estimate (ratio 1.0), never the
    report's worst misestimate."""
    from spark_rapids_tpu.plan import estimates

    class _M(dict):
        def get(self, k, d=None):
            return dict.get(self, k, d)

    class _N:
        def __init__(self):
            self.children = []
            self.metrics = _M()

    n = _N()
    n.est_rows = 0
    n.metrics["numOutputRows"] = 0
    rep = estimates.drift_report(n)
    assert rep and rep[0]["ratio"] == 1.0 and not rep[0]["flagged"], rep


def test_pool_threads_attribute_to_their_own_concurrent_query():
    """Two CONCURRENT queries in one process: each query's task-pool
    events attribute to its OWN query id (run_partition_tasks routes the
    submitting thread's context explicitly), never to whichever query
    entered the process default last."""
    import threading
    from spark_rapids_tpu.exec import query_context as qc
    from spark_rapids_tpu.exec.tasks import run_partition_tasks
    barrier = threading.Barrier(2, timeout=30)
    got = {}

    def run(qname):
        with qc.query_scope(qc.QueryContext(qname)):
            barrier.wait()       # both defaults pushed before any task

            def task(pid, part):
                barrier.wait()   # tasks of both queries in flight
                return qc.current_query_id()

            got[qname] = set(run_partition_tasks([0, 1], task,
                                                 max_workers=2))

    threads = [threading.Thread(target=run, args=(q,))
               for q in ("q-one", "q-two")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert got == {"q-one": {"q-one"}, "q-two": {"q-two"}}, got


# ---------------------------------------------------------------------------
# Query log + report CLI
# ---------------------------------------------------------------------------

def test_query_log_record_and_report_cli(tmp_path):
    log_dir = str(tmp_path / "qlog")
    s = _session(**{"spark.rapids.tpu.sql.mesh.enabled": "false",
                    "spark.rapids.tpu.sql.telemetry.queryLog.dir": log_dir,
                    **_Q3_CONF})
    _run_q3(s)
    path = os.path.join(log_dir, f"query_log-{os.getpid()}.jsonl")
    assert os.path.exists(path)
    rec = [json.loads(line) for line in open(path)][-1]
    from spark_rapids_tpu.service.query_log import QUERY_LOG_FIELDS
    assert set(rec) <= set(QUERY_LOG_FIELDS)
    assert rec["queryId"] == s.last_query_id()
    assert rec["planCache"] in ("hit", "miss", "uncacheable", "off")
    assert rec["resultCache"] in ("hit", "miss", "uncacheable", "off")
    assert len(rec["stageStats"]) == 4
    assert rec["stageRetries"] == 0 and rec["faultsFired"] == 0
    assert rec["wallS"] > 0 and rec["operators"]
    assert rec["drift"]["nodes"] > 0
    # the CLI renders a digest naming the query, skew and drift
    from tools.query_report import render
    text = render([path])
    assert rec["queryId"] in text
    assert "skewest exchange" in text
    assert "top operators by time" in text
    # and survives being driven as a subprocess CLI
    out = subprocess.run(
        [sys.executable, "-m", "tools.query_report", path],
        capture_output=True, text=True, cwd=_REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0 and rec["queryId"] in out.stdout


# ---------------------------------------------------------------------------
# Flight-recorder query scoping
# ---------------------------------------------------------------------------

def test_flight_dump_filters_by_query_id(tmp_path):
    from spark_rapids_tpu.exec import query_context as qc
    from spark_rapids_tpu.service import telemetry as tel
    tel.FlightRecorder.reset()
    try:
        with qc.query_scope(qc.QueryContext("qAAA")):
            tel.flight_record("span", "a-span", {"durS": 1})
        with qc.query_scope(qc.QueryContext("qBBB")):
            tel.flight_record("span", "b-span", {"durS": 1})
        tel.flight_record("conf", "ambient-key", {"value": "1"})
        # events carry the ambient query id
        evs = {e["name"]: e for e in tel.FlightRecorder.get().events()}
        assert evs["a-span"]["data"]["query"] == "qAAA"
        assert evs["b-span"]["data"]["query"] == "qBBB"
        assert "query" not in evs["ambient-key"].get("data", {})
        # a query-scoped dump names the query and filters the other one
        path = tel.FlightRecorder.get().dump(
            path=str(tmp_path / "flight-qAAA.json"), query_id="qAAA")
        doc = json.load(open(path))
        names = [e["name"] for e in doc["events"]]
        assert "a-span" in names and "ambient-key" in names
        assert "b-span" not in names
        assert doc["queryId"] == "qAAA"
        # the default filename carries the failing query id
        auto = tel.FlightRecorder.get().dump(query_id="qAAA")
        try:
            assert "qAAA" in os.path.basename(auto)
        finally:
            os.unlink(auto)
    finally:
        tel.FlightRecorder.reset()


# ---------------------------------------------------------------------------
# Durable shuffle tier GC budget
# ---------------------------------------------------------------------------

def test_durable_gc_budget_evicts_oldest_completed(tmp_path):
    import glob
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.service.telemetry import MetricsRegistry
    from spark_rapids_tpu.shuffle.transport import ShuffleStore
    d = str(tmp_path / "durable")
    batch = ColumnarBatch.from_pydict(
        {"a": list(range(1000))}).fetch_to_host()
    nbytes = sum(int(a.nbytes) for c in batch.columns
                 for a in c.arrays())
    # budget fits ~2 shuffles; the third completion evicts the oldest
    store = ShuffleStore(durable_dir=d, durable_budget=2 * nbytes + 64)
    before = MetricsRegistry.get().counter(
        "tpu_durable_evicted_bytes_total").value
    for sid in (1, 2, 3):
        store.register_batch(sid, 0, batch)
        store.mark_complete(sid)
    assert not glob.glob(os.path.join(d, "buf-1-*")), \
        "oldest completed shuffle's durable files must evict"
    assert not os.path.exists(os.path.join(d, "complete-1"))
    assert glob.glob(os.path.join(d, "buf-3-*")), \
        "the newest completed shuffle is never evicted"
    assert MetricsRegistry.get().counter(
        "tpu_durable_evicted_bytes_total").value >= before + nbytes
    # eviction touches only the durable tier: in-memory still serves
    assert store.local_batches(1, 0)
    # a reloading store obeys the same budget
    store2 = ShuffleStore(durable_dir=d, durable_budget=nbytes + 64)
    n = store2.reload_durable()
    assert n >= 1
    assert glob.glob(os.path.join(d, "buf-3-*"))
    assert not glob.glob(os.path.join(d, "buf-2-*"))
    # budget off (0) never evicts
    d2 = str(tmp_path / "durable2")
    store3 = ShuffleStore(durable_dir=d2, durable_budget=0)
    for sid in (1, 2, 3):
        store3.register_batch(sid, 0, batch)
        store3.mark_complete(sid)
    assert len(glob.glob(os.path.join(d2, "buf-*-*.npz"))) == 3


# ---------------------------------------------------------------------------
# Two-OS-process acceptance: one merged timeline, one query id, logs
# ---------------------------------------------------------------------------

_WORKER = """
import sys, json, os
sys.path.insert(0, {repo!r})
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
from spark_rapids_tpu.shuffle.manager import init_worker

wid = int(sys.argv[1]); n = int(sys.argv[2]); log_dir = sys.argv[3]
ctx = init_worker(wid, n)
print(json.dumps({{"port": ctx.port}}), flush=True)
peers = json.loads(sys.stdin.readline())
ctx.set_peers({{int(k): tuple(v) for k, v in peers.items()}})

from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.functions import col

s = TpuSession.builder.config({{
    "spark.rapids.tpu.sql.explain": "NONE",
    "spark.rapids.tpu.sql.shuffle.partitions": "4",
    "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": "-1",
    "spark.rapids.tpu.sql.tracing.timeline": "true",
    "spark.rapids.tpu.sql.telemetry.queryLog.dir": log_dir,
}}).getOrCreate()

base = wid * 1000
ks = [(base + i) % 7 for i in range(200)]
vs = [float(i % 13) for i in range(200)]
s.createDataFrame({{"k": ks, "v": vs}}).createOrReplaceTempView("t")
rk = list(range(7))
s.createDataFrame({{"k": rk, "w": [k * 10.0 for k in rk]}}) \\
    .createOrReplaceTempView("dim")

out = (s.table("t")
       .join(s.table("dim"), on="k", how="inner")
       .groupBy("k")
       .agg(F.sum(col("v") + col("w")).alias("sv"))
       .collect())

rec = getattr(s, "_last_span_recorder")
log_path = os.path.join(log_dir, f"query_log-{{os.getpid()}}.jsonl")
print(json.dumps({{
    "rows": [list(r) for r in out],
    "qid": s.last_query_id(),
    "stats": s.last_stage_stats(),
    "trace": rec.chrome_trace(),
    "ea": s.explain_analyze(),
    "log": [json.loads(l) for l in open(log_path)],
}}), flush=True)
ctx.shutdown()
"""


def test_two_process_merged_timeline_and_query_log(tmp_path):
    """ISSUE 14 acceptance: a two-OS-process distributed query produces
    ONE merged timeline whose spans from BOTH workers carry the same
    query id; each worker's query-log record carries stage stats,
    retries and cache verdicts; the distributed exchange statistics
    (summed across workers) agree with the same query's local-mode
    statistics; and EXPLAIN ANALYZE on the distributed plane shows the
    exchange stats + drift surface too."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    procs = []
    for wid in range(2):
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _WORKER.format(repo=_REPO),
             str(wid), "2", str(tmp_path / f"qlog-{wid}")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, env=env, text=True))
    results = []
    try:
        ports = {}
        for wid, p in enumerate(procs):
            line = readline_bounded(p)
            assert line, p.stderr.read()
            ports[wid] = ("127.0.0.1", json.loads(line)["port"])
        peers = json.dumps({str(w): list(a) for w, a in ports.items()})
        for p in procs:
            p.stdin.write(peers + "\n")
            p.stdin.flush()
        for p in procs:
            out, err = p.communicate(timeout=300)
            assert p.returncode == 0, err
            for line in out.splitlines():
                try:
                    d = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if "qid" in d:
                    results.append(d)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    assert len(results) == 2
    w0, w1 = results

    # --- the lockstep query id is SHARED across both OS processes
    qid = w0["qid"]
    assert qid and w1["qid"] == qid

    # --- one merged timeline, spans from BOTH workers, one query id
    from spark_rapids_tpu.exec.tracing import merge_chrome_traces
    merged = merge_chrome_traces([w0["trace"], w1["trace"]],
                                 query_id=qid)
    spans = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
    assert spans
    pids = {e["pid"] for e in spans}
    assert pids == {0, 1}, pids
    assert all(e["args"]["query"] == qid for e in spans)
    assert merged["queryId"] == qid

    # --- each worker's query-log record: stage stats, retries, verdicts
    for w in (w0, w1):
        rec = w["log"][-1]
        assert rec["queryId"] == qid
        assert rec["stageStats"] and all(
            st["plane"] == "dcn" for st in rec["stageStats"])
        assert "stageRetries" in rec and rec["stageRetries"] == 0
        assert rec["planCache"] in ("hit", "miss", "uncacheable", "off")
        assert rec["resultCache"] in ("hit", "miss", "uncacheable",
                                      "off")

    # --- EXPLAIN ANALYZE shows the exchange stats + drift surface on
    # the distributed plane as well
    for w in (w0, w1):
        for needle in ("exchange [dcn]", "p50Bytes=", "skew=",
                       "rows: est=", f"queryId={qid}"):
            assert needle in w["ea"], (needle, w["ea"][:2000])

    # --- exchange-statistics parity: distributed per-partition rows
    # summed across workers == the SAME query's local-mode statistics
    # (identical hash partitioning; the dim table is replicated on both
    # workers so its exchange doubles — compare the fact-side exchange)
    s = _session(**{"spark.rapids.tpu.sql.mesh.enabled": "false",
                    **_Q3_CONF})
    frames = []
    for wid in range(2):
        base = wid * 1000
        frames.append(pd.DataFrame({
            "k": [(base + i) % 7 for i in range(200)],
            "v": [float(i % 13) for i in range(200)]}))
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.functions import col
    s.createDataFrame(pd.concat(frames)).createOrReplaceTempView("t")
    s.createDataFrame({"k": list(range(7)),
                       "w": [k * 10.0 for k in range(7)]}) \
        .createOrReplaceTempView("dim")
    (s.table("t").join(s.table("dim"), on="k", how="inner")
     .groupBy("k").agg(F.sum(col("v") + col("w")).alias("sv")).collect())
    local = {st["stageId"]: st for st in s.last_stage_stats()}
    d0 = {st["stageId"]: st for st in w0["stats"]}
    d1 = {st["stageId"]: st for st in w1["stats"]}
    assert set(local) == set(d0) == set(d1), (local.keys(), d0.keys())
    fact_sids = [sid for sid, st in local.items()
                 if st["totalRows"] == 400]
    assert fact_sids, local
    for sid in fact_sids:
        summed = [a + b for a, b in zip(d0[sid]["rows"],
                                        d1[sid]["rows"])]
        assert summed == local[sid]["rows"], (sid, summed,
                                              local[sid]["rows"])


# ---------------------------------------------------------------------------
# Query lifecycle control (ISSUE 20): cancel, suspend/resume, preemption
# ---------------------------------------------------------------------------

def test_cancel_token_state_machine():
    """Unit transitions of the CancelToken: idempotent cancel with
    first-reason-wins, suspend/resume re-arming, check() raising, and
    the append-only transition log."""
    import pytest
    from spark_rapids_tpu.exec import lifecycle as lc

    tok = lc.CancelToken("q-unit")
    assert tok.state == lc.RUNNING
    assert not tok.cancelled and not tok.suspend_requested
    tok.check()                                    # clean: no raise

    assert tok.request_suspend("preempt") is True
    assert tok.request_suspend("again") is False   # already requested
    with pytest.raises(lc.QuerySuspendedError):
        tok.check()
    tok.park_cursor(stage="stage-1", partitions_done=[0, 2])
    tok.mark_suspended()
    assert tok.state == lc.SUSPENDED
    assert tok.cursor == {"stage": "stage-1", "partitionsDone": [0, 2]}

    tok.resume()
    assert tok.state == lc.RESUMED and not tok.suspend_requested
    tok.check()                                    # resumed: clean again

    assert tok.cancel("user-request") is True
    assert tok.cancel("too-late") is False         # idempotent
    with pytest.raises(lc.QueryCancelledError) as ei:
        tok.check()
    assert ei.value.reason == "user-request"       # first reason wins
    assert tok.request_suspend() is False          # cancelled is terminal

    assert [t["state"] for t in tok.transitions] == [
        lc.RUNNING, lc.SUSPEND_REQUESTED, lc.SUSPENDED, lc.RESUMED,
        lc.CANCELLED]


def test_cancel_inject_fails_query_and_plan_cache_survives():
    """A deterministic mid-execution cancel (chaos point cancel.inject)
    raises the typed QueryCancelledError, unregisters the query, records
    the transition for post-mortems — and the plan cache still serves
    the identical query correctly afterwards. Runs under
    bufferLedger=enforce, so a leaked buffer on the cancel unwind path
    would raise instead of passing."""
    import pytest
    from spark_rapids_tpu.analysis import faults
    from spark_rapids_tpu.exec import lifecycle as lc

    s = _session(**{
        "spark.rapids.tpu.sql.shuffle.partitions": "4",
        "spark.rapids.tpu.sql.analysis.bufferLedger": "enforce"})
    ks = [i % 7 for i in range(300)]
    vs = [float(i % 13) for i in range(300)]
    s.createDataFrame({"k": ks, "v": vs}).createOrReplaceTempView("lct")
    sql = "SELECT k, sum(v) AS sv FROM lct GROUP BY k ORDER BY k"
    oracle = s.sql(sql).collect()

    faults.install("cancel.inject")
    try:
        with pytest.raises(lc.QueryCancelledError) as ei:
            s.sql(sql).collect()
    finally:
        faults.reset()
    assert ei.value.reason == "cancel.inject"
    qid = ei.value.query_id
    assert qid and qid not in lc.live_queries()    # unregistered
    states = [t["state"] for t in lc.transitions_for(qid)]
    assert lc.CANCELLED in states                  # retired log kept

    # the plan is not poisoned: the same text serves again, correctly
    assert s.sql(sql).collect() == oracle


def test_deadline_lapse_cancels_mid_execution():
    """Satellite 1: a lapsed deadline now fires DURING execution through
    the cooperative poll (reason "deadline"), not only while queued."""
    import time as _time

    import pytest
    from spark_rapids_tpu.exec import lifecycle as lc
    from spark_rapids_tpu.exec import query_context as qc

    s = _session(**{"spark.rapids.tpu.sql.shuffle.partitions": "4"})
    ks = [i % 5 for i in range(200)]
    s.createDataFrame({"k": ks, "v": [float(i) for i in range(200)]}) \
        .createOrReplaceTempView("ddt")
    with qc.deadline_scope(_time.perf_counter() - 0.001):   # lapsed
        with pytest.raises(lc.QueryCancelledError) as ei:
            s.sql("SELECT k, sum(v) AS sv FROM ddt GROUP BY k").collect()
    assert ei.value.reason == "deadline"


def test_preempt_inject_parks_and_resume_is_oracle_identical():
    """Satellite 4 core: a deterministic suspension (preempt.inject)
    mid-execution parks the ticket WITHOUT failing it; the service
    counts the preemption, resume() re-admits it through the scheduler,
    and the result is oracle-identical — under bufferLedger=enforce +
    lockdep=enforce."""
    import time as _time

    from spark_rapids_tpu.analysis import faults
    from spark_rapids_tpu.service.server import QueryService, TenantSpec

    s = _session(**{
        "spark.rapids.tpu.sql.shuffle.partitions": "4",
        "spark.rapids.tpu.sql.analysis.lockdep": "enforce",
        "spark.rapids.tpu.sql.analysis.bufferLedger": "enforce"})
    ks = [i % 7 for i in range(300)]
    vs = [float(i % 11) for i in range(300)]
    s.createDataFrame({"k": ks, "v": vs}).createOrReplaceTempView("ppt")
    sql = "SELECT k, sum(v) AS sv FROM ppt GROUP BY k ORDER BY k"
    oracle = s.sql(sql).collect()

    svc = QueryService(s, max_workers=1,
                       tenants=[TenantSpec("t", priority=1)])
    faults.install("preempt.inject")
    try:
        ticket = svc.submit("t", sql, label="preempt-me")
        deadline = _time.time() + 20
        while _time.time() < deadline and not svc.suspended_queries():
            _time.sleep(0.01)
        parked = svc.suspended_queries()
        assert parked, "query never parked on the injected suspension"
        assert svc.stats()["tenants"]["t"]["preempted"] == 1
        assert not ticket.done()

        resumed = svc.resume(parked[0])
        assert resumed is ticket
        rows = ticket.result(timeout=120).rows()
        assert rows == oracle
        st = svc.stats()["tenants"]["t"]
        assert st["resumed"] == 1 and st["completed"] == 1
        assert svc.suspended_queries() == []
    finally:
        faults.reset()
        svc.close()


def test_wfq_weighted_share_and_no_starvation():
    """Weighted-fair scheduling: with equal priorities and one worker
    slot, a weight-4 tenant is served ~4x as often as a weight-1 tenant
    early on, and the light tenant is never starved."""
    import threading
    import time as _time

    from spark_rapids_tpu.service.server import QueryService, TenantSpec

    s = _session(**{
        "spark.rapids.tpu.sql.service.scheduler.policy": "wfq"})
    svc = QueryService(s, max_workers=1, tenants=[
        TenantSpec("blk", priority=0, slots=1),
        TenantSpec("a", priority=0, slots=1, weight=4.0),
        TenantSpec("b", priority=0, slots=1, weight=1.0)])
    order = []
    mu = threading.Lock()
    gate = threading.Event()

    def mk(name):
        def run():
            with mu:
                order.append(name)
            return name
        return run

    try:
        blocker = svc.submit("blk", lambda: gate.wait(30))
        deadline = _time.time() + 10
        while _time.time() < deadline and svc.stats()["running"] < 1:
            _time.sleep(0.005)
        tickets = []
        for _ in range(5):               # interleaved arrivals
            tickets.append(svc.submit("a", mk("a")))
            tickets.append(svc.submit("b", mk("b")))
        gate.set()
        for t in tickets:
            t.result(timeout=60)
        blocker.result(timeout=60)
        # weight 4 vs 1: the heavy tenant dominates the early pops...
        assert order[:6].count("a") >= 4, order
        # ...but the light tenant still gets its full share served
        assert order.count("a") == 5 and order.count("b") == 5, order
        stats = svc.stats()
        assert stats["policy"] == "wfq"
        # normalized service: a's 5 pops at cost/4 vs b's 5 at cost/1
        assert stats["tenants"]["a"]["serviceUnits"] < \
            stats["tenants"]["b"]["serviceUnits"]
    finally:
        gate.set()
        svc.close()


_CANCEL_WORKER = """
import sys, json, os
sys.path.insert(0, {repo!r})
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
from spark_rapids_tpu.shuffle.manager import init_worker

wid = int(sys.argv[1]); n = int(sys.argv[2])
ctx = init_worker(wid, n)
print(json.dumps({{"port": ctx.port}}), flush=True)
peers = json.loads(sys.stdin.readline())
ctx.set_peers({{int(k): tuple(v) for k, v in peers.items()}})

from spark_rapids_tpu.api.session import TpuSession

s = TpuSession.builder.config({{
    "spark.rapids.tpu.sql.explain": "NONE",
    "spark.rapids.tpu.sql.shuffle.partitions": "4",
    "spark.rapids.tpu.sql.recovery.retryBackoff": "0.0",
}}).getOrCreate()

base = wid * 1000
ks = [(base + i) % 7 for i in range(200)]
vs = [float(i % 13) for i in range(200)]
s.createDataFrame({{"k": ks, "v": vs}}).createOrReplaceTempView("t")

if wid == 0:
    # worker 0's query cancels at its FIRST poll; worker 1 only learns
    # about it from the cancelled stamp on worker 0's META reply
    from spark_rapids_tpu.analysis import faults
    faults.install("cancel.inject")

err = None
try:
    s.sql("SELECT k, sum(v) AS sv FROM t GROUP BY k").collect()
except Exception as e:
    err = [type(e).__name__, str(e)]

from spark_rapids_tpu.exec.spill import BufferCatalog
cat = BufferCatalog.peek()
dev = sum((cat.tenant_device_bytes() or {{}}).values()) if cat else 0
print(json.dumps({{"err": err, "tenantDeviceBytes": dev}}), flush=True)
sys.stdin.readline()     # stay alive to serve the peer's META polls
ctx.shutdown()
"""


def test_two_process_cancel_propagates_over_meta(tmp_path):
    """Distributed cancellation: worker 0 cancels locally
    (cancel.inject); worker 1, blocked fetching worker 0's outputs,
    sees the cancelled stamp on the META reply and cancels its OWN
    token — both workers fail with the typed QueryCancelledError, no
    fetch-timeout wedge, and tenant device bytes are zero on both."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    procs = []
    for wid in range(2):
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _CANCEL_WORKER.format(repo=_REPO),
             str(wid), "2"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, env=env, text=True))
    results = {}
    try:
        ports = {}
        for wid, p in enumerate(procs):
            line = readline_bounded(p)
            assert line, p.stderr.read()
            ports[wid] = ("127.0.0.1", json.loads(line)["port"])
        peers = json.dumps({str(w): list(a) for w, a in ports.items()})
        for p in procs:
            p.stdin.write(peers + "\n")
            p.stdin.flush()
        for wid, p in enumerate(procs):
            line = readline_bounded(p)
            assert line, p.stderr.read()
            results[wid] = json.loads(line)
        for p in procs:            # release the stay-alive gate
            p.stdin.write("done\n")
            p.stdin.flush()
        for p in procs:
            p.communicate(timeout=60)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()

    assert results[0]["err"] is not None, results
    assert results[0]["err"][0] == "QueryCancelledError", results[0]
    assert "cancel.inject" in results[0]["err"][1]
    assert results[1]["err"] is not None, results
    assert results[1]["err"][0] == "QueryCancelledError", results[1]
    assert "peer-cancelled" in results[1]["err"][1], results[1]
    for wid in (0, 1):
        assert results[wid]["tenantDeviceBytes"] == 0


def test_query_log_records_lifecycle_transitions(tmp_path):
    """Satellite 5: a suspended-then-resumed query's log record carries
    the full transition list in the ``lifecycle`` field; a plain query's
    record omits the field entirely."""
    import time as _time

    from spark_rapids_tpu.analysis import faults
    from spark_rapids_tpu.service.server import QueryService, TenantSpec

    log_dir = str(tmp_path / "qlog")
    s = _session(**{
        "spark.rapids.tpu.sql.shuffle.partitions": "4",
        "spark.rapids.tpu.sql.telemetry.queryLog.dir": log_dir})
    s.createDataFrame({"k": [i % 3 for i in range(60)],
                       "v": [float(i) for i in range(60)]}) \
        .createOrReplaceTempView("qlt")
    sql = "SELECT k, sum(v) AS sv FROM qlt GROUP BY k ORDER BY k"
    s.sql(sql).collect()                       # plain: no lifecycle field

    svc = QueryService(s, max_workers=1,
                       tenants=[TenantSpec("t", priority=1)])
    faults.install("preempt.inject")
    try:
        ticket = svc.submit("t", sql)
        deadline = _time.time() + 20
        while _time.time() < deadline and not svc.suspended_queries():
            _time.sleep(0.01)
        assert svc.suspended_queries()
        svc.resume(svc.suspended_queries()[0])
        ticket.result(timeout=120)
    finally:
        faults.reset()
        svc.close()

    recs = []
    for name in os.listdir(log_dir):
        with open(os.path.join(log_dir, name)) as f:
            recs.extend(json.loads(l) for l in f if l.strip())
    cycled = [r for r in recs if r.get("lifecycle")]
    assert cycled, recs
    states = [t["state"] for t in cycled[0]["lifecycle"]]
    assert states[0] == "running"
    assert "suspended" in states and "resumed" in states
    plain = [r for r in recs if not r.get("lifecycle")]
    assert plain                               # the direct collect
