"""The host ledger, ``session.last_query_metrics()["host"]``: the caller's
call from ``session.sql`` to the result's ``fetch_to_host``, in parts that
tile it; the span report that tiles the recorder's wall; and under
``tracing.enabled`` the per-batch host sites (``exec/tracing.HOST_SITES``).

On the CPU: a Q6-shaped query (a fused filter-sum without keys) over eight
scan batches and over one, and a rehearsal of each of the four benchmark
cells' SQL at a small scale, as ``tests/test_coalesce_bypass_cells.py``
builds them. Counts and host-clock identities only: nothing here is a
device number. docs/observability.md §9 is the prose."""

import glob
import os
import re
import statistics
import sys

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.exec import tracing
from spark_rapids_tpu.exec.tracing import (HOST_SITES, SpanRecorder,
                                           host_site, trace_span)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS = 4096                # reader.batchSizeRows: one scan batch
TRACING = "spark.rapids.tpu.sql.tracing.enabled"
#: every key of the ledger
HOST_KEYS = {"callS", "parseS", "parseCacheHit", "planS", "dispatchS",
             "dispatches", "syncWaitS", "operatorS", "fetchS", "offThreadS",
             "unaccountedS", "sites"}
#: its parts, which with ``unaccountedS`` make ``callS`` (``offThreadS``,
#: their overlap on pool threads, taken out once)
PARTS = ("parseS", "planS", "dispatchS", "syncWaitS", "operatorS", "fetchS")
RESERVED = ("wallS", "concurrency", "semaphoreHoldS")


def _session(traced, **conf):
    s = TpuSession.builder.config(
        {"spark.rapids.tpu.sql.explain": "NONE",
         TRACING: "true" if traced else "false", **conf}).getOrCreate()
    tracing.reset_cache()
    return s


@pytest.fixture(autouse=True)
def _tracing_off_afterwards():
    yield
    _session(False)


def _frame(batches, seed=5):
    n = batches * ROWS - 1000
    rng = np.random.default_rng(seed)
    return pd.DataFrame({
        "d": rng.integers(8000, 10000, n).astype("int32"),
        "disc": np.round(rng.integers(0, 11, n) * 0.01, 2),
        "q": rng.integers(1, 51, n).astype("float64"),
        "v": rng.random(n) * 1e5})


def _q6(i):
    lo = 8000 + (i * 37) % 1500
    return (f"SELECT sum(v * disc) AS revenue FROM t WHERE d >= {lo} "
            f"AND d < {lo + 365} AND disc BETWEEN 0.05 AND 0.07 "
            f"AND q < {24 + i % 2}")


def _call(session, text):
    """The caller's call, as ``perfbench/run.py:execute`` makes it."""
    return session.sql(text).collect_batch().fetch_to_host().rows()


def _q6_runs(batches, traced, warm=3, runs=5):
    """``last_query_metrics()`` of ``runs`` warm Q6-shaped queries, each
    with new literals (new text: a parse-cache miss), over ``batches`` scan
    batches. Counts are equal in all of them; what is a reading of the
    host's clock is judged by their MEDIAN (the suite's workers share their
    machine: one query of 4 ms can lose a millisecond to another tenant)."""
    s = _session(traced, **{
        "spark.rapids.tpu.sql.reader.batchSizeRows": str(ROWS)})
    s.createDataFrame(_frame(batches)).createOrReplaceTempView("t")
    out = []
    for i in range(warm + runs):
        _call(s, _q6(i + 100 * batches + (50 if traced else 0)))
        if i >= warm:
            out.append(s.last_query_metrics())
    return s, out


def _q6_metrics(batches, traced, warm=3):
    s, runs = _q6_runs(batches, traced, warm, runs=1)
    return s, runs[0]


def _median(values):
    return statistics.median(values)


def _check_ledger(m):
    """What holds of every query's ledger, on one thread or several;
    returns ``unaccountedS / callS``, a reading of the clock."""
    host = m["host"]
    assert set(host) == HOST_KEYS
    for k in HOST_KEYS - {"sites", "unaccountedS"}:
        assert host[k] >= 0, k
    # the parts tile the call: they never pass it, and what no part names
    # is ``unaccountedS``, to the rounding of eleven six-digit numbers
    named = sum(host[k] for k in PARTS) - host["offThreadS"]
    assert host["callS"] >= named - 2e-5
    assert host["unaccountedS"] == pytest.approx(host["callS"] - named,
                                                 abs=2e-5)
    assert host["unaccountedS"] >= -2e-5
    # the dispatches are the ``programs`` map's, and so are their seconds
    assert host["dispatches"] == sum(
        p["dispatches"] for p in m["programs"].values())
    assert host["dispatchS"] == pytest.approx(
        sum(p["dispatchS"] for p in m["programs"].values()), abs=1e-5)
    assert host["syncWaitS"] == m["sync"]["syncWaitS"]
    assert set(host["sites"]) <= set(HOST_SITES)
    assert host["callS"] >= m["spans"]["wallS"] - 2e-4
    return host["unaccountedS"] / host["callS"]


#: site -> passes a query of ``b`` batches makes through it, a function of
#: the plan alone (scan batches, the update / merge / final programs, the
#: concat of the partials): pinned from the sandbox's readings
SITE_COUNTS = {
    8: {"admission": 20, "conf_read": 12, "count_arg": 18, "flat_args": 40,
        "fusable": 10, "param_args": 8, "program_key": 31, "program_lookup": 11,
        "shrink": 19, "spillable": 30, "window": 8},
    1: {"admission": 5, "conf_read": 4, "count_arg": 2, "flat_args": 7,
        "fusable": 2, "param_args": 1, "program_key": 6, "program_lookup": 2,
        "shrink": 3, "spillable": 7, "window": 1},
}
#: spans a warm Q6-shaped query opens: the parent's 27 / 13 less its
#: ``semaphore_hold`` (a scalar now), plus ``parse``, ``drain``, ``query_end``
SPAN_COUNT = {8: 29, 1: 15}
PROGRAMS = {
    8: {"agg/final/complete/final": 1,
        "agg/merge/complete/pre_stage/reduce": 1,
        "agg/update/complete/pre_stage/reduce": 8, "concat": 1},
    1: {"agg/final/complete/final": 1,
        "agg/update/complete/pre_stage/reduce": 1},
}


def _dispatches(m):
    return {k: p["dispatches"] for k, p in m["programs"].items()
            if p["dispatches"]}


def _span_count(spans):
    return sum(v["count"] for k, v in spans.items() if k not in RESERVED)


@pytest.mark.parametrize("batches", [8, 1])
def test_traced_query_has_a_ledger_that_tiles_and_exact_site_counts(batches):
    _s, runs = _q6_runs(batches, traced=True)
    # the CPU's loose form of the chip's criterion (0.10 there)
    assert _median(_check_ledger(m) for m in runs) <= 0.25
    m = runs[-1]
    host = m["host"]
    assert host["parseCacheHit"] == 0 and host["parseS"] > 0
    assert host["planS"] > 0 and host["operatorS"] > 0
    assert host["dispatchS"] > 0 and host["offThreadS"] <= 1e-5
    assert _dispatches(m) == PROGRAMS[batches]
    assert {k: v["count"] for k, v in host["sites"].items()} == \
        SITE_COUNTS[batches]
    # a site's seconds are its own: together with the program calls they
    # stay inside the execution's host time
    site_s = sum(v["s"] for v in host["sites"].values())
    assert 0 < site_s <= host["operatorS"] + 1e-5
    # ... and are divided among the spans they were passed in: the
    # dispatch path's lie inside ``aggregate`` (and the partials' concat)
    for name, v in host["sites"].items():
        assert sum(v["bySpan"].values()) == pytest.approx(v["s"], abs=1e-5)
    for name in ("window", "param_args", "program_lookup"):
        assert set(host["sites"][name]["bySpan"]) <= {"aggregate", "concat"}
    in_agg = sum(v["bySpan"].get("aggregate", 0.0)
                 for v in host["sites"].values())
    agg_calls = sum(p["dispatchS"] for f, p in m["programs"].items()
                    if f.startswith("agg/"))
    assert in_agg + agg_calls <= m["spans"]["aggregate"]["selfS"] + 2e-4
    # per batch: the update program's count and keys, eight times
    if batches == 8:
        one = SITE_COUNTS[1]
        assert SITE_COUNTS[8]["window"] == 8 * one["window"]
        assert SITE_COUNTS[8]["param_args"] == 8 * one["param_args"]


@pytest.mark.parametrize("batches", [8, 1])
def test_untraced_query_has_no_sites_and_the_pinned_spans(batches):
    _s, runs = _q6_runs(batches, traced=False)
    assert _median(_check_ledger(m) for m in runs) <= 0.25
    m = runs[-1]
    assert m["host"]["sites"] == {}
    assert _span_count(m["spans"]) == SPAN_COUNT[batches]
    assert _dispatches(m) == PROGRAMS[batches]
    assert m["sync"]["hostSyncs"] == 0
    # ... and the traced run dispatches and syncs the same
    _s, traced = _q6_metrics(batches, traced=True)
    assert _dispatches(traced) == _dispatches(m)
    assert traced["sync"]["hostSyncs"] == m["sync"]["hostSyncs"]
    assert _span_count(traced["spans"]) == SPAN_COUNT[batches]


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("batches", [8, 1])
def test_one_thread_span_report_tiles_its_wall(batches, traced):
    _s, runs = _q6_runs(batches, traced)
    assert abs(_median(m["spans"]["concurrency"] for m in runs) - 1.0) \
        <= 0.05, [m["spans"]["concurrency"] for m in runs]
    # the root keeps only what is the root's
    assert _median(m["spans"]["query"]["selfS"] / m["spans"]["wallS"]
                   for m in runs) <= 0.25
    m = runs[-1]
    spans = m["spans"]
    assert "semaphore_hold" not in spans
    assert 0 < spans["semaphoreHoldS"] <= spans["wallS"] + 1e-4
    for name in ("parse", "drain", "query_end", "fetch_to_host"):
        assert spans[name]["count"] == 1, name
    # a resident table: the cache serves, nothing is uploaded, and no span
    # says otherwise
    assert m["scan"]["uploadedBatches"] == 0
    assert "scan_upload" not in spans
    assert spans["scan_cached"]["count"] == m["scan"]["batches"] == batches


def test_scan_upload_spans_are_the_uploads():
    s = _session(False, **{
        "spark.rapids.tpu.sql.reader.batchSizeRows": str(ROWS)})
    s.createDataFrame(_frame(3, seed=11)).createOrReplaceTempView("t")
    _call(s, _q6(1))
    m = s.last_query_metrics()
    assert m["scan"]["uploadedBatches"] == 3
    assert m["spans"]["scan_upload"]["count"] == 3
    assert "scan_cached" not in m["spans"]
    _call(s, _q6(2))
    m = s.last_query_metrics()
    assert m["scan"]["uploadedBatches"] == 0
    assert "scan_upload" not in m["spans"]
    assert m["spans"]["scan_cached"]["count"] == 3


def test_same_text_again_hits_the_parse_cache():
    s, first = _q6_metrics(1, traced=False, warm=0)
    text = _q6(777)
    _call(s, text)
    miss = s.last_query_metrics()["host"]
    hits = []
    for _ in range(3):
        _call(s, text)
        hits.append(s.last_query_metrics()["host"])
    assert first["host"]["parseCacheHit"] == 0
    assert miss["parseCacheHit"] == 0
    assert [h["parseCacheHit"] for h in hits] == [1, 1, 1]
    assert min(h["parseS"] for h in hits) < miss["parseS"]
    assert s.serving_stats()["parseCacheHits"] >= 3


def test_a_frame_not_from_sql_starts_at_the_action():
    s = _session(False)
    df = s.createDataFrame(pd.DataFrame({"k": [1, 2, 1] * 50,
                                         "v": [1.0, 2.0, 3.0] * 50}))
    df.groupBy("k").count().collect()
    m = s.last_query_metrics()
    _check_ledger(m)
    assert m["host"]["parseS"] == 0 and m["host"]["parseCacheHit"] == 0
    assert "parse" not in m["spans"]
    # the frame of sql() hands its parse to its FIRST action only
    s.createDataFrame(pd.DataFrame({"x": [1, 2, 3]})) \
        .createOrReplaceTempView("few")
    frame = s.sql("SELECT sum(x) AS s FROM few")
    frame.collect()
    assert s.last_query_metrics()["spans"]["parse"]["count"] == 1
    frame.collect()
    assert "parse" not in s.last_query_metrics()["spans"]


def test_pool_threads_leave_the_ledger_whole():
    """Several partitions drain on the task pool: their program calls and
    readbacks are the query's (``dispatchS``, ``syncWaitS``) without being
    the driving thread's, and ``offThreadS`` says how much."""
    s = _session(False)
    rng = np.random.default_rng(3)
    pdf = pd.DataFrame({"k": rng.integers(0, 9, 20000),
                        "v": rng.random(20000)})
    out = (s.createDataFrame(pdf).repartition(4, "k").groupBy("k").count()
           .collect())
    assert len(out) == 9
    m = s.last_query_metrics()
    _check_ledger(m)
    assert m["host"]["dispatches"] > 0


def test_explain_analyze_prints_the_ledger():
    s, m = _q6_metrics(1, traced=False, warm=0)
    line = next(l for l in s.explain_analyze().splitlines()
                if l.startswith("query:"))
    for key in sorted(HOST_KEYS - {"sites"}):
        assert f" {key}=" in line, key
    assert f" dispatches={m['host']['dispatches']} " in line


# -- the recorder's own rules ------------------------------------------------

def test_add_charges_the_enclosing_frame():
    rec = SpanRecorder()
    with rec:
        with trace_span("outer"):
            rec.add("waited", 5.0)      # an interval that has just ended
    rep = rec.report()
    assert rep["waited"] == {"selfS": 5.0, "count": 1}
    assert rep["outer"]["selfS"] < 0.1          # not counted twice


def test_note_inner_leaves_the_hosts_own_time():
    rec = SpanRecorder()
    with rec:
        with trace_span("query"):
            with trace_span("aggregate"):
                rec.note_inner(0.25)
    host = rec.host_ledger(
        {"f": {"dispatches": 1, "dispatchS": 0.25}}, 0.0)
    assert host["dispatches"] == 1 and host["dispatchS"] == 0.25
    assert host["operatorS"] < 0.01 and host["offThreadS"] == 0
    assert host["unaccountedS"] < 0.01


def test_host_site_off_is_inert_and_names_are_a_vocabulary():
    _session(False)
    rec = SpanRecorder()
    with rec:
        with host_site("count_arg"):
            pass

        @host_site("shrink")
        def f(x):
            return x + 1
        assert f(1) == 2
    assert rec.host_ledger({}, 0.0)["sites"] == {}
    assert not getattr(tracing._site_tls, "stack", None)
    with pytest.raises(AssertionError):
        host_site("not_a_site")


def test_host_site_on_counts_self_seconds():
    import time
    _session(True)
    rec = SpanRecorder()
    with rec:
        t0 = time.perf_counter()
        with host_site("window"):
            time.sleep(0.02)
            with host_site("shrink"):
                time.sleep(0.03)
                rec.note_inner(0.01)        # a readback wait inside it
        wall = time.perf_counter() - t0
    sites = rec.host_ledger({}, 0.0)["sites"]
    assert sites["window"]["count"] == sites["shrink"]["count"] == 1
    # each its own seconds: the nested site and the wait are taken out
    assert sites["window"]["s"] >= 0.019 and sites["shrink"]["s"] >= 0.019
    assert sites["window"]["s"] + sites["shrink"]["s"] + 0.01 == \
        pytest.approx(wall, abs=2e-3)
    assert sites["shrink"]["bySpan"] == {"<no-span>": sites["shrink"]["s"]}


# -- the profile: spans, programs and sites on one clock ---------------------

def _profile_of_one_query(tmp_path):
    import jax
    from jax.profiler import ProfileOptions
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from perfbench import trace_reduce
    s, _m = _q6_metrics(8, traced=True)
    options = ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _call(s, _q6(4242))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    planes = trace_reduce.load_xplane(path)
    return [e for p in planes if p["name"] == trace_reduce.HOST_PLANE
            for line in p["lines"] for e in line["events"]]


def test_profile_holds_parse_query_programs_and_sites(tmp_path):
    events = _profile_of_one_query(tmp_path)

    def named(name):
        return [(b, b + d) for n, b, d in events if n == name]

    def site(name):
        return named("site:" + name)

    def inside(iv, outers):
        return any(a <= iv[0] and iv[1] <= b for a, b in outers)

    (parse,), (query,) = named("parse"), named("query")
    assert parse[1] <= query[0]
    operator = [iv for n in ("aggregate", "concat", "scan_cached",
                             "collect_concat") for iv in named(n)]
    programs = [(n, (b, b + d)) for n, b, d in events
                if n.startswith("program:")]
    assert len(programs) == 11
    for name, iv in programs:
        assert inside(iv, operator), name
        assert inside(iv, [query])
    aggregate = named("aggregate")
    assert len(aggregate) == 10
    for name in HOST_SITES:
        ivs = site(name)
        assert ivs, name
        assert any(inside(iv, aggregate) for iv in ivs), name
        # none lies outside the query's spans (the caller's fetch flattens
        # the result after the root closed)
        assert all(inside(iv, [query] + named("fetch_to_host"))
                   for iv in ivs), name
    # every pass through a per-batch site of the dispatch path is inside
    # ``aggregate`` (or the concat of the partials)
    for name in ("count_arg", "program_lookup", "param_args", "window"):
        assert all(inside(iv, aggregate + named("concat"))
                   for iv in site(name)), name


# -- the four benchmark cells, rehearsed -------------------------------------

#: cell -> (rows_scale, reader.batchSizeRows or None): tpch_sf10.q6 in eight
#: scan batches, the others in one
CELLS = {"tpch_sf1.q1": (0.002, None), "tpch_sf1.q6": (0.002, None),
         "tpch_sf10.q6": (0.0005, 4096), "tpch_sf1_mesh4.q3": (0.01, None)}
SEED = 2147483777
MESH_WORKERS = 2


def _rehearse(cell, executions=3):
    """``last_query_metrics()`` of ``executions`` traced executions of the
    cell's SQL after its warm-up (tests/test_coalesce_bypass_cells.py)."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from perfbench import run
    from spark_rapids_tpu.parallel import mesh as M
    scale, batch_rows = CELLS[cell]
    workload = run.load_json("workloads", cell + ".json")
    config = run.load_json("configs", workload["config"] + ".json")
    conf = dict(config["conf"])
    conf[TRACING] = "true"
    if batch_rows:
        conf["spark.rapids.tpu.sql.reader.batchSizeRows"] = str(batch_rows)
    env = {run.conf_env(k): str(v) for k, v in conf.items()}
    make_mesh = M.make_mesh
    if config["chips"] > 1:
        env[run.conf_env(
            "spark.rapids.tpu.sql.autoBroadcastJoinThreshold")] = "-1"
        M.make_mesh = lambda n=None: make_mesh(n or MESH_WORKERS)
    before = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        session = TpuSession.builder.config(conf).getOrCreate()
        tracing.reset_cache()
        traffic = run.Traffic(workload, SEED)
        tables, _ = run.make_tables(config, traffic.query.TABLES, SEED, scale)
        for name, cols in tables.items():
            session.createDataFrame(run.to_arrow(cols)) \
                .createOrReplaceTempView(name)
        out = []
        for i in range(int(workload["warmup_executions"]) + executions):
            _params, text = traffic.next()
            run.execute(session, text)
            if i >= int(workload["warmup_executions"]):
                out.append(session.last_query_metrics())
        for view in tables:
            session.createDataFrame({"x": [0]}).createOrReplaceTempView(view)
            session.sql(f"SELECT count(*) FROM {view}").collect()
        return out
    finally:
        M.make_mesh = make_mesh
        for k, v in before.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_rehearsal_has_a_ledger_that_tiles(cell):
    runs = _rehearse(cell)
    assert _median(_check_ledger(m) for m in runs) <= 0.25
    for m in runs:
        host = m["host"]
        assert host["parseS"] > 0 and host["planS"] > 0
        assert host["dispatches"] > 0 and host["sites"]
        if cell.endswith(".q6"):
            # new literals, new text: never a parse-cache hit; one thread
            assert host["parseCacheHit"] == 0
            assert abs(m["spans"]["concurrency"] - 1.0) <= 0.1
            assert m["scan"]["batches"] == \
                (8 if cell == "tpch_sf10.q6" else 1)
            assert host["sites"]["count_arg"]["count"] == \
                SITE_COUNTS[m["scan"]["batches"]]["count_arg"]


# -- the vocabulary of span names has its readers ----------------------------

_SPAN_CALL = re.compile(
    r'(?:trace_span|record_span|resumed|_step)\(\s*(f?)"([^"]+)"')


def _span_names_in_the_package():
    names = set()
    for path in glob.glob(os.path.join(ROOT, "spark_rapids_tpu", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            for is_f, name in _SPAN_CALL.findall(f.read()):
                # an f-string's braces are a family of names: ``op_<Exec>``
                names.add(re.sub(r"\{[^}]*\}", "<>", name) if is_f else name)
    return names


def _span_table():
    with open(os.path.join(ROOT, "docs", "observability.md")) as f:
        text = f.read()
    section = text[text.index("### Span names and their readers"):]
    rows = [l for l in section.splitlines() if l.startswith("| `")]
    return {re.sub(r"<[^>]*>", "<>", name) for l in rows
            for name in re.findall(r"`([^`]+)`", l.split("|")[1])}


def test_every_span_name_in_the_package_has_a_reader_in_the_docs():
    names, table = _span_names_in_the_package(), _span_table()
    assert {"query", "parse", "plan", "drain", "query_end", "aggregate",
            "scan_cached", "scan_upload", "fetch_to_host", "mesh_spmd",
            "op_<>", "fused_<>"} <= names
    assert names - table == set()
    assert table - names == set()       # and the table names no dead span
    assert "semaphore_hold" not in names


def test_docs_name_every_key_and_site():
    with open(os.path.join(ROOT, "docs", "observability.md")) as f:
        text = f.read()
    assert "`semaphoreHoldS`" in text
    section = text[text.index("### The host ledger"):]
    for key in sorted(HOST_KEYS | {"dispatchS"}):
        assert f"`{key}`" in section, key
    for site in HOST_SITES:
        assert f"`{site}`" in section, site


def test_the_tool_reads_a_cells_ledger_from_last_query_metrics():
    """``tools/host_ledger.py`` on the CPU rehearsal of ``tpch_sf1.q6``:
    the program's ``callS`` beside the harness's own latency of the same
    traced queries."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from tools import host_ledger
    summary, full = host_ledger.ledger_of_cell("tpch_sf1.q6", SEED,
                                               rows_scale=0.002)
    tracing.reset_cache()
    assert summary["correct"] and summary["queries"] == 5
    assert len(full["latencies_s"]) == len(full["query_metrics"]) == 5
    # the call the program accounts for is the call the harness times
    assert 0.9 <= summary["call_over_latency"] <= 1.0
    assert summary["unaccounted_share"] <= 0.25
    assert summary["dispatches"] == 2 and summary["parse_cache_hits"] == 0
    assert set(summary["sites"]) <= set(HOST_SITES)
    assert summary["aggregate"]["named_share"] > 0.5
    assert summary["host_ms"]["call"] == pytest.approx(
        sum(summary["host_ms"][k[:-1]] for k in PARTS + ("unaccountedS",))
        - summary["host_ms"]["offThread"], abs=0.01)
