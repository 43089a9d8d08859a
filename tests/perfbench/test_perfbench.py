"""The benchmark's own tests: its references against the engine, its
control and its broken paths against ``correct``, its traffic, bytes
counts, data files and trace reduction. Everything runs on the CPU at
SF0.002; nothing here is a device number."""

import glob
import io
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import compare, probes, run, trace_reduce  # noqa: E402
from perfbench.queries import days, q1, q6  # noqa: E402
from perfbench.tables import CURRENTDATE, lineitem  # noqa: E402

SCALE = 0.002
QUERIES = {"q6": q6, "q1": q1}
CELLS = ["tpch_sf1.q6", "tpch_sf1.q1"]
BENCH = run.load_benchmark()
CONFIG = run.load_json("configs", "tpch_sf1.json")
WORKLOADS = {c: run.load_json("workloads", c + ".json") for c in CELLS}
HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module", autouse=True)
def _leave_the_worker_as_found():
    """A rehearsal configures the process-global session and leaves its last
    tables registered (about 1 MB in the device scan cache). Later files on
    this worker get the default session back and the cache empty: a one-row
    table takes each view's place and one scan drains the evictions.
    ``run_cell`` itself removes its listeners, handler and environment."""
    yield
    from spark_rapids_tpu.api.session import TpuSession
    session = TpuSession.builder.config(
        {"spark.rapids.tpu.sql.explain": "NONE",
         run._TRACING_CONF: "false"}).getOrCreate()
    for view in ("lineitem",):
        session.createDataFrame({"x": [0]}).createOrReplaceTempView(view)
        session.sql(f"SELECT count(*) FROM {view}").collect()


def rehearse(cell, seed, trace=0, seconds=0.0):
    out, err = io.StringIO(), io.StringIO()
    result = run.run_cell(cell, seed, seconds, trace, rows_scale=SCALE,
                          out=out, err=err)
    return result, out.getvalue(), err.getvalue()


# -- the references against the engine, through the whole run ---------------

@pytest.mark.parametrize("cell", CELLS)
def test_reference_agrees_with_engine_for_three_draws(cell):
    """Two warm-up draws and one of the window, every answer compared: the
    rehearsal entry end to end, with the result line the contract names."""
    result, out, err = rehearse(cell, 2147483659)
    assert json.loads(out.strip().splitlines()[-1]) == result
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 1
    assert json.loads([l for l in err.splitlines() if "compared" in l][0])[
        "compared"] == WORKLOADS[cell]["warmup_executions"] + 1
    assert list(result)[-1] == "checks"
    assert result["checks"]["rows_wrong"] == {"value": 0, "limit": 0}
    assert result["checks"]["max_rel_gap"]["value"] < 1e-12
    assert err.strip().splitlines()[-1].startswith("check max_rel_gap:")
    wanted = {m["name"] for m in run.metrics_of_cell(BENCH["end_to_end"],
                                                     cell)}
    assert set(result["metrics"]) == wanted == {"query_s", "setup_s"}
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}


def test_rehearsal_leaves_no_listener_handler_or_conf_behind():
    """``run_cell`` takes its compile listeners, its log handler and the
    configuration's environment back out, also when the run fails."""
    import logging
    import jax._src.monitoring as monitoring

    def state():
        return (len(monitoring.get_event_listeners()),
                len(monitoring.get_event_duration_listeners()),
                len(logging.getLogger(probes.FUSION_LOGGER).handlers),
                sorted(k for k in os.environ if "RAPIDS_TPU_CONF" in k))
    before = state()
    rehearse("tpch_sf1.q6", 3, trace=1)
    assert state() == before
    with pytest.raises(FileNotFoundError):
        rehearse("tpch_sf1.no_such_cell", 3)
    assert state() == before


def test_traced_rehearsal_reports_the_program_side_metrics():
    """On the CPU no device plane exists: the trace readers find nothing and
    their metrics are left out, never reported as 0."""
    result, _, _ = rehearse("tpch_sf1.q1", 5, trace=1)
    assert result["correct"] is True
    assert result["attempted"] == WORKLOADS["tpch_sf1.q1"]["traced_queries"]
    assert set(result["metrics"]) == {"plan_ms", "scan_ms", "host_syncs",
                                      "compiles_per_query", "compile_ms"}
    assert result["metrics"]["host_syncs"]["value"] == 2
    assert "breakdown" not in result and "busy_s" not in result["device"]


# -- the control and the broken paths must come out as not correct ----------

def small_tables(names, seed):
    return run.make_tables(CONFIG, names, seed, SCALE)[0]


@pytest.mark.parametrize("name", QUERIES)
def test_control_in_the_precision_below_is_not_correct(name):
    """The reference in the precision below the one the cell's file states,
    put in the program's place, fails ``max_rel_gap`` (and only that) at the
    limit that file gives."""
    q = QUERIES[name]
    workload = WORKLOADS["tpch_sf1." + name]
    below = {"float32": np.float32}[workload["control_arithmetic"]]
    stated = {"float64": np.float64}[workload["sum_arithmetic"]]
    rng = np.random.default_rng(3)
    tables = small_tables(q.TABLES, 3)
    draws = [q.draw(rng) for _ in range(3)]
    refs = [q.reference(tables, p) for p in draws]
    control = [q.reference(tables, p, dtype=below) for p in draws]
    as_stated = [q.reference(tables, p, dtype=stated) for p in draws]
    assert compare.judge(as_stated, refs, workload["limits"])[0] is True
    correct, checks = compare.judge(control, refs, workload["limits"])
    assert correct is False
    assert checks["rows_wrong"]["value"] == 0
    assert checks["max_rel_gap"]["value"] > checks["max_rel_gap"]["limit"]


def _altered(execute):
    def altered(session, text):
        rows = execute(session, text)
        first = rows[0]
        i = max(j for j, v in enumerate(first) if isinstance(v, float))
        return [first[:i] + (first[i] * (1 + 1e-8),) + first[i + 1:]] \
            + rows[1:]
    return altered


def _half_of_lineitem(to_arrow):
    def half(cols):
        if "l_orderkey" in cols:
            cols = {k: v[: len(v) // 2] for k, v in cols.items()}
        return to_arrow(cols)
    return half


def _raising(execute):
    def raising(session, text):
        raise RuntimeError("the timed path is broken")
    return raising


@pytest.mark.parametrize("cell", ["tpch_sf1.q1", "tpch_sf1.q6"])
@pytest.mark.parametrize("fault,attr", [
    ("altered", "execute"), ("half", "to_arrow"), ("raising", "execute")])
def test_broken_timed_path_is_not_correct(monkeypatch, fault, attr, cell):
    """The rest of a run with the timed path broken underneath: an answer
    altered where it is produced (by 1e-8: float32 would do ten times
    that), half of the rows left out of what the engine is given, a query
    that never answers."""
    wrap = {"altered": _altered, "half": _half_of_lineitem,
            "raising": _raising}[fault]
    monkeypatch.setattr(run, attr, wrap(getattr(run, attr)))
    result, _, err = rehearse(cell, 11)
    assert result["correct"] is False
    limit = WORKLOADS[cell]["limits"]["max_rel_gap"]
    compared = WORKLOADS[cell]["warmup_executions"] + 1
    if fault == "raising":
        assert result["failed"] == result["attempted"] == 1
        assert result["checks"]["rows_wrong"]["value"] == compared
    elif fault == "half" and cell == "tpch_sf1.q1":
        assert result["checks"]["rows_wrong"]["value"] == compared  # counts
    else:
        assert result["checks"]["max_rel_gap"]["value"] > limit


def test_compare_is_exact_on_keys_and_relative_on_floats():
    ref = [(1, 8350, "A", 100.0), (2, 8351, "B", 0.05)]
    assert compare.compare_rows(list(ref), ref) == (False, 0.0)
    got = [(1, 8350, "A", 100.0), (2, 8351, "B", 0.05 * (1 + 1e-8))]
    wrong, gap = compare.compare_rows(got, ref)
    assert not wrong and gap == pytest.approx(1e-8, rel=1e-3)
    for bad in ([(1, 8350, "A", 100.0)],                       # a row short
                [(1, 8350, "A", 100.0), (3, 8351, "B", 0.05)],  # a key
                [(1, 8350, "A", 100.0), (2, 8351, "C", 0.05)],  # a string
                [(1, 8350, "A", 100.0), (2.0, 8351, "B", 0.05)],
                None):
        assert compare.compare_rows(bad, ref)[0] is True
    import datetime
    assert compare.compare_rows(
        [(datetime.date(1992, 11, 11),)], [(8350,)]) == (False, 0.0)
    assert compare.judge([], [], {"rows_wrong": 0, "max_rel_gap": 1})[0] is False


# -- traffic: spec ranges, and a function of the seed alone -----------------

@pytest.mark.parametrize("name", QUERIES)
def test_draws_stay_in_their_clause_and_follow_the_seed(name):
    q = QUERIES[name]
    a = [q.draw(np.random.default_rng(7)) for _ in range(1)]
    rng = np.random.default_rng(7)
    draws = [q.draw(rng) for _ in range(3000)]
    assert draws[0] == a[0]
    for p in draws:
        if name == "q6":
            assert 1993 <= p["year"] <= 1997 and p["quantity"] in (24, 25)
            assert 2 <= p["discount_pct"] <= 9
        else:
            assert 60 <= p["delta"] <= 120
    seen = {json.dumps(p, sort_keys=True) for p in draws}
    assert len(seen) == {"q6": 5 * 8 * 2, "q1": 61}[name]


@pytest.mark.parametrize("cell", CELLS)
def test_traffic_and_tables_are_functions_of_the_seed(cell):
    workload = run.load_json("workloads", cell + ".json")
    big = 3000000019                 # more than 32 signed bits hold
    first = [run.Traffic(workload, big).next() for _ in range(2)]
    assert first[0] == first[1]
    t1 = small_tables({"lineitem"}, big)["lineitem"]
    t2 = small_tables({"lineitem"}, big)["lineitem"]
    t3 = small_tables({"lineitem"}, big + 1)["lineitem"]
    assert all(np.asarray(t1[k] == t2[k]).all() for k in t1
               if isinstance(t1[k], np.ndarray))
    assert t1["l_comment"].equals(t2["l_comment"])
    assert not (t1["l_partkey"] == t3["l_partkey"]).all()


def test_lineitem_follows_the_specification():
    """Clause 4.2.3: sixteen columns; 1..7 lines to an order on sparse keys,
    the same sizes for every seed; dates from the order's; flag and status
    from the dates; price from quantity and the part's retail price; two
    exact decimals; text columns of the spec's widths."""
    rows = {t: int(n * SCALE) for t, n in CONFIG["rows"].items()}
    a, b = lineitem.generate(rows, 5), lineitem.generate(rows, 6)
    assert len(a) == 16 and all(len(v) == rows["lineitem"]
                                for t in (a, b) for v in t.values())
    keys, lines = np.unique(a["l_orderkey"], return_counts=True)
    assert len(keys) == rows["orders"] and (keys % 32 <= 8).all()
    assert 1 <= lines.min() and lines.max() <= 7
    assert sorted(lines) == sorted(
        np.unique(b["l_orderkey"], return_counts=True)[1])
    assert (a["l_linenumber"][np.r_[0, np.cumsum(lines)[:-1]]] == 1).all()
    assert a["l_linenumber"].max() == 7
    ship = a["l_shipdate"]
    assert ((a["l_receiptdate"] - ship >= 1)
            & (a["l_receiptdate"] - ship <= 30)).all()
    assert ((a["l_linestatus"] == "O") == (ship > CURRENTDATE)).all()
    late = a["l_receiptdate"] > CURRENTDATE
    assert (a["l_returnflag"][late] == "N").all()
    assert set(a["l_returnflag"][~late]) == {"R", "A"}
    cents = a["l_quantity"] * lineitem.retail_price_cents(a["l_partkey"])
    assert (a["l_extendedprice"] == cents / 100.0).all()
    assert a["l_extendedprice"].min() >= 900 and \
        a["l_extendedprice"].max() <= 104950
    assert set(np.round(a["l_discount"] * 100)) == set(range(11))
    assert (a["l_discount"] == np.array(
        [float(f"{d:.2f}") for d in a["l_discount"]])).all()
    assert set(a["l_shipmode"].to_pylist()) == set(lineitem.MODES)
    assert set(a["l_shipinstruct"].to_pylist()) == set(lineitem.INSTRUCTIONS)
    widths = [len(c) for c in a["l_comment"].to_pylist()]
    assert min(widths) >= 10 and max(widths) <= 43
    assert 1 <= a["l_suppkey"].min() and a["l_suppkey"].max() <= \
        rows["supplier"]


def test_sql_text_carries_the_drawn_literals():
    text = q6.sql({"year": 1994, "discount_pct": 6, "quantity": 24})
    assert "DATE '1994-01-01'" in text and "DATE '1995-01-01'" in text
    assert "BETWEEN 0.05 AND 0.07" in text and "l_quantity < 24" in text
    assert "DATE '1998-09-02'" in q1.sql({"delta": 90})
    assert days(1970, 1, 2) == 1


# -- bytes functions against hand counts ------------------------------------

@pytest.mark.parametrize("name,expected", [
    ("q6", 6_000_000 * (4 + 8 + 8 + 8)),
    ("q1", 6_000_000 * (1 + 1 + 8 + 8 + 8 + 8 + 4))])
def test_bytes_read_against_hand_counts(name, expected):
    q = QUERIES[name]
    assert q.bytes_read(CONFIG["rows"]) == expected
    text = q.sql(q.draw(np.random.default_rng(0)))
    named = [c for cols in q.COLUMNS.values() for c in cols]
    assert all(c in text for c in named)


# -- the command refuses anything but the TPU -------------------------------

def test_main_refuses_a_platform_that_is_not_tpu(capsys):
    rc = run.main(["--workload", "tpch_sf1.q6", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    printed = capsys.readouterr()
    assert rc == 1 and printed.out == "" and "TPU" in printed.err


# -- every data file loads by the name BENCHMARK.json gives -----------------

def test_benchmark_json_names_files_that_exist():
    assert BENCH["command"] == ["python3", "perfbench/run.py"]
    for c in BENCH["configs"]:
        on_disk = json.load(open(os.path.join(ROOT, c["file"])))
        assert on_disk["name"] == c["name"]
        assert on_disk["source"] == c["source"]
        assert on_disk["reduced"] == c["reduced"]
    assert {m["name"] for m in BENCH["end_to_end"]} >= {"setup_s", "query_s"}
    readers = {os.path.basename(f)[:-3] for f in glob.glob(
        os.path.join(ROOT, "perfbench", "readers", "*.py"))}
    named = {m["name"] for m in BENCH["per_layer"] + BENCH["end_to_end"]}
    assert named <= readers
    assert readers - named == {"__init__", "query_p95_s"}   # q6's, PERF.md


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_loads_by_name(cell):
    entry = [w for w in BENCH["workloads"] if w["name"] == cell][0]
    workload = run.load_json("workloads", cell + ".json")
    assert (workload["config"], workload["traffic"], workload["why"]) == (
        entry["config"], entry["traffic"], entry["why"])
    config = run.load_json("configs", workload["config"] + ".json")
    assert config["chips"] == entry["chips"]
    assert set(run.Traffic(workload, 1).query.TABLES) <= set(config["rows"])
    assert run.metrics_of_cell(BENCH["per_layer"], cell)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]]
                         + ["query_s", "query_p95_s"])
def test_metric_loads_by_name_and_reads_nothing_from_nothing(metric):
    reader = __import__(f"perfbench.readers.{metric}", fromlist=["read"])
    empty = {"queries": 0, "latencies_s": [], "window_s": 0.0,
             "setup_s": 1.0, "query_metrics": [], "trace": None,
             "compile": {"compiles": 0, "compile_s": 0.0},
             "bytes_per_query": 0, "peaks": None}
    assert reader.read(empty) is None


def test_readers_on_a_known_context():
    ctx = {"queries": 2, "bytes_per_query": 168e6, "window_s": 8.5,
           "setup_s": 30.0, "latencies_s": [4.0, 4.4],
           "peaks": run.load_json("peaks.json")["TPU v5 lite"],
           "compile": {"compiles": 3, "compile_s": 0.5},
           "trace": {"busy_s": 0.004, "window_s": 2.0},
           "query_metrics": [
               {"planTimeS": 0.001, "sync": {"hostSyncs": 2}, "operators": [
                   {"operator": "TpuLocalScanExec",
                    "metrics": {"scanTime": 0.002}},
                   {"operator": "TpuHashAggregateExec", "metrics": {}}]},
               {"planTimeS": 0.003, "sync": {"hostSyncs": 4}, "operators": [
                   {"operator": "TpuLocalScanExec",
                    "metrics": {"scanTime": 0.004}}]}]}

    def read(name):
        return __import__(f"perfbench.readers.{name}",
                          fromlist=["read"]).read(ctx)

    assert read("query_s") == 4.25 and read("setup_s") == 30.0
    assert read("query_p95_s") is None          # two queries have no tail
    ctx["latencies_s"] = [1.0] * 38 + [2.0, 3.0]
    assert read("query_p95_s") == pytest.approx(1.05)
    assert read("plan_ms") == pytest.approx(2.0)
    assert read("scan_ms") == pytest.approx(3.0)
    assert read("host_syncs") == 3
    assert read("compiles_per_query") == 1.5
    assert read("compile_ms") == pytest.approx(250.0)
    assert read("device_busy_ms") == pytest.approx(2.0)
    assert read("device_idle_pct") == pytest.approx(99.8)
    # 168 MB at 819 GB/s is 0.2051 ms of the 2 ms the device was busy
    assert read("query_hbm_roofline") == pytest.approx(10.256, rel=1e-3)


# -- the compile counter tells a load from a compilation --------------------

def test_compile_counter_separates_loads_from_compilations():
    c = probes.CompileCounter()
    c._on_duration(probes._BACKEND_COMPILE, 2.0)
    c._on_event(probes._CACHE_HIT)
    c._on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.1)
    c._on_duration(probes._BACKEND_COMPILE, 0.25)
    c._on_duration(probes._BACKEND_COMPILE, 1.0)
    assert c.snapshot() == {"compiles": 2, "compile_s": 3.0,
                            "loads": 1, "load_s": 0.25}


# -- the trace reduction on a small trace kept beside this file -------------

def hand_trace():
    """Window 1000..11000 ns. Device: an op that starts before the window,
    two that overlap each other, one that crosses the window's end."""
    return [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            [trace_reduce.WINDOW, 1000.0, 10000.0],
            ["perfbench_query", 1000.0, 10000.0],
            ["aggregate", 2000.0, 3500.0],
            ["collect_concat", 8500.0, 1000.0]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_f", 0.0, 20000.0]]},
            {"name": "XLA Ops", "events": [
                ["fusion.1", 500.0, 1500.0],       # 1000..2000 counts
                ["fusion.2", 6000.0, 1000.0],
                ["copy.3", 6500.0, 1500.0],        # union 6000..8000
                ["fusion.1", 10500.0, 2000.0]]}]},  # 10500..11000 counts
        {"name": "/device:TPU:0 extra", "lines": []}]


def test_trace_reduction_by_hand():
    r = trace_reduce.reduce_trace(hand_trace())
    assert r["window_s"] == pytest.approx(10000e-9)
    assert r["busy_s"] == pytest.approx(3500e-9)   # 1000 + 2000 + 500
    assert r["devices"] == 1
    assert r["device_ops"] == [["fusion.1", pytest.approx(1500e-9)],
                               ["copy.3", pytest.approx(1500e-9)],
                               ["fusion.2", pytest.approx(1000e-9)]] or \
        dict(map(tuple, r["device_ops"])) == pytest.approx(
            {"fusion.1": 1500e-9, "copy.3": 1500e-9, "fusion.2": 1000e-9})
    # gaps: 2000..6000 (aggregate covers 3500 of it, the innermost), and
    # 8000..10500 (collect_concat covers 1000: the query span covers all)
    assert dict(map(tuple, r["idle_gaps"])) == pytest.approx(
        {"perfbench_query": 6500e-9})


def test_trace_reduction_names_the_innermost_covering_span():
    planes = hand_trace()
    planes[0]["lines"][0]["events"].append(["semaphore_wait", 1900.0, 4200.0])
    r = trace_reduce.reduce_trace(planes)
    assert dict(map(tuple, r["idle_gaps"])) == pytest.approx(
        {"semaphore_wait": 4000e-9, "perfbench_query": 2500e-9})


def test_trace_reduction_on_a_trace_cut_from_the_chip():
    """One q6 execution as the v5e's profiler recorded it. Busy time against
    a count of the microseconds in which some op of the ops line ran."""
    with open(os.path.join(HERE, "data", "q6_chip_trace.json")) as f:
        planes = json.load(f)["planes"]
    r = trace_reduce.reduce_trace(planes)
    window = [e for e in planes[1]["lines"][5]["events"]
              if e[0] == trace_reduce.WINDOW][0]
    assert r["window_s"] == pytest.approx(window[2] / 1e9)
    ops = [l for l in planes[0]["lines"] if l["name"] == "XLA Ops"][0]
    t0, t1 = window[1], window[1] + window[2]
    cells = np.zeros(int((t1 - t0) / 1e3) + 1, dtype=bool)
    for _, start, dur in ops["events"]:
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            cells[int((a - t0) / 1e3): int(np.ceil((b - t0) / 1e3))] = True
    assert r["busy_s"] == pytest.approx(cells.sum() / 1e6, rel=1e-3)
    assert 0.99 < r["busy_s"] / r["window_s"] < 1.0
    # the serial float64 sum is the query: 0.93 s of its 1.01 s
    assert r["device_ops"][0][0].startswith("%fusion.1 = (f32[1]")
    assert r["device_ops"][0][1] == pytest.approx(0.929, abs=1e-3)
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) <= 10
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)


def test_trace_without_device_work_reduces_to_nothing():
    planes = hand_trace()
    planes[1]["lines"][1]["events"] = [["fusion.9", 20000.0, 10.0]]
    assert trace_reduce.reduce_trace(planes) is None
    assert trace_reduce.reduce_trace(planes[:1]) is None
    unmarked = hand_trace()
    del unmarked[0]["lines"][0]["events"][0]        # no window annotation
    assert trace_reduce.reduce_trace(unmarked) is None


def test_trace_on_four_devices_averages_their_busy_time():
    planes = hand_trace()
    other = json.loads(json.dumps(planes[1]))
    other["name"] = "/device:TPU:1"
    other["lines"][1]["events"] = [["all-reduce", 3000.0, 500.0]]
    r = trace_reduce.reduce_trace(planes + [other])
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((3500e-9 + 500e-9) / 2)
