"""Configuration ``tpch_sf10``, traffic ``q6_memo`` and the cell
``tpch_sf10.q6``: the memoised reference against ``q6``'s own, CPU rehearsals
of the cell against the engine, its control and its broken paths, and the
program's per-query scan and plan-cache counters under the cell's traffic
(no per-layer metric reads them yet: PERF.md section 7 (i)). Everything runs
on the CPU at a small ``rows_scale``; nothing here is a device number."""

import io
import itertools
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import compare, run  # noqa: E402
from perfbench.queries import q6, q6_memo  # noqa: E402

CELL = "tpch_sf10.q6"
SCALE = 0.0004                       # 24 000 lines
BENCH = run.load_benchmark()
CONFIG = run.load_json("configs", "tpch_sf10.json")
WORKLOAD = run.load_json("workloads", CELL + ".json")
PER_LAYER_ON_CPU = {"plan_ms", "scan_ms", "host_syncs", "compiles_per_query",
                    "compile_ms"}
ALL_SETS = [{"year": y, "discount_pct": d, "quantity": q}
            for y, d, q in itertools.product(range(1993, 1998), range(2, 10),
                                             (24, 25))]


@pytest.fixture(scope="module", autouse=True)
def _leave_the_worker_as_found():
    """As ``test_perfbench.py`` does: the default session back, the
    rehearsals' table out of the device scan cache."""
    yield
    from spark_rapids_tpu.api.session import TpuSession
    session = TpuSession.builder.config(
        {"spark.rapids.tpu.sql.explain": "NONE",
         run._TRACING_CONF: "false"}).getOrCreate()
    session.createDataFrame({"x": [0]}).createOrReplaceTempView("lineitem")
    session.sql("SELECT count(*) FROM lineitem").collect()


def small_tables(seed, scale=SCALE):
    return run.make_tables(CONFIG, q6.TABLES, seed, scale)[0]


def rehearse(seed, trace=0, scale=SCALE):
    out, err = io.StringIO(), io.StringIO()
    result = run.run_cell(CELL, seed, 0.0, trace, rows_scale=scale,
                          out=out, err=err)
    assert json.loads(out.getvalue().splitlines()[-1]) == result
    return result, err.getvalue()


# -- the memo ------------------------------------------------------------------

def test_there_are_eighty_parameter_sets():
    rng = np.random.default_rng(7)
    drawn = {tuple(sorted(q6_memo.draw(rng).items())) for _ in range(4000)}
    assert drawn == {tuple(sorted(p.items())) for p in ALL_SETS}
    assert len(ALL_SETS) == 80


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
def test_memo_equals_q6_for_every_parameter_set(dtype):
    tables = small_tables(21)
    for p in ALL_SETS:
        assert q6_memo.reference(tables, p, dtype) == \
            q6.reference(tables, p, dtype)
    assert q6_memo.reference(tables, ALL_SETS[0]) == \
        q6.reference(tables, ALL_SETS[0])          # the harness's call


def test_memo_calls_the_reference_once_per_set_and_dtype(monkeypatch):
    calls = []
    real = q6.reference

    def counting(tables, p, dtype=np.float64):
        calls.append((p["year"], p["discount_pct"], p["quantity"],
                      np.dtype(dtype).name))
        return real(tables, p, dtype)
    monkeypatch.setattr(q6, "reference", counting)
    tables = small_tables(22)
    rng = np.random.default_rng(1)
    draws = [q6_memo.draw(rng) for _ in range(600)]
    for p in draws:
        q6_memo.reference(tables, p)
    distinct = {(p["year"], p["discount_pct"], p["quantity"]) for p in draws}
    assert len(calls) == len(set(calls)) == len(distinct) <= 80
    for p in draws[:50]:
        q6_memo.reference(tables, p, np.float32)
    assert len(calls) == len(set(calls))
    assert {c[3] for c in calls} == {"float64", "float32"}


def test_memo_does_not_leak_across_tables():
    """A second run in the same process (another seed, or the same seed with
    half the rows withheld) gets its own table's answers."""
    a, b = small_tables(23), small_tables(24)
    p = {"year": 1994, "discount_pct": 6, "quantity": 24}
    ra = q6_memo.reference(a, p)
    rb = q6_memo.reference(b, p)
    assert ra == q6.reference(a, p) and rb == q6.reference(b, p) and ra != rb
    assert q6_memo.reference(a, p) == ra            # and back again
    half = {"lineitem": {k: v[: len(v) // 2]
                         for k, v in a["lineitem"].items()}}
    assert q6_memo.reference(half, p) == q6.reference(half, p) != ra


def test_memo_shares_everything_else_with_q6():
    for name in ("TABLES", "COLUMNS", "draw", "sql", "bytes_read"):
        assert getattr(q6_memo, name) is getattr(q6, name)
    assert q6_memo.bytes_read(CONFIG["rows"]) == 28 * 60_000_000
    source = open(q6_memo.__file__).read()
    assert "spark_rapids_tpu" not in source.replace(
        "Nothing here imports the engine", "")
    assert "benchmarks" not in source.split('"""')[2]


# -- the configuration and the cell as data --------------------------------------

def test_configuration_is_ten_times_tpch_sf1():
    sf1 = run.load_json("configs", "tpch_sf1.json")
    assert CONFIG["rows"] == {t: 10 * n for t, n in sf1["rows"].items()}
    assert CONFIG["scale_factor"] == 10 * sf1["scale_factor"] == 10
    assert CONFIG["chips"] == 1 and CONFIG["reduced"] == []
    assert set(CONFIG["conf"]) == set(sf1["conf"]) - {
        "spark.rapids.tpu.sql.agg.matmul.enabled"}
    assert CONFIG["guarantees"].startswith(sf1["guarantees"].split(
        "8.9e-10")[0])
    assert "float64 literal" in CONFIG["guarantees"]
    assert any("pair of float32" in a for a in CONFIG["assumed"])
    assert any("60 000 000 rows exactly" in a for a in CONFIG["assumed"])
    assert len(CONFIG["source"]) <= 200


def test_benchmark_lists_the_cells_under_the_accepted_metrics():
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert cells[CELL] == {"name": CELL, "config": "tpch_sf10",
                           "traffic": "q6_memo", "chips": 1,
                           "why": WORKLOAD["why"]}
    assert cells["tpch_sf1.q6"]["traffic"] == "q6"
    assert len(WORKLOAD["why"]) <= 200
    both = ["tpch_sf1.q6", CELL]
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in ("plan_ms", "scan_ms", "compiles_per_query", "compile_ms",
                 "host_syncs", "device_busy_ms", "device_idle_pct",
                 "query_hbm_roofline"):
        assert by_name[name]["workloads"][-2:] == both
    for w in both:
        reported = {m["name"] for m in run.metrics_of_cell(
            BENCH["end_to_end"], w)}
        assert {"query_s", "setup_s"} <= reported
    assert WORKLOAD["limits"] == {"rows_wrong": 0, "max_rel_gap": 1e-09}


def _ctx(query_metrics):
    return {"queries": len(query_metrics), "latencies_s": [],
            "window_s": 0.0, "setup_s": 1.0, "query_metrics": query_metrics,
            "trace": None, "compile": {"compiles": 0, "compile_s": 0.0},
            "bytes_per_query": 0, "peaks": None}


def _keeping_query_metrics(monkeypatch):
    """``last_query_metrics()`` of every traced query, as the harness hands
    them to its readers."""
    from spark_rapids_tpu.api.session import TpuSession
    kept = []
    real = TpuSession.last_query_metrics

    def keeping(self):
        kept.append(real(self))
        return kept[-1]
    monkeypatch.setattr(TpuSession, "last_query_metrics", keeping)
    return kept


# -- the cell against the engine ---------------------------------------------------

def test_untraced_rehearsal_is_correct():
    result, err = rehearse(31)
    assert result["correct"] is True and result["failed"] == 0
    assert result["checks"]["rows_wrong"]["value"] == 0
    assert result["checks"]["max_rel_gap"]["value"] < 1e-12
    assert set(result["metrics"]) == {"query_s", "setup_s"}
    compared = [json.loads(line) for line in err.splitlines()
                if line.startswith('{"reference_s"')][0]
    assert compared["compared"] == WORKLOAD["warmup_executions"] + 1


def test_traced_rehearsal_and_the_programs_counters(monkeypatch):
    """2 400 lines are one scan batch; the table is resident after the
    warm-up, the plan cache serves every traced query, and Q6 binds its
    date bounds, its discount bounds and its quantity."""
    kept = _keeping_query_metrics(monkeypatch)
    result, _err = rehearse(32, trace=1, scale=0.00004)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == WORKLOAD["traced_queries"] == 5
    assert set(result["metrics"]) == PER_LAYER_ON_CPU
    assert result["metrics"]["compiles_per_query"]["value"] == 0
    assert len(kept) == 5
    for m in kept:
        assert m["scan"] == {"batches": 1, "uploadedBatches": 0}
        assert m["planCache"] == {"hit": 1, "params": 5}


def test_several_scan_batches_are_counted(monkeypatch):
    """The configuration's shape in small: the scan hands on more than one
    batch a query, all served from the device scan cache after the first
    execution uploaded them."""
    monkeypatch.setitem(CONFIG["conf"],
                        "spark.rapids.tpu.sql.reader.batchSizeRows", "4096")
    load_json = run.load_json
    monkeypatch.setattr(run, "load_json", lambda *parts: (
        CONFIG if parts == ("configs", "tpch_sf10.json")
        else load_json(*parts)))
    kept = _keeping_query_metrics(monkeypatch)
    result, _err = rehearse(33, trace=1)
    assert result["correct"] is True
    batches = -(-int(60_000_000 * SCALE) // 4096)
    assert batches == 6 and len(kept) == 5
    assert [m["scan"] for m in kept] == [
        {"batches": batches, "uploadedBatches": 0}] * 5


def test_first_execution_uploads_what_later_ones_find_resident():
    from spark_rapids_tpu.api.session import TpuSession
    tables = small_tables(36)
    session = TpuSession.builder.config(CONFIG["conf"]).getOrCreate()
    session.createDataFrame(run.to_arrow(tables["lineitem"])
                            ).createOrReplaceTempView("lineitem")
    seen = []
    for p in ALL_SETS[:3]:
        run.execute(session, q6_memo.sql(p))
        m = session.last_query_metrics()
        seen.append((m["scan"]["uploadedBatches"], m["planCache"]["hit"]))
    assert seen == [(1, 0), (0, 1), (0, 1)]


def test_control_in_float32_is_not_correct():
    tables = small_tables(34, 0.002)
    rng = np.random.default_rng(34)
    draws = [q6_memo.draw(rng) for _ in range(3)]
    refs = [q6_memo.reference(tables, p) for p in draws]
    control = [q6_memo.reference(tables, p, np.float32) for p in draws]
    assert compare.judge(refs, refs, WORKLOAD["limits"])[0] is True
    correct, checks = compare.judge(control, refs, WORKLOAD["limits"])
    assert correct is False
    assert checks["rows_wrong"]["value"] == 0
    assert checks["max_rel_gap"]["value"] > checks["max_rel_gap"]["limit"]


def _altered(execute):
    def altered(session, text):
        (revenue,), = execute(session, text)
        return [(revenue * (1 + 1e-8),)]
    return altered


def _half_of_lineitem(to_arrow):
    def half(cols):
        return to_arrow({k: v[: len(v) // 2] for k, v in cols.items()})
    return half


@pytest.mark.parametrize("fault,attr", [("altered", "execute"),
                                        ("half", "to_arrow")])
def test_broken_timed_path_is_not_correct(monkeypatch, fault, attr):
    wrap = {"altered": _altered, "half": _half_of_lineitem}[fault]
    monkeypatch.setattr(run, attr, wrap(getattr(run, attr)))
    result, _err = rehearse(35)
    assert result["correct"] is False
    assert result["checks"]["max_rel_gap"]["value"] > \
        WORKLOAD["limits"]["max_rel_gap"]


def test_roofline_reader_takes_q6s_bytes_at_sf10():
    """28 B a row over one chip's bandwidth against the busy time: PR 32's
    51.8 ms of busy time a query read 3.96%."""
    from perfbench.readers import query_hbm_roofline
    peaks = run.load_json("peaks.json")["TPU v5 lite"]
    ctx = dict(_ctx([{}]), trace={"busy_s": 0.0518, "window_s": 0.062},
               peaks=peaks, bytes_per_query=q6_memo.bytes_read(CONFIG["rows"]))
    share = query_hbm_roofline.read(ctx)
    assert share == pytest.approx(100 * (1.68e9 / 819e9) / 0.0518, rel=1e-3)
    assert 3.9 < share < 4.0
