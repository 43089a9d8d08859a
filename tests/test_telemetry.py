"""Process-lifetime telemetry (ISSUE 7): metrics registry + Prometheus
round trip, HBM watermark accounting with per-operator peak attribution,
the always-on flight recorder (auto-dump on task failure), the scrape
endpoint, and the registry-publish discipline (resolve boundaries, never
per row)."""

import json
import os
import urllib.error
import urllib.request

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.service import telemetry as tel


def _session(**conf):
    return TpuSession.builder.config(
        {"spark.rapids.tpu.sql.explain": "NONE", **conf}).getOrCreate()


def _q3_tables(s, n=4096):
    rng = np.random.default_rng(11)
    line = pd.DataFrame({
        "l_order": rng.integers(0, 500, n).astype("int64"),
        "l_price": rng.normal(100.0, 10.0, n)})
    orders = pd.DataFrame({
        "o_key": np.arange(500, dtype="int64"),
        "o_cust": rng.integers(0, 50, 500).astype("int64"),
        "o_date": rng.integers(0, 500, 500).astype("int64")})
    cust = pd.DataFrame({
        "c_key": np.arange(50, dtype="int64"),
        "c_seg": rng.integers(0, 3, 50).astype("int64")})
    s.createDataFrame(line).createOrReplaceTempView("t_lineitem")
    s.createDataFrame(orders).createOrReplaceTempView("t_orders")
    s.createDataFrame(cust).createOrReplaceTempView("t_customer")


T_Q3 = ("SELECT l_price, o_date, c_seg FROM t_lineitem "
        "JOIN t_orders ON l_order = o_key "
        "JOIN t_customer ON o_cust = c_key "
        "WHERE o_date < 350 AND c_seg = 1")


# ---------------------------------------------------------------------------
# Registry model
# ---------------------------------------------------------------------------

def test_registry_counter_gauge_histogram_basics():
    tel.MetricsRegistry.reset()
    reg = tel.MetricsRegistry.get()
    c = reg.counter("tpu_flight_dumps_total", "help text")
    c.inc()
    c.inc(2)
    assert c.value == 3
    with pytest.raises(ValueError):
        c.inc(-1)                       # counters only grow
    g = reg.gauge("tpu_hbm_bytes", "", store="device")
    g.set(100)
    g.set(40)
    assert g.value == 40
    # same name + different labels = distinct samples
    g2 = reg.gauge("tpu_hbm_bytes", "", store="host")
    g2.set(7)
    assert g.value == 40 and g2.value == 7
    h = reg.histogram("tpu_span_seconds", "", name="sort")
    h.observe(0.003)
    h.observe(0.2)
    assert h.count == 2 and abs(h.sum - 0.203) < 1e-9
    # one name cannot change kind
    with pytest.raises(ValueError):
        reg.gauge("tpu_flight_dumps_total")
    tel.MetricsRegistry.reset()


def test_prometheus_text_round_trip():
    """Parse what we emit: every sample value and label survives the
    text exposition format, histograms included (cumulative buckets +
    _sum/_count)."""
    tel.MetricsRegistry.reset()
    reg = tel.MetricsRegistry.get()
    reg._collectors = []               # no harvest: a closed fixture
    reg.counter("tpu_recompiles_total", "compile builds").inc(17)
    reg.gauge("tpu_hbm_peak_bytes", "peak", store="device").set(4096)
    reg.gauge("tpu_hbm_peak_operator_info", "", store="device",
              operator='Tpu"Weird"\nExec').set(1)
    # literal backslash-n (NOT a newline): chained-replace unescaping
    # would corrupt this into backslash+newline
    reg.gauge("tpu_backend_info", "", platform=r"c:\new\tpu").set(1)
    h = reg.histogram("tpu_span_seconds", "spans", name="join")
    for v in (0.0005, 0.004, 0.07, 2.0):
        h.observe(v)

    parsed = tel.parse_prometheus_text(reg.prometheus_text())
    assert parsed["tpu_recompiles_total"] == [({}, 17.0)]
    assert ({"store": "device"}, 4096.0) in parsed["tpu_hbm_peak_bytes"]
    # label escaping round-trips
    (labels, one), = parsed["tpu_hbm_peak_operator_info"]
    assert labels["operator"] == 'Tpu"Weird"\nExec' and one == 1.0
    (labels2, _), = parsed["tpu_backend_info"]
    assert labels2["platform"] == r"c:\new\tpu"
    # histogram: cumulative buckets end at the total count
    buckets = parsed["tpu_span_seconds_bucket"]
    assert buckets[-1][0]["le"] == "+Inf" and buckets[-1][1] == 4.0
    counts = [v for _l, v in buckets]
    assert counts == sorted(counts), "bucket counts must be cumulative"
    assert parsed["tpu_span_seconds_count"][0][1] == 4.0
    assert abs(parsed["tpu_span_seconds_sum"][0][1] - 2.0745) < 1e-9
    tel.MetricsRegistry.reset()


def test_exec_bag_publishes_at_resolve_not_per_inc():
    """The registry hot-path discipline: TpuMetrics.inc never touches the
    registry; the fold happens at resolve (a reporting boundary), once,
    without double counting on later resolves."""
    from spark_rapids_tpu.exec.metrics import TpuMetrics
    tel.MetricsRegistry.reset()
    reg = tel.MetricsRegistry.get()
    bag = TpuMetrics()
    for _ in range(1000):
        bag.inc("numOutputRows", 1)
    ctr = reg.counter("tpu_exec_metric_total", key="numOutputRows")
    assert ctr.value == 0, "inc must not publish"
    bag.resolve()
    assert ctr.value == 1000
    bag.resolve()                       # idempotent: no new delta
    assert ctr.value == 1000
    bag.inc("numOutputRows", 5)
    assert dict(bag.items())["numOutputRows"] == 1005  # items() resolves
    assert ctr.value == 1005
    tel.MetricsRegistry.reset()


# ---------------------------------------------------------------------------
# Watermarks
# ---------------------------------------------------------------------------

def test_watermark_peak_monotonic_and_operator_attribution():
    from spark_rapids_tpu.exec.metrics import TpuMetrics, exec_scope
    tel.reset_watermarks()
    wm = tel.watermark("device", bag_key="peakDeviceBytes")
    bag = TpuMetrics()
    bag.owner = "TpuFakeJoinExec"
    wm.update(100)
    with exec_scope(bag):
        wm.update(5000)                 # new peak inside the exec scope
    wm.update(300)                      # current falls, peak must not
    assert wm.current == 300
    assert wm.peak == 5000
    assert wm.peak_operator == "TpuFakeJoinExec"
    assert bag.get("peakDeviceBytes") == 5000
    # a lower later "peak" never overwrites the bag watermark either
    with exec_scope(bag):
        wm.update(400)
    assert wm.peak == 5000 and bag.get("peakDeviceBytes") == 5000
    tel.reset_watermarks()


@pytest.fixture
def empty_catalog():
    """The spill catalog is process-wide, and the batches of an earlier
    file's LAST session stay registered until a new session takes its
    place (its views die, their finalizers drain). Left to this test's
    own first query, that release came AFTER the watermark reset: the
    first update, outside any exec, stood at the leftovers' height, the
    join's own registrations never passed it, and no operator owned the
    peak (tests/test_pipeline_window.py before this file, in one
    process: 354 816 bytes left, peak 336 384 with no operator). Let go
    of the leftovers first."""
    import gc
    from spark_rapids_tpu.exec.spill import drain_deferred_finalizers
    _session()
    gc.collect()
    drain_deferred_finalizers()
    yield
    tel.reset_watermarks()


def test_q3_join_drives_device_watermark_with_attribution(empty_catalog):
    """End to end under the q3-shaped 3-way join: batch registration in
    the spill catalog moves the device watermark, the peak is monotone
    vs current, and the peak carries an operator attribution (the open
    exec scope at registration time)."""
    s = _session(**{"spark.rapids.tpu.sql.reader.batchSizeRows": 1024})
    _q3_tables(s)
    rows = s.sql(T_Q3).collect()
    assert rows                          # the join produced output
    wm = tel.watermarks().get("device")
    assert wm is not None and wm.peak > 0
    assert wm.peak >= wm.current
    assert wm.peak_operator and wm.peak_operator.startswith("Tpu")
    # ... and the registry exposes it (acceptance: HBM watermarks from
    # the one registry)
    snap = s.metrics_snapshot()
    fam = snap["metrics"]["tpu_hbm_peak_bytes"]
    dev = [x for x in fam["samples"] if x["labels"].get("store") == "device"]
    assert dev and dev[0]["value"] == wm.peak


def test_metrics_snapshot_exposes_all_subsystems():
    """Acceptance check: semaphore, lockdep, sync, recompile, spill,
    shuffle-transport and HBM watermark metrics from ONE registry."""
    s = _session()
    df = s.createDataFrame(pd.DataFrame(
        {"k": [1, 2, 1, 3] * 64, "v": [1.0, 2.0, 3.0, 4.0] * 64}))
    df.groupBy("k").agg(F.sum("v").alias("sv")).collect()
    _ = s.last_query_metrics()          # resolve boundary: bags publish
    names = set(s.metrics_snapshot()["metrics"])
    for want in ("tpu_semaphore_wait_seconds_total",
                 "tpu_semaphore_hold_seconds_total",
                 "tpu_lock_acquires_total",       # conftest: lockdep=record
                 "tpu_host_syncs_total",
                 "tpu_recompiles_total",
                 "tpu_spill_device_bytes",
                 "tpu_shuffle_bytes_fetched_total",
                 "tpu_hbm_bytes", "tpu_hbm_peak_bytes",
                 "tpu_exec_metric_total",
                 "tpu_span_seconds",
                 "tpu_device_budget_bytes"):
        assert want in names, f"{want} missing from the registry snapshot"
    # JSONL export appends one parseable line per call
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "sub", "metrics.jsonl")
        s.metrics_snapshot(path)
        s.metrics_snapshot(path)
        lines = open(path).read().strip().splitlines()
        assert len(lines) == 2
        assert "tpu_host_syncs_total" in json.loads(lines[0])["metrics"]


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------

def test_flight_ring_fixed_size_newest_win():
    r = tel.FlightRecorder(capacity=16)
    for i in range(40):
        r.record("span", f"s{i}")
    ev = r.events()
    assert len(ev) == 16
    assert ev[0]["name"] == "s24" and ev[-1]["name"] == "s39"
    assert r.event_count() == 40


def test_spans_feed_flight_ring_without_tracing_enabled():
    """The always-on property: NO tracing conf, no SpanRecorder — spans
    still land in the ring (post-mortems must not require foresight)."""
    from spark_rapids_tpu.exec.tracing import trace_span
    tel.FlightRecorder.reset()
    _session()                          # primes the flight gate
    with trace_span("always_on_probe"):
        pass
    names = [e["name"] for e in tel.FlightRecorder.get().events()
             if e["kind"] == "span"]
    assert "always_on_probe" in names


def test_flight_dump_on_injected_task_failure(tmp_path):
    """A task-body failure must produce a flight artifact WITHOUT any
    tracing pre-enabled, containing the failing span, and the original
    exception must propagate unmasked."""
    flight_dir = str(tmp_path / "flight")
    s = _session(**{
        "spark.rapids.tpu.sql.telemetry.flightRecorderDir": flight_dir})
    tel.FlightRecorder.reset()          # fresh ring for a clean assert
    df = s.createDataFrame(pd.DataFrame({"a": [1.0, 2.0, 3.0, 4.0]}))

    def boom(it):
        for _pdf in it:
            raise ValueError("injected task failure")

    from spark_rapids_tpu.columnar import dtypes as dt
    bad = df.mapInPandas(boom, dt.Schema([dt.Field("a", dt.FLOAT64)]))
    with pytest.raises(ValueError, match="injected task failure"):
        bad.collect()
    arts = sorted(os.listdir(flight_dir))
    assert arts, "no flight artifact written"
    doc = json.load(open(os.path.join(flight_dir, arts[0])))
    assert "injected task failure" in (doc["reason"] or "")
    spans = [e for e in doc["events"] if e["kind"] == "span"]
    assert spans, "artifact carries no spans"
    # the failing span is error-marked (the exception unwound through it)
    assert any(e.get("data", {}).get("error") for e in spans), spans


def test_failed_flight_dump_never_masks_query_exception(tmp_path):
    """An unwritable dump dir loses the artifact, NEVER the original
    exception (satellite: telemetry writes must not mask errors)."""
    blocker = tmp_path / "blocker"
    blocker.write_text("a file where a directory is expected")
    s = _session(**{
        "spark.rapids.tpu.sql.telemetry.flightRecorderDir":
            str(blocker / "sub")})
    df = s.createDataFrame(pd.DataFrame({"a": [1.0, 2.0]}))

    def boom(it):
        for _pdf in it:
            raise ValueError("the real failure")

    from spark_rapids_tpu.columnar import dtypes as dt
    bad = df.mapInPandas(boom, dt.Schema([dt.Field("a", dt.FLOAT64)]))
    with pytest.raises(ValueError, match="the real failure"):
        bad.collect()


def test_session_dump_flight_record_on_demand(tmp_path):
    s = _session()
    with_path = s.dump_flight_record(str(tmp_path / "deep" / "fr.json"))
    doc = json.load(open(with_path))
    assert doc["reason"] == "on-demand"
    assert isinstance(doc["events"], list)


def test_conf_change_recorded(tmp_path):
    s = _session()
    from spark_rapids_tpu.api.session import RuntimeConf
    RuntimeConf(s).set("spark.rapids.tpu.sql.shuffle.partitions", 4)
    ev = [e for e in tel.FlightRecorder.get().events()
          if e["kind"] == "conf"]
    assert any(e["name"] == "spark.rapids.tpu.sql.shuffle.partitions"
               for e in ev)


# ---------------------------------------------------------------------------
# Scrape endpoint
# ---------------------------------------------------------------------------

def test_scrape_endpoint_serves_and_shuts_down():
    tel.stop_server()
    srv = tel.start_server(0)           # ephemeral port
    assert srv.port > 0
    base = f"http://127.0.0.1:{srv.port}"
    with urllib.request.urlopen(base + "/metrics", timeout=5) as resp:
        assert resp.status == 200
        text = resp.read().decode()
    parsed = tel.parse_prometheus_text(text)
    assert any(n.startswith("tpu_") for n in parsed)
    with urllib.request.urlopen(base + "/snapshot", timeout=5) as resp:
        snap = json.loads(resp.read().decode())
    assert "metrics" in snap
    with pytest.raises(urllib.error.HTTPError):
        urllib.request.urlopen(base + "/nope", timeout=5)
    tel.stop_server()
    with pytest.raises((urllib.error.URLError, ConnectionError, OSError)):
        urllib.request.urlopen(base + "/metrics", timeout=1)


# ---------------------------------------------------------------------------
# Overhead guard
# ---------------------------------------------------------------------------

def test_telemetry_overhead_within_small_factor():
    """The fused pipeline with telemetry (metrics + flight recorder) on
    stays within a coarse factor of disabled — the registry publishes at
    resolve/flush boundaries, so per-batch cost is a handful of dict
    ops, not a per-row stream. Bound is deliberately loose (2-CPU CI
    boxes under load), but a per-row publish would blow it by orders of
    magnitude."""
    import time

    data = pd.DataFrame({"k": np.arange(8192) % 37,
                         "v": np.linspace(0.0, 1.0, 8192)})

    def run_query(s):
        df = s.createDataFrame(data)
        return (df.filter(F.col("v") > 0.1)
                  .groupBy("k").agg(F.sum("v").alias("sv")).collect())

    def timed(s, iters=3):
        run_query(s)                    # warm: compile cache primed
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            run_query(s)
            best = min(best, time.perf_counter() - t0)
        return best

    off = timed(_session(**{
        "spark.rapids.tpu.sql.metrics.enabled": "false",
        "spark.rapids.tpu.sql.telemetry.flightRecorder": "false"}))
    on = timed(_session())              # defaults: both on
    assert on <= off * 8 + 0.25, (on, off)
