"""Runtime sync auditor + recompile audit (analysis/sync_audit.py,
analysis/recompile.py): per-span sync attribution, transfer-guard arming,
the q3-shaped join staying O(1) transfers per stage under span accounting,
and distinct-compile tracking with the per-batch-shape flag.
"""

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.analysis import recompile, sync_audit
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession


def _session(**conf):
    return TpuSession.builder.config(
        {"spark.rapids.tpu.sql.explain": "NONE", **conf}).getOrCreate()


# ---------------------------------------------------------------------------
# Per-span sync attribution (exec/tracing.SyncCounter + SpanRecorder)
# ---------------------------------------------------------------------------

def test_sync_report_carries_span_breakdown():
    s = _session()
    df = s.createDataFrame(pd.DataFrame(
        {"k": [1, 2, 1, 3] * 64, "v": [1., 2., 3., 4.] * 64}))
    df.groupBy("k").agg(F.sum("v").alias("sv")).collect()
    sync = s.last_query_metrics()["sync"]
    assert "syncSpans" in sync
    # every counted sync is attributed to some span bucket
    assert sum(sync["syncSpans"].values()) == sync["hostSyncs"]


def test_span_attribution_names_pipeline_resolve():
    """The batched deferred-scalar readback must be attributed to ITS span
    (pipeline_resolve), not smeared over the operator spans around it."""
    from spark_rapids_tpu.exec.pipeline import PipelineWindow
    from spark_rapids_tpu.exec.tracing import SpanRecorder, SyncCounter
    import jax.numpy as jnp
    with SyncCounter() as sc, SpanRecorder():
        win = PipelineWindow(4)
        outs = []
        for i in range(8):
            outs.extend(win.push(lambda v: v, jnp.int32(i) + 1))
        outs.extend(win.flush())
    assert outs == [1, 2, 3, 4, 5, 6, 7, 8]
    rep = sc.report()
    if rep["hostSyncs"]:                    # CPU backend may serve cached
        assert set(rep["syncSpans"]) == {"pipeline_resolve"}, rep


# ---------------------------------------------------------------------------
# q3-shaped 3-way join: O(1) transfers per stage, span-attributed
# ---------------------------------------------------------------------------

def test_q3_shaped_join_syncs_stay_o1_with_span_accounting():
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
    s = _session(**{"spark.rapids.tpu.sql.reader.batchSizeRows": 1024})
    s.createDataFrame(line).createOrReplaceTempView("a_lineitem")
    s.createDataFrame(orders).createOrReplaceTempView("a_orders")
    s.createDataFrame(cust).createOrReplaceTempView("a_customer")
    df = s.sql(
        "SELECT l_price, o_date, c_seg FROM a_lineitem "
        "JOIN a_orders ON l_order = o_key "
        "JOIN a_customer ON o_cust = c_key "
        "WHERE o_date < 700 AND c_seg = 1")
    rows = df.collect()
    exp = (line.merge(orders, left_on="l_order", right_on="o_key")
               .merge(cust, left_on="o_cust", right_on="c_key"))
    exp = exp[(exp.o_date < 700) & (exp.c_seg == 1)]
    assert len(rows) == len(exp)
    sync = s.last_query_metrics()["sync"]
    # 8 stream batches/join stage: per-batch sizing readbacks would put
    # ~8+ syncs on the window; batched landing keeps it O(1) per stage
    resolve_syncs = sum(v for span, v in sync["syncSpans"].items()
                        if span == "pipeline_resolve")
    assert resolve_syncs <= 4, sync
    assert sum(sync["syncSpans"].values()) == sync["hostSyncs"]


# ---------------------------------------------------------------------------
# Transfer-guard arming (CPU backend: arming must at least be harmless)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["log", "disallow"])
def test_audit_modes_run_clean(mode):
    try:
        s = _session(**{"spark.rapids.tpu.sql.analysis.syncAudit": mode})
        # the session-set conf must actually reach the audit (a fresh
        # default TpuConf would read 'off' and arm nothing — vacuous)
        assert sync_audit.audit_mode() == mode
        df = s.createDataFrame(pd.DataFrame(
            {"k": [1, 2, 1], "v": [1., 2., 3.]}))
        out = df.groupBy("k").agg(F.sum("v").alias("s")).orderBy("k").collect()
        assert out == [(1, 4.0), (2, 2.0)]
    finally:
        sync_audit.reset_cache()


def test_new_session_reprimes_audit_caches():
    _session(**{"spark.rapids.tpu.sql.analysis.syncAudit": "log"})
    assert sync_audit.audit_mode() == "log"
    _session()                      # new session, default conf
    assert sync_audit.audit_mode() == "off"


def test_allowed_host_transfer_requires_reason_and_nests():
    with pytest.raises(AssertionError):
        with sync_audit.allowed_host_transfer(""):
            pass
    with sync_audit.allowed_host_transfer("test crossing"):
        pass                                   # unarmed: pure no-op


# ---------------------------------------------------------------------------
# Recompile audit
# ---------------------------------------------------------------------------

def test_repeat_query_compiles_nothing_new():
    s = _session()
    df = s.createDataFrame(pd.DataFrame(
        {"k": [1, 2, 1, 3] * 32, "v": [1., 2., 3., 4.] * 32}))

    def q():
        return df.groupBy("k").agg(F.sum("v").alias("sv")).orderBy(
            "k").collect()

    first = q()
    base = recompile.snapshot()
    assert q() == first
    growth = recompile.delta(base)
    compiles = sum(d["compiles"] for d in growth.values())
    calls = sum(d["calls"] for d in growth.values())
    assert compiles == 0, growth       # same shapes: all fused-cache hits
    assert calls > 0, growth           # ...and the cache actually served


def test_fused_stage_calls_count_executions_not_instances():
    """Every batch through a FusedStage counts as a call; otherwise
    compiles ~= calls by construction and flagged() fires spuriously."""
    s = _session(**{"spark.rapids.tpu.sql.reader.batchSizeRows": 1024})
    df = s.createDataFrame(pd.DataFrame(
        {"v": [float(i) for i in range(4096)]}))
    base = recompile.snapshot()
    df.select((F.col("v") * 2).alias("x")).collect()   # 4 batches
    d = recompile.delta(base)
    assert d["project"]["calls"] >= 4, d
    assert d["project"]["compiles"] <= 1, d
    assert not recompile.flagged(d), (d, recompile.flagged(d))


def test_flagged_detects_per_shape_compiles():
    counters = {
        "well_bucketed": {"compiles": 2, "distinctShapes": 2, "calls": 100},
        "per_shape": {"compiles": 20, "distinctShapes": 20, "calls": 22},
        # eviction churn: few distinct shapes but compiling every call
        "evicted": {"compiles": 30, "distinctShapes": 3, "calls": 32},
    }
    flags = recompile.flagged(counters)
    assert "per_shape" in flags and "evicted" in flags
    assert "well_bucketed" not in flags


def test_kernel_of_joins_string_tags():
    assert recompile.kernel_of(("concat", ("f64",), (8,), (0,), 8)) == \
        "concat"
    assert recompile.kernel_of(
        ("agg", "update", "partial", ("k",), ("b",), (), ("f64",),
         "sort", 128)) == "agg/update/partial/sort"
    assert recompile.kernel_of(42) == "anon"
