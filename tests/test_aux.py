"""Auxiliary subsystems: ML export, compression codecs, tracing spans."""

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.api import functions as F


def _session():
    return TpuSession.builder.config(
        "spark.rapids.tpu.sql.explain", "NONE").getOrCreate()


# -- ML export (ColumnarRdd / InternalColumnarRddConverter analog) -----------

def test_to_feature_matrix_and_labels():
    from spark_rapids_tpu import models
    s = _session()
    df = s.createDataFrame(pd.DataFrame({
        "a": [1.0, 2.0, None, 4.0],
        "b": [10, 20, 30, 40],
        "y": [0.0, 1.0, 0.0, 1.0]}))
    feats, labels = models.to_feature_matrix(df, label_col="y")
    f = np.asarray(feats)
    assert f.shape == (4, 2) and f.dtype == np.float32
    assert np.isnan(f[2, 0])            # NULL -> NaN (DMatrix missing)
    assert list(np.asarray(labels)) == [0.0, 1.0, 0.0, 1.0]


def test_to_device_arrays_stays_on_device():
    import jax
    from spark_rapids_tpu import models
    s = _session()
    df = s.createDataFrame({"x": [1, 2, 3]})
    arrays = models.to_device_arrays(df)
    data, valid = arrays["x"]
    assert isinstance(data, jax.Array)
    assert list(np.asarray(data)) == [1, 2, 3]


def test_to_torch():
    from spark_rapids_tpu import models
    s = _session()
    df = s.createDataFrame(pd.DataFrame({"a": [1.0, 2.0], "y": [0.0, 1.0]}))
    feats, labels = models.to_torch(df, label_col="y")
    assert feats.shape == (2, 1)
    assert labels.tolist() == [0.0, 1.0]


def test_feature_matrix_rejects_strings():
    from spark_rapids_tpu import models
    s = _session()
    df = s.createDataFrame({"a": [1.0], "s": ["x"]})
    with pytest.raises(TypeError):
        models.to_feature_matrix(df, feature_cols=["s"])


# -- compression codecs ------------------------------------------------------

def test_codec_roundtrip():
    from spark_rapids_tpu.shuffle.compression import get_codec
    data = bytes(range(256)) * 100
    for name in ("none", "zlib"):
        c = get_codec(name)
        enc = c.compress(data)
        assert c.decompress(enc, len(data)) == data
    z = get_codec("zlib")
    assert len(z.compress(b"a" * 10000)) < 200


def test_unknown_codec_rejected():
    from spark_rapids_tpu.shuffle.compression import get_codec
    with pytest.raises(ValueError):
        get_codec("snappy")


def test_transport_with_zlib_codec():
    """Server compresses chunk payloads; client transparently decompresses
    (CRC covers the wire form)."""
    import socket
    import threading
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.shuffle.transport import (ShuffleClient,
                                                    ShuffleServer,
                                                    ShuffleStore,
                                                    SocketConnection)
    store = ShuffleStore()
    batch = ColumnarBatch.from_pydict({
        "a": list(range(5000)), "b": [0.5] * 5000})
    store.register_batch(9, 0, batch)
    srv = ShuffleServer(store, chunk_bytes=4096, codec="zlib")

    def connect():
        a, b = socket.socketpair()
        threading.Thread(target=srv.handle_connection,
                         args=(SocketConnection(b),), daemon=True).start()
        return SocketConnection(a)

    got = ShuffleClient(connect).fetch(9, [0])
    assert sorted(got[0].rows()) == sorted(batch.rows())


def test_spill_disk_compression(tmp_path):
    import os
    from spark_rapids_tpu import config as cfg
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.exec.spill import BufferCatalog, \
        SpillableColumnarBatch
    cat = BufferCatalog(device_budget=1 << 30, host_budget=1 << 30,
                        spill_dir=str(tmp_path))
    # highly compressible payload
    b = ColumnarBatch.from_pydict({"x": [7] * 4096})
    s = SpillableColumnarBatch(b, catalog=cat)
    import os as _os
    _os.environ["SPARK_RAPIDS_TPU_CONF__SPARK__RAPIDS__TPU__MEMORY__SPILL__COMPRESSION__CODEC"] = "zlib"
    try:
        buf = cat.buffers[s._id]
        buf.spill_to_host()
        buf.spill_to_disk(str(tmp_path))
        files = list(tmp_path.glob("spill-*.npz"))
        assert files
        assert files[0].stat().st_size < b.device_size_bytes() / 4
        back = s.get_batch()
        assert back.rows() == b.rows()
    finally:
        del _os.environ[
            "SPARK_RAPIDS_TPU_CONF__SPARK__RAPIDS__TPU__MEMORY__SPILL__COMPRESSION__CODEC"]
        s.close()


# -- tracing -----------------------------------------------------------------

def test_trace_span_noop_and_enabled():
    from spark_rapids_tpu.exec import tracing
    tracing.reset_cache()
    with tracing.trace_span("test-span"):
        x = 1 + 1
    assert x == 2
    # forced on: spans must still nest/execute correctly
    import os
    os.environ["SPARK_RAPIDS_TPU_CONF__SPARK__RAPIDS__TPU__SQL__TRACING__ENABLED"] = "true"
    tracing.reset_cache()
    try:
        with tracing.trace_span("outer"):
            with tracing.trace_span("inner"):
                x = 2 + 2
        assert x == 4
    finally:
        del os.environ[
            "SPARK_RAPIDS_TPU_CONF__SPARK__RAPIDS__TPU__SQL__TRACING__ENABLED"]
        tracing.reset_cache()


# -- regexp_replace + api_validation -----------------------------------------

def test_regexp_replace_golden():
    from golden import assert_tpu_and_cpu_equal
    assert_tpu_and_cpu_equal(
        lambda s: s.createDataFrame({"s": ["ab12cd", "x9", None, "zz"]})
        .select(F.regexp_replace(F.col("s"), r"\d+", "#").alias("r")),
        conf={"spark.rapids.tpu.sql.incompatibleOps.enabled": "true"})


def test_regexp_replace_group_refs():
    from golden import assert_tpu_and_cpu_equal
    rows = assert_tpu_and_cpu_equal(
        lambda s: s.createDataFrame({"s": ["a-b", "c-d"]})
        .select(F.regexp_replace(F.col("s"), r"(\w)-(\w)", "$2_$1")
                .alias("r")),
        conf={"spark.rapids.tpu.sql.incompatibleOps.enabled": "true"})
    assert sorted(r[0] for r in rows) == ["b_a", "d_c"]


def test_api_validation_tool():
    from tools.api_validation import validate
    report = validate()
    assert report["ok"], report["problems"]
    assert report["n_expressions"] > 100
    assert report["n_execs"] >= 15


def test_last_query_metrics_surfaced():
    """Per-query SQLMetrics analog (ref GpuMetricNames, GpuExec.scala:27-56):
    operator counters surface in plan order with memory-runtime totals."""
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.functions import col

    s = TpuSession.builder.getOrCreate()
    df = s.createDataFrame({"k": [1, 2, 1, 3] * 50, "v": [1.0] * 200})
    df.filter(col("v") > 0).groupBy("k").agg(
        F.sum("v").alias("s")).collect()
    rep = s.last_query_metrics()
    ops = {o["operator"].split("[")[0]: o["metrics"] for o in rep["operators"]}
    assert any("HashAggregate" in name for name in ops), ops.keys()
    agg = next(m for name, m in ops.items() if "HashAggregate" in name)
    assert agg.get("numOutputRows") == 3
    assert "computeAggTime" in agg
    scan = next(m for name, m in ops.items() if "Scan" in name)
    assert scan.get("numOutputRows") == 200
    assert set(rep["memory"]) == {"deviceBytesHeld", "hostBytesHeld",
                                  "spilledDeviceBytes", "spilledHostBytes"}
    text = s.explain_metrics()
    assert "numOutputRows" in text and "memory:" in text


def test_hash_optimize_sort_insertion():
    """HashSortOptimizeSuite analog: with hashOptimizeSort.enabled a local
    sort lands above hash-agg outputs; results unchanged; default off."""
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.functions import col
    from spark_rapids_tpu.plan.physical import TpuSortExec

    data = {"k": [3, 1, 2, 1] * 10, "v": [1.0] * 40}

    s1 = TpuSession.builder.config(
        {"spark.rapids.tpu.sql.hashOptimizeSort.enabled": "true"}).getOrCreate()
    out = dict(s1.createDataFrame(data).groupBy("k").agg(
        F.sum("v").alias("sv")).collect())
    assert out == {1: 20.0, 2: 10.0, 3: 10.0}

    def has_sort_above_agg(node):
        if isinstance(node, TpuSortExec) and not node.is_global:
            return True
        return any(has_sort_above_agg(c) for c in node.children)
    assert has_sort_above_agg(s1.last_plan())
    s1.stop()

    s2 = TpuSession.builder.getOrCreate()
    s2.createDataFrame(data).groupBy("k").agg(F.sum("v").alias("sv")).collect()
    assert not has_sort_above_agg(s2.last_plan())


def test_dataframe_cache_golden():
    """df.cache(): later queries serve from the materialized in-memory
    table (cache_test analog; ref GpuInMemoryTableScanExec)."""
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.functions import col
    from spark_rapids_tpu.plan import logical as lp

    s = TpuSession.builder.getOrCreate()
    base = s.createDataFrame({"k": [1, 2, 1, 3] * 25, "v": [2.0] * 100})
    filtered = base.filter(col("v") > 0)
    orig_plan = filtered._plan
    filtered.cache()                 # Spark idiom: in-place side effect
    assert isinstance(filtered._plan, lp.CachedScan)
    out1 = dict(filtered.groupBy("k").agg(F.sum("v").alias("s")).collect())
    out2 = dict(filtered.groupBy("k").agg(F.count("*").alias("c")).collect())
    assert out1 == {1: 100.0, 2: 50.0, 3: 50.0}
    assert out2 == {1: 50, 2: 25, 3: 25}
    # cache of a cache is a no-op; persist accepts a storage level;
    # unpersist restores the original plan
    assert filtered.cache() is filtered
    assert filtered.persist("MEMORY_ONLY") is filtered
    # a frame derived from the cached one keeps working after unpersist
    derived = filtered.groupBy("k").agg(F.count("*").alias("c"))
    filtered.unpersist()
    assert filtered._plan is orig_plan
    assert dict(filtered.groupBy("k").agg(
        F.sum("v").alias("s")).collect()) == out1
    assert dict(derived.collect()) == out2
    # dropping every reference reclaims the cached batch (the session's
    # last-plan capture holds one until the next query replaces it)
    import gc
    import weakref
    owner_ref = weakref.ref(derived._plan.children[0].owner)
    del derived
    s._last_exec_plan = None
    s._last_overrides = None
    gc.collect()
    gc.collect()
    assert owner_ref() is None


def test_span_breakdown_names_query_time():
    """The per-query span report (trace_span -> SpanRecorder) names where
    execute time goes: q1-shaped query must show the hot regions with
    nonzero self time, and span self-times must be nesting-deduplicated
    (each <= executeTimeS-ish wall, not elapsed-of-parent double counts)."""
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.functions import col

    s = TpuSession.builder.config(
        {"spark.rapids.tpu.sql.explain": "NONE"}).getOrCreate()
    df = s.createDataFrame({
        "k": [i % 5 for i in range(1000)],
        "v": [float(i % 97) for i in range(1000)]})
    (df.filter(col("v") > 3)
       .groupBy("k")
       .agg(F.sum("v").alias("sv"), F.avg("v").alias("av"))
       .orderBy("k").collect())
    m = s.last_query_metrics()
    spans = m["spans"]
    assert spans, "span report must not be empty"
    # reserved query-level scalars ride next to the per-name records
    assert spans["wallS"] > 0.0 and spans["concurrency"] >= 0.0
    for name, rec in spans.items():
        if name in ("wallS", "concurrency", "semaphoreHoldS"):
            continue
        assert rec["selfS"] >= 0.0 and rec["count"] >= 1, (name, rec)
    # the aggregate/sort pipeline must be named
    assert any(n in spans for n in ("aggregate", "fused_project",
                                    "fused_filter_project", "sort",
                                    "op_TpuSortExec")), spans
