"""Two-plane shuffle exchange (docs/shuffle.md): ICI collective routing
under a mesh, DCN fallback, forced planes, the pipelined map-side split's
O(1)-syncs-per-stage property, plane telemetry, and the exchange-plane
plan contract. Runs on the virtual 8-device CPU mesh from conftest.
"""

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.functions import col
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.shuffle.exchange import (TpuHashExchangeExec,
                                               TpuShuffleExchangeExec,
                                               plane_totals, shuffle_report)


def _session(**conf):
    return TpuSession.builder.config(
        {"spark.rapids.tpu.sql.explain": "NONE", **conf}).getOrCreate()


def _find(node, klass):
    out = [node] if isinstance(node, klass) else []
    for c in node.children:
        out.extend(_find(c, klass))
    return out


def _df(n=4000, seed=3):
    rng = np.random.default_rng(seed)
    return pd.DataFrame({"k": rng.integers(0, 50, n).astype("int64"),
                         "v": rng.normal(0, 1, n)})


def _roundtrip_rows(got, df):
    assert sorted(((int(k), round(float(v), 9)) for k, v in got)) == \
        sorted((int(k), round(float(v), 9)) for k, v in zip(df.k, df.v))


# ---------------------------------------------------------------------------
# Plane routing
# ---------------------------------------------------------------------------

def test_auto_plane_picks_ici_under_mesh():
    s = _session(**{"spark.rapids.tpu.sql.mesh.enabled": "true"})
    df = _df()
    got = s.createDataFrame(df).repartition(4, col("k")).collect()
    _roundtrip_rows(got, df)
    exes = _find(s.last_plan(), TpuShuffleExchangeExec)
    assert exes and all(e.plane_used == "ici" for e in exes), \
        [(type(e).__name__, e.plane, e.plane_used) for e in exes]
    rep = shuffle_report(s.last_plan())
    assert rep and rep[0]["plane"] == "ici"
    assert rep[0]["bytesWritten"] > 0 and rep[0]["bytesRead"] > 0


def test_auto_plane_falls_back_to_dcn_without_mesh():
    s = _session(**{"spark.rapids.tpu.sql.mesh.enabled": "false"})
    df = _df(seed=5)
    got = s.createDataFrame(df).repartition(4, col("k")).collect()
    _roundtrip_rows(got, df)
    exes = _find(s.last_plan(), TpuShuffleExchangeExec)
    assert exes and all(e.plane_used == "dcn" for e in exes)
    assert all(e.mesh is None for e in exes)


def test_forced_dcn_under_mesh_still_correct():
    s = _session(**{"spark.rapids.tpu.sql.mesh.enabled": "true",
                    "spark.rapids.tpu.sql.shuffle.plane": "dcn"})
    df = _df(seed=7)
    got = s.createDataFrame(df).repartition(4, col("k")).collect()
    _roundtrip_rows(got, df)
    exes = _find(s.last_plan(), TpuShuffleExchangeExec)
    assert exes and all(e.plane_used == "dcn" for e in exes)


def test_forced_ici_without_mesh_fails_at_plan_time():
    s = _session(**{"spark.rapids.tpu.sql.mesh.enabled": "false",
                    "spark.rapids.tpu.sql.shuffle.plane": "ici"})
    with pytest.raises(RuntimeError, match="plane=ici"):
        s.createDataFrame(_df()).repartition(4, col("k")).collect()


def test_ici_declines_string_free_schemas_only_when_nested():
    """STRING payloads ride the ICI plane (flat 3-array protocol)."""
    s = _session(**{"spark.rapids.tpu.sql.mesh.enabled": "true"})
    rng = np.random.default_rng(11)
    df = pd.DataFrame({"k": rng.integers(0, 20, 800).astype("int64"),
                       "s": [f"name-{i % 13}" for i in range(800)]})
    got = s.createDataFrame(df).repartition(4, col("k")).collect()
    assert sorted((int(k), v) for k, v in got) == \
        sorted((int(k), v) for k, v in zip(df.k, df.s))
    exes = _find(s.last_plan(), TpuShuffleExchangeExec)
    assert exes and all(e.plane_used == "ici" for e in exes)


# ---------------------------------------------------------------------------
# Multichip shuffle join over ICI exchanges: correct + O(1) syncs/stage
# ---------------------------------------------------------------------------

ICI_JOIN_CONF = {
    "spark.rapids.tpu.sql.mesh.enabled": "true",
    # a tiny maxStageBytes declines the fused TpuMeshJoinExec route, so
    # the planner emits hash exchanges — which the forced plane then
    # routes over collectives: a real shuffled join on the ICI plane
    "spark.rapids.tpu.sql.mesh.maxStageBytes": "1",
    "spark.rapids.tpu.sql.shuffle.plane": "ici",
    "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": "-1",
}


def test_ici_shuffled_join_correct():
    s = _session(**ICI_JOIN_CONF)
    rng = np.random.default_rng(17)
    left = _df(3000, seed=13)
    right = pd.DataFrame({"b": rng.integers(0, 70, 500).astype("int64"),
                          "y": rng.integers(0, 9, 500).astype("int64")})
    got = (s.createDataFrame(left)
           .join(s.createDataFrame(right), on=(col("k") == col("b")),
                 how="inner").collect())
    exes = _find(s.last_plan(), TpuHashExchangeExec)
    assert len(exes) == 2 and all(e.plane_used == "ici" for e in exes)
    exp = left.merge(right, left_on="k", right_on="b", how="inner")
    got_rows = sorted((int(k), round(float(v), 9), int(b), int(y))
                      for k, v, b, y in got)
    exp_rows = sorted((int(r.k), round(float(r.v), 9), int(r.b), int(r.y))
                      for r in exp.itertuples())
    assert got_rows == exp_rows


def test_q3_shaped_ici_shuffle_join_o1_syncs_per_stage():
    """BASELINE milestone 4 / ISSUE 8 acceptance: a q3-shaped multichip
    3-way shuffle join over the ICI plane pays O(1) host syncs per
    stage — each collective exchange reads back exactly ONE packed
    counts array (span-attributed under shuffle_write), and no sizing
    readback rides the fetch side at all."""
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
    s = _session(**ICI_JOIN_CONF)
    s.createDataFrame(line).createOrReplaceTempView("p_lineitem")
    s.createDataFrame(orders).createOrReplaceTempView("p_orders")
    s.createDataFrame(cust).createOrReplaceTempView("p_customer")
    df = s.sql(
        "SELECT l_price, o_date, c_seg FROM p_lineitem "
        "JOIN p_orders ON l_order = o_key "
        "JOIN p_customer ON o_cust = c_key "
        "WHERE o_date < 700 AND c_seg = 1")
    rows = df.collect()
    exp = (line.merge(orders, left_on="l_order", right_on="o_key")
               .merge(cust, left_on="o_cust", right_on="c_key"))
    exp = exp[(exp.o_date < 700) & (exp.c_seg == 1)]
    assert len(rows) == len(exp)
    exes = _find(s.last_plan(), TpuShuffleExchangeExec)
    assert len(exes) == 4 and all(e.plane_used == "ici" for e in exes)
    sync = s.last_query_metrics()["sync"]
    # each ICI exchange = ONE counts readback inside its shuffle_write
    # span, plus — only for the one exchange fed by the join, whose output
    # row counts are still device-resident — ONE packed readback of those
    # counts to size the shards: O(1) per exchange, never one per batch
    # (resolve_counts reading k counts as k syncs is what this caught)
    assert sync["syncSpans"].get("shuffle_write", 0) <= len(exes) + 1, sync
    # and the fetch side (run slicing) never syncs
    assert sync["syncSpans"].get("shuffle_fetch", 0) == 0, sync


# ---------------------------------------------------------------------------
# DCN plane: the pipelined map-side split packs its sizing readbacks
# ---------------------------------------------------------------------------

def _dcn_join_syncs(depth: int):
    rng = np.random.default_rng(7)
    n = 16384
    line = pd.DataFrame({"l_order": rng.integers(0, 1000, n).astype("int64"),
                         "l_price": rng.normal(100.0, 10.0, n)})
    orders = pd.DataFrame({"o_key": np.arange(1000, dtype="int64"),
                           "o_cust": rng.integers(0, 100, 1000).astype("int64")})
    s = _session(**{
        "spark.rapids.tpu.sql.mesh.enabled": "false",
        "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": "-1",
        "spark.rapids.tpu.sql.shuffle.pipelineDepth": str(depth),
        "spark.rapids.tpu.sql.reader.batchSizeRows": "1024"})
    got = (s.createDataFrame(line)
           .join(s.createDataFrame(orders),
                 on=(col("l_order") == col("o_key")), how="inner").collect())
    assert len(got) == n
    sync = s.last_query_metrics()["sync"]
    return sync["syncSpans"].get("pipeline_resolve", 0), sync


def test_dcn_map_split_sizing_packs_into_o1_resolves():
    """The 16-batch stream exchange must NOT pay one sizing readback per
    batch: with the split window deep enough, the whole map phase packs
    into a handful of batched resolves — strictly fewer than the batch
    count, and strictly fewer than the depth-1 (read-per-batch) run of
    the identical query."""
    stream_batches = 16
    packed, sync = _dcn_join_syncs(depth=32)
    assert packed < stream_batches, sync
    per_batch, _ = _dcn_join_syncs(depth=1)
    assert packed < per_batch, (packed, per_batch)
    # every counted sync is span-attributed (no unattributed leaks)
    assert sum(sync["syncSpans"].values()) == sync["hostSyncs"]


# ---------------------------------------------------------------------------
# Telemetry + contract
# ---------------------------------------------------------------------------

def test_plane_totals_and_telemetry_gauges():
    before = plane_totals()
    s = _session(**{"spark.rapids.tpu.sql.mesh.enabled": "true"})
    df = _df(seed=23)
    s.createDataFrame(df).repartition(4, col("k")).collect()
    after = plane_totals()
    assert after["ici_exchanges"] > before["ici_exchanges"]
    assert after["ici_bytes"] > before["ici_bytes"]
    assert after["ici_seconds"] > before["ici_seconds"]
    from spark_rapids_tpu.service.telemetry import (MetricsRegistry,
                                                    compact_snapshot)
    snap = MetricsRegistry.get().collect()
    fam = snap.get("tpu_shuffle_exchanges_total")
    assert fam is not None
    planes = {dict(s0["labels"]).get("plane"): s0["value"]
              for s0 in fam["samples"]}
    assert planes.get("ici", 0) >= after["ici_exchanges"] - 1
    compact = compact_snapshot()
    assert "shufflePlanes" in compact and "ici" in compact["shufflePlanes"]
    assert compact["shufflePlanes"]["ici"]["exchanges"] >= 1


def test_exchange_plane_contract_flags_forced_ici_without_mesh():
    """The plan-contract validator knows the exchange's plane shape: a
    plane forced to ici with no mesh attached is a structural violation
    (validate_plan), independent of the plan-time RuntimeError."""
    from spark_rapids_tpu.analysis.contracts import validate_plan
    from spark_rapids_tpu.plan.physical import TpuLocalScanExec
    from spark_rapids_tpu.ops.expressions import ColumnRef
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    b = ColumnarBatch.from_pydict({"k": [1, 2, 3], "v": [1.0, 2.0, 3.0]})
    scan = TpuLocalScanExec(b.to_arrow(), b.schema)
    ex = TpuShuffleExchangeExec(scan, 4, [ColumnRef("k").resolve(b.schema)],
                                plane="ici", mesh=None)
    violations = validate_plan(ex)
    assert any("ici" in v.message and "mesh" in v.message
               for v in violations), violations
    # a well-formed auto exchange is clean
    ok = TpuShuffleExchangeExec(scan, 4, [ColumnRef("k").resolve(b.schema)])
    assert not [v for v in validate_plan(ok)
                if "plane" in v.message or "mesh" in v.message]
