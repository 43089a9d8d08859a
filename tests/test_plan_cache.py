"""Serving front door units (ISSUE 12, docs/plan_cache.md): plan
parameterization, the parameterized-plan cache, prepared statements,
the result cache's snapshot/invalidation, and the cached-binding
validation policy (analysis/contracts.validate_cached_binding)."""

import datetime

import pandas as pd
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.functions import col, lit


def _session(**conf):
    from spark_rapids_tpu.api.session import TpuSession
    base = {"spark.rapids.tpu.sql.explain": "NONE"}
    base.update(conf)
    return TpuSession.builder.config(base).getOrCreate()


def _dates_df(session):
    df = session.createDataFrame(pd.DataFrame({
        "d": pd.to_datetime(["1994-01-05", "1994-06-01",
                             "1995-02-01", "1995-07-07"]).date,
        "v": [1.0, 2.0, 3.0, 4.0]}))
    df.createOrReplaceTempView("t")
    return df


def _q6ish(df, lo, hi, qty):
    """q6-shaped: parameterizable filter chain folded under an agg."""
    return (df.filter((col("v") >= lit(lo)) & (col("v") < lit(hi)) &
                      (col("k") < lit(qty)))
            .agg(F.sum(col("v") * col("k")).alias("s")))


def _kv_df(session, n=512):
    return session.createDataFrame({
        "k": [i % 11 for i in range(n)],
        "v": [float(i) for i in range(n)]})


# ---------------------------------------------------------------------------
# Parameterization
# ---------------------------------------------------------------------------

def test_parameterize_extracts_filter_literals_and_slots_are_structural():
    import copy
    from spark_rapids_tpu.plan import logical as lp
    from spark_rapids_tpu.plan import plan_cache as pc
    from spark_rapids_tpu.ops import expressions as ex
    session = _session()
    df = _kv_df(session)

    def analyzed(lo, hi, qty):
        plan = copy.deepcopy(_q6ish(df, lo, hi, qty).logical_plan())
        return lp.analyze(plan)

    p1 = analyzed(1.0, 9.0, 5)
    params = pc.parameterize(p1)
    assert len(params) == 3
    assert [p.slot for p in params] == [0, 1, 2]
    assert all(isinstance(p, ex.Parameter) for p in params)
    f1 = pc.plan_fingerprint(p1)
    # different literal VALUES: identical fingerprint
    p2 = analyzed(3.0, 200.0, 8)
    pc.parameterize(p2)
    assert pc.plan_fingerprint(p2) == f1
    # different STRUCTURE: different fingerprint
    p3 = analyzed(1.0, 9.0, 5)
    p3 = lp.analyze(lp.Limit(p3, 7))
    pc.parameterize(p3)
    assert pc.plan_fingerprint(p3) != f1


def test_uncacheable_plans_fingerprint_none_but_run():
    from spark_rapids_tpu.plan import plan_cache as pc
    session = _session()
    df = _kv_df(session, 64)
    # nondeterministic expression: rand() plans must re-plan per run
    q = df.withColumn("r", F.rand(seed=7)).agg(F.sum("v").alias("s"))
    q.collect()
    assert session._last_serving["planCache"] == "uncacheable"
    assert session._last_serving["fingerprint"] is None
    q.collect()                      # still runs fine, still uncached
    assert pc.serving_stats(session)["planHits"] == 0


def test_plan_cache_hit_with_changed_literals_compiles_nothing():
    from spark_rapids_tpu.analysis import recompile
    session = _session()
    df = _kv_df(session)
    r1 = _q6ish(df, 1.0, 300.0, 6).collect()
    snap = recompile.snapshot()
    r2 = _q6ish(df, 2.0, 400.0, 9).collect()
    bad = {k: v for k, v in recompile.delta(snap).items()
           if v.get("compiles")}
    assert not bad, bad
    st = session.serving_stats()
    assert st["planHits"] == 1 and st["plansBuilt"] == 1, st
    assert r1 != r2                   # the literals really did change
    # oracle: fresh planning (cache off) agrees
    s2 = _session(**{"spark.rapids.tpu.sql.planCache.enabled": "false"})
    df2 = _kv_df(s2)
    assert _q6ish(df2, 2.0, 400.0, 9).collect() == r2


def test_param_traced_vs_eager_parity():
    """The fused (traced-argument) evaluation of a parameterized filter
    agrees with the per-op eager path."""
    session = _session()
    df = _kv_df(session)
    q = df.filter((col("v") >= lit(100.0)) & (col("k") < lit(7))) \
          .select((col("v") * lit(2.0)).alias("w"))
    fused = sorted(q.collect())
    s_off = _session(**{
        "spark.rapids.tpu.sql.wholeStageFusion.enabled": "false"})
    df_off = _kv_df(s_off)
    q_off = df_off.filter((col("v") >= lit(100.0)) & (col("k") < lit(7))) \
                  .select((col("v") * lit(2.0)).alias("w"))
    assert sorted(q_off.collect()) == fused


def test_conf_mutation_never_serves_a_stale_plan():
    from spark_rapids_tpu.plan.stage_compiler import TpuWholeStageExec
    session = _session()
    df = _kv_df(session)
    q = df.select((col("v") + lit(1.0)).alias("a"), col("k")) \
          .filter(col("a") > lit(10.0))
    q.collect()
    session.conf.set("spark.rapids.tpu.sql.fusion.wholeStage", "false")
    q.collect()

    def walk(n):
        yield n
        for c in n.children:
            yield from walk(c)
    assert not [n for n in walk(session.last_plan())
                if isinstance(n, TpuWholeStageExec)]


# ---------------------------------------------------------------------------
# Prepared statements
# ---------------------------------------------------------------------------

def test_prepared_statement_plans_once_executes_many():
    from spark_rapids_tpu.analysis import recompile
    session = _session()
    _dates_df(session)
    stmt = session.prepare(
        "SELECT sum(v) AS s FROM t WHERE d >= :lo AND d < :hi")
    assert stmt.parameter_names == ["hi", "lo"]
    r94 = stmt.collect(lo=datetime.date(1994, 1, 1),
                       hi=datetime.date(1995, 1, 1))
    assert r94 == [(3.0,)]
    snap = recompile.snapshot()
    r95 = stmt.collect(lo=datetime.date(1995, 1, 1),
                       hi=datetime.date(1996, 1, 1))
    assert r95 == [(7.0,)]
    bad = {k: v for k, v in recompile.delta(snap).items()
           if v.get("compiles")}
    assert not bad, bad
    st = session.serving_stats()
    # EXACTLY one parse / analyze / plan-build across both executions
    assert st["parses"] == 1 and st["analyzes"] == 1 and \
        st["plansBuilt"] == 1, st
    assert st["planHits"] >= 1, st
    # ISO strings bind as dates too
    assert stmt.collect(lo="1994-01-01", hi="1996-01-01") == [(10.0,)]


def test_prepared_statement_binding_errors():
    session = _session()
    _dates_df(session)
    stmt = session.prepare("SELECT sum(v) AS s FROM t WHERE v > :x")
    with pytest.raises(ValueError, match="missing"):
        stmt.execute()
    with pytest.raises(ValueError, match="unexpected"):
        stmt.execute(x=1.0, y=2.0)
    with pytest.raises(ValueError, match="NULL"):
        stmt.execute(x=None)


def test_prepared_statement_dtype_change_replans():
    session = _session()
    _dates_df(session)
    stmt = session.prepare("SELECT sum(v) AS s FROM t WHERE v > :x")
    assert stmt.collect(x=2)[0][0] == 7.0      # INT64 plan
    assert stmt.collect(x=2.5)[0][0] == 7.0    # FLOAT64: new fingerprint
    st = session.serving_stats()
    assert st["plansBuilt"] == 2, st
    # back to int: the first entry still serves
    assert stmt.collect(x=3)[0][0] == 4.0
    assert session.serving_stats()["plansBuilt"] == 2


def test_prepared_statement_param_in_unsupported_position_raises():
    session = _session()
    _dates_df(session)
    stmt = session.prepare("SELECT sum(v) AS s FROM t GROUP BY :g")
    with pytest.raises(ValueError, match="supported in WHERE"):
        stmt.execute(g=1)


def test_prepared_non_aggregate_select_works():
    """prepare() must not crash on non-aggregate SELECTs: the parser's
    schema probes analyze throwaway copies BEFORE the first bind, so an
    unbound placeholder types as NULLTYPE there (review finding)."""
    session = _session()
    _dates_df(session)
    stmt = session.prepare("SELECT v FROM t WHERE v > :x")
    assert sorted(stmt.collect(x=2.0)) == [(3.0,), (4.0,)]
    assert sorted(stmt.collect(x=3.0)) == [(4.0,)]
    star = session.prepare("SELECT * FROM t WHERE v > :x")
    assert len(star.collect(x=2.0)) == 2


def test_placeholders_correct_with_plan_cache_disabled():
    """With planCache.enabled=false, placeholders still get slots (an
    unslotted pair would collide on one fused-program key and silently
    serve a stale baked value — review finding)."""
    session = _session(**{"spark.rapids.tpu.sql.planCache.enabled":
                          "false"})
    session.createDataFrame({"v": [float(i) for i in range(10)]}) \
        .createOrReplaceTempView("nums")
    stmt = session.prepare(
        "SELECT sum(v) AS s FROM nums WHERE v >= :lo AND v < :hi")
    assert stmt.collect(lo=2.0, hi=5.0) == [(9.0,)]
    assert stmt.collect(lo=3.0, hi=8.0) == [(25.0,)]
    assert stmt.collect(lo=0.0, hi=10.0) == [(45.0,)]


def test_coerced_and_arith_wrapped_params_stay_fused(caplog):
    """The analyzer coerces placeholder dtypes with Casts (:q bound to a
    LONG against a DOUBLE column) and prepared trees keep arithmetic
    around placeholders (:d - 10.0). Both scalar folds run inside the
    fused trace, where their pure-numpy literal paths would concretize
    the traced parameter and silently degrade the whole stage to eager —
    they must compile into the program instead. Value-dependent-null
    folds (x / :z) can't, and must fall back with correct results."""
    import logging
    session = _session()
    session.createDataFrame({"v": [float(i) for i in range(100)]}) \
        .createOrReplaceTempView("nums")
    with caplog.at_level(logging.WARNING,
                         logger="spark_rapids_tpu.fusion"):
        stmt = session.prepare("SELECT sum(v) AS s FROM nums WHERE v < :q")
        assert stmt.collect(q=24)[0][0] == float(sum(range(24)))
        assert stmt.collect(q=30)[0][0] == float(sum(range(30)))
        arith = session.prepare("SELECT sum(v) AS s FROM nums "
                                "WHERE v >= :d - 10.0 AND v < :d + 10.0")
        assert arith.collect(d=30.0)[0][0] == float(sum(range(20, 40)))
        assert arith.collect(d=50.0)[0][0] == float(sum(range(40, 60)))
    eager = [r for r in caplog.records
             if "fell back to eager" in r.getMessage()]
    assert not eager, [r.getMessage() for r in eager]
    # div-by-param nullness depends on the traced value: eager, but right
    div = session.prepare("SELECT sum(v) AS s FROM nums WHERE v < 100.0 / :z")
    assert div.collect(z=2.0)[0][0] == float(sum(range(50)))
    assert div.collect(z=4.0)[0][0] == float(sum(range(25)))


def test_string_param_rebind_never_serves_stale_program():
    """Non-traceable (string) parameter values bake into the compiled
    programs AND the plan fingerprint, so the prepared fast path must
    NOT rebind a cached entry in place — the whole-stage exec's frozen
    program would serve the previous value's rows (review finding:
    m='RAIL' returned m='AIR' rows). Each distinct value gets its own
    plan-cache entry instead, which still hits on repeats."""
    session = _session()
    session.createDataFrame({
        "v": [1.0, 2.0, 3.0], "m": ["AIR", "RAIL", "AIR"]}) \
        .createOrReplaceTempView("ship")
    stmt = session.prepare("SELECT v FROM ship WHERE m = :m")
    assert sorted(stmt.collect(m="AIR")) == [(1.0,), (3.0,)]
    assert sorted(stmt.collect(m="RAIL")) == [(2.0,)]
    # flip back and forth: the per-value entries keep serving correctly
    assert sorted(stmt.collect(m="AIR")) == [(1.0,), (3.0,)]
    assert sorted(stmt.collect(m="RAIL")) == [(2.0,)]
    st = session.serving_stats()
    assert st["plansBuilt"] == 2 and st["planHits"] == 2, st


def test_result_hit_clears_span_recorder():
    """A result-cache hit runs nothing, so the session must not keep the
    PREVIOUS query's span recorder — a timeline export after the hit
    would attribute the old query's spans to this collect."""
    session = _session(**{
        "spark.rapids.tpu.sql.resultCache.enabled": "true"})
    df = _kv_df(session, 64)
    q = df.filter(col("v") >= lit(3.0)).agg(F.sum("v").alias("s"))
    q.collect()
    assert session._last_span_recorder is not None
    q.collect()                       # exact repeat: short-circuits
    assert session._last_serving["resultCache"] == "hit"
    assert session._last_span_recorder is None


def test_tainted_entry_discarded_after_error_mode_drift():
    """An error-mode drift raise must DISCARD the tainted entry so a
    clean retry replans instead of re-raising forever (review
    finding)."""
    from spark_rapids_tpu.analysis.contracts import PlanContractError
    from spark_rapids_tpu.columnar import dtypes as dt
    session = _session(**{
        "spark.rapids.tpu.sql.analysis.validatePlan": "error"})
    df = _kv_df(session)
    _q6ish(df, 1.0, 300.0, 6).collect()
    entry = _entry_for_last(session)
    entry.validated_dtypes = (dt.STRING,) + entry.validated_dtypes[1:]
    with pytest.raises(PlanContractError):
        _q6ish(df, 2.0, 300.0, 6).collect()
    # the retry replans cleanly (a poisoned entry would re-raise)
    r = _q6ish(df, 2.0, 300.0, 6).collect()
    assert r and session.serving_stats()["plansBuilt"] == 2


def test_prepared_dataframe_shares_the_plan_cache():
    session = _session()
    df = _kv_df(session)
    stmt = session.prepare(_q6ish(df, 1.0, 300.0, 6))
    r1 = stmt.execute().rows()
    r2 = stmt.execute().rows()
    assert r1 == r2
    st = session.serving_stats()
    assert st["plansBuilt"] == 1 and st["planHits"] >= 1, st


# ---------------------------------------------------------------------------
# Result cache
# ---------------------------------------------------------------------------

def test_result_cache_exact_repeat_short_circuits():
    session = _session(**{"spark.rapids.tpu.sql.resultCache.enabled":
                          "true"})
    df = _kv_df(session)
    q = _q6ish(df, 1.0, 300.0, 6)
    r1 = q.collect()
    r2 = q.collect()
    assert r1 == r2
    st = session.serving_stats()
    assert st["resultStores"] >= 1 and st["resultHits"] == 1, st
    # the serving line in EXPLAIN ANALYZE names the hit
    assert "resultCache=hit" in session.explain_analyze()
    # a different literal misses the result cache but hits the plan cache
    _q6ish(df, 2.0, 300.0, 6).collect()
    st = session.serving_stats()
    assert st["resultHits"] == 1 and st["planHits"] >= 2, st


def test_result_cache_invalidates_on_view_swap():
    session = _session(**{"spark.rapids.tpu.sql.resultCache.enabled":
                          "true"})
    _dates_df(session)
    q = "SELECT sum(v) AS s FROM t WHERE v > 0"
    assert session.sql(q).collect() == [(10.0,)]
    # new data under the same view name: a NEW base table identity, so
    # neither the plan fingerprint nor the result snapshot can alias
    df2 = session.createDataFrame({"d": [datetime.date(1994, 1, 2)],
                                   "v": [100.0]})
    df2.createOrReplaceTempView("t")
    assert session.sql(q).collect() == [(100.0,)]


def test_result_cache_byte_bound_and_entry_bound():
    from spark_rapids_tpu.plan.plan_cache import ResultCache
    rc = ResultCache(max_bytes=1000, max_entry_bytes=400)
    rc.put(("a",), "batch-a", 300)
    rc.put(("b",), "batch-b", 300)
    rc.put(("big",), "batch-big", 500)       # over maxEntryBytes: refused
    assert rc.get(("big",)) is None
    assert rc.get(("a",)) == "batch-a"
    rc.put(("c",), "batch-c", 300)
    rc.put(("d",), "batch-d", 300)           # evicts LRU (b)
    assert rc.get(("b",)) is None
    assert rc.bytes <= 1000


# ---------------------------------------------------------------------------
# Cached-binding validation (the contracts satellite)
# ---------------------------------------------------------------------------

def _entry_for_last(session):
    from spark_rapids_tpu.plan import plan_cache as pc
    cache, _rc = pc.session_caches(session)
    return cache.peek(session._last_serving["fingerprint"])


def test_binding_dtype_drift_retriggers_validation():
    from spark_rapids_tpu.columnar import dtypes as dt
    session = _session()
    df = _kv_df(session)
    _q6ish(df, 1.0, 300.0, 6).collect()
    st0 = session.serving_stats()
    assert st0["revalidations"] == 0
    entry = _entry_for_last(session)
    assert entry is not None and entry.params
    # seeded drift: pretend the entry was validated with another dtype
    # (a parameter substitution that changed a bound ref's dtype)
    entry.validated_dtypes = (dt.STRING,) + entry.validated_dtypes[1:]
    _q6ish(df, 2.0, 300.0, 6).collect()
    st = session.serving_stats()
    # the hit did NOT skip validation: the full walk re-ran, the tainted
    # entry was discarded, and the query replanned
    assert st["revalidations"] == 1, st
    assert st["plansBuilt"] == 2, st
    # the rebuilt entry serves clean hits again (validation skipped)
    _q6ish(df, 3.0, 300.0, 6).collect()
    st = session.serving_stats()
    assert st["revalidations"] == 1 and st["planHits"] >= 1, st


def test_binding_dtype_drift_error_mode_raises():
    from spark_rapids_tpu.analysis.contracts import PlanContractError
    from spark_rapids_tpu.columnar import dtypes as dt
    session = _session(**{
        "spark.rapids.tpu.sql.analysis.validatePlan": "error"})
    df = _kv_df(session)
    _q6ish(df, 1.0, 300.0, 6).collect()
    entry = _entry_for_last(session)
    entry.validated_dtypes = (dt.STRING,) + entry.validated_dtypes[1:]
    with pytest.raises(PlanContractError, match="rebound"):
        _q6ish(df, 2.0, 300.0, 6).collect()


def test_validate_cached_binding_unit():
    from spark_rapids_tpu.analysis import contracts as C
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.ops import expressions as ex

    class _Root:
        children = ()
    p = ex.Parameter(5, dt.INT64, slot=0)
    # clean binding: validation skipped
    reval, violations = C.validate_cached_binding(
        _Root(), [p], (dt.INT64,), "warn")
    assert not reval and not violations
    # drifted dtype: full revalidation with a drift violation
    reval, violations = C.validate_cached_binding(
        _Root(), [p], (dt.FLOAT64,), "warn")
    assert reval and any("rebound" in v.message for v in violations)
    # off mode: never validates
    assert C.validate_cached_binding(
        _Root(), [p], (dt.FLOAT64,), "off") == (False, [])


# ---------------------------------------------------------------------------
# Telemetry / EXPLAIN surfaces
# ---------------------------------------------------------------------------

def test_serving_counters_reach_the_metrics_registry():
    session = _session()
    df = _kv_df(session)
    _q6ish(df, 1.0, 300.0, 6).collect()
    _q6ish(df, 2.0, 300.0, 6).collect()
    text = session.prometheus_metrics()
    assert "tpu_plan_cache_hits_total" in text
    assert "tpu_plan_cache_misses_total" in text


def test_serving_series_ride_the_history_gate():
    """plan_cache_plans_per_s (higher better) and warm_traffic_q6_s
    (lower better) are series of the regression gate."""
    from benchmarks import history as bh
    assert bh.WARM_TRAFFIC_Q6_S in bh.INVERTED_QUERIES
    assert bh.PLAN_CACHE_PLANS_PER_S not in bh.INVERTED_QUERIES
    entry = bh.round_entry(
        "bench", {bh.PLAN_CACHE_PLANS_PER_S: 80.0,
                  bh.WARM_TRAFFIC_Q6_S: 0.5}, backend="cpu")
    assert bh._hib_for(entry, bh.WARM_TRAFFIC_Q6_S) is False
    assert bh._hib_for(entry, bh.PLAN_CACHE_PLANS_PER_S) is True
    # a slower warm-traffic window FAILS against a faster baseline
    v = bh.verdict_for(1.0, 0.5, higher_is_better=False)
    assert v["verdict"] == "fail"


def test_explain_analyze_shows_serving_line():
    session = _session()
    df = _kv_df(session)
    _q6ish(df, 1.0, 300.0, 6).collect()
    out = session.explain_analyze()
    assert "serving: planCache=miss" in out
    _q6ish(df, 2.0, 300.0, 6).collect()
    out = session.explain_analyze()
    assert "serving: planCache=hit" in out and "params=3" in out
