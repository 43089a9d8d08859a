"""Serving-parity suite + repeat-execute gate (ISSUE 12 acceptance).

Every TPC-H/TPC-DS bench plan runs through the prepared
(plan-once/execute-many) path and must produce results identical to the
direct first execution — a cached exec tree re-executed after a
parameter rebind may only change how the plan was OBTAINED, never what
it computes. The gate half pins the serving contract on q6: executing
twice with different date-range literals performs exactly one
parse/analyze/optimize/validate pass and compiles NOTHING on the second
execution, and an exact repeat short-circuits at the result cache.

Named ``test_zz_*`` so it runs after the golden suites have warmed the
process-global fused cache at this scale."""

import math

import pytest

from benchmarks import datagen, queries as Q, tpcds_queries as DS

_SF = 0.002

_CASES = ([("tpch", n) for n in sorted(Q.QUERIES)] +
          [("tpcds", n) for n in sorted(DS.TPCDS_QUERIES)])


@pytest.fixture(scope="module")
def corpus():
    from spark_rapids_tpu.api.session import TpuSession
    session = TpuSession.builder.config(
        {"spark.rapids.tpu.sql.explain": "NONE"}).getOrCreate()
    return session, {"tpch": datagen.register_tables(session, _SF),
                     "tpcds": datagen.register_tpcds_tables(session, _SF)}


def _cells_equal(a, b) -> bool:
    if isinstance(a, float) and isinstance(b, float):
        if math.isnan(a) and math.isnan(b):
            return True
        return abs(a - b) <= 1e-6 * max(1.0, abs(a), abs(b))
    return a == b


def _rows_equal(on, off):
    assert len(on) == len(off), (len(on), len(off))
    for i, (ra, rb) in enumerate(zip(on, off)):
        assert len(ra) == len(rb) and all(
            _cells_equal(a, b) for a, b in zip(ra, rb)), (i, ra, rb)


def corpus_test(cases):
    """The parametrised corpus test over ``cases`` — a factory, so the
    ``test_zz_serving_parity_s1`` / ``_s2`` files can each run a third of
    the corpus: ``--dist loadfile`` balances whole files, and 60 queries
    in one file pinned a single worker for ten minutes at the run's tail."""
    @pytest.mark.parametrize("suite,qname", cases,
                             ids=[f"{s}/{n}" for s, n in cases])
    def test_prepared_vs_direct_parity(corpus, suite, qname):
        """direct execution == prepared execute == prepared RE-execute (the
        cached-tree re-execution that serving traffic lives on)."""
        session, tables = corpus
        qfn = Q.QUERIES[qname] if suite == "tpch" else DS.TPCDS_QUERIES[qname]
        direct = qfn(tables[suite]).collect_batch().fetch_to_host().rows()
        stmt = session.prepare(qfn(tables[suite]))
        _rows_equal(direct, stmt.execute().fetch_to_host().rows())
        _rows_equal(direct, stmt.execute().fetch_to_host().rows())
    return test_prepared_vs_direct_parity


test_prepared_vs_direct_parity = corpus_test(_CASES[0::3])


def _q6_sql_dates(session, tables, lo, hi):
    from spark_rapids_tpu.api.functions import col, lit
    import spark_rapids_tpu.api.functions as F
    l = tables["lineitem"]
    return (l.filter((col("l_shipdate") >= lit(lo)) &
                     (col("l_shipdate") < lit(hi)) &
                     (col("l_discount") >= lit(0.05)) &
                     (col("l_discount") <= lit(0.07)) &
                     (col("l_quantity") < lit(24)))
            .agg(F.sum(col("l_extendedprice") * col("l_discount"))
                 .alias("revenue")))


def test_repeat_execute_gate_q6(corpus):
    """The ISSUE 12 acceptance pin: q6 twice with different date-range
    literals = ONE parse/analyze/optimize/validate pass, ZERO cold or
    in-memory compiles on the second execution, >= 1 plan-cache hit."""
    import datetime
    from spark_rapids_tpu.analysis import recompile
    from spark_rapids_tpu.api.session import TpuSession
    session = TpuSession.builder.config(
        {"spark.rapids.tpu.sql.explain": "NONE"}).getOrCreate()
    tables = datagen.register_tables(session, _SF)
    tables["lineitem"].createOrReplaceTempView("gate_lineitem")
    stmt = session.prepare(
        "SELECT sum(l_extendedprice * l_discount) AS revenue "
        "FROM gate_lineitem "
        "WHERE l_shipdate >= :lo AND l_shipdate < :hi "
        "AND l_discount >= 0.05 AND l_discount <= 0.07 "
        "AND l_quantity < 24")
    r94 = stmt.execute(lo=datetime.date(1994, 1, 1),
                       hi=datetime.date(1995, 1, 1)).rows()
    snap = recompile.snapshot()
    r95 = stmt.execute(lo=datetime.date(1995, 1, 1),
                       hi=datetime.date(1996, 1, 1)).rows()
    # ZERO cold or in-memory compiles on the literal-changed repeat
    bad = {k: v for k, v in recompile.delta(snap).items()
           if v.get("compiles")}
    assert not bad, bad
    st = session.serving_stats()
    assert st["parses"] == 1, st          # one parse pass
    assert st["analyzes"] == 1, st        # one analyze pass
    assert st["plansBuilt"] == 1, st      # one optimize/validate pass
    assert st["planHits"] >= 1, st        # served from the plan cache
    # the values really steered the result
    assert r94 != r95, (r94, r95)
    # oracle: the dataframe q6 with the same ranges agrees
    d94 = (datetime.date(1994, 1, 1) - datetime.date(1970, 1, 1)).days
    d95 = (datetime.date(1995, 1, 1) - datetime.date(1970, 1, 1)).days
    d96 = (datetime.date(1996, 1, 1) - datetime.date(1970, 1, 1)).days
    _rows_equal(r94, _q6_sql_dates(session, tables, d94, d95)
                .collect_batch().fetch_to_host().rows())
    _rows_equal(r95, _q6_sql_dates(session, tables, d95, d96)
                .collect_batch().fetch_to_host().rows())


def test_exact_repeat_short_circuits_at_result_cache(corpus):
    import datetime
    from spark_rapids_tpu.api.session import TpuSession
    session = TpuSession.builder.config({
        "spark.rapids.tpu.sql.explain": "NONE",
        "spark.rapids.tpu.sql.resultCache.enabled": "true"}).getOrCreate()
    tables = datagen.register_tables(session, _SF)
    d94 = (datetime.date(1994, 1, 1) - datetime.date(1970, 1, 1)).days
    d95 = (datetime.date(1995, 1, 1) - datetime.date(1970, 1, 1)).days
    q = _q6_sql_dates(session, tables, d94, d95)
    r1 = q.collect_batch().fetch_to_host().rows()
    r2 = q.collect_batch().fetch_to_host().rows()
    _rows_equal(r1, r2)
    st = session.serving_stats()
    assert st["resultHits"] == 1 and st["resultStores"] >= 1, st
    assert "resultCache=hit" in session.explain_analyze()
