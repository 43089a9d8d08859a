"""Tier-1 recompile gate over the full TPC-H/TPC-DS bench plan corpus
(ISSUE 10 acceptance: ``recompileFlags`` promoted from bench-report
advisory to a tier-1 gate; docs/compile.md §3).

Named ``test_zz_*`` so it runs LAST in the alphabetical tier-1 order:
by then the golden suites (test_tpch_queries / test_tpcds_queries) have
executed every corpus query once at the same scale, so the process-
global fused cache is warm and each gate execution here is cheap. The
assertions do NOT depend on that warmth — a cold first run merely
re-seeds the cache; the invariant checked is that the back-to-back
REPEAT of each query compiles NOTHING (the repeat-traffic discipline
the whole bucket/cache design exists for) and that no query's delta
trips ``recompile.flagged``."""

import json

import pytest

from benchmarks import datagen, queries as Q, tpcds_queries as DS

_SF = 0.002


@pytest.fixture(scope="module", autouse=True)
def _audit_this_corpus_only():
    """The recompile audit is process-wide, and under xdist a worker's
    history is whatever files it happened to run — some compile odd sizes
    on purpose (test_udf's 100-row rebatch, a map's 3-lane width). The
    size-class test below must judge the corpus, so this file starts with
    clean counters and cold program caches: the corpus rebuilds, and what
    it builds is what is audited."""
    import jax
    from spark_rapids_tpu.analysis import recompile
    from spark_rapids_tpu.exec import compile_cache
    jax.clear_caches()
    compile_cache.drop_program_caches()
    recompile.reset()
    yield


def _corpus(session):
    tpch = datagen.register_tables(session, _SF)
    tpcds = datagen.register_tpcds_tables(session, _SF)
    for name in sorted(Q.QUERIES):
        yield f"tpch/{name}", Q.QUERIES[name], tpch
    for name in sorted(DS.TPCDS_QUERIES):
        yield f"tpcds/{name}", DS.TPCDS_QUERIES[name], tpcds


def test_recompile_flags_clean_over_bench_corpus():
    from spark_rapids_tpu.analysis import recompile
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.exec import compile_cache
    session = TpuSession.builder.config(
        {"spark.rapids.tpu.sql.explain": "NONE"}).getOrCreate()
    repeat_offenders = {}
    flagged = {}

    def run_pair(qfn, tables):
        relief0 = compile_cache.relief_count()
        pair0 = recompile.snapshot()
        qfn(tables).collect_batch().fetch_to_host()  # may re-seed cache
        snap = recompile.snapshot()
        qfn(tables).collect_batch().fetch_to_host()  # the repeat
        rd = recompile.delta(snap)
        bad = {k: v for k, v in rd.items() if v.get("compiles")}
        flags = recompile.flagged(recompile.delta(pair0))
        # a JIT map-pressure relief landing INSIDE the pair legitimately
        # rebuilds programs between the two runs — not a discipline
        # violation; the caller retries once on a quiet window
        relieved = compile_cache.relief_count() != relief0
        return bad, flags, relieved

    for name, qfn, tables in _corpus(session):
        bad, flags, relieved = run_pair(qfn, tables)
        if (bad or flags) and relieved:
            bad, flags, _ = run_pair(qfn, tables)
        if bad:
            repeat_offenders[name] = bad
        if flags:
            flagged[name] = flags
    assert not repeat_offenders, (
        "repeat-query compiles over the bench corpus (a repeated shape "
        "must hit the fused cache):\n" +
        json.dumps(repeat_offenders, indent=1, default=str))
    assert not flagged, (
        "recompileFlags non-empty over the bench corpus:\n" +
        json.dumps(flagged, indent=1))


def test_stage_programs_ride_the_compile_audit_funnel():
    """ISSUE 11: whole-stage programs (plan/stage_compiler) classify
    cold-build vs disk-hit through exec/compile_cache like every other
    kernel family, and a repeat run of the same chain compiles nothing.
    (The corpus gate above already runs the 60 bench plans with
    ``fusion.wholeStage`` at its default ON — this pins the stage family
    explicitly.)"""
    import numpy as np
    from spark_rapids_tpu.analysis import recompile
    from spark_rapids_tpu.api.functions import col, lit
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.exec import compile_cache
    session = TpuSession.builder.config(
        {"spark.rapids.tpu.sql.explain": "NONE"}).getOrCreate()
    rng = np.random.default_rng(97)
    df = session.createDataFrame({
        "a": [float(x) for x in rng.normal(0, 10, 4096)],
        "b": [int(x) for x in rng.integers(0, 100, 4096)]})
    # literals unique to this test: the process-global fused cache must
    # not already hold the chain
    q = (df.select((col("a") * lit(7.03125)).alias("x"), col("b"))
         .filter(col("x") > lit(0.15625))
         .select((col("x") - col("b")).alias("y"), col("b"))
         .filter(col("b") != lit(63)))
    base = recompile.snapshot()
    q.collect_batch().fetch_to_host()
    d = recompile.delta(base)
    stage = {k: v for k, v in d.items() if k.startswith("stage")}
    assert stage, d
    (_fam, ent), = stage.items()
    assert ent["compiles"] == 1, ent
    # classified through the persistent-cache funnel: exactly one of
    # cold-build / disk-hit, with first-call wall seconds metered
    assert ent["coldCompiles"] + ent["diskHits"] == 1, ent
    assert ent["compileS"] >= 0.0
    # the signature was recorded in the persistent index: a second
    # process (or this one after an eviction) would classify 'disk'
    # when a cache dir is configured, 'cold' otherwise — classify() is
    # deterministic per key either way
    snap = recompile.snapshot()
    q.collect_batch().fetch_to_host()
    rd = recompile.delta(snap)
    assert not any(v.get("compiles") for v in rd.values()), rd


def test_size_class_discipline_clean_over_corpus():
    """After the corpus gate above every signature it compiled traces
    back to bucketed dimensions only —
    no string width, group bucket, or frame size leaked past the
    power-of-two size classes."""
    from spark_rapids_tpu.analysis import recompile
    leaks = recompile.size_class_report()
    assert leaks == {}, (
        "un-bucketed dimensions reached compiled signatures:\n" +
        json.dumps(leaks, indent=1))
