"""Fusion-parity suite (ISSUE 11 acceptance): every TPC-H/TPC-DS bench
plan runs with ``spark.rapids.tpu.sql.fusion.wholeStage`` ON vs OFF and
must produce identical results — the stage compiler may only change how
many programs a stage lowers to, never what they compute.

Named ``test_zz_*`` so it runs after the golden suites have warmed the
process-global fused cache at the same scale (the assertions do not
depend on the warmth — a cold run just pays the compiles twice)."""

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


def corpus_test(cases):
    """The parametrised corpus test over ``cases`` — a factory, so the
    ``test_zz_fusion_parity_s1`` / ``_s2`` files can each run a third of
    the corpus: ``--dist loadfile`` balances whole files, and 60 queries
    in one file pinned a single worker for ten minutes at the run's tail."""
    @pytest.mark.parametrize("suite,qname", cases,
                             ids=[f"{s}/{n}" for s, n in cases])
    def test_fusion_on_off_parity(corpus, suite, qname):
        session, tables = corpus
        qfn = Q.QUERIES[qname] if suite == "tpch" else DS.TPCDS_QUERIES[qname]
        on = qfn(tables[suite]).collect_batch().fetch_to_host().rows()
        session.conf.set("spark.rapids.tpu.sql.fusion.wholeStage", "false")
        try:
            off = qfn(tables[suite]).collect_batch().fetch_to_host().rows()
        finally:
            session.conf.set("spark.rapids.tpu.sql.fusion.wholeStage", "true")
        assert len(on) == len(off), (len(on), len(off))
        # row order is part of parity for ordered queries; float cells compare
        # to aggregation tolerance (one-program XLA reduction order may differ
        # from the per-op programs at ~1e-7 rel)
        for i, (ra, rb) in enumerate(zip(on, off)):
            assert len(ra) == len(rb) and all(
                _cells_equal(a, b) for a, b in zip(ra, rb)), (i, ra, rb)
    return test_fusion_on_off_parity


test_fusion_on_off_parity = corpus_test(_CASES[0::3])


