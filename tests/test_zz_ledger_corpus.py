"""Corpus leak audit (ISSUE 19 acceptance): every TPC-H/TPC-DS bench
plan runs under ``spark.rapids.tpu.sql.analysis.bufferLedger=enforce``
and must finish leak-free — a device buffer minted by the query and
still catalog-resident past collect end raises
:class:`~spark_rapids_tpu.analysis.ledger.BufferLeakError` inside the
collect, which IS the assertion. Use-after-free and use-after-donate
also raise at their access sites here, so the whole corpus doubles as
a runtime exercise of the donation/spill/staging hand-off discipline.

Named ``test_zz_*`` so it runs after the golden suites have warmed the
process-global fused cache at the same scale (warmth only saves
compiles — the audit is per-query and cache-independent)."""

import pytest

from benchmarks import datagen, queries as Q, tpcds_queries as DS
from spark_rapids_tpu.analysis import ledger

_SF = 0.002

_CASES = ([("tpch", n) for n in sorted(Q.QUERIES)] +
          [("tpcds", n) for n in sorted(DS.TPCDS_QUERIES)])


@pytest.fixture(scope="module")
def corpus():
    from spark_rapids_tpu.api.session import TpuSession
    session = TpuSession.builder.config({
        "spark.rapids.tpu.sql.explain": "NONE",
        "spark.rapids.tpu.sql.analysis.bufferLedger": "enforce",
    }).getOrCreate()
    assert ledger.mode() == "enforce"
    yield session, {"tpch": datagen.register_tables(session, _SF),
                    "tpcds": datagen.register_tpcds_tables(session, _SF)}
    # back to the suite-wide record default (conftest env conf)
    ledger.install("record")


def corpus_test(cases):
    """The parametrised corpus test over ``cases`` — a factory, so the
    ``test_zz_ledger_corpus_s1`` / ``_s2`` files can each run a third of
    the corpus: ``--dist loadfile`` balances whole files, and 60 queries
    in one file pinned a single worker for ten minutes at the run's tail."""
    @pytest.mark.parametrize("suite,qname", cases,
                             ids=[f"{s}/{n}" for s, n in cases])
    def test_corpus_leak_free_under_enforce(corpus, suite, qname):
        session, tables = corpus
        qfn = Q.QUERIES[qname] if suite == "tpch" else DS.TPCDS_QUERIES[qname]
        # enforce mode: a leak raises BufferLeakError from inside collect
        rows = qfn(tables[suite]).collect_batch().fetch_to_host().rows()
        assert rows is not None
        led = session._last_ledger
        assert led is not None, "end-of-query audit must run under enforce"
        assert led["leakedBuffers"] == 0, led
    return test_corpus_leak_free_under_enforce


test_corpus_leak_free_under_enforce = corpus_test(_CASES[0::3])


