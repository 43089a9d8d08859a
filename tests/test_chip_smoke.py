"""``chip_smoke.py``'s failure rules, exercised on the CPU backend at
SF0.002 — so "no silent pass" is itself guarded at no chip time. The
script's ``main`` refuses to run here at all; ``run``/``verdict`` are the
platform-agnostic halves the rules live in."""

import json
import logging
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
SF = 0.002


@pytest.fixture(scope="module", autouse=True)
def _default_compile_conf_afterwards():
    """The smoke turns the managed compile cache on (process-global);
    later files on this worker get the default back."""
    yield
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.exec import compile_cache
    TpuSession.builder.config(
        {"spark.rapids.tpu.sql.explain": "NONE"}).getOrCreate()
    compile_cache.configure(None)


@pytest.fixture(scope="module")
def clean_records():
    """One real pass of the smoke's phases (q6 + q1, oracle children and
    all) on whatever backend the suite runs on."""
    return chip_smoke.run(SF, ("q6", "q1"))


def test_clean_run_still_fails_off_the_tpu(clean_records):
    """Every phase clean and verified — and the verdict is still
    ``ok: false``, for exactly one reason: the platform is not ``tpu``."""
    assert [r["faults"] for r in clean_records] == [[]] * len(clean_records)
    verified = {r["query"]: r["verified"] for r in clean_records
                if r["phase"] == "verify"}
    assert verified == {"q6": True, "q1": True}
    out = chip_smoke.verdict(clean_records, CPU, 1, ("q6", "q1"))
    assert out["ok"] is False
    assert out["failures"] == ["platform is 'cpu', not 'tpu'"]
    # the same records on the device the contract names: a pass
    assert chip_smoke.verdict(clean_records, V5E, 1, ("q6", "q1")) == {
        "ok": True, "device": V5E}
    # ... but not with a query missing, or on the wrong number of chips
    assert not chip_smoke.verdict(clean_records, V5E, 1,
                                  ("q6", "q1", "q3"))["ok"]
    assert not chip_smoke.verdict(clean_records, V5E, 4, ("q6", "q1"))["ok"]


def test_main_refuses_to_run_without_a_tpu(capsys, monkeypatch):
    monkeypatch.setattr(chip_smoke, "run", lambda *a, **k: pytest.fail(
        "a query phase started without a TPU"))
    assert chip_smoke.main([]) == 1
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"


def test_injected_fusion_warning_fails_the_smoke(monkeypatch):
    """A fused program that fell back to per-op eager still answers
    correctly; only the warning says so — and the smoke listens."""
    from benchmarks import queries as Q
    real_q6 = Q.QUERIES["q6"]

    def q6_with_fallback(tables):
        logging.getLogger(chip_smoke.FUSION_LOGGER).warning(
            "whole-stage program fell back to per-op eager for stage "
            "#0 (injected)")
        return real_q6(tables)

    monkeypatch.setitem(Q.QUERIES, "q6", q6_with_fallback)
    records = chip_smoke.run(SF, ("q6",))
    query = next(r for r in records if r["phase"] == "query")
    assert any("fusion warning" in f and "injected" in f
               for f in query["faults"])
    # right rows, and still a failure on the right device
    assert next(r for r in records if r["phase"] == "verify")["verified"]
    out = chip_smoke.verdict(records, V5E, 1, ("q6",))
    assert out["ok"] is False and "fusion warning" in out["failures"][0]


class _FakeOracles:
    def __init__(self, rows=None, error=None):
        self._rows, self._error = rows, error

    def rows(self, name, timeout_s):
        if self._error:
            raise RuntimeError(self._error)
        return self._rows


@pytest.mark.parametrize("oracle,result,fault", [
    (_FakeOracles([(1, 2.0)]), [(1, 2.5)], "rows differ"),
    (_FakeOracles([(1, 2.0), (2, 3.0)]), [(1, 2.0)], "rows differ"),
    (_FakeOracles(error="oracle child for q6 failed (rc=1)"),
     [(1, 2.0)], "raised RuntimeError"),
    (_FakeOracles([(1, 2.0)]), None, "no rows to compare"),
])
def test_wrong_or_missing_rows_fail_the_smoke(oracle, result, fault):
    rec = chip_smoke.verify_query("q6", result, oracle, 1.0)
    assert rec["verified"] is False and fault in rec["faults"][0]
    assert not chip_smoke.verdict([rec], V5E, 1, ("q6",))["ok"]
    # within the oracle's epsilon is a match
    ok = chip_smoke.verify_query("q6", [(1, 2.0 + 1e-6)],
                                 _FakeOracles([(1, 2.0)]), 1.0)
    assert ok["verified"] is True and ok["faults"] == []


def test_cpu_fallback_node_and_raised_query_fail_the_smoke(monkeypatch):
    from benchmarks import queries as Q
    from spark_rapids_tpu.api.session import TpuSession
    session = TpuSession.builder.config(
        {"spark.rapids.tpu.sql.explain": "NONE"}).getOrCreate()
    monkeypatch.setattr(session, "assert_on_tpu", lambda: (_ for _ in ())
                        .throw(AssertionError("Sort ran on CPU; explain:")))
    session.createDataFrame({"a": [1, 2, 3]}).collect()
    assert chip_smoke.plan_faults(session) == [
        "cpu fallback: Sort ran on CPU; explain:"]

    def boom(tables):
        raise ValueError("planner exploded")
    monkeypatch.setitem(Q.QUERIES, "q6", boom)
    rec = chip_smoke.run_query(session, {}, "q6",
                               chip_smoke.FusionWarnings())
    assert rec["faults"] == ["raised ValueError: planner exploded"]
    assert "resultRows" not in rec


def test_boundary_counts_are_exact_and_a_dropped_boundary_fails(
        clean_records, monkeypatch):
    """The check that would have caught ROADMAP M1: float64 equality and
    BETWEEN at values the column holds, against numpy, exact. A count that
    loses its boundary rows (here: the engine's rows are right and the
    oracle's column moved, which reads the same) fails the smoke."""
    import numpy as np
    from benchmarks import datagen
    from spark_rapids_tpu.api.session import TpuSession
    rec = next(r for r in clean_records if r["phase"] == "boundary")
    d = datagen.gen_lineitem(SF)["l_discount"]
    assert rec["faults"] == []
    assert rec["counts"]["l_discount = 0.05"] == int((d == 0.05).sum()) > 0
    assert rec["counts"]["l_discount BETWEEN 0.05 AND 0.07"] == int(
        ((d >= 0.05) & (d <= 0.07)).sum())
    assert len(rec["counts"]) == 5

    session = TpuSession.builder.config(
        {"spark.rapids.tpu.sql.explain": "NONE"}).getOrCreate()
    datagen.register_tables(session, SF)
    real = datagen.gen_lineitem
    monkeypatch.setattr(datagen, "gen_lineitem", lambda sf: dict(
        real(sf), l_discount=np.nextafter(real(sf)["l_discount"], 1.0)))
    bad = chip_smoke.boundary_counts(session, SF)
    assert len(bad["faults"]) == 5 and "numpy counts 0" in bad["faults"][0]
    assert not chip_smoke.verdict([bad], V5E, 1, ())["ok"]
