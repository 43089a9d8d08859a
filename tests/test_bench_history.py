"""Bench history + regression gate (ISSUE 7, benchmarks/history.py):
round-over-round verdicts against the best prior clean same-backend
round, with errored rounds recorded but never judged or used as
baselines."""

import json
import os

from benchmarks import history as bh


def _clean_round(kind="bench", backend="tpu", **queries):
    return bh.round_entry(kind, queries, backend=backend)


def test_verdicts_clean_to_clean_improvement(tmp_path):
    path = str(tmp_path / "h.jsonl")
    bh.append(_clean_round(fused=100.0), path)
    gate = bh.stamp("bench", {"fused": 120.0}, backend="tpu", path=path)
    v = gate["verdicts"]["fused"]
    assert v["verdict"] == "improvement"
    assert v["baseline"] == 100.0 and v["changePct"] == 20.0
    assert gate["overall"] == "improvement"
    # ... and the new round became history: a same-value follow-up is ok
    gate2 = bh.stamp("bench", {"fused": 120.0}, backend="tpu", path=path)
    assert gate2["verdicts"]["fused"]["verdict"] == "ok"
    assert gate2["verdicts"]["fused"]["baseline"] == 120.0


def test_seeded_regression_warns_and_fails(tmp_path):
    path = str(tmp_path / "h.jsonl")
    bh.append(_clean_round(fused=200.0), path)
    # 12% down: warn
    warn = bh.stamp("bench", {"fused": 176.0}, backend="tpu", path=path)
    assert warn["verdicts"]["fused"]["verdict"] == "warn"
    # 30% down vs the BEST prior clean round (still 200): fail
    fail = bh.stamp("bench", {"fused": 140.0}, backend="tpu", path=path)
    v = fail["verdicts"]["fused"]
    assert v["verdict"] == "fail" and v["baseline"] == 200.0
    assert fail["overall"] == "fail"


def test_errored_round_excluded_from_baseline_and_never_judged(tmp_path):
    path = str(tmp_path / "h.jsonl")
    bh.append(_clean_round(fused=200.0), path)
    # a failed round: recorded with its error ...
    dark = bh.stamp("bench", {"fused": 3.0}, backend="tpu",
                    error="device lost mid-run", path=path)
    assert dark["verdicts"]["fused"]["verdict"] == "excluded"
    # ... but the NEXT clean round is judged against 200, not 3
    nxt = bh.stamp("bench", {"fused": 198.0}, backend="tpu", path=path)
    v = nxt["verdicts"]["fused"]
    assert v["baseline"] == 200.0 and v["verdict"] == "ok"


def test_backend_series_never_cross(tmp_path):
    """A cpu round must not be judged against an accelerator baseline
    (and vice versa) — cross-backend comparison is noise."""
    path = str(tmp_path / "h.jsonl")
    bh.append(_clean_round(fused=200.0, backend="tpu"), path)
    cpu = bh.stamp("bench", {"fused": 2.0}, backend="cpu", path=path)
    assert cpu["verdicts"]["fused"]["verdict"] == "no-baseline"


def test_lower_is_better_direction(tmp_path):
    """Runner series store hot SECONDS: lower is better, so a higher
    value regresses."""
    path = str(tmp_path / "h.jsonl")
    bh.append(bh.round_entry("runner-tpch-sf0.01", {"q1": 1.0},
                             backend="cpu", higher_is_better=False), path)
    worse = bh.stamp("runner-tpch-sf0.01", {"q1": 1.4}, backend="cpu",
                     higher_is_better=False, path=path)
    assert worse["verdicts"]["q1"]["verdict"] == "fail"
    better = bh.stamp("runner-tpch-sf0.01", {"q1": 0.8}, backend="cpu",
                      higher_is_better=False, path=path)
    v = better["verdicts"]["q1"]
    assert v["verdict"] == "improvement" and v["baseline"] == 1.0


def test_zeroed_and_missing_values(tmp_path):
    """A zero value (a round that measured nothing) is never a
    baseline and reads no-measurement when judged."""
    path = str(tmp_path / "h.jsonl")
    bh.append(_clean_round(fused=0.0), path)          # zeroed clean round
    gate = bh.stamp("bench", {"fused": 50.0, "other": 0.0},
                    backend="tpu", path=path)
    assert gate["verdicts"]["fused"]["verdict"] == "no-baseline"
    assert gate["verdicts"]["other"]["verdict"] == "no-measurement"


def test_history_tolerates_corrupt_lines(tmp_path):
    path = str(tmp_path / "h.jsonl")
    bh.append(_clean_round(fused=100.0), path)
    with open(path, "a") as f:
        f.write("{torn json line\n")
        f.write("42\n")
    bh.append(_clean_round(fused=110.0), path)
    h = bh.load(path)
    assert [e["queries"]["fused"] for e in h] == [100.0, 110.0]
    assert bh.baseline(h, "bench", "tpu", "fused") == 110.0


def test_stamp_appends_round_with_verdict_summary(tmp_path):
    path = str(tmp_path / "h.jsonl")
    bh.stamp("bench", {"fused": 100.0}, backend="tpu", path=path,
             meta={"rows": 123})
    lines = [json.loads(x) for x in open(path).read().splitlines()]
    assert len(lines) == 1
    assert lines[0]["queries"] == {"fused": 100.0}
    assert lines[0]["regression"] == {"fused": "no-baseline"}
    assert lines[0]["meta"] == {"rows": 123}


def test_seeded_history_gates_the_next_round(tmp_path):
    """A history holding clean rounds and errored ones: the next clean
    round is judged against the best prior CLEAN round, with the errored
    rounds excluded however they sort."""
    path = str(tmp_path / "seed.jsonl")
    for v in (5.05, 221.13, 220.43):
        bh.append(_clean_round(fused_pipeline=v), path)
    for _ in range(2):
        bh.stamp("bench", {"fused_pipeline": 0}, backend="tpu",
                 error="device unreachable", path=path)
    h = bh.load(path)
    assert len(h) == 5
    base = bh.baseline(h, "bench", "tpu", "fused_pipeline")
    assert base == 221.13
    # a 30%-down next round would FAIL loudly instead of shipping unseen
    v = bh.verdict_for(154.0, base)
    assert v["verdict"] == "fail"


def test_default_path_honors_env(tmp_path, monkeypatch):
    monkeypatch.setenv("SPARK_RAPIDS_TPU_BENCH_HISTORY",
                       str(tmp_path / "env.jsonl"))
    assert bh.default_path() == str(tmp_path / "env.jsonl")
    bh.stamp("bench", {"fused": 1.0}, backend="cpu")
    assert os.path.exists(str(tmp_path / "env.jsonl"))
