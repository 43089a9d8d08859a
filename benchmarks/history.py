"""Bench round history + regression gate.

A bench number means something only next to the rounds before it, and a
round that errored must never become the bar later rounds are judged
against. The rule:

* every bench / multichip / runner / replay round APPENDS one line to a
  history JSONL (``benchmarks/reports/bench_history.jsonl``, made at run
  time), keyed by query, carrying its backend label and error state;
* errored rounds are recorded but EXCLUDED from baselines and never
  judged;
* each new round is stamped with a per-query regression verdict against
  the best prior clean round **on the same backend** (a cpu round judged
  against an accelerator baseline is noise, not signal):
  ``fail`` at >= 25% worse, ``warn`` at >= 10% worse, ``improvement``
  when better, ``ok`` in between, ``no-baseline`` for a first round.

``benchmarks/runner.py``, ``benchmarks/replay.py`` and the
multichip dryrun all stamp through :func:`stamp`; the verdicts ride the
artifact JSON so a slow round is visible in the round itself.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional

WARN_PCT = 0.10
FAIL_PCT = 0.25

#: serving front-door series (ISSUE 12, docs/plan_cache.md; no harness
#: stamps them today): PLAN_CACHE_PLANS_PER_S is the steady-state rate
#: of plan-cache-served q6 executions with ROTATING literals (parse +
#: analyze + rebind + execute per iteration; higher is better) —
#: the plans/s the serving tier can sustain; WARM_TRAFFIC_Q6_S is the
#: wall seconds of that warm literal-rotating traffic window (lower is
#: better).
PLAN_CACHE_PLANS_PER_S = "plan_cache_plans_per_s"
WARM_TRAFFIC_Q6_S = "warm_traffic_q6_s"

#: traffic-replay series stamped by benchmarks/replay.py (ISSUE 15,
#: docs/service.md §7): REPLAY_QPS is completed queries per second of N
#: concurrent mixed-tenant TPC-H streams through ONE engine under
#: lockdep=enforce (higher is better); REPLAY_P50_S / REPLAY_P99_S are
#: the submit->result latency percentiles of that traffic (lower is
#: better) — the first p99-under-concurrent-load numbers the north star
#: asks for. REPLAY_CHAOS_P99_S is the same p99 with the chaos harness
#: armed (--faults), stamped only when results matched the fault-free
#: oracle and every armed fault fired.
REPLAY_QPS = "replay_qps"
REPLAY_P50_S = "replay_p50_s"
REPLAY_P99_S = "replay_p99_s"
REPLAY_CHAOS_P99_S = "replay_chaos_p99_s"
#: REPLAY_PREEMPT_P99_S is the gold-tenant p99 of the preemption-armed
#: mixed-priority leg (scheduler policy=wfq, ISSUE 20): high-priority
#: latency while low-priority work is being suspended/resumed around it
#: (lower is better; stamped only when >=1 suspend/resume cycle was
#: actually observed and every query, preempted ones included, returned
#: oracle-correct rows).
REPLAY_PREEMPT_P99_S = "replay_preempt_p99_s"

#: cold-path series stamped by benchmarks/runner.py --prewarm and
#: benchmarks/replay.py (ISSUE 17, docs/compile.md §5): COLD_Q6_S is the
#: FRESH-PROCESS wall seconds of q6 served with a warmed compile-cache
#: dir and prewarm — the first-touch latency the async pool + prewarm
#: exist to kill (lower is better; stamped only when the honesty checks
#: pass: rows identical to the sync path, zero query-triggered cold
#: compiles on the query thread). FIRST_ROW_P99_S is the p99 of
#: submit->first-batch wall seconds across the replay bench's streaming
#: queries (lower is better) — the time-to-first-row the streaming
#: collect exists to shrink.
COLD_Q6_S = "cold_q6_s"
FIRST_ROW_P99_S = "first_row_p99_s"

#: queries whose direction flips relative to their round's
#: ``higherIsBetter`` flag (seconds-valued series riding a throughput
#: round): recorded per entry so old history lines stay judgeable
INVERTED_QUERIES = frozenset({WARM_TRAFFIC_Q6_S,
                              REPLAY_P50_S, REPLAY_P99_S,
                              REPLAY_CHAOS_P99_S, REPLAY_PREEMPT_P99_S,
                              COLD_Q6_S, FIRST_ROW_P99_S})

#: default history file (each bench round is a fresh process; the file
#: is what gives the gate memory across rounds on one machine)
DEFAULT_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "reports", "bench_history.jsonl")


def default_path() -> str:
    """The history file every stamper uses unless told otherwise. The
    env override exists so the TEST suite (which drives bench/dryrun
    code paths) never appends synthetic rounds to a measured history."""
    return os.environ.get("SPARK_RAPIDS_TPU_BENCH_HISTORY") or DEFAULT_PATH


def load(path: Optional[str] = None) -> List[Dict]:
    """Every parseable round in the history file, in append order.
    Corrupt lines are skipped — a torn write from a killed round must
    not take the whole gate down."""
    path = path or default_path()
    if not os.path.exists(path):
        return []
    out: List[Dict] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                entry = json.loads(line)
            except ValueError:
                continue
            if isinstance(entry, dict) and "queries" in entry:
                out.append(entry)
    return out


def append(entry: Dict, path: Optional[str] = None) -> str:
    """Append one round line (parent dirs created defensively)."""
    path = path or default_path()
    parent = os.path.dirname(os.path.abspath(path))
    os.makedirs(parent, exist_ok=True)
    with open(path, "a") as f:
        f.write(json.dumps(entry) + "\n")
    return path


def round_entry(kind: str, queries: Dict[str, float], *, backend: str,
                error: Optional[str] = None,
                higher_is_better: bool = True,
                meta: Optional[Dict] = None) -> Dict:
    """Build one history line. ``kind`` namespaces the comparison series
    (e.g. ``bench``, ``multichip``, ``runner-tpch-sf0.01``): values are
    only ever compared within one kind. ``queries`` maps query name ->
    the round's number (Mrows/s for BENCH — higher better; hot seconds
    for the runner — lower better)."""
    entry = {
        "atS": round(time.time(), 3),
        "kind": kind,
        "backend": backend,
        "higherIsBetter": bool(higher_is_better),
        "queries": {q: v for q, v in queries.items() if v is not None},
    }
    inverted = sorted(q for q in entry["queries"] if q in INVERTED_QUERIES)
    if inverted:
        # per-query direction override (seconds series inside a
        # throughput round): the gate flips higherIsBetter for these
        entry["invertedQueries"] = inverted
    if error:
        entry["error"] = str(error)[:400]
    if meta:
        entry["meta"] = meta
    return entry


def _hib_for(entry: Dict, query: str) -> bool:
    """Effective direction for one query in one round: the round's
    ``higherIsBetter`` flag, flipped for its ``invertedQueries``."""
    hib = bool(entry.get("higherIsBetter", True))
    if query in entry.get("invertedQueries", ()) or \
            query in INVERTED_QUERIES:
        return not hib
    return hib


def _clean(entry: Dict, kind: str, backend: str) -> bool:
    """A round usable as baseline: same series, same backend, not
    errored."""
    return (entry.get("kind") == kind and
            entry.get("backend") == backend and
            not entry.get("error"))


def baseline(history: List[Dict], kind: str, backend: str,
             query: str, higher_is_better: bool = True) -> Optional[float]:
    """Best prior clean same-backend value for ``query`` (max when higher
    is better, min otherwise); None with no usable prior round. Zero /
    negative values never qualify — a zeroed metric is a failed round,
    not a record."""
    vals = [e["queries"][query] for e in history
            if _clean(e, kind, backend) and
            isinstance(e["queries"].get(query), (int, float)) and
            e["queries"][query] > 0]
    if not vals:
        return None
    return max(vals) if higher_is_better else min(vals)


def verdict_for(value: Optional[float], base: Optional[float],
                higher_is_better: bool = True) -> Dict:
    """One query's regression verdict vs its baseline."""
    if value is None or value <= 0:
        return {"verdict": "no-measurement", "baseline": base}
    if base is None:
        return {"verdict": "no-baseline", "value": value}
    # normalized so positive change == better, regardless of direction
    if higher_is_better:
        change = (value - base) / base
    else:
        change = (base - value) / base
    out = {"value": value, "baseline": base,
           "changePct": round(change * 100, 2)}
    if change <= -FAIL_PCT:
        out["verdict"] = "fail"
    elif change <= -WARN_PCT:
        out["verdict"] = "warn"
    elif change > 0:
        out["verdict"] = "improvement"
    else:
        out["verdict"] = "ok"
    return out


def verdicts(history: List[Dict], entry: Dict) -> Dict[str, Dict]:
    """Per-query verdicts for ``entry`` against ``history``. An errored
    round is never judged (its values are infra artifacts): every query
    reads ``excluded``."""
    kind = entry["kind"]
    backend = entry["backend"]
    out: Dict[str, Dict] = {}
    for q, v in entry["queries"].items():
        if entry.get("error"):
            out[q] = {"verdict": "excluded",
                      "reason": "errored round: recorded, never judged "
                                "or used as baseline"}
            continue
        hib = _hib_for(entry, q)
        out[q] = verdict_for(v, baseline(history, kind, backend, q, hib),
                             hib)
    return out


def worst(vs: Dict[str, Dict]) -> str:
    """The round's overall verdict (the single word a dashboard shows)."""
    order = ("fail", "warn", "no-measurement", "ok", "improvement",
             "no-baseline", "excluded")
    present = {v.get("verdict") for v in vs.values()}
    for level in order:
        if level in present:
            return level
    return "no-data"


def stamp(kind: str, queries: Dict[str, float], *, backend: str,
          error: Optional[str] = None,
          higher_is_better: bool = True, meta: Optional[Dict] = None,
          path: Optional[str] = None) -> Dict:
    """The one-call gate: verdicts for this round against the existing
    history, then append the round so the NEXT one sees it. Returns
    ``{"verdicts": {q: ...}, "overall": str, "rounds": n}``. Never
    raises — a broken history file downgrades to no-baseline verdicts,
    and an unwritable file loses persistence, not the round's report."""
    path = path or default_path()
    try:
        history = load(path)
    except Exception:
        history = []
    entry = round_entry(kind, queries, backend=backend, error=error,
                        higher_is_better=higher_is_better, meta=meta)
    vs = verdicts(history, entry)
    entry["regression"] = {q: v.get("verdict") for q, v in vs.items()}
    try:
        append(entry, path)
    except Exception:
        pass
    return {"verdicts": vs, "overall": worst(vs),
            "rounds": len(history) + 1}
