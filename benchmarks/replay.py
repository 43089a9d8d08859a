"""Traffic-replay benchmark: N concurrent TPC-H streams, one engine.

The bench suite so far measured queries ONE AT A TIME — the "heavy
traffic from millions of users" scenario (ROADMAP item 4) was invisible:
no number said what p99 latency or queries/second this engine sustains
when concurrent tenants hammer shared TPU state. This module is that
measurement:

* ``streams`` worker streams (the TPC-H throughput-test shape) submit
  TPC-H-shaped queries to ONE :class:`QueryService` over ONE session,
  alternating between a high-priority ``gold`` tenant and a
  low-priority ``bronze`` tenant (mixed-tenant traffic);
* parameters ROTATE through prepared statements (the PR 12 serving
  front door): every stream re-executes the same plan with different
  literal windows, so the replay measures the serving hot path, not
  repeated planning;
* the whole replay runs under ``lockdep=enforce`` — a lock-order
  inversion anywhere in the concurrent engine fails the bench loudly;
* ``faults`` arms the chaos harness (PR 13) during the replay: results
  must still match the fault-free oracle and recovery must be absorbed
  by stage retries under concurrent load.

Artifact series (benchmarks/history.py, kind ``replay``):
``replay_qps`` (higher better), ``replay_p50_s`` / ``replay_p99_s``
(submit->result latency percentiles, lower better),
``first_row_p99_s`` (submit->FIRST-BATCH p99 of the streaming leg's
``submit_stream`` traffic, lower better), ``replay_chaos_p99_s``
for the chaos mode, and ``replay_preempt_p99_s`` (gold p99 of the
preemption-armed mixed-priority leg, --preempt: weighted-fair
scheduling suspends a running low-priority query so the high-priority
arrival runs first, then resumes it — ISSUE 20). Stamped only when
every query returned oracle-correct rows (under chaos, every armed
fault additionally fired; under --preempt, at least one suspend/resume
cycle was additionally observed) — a wrong-answer replay is void, not
fast.

CLI::

    python -m benchmarks.replay --sf 0.002 --streams 4 --iters 6
    python -m benchmarks.replay --faults "fetch.fail;task.poison"
    python -m benchmarks.replay --preempt --iters 6
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import threading
import time
from typing import Dict, List, Optional

#: the default chaos spec for ``--faults default`` (one failed fetch +
#: one poisoned map batch, absorbed by stage retry)
DEFAULT_FAULTS = "fetch.fail;task.poison"


def _rows_close(a, b, rel_tol=1e-9) -> bool:
    """Row-wise equality with fp tolerance (retries and concurrent
    scheduling legally reorder float aggregation)."""
    if len(a) != len(b):
        return False
    for ra, rb in zip(a, b):
        if len(ra) != len(rb):
            return False
        for va, vb in zip(ra, rb):
            if isinstance(va, float) and isinstance(vb, float):
                if not math.isclose(va, vb, rel_tol=rel_tol,
                                    abs_tol=1e-12):
                    return False
            elif va != vb:
                return False
    return True


def _percentile(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank percentile over an already-sorted latency list."""
    if not sorted_vals:
        return 0.0
    idx = max(0, math.ceil(q * len(sorted_vals)) - 1)
    return sorted_vals[idx]


def _window(i: int):
    """Rotating one-year date window (epoch days), 24 phases."""
    import datetime
    lo = datetime.date(1993, 1, 1) + datetime.timedelta(days=30 * (i % 24))
    return lo, lo + datetime.timedelta(days=365)


#: the replay's prepared-statement shapes (SQL with :name placeholders
#: bound per iteration). q6-shaped: tight filter + global sum; q1-shaped:
#: filter + grouped wide aggregate. Both read the lineitem view.
_Q6_SQL = ("SELECT sum(l_extendedprice * l_discount) AS revenue "
           "FROM replay_lineitem "
           "WHERE l_shipdate >= :lo AND l_shipdate < :hi "
           "AND l_discount >= 0.05 AND l_discount <= 0.07 "
           "AND l_quantity < 24")
_Q1_SQL = ("SELECT l_returnflag, sum(l_quantity) AS sum_qty, "
           "avg(l_extendedprice) AS avg_price, count(*) AS cnt "
           "FROM replay_lineitem WHERE l_shipdate < :hi "
           "GROUP BY l_returnflag ORDER BY l_returnflag")


def _build_session(faults: Optional[str], extra_conf: Optional[dict]):
    from spark_rapids_tpu.api.session import TpuSession
    conf = {
        "spark.rapids.tpu.sql.explain": "NONE",
        # the whole replay runs under ENFORCE: any lock-order inversion
        # in the concurrent engine raises instead of logging
        "spark.rapids.tpu.sql.analysis.lockdep": "enforce",
        "spark.rapids.tpu.sql.shuffle.partitions": "4",
    }
    if faults:
        # chaos injection points live on the DCN map/fetch paths
        conf["spark.rapids.tpu.sql.shuffle.plane"] = "dcn"
        conf["spark.rapids.tpu.sql.recovery.retryBackoff"] = "0.0"
    conf.update(extra_conf or {})
    return TpuSession.builder.config(conf).getOrCreate()


def run_replay(sf: float = 0.002, streams: int = 4,
               queries_per_stream: int = 6,
               faults: Optional[str] = None,
               stamp: bool = True,
               history_path: Optional[str] = None,
               extra_conf: Optional[dict] = None) -> Dict:
    """Drive the replay and return the artifact dict (see module doc).
    ``faults`` arms the chaos harness for the traffic window (results
    still must match the fault-free oracle)."""
    import jax
    from benchmarks import datagen
    from benchmarks import queries as Q
    from spark_rapids_tpu.analysis import faults as faults_mod
    from spark_rapids_tpu.api.functions import col
    from spark_rapids_tpu.service.server import QueryService, TenantSpec
    from spark_rapids_tpu.service.telemetry import MetricsRegistry

    session = _build_session(faults, extra_conf)
    tables = datagen.register_tables(session, sf)
    tables["lineitem"].createOrReplaceTempView("replay_lineitem")

    # chaos traffic must traverse a DCN exchange (the injection points):
    # a q6-shaped aggregate over a hash-repartitioned lineitem
    shuffled = dict(tables)
    shuffled["lineitem"] = tables["lineitem"].repartition(
        4, col("l_orderkey"))

    def make_query(stream: int, i: int):
        """(kind, execute-thunk-args) for stream position i."""
        if faults:
            return ("shuffle_q6", None)
        return ("q6", _window(stream + i)) if (stream + i) % 2 == 0 \
            else ("q1", _window(stream + i))

    # ---- fault-free oracle: every (kind, params) executed DIRECTLY once
    oracle: Dict[tuple, list] = {}
    for s in range(streams):
        for i in range(queries_per_stream):
            kind, win = make_query(s, i)
            key = (kind, win)
            if key in oracle:
                continue
            if kind == "shuffle_q6":
                oracle[key] = Q.QUERIES["q6"](shuffled).collect()
            else:
                stmt = session.prepare(_Q6_SQL if kind == "q6"
                                       else _Q1_SQL)
                params = {"lo": win[0], "hi": win[1]} if kind == "q6" \
                    else {"hi": win[1]}
                oracle[key] = stmt.execute(**params).rows()

    def retries_total() -> float:
        try:
            return float(MetricsRegistry.get().counter(
                "tpu_stage_retries_total", "x").value)
        except Exception:
            return 0.0

    svc = QueryService(session, tenants=[
        TenantSpec("gold", priority=10, slots=max(1, streams // 2),
                   memory_budget_bytes=1 << 30),
        TenantSpec("bronze", priority=0, slots=max(1, streams // 2),
                   memory_budget_bytes=256 << 20)])

    latencies: List[float] = []
    first_rows: List[float] = []
    wrong: List[str] = []
    errors: List[str] = []
    lat_mu = threading.Lock()  # lint: raw-lock-ok bench-local result list, dies with the run

    # streaming leg (fault-free mode): per stream, a few queries go
    # through submit_stream and the submit->FIRST-BATCH wall is measured
    # — the time-to-first-row number the streaming collect exists to
    # shrink (ISSUE 17; stamped as first_row_p99_s). Oracle rows come
    # from the same frames' materializing collect.
    streaming_per_stream = 0 if faults else max(1, queries_per_stream // 3)
    stream_oracle: Dict[str, list] = {}
    if streaming_per_stream:
        stream_oracle = {k: Q.QUERIES[k](tables).collect()
                         for k in ("q1", "q6")}

    def stream_body(s: int) -> None:
        # one PreparedStatement per shape PER STREAM: a statement binds
        # in place, so it must never have two in-flight executes
        stmts = {"q6": session.prepare(_Q6_SQL),
                 "q1": session.prepare(_Q1_SQL)}
        tenant = "gold" if s % 2 == 0 else "bronze"
        for i in range(queries_per_stream):
            kind, win = make_query(s, i)
            if kind == "shuffle_q6":
                ticket = svc.submit(
                    tenant, Q.QUERIES["q6"](shuffled),
                    label=f"s{s}-{i}-{kind}")
            else:
                params = {"lo": win[0], "hi": win[1]} if kind == "q6" \
                    else {"hi": win[1]}
                ticket = svc.submit(tenant, stmts[kind], params=params,
                                    label=f"s{s}-{i}-{kind}")
            try:
                rows = ticket.result(timeout=600).rows()
            except Exception as e:
                with lat_mu:
                    errors.append(f"s{s}-{i}-{kind}: "
                                  f"{type(e).__name__}: {e}"[:200])
                continue
            ok = _rows_close(rows, oracle[(kind, win)])
            with lat_mu:
                latencies.append(ticket.latency_s())
                if not ok:
                    wrong.append(f"s{s}-{i}-{kind}")
        for j in range(streaming_per_stream):
            kind = "q6" if (s + j) % 2 == 0 else "q1"
            ticket = svc.submit_stream(tenant, Q.QUERIES[kind](tables),
                                       label=f"s{s}-stream{j}-{kind}")
            rows = []
            fr = None
            try:
                for b in ticket.stream():
                    if fr is None:
                        fr = time.perf_counter() - ticket.submitted_at
                    rows.extend(b.rows())
                ticket.result(timeout=600)
            except Exception as e:
                with lat_mu:
                    errors.append(f"s{s}-stream{j}-{kind}: "
                                  f"{type(e).__name__}: {e}"[:200])
                continue
            ok = _rows_close(rows, stream_oracle[kind])
            with lat_mu:
                if fr is not None:
                    first_rows.append(fr)
                if not ok:
                    wrong.append(f"s{s}-stream{j}-{kind}")

    retries0 = retries_total()
    armed = 0
    try:
        if faults:
            armed = faults_mod.install(faults)
        t0 = time.perf_counter()
        threads = [threading.Thread(target=stream_body, args=(s,),
                                    name=f"replay-stream-{s}")
                   for s in range(streams)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        fired = faults_mod.fired_total() if faults else 0
    finally:
        if faults:
            faults_mod.reset()         # never leak chaos downstream
        svc.close()
    stage_retries = retries_total() - retries0

    total = streams * queries_per_stream
    expected_streaming = streams * streaming_per_stream
    latencies.sort()
    first_rows.sort()
    qps = len(latencies) / wall if wall > 0 else 0.0
    p50 = _percentile(latencies, 0.50)
    p99 = _percentile(latencies, 0.99)
    ok = (not wrong and not errors and len(latencies) == total and
          len(first_rows) == expected_streaming and
          (not faults or (fired >= armed and stage_retries >= 1)))
    line: Dict = {
        "metric": "traffic replay",
        "backend": jax.devices()[0].platform,
        "sf": sf,
        "streams": streams,
        "queries": total,
        "completed": len(latencies),
        "wall_s": round(wall, 4),
        "replay_qps": round(qps, 3),
        "replay_p50_s": round(p50, 4),
        "replay_p99_s": round(p99, 4),
        "faults_spec": faults or "",
        "faults_fired": int(fired),
        "stage_retries": int(stage_retries),
        "replay_ok": ok,
        "service": svc.stats(),
    }
    if expected_streaming:
        line["streaming_queries"] = len(first_rows)
        line["first_row_p50_s"] = round(_percentile(first_rows, 0.50), 4)
        line["first_row_p99_s"] = round(_percentile(first_rows, 0.99), 4)
    if wrong:
        line["wrong_results"] = wrong[:10]
    if errors:
        line["errors"] = errors[:10]
    if faults:
        line["replay_chaos_p99_s"] = round(p99, 4)

    if stamp and ok:
        # the regression gate (benchmarks/history.py): replay latency
        # and throughput ride the same verdict machinery as every bench
        from benchmarks import history as bh
        if faults:
            queries = {bh.REPLAY_CHAOS_P99_S: line["replay_chaos_p99_s"]}
        else:
            queries = {bh.REPLAY_QPS: line["replay_qps"],
                       bh.REPLAY_P50_S: line["replay_p50_s"],
                       bh.REPLAY_P99_S: line["replay_p99_s"]}
            if expected_streaming:
                queries[bh.FIRST_ROW_P99_S] = line["first_row_p99_s"]
        gate = bh.stamp("replay", queries, backend=line["backend"],
                        higher_is_better=True,
                        meta={"sf": sf, "streams": streams,
                              "faults": faults or ""},
                        path=history_path)
        line["regression"] = {q: v.get("verdict")
                              for q, v in gate["verdicts"].items()}
        line["regression_overall"] = gate["overall"]
    return line


def run_preempt_replay(sf: float = 0.002, rounds: int = 6,
                       stamp: bool = True,
                       history_path: Optional[str] = None) -> Dict:
    """Preemption-armed mixed-priority leg (ISSUE 20, docs/service.md
    §4): ONE worker slot, weighted-fair scheduling with preemption ON.

    Each round submits a long low-priority ``bronze`` shuffle query,
    waits for it to occupy the slot, then a high-priority ``gold`` query
    arrives: the scheduler requests suspension of the running bronze
    query, which parks its working set at the next cancel poll; gold
    runs in the freed slot; a resumer thread re-admits the parked query,
    which must still return oracle-correct rows. Stamps
    ``replay_preempt_p99_s`` (gold submit->result p99, lower better)
    ONLY when at least one full suspend/resume cycle was actually
    observed and EVERY query — the preempted ones included — matched
    the fault-free oracle: a preemption leg where nothing got preempted
    (or a preempted query came back wrong) is void, not fast.
    """
    import jax
    from benchmarks import datagen
    from benchmarks import queries as Q
    from spark_rapids_tpu.api.functions import col
    from spark_rapids_tpu.service.server import QueryService, TenantSpec

    session = _build_session(None, {
        "spark.rapids.tpu.sql.service.scheduler.policy": "wfq",
        "spark.rapids.tpu.sql.service.scheduler.preemption": "true",
        # a preempted query's park/resume must keep the buffer ledger
        # clean — enforce raises on any leaked lifecycle, so the leg
        # doubles as the suspend-path leak check
        "spark.rapids.tpu.sql.analysis.bufferLedger": "enforce",
        # more partitions -> more per-partition cancel polls, so the
        # running bronze query reaches a suspension point quickly
        "spark.rapids.tpu.sql.shuffle.partitions": "8",
    })
    tables = datagen.register_tables(session, sf)
    tables["lineitem"].createOrReplaceTempView("replay_lineitem")
    shuffled = dict(tables)
    shuffled["lineitem"] = tables["lineitem"].repartition(
        8, col("l_orderkey"))

    # fault-free oracles, executed directly before the service opens
    bronze_oracle = Q.QUERIES["q6"](shuffled).collect()
    gold_stmt = session.prepare(_Q6_SQL)
    gold_oracle: Dict[int, list] = {}
    for i in range(rounds):
        lo, hi = _window(i)
        gold_oracle[i] = gold_stmt.execute(lo=lo, hi=hi).rows()

    # one slot total: a gold arrival while bronze runs ALWAYS finds the
    # service saturated, which is the preemption precondition. Gold's
    # larger weight keeps its service-unit clock slower, so the freed
    # slot goes to gold, not straight back to the resumed bronze.
    svc = QueryService(session, max_workers=1, tenants=[
        TenantSpec("gold", priority=10, slots=1, weight=4.0,
                   memory_budget_bytes=1 << 30),
        TenantSpec("bronze", priority=0, slots=1, weight=1.0,
                   memory_budget_bytes=256 << 20)])

    stop = threading.Event()

    def resumer() -> None:
        # the re-admission half of the cycle: parked queries go back
        # through the scheduler as soon as they are seen
        while not stop.is_set():
            for qid in svc.suspended_queries():
                try:
                    svc.resume(qid)
                except Exception:
                    # a ticket resumed by a racing pass or a closing
                    # service is not a bench failure
                    pass
            stop.wait(0.01)

    gold_lat: List[float] = []
    wrong: List[str] = []
    errors: List[str] = []
    bronze_tickets = []
    res_thread = threading.Thread(target=resumer, daemon=True,
                                  name="preempt-replay-resumer")
    res_thread.start()
    try:
        for i in range(rounds):
            bt = svc.submit("bronze", Q.QUERIES["q6"](shuffled),
                            label=f"bronze-{i}")
            bronze_tickets.append((i, bt))
            # wait for bronze to actually occupy the slot (preemption
            # only targets RUNNING queries)
            deadline = time.perf_counter() + 5.0
            while time.perf_counter() < deadline:
                if svc.stats()["running"] >= 1:
                    break
                time.sleep(0.002)
            lo, hi = _window(i)
            gt = svc.submit("gold", gold_stmt,
                            params={"lo": lo, "hi": hi},
                            label=f"gold-{i}")
            try:
                rows = gt.result(timeout=600).rows()
            except Exception as e:
                errors.append(f"gold-{i}: {type(e).__name__}: {e}"[:200])
                continue
            gold_lat.append(gt.latency_s())
            if not _rows_close(rows, gold_oracle[i]):
                wrong.append(f"gold-{i}")
        # the preempted queries must come back and come back RIGHT
        for i, bt in bronze_tickets:
            try:
                rows = bt.result(timeout=600).rows()
            except Exception as e:
                errors.append(f"bronze-{i}: {type(e).__name__}: {e}"[:200])
                continue
            if not _rows_close(rows, bronze_oracle):
                wrong.append(f"bronze-{i}")
    finally:
        stop.set()
        res_thread.join(timeout=5)
        stats = svc.stats()
        svc.close()

    bronze_stats = stats["tenants"]["bronze"]
    preempted = int(bronze_stats["preempted"])
    resumed = int(bronze_stats["resumed"])
    gold_lat.sort()
    p99 = _percentile(gold_lat, 0.99)
    # honesty: the leg is void without >=1 OBSERVED suspend/resume
    # cycle — otherwise it silently degrades into a plain WFQ replay
    ok = (not wrong and not errors and len(gold_lat) == rounds and
          preempted >= 1 and resumed >= 1)
    line: Dict = {
        "metric": "preempt replay",
        "backend": jax.devices()[0].platform,
        "sf": sf,
        "rounds": rounds,
        "gold_completed": len(gold_lat),
        "preempted": preempted,
        "resumed": resumed,
        "replay_preempt_p99_s": round(p99, 4),
        "replay_ok": ok,
        "service": stats,
    }
    if wrong:
        line["wrong_results"] = wrong[:10]
    if errors:
        line["errors"] = errors[:10]
    if stamp and ok:
        from benchmarks import history as bh
        gate = bh.stamp(
            "replay",
            {bh.REPLAY_PREEMPT_P99_S: line["replay_preempt_p99_s"]},
            backend=line["backend"], higher_is_better=True,
            meta={"sf": sf, "mode": "preempt", "rounds": rounds},
            path=history_path)
        line["regression"] = {q: v.get("verdict")
                              for q, v in gate["verdicts"].items()}
        line["regression_overall"] = gate["overall"]
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="concurrent mixed-tenant TPC-H traffic replay "
                    "through the multi-tenant query service")
    ap.add_argument("--sf", type=float, default=0.002,
                    help="TPC-H scale factor of the generated tables")
    ap.add_argument("--streams", type=int, default=4,
                    help="concurrent submission streams")
    ap.add_argument("--iters", type=int, default=6,
                    help="queries per stream")
    ap.add_argument("--faults", default=None,
                    help="chaos spec for the replay window ('default' = "
                         f"{DEFAULT_FAULTS!r})")
    ap.add_argument("--preempt", action="store_true",
                    help="run the preemption-armed mixed-priority leg "
                         "(wfq + suspend/resume) instead of the stream "
                         "replay")
    ap.add_argument("--no-stamp", action="store_true",
                    help="skip the bench-history regression stamp")
    args = ap.parse_args(argv)
    # a measurement entry point: no TPU, no replay numbers (the library
    # functions above stay backend-agnostic — the tests drive them on CPU)
    from benchmarks.preflight import require_chip
    require_chip()
    if args.preempt:
        line = run_preempt_replay(sf=args.sf, rounds=args.iters,
                                  stamp=not args.no_stamp)
    else:
        faults = DEFAULT_FAULTS if args.faults == "default" else args.faults
        line = run_replay(sf=args.sf, streams=args.streams,
                          queries_per_stream=args.iters, faults=faults,
                          stamp=not args.no_stamp)
    print(json.dumps(line, default=str))
    return 0 if line.get("replay_ok") else 1


if __name__ == "__main__":
    sys.exit(main())
