"""BenchmarkRunner: run TPC-H-like queries, write JSON reports.

Analog of the reference's BenchmarkRunner / BenchUtils
(integration_tests/.../BenchmarkRunner.scala, tests/common/BenchUtils.scala;
docs/benchmarks.md): per-query iterations with cold/hot timings, collected row
counts, plan summaries, optional CPU-engine result verification with epsilon
(BenchUtils.compareResults epsilon=1e-4).

Usage: python -m benchmarks.runner --sf 0.01 --queries q1,q6 --iterations 2
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Optional

from . import datagen, queries as Q


def run_benchmark(sf: float = 0.01, query_names: Optional[List[str]] = None,
                  iterations: int = 2, verify: bool = False,
                  output: Optional[str] = None, suite: str = "tpch",
                  concurrent_tasks: Optional[int] = None,
                  trace_dir: Optional[str] = None,
                  history_path: Optional[str] = None,
                  compile_cache_dir: Optional[str] = None,
                  prewarm: bool = False) -> Dict:
    import os
    # no TPU, no measurement: the probe's error propagates
    from .preflight import require_chip
    probe = require_chip()
    from spark_rapids_tpu.api.session import TpuSession
    if concurrent_tasks is None:
        # pin device admission to host parallelism: the engine default (2)
        # under a 4-thread task pool makes reports measure semaphore
        # admission thrash instead of engine time
        concurrent_tasks = os.cpu_count() or 4
    if trace_dir is None and output:
        trace_dir = f"{output}.traces"
    session = TpuSession.builder.config(
        "spark.rapids.tpu.sql.explain", "NONE").config(
        "spark.rapids.tpu.sql.concurrentTpuTasks",
        concurrent_tasks).config(
        # per-query Chrome-trace timelines (exec/tracing.SpanRecorder):
        # recorded when a trace dir exists to dump them into
        "spark.rapids.tpu.sql.tracing.timeline",
        "true" if trace_dir else "false").config(
        # lock-order graph + per-lock wait/hold attribution on for bench
        # runs (the documented tests/bench default for analysis.lockdep)
        "spark.rapids.tpu.sql.analysis.lockdep", "record").config(
        # buffer-lifecycle ledger in record mode (analysis/ledger.py):
        # every bench round reports leaks/use-after-free without ever
        # failing a measurement — the lockdep discipline for HBM
        "spark.rapids.tpu.sql.analysis.bufferLedger", "record").config(
        # persistent compile cache: repeated runner invocations against
        # the same dir pay disk hits instead of cold builds
        "spark.rapids.tpu.sql.compile.cacheDir",
        compile_cache_dir or "").config(
        # cache prewarm (docs/compile.md §5): bootstrap replays the
        # hottest fused-stage signatures from the corpus beside the
        # signature index onto the background compile pool, so a fresh
        # process serves known queries with zero query-triggered builds
        "spark.rapids.tpu.sql.compile.prewarm.enabled",
        "true" if (prewarm and compile_cache_dir) else
        "false").getOrCreate()
    prewarm_info = None
    if prewarm and compile_cache_dir:
        # wait for the bootstrap-submitted prewarm builds BEFORE the
        # query loop: cold_s below then measures a genuinely prewarmed
        # first touch, and the honesty check (zero query-triggered cold
        # compiles) is meaningful
        from spark_rapids_tpu.exec import compile_pool
        compile_pool.drain(timeout_s=120.0)
        prewarm_info = compile_pool.stats()
    if trace_dir:
        # defensive: --trace-dir may name a nested path that does not
        # exist yet; a failed trace write must never fail the run
        try:
            os.makedirs(trace_dir, exist_ok=True)
        except OSError:
            trace_dir = None
    # the listener API (session.register_query_listener) delivers the
    # executed plan + metrics tree per query; the LAST capture per name
    # lands in the report as that query's per-operator metrics tree
    # (registered around the query loop below, unregistered in a finally
    # — getOrCreate can hand this session to later callers)
    captures: List = []

    if suite == "tpcds":
        from . import tpcds_queries
        queries = tpcds_queries.TPCDS_QUERIES
        register = datagen.register_tpcds_tables
    elif suite == "tpcxbb":
        from . import tpcxbb_queries
        queries = tpcxbb_queries.TPCXBB_QUERIES
        register = datagen.register_tpcds_tables
    else:
        queries = Q.QUERIES
        register = datagen.register_tables
    t_gen0 = time.perf_counter()
    tables = register(session, sf)
    gen_s = time.perf_counter() - t_gen0

    report: Dict = {"suite": suite, "sf": sf, "datagen_s": round(gen_s, 3),
                    "concurrentTpuTasks": concurrent_tasks,
                    "backend": probe["platform"],
                    "deviceProbe": probe,
                    "queries": {}}
    names = query_names or list(queries)
    try:
        for name in names:
            session.register_query_listener(captures.append)
            from spark_rapids_tpu.exec.device import TpuSemaphore
            from spark_rapids_tpu.analysis import lockdep, recompile
            qfn = queries[name]
            timings = []
            rows = 0
            sem0 = TpuSemaphore.get().stats()
            rc0 = recompile.snapshot()
            lk0 = lockdep.stats()
            from spark_rapids_tpu.analysis import ledger as _ledger
            led0 = _ledger.stats()
            for it in range(iterations):
                if it == 1:
                    # capture (listener snapshots + QueryExecution build)
                    # rides the COLD iteration only: hot_s = min of the
                    # later iterations must not time observability work
                    session.unregister_query_listener(captures.append)
                t0 = time.perf_counter()
                df = qfn(tables)
                batch = df.collect_batch().fetch_to_host()
                rows = batch.num_rows
                timings.append(round(time.perf_counter() - t0, 4))
            sem1 = TpuSemaphore.get().stats()
            entry = {
                "rows": rows,
                "cold_s": timings[0],
                "hot_s": min(timings[1:]) if len(timings) > 1 else timings[0],
                "timings_s": timings,
                # admission contention vs device occupancy, separable
                # (wait = blocked acquiring a permit; hold = acquire->release)
                "semaphore": {
                    "waitS": round(sem1["waitS"] - sem0["waitS"], 4),
                    "holdS": round(sem1["holdS"] - sem0["holdS"], 4),
                    "acquires": sem1["acquires"] - sem0["acquires"],
                },
                # distinct-compile counts across this query's iterations
                # (analysis/recompile.py): a kernel compiling per iteration
                # means its shapes never hit the fused cache
                "recompiles": recompile.delta(rc0),
            }
            # compile-time summary (exec/compile_cache): seconds this
            # query paid building programs, split cold vs persistent-
            # cache disk hit — with compile.cacheDir set, a repeat run
            # should show cold == 0
            rc = entry["recompiles"]
            compile_summary = {
                "coldCompiles": sum(v.get("coldCompiles", 0)
                                    for v in rc.values()),
                "diskHits": sum(v.get("diskHits", 0) for v in rc.values()),
                "compileS": round(sum(v.get("compileS", 0.0)
                                      for v in rc.values()), 4),
            }
            if any(compile_summary.values()):
                entry["compile"] = compile_summary
            flags = recompile.flagged(entry["recompiles"])
            if flags:
                entry["recompileFlags"] = flags
            # per-lock wait/hold deltas attributed to trace spans, next to
            # the semaphore wait/hold split (analysis/lockdep.py): which
            # lock a query's threads actually contended, and in which
            # named execute region
            locks = _lock_delta(lk0, lockdep.stats())
            if locks:
                entry["locks"] = locks
            # buffer-lifecycle verdict for this query: the end-of-query
            # audit of the LAST iteration (leaks, peak device bytes)
            # plus the run-counter deltas across all iterations — a
            # query whose iterations leak or touch dead buffers says so
            # in its own report entry
            led1 = _ledger.stats()
            led = {k: led1[k] - led0[k]
                   for k in ("leaks", "use_after_free",
                             "use_after_donate", "double_free")
                   if led1[k] - led0[k]}
            last_audit = getattr(session, "_last_ledger", None)
            if last_audit:
                entry["ledger"] = {
                    "leakedBuffers": last_audit.get("leakedBuffers", 0),
                    "leakedBytes": last_audit.get("leakedBytes", 0),
                    "peakDeviceBytes":
                        last_audit.get("peakDeviceBytes", 0),
                    **({"deltas": led} if led else {}),
                }
            elif led:
                entry["ledger"] = {"deltas": led}
            try:
                # per-exchange shuffle accounting (docs/shuffle.md): which
                # data plane each exchange took (ici collectives vs the
                # host/DCN path), bytes moved, and GB/s
                from spark_rapids_tpu.shuffle.exchange import shuffle_report
                shuffles = shuffle_report(session.last_plan())
                if shuffles:
                    entry["shuffle"] = shuffles
            except Exception:
                pass
            try:
                # stage-boundary exchange statistics + drift summary
                # (docs/observability.md §8) next to the metricsTree:
                # what each exchange actually produced (partition shape,
                # skew) and where the planner's row estimates missed —
                # the SAME artifact shapes the structured query log
                # writes, from the shared helpers
                from spark_rapids_tpu.service.query_log import (
                    drift_summary, stage_summaries)
                entry["queryId"] = session.last_query_id()
                stats = stage_summaries(session.last_plan())
                if stats:
                    entry["stageStats"] = stats
                drift = drift_summary(session.last_plan(),
                                      conf=session.conf)
                if drift["nodes"]:
                    entry["drift"] = drift
            except Exception:
                pass
            try:
                m = session.last_query_metrics()
                entry["planTimeS"] = m.get("planTimeS")
                entry["executeTimeS"] = m.get("executeTimeS")
                # sync includes the per-span breakdown (syncSpans): which named
                # execute region paid the device->host round trips
                entry["sync"] = m.get("sync")
                entry["spans"] = m.get("spans")
                # per-operator metrics tree of the captured (cold)
                # iteration (EXPLAIN ANALYZE's data, via the query
                # listener): which node paid the rows/time/syncs/recompiles
                if captures:
                    entry["metricsTree"] = [
                        {"depth": d, "operator": op,
                         "metrics": {k: (round(v, 4) if isinstance(v, float)
                                         else v)
                                     for k, v in mm.items()}}
                        for d, op, mm in captures[-1].metrics_tree]
            except Exception:
                pass
            if trace_dir:
                # Chrome-trace timeline of the last iteration in the
                # MERGED form (query-id-stamped spans, per-worker process
                # groups — open in chrome://tracing / ui.perfetto.dev):
                # a distributed run appends the remote workers' trace
                # dumps via session.merged_timeline(extra=...) and the
                # spans join under the shared query id. No recorder
                # (timeline off / short-circuited query) or a failed
                # write just skips the artifact.
                try:
                    path = os.path.join(trace_dir, f"{name}.trace.json")
                    entry["traceFile"] = session.merged_timeline(path=path)
                except Exception:
                    pass
            captures.clear()
            if verify:
                entry["verified"] = _verify(session, qfn(tables))
            report["queries"][name] = entry
    finally:
        session.unregister_query_listener(captures.append)
    # run-level size-class audit (analysis/recompile.size_class_report):
    # every compiled signature carrying a dimension that escaped the
    # power-of-two bucket discipline, traced to the leaking ints — the
    # "which un-bucketed dimension caused this recompile" answer
    from spark_rapids_tpu.analysis import recompile as _recompile
    leaks = _recompile.size_class_report()
    if leaks:
        report["sizeClassLeaks"] = leaks
    # run-level lockdep findings: order-inversion cycles (with both
    # acquisition stacks) and lock-held-across-transfer events
    from spark_rapids_tpu.analysis import lockdep
    lk = lockdep.report()
    if lk["cycles"] or lk["heldAcrossTransfer"]:
        report["lockdep"] = {
            "cycles": lk["cycles"],
            "heldAcrossTransfer": [
                {"locks": t["locks"], "transfer": t["transfer"]}
                for t in lk["heldAcrossTransfer"]],
        }
    # regression gate (benchmarks/history.py): per-query hot seconds vs
    # the best prior clean same-backend round of this suite+sf series;
    # the verdict lands both per query and as a report summary
    try:
        from . import history as bh
        gate = bh.stamp(
            f"runner-{suite}-sf{sf}",
            {name: e.get("hot_s") for name, e in report["queries"].items()},
            backend=report["backend"],
            higher_is_better=False,        # hot seconds: lower is better
            meta={"iterations": iterations,
                  "concurrentTpuTasks": concurrent_tasks},
            path=history_path)
        for name, v in gate["verdicts"].items():
            if name in report["queries"]:
                report["queries"][name]["regression"] = v
        report["regression_overall"] = gate["overall"]
    except Exception as e:        # the gate must not kill the report
        report["regression_error"] = str(e)[:200]
    # cold-path series (ISSUE 17, docs/compile.md §5): with --prewarm
    # against a warmed cache dir, q6's FIRST iteration in this fresh
    # process is the cold_q6_s measurement. Stamped only when the
    # honesty checks pass: rows came back and the query thread paid
    # ZERO cold compiles (the builds all landed at prewarm time).
    if prewarm_info is not None:
        report["prewarm"] = prewarm_info
        try:
            from . import history as bh
            e = report["queries"].get("q6")
            if e is not None:
                comp = e.get("compile", {}) or {}
                honest = (comp.get("coldCompiles", 0) == 0
                          and e.get("rows", 0) > 0)
                report["cold_path"] = {
                    "coldQ6S": e["cold_s"],
                    "queryColdCompiles": comp.get("coldCompiles", 0),
                    "queryDiskHits": comp.get("diskHits", 0),
                    "prewarmBuilt": prewarm_info.get("prewarmBuilt", 0),
                    "honest": honest,
                }
                if honest:
                    bh.stamp(
                        "cold_path",
                        {bh.COLD_Q6_S: e["cold_s"]},
                        backend=report["backend"],
                        higher_is_better=False,
                        meta={"rows": e.get("rows", 0),
                              "prewarmBuilt":
                                  prewarm_info.get("prewarmBuilt", 0),
                              "asyncBuilt":
                                  prewarm_info.get("asyncBuilt", 0),
                              "queryColdCompiles":
                                  comp.get("coldCompiles", 0)},
                        path=history_path)
        except Exception as e:
            report["cold_path_error"] = str(e)[:200]
    # process-telemetry registry snapshot rides the artifact (parity
    # with BENCH/MULTICHIP tails): semaphore/lockdep/sync/recompile/
    # spill/shuffle/HBM numbers for this whole run
    try:
        from spark_rapids_tpu.service.telemetry import compact_snapshot
        report["telemetry"] = compact_snapshot()
    except Exception:
        pass
    # run-level determinism summary (docs/analysis.md §6): static lint
    # verdict over the shipped tree plus the divergence-audit counters
    # for this run — a bench round that tripped the nondeterminism
    # analyzer or desynced mid-run says so in its own artifact
    try:
        import os as _os
        from spark_rapids_tpu.analysis import divergence as _div
        from spark_rapids_tpu.analysis import lint as _lint
        _pkg = _os.path.dirname(_os.path.abspath(_lint.__file__))
        _pkg = _os.path.dirname(_pkg)          # spark_rapids_tpu/
        _viol = _lint.run(_pkg)
        from spark_rapids_tpu.analysis import ledger as _led
        report["analysis"] = {
            "lintViolations": len(_viol),
            "divergence": _div.stats(),
            "ledger": _led.stats(),
        }
        _dv = report["analysis"]["divergence"]
        _lg = report["analysis"]["ledger"]
        print(f"ANALYSIS lint_violations={len(_viol)} "
              f"divergence_mode={_dv['mode']} "
              f"divergence_checks={_dv['checks']} desyncs={_dv['desyncs']} "
              f"ledger_mode={_lg['mode']} audits={_lg['audits']} "
              f"leaks={_lg['leaks']} "
              f"use_after_free={_lg['use_after_free']}")
    except Exception as e:        # the summary must not kill the report
        report["analysis_error"] = str(e)[:200]
    if output:
        with open(output, "w") as f:
            json.dump(report, f, indent=2)
    return report


def _lock_delta(before: Dict, after: Dict) -> Dict:
    """Per-lock wait/hold/acquires growth (moved to
    ``analysis/lockdep.stats_delta`` so query listeners share it)."""
    from spark_rapids_tpu.analysis import lockdep
    return lockdep.stats_delta(before, after)


def oracle_rows(df) -> List[tuple]:
    """``df``'s rows as the pandas oracle computes them
    (``cpu/engine.py`` over the analyzed logical plan — no planner, no
    device), in the canonical order :func:`rows_match` compares in."""
    from spark_rapids_tpu.cpu.engine import execute as cpu_execute
    cpu = cpu_execute(df._analyzed())
    return sorted((tuple(r) for r in
                   cpu.itertuples(index=False, name=None)), key=repr)


def rows_match(cpu_rows: List[tuple], tpu_rows: List[tuple],
               epsilon: float = 1e-4) -> bool:
    """BenchUtils.compareResults analog: same rows up to order, floats
    within ``epsilon`` relative (NaN equals NaN, NULL equals NULL)."""
    import math
    tpu_rows = sorted(tpu_rows, key=repr)
    if len(cpu_rows) != len(tpu_rows):
        return False
    for cr, tr in zip(cpu_rows, tpu_rows):
        for cv, tv in zip(cr, tr):
            if cv is None or tv is None:
                if cv is not tv:
                    return False
                continue
            if isinstance(cv, float) and isinstance(tv, float):
                if math.isnan(cv) != math.isnan(tv):
                    return False
                if not math.isnan(cv) and \
                        abs(cv - tv) > epsilon * max(abs(cv), abs(tv), 1.0):
                    return False
            elif cv != tv:
                return False
    return True


def _verify(session, df, epsilon: float = 1e-4) -> bool:
    """CPU-engine compare of one executed DataFrame."""
    return rows_match(oracle_rows(df), df.collect(), epsilon)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=0.01)
    ap.add_argument("--suite", type=str, default="tpch",
                    choices=("tpch", "tpcds", "tpcxbb"))
    ap.add_argument("--queries", type=str, default=None)
    ap.add_argument("--iterations", type=int, default=2)
    ap.add_argument("--verify", action="store_true")
    ap.add_argument("--output", type=str, default=None)
    ap.add_argument("--concurrent-tasks", type=int, default=None,
                    help="concurrentTpuTasks (default: host cpu count)")
    ap.add_argument("--trace-dir", type=str, default=None,
                    help="directory for per-query Chrome-trace timelines "
                         "(default: <output>.traces when --output is set)")
    ap.add_argument("--history", type=str, default=None,
                    help="bench-history JSONL for the regression gate "
                         "(default: benchmarks/reports/bench_history.jsonl, "
                         "made at run time)")
    ap.add_argument("--compile-cache-dir", type=str, default=None,
                    help="persistent compile cache directory "
                         "(spark.rapids.tpu.sql.compile.cacheDir): repeat "
                         "runs against the same dir pay zero cold compiles")
    ap.add_argument("--prewarm", action="store_true",
                    help="compile the hottest recorded fused-stage "
                         "signatures on the background pool before the "
                         "query loop (requires --compile-cache-dir with a "
                         "prior run's corpus); stamps cold_q6_s when the "
                         "honesty checks pass")
    args = ap.parse_args()
    report = run_benchmark(args.sf,
                           args.queries.split(",") if args.queries else None,
                           args.iterations, args.verify, args.output,
                           suite=args.suite,
                           concurrent_tasks=args.concurrent_tasks,
                           trace_dir=args.trace_dir,
                           history_path=args.history,
                           compile_cache_dir=args.compile_cache_dir,
                           prewarm=args.prewarm)
    print(json.dumps(report, indent=2))


if __name__ == "__main__":
    main()
