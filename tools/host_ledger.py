"""Print the host ledger of a benchmark cell's traced queries.

Usage::

    chiprun -- python3 tools/host_ledger.py --workload tpch_sf10.q6 --seed 7

Runs ``perfbench/run.py``'s traced run of the cell (``--trace 1``: the
program's ``tracing.enabled`` on, the workload's ``traced_queries``
executions inside a ``jax.profiler`` trace) and prints what the program
itself says of each traced query: ``session.last_query_metrics()["host"]``
(docs/observability.md §9) beside the harness's own latency of the same
query, the host sites, the spans' self times and the ``programs`` map's
``dispatchS``. Every number of the program is one of
``last_query_metrics()``, those a reader of ``perfbench/readers/`` finds in
``ctx["query_metrics"]``; the harness's timed call is timed once more here,
unrounded. The full maps go to
``chiprun_out/host_ledger/<workload>.<seed>.json``. Refuses any platform
but ``tpu``, like the harness.
"""

from __future__ import annotations

import argparse
import io
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

PARTS = ("parseS", "planS", "dispatchS", "syncWaitS", "operatorS", "fetchS",
         "unaccountedS")


def _mean(values):
    values = list(values)
    return sum(values) / len(values) if values else 0.0


def summarize(latencies, metrics):
    """Means over the traced queries, in milliseconds where a time."""
    hosts = [m["host"] for m in metrics]
    out = {"queries": len(hosts),
           "latency_ms": [round(1e3 * t, 3) for t in latencies],
           "call_ms": [round(1e3 * h["callS"], 3) for h in hosts],
           "call_over_latency": round(
               _mean(h["callS"] for h in hosts) / _mean(latencies), 4),
           "host_ms": {k[:-1]: round(1e3 * _mean(h[k] for h in hosts), 4)
                       for k in PARTS + ("callS", "offThreadS")},
           "dispatches": _mean(h["dispatches"] for h in hosts),
           "parse_cache_hits": sum(h["parseCacheHit"] for h in hosts),
           "unaccounted_share": round(
               _mean(h["unaccountedS"] for h in hosts) /
               _mean(h["callS"] for h in hosts), 4)}
    sites = sorted({s for h in hosts for s in h["sites"]})
    out["sites"] = {
        s: {"count": _mean(h["sites"].get(s, {}).get("count", 0)
                           for h in hosts),
            "ms": round(1e3 * _mean(h["sites"].get(s, {}).get("s", 0.0)
                                    for h in hosts), 4)}
        for s in sites}
    spans = sorted({n for m in metrics for n, v in m["spans"].items()
                    if isinstance(v, dict)})
    out["spans_self_ms"] = {
        n: {"count": _mean(m["spans"].get(n, {}).get("count", 0)
                           for m in metrics),
            "ms": round(1e3 * _mean(m["spans"].get(n, {}).get("selfS", 0.0)
                                    for m in metrics), 3)}
        for n in spans}
    out["concurrency"] = [m["spans"]["concurrency"] for m in metrics]
    out["semaphore_hold_ms"] = round(
        1e3 * _mean(m["spans"]["semaphoreHoldS"] for m in metrics), 3)
    families = sorted({f for m in metrics for f in m["programs"]})
    out["programs"] = {
        f: {"dispatches": _mean(m["programs"].get(f, {}).get("dispatches", 0)
                                for m in metrics),
            "dispatch_ms": round(1e3 * _mean(
                m["programs"].get(f, {}).get("dispatchS", 0.0)
                for m in metrics), 4)}
        for f in families}
    agg = out["spans_self_ms"].get("aggregate")
    if agg and agg["ms"]:
        # what of ``aggregate``'s self time the ledger names: the calls of
        # its programs and the host sites passed inside it (self seconds)
        inside = {s: round(1e3 * _mean(
            h["sites"].get(s, {}).get("bySpan", {}).get("aggregate", 0.0)
            for h in hosts), 4) for s in sites}
        named = sum(p["dispatch_ms"] for f, p in out["programs"].items()
                    if f.startswith("agg/")) + sum(inside.values())
        out["aggregate"] = {"self_ms": agg["ms"], "sites_ms": inside,
                            "named_share": round(named / agg["ms"], 4)}
    out["sync"] = {"hostSyncs": _mean(m["sync"]["hostSyncs"]
                                      for m in metrics),
                   "syncWaitMs": round(1e3 * _mean(
                       m["sync"].get("syncWaitS", 0.0) for m in metrics), 3)}
    return out


def ledger_of_cell(workload, seed, rows_scale=1.0):
    """The traced run of ``workload`` and its summary (``rows_scale``: the
    tests' rehearsal on the CPU, as ``perfbench.run.run_cell`` takes it)."""
    import time
    from perfbench import run
    from spark_rapids_tpu.api.session import TpuSession
    # every last_query_metrics() the harness reads (one per traced query,
    # into ctx["query_metrics"]) is kept here too, and the timed call is
    # timed once more, unrounded
    seen, calls = [], []
    read, execute = TpuSession.last_query_metrics, run.execute

    def keeping(session):
        m = read(session)
        seen.append(m)
        return m

    def timed(session, text):
        t0 = time.perf_counter()
        try:
            return execute(session, text)
        finally:
            calls.append(time.perf_counter() - t0)
    TpuSession.last_query_metrics, run.execute = keeping, timed
    try:
        result = run.run_cell(workload, seed, 0, 1, rows_scale=rows_scale,
                              out=io.StringIO(), err=io.StringIO())
    finally:
        TpuSession.last_query_metrics, run.execute = read, execute
    n = len(seen)
    latencies, metrics = calls[-n:], seen
    summary = summarize(latencies, metrics)
    summary.update(workload=workload, seed=seed, correct=result["correct"],
                   device=result["device"],
                   per_layer={k: v["value"]
                              for k, v in result["metrics"].items()})
    return summary, {
        "summary": summary, "latencies_s": latencies,
        "query_metrics": [{k: m[k] for k in ("host", "spans", "programs",
                                             "sync", "scan", "coalesce")}
                          for m in metrics],
        "breakdown": result.get("breakdown")}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    from perfbench import run
    workload = run.load_json("workloads", args.workload + ".json")
    config = run.load_json("configs", workload["config"] + ".json")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < config["chips"]:
        print(f"host_ledger: needs {config['chips']} TPU chip(s), found "
              f"{len(devices)} of platform {devices[0].platform!r}",
              file=sys.stderr)
        return 1
    summary, full = ledger_of_cell(args.workload, args.seed)
    out_dir = os.path.join(ROOT, "chiprun_out", "host_ledger")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir,
                           f"{args.workload}.{args.seed}.json"), "w") as f:
        json.dump(full, f, indent=1)
    print(json.dumps(summary))
    return 0 if summary["correct"] else 3


if __name__ == "__main__":
    sys.exit(main())
