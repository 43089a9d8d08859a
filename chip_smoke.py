"""chip_smoke.py — the engine's main path, once, on the TPU, or failure.

One process drives ``TpuSession.builder.getOrCreate()`` ->
``benchmarks.datagen.register_tables`` -> ``benchmarks.queries.QUERIES[q]``
-> ``Overrides`` -> ``Tpu*Exec`` -> ``collect`` at TPC-H-like SF1 (6 M
lineitem rows) with DEFAULT confs, so batch autotune, whole-stage fusion
and the MXU aggregation paths take the branches the chip selects. Every
query's rows are compared with the pandas oracle (``cpu/engine.py``, the
two halves of ``benchmarks.runner._verify``, epsilon 1e-4). The oracle is
row-at-a-time Python — minutes per query at SF1 — so each query's oracle
runs in a child pinned to the CPU backend (it never asks for the chip)
while this process, the only one that touches the chip, compiles and runs.

    python chip_smoke.py             # one chip: q6, q1, q3
    python chip_smoke.py --chips 4   # four chips: ONLY the SPMD mesh path

Each phase prints one JSON line; the LAST line of stdout is the verdict
``{"ok": ..., "device": {"platform", "kind", "count"}}``. The exit code is
0 only when ``ok`` is true. There is no CPU pass: a platform other than
``tpu`` fails before any query runs, and so does any of

* a query that raises, or whose rows differ from the oracle;
* a ``CpuFallbackExec`` / ``CpuOpBridgeExec`` in an executed plan;
* a WARNING on logger ``spark_rapids_tpu.fusion`` or a stage flagged
  ``broken`` — a fused program the compiler refused, answered by the
  per-op eager path instead.

A cold compile on a query's SECOND execution is printed, not fatal.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import pickle
import shutil
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional, Sequence

FUSION_LOGGER = "spark_rapids_tpu.fusion"
SF = 1.0                        # TPC-H-like: 6 M lineitem, 1.5 M orders
#: q18 is not here: its cold compile alone ran past 680 s on the chip, and
#: a query cannot be stopped mid-compile — the 1200 s limit does not allow it
ONE_CHIP_QUERIES = ("q6", "q1", "q3")
MESH_QUERIES = ("q1", "q3")
#: the driver stops the script at 1200 s; past this an oracle still
#: running is a failure reported, not a kill taken
BUDGET_S = 1100.0


class FusionWarnings(logging.Handler):
    """Collects every WARNING+ record of the fusion logger: each one is a
    fused program that did not compile and fell back to per-op eager."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.messages: List[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        self.messages.append(record.getMessage())


def emit(obj: Dict) -> None:
    print(json.dumps(obj, default=str), flush=True)


def device_info() -> Dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _walk(node):
    yield node
    for c in node.children:
        yield from _walk(c)


def plan_faults(session) -> List[str]:
    """What in the LAST executed plan hid the device: CPU-fallback nodes
    and fused stages that gave up (``broken`` / ``_fusion_broken``)."""
    faults: List[str] = []
    try:
        session.assert_on_tpu()
    except AssertionError as e:
        faults.append("cpu fallback: " + str(e).splitlines()[0])
    for node in _walk(session.last_plan()):
        if getattr(node, "broken", False) or \
                getattr(node, "_fusion_broken", False):
            faults.append(f"fused program of {node.name} fell back to "
                          "per-op eager")
    return faults


def _compile_counts(delta: Dict[str, Dict]) -> Dict:
    return {"cold": sum(v.get("coldCompiles", 0) for v in delta.values()),
            "disk": sum(v.get("diskHits", 0) for v in delta.values()),
            "seconds": round(sum(v.get("compileS", 0.0)
                                 for v in delta.values()), 2)}


def oracle_child(name: str, sf: float, out_path: str) -> None:
    """Body of one oracle child: the same seeded tables, the query's
    logical plan, the pandas oracle — rows pickled to ``out_path``."""
    from benchmarks import datagen, queries as Q
    from benchmarks.runner import oracle_rows
    from spark_rapids_tpu.api.session import TpuSession
    session = TpuSession.builder.config(
        {"spark.rapids.tpu.sql.explain": "NONE"}).getOrCreate()
    tables = datagen.register_tables(session, sf)
    rows = oracle_rows(Q.QUERIES[name](tables))
    with open(out_path + ".tmp", "wb") as f:
        pickle.dump(rows, f)
    os.replace(out_path + ".tmp", out_path)


class Oracles:
    """One CPU-pinned child per query, started together up front; the
    parent collects each query's oracle rows when it needs them and
    :meth:`close` stops whatever is still running."""

    def __init__(self, queries: Sequence[str], sf: float):
        self.dir = tempfile.mkdtemp(prefix="chip_smoke_oracle_")
        here = os.path.dirname(os.path.abspath(__file__))
        # JAX_PLATFORMS=cpu: the child computes on the host and can
        # neither take the chip from the parent nor wait for it
        env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": here}
        self.procs: Dict[str, subprocess.Popen] = {}
        self.errs: Dict[str, object] = {}
        for q in queries:
            self.errs[q] = open(os.path.join(self.dir, q + ".err"), "w+")
            self.procs[q] = subprocess.Popen(
                [sys.executable, "-c",
                 "import sys, chip_smoke; chip_smoke.oracle_child("
                 "sys.argv[1], float(sys.argv[2]), sys.argv[3])",
                 q, repr(sf), self._path(q)],
                cwd=here, env=env, stdout=self.errs[q],
                stderr=subprocess.STDOUT)

    def _path(self, q: str) -> str:
        return os.path.join(self.dir, q + ".pkl")

    def rows(self, q: str, timeout_s: float) -> List[tuple]:
        """Wait (bounded) for ``q``'s child; raises when it failed."""
        proc = self.procs[q]
        try:
            rc = proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            raise RuntimeError(f"oracle child for {q} still running after "
                               f"{timeout_s:.0f}s") from None
        if rc != 0 or not os.path.exists(self._path(q)):
            self.errs[q].seek(0)
            raise RuntimeError(f"oracle child for {q} failed (rc={rc}): "
                               + self.errs[q].read()[-1500:])
        with open(self._path(q), "rb") as f:
            return pickle.load(f)          # bytes this program's child wrote

    def close(self) -> None:
        for proc in self.procs.values():
            if proc.poll() is None:
                proc.kill()
            proc.wait()
        for f in self.errs.values():
            f.close()
        shutil.rmtree(self.dir, ignore_errors=True)


def run_query(session, tables, name: str, fusion: FusionWarnings,
              require_mesh: bool = False) -> Dict:
    """The chip half of one query: cold run, second run, plan checks.
    ``faults`` lists everything that must fail the smoke; ``resultRows``
    (popped before printing) are what :func:`verify_query` compares."""
    from benchmarks import queries as Q
    from spark_rapids_tpu.analysis import recompile
    rec: Dict = {"phase": "query", "query": name, "faults": []}
    warned0 = len(fusion.messages)
    try:
        runs = []
        for _ in range(2):
            rc0 = recompile.snapshot()
            t0 = time.perf_counter()
            batch = Q.QUERIES[name](tables).collect_batch().fetch_to_host()
            runs.append({
                "seconds": round(time.perf_counter() - t0, 3),
                "compiles": _compile_counts(recompile.delta(rc0)),
                "hostSyncs": (session.last_query_metrics().get("sync")
                              or {}).get("hostSyncs")})
            rec["faults"] += plan_faults(session)
        rec["cold"], rec["second"] = runs
        rec["rows"] = batch.num_rows
        rec["resultRows"] = batch.rows()
        plan = [type(n).__name__ for n in _walk(session.last_plan())]
        if require_mesh:
            rec["meshExecs"] = sorted({p for p in plan
                                       if p.startswith("TpuMesh")})
            if not rec["meshExecs"]:
                rec["faults"].append("no TpuMesh*Exec in the plan: "
                                     + " > ".join(plan))
        elif any(p.startswith("TpuMesh") for p in plan):
            rec["faults"].append("one-chip run planned a mesh exec")
    except Exception as e:                # a raised phase fails the smoke
        rec["faults"].append(f"raised {type(e).__name__}: {e}"[:2000])
    rec["faults"] += [f"fusion warning: {m}"[:2000]
                      for m in fusion.messages[warned0:]]
    rec["faults"] = sorted(set(rec["faults"]))
    return rec


def boundary_counts(session, sf: float) -> Dict:
    """Float64 predicates at their boundaries (ROADMAP M1): ``count(*)``
    where ``l_discount`` equals, or lies between, values the data holds,
    against numpy on the host column, exact. The queries' fixed parameters
    never met the fault this catches: a column value and the equal literal
    comparing unequal on the chip."""
    import numpy as np
    from benchmarks import datagen
    rec: Dict = {"phase": "boundary", "faults": [], "counts": {}}
    try:
        d = datagen.gen_lineitem(sf)["l_discount"]
        checks = [(f"l_discount = {v}", d == v) for v in (0.05, 0.06, 0.09)]
        checks += [(f"l_discount BETWEEN {lo} AND {hi}", (d >= lo) & (d <= hi))
                   for lo, hi in ((0.05, 0.07), (0.02, 0.04))]
        for where, keep in checks:
            got = session.sql("SELECT count(*) FROM lineitem WHERE "
                              + where).collect()[0][0]
            rec["counts"][where] = got
            rec["faults"] += plan_faults(session)
            if got != int(keep.sum()):
                rec["faults"].append(f"count(*) WHERE {where}: {got}, numpy "
                                     f"counts {int(keep.sum())}")
    except Exception as e:
        rec["faults"].append(f"raised {type(e).__name__}: {e}"[:2000])
    rec["faults"] = sorted(set(rec["faults"]))
    return rec


def verify_query(name: str, result_rows: Optional[List[tuple]],
                 oracles: Oracles, timeout_s: float) -> Dict:
    """The oracle half: the chip's rows against the pandas oracle's."""
    from benchmarks.runner import rows_match
    rec: Dict = {"phase": "verify", "query": name, "verified": False,
                 "faults": []}
    try:
        if result_rows is None:
            raise RuntimeError("the query produced no rows to compare")
        t0 = time.perf_counter()
        expected = oracles.rows(name, timeout_s)
        rec["oracleWaitSeconds"] = round(time.perf_counter() - t0, 1)
        rec["oracleRows"] = len(expected)
        rec["verified"] = rows_match(expected, result_rows)
        if not rec["verified"]:
            rec["faults"].append("rows differ from the pandas oracle")
    except Exception as e:
        rec["faults"].append(f"raised {type(e).__name__}: {e}"[:2000])
    return rec


def mesh_faults(n_chips: int) -> List[str]:
    """The four-chip claims beyond right answers: exchanges rode the ICI
    plane and the SPMD programs' inputs and outputs sat on every chip."""
    from spark_rapids_tpu.parallel import mesh as M
    from spark_rapids_tpu.shuffle.exchange import plane_totals
    faults = []
    planes = plane_totals()
    if not planes.get("ici_exchanges"):
        faults.append(f"no exchange reported the ici plane: {planes}")
    placement = M.placement_report()
    if not placement:
        faults.append("no SPMD mesh program ran")
    for kind, spread in placement.items():
        if spread["in"] != n_chips or spread["out"] != n_chips:
            faults.append(f"mesh program {kind!r} spread over {spread} "
                          f"devices, expected {n_chips} each way")
    return faults


def run(sf: float, queries: Sequence[str], mesh: bool = False,
        started: Optional[float] = None) -> List[Dict]:
    """Every phase of the smoke through the normal session path. Returns
    the printed records; :func:`verdict` turns them into the last line.
    Platform-agnostic on purpose — :func:`main` is what refuses to start
    without a TPU, so the failure rules can be exercised on any backend."""
    import jax
    started = time.perf_counter() if started is None else started
    fusion = FusionWarnings()
    logging.getLogger(FUSION_LOGGER).addHandler(fusion)
    records: List[Dict] = []

    def note(rec: Dict) -> Dict:
        records.append(rec)
        emit(rec)
        return rec

    oracles = Oracles(queries, sf)      # first: they take the longest
    try:
        from benchmarks import datagen
        from spark_rapids_tpu.api.session import TpuSession
        from spark_rapids_tpu.exec import compile_cache
        from spark_rapids_tpu.exec.device import DeviceManager
        conf = {"spark.rapids.tpu.sql.explain": "NONE",
                # the managed layer over the ONE xla cache directory (the
                # signature index and prewarm corpus beside the cache);
                # cold vs disk in the counts below is XLA's own report
                "spark.rapids.tpu.sql.compile.cacheDir":
                    compile_cache.xla_cache_dir()}
        if mesh:
            conf["spark.rapids.tpu.sql.mesh.enabled"] = "true"
        session = TpuSession.builder.config(conf).getOrCreate()
        t0 = time.perf_counter()
        tables = datagen.register_tables(session, sf)
        note({"phase": "setup", "sf": sf, "mesh": mesh, "faults": [],
              "datagenSeconds": round(time.perf_counter() - t0, 2),
              "rows": {"lineitem": int(datagen.LINEITEM_PER_SF * sf),
                       "orders": int(datagen.ORDERS_PER_SF * sf),
                       "customer": int(datagen.CUSTOMER_PER_SF * sf)},
              "compileCacheDir": jax.config.jax_compilation_cache_dir,
              "memoryBudgetBytes": DeviceManager.get().memory_budget_bytes})
        note(boundary_counts(session, sf))
        results: Dict[str, Optional[List[tuple]]] = {}
        for name in queries:
            rec = run_query(session, tables, name, fusion,
                            require_mesh=mesh)
            results[name] = rec.pop("resultRows", None)
            note(rec)
        if mesh:
            from spark_rapids_tpu.parallel import mesh as M
            from spark_rapids_tpu.shuffle.exchange import plane_totals
            note({"phase": "mesh", "planes": plane_totals(),
                  "placement": M.placement_report(),
                  "faults": mesh_faults(len(jax.devices()))})
        stats = jax.devices()[0].memory_stats() or {}
        note({"phase": "memory", "faults": [],
              "peakBytesInUse": stats.get("peak_bytes_in_use"),
              "bytesLimit": stats.get("bytes_limit")})
        # the oracles have been running beside all of the above
        for name, rows in results.items():
            left = BUDGET_S - (time.perf_counter() - started)
            note(verify_query(name, rows, oracles, max(left, 1.0)))
    except Exception as e:                # set-up raised: still a record
        note({"phase": "setup", "faults":
              [f"raised {type(e).__name__}: {e}"[:2000]]})
    finally:
        oracles.close()
        logging.getLogger(FUSION_LOGGER).removeHandler(fusion)
    return records


def verdict(records: Sequence[Dict], device: Dict, chips: int,
            queries: Sequence[str]) -> Dict:
    """The last line. ``ok`` only on a TPU of the asked size, with every
    required query run, verified, and fault-free."""
    failures: List[str] = []
    if device["platform"] != "tpu":
        failures.append(f"platform is {device['platform']!r}, not 'tpu'")
    if device["count"] != chips:
        failures.append(f"{device['count']} device(s) visible, "
                        f"{chips} asked for")
    for rec in records:
        failures += [f"{rec.get('query', rec.get('phase'))}: {f}"
                     for f in rec.get("faults", ())]
    done = {r["query"] for r in records
            if r.get("phase") == "verify" and r.get("verified")}
    for q in queries:
        if q not in done:
            failures.append(f"{q}: did not run to a verified result")
    out = {"ok": not failures, "device": device}
    if failures:
        out["failures"] = failures
    return out


def main(argv: Optional[Sequence[str]] = None) -> int:
    started = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs ONLY the SPMD mesh path (q1, q3) over "
                         "four devices; 1 (default) never touches the mesh")
    args = ap.parse_args(argv)
    mesh = args.chips == 4
    queries = MESH_QUERIES if mesh else ONE_CHIP_QUERIES
    device = device_info()
    if device["platform"] != "tpu":
        # no CPU pass, ever: nothing runs, nothing that looks like a
        # result is printed
        emit(verdict((), device, args.chips, queries))
        return 1
    records = run(SF, queries, mesh=mesh, started=started)
    emit({"phase": "total",
          "seconds": round(time.perf_counter() - started, 1)})
    last = verdict(records, device, args.chips, queries)
    emit(last)
    return 0 if last["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
