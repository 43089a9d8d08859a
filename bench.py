"""Benchmark: fused columnar SQL pipeline throughput on the TPU chip.

Measures the flagship whole-stage pipeline — filter -> project -> group-by
aggregate (sum/count/avg) — over a 64M-row batch, the scan+filter+project+agg
hot path of SURVEY.md §3.3 (BASELINE.md milestone config 1/2). The group-by
rides the dense-range MXU path (ops/aggregates.py groupby_dense): no sort, no
compaction — elementwise passes plus chunked one-hot matmuls on the systolic
array. The key range (the static slot count) comes from input statistics, the
same information a parquet scan gets for free from row-group min/max stats.

The identical query runs on single-core pandas as the baseline, so
``vs_baseline`` is the TPU speedup over single-core pandas (the reference
repo publishes no numeric GPU baselines — BASELINE.md: "chart image only").

Methodology: iterations are dispatched back-to-back and ALL results are
forced at the end (inputs varied per iteration to defeat any caching), i.e.
steady-state throughput with the device pipeline kept full — the execution
cadence of a scan feeding consecutive batches. A per-iteration host sync
would instead measure the fixed cost of a blocking readback.

Runs on a TPU or not at all (benchmarks/preflight.require_chip): there is
no CPU fallback, and a phase that raises fails the run with a non-zero
exit code. One chip belongs to one process at a time, so the warm-restart
phase — two fresh child processes, each needing the chip — runs FIRST,
before this process touches a device.

Prints ONE json line: {"metric", "value", "unit", "vs_baseline", ...extras}.
"""

import json
import os
import time

import numpy as np

N_KEYS = 1024


def _k_slots() -> int:
    """Static slot bucket from the key span (bucket(span+2), the same
    derivation the engine's dense dispatch uses) — not a hard-coded 2048."""
    from spark_rapids_tpu.columnar.column import bucket
    return bucket(N_KEYS + 2, 128)


K_SLOTS = None          # resolved in main() after imports


def build_inputs(n_rows: int, cap: int):
    rng = np.random.default_rng(42)
    keys = np.zeros(cap, dtype=np.int64)
    keys[:n_rows] = rng.integers(0, N_KEYS, n_rows)
    key_valid = np.zeros(cap, dtype=bool)
    key_valid[:n_rows] = True
    vals = np.zeros(cap, dtype=np.float64)
    vals[:n_rows] = rng.normal(0, 10, n_rows)
    val_valid = np.zeros(cap, dtype=bool)
    val_valid[:n_rows] = rng.random(n_rows) < 0.95
    flags = np.zeros(cap, dtype=bool)
    flags[:n_rows] = rng.random(n_rows) < 0.8
    return keys, key_valid, vals, val_valid, flags


def bench_tpu(n_rows: int, cap: int, iters: int = 8):
    """One fused jit per iteration: filter -> project -> dense MXU group-by.
    Returns (rows_per_s, sample result arrays for validation)."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.column import Column
    from spark_rapids_tpu.ops import aggregates as agg_k

    keys, key_valid, vals, val_valid, flags = build_inputs(n_rows, cap)

    def fused(keys, key_valid, vals, val_valid, flags, num_rows):
        live = jnp.arange(cap) < num_rows
        keep = live & flags & val_valid & (vals > 0)
        kcol = Column(dt.INT64, keys, key_valid)
        proj = Column(dt.FLOAT64, vals * 2.0 + 1.0, val_valid)
        rmin = jnp.min(jnp.where(keep & key_valid, keys,
                                 jnp.iinfo(jnp.int64).max))
        rmin = jnp.where(jnp.any(keep & key_valid), rmin, 0)
        out_keys, out_aggs, n_groups = agg_k.groupby_dense(
            kcol, [agg_k.AggSpec("sum", proj),
                   agg_k.AggSpec("count", proj),
                   agg_k.AggSpec("avg", proj)],
            num_rows, K_SLOTS, rmin, extra_mask=keep)
        return (out_keys[0].data, out_keys[0].validity,
                out_aggs[0].data, out_aggs[1].data, out_aggs[2].data,
                n_groups)

    f = jax.jit(fused)
    args = (jnp.asarray(keys), jnp.asarray(key_valid), jnp.asarray(vals),
            jnp.asarray(val_valid), jnp.asarray(flags))
    jax.block_until_ready(args)

    warm = f(*args, jnp.int32(n_rows))
    sample = [np.asarray(x) for x in warm]        # forces compile + run

    t0 = time.perf_counter()
    outs = [f(*args, jnp.int32(n_rows - i)) for i in range(iters)]
    for o in outs:                                 # force EVERY iteration
        np.asarray(o[3])
    dt_s = (time.perf_counter() - t0) / iters
    return n_rows / dt_s, sample


def bench_pandas(n_rows: int, cap: int, iters: int = 2):
    import pandas as pd
    keys, key_valid, vals, val_valid, flags = build_inputs(n_rows, cap)
    df = pd.DataFrame({
        "k": keys[:n_rows],
        "v": np.where(val_valid[:n_rows], vals[:n_rows], np.nan),
        "flag": flags[:n_rows]})
    t0 = time.perf_counter()
    for _ in range(iters):
        sub = df[df["flag"] & (df["v"] > 0)]
        proj = sub.assign(p=sub["v"] * 2.0 + 1.0)
        res = proj.groupby("k")["p"].agg(["sum", "count", "mean"])
    dt_s = (time.perf_counter() - t0) / iters
    return n_rows / dt_s, res


def validate(sample, pd_res):
    """The two engines must agree on the sample run (counts exact, sums/avgs
    to float-agg tolerance, same group set) — a bench that drifts from the
    oracle is void."""
    gk, gkv, gsum, gcnt, gavg, ng = sample
    ng = int(ng)
    got = {int(k): (s, int(c), a)
           for k, kv, s, c, a in zip(gk[:ng], gkv[:ng], gsum[:ng],
                                     gcnt[:ng], gavg[:ng]) if kv}
    assert ng == len(got) == len(pd_res), (ng, len(got), len(pd_res))
    for k, row in pd_res.iterrows():
        s, c, a = got[int(k)]
        assert c == int(row["count"]), (k, c, row["count"])
        assert abs(s - row["sum"]) <= 1e-6 * max(1.0, abs(row["sum"])), \
            (k, s, row["sum"])
        assert abs(a - row["mean"]) <= 1e-6 * max(1.0, abs(row["mean"])), \
            (k, a, row["mean"])
    return len(got)


def bench_engine(sf: float, query: str, iters: int = 2,
                 extra_conf=None, with_oracle: bool = True):
    """End-to-end ENGINE throughput: the query runs through the API /
    planner / fused execution (not a hand-built kernel), timed WARM (min
    of post-cold iterations — the steady-state number the history gate
    judges) after one cold (compile) iteration; baseline is pandas
    running the same query. Returns (rows/s, pandas rows/s, cold_s)."""
    from benchmarks import datagen, queries as Q
    from spark_rapids_tpu.api.session import TpuSession
    conf = {"spark.rapids.tpu.sql.explain": "NONE"}
    conf.update(extra_conf or {})
    session = TpuSession.builder.config(conf).getOrCreate()
    tables = datagen.register_tables(session, sf)
    n_rows = int(datagen.LINEITEM_PER_SF * sf)
    qfn = Q.QUERIES[query]
    t0 = time.perf_counter()
    qfn(tables).collect_batch().fetch_to_host()
    cold_s = time.perf_counter() - t0
    hots = []
    for _ in range(iters):
        t0 = time.perf_counter()
        qfn(tables).collect_batch().fetch_to_host()
        hots.append(time.perf_counter() - t0)
    hot_s = min(hots)

    if not with_oracle:
        return n_rows / hot_s, 0.0, cold_s
    # pandas oracle on the same data (single-core, like the r01 baseline)
    li = __import__("pandas").DataFrame(datagen.gen_lineitem(sf))
    t0 = time.perf_counter()
    _pandas_query(query, li)
    pd_s = time.perf_counter() - t0
    return n_rows / hot_s, n_rows / pd_s, cold_s


def bench_shuffle(n_rows: int, iters: int = 2):
    """Engine shuffle-exchange throughput: repartition ``n_rows`` through
    TpuShuffleExchangeExec (hash keys) and report GB/s of shuffle bytes
    moved over exchange wall time, plus which data plane carried it
    (docs/shuffle.md). The hot iteration is the measurement; the cold one
    pays compiles."""
    import numpy as np
    from spark_rapids_tpu.api.functions import col
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.shuffle.exchange import shuffle_report
    session = TpuSession.builder.config(
        {"spark.rapids.tpu.sql.explain": "NONE"}).getOrCreate()
    rng = np.random.default_rng(11)
    df = session.createDataFrame({
        "k": [int(x) for x in rng.integers(0, 1 << 20, n_rows)],
        "v": [float(x) for x in rng.normal(0, 10, n_rows)]})
    best = None
    for it in range(max(1, iters) + 1):
        t0 = time.perf_counter()
        batch = df.repartition(8, col("k")).collect_batch()
        wall = time.perf_counter() - t0
        assert batch.num_rows == n_rows, (batch.num_rows, n_rows)
        rep = shuffle_report(session.last_plan())
        # write-side bytes only: the same definition note_plane and the
        # tpu_shuffle_gbps gauge use (each shuffled byte counted once)
        moved = sum(e.get("bytesWritten", 0) for e in rep)
        plane = rep[0]["plane"] if rep else None
        if it == 0 or moved <= 0:
            continue                       # cold iteration pays compiles
        gbps = moved / wall / 1e9
        if best is None or gbps > best["shuffle_gbps"]:
            best = {"shuffle_gbps": round(gbps, 4),
                    "shuffle_bytes": moved,
                    "shuffle_plane": plane,
                    "shuffle_wall_s": round(wall, 4)}
    return best


def bench_warm_restart(sf: float = 0.01):
    """Warm-restart micro-bench (ISSUE 10): run a query in a fresh child
    process against the compile cache directory, then ANOTHER fresh
    process on the same directory — the second must classify ZERO cold
    compiles (every build is a persistent-cache disk hit) and its wall
    time is the restart cost a redeploy actually pays. Each child needs
    the chip for itself: call this only from a process that has not
    touched a device yet. The directory is the ONE the engine uses
    (exec/compile_cache.xla_cache_dir), so the first child is cold only
    as far as that directory was; its own cold/disk split is reported.
    Returns the artifact fields incl. the lower-is-better history series
    values."""
    import subprocess
    import sys
    from spark_rapids_tpu.exec.compile_cache import xla_cache_dir
    cache_dir = xla_cache_dir()
    child = r"""
import json, sys, time
t0 = time.time()
from benchmarks.preflight import require_chip
require_chip()
from spark_rapids_tpu.api.session import TpuSession
from benchmarks import datagen, queries as Q
session = TpuSession.builder.config({
    "spark.rapids.tpu.sql.explain": "NONE",
    "spark.rapids.tpu.sql.compile.cacheDir": sys.argv[1]}).getOrCreate()
tables = datagen.register_tables(session, float(sys.argv[2]))
Q.QUERIES["q6"](tables).collect_batch().fetch_to_host()
from spark_rapids_tpu.analysis import recompile
rep = recompile.report()
print(json.dumps({
    "wall_s": round(time.time() - t0, 3),
    "cold": sum(v["coldCompiles"] for v in rep.values()),
    "disk": sum(v["diskHits"] for v in rep.values()),
    "compile_s": round(sum(v["compileS"] for v in rep.values()), 3)}))
"""
    here = os.path.dirname(os.path.abspath(__file__))

    def run_child():
        out = subprocess.run(
            [sys.executable, "-c", child, cache_dir, str(sf)],
            capture_output=True, text=True, timeout=900, cwd=here)
        if out.returncode != 0:
            raise RuntimeError(f"warm-restart child failed: "
                               f"{out.stderr.strip()[-300:]}")
        return json.loads(out.stdout.strip().splitlines()[-1])

    cold = run_child()          # seeds the XLA cache + signature index
    warm = run_child()          # must pay zero cold builds
    return {
        "compile_cache_dir": cache_dir,
        "compile_s": cold["compile_s"],
        "cold_restart_s": cold["wall_s"],
        "cold_restart_cold_compiles": cold["cold"],
        "cold_restart_disk_hits": cold["disk"],
        "warm_restart_s": warm["wall_s"],
        "warm_restart_cold_compiles": warm["cold"],
        "warm_restart_disk_hits": warm["disk"],
        "warm_restart_ok": warm["cold"] == 0,
    }


def bench_serving(sf: float = 0.01, iters: int = 24):
    """Serving front-door micro-bench (ISSUE 12, docs/plan_cache.md):
    steady-state q6 executions with ROTATING date-range literals through
    a prepared statement — after one cold (plan + compile) iteration,
    every execute is a parse-free plan-cache-served rebind+run, the warm
    serving hot path a dashboard tier lives on. Reports plans served per
    second (higher better) and the warm-traffic window wall seconds
    (lower better), both stamped into the history gate, plus the
    plan-cache counters as honesty checks (hits must cover the loop and
    exactly ONE plan may have been built)."""
    import datetime
    from benchmarks import datagen
    from spark_rapids_tpu.api.session import TpuSession
    session = TpuSession.builder.config(
        {"spark.rapids.tpu.sql.explain": "NONE"}).getOrCreate()
    tables = datagen.register_tables(session, sf)
    tables["lineitem"].createOrReplaceTempView("serving_lineitem")
    stmt = session.prepare(
        "SELECT sum(l_extendedprice * l_discount) AS revenue "
        "FROM serving_lineitem "
        "WHERE l_shipdate >= :lo AND l_shipdate < :hi "
        "AND l_discount >= 0.05 AND l_discount <= 0.07 "
        "AND l_quantity < 24")

    def window(i):
        lo = datetime.date(1993, 1, 1) + datetime.timedelta(
            days=30 * (i % 24))
        return lo, lo + datetime.timedelta(days=365)

    lo, hi = window(0)
    stmt.execute(lo=lo, hi=hi)          # cold: plans once, compiles
    t0 = time.perf_counter()
    for i in range(1, iters + 1):       # warm traffic, literals rotate
        lo, hi = window(i)
        stmt.execute(lo=lo, hi=hi)
    wall = time.perf_counter() - t0
    st = session.serving_stats()
    return {
        "plan_cache_plans_per_s": round(iters / wall, 2),
        "warm_traffic_q6_s": round(wall, 4),
        "serving_iters": iters,
        "serving_plan_hits": st["planHits"],
        "serving_plans_built": st["plansBuilt"],
        "serving_ok": st["plansBuilt"] == 1 and st["planHits"] >= iters,
    }


def bench_donation_hbm(n_rows: int):
    """Peak live device bytes of a fused filter consuming one batch,
    donation on vs off: with ``compile.donate`` the input columns free
    the moment the program ingests them, so steady-state residency drops
    by ~the consumed batch. Measured deterministically from
    jax.live_arrays() after the call and fed into the ``xla_live`` HBM
    watermark so the artifact's telemetry tail carries the peak."""
    import gc
    import jax
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.ops import expressions as ex
    from spark_rapids_tpu.ops import predicates as pr
    from spark_rapids_tpu.plan import physical as P
    from spark_rapids_tpu.service.telemetry import watermark

    def live_bytes():
        return sum(int(a.size * a.dtype.itemsize)
                   for a in jax.live_arrays())

    schema = dt.Schema([dt.Field("v", dt.FLOAT64)])
    pred = pr.GreaterThan(ex.BoundReference(0, dt.FLOAT64, True),
                          ex.Literal(0.0, dt.FLOAT64))
    rng = np.random.default_rng(7)
    out = {}
    wm = watermark("xla_live")
    for donate in (True, False):
        TpuSession.builder.config({
            "spark.rapids.tpu.sql.explain": "NONE",
            "spark.rapids.tpu.sql.compile.donate":
                "true" if donate else "false"}).getOrCreate()
        stage = P.FusedStage([pred], schema, schema, mode="filter")
        gc.collect()
        batch = ColumnarBatch.from_pydict(
            {"v": rng.normal(0, 10, n_rows)}, schema)
        stage(batch)           # warm: compile outside the measurement
        del batch
        gc.collect()
        base = live_bytes()
        batch = ColumnarBatch.from_pydict(
            {"v": rng.normal(0, 10, n_rows)}, schema)
        res = stage(batch)
        wm.update(live_bytes())
        peak = live_bytes() - base
        out["hbm_live_peak_donate_on" if donate
            else "hbm_live_peak_donate_off"] = peak
        del batch, res
        gc.collect()
    if out.get("hbm_live_peak_donate_off"):
        out["hbm_donate_savings_pct"] = round(
            100.0 * (1 - out["hbm_live_peak_donate_on"] /
                     out["hbm_live_peak_donate_off"]), 1)
    return out


def _rows_close(a, b, rel_tol=1e-9):
    """Row-wise equality with fp tolerance: a stage retry re-runs the
    map, so slices can land in a different order and float aggregation
    order (legally) drifts at the last bits — bitwise identity across
    retries is not a guarantee any shuffle engine makes."""
    import math
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


def bench_chaos(sf: float = 0.002):
    """Chaos mode (ISSUE 13, docs/resilience.md): a q6-shaped MULTI-BATCH
    shuffled run — lineitem rides a hash-repartition exchange before the
    q6 filter+aggregate, so the shuffle map/fetch paths are on the
    critical path — executed under injected faults: one failed fetch and
    one poisoned map-task batch, both absorbed by the stage-retry driver
    (exec/recovery.py). Honesty checks: results match the fault-free
    run (fp-tolerant — a retry legally reorders float aggregation, see
    :func:`_rows_close`), >=1 stage retry recorded, every armed fault
    fired.
    The chaos wall seconds stamp the history gate as
    ``chaos_q6_recovery_s`` (lower is better), so recovery-time
    regressions fail the bench like any perf regression."""
    from benchmarks import datagen
    from spark_rapids_tpu.analysis import faults
    from spark_rapids_tpu.api.functions import col
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.service.telemetry import MetricsRegistry
    from benchmarks import queries as Q
    session = TpuSession.builder.config({
        "spark.rapids.tpu.sql.explain": "NONE",
        "spark.rapids.tpu.sql.shuffle.partitions": "4",
        "spark.rapids.tpu.sql.recovery.retryBackoff": "0.0",
        # the injection points live on the DCN map/fetch paths; under
        # mesh auto the exchange would lower to ICI and the chaos run
        # would silently fire nothing
        "spark.rapids.tpu.sql.shuffle.plane": "dcn",
    }).getOrCreate()
    tables = dict(datagen.register_tables(session, sf))
    tables["lineitem"] = tables["lineitem"].repartition(
        4, col("l_orderkey"))

    def run():
        return Q.QUERIES["q6"](tables).collect()

    def retries():
        return float(MetricsRegistry.get().counter(
            "tpu_stage_retries_total", "x").value)

    run()                                    # cold: compile
    t0 = time.perf_counter()
    baseline = run()                         # warm fault-free reference
    fault_free_s = time.perf_counter() - t0
    before = retries()
    try:
        faults.install("fetch.fail;task.poison")
        t0 = time.perf_counter()
        got = run()
        chaos_s = time.perf_counter() - t0
        fired = faults.fired_total()
    finally:
        faults.reset()                       # never leak chaos downstream
    stage_retries = retries() - before
    ok = _rows_close(got, baseline) and stage_retries >= 1 and fired == 2
    return {
        "chaos_q6_recovery_s": round(chaos_s, 4),
        "chaos_q6_fault_free_s": round(fault_free_s, 4),
        "chaos_q6_overhead_s": round(chaos_s - fault_free_s, 4),
        "chaos_stage_retries": int(stage_retries),
        "chaos_faults_fired": int(fired),
        "chaos_ok": ok,
    }


def bench_aqe_skew(n_rows: int = 20_000):
    """AQE skewed-workload bench (ISSUE 16, docs/aqe.md): a deliberately
    skewed q3-shaped join+aggregate — one hot key owns 90% of the fact
    side, so one reduce partition dwarfs the rest — run warm with
    adaptive execution ON (``aqe_skew_q3_s``, lower is better) and OFF,
    with the on/off wall ratio stamped as ``aqe_ab_q3`` (< 1 means the
    re-planner pays for itself on skew).

    Honesty checks gate the stamp (``aqe_ok``): identical rows on/off;
    at least one APPLIED coalesce, skew-split, join-promote and
    join-demote decision across the legs; each decision visible in
    EXPLAIN ANALYZE, the query log record, and the
    ``tpu_aqe_decisions_total`` telemetry counter; and the demoted
    re-planned stage passing contract validation in ERROR mode. The
    skew leg repeats on a mesh/ICI-attached plan (needs >= 2 devices;
    recorded in ``aqe_ici_skew_split``): the first execution records the
    stage-stats baseline, the second falls the skewed stage back to DCN
    and splits."""
    import glob
    import tempfile
    from benchmarks import queries as Q  # noqa: F401  (q3 shape reference)
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.functions import col
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.service.telemetry import MetricsRegistry

    hot = int(n_rows * 0.9)
    ks = [7] * hot + [i % 40 for i in range(n_rows - hot)]
    vs = [float(i % 13) for i in range(n_rows)]
    dim_k = list(range(41))
    dim_w = [k * 10.0 for k in dim_k]
    log_dir = tempfile.mkdtemp(prefix="aqe_bench_log_")

    def q3_shaped(s):
        fact = s.createDataFrame({"k": ks, "v": vs})
        dim = s.createDataFrame({"k": dim_k, "w": dim_w})
        return (fact.join(dim, on="k", how="inner")
                .groupBy("k").agg(F.sum(col("v") + col("w")).alias("rev")))

    def timed(q):
        q.collect()                          # cold: compile
        t0 = time.perf_counter()
        rows = sorted(q.collect())
        return rows, time.perf_counter() - t0

    base_conf = {
        "spark.rapids.tpu.sql.explain": "NONE",
        "spark.rapids.tpu.sql.shuffle.partitions": "4",
        "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": "-1",
        "spark.rapids.tpu.sql.adaptive.skewJoin.skewedPartitionThreshold":
            "4096",
    }
    counts = {"coalesce": 0, "skew-split": 0, "join-promote": 0,
              "join-demote": 0}
    surfaced = {"explain": set(), "log": set(), "telemetry": set()}

    def note(session, log_rec=None):
        """Fold one leg's decisions into the honesty tallies."""
        applied = [d for d in session.last_aqe_decisions() if d["applied"]]
        for d in applied:
            if d["rule"] in counts:
                counts[d["rule"]] += 1
        text = session.explain_analyze()
        for d in applied:
            if f"* aqe {d['rule']}:" in text:
                surfaced["explain"].add(d["rule"])
        for rule, c in ((log_rec or {}).get("aqe", {})
                        .get("rules", {}).items()):
            if c.get("applied"):
                surfaced["log"].add(rule)
        return applied

    # -- skew leg: AQE on (with query log) vs off ---------------------------
    s_on = TpuSession.builder.config(dict(
        base_conf, **{
            "spark.rapids.tpu.sql.adaptive.enabled": "true",
            "spark.rapids.tpu.sql.telemetry.queryLog.dir": log_dir,
        })).getOrCreate()
    rows_on, on_s = timed(q3_shaped(s_on))
    lines = []
    for p in glob.glob(os.path.join(log_dir, "query_log-*.jsonl")):
        with open(p) as f:
            lines += [json.loads(ln) for ln in f if ln.strip()]
    note(s_on, lines[-1] if lines else None)
    s_off = TpuSession.builder.config(dict(
        base_conf, **{
            "spark.rapids.tpu.sql.adaptive.enabled": "false",
            # same log overhead as the ON leg: the A/B compares planning,
            # not artifact writes
            "spark.rapids.tpu.sql.telemetry.queryLog.dir":
                tempfile.mkdtemp(prefix="aqe_bench_log_off_"),
        })).getOrCreate()
    rows_off, off_s = timed(q3_shaped(s_off))

    # -- ICI leg: the skewed stage falls back to DCN on repeat execution ----
    import jax
    ici_ok = False
    ici_skipped = None
    if len(jax.devices()) < 2:
        ici_skipped = (f"{len(jax.devices())} device(s): mesh needs a "
                       "multi-device ICI plane")
    else:
        s_ici = TpuSession.builder.config(dict(
            base_conf, **{
                "spark.rapids.tpu.sql.adaptive.enabled": "true",
                "spark.rapids.tpu.sql.mesh.enabled": "true",
                "spark.rapids.tpu.sql.shuffle.plane": "ici",
                "spark.rapids.tpu.sql.mesh.maxStageBytes": "1024",
            })).getOrCreate()
        q = q3_shaped(s_ici)
        q.collect()                  # run 1 records the baseline
        rows_ici = sorted(q.collect())
        ici_ok = rows_ici == rows_on and any(
            d["rule"] == "skew-split" and d["applied"] and
            "[ici->dcn]" in str(d.get("after"))
            for d in note(s_ici))

    # -- join-switch legs: promote (observed small) / demote (observed big)
    s_sw = TpuSession.builder.config({
        "spark.rapids.tpu.sql.explain": "NONE",
        "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": "65536",
        "spark.rapids.tpu.sql.adaptive.enabled": "true",
        # acceptance: the demoted re-planned stage must PASS contract
        # validation in error mode
        "spark.rapids.tpu.sql.analysis.validatePlan": "error",
    }).getOrCreate()
    big = s_sw.createDataFrame({"k": [i % 50 for i in range(2000)],
                                "v": [float(i) for i in range(2000)]})
    # estimates say a 32k-row build side shuffles; the aggregate's
    # observed output (50 groups) lands under threshold -> promote
    small = (s_sw.createDataFrame(
        {"k": [i % 50 for i in range(32000)],
         "w": [float(i) for i in range(32000)]})
        .groupBy("k").agg(F.sum(col("w")).alias("w")))
    big.join(small, on="k", how="inner").collect()
    note(s_sw)
    # arrow-side estimates say broadcast; device strings pad to the
    # max length, so the OBSERVED build blows the threshold -> demote
    strs = ["x" * (2000 if i == 0 else 2) for i in range(200)]
    fact = s_sw.createDataFrame({"k": [i % 200 for i in range(4000)],
                                 "v": [float(i) for i in range(4000)]})
    dim = s_sw.createDataFrame({"k": list(range(200)), "t": strs})
    fact.join(dim, on="k", how="inner").select(
        col("k"), col("v")).collect()
    note(s_sw)

    # telemetry surface: every counted rule has a counter sample
    snap = MetricsRegistry.get().snapshot()["metrics"]
    for sample in snap.get("tpu_aqe_decisions_total",
                           {}).get("samples", ()):
        surfaced["telemetry"].add(sample["labels"].get("rule"))

    need = set(counts)
    ok = (_rows_close(rows_on, rows_off) and
          all(counts[r] >= 1 for r in need) and
          need <= surfaced["explain"] and
          need <= surfaced["telemetry"] and
          # the query log leg only sees the skew/coalesce rules
          {"coalesce", "skew-split"} <= surfaced["log"] and
          (ici_ok or ici_skipped is not None))
    out = {
        "aqe_skew_q3_s": round(on_s, 4),
        "aqe_off_q3_s": round(off_s, 4),
        "aqe_ab_q3": round(on_s / off_s, 3) if off_s > 0 else None,
        "aqe_decisions": dict(counts),
        "aqe_ici_skew_split": ici_ok,
        "aqe_ok": ok,
    }
    if ici_skipped:
        out["aqe_ici_skipped"] = ici_skipped
    return out


def _pandas_query(query: str, li):
    import pandas as pd
    if query == "q6":
        d0, d1 = 8766, 9131
        sub = li[(li.l_shipdate >= d0) & (li.l_shipdate < d1) &
                 (li.l_discount >= 0.05) & (li.l_discount <= 0.07) &
                 (li.l_quantity < 24)]
        return (sub.l_extendedprice * sub.l_discount).sum()
    if query == "q1":
        sub = li[li.l_shipdate <= 10471]
        g = sub.assign(
            disc_price=sub.l_extendedprice * (1 - sub.l_discount),
            charge=sub.l_extendedprice * (1 - sub.l_discount) *
            (1 + sub.l_tax))
        return g.groupby(["l_returnflag", "l_linestatus"]).agg(
            sum_qty=("l_quantity", "sum"),
            sum_base=("l_extendedprice", "sum"),
            sum_disc=("disc_price", "sum"),
            sum_charge=("charge", "sum"),
            avg_qty=("l_quantity", "mean"),
            avg_price=("l_extendedprice", "mean"),
            avg_disc=("l_discount", "mean"),
            cnt=("l_quantity", "count"))
    raise ValueError(query)


def main():
    global K_SLOTS
    # compile-time discipline (ISSUE 10): the warm-restart micro-bench
    # runs FIRST — its two children each need the chip, and this process
    # has not touched a device yet (one chip, one process). Fixed tiny sf:
    # it measures compile caching, which is shape-dependent and data-size
    # independent.
    warm = bench_warm_restart()
    # from here on this process owns the chip; no TPU, no bench
    from benchmarks.preflight import require_chip
    probe = require_chip()
    platform = probe["platform"]
    K_SLOTS = _k_slots()
    n_rows, cap = 64_000_000, 1 << 26
    # 24M lineitem rows: the engine's fixed per-query cost (a handful of
    # blocking host readbacks) amortizes while pandas scales linearly;
    # scan batches ride the device cache so hot runs pay no upload
    engine_sf = 4.0

    tpu_rows_per_s, sample = bench_tpu(n_rows, cap)
    cpu_rows_per_s, pd_res = bench_pandas(n_rows, cap)
    n_groups = validate(sample, pd_res)

    # engine end-to-end (API -> planner -> fused execution) on q6 and q1
    engine = dict(warm)
    for q in ("q6", "q1"):
        eng_rps, pd_rps, cold_s = bench_engine(engine_sf, q)
        engine[f"engine_{q}_mrows_per_s"] = round(eng_rps / 1e6, 3)
        engine[f"engine_{q}_vs_pandas"] = round(eng_rps / pd_rps, 2)
        engine[f"engine_{q}_cold_s"] = round(cold_s, 1)

    # fusion A/B (ISSUE 11): warm engine q6 with the stage compiler OFF —
    # the on/off speedup rides the history gate so a regression in what
    # whole-stage fusion buys is judged, not just remembered
    off_rps, _pd, _cold = bench_engine(
        engine_sf, "q6", with_oracle=False,
        extra_conf={"spark.rapids.tpu.sql.fusion.wholeStage": "false"})
    engine["engine_q6_fusion_off_mrows_per_s"] = round(off_rps / 1e6, 3)
    engine["fusion_ab_q6"] = round(
        engine["engine_q6_mrows_per_s"] / (off_rps / 1e6), 2)

    # shuffle-exchange throughput (ISSUE 8: shuffle GB/s + plane in every
    # bench artifact; judged by the same regression gate as the pipeline)
    shuffle = bench_shuffle(4_000_000)
    if shuffle:
        engine.update(shuffle)

    # donation HBM micro-bench (peak live device bytes with
    # compile.donate on vs off, via the xla_live watermark)
    engine.update(bench_donation_hbm(16_000_000))

    # serving front door (ISSUE 12): steady-state plans/s + warm-traffic
    # latency of literal-rotating q6 through the prepared path
    serving = bench_serving(sf=0.01)
    engine.update(serving)

    # chaos mode (ISSUE 13): q6-shaped shuffled run under injected
    # faults — recovery wall seconds ride the gate lower-is-better
    chaos = bench_chaos(sf=0.01)
    engine.update(chaos)

    # adaptive execution (ISSUE 16): deliberately skewed q3-shaped join —
    # AQE-on wall + on/off ratio ride the gate lower-is-better
    aqe_bench = bench_aqe_skew(200_000)
    engine.update(aqe_bench)

    bytes_per_row = 8 + 1 + 8 + 1 + 1            # key, kvalid, val, vvalid, flag
    gbytes_per_s = tpu_rows_per_s * bytes_per_row / 1e9
    # one-hot matmul flops: rows x slots x 2 (mul+add) x planned feature
    # planes (occupancy + contrib + hi/lo/nan for the fused sum/count/avg)
    from spark_rapids_tpu.columnar import dtypes as _dt
    from spark_rapids_tpu.columnar.column import Column as _Col
    from spark_rapids_tpu.ops import aggregates as _agg
    _c = _Col(_dt.FLOAT64, np.zeros(8), np.zeros(8, dtype=bool))
    n_feats = _agg.dense_feature_count(
        [_agg.AggSpec("sum", _c), _agg.AggSpec("count", _c),
         _agg.AggSpec("avg", _c)])
    tflops = tpu_rows_per_s * K_SLOTS * 2 * n_feats / 1e12
    line = {
        "metric": "fused filter+project+groupby throughput",
        "value": round(tpu_rows_per_s / 1e6, 2),
        "unit": "Mrows/s",
        "vs_baseline": round(tpu_rows_per_s / cpu_rows_per_s, 2),
        "rows": n_rows,
        "groups": n_groups,
        "input_gb_per_s": round(gbytes_per_s, 2),
        "matmul_tflops": round(tflops, 2),
        "baseline_mrows_per_s": round(cpu_rows_per_s / 1e6, 2),
        "engine_sf": engine_sf,
        # every number names the device it came from
        "backend": platform,
        "device_kind": probe["kind"],
        "device_count": probe["count"],
        "probe_s": probe["latencyS"],
    }
    line.update(engine)

    # regression gate (benchmarks/history.py): stamp this round against
    # the best prior clean same-backend round and append it to the
    # history JSONL, so round-over-round trajectory lives in the
    # artifact instead of in whoever remembers the last round
    from benchmarks import history as bh
    queries = {"fused_pipeline": line["value"],
               "engine_q6": engine["engine_q6_mrows_per_s"],
               "engine_q1": engine["engine_q1_mrows_per_s"]}
    # whole-query orchestration series (ISSUE 11): the fused-microbench
    # to warm-engine-q6 gap (lower is better) and the fusion on/off A/B
    # speedup
    gap = line["value"] / engine["engine_q6_mrows_per_s"]
    queries[bh.WHOLE_QUERY_GAP] = round(gap, 3)
    line["whole_query_gap"] = round(gap, 3)
    queries[bh.FUSION_AB_Q6] = engine["fusion_ab_q6"]
    if shuffle and shuffle.get("shuffle_gbps"):
        # shuffle GB/s rides the same higher-is-better gate
        queries[bh.SHUFFLE_GBPS] = shuffle["shuffle_gbps"]
    if warm["warm_restart_ok"]:
        # compile seconds + warm-restart wall ride the gate as
        # lower-is-better series (history.INVERTED_QUERIES)
        queries[bh.COMPILE_S] = warm["compile_s"]
        queries[bh.WARM_RESTART_S] = warm["warm_restart_s"]
    if serving["serving_ok"]:
        # serving front door (ISSUE 12): plans/s higher-is-better,
        # warm-traffic wall lower-is-better (INVERTED_QUERIES)
        queries[bh.PLAN_CACHE_PLANS_PER_S] = \
            serving["plan_cache_plans_per_s"]
        queries[bh.WARM_TRAFFIC_Q6_S] = serving["warm_traffic_q6_s"]
    if chaos.get("chaos_ok"):
        # chaos recovery wall (ISSUE 13): stamped only when the
        # honesty checks held (identical rows, >=1 stage retry,
        # every armed fault fired) — lower-is-better
        queries[bh.CHAOS_Q6_RECOVERY_S] = chaos["chaos_q6_recovery_s"]
    if aqe_bench.get("aqe_ok"):
        # adaptive execution (ISSUE 16): stamped only when the
        # honesty checks held (rows on == off, every rule applied
        # at least once and visible on all decision surfaces) —
        # both lower-is-better
        queries[bh.AQE_SKEW_Q3_S] = aqe_bench["aqe_skew_q3_s"]
        if aqe_bench.get("aqe_ab_q3"):
            queries[bh.AQE_AB_Q3] = aqe_bench["aqe_ab_q3"]
    gate = bh.stamp(
        "bench", queries, backend=line["backend"], higher_is_better=True,
        meta={"rows": n_rows, "engine_sf": engine_sf,
              "device_kind": probe["kind"]})
    line["regression"] = {q: v.get("verdict")
                          for q, v in gate["verdicts"].items()}
    line["regression_overall"] = gate["overall"]

    # process-telemetry tail (service/telemetry): the registry numbers a
    # round-over-round reader diffs (parity with the MULTICHIP artifact)
    from spark_rapids_tpu.service.telemetry import compact_snapshot
    line["telemetry"] = compact_snapshot()

    print(json.dumps(line))


if __name__ == "__main__":
    main()
