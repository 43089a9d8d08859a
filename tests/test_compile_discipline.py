"""Compile-time & HBM discipline (ISSUE 10, docs/compile.md): the
persistent compile cache round trip, buffer donation, and the
capacity-bucket compile-once invariant."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fresh_session(**conf):
    from spark_rapids_tpu.api.session import TpuSession
    base = {"spark.rapids.tpu.sql.explain": "NONE"}
    base.update(conf)
    return TpuSession.builder.config(base).getOrCreate()


@pytest.fixture
def default_compile_conf():
    """Restore the default compile gates after a test flips them (the
    donation/cacheDir primes are process-global)."""
    yield
    from spark_rapids_tpu.exec import compile_cache
    _fresh_session()
    compile_cache.configure(None)


# ---------------------------------------------------------------------------
# Persistent cache round trip across a process restart
# ---------------------------------------------------------------------------

_CHILD = r"""
import json, sys, time
t0 = time.time()
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.functions import col
session = TpuSession.builder.config({
    "spark.rapids.tpu.sql.explain": "NONE",
    "spark.rapids.tpu.sql.compile.cacheDir": sys.argv[1]}).getOrCreate()
import numpy as np
rng = np.random.default_rng(3)
df = session.createDataFrame({
    "k": [int(x) for x in rng.integers(0, 50, 4000)],
    "v": [float(x) for x in rng.normal(0, 10, 4000)]})
out = (df.filter(col("v") > 0).groupBy("k")
       .agg(F.sum("v").alias("s"), F.count("*").alias("c"))
       .collect())
assert len(out) == 50, len(out)
from spark_rapids_tpu.analysis import recompile
rep = recompile.report()
print(json.dumps({
    "wall_s": round(time.time() - t0, 3),
    "cold": sum(v["coldCompiles"] for v in rep.values()),
    "disk": sum(v["diskHits"] for v in rep.values()),
    "compile_s": round(sum(v["compileS"] for v in rep.values()), 3),
    "families": sorted(rep)}))
"""


def _run_child(cache_dir):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("SPARK_RAPIDS_TPU_CONF__SPARK__RAPIDS__TPU__SQL"
            "__ANALYSIS__LOCKDEP", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)   # the test places its own
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, cache_dir],
        capture_output=True, text=True, timeout=600, cwd=ROOT, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_persistent_cache_round_trip_across_processes(tmp_path):
    """Same shapes in a FRESH process against the same compile.cacheDir:
    zero cold builds — every program classifies as a disk hit (the
    signature index persisted by process 1) — and compile seconds are
    metered in both."""
    cache_dir = str(tmp_path / "compile_cache")
    first = _run_child(cache_dir)
    assert first["cold"] > 0          # the seeding run builds for real
    assert first["compile_s"] > 0
    # jax's on-disk cache wrote executables + our index beside them
    assert os.path.exists(
        os.path.join(cache_dir, "fused_signature_index.jsonl"))
    second = _run_child(cache_dir)
    assert second["cold"] == 0, (
        f"warm restart paid {second['cold']} cold compiles "
        f"(families: {second['families']})")
    assert second["disk"] > 0
    # the warm process loads executables from disk: its compile seconds
    # must undercut the cold run's (a full re-trace would match them)
    assert second["compile_s"] < first["compile_s"]


def test_unwritable_cache_dir_warns_never_fails(caplog,
                                               default_compile_conf):
    """A bad cacheDir logs a loud warning and degrades to in-memory
    caching — the query still runs."""
    import logging
    with caplog.at_level(logging.WARNING, logger="spark_rapids_tpu.compile"):
        session = _fresh_session(**{
            "spark.rapids.tpu.sql.compile.cacheDir": "/dev/null/nope"})
    assert any("not usable" in r.message and "DISABLED" in r.message
               for r in caplog.records)
    from spark_rapids_tpu.exec import compile_cache
    assert compile_cache.active_dir() is None
    rows = session.createDataFrame({"a": [1, 2, 3]}).collect()
    assert [r[0] for r in rows] == [1, 2, 3]


# ---------------------------------------------------------------------------
# Where the cache lives: one helper, placeable from outside
# ---------------------------------------------------------------------------

_CACHE_DIR_CHILD = r"""
import json, sys
import jax
import spark_rapids_tpu
seen = [jax.config.jax_compilation_cache_dir]          # after import
from spark_rapids_tpu.api.session import TpuSession
for conf_dir in ("", sys.argv[1]):
    TpuSession.builder.config({
        "spark.rapids.tpu.sql.explain": "NONE",
        "spark.rapids.tpu.sql.compile.cacheDir": conf_dir}).getOrCreate()
    seen.append(jax.config.jax_compilation_cache_dir)  # after bootstrap
from spark_rapids_tpu.exec import compile_cache
print(json.dumps({"seen": seen, "managed": compile_cache.active_dir(),
                  "minS": jax.config.jax_persistent_cache_min_compile_time_secs}))
"""


def _cache_dir_child(conf_dir, env_dir=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = subprocess.run(
        [sys.executable, "-c", _CACHE_DIR_CHILD, conf_dir],
        capture_output=True, text=True, timeout=300, cwd=ROOT, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_env_cache_dir_is_the_only_directory_the_program_uses(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, importing the package and
    bootstrapping a session — without and with compile.cacheDir — leaves
    jax's cache directory equal to it; the managed layer (signature
    index, prewarm corpus) sits in that same directory."""
    env_dir = str(tmp_path / "from_env")
    out = _cache_dir_child(str(tmp_path / "from_conf"), env_dir=env_dir)
    assert out["seen"] == [env_dir] * 3
    assert out["managed"] == env_dir
    assert not os.path.exists(str(tmp_path / "from_conf"))
    assert out["minS"] == 0.0            # every program is kept


def test_default_cache_dir_is_one_fixed_path_in_the_checkout(tmp_path):
    """Unset, the directory is a fixed path inside the checkout —
    identical across two fresh processes (never home-, pid-, time- or
    mkdtemp-derived: XLA keys its cache by path) — and a session's
    compile.cacheDir still places it."""
    conf_dir = str(tmp_path / "from_conf")
    first = _cache_dir_child(conf_dir)
    second = _cache_dir_child(conf_dir)
    fixed = os.path.join(ROOT, ".jax_cache")
    assert first["seen"] == second["seen"] == [fixed, fixed, conf_dir]
    assert first["managed"] == conf_dir


# ---------------------------------------------------------------------------
# Buffer donation
# ---------------------------------------------------------------------------

def _filter_stage():
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.ops import expressions as ex
    from spark_rapids_tpu.ops import predicates as pr
    from spark_rapids_tpu.plan import physical as P
    schema = dt.Schema([dt.Field("v", dt.FLOAT64)])
    pred = pr.GreaterThan(ex.BoundReference(0, dt.FLOAT64, True),
                          ex.Literal(0.0, dt.FLOAT64))
    return schema, P.FusedStage([pred], schema, schema, mode="filter")


def _batch(schema, n, seed=0):
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    rng = np.random.default_rng(seed)
    return ColumnarBatch.from_pydict({"v": rng.normal(0, 1, n)}, schema)


def test_donation_deletes_consumed_buffer(default_compile_conf):
    """A fused filter CONSUMES its input: with compile.donate on
    (default) the batch's device buffers are deleted the moment the
    program ingests them — the eager-HBM-release invariant."""
    _fresh_session()
    schema, stage = _filter_stage()
    b = _batch(schema, 1000)
    arrays = b.flat_arrays()
    res = stage(b)
    assert res is not None
    assert all(a.is_deleted() for a in arrays), \
        "donated input buffers survived the fused call"
    # the output is intact and correct
    cols, count = res
    assert int(count) == int(np.sum(
        np.asarray(_batch(schema, 1000).columns[0].data)[:1000] > 0))


def test_donation_skips_shared_and_origin_batches(default_compile_conf):
    """Catalog-acquired (shared) and scan-cache-served (origin) batches
    must NEVER be donated — their arrays are re-read later."""
    _fresh_session()
    schema, stage = _filter_stage()
    b = _batch(schema, 1000)
    b.shared = True
    arrays = b.flat_arrays()
    assert stage(b) is not None
    assert not any(a.is_deleted() for a in arrays)
    b2 = _batch(schema, 1000, seed=1)
    b2.origin = object()      # any live owner marker
    arrays2 = b2.flat_arrays()
    assert stage(b2) is not None
    assert not any(a.is_deleted() for a in arrays2)


def test_donation_conf_off_keeps_buffers(default_compile_conf):
    _fresh_session(**{"spark.rapids.tpu.sql.compile.donate": "false"})
    schema, stage = _filter_stage()
    b = _batch(schema, 1000)
    arrays = b.flat_arrays()
    assert stage(b) is not None
    assert not any(a.is_deleted() for a in arrays)


def test_spill_acquired_batch_marked_shared():
    """BufferCatalog.acquire_batch marks its batches shared, so the
    donation gate can never free arrays the spill store still owns."""
    from spark_rapids_tpu.exec.spill import SpillableColumnarBatch
    _fresh_session()
    schema, _ = _filter_stage()
    handle = SpillableColumnarBatch(_batch(schema, 256))
    try:
        got = handle.get_batch()
        assert got.shared is True
    finally:
        handle.close()


# ---------------------------------------------------------------------------
# Bucket discipline: ragged sizes share one size class -> one compile
# ---------------------------------------------------------------------------

def test_ragged_batches_share_one_compile(default_compile_conf):
    """Batches of 1000 and 1017 rows both bucket to capacity 1024: the
    second run must compile NOTHING new (the size-class invariant the
    whole discipline exists for)."""
    from spark_rapids_tpu.analysis import recompile
    _fresh_session()
    schema, stage = _filter_stage()
    assert stage(_batch(schema, 1000)) is not None
    snap = recompile.snapshot()
    assert stage(_batch(schema, 1017, seed=2)) is not None
    d = recompile.delta(snap)
    assert sum(v["compiles"] for v in d.values()) == 0, d
    # and both batches really did share the 1024 size class
    assert _batch(schema, 1000).capacity == _batch(schema, 1017).capacity


def test_size_class_audit_traces_unbucketed_dims():
    """The audit names the non-power-of-two dimension that made a
    signature distinct."""
    from spark_rapids_tpu.analysis import recompile
    assert recompile.unbucketed_dims(
        ("fam", ("sig",), 1024, (999, 128))) == [999]
    assert recompile.unbucketed_dims(("fam", 512, 8, 2, True)) == []


# ---------------------------------------------------------------------------
# ISSUE 25: the program boundary — names, dispatch counts, XLA's own
# report of what a build was
# ---------------------------------------------------------------------------

_BACKEND = "/jax/core/compile/backend_compile_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_HIT = "/jax/compilation_cache/cache_hits"


@pytest.mark.parametrize("key, family, name", [
    (("agg", "update", "partial", ("k", 1024), 4096, "sort", 8388608),
     "agg/update/partial/sort", "agg_update_partial_sort"),
    (("stage", (("a", "float64"),), ("donate", True)), "stage", "stage"),
    (("mesh", "groupby-v2", 8), "mesh/groupby-v2", "mesh_groupby_v2"),
    ((7, 9), "anon", "anon"),
])
def test_program_name_is_a_function_of_the_keys_tags_alone(key, family,
                                                           name):
    """No shape, literal or ``id()`` of the key reaches the name a
    program compiles under: it is the same in every process."""
    from spark_rapids_tpu.analysis import recompile
    from spark_rapids_tpu.exec import compile_cache
    assert recompile.kernel_of(key) == family
    assert compile_cache.program_name(family) == name
    assert name.isidentifier()


def test_program_counts_each_call_once_and_leaves_the_breadcrumb(
        tmp_path, monkeypatch, default_compile_conf):
    """One count per call, for the audit and for the active query alike;
    the first call still writes ``SRT_COMPILE_TRACE``'s BEGIN/END pair,
    later calls none."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.analysis import recompile
    from spark_rapids_tpu.exec import compile_cache
    from spark_rapids_tpu.exec.tracing import SpanRecorder
    _fresh_session()
    crumbs = tmp_path / "compile_trace.txt"
    monkeypatch.setenv("SRT_COMPILE_TRACE", str(crumbs))
    prog = compile_cache.Program(jax.jit(lambda x: x * 3 + 1),
                                 "test25/breadcrumb")
    assert prog._fn.__wrapped__.__name__ == "test25_breadcrumb"
    base = recompile.snapshot()
    with SpanRecorder() as rec:
        for _ in range(3):
            assert float(prog(jnp.float32(2.0))) == 7.0
    lines = crumbs.read_text().splitlines()
    assert [ln.split()[0] for ln in lines] == ["BEGIN", "END"]
    assert "test25/breadcrumb" in lines[0] and "maps=" in lines[0]
    ent = recompile.delta(base)["test25/breadcrumb"]
    assert ent["calls"] == 3 and ent["compiles"] == 0
    assert ent["coldCompiles"] + ent["diskHits"] == 1     # XLA's count
    mine = rec.programs["test25/breadcrumb"]
    assert mine["dispatches"] == 3 and mine["traces"] == 1
    assert mine["compiles"] + mine["cacheLoads"] == 1
    assert ent["compileS"] == pytest.approx(
        mine["traceS"] + mine["lowerS"] + mine["compileS"] + mine["loadS"],
        abs=1e-3)
    assert compile_cache.open_family() is None


def test_cold_or_disk_is_what_xla_reports(default_compile_conf):
    """A backend-compile duration after a ``cache_hits`` event on the same
    thread is a LOAD; without one, a compilation. Each goes to the family
    whose program is being called, or to ``<eager>:<op>`` — which stays
    out of the process-wide audit. The side index forecasts, for the
    compile pool, and classifies nothing."""
    import jax.monitoring as m
    from spark_rapids_tpu.analysis import recompile
    from spark_rapids_tpu.exec import compile_cache
    from spark_rapids_tpu.exec.tracing import SpanRecorder
    _fresh_session()
    assert not hasattr(compile_cache, "classify")
    assert compile_cache.seen_on_disk(("never", "built")) is False

    def builds(*_):
        m.record_event_duration_secs(_LOWER, 0.125, fun_name="jit(x)")
        m.record_event(_HIT)
        m.record_event_duration_secs(_BACKEND, 0.25, fun_name="jit(x)")
        m.record_event_duration_secs(_BACKEND, 0.5, fun_name="jit(x)")

    prog = compile_cache.Program(builds, "test25/xla-truth")
    base = recompile.snapshot()
    with SpanRecorder() as rec:
        prog()
        builds()                      # the same events, no program open
    ent = recompile.delta(base)["test25/xla-truth"]
    assert (ent["diskHits"], ent["coldCompiles"], ent["calls"]) == (1, 1, 1)
    assert ent["compileS"] == pytest.approx(0.875)
    called = dict(rec.programs["test25/xla-truth"])
    # the host's seconds inside the one call (PR 36): a clock reading
    assert 0.0 < called.pop("dispatchS") < 1.0
    assert called == {
        "dispatches": 1, "traces": 0, "traceS": 0.0, "lowerS": 0.125,
        "compiles": 1, "compileS": 0.5, "cacheLoads": 1, "loadS": 0.25}
    assert rec.programs["<eager>:x"]["dispatchS"] == 0.0
    eager = rec.programs["<eager>:x"]
    assert (eager["dispatches"], eager["compiles"],
            eager["cacheLoads"]) == (0, 1, 1)
    assert "<eager>:x" not in recompile.report()
    assert rec.report()["<no-span>"]["rebuilds"] == 6
