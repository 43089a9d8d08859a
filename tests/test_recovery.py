"""Fault-tolerant execution (ISSUE 13): the recoverable-error taxonomy
maps every failure to the right action, the stage-retry driver absorbs
recoverable failures within its conf budget, shuffle outputs survive in
the durable tier, workers die and rejoin, and the deterministic
fault-injection harness (analysis/faults.py) makes all of it reachable
from tests — chaos runs return results identical to fault-free runs,
with the recovery trail visible in telemetry and the flight record
(docs/resilience.md).
"""

import os
import time

import numpy as np
import pandas as pd
import pytest

from procs import readline_bounded

from spark_rapids_tpu.analysis import faults
from spark_rapids_tpu.analysis.faults import FaultSpecError
from spark_rapids_tpu.api.session import RuntimeConf, TpuSession
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exec import recovery
from spark_rapids_tpu.exec.recovery import (InjectedTaskFault,
                                            RecoveryAction, StageRetryState,
                                            classify, retry_stage)
from spark_rapids_tpu.exec.spill import BufferLostError
from spark_rapids_tpu.service.telemetry import FlightRecorder, MetricsRegistry
from spark_rapids_tpu.shuffle.manager import WorkerContext
from spark_rapids_tpu.shuffle.transport import (ShuffleClient,
                                                ShuffleDesyncError,
                                                ShuffleFetchError,
                                                ShuffleProtocolError,
                                                ShuffleStore,
                                                ShuffleWorkerLostError)


@pytest.fixture(autouse=True)
def _clean_harness():
    """Every test leaves the process-global chaos plan disarmed and the
    mesh re-admitted (both are module singletons by design)."""
    yield
    faults.reset()
    recovery.clear_mesh_lost()
    recovery.reset_cache()


def _session(**conf):
    return TpuSession.builder.config(
        {"spark.rapids.tpu.sql.explain": "NONE", **conf}).getOrCreate()


def _counter(name: str) -> float:
    return float(MetricsRegistry.get().counter(name, "x").value)


def _flight_names(kind: str):
    return [e["name"] for e in FlightRecorder.get().events()
            if e["kind"] == kind]


# ---------------------------------------------------------------------------
# Taxonomy: every failure class maps to the right recovery action
# ---------------------------------------------------------------------------

def test_classify_maps_each_taxonomy_type():
    assert classify(ShuffleDesyncError("x")) is RecoveryAction.FAIL_QUERY
    assert classify(ShuffleProtocolError("x")) is RecoveryAction.FAIL_QUERY
    assert classify(ShuffleWorkerLostError(3, "w3 died")) is \
        RecoveryAction.RETRY_STAGE
    assert classify(ShuffleFetchError("gave up")) is \
        RecoveryAction.RETRY_STAGE
    assert classify(BufferLostError("b9")) is RecoveryAction.RETRY_STAGE
    assert classify(InjectedTaskFault("poison")) is \
        RecoveryAction.RETRY_STAGE
    assert classify(ConnectionError("reset")) is RecoveryAction.RETRY_FETCH
    assert classify(OSError("io")) is RecoveryAction.RETRY_FETCH
    # unknown failures propagate unmasked — recovery never eats a bug
    assert classify(ValueError("bug")) is RecoveryAction.FAIL_QUERY


def test_stage_retry_budget_and_backoff():
    rs = StageRetryState("t", max_retries=2, backoff_s=0.0)
    rs.failed(ShuffleFetchError("a"))          # attempt 1: absorbed
    rs.failed(ShuffleFetchError("b"))          # attempt 2: absorbed
    with pytest.raises(ShuffleFetchError, match="c"):
        rs.failed(ShuffleFetchError("c"))      # budget exhausted
    assert rs.attempts == 3


def test_stage_retry_fail_query_types_propagate_immediately():
    rs = StageRetryState("t", max_retries=5, backoff_s=0.0)
    with pytest.raises(ShuffleDesyncError):
        rs.failed(ShuffleDesyncError("diverged"))
    with pytest.raises(ValueError):
        rs.failed(ValueError("not ours"))
    assert rs.attempts == 0                    # never counted as retries


def test_stage_retry_caller_gate_blocks():
    rs = StageRetryState("t", retryable=lambda e: False,
                         max_retries=5, backoff_s=0.0)
    with pytest.raises(ShuffleFetchError):
        rs.failed(ShuffleFetchError("indeterminate upstream"))


def test_retry_stage_driver_recovers_and_discards_partial_state():
    calls = {"n": 0, "discards": []}

    def attempt():
        calls["n"] += 1
        if calls["n"] < 3:
            raise InjectedTaskFault(f"poison {calls['n']}")
        return "ok"

    def on_retry(exc, attempt_no):
        calls["discards"].append(attempt_no)

    before = _counter("tpu_stage_retries_total")
    out = retry_stage("unit", attempt, on_retry=on_retry,
                      max_retries=5, backoff_s=0.0)
    assert out == "ok" and calls["n"] == 3
    assert calls["discards"] == [1, 2]
    assert _counter("tpu_stage_retries_total") >= before + 2
    assert any("stage-retry-unit" in n for n in _flight_names("recovery"))
    assert any("recovered-unit" in n for n in _flight_names("recovery"))


def test_recovery_knobs_prime_from_session_conf():
    _session(**{"spark.rapids.tpu.sql.recovery.maxStageRetries": "7",
                "spark.rapids.tpu.sql.recovery.retryBackoff": "0.0",
                "spark.rapids.tpu.sql.shuffle.durable": "true"})
    assert recovery.max_stage_retries() == 7
    assert recovery.retry_backoff_s() == 0.0
    assert recovery.shuffle_durable()
    # a runtime conf change re-primes (the audit-cache discipline)
    s = TpuSession.active()
    RuntimeConf(s).set("spark.rapids.tpu.sql.recovery.maxStageRetries", "3")
    assert recovery.max_stage_retries() == 3


# ---------------------------------------------------------------------------
# Fault harness: spec grammar, deterministic firing, callbacks
# ---------------------------------------------------------------------------

def test_fault_spec_grammar():
    plan = faults.parse_spec(
        "fetch.fail:2;task.poison@p1b3;conn.kill@4;worker.die;mesh.drop")
    assert [f.point for f in plan] == ["fetch.fail", "task.poison",
                                      "conn.kill", "worker.die",
                                      "mesh.drop"]
    assert plan[0].remaining == 2
    assert (plan[1].pid, plan[1].batch) == (1, 3)
    assert plan[2].after == 4
    assert faults.parse_spec("") == []
    for bad in ("nope.fault", "fetch.fail:0", "fetch.fail:x",
                "task.poison@z9", "worker.die@p1", "fetch.fail@@"):
        with pytest.raises(FaultSpecError):
            faults.parse_spec(bad)


def test_fault_firing_counts_and_selectors():
    faults.install("task.poison:2@p1")
    assert faults.armed()
    assert not faults.fire("task.poison", pid=0)     # selector mismatch
    assert faults.fire("task.poison", pid=1)
    assert faults.fire("task.poison", pid=1)
    assert not faults.fire("task.poison", pid=1)     # count exhausted
    assert not faults.armed()
    # conn.kill fires only once >= `after` chunks were sent
    faults.install("conn.kill@3")
    assert not faults.fire("conn.kill", chunk=2)
    assert faults.fire("conn.kill", chunk=3)


def test_fault_firing_is_observable_and_callbacks_run():
    fired = []
    before = _counter("tpu_faults_injected_total")
    faults.install("worker.die")
    faults.on_fire("worker.die", lambda: fired.append(1))
    faults.on_fire("worker.die", lambda: 1 / 0)   # broken hooks swallowed
    assert faults.fire("worker.die")
    assert fired == [1]
    assert faults.fired_total() == 1
    assert _counter("tpu_faults_injected_total") == before + 1
    assert "worker.die" in _flight_names("fault")


def test_fault_spec_primes_from_session_conf():
    _session(**{"spark.rapids.tpu.sql.faults.spec": "fetch.fail:3"})
    assert faults.armed()
    s = TpuSession.active()
    RuntimeConf(s).set("spark.rapids.tpu.sql.faults.spec", "")
    assert not faults.armed()


# ---------------------------------------------------------------------------
# Durable shuffle tier
# ---------------------------------------------------------------------------

def _host_batch(vals):
    return ColumnarBatch.from_pydict({"a": list(vals)}).fetch_to_host()


def test_durable_store_persists_and_reloads(tmp_path):
    d = str(tmp_path / "w0")
    store = ShuffleStore(durable_dir=d)
    store.register_batch(4, 0, _host_batch([1, 2, 3]))
    store.register_batch(4, 1, _host_batch([4, 5]))
    store.mark_complete(4)
    assert len([f for f in os.listdir(d) if f.endswith(".npz")]) == 2
    # a rejoining worker (fresh process analog): new store, same dir
    store2 = ShuffleStore(durable_dir=d)
    assert store2.reload_durable() == 2
    assert store2.is_complete(4)
    metas = store2.metas(4, [0, 1])
    assert sorted(m.reduce_id for m in metas) == [0, 1]
    got = store2.payload(metas[0].buffer_id)
    assert got is not None
    # removal unlinks the durable files (no leak across shuffles)
    store2.remove_shuffle(4)
    assert not [f for f in os.listdir(d) if f.startswith("buf-4-")]
    assert ShuffleStore(durable_dir=d).reload_durable() == 0


def test_durable_store_tolerates_torn_write(tmp_path):
    d = str(tmp_path / "w0")
    store = ShuffleStore(durable_dir=d)
    store.register_batch(5, 0, _host_batch([1]))
    # a death mid-write leaves a json without a readable npz
    stem = os.path.join(d, "buf-5-1-999")
    with open(stem + ".json", "w") as f:
        f.write('{"buffer_id": 999')        # torn
    with open(stem + ".npz", "wb") as f:
        f.write(b"not-an-npz")
    store2 = ShuffleStore(durable_dir=d)
    assert store2.reload_durable() == 1     # the intact buffer only


def test_local_durable_read_keeps_slices_and_pins_to_disk(tmp_path):
    from spark_rapids_tpu.exec.spill import (SpillableColumnarBatch,
                                             StorageTier)
    from spark_rapids_tpu.shuffle.exchange import (LocalShuffle,
                                                   OUTPUT_FOR_SHUFFLE_PRIORITY)
    _session(**{"spark.rapids.tpu.memory.spillDir": str(tmp_path)})
    sh = LocalShuffle(2, durable=True)
    for p, vals in ((0, [1, 2]), (1, [3])):
        sh.slices[p].append(SpillableColumnarBatch(
            ColumnarBatch.from_pydict({"a": vals}),
            OUTPUT_FOR_SHUFFLE_PRIORITY, sh.catalog))
    schema = ColumnarBatch.from_pydict({"a": [1]}).schema
    first = list(sh.read(0, schema))
    assert first and first[0].num_rows == 2
    # durable: the read did NOT close the slices — a stage retry re-reads
    again = list(sh.read(0, schema))
    assert again and again[0].num_rows == 2
    pinned = sh.pin_outputs_to_disk()
    assert pinned > 0
    assert all(s.catalog.buffers[s._id].tier is StorageTier.DISK
               for slices in sh.slices.values() for s in slices)
    # pinned outputs re-promote transparently on the next read, and the
    # read re-pins them to DISK once the batch is built — retained
    # outputs never stay device-resident after a consumer pass
    paths_before = [sh.catalog.buffers[s._id]._disk_path
                    for s in sh.slices[1]]
    after_pin = list(sh.read(1, schema))
    assert after_pin and after_pin[0].num_rows == 1
    assert all(s.catalog.buffers[s._id].tier is StorageTier.DISK
               for s in sh.slices[1])
    # the re-pin is a zero-IO tier flip: the SAME retained npz payload,
    # not a fresh D2H + savez round trip per read
    assert [sh.catalog.buffers[s._id]._disk_path
            for s in sh.slices[1]] == paths_before
    assert all(os.path.exists(p) for p in paths_before)
    sh.close_pending()
    assert all(s._closed for slices in sh.slices.values() for s in slices)


def test_pin_to_disk_failed_disk_write_keeps_accounting_consistent(
        tmp_path):
    """A disk write failing mid pin_to_disk must not tear the catalog
    byte accounting: the already-landed device->host move stays
    accounted, so later frees cannot drive host_bytes negative while
    device_bytes overcounts phantom pressure."""
    from spark_rapids_tpu.exec.spill import (BufferCatalog,
                                             SpillableColumnarBatch,
                                             StorageTier)
    cat = BufferCatalog(spill_dir=str(tmp_path / "ok"))
    s = SpillableColumnarBatch(
        ColumnarBatch.from_pydict({"a": [1, 2, 3]}), 10, cat)
    dev0, host0 = cat.device_bytes, cat.host_bytes
    cat.spill_dir = str(tmp_path / "file")   # a FILE: makedirs will fail
    (tmp_path / "file").write_text("x")
    with pytest.raises(OSError):
        cat.pin_to_disk(s._id)
    buf = cat.buffers[s._id]
    assert buf.tier is StorageTier.HOST      # host move landed...
    assert cat.device_bytes == dev0 - s.size_bytes   # ...and is accounted
    assert cat.host_bytes == host0 + s.size_bytes
    s.close()                                # removes at HOST tier
    assert cat.device_bytes == dev0 - s.size_bytes
    assert cat.host_bytes == host0           # never negative


def test_shuffle_client_retry_knobs_conf_driven():
    _session(**{"spark.rapids.tpu.sql.shuffle.fetch.maxRetries": "5",
                "spark.rapids.tpu.sql.shuffle.fetch.retryBackoff": "0.01"})
    c = ShuffleClient(lambda: (_ for _ in ()).throw(ConnectionError()))
    assert c.max_retries == 5 and c.retry_backoff_s == 0.01
    pinned = ShuffleClient(lambda: None, max_retries=1,
                           retry_backoff_s=0.5)
    assert pinned.max_retries == 1 and pinned.retry_backoff_s == 0.5


# ---------------------------------------------------------------------------
# Worker death / rejoin
# ---------------------------------------------------------------------------

def _pair(fetch_timeout_s=5.0, durable_dir=None):
    a = WorkerContext(0, 2, fetch_timeout_s=fetch_timeout_s)
    b = WorkerContext(1, 2, fetch_timeout_s=fetch_timeout_s,
                      durable_dir=durable_dir)
    a.set_peers({1: ("127.0.0.1", b.port)})
    b.set_peers({0: ("127.0.0.1", a.port)})
    return a, b


def test_mark_probe_admit_lifecycle():
    a, b = _pair()
    try:
        lost_before = _counter("tpu_worker_lost_total")
        rejoin_before = _counter("tpu_worker_rejoin_total")
        a.mark_worker_lost(1, ConnectionError("refused"))
        a.mark_worker_lost(1)                 # idempotent per episode
        assert a.is_worker_lost(1) and a.lost_workers() == [1]
        assert _counter("tpu_worker_lost_total") == lost_before + 1
        assert any("worker-lost-1" in n for n in _flight_names("recovery"))
        assert a.probe_peer(1)                # b's server is alive
        b.server.stop()
        assert not a.probe_peer(1)
        b.restart_server()
        assert a.probe_peer(1)
        a.admit_worker(1)
        assert not a.is_worker_lost(1)
        assert _counter("tpu_worker_rejoin_total") == rejoin_before + 1
        assert any("worker-rejoin-1" in n
                   for n in _flight_names("recovery"))
    finally:
        a.shutdown()
        b.shutdown()


def test_fetch_recovers_across_worker_death_and_rejoin(tmp_path):
    """The injected worker death (faults worker.die) drops the server at
    the exact protocol point; the fetching peer marks it lost, probes
    with backoff, re-admits the restarted server and re-fetches the
    DURABLE outputs — no partial rows, no query abort."""
    import threading
    _session(**{"spark.rapids.tpu.sql.recovery.maxStageRetries": "60",
                "spark.rapids.tpu.sql.recovery.retryBackoff": "0.02"})
    # fetch_timeout shorter than the rejoin delay: the completion poll
    # must EXHAUST (surfacing worker-lost) rather than silently absorb
    # the outage inside its own connect-retry window
    a, b = _pair(fetch_timeout_s=0.5, durable_dir=str(tmp_path / "w1"))
    try:
        b.store.set_fingerprint(7, "fp")
        b.store.register_batch(7, 0, _host_batch([1, 2, 3]))
        b.store.mark_complete(7)
        faults.install("worker.die")

        def die():
            b.server.stop()
            threading.Timer(1.2, b.restart_server).start()

        faults.on_fire("worker.die", die)
        lost_before = _counter("tpu_worker_lost_total")
        got = a.fetch_from_peer(1, 7, [0], fingerprint="fp")
        assert sorted(got[0].rows()) == [(1,), (2,), (3,)]
        assert faults.fired_total() == 1
        assert _counter("tpu_worker_lost_total") == lost_before + 1
        assert not a.is_worker_lost(1)        # re-admitted on success
        # the durable tier really holds the outputs: a FRESH store (true
        # process-death rejoin) re-serves them
        store2 = ShuffleStore(durable_dir=str(tmp_path / "w1"))
        assert store2.reload_durable() == 1 and store2.is_complete(7)
    finally:
        a.shutdown()
        b.shutdown()


def test_dead_worker_without_rejoin_exhausts_budget_loudly():
    _session(**{"spark.rapids.tpu.sql.recovery.maxStageRetries": "2",
                "spark.rapids.tpu.sql.recovery.retryBackoff": "0.01"})
    a, b = _pair(fetch_timeout_s=1.0)
    b.server.stop()
    try:
        with pytest.raises(ShuffleWorkerLostError) as ei:
            a.fetch_from_peer(1, 3, [0])
        assert ei.value.worker_id == 1
        assert a.is_worker_lost(1)            # stays excluded
    finally:
        a.shutdown()
        b.shutdown()


# ---------------------------------------------------------------------------
# Mesh-participant loss: ICI declines gracefully to DCN
# ---------------------------------------------------------------------------

def test_mesh_drop_declines_ici_exchange_to_dcn():
    from spark_rapids_tpu.api.functions import col
    from spark_rapids_tpu.shuffle.exchange import TpuShuffleExchangeExec
    s = _session(**{"spark.rapids.tpu.sql.mesh.enabled": "true"})
    df = pd.DataFrame({"k": np.arange(64, dtype="int64"),
                       "v": np.arange(64).astype("float64")})

    def planes():
        got = s.createDataFrame(df).repartition(4, col("k")).collect()
        assert len(got) == 64
        out = []

        def walk(n):
            if isinstance(n, TpuShuffleExchangeExec):
                out.append(n.plane_used)
            for c in n.children:
                walk(c)
        walk(s.last_plan())
        return out

    assert planes() == ["ici"]
    faults.install("mesh.drop")
    assert planes() == ["dcn"]                 # declined, still correct
    assert recovery.mesh_lost() is not None
    assert any("mesh-lost" in n for n in _flight_names("recovery"))
    # forced ici is a loud error while the mesh is down
    s2 = _session(**{"spark.rapids.tpu.sql.mesh.enabled": "true",
                     "spark.rapids.tpu.sql.shuffle.plane": "ici"})
    with pytest.raises(RuntimeError, match="lost a participant"):
        s2.createDataFrame(df).repartition(4, col("k")).collect()
    recovery.clear_mesh_lost()
    assert planes() == ["ici"]                 # re-admitted


# ---------------------------------------------------------------------------
# q3-shaped chaos integration: local mode, lockdep=enforce
# ---------------------------------------------------------------------------

def _q3_frames(n=4000):
    rng = np.random.default_rng(13)
    line = pd.DataFrame({
        "l_order": rng.integers(0, 500, n).astype("int64"),
        "l_price": rng.normal(100.0, 10.0, n)})
    orders = pd.DataFrame({
        "o_key": np.arange(500, dtype="int64"),
        "o_cust": rng.integers(0, 50, 500).astype("int64"),
        "o_date": rng.integers(0, 1000, 500).astype("int64")})
    cust = pd.DataFrame({
        "c_key": np.arange(50, dtype="int64"),
        "c_seg": rng.integers(0, 3, 50).astype("int64")})
    return line, orders, cust


_Q3 = ("SELECT l_price, o_date, c_seg FROM q3_lineitem "
       "JOIN q3_orders ON l_order = o_key "
       "JOIN q3_customer ON o_cust = c_key "
       "WHERE o_date < 700 AND c_seg = 1")


def _q3_session(**extra):
    s = _session(**{
        "spark.rapids.tpu.sql.shuffle.partitions": "4",
        "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": "-1",
        "spark.rapids.tpu.sql.mesh.enabled": "false",
        "spark.rapids.tpu.sql.reader.batchSizeRows": "512",
        "spark.rapids.tpu.sql.recovery.maxStageRetries": "4",
        "spark.rapids.tpu.sql.recovery.retryBackoff": "0.0",
        "spark.rapids.tpu.sql.analysis.lockdep": "enforce",
        **extra})
    line, orders, cust = _q3_frames()
    s.createDataFrame(line).createOrReplaceTempView("q3_lineitem")
    s.createDataFrame(orders).createOrReplaceTempView("q3_orders")
    s.createDataFrame(cust).createOrReplaceTempView("q3_customer")
    return s


def test_q3_chaos_fetch_failure_and_task_poison_identical_results():
    """ISSUE 13 satellite + acceptance shape: a multi-batch q3-shaped
    3-way shuffled join completes with results IDENTICAL to the
    fault-free run under one injected mid-query fetch failure and one
    injected map-task poison, with the stage retries visible in
    telemetry and the flight record — all under lockdep=enforce."""
    s = _q3_session()
    baseline = sorted(s.sql(_Q3).collect())
    assert baseline                             # non-trivial result set
    retries_before = _counter("tpu_stage_retries_total")
    faults_before = _counter("tpu_faults_injected_total")
    faults.install("fetch.fail;task.poison@b1")
    t0 = time.perf_counter()
    got = sorted(s.sql(_Q3).collect())
    recovery_wall = time.perf_counter() - t0
    assert got == baseline
    assert faults.fired_total() == 2
    assert _counter("tpu_stage_retries_total") >= retries_before + 2
    assert _counter("tpu_faults_injected_total") == faults_before + 2
    rec = _flight_names("recovery")
    assert any(n.startswith("stage-retry-shuffle-reduce") for n in rec)
    assert any(n.startswith("stage-retry-shuffle-map") for n in rec)
    flts = _flight_names("fault")
    assert "fetch.fail" in flts and "task.poison" in flts
    assert recovery_wall < 120                  # bounded, not hung
    # the recovery-seconds histogram observed the episode
    txt = MetricsRegistry.get().prometheus_text()
    count_lines = [l for l in txt.splitlines()
                   if l.startswith("tpu_recovery_seconds_count")]
    assert count_lines and float(count_lines[0].split()[-1]) >= 1


def test_q3_durable_retry_rereads_without_map_rerun(tmp_path):
    """With the durable tier on, a consumer-side retry re-reads the
    retained slices: results identical, and the flight record shows the
    retry recovered without the refill path discarding correctness."""
    s = _q3_session(**{
        "spark.rapids.tpu.sql.shuffle.durable": "true",
        "spark.rapids.tpu.memory.spillDir": str(tmp_path)})
    baseline = sorted(s.sql(_Q3).collect())
    faults.install("fetch.fail:2")
    got = sorted(s.sql(_Q3).collect())
    assert got == baseline and faults.fired_total() == 2


# ---------------------------------------------------------------------------
# Two-process chaos: worker death + mid-window transport kill, planner-driven
# ---------------------------------------------------------------------------

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHAOS_WORKER = """
import sys, json, threading
sys.path.insert(0, {repo!r})
import os
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
from spark_rapids_tpu.shuffle.manager import init_worker

wid = int(sys.argv[1]); n = int(sys.argv[2]); durable_root = sys.argv[3]
ctx = init_worker(wid, n, fetch_timeout_s=0.7,
                  durable_dir=os.path.join(durable_root, f"w{{wid}}"))
print(json.dumps({{"port": ctx.port}}), flush=True)
peers = json.loads(sys.stdin.readline())
ctx.set_peers({{int(k): tuple(v) for k, v in peers.items()}})

from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.functions import col

s = TpuSession.builder.config({{
    "spark.rapids.tpu.sql.explain": "NONE",
    "spark.rapids.tpu.sql.shuffle.partitions": "4",
    "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": "-1",
    "spark.rapids.tpu.sql.reader.batchSizeRows": "128",
    "spark.rapids.tpu.sql.analysis.lockdep": "enforce",
    "spark.rapids.tpu.sql.recovery.maxStageRetries": "120",
    "spark.rapids.tpu.sql.recovery.retryBackoff": "0.02",
}}).getOrCreate()

# chaos plan (armed AFTER session bootstrap so faults.refresh cannot
# clear it): worker 1 dies at its server's next connection and rejoins
# 1.5s later; a later send window tears mid-stream; worker 0 fails its
# first fetch attempt before touching the wire
from spark_rapids_tpu.analysis import faults
if wid == 1:
    faults.install("worker.die;conn.kill")

    def _die():
        ctx.server.stop()
        threading.Timer(1.5, ctx.restart_server).start()

    faults.on_fire("worker.die", _die)
else:
    faults.install("fetch.fail")

# disjoint q3-shaped shards: each table row lives on exactly ONE worker
half_o = 250; half_c = 25; n_l = 400
base_l = wid * n_l
lo = {{"l_order": [(base_l + i) % 500 for i in range(n_l)],
      "l_price": [float(i % 97) + 0.25 for i in range(n_l)]}}
oo = {{"o_key": list(range(wid * half_o, (wid + 1) * half_o)),
      "o_cust": [k % 50 for k in range(wid * half_o, (wid + 1) * half_o)]}}
cc = {{"c_key": list(range(wid * half_c, (wid + 1) * half_c)),
      "c_seg": [k % 3 for k in range(wid * half_c, (wid + 1) * half_c)]}}
s.createDataFrame(lo).createOrReplaceTempView("cl")
s.createDataFrame(oo).createOrReplaceTempView("co")
s.createDataFrame(cc).createOrReplaceTempView("cc")

out = (s.table("cl")
       .join(s.table("co"), on=(col("l_order") == col("o_key")),
             how="inner")
       .join(s.table("cc"), on=(col("o_cust") == col("c_key")),
             how="inner")
       .groupBy("c_seg")
       .agg(F.sum(col("l_price")).alias("rev"))
       .collect())

from spark_rapids_tpu.service.telemetry import FlightRecorder, MetricsRegistry
reg = MetricsRegistry.get()

def cval(nm):
    return float(reg.counter(nm, "x").value)

ev = FlightRecorder.get().events()
print(json.dumps({{
    "rows": [list(r) for r in out],
    "stage_retries": cval("tpu_stage_retries_total"),
    "worker_lost": cval("tpu_worker_lost_total"),
    "worker_rejoin": cval("tpu_worker_rejoin_total"),
    "faults": faults.fired_total(),
    "recovery_events": sorted({{e["name"] for e in ev
                               if e["kind"] == "recovery"}}),
    "fault_events": sorted({{e["name"] for e in ev
                            if e["kind"] == "fault"}})}}), flush=True)
ctx.shutdown()
"""


def _chaos_oracle():
    """Pandas oracle over the union of both workers' disjoint shards."""
    frames_l, frames_o, frames_c = [], [], []
    for wid in range(2):
        base_l = wid * 400
        frames_l.append(pd.DataFrame({
            "l_order": [(base_l + i) % 500 for i in range(400)],
            "l_price": [float(i % 97) + 0.25 for i in range(400)]}))
        okeys = list(range(wid * 250, (wid + 1) * 250))
        frames_o.append(pd.DataFrame(
            {"o_key": okeys, "o_cust": [k % 50 for k in okeys]}))
        ckeys = list(range(wid * 25, (wid + 1) * 25))
        frames_c.append(pd.DataFrame(
            {"c_key": ckeys, "c_seg": [k % 3 for k in ckeys]}))
    j = (pd.concat(frames_l)
         .merge(pd.concat(frames_o), left_on="l_order", right_on="o_key")
         .merge(pd.concat(frames_c), left_on="o_cust", right_on="c_key"))
    return {int(k): float(v)
            for k, v in j.groupby("c_seg").l_price.sum().items()}


def test_two_process_chaos_worker_death_and_conn_kill(tmp_path):
    """ISSUE 13 acceptance: a multi-batch q3-shaped shuffled join across
    two OS processes, green under lockdep=enforce, with an injected
    WORKER DEATH (+1.5s rejoin) and an injected MID-WINDOW TRANSPORT
    KILL on worker 1 plus a first-attempt fetch failure on worker 0 —
    returns results identical to the fault-free oracle, with >=1 stage
    retry and >=1 worker-lost (and rejoin) event visible in telemetry
    and the flight record."""
    import json
    import subprocess
    import sys
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    procs = [subprocess.Popen(
        [sys.executable, "-c", _CHAOS_WORKER.format(repo=_REPO),
         str(wid), "2", str(tmp_path)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, env=env, text=True) for wid in range(2)]
    try:
        ports = {}
        for wid, p in enumerate(procs):
            line = readline_bounded(p)
            assert line, p.stderr.read()
            ports[wid] = ("127.0.0.1", json.loads(line)["port"])
        peers = json.dumps({str(w): list(a) for w, a in ports.items()})
        for p in procs:
            p.stdin.write(peers + "\n")
            p.stdin.flush()
        reports = {}
        for wid, p in enumerate(procs):
            out, err = p.communicate(timeout=280)
            assert p.returncode == 0, err[-4000:]
            for line in out.splitlines():
                try:
                    d = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if "rows" in d:
                    reports[wid] = d
        assert set(reports) == {0, 1}
        # identical to the fault-free run: union of owned partitions
        # equals the pandas oracle over the union of shards
        got = {}
        for d in reports.values():
            for k, v in d["rows"]:
                assert k not in got      # each group owned exactly once
                got[int(k)] = float(v)
        oracle = _chaos_oracle()
        assert set(got) == set(oracle)
        for k in oracle:
            assert abs(got[k] - oracle[k]) <= 1e-6 * max(1.0, oracle[k])
        # every armed fault fired: death + torn window on w1, fetch on w0
        assert reports[0]["faults"] == 1
        assert "fetch.fail" in reports[0]["fault_events"]
        assert reports[1]["faults"] == 2
        assert "worker.die" in reports[1]["fault_events"]
        assert "conn.kill" in reports[1]["fault_events"]
        # the recovery trail: worker 0 lost its peer, retried the fetch
        # stage, and re-admitted the rejoined worker
        assert reports[0]["stage_retries"] >= 1
        assert reports[0]["worker_lost"] >= 1
        assert reports[0]["worker_rejoin"] >= 1
        rec = reports[0]["recovery_events"]
        assert any(n.startswith("worker-lost-1") for n in rec)
        assert any(n.startswith("worker-rejoin-1") for n in rec)
        assert any(n.startswith("stage-retry-") for n in rec)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
