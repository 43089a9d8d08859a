"""Lockstep-determinism discipline (ISSUE 18): the divergence audit's
digest fold/compare semantics, query-namespaced shuffle-id minting, the
DesyncError recovery contract, and the two-OS-process acceptance runs —
two CONCURRENT distributed queries returning oracle-correct rows under
``divergence=enforce``, and an injected desync surfacing the typed error
naming the first divergent event.
"""

import json
import os
import subprocess
import sys
import tempfile
import threading

import pandas as pd
import pytest

from procs import readline_bounded

from spark_rapids_tpu.analysis import divergence
from spark_rapids_tpu.analysis.divergence import DesyncError

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_audit():
    divergence.reset()
    yield
    divergence.reset()


# ---------------------------------------------------------------------------
# Digest fold / snapshot / compare units
# ---------------------------------------------------------------------------

def _fold(qid, labels):
    for lb in labels:
        divergence.note_event(lb, query_id=qid)


def test_fold_and_snapshot_shape():
    divergence.install("record")
    _fold("q1", ["a", "b", "c"])
    snap = divergence.snapshot("q1")
    assert snap["count"] == 3
    assert len(snap["digest"]) == 16
    assert [tuple(e)[0::2] for e in snap["ring"]] == \
        [(1, "a"), (2, "b"), (3, "c")]
    # identical streams digest identically; unknown query is the empty
    # stream (the peer treats it as lag)
    _fold("q2", ["a", "b", "c"])
    assert divergence.snapshot("q2")["digest"] == snap["digest"]
    assert divergence.snapshot("q9") == \
        {"count": 0, "digest": "", "ring": []}
    divergence.reset()
    assert divergence.snapshot("q1") is None      # off: no audit surface


def test_check_names_first_divergent_event():
    divergence.install("enforce")
    _fold("q1", ["mint:1", "mint:2", "mint:3"])
    _fold("q2", ["mint:1", "rogue", "mint:3"])    # stand-in peer stream
    peer = divergence.snapshot("q2")
    with pytest.raises(DesyncError) as ei:
        divergence.check("q1", peer, peer_label="worker 1")
    e = ei.value
    assert e.query_id == "q1"
    assert e.first_divergent_index == 2
    assert e.mine[1] == "mint:2" and e.theirs[1] == "rogue"
    assert "mint:2" in str(e) and "rogue" in str(e)
    assert "worker 1" in str(e)
    st = divergence.stats()
    assert st["checks"] == 1 and st["desyncs"] == 1


def test_lag_is_not_divergence():
    divergence.install("enforce")
    _fold("q1", ["a", "b", "c", "d"])
    _fold("q2", ["a", "b"])                       # same prefix, behind
    divergence.check("q1", divergence.snapshot("q2"))
    divergence.check("q2", divergence.snapshot("q1"))
    # a peer that has not folded the query at all is pure lag too
    divergence.check("q1", {"count": 0, "digest": "", "ring": []})
    st = divergence.stats()
    assert st["checks"] == 3 and st["desyncs"] == 0


def test_record_mode_counts_without_raising():
    divergence.install("record")
    _fold("q1", ["a", "b"])
    _fold("q2", ["a", "x"])
    divergence.check("q1", divergence.snapshot("q2"))   # no raise
    assert divergence.stats()["desyncs"] == 1


def test_pre_window_divergence_reports_index_minus_one():
    divergence.install("enforce")
    _fold("q1", ["a", "b"])
    # same event count, non-empty differing digest, NO common ring
    # window: the divergence predates the diagnostic ring
    peer = {"count": 2, "digest": "feedfacecafebeef", "ring": []}
    with pytest.raises(DesyncError) as ei:
        divergence.check("q1", peer)
    assert ei.value.first_divergent_index == -1
    assert "diagnostic window" in str(ei.value)


def test_install_rejects_unknown_mode_and_off_is_noop():
    with pytest.raises(ValueError):
        divergence.install("audit-harder")
    divergence.reset()
    assert not divergence.armed()
    divergence.note_event("a", query_id="q1")     # no-op while off
    divergence.check("q1", {"count": 1, "digest": "ff", "ring": []})
    assert divergence.stats() == \
        {"mode": "off", "checks": 0, "desyncs": 0, "queries": 0}


def test_ring_is_bounded_and_digest_rolls_past_it():
    divergence.install("record")
    _fold("q1", [f"e{i}" for i in range(divergence.RING_CAPACITY + 10)])
    snap = divergence.snapshot("q1")
    assert snap["count"] == divergence.RING_CAPACITY + 10
    assert len(snap["ring"]) == divergence.RING_CAPACITY
    assert snap["ring"][0][0] == 11               # oldest entries evicted


def test_desync_error_classifies_fail_query():
    from spark_rapids_tpu.exec.recovery import RecoveryAction, classify
    e = DesyncError("streams diverged", query_id="q1", index=3,
                    mine=("aa", "x"), theirs=("bb", "y"))
    assert classify(e) is RecoveryAction.FAIL_QUERY


# ---------------------------------------------------------------------------
# Query-namespaced shuffle ids (the concurrent-distributed gating fix)
# ---------------------------------------------------------------------------

def test_shuffle_ids_namespaced_by_query_sequence():
    from spark_rapids_tpu.exec import query_context as qc
    from spark_rapids_tpu.shuffle.manager import NS_SHIFT, WorkerContext
    wc = WorkerContext(0, 1)
    try:
        ctx_a = qc.QueryContext("q000041-aaaaaaaa")
        ctx_b = qc.QueryContext("q000042-bbbbbbbb")
        got_a, got_b = [], []
        # interleave mints across the two ambient queries: each draws
        # from its OWN counter, so the interleaving cannot skew either
        for _ in range(3):
            with qc.query_scope(ctx_a):
                got_a.append(wc.next_shuffle_id())
            with qc.query_scope(ctx_b):
                got_b.append(wc.next_shuffle_id())
        base_a, base_b = 41 << NS_SHIFT, 42 << NS_SHIFT
        assert got_a == [base_a + 1, base_a + 2, base_a + 3]
        assert got_b == [base_b + 1, base_b + 2, base_b + 3]
        # no ambient query -> namespace 0 (direct shuffle-layer callers)
        assert wc.next_shuffle_id() == 1
    finally:
        wc.shutdown()


def test_shuffle_id_mints_fold_into_divergence_stream():
    from spark_rapids_tpu.exec import query_context as qc
    from spark_rapids_tpu.shuffle.manager import NS_SHIFT, WorkerContext
    divergence.install("record")
    wc = WorkerContext(0, 1)
    try:
        with qc.query_scope(qc.QueryContext("q000007-cafecafe")):
            sid = wc.next_shuffle_id()
        snap = divergence.snapshot("q000007-cafecafe")
        assert sid == (7 << NS_SHIFT) + 1
        assert snap["count"] == 1
        assert snap["ring"][0][2] == f"shuffle-id:{sid}"
    finally:
        wc.shutdown()


# ---------------------------------------------------------------------------
# Two OS processes, two CONCURRENT distributed queries (the acceptance
# runs: lockstep-correct under enforce; injected desync surfaces typed)
# ---------------------------------------------------------------------------

_WORKER = """
import sys, json, threading
sys.path.insert(0, {repo!r})
import os
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
from spark_rapids_tpu.shuffle.manager import init_worker

wid = int(sys.argv[1]); n = int(sys.argv[2])
fault = sys.argv[3]; flight_dir = sys.argv[4]
ctx = init_worker(wid, n)
print(json.dumps({{"port": ctx.port}}), flush=True)
peers = json.loads(sys.stdin.readline())
ctx.set_peers({{int(k): tuple(v) for k, v in peers.items()}})

from spark_rapids_tpu.api.session import TpuSession
conf = {{"spark.rapids.tpu.sql.explain": "NONE",
         "spark.rapids.tpu.sql.shuffle.partitions": "4",
         "spark.rapids.tpu.sql.analysis.divergence": "enforce",
         "spark.rapids.tpu.sql.telemetry.flightRecorderDir": flight_dir}}
if fault != "none" and wid == 1:
    # poison ONE lockstep event on THIS worker only: its digest stream
    # now disagrees with worker 0's, and the next META round trip must
    # surface the typed desync
    conf["spark.rapids.tpu.sql.faults.spec"] = fault
s = TpuSession.builder.config(conf).getOrCreate()

base = wid * 1000
ks = [(base + i) % 7 for i in range(200)]
vs = [float(i % 13) for i in range(200)]
s.createDataFrame({{"k": ks, "v": vs}}).createOrReplaceTempView("t")

df_a = s.sql("SELECT k, sum(v) AS sv FROM t GROUP BY k")
df_b = s.sql("SELECT k, count(*) AS c FROM t GROUP BY k")

# the lockstep concurrency discipline (docs/shuffle.md): mint both query
# identities on the MAIN thread in program order — every worker draws
# the same sequence numbers — then collect concurrently under the
# reserved contexts, so the racy collect order never touches the
# query-id counter
from spark_rapids_tpu.exec import query_context as qc
ctx_a = qc.QueryContext(qc.mint_query_id())
ctx_b = qc.QueryContext(qc.mint_query_id())

results = {{}}
def run(name, qctx, df):
    qc.reserve_query(qctx)
    try:
        results[name] = {{"rows": [list(r) for r in df.collect()]}}
    except BaseException as e:
        out = {{"error": type(e).__name__, "msg": str(e),
               "qid": getattr(e, "query_id", None),
               "index": getattr(e, "first_divergent_index", None)}}
        from spark_rapids_tpu.service.telemetry import dump_on_error
        path = dump_on_error(e)
        if path:
            with open(path) as f:
                doc = json.load(f)
            out["dumpQueryId"] = doc.get("queryId")
            out["dumpDesyncEvents"] = sum(
                1 for ev in doc["events"] if ev["kind"] == "desync")
        results[name] = out

ta = threading.Thread(target=run, args=("a", ctx_a, df_a))
tb = threading.Thread(target=run, args=("b", ctx_b, df_b))
ta.start(); tb.start(); ta.join(); tb.join()

from spark_rapids_tpu.analysis import divergence as _div
print(json.dumps({{"wid": wid, "results": results,
                   "stats": _div.stats()}}), flush=True)
ctx.shutdown()
"""


def _run_concurrent_cluster(fault="none", n_workers=2):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    flight_dir = tempfile.mkdtemp(prefix="tpu-flight-determinism-")
    procs = []
    for wid in range(n_workers):
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _WORKER.format(repo=_REPO),
             str(wid), str(n_workers), fault, flight_dir],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, env=env, text=True))
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
        out = {}
        for p in procs:
            stdout, err = p.communicate(timeout=300)
            for line in stdout.splitlines():
                try:
                    d = json.loads(line)
                    if "wid" in d:
                        out[d["wid"]] = d
                except json.JSONDecodeError:
                    continue
            assert p.returncode == 0, err
        assert set(out) == set(range(n_workers)), out
        return out
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()


def _oracle():
    sh = pd.concat(pd.DataFrame({
        "k": [(wid * 1000 + i) % 7 for i in range(200)],
        "v": [float(i % 13) for i in range(200)]}) for wid in range(2))
    g = sh.groupby("k")
    exp_a = sorted((int(k), float(v)) for k, v in g.v.sum().items())
    exp_b = sorted((int(k), int(v)) for k, v in g.v.count().items())
    return exp_a, exp_b


def test_two_process_concurrent_distributed_queries_enforced():
    """The gating acceptance: TWO distributed queries run CONCURRENTLY
    (threads) across two OS processes under divergence=enforce, and both
    return oracle-correct rows — namespaced shuffle ids keep the two
    id streams disjoint, so the interleaving cannot desync them."""
    out = _run_concurrent_cluster("none")
    rows_a, rows_b = [], []
    for wid, doc in out.items():
        for name, res in doc["results"].items():
            assert "error" not in res, (wid, name, res)
        rows_a.extend(tuple(r) for r in doc["results"]["a"]["rows"])
        rows_b.extend(tuple(r) for r in doc["results"]["b"]["rows"])
        assert doc["stats"]["mode"] == "enforce"
        assert doc["stats"]["desyncs"] == 0
    exp_a, exp_b = _oracle()
    assert sorted(rows_a) == exp_a
    assert sorted(rows_b) == exp_b
    # the audit actually ran: every worker compared digests on its
    # peer round trips
    assert all(doc["stats"]["checks"] > 0 for doc in out.values())


def test_injected_desync_raises_typed_error_with_first_event():
    """Chaos acceptance: one poisoned lockstep event on worker 1
    (faults point desync.inject) surfaces DesyncError on the next
    metadata round trip — typed, naming the first divergent event, with
    the flight-recorder dump scoped to the desynced query."""
    out = _run_concurrent_cluster("desync.inject:1")
    errors = [res
              for doc in out.values()
              for res in doc["results"].values()
              if "error" in res]
    assert errors, out
    assert all(e["error"] == "DesyncError" for e in errors), errors
    # the diagnosis names the injected event at a concrete index
    named = [e for e in errors if "desync.inject" in e["msg"]]
    assert named, errors
    for e in named:
        assert e["index"] is not None and e["index"] >= 1
        assert e["qid"] and e["qid"].startswith("q")
    # the post-mortem artifact is scoped to the desynced query and
    # carries the desync flight event
    dumped = [e for e in errors if e.get("dumpQueryId")]
    assert dumped, errors
    for e in dumped:
        assert e["dumpQueryId"] == e["qid"]
        assert e["dumpDesyncEvents"] >= 1
    # the detecting worker counted the desync
    assert any(doc["stats"]["desyncs"] >= 1 for doc in out.values())
