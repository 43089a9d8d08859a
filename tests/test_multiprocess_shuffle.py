"""Multi-process shuffle manager: one planner-driven query runs across
two OS processes over the TCP transport (VERDICT round-3 item 3 — the
local/remote split of RapidsCachingReader.scala:49-148 +
RapidsShuffleInternalManager.scala:200-374).

Each worker process bootstraps a WorkerContext (its own ShuffleStore +
ShuffleServer), registers its LOCAL data shard, and runs the same logical
query; the planner inserts partial->exchange->final aggregates and
co-partitioned shuffled joins whose exchanges route map slices into the
local store and fetch peers' slices over TCP. Every worker's collect
yields the rows of its owned reduce partitions; the parent combines and
golden-compares against pandas."""

import os
import subprocess
import sys
import json

import pandas as pd
import pytest

from procs import readline_bounded

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = """
import sys, json, socket, time
sys.path.insert(0, {repo!r})
import os
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
from spark_rapids_tpu.shuffle.manager import init_worker

wid = int(sys.argv[1]); n = int(sys.argv[2]); query = sys.argv[3]
ctx = init_worker(wid, n)
print(json.dumps({{"port": ctx.port}}), flush=True)
peers = json.loads(sys.stdin.readline())
ctx.set_peers({{int(k): tuple(v) for k, v in peers.items()}})

from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.functions import col

conf = {{"spark.rapids.tpu.sql.explain": "NONE",
         "spark.rapids.tpu.sql.shuffle.partitions": "4"}}
if query == "join_agg":
    # keep the co-partitioned path exercised: without this, the tiny dim
    # table flips the runtime AQE switch and the shuffled join never runs
    conf["spark.rapids.tpu.sql.autoBroadcastJoinThreshold"] = "-1"
s = TpuSession.builder.config(conf).getOrCreate()

# each worker holds its own data SHARD (disjoint by construction)
base = wid * 1000
ks = [(base + i) % 7 for i in range(200)]
vs = [float(i % 13) for i in range(200)]
s.createDataFrame({{"k": ks, "v": vs}}).createOrReplaceTempView("t")
rk = list(range(7))
s.createDataFrame({{"k": rk, "w": [k * 10.0 for k in rk]}}) \\
    .createOrReplaceTempView("dim" )

if query == "agg":
    out = s.sql("SELECT k, sum(v) AS sv, count(*) AS c FROM t GROUP BY k") \\
        .collect()
elif query in ("join_agg", "join_agg_aqe"):
    out = (s.table("t")
           .join(s.table("dim"), on="k", how="inner")
           .groupBy("k")
           .agg(F.sum(col("v") + col("w")).alias("sv"))
           .collect())
else:
    raise SystemExit(f"unknown query {{query}}")

rtb = 0
def _walk(n):
    global rtb
    rtb += int(n.metrics.resolve().get("runtimeBroadcastJoins", 0))
    for c in n.children:
        _walk(c)
_walk(s.last_plan())
print(json.dumps({{"rows": [list(r) for r in out], "rtb": rtb}}), flush=True)
ctx.shutdown()
"""


def _run_cluster(query: str, n_workers: int = 2):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    procs = []
    for wid in range(n_workers):
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _WORKER.format(repo=_REPO),
             str(wid), str(n_workers), query],
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
        rows, rtb = [], 0
        for p in procs:
            out, err = p.communicate(timeout=300)
            for line in out.splitlines():
                try:
                    d = json.loads(line)
                    rows.extend(tuple(r) for r in d["rows"])
                    rtb += d.get("rtb", 0)
                except (json.JSONDecodeError, KeyError):
                    continue
            assert p.returncode == 0, err
        return rows, rtb
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()


def _shards(n_workers: int = 2):
    frames = []
    for wid in range(n_workers):
        base = wid * 1000
        frames.append(pd.DataFrame({
            "k": [(base + i) % 7 for i in range(200)],
            "v": [float(i % 13) for i in range(200)]}))
    return pd.concat(frames)


def test_two_process_planner_driven_aggregate():
    """Two-phase agg: partial -> hash exchange (over TCP between two OS
    processes) -> final; union of both workers' owned partitions equals
    the pandas oracle over the union of shards."""
    rows, _ = _run_cluster("agg")
    got = sorted(rows)
    oracle = _shards().groupby("k").agg(sv=("v", "sum"), c=("v", "count"))
    exp = sorted((int(k), float(r["sv"]), int(r["c"]))
                 for k, r in oracle.iterrows())
    assert got == exp


def _join_agg_oracle():
    sh = _shards()
    dim = pd.DataFrame({"k": list(range(7)),
                        "w": [k * 10.0 for k in range(7)]})
    j = sh.merge(dim, on="k")
    oracle = (j.assign(x=j.v + j.w).groupby("k").x.sum())
    # the dim table is REPLICATED on both workers (a registered dimension,
    # not a shard): the join therefore sees it twice across the cluster —
    # matching real deployments where dims are broadcast-registered
    # per-worker; the oracle doubles it accordingly
    return sorted((int(k), 2 * float(v)) for k, v in oracle.items())


def test_two_process_shuffled_join_plus_aggregate():
    """Co-partitioned shuffled join (both sides exchanged across the two
    processes; static broadcast is disabled because each worker only holds
    a shard of the build side, and the runtime switch is off via
    threshold=-1) followed by a grouped aggregate."""
    rows, rtb = _run_cluster("join_agg")
    assert rtb == 0                       # stayed co-partitioned
    assert sorted(rows) == _join_agg_oracle()


def test_two_process_mesh_consistent_runtime_broadcast():
    """AQE runtime join switch ACROSS WORKERS: the build-side exchange's
    observed size is summed through the control-plane allreduce, every
    worker takes the same branch, and a switch materializes the COMPLETE
    build side (all peers' slices) before broadcast-joining the raw local
    stream shard — same rows as the co-partitioned plan."""
    rows, rtb = _run_cluster("join_agg_aqe")
    assert rtb == 2                       # both workers switched
    assert sorted(rows) == _join_agg_oracle()


def test_fetch_when_complete_waits_for_late_map():
    """A reduce-side fetch issued BEFORE the peer finished (or even
    started) its map phase polls until the completion mark instead of
    reading partial data (the stage-ordering guarantee)."""
    import threading
    import time
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.shuffle.transport import (ShuffleClient,
                                                    ShuffleServer,
                                                    ShuffleStore)
    store = ShuffleStore()
    srv = ShuffleServer(store, port=0).start()
    try:
        def late_map():
            time.sleep(0.3)
            b = ColumnarBatch.from_pydict({"a": [1, 2, 3]})
            store.register_batch(7, 0, b.fetch_to_host())
            store.mark_complete(7)
        t = threading.Thread(target=late_map)
        t.start()
        client = ShuffleClient.for_address("127.0.0.1", srv.port)
        got = client.fetch_when_complete(7, [0], timeout_s=10)
        t.join(timeout=30)
        assert not t.is_alive()
        assert len(got) == 1 and sorted(got[0].rows()) == [(1,), (2,), (3,)]
    finally:
        srv.stop()


def test_fetch_when_complete_times_out():
    """A peer that never completes surfaces ShuffleFetchError (the
    RapidsShuffleFetchFailedException analog the caller maps to a stage
    retry)."""
    from spark_rapids_tpu.shuffle.transport import (ShuffleClient,
                                                    ShuffleFetchError,
                                                    ShuffleServer,
                                                    ShuffleStore)
    srv = ShuffleServer(ShuffleStore(), port=0).start()
    try:
        client = ShuffleClient.for_address("127.0.0.1", srv.port)
        with pytest.raises(ShuffleFetchError):
            client.fetch_when_complete(9, [0], timeout_s=0.4, poll_s=0.05)
    finally:
        srv.stop()
