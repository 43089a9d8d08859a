"""The shared deferred-scalar pipeline window (exec/pipeline.py) and the
pipelined join stream loop built on it.

Reference analog: the per-batch join stream loop with no host sync
(GpuHashJoin.scala:193-249) and the streaming aggregate's in-flight batch
window (aggregate.scala:427-485). On high-latency links the engine's perf
metric of record is the attributed host-sync count (exec/tracing.py), so
these tests pin the O(1)-syncs-per-stage contract, not wall time.
"""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

import jax.numpy as jnp

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.exec.pipeline import PipelineWindow
from spark_rapids_tpu.exec.tracing import SpanRecorder, SyncCounter, trace_span
from spark_rapids_tpu.ops import expressions as ex
from spark_rapids_tpu.ops import predicates as pr
from spark_rapids_tpu.plan import logical as lp
from spark_rapids_tpu.plan.physical import (TpuFilterExec, TpuLocalScanExec,
                                            TpuSortMergeJoinExec)


# ---------------------------------------------------------------------------
# PipelineWindow unit behavior
# ---------------------------------------------------------------------------

def test_depth1_degenerates_to_blocking():
    """depth=1: every push lands its own entry immediately — today's
    read-per-batch cadence, no behavior change."""
    win = PipelineWindow(1)
    out = win.push(lambda v: ("r", int(v)), jnp.int32(7))
    assert out == [("r", 7)]
    assert len(win) == 0
    assert win.flush() == []
    assert win.resolves == 1


def test_window_fills_then_lands_oldest_half():
    win = PipelineWindow(4)
    res = []
    for i in range(3):
        res += win.push(lambda v, i=i: (i, int(v)), jnp.int32(i * 10))
    assert res == []                      # window not yet full: no readback
    assert win.resolves == 0
    res += win.push(lambda v: (3, int(v)), jnp.int32(30))
    assert res == [(0, 0), (1, 10)]       # oldest half landed, FIFO
    assert win.resolves == 1              # ... in ONE batched resolve
    res += win.flush()                    # partition end: drain the rest
    assert res == [(0, 0), (1, 10), (2, 20), (3, 30)]
    assert len(win) == 0


def test_partition_end_flush_empty_window():
    assert PipelineWindow(8).flush() == []


def test_scalar_free_entries_ride_through():
    """Entries with no scalars (semi/anti joins) run immediately when
    nothing older is pending — scalar-free streams stay incremental — but
    queue FIFO behind an in-flight scalar entry."""
    win = PipelineWindow(8)
    assert win.push(lambda: "now") == ["now"]
    assert win.push(lambda v: int(v), jnp.int32(5)) == []
    assert win.push(lambda: "later") == []     # FIFO: must not overtake
    assert win.flush() == [5, "later"]


def test_mixed_dtypes_arrays_and_host_values():
    """int32 scalars, float64 stat vectors, and host values resolve in one
    landing; array shapes survive the packed transfer; no cross-dtype cast
    (counts never round-trip through a float)."""
    win = PipelineWindow(4)
    stats = jnp.asarray([3.0, 1.5e9], dtype=jnp.float64)
    got = []
    win.push(lambda a, b, c: got.append((a, b, c)),
             jnp.int32(1 << 25), stats, 42)
    win.push(lambda v: got.append(v), jnp.int32(2))
    win.flush()
    a, b, c = got[0]
    assert int(a) == 1 << 25              # > 2^24: would corrupt via f32
    assert b.shape == (2,) and np.allclose(np.asarray(b), [3.0, 1.5e9])
    assert c == 42                        # host value passes through
    assert int(got[1]) == 2


def test_batched_resolve_is_one_sync_per_dtype():
    """k same-dtype pending scalars cost ONE attributed host sync (the
    packed-concat read), not k — the whole point of the window."""
    win = PipelineWindow(16)
    for i in range(8):
        win.push(lambda v, i=i: int(v), jnp.int32(i) + jnp.int32(1))
    with SyncCounter() as sc:
        out = win.flush()
    assert out == [i + 1 for i in range(8)]
    assert sc.total <= 2, sc.sites        # packed read (+ slack), not 8


# ---------------------------------------------------------------------------
# Pipelined join stream loop (exec level)
# ---------------------------------------------------------------------------

def _scan(df: pd.DataFrame, batch_rows: int):
    table = pa.Table.from_pandas(df, preserve_index=False)
    schema = dt.Schema([dt.Field(f.name, dt.from_arrow(f.type), f.nullable)
                        for f in table.schema])
    return TpuLocalScanExec(table, schema, batch_rows=batch_rows)


def _collect_rows(exec_node):
    rows = []
    for part in exec_node.execute():
        for batch in part:
            d = batch.to_pydict()
            rows.extend(zip(*[d[n] for n in d.keys()]))
    exec_node.cleanup()
    return rows


def _join_exec(ldf, rdf, how, lkey, rkey, depth, batch_rows=1024,
               stream_filter=None):
    left = _scan(ldf, batch_rows)
    if stream_filter is not None:
        left = TpuFilterExec(left, stream_filter)
    j = TpuSortMergeJoinExec(left, _scan(rdf, 1 << 20), how,
                             [ex.ColumnRef(lkey)], [ex.ColumnRef(rkey)])
    j.pipeline_depth = depth
    return j


@pytest.fixture
def join_frames():
    rng = np.random.default_rng(11)
    n = 8192                              # 8 stream batches at 1024 rows
    left = pd.DataFrame({"k": rng.integers(0, 300, n).astype("int64"),
                         "v": rng.normal(0, 10, n)})
    right = pd.DataFrame({"rk": np.arange(250, dtype="int64"),
                          "w": rng.normal(0, 1, 250)})
    return left, right


@pytest.mark.parametrize("how", ["inner", "left", "left_semi", "left_anti"])
def test_pipelined_join_matches_depth1(join_frames, how):
    """Every join family produces identical rows at depth=1 (blocking,
    today's behavior) and a deep window (pipelined)."""
    left, right = join_frames
    r1 = sorted(_collect_rows(_join_exec(left, right, how, "k", "rk", 1)),
                key=repr)
    r16 = sorted(_collect_rows(_join_exec(left, right, how, "k", "rk", 16)),
                 key=repr)
    assert r1 == r16
    # pandas oracle for the inner case
    if how == "inner":
        exp = left.merge(right, left_on="k", right_on="rk")
        assert len(r16) == len(exp)


def _join_path_syncs(sc: SyncCounter) -> int:
    """Syncs attributed to the join/pipeline machinery (the collection
    helper's own per-batch to_pydict reads are not the join path)."""
    return sum(v for site, v in sc.sites.items()
               if "exec/pipeline.py" in site or "plan/physical.py" in site
               or "ops/joins.py" in site)


def test_pipelined_join_fewer_syncs_than_blocking(join_frames):
    """The pipelined window must collapse the per-batch sizing readbacks:
    8 stream batches at depth 16 resolve in O(1) batched reads vs 8
    blocking reads at depth 1."""
    left, right = join_frames
    j1 = _join_exec(left, right, "inner", "k", "rk", 1)
    with SyncCounter() as sc1:
        n1 = len(_collect_rows(j1))
    j16 = _join_exec(left, right, "inner", "k", "rk", 16)
    with SyncCounter() as sc16:
        n16 = len(_collect_rows(j16))
    assert n1 == n16 > 0
    # depth 1 = one blocking sizing read per stream batch; the window
    # collapses them to O(1) per stage
    assert _join_path_syncs(sc1) >= 8, sc1.sites
    assert _join_path_syncs(sc16) <= 2, sc16.sites


def test_pipelined_join_empty_batch_flow(join_frames):
    """Batches a filter emptied (device-resident zero counts) flow through
    the window without wedging it or emitting phantom rows."""
    left, right = join_frames
    # keep only k < 30: most 1024-row batches still match something, but
    # shrink right so several batches join to nothing
    cond = pr.LessThan(ex.ColumnRef("k"), ex.lit(30))
    j = _join_exec(left, right, "inner", "k", "rk", 16,
                   stream_filter=cond)
    rows = _collect_rows(j)
    exp = left[left.k < 30].merge(right, left_on="k", right_on="rk")
    assert len(rows) == len(exp)
    got_keys = sorted(r[0] for r in rows)
    assert got_keys == sorted(exp.k.tolist())


def test_full_outer_unmatched_tail_through_window(join_frames):
    """Full outer: the unmatched-build tail rides the pipelined path with
    a device-resident count (no per-stage blocking tail readback)."""
    left, right = join_frames
    # right keys 0..249, left keys 0..299: some right rows unmatched too
    lsmall = left[left.k >= 50].reset_index(drop=True)   # right 0..49 unmatched
    j = _join_exec(lsmall, right, "full", "k", "rk", 16)
    rows = _collect_rows(j)
    exp = lsmall.merge(right, left_on="k", right_on="rk", how="outer")
    assert len(rows) == len(exp)
    # unmatched build rows came out with NULL left columns
    null_left = [r for r in rows if r[0] is None]
    assert len(null_left) == 50
    assert sorted(r[2] for r in null_left) == list(range(50))


# ---------------------------------------------------------------------------
# Session-level: q3-shaped multi-join host syncs are O(1) per stage
# ---------------------------------------------------------------------------

def _q3_frames():
    rng = np.random.default_rng(5)
    n = 16384
    line = pd.DataFrame({
        "l_order": rng.integers(0, 2000, n).astype("int64"),
        "l_price": rng.normal(100.0, 10.0, n)})
    orders = pd.DataFrame({
        "o_key": np.arange(2000, dtype="int64"),
        "o_cust": rng.integers(0, 150, 2000).astype("int64"),
        "o_date": rng.integers(0, 1000, 2000).astype("int64")})
    cust = pd.DataFrame({
        "c_key": np.arange(150, dtype="int64"),
        "c_seg": rng.integers(0, 3, 150).astype("int64")})
    return line, orders, cust


def _run_q3(line, orders, cust, batch_rows):
    from spark_rapids_tpu.api.session import TpuSession
    s = TpuSession.builder.config({
        "spark.rapids.tpu.sql.explain": "NONE",
        "spark.rapids.tpu.sql.reader.batchSizeRows": batch_rows,
    }).getOrCreate()
    s.createDataFrame(line).createOrReplaceTempView("q3_lineitem")
    s.createDataFrame(orders).createOrReplaceTempView("q3_orders")
    s.createDataFrame(cust).createOrReplaceTempView("q3_customer")
    df = s.sql(
        "SELECT l_price, o_date, c_seg FROM q3_lineitem "
        "JOIN q3_orders ON l_order = o_key "
        "JOIN q3_customer ON o_cust = c_key "
        "WHERE o_date < 700 AND c_seg = 1")
    rows = df.collect()
    return rows, s.last_query_metrics()["sync"]


def test_q3_shaped_multi_join_host_syncs_o1_per_stage():
    """Acceptance: a q3-shaped 3-way join at multi-batch scale shows
    join-path host syncs ~O(1) per stage in last_query_metrics()['sync'],
    not one blocking readback per stream batch (VERDICT r5: 16 of q3's 51
    syncs were the per-batch join-size readback)."""
    line, orders, cust = _q3_frames()
    rows_one, sync_one = _run_q3(line, orders, cust, 1 << 20)  # 1 batch
    rows_many, sync_many = _run_q3(line, orders, cust, 1024)   # 16 batches
    assert sorted(rows_one, key=repr) == sorted(rows_many, key=repr)
    # pandas oracle
    exp = (line.merge(orders, left_on="l_order", right_on="o_key")
               .merge(cust, left_on="o_cust", right_on="c_key"))
    exp = exp[(exp.o_date < 700) & (exp.c_seg == 1)]
    assert len(rows_many) == len(exp)
    # join-path sizing resolves attribute to the pipeline window; they
    # must stay O(1) per stage at 16x the batch count
    pipeline_syncs = sum(
        v for site, v in sync_many["syncSites"].items()
        if "exec/pipeline.py" in site)
    assert pipeline_syncs <= 4, sync_many["syncSites"]
    # and totals must not scale with the batch count (16x batches; a
    # per-batch readback regression would add ~15+ syncs per stage)
    assert sync_many["hostSyncs"] <= sync_one["hostSyncs"] + 12, \
        (sync_one, sync_many)


# ---------------------------------------------------------------------------
# SpanRecorder: generator-suspended spans close out of order
# ---------------------------------------------------------------------------

def test_span_recorder_out_of_order_close_keeps_attribution():
    """A span held open across a generator yield closes while a younger
    span is still open; its self-time must be its own, and it must not
    steal the younger frame off the stack (the old unconditional pop)."""
    import time
    rec = SpanRecorder()
    with rec:
        def gen():
            with trace_span("g_span"):
                yield
        g = gen()
        next(g)
        with trace_span("outer"):
            time.sleep(0.05)
            next(g, None)         # g_span closes under outer
            time.sleep(0.01)
    rep = rec.report()
    assert rep["g_span"]["count"] == 1
    assert rep["outer"]["count"] == 1
    # old behavior: g_span's close popped OUTER's frame and credited the
    # elapsed time to g_span's own frame, zeroing g_span's self-time
    assert rep["g_span"]["selfS"] >= 0.04
    assert rep["outer"]["selfS"] >= 0.04


def test_span_recorder_add_feeds_report():
    rec = SpanRecorder()
    with rec:
        rec.add("external", 1.25)
        rec.add("external", 0.25)
    rep = rec.report()
    assert rep["external"]["count"] == 2
    assert rep["external"]["selfS"] == pytest.approx(1.5)


# ---------------------------------------------------------------------------
# Semaphore wait-vs-hold split
# ---------------------------------------------------------------------------

def test_semaphore_wait_hold_split_spans_and_stats():
    import time
    from spark_rapids_tpu.exec.device import TpuSemaphore
    sem = TpuSemaphore.initialize(1)
    rec = SpanRecorder()
    try:
        with rec:
            sem.acquire_if_necessary()
            time.sleep(0.02)
            sem.release_if_necessary()
        rep = rec.report()
        assert rep["semaphore_wait"]["count"] == 1
        # the hold brackets the whole task: a reserved scalar beside
        # wallS, no entry of the self-time map
        assert "semaphore_hold" not in rep
        assert rep["semaphoreHoldS"] >= 0.015
        st = sem.stats()
        assert st["acquires"] == 1
        assert st["holdS"] >= 0.015
        assert st["waitS"] >= 0.0
    finally:
        TpuSemaphore.reset()


def test_semaphore_wait_measures_contention():
    import threading
    import time
    from spark_rapids_tpu.exec.device import TpuSemaphore
    sem = TpuSemaphore.initialize(1)
    try:
        sem.acquire_if_necessary()

        def worker():
            sem.acquire_if_necessary()
            sem.release_if_necessary()
        t = threading.Thread(target=worker)
        t.start()
        time.sleep(0.05)              # worker blocks on the held permit
        sem.release_if_necessary()
        t.join()
        st = sem.stats()
        assert st["acquires"] == 2
        assert st["waitS"] >= 0.04    # the worker's blocked time
    finally:
        TpuSemaphore.reset()
