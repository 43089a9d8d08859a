"""``TpuCoalesceBatchesExec`` with goal ``"target"``: the target is a ceiling.

A batch already at the target is handed on as it is; smaller ones are
concatenated in runs that stop BEFORE they would pass the target; goal
``"single"`` still makes one batch; counts that are still on the device cost
one batched readback per chunk of eight; and an aggregate over several full
batches (the shape of TPC-H at SF10 on the chip: eight scan batches, each
the target) answers as it does over one batch, without a concat of its input.
(The reference's ``GpuCoalesceBatches`` passes a batch through when it alone
meets the goal.)"""

import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.analysis.contracts import exec_contract
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exec.metrics import exec_metrics
from spark_rapids_tpu.exec.tracing import SyncCounter
from spark_rapids_tpu.plan import physical as ph

SCHEMA = dt.Schema([dt.Field("i", dt.INT64, False)])


class _Batches(ph.TpuExec):
    """A child that hands on the batches it was given, one partition."""

    CONTRACT = exec_contract(schema="defined", partitioning="source")
    METRICS = exec_metrics()

    def __init__(self, batches):
        super().__init__()
        self.batches = batches

    @property
    def schema(self):
        return SCHEMA

    def execute(self):
        return [iter(self.batches)]


def _stream(sizes, on_device=()):
    """Batches of consecutive integers, ``sizes[j]`` rows each; the counts
    of the positions in ``on_device`` are device scalars, not host ints."""
    batches, start = [], 0
    for j, n in enumerate(sizes):
        b = ColumnarBatch.from_pydict(
            {"i": np.arange(start, start + n, dtype=np.int64)}, SCHEMA)
        if j in on_device:
            b = ColumnarBatch(SCHEMA, b.columns, jnp.int32(n))
        batches.append(b)
        start += n
    return batches


def _coalesce(batches, goal="target", target=1000):
    node = ph.TpuCoalesceBatchesExec(_Batches(batches), goal=goal,
                                     target_rows=target)
    (part,) = node.execute()
    return list(part), node.metrics.resolve()


def _counters(metrics):
    return tuple(metrics.get(k, 0) for k in
                 ("passedBatches", "concatBatches", "concatOutputs"))


def _rows(outs):
    return [v for b in outs for v in b.to_pydict()["i"]]


@pytest.fixture
def concats(monkeypatch):
    """(inputs, output capacity) of every fused concat program called."""
    seen = []
    real = ph._concat_fused

    def recording(schema, batches, out_cap):
        seen.append((len(batches), out_cap))
        return real(schema, batches, out_cap)
    monkeypatch.setattr(ph, "_concat_fused", recording)
    return seen


# sizes of the input batches, goal -> sizes of the outputs, the counters
# (passedBatches, concatBatches, concatOutputs) and the concat programs
# called (a run of one costs none); target 1000 rows
STREAMS = {
    # rule 1: at or over the target, handed on
    "full_batches_pass": ([1000, 1000, 1000], "target",
                          [1000, 1000, 1000], (3, 0, 0), 0),
    "over_the_target_passes_too": ([1000, 2500, 1000], "target",
                                   [1000, 2500, 1000], (3, 0, 0), 0),
    "the_shape_of_sf10": ([1000] * 7 + [153], "target",
                          [1000] * 7 + [153], (7, 1, 1), 0),
    # rule 2: runs stop before they would pass the target
    "runs_stop_before_the_target": ([300] * 5, "target",
                                    [900, 600], (0, 5, 2), 2),
    "a_run_may_reach_the_target": ([400, 600, 1], "target",
                                   [1000, 1], (0, 3, 2), 1),
    "two_that_would_pass_stay_apart": ([600, 600, 600], "target",
                                       [600, 600, 600], (0, 3, 3), 0),
    "many_tiny_batches": ([64] * 40, "target",
                          [960, 960, 640], (0, 40, 3), 3),
    # order within the partition: what waits is flushed before a full batch
    "small_big_small": ([200, 1000, 300], "target",
                        [200, 1000, 300], (1, 2, 2), 0),
    "runs_round_full_batches": ([500, 400, 1000, 1000, 700, 200, 200],
                                "target", [900, 1000, 1000, 900, 200],
                                (2, 5, 3), 2),
    "empty_batches_are_dropped": ([0, 500, 0, 400, 0], "target",
                                  [900], (0, 2, 1), 1),
    "nothing_in_nothing_out": ([0, 0], "target", [], (0, 0, 0), 0),
    # goal "single" is untouched: one batch out
    "single_is_one_batch": ([200, 1000, 300, 1000], "single",
                            [2500], (0, 4, 1), 1),
    "single_of_full_batches": ([1000] * 9, "single", [9000], (0, 9, 1), 1),
    "single_of_one_batch": ([1000], "single", [1000], (0, 1, 1), 0),
}


@pytest.mark.parametrize("name", sorted(STREAMS))
def test_target_is_a_ceiling(name, concats):
    sizes, goal, expected, counters, programs = STREAMS[name]
    batches = _stream(sizes)
    with SyncCounter() as syncs:
        outs, metrics = _coalesce(batches, goal)
    assert [b.num_rows for b in outs] == expected
    assert _rows(outs) == list(range(sum(sizes)))       # row order kept
    assert _counters(metrics) == counters
    assert syncs.total == 0                             # host counts: no read
    assert len(concats) == programs
    if goal == "target":
        # an output is over the target only where one input batch was,
        # and what was at the target came through as the object it was
        full = [b for b in batches if b.num_rows >= 1000]
        assert [b.num_rows for b in outs if b.num_rows > 1000] == \
            [b.num_rows for b in full if b.num_rows > 1000]
        assert sum(any(b is f for f in full) for b in outs) == \
            len(full) == counters[0]
        assert all(cap <= 1024 for _n, cap in concats)


# positions whose counts are on the device -> readbacks (one per chunk of
# eight that holds such a count; a chunk starts at the first one waiting,
# and an empty batch whose count the host knows joins none)
DEVICE_COUNTS = {
    "all_twenty": (range(20), 3),
    "first_eight": (range(8), 1),
    "one_in_the_middle": ([9], 1),
    "one_a_chunk": ([0, 11], 2),
    "none": ([], 0),
}


@pytest.mark.parametrize("goal", ["target", "single"])
@pytest.mark.parametrize("name", sorted(DEVICE_COUNTS))
def test_device_counts_cost_one_readback_a_chunk_of_eight(name, goal):
    on_device, readbacks = DEVICE_COUNTS[name]
    sizes = [300, 1000, 0, 200] * 5
    with SyncCounter() as syncs:
        outs, metrics = _coalesce(_stream(sizes, set(on_device)), goal)
    assert syncs.total == readbacks
    assert _rows(outs) == list(range(sum(sizes)))
    if goal == "single":
        assert [b.num_rows for b in outs] == [sum(sizes)]
    else:
        assert [b.num_rows for b in outs] == [300, 1000] + \
            [500, 1000] * 4 + [200]
        assert _counters(metrics) == (5, 10, 6)


# -- through the planner: aggregates over several full batches -------------------

ROWS = 4096          # reader.batchSizeRows: a scan batch, and so the target


def _session(batch_rows=ROWS):
    from spark_rapids_tpu.api.session import TpuSession
    return TpuSession.builder.config(
        {"spark.rapids.tpu.sql.reader.batchSizeRows": str(batch_rows)}
    ).getOrCreate()


def _frame(n, seed=5):
    rng = np.random.default_rng(seed)
    return pd.DataFrame({"k": rng.integers(0, 7, n),
                         "q": rng.integers(1, 51, n),
                         "v": rng.random(n) * 1e5})


QUERIES = {
    "no_key": "SELECT sum(v * q) AS r, sum(q) AS s, count(*) AS c "
              "FROM t WHERE q < 24",
    "keyed": "SELECT k, sum(v) AS r, sum(q) AS s, count(*) AS c, "
             "min(v) AS lo FROM t GROUP BY k ORDER BY k",
}


def _oracle(pdf, which):
    if which == "no_key":
        sel = pdf[pdf.q < 24]
        return [((sel.v * sel.q).sum(), int(sel.q.sum()), len(sel))]
    g = pdf.groupby("k")
    return [(int(k), r.v.sum(), int(r.q.sum()), len(r), r.v.min())
            for k, r in g]


def _run(session, pdf, sql):
    session.createDataFrame(pdf).createOrReplaceTempView("t")
    rows = [tuple(r) for r in session.sql(sql).collect()]
    return rows, session.last_query_metrics()


def _assert_same(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        for a, b in zip(g, w):
            if isinstance(b, float):
                assert a == pytest.approx(b, rel=1e-14)
            else:
                assert a == b


@pytest.mark.parametrize("batches", [8, 3])
@pytest.mark.parametrize("which", sorted(QUERIES))
def test_aggregate_over_full_batches_equals_one_batch(which, batches,
                                                      concats):
    """``batches`` scan batches, all but the last the target: each is
    reduced where it lies and the partials are merged; integers equal
    the one-batch answer and pandas exactly, float64 sums to 1e-14."""
    pdf = _frame(batches * ROWS - 1000)
    many, m = _run(_session(), pdf, QUERIES[which])
    assert m["scan"]["batches"] == batches
    assert m["coalesce"]["passed"] == batches - 1
    # the last, short batch is a run of one (and the keyed query's ORDER BY
    # has a "single" coalesce of its own over the few groups)
    assert m["coalesce"]["outputs"] <= 2
    update = [k for k in m["programs"] if k.startswith("agg/update/")]
    assert [m["programs"][k]["dispatches"] for k in update] == [batches]
    # no concat of the input: only partials (a row a group) are concatenated
    assert concats and all(cap <= 128 for _n, cap in concats)
    one, m1 = _run(_session(1 << 20), pdf, QUERIES[which])
    assert m1["scan"]["batches"] == 1 and m1["coalesce"]["passed"] == 0
    _assert_same(many, one)
    _assert_same(many, _oracle(pdf, which))


@pytest.mark.parametrize("n,passed,concatenated,outputs", [
    (8 * ROWS - 1000, 7, 1, 1),      # the shape of tpch_sf10.q6
    (8 * ROWS, 8, 0, 0),
    (ROWS - 1000, 0, 1, 1),          # one short batch: the SF1 cells
    (ROWS, 1, 0, 0),
])
def test_last_query_metrics_reports_the_coalesce(n, passed, concatenated,
                                                 outputs):
    _, m = _run(_session(), _frame(n), QUERIES["no_key"])
    assert m["coalesce"] == {"passed": passed, "concatenated": concatenated,
                             "outputs": outputs}
    assert m["sync"]["hostSyncs"] == 0
    (op,) = [o for o in m["operators"]
             if o["operator"] == "TpuCoalesceBatchesExec"]
    assert op["metrics"].get("passedBatches", 0) == passed


def test_join_streams_full_batches_without_a_concat(concats):
    """The stream side of a hash join has goal ``"target"`` too: its full
    batches reach the join as they are, the build side is still one."""
    pdf = _frame(3 * ROWS)
    dim = pd.DataFrame({"k": np.arange(7), "w": np.arange(7) * 10})
    s = _session()
    s.createDataFrame(pdf).createOrReplaceTempView("t")
    s.createDataFrame(dim).createOrReplaceTempView("d")
    got = s.sql("SELECT sum(t.q * d.w) AS r, count(*) AS c "
                "FROM t JOIN d ON t.k = d.k").collect()
    m = s.last_query_metrics()
    assert [tuple(r) for r in got] == [
        (int((pdf.q * pdf.k * 10).sum()), len(pdf))]
    assert m["coalesce"]["passed"] >= 3
    assert all(cap < ROWS for _n, cap in concats)
