"""Sort-merge join kernel tests against pandas merge oracles.

Reference analog: join integration tests + GpuHashJoin tag/remap behavior
(SURVEY.md §2.4, §4).
"""

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.columnar import dtypes as dt
import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.column import Column, bucket
from spark_rapids_tpu.ops import kernels as K
from spark_rapids_tpu.ops.joins import (_key_words, _merge_bounds,
                                        _probe_sorted, cross_join_gather,
                                        join_gather, join_match,
                                        unmatched_build_gather)


def _col(vals, dtype):
    return Column.from_pylist(vals, dtype)


def _join(build_keys, build_cols, n_build, stream_keys, stream_cols, n_stream,
          how="inner"):
    m = join_match(build_keys, n_build, stream_keys, n_stream,
                   stream_keys[0].capacity)
    total = int(m.total_pairs)
    if how == "left":
        total = int(np.sum(np.maximum(np.asarray(m.count)[:n_stream], 1)))
    cap = bucket(max(total, 1))
    s_out, b_out, cnt = join_gather(m, stream_cols, build_cols, cap, how,
                                    n_stream=n_stream)
    n = int(cnt)
    return ([c.to_pylist(n) for c in s_out], [c.to_pylist(n) for c in b_out], m)


def _rows(*cols):
    return sorted(zip(*cols), key=lambda r: tuple(
        (x is None, x if x is not None else 0) for x in r))


def test_inner_join_basic():
    bk = _col([1, 2, 2, 3], dt.INT64)
    bv = _col(["b1", "b2a", "b2b", "b3"], dt.STRING)
    sk = _col([2, 1, 4, 2], dt.INT64)
    sv = _col([100, 200, 300, 400], dt.INT64)
    s_out, b_out, m = _join([bk], [bv], 4, [sk], [sk, sv], 4, "inner")
    got = _rows(s_out[1], b_out[0])
    assert got == _rows([100, 100, 200, 400, 400], ["b2a", "b2b", "b1", "b2a", "b2b"])


def test_null_keys_never_match():
    bk = _col([1, None], dt.INT64)
    bv = _col([10, 20], dt.INT64)
    sk = _col([1, None], dt.INT64)
    sv = _col([100, 200], dt.INT64)
    s_out, b_out, _ = _join([bk], [bv], 2, [sk], [sv], 2, "inner")
    assert s_out[0] == [100]
    assert b_out[0] == [10]


def test_left_join():
    bk = _col([1, 2], dt.INT64)
    bv = _col([10, 20], dt.INT64)
    sk = _col([2, 5, None], dt.INT64)
    sv = _col([100, 200, 300], dt.INT64)
    s_out, b_out, _ = _join([bk], [bv], 2, [sk], [sk, sv], 3, "left")
    got = _rows(s_out[1], b_out[0])
    assert got == _rows([100, 200, 300], [20, None, None])


def test_semi_anti_join():
    bk = _col([1, 2, 2], dt.INT64)
    sk = _col([2, 3, None, 1], dt.INT64)
    sv = _col([100, 200, 300, 400], dt.INT64)
    m = join_match([bk], 3, [sk], 4, sk.capacity)
    s_out, _, cnt = join_gather(m, [sv], [], 128, "left_semi", n_stream=4)
    assert sorted(s_out[0].to_pylist(int(cnt))) == [100, 400]
    s_out, _, cnt = join_gather(m, [sv], [], 128, "left_anti", n_stream=4)
    assert sorted(s_out[0].to_pylist(int(cnt))) == [200, 300]


def test_full_outer_pieces():
    bk = _col([1, 9, None], dt.INT64)
    bv = _col([10, 90, 99], dt.INT64)
    sk = _col([1, 5], dt.INT64)
    m = join_match([bk], 3, [sk], 2, sk.capacity)
    un, cnt = unmatched_build_gather(m, [bv], 3)
    # build rows 9 and NULL-key row are unmatched
    assert sorted(un[0].to_pylist(int(cnt))) == [90, 99]


def test_string_key_join():
    bk = _col(["apple", "pear", None], dt.STRING)
    bv = _col([1, 2, 3], dt.INT64)
    sk = _col(["pear", "apple", "kiwi", None], dt.STRING)
    sv = _col([10, 20, 30, 40], dt.INT64)
    s_out, b_out, _ = _join([bk], [bv], 3, [sk], [sv], 4, "inner")
    got = _rows(s_out[0], b_out[0])
    assert got == _rows([10, 20], [2, 1])


def test_multi_key_join():
    bk1 = _col([1, 1, 2], dt.INT64)
    bk2 = _col(["x", "y", "x"], dt.STRING)
    bv = _col([11, 12, 21], dt.INT64)
    sk1 = _col([1, 2, 1], dt.INT64)
    sk2 = _col(["y", "x", "z"], dt.STRING)
    sv = _col([100, 200, 300], dt.INT64)
    s_out, b_out, _ = _join([bk1, bk2], [bv], 3, [sk1, sk2], [sv], 3, "inner")
    got = _rows(s_out[0], b_out[0])
    assert got == _rows([100, 200], [12, 21])


def test_float_key_join_nan_matches_nan():
    nan = float("nan")
    bk = _col([1.0, nan], dt.FLOAT64)
    bv = _col([1, 2], dt.INT64)
    sk = _col([nan, 1.0, 2.0], dt.FLOAT64)
    sv = _col([10, 20, 30], dt.INT64)
    s_out, b_out, _ = _join([bk], [bv], 2, [sk], [sv], 3, "inner")
    got = _rows(s_out[0], b_out[0])
    # Spark: NaN == NaN in joins
    assert got == _rows([10, 20], [2, 1])


def test_cross_join():
    lk = _col([1, 2], dt.INT64)
    rk = _col([10, 20, 30], dt.INT64)
    l_out, r_out, cnt = cross_join_gather([lk], 2, [rk], 3, 128)
    n = int(cnt)
    assert n == 6
    pairs = sorted(zip(l_out[0].to_pylist(n), r_out[0].to_pylist(n)))
    assert pairs == [(1, 10), (1, 20), (1, 30), (2, 10), (2, 20), (2, 30)]


def test_join_random_vs_pandas():
    rng = np.random.default_rng(7)
    n_b, n_s = 200, 300
    bk = rng.integers(0, 60, n_b)
    bv = rng.integers(0, 1000, n_b)
    sk = rng.integers(0, 80, n_s)
    sv = rng.integers(0, 1000, n_s)
    bkc, bvc = _col(list(bk), dt.INT64), _col(list(bv), dt.INT64)
    skc, svc = _col(list(sk), dt.INT64), _col(list(sv), dt.INT64)

    for how in ("inner", "left"):
        s_out, b_out, _ = _join([bkc], [bvc], n_b, [skc], [skc, svc], n_s, how)
        got = _rows(s_out[0], s_out[1], b_out[0])
        df_b = pd.DataFrame({"k": bk, "bv": bv})
        df_s = pd.DataFrame({"k": sk, "sv": sv})
        merged = df_s.merge(df_b, on="k", how=how)
        exp = _rows(list(merged["k"]), list(merged["sv"]),
                    [None if pd.isna(x) else int(x) for x in merged["bv"]])
        assert got == exp


def test_null_build_keys_all_types():
    """NULL build keys must never match (they sort first with zeroed data
    words — the search must rank them below every usable probe key)."""
    bk = _col([None, -5, 0, 3], dt.INT64)
    bv = _col([100, 200, 300, 400], dt.INT64)
    sk = _col([0, -5], dt.INT64)
    sv = _col([10, 20], dt.INT64)
    s_out, b_out, m = _join([bk], [bv], 4, [sk], [sv], 2, "inner")
    got = _rows(s_out[0], b_out[0])
    assert got == _rows([10, 20], [300, 200])

    # semi/anti against build side containing NULL keys
    s_out, _, _ = _join([bk], [bv], 4, [sk], [sv], 2, "left_semi")
    assert sorted(s_out[0]) == [10, 20]
    sk2 = _col([7, -5, None], dt.INT64)
    sv2 = _col([1, 2, 3], dt.INT64)
    s_out, _, _ = _join([bk], [bv], 4, [sk2], [sv2], 3, "left_anti")
    assert sorted(s_out[0]) == [1, 3]


def test_null_build_keys_left_and_unmatched():
    bk = _col([None, 2], dt.INT64)
    bv = _col([111, 222], dt.INT64)
    sk = _col([2, 9], dt.INT64)
    sv = _col([10, 20], dt.INT64)
    s_out, b_out, m = _join([bk], [bv], 2, [sk], [sv], 2, "left")
    got = _rows(s_out[0], b_out[0])
    assert got == _rows([10, 20], [222, None])
    # full-outer composition: the NULL-key build row is unmatched
    un_cols, ucnt = unmatched_build_gather(m, [bv], 2)
    assert un_cols[0].to_pylist(int(ucnt)) == [111]


def test_float64_keys_full_precision():
    """f64 keys differing only beyond f32 precision must not join."""
    a = 1.0
    b = 1.0 + 2.0 ** -40          # == a when rounded to f32
    bk = _col([a, b], dt.FLOAT64)
    bv = _col([1, 2], dt.INT64)
    sk = _col([a], dt.FLOAT64)
    sv = _col([10], dt.INT64)
    s_out, b_out, _ = _join([bk], [bv], 2, [sk], [sv], 1, "inner")
    assert b_out[0] == [1]


def test_negative_zero_joins_positive_zero():
    bk = _col([-0.0, 5.0], dt.FLOAT64)
    bv = _col([1, 2], dt.INT64)
    sk = _col([0.0], dt.FLOAT64)
    sv = _col([10], dt.INT64)
    s_out, b_out, _ = _join([bk], [bv], 2, [sk], [sv], 1, "inner")
    assert b_out[0] == [1]


def test_string_keys_different_widths():
    """Build/stream string key columns with different padded byte widths."""
    bk = _col(["apple", "fig"], dt.STRING)
    bv = _col([1, 2], dt.INT64)
    sk = _col(["a-much-longer-string-key-here", "apple", "fig"], dt.STRING)
    sv = _col([10, 20, 30], dt.INT64)
    assert bk.data.shape[1] != sk.data.shape[1]
    s_out, b_out, _ = _join([bk], [bv], 2, [sk], [sv], 3, "inner")
    got = _rows(s_out[0], b_out[0])
    assert got == _rows([20, 30], [1, 2])


# ---------------------------------------------------------------------------
# The probe's rank itself: [lo, hi) against numpy.searchsorted
# ---------------------------------------------------------------------------

_NAN = float("nan")
_RNG = np.random.default_rng(30)
_DUP_B = [int(x) for x in _RNG.integers(0, 6, 100)]
_DUP_S = [int(x) for x in _RNG.integers(-1, 8, 120)]

# name -> (key dtypes, build rows, stream rows, n_build); a row is one value
# per key column. n_build None: every build row is live; "device": that
# count as a device scalar, as the pipelined exec layer passes it
_RANK_CASES = {
    "int64": ([dt.INT64], [5, -3, 9, 5, 0, 2 ** 40],
              [5, 0, 7, -3, 2 ** 40, -(2 ** 40)], None),
    "int32": ([dt.INT32], [5, -3, 9, 5, 0], [5, 0, 7, -3, -9], None),
    "date": ([dt.DATE], [9000, 8000, 9000, -1], [9000, -1, 8500, 8000], None),
    "float64_nan": ([dt.FLOAT64], [1.5, _NAN, -2.0, _NAN, 1e300],
                    [_NAN, 1.5, 2.0, -1e300, 1e300], None),
    "float64_negative_zero": ([dt.FLOAT64], [0.0, -0.0, 1.0, -0.0, -1.0],
                              [-0.0, 0.0, 1.0, -1.0, 0.5], None),
    "string": ([dt.STRING], ["pear", "apple", "", "pear", "a-long-key-12"],
               ["pear", "", "kiwi", "a-long-key-12", "a-long-key"], None),
    "two_columns": ([dt.INT64, dt.STRING],
                    [(1, "x"), (1, "y"), (2, "x"), (1, "x"), (0, "z")],
                    [(1, "y"), (2, "x"), (1, "z"), (1, "x"), (2, "y")], None),
    "nulls_in_build": ([dt.INT64], [None, 4, None, 0, 4], [4, 0, 1, -7],
                       None),
    "nulls_in_stream": ([dt.INT64], [4, 0, 4, 8], [None, 4, None, 0, 9],
                        None),
    "nulls_two_columns": ([dt.INT64, dt.STRING],
                          [(1, None), (None, "x"), (1, "x")],
                          [(1, "x"), (1, None), (None, "x")], None),
    "heavy_duplicates": ([dt.INT64], _DUP_B, _DUP_S, None),
    "n_build_0": ([dt.INT64], [], [3, 0, -1], None),
    "n_build_1": ([dt.INT64], [3], [3, 0, 4], None),
    "n_build_cap": ([dt.INT64], [int(x) for x in _RNG.integers(0, 40, 128)],
                    [int(x) for x in _RNG.integers(-2, 42, 128)], None),
    "n_build_below_rows": ([dt.INT64], [7, 7, 1, 9, 7, 3], [7, 1, 9, 3], 4),
    "n_stream_0": ([dt.INT64], [3, 1, 3], [], None),
    "n_build_device_scalar": ([dt.INT64], [3, 1, 3, 8], [3, 8, 2, 1],
                              "device"),
}


def _key_cols(dtypes, rows, cap):
    if len(dtypes) == 1:
        rows = [(r,) for r in rows]
    # one byte width for both sides, as join_match's widening leaves them
    return [Column.from_pylist([r[i] for r in rows], t, capacity=cap,
                               width=16 if t == dt.STRING else None)
            for i, t in enumerate(dtypes)]


def _encoded(cols):
    """Each row's encoded key words as one python tuple."""
    arrs = [np.asarray(w) for w, _bits in _key_words(cols)[0]]
    return list(zip(*(a.tolist() for a in arrs)))


@pytest.mark.parametrize("case", sorted(_RANK_CASES))
def test_probe_rank_equals_searchsorted(case):
    dtypes, b_rows, s_rows, n_build = _RANK_CASES[case]
    as_device = n_build == "device"
    if n_build is None or as_device:
        n_build = len(b_rows)
    n_stream = len(s_rows)
    cap_b, cap_s = bucket(len(b_rows)), bucket(n_stream)
    build = _key_cols(dtypes, b_rows, cap_b)
    stream = _key_cols(dtypes, s_rows, cap_s)
    nb = jnp.asarray(n_build, jnp.int32) if as_device else n_build

    order = K.sort_indices([K.SortKey(c) for c in build], nb, cap_b)
    sorted_build = [K.gather_column(c, order) for c in build]
    s_words, s_usable = _key_words(stream)
    lo, hi = _merge_bounds(_key_words(sorted_build)[0], nb, s_words)

    # the reference: numpy.searchsorted over the dense ranks of the
    # encoded keys (python tuples order as the words do; -0.0 == 0.0)
    b_keys = _encoded(sorted_build)[:n_build]
    s_keys = _encoded(stream)
    rank = {k: i for i, k in enumerate(sorted(set(b_keys) | set(s_keys)))}
    b_rank = np.array([rank[k] for k in b_keys], dtype=np.int64)
    s_rank = np.array([rank[k] for k in s_keys], dtype=np.int64)
    assert (np.diff(b_rank) >= 0).all()       # the build sort's own order
    assert np.array_equal(np.asarray(lo),
                          np.searchsorted(b_rank, s_rank, "left"))
    assert np.array_equal(np.asarray(hi),
                          np.searchsorted(b_rank, s_rank, "right"))

    # and what join_match makes of it: NULL and dead stream rows match
    # nothing, the others their [lo, hi)
    m = join_match(build, nb, stream, n_stream, cap_s)
    usable = np.asarray(s_usable) & (np.arange(cap_s) < n_stream)
    want = np.where(usable, np.asarray(hi) - np.asarray(lo), 0)
    assert np.array_equal(np.asarray(m.count), want)
    assert np.array_equal(np.asarray(m.lo)[usable], np.asarray(lo)[usable])
    assert int(m.total_pairs) == int(want.sum())


def _primitives(jaxpr):
    """Names of every primitive in a jaxpr, sub-jaxprs included."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names.extend(_primitives(sub))
    return names


def test_probe_holds_no_loop_of_gathers():
    """The probe reads each key once: no ``while`` whose body gathers, and
    the build's row count is an operand (two counts, one trace)."""
    cap = 128
    traces = []

    def probe(n_build, bk, bv, sk, sv):
        traces.append(n_build)
        m = _probe_sorted([Column(dt.INT64, bk, bv)],
                          jnp.arange(cap, dtype=jnp.int32), n_build, cap,
                          [Column(dt.INT64, sk, sv)], cap, cap)
        return m.lo, m.count

    keys = jnp.arange(cap, dtype=jnp.int64) // 2      # sorted, in pairs
    valid = jnp.ones(cap, jnp.bool_)
    jaxpr = jax.make_jaxpr(probe)(jnp.int32(cap), keys, valid, keys, valid)
    assert "sort" in _primitives(jaxpr.jaxpr)
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            if eqn.primitive.name in ("while", "scan"):
                assert "gather" not in _primitives(sub), eqn
    del traces[:]
    jitted = jax.jit(lambda *args: probe(*args))
    lo_a, count_a = jitted(jnp.int32(cap), keys, valid, keys, valid)
    lo_b, count_b = jitted(jnp.int32(10), keys, valid, keys, valid)
    assert len(traces) == 1
    assert np.asarray(count_a).tolist() == [2] * cap
    assert np.asarray(count_b).tolist() == [2] * 10 + [0] * (cap - 10)
    assert np.asarray(lo_a).tolist() == [i - i % 2 for i in range(cap)]


def test_runtime_broadcast_switch():
    """AQE join-strategy switch: a shuffled join whose build side turns
    out SMALL at runtime joins via a materialized broadcast batch and
    skips the stream-side shuffle (runtimeBroadcastJoins metric set);
    a large build side stays co-partitioned."""
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.functions import col
    from spark_rapids_tpu.plan.physical import TpuShuffledJoinExec

    def find(node, klass):
        out = [node] if isinstance(node, klass) else []
        for c in node.children:
            out.extend(find(c, klass))
        return out

    s = TpuSession.builder.config({
        # estimates below force the SHUFFLED plan; runtime sizes overrule
        "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": "1",
        "spark.rapids.tpu.sql.adaptive.enabled": "true",
        "spark.rapids.tpu.sql.explain": "NONE",
    }).getOrCreate()
    big = s.createDataFrame({"k": [i % 50 for i in range(2000)],
                             "v": [float(i) for i in range(2000)]})
    small = s.createDataFrame({"k": list(range(50)),
                               "w": [k * 2.0 for k in range(50)]})
    out = (big.join(small, on="k", how="inner")
           .groupBy("k").agg(F.sum(col("v") + col("w")).alias("s"))
           .collect())
    assert len(out) == 50
    joins = find(s.last_plan(), TpuShuffledJoinExec)
    assert joins, s.last_plan()
    j = joins[0]
    assert j.aqe_broadcast_threshold == 1
    # build side is tiny but > 1 byte, so threshold=1 keeps co-partition;
    # re-run with a generous runtime threshold to see the switch
    s2 = TpuSession.builder.config({
        "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": "1",
        "spark.rapids.tpu.sql.adaptive.enabled": "true",
        "spark.rapids.tpu.sql.explain": "NONE",
    }).getOrCreate()
    big2 = s2.createDataFrame({"k": [i % 50 for i in range(2000)],
                               "v": [float(i) for i in range(2000)]})
    small2 = s2.createDataFrame({"k": list(range(50)),
                                 "w": [k * 2.0 for k in range(50)]})
    df2 = big2.join(small2, on="k", how="inner") \
        .groupBy("k").agg(F.sum(col("v") + col("w")).alias("s"))
    exec_plan = df2._execute()
    joins = find(exec_plan, TpuShuffledJoinExec)
    assert joins
    joins[0].aqe_broadcast_threshold = 10 << 20   # runtime: plenty
    batch = exec_plan.execute_collect()
    rows = sorted(batch.rows())
    assert len(rows) == 50
    joins[0].metrics.resolve()
    assert joins[0].metrics.get("runtimeBroadcastJoins", 0) == 1, \
        dict(joins[0].metrics)
    # oracle spot check: k=0 -> sum over 40 rows of v + w
    exp0 = sum(float(i) for i in range(0, 2000, 50)) + 40 * 0.0
    assert abs(dict(rows)[0] - exp0) < 1e-6


def test_skew_join_split():
    """AQE skew split: a hot stream partition (one dominant key) larger
    than the skew threshold executes as >=2 mapper-subset tasks joined
    against the SAME shared build partition — results identical to the
    unsplit plan (OptimizeSkewedJoin + partial-mapper partition specs)."""
    import pandas as pd
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.functions import col
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.plan.physical import TpuShuffledJoinExec
    from spark_rapids_tpu.shuffle.exchange import TpuShuffleExchangeExec

    def find(node, klass):
        out = [node] if isinstance(node, klass) else []
        for c in node.children:
            out.extend(find(c, klass))
        return out

    # 90% of rows share one key -> one hot reduce partition
    ks = [7] * 1800 + [i % 40 for i in range(200)]
    vs = [float(i % 13) for i in range(2000)]
    conf = {
        "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": "-1",
        "spark.rapids.tpu.sql.adaptive.enabled": "true",
        "spark.rapids.tpu.sql.adaptive.skewJoin.skewedPartitionThreshold":
            "4096",
        "spark.rapids.tpu.sql.shuffle.partitions": "4",
        "spark.rapids.tpu.sql.explain": "NONE",
    }
    s = TpuSession.builder.config(dict(conf)).getOrCreate()
    big = s.createDataFrame({"k": ks, "v": vs})
    dim = s.createDataFrame({"k": list(range(41)),
                             "w": [k * 10.0 for k in range(41)]})
    rows = sorted(big.join(dim, on="k", how="inner")
                  .select(col("k"), (col("v") + col("w")).alias("x"))
                  .collect())
    joins = find(s.last_plan(), TpuShuffledJoinExec)
    assert joins and joins[0].aqe_skew_threshold == 4096
    m = joins[0].metrics.resolve()
    assert m.get("skewJoinSplits", 0) >= 1, m
    ex_metrics = [e.metrics.resolve()
                  for e in find(s.last_plan(), TpuShuffleExchangeExec)]
    assert any(em.get("skewSplitTasks", 0) >= 2 for em in ex_metrics), \
        ex_metrics
    # oracle: same join without skew splitting
    pb = pd.DataFrame({"k": ks, "v": vs})
    pdim = pd.DataFrame({"k": list(range(41)),
                         "w": [k * 10.0 for k in range(41)]})
    j = pb.merge(pdim, on="k")
    exp = sorted((int(r.k), float(r.v + r.w))
                 for r in j.itertuples(index=False))
    assert rows == exp
