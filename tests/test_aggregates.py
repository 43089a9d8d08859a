"""Group-by / reduction kernel tests against pandas-style oracles.

Reference analog: HashAggregatesSuite (SURVEY.md §4 ring 1).
"""

import math

import numpy as np
import pytest

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.column import Column
from spark_rapids_tpu.ops.aggregates import (AggSpec, groupby_aggregate,
                                             reduce_aggregate)


def _col(vals, dtype):
    return Column.from_pylist(vals, dtype)


def _run_groupby(keys, specs, n):
    cap = keys[0].capacity
    out_keys, out_aggs, n_groups = groupby_aggregate(keys, specs, n, cap)
    g = int(n_groups)
    return ([k.to_pylist(g) for k in out_keys],
            [a.to_pylist(g) for a in out_aggs])


def test_groupby_sum_count():
    k = _col([1, 2, 1, 2, 1, None], dt.INT64)
    v = _col([10, 20, 30, None, 50, 60], dt.INT64)
    keys, aggs = _run_groupby(
        [k], [AggSpec("sum", v), AggSpec("count", v), AggSpec("count_star", None)], 6)
    # groups sorted: NULL first, then 1, 2
    assert keys[0] == [None, 1, 2]
    assert aggs[0] == [60, 90, 20]
    assert aggs[1] == [1, 3, 1]
    assert aggs[2] == [1, 3, 2]


def test_groupby_min_max_avg():
    k = _col(["a", "b", "a", "b"], dt.STRING)
    v = _col([3.0, None, 1.0, 7.5], dt.FLOAT64)
    keys, aggs = _run_groupby(
        [k], [AggSpec("min", v), AggSpec("max", v), AggSpec("avg", v)], 4)
    assert keys[0] == ["a", "b"]
    assert aggs[0] == [1.0, 7.5]
    assert aggs[1] == [3.0, 7.5]
    assert aggs[2] == [2.0, 7.5]


def test_groupby_all_null_group():
    k = _col([1, 1, 2], dt.INT32)
    v = _col([None, None, 5], dt.INT64)
    keys, aggs = _run_groupby(
        [k], [AggSpec("sum", v), AggSpec("count", v), AggSpec("min", v)], 3)
    assert keys[0] == [1, 2]
    assert aggs[0] == [None, 5]
    assert aggs[1] == [0, 1]
    assert aggs[2] == [None, 5]


def test_groupby_string_minmax():
    k = _col([1, 1, 1], dt.INT32)
    v = _col(["pear", "apple", None], dt.STRING)
    keys, aggs = _run_groupby([k], [AggSpec("min", v), AggSpec("max", v)], 3)
    assert aggs[0] == ["apple"]
    assert aggs[1] == ["pear"]


def test_groupby_float_nan():
    nan = float("nan")
    k = _col([1, 1, 2, 2], dt.INT32)
    v = _col([nan, 2.0, 3.0, 4.0], dt.FLOAT64)
    keys, aggs = _run_groupby([k], [AggSpec("min", v), AggSpec("max", v)], 4)
    assert aggs[0][0] == 2.0          # min skips NaN (NaN is largest)
    assert math.isnan(aggs[1][0])     # max of group with NaN = NaN
    assert aggs[0][1] == 3.0 and aggs[1][1] == 4.0


def test_groupby_first_last():
    k = _col([1, 1, 1, 2], dt.INT32)
    v = _col([None, 20, 30, 40], dt.INT64)
    keys, aggs = _run_groupby(
        [k], [AggSpec("first", v, ignore_nulls=True),
              AggSpec("first", v, ignore_nulls=False),
              AggSpec("last", v)], 4)
    assert aggs[0] == [20, 40]
    assert aggs[1] == [None, 40]
    assert aggs[2] == [30, 40]


def test_groupby_multi_key():
    k1 = _col([1, 1, 2, 1], dt.INT32)
    k2 = _col(["x", "y", "x", "x"], dt.STRING)
    v = _col([1, 2, 3, 4], dt.INT64)
    keys, aggs = _run_groupby([k1, k2], [AggSpec("sum", v)], 4)
    assert keys[0] == [1, 1, 2]
    assert keys[1] == ["x", "y", "x"]
    assert aggs[0] == [5, 2, 3]


def test_groupby_bool_minmax():
    k = _col([1, 1, 2], dt.INT32)
    v = _col([True, False, True], dt.BOOL)
    keys, aggs = _run_groupby([k], [AggSpec("min", v), AggSpec("max", v)], 3)
    assert aggs[0] == [False, True]
    assert aggs[1] == [True, True]


def test_reduce_no_groups():
    v = _col([1, 2, None, 4], dt.INT64)
    out = reduce_aggregate(
        [AggSpec("sum", v), AggSpec("count", v), AggSpec("avg", v),
         AggSpec("min", v), AggSpec("max", v)], 4, v.capacity)
    assert [c.to_pylist(1)[0] for c in out] == [7, 3, 7 / 3, 1, 4]


def test_reduce_empty_input():
    v = Column.full_null(dt.INT64, 128)
    out = reduce_aggregate(
        [AggSpec("sum", v), AggSpec("count", v), AggSpec("count_star", None)],
        0, 128)
    assert out[0].to_pylist(1) == [None]
    assert out[1].to_pylist(1) == [0]
    assert out[2].to_pylist(1) == [0]


def test_groupby_large_random_vs_pandas():
    import pandas as pd
    rng = np.random.default_rng(42)
    n = 1000
    k = rng.integers(0, 50, n)
    v = rng.normal(size=n)
    null_mask = rng.random(n) < 0.1
    kcol = _col(list(k), dt.INT64)
    vcol = Column.from_pylist(
        [None if m else float(x) for m, x in zip(null_mask, v)], dt.FLOAT64)
    keys, aggs = _run_groupby(
        [kcol], [AggSpec("sum", vcol), AggSpec("count", vcol),
                 AggSpec("min", vcol), AggSpec("max", vcol)], n)
    df = pd.DataFrame({"k": k, "v": [None if m else x for m, x in zip(null_mask, v)]})
    g = df.groupby("k")["v"]
    expected = g.agg(["sum", "count", "min", "max"]).reset_index()
    assert keys[0] == list(expected["k"])
    # float sum order differs from pandas (the reference gates this behind
    # spark.rapids.sql.variableFloatAgg.enabled) — epsilon compare
    np.testing.assert_allclose(aggs[0], expected["sum"], rtol=1e-9)
    assert aggs[1] == list(expected["count"])
    np.testing.assert_allclose(aggs[2], expected["min"])
    np.testing.assert_allclose(aggs[3], expected["max"])


# ---------------------------------------------------------------------------
# One group-by: what the float32 MXU paths' tests guarded (PR 29), asked of
# ``groupby_aggregate`` and of the operator's two ways into it
# ---------------------------------------------------------------------------

_BIG = 3_000_000_000_000_000_000
_NAN, _INF = float("nan"), float("inf")

# (id, key dtype, keys, value dtype, values, ops, live rows or None,
#  expected keys, expected aggregates)
_EDGE_CASES = [
    ("negative_keys_and_null_group", dt.INT32, [-3, -1, None, -3],
     dt.FLOAT64, [1.0, 2.0, 3.0, 4.0], ["sum"], None,
     [None, -3, -1], [[3.0, 5.0, 2.0]]),
    ("all_null_keys", dt.INT64, [None, None],
     dt.FLOAT64, [1.0, 2.0], ["sum"], None, [None], [[3.0]]),
    ("empty_input", dt.INT64, [], dt.FLOAT64, [], ["sum"], None, [], [[]]),
    # 2 * _BIG overflows int64 and wraps exactly like Spark's bigint
    ("int64_sum_wraps_bit_exact", dt.INT64, [5, 5, 6, 6],
     dt.INT64, [_BIG, _BIG, -_BIG, 17], ["sum"], None,
     [5, 6], [[int(np.int64(np.uint64(_BIG * 2 % (1 << 64)))), -_BIG + 17]]),
    ("live_mask_folds_a_filter", dt.INT64, [1, 2, 1, 2],
     dt.FLOAT64, [10.0, 20.0, 30.0, 40.0], ["sum"],
     [True, False, True, False], [1], [[40.0]]),
    # beyond float32's range: nothing here rides a float32 pair
    ("1e40_and_inf_sum_exactly", dt.INT64, [1, 1, 2, 2],
     dt.FLOAT64, [1e40, 3.0, _INF, 5.0], ["sum"], None,
     [1, 2], [[1e40 + 3.0, _INF]]),
    ("nan_poisons_only_its_group", dt.INT64, [1, 1, 2, 2],
     dt.FLOAT64, [_NAN, 2.0, 3.0, 4.0], ["sum", "avg"], None,
     [1, 2], [[_NAN, 7.0], [_NAN, 3.5]]),
]


@pytest.mark.parametrize(
    "kt, kv, vt, vv, ops, live, want_keys, want_aggs",
    [c[1:] for c in _EDGE_CASES], ids=[c[0] for c in _EDGE_CASES])
def test_groupby_edge_cases(kt, kv, vt, vv, ops, live, want_keys, want_aggs):
    import jax.numpy as jnp
    k, v = _col(kv, kt), _col(vv, vt)
    mask = None if live is None else jnp.asarray(
        live + [False] * (k.capacity - len(live)))
    out_keys, out_aggs, ng = groupby_aggregate(
        [k], [AggSpec(op, v) for op in ops], len(kv), k.capacity,
        live_mask=mask)
    g = int(ng)
    assert out_keys[0].to_pylist(g) == want_keys
    for agg, want in zip(out_aggs, want_aggs):
        got = agg.to_pylist(g)
        assert len(got) == len(want)
        for a, b in zip(got, want):
            assert (math.isnan(a) and math.isnan(b)) or a == b, (got, want)


def test_groupby_small_span_int_key_vs_pandas():
    """A small-span int key with negative keys and a NULL-key group, every
    kind of aggregate: sums of doubles to float64's rounding, not to a
    float32 pair's."""
    import pandas as pd
    rng = np.random.default_rng(11)
    n = 500
    kv = [None if rng.random() < 0.08 else int(x)
          for x in rng.integers(-40, 40, n)]
    vv = [None if rng.random() < 0.1 else float(x)
          for x in rng.normal(0, 10, n)]
    iv = [None if x is None else x * 7 for x in kv]
    k, v, i = _col(kv, dt.INT64), _col(vv, dt.FLOAT64), _col(iv, dt.INT64)
    ops = [("sum", v), ("count", v), ("avg", v), ("min", v), ("max", v),
           ("count_star", None), ("sum", i), ("first", v), ("last", v)]
    keys, aggs = _run_groupby([k], [AggSpec(op, c) for op, c in ops], n)
    df = pd.DataFrame({"k": pd.array(kv, dtype="Int64"),
                       "v": pd.array(vv, dtype="Float64"),
                       "i": pd.array(iv, dtype="Int64")})
    g = df.groupby("k", dropna=False, sort=True)
    want = pd.DataFrame({
        0: g.v.sum(min_count=1), 1: g.v.count(), 2: g.v.mean(),
        3: g.v.min(), 4: g.v.max(), 5: g.size(),
        6: g.i.sum(min_count=1), 7: g.v.first(), 8: g.v.last()})
    # pandas sorts the NULL key last, the engine (Spark's order) first
    want = pd.concat([want[want.index.isna()], want[~want.index.isna()]])
    assert keys[0] == [None if pd.isna(x) else int(x) for x in want.index]
    for c, got in enumerate(aggs):
        for a, b in zip(got, want[c]):
            if a is None or pd.isna(b):
                assert a is None and pd.isna(b), (ops[c][0], a, b)
            elif isinstance(a, float):
                assert a == pytest.approx(float(b), rel=1e-13), (ops[c][0],)
            else:
                assert a == int(b), (ops[c][0], a, b)


def test_session_with_the_retired_matmul_key_answers_exactly(monkeypatch):
    """``spark.rapids.tpu.sql.agg.matmul.enabled`` is no conf any more
    (PR 29), yet the benchmark's configurations still set it, in the
    session and in the environment: an unknown key is inert. ``true`` used
    to select float32 sums; the answer is exact and every aggregate
    program of the query is of the ``sort`` or ``final`` family."""
    import pandas as pd
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.api import functions as F
    key = "spark.rapids.tpu.sql.agg.matmul.enabled"
    monkeypatch.setenv("SPARK_RAPIDS_TPU_CONF__" +
                       key.upper().replace(".", "__"), "true")
    rng = np.random.default_rng(41)
    n = 5000
    df = pd.DataFrame({
        "k": [f"g{int(x)}" for x in rng.integers(0, 23, n)],  # string keys
        "v": rng.normal(0, 10, n) * 1e6,
        "q": rng.integers(0, 50, n)})
    s = TpuSession.builder.config({
        "spark.rapids.tpu.sql.explain": "NONE", key: "true"}).getOrCreate()
    got = {r[0]: r[1:] for r in
           (s.createDataFrame(df).filter(F.col("v") > -5e6)
            .groupBy("k").agg(F.sum("v").alias("sv"),
                              F.count("*").alias("n"),
                              F.avg("v").alias("av"),
                              F.sum("q").alias("sq"),
                              F.min("v").alias("mv")).collect())}
    agg_programs = [p for p in s.last_query_metrics()["programs"]
                    if p.startswith("agg/")]
    assert agg_programs and all(
        p.endswith(("/sort", "/final")) for p in agg_programs), agg_programs
    sub = df[df.v > -5e6]
    exp = sub.groupby("k").agg(sv=("v", "sum"), n=("v", "size"),
                               av=("v", "mean"), sq=("q", "sum"),
                               mv=("v", "min"))
    assert len(got) == len(exp)
    for k, row in exp.iterrows():
        sv, cnt, av, sq, mv = got[k]
        assert cnt == row["n"] and sq == row["sq"] and mv == row["mv"]
        # float64's rounding; a float32 pair gave 1e-7
        assert sv == pytest.approx(row["sv"], rel=1e-12)
        assert av == pytest.approx(row["av"], rel=1e-12)


# ---------------------------------------------------------------------------
# Few groups: masked reductions in place of the scatter (FEW_GROUPS_MAX)
# ---------------------------------------------------------------------------

import functools  # noqa: E402

from spark_rapids_tpu.ops import aggregates as agg_k  # noqa: E402

_S = agg_k.FEW_GROUPS_MAX
_FEW_ROWS, _FEW_CAP = 1500, 2048
# (name, op, input column, ignore_nulls)
_FEW_SPECS = [
    ("sum_f64", "sum", "v", True), ("sum_i64", "sum", "i", True),
    ("sum_bool", "sum", "b", True), ("count", "count", "v", True),
    ("count_star", "count_star", None, True), ("avg", "avg", "v", True),
    ("min_f64", "min", "v", True), ("max_f64", "max", "v", True),
    ("min_i64", "min", "i", True), ("max_i64", "max", "i", True),
    ("min_bool", "min", "b", True), ("max_bool", "max", "b", True),
    ("min_str", "min", "s", True), ("first", "first", "v", True),
    ("last", "last", "i", True), ("first_with_nulls", "first", "v", False),
    # PR 28: the reductions read rows where they lie; first / last pick by
    # ORIGINAL row over groups of repeated keys and distinct values
    ("last_with_nulls", "last", "v", False), ("first_i64", "first", "i", True),
    ("last_f64", "last", "v", True), ("max_str", "max", "s", True),
]


def _few_data(n_groups):
    """``n_groups`` groups over 1500 rows in no order: a NULL-key group, a
    group whose values are all NULL, a NaN in one group only and an inf in
    another only."""
    rng = np.random.default_rng(1000 + n_groups)
    gid = rng.integers(0, n_groups, _FEW_ROWS)
    gid[:n_groups] = np.arange(n_groups)
    null_key = n_groups - 1 if n_groups > 1 else -1
    v = rng.uniform(1.0, 100.0, _FEW_ROWS)
    v_null = rng.random(_FEW_ROWS) < 0.2
    if n_groups > 3:
        v_null |= gid == 1                       # an all-NULL group
        v[np.flatnonzero(gid == 2)[0]] = np.nan
        v[np.flatnonzero(gid == 3)[0]] = np.inf
        v_null[np.flatnonzero(gid == 2)[0]] = False
        v_null[np.flatnonzero(gid == 3)[0]] = False
    i = rng.integers(-10**12, 10**12, _FEW_ROWS)
    i_null = rng.random(_FEW_ROWS) < 0.1
    b = rng.random(_FEW_ROWS) < 0.5
    s = rng.integers(0, 10**6, _FEW_ROWS)
    cols = {
        "k": _col([None if g == null_key else int(g) for g in gid], dt.INT64),
        "v": _col([None if m else float(x) for m, x in zip(v_null, v)],
                  dt.FLOAT64),
        "i": _col([None if m else int(x) for m, x in zip(i_null, i)],
                  dt.INT64),
        "b": _col([bool(x) for x in b], dt.BOOL),
        "s": _col([f"w{x}" for x in s], dt.STRING),
    }
    # dead rows between live ones, every group keeping a live row
    live = rng.random(_FEW_ROWS) < 0.7
    live[:n_groups] = True
    return cols, gid, (v, v_null), live


@functools.lru_cache(maxsize=None)
def _few_and_scatter(n_groups, masked):
    """Every aggregate of ``_FEW_SPECS`` in one group-by, as the code
    chooses (masked reductions up to FEW_GROUPS_MAX groups) and with the
    choice taken away (the scatter path, as before PR 26)."""
    import jax.numpy as jnp
    cols, gid, (v, v_null), live = _few_data(n_groups)
    i_vals = np.array(cols["i"].to_pylist(_FEW_ROWS), dtype=object)
    i_null = np.array([x is None for x in i_vals])
    specs = [AggSpec(op, cols[c] if c else None, ignore_nulls=ign)
             for _n, op, c, ign in _FEW_SPECS]
    mask = None
    if masked:
        mask = jnp.asarray(np.concatenate(
            [live, np.zeros(_FEW_CAP - _FEW_ROWS, bool)]))
    else:
        live = np.ones(_FEW_ROWS, bool)

    def run():
        keys, aggs, ng = groupby_aggregate([cols["k"]], specs, _FEW_ROWS,
                                           _FEW_CAP, live_mask=mask)
        assert int(ng) == n_groups
        # the whole capacity: what lies beyond the groups counts too
        return [[np.asarray(a) for a in c.arrays()] for c in keys + aggs]

    chosen = run()
    old = agg_k.FEW_GROUPS_MAX
    agg_k.FEW_GROUPS_MAX = 0
    try:
        scatter = run()
    finally:
        agg_k.FEW_GROUPS_MAX = old
    # numpy's float64 sums, groups in the output's order (NULL key first)
    order = ([n_groups - 1] if n_groups > 1 else []) + \
        list(range(n_groups - 1 if n_groups > 1 else 1))
    sums = [v[(gid == g) & live & ~v_null].sum() for g in order]

    def pick(vals, null, last, ignore_nulls):
        """numpy's first / last: (value, valid) per group, by row index."""
        out = []
        for g in order:
            rows = np.flatnonzero((gid == g) & live &
                                  (~null if ignore_nulls else True))
            r = rows[-1 if last else 0] if len(rows) else None
            out.append((0, False) if r is None or null[r]
                       else (vals[r], True))
        return out
    picks = {"first": pick(v, v_null, False, True),
             "last": pick(i_vals, i_null, True, True),
             "first_with_nulls": pick(v, v_null, False, False),
             "last_with_nulls": pick(v, v_null, True, False),
             "first_i64": pick(i_vals, i_null, False, True),
             "last_f64": pick(v, v_null, True, True)}
    return chosen, scatter, np.array(sums), picks


@pytest.mark.parametrize("n_groups, masked", [
    (1, False), (_S - 1, False), (_S - 1, True), (_S, True), (_S + 1, False),
    (4, True), (_S + 1, True)],
    ids=["1", "S-1", "S-1_live_mask", "S_live_mask", "S+1", "4_live_mask",
         "S+1_live_mask"])
@pytest.mark.parametrize(
    "which", range(len(_FEW_SPECS) + 1),
    ids=["keys"] + [name for name, *_ in _FEW_SPECS])
def test_few_groups_equal_the_scatter_path(which, n_groups, masked):
    """Up to FEW_GROUPS_MAX groups the reductions are masked ones, beyond
    it the scatter: either way every slot of every output array equals the
    scatter path's — bit for bit but for float64 sums, which are held to
    the scatter's chain of adds at 1e-13 and to numpy's sum at 1e-15."""
    chosen, scatter, np_sums, np_picks = _few_and_scatter(n_groups, masked)
    name = "keys" if which == 0 else _FEW_SPECS[which - 1][0]
    for got, want in zip(chosen[which], scatter[which]):
        if name in ("sum_f64", "avg") and got.dtype == np.float64:
            # the scatter adds a group's rows one after another
            np.testing.assert_allclose(got, want, rtol=1e-13, atol=0)
        else:
            np.testing.assert_array_equal(got, want)
    if name == "sum_f64":
        data, valid = chosen[which]
        finite = np.isfinite(np_sums) & (np_sums != 0)
        assert finite.sum() >= max(1, (n_groups - 3) * 3 // 4)
        np.testing.assert_allclose(data[:n_groups][finite], np_sums[finite],
                                   rtol=1e-15)
        # the NaN and the inf stayed in their own groups
        assert np.array_equal(np.isnan(data[:n_groups]), np.isnan(np_sums))
        assert np.array_equal(np.isinf(data[:n_groups]), np.isinf(np_sums))
        assert not valid[n_groups:].any() and not data[n_groups:].any()
    if name in np_picks:
        data, valid = chosen[which]
        want_data, want_valid = zip(*np_picks[name])
        assert list(valid[:n_groups]) == list(want_valid)
        np.testing.assert_array_equal(      # a NaN may be the pick
            data[:n_groups][list(want_valid)],
            np.array([x for x, ok in np_picks[name] if ok], data.dtype))


@pytest.mark.parametrize("ignore_nulls", [True, False],
                         ids=["ignore_nulls", "respect_nulls"])
@pytest.mark.parametrize("op", ["first", "last"])
def test_first_last_pick_what_gather_then_reduce_picked(op, ignore_nulls):
    """Until PR 28 the group-by gathered every input into sort order and
    picked first / last by SORTED position; it now picks by original row.
    The two agree because ``sort_indices`` is stable (every pass of
    ``_lexsort_passes`` is): pinned here against the old form, over groups
    of repeated keys and distinct values with dead rows between live ones."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops import kernels as K
    cols, _gid, _v, live = _few_data(7)
    mask = jnp.asarray(np.concatenate(
        [live, np.zeros(_FEW_CAP - _FEW_ROWS, bool)]))
    spec = AggSpec(op, cols["v"], ignore_nulls=ignore_nulls)
    _keys, (got,), ng = groupby_aggregate([cols["k"]], [spec], _FEW_ROWS,
                                          _FEW_CAP, live_mask=mask)
    # the old form: rows to the ids
    n_live = jnp.sum(mask).astype(jnp.int32)
    order = K.sort_indices([K.SortKey(cols["k"])], n_live, _FEW_CAP,
                           live_mask=mask)
    starts = K.segment_starts_from_sorted_keys(
        [K.gather_column(cols["k"], order)], n_live, _FEW_CAP)
    want = agg_k.segment_aggregate(
        spec._replace(column=K.gather_column(cols["v"], order)),
        K.segment_ids(starts), jnp.arange(_FEW_CAP) < n_live, _FEW_CAP)
    g = int(ng)
    assert g == 7
    for a, b in zip(got.arrays(), want.arrays()):
        np.testing.assert_array_equal(np.asarray(a)[:g], np.asarray(b)[:g])
    picked = [x for x in got.to_pylist(g) if x is not None]
    assert len(set(picked)) == len(picked) >= 3     # distinct values


@pytest.mark.parametrize("counted", [False, True],
                         ids=["every_slot", "groups_present"])
@pytest.mark.parametrize("dtype", ["float64", "int64", "int32"])
@pytest.mark.parametrize("kind", ["sum", "min", "max"])
def test_masked_segment_reduce_takes_rows_in_any_order(kind, dtype, counted):
    """The helper alone, rows NOT sorted by segment, a NaN and an inf among
    them: what ``jax.ops.segment_<kind>`` gives, slot for slot — the empty
    slots too."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(7)
    n, present = 3000, _S - 5
    ids = rng.integers(0, present, n).astype(np.int32)
    data = rng.uniform(-50.0, 50.0, n)
    if dtype == "float64" and kind != "sum":
        data[np.flatnonzero(ids == 3)[0]] = np.inf
    data = jnp.asarray(data.astype(dtype))
    segs = agg_k._Segs(jnp.asarray(ids), _S,
                       jnp.int32(present) if counted else None)
    got = np.asarray(agg_k._masked_segment_reduce(kind, data, segs))
    want = np.asarray(getattr(jax.ops, f"segment_{kind}")(
        data, segs.ids, num_segments=_S))
    assert got.dtype == want.dtype
    if dtype == "float64" and kind == "sum":
        np.testing.assert_allclose(got, want, rtol=1e-13, atol=1e-11)
        ref = [np.asarray(data)[ids == g].sum() for g in range(present)]
        np.testing.assert_allclose(got[:present], ref, rtol=1e-13,
                                   atol=1e-11)
    else:
        np.testing.assert_array_equal(got, want)
