"""Kernel tests: sort encodings, lexsort, filter compaction, concat, segments.

Reference analog: SortExecSuite / GpuCoalesceBatchesSuite-style unit coverage
(SURVEY.md §4 ring 1) against numpy oracles.
"""

import numpy as np

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.column import Column
from spark_rapids_tpu.ops import kernels as K


def _col(vals, dtype):
    return Column.from_pylist(vals, dtype)


def _sorted_pylist(keys, n=None, **kw):
    n = n if n is not None else _count(keys)
    cap = keys[0].column.capacity
    idx = K.sort_indices(keys, n, cap)
    return [k.column.to_pylist(cap) and K.gather_column(k.column, idx).to_pylist(n)
            for k in keys]


def _count(keys):
    return None


def test_sort_ints_asc_nulls_first():
    col = _col([3, None, 1, 2, None], dt.INT64)
    idx = K.sort_indices([K.SortKey(col)], 5, col.capacity)
    out = K.gather_column(col, idx).to_pylist(5)
    assert out == [None, None, 1, 2, 3]


def test_sort_ints_desc_nulls_last():
    col = _col([3, None, 1, 2], dt.INT64)
    idx = K.sort_indices([K.SortKey(col, ascending=False, nulls_first=False)],
                         4, col.capacity)
    out = K.gather_column(col, idx).to_pylist(4)
    assert out == [3, 2, 1, None]


def test_sort_negative_ints():
    col = _col([5, -3, 0, -100, 77], dt.INT64)
    idx = K.sort_indices([K.SortKey(col)], 5, col.capacity)
    assert K.gather_column(col, idx).to_pylist(5) == [-100, -3, 0, 5, 77]


def test_sort_floats_nan_largest():
    col = _col([1.5, float("nan"), -2.0, 0.0], dt.FLOAT64)
    idx = K.sort_indices([K.SortKey(col)], 4, col.capacity)
    out = K.gather_column(col, idx).to_pylist(4)
    assert out[:3] == [-2.0, 0.0, 1.5]
    assert np.isnan(out[3])


def test_sort_floats_desc_nan_first():
    col = _col([1.5, float("nan"), -2.0], dt.FLOAT64)
    idx = K.sort_indices([K.SortKey(col, ascending=False, nulls_first=False)],
                         3, col.capacity)
    out = K.gather_column(col, idx).to_pylist(3)
    assert np.isnan(out[0])
    assert out[1:] == [1.5, -2.0]


def test_sort_strings():
    col = _col(["pear", "apple", None, "banana", "app"], dt.STRING)
    idx = K.sort_indices([K.SortKey(col)], 5, col.capacity)
    out = K.gather_column(col, idx).to_pylist(5)
    assert out == [None, "app", "apple", "banana", "pear"]


def test_sort_multi_key_stability():
    k1 = _col([1, 2, 1, 2, 1], dt.INT32)
    k2 = _col(["b", "x", "a", "y", "a"], dt.STRING)
    idx = K.sort_indices([K.SortKey(k1), K.SortKey(k2)], 5, k1.capacity)
    o1 = K.gather_column(k1, idx).to_pylist(5)
    o2 = K.gather_column(k2, idx).to_pylist(5)
    assert o1 == [1, 1, 1, 2, 2]
    assert o2 == ["a", "a", "b", "x", "y"]


def test_lexsort_passes_equal_one_variadic_lexsort():
    """The LSD-pass formulation (one two-operand sort in a loop — what
    keeps the TPU compile in seconds) is the SAME stable permutation as
    one variadic jnp.lexsort, over every lane kind sort_indices emits:
    packed uint32 runs, a raw uint64 (split hi/lo), a float value lane."""
    import jax.numpy as jnp
    rng = np.random.default_rng(11)
    n = 4096
    lanes = [
        jnp.asarray(rng.integers(0, 3, n).astype(np.uint32)),
        jnp.asarray(rng.integers(0, 2 ** 63, n).astype(np.uint64) *
                    np.uint64(2) + rng.integers(0, 2, n).astype(np.uint64)),
        jnp.asarray(rng.integers(0, 4, n).astype(np.uint32)),
        jnp.asarray(np.round(rng.normal(0, 2, n), 1)),       # ties
        jnp.asarray(rng.integers(0, 5, n).astype(np.uint32)),
        jnp.asarray(rng.integers(0, 5, n).astype(np.uint32)),
    ]
    got = np.asarray(K._lexsort_passes(lanes))
    want = np.asarray(jnp.lexsort(tuple(reversed(lanes))))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    # a single lane is one pass
    np.testing.assert_array_equal(
        np.asarray(K._lexsort_passes(lanes[:1])),
        np.asarray(jnp.argsort(lanes[0], stable=True)))


def test_compact_columns():
    col = _col([10, 20, 30, 40, 50], dt.INT64)
    keep = np.zeros(col.capacity, dtype=bool)
    keep[[1, 3]] = True
    import jax.numpy as jnp
    [out], count = K.compact_columns([col], jnp.asarray(keep))
    assert int(count) == 2
    assert out.to_pylist(2) == [20, 40]
    # rows beyond count are invalid
    assert not bool(np.asarray(out.validity)[2:].any())


def test_concat_columns():
    a = _col([1, 2], dt.INT64)
    b = _col([3, None], dt.INT64)
    out = K.concat_columns([a, b], [2, 2], 256)
    assert out.to_pylist(4) == [1, 2, 3, None]
    assert out.capacity == 256


def test_concat_string_width_mismatch():
    a = _col(["ab"], dt.STRING)
    b = _col(["longer-string-here"], dt.STRING)
    out = K.concat_columns([a, b], [1, 1], 128)
    assert out.to_pylist(2) == ["ab", "longer-string-here"]


def test_segment_starts_and_ids():
    col = _col([1, 1, 2, 2, 2, None, None], dt.INT64)
    starts = K.segment_starts_from_sorted_keys([col], 7, col.capacity)
    s = np.asarray(starts)[:7]
    assert list(s) == [True, False, True, False, False, True, False]
    ids = np.asarray(K.segment_ids(starts))[:7]
    assert list(ids) == [0, 0, 1, 1, 1, 2, 2]


def test_slice_column():
    col = _col([0, 1, 2, 3, 4, 5], dt.INT64)
    out = K.slice_column(col, 2, 128, 3)
    assert out.to_pylist(3) == [2, 3, 4]
