"""Group-by and reduction aggregate kernels: the cuDF ``groupBy.aggregate`` analog.

Reference: ``org/apache/spark/sql/rapids/AggregateFunctions.scala`` (531 LoC) —
each Spark aggregate decomposes into ``CudfAggregate`` update/merge pairs
(average = sum + count; the hash-agg exec drives update-aggregation per batch and
merge-aggregation across batches, aggregate.scala:305-560).

TPU-first design (DESIGN.md §3): no device hash tables. Group-by is sort-based:
  lexsort rows by the group keys -> segment-start flags -> segment ids,
  carried back to the rows where they lie -> segment reductions of the
  UNSORTED input columns with num_segments = capacity (static shape).
Group count travels as a device scalar; group keys are the key values at segment
starts, compacted to the front. SQL null semantics: aggregates skip NULL inputs;
an all-NULL (or empty) group yields NULL for sum/min/max/avg and 0 for count.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar import dtypes as dt
from ..columnar.column import Column, build_column
from ..exec.tracing import stage
from . import kernels as K


class AggSpec(NamedTuple):
    """One aggregation over one input column (None input = COUNT(*))."""
    op: str                      # count/count_star/sum/min/max/avg/first/last
    column: Optional[Column]
    ignore_nulls: bool = True    # for first/last


def _sum_dtype(in_dtype: dt.DType) -> dt.DType:
    """Spark widens SUM: integral -> bigint, floating -> double."""
    if in_dtype.is_integral or in_dtype == dt.BOOL:
        return dt.INT64
    return dt.FLOAT64


def result_dtype(op: str, in_dtype: Optional[dt.DType]) -> dt.DType:
    if op in ("count", "count_star"):
        return dt.INT64
    if op == "sum":
        return _sum_dtype(in_dtype)
    if op == "avg":
        return dt.FLOAT64
    return in_dtype  # min/max/first/last preserve type


# ---------------------------------------------------------------------------
# Segment reductions (update phase)
# ---------------------------------------------------------------------------
#
# What the chip showed (TPU v5e, one 8 Mi-row batch; PERF.md sections 5-6):
# ``jax.ops.segment_sum`` is an index sort plus a scatter-add, and a 64-bit
# scatter-ADD (float64 and int64 are carried as 32-bit lanes) serialises at
# ~120 ns a row: 0.76-1.07 s a column, however few slots are filled. An
# int32 one takes 73 ms and a unique-index scatter 41 ms. A reduction does
# not serialise, so where the groups are few each group's value is a masked
# reduction over the whole batch, in the column's own dtype.

#: Most groups for which a segment reduction is per-group masked reductions
#: (one pass over the rows for each group) and not a scatter. One pass over
#: 8 Mi rows reads 0.14 ms a group in float64, 0.10 in int32 and 0.06 in
#: int64 on a v5e, so 128 groups cost 18 / 12.5 / 7 ms where the scatter
#: costs 800 / 74-83 ms whatever the count (builder's chip run, PR 26); the
#: curves cross near 700 groups for int32 and 5 000 for float64.
FEW_GROUPS_MAX = 128


class _Segs(NamedTuple):
    """Where the rows of a batch reduce to: ``ids`` is int32[capacity], the
    group of each row in ANY row order; ``num`` the output slots (static);
    ``n_groups`` a device count of the groups present where the caller has
    one and it is known to be <= ``num`` (slots beyond it are left empty)."""
    ids: jnp.ndarray
    num: int
    n_groups: Optional[jnp.ndarray] = None


_SCATTER = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
            "max": jax.ops.segment_max}
# dtype: jnp.sum alone would widen an int32 count, segment_sum does not
_REDUCE = {"sum": lambda x: jnp.sum(x, dtype=x.dtype), "min": jnp.min,
           "max": jnp.max}


def _identity(kind: str, dtype):
    """What ``jax.ops.segment_<kind>`` leaves in a segment with no row."""
    if kind == "sum":
        return jnp.zeros((), dtype)
    top = kind == "min"
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf if top else -jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.max if top else info.min, dtype)


def _masked_segment_reduce(kind: str, data, segs: _Segs):
    """``segs.num`` slots, slot g = reduce(where(ids == g, data, identity)):
    no scatter, exact in ``data``'s dtype (a tree of adds, not a chain), rows
    in any order. ``where`` and not a product, so a NaN or an inf stays in
    its own group. A loop of one pass over the rows per group, over the
    groups PRESENT where the count is on the device, so nothing of
    ``num`` x rows is ever held."""
    ident = _identity(kind, data.dtype)
    slots = jnp.arange(segs.num, dtype=jnp.int32)

    def one(g, acc):
        r = _REDUCE[kind](jnp.where(segs.ids == g, data, ident))
        return jnp.where(slots == g, r, acc)

    return jax.lax.fori_loop(
        0, segs.num if segs.n_groups is None else segs.n_groups, one,
        jnp.full((segs.num,), ident))


def _seg_reduce(kind: str, data, segs: _Segs):
    if segs.num <= FEW_GROUPS_MAX:
        return _masked_segment_reduce(kind, data, segs)
    return _SCATTER[kind](data, segs.ids, num_segments=segs.num)


def _seg_sum(data, segs: _Segs):
    return _seg_reduce("sum", data, segs)


def _seg_min(data, segs: _Segs):
    return _seg_reduce("min", data, segs)


def _seg_max(data, segs: _Segs):
    return _seg_reduce("max", data, segs)


def _masked(data, mask, fill):
    return jnp.where(mask, data, jnp.asarray(fill, data.dtype))


def _string_ordinal_minmax(col: Column, contrib, segs: _Segs, want_min: bool):
    """Min/max for strings: reduce over the *row index* ordered by the encoded
    string key, then gather the winning row's bytes."""
    cap = col.capacity
    words = K.pack_string_words(col.data, col.lengths)
    # build a sortable composite: argsort rows by string order, then the rank of
    # each row is a uint32 we can min/max within segments
    order = jnp.lexsort(tuple(reversed(
        [w for w in words.T] + [col.lengths.astype(jnp.uint32)])))
    rank = jnp.zeros(cap, dtype=jnp.int32).at[order].set(
        jnp.arange(cap, dtype=jnp.int32))
    sentinel = jnp.int32(cap) if want_min else jnp.int32(-1)
    r = jnp.where(contrib, rank, sentinel)
    red = _seg_min(r, segs) if want_min else _seg_max(r, segs)
    has = red != sentinel
    win_rank = jnp.where(has, red, 0)
    # rank -> row index
    win_row = order[jnp.clip(win_rank, 0, cap - 1)]
    return win_row, has


def segment_aggregate(spec: AggSpec, seg_ids: jnp.ndarray, live: jnp.ndarray,
                      capacity: int, num_segments: Optional[int] = None,
                      n_groups=None) -> Column:
    """Update-phase aggregation: reduce each segment of input rows to one output
    row per group id. Output column has ``num_segments`` slots (group g at
    slot g; defaults to ``capacity`` for the sort-based path where segment ids
    live in row space); slots beyond the group count are zeroed+invalid by
    construction because no row contributes to them.

    At ``num_segments`` <= ``FEW_GROUPS_MAX`` no scatter is emitted: every
    reduction is per-group masked reductions, over the groups present where
    ``n_groups`` (a device count, <= ``num_segments``) says how many.
    """
    ns = capacity if num_segments is None else num_segments
    # the implementations of a segment reduction, named apart
    # (exec/tracing.STAGES)
    if spec.op in ("min", "max", "first", "last"):
        name = "segment_minmax"
    elif ns <= FEW_GROUPS_MAX:
        name = "segment_sum_masked"
    else:
        name = "segment_sum_scatter"
    with jax.named_scope(name):
        return _segment_aggregate(spec, _Segs(seg_ids, ns, n_groups), live,
                                  capacity)


def _segment_aggregate(spec: AggSpec, segs: _Segs, live: jnp.ndarray,
                       capacity: int) -> Column:
    op = spec.op
    if op == "count_star":
        data = _seg_sum(live.astype(jnp.int64), segs)
        valid = _seg_sum(live.astype(jnp.int32), segs) > 0
        return Column(dt.INT64, data, valid)

    col = spec.column
    contrib = live & col.validity
    if op == "count":
        data = _seg_sum(contrib.astype(jnp.int64), segs)
        valid = _seg_sum(live.astype(jnp.int32), segs) > 0
        return Column(dt.INT64, data, valid)

    group_has = _seg_sum(contrib.astype(jnp.int32), segs) > 0

    if op == "sum":
        out_t = _sum_dtype(col.dtype)
        d = _masked(col.data.astype(out_t.numpy_dtype), contrib, 0)
        data = _seg_sum(d, segs)
        return Column(out_t, _masked(data, group_has, 0), group_has)

    if op == "avg":
        d = _masked(col.data.astype(jnp.float64), contrib, 0.0)
        s = _seg_sum(d, segs)
        c = _seg_sum(contrib.astype(jnp.float64), segs)
        data = jnp.where(group_has, s / jnp.maximum(c, 1.0), 0.0)
        return Column(dt.FLOAT64, data, group_has)

    if op in ("min", "max"):
        if col.dtype == dt.STRING:
            win_row, has = _string_ordinal_minmax(col, contrib, segs,
                                                  want_min=(op == "min"))
            out = K.gather_column(col, win_row, out_valid=has)
            return out
        if col.dtype.is_floating:
            # Spark total order: NaN largest. Use +/-inf fill, restore NaN via flags.
            is_nan = jnp.isnan(col.data) & contrib
            seg_nan = _seg_sum(is_nan.astype(jnp.int32), segs) > 0
            seg_non_nan = _seg_sum((contrib & ~is_nan).astype(jnp.int32),
                                   segs) > 0
            fill = jnp.inf if op == "min" else -jnp.inf
            d = _masked(col.data, contrib & ~is_nan, fill)
            red = (_seg_min if op == "min" else _seg_max)(d, segs)
            if op == "min":
                data = jnp.where(seg_non_nan, red, jnp.nan)  # all-NaN group -> NaN
            else:
                data = jnp.where(seg_nan, jnp.nan, red)      # any NaN -> NaN max
            data = jnp.where(group_has, data, 0.0).astype(col.data.dtype)
            return Column(col.dtype, data, group_has)
        if col.dtype == dt.BOOL:
            d = _masked(col.data.astype(jnp.int32), contrib, 1 if op == "min" else 0)
            red = (_seg_min if op == "min" else _seg_max)(d, segs)
            data = (red > 0) & group_has
            return Column(dt.BOOL, data, group_has)
        info = jnp.iinfo(col.data.dtype)
        fill = info.max if op == "min" else info.min
        d = _masked(col.data, contrib, fill)
        red = (_seg_min if op == "min" else _seg_max)(d, segs)
        return Column(col.dtype, _masked(red, group_has, 0), group_has)

    if op in ("first", "last"):
        idx = jnp.arange(capacity, dtype=jnp.int32)
        pick_from = contrib if spec.ignore_nulls else live
        grp_has = _seg_sum(pick_from.astype(jnp.int32), segs) > 0
        if op == "first":
            r = jnp.where(pick_from, idx, capacity)
            win = _seg_min(r, segs)
        else:
            r = jnp.where(pick_from, idx, -1)
            win = _seg_max(r, segs)
        win = jnp.clip(win, 0, capacity - 1)
        return K.gather_column(col, win, out_valid=grp_has)

    raise ValueError(f"unknown aggregate op {op!r}")


# ---------------------------------------------------------------------------
# Whole group-by driver
# ---------------------------------------------------------------------------

def groupby_aggregate(key_cols: Sequence[Column], specs: Sequence[AggSpec],
                      num_rows, capacity: int,
                      live_mask: Optional[jnp.ndarray] = None
                      ) -> Tuple[List[Column], List[Column], jnp.ndarray]:
    """Sort-based group-by: returns (group key columns, agg result columns,
    device group count). All outputs have ``capacity`` slots with groups
    compacted to the front. ``live_mask`` (folded-filter rows) sorts dead
    rows last instead of requiring a compacted input.

    cuDF analog: ``Table.groupBy(...).aggregate(...)`` as driven by
    GpuHashAggregateExec (aggregate.scala:427-485).
    """
    if live_mask is not None:
        num_rows = jnp.sum(live_mask).astype(jnp.int32)
    sort_keys = [K.SortKey(c) for c in key_cols]
    order = K.sort_indices(sort_keys, num_rows, capacity,
                           live_mask=live_mask)
    sorted_keys = [K.gather_column(c, order) for c in key_cols]
    starts = K.segment_starts_from_sorted_keys(sorted_keys, num_rows, capacity)
    n_groups = jnp.sum(starts).astype(jnp.int32)
    # The ids go to the rows, not each aggregate's input to the ids (0.4 s a
    # float64 column of 8 Mi rows on a v5e; a reduction takes any row order).
    # first / last pick by ORIGINAL row: the sort's pick, since it is stable.
    seg_ids = K.segment_ids_by_row(K.segment_ids(starts), order)
    live = jnp.arange(capacity) < num_rows if live_mask is None else live_mask

    # group keys: gather the first row of each segment to the front
    with jax.named_scope("segment_starts"):
        start_perm, _ = K.compaction_indices(starts)
        group_live = jnp.arange(capacity) < n_groups
    out_keys = [K.gather_column(c, start_perm, out_valid=group_live)
                for c in sorted_keys]

    def reduce_all(num_segments: int, known_groups=None):
        # mask agg slots beyond the group count (paranoia: segment ids of
        # padding rows alias a real group, so data is fine; but enforce
        # the padding invariant explicitly)
        return [_mask_to(_pad_slots(segment_aggregate(
            s, seg_ids, live, capacity, num_segments, known_groups),
            capacity), group_live).arrays() for s in specs]

    # The choice the data makes, on the device: few groups take no scatter
    # (the 64-bit scatter-add serialises on a TPU, see the top of the file).
    # ONE ``cond`` round all the aggregates, no sorted copies being left to
    # keep alive across it: q1 takes 2.34 s on a v5e where one per aggregate
    # takes 2.75 (the same key gathers, scheduled worse); the price is the
    # scatter branch's temporaries, 452 MB for five aggregates of 8 Mi rows
    # where one per aggregate holds 142 (PERF.md section 6, PR 28).
    few = min(capacity, FEW_GROUPS_MAX)
    if few == capacity:             # the smallest bucket: nothing to choose
        arrays = reduce_all(few, n_groups)
    else:
        arrays = jax.lax.cond(n_groups <= few,
                              lambda: reduce_all(few, n_groups),
                              lambda: reduce_all(capacity))
    out_aggs = [build_column(_agg_dtype(s), a)[0]
                for s, a in zip(specs, arrays)]
    return out_keys, out_aggs, n_groups


@stage("reduce")
def reduce_aggregate(specs: Sequence[AggSpec], num_rows, capacity: int,
                     live_mask: Optional[jnp.ndarray] = None
                     ) -> List[Column]:
    """Grouping-free reduction (SELECT SUM(x) FROM t): one output row at
    slot 0 of a min-bucket (128-slot) column.

    Empty input: count = 0, everything else NULL (aggregate.scala:487-505
    empty-input reduction semantics). ``live_mask`` replaces the prefix
    row mask for folded-filter inputs (no compaction needed at all here).
    Internally this is ``segment_aggregate`` with ONE segment, which is a
    masked reduce and no scatter (``FEW_GROUPS_MAX``): ``jax.ops.segment_sum``
    into one slot is, on the TPU, a scatter-add of every row into that slot
    (929 ms of q6's 1004 ms busy at SF1; 7 ms as a reduce).
    """
    seg_ids = jnp.zeros(capacity, dtype=jnp.int32)
    live = live_mask if live_mask is not None \
        else jnp.arange(capacity) < num_rows
    out_cap = 128                       # MIN_CAPACITY bucket
    out: List[Column] = []
    one = jnp.arange(out_cap) < 1
    for spec in specs:
        agg = _pad_slots(segment_aggregate(spec, seg_ids, live, capacity,
                                           num_segments=1), out_cap)
        if spec.op in ("count", "count_star"):
            # count of empty input is 0 (valid), not NULL
            data = jnp.where(one, agg.data, 0)
            out.append(Column(dt.INT64, data, one))
        else:
            out.append(_mask_to(agg, one))
    return out


# ---------------------------------------------------------------------------
# MXU fast path: one-hot matmul segment reductions (TPU-native)
# ---------------------------------------------------------------------------
#
# The scatter-ADD behind ``jax.ops.segment_sum`` serialises on the TPU (0.8 s
# for one float64 column of 8 Mi rows whatever the slot count, 74-83 ms for
# an int32 one; see the top of the file); the systolic array is the fastest
# unit. For bounded group counts the reduction is a matmul: sum_g =
# one_hot(seg_ids, K)^T @ values, generated on the fly and fed to the MXU.
# float64 values ride a hi/lo float32 split with chunked float64
# accumulation: NOT exact — 1.1e-07-2.3e-07 relative on q1's sums at SF1 on
# the chip (PERF.md section 2), inside the reference's own benchmark epsilon
# (BenchUtils.compareResults epsilon=1e-4) and the spirit of its
# variableFloatAgg conf, outside TPC-H's $100. Where sums must be exact and
# the groups are few, the masked reductions above are both exact and faster
# (q1: 0.006 s against this path's whole group-by at 4.0 s). Counts are
# exact (integer sums < 2^24 per chunk are exact in f32, chunk totals
# accumulate in f64).

MATMUL_MAX_GROUPS = 4096
_MM_CHUNK = 1 << 17


def _mm_chunks(n: int) -> int:
    return max(1, n // _MM_CHUNK)


def _matmul_segment_sum_f64(data: jnp.ndarray, contrib: jnp.ndarray,
                            seg_ids: jnp.ndarray, K: int) -> jnp.ndarray:
    cap = data.shape[0]
    ch = _mm_chunks(cap)
    d = jnp.where(contrib, data, 0.0)
    ids = jnp.where(contrib, seg_ids, K)        # masked rows -> dropped slot
    hi = d.astype(jnp.float32)
    lo = (d - hi.astype(jnp.float64)).astype(jnp.float32)
    oh = jax.nn.one_hot(ids.reshape(ch, -1), K, dtype=jnp.float32)
    shi = jnp.einsum("cnk,cn->ck", oh, hi.reshape(ch, -1),
                     precision=jax.lax.Precision.HIGHEST)
    slo = jnp.einsum("cnk,cn->ck", oh, lo.reshape(ch, -1),
                     precision=jax.lax.Precision.HIGHEST)
    return (shi.astype(jnp.float64) + slo.astype(jnp.float64)).sum(0)


def _matmul_segment_count(contrib: jnp.ndarray, seg_ids: jnp.ndarray,
                          K: int) -> jnp.ndarray:
    cap = contrib.shape[0]
    ch = _mm_chunks(cap)
    ids = jnp.where(contrib, seg_ids, K)
    oh = jax.nn.one_hot(ids.reshape(ch, -1), K, dtype=jnp.float32)
    c = jnp.einsum("cnk->ck", oh,
                   precision=jax.lax.Precision.HIGHEST)
    return c.astype(jnp.int64).sum(0)


def _matmul_supported(spec: AggSpec) -> bool:
    if spec.op in ("count", "count_star"):
        return True
    if spec.op in ("sum", "avg") and spec.column is not None and \
            spec.column.dtype.is_floating:
        return True
    return False


@stage("segment_sum_matmul")
def segment_aggregate_matmul(spec: AggSpec, seg_ids: jnp.ndarray,
                             live: jnp.ndarray, K: int) -> Column:
    """MXU reduction to K group slots (first K slots of capacity outputs)."""
    op = spec.op
    if op == "count_star":
        data = _matmul_segment_count(live, seg_ids, K)
        return Column(dt.INT64, data, jnp.ones(K, jnp.bool_))
    col = spec.column
    contrib = live & col.validity
    cnt = _matmul_segment_count(contrib, seg_ids, K)
    if op == "count":
        return Column(dt.INT64, cnt, jnp.ones(K, jnp.bool_))
    has = cnt > 0
    s = _matmul_segment_sum_f64(col.data.astype(jnp.float64), contrib,
                                seg_ids, K)
    if op == "sum":
        return Column(dt.FLOAT64, jnp.where(has, s, 0.0), has)
    if op == "avg":
        data = jnp.where(has, s / jnp.maximum(cnt.astype(jnp.float64), 1.0),
                         0.0)
        return Column(dt.FLOAT64, data, has)
    raise ValueError(f"matmul path does not support {op}")


# ---------------------------------------------------------------------------
# Dense-range MXU group-by: the perfect-hash fast path (sort-free)
# ---------------------------------------------------------------------------
#
# When a single fixed-width integral key spans a small range (DuckDB's
# "perfect hash aggregate" condition; scans know key ranges from parquet
# row-group statistics), the group slot is simply ``key - rmin``: no sort, no
# compaction, no large gathers. Every aggregate becomes ONE chunked one-hot
# matmul on the MXU plus a K-sized cleanup. This is the fastest group-by
# shape on TPU by ~50x over the sort-based path (the whole pipeline is
# elementwise passes + systolic-array matmuls at full HBM bandwidth).
#
# Exactness: counts ride f32 per-chunk (chunk = 2^17 < 2^24 exact),
# accumulated in i64. Float sums ride a hi/lo f32 split with f64 chunk
# accumulation (~1e-6 abs; values must be within F32_SAFE_ABSMAX — the
# dispatch checks and falls back). Integer sums are bit-exact: 16 nibble
# planes per i64, each plane's per-chunk f32 sum <= 15 * 2^17 < 2^24,
# recombined with shifts in i64 (wraparound = Spark bigint overflow).
# min/max/first/last are K-slot segment reductions: masked ones up to
# FEW_GROUPS_MAX slots, K-sized scatters beyond.

DENSE_MAX_SLOTS = 4096
_DENSE_CHUNK = 1 << 17


def dense_supported_key(col: Column) -> bool:
    return col.dtype in (dt.INT8, dt.INT16, dt.INT32, dt.INT64, dt.BOOL,
                         dt.DATE, dt.TIMESTAMP)


# chunk partial sums of the hi/lo f32 planes must stay finite in f32:
# |v| * chunk_rows must be < f32 max (3.4e38); 1e33 * 2^17 ~ 1.3e38.
F32_SAFE_ABSMAX = 1e33


@stage("reduce")
def dense_key_stats(key_col: Column, num_rows,
                    extra_mask: Optional[jnp.ndarray] = None,
                    float_cols: Sequence[Column] = ()):
    """Dense-dispatch statistics in ONE device computation.

    Returns ``(rmin, decision)``: ``rmin`` stays a device i64 scalar (exact,
    fed straight into ``groupby_dense``); ``decision`` is one f64 vector
    ``[span, n_usable, *absmax_per_float_col]`` — a single host sync decides
    the static slot count and whether every float agg column is within the
    f32-safe range (values beyond it would overflow the hi/lo split).
    """
    cap = key_col.capacity
    live = jnp.arange(cap) < num_rows
    if extra_mask is not None:
        live = live & extra_mask
    usable = live & key_col.validity
    k = key_col.data.astype(jnp.int64)
    imax = jnp.iinfo(jnp.int64).max
    imin = jnp.iinfo(jnp.int64).min
    rmin = jnp.min(jnp.where(usable, k, imax))
    rmax = jnp.max(jnp.where(usable, k, imin))
    nu = jnp.sum(usable.astype(jnp.int32))
    # span in f64 (approximate is fine: it only gates the <= DENSE_MAX_SLOTS
    # test, where exact small spans are exactly representable)
    span = jnp.where(nu > 0,
                     rmax.astype(jnp.float64) - rmin.astype(jnp.float64), 0.0)
    rmin = jnp.where(nu > 0, rmin, 0)
    parts = [span, nu.astype(jnp.float64)]
    for c in float_cols:
        contrib = live & c.validity
        a = jnp.abs(c.data)
        a = jnp.where(contrib & ~jnp.isnan(c.data), a, 0.0)  # NaN sums are
        parts.append(jnp.max(a).astype(jnp.float64))         # NaN either way
    return rmin, jnp.stack(parts)


def _onehot_feature_sums(seg: jnp.ndarray, feats: Sequence[jnp.ndarray],
                         K_slots: int) -> jnp.ndarray:
    """sum of each feature per slot via ONE chunked one-hot matmul; f64[K, F].

    ``feats`` is a list of f32[cap] arrays; they are stacked per chunk inside
    the scan body so the full [cap, F] matrix never materializes in HBM.

    Non-bucketed capacities are zero-padded up to a multiple of _DENSE_CHUNK
    so (a) the chunk reshape is always legal for any public caller and (b)
    per-chunk rows never exceed _DENSE_CHUNK — the bound the f32-exactness
    analysis (top of this section) assumes.
    """
    cap = seg.shape[0]
    if cap <= _DENSE_CHUNK:
        ch = 1
    else:
        ch = -(-cap // _DENSE_CHUNK)
        padded = ch * _DENSE_CHUNK
        if padded != cap:
            pad = padded - cap
            # padded rows contribute 0 to every feature plane regardless of
            # their (zero) segment id
            seg = jnp.concatenate([seg, jnp.zeros(pad, seg.dtype)])
            feats = [jnp.concatenate([f, jnp.zeros(pad, f.dtype)])
                     for f in feats]
            cap = padded

    def body(acc, xs):
        s, fs = xs
        f = jnp.stack(fs, axis=-1)
        oh = jax.nn.one_hot(s, K_slots, dtype=jnp.float32)
        p = jnp.einsum("nk,nf->kf", oh, f,
                       precision=jax.lax.Precision.HIGHEST)
        return acc + p.astype(jnp.float64), None

    acc, _ = jax.lax.scan(
        body, jnp.zeros((K_slots, len(feats)), jnp.float64),
        (seg.reshape(ch, -1), tuple(f.reshape(ch, -1) for f in feats)))
    return acc


def _int_nibble_planes(data: jnp.ndarray, contrib: jnp.ndarray
                       ) -> List[jnp.ndarray]:
    """16 f32 nibble planes of an int64; per-chunk f32 sums stay exact."""
    u = data.astype(jnp.int64).astype(jnp.uint64)
    return [jnp.where(contrib,
                      ((u >> jnp.uint64(4 * p)) & jnp.uint64(0xF)
                       ).astype(jnp.float32), 0.0)
            for p in range(16)]


def _recombine_nibble_sums(acc: jnp.ndarray) -> jnp.ndarray:
    """i64 totals from 16 nibble-plane f64 sums (wraps like Spark bigint)."""
    total = jnp.zeros(acc.shape[0], dtype=jnp.uint64)
    for p in range(16):
        total = total + (acc[:, p].astype(jnp.uint64) << jnp.uint64(4 * p))
    return total.astype(jnp.int64)


@stage("segment_sum_dense")
def groupby_dense(key_col: Column, specs: Sequence[AggSpec], num_rows,
                  K_slots: int, rmin,
                  extra_mask: Optional[jnp.ndarray] = None
                  ) -> Tuple[List[Column], List[Column], jnp.ndarray]:
    """Dense-range group-by. Fully traceable (jit-safe): only ``K_slots`` is
    static; ``rmin``/``num_rows`` may be device scalars.

    Caller contract: every live non-NULL key satisfies
    ``0 <= key - rmin <= K_slots - 2`` (slot ``K_slots - 1`` is reserved for
    the NULL-key group, which Spark keeps as a real group). Outputs are
    compacted to the front, key-ordered with the NULL group last; returns
    (key columns, agg columns, device group count) at K_slots capacity.
    """
    cap = key_col.capacity
    live = jnp.arange(cap) < num_rows
    if extra_mask is not None:
        live = live & extra_mask
    key_ok = live & key_col.validity
    k_i = key_col.data.astype(jnp.int64)
    null_slot = jnp.int32(K_slots - 1)
    seg = jnp.where(key_ok, (k_i - rmin).astype(jnp.int32), null_slot)
    seg = jnp.clip(jnp.where(live, seg, null_slot), 0, K_slots - 1)

    # Plan every matmul-reducible feature into ONE chunked one-hot scan
    # (occupancy + per-column contrib counts + hi/lo value planes + int
    # nibble planes), then assemble per-spec outputs from the [K, F] sums.
    feats: List[jnp.ndarray] = [live.astype(jnp.float32)]   # 0: occupancy
    feat_idx = {}

    def add_feats(key, build_list) -> int:
        """Register feature array(s) once per (role, column); return index."""
        if key not in feat_idx:
            feat_idx[key] = len(feats)
            built = build_list()
            feats.extend(built if isinstance(built, list) else [built])
        return feat_idx[key]

    plans = []
    for spec in specs:
        op = spec.op
        if op == "count_star":
            plans.append(("count_star",))
            continue
        col = spec.column
        contrib = live & col.validity
        cid = id(col.data)
        if op in ("min", "max", "first", "last"):
            # K-slot segment reductions; reuse the canonical Spark
            # semantics (NaN total order, sentinels, nulls)
            plans.append(("done", segment_aggregate(spec, seg, live, cap,
                                                    num_segments=K_slots)))
            continue
        ci = add_feats(("contrib", cid),
                       lambda c=contrib: c.astype(jnp.float32))
        if op == "count":
            plans.append(("count", ci))
        elif op == "sum" and (col.dtype.is_integral or col.dtype == dt.BOOL):
            ni = add_feats(("nibbles", cid),
                           lambda c=col, m=contrib: _int_nibble_planes(
                               c.data, m))
            plans.append(("int_sum", ni, ci))
        elif op in ("sum", "avg"):
            # NaN contributions are excluded from the matmul features (0*NaN
            # would poison every slot in the chunk) and re-introduced per
            # slot via a NaN-count feature: any NaN in a group -> NaN result
            def hilo(c=col, m=contrib):
                d = c.data.astype(jnp.float64)
                nan = jnp.isnan(d)
                hi = jnp.where(nan, 0.0, d).astype(jnp.float32)
                lo = (jnp.where(nan, 0.0, d)
                      - hi.astype(jnp.float64)).astype(jnp.float32)
                z = jnp.float32(0)
                mnn = m & ~nan
                return [jnp.where(mnn, hi, z), jnp.where(mnn, lo, z),
                        (m & nan).astype(jnp.float32)]
            hl = add_feats(("hilo", cid), hilo)
            plans.append((op, hl, ci))
        else:
            raise ValueError(f"dense path does not support {op!r}")

    acc = _onehot_feature_sums(seg, feats, K_slots)
    occupancy = acc[:, 0]
    present = occupancy > 0

    slot_aggs: List[Column] = []
    for plan in plans:
        kind = plan[0]
        if kind == "done":
            slot_aggs.append(plan[1])
        elif kind == "count_star":
            slot_aggs.append(Column(dt.INT64, occupancy.astype(jnp.int64),
                                    present))
        elif kind == "count":
            c = acc[:, plan[1]]
            slot_aggs.append(Column(dt.INT64, c.astype(jnp.int64), present))
        elif kind == "int_sum":
            ni, ci = plan[1], plan[2]
            s = _recombine_nibble_sums(acc[:, ni:ni + 16])
            has = acc[:, ci] > 0
            slot_aggs.append(Column(dt.INT64, _masked(s, has, 0), has))
        else:                                     # sum / avg on floats
            hl, ci = plan[1], plan[2]
            s = acc[:, hl] + acc[:, hl + 1]
            s = jnp.where(acc[:, hl + 2] > 0, jnp.nan, s)   # NaN contribs
            cnt = acc[:, ci]
            has = cnt > 0
            if kind == "sum":
                slot_aggs.append(
                    Column(dt.FLOAT64, jnp.where(has, s, 0.0), has))
            else:
                data = jnp.where(has, s / jnp.maximum(cnt, 1.0), 0.0)
                slot_aggs.append(Column(dt.FLOAT64, data, has))

    # key column per slot: rmin + slot index; NULL group at the last slot
    slot_ids = jnp.arange(K_slots, dtype=jnp.int64)
    key_data_i = jnp.asarray(rmin, jnp.int64) + slot_ids
    is_null_slot = slot_ids == (K_slots - 1)
    key_valid = present & ~is_null_slot
    if key_col.dtype == dt.BOOL:
        key_data = (key_data_i != 0) & key_valid
    else:
        key_data = jnp.where(key_valid, key_data_i,
                             0).astype(key_col.data.dtype)

    # compact occupied slots to the front (stable: keeps key order,
    # NULL group last)
    perm, n_groups = K.compaction_indices(present)
    group_live = jnp.arange(K_slots) < n_groups
    out_key = K.gather_column(
        Column(key_col.dtype, key_data, key_valid), perm,
        out_valid=group_live)
    out_aggs = [K.gather_column(c, perm, out_valid=group_live)
                for c in slot_aggs]
    return [out_key], out_aggs, n_groups


def dense_feature_count(specs: Sequence[AggSpec]) -> int:
    """Number of matmul feature planes groupby_dense builds for ``specs``
    (mirrors the planning loop above; used to report accurate FLOPs)."""
    n = 1                                   # occupancy
    seen = set()
    for spec in specs:
        if spec.op in ("count_star", "min", "max", "first", "last"):
            continue
        cid = id(spec.column.data)
        if ("contrib", cid) not in seen:
            seen.add(("contrib", cid))
            n += 1
        if spec.op == "sum" and (spec.column.dtype.is_integral or
                                 spec.column.dtype == dt.BOOL):
            if ("nibbles", cid) not in seen:
                seen.add(("nibbles", cid))
                n += 16
        elif spec.op in ("sum", "avg"):
            if ("hilo", cid) not in seen:
                seen.add(("hilo", cid))
                n += 3
    return n


def _dense_spec_supported(spec: AggSpec) -> bool:
    if spec.op in ("count", "count_star"):
        return True
    c = spec.column
    if c is None:
        return False
    if spec.op in ("sum", "avg"):
        return c.dtype.is_integral or c.dtype == dt.BOOL or c.dtype.is_floating
    if spec.op in ("min", "max"):
        return c.dtype != dt.STRING
    return spec.op in ("first", "last")


def groupby_aggregate_fast(key_cols: Sequence[Column], specs: Sequence[AggSpec],
                           num_rows: int, capacity: int,
                           allow_matmul: bool = True,
                           dense_state: Optional[dict] = None
                           ) -> Tuple[List[Column], List[Column], int]:
    """Eager (host-driven) group-by: dispatches the dense-range MXU path when
    a single integral key spans a small range (one cheap stats sync), else
    sorts, syncs the group count, and uses MXU matmul reductions when the
    group-count bucket is small enough; otherwise the traced sort path.

    ``dense_state`` is an optional caller-held memo dict: once a batch's key
    span disqualifies the dense path, ``dense_state["enabled"]`` flips False
    so later batches of the same operator skip the stats pass entirely
    (key domains are stable across a stream; the flag never flips back).

    Returns host-int group count (callers outside jit). The host sync here is
    the same one TpuHashAggregateExec already performs on n_groups.
    """
    import numpy as _np
    from ..columnar.column import bucket as _bucket
    float_cols = [s.column for s in specs
                  if s.op in ("sum", "avg") and s.column is not None
                  and s.column.dtype.is_floating]
    f32_safe = None        # unknown until a stats sync measures the values
    if (allow_matmul and len(key_cols) == 1
            and (dense_state is None or dense_state.get("enabled", True))
            and dense_supported_key(key_cols[0])
            and all(_dense_spec_supported(s) for s in specs)):
        rmin_d, decision = dense_key_stats(key_cols[0], num_rows,
                                           float_cols=float_cols)
        stats = _np.asarray(decision)  # lint: host-sync-ok the ONE dense-path stats sync (span/absmax decide the kernel)
        span, absmaxes = stats[0], stats[2:]
        f32_safe = bool(all(a <= F32_SAFE_ABSMAX for a in absmaxes))
        if span + 2 <= DENSE_MAX_SLOTS and f32_safe:
            Kb = _bucket(int(span) + 2, 128)
            out_keys, out_aggs, ngd = groupby_dense(
                key_cols[0], specs, num_rows, Kb, rmin_d)
            return out_keys, out_aggs, int(ngd)
        if span + 2 > DENSE_MAX_SLOTS and dense_state is not None:
            dense_state["enabled"] = False

    sort_keys = [K.SortKey(c) for c in key_cols]
    order = K.sort_indices(sort_keys, num_rows, capacity)
    sorted_keys = [K.gather_column(c, order) for c in key_cols]
    live = jnp.arange(capacity) < num_rows
    starts = K.segment_starts_from_sorted_keys(sorted_keys, num_rows, capacity)
    seg_ids = K.segment_ids(starts)
    if f32_safe is None and allow_matmul and float_cols:
        # fold the value-range check into the n_groups sync: the hi/lo f32
        # matmul path is only safe for values within F32_SAFE_ABSMAX
        parts = [jnp.sum(starts).astype(jnp.float64)]
        for c in float_cols:
            contrib = live & c.validity
            a = jnp.where(contrib & ~jnp.isnan(c.data), jnp.abs(c.data), 0.0)
            parts.append(jnp.max(a).astype(jnp.float64))
        arr = _np.asarray(jnp.stack(parts))  # lint: host-sync-ok n_groups + f32-range folded into one stats sync
        n_groups = int(arr[0])
        f32_safe = bool(all(a <= F32_SAFE_ABSMAX for a in arr[1:]))
    else:
        n_groups = int(jnp.sum(starts))  # lint: host-sync-ok eager-path group-count sync sizes the output bucket

    Kb = _bucket(max(n_groups, 1))
    use_mm = (allow_matmul and Kb <= MATMUL_MAX_GROUPS and
              f32_safe is not False and
              all(_matmul_supported(s) for s in specs))

    start_perm, _ = K.compaction_indices(starts)
    group_live = jnp.arange(capacity) < n_groups
    out_keys = [K.gather_column(c, start_perm, out_valid=group_live)
                for c in sorted_keys]

    out_aggs: List[Column] = []
    if use_mm:
        kidx = start_perm[:Kb]
        out_keys = [K.gather_column(c, kidx,
                                    out_valid=jnp.arange(Kb) < n_groups)
                    for c in sorted_keys]
        for spec in specs:
            s = spec
            if spec.column is not None:
                s = spec._replace(column=K.gather_column(spec.column, order))
            agg = segment_aggregate_matmul(s, seg_ids, live, Kb)
            out_aggs.append(_mask_to(agg, jnp.arange(Kb) < n_groups))
        return out_keys, out_aggs, n_groups

    for spec in specs:
        s = spec
        if spec.column is not None:
            s = spec._replace(column=K.gather_column(spec.column, order))
        agg = segment_aggregate(s, seg_ids, live, capacity)
        out_aggs.append(_mask_to(agg, group_live))
    return out_keys, out_aggs, n_groups


def _agg_dtype(spec: AggSpec) -> dt.DType:
    return result_dtype(spec.op,
                        None if spec.column is None else spec.column.dtype)


def _pad_slots(col: Column, capacity: int) -> Column:
    """``col`` grown to ``capacity`` slots, the new ones zeroed and invalid."""
    pad = capacity - col.capacity
    if pad == 0:
        return col
    arrays = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
              for a in col.arrays()]
    return build_column(col.dtype, arrays)[0]


def _mask_to(col: Column, mask: jnp.ndarray) -> Column:
    validity = col.validity & mask
    if col.dtype == dt.STRING:
        data = jnp.where(mask[:, None], col.data, jnp.uint8(0))
        lengths = jnp.where(mask, col.lengths, jnp.int32(0))
        return Column(col.dtype, data, validity, lengths)
    data = jnp.where(validity, col.data, jnp.zeros((), col.data.dtype))
    return Column(col.dtype, data, validity)
