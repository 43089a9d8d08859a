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
