"""Core columnar device kernels: gather, compaction, sort-key encoding, lexsort.

This is the in-tree replacement for the cuDF kernel surface the reference calls
through JNI (``SURVEY.md`` §2.11: join/groupby/sort/filter/contiguous-split all come
from ``ai.rapids.cudf``). Everything here is pure-functional jax.numpy so it can run
eagerly, under ``jax.jit``, or inside a fused whole-stage computation (DESIGN.md §2).

Key techniques (TPU-first, no data-dependent shapes):
* filter = stable compaction by ``argsort`` of the keep-mask — output capacity equals
  input capacity, the true row count travels as a device scalar
* sort = lexicographic order over *order-preserving unsigned key encodings* (sign-flip
  for ints, floats kept as floats behind a NaN rank, big-endian packed words for
  strings) with explicit null-rank and padding-rank keys, taken as stable
  least-significant-first passes of one two-operand sort (``_lexsort_passes``)
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import dtypes as dt
from ..columnar.column import Column
from ..exec.tracing import stage

_UNSIGNED = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}
_SIGNBIT = {1: 0x80, 2: 0x8000, 4: 0x8000_0000, 8: 0x8000_0000_0000_0000}


# ---------------------------------------------------------------------------
# Order-preserving unsigned encodings (for radix-style lexsort keys)
# ---------------------------------------------------------------------------

def encode_orderable_words(data: jnp.ndarray, dtype: dt.DType,
                           descending: bool = False) -> List[jnp.ndarray]:
    """Sort-key arrays (most-significant first) whose lexicographic order equals
    SQL ascending (or descending) order for this dtype.

    Ints/bool/date/timestamp: unsigned sign-flip encoding (bitwise NOT for desc).
    Floats: kept AS FLOATS — a NaN-rank key plus a NaN-free value key (negated for
    desc). No f64 bitcasts: TPU's X64 rewrite cannot bitcast emulated f64, and XLA
    sorts floats natively anyway. Spark semantics preserved: all NaN sort largest
    and equal (so desc puts NaN first).
    """
    if dtype == dt.BOOL:
        u = data.astype(jnp.uint8)
        return [~u if descending else u]
    if dtype.is_integral or dtype in (dt.DATE, dt.TIMESTAMP):
        w = dtype.byte_width
        u = data.astype(_UNSIGNED[w]) ^ jnp.asarray(_SIGNBIT[w], dtype=_UNSIGNED[w])
        return [~u if descending else u]
    if dtype.is_floating:
        is_nan = jnp.isnan(data)
        nan_rank = jnp.where(is_nan, jnp.uint8(0 if descending else 1),
                             jnp.uint8(1 if descending else 0))
        value = jnp.where(is_nan, jnp.zeros((), data.dtype), data)
        return [nan_rank, -value if descending else value]
    raise TypeError(f"not an orderable fixed-width type: {dtype}")


def pack_string_words(data: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
    """Pack a padded uint8[N, W] byte matrix into big-endian uint32[N, W/4] words.

    Unsigned word-wise lexicographic order == byte-wise lexicographic order because
    padding bytes are zero and any byte beats end-of-string (0 pad). Cuts lexsort
    passes by 4x vs per-byte keys.
    """
    n, w = data.shape
    pad_w = (-w) % 4
    if pad_w:
        data = jnp.pad(data, ((0, 0), (0, pad_w)))
        w += pad_w
    b = data.reshape(n, w // 4, 4).astype(jnp.uint32)
    return (b[..., 0] << 24) | (b[..., 1] << 16) | (b[..., 2] << 8) | b[..., 3]


class SortKey(NamedTuple):
    column: Column
    ascending: bool = True
    nulls_first: bool = True   # Spark default: NULLS FIRST for asc, NULLS LAST for desc


def _key_arrays_bits(key: SortKey) -> List[Tuple[jnp.ndarray, Optional[int]]]:
    """Most-significant-first (array, value_bit_width) pairs encoding one
    sort key. bit_width None marks float value keys (unpackable — they stay
    raw operands); small widths (1-bit null ranks, short string payloads)
    let pack_key_bits collapse whole key sets into one 32-bit sort lane,
    which is the difference between a seconds and a minutes sort compile."""
    col, asc = key.column, key.ascending
    encoded: List[Tuple[jnp.ndarray, Optional[int]]] = []
    if col.dtype == dt.STRING:
        W = int(col.data.shape[1])
        len_bits = max(1, (W + 1).bit_length())
        if W <= 3 and 8 * W + len_bits <= 32:
            # short strings: chars || length in ONE sub-32-bit value
            # (length low bits give the prefix tie-break directly)
            word = jnp.zeros(col.data.shape[0], jnp.uint32)
            for j in range(W):
                word = (word << jnp.uint32(8)) | col.data[:, j].astype(
                    jnp.uint32)
            word = (word << jnp.uint32(len_bits)) | col.lengths.astype(
                jnp.uint32)
            encoded.append((word, 8 * W + len_bits))
        else:
            words = pack_string_words(col.data, col.lengths)
            encoded += [(words[:, i], 32) for i in range(words.shape[1])]
            # length as final tie-break: zero padding is indistinguishable
            # from an embedded NUL in the word keys
            encoded.append((col.lengths.astype(jnp.uint32), len_bits))
        if not asc:
            encoded = [((a ^ jnp.uint32((1 << b) - 1)), b)
                       for a, b in encoded]
    else:
        for a in encode_orderable_words(col.data, col.dtype,
                                        descending=not asc):
            bw = _bit_width(a)
            encoded.append((a, bw))     # None for float value keys
    # null rank precedes value: 0 sorts before 1 (1-bit value)
    null_first = key.nulls_first
    null_rank = jnp.where(col.validity, jnp.uint8(1 if null_first else 0),
                          jnp.uint8(0 if null_first else 1))
    return [(null_rank, 1)] + encoded


def _key_arrays(key: SortKey) -> List[jnp.ndarray]:
    """Most-significant-first list of unsigned arrays encoding one sort key
    (unpacked form; mesh bound-comparison uses these directly)."""
    return [a for a, _b in _key_arrays_bits(key)]


def _bit_width(a: jnp.ndarray) -> Optional[int]:
    return {jnp.uint8: 8, jnp.uint16: 16, jnp.uint32: 32,
            jnp.uint64: 64}.get(a.dtype.type)


def pack_key_bits(items: List[Tuple[jnp.ndarray, Optional[int]]]
                  ) -> List[jnp.ndarray]:
    """Pack consecutive (array, bit_width) most-significant-first keys into
    uint32 lanes (earlier keys in higher bits), preserving lexicographic
    order while collapsing the sort operand count.

    Why: XLA's variadic-sort comparator compile time grows steeply with
    operand count (~15-30s PER 32-bit operand on both the CPU and TPU
    backends measured here), so a 7-operand lexsort costs minutes to
    compile. A groupby on two short string keys plus null/pad ranks fits in
    ONE packed lane. 32-bit lanes (not 64) because 64-bit integers are
    emulated on TPU under the x64 rewrite — a u64 comparator costs two u32
    comparators anyway. Values wider than 32 bits (and float value keys,
    width None) pass through as raw operands."""
    out: List[jnp.ndarray] = []
    cur: Optional[jnp.ndarray] = None
    used = 0
    for a, bits in items:
        if bits is None or bits > 32:
            if cur is not None:
                out.append(cur)
                cur, used = None, 0
            out.append(a)
            continue
        aa = a.astype(jnp.uint32)
        if cur is None:
            cur, used = aa, bits
        elif used + bits <= 32:
            cur = (cur << jnp.uint32(bits)) | aa
            used += bits
        else:
            out.append(cur)
            cur, used = aa, bits
    if cur is not None:
        out.append(cur)
    return out


def sort_indices(keys: Sequence[SortKey], num_rows, capacity: int,
                 live_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Stable permutation ordering live rows by the keys; padding rows go last.

    cuDF analog: ``Table.orderBy`` (used by GpuSortExec, GpuSortExec.scala:33-105).
    ``num_rows`` may be a python int or a traced device scalar.
    ``live_mask`` marks live rows explicitly (already padding-masked):
    folded-filter consumers rank filtered-out rows last INSTEAD of
    physically compacting first — compaction's scatter is the slowest
    primitive on TPU, the sort is nearly free.
    """
    return _lexsort_passes(_encode_keys(keys, num_rows, capacity, live_mask))


@stage("key_encode")
def _encode_keys(keys: Sequence[SortKey], num_rows, capacity: int,
                 live_mask: Optional[jnp.ndarray]) -> List[jnp.ndarray]:
    """The keys as packed orderable lanes, most significant first, led by
    the rank that sends padding (and masked-out) rows last."""
    if live_mask is not None:
        pad_rank = (~live_mask).astype(jnp.uint8)
    else:
        pad_rank = (jnp.arange(capacity) >= num_rows).astype(jnp.uint8)
    msf: List[Tuple[jnp.ndarray, Optional[int]]] = [(pad_rank, 1)]
    for key in keys:
        msf.extend(_key_arrays_bits(key))
    return pack_key_bits(msf)


def _sort_pass(lane: jnp.ndarray, perm: jnp.ndarray) -> jnp.ndarray:
    """One stable pass: reorder ``perm`` by ``lane`` (read through it)."""
    _, perm = jax.lax.sort((lane[perm], perm), num_keys=1, is_stable=True)
    return perm


def _split_uint64_lanes(lanes_msf: Sequence[jnp.ndarray]
                        ) -> List[jnp.ndarray]:
    """uint64 lanes as two uint32 lanes each, most significant first."""
    lanes: List[jnp.ndarray] = []
    for a in lanes_msf:
        if a.dtype == jnp.uint64:
            lanes += [(a >> jnp.uint64(32)).astype(jnp.uint32),
                      a.astype(jnp.uint32)]
        else:
            lanes.append(a)
    return lanes


@stage("lexsort")
def _lexsort_passes(lanes_msf: List[jnp.ndarray]) -> jnp.ndarray:
    """Stable int32 permutation ordering rows by most-significant-first key
    lanes — ``jnp.lexsort`` done as least-significant-first PASSES of one
    two-operand stable sort (key lane, permutation), the uint32 lanes
    walked by a ``fori_loop`` so the program holds ONE sort instruction
    however many lanes there are.

    Why not one variadic sort: the TPU compiler's cost for a sort grows
    steeply with its operand count — a 6-lane lexsort of 64 Ki rows took
    it 270 s on the chip (q3's partial group-by) where this loop takes
    ~20 s — and with every large sort at 30-100 s, the operand count was
    what made a cold query take twenty minutes. Same permutation: stable
    LSD passes ARE the lexicographic order.

    uint64 lanes (values wider than 32 bits) split into two uint32 lanes
    first — the TPU emulates 64-bit compares as two 32-bit ones anyway;
    float lanes (unpackable value keys) get a pass of their own."""
    lanes = _split_uint64_lanes(lanes_msf)
    perm = jnp.arange(lanes[0].shape[0], dtype=jnp.int32)
    hi = len(lanes)
    while hi > 0:                        # least-significant lane first
        lo = hi
        while lo > 0 and lanes[lo - 1].dtype == jnp.uint32:
            lo -= 1
        if hi - lo > 1:                  # a run of uint32 lanes: one loop
            run = jnp.stack(lanes[lo:hi])
            k = hi - lo
            perm = jax.lax.fori_loop(
                0, k, lambda t, p, run=run, k=k: _sort_pass(
                    jax.lax.dynamic_index_in_dim(run, k - 1 - t, 0,
                                                 keepdims=False), p), perm)
            hi = lo
        else:
            perm = _sort_pass(lanes[hi - 1], perm)
            hi -= 1
    return perm


@stage("lexsort")
def lexsort_carrying(lanes_msf: Sequence[jnp.ndarray], payload: jnp.ndarray
                     ) -> Tuple[List[jnp.ndarray], jnp.ndarray]:
    """Rows stably ordered by most-significant-first key lanes, as the
    SORTED lanes themselves (uint64 lanes split in two) and ``payload``
    carried along: for a caller that reads the keys in sorted order.

    The same least-significant-first passes as ``_lexsort_passes``, but
    each pass sorts one lane as the key and CARRIES the others as operands
    where that one reads ``lane[perm]``: a full-size gather costs the chip
    three sorts (60 ms against 21.6 ms for 8 Mi rows, ``tpch_sf1.q1``,
    ledger PR 29). Single-key passes and not one variadic sort, for the
    compiler's sake again: the two-key, three-operand sort of 10 Mi rows
    that an int64 join key needs took it 134-161 s cold on the chip's host,
    a single-key pass carrying the same lanes 52 s (PERF.md §6, PR 30).
    Called eagerly, the passes over equal dtypes share one program."""
    lanes = _split_uint64_lanes(lanes_msf)
    for t in reversed(range(len(lanes))):          # least significant first
        key, *rest = jax.lax.sort(
            (lanes[t], *lanes[:t], *lanes[t + 1:], payload),
            num_keys=1, is_stable=True)
        lanes, payload = rest[:t] + [key] + rest[t:-1], rest[-1]
    return lanes, payload


# ---------------------------------------------------------------------------
# Gather / compaction / slicing
# ---------------------------------------------------------------------------

@stage("gather")
def gather_column(col: Column, indices: jnp.ndarray,
                  out_valid: Optional[jnp.ndarray] = None) -> Column:
    """Row gather; ``out_valid`` additionally masks output rows (False => null+zero).

    cuDF analog: ``Table.gather``. Out-of-range indices must not occur (clip upstream).
    """
    from ..columnar.column import StructColumn
    validity = col.validity[indices]
    if out_valid is not None:
        validity = validity & out_valid
    if isinstance(col, StructColumn):
        kids = [gather_column(c, indices, out_valid=out_valid)
                for c in col.children]
        return StructColumn(col.dtype, kids, validity)
    if col.dtype.var_width:
        keep = out_valid if out_valid is not None else None
        data = col.data[indices]
        lengths = col.lengths[indices]
        evalid = (col.elem_validity[indices]
                  if col.elem_validity is not None else None)
        if keep is not None:
            data = jnp.where(keep[:, None], data,
                             jnp.zeros((), data.dtype))
            lengths = jnp.where(keep, lengths, jnp.int32(0))
            if evalid is not None:
                evalid = evalid & keep[:, None]
        return Column(col.dtype, data, validity, lengths, evalid)
    data = col.data[indices]
    if out_valid is not None:
        data = jnp.where(out_valid, data, jnp.zeros((), data.dtype))
    return Column(col.dtype, data, validity)


def compaction_indices(keep: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(perm, count): stable order with kept rows first. keep must be False
    on padding.

    Sort-free: cumsum ranks each row within its class (kept/dropped), one
    scatter inverts the position map. An XLA sort here would cost both a
    pathological comparator compile (tens of seconds per sort instance on
    some backends) and O(n log n) runtime for what is an O(n) operation.
    """
    n = keep.shape[0]
    n_keep = jnp.sum(keep).astype(jnp.int32)
    pos_keep = jnp.cumsum(keep).astype(jnp.int32) - 1
    pos_drop = n_keep + jnp.cumsum(~keep).astype(jnp.int32) - 1
    pos = jnp.where(keep, pos_keep, pos_drop)
    perm = jnp.zeros(n, jnp.int32).at[pos].set(
        jnp.arange(n, dtype=jnp.int32))
    return perm, n_keep


@stage("compact")
def compact_columns(cols: Sequence[Column], keep: jnp.ndarray
                    ) -> Tuple[List[Column], jnp.ndarray]:
    """Filter: keep rows where ``keep`` is True, compacted to the front.

    cuDF analog: ``Table.filter`` (GpuFilter helper, basicPhysicalOperators.scala:98-130).
    Returns same-capacity columns + device row count; caller syncs/rebuckets at a
    host boundary (DESIGN.md "dynamic-size protocol").
    """
    perm, count = compaction_indices(keep)
    live = jnp.arange(keep.shape[0]) < count
    return [gather_column(c, perm, out_valid=live) for c in cols], count


def slice_column(col: Column, start: int, out_capacity: int, length) -> Column:
    """Contiguous slice [start, start+length) into a fresh capacity (host-known start)."""
    idx = jnp.clip(jnp.arange(out_capacity) + start, 0, col.capacity - 1)
    live = jnp.arange(out_capacity) < length
    return gather_column(col, idx, out_valid=live)


def concat_columns(cols: Sequence[Column], counts: Sequence[int],
                   out_capacity: int) -> Column:
    """Concatenate same-dtype columns into one of out_capacity rows.

    cuDF analog: ``Table.concatenate`` (GpuCoalesceBatches.scala:132-702). Host-known
    counts (this runs at batch-coalesce boundaries, not inside fused stages).
    """
    from ..columnar.column import StructColumn
    dtype = cols[0].dtype
    if isinstance(cols[0], StructColumn):
        total = sum(counts)
        pad = out_capacity - total
        valids = [c.validity[:n] for c, n in zip(cols, counts)]
        if pad:
            valids.append(jnp.zeros(pad, jnp.bool_))
        kids = [concat_columns([c.children[k] for c in cols], counts,
                               out_capacity)
                for k in range(len(cols[0].children))]
        return StructColumn(dtype, kids, jnp.concatenate(valids))
    if dtype.var_width:
        width = max(int(c.data.shape[1]) for c in cols)
        has_ev = cols[0].elem_validity is not None
        datas, valids, lens, evs = [], [], [], []
        for c, n in zip(cols, counts):
            d = c.data[:n]
            if d.shape[1] < width:
                d = jnp.pad(d, ((0, 0), (0, width - d.shape[1])))
            datas.append(d)
            valids.append(c.validity[:n])
            lens.append(c.lengths[:n])
            if has_ev:
                e = c.elem_validity[:n]
                if e.shape[1] < width:
                    e = jnp.pad(e, ((0, 0), (0, width - e.shape[1])))
                evs.append(e)
        total = sum(counts)
        pad = out_capacity - total
        data = jnp.concatenate(datas + ([jnp.zeros((pad, width), datas[0].dtype)] if pad else []))
        valid = jnp.concatenate(valids + ([jnp.zeros(pad, jnp.bool_)] if pad else []))
        lengths = jnp.concatenate(lens + ([jnp.zeros(pad, jnp.int32)] if pad else []))
        evalid = None
        if has_ev:
            evalid = jnp.concatenate(
                evs + ([jnp.zeros((pad, width), jnp.bool_)] if pad else []))
        return Column(dtype, data, valid, lengths, evalid)
    datas = [c.data[:n] for c, n in zip(cols, counts)]
    valids = [c.validity[:n] for c, n in zip(cols, counts)]
    total = sum(counts)
    pad = out_capacity - total
    if pad:
        datas.append(jnp.zeros(pad, datas[0].dtype))
        valids.append(jnp.zeros(pad, jnp.bool_))
    return Column(dtype, jnp.concatenate(datas), jnp.concatenate(valids))


def rebucket_column(col: Column, num_rows: int, new_capacity: int) -> Column:
    """Grow/shrink capacity around the first num_rows rows (host-known count)."""
    return slice_column(col, 0, new_capacity, num_rows)


# ---------------------------------------------------------------------------
# Segment utilities (groupby/window building blocks)
# ---------------------------------------------------------------------------

@stage("segment_starts")
def segment_starts_from_sorted_keys(key_cols: Sequence[Column], num_rows,
                                    capacity: int) -> jnp.ndarray:
    """Bool[cap]: True where row i starts a new group in key-sorted data.

    NULL keys compare equal to each other (Spark groupby semantics). Padding rows
    are never starts.
    """
    live = jnp.arange(capacity) < num_rows
    is_start = live & (jnp.arange(capacity) == 0)
    changed = jnp.zeros(capacity, dtype=jnp.bool_)
    for col in key_cols:
        prev_valid = jnp.concatenate([col.validity[:1], col.validity[:-1]])
        vdiff = col.validity != prev_valid
        if col.dtype == dt.STRING:
            prev_d = jnp.concatenate([col.data[:1], col.data[:-1]])
            ddiff = jnp.any(col.data != prev_d, axis=1)
            prev_l = jnp.concatenate([col.lengths[:1], col.lengths[:-1]])
            ddiff = ddiff | (col.lengths != prev_l)
        else:
            prev_d = jnp.concatenate([col.data[:1], col.data[:-1]])
            if col.dtype.is_floating:
                # NaN == NaN for grouping (Spark normalizes)
                both_nan = jnp.isnan(col.data) & jnp.isnan(prev_d)
                ddiff = (col.data != prev_d) & ~both_nan
            else:
                ddiff = col.data != prev_d
        # data diff only matters when both rows valid
        changed = changed | vdiff | (ddiff & col.validity & prev_valid)
    idx = jnp.arange(capacity)
    return is_start | (live & (idx > 0) & changed)


@stage("segment_starts")
def segment_ids(starts: jnp.ndarray) -> jnp.ndarray:
    """Int32[cap] group id per row from group-start flags (0-based; padding gets last id+)."""
    return (jnp.cumsum(starts.astype(jnp.int32)) - 1).astype(jnp.int32)


@stage("segment_ids_to_rows")
def segment_ids_by_row(seg_ids: jnp.ndarray,
                       order: jnp.ndarray) -> jnp.ndarray:
    """Int32[cap]: the group id of each row WHERE IT LIES, from the ids of
    the rows in sort order and the permutation that sorted them — one
    unique-index scatter (52 ms for 8 Mi rows on a v5e), so that a segment
    reduction can read its input columns unsorted."""
    return jnp.zeros_like(seg_ids).at[order].set(seg_ids, unique_indices=True)
