"""Sort-merge equality join kernels: the cuDF join analog, TPU-first.

Reference: per-shim ``GpuHashJoin.scala:29-296`` drives cuDF hash joins
(``Table.onColumns(...).leftJoin/innerJoin``); the plugin replaces Spark's
sort-merge join with hash join. Here we invert (DESIGN.md §3): TPU has no device
hash tables but sorts fast, so all equality joins are sort-merge:

  1. lexsort the BUILD side by its keys (order-preserving unsigned encodings)
  2. a merge rank gives, per STREAM row, the contiguous range [lo, hi) of
     matching build rows: ONE stable sort of the build's and the stream's
     encoded keys together (``_merge_bounds``). Not a binary search: that
     gathers every build word once a round, 23 rounds for an 8 Mi-row
     build, and the chip gathers ~85 M elements a second where it sorts
     8 Mi two-operand rows in 22 ms (the ledger's PR 29 breakdowns: the
     search was 21.4 of ``tpch_sf1_mesh4.q3``'s 38.4 s a query)
  3. a prefix-sum over match counts + gather expands the pairs into output rows

Two-phase dynamic-size protocol (DESIGN.md): ``join_match`` returns the device
total pair count; the host reads it, buckets an output capacity, and calls
``join_gather`` — the same cadence as cuDF's size-returning join calls. The
exec layer PIPELINES the two phases (exec/pipeline.PipelineWindow): match
dispatches for batches k+1..k+depth before batch k's size scalar resolves,
and sizes land in batched readbacks, so the per-batch device->host round
trip overlaps compute instead of serializing the stream. To keep the
dispatch half sync-free, every ``n_build``/``n_stream`` argument here
accepts a python int OR a device int scalar (all consumers are jnp ops).

SQL semantics: NULL keys never match (null-aware anti join is handled at the
exec level); Spark float semantics make NaN == NaN for joins, which the
encoded-words equality gives us for free (all NaN encode identically).
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar import dtypes as dt
from ..columnar.column import Column
from ..exec.tracing import stage
from . import kernels as K


def _widen_string(col: Column, width: int) -> Column:
    """Zero-pad a string column's byte matrix to ``width`` (order-preserving)."""
    cur = col.data.shape[1]
    if cur >= width:
        return col
    data = jnp.pad(col.data, ((0, 0), (0, width - cur)))
    return Column(col.dtype, data, col.validity, col.lengths)


def _key_words(cols: Sequence[Column]):
    """All key columns' sort-key words as one most-significant-first list of
    ``(array, bit_width)`` pairs, plus the row-is-usable (all keys non-NULL)
    mask.

    EXACTLY the encoding ``sort_indices`` sorts by (``_key_arrays_bits``:
    null-rank word + value words), so the merge's lexicographic order is
    the build side's sorted order, NULL rows included (they sort first and
    carry zeroed data words). Word equality == SQL join-key equality for
    usable rows: NaNs unified by the NaN-rank word, f64 compared at full
    precision, -0.0 == 0.0 once ``_merge_bounds`` has folded the zeros.
    """
    words = []
    usable = None
    for c in cols:
        words.extend(K._key_arrays_bits(K.SortKey(c)))
        usable = c.validity if usable is None else (usable & c.validity)
    return words, usable


def _lex_cmp(a_words: List[jnp.ndarray], b_words: List[jnp.ndarray]):
    """(a < b, a == b) elementwise lexicographic over word lists."""
    lt = jnp.zeros(a_words[0].shape, dtype=jnp.bool_)
    eq = jnp.ones(a_words[0].shape, dtype=jnp.bool_)
    for a, b in zip(a_words, b_words):
        lt = lt | (eq & (a < b))
        eq = eq & (a == b)
    return lt, eq


def _merge_bounds(build_words, n_build, probe_words
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(lo, hi)`` per probe row: the range of the SORTED build keys equal
    to it, ``numpy.searchsorted`` ``"left"`` / ``"right"`` over the first
    ``n_build`` build rows (the rows behind them are +infinity).

    A merge rank: ONE stable sort of build and probe keys together reads
    every key once, where a binary search gathers every build word once a
    round (module docstring, step 2). Build rows stand first in the
    concatenation, so on equal keys a build row precedes a probe row; a
    probe row's ``hi`` is then the number of live build rows before it,
    and its ``lo`` that number as it stood where its run of equal keys
    began. The build's padding rows count for nothing wherever their
    words send them: they are no live build row, and inside a run they
    carry the run's key.
    """
    cap_b = build_words[0][0].shape[0]
    # a host row count would be a literal of a jitted caller: one program
    # per count, built anew for every data set. As an array it is an operand
    n_build = jnp.asarray(n_build, dtype=jnp.int32)
    words = []
    for (b, bits), (p, _) in zip(build_words, probe_words):
        w = jnp.concatenate([b, p])
        if bits is None:
            # a float value: the sort would put every -0.0 before every
            # 0.0, SQL calls them equal
            w = jnp.where(w == 0, jnp.zeros((), w.dtype), w)
        words.append((w, bits))
    src = jnp.arange(words[0][0].shape[0], dtype=jnp.int32)
    lanes, src = K.lexsort_carrying(K.pack_key_bits(words), src)
    is_build = src < n_build                     # a LIVE build row
    below = jnp.cumsum(is_build, dtype=jnp.int32)   # live build rows <= here
    differs = lanes[0][1:] != lanes[0][:-1]
    for w in lanes[1:]:
        differs = differs | (w[1:] != w[:-1])
    run_start = jnp.concatenate([jnp.ones(1, dtype=jnp.bool_), differs])
    # live build rows before the run: never decreases, so the run's first
    # value rides along it as a running maximum
    lo = jax.lax.cummax(jnp.where(run_start, below - is_build, 0))
    # back to probe order: the probe rows are the last of the concatenation
    # (two scatters by ``src`` cost three times this sort on the chip)
    _, lo, hi = jax.lax.sort((src, lo, below), num_keys=1, is_stable=False)
    return lo[cap_b:], hi[cap_b:]


class JoinMatch(NamedTuple):
    lo: jnp.ndarray            # int32[stream_cap] first matching build row
    count: jnp.ndarray         # int32[stream_cap] matches per stream row
    build_order: jnp.ndarray   # int32[build_cap] sort permutation of build side
    total_pairs: jnp.ndarray   # int32 scalar: sum of counts
    build_matched: jnp.ndarray  # bool[build_cap] (in sorted order) build row matched


def join_match(build_keys: Sequence[Column], n_build,
               stream_keys: Sequence[Column], n_stream,
               stream_capacity: int) -> JoinMatch:
    """Phase 1: sort build side, find per-stream-row match ranges + counts."""
    build_cap = build_keys[0].capacity
    # string key pairs must encode to the same number of words: widen both
    # sides' byte matrices to the pair's max padded width (order-preserving)
    build_keys = list(build_keys)
    stream_keys = list(stream_keys)
    for i, (b, s) in enumerate(zip(build_keys, stream_keys)):
        if b.dtype == dt.STRING and s.dtype == dt.STRING:
            width = max(b.data.shape[1], s.data.shape[1])
            build_keys[i] = _widen_string(b, width)
            stream_keys[i] = _widen_string(s, width)
    order = K.sort_indices([K.SortKey(c) for c in build_keys], n_build, build_cap)
    sorted_build = [K.gather_column(c, order) for c in build_keys]
    return _probe_sorted(sorted_build, order, n_build, build_cap,
                         stream_keys, n_stream, stream_capacity)


@stage("join_probe")
def _probe_sorted(sorted_build: Sequence[Column], order, n_build,
                  build_cap: int, stream_keys: Sequence[Column], n_stream,
                  stream_capacity: int) -> JoinMatch:
    """The probe half of :func:`join_match`: rank every stream key among
    the sorted build keys."""
    b_words, b_usable = _key_words(sorted_build)
    s_words, s_usable = _key_words(stream_keys)
    lo, hi = _merge_bounds(b_words, n_build, s_words)

    s_live = jnp.arange(stream_capacity) < n_stream
    ok = s_usable & s_live
    count = jnp.where(ok, hi - lo, 0).astype(jnp.int32)
    # null build rows sort first (nulls_first) and can only match null probes,
    # which `ok` already excludes; but guard against usable-build mismatch
    b_live = jnp.arange(build_cap) < n_build
    # mark matched build rows: +1 at lo, -1 at hi, prefix sum > 0
    delta = jnp.zeros(build_cap + 1, dtype=jnp.int32)
    add = jnp.where(ok, 1, 0)
    delta = delta.at[jnp.clip(lo, 0, build_cap)].add(add)
    delta = delta.at[jnp.clip(hi, 0, build_cap)].add(-add)
    covered = jnp.cumsum(delta[:-1]) > 0
    build_matched = covered & b_live & b_usable
    total = jnp.sum(count).astype(jnp.int32)
    return JoinMatch(lo, count, order, total, build_matched)


def _expand_indices(m: JoinMatch, out_capacity: int
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(stream_idx, build_sorted_idx, live) for each of out_capacity output slots."""
    cum = jnp.cumsum(m.count)                    # inclusive
    starts = cum - m.count                       # exclusive prefix
    out_i = jnp.arange(out_capacity, dtype=jnp.int32)
    live = out_i < m.total_pairs
    # which stream row does output slot i belong to: the first j with
    # cum[j] > i, which is how many j have cum[j] <= i. The slots are an
    # arange, so that is a histogram of cum summed up to i: no search
    ends = jnp.zeros(out_capacity, dtype=jnp.int32).at[cum].add(
        1, mode="drop", indices_are_sorted=True)
    stream_idx = jnp.clip(jnp.cumsum(ends), 0, m.count.shape[0] - 1)
    offset = out_i - starts[stream_idx]
    build_sorted_idx = m.lo[stream_idx] + offset
    return stream_idx, build_sorted_idx, live


@stage("join_gather")
def join_gather(m: JoinMatch, stream_cols: Sequence[Column],
                build_cols: Sequence[Column], out_capacity: int,
                join_type: str = "inner", n_stream=None,
                ) -> Tuple[List[Column], List[Column], jnp.ndarray]:
    """Phase 2: expand matches into output columns at a host-chosen capacity.

    join_type:
      inner       — matched pairs only
      left        — + unmatched stream rows with NULL build columns
      left_semi   — stream rows with >=1 match (stream columns only)
      left_anti   — stream rows with 0 matches (stream columns only)
    Right joins are planned as left joins with sides swapped (the reference does
    the same remap, GpuHashJoin.scala:112-132). full outer = left + the
    unmatched build rows appended (exec layer composes it via
    ``unmatched_build_gather``).
    Returns (stream output cols, build output cols, device row count).
    """
    stream_cap = m.count.shape[0]
    if join_type in ("left_semi", "left_anti"):
        s_live = jnp.arange(stream_cap) < n_stream
        keep = (m.count > 0) if join_type == "left_semi" else \
            ((m.count == 0) & s_live)
        keep = keep & s_live
        perm, cnt = K.compaction_indices(keep)
        live = jnp.arange(stream_cap) < cnt
        out = [K.gather_column(c, perm, out_valid=live) for c in stream_cols]
        return out, [], cnt

    if join_type == "left":
        # every stream row emits max(count, 1) rows; the padded row carries
        # NULL build columns
        count = jnp.where(jnp.arange(stream_cap) < n_stream,
                          jnp.maximum(m.count, 1), 0).astype(jnp.int32)
        matched = m.count > 0
        m2 = m._replace(count=count, total_pairs=jnp.sum(count).astype(jnp.int32))
        stream_idx, build_sorted_idx, live = _expand_indices(m2, out_capacity)
        row_matched = matched[stream_idx]
        s_out = [K.gather_column(c, stream_idx, out_valid=live)
                 for c in stream_cols]
        bidx = m.build_order[jnp.clip(build_sorted_idx, 0,
                                      m.build_order.shape[0] - 1)]
        b_valid = live & row_matched
        b_out = [K.gather_column(c, bidx, out_valid=b_valid) for c in build_cols]
        return s_out, b_out, m2.total_pairs

    # inner
    stream_idx, build_sorted_idx, live = _expand_indices(m, out_capacity)
    s_out = [K.gather_column(c, stream_idx, out_valid=live) for c in stream_cols]
    bidx = m.build_order[jnp.clip(build_sorted_idx, 0, m.build_order.shape[0] - 1)]
    b_out = [K.gather_column(c, bidx, out_valid=live) for c in build_cols]
    return s_out, b_out, m.total_pairs


@stage("join_gather")
def unmatched_build_gather(m: JoinMatch, build_cols: Sequence[Column], n_build
                           ) -> Tuple[List[Column], jnp.ndarray]:
    """Build rows with no stream match, compacted (for FULL OUTER composition).
    Note: NULL-key build rows count as unmatched (full outer emits them)."""
    build_cap = m.build_order.shape[0]
    b_live = jnp.arange(build_cap) < n_build
    keep_sorted = b_live & ~m.build_matched
    # back to original row order indices
    perm, cnt = K.compaction_indices(keep_sorted)
    orig_idx = m.build_order[perm]
    live = jnp.arange(build_cap) < cnt
    out = [K.gather_column(c, orig_idx, out_valid=live) for c in build_cols]
    return out, cnt


@stage("join_gather")
def cross_join_gather(left_cols: Sequence[Column], n_left,
                      right_cols: Sequence[Column], n_right,
                      out_capacity: int
                      ) -> Tuple[List[Column], List[Column], jnp.ndarray]:
    """Cartesian product (GpuCartesianProductExec / BroadcastNestedLoop analog):
    output slot i -> (left i // n_right, right i % n_right)."""
    out_i = jnp.arange(out_capacity, dtype=jnp.int64)
    total = (jnp.asarray(n_left, jnp.int64) * jnp.asarray(n_right, jnp.int64)
             ).astype(jnp.int32)
    live = out_i < total
    nr = jnp.maximum(jnp.asarray(n_right, jnp.int64), 1)
    li = jnp.clip((out_i // nr).astype(jnp.int32), 0,
                  left_cols[0].capacity - 1 if left_cols else 0)
    ri = jnp.clip((out_i % nr).astype(jnp.int32), 0,
                  right_cols[0].capacity - 1 if right_cols else 0)
    l_out = [K.gather_column(c, li, out_valid=live) for c in left_cols]
    r_out = [K.gather_column(c, ri, out_valid=live) for c in right_cols]
    return l_out, r_out, total
