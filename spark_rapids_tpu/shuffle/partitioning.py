"""Partitioning strategies: hash / range / round-robin / single.

Reference: ``GpuPartitioning.scala:45-72`` (device slice + host copy paths),
``GpuHashPartitioning.scala`` (Murmur3-compatible device hash -> contiguous
split), ``GpuRangePartitioning.scala`` + ``GpuRangePartitioner`` (reservoir
sample bounds -> upper_bound search), ``GpuRoundRobinPartitioning.scala``,
``GpuSinglePartitioning.scala``.

Spark-compatible placement matters (golden-compare across engines), so the
hash path uses the bit-compatible Murmur3 from ops/hashing.py with Spark's
``pmod(hash, n)`` partition id."""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..columnar import dtypes as dt
from ..columnar.batch import ColumnarBatch
from ..columnar.column import Column, bucket
from ..ops import expressions as ex
from ..ops import kernels as K
from ..ops.hashing import murmur3_batch

# fused map-side split kernels, keyed by (num_partitions, cap, array
# signature): partition-id mask -> stable sort by partition -> gather of
# every payload array -> per-partition counts, ONE compiled program per
# shape class instead of a chain of eager dispatches per batch
_SPLIT_FN_CACHE: Dict[tuple, Any] = {}

# registered with the JIT map-pressure relief valve
# (exec/compile_cache.jit_map_guard): cached split programs pin loaded
# executables
from ..exec.compile_cache import register_program_cache as _rpc  # noqa: E402
_rpc(_SPLIT_FN_CACHE.clear)
del _rpc


def _fused_split_fn(num_partitions: int, cap: int, sig: tuple):
    """One jitted program: (pids, live, *arrays) -> (*sorted_arrays,
    counts). Rows sort stably by partition id (padding rows last), so
    partition p occupies rows [offsets[p], offsets[p]+counts[p])."""
    import jax
    from ..exec.tracing import shared_stage

    @shared_stage("shuffle_split")
    def fn(pids, live, *arrays):
        pids = jnp.where(live, pids, num_partitions)      # padding last
        order = jnp.argsort(pids, stable=True)
        sorted_arrays = [a[order] for a in arrays]
        counts = jnp.bincount(
            jnp.clip(pids, 0, num_partitions),
            length=num_partitions + 1)[:num_partitions]
        return tuple(sorted_arrays) + (counts.astype(jnp.int32),)
    # lint: naked-jit-ok map-side split builder: every call rides _split_kernel -> compile_cache.note_build (audited + persisted)
    return jax.jit(fn)


def _split_kernel(num_partitions: int, cap: int, arrays: List[jnp.ndarray]):
    sig = tuple((str(a.dtype), tuple(a.shape[1:])) for a in arrays)
    key = (num_partitions, cap, sig)
    fn = _SPLIT_FN_CACHE.get(key)
    if fn is None:
        if len(_SPLIT_FN_CACHE) > 256:
            _SPLIT_FN_CACHE.clear()  # lint: unguarded-ok idempotent jit cache: a racing refill rebuilds the same function
        # shuffle split compiles ride the recompile audit + persistent
        # compile cache like every _fused_fn program
        from ..exec import compile_cache as _cc
        wrap = _cc.note_build(("shuffle_split",) + key, "shuffle_split")
        fn = _SPLIT_FN_CACHE[key] = wrap(_fused_split_fn(num_partitions, cap, sig))  # lint: unguarded-ok idempotent jit cache: a racing refill rebuilds the same function
    return fn


class TpuPartitioner:
    num_partitions: int

    def partition_ids(self, batch: ColumnarBatch) -> jnp.ndarray:
        """int32[cap] partition id per row (live rows)."""
        raise NotImplementedError

    def split_deferred(self, batch: ColumnarBatch
                       ) -> Optional[Tuple[jnp.ndarray, Callable]]:
        """Device half of :meth:`split`, sizing readback deferred.

        Dispatches the fused split kernel (partition-id hash -> stable
        sort by partition -> counts) and returns ``(counts_device,
        make_pieces)`` WITHOUT reading the counts back: the caller parks
        ``counts_device`` in a :class:`~..exec.pipeline.PipelineWindow`
        so batch k+1's split dispatches before batch k's sizing lands,
        and calls ``make_pieces(host_counts)`` once resolved (``None``
        host counts re-read blocking — the window's degraded-resolve
        contract). Returns ``None`` when there is nothing to defer
        (empty batch / single partition): the caller should fall back to
        the blocking :meth:`split`, which is then readback-free."""
        if batch.num_rows == 0 or self.num_partitions == 1:
            return None
        from ..columnar.column import StructColumn
        cap = batch.capacity
        pids = self.partition_ids(batch)
        live = batch.row_mask()
        if any(isinstance(c, StructColumn) for c in batch.columns):
            # struct payloads have a nested array layout the flat fused
            # kernel cannot carry: sort+count eagerly, gather through the
            # struct-aware gather (rare path; exchanges over structs)
            pids_m = jnp.where(live, pids, self.num_partitions)
            order = jnp.argsort(pids_m, stable=True)
            counts = jnp.bincount(
                jnp.clip(pids_m, 0, self.num_partitions),
                length=self.num_partitions + 1
            )[:self.num_partitions].astype(jnp.int32)
            sorted_cols = [K.gather_column(c, order) for c in batch.columns]
        else:
            arrays = [a for c in batch.columns for a in c.arrays()]
            outs = _split_kernel(self.num_partitions, cap, arrays)(
                pids, live, *arrays)
            counts = outs[-1]
            sorted_cols = []
            i = 0
            for c in batch.columns:
                n = len(c.arrays())
                sorted_cols.append(Column(
                    c.dtype, outs[i], outs[i + 1],
                    outs[i + 2] if c.dtype.var_width else None,
                    outs[i + 3] if n == 4 else None))
                i += n

        def make_pieces(host_counts) -> List[ColumnarBatch]:
            if host_counts is None:      # degraded resolve: re-read
                from ..analysis.sync_audit import allowed_host_transfer
                with allowed_host_transfer("map-side split sizing"):
                    host_counts = np.asarray(counts)  # lint: host-sync-ok map-side split sizing: degraded-resolve fallback, one readback for this batch
            host_counts = np.asarray(host_counts).reshape(-1)
            out: List[ColumnarBatch] = []
            offset = 0
            for p in range(self.num_partitions):
                n = int(host_counts[p])
                if n == 0:
                    out.append(ColumnarBatch.empty(batch.schema))
                    continue
                pcap = bucket(n)
                cols = [K.slice_column(c, offset, pcap, n)
                        for c in sorted_cols]
                out.append(ColumnarBatch(batch.schema, cols, n))
                offset += n
            return out

        return counts, make_pieces

    def split(self, batch: ColumnarBatch) -> List[ColumnarBatch]:
        """Slice a batch into per-partition batches (contiguous_split analog:
        one stable sort by partition id + counted slices). Blocking form:
        the sizing readback resolves immediately — the pipelined map path
        uses :meth:`split_deferred` instead."""
        if batch.num_rows == 0:
            return [ColumnarBatch.empty(batch.schema)
                    for _ in range(self.num_partitions)]
        deferred = self.split_deferred(batch)
        if deferred is None:
            return [batch]                       # single partition
        counts, make_pieces = deferred
        from ..analysis.sync_audit import allowed_host_transfer
        with allowed_host_transfer("map-side split sizing"):
            host_counts = np.asarray(counts)  # lint: host-sync-ok map-side split sizing: one readback sizes every slice of this batch
        return make_pieces(host_counts)


class SinglePartitioner(TpuPartitioner):
    def __init__(self):
        self.num_partitions = 1

    def partition_ids(self, batch: ColumnarBatch) -> jnp.ndarray:
        return jnp.zeros(batch.capacity, dtype=jnp.int32)

    def split(self, batch: ColumnarBatch) -> List[ColumnarBatch]:
        return [batch]


class HashPartitioner(TpuPartitioner):
    """pmod(murmur3(keys, seed=42), n) — Spark HashPartitioning compatible."""

    def __init__(self, num_partitions: int, key_exprs: Sequence[ex.Expression]):
        self.num_partitions = num_partitions
        self.key_exprs = key_exprs

    def partition_ids(self, batch: ColumnarBatch) -> jnp.ndarray:
        cols = [ex.materialize(e.eval(batch), batch) for e in self.key_exprs]
        h = murmur3_batch(cols, batch.capacity)
        n = jnp.int32(self.num_partitions)
        return jnp.mod(jnp.mod(h, n) + n, n)


#: device round-robin index per (capacity, num_partitions, start%n):
#: rebuilding arange+mod per batch re-uploads/re-dispatches an array that
#: is a pure function of the shape class (the columnar/batch.py
#: ``_UNPACK_CACHE`` pattern applied to pick indices)
_RR_IDX_CACHE: Dict[Tuple[int, int, int], jnp.ndarray] = {}


class RoundRobinPartitioner(TpuPartitioner):
    def __init__(self, num_partitions: int, start: int = 0):
        self.num_partitions = num_partitions
        self.start = start

    def partition_ids(self, batch: ColumnarBatch) -> jnp.ndarray:
        key = (batch.capacity, self.num_partitions,
               self.start % self.num_partitions)
        idx = _RR_IDX_CACHE.get(key)
        if idx is None:
            if len(_RR_IDX_CACHE) > 256:
                _RR_IDX_CACHE.clear()  # lint: unguarded-ok idempotent device-constant cache: a racing refill recomputes the same array
            idx = jnp.mod(
                jnp.arange(batch.capacity, dtype=jnp.int32) + key[2],
                self.num_partitions)
            _RR_IDX_CACHE[key] = idx  # lint: unguarded-ok idempotent device-constant cache: a racing refill recomputes the same array
        return idx


class RangePartitioner(TpuPartitioner):
    """Sample-based range partitioning (GpuRangePartitioner: reservoir sample
    -> sorted bounds -> device upper_bound). Bounds are computed host-side
    from a sample; ids via searchsorted on the encoded sort keys."""

    def __init__(self, num_partitions: int, orders: List, sample_batches):
        from ..plan.logical import SortOrder
        self.num_partitions = num_partitions
        self.orders = orders
        self._bounds: Optional[List[ColumnarBatch]] = None
        self._sample = sample_batches

    def _compute_bounds(self, batch_schema) -> ColumnarBatch:
        """Collect sample rows, sort, pick n-1 evenly spaced bound rows."""
        from ..plan.physical import concat_batches
        sample = concat_batches(batch_schema, list(self._sample))
        cap = sample.capacity
        keys = []
        for o in self.orders:
            c = ex.materialize(o.child.eval(sample), sample)
            keys.append(K.SortKey(c, o.ascending, o.nulls_first))
        order = K.sort_indices(keys, sample.num_rows, cap)
        cols = [K.gather_column(c, order) for c in sample.columns]
        n = sample.num_rows
        k = self.num_partitions
        if n == 0 or k <= 1:
            return None
        picks = [min(n - 1, max(0, (i + 1) * n // k)) for i in range(k - 1)]
        idx = jnp.asarray(picks, dtype=jnp.int32)
        bcols = [K.gather_column(c, idx,
                                 out_valid=jnp.ones(len(picks), jnp.bool_))
                 for c in cols]
        return ColumnarBatch(sample.schema, bcols, len(picks))

    def partition_ids(self, batch: ColumnarBatch) -> jnp.ndarray:
        if self._bounds is None:
            self._bounds = self._compute_bounds(batch.schema) or "empty"
        if self._bounds == "empty":
            return jnp.zeros(batch.capacity, dtype=jnp.int32)
        bounds = self._bounds
        # rank rows against bound rows with the join machinery's word compare
        from ..ops.joins import _lex_cmp
        row_words, bound_words = self._encode(batch), self._encode(bounds)
        # Spark RangePartitioning.getPartition: advance while key > bound, so
        # pid = count of bounds strictly less than the row's key
        pid = jnp.zeros(batch.capacity, dtype=jnp.int32)
        for bi in range(bounds.num_rows):
            bw = [jnp.broadcast_to(w[bi], (batch.capacity,))
                  for w in bound_words]
            blt, _beq = _lex_cmp(bw, row_words)   # bound < row
            pid = pid + blt.astype(jnp.int32)
        return jnp.clip(pid, 0, self.num_partitions - 1)

    def _encode(self, batch: ColumnarBatch):
        words: List[jnp.ndarray] = []
        for o in self.orders:
            c = ex.materialize(o.child.eval(batch), batch)
            arrs = K._key_arrays(K.SortKey(c, o.ascending, o.nulls_first))
            # floats in _key_arrays stay as floats; bitcast like joins do
            import jax
            for w in arrs:
                if w.dtype.kind == "f":
                    bits = jax.lax.bitcast_convert_type(
                        w.astype(jnp.float32), jnp.uint32)
                    sign = bits >> 31
                    w = jnp.where(sign == 1, ~bits, bits | jnp.uint32(0x80000000))
                words.append(w)
        return words
