"""Physical columnar operators: the GpuExec layer.

Reference: ``GpuExec.scala:65-96`` (base trait + metrics),
``basicPhysicalOperators.scala`` (project/filter/range/union/coalesce),
``aggregate.scala:305-560`` (hash aggregate pipeline), ``GpuSortExec.scala``,
per-shim ``GpuHashJoin.scala`` (build-side single batch + stream loop),
``limit.scala``, ``GpuExpandExec.scala``, ``GpuCoalesceBatches.scala``.

Execution model: an exec's ``execute()`` returns a list of partitions, each a
generator of ``ColumnarBatch``. Single-process here; the shuffle layer
(shuffle/) exchanges partitions between stages, and parallel/ runs the same
operators SPMD over a device mesh. Expressions are bound to child output
ordinals before eval (GpuBindReferences analog).

Dynamic-size protocol (DESIGN.md): shrink/grow ops read the device count at
batch boundaries and rebucket lazily via CoalesceGoal targets.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..analysis.contracts import exec_contract
from ..columnar import dtypes as dt
from ..columnar.batch import ColumnarBatch
from ..columnar.column import Column, Scalar, bucket
from ..ops import expressions as ex
from ..ops import kernels as K
from ..ops import aggregates as agg_k
from ..ops import joins as join_k
from ..exec.tracing import (host_site, operator_scope, shared_stage,
                            trace_span)
from . import logical as lp

Partition = Iterator[ColumnarBatch]


# ---------------------------------------------------------------------------
# Reference binding (GpuBindReferences / GpuBoundAttribute.scala)
# ---------------------------------------------------------------------------

def bind_refs(e: ex.Expression, schema: dt.Schema) -> ex.Expression:
    def fn(node):
        if isinstance(node, ex.ColumnRef):
            i = schema.index_of(node.col_name)
            f = schema[i]
            return ex.BoundReference(i, f.dtype, f.nullable, f.name)
        return None
    return e.transform(fn)


# ---------------------------------------------------------------------------
# Metrics (GpuMetricNames, GpuExec.scala:27-56)
# ---------------------------------------------------------------------------

@host_site("count_arg")
def _dev_count(batch) -> "Any":
    """A batch's row count as a device int32 scalar for a fused-program
    argument — WITHOUT forcing a host sync when the count is still
    device-resident (lazy counts ride the stream; see ColumnarBatch)."""
    import jax.numpy as jnp
    nr = batch.num_rows_raw
    if isinstance(nr, int):
        return jnp.int32(nr)
    if getattr(nr, "dtype", None) == jnp.int32:
        return nr
    return nr.astype(jnp.int32)


# The metrics bag + per-exec attribution live in exec/metrics.py; the
# ``Metrics`` name stays importable from here for existing call sites.
from ..exec.metrics import TpuMetrics as Metrics, exec_metrics  # noqa: E402


# ---------------------------------------------------------------------------
# Exec base
# ---------------------------------------------------------------------------

class TpuExec:
    """Base physical operator (GpuExec trait analog).

    Every concrete subclass declares a ``CONTRACT``
    (:func:`..analysis.contracts.exec_contract`): how its output schema
    relates to its children and what distribution it produces. The
    project linter enforces the declaration exists; the plan-contract
    validator (``analysis/contracts.validate_plan``, run by the planner
    after every conversion) enforces it holds."""

    CONTRACT = None          # abstract base: concrete execs must declare
    METRICS = None           # abstract base: concrete execs must declare

    def __init__(self, *children: "TpuExec"):
        self.children = list(children)
        self.metrics = Metrics()
        # the owning operator's name rides the bag so cross-cutting
        # attribution (HBM watermark peaks, service/telemetry) can name
        # the exec that was innermost-open, not just charge its bag
        self.metrics.owner = type(self).__name__

    @property
    def schema(self) -> dt.Schema:
        raise NotImplementedError

    @property
    def name(self) -> str:
        return type(self).__name__

    @property
    def output_partitions(self) -> int:
        """Estimated number of output partitions (Spark outputPartitioning
        analog, reduced to a count): the planner uses this to decide when a
        two-phase aggregate / co-partitioned join / range-partitioned sort
        needs an exchange."""
        return self.children[0].output_partitions if self.children else 1

    def children_coalesce_goal(self, i: int):
        """Per-child batch goal (CoalesceGoal lattice,
        GpuCoalesceBatches.scala:117-130): None (no requirement), "target"
        (batches under the configured batch size are concatenated up to it
        and never past it; one already at it is handed on untouched), or
        "single" (RequireSingleBatch: the op needs the whole partition in
        one batch). The transition pass inserts TpuCoalesceBatchesExec
        accordingly."""
        return None

    def execute(self) -> List[Partition]:
        raise NotImplementedError

    def execute_collect(self) -> ColumnarBatch:
        """Materialize all partitions into one batch (driver collect).
        Partitions drain concurrently as tasks (Spark's task parallelism);
        accumulated results are spillable so N in-flight partitions cannot
        pin the whole dataset in HBM. Query-scoped state (broadcast builds,
        unread shuffle slices) is released afterwards."""
        from ..exec.tasks import run_partition_tasks

        try:
            # ``drain``, once a query: its own time is the pull through
            # the operators' iterators, between their spans (with several
            # partitions, the wait for the task pool)
            with trace_span("drain"):
                per_part = run_partition_tasks(
                    self.execute(), lambda pid, part: drain_spillable(part))
            with trace_span("collect_concat"):
                return concat_spillable(
                    self.schema, [s for lst in per_part for s in lst])
        finally:
            self.cleanup()

    def execute_collect_iter(self):
        """Streaming collect: yield ONE host batch per drained partition,
        in partition order, as each completes — the consumer sees first
        rows in first-partition time instead of whole-result time
        (``DataFrame.collect_iter``). Row content and order across the
        yielded batches are identical to :meth:`execute_collect`'s single
        concat. Cleanup runs when the stream is exhausted AND when the
        consumer closes it early (generator finally)."""
        from ..exec.tasks import stream_partition_tasks

        done = object()
        drains = stream_partition_tasks(
            self.execute(), lambda pid, part: drain_spillable(part))
        try:
            while True:
                # ``drain``, once a partition: the wait for its task (the
                # consumer's time between two batches is outside it)
                with trace_span("drain"):
                    spillables = next(drains, done)
                if spillables is done:
                    break
                if not spillables:
                    continue
                with trace_span("collect_concat"):
                    yield concat_spillable(self.schema, spillables)
        finally:
            drains.close()
            self.cleanup()

    def cleanup(self) -> None:
        """Release query-scoped resources tree-wide after the final drain
        (the reference ties these to task/stage completion listeners)."""
        self._cleanup()
        for c in self.children:
            c.cleanup()

    def _cleanup(self) -> None:
        pass

    def subtree_deterministic(self) -> bool:
        """False when any expression below draws per-execution state (Rand,
        monotonically_increasing_id): re-executing such a subtree yields
        different rows, so shuffle stage-retry must recompute ALL reduce
        partitions (Spark's indeterminate-stage rule)."""
        return self._node_deterministic() and all(
            c.subtree_deterministic() for c in self.children)

    def _node_deterministic(self) -> bool:
        from ..ops import expressions as _ex

        def flat_exprs(v):
            if isinstance(v, _ex.Expression):
                yield v
            elif isinstance(v, lp.SortOrder):
                yield v.child
            elif isinstance(v, (list, tuple)):
                for x in v:
                    yield from flat_exprs(x)

        for attr in ("exprs", "grouping", "aggregate_exprs", "condition",
                     "orders", "projections", "left_keys", "right_keys",
                     "generator", "pre_filter", "_pre_stage_exprs",
                     "window_exprs", "by"):
            v = getattr(self, attr, None)
            if v is None:
                continue
            for e in flat_exprs(v):
                if e.collect(lambda x: not x.side_effect_free):
                    return False
        # execs that carry a logical node/subtree (generate, write,
        # python-UDF wrappers, CPU fallback): walk the WHOLE subtree —
        # expressions() is per-node
        p = getattr(self, "plan", None)
        if p is not None and hasattr(p, "expressions"):
            stack = [p]
            while stack:
                node = stack.pop()
                for e in node.expressions():
                    if e.collect(lambda x: not x.side_effect_free):
                        return False
                stack.extend(getattr(node, "children", ()))
        return True

    def metrics_tree(self, with_path: bool = False) -> List[tuple]:
        """Per-exec metrics in plan-tree order: [(depth, node name,
        resolved metrics dict)] — the SQLMetrics-per-operator surface the
        reference renders in the Spark UI (GpuMetricNames,
        GpuExec.scala:27-56). ``with_path=True`` appends the root->node
        class-name path (the same format ``analysis/contracts`` keys its
        violations on) as a fourth element."""
        out: List[tuple] = []

        def walk(node, depth, path, idx=None):
            # path mirrors contracts.validate_plan: child ordinal included
            # so same-class siblings key different paths
            here = (f"{path}/{idx}.{type(node).__name__}" if path
                    else type(node).__name__)
            row = (depth, node._node_string(),
                   dict(node.metrics.resolve()))
            out.append(row + (here,) if with_path else row)
            for i, c in enumerate(node.children):
                walk(c, depth + 1, here, i)
        walk(self, 0, "")
        return out

    def metrics_lines(self, annotate: Optional[Callable] = None
                      ) -> List[str]:
        """Rendered metrics tree, one list entry per line: node name then
        its sorted metrics (floats rounded to 4). ``annotate(path)`` may
        return extra lines to attach under a node — EXPLAIN ANALYZE hangs
        plan-contract diagnostics there."""
        lines: List[str] = []
        for depth, name, m, path in self.metrics_tree(with_path=True):
            pad = "  " * depth
            lines.append(pad + name)
            for k in sorted(m):
                v = m[k]
                v = round(v, 4) if isinstance(v, float) else v
                lines.append(pad + f"  {k}: {v}")
            for extra in (annotate(path) if annotate is not None else ()):
                lines.append(pad + f"  {extra}")
        return lines

    def metrics_string(self) -> str:
        """The executed plan annotated with each operator's metrics."""
        return "\n".join(self.metrics_lines())

    def _tree_string(self, depth: int = 0) -> str:
        out = "  " * depth + self._node_string()
        for c in self.children:
            out += "\n" + c._tree_string(depth + 1)
        return out

    def _node_string(self) -> str:
        return self.name

    def __repr__(self):
        return self._tree_string()


def _prepare_stateful(exprs: List[ex.Expression], pid: int
                      ) -> Tuple[List[ex.Expression], List[ex.Expression]]:
    """Per-partition clone + bind of stateful expressions (Rand,
    monotonically_increasing_id, spark_partition_id): bound exprs are shared
    across partitions, so stateful nodes must be copied per partition and
    given their partition index (GpuRand / GpuMonotonicallyIncreasingID get
    this from TaskContext in the reference). Returns (exprs, stateful nodes);
    the caller calls ``advance(n_rows)`` on each node after every batch so
    per-row streams progress instead of replaying."""
    import copy
    if not any(e.collect(lambda x: not x.side_effect_free) for e in exprs):
        return exprs, []
    exprs = [copy.deepcopy(e) for e in exprs]
    stateful = [n for e in exprs
                for n in e.collect(lambda x: not x.side_effect_free)]
    for n in stateful:
        if hasattr(n, "partition_index"):
            n.partition_index = pid
    return exprs, [n for n in stateful if hasattr(n, "advance")]


@host_site("admission")
def _task_begin() -> None:
    """Device admission at task (partition evaluation) start: the semaphore
    bounds concurrently-executing device tasks. Ordering contract preserved
    from the reference (GpuSemaphore.scala:74-78): acquire after host-side
    input is ready, before device work. The semaphore itself records the
    wait-vs-hold split (span ``semaphore_wait`` / the span report's
    ``semaphoreHoldS``) — the NVTX-range analog of GpuSemaphore.scala:107,
    but separable into admission contention vs device occupancy."""
    from ..exec.device import TpuSemaphore
    TpuSemaphore.get().acquire_if_necessary()


@host_site("admission")
def _reserve(nbytes: int) -> None:
    """Admission-check ~nbytes of imminent device materialization against the
    spill catalog (DeviceMemoryEventHandler.onAllocFailure analog): spills
    lower-priority buffers until the allocation fits the budget."""
    from ..exec.spill import BufferCatalog
    BufferCatalog.get().reserve(nbytes)


def drain_spillable(part, acquire: bool = False
                    ) -> List["SpillableColumnarBatch"]:
    """Drain one partition into spillable handles, resolving device-resident
    row counts in chunked batched readbacks (one host round-trip per 8
    batches, not one per batch) and dropping empties. ``acquire=True``
    takes the task semaphore once the first batch exists (the reference's
    acquire-after-host-IO ordering, GpuSemaphore.scala:74-78)."""
    from ..columnar.batch import resolve_counts
    from ..exec.spill import BorrowedSpillableView, SpillableColumnarBatch
    out: List[SpillableColumnarBatch] = []
    chunk: List[ColumnarBatch] = []

    def spillable(b: ColumnarBatch):
        # batches served from the scan device cache are ALREADY registered;
        # borrow that registration instead of double-counting the HBM
        if b.origin is not None and not b.origin.closed:
            return BorrowedSpillableView(b.origin, b)
        return SpillableColumnarBatch(b)

    def flush(last: bool = False):
        if last and not out and len(chunk) == 1:
            # the whole partition is ONE batch (tight-aggregate queries):
            # registration keeps its count lazy, so skipping the resolve
            # here lets the final fetch read count + data in a single
            # round trip (each blocking readback costs a full RTT)
            out.append(spillable(chunk[0]))
            chunk.clear()
            return
        with trace_span("drain_resolve"):
            resolve_counts(chunk)      # one round-trip per chunk
        out.extend(spillable(b) for b in chunk if b.num_rows > 0)
        chunk.clear()

    first = True
    for b in part:
        if first and acquire:
            _task_begin()
            first = False
        if isinstance(b.num_rows_raw, int) and b.num_rows_raw == 0:
            continue
        chunk.append(b)
        if len(chunk) >= 8:
            flush()
    flush(last=True)
    return out


def accumulate_spillable(parts) -> List["SpillableColumnarBatch"]:
    """Drain partitions into spillable handles: accumulated build/sort inputs
    must not pin HBM while more batches stream in (SpillableColumnarBatch
    treatment of build sides, GpuShuffledHashJoinExec / GpuSortExec).
    Partitions drain concurrently as tasks."""
    from ..exec.tasks import run_partition_tasks

    parts = list(parts)
    per_part = run_partition_tasks(parts, lambda pid, p: drain_spillable(p))
    return [s for lst in per_part for s in lst]


def concat_spillable(schema: dt.Schema,
                     spillables: List["SpillableColumnarBatch"],
                     by_capacity: bool = False) -> ColumnarBatch:
    """Materialize accumulated spillables and concatenate, reserving device
    room for inputs + output first. ``by_capacity``: the output's capacity
    class comes from the inputs' capacities, not from their row counts (a
    class that what a query's literals select cannot move)."""
    total = sum(s.size_bytes for s in spillables)
    _reserve(2 * total)
    batches = [s.get_batch() for s in spillables]
    for s in spillables:
        s.close()
    if by_capacity and len(batches) > 1:
        return concat_batches(schema, batches, target_capacity=bucket(
            sum(b.capacity for b in batches)))
    return concat_batches(schema, batches)


def concat_batches(schema: dt.Schema, batches: List[ColumnarBatch],
                   target_capacity: Optional[int] = None) -> ColumnarBatch:
    """Concatenate batches in ONE fused device program (GpuCoalesceBatches
    concat path). The eager per-column form dispatched 2-3 dynamic-slice
    programs per column per batch — hundreds of tiny executions per merge
    cycle, the dominant steady-state cost on dispatch-latency-bound links.
    The fused program takes every batch's arrays + row counts (device
    scalars welcome) and emits the packed output columns."""
    from ..columnar.batch import resolve_counts
    batches = [b for b in batches
               if not (isinstance(b.num_rows_raw, int)
                       and b.num_rows_raw == 0)]
    if not batches:
        return ColumnarBatch.empty(schema)
    if len(batches) == 1 and target_capacity is None:
        return batches[0]
    if target_capacity is None:
        resolve_counts(batches)          # one batched readback
        batches = [b for b in batches if b.num_rows > 0]
        if not batches:
            return ColumnarBatch.empty(schema)
        if len(batches) == 1:
            return batches[0]
        cap = bucket(sum(b.num_rows for b in batches))
    else:
        cap = target_capacity
    return _concat_fused(schema, batches, cap)


def _concat_fused(schema: dt.Schema, batches: List[ColumnarBatch],
                  out_cap: int) -> ColumnarBatch:
    """Generic over the FLAT-ARRAY protocol (Column.arrays /
    build_column): every storage array is either rows[cap] or a row
    matrix [cap, W]; concat row-stacks each position independently and
    zeroes the output padding — so strings, arrays (+ element validity),
    maps, and struct-of-columns all concat through one fused program."""
    import jax
    import jax.numpy as jnp

    nb = len(batches)
    caps = tuple(b.capacity for b in batches)
    max_cap = max(caps)
    flats_per_batch = [b.flat_arrays() for b in batches]
    n_arr = len(flats_per_batch[0])
    two_d = tuple(flats_per_batch[0][ai].ndim == 2 for ai in range(n_arr))
    # static padded width per array position (inputs may differ)
    widths = tuple(
        max(int(fb[ai].shape[1]) for fb in flats_per_batch)
        if two_d[ai] else 0 for ai in range(n_arr))
    # NO donation at this funnel: concat is called with batches whose
    # provenance it cannot know (range-partitioner bound samples, UDF
    # rebatch pendings, coalesce accumulations) and several callers
    # legitimately re-read their inputs — the exec-stream ownership
    # argument that justifies FusedStage/aggregate donation does not
    # hold here
    sig = ("concat", _schema_sig(schema), caps, widths, out_cap)

    def build():
        @shared_stage("concat")
        def fn(*args):
            counts = args[:nb]
            flats = args[nb:]
            per_batch = [flats[bi * n_arr:(bi + 1) * n_arr]
                         for bi in range(nb)]
            offs = []
            total = jnp.int32(0)
            for bi in range(nb):
                offs.append(total)
                total = total + counts[bi].astype(jnp.int32)
            live = jnp.arange(out_cap) < total
            ext = out_cap + max_cap    # updates never clamp (see below)
            out_arrays = []
            for ai in range(n_arr):
                W = widths[ai]
                src0 = per_batch[0][ai]
                buf = (jnp.zeros((ext, W), src0.dtype) if two_d[ai]
                       else jnp.zeros(ext, src0.dtype))
                # forward order: batch i+1's block starts exactly at
                # offs[i]+counts[i], overwriting batch i's padding tail;
                # the extended operand keeps dynamic_update_slice from
                # clamping starts (offs[bi] <= out_cap, cap_bi <= max_cap)
                for bi in range(nb):
                    a = per_batch[bi][ai]
                    if two_d[ai] and a.shape[1] < W:
                        a = jnp.pad(a, ((0, 0), (0, W - a.shape[1])))
                    buf = jax.lax.dynamic_update_slice(
                        buf, a, (offs[bi], jnp.int32(0)) if two_d[ai]
                        else (offs[bi],))
                # clip to out_cap and zero the padding (batch invariant:
                # bools -> False, so validity masks fold in too)
                buf = buf[:out_cap]
                buf = jnp.where(live[:, None] if two_d[ai] else live,
                                buf, jnp.zeros((), buf.dtype))
                out_arrays.append(buf)
            return tuple(out_arrays) + (total,)
        return jax.jit(fn)

    fn = _fused_fn(sig, build)
    args = [_dev_count(b) for b in batches]
    for fb in flats_per_batch:
        args.extend(fb)
    outs = fn(*args)
    total_host = sum(b.num_rows_raw for b in batches) \
        if all(isinstance(b.num_rows_raw, int) for b in batches) else outs[-1]
    return ColumnarBatch.from_flat_arrays(schema, list(outs[:-1]), total_host)


# ---------------------------------------------------------------------------
# Whole-stage fusion (DESIGN.md §2; the TPU analog of codegen stages)
# ---------------------------------------------------------------------------
#
# Eager evaluation dispatches every jnp op as its own compiled program —
# hundreds of device round-trips per batch, the dominant engine cost (each
# expression node is a separate kernel launch, exactly the fusion gap
# SURVEY.md §3.3 calls out in the reference's per-expression JNI launches).
# A fused stage traces the WHOLE per-batch computation once per shape:
# one device call per batch.

def _fusion_enabled(node) -> bool:
    flag = getattr(node, "_fusion", None)
    if flag is not None:
        return flag
    from .. import config as cfg
    with host_site("conf_read"):
        return bool(cfg.TpuConf().get(cfg.WHOLESTAGE_FUSION))


# Fused programs cache GLOBALLY on (expression structure, schema dtypes,
# shapes): repeated queries reuse compiled stages across exec instances —
# per-exec closures would force a recompile every query.
_FUSED_CACHE: Dict[tuple, Any] = {}
# Bound on retained programs. The old behavior cleared the WHOLE cache
# past the bound — the recompile audit measured the fallout as same-key
# REBUILDS (distinctShapes 0) on tpcds_q65 mid-corpus. Eviction now
# drops only the oldest half (dict preserves insertion order), so the
# working set survives; the bound itself stays moderate because every
# retained program pins an XLA CPU executable (JIT code mappings are a
# finite process resource, not just bytes — see the map-pressure relief
# valve in exec/compile_cache, which this cache registers with below).
_FUSED_CACHE_MAX = 512

from ..exec.compile_cache import register_program_cache as _rpc  # noqa: E402
_rpc(_FUSED_CACHE.clear)
del _rpc

# Cached fused programs must NOT close over an exec instance: the cache is
# process-global, so a captured exec would pin its whole plan tree (and any
# CachedScan owner) for the process lifetime. Trace-time helpers resolve the
# exec through this call-scoped THREAD-LOCAL stack instead (partition tasks
# run on pool threads, so concurrent drains of two aggregate execs must not
# see each other's exec); the cache key guarantees any exec seen here is
# structurally identical to the one the trace was built for, so a retrace
# under a different exec produces the same program.
_TRACE_TLS = __import__("threading").local()


def _trace_exec_stack() -> List[Any]:
    stack = getattr(_TRACE_TLS, "stack", None)
    if stack is None:
        stack = _TRACE_TLS.stack = []
    return stack


class _trace_exec:
    def __init__(self, node):
        self.node = node

    def __enter__(self):
        _trace_exec_stack().append(self.node)

    def __exit__(self, *exc):
        _trace_exec_stack().pop()


@host_site("program_lookup")
def _fused_fn(key: tuple, builder):
    from ..analysis import recompile as _recompile
    from ..exec import compile_cache as _cc
    fn = _FUSED_CACHE.get(key)
    if fn is None:
        if len(_FUSED_CACHE) > _FUSED_CACHE_MAX:
            for old in list(_FUSED_CACHE)[:_FUSED_CACHE_MAX // 2]:
                _FUSED_CACHE.pop(old, None)
        kernel = _recompile.kernel_of(key)
        # the program compiles under its family's name and counts its
        # own dispatches (exec/compile_cache.Program); the signature is
        # persisted for the next process
        fn = _FUSED_CACHE[key] = _cc.Program(builder(), kernel)
        _recompile.note_compile(kernel, key)
        _cc.record(key, kernel)
    else:
        # LRU touch (dict order = insertion order): eviction drops the
        # oldest half, so a hot program must not age by its build date.
        # The pop/reinsert pair is not atomic across task threads — the
        # worst case is a racing miss rebuilding one program, which the
        # audit then honestly counts.
        if _FUSED_CACHE.pop(key, None) is not None:
            _FUSED_CACHE[key] = fn
    return fn


def fused_cached(key: tuple) -> bool:
    """Whether a program for ``key`` is already resident — WITHOUT the
    LRU touch of a real :func:`_fused_fn` consult.
    The async compile pool's swap point: once its build lands here, the
    requesting stage's next batch takes the plain cache-hit path."""
    return key in _FUSED_CACHE


@host_site("flat_args")
def _donate_argnums(batch: ColumnarBatch, start: int) -> tuple:
    """jit argnums donating ``batch``'s flat arrays to a fused program
    that CONSUMES the batch (XLA reuses/frees the HBM eagerly), or ()
    when donation is off or unsafe. Safe only for exclusively-owned
    batches: scan-cache-served (``origin``) and catalog-acquired
    (``shared``) arrays are re-read later, and an array aliased into two
    argument slots cannot be donated twice. The donate bit must ride the
    fused-cache key — donation is baked into the compiled program."""
    from ..exec import compile_cache as _cc
    if not _cc.donate_enabled():
        return ()
    if batch.origin is not None or getattr(batch, "shared", False):
        return ()
    flat = batch.flat_arrays()
    seen = set()
    for a in flat:
        if id(a) in seen:
            return ()
        seen.add(id(a))
    return tuple(range(start, start + len(flat)))


def _donation_consumed(batch: ColumnarBatch) -> bool:
    """After a FAILED fused call: True when a donating execution already
    deleted the batch's buffers — the eager fallback cannot re-read them,
    so the caller must re-raise the real error instead of letting the
    fallback crash on 'Array has been deleted'. (Trace-time failures
    never execute, so donated inputs survive them and fallback stays
    available — the common fusion-fallback case.)"""
    try:
        return any(getattr(a, "is_deleted", lambda: False)()
                   for a in batch.flat_arrays())
    except Exception:
        return True


@host_site("flat_args")
def _note_donated(batch: ColumnarBatch, donate: tuple) -> None:
    """After a SUCCESSFUL donated fused invocation: tombstone ``batch``
    in the buffer-lifecycle ledger (analysis/ledger.py) — its arrays are
    dead, and a later read should diagnose as use-after-donate instead
    of surfacing jax's bare deleted-array error. No-op for the plain
    (un-donated) variant and when the ledger is off."""
    if donate:
        from ..analysis import ledger
        ledger.mark_donated(batch)


@host_site("program_key")
def _schema_sig(schema: dt.Schema) -> tuple:
    return tuple(f.dtype.name for f in schema)


def _expr_cache_key(e: ex.Expression, traced: frozenset = frozenset()):
    """Structural cache key covering every instance attribute (reprs alone
    are not faithful — e.g. Like's pattern is not in its repr). Returns None
    when an attribute is opaque (unkeyable): the stage then jits per-exec
    instead of sharing the global cache. ``traced``: ids of the string
    literals the caller's program takes as arguments
    (``ex.traced_literal_ids``): their value is no part of the program."""
    if id(e) in traced:
        return ("strarg", f"a{e.trace_pos}")
    if isinstance(e, ex.Parameter):
        # a traceable parameter's VALUE is a runtime argument, never part
        # of the compiled program: two plans differing only in bound
        # values share one fused signature (the zero-recompile serving
        # property, docs/plan_cache.md). Non-traceable (string) values
        # stay baked, so the value must ride the key.
        # slot stringified: it is an IDENTITY, not a shape — the
        # size-class audit flags raw non-pow2 ints >= 8 in keys as
        # bucket-discipline leaks (a 9th parameter is not a dimension)
        if e.slot < 0:
            # UNSLOTTED (never passed through plan_cache.parameterize):
            # two such params would collide on one key and share a stale
            # program — unkeyable forces per-exec compilation instead
            return None
        if e.traceable():
            return ("param", f"s{e.slot}", e.dtype.name)
        return ("param", f"s{e.slot}", e.dtype.name, repr(e.value))
    parts: list = [type(e).__name__]
    for k, v in sorted(vars(e).items()):
        if k == "children":
            continue
        if isinstance(v, ex.Expression):
            sub = _expr_cache_key(v, traced)
            if sub is None:
                return None
            parts.append((k, sub))
            continue
        r = repr(v)
        if " at 0x" in r:
            return None
        parts.append((k, r))
    for c in e.children:
        sub = _expr_cache_key(c, traced)
        if sub is None:
            return None
        parts.append(sub)
    return tuple(parts)


class FusedStage:
    """One jitted program evaluating bound expression trees over a batch.

    mode 'project': outputs = evaluated expression columns.
    mode 'filter':  single boolean expression; outputs = compacted input
    columns + device row count (the host syncs the count, as the eager
    path already does).

    Any trace failure (an expression doing host-side work despite its
    fusable flag) permanently falls back to eager for this stage.
    """

    def __init__(self, exprs: List[ex.Expression], in_schema: dt.Schema,
                 out_schema: dt.Schema, mode: str = "project"):
        self.exprs = exprs
        self.in_schema = in_schema
        self.out_schema = out_schema
        self.mode = mode
        self.broken = False
        # donate-bit -> jitted program: donation is baked into a compiled
        # program, and a stream can mix donatable (fresh) batches with
        # cache-served ones, so each stage holds up to two variants
        self._fns: Dict[bool, Any] = {}
        self._ekeys = None
        # query parameters inside the expressions (plan-cache
        # parameterization): their CURRENT values append to every program
        # call as extra traced scalars, in stamped trace_pos order
        self._params = ex.ordered_params(exprs)

    @staticmethod
    def maybe(node, exprs, in_schema, out_schema, stateful,
              mode: str = "project"):
        """A FusedStage when fusion applies: enabled, every tree fusable,
        and no stateful expressions (their host-side per-batch state would
        bake into the trace)."""
        if not _fusion_enabled(node):
            return None
        if stateful or not all(e.tree_fusable() for e in exprs):
            return None
        return FusedStage(exprs, in_schema, out_schema, mode)

    def _build(self, donate: tuple = ()):
        import jax

        def run_project(num_rows, *arrays):
            b = ColumnarBatch.from_flat_arrays(self.in_schema, arrays,
                                               num_rows)
            with operator_scope("TpuProjectExec"), jax.named_scope("project"):
                cols = [ex.materialize(e.eval(b), b) for e in self.exprs]
            return tuple(a for c in cols for a in c.arrays())

        def run_filter(num_rows, *arrays):
            b = ColumnarBatch.from_flat_arrays(self.in_schema, arrays,
                                               num_rows)
            with operator_scope("TpuFilterExec"):
                with jax.named_scope("filter"):
                    pred = self.exprs[0].eval(b)
                    if isinstance(pred, Scalar):   # constant predicate: eager
                        raise _ScalarPredicate()
                    keep = pred.data & pred.validity & b.row_mask()
                cols, count = K.compact_columns(b.columns, keep)
            return tuple(a for c in cols for a in c.arrays()) + (count,)

        return jax.jit(run_project if self.mode == "project"
                       else run_filter, donate_argnums=donate)

    def __call__(self, batch: ColumnarBatch):
        """project -> ColumnarBatch | filter -> (ColumnarBatch, count) |
        None on permanent fallback."""
        if self.broken:
            return None
        import jax.numpy as jnp
        from ..exec.tracing import trace_span
        try:
            from ..analysis import recompile as _recompile
            # consumed-batch donation (exec/compile_cache): the stage's
            # program frees/reuses the input column HBM on ingestion;
            # cache-served batches (origin/shared) keep the plain variant
            donate = _donate_argnums(batch, 1)
            fn = self._fns.get(bool(donate))
            if fn is None:
                if self._ekeys is None:
                    traced = ex.traced_literal_ids(self._params)
                    self._ekeys = [_expr_cache_key(e, traced)
                                   for e in self.exprs]
                ekeys = self._ekeys
                if any(k is None for k in ekeys):
                    # unkeyable: per-exec jit, same Program boundary
                    from ..exec.compile_cache import Program
                    kernel = f"fused_{self.mode}_unkeyable"
                    fn = Program(self._build(donate), kernel)
                    _recompile.note_compile(
                        kernel,
                        ("unkeyable", self.mode, id(self), bool(donate)))
                else:
                    key = (self.mode, _schema_sig(self.in_schema),
                           tuple(ekeys), ("donate", bool(donate)))
                    fn = _fused_fn(key, lambda: self._build(donate))
                self._fns[bool(donate)] = fn
            with trace_span(f"fused_{self.mode}"):
                outs = fn(_dev_count(batch),
                          *batch.flat_arrays(),
                          *ex.param_arg_values(self._params))
            _note_donated(batch, donate)
        except _ScalarPredicate:
            self.broken = True
            return None
        except Exception as e:
            if _donation_consumed(batch):
                raise          # executed-and-donated: no eager re-read
            # host-side expression slipped through the fusable gate
            import logging
            logging.getLogger("spark_rapids_tpu.fusion").warning(
                "whole-stage fusion fell back to eager for %s stage: %s",
                self.mode, e)
            self.broken = True
            return None
        if self.mode == "project":
            return ColumnarBatch.from_flat_arrays(self.out_schema,
                                                  list(outs),
                                                  batch.num_rows)
        # filter: compacted columns + device count (caller syncs)
        tmp = ColumnarBatch.from_flat_arrays(self.out_schema,
                                             list(outs[:-1]), 0)
        return tmp.columns, outs[-1]


class _ScalarPredicate(Exception):
    pass


# ---------------------------------------------------------------------------
# Leaves
# ---------------------------------------------------------------------------

class TpuLocalScanExec(TpuExec):
    """In-memory arrow table scan -> device batches (HostColumnarToGpu analog)."""

    CONTRACT = exec_contract(schema="defined", partitioning="source")
    METRICS = exec_metrics("scanTime", "cacheHitBatches", "uploadedBatches")

    def __init__(self, table, schema: dt.Schema, batch_rows: int = 1 << 20,
                 num_partitions: int = 1, base_data=None):
        super().__init__()
        self.table = table
        self._schema = schema
        self.batch_rows = batch_rows
        self.num_partitions = max(1, num_partitions)
        # stable identity for the device cache: the ORIGINAL registered
        # table when this scan is a pruned per-query view of it
        self.base_data = base_data if base_data is not None else table

    @property
    def schema(self):
        return self._schema

    @property
    def output_partitions(self) -> int:
        return self.num_partitions

    def execute(self) -> List[Partition]:
        n = self.table.num_rows
        per_part = max(1, -(-n // self.num_partitions))
        parts = []
        for p in range(self.num_partitions):
            lo = min(p * per_part, n)
            hi = min(lo + per_part, n)
            parts.append(self._part_iter(lo, hi))
        return parts

    # DEVICE cache for in-memory tables: arrow tables are immutable, so
    # each scan batch caches as a SPILLABLE device batch reusable across
    # query runs (the reference's InMemoryTableScan / cached-table path,
    # GpuInMemoryTableScanExec). Caching only the host-prepped numpy form
    # would re-upload every batch per run, and the host->device copy is
    # the scan's whole hot-path cost, so hits serve device-resident
    # columns. Entries key by the BASE
    # table identity + kept columns (pruning builds a fresh pa.Table per
    # query) and a weakref finalizer closes the handles when the base
    # table is collected; memory pressure spills entries through the
    # normal device->host->disk tiers, and a later hit re-promotes.
    _DEVICE_CACHE: Dict[tuple, dict] = {}
    _DEVICE_CACHE_MAX_BYTES = 6 << 30   # admission bound (spill tiers
    _device_cache_bytes = 0             # otherwise grow host/disk forever)
    _device_cache_lock = __import__("threading").Lock()

    @classmethod
    def _evict_table(cls, cache_key: tuple) -> None:
        # weakref-finalizer entry point: fires at an arbitrary bytecode,
        # possibly inside a frame HOLDING the cache/catalog/watermark
        # locks — taking them inline here self-deadlocks that thread
        # (exec/spill.defer_finalizer). Enqueue only; the next scan-cache
        # access or partition-task launch drains.
        from ..exec.spill import defer_finalizer
        defer_finalizer(cls._evict_table_now, cache_key)

    @classmethod
    def _evict_table_now(cls, cache_key: tuple) -> None:
        with cls._device_cache_lock:
            ent = cls._DEVICE_CACHE.pop(cache_key, None)
            if ent:
                cls._device_cache_bytes -= sum(
                    h.size_bytes for h in ent.values())
        for handle in (ent or {}).values():
            try:
                handle.close()
            except Exception:
                pass

    def _table_cache(self):
        import weakref
        from ..exec.spill import drain_deferred_finalizers
        drain_deferred_finalizers()
        cls = TpuLocalScanExec
        key = (id(self.base_data), tuple(self._schema.names()),
               self.batch_rows)
        with cls._device_cache_lock:
            ent = cls._DEVICE_CACHE.get(key)
            if ent is not None:
                return ent
            try:
                weakref.finalize(self.base_data, cls._evict_table, key)
            except TypeError:
                return None
            ent = cls._DEVICE_CACHE[key] = {}
            return ent

    def _part_iter(self, lo: int, hi: int) -> Partition:
        from ..exec.spill import (BufferLostError, CACHE_PRIORITY,
                                  SpillableColumnarBatch)
        from ..exec.tasks import prefetch_map

        def chunks():
            pos = lo
            while pos < hi:
                end = min(pos + self.batch_rows, hi)
                yield (pos, end - pos)
                pos = end

        cache = self._table_cache()

        def prep(item):
            pos, rows = item
            key = (pos, rows)
            if cache is not None:
                handle = cache.get(key)
                if handle is not None:
                    return ("cached", key, handle)
            return ("prep", key,
                    ColumnarBatch.prep_from_arrow(self.table.slice(pos,
                                                                   rows)))

        # HOST-side arrow->numpy conversion runs one batch ahead on a
        # background thread; the device upload stays on the task thread
        # BEHIND semaphore acquisition and memory admission, preserving the
        # ordering contract (GpuSemaphore.scala:74: acquire after host IO,
        # before device work)
        from ..exec.tracing import trace_span
        first = True
        for kind, key, payload in prefetch_map(chunks(), prep):
            if first:
                _task_begin()
                first = False
            batch = None
            if kind == "cached":
                # served by the device scan cache: nothing is uploaded
                with trace_span("scan_cached", self.metrics, "scanTime"):
                    try:
                        batch = payload.get_batch()
                        batch.origin = payload
                        self.metrics.inc("cacheHitBatches")
                    except BufferLostError:  # lint: recover-ok scan-cache miss repair: rebuilds the evicted device cache entry in place, no stage re-execution involved
                        # catalog was reset under us (tests do): rebuild
                        with TpuLocalScanExec._device_cache_lock:
                            if cache.get(key) is payload:
                                del cache[key]
                                TpuLocalScanExec._device_cache_bytes -= \
                                    payload.size_bytes
            if batch is None:
                with trace_span("scan_upload", self.metrics, "scanTime"):
                    prepped = payload if kind == "prep" else \
                        ColumnarBatch.prep_from_arrow(self.table.slice(*key))
                    nbytes = ColumnarBatch.prepped_size_bytes(prepped)
                    _reserve(nbytes)
                    batch = ColumnarBatch.upload_prepped(prepped)
                    self.metrics.inc("uploadedBatches")
                    cls = TpuLocalScanExec
                    if cache is not None and prepped[0] == "packed":
                        # budget check under the lock: concurrent tasks
                        # must not both pass a stale-byte admission test
                        handle = None
                        with cls._device_cache_lock:
                            if key not in cache and \
                                    cls._device_cache_bytes + nbytes <= \
                                    cls._DEVICE_CACHE_MAX_BYTES:
                                handle = SpillableColumnarBatch(
                                    batch, CACHE_PRIORITY)
                                cache[key] = handle
                                cls._device_cache_bytes += handle.size_bytes
                        if handle is not None:
                            batch.origin = handle
            self.metrics.inc("numOutputRows", batch.num_rows_raw)
            self.metrics.inc("numOutputBatches")
            yield batch


class TpuCachedScanExec(TpuExec):
    """Scan over a df.cache()-materialized spillable batch: the device (or
    re-promoted) columns serve directly, no host conversion or upload
    (GpuInMemoryTableScanExec, reference spark310 shim)."""

    CONTRACT = exec_contract(schema="defined", partitioning="single")
    METRICS = exec_metrics()

    def __init__(self, plan):
        super().__init__()
        self.plan = plan

    @property
    def schema(self):
        return self.plan.schema

    @property
    def output_partitions(self) -> int:
        return 1

    def execute(self) -> List[Partition]:
        def part():
            _task_begin()
            # no _reserve: a device-resident cached batch is already in
            # the catalog's accounting, and acquire_batch performs
            # admission itself when re-promoting a spilled one
            batch = self.plan.handle.get_batch()
            self.metrics.inc("numOutputRows", batch.num_rows_raw)
            self.metrics.inc("numOutputBatches")
            yield batch
        return [part()]

    # the handle is DataFrame-owned (released by unpersist/GC), never by
    # query-scoped cleanup

    def _node_string(self):
        return "TpuCachedScanExec"


class TpuRangeExec(TpuExec):
    """range() generated on device (GpuRangeExec, basicPhysicalOperators.scala:187)."""

    CONTRACT = exec_contract(schema="defined", partitioning="source")
    METRICS = exec_metrics()

    def __init__(self, start: int, end: int, step: int, num_partitions: int = 1,
                 batch_rows: int = 1 << 20):
        super().__init__()
        self.start, self.end, self.step = start, end, step
        self.num_partitions = max(1, num_partitions)
        self.batch_rows = batch_rows
        self._schema = dt.Schema([dt.Field("id", dt.INT64, nullable=False)])

    @property
    def schema(self):
        return self._schema

    @property
    def output_partitions(self) -> int:
        return self.num_partitions

    def execute(self) -> List[Partition]:
        import jax.numpy as jnp
        total = max(0, -(-(self.end - self.start) // self.step))
        per_part = max(1, -(-total // self.num_partitions))

        def part(p):
            base = p * per_part
            count = max(0, min(per_part, total - base))
            pos = 0
            while pos < count:
                take = min(self.batch_rows, count - pos)
                cap = bucket(take)
                idx = jnp.arange(cap, dtype=jnp.int64)
                vals = self.start + (base + pos + idx) * self.step
                live = idx < take
                col = Column(dt.INT64, jnp.where(live, vals, 0), live)
                self.metrics.inc("numOutputRows", take)
                yield ColumnarBatch(self._schema, [col], take)
                pos += take

        return [part(p) for p in range(self.num_partitions)]


# ---------------------------------------------------------------------------
# Project / Filter
# ---------------------------------------------------------------------------

class TpuProjectExec(TpuExec):
    """Columnar projection (GpuProjectExec, basicPhysicalOperators.scala:64)."""

    CONTRACT = exec_contract(schema="defined", partitioning="preserve",
                             bound={"exprs": 0})
    METRICS = exec_metrics()

    def __init__(self, child: TpuExec, exprs: List[ex.Expression]):
        super().__init__(child)
        self.exprs = [bind_refs(e, child.schema) for e in exprs]
        self._schema = dt.Schema([
            dt.Field(ex.output_name(e, i), e.dtype, e.nullable)
            for i, e in enumerate(exprs)])

    @property
    def schema(self):
        return self._schema

    def execute(self) -> List[Partition]:
        return [self._map(p, i)
                for i, p in enumerate(self.children[0].execute())]

    def _map(self, part: Partition, pid: int = 0) -> Partition:
        exprs, stateful = _prepare_stateful(self.exprs, pid)
        fused = FusedStage.maybe(self, exprs, self.children[0].schema,
                                 self._schema, stateful)
        for batch in part:
            with trace_span(f"op_{type(self).__name__}", self.metrics, "opTime"):
                out = fused(batch) if fused is not None else None
                if out is None:
                    cols = [ex.materialize(e.eval(batch), batch)
                            for e in exprs]
                    out = ColumnarBatch(self._schema, cols, batch.num_rows)
            for n in stateful:
                n.advance(batch.num_rows)
            self.metrics.inc("numOutputRows", out.num_rows_raw)
            self.metrics.inc("numOutputBatches")
            yield out


class TpuFilterExec(TpuExec):
    """Columnar filter via compaction (GpuFilterExec + GpuFilter helper,
    basicPhysicalOperators.scala:98-132). Device count read at the batch
    boundary per the dynamic-size protocol."""

    CONTRACT = exec_contract(schema="passthrough", partitioning="preserve",
                             bound={"condition": 0})
    METRICS = exec_metrics()

    def __init__(self, child: TpuExec, condition: ex.Expression):
        super().__init__(child)
        self.condition = bind_refs(condition, child.schema)
        self._schema = child.schema

    @property
    def schema(self):
        return self._schema

    def execute(self) -> List[Partition]:
        return [self._map(p, i)
                for i, p in enumerate(self.children[0].execute())]

    def _map(self, part: Partition, pid: int = 0) -> Partition:
        (condition,), stateful = _prepare_stateful([self.condition], pid)
        fused = FusedStage.maybe(self, [condition], self.children[0].schema,
                                 self._schema, stateful, mode="filter")
        for batch in part:
            with trace_span(f"op_{type(self).__name__}", self.metrics, "opTime"):
                if fused is not None:
                    res = fused(batch)
                    if res is not None:
                        cols, count = res
                        # the count stays device-resident (possibly-empty
                        # batches flow through) so a filter never serializes
                        # the stream on a host readback
                        out = ColumnarBatch(self._schema, cols, count)
                        self.metrics.inc("numOutputRows", out.num_rows_raw)
                        self.metrics.inc("numOutputBatches")
                        yield out
                        continue
                pred = condition.eval(batch)
                for s in stateful:
                    s.advance(batch.num_rows)
                if isinstance(pred, Scalar):
                    if pred.value is True:
                        yield batch
                        continue
                    else:
                        continue
                keep = pred.data & pred.validity & batch.row_mask()
                cols, count = K.compact_columns(batch.columns, keep)
                out = ColumnarBatch(self._schema, cols, count)
                self.metrics.inc("numOutputRows", out.num_rows_raw)
                self.metrics.inc("numOutputBatches")
            yield out


class TpuCoalesceBatchesExec(TpuExec):
    """Bring small batches up to a goal (GpuCoalesceBatches). goal 'single'
    (RequireSingleBatch): the whole partition as one batch. goal 'target':
    ``target_rows`` is a CEILING, not a trigger. A batch already at or over
    it is handed on as it is (the same object, no program), and smaller
    ones are concatenated in runs that stop before they would pass it, so
    an output is larger than the target only where one input batch was.
    Row order within the partition is kept.

    Metrics: ``passedBatches`` (handed on untouched), ``concatBatches``
    (inputs that went into a run; a run of one costs no program either)
    and ``concatOutputs`` (runs); ``concatTime`` is round the runs only."""

    CONTRACT = exec_contract(schema="passthrough", partitioning="preserve")
    METRICS = exec_metrics("concatTime", "passedBatches", "concatBatches",
                           "concatOutputs")

    def __init__(self, child: TpuExec, goal: Any = "single",
                 target_rows: int = 1 << 22):
        super().__init__(child)
        self.goal = goal
        self.target_rows = target_rows

    @property
    def schema(self):
        return self.children[0].schema

    def execute(self) -> List[Partition]:
        return [self._map(p) for p in self.children[0].execute()]

    def _map(self, part: Partition) -> Partition:
        # accumulated batches are spillable while more stream in — raw device
        # batches must not pin a whole partition in HBM below sort/window
        # (the reference's GpuCoalesceBatches accumulates spillable batches).
        # Device-resident counts resolve in chunked batched readbacks (one
        # host round-trip per 8 batches) and the goal is then applied batch
        # by batch; while no such count is waiting, a host-known count (every
        # scan-cache batch) is judged as it arrives. Outputs yield
        # INCREMENTALLY so downstream consumes while upstream streams.
        from ..columnar.batch import resolve_counts
        from ..exec.spill import SpillableColumnarBatch
        to_target = self.goal != "single"
        pending: List[SpillableColumnarBatch] = []
        pending_rows = 0
        chunk: List[ColumnarBatch] = []
        lazy = False                 # a device-resident count is waiting

        def flush() -> Partition:
            nonlocal pending, pending_rows
            if not pending:
                return
            run, pending, pending_rows = pending, [], 0
            self.metrics.inc("concatBatches", len(run))
            self.metrics.inc("concatOutputs")
            with trace_span("concat", self.metrics, "concatTime"):
                out = concat_spillable(self.schema, run)
            yield out

        def admit() -> Partition:
            nonlocal pending_rows, lazy
            resolve_counts(chunk)        # one round-trip per chunk
            for b in chunk:
                n = b.num_rows
                if n == 0:
                    continue
                if to_target:
                    if n >= self.target_rows:
                        yield from flush()   # what came before goes first
                        self.metrics.inc("passedBatches")
                        yield b
                        continue
                    if pending_rows + n > self.target_rows:
                        yield from flush()
                pending.append(SpillableColumnarBatch(b))
                pending_rows += n
            chunk.clear()
            lazy = False

        for batch in part:
            if not isinstance(batch.num_rows_raw, int):
                lazy = True
            elif batch.num_rows_raw == 0:
                continue
            chunk.append(batch)
            if not lazy or len(chunk) >= 8:
                yield from admit()
        yield from admit()
        yield from flush()


# ---------------------------------------------------------------------------
# Aggregate
# ---------------------------------------------------------------------------

class TpuHashAggregateExec(TpuExec):
    """Sort-based group-by aggregate (GpuHashAggregateExec pipeline,
    aggregate.scala:305-560; decomposition per AggregateFunctions.scala).

    mode: 'complete' (this node sees all rows for its groups), 'partial'
    (update aggregation producing internal sum/count columns), or 'final'
    (merge partials + result projection). partial+final compose across a
    hash exchange exactly like the reference's two-phase planning.
    """

    CONTRACT = exec_contract(schema="defined", partitioning="defined",
                             extras=("agg_distribution",))
    METRICS = exec_metrics("computeAggTime", "aggFewGroupBatches",
                           "aggScatterBatches")

    def __init__(self, child: TpuExec, grouping: List[ex.Expression],
                 aggregate_exprs: List[ex.Expression], mode: str = "complete",
                 per_partition_final: bool = False,
                 pre_filter: Optional[ex.Expression] = None,
                 pre_stage=None):
        super().__init__(child)
        self.mode = mode
        # pre_stage: a whole filter/project CHAIN the stage compiler folded
        # into this aggregate (plan/stage_compiler.StageChain, bound along
        # the original operator chain): the update phase evaluates the
        # chain and compacts via live-row mask inside ITS OWN fused
        # program, eliminating the separate per-op programs + count syncs
        # per batch (the whole-stage scan->filter->project->partial-agg
        # pipeline; docs/fusion.md). ``pre_filter`` is the legacy
        # single-condition form and converts to a one-step chain.
        if pre_stage is None and pre_filter is not None:
            from .stage_compiler import chain_of_filter
            pre_stage = chain_of_filter(pre_filter, child.schema)
        self.pre_stage = pre_stage
        # back-compat view: the folded condition when the chain is exactly
        # one filter (planner tests and repr key off it)
        self.pre_filter = pre_filter
        if pre_filter is None and pre_stage is not None and \
                len(pre_stage.steps) == 1 and \
                pre_stage.steps[0][0] == "filter":
            self.pre_filter = pre_stage.steps[0][1]
        # deterministic-subtree walk sees the chain's expressions
        self._pre_stage_exprs = pre_stage.exprs() if pre_stage is not None \
            else None
        # per_partition_final: the planner guarantees the child is hash-
        # partitioned on the grouping keys (an exchange directly below), so
        # each partition's groups are disjoint and the final merge runs
        # per-partition instead of draining every partition into one stream
        # (the reference's HashClusteredDistribution requirement that the
        # exchange satisfies, aggregate.scala two-phase planning)
        self.per_partition_final = per_partition_final
        self.grouping_src = grouping
        self.aggregate_exprs = aggregate_exprs
        # collect aggregate leaves across output expressions
        self.leaves: List[lp.AggregateExpression] = []
        for e in aggregate_exprs:
            self.leaves.extend(
                e.collect(lambda x: isinstance(x, lp.AggregateExpression)))
        if mode == "final":
            # the child emits the internal partial schema: keys then update
            # cols, positionally — original names do not exist downstream
            self.grouping = [ex.BoundReference(i, g.dtype, True)
                             for i, g in enumerate(grouping)]
            self.bound_leaf_inputs = [None] * len(self.leaves)
        else:
            # with a folded pre_stage the agg's inputs are the CHAIN's
            # output rows, not the (now deeper) child's — bind against the
            # chain output schema
            in_schema = self.pre_stage.out_schema \
                if self.pre_stage is not None else child.schema
            self.grouping = [bind_refs(e, in_schema) for e in grouping]
            self.bound_leaf_inputs = [
                bind_refs(l.children[0], in_schema) if l.children else None
                for l in self.leaves]
        self._out_schema = dt.Schema([
            dt.Field(ex.output_name(e, i), e.dtype, e.nullable)
            for i, e in enumerate(aggregate_exprs)])
        if mode == "partial":
            self._out_schema = self._partial_schema()

    @host_site("shrink")
    def _partial_schema(self) -> dt.Schema:
        """Internal partial-form schema: key cols + per-leaf update cols
        (identical construction in the upstream partial and downstream final
        execs, so the exchange carries a consistent internal schema)."""
        fields = [dt.Field(f"_k{i}", g.dtype, True)
                  for i, g in enumerate(self.grouping_src)]
        for i, l in enumerate(self.leaves):
            for j, (op, t) in enumerate(self._update_cols(l)):
                fields.append(dt.Field(f"_a{i}_{j}", t, True))
        return dt.Schema(fields)

    def _update_cols(self, leaf: lp.AggregateExpression):
        """(op, dtype) pairs of the update-phase outputs for one aggregate
        (avg decomposes into sum+count, AggregateFunctions.scala avg)."""
        t = leaf.children[0].dtype if leaf.children else None
        if leaf.op == "avg":
            return [("sum", dt.FLOAT64), ("count", dt.INT64)]
        if leaf.op in ("count", "count_star"):
            return [(leaf.op, dt.INT64)]
        return [(leaf.op, agg_k.result_dtype(leaf.op, t))]

    @property
    def schema(self):
        return self._out_schema

    def children_coalesce_goal(self, i: int):
        # _stream_merge updates per batch. Batches UNDER the target batch
        # size are concatenated up to it first (each costs a dispatch of
        # the update program); a batch already at the target is passed as
        # it is: a copy of it would buy no dispatch back (the reference's
        # TargetSize)
        return "target"

    @property
    def output_partitions(self) -> int:
        if self.mode == "partial" or self.per_partition_final:
            return self.children[0].output_partitions
        return 1

    def execute(self) -> List[Partition]:
        parts = self.children[0].execute()
        if self.mode == "partial":
            # update-only aggregation is per-partition (upstream of the
            # hash exchange, like the reference's partial mode)
            return [self._stream_merge(p, project=False) for p in parts]
        if self.mode == "final" and self.per_partition_final:
            # child is hash-partitioned on the grouping keys: groups are
            # disjoint per partition, each merges independently (the
            # distributed reduce side)
            return [self._stream_merge(p, project=True) for p in parts]
        # complete/final must see every row of a group: all partitions feed
        # ONE streaming update+merge loop (aggregate.scala:427-485) whose
        # state is one spillable partial batch — never a concat of the input
        def stream():
            for p in parts:
                yield from p
        return [self._stream_merge(stream(), project=(self.mode != "partial"))]

    # -- streaming update + merge loop ---------------------------------------
    # pending update-phase partials accumulate (spillable) up to this many
    # before one merge pass: merging every batch would dispatch a merge
    # program per input batch; partials are tiny (bucket(n_groups)) so the
    # fan-in costs little memory and cuts merge dispatches ~MERGE_FAN_IN x
    MERGE_FAN_IN = 8

    def _stream_merge(self, batches, project: bool) -> Partition:
        """Per-batch update-agg; pending partials merge in fan-in groups
        (the reference's hot loop, aggregate.scala:427-485, with batched
        merge cadence). All state lives in the spill catalog between
        batches, so aggregation residency stays bounded.

        Each batch's partial goes through the shared deferred-scalar
        window (exec/pipeline.PipelineWindow — the primitive the join
        stream loop uses). No entry parks a scalar on it today (the whole
        kernel is dispatched with its count left on the device), so each
        lands as it is pushed; the one blocking read of the loop,
        ``_shrink_fused``'s count of a large partial, is what could ride
        the window's batched readback (ROADMAP, debts)."""
        from .. import config as cfg
        from ..exec.pipeline import PipelineWindow
        from ..exec.spill import SpillableColumnarBatch
        pschema = self._partial_schema()
        pending: List[SpillableColumnarBatch] = []

        def merge_pending() -> None:
            if len(pending) <= 1:
                return
            batches_ = []
            total = 0
            for s in pending:
                b = s.get_batch()
                total += b.device_size_bytes()
                batches_.append(b)
                s.close()
            pending.clear()
            _reserve(2 * total)
            merged_in = concat_batches(pschema, batches_)
            pending.append(SpillableColumnarBatch(
                self._merge_to_partial(merged_in)))

        def bank(pb: ColumnarBatch) -> None:
            pending.append(SpillableColumnarBatch(pb))
            if len(pending) >= self.MERGE_FAN_IN:
                merge_pending()

        with host_site("conf_read"):
            depth = max(1, int(cfg.TpuConf().get(cfg.AGG_PIPELINE_DEPTH)))
        # metrics=: the window's batched readbacks charge THIS exec's
        # hostSyncs (exec/metrics.exec_scope), not just the span string
        win = PipelineWindow(depth, metrics=self.metrics)
        for batch in batches:
            # the span covers ALL the host does for the batch: admission
            # and banking the partial too (the host sites of
            # exec/tracing.HOST_SITES lie inside it)
            with trace_span("aggregate", self.metrics, "computeAggTime"):
                # semaphore ordering contract: acquire only once the first
                # input batch exists (upstream host IO done),
                # GpuSemaphore.scala:74-78; the wait is the child span
                # ``semaphore_wait``
                _task_begin()
                _reserve(batch.device_size_bytes())
                if self.mode == "final":
                    ready = win.push(lambda b=batch: b)
                else:
                    tok = self._fused_dispatch(batch, "update")
                    if tok is None:
                        pb = self._update_partial_eager(batch)
                        ready = win.push(lambda p=pb: p)
                    else:
                        # whole kernel already dispatched, count
                        # device-resident — nothing to resolve
                        ready = win.push(
                            lambda t=tok: self._shrink_fused(*t))
                for pb in ready:
                    bank(pb)
        with trace_span("aggregate", self.metrics, "computeAggTime"):
            for pb in win.flush():
                bank(pb)
            merge_pending()
        if not pending:
            final_in = ColumnarBatch.empty(pschema)
        else:
            final_in = pending[0].get_batch()
            pending[0].close()
        if project:
            yield from self._final(final_in)
        else:
            self.metrics.inc("numOutputRows", final_in.num_rows_raw)
            yield final_in

    # -- update (per input batch) --------------------------------------------
    def _build_update_specs(self, batch: ColumnarBatch):
        keys = [ex.materialize(g.eval(batch), batch) for g in self.grouping]
        specs: List[agg_k.AggSpec] = []
        for leaf, bound in zip(self.leaves, self.bound_leaf_inputs):
            col = ex.materialize(bound.eval(batch), batch) \
                if bound is not None else None
            for (op, _t) in self._update_cols(leaf):
                if leaf.op == "avg":
                    import jax.numpy as jnp
                    c = col
                    if op == "sum" and c.dtype != dt.FLOAT64:
                        c = Column(dt.FLOAT64,
                                   c.data.astype(jnp.float64), c.validity)
                    specs.append(agg_k.AggSpec(op, c))
                else:
                    specs.append(agg_k.AggSpec(
                        op, col, ignore_nulls=leaf.ignore_nulls))
        return keys, specs

    def _update_partial_eager(self, batch: ColumnarBatch) -> ColumnarBatch:
        """Eager (per-op dispatch) update aggregation — the fallback when
        whole-stage fusion does not apply."""
        batch = self._apply_pre_stage_eager(batch)
        keys, specs = self._build_update_specs(batch)
        cap = batch.capacity
        if not self.grouping:
            aggs = agg_k.reduce_aggregate(specs, batch.num_rows, cap)
            return ColumnarBatch(self._partial_schema(), aggs, 1)
        out_keys, aggs, n_groups = self._groupby_eager(keys, specs, batch)
        return self._shrink_partial(
            ColumnarBatch(self._partial_schema(), out_keys + aggs, n_groups))

    def _groupby_eager(self, keys, specs, batch: ColumnarBatch):
        """The fused programs' kernel, called op by op on columns already
        evaluated: what the eager fallback protects against is an
        expression that will not trace, and the kernel always traces."""
        out_keys, aggs, ng = agg_k.groupby_aggregate(
            keys, specs, batch.num_rows, batch.capacity)
        return out_keys, aggs, int(ng)  # lint: host-sync-ok eager-path group-count sync sizes the output bucket

    def _shrink_partial(self, batch: ColumnarBatch) -> ColumnarBatch:
        """Compact a partial batch to bucket(n_groups) capacity: group-by
        outputs inherit the INPUT capacity, and carrying a million-slot
        batch holding six groups into the merge/final phases wastes memory
        and forces the downstream fused programs to compile at the huge
        capacity (compile cost grows steeply with shape on some backends)."""
        ncap = bucket(max(batch.num_rows, 1))
        if ncap >= batch.capacity:
            return batch
        cols = [K.rebucket_column(c, batch.num_rows, ncap)
                for c in batch.columns]
        return ColumnarBatch(batch.schema, cols, batch.num_rows)

    #: A fused phase's output of at most this many slots keeps its capacity
    #: and its device-resident count.
    SHRINK_ABOVE_SLOTS = 4096

    @host_site("shrink")
    def _shrink_fused(self, kind: str, pb: ColumnarBatch) -> ColumnarBatch:
        """A fused phase's output (``_fused_dispatch``'s token, spread),
        shrunk where it is large (a small one keeps its device-resident
        count: a shrink would force a blocking readback per cycle). Where
        the program chose between the masked and the scatter reductions on
        the device (``groupby_aggregate``), the count the shrink has just
        read says which it took."""
        if pb.capacity <= self.SHRINK_ABOVE_SLOTS:
            return pb
        pb = self._shrink_partial(pb)
        if kind == "sorted":
            if pb.num_rows <= agg_k.FEW_GROUPS_MAX:
                self.metrics.inc("aggFewGroupBatches")
            else:
                self.metrics.inc("aggScatterBatches")
        return pb

    def _apply_pre_stage_eager(self, batch: ColumnarBatch) -> ColumnarBatch:
        """Eager fallback of the folded filter/project chain (fused paths
        evaluate the chain inside their own traced programs)."""
        if self.pre_stage is None or batch.num_rows == 0:
            return batch
        return self.pre_stage.eval_eager(batch)

    def _stage_param_args(self) -> tuple:
        """Current values of the folded chain's query parameters — the
        extra traced scalars every UPDATE-phase fused program takes after
        the batch's flat arrays (merge/final programs never evaluate the
        chain, so they take none)."""
        if self.pre_stage is None or not self.pre_stage.params:
            return ()
        return ex.param_arg_values(self.pre_stage.params)

    def _traced_pre_stage(self, b: ColumnarBatch):
        """Folded-chain evaluation inside a fused trace: returns
        (post-chain batch, live-row mask or None). The mask replaces
        physical compaction — a scatter, the slowest TPU primitive — and
        the agg kernels rank/mask dead rows for free."""
        if self.pre_stage is None:
            return b, None
        return self.pre_stage.eval_traced(b)

    # -- whole-stage fused group-by (expression eval + kernel in ONE
    # device program per batch; see the fusion section above) ---------------
    @host_site("program_key")
    def _fusion_sig(self, phase: str, in_schema: dt.Schema):
        gk = [_expr_cache_key(g) for g in self.grouping]
        bk = [None if b is None else _expr_cache_key(b)
              for b in self.bound_leaf_inputs]
        if any(k is None for k in gk) or any(
                b is not None and k is None for b, k in
                zip(self.bound_leaf_inputs, bk)):
            return None
        return ("agg", phase, self.mode, tuple(gk), tuple(bk),
                tuple((l.op, l.ignore_nulls) for l in self.leaves),
                _schema_sig(in_schema))

    def _build_eval_fn(self, phase: str):
        # resolves the exec via the thread-local stack, NOT a captured
        # self: these closures end up inside globally-cached jitted
        # programs, and a strong self would leak the exec (+ its
        # CachedScan owners) forever
        def build_eval(b):
            # the folded filter/project CHAIN (pre_stage) evaluates inside
            # the traced program (update phase only: merge/final consume
            # already-filtered partials); its filters become a LIVE-ROW
            # MASK — physical compaction would cost a scatter, the slowest
            # TPU primitive, per batch, while the sort kernel ranks/masks
            # dead rows for free. Returns (keys, specs,
            # effective_row_count, live_mask); kernels must see the
            # POST-filter count or dead rows would join the NULL group,
            # and live_mask is None when the chain has no filter.
            node = _trace_exec_stack()[-1]
            n_eff = b.num_rows
            mask = None
            if phase == "update":
                # the folded operators scope themselves (StageChain)
                b, mask = node._traced_pre_stage(b)
                import jax
                with operator_scope(node):
                    if mask is not None:
                        import jax.numpy as jnp
                        with jax.named_scope("filter"):
                            n_eff = jnp.sum(mask).astype(jnp.int32)
                    with jax.named_scope("project"):
                        keys, specs = node._build_update_specs(b)
            else:
                keys, specs = node._merge_specs(b)
            return keys, specs, n_eff, mask
        return build_eval

    def _fused_dispatch(self, batch: ColumnarBatch, phase: str):
        """The fused phase: the whole kernel in ONE program, dispatched
        without any blocking sync (the group count stays on the device).
        Returns ``("done", partial)`` for a grouping-free reduction,
        ``("sorted", partial)`` for a group-by, or None -> eager."""
        if getattr(self, "_fusion_broken", False) or not _fusion_enabled(self):
            return None
        with host_site("fusable"):
            if not all(e.tree_fusable() for e in self.grouping) or any(
                    b is not None and not b.tree_fusable()
                    for b in self.bound_leaf_inputs):
                return None
            if self.pre_stage is not None and not self.pre_stage.fusable():
                return None
        import jax

        in_schema = batch.schema
        cap = batch.capacity
        sig = self._fusion_sig(phase, in_schema)
        if sig is None:
            return None
        if self.pre_stage is not None:
            skey = self.pre_stage.cache_key()
            if skey is None:
                return None
            sig = sig + ("pre_stage", skey)
        build_eval = self._build_eval_fn(phase)
        op = type(self).__name__       # the programs' operator scope
        pschema = self._partial_schema()
        # folded-chain query parameters ride ONLY the update-phase
        # programs (the chain evaluates there); current values append
        # after the flat arrays, positions baked by StageChain stamping
        pargs = self._stage_param_args() if phase == "update" else ()

        try:
            if not self.grouping:
                donate = _donate_argnums(batch, 1)

                def build_reduce():
                    def fn(num_rows, *arrays):
                        b = ColumnarBatch.from_flat_arrays(
                            in_schema, arrays, num_rows)
                        _keys, specs, n_eff, mask = build_eval(b)
                        with operator_scope(op):
                            aggs = agg_k.reduce_aggregate(specs, n_eff,
                                                          b.capacity,
                                                          live_mask=mask)
                        return tuple(a for c in aggs for a in c.arrays())
                    return jax.jit(fn, donate_argnums=donate)
                fn = _fused_fn(sig + ("reduce", cap,
                                      ("donate", bool(donate))),
                               build_reduce)
                with _trace_exec(self):
                    outs = fn(_dev_count(batch), *batch.flat_arrays(),
                              *pargs)
                _note_donated(batch, donate)
                return ("done", ColumnarBatch.from_flat_arrays(
                    pschema, list(outs), 1))

            return self._dispatch_plain_sort(batch, sig, in_schema, cap,
                                             build_eval, pargs)
        except Exception as e:
            if _donation_consumed(batch):
                raise          # executed-and-donated: no eager re-read
            import logging
            logging.getLogger("spark_rapids_tpu.fusion").warning(
                "fused %s group-by fell back to eager: %s", phase, e)
            self._fusion_broken = True
            return None

    def _dispatch_plain_sort(self, batch: ColumnarBatch, sig, in_schema, cap,
                             build_eval, pargs: tuple = ()):
        """Whole sort-based group-by in ONE dispatch, count left
        device-resident (no readback): ``groupby_aggregate``, which takes
        the masked or the scatter reductions by the group count it finds.
        Token ``sorted``, so that ``_shrink_fused`` can say which."""
        import jax
        pschema = self._partial_schema()
        donate = _donate_argnums(batch, 1)
        op = type(self).__name__

        def build_sort():
            def fn(num_rows, *arrays):
                b = ColumnarBatch.from_flat_arrays(in_schema, arrays,
                                                   num_rows)
                keys, specs, n_eff, mask = build_eval(b)
                with operator_scope(op):
                    ok, oa, ng = agg_k.groupby_aggregate(
                        keys, specs, n_eff, b.capacity, live_mask=mask)
                flat = [a for c in ok + oa for a in c.arrays()]
                return tuple(flat) + (ng,)
            return jax.jit(fn, donate_argnums=donate)
        fn = _fused_fn(sig + ("sort", cap, ("donate", bool(donate))),
                       build_sort)
        with _trace_exec(self):
            outs = fn(_dev_count(batch), *batch.flat_arrays(), *pargs)
        _note_donated(batch, donate)
        pb = ColumnarBatch.from_flat_arrays(pschema, list(outs[:-1]),
                                            outs[-1])
        return ("sorted", pb)

    # -- final (merge partials) ---------------------------------------------
    def _merge_ops(self, leaf: lp.AggregateExpression):
        if leaf.op == "avg":
            return ["sum", "sum"]
        if leaf.op in ("count", "count_star"):
            return ["sum"]
        return [leaf.op]

    def _merge_specs(self, batch: ColumnarBatch):
        nk = len(self.grouping_src)
        keys = list(batch.columns[:nk])
        specs: List[agg_k.AggSpec] = []
        ci = nk
        for leaf in self.leaves:
            for op in self._merge_ops(leaf):
                specs.append(agg_k.AggSpec(op, batch.columns[ci],
                                           ignore_nulls=leaf.ignore_nulls))
                ci += 1
        return keys, specs

    def _merge_to_partial(self, batch: ColumnarBatch) -> ColumnarBatch:
        """Merge-phase aggregation of concatenated partials back to one row
        per group (the merge half of the CudfAggregate update/merge pairs)."""
        tok = self._fused_dispatch(batch, "merge")
        if tok is not None:
            return self._shrink_fused(*tok)
        keys, specs = self._merge_specs(batch)
        if not keys:
            aggs = agg_k.reduce_aggregate(specs, batch.num_rows,
                                          batch.capacity)
            return ColumnarBatch(self._partial_schema(), aggs, 1)
        out_keys, aggs, n_groups = self._groupby_eager(keys, specs, batch)
        return self._shrink_partial(
            ColumnarBatch(self._partial_schema(), out_keys + aggs, n_groups))

    def _final(self, batch: ColumnarBatch) -> Partition:
        with trace_span("aggregate", self.metrics, "computeAggTime"):
            fused = self._maybe_fused_final(batch)
            if fused is not None:
                self.metrics.inc("numOutputRows", fused.num_rows_raw)
                yield fused
                return
            keys, specs = self._merge_specs(batch)
            if not keys:
                aggs = agg_k.reduce_aggregate(specs, batch.num_rows,
                                              batch.capacity)
                n_groups = 1
                out_keys = []
            else:
                out_keys, aggs, n_groups = self._groupby_eager(
                    keys, specs, batch)
        out = self._project_results(out_keys, aggs, n_groups)
        self.metrics.inc("numOutputRows", out.num_rows_raw)
        yield out

    def _maybe_fused_final(self, batch: ColumnarBatch
                           ) -> Optional[ColumnarBatch]:
        """Fused merge + result projection: one device program for the whole
        final phase (merge groupby -> leaf assembly -> result expressions)."""
        if getattr(self, "_fusion_broken", False) or not _fusion_enabled(self):
            return None
        with host_site("fusable"):
            if not all(e.tree_fusable() for e in self.aggregate_exprs):
                return None
        import jax
        import jax.numpy as jnp
        sig = self._fusion_sig("final", batch.schema)
        if sig is None:
            return None
        with host_site("program_key"):
            rkeys = [_expr_cache_key(e) for e in self.aggregate_exprs]
        if any(k is None for k in rkeys):
            return None
        in_schema = batch.schema
        cap = batch.capacity
        donate = _donate_argnums(batch, 1)

        def build():
            def fn(num_rows, *arrays):
                node = _trace_exec_stack()[-1]   # no self capture: see _FUSED_CACHE
                b = ColumnarBatch.from_flat_arrays(in_schema, arrays,
                                                   num_rows)
                keys, specs = node._merge_specs(b)
                with operator_scope(node):
                    if not keys:
                        aggs = agg_k.reduce_aggregate(specs, num_rows,
                                                      b.capacity)
                        ok, ng = [], jnp.int32(1)
                    else:
                        ok, aggs, ng = agg_k.groupby_aggregate(
                            keys, specs, num_rows, b.capacity)
                    with jax.named_scope("project"):
                        out = node._project_results(
                            ok, aggs, ng if keys else 1)
                return tuple(out.flat_arrays()) + (ng,)
            return jax.jit(fn, donate_argnums=donate)

        try:
            fn = _fused_fn(sig + ("final", tuple(rkeys), cap,
                                  ("donate", bool(donate))), build)
            with _trace_exec(self):
                outs = fn(_dev_count(batch), *batch.flat_arrays())
            _note_donated(batch, donate)
            return ColumnarBatch.from_flat_arrays(
                self._out_schema, list(outs[:-1]), outs[-1])
        except Exception as e:
            if _donation_consumed(batch):
                raise          # executed-and-donated: no eager re-read
            import logging
            logging.getLogger("spark_rapids_tpu.fusion").warning(
                "fused final group-by fell back to eager: %s", e)
            self._fusion_broken = True
            return None

    # -- result projection ---------------------------------------------------
    def _project_results(self, out_keys: List[Column], aggs: List[Column],
                         n_groups: int) -> ColumnarBatch:
        """Build the output batch by evaluating result expressions over an
        internal batch of [key cols..., leaf agg cols...] (boundFinal/result
        projections, aggregate.scala:487-560)."""
        import jax.numpy as jnp
        # assemble leaf values: for avg, divide sum/count here
        leaf_cols: List[Column] = []
        ai = 0
        for leaf in self.leaves:
            ncols = len(self._update_cols(leaf)) if self.mode != "final" else \
                len(self._merge_ops(leaf))
            if leaf.op == "avg":
                s, c = aggs[ai], aggs[ai + 1]
                valid = s.validity & (c.data > 0)
                data = jnp.where(valid, s.data / jnp.maximum(
                    c.data.astype(jnp.float64), 1.0), 0.0)
                leaf_cols.append(Column(dt.FLOAT64, data, valid))
            elif leaf.op in ("count", "count_star"):
                # counts are never NULL: empty/all-null groups read 0
                # (jnp.maximum: n_groups may be traced in the fused final)
                c = aggs[ai]
                live = jnp.arange(c.capacity) < jnp.maximum(n_groups, 1)
                data = jnp.where(live, jnp.where(c.validity, c.data, 0), 0)
                leaf_cols.append(Column(dt.INT64, data, live))
            else:
                leaf_cols.append(aggs[ai])
            ai += ncols

        cap = (out_keys[0].capacity if out_keys else
               (leaf_cols[0].capacity if leaf_cols else 128))
        internal_fields = [dt.Field(f"_k{i}", self.grouping_src[i].dtype, True)
                           for i in range(len(out_keys))]
        internal_fields += [dt.Field(f"_l{i}", l.dtype, True)
                            for i, l in enumerate(self.leaves)]
        internal = ColumnarBatch(dt.Schema(internal_fields),
                                 out_keys + leaf_cols, n_groups)

        # rewrite output exprs: leaves -> bound refs into internal batch
        # (no metrics here: n_groups may be a tracer in the fused final;
        # callers account rows at the host boundary)
        out_cols = []
        for e in self.aggregate_exprs:
            rewritten = self._rewrite_result(e, len(out_keys))
            out_cols.append(ex.materialize(rewritten.eval(internal), internal))
        return ColumnarBatch(self._out_schema, out_cols, n_groups)

    def _rewrite_result(self, e: ex.Expression, nk: int) -> ex.Expression:
        # computed grouping keys restated in the output (SQL `GROUP BY
        # expr` re-parses the expression) match STRUCTURALLY via
        # _expr_cache_key; unkeyable exprs still need identity
        gkeys = [None if isinstance(g, ex.ColumnRef) else _expr_cache_key(g)
                 for g in self.grouping_src]

        def fn(node):
            for i, leaf in enumerate(self.leaves):
                if node is leaf:
                    return ex.BoundReference(nk + i, leaf.dtype, True)
            for gi, g in enumerate(self.grouping_src):
                if node is g or (
                        isinstance(node, ex.ColumnRef) and
                        isinstance(g, ex.ColumnRef) and
                        node.col_name == g.col_name):
                    return ex.BoundReference(gi, g.dtype, True)
                if gkeys[gi] is not None and type(node) is type(g) \
                        and _expr_cache_key(node) == gkeys[gi]:
                    return ex.BoundReference(gi, g.dtype, True)
            return None
        # top-down: leaf matching is by identity (see overrides rewrite note)
        return e.transform_down(fn)


# ---------------------------------------------------------------------------
# Sort / Limit
# ---------------------------------------------------------------------------

class TpuSortExec(TpuExec):
    """Device sort (GpuSortExec: cudf orderBy analog). Global sort concatenates
    the partition's batches (RequireSingleBatch when global, GpuSortExec.scala)."""

    CONTRACT = exec_contract(schema="passthrough", partitioning="preserve",
                             bound={"orders": 0})
    METRICS = exec_metrics("sortTime")

    def __init__(self, child: TpuExec, orders: List[lp.SortOrder],
                 is_global: bool = True):
        super().__init__(child)
        self.orders = [lp.SortOrder(bind_refs(o.child, child.schema),
                                    o.ascending, o.nulls_first)
                       for o in orders]
        self.is_global = is_global

    @property
    def schema(self):
        return self.children[0].schema

    def children_coalesce_goal(self, i: int):
        # device sort needs the whole partition in one batch
        # (RequireSingleBatch when global, GpuSortExec.scala)
        return "single"

    def execute(self) -> List[Partition]:
        return [self._sort(p) for p in self.children[0].execute()]

    def _sort(self, part: Partition) -> Partition:
        spillables = drain_spillable(part, acquire=True)
        if not spillables:
            return
        batch = concat_spillable(self.schema, spillables)
        with trace_span("sort", self.metrics, "sortTime"):
            keys = [K.SortKey(ex.materialize(o.child.eval(batch), batch),
                              o.ascending, o.nulls_first)
                    for o in self.orders]
            idx = K.sort_indices(keys, batch.num_rows, batch.capacity)
            cols = [K.gather_column(c, idx) for c in batch.columns]
        self.metrics.inc("numOutputRows", batch.num_rows_raw)
        yield ColumnarBatch(self.schema, cols, batch.num_rows)


class TpuLimitExec(TpuExec):
    """Local/global limit (limit.scala)."""

    CONTRACT = exec_contract(schema="passthrough", partitioning="defined")
    METRICS = exec_metrics()

    def __init__(self, child: TpuExec, n: int, is_global: bool = True):
        super().__init__(child)
        self.n = n
        self.is_global = is_global

    @property
    def schema(self):
        return self.children[0].schema

    @property
    def output_partitions(self) -> int:
        return 1 if self.is_global else self.children[0].output_partitions

    def execute(self) -> List[Partition]:
        parts = self.children[0].execute()
        if self.is_global and len(parts) > 1:
            # global limit: single partition of the first n rows
            def gen():
                remaining = self.n
                for p in parts:
                    for b in p:
                        if remaining <= 0:
                            return
                        take = min(remaining, b.num_rows)
                        yield self._slice(b, take)
                        remaining -= take
            return [gen()]

        def local(p):
            remaining = self.n
            for b in p:
                if remaining <= 0:
                    return
                take = min(remaining, b.num_rows)
                yield self._slice(b, take)
                remaining -= take
        return [local(p) for p in parts]

    def _slice(self, batch: ColumnarBatch, n: int) -> ColumnarBatch:
        if n >= batch.num_rows:
            return batch
        cols = [K.rebucket_column(c, n, bucket(n)) for c in batch.columns]
        return ColumnarBatch(self.schema, cols, n)


class TpuUnionExec(TpuExec):
    """Union all (GpuUnionExec)."""

    CONTRACT = exec_contract(schema="union", partitioning="defined")
    METRICS = exec_metrics()

    @property
    def schema(self):
        return self.children[0].schema

    @property
    def output_partitions(self) -> int:
        return sum(c.output_partitions for c in self.children)

    def execute(self) -> List[Partition]:
        parts: List[Partition] = []
        for c in self.children:
            parts.extend(self._retag(p) for p in c.execute())
        return parts

    def _retag(self, p: Partition) -> Partition:
        for b in p:
            # align column names to union schema
            yield ColumnarBatch(self.schema, b.columns, b.num_rows)


class TpuExpandExec(TpuExec):
    """Grouping-sets expand (GpuExpandExec.scala): one output batch per
    projection list, unioned."""

    CONTRACT = exec_contract(schema="defined", partitioning="preserve",
                             bound={"projections": 0})
    METRICS = exec_metrics()

    def __init__(self, child: TpuExec, projections: List[List[ex.Expression]],
                 output_names: List[str]):
        super().__init__(child)
        self.projections = [[bind_refs(e, child.schema) for e in p]
                            for p in projections]
        first = projections[0]
        self._schema = dt.Schema([
            dt.Field(n, e.dtype, True)
            for n, e in zip(output_names, first)])

    @property
    def schema(self):
        return self._schema

    def execute(self) -> List[Partition]:
        return [self._map(p) for p in self.children[0].execute()]

    def _map(self, part: Partition) -> Partition:
        for batch in part:
            for proj in self.projections:
                cols = [ex.materialize(e.eval(batch), batch) for e in proj]
                out = ColumnarBatch(self._schema, cols, batch.num_rows)
                self.metrics.inc("numOutputRows", out.num_rows_raw)
                yield out


class TpuMapInPandasExec(TpuExec):
    """mapInPandas (GpuMapInPandasExec, SURVEY.md §2.9): device batches
    cross to pandas through Arrow, the user fn maps an iterator of frames,
    results re-enter the device columnar world. Input batches are re-aligned
    to a steady size first (RebatchingRoundoffIterator analog)."""

    CONTRACT = exec_contract(schema="defined", partitioning="preserve")
    METRICS = exec_metrics("udfTime")

    def __init__(self, child: TpuExec, plan: "lp.MapInPandas",
                 target_rows: int = 1 << 16):
        super().__init__(child)
        self.plan = plan
        self.target_rows = target_rows

    @property
    def schema(self):
        return self.plan.out_schema

    def execute(self) -> List[Partition]:
        return [self._map(p) for p in self.children[0].execute()]

    def _map(self, part: Partition) -> Partition:
        from ..ops.python_udf import rebatch_iterator

        def frames():
            for b in rebatch_iterator(part, self.target_rows):
                yield b.to_pandas()

        # the user fn runs lazily inside next(): metering each pull (like
        # the sibling pandas execs' pandas_udf span) times fn execution
        # only — not downstream device consumption — and an exception in
        # the fn unwinds through the span, error-marking it in the
        # flight ring for the post-mortem artifact. The construction is
        # metered too: a non-generator fn runs (and can fail) right here
        with trace_span("pandas_udf", self.metrics, "udfTime"):
            it = iter(self.plan.fn(frames()))
        end = object()       # a fn yielding None must fail loudly below,
        while True:          # not silently truncate the stream
            with trace_span("pandas_udf", self.metrics, "udfTime"):
                out_df = next(it, end)
            if out_df is end:
                break
            n = len(out_df)
            if n == 0:
                continue
            out = _df_to_batch(out_df, self.plan.out_schema)
            self.metrics.inc("numOutputRows", n)
            yield out


def _group_pandas_frames(part: Partition, grouping):
    """Drain one partition to pandas and slice a frame per group key:
    yields ``(key_tuple, frame)`` in sorted key order; returns early on an
    empty partition. Shared by the grouped/cogrouped pandas execs."""
    import pandas as pd
    batches = [b for b in part
               if not (isinstance(b.num_rows_raw, int)
                       and b.num_rows_raw == 0)]
    if not batches:
        return None, {}
    merged = concat_batches(batches[0].schema, batches)
    pdf = merged.to_pandas()
    keys = [ex.materialize(g.eval(merged), merged)
            .to_pylist(merged.num_rows) for g in grouping]
    kf = pd.DataFrame({f"_gk{i}": k for i, k in enumerate(keys)})
    groups = {}
    for key, idx in kf.groupby(list(kf.columns), sort=True,
                               dropna=False).groups.items():
        if not isinstance(key, tuple):
            key = (key,)
        groups[key] = pdf.loc[idx].reset_index(drop=True)
    return pdf, groups


class TpuFlatMapGroupsInPandasExec(TpuExec):
    """groupBy().applyInPandas (GpuFlatMapGroupsInPandasExec): each
    partition's rows cross to pandas once, group frames slice out per key,
    the user fn maps each to an output frame. The planner hash-exchanges
    on the keys first when the child is multi-partition, so every group's
    rows are co-located (requiredChildDistribution = clustered(keys))."""

    CONTRACT = exec_contract(schema="defined", partitioning="preserve",
                             bound={"grouping": 0})
    METRICS = exec_metrics("udfTime")

    def __init__(self, child: TpuExec, plan: "lp.FlatMapGroupsInPandas"):
        super().__init__(child)
        self.plan = plan
        self.grouping = [bind_refs(g, child.schema)
                         for g in plan.grouping]
        self._key_names = [ex.output_name(g, i)
                           for i, g in enumerate(plan.grouping)]

    @property
    def schema(self):
        return self.plan.out_schema

    def execute(self) -> List[Partition]:
        return [self._apply(p) for p in self.children[0].execute()]

    def _group_frames(self, part: Partition):
        """(key_tuple, pandas frame) per group in this partition."""
        _pdf, groups = _group_pandas_frames(part, self.grouping)
        yield from groups.items()

    def _apply(self, part: Partition) -> Partition:
        import inspect
        import pandas as pd
        fn = self.plan.fn
        try:
            two_arg = len(inspect.signature(fn).parameters) == 2
        except (TypeError, ValueError):
            two_arg = False
        frames = []
        with trace_span("pandas_udf", self.metrics, "udfTime"):
            for key, pdf in self._group_frames(part):
                out = fn(key, pdf) if two_arg else fn(pdf)
                if out is not None and len(out):
                    frames.append(out)
        if frames:
            combined = pd.concat(frames, ignore_index=True)
            out = _df_to_batch(combined, self.plan.out_schema)
            self.metrics.inc("numOutputRows", out.num_rows_raw)
            yield out

    def _node_string(self):
        return ("TpuFlatMapGroupsInPandasExec "
                f"[{getattr(self.plan.fn, '__name__', 'fn')}]")


class TpuFlatMapCoGroupsInPandasExec(TpuExec):
    """cogroup().applyInPandas (GpuFlatMapCoGroupsInPandasExec): both
    sides drain to pandas, group frames pair up per key (union of key
    sets; a missing side passes an empty frame), fn maps each pair."""

    CONTRACT = exec_contract(schema="defined", partitioning="defined")
    METRICS = exec_metrics("udfTime")

    def __init__(self, left: TpuExec, right: TpuExec,
                 plan: "lp.FlatMapCoGroupsInPandas"):
        super().__init__(left, right)
        self.plan = plan
        self.left_grouping = [bind_refs(g, left.schema)
                              for g in plan.left_grouping]
        self.right_grouping = [bind_refs(g, right.schema)
                               for g in plan.right_grouping]

    @property
    def schema(self):
        return self.plan.out_schema

    def execute(self) -> List[Partition]:
        lparts = self.children[0].execute()
        rparts = self.children[1].execute()
        n = max(len(lparts), len(rparts))

        def empty():
            return
            yield
        lparts += [empty() for _ in range(n - len(lparts))]
        rparts += [empty() for _ in range(n - len(rparts))]
        return [self._apply(lp_, rp_)
                for lp_, rp_ in zip(lparts, rparts)]

    @staticmethod
    def _collect_side(part: Partition, grouping):
        return _group_pandas_frames(part, grouping)

    def _apply(self, lpart: Partition, rpart: Partition) -> Partition:
        import inspect
        import pandas as pd
        fn = self.plan.fn
        try:
            three_arg = len(inspect.signature(fn).parameters) == 3
        except (TypeError, ValueError):
            three_arg = False
        lp_df, lgroups = self._collect_side(lpart, self.left_grouping)
        rp_df, rgroups = self._collect_side(rpart, self.right_grouping)
        lempty = (lp_df.iloc[0:0] if lp_df is not None else
                  pd.DataFrame(columns=self.children[0].schema.names()))
        rempty = (rp_df.iloc[0:0] if rp_df is not None else
                  pd.DataFrame(columns=self.children[1].schema.names()))
        frames = []
        with trace_span("pandas_udf", self.metrics, "udfTime"):
            for key in sorted(set(lgroups) | set(rgroups), key=repr):
                l = lgroups.get(key, lempty)
                r = rgroups.get(key, rempty)
                out = fn(key, l, r) if three_arg else fn(l, r)
                if out is not None and len(out):
                    frames.append(out)
        if frames:
            combined = pd.concat(frames, ignore_index=True)
            out = _df_to_batch(combined, self.plan.out_schema)
            self.metrics.inc("numOutputRows", out.num_rows_raw)
            yield out

    def _node_string(self):
        return ("TpuFlatMapCoGroupsInPandasExec "
                f"[{getattr(self.plan.fn, '__name__', 'fn')}]")


class TpuAggregateInPandasExec(TpuExec):
    """groupBy().agg(grouped-agg pandas UDFs) (GpuAggregateInPandasExec,
    198 LoC in the reference): fn(Series...) -> scalar once per
    (group, udf); output = key columns + one column per udf."""

    CONTRACT = exec_contract(schema="defined", partitioning="preserve",
                             bound={"grouping": 0})
    METRICS = exec_metrics("udfTime")

    def __init__(self, child: TpuExec, plan: "lp.AggregateInPandas"):
        super().__init__(child)
        self.plan = plan
        self.grouping = [bind_refs(g, child.schema) for g in plan.grouping]
        self.aggs = [type(a)(a.fn, a.return_type,
                             *[bind_refs(c, child.schema)
                               for c in a.children],
                             name=a.udf_name)
                     for a in plan.aggs]

    @property
    def schema(self):
        return self.plan.schema

    def execute(self) -> List[Partition]:
        return [self._apply(p) for p in self.children[0].execute()]

    def _apply(self, part: Partition) -> Partition:
        import pandas as pd
        batches = [b for b in part
                   if not (isinstance(b.num_rows_raw, int)
                           and b.num_rows_raw == 0)]
        if not batches:
            return
        merged = concat_batches(batches[0].schema, batches)
        n = merged.num_rows
        key_lists = [ex.materialize(g.eval(merged), merged).to_pylist(n)
                     for g in self.grouping]
        # per udf: its input series, sliced per group
        agg_inputs = [[ex.materialize(c.eval(merged), merged)
                       .to_arrow(n).to_pandas()
                       for c in a.children] for a in self.aggs]
        kf = pd.DataFrame({f"_gk{i}": k for i, k in enumerate(key_lists)})
        rows = []
        with trace_span("pandas_udf", self.metrics, "udfTime"):
            for key, idx in kf.groupby(list(kf.columns), sort=True,
                                       dropna=False).groups.items():
                if not isinstance(key, tuple):
                    key = (key,)
                vals = []
                for a, inputs in zip(self.aggs, agg_inputs):
                    sliced = [s.loc[idx].reset_index(drop=True)
                              for s in inputs]
                    vals.append(a.fn(*sliced))
                rows.append(tuple(key) + tuple(vals))
        if rows:
            out_schema = self.plan.schema
            data = {f.name: [r[i] for r in rows]
                    for i, f in enumerate(out_schema)}
            out = _df_to_batch(pd.DataFrame(data), out_schema)
            self.metrics.inc("numOutputRows", out.num_rows_raw)
            yield out

    def _node_string(self):
        return (f"TpuAggregateInPandasExec "
                f"[{', '.join(a.udf_name for a in self.aggs)}]")


class TpuGenerateExec(TpuExec):
    """explode/posexplode (GpuGenerateExec.scala: per-row repeat + flatten).
    ``Explode(StringSplit(s, d))`` fuses split+explode into one kernel —
    the intermediate array<string> never materializes."""

    CONTRACT = exec_contract(schema="defined", partitioning="preserve")
    METRICS = exec_metrics("generateTime")

    def __init__(self, child: TpuExec, plan: lp.Generate):
        super().__init__(child)
        from ..ops import arrays as ar_ops
        self.plan = plan
        gen = plan.generator
        self.pos = getattr(gen, "pos", False)
        inner = gen.children[0]
        if isinstance(inner, ar_ops.StringSplit):
            self.split_delim = inner.delimiter
            self.gen_input = bind_refs(inner.children[0], child.schema)
        else:
            self.split_delim = None
            self.gen_input = bind_refs(inner, child.schema)
        self._schema = plan.schema

    @property
    def schema(self):
        return self._schema

    def execute(self) -> List[Partition]:
        return [self._map(p) for p in self.children[0].execute()]

    def _map(self, part: Partition) -> Partition:
        from ..ops import arrays as ar_ops
        for batch in part:
            with trace_span("generate", self.metrics, "generateTime"):
                arr = ex.materialize(self.gen_input.eval(batch), batch)
                live = batch.row_mask()
                # one host sync sizes the output bucket (the dynamic-size
                # protocol's batch-boundary read, DESIGN.md)
                if self.split_delim is not None:
                    pre = ar_ops.split_part_counts(arr,
                                                   ord(self.split_delim))
                    import jax.numpy as jnp
                    total = int(jnp.sum(jnp.where(live, pre[1], 0)))  # lint: host-sync-ok generate output sizing: the dynamic-size protocol's batch-boundary read
                    out_cap = bucket(max(total, 1))
                    others, elem, pos_col, count = ar_ops.split_explode(
                        arr, ord(self.split_delim), batch.columns, live,
                        out_cap, precomputed=pre)
                else:
                    total = int(jnp_total_len(arr, live))
                    out_cap = bucket(max(total, 1))
                    others, elem, pos_col, count = ar_ops.explode_array(
                        arr, batch.columns, live, out_cap)
                n = int(count)
            if n == 0:
                continue
            cols = others + ([pos_col] if self.pos else []) + [elem]
            out = ColumnarBatch(self._schema, cols, n)
            self.metrics.inc("numOutputRows", n)
            yield out


def jnp_total_len(arr: Column, live) -> "jnp.ndarray":
    import jax.numpy as jnp
    return jnp.sum(jnp.where(live & arr.validity, arr.lengths, 0))





# ---------------------------------------------------------------------------
# Joins
# ---------------------------------------------------------------------------

class TpuSortMergeJoinExec(TpuExec):
    """Equality join: build side materialized to a single sorted batch, stream
    side joined per batch (GpuShuffledHashJoinExec shape, but sort-merge
    kernels per DESIGN.md §3; build-side-single-batch mirrors
    GpuHashJoin.scala:193-249's stream loop)."""

    CONTRACT = exec_contract(schema="defined", partitioning="defined",
                             bound={"left_keys": 0, "right_keys": 1},
                             extras=("join_schema",))
    METRICS = exec_metrics("joinTime", "buildTime")

    # AQE join-strategy demotion policy: dict(threshold, factor,
    # partitions, validate) stamped by the planner on a broadcast-form
    # join when adaptive execution is on (plan/aqe.py). None = off.
    aqe_demote_policy: Optional[dict] = None

    def __init__(self, left: TpuExec, right: TpuExec, how: str,
                 left_keys: List[ex.Expression], right_keys: List[ex.Expression],
                 condition: Optional[ex.Expression] = None):
        super().__init__(left, right)
        self.how = how
        self.left_keys = [bind_refs(e, left.schema) for e in left_keys]
        self.right_keys = [bind_refs(e, right.schema) for e in right_keys]
        self._out_schema = self._compute_schema()
        self.condition = bind_refs(condition, self._merged_schema()) \
            if condition is not None else None

    def _merged_schema(self):
        return dt.Schema(list(self.children[0].schema.fields) +
                         list(self.children[1].schema.fields))

    def _compute_schema(self) -> dt.Schema:
        left, right = self.children[0].schema, self.children[1].schema
        if self.how in ("left_semi", "left_anti"):
            return left
        lf = [dt.Field(f.name, f.dtype, True if self.how == "full" else f.nullable)
              for f in left.fields]
        rf = [dt.Field(f.name, f.dtype,
                       True if self.how in ("left", "full") else f.nullable)
              for f in right.fields]
        return dt.Schema(lf + rf)

    @property
    def schema(self):
        return self._out_schema

    @property
    def output_partitions(self) -> int:
        return 1 if self.how == "full" else self.children[0].output_partitions

    def children_coalesce_goal(self, i: int):
        # build side is materialized to a single batch; stream side benefits
        # from target-size batches (GpuShuffledHashJoinExec goals)
        return "target" if i == 0 else "single"

    def execute(self) -> List[Partition]:
        # build side = right (stream left), matching Spark BuildRight default.
        # The build is materialized ONCE as a spillable handle shared by every
        # stream partition (broadcast semantics: the reference's broadcast
        # batch is likewise materialized lazily once per executor and held
        # spillable, GpuBroadcastExchangeExec.scala:238-367); partitions
        # re-acquire it, so it can spill between partition tasks.
        from ..exec.spill import SpillableColumnarBatch
        from ..shuffle.exchange import TpuBroadcastExchangeExec
        self._aqe_decisions = []       # fresh per execution (plan/aqe.py)
        bchild = self.children[1]
        if isinstance(bchild, TpuBroadcastExchangeExec):
            handle = bchild.materialize()
            if getattr(self, "aqe_demote_policy", None):
                # AQE join-strategy demotion: the planner chose broadcast
                # from estimates, but the materialized build is observed
                # oversized — re-plan as a co-partitioned shuffled join
                # reusing the already-built batch (plan/aqe.py)
                from . import aqe
                demoted = aqe.maybe_demote_broadcast(self, bchild, handle)
                if demoted is not None:
                    return demoted
        else:
            # metered separately from the stream loop (the reference's
            # buildTime vs joinTime split, GpuMetricNames)
            with trace_span("join_build", self.metrics, "buildTime"):
                build = concat_spillable(
                    bchild.schema, accumulate_spillable(bchild.execute()))
            handle = self._build_handle = SpillableColumnarBatch(build)
        stream_parts = self.children[0].execute()
        if self.how == "full":
            # unmatched-build accounting happens inside one join pass, so full
            # outer needs the ENTIRE stream side in a single partition — a
            # per-partition pass would re-emit matched build rows as unmatched
            merged = concat_spillable(self.children[0].schema,
                                      accumulate_spillable(stream_parts))
            stream_parts = [iter([merged])]
        return [self._join_part(p, handle) for p in stream_parts]

    def _cleanup(self) -> None:
        h = getattr(self, "_build_handle", None)
        if h is not None:
            h.close()
            self._build_handle = None
        rep = getattr(self, "_aqe_demoted", None)
        if rep is not None:
            rep.cleanup()              # idempotent per exec contract
            self._aqe_demoted = None

    def _pipeline_depth(self) -> int:
        """Join pipeline window depth: planner-set override (the session
        conf wired through overrides) or the global conf default."""
        d = getattr(self, "pipeline_depth", None)
        if d is None:
            from .. import config as cfg
            with host_site("conf_read"):
                d = cfg.TpuConf().get(cfg.JOIN_PIPELINE_DEPTH)
        return max(1, int(d))

    def _join_part(self, part: Partition,
                   build_handle: "SpillableColumnarBatch") -> Partition:
        # full outer: execute() has already merged the whole stream side into
        # this one partition as a single (possibly empty) batch
        from ..exec.pipeline import PipelineWindow
        import jax.numpy as jnp
        _task_begin()
        build = build_handle.get_batch()
        bkey_cols = [ex.materialize(e.eval(build), build)
                     for e in self.right_keys]

        # PIPELINED stream loop (the reference's per-batch join stream loop
        # has no host sync at all, GpuHashJoin.scala:193-249): join_match
        # for batches k+1..k+depth dispatches before batch k's gather
        # sizing resolves; the window lands half a depth of size scalars
        # per batched readback, so join-path host syncs are O(1) per stage
        # instead of one blocking RTT per stream batch.
        # metrics=: sizing-scalar readbacks attribute their hostSyncs to
        # this join exec (the EXPLAIN ANALYZE per-node sync count)
        win = PipelineWindow(self._pipeline_depth(), metrics=self.metrics)
        for batch in part:
            # admission: up to `depth` stream batches (+ match state) stay
            # device-resident while their sizing scalars are in flight —
            # account each to the spill manager like the aggregate window
            _reserve(batch.device_size_bytes())
            with trace_span("join", self.metrics, "joinTime"):
                skey_cols = [ex.materialize(e.eval(batch), batch)
                             for e in self.left_keys]
                how = self.how if self.how in (
                    "inner", "left", "left_semi", "left_anti") else (
                    "left" if self.how == "full" else "inner")
                m = join_k.join_match(bkey_cols, build.num_rows_raw,
                                      skey_cols, batch.num_rows_raw,
                                      batch.capacity)
                if how in ("left_semi", "left_anti"):
                    # semi/anti outputs compact at STREAM capacity —
                    # join_gather ignores out_capacity, so no size scalar:
                    # the entry rides through the window immediately
                    cont = (lambda b=batch, mm=m, h=how:
                            self._join_finish(build, b, mm, h, None, None))
                    scalars = ()
                else:
                    # the sizing scalar stays in flight on the window
                    # (left-outer's emit total computes on DEVICE — a full
                    # per-row counts download costs ~capacity bytes over a
                    # slow link)
                    if how == "left":
                        live = batch.row_mask_raw()
                        size_dev = jnp.sum(
                            jnp.where(live, jnp.maximum(m.count, 1), 0))
                    else:
                        size_dev = m.total_pairs
                    cont = (lambda total, b=batch, mm=m, h=how, sd=size_dev:
                            self._join_finish(build, b, mm, h, sd, total))
                    scalars = (size_dev,)
            # push OUTSIDE the dispatch span: a landing runs _join_finish's
            # own metered "join" span, which must be a sibling (the two
            # halves SUM into joinTime), never nested (it would double-count)
            for outs in win.push(cont, *scalars):
                yield from outs
        for outs in win.flush():
            yield from outs

    def _join_finish(self, build: ColumnarBatch, batch: ColumnarBatch,
                     m, how: str, size_dev, total) -> List[ColumnarBatch]:
        """Second half of one stream batch's join: gather at the
        host-sized output bucket. Runs when the pipeline window resolves
        this batch's sizing scalar; returns the output batches."""
        import jax
        with trace_span("join", self.metrics, "joinTime"):
            if how in ("left_semi", "left_anti"):
                out_cap = batch.capacity
            else:
                if total is None:
                    # window-degraded entry (batched readback failed):
                    # re-read this batch's scalar alone
                    total = jax.device_get(size_dev)  # lint: host-sync-ok window-degraded re-read of ONE batch's sizing scalar
                out_cap = bucket(max(int(total), 1))
            s_out, b_out, cnt = join_k.join_gather(
                m, batch.columns, build.columns, out_cap, how,
                n_stream=batch.num_rows_raw)
            # the output count stays device-resident; downstream boundaries
            # resolve it in batched readbacks (possibly-empty batches flow)
            if self.how in ("left_semi", "left_anti"):
                out = ColumnarBatch(self._out_schema, s_out, cnt)
            else:
                out = ColumnarBatch(self._out_schema, s_out + b_out, cnt)
            if self.condition is not None and self.how == "inner":
                # conditional join: post-filter (reference: inner-only
                # conditional joins via post-join filter). Row mask from the
                # device-resident count — row_mask() would force a sync.
                pred = self.condition.eval(out)
                keep = pred.data & pred.validity & out.row_mask_raw()
                cols, count = K.compact_columns(out.columns, keep)
                out = ColumnarBatch(self._out_schema, cols, count)
            self.metrics.inc("numOutputRows", out.num_rows_raw)
            outs = [out]
            if self.how == "full":
                # append unmatched build rows with NULL left columns; the
                # count stays device-resident too (the tail's former
                # blocking `int(ucnt)` was one more RTT per stage)
                un_cols, ucnt = join_k.unmatched_build_gather(
                    m, build.columns, build.num_rows_raw)
                ucap = un_cols[0].capacity if un_cols else build.capacity
                left_nulls = [Column.full_null(f.dtype, ucap)
                              for f in self.children[0].schema]
                uout = ColumnarBatch(self._out_schema,
                                     left_nulls + un_cols, ucnt)
                self.metrics.inc("numOutputRows", uout.num_rows_raw)
                outs.append(uout)
            return outs


class TpuShuffledJoinExec(TpuSortMergeJoinExec):
    """Co-partitioned equality join: both children are hash-exchanged on the
    join keys with the same partition count, so partition i of the stream
    side joins only partition i of the build side
    (GpuShuffledHashJoinExec shape, shims/spark300/GpuShuffledHashJoinExec
    .scala — with sort-merge kernels per DESIGN.md §3). Unlike the broadcast
    form, the build side is never materialized whole: one build partition at
    a time. Full outer is correct per partition pair because co-partitioning
    makes key ownership disjoint."""

    CONTRACT = exec_contract(schema="defined", partitioning="defined",
                             bound={"left_keys": 0, "right_keys": 1},
                             extras=("join_schema", "copartitioned"))
    METRICS = exec_metrics("joinTime", "buildTime", "skewJoinSplits",
                           "runtimeBroadcastJoins")

    # runtime AQE join switch: set by the planner to the broadcast-join
    # byte threshold when adaptive execution is on (None = off)
    aqe_broadcast_threshold: Optional[int] = None
    # AQE skew-join split: a stream-side reduce partition larger than this
    # many observed bytes splits into mapper-subset tasks, each joined
    # against the SAME build partition (OptimizeSkewedJoin +
    # GpuCustomShuffleReaderExec partial-mapper specs). None = off.
    aqe_skew_threshold: Optional[int] = None
    # skewedPartitionFactor: raises the cut line to factor x median
    # observed partition bytes when higher (plan/aqe.py). None = absolute
    # threshold only.
    aqe_skew_factor: Optional[float] = None
    # joinSwitch.demoteFactor: the promote side of the hysteresis dead
    # band — an observed build in (threshold, threshold x factor] records
    # a declined decision and stays shuffled (no flapping)
    aqe_demote_factor: Optional[float] = None

    @property
    def output_partitions(self) -> int:
        return self.children[0].output_partitions

    def execute(self) -> List[Partition]:
        self._aqe_decisions = []       # fresh per execution (plan/aqe.py)
        switched, rparts = self._maybe_runtime_broadcast()
        if switched is not None:
            return switched
        skewed = self._maybe_skew_split(rparts)
        if skewed is not None:
            return skewed
        lparts = self.children[0].execute()
        if rparts is None:
            rparts = self.children[1].execute()
        assert len(lparts) == len(rparts), \
            f"co-partition mismatch: {len(lparts)} vs {len(rparts)}"
        return [self._join_copart(sp, bp)
                for sp, bp in zip(lparts, rparts)]

    def _maybe_skew_split(self, rparts) -> Optional[List[Partition]]:
        """Skew handling: hot stream partitions split into mapper-subset
        tasks (>=2 output partitions per hot input partition), the build
        partition materialized ONCE and shared by its sub-tasks. Inner/
        left only — right/full outer would emit unmatched build rows once
        per sub-task."""
        from ..shuffle.exchange import TpuShuffleExchangeExec
        from ..shuffle.manager import WorkerContext
        thr = self.aqe_skew_threshold
        if thr is None or thr <= 0 or self.how in ("right", "full") or \
                WorkerContext.current is not None:
            return None
        from . import aqe
        sx = self.children[0]
        if not isinstance(sx, TpuShuffleExchangeExec):
            return None
        if sx.would_use_ici():
            # device-resident exchange (docs/shuffle.md): rows never stage
            # as host slices, so there are no per-slice observed sizes to
            # split on. The PRIOR execution's stage stats for the same
            # exchange fingerprint can still prove skew — then the skewed
            # stage only falls back to DCN (execute_skew forces the host
            # plane); otherwise this run records the baseline and stays
            # on the ICI plane.
            fall_back, why = aqe.ici_skew_fallback(
                sx, thr, getattr(self, "aqe_skew_factor", None))
            if not fall_back:
                aqe.record_decision(self, "skew-split", applied=False,
                                    reason=f"ici plane: {why}")
                return None
            ici_fell_back = True
        else:
            ici_fell_back = False
        sgroups = sx.execute_skew(thr,
                                  getattr(self, "aqe_skew_factor", None))
        hot = sum(1 for g in sgroups if len(g) > 1)
        if hot:
            aqe.record_decision(
                self, "skew-split", stage_id=sx.stage_id,
                before=f"{len(sgroups)} partitions"
                       + (" [ici]" if ici_fell_back else ""),
                after=(f"{hot} hot partition(s) split into "
                       f"{sum(len(g) for g in sgroups)} tasks"
                       + (" [ici->dcn]" if ici_fell_back else "")),
                reason=f"observed partition bytes past threshold {thr}")
        if all(len(g) == 1 for g in sgroups):
            # nothing hot: fall through to the plain co-partitioned loop
            return [self._join_copart(g[0], bp)
                    for g, bp in zip(sgroups, rparts
                                     if rparts is not None
                                     else self.children[1].execute())]
        if rparts is None:
            rparts = self.children[1].execute()
        assert len(sgroups) == len(rparts)
        out: List[Partition] = []
        for subs, bp in zip(sgroups, rparts):
            if len(subs) == 1:
                out.append(self._join_copart(subs[0], bp))
                continue
            self.metrics.inc("skewJoinSplits")
            shared = _SharedBuild(self.children[1].schema, bp, len(subs))
            for sub in subs:
                out.append(self._join_split(sub, shared))
        return out

    def _join_split(self, stream_part: Partition,
                    shared: "_SharedBuild") -> Partition:
        try:
            yield from self._join_part(stream_part, shared.handle())
        finally:
            shared.release()

    def _maybe_runtime_broadcast(self):
        """AQE runtime join-strategy switch (the reference's AQE broadcast
        conversion + GpuCustomShuffleReaderExec territory): run the BUILD
        side's exchange map phase first; when its OBSERVED output is under
        the broadcast threshold, materialize one broadcast build batch
        from the already-shuffled slices and stream-join against the
        UNexchanged stream child — the stream-side shuffle never executes.
        Planner estimates decided shuffled; runtime sizes overrule.

        Returns ``(broadcast_partitions, None)`` on a switch, or
        ``(None, build_partitions_or_None)`` when staying co-partitioned
        (execute() owns the single co-partitioned join loop either way)."""
        from ..shuffle.exchange import TpuShuffleExchangeExec
        from ..shuffle.manager import WorkerContext
        thr = self.aqe_broadcast_threshold
        if thr is None or thr < 0 or self.how in ("right", "full"):
            # right/full outer against a broadcast build would duplicate
            # unmatched build rows per stream partition
            return None, None
        sx, bx = self.children
        if not isinstance(sx, TpuShuffleExchangeExec) or \
                not isinstance(bx, TpuShuffleExchangeExec):
            return None, None
        raw_stream = sx.children[0]
        bparts = bx.execute()          # map phase runs: size now observed
        observed = bx.metrics.resolve().get("dataSize", 0)
        ctx = WorkerContext.current
        if ctx is not None:
            # mesh-consistent decision: the LOCAL observed size is one
            # shard's contribution; sum it across workers through the
            # control-plane allreduce so every worker takes the SAME
            # branch (a split decision would desync the lockstep
            # shuffle-id streams — and the fingerprint handshake would
            # abort the query)
            observed = ctx.allreduce_bytes(bx._shuffle.shuffle_id, observed)
        from . import aqe
        if observed > thr:
            f = float(getattr(self, "aqe_demote_factor", None) or 2.0)
            if observed <= int(thr * f):
                # hysteresis dead band: a borderline build must not flap
                # between strategies across repeat executions
                aqe.record_decision(
                    self, "join-promote", applied=False,
                    stage_id=bx.stage_id, before="shuffled",
                    reason=(f"observed build {observed}B in hysteresis "
                            f"band ({thr}B, {int(thr * f)}B]: staying "
                            "shuffled"))
            # stay co-partitioned (stream exchange proceeds as planned)
            return None, bparts
        from ..exec.spill import SpillableColumnarBatch
        if ctx is not None:
            # the full build side = EVERY reduce partition (local + peers),
            # not just the owned ones: each worker broadcast-joins its raw
            # local stream shard against the complete build; one source
            # generator per peer so fetches drain concurrently
            build = concat_spillable(
                bx.schema,
                accumulate_spillable(
                    bx._shuffle.read_all_partition_sources()))
        else:
            # concurrent drain (accumulate_spillable): a serial sweep would
            # pay one blocking readback (host sync) per shuffle partition
            build = concat_spillable(bx.schema,
                                     accumulate_spillable(bparts))
        self._rt_broadcast = SpillableColumnarBatch(build)
        self.metrics.inc("runtimeBroadcastJoins")
        aqe.record_decision(
            self, "join-promote", stage_id=bx.stage_id,
            before=f"shuffled[{len(bparts)}]", after="broadcast",
            reason=f"observed build {observed}B <= threshold {thr}B")

        def gen(p):
            yield from self._join_part(p, self._rt_broadcast)
        return [gen(p) for p in raw_stream.execute()], None

    def _cleanup(self) -> None:
        h = getattr(self, "_rt_broadcast", None)
        if h is not None:
            h.close()
            self._rt_broadcast = None

    def _join_copart(self, stream_part: Partition,
                     build_part: Partition) -> Partition:
        from ..exec.spill import SpillableColumnarBatch
        with trace_span("join_build", self.metrics, "buildTime"):
            build = concat_spillable(
                self.children[1].schema,
                [SpillableColumnarBatch(b) for b in build_part
                 if b.num_rows > 0])
            handle = SpillableColumnarBatch(build)
        try:
            if self.how == "full":
                merged = concat_spillable(
                    self.children[0].schema,
                    [SpillableColumnarBatch(b) for b in stream_part
                     if b.num_rows > 0])
                stream_part = iter([merged])
            yield from self._join_part(stream_part, handle)
        finally:
            handle.close()


class _SharedBuild:
    """One build partition materialized once, shared by the skew-split
    sub-tasks of its stream partition; freed when the LAST sub-task
    releases (sub-tasks drain concurrently on the task pool, so
    materialization and refcounting are locked)."""

    def __init__(self, schema, build_part: Partition, refs: int):
        import threading
        self._schema = schema
        self._part = build_part
        self._refs = refs
        self._handle = None
        self._mu = threading.Lock()

    def handle(self):
        from ..exec.spill import SpillableColumnarBatch
        with self._mu:
            if self._handle is None:
                build = concat_spillable(
                    self._schema,
                    [SpillableColumnarBatch(b) for b in self._part
                     if b.num_rows > 0])
                self._handle = SpillableColumnarBatch(build)
            return self._handle

    def release(self):
        with self._mu:
            self._refs -= 1
            if self._refs == 0 and self._handle is not None:
                self._handle.close()
                self._handle = None


class TpuCrossJoinExec(TpuExec):
    """Cartesian product (GpuCartesianProductExec)."""

    CONTRACT = exec_contract(schema="defined", partitioning="defined")
    METRICS = exec_metrics()

    def __init__(self, left: TpuExec, right: TpuExec,
                 condition: Optional[ex.Expression] = None):
        super().__init__(left, right)
        self._out_schema = dt.Schema(
            list(left.schema.fields) + list(right.schema.fields))
        self.condition = bind_refs(condition, self._out_schema) \
            if condition is not None else None

    @property
    def schema(self):
        return self._out_schema

    def execute(self) -> List[Partition]:
        right = concat_spillable(
            self.children[1].schema,
            accumulate_spillable(self.children[1].execute()))
        return [self._map(p, right) for p in self.children[0].execute()]

    def _map(self, part: Partition, right: ColumnarBatch) -> Partition:
        for batch in part:
            total = batch.num_rows * right.num_rows
            cap = bucket(max(total, 1))
            l_out, r_out, cnt = join_k.cross_join_gather(
                batch.columns, batch.num_rows, right.columns, right.num_rows,
                cap)
            n = int(cnt)
            out = ColumnarBatch(self._out_schema, l_out + r_out, n)
            if self.condition is not None:
                pred = self.condition.eval(out)
                keep = pred.data & pred.validity & out.row_mask()
                cols, count = K.compact_columns(out.columns, keep)
                n = int(count)
                out = ColumnarBatch(self._out_schema, cols, n)
            if n > 0:
                self.metrics.inc("numOutputRows", n)
                yield out


# ---------------------------------------------------------------------------
# CPU fallback + transitions
# ---------------------------------------------------------------------------

class CpuFallbackExec(TpuExec):
    """Executes a logical subtree on the CPU engine (the 'stays on CPU' side
    of a mixed plan; transition = GpuRowToColumnarExec analog on output)."""

    CONTRACT = exec_contract(schema="defined", partitioning="single")
    METRICS = exec_metrics()

    def __init__(self, plan: lp.LogicalPlan):
        super().__init__()
        self.plan = plan

    @property
    def schema(self):
        return self.plan.schema

    def execute(self) -> List[Partition]:
        from ..cpu.engine import execute as cpu_execute
        df = cpu_execute(self.plan)

        def gen():
            yield _df_to_batch(df, self.plan.schema)
        return [gen()]

    def _node_string(self):
        return f"CpuFallbackExec[{self.plan.name}]"


def _df_to_batch(df, schema: dt.Schema) -> ColumnarBatch:
    cols = []
    n = len(df)
    cap = bucket(n)
    # positional alignment when the frame carries duplicate names (USING
    # joins, self-joins): df[name] would return a sub-frame there
    names = list(df.columns)
    positional = len(names) == len(schema.fields) and \
        len(set(names)) != len(names)
    for i, f in enumerate(schema):
        if positional:
            vals = list(df.iloc[:, i])
        else:
            vals = list(df[f.name]) if f.name in df.columns else [None] * n
        vals = [None if _is_na(v) else v for v in vals]
        cols.append(Column.from_pylist(vals, f.dtype, capacity=cap))
    return ColumnarBatch(schema, cols, n)


def _is_na(v) -> bool:
    if v is None:
        return True
    try:
        import pandas as pd
        return v is pd.NA or (isinstance(v, float) and pd.isna(v) and
                              not np.isnan(v))
    except Exception:
        return False
