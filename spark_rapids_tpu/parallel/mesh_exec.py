"""Mesh-routed physical operators: the planner emits these when an SPMD
device mesh is active (``spark.rapids.tpu.sql.mesh.enabled``), replacing the
host-orchestrated exchange pipeline with fused XLA collectives over ICI.

Mapping to the reference (SURVEY.md §2.6/§2.8): the exchange operators
(GpuShuffleExchangeExec + GpuHashPartitioning / GpuRangePartitioning) and the
downstream op collapse into one jitted shard_map program per stage —
GpuHashAggregate(partial) -> exchange -> GpuHashAggregate(final) becomes one
XLA computation whose shuffle is a single ``all_to_all`` riding ICI
(parallel/mesh.py). Host staging happens only at the stage boundary: child
partitions are drained, concatenated, and split into one shard per worker.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from ..analysis.contracts import exec_contract
from ..columnar import dtypes as dt
from ..columnar.batch import ColumnarBatch, resolve_counts
from ..columnar.column import MIN_CAPACITY, bucket
from ..exec.tasks import run_partition_tasks
from ..ops import expressions as ex
from ..ops import kernels as K
from ..plan import logical as lp
from ..plan.physical import (Partition, TpuExec, TpuShuffledJoinExec,
                             bind_refs, concat_spillable, drain_spillable,
                             exec_metrics)
from . import mesh as M
from ..exec.tracing import trace_span

# ops the SPMD group-by pipeline merges correctly (first/last are excluded:
# their distributed result would depend on shard order)
MESH_AGG_OPS = ("sum", "count", "count_star", "avg", "min", "max")


#: factor between the capacity classes an SPMD stage's input can take
SHRINK_STEP = 64


def stage_capacity(static_cap: int, live_rows: int) -> int:
    """The capacity class of an SPMD stage's per-worker input: the one its
    child's batches came in (their static bound: a filter keeps its
    input's capacity, whatever it selects), divided by :data:`SHRINK_STEP`
    as often as the live rows then still leave half of it empty.

    Why it shrinks at all: every stage receives into ``n * cap`` rows, so
    the static bound alone grows n-fold per chained stage (a 2 Mi-row join
    output under a filter, a group-by and a sort on four workers: 2 Mi,
    8 Mi, 32 Mi rows a worker for the few thousand that are live). Why in
    steps this wide: the class is part of every program of the stage and
    of the ones downstream, and the live rows follow what the query's
    literals select. A workload whose rows vary by a factor r from draw
    to draw straddles a boundary of power-of-two classes with probability
    ~log2(r), of these with ~log2(r)/6: a rebuilt stage (seconds to
    minutes of compile) is traded for running over at most 128 times the
    live rows (PERF.md section 6, PR 27, has both sides measured)."""
    cap = static_cap
    while cap // SHRINK_STEP >= max(2 * live_rows, MIN_CAPACITY):
        cap //= SHRINK_STEP
    return cap


def shard_for_mesh(child: TpuExec, n: int) -> List[ColumnarBatch]:
    """Drain the child into n per-worker shards at one common capacity
    (uniform shapes are what lets the whole stage trace once), chosen by
    :func:`stage_capacity` from the capacities the child's batches have
    and ONE readback of their row counts. A child that already comes in n
    partitions (a mesh stage under a filter or a project) hands worker w
    its w-th partition as it is; any other is concatenated and cut into n
    runs of equal rows. The drain stages through spillable handles."""
    schema = child.schema
    per_part = run_partition_tasks(
        child.execute(), lambda _pid, part: drain_spillable(part))
    if len(per_part) != n:
        per_part = [[s for part in per_part for s in part]]
    merged = [concat_spillable(schema, part, by_capacity=True)
              for part in per_part]
    resolve_counts(merged)
    if len(merged) == n:
        cap = stage_capacity(max(b.capacity for b in merged),
                             max(b.num_rows for b in merged))
        return [b if b.capacity == cap else ColumnarBatch(
            schema, [K.rebucket_column(c, b.num_rows, cap)
                     for c in b.columns], b.num_rows) for b in merged]
    batch, = merged
    per = -(-batch.num_rows // n) if batch.num_rows else 0
    cap = stage_capacity(bucket(-(-batch.capacity // n)), per)
    shards = []
    for w in range(n):
        lo = min(w * per, batch.num_rows)
        take = max(0, min(per, batch.num_rows - lo))
        cols = [K.slice_column(c, lo, cap, take) for c in batch.columns]
        shards.append(ColumnarBatch(schema, cols, take))
    return shards


def _append_eval_columns(batch: ColumnarBatch, exprs: List[ex.Expression]
                         ) -> Tuple[ColumnarBatch, List[int]]:
    """Batch extended with evaluated expression columns; plain bound refs
    reuse their existing column instead of duplicating it."""
    cols = list(batch.columns)
    fields = list(batch.schema.fields)
    positions = []
    for i, e in enumerate(exprs):
        if isinstance(e, ex.BoundReference):
            positions.append(e.ordinal)
            continue
        c = ex.materialize(e.eval(batch), batch)
        positions.append(len(cols))
        cols.append(c)
        fields.append(dt.Field(f"_mk{i}", c.dtype, True))
    return ColumnarBatch(dt.Schema(fields), cols, batch.num_rows), positions


class TpuMeshGroupByExec(TpuExec):
    """Fused SPMD group-by over the mesh: per-worker partial aggregate ->
    hash-bucketed ``all_to_all`` -> merge aggregate, one XLA computation
    (mesh.distributed_groupby_fn). Output: one partition per worker with
    disjoint key ownership."""

    CONTRACT = exec_contract(schema="defined", partitioning="defined",
                             bound={"grouping": 0})
    METRICS = exec_metrics("meshGroupByTime")

    def __init__(self, child: TpuExec, grouping: List[ex.Expression],
                 outputs: List[ex.Expression], mesh,
                 window_rows: "Optional[int]" = None):
        super().__init__(child)
        self.mesh = mesh
        self.window_rows = window_rows
        self.grouping_src = grouping
        self.grouping = [bind_refs(e, child.schema) for e in grouping]
        self.outputs = outputs
        # classify each output as a grouping key or an aggregate leaf
        self._spec: List[Tuple[str, int]] = []
        self.agg_leaves: List[lp.AggregateExpression] = []
        for e in outputs:
            inner = e.children[0] if isinstance(e, ex.Alias) else e
            if isinstance(inner, lp.AggregateExpression):
                self._spec.append(("agg", len(self.agg_leaves)))
                self.agg_leaves.append(inner)
            else:
                self._spec.append(("key", _grouping_index(inner, grouping)))
        self.bound_leaf_inputs = [
            bind_refs(l.children[0], child.schema) if l.children else None
            for l in self.agg_leaves]
        self._schema = dt.Schema([
            dt.Field(ex.output_name(e, i), e.dtype, e.nullable)
            for i, e in enumerate(outputs)])

    @property
    def schema(self):
        return self._schema

    @property
    def output_partitions(self) -> int:
        return int(self.mesh.devices.size)

    def execute(self) -> List[Partition]:
        n = int(self.mesh.devices.size)
        shards = shard_for_mesh(self.children[0], n)
        nk = len(self.grouping)
        proj_shards = []
        for shard in shards:
            keys = [ex.materialize(g.eval(shard), shard)
                    for g in self.grouping]
            vals = []
            for leaf, bound in zip(self.agg_leaves, self.bound_leaf_inputs):
                if bound is None:              # COUNT(*): any column works
                    vals.append(keys[0])
                else:
                    vals.append(ex.materialize(bound.eval(shard), shard))
            fields = [dt.Field(f"k{i}", c.dtype, True)
                      for i, c in enumerate(keys)]
            fields += [dt.Field(f"v{i}", c.dtype, True)
                       for i, c in enumerate(vals)]
            proj_shards.append(ColumnarBatch(dt.Schema(fields), keys + vals,
                                             shard.num_rows))
        with trace_span("mesh_groupby", self.metrics, "meshGroupByTime"):
            results = M.run_distributed_groupby(
                self.mesh, proj_shards,
                key_idx=list(range(nk)),
                val_idx=list(range(nk, nk + len(self.agg_leaves))),
                agg_ops=[l.op for l in self.agg_leaves],
                window_rows=self.window_rows)
        out = []
        for r in results:
            # r columns: [k0..k{nk-1}, a0..]; order per output spec
            cols = []
            for kind, idx in self._spec:
                cols.append(r.columns[idx] if kind == "key"
                            else r.columns[nk + idx])
            self.metrics.inc("numOutputRows", r.num_rows)
            out.append(iter([ColumnarBatch(self._schema, cols, r.num_rows)]))
        return out


def _grouping_index(e: ex.Expression, grouping: List[ex.Expression]) -> int:
    for gi, g in enumerate(grouping):
        if e is g or (isinstance(e, ex.ColumnRef) and
                      isinstance(g, ex.ColumnRef) and
                      e.col_name == g.col_name):
            return gi
    raise ValueError(f"output {e!r} is not a grouping expression")


class TpuMeshSortExec(TpuExec):
    """Fused SPMD global sort (mesh.distributed_sort_fn): sample ->
    all_gather bounds -> all_to_all -> local sort, one XLA computation.
    Worker w's partition is the w-th key range, locally sorted."""

    CONTRACT = exec_contract(schema="passthrough", partitioning="defined",
                             bound={"orders": 0})
    METRICS = exec_metrics("meshSortTime")

    def __init__(self, child: TpuExec, orders: List[lp.SortOrder], mesh):
        super().__init__(child)
        self.mesh = mesh
        self.orders = [lp.SortOrder(bind_refs(o.child, child.schema),
                                    o.ascending, o.nulls_first)
                       for o in orders]

    @property
    def schema(self):
        return self.children[0].schema

    @property
    def output_partitions(self) -> int:
        return int(self.mesh.devices.size)

    def execute(self) -> List[Partition]:
        n = int(self.mesh.devices.size)
        shards = shard_for_mesh(self.children[0], n)
        n_payload = len(self.schema)
        ext_shards, positions = [], None
        for shard in shards:
            extb, positions = _append_eval_columns(
                shard, [o.child for o in self.orders])
            ext_shards.append(extb)
        with trace_span("mesh_sort", self.metrics, "meshSortTime"):
            results = M.run_distributed_sort(
                self.mesh, ext_shards, positions,
                [o.ascending for o in self.orders],
                [o.nulls_first for o in self.orders])
        out = []
        for r in results:
            b = ColumnarBatch(self.schema, r.columns[:n_payload], r.num_rows)
            self.metrics.inc("numOutputRows", b.num_rows)
            out.append(iter([b]))
        return out


class TpuMeshJoinExec(TpuShuffledJoinExec):
    """SPMD shuffled join: both sides co-partitioned by one fused
    ``all_to_all`` exchange each (mesh.copartition_exchange_fn), then the
    per-worker partition pairs run the sort-merge join kernels. Inherits the
    per-pair join semantics (incl. full outer, which is correct per worker
    because co-partitioning makes key ownership disjoint)."""

    # co-partitioning happens inside the fused all_to_all, not via child
    # exchanges — so no "copartitioned" extra here
    CONTRACT = exec_contract(schema="defined", partitioning="defined",
                             bound={"left_keys": 0, "right_keys": 1},
                             extras=("join_schema",))
    METRICS = exec_metrics("joinTime", "buildTime", "skewJoinSplits",
                           "runtimeBroadcastJoins", "meshExchangeTime")

    def __init__(self, left: TpuExec, right: TpuExec, how: str,
                 left_keys, right_keys, condition, mesh,
                 part_left_keys=None, part_right_keys=None):
        super().__init__(left, right, how, left_keys, right_keys, condition)
        self.mesh = mesh
        # partitioning keys may carry promotion casts so both sides hash
        # the same type; they default to the join keys
        self.part_left_keys = [bind_refs(e, left.schema)
                               for e in (part_left_keys or left_keys)]
        self.part_right_keys = [bind_refs(e, right.schema)
                                for e in (part_right_keys or right_keys)]

    @property
    def output_partitions(self) -> int:
        return int(self.mesh.devices.size)

    def _copartition(self, child: TpuExec, part_keys) -> List[ColumnarBatch]:
        n = int(self.mesh.devices.size)
        shards = shard_for_mesh(child, n)
        n_payload = len(child.schema)
        ext, positions = [], None
        for shard in shards:
            extb, positions = _append_eval_columns(shard, part_keys)
            ext.append(extb)
        with trace_span("mesh_exchange", self.metrics, "meshExchangeTime"):
            co = M.run_copartition_exchange(self.mesh, ext, positions)
        return [ColumnarBatch(child.schema, b.columns[:n_payload], b.num_rows)
                for b in co]

    def execute(self) -> List[Partition]:
        l_co = self._copartition(self.children[0], self.part_left_keys)
        r_co = self._copartition(self.children[1], self.part_right_keys)
        return [self._join_copart(iter([lb]), iter([rb]))
                for lb, rb in zip(l_co, r_co)]
