"""Shuffle exchange: repartition batches between stages.

Reference: ``GpuShuffleExchangeExec`` (SURVEY.md §2.6) builds a
GpuShuffleDependency with a GpuPartitioning and moves partition slices through
the shuffle manager; ``RapidsCachingWriter`` keeps slices in the spillable
device store instead of writing shuffle files
(RapidsShuffleInternalManager.scala:73-192).

The exchange is TWO-PLANE (docs/shuffle.md, conf
``spark.rapids.tpu.sql.shuffle.plane``):

* **ICI** — with an active device mesh, the whole exchange lowers to one
  fused ``all_to_all`` program (parallel/mesh.run_partition_exchange):
  partitioned rows move device->device over the interconnect, uncompressed,
  and the host reads back ONE counts array per exchange. The TPU analog of
  the reference's device store + RDMA transport (SURVEY.md §2.8, §5).
* **DCN** — the host-staged path below: map side splits each batch with a
  partitioner (slice sizing pipelined through a PipelineWindow so the map
  phase pays O(1) host syncs, not one per batch) and registers the slices
  as spillable buffers; reduce side pulls and concatenates. Multi-process,
  the TCP transfer server (shuffle/transport.py) moves the bytes with the
  shuffle/compression.py codec on the wire; this plane also carries the
  elastic-retry and AQE skew-split machinery the ICI plane does not need.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Dict, List, Optional, Tuple

from ..analysis.contracts import exec_contract
from ..analysis.lockdep import named_lock
from ..columnar import dtypes as dt
from ..columnar.batch import ColumnarBatch
from ..exec.spill import (OUTPUT_FOR_SHUFFLE_PRIORITY, BufferCatalog,
                          SpillableColumnarBatch)
from ..ops import expressions as ex
from ..plan.physical import (Partition, TpuExec, bind_refs, concat_batches,
                             exec_metrics)
from ..exec.tracing import trace_span
from .partitioning import (HashPartitioner, RoundRobinPartitioner,
                           SinglePartitioner, TpuPartitioner)


# ---------------------------------------------------------------------------
# Process-lifetime plane totals (service/telemetry harvest): which plane
# exchanges actually took, how many bytes each moved, and how long — the
# numbers behind the ``tpu_shuffle_gbps{plane=...}`` gauge and the bench
# artifacts' shuffle report. Bumped once per exchange at completion
# boundaries, never per batch.
# ---------------------------------------------------------------------------

log = logging.getLogger("spark_rapids_tpu.shuffle")

_PLANE_TOTALS: Dict[str, float] = {
    "ici_exchanges": 0, "dcn_exchanges": 0,
    "ici_bytes": 0, "dcn_bytes": 0,
    "ici_seconds": 0.0, "dcn_seconds": 0.0,
}
_plane_mu = named_lock("shuffle.exchange._plane_mu")


def note_plane(plane: str, bytes_moved: int, seconds: float) -> None:
    """Record one completed exchange on ``plane`` ('ici' | 'dcn')."""
    with _plane_mu:
        _PLANE_TOTALS[f"{plane}_exchanges"] += 1
        _PLANE_TOTALS[f"{plane}_bytes"] += int(bytes_moved)
        _PLANE_TOTALS[f"{plane}_seconds"] += float(seconds)


def plane_totals() -> Dict[str, float]:
    """Cumulative per-plane exchange totals for this process."""
    with _plane_mu:
        return dict(_PLANE_TOTALS)


# ---------------------------------------------------------------------------
# Stage-boundary exchange statistics (docs/observability.md §8): what an
# exchange ACTUALLY produced, per reduce partition — the feed AQE's
# coalesce/skew decisions read (ROADMAP item 2), recorded at
# materialization on all three planes (local DCN, distributed, ICI).
# ---------------------------------------------------------------------------

#: byte-scale buckets for the per-partition size histogram (the default
#: registry buckets are second-scale)
_PARTITION_BYTE_BUCKETS = (1 << 10, 1 << 14, 1 << 17, 1 << 20, 1 << 23,
                           1 << 26, 1 << 30, float("inf"))


def compute_stage_stats(stage_id: Optional[int], plane: str,
                        rows: List[int], bytes_: List[int],
                        query_id: Optional[str] = None) -> Dict[str, Any]:
    """Derive the stage-boundary statistics of one materialized exchange
    from its per-partition row/byte observations: partition count, p50
    and max partition bytes, and the skew factor (max partition bytes
    over the MEAN partition bytes — 1.0 is perfectly balanced; the AQE
    skew splitter compares this shape against its threshold)."""
    import statistics
    n = len(bytes_)
    total_b = int(sum(bytes_))
    total_r = int(sum(rows))
    p50 = float(statistics.median(bytes_)) if bytes_ else 0.0
    mx = int(max(bytes_)) if bytes_ else 0
    mean = total_b / n if n else 0.0
    skew = round(mx / mean, 4) if mean > 0 else 1.0
    return {"stageId": stage_id, "queryId": query_id, "plane": plane,
            "partitions": n,
            "rows": [int(r) for r in rows],
            "bytes": [int(b) for b in bytes_],
            "totalRows": total_r, "totalBytes": total_b,
            "p50Bytes": p50, "maxBytes": mx, "skew": skew}


def publish_stage_stats(stats: Dict[str, Any]) -> None:
    """Surface one exchange's stage statistics into the continuous
    telemetry layer: per-partition bytes into the
    ``tpu_exchange_partition_bytes`` histogram, the derived shape into
    the last-exchange gauges, and a flight-recorder breadcrumb (kind
    ``stage``, query id auto-stamped by the funnel). Bumped once per
    exchange at materialization, never per batch."""
    from ..service.telemetry import MetricsRegistry, flight_record
    flight_record("stage", f"stage-{stats.get('stageId')}",
                  {k: stats[k] for k in ("plane", "partitions", "totalRows",
                                         "totalBytes", "maxBytes", "skew")})
    try:
        reg = MetricsRegistry.get()
        plane = stats["plane"]
        h = reg.histogram("tpu_exchange_partition_bytes",
                          "post-shuffle partition sizes at exchange "
                          "materialization", _PARTITION_BYTE_BUCKETS,
                          plane=plane)
        for b in stats["bytes"]:
            h.observe(b)
        reg.gauge("tpu_exchange_skew_factor",
                  "last exchange's max/mean partition-size ratio",
                  plane=plane).set(stats["skew"])
        reg.gauge("tpu_exchange_p50_bytes",
                  "last exchange's median partition bytes",
                  plane=plane).set(stats["p50Bytes"])
        reg.gauge("tpu_exchange_max_bytes",
                  "last exchange's largest partition bytes",
                  plane=plane).set(stats["maxBytes"])
    except Exception:
        pass               # telemetry must never fail the exchange


def assign_stage(node) -> None:
    """Draw ``node``'s query id + stage id for THIS execution from the
    ambient query context (exec/query_context.py). Exchange ``execute()``
    runs on the single driving thread during plan-tree construction, so
    stage ids are deterministic per query — lockstep workers number
    their exchanges identically."""
    from ..exec import query_context as qc
    ctx = qc.current()
    node.query_id = ctx.query_id if ctx is not None else None
    node.stage_id = ctx.next_stage_id() if ctx is not None else None
    node.stage_stats = None            # fresh per execution
    node._aqe_decisions = []           # fresh per execution (plan/aqe.py)
    if node.stage_id is not None:
        # a stage-id draw is a lockstep-relevant event: fold it into the
        # per-query divergence digest (analysis/divergence.py)
        from ..analysis import divergence
        divergence.note_event(
            f"stage-id:{node.stage_id}:{type(node).__name__}",
            query_id=node.query_id)


def record_local_shuffle_stats(node, shuffle) -> None:
    """Per-partition rows/bytes from a LocalShuffle's registered
    map-output slices (the local DCN plane's materialization boundary);
    commits + publishes the node's stage statistics. Gated by
    ``sql.metrics.enabled`` — the dataSize AQE feed stays load-bearing
    regardless."""
    from ..exec.metrics import metrics_enabled
    if not metrics_enabled():
        return
    rows: List[int] = []
    bytes_: List[int] = []
    for p in range(node.num_partitions):
        r = b = 0
        for s in shuffle.slices[p]:
            try:
                r += int(s.num_rows)
            except Exception:
                pass           # a closed/lazy slice: rows stay partial
            b += int(getattr(s, "size_bytes", 0) or 0)
        rows.append(r)
        bytes_.append(b)
    node.stage_stats = compute_stage_stats(
        node.stage_id, "dcn", rows, bytes_, query_id=node.query_id)
    publish_stage_stats(node.stage_stats)
    _note_aqe_stats(node)


def _note_aqe_stats(node) -> None:
    """Feed one committed materialization into AQE's fingerprint-keyed
    stage history (plan/aqe.py) — what lets a repeat execution of the
    same structural exchange decide from observed shape before its map
    phase runs (the ICI skew fallback). Best-effort."""
    try:
        from ..plan import aqe
        aqe.note_stage_stats(node)
    except Exception:
        pass               # the history feed must never fail the exchange


def collect_stage_stats(root) -> List[Dict[str, Any]]:
    """Every exchange's stage statistics in an executed plan tree, in
    tree order with the operator name attached —
    ``session.last_stage_stats()``'s data, shaped so the AQE feedback
    loop (ROADMAP item 2) consumes it without rework."""
    out: List[Dict[str, Any]] = []

    def walk(node) -> None:
        st = getattr(node, "stage_stats", None)
        if st:
            out.append({"operator": type(node).__name__, **st})
        for c in getattr(node, "children", ()):
            walk(c)

    walk(root)
    return out


def stage_stats_annotations(root) -> Dict[str, List[str]]:
    """Per-exchange EXPLAIN ANALYZE annotations keyed by the same
    root->node class-name path the contract validator and
    ``stage_compiler.fusion_annotations`` use."""
    out: Dict[str, List[str]] = {}

    def walk(node, path: str, idx: Optional[int] = None) -> None:
        name = type(node).__name__
        here = f"{path}/{idx}.{name}" if path else name
        st = getattr(node, "stage_stats", None)
        if st:
            out[here] = [
                f"* stage {st.get('stageId')} exchange [{st['plane']}]: "
                f"partitions={st['partitions']} rows={st['totalRows']} "
                f"p50Bytes={int(st['p50Bytes'])} "
                f"maxBytes={st['maxBytes']} skew={st['skew']}"]
        for i, c in enumerate(getattr(node, "children", ())):
            walk(c, here, i)

    walk(root, "")
    return out


def shuffle_report(root) -> List[Dict[str, Any]]:
    """Per-exchange shuffle accounting for an executed plan tree: which
    plane each exchange took, bytes written/read, write/fetch seconds and
    the resulting GB/s — the bench artifacts' per-query shuffle story."""
    out: List[Dict[str, Any]] = []

    def walk(node) -> None:
        if isinstance(node, TpuShuffleExchangeExec):
            m = node.metrics
            bw = m.get("shuffleBytesWritten", 0) or 0
            br = m.get("shuffleBytesRead", 0) or 0
            ws = m.get("shuffleWriteTime", 0.0) or 0.0
            fw = m.get("fetchWaitTime", 0.0) or 0.0
            entry: Dict[str, Any] = {
                "exec": type(node).__name__,
                "plane": getattr(node, "plane_used", None),
                "partitions": node.num_partitions,
                "bytesWritten": int(bw), "bytesRead": int(br),
                "writeTimeS": round(float(ws), 4),
                "fetchWaitS": round(float(fw), 4),
            }
            # GB/s definition matches note_plane / tpu_shuffle_gbps:
            # bytes enter the exchange ONCE (the write side) over total
            # exchange seconds — read bytes are reported but not summed
            # into the rate, or the same byte would count twice
            rate = m.gbps(("shuffleBytesWritten",),
                          ("shuffleWriteTime", "fetchWaitTime"))
            if rate is not None:
                entry["gbps"] = round(rate, 6)
            out.append(entry)
        for c in getattr(node, "children", ()):
            walk(c)

    walk(root)
    return out


class LocalShuffle:
    """In-process shuffle state: (reduce partition) -> list of spillable
    slices (ShuffleBufferCatalog analog, scoped to one exchange).

    ``durable`` (conf ``spark.rapids.tpu.sql.shuffle.durable``) keeps
    slices REGISTERED after a read instead of closing them, and pins the
    map outputs through the spill store's disk tier at map-phase end —
    so a reduce-side stage retry re-reads the durable outputs instead of
    re-running the map stage (docs/resilience.md). Slices free at
    ``close_pending`` (exchange cleanup) as before."""

    def __init__(self, num_partitions: int,
                 catalog: Optional[BufferCatalog] = None,
                 durable: bool = False):
        self.num_partitions = num_partitions
        self.catalog = catalog or BufferCatalog.get()
        self.durable = durable
        self.slices: Dict[int, List[SpillableColumnarBatch]] = {
            p: [] for p in range(num_partitions)}

    def write(self, partitioner: TpuPartitioner, batch: ColumnarBatch) -> None:
        for p, piece in enumerate(partitioner.split(batch)):
            if piece.num_rows > 0:
                self.slices[p].append(SpillableColumnarBatch(
                    piece, OUTPUT_FOR_SHUFFLE_PRIORITY, self.catalog))

    def write_deferred(self, window, partitioner: TpuPartitioner,
                       batch: ColumnarBatch) -> None:
        """Pipelined map-side write: dispatch the fused device split now,
        park the slice-sizing scalar in ``window`` (a PipelineWindow), and
        register the slices when the batched readback lands — batch k+1's
        split dispatches before batch k's sizing resolves, so a map phase
        of B batches pays O(1) packed syncs instead of B blocking ones."""
        deferred = partitioner.split_deferred(batch)
        if deferred is None:          # nothing to defer (empty / single)
            self.write(partitioner, batch)
            return
        counts, make_pieces = deferred

        def land(host_counts):
            for p, piece in enumerate(make_pieces(host_counts)):
                if piece.num_rows > 0:
                    self.slices[p].append(SpillableColumnarBatch(
                        piece, OUTPUT_FOR_SHUFFLE_PRIORITY, self.catalog))

        window.push(land, counts)

    def read(self, p: int, schema: dt.Schema) -> Partition:
        pending = self.slices[p]
        batches = []
        for s in pending:
            batches.append(s.get_batch())
            if not self.durable:
                s.close()          # durable outputs stay re-fetchable
        if batches:
            out = concat_batches(schema, batches)
            if self.durable:
                # get_batch re-promoted the pinned slices DISK->DEVICE;
                # re-pin them NOW (before yielding — an abandoned
                # consumer must not strand them device-resident) so only
                # the in-flight partition holds HBM, keeping
                # pin_outputs_to_disk's discipline across reads. Safe
                # even when ``out`` aliases a demoted buffer's arrays
                # (single-slice concat short-circuit): jax arrays are
                # immutable and acquire_batch marked the batch shared,
                # so no downstream program can donate them.
                del batches
                for s in pending:
                    s.pin_to_disk()
            yield out

    def pin_outputs_to_disk(self) -> int:
        """Durable tier: push every registered slice through to the disk
        tier of the spill store (the checkpoint write of SURVEY §5
        "Checkpoint / resume" — paid once at map-phase end, bounding the
        memory the retained outputs hold). Returns bytes pinned."""
        pinned = 0
        for pending in self.slices.values():
            for s in pending:
                if not s._closed:
                    pinned += s.pin_to_disk()
        return pinned

    def read_slices(self, p: int, lo: int, hi: int,
                    schema: dt.Schema) -> Partition:
        """A mapper-subset read of reduce partition ``p``: slices
        [lo, hi) only — the partial-mapper partition spec behind AQE skew
        splitting (ShuffledBatchRDD.scala:202 PartialMapperPartitionSpec)."""
        batches = []
        for s in self.slices[p][lo:hi]:
            batches.append(s.get_batch())
            s.close()
        if batches:
            yield concat_batches(schema, batches)

    def read_row_chunk(self, p: int, idx: int, chunk: int, n_chunks: int,
                       schema: dt.Schema) -> Partition:
        """Row-range read of one slice of partition ``p``: chunk
        ``chunk``/``n_chunks`` by row position — sub-mapper granularity
        for the single-giant-slice skew case (finer than the reference's
        map-block granularity; columnar row gathers make it cheap). The
        slice is SHARED by its chunks, so it is not closed here —
        ``close_pending`` releases it at exchange cleanup."""
        import jax.numpy as jnp
        from ..columnar.column import bucket
        from ..ops import kernels as K
        b = self.slices[p][idx].get_batch()
        n = b.num_rows
        lo = (n * chunk) // n_chunks
        hi = (n * (chunk + 1)) // n_chunks
        count = hi - lo
        if count <= 0:
            return
        cap = bucket(max(count, 1))
        live = jnp.arange(cap) < count
        idxs = jnp.where(live, jnp.arange(cap, dtype=jnp.int32) + lo, 0)
        cols = [K.gather_column(c, idxs, out_valid=live)
                for c in b.columns]
        yield ColumnarBatch(schema, cols, count)

    def close_pending(self) -> None:
        """Release slices never pulled (early-terminating consumers)."""
        for pending in self.slices.values():
            for s in pending:
                if not s._closed:
                    s.close()


class TpuShuffleExchangeExec(TpuExec):
    """Repartition(n) / repartition(n, cols) exchange.

    ``adaptive_ok``: the planner marks exchanges whose consumer tolerates a
    runtime-reduced partition count (aggregates: merged partitions keep key
    ownership disjoint) — those coalesce small post-shuffle partitions from
    OBSERVED map-side sizes, the AQE + GpuCustomShuffleReaderExec behavior
    (GpuOverrides.scala:1920). Join exchanges stay fixed: both sides must
    keep identical partitioning."""

    CONTRACT = exec_contract(schema="passthrough", partitioning="defined",
                             extras=("exchange_plane",))
    METRICS = exec_metrics("dataSize", "shuffleWriteTime", "fetchWaitTime",
                           "shuffleBytesWritten", "shuffleBytesRead",
                           "iciExchanges", "dcnExchanges",
                           "skewSplitPartitions", "skewSplitTasks",
                           "coalescedPartitions", "fetchFailedRetries",
                           "stageRetries")

    def __init__(self, child: TpuExec, num_partitions: int,
                 by: Optional[List[ex.Expression]] = None,
                 adaptive_ok: bool = False,
                 adaptive_min_bytes: Optional[int] = None,
                 plane: str = "auto", mesh=None,
                 split_depth: Optional[int] = None):
        super().__init__(child)
        self.num_partitions = max(1, num_partitions)
        self.by = [bind_refs(e, child.schema) for e in by] if by else None
        self.adaptive_ok = adaptive_ok
        # resolved at PLAN time from the session conf (exec-level TpuConf()
        # would read global defaults, not the session's settings)
        self.adaptive_min_bytes = adaptive_min_bytes
        self.coalesced_to: Optional[int] = None    # runtime observation
        # data-plane routing (spark.rapids.tpu.sql.shuffle.plane), also
        # plan-time-resolved: 'auto' rides the mesh the planner handed us
        # (None when no mesh is active or the stage is too large to stage
        # device-resident), 'ici' forces collectives, 'dcn' forces the
        # host/TCP path. plane_used records the runtime decision.
        self.plane = plane
        self.mesh = mesh
        self.split_depth = split_depth
        self.plane_used: Optional[str] = None
        # query-lifecycle identity + the exchange's stage-boundary
        # statistics (docs/observability.md §8): assigned at execute time
        # from the ambient query context, refreshed per execution (cached
        # plan trees re-execute under new query ids)
        self.query_id: Optional[str] = None
        self.stage_id: Optional[int] = None
        self.stage_stats: Optional[Dict[str, Any]] = None

    @property
    def schema(self):
        return self.children[0].schema

    @property
    def output_partitions(self) -> int:
        return self.num_partitions

    def _make_partitioner(self) -> TpuPartitioner:
        if self.num_partitions == 1:
            return SinglePartitioner()
        if self.by:
            return HashPartitioner(self.num_partitions, self.by)
        return RoundRobinPartitioner(self.num_partitions)

    def _split_window_depth(self) -> int:
        if self.split_depth is not None:
            return max(1, int(self.split_depth))
        from .. import config as cfg
        return max(1, int(cfg.TpuConf().get(cfg.SHUFFLE_PIPELINE_DEPTH)))

    def _run_map_phase(self, shuffle) -> None:
        """Map side: split every upstream batch and register the slices,
        one task per upstream partition, drained concurrently (shared by
        the local, distributed, and skew-split execute forms). Slice
        sizing is PIPELINED: each task parks its batches' packed split
        counts in a PipelineWindow, so the sizing readbacks land in O(1)
        batched resolves per task instead of one blocking readback per
        batch (the host-plane half of the device-resident shuffle)."""
        from ..analysis import faults
        from ..exec import recovery
        from ..exec.pipeline import PipelineWindow
        from ..exec.tasks import run_partition_tasks
        partitioner = self._make_partitioner()
        depth = self._split_window_depth()
        written: List[int] = []            # per-task input bytes
        t0 = time.perf_counter()

        def map_task(pid, part):
            win = PipelineWindow(depth, metrics=self.metrics)
            local_bytes = 0
            for bi, batch in enumerate(part):
                if faults.armed() and faults.fire("task.poison",
                                                  pid=pid, batch=bi):
                    raise recovery.InjectedTaskFault(
                        f"injected task poison (partition {pid}, "
                        f"batch {bi})")
                shuffle.write_deferred(win, partitioner, batch)
                local_bytes += batch.device_size_bytes()
            win.flush()
            written.append(local_bytes)    # GIL-atomic append

        with trace_span("shuffle_write", self.metrics, "shuffleWriteTime"):
            run_partition_tasks(self.children[0].execute(), map_task)
        if getattr(shuffle, "durable", False):
            shuffle.pin_outputs_to_disk()
        # metrics commit only on map-phase SUCCESS: a failed attempt's
        # partial bytes must not pollute dataSize (the AQE broadcast
        # switch reads it) or the shuffle write totals on a recovered run
        total = sum(written)
        self.metrics.inc("dataSize", total)
        self.metrics.inc("shuffleBytesWritten", total)
        self.metrics.inc("dcnExchanges")
        note_plane("dcn", total, time.perf_counter() - t0)

    def _assign_stage(self) -> None:
        assign_stage(self)

    def _finish_stage_stats(self, plane: str, rows: List[int],
                            bytes_: List[int]) -> None:
        """Commit + publish this exchange's materialization statistics
        (stats collection rides the sql.metrics.enabled gate; the
        dataSize AQE feed stays load-bearing regardless)."""
        from ..exec.metrics import metrics_enabled
        if not metrics_enabled():
            return
        self.stage_stats = compute_stage_stats(
            self.stage_id, plane, rows, bytes_, query_id=self.query_id)
        publish_stage_stats(self.stage_stats)
        _note_aqe_stats(self)

    def _record_local_stats(self, shuffle: "LocalShuffle") -> None:
        record_local_shuffle_stats(self, shuffle)

    def execute(self) -> List[Partition]:
        from .manager import WorkerContext
        self._assign_stage()
        ctx = WorkerContext.current
        plane = self._resolve_plane(ctx)
        self.plane_used = plane
        if ctx is not None:
            return self._execute_distributed(ctx)
        if plane == "ici":
            return self._execute_ici()
        shuffle = self._local_map_with_retry()
        self._record_local_stats(shuffle)
        groups = self._reduce_groups(shuffle)
        return [self._read_group(shuffle, g) for g in groups]

    def _local_map_with_retry(self) -> LocalShuffle:
        """Local map phase under the stage-retry discipline
        (exec/recovery.py): an injected task fault or a recoverable
        upstream failure discards the half-written shuffle and
        re-executes the map from its (deterministic or not — nothing
        was consumed yet) inputs. Shared by :meth:`execute` and the
        skew-split path."""
        from ..exec import recovery

        def attempt():
            # an OUTER exchange's stage retry re-executes this whole
            # subtree: a stale _shuffle from the prior execution would be
            # orphaned by the reassignment below with its slices still
            # registered in the catalog — release it first (idempotent;
            # the normal path nulls _shuffle at query cleanup)
            stale = getattr(self, "_shuffle", None)
            if stale is not None:
                stale.close_pending()
            shuffle = LocalShuffle(self.num_partitions,
                                   durable=recovery.shuffle_durable())
            self._shuffle = shuffle
            self._run_map_phase(shuffle)
            return shuffle

        def discard(exc, attempt_no):
            self.metrics.inc("stageRetries")
            sh = getattr(self, "_shuffle", None)
            if sh is not None:
                sh.close_pending()     # release the partial map outputs

        return recovery.retry_stage("shuffle-map", attempt,
                                    on_retry=discard)

    # -- plane routing -------------------------------------------------------

    def _ici_capable(self) -> bool:
        """The fused ICI exchange carries flat primitive/string columns
        (mesh._rebuild_columns' array protocol); structs and other nested
        layouts stay on the host plane."""
        for f in self.schema:
            t = f.dtype
            if dt.is_struct(t) or dt.is_map(t) or dt.is_array(t):
                return False
            if t.var_width and t != dt.STRING:
                return False
        return True

    def _resolve_plane(self, ctx) -> str:
        """'ici' or 'dcn' for THIS execution. ``auto`` takes collectives
        exactly when the planner handed us a mesh and the shape qualifies;
        a forced ``ici`` that cannot run is a loud error, never a silent
        downgrade (the mesh.enabled=true contract)."""
        plane = (self.plane or "auto").lower()
        if plane == "dcn":
            return "dcn"
        forced = plane == "ici"
        if ctx is not None:
            # multi-process workers reach each other over DCN only; their
            # chips are not one mesh
            if forced:
                raise RuntimeError(
                    "spark.rapids.tpu.sql.shuffle.plane=ici is invalid "
                    "under a multi-process WorkerContext: peer chips are "
                    "not one ICI mesh — use auto or dcn")
            return "dcn"
        if self.mesh is None or int(self.mesh.devices.size) < 2:
            if forced:
                raise RuntimeError(
                    "spark.rapids.tpu.sql.shuffle.plane=ici but no device "
                    "mesh is active (spark.rapids.tpu.sql.mesh.enabled)")
            return "dcn"
        # mesh-participant loss (real or chaos-injected): the ICI plane
        # declines GRACEFULLY to DCN under auto — dispatching a
        # collective onto a mesh missing a participant would hang, and
        # the host plane carries the exchange correctly, just slower.
        # Forced ici stays a loud error (the mesh.enabled=true contract)
        from ..analysis import faults
        from ..exec import recovery
        if faults.armed() and faults.fire("mesh.drop"):
            recovery.note_mesh_lost(faults.INJECTED_MESH_DROP_REASON)
        lost = recovery.mesh_lost()
        if lost is not None:
            if forced:
                raise RuntimeError(
                    "spark.rapids.tpu.sql.shuffle.plane=ici but the ICI "
                    f"mesh lost a participant ({lost})")
            return "dcn"
        if self.num_partitions == 1:
            return "dcn"          # single sink: nothing to exchange
        if not self._ici_capable():
            if forced:
                raise RuntimeError(
                    "spark.rapids.tpu.sql.shuffle.plane=ici but the "
                    f"exchange schema [{self.schema}] carries nested "
                    "columns the fused collective cannot move")
            return "dcn"
        return "ici"

    def would_use_ici(self) -> bool:
        """Plane this exchange WILL take if executed now (consumers like
        the AQE skew splitter ask before running the map phase: the
        device-resident plane has no per-slice observed sizes to split
        on, so skew handling stays a host-plane feature)."""
        from .manager import WorkerContext
        return self._resolve_plane(WorkerContext.current) == "ici"

    def _execute_ici(self) -> List[Partition]:
        """Device-resident exchange: shard the child across the mesh,
        route every row to its reduce partition's owning worker through
        one fused ``all_to_all`` program, and slice each worker's
        pid-sorted rows into its owned partitions. Payload bytes never
        touch the host; the one readback is the counts array."""
        from ..parallel import mesh as M
        from ..parallel.mesh_exec import shard_for_mesh
        mesh = self.mesh
        n = int(mesh.devices.size)
        with trace_span("shuffle_write", self.metrics, "shuffleWriteTime"):
            shards = shard_for_mesh(self.children[0], n)
            moved = 0
            for s in shards:
                moved += s.device_size_bytes()
                self.metrics.inc("dataSize", s.device_size_bytes())
            self.metrics.inc("shuffleBytesWritten", moved)
            partitioner = self._make_partitioner()
            pids = [partitioner.partition_ids(s) for s in shards]
            results = self._ici_results = M.run_partition_exchange(
                mesh, shards, pids, self.num_partitions)
        # the process plane totals are fed where the stage runs
        # (parallel/mesh.run_stage), as for every SPMD stage
        self.metrics.inc("iciExchanges")
        # stage-boundary statistics from the ONE counts readback that
        # already came home: per-partition rows are the column sums of
        # the [n, num_partitions] counts; bytes are estimated from the
        # exchange's fixed-width row footprint (moved / total rows) —
        # the ICI plane never stages per-slice host bytes to measure
        counts = [r[1] for r in results]
        rows = [int(sum(int(c[p]) for c in counts))
                for p in range(self.num_partitions)]
        total_rows = sum(rows)
        bpr = (moved / total_rows) if total_rows else 0.0
        self._finish_stage_stats("ici", rows,
                                 [int(r * bpr) for r in rows])

        def gen(p: int) -> Partition:
            from ..columnar.column import bucket
            from ..ops import kernels as K
            cols_w, counts_w = self._ici_results[p % n]
            count = int(counts_w[p])
            if count <= 0:
                return
            offset = int(counts_w[:p].sum())
            with trace_span("shuffle_fetch", self.metrics, "fetchWaitTime"):
                pcap = bucket(count)
                cols = [K.slice_column(c, offset, pcap, count)
                        for c in cols_w]
                out = ColumnarBatch(self.schema, cols, count)
            self.metrics.inc("shuffleBytesRead", out.device_size_bytes())
            yield out

        return [gen(p) for p in range(self.num_partitions)]

    def execute_skew(self, threshold: int,
                     factor: Optional[float] = None
                     ) -> List[List[Partition]]:
        """AQE skew-split form of :meth:`execute` (local mode): run the
        map phase, then return per reduce partition a LIST of
        sub-partitions — one when under ``threshold`` observed bytes,
        multiple mapper-subset reads (partial-mapper partition specs,
        ShuffledBatchRDD.scala:202) when a hot partition exceeds it. The
        caller (skewed join) keeps the other side aligned per ORIGINAL
        partition index. Unsplit partitions keep the elastic-recovery
        read path; SPLIT chunks cannot re-run the map phase safely (other
        chunks of the same partition may already be consumed against the
        old slice boundaries), so a lost buffer there aborts loudly."""
        from .manager import WorkerContext
        assert WorkerContext.current is None, \
            "skew split is a local-mode path"
        self._assign_stage()
        self.plane_used = "dcn"       # skew split is a host-plane feature
        shuffle = self._local_map_with_retry()
        self._record_local_stats(shuffle)
        # effective cut line: at least ``threshold`` bytes, raised to
        # ``factor x median partition bytes`` when that is higher — a
        # partition must be both large AND an outlier among its siblings
        # (plan/aqe.py's skewedPartitionFactor rule)
        totals = [sum(s.size_bytes for s in shuffle.slices[p])
                  for p in range(self.num_partitions)]
        import statistics
        from ..plan import aqe
        median = float(statistics.median(totals)) if totals else 0.0
        eff = aqe.effective_skew_threshold(threshold, factor, median)
        out: List[List[Partition]] = []
        for p in range(self.num_partitions):
            sizes = [s.size_bytes for s in shuffle.slices[p]]
            total = totals[p]
            if total <= eff:
                out.append([self._read_group(shuffle, [p])])
                continue
            if len(sizes) < 2:
                # one giant map slice: split by row ranges instead
                n_chunks = min(-(-total // eff), 64)
                chunks = [shuffle.read_row_chunk(p, 0, c, n_chunks,
                                                 self.schema)
                          for c in range(n_chunks)]
            else:
                # split on slice (mapper-output) boundaries into chunks
                # of ~eff bytes, at least one slice each
                chunks = []
                lo = 0
                acc = 0
                for i, sz in enumerate(sizes):
                    acc += sz
                    if acc >= eff and i + 1 > lo:
                        chunks.append(shuffle.read_slices(p, lo, i + 1,
                                                          self.schema))
                        lo, acc = i + 1, 0
                if lo < len(sizes):
                    chunks.append(shuffle.read_slices(p, lo, len(sizes),
                                                      self.schema))
            self.metrics.inc("skewSplitPartitions")
            self.metrics.inc("skewSplitTasks", len(chunks))
            out.append([self._loud_chunk(c, p) for c in chunks])
        return out

    def _loud_chunk(self, chunk: Partition, p: int) -> Partition:
        """Split-chunk reads abort with CONTEXT on lost buffers instead
        of recovering — re-running the map phase would move the slice/row
        boundaries under chunks that were already consumed."""
        from ..exec.spill import BufferLostError
        try:
            yield from chunk
        except BufferLostError as e:  # lint: recover-ok deliberate FAIL_QUERY: consumed sibling chunks pin the old slice boundaries, re-execution is unsafe here
            raise RuntimeError(
                f"skew-split chunk of shuffle partition {p} lost a "
                f"buffer; map-stage retry is unsafe for split chunks "
                f"(consumed siblings pin the old boundaries): {e}") from e

    def plan_fingerprint(self) -> str:
        """Structural hash of this exchange's plan subtree: exec class
        names + output schemas + the partitioning KEY EXPRESSIONS,
        recursively. Deliberately EXCLUDES data-dependent detail (row
        counts, shard paths) so every worker running the same logical
        query computes the same value, while structurally different
        exchanges — including two identical trees hash-partitioned on
        different columns, the exact silent-wrong-data signature —
        compute different ones."""
        import hashlib

        def desc(node) -> str:
            try:
                sch = ",".join(f"{f.name}:{f.dtype.name}"
                               for f in node.schema)
            except Exception:
                sch = "?"
            kids = ";".join(desc(c) for c in node.children)
            return f"{type(node).__name__}[{sch}]({kids})"
        by = ",".join(repr(e) for e in self.by) if self.by else ""
        s = f"{desc(self)}|n={self.num_partitions}|by={by}"
        return hashlib.sha1(s.encode()).hexdigest()[:16]

    @staticmethod
    def _subtree_allocates_shuffle_ids(node) -> bool:
        """True when ``node``'s subtree holds an exchange that would
        allocate a lockstep shuffle id if re-executed (distributed
        mode's :class:`DistributedShuffle` constructor)."""
        if isinstance(node, TpuShuffleExchangeExec):
            return True
        return any(TpuShuffleExchangeExec._subtree_allocates_shuffle_ids(c)
                   for c in node.children)

    def _execute_distributed(self, ctx) -> List[Partition]:
        """Multi-process mode: map slices register in the worker's
        ShuffleStore (RapidsCachingWriter), reduce partitions this worker
        OWNS read local + peer slices (RapidsCachingReader split); the
        other partitions are empty here — their owners produce them.
        Adaptive coalescing stays off: partition->worker ownership must be
        identical on every worker."""
        from ..exec import recovery
        from .manager import DistributedShuffle
        # the shuffle is created ONCE (its id comes from the lockstep
        # counter — a retry must not consume another id); only the map
        # run retries, resetting this worker's partial outputs first.
        # Safe because peers cannot have fetched yet: completion is only
        # marked after the retry loop succeeds
        shuffle = self._shuffle = DistributedShuffle(
            self.num_partitions, ctx, fingerprint=self.plan_fingerprint())

        def attempt():
            self._run_map_phase(shuffle)

        def discard(exc, attempt_no):
            self.metrics.inc("stageRetries")
            shuffle.reset_outputs()

        # a retry re-executes the whole child subtree; if that subtree
        # holds ANOTHER exchange, re-running it would consume a fresh
        # lockstep shuffle id on THIS worker only, desyncing the id /
        # fingerprint streams from peers (each budget attempt would then
        # burn a full fetch timeout against a shuffle no peer completes).
        # Query-namespaced ids (shuffle/manager.py) do NOT lift this:
        # namespacing fixes id COLLISION across queries, not lockstep
        # AGREEMENT within one — the retried child exchange is a
        # distributed barrier that peers (who saw no failure) never
        # re-enter, so one worker re-running it alone can never complete
        # it under any namespace. Recovery stays declined — the fault
        # propagates unmasked instead of wedging (docs/resilience.md
        # "nested-exchange maps")
        nested = self._subtree_allocates_shuffle_ids(self.children[0])

        def gate(exc):
            if nested:
                log.warning(
                    "shuffle-map retry declined: child subtree holds "
                    "another exchange (lockstep id streams cannot "
                    "re-execute on one worker); propagating %s",
                    type(exc).__name__)
                return False
            return True

        recovery.retry_stage("shuffle-map", attempt, on_retry=discard,
                             retryable=gate)
        shuffle.finish_writes()
        self._record_distributed_stats(shuffle, ctx)

        def owned(p):
            with trace_span("shuffle_fetch", self.metrics, "fetchWaitTime"):
                for b in shuffle.read(p, self.schema):
                    self.metrics.inc("shuffleBytesRead",
                                     b.device_size_bytes())
                    yield b

        def empty():
            return
            yield

        return [owned(p) if ctx.owns_reduce(p) else empty()
                for p in range(self.num_partitions)]

    def _record_distributed_stats(self, shuffle, ctx) -> None:
        """Per-partition rows/bytes of THIS worker's map outputs, read
        from the shuffle store's registered buffer metadata (the
        distributed plane's materialization boundary). Each worker
        records its own map-side contribution; the union across workers
        is the exchange's global shape — summing here would cost a
        cross-worker round trip per exchange."""
        from ..exec.metrics import metrics_enabled
        if not metrics_enabled():
            return
        rows = [0] * self.num_partitions
        bytes_ = [0] * self.num_partitions
        try:
            metas = ctx.store.metas(shuffle.shuffle_id,
                                    list(range(self.num_partitions)))
            for m in metas:
                if 0 <= m.reduce_id < self.num_partitions:
                    rows[m.reduce_id] += int(m.num_rows)
                    bytes_[m.reduce_id] += int(m.total_bytes)
        except Exception:
            return             # stats must never fail the exchange
        self._finish_stage_stats("dcn", rows, bytes_)

    def _reduce_groups(self, shuffle: LocalShuffle) -> List[List[int]]:
        """Adaptive partition coalescing: group adjacent reduce partitions
        below minPartitionSize using the map side's observed slice sizes
        (the grouping itself is plan/aqe.py's coalesce rule; this method
        feeds it the observations and records the decision)."""
        all_parts = [[p] for p in range(self.num_partitions)]
        if not self.adaptive_ok or not self.adaptive_min_bytes:
            return all_parts
        target = int(self.adaptive_min_bytes)
        sizes = [sum(s.size_bytes for s in shuffle.slices[p])
                 for p in range(self.num_partitions)]
        from ..plan import aqe
        groups = aqe.plan_coalesce(sizes, target)
        self.coalesced_to = len(groups)
        if len(groups) < self.num_partitions:
            self.metrics.inc("coalescedPartitions",
                             self.num_partitions - len(groups))
            aqe.record_decision(
                self, "coalesce", stage_id=self.stage_id,
                before=f"{self.num_partitions} partitions",
                after=f"{len(groups)} partitions",
                reason=(f"observed {sum(sizes)}B across "
                        f"{self.num_partitions} partitions; target "
                        f"{target}B per task"))
        return groups

    def _read_group(self, shuffle: LocalShuffle, group: List[int]) -> Partition:
        """Reduce-side read with ELASTIC RECOVERY: a failed fetch (lost /
        released buffers, transport give-up) re-executes up to
        ``recovery.maxStageRetries`` times with backoff — the analog of
        RapidsShuffleFetchFailedException -> Spark FetchFailed -> map-stage
        retry (RapidsShuffleIterator.scala:28,49). With DURABLE outputs
        the retry re-reads the retained slices; only a genuinely lost
        buffer re-runs the upstream map for the lost partitions."""
        from ..exec import recovery
        from ..exec.spill import BufferLostError
        from .transport import ShuffleFetchError

        def retryable(exc):
            if self.children[0].subtree_deterministic():
                return True
            # a consumed-elsewhere indeterminate map stage would
            # re-partition rows differently on refill; the durable
            # re-read path is still safe (same slices, no re-execution)
            return shuffle.durable and not isinstance(exc, BufferLostError)

        rs = recovery.StageRetryState(f"shuffle-reduce-p{group}",
                                      retryable=retryable)
        from ..exec.lifecycle import check_cancel
        while True:
            check_cancel()       # a cancelled query must not keep retrying
            try:
                with trace_span("shuffle_fetch", self.metrics,
                                "fetchWaitTime"):
                    batches = self._count_read(
                        self._pull_group(shuffle, group))
                rs.succeeded()
                break
            except (ShuffleFetchError, BufferLostError) as e:  # lint: recover-ok the FetchFailed -> map-stage-retry boundary, driven by exec/recovery's budget
                # discard partial state BEFORE the backoff dwell: the
                # failed attempt's half-read slices must not stay pinned
                # through the sleep (the retry_stage discipline)
                rs.failed(e, sleep=False)  # re-raises when not retryable
                self.metrics.inc("fetchFailedRetries")
                self.metrics.inc("stageRetries")
                if not shuffle.durable or isinstance(e, BufferLostError):
                    # no durable tier to re-read (or it lost a buffer):
                    # re-run the upstream map for the lost partitions
                    self._refill(shuffle, group)
                rs.sleep_backoff()
        if batches:
            yield concat_batches(self.schema, batches)

    def _count_read(self, batches: List[ColumnarBatch]
                    ) -> List[ColumnarBatch]:
        """Meter shuffleBytesRead AFTER a group pull succeeds: counting
        inside the pull would leave a failed mid-group attempt's bytes in
        the counter and re-count them on the elastic retry."""
        for b in batches:
            self.metrics.inc("shuffleBytesRead", b.device_size_bytes())
        return batches

    def _pull_group(self, shuffle: LocalShuffle,
                    group: List[int]) -> List[ColumnarBatch]:
        from ..analysis import faults
        from .transport import ShuffleFetchError
        if faults.armed() and faults.fire("fetch.fail"):
            raise ShuffleFetchError(
                f"injected fetch fault (partitions {group})")
        batches = []
        for p in group:
            for b in shuffle.read(p, self.schema):
                batches.append(b)
        return batches

    def _refill(self, shuffle: LocalShuffle, group: List[int]) -> None:
        """Re-run the upstream map tasks, keeping ONLY the lost reduce
        partitions' slices (Spark recomputes lost map outputs from lineage;
        other partitions' refills are discarded). Caller guarantees the
        upstream is deterministic."""
        from ..exec.tasks import run_partition_tasks
        lost = set(group)
        partitioner = self._make_partitioner()
        for p in lost:
            for s in shuffle.slices[p]:
                if not s._closed:     # release survivors before replacing
                    s.close()
            shuffle.slices[p] = []

        def map_task(pid, part):
            for batch in part:
                for pi, piece in enumerate(partitioner.split(batch)):
                    if pi in lost and piece.num_rows > 0:
                        shuffle.slices[pi].append(SpillableColumnarBatch(
                            piece, OUTPUT_FOR_SHUFFLE_PRIORITY,
                            shuffle.catalog))

        run_partition_tasks(self.children[0].execute(), map_task)

    def _cleanup(self) -> None:
        sh = getattr(self, "_shuffle", None)
        if sh is not None:
            sh.close_pending()
            self._shuffle = None
        if getattr(self, "_ici_results", None) is not None:
            self._ici_results = None       # release the device arrays


class TpuHashExchangeExec(TpuShuffleExchangeExec):
    """Hash exchange for aggregate/join key distribution (partial->final)."""

    CONTRACT = exec_contract(schema="passthrough", partitioning="defined",
                             bound={"by": 0}, extras=("exchange_plane",))
    METRICS = TpuShuffleExchangeExec.METRICS   # emits only inherited keys

    def __init__(self, child: TpuExec, num_partitions: int,
                 keys: List[ex.Expression], adaptive_ok: bool = False,
                 adaptive_min_bytes: Optional[int] = None,
                 plane: str = "auto", mesh=None,
                 split_depth: Optional[int] = None):
        super().__init__(child, num_partitions, by=keys,
                         adaptive_ok=adaptive_ok,
                         adaptive_min_bytes=adaptive_min_bytes,
                         plane=plane, mesh=mesh, split_depth=split_depth)


class TpuRangeExchangeExec(TpuExec):
    """Range exchange for distributed sort (GpuRangePartitioning.scala +
    GpuRangePartitioner.scala:237): sample the child, compute ordered bound
    rows, route every row to the partition owning its key range. Partition i
    of the output holds keys strictly below partition i+1's, so per-partition
    sorts compose into a total order.

    Two passes over spillable handles: accumulate (bounded residency), sample
    bounds, then split — the reference samples with a driver-side reservoir;
    here the sample is a per-batch random gather (~sample_target rows total).
    """

    CONTRACT = exec_contract(schema="passthrough", partitioning="defined",
                             bound={"orders": 0})
    METRICS = exec_metrics("sampleTime", "shuffleWriteTime")

    SAMPLE_TARGET_PER_PARTITION = 100

    def __init__(self, child: TpuExec, num_partitions: int, orders):
        super().__init__(child)
        from ..plan.physical import bind_refs
        from ..plan import logical as lp
        self.num_partitions = max(1, num_partitions)
        self.orders = [lp.SortOrder(bind_refs(o.child, child.schema),
                                    o.ascending, o.nulls_first)
                       for o in orders]
        self.query_id: Optional[str] = None
        self.stage_id: Optional[int] = None
        self.stage_stats: Optional[Dict[str, Any]] = None

    @property
    def schema(self):
        return self.children[0].schema

    @property
    def output_partitions(self) -> int:
        return self.num_partitions

    def _sample(self, batch: ColumnarBatch, k: int) -> ColumnarBatch:
        import numpy as np
        import jax.numpy as jnp
        from ..columnar.column import bucket
        from ..ops import kernels as K
        n = batch.num_rows
        take = min(n, k)
        rng = np.random.default_rng(42 + n)
        idx = jnp.asarray(np.sort(rng.choice(n, size=take, replace=False)),
                          dtype=jnp.int32)
        live = jnp.arange(len(idx)) < take
        cols = [K.gather_column(c, idx, out_valid=live)
                for c in batch.columns]
        return ColumnarBatch(batch.schema, cols, take)

    def execute(self) -> List[Partition]:
        from ..plan.physical import accumulate_spillable
        from .partitioning import RangePartitioner
        assign_stage(self)
        spillables = accumulate_spillable(self.children[0].execute())
        if not spillables:
            def empty():
                return
                yield
            return [empty() for _ in range(self.num_partitions)]
        target = self.SAMPLE_TARGET_PER_PARTITION * self.num_partitions
        per_batch = max(8, -(-target // len(spillables)))
        samples = []
        with trace_span("range_sample", self.metrics, "sampleTime"):
            for s in spillables:
                samples.append(self._sample(s.get_batch(), per_batch))
        partitioner = RangePartitioner(self.num_partitions, self.orders,
                                       samples)
        stale = getattr(self, "_shuffle", None)
        if stale is not None:       # re-execution under an outer stage
            stale.close_pending()   # retry: release the orphaned slices
        shuffle = self._shuffle = LocalShuffle(self.num_partitions)
        from .. import config as cfg
        from ..exec.pipeline import PipelineWindow
        win = PipelineWindow(
            max(1, int(cfg.TpuConf().get(cfg.SHUFFLE_PIPELINE_DEPTH))),
            metrics=self.metrics)
        with trace_span("shuffle_write", self.metrics, "shuffleWriteTime"):
            for s in spillables:
                shuffle.write_deferred(win, partitioner, s.get_batch())
                s.close()
            win.flush()
        record_local_shuffle_stats(self, shuffle)
        return [shuffle.read(p, self.schema)
                for p in range(self.num_partitions)]

    def _cleanup(self) -> None:
        sh = getattr(self, "_shuffle", None)
        if sh is not None:
            sh.close_pending()
            self._shuffle = None


class TpuBroadcastExchangeExec(TpuExec):
    """Broadcast exchange: collect the child ONCE into a single spillable
    batch shared by every consumer partition
    (GpuBroadcastExchangeExec.scala:47,238-367 — async driver collect +
    lazy device materialization on executors; standalone, the 'broadcast'
    is one registered spillable buffer re-acquired per stream partition).
    """

    CONTRACT = exec_contract(schema="passthrough", partitioning="single")
    METRICS = exec_metrics("broadcastTime", "dataSize")

    def __init__(self, child: TpuExec):
        super().__init__(child)
        self._handle: Optional[SpillableColumnarBatch] = None
        self._lock = __import__("threading").Lock()

    @property
    def schema(self):
        return self.children[0].schema

    @property
    def output_partitions(self) -> int:
        return 1

    def materialize(self) -> SpillableColumnarBatch:
        """Build (once) and return the shared broadcast handle."""
        from ..plan.physical import accumulate_spillable, concat_spillable
        with self._lock:
            if self._handle is None:
                with trace_span("broadcast_build", self.metrics, "broadcastTime"):
                    batch = concat_spillable(
                        self.schema,
                        accumulate_spillable(self.children[0].execute()))
                self.metrics.inc("dataSize", batch.device_size_bytes())
                self._handle = SpillableColumnarBatch(batch)
            return self._handle

    def execute(self) -> List[Partition]:
        def gen():
            yield self.materialize().get_batch()
        return [gen()]

    def _cleanup(self) -> None:
        with self._lock:
            if self._handle is not None:
                self._handle.close()
                self._handle = None
