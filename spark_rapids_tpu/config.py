"""Typed configuration registry for spark-rapids-tpu.

TPU-native analog of the reference's ``RapidsConf`` (see
``/root/reference/sql-plugin/src/main/scala/com/nvidia/spark/rapids/RapidsConf.scala:116-278``
for the builder DSL and ``:282-762`` for the key registry). Mirrors its shape:

* a self-documenting builder DSL (``conf("spark.rapids.tpu...").doc(...).integerConf
  .createWithDefault(...)``)
* byte-unit parsing for memory sizes
* ``internal()`` keys hidden from docs
* per-operator auto-generated enable/disable keys (``spark.rapids.tpu.sql.expression.<Name>``,
  cf. GpuOverrides.scala:129-137) are registered dynamically by the rule registry in
  ``plan/overrides.py``
* ``help_text()`` generates docs/configs.md like RapidsConf.confHelp (RapidsConf.scala:133-168)
"""

from __future__ import annotations

import os
import re
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

_BYTE_SUFFIXES = {
    "b": 1,
    "k": 1 << 10, "kb": 1 << 10, "kib": 1 << 10,
    "m": 1 << 20, "mb": 1 << 20, "mib": 1 << 20,
    "g": 1 << 30, "gb": 1 << 30, "gib": 1 << 30,
    "t": 1 << 40, "tb": 1 << 40, "tib": 1 << 40,
}


def parse_bytes(value: Any) -> int:
    """Parse '2g', '512m', '1024' etc. into a byte count (Spark byte-string semantics)."""
    if isinstance(value, (int, float)):
        return int(value)
    s = str(value).strip().lower()
    # negative values pass through (sentinels like autoBroadcastJoinThreshold=-1)
    m = re.fullmatch(r"(-?\d+(?:\.\d+)?)\s*([a-z]*)", s)
    if not m:
        raise ValueError(f"cannot parse byte value: {value!r}")
    num, suffix = m.groups()
    mult = _BYTE_SUFFIXES.get(suffix or "b")
    if mult is None:
        raise ValueError(f"unknown byte suffix in {value!r}")
    return int(float(num) * mult)


def _parse_bool(value: Any) -> bool:
    if isinstance(value, bool):
        return value
    s = str(value).strip().lower()
    if s in ("true", "1", "yes", "on"):
        return True
    if s in ("false", "0", "no", "off"):
        return False
    raise ValueError(f"cannot parse boolean value: {value!r}")


@dataclass
class ConfEntry:
    key: str
    doc: str
    default: Any
    converter: Callable[[Any], Any]
    type_name: str
    internal: bool = False
    validator: Optional[Callable[[Any], bool]] = None

    def convert(self, raw: Any) -> Any:
        v = self.converter(raw)
        if self.validator is not None and not self.validator(v):
            raise ValueError(f"invalid value {raw!r} for {self.key}")
        return v


class _ConfBuilder:
    """Builder DSL: conf(key).doc(...).internal().booleanConf.create_with_default(...)."""

    def __init__(self, registry: "ConfRegistry", key: str):
        self._registry = registry
        self._key = key
        self._doc = ""
        self._internal = False
        self._validator: Optional[Callable[[Any], bool]] = None
        self._converter: Optional[Callable[[Any], Any]] = None
        self._type_name = "string"

    def doc(self, text: str) -> "_ConfBuilder":
        self._doc = text
        return self

    def internal(self) -> "_ConfBuilder":
        self._internal = True
        return self

    def check(self, validator: Callable[[Any], bool]) -> "_ConfBuilder":
        self._validator = validator
        return self

    @property
    def boolean_conf(self) -> "_ConfBuilder":
        self._converter, self._type_name = _parse_bool, "boolean"
        return self

    @property
    def integer_conf(self) -> "_ConfBuilder":
        self._converter, self._type_name = int, "integer"
        return self

    @property
    def double_conf(self) -> "_ConfBuilder":
        self._converter, self._type_name = float, "double"
        return self

    @property
    def string_conf(self) -> "_ConfBuilder":
        self._converter, self._type_name = str, "string"
        return self

    @property
    def bytes_conf(self) -> "_ConfBuilder":
        self._converter, self._type_name = parse_bytes, "byteSize"
        return self

    def create_with_default(self, default: Any) -> ConfEntry:
        entry = ConfEntry(
            key=self._key,
            doc=self._doc,
            default=default,
            converter=self._converter or str,
            type_name=self._type_name,
            internal=self._internal,
            validator=self._validator,
        )
        self._registry.register(entry)
        return entry


class ConfRegistry:
    def __init__(self) -> None:
        from .analysis.lockdep import named_lock
        self._entries: Dict[str, ConfEntry] = {}
        self._lock = named_lock("config.ConfRegistry._lock")

    def conf(self, key: str) -> _ConfBuilder:
        return _ConfBuilder(self, key)

    def register(self, entry: ConfEntry) -> None:
        with self._lock:
            if entry.key in self._entries:
                raise ValueError(f"duplicate conf key {entry.key}")
            self._entries[entry.key] = entry

    def register_dynamic(self, key: str, doc: str, default: bool) -> ConfEntry:
        """Per-operator enable keys; idempotent (re-registration returns existing)."""
        with self._lock:
            if key in self._entries:
                return self._entries[key]
            entry = ConfEntry(key=key, doc=doc, default=default,
                              converter=_parse_bool, type_name="boolean")
            self._entries[key] = entry
            return entry

    def get_entry(self, key: str) -> Optional[ConfEntry]:
        return self._entries.get(key)

    def entries(self) -> List[ConfEntry]:
        return sorted(self._entries.values(), key=lambda e: e.key)

    def help_text(self, include_internal: bool = False) -> str:
        lines = [
            "# spark-rapids-tpu Configuration",
            "",
            "| Name | Description | Default |",
            "|---|---|---|",
        ]
        for e in self.entries():
            if e.internal and not include_internal:
                continue
            lines.append(f"| {e.key} | {e.doc} | {e.default} |")
        return "\n".join(lines) + "\n"


REGISTRY = ConfRegistry()
_conf = REGISTRY.conf

# ---------------------------------------------------------------------------
# Core keys (mirroring RapidsConf.scala where the concept transfers; citations
# point at the reference key this replaces).
# ---------------------------------------------------------------------------

SQL_ENABLED = _conf("spark.rapids.tpu.sql.enabled").doc(
    "Master enable for columnar TPU acceleration (ref: spark.rapids.sql.enabled, "
    "RapidsConf.scala:744 area)").boolean_conf.create_with_default(True)

EXPLAIN = _conf("spark.rapids.tpu.sql.explain").doc(
    "Explain why parts of a query did or did not run on TPU: NONE, NOT_ON_GPU, ALL "
    "(ref: spark.rapids.sql.explain)").string_conf.check(
        lambda v: v in ("NONE", "NOT_ON_GPU", "ALL")).create_with_default("NONE")

INCOMPATIBLE_OPS = _conf("spark.rapids.tpu.sql.incompatibleOps.enabled").doc(
    "Enable ops whose TPU results differ from CPU in corner cases "
    "(ref: spark.rapids.sql.incompatibleOps.enabled)").boolean_conf.create_with_default(False)

HAS_NANS = _conf("spark.rapids.tpu.sql.hasNans").doc(
    "Assume floating point data may contain NaNs; gates some agg/join key paths "
    "(ref: spark.rapids.sql.hasNans)").boolean_conf.create_with_default(True)

VARIABLE_FLOAT_AGG = _conf("spark.rapids.tpu.sql.variableFloatAgg.enabled").doc(
    "Allow float aggregations whose result may differ from CPU due to reduction order "
    "(ref: spark.rapids.sql.variableFloatAgg.enabled)").boolean_conf.create_with_default(True)

BATCH_SIZE_BYTES = _conf("spark.rapids.tpu.sql.batchSizeBytes").doc(
    "Target coalesced columnar batch size in bytes "
    "(ref: spark.rapids.sql.batchSizeBytes default 2g, RapidsConf.scala:282-377)"
).bytes_conf.create_with_default(512 * 1024 * 1024)

MAX_READER_BATCH_SIZE_ROWS = _conf("spark.rapids.tpu.sql.reader.batchSizeRows").doc(
    "Cap on rows per scan/coalesced batch (ref: spark.rapids.sql.reader."
    "batchSizeRows). Whole-stage programs compile per batch capacity; 1M "
    "rows amortizes per-dispatch link latency ~8x vs 128k while the "
    "persistent compile cache absorbs the one-time larger-shape compile"
).integer_conf.create_with_default(1 << 20)

CONCURRENT_TPU_TASKS = _conf("spark.rapids.tpu.sql.concurrentTpuTasks").doc(
    "Number of tasks that may hold the device concurrently "
    "(ref: spark.rapids.sql.concurrentGpuTasks, RapidsConf.scala:351)"
).integer_conf.create_with_default(2)

TASK_POOL_THREADS = _conf("spark.rapids.tpu.sql.taskPoolThreads").doc(
    "Threads draining partitions concurrently (Spark's executor task slots; "
    "the TpuSemaphore still bounds how many hold the device at once)"
).integer_conf.create_with_default(4)

ALLOC_FRACTION = _conf("spark.rapids.tpu.memory.allocFraction").doc(
    "Fraction of device HBM the pool may use (ref: spark.rapids.memory.gpu.allocFraction)"
).double_conf.check(lambda v: 0.0 < v <= 1.0).create_with_default(0.9)

HOST_SPILL_STORAGE_SIZE = _conf("spark.rapids.tpu.memory.host.spillStorageSize").doc(
    "Bound on host-memory spill tier before cascading to disk "
    "(ref: spark.rapids.memory.host.spillStorageSize, RapidsConf.scala:330)"
).bytes_conf.create_with_default(4 * 1024 * 1024 * 1024)

SPILL_DIR = _conf("spark.rapids.tpu.memory.spillDir").doc(
    "Directory for the disk spill tier (ref: Spark local dirs via RapidsDiskBlockManager)"
).string_conf.create_with_default("/tmp/spark_rapids_tpu_spill")

SHUFFLE_PARTITIONS = _conf("spark.rapids.tpu.sql.shuffle.partitions").doc(
    "Default number of shuffle partitions (ref: spark.sql.shuffle.partitions)"
).integer_conf.create_with_default(8)

SHUFFLE_PLANE = _conf("spark.rapids.tpu.sql.shuffle.plane").doc(
    "Shuffle exchange data plane: 'auto' (device->device ICI collectives "
    "over the active mesh when one exists, the host/DCN path otherwise), "
    "'ici' (force collectives; planning fails without a mesh), 'dcn' "
    "(force the host-staged transport path). The ICI plane moves "
    "uncompressed device buffers through all_to_all (SURVEY.md §5: the "
    "UCX/RDMA -> ICI re-design); the DCN plane keeps the TCP transfer "
    "server, elastic retry, and the wire compression codec "
    "(see docs/shuffle.md)").string_conf.check(
        lambda v: str(v).lower() in ("auto", "ici", "dcn")
).create_with_default("auto")

SHUFFLE_PIPELINE_DEPTH = _conf("spark.rapids.tpu.sql.shuffle.pipelineDepth").doc(
    "Map-side split batches kept in flight before the oldest batch's "
    "slice-sizing readback lands: batch k+1's fused split (hash -> stable "
    "sort by partition id -> counts) dispatches before batch k's packed "
    "sizing resolves, so the map phase pays O(1) host syncs instead of "
    "one per batch. 1 degenerates to read-per-batch. Device residency "
    "grows by one sorted batch per slot"
).integer_conf.check(lambda v: int(v) >= 1).create_with_default(8)

SHUFFLE_DURABLE = _conf("spark.rapids.tpu.sql.shuffle.durable").doc(
    "Durable shuffle outputs (docs/resilience.md): map outputs stay "
    "registered (re-fetchable) until the exchange releases them and are "
    "pinned through the spill store's host/disk tiers — a consumer-side "
    "stage retry re-fetches instead of re-running the map stage, and a "
    "multi-process worker that dies and rejoins re-serves its outputs "
    "from the durable .npz tier (the reference's checkpoint/resume "
    "trade, SURVEY §5). Off keeps the memory-only fast path"
).boolean_conf.create_with_default(False)

SHUFFLE_DURABLE_MAX_BYTES = _conf(
    "spark.rapids.tpu.sql.shuffle.durable.maxBytes").doc(
    "Disk budget for the durable shuffle tier's .npz write-through "
    "(docs/shuffle.md): once the durable files exceed this many bytes, "
    "the OLDEST COMPLETED shuffle's durable files are evicted (the "
    "in-memory outputs keep serving this process; only the dead-worker "
    "rejoin re-serve for that old shuffle is given up), metered into "
    "tpu_durable_evicted_bytes_total — a long-lived session with "
    "shuffle.durable on cannot fill the disk. The newest completed "
    "shuffle is never evicted. 0 disables the budget"
).bytes_conf.create_with_default(2 * 1024 * 1024 * 1024)

SHUFFLE_FETCH_MAX_RETRIES = _conf(
    "spark.rapids.tpu.sql.shuffle.fetch.maxRetries").doc(
    "Transport-level retries per shuffle fetch before the failure "
    "escalates to the stage-retry taxonomy (exec/recovery.py): each "
    "retry uses a fresh connection; CRC mismatches and connection "
    "failures retry, desyncs never do (ShuffleClient; attempts are "
    "metered into tpu_shuffle_retries_total)"
).integer_conf.check(lambda v: int(v) >= 0).create_with_default(3)

SHUFFLE_FETCH_RETRY_BACKOFF = _conf(
    "spark.rapids.tpu.sql.shuffle.fetch.retryBackoff").doc(
    "Linear backoff (seconds x attempt) between transport-level fetch "
    "retries").double_conf.check(
        lambda v: float(v) >= 0).create_with_default(0.05)

RECOVERY_MAX_STAGE_RETRIES = _conf(
    "spark.rapids.tpu.sql.recovery.maxStageRetries").doc(
    "Stage re-executions a recoverable failure (lost shuffle buffer, "
    "fetch give-up, dead worker, injected task fault) may consume "
    "before the query fails — the standalone analog of Spark's "
    "spark.stage.maxConsecutiveAttempts driving FetchFailed map-stage "
    "retries (docs/resilience.md). 0 propagates every failure"
).integer_conf.check(lambda v: int(v) >= 0).create_with_default(2)

RECOVERY_RETRY_BACKOFF = _conf(
    "spark.rapids.tpu.sql.recovery.retryBackoff").doc(
    "Linear backoff (seconds x attempt) between stage retries "
    "(dead-worker liveness probes pace on their own exponential "
    "window, one fetch timeout per budget attempt)"
).double_conf.check(lambda v: float(v) >= 0).create_with_default(0.1)

FAULTS_SPEC = _conf("spark.rapids.tpu.sql.faults.spec").doc(
    "Deterministic fault-injection spec for the chaos harness "
    "(analysis/faults.py, docs/resilience.md): semicolon-separated "
    "point[:count][@selector] clauses over fetch.fail, conn.kill, "
    "task.poison, worker.die, mesh.drop, desync.inject — each fires a "
    "bounded number of times, flight-recorded and counted in "
    "tpu_faults_injected_total. Empty disables injection"
).string_conf.create_with_default("")

SHUFFLE_COMPRESSION_CODEC = _conf("spark.rapids.tpu.shuffle.compression.codec").doc(
    "Codec for shuffle transfer payloads: none, zlib (ref: spark.rapids."
    "shuffle.compression.codec / NvcompLZ4CompressionCodec, "
    "RapidsConf.scala:729; host-side here — no TPU decompression engine)"
).string_conf.check(
        lambda v: v in ("none", "zlib")).create_with_default("none")

SPILL_COMPRESSION_CODEC = _conf("spark.rapids.tpu.memory.spill.compression.codec").doc(
    "Codec for the disk spill tier: none, zlib").string_conf.check(
        lambda v: v in ("none", "zlib")).create_with_default("none")

ADAPTIVE_ENABLED = _conf("spark.rapids.tpu.sql.adaptive.enabled").doc(
    "Adaptive execution: coalesce small shuffle partitions at runtime from "
    "observed map-side sizes (ref: AQE + GpuCustomShuffleReaderExec, "
    "GpuOverrides.scala:1920)").boolean_conf.create_with_default(True)

ADAPTIVE_MIN_PARTITION_BYTES = _conf(
    "spark.rapids.tpu.sql.adaptive.coalescePartitions.minPartitionSize").doc(
    "Target minimum bytes per post-shuffle partition when adaptive "
    "coalescing merges small ones (ref: spark.sql.adaptive."
    "coalescePartitions.minPartitionSize)"
).bytes_conf.create_with_default(8 * 1024 * 1024)

SKEW_JOIN_THRESHOLD = _conf(
    "spark.rapids.tpu.sql.adaptive.skewJoin.skewedPartitionThreshold").doc(
    "A shuffled join's stream-side reduce partition larger than this many "
    "observed bytes splits into mapper-subset tasks, each joined against "
    "the same (shared) build partition (ref: spark.sql.adaptive.skewJoin."
    "skewedPartitionThresholdInBytes + partial-mapper partition specs, "
    "ShuffledBatchRDD.scala:202). 0 disables skew splitting."
).bytes_conf.create_with_default(256 * 1024 * 1024)

ADAPTIVE_COALESCE_ENABLED = _conf(
    "spark.rapids.tpu.sql.adaptive.coalescePartitions.enabled").doc(
    "AQE rule toggle (plan/aqe.py, docs/aqe.md): merge small post-shuffle "
    "partitions up to coalescePartitions.minPartitionSize from observed "
    "map-side sizes. Subordinate to adaptive.enabled (ref: spark.sql."
    "adaptive.coalescePartitions.enabled)"
).boolean_conf.create_with_default(True)

ADAPTIVE_SKEW_JOIN_ENABLED = _conf(
    "spark.rapids.tpu.sql.adaptive.skewJoin.enabled").doc(
    "AQE rule toggle (plan/aqe.py, docs/aqe.md): split a shuffled join's "
    "oversized stream partitions into mapper-subset tasks at runtime. "
    "Subordinate to adaptive.enabled (ref: spark.sql.adaptive.skewJoin."
    "enabled)").boolean_conf.create_with_default(True)

ADAPTIVE_SKEW_FACTOR = _conf(
    "spark.rapids.tpu.sql.adaptive.skewJoin.skewedPartitionFactor").doc(
    "A partition is skewed when its observed bytes exceed BOTH "
    "skewedPartitionThreshold and this factor times the median partition "
    "bytes of its exchange — the relative half of the skew test, so one "
    "uniformly-large shuffle does not split everything (ref: spark.sql."
    "adaptive.skewJoin.skewedPartitionFactor)").double_conf.check(
        lambda v: float(v) >= 1.0).create_with_default(5.0)

ADAPTIVE_JOIN_SWITCH_ENABLED = _conf(
    "spark.rapids.tpu.sql.adaptive.joinSwitch.enabled").doc(
    "AQE rule toggle (plan/aqe.py, docs/aqe.md): switch join strategy from "
    "observed build-side size — promote shuffled->broadcast when the "
    "materialized build lands at or under autoBroadcastJoinThreshold, "
    "demote broadcast->shuffled when it lands over threshold x "
    "joinSwitch.demoteFactor. Subordinate to adaptive.enabled"
).boolean_conf.create_with_default(True)

ADAPTIVE_JOIN_DEMOTE_FACTOR = _conf(
    "spark.rapids.tpu.sql.adaptive.joinSwitch.demoteFactor").doc(
    "Hysteresis band of the AQE join-strategy switch: a planned broadcast "
    "only demotes to a shuffled join when its observed device bytes exceed "
    "autoBroadcastJoinThreshold times this factor, and a shuffled join "
    "only promotes at or under the bare threshold — observed sizes inside "
    "(threshold, threshold*factor] change nothing, so a borderline build "
    "side cannot flap between strategies across repeat executions"
).double_conf.check(lambda v: float(v) >= 1.0).create_with_default(2.0)

ADAPTIVE_FEEDBACK_ENABLED = _conf(
    "spark.rapids.tpu.sql.adaptive.feedback.enabled").doc(
    "AQE rule toggle (plan/aqe.py, docs/aqe.md): fold observed per-node "
    "actual row counts back into est_rows on the next execution of the "
    "same plan fingerprint, so plan-cache repeat queries estimate from "
    "observed cardinalities instead of the static selectivity heuristics "
    "(the cardinality-feedback loop over plan/estimates.py drift). "
    "Subordinate to adaptive.enabled").boolean_conf.create_with_default(True)

AUTO_BROADCAST_JOIN_THRESHOLD = _conf(
    "spark.rapids.tpu.sql.autoBroadcastJoinThreshold").doc(
    "Build sides at or under this many bytes broadcast (materialize once, "
    "reused across stream partitions); larger builds co-partition both sides "
    "through a hash exchange (ref: spark.sql.autoBroadcastJoinThreshold + "
    "GpuBroadcastExchangeExec.scala:47). -1 disables broadcast."
).bytes_conf.create_with_default(10 * 1024 * 1024)

REPLACE_SORT_MERGE_JOIN = _conf("spark.rapids.tpu.sql.replaceHashJoin.enabled").doc(
    "Replace hash joins with TPU sort-merge joins (inverse of the reference's "
    "spark.rapids.sql.replaceSortMergeJoin.enabled, RapidsConf.scala:450 — TPU prefers "
    "sort-based joins)").boolean_conf.create_with_default(True)

IMPROVED_TIME_OPS = _conf("spark.rapids.tpu.sql.improvedTimeOps.enabled").doc(
    "Enable full-range timestamp parsing ops that may differ from CPU "
    "(ref: spark.rapids.sql.improvedTimeOps.enabled)").boolean_conf.create_with_default(False)

CAST_FLOAT_TO_STRING = _conf("spark.rapids.tpu.sql.castFloatToString.enabled").doc(
    "Enable float->string casts (formatting differs in corner cases; "
    "ref: spark.rapids.sql.castFloatToString.enabled)").boolean_conf.create_with_default(False)

CAST_STRING_TO_FLOAT = _conf("spark.rapids.tpu.sql.castStringToFloat.enabled").doc(
    "Enable string->float casts (ref: spark.rapids.sql.castStringToFloat.enabled)"
).boolean_conf.create_with_default(False)

CAST_STRING_TO_TIMESTAMP = _conf("spark.rapids.tpu.sql.castStringToTimestamp.enabled").doc(
    "Enable string->timestamp casts (ref: spark.rapids.sql.castStringToTimestamp.enabled)"
).boolean_conf.create_with_default(False)

MAX_STRING_BYTES = _conf("spark.rapids.tpu.sql.maxStringBytes").doc(
    "Maximum padded width of a device string column; wider data falls back to CPU "
    "(TPU-specific: strings are fixed-width padded byte matrices, see DESIGN.md §4)"
).integer_conf.create_with_default(1024)

WHOLESTAGE_FUSION = _conf("spark.rapids.tpu.sql.wholeStageFusion.enabled").doc(
    "MASTER fusion switch: per-operator fused programs (FusedStage and "
    "the fused aggregate phases) into single XLA computations "
    "(TPU-specific; see DESIGN.md §2). Off also disables the stage-level "
    "compiler gated by fusion.wholeStage"
).boolean_conf.create_with_default(True)

FUSION_WHOLE_STAGE = _conf("spark.rapids.tpu.sql.fusion.wholeStage").doc(
    "STAGE-level fusion (plan/stage_compiler.py, docs/fusion.md): compile "
    "a pipeline-breaker-free operator CHAIN (scan-unpack -> filter -> "
    "project -> partial-agg) into ONE fused program per stage instead of "
    "one per operator — the whole-stage-codegen analog (SURVEY §3.3). "
    "Off falls back to the per-OPERATOR fused path, which stays governed "
    "by the master switch wholeStageFusion.enabled; per-node decline "
    "reasons surface in EXPLAIN ANALYZE either way"
).boolean_conf.create_with_default(True)

SCAN_PREFETCH_THREADS = _conf("spark.rapids.tpu.sql.scan.prefetchThreads").doc(
    "CPU decode/prefetch threads for the streaming file scan "
    "(io/scan.py): background threads named tpu-scan-prefetch-N read, "
    "decode and stage batches ahead of device upload, overlapping host "
    "decode with device compute; joined with a bounded timeout on "
    "shutdown (the transport-thread discipline)"
).integer_conf.check(lambda v: int(v) >= 1).create_with_default(4)

BATCH_AUTOTUNE = _conf("spark.rapids.tpu.sql.batch.autotune").doc(
    "Autotune the scan/coalesce target batch rows from the device HBM "
    "budget and the live device watermark (service/telemetry): fused "
    "stages run at the largest safe batch — "
    "min(batchSizeBytes, available-HBM share) / row bytes, quantized to "
    "a power of two (plan/stage_compiler.tuned_batch_rows, "
    "docs/fusion.md §4). An explicitly-set reader.batchSizeRows stays a "
    "hard cap; off reproduces the legacy bytes-derived target"
).boolean_conf.create_with_default(True)

BATCH_AUTOTUNE_MAX_ROWS = _conf(
    "spark.rapids.tpu.sql.batch.autotuneMaxRows").doc(
    "Ceiling on the autotuned rows-per-batch pick (fused programs "
    "compile per capacity bucket; this bounds worst-case compile shapes "
    "and per-batch HBM)"
).integer_conf.check(lambda v: int(v) >= (1 << 14)
                     ).create_with_default(1 << 23)

TEST_CONF = _conf("spark.rapids.tpu.sql.test.enabled").doc(
    "Test mode: assert everything that should be on TPU is on TPU "
    "(ref: spark.rapids.sql.test.enabled / assertIsOnTheGpu, "
    "GpuTransitionOverrides.scala:311-367)").internal().boolean_conf.create_with_default(False)

TEST_ALLOWED_NON_TPU = _conf("spark.rapids.tpu.sql.test.allowedNonTpu").doc(
    "Comma-separated exec/expr class names allowed on CPU in test mode "
    "(ref: spark.rapids.sql.test.allowedNonGpu)").internal().string_conf.create_with_default("")

METRICS_ENABLED = _conf("spark.rapids.tpu.sql.metrics.enabled").doc(
    "Collect per-operator metrics (ref: SQLMetrics/GpuMetricNames, GpuExec.scala:27-56)"
).boolean_conf.create_with_default(True)

TRACING_ENABLED = _conf("spark.rapids.tpu.sql.tracing.enabled").doc(
    "Wrap hot regions in jax profiler TraceAnnotations (ref: NVTX ranges, "
    "NvtxWithMetrics.scala:27)").boolean_conf.create_with_default(False)

TRACING_TIMELINE = _conf("spark.rapids.tpu.sql.tracing.timeline").doc(
    "Record every trace span's begin/end with its thread and export a "
    "Chrome-trace/Perfetto timeline per query "
    "(SpanRecorder.chrome_trace; the bench runner dumps trace.json per "
    "query — open in chrome://tracing or ui.perfetto.dev, see "
    "docs/observability.md)").boolean_conf.create_with_default(False)

READER_TYPE = _conf("spark.rapids.tpu.sql.format.parquet.reader.type").doc(
    "Parquet reader strategy: PERFILE, COALESCING, MULTITHREADED "
    "(ref: spark.rapids.sql.format.parquet.reader.type, RapidsConf.scala:510)"
).string_conf.check(lambda v: v in ("PERFILE", "COALESCING", "MULTITHREADED")
                    ).create_with_default("COALESCING")

MESH_ENABLED = _conf("spark.rapids.tpu.sql.mesh.enabled").doc(
    "SPMD execution over a jax device mesh: 'auto' (multi-device accelerator "
    "platforms), 'true' (force, incl. virtual CPU meshes for tests), 'false'. "
    "Routes supported group-by/join/sort plans through fused all_to_all "
    "pipelines (parallel/mesh.py) instead of the host exchange"
).string_conf.check(
    lambda v: str(v).lower() in ("auto", "true", "false", "1", "0")
).create_with_default("auto")

MESH_MAX_STAGE_BYTES = _conf("spark.rapids.tpu.sql.mesh.maxStageBytes").doc(
    "Upper bound on the estimated input size of a SINGLE-SHOT mesh stage "
    "(whole input staged at once, receive windows workers*cap). "
    "Fixed-width group-bys above this stream in bounded multi-round "
    "windows instead (mesh.streamWindowRows); var-width stages keep the "
    "spillable host exchange path"
).bytes_conf.create_with_default(2 * 1024 * 1024 * 1024)

MESH_STREAM_WINDOW_ROWS = _conf(
    "spark.rapids.tpu.sql.mesh.streamWindowRows").doc(
    "Rows per worker per round for the STREAMING mesh group-by (stages "
    "above mesh.maxStageBytes): per-round residency is "
    "O(workers x window) input plus the group accumulator, the analog of "
    "the reference's windowed shuffle transfers "
    "(WindowedBlockIterator.scala)"
).integer_conf.check(lambda v: int(v) >= 1024).create_with_default(1 << 17)

HASH_OPTIMIZE_SORT = _conf("spark.rapids.tpu.sql.hashOptimizeSort.enabled").doc(
    "Insert a per-partition sort on hash-aggregate/join outputs so "
    "downstream file writes compress better (ref: "
    "spark.rapids.sql.hashOptimizeSort.enabled, "
    "GpuTransitionOverrides.scala:268-304)"
).boolean_conf.create_with_default(False)

AGG_PIPELINE_DEPTH = _conf("spark.rapids.tpu.sql.agg.pipelineDepth").doc(
    "Depth of the streaming aggregation's deferred-scalar window "
    "(exec/pipeline.PipelineWindow): entries that park a device scalar "
    "land by halves of this depth, in one batched readback. The "
    "group-by's one program leaves its count on the device and parks "
    "none, so each partial lands as it is pushed whatever the depth"
).integer_conf.check(lambda v: int(v) >= 1).create_with_default(48)

JOIN_PIPELINE_DEPTH = _conf("spark.rapids.tpu.sql.join.pipelineDepth").doc(
    "Stream batches whose join-output sizing scalars are kept in flight "
    "before the oldest batch's gather is dispatched: the per-batch "
    "device->host size readback (a full link round trip) resolves in ONE "
    "batched read per half-window instead of one blocking read per batch, "
    "making join-path host syncs O(1) per stage. 1 degenerates to "
    "read-per-batch. Device residency grows by one stream batch's match "
    "state per slot"
).integer_conf.check(lambda v: int(v) >= 1).create_with_default(16)

READER_THREADS = _conf("spark.rapids.tpu.sql.format.parquet.multiThreadedRead.numThreads").doc(
    "Background decode threads for the MULTITHREADED reader "
    "(ref: RapidsConf.scala:548)").integer_conf.create_with_default(4)

ANALYSIS_VALIDATE_PLAN = _conf("spark.rapids.tpu.sql.analysis.validatePlan").doc(
    "Plan-contract validation mode: off, warn (default; violations append "
    "to the explain output and log once), error (reject the plan with a "
    "diagnostic). Runs after conversion, before execution: parent/child "
    "schema+dtype agreement, exchange distribution invariants, and "
    "conversion-vs-tagging consistency (analysis/contracts.py; see "
    "docs/analysis.md)").string_conf.check(
        lambda v: str(v).lower() in ("off", "warn", "error")
).create_with_default("warn")

ANALYSIS_SYNC_AUDIT = _conf("spark.rapids.tpu.sql.analysis.syncAudit").doc(
    "Runtime sync audit: off, log, disallow — arms jax.transfer_guard "
    "(device->host) around partition-drain task regions so implicit host "
    "materializations in operator hot paths are logged or rejected on "
    "real accelerators; explicit batched resolves (jax.device_get) stay "
    "legal (analysis/sync_audit.py)").string_conf.check(
        lambda v: str(v).lower() in ("off", "log", "disallow")
).create_with_default("off")

ANALYSIS_DIVERGENCE = _conf("spark.rapids.tpu.sql.analysis.divergence").doc(
    "Cross-worker lockstep divergence audit: off, record, enforce. Each "
    "worker folds its lockstep-relevant event stream (shuffle-id mints, "
    "exchange fingerprints, stage-id draws, AQE decisions) into a "
    "per-query rolling digest carried on the shuffle metadata round "
    "trip; a mismatch names the FIRST divergent event. record logs, "
    "flight-records and counts (tpu_desync_total); enforce raises a "
    "typed DesyncError the recovery ladder maps to fail-query — a "
    "desync is never retried (analysis/divergence.py, docs/analysis.md "
    "§6)").string_conf.check(
        lambda v: str(v).lower() in ("off", "record", "enforce")
).create_with_default("off")

ANALYSIS_BUFFER_LEDGER = _conf(
    "spark.rapids.tpu.sql.analysis.bufferLedger").doc(
    "Runtime buffer-lifecycle ledger: off, record, enforce. Tags every "
    "catalog register/acquire/tier-move/donate/free with the ambient "
    "query id + allocation site; an end-of-query residency audit flags "
    "buffers the query minted that are still device-resident and not "
    "cache/durable-owned as leaks, and freed/donated buffers are "
    "tombstoned so later access diagnoses instead of reading garbage. "
    "record logs, flight-records and counts (tpu_buffer_leaks_total, "
    "tpu_use_after_free_total); enforce raises typed BufferLeakError / "
    "UseAfterFreeError / UseAfterDonateError with mint/free sites "
    "(analysis/ledger.py, docs/analysis.md §7)").string_conf.check(
        lambda v: str(v).lower() in ("off", "record", "enforce")
).create_with_default("off")

ANALYSIS_RECOMPILE_AUDIT = _conf(
    "spark.rapids.tpu.sql.analysis.recompileAudit").doc(
    "Track distinct compiled signatures per fused kernel and flag "
    "operators compiling once per batch shape (missed capacity-bucket "
    "padding); the bench runner reports per-query deltas "
    "(analysis/recompile.py)").boolean_conf.create_with_default(True)

COMPILE_CACHE_DIR = _conf("spark.rapids.tpu.sql.compile.cacheDir").doc(
    "Directory for the persistent (on-disk) XLA compilation cache plus "
    "the engine's fused-program signature index: a fresh process serving "
    "query shapes it has served before loads compiled executables from "
    "disk instead of paying seconds of cold compile per shape, and the "
    "recompile audit splits builds into cold builds vs disk hits with "
    "compile seconds per kernel family. Where the environment sets "
    "JAX_COMPILATION_CACHE_DIR that directory is used instead and no "
    "other is ever set. Empty leaves the XLA cache at its default place "
    "(a fixed .jax_cache in the checkout) without the signature index; "
    "an unusable directory logs a loud warning and degrades to in-memory "
    "caching, never a query failure (exec/compile_cache.py, "
    "docs/compile.md)"
).string_conf.create_with_default("")

COMPILE_DONATE = _conf("spark.rapids.tpu.sql.compile.donate").doc(
    "Donate consumed batch columns to the fused programs that ingest "
    "them (jax donate_argnums): XLA may reuse the input HBM for outputs "
    "and frees donated buffers the moment the program consumes them, "
    "lowering peak device bytes on multi-operator pipelines by ~one "
    "batch per stage. Spill-store-registered and scan-cache-served "
    "batches are never donated — their arrays are re-read through the "
    "catalog (docs/compile.md)").boolean_conf.create_with_default(True)

COMPILE_ASYNC = _conf("spark.rapids.tpu.sql.compile.async.enabled").doc(
    "Background compilation of fused-stage programs (exec/compile_pool.py, "
    "docs/compile.md §5): a cold stage build requested from a latency-"
    "sensitive context (a streaming collect_iter, or a service query whose "
    "deadline cannot absorb the build — see compile.async.deadlineSlackS) "
    "is submitted to a bounded worker pool and the stage serves batches "
    "through its per-op eager path until the compiled program is ready, "
    "swapping in at the next batch boundary. Plain batch collects keep "
    "the synchronous build path unchanged").boolean_conf.create_with_default(True)

COMPILE_ASYNC_WORKERS = _conf("spark.rapids.tpu.sql.compile.async.workers").doc(
    "Compile-pool worker threads shared by async stage builds and "
    "prewarm (query-triggered builds always outrank prewarm in the "
    "pool's priority queue)").integer_conf.check(
        lambda v: int(v) >= 1).create_with_default(2)

COMPILE_ASYNC_DEADLINE_SLACK_S = _conf(
    "spark.rapids.tpu.sql.compile.async.deadlineSlackS").doc(
    "Deadline-aware compile policy (docs/service.md): a query running "
    "under a service deadline keeps a cold stage build OFF its own "
    "thread — routing it to the compile pool and staying on the eager "
    "path — whenever less than this many seconds remain before the "
    "deadline. With more slack than this the query compiles "
    "synchronously (the build amortizes; eager would burn the slack "
    "anyway)").double_conf.check(
        lambda v: float(v) >= 0.0).create_with_default(5.0)

COMPILE_PREWARM = _conf("spark.rapids.tpu.sql.compile.prewarm.enabled").doc(
    "Compile the hottest persisted stage signatures on the compile pool "
    "at session bootstrap, before traffic arrives (docs/compile.md §5): "
    "reads the prewarm corpus recorded beside the signature index in "
    "compile.cacheDir, so a restarted replica serves its first query "
    "warm. No-op without a cache dir. Off by default — enable per "
    "replica, via tools/prewarm, or benchmarks.runner --prewarm"
).boolean_conf.create_with_default(False)

COMPILE_PREWARM_TOP_N = _conf("spark.rapids.tpu.sql.compile.prewarm.topN").doc(
    "How many of the hottest recorded stage signatures prewarm builds "
    "(hotness = times a signature was built or rebuilt across recorded "
    "processes)").integer_conf.check(
        lambda v: int(v) >= 1).create_with_default(32)

ADAPTIVE_FEEDBACK_CHECKPOINT = _conf(
    "spark.rapids.tpu.sql.adaptive.feedback.checkpoint").doc(
    "Persist the AQE cardinality-feedback bank (docs/aqe.md rule 4) as "
    "JSONL beside the compile-cache signature index and reload it at "
    "session bootstrap, so plan-cache repeats in a fresh process plan "
    "from observed actuals instead of re-learning them. No-op without "
    "compile.cacheDir; torn tail lines are skipped on load"
).boolean_conf.create_with_default(True)

PLAN_CACHE_ENABLED = _conf("spark.rapids.tpu.sql.planCache.enabled").doc(
    "Parameterized-plan cache (the serving front door, "
    "docs/plan_cache.md): eligible literals in WHERE/SELECT expressions "
    "extract into runtime parameters, and plans of the same normalized "
    "fingerprint reuse one analyzed/optimized/contract-validated/"
    "stage-compiled exec tree — and the SAME compiled program "
    "signatures — across executions with different literal values. "
    "``session.prepare(sql)`` plans once / executes many; plain "
    "``session.sql()`` hits the cache transparently. Plans carrying "
    "writes, nondeterministic expressions or unkeyable attributes are "
    "served the classic way").boolean_conf.create_with_default(True)

PLAN_CACHE_MAX_ENTRIES = _conf(
    "spark.rapids.tpu.sql.planCache.maxEntries").doc(
    "LRU bound on cached parameterized plans per session (each entry "
    "pins its exec tree and the fused stage programs it references; the "
    "JIT map-pressure relief valve drops all plan caches under mapping "
    "pressure)").integer_conf.check(
        lambda v: int(v) >= 1).create_with_default(64)

RESULT_CACHE_ENABLED = _conf("spark.rapids.tpu.sql.resultCache.enabled").doc(
    "Result cache for exact-repeat queries (docs/plan_cache.md): "
    "executions keyed by (plan fingerprint, parameter values, input "
    "snapshot) short-circuit BEFORE the planner and serve the stored "
    "host-resident result. Snapshots ride the scan data's ownership "
    "tokens (entries invalidate when the base table dies or a file's "
    "mtime/size changes). Off by default: a served result skips "
    "execution, so per-query spans/metrics reflect the original run"
).boolean_conf.create_with_default(False)

RESULT_CACHE_MAX_BYTES = _conf(
    "spark.rapids.tpu.sql.resultCache.maxBytes").doc(
    "Host-memory bound on the per-session result cache (LRU evicts "
    "past it)").bytes_conf.create_with_default(256 * 1024 * 1024)

RESULT_CACHE_MAX_ENTRY_BYTES = _conf(
    "spark.rapids.tpu.sql.resultCache.maxEntryBytes").doc(
    "Largest single result the cache will store; bigger results are "
    "served normally and never cached (serving-shaped results are "
    "small — a huge analytical result would evict everything else)"
).bytes_conf.create_with_default(32 * 1024 * 1024)

ANALYSIS_LOCKDEP = _conf("spark.rapids.tpu.sql.analysis.lockdep").doc(
    "Runtime lock-order tracking over the engine's named locks "
    "(analysis/lockdep.py): off, record (build the lock-order graph, log "
    "order-inversion cycles and lock-held-across-host-transfer findings, "
    "accumulate per-lock wait/hold stats attributed to trace spans — the "
    "tests/bench default), enforce (raise LockOrderInversionError / "
    "LockHeldAcrossTransferError at the offending acquisition, with both "
    "acquisition stacks)").string_conf.check(
        lambda v: str(v).lower() in ("off", "record", "enforce")
).create_with_default("off")

TELEMETRY_PORT = _conf("spark.rapids.tpu.sql.telemetry.port").doc(
    "Port for the background telemetry scrape endpoint serving /metrics "
    "(Prometheus text) and /snapshot (JSON) from the process metrics "
    "registry (service/telemetry.py; the live-Spark-UI metrics-stream "
    "analog). 0 disables the endpoint"
).integer_conf.create_with_default(0)

TELEMETRY_FLIGHT_RECORDER = _conf(
    "spark.rapids.tpu.sql.telemetry.flightRecorder").doc(
    "Always-on flight recorder: a fixed-size ring of recent span ends, "
    "sync/recompile/spill/lock incidents and conf changes, dumped to a "
    "JSON artifact automatically when a task body or collect() raises "
    "(service/telemetry.FlightRecorder; see docs/telemetry.md)"
).boolean_conf.create_with_default(True)

TELEMETRY_FLIGHT_DIR = _conf(
    "spark.rapids.tpu.sql.telemetry.flightRecorderDir").doc(
    "Directory for automatic flight-recorder dump artifacts (created on "
    "demand; a failed dump never masks the query exception)"
).string_conf.create_with_default("/tmp/spark_rapids_tpu_flight")

TELEMETRY_FLIGHT_EVENTS = _conf(
    "spark.rapids.tpu.sql.telemetry.flightRecorderEvents").doc(
    "Capacity of the flight-recorder ring; the newest events win"
).integer_conf.check(lambda v: int(v) >= 16).create_with_default(4096)

TELEMETRY_QUERY_LOG_DIR = _conf(
    "spark.rapids.tpu.sql.telemetry.queryLog.dir").doc(
    "Opt-in structured query log (service/query_log.py, "
    "docs/observability.md §8): one JSONL record per executed query — "
    "query id, plan fingerprint, cache verdicts, per-stage exchange "
    "statistics and wall, stage retries, faults fired, shuffle plane "
    "bytes, HBM peak operator, drift flags, top operators — appended to "
    "<dir>/query_log-<pid>.jsonl (render with python -m "
    "tools.query_report). Empty disables the log"
).string_conf.create_with_default("")

SERVICE_MAX_CONCURRENT = _conf(
    "spark.rapids.tpu.sql.service.maxConcurrentQueries").doc(
    "Worker threads of the multi-tenant query service "
    "(service/server.QueryService): the number of admitted queries "
    "executing concurrently against the shared engine. Layered ABOVE "
    "concurrentTpuTasks — the TpuSemaphore still bounds how many of "
    "those queries' tasks hold the device at once (docs/service.md)"
).integer_conf.check(lambda v: int(v) >= 1).create_with_default(4)

SERVICE_DEFAULT_SLOTS = _conf(
    "spark.rapids.tpu.sql.service.defaultTenantSlots").doc(
    "Concurrent queries ONE tenant may occupy in the service pool when "
    "its TenantSpec does not set slots explicitly (the per-tenant "
    "concurrency bound of docs/service.md §2)"
).integer_conf.check(lambda v: int(v) >= 1).create_with_default(2)

SERVICE_DEFAULT_QUEUE_DEPTH = _conf(
    "spark.rapids.tpu.sql.service.defaultTenantQueueDepth").doc(
    "Queued (not yet running) queries one tenant may hold before the "
    "service load-sheds further submissions with a typed "
    "AdmissionRejected (default for TenantSpecs without an explicit "
    "max_queue_depth; docs/service.md §2)"
).integer_conf.check(lambda v: int(v) >= 1).create_with_default(16)

SERVICE_DEFAULT_MEMORY_BYTES = _conf(
    "spark.rapids.tpu.sql.service.defaultTenantMemoryBytes").doc(
    "Default per-tenant device-byte budget installed at tenant "
    "registration when the TenantSpec does not set one: a tenant "
    "holding more device bytes than its budget spills its OWN buffers "
    "first at reserve/register boundaries, and its buffers are the "
    "global cascade's first victims (docs/service.md §3). 0 = "
    "unbudgeted"
).bytes_conf.create_with_default(0)

SERVICE_ADMISSION_EXPENSIVE_BYTES = _conf(
    "spark.rapids.tpu.sql.service.admission.expensiveBytes").doc(
    "Observed-cost admission weighting (docs/service.md, plan/aqe.py): a "
    "plan fingerprint whose last execution shuffled more than this many "
    "total exchange bytes charges one extra queue-depth unit per multiple "
    "on its tenant's next admit — an observed-expensive repeat query "
    "consumes budget proportional to what it actually cost, not a flat "
    "1 unit. 0 disables cost weighting (every admit charges 1)"
).bytes_conf.create_with_default(0)

SERVICE_SCHEDULER_POLICY = _conf(
    "spark.rapids.tpu.sql.service.scheduler.policy").doc(
    "Queue discipline of the multi-tenant service (docs/service.md §4). "
    "'priority': strict (priority DESC, deadline, arrival) — a "
    "low-priority flood cannot starve a high-priority tenant, the "
    "converse is intended. 'wfq': weighted deficit round-robin over "
    "tenants (TenantSpec.weight shares) with preemption — a "
    "high-priority arrival finding every slot busy suspends the running "
    "query with the largest deficit instead of queueing behind it"
).string_conf.check(
    lambda v: str(v) in ("priority", "wfq")).create_with_default(
    "priority")

SERVICE_DEFAULT_TENANT_WEIGHT = _conf(
    "spark.rapids.tpu.sql.service.defaultTenantWeight").doc(
    "Weighted-fair share for TenantSpecs without an explicit weight "
    "under service.scheduler.policy=wfq: each scheduling round credits "
    "a tenant's deficit counter by its weight, and the eligible tenant "
    "with the largest deficit runs next (docs/service.md §4)"
).double_conf.check(lambda v: float(v) > 0).create_with_default(1.0)

SERVICE_SCHEDULER_PREEMPTION = _conf(
    "spark.rapids.tpu.sql.service.scheduler.preemption").doc(
    "Under the wfq policy, allow a strictly higher-priority arrival "
    "that finds all execution slots busy to SUSPEND the running query "
    "with the largest deficit (working set spilled via the tenant "
    "catalog, stage cursor parked, re-admitted on resume — "
    "docs/service.md §4b). Off: arrivals always queue"
).boolean_conf.create_with_default(True)

PARSE_CACHE_MAX_ENTRIES = _conf(
    "spark.rapids.tpu.sql.service.parseCache.maxEntries").doc(
    "LRU bound on the per-session SQL-text -> parsed-plan cache serving "
    "non-prepared session.sql() traffic ahead of the plan-cache "
    "fingerprint (docs/plan_cache.md): a repeated SQL string skips the "
    "lexer/parser entirely; hits/misses ride serving_stats() as "
    "parseCacheHits/parseCacheMisses. Entries key on the view identity "
    "snapshot, so re-registering a temp view invalidates naturally. "
    "0 disables"
).integer_conf.check(lambda v: int(v) >= 0).create_with_default(256)

OBSERVABILITY_DRIFT_THRESHOLD = _conf(
    "spark.rapids.tpu.sql.observability.driftThreshold").doc(
    "Estimate-vs-actual row drift ratio at which a plan node is flagged "
    "as a misestimate (plan/estimates.py; the cardinality-feedback "
    "groundwork): a node whose actual/estimated output rows ratio is "
    ">= this factor (or <= its inverse) lands in the per-query drift "
    "report (session.last_drift_report) and is marked '! drift' in "
    "EXPLAIN ANALYZE").double_conf.check(
        lambda v: float(v) > 1.0).create_with_default(4.0)


class TpuConf:
    """Immutable-ish view over a key->value dict with typed accessors.

    Analog of ``RapidsConf`` the *instance* (constructed per-session from the config map).
    """

    def __init__(self, settings: Optional[Dict[str, Any]] = None):
        self._settings: Dict[str, Any] = dict(settings or {})
        # Environment overrides (lower priority than explicit settings):
        # SPARK_RAPIDS_TPU_CONF__<KEY WITH DOTS AS __>, case-insensitive —
        # env names are uppercase so the parsed key is matched against the
        # registry ignoring case (registered keys are camelCase).
        lower_to_key = {e.key.lower(): e.key for e in REGISTRY.entries()}
        for env_key, env_val in os.environ.items():
            if env_key.startswith("SPARK_RAPIDS_TPU_CONF__"):
                raw = env_key[len("SPARK_RAPIDS_TPU_CONF__"):].replace("__", ".").lower()
                key = lower_to_key.get(raw, raw)
                self._settings.setdefault(key, env_val)

    def get(self, entry: ConfEntry) -> Any:
        raw = self._settings.get(entry.key, None)
        if raw is None:
            return entry.default
        return entry.convert(raw)

    def get_key(self, key: str, default: Any = None) -> Any:
        entry = REGISTRY.get_entry(key)
        if entry is not None:
            raw = self._settings.get(key)
            return entry.default if raw is None else entry.convert(raw)
        return self._settings.get(key, default)

    def set(self, key: str, value: Any) -> "TpuConf":
        self._settings[key] = value
        return self

    def with_overrides(self, overrides: Dict[str, Any]) -> "TpuConf":
        merged = dict(self._settings)
        merged.update(overrides)
        return TpuConf(merged)

    def is_operator_enabled(self, key: str, default: bool) -> bool:
        # unset -> THIS caller's default, not the default of whoever
        # registered the key first (an incompatible op asks with False,
        # then with True: the answer must not depend on query order)
        entry = REGISTRY.register_dynamic(key, "(per-operator enable key)", default)
        raw = self._settings.get(key)
        return default if raw is None else entry.convert(raw)

    # Convenience typed properties used across the codebase ------------------
    @property
    def sql_enabled(self) -> bool: return self.get(SQL_ENABLED)
    @property
    def explain(self) -> str: return self.get(EXPLAIN)
    @property
    def incompatible_ops(self) -> bool: return self.get(INCOMPATIBLE_OPS)
    @property
    def has_nans(self) -> bool: return self.get(HAS_NANS)
    @property
    def batch_size_bytes(self) -> int: return self.get(BATCH_SIZE_BYTES)
    @property
    def concurrent_tpu_tasks(self) -> int: return self.get(CONCURRENT_TPU_TASKS)

    @property
    def task_pool_threads(self) -> int: return self.get(TASK_POOL_THREADS)
    @property
    def host_spill_storage_size(self) -> int: return self.get(HOST_SPILL_STORAGE_SIZE)
    @property
    def spill_dir(self) -> str: return self.get(SPILL_DIR)
    @property
    def shuffle_partitions(self) -> int: return self.get(SHUFFLE_PARTITIONS)
    @property
    def max_string_bytes(self) -> int: return self.get(MAX_STRING_BYTES)
    @property
    def wholestage_fusion(self) -> bool: return self.get(WHOLESTAGE_FUSION)
    @property
    def test_enabled(self) -> bool: return self.get(TEST_CONF)
    @property
    def test_allowed_non_tpu(self) -> List[str]:
        raw = self.get(TEST_ALLOWED_NON_TPU)
        return [s.strip() for s in raw.split(",") if s.strip()]
    @property
    def metrics_enabled(self) -> bool: return self.get(METRICS_ENABLED)
    @property
    def tracing_enabled(self) -> bool: return self.get(TRACING_ENABLED)
