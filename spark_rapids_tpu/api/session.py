"""TpuSession: the SparkSession analog + plugin bootstrap.

Reference: ``SQLPlugin.scala`` + ``Plugin.scala:108-154`` (driver/executor
init: conf fixup, device+memory init, semaphore init). Standalone, session
construction performs the executor-side bootstrap directly.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Sequence, Union

from .. import config as cfg
from ..analysis.lockdep import named_lock
from ..columnar import dtypes as dt
from ..plan import logical as lp
from .dataframe import DataFrame


# guards every session's SQL-text parse cache (leaf: only dict ops run
# under it; concurrent service workers hit sql() from pool threads)
_parse_cache_mu = named_lock("api.session._parse_cache_mu")


class TpuSessionBuilder:
    def __init__(self):
        self._conf: Dict[str, Any] = {}

    def config(self, key: str, value: Any = None) -> "TpuSessionBuilder":
        if isinstance(key, dict):
            self._conf.update(key)
        else:
            self._conf[key] = value
        return self

    def appName(self, name: str) -> "TpuSessionBuilder":
        self._conf["app.name"] = name
        return self

    def master(self, m: str) -> "TpuSessionBuilder":
        return self

    def getOrCreate(self) -> "TpuSession":
        return TpuSession(cfg.TpuConf(self._conf))


class RuntimeConf:
    """session.conf facade (set/get like Spark's RuntimeConfig)."""

    def __init__(self, session: "TpuSession"):
        self._session = session

    def set(self, key: str, value: Any) -> None:
        self._session.conf = self._session.conf.with_overrides({key: value})
        # conf changes are flight-recorder events: a post-mortem on a
        # dead run needs to know which knobs moved right before it died
        from ..service.telemetry import flight_record
        flight_record("conf", key, {"value": str(value)})
        # the audits cache their gates per process (conf reads on hot
        # paths would defeat them); a runtime change to an analysis.* key
        # must re-prime those caches or the first-primed value latches
        # for the rest of the process
        if ".analysis." in key:
            from ..analysis import recompile, sync_audit
            recompile.reset_cache()
            sync_audit.reset_cache()
        # compile.* keys reconfigure the persistent cache + donation gate
        if ".compile." in key:
            from ..exec import compile_cache
            compile_cache.configure(self._session.conf)
        # recovery budget / durable tier / fetch-retry knobs / chaos
        # plan re-prime on their keys (they cache per process like the
        # audits)
        if ".recovery." in key or ".shuffle.durable" in key or \
                ".shuffle.fetch." in key:
            from ..exec import recovery
            recovery.refresh(self._session.conf)
        if ".faults." in key:
            from ..analysis import faults
            faults.refresh(self._session.conf)
        if ".analysis.divergence" in key:
            from ..analysis import divergence
            divergence.refresh(self._session.conf)
        if ".analysis.bufferledger" in key.lower():
            from ..analysis import ledger
            ledger.refresh(self._session.conf)
        # ANY conf change drops the session's serving caches: cached
        # plans were analyzed/optimized/validated under the old conf, and
        # a stored result may have been produced by it (the parse cache
        # is conf-independent, but dropping it keeps one rule)
        self._session._plan_cache = None
        self._session._result_cache = None
        self._session._sql_parse_cache = None

    def get(self, key: str, default: Any = None) -> Any:
        return self._session.conf.get_key(key, default)


class _BuilderAccessor:
    """``TpuSession.builder`` returns a FRESH builder per access — a shared
    mutable builder would leak .config() settings into later sessions."""

    def __get__(self, obj, objtype=None):
        return TpuSessionBuilder()


def _annotated_plan_lines(plan, violations, conf=None) -> List[str]:
    """Executed-plan tree with runtime metrics plus the per-node
    annotations EXPLAIN ANALYZE renders — contract diagnostics keyed by
    validator path, fused-stage membership / decline reasons
    (plan/stage_compiler.fusion_annotations), per-exchange stage-boundary
    statistics (shuffle/exchange.stage_stats_annotations), and the
    estimate-vs-actual row drift per node (plan/estimates). One
    implementation for both the session-level and captured-
    QueryExecution renderings."""
    by_path: Dict[str, List[str]] = {}
    for v in violations:
        by_path.setdefault(v.path, []).append(f"! contract: {v.message}")
    from ..plan.stage_compiler import fusion_annotations
    for path, notes in fusion_annotations(plan).items():
        by_path.setdefault(path, []).extend(notes)
    from ..shuffle.exchange import stage_stats_annotations
    for path, notes in stage_stats_annotations(plan).items():
        by_path.setdefault(path, []).extend(notes)
    from ..plan.estimates import drift_annotations
    for path, notes in drift_annotations(plan, conf=conf).items():
        by_path.setdefault(path, []).extend(notes)
    from ..plan.aqe import aqe_annotations
    for path, notes in aqe_annotations(plan).items():
        by_path.setdefault(path, []).extend(notes)
    return plan.metrics_lines(
        annotate=lambda path: list(by_path.get(path, ())))


class QueryExecution:
    """Everything a query-execution listener receives for ONE executed
    query (the ExecutionPlanCaptureCallback analog, Plugin.scala:211-300,
    widened with the observability reports): the executed physical plan,
    the per-operator metrics tree, and the sync/span/recompile/lock
    reports the bench runner prints. Self-contained: renders from ITS
    OWN captured plan and violations, so a capture for query N stays
    correct after later queries run."""

    def __init__(self, session: "TpuSession", plan, sync: dict,
                 spans: dict, recompiles: dict, locks: dict,
                 violations=()):
        self.session = session
        self.plan = plan                   # executed TpuExec tree
        self.sync = sync                   # SyncCounter.report()
        self.spans = spans                 # SpanRecorder.report()
        # per family, in recompile.delta's shape, from this query's own
        # ``programs`` map (analysis/recompile.recompiles_of)
        self.recompiles = recompiles
        self.locks = locks                 # lockdep stats delta
        self.violations = list(violations)  # contract diags at capture
        self._metrics_tree = None

    @property
    def metrics_tree(self):
        """[(depth, operator, metrics)] — materialized LAZILY: resolving
        the bags costs device readbacks, which must not land inside a
        benchmark's timed collect window."""
        if self._metrics_tree is None:
            self._metrics_tree = self.plan.metrics_tree()
        return self._metrics_tree

    def explain_analyze(self) -> str:
        """THIS query's executed plan annotated with runtime metrics,
        its captured contract diagnostics, and fused-stage membership
        (rendered on demand)."""
        lines = ["== Executed Plan (analyzed) =="]
        lines += _annotated_plan_lines(self.plan, self.violations,
                                       conf=self.session.conf)
        lines.append(
            f"query: hostSyncs={self.sync.get('hostSyncs', 0)} "
            f"spanWallS={self.spans.get('wallS', 0.0)} "
            f"concurrency={self.spans.get('concurrency', 0.0)}")
        return "\n".join(lines)


class TpuSession:
    builder = _BuilderAccessor()

    _active: Optional["TpuSession"] = None
    _lock = named_lock("api.session.TpuSession._lock")

    def __init__(self, conf: Optional[cfg.TpuConf] = None):
        self.conf = conf or cfg.TpuConf()
        self._views: Dict[str, lp.LogicalPlan] = {}
        self._last_exec_plan = None
        self._last_overrides = None
        self._last_serving = None
        # serving front door (plan/plan_cache.py): lazily built from the
        # conf; RuntimeConf.set drops them so conf changes replan
        self._plan_cache = None
        self._result_cache = None
        self._serving_stats = None
        self._query_listeners: List = []
        self._bootstrap()
        with TpuSession._lock:
            TpuSession._active = self

    def _bootstrap(self) -> None:
        """Executor-plugin init analog (Plugin.scala:124-154): device, memory
        budget, semaphore, spill catalog."""
        from ..exec.device import DeviceManager, TpuSemaphore
        from ..exec.spill import BufferCatalog
        dm = DeviceManager.get(self.conf)
        TpuSemaphore.initialize(self.conf.concurrent_tpu_tasks)
        cat = BufferCatalog.get()
        cat.device_budget = dm.memory_budget_bytes
        # audit caches prime from the ACTIVE session's conf at first use;
        # a new session (possibly with different analysis.* keys) must
        # re-prime them
        from ..analysis import lockdep, recompile, sync_audit
        from ..exec import metrics as exec_metrics_mod, tracing
        sync_audit.reset_cache()
        recompile.reset_cache()
        # metrics gate primes EAGERLY from THIS conf (like lockdep): a
        # lazy read at first inc could run under the spill catalog's
        # admission lock and recurse into the session lock
        exec_metrics_mod.refresh(self.conf)
        tracing.reset_cache()               # tracing.enabled / .timeline
        # lockdep primes EAGERLY from THIS session's conf (a lazy read at
        # first acquire would recurse through the conf-registry lock)
        lockdep.refresh_mode(self.conf)
        # telemetry primes EAGERLY too (flight-recorder gate/capacity/dir)
        # and starts the scrape endpoint when telemetry.port is set
        from ..service import telemetry
        telemetry.refresh(self.conf)
        # persistent compile cache + donation gate (compile.cacheDir /
        # compile.donate): wires jax's on-disk compilation cache and
        # loads the fused-program signature index; degrades gracefully
        from ..exec import compile_cache
        compile_cache.configure(self.conf)
        # XLA's own report of every trace, lowering, compile and cache
        # load, charged to the program, span and operator that paid it
        compile_cache.install_compile_listener()
        # recovery knobs + fault-injection plan prime EAGERLY (the
        # lockdep pattern: a lazy conf read inside a failing partition
        # drain could recurse into the conf-registry lock)
        from ..analysis import faults
        from ..exec import recovery
        recovery.refresh(self.conf)
        faults.refresh(self.conf)
        # lockstep divergence audit mode (analysis/divergence.py): primed
        # eagerly like faults — the mint-site hooks read a lock-free flag
        from ..analysis import divergence
        divergence.refresh(self.conf)
        # buffer-lifecycle ledger mode (analysis/ledger.py): same eager
        # priming — the spill-store hooks read a lock-free flag
        from ..analysis import ledger
        ledger.refresh(self.conf)
        # cold-path killers (docs/compile.md §5): reload the AQE
        # cardinality-feedback checkpoint and prewarm the hottest fused
        # stages from the corpus beside the signature index. Both are
        # best-effort — a torn or missing artifact must not fail
        # bootstrap; prewarm submits to the background pool and returns
        # without blocking.
        try:
            from ..plan import aqe
            aqe.reload_checkpoint(self.conf)
        except Exception:
            pass
        try:
            if bool(self.conf.get(cfg.COMPILE_PREWARM)):
                from ..exec import compile_pool
                compile_pool.prewarm(self.conf)
        except Exception:
            pass

    @classmethod
    def active(cls) -> "TpuSession":
        with cls._lock:
            if cls._active is None:
                cls._active = TpuSession()
            return cls._active

    # -- dataframe creation --------------------------------------------------
    def createDataFrame(self, data, schema=None) -> DataFrame:
        import pandas as pd
        import pyarrow as pa
        if isinstance(data, pd.DataFrame):
            table = pa.Table.from_pandas(data, preserve_index=False)
        elif isinstance(data, pa.Table):
            table = data
        elif isinstance(data, dict):
            table = self._table_from_pydict(data)
        else:
            # rows: list of tuples/dicts (+ schema names)
            if schema is not None and isinstance(schema, (list, tuple)):
                names = list(schema)
                cols = {n: [row[i] for row in data] for i, n in enumerate(names)}
                table = pa.table(cols)
            elif data and isinstance(data[0], dict):
                names = list(data[0].keys())
                cols = {n: [row.get(n) for row in data] for n in names}
                table = pa.table(cols)
            else:
                raise TypeError("provide schema names for row data")
        if isinstance(schema, dt.Schema):
            # cast arrow table to requested types
            import pyarrow as pa
            fields = [pa.field(f.name, dt.to_arrow(f.dtype)) for f in schema]
            table = table.cast(pa.schema(fields))
        return DataFrame(lp.LocalScan(table), self)

    @staticmethod
    def _table_from_pydict(data):
        """pa.table() with MAP columns handled: pyarrow infers python
        dicts as structs (and rejects non-string keys), so columns holding
        dicts get an explicit arrow map type from the inferred SQL type."""
        import pyarrow as pa
        from ..columnar.batch import _infer_dtype
        if not any(isinstance(values, list) and
                   any(isinstance(v, dict) for v in values)
                   for values in data.values()):
            return pa.table(data)       # no map columns: the fast path
        cols, fields = [], []
        for name, values in data.items():
            vals = list(values) if not hasattr(values, "dtype") else values
            has_dict = isinstance(vals, list) and any(
                isinstance(v, dict) for v in vals)
            if has_dict:
                t = dt.to_arrow(_infer_dtype(vals))
                cols.append(pa.array(
                    [None if v is None else list(v.items()) for v in vals],
                    type=t))
                fields.append(pa.field(name, t))
            else:
                arr = pa.array(vals)
                cols.append(arr)
                fields.append(pa.field(name, arr.type))
        return pa.Table.from_arrays(cols, schema=pa.schema(fields))

    def range(self, start: int, end: Optional[int] = None, step: int = 1,
              numPartitions: int = 1) -> DataFrame:
        if end is None:
            start, end = 0, start
        return DataFrame(lp.Range(start, end, step, numPartitions), self)

    def table(self, name: str) -> DataFrame:
        return DataFrame(self._views[name], self)

    @property
    def read(self) -> "DataFrameReader":
        return DataFrameReader(self)

    def sql(self, query: str) -> DataFrame:
        """The frame of a SQL text. The lookup in the parse cache (or the
        lexer and parser on a miss) runs under the span ``parse``, before
        any query records: the frame carries its begin, seconds and
        whether the cache hit, and the query that collects the frame
        takes them over (``last_query_metrics()["host"]``)."""
        import time
        from ..exec.tracing import trace_span
        t0 = time.perf_counter()
        with trace_span("parse"):
            df, hit = self._sql_frame(query)
        df._parsed = (t0, time.perf_counter() - t0, hit)
        return df

    def _sql_frame(self, query: str):
        from ..plan import plan_cache as pc
        from .sql import parse_sql
        st = pc.serving_stats(self)
        plan = self._parse_cache_get(query)
        if plan is not None:
            # SQL-text parse cache hit (docs/plan_cache.md §parse): the
            # lexer/parser is skipped entirely; the plan-cache
            # fingerprint downstream still decides plan reuse
            st["parseCacheHits"] += 1
            return DataFrame(plan, self), True
        if int(self.conf.get(cfg.PARSE_CACHE_MAX_ENTRIES)) > 0:
            st["parseCacheMisses"] += 1
        st["parses"] += 1
        df = parse_sql(query, self)
        self._parse_cache_put(query, df.logical_plan())
        return df, False

    # -- SQL-text -> parsed-plan cache (PR 12 follow-up: the layer AHEAD
    # of the plan-cache fingerprint for non-prepared sql() traffic) ------
    def _parse_cache_views_sig(self) -> tuple:
        """Identity snapshot of the session catalog: a parsed plan embeds
        references to the view plan OBJECTS it resolved, so a hit is
        only legal while every registered view is still the same object
        (re-registering a temp view invalidates naturally)."""
        return tuple(sorted((n, id(p)) for n, p in self._views.items()))

    def _parse_cache(self):
        cache = getattr(self, "_sql_parse_cache", None)
        if cache is None:
            from collections import OrderedDict
            cache = self._sql_parse_cache = OrderedDict()  # lint: unguarded-ok every caller holds _parse_cache_mu (module-level helper lock, not the session class lock)
        return cache

    def _parse_cache_get(self, query: str):
        max_entries = int(self.conf.get(cfg.PARSE_CACHE_MAX_ENTRIES))
        if max_entries <= 0:
            return None
        with _parse_cache_mu:
            cache = self._parse_cache()
            hit = cache.get(query)
            if hit is None:
                return None
            views_sig, plan = hit
            if views_sig != self._parse_cache_views_sig():
                del cache[query]     # a referenced view was re-registered
                return None
            cache.move_to_end(query)
            return plan

    def _parse_cache_put(self, query: str, plan) -> None:
        max_entries = int(self.conf.get(cfg.PARSE_CACHE_MAX_ENTRIES))
        if max_entries <= 0:
            return
        with _parse_cache_mu:
            cache = self._parse_cache()
            cache[query] = (self._parse_cache_views_sig(), plan)
            cache.move_to_end(query)
            while len(cache) > max_entries:
                cache.popitem(last=False)

    def prepare(self, query: Union[str, DataFrame]) -> "PreparedStatement":
        """Prepared-statement API (the serving front door,
        docs/plan_cache.md): parse ONCE, plan/contract-validate/
        stage-compile once (through the parameterized-plan cache),
        execute many. SQL text may carry ``:name`` placeholders bound
        per execution::

            stmt = session.prepare(
                "SELECT sum(v) FROM t WHERE d >= :lo AND d < :hi")
            stmt.execute(lo=date(1994, 1, 1), hi=date(1995, 1, 1))
            stmt.execute(lo=date(1995, 1, 1), hi=date(1996, 1, 1))

        A DataFrame works too (its literals auto-parameterize, so later
        frames of the same shape share the plan)."""
        from .sql import PreparedStatement
        return PreparedStatement(self, query)

    def serving_stats(self) -> Dict[str, int]:
        """Counters of the serving front door on THIS session: parses,
        analyzes, plans built, plan/result cache hits and misses,
        binding revalidations (tests and dashboards read these; the
        process-wide analogs are the ``tpu_plan_cache_*`` /
        ``tpu_result_cache_*`` telemetry series)."""
        from ..plan import plan_cache as pc
        return dict(pc.serving_stats(self))

    def stop(self) -> None:
        with TpuSession._lock:
            if TpuSession._active is self:
                TpuSession._active = None

    # -- process telemetry (service/telemetry: the continuous layer) --------
    def metrics_snapshot(self, path: Optional[str] = None) -> dict:
        """Point-in-time snapshot of the PROCESS metrics registry —
        semaphore, lockdep, sync, recompile, spill, shuffle-transport and
        HBM watermark metrics from one surface (the live-Spark-UI
        metrics stream, pulled). With ``path``, one JSONL line is also
        appended there (the scrape-less export)."""
        from ..service.telemetry import MetricsRegistry
        reg = MetricsRegistry.get()
        snap = reg.snapshot()
        if path:
            # the line on disk IS the returned dict (one harvest)
            reg.snapshot_jsonl(path, snap)
        return snap

    def prometheus_metrics(self) -> str:
        """The registry in Prometheus text format (what the scrape
        endpoint at ``spark.rapids.tpu.sql.telemetry.port`` serves)."""
        from ..service.telemetry import MetricsRegistry
        return MetricsRegistry.get().prometheus_text()

    def dump_flight_record(self, path: Optional[str] = None,
                           query_id: Optional[str] = None) -> str:
        """Write the always-on flight ring to a JSON artifact on demand
        (the automatic dump fires when a task body or collect raises);
        returns the artifact path. ``query_id`` scopes the artifact to
        one query: the filename carries the id and another query's
        attributed events are filtered out."""
        from ..service.telemetry import FlightRecorder
        return FlightRecorder.get().dump(path, reason="on-demand",
                                         query_id=query_id)

    # -- query-lifecycle control (exec/lifecycle.py, docs/service.md §4) ----
    def cancel_query(self, query_id: str, reason: str = "cancel") -> bool:
        """Cooperatively cancel a RUNNING query by id (from another
        thread — a collect is synchronous on its own): sets the query's
        cancel flag, and the execution unwinds with a typed
        ``QueryCancelledError`` at its next poll point (partition drain,
        fetch/completion poll, retry backoff, ``collect_iter``
        delivery). Never a thread kill; cleanup runs the normal error
        path (arenas release, the buffer ledger audits residency).
        False when no such query is live."""
        from ..exec import lifecycle
        return lifecycle.cancel_query(query_id, reason)

    def live_queries(self) -> List[str]:
        """Query ids currently registered with the lifecycle control
        plane in this process (running collects; suspended queries stay
        with the service that parked them)."""
        from ..exec import lifecycle
        return lifecycle.live_queries()

    # -- query-lifecycle observability (docs/observability.md §8) -----------
    def last_query_id(self) -> Optional[str]:
        """The query id minted for the last executed collect (None before
        the first execution; shared by every worker of a lockstep
        distributed run)."""
        return getattr(self, "_last_query_id", None)

    def last_stage_stats(self) -> List[dict]:
        """Stage-boundary exchange statistics of the last executed query:
        one entry per exchange node in tree order — stage id, data plane,
        per-partition rows/bytes, p50/max partition bytes and the skew
        factor observed at materialization. This is the AQE feed
        (ROADMAP item 2): coalesce/skew re-planning reads exactly this
        shape."""
        if self._last_exec_plan is None:
            raise RuntimeError("no plan executed yet")
        from ..shuffle.exchange import collect_stage_stats
        return collect_stage_stats(self._last_exec_plan)

    def last_aqe_decisions(self) -> List[dict]:
        """Adaptive-execution decisions of the last executed query, in
        plan-tree order: per record the rule (coalesce / skew-split /
        join-promote / join-demote / drift-feedback), whether it was
        applied or declined, the owning operator + plan path, the
        before/after shapes, and the reason (plan/aqe.py,
        docs/aqe.md)."""
        if self._last_exec_plan is None:
            raise RuntimeError("no plan executed yet")
        from ..plan.aqe import collect_decisions
        return collect_decisions(self._last_exec_plan)

    def last_drift_report(self) -> List[dict]:
        """Estimate-vs-actual row drift of the last executed query, worst
        first: per plan node the planner's estimate, the executed actual,
        the drift ratio, and whether it crossed
        ``observability.driftThreshold`` (the cardinality-feedback
        groundwork, plan/estimates.py)."""
        if self._last_exec_plan is None:
            raise RuntimeError("no plan executed yet")
        from ..plan.estimates import drift_report
        return drift_report(self._last_exec_plan, conf=self.conf)

    def merged_timeline(self, extra=(), query_id: Optional[str] = None,
                        path: Optional[str] = None):
        """ONE Chrome-trace timeline for the last executed query across
        every worker that ran it: this session's recorded spans merged
        with ``extra`` trace documents (dicts or trace.json paths —
        typically the REMOTE workers' dumps), filtered to the shared
        query id, each source under its own process group. Requires the
        timeline conf (``tracing.timeline``) or a trace-recording run.
        Returns the merged trace dict; with ``path``, also writes it
        there and returns the path."""
        rec = getattr(self, "_last_span_recorder", None)
        if rec is None:
            raise RuntimeError("no recorded query timeline (enable "
                               "spark.rapids.tpu.sql.tracing.timeline)")
        from ..exec.tracing import merge_chrome_traces
        qid = query_id or getattr(self, "_last_query_id", None)
        merged = merge_chrome_traces(
            [rec.chrome_trace()] + list(extra), query_id=qid)
        if path:
            import json
            import os
            os.makedirs(os.path.dirname(os.path.abspath(path)),
                        exist_ok=True)
            with open(path, "w") as f:
                json.dump(merged, f)
            return path
        return merged

    # -- testing hooks (ExecutionPlanCaptureCallback analog) ----------------
    def last_plan(self):
        return self._last_exec_plan

    # -- per-query metrics (SQLMetrics-in-the-UI analog: GpuMetricNames +
    # per-exec additionalMetrics, GpuExec.scala:27-56; spill volume feeds
    # the query summary like TaskMetrics.memoryBytesSpilled) --------------
    def last_query_metrics(self) -> dict:
        """Structured metrics for the last executed query: per-operator
        counters/timers in plan-tree order, spill DELTAS attributable to
        that query (TaskMetrics.memoryBytesSpilled analog), and the
        point-in-time catalog residency gauges."""
        if self._last_exec_plan is None:
            raise RuntimeError("no plan executed yet")
        from ..exec.spill import BufferCatalog
        cat = BufferCatalog.get()
        base_dev, base_host = getattr(self, "_mem_baseline", (0, 0))
        # read from the recorder itself where it is still reachable: the
        # caller's fetch_to_host of the result adds its span after the
        # collect returned
        rec = getattr(self, "_last_span_recorder", None)
        from ..analysis import recompile
        operators = [
            {"depth": d, "operator": name, "metrics": m}
            for d, name, m in self._last_exec_plan.metrics_tree()]
        scans = [op["metrics"] for op in operators
                 if "Scan" in op["operator"]]
        coalesces = [op["metrics"] for op in operators
                     if op["operator"] == "TpuCoalesceBatchesExec"]
        serving = getattr(self, "_last_serving", None) or {}
        sync = getattr(self, "_last_sync_report",
                       {"hostSyncs": 0, "syncSites": {}})
        programs = recompile.programs_report(rec.programs) \
            if rec is not None else {}
        return {
            "operators": operators,
            # what the scans handed on, and how much of it was uploaded in
            # THIS query and not served from the device scan cache (0 while
            # a registered table stays resident)
            "scan": {
                "batches": sum(m.get("numOutputBatches", 0) for m in scans),
                "uploadedBatches": sum(m.get("uploadedBatches", 0)
                                       for m in scans),
            },
            # what the plan's coalesces did with their input batches:
            # handed on untouched (already at the target), or concatenated
            # into ``outputs`` runs (TpuCoalesceBatchesExec)
            "coalesce": {
                "passed": sum(m.get("passedBatches", 0) for m in coalesces),
                "concatenated": sum(m.get("concatBatches", 0)
                                    for m in coalesces),
                "outputs": sum(m.get("concatOutputs", 0) for m in coalesces),
            },
            # the parameterized-plan cache: whether this query's plan was
            # served from it, and how many literals rode as bound
            # parameters (docs/plan_cache.md)
            "planCache": {
                "hit": int(serving.get("planCache") == "hit"),
                "params": int(serving.get("params") or 0),
            },
            "memory": {
                "deviceBytesHeld": cat.device_bytes,
                "hostBytesHeld": cat.host_bytes,
                "spilledDeviceBytes": cat.spilled_device_bytes - base_dev,
                "spilledHostBytes": cat.spilled_host_bytes - base_host,
            },
            # attributed blocking device->host readbacks during the collect
            # (the dominant end-to-end cost on high-latency links; see
            # exec/tracing.SyncCounter)
            "sync": sync,
            # per-span wall-clock breakdown (self time, nesting excluded):
            # names where executeTimeS went — concurrent partition tasks
            # can legitimately sum past the wall clock
            "spans": rec.report() if rec is not None
            else getattr(self, "_last_span_report", {}),
            # every program this query dispatched or rebuilt, by kernel
            # family (``<eager>:<op>`` for a jnp op outside the program
            # funnel): dispatches, and XLA's own count and seconds of
            # traces, lowerings, backend compiles and persistent-cache
            # loads, and the host's seconds inside the calls
            # (docs/observability.md §9)
            "programs": programs,
            # the host's account of the caller's call, from session.sql
            # to the result's fetch_to_host, in parts that tile it; under
            # tracing.enabled also the per-batch host sites
            # (SpanRecorder.host_ledger, docs/observability.md §9)
            "host": rec.host_ledger(programs, sync.get("syncWaitS", 0.0))
            if rec is not None else {},
            # what the query's SPMD mesh stages moved and how long their
            # three steps took (exec/tracing.MESH_COUNTERS; all zero for
            # a query that ran none)
            "mesh": dict(rec.mesh) if rec is not None else {},
            # driver-side planning (analyze + overrides) wall time and the
            # execute_collect wall (device work + transfers + syncs): with
            # the per-operator timers these account for the query's wall
            # clock end to end
            "planTimeS": round(getattr(self, "_last_plan_time_s", 0.0), 4),
            "executeTimeS": round(
                getattr(self, "_last_execute_time_s", 0.0), 4),
            # wall seconds to the first batch: == executeTimeS for a
            # materializing collect, smaller for collect_iter streams
            "firstRowS": round(
                getattr(self, "_last_first_row_s", 0.0) or 0.0, 4),
        }

    def explain_metrics(self) -> str:
        """The last executed plan annotated with each operator's metrics
        (the explain-with-SQLMetrics view of the Spark UI)."""
        if self._last_exec_plan is None:
            raise RuntimeError("no plan executed yet")
        rep = self.last_query_metrics()
        mem = rep["memory"]
        tail = ("memory: " +
                ", ".join(f"{k}={v}" for k, v in sorted(mem.items())))
        return self._last_exec_plan.metrics_string() + "\n" + tail

    def explain_analyze(self) -> str:
        """EXPLAIN ANALYZE of the last executed query: the executed plan
        tree with each node's runtime metrics inline (rows, batches,
        opTime, attributed hostSyncs/recompiles/spillBytes, ...), the
        plan-contract validator's diagnostics attached to the offending
        node, and the query-level wall/sync/span summary — the Spark-UI
        SQL-tab view, in text. ``df.explain(\"analyze\")`` executes the
        frame and prints this."""
        if self._last_exec_plan is None:
            raise RuntimeError("no plan executed yet")
        # contract violations keyed by root->node path (the same path
        # contracts.validate_plan builds and metrics_tree(with_path=True)
        # reproduces)
        # annotations computed from the EXECUTED tree so runtime fusion
        # fallbacks (stage broken -> per-op eager) show too
        ov = self._last_overrides
        lines: List[str] = ["== Executed Plan (analyzed) =="]
        lines += _annotated_plan_lines(
            self._last_exec_plan,
            getattr(ov, "last_violations", []) if ov else [],
            conf=self.conf)
        rep = self.last_query_metrics()
        sync = rep.get("sync", {})
        spans = rep.get("spans", {})
        qid = getattr(self, "_last_query_id", None)
        lines.append(
            f"query: {'queryId=' + qid + ' ' if qid else ''}"
            f"planTimeS={rep.get('planTimeS')} "
            f"executeTimeS={rep.get('executeTimeS')} "
            f"firstRowS={rep.get('firstRowS')} "
            f"hostSyncs={sync.get('hostSyncs', 0)} "
            f"spanWallS={spans.get('wallS', 0.0)} "
            f"concurrency={spans.get('concurrency', 0.0)}"
            + "".join(f" {k}={v}" for k, v in rep.get("host", {}).items()
                      if k != "sites"))
        # serving-cache hit/miss per layer (plan/plan_cache.py)
        from ..plan.plan_cache import serving_line
        sl = serving_line(getattr(self, "_last_serving", None))
        if sl:
            lines.append(sl)
        # buffer-lifecycle verdict (analysis/ledger.py end_of_query):
        # present whenever the ledger audited this query
        led = getattr(self, "_last_ledger", None)
        if led:
            lines.append(
                f"ledger: leakedBuffers={led.get('leakedBuffers', 0)} "
                f"leakedBytes={led.get('leakedBytes', 0)} "
                f"peakDeviceBytes={led.get('peakDeviceBytes', 0)} "
                f"mintedBuffers={led.get('mintedBuffers', 0)}")
        return "\n".join(lines)

    # -- query-execution listeners (ExecutionPlanCaptureCallback analog,
    # Plugin.scala:211-300): tests and the bench runner register callbacks
    # receiving a QueryExecution per executed query -----------------------
    def register_query_listener(self, callback) -> None:
        """``callback(QueryExecution)`` fires after every collect-style
        action on this session. Exceptions in listeners are logged and
        swallowed — observability must never fail the query."""
        if callback not in self._query_listeners:
            self._query_listeners.append(callback)

    def unregister_query_listener(self, callback) -> None:
        try:
            self._query_listeners.remove(callback)
        except ValueError:
            pass

    def _notify_query_listeners(self, qe: "QueryExecution") -> None:
        import logging
        for cb in list(self._query_listeners):
            try:
                cb(qe)
            except Exception:
                logging.getLogger("spark_rapids_tpu.listener").exception(
                    "query listener %r failed", cb)

    def assert_on_tpu(self, allowed_fallbacks: Sequence[str] = ()) -> None:
        """assertIsOnTheGpu test mode (GpuTransitionOverrides.scala:311-367)."""
        from ..plan.physical import CpuFallbackExec
        from ..plan.overrides import CpuOpBridgeExec

        def walk(node):
            if isinstance(node, (CpuFallbackExec, CpuOpBridgeExec)):
                name = node.plan.name
                if name not in allowed_fallbacks:
                    raise AssertionError(
                        f"{name} ran on CPU; explain:\n"
                        f"{self._last_overrides.last_explain}")
            for c in node.children:
                walk(c)
        assert self._last_exec_plan is not None, "no plan executed yet"
        walk(self._last_exec_plan)


class DataFrameReader:
    def __init__(self, session: TpuSession):
        self.session = session
        self._options: Dict[str, Any] = {}
        self._schema: Optional[dt.Schema] = None

    def option(self, k: str, v: Any) -> "DataFrameReader":
        self._options[k] = v
        return self

    def options(self, **kw) -> "DataFrameReader":
        self._options.update(kw)
        return self

    def schema(self, s: dt.Schema) -> "DataFrameReader":
        self._schema = s
        return self

    def parquet(self, *paths: str) -> DataFrame:
        return self._scan("parquet", list(paths))

    def csv(self, *paths: str) -> DataFrame:
        return self._scan("csv", list(paths))

    def orc(self, *paths: str) -> DataFrame:
        return self._scan("orc", list(paths))

    def _scan(self, fmt: str, paths: List[str]) -> DataFrame:
        return DataFrame(
            lp.FileScan(fmt, paths, self._schema, self._options), self.session)
