"""DataFrame + session API (pyspark surface over the logical plan builder).

The reference rides Spark's own DataFrame API; standalone we mirror the
pyspark subset its integration tests exercise (SURVEY.md §4 ring 2: joins,
aggregates, sorts, repartition, IO round-trips) so those test shapes port.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Union

import copy

from ..columnar import dtypes as dt
from ..ops import conditionals as cd
from ..ops import expressions as ex
from ..ops import predicates as pr
from ..plan import logical as lp
from .column import Col, _unwrap
from . import functions as F

ColumnOrName = Union[Col, str]


def _to_expr(c: ColumnOrName) -> ex.Expression:
    if isinstance(c, str):
        return ex.ColumnRef(c)
    return _unwrap(c)


class DataFrame:
    def __init__(self, plan: lp.LogicalPlan, session: "TpuSession"):
        self._plan = plan
        self.session = session
        # (begin, seconds, cache hit) of the ``parse`` span, on a frame
        # that ``session.sql`` built: the first action takes it over
        self._parsed = None

    # -- plan access ---------------------------------------------------------
    @property
    def schema(self) -> dt.Schema:
        return self._analyzed().schema

    @property
    def columns(self) -> List[str]:
        return self.schema.names()

    def logical_plan(self) -> lp.LogicalPlan:
        return self._plan

    def _analyzed(self) -> lp.LogicalPlan:
        import copy
        plan = copy.deepcopy(self._plan)
        return lp.analyze(plan)

    def _df(self, plan: lp.LogicalPlan) -> "DataFrame":
        return DataFrame(plan, self.session)

    # -- transformations -----------------------------------------------------
    def select(self, *cols: ColumnOrName) -> "DataFrame":
        if not cols:
            cols = tuple(self.columns)
        exprs = [_to_expr(c) for c in cols]
        gen = self._lift_generator(exprs)
        if gen is not None:
            return gen
        win = self._lift_windows(exprs)
        if win is not None:
            return win
        return self._df(lp.Project(self._plan, exprs))

    def _lift_windows(self, exprs) -> Optional["DataFrame"]:
        """Col.over() window expressions in a select lift into a Window
        node under the projection (Catalyst's ExtractWindowExpressions
        rule): each WindowExpression becomes a generated column of an
        lp.Window, and the projection references it — so windows compose
        inside arithmetic (e.g. ``col("rev") * 100 / sum("rev").over(w)``)."""
        from ..ops.window import WindowExpression
        hoisted: List = []

        def repl(e):
            if isinstance(e, WindowExpression):
                name = f"__w{len(hoisted)}"
                hoisted.append((name, e))
                return ex.ColumnRef(name)
            return None

        new_exprs = []
        for e in exprs:
            if e.collect(lambda x: isinstance(x, WindowExpression)):
                new_exprs.append(e.transform_down(repl))
            else:
                new_exprs.append(e)
        if not hoisted:
            return None
        w = lp.Window(self._plan, hoisted)
        return self._df(lp.Project(w, new_exprs))

    def _lift_generator(self, exprs) -> Optional["DataFrame"]:
        """explode/posexplode in a select lifts into a Generate node under
        the projection (Catalyst's ExtractGenerator rule)."""
        from ..ops import arrays as ar_ops

        def inner(e):
            return e.children[0] if isinstance(e, ex.Alias) else e

        gen_idx = [i for i, e in enumerate(exprs)
                   if isinstance(inner(e), ar_ops.Explode)]
        if not gen_idx:
            return None
        if len(gen_idx) > 1:
            raise ValueError("only one generator per select (Spark rule)")
        i = gen_idx[0]
        e = exprs[i]
        g_expr = inner(e)
        col_name = e.alias if isinstance(e, ex.Alias) else "col"
        g = lp.Generate(self._plan, g_expr, col_name=col_name)
        out = []
        for j, e2 in enumerate(exprs):
            if j == i:
                if g_expr.pos:
                    out.append(ex.ColumnRef("pos"))
                out.append(ex.ColumnRef(col_name))
            else:
                out.append(e2)
        return self._df(lp.Project(g, out))

    def selectExpr(self, *exprs: str) -> "DataFrame":
        raise NotImplementedError("SQL string expressions need the parser")

    def filter(self, condition: Col) -> "DataFrame":
        return self._df(lp.Filter(self._plan, _unwrap(condition)))

    where = filter

    def withColumn(self, name: str, col: Col) -> "DataFrame":
        exprs: List[ex.Expression] = []
        replaced = False
        for c in self.columns:
            if c == name:
                exprs.append(ex.Alias(_unwrap(col), name))
                replaced = True
            else:
                exprs.append(ex.ColumnRef(c))
        if not replaced:
            exprs.append(ex.Alias(_unwrap(col), name))
        gen = self._lift_generator(exprs)     # explode() works here too
        if gen is not None:
            return gen
        win = self._lift_windows(exprs)       # Col.over() too
        if win is not None:
            return win
        return self._df(lp.Project(self._plan, exprs))

    def withColumnRenamed(self, old: str, new: str) -> "DataFrame":
        exprs = [ex.Alias(ex.ColumnRef(c), new) if c == old else ex.ColumnRef(c)
                 for c in self.columns]
        return self._df(lp.Project(self._plan, exprs))

    def drop(self, *names: str) -> "DataFrame":
        exprs = [ex.ColumnRef(c) for c in self.columns if c not in names]
        return self._df(lp.Project(self._plan, exprs))

    def groupBy(self, *cols: ColumnOrName) -> "GroupedData":
        return GroupedData(self, [_to_expr(c) for c in cols])

    groupby = groupBy

    def rollup(self, *cols: ColumnOrName) -> "GroupedData":
        """Hierarchical grouping sets {(a,b), (a), ()} via an Expand
        under the aggregate (GpuExpandExec path; Spark df.rollup)."""
        return GroupedData(self, [_to_expr(c) for c in cols],
                           sets="rollup")

    def cube(self, *cols: ColumnOrName) -> "GroupedData":
        """All 2^n grouping-set combinations (Spark df.cube)."""
        return GroupedData(self, [_to_expr(c) for c in cols], sets="cube")

    def agg(self, *aggs: Col) -> "DataFrame":
        return GroupedData(self, []).agg(*aggs)

    def join(self, other: "DataFrame", on=None, how: str = "inner"
             ) -> "DataFrame":
        how = {"outer": "full", "full_outer": "full", "leftouter": "left",
               "left_outer": "left", "rightouter": "right",
               "right_outer": "right", "leftsemi": "left_semi",
               "semi": "left_semi", "leftanti": "left_anti",
               "anti": "left_anti"}.get(how, how)
        cond = None
        using = None
        if isinstance(on, Col):
            cond = _unwrap(on)
        elif isinstance(on, str):
            using = [on]
        elif isinstance(on, (list, tuple)) and on:
            if isinstance(on[0], str):
                using = list(on)
            else:
                c = _unwrap(on[0])
                for o in on[1:]:
                    c = pr.And(c, _unwrap(o))
                cond = c
        if using is not None:
            cond = None
            for name in using:
                eq = pr.EqualTo(ex.ColumnRef(name), _UsingRight(name))
                cond = eq if cond is None else pr.And(cond, eq)
            plan = lp.Join(self._plan, other._plan, how, cond, using)
            return self._df(_dedupe_using(plan, using, how, self, other))
        return self._df(lp.Join(self._plan, other._plan, how, cond))

    def crossJoin(self, other: "DataFrame") -> "DataFrame":
        return self._df(lp.Join(self._plan, other._plan, "cross"))

    def union(self, other: "DataFrame") -> "DataFrame":
        return self._df(lp.Union(self._plan, other._plan))

    unionAll = union

    def distinct(self) -> "DataFrame":
        return self._df(lp.Distinct(self._plan))

    def dropDuplicates(self, subset: Optional[List[str]] = None) -> "DataFrame":
        if subset is None:
            return self.distinct()
        grouping = [ex.ColumnRef(c) for c in subset]
        aggs = []
        for c in self.columns:
            if c in subset:
                aggs.append(ex.ColumnRef(c))
            else:
                aggs.append(ex.Alias(
                    lp.AggregateExpression("first", ex.ColumnRef(c)), c))
        return self._df(lp.Aggregate(self._plan, grouping, aggs))

    def orderBy(self, *cols, ascending: Optional[Any] = None) -> "DataFrame":
        orders = []
        for i, c in enumerate(cols):
            if isinstance(c, lp.SortOrder):
                orders.append(c)
                continue
            e = _to_expr(c)
            asc = True
            if ascending is not None:
                asc = ascending[i] if isinstance(ascending, (list, tuple)) \
                    else bool(ascending)
            orders.append(lp.SortOrder(e, asc))
        return self._df(lp.Sort(self._plan, orders, is_global=True))

    sort = orderBy

    def limit(self, n: int) -> "DataFrame":
        return self._df(lp.Limit(self._plan, n))

    def mapInPandas(self, fn, schema) -> "DataFrame":
        """fn(iterator of pandas DataFrames) -> iterator of DataFrames
        (GpuMapInPandasExec analog)."""
        from ..columnar import dtypes as dtm
        if not isinstance(schema, dtm.Schema):
            schema = dtm.Schema(schema)
        return self._df(lp.MapInPandas(self._plan, fn, schema))

    def repartition(self, n: int, *cols: ColumnOrName) -> "DataFrame":
        by = [_to_expr(c) for c in cols] or None
        return self._df(lp.Repartition(self._plan, n, by))

    def coalesce(self, n: int) -> "DataFrame":
        return self._df(lp.Repartition(self._plan, n))

    def alias(self, name: str) -> "DataFrame":
        return self  # single-session name scoping not needed yet

    # -- actions -------------------------------------------------------------
    def _execute(self):
        """Plan (or serve from the parameterized-plan cache) this
        frame's query. Returns the exec tree, and leaves the serving
        info — plan-cache hit/miss, result-cache key — on the session
        (plan/plan_cache.py, docs/plan_cache.md)."""
        import time
        from ..exec.spill import BufferCatalog
        from ..exec.tracing import trace_span
        from ..plan import plan_cache as pc
        t0 = time.perf_counter()
        with trace_span("plan"):
            with trace_span("analyze"):
                plan = self._analyzed()
            with trace_span("plan_cache"):
                exec_plan, serving = pc.plan_for(self.session, plan)
        self.session._last_plan_time_s = time.perf_counter() - t0
        self.session._last_exec_plan = exec_plan
        self.session._last_serving = serving
        # the session attr is an observability surface that concurrent
        # service workers clobber; the execution pipeline reads THIS
        # thread's serving info (collect_batch, the prepared capture)
        pc.note_thread_serving(serving)
        # result-cache key read NOW (snapshot = current table tokens /
        # file stats) so the collect can short-circuit or store
        serving["resultKey"] = pc.result_key(self.session, serving, plan)
        # spill counters are process-cumulative; snapshot them so
        # last_query_metrics() can report THIS query's deltas
        cat = BufferCatalog.get()
        self.session._mem_baseline = (cat.spilled_device_bytes,
                                      cat.spilled_host_bytes)
        return exec_plan

    def cache(self) -> "DataFrame":
        """Materialize this DataFrame once into a SPILLABLE device batch
        and serve later queries straight from it, IN PLACE like Spark's
        df.cache() (GpuInMemoryTableScanExec analog): no re-execution, no
        host re-conversion, no re-upload; memory pressure spills the
        cached batch through the normal tiers. Returns self."""
        if isinstance(self._plan, lp.CachedScan):
            return self                     # already cached
        from ..exec.spill import CACHE_PRIORITY, SpillableColumnarBatch
        batch = self.collect_batch()
        handle = SpillableColumnarBatch(batch, CACHE_PRIORITY)
        self._uncached_plan = self._plan
        self._plan = lp.CachedScan(batch.schema, lp._CacheOwner(handle))
        return self

    def persist(self, storageLevel=None) -> "DataFrame":
        """Spark-compat alias of cache(); the storage level is accepted and
        ignored (the spill tiers decide residency here)."""
        return self.cache()

    def unpersist(self) -> "DataFrame":
        """Restore the original plan: later queries on THIS frame
        re-execute it (no-op for frames never cached). The cached batch
        itself is released when its last reference dies — derived frames
        still sharing it keep working, matching Spark's always-safe
        unpersist."""
        orig = getattr(self, "_uncached_plan", None)
        if orig is not None:
            self._plan = orig
            self._uncached_plan = None
        return self

    def collect_batch(self):
        from ..exec.tracing import QueryRecording
        from ..plan import plan_cache as pc
        # the query's recorders open BEFORE planning: the root span
        # ``query`` covers plan + execute (docs/observability.md §9)
        parsed, self._parsed = self._parsed, None
        recording = QueryRecording().open(parsed)
        try:
            try:
                exec_plan = self._execute()
            except BaseException:
                # plan_for may have CLAIMED a cache entry before a later
                # step of _execute raised (result-key snapshot,
                # baseline): release it or the entry reads busy forever.
                # A stale serving dict from a previous query is harmless
                # — its planEntry was already popped by that query's
                # release.
                pc.release_plan_entry(pc.thread_serving())
                raise
            serving = pc.thread_serving() or {}
            try:
                hit = pc.serve_result_hit(self.session, serving)
                if hit is not None:
                    # exact-repeat short circuit: no execution at all —
                    # the stored HOST batch serves (no spans/metrics/
                    # listeners for this collect; EXPLAIN ANALYZE marks
                    # the hit)
                    return hit
                return self._collect_planned(exec_plan, serving, recording)
            finally:
                # the exec tree claimed from the plan cache is free for
                # the next execution (concurrent collects on a busy entry
                # plan fresh trees, PlanEntry.try_begin_execution)
                pc.release_plan_entry(serving)
        finally:
            recording.close()

    def _collect_planned(self, exec_plan, serving, recording=None):
        """Execute a planned query. ``recording`` is the caller's open
        :class:`QueryRecording` (``collect_batch`` opens it before
        planning); the prepared-statement fast path has none and gets
        one here."""
        import time
        from ..exec import query_context as qc
        from ..exec.tracing import QueryRecording
        from ..plan import plan_cache as pc
        if recording is None:
            recording = QueryRecording().open()
        sc, spans = recording.sync, recording.spans
        listeners = bool(self.session._query_listeners)
        if listeners:
            # snapshot only when someone is listening
            from ..analysis import lockdep
            lk0 = lockdep.stats()
        # the query-lifecycle identity (docs/observability.md §8): ONE
        # query id minted at collect time, ambient for the execution so
        # spans, flight events, shuffle protocol traffic and exchange
        # stage ids all attribute to this query — lockstep-deterministic,
        # so distributed workers running the same query mint the same id
        # a pre-minted reservation (qc.reserve_query) wins over a fresh
        # mint: concurrent distributed drivers mint their contexts in
        # lockstep program order on the main thread, then collect on
        # worker threads — the racy collect order must not draw from
        # the query-id counter
        ctx = qc.take_reserved()
        if ctx is not None:
            qid = ctx.query_id
        else:
            qid = qc.mint_query_id(exec_plan)
            # the context picks up the ambient tenant hint (the
            # service's tenant_scope on this thread); captured here so
            # the query-log record and session surface carry it after
            # the scope closes
            ctx = qc.QueryContext(qid)
        self.session._last_query_id = qid
        qc.note_thread_query_id(qid)
        self.session._last_tenant = ctx.tenant
        self.session._last_first_row_s = None
        # lifecycle control plane (exec/lifecycle.py): index this query's
        # cancel token by id so cancel/suspend surfaces (QueryService,
        # session.cancel_query, the peer META reply) can reach the
        # running execution; unregistered in the finally below
        from ..exec import lifecycle as _lifecycle
        _lifecycle.register(ctx)
        from ..analysis import faults as _faults
        faults0 = _faults.fired_total()
        # AQE pre-execution hook (plan/aqe.py): clear the prior run's
        # decision records and fold stored observed cardinalities for
        # this fingerprint back into est_rows (drift feedback).
        # Best-effort — adaptive machinery must never fail the query.
        try:
            from ..plan import aqe
            aqe.begin_query(self.session, exec_plan, serving)
        except Exception:
            pass
        t0 = time.perf_counter()
        try:
            with qc.query_scope(ctx):
                try:
                    try:
                        spans.query_id = qid
                        out = exec_plan.execute_collect()
                        # the result's fetch_to_host is the caller's to
                        # run: its span joins this query's recorder
                        out.recording = recording
                    finally:
                        recording.close()
                except BaseException as e:
                    # post-mortem for failures OUTSIDE task bodies
                    # (planner-side execute, concat, exchange setup): dump
                    # the flight ring INSIDE the query scope so the
                    # artifact is scoped+named to the failing query.
                    # dump_on_error never raises and dedups against the
                    # task-level hook, so the original exception
                    # propagates unmasked.
                    from ..service.telemetry import dump_on_error
                    dump_on_error(e)
                    raise
            # what follows the execution, once a query (the adaptive
            # feedback, the reports, the listeners, the result cache, the
            # ledger's audit, the query log), is the span ``query_end`` of
            # the same query: the sync window stays closed
            with recording.resumed("query_end"):
                self.session._last_execute_time_s = time.perf_counter() - t0
                # a materializing collect serves its first row when it serves
                # its last: firstRowS == executeTimeS, honestly (collect_iter
                # is the path that beats it; docs/observability.md)
                self.session._last_first_row_s = \
                    self.session._last_execute_time_s
                try:
                    # AQE post-execution hook: store observed cardinalities +
                    # exchange bytes under this fingerprint for the NEXT
                    # execution (drift feedback, admission cost weighting)
                    from ..plan import aqe
                    aqe.note_execution(self.session, exec_plan, serving)
                except Exception:
                    pass
                try:
                    from ..service.telemetry import MetricsRegistry
                    MetricsRegistry.get().histogram(
                        "tpu_query_execute_seconds",
                        "collect-action execute wall seconds").observe(
                        self.session._last_execute_time_s)
                except Exception:
                    pass           # observability must never fail the query
                self.session._last_sync_report = sc.report()
                self.session._last_span_report = spans.report()
                # the recorder itself stays reachable so the bench runner /
                # tests can export the Chrome-trace timeline of this query
                self.session._last_span_recorder = spans
                if listeners:
                    from ..analysis import recompile
                    from .session import QueryExecution
                    ov = self.session._last_overrides
                    self.session._notify_query_listeners(QueryExecution(
                        self.session, exec_plan,
                        self.session._last_sync_report,
                        self.session._last_span_report,
                        recompile.recompiles_of(spans.programs),
                        lockdep.stats_delta(lk0),
                        violations=getattr(ov, "last_violations", ()) if ov
                        else ()))
                rkey = serving.get("resultKey")
                if rkey is not None:
                    # store AFTER the sync/span windows closed: the caching
                    # fetch must not perturb this query's reported sync counts
                    out = pc.store_result(self.session, rkey, out)
                # end-of-query buffer-lifecycle audit (analysis/ledger.py):
                # runs AFTER store_result so a cached result's pinned buffers
                # are owned by the cache, not leaked by this query.
                # BufferLeakError propagates in enforce mode — leak
                # discipline is the point.
                from ..analysis import ledger as _ledger
                self.session._last_ledger = _ledger.end_of_query(qid)
                try:
                    # opt-in structured query log (service/query_log.py, conf
                    # telemetry.queryLog.dir): one JSONL record per execution.
                    # Best-effort — the log must never fail the query.
                    from ..service import query_log
                    query_log.maybe_log(self.session, exec_plan, serving, qid,
                                        faults_before=faults0,
                                        tenant=ctx.tenant)
                except Exception:
                    pass
            return out
        finally:
            import sys as _sys
            if _sys.exc_info()[0] is not None:
                # failed (or cancelled) queries get the residency audit
                # too: a cancellation's cleanup must be ledger-provable,
                # and had_error keeps enforce mode from masking the
                # propagating exception with a leak report
                try:
                    from ..analysis import ledger as _ledger_err
                    self.session._last_ledger = _ledger_err.end_of_query(
                        qid, had_error=True)
                except Exception:
                    pass
            # the token's transition log retires with the query (the
            # query-log record read it above; a late peer META poll still
            # sees the cancelled verdict through the retired map)
            _lifecycle.unregister(qid)

    def collect_iter(self):
        """Streaming collect: yield host-resident batches as partitions
        drain (one batch per partition, in partition order) instead of
        materializing the whole result — the consumer sees first rows in
        first-partition time (docs/observability.md firstRowS). The
        concatenated rows of the yielded batches are IDENTICAL to
        ``collect()``'s, in the same order.

        The generator owns the full query lifecycle: closing it early
        releases the plan-cache entry, cancels undrained partitions,
        waits for running drains so staging arenas release, and still
        writes the query-log record. While the stream is live, cold
        fused-stage builds route to the background compile pool and
        batches flow through the per-op eager path until the compiled
        program swaps in (docs/compile.md §5). Streaming results are
        never stored in the result cache (an exact-repeat hit is still
        SERVED, as a single batch)."""
        from ..exec.tracing import QueryRecording
        from ..plan import plan_cache as pc
        parsed, self._parsed = self._parsed, None
        recording = QueryRecording().open(parsed)     # before planning
        try:
            try:
                exec_plan = self._execute()
            except BaseException:
                pc.release_plan_entry(pc.thread_serving())
                raise
            serving = pc.thread_serving() or {}
            try:
                hit = pc.serve_result_hit(self.session, serving)
                if hit is not None:
                    self.session._last_first_row_s = 0.0
                    yield hit
                    return
                for batch in self._collect_iter_planned(exec_plan, serving,
                                                        recording):
                    yield batch
            finally:
                pc.release_plan_entry(serving)
        finally:
            recording.close()

    def _collect_iter_planned(self, exec_plan, serving, recording):
        import time
        from ..exec import query_context as qc
        listeners = bool(self.session._query_listeners)
        if listeners:
            from ..analysis import lockdep
            lk0 = lockdep.stats()
        # reserved contexts win here too (the materializing collect's
        # adoption rule, above)
        ctx = qc.take_reserved()
        if ctx is not None:
            qid = ctx.query_id
        else:
            qid = qc.mint_query_id(exec_plan)
            ctx = qc.QueryContext(qid)
        self.session._last_query_id = qid
        qc.note_thread_query_id(qid)
        # the streaming marker rides the context to every partition-drain
        # worker thread: cold stage builds route to the compile pool
        # instead of blocking the first batches (compile_pool.routable)
        ctx.streaming = True
        self.session._last_tenant = ctx.tenant
        # lifecycle token index (the materializing collect's rule above);
        # unregistered in the finally
        from ..exec import lifecycle as _lifecycle
        _lifecycle.register(ctx)
        from ..analysis import faults as _faults
        faults0 = _faults.fired_total()
        try:
            from ..plan import aqe
            aqe.begin_query(self.session, exec_plan, serving)
        except Exception:
            pass
        self.session._last_first_row_s = None
        first_row_s = None
        sc, spans = recording.sync, recording.spans
        t0 = time.perf_counter()
        try:
            with qc.query_scope(ctx):
                try:
                    spans.query_id = qid
                    try:
                        for batch in exec_plan.execute_collect_iter():  # lint: cancel-ok body polls check_cancel per delivered batch
                            # streaming delivery is a lifecycle poll
                            # point: a cancelled stream stops between
                            # batches instead of draining to the end
                            _lifecycle.check_cancel()
                            if first_row_s is None:
                                first_row_s = time.perf_counter() - t0
                                self.session._last_first_row_s = \
                                    first_row_s
                            yield batch
                    except BaseException as e:
                        from ..service.telemetry import dump_on_error
                        dump_on_error(e)
                        raise
                finally:
                    recording.close()
        finally:
            # runs on exhaustion, failure AND early close: the lifecycle
            # bookkeeping must not depend on the consumer finishing
            self.session._last_execute_time_s = time.perf_counter() - t0
            try:
                from ..plan import aqe
                aqe.note_execution(self.session, exec_plan, serving)
            except Exception:
                pass
            try:
                from ..service.telemetry import MetricsRegistry
                reg = MetricsRegistry.get()
                reg.histogram(
                    "tpu_query_execute_seconds",
                    "collect-action execute wall seconds").observe(
                    self.session._last_execute_time_s)
                if first_row_s is not None:
                    reg.histogram(
                        "tpu_query_first_row_seconds",
                        "wall seconds from streaming collect to its "
                        "first yielded batch").observe(first_row_s)
            except Exception:
                pass
            self.session._last_sync_report = sc.report()
            self.session._last_span_report = spans.report()
            self.session._last_span_recorder = spans
            if listeners:
                try:
                    from ..analysis import recompile
                    from .session import QueryExecution
                    ov = self.session._last_overrides
                    self.session._notify_query_listeners(QueryExecution(
                        self.session, exec_plan,
                        self.session._last_sync_report,
                        self.session._last_span_report,
                        recompile.recompiles_of(spans.programs),
                        lockdep.stats_delta(lk0),
                        violations=getattr(ov, "last_violations", ())
                        if ov else ()))
                except Exception:
                    pass
            # end-of-query audit for the streaming path: had_error keeps
            # enforce mode from masking a propagating failure with a
            # leak report (the audit downgrades itself to record)
            import sys as _sys
            from ..analysis import ledger as _ledger
            self.session._last_ledger = _ledger.end_of_query(
                qid, had_error=_sys.exc_info()[0] is not None)
            try:
                from ..service import query_log
                query_log.maybe_log(self.session, exec_plan, serving,
                                    qid, faults_before=faults0,
                                    tenant=ctx.tenant)
            except Exception:
                pass
            _lifecycle.unregister(qid)

    def collect(self) -> List[tuple]:
        return self.collect_batch().rows()

    def toPandas(self):
        return self.collect_batch().to_pandas()

    def to_arrow(self):
        return self.collect_batch().to_arrow()

    def count(self) -> int:
        plan = lp.Aggregate(self._plan, [], [
            ex.Alias(lp.AggregateExpression("count_star", None), "count")])
        df = self._df(plan)
        return df.collect()[0][0]

    def show(self, n: int = 20, truncate: bool = True) -> None:
        print(self.limit(n).toPandas().to_string(index=False))

    def explain(self, extended: bool = False) -> None:
        """Print the physical plan. ``extended=True`` adds the overrides
        explain (fallback reasons + contract diagnostics);
        ``extended="analyze"`` EXECUTES the query (Spark's EXPLAIN
        ANALYZE) and prints the executed tree with each node's runtime
        metrics inline plus the query-level summary."""
        if isinstance(extended, str) and extended.lower() == "analyze":
            self.collect_batch()
            print(self.session.explain_analyze())
            return
        plan = self._analyzed()
        from ..plan.overrides import Overrides
        conf = self.session.conf.with_overrides(
            {"spark.rapids.tpu.sql.explain": "NONE"})
        ov = Overrides(conf)
        exec_plan = ov.apply(plan)
        print(exec_plan)
        if extended and ov.last_explain:
            print(ov.last_explain)

    @property
    def write(self) -> "DataFrameWriter":
        return DataFrameWriter(self)

    def createOrReplaceTempView(self, name: str) -> None:
        self.session._views[name] = self._plan


class _UsingRight(ex.ColumnRef):
    """Marker ref that must resolve against the RIGHT side in a USING join."""


def _dedupe_using(plan: lp.Join, using: List[str], how: str,
                  left: DataFrame, right: DataFrame) -> lp.LogicalPlan:
    """USING-join output keeps one copy of the key columns (Spark semantics)."""
    lnames = left.columns
    rnames = right.columns
    if how in ("left_semi", "left_anti"):
        return plan
    keep: List[ex.Expression] = []
    for c in lnames:
        keep.append(ex.ColumnRef(c))
    for c in rnames:
        if c not in using:
            keep.append(ex.ColumnRef(c))
    return lp.Project(plan, keep)


class GroupedData:
    def __init__(self, df: DataFrame, grouping: List[ex.Expression],
                 sets: Optional[str] = None):
        self.df = df
        self.grouping = grouping
        self.sets = sets          # None | "rollup" | "cube"

    def agg(self, *aggs: Union[Col, Dict[str, str]]) -> DataFrame:
        from ..ops.python_udf import PandasAggUDF
        if len(aggs) == 1 and isinstance(aggs[0], dict):
            aggs = tuple(
                getattr(F, op if op != "mean" else "avg")(F.col(c))
                for c, op in aggs[0].items())
        agg_exprs = [_unwrap(a) for a in aggs]

        def is_pandas_agg(e):
            inner = e.children[0] if isinstance(e, ex.Alias) else e
            return isinstance(inner, PandasAggUDF)
        if any(is_pandas_agg(e) for e in agg_exprs):
            if self.sets:
                raise ValueError(
                    "grouped-agg pandas UDFs do not support rollup/cube")
            if not all(is_pandas_agg(e) for e in agg_exprs):
                raise ValueError(
                    "cannot mix grouped-agg pandas UDFs with built-in "
                    "aggregates in one agg() (pyspark restriction)")
            names = [ex.output_name(g, i)
                     for i, g in enumerate(self.grouping)]
            names += [e.alias if isinstance(e, ex.Alias)
                      else ex.output_name(e, len(names) + i)
                      for i, e in enumerate(agg_exprs)]
            inner = [e.children[0] if isinstance(e, ex.Alias) else e
                     for e in agg_exprs]
            return self.df._df(lp.AggregateInPandas(
                self.df._plan, self.grouping, inner, names))
        if self.sets:
            return self._agg_grouping_sets(agg_exprs)
        out: List[ex.Expression] = list(self.grouping) + agg_exprs
        return self.df._df(lp.Aggregate(self.df._plan, self.grouping, out))

    def applyInPandas(self, fn, schema) -> DataFrame:
        """fn(pandas.DataFrame) -> DataFrame — or fn(key_tuple, pdf) —
        applied once per group (GpuFlatMapGroupsInPandasExec analog)."""
        from ..columnar import dtypes as dtm
        if not isinstance(schema, dtm.Schema):
            schema = dtm.Schema(schema)
        return self.df._df(lp.FlatMapGroupsInPandas(
            self.df._plan, list(self.grouping), fn, schema))

    def cogroup(self, other: "GroupedData") -> "CoGroupedData":
        """Pair this grouping with another frame's grouping for
        cogroup(...).applyInPandas (GpuFlatMapCoGroupsInPandasExec)."""
        return CoGroupedData(self, other)

    def _agg_grouping_sets(self, agg_exprs: List[ex.Expression]) -> DataFrame:
        """rollup/cube: Expand replicates every input row once per grouping
        set, nulling the grouped-out keys and tagging a grouping id; one
        hash aggregate over (keys..., gid) then computes all sets at once
        (the reference's GpuExpandExec + GpuHashAggregateExec pipeline,
        GpuExpandExec.scala)."""
        import itertools
        nk = len(self.grouping)
        if self.sets == "rollup":
            masks = [tuple(i < keep for i in range(nk))
                     for keep in range(nk, -1, -1)]
        else:
            masks = [tuple(bits) for bits in
                     itertools.product((True, False), repeat=nk)]
        child_cols = self.df.columns
        key_names = [ex.output_name(g, i)
                     for i, g in enumerate(self.grouping)]
        out_names = list(child_cols) + \
            [f"_g{i}" for i in range(nk)] + ["_gid"]
        projections: List[List[ex.Expression]] = []
        for mask in masks:
            proj: List[ex.Expression] = [ex.ColumnRef(c)
                                         for c in child_cols]
            gid = 0
            for i, keep in enumerate(mask):
                if keep:
                    proj.append(copy.deepcopy(self.grouping[i]))
                else:
                    # typed NULL of the key's dtype: a never-true branch
                    # keeps the analyzer's coercion rules in charge
                    proj.append(cd.CaseWhen(
                        [(ex.lit(False), copy.deepcopy(self.grouping[i]))],
                        None))
                    gid |= 1 << (nk - 1 - i)
            proj.append(ex.lit(gid))
            projections.append(proj)
        expand = lp.Expand(self.df._plan, projections, out_names)
        grouping = [ex.ColumnRef(f"_g{i}") for i in range(nk)] + \
            [ex.ColumnRef("_gid")]
        outputs = [ex.Alias(ex.ColumnRef(f"_g{i}"), key_names[i])
                   for i in range(nk)] + agg_exprs
        return self.df._df(lp.Aggregate(expand, grouping, outputs))

    def count(self) -> DataFrame:
        return self.agg(Col(ex.Alias(
            lp.AggregateExpression("count_star", None), "count")))

    def sum(self, *cols: str) -> DataFrame:
        return self.agg(*[F.sum(c).alias(f"sum({c})") for c in cols])

    def avg(self, *cols: str) -> DataFrame:
        return self.agg(*[F.avg(c).alias(f"avg({c})") for c in cols])

    mean = avg

    def min(self, *cols: str) -> DataFrame:
        return self.agg(*[F.min(c).alias(f"min({c})") for c in cols])

    def max(self, *cols: str) -> DataFrame:
        return self.agg(*[F.max(c).alias(f"max({c})") for c in cols])


class CoGroupedData:
    def __init__(self, left: "GroupedData", right: "GroupedData"):
        self.left = left
        self.right = right

    def applyInPandas(self, fn, schema) -> DataFrame:
        """fn(left_pdf, right_pdf) -> DataFrame — or fn(key, l, r) —
        applied once per key present on EITHER side (missing side =
        empty frame), matching pyspark cogroup semantics."""
        from ..columnar import dtypes as dtm
        if len(self.left.grouping) != len(self.right.grouping):
            raise ValueError(
                f"cogroup key counts differ: {len(self.left.grouping)} "
                f"vs {len(self.right.grouping)} (pyspark raises too)")
        if not isinstance(schema, dtm.Schema):
            schema = dtm.Schema(schema)
        return self.left.df._df(lp.FlatMapCoGroupsInPandas(
            self.left.df._plan, self.right.df._plan,
            list(self.left.grouping), list(self.right.grouping),
            fn, schema))


class DataFrameWriter:
    def __init__(self, df: DataFrame):
        self.df = df
        self._mode = "error"
        self._options: Dict[str, Any] = {}
        self._partition_by: List[str] = []

    def mode(self, m: str) -> "DataFrameWriter":
        self._mode = m
        return self

    def option(self, k: str, v: Any) -> "DataFrameWriter":
        self._options[k] = v
        return self

    def partitionBy(self, *cols: str) -> "DataFrameWriter":
        self._partition_by = list(cols)
        return self

    def parquet(self, path: str) -> None:
        self._write("parquet", path)

    def csv(self, path: str) -> None:
        self._write("csv", path)

    def orc(self, path: str) -> None:
        self._write("orc", path)

    def _write(self, fmt: str, path: str) -> None:
        plan = lp.WriteFile(self.df._plan, fmt, path, self._mode,
                            self._options, self._partition_by)
        df = self.df._df(plan)
        from ..plan import plan_cache as pc
        try:
            exec_plan = df._execute()
            for part in exec_plan.execute():
                for _ in part:
                    pass
        finally:
            # writes plan uncacheable today (fingerprint None) so this
            # is a no-op, but the release hook keeps every _execute()
            # caller symmetric if that ever changes
            pc.release_plan_entry(pc.thread_serving())
