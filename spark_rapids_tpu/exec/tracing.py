"""Tracing spans: named profiler ranges around hot regions.

Reference: ``NvtxWithMetrics.scala:27`` — NVTX ranges (optionally fused with
SQLMetrics timers) wrap every hot region so Nsight shows named spans:
semaphore acquire (GpuSemaphore.scala:107), agg batches (aggregate.scala:435),
shuffle write (RapidsShuffleInternalManager.scala:91).

TPU analog: ``jax.profiler.TraceAnnotation`` spans show up in xprof/
TensorBoard traces, on the same clock as the device ops. Disabled (no-op,
zero overhead beyond one attr check) unless
``spark.rapids.tpu.sql.tracing.enabled`` is on.

INSIDE a device program the layers are named by ``jax.named_scope``: the
plan operator outermost, then one of :data:`STAGES` (:func:`stage`).
Scopes exist only while jax traces and change HLO metadata alone; a
profile's ``XLA Ops`` then read ``jit(<family>)/<operator>/<stage>/...``.

Beyond the per-name self-time totals, ``SpanRecorder`` optionally records
every span's begin/end with its thread (conf
``spark.rapids.tpu.sql.tracing.timeline``) and exports a Chrome-trace /
Perfetto ``trace.json`` (:meth:`SpanRecorder.chrome_trace`), turning the
flat self-time map into an actual timeline — open it in chrome://tracing
or ui.perfetto.dev (see docs/observability.md).
"""

from __future__ import annotations

import functools
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

from ..analysis.lockdep import named_lock
from .metrics import current, metrics_enabled, pop_exec, push_exec

_enabled: Optional[bool] = None
_timeline: Optional[bool] = None


def _effective_conf():
    from ..analysis.sync_audit import _effective_conf as eff
    return eff()


def _tracing_on() -> bool:
    global _enabled
    if _enabled is None:
        from .. import config as cfg
        _enabled = bool(_effective_conf().get(cfg.TRACING_ENABLED))
    return _enabled


def _timeline_on() -> bool:
    global _timeline
    if _timeline is None:
        try:
            from .. import config as cfg
            _timeline = bool(_effective_conf().get(cfg.TRACING_TIMELINE))
        except Exception:
            _timeline = False
    return _timeline


def reset_cache() -> None:
    global _enabled, _timeline
    _enabled = None
    _timeline = None


#: the kernel stages a device program is divided into, each a
#: ``jax.named_scope`` inside its operator's scope. A stage with two
#: implementations has two names, so that moving work from one to the
#: other shows as seconds leaving one name and arriving at the other.
STAGES = (
    "scan_unpack", "filter", "project", "key_encode", "lexsort", "gather",
    "segment_starts", "segment_ids_to_rows", "segment_sum_scatter",
    "segment_sum_masked", "segment_minmax", "reduce",
    "join_probe", "join_gather", "compact", "concat", "shuffle_split",
    # the steps of an SPMD mesh stage (parallel/mesh.py), outside the
    # kernels' own stages: ``TpuMeshGroupByExec/partial_agg/lexsort/...``
    "partial_agg", "bucket", "all_to_all", "flatten", "merge_agg", "sample",
    "local_sort")

#: what a query's mesh stages count (``last_query_metrics()["mesh"]``,
#: fed by ``parallel/mesh.run_stage``): executions of an SPMD stage, their
#: ``all_to_all``s and the operand bytes of those, the bytes copied from
#: the home device to the workers before a stage and back after it, and
#: the host-clock seconds of the three steps of a stage
MESH_COUNTERS = ("stages", "iciExchanges", "iciBytes", "placeBytes",
                 "gatherBytes", "placeS", "spmdS", "gatherS")


#: what the host does per batch between two program calls, as named sites
#: (:func:`host_site`; docs/observability.md §9 lists the functions under
#: each): only under ``tracing.enabled``, as profiler annotations and as
#: ``last_query_metrics()["host"]["sites"]``
HOST_SITES = (
    "count_arg", "conf_read", "fusable", "program_key", "program_lookup",
    "param_args", "flat_args", "spillable", "admission", "window", "shrink")


def stage(name: str, operator: Optional[str] = None):
    """Decorator: the kernel runs inside ``jax.named_scope(name)``, one of
    :data:`STAGES` — and, given ``operator``, inside that scope first.
    Costs a context-manager entry where the kernel runs eagerly and
    nothing in a compiled program."""
    assert name in STAGES, name

    def deco(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            import jax
            with jax.named_scope(f"{operator}/{name}" if operator else name):
                return fn(*args, **kwargs)
        return scoped
    return deco


def shared_stage(name: str):
    """:func:`stage` for the whole of a program that several operators
    share (the scan unpack, concat, the shuffle split): its ops read
    ``shared/<stage>``."""
    return stage(name, operator="shared")


def operator_scope(node):
    """``jax.named_scope`` of a plan operator (its class name, or the
    name itself): the outer scope of what it contributes to a program."""
    import jax
    return jax.named_scope(node if isinstance(node, str)
                           else type(node).__name__)


def _annotation(name: str, rec: Optional["SpanRecorder"], **kw):
    """A profiler annotation that carries the active query's id."""
    import jax
    qid = rec.query_id if rec is not None else None
    if qid is not None:
        kw["query"] = qid
    return jax.profiler.TraceAnnotation(name, **kw)


def _open_exec() -> Dict[str, str]:
    """``op=<exec>`` of the innermost open exec on this thread, for an
    annotation."""
    op = getattr(current(), "owner", None)
    return {"op": op} if op else {}


def program_annotation(family: str, rec: Optional["SpanRecorder"]):
    """``program:<family>`` round one program call (only under
    ``tracing.enabled``): the host's tracing, cache load and enqueue lie
    in the profile beside the device ops the call launched."""
    return _annotation("program:" + family, rec, **_open_exec())


# ---------------------------------------------------------------------------
# Host sites: what the host does per batch, named (tracing.enabled only)
# ---------------------------------------------------------------------------

_site_tls = threading.local()
_SITES: Dict[str, "_HostSite"] = {}


class _HostSite:
    """One name of :data:`HOST_SITES` as a context manager and a
    decorator. Off, it costs the cached ``_tracing_on()`` check; on, it is
    a profiler annotation ``site:<name>`` (``query=<id>``, ``op=<exec>``)
    and adds a count and its SELF seconds to the open recorder: the sites
    nested inside it, the program calls and the readback waits
    (:meth:`SpanRecorder.note_inner`) are taken out, so that sites,
    ``dispatchS`` and ``syncWaitS`` add up without overlap."""

    __slots__ = ("name",)

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        if _tracing_on():
            rec = SpanRecorder.active
            ann = _annotation("site:" + self.name, rec, **_open_exec())
            ann.__enter__()
            stack = getattr(_site_tls, "stack", None)
            if stack is None:
                stack = _site_tls.stack = []
            # [site, annotation, recorder, begin, seconds not its own]
            stack.append([self, ann, rec, time.perf_counter(), 0.0])
        return self

    def __exit__(self, *exc):
        if not _enabled:
            return False
        stack = getattr(_site_tls, "stack", None)
        if not stack or stack[-1][0] is not self:
            return False            # entered with tracing off
        _site, ann, rec, t0, inner = stack.pop()
        elapsed = time.perf_counter() - t0
        ann.__exit__(*exc)
        if stack:
            stack[-1][4] += elapsed
        if rec is not None:
            rec.note_site(self.name, max(0.0, elapsed - inner))
        return False

    def __call__(self, fn):
        @functools.wraps(fn)
        def at_site(*args, **kwargs):
            if not _tracing_on():
                return fn(*args, **kwargs)
            with self:
                return fn(*args, **kwargs)
        return at_site


def host_site(name: str) -> _HostSite:
    """The site ``name`` of :data:`HOST_SITES`: ``with host_site(n):`` or
    ``@host_site(n)``."""
    site = _SITES.get(name)
    if site is None:
        assert name in HOST_SITES, name
        site = _SITES[name] = _HostSite(name)
    return site


# the process registry's ``tpu_span_seconds`` handles by span name: a
# lookup per span close would take the registry's lock twice
_span_hists: Dict[str, Any] = {}
_span_hists_of: Any = None
_tel: Any = None


def _telemetry_span(name: str, begin: float, elapsed: float,
                    err: bool) -> None:
    """Feed the process-lifetime telemetry at span close (a flush
    boundary): the always-on flight ring gets the span (error-marked
    when it unwound on an exception — the post-mortem breadcrumb), and
    the registry span histogram gets its duration."""
    global _tel, _span_hists, _span_hists_of
    tel = _tel
    if tel is None:
        from ..service import telemetry as tel
        _tel = tel
    try:
        if tel._flight_on():
            data = {"beginS": round(begin, 6), "durS": round(elapsed, 6)}
            if err:
                data["error"] = True
            tel.FlightRecorder.get().record("span", name, data)
        if metrics_enabled():
            reg = tel.MetricsRegistry.get()
            if reg is not _span_hists_of:       # the registry was reset
                _span_hists, _span_hists_of = {}, reg
            hist = _span_hists.get(name)
            if hist is None:
                hist = _span_hists[name] = reg.histogram(
                    "tpu_span_seconds", "trace span durations", name=name)
            hist.observe(elapsed)
    except Exception:
        pass                   # telemetry must never fail the span


class trace_span:
    """Named profiler span (NvtxWithMetrics: optionally also feeds a
    metrics timer). Always feeds the active :class:`SpanRecorder` (the
    per-query wall-clock breakdown) and the ALWAYS-ON flight recorder
    (``service/telemetry``: post-mortems without tracing pre-enabled);
    the jax profiler annotation is config-gated. When ``metrics`` is an
    exec's bag, the span also marks that exec as the innermost open one
    on this thread (``exec/metrics.push_exec``) so attributed events —
    host syncs, recompiles, spill bytes — land on its operator node."""

    __slots__ = ("name", "metrics", "metric_key", "_rec", "_frame", "_t0",
                 "_ann")

    def __init__(self, name: str, metrics=None,
                 metric_key: Optional[str] = None):
        self.name = name
        self.metrics = metrics
        self.metric_key = metric_key

    def __enter__(self):
        rec = self._rec = SpanRecorder.active
        self._t0 = time.perf_counter()
        self._frame = rec._push(self.name) if rec is not None else None
        if self.metrics is not None:
            push_exec(self.metrics)
        if _tracing_on():
            self._ann = _annotation(self.name, rec)
            self._ann.__enter__()
        else:
            self._ann = None
        return None

    def __exit__(self, etype, evalue, tb):
        if self._ann is not None:
            self._ann.__exit__(etype, evalue, tb)
        metrics = self.metrics
        if metrics is not None:
            pop_exec(metrics)
        t0 = self._t0
        elapsed = time.perf_counter() - t0
        if self._rec is not None:
            self._rec._pop(self._frame, self.name, elapsed, begin=t0)
        if metrics is not None and self.metric_key:
            metrics.inc(self.metric_key, elapsed)
        _telemetry_span(self.name, t0, elapsed, etype is not None)
        return False


#: the parts of the host ledger a span's own seconds go to: the spans
#: named here (and what is nested in them) are a part by themselves, the
#: root keeps what nobody claims, every other span is execution
_SECTIONS = ("parse", "plan", "operator", "fetch", "root")
_SECTION_OF = {"parse": "parse", "plan": "plan", "fetch_to_host": "fetch",
               "query": "root"}


class SpanRecorder:
    """Per-query wall-clock breakdown: every ``trace_span`` while a
    recorder is active contributes its SELF time (elapsed minus enclosed
    child spans) to a name -> seconds map, so the report names where the
    execute wall went without double counting nesting (the NVTX-range
    timeline of the reference, reduced to per-name totals). Partitions
    drain on a thread pool, so stacks are thread-local and concurrent
    spans can legitimately sum past the wall clock — ``report()`` carries
    the wall clock and the ``concurrency`` ratio (sum of self-time over
    wall) so such reports read as parallelism, not as confusion.

    With ``timeline=True`` (or conf ``...sql.tracing.timeline``) every
    span's (begin, duration, thread) is kept and
    :meth:`chrome_trace` exports Chrome-trace JSON."""

    active: Optional["SpanRecorder"] = None

    def __init__(self, timeline: Optional[bool] = None):
        import collections
        self._self_s = collections.defaultdict(float)
        self._count = collections.defaultdict(int)
        self._mu = named_lock("exec.tracing.SpanRecorder._mu")
        self._tls = threading.local()
        self._timeline = _timeline_on() if timeline is None else timeline
        # (name, begin, dur, tid, tname, parent span name or None)
        self._events: List[tuple] = []
        # per-span compile events: name -> [count, seconds] of what XLA
        # rebuilt (trace, lowering, compile, cache load) while the span
        # was the innermost open one (exec/compile_cache._on_duration)
        self._rebuilds: Dict[str, list] = {}
        # family -> counters of every program this query dispatched or
        # rebuilt; written under analysis/recompile's lock
        # (recompile.note_call / note_rebuild), reported by
        # last_query_metrics()["programs"]
        self.programs: Dict[str, Dict[str, Any]] = {}
        # this query's :data:`MESH_COUNTERS`
        self.mesh: Dict[str, Any] = dict.fromkeys(MESH_COUNTERS, 0)
        self._root: Optional[str] = None   # the driving thread's open root
        self._t0: Optional[float] = None   # entered wall-clock origin
        self._wall: Optional[float] = None
        # -- the host ledger (:meth:`host_ledger`) -----------------------
        # the thread that entered the recorder, whose wall the ledger
        # tiles; the host's own seconds of its spans by ledger section;
        # its seconds inside program calls and readback waits
        self._driver: Optional[int] = None
        self._host_s = dict.fromkeys(_SECTIONS, 0.0)
        self._driver_inner_s = 0.0
        # where the caller's call began and ended on the host clock: the
        # parse of ``session.sql`` where the frame came from it, else the
        # enter; the exit, or the end of the last resumed span
        self._origin: Optional[float] = None
        self._end: Optional[float] = None
        self._parse_s = 0.0
        self.parse_cache_hit = 0
        # seconds the task semaphore was held (acquire to release: it
        # brackets the task, so it is no self time of any span)
        self._hold_s = 0.0
        # host site -> [count, self seconds, those seconds by innermost
        # open span] (tracing.enabled only)
        self._sites: Dict[str, list] = {}
        # the query id this recorder's spans belong to (set by the
        # collect that enters the recorder, exec/query_context.py):
        # rides every exported Chrome-trace event so merged multi-worker
        # timelines can join both workers' spans under one query
        self.query_id: Optional[str] = None

    def __enter__(self):
        self._prev = SpanRecorder.active  # lint: unguarded-ok recorder entered on the driving thread only; pool workers read .active, never swap it
        SpanRecorder.active = self  # lint: unguarded-ok single driving-thread swap; worker reads race only with query start/end, where no spans are open
        self._t0 = time.perf_counter()  # lint: unguarded-ok driving-thread-only enter bookkeeping
        self._driver = threading.get_ident()  # lint: unguarded-ok driving-thread-only enter bookkeeping
        if self._origin is None:
            self._origin = self._t0  # lint: unguarded-ok driving-thread-only enter bookkeeping
        return self

    def __exit__(self, *exc):
        SpanRecorder.active = self._prev  # lint: unguarded-ok same single driving-thread swap as __enter__
        if self._t0 is not None:
            self._end = time.perf_counter()  # lint: unguarded-ok driving-thread-only exit bookkeeping
            self._wall = self._end - self._t0  # lint: unguarded-ok driving-thread-only exit bookkeeping
        return False

    def adopt_parse(self, begin: float, seconds: float, cache_hit: bool
                    ) -> None:
        """Take over the ``parse`` span ``session.sql`` timed before this
        recorder existed: its seconds as a span of this query's report,
        its begin as the origin of the ledger's ``callS``."""
        self._origin = begin  # lint: unguarded-ok driving-thread-only, before any span of the query opens
        self._parse_s = seconds  # lint: unguarded-ok driving-thread-only, before any span of the query opens
        self.parse_cache_hit = int(cache_hit)
        with self._mu:
            self._self_s["parse"] += seconds
            self._count["parse"] += 1
            self._host_s["parse"] += seconds
            if self._timeline:
                t = threading.current_thread()
                self._events.append(("parse", begin, seconds, t.ident,
                                     t.name, None))

    def _stack(self):
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _push(self, name):
        # the frame carries its span name so the sync counter can
        # attribute device->host readbacks to the innermost open span
        # (the syncs-per-span breakdown the bench runner reports)
        st = self._stack()
        # the span that caused this one: the enclosing span on this
        # thread, else (a pool thread's first span) the query's root
        if st:
            parent = st[-1]["name"]
            # what lies under ``parse`` / ``plan`` / ``fetch_to_host``
            # belongs to them; under the root or an operator span, a span
            # is what its own name says
            section = st[-1]["section"]
            if section == "root" or section == "operator":
                section = _SECTION_OF.get(name, "operator")
        else:
            parent = self._root
            section = _SECTION_OF.get(name, "operator")
            if parent is None:
                self._root = name  # lint: unguarded-ok set once by the driving thread's first span, before any task thread runs
        # inner_s: the seconds of this frame's SELF time the host spent
        # inside program calls and readback waits (note_inner)
        frame = {"name": name, "child_s": 0.0, "parent": parent,
                 "section": section, "inner_s": 0.0}
        st.append(frame)
        return frame

    def current_span(self):
        """Innermost open span name on THIS thread (None outside spans)."""
        st = self._stack()
        return st[-1]["name"] if st else None

    def _pop(self, frame, name, elapsed, begin: Optional[float] = None):
        # remove THIS frame by identity, not the stack top: spans held open
        # across generator yields (the pipelined join suspends mid-span)
        # close out of order, and popping the top would steal an unrelated
        # open frame — misattributing every enclosing span's self-time
        st = self._stack()
        idx = None
        for i in range(len(st) - 1, -1, -1):
            if st[i] is frame:
                idx = i
                break
        if idx is not None:
            del st[idx]
            if idx > 0:
                # elapsed counts as child time of the frame that was the
                # parent at open time (the one below it), even if younger
                # frames are still open above
                st[idx - 1]["child_s"] += elapsed
        self_s = max(0.0, elapsed - frame["child_s"])
        ev = None
        if self._timeline and begin is not None:
            t = threading.current_thread()
            ev = (name, begin, elapsed, t.ident, t.name, frame["parent"])
        driver = threading.get_ident() == self._driver
        with self._mu:
            self._self_s[name] += self_s
            self._count[name] += 1
            if driver:
                self._host_s[frame["section"]] += max(
                    0.0, self_s - frame["inner_s"])
            if ev is not None:
                self._events.append(ev)

    def note_inner(self, seconds: float) -> None:
        """Seconds the host just spent inside a program call
        (``exec/compile_cache.Program``) or a counted readback wait
        (:class:`SyncCounter`): no part of the host's OWN time in the
        innermost open span, nor in the innermost open host site."""
        st = getattr(self._tls, "stack", None)
        if st:
            st[-1]["inner_s"] += seconds
        if threading.get_ident() == self._driver:
            self._driver_inner_s += seconds  # lint: unguarded-ok written by the driving thread alone
        sites = getattr(_site_tls, "stack", None)
        if sites:
            sites[-1][4] += seconds

    def note_site(self, name: str, seconds: float) -> None:
        """One pass through a host site (:class:`_HostSite`), inside the
        innermost span open on this thread."""
        span = self.current_span() or "<no-span>"
        with self._mu:
            ent = self._sites.setdefault(name, [0, 0.0, {}])
            ent[0] += 1
            ent[1] += seconds
            ent[2][span] = ent[2].get(span, 0.0) + seconds

    def note_hold(self, seconds: float) -> None:
        """The task semaphore was released after ``seconds``."""
        with self._mu:
            self._hold_s += seconds

    def note_rebuild(self, seconds: float) -> None:
        """Charge one compile event of XLA's to the innermost span open
        on this thread (``<no-span>`` outside any)."""
        name = self.current_span() or "<no-span>"
        with self._mu:
            ent = self._rebuilds.setdefault(name, [0, 0.0])
            ent[0] += 1
            ent[1] += seconds

    def note_mesh(self, **deltas) -> None:
        """Add to this query's :data:`MESH_COUNTERS`."""
        with self._mu:
            for key, value in deltas.items():
                self.mesh[key] += value

    def add(self, name, seconds):
        """Account an externally-timed interval that has just ended as a
        leaf span (the wait for the task semaphore): a child of the span
        open on this thread, where there is one, so that the interval is
        not that span's self time as well."""
        st = self._stack()
        if st:
            st[-1]["child_s"] += seconds
        ev = None
        if self._timeline:
            t = threading.current_thread()
            ev = (name, time.perf_counter() - seconds, seconds,
                  t.ident, t.name, st[-1]["name"] if st else self._root)
        driver = threading.get_ident() == self._driver
        with self._mu:
            self._self_s[name] += seconds
            self._count[name] += 1
            if driver:
                self._host_s[_SECTION_OF.get(name, "operator")] += seconds
            if ev is not None:
                self._events.append(ev)

    def wall_s(self) -> float:
        """Wall clock between __enter__ and __exit__ (or now, while still
        open), with the adopted ``parse`` before it and the resumed spans
        after it; 0.0 when the recorder was never entered."""
        if self._wall is not None:
            return self._wall + self._parse_s
        if self._t0 is None:
            return 0.0
        return time.perf_counter() - self._t0 + self._parse_s

    def report(self) -> dict:
        """name -> {selfS, count}, most-expensive first, plus three
        reserved scalar entries: ``wallS`` (the recorder's wall clock),
        ``concurrency`` (sum of self-time over wall — pool threads
        legitimately push this past 1.0; ~1.0 means serial execution:
        the self times of a one-thread query tile its wall) and
        ``semaphoreHoldS`` (seconds the task semaphore was held: it
        brackets whole tasks, so it is no part of that sum).
        A span under which XLA rebuilt anything also carries
        ``rebuilds`` / ``rebuildS`` (compile events and their seconds)."""
        with self._mu:
            out: Dict[str, Any] = {
                name: {"selfS": round(s, 4), "count": self._count[name]}
                for name, s in sorted(self._self_s.items(),
                                      key=lambda kv: -kv[1])}
            total_self = sum(self._self_s.values())
            for name, (n, secs) in self._rebuilds.items():
                ent = out.setdefault(name, {"selfS": 0.0, "count": 0})
                ent["rebuilds"] = n
                ent["rebuildS"] = round(secs, 6)
        wall = self.wall_s()
        out["wallS"] = round(wall, 4)
        out["concurrency"] = round(total_self / wall, 2) if wall > 0 else 0.0
        out["semaphoreHoldS"] = round(self._hold_s, 4)
        return out

    def host_ledger(self, programs: Dict[str, Dict[str, Any]],
                    sync_wait_s: float) -> Dict[str, Any]:
        """The host's account of ONE query, in seconds on the host clock
        of the thread that ran it (``last_query_metrics()["host"]``,
        docs/observability.md §9): ``callS`` from the origin (the parse
        of ``session.sql``, else the action) to the end (the caller's
        last ``fetch_to_host`` of the result, else the end of the
        collect), and the parts that tile it. ``dispatchS`` and
        ``syncWaitS`` are the query's totals (the sums over ``programs``,
        its reported map, and the ``sync`` report's); what of them ran on
        pool threads, beside the driving thread's wall and not inside it,
        is ``offThreadS``.
        ``unaccountedS`` is what no span but the root names."""
        with self._mu:
            host = dict(self._host_s)
            sites = {k: {"count": n, "s": round(secs, 6),
                         "bySpan": {sp: round(v, 6)
                                    for sp, v in sorted(by.items())}}
                     for k, (n, secs, by) in sorted(self._sites.items())}
        dispatches = sum(p["dispatches"] for p in programs.values())
        dispatch_s = sum(p["dispatchS"] for p in programs.values())
        end = self._end if self._end is not None else time.perf_counter()
        call_s = end - self._origin if self._origin is not None else 0.0
        off_thread_s = max(
            0.0, dispatch_s + sync_wait_s - self._driver_inner_s)
        named = (host["parse"] + host["plan"] + host["operator"] +
                 host["fetch"] + dispatch_s + sync_wait_s - off_thread_s)
        return {
            "callS": round(call_s, 6),
            "parseS": round(host["parse"], 6),
            "parseCacheHit": self.parse_cache_hit,
            "planS": round(host["plan"], 6),
            "dispatchS": round(dispatch_s, 6),
            "dispatches": dispatches,
            "syncWaitS": round(sync_wait_s, 6),
            "operatorS": round(host["operator"], 6),
            "fetchS": round(host["fetch"], 6),
            "offThreadS": round(off_thread_s, 6),
            "unaccountedS": round(call_s - named, 6),
            "sites": sites,
        }

    # -- Chrome-trace / Perfetto timeline export ----------------------------
    def chrome_trace(self) -> dict:
        """The recorded spans as a Chrome-trace JSON object (the format
        chrome://tracing and ui.perfetto.dev open natively): one complete
        ("X") event per span with microsecond ts/dur relative to recorder
        entry, grouped by thread, plus thread_name metadata so the task
        pool / shuffle threads show under their real names."""
        base = self._t0 if self._t0 is not None else 0.0
        with self._mu:
            events = list(self._events)
        out: List[dict] = [{
            "ph": "M", "name": "process_name", "pid": 0, "tid": 0,
            "args": {"name": "spark-rapids-tpu query"}}]
        # synthetic track ids keyed on (ident, name): CPython REUSES
        # thread idents after a thread exits, so keying on ident alone
        # would merge a dead shuffle-conn thread's spans into whichever
        # later thread inherited its ident
        track_of: Dict[tuple, int] = {}
        for name, begin, dur, tid, tname, parent in events:
            track = track_of.setdefault((tid, tname), len(track_of) + 1)
            ev = {
                "ph": "X", "cat": "span", "name": name, "pid": 0,
                "tid": track, "ts": round((begin - base) * 1e6, 1),
                "dur": round(dur * 1e6, 1)}
            args = {}
            if self.query_id is not None:
                # per-event query attribution: the merged multi-worker
                # timeline filters/joins spans on this
                args["query"] = self.query_id
            if parent is not None:
                args["parent"] = parent
            if args:
                ev["args"] = args
            out.append(ev)
        for (_tid, tname), track in sorted(track_of.items(),
                                           key=lambda kv: kv[1]):
            out.append({"ph": "M", "name": "thread_name", "pid": 0,
                        "tid": track, "args": {"name": tname}})
        doc = {"traceEvents": out, "displayTimeUnit": "ms"}
        if self.query_id is not None:
            doc["queryId"] = self.query_id
        return doc

    def dump_chrome_trace(self, path: str) -> str:
        """Write :meth:`chrome_trace` to ``path`` (the per-query
        ``trace.json`` the bench runner emits); returns the path.
        Parent directories are created defensively — a --trace-dir
        naming a not-yet-existing nested path must not fail the dump."""
        import json
        import os
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path


def merge_chrome_traces(traces, query_id: Optional[str] = None) -> dict:
    """Join several workers' Chrome-trace documents into ONE timeline
    (docs/observability.md §8): each source becomes a distinct ``pid``
    (its own process group in chrome://tracing / ui.perfetto.dev), its
    thread tracks and thread_name metadata ride along unchanged, and —
    when ``query_id`` is given — span ("X") events are filtered to the
    ones carrying that query id, so a merged distributed timeline shows
    exactly one query across every worker that executed it.

    ``traces`` items are Chrome-trace dicts (``SpanRecorder.chrome_trace``
    output) or paths to dumped trace.json files."""
    import json
    traces = list(traces)
    events: List[dict] = []
    for w, tr in enumerate(traces):
        if isinstance(tr, str):
            with open(tr) as f:
                tr = json.load(f)
        label = f"worker {w}"
        saw_process_meta = False
        for ev in tr.get("traceEvents", ()):
            ev = dict(ev)
            if ev.get("ph") == "X" and query_id is not None and \
                    (ev.get("args") or {}).get("query") != query_id:
                continue
            if ev.get("ph") == "M" and ev.get("name") == "process_name":
                saw_process_meta = True
                prev = (ev.get("args") or {}).get("name", "")
                ev["args"] = {"name": f"{label}: {prev}" if prev else label}
            ev["pid"] = w
            events.append(ev)
        if not saw_process_meta:
            events.append({"ph": "M", "name": "process_name", "pid": w,
                           "tid": 0, "args": {"name": label}})
    doc = {"traceEvents": events, "displayTimeUnit": "ms",
           "mergedSources": len(traces)}
    if query_id is not None:
        doc["queryId"] = query_id
    return doc


def record_span(name: str, seconds: float) -> None:
    """Feed an externally-timed interval into the active recorder (no-op
    when no query is recording)."""
    rec = SpanRecorder.active
    if rec is not None:
        rec.add(name, seconds)


def record_hold(seconds: float) -> None:
    """The task semaphore's hold, acquire to release, into the active
    recorder's ``semaphoreHoldS``."""
    rec = SpanRecorder.active
    if rec is not None:
        rec.note_hold(seconds)


class QueryRecording:
    """The recorders of ONE collect: a :class:`SyncCounter` and a
    :class:`SpanRecorder` with the root span ``query`` open. Opened
    before planning and closed where execution ends (the reports are
    read after that); :meth:`resumed` re-opens the span side for the
    result's ``fetch_to_host``, which the caller runs afterwards."""

    def __init__(self):
        self.sync = SyncCounter()
        self.spans = SpanRecorder()
        self._open = None

    def open(self, parsed: Optional[tuple] = None) -> "QueryRecording":
        """``parsed``: the (begin, seconds, cache hit) ``session.sql``
        left on the frame this query collects, if it built it."""
        import contextlib
        if parsed is not None:
            self.spans.adopt_parse(*parsed)
        with contextlib.ExitStack() as st:
            st.enter_context(self.sync)
            st.enter_context(self.spans)
            st.enter_context(trace_span("query"))
            self._open = st.pop_all()
        return self

    def close(self) -> None:
        """Idempotent; an exception in flight marks the root span."""
        import sys
        st, self._open = self._open, None
        if st is not None:
            st.__exit__(*sys.exc_info())

    @contextmanager
    def resumed(self, name: str):
        """A span of the same query after its recorder closed: recorded
        with the query's id, its time added to the recorder's wall, its
        end the end of the ledger's ``callS``."""
        rec = self.spans
        if self._open is not None or SpanRecorder.active is not None:
            with trace_span(name):    # still (or again) inside a query
                yield
            return
        SpanRecorder.active = rec  # lint: unguarded-ok the caller's thread, after its query ended and with no other recorder active
        rec._driver = threading.get_ident()  # lint: unguarded-ok the caller's thread, after its query ended
        t0 = time.perf_counter()
        try:
            with trace_span(name):
                yield
        finally:
            SpanRecorder.active = None  # lint: unguarded-ok restores the idle state checked above
            if rec._wall is not None:
                rec._end = time.perf_counter()  # lint: unguarded-ok caller-thread bookkeeping after the query ended
                rec._wall += rec._end - t0  # lint: unguarded-ok caller-thread bookkeeping after the query ended


# ---------------------------------------------------------------------------
# Attributed host-sync counting
# ---------------------------------------------------------------------------
#
# Each blocking device->host readback is a host sync: the host stops
# dispatching until the device has drained to that point, so a query's
# end-to-end time tracks HOW MANY syncs it performs as much as kernel
# time. Wall-clock varies run to run on a shared host; attributed sync
# counts are deterministic, so they are a regression metric that needs
# no chip (the reference's analog is NVTX ranges + nsys counting kernel
# launches and D2H copies).

class SyncCounter:
    """Counts blocking device->host materializations while active, each
    attributed to the innermost spark_rapids_tpu frame that triggered it.
    Works by wrapping ``ArrayImpl._value`` — the single funnel every
    np.asarray / device_get / float() / int() readback goes through.

    The wrapper installs once and STAYS installed (one None check per
    readback when no counter is active — cheaper than racing property
    swaps on the live class). The entering thread's counter also becomes
    the process default so task-pool worker threads (which do the actual
    partition drains) record into it; a thread entering its own counter
    overrides the default for itself. ``_uninstall`` exists for tests
    that must restore the pristine property."""

    _tls = None                    # lazy threading.local
    #: process-lifetime total of counted syncs (telemetry registry gauge
    #: ``tpu_host_syncs_total``); best-effort like the per-counter maps
    process_total: int = 0
    _default_stack: List["SyncCounter"] = []
    # guards _default_stack: counters enter on the driving thread but
    # exits can interleave across threads (generator-suspended queries,
    # tests driving counters from workers), and bare list.append/remove
    # racing on the shared stack can drop or resurrect a default counter
    _stack_mu = named_lock("exec.tracing.SyncCounter._default_stack")
    _orig_value = None

    @classmethod
    def _get_active(cls) -> Optional["SyncCounter"]:
        tls = cls._tls
        local = getattr(tls, "active", None) if tls is not None else None
        if local is not None:
            return local
        # LOCK-FREE read: this runs on EVERY ArrayImpl._value access (the
        # readback funnel), so it must not acquire. Mutations (__enter__/
        # __exit__) serialize under _stack_mu; the read handles the
        # check-then-index window (a concurrent exit emptying the list)
        # by catching instead of locking — either counter-or-None answer
        # is valid during a swap
        try:
            return cls._default_stack[-1]
        except IndexError:
            return None

    def __init__(self):
        self.total = 0
        self.sites: dict = {}
        self.spans: dict = {}      # innermost-span name -> sync count
        # seconds the host stood blocked in those readbacks: in all, per
        # site and per innermost span (same keys as the counts)
        self.wait_s = 0.0
        self.site_wait_s: dict = {}
        self.span_wait_s: dict = {}

    # -- patch management ---------------------------------------------------
    @classmethod
    def _install(cls):
        if cls._orig_value is not None:
            return
        from jax._src import array as jarray
        orig = jarray.ArrayImpl._value

        def counting_value(self_arr):
            c = cls._get_active()
            # only count REAL syncs: a cached host value is free
            if c is None or \
                    getattr(self_arr, "_npy_value", None) is not None:
                return orig.fget(self_arr)
            return c._timed_read(orig.fget, self_arr)

        cls._orig_value = orig  # lint: unguarded-ok one-time process-lifetime patch installed from the first entering thread
        jarray.ArrayImpl._value = property(counting_value)

    @classmethod
    def _uninstall(cls):
        if cls._orig_value is None:
            return
        from jax._src import array as jarray
        jarray.ArrayImpl._value = cls._orig_value
        cls._orig_value = None  # lint: unguarded-ok test-only restore of the pristine property

    def _timed_read(self, read, arr):
        """Count the readback, then time the wait for it; under
        ``tracing.enabled`` the wait is a ``host_sync`` profiler span."""
        site, span = self._record()
        rec = SpanRecorder.active
        t0 = time.perf_counter()
        try:
            if _tracing_on():
                with _annotation("host_sync", rec, site=site):
                    return read(arr)
            return read(arr)
        finally:
            waited = time.perf_counter() - t0
            if rec is not None:
                rec.note_inner(waited)
            self.wait_s += waited  # lint: unguarded-ok best-effort counter, see total in _record
            self.site_wait_s[site] = self.site_wait_s.get(site, 0.0) + waited  # lint: unguarded-ok best-effort counter map, see total in _record
            self.span_wait_s[span] = self.span_wait_s.get(span, 0.0) + waited  # lint: unguarded-ok best-effort counter map, see total in _record

    def _record(self):
        import traceback
        self.total += 1  # lint: unguarded-ok best-effort counter: concurrent increments may undercount, the attributed counts are advisory diagnostics
        SyncCounter.process_total += 1  # lint: unguarded-ok same best-effort counter discipline, harvested as a telemetry gauge
        site = "<unknown>"
        for frame in reversed(traceback.extract_stack(limit=24)):
            fn = frame.filename
            if "spark_rapids_tpu" in fn and "tracing.py" not in fn:
                short = fn[fn.rindex("spark_rapids_tpu"):]
                site = f"{short}:{frame.lineno}"
                break
        self.sites[site] = self.sites.get(site, 0) + 1  # lint: unguarded-ok best-effort counter map, see total above
        # flight-recorder breadcrumb: which code path paid a round trip
        # right before a crash (the post-mortem question)
        from ..service.telemetry import flight_record
        flight_record("sync", site)
        # attribute to the innermost open span on this thread (the
        # analysis/sync_audit per-span breakdown): which named region of
        # the execute wall is paying link round trips
        rec = SpanRecorder.active
        span = rec.current_span() if rec is not None else None
        span = span or "<no-span>"
        self.spans[span] = self.spans.get(span, 0) + 1  # lint: unguarded-ok best-effort counter map, see total above
        # ...and to the innermost open EXEC's metrics bag, so EXPLAIN
        # ANALYZE shows which plan node paid the round trip
        from .metrics import attribute
        attribute("hostSyncs")
        return site, span

    # -- context ------------------------------------------------------------
    def __enter__(self):
        cls = SyncCounter
        cls._install()
        if cls._tls is None:
            cls._tls = threading.local()
        self._prev = getattr(cls._tls, "active", None)  # lint: unguarded-ok entering thread's own field, set before the counter is shared
        cls._tls.active = self
        # the entering thread's counter is also the process default so
        # pool worker threads record into it; removal is by identity (not
        # LIFO) so interleaved exits across threads cannot resurrect a
        # finished counter as the lingering default
        with cls._stack_mu:
            cls._default_stack.append(self)
        return self

    def __exit__(self, *exc):
        SyncCounter._tls.active = self._prev
        with SyncCounter._stack_mu:
            try:
                SyncCounter._default_stack.remove(self)
            except ValueError:
                pass
        return False

    def report(self, top: int = 10) -> dict:
        ordered = sorted(self.sites.items(), key=lambda kv: -kv[1])
        spans = sorted(self.spans.items(), key=lambda kv: -kv[1])
        return {"hostSyncs": self.total,
                "syncSites": dict(ordered[:top]),
                "syncSpans": dict(spans[:top]),
                "syncWaitS": round(self.wait_s, 6),
                "syncSiteWaitS": {k: round(self.site_wait_s.get(k, 0.0), 6)
                                  for k, _ in ordered[:top]},
                "syncSpanWaitS": {k: round(self.span_wait_s.get(k, 0.0), 6)
                                  for k, _ in spans[:top]}}
