"""Parameterized-plan + result caching: the serving front door
(docs/plan_cache.md).

Million-user serving traffic is repetitive — the same query SHAPES with
different literals. PR 10 made *compiled programs* restart-proof; this
module hoists the identical trick up the stack to plans and results:

* **Plan parameterization** (:func:`parameterize`) — eligible constant
  subtrees in ``Filter`` conditions and ``Project`` expressions fold
  host-side and are replaced by :class:`ops.expressions.Parameter`
  nodes, so q6 with a different date range produces the SAME plan
  fingerprint and the same compiled ``_fused_fn`` signatures (the
  structural key is ``("param", slot, dtype)``, never the value; fused
  programs take the values as extra traced scalar arguments).

* **Parameterized-plan cache** (:class:`PlanCache`) — an LRU of fully
  planned entries keyed on the normalized :func:`plan_fingerprint`:
  a hit skips analyze-side optimization, contract validation and stage
  compilation entirely, rebinds the parameters, and re-executes the
  SAME exec tree — zero recompiles across literal changes, enforced by
  the PR 10 repeat-compile gate. ``session.prepare(sql)`` rides this
  cache; plain ``session.sql()`` hits it transparently.

* **Result cache** (:class:`ResultCache`) — exact repeats short-circuit
  before the planner: entries key on (plan fingerprint, parameter
  values, input snapshot) where the snapshot is the scan's OWNERSHIP
  token (the same base-table identity the scan device cache keys by —
  a weakref finalizer invalidates entries when the table dies) or the
  file set's (path, mtime, size) stats. Values are host-resident
  batches under a byte-capped LRU. Off by default
  (``spark.rapids.tpu.sql.resultCache.enabled``): serving a stored
  result skips execution, which also skips per-query spans/metrics.

Correctness boundaries (why the extraction scope is what it is):

* Only ``Filter.condition`` / ``Project.exprs`` are parameterized —
  exactly the expressions whose consumers (``FusedStage``,
  ``TpuWholeStageExec``, the aggregate's folded ``pre_stage`` chain,
  and every eager/CPU fallback) thread parameter values as runtime
  arguments. A ``Parameter`` anywhere else (e.g. a ``:name``
  placeholder in GROUP BY) would silently BAKE its first value into a
  shared compiled program, so :func:`parameterize` raises instead.
* Plans carrying side-effecting / nondeterministic expressions, writes,
  or unkeyable attributes (python callables) fingerprint to ``None``
  and are served the classic way — planned per execution.
* A conf change on the session (``RuntimeConf.set``) clears both
  caches: entries were planned under the old conf.
"""

from __future__ import annotations

import itertools
import logging
import os
import threading
import weakref
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

from ..columnar import dtypes as dt
from ..columnar.column import Scalar
from ..ops import arithmetic as ar
from ..ops import expressions as ex
from ..ops import predicates as pr
from . import logical as lp
from .physical import _expr_cache_key

log = logging.getLogger("spark_rapids_tpu.plan_cache")

#: dtypes a runtime parameter may carry: fixed-width scalars a fused
#: program can take as a traced 0-d argument (strings are padded byte
#: matrices — a string literal stays baked and rides the fingerprint)
PARAM_DTYPES = (dt.BOOL, dt.INT8, dt.INT16, dt.INT32, dt.INT64,
                dt.FLOAT32, dt.FLOAT64, dt.DATE, dt.TIMESTAMP)


# ---------------------------------------------------------------------------
# Data-identity tokens (the result cache's snapshot + invalidation hook)
# ---------------------------------------------------------------------------

_tok_lock = threading.Lock()  # lint: raw-lock-ok leaf token-registry lock; never taken with another engine lock held
_TOKENS: Dict[int, int] = {}          # id(obj) -> stable token
_token_counter = itertools.count(1)  # lint: nondeterminism-ok process-local cache-identity token, never compared across workers
#: live result caches, purged when a token's owner is collected
_RESULT_CACHES: "weakref.WeakSet" = weakref.WeakSet()


def _forget_token_now(oid: int, tok: int) -> None:
    with _tok_lock:
        if _TOKENS.get(oid) == tok:
            del _TOKENS[oid]
    for rc in list(_RESULT_CACHES):
        rc.invalidate_token(tok)


def _forget_token(oid: int, tok: int) -> None:
    # weakref-finalizer entry point: enqueue only (a GC callback may
    # interrupt a frame holding engine locks — exec/spill.defer_finalizer
    # discipline); the next plan-cache access drains
    from ..exec.spill import defer_finalizer
    defer_finalizer(_forget_token_now, oid, tok)


def data_token(obj: Any) -> Optional[int]:
    """Stable identity token for a scan's base data object (arrow table,
    cache owner): the same ownership lifetime the scan device cache keys
    by. A new table — even under a re-registered view name — gets a new
    token, so plan fingerprints and result snapshots can never alias
    across data versions. Returns None for un-weakref-able objects."""
    with _tok_lock:
        tok = _TOKENS.get(id(obj))
        if tok is not None:
            return tok
        tok = next(_token_counter)
        _TOKENS[id(obj)] = tok
    try:
        weakref.finalize(obj, _forget_token, id(obj), tok)
    except TypeError:
        with _tok_lock:
            _TOKENS.pop(id(obj), None)
        return None
    return tok


# ---------------------------------------------------------------------------
# Parameterization: constant subtrees -> runtime Parameters
# ---------------------------------------------------------------------------

#: parents under which a constant child may become a parameter: binary
#: comparisons and arithmetic evaluate scalars through the broadcasting
#: (trace-safe) path, so a traced 0-d value is a drop-in
_PARAM_PARENTS = (pr.BinaryComparison, pr.EqualNullSafe,
                  ar.BinaryArithmetic)


def _is_const_subtree(e: ex.Expression) -> bool:
    """Every leaf a plain Literal (never a Parameter), every node
    deterministic: the subtree folds to one host value."""
    stack = [e]
    while stack:
        n = stack.pop()
        if not n.side_effect_free:
            return False
        if isinstance(n, ex.Parameter):
            return False
        if not n.children:
            if not isinstance(n, ex.Literal):
                return False
        stack.extend(n.children)
    return True


def _fold_to_param(e: ex.Expression) -> Optional[ex.Parameter]:
    """Host-fold a constant subtree and wrap it as an (unslotted)
    Parameter of the subtree's STATIC dtype; None when the fold fails or
    the dtype cannot ride as a traced scalar."""
    import numpy as np
    try:
        t = e.dtype
    except Exception:
        return None
    if t not in PARAM_DTYPES or t.numpy_dtype is None:
        return None
    try:
        v = e.eval(None)
    except Exception:
        return None
    if not isinstance(v, Scalar) or v.is_null:
        return None
    value = v.value
    if isinstance(value, np.generic):
        value = value.item()
    if not isinstance(value, (bool, int, float)):
        return None
    try:
        # the boxing the call sites will do must round-trip
        np.asarray(value, dtype=t.numpy_dtype)
    except Exception:
        return None
    return ex.Parameter(value, t)


class _Extractor:
    def __init__(self, extract: bool = True):
        self.extract = extract
        self.params: List[ex.Parameter] = []

    def assign(self, p: ex.Parameter) -> None:
        if p not in self.params:
            p.slot = len(self.params)
            self.params.append(p)

    def walk_expr(self, e: ex.Expression) -> ex.Expression:
        if isinstance(e, ex.Parameter):
            self.assign(e)
            return e
        if not self.extract:
            e.children = [self.walk_expr(c) for c in e.children]
            e._rebind_child_aliases()
            return e
        if isinstance(e, _PARAM_PARENTS) and len(e.children) == 2:
            l, r = e.children
            lc = _is_const_subtree(l)
            rc = _is_const_subtree(r)
            # exactly one constant side becomes a parameter (both-const
            # subtrees fold at THEIR parent; a both-const binary node
            # here means the whole predicate is constant — leave it, the
            # scalar fast paths own that case)
            if lc != rc:
                i = 0 if lc else 1
                p = _fold_to_param(e.children[i])
                if p is not None:
                    self.assign(p)
                    e.children[i] = p
                    e._rebind_child_aliases()
                self.walk_expr(e.children[1 - i])
                return e
        e.children = [self.walk_expr(c) for c in e.children]
        e._rebind_child_aliases()
        return e


def parameterize(plan: lp.LogicalPlan,
                 extract: bool = True) -> List[ex.Parameter]:
    """Extract runtime parameters out of an ANALYZED logical plan,
    in place: constant subtrees under comparisons/arithmetic inside
    ``Filter`` conditions and ``Project`` expressions become
    :class:`Parameter` nodes with deterministic slot numbering (same
    structure => same slots => same fingerprint). Pre-placed named
    placeholders (``:name``) in those positions get slots too; one
    anywhere else raises — its value would bake into a shared compiled
    program on rebind, a silent wrong-answer generator.

    ``extract=False`` assigns slots to pre-placed placeholders WITHOUT
    extracting literals — run even when the plan cache is off, because
    unslotted placeholders would collide on one fused-program key."""
    xt = _Extractor(extract)

    def walk(p: lp.LogicalPlan) -> None:
        if isinstance(p, lp.Filter):
            p.condition = xt.walk_expr(p.condition)
        elif isinstance(p, lp.Project):
            p.exprs = [xt.walk_expr(e) for e in p.exprs]
        for c in p.children:
            walk(c)

    walk(plan)
    claimed = {id(p) for p in xt.params}
    stray = []

    def check(p: lp.LogicalPlan) -> None:
        for e in p.expressions():
            for n in e.collect(lambda x: isinstance(x, ex.Parameter)):
                if id(n) not in claimed:
                    stray.append((type(p).__name__, n))
        for c in p.children:
            check(c)

    check(plan)
    if stray:
        node, n = stray[0]
        raise ValueError(
            f"parameter {n!r} appears under {node}; placeholders are "
            "supported in WHERE conditions and SELECT expressions only "
            "(anywhere else the value would bake into a shared compiled "
            "program)")
    return xt.params


# ---------------------------------------------------------------------------
# Plan fingerprint: the normalized structural key
# ---------------------------------------------------------------------------

def _value_key(v: Any):
    if isinstance(v, ex.Expression):
        return _expr_cache_key(v)
    if isinstance(v, lp.SortOrder):
        ck = _expr_cache_key(v.child)
        if ck is None:
            return None
        return ("sort", ck, v.ascending, v.nulls_first)
    if isinstance(v, dt.Schema):
        return tuple((f.name, f.dtype.name) for f in v.fields)
    if isinstance(v, (list, tuple)):
        sub = tuple(_value_key(x) for x in v)
        return None if any(s is None for s in sub) else ("seq",) + sub
    if isinstance(v, dict):
        sub = tuple((repr(k), _value_key(x)) for k, x in sorted(
            v.items(), key=lambda kv: repr(kv[0])))
        return None if any(s is None for _k, s in sub) else ("map",) + sub
    r = repr(v)
    if " at 0x" in r:
        return None                 # opaque (callables, live objects)
    return r


def _node_key(p: lp.LogicalPlan):
    if isinstance(p, lp.WriteFile):
        return None                 # side effects never cache
    for e in p.expressions():
        if e.collect(lambda x: not x.side_effect_free):
            return None             # nondeterministic plans re-execute
    if isinstance(p, lp.CachedScan):
        # never cache plans over df.cache() frames: a plan entry would
        # PIN the spillable batch's _CacheOwner, breaking the documented
        # reclaim-on-last-reference contract (weakref finalizer in
        # plan/logical._CacheOwner). The scan itself is already
        # materialized — replanning it is cheap and the fused programs
        # still hit the global cache.
        return None
    if isinstance(p, lp.LocalScan):
        tok = data_token(p.base_data)
        if tok is None:
            return None
        # the pruned per-query view is a fresh pa.Table: key by the BASE
        # identity + the kept columns, like the scan device cache
        return ("LocalScan", tok, _value_key(p.schema))
    if isinstance(p, lp.FileScan):
        return ("FileScan", p.fmt, tuple(p.paths),
                _value_key(p.options),
                _value_key([pf for pf in p.pushed_filters]))
    parts: List[Any] = [type(p).__name__]
    for k, v in sorted(vars(p).items()):
        if k in ("children", "_schema") or k.startswith("__"):
            continue
        vk = _value_key(v)
        if vk is None:
            return None
        parts.append((k, vk))
    return tuple(parts)


def _conf_sig(conf) -> tuple:
    """Stable signature of a session conf's explicit settings."""
    try:
        return tuple(sorted(
            (str(k), str(v)) for k, v in conf._settings.items()))
    except Exception:
        return ("unkeyable-conf", id(conf))


def plan_fingerprint(plan: lp.LogicalPlan) -> Optional[tuple]:
    """Structural fingerprint of an analyzed (and parameterized) plan,
    or None when any part is unkeyable — such plans are served the
    classic way, planned per execution."""
    nk = _node_key(plan)
    if nk is None:
        return None
    child_keys = []
    for c in plan.children:
        ck = plan_fingerprint(c)
        if ck is None:
            return None
        child_keys.append(ck)
    return (nk, tuple(child_keys))


def snapshot_key(plan: lp.LogicalPlan) -> Optional[tuple]:
    """Input-snapshot component of a result-cache key, read at serve
    time: ownership tokens for in-memory/cached scans (invalidated by
    the owner's death), (path, mtime, size) stats for file scans. None
    when any leaf cannot snapshot — the result is then never cached."""
    parts: List[Any] = []

    def walk(p: lp.LogicalPlan) -> bool:
        if isinstance(p, lp.CachedScan):
            tok = data_token(p.owner)
            if tok is None:
                return False
            parts.append(("cached", tok))
        elif isinstance(p, lp.LocalScan):
            tok = data_token(p.base_data)
            if tok is None:
                return False
            parts.append(("local", tok))
        elif isinstance(p, lp.FileScan):
            from ..io import expand_paths
            try:
                stats = []
                for f in expand_paths(p.paths):
                    st = os.stat(f)          # one stat per file
                    stats.append((f, st.st_mtime_ns, st.st_size))
            except OSError:
                return False
            parts.append(("files", p.fmt, tuple(stats)))
        elif isinstance(p, lp.Range):
            parts.append(("range", p.start, p.end, p.step))
        elif not p.children:
            return False            # unknown leaf: no snapshot identity
        return all(walk(c) for c in p.children)

    if not walk(plan):
        return None
    return tuple(parts)


# ---------------------------------------------------------------------------
# The caches
# ---------------------------------------------------------------------------

class PlanEntry:
    """One fully planned, contract-validated, stage-compiled execution
    plan plus its rebinding surface."""

    def __init__(self, fingerprint: tuple, exec_plan, overrides,
                 params: List[ex.Parameter], validate_mode: str,
                 logical_plan=None):
        self.fingerprint = fingerprint
        self.exec_plan = exec_plan
        self.overrides = overrides            # keeps last_explain/_violations
        self.logical_plan = logical_plan      # for result-cache snapshots
        self.params = params                  # slot order; shared with the tree
        self.validate_mode = validate_mode
        # the dtypes the plan was contract-validated with: a binding that
        # drifts a slot's dtype re-triggers validation
        # (analysis/contracts.validate_cached_binding)
        self.validated_dtypes = tuple(p.dtype for p in params)
        self.hits = 0
        # execution exclusivity (the multi-tenant service runs CONCURRENT
        # collects on one session, docs/service.md §5): a cached entry's
        # exec tree is a LIVE object — bind() mutates its Parameters and
        # exchanges assign per-execution shuffle state — so exactly one
        # execution may own it at a time. Concurrent hits on a busy entry
        # plan a fresh tree instead (serving verdict "busy"); try-only,
        # never blocking, so no lock-order edge exists
        self._exec_mu = threading.Lock()  # lint: raw-lock-ok try-only leaf lock; no engine lock taken under it

    def try_begin_execution(self) -> bool:
        """Claim the entry's exec tree for one execution (non-blocking).
        False -> the tree is mid-execution on another thread; the caller
        must plan a fresh tree."""
        return self._exec_mu.acquire(blocking=False)

    def end_execution(self) -> None:
        try:
            self._exec_mu.release()
        except RuntimeError:
            pass                       # release raced a relief-valve drop

    def bind(self, values: List[Any]) -> Tuple[bool, list]:
        """Rebind parameter values for the next execution. Returns
        (revalidated, violations) from the cached-binding validation
        policy: a hit skips the full contract walk unless a slot's dtype
        drifted since validation."""
        from ..analysis import contracts as _contracts
        if len(values) != len(self.params):
            raise ValueError(
                f"plan expects {len(self.params)} parameters, got "
                f"{len(values)}")
        for p, v in zip(self.params, values):
            p.bind(v)
        return _contracts.validate_cached_binding(
            self.exec_plan, self.params, self.validated_dtypes,
            self.validate_mode)

    def reset_metrics(self) -> None:
        """Fresh per-operator metric bags before a re-execution, so
        EXPLAIN ANALYZE and listeners see THIS execution's numbers (a
        freshly planned tree starts at zero; a cached one must too)."""

        def walk(node) -> None:
            bag = getattr(node, "metrics", None)
            if bag is not None:
                fresh = type(bag)()
                fresh.owner = getattr(bag, "owner", type(node).__name__)
                node.metrics = fresh
            for c in getattr(node, "children", ()):
                walk(c)

        walk(self.exec_plan)


class PlanCache:
    """Per-session LRU of :class:`PlanEntry` keyed by fingerprint."""

    def __init__(self, max_entries: int = 64):
        self.max_entries = max(1, int(max_entries))
        self._lock = threading.Lock()  # lint: raw-lock-ok per-session leaf lock; no engine lock taken under it
        self._entries: "OrderedDict[tuple, PlanEntry]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        _ALL_PLAN_CACHES.add(self)

    def get(self, fingerprint: tuple) -> Optional[PlanEntry]:
        with self._lock:
            ent = self._entries.get(fingerprint)
            if ent is not None:
                self._entries.move_to_end(fingerprint)
                ent.hits += 1
                self.hits += 1
            else:
                self.misses += 1
            return ent

    def peek(self, fingerprint: tuple) -> Optional[PlanEntry]:
        """get() without touching LRU order or hit/miss stats."""
        with self._lock:
            return self._entries.get(fingerprint)

    def put(self, entry: PlanEntry) -> None:
        with self._lock:
            self._entries[entry.fingerprint] = entry
            self._entries.move_to_end(entry.fingerprint)
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)

    def discard(self, fingerprint: tuple) -> None:
        with self._lock:
            self._entries.pop(fingerprint, None)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


#: every live plan cache: the JIT map-pressure relief valve drops them
#: all (cached exec trees pin compiled stage programs via their _fns)
_ALL_PLAN_CACHES: "weakref.WeakSet" = weakref.WeakSet()


def _clear_all_plan_caches() -> None:
    for c in list(_ALL_PLAN_CACHES):
        c.clear()


from ..exec.compile_cache import register_program_cache as _rpc  # noqa: E402
_rpc(_clear_all_plan_caches)
del _rpc


class ResultCache:
    """Byte-capped LRU of host-resident result batches keyed on
    (fingerprint, parameter values, input snapshot)."""

    def __init__(self, max_bytes: int = 256 << 20,
                 max_entry_bytes: int = 32 << 20):
        self.max_bytes = max(0, int(max_bytes))
        self.max_entry_bytes = max(0, int(max_entry_bytes))
        self._lock = threading.Lock()  # lint: raw-lock-ok per-session leaf lock; no engine lock taken under it
        self._entries: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._bytes = 0
        self.hits = 0
        self.misses = 0
        _RESULT_CACHES.add(self)

    @staticmethod
    def _entry_tokens(key: tuple):
        for part in key[2]:
            if part and part[0] in ("local", "cached"):
                yield part[1]

    def get(self, key: tuple):
        with self._lock:
            ent = self._entries.get(key)
            if ent is None:
                self.misses += 1
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            return ent[0]

    def put(self, key: tuple, batch, nbytes: int) -> None:
        if nbytes > self.max_entry_bytes or nbytes > self.max_bytes:
            return
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old[1]
            self._entries[key] = (batch, nbytes)
            self._bytes += nbytes
            while self._bytes > self.max_bytes and self._entries:
                _k, (_b, n) = self._entries.popitem(last=False)
                self._bytes -= n

    def invalidate_token(self, tok: int) -> None:
        """Scan-invalidation hook: the base table / cached batch carrying
        ``tok`` died — every result derived from it is unservable."""
        with self._lock:
            dead = [k for k in self._entries
                    if tok in self._entry_tokens(k)]
            for k in dead:
                self._bytes -= self._entries.pop(k)[1]

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._bytes = 0

    @property
    def bytes(self) -> int:
        return self._bytes

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


# ---------------------------------------------------------------------------
# The serving entry points (api/dataframe wires these)
# ---------------------------------------------------------------------------

def _counter(name: str, doc: str):
    try:
        from ..service.telemetry import MetricsRegistry
        return MetricsRegistry.get().counter(name, doc)
    except Exception:
        return None


def _inc(name: str, doc: str, n: int = 1) -> None:
    c = _counter(name, doc)
    if c is not None:
        try:
            c.inc(n)
        except Exception:
            pass


def _gauge_set(name: str, doc: str, value: float) -> None:
    try:
        from ..service.telemetry import MetricsRegistry
        MetricsRegistry.get().gauge(name, doc).set(value)
    except Exception:
        pass


def session_caches(session) -> Tuple[PlanCache, ResultCache]:
    """The session's plan/result caches, created from its conf on first
    use (``RuntimeConf.set`` drops them so a conf change replans)."""
    from .. import config as cfg
    pc = getattr(session, "_plan_cache", None)
    if pc is None:
        pc = session._plan_cache = PlanCache(
            int(session.conf.get(cfg.PLAN_CACHE_MAX_ENTRIES)))
    rc = getattr(session, "_result_cache", None)
    if rc is None:
        rc = session._result_cache = ResultCache(
            int(session.conf.get(cfg.RESULT_CACHE_MAX_BYTES)),
            int(session.conf.get(cfg.RESULT_CACHE_MAX_ENTRY_BYTES)))
    return pc, rc


def serving_stats(session) -> Dict[str, int]:
    st = getattr(session, "_serving_stats", None)
    if st is None:
        st = session._serving_stats = {
            "parses": 0, "analyzes": 0, "plansBuilt": 0,
            "planHits": 0, "planMisses": 0, "planBusy": 0,
            "parseCacheHits": 0, "parseCacheMisses": 0,
            "resultHits": 0, "resultMisses": 0, "resultStores": 0,
            "revalidations": 0,
        }
    return st


#: the CURRENT thread's serving info for the execution in flight —
#: ``session._last_serving`` is a cross-thread observability surface that
#: concurrent service workers clobber, so the execution pipeline
#: (collect_batch -> release, the prepared-statement capture) reads the
#: thread-local copy instead (docs/service.md §5)
_tls_serving = threading.local()


def note_thread_serving(serving: Optional[dict]) -> None:
    _tls_serving.value = serving  # lint: unguarded-ok executing thread's own TLS field


def thread_serving() -> Optional[dict]:
    return getattr(_tls_serving, "value", None)


def release_plan_entry(serving: Optional[dict]) -> None:
    """End-of-execution hook for the entry exclusivity claimed in
    :func:`plan_for` / the prepared fast path: pops ``planEntry`` from
    the serving info (so a double call is a no-op) and releases the
    tree for the next execution. Call from a ``finally`` wherever an
    exec tree obtained through the serving front door finishes."""
    if not serving:
        return
    entry = serving.pop("planEntry", None)
    if entry is not None:
        entry.end_execution()


class _CachedOverrides:
    """What a plan-cache hit exposes where a fresh ``Overrides`` would
    be: the entry's captured explain text and the violations of the LAST
    binding validation (empty on a clean hit)."""

    def __init__(self, overrides, violations):
        self.last_explain = getattr(overrides, "last_explain", "")
        self.last_meta = getattr(overrides, "last_meta", None)
        self.last_violations = list(violations)


def plan_for(session, plan: lp.LogicalPlan):
    """The planning front door: parameterize + fingerprint the analyzed
    plan, serve a cached entry (rebound + cheaply revalidated) or build
    one via ``Overrides.apply`` and cache it. Returns
    ``(exec_plan, serving-info dict)``; the caller stores the info on
    the session for EXPLAIN ANALYZE and the result-cache round trip."""
    from .. import config as cfg
    from ..exec.spill import drain_deferred_finalizers
    from ..exec.tracing import trace_span
    from .overrides import Overrides
    drain_deferred_finalizers()
    st = serving_stats(session)
    st["analyzes"] += 1
    enabled = bool(session.conf.get(cfg.PLAN_CACHE_ENABLED))
    serving: Dict[str, Any] = {
        "planCache": "off", "resultCache": "off", "params": 0,
        "fingerprint": None, "values": None, "snapshot": None,
        "cacheable": False, "revalidated": False,
    }
    params: List[ex.Parameter] = []
    fingerprint = None
    if enabled:
        params = parameterize(plan)
        fingerprint = plan_fingerprint(plan)
    else:
        # cache off: :name placeholders still need slots — unslotted
        # parameters are unkeyable (per-exec compiles), and two of them
        # must never collide on one shared program key
        parameterize(plan, extract=False)
    if enabled:
        if fingerprint is not None:
            # the conf is part of the plan's identity: planning decisions
            # (fusion, thresholds, validation mode) read it, and tests
            # mutate a session's conf in place between collects
            fingerprint = (fingerprint, _conf_sig(session.conf))
        serving["params"] = len(params)
        serving["fingerprint"] = fingerprint
    if fingerprint is None:
        if enabled:
            serving["planCache"] = "uncacheable"
        ov = Overrides(session.conf)
        with trace_span("overrides"):
            exec_plan = ov.apply(plan)
        session._last_overrides = ov
        st["plansBuilt"] += 1
        return exec_plan, serving

    cache, _rc = session_caches(session)
    values = [p.value for p in params]
    serving["values"] = tuple(values)
    serving["cacheable"] = True
    entry = cache.get(fingerprint)
    busy = False
    if entry is not None:
        # claim the tree BEFORE binding: bind() mutates the Parameters
        # the live tree shares, and a concurrent execution may be
        # mid-flight on them (the service's concurrent-collect shape)
        if not entry.try_begin_execution():
            busy = True
            entry = None
    if entry is not None:
        try:
            revalidated, violations = entry.bind(values)
        except Exception:
            # error-mode drift raises out of the binding validation: the
            # tainted entry must not stay cached (a retry with clean
            # values would re-raise forever)
            entry.end_execution()
            cache.discard(fingerprint)
            raise
        if revalidated:
            st["revalidations"] += 1
            serving["revalidated"] = True
        if revalidated and violations:
            # the binding broke the validated contract: drop the entry
            # and replan from scratch (never execute a known-bad tree)
            entry.end_execution()
            cache.discard(fingerprint)
        else:
            entry.reset_metrics()
            st["planHits"] += 1
            serving["planCache"] = "hit"
            serving["planEntry"] = entry
            _inc("tpu_plan_cache_hits_total",
                 "parameterized-plan cache hits (analyze/optimize/"
                 "validate/stage-compile skipped)")
            _gauge_set("tpu_plan_cache_entries",
                       "live parameterized-plan cache entries",
                       len(cache))
            session._last_overrides = _CachedOverrides(
                entry.overrides, violations)
            return entry.exec_plan, serving

    if busy:
        # the cached tree is executing on another thread: plan a FRESH
        # tree for this execution and leave the cache alone (the busy
        # entry keeps serving future hits). Counted separately so the
        # service's concurrency shows up in serving_stats instead of
        # masquerading as cold misses.
        st["planBusy"] += 1
        serving["planCache"] = "busy"
    else:
        st["planMisses"] += 1
        serving["planCache"] = "miss"
        _inc("tpu_plan_cache_misses_total",
             "parameterized-plan cache misses (full planning pass)")
    ov = Overrides(session.conf)
    with trace_span("overrides"):
        exec_plan = ov.apply(plan)
    session._last_overrides = ov
    st["plansBuilt"] += 1
    if not busy:
        mode = str(session.conf.get(cfg.ANALYSIS_VALIDATE_PLAN))
        fresh = PlanEntry(fingerprint, exec_plan, ov, params, mode,
                          logical_plan=plan)
        # the fresh entry is about to EXECUTE: claim it before it becomes
        # visible in the cache, or a concurrent hit could bind over it
        fresh.try_begin_execution()
        serving["planEntry"] = fresh
        cache.put(fresh)
        _gauge_set("tpu_plan_cache_entries",
                   "live parameterized-plan cache entries", len(cache))
    return exec_plan, serving


def result_key(session, serving, plan: lp.LogicalPlan) -> Optional[tuple]:
    """The (fingerprint, values, snapshot) key for this execution, or
    None when the result cache is off / the plan cannot snapshot."""
    from .. import config as cfg
    if not bool(session.conf.get(cfg.RESULT_CACHE_ENABLED)):
        return None
    if not serving.get("cacheable"):
        serving["resultCache"] = "uncacheable"
        return None
    snap = snapshot_key(plan)
    if snap is None:
        serving["resultCache"] = "uncacheable"
        return None
    serving["snapshot"] = snap
    return (serving["fingerprint"], serving["values"], snap)


def lookup_result(session, key: Optional[tuple]):
    """Exact-repeat short circuit: the stored host batch, or None."""
    if key is None:
        return None
    _pc, rc = session_caches(session)
    out = rc.get(key)
    st = serving_stats(session)
    if out is not None:
        st["resultHits"] += 1
        _inc("tpu_result_cache_hits_total",
             "result cache hits (execution short-circuited)")
    else:
        st["resultMisses"] += 1
        _inc("tpu_result_cache_misses_total",
             "result cache misses (query executed)")
    return out


def serve_result_hit(session, serving: dict):
    """Exact-repeat short circuit, shared by ``DataFrame.collect_batch``
    and the prepared-statement fast path: look up ``serving['resultKey']``
    and, on a hit, stamp the no-execution post-query state (empty
    sync/span reports, NO span recorder — the previous query's timeline
    must not attach to this collect) and return the stored host batch.
    None -> execute normally (``serving['resultCache']`` already marked
    miss when a key was present)."""
    rkey = serving.get("resultKey")
    if rkey is None:
        return None
    hit = lookup_result(session, rkey)
    serving["resultCache"] = "hit" if hit is not None else "miss"
    if hit is None:
        return None
    session._last_sync_report = {"hostSyncs": 0, "syncSites": {}}
    session._last_span_report = {}
    session._last_span_recorder = None
    session._last_execute_time_s = 0.0
    return hit


def store_result(session, key: Optional[tuple], batch):
    """Fetch the collected batch host-side and remember it under
    ``key``; returns the host batch (callers fetch anyway). Called
    OUTSIDE the query's sync-counting window."""
    if key is None:
        return batch
    from .. import config as cfg
    max_entry = int(session.conf.get(cfg.RESULT_CACHE_MAX_ENTRY_BYTES))
    try:
        if batch.device_size_bytes() > 2 * max_entry:
            return batch               # cheap pre-check before the fetch
        host = batch.fetch_to_host()
        nbytes = 0
        for c in host.columns:
            try:
                nbytes += sum(int(getattr(a, "nbytes", 64))
                              for a in c.arrays())
            except Exception:
                nbytes += 64           # host-object columns: rough floor
    except Exception:
        return batch                   # caching must never fail a query
    _pc, rc = session_caches(session)
    rc.put(key, host, max(nbytes, 1))
    serving_stats(session)["resultStores"] += 1
    _gauge_set("tpu_result_cache_bytes",
               "host bytes held by the result cache", rc.bytes)
    return host


def serving_line(serving: Optional[dict]) -> Optional[str]:
    """The EXPLAIN ANALYZE serving-cache summary line."""
    if not serving:
        return None
    return (f"serving: planCache={serving.get('planCache', 'off')} "
            f"resultCache={serving.get('resultCache', 'off')} "
            f"params={serving.get('params', 0)}")
