"""Recompile audit: distinct compiled shapes per fused kernel.

Whole-stage programs compile per (expression structure, schema, capacity)
signature; the capacity-bucketing discipline (columnar.column.bucket)
exists precisely so a stream of slightly-different batch sizes reuses ONE
compiled program instead of recompiling per shape. A regression there is
invisible in unit tests (everything still returns the right rows) but
catastrophic on real backends where compiles cost seconds — so this audit
counts, per kernel family, how many distinct signatures actually compiled
versus how many calls ran, and flags kernels whose compile count tracks
their call count (the compiling-once-per-batch-shape smell).

Wired into the one funnel every fused program goes through
(``plan/physical._fused_fn`` and the program caches beside it, each
handing out an ``exec/compile_cache.Program``); the bench runner reports
per-query deltas (``report``/``snapshot``/``delta``) next to the sync and
semaphore metrics, and every query carries its own ``programs`` map
(``last_query_metrics()``). Gated by
``spark.rapids.tpu.sql.analysis.recompileAudit`` (default on — the cost
is a dict increment per fused-program call).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from .lockdep import named_lock

# flag a kernel once it has compiled this many times AND compiles on at
# least half of its calls — a well-bucketed kernel stream compiles a
# handful of shapes then hits the cache forever
FLAG_MIN_COMPILES = 8

_lock = named_lock("analysis.recompile._lock")
# name -> {keys: set, compiles: int, calls: int, coldCompiles: int,
# diskHits: int, compileS: float}. ``compiles`` counts EVERY program-cache
# miss (a same-key rebuild after the fused cache evicts is real churn
# and must show), ``keys`` counts distinct shapes, ``calls`` counts
# dispatches (exec/compile_cache.Program). The rest is XLA's own report,
# heard through ``jax.monitoring`` (exec/compile_cache._on_duration) and
# charged to the family whose program was being called: ``coldCompiles``
# backend compilations, ``diskHits`` loads from the persistent cache (a
# warm restart with a cache dir should show coldCompiles == 0 for
# repeated shapes), ``compileS`` every second paid to rebuild — trace,
# lowering, backend compile and load. One jitted program re-traces per
# argument shape, so cold + disk can exceed ``compiles``.
_kernels: Dict[str, Dict[str, Any]] = {}
_enabled_cache: Optional[bool] = None

#: one family's entry in a query's ``programs`` map
#: (``last_query_metrics()["programs"]``, docs/observability.md §9)
_PROGRAM_ZERO = {"dispatches": 0, "dispatchS": 0.0, "traces": 0,
                 "traceS": 0.0, "lowerS": 0.0, "compiles": 0,
                 "compileS": 0.0, "cacheLoads": 0, "loadS": 0.0}


def _enabled() -> bool:
    global _enabled_cache
    if _enabled_cache is None:
        try:
            from .. import config as cfg
            from .sync_audit import _effective_conf
            enabled = bool(
                _effective_conf().get(cfg.ANALYSIS_RECOMPILE_AUDIT))
        except Exception:
            enabled = True
        with _lock:
            _enabled_cache = enabled
    return _enabled_cache


def reset_cache() -> None:
    global _enabled_cache
    with _lock:
        _enabled_cache = None


def kernel_of(key: Any) -> str:
    """Kernel family of a fused-cache signature: the top-level string
    tags joined (``concat``, ``project``, ``agg/update/partial/sort``,
    ...) — shapes/schemas live in nested tuples and stay out of the
    family name."""
    if isinstance(key, tuple):
        tags = [p for p in key if isinstance(p, str)]
        if tags:
            return "/".join(tags)
    return "anon"


def _ent(kernel: str) -> Dict[str, Any]:
    return _kernels.setdefault(kernel,
                               {"keys": set(), "compiles": 0, "calls": 0,
                                "coldCompiles": 0, "diskHits": 0,
                                "compileS": 0.0})


def note_compile(kernel: str, key: Any) -> None:
    """Record a program-cache miss: a program built (new shape OR a
    same-key rebuild after eviction). What the build costs arrives with
    its first call, from XLA (:func:`note_rebuild`)."""
    if not _enabled():
        return
    with _lock:
        ent = _ent(kernel)
        ent["keys"].add(key)
        ent["compiles"] += 1
    # charge the innermost open exec's metrics bag so EXPLAIN ANALYZE
    # shows which plan node paid the compile (exec/metrics attribution)
    from ..exec.metrics import attribute
    attribute("recompiles")
    # flight-recorder breadcrumb: a compile right before a crash is a
    # prime post-mortem suspect (OOM during build, shape explosion)
    from ..service.telemetry import flight_record
    flight_record("recompile", kernel)


def note_call(kernel: str,
              query_programs: Optional[Dict[str, Dict[str, Any]]] = None,
              seconds: float = 0.0) -> None:
    """Record one dispatch of a family's program: the audit's ``calls``
    and, where a query is recording, its ``programs`` map with the
    host's ``seconds`` inside the call — one lock for both
    (exec/compile_cache.Program calls this per program call)."""
    audit = _enabled()
    if not audit and query_programs is None:
        return
    with _lock:
        if audit:
            _ent(kernel)["calls"] += 1
        if query_programs is not None:
            ent = _program_ent(query_programs, kernel)
            ent["dispatches"] += 1
            ent["dispatchS"] += seconds


def _program_ent(programs: Dict[str, Dict[str, Any]], family: str):
    ent = programs.get(family)
    if ent is None:
        ent = programs[family] = dict(_PROGRAM_ZERO)
    return ent


def note_rebuild(family: str, fields, seconds: float, funnel: bool,
                 query_programs: Optional[Dict[str, Dict[str, Any]]] = None
                 ) -> None:
    """One of XLA's compile events (exec/compile_cache._on_duration):
    ``fields`` is the (count, seconds) pair of a ``programs`` entry it
    feeds — (traces, traceS), (None, lowerS), (compiles, compileS) or
    (cacheLoads, loadS). ``funnel`` families (a :class:`Program` was
    open) also feed the process-wide audit; eager ops only the query."""
    count_field, seconds_field = fields
    audit = funnel and _enabled()
    if not audit and query_programs is None:
        return
    with _lock:
        if audit:
            ent = _ent(family)
            ent["compileS"] += float(seconds)
            if count_field == "compiles":
                ent["coldCompiles"] += 1
            elif count_field == "cacheLoads":
                ent["diskHits"] += 1
        if query_programs is not None:
            ent = _program_ent(query_programs, family)
            ent[seconds_field] += float(seconds)
            if count_field is not None:
                ent[count_field] += 1


def programs_report(programs: Dict[str, Dict[str, Any]]
                    ) -> Dict[str, Dict[str, Any]]:
    """A query's ``programs`` map as it is reported: a copy, seconds
    rounded, families in name order."""
    with _lock:
        return {k: {f: (round(v, 6) if isinstance(v, float) else v)
                    for f, v in ent.items()}
                for k, ent in sorted(programs.items())}


def recompiles_of(programs: Dict[str, Dict[str, Any]]
                  ) -> Dict[str, Dict[str, Any]]:
    """A query's ``programs`` map in the shape of :func:`delta` (what a
    query listener's ``QueryExecution.recompiles`` holds): funnel
    families only, those that built anything or were called."""
    out: Dict[str, Dict[str, Any]] = {}
    for k, p in programs_report(programs).items():
        if k.startswith("<eager>:"):
            continue
        out[k] = {"compiles": p["compiles"] + p["cacheLoads"],
                  "calls": p["dispatches"],
                  "coldCompiles": p["compiles"],
                  "diskHits": p["cacheLoads"],
                  "compileS": round(p["traceS"] + p["lowerS"] +
                                    p["compileS"] + p["loadS"], 4)}
    return out


def report() -> Dict[str, Dict[str, int]]:
    with _lock:
        return {k: {"compiles": v["compiles"],
                    "distinctShapes": len(v["keys"]),
                    "calls": v["calls"],
                    "coldCompiles": v.get("coldCompiles", 0),
                    "diskHits": v.get("diskHits", 0),
                    "compileS": round(v.get("compileS", 0.0), 4)}
                for k, v in sorted(_kernels.items())}


def snapshot() -> Dict[str, Dict[str, int]]:
    """Point-in-time counters for delta reporting (bench runner)."""
    return report()


def delta(base: Dict[str, Dict[str, int]]) -> Dict[str, Dict[str, int]]:
    """Per-kernel counter growth since ``base`` (dropping unchanged
    kernels)."""
    out: Dict[str, Dict[str, int]] = {}
    zero = {"compiles": 0, "distinctShapes": 0, "calls": 0}
    for k, now in report().items():
        was = base.get(k, zero)
        d = {f: now[f] - was.get(f, 0) for f in now}
        if any(d.values()):
            out[k] = d
    return out


def flagged(counters: Optional[Dict[str, Dict[str, int]]] = None
            ) -> Dict[str, str]:
    """Kernels compiling once per call: many compiles AND compiling on >=
    half their calls — missed capacity-bucket padding, or cache-eviction
    churn (same shapes rebuilt after _FUSED_CACHE clears)."""
    counters = report() if counters is None else counters
    out: Dict[str, str] = {}
    leaks = size_class_report()
    for k, c in counters.items():
        n, calls = c["compiles"], max(c["calls"], 1)
        # STRICTLY more than half the calls: the cold+hot two-iteration
        # pattern with perfect cache reuse lands exactly at
        # calls == 2*compiles, which is the healthy baseline the bench
        # runner produces — only compiling beyond it is churn
        if n >= FLAG_MIN_COMPILES and n * 2 > calls:
            msg = (f"{n} compiles ({c.get('distinctShapes', n)} distinct "
                   f"shapes) over {calls} calls — compiling per batch "
                   "shape or churning the fused cache (check capacity "
                   "bucketing)")
            if k in leaks:
                msg += (f"; un-bucketed dimensions in its signatures: "
                        f"{leaks[k]['dims']}")
            out[k] = msg
    return out


# ---------------------------------------------------------------------------
# Size-class audit: trace signatures back to un-bucketed dimensions
# ---------------------------------------------------------------------------

def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


def unbucketed_dims(key: Any) -> list:
    """Integer dimensions inside one compiled signature that escaped the
    power-of-two size-class discipline: every shape-bearing int in a
    fused-cache key (capacities, padded string widths, group buckets
    ``Kb``, window frames) is supposed to be a power of two >= its
    class minimum, so a stream of ragged batches reuses ONE program.
    Anything >= 8 and not a power of two is a leak — the dimension that
    made this signature distinct. Small ints (< 8) are op counts and
    flags, not shapes; bools are flags."""
    out = []

    def walk(v):
        if isinstance(v, bool):
            return
        if isinstance(v, int):
            if v >= 8 and not _is_pow2(v):
                out.append(v)
            return
        if isinstance(v, tuple):
            for x in v:
                walk(x)
    walk(key)
    return out


#: families whose signatures legitimately carry non-power-of-two ints:
#: scan_unpack keys hold 8-byte-aligned staging-buffer OFFSETS — sums of
#: bucketed per-column footprints (each pow2-derived, the sum not) — so
#: their distinctness is bounded by #tables x #cap-buckets, never by the
#: per-batch row count the bucket discipline exists to absorb
SIZE_CLASS_EXEMPT = ("scan_unpack",)


def size_class_report() -> Dict[str, Dict[str, Any]]:
    """Per-kernel-family audit of signatures carrying un-bucketed
    dimensions: ``{family: {"dims": [ints], "signatures": n}}`` for every
    family where at least one compiled signature leaked past the bucket
    discipline — the 'which dimension caused this recompile' answer the
    flag message alone cannot give."""
    with _lock:
        snap = {k: list(v["keys"]) for k, v in _kernels.items()}
    out: Dict[str, Dict[str, Any]] = {}
    for kernel, keys in sorted(snap.items()):
        if kernel in SIZE_CLASS_EXEMPT:
            continue
        dims: set = set()
        hit = 0
        for key in keys:
            # unkeyable per-instance builds carry id(self) in their key
            # (FusedStage's note_compile) — a memory address is not a
            # shape dimension
            if isinstance(key, tuple) and "unkeyable" in [
                    p for p in key if isinstance(p, str)]:
                continue
            d = unbucketed_dims(key)
            if d:
                hit += 1
                dims.update(d)
        if hit:
            out[kernel] = {"dims": sorted(dims), "signatures": hit}
    return out


def reset() -> None:
    """Drop all counters (tests)."""
    with _lock:
        _kernels.clear()
