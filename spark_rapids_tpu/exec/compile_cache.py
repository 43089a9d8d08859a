"""Persistent compile cache + buffer donation gate (docs/compile.md).

Compile time and device memory are managed resources at the ``_fused_fn``
funnel (``plan/physical.py``), not side effects:

* **Persistent compile cache** — JAX's on-disk XLA compilation cache
  lives in ONE directory decided by :func:`xla_cache_dir`: where
  ``JAX_COMPILATION_CACHE_DIR`` is set it is that directory and this
  package sets no other; otherwise ``spark.rapids.tpu.sql.compile.cacheDir``
  when a session names one, else a fixed ``.jax_cache`` inside the
  checkout (the path is part of XLA's cache key: a directory that moves
  never hits). Every program is kept, however fast it compiled. With
  ``compile.cacheDir`` set the engine also keeps a *signature index*
  (one JSONL line per fused-program cache key ever built) in that same
  directory. A fresh process serving query shapes it has served
  before classifies each build as a **disk** hit (the executable loads
  from the XLA cache instead of recompiling) versus a **cold** build, and
  the recompile audit reports the split per kernel family with compile
  *seconds*, not just counts. An unwritable/unusable cache dir logs a
  loud warning and degrades to in-memory-only caching — never a query
  failure.

* **Buffer donation** — ``spark.rapids.tpu.sql.compile.donate`` (default
  on) lets the fused programs that *consume* a batch take its column
  arrays as donated jit arguments (``donate_argnums``): XLA may reuse
  the input HBM for outputs and frees the rest the moment the program
  ingests them, so peak device residency on multi-operator pipelines
  drops by roughly one batch per pipeline stage. Spill-store-registered
  and scan-cache-served batches are NEVER donated — their arrays are
  owned by a catalog entry that re-reads them (``ColumnarBatch.origin``
  / ``.shared``).

Every program that reaches the device through a program cache is wrapped
in a :class:`Program` (docs/observability.md §9): compiled under the
stable name of its kernel family, counted per dispatch, and — under
``tracing.enabled`` — bracketed by a ``program:<family>`` profiler span.
What XLA rebuilds (trace, lowering, backend compile, persistent-cache
load) is heard from ``jax.monitoring`` (:func:`install_compile_listener`)
and charged to the family open on the thread, the innermost open span,
the innermost open exec's ``compileSeconds`` and the
``tpu_compile_seconds{kind}`` telemetry histogram.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import re
import threading
import time
import warnings
from typing import Any, Optional, Set

from ..analysis import recompile
from ..analysis.lockdep import named_lock
from . import metrics as em, tracing

# Donating a buffer whose shape/layout XLA cannot reuse for an output
# still FREES it the moment the program ingests it — that eager free IS
# the point of the donation discipline, so jax's per-compile "not
# usable" advisory is expected steady state here, not a defect signal.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")

log = logging.getLogger("spark_rapids_tpu.compile")

#: file (inside the cache dir) holding one JSON line per fused-program
#: signature ever built against this cache — the engine-level index that
#: lets a fresh process distinguish disk hits from cold builds
INDEX_NAME = "fused_signature_index.jsonl"

#: where the XLA cache lives when neither JAX_COMPILATION_CACHE_DIR nor
#: compile.cacheDir names a directory: one fixed path in the checkout
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_lock = named_lock("exec.compile_cache._lock")
_cache_dir: Optional[str] = None     # active persistent dir (None = off)
_index: Set[str] = set()             # signature hashes known on disk
_index_path: Optional[str] = None
_writable: bool = False
_warned_unwritable: bool = False
_donate_cache: Optional[bool] = None


def xla_cache_dir(conf_dir: str = "") -> str:
    """THE directory of the on-disk XLA compilation cache (and of the
    signature index, prewarm corpus and AQE checkpoint kept beside it).
    ``JAX_COMPILATION_CACHE_DIR`` wins outright; then a session's
    ``compile.cacheDir``; then the fixed in-checkout default."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env:
        return env
    if conf_dir:
        return os.path.abspath(os.path.expanduser(conf_dir))
    return _CHECKOUT_CACHE_DIR


def point_xla_cache(d: str) -> None:
    """Point JAX's persistent cache at ``d`` and keep EVERY program (a
    cold run on a fresh machine wants the sub-second builds back too).
    With ``JAX_COMPILATION_CACHE_DIR`` set jax already reads ``d`` from
    the environment and no directory is set from code."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip() and \
            jax.config.jax_compilation_cache_dir != d:
        jax.config.update("jax_compilation_cache_dir", d)
        # jax binds its cache object to the directory at the first
        # compile: a later move needs the binding dropped
        from jax.experimental.compilation_cache import (
            compilation_cache as jax_cc)
        jax_cc.reset_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def configure(conf=None) -> None:
    """Prime the persistent cache + donation gate from a session conf
    (session bootstrap; re-run by ``RuntimeConf.set`` on ``compile.*``
    changes). Degrades gracefully: any failure to use the cache dir logs
    a loud warning and leaves the engine on in-memory caching only."""
    global _cache_dir, _index_path, _writable, _warned_unwritable
    global _donate_cache
    from .. import config as cfg
    if conf is None:
        conf = cfg.TpuConf()
    try:
        # the async compile pool rides the same compile.* conf surface
        # (and the same RuntimeConf.set re-configure trigger)
        from . import compile_pool
        compile_pool.configure(conf)
    except Exception:
        log.debug("compile pool configure failed", exc_info=True)
    try:
        donate = bool(conf.get(cfg.COMPILE_DONATE))
    except Exception:
        donate = True
    with _lock:
        _donate_cache = donate
    try:
        d = str(conf.get(cfg.COMPILE_CACHE_DIR) or "").strip()
    except Exception:
        d = ""
    if not d:
        point_xla_cache(xla_cache_dir())
        with _lock:
            _cache_dir = None
            _index_path = None
            _writable = False
            _index.clear()
        return
    d = xla_cache_dir(d)
    index_path = os.path.join(d, INDEX_NAME)
    try:
        os.makedirs(d, exist_ok=True)
        # probe writability up front so the first compile is not the one
        # discovering a read-only volume
        with open(index_path, "a"):
            pass
        writable = True
    except OSError as e:
        log.warning(
            "compile.cacheDir %r is not usable (%s): persistent compile "
            "cache DISABLED for this process — queries run correctly but "
            "every restart pays full cold compiles", d, e)
        with _lock:
            _warned_unwritable = True
            _cache_dir = None
            _index_path = None
            _writable = False
        return
    point_xla_cache(d)
    loaded: Set[str] = set()
    try:
        with open(index_path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    ent = json.loads(line)
                except ValueError:
                    continue     # torn write from a killed process
                sig = ent.get("sig") if isinstance(ent, dict) else None
                if sig:
                    loaded.add(sig)
    except OSError:
        pass
    with _lock:
        _cache_dir = d
        _index_path = index_path
        _writable = writable
        _index.clear()
        _index.update(loaded)


def reset_cache() -> None:
    """Drop the donation-gate prime (tests; session bootstrap calls
    :func:`configure`, which re-primes everything)."""
    global _donate_cache
    with _lock:
        _donate_cache = None


def active_dir() -> Optional[str]:
    return _cache_dir


def donate_enabled() -> bool:
    """Whether consumed-batch donation is on (cached; primed eagerly by
    :func:`configure` at session bootstrap — a lazy conf read here would
    run on the per-batch hot path)."""
    global _donate_cache
    if _donate_cache is None:
        try:
            from .. import config as cfg
            donate = bool(cfg.TpuConf().get(cfg.COMPILE_DONATE))
        except Exception:
            donate = True
        with _lock:
            _donate_cache = donate
    return _donate_cache


def sig_hash(key: Any) -> str:
    """Stable cross-process hash of a fused-program cache key. Keys are
    tuples of strings/ints/structural expression keys (anything carrying
    a memory address is unkeyable and never reaches the cache), so their
    repr is deterministic across processes."""
    return hashlib.sha256(repr(key).encode()).hexdigest()


def seen_on_disk(key: Any) -> bool:
    """Whether a previous process built this signature against the active
    cache dir, so that XLA will most likely load the executable instead of
    compiling it. A FORECAST for the compile pool's routing only: what a
    build turned out to be is XLA's own report (:func:`_on_duration`)."""
    return _cache_dir is not None and sig_hash(key) in _index


def record(key: Any, kernel: str) -> None:
    """Persist one built signature into the index (idempotent; a failed
    write warns once and stops persisting, never raises)."""
    global _writable, _warned_unwritable
    if _cache_dir is None or not _writable:
        return
    h = sig_hash(key)
    with _lock:
        if h in _index:
            return
        _index.add(h)
        path = _index_path
    try:
        with open(path, "a") as f:
            f.write(json.dumps({"sig": h, "kernel": kernel}) + "\n")
    except OSError as e:
        with _lock:
            warn = not _warned_unwritable
            _writable = False
            _warned_unwritable = True
        if warn:
            log.warning("compile signature index %r became unwritable "
                        "(%s): restart-classification degrades to 'cold' "
                        "for new shapes", path, e)


# ---------------------------------------------------------------------------
# JIT map-pressure relief
# ---------------------------------------------------------------------------
#
# Every live XLA CPU executable pins JIT code mappings, and a process has
# a finite mmap budget (vm.max_map_count, default 65530 on Linux): a
# long-lived engine that keeps compiling new shapes runs LLVM's mmap
# into the wall and SEGFAULTS mid-compile — measured at maps=65520 on
# this repo's own tier-1 suite. Bytes are not the binding resource;
# mappings are. The relief valve below counts /proc/self/maps every few
# builds and, past a soft fraction of the limit, clears every registered
# program cache (fused, scan unpack, shuffle split, mesh SPMD) and GCs —
# traffic rebuilds what it still needs (disk hits when cacheDir is set),
# and the recompile audit reports the rebuilds honestly.

#: program caches to drop under map pressure (each registers its clear)
_PROGRAM_CACHE_CLEARS: list = []
_RELIEF_CHECK_EVERY = 32         # builds between /proc/self/maps reads
_RELIEF_FRACTION = 0.7           # relieve past this fraction of the limit
_builds_since_check = 0
_map_limit: Optional[int] = None
_relief_count = 0


def register_program_cache(clear_fn) -> None:
    """Register a compiled-program cache's clear() with the relief valve
    (module import time; the registry is append-only)."""
    _PROGRAM_CACHE_CLEARS.append(clear_fn)


def relief_count() -> int:
    """How many times the valve fired this process (tests use this to
    detect a relief landing inside a timing-sensitive window)."""
    return _relief_count


def _read_map_limit() -> int:
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            return int(f.read().strip())
    except (OSError, ValueError):
        return 0                  # non-Linux: valve disabled


def _map_count() -> int:
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)
    except OSError:
        return -1


def drop_program_caches() -> None:
    """Clear every registered compiled-program cache and collect: the
    executables they pinned (and their process mappings) are released."""
    for clear in list(_PROGRAM_CACHE_CLEARS):
        try:
            clear()
        except Exception:
            log.exception("program-cache clear failed")
    import gc
    gc.collect()


def jit_map_guard() -> None:
    """Pre-compile check (TimedFirstCall first call): every
    ``_RELIEF_CHECK_EVERY`` builds, read the process map count and
    relieve pressure before LLVM hits the hard limit."""
    global _builds_since_check, _map_limit, _relief_count
    _builds_since_check += 1  # lint: unguarded-ok monotone counter; a racing lost increment only delays one check interval
    if _builds_since_check < _RELIEF_CHECK_EVERY:
        return
    _builds_since_check = 0  # lint: unguarded-ok monotone counter; a racing lost increment only delays one check interval
    if _map_limit is None:
        _map_limit = _read_map_limit()  # lint: unguarded-ok idempotent lazy prime; a racing double read stores the same value
    if not _map_limit:
        return
    n = _map_count()
    if n < 0 or n < _RELIEF_FRACTION * _map_limit:
        return
    # cooldown: live plans can pin executables past our caches, so one
    # relief may not get fully below threshold — re-firing every check
    # interval would thrash the caches for no mapping gain
    _builds_since_check = -(_RELIEF_CHECK_EVERY * 7)  # lint: unguarded-ok monotone counter; a racing lost write only shortens one cooldown
    with _lock:
        _relief_count += 1
        count = _relief_count
    log.warning(
        "JIT map pressure: %d/%d process mappings — dropping %d compiled-"
        "program caches before LLVM's mmap fails (relief #%d). Rebuilds "
        "are %s.", n, _map_limit, len(_PROGRAM_CACHE_CLEARS), count,
        "disk hits (compile.cacheDir set)" if _cache_dir
        else "cold (set compile.cacheDir to make them disk hits)")
    drop_program_caches()
    # NOTE: deliberately NOT jax.clear_caches() here — it would also
    # invalidate every LIVE jitted function's traced cache, turning one
    # relief into a process-wide retrace storm. Dropping the program
    # caches + GC releases the executables (and their mappings); the few
    # residual per-program mappings jax's internals keep only matter
    # after many cycles, and the next check fires again if they do.
    try:
        from ..service.telemetry import MetricsRegistry, flight_record
        flight_record("jit_relief", "maps", {"maps": n, "limit": _map_limit})
        MetricsRegistry.get().counter(
            "tpu_jit_map_relief_total",
            "compiled-program cache drops forced by process map-count "
            "pressure").inc()
    except Exception:
        pass


# ---------------------------------------------------------------------------
# The program boundary: names, dispatch counts, spans, compile events
# ---------------------------------------------------------------------------

#: what is open on THIS thread: ``family`` of the program being called
#: (None outside any), ``cache_hit`` when jax has just reported a load
#: from the persistent cache (its backend-compile duration follows),
#: ``tracing`` how many jaxpr traces are open (a jitted function traces
#: the jitted jnp helpers it calls inside its own trace)
_tls = threading.local()

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
#: duration event -> (count field, seconds field) of a ``programs`` entry
_EVENT_FIELDS = {_TRACE_EVENT: ("traces", "traceS"),
                 _LOWER_EVENT: (None, "lowerS"),
                 _BACKEND_EVENT: ("compiles", "compileS")}
_LOAD_FIELDS = ("cacheLoads", "loadS")
_listener_installed = False


def program_name(family: str) -> str:
    """The name a family's programs compile under (the profiler's
    ``XLA Modules`` line shows ``jit_<name>``): the family with every
    non-identifier character turned to ``_``. A function of the cache
    key's string tags alone — no shape, literal or ``id()`` — so it is
    the same in every process and the persistent cache keeps hitting."""
    return re.sub(r"\W", "_", family)


def open_family() -> Optional[str]:
    """Family of the program being called on this thread, if any."""
    return getattr(_tls, "family", None)


def _eager_family(fun_name) -> str:
    """``<eager>:<op>`` for a compile event no :class:`Program` was open
    for (a jnp op dispatched on its own). jax names the traced function
    ``sort`` and its module ``jit(sort)``: both land on one key."""
    name = str(fun_name or "?")
    if name.startswith("jit(") and name.endswith(")"):
        name = name[4:-1]
    return "<eager>:" + name


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT_EVENT:
        _tls.cache_hit = True


def _on_scalar(event: str, _value, **_kw) -> None:
    # jax reports the START of each timed region as a scalar
    if event == _TRACE_EVENT:
        _tls.tracing = getattr(_tls, "tracing", 0) + 1


def _on_duration(event: str, seconds: float, **kw) -> None:
    fields = _EVENT_FIELDS.get(event)
    if fields is None:
        return
    family = open_family()
    if event == _TRACE_EVENT:
        # only the outermost trace counts: the seconds of the helpers
        # traced inside it are part of its own
        depth = _tls.tracing = max(getattr(_tls, "tracing", 1) - 1, 0)
        if depth:
            return
        if family is not None and \
                kw.get("fun_name") != program_name(family):
            # a helper traced while the program lowers (a sort's
            # comparator, a scan's body): its seconds, not a re-trace
            fields = (None, fields[1])
    kind = None
    if event == _BACKEND_EVENT:
        # jax times a load from the persistent cache under the same
        # event, after a cache_hits event on the same thread
        kind = "disk" if getattr(_tls, "cache_hit", False) else "cold"
        _tls.cache_hit = False
        if kind == "disk":
            fields = _LOAD_FIELDS
    rec = tracing.SpanRecorder.active
    recompile.note_rebuild(
        family or _eager_family(kw.get("fun_name")), fields, seconds,
        funnel=family is not None,
        query_programs=rec.programs if rec is not None else None)
    if rec is not None:
        rec.note_rebuild(seconds)
    em.attribute("compileSeconds", seconds)
    if kind is not None:
        try:
            from ..service.telemetry import MetricsRegistry
            MetricsRegistry.get().histogram(
                "tpu_compile_seconds",
                "backend seconds of each XLA build, by cold compile vs "
                "load from the persistent cache", kind=kind).observe(seconds)
        except Exception:
            pass         # telemetry must never fail a compile


def install_compile_listener() -> None:
    """Hear every trace, lowering, backend compile and persistent-cache
    load from jax itself (session bootstrap; installs once a process)."""
    global _listener_installed
    with _lock:
        if _listener_installed:
            return
        _listener_installed = True
    import jax.monitoring
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_scalar_listener(_on_scalar)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


class Program:
    """One compiled program as its cache hands it out: the jitted
    callable named after its family, and the ONE place a call of it is
    seen — a dispatch count and the host's seconds inside the call (the
    audit's ``calls`` and the active query's ``programs`` map, one lock;
    the seconds also leave the innermost open span's and host site's own
    time, ``SpanRecorder.note_inner``), the family left open on the
    thread for the compile listener, and under ``tracing.enabled`` a
    ``program:<family>`` profiler span carrying the query id and the
    innermost open exec."""

    __slots__ = ("_fn", "_family", "_called")

    def __init__(self, fn, family: str):
        inner = getattr(fn, "__wrapped__", None)
        if inner is not None:
            # jax reads the name when it traces, so this is in time
            try:
                inner.__name__ = inner.__qualname__ = program_name(family)
            except (AttributeError, TypeError):
                pass
        self._fn = fn
        self._family = family
        self._called = False

    def _first_call(self, args):
        """Crash forensics round the call that compiles: relieve JIT map
        pressure first, and under ``SRT_COMPILE_TRACE`` leave a BEGIN line
        naming the program (the last one names a compile that never
        returned; maps = /proc/self/maps entries)."""
        jit_map_guard()
        trace = os.environ.get("SRT_COMPILE_TRACE")
        if trace:
            with open(trace, "a") as f:
                f.write(f"BEGIN {time.time():.1f} {self._family} "
                        f"maps={_map_count()} "
                        f"args={[getattr(a, 'shape', a) for a in args]}\n")
        return trace

    def __call__(self, *args, **kwargs):
        rec = tracing.SpanRecorder.active
        trace = None if self._called else self._first_call(args)
        prev = open_family()
        _tls.family = self._family
        t0 = time.perf_counter()
        try:
            if tracing._tracing_on():
                with tracing.program_annotation(self._family, rec):
                    out = self._fn(*args, **kwargs)
            else:
                out = self._fn(*args, **kwargs)
        finally:
            _tls.family = prev
            # the host's seconds inside the call: argument processing,
            # transfer of host arguments, enqueue (a first call: its
            # trace and load too). A program called while another one
            # traces is inlined into it: a dispatch, no seconds of its own
            seconds = time.perf_counter() - t0 if prev is None else 0.0
            recompile.note_call(
                self._family, rec.programs if rec is not None else None,
                seconds)
            if rec is not None and seconds:
                rec.note_inner(seconds)
        if trace:
            with open(trace, "a") as f:
                f.write(f"END {time.time():.1f} {self._family}\n")
        self._called = True
        return out


def note_build(key: Any, kernel: str):
    """One-call integration for program caches OUTSIDE the ``_fused_fn``
    funnel (mesh SPMD stages, the scan unpack cache, the shuffle split
    cache): account the build in the recompile audit, persist the
    signature, and return ``wrap`` where ``wrap(fn)`` is the
    :class:`Program` of the family."""
    recompile.note_compile(kernel, key)
    record(key, kernel)
    return lambda fn: Program(fn, kernel)
