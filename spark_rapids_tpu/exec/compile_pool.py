"""Background compile pool: cold fused-stage builds off the query thread
(docs/compile.md §5, the ISSUE 17 tentpole).

A cold query's first-touch latency is XLA whole-program compilation
(seconds to minutes on the chip, PERF.md), paid synchronously on the
thread that owes the user rows. This module moves
that compile OFF the query thread when the caller is latency-sensitive:

* a **streaming collect** (``DataFrame.collect_iter``) must yield its
  first batch in first-batch time, not first-batch-plus-compile time;
* a **service query under a deadline** whose remaining slack cannot
  absorb a cold build (``compile.async.deadlineSlackS``) must not gamble
  the deadline on the compiler.

In either context, :meth:`TpuWholeStageExec._fused` consults this pool
instead of building inline: the build is queued on a bounded worker
pool, the stage serves batches through its per-op eager path while the
build is in flight, and the compiled program swaps in at the next batch
boundary once ready (``consult`` stops answering ``pending`` the moment
the job completes, and the stage's next ``_fused_fn`` consult is a pure
cache hit). Plain batch collects with no deadline keep the synchronous
build path byte-for-byte unchanged — that is what keeps the repeat-
compiles-nothing gates (tests/test_zz_recompile_gate.py) meaningful.

Every pool build goes through the SAME ``_fused_fn`` funnel as a
synchronous build (plan/physical.py): recompile audit, signature-index
record, the named and counted ``Program``. The pool worker then
warm-calls the jitted program with zero-filled dummies captured on the
submitting thread (``jnp.zeros_like`` preserves shape/dtype/weak-type,
so the warm call's jit signature exactly matches the real call) — the
compile genuinely happens on the pool thread, and the query thread's
later call is a traced-cache hit. ``exec.metrics.attribute`` finds no
open exec on pool threads, so ``compileSeconds`` lands on the query's
exec tree ONLY for synchronous builds — that asymmetry is exactly the
async-vs-sync attribution split ``tools/query_report`` reports.

**Prewarm** closes the restart half of the cold path: beside the
persistent signature index, every new stage build appends a *prewarm
corpus* line (the pickled chain + donate tuple + argument avals — what
it takes to rebuild the identical program in a fresh process). At
bootstrap (``compile.prewarm.enabled``, ``tools/prewarm``, ``runner
--prewarm``) the pool replays the top-N hottest signatures as tier-1
jobs — strictly below tier-0 query-triggered builds in the priority
queue — so a restarted replica's first query finds its programs already
in the fused cache and triggers ZERO compiles of its own.

Deadline priority: tier-0 jobs order by the submitting query's
``perf_counter`` deadline (exec/query_context.current_deadline_at),
soonest first; deadline-free submissions sort after every dated one.
"""

from __future__ import annotations

import base64
import heapq
import itertools
import logging
import os
import pickle
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ..analysis.lockdep import named_lock
from . import query_context as qc

log = logging.getLogger("spark_rapids_tpu.compile_pool")

#: file (inside compile.cacheDir) holding one JSON line per stage-program
#: BUILD event: the rebuild recipe + hotness signal prewarm replays from
CORPUS_NAME = "prewarm_corpus.jsonl"

_INF = float("inf")
_FAILED_MAX = 128                  # distinct failing keys remembered
_PREWARM_TIER = 1                  # tier 0 = query-triggered, always first

_mu = named_lock("exec.compile_pool._mu")
_cond = threading.Condition(_mu)  # lint: raw-lock-ok condition OVER the named pool lock; wait/notify not expressible through NamedLock alone

_enabled: bool = True
_workers_target: int = 2
_slack_s: float = 5.0
_shutdown: bool = False
_threads: List[threading.Thread] = []
_queue: List[tuple] = []           # heap: (tier, deadline_at, seq, key)
_jobs: Dict[Any, "_Job"] = {}      # PENDING/RUNNING; DONE jobs drop out
_failed: Dict[Any, BaseException] = {}
_seq = itertools.count(1)
_corpus_recorded: set = set()      # sig hashes already appended this process
_async_built = 0                   # tier-0 programs built by the pool
_prewarm_built = 0                 # tier-1 programs built by the pool

#: test seam: sleep this long in the worker before building, so race
#: tests can hold a build in flight while batches drain eagerly
_test_build_delay_s: float = 0.0


class _Job:
    """One queued build: the ``_fused_fn`` key, the program builder, and
    the dummy arguments whose first call pays the compile."""

    __slots__ = ("key", "builder", "warm_args", "kernel", "tier",
                 "deadline_at", "running")

    def __init__(self, key, builder, warm_args, kernel, tier, deadline_at):
        self.key = key
        self.builder = builder
        self.warm_args = warm_args
        self.kernel = kernel
        self.tier = tier
        self.deadline_at = deadline_at if deadline_at is not None else _INF
        self.running = False


# ---------------------------------------------------------------------------
# Configuration
# ---------------------------------------------------------------------------

def configure(conf=None) -> None:
    """Prime the pool from a session conf (wired from
    ``compile_cache.configure`` so every ``compile.*`` conf change
    reaches it). Worker threads spawn lazily at first submission."""
    global _enabled, _workers_target, _slack_s
    from .. import config as cfg
    if conf is None:
        conf = cfg.TpuConf()
    try:
        enabled = bool(conf.get(cfg.COMPILE_ASYNC))
        workers = max(1, int(conf.get(cfg.COMPILE_ASYNC_WORKERS)))
        slack = float(conf.get(cfg.COMPILE_ASYNC_DEADLINE_SLACK_S))
    except Exception:
        enabled, workers, slack = True, 2, 5.0
    with _mu:
        _enabled = enabled
        _workers_target = workers
        _slack_s = slack


def enabled() -> bool:
    return _enabled and not _shutdown


def deadline_slack_s() -> float:
    return _slack_s


# ---------------------------------------------------------------------------
# Routing policy (deadline-aware compile scheduling, docs/service.md)
# ---------------------------------------------------------------------------

def routable(key) -> bool:
    """Should a cold build for ``key`` go to the pool instead of the
    query thread? Yes only when the pool is on, the build would be COLD
    (disk-classified builds load from the XLA cache — cheap enough to
    take inline), and the caller is latency-sensitive: a streaming
    collect, or a deadline whose remaining slack is under
    ``compile.async.deadlineSlackS``. Everything else keeps the
    synchronous path unchanged."""
    if not _enabled or _shutdown:
        return False
    from . import compile_cache as _cc
    if _cc.seen_on_disk(key):
        return False
    if qc.streaming_active():
        return True
    deadline_at = qc.current_deadline_at()
    if deadline_at is None:
        return False
    return (deadline_at - time.perf_counter()) < _slack_s


# ---------------------------------------------------------------------------
# Submission / consultation (the stage-compiler handshake)
# ---------------------------------------------------------------------------

def consult(key, builder, warm_args, kernel: str = "") -> str:
    """One stage's build request. Returns:

    * ``"pending"`` — the build is queued or running (possibly submitted
      right now): serve this batch eagerly and ask again next batch;
    * ``"failed"`` — a pool build of this key raised; the stored
      exception (:func:`failure`) lets the caller replicate its
      synchronous failure semantics;
    * ``"go-sync"`` — the pool is off/closing: build inline.

    A completed job is dropped from the table, so the caller's next
    consult never reaches here — ``plan.physical.fused_cached`` turns
    True first and the stage takes the plain cache-hit path (the
    eager -> compiled swap, one batch boundary after the build lands)."""
    # consult is called once per batch boundary while a build is in
    # flight — a named lifecycle poll point: a cancelled query must stop
    # re-asking for a program it will never run
    from .lifecycle import check_cancel
    check_cancel()
    deadline_at = qc.current_deadline_at()
    with _cond:
        if key in _failed:
            return "failed"
        job = _jobs.get(key)
        if job is not None:
            if not job.running and deadline_at is not None and \
                    deadline_at < job.deadline_at:
                # a more urgent query wants the same program: re-push at
                # the tighter deadline (the stale heap entry is skipped)
                job.deadline_at = deadline_at
                job.tier = 0
                heapq.heappush(_queue, (0, deadline_at, next(_seq), key))
                _cond.notify()
            return "pending"
        if _shutdown or not _enabled:
            return "go-sync"
        job = _Job(key, builder, warm_args, kernel, tier=0,
                   deadline_at=deadline_at)
        _jobs[key] = job
        heapq.heappush(_queue, (0, job.deadline_at, next(_seq), key))
        _ensure_workers_locked()
        _cond.notify()
        depth = len(_jobs)
    _publish_depth(depth)
    return "pending"


def status(key) -> Optional[str]:
    """``"pending"`` while a build of ``key`` is queued/running,
    ``"failed"`` when a pool build of it raised, None when the pool is
    not tracking it (never submitted, or completed — completed keys are
    answered by the fused cache itself, not by this table)."""
    with _mu:
        if key in _failed:
            return "failed"
        if key in _jobs:
            return "pending"
    return None


def failure(key) -> Optional[BaseException]:
    """The exception a pool build of ``key`` died with (None when the
    key never failed). Failed keys are remembered — dropping them would
    resubmit the doomed build every batch — bounded to the oldest
    ``_FAILED_MAX`` distinct keys."""
    with _mu:
        return _failed.get(key)


def drain(timeout_s: float = 120.0) -> bool:
    """Block until every queued/running build completes (tests, the
    prewarm CLI, ``runner --prewarm``). True when the pool went idle
    inside the timeout."""
    deadline = time.monotonic() + timeout_s
    with _cond:
        while _jobs:
            left = deadline - time.monotonic()
            if left <= 0:
                return False
            _cond.wait(min(left, 0.2))
    return True


def stats() -> Dict[str, int]:
    with _mu:
        return {"pending": len(_jobs),
                "failed": len(_failed),
                "asyncBuilt": _async_built,
                "prewarmBuilt": _prewarm_built}


# ---------------------------------------------------------------------------
# Worker pool
# ---------------------------------------------------------------------------

def _ensure_workers_locked() -> None:
    while len(_threads) < _workers_target:
        t = threading.Thread(target=_worker_loop, daemon=True,
                             name=f"tpu-compile-{len(_threads)}")
        _threads.append(t)
        t.start()


def _worker_loop() -> None:
    while True:
        with _cond:
            while not _queue and not _shutdown:
                _cond.wait(0.2)
            if _shutdown:
                return
            _tier, _dl, _s, key = heapq.heappop(_queue)
            job = _jobs.get(key)
            if job is None or job.running:
                continue           # stale heap entry (re-push / done)
            job.running = True
        _run_job(job)


def _run_job(job: "_Job") -> None:
    delay = _test_build_delay_s
    if delay:
        time.sleep(delay)
    err: Optional[BaseException] = None
    t0 = time.perf_counter()
    try:
        from ..plan.physical import _fused_fn
        # the SAME funnel as a synchronous build: recompile audit,
        # signature record, the named Program — then the warm call
        # actually pays the XLA compile here, on the pool thread
        fn = _fused_fn(job.key, job.builder)
        fn(*job.warm_args)
    except BaseException as e:
        err = e
    global _async_built, _prewarm_built
    with _cond:
        _jobs.pop(job.key, None)
        if err is not None:
            if len(_failed) >= _FAILED_MAX:
                _failed.pop(next(iter(_failed)), None)
            _failed[job.key] = err
        elif job.tier == _PREWARM_TIER:
            _prewarm_built += 1
        else:
            _async_built += 1
        depth = len(_jobs)
        prewarm_done = err is None and job.tier == _PREWARM_TIER
        _cond.notify_all()
    _publish_depth(depth)
    if prewarm_done:
        try:
            from ..service.telemetry import MetricsRegistry
            MetricsRegistry.get().counter(
                "tpu_prewarm_compiles_total",
                "fused programs built by bootstrap prewarm (tier-1 pool "
                "jobs, strictly below query-triggered builds)").inc()
        except Exception:
            pass
    if err is not None:
        log.warning(
            "background build of %s failed after %.3fs (%s: %s) — the "
            "requesting stage falls back to per-op eager",
            job.kernel or "program", time.perf_counter() - t0,
            type(err).__name__, err)


def _publish_depth(depth: int) -> None:
    try:
        from ..service.telemetry import MetricsRegistry
        MetricsRegistry.get().gauge(
            "tpu_compile_queue_depth",
            "compile-pool jobs queued or building").set(float(depth))
    except Exception:
        pass


# ---------------------------------------------------------------------------
# Prewarm corpus (record on build, replay at bootstrap)
# ---------------------------------------------------------------------------

def _corpus_path() -> Optional[str]:
    from . import compile_cache as _cc
    d = _cc.active_dir()
    return os.path.join(d, CORPUS_NAME) if d else None


def _arg_specs(warm_args: tuple) -> Optional[List[tuple]]:
    import jax
    import numpy as np
    specs: List[tuple] = []
    for a in warm_args:
        if isinstance(a, jax.Array):
            specs.append(("arr", tuple(a.shape), str(a.dtype),
                          bool(getattr(a, "weak_type", False))))
        elif isinstance(a, np.ndarray):
            # host param arrays (ex.param_arg_values): jit signatures
            # depend only on shape/dtype, so a zeros stand-in replays
            specs.append(("np", tuple(a.shape), str(a.dtype)))
        elif isinstance(a, (int, float, bool)) or a is None:
            specs.append(("py", a))
        else:
            return None            # unreplayable argument kind
    return specs


def _reconstruct_args(specs: List[tuple]) -> tuple:
    import jax.numpy as jnp
    import numpy as np
    args: List[Any] = []
    for spec in specs:
        if spec[0] == "py":
            args.append(spec[1])
            continue
        if spec[0] == "np":
            args.append(np.zeros(spec[1], dtype=spec[2]))
            continue
        _tag, shape, dtype, weak = spec
        if weak and shape == ():
            # weak scalars only arise from python-number arguments:
            # replay one so the jit signature matches
            args.append(jnp.zeros((), dtype).item())  # lint: host-sync-ok prewarm arg replay on the pool thread, not a query hot path
        else:
            args.append(jnp.zeros(shape, dtype))
    return tuple(args)


def note_stage_signature(key, kernel: str, chain, donate: tuple,
                         warm_args: tuple) -> None:
    """Record one stage build into the prewarm corpus (best-effort,
    once per signature per process): the pickled rebuild recipe a fresh
    process replays at bootstrap. Unpicklable chains are skipped with a
    debug note — prewarm is an optimization, never a correctness
    surface."""
    path = _corpus_path()
    if path is None:
        return
    from . import compile_cache as _cc
    sig = _cc.sig_hash(key)
    with _mu:
        if sig in _corpus_recorded:
            return
        _corpus_recorded.add(sig)
    try:
        specs = _arg_specs(warm_args)
        if specs is None:
            return
        payload = pickle.dumps((key, chain, tuple(donate), specs),
                               protocol=pickle.HIGHEST_PROTOCOL)
        import json
        line = json.dumps({"sig": sig, "kernel": kernel,
                           "spec": base64.b64encode(payload).decode()})
        with open(path, "a") as f:
            f.write(line + "\n")
    except Exception as e:
        log.debug("prewarm corpus record skipped for %s: %s", kernel, e)


def _load_corpus(path: str) -> List[Tuple[int, str, dict]]:
    """Corpus entries ranked hottest-first: (build count, signature,
    latest entry) per signature. Torn tail lines are skipped, exactly
    like the signature index load. Ties break on the stable signature
    hash, NOT file position — two corpora with the same content in a
    different append order replay identically (the prewarm order is
    lockstep-observable through compile timing)."""
    import json
    counts: Dict[str, int] = {}
    latest: Dict[str, Tuple[int, dict]] = {}
    try:
        with open(path) as f:
            for i, line in enumerate(f):
                line = line.strip()
                if not line:
                    continue
                try:
                    ent = json.loads(line)
                except ValueError:
                    continue       # torn write from a killed process
                sig = ent.get("sig") if isinstance(ent, dict) else None
                if not sig or "spec" not in ent:
                    continue
                counts[sig] = counts.get(sig, 0) + 1
                latest[sig] = (i, ent)
    except OSError:
        return []
    ranked = [(counts[sig], sig, ent) for sig, (_i, ent) in latest.items()]
    ranked.sort(key=lambda t: (-t[0], t[1]))
    return ranked


def prewarm(conf=None) -> int:
    """Queue tier-1 builds for the top-N hottest recorded signatures
    (``compile.prewarm.topN``) and return how many were submitted.
    Non-blocking — callers that must be warm BEFORE serving (the CLI,
    ``runner --prewarm``, the subprocess gate test) follow with
    :func:`drain`. Signatures already in the fused cache are skipped."""
    from .. import config as cfg
    if conf is None:
        conf = cfg.TpuConf()
    path = _corpus_path()
    if path is None:
        return 0
    try:
        top_n = max(1, int(conf.get(cfg.COMPILE_PREWARM_TOP_N)))
    except Exception:
        top_n = 32
    from ..plan import physical as ph
    from ..plan.stage_compiler import build_stage_program
    submitted = 0
    for _count, _ln, ent in _load_corpus(path)[:top_n]:
        try:
            payload = base64.b64decode(ent["spec"])
            key, chain, donate, specs = pickle.loads(payload)
            warm_args = _reconstruct_args(specs)
        except Exception as e:
            log.debug("prewarm entry %s skipped: %s",
                      ent.get("kernel"), e)
            continue
        if ph.fused_cached(key):
            continue
        with _cond:
            if _shutdown or not _enabled or key in _jobs:
                continue
            job = _Job(key, _prewarm_builder(build_stage_program, chain,
                                             donate),
                       warm_args, str(ent.get("kernel") or ""),
                       tier=_PREWARM_TIER, deadline_at=None)
            _jobs[key] = job
            heapq.heappush(_queue,
                           (_PREWARM_TIER, _INF, next(_seq), key))
            _ensure_workers_locked()
            _cond.notify()
            depth = len(_jobs)
        _publish_depth(depth)
        submitted += 1
    if submitted:
        log.info("prewarm: %d stage program(s) queued from %s",
                 submitted, path)
    return submitted


def _prewarm_builder(build_stage_program, chain, donate):
    return lambda: build_stage_program(chain, donate)


# ---------------------------------------------------------------------------
# Test / lifecycle plumbing
# ---------------------------------------------------------------------------

def set_test_build_delay(seconds: float) -> None:
    """Hold every pool build in flight for ``seconds`` (race tests: the
    window in which batches MUST drain eagerly)."""
    global _test_build_delay_s
    _test_build_delay_s = float(seconds)  # lint: unguarded-ok test-only scalar toggle


def reset_for_tests() -> None:
    """Drop queued jobs, failure memory and counters (unit-test
    isolation). Running builds finish on their own; their results land
    in the fused cache harmlessly."""
    global _async_built, _prewarm_built, _test_build_delay_s
    with _cond:
        _queue.clear()
        for key in [k for k, j in _jobs.items() if not j.running]:
            _jobs.pop(key, None)
        _failed.clear()
        _corpus_recorded.clear()
        _async_built = 0
        _prewarm_built = 0
        _test_build_delay_s = 0.0
        _cond.notify_all()
