"""Task execution: thread-pooled partition drains with semaphore discipline.

Reference: Spark executors run N concurrent tasks; ``GpuSemaphore`` bounds how
many of them may hold the device at once (GpuSemaphore.scala:27-161), and a
task-completion listener releases the permit. Here a "task" is the drain of
one partition's batch iterator on a pool thread; ``physical._task_begin``
acquires the semaphore lazily at the first device op inside the drain, and the
runner releases it in a ``finally`` when the partition is exhausted — the
task-completion-listener contract (GpuSemaphore.scala:93) without Spark.

The pool size (``spark.rapids.tpu.sql.taskPoolThreads``) may exceed the
semaphore permits: extra threads block in ``acquire`` exactly like Spark tasks
queueing on the GPU, keeping host-side input preparation overlapped with
device work.
"""

from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Iterable, List, Sequence, TypeVar

from .tracing import host_site

T = TypeVar("T")


def _pool_threads() -> int:
    from .. import config as cfg
    with host_site("conf_read"):
        return cfg.TpuConf().task_pool_threads


def _release_semaphore() -> None:
    from .device import TpuSemaphore
    TpuSemaphore.get().release_if_necessary()


def _park_on_suspend(exc: BaseException, ctx, done_pids) -> None:
    """A partition drain unwinding on a suspension request parks its
    stage cursor — which drain, which partitions already completed — on
    the query's lifecycle token. The service worker loop stashes the
    cursor with the suspended ticket; on resume the stage-retry driver's
    re-entry (plan cache + durable shuffle outputs) makes re-running the
    already-done partitions cheap. Never raises."""
    try:
        from .lifecycle import QuerySuspendedError
        if not isinstance(exc, QuerySuspendedError):
            return
        token = getattr(ctx, "cancel_token", None) if ctx is not None \
            else None
        if token is not None:
            token.park_cursor(stage="partition-drain",
                              partitions_done=sorted(done_pids))
    except Exception:
        pass


def _record_swallowed(name: str, exc: BaseException) -> None:
    """A worker exception that will never re-raise on the consumer side
    (early generator close, bounded-join teardown) is LOGGED and
    flight-recorded instead of silently discarded — the teardown
    discipline of docs/resilience.md. Never raises: teardown reporting
    must not replace the (absent) original failure with its own."""
    try:
        import logging
        logging.getLogger("spark_rapids_tpu.tasks").warning(
            "%s teardown swallowed a worker exception: %s: %s",
            name, type(exc).__name__, exc)
        from ..service.telemetry import flight_record
        flight_record("teardown", f"{name}-swallowed",
                      {"error": f"{type(exc).__name__}: {exc}"[:300]})
    except Exception:
        pass


def record_join_timeout(name: str, threads: List[str],
                        logger: str = "spark_rapids_tpu.tasks") -> None:
    """Bounded-join teardown: threads that outlived their join window
    are LOGGED and flight-recorded, not silently abandoned — the wedge
    stays visible in post-mortems (docs/resilience.md). Never raises:
    this runs in finally/teardown paths where a reporting failure must
    not replace the (absent) original error."""
    try:
        import logging
        logging.getLogger(logger).warning(
            "%s: %d thread(s) still alive after bounded join: %s",
            name, len(threads), threads)
        from ..service.telemetry import flight_record
        flight_record("teardown", f"{name}-join-timeout",
                      {"threads": threads})
    except Exception:
        pass


def prefetch_map(items: Iterable[Any], fn: Callable[[Any], T],
                 depth: int = 2,
                 name: str = "spark-rapids-tpu-prefetch") -> Iterable[T]:
    """Map ``fn`` over ``items`` on a background thread, keeping up to
    ``depth`` results ready ahead of the consumer — overlaps host-side
    work (arrow decode/conversion) with downstream device compute, the
    role of the reference's background fetch threads
    (MultiFileCloudParquetPartitionReader, GpuParquetScan.scala:1145)."""
    import queue

    q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
    sentinel = object()
    stop = threading.Event()
    err: List[BaseException] = []

    def worker() -> None:
        from .lifecycle import check_cancel
        try:
            for it in items:
                check_cancel()          # per-item lifecycle poll
                res = fn(it)
                while not stop.is_set():  # lint: cancel-ok bounded put retry; the per-item poll above covers the drain
                    try:
                        q.put(res, timeout=0.2)
                        break
                    except queue.Full:
                        continue
                if stop.is_set():
                    return
        except BaseException as e:          # re-raised on the consumer side
            err.append(e)
        finally:
            while not stop.is_set():  # lint: cancel-ok teardown sentinel delivery must complete even for a cancelled query
                try:
                    q.put(sentinel, timeout=0.2)
                    break
                except queue.Full:
                    continue

    t = threading.Thread(target=worker, daemon=True, name=name)
    t.start()
    delivered = False
    try:
        from .lifecycle import check_cancel
        while True:
            try:
                v = q.get(timeout=0.2)
            except queue.Empty:
                check_cancel()          # delivery-wait lifecycle poll
                continue
            if v is sentinel:
                if err:
                    delivered = True
                    raise err[0]
                return
            yield v
    finally:
        stop.set()                          # unblock the worker on early exit
        if err and not delivered:
            # the consumer closed early: the worker's exception would be
            # silently discarded — flight-record it so teardown never
            # swallows a real failure (docs/resilience.md)
            _record_swallowed(name, err[0])


def ordered_prefetch(items: Iterable[Any], fn: Callable[[Any], T],
                     threads: int = 2, depth: int = 2,
                     name: str = "tpu-prefetch") -> Iterable[T]:
    """Map ``fn`` over ``items`` on ``threads`` named background threads
    (``<name>-N``), yielding results in INPUT ORDER with at most ``depth``
    completed results buffered ahead of the consumer — the multi-worker
    generalization of :func:`prefetch_map` the streaming scan drains
    batch-by-batch (double-buffered CPU decode overlapping device
    compute; MultiFileCloudParquetPartitionReader's pool role).

    Workers join with a bounded timeout on shutdown (the PR 4
    transport-thread discipline); a worker exception re-raises on the
    consumer side; closing the generator early stops the workers."""
    import queue

    items = list(items)
    if not items:
        return
    threads = max(1, min(threads, len(items)))
    # depth >= threads or in-flight workers for LATER items could hold
    # every result slot while the next-to-yield item's worker starves on
    # acquire (the consumer only frees slots in order)
    depth = max(1, depth, threads)
    idx_q: "queue.SimpleQueue[int]" = queue.SimpleQueue()
    for i in range(len(items)):  # lint: cancel-ok SimpleQueue.put is unbounded and non-blocking — work-list seeding, no dwell
        idx_q.put(i)
    results: dict = {}
    cond = threading.Condition()  # lint: raw-lock-ok per-iterator transient coordination, dies with the generator — not shared engine state
    state = {"next": 0}            # next index the consumer will yield
    stop = threading.Event()
    errs: List[BaseException] = []

    def worker() -> None:
        from .lifecycle import check_cancel
        while not stop.is_set():  # lint: cancel-ok body polls check_cancel per item below
            try:
                i = idx_q.get_nowait()
            except queue.Empty:
                return
            # window admission ordered on the CONSUMER's position: index i
            # may compute only once i < next+depth. The worker holding the
            # next-to-yield index always passes, so (unlike a shared
            # semaphore, whose unfair wakeups let later-index workers
            # starve it — a real deadlock) progress is guaranteed while
            # buffered results stay bounded at `depth`.
            with cond:
                while not stop.is_set() and i >= state["next"] + depth:  # lint: cancel-ok a cancelled consumer sets stop in its finally, releasing this wait
                    cond.wait(0.2)
            if stop.is_set():
                return
            try:
                check_cancel()          # per-item lifecycle poll
                res = fn(items[i])
            except BaseException as e:   # re-raised on the consumer side
                with cond:
                    errs.append(e)
                    stop.set()
                    cond.notify_all()
                return
            with cond:
                results[i] = res
                cond.notify_all()

    workers = [threading.Thread(target=worker, daemon=True,
                                name=f"{name}-{i}")
               for i in range(threads)]
    for t in workers:
        t.start()
    delivered = False
    try:
        from .lifecycle import check_cancel
        for i in range(len(items)):  # lint: cancel-ok the inner delivery wait polls check_cancel
            with cond:
                while i not in results and not errs:
                    check_cancel()  # delivery-wait lifecycle poll
                    cond.wait(0.2)
                if errs:
                    delivered = True     # re-raised, not swallowed
                    raise errs[0]
                res = results.pop(i)
                state["next"] = i + 1
                cond.notify_all()
            yield res
    finally:
        stop.set()
        with cond:
            cond.notify_all()
        for t in workers:                # lint: cancel-ok bounded teardown join; stop is already set so workers exit on their own polls
            t.join(timeout=5.0)
        # bounded-join teardown discipline: a worker that outlived its
        # join window, or an exception captured but never re-raised
        # (consumer closed early), is LOGGED instead of discarded
        alive = [t.name for t in workers if t.is_alive()]
        if alive:
            record_join_timeout(name, alive)
        if not delivered:
            with cond:
                pending_errs = list(errs)
            for e in pending_errs:
                _record_swallowed(name, e)


def stream_partition_tasks(parts: Sequence[Any],
                           fn: Callable[[int, Any], T],
                           max_workers: int = 0) -> Iterable[T]:
    """Generator form of :func:`run_partition_tasks`: yield each
    partition's result IN PARTITION ORDER as soon as it (and every
    earlier partition) completes, instead of materializing the full
    result list — the streaming-collect drain (``DataFrame.collect_iter``,
    docs/observability.md firstRowS). Identical per-task discipline:
    deferred-finalizer drain at launch, query-context propagation,
    audited region, semaphore release, dump-on-error.

    Early close (the consumer abandons the stream) cancels unstarted
    tasks and then waits for RUNNING drains to finish, so every scan's
    ``_drain`` finally fires and staging arenas / prefetch threads
    release (io/scan._StagingTracker); exceptions from tasks that
    completed after the consumer left are logged via the teardown
    discipline, never silently discarded."""
    if max_workers <= 0:
        max_workers = _pool_threads()
    from .spill import drain_deferred_finalizers
    drain_deferred_finalizers()
    from . import query_context as _qc
    from .lifecycle import check_cancel
    _query_ctx = _qc.current()
    done_pids: List[int] = []

    def task(pid_part):
        pid, part = pid_part
        try:
            from ..analysis.sync_audit import audited_region
            with _qc.thread_scope(_query_ctx), audited_region():
                check_cancel()      # partition-drain lifecycle poll
                out = fn(pid, part)
                done_pids.append(pid)   # list.append is GIL-atomic
                return out
        except BaseException as e:
            _park_on_suspend(e, _query_ctx, done_pids)
            from ..service.telemetry import dump_on_error
            dump_on_error(e)
            raise
        finally:
            _release_semaphore()

    parts = list(parts)
    if len(parts) <= 1 or max_workers <= 1:
        for i, p in enumerate(parts):  # lint: cancel-ok serial path; task() polls per partition
            yield task((i, p))
        return
    pool = ThreadPoolExecutor(max_workers=min(max_workers, len(parts)),
                              thread_name_prefix="tpu-task")
    futures = [pool.submit(task, (i, p)) for i, p in enumerate(parts)]
    delivered = -1
    raised = False
    try:
        for i, f in enumerate(futures):  # lint: cancel-ok every task polls; a cancelled task's failure re-raises from f.result()
            try:
                res = f.result()
            except BaseException:  # the task failure re-raises here
                raised = True
                raise
            delivered = i
            yield res
    finally:
        for f in futures:
            f.cancel()
        # wait=True: running drains must complete so their finallys
        # release staging arenas before the consumer moves on
        pool.shutdown(wait=True)
        for i, f in enumerate(futures):
            if i <= delivered or not f.done() or f.cancelled():
                continue
            if raised and i == delivered + 1:
                continue           # this failure re-raised, not swallowed
            e = f.exception()
            if e is not None:
                _record_swallowed("tpu-stream-task", e)


def run_partition_tasks(parts: Sequence[Any],
                        fn: Callable[[int, Any], T],
                        max_workers: int = 0) -> List[T]:
    """Run ``fn(pid, partition)`` for each partition as a task, returning
    results in partition order. Tasks run on a fresh pool (nested calls —
    e.g. an exchange inside a collect — must not share a bounded pool, or
    a parent task waiting on child tasks could starve the pool); each task
    releases the TpuSemaphore on completion regardless of outcome."""
    if max_workers <= 0:
        max_workers = _pool_threads()
    # safe point for GC-deferred cleanup (exec/spill.defer_finalizer):
    # no engine locks are held at task launch
    from .spill import drain_deferred_finalizers
    drain_deferred_finalizers()
    # capture the SUBMITTING thread's query context and install it on
    # each worker thread (TLS-only): with two concurrent queries in one
    # process, pool events must attribute to their own query, not to
    # whichever query entered the process default last
    from . import query_context as _qc
    from .lifecycle import check_cancel
    _query_ctx = _qc.current()
    done_pids: List[int] = []

    def task(pid_part):
        pid, part = pid_part
        try:
            # runtime sync audit (analysis/sync_audit.py): when armed via
            # spark.rapids.tpu.sql.analysis.syncAudit, the partition-drain
            # body — the operator execute region — runs under
            # jax.transfer_guard_device_to_host(log|disallow); sanctioned
            # implicit crossings wrap themselves in allowed_host_transfer
            from ..analysis.sync_audit import audited_region
            with _qc.thread_scope(_query_ctx), audited_region():
                check_cancel()      # partition-drain lifecycle poll
                out = fn(pid, part)
                done_pids.append(pid)   # list.append is GIL-atomic
                return out
        except BaseException as e:
            _park_on_suspend(e, _query_ctx, done_pids)
            # post-mortem: dump the always-on flight ring for a dying
            # task body. dump_on_error never raises and marks the
            # exception, so the collect-level hook will not dump twice
            # and the original error propagates unmasked.
            from ..service.telemetry import dump_on_error
            dump_on_error(e)
            raise
        finally:
            _release_semaphore()

    if len(parts) <= 1 or max_workers <= 1:
        return [task((i, p)) for i, p in enumerate(parts)]
    # named pool threads: lockdep acquisition stacks and teardown reports
    # attribute lock traffic to the drain pool instead of Thread-N
    with ThreadPoolExecutor(max_workers=min(max_workers, len(parts)),
                            thread_name_prefix="tpu-task") as pool:
        return list(pool.map(task, enumerate(parts)))
