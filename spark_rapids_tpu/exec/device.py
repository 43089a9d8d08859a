"""Device manager + task semaphore: the GpuDeviceManager / GpuSemaphore analog.

Reference: ``GpuDeviceManager.scala:31-306`` (one GPU per executor, RMM pool
init, pinned pool) and ``GpuSemaphore.scala:27-161`` (bounds concurrent tasks
on the device; acquire AFTER first batch materialized / IO done).

TPU differences: XLA/PJRT owns the HBM allocator, so the "pool" here is an
accounting budget enforced by the spill framework (spill.py) rather than a
sub-allocator; jax array donation + XLA buffer reuse replace RMM arena blocks.
The semaphore contract transfers unchanged: admission control for host threads
driving device work, sized by ``spark.rapids.tpu.sql.concurrentTpuTasks``.
"""

from __future__ import annotations

import os
import threading
from typing import Optional

from .. import config as cfg
from ..analysis.lockdep import named_lock


class DeviceManager:
    """Process-singleton device bootstrap (GpuDeviceManager.initializeGpuAndMemory
    analog, Plugin.scala:124-154 executor init)."""

    _instance: Optional["DeviceManager"] = None
    _lock = named_lock("exec.device.DeviceManager._lock")

    def __init__(self, conf: Optional[cfg.TpuConf] = None):
        import jax
        self.conf = conf or cfg.TpuConf()
        self.devices = jax.devices()
        self.device = self.devices[0]
        self.platform = self.device.platform
        self.memory_budget_bytes = self._compute_budget()

    def _compute_budget(self) -> int:
        """allocFraction * device memory (GpuDeviceManager.scala:159-262).
        An accelerator that reports no ``bytes_limit`` is an error — a
        guessed budget would size every batch and spill decision wrong;
        only the CPU backend (tests) has none, and gets a fixed budget."""
        frac = self.conf.get(cfg.ALLOC_FRACTION)
        if self.platform == "cpu":
            return int(self.conf.get(cfg.BATCH_SIZE_BYTES)) * 8
        stats = self.device.memory_stats()
        if not stats or "bytes_limit" not in stats:
            raise RuntimeError(
                f"{self.device} reports no memory bytes_limit "
                f"(memory_stats()={stats!r}): cannot size the HBM budget")
        return int(stats["bytes_limit"] * frac)

    @classmethod
    def get(cls, conf: Optional[cfg.TpuConf] = None) -> "DeviceManager":
        with cls._lock:
            if cls._instance is None:
                cls._instance = DeviceManager(conf)
            return cls._instance

    @classmethod
    def peek(cls) -> Optional["DeviceManager"]:
        """The existing instance or None — never constructs (the
        telemetry harvest must not probe a device as a side effect)."""
        with cls._lock:
            return cls._instance

    @classmethod
    def reset(cls) -> None:
        with cls._lock:
            cls._instance = None

    def synchronize(self) -> None:
        """Block until all outstanding device work completes."""
        import jax
        (jax.device_put(0) + 0).block_until_ready()  # lint: host-sync-ok device warmup barrier at init, not a hot path


class TpuSemaphore:
    """Bounds the number of concurrently-executing device tasks
    (GpuSemaphore.scala:27-161). Ordering contract preserved from the
    reference: acquire only after the task's first input batch is ready
    (i.e. after host-side IO/decode), release on task completion.

    Instrumented with a wait-vs-hold split: WAIT is the time a task blocks
    acquiring a permit (admission contention — fixed by raising
    concurrentTpuTasks), HOLD is acquire->release (device occupancy —
    fixed by making the held work faster, e.g. pipelining its readbacks).
    Both feed the per-query span report (``semaphore_wait`` /
    ``semaphore_hold``) and cumulative counters the bench harness reads,
    so the two failure modes are separable in reports instead of one
    undifferentiated ``semaphore_acquire`` bucket."""

    _instance: Optional["TpuSemaphore"] = None
    _lock = named_lock("exec.device.TpuSemaphore._lock")

    def __init__(self, max_concurrent: int):
        self.max_concurrent = max_concurrent
        # deliberately raw: the admission semaphore is HELD across whole
        # device task bodies (transfers included) by contract — it is
        # instrumented separately with the wait/hold split below
        self._sem = threading.Semaphore(max_concurrent)  # lint: raw-lock-ok admission semaphore, held across device work by design; wait/hold instrumented here
        self._held = threading.local()
        self._stats_mu = named_lock("exec.device.TpuSemaphore._stats_mu")
        self.wait_s = 0.0
        self.hold_s = 0.0
        self.acquires = 0
        # threads currently BLOCKED in acquire: the live device-admission
        # queue depth (the multi-tenant service's dashboard shows it next
        # to its own per-tenant queue depth, docs/service.md §1)
        self.waiting = 0

    @classmethod
    def initialize(cls, max_concurrent: int) -> "TpuSemaphore":
        with cls._lock:
            cls._instance = TpuSemaphore(max_concurrent)
            return cls._instance

    @classmethod
    def get(cls) -> "TpuSemaphore":
        with cls._lock:
            if cls._instance is None:
                cls._instance = TpuSemaphore(
                    cfg.TpuConf().get(cfg.CONCURRENT_TPU_TASKS))
            return cls._instance

    @classmethod
    def peek(cls) -> Optional["TpuSemaphore"]:
        """The existing instance or None — never constructs (telemetry
        harvest: an idle process contributes no samples)."""
        with cls._lock:
            return cls._instance

    @classmethod
    def reset(cls) -> None:
        with cls._lock:
            cls._instance = None

    def stats(self) -> dict:
        """Cumulative wait/hold seconds + acquire count + live blocked
        count (bench harness, the service dashboard)."""
        with self._stats_mu:
            return {"waitS": round(self.wait_s, 4),
                    "holdS": round(self.hold_s, 4),
                    "acquires": self.acquires,
                    "waiting": self.waiting}

    def acquire_if_necessary(self) -> None:
        """Idempotent per-thread acquire (GpuSemaphore.acquireIfNecessary)."""
        import time
        from .tracing import record_span
        if getattr(self._held, "value", False):
            return
        t0 = time.perf_counter()
        with self._stats_mu:
            self.waiting += 1
        try:
            self._sem.acquire()
        finally:
            with self._stats_mu:
                self.waiting -= 1
        now = time.perf_counter()
        waited = now - t0
        self._held.value = True
        self._held.acquired_at = now
        record_span("semaphore_wait", waited)
        with self._stats_mu:
            self.wait_s += waited
            self.acquires += 1

    def release_if_necessary(self) -> None:
        import time
        from .tracing import record_hold
        if getattr(self._held, "value", False):
            held_for = time.perf_counter() - getattr(
                self._held, "acquired_at", time.perf_counter())
            self._sem.release()
            self._held.value = False
            record_hold(held_for)
            with self._stats_mu:
                self.hold_s += held_for

    def __enter__(self):
        self.acquire_if_necessary()
        return self

    def __exit__(self, *exc):
        self.release_if_necessary()
        return False
