"""3-tier spillable buffer store: device (HBM) -> host (RAM) -> disk.

Reference: ``RapidsBufferCatalog.scala:34-211`` (global id->buffer map + spill
chain wiring), ``RapidsBufferStore.scala:30-351`` (tiered store, spill-priority
queue, synchronousSpill), ``RapidsDeviceMemoryStore`` / ``RapidsHostMemoryStore``
/ ``RapidsDiskStore``, ``DeviceMemoryEventHandler.scala:33-95`` (alloc-failure
callback -> spill), ``SpillableColumnarBatch.scala:28-137``, and
``SpillPriorities.scala:26-60``.

TPU mapping: the device tier holds jax arrays (XLA/PJRT HBM buffers); the host
tier numpy arrays; the disk tier .npz files under the spill dir. There is no
RMM alloc-failure hook in PJRT, so the budget is enforced *cooperatively*:
``MemoryAccountant.reserve(nbytes)`` is called before device materialization
and triggers synchronous spill when the accounted device total would exceed
the budget — the same control flow as the RMM event handler, moved from an
allocator callback to an admission check.
"""

from __future__ import annotations

import heapq
import itertools
import os
import threading
from dataclasses import dataclass, field
from enum import Enum
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..analysis import ledger as _ledger
from ..analysis import lockdep
from ..analysis.lockdep import named_lock, named_rlock
from ..columnar import dtypes as dt
from ..columnar.batch import ColumnarBatch
from ..columnar.column import Column
from .tracing import host_site

# Spill priority constants (SpillPriorities.scala:26-60): lower spills first.
OUTPUT_FOR_SHUFFLE_PRIORITY = -100.0   # shuffle outputs idle longest
HOST_MEMORY_BUFFER_PRIORITY = -50.0
CACHE_PRIORITY = -75.0                 # cached tables yield to active work
ACTIVE_ON_DECK_PRIORITY = 100.0        # actively-used batches spill last


class StorageTier(Enum):
    DEVICE = 0
    HOST = 1
    DISK = 2


_id_counter = itertools.count(1)


def next_buffer_id() -> int:
    return next(_id_counter)


class BufferLostError(RuntimeError):
    """A spillable buffer was released or evicted before its read — the
    recoverable 'shuffle block lost' condition (consumers like the shuffle
    exchange re-execute the producing stage, Spark FetchFailed style)."""


# ---------------------------------------------------------------------------
# GC-callback-safe deferred finalization
# ---------------------------------------------------------------------------
#
# A weakref finalizer fires at an ARBITRARY bytecode on an arbitrary
# thread — including inside a frame that already holds the buffer
# catalog / watermark / device-manager locks. Cleanup that re-takes any
# of those locks inline self-deadlocks the thread on its own
# non-reentrant lock (observed: the scan-cache eviction finalizer firing
# inside ``reserve -> watermark`` and blocking on the watermark lock the
# interrupted frame held). Finalizers therefore only ENQUEUE their work
# (``list.append`` is atomic, no lock) and the engine drains the queue
# at safe points: partition-task launch and scan-cache access.

_DEFERRED_FINALIZERS: List[Tuple[Callable, tuple]] = []


def defer_finalizer(fn: Callable, *args) -> None:
    """Enqueue lock-taking cleanup from a GC/weakref callback (run later
    by :func:`drain_deferred_finalizers` from a safe call context)."""
    _DEFERRED_FINALIZERS.append((fn, args))


def drain_deferred_finalizers() -> None:
    """Run enqueued finalizer work. Callers must hold NO engine locks.
    Failures are swallowed — deferred cleanup must never fail the query
    that happened to trigger the drain."""
    while _DEFERRED_FINALIZERS:
        try:
            fn, args = _DEFERRED_FINALIZERS.pop()
        except IndexError:
            break
        try:
            fn(*args)
        except Exception:
            pass


@dataclass
class BufferMeta:
    """Schema + shape info to rebuild a ColumnarBatch from raw arrays
    (MetaUtils TableMeta analog, MetaUtils.scala:33-241)."""
    schema: dt.Schema
    num_rows: int
    capacity: int


class SpillableBuffer:
    """One registered buffer: a columnar batch's arrays at some tier
    (RapidsBufferBase analog with acquire/close refcounting,
    RapidsBufferStore.scala:245-351)."""

    def __init__(self, buffer_id: int, meta: BufferMeta, priority: float,
                 device_arrays: Optional[List[Any]] = None,
                 col_dtypes: Optional[List[dt.DType]] = None,
                 obj_cols: Optional[Dict[int, Column]] = None,
                 tenant: Optional[str] = None):
        self.id = buffer_id
        self.meta = meta
        self.priority = priority
        # the tenant whose query registered this buffer (service
        # multi-tenancy, docs/service.md): device residency is accounted
        # per tenant and an over-budget tenant's buffers are the spill
        # cascade's first victims. None = untenanted (direct sessions,
        # shared cache entries)
        self.tenant = tenant
        self.tier = StorageTier.DEVICE
        self.col_dtypes = col_dtypes or []
        self._device_arrays = device_arrays        # list of jax arrays
        self._host_arrays: Optional[List[np.ndarray]] = None
        self._disk_path: Optional[str] = None
        # CPU-engine-only columns (ObjectColumn: map<string,_> etc.) are
        # python-object payloads that never touch the device; they ride the
        # buffer untiered (already host-resident, nothing to spill)
        self._obj_cols = obj_cols or {}
        # durable-shuffle pin (BufferCatalog.pin_to_disk): a pinned
        # buffer's npz payload is RETAINED across promotion (immutable,
        # write-once) so the post-read re-pin is a tier flip, not a
        # fresh D2H + savez round trip per read
        self.disk_pinned = False
        self._pinned_path: Optional[str] = None
        # every buffer lock shares ONE lockdep name (a lock CLASS, kernel-
        # lockdep style): order edges are per class of lock, not per buffer
        self._lock = named_rlock("exec.spill.SpillableBuffer._lock")
        self.size_bytes = sum(
            a.size * a.dtype.itemsize for a in (device_arrays or []))

    # -- tier movement -------------------------------------------------------
    #
    # Tier moves follow the snapshot/work/publish shape: grab array refs
    # under the lock, do the blocking device readback or disk write
    # UNLOCKED (holding a mutex across a link round trip or an npz write
    # serializes every peer thread behind IO), then re-take the lock and
    # flip the tier only if no concurrent move/free won the race.

    def spill_to_host(self) -> int:
        with self._lock:
            if self.tier != StorageTier.DEVICE or \
                    self._device_arrays is None:
                return 0
            dev = list(self._device_arrays)
        from ..analysis.sync_audit import allowed_host_transfer
        with allowed_host_transfer("spill tier: device->host move"):
            host = [np.asarray(a) for a in dev]  # lint: host-sync-ok spill tier: the device->host move IS the operation
        with self._lock:
            if self.tier != StorageTier.DEVICE or \
                    self._device_arrays is None:
                return 0               # concurrent spill/free won the race
            self._host_arrays = host
            self._device_arrays = None
            self.tier = StorageTier.HOST
        # ledger AFTER the buffer lock releases (its lock is a leaf)
        _ledger.note_tier(self.id, StorageTier.HOST)
        # charge the innermost open exec (exec/metrics attribution): the
        # operator whose pressure pushed this buffer off the device shows
        # spillBytes on its EXPLAIN ANALYZE node
        from .metrics import attribute
        attribute("spillBytes", self.size_bytes)
        from ..service.telemetry import flight_record
        flight_record("spill", f"buffer-{self.id}",
                      {"bytes": self.size_bytes, "to": "host"})
        return self.size_bytes

    def spill_to_disk(self, spill_dir: str) -> int:
        # zero-IO path for disk-pinned buffers already staged on host:
        # the retained npz IS the payload (immutable), so the pressure
        # cascade's host->disk move restores it instead of paying a
        # fresh savez rewrite at the worst possible time. HOST-only:
        # callers' accounting assumes the bytes came off the host tier
        if self.demote_to_pinned_disk(
                only_from=StorageTier.HOST) is not None:
            return self.size_bytes
        self.spill_to_host()           # no-op unless device-resident
        with self._lock:
            if self.tier != StorageTier.HOST or self._host_arrays is None:
                return 0
            host = self._host_arrays
        os.makedirs(spill_dir, exist_ok=True)
        # per-attempt unique path: a racing spill of the same buffer must
        # never clobber (or unlink) the winner's file
        path = os.path.join(
            spill_dir, f"spill-{self.id}-{next(_id_counter)}.npz")
        # codec per spill.compression.codec (TableCompressionCodec
        # analog for the disk tier; zlib = np's deflate container)
        from .. import config as cfg
        codec = str(cfg.TpuConf().get(cfg.SPILL_COMPRESSION_CODEC))
        save = np.savez_compressed if codec == "zlib" else np.savez
        save(path, *host)
        with self._lock:
            if self.tier != StorageTier.HOST or \
                    self._host_arrays is not host:
                won = False            # concurrent move/free won the race
            else:
                self._disk_path = path
                self._host_arrays = None
                self.tier = StorageTier.DISK
                won = True
        if not won:
            try:
                os.unlink(path)
            except OSError:
                pass
            return 0
        _ledger.note_tier(self.id, StorageTier.DISK)
        from ..service.telemetry import flight_record
        flight_record("spill", f"buffer-{self.id}",
                      {"bytes": self.size_bytes, "to": "disk"})
        return self.size_bytes

    def _load_arrays(self) -> List[Any]:
        """Arrays at whatever tier, promoted to device (RapidsBuffer
        .getColumnarBatch re-promotion, RapidsBufferStore.scala:275-301).
        Snapshot under the lock, materialize unlocked (np.load and the
        host->device transfer both block)."""
        import jax.numpy as jnp
        with self._lock:
            tier = self.tier
            dev, host, path = (self._device_arrays, self._host_arrays,
                               self._disk_path)
        if tier == StorageTier.DEVICE:
            if dev is None:
                raise BufferLostError(f"buffer {self.id} was freed")
            return dev
        if tier == StorageTier.HOST:
            if host is None:
                raise BufferLostError(f"buffer {self.id} was freed")
            return [jnp.asarray(a) for a in host]
        try:
            with np.load(path) as z:
                return [jnp.asarray(z[k]) for k in z.files]
        except (FileNotFoundError, TypeError) as e:
            raise BufferLostError(
                f"buffer {self.id} disk payload vanished mid-read "
                f"(concurrent free): {e}") from None

    def get_batch(self, promote: bool = True) -> ColumnarBatch:
        from ..columnar.column import build_column
        arrays = self._load_arrays()
        cols: List[Column] = []
        i = 0
        for ci, f in enumerate(self.meta.schema):
            if ci in self._obj_cols:
                cols.append(self._obj_cols[ci])
            else:
                c, i = build_column(f.dtype, arrays, i)
                cols.append(c)
        return ColumnarBatch(self.meta.schema, cols, self.meta.num_rows)

    def promote_to_device(self, arrays: List[Any]) -> None:
        """Move the buffer back to the device tier (re-promotion on acquire,
        RapidsBufferStore.scala:275-301); caller accounts the bytes. A
        disk-pinned buffer's npz is stashed, not unlinked — the durable
        re-pin restores it without rewriting (buffers are immutable)."""
        with self._lock:
            self._device_arrays = arrays
            self._host_arrays = None
            if self._disk_path:
                if self.disk_pinned:
                    if self._pinned_path and \
                            self._pinned_path != self._disk_path and \
                            os.path.exists(self._pinned_path):
                        os.unlink(self._pinned_path)  # superseded stash
                    self._pinned_path = self._disk_path
                elif os.path.exists(self._disk_path):
                    os.unlink(self._disk_path)
            self._disk_path = None
            self.tier = StorageTier.DEVICE
        _ledger.note_tier(self.id, StorageTier.DEVICE)

    def demote_to_pinned_disk(self, only_from: Optional["StorageTier"]
                              = None) -> Optional["StorageTier"]:
        """Zero-IO demotion for disk-pinned buffers: the retained npz
        payload becomes the buffer again. Returns the tier demoted FROM
        (caller accounts the bytes), or None when there is no retained
        payload / the buffer is already on disk / ``only_from`` names a
        different tier (callers whose accounting assumes a specific
        source tier pass it so a racing move can't skew the books)."""
        with self._lock:
            if self._pinned_path is None or \
                    self.tier == StorageTier.DISK:
                return None
            if only_from is not None and self.tier != only_from:
                return None
            if not os.path.exists(self._pinned_path):
                self._pinned_path = None   # payload vanished; full spill
                return None
            prev = self.tier
            self._device_arrays = None
            self._host_arrays = None
            self._disk_path = self._pinned_path
            self._pinned_path = None
            self.tier = StorageTier.DISK
        _ledger.note_tier(self.id, StorageTier.DISK)
        from ..service.telemetry import flight_record
        flight_record("spill", f"buffer-{self.id}",
                      {"bytes": self.size_bytes, "to": "disk",
                       "pinned": True})
        return prev

    def free(self) -> None:
        with self._lock:
            self._device_arrays = None
            self._host_arrays = None
            if self._disk_path and os.path.exists(self._disk_path):
                os.unlink(self._disk_path)
            self._disk_path = None
            if self._pinned_path and os.path.exists(self._pinned_path):
                os.unlink(self._pinned_path)
            self._pinned_path = None


class BufferCatalog:
    """Global buffer registry + spill orchestration (RapidsBufferCatalog +
    the three RapidsBufferStores collapsed into one coordinator)."""

    _instance: Optional["BufferCatalog"] = None
    _lock = named_lock("exec.spill.BufferCatalog._lock")

    def __init__(self, device_budget: int = 1 << 34,
                 host_budget: int = 1 << 33,
                 spill_dir: str = "/tmp/spark_rapids_tpu_spill"):
        self.device_budget = device_budget
        self.host_budget = host_budget
        self.spill_dir = spill_dir
        self.buffers: Dict[int, SpillableBuffer] = {}
        self.device_bytes = 0
        self.host_bytes = 0
        self.spilled_device_bytes = 0     # metrics: total spilled (task metrics analog)
        self.spilled_host_bytes = 0
        # per-tenant DEVICE residency (service multi-tenancy): bytes held
        # on device by each tenant's buffers, maintained at the same
        # accounting boundaries as device_bytes; entries drop at 0 so an
        # idle tenant's watermark reads exactly zero
        self.tenant_device: Dict[str, int] = {}
        self._mu = named_rlock("exec.spill.BufferCatalog._mu")

    @classmethod
    def get(cls) -> "BufferCatalog":
        # double-checked creation: dependencies are built OUTSIDE the
        # class lock. The old shape called DeviceManager.get() (which
        # takes DeviceManager._lock and can probe the device) while
        # holding BufferCatalog._lock — an undocumented cross-singleton
        # order edge that lockdep flagged on its first clean run
        with cls._lock:
            inst = cls._instance
        if inst is not None:
            return inst
        from .. import config as cfg
        conf = cfg.TpuConf()
        try:
            # real device budget even when no session was built —
            # the 16 GiB constructor default is only a last resort
            from .device import DeviceManager
            device_budget = DeviceManager.get(conf).memory_budget_bytes
        except Exception:
            device_budget = 1 << 34
        candidate = BufferCatalog(
            device_budget=device_budget,
            host_budget=conf.host_spill_storage_size,
            spill_dir=conf.spill_dir)
        with cls._lock:
            if cls._instance is None:
                cls._instance = candidate
            return cls._instance

    @classmethod
    def peek(cls) -> Optional["BufferCatalog"]:
        """The existing instance or None — never constructs (telemetry
        harvest: reading residency must not bootstrap a catalog)."""
        with cls._lock:
            return cls._instance

    @classmethod
    def reset(cls) -> None:
        with cls._lock:
            if cls._instance is not None:
                for b in list(cls._instance.buffers.values()):
                    b.free()
            cls._instance = None
        # catalog reset is test teardown, not a free: drop the ledger's
        # buffer tables instead of tombstoning every torn-down id
        _ledger.forget_all()

    def buffer_count(self) -> int:
        with self._mu:
            return len(self.buffers)

    def residency_snapshot(self) -> List[Tuple[int, "StorageTier",
                                               float, bool]]:
        """(id, tier, priority, disk_pinned) per registered buffer — the
        ledger's end-of-query audit input, taken BEFORE the ledger lock
        (its lock is a leaf under this one)."""
        with self._mu:
            return [(b.id, b.tier, b.priority, b.disk_pinned)
                    for b in self.buffers.values()]

    # -- per-tenant residency (service multi-tenancy, docs/service.md) ------
    def _tenant_device_delta_locked(self, buf: "SpillableBuffer",
                                    delta: int) -> None:
        """Account ``delta`` device bytes to the buffer's tenant (caller
        holds ``self._mu``; untenanted buffers are a no-op). Entries
        drop at <= 0 so per-tenant watermarks return to exactly 0."""
        t = buf.tenant
        if t is None or not delta:
            return
        cur = self.tenant_device.get(t, 0) + delta
        if cur > 0:
            self.tenant_device[t] = cur
        else:
            self.tenant_device.pop(t, None)

    def tenant_device_bytes(self) -> Dict[str, int]:
        """Device bytes currently held per tenant (the
        ``tpu_tenant_device_bytes`` telemetry gauge's source)."""
        with self._mu:
            return dict(self.tenant_device)

    def _note_residency(self) -> None:
        """Update the process HBM/host watermarks after an accounting
        change (service/telemetry): current + peak bytes with
        per-operator peak attribution through the open exec scope.
        Called at admission/registration/free boundaries — never per
        row, never per element."""
        from ..service import telemetry
        telemetry.watermark("device", bag_key="peakDeviceBytes").update(
            self.device_bytes)
        telemetry.watermark("host").update(self.host_bytes)

    # -- registration --------------------------------------------------------
    def register_batch(self, batch: ColumnarBatch,
                       priority: float = ACTIVE_ON_DECK_PRIORITY) -> int:
        from ..columnar.column import ObjectColumn
        arrays: List[Any] = []
        col_dtypes: List[dt.DType] = []
        obj_cols: Dict[int, Column] = {}
        for ci, c in enumerate(batch.columns):
            if isinstance(c, ObjectColumn):
                obj_cols[ci] = c
                continue
            arrays.extend(c.arrays())
            col_dtypes.append(c.dtype)
        # tenant attribution (service multi-tenancy): the ambient query
        # context's tenant owns this buffer's residency. CACHE_PRIORITY
        # registrations (scan device cache, df.cache()) stay UNTENANTED —
        # cached tables are shared infrastructure served to every tenant,
        # and charging them to whichever tenant scanned first would leave
        # that tenant's watermark pinned above zero forever
        tenant = None
        if priority != CACHE_PRIORITY:
            from .query_context import current_tenant
            tenant = current_tenant()
        buf = SpillableBuffer(
            next_buffer_id(),
            BufferMeta(batch.schema, batch.num_rows_raw, batch.capacity),
            priority, arrays, col_dtypes, obj_cols, tenant=tenant)
        with self._mu:
            self.buffers[buf.id] = buf
            self.device_bytes += buf.size_bytes
            self._tenant_device_delta_locked(buf, buf.size_bytes)
            self._maybe_spill_locked()
            # per-tenant budget at the REGISTER boundary: a tenant past
            # its device budget spills its OWN buffers first (never the
            # one just registered — the active batch is not its own
            # victim; it becomes eligible at the next tenant's pressure)
            self._enforce_tenant_budget_locked(tenant, exclude_id=buf.id)
            self._note_residency()
        # ledger AFTER the admission lock releases; the registration
        # cascade may already have spilled this buffer, so pass its tier
        _ledger.note_register(buf.id, buf.size_bytes, priority, tenant,
                              tier=buf.tier)
        return buf.id

    def acquire_batch(self, buffer_id: int) -> ColumnarBatch:
        """Materialize a registered batch on device. A spilled buffer is
        re-promoted to the device tier WITH accounting — admission first
        (possibly spilling lower-priority buffers), then the promotion is
        charged against the device budget, so concurrent acquires cannot
        silently exceed it (RapidsBufferStore.scala:275-301)."""
        _ledger.note_access(buffer_id)
        with self._mu:
            buf = self.buffers[buffer_id]
            if buf.tier != StorageTier.DEVICE:
                target = self.device_budget - buf.size_bytes
                if self.device_bytes > target:
                    self._spill_device_to_locked(max(target, 0))
                prev_tier = buf.tier
                arrays = buf._load_arrays()
                buf.promote_to_device(arrays)
                if prev_tier == StorageTier.HOST:
                    self.host_bytes -= buf.size_bytes
                self.device_bytes += buf.size_bytes
                self._tenant_device_delta_locked(buf, buf.size_bytes)
                # re-promotion is a reserve-like boundary: an over-budget
                # tenant re-admitting a buffer yields its OTHER residents
                self._enforce_tenant_budget_locked(buf.tenant,
                                                   exclude_id=buf.id)
                self._note_residency()
        # device-tier rebuild happens OUTSIDE the catalog lock so concurrent
        # task threads on the (common) unspilled path never serialize here
        batch = buf.get_batch()
        # the catalog still owns (and may re-serve) these arrays: mark
        # the batch so fused programs never take them as donated buffers
        batch.shared = True
        return batch

    def pin_to_disk(self, buffer_id: int) -> int:
        """Push one registered buffer through to the DISK tier now (the
        durable-shuffle checkpoint write, docs/resilience.md) — unlike
        the pressure-driven cascade this is caller-initiated, so durable
        map outputs stop holding device/host memory the moment the map
        phase ends. Returns the buffer's size when it reached disk. The
        buffer stays registered and re-promotes on its next read.

        The npz IO runs OUTSIDE the admission lock (the ShuffleStore
        write-through rule: checkpoint writes must not stall every
        concurrent allocation/spill): the buffer's own lock serializes
        its tier moves, and each move's accounting commits immediately
        after the move lands — a disk write failing halfway must not
        tear the device/host byte counts (the host move already
        happened and stays accounted)."""
        with self._mu:
            buf = self.buffers.get(buffer_id)
        if buf is None:
            return 0
        buf.disk_pinned = True
        # re-pin fast path: a read promoted this pinned buffer and its
        # npz payload was retained — demotion is a tier flip, no IO
        prev = buf.demote_to_pinned_disk()
        if prev is not None:
            with self._mu:
                if prev == StorageTier.DEVICE:
                    self.device_bytes -= buf.size_bytes
                    self._tenant_device_delta_locked(buf, -buf.size_bytes)
                    self.spilled_device_bytes += buf.size_bytes
                elif prev == StorageTier.HOST:
                    self.host_bytes -= buf.size_bytes
                    self.spilled_host_bytes += buf.size_bytes
                self._note_residency()
            return buf.size_bytes
        moved = buf.spill_to_host()
        if moved:
            with self._mu:
                self.device_bytes -= moved
                self._tenant_device_delta_locked(buf, -moved)
                self.host_bytes += moved
                self.spilled_device_bytes += moved
                self._note_residency()
        moved_d = buf.spill_to_disk(self.spill_dir)
        if moved_d:
            with self._mu:
                self.host_bytes -= moved_d
                self.spilled_host_bytes += moved_d
                self._note_residency()
        return buf.size_bytes if buf.tier == StorageTier.DISK else 0

    def pin_working_set(self, tenant: Optional[str]) -> Tuple[int, int]:
        """Spill EVERY device-resident buffer of ``tenant`` to the host
        tier now — the suspend path of the query lifecycle control plane
        (docs/service.md): a preempted query's working set leaves the
        device so the preempting query gets real HBM headroom, not just
        a freed scheduler slot. Unlike the pressure-driven cascade this
        is caller-initiated and unconditional for the tenant; untenanted
        buffers (shared caches, CACHE_PRIORITY) are never victims.
        Returns ``(buffers_moved, bytes_moved)``. The spilled buffers
        stay registered and re-promote lazily on their next read
        (``acquire_batch``) after resume, so resumption pays
        re-promotion only for what it actually re-touches."""
        if tenant is None:
            return (0, 0)
        moved_n = moved_bytes = 0
        with self._mu:
            victims = sorted(
                (b for b in self.buffers.values()
                 if b.tier == StorageTier.DEVICE and b.tenant == tenant),
                key=lambda b: b.priority)
            with lockdep.allowed_while_locked(
                    "suspend working-set spill under the admission lock "
                    "(the synchronous-spill discipline, docs/service.md)"):
                for buf in victims:
                    moved = buf.spill_to_host()
                    if moved:
                        self.device_bytes -= moved
                        self._tenant_device_delta_locked(buf, -moved)
                        self.host_bytes += moved
                        self.spilled_device_bytes += moved
                        moved_n += 1
                        moved_bytes += moved
            self._note_residency()
            if self.host_bytes > self.host_budget:
                self._spill_host_to_locked(self.host_budget)
        return (moved_n, moved_bytes)

    def remove(self, buffer_id: int) -> None:
        with self._mu:
            buf = self.buffers.pop(buffer_id, None)
            if buf is not None:
                if buf.tier == StorageTier.DEVICE:
                    self.device_bytes -= buf.size_bytes
                    self._tenant_device_delta_locked(buf, -buf.size_bytes)
                elif buf.tier == StorageTier.HOST:
                    self.host_bytes -= buf.size_bytes
                buf.free()
                self._note_residency()
        # unconditional (outside the admission lock): a remove of an
        # already-removed id is exactly the double-free the ledger exists
        # to diagnose
        _ledger.note_free(buffer_id)

    # -- spill logic ---------------------------------------------------------
    def reserve(self, nbytes: int) -> None:
        """Admission check before materializing ~nbytes on device
        (DeviceMemoryEventHandler.onAllocFailure analog: spill until the
        allocation fits, DeviceMemoryEventHandler.scala:42-69). Also the
        per-tenant RESERVE boundary: a tenant already past its device
        budget spills its own resident buffers before growing."""
        from .query_context import current_tenant
        tenant = current_tenant()
        with self._mu:
            target = self.device_budget - nbytes
            if self.device_bytes > target:
                self._spill_device_to_locked(max(target, 0))
            self._enforce_tenant_budget_locked(tenant)
            self._note_residency()

    def _maybe_spill_locked(self) -> None:
        if self.device_bytes > self.device_budget:
            self._spill_device_to_locked(self.device_budget)

    def _over_budget_tenants_locked(self) -> set:
        """Tenants currently holding more device bytes than their
        installed budget (service/tenants.py) — the cascade's preferred
        victim class. Caller holds ``self._mu``."""
        from ..service import tenants as tn
        return {t for t, held in self.tenant_device.items()
                if tn.over_budget(t, held)}

    def _spill_device_to_locked(self, target: int) -> None:
        """Pop lowest-priority device buffers and push to host tier
        (RapidsBufferStore.synchronousSpill, RapidsBufferStore.scala:139-201).
        Caller holds ``self._mu`` (the ``_locked`` convention).

        Cross-tenant spill priority (docs/service.md §3): buffers of
        tenants OVER their device budget are cascade victims before any
        under-budget (or untenanted) tenant's, so global pressure caused
        by one tenant's overdraw lands on that tenant first; within a
        class the usual spill priority orders."""
        over = self._over_budget_tenants_locked()
        device_bufs = sorted(
            (b for b in self.buffers.values() if b.tier == StorageTier.DEVICE),
            key=lambda b: (0 if b.tenant in over else 1, b.priority))
        with lockdep.allowed_while_locked(
                "synchronous spill: the admission lock serializes tier "
                "moves by design (DeviceMemoryEventHandler analog)"):
            for buf in device_bufs:
                if self.device_bytes <= target:
                    break
                moved = buf.spill_to_host()
                self.device_bytes -= moved
                self._tenant_device_delta_locked(buf, -moved)
                self.host_bytes += moved
                self.spilled_device_bytes += moved
        self._note_residency()     # host tier may have just peaked
        if self.host_bytes > self.host_budget:
            self._spill_host_to_locked(self.host_budget)

    def _enforce_tenant_budget_locked(self, tenant: Optional[str],
                                      exclude_id: Optional[int] = None
                                      ) -> None:
        """Per-tenant budget enforcement at the reserve/register
        boundaries: while ``tenant`` holds more device bytes than its
        budget (service/tenants.py), its OWN device buffers spill
        lowest-priority-first — an overdrawing tenant pays with its own
        residency before any neighbor does. ``exclude_id`` protects the
        buffer being registered right now (the active batch is never its
        own victim). Caller holds ``self._mu``."""
        from ..service import tenants as tn
        if tenant is None:
            return
        held = self.tenant_device.get(tenant, 0)
        if not tn.over_budget(tenant, held):
            return
        budget = tn.budget_for(tenant)
        victims = sorted(
            (b for b in self.buffers.values()
             if b.tier == StorageTier.DEVICE and b.tenant == tenant and
             b.id != exclude_id),
            key=lambda b: b.priority)
        with lockdep.allowed_while_locked(
                "per-tenant budget spill under the admission lock (the "
                "synchronous-spill discipline, docs/service.md)"):
            for buf in victims:
                if self.tenant_device.get(tenant, 0) <= budget:
                    break
                moved = buf.spill_to_host()
                self.device_bytes -= moved
                self._tenant_device_delta_locked(buf, -moved)
                self.host_bytes += moved
                self.spilled_device_bytes += moved
        self._note_residency()
        if self.host_bytes > self.host_budget:
            self._spill_host_to_locked(self.host_budget)

    def _spill_host_to_locked(self, target: int) -> None:
        host_bufs = sorted(
            (b for b in self.buffers.values() if b.tier == StorageTier.HOST),
            key=lambda b: b.priority)
        with lockdep.allowed_while_locked(
                "synchronous host->disk cascade under the admission lock"):
            for buf in host_bufs:
                if self.host_bytes <= target:
                    break
                moved = buf.spill_to_disk(self.spill_dir)
                self.host_bytes -= moved
                self.spilled_host_bytes += moved


class SpillableColumnarBatch:
    """Handle to a batch that may be spilled and rematerialized on demand
    (SpillableColumnarBatch.scala:28-137)."""

    @host_site("spillable")
    def __init__(self, batch: ColumnarBatch,
                 priority: float = ACTIVE_ON_DECK_PRIORITY,
                 catalog: Optional[BufferCatalog] = None):
        self.catalog = catalog or BufferCatalog.get()
        # keep a device-resident count lazy: registering a streamed batch
        # must not force a host sync (see ColumnarBatch.num_rows)
        self._num_rows = batch.num_rows_raw
        self.schema = batch.schema
        self.size_bytes = batch.device_size_bytes()
        self._id = self.catalog.register_batch(batch, priority)
        self._closed = False

    @property
    def num_rows(self):
        nr = self._num_rows
        if not isinstance(nr, int):
            nr = int(nr)
            self._num_rows = nr
        return nr

    @host_site("spillable")
    def get_batch(self) -> ColumnarBatch:
        if self._closed:
            raise BufferLostError(f"buffer {self._id} released")
        try:
            return self.catalog.acquire_batch(self._id)
        except KeyError:
            raise BufferLostError(f"buffer {self._id} missing from the "
                                  "catalog") from None

    def pin_to_disk(self) -> int:
        """Durable pin: push this handle's buffer to the disk tier now
        (see :meth:`BufferCatalog.pin_to_disk`); 0 when already closed."""
        if self._closed:
            return 0
        return self.catalog.pin_to_disk(self._id)

    def close(self) -> None:
        if not self._closed:
            self.catalog.remove(self._id)
            self._closed = True

    @property
    def closed(self) -> bool:
        return self._closed

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class BorrowedSpillableView:
    """Non-owning stand-in for an already-registered batch (a scan
    device-cache entry served straight downstream): re-registering the
    same device arrays would double-count HBM in the catalog, so drain
    layers borrow the owner's registration. ``get_batch`` returns the
    borrowed batch directly (our reference pins the arrays regardless of
    the owner's spill state) and ``close`` is a no-op — lifetime belongs
    to the cache entry."""

    def __init__(self, owner: "SpillableColumnarBatch",
                 batch: ColumnarBatch):
        self._batch = batch
        self.schema = batch.schema
        self.size_bytes = owner.size_bytes
        self._num_rows = batch.num_rows_raw

    @property
    def num_rows(self):
        nr = self._num_rows
        if not isinstance(nr, int):
            nr = int(nr)
            self._num_rows = nr
        return nr

    def get_batch(self) -> ColumnarBatch:
        return self._batch

    def close(self) -> None:
        pass
