"""Per-operator metrics: the GpuMetric / SQLMetrics layer.

Reference: ``GpuExec.scala:27-56`` — every GpuExec owns a bag of SQLMetrics
(``GpuMetricNames``: numOutputRows, numOutputBatches, opTime, plus
per-operator ``additionalMetrics``) surfaced per operator in the Spark UI.
Here every :class:`~..plan.physical.TpuExec` instance owns a
:class:`TpuMetrics` bag, populated three ways:

* explicitly — ``self.metrics.inc("numOutputRows", n)`` and
  ``trace_span(name, self.metrics, "opTime")`` timer feeds;
* by ATTRIBUTION — while a metered span is open, this module tracks the
  innermost open exec's bag in a thread-local stack (:func:`exec_scope`),
  and cross-cutting instruments route their events to it:
  ``SyncCounter`` adds ``hostSyncs`` per blocking device->host readback,
  the recompile audit adds ``recompiles`` per fused-program build, and the
  spill store adds ``spillBytes`` when a buffer leaves the device tier —
  so EXPLAIN ANALYZE shows which operator paid for what, not just a
  process-wide total;
* lazily — device-resident amounts (lazy batch counts) bank unresolved and
  fold in one batched readback at reporting boundaries (``resolve``).

Every exec class declares its metric-key surface with
``METRICS = exec_metrics(...)`` next to its CONTRACT; the project linter
(``analysis/lint.py`` rules ``exec-metrics`` / ``metric-key``) enforces
that declared set covers every literal key the class emits, keeping the
metrics surface greppable and drift-free.

Collection is gated by ``spark.rapids.tpu.sql.metrics.enabled``
(default on; one cached-bool check per inc when off).
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Iterator, Optional, Tuple

# ---------------------------------------------------------------------------
# Declared metric keys
# ---------------------------------------------------------------------------

#: Keys every exec may emit without declaring them: the GpuMetricNames
#: basics plus the cross-cutting attributed keys this module routes.
#: (Mirrored in analysis/lint.py BASE_METRIC_KEYS — the linter is pure
#: AST and cannot import this module.)
BASE_METRICS: Tuple[str, ...] = (
    "numOutputRows", "numOutputBatches", "opTime",
    "hostSyncs", "recompiles", "spillBytes", "peakDeviceBytes",
    "compileSeconds",
)


def exec_metrics(*extras: str) -> frozenset:
    """Declare an exec class's metric-key surface (its ``METRICS`` class
    attribute): the base keys plus the class's additionalMetrics
    (``GpuExec.additionalMetrics`` analog). Keys must be string literals —
    the linter checks usage against the declaration lexically."""
    assert all(isinstance(k, str) and k for k in extras), extras
    return frozenset(BASE_METRICS) | frozenset(extras)


# ---------------------------------------------------------------------------
# Enabled gate (spark.rapids.tpu.sql.metrics.enabled)
# ---------------------------------------------------------------------------

_enabled_cache: Optional[bool] = None


def metrics_enabled() -> bool:
    # primed EAGERLY by session bootstrap (refresh) like lockdep: a lazy
    # read of the ACTIVE session's conf would take TpuSession._lock, and
    # attributed incs can run under the spill catalog's admission lock —
    # a lazy prime there would add a catalog->session lock-order edge
    # opposing bootstrap's session->catalog one
    global _enabled_cache
    if _enabled_cache is None:
        try:
            from .. import config as cfg
            _enabled_cache = bool(cfg.TpuConf().get(cfg.METRICS_ENABLED))
        except Exception:
            _enabled_cache = True
    return _enabled_cache


def refresh(conf) -> None:
    """Prime the enabled gate from a session conf (bootstrap)."""
    global _enabled_cache
    try:
        from .. import config as cfg
        _enabled_cache = bool(conf.get(cfg.METRICS_ENABLED))
    except Exception:
        _enabled_cache = True


def reset_cache() -> None:
    global _enabled_cache
    _enabled_cache = None


# ---------------------------------------------------------------------------
# Innermost-open-exec attribution
# ---------------------------------------------------------------------------
#
# trace_span(metrics=...) pushes the bag for the span's duration; the stack
# is thread-local because partition drains run concurrently on the task
# pool and two execs' spans must not see each other. Cross-cutting
# instruments (SyncCounter, recompile audit, spill store) call
# ``attribute`` to charge the innermost open exec.

_TLS = threading.local()


def _stack() -> list:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


def push_exec(metrics: "TpuMetrics") -> None:
    """Mark ``metrics`` as the innermost open exec bag on this thread
    (``trace_span`` with a metered exec; paired with :func:`pop_exec`)."""
    _stack().append(metrics)


def pop_exec(metrics: "TpuMetrics") -> None:
    # remove by identity, not pop(): spans held open across generator
    # yields close out of order (the SpanRecorder._pop lesson), and a
    # bare pop would steal a younger exec's open scope
    st = _stack()
    for i in range(len(st) - 1, -1, -1):
        if st[i] is metrics:
            del st[i]
            break


@contextmanager
def exec_scope(metrics: Optional["TpuMetrics"]) -> Iterator[None]:
    """Mark ``metrics`` as the innermost open exec bag on this thread for
    the duration (no-op for None): the ``with`` form of the pair, for a
    region that is no span (``trace_span`` pushes and pops the bag of a
    metered span itself)."""
    if metrics is None:
        yield
        return
    push_exec(metrics)
    try:
        yield
    finally:
        pop_exec(metrics)


def current() -> Optional["TpuMetrics"]:
    """The innermost open exec's metrics bag on THIS thread (None outside
    any metered exec span)."""
    st = _stack()
    return st[-1] if st else None


def attribute(key: str, amount: float = 1) -> None:
    """Charge ``amount`` of ``key`` to the innermost open exec, if any.
    The funnel SyncCounter (hostSyncs), the recompile audit (recompiles)
    and the spill store (spillBytes) route through."""
    m = current()
    if m is not None:
        m.inc(key, amount)


# ---------------------------------------------------------------------------
# The metrics bag
# ---------------------------------------------------------------------------

class TpuMetrics(dict):
    """One exec instance's metric bag (GpuExec.allMetrics analog).

    Plain ``dict`` of key -> number. Device-resident amounts (lazy batch
    counts) bank unresolved and fold in one batched readback at reporting
    boundaries so metric accounting never forces a device sync on the hot
    path."""

    # a RAW leaf lock on purpose: inc runs per batch per operator on every
    # task thread, and a lockdep NamedLock would take the process-global
    # lockdep state mutex up to 3x per inc under record mode (the bench
    # default) — serializing the task pool on the counters the bench
    # exists to measure. The bag lock never nests, so order tracking
    # buys nothing here.
    _lock = threading.Lock()  # lint: raw-lock-ok leaf counter lock on the hottest inc path; lockdep instrumentation would contend the global lockdep state per metric inc

    # keys that are LOAD-BEARING, not just observability: the AQE runtime
    # broadcast switch reads the exchange's observed dataSize
    # (physical._maybe_runtime_broadcast), so it must accumulate even
    # when sql.metrics.enabled is off
    LOAD_BEARING_KEYS = frozenset({"dataSize"})

    # watermark-style keys are SET (max), not summed — publishing their
    # growth into a cumulative registry counter would add peaks together
    WATERMARK_KEYS = frozenset({"peakDeviceBytes"})

    def inc(self, key: str, amount: float = 1) -> None:
        # partitions drain on concurrent task threads; keep counters exact.
        if not metrics_enabled() and key not in TpuMetrics.LOAD_BEARING_KEYS:
            return
        if not isinstance(amount, (int, float)):
            with TpuMetrics._lock:
                if not hasattr(self, "_pending"):
                    self._pending = []
                self._pending.append((key, amount))
                flush = len(self._pending) >= 256
            if flush:          # bound the deferred-scalar backlog
                self.resolve()
            return
        with TpuMetrics._lock:
            self[key] = dict.get(self, key, 0) + amount

    def max(self, key: str, value: float) -> None:
        """Raise ``key`` to at least ``value`` (watermark-style metrics:
        the HBM peak attribution sets, never sums)."""
        if not metrics_enabled():
            return
        with TpuMetrics._lock:
            if value > dict.get(self, key, 0):
                self[key] = value

    def resolve(self) -> "TpuMetrics":
        """Fold deferred device-scalar amounts into the counters in one
        batched readback (reporting boundaries; readers below call it)."""
        with TpuMetrics._lock:
            pend = getattr(self, "_pending", [])
            self._pending = []
        if pend:
            import jax
            try:
                vals = jax.device_get([a for _k, a in pend])
            except Exception:
                # one bad scalar must not zero the whole flush: fall back
                # to per-value reads, dropping only the failed ones
                vals = []
                for _k, a in pend:
                    try:
                        vals.append(jax.device_get(a))
                    except Exception:
                        vals.append(None)
            with TpuMetrics._lock:
                for (key, _a), v in zip(pend, vals):
                    if v is None:
                        continue
                    v = v.item() if hasattr(v, "item") else v  # lint: lock-blocking-ok v is a host numpy value (device_get ran unlocked above); .item() is a cast, not a readback
                    if isinstance(v, float) and v.is_integer():
                        v = int(v)     # row/batch counters stay integral
                    self[key] = dict.get(self, key, 0) + v
        self._publish()
        return self

    def _publish(self) -> None:
        """Fold this bag's growth since the last publish into the
        process metrics registry (``tpu_exec_metric_total{key=...}``) —
        the resolve-boundary publish of the continuous-telemetry layer.
        Resolve runs at reporting boundaries, so the registry never sees
        per-batch (let alone per-row) traffic."""
        if not metrics_enabled():
            return
        with TpuMetrics._lock:
            pub = getattr(self, "_published", None)
            if pub is None:
                pub = self._published = {}
            deltas = []
            for key in dict.keys(self):
                if key in TpuMetrics.WATERMARK_KEYS:
                    continue
                d = dict.get(self, key, 0) - pub.get(key, 0)
                if d > 0:
                    deltas.append((key, d))
                    pub[key] = pub.get(key, 0) + d
        if not deltas:
            return
        try:
            from ..service.telemetry import MetricsRegistry
            reg = MetricsRegistry.get()
            for key, d in deltas:
                reg.counter("tpu_exec_metric_total",
                            "per-exec metric totals folded in at bag "
                            "resolve", key=key).inc(d)
        except Exception:
            pass               # telemetry must never fail a metrics read

    # readers see resolved counters (deferred amounts fold in lazily)
    def __getitem__(self, key):
        self.resolve()
        return dict.__getitem__(self, key)

    def get(self, key, default=None):
        if getattr(self, "_pending", None):
            self.resolve()
        return dict.get(self, key, default)

    def items(self):
        self.resolve()
        return dict.items(self)

    def timer(self, key: str):
        return _Timer(self, key)

    def gbps(self, bytes_keys, seconds_keys) -> Optional[float]:
        """Throughput view over this bag: GB/s of the summed byte
        counters over the summed second counters (None when either side
        is empty — a never-executed operator has no rate). The shuffle
        report reads exchange GB/s through this."""
        b = sum(self.get(k, 0) or 0 for k in bytes_keys)
        s = sum(self.get(k, 0.0) or 0.0 for k in seconds_keys)
        if b <= 0 or s <= 0:
            return None
        return b / s / 1e9


class _Timer:
    def __init__(self, metrics: TpuMetrics, key: str):
        self.metrics = metrics
        self.key = key

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.metrics.inc(self.key, time.perf_counter() - self.t0)
        return False


# Back-compat alias: physical.py re-exports this as ``Metrics``
Metrics = TpuMetrics
