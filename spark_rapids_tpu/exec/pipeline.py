"""Deferred-scalar pipeline window: the engine's ONE pipelining primitive.

Each blocking device->host readback is a host sync — the host stops
dispatching until the device has drained to it — so any operator that
sizes its next dispatch from a device scalar (join output totals, compact
counts, group stats) serializes the stream if it reads that scalar per
batch. The
reference never pays this: cuDF's size-returning calls ride one stream
(GpuHashJoin.scala:193-249), and the aggregate hot loop keeps the device
busy across batches (aggregate.scala:427-485).

The window generalizes the streaming aggregate's bespoke in-flight deque
(physical.py round 4) into a reusable primitive:

* operators ``push(continuation, *device_scalars)`` — the continuation is
  the second half of the batch's work, parameterized on the CONCRETE host
  values of the scalars;
* the window holds up to ``depth`` pending entries; when full it lands the
  oldest half, resolving EVERY landing entry's scalars with ONE
  ``jax.device_get([...])`` (a single host round trip, ~8x cheaper than
  sequential gets at depth 16), then runs their continuations in FIFO
  order;
* ``flush()`` lands everything at partition end.

depth=1 degenerates to today's blocking behavior (every push lands
immediately). Entries with NO scalars ride through untouched when nothing
older is pending, so scalar-free operators (semi/anti joins) keep
streaming incrementally instead of buffering a window they don't need.

Failure containment: if the batched ``device_get`` fails (a dispatched
program erroring at execution time), each landing continuation receives
``None`` for its scalars — callers re-read per entry and degrade that one
batch (the aggregate path falls back to eager), so one bad program never
zeroes a whole window.
"""

from __future__ import annotations

from collections import deque
from typing import Any, Callable, List

from .tracing import host_site, trace_span


class PipelineWindow:
    """FIFO window of (device scalars, continuation) pairs resolved in
    batched host readbacks. Single-consumer: one window per partition
    drain (partition tasks each build their own)."""

    def __init__(self, depth: int, metrics=None):
        self.depth = max(1, int(depth))
        # owning exec's metrics bag: batched resolves run OUTSIDE the
        # operator's metered span (the push happens after it closes), so
        # the window re-opens the exec scope itself for sync attribution
        self.metrics = metrics
        self._pending: deque = deque()
        # observability: how many batched resolves ran, how many scalars
        # they carried, and how many landings degraded to per-entry reads
        # (exported into span/metric reports by callers that care)
        self.resolves = 0
        self.resolved_scalars = 0
        self.resolve_failures = 0

    def __len__(self) -> int:
        return len(self._pending)

    @host_site("window")
    def push(self, continuation: Callable[..., Any],
             *scalars) -> List[Any]:
        """Enqueue one entry; returns the results of any entries that
        landed as a consequence (possibly empty, FIFO order). The
        continuation is called as ``continuation(*host_values)`` with one
        concrete value per pushed scalar (or ``None`` per scalar when the
        batched readback failed)."""
        if not scalars and not self._pending:
            # scalar-free entry with nothing older in flight: nothing to
            # wait for and no FIFO hazard — run it now so scalar-free
            # streams stay incremental at any depth
            return [continuation()]
        self._pending.append((list(scalars), continuation))
        if len(self._pending) >= self.depth:
            # land the oldest half: the younger half keeps its scalars in
            # flight so their transfers hide behind the continuations'
            # dispatch work (same cadence as the streaming aggregate)
            return self._land(max(self.depth // 2, 1))
        return []

    def flush(self) -> List[Any]:
        """Land every pending entry (partition end)."""
        out: List[Any] = []
        while self._pending:
            out.extend(self._land(max(self.depth // 2, 1)))
        return out

    # -- internal -----------------------------------------------------------
    def _land(self, k: int) -> List[Any]:
        k = min(k, len(self._pending))
        entries = [self._pending.popleft() for _ in range(k)]
        flat = [s for scalars, _cont in entries for s in scalars]
        vals = self._resolve(flat)
        if flat:
            self.resolves += 1
            self.resolved_scalars += len(flat)
        results: List[Any] = []
        pos = 0
        for scalars, cont in entries:
            take = vals[pos:pos + len(scalars)]
            pos += len(scalars)
            results.append(cont(*take))
        return results

    def _resolve(self, flat: List[Any]) -> List[Any]:
        """Materialize every scalar with ONE host readback per distinct
        dtype (typically one): same-dtype scalars pack into a single
        device array via one fused concat dispatch, so k pending scalars
        cost one transfer, not k blocking round trips — and the engine's
        attributed-sync count (exec/tracing.SyncCounter)
        sees O(1) reads per landing, not O(window). No cross-dtype cast:
        int32 counts above 2^24 must not round-trip through a float."""
        if not flat:
            return []
        import numpy as np
        # numpy values are ALREADY host: routing them through the packed
        # device_get would pay an upload + a readback for data the caller
        # could use directly
        device = [(i, s) for i, s in enumerate(flat)
                  if hasattr(s, "dtype") and hasattr(s, "shape")
                  and not isinstance(s, (np.ndarray, np.generic))]
        vals: List[Any] = list(flat)       # host values pass through
        if not device:
            return vals
        import jax
        import jax.numpy as jnp
        with trace_span("pipeline_resolve", self.metrics):
            try:
                groups: dict = {}
                for i, s in device:
                    groups.setdefault(np.dtype(s.dtype), []).append((i, s))
                packed = [jnp.concatenate([jnp.ravel(s) for _i, s in grp])
                          if len(grp) > 1 or grp[0][1].shape != ()
                          else grp[0][1]
                          for grp in groups.values()]
                hosts = [np.asarray(h) for h in jax.device_get(packed)]
                for grp, host in zip(groups.values(), hosts):
                    host = np.atleast_1d(host)
                    pos = 0
                    for i, s in grp:
                        n = int(np.prod(s.shape)) if s.shape else 1
                        chunk = host[pos:pos + n]
                        pos += n
                        vals[i] = chunk.reshape(s.shape) if s.shape \
                            else chunk[0]
            except Exception as e:
                # a dispatched program failed at execution time: hand
                # every landing continuation None so each re-reads (and
                # degrades) its OWN batch instead of the whole window.
                # Count + log it — a PERSISTENT failure here silently
                # reverts the engine to per-batch-sync cadence, which must
                # be visible in logs/metrics, not only in CI sync tests
                self.resolve_failures += 1
                import logging
                logging.getLogger("spark_rapids_tpu.pipeline").warning(
                    "pipeline window batched resolve failed (landing "
                    "degrades to per-entry blocking reads): %s", e)
                for i, _s in device:
                    vals[i] = None
        return vals
