"""Multi-process shuffle manager: the local/remote split that lets one
planner-driven query run across worker processes.

Reference mapping (SURVEY.md §2.8, VERDICT round-3 missing #1):
- ``RapidsShuffleInternalManager.scala:200-374`` -> :class:`WorkerContext`
  — per-worker singleton wiring the shuffle store, transfer server, and
  peer addresses (the BlockManagerId topology the reference advertises in
  MapStatus).
- ``RapidsCachingWriter`` (":73-192") -> :meth:`DistributedShuffle.write`
  — map output slices register in the LOCAL store keyed by
  (shuffle_id, reduce partition); nothing is written to disk.
- ``RapidsCachingReader.scala:49-148`` -> :meth:`DistributedShuffle.read`
  — reduce tasks short-circuit local slices straight out of the local
  store and ``ShuffleClient``-fetch remote peers' slices over TCP.

Worker model: every worker runs the SAME logical query over its own local
data shard. Exchange ids are allocated from a per-context counter, so
identical query sequences allocate identical shuffle ids on every worker
(Spark's driver hands out shuffle ids; standalone, the lockstep-query
contract replaces the driver). Reduce-partition ownership is
``p % n_workers == worker_id``; each worker's collect returns the rows of
its owned partitions, and the caller (or a front tier) concatenates.

Map-completion barrier: a reduce-side fetch must not observe a peer's
half-written map output. The writer marks (shuffle_id) complete in its
store after its map phase; the fetch protocol's metadata response carries
the flag and :meth:`ShuffleClient.fetch_when_complete` polls with backoff
until the peer's map is done (the reference gets this ordering for free
from Spark's stage scheduler; the flag replaces it standalone).
"""

from __future__ import annotations

import re
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..analysis.lockdep import named_lock
from ..columnar import dtypes as dt
from ..columnar.batch import ColumnarBatch
from .transport import (ShuffleClient, ShuffleDesyncError, ShuffleFetchError,
                        ShuffleServer, ShuffleStore, ShuffleWorkerLostError,
                        _rebuild_batch)

#: shuffle-id namespace width: ids are ``(query seq << NS_SHIFT) + n``,
#: giving each query its own 2**NS_SHIFT-wide id range (docs/shuffle.md).
#: Query ids are lockstep-deterministic (exec/query_context.py), so every
#: worker derives the SAME namespace for the same query — which is what
#: lets two distributed queries be in flight CONCURRENTLY without
#: desyncing the id stream (the old single global counter interleaved
#: nondeterministically under concurrency).
NS_SHIFT = 20

_QSEQ_RE = re.compile(r"^q(\d+)")


def _query_namespace() -> int:
    """The shuffle-id namespace of the AMBIENT query: its lockstep query
    sequence number (the ``q<seq>`` prefix every worker mints identically
    for the same query), or namespace 0 when no query context is active
    (direct shuffle-layer callers, tests)."""
    from ..exec.query_context import current_query_id
    qid = current_query_id()
    if not qid:
        return 0
    m = _QSEQ_RE.match(qid)
    return int(m.group(1)) if m else 0


class WorkerContext:
    """Per-process shuffle worker state (GpuShuffleEnv + shuffle-manager
    singleton analog). ``current`` activates multi-process shuffle in every
    exchange exec planned afterwards."""

    current: Optional["WorkerContext"] = None
    # class-level: ``current`` is a CLASS attribute, so its two writers
    # (init_worker, shutdown) must share one lock — a per-instance lock
    # would let a dying context's check-then-clear race a fresh
    # init_worker and clobber the new context
    _current_mu = named_lock("shuffle.manager.WorkerContext._current_mu")

    def __init__(self, worker_id: int, n_workers: int,
                 port: int = 0, codec: str = "none",
                 fetch_timeout_s: float = 60.0,
                 durable_dir: Optional[str] = None):
        self.worker_id = worker_id
        self.n_workers = n_workers
        # durable shuffle tier (docs/resilience.md): explicit dir wins;
        # otherwise conf shuffle.durable pins map outputs under the
        # spill dir so a dead worker's rejoin re-serves them. The knobs
        # come from the recovery-primed state (session bootstrap primes
        # it) — a fresh TpuConf() here would only see env/defaults and
        # silently ignore the session's conf
        if durable_dir is None:
            from ..exec import recovery
            if recovery.shuffle_durable():
                import os
                durable_dir = os.path.join(
                    recovery.spill_dir(),
                    f"shuffle-durable-w{worker_id}")
        self.durable_dir = durable_dir
        from ..exec import recovery as _recovery
        self.store = ShuffleStore(
            durable_dir=durable_dir,
            durable_budget=_recovery.durable_max_bytes())
        self.store.release_quorum = n_workers
        if durable_dir:
            # a rejoining worker (fresh process, same durable dir)
            # re-serves the outputs its previous incarnation pinned
            self.store.reload_durable()
        self.server = ShuffleServer(self.store, port=port,
                                    codec=codec).start()
        self.port = self.server.port
        self.codec = codec
        self.peers: Dict[int, Tuple[str, int]] = {}
        self.fetch_timeout_s = fetch_timeout_s
        # per-query-NAMESPACE lockstep counters (LOCKSTEP_IDS registry,
        # analysis/determinism.py), resumed lazily on first mint: each
        # namespace's counter starts PAST any durable-reloaded ids in
        # that namespace — reusing a previous incarnation's shuffle id
        # would merge its rows into a new query and answer peers'
        # completion polls from the stale mark (an id colliding with a
        # peer's LATER exchange fails the fingerprint handshake loudly
        # instead)
        self._next_by_ns: Dict[int, int] = {}
        self._peer_complete: set = set()    # (worker_id, shuffle_id)
        self._lost: set = set()             # failed-send-detected peers
        self._mu = named_lock("shuffle.manager.WorkerContext._mu")

    def set_peers(self, peers: Dict[int, Tuple[str, int]]) -> None:
        """worker_id -> (host, port) for every OTHER worker."""
        self.peers = {int(w): (h, int(p)) for w, (h, p) in peers.items()  # lint: unguarded-ok cluster wiring: set once at startup before any query thread runs
                      if int(w) != self.worker_id}

    def next_shuffle_id(self) -> int:
        """Deterministic across workers running the same query sequence
        (the standalone replacement for driver-issued shuffle ids),
        NAMESPACED by the ambient query: ``(query seq << NS_SHIFT) + n``.
        Two concurrent distributed queries draw from disjoint counters,
        so their interleaving cannot desync the id stream — the gating
        contract for concurrent distributed serving (docs/shuffle.md)."""
        ns = _query_namespace()
        base = ns << NS_SHIFT
        with self._mu:
            nxt = self._next_by_ns.get(ns)
            if nxt is None:
                # first mint in this namespace: resume past the durable
                # tier's ids WITHIN the namespace (a rejoining worker
                # re-serving old outputs must not re-mint their ids)
                nxt = max(base, self.store.durable_max_shuffle_id_in(
                    base, base + (1 << NS_SHIFT))) + 1
            sid = nxt
            self._next_by_ns[ns] = sid + 1
        # the mint is a lockstep-relevant event: fold it into the
        # per-query divergence digest (outside the mutex — the audit
        # takes its own leaf lock and may flight-record)
        from ..analysis import divergence
        divergence.note_event(f"shuffle-id:{sid}")
        return sid

    def owns_reduce(self, p: int) -> bool:
        return p % self.n_workers == self.worker_id

    def client_for(self, worker_id: int) -> ShuffleClient:
        host, port = self.peers[worker_id]
        return ShuffleClient.for_address(host, port)

    # -- liveness / death / rejoin ------------------------------------------
    def mark_worker_lost(self, worker_id: int,
                         exc: Optional[BaseException] = None) -> None:
        """Failed-send detection: record the peer as dead (telemetry
        counter + flight record; idempotent per loss episode)."""
        with self._mu:
            fresh = worker_id not in self._lost
            self._lost.add(worker_id)
        if fresh:
            from ..exec import recovery
            recovery.note_worker_lost(worker_id, exc)

    def is_worker_lost(self, worker_id: int) -> bool:
        with self._mu:
            return worker_id in self._lost

    def lost_workers(self) -> List[int]:
        with self._mu:
            return sorted(self._lost)

    def admit_worker(self, worker_id: int,
                     address: Optional[Tuple[str, int]] = None) -> None:
        """(Re-)admit a peer: update its address when given and clear
        the lost mark — the rejoin half of death/rejoin. A worker that
        restarted with a durable store re-serves its old outputs, so
        in-flight stage retries recover without re-running map stages."""
        with self._mu:
            was_lost = worker_id in self._lost
            self._lost.discard(worker_id)
            if address is not None:
                self.peers[worker_id] = (address[0], int(address[1]))
        if was_lost:
            from ..exec import recovery
            recovery.note_worker_rejoin(worker_id)

    def probe_peer(self, worker_id: int, timeout_s: float = 1.0) -> bool:
        """Cheap liveness heartbeat: one metadata round trip against the
        peer's transfer server (shuffle 0 is never registered, so the
        reply content is irrelevant — answering at all means alive)."""
        from .wire import META_REQ, FrameReader, encode_frame
        import socket as _socket
        host, port = self.peers[worker_id]
        conn = None
        try:
            sock = _socket.create_connection((host, port),
                                             timeout=timeout_s)
            from .transport import SocketConnection
            conn = SocketConnection(sock)
            conn.send(encode_frame(META_REQ, {"shuffle_id": 0,
                                              "reduce_ids": []}))
            FrameReader(conn.read_exact).next_frame()
            return True
        except (ConnectionError, OSError):
            return False
        finally:
            if conn is not None:
                conn.close()

    def restart_server(self) -> int:
        """Restart this worker's transfer server on its ORIGINAL port
        (peers keep their address book) — the in-process rejoin after an
        injected or real server death. Returns the bound port."""
        old = self.server
        try:
            old.stop()
        except Exception:
            pass
        server = ShuffleServer(self.store, port=self.port,
                               codec=self.codec).start()
        with self._mu:
            self.server = server
            self.port = server.port
        return server.port

    def fetch_from_peer(self, worker_id: int, shuffle_id: int,
                        reduce_ids: List[int],
                        fingerprint: Optional[str] = None):
        """One peer fetch under the stage-retry discipline
        (exec/recovery.py): a desync aborts immediately; a dead worker
        is marked lost and probed on its OWN wall-clock window (one
        fetch timeout per budget attempt — liveness probes are not
        stage retries, so they neither consume the budget nor count in
        ``tpu_stage_retries_total``); a rejoined server (durable
        outputs re-served) is re-admitted and the fetch re-executes
        from those durable inputs; stragglers/released outputs retry on
        the same budget. The budget exhausted, the original loud error
        propagates (partial rows are never returned)."""
        import time as _time
        from ..exec import recovery
        rs = recovery.StageRetryState(f"fetch-peer{worker_id}")
        while True:
            try:
                out = self._fetch_attempt(worker_id, shuffle_id,
                                          reduce_ids, fingerprint)
                rs.succeeded()
                if rs.attempts:
                    # the peer answered after a loss episode: re-admit
                    self.admit_worker(worker_id)
                return out
            except ShuffleWorkerLostError as e:  # lint: recover-ok failed-send detection: marks the peer lost, then routes into the recovery retry loop
                self.mark_worker_lost(worker_id, e)
                # sleep=False: the probe loop below paces itself from
                # 50ms — prepending the stage-retry backoff would only
                # delay the millisecond-scale dead-peer probe this
                # method exists to provide
                rs.failed(e, sleep=False)  # re-raises when budget exhausted
                # probe window: a dead peer fails each probe in
                # milliseconds instead of burning a full fetch timeout;
                # the window expiring just returns to the fetch attempt,
                # which re-fails and consumes the NEXT budget unit
                deadline = _time.monotonic() + max(self.fetch_timeout_s,
                                                   0.5)
                wait = 0.05
                while not self.probe_peer(worker_id):
                    if _time.monotonic() > deadline:
                        break
                    _time.sleep(wait)
                    wait = min(wait * 2, 1.0)
                else:
                    self.admit_worker(worker_id)
            except ShuffleFetchError as e:  # lint: recover-ok straggler/released-output failures route into the recovery retry loop (desync FAIL_QUERYs inside)
                rs.failed(e)           # desync/protocol re-raise inside

    def _fetch_attempt(self, worker_id: int, shuffle_id: int,
                       reduce_ids: List[int],
                       fingerprint: Optional[str] = None):
        """One fetch attempt with per-(peer, shuffle) completion caching:
        map completion is monotonic, so only the FIRST fetch per
        peer+shuffle pays the completion-poll round trips. Failures
        surface LOUDLY and with the right label: a desync keeps its type
        (wrong-pairing detection); connection-rooted failures become
        :class:`ShuffleWorkerLostError` naming the peer; protocol/
        straggler failures (released outputs, live-but-slow map phase)
        keep their ShuffleFetchError identity with the peer id prepended
        — a slow worker is not a dead worker."""
        client = self.client_for(worker_id)
        key = (worker_id, shuffle_id)
        with self._mu:
            complete = key in self._peer_complete
        try:
            if complete:
                return client.fetch(shuffle_id, reduce_ids,
                                    fingerprint=fingerprint)
            out = client.fetch_when_complete(
                shuffle_id, reduce_ids, timeout_s=self.fetch_timeout_s,
                fingerprint=fingerprint)
        except ShuffleDesyncError as e:  # lint: recover-ok relabeling boundary: prepends the peer id, keeps the type, never retries
            raise ShuffleDesyncError(
                f"worker {worker_id}: {e}") from e
        except ShuffleFetchError as e:  # lint: recover-ok relabeling boundary: maps connection-rooted failures to worker-lost for the recovery loop above
            if isinstance(e.__cause__, (ConnectionError, OSError)):
                raise ShuffleWorkerLostError(
                    worker_id,
                    f"worker {worker_id} lost while fetching shuffle "
                    f"{shuffle_id} partitions {reduce_ids}: {e}") from e
            raise ShuffleFetchError(
                f"worker {worker_id}: {e}") from e
        with self._mu:
            self._peer_complete.add(key)
        return out

    def release_shuffle(self, shuffle_id: int) -> None:
        """This worker finished ALL reads of ``shuffle_id``: ack locally
        and notify every peer (fire-and-forget). Each store frees the
        shuffle's outputs once the full quorum has acked."""
        self.store.add_release(shuffle_id, self.worker_id)
        for wid in sorted(self.peers):
            self.client_for(wid).send_release(shuffle_id, self.worker_id)

    def allreduce_bytes(self, tag: int, value: int) -> int:
        """Sum one integer across all workers through the shuffle store
        (the control-plane allreduce behind mesh-consistent runtime
        decisions — every worker computes the SAME total, so adaptive
        branches stay lockstep). ``tag`` keys a reserved negative shuffle
        namespace so control values never collide with data shuffles."""
        ctrl_sid = -abs(int(tag))
        batch = ColumnarBatch.from_pydict({"v": [int(value)]})
        self.store.register_batch(ctrl_sid, self.worker_id,
                                  batch.fetch_to_host())
        self.store.mark_complete(ctrl_sid)
        total = int(value)
        for wid in sorted(self.peers):
            for b in self.fetch_from_peer(wid, ctrl_sid, [wid]):
                total += int(b.rows()[0][0])
        self.release_shuffle(ctrl_sid)
        return total

    def shutdown(self) -> None:
        self.server.stop()
        with WorkerContext._current_mu:
            if WorkerContext.current is self:
                WorkerContext.current = None


def init_worker(worker_id: int, n_workers: int, port: int = 0,
                codec: str = "none", fetch_timeout_s: float = 60.0,
                durable_dir: Optional[str] = None) -> WorkerContext:
    """Bootstrap this process as shuffle worker ``worker_id`` (the
    RapidsExecutorPlugin.init analog). Returns the context; call
    ``set_peers`` once every worker's port is known."""
    ctx = WorkerContext(worker_id, n_workers, port, codec,
                        fetch_timeout_s=fetch_timeout_s,
                        durable_dir=durable_dir)
    with WorkerContext._current_mu:
        WorkerContext.current = ctx
    return ctx


class DistributedShuffle:
    """LocalShuffle-compatible exchange state backed by the worker's
    ShuffleStore + peer fetches (the caching writer/reader pair).

    ``fingerprint`` is the structural hash of the exchange's plan subtree:
    registered with the local store and sent on every peer fetch, so a
    worker whose query stream diverged (the lockstep shuffle-id contract)
    gets a LOUD :class:`ShuffleDesyncError` instead of silently joining
    mismatched shuffles."""

    def __init__(self, num_partitions: int, ctx: WorkerContext,
                 fingerprint: Optional[str] = None):
        self.num_partitions = num_partitions
        self.ctx = ctx
        self.shuffle_id = ctx.next_shuffle_id()
        self.fingerprint = fingerprint
        if fingerprint:
            # bind BEFORE any write: peers polling completion already get
            # fingerprint validation on their first metadata round trip
            ctx.store.set_fingerprint(self.shuffle_id, fingerprint)
            from ..analysis import divergence
            divergence.note_event(
                f"fingerprint:{self.shuffle_id}:{fingerprint[:16]}")
        self._wrote = False

    # -- map side ------------------------------------------------------------
    def write(self, partitioner, batch: ColumnarBatch) -> None:
        for p, piece in enumerate(partitioner.split(batch)):
            if piece.num_rows > 0:
                # ONE batched device->host transfer per slice; the store
                # serves host bytes (the reference's device-store residency
                # trades off against one host sync per array here)
                self.ctx.store.register_batch(self.shuffle_id, p,
                                              piece.fetch_to_host())
        self._wrote = True

    def write_deferred(self, window, partitioner,
                       batch: ColumnarBatch) -> None:
        """Pipelined map-side write (LocalShuffle.write_deferred's store
        twin): the fused device split dispatches now, the slice-sizing
        scalar parks in ``window``, and the host staging transfer runs at
        landing — so the per-batch sizing readbacks pack into O(1)
        resolves per map task while the store still serves host bytes."""
        deferred = partitioner.split_deferred(batch)
        if deferred is None:
            self.write(partitioner, batch)
            return
        counts, make_pieces = deferred

        def land(host_counts):
            for p, piece in enumerate(make_pieces(host_counts)):
                if piece.num_rows > 0:
                    self.ctx.store.register_batch(self.shuffle_id, p,
                                                  piece.fetch_to_host())
            self._wrote = True  # lint: unguarded-ok single-writer flag: each task's window lands on its own thread; True is the only value ever written

        window.push(land, counts)

    def finish_writes(self) -> None:
        self.ctx.store.mark_complete(self.shuffle_id)

    @property
    def durable(self) -> bool:
        """True when the worker's store write-throughs to the durable
        .npz tier (outputs survive a worker death for rejoin re-serve)."""
        return bool(self.ctx.store.durable_dir)

    def pin_outputs_to_disk(self) -> int:
        """No-op: the durable ShuffleStore persists each slice at
        registration (write-through), unlike the local spill-store pin."""
        return 0

    def reset_outputs(self) -> None:
        """Discard this worker's (partial) map outputs for a stage
        retry. Only legal BEFORE ``finish_writes``: peers poll the
        completion mark before fetching, so nothing was observed yet."""
        self.ctx.store.remove_shuffle(self.shuffle_id)
        if self.fingerprint:
            self.ctx.store.set_fingerprint(self.shuffle_id,
                                           self.fingerprint)
        self._wrote = False  # lint: unguarded-ok single-writer flag: reset runs on the one thread driving this exchange's map retry

    # -- reduce side ---------------------------------------------------------
    def read(self, p: int, schema: dt.Schema):
        """All slices of reduce partition ``p``: local short-circuit +
        remote fetches (RapidsCachingReader's local/remote block split)."""
        from ..plan.physical import concat_batches
        batches = list(self.ctx.store.local_batches(self.shuffle_id, p))
        for wid in sorted(self.ctx.peers):
            batches.extend(self.ctx.fetch_from_peer(
                wid, self.shuffle_id, [p], fingerprint=self.fingerprint))
        if batches:
            yield concat_batches(schema, batches)

    def read_all_partition_sources(self) -> List:
        """EVERY reduce partition's full data (local + all peers), not
        just the owned ones — the mesh-consistent runtime-broadcast path:
        when the global build size is under threshold, every worker
        materializes the complete build side from the already-shuffled
        slices. Returned as one generator per SOURCE (local store + each
        peer) so the caller's task runner drains sources concurrently
        instead of paying each peer's fetch latency serially."""
        def local():
            for p in range(self.num_partitions):
                yield from self.ctx.store.local_batches(self.shuffle_id, p)

        def from_peer(wid):
            yield from self.ctx.fetch_from_peer(
                wid, self.shuffle_id, list(range(self.num_partitions)),
                fingerprint=self.fingerprint)

        return [local()] + [from_peer(w) for w in sorted(self.ctx.peers)]

    def close_pending(self) -> None:
        """This worker is done READING this shuffle: ack the release
        quorum (local + every peer). Nothing is freed until ALL workers
        have acked, so a faster worker's cleanup can never strand slower
        peers still fetching its map outputs — but once the quorum
        completes, every store frees the outputs instead of holding them
        until ``WorkerContext.shutdown`` (the reference's driver-scoped
        active-shuffle lifecycle, ShuffleBufferCatalog.scala)."""
        self.ctx.release_shuffle(self.shuffle_id)
