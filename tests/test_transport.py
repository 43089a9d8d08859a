"""Shuffle transport tests: wire format, windowed chunk streaming, inflight
throttling, fault injection -> retry, and a real two-process fetch over
localhost TCP.

The mock rig mirrors the reference's RapidsShuffleTestHelper
(tests/.../shuffle/RapidsShuffleTestHelper.scala:26-187): an in-process
connection pair drives the REAL server handler and client protocol code,
with fault-injecting connection wrappers standing in for Mockito mocks.
"""

import socket
import threading

import numpy as np
import pytest

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import Column
from spark_rapids_tpu.shuffle import wire
from spark_rapids_tpu.shuffle.transport import (Connection, ShuffleClient,
                                                ShuffleFetchError,
                                                ShuffleServer, ShuffleStore,
                                                SocketConnection)


def _batch(n=100, base=0, with_strings=False):
    cols = {"a": np.arange(base, base + n, dtype=np.int64),
            "b": np.linspace(0, 1, n)}
    b = ColumnarBatch.from_pydict({k: list(v) for k, v in cols.items()})
    if with_strings:
        b = ColumnarBatch.from_pydict({
            "a": list(cols["a"]), "s": [f"row-{i}" for i in range(n)]})
    return b


def _rows(batch):
    return sorted(batch.rows())


# ---------------------------------------------------------------------------
# wire format
# ---------------------------------------------------------------------------

def test_frame_roundtrip():
    f = wire.encode_frame(wire.META_REQ, {"shuffle_id": 3,
                                          "reduce_ids": [0, 1]}, b"xyz")
    buf = [f]

    def read_exact(n):
        out, buf[0] = buf[0][:n], buf[0][n:]
        return out

    t, h, p = wire.FrameReader(read_exact).next_frame()
    assert t == wire.META_REQ and h["shuffle_id"] == 3 and p == b"xyz"


def test_chunk_ranges_windowing():
    assert wire.chunk_ranges(0, 10) == [(0, 0)]
    assert wire.chunk_ranges(10, 10) == [(0, 10)]
    assert wire.chunk_ranges(25, 10) == [(0, 10), (10, 10), (20, 5)]
    total = 1 << 20
    rs = wire.chunk_ranges(total, 4096)
    assert sum(ln for _o, ln in rs) == total
    assert all(ln <= 4096 for _o, ln in rs)


# ---------------------------------------------------------------------------
# mock rig: in-process loopback with fault injection
# ---------------------------------------------------------------------------

class CorruptingConnection(Connection):
    """Flips one byte of server->client traffic past ``after_bytes``, once
    per shared state dict (first attempt only)."""

    def __init__(self, inner, state, after_bytes=600):
        self.inner = inner
        self.state = state
        self.after = after_bytes
        self.seen = 0

    def send(self, data):
        self.inner.send(data)

    def read_exact(self, n):
        data = self.inner.read_exact(n)
        if not self.state.get("corrupted") and self.seen + n > self.after:
            self.state["corrupted"] = True
            i = max(0, self.after - self.seen)
            if i < len(data):
                data = data[:i] + bytes([data[i] ^ 0xFF]) + data[i + 1:]
        self.seen += n
        return data

    def close(self):
        self.inner.close()


class DroppingConnection(Connection):
    """Kills the connection after N bytes read (first attempt only)."""

    def __init__(self, inner, state, after_bytes=400):
        self.inner = inner
        self.state = state
        self.after = after_bytes
        self.seen = 0

    def send(self, data):
        self.inner.send(data)

    def read_exact(self, n):
        if not self.state.get("dropped") and self.seen + n > self.after:
            self.state["dropped"] = True
            self.inner.close()
            raise ConnectionError("injected drop")
        self.seen += n
        return self.inner.read_exact(n)

    def close(self):
        self.inner.close()


def loopback_client(server: ShuffleServer, wrap=None, **kw) -> ShuffleClient:
    """Client whose every connection is an in-process socketpair served by
    the REAL server handler on a daemon thread."""

    def connect():
        a, b = socket.socketpair()
        threading.Thread(target=server.handle_connection,
                         args=(SocketConnection(b),), daemon=True).start()
        conn = SocketConnection(a)
        return wrap(conn) if wrap else conn

    return ShuffleClient(connect, **kw)


def _server_with(batches, chunk_bytes=wire.DEFAULT_CHUNK_BYTES):
    store = ShuffleStore()
    for rid, b in batches:
        store.register_batch(7, rid, b)
    return ShuffleServer(store, chunk_bytes=chunk_bytes)


def test_fetch_single_partition():
    b = _batch(500)
    srv = _server_with([(0, b)])
    got = loopback_client(srv).fetch(7, [0])
    assert len(got) == 1
    assert _rows(got[0]) == _rows(b)


def test_fetch_multi_partition_multi_chunk():
    """Small chunk size forces many windows per buffer."""
    batches = [(r, _batch(2000, base=r * 10000)) for r in range(3)]
    srv = _server_with(batches, chunk_bytes=1024)
    client = loopback_client(srv)
    got = client.fetch(7, [0, 1, 2])
    assert len(got) == 3
    all_got = sorted(r for g in got for r in g.rows())
    all_exp = sorted(r for _rid, b in batches for r in b.rows())
    assert all_got == all_exp
    assert client.metrics["chunks"] > 3      # windowing actually chunked


def test_fetch_string_columns():
    b = _batch(64, with_strings=True)
    srv = _server_with([(0, b)])
    got = loopback_client(srv).fetch(7, [0])
    assert _rows(got[0]) == _rows(b)


def test_transport_totals_symmetric_send_and_fetch():
    """The server's send-side totals (bumped at send-window completion)
    must mirror the client's fetch-side totals: over a clean loopback
    fetch, bytes_sent == bytes_fetched and chunks_sent == chunks."""
    from spark_rapids_tpu.shuffle.transport import transport_totals
    before = transport_totals()
    batches = [(r, _batch(1500, base=r * 1000)) for r in range(2)]
    srv = _server_with(batches, chunk_bytes=2048)
    client = loopback_client(srv)
    got = client.fetch(7, [0, 1])
    assert len(got) == 2
    # the send side bumps its totals when a send window COMPLETES, on the
    # server's thread: the client can have its last bytes before that —
    # wait (bounded) for the server to catch up rather than race it
    import time
    deadline = time.monotonic() + 10.0
    while True:
        after = transport_totals()
        sent_b = after["bytes_sent"] - before["bytes_sent"]
        fetched_b = after["bytes_fetched"] - before["bytes_fetched"]
        if sent_b == fetched_b or time.monotonic() > deadline:
            break
        time.sleep(0.02)
    assert sent_b == fetched_b > 0, (sent_b, fetched_b)
    sent_c = after["chunks_sent"] - before["chunks_sent"]
    fetched_c = after["chunks"] - before["chunks"]
    assert sent_c == fetched_c > 2, (sent_c, fetched_c)


def test_inflight_throttling_tiny_window():
    """max_inflight_bytes below a single buffer still makes progress (the
    throttle always admits at least one), and many buffers complete."""
    batches = [(r, _batch(300, base=r * 1000)) for r in range(6)]
    srv = _server_with(batches, chunk_bytes=512)
    client = loopback_client(srv, max_inflight_bytes=1)
    got = client.fetch(7, list(range(6)))
    assert len(got) == 6
    all_got = sorted(r for g in got for r in g.rows())
    all_exp = sorted(r for _rid, b in batches for r in b.rows())
    assert all_got == all_exp


def test_corruption_detected_and_retried():
    b = _batch(1000)
    srv = _server_with([(0, b)], chunk_bytes=512)
    state = {}
    client = loopback_client(
        srv, wrap=lambda c: CorruptingConnection(c, state))
    got = client.fetch(7, [0])
    assert state["corrupted"], "fault was never injected"
    assert client.metrics["retries"] >= 1
    assert _rows(got[0]) == _rows(b)


def test_connection_drop_retried():
    b = _batch(1000)
    srv = _server_with([(0, b)], chunk_bytes=512)
    state = {}
    client = loopback_client(
        srv, wrap=lambda c: DroppingConnection(c, state))
    got = client.fetch(7, [0])
    assert state["dropped"]
    assert client.metrics["retries"] >= 1
    assert _rows(got[0]) == _rows(b)


def test_fetch_fails_after_exhausted_retries():
    class AlwaysDrop(Connection):
        def send(self, data):
            pass

        def read_exact(self, n):
            raise ConnectionError("dead peer")

    client = ShuffleClient(lambda: AlwaysDrop(), max_retries=2,
                           retry_backoff_s=0.001)
    with pytest.raises(ShuffleFetchError):
        client.fetch(1, [0])
    assert client.metrics["retries"] == 2


def test_unknown_buffer_errors():
    srv = _server_with([(0, _batch(10))])
    client = loopback_client(srv, max_retries=0)
    got = client.fetch(7, [5])       # empty partition: no buffers, no error
    assert got == []


# ---------------------------------------------------------------------------
# real two-process shuffle over localhost TCP
# ---------------------------------------------------------------------------

_CHILD_SERVER = r"""
import sys
sys.path.insert(0, {repo!r})
import numpy as np
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.shuffle.transport import ShuffleServer, ShuffleStore

store = ShuffleStore()
for rid in range(4):
    batch = ColumnarBatch.from_pydict({{
        "a": list(range(rid * 1000, rid * 1000 + 512)),
        "b": [float(i) * 0.5 for i in range(512)],
    }})
    store.register_batch(42, rid, batch)
srv = ShuffleServer(store, chunk_bytes=2048).start()
print(srv.port, flush=True)
import time
time.sleep(60)
"""


def test_two_process_shuffle_over_tcp(tmp_path):
    """A separate server process hosts real batches; this process fetches
    them over localhost TCP and validates every row."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # the child inserts the repo path itself; an inherited PYTHONPATH must
    # not bring in anything that re-targets the CPU-forced child
    env.pop("PYTHONPATH", None)
    proc = subprocess.Popen(
        [sys.executable, "-c", _CHILD_SERVER.format(repo=repo)],
        stdout=subprocess.PIPE, env=env, text=True)
    try:
        from procs import readline_bounded
        port = int(readline_bounded(proc).strip())
        client = ShuffleClient.for_address("127.0.0.1", port)
        got = client.fetch(42, [0, 1, 2, 3])
        assert len(got) == 4
        rows = sorted(r for g in got for r in g.rows())
        exp = sorted((rid * 1000 + i, float(i) * 0.5)
                     for rid in range(4) for i in range(512))
        assert rows == exp
        assert client.metrics["bytes_fetched"] > 0
    finally:
        proc.kill()
        proc.wait(timeout=30)


# -- native AddressSpaceAllocator + bounce arena (ref:
# AddressSpaceAllocator.scala:22, BounceBufferManager.scala:35) --------------

import pytest as _pytest


@_pytest.mark.parametrize("force_python", [False, True])
def test_address_space_allocator(force_python):
    from spark_rapids_tpu.exec.native_alloc import AddressSpaceAllocator
    a = AddressSpaceAllocator(1000, force_python=force_python)
    o1 = a.allocate(100)
    o2 = a.allocate(200)
    o3 = a.allocate(300)
    assert (o1, o2, o3) == (0, 100, 300)
    assert a.allocated_bytes == 600
    a.free(o2)                            # hole at [100, 300)
    assert a.free_block_count == 2
    o4 = a.allocate(150)                  # first-fit into the hole
    assert o4 == 100
    a.free(o4)
    a.free(o1)
    a.free(o3)
    assert a.allocated_bytes == 0
    # full coalescing: one free block spanning everything
    assert a.free_block_count == 1
    assert a.largest_free == 1000
    assert a.allocate(1000) == 0
    assert a.allocate(1) is None          # exhausted
    assert a.allocate(0) is None
    a.close()


def test_native_allocator_is_actually_native():
    """g++ is in this image: the C++ build must succeed and load."""
    from spark_rapids_tpu.exec.native_alloc import AddressSpaceAllocator
    a = AddressSpaceAllocator(64)
    assert a.native, "expected the C++ allocator to build via g++"
    a.close()


def test_free_unallocated_offset_raises():
    from spark_rapids_tpu.exec.native_alloc import AddressSpaceAllocator
    a = AddressSpaceAllocator(64)
    if a.native:
        with pytest.raises(ValueError):
            a.free(7)
    a.close()


def test_fetch_through_bounce_arena():
    """Client staging rides the arena: windows acquire and release across a
    multi-buffer fetch."""
    batches = [(r, _batch(1000, base=r * 5000)) for r in range(4)]
    srv = _server_with(batches, chunk_bytes=2048)
    client = loopback_client(srv)
    got = client.fetch(7, [0, 1, 2, 3])
    assert len(got) == 4
    assert client.bounce.allocator.allocated_bytes == 0   # all released
    all_got = sorted(r for g in got for r in g.rows())
    all_exp = sorted(r for _rid, b in batches for r in b.rows())
    assert all_got == all_exp


def test_exchange_stage_retry_on_lost_buffers():
    """Elastic recovery (RapidsShuffleIterator.scala:28,49): losing a reduce
    partition's buffers mid-read triggers one map-stage re-execution for the
    lost partitions and the query still returns correct results."""
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.functions import col
    from spark_rapids_tpu.shuffle.exchange import TpuShuffleExchangeExec

    s = TpuSession.builder.config(
        {"spark.rapids.tpu.sql.shuffle.partitions": "4",
         "spark.rapids.tpu.sql.adaptive.enabled": "false"}).getOrCreate()
    df = s.createDataFrame({"k": list(range(40)) * 5, "v": [1.0] * 200})
    agg = df.repartition(4, "k").groupBy("k").agg(F.sum("v").alias("sv"))

    orig_execute = TpuShuffleExchangeExec.execute
    state = {"sabotaged": False, "node": None}

    def sabotaging_execute(self):
        parts = orig_execute(self)
        sh = self._shuffle
        if not state["sabotaged"] and sh is not None:
            # lose partition 0's slices AFTER the map phase wrote them
            for sl in sh.slices[0]:
                sl.close()
            state["sabotaged"] = True
            state["node"] = self
        return parts

    TpuShuffleExchangeExec.execute = sabotaging_execute
    try:
        out = dict(agg.collect())
    finally:
        TpuShuffleExchangeExec.execute = orig_execute
    assert state["sabotaged"]
    assert out == {k: 5.0 for k in range(40)}
    assert state["node"].metrics.get("fetchFailedRetries", 0) >= 1
