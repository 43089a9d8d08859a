"""ColumnarBatch: a set of equal-capacity device columns + host-known row count.

Analog of Spark's ``ColumnarBatch`` carrying ``GpuColumnVector``s
(``GpuColumnVector.java:40-535``; batch<->Table converters). The TPU twist
(DESIGN.md §1): all columns share a bucketed capacity, rows beyond ``num_rows``
are zeroed+invalid padding, and kernels carry counts as device scalars until a
host boundary reads them.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from ..exec.tracing import host_site
from . import dtypes as dt
from .column import Column, Scalar, bucket


class ColumnarBatch:
    # ``origin``: the open catalog registration (SpillableColumnarBatch)
    # that already OWNS this batch's device arrays — set by the scan device
    # cache so downstream spillable-drain layers borrow that registration
    # instead of double-counting the same HBM under a second buffer id.
    # ``shared``: the arrays are owned by a live catalog entry that may
    # re-read them (set by BufferCatalog.acquire_batch) — such a batch
    # must NEVER have its buffers donated to a fused program
    # (exec/compile_cache donation gate; docs/compile.md)
    # ``params``: traced query-parameter scalars riding INSIDE a fused
    # program only (plan cache parameterization, docs/plan_cache.md):
    # ``from_flat_arrays`` attaches any arguments beyond the schema's
    # arity here, and ``ops.expressions.Parameter`` reads them by its
    # stamped trace position. Host-side batches always carry ().
    # ``donated``: non-None once a fused program consumed this batch's
    # arrays at donated positions (analysis/ledger.mark_donated stamps
    # the donation site) — the arrays are DEAD and any further read
    # through the funnels below diagnoses as use-after-donate instead of
    # surfacing jax's bare "Array has been deleted"
    # ``recording``: on a collect's RESULT batch, the query's recorders
    # (exec/tracing.QueryRecording) — the caller's fetch_to_host then
    # records its span under the same query id
    __slots__ = ("schema", "columns", "_num_rows", "origin", "shared",
                 "params", "donated", "recording")

    def __init__(self, schema: dt.Schema, columns: List[Column], num_rows: int):
        assert len(schema) == len(columns), "schema/column arity mismatch"
        caps = {c.capacity for c in columns}
        assert len(caps) <= 1, f"mixed capacities in batch: {caps}"
        self.schema = schema
        self.columns = columns
        self.origin = None
        self.shared = False
        self.params = ()
        self.donated = None
        self.recording = None
        if isinstance(num_rows, (int, np.integer)):
            self._num_rows = int(num_rows)
        else:
            # Traced tracer (batches built inside fused/jitted stages) or a
            # CONCRETE device scalar: the count stays device-resident until a
            # host consumer reads `num_rows` — so a streaming pipeline can
            # dispatch batch after batch without a blocking readback per
            # batch (the dominant engine cost on high-latency links).
            self._num_rows = num_rows

    # -- shape ---------------------------------------------------------------
    @property
    def num_rows(self):
        """Host row count. Lazily syncs a device-resident count on first
        access (cross host boundaries with ``resolve_counts`` to batch the
        readbacks); returns the tracer unchanged inside traced code."""
        nr = self._num_rows
        if isinstance(nr, int):
            return nr
        import jax
        if isinstance(nr, jax.core.Tracer):
            return nr
        nr = int(nr)                       # device->host sync
        self._num_rows = nr
        return nr

    @property
    def num_rows_raw(self):
        """The count in whatever form it currently has (int / device scalar /
        tracer) — no sync."""
        return self._num_rows

    @property
    def capacity(self) -> int:
        return self.columns[0].capacity if self.columns else bucket(self.num_rows)

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    def device_size_bytes(self) -> int:
        return sum(c.device_size_bytes() for c in self.columns)

    def row_mask(self) -> jnp.ndarray:
        """Bool[capacity] mask of live rows (True for rows < num_rows)."""
        return jnp.arange(self.capacity) < self.num_rows

    def row_mask_raw(self) -> jnp.ndarray:
        """row_mask built from the count in whatever form it has — never
        forces a device-resident count to host (sync-free hot paths)."""
        return jnp.arange(self.capacity) < self._num_rows

    def column(self, name_or_idx) -> Column:
        if isinstance(name_or_idx, int):
            return self.columns[name_or_idx]
        return self.columns[self.schema.index_of(name_or_idx)]

    def with_columns(self, schema: dt.Schema, columns: List[Column],
                     num_rows: Optional[int] = None) -> "ColumnarBatch":
        return ColumnarBatch(
            schema, columns,
            self._num_rows if num_rows is None else num_rows)

    # -- construction --------------------------------------------------------
    @staticmethod
    def from_pydict(data: Dict[str, Sequence[Any]],
                    schema: Optional[dt.Schema] = None,
                    capacity: Optional[int] = None) -> "ColumnarBatch":
        # build in schema order when one is given so fields and columns line up
        names = schema.names() if schema is not None else list(data.keys())
        n = len(next(iter(data.values()))) if data else 0
        cap = capacity or bucket(n)
        cols: List[Column] = []
        fields: List[dt.Field] = []
        for name in names:
            values = data[name]
            if len(values) != n:
                raise ValueError(
                    f"column {name!r} has {len(values)} rows, expected {n}")
            if schema is not None:
                dtype = schema[name].dtype
            else:
                dtype = _infer_dtype(values)
            if isinstance(values, np.ndarray) and dtype != dt.STRING:
                col = Column.from_numpy(values, dtype, capacity=cap)
            else:
                col = Column.from_pylist(list(values), dtype, capacity=cap)
            cols.append(col)
            fields.append(dt.Field(name, dtype))
        return ColumnarBatch(schema or dt.Schema(fields), cols, n)

    @staticmethod
    def from_arrow(table, capacity: Optional[int] = None) -> "ColumnarBatch":
        """pyarrow Table/RecordBatch -> device batch (the HostColumnarToGpu analog,
        ref HostColumnarToGpu.scala:30-235).

        All columns ride ONE staging-buffer upload + one cached unpack
        program (per-array transfer overhead would otherwise dominate scan
        streams on high-latency links — the bounce-buffer idea from the
        reference's shuffle, applied at the scan boundary)."""
        return ColumnarBatch.upload_prepped(
            ColumnarBatch.prep_from_arrow(table, capacity))

    @staticmethod
    def prep_from_arrow(table, capacity: Optional[int] = None):
        """Host-only half of ``from_arrow``: arrow -> padded numpy arrays,
        NO device work — safe to run on a prefetch thread before the task
        holds the semaphore or has reserved memory. Feed the result to
        ``upload_prepped`` (on the task thread, after admission)."""
        n = table.num_rows
        cap = capacity or bucket(n)
        fields = [dt.Field(table.schema.names[i],
                           dt.from_arrow(table.schema.types[i]))
                  for i in range(table.num_columns)]
        schema = dt.Schema(fields)
        # ARRAY<...> columns need the python-list path (device-building):
        # decide from the schema BEFORE converting anything twice
        if n == 0 or any(dt.is_array(f.dtype) or dt.is_map(f.dtype) or
                         dt.is_struct(f.dtype)
                         for f in fields):
            return ("fallback", schema, table, cap, n)
        hosts = [Column.host_from_arrow(table.column(i), capacity=cap)
                 for i in range(table.num_columns)]
        nbytes = sum(a.nbytes for _d, arrs in hosts for a in arrs)
        return ("packed", schema, hosts, cap, n, nbytes)

    @staticmethod
    def stage_prepped(prep, acquire=None):
        """Optional host half 2 of ``from_arrow``: PACK a 'packed' prep
        into its one contiguous staging buffer on the CALLING thread — a
        scan prefetch thread pays the memcpy so the task thread only
        uploads. ``acquire(nbytes)`` may return a writable window from a
        pinned bounce-buffer arena (exec/native_alloc); the returned prep
        then carries the window and ``upload_prepped`` force-copies to
        device so the caller can release the window right after upload.
        Non-'packed' preps pass through unchanged."""
        if prep[0] != "packed":
            return prep
        _tag, schema, hosts, cap, n, nbytes = prep
        spec, total, buf, window = _pack_staging(hosts, acquire)
        layout = [(dtype, len(arrs)) for dtype, arrs in hosts]
        return ("staged", schema, layout, spec, total, buf, window, cap, n,
                nbytes)

    @staticmethod
    def upload_prepped(prep) -> "ColumnarBatch":
        """Device half of ``from_arrow``: one packed staging upload + one
        cached unpack program (or the per-column fallback path)."""
        if prep[0] == "fallback":
            _tag, schema, table, cap, n = prep
            cols = [Column.from_arrow(table.column(i), capacity=cap)
                    for i in range(table.num_columns)]
            return ColumnarBatch(schema, cols, n)
        if prep[0] == "staged":
            (_tag, schema, layout, spec, total, buf, window, _cap, n,
             _nbytes) = prep
            # arena-windowed buffers force a device-owned copy: the window
            # is released (and reused) as soon as this returns
            cols = _unpack_staged(layout, spec, total, buf,
                                  force_copy=window is not None)
            return ColumnarBatch(schema, cols, n)
        _tag, schema, hosts, _cap, n, _nbytes = prep
        return ColumnarBatch(schema, _upload_packed(hosts), n)

    @staticmethod
    def prepped_size_bytes(prep) -> int:
        """Approximate device bytes ``upload_prepped`` will allocate (for
        admission before the upload)."""
        if prep[0] == "packed":
            return prep[5]
        if prep[0] == "staged":
            return prep[9]
        table = prep[2]
        return int(getattr(table, "nbytes", 0)) * 2

    @staticmethod
    def staged_window(prep):
        """The arena window a 'staged' prep holds (None otherwise) — the
        scan releases it after ``upload_prepped``."""
        return prep[6] if prep[0] == "staged" else None

    @staticmethod
    def empty(schema: dt.Schema, capacity: int = 128) -> "ColumnarBatch":
        cols = [Column.full_null(f.dtype, capacity) for f in schema]
        return ColumnarBatch(schema, cols, 0)

    # -- flat array form (fused stages / spill / wire share this layout) -----
    @host_site("flat_args")
    def flat_arrays(self) -> List[jnp.ndarray]:
        """All underlying arrays in schema order: [data, validity(, lengths)]
        per column — the jit-boundary form of a batch."""
        if self.donated is not None:
            from ..analysis import ledger
            ledger.check_batch_access(self)
        out: List[jnp.ndarray] = []
        for c in self.columns:
            out.extend(c.arrays())
        return out

    @staticmethod
    def from_flat_arrays(schema: dt.Schema, arrays: Sequence[jnp.ndarray],
                         num_rows) -> "ColumnarBatch":
        """Inverse of flat_arrays; num_rows may be a traced scalar inside
        fused stages. Per-column arity is a pure function of the dtype
        (column_arity), so arrays/structs reconstruct consistently at
        every site (fused stages, spill, shuffle wire)."""
        from .column import build_column
        cols: List[Column] = []
        i = 0
        for f in schema:
            c, i = build_column(f.dtype, arrays, i)
            cols.append(c)
        out = ColumnarBatch(schema, cols, num_rows)
        if i < len(arrays):
            # arguments beyond the schema's arity are appended query
            # parameters (traced 0-d scalars inside a fused program)
            out.params = tuple(arrays[i:])
        return out

    # -- host extraction -----------------------------------------------------
    def fetch_to_host(self) -> "ColumnarBatch":
        """Materialize every column on host in ONE batched transfer
        (GpuColumnarToRowExec's single device->host copy, vs a blocking
        round-trip per array — which dominates on high-latency links).
        Returns a batch whose columns are numpy-backed, sliced to
        ``num_rows``."""
        if self.donated is not None:
            from ..analysis import ledger
            ledger.check_batch_access(self)
        if not self.columns:
            self.num_rows                     # resolve the count
            return self
        if all(isinstance(c.data, np.ndarray) for c in self.columns):
            self.num_rows
            return self
        if self.recording is None:
            return self._fetch_device_columns()
        with self.recording.resumed("fetch_to_host"):
            return self._fetch_device_columns()

    def _fetch_device_columns(self) -> "ColumnarBatch":
        import jax
        if not isinstance(self.num_rows_raw, int) and \
                self.capacity <= (1 << 14):
            # device-resident count + small batch: ONE transfer carries the
            # count along with the data (a separate count readback would be
            # one more host sync)
            flat = self.flat_arrays() + [self.num_rows_raw]
            host = jax.device_get(flat)
            n = int(host[-1])
            self._num_rows = n
            return ColumnarBatch.from_flat_arrays(self.schema, host[:-1], n)
        n = self.num_rows                     # the one count sync
        # slice to a BUCKETED length before the transfer: padding beyond
        # bucket(n) stays on device, while the power-of-two slice shapes
        # keep the compile cache bounded (vs one slice program per n)
        from .column import ObjectColumn
        cap = self.capacity
        m = cap if cap <= (1 << 14) else min(bucket(max(n, 1)), cap)
        sliced: List[Any] = []
        obj_cols = {}
        for ci, c in enumerate(self.columns):
            if isinstance(c, ObjectColumn):   # host python payload already
                obj_cols[ci] = c
                continue
            for a in c.arrays():              # rows are always axis 0
                sliced.append(a if m == cap else a[:m])
        host = jax.device_get(sliced)         # one round trip for the batch
        if not obj_cols:
            return ColumnarBatch.from_flat_arrays(self.schema, host, n)
        from .column import build_column
        cols: List[Column] = []
        i = 0
        for ci, f in enumerate(self.schema):
            if ci in obj_cols:
                cols.append(obj_cols[ci])
                continue
            c, i = build_column(f.dtype, host, i)
            cols.append(c)
        return ColumnarBatch(self.schema, cols, n)

    def to_pydict(self) -> Dict[str, List[Any]]:
        host = self.fetch_to_host()
        return {f.name: c.to_pylist(host.num_rows)
                for f, c in zip(host.schema, host.columns)}

    def to_arrow(self):
        import pyarrow as pa
        host = self.fetch_to_host()
        arrays = [c.to_arrow(host.num_rows) for c in host.columns]
        return pa.table(arrays, names=host.schema.names())

    def to_pandas(self):
        return self.to_arrow().to_pandas()

    def rows(self) -> List[tuple]:
        """Materialize host rows (GpuColumnarToRowExec analog for small results)."""
        host = self.fetch_to_host()
        cols = [c.to_pylist(host.num_rows) for c in host.columns]
        return list(zip(*cols)) if cols else [()] * host.num_rows

    def __repr__(self):
        return (f"ColumnarBatch(rows={self.num_rows}, cap={self.capacity}, "
                f"schema={self.schema})")


_UNPACK_CACHE: Dict[tuple, Any] = {}

# registered with the JIT map-pressure relief valve: each cached unpack
# program pins a loaded executable (exec/compile_cache.jit_map_guard)
from ..exec.compile_cache import register_program_cache as _rpc  # noqa: E402
_rpc(_UNPACK_CACHE.clear)
del _rpc


def _staging_spec(metas) -> tuple:
    """Staging layout of arrays given as ``(numpy dtype, shape)`` pairs:
    ``(spec, total bytes)`` with one ``(dtype str, shape, offset, nbytes)``
    entry per array, segments 8-byte aligned. The layout is all the
    unpack program depends on (no data), so it can be compiled from
    shapes alone."""
    spec: List[tuple] = []
    pos = 0
    for npdt, shape in metas:
        npdt = np.dtype(npdt)
        nbytes = int(np.prod(shape, dtype=np.int64)) * npdt.itemsize
        spec.append((npdt.str, tuple(shape), pos, nbytes))
        pos += (nbytes + 7) & ~7          # 8-byte aligned segments
    return tuple(spec), pos


def _pack_staging(hosts, acquire=None):
    """Pack every column's padded host arrays into one aligned uint8
    staging buffer. ``acquire(nbytes)`` may hand back a writable window
    from the pinned bounce-buffer arena (exec/native_alloc) — the staging
    tier of the streaming scan; None (or an exhausted arena) falls back
    to a transient numpy buffer. Returns (spec, total, buf, window)."""
    arrays = [np.ascontiguousarray(a) for _dtype, arrs in hosts
              for a in arrs]
    spec, pos = _staging_spec((a.dtype, a.shape) for a in arrays)
    window = acquire(pos) if acquire is not None else None
    if window is not None:
        buf = np.frombuffer(window, dtype=np.uint8, count=pos)
        buf[:] = 0
    else:
        buf = np.zeros(pos, dtype=np.uint8)
    for a, (_d, _s, off, nbytes) in zip(arrays, spec):
        buf[off:off + nbytes] = a.view(np.uint8).ravel()
    return spec, pos, buf, window


def _unpack_program(spec, pos):
    """The cached jitted unpack (slice + bitcast) for one staging layout."""
    import jax
    import jax.lax as lax
    from ..exec import compile_cache as _cc
    from ..exec.tracing import shared_stage
    # donate the staging buffer: the unpack is its only consumer, and at
    # one full batch of bytes it is exactly the transient the HBM
    # watermark blames on scans (baked into the program -> keyed)
    donate = (0,) if _cc.donate_enabled() else ()
    key = (tuple(spec), pos, bool(donate))
    fn = _UNPACK_CACHE.get(key)
    if fn is None:
        if len(_UNPACK_CACHE) > 256:
            _UNPACK_CACHE.clear()

        @shared_stage("scan_unpack")
        def unpack(b):
            outs = []
            for dstr, shape, off, nbytes in spec:
                seg = lax.slice(b, (off,), (off + nbytes,))
                npdt = np.dtype(dstr)
                if npdt == np.uint8:
                    outs.append(seg.reshape(shape))
                elif npdt == np.bool_:
                    outs.append((seg != 0).reshape(shape))
                else:
                    flat = lax.bitcast_convert_type(
                        seg.reshape(-1, npdt.itemsize), jnp.dtype(npdt))
                    outs.append(flat.reshape(shape))
            return tuple(outs)
        # audited + persisted like every _fused_fn program (the naked-jit
        # rule: no compile escapes the recompile/compile-cache funnel)
        wrap = _cc.note_build(("scan_unpack",) + key, "scan_unpack")
        fn = _UNPACK_CACHE[key] = wrap(
            jax.jit(unpack, donate_argnums=donate))  # lint: naked-jit-ok scan unpack cache: audited via compile_cache.note_build above
    return fn


def _unpack_staged(layout, spec, pos, buf, force_copy: bool) -> List[Column]:
    """Upload one pre-packed staging buffer and carve the device columns
    out (the device half shared by _upload_packed and 'staged' preps).
    ``force_copy`` guarantees a device-OWNED buffer when ``buf`` views a
    reusable arena window (jnp.asarray may alias host memory on the CPU
    backend — an aliased window would be clobbered on reuse)."""
    fn = _unpack_program(spec, pos)
    src = jnp.array(buf) if force_copy else jnp.asarray(buf)
    dev = fn(src)                            # ONE upload + ONE dispatch
    cols: List[Column] = []
    i = 0
    for dtype, arity in layout:
        cols.append(Column(dtype, *dev[i:i + arity]))
        i += arity
    return cols


def _upload_packed(hosts) -> List[Column]:
    """Pack every column's padded host arrays into one aligned uint8
    staging buffer, upload it in a single transfer, and carve the device
    arrays back out with one cached jitted unpack (slice + bitcast)."""
    spec, pos, buf, _window = _pack_staging(hosts)
    layout = [(dtype, len(arrs)) for dtype, arrs in hosts]
    return _unpack_staged(layout, spec, pos, buf, force_copy=False)


def resolve_counts(batches: Sequence["ColumnarBatch"]) -> None:
    """Materialize every device-resident row count in ONE readback (a
    single host sync) instead of one blocking readback per batch — the
    cheap way to cross a host boundary after a lazily counted stream.
    The counts stack into one device array first: ``jax.device_get`` of a
    LIST reads each array's value in turn, which is k syncs, not one."""
    lazy = [(b, b.num_rows_raw) for b in batches
            if not isinstance(b.num_rows_raw, int)]
    if not lazy:
        return
    import jax
    packed = lazy[0][1] if len(lazy) == 1 else \
        jnp.stack([jnp.asarray(r, jnp.int32) for _, r in lazy])
    vals = np.atleast_1d(jax.device_get(packed))
    for (b, _), v in zip(lazy, vals):
        b._num_rows = int(v)


def _infer_dtype(values: Sequence[Any]) -> dt.DType:
    if isinstance(values, np.ndarray):
        return dt.of(values.dtype)
    for v in values:
        if v is None:
            continue
        if isinstance(v, bool):
            return dt.BOOL
        if isinstance(v, int):
            return dt.INT64
        if isinstance(v, float):
            return dt.FLOAT64
        if isinstance(v, (str, bytes)):
            return dt.STRING
        if isinstance(v, dict):
            # widen across EVERY dict in the column (a single-sample
            # inference mistyped e.g. int-then-float value columns and the
            # encoding silently truncated); empty-map-only columns default
            # to map<bigint,bigint>
            ks: list = []
            vs: list = []
            for d in values:
                if isinstance(d, dict):
                    ks.extend(d.keys())
                    vs.extend(x for x in d.values() if x is not None)
            if not ks:
                return dt.MAP(dt.INT64, dt.INT64)
            return dt.MAP(_widen_across(ks), _widen_across(vs or [0]))
        if isinstance(v, (list, tuple)) and v:
            elems = [x for lst in values
                     if isinstance(lst, (list, tuple))
                     for x in lst if x is not None]
            if any(isinstance(x, str) for x in elems):
                return dt.ARRAY_STRING
            return dt.ARRAY(_widen_across(elems or [0]))
    return dt.STRING


def _widen_across(values: Sequence[Any]) -> dt.DType:
    """Widest primitive dtype across observed python values: any float
    promotes int to float64, any string wins outright (mixed map columns
    must not truncate later-row values)."""
    out: dt.DType = None
    for v in values:
        t = (dt.BOOL if isinstance(v, bool) else
             dt.INT64 if isinstance(v, int) else
             dt.FLOAT64 if isinstance(v, float) else dt.STRING)
        if out is None or out == t:
            out = t
        elif {out, t} == {dt.INT64, dt.FLOAT64}:
            out = dt.FLOAT64
        else:
            out = dt.STRING if dt.STRING in (out, t) else dt.FLOAT64
    return out or dt.INT64
