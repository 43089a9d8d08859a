"""Device columnar containers: the ``GpuColumnVector`` / ``ColumnarBatch`` analog.

Reference: ``GpuColumnVector.java:40-535`` (Spark ColumnVector over a cuDF column) and
``SURVEY.md`` §2.7. TPU-first differences (DESIGN.md §1, §4):

* every column lives in a *bucketed capacity* (next power of two, min 128) so XLA's
  compile cache stays bounded; the batch tracks the logical ``num_rows``
* NULLs are a dense bool validity vector (True = valid), not a bitmask
* strings are fixed-width padded byte matrices ``uint8[cap, byte_cap]`` plus an
  ``int32[cap]`` length vector — vectorizable on the VPU — instead of Arrow offsets
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import dtypes as dt

MIN_CAPACITY = 128
MIN_STRING_WIDTH = 8


def _bits_from_values(vals, dtype: dt.DType) -> np.ndarray:
    """Logical values -> int64 bitpatterns for the MAP layout: integral /
    bool / date / timestamp store the int64 VALUE; floats store the
    float64 bitpattern (f32 widens exactly)."""
    if dtype.is_floating:
        return np.asarray(vals, np.float64).view(np.int64)
    return np.asarray([int(v) for v in vals], np.int64)


def _values_from_bits(bits: np.ndarray, dtype: dt.DType) -> np.ndarray:
    if dtype.is_floating:
        return bits.view(np.float64).astype(dtype.numpy_dtype)
    return bits.astype(dtype.numpy_dtype)


def bucket(n: int, minimum: int = MIN_CAPACITY) -> int:
    """Smallest power of two >= max(n, minimum). Bounds XLA recompiles per DESIGN.md §1."""
    n = max(int(n), minimum)
    return 1 << (n - 1).bit_length()


def string_width_bucket(max_len: int) -> int:
    return bucket(max_len, MIN_STRING_WIDTH)


# ---------------------------------------------------------------------------
# ONE route for a host float64 onto the device
# ---------------------------------------------------------------------------
# A TPU holds a float64 as a PAIR of float32. A value that arrives as a
# float64 (an argument, a device_put, a constant of the program) is split on
# the host or by the compiler; one that arrives as bytes and is bitcast on
# the device, as every scanned column is (batch._unpack_program), is split
# by the program, and the two splits round the low word differently: equal
# float64 values then compare unequal. So every float64 the HOST hands the
# device goes as its eight bytes and becomes a float64 THERE: columns through
# the staging buffer's unpack, scalars through the two functions below.

def float64_words(value) -> np.ndarray:
    """The eight bytes of ``value`` as a float64, ``uint8[8]``."""
    return np.asarray(value, dtype=np.float64).reshape(1).view(np.uint8)  # lint: host-sync-ok boxes a python scalar host-side; no device value involved


def float64_from_words(words):
    """``uint8[..., 8]`` on the device -> ``float64[...]``, the bitcast the
    scan unpack makes its columns by."""
    return jax.lax.bitcast_convert_type(words, jnp.float64)


def device_scalar(value, npdt):
    """A host scalar as a 0-d device value of ``npdt``; a value that is
    already on the device or traced passes through. The float64's bytes sit
    behind a barrier: folded at compile time they would be split as a
    constant is, not as the columns are."""
    if np.dtype(npdt) != np.float64 or isinstance(value, jax.Array):
        return jnp.asarray(value, dtype=npdt)
    return float64_from_words(
        jax.lax.optimization_barrier(jnp.asarray(float64_words(value))))


def device_column(dtype: dt.DType, arrays) -> "Column":
    """Host arrays of one column -> a device Column; float64 data takes the
    scan's route (one staging buffer, the cached unpack program)."""
    if any(a.dtype == np.float64 for a in arrays):
        from .batch import _upload_packed
        return _upload_packed([(dtype, list(arrays))])[0]
    return Column(dtype, *[jnp.asarray(a) for a in arrays])


@dataclass(frozen=True)
class Scalar:
    """Device-free scalar value paired with its SQL type (cuDF ``Scalar`` analog,
    used by ``GpuLiteral``/``GpuScalar`` — literals.scala in the reference)."""
    value: Any                      # python value; None = null scalar
    dtype: dt.DType

    @property
    def is_null(self) -> bool:
        return self.value is None


class Column:
    """A device column: storage arrays sized to a capacity >= the batch's num_rows.

    numeric/bool/date/timestamp: ``data[cap]`` with the type's numpy dtype
    string:                      ``data[cap, byte_cap] uint8`` + ``lengths[cap] int32``
    All carry ``validity[cap] bool`` (True = valid). Padding rows must be invalid and
    their data zeroed (zeroed padding keeps kernels free of NaN/garbage hazards).
    """

    __slots__ = ("dtype", "data", "validity", "lengths", "elem_validity")

    def __init__(self, dtype: dt.DType, data, validity, lengths=None,
                 elem_validity=None):
        self.dtype = dtype
        self.data = data
        self.validity = validity
        self.lengths = lengths
        # ARRAY<primitive> element nullability: bool[cap, W] aligned with
        # the element matrix (True = element valid). MANDATORY for device
        # arrays so the flat-array protocol's arity is a function of the
        # dtype (column_arity), never of the instance.
        self.elem_validity = elem_validity
        if dtype.var_width:
            assert lengths is not None and data.ndim == 2, \
                "var-width (string/array) column needs lengths + 2D data"
            if dt.is_array(dtype) and dtype.numpy_dtype is not None:
                assert elem_validity is not None, \
                    "device ARRAY column needs an element-validity matrix"
        else:
            assert data.ndim == 1, f"fixed-width column must be 1D, got {data.ndim}D"

    # -- capacity / shape ----------------------------------------------------
    @property
    def capacity(self) -> int:
        return int(self.data.shape[0])

    @property
    def byte_width(self) -> int:
        """Padded width for var-width columns; storage width for fixed types."""
        if self.dtype.var_width:
            return int(self.data.shape[1])
        return self.dtype.byte_width

    def device_size_bytes(self) -> int:
        total = self.data.size * self.data.dtype.itemsize
        total += self.validity.size * 1
        if self.lengths is not None:
            total += self.lengths.size * 4
        if self.elem_validity is not None:
            total += self.elem_validity.size * 1
        return int(total)

    def arrays(self) -> List[jnp.ndarray]:
        out = [self.data, self.validity]
        if self.lengths is not None:
            out.append(self.lengths)
        if self.elem_validity is not None:
            out.append(self.elem_validity)
        return out

    def with_arrays(self, data, validity, lengths=None) -> "Column":
        return Column(self.dtype, data, validity,
                      lengths if lengths is not None else
                      (self.lengths if self.dtype.var_width else None),
                      self.elem_validity)

    # -- construction --------------------------------------------------------
    @staticmethod
    def from_numpy(values: np.ndarray, dtype: Optional[dt.DType] = None,
                   validity: Optional[np.ndarray] = None,
                   capacity: Optional[int] = None) -> "Column":
        values = np.asarray(values)
        if dtype is None:
            dtype = dt.of(values.dtype)
        n = len(values)
        cap = capacity or bucket(n)
        storage = np.zeros(cap, dtype=dtype.numpy_dtype)
        valid = np.zeros(cap, dtype=np.bool_)
        v = values.astype(dtype.numpy_dtype, copy=False)
        if validity is None:
            validity = np.ones(n, dtype=np.bool_)
            if dtype.is_floating:
                # NaN stays valid (SQL NaN != NULL); nothing to mask here.
                pass
        storage[:n] = np.where(validity, v, np.zeros((), dtype=dtype.numpy_dtype)) \
            if len(v) else v
        valid[:n] = validity
        return device_column(dtype, (storage, valid))

    @staticmethod
    def from_pylist(values: Sequence[Any], dtype: dt.DType,
                    capacity: Optional[int] = None,
                    width: Optional[int] = None) -> "Column":
        n = len(values)
        if dt.is_struct(dtype):
            if all(_device_capable(t) for _, t in dtype.fields):
                return StructColumn.from_pylist_struct(values, dtype,
                                                       capacity)
            # a field type with no device layout (e.g. map<string,_>):
            # host objects carry the values across the collect boundary
            return ObjectColumn(dtype, values, capacity)
        if (dt.is_map(dtype) or dt.is_array(dtype)) and \
                dtype.numpy_dtype is None:
            # CPU-engine-only complex dtype (e.g. map<string,_>): these are
            # planner-gated off the device, so the column only exists to
            # carry CpuFallback results across the collect boundary — keep
            # the python objects instead of the device bitpattern layout
            # (which would misencode/crash on string keys)
            return ObjectColumn(dtype, values, capacity)
        valid_np = np.array([v is not None for v in values], dtype=np.bool_)
        if dt.is_map(dtype):
            # MAP<K,V>: int64[cap, 3W] INTERLEAVED bitpattern matrix
            # ([k, v, value-valid] per entry lane — pad-safe, see
            # dtypes.MAP) + entry counts; duplicate keys keep the LAST
            # entry (spark.sql.mapKeyDedupPolicy=LAST_WIN)
            dicts = [dict(v) if v is not None else None for v in values]
            max_len = max((len(d) for d in dicts if d is not None), default=0)
            w = width or bucket(max_len, 4)
            cap = capacity or bucket(n)
            mat = np.zeros((cap, 3 * w), dtype=np.int64)
            lens = np.zeros(cap, dtype=np.int32)
            for i, d in enumerate(dicts):
                if d is None:
                    continue
                ks = list(d.keys())
                vs = list(d.values())
                ln = len(ks)
                vv = np.array([v is not None for v in vs], np.bool_)
                mat[i, 0:3 * ln:3] = _bits_from_values(ks, dtype.key)
                mat[i, 1:3 * ln + 1:3] = np.where(
                    vv, _bits_from_values(
                        [v if v is not None else 0 for v in vs],
                        dtype.element), 0)
                mat[i, 2:3 * ln + 2:3] = vv.astype(np.int64)
                lens[i] = ln
            valid_full = np.zeros(cap, np.bool_)
            valid_full[:n] = valid_np
            return Column(dtype, jnp.asarray(mat), jnp.asarray(valid_full),
                          jnp.asarray(lens))
        if dt.is_array(dtype):
            # ARRAY<primitive>: padded element matrix + per-row lengths +
            # element-validity matrix (NULL elements round-trip)
            max_len = max((len(v) for v in values if v is not None),
                          default=0)
            w = width or bucket(max_len, 4)
            cap = capacity or bucket(n)
            mat = np.zeros((cap, w), dtype=dtype.numpy_dtype)
            lens = np.zeros(cap, dtype=np.int32)
            evalid = np.zeros((cap, w), dtype=np.bool_)
            for i, v in enumerate(values):
                if v is None:
                    continue
                ev = np.array([e is not None for e in v], np.bool_)
                mat[i, :len(v)] = np.asarray(
                    [e if e is not None else 0 for e in v],
                    dtype=dtype.numpy_dtype)
                evalid[i, :len(v)] = ev
                lens[i] = len(v)
            valid_full = np.zeros(cap, np.bool_)
            valid_full[:n] = valid_np
            return Column(dtype, jnp.asarray(mat), jnp.asarray(valid_full),
                          jnp.asarray(lens), jnp.asarray(evalid))
        if dtype == dt.STRING:
            encoded = [v.encode("utf-8") if isinstance(v, str)
                       else (v if isinstance(v, bytes) else b"") for v in values]
            max_len = max((len(b) for b in encoded), default=0)
            w = width or string_width_bucket(max_len)
            if max_len > w:
                raise ValueError(f"string of {max_len} bytes exceeds width {w}")
            cap = capacity or bucket(n)
            mat = np.zeros((cap, w), dtype=np.uint8)
            lens = np.zeros(cap, dtype=np.int32)
            for i, b in enumerate(encoded):
                mat[i, :len(b)] = np.frombuffer(b, dtype=np.uint8)
                lens[i] = len(b)
            lens[:n] = np.where(valid_np, lens[:n], 0)
            return Column(dt.STRING, jnp.asarray(mat), jnp.asarray(valid_np if cap == n else
                          np.concatenate([valid_np, np.zeros(cap - n, np.bool_)])),
                          jnp.asarray(lens))
        vals = np.array([v if v is not None else 0 for v in values],
                        dtype=dtype.numpy_dtype)
        return Column.from_numpy(vals, dtype, valid_np, capacity)

    @staticmethod
    def from_arrow(arr, capacity: Optional[int] = None,
                   width: Optional[int] = None) -> "Column":
        """Build a device column from a pyarrow Array/ChunkedArray (host boundary)."""
        host = Column.host_from_arrow(arr, capacity, width)
        if host is None:            # ARRAY/MAP<...>: python-object path
            import pyarrow as pa
            if isinstance(arr, pa.ChunkedArray):
                arr = arr.combine_chunks()
            dtype = dt.from_arrow(arr.type)
            vals = arr.to_pylist()
            if dt.is_map(dtype):
                # pyarrow maps materialize as lists of (k, v) tuples
                vals = [dict(v) if v is not None else None for v in vals]
            return Column.from_pylist(vals, dtype, capacity, width)
        dtype, arrays = host
        return device_column(dtype, arrays)

    @staticmethod
    def host_from_arrow(arr, capacity: Optional[int] = None,
                        width: Optional[int] = None):
        """Arrow -> padded host numpy arrays [data, validity(, lengths)]
        WITHOUT the device upload, so a batch-level caller can pack every
        column into one staging buffer and upload once (per-array transfer
        overhead dominates scan streams on high-latency links). Returns
        (dtype, arrays) or None for types that need the pylist path."""
        import pyarrow as pa
        if isinstance(arr, pa.ChunkedArray):
            arr = arr.combine_chunks()
        dtype = dt.from_arrow(arr.type)
        if dtype == dt.STRING:
            # vectorized offsets+values -> padded byte matrix: the python
            # per-row loop in from_pylist costs ~0.7s per 131k-row batch,
            # which dominated scan-heavy queries end to end
            import pyarrow as pa
            sa = arr
            n = len(sa)
            off_t = np.int64 if pa.types.is_large_string(sa.type) else np.int32
            off_buf = sa.buffers()[1]
            offs = (np.frombuffer(off_buf, dtype=off_t)
                    [sa.offset:sa.offset + n + 1].astype(np.int64)
                    if off_buf is not None else np.zeros(n + 1, np.int64))
            data_buf = sa.buffers()[2]
            vals = (np.frombuffer(data_buf, dtype=np.uint8)
                    if data_buf is not None else np.zeros(0, np.uint8))
            lens = (offs[1:] - offs[:-1]).astype(np.int32)
            valid = np.ones(n, np.bool_) if sa.null_count == 0 else \
                np.asarray(sa.is_valid())
            lens = np.where(valid, lens, 0).astype(np.int32)
            max_len = int(lens.max()) if n else 0
            w = width or string_width_bucket(max_len)
            if max_len > w:
                raise ValueError(
                    f"string of {max_len} bytes exceeds width {w}")
            cap = capacity or bucket(n)
            mat = np.zeros((cap, w), dtype=np.uint8)
            if n:
                mask = np.arange(w)[None, :] < lens[:, None]
                src = offs[:-1, None] + np.arange(w)[None, :]
                mat[:n][mask] = vals[src[mask]]
            lens_full = np.zeros(cap, np.int32)
            lens_full[:n] = lens
            valid_full = np.zeros(cap, np.bool_)
            valid_full[:n] = valid
            return (dt.STRING, [mat, valid_full, lens_full])
        if dt.is_array(dtype) or dt.is_map(dtype) or dt.is_struct(dtype):
            return None
        np_valid = np.ones(len(arr), dtype=np.bool_) if arr.null_count == 0 else \
            np.asarray(arr.is_valid())
        if dtype == dt.TIMESTAMP:
            values = np.asarray(arr.cast(pa.timestamp("us")).view(pa.int64())
                                .fill_null(0)).astype(np.int64)
        elif dtype == dt.DATE:
            values = np.asarray(arr.view(pa.int32()).fill_null(0)).astype(np.int32)
        elif dtype == dt.BOOL:
            values = np.asarray(arr.fill_null(False))
        else:
            values = np.asarray(arr.fill_null(0)).astype(dtype.numpy_dtype)
        n = len(values)
        cap = capacity or bucket(n)
        storage = np.zeros(cap, dtype=dtype.numpy_dtype)
        valid = np.zeros(cap, dtype=np.bool_)
        storage[:n] = np.where(np_valid, values,
                               np.zeros((), dtype=dtype.numpy_dtype)) \
            if n else values
        valid[:n] = np_valid
        return (dtype, [storage, valid])

    @staticmethod
    def full_null(dtype: dt.DType, capacity: int, width: int = MIN_STRING_WIDTH) -> "Column":
        valid = jnp.zeros(capacity, dtype=jnp.bool_)
        if dt.is_struct(dtype):
            return StructColumn(
                dtype, [Column.full_null(t, capacity) for _, t in
                        dtype.fields], valid)
        if dtype == dt.STRING:
            return Column(dtype, jnp.zeros((capacity, width), dtype=jnp.uint8), valid,
                          jnp.zeros(capacity, dtype=jnp.int32))
        if dt.is_array(dtype) and dtype.numpy_dtype is not None:
            return Column(dtype,
                          jnp.zeros((capacity, width), dtype=dtype.numpy_dtype),
                          valid, jnp.zeros(capacity, dtype=jnp.int32),
                          jnp.zeros((capacity, width), dtype=jnp.bool_))
        if dtype.var_width:              # MAP bitpattern matrix
            return Column(dtype,
                          jnp.zeros((capacity, width),
                                    dtype=dtype.numpy_dtype),
                          valid, jnp.zeros(capacity, dtype=jnp.int32))
        return Column(dtype, jnp.zeros(capacity, dtype=dtype.numpy_dtype), valid)

    @staticmethod
    def from_scalar(scalar: Scalar, num_rows: int, capacity: Optional[int] = None) -> "Column":
        cap = capacity or bucket(num_rows)
        if scalar.is_null:
            return Column.full_null(scalar.dtype, cap)
        valid = jnp.arange(cap) < num_rows
        if scalar.dtype == dt.STRING:
            # trace-safe broadcast: the byte row is STATIC (the literal),
            # only the live mask depends on num_rows — a pylist build
            # would do `[value] * tracer` and break whole-stage fusion
            b = scalar.value.encode("utf-8") if isinstance(
                scalar.value, str) else bytes(scalar.value)
            w = string_width_bucket(len(b))
            row = np.zeros(w, dtype=np.uint8)
            row[:len(b)] = np.frombuffer(b, dtype=np.uint8)
            data = jnp.where(valid[:, None],
                             jnp.broadcast_to(jnp.asarray(row), (cap, w)),
                             jnp.zeros((), jnp.uint8))
            lengths = jnp.where(valid, jnp.int32(len(b)), 0)
            return Column(dt.STRING, data, valid, lengths)
        npdt = scalar.dtype.numpy_dtype
        data = jnp.where(valid, device_scalar(scalar.value, npdt),
                         jnp.zeros((), dtype=npdt))
        return Column(scalar.dtype, data, valid)

    # -- host extraction -----------------------------------------------------
    def to_numpy(self, num_rows: int) -> np.ndarray:
        """Host values for the first num_rows rows; NULLs as masked array fill."""
        if self.dtype == dt.STRING:
            raise TypeError("use to_pylist for string columns")
        return np.asarray(self.data[:num_rows])

    def to_pylist(self, num_rows: int) -> List[Any]:
        valid = np.asarray(self.validity[:num_rows])
        if dt.is_map(self.dtype):
            mat = np.asarray(self.data[:num_rows])
            lens = np.asarray(self.lengths[:num_rows])
            kt, vt = self.dtype.key, self.dtype.element
            kconv = (float if kt.is_floating else
                     bool if kt == dt.BOOL else int)
            vconv = (float if vt.is_floating else
                     bool if vt == dt.BOOL else int)
            out: List[Any] = []
            for i in range(num_rows):
                if not valid[i]:
                    out.append(None)
                    continue
                ln = int(lens[i])
                ks = _values_from_bits(mat[i, 0:3 * ln:3], kt)
                vs = _values_from_bits(mat[i, 1:3 * ln + 1:3], vt)
                vv = mat[i, 2:3 * ln + 2:3] != 0
                out.append({kconv(k): (vconv(v) if ok else None)
                            for k, v, ok in zip(ks, vs, vv)})
            return out
        if dt.is_array(self.dtype):
            mat = np.asarray(self.data[:num_rows])
            lens = np.asarray(self.lengths[:num_rows])
            ev = (np.asarray(self.elem_validity[:num_rows])
                  if self.elem_validity is not None else None)
            elem = self.dtype.element
            conv = (int if elem.is_integral or elem in (dt.DATE, dt.TIMESTAMP)
                    else bool if elem == dt.BOOL else float)
            return [[conv(x) if ev is None or ev[i, j] else None
                     for j, x in enumerate(mat[i, :lens[i]])]
                    if valid[i] else None
                    for i in range(num_rows)]
        if self.dtype == dt.STRING:
            mat = np.asarray(self.data[:num_rows])
            lens = np.asarray(self.lengths[:num_rows])
            out: List[Any] = []
            for i in range(num_rows):
                if not valid[i]:
                    out.append(None)
                else:
                    out.append(bytes(mat[i, :lens[i]]).decode("utf-8", errors="replace"))
            return out
        data = np.asarray(self.data[:num_rows])
        if self.dtype == dt.BOOL:
            return [bool(v) if ok else None for v, ok in zip(data, valid)]
        if self.dtype.is_integral or self.dtype in (dt.DATE, dt.TIMESTAMP):
            return [int(v) if ok else None for v, ok in zip(data, valid)]
        return [float(v) if ok else None for v, ok in zip(data, valid)]

    def to_arrow(self, num_rows: int):
        import pyarrow as pa
        valid = np.asarray(self.validity[:num_rows])
        if self.dtype == dt.STRING or dt.is_array(self.dtype) or \
                dt.is_map(self.dtype):
            return pa.array(self.to_pylist(num_rows),
                            type=dt.to_arrow(self.dtype))
        data = np.asarray(self.data[:num_rows])
        mask = ~valid  # pyarrow mask semantics: True = null
        if self.dtype == dt.DATE:
            return pa.array(data, type=pa.date32(), mask=mask)
        if self.dtype == dt.TIMESTAMP:
            return pa.array(data, type=pa.timestamp("us"), mask=mask)
        return pa.array(data, type=dt.to_arrow(self.dtype), mask=mask)

    def __repr__(self):
        extra = f", width={self.data.shape[1]}" if self.dtype.var_width else ""
        return f"Column({self.dtype}, cap={self.capacity}{extra})"


class ObjectColumn(Column):
    """Host-only python-object column for CPU-engine-only dtypes (maps with
    string keys/values, array<string>). The planner's type gate keeps these
    off the device (overrides type check, like the reference's unsupported
    nested types in GpuColumnVector.java's matrix), so an ObjectColumn only
    carries CpuFallback results across the host collect boundary — any
    device op touching it is a planner bug and raises."""

    __slots__ = ("values",)

    def __init__(self, dtype: dt.DType, values: Sequence[Any],
                 capacity: Optional[int] = None):
        n = len(values)
        cap = capacity or bucket(n)
        vals = list(values) + [None] * (cap - n)
        if dt.is_map(dtype):
            # normalize list-of-pairs (arrow's map rendering) to dicts
            vals = [dict(v) if isinstance(v, (list, tuple)) else v
                    for v in vals]
        self.dtype = dtype
        self.values = vals
        self.data = np.empty((cap, 0), dtype=np.uint8)
        self.validity = np.array([v is not None for v in vals], np.bool_)
        self.lengths = np.zeros(cap, np.int32)
        self.elem_validity = None

    @property
    def capacity(self) -> int:
        return len(self.values)

    def device_size_bytes(self) -> int:
        return 0

    def arrays(self) -> List[jnp.ndarray]:
        raise TypeError(
            f"{self.dtype} columns are host-only (CPU-engine dtype); "
            "no device arrays exist")

    def with_arrays(self, data, validity, lengths=None) -> "Column":
        raise TypeError(f"{self.dtype} columns are host-only")

    def to_pylist(self, num_rows: int) -> List[Any]:
        return self.values[:num_rows]

    def to_arrow(self, num_rows: int):
        import pyarrow as pa
        vals = self.values[:num_rows]
        if dt.is_map(self.dtype):
            vals = [None if v is None else list(v.items()) for v in vals]
        return pa.array(vals, type=dt.to_arrow(self.dtype))

    def __repr__(self):
        return f"ObjectColumn({self.dtype}, cap={self.capacity})"


class StructColumn(Column):
    """Device STRUCT layout: struct-of-columns + a struct-level validity
    vector (the GpuColumnVector struct form, GpuColumnVector.java:40-535).
    Scans still SHRED field accesses into flat columns (the fast path);
    this layout is for WHOLE-struct values flowing through joins, sorts,
    exchanges, and collects without the host ObjectColumn crawl: the
    row-reorder kernels (gather/concat) recurse into the children, and
    the flat-array protocol flattens [validity, *children...] with an
    arity that is a pure function of the dtype (column_arity)."""

    def __init__(self, dtype: dt.DType, children: List[Column], validity):
        self.dtype = dtype
        self.children = children
        self.validity = validity
        self.data = None
        self.lengths = None
        self.elem_validity = None

    @staticmethod
    def from_pylist_struct(values: Sequence[Any], dtype: dt.DType,
                           capacity: Optional[int] = None) -> "StructColumn":
        n = len(values)
        cap = capacity or bucket(n)
        valid = np.zeros(cap, np.bool_)
        valid[:n] = [v is not None for v in values]
        children = []
        for fname, ftype in dtype.fields:
            fvals = [None if v is None else
                     (v.get(fname) if isinstance(v, dict)
                      else getattr(v, fname)) for v in values]
            children.append(Column.from_pylist(fvals, ftype, capacity=cap))
        return StructColumn(dtype, children, jnp.asarray(valid))

    @property
    def capacity(self) -> int:
        return int(self.validity.shape[0])

    @property
    def byte_width(self) -> int:
        return sum(c.byte_width for c in self.children)

    def device_size_bytes(self) -> int:
        return int(self.validity.size) + \
            sum(c.device_size_bytes() for c in self.children)

    def arrays(self) -> List[jnp.ndarray]:
        out = [self.validity]
        for c in self.children:
            out.extend(c.arrays())
        return out

    def with_arrays(self, data, validity, lengths=None) -> "Column":
        raise TypeError("use build_column to reconstruct struct columns")

    def to_pylist(self, num_rows: int) -> List[Any]:
        valid = np.asarray(self.validity[:num_rows])
        kids = [c.to_pylist(num_rows) for c in self.children]
        names = [n for n, _ in self.dtype.fields]
        return [dict(zip(names, vals)) if ok else None
                for ok, vals in zip(valid, zip(*kids))] if kids else \
            [None] * num_rows

    def to_arrow(self, num_rows: int):
        import pyarrow as pa
        return pa.array(self.to_pylist(num_rows),
                        type=dt.to_arrow(self.dtype))

    def __repr__(self):
        return f"StructColumn({self.dtype}, cap={self.capacity})"


def _device_capable(t: dt.DType) -> bool:
    """Types with a device layout (vs host-only ObjectColumn types)."""
    if dt.is_struct(t):
        return all(_device_capable(ft) for _, ft in t.fields)
    if dt.is_array(t) or dt.is_map(t):
        return t.numpy_dtype is not None
    return True


def column_arity(t: dt.DType) -> int:
    """Number of flat storage arrays a device column of type ``t``
    contributes — a pure function of the dtype, shared by every
    reconstruction site (fused stages, spill, shuffle wire)."""
    if dt.is_struct(t):
        return 1 + sum(column_arity(ft) for _, ft in t.fields)
    if dt.is_array(t) and t.numpy_dtype is not None:
        return 4                      # data, validity, lengths, elem_valid
    if t.var_width:
        return 3                      # data, validity, lengths
    return 2                          # data, validity


def build_column(t: dt.DType, arrays: Sequence[Any], i: int = 0):
    """(column, next_index): rebuild one column from the flat-array form
    starting at ``arrays[i]`` (inverse of ``Column.arrays()``)."""
    if dt.is_struct(t):
        validity = arrays[i]
        i += 1
        children = []
        for _, ft in t.fields:
            c, i = build_column(ft, arrays, i)
            children.append(c)
        return StructColumn(t, children, validity), i
    if dt.is_array(t) and t.numpy_dtype is not None:
        return Column(t, arrays[i], arrays[i + 1], arrays[i + 2],
                      arrays[i + 3]), i + 4
    if t.var_width:
        return Column(t, arrays[i], arrays[i + 1], arrays[i + 2]), i + 3
    return Column(t, arrays[i], arrays[i + 1]), i + 2
