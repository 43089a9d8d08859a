"""Hash and misc nondeterministic expressions.

Reference: ``HashFunctions.scala`` (Md5, Murmur3Hash), ``GpuRand``,
``GpuMonotonicallyIncreasingID``, ``GpuSparkPartitionID`` (SURVEY.md §2.3).

Murmur3 here is bit-compatible with Spark's ``Murmur3Hash`` (x86_32 variant,
seed 42, Spark's special handling: ints/dates hash as int32, longs/timestamps
as int64, floats widened like Spark's hashLong/hashInt normalization, strings
hash their UTF-8 bytes). Bit-compat matters because hash partitioning must
place rows identically to Spark for golden-compare shuffles.
"""

from __future__ import annotations

from typing import List, Sequence

import jax.numpy as jnp
import numpy as np

from ..columnar import dtypes as dt
from ..columnar.batch import ColumnarBatch
from ..columnar.column import Column, Scalar
from .expressions import Expression, result_column

_C1 = jnp.uint32(0xCC9E2D51)
_C2 = jnp.uint32(0x1B873593)


def _rotl(x, r):
    return (x << r) | (x >> (32 - r))


def _mix_k1(k1):
    k1 = (k1 * _C1).astype(jnp.uint32)
    k1 = _rotl(k1, 15)
    return (k1 * _C2).astype(jnp.uint32)


def _mix_h1(h1, k1):
    h1 = h1 ^ k1
    h1 = _rotl(h1, 13)
    return (h1 * jnp.uint32(5) + jnp.uint32(0xE6546B64)).astype(jnp.uint32)


def _fmix(h1, length):
    h1 = h1 ^ jnp.uint32(length) if isinstance(length, int) else h1 ^ length.astype(jnp.uint32)
    h1 = h1 ^ (h1 >> 16)
    h1 = (h1 * jnp.uint32(0x85EBCA6B)).astype(jnp.uint32)
    h1 = h1 ^ (h1 >> 13)
    h1 = (h1 * jnp.uint32(0xC2B2AE35)).astype(jnp.uint32)
    return h1 ^ (h1 >> 16)


def _hash_int32(data: jnp.ndarray, seed: jnp.ndarray) -> jnp.ndarray:
    """Spark Murmur3_x86_32.hashInt: one 4-byte block."""
    k1 = _mix_k1(data.astype(jnp.uint32))
    h1 = _mix_h1(seed, k1)
    return _fmix(h1, 4)


def _hash_int64(data: jnp.ndarray, seed: jnp.ndarray) -> jnp.ndarray:
    """Spark hashLong: low word block then high word block."""
    low = data.astype(jnp.uint64).astype(jnp.uint32)
    high = (data.astype(jnp.uint64) >> 32).astype(jnp.uint32)
    h1 = _mix_h1(seed, _mix_k1(low))
    h1 = _mix_h1(h1, _mix_k1(high))
    return _fmix(h1, 8)


def _hash_bytes(data: jnp.ndarray, lengths: jnp.ndarray,
                seed: jnp.ndarray) -> jnp.ndarray:
    """Spark hashUnsafeBytes over UTF-8 strings: 4-byte little-endian blocks,
    then Spark's *signed-byte* tail mixing (each trailing byte hashed as an int
    block — matches UnsafeHashedRelation's hashUnsafeBytes, which Spark uses
    for string columns in Murmur3Hash)."""
    n, w = data.shape
    nblocks = w // 4
    h1 = jnp.broadcast_to(seed, (n,)).astype(jnp.uint32)
    # full 4-byte blocks while block fits within length
    for b in range(nblocks):
        chunk = data[:, b * 4:(b + 1) * 4].astype(jnp.uint32)
        k1 = chunk[:, 0] | (chunk[:, 1] << 8) | (chunk[:, 2] << 16) | (chunk[:, 3] << 24)
        in_block = lengths >= (b + 1) * 4
        h1 = jnp.where(in_block, _mix_h1(h1, _mix_k1(k1)), h1)
    # tail: Spark hashes each remaining byte as a SIGNED int block
    for i in range(4):
        # byte index = (len//4)*4 + i for rows where that's < len
        base = (lengths // 4) * 4
        idx = base + i
        take = idx < lengths
        byte = jnp.take_along_axis(
            data, jnp.clip(idx, 0, w - 1)[:, None].astype(jnp.int32), axis=1)[:, 0]
        sbyte = byte.astype(jnp.int8).astype(jnp.int32).astype(jnp.uint32)
        h1 = jnp.where(take, _mix_h1(h1, _mix_k1(sbyte)), h1)
    return _fmix(h1, lengths)


_POW2_STEPS = (512, 256, 128, 64, 32, 16, 8, 4, 2, 1)


def _float64_bits(x: jnp.ndarray) -> jnp.ndarray:
    """``Double.doubleToLongBits`` by arithmetic: int64 IEEE-754 bits of a
    float64 array, NaN canonical. There is deliberately NO f64->int
    bitcast here — the TPU compiler's 64-bit rewrite does not implement
    one (only the int->f64 direction), so a bitcast in a hash-partitioned
    key would make the whole exchange program uncompilable on the chip.
    Every scaling is by a power of two, so the result is bit-exact
    wherever f64 arithmetic is IEEE (tests pin it against numpy's view).
    Subnormals and -0.0 read as +0.0: XLA flushes subnormals in every
    comparison the engine makes, and Spark's hash normalizes -0.0."""
    a0 = jnp.abs(x)
    tiny = a0 < 2.0 ** -1022
    a = jnp.where(jnp.isnan(x) | jnp.isinf(x) | tiny, 1.0, a0)
    e = jnp.zeros(x.shape, jnp.int32)
    for k in _POW2_STEPS:                 # a >= 2: scale down into [1, 2)
        big = a >= 2.0 ** k
        a = jnp.where(big, a * 2.0 ** -k, a)
        e = e + jnp.where(big, k, 0)
    for k in _POW2_STEPS:                 # a < 1: scale up into [1, 2)
        small = a < 2.0 ** (1 - k)
        a = jnp.where(small, a * 2.0 ** k, a)
        e = e - jnp.where(small, k, 0)
    bits = ((e + 1023).astype(jnp.int64) << 52) | \
        ((a - 1.0) * 2.0 ** 52).astype(jnp.int64)
    bits = jnp.where(tiny, jnp.int64(0), bits)
    bits = jnp.where(jnp.isinf(x), jnp.int64(0x7FF0_0000_0000_0000), bits)
    bits = jnp.where(x < 0, bits | jnp.int64(-(1 << 63)), bits)
    return jnp.where(jnp.isnan(x), jnp.int64(0x7FF8_0000_0000_0000), bits)


def murmur3_column(col: Column, seed: jnp.ndarray) -> jnp.ndarray:
    """int32 hash per row; NULL rows leave the seed unchanged (Spark semantics:
    null columns don't contribute to the hash)."""
    if col.dtype == dt.STRING:
        h = _hash_bytes(col.data, col.lengths, seed)
    elif col.dtype in (dt.INT64, dt.TIMESTAMP):
        h = _hash_int64(col.data, seed)
    elif col.dtype == dt.FLOAT64:
        # Spark: normalize -0.0 to 0.0, hash as long bits
        norm = jnp.where(col.data == 0.0, 0.0, col.data)
        h = _hash_int64(_float64_bits(norm), seed)
    elif col.dtype == dt.FLOAT32:
        norm = jnp.where(col.data == 0.0, jnp.float32(0.0), col.data)
        import jax
        bits = jax.lax.bitcast_convert_type(norm, jnp.int32)
        h = _hash_int32(bits, seed)
    elif col.dtype == dt.BOOL:
        h = _hash_int32(col.data.astype(jnp.int32), seed)
    else:  # int8/16/32, date — all hash as int blocks
        h = _hash_int32(col.data.astype(jnp.int32), seed)
    return jnp.where(col.validity, h, seed).astype(jnp.uint32)


def murmur3_batch(cols: Sequence[Column], capacity: int,
                  seed: int = 42) -> jnp.ndarray:
    """Row hash across columns, chained like Spark's Murmur3Hash(children, 42):
    the previous column's hash is the next column's seed. Returns int32[cap]."""
    h = jnp.full(capacity, seed, dtype=jnp.uint32)
    for c in cols:
        h = murmur3_column(c, h)
    return h.astype(jnp.int32)


class Murmur3Hash(Expression):
    """hash(...) expression (Spark Murmur3Hash, seed 42)."""

    def __init__(self, *children: Expression, seed: int = 42):
        super().__init__(*children)
        self.seed = seed

    @property
    def dtype(self):
        return dt.INT32

    @property
    def nullable(self):
        return False

    def eval(self, batch: ColumnarBatch):
        from .expressions import materialize
        cols = [materialize(c.eval(batch), batch) for c in self.children]
        data = murmur3_batch(cols, batch.capacity, self.seed)
        live = batch.row_mask()
        return result_column(dt.INT32, jnp.where(live, data, 0), live,
                             batch.capacity)


class Md5(Expression):
    """md5(string) — host computed (no TPU digest units; the reference runs this
    on GPU via cuDF but the op is cold-path)."""
    fusable = False

    @property
    def dtype(self):
        return dt.STRING

    def eval(self, batch: ColumnarBatch):
        import hashlib
        v = self.children[0].eval(batch)
        if isinstance(v, Scalar):
            if v.is_null:
                return Scalar(None, dt.STRING)
            return Scalar(hashlib.md5(str(v.value).encode()).hexdigest(), dt.STRING)
        vals = v.to_pylist(batch.num_rows)
        out = [None if x is None else hashlib.md5(x.encode()).hexdigest()
               for x in vals]
        return Column.from_pylist(out, dt.STRING, capacity=batch.capacity)


class Rand(Expression):
    """rand(seed): per-row uniform [0,1) via threefry — deterministic given
    (seed, partition, batch ordinal) like GpuRand's per-partition XORShift
    stream. The batch ordinal is folded into the PRNG key so successive
    batches in a partition draw fresh values instead of replaying the
    sequence; the exec advances it via ``advance()`` after each batch."""
    side_effect_free = False

    def __init__(self, seed: int = 0):
        super().__init__()
        self.seed = seed
        self.partition_index = 0
        self._batch_ordinal = 0

    @property
    def dtype(self):
        return dt.FLOAT64

    @property
    def nullable(self):
        return False

    def advance(self, n_rows: int) -> None:
        self._batch_ordinal += 1

    def eval(self, batch: ColumnarBatch):
        import jax
        key = jax.random.fold_in(
            jax.random.key(self.seed + self.partition_index),
            self._batch_ordinal)
        data = jax.random.uniform(key, (batch.capacity,), dtype=jnp.float64)
        live = batch.row_mask()
        return result_column(dt.FLOAT64, jnp.where(live, data, 0.0), live,
                             batch.capacity)


class MonotonicallyIncreasingID(Expression):
    """(partition_id << 33) + row index (GpuMonotonicallyIncreasingID)."""
    side_effect_free = False

    def __init__(self):
        super().__init__()
        self.partition_index = 0
        self.row_offset = 0

    @property
    def dtype(self):
        return dt.INT64

    @property
    def nullable(self):
        return False

    def advance(self, n_rows: int) -> None:
        self.row_offset += n_rows

    def eval(self, batch: ColumnarBatch):
        base = (self.partition_index << 33) + self.row_offset
        data = jnp.arange(batch.capacity, dtype=jnp.int64) + base
        live = batch.row_mask()
        return result_column(dt.INT64, jnp.where(live, data, 0), live,
                             batch.capacity)


class SparkPartitionID(Expression):
    """spark_partition_id() (GpuSparkPartitionID)."""
    side_effect_free = False

    def __init__(self):
        super().__init__()
        self.partition_index = 0

    @property
    def dtype(self):
        return dt.INT32

    @property
    def nullable(self):
        return False

    def eval(self, batch: ColumnarBatch):
        live = batch.row_mask()
        data = jnp.where(live, jnp.int32(self.partition_index), 0)
        return result_column(dt.INT32, data, live, batch.capacity)


class InputFileName(Expression):
    """input_file_name() — populated by the scan exec via thread-local context
    (GpuInputFileBlock analog). Thread-local: partitions drain on concurrent
    task threads, each reading a different file."""
    side_effect_free = False

    _tls = __import__("threading").local()

    @property
    def dtype(self):
        return dt.STRING

    @property
    def nullable(self):
        return False

    @classmethod
    def set_current(cls, path: str) -> None:
        cls._tls.current_file = path

    def eval(self, batch: ColumnarBatch):
        return Scalar(getattr(self._tls, "current_file", ""), dt.STRING)
