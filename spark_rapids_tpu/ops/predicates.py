"""Predicate expressions: comparisons, AND/OR/NOT, IsNull/IsNaN, In/InSet.

Reference: ``org/apache/spark/sql/rapids/predicates.scala`` (629 LoC). Spark null
semantics: comparisons are NULL if either side is NULL (except ``<=>``); AND/OR are
Kleene three-valued. Spark's NaN semantics (unlike IEEE): NaN = NaN is TRUE and NaN
is greater than every other double — implemented via ``float_eq``/``float_lt``,
consistent with the total order kernels.py uses for sort/group.
"""

from __future__ import annotations

from typing import List

import jax.numpy as jnp

from ..columnar import dtypes as dt
from ..columnar.batch import ColumnarBatch
from ..columnar.column import Column, Scalar, device_scalar
from .expressions import (Expression, combine_validity, data_validity,
                          result_column)
from .strings_util import string_equal, string_compare


def float_eq(l, r):
    """Spark float equality: NaN = NaN is TRUE (unlike IEEE)."""
    return (l == r) | (jnp.isnan(l) & jnp.isnan(r))


def float_lt(l, r):
    """Spark float ordering: NaN is greater than every other value."""
    return (l < r) | (jnp.isnan(r) & ~jnp.isnan(l))


class BinaryComparison(Expression):
    symbol = "?"

    @property
    def dtype(self) -> dt.DType:
        return dt.BOOL

    @property
    def left(self):
        return self.children[0]

    @property
    def right(self):
        return self.children[1]

    def _cmp(self, l, r):
        raise NotImplementedError

    def _cmp_float(self, l, r):
        """Spark NaN semantics (NaN = NaN true, NaN greatest); see float_eq/float_lt."""
        raise NotImplementedError

    #: a string literal operand may ride as an argument of a fused program
    #: (ops/expressions.ordered_params): both string kernels take it traced
    compares_strings = True

    def _string_cmp(self, lv, rv, batch):
        cmp = string_compare(lv, rv, batch.capacity)
        return self._cmp(cmp, jnp.zeros((), jnp.int32))

    def eval(self, batch: ColumnarBatch):
        in_dtype = self.left.dtype
        lv = self.left.eval(batch)
        rv = self.right.eval(batch)
        if isinstance(lv, Scalar) and isinstance(rv, Scalar):
            if lv.is_null or rv.is_null:
                return Scalar(None, dt.BOOL)
            return Scalar(bool(self._py_cmp(lv, rv)), dt.BOOL)
        if in_dtype == dt.STRING:
            data = self._string_cmp(lv, rv, batch)
            lval = lv.validity if isinstance(lv, Column) else (not lv.is_null)
            rval = rv.validity if isinstance(rv, Column) else (not rv.is_null)
            validity = combine_validity(lval, rval)
        else:
            ld, lval = data_validity(lv, in_dtype)
            rd, rval = data_validity(rv, in_dtype)
            data = self._cmp_float(ld, rd) if in_dtype.is_floating \
                else self._cmp(ld, rd)
            validity = combine_validity(lval, rval)
        if validity is not True:
            data = data & jnp.broadcast_to(validity, (batch.capacity,))
        return result_column(dt.BOOL, data, validity, batch.capacity)

    def _py_cmp(self, lv: Scalar, rv: Scalar):
        """Pure-host scalar compare — Spark's NaN semantics (NaN = NaN is
        TRUE, NaN greater than everything) inlined so a literal-literal
        fold never touches the device (this path runs per batch)."""
        l, r = lv.value, rv.value
        if self.left.dtype == dt.STRING:
            mapping = {"=": l == r, "<": l < r, "<=": l <= r, ">": l > r,
                       ">=": l >= r}
            return mapping[self.symbol] if self.symbol in mapping else (
                l != r)
        if self.left.dtype.is_floating:
            import math
            import numpy as np
            # round to the COLUMN dtype first (float32 literals must
            # compare at float32, like the column path): f32->f64 widening
            # is exact, so the python compare then matches a _cmp at npdt
            npdt = np.dtype(self.left.dtype.numpy_dtype).type
            l, r = float(npdt(l)), float(npdt(r))
            ln, rn = math.isnan(l), math.isnan(r)
            if ln or rn:
                eq = ln and rn
                lt = rn and not ln
            else:
                eq, lt = (l == r), (l < r)
        else:
            eq, lt = (l == r), (l < r)
        return {"=": eq, "!=": not eq, "<": lt, "<=": lt or eq,
                ">": not (lt or eq), ">=": not lt}[self.symbol]

    def __repr__(self):
        return f"({self.children[0]!r} {self.symbol} {self.children[1]!r})"


class EqualTo(BinaryComparison):
    symbol = "="
    def _cmp(self, l, r): return l == r
    def _cmp_float(self, l, r): return float_eq(l, r)
    def _string_cmp(self, lv, rv, batch):
        return string_equal(lv, rv, batch.capacity)


class LessThan(BinaryComparison):
    symbol = "<"
    def _cmp(self, l, r): return l < r
    def _cmp_float(self, l, r): return float_lt(l, r)


class LessThanOrEqual(BinaryComparison):
    symbol = "<="
    def _cmp(self, l, r): return l <= r
    def _cmp_float(self, l, r): return float_lt(l, r) | float_eq(l, r)


class GreaterThan(BinaryComparison):
    symbol = ">"
    def _cmp(self, l, r): return l > r
    def _cmp_float(self, l, r): return float_lt(r, l)


class GreaterThanOrEqual(BinaryComparison):
    symbol = ">="
    def _cmp(self, l, r): return l >= r
    def _cmp_float(self, l, r): return float_lt(r, l) | float_eq(l, r)


class NotEqual(BinaryComparison):
    """Spark has Not(EqualTo) but a direct != is convenient for the CPU engine too."""
    symbol = "!="
    def _cmp(self, l, r): return l != r
    def _cmp_float(self, l, r): return ~float_eq(l, r)
    def _string_cmp(self, lv, rv, batch):
        return ~string_equal(lv, rv, batch.capacity)


class EqualNullSafe(Expression):
    """`<=>`: never NULL; NULL <=> NULL is true (GpuEqualNullSafe)."""
    symbol = "<=>"
    compares_strings = True          # as BinaryComparison

    @property
    def dtype(self):
        return dt.BOOL

    @property
    def nullable(self):
        return False

    def eval(self, batch: ColumnarBatch):
        lv = self.children[0].eval(batch)
        rv = self.children[1].eval(batch)
        in_dtype = self.children[0].dtype
        if in_dtype == dt.STRING:
            eq = string_equal(lv, rv, batch.capacity)
        else:
            ld, lval = data_validity(lv, in_dtype)
            rd, rval = data_validity(rv, in_dtype)
            eq = float_eq(ld, rd) if in_dtype.is_floating else (ld == rd)
        lval = lv.validity if isinstance(lv, Column) else (not lv.is_null)
        rval = rv.validity if isinstance(rv, Column) else (not rv.is_null)
        lval = jnp.broadcast_to(jnp.asarray(lval), (batch.capacity,))
        rval = jnp.broadcast_to(jnp.asarray(rval), (batch.capacity,))
        data = jnp.where(lval & rval, jnp.broadcast_to(eq, (batch.capacity,)),
                         lval == rval)
        # padding rows are invalid==invalid -> would read True; mask to live rows.
        # validity is the live-row mask (never NULL on live rows) so the padding
        # invariant (invalid + zeroed) holds for downstream consumers like Not.
        live = batch.row_mask()
        data = data & live
        return result_column(dt.BOOL, data, live, batch.capacity)


class And(Expression):
    """Kleene AND (GpuAnd): F & NULL = F; T & NULL = NULL."""
    symbol = "AND"

    @property
    def dtype(self):
        return dt.BOOL

    def eval(self, batch: ColumnarBatch):
        lv = self.children[0].eval(batch)
        rv = self.children[1].eval(batch)
        ld, lval = data_validity(lv, dt.BOOL)
        rd, rval = data_validity(rv, dt.BOOL)
        lval = jnp.broadcast_to(jnp.asarray(lval), (batch.capacity,))
        rval = jnp.broadcast_to(jnp.asarray(rval), (batch.capacity,))
        l_false = lval & ~ld
        r_false = rval & ~rd
        validity = l_false | r_false | (lval & rval)
        data = jnp.broadcast_to(ld & rd, (batch.capacity,)) & validity
        return result_column(dt.BOOL, data, validity, batch.capacity)

    def __repr__(self):
        return f"({self.children[0]!r} AND {self.children[1]!r})"


class Or(Expression):
    """Kleene OR (GpuOr): T | NULL = T; F | NULL = NULL."""
    symbol = "OR"

    @property
    def dtype(self):
        return dt.BOOL

    def eval(self, batch: ColumnarBatch):
        lv = self.children[0].eval(batch)
        rv = self.children[1].eval(batch)
        ld, lval = data_validity(lv, dt.BOOL)
        rd, rval = data_validity(rv, dt.BOOL)
        lval = jnp.broadcast_to(jnp.asarray(lval), (batch.capacity,))
        rval = jnp.broadcast_to(jnp.asarray(rval), (batch.capacity,))
        l_true = lval & ld
        r_true = rval & rd
        validity = l_true | r_true | (lval & rval)
        data = jnp.broadcast_to(l_true | r_true, (batch.capacity,))
        return result_column(dt.BOOL, data, validity, batch.capacity)

    def __repr__(self):
        return f"({self.children[0]!r} OR {self.children[1]!r})"


class Not(Expression):
    """GpuNot."""
    @property
    def dtype(self):
        return dt.BOOL

    def eval(self, batch: ColumnarBatch):
        v = self.children[0].eval(batch)
        if isinstance(v, Scalar):
            return Scalar(None if v.is_null else (not v.value), dt.BOOL)
        return Column(dt.BOOL, (~v.data) & v.validity, v.validity)

    def __repr__(self):
        return f"(NOT {self.children[0]!r})"


class IsNull(Expression):
    """GpuIsNull — never NULL itself."""
    @property
    def dtype(self):
        return dt.BOOL

    @property
    def nullable(self):
        return False

    def eval(self, batch: ColumnarBatch):
        v = self.children[0].eval(batch)
        if isinstance(v, Scalar):
            return Scalar(v.is_null, dt.BOOL)
        # padding rows are invalid; mask to live rows so they don't read as "null
        # rows", and keep validity=live so padding stays invalid + zeroed
        live = batch.row_mask()
        data = (~v.validity) & live
        return result_column(dt.BOOL, data, live, batch.capacity)


class IsNotNull(Expression):
    """GpuIsNotNull."""
    @property
    def dtype(self):
        return dt.BOOL

    @property
    def nullable(self):
        return False

    def eval(self, batch: ColumnarBatch):
        v = self.children[0].eval(batch)
        if isinstance(v, Scalar):
            return Scalar(not v.is_null, dt.BOOL)
        live = batch.row_mask()
        return result_column(dt.BOOL, v.validity & live, live, batch.capacity)


class IsNaN(Expression):
    """GpuIsNan."""
    @property
    def dtype(self):
        return dt.BOOL

    @property
    def nullable(self):
        return False

    def eval(self, batch: ColumnarBatch):
        v = self.children[0].eval(batch)
        if isinstance(v, Scalar):
            import math
            return Scalar(bool(v.value is not None and math.isnan(v.value)), dt.BOOL)
        live = batch.row_mask()
        return result_column(dt.BOOL, jnp.isnan(v.data) & v.validity, live,
                             batch.capacity)


class In(Expression):
    """GpuInSet/GpuIn with literal list: NULL semantics — if no match and the list
    contains NULL, result is NULL; NULL input gives NULL."""

    def __init__(self, child: Expression, values: List):
        super().__init__(child)
        self.values = values

    @property
    def dtype(self):
        return dt.BOOL

    def eval(self, batch: ColumnarBatch):
        child = self.children[0]
        v = child.eval(batch)
        has_null = any(x is None for x in self.values)
        concrete = [x for x in self.values if x is not None]
        if child.dtype == dt.STRING:
            match = jnp.zeros(batch.capacity, dtype=jnp.bool_)
            for s in concrete:
                match = match | string_equal(v, Scalar(s, dt.STRING), batch.capacity)
        else:
            vd, vval = data_validity(v, child.dtype)
            match = jnp.zeros(batch.capacity, dtype=jnp.bool_)
            for x in concrete:
                match = match | jnp.broadcast_to(
                    vd == device_scalar(x, child.dtype.numpy_dtype),
                    (batch.capacity,))
        vval = v.validity if isinstance(v, Column) else jnp.broadcast_to(
            jnp.asarray(not v.is_null), (batch.capacity,))
        validity = vval & (match | (not has_null))
        data = match & validity
        return result_column(dt.BOOL, data, validity, batch.capacity)

    def __repr__(self):
        return f"({self.children[0]!r} IN {self.values!r})"
