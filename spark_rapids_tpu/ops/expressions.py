"""Expression tree base classes: the ``GpuExpression`` analog.

Reference: ``GpuExpressions.scala:63-109`` (columnarEval contract: each expression
evaluates a ColumnarBatch to a GpuColumnVector or Scalar) plus ``literals.scala``,
``GpuBoundAttribute.scala``, ``namedExpressions.scala``.

TPU-first difference (DESIGN.md §2): ``eval`` is pure jax.numpy over the batch's
device arrays, so an entire expression tree traces into ONE XLA computation instead
of one cuDF kernel launch per node. Expressions that need host work (e.g. number->
string formatting) set ``fusable = False`` and run eagerly between fused stages.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence, Union

import jax.numpy as jnp
import numpy as np

from ..columnar import dtypes as dt
from ..columnar.batch import ColumnarBatch
from ..exec.tracing import host_site
from ..columnar.column import (Column, Scalar, device_scalar,
                               float64_from_words, float64_words)

ColumnOrScalar = Union[Column, Scalar]


def is_traced(value: Any) -> bool:
    """True when ``value`` is a jax tracer — a rebindable :class:`Parameter`
    riding an active fused trace. Scalar folds must keep such values
    in-graph (jnp): any numpy/python conversion would concretize the tracer
    and abort the whole fused program back to eager."""
    import jax

    return isinstance(value, jax.core.Tracer)


class Expression:
    """Base expression. Subclasses set ``children`` and implement ``dtype``/``eval``."""

    fusable: bool = True          # False => needs host execution, breaks stage fusion
    side_effect_free: bool = True

    def __init__(self, *children: "Expression"):
        self.children: List[Expression] = list(children)

    @property
    def dtype(self) -> dt.DType:
        raise NotImplementedError

    @property
    def nullable(self) -> bool:
        return any(c.nullable for c in self.children) if self.children else True

    def eval(self, batch: ColumnarBatch) -> ColumnOrScalar:
        raise NotImplementedError

    # -- tree utilities ------------------------------------------------------
    def transform(self, fn) -> "Expression":
        """Bottom-up transform returning a new tree (Catalyst transformUp analog)."""
        new_children = [c.transform(fn) for c in self.children]
        node = self
        if new_children != self.children:
            node = self.with_children(new_children)
        replaced = fn(node)
        return node if replaced is None else replaced

    def transform_down(self, fn) -> "Expression":
        """Top-down transform (Catalyst transformDown analog): ``fn`` sees
        each ORIGINAL node before its children are rewritten, and a replaced
        node's subtree is not descended into. Required whenever ``fn`` matches
        nodes by identity — a bottom-up pass copies any node whose children
        changed, so identity checks would silently miss it."""
        replaced = fn(self)
        if replaced is not None:
            return replaced
        new_children = [c.transform_down(fn) for c in self.children]
        if new_children != self.children:
            return self.with_children(new_children)
        return self

    def with_children(self, children: List["Expression"]) -> "Expression":
        import copy
        node = copy.copy(node_src := self)
        node.children = children
        # subclasses keeping aliases of children must override
        node._rebind_child_aliases()
        return node

    def _rebind_child_aliases(self) -> None:
        pass

    def collect(self, pred) -> List["Expression"]:
        out = [self] if pred(self) else []
        for c in self.children:
            out.extend(c.collect(pred))
        return out

    def tree_fusable(self) -> bool:
        return self.fusable and all(c.tree_fusable() for c in self.children)

    @property
    def name(self) -> str:
        return type(self).__name__

    def sql_name(self) -> str:
        return type(self).__name__.lower()

    def __repr__(self):
        args = ", ".join(repr(c) for c in self.children)
        return f"{type(self).__name__}({args})"


# ---------------------------------------------------------------------------
# Leaves
# ---------------------------------------------------------------------------

class Literal(Expression):
    """GpuLiteral analog (literals.scala)."""

    #: position among a fused program's appended arguments, for a string
    #: literal that rides as one (:func:`ordered_params`); ``None``: a
    #: constant of the program
    trace_pos: Optional[int] = None

    def __init__(self, value: Any, dtype: Optional[dt.DType] = None):
        super().__init__()
        if dtype is None:
            if isinstance(value, bool):
                dtype = dt.BOOL
            elif isinstance(value, int):
                dtype = dt.INT64  # will narrow via implicit cast if needed
            elif isinstance(value, float):
                dtype = dt.FLOAT64
            elif isinstance(value, str):
                dtype = dt.STRING
            elif value is None:
                dtype = dt.NULLTYPE
            else:
                raise TypeError(f"cannot infer literal type for {value!r}")
        self._dtype = dtype
        self.value = value

    @property
    def dtype(self) -> dt.DType:
        return self._dtype

    @property
    def nullable(self) -> bool:
        return self.value is None

    def eval(self, batch: ColumnarBatch) -> Scalar:
        if self.trace_pos is not None and batch is not None:
            pv = getattr(batch, "params", ())
            if self.trace_pos < len(pv):
                # inside a fused trace: the bytes are a traced argument
                return Scalar(pv[self.trace_pos], self._dtype)
        return Scalar(self.value, self._dtype)

    def __repr__(self):
        return f"Literal({self.value!r})"


class Parameter(Literal):
    """A runtime query parameter: a :class:`Literal` whose VALUE is a
    rebindable scalar argument instead of a plan constant (the serving
    front door, docs/plan_cache.md).

    The plan cache's parameterization pass replaces eligible constant
    subtrees with Parameters so q6 with a different date range produces
    the SAME plan fingerprint and the same compiled ``_fused_fn``
    signatures — the structural cache key is ``("param", slot, dtype)``,
    never the value. Fused programs receive the current values as extra
    traced jit arguments appended after the batch's flat arrays
    (``ColumnarBatch.params``); eager/CPU paths read ``self.value`` like
    any literal (Parameter IS-A Literal, so every isinstance fast path
    keeps working).

    ``slot``: plan-wide parameter index (deterministic traversal order —
    structural, so two plans of the same shape number identically).
    ``trace_pos``: position of this parameter inside its consuming fused
    program's appended argument tuple (stamped by the consumer before its
    first trace; baked into the compiled program).
    ``name``: optional prepared-statement placeholder name (``:name``).
    """

    def __init__(self, value: Any = None, dtype: Optional[dt.DType] = None,
                 slot: int = -1, name: Optional[str] = None):
        if dtype is None and value is None:
            # a named placeholder before its first bind: dtype resolves
            # from the first execute()'s value
            Expression.__init__(self)
            self._dtype = None
            self.value = None
        else:
            super().__init__(value, dtype)
        self.slot = slot
        self.param_name = name
        self.trace_pos: Optional[int] = None

    @property
    def dtype(self) -> dt.DType:
        if self._dtype is None:
            # pre-bind: parse builds throwaway analyzed copies (schema
            # probes like df.columns) that must not crash on a
            # placeholder nobody has bound yet — it types as NULLTYPE
            # there. Execution re-analyzes AFTER binding, and eval()
            # still refuses to run unbound.
            return dt.NULLTYPE
        return self._dtype

    @property
    def nullable(self) -> bool:
        return False          # parameters never bind NULL (bind() rejects)

    def bind(self, value: Any, dtype: Optional[dt.DType] = None,
             retype: bool = False) -> None:
        """Rebind the runtime value. The dtype is FIXED once set — the
        compiled programs were traced for it; only a prepared
        statement's PARSE-TREE placeholders may ``retype`` (a dtype
        change there produces a different fingerprint and a fresh
        plan, never a stale program)."""
        if value is None:
            raise ValueError(
                f"parameter :{self.param_name or self.slot} cannot bind "
                "NULL (plan a literal NULL instead)")
        if self._dtype is None or retype:
            self._dtype = dtype if dtype is not None else \
                Literal(value).dtype
        self.value = value

    def traceable(self) -> bool:
        """Whether this parameter's value can ride as a traced 0-d jit
        argument (fixed-width scalar dtypes). Non-traceable parameters
        (strings) stay baked: their VALUE joins the structural cache key
        so a rebind can never reuse a stale program."""
        return (self._dtype is not None and
                self._dtype.numpy_dtype is not None and
                not self._dtype.var_width)

    def eval(self, batch: ColumnarBatch) -> Scalar:
        if self._dtype is None or self.value is None:
            raise RuntimeError(
                f"unbound parameter :{self.param_name or self.slot} — "
                "prepared statements must bind every placeholder before "
                "execution")
        pv = getattr(batch, "params", ()) if batch is not None else ()
        if pv and self.trace_pos is not None and self.trace_pos < len(pv):
            # inside a fused trace: the value is a traced 0-d argument; a
            # float64's is its eight bytes (param_arg_values)
            v = pv[self.trace_pos]
            if self.dtype == dt.FLOAT64:
                v = float64_from_words(v)
            return Scalar(v, self.dtype)
        return Scalar(self.value, self.dtype)

    def __repr__(self):
        tag = self.param_name or f"p{self.slot}"
        return f"Param(:{tag}={self.value!r})"


def _compared_string_literals(e: Expression) -> List["Literal"]:
    """The plain string literals of ``e`` that a comparison
    (``compares_strings``) holds against something read from the batch,
    in traversal order. Their bytes can ride as an argument of the fused
    program: ``c_mktsegment = 'BUILDING'`` and ``... = 'MACHINERY'`` are
    then ONE program, where a constant would make one for each (the plan
    and its cache entry stay per value: the fingerprint keeps it)."""
    out: List[Literal] = []

    def reads_batch(x: Expression) -> bool:
        return bool(x.collect(
            lambda n: isinstance(n, (BoundReference, ColumnRef))))

    def walk(n: Expression) -> None:
        if getattr(n, "compares_strings", False) and len(n.children) == 2:
            for lit, other in (n.children, n.children[::-1]):
                if type(lit) is Literal and lit.dtype == dt.STRING \
                        and isinstance(lit.value, str) \
                        and reads_batch(other):
                    out.append(lit)
        for c in n.children:
            walk(c)
    walk(e)
    return out


def ordered_params(exprs: Sequence[Expression]) -> List["Literal"]:
    """What a fused program over ``exprs`` takes as appended arguments,
    each stamped with its ``trace_pos`` — the canonical ordering the
    program and its call sites must agree on: the unique TRACEABLE
    Parameters in slot order, then the string literals of comparisons
    (:func:`_compared_string_literals`). Non-traceable parameters
    (strings) stay baked; their values ride the structural cache key
    instead."""
    by_slot: dict = {}
    for e in exprs:
        for p in e.collect(lambda x: isinstance(x, Parameter)):
            if p.traceable():
                by_slot.setdefault(p.slot, p)
    out: List[Literal] = [by_slot[s] for s in sorted(by_slot)]
    seen = set()
    for e in exprs:
        for lit in _compared_string_literals(e):
            if id(lit) not in seen:
                seen.add(id(lit))
                out.append(lit)
    for i, p in enumerate(out):
        p.trace_pos = i
    return out


def traced_literal_ids(params: Sequence["Literal"]) -> frozenset:
    """ids of the string literals among :func:`ordered_params`: what a
    consumer's structural cache key must NOT hold the value of."""
    return frozenset(id(p) for p in params if type(p) is Literal)


#: narrowest width class a string literal travels in as an argument. The
#: class is the argument's SHAPE, so two literals in different classes are
#: two programs: with the columns' own minimum (8) TPC-H Q3's 'BUILDING'
#: (8 bytes) and 'MACHINERY' (9) were. 32 holds every enumerated value of
#: the TPC-H columns a query compares (the longest, p_type, has 25 bytes);
#: above it the classes are the columns' powers of two.
TRACED_LITERAL_MIN_WIDTH = 32


def string_literal_array(value: str) -> np.ndarray:
    """A string literal as ONE argument of a fused program: its UTF-8
    bytes zero-padded to a width class of at least
    :data:`TRACED_LITERAL_MIN_WIDTH`, then the byte count as four
    little-endian bytes (``ops/strings_util.traced_scalar`` reads it)."""
    from ..columnar.column import bucket
    raw = np.frombuffer(value.encode("utf-8"), dtype=np.uint8)
    width = bucket(len(raw), TRACED_LITERAL_MIN_WIDTH)
    out = np.zeros(width + 4, dtype=np.uint8)
    out[:len(raw)] = raw
    out[width:] = np.frombuffer(np.int32(len(raw)).tobytes(), dtype=np.uint8)
    return out


@host_site("param_args")
def param_arg_values(params: Sequence["Literal"]) -> tuple:
    """The current value of each of :func:`ordered_params` as a
    dtype-stable numpy array — the extra jit arguments appended after a
    batch's flat arrays: a parameter's binding as a 0-d scalar, or for a
    float64 as its eight bytes (the columns' route onto the device,
    ``columnar/column.float64_words``); a string literal as
    :func:`string_literal_array`. Host-side value boxing, no device
    sync."""
    return tuple(
        string_literal_array(p.value) if type(p) is Literal else
        float64_words(p.value) if p.dtype == dt.FLOAT64 else
        np.asarray(p.value, dtype=p.dtype.numpy_dtype)  # lint: host-sync-ok boxes a python scalar host-side; no device value involved
        for p in params)


class ColumnRef(Expression):
    """Name-based column reference (pre-binding; Catalyst AttributeReference analog)."""

    def __init__(self, col_name: str):
        super().__init__()
        self.col_name = col_name
        self._resolved: Optional[dt.Field] = None

    def resolve(self, schema: dt.Schema) -> "ColumnRef":
        self._resolved = schema[self.col_name]
        return self

    @property
    def dtype(self) -> dt.DType:
        if self._resolved is None:
            raise RuntimeError(f"unresolved column {self.col_name!r}")
        return self._resolved.dtype

    @property
    def nullable(self) -> bool:
        return self._resolved.nullable if self._resolved else True

    def eval(self, batch: ColumnarBatch) -> Column:
        return batch.column(self.col_name)

    def __repr__(self):
        return f"col({self.col_name!r})"


class BoundReference(Expression):
    """Ordinal-bound input reference (GpuBoundReference, GpuBoundAttribute.scala)."""

    def __init__(self, ordinal: int, dtype: dt.DType, nullable: bool = True,
                 col_name: str = ""):
        super().__init__()
        self.ordinal = ordinal
        self._dtype = dtype
        self._nullable = nullable
        self.col_name = col_name

    @property
    def dtype(self) -> dt.DType:
        return self._dtype

    @property
    def nullable(self) -> bool:
        return self._nullable

    def eval(self, batch: ColumnarBatch) -> Column:
        return batch.columns[self.ordinal]

    def __repr__(self):
        return f"input[{self.ordinal}, {self._dtype}]"


class Alias(Expression):
    """Named output wrapper (GpuAlias, namedExpressions.scala)."""

    def __init__(self, child: Expression, alias: str):
        super().__init__(child)
        self.alias = alias

    @property
    def child(self) -> Expression:
        return self.children[0]

    def _rebind_child_aliases(self) -> None:
        pass

    @property
    def dtype(self) -> dt.DType:
        return self.child.dtype

    @property
    def nullable(self) -> bool:
        return self.child.nullable

    def eval(self, batch: ColumnarBatch) -> ColumnOrScalar:
        return self.child.eval(batch)

    def __repr__(self):
        return f"{self.child!r} AS {self.alias}"


def output_name(expr: Expression, idx: int) -> str:
    if isinstance(expr, Alias):
        return expr.alias
    if isinstance(expr, ColumnRef):
        return expr.col_name
    if isinstance(expr, BoundReference) and expr.col_name:
        return expr.col_name
    return f"col{idx}"


# ---------------------------------------------------------------------------
# Eval helpers shared by concrete expression modules
# ---------------------------------------------------------------------------

def materialize(value: ColumnOrScalar, batch: ColumnarBatch) -> Column:
    """Scalar -> broadcast Column at the batch's capacity (rare; ops prefer inline)."""
    if isinstance(value, Scalar):
        return Column.from_scalar(value, batch.num_rows, batch.capacity)
    return value


def data_validity(value: ColumnOrScalar, dtype: dt.DType):
    """(data, validity) pair usable in jnp broadcasting.

    Scalars become 0-d jnp values + validity True/False python bools so XLA folds
    them as constants inside fused computations (a float64 stays its bytes
    until the device: ``columnar/column.device_scalar``).
    """
    if isinstance(value, Scalar):
        if value.is_null:
            return jnp.zeros((), dtype=dtype.numpy_dtype), False
        return device_scalar(value.value, dtype.numpy_dtype), True
    return value.data, value.validity


def combine_validity(*vs):
    """AND of validities where python ``True`` means always-valid."""
    cols = [v for v in vs if not (v is True)]
    if not cols:
        return True
    out = cols[0]
    for v in cols[1:]:
        out = out & v
    return out


def result_column(dtype: dt.DType, data: jnp.ndarray, validity, capacity: int,
                  lengths=None) -> Column:
    if validity is True:
        validity = jnp.ones(capacity, dtype=jnp.bool_)
    elif validity is False:
        validity = jnp.zeros(capacity, dtype=jnp.bool_)
    if data.ndim == 0 or (dtype != dt.STRING and data.shape[0] != capacity):
        data = jnp.broadcast_to(data, (capacity,))
    return Column(dtype, data, validity, lengths)


def lit(value: Any, dtype: Optional[dt.DType] = None) -> Literal:
    return Literal(value, dtype)


def col(name: str) -> ColumnRef:
    return ColumnRef(name)
