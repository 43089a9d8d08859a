"""CPU reference engine: executes logical plans on pandas.

Role (DESIGN.md §8): this is the "CPU Spark" side of the golden-compare
harness — the reference's correctness strategy runs every query on both CPU
Spark and the GPU plugin and diffs results (SparkQueryCompareTestSuite,
SURVEY.md §4). Being standalone, we supply the CPU side ourselves with an
independent pandas implementation; it doubles as the fallback executor for
operators tagged off the TPU (RapidsMeta.willNotWorkOnGpu analog).

Null model: object-dtype / float-NaN-free representation — every cell is a
python value or None, so SQL three-valued logic is explicit rather than
riding pandas NaN coercion.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np
import pandas as pd

from ..columnar import dtypes as dt
from ..ops import expressions as ex
from ..ops import arithmetic as ar
from ..ops import predicates as pr
from ..ops import conditionals as co
from ..ops import math_ops as mo
from ..ops import strings as st
from ..ops import datetime as dtime
from ..ops import hashing as hs
from ..ops.cast import Cast
from ..plan import logical as lp


def _cells(series_or_list) -> List[Any]:
    if isinstance(series_or_list, list):
        return series_or_list
    return list(series_or_list)


class CpuEvaluator:
    """Row-wise expression evaluator with Spark SQL semantics.

    ``schema`` (the plan child's Schema) resolves column refs by ORDINAL —
    post-join frames carry duplicate column names, where pandas ``df[name]``
    would return a frame instead of a series."""

    def __init__(self, df: pd.DataFrame, schema=None):
        self.df = df
        self.schema = schema
        self.n = len(df)

    def _col_by_name(self, name: str):
        if self.schema is not None and name in self.schema:
            return _cells(self.df.iloc[:, self.schema.index_of(name)])
        col = self.df[name]
        if isinstance(col, pd.DataFrame):   # duplicate names: first wins
            col = col.iloc[:, 0]
        return _cells(col)

    def eval(self, e: ex.Expression) -> List[Any]:
        out = self._eval(e)
        if not isinstance(out, list):
            out = [out] * self.n
        return out

    # -- dispatch ------------------------------------------------------------
    def _eval(self, e: ex.Expression):
        if isinstance(e, ex.Literal):
            return [e.value] * self.n
        if isinstance(e, st.RegExpReplaceHost):
            return e.apply_list(self._eval(e.children[0]))
        from ..ops.structs import GetField
        if isinstance(e, GetField):
            vals = self._eval(e.children[0])
            return [None if v is None else
                    (v.get(e.field) if isinstance(v, dict)
                     else getattr(v, e.field, None))
                    for v in vals]
        from ..ops.python_udf import PandasUDF
        if isinstance(e, PandasUDF):
            import pandas as pd
            series = [pd.Series(self._eval(c), dtype=object)
                      for c in e.children]
            out = e.fn(*series)
            if len(out) != self.n:        # same contract as the device path
                raise ValueError(
                    f"pandas UDF {e.udf_name!r} returned {len(out)} rows "
                    f"for {self.n} input rows")
            return [None if pd.isna(v) else v for v in out]
        from ..ops import arrays as ar_ops
        if isinstance(e, ar_ops.StringSplit):
            vals = self._eval(e.children[0])
            return [None if v is None else v.split(e.delimiter)
                    for v in vals]
        if isinstance(e, ar_ops.Size):
            vals = self._eval(e.children[0])
            # Spark 3.0 legacy sizeOfNull: size(NULL) = -1
            return [-1 if v is None else len(v) for v in vals]
        if isinstance(e, ar_ops.GetArrayItem):
            arrs = self._eval(e.children[0])
            idxs = self._eval(e.children[1])
            out = []
            for a, i in zip(arrs, idxs):
                if a is None or i is None:
                    out.append(None)
                    continue
                i = int(i)
                if getattr(e, "one_based", False):
                    if i == 0:
                        out.append(None)
                        continue
                    i = i - 1 if i > 0 else len(a) + i
                out.append(a[i] if 0 <= i < len(a) else None)
            return out
        from ..ops import maps as mp_ops

        def _as_map(o):
            # pandas materializes arrow map cells as lists of (k, v)
            # tuples; dict() also applies LAST_WIN dedup like the device
            return o if o is None or isinstance(o, dict) else dict(o)

        if isinstance(e, mp_ops.CreateMap):
            cols = [self._eval(c) for c in e.children]
            out = []
            for row in zip(*cols):
                ks, vs = row[0::2], row[1::2]
                # NULL key -> NULL map; duplicate keys: LAST_WIN
                out.append(None if any(k is None for k in ks)
                           else dict(zip(ks, vs)))
            return out
        if isinstance(e, mp_ops.GetMapValue):
            ms = [_as_map(m) for m in self._eval(e.children[0])]
            ks = self._eval(e.children[1])
            return [None if m is None or k is None else m.get(k)
                    for m, k in zip(ms, ks)]
        if isinstance(e, mp_ops.GetItem):
            from ..columnar import dtypes as _dt
            objs = self._eval(e.children[0])
            if _dt.is_map(e.children[0].dtype):
                objs = [_as_map(o) for o in objs]
            ks = self._eval(e.children[1])
            out = []
            for o, k in zip(objs, ks):
                if o is None or k is None:
                    out.append(None)
                elif isinstance(o, dict):
                    out.append(o.get(k))
                else:
                    i = int(k)
                    if e.one_based:
                        if i == 0:
                            out.append(None)
                            continue
                        i = i - 1 if i > 0 else len(o) + i
                    out.append(o[i] if 0 <= i < len(o) else None)
            return out
        if isinstance(e, mp_ops.MapKeys):
            ms = [_as_map(m) for m in self._eval(e.children[0])]
            return [None if m is None else list(m.keys()) for m in ms]
        if isinstance(e, mp_ops.MapValues):
            ms = [_as_map(m) for m in self._eval(e.children[0])]
            # NULL map values surface as NULL array elements (the device
            # array layout carries per-element validity)
            return [None if m is None else list(m.values()) for m in ms]
        if isinstance(e, ex.ColumnRef):
            return self._col_by_name(e.col_name)
        if isinstance(e, ex.BoundReference):
            return _cells(self.df.iloc[:, e.ordinal])
        if isinstance(e, ex.Alias):
            return self._eval(e.children[0])
        if isinstance(e, Cast):
            return self._cast(e)
        if isinstance(e, ar.BinaryArithmetic):
            return self._binary_arith(e)
        if isinstance(e, (ar.UnaryMinus, ar.UnaryPositive, ar.Abs)):
            return self._unary_arith(e)
        if isinstance(e, pr.EqualNullSafe):
            l, r = (self._eval(c) for c in e.children)
            return [_null_safe_eq(a, b) for a, b in zip(l, r)]
        if isinstance(e, pr.BinaryComparison):
            return self._comparison(e)
        if isinstance(e, pr.And):
            l, r = (self._eval(c) for c in e.children)
            return [_kleene_and(a, b) for a, b in zip(l, r)]
        if isinstance(e, pr.Or):
            l, r = (self._eval(c) for c in e.children)
            return [_kleene_or(a, b) for a, b in zip(l, r)]
        if isinstance(e, pr.Not):
            return [None if v is None else (not v)
                    for v in self._eval(e.children[0])]
        if isinstance(e, pr.IsNull):
            return [v is None for v in self._eval(e.children[0])]
        if isinstance(e, pr.IsNotNull):
            return [v is not None for v in self._eval(e.children[0])]
        if isinstance(e, pr.IsNaN):
            return [v is not None and isinstance(v, float) and math.isnan(v)
                    for v in self._eval(e.children[0])]
        if isinstance(e, pr.In):
            return self._in(e)
        if isinstance(e, co.If):
            c, t, f = (self._eval(x) for x in e.children)
            return [tv if (cv is True) else fv for cv, tv, fv in zip(c, t, f)]
        if isinstance(e, co.CaseWhen):
            return self._case_when(e)
        if isinstance(e, co.Coalesce):
            cols = [self._eval(c) for c in e.children]
            return [next((v for v in row if v is not None), None)
                    for row in zip(*cols)]
        if isinstance(e, co.NullIf):
            l, r = (self._eval(c) for c in e.children)
            return [None if (a is not None and b is not None and
                             _sql_eq(a, b)) else a for a, b in zip(l, r)]
        if isinstance(e, (co.Least, co.Greatest)):
            cols = [self._eval(c) for c in e.children]
            pick = min if isinstance(e, co.Least) else max
            out = []
            for row in zip(*cols):
                vals = [v for v in row if v is not None]
                out.append(pick(vals, key=_order_key) if vals else None)
            return out
        if isinstance(e, mo.UnaryMath):
            return self._unary_math(e)
        if isinstance(e, (mo.Floor, mo.Ceil)):
            f = math.floor if isinstance(e, mo.Floor) else math.ceil
            return [None if v is None else int(f(v))
                    for v in self._eval(e.children[0])]
        if isinstance(e, mo.Round):
            return self._round(e)
        if isinstance(e, mo.Pow):
            l, r = (self._eval(c) for c in e.children)
            return [None if a is None or b is None else float(a) ** float(b)
                    for a, b in zip(l, r)]
        if isinstance(e, mo.Atan2):
            l, r = (self._eval(c) for c in e.children)
            return [None if a is None or b is None else math.atan2(a, b)
                    for a, b in zip(l, r)]
        handler = _STRING_HANDLERS.get(type(e)) or _DATE_HANDLERS.get(type(e))
        if handler is not None:
            return handler(self, e)
        if isinstance(e, hs.Murmur3Hash):
            return self._murmur3(e)
        raise NotImplementedError(
            f"CPU engine: unsupported expression {type(e).__name__}")

    # -- numeric -------------------------------------------------------------
    def _binary_arith(self, e: ar.BinaryArithmetic):
        l, r = (self._eval(c) for c in e.children)
        t = e.dtype
        out = []
        for a, b in zip(l, r):
            if a is None or b is None:
                out.append(None)
                continue
            out.append(_arith_op(e, a, b, t))
        return out

    def _unary_arith(self, e):
        vals = self._eval(e.children[0])
        if isinstance(e, ar.UnaryPositive):
            return vals
        if isinstance(e, ar.UnaryMinus):
            return [None if v is None else _wrap_int(-v, e.dtype) for v in vals]
        return [None if v is None else _wrap_int(abs(v), e.dtype) for v in vals]

    def _comparison(self, e: pr.BinaryComparison):
        l, r = (self._eval(c) for c in e.children)
        op = type(e).__name__
        out = []
        for a, b in zip(l, r):
            if a is None or b is None:
                out.append(None)
                continue
            ka, kb = _order_key(a), _order_key(b)
            if op == "EqualTo":
                out.append(ka == kb)
            elif op == "NotEqual":
                out.append(ka != kb)
            elif op == "LessThan":
                out.append(ka < kb)
            elif op == "LessThanOrEqual":
                out.append(ka <= kb)
            elif op == "GreaterThan":
                out.append(ka > kb)
            else:
                out.append(ka >= kb)
        return out

    def _in(self, e: pr.In):
        vals = self._eval(e.children[0])
        has_null = any(x is None for x in e.values)
        concrete = [x for x in e.values if x is not None]
        out = []
        for v in vals:
            if v is None:
                out.append(None)
            elif any(_sql_eq(v, x) for x in concrete):
                out.append(True)
            else:
                out.append(None if has_null else False)
        return out

    def _case_when(self, e: co.CaseWhen):
        n = self.n
        result = self._eval(e.children[-1]) if e.has_else else [None] * n
        decided = [False] * n
        out = list(result)
        for i in range(e.num_branches):
            conds = self._eval(e.children[2 * i])
            vals = self._eval(e.children[2 * i + 1])
            for j in range(n):
                if not decided[j] and conds[j] is True:
                    out[j] = vals[j]
                    decided[j] = True
        return out

    def _unary_math(self, e: mo.UnaryMath):
        vals = self._eval(e.children[0])
        out = []
        for v in vals:
            if v is None:
                out.append(None)
                continue
            try:
                r = e.pyfn(float(v)) if e.pyfn else None
                if r is None:
                    raise ValueError
            except (ValueError, OverflowError, ZeroDivisionError):
                r = None
            out.append(r)
        return out

    def _round(self, e: mo.Round):
        from decimal import Decimal, ROUND_HALF_UP
        vals = self._eval(e.children[0])
        out = []
        for v in vals:
            if v is None:
                out.append(None)
            elif isinstance(v, float) and (math.isnan(v) or math.isinf(v)):
                out.append(v)
            else:
                q = Decimal(10) ** -e.scale
                r = float(Decimal(str(v)).quantize(q, rounding=ROUND_HALF_UP))
                out.append(r if e.dtype.is_floating else int(r))
        return out

    def _cast(self, e: Cast):
        vals = self._eval(e.children[0])
        src, dst = e.children[0].dtype, e.to
        return [_cast_value(v, src, dst) for v in vals]

    def _murmur3(self, e: hs.Murmur3Hash):
        cols = [self._eval(c) for c in e.children]
        types = [c.dtype for c in e.children]
        out = []
        for row in zip(*cols):
            h = e.seed
            for v, t in zip(row, types):
                h = _murmur3_value(v, t, h)
            out.append(h - (1 << 32) if h >= 1 << 31 else h)
        return out


# -- value helpers -----------------------------------------------------------

def _order_key(v):
    """Total-order key: NaN sorts greater than everything (Spark)."""
    if isinstance(v, float) and math.isnan(v):
        return (1, 0.0)
    if isinstance(v, bool):
        return (0, int(v))
    if isinstance(v, str):
        return (0, v.encode("utf-8"))
    return (0, v)


def _sql_eq(a, b) -> bool:
    if isinstance(a, float) and isinstance(b, float) and \
            math.isnan(a) and math.isnan(b):
        return True
    if isinstance(a, str) != isinstance(b, str):
        return False
    return a == b


def _null_safe_eq(a, b):
    if a is None or b is None:
        return a is None and b is None
    return _sql_eq(a, b)


def _kleene_and(a, b):
    if a is False or b is False:
        return False
    if a is None or b is None:
        return None
    return True


def _kleene_or(a, b):
    if a is True or b is True:
        return True
    if a is None or b is None:
        return None
    return False


_INT_BITS = {dt.INT8: 8, dt.INT16: 16, dt.INT32: 32, dt.INT64: 64}


def _wrap_int(v, t: dt.DType):
    bits = _INT_BITS.get(t)
    if bits is None or not isinstance(v, int):
        return v
    m = 1 << bits
    v &= m - 1
    return v - m if v >= m >> 1 else v


def _arith_op(e, a, b, t: dt.DType):
    if isinstance(e, ar.Add):
        return _wrap_int(a + b, t)
    if isinstance(e, ar.Subtract):
        return _wrap_int(a - b, t)
    if isinstance(e, ar.Multiply):
        return _wrap_int(a * b, t)
    if isinstance(e, ar.Divide):
        if b == 0:
            return None
        return a / b
    if isinstance(e, ar.IntegralDivide):
        if b == 0:
            return None
        return _wrap_int(int(_java_mod_div(a, b)), dt.INT64)
    if isinstance(e, ar.Remainder):
        if b == 0:
            return None
        if t.is_floating:
            return math.fmod(a, b)
        return _wrap_int(int(math.fmod(a, b)), t)
    if isinstance(e, ar.Pmod):
        if b == 0:
            return None
        if t.is_floating:
            r = math.fmod(a, b)
            return r + abs(b) if r < 0 else r
        r = int(math.fmod(a, b))
        return _wrap_int(r + abs(b) if r < 0 else r, t)
    raise NotImplementedError(type(e).__name__)


def _java_mod_div(a, b):
    """Java integer division truncates toward zero."""
    q = abs(a) // abs(b)
    return q if (a >= 0) == (b >= 0) else -q


def _cast_value(v, src: dt.DType, dst: dt.DType):
    if v is None:
        return None
    if src == dst:
        return v
    if dst == dt.STRING:
        if src == dt.BOOL:
            return "true" if v else "false"
        if src.is_floating:
            return repr(float(v))
        if src == dt.DATE:
            import datetime
            return (datetime.date(1970, 1, 1) +
                    datetime.timedelta(days=int(v))).isoformat()
        if src == dt.TIMESTAMP:
            import datetime
            base = datetime.datetime(1970, 1, 1) + \
                datetime.timedelta(microseconds=int(v))
            return base.strftime("%Y-%m-%d %H:%M:%S")
        return str(v)
    if src == dt.STRING:
        from ..ops.cast import _parse_value
        return _parse_value(v, dst)
    if dst == dt.BOOL:
        return v != 0
    if dst.is_integral:
        if src == dt.BOOL:
            return int(v)
        if src.is_floating:
            if math.isnan(v):
                return 0
            lo = -(1 << (_INT_BITS[dst] - 1))
            hi = (1 << (_INT_BITS[dst] - 1)) - 1
            return max(lo, min(hi, int(v)))
        return _wrap_int(int(v), dst)
    if dst.is_floating:
        return float(v)
    if dst == dt.DATE and src == dt.TIMESTAMP:
        return int(v // 86_400_000_000) if v >= 0 or v % 86_400_000_000 == 0 \
            else int(v // 86_400_000_000)
    if dst == dt.TIMESTAMP and src == dt.DATE:
        return int(v) * 86_400_000_000
    if dst == dt.TIMESTAMP and src.is_integral:
        return int(v) * 1_000_000
    if dst.is_integral and src == dt.TIMESTAMP:
        return _wrap_int(int(v // 1_000_000), dst)
    if dst == dt.DATE and src.is_integral:
        return _wrap_int(int(v), dt.INT32)   # day-number reinterpret
    if dst.is_integral and src == dt.DATE:
        return _wrap_int(int(v), dst)
    raise NotImplementedError(f"cpu cast {src} -> {dst}")


def _murmur3_value(v, t: dt.DType, seed: int) -> int:
    M = 0xFFFFFFFF

    def rotl(x, r):
        return ((x << r) | (x >> (32 - r))) & M

    def mixk1(k1):
        k1 = (k1 * 0xCC9E2D51) & M
        return (rotl(k1, 15) * 0x1B873593) & M

    def mixh1(h1, k1):
        h1 ^= k1
        return (rotl(h1, 13) * 5 + 0xE6546B64) & M

    def fmix(h1, ln):
        h1 ^= ln
        h1 ^= h1 >> 16
        h1 = (h1 * 0x85EBCA6B) & M
        h1 ^= h1 >> 13
        h1 = (h1 * 0xC2B2AE35) & M
        return h1 ^ (h1 >> 16)

    if v is None:
        return seed
    if t == dt.STRING:
        bs = v.encode("utf-8")
        h1 = seed
        n = len(bs)
        for i in range(0, n // 4 * 4, 4):
            k1 = bs[i] | bs[i + 1] << 8 | bs[i + 2] << 16 | bs[i + 3] << 24
            h1 = mixh1(h1, mixk1(k1))
        for i in range(n // 4 * 4, n):
            b = bs[i] - 256 if bs[i] >= 128 else bs[i]
            h1 = mixh1(h1, mixk1(b & M))
        return fmix(h1, n)
    if t in (dt.INT64, dt.TIMESTAMP):
        lv = int(v) & 0xFFFFFFFFFFFFFFFF
        h1 = mixh1(seed, mixk1(lv & M))
        h1 = mixh1(h1, mixk1((lv >> 32) & M))
        return fmix(h1, 8)
    if t == dt.FLOAT64:
        import struct
        x = 0.0 if v == 0.0 else float(v)
        bits = struct.unpack("<Q", struct.pack("<d", x))[0]
        h1 = mixh1(seed, mixk1(bits & M))
        h1 = mixh1(h1, mixk1((bits >> 32) & M))
        return fmix(h1, 8)
    if t == dt.FLOAT32:
        import struct
        x = 0.0 if v == 0.0 else float(np.float32(v))
        bits = struct.unpack("<I", struct.pack("<f", np.float32(x)))[0]
        return fmix(mixh1(seed, mixk1(bits)), 4)
    iv = int(v) & M
    return fmix(mixh1(seed, mixk1(iv)), 4)


# -- string / datetime handlers ---------------------------------------------

def _h_strings(method):
    def h(ev: CpuEvaluator, e):
        args = [ev._eval(c) for c in e.children]
        return method(ev, e, args)
    return h


def _str1(fn):
    def h(ev, e, args):
        return [None if v is None else fn(e, v) for v in args[0]]
    return h


_STRING_HANDLERS: Dict[type, Callable] = {
    st.Length: _h_strings(_str1(lambda e, v: len(v))),
    st.Upper: _h_strings(_str1(lambda e, v: _ascii_case(v, True))),
    st.Lower: _h_strings(_str1(lambda e, v: _ascii_case(v, False))),
    st.InitCap: _h_strings(_str1(
        lambda e, v: " ".join(w[:1].upper() + w[1:].lower() for w in v.split(" ")))),
    st.StringTrim: _h_strings(_str1(lambda e, v: v.strip(" "))),
    st.StringTrimLeft: _h_strings(_str1(lambda e, v: v.lstrip(" "))),
    st.StringTrimRight: _h_strings(_str1(lambda e, v: v.rstrip(" "))),
}


def _ascii_case(s: str, up: bool) -> str:
    out = []
    for ch in s:
        if up and "a" <= ch <= "z":
            out.append(chr(ord(ch) - 32))
        elif not up and "A" <= ch <= "Z":
            out.append(chr(ord(ch) + 32))
        else:
            out.append(ch)
    return "".join(out)


def _h_substring(ev, e):
    s, p, ln = (ev._eval(c) for c in e.children)
    out = []
    for v, pos, l in zip(s, p, ln):
        if v is None or pos is None or l is None:
            out.append(None)
            continue
        l = max(l, 0)
        if pos > 0:
            start = pos - 1
        elif pos < 0:
            start = max(len(v) + pos, 0)
        else:
            start = 0
        out.append(v[start:start + l])
    return out


def _h_concat(ev, e):
    cols = [ev._eval(c) for c in e.children]
    out = []
    for row in zip(*cols):
        if any(v is None for v in row):
            out.append(None)
        else:
            out.append("".join(str(v) for v in row))
    return out


def _h_pattern(ev, e):
    s = ev._eval(e.children[0])
    p = ev._eval(e.children[1])
    out = []
    for v, pat in zip(s, p):
        if v is None or pat is None:
            out.append(None)
        else:
            out.append(e._py(v, pat))
    return out


def _h_like(ev, e):
    s = ev._eval(e.children[0])
    return [None if v is None else st._like_py(v, e.pattern, e.escape) for v in s]


def _h_locate(ev, e):
    sub = e.children[0]
    s = ev._eval(e.children[1])
    start = ev._eval(e.children[2])
    out = []
    for v, sv in zip(s, start):
        if v is None or sub.value is None:
            out.append(None)
        else:
            sv = sv or 1
            out.append(0 if sv < 1 else v.find(str(sub.value), sv - 1) + 1)
    return out


def _h_replace(ev, e):
    s = ev._eval(e.children[0])
    return [None if v is None else v.replace(e.search, e.replacement) for v in s]


def _h_pad(ev, e):
    s = ev._eval(e.children[0])
    return [None if v is None else st._pad_py(v, e.width, e.pad, e._left)
            for v in s]


def _h_regexp(ev, e):
    import re
    rx = re.compile(e.pattern)
    s = ev._eval(e.children[0])
    out = []
    for v in s:
        if v is None:
            out.append(None)
        else:
            m = rx.search(v)
            out.append(m.group(e.group) if m else "")
    return out


_STRING_HANDLERS.update({
    st.Substring: _h_substring,
    st.ConcatStr: _h_concat,
    st.Contains: _h_pattern,
    st.StartsWith: _h_pattern,
    st.EndsWith: _h_pattern,
    st.Like: _h_like,
    st.StringLocate: _h_locate,
    st.StringReplace: _h_replace,
    st.StringLPad: _h_pad,
    st.StringRPad: _h_pad,
    st.RegExpExtractHost: _h_regexp,
})


def _date_parts(v, t: dt.DType):
    import datetime
    if t == dt.TIMESTAMP:
        days, rem = divmod(int(v), 86_400_000_000)
    else:
        days = int(v)
    return datetime.date(1970, 1, 1) + datetime.timedelta(days=days)


def _h_datepart(fn):
    def h(ev, e):
        t = e.children[0].dtype
        vals = ev._eval(e.children[0])
        return [None if v is None else fn(_date_parts(v, t), v, t) for v in vals]
    return h


def _time_of(v, t):
    sec = int(v) // 1_000_000
    return sec


_DATE_HANDLERS: Dict[type, Callable] = {
    dtime.Year: _h_datepart(lambda d, v, t: d.year),
    dtime.Month: _h_datepart(lambda d, v, t: d.month),
    dtime.DayOfMonth: _h_datepart(lambda d, v, t: d.day),
    dtime.Quarter: _h_datepart(lambda d, v, t: (d.month - 1) // 3 + 1),
    dtime.DayOfWeek: _h_datepart(lambda d, v, t: d.isoweekday() % 7 + 1),
    dtime.WeekDay: _h_datepart(lambda d, v, t: d.weekday()),
    dtime.DayOfYear: _h_datepart(lambda d, v, t: d.timetuple().tm_yday),
    dtime.Hour: _h_datepart(lambda d, v, t: (_time_of(v, t) // 3600) % 24),
    dtime.Minute: _h_datepart(lambda d, v, t: (_time_of(v, t) // 60) % 60),
    dtime.Second: _h_datepart(lambda d, v, t: _time_of(v, t) % 60),
}


def _h_lastday(ev, e):
    import calendar
    t = e.children[0].dtype
    vals = ev._eval(e.children[0])
    out = []
    import datetime
    for v in vals:
        if v is None:
            out.append(None)
            continue
        d = _date_parts(v, t)
        last = calendar.monthrange(d.year, d.month)[1]
        out.append((datetime.date(d.year, d.month, last) -
                    datetime.date(1970, 1, 1)).days)
    return out


def _h_dateadd(ev, e):
    l = ev._eval(e.children[0])
    r = ev._eval(e.children[1])
    sign = e._sign
    return [None if a is None or b is None else int(a) + sign * int(b)
            for a, b in zip(l, r)]


def _h_datediff(ev, e):
    l = ev._eval(e.children[0])
    r = ev._eval(e.children[1])
    return [None if a is None or b is None else int(a) - int(b)
            for a, b in zip(l, r)]


def _h_addmonths(ev, e):
    import datetime
    import calendar
    l = ev._eval(e.children[0])
    r = ev._eval(e.children[1])
    out = []
    for a, b in zip(l, r):
        if a is None or b is None:
            out.append(None)
            continue
        d = _date_parts(a, dt.DATE)
        total = d.year * 12 + (d.month - 1) + int(b)
        y, m = divmod(total, 12)
        m += 1
        day = min(d.day, calendar.monthrange(y, m)[1])
        out.append((datetime.date(y, m, day) - datetime.date(1970, 1, 1)).days)
    return out


def _h_unixts(ev, e):
    t = e.children[0].dtype
    vals = ev._eval(e.children[0])
    if t == dt.DATE:
        return [None if v is None else int(v) * 86_400 for v in vals]
    return [None if v is None else int(v) // 1_000_000 for v in vals]


def _h_fromunix(ev, e):
    vals = ev._eval(e.children[0])
    return [None if v is None else int(v) * 1_000_000 for v in vals]


def _h_todate(ev, e):
    t = e.children[0].dtype
    vals = ev._eval(e.children[0])
    if t == dt.DATE:
        return vals
    return [None if v is None else int(v) // 86_400_000_000 for v in vals]


_DATE_HANDLERS.update({
    dtime.LastDay: _h_lastday,
    dtime.DateAdd: _h_dateadd,
    dtime.DateSub: _h_dateadd,
    dtime.DateDiff: _h_datediff,
    dtime.AddMonths: _h_addmonths,
    dtime.UnixTimestamp: _h_unixts,
    dtime.FromUnixTime: _h_fromunix,
    dtime.ToDate: _h_todate,
})


# ---------------------------------------------------------------------------
# Plan execution
# ---------------------------------------------------------------------------

def execute(plan: lp.LogicalPlan) -> pd.DataFrame:
    """Execute an analyzed logical plan entirely on CPU, returning an
    object-dtype DataFrame (None for NULL)."""
    return _exec(plan)


def _obj_df(columns: Dict[str, List[Any]]) -> pd.DataFrame:
    # ONE construction: inserting column by column grows the (arrow-backed
    # string) column Index once per insert, which pandas 3 makes ~1 ms
    # each — per-pair residual evaluation in _exec_join calls this per row
    return pd.DataFrame({k: pd.Series(v, dtype=object)
                         for k, v in columns.items()})


def _from_arrow(table) -> pd.DataFrame:
    cols = {}
    for i, name in enumerate(table.schema.names):
        t = dt.from_arrow(table.schema.types[i])
        arr = table.column(i)
        vals = arr.to_pylist()
        if t == dt.DATE:
            import datetime
            vals = [None if v is None else (v - datetime.date(1970, 1, 1)).days
                    for v in vals]
        elif t == dt.TIMESTAMP:
            import pyarrow as pa
            vals = arr.combine_chunks().cast(pa.timestamp("us")) \
                .cast(pa.int64()).to_pylist() if hasattr(arr, "combine_chunks") \
                else vals
        cols[name] = vals
    return _obj_df(cols)


def _exec(plan: lp.LogicalPlan) -> pd.DataFrame:
    if isinstance(plan, (lp.LocalScan, lp.CachedScan)):
        return _from_arrow(plan.data)
    if isinstance(plan, lp.FileScan):
        from ..io import read_to_arrow
        return _from_arrow(read_to_arrow(plan.fmt, plan.paths, plan.options))
    if isinstance(plan, lp.Range):
        vals = list(range(plan.start, plan.end, plan.step))
        return _obj_df({"id": vals})
    if isinstance(plan, lp.Project):
        child = _exec(plan.children[0])
        ev = CpuEvaluator(child, plan.children[0].schema)
        cols = [ev.eval(e) for e in plan.exprs]
        names = [ex.output_name(e, i) for i, e in enumerate(plan.exprs)]
        out = pd.DataFrame({i: pd.Series(c, dtype=object)
                            for i, c in enumerate(cols)})
        if not len(child):
            out = pd.DataFrame({i: pd.Series([], dtype=object)
                                for i in range(len(cols))})
        out.columns = names
        return out
    if isinstance(plan, lp.Filter):
        child = _exec(plan.children[0])
        mask = CpuEvaluator(child, plan.children[0].schema).eval(plan.condition)
        keep = [m is True for m in mask]
        return child.loc[keep].reset_index(drop=True)
    if isinstance(plan, lp.Aggregate):
        return _exec_aggregate(plan)
    if isinstance(plan, lp.Join):
        return _exec_join(plan)
    if isinstance(plan, lp.Sort):
        return _exec_sort(plan)
    if isinstance(plan, lp.Limit):
        return _exec(plan.children[0]).head(plan.n).reset_index(drop=True)
    if isinstance(plan, lp.Union):
        dfs = [_exec(c) for c in plan.children]
        out = pd.concat(dfs, ignore_index=True)
        out.columns = plan.schema.names()
        return out
    if isinstance(plan, lp.Distinct):
        child = _exec(plan.children[0])
        key = child.apply(lambda r: tuple(
            ("nan" if isinstance(x, float) and math.isnan(x) else x)
            for x in r), axis=1) if len(child) else pd.Series([], dtype=object)
        return child.loc[~key.duplicated()].reset_index(drop=True) \
            if len(child) else child
    if isinstance(plan, lp.Repartition):
        return _exec(plan.children[0])
    if isinstance(plan, lp.Expand):
        child = _exec(plan.children[0])
        frames = []
        for proj in plan.projections:
            ev = CpuEvaluator(child)
            frames.append(_obj_df({
                n: ev.eval(e) for n, e in zip(plan.output_names, proj)}))
        return pd.concat(frames, ignore_index=True) if frames else _obj_df({})
    if isinstance(plan, lp.Window):
        from .window import exec_window_cpu
        return exec_window_cpu(plan, _exec(plan.children[0]))
    if isinstance(plan, lp.MapInPandas):
        child = _exec(plan.children[0])
        frames = list(plan.fn(iter([child])))
        names = plan.out_schema.names()
        if not frames:
            return _obj_df({n: [] for n in names})
        out = pd.concat(frames, ignore_index=True)
        # coerce to the declared schema: order + presence (the TPU path
        # rebuilds through _df_to_batch(out_schema) the same way)
        return out[[n for n in names]]
    if isinstance(plan, lp.FlatMapGroupsInPandas):
        import inspect
        child = _exec(plan.children[0])
        ev = CpuEvaluator(child)
        kf = pd.DataFrame({f"_gk{i}": ev.eval(g)
                           for i, g in enumerate(plan.grouping)})
        try:
            two_arg = len(inspect.signature(plan.fn).parameters) == 2
        except (TypeError, ValueError):
            two_arg = False
        frames = []
        for key, idx in kf.groupby(list(kf.columns), sort=True,
                                   dropna=False).groups.items():
            if not isinstance(key, tuple):
                key = (key,)
            pdf = child.loc[idx].reset_index(drop=True)
            out = plan.fn(key, pdf) if two_arg else plan.fn(pdf)
            if out is not None and len(out):
                frames.append(out)
        names = plan.out_schema.names()
        if not frames:
            return _obj_df({n: [] for n in names})
        return pd.concat(frames, ignore_index=True)[[n for n in names]]
    if isinstance(plan, lp.FlatMapCoGroupsInPandas):
        import inspect
        left = _exec(plan.children[0])
        right = _exec(plan.children[1])

        def side_groups(child, grouping):
            ev = CpuEvaluator(child)
            kf = pd.DataFrame({f"_gk{i}": ev.eval(g)
                               for i, g in enumerate(grouping)})
            out = {}
            if len(child):
                for key, idx in kf.groupby(list(kf.columns), sort=True,
                                           dropna=False).groups.items():
                    if not isinstance(key, tuple):
                        key = (key,)
                    out[key] = child.loc[idx].reset_index(drop=True)
            return out
        lgroups = side_groups(left, plan.left_grouping)
        rgroups = side_groups(right, plan.right_grouping)
        try:
            three_arg = len(inspect.signature(plan.fn).parameters) == 3
        except (TypeError, ValueError):
            three_arg = False
        frames = []
        for key in sorted(set(lgroups) | set(rgroups), key=repr):
            l = lgroups.get(key, left.iloc[0:0])
            r = rgroups.get(key, right.iloc[0:0])
            out = plan.fn(key, l, r) if three_arg else plan.fn(l, r)
            if out is not None and len(out):
                frames.append(out)
        names = plan.out_schema.names()
        if not frames:
            return _obj_df({n: [] for n in names})
        return pd.concat(frames, ignore_index=True)[[n for n in names]]
    if isinstance(plan, lp.AggregateInPandas):
        child = _exec(plan.children[0])
        ev = CpuEvaluator(child)
        kf = pd.DataFrame({f"_gk{i}": ev.eval(g)
                           for i, g in enumerate(plan.grouping)})
        inputs = [[pd.Series(ev.eval(c)) for c in a.children]
                  for a in plan.aggs]
        rows = []
        for key, idx in kf.groupby(list(kf.columns), sort=True,
                                   dropna=False).groups.items():
            if not isinstance(key, tuple):
                key = (key,)
            vals = [a.fn(*[s.loc[idx].reset_index(drop=True)
                           for s in ins])
                    for a, ins in zip(plan.aggs, inputs)]
            rows.append(tuple(key) + tuple(vals))
        names = plan.out_names
        return _obj_df({n: [r[i] for r in rows]
                        for i, n in enumerate(names)})
    if isinstance(plan, lp.Generate):
        child = _exec(plan.children[0])
        ev = CpuEvaluator(child)
        gen = plan.generator
        arrays = ev.eval(gen.children[0])
        rows, poss, elems = [], [], []
        for i, a in enumerate(arrays):
            if a is None:
                continue
            for p_i, v in enumerate(a):
                rows.append(i)
                poss.append(p_i)
                elems.append(v)
        out = child.iloc[rows].reset_index(drop=True) if len(child) else \
            child.iloc[0:0]
        if getattr(gen, "pos", False):
            out[plan.pos_name] = pd.Series(poss, dtype=object)
        out[plan.col_name] = pd.Series(elems, dtype=object)
        return out
    raise NotImplementedError(f"CPU engine: {plan.name}")


def _exec_aggregate(plan: lp.Aggregate) -> pd.DataFrame:
    child = _exec(plan.children[0])
    ev = CpuEvaluator(child)
    n = len(child)

    # evaluate grouping exprs
    gcols = [ev.eval(g) for g in plan.grouping]

    # collect aggregate leaf expressions
    agg_leaves: List[lp.AggregateExpression] = []
    for e in plan.aggregate_exprs:
        agg_leaves.extend(e.collect(lambda x: isinstance(x, lp.AggregateExpression)))
    leaf_inputs = [ev.eval(a.children[0]) if a.children else [1] * n
                   for a in agg_leaves]

    def group_key(i):
        return tuple(_group_cell(c[i]) for c in gcols)

    groups: Dict[tuple, List[int]] = {}
    order: List[tuple] = []
    for i in range(n):
        k = group_key(i)
        if k not in groups:
            groups[k] = []
            order.append(k)
        groups[k].append(i)
    if not plan.grouping and not order:
        order = [()]
        groups[()] = []

    # compute aggregate values per group per leaf
    leaf_results: List[Dict[tuple, Any]] = []
    for leaf, inputs in zip(agg_leaves, leaf_inputs):
        res = {}
        for k in order:
            rows = groups[k]
            vals = [inputs[i] for i in rows]
            if leaf.distinct:
                seen, dd = set(), []
                for v in vals:
                    kk = _group_cell(v)
                    if kk not in seen:
                        seen.add(kk)
                        dd.append(v)
                vals = dd
            res[k] = _agg_py(leaf.op, vals, leaf.ignore_nulls)
        leaf_results.append(res)

    # assemble output rows: substitute aggregate leaves, then evaluate the
    # result expression per group
    out_cols: Dict[str, List[Any]] = {}
    for i, e in enumerate(plan.aggregate_exprs):
        name = ex.output_name(e, i)
        col_vals = []
        for k in order:
            col_vals.append(_eval_result_expr(e, k, plan, gcols, groups,
                                              agg_leaves, leaf_results))
        out_cols[name] = col_vals
    return _obj_df(out_cols)


def _group_cell(v):
    if isinstance(v, float) and math.isnan(v):
        return ("nan",)
    # struct/array cells surface as dicts/lists (unhashable): canonicalize
    # recursively so CPU-fallback joins/group-bys on them can key a map
    if isinstance(v, dict):
        return ("dict",) + tuple(
            (k, _group_cell(x))
            for k, x in sorted(v.items(), key=lambda kv: repr(kv[0])))
    if isinstance(v, (list, tuple)):
        return ("seq",) + tuple(_group_cell(x) for x in v)
    return v


def _agg_py(op: str, vals: List[Any], ignore_nulls: bool):
    non_null = [v for v in vals if v is not None]
    if op == "count_star":
        return len(vals)
    if op == "count":
        return len(non_null)
    if op == "sum":
        return sum(non_null) if non_null else None
    if op == "avg":
        return sum(non_null) / len(non_null) if non_null else None
    if op == "min":
        return min(non_null, key=_order_key) if non_null else None
    if op == "max":
        return max(non_null, key=_order_key) if non_null else None
    if op == "first":
        pool = non_null if ignore_nulls else vals
        return pool[0] if pool else None
    if op == "last":
        pool = non_null if ignore_nulls else vals
        return pool[-1] if pool else None
    raise NotImplementedError(op)


def _eval_result_expr(e, k, plan, gcols, groups, agg_leaves, leaf_results):
    """Evaluate an output expression for group k: aggregate leaves are looked
    up; grouping expressions take the group's key value; literals fold."""
    # grouping match FIRST (an aliased computed grouping key is the same
    # object in both lists — stripping the alias before comparing would
    # miss it and recurse into unresolvable column refs)
    for gi, g in enumerate(plan.grouping):
        if _same_expr(e, g):
            return k[gi] if not isinstance(k[gi], tuple) else (
                float("nan") if k[gi] == ("nan",) else k[gi])
    if isinstance(e, ex.Alias):
        return _eval_result_expr(e.children[0], k, plan, gcols, groups,
                                 agg_leaves, leaf_results)
    for i, leaf in enumerate(agg_leaves):
        if e is leaf:
            return leaf_results[i][k]
    if isinstance(e, ex.Literal):
        return e.value
    # arithmetic over aggregate results (e.g. sum/count)
    sub = [
        _eval_result_expr(c, k, plan, gcols, groups, agg_leaves, leaf_results)
        for c in e.children]
    df = _obj_df({f"c{i}": [v] for i, v in enumerate(sub)})
    rewired = e.with_children([
        ex.BoundReference(i, c.dtype, True) for i, c in enumerate(e.children)])
    return CpuEvaluator(df).eval(rewired)[0]


def _same_expr(a: ex.Expression, b: ex.Expression) -> bool:
    if a is b:
        return True
    if isinstance(a, ex.Alias):
        return _same_expr(a.children[0], b)
    if isinstance(b, ex.Alias):
        return _same_expr(a, b.children[0])
    if isinstance(a, ex.ColumnRef) and isinstance(b, ex.ColumnRef):
        return a.col_name == b.col_name
    return False


def _exec_join(plan: lp.Join) -> pd.DataFrame:
    from ..ops import predicates as pr_
    left = _exec(plan.children[0])
    right = _exec(plan.children[1])
    how = plan.how
    lnames = plan.children[0].schema.names()
    rnames = plan.children[1].schema.names()

    # extract equi-join keys from the condition (conjunctive EqualTo chains)
    lkeys, rkeys, residual = _extract_equi_keys(plan.condition, lnames, rnames)

    if how == "cross" or (plan.condition is None and not lkeys):
        out = left.merge(right, how="cross") if len(left.columns) and \
            len(right.columns) else left.merge(right, how="cross")
        out.columns = lnames + rnames
        return out

    lev = CpuEvaluator(left)
    rev = CpuEvaluator(right)
    lkc = [lev.eval(e) for e in lkeys]
    rkc = [rev.eval(e) for e in rkeys]

    rmap: Dict[tuple, List[int]] = {}
    for j in range(len(right)):
        kt = tuple(_group_cell(c[j]) for c in rkc)
        if any(c[j] is None for c in rkc):
            continue
        rmap.setdefault(kt, []).append(j)

    pairs: List[tuple] = []
    matched_right = set()
    l_matched = [False] * len(left)
    for i in range(len(left)):
        if any(c[i] is None for c in lkc):
            continue
        kt = tuple(_group_cell(c[i]) for c in lkc)
        for j in rmap.get(kt, []):
            pairs.append((i, j))
            l_matched[i] = True
            matched_right.add(j)

    if residual is not None:
        # the residual over ALL candidate pairs at once: one frame with a
        # row per pair (evaluation is row-wise either way; a one-row frame
        # per pair cost milliseconds of pandas construction each)
        keep_pairs = []
        if pairs:
            cols = {}
            for c in lnames:
                vals = left[c].tolist()
                cols[c] = [vals[i] for i, _ in pairs]
            for c in rnames:
                vals = right[c].tolist()
                cols[f"__r_{c}"] = [vals[j] for _, j in pairs]
            cond = _rewire_condition(residual, lnames, rnames)
            verdicts = CpuEvaluator(_obj_df(cols)).eval(cond)
            keep_pairs = [p for p, v in zip(pairs, verdicts) if v is True]
        # recompute matched flags under the residual
        pairs = keep_pairs
        l_matched = [False] * len(left)
        matched_right = set()
        for (i, j) in pairs:
            l_matched[i] = True
            matched_right.add(j)

    if how == "left_semi":
        keep = sorted({i for i, _ in pairs})
        return left.iloc[keep].reset_index(drop=True)
    if how == "left_anti":
        keep = [i for i in range(len(left)) if not l_matched[i]]
        return left.iloc[keep].reset_index(drop=True)

    # column lists once: a pandas scalar .iloc per cell costs ~10 us
    lvals = [left[c].tolist() for c in lnames]
    rvals = [right[c].tolist() for c in rnames]
    rows = []
    for (i, j) in pairs:
        rows.append([v[i] for v in lvals] + [v[j] for v in rvals])
    if how in ("left", "full"):
        for i in range(len(left)):
            if not l_matched[i]:
                rows.append([v[i] for v in lvals] + [None] * len(rnames))
    if how in ("right", "full"):
        for j in range(len(right)):
            if j not in matched_right:
                rows.append([None] * len(lnames) + [v[j] for v in rvals])
    # positional build: duplicate column names (self-joins, USING) must not
    # collapse through a dict
    names = lnames + rnames
    out = pd.DataFrame(
        {i: pd.Series([r[i] for r in rows], dtype=object)
         for i in range(len(names))})
    if not len(rows):
        out = pd.DataFrame({i: pd.Series([], dtype=object)
                            for i in range(len(names))})
    out.columns = names
    return out


def _extract_equi_keys(cond, lnames, rnames):
    from ..ops import predicates as pr_
    lkeys, rkeys = [], []
    residual = None
    if cond is None:
        return lkeys, rkeys, None

    def visit(e):
        nonlocal residual
        if isinstance(e, pr_.And):
            visit(e.children[0])
            visit(e.children[1])
            return
        if isinstance(e, pr_.EqualTo):
            l, r = e.children
            lrefs = {c.col_name for c in l.collect(
                lambda x: isinstance(x, ex.ColumnRef))}
            rrefs = {c.col_name for c in r.collect(
                lambda x: isinstance(x, ex.ColumnRef))}
            if lrefs <= set(lnames) and rrefs <= set(rnames):
                lkeys.append(l)
                rkeys.append(r)
                return
            if lrefs <= set(rnames) and rrefs <= set(lnames):
                lkeys.append(r)
                rkeys.append(l)
                return
        residual = e if residual is None else pr_.And(residual, e)

    visit(cond)
    return lkeys, rkeys, residual


def _rewire_condition(cond, lnames, rnames):
    """Rewrite right-side column refs to the prefixed merged frame columns."""
    def fn(node):
        if isinstance(node, ex.ColumnRef) and node.col_name in rnames \
                and node.col_name not in lnames:
            return ex.ColumnRef(f"__r_{node.col_name}")._copy_resolution(node)
        return None
    # ColumnRef lacks _copy_resolution; simpler: rebuild and re-resolve lazily
    def fn2(node):
        if isinstance(node, ex.ColumnRef):
            nn = ex.ColumnRef(f"__r_{node.col_name}"
                              if node.col_name in rnames and
                              node.col_name not in lnames else node.col_name)
            nn._resolved = node._resolved
            return nn
        return None
    return cond.transform(fn2)


def _exec_sort(plan: lp.Sort) -> pd.DataFrame:
    child = _exec(plan.children[0])
    if not len(child):
        return child
    ev = CpuEvaluator(child)
    keys = [ev.eval(o.child) for o in plan.orders]
    idx = list(range(len(child)))

    def key_fn(i):
        parts = []
        for k, o in zip(keys, plan.orders):
            v = k[i]
            null_rank = 0 if (v is None) == o.nulls_first else 1
            if v is None:
                parts.append((null_rank, 0, b"" if False else 0))
                continue
            ok = _order_key(v)
            if not o.ascending:
                parts.append((null_rank, _Neg(ok)))
            else:
                parts.append((null_rank, _Asc(ok)))
        return tuple(parts)

    idx.sort(key=key_fn)
    return child.iloc[idx].reset_index(drop=True)


class _Asc:
    __slots__ = ("k",)

    def __init__(self, k):
        self.k = k

    def __lt__(self, other):
        return self.k < other.k

    def __eq__(self, other):
        return self.k == other.k


class _Neg:
    __slots__ = ("k",)

    def __init__(self, k):
        self.k = k

    def __lt__(self, other):
        return other.k < self.k

    def __eq__(self, other):
        return self.k == other.k
