"""Vectorized helpers over padded-byte-matrix string columns (DESIGN.md §4).

Strings are ``uint8[cap, W]`` zero-padded + ``int32[cap]`` lengths. Lexicographic
comparison on the padded bytes is exact because the zero pad sorts before any real
byte (caveat, documented: strings containing NUL bytes compare as if truncated —
matches the reference's "corner cases fall back" stance for exotic data).
"""

from __future__ import annotations

from typing import Tuple, Union

import jax.numpy as jnp
import numpy as np

from ..columnar import dtypes as dt
from ..columnar.column import Column, Scalar

StrOperand = Union[Column, Scalar]


def scalar_bytes(s: Scalar) -> Tuple[np.ndarray, int]:
    b = s.value.encode("utf-8") if isinstance(s.value, str) else (s.value or b"")
    return np.frombuffer(b, dtype=np.uint8), len(b)


def traced_scalar(v: StrOperand) -> bool:
    """A string literal that reached a fused program as an argument
    (``ops/expressions.string_literal_array``): uint8[W + 4], the bytes
    zero-padded to W and the byte count as four little-endian bytes."""
    return isinstance(v, Scalar) and hasattr(v.value, "shape")


def operand_arrays(v: StrOperand, capacity: int, width: int):
    """(data[cap|1, W], lengths[cap|1]) as jnp arrays padded to ``width``."""
    if traced_scalar(v):
        row, tail = v.value[:-4], v.value[-4:].astype(jnp.int32)
        n = tail[0] | (tail[1] << 8) | (tail[2] << 16) | (tail[3] << 24)
        row = jnp.pad(row, (0, width - row.shape[0]))
        return row[None, :], n[None]
    if isinstance(v, Scalar):
        raw, n = scalar_bytes(v)
        assert n <= width, f"scalar of {n} bytes vs width {width}; use _widths()"
        row = np.zeros((1, width), dtype=np.uint8)
        row[0, :n] = raw
        return jnp.asarray(row), jnp.asarray(np.array([n], dtype=np.int32))
    data = v.data
    if data.shape[1] < width:
        data = jnp.pad(data, ((0, 0), (0, width - data.shape[1])))
    return data, v.lengths


def _widths(lv: StrOperand, rv: StrOperand) -> int:
    w = 1
    for v in (lv, rv):
        if traced_scalar(v):
            w = max(w, int(v.value.shape[0]) - 4)
        elif isinstance(v, Scalar):
            w = max(w, len(scalar_bytes(v)[0]))
        else:
            w = max(w, int(v.data.shape[1]))
    return w


def string_compare(lv: StrOperand, rv: StrOperand, capacity: int) -> jnp.ndarray:
    """Three-way lexicographic compare -> int32[cap] in {-1, 0, 1}."""
    w = _widths(lv, rv)
    ld, _ = operand_arrays(lv, capacity, w)
    rd, _ = operand_arrays(rv, capacity, w)
    d = ld.astype(jnp.int16) - rd.astype(jnp.int16)
    nz = d != 0
    first = jnp.argmax(nz, axis=1)
    any_diff = jnp.any(nz, axis=1)
    byte_cmp = jnp.take_along_axis(d, first[:, None], axis=1)[:, 0]
    out = jnp.where(any_diff, jnp.sign(byte_cmp).astype(jnp.int32), jnp.int32(0))
    return jnp.broadcast_to(out, (capacity,))


def string_equal(lv: StrOperand, rv: StrOperand, capacity: int) -> jnp.ndarray:
    w = _widths(lv, rv)
    ld, ll = operand_arrays(lv, capacity, w)
    rd, rl = operand_arrays(rv, capacity, w)
    eq = jnp.all(ld == rd, axis=1) & (ll == rl)
    return jnp.broadcast_to(eq, (capacity,))
