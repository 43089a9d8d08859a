"""Native AddressSpaceAllocator binding + bounce-buffer manager.

Reference: ``AddressSpaceAllocator.scala:22`` (first-fit sub-allocator over a
long address space) + ``BounceBufferManager.scala:35`` (pool of fixed-size
registered buffers carved from ONE allocation) — the shuffle transport's
staging-memory management (SURVEY.md §2.7/§2.8).

The allocator itself is C++ (exec/native/address_space_allocator.cpp),
compiled on first use with g++ and bound via ctypes (no pybind11 in this
image); a pure-python mirror backs environments without a toolchain. The
BounceBufferManager sub-allocates client receive staging out of one host
bytearray arena, so a fetch of N buffers performs one arena allocation
instead of N transient bytearrays.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Dict, Optional

from ..analysis.lockdep import named_lock

_FAIL = (1 << 64) - 1
_lib_lock = named_lock("exec.native_alloc._lib_lock")
_lib: Optional[ctypes.CDLL] = None
_lib_tried = False


def _build_and_load() -> Optional[ctypes.CDLL]:
    """Load the C++ allocator, compiling it from the tracked source on
    first use (the ``.so`` is a build product, never committed). A failed
    build or load warns once and leaves the python mirror in charge."""
    global _lib, _lib_tried
    with _lib_lock:
        if _lib is not None or _lib_tried:
            return _lib
        _lib_tried = True
        here = os.path.dirname(os.path.abspath(__file__))
        src = os.path.join(here, "native", "address_space_allocator.cpp")
        out = os.path.join(here, "native", "_asa.so")
        try:
            if (not os.path.exists(out) or
                    os.path.getmtime(out) < os.path.getmtime(src)):
                # build beside the target and rename: concurrent first
                # users (test workers) must never load a half-written .so
                tmp = f"{out}.{os.getpid()}.tmp"
                subprocess.run(  # lint: lock-blocking-ok one-time toolchain compile must be serialized; every later call hits the cached .so
                    ["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
                     src, "-o", tmp],
                    check=True, capture_output=True, timeout=120)
                os.replace(tmp, out)
            lib = ctypes.CDLL(out)
            lib.asa_create.restype = ctypes.c_void_p
            lib.asa_create.argtypes = [ctypes.c_uint64]
            lib.asa_destroy.argtypes = [ctypes.c_void_p]
            lib.asa_allocate.restype = ctypes.c_uint64
            lib.asa_allocate.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
            lib.asa_free.restype = ctypes.c_int
            lib.asa_free.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
            for f in ("asa_allocated_bytes", "asa_free_block_count",
                      "asa_largest_free"):
                getattr(lib, f).restype = ctypes.c_uint64
                getattr(lib, f).argtypes = [ctypes.c_void_p]
            _lib = lib
        except (OSError, subprocess.SubprocessError, AttributeError) as e:
            detail = getattr(e, "stderr", b"") or b""
            logging.getLogger("spark_rapids_tpu.native").warning(
                "native staging allocator unavailable (%s%s): using the "
                "pure-python mirror", e,
                (": " + detail.decode(errors="replace").strip()[-300:])
                if detail else "")
            _lib = None
        return _lib


class _PyAllocator:
    """Pure-python mirror of the native allocator (toolchain-less hosts)."""

    def __init__(self, size: int):
        self.size = size
        self._free: Dict[int, int] = {0: size} if size else {}
        self._used: Dict[int, int] = {}
        self.allocated_bytes = 0
        self._mu = named_lock("exec.native_alloc._PyAllocator._mu")

    def allocate(self, want: int) -> Optional[int]:
        if want <= 0:
            return None
        with self._mu:
            for off in sorted(self._free):
                ln = self._free[off]
                if ln < want:
                    continue
                del self._free[off]
                if ln > want:
                    self._free[off + want] = ln - want
                self._used[off] = want
                self.allocated_bytes += want
                return off
            return None

    def free(self, off: int) -> None:
        with self._mu:
            ln = self._used.pop(off)
            self.allocated_bytes -= ln
            self._free[off] = ln
            # coalesce neighbours
            offs = sorted(self._free)
            merged: Dict[int, int] = {}
            for o in offs:
                if merged:
                    lo = max(merged)
                    if lo + merged[lo] == o:
                        merged[lo] += self._free[o]
                        continue
                merged[o] = self._free[o]
            self._free = merged

    @property
    def free_block_count(self) -> int:
        with self._mu:
            return len(self._free)

    @property
    def largest_free(self) -> int:
        with self._mu:
            return max(self._free.values(), default=0)

    def close(self) -> None:
        pass


class AddressSpaceAllocator:
    """First-fit sub-allocator over [0, size) — native-backed when g++ is
    available, python otherwise. Thread-safe."""

    def __init__(self, size: int, force_python: bool = False):
        self.size = size
        lib = None if force_python else _build_and_load()
        self._lib = lib
        if lib is not None:
            self._h = lib.asa_create(size)
            self.native = True
        else:
            self._py = _PyAllocator(size)
            self.native = False

    def allocate(self, size: int) -> Optional[int]:
        if self.native:
            off = self._lib.asa_allocate(self._h, size)
            return None if off == _FAIL else off
        return self._py.allocate(size)

    def free(self, offset: int) -> None:
        if self.native:
            if self._lib.asa_free(self._h, offset) != 0:
                raise ValueError(f"free of unallocated offset {offset}")
        else:
            self._py.free(offset)

    @property
    def allocated_bytes(self) -> int:
        return (self._lib.asa_allocated_bytes(self._h) if self.native
                else self._py.allocated_bytes)

    @property
    def free_block_count(self) -> int:
        return (self._lib.asa_free_block_count(self._h) if self.native
                else self._py.free_block_count)

    @property
    def largest_free(self) -> int:
        return (self._lib.asa_largest_free(self._h) if self.native
                else self._py.largest_free)

    def close(self) -> None:
        if self.native and self._h:
            self._lib.asa_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class BounceBufferManager:
    """One host arena + sub-allocated staging windows
    (BounceBufferManager.scala:35: fixed pools over one allocation).
    The shuffle client stages chunk reassembly here."""

    def __init__(self, arena_bytes: int = 64 << 20,
                 force_python: bool = False):
        self.arena = bytearray(arena_bytes)
        self.allocator = AddressSpaceAllocator(arena_bytes,
                                               force_python=force_python)

    def acquire(self, size: int) -> Optional[memoryview]:
        """A writable window of ``size`` bytes, or None when the arena is
        exhausted (caller falls back to a transient buffer — the
        reference throttles instead; our inflight limit already bounds
        concurrent staging)."""
        off = self.allocator.allocate(size)
        if off is None:
            return None
        mv = memoryview(self.arena)[off:off + size]
        self._offsets = getattr(self, "_offsets", {})
        self._offsets[id(mv)] = off
        self._note_arena()
        return mv

    def release(self, mv: memoryview) -> None:
        off = self._offsets.pop(id(mv), None)
        if off is not None:
            mv.release()
            self.allocator.free(off)
            self._note_arena()

    def _note_arena(self) -> None:
        """Track the staging arena's current + peak occupancy on the
        process watermark (service/telemetry): shuffle receive pressure
        becomes scrapeable next to the HBM stores."""
        from ..service.telemetry import watermark
        watermark("native_arena").update(self.allocator.allocated_bytes)
