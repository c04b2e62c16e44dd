"""ctypes bindings for the native arena (the jucx/nvkv replacement).

Builds ``libtpushuffle-<key>.so`` from ``arena.cpp`` on first import (g++,
cached next to the source).  ``<key>`` hashes the source text and the compiler
command, so a library left over from different source or flags — the checkout
is copied between machines as it stands on disk, ignored files included — is
never loaded: a changed key is a different file name, which is built here.
If no compiler is available the pure-Python paths keep working,
``native_available()`` returns False and ``build_error()`` says why; callers
that report host-side numbers print both, since which path ran decides them.
"""

from __future__ import annotations

import contextlib
import ctypes
import glob
import hashlib
import os
import subprocess
import threading
import weakref
from typing import Optional

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "arena.cpp")
_CXX = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread"]
_LOCK = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_error: Optional[str] = None
_built_here = False


class TsSegment(ctypes.Structure):
    _fields_ = [
        ("dst_off", ctypes.c_uint64),
        ("src_off", ctypes.c_uint64),
        ("len", ctypes.c_uint64),
    ]


def _so_path() -> str:
    """Library path keyed on what it is built from (source bytes + command)."""
    h = hashlib.sha256(" ".join(_CXX).encode())
    with open(_SRC, "rb") as f:
        h.update(f.read())
    return os.path.join(_DIR, f"libtpushuffle-{h.hexdigest()[:16]}.so")


def _build(so: str) -> Optional[str]:
    # build under a private name and rename: a concurrent process either sees
    # no library (and builds its own) or a complete one, never a partial file
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        proc = subprocess.run(
            _CXX + [_SRC, "-o", tmp], capture_output=True, text=True, timeout=120
        )
        if proc.returncode != 0:
            return f"g++ failed: {proc.stderr[-2000:]}"
        os.replace(tmp, so)
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"build failed: {e}"
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    for stale in glob.glob(os.path.join(_DIR, "libtpushuffle*.so")):
        if stale != so:
            try:
                os.unlink(stale)
            except OSError:
                pass
    return None


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_error, _built_here
    with _LOCK:
        if _lib is not None or _build_error is not None:
            return _lib
        so = _so_path()
        if not os.path.exists(so):
            err = _build(so)
            if err is not None:
                _build_error = err
                return None
            _built_here = True
        try:
            lib = ctypes.CDLL(so)
        except OSError as e:
            _build_error = str(e)
            return None
        lib.ts_alloc_aligned.restype = ctypes.c_void_p
        lib.ts_alloc_aligned.argtypes = [ctypes.c_uint64, ctypes.c_uint64]
        lib.ts_free_aligned.argtypes = [ctypes.c_void_p]
        lib.ts_mlock.restype = ctypes.c_int
        lib.ts_mlock.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.ts_munlock.restype = ctypes.c_int
        lib.ts_munlock.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.ts_shm_open.restype = ctypes.c_void_p
        lib.ts_shm_open.argtypes = [ctypes.c_char_p, ctypes.c_uint64, ctypes.c_int]
        lib.ts_shm_addr.restype = ctypes.c_void_p
        lib.ts_shm_addr.argtypes = [ctypes.c_void_p]
        lib.ts_shm_size.restype = ctypes.c_uint64
        lib.ts_shm_size.argtypes = [ctypes.c_void_p]
        lib.ts_shm_close.argtypes = [ctypes.c_void_p]
        lib.ts_shm_unlink.restype = ctypes.c_int
        lib.ts_shm_unlink.argtypes = [ctypes.c_char_p]
        lib.ts_batch_copy.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p,
            ctypes.POINTER(TsSegment), ctypes.c_uint64, ctypes.c_int,
        ]
        lib.ts_landing_pool_new.restype = ctypes.c_void_p
        lib.ts_landing_pool_new.argtypes = [ctypes.c_uint64, ctypes.c_uint64]
        lib.ts_landing_pool_retire.argtypes = [ctypes.c_void_p]
        lib.ts_landing_pool_stats.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64)]
        lib.ts_version.restype = ctypes.c_uint64
        _lib = lib
        return _lib


def native_available() -> bool:
    return _load() is not None


def build_error() -> Optional[str]:
    _load()
    return _build_error


def built_here() -> bool:
    """True when this process compiled the library (False: a library with the
    current key was already on disk, or the build failed)."""
    _load()
    return _built_here


def _as_np(addr: int, size: int) -> np.ndarray:
    buf = (ctypes.c_uint8 * size).from_address(addr)
    return np.frombuffer(buf, dtype=np.uint8)


class PinnedBuffer:
    """Page-aligned (optionally mlocked) host buffer — the registered-memory
    analogue of the reference's ``ucxContext.memoryMap`` slabs."""

    def __init__(self, size: int, alignment: int = 4096, pin: bool = True) -> None:
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native arena unavailable: {_build_error}")
        self._lib = lib
        self.size = size
        self._ptr = lib.ts_alloc_aligned(size, alignment)
        if not self._ptr:
            raise MemoryError(f"ts_alloc_aligned({size}) failed")
        self.pinned = pin and lib.ts_mlock(self._ptr, size) == 0
        self.array = _as_np(self._ptr, size)

    def close(self) -> None:
        if self._ptr:
            if self.pinned:
                self._lib.ts_munlock(self._ptr, self.size)
            self.array = None
            self._lib.ts_free_aligned(self._ptr)
            self._ptr = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class SharedArena:
    """Named cross-process shared-memory arena — the NVKV-store analogue for
    single-host multi-executor deployments.  The creating process passes
    ``create=True`` and should ``unlink()`` at teardown."""

    def __init__(self, name: str, size: int, create: bool) -> None:
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native arena unavailable: {_build_error}")
        self._lib = lib
        self.name = name
        self.size = size
        self.created = create
        self._handle = lib.ts_shm_open(name.encode(), size, 1 if create else 0)
        if not self._handle:
            raise OSError(f"ts_shm_open({name!r}, create={create}) failed")
        self.array = _as_np(lib.ts_shm_addr(self._handle), size)

    def close(self) -> None:
        if self._handle:
            self.array = None
            self._lib.ts_shm_close(self._handle)
            self._handle = None

    def unlink(self) -> None:
        self._lib.ts_shm_unlink(self.name.encode())

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        if self.created:
            self.unlink()


#: NumPy's C API table holds ``PyDataMem_SetHandler`` at this index since
#: NumPy 1.22 (``numpy/__multiarray_api.h``; the table is the ABI and its
#: indices never move)
_NPY_SET_HANDLER = 304
_set_handler = None  # resolved once by _numpy_set_handler()


def _numpy_set_handler():
    """NumPy's ``PyDataMem_SetHandler`` (NEP 49) as a Python callable, or
    None where this NumPy has none.  It is C API only; it is reached through
    the table NumPy publishes for extension modules (``_ARRAY_API``)."""
    global _set_handler
    if _set_handler is None:
        try:
            try:
                from numpy._core import _multiarray_umath as umath
            except ImportError:  # NumPy 1.x
                from numpy.core import _multiarray_umath as umath
            get_pointer = ctypes.pythonapi.PyCapsule_GetPointer
            get_pointer.restype = ctypes.c_void_p
            get_pointer.argtypes = [ctypes.py_object, ctypes.c_char_p]
            table = get_pointer(umath._ARRAY_API, None)
            addr = (ctypes.c_void_p * (_NPY_SET_HANDLER + 1)).from_address(table)[_NPY_SET_HANDLER]
            _set_handler = ctypes.PYFUNCTYPE(ctypes.py_object, ctypes.py_object)(addr)
        except (ImportError, AttributeError, ValueError, TypeError):
            _set_handler = False
    return _set_handler or None


def _handler_name() -> str:
    try:
        from numpy._core.multiarray import get_handler_name
    except ImportError:  # NumPy 1.x
        from numpy.core.multiarray import get_handler_name
    return get_handler_name()


class LandingPool:
    """Host blocks that come back: a NumPy data allocator (NEP 49) that keeps
    the large blocks its arrays release and hands them to the next array of
    that size, so a buffer of 64 MiB is pages this process already holds
    instead of a fresh mapping a time.

    ``with pool.allocating():`` names the pool the allocator of every NumPy
    array the calling thread creates inside (NumPy keeps the choice in a
    context variable: other threads are not touched) — also of the array the
    JAX runtime allocates for ``copy_to_host_async``.  An array remembers its
    allocator, so wherever its last reference is dropped its block comes back
    here: kept if it has ``min_bytes`` or more, under ``budget_bytes`` (the
    blocks that came back longest ago make room), freed otherwise.  Nothing is ever taken from under a
    live array.  ``None`` from ``create`` where the native library or NumPy's
    hook is missing: the caller then allocates as it always did."""

    NAME = "sparkucx_tpu_landing"

    def __init__(self, lib, handle: int, set_handler) -> None:
        self._lib = lib
        self._handle = handle
        self._set_handler = set_handler
        new_capsule = ctypes.pythonapi.PyCapsule_New
        new_capsule.restype = ctypes.py_object
        new_capsule.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_void_p]
        self._capsule = new_capsule(handle, b"mem_handler", None)
        # the native pool outlives this object (arrays hold its handler); it
        # only stops keeping blocks
        weakref.finalize(self, lib.ts_landing_pool_retire, handle)

    @classmethod
    def create(cls, budget_bytes: int, min_bytes: int = 1 << 20) -> Optional["LandingPool"]:
        lib, set_handler = _load(), _numpy_set_handler()
        if lib is None or set_handler is None or budget_bytes <= 0:
            return None
        handle = lib.ts_landing_pool_new(int(budget_bytes), int(min_bytes))
        if not handle:
            return None
        pool = cls(lib, handle, set_handler)
        with pool.allocating():  # does NumPy take it?
            taken = _handler_name() == cls.NAME
        return pool if taken else None

    @contextlib.contextmanager
    def allocating(self):
        """NumPy arrays the calling thread creates inside come from the pool."""
        previous = self._set_handler(self._capsule)
        try:
            yield self
        finally:
            self._set_handler(previous)

    def stats(self) -> dict:
        """``hits`` / ``misses`` (large allocations served from a kept block /
        freshly allocated), ``kept_blocks`` / ``dropped_blocks`` (released
        blocks taken back / freed: refused, or pushed out by newer ones),
        ``held_bytes``."""
        out = (ctypes.c_uint64 * 6)()
        self._lib.ts_landing_pool_stats(self._handle, out)
        names = ("hits", "misses", "kept_blocks", "dropped_blocks", "held_bytes", "budget_bytes")
        return dict(zip(names, map(int, out)))


def batch_copy(
    dst: np.ndarray,
    src: np.ndarray,
    segments,  # iterable of (dst_off, src_off, length)
    max_threads: int = 0,
) -> None:
    """Copy scattered segments src->dst.  Native threaded path when available,
    else a numpy loop (same semantics)."""
    lib = _load()
    segs = list(segments)
    if lib is None:
        d = dst.reshape(-1).view(np.uint8)
        s = src.reshape(-1).view(np.uint8)
        for dst_off, src_off, length in segs:
            d[dst_off : dst_off + length] = s[src_off : src_off + length]
        return
    arr = (TsSegment * len(segs))(*[TsSegment(d, s, l) for d, s, l in segs])
    dptr = dst.ctypes.data if isinstance(dst, np.ndarray) else dst
    sptr = src.ctypes.data if isinstance(src, np.ndarray) else src
    lib.ts_batch_copy(dptr, sptr, arr, len(segs), max_threads)
