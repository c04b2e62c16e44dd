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

import ctypes
import glob
import hashlib
import os
import subprocess
import threading
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
