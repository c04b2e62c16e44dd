"""Block identity and memory contracts.

Counterpart of the reference's block/memory API surface:

* ``BlockId`` / ``Block`` / ``MemoryBlock`` traits — ShuffleTransport.scala:13-53
* ``UcxShuffleBlockId`` (shuffleId, mapId, reduceId) — UcxShuffleTransport.scala:55-72

Differences by design (TPU-first):

* ``MemoryBlock`` wraps a ``memoryview``/numpy buffer or a ``jax.Array`` rather than a
  raw address; zero-copy views are ordinary array slices instead of
  ``sun.nio.ch.DirectBuffer`` reflection (UnsafeUtils.scala:25-36).
* ``ShuffleBlockId.serialize`` writes all three ids (12 bytes, little-endian int32).
  The reference's fork elides shuffleId and writes 8 bytes
  (UcxShuffleTransport.scala:55-72, "shuffleId commented out") — an acknowledged POC
  shortcut we do not reproduce.
"""

from __future__ import annotations

import struct
import sys
import threading
from abc import ABC, abstractmethod
from dataclasses import dataclass, field
from typing import Optional, Union

import numpy as np

#: Wire format of a ShuffleBlockId: little-endian (shuffle_id, map_id, reduce_id).
_BLOCK_ID_STRUCT = struct.Struct("<iii")


class BlockId(ABC):
    """Opaque identifier of a shuffle block (ShuffleTransport.scala:22-27)."""

    @abstractmethod
    def serialized_size(self) -> int:
        ...

    @abstractmethod
    def serialize(self) -> bytes:
        ...


@dataclass(frozen=True, order=True)
class ShuffleBlockId(BlockId):
    """(shuffleId, mapId, reduceId) triple (UcxShuffleTransport.scala:55-72)."""

    shuffle_id: int
    map_id: int
    reduce_id: int

    def serialized_size(self) -> int:
        return _BLOCK_ID_STRUCT.size

    def serialize(self) -> bytes:
        return _BLOCK_ID_STRUCT.pack(self.shuffle_id, self.map_id, self.reduce_id)

    @staticmethod
    def deserialize(data: Union[bytes, memoryview]) -> "ShuffleBlockId":
        s, m, r = _BLOCK_ID_STRUCT.unpack_from(data)
        return ShuffleBlockId(s, m, r)

    @property
    def name(self) -> str:
        return f"shuffle_{self.shuffle_id}_{self.map_id}_{self.reduce_id}"


BufferLike = Union[np.ndarray, memoryview, bytearray]


def _as_u8(buf: BufferLike) -> np.ndarray:
    """View any writable byte-ish buffer as a 1-D uint8 numpy array (zero copy)."""
    if isinstance(buf, np.ndarray):
        return buf.reshape(-1).view(np.uint8)
    return np.frombuffer(buf, dtype=np.uint8)


@dataclass
class MemoryBlock:
    """A sized region of host or device memory (ShuffleTransport.scala:13-20).

    ``data`` is either a host buffer (numpy uint8 array / memoryview) or a
    ``jax.Array`` resident in HBM.  ``is_host_memory`` mirrors the reference field
    that anticipated GPU buffers (ShuffleTransport.scala:16); here device memory is
    the *normal* case for staged shuffle blocks.

    ``close()`` releases the block back to its owning pool (MemoryPool.scala:22-24);
    pools install ``_on_close``.
    """

    data: object  # np.ndarray[uint8] | jax.Array | memoryview
    size: int
    is_host_memory: bool = True
    #: opaque owning-allocator bookkeeping slot (e.g. the backing slab) —
    #: reserved for the pool that created this block; never interpreted here
    allocator_token: Optional[object] = field(default=None, repr=False)
    _on_close: Optional[callable] = field(default=None, repr=False)
    _closed: bool = field(default=False, repr=False)
    #: sanitize-mode hook (memory/sanitizer.py): called on a close() of an
    #: already-closed block.  Normal mode leaves it None and close() stays
    #: idempotent — the documented contract free-list parking depends on.
    _on_double_close: Optional[callable] = field(default=None, repr=False)

    def host_view(self) -> np.ndarray:
        """1-D uint8 view of the first ``size`` bytes (host memory only)."""
        if not self.is_host_memory:
            raise TransportMemoryError("host_view() on device MemoryBlock")
        return _as_u8(self.data)[: self.size]

    def to_bytes(self) -> bytes:
        if self.is_host_memory:
            return self.host_view().tobytes()
        return np.asarray(self.data).reshape(-1).view(np.uint8)[: self.size].tobytes()

    def close(self) -> None:
        if self._closed:
            if self._on_double_close is not None:
                self._on_double_close(self)  # raises under sanitize mode
            return
        self._closed = True
        if self._on_close is not None:
            try:
                self._on_close(self)
            except BaseException:
                # A failed recycle (e.g. sanitize-mode live-view raise) must
                # leave the block checked out and closeable, not half-dead.
                self._closed = False
                raise

    def rearm(self) -> None:
        """Allocator checkout hook: make ``close()`` live again after a pooled
        block is handed back out.  Blocks parked in a free list stay closed so a
        stale holder's second ``close()`` is a no-op, not a double-free."""
        self._closed = False


class TransportMemoryError(RuntimeError):
    pass


class Block(ABC):
    """Server-side registered block (ShuffleTransport.scala:29-53).

    The reference guards mutation with a ``StampedLock`` (ShuffleTransport.scala:31-34,
    unused in practice); we keep an honest ``threading.RLock`` used by
    ``ShuffleTransport.mutate``.
    """

    def __init__(self) -> None:
        self.lock = threading.RLock()

    @abstractmethod
    def get_size(self) -> int:
        ...

    @abstractmethod
    def get_block(self, dest: BufferLike) -> None:
        """Copy block contents into ``dest`` (at least ``get_size()`` bytes)."""

    def get_memory_block(self) -> MemoryBlock:
        """Materialize into a fresh host MemoryBlock.

        The reference leaves this as an unimplemented stub (``???``,
        ShuffleTransport.scala:43); here it is a working default.
        """
        out = np.empty(self.get_size(), dtype=np.uint8)
        self.get_block(out)
        return MemoryBlock(data=out, size=out.size, is_host_memory=True)

    def memory_view(self) -> Optional[np.ndarray]:
        """Zero-copy serving hook: a stable uint8 view of the block's bytes,
        or None when no such view exists (an unmappable source — the server
        then materializes via ``get_memory_block``).  Serving paths capture
        the view under ``self.lock``; a concurrent ``mutate`` swaps the
        backing array but the captured view keeps the old one alive — the
        same consistent-at-capture semantics as ``get_memory_block``.
        Subclasses should override where a stable view is possible
        (BytesBlock: the payload array; FileBackedBlock: a cached read-only
        mmap): materializing a fresh buffer per fetch costs an allocation, a
        copy and page faults per request on the peer-serving path."""
        return None

    def close(self) -> None:
        """Release resources held for serving (mappings, fds).  Called by the
        transports on block unregistration / shuffle removal; must be safe to
        call more than once, and the block must still be servable afterwards
        (a later ``memory_view``/``get_block`` may recreate the resource)."""


class BytesBlock(Block):
    """A block backed by an in-memory byte buffer (test/loopback helper)."""

    def __init__(self, payload: Union[bytes, np.ndarray]) -> None:
        super().__init__()
        self._payload = _as_u8(np.asarray(bytearray(payload)) if isinstance(payload, (bytes, bytearray)) else payload)

    def get_size(self) -> int:
        return int(self._payload.size)

    def get_block(self, dest: BufferLike) -> None:
        view = _as_u8(dest)
        view[: self._payload.size] = self._payload

    def memory_view(self) -> np.ndarray:
        return self._payload

    def set_payload(self, payload: Union[bytes, np.ndarray]) -> None:
        with self.lock:
            self._payload = _as_u8(
                np.asarray(bytearray(payload)) if isinstance(payload, (bytes, bytearray)) else payload
            )


class FileBackedBlock(Block):
    """Positioned-read block over a file segment.

    Counterpart of ``FileBackedMemoryBlock`` + the resolver's registered blocks that
    do positioned ``FileChannel.read`` (CommonUcxShuffleBlockResolver.scala:37-61).
    Serving goes through a lazily created read-only ``np.memmap`` of the
    segment (``memory_view``), so the peer server's vectored ``sendmsg``
    transmits straight from the page cache — the mmap analogue of
    ``UnsafeUtils.mmap`` (UnsafeUtils.scala:38-56), with no per-fetch read
    or copy.  ``get_block`` stays a plain positioned read for callers that
    want bytes in their own buffer.
    """

    def __init__(self, path: str, offset: int, length: int) -> None:
        super().__init__()
        self.path = path
        self.offset = int(offset)
        self.length = int(length)
        self._mm: Optional[np.ndarray] = None

    def get_size(self) -> int:
        return self.length

    def get_block(self, dest: BufferLike) -> None:
        view = _as_u8(dest)
        with open(self.path, "rb") as f:
            f.seek(self.offset)
            data = f.read(self.length)
        view[: len(data)] = np.frombuffer(data, dtype=np.uint8)

    def memory_view(self) -> Optional[np.ndarray]:
        if self.length == 0:
            return np.empty(0, dtype=np.uint8)
        if self._mm is None:
            try:
                self._mm = np.memmap(
                    self.path, dtype=np.uint8, mode="r",
                    offset=self.offset, shape=(self.length,),
                )
            except (OSError, ValueError):
                return None  # unmappable (e.g. pipe): materialize instead
        return self._mm

    def close(self) -> None:
        """Drop the cached mapping so its fd and pages are released now, not
        never — without this every served spill segment pins an open fd for
        the life of the process (the leak: unregistration never dropped
        ``self._mm``).  The map is unmapped eagerly only when this block holds
        the sole reference; numpy 2.x lets ``mmap.close()`` succeed with live
        views, so closing under an in-flight fetch would turn its captured
        view into a use-after-unmap.  With views outstanding the reference is
        merely dropped and CPython refcounting closes the fd the moment the
        last view dies.  A later ``memory_view`` simply remaps."""
        with self.lock:
            mm, self._mm = self._mm, None
            if mm is None or not isinstance(mm, np.memmap):
                return
            if sys.getrefcount(mm) == 2:  # only `mm` + getrefcount's argument
                try:
                    mm._mmap.close()
                except (AttributeError, BufferError):
                    pass  # numpy internals moved / exporter alive: defer to GC
