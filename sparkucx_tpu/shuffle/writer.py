"""Map-side output writer (L4) — the Spark ``ShuffleMapOutputWriter`` SPI shape.

Counterpart of ``NvkvShuffleMapOutputWriter`` (+ inner ``NvkvShufflePartitionWriter``
/ ``PartitionWriterStream``, NvkvShuffleMapOutputWriter.scala, 274 LoC): one writer
per map task, partitions opened in increasing order (:108), stream writes delegated
to the staged store at a running offset (:228-234), ``close`` records
(offset, length) + padding (:236-246), and ``commit_all_partitions`` packs the
MapperInfo commit blob and ships it through the transport (:116-148, AM id 2).

Differences by design: space is accounted dynamically by the store (no static
``shuffleId*shuffleBlockSize`` carve-up, :94-103) and the commit also returns the
partition-lengths array Spark's scheduler expects (``MapOutputCommitMessage``).
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import numpy as np

from sparkucx_tpu.core.operation import TransportError
from sparkucx_tpu.core.transport import ShuffleTransport
from sparkucx_tpu.store.hbm_store import HbmBlockStore
from sparkucx_tpu.store.writer import MapWriter


class PartitionWriterStream:
    """File-like stream for one reduce partition
    (``PartitionWriterStream``, NvkvShuffleMapOutputWriter.scala:151-226)."""

    def __init__(self, owner: "TpuShuffleMapOutputWriter", reduce_id: int) -> None:
        self._owner = owner
        self.reduce_id = reduce_id
        self.count = 0
        self._closed = False
        self._scratch: Optional[bytearray] = None  # a buffered-path body being received

    def write(self, data: bytes) -> int:
        if self._closed:
            raise TransportError("write to closed partition stream")
        self._owner.map_writer.write(data)
        self.count += len(data)
        return len(data)

    def reserve(self, nbytes: int) -> memoryview:
        """One frame's body of a stream fed from a socket: a writable view of
        ``nbytes`` for the caller to fill and then report with
        ``end_receive`` — the body's own place in staging
        (``MapWriter.reserve``: no copy follows), or a scratch buffer that
        goes through ``write`` when the partition is on the buffered path.
        Errors of admission are raised here, before a byte of the body is
        read."""
        if self._closed:
            raise TransportError("write to closed partition stream")
        view = self._owner.map_writer.reserve(nbytes)
        if view is None:
            self._scratch = bytearray(nbytes)
            view = memoryview(self._scratch)
        return view

    def end_receive(self, nbytes: int, filled: bool) -> None:
        """The view ``reserve`` handed out was filled, or its body never
        fully arrived: then the partition is lost (the map cannot commit; its
        retry writes it again)."""
        scratch, self._scratch = self._scratch, None
        if scratch is None:
            self._owner.map_writer.end_receive(nbytes, filled)
        elif filled:
            self._owner.map_writer.write(scratch)
        if filled:
            self.count += nbytes

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._owner.map_writer.close_partition()
        self._owner.record_partition_length(self.reduce_id, self.count)

    def __enter__(self) -> "PartitionWriterStream":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class TpuShufflePartitionWriter:
    """Per-partition writer handle (``NvkvShufflePartitionWriter``,
    NvkvShuffleMapOutputWriter.scala:150-175)."""

    def __init__(self, owner: "TpuShuffleMapOutputWriter", reduce_id: int) -> None:
        self._owner = owner
        self.reduce_id = reduce_id
        self._stream: Optional[PartitionWriterStream] = None

    def open_stream(self) -> PartitionWriterStream:
        if self._stream is None:
            self._owner.map_writer.open_partition(self.reduce_id)
            self._stream = PartitionWriterStream(self._owner, self.reduce_id)
        return self._stream

    def get_num_bytes_written(self) -> int:
        return self._stream.count if self._stream is not None else 0


class DeviceMapWriter:
    """Device-resident per-map writer (conf.device_staging): a map task's
    output arrives as ``(rows, lane)`` int32 device arrays — a block a call,
    or the task's whole packed output in one — and never visits host memory:
    the block-scatter kernel places it into the shuffle's HBM staging as it is
    written (store/hbm_store.py ``MapWriter.write_partitions_device``).  Same
    sequential protocol and first-commit-wins retry semantics as the host
    ``MapWriter``; this wrapper is the writer-layer surface that enforces the
    conf gate."""

    def __init__(self, store: HbmBlockStore, shuffle_id: int, map_id: int) -> None:
        if not store.conf.device_staging:
            raise TransportError(
                "device staging disabled — set spark.shuffle.tpu.deviceStaging=true"
            )
        self.shuffle_id = shuffle_id
        self.map_id = map_id
        self.map_writer: MapWriter = store.map_writer(shuffle_id, map_id)

    def write_partition(self, reduce_id: int, rows, length: Optional[int] = None) -> None:
        self.map_writer.write_partition_device(reduce_id, rows, length=length)

    def write_partitions(self, packed, reduce_ids: Sequence[int], lengths: Sequence[int]) -> None:
        """The task's packed output in one call: one scatter dispatch."""
        self.map_writer.write_partitions_device(packed, reduce_ids, lengths)

    def commit(self):
        return self.map_writer.commit()


class TpuShuffleMapOutputWriter:
    """One map task's output writer.  Sequential partition protocol enforced by
    the underlying store writer (NvkvShuffleMapOutputWriter.scala:108)."""

    def __init__(
        self,
        store: HbmBlockStore,
        transport: ShuffleTransport,
        shuffle_id: int,
        map_id: int,
        num_partitions: int,
        on_commit: Optional[Callable[[], None]] = None,
    ) -> None:
        self.shuffle_id = shuffle_id
        self.map_id = map_id
        self.num_partitions = num_partitions
        self._transport = transport
        #: called once, after the commit shipped (the manager registers the
        #: map's blocks with its resolver here)
        self._on_commit = on_commit
        self._conf = store.conf
        #: public: the friend writer/stream classes above drive this handle
        self.map_writer: MapWriter = store.map_writer(shuffle_id, map_id)
        self._partition_lengths = np.zeros(num_partitions, dtype=np.int64)
        self._committed = False
        self._last_partition = -1

    def get_partition_writer(self, reduce_id: int) -> TpuShufflePartitionWriter:
        if self._committed:
            raise TransportError("writer already committed")
        if reduce_id <= self._last_partition:
            raise TransportError(
                f"partitions must be requested in increasing order "
                f"(got {reduce_id} after {self._last_partition})"
            )
        if not (0 <= reduce_id < self.num_partitions):
            raise ValueError(f"reduce_id {reduce_id} out of range")
        self._last_partition = reduce_id
        return TpuShufflePartitionWriter(self, reduce_id)

    def write_partition_device(self, reduce_id: int, rows, length: Optional[int] = None) -> None:
        """Device-path partition write: ``rows`` is a ``(r, lane)`` int32
        device array staged without a host round trip — the one-block case of
        ``write_partitions_device``."""
        if length is None:
            length = int(rows.shape[0]) * (rows.shape[1] * 4)
        self._check_device_write([reduce_id])
        self.map_writer.write_partition_device(reduce_id, rows, length=length)
        self._record_device_write([reduce_id], [length])

    def write_partitions_device(self, packed, reduce_ids: Sequence[int], lengths: Sequence[int]) -> None:
        """A map task's whole device output in one call (requires
        spark.shuffle.tpu.deviceStaging=true): ``packed`` is a ``(rows,
        lane)`` int32 array on the executor's device holding the task's
        non-empty blocks back to back in increasing reducer order, each from a
        fresh row; ``reduce_ids`` and ``lengths`` name them and give their
        true byte counts.  One block-scatter dispatch places them into the
        shuffle's device staging; ``packed`` may be deleted as soon as this
        returns.  Follows the same increasing reduce-order protocol as
        ``get_partition_writer`` and records the lengths for the commit
        message."""
        self._check_device_write(reduce_ids)
        self.map_writer.write_partitions_device(packed, reduce_ids, lengths)
        self._record_device_write(reduce_ids, lengths)

    def _check_device_write(self, reduce_ids: Sequence[int]) -> None:
        if not self._conf.device_staging:
            raise TransportError(
                "device staging disabled — set spark.shuffle.tpu.deviceStaging=true"
            )
        if self._committed:
            raise TransportError("writer already committed")
        last = self._last_partition
        for reduce_id in reduce_ids:
            if reduce_id <= last:
                raise TransportError(
                    f"partitions must be requested in increasing order "
                    f"(got {reduce_id} after {last})"
                )
            if not (0 <= reduce_id < self.num_partitions):
                raise ValueError(f"reduce_id {reduce_id} out of range")
            last = reduce_id

    def _record_device_write(self, reduce_ids: Sequence[int], lengths: Sequence[int]) -> None:
        if len(reduce_ids):
            self._last_partition = int(reduce_ids[-1])
            self._partition_lengths[np.asarray(reduce_ids, dtype=np.int64)] = lengths

    def record_partition_length(self, reduce_id: int, count: int) -> None:
        """Called by PartitionWriterStream.close() with the partition's byte
        count (the lengths array is Spark's MapOutputCommitMessage)."""
        self._partition_lengths[reduce_id] = count

    def commit_all_partitions(self) -> np.ndarray:
        """Pack + ship the MapperInfo commit (NvkvShuffleMapOutputWriter.scala:116-148)
        and return per-partition lengths (Spark's MapOutputCommitMessage)."""
        if self._committed:
            raise TransportError("writer already committed")
        info = self.map_writer.commit(ends_task=False)
        self._transport.commit_block(info.pack())
        self._committed = True
        if self._on_commit is not None:
            self._on_commit()
        self.map_writer.end_task()  # span ``write.task``: the commit has shipped
        return self._partition_lengths.copy()

    def abort(self, error: Optional[BaseException] = None) -> None:
        """Drop without committing (task failure/retry path)."""
        self._committed = True
