"""A map task's writer: its buffered chunks, its open partition and the extent
it is received into, its clock marks and counters.  Every change of the
shuffle's staging state is a method of the store (``store/hbm_store.py``),
called here under the store's ``lock`` where its docstring says so: this
module reads the state's geometry and writes none of its fields."""

from __future__ import annotations

import functools
import resource
import threading
from time import perf_counter_ns
from typing import TYPE_CHECKING, List, Optional, Sequence, Tuple

import numpy as np

from sparkucx_tpu.core.definitions import MapperInfo
from sparkucx_tpu.core.operation import TransportError
from sparkucx_tpu.utils.trace import TRACER

if TYPE_CHECKING:  # the store imports this module: nothing of it is needed at run time
    from sparkucx_tpu.store.hbm_store import HbmBlockStore

#: under full tracing, the buffered-path blocks of the process recorded by
#: phase (``write.block`` and its three children): numbers 0, 199, 398, ...
#: counted over every writer of the process since tracing came on.  A prime
#: that divides none of the benchmark's blocks a map task (200, 100, 75, 63),
#: so the sampled reduce ids rotate from task to task; 32 of the 1k job's
#: 6,350 blocks a job.  At one in 37 (172 a job) the traced write of that job
#: was 6 ms, 7%, longer on the chip's host (``PERF.md`` section 6, PR 50).
WRITE_BLOCK_EVERY = 199
_blocks_traced = 0  # benign race between writer threads: a sampling count
_WRITE_BLOCK_PHASES = ("write.block.admit", "write.block.copy", "write.block.record")
#: the calling thread's resource usage, where the platform has it (Linux)
_RUSAGE_THREAD = getattr(resource, "RUSAGE_THREAD", None)


@functools.lru_cache(maxsize=None)
def _kernel_counts_faults() -> bool:
    """Whether a thread's ``ru_minflt`` can be read and the kernel keeps the
    count at all, asked once a process: a process that has imported NumPy has
    faulted thousands of pages in, so a count of 0 for the whole process is a
    kernel that keeps none (a sandboxed one: the chip's host, where the read
    is a 9 us system call that answers 0 through any first touch; ``PERF.md``
    section 6, PR 50).  There a task's rise says nothing and is left out."""
    return _RUSAGE_THREAD is not None and resource.getrusage(resource.RUSAGE_SELF).ru_minflt > 0


def _thread_minor_faults() -> Optional[Tuple[int, int]]:
    """``(thread ident, ru_minflt)`` of the calling thread: page faults that
    needed no I/O — a first touch of a fresh page is one.  None where the
    kernel keeps no such count."""
    if not _kernel_counts_faults():
        return None
    return threading.get_ident(), resource.getrusage(_RUSAGE_THREAD).ru_minflt


def _copy_chunks(staging: np.ndarray, start: int, chunks: Sequence[bytes]) -> None:
    """A buffered block's copy: the writer's ``chunks`` back to back into
    ``staging`` from byte ``start`` — slice assignment, one ``memcpy`` a chunk
    with the interpreter given up."""
    for chunk in chunks:
        n = len(chunk)
        staging[start : start + n] = np.frombuffer(chunk, dtype=np.uint8)
        start += n


class _ChunkCursor:
    """A writer's buffered chunks read in order, a run of bytes at a time:
    what a block staged in pieces copies into each piece.  The runs are views
    of the chunks (``_copy_chunks`` takes them as it takes chunks)."""

    __slots__ = ("_chunks", "_index", "_at")

    def __init__(self, chunks: Sequence[bytes]) -> None:
        self._chunks, self._index, self._at = chunks, 0, 0

    def take(self, nbytes: int) -> List[memoryview]:
        run: List[memoryview] = []
        while nbytes:
            chunk = self._chunks[self._index]
            n = min(nbytes, len(chunk) - self._at)
            run.append(memoryview(chunk)[self._at : self._at + n])
            nbytes -= n
            self._at += n
            if self._at == len(chunk):
                self._index, self._at = self._index + 1, 0
        return run


class MapWriter:
    """Sequential per-map partition writer handle.

    Mirrors the ``NvkvShufflePartitionWriter``/``PartitionWriterStream`` protocol:
    partitions must be opened in increasing reduce order
    (NvkvShuffleMapOutputWriter.scala:108), a partition's bytes stream in via any
    number of ``write`` calls, and ``close_partition`` pads to alignment and
    records (offset, length) (:236-246).

    Concurrency: streamed bytes buffer writer-locally (the role of the
    reference's 8 KB pinned write buffer, NvkvHandler.scala:26,213-242) and
    reach staging at close, in three steps that the buffered close and a
    partition fed from a socket (``reserve`` / ``end_receive``; the daemon's
    ``WritePartition``) share, each a method of the store.  **Atomic under
    the store's lock, before a byte moves** (``take_extent``): the admission
    checks, the tenant charge, the rollover when the region cannot take the
    block, the region allocate and the round's in-flight count.  **Outside the lock**: the bytes reach
    the extent — the copy of the writer's chunks, the socket's ``recv_into``
    — which no block names yet and no other writer can be given; so the map
    tasks of an executor's slots copy into one store AT ONCE, and what a
    task waits at the lock is other tasks' allocates and records, not their
    copies (``lock_wait_ns``).  **Atomic under it again** (``record_extent``):
    the table entry, which names the extent and the round it was taken in.
    ``close_partition`` returns after the record, so a task's ``commit``
    follows the last byte of its last block.  While the shuffle has ONE
    writer open (``_ShuffleState.open_writers``: every cell but the executor
    with task slots) nobody can be kept waiting by a copy, and a buffered
    block keeps the lock through all three steps — the same sequence with
    the release and the second take left out, a microsecond or two a block
    (``unlocked_copy_blocks`` / ``unlocked_copy_bytes`` count the others).

    A rollover MAY interleave with bytes on their way on the RAM arm — the
    completed round's buffer lives on in ``prev_rounds`` and the copy or the
    receive ends in it — and with a partition that is reserved but not closed
    on either arm (its bytes are in the round, wherever the round went).  A
    rollover's disk arm, ``seal``, ``remove_shuffle`` and ``close`` may NOT:
    whoever would read, zero or hand on a round's buffer first waits, on the
    store's condition, until nothing is in flight into it
    (``inflight_wait_ns``); while one waits no new extent is held.  Bytes
    that never fully arrive — a body cut short, a copy that raised — leave
    their extent a hole that no entry names (padding; tenant charge given
    back) and the partition lost: the map cannot commit, so the retry writes
    it again.

    Tracing (``docs/OBSERVABILITY.md``; PR 50): a committed writer is one span
    ``write.task``, from its creation to the end of its commit (``end_task``),
    recorded from clock marks at the commit — nothing is open meanwhile.
    Under full tracing it has the children ``write.task.copy`` and
    ``write.task.lock_wait`` (summed spans of ``_copy_ns`` / ``_lock_wait_ns``:
    no clock is read for them that was not read before), ``write.task.commit``
    and, one buffered-path block in ``WRITE_BLOCK_EVERY`` of the process,
    ``write.block`` with the three phases of its ``close_partition``; and the
    argument ``minor_faults``.  A discarded retry and an aborted writer record
    nothing.  Untraced, a block pays one ``None`` check at its open and two
    at its close.

    A block longer than a peer region (PR 58) is these three steps a
    PIECE: it takes the room its region has, the round rolls, it takes the
    next, and ONE entry names the pieces in order (``_close_split``;
    ``MapperInfo.splits``).  The write has no block-size limit; the device
    write (one scatter a task) keeps its typed refusal.

    Behind the writer (``_PutBehind``; PR 51): where the store will seal the
    shuffle's single round onto its device in pieces, the ``close_partition``
    (or ``end_receive``) that takes a region's used prefix past the end of a
    piece puts that piece from this thread, after the block is recorded and
    outside the store's lock (``HbmBlockStore.put_behind``).  Anywhere else
    a block pays one more ``None`` check under the lock.  The same call, by
    the writer whose record finds one ready, puts a multi-round job's
    completed rounds once they are final (``_EarlyRounds``; PR 57).
    """

    def __init__(self, store: HbmBlockStore, state, map_id: int, discard: bool = False) -> None:
        global _blocks_traced
        self._store = store
        self._state = state
        self.map_id = map_id
        self._last_reduce = -1
        self._open_reduce: Optional[int] = None
        self._chunks: List[bytes] = []
        self._written = 0
        #: ns this writer spent copying payload (``bytes(data)`` in ``write``,
        #: the staging copy in ``close_partition``); joins the store's
        #: ``copy_ns`` counter at ``commit``
        self._copy_ns = 0
        #: ns this writer's ``close_partition`` calls waited for the store's
        #: lock (other writers' copies and rollovers); joins ``lock_wait_ns``
        #: at ``commit``
        self._lock_wait_ns = 0
        #: timed copies and takes of the store's lock beside the one of each
        #: that a buffered block's ``close_partition`` makes (those are counted
        #: at ``commit``, from the table): the summed spans' ``turns``
        self._extra_copies = self._extra_lock_takes = 0
        self._counted = False  # this writer's blocks are in the store's counters
        #: the open partition's extent while it is received in place: the
        #: store's reservation (``take_extent`` makes it and changes it)
        self._resv = None
        self._receiving = False  # between ``reserve`` and ``end_receive``
        self._lost = False  # a body of the open partition never fully arrived
        #: blocks and bytes recorded in place and partitions that went back
        #: to the buffered path; join the store's counters at ``commit``
        self._inplace_blocks = self._inplace_bytes = self._inplace_fallbacks = 0
        #: buffered blocks and bytes copied into their extent outside the
        #: store's lock; join the store's counters at ``commit``
        self._unlocked_blocks = self._unlocked_bytes = 0
        #: First-commit-wins task-retry semantics: when a successful commit for
        #: this map already exists, the retry attempt's writes are swallowed and
        #: commit() returns the existing table — the reference's atomic
        #: check-or-replace protocol (IndexShuffleBlockResolver.scala:161-217:
        #: "if an existing index is valid, keep it and discard this attempt").
        self._discard = discard
        #: ``write.task``: the clock at this writer's creation, 0 where nothing
        #: records (both switches off, a discarded retry) and once recorded
        self._t_open = perf_counter_ns() if (TRACER.recording or TRACER.enabled) and not discard else 0
        #: under full tracing: the sampled blocks' marks, waiting for the
        #: commit; the open block's marks where it is sampled; the thread's
        #: minor faults at creation.  ``None`` untraced: no mark is taken
        self._blocks: Optional[List[Tuple[int, int, List[int]]]] = None
        self._block: Optional[List[int]] = None
        self._faults: Optional[Tuple[int, int]] = None
        self._task: Optional[tuple] = None  # ``commit``'s marks, for ``end_task``
        if not TRACER.enabled:
            _blocks_traced = 0  # tracing is off: the next count starts anew
        elif self._t_open:
            self._blocks = []
            self._faults = _thread_minor_faults()

    def open_partition(self, reduce_id: int) -> None:
        global _blocks_traced
        if self._open_reduce is not None:
            raise TransportError("previous partition still open")
        if reduce_id <= self._last_reduce:
            raise TransportError(
                f"partitions must be opened in increasing reduce order "
                f"(got {reduce_id} after {self._last_reduce})"
            )
        self._state.owner_of(reduce_id)  # validate range
        self._open_reduce = reduce_id
        self._chunks = []
        self._written = 0
        if self._blocks is not None:
            n = _blocks_traced
            _blocks_traced = n + 1
            self._block = [perf_counter_ns()] if n % WRITE_BLOCK_EVERY == 0 else None

    def write(self, data: bytes) -> None:
        if self._open_reduce is None:
            raise TransportError("no open partition")
        if not self._discard:
            if type(data) is bytes:  # bytes(data) would hand it back: no copy to time
                self._chunks.append(data)
            else:
                t0 = perf_counter_ns()
                self._chunks.append(bytes(data))
                self._copy_ns += perf_counter_ns() - t0
                self._extra_copies += 1
        self._written += len(data)

    def close_partition(self) -> None:
        if self._open_reduce is None:
            raise TransportError("no open partition")
        marks = self._block  # a sampled block's clock marks (full tracing)
        if marks is not None:
            marks.append(perf_counter_ns())
        if self._lost:
            self._refuse_unsettled()
        if self._resv is not None and self._close_reserved():
            return
        st, store = self._state, self._store
        reduce_id = self._open_reduce
        passed = False  # this block took its region's final mark past the end of a piece to put
        if not self._discard:
            padded = -(-self._written // st.alignment) * st.alignment
            # watermark gate before taking the lock: a shed write fails typed
            # (retryable ResourceExhaustedError) with nothing allocated
            store.check_memory_pressure("close_partition", padded)
            if padded > st.region_size:  # longer than a region: staged in pieces
                t0, t1, passed = self._close_split(reduce_id, padded)
            else:
                lock = store.lock
                t_lock = perf_counter_ns()
                with lock:
                    self._lock_wait_ns += perf_counter_ns() - t_lock
                    # the only writer open keeps nobody waiting: it keeps the lock
                    # through all three steps (a writer opened meanwhile waits
                    # for this one copy)
                    unlocked = st.open_writers > 1
                    staging, start, resv = store.take_extent(st, reduce_id, padded, None, unlocked)
                    try:
                        round_idx = st.round  # the extent's own: a rollover may interleave with the copy
                        try:
                            if unlocked:
                                # the extent is this writer's alone: no block names
                                # it yet and no other writer can be given it; whoever
                                # would read, zero or hand on its round waits for the
                                # in-flight count
                                lock.release()
                            t0 = perf_counter_ns()
                            _copy_chunks(staging, start, self._chunks)
                            t1 = perf_counter_ns()
                        finally:
                            if unlocked:
                                t_lock = perf_counter_ns()
                                lock.acquire()
                                self._lock_wait_ns += perf_counter_ns() - t_lock
                                self._extra_lock_takes += 1
                                store.receive_ended(st, resv, 0)
                        if st.removed:  # a removal latches ``removed``, then waits for this copy
                            raise TransportError(f"unknown shuffle {st.shuffle_id}")
                    except BaseException as e:
                        self._lost = True  # the map's retry writes the partition again
                        store.lose_extent(st, padded, resv)
                        if isinstance(e, TransportError) or not isinstance(e, Exception):
                            raise  # an interrupt stays an interrupt
                        raise TransportError(
                            f"partition ({self.map_id},{reduce_id}) lost its copy into staging: {e!r}"
                        ) from e
                    passed = store.record_extent(
                        st, (self.map_id, reduce_id), self._written, start, padded, round_idx, resv
                    )
                self._copy_ns += t1 - t0
                if unlocked:
                    self._unlocked_blocks += 1
                    self._unlocked_bytes += self._written
        self._last_reduce = reduce_id
        self._open_reduce = None
        self._chunks = []
        if marks is not None:  # sampled only where ``_blocks`` is: never a discard
            marks += (t0, t1, perf_counter_ns())
            self._blocks.append((reduce_id, self._written, marks))
            self._block = None
        if passed:
            store.put_behind(st)

    def _close_split(self, reduce_id: int, padded: int) -> Tuple[int, int, bool]:
        """``close_partition`` of a block longer than a peer region: it is
        staged as consecutive pieces, each the room its region has in the
        staging round of the moment, the round rolled between two of them
        (``HbmBlockStore.take_piece``), and recorded as ONE entry that names
        the pieces in order (``record_pieces``; ``MapperInfo.splits``).

        What holds for a block holds for this one as a whole: the watermark
        gate (the caller's) and the tenant charge come before any piece, so a
        shed or over-quota block fails typed with nothing allocated, rolled
        or copied; a block that fails later — the shuffle sealed or removed
        under it, a copy that raised — leaves the pieces it had placed as
        holes that no entry names, gives its charge back and loses the
        partition.  Piece by piece it is the three steps of every buffered
        close: the only writer open keeps the lock through all of them; with
        more open each piece is copied outside the lock under its round's
        in-flight count — so a piece's bytes are in its round before that
        round is spilled, put early or handed on — and other writers'
        blocks may land between two pieces (the entry names each piece's
        round and offset; nothing assumes they are neighbours).

        Span ``store.block_split``, once a block recorded, from its first
        extent taken to its record (``docs/OBSERVABILITY.md``); the store's
        counters ``split_blocks`` / ``split_pieces`` / ``split_bytes`` /
        ``split_rollovers``."""
        st, store = self._state, self._store
        lock = store.lock
        pieces: List[Tuple[int, int, int]] = []
        rollovers = copy_ns = 0
        left, cursor = padded, _ChunkCursor(self._chunks)
        t_lock = perf_counter_ns()
        with lock:
            t_first = perf_counter_ns()
            self._lock_wait_ns += t_first - t_lock
            unlocked = st.open_writers > 1
            try:
                while left:
                    staging, start, taken, round_idx, rolled, resv = store.take_piece(
                        st, reduce_id, left, not pieces, unlocked
                    )
                    left -= taken
                    rollovers += rolled
                    nbytes = taken if left else self._written - sum(n for _, _, n in pieces)
                    pieces.append((round_idx, start, nbytes))
                    try:
                        if unlocked:
                            lock.release()
                        t0 = perf_counter_ns()
                        _copy_chunks(staging, start, cursor.take(nbytes))
                        t1 = perf_counter_ns()
                        copy_ns += t1 - t0
                    finally:
                        if unlocked:
                            t_lock = perf_counter_ns()
                            lock.acquire()
                            self._lock_wait_ns += perf_counter_ns() - t_lock
                            self._extra_lock_takes += 1
                            store.receive_ended(st, resv, 0)
                            st.settled(resv)
                if st.removed:  # a removal latches ``removed``, then waits for the copy
                    raise TransportError(f"unknown shuffle {st.shuffle_id}")
            except BaseException as e:
                self._lost = True  # the map's retry writes the partition again
                if pieces:  # the block's charge, taken with its first piece
                    store.lose_extent(st, padded, None)
                if isinstance(e, TransportError) or not isinstance(e, Exception):
                    raise  # an interrupt stays an interrupt
                raise TransportError(
                    f"partition ({self.map_id},{reduce_id}) lost its copy into staging: {e!r}"
                ) from e
            passed = store.record_pieces(
                st, (self.map_id, reduce_id), self._written, padded, pieces, rollovers
            )
        if TRACER.active:  # from clock marks, once the block is recorded: a block that failed records none
            TRACER.record_spans(None, (("store.block_split", t_first, perf_counter_ns(), {
                "map_id": self.map_id, "reduce_id": reduce_id, "executor": store.executor_id,
                "pieces": len(pieces), "bytes": self._written, "rollovers": rollovers,
            }),))
        self._copy_ns += copy_ns
        self._extra_copies += len(pieces) - 1
        if unlocked:
            self._unlocked_blocks += 1
            self._unlocked_bytes += self._written
        return t_first, t_first + copy_ns, passed

    # -- receive in place (a partition fed from a socket) -------------------

    def reserve(self, nbytes: int) -> Optional[memoryview]:
        """The next ``nbytes`` of the open partition as a writable view of
        their place in staging, for the caller to fill from a socket outside
        every lock and then report with ``end_receive``; None when this
        partition is on the buffered path (a retry's discarded writes, a
        partition already fed through ``write``, one whose extent could not
        grow in place): the caller then feeds ``write``.

        Under the store's lock, before a byte is read, this is the buffered
        close's first step (``take_extent``: ``check_memory_pressure``
        before the lock; the sealed / device-mode checks, the tenant charge,
        the rollover when the region cannot take the block).  A partition
        that outgrows a whole region goes back to the buffered path before
        any of that (``inplace_fallbacks``): the buffered close stages it in
        pieces.
        The first frame of a partition takes its extent at the region's tail;
        a further frame grows it while that tail is still the extent's end
        and the region has room, and otherwise the partition goes back to the
        buffered path (``inplace_fallbacks``; the extent stays as padding).
        The round's in-flight count is taken here and given back by
        ``end_receive``."""
        if self._open_reduce is None:
            raise TransportError("no open partition")
        self._refuse_unsettled()
        if self._discard or self._chunks:
            return None
        st, store = self._state, self._store
        total = self._written + nbytes
        if total > st.region_size:
            # longer than a region: no one extent takes it.  The buffered
            # close stages it in pieces (``_close_split``); what was received
            # in place so far goes with it
            if self._resv is None:
                self._inplace_fallbacks += 1
            else:
                with store.lock:
                    self._extra_lock_takes += 1
                    self._unreserve()
            return None
        padded = -(-total // st.alignment) * st.alignment
        held = self._resv.padded if self._resv is not None else 0
        store.check_memory_pressure("reserve_partition", padded - held)
        t_lock = perf_counter_ns()
        with store.lock:
            self._lock_wait_ns += perf_counter_ns() - t_lock
            self._extra_lock_takes += 1
            extent = store.take_extent(st, self._open_reduce, padded, self._resv, True)
            if extent is None:  # the extent cannot grow in place
                self._unreserve()
                return None
            self._receiving = True
            staging, start, self._resv = extent
            at = start + self._resv.filled
            return memoryview(staging)[at : at + nbytes]

    def end_receive(self, nbytes: int, filled: bool) -> None:
        """The receive ``reserve`` handed out has ended: ``filled`` says all
        ``nbytes`` arrived.  Gives the round's in-flight count back and wakes
        whoever waits for it.  A body that did not fully arrive loses the
        partition: its extent stays a hole (padding that no entry names), its
        tenant charge is given back, and the writer refuses to close or
        commit — the map's retry writes it again."""
        st, store = self._state, self._store
        resv = self._resv
        with store.lock:
            self._receiving = False
            if filled:
                self._written += nbytes
                store.receive_ended(st, resv, nbytes)
            else:
                self._lost = True
                store.lose_extent(st, resv.padded, resv)
                store.receive_ended(st, resv, 0)
            engaged = st.put_behind is not None
        if engaged:  # the whole rows received are final now: the put cursor may pass them
            store.put_behind(st)

    def _refuse_unsettled(self) -> None:
        if self._lost or self._receiving:
            raise TransportError(
                f"partition ({self.map_id},{self._open_reduce}) "
                + ("lost a body mid-receive" if self._lost else "has a receive in flight")
            )

    def _unreserve(self) -> None:
        """Back to the buffered path (caller holds the store's lock): what
        was received so far leaves its extent for ``_chunks``, the extent
        stays behind as padding, its tenant charge is given back
        (``close_partition`` charges the whole partition again)."""
        st, store, resv = self._state, self._store, self._resv
        t0 = perf_counter_ns()
        self._chunks.insert(0, store.extent_received(st, resv).tobytes())
        self._copy_ns += perf_counter_ns() - t0
        self._extra_copies += 1
        store.lose_extent(st, resv.padded, resv)
        self._resv = None
        self._inplace_fallbacks += 1

    def _close_reserved(self) -> bool:
        """``close_partition`` of a partition received in place: only the
        table record — the extent was allocated and charged at ``reserve``
        and the bytes are there.  False when ``write`` fed the partition
        after its reservation: it goes back to the buffered path and the
        caller carries on with the allocate + copy."""
        self._refuse_unsettled()
        st, store, resv = self._state, self._store, self._resv
        t_lock = perf_counter_ns()
        with store.lock:
            self._lock_wait_ns += perf_counter_ns() - t_lock
            self._extra_lock_takes += 1
            if st.removed:
                raise TransportError(f"unknown shuffle {st.shuffle_id}")
            if st.sealed:
                raise TransportError(f"shuffle {st.shuffle_id} already sealed")
            if self._chunks:
                self._unreserve()
                return False
            self._inplace_blocks += 1
            self._inplace_bytes += self._written
            # the extent's last row is final now: it may end a piece to put
            passed = store.record_extent(
                st, (self.map_id, self._open_reduce), self._written,
                resv.start, resv.padded, resv.round, resv,
            )
            self._resv = None
        self._last_reduce = self._open_reduce
        self._open_reduce = None
        self._block = None  # received in place: the daemon's phases, no ``write.block``
        if passed:
            store.put_behind(st)
        return True

    def write_partition(self, reduce_id: int, data: bytes) -> None:
        """Convenience: open + write + close in one call."""
        self.open_partition(reduce_id)
        if data:
            self.write(data)
        self.close_partition()

    def write_partition_device(self, reduce_id: int, rows, length: Optional[int] = None) -> None:
        """One device block: the one-block case of ``write_partitions_device``
        (``rows`` is the block's ``(r, lane)`` int32 device array, ``length``
        its true byte count when the last row is padding-tailed; defaults to
        the full ``rows`` extent)."""
        nrows = int(rows.shape[0]) if getattr(rows, "ndim", 0) == 2 else 0
        padded = nrows * self._state.alignment
        if length is None:
            length = padded
        if not (max(padded - self._state.alignment + 1, 0) <= length <= padded):
            raise TransportError(
                f"length {length} inconsistent with {nrows} staged rows of "
                f"{self._state.alignment} B each"
            )
        self.write_partitions_device(rows, [reduce_id], [length])

    def write_partitions_device(self, packed, reduce_ids: Sequence[int], lengths: Sequence[int]) -> None:
        """Device-path write of a map task's output (conf.device_staging):
        ``packed`` is a ``(rows, lane)`` int32 array on the store's device —
        one row per ``alignment`` bytes, already the exchange's wire unit —
        holding the blocks of ``reduce_ids`` back to back in that order, each
        from a fresh row (the bytes of a last row past a block's length should
        be zeros: they travel as padding); ``lengths`` are the blocks' true
        byte counts.  The blocks are placed into the shuffle's device staging
        AT ONCE, by one block-scatter dispatch straight out of ``packed`` (one
        more for each staging round the task crosses): the payload never
        visits host memory, nothing of ``packed`` is kept, and the caller may
        delete it as soon as this returns.  Same protocol and offset table as
        the host path: increasing reduce order across the writer's calls, one
        write per partition, first commit wins (a discarded retry dispatches
        nothing)."""
        if self._open_reduce is not None:
            raise TransportError("previous partition still open")
        st = self._state
        align = st.alignment
        lane = align // 4
        if getattr(packed, "ndim", 0) != 2 or packed.shape[1] != lane:
            raise TransportError(
                f"device partition must be (rows, {lane}) int32, got shape "
                f"{getattr(packed, 'shape', None)}"
            )
        reduce_ids = [int(r) for r in reduce_ids]
        lengths = [int(n) for n in lengths]
        if len(reduce_ids) != len(lengths):
            raise TransportError(f"{len(reduce_ids)} reduce ids for {len(lengths)} lengths")
        last = self._last_reduce
        for reduce_id in reduce_ids:
            if reduce_id <= last:
                raise TransportError(
                    f"partitions must be opened in increasing reduce order "
                    f"(got {reduce_id} after {last})"
                )
            last = reduce_id
        peers = [st.owner_of(r) for r in reduce_ids]  # validates the range
        if any(n < 0 for n in lengths):
            raise TransportError("negative block length")
        nrows = [-(-n // align) for n in lengths]
        total = sum(nrows) * align
        if sum(nrows) > int(packed.shape[0]):
            raise TransportError(
                f"blocks of {sum(nrows)} rows in a packed array of {int(packed.shape[0])}"
            )
        if not reduce_ids:
            return
        if not self._discard:
            if max(nrows) * align > st.region_size:
                raise TransportError(
                    f"a partition of map {self.map_id} exceeds a whole region "
                    f"({st.region_size} B) on the device write, which places a task's blocks "
                    "in one scatter and stages none in pieces — raise stagingCapacity, or "
                    "write this map task through the host path"
                )
            self._store.place_device_blocks(
                st, self.map_id, packed, zip(reduce_ids, peers, lengths, nrows), total
            )
        self._last_reduce = last

    def commit(self, ends_task: bool = True) -> MapperInfo:
        """Commit this map task's outputs — the ``commitAllPartitions`` packing
        (NvkvShuffleMapOutputWriter.scala:116-148).  Returns the MapperInfo blob
        object the transport ships as AM id 2.  For a retry attempt (discard
        mode) this returns the FIRST successful attempt's table.

        ``ends_task``: the span ``write.task`` ends with this call; a caller
        that ships the commit passes False and calls ``end_task`` once it has
        shipped (``TpuShuffleMapOutputWriter.commit_all_partitions``)."""
        if self._open_reduce is not None:
            raise TransportError("commit with open partition")
        t_commit = perf_counter_ns() if self._t_open else 0
        st = self._state
        parts, rounds = [], []
        splits = None  # partition -> pieces, of the blocks staged in more than one
        blocks = nbytes = 0
        for r in range(st.num_reducers):
            e = st.blocks.get((self.map_id, r))
            if e is None:
                parts.append((0, 0))
                rounds.append(0)
            else:
                parts.append((e.offset, e.length))
                rounds.append(e.round)
                blocks += 1
                nbytes += e.length
                if e.pieces is not None:
                    if splits is None:
                        splits = {}
                    splits[r] = e.pieces
        adds, largest = None, 0
        # once a writer; a retry's table is the first attempt's, counted then
        if not (self._discard or self._counted):
            self._counted = True
            largest = max((length for _, length in parts), default=0)
            adds = {
                "staged_blocks": blocks, "staged_bytes": nbytes,
                "copy_ns": self._copy_ns, "lock_wait_ns": self._lock_wait_ns,
                "inplace_blocks": self._inplace_blocks, "inplace_bytes": self._inplace_bytes,
                "inplace_fallbacks": self._inplace_fallbacks,
                "unlocked_copy_blocks": self._unlocked_blocks,
                "unlocked_copy_bytes": self._unlocked_bytes,
            }
        self._store.commit_map(st, self.map_id, adds, largest)
        if t_commit:
            # a buffered block is one copy and one take; a shuffle is staged
            # on the host or on the device, never both
            buffered = 0 if st.device_mode else blocks - self._inplace_blocks
            self._task = (
                t_commit, blocks, nbytes,
                self._copy_ns, buffered + self._extra_copies,
                self._lock_wait_ns, buffered + self._extra_lock_takes,
            )
            if ends_task:
                self.end_task()
        self._copy_ns = self._lock_wait_ns = 0
        return MapperInfo(
            st.shuffle_id, self.map_id, tuple(parts),
            tuple(rounds) if any(rounds) else None, splits,
        )

    def end_task(self) -> None:
        """The committed task's interval ends here: ``write.task`` and, under
        full tracing, its children go to the tracer in one call, from the
        marks this writer took (class docstring).  The summed spans are laid
        end to end from the task's open, as ``read.window.decode`` is; one
        without a turn is left out.  Nothing to do for a writer that recorded
        no marks, or has handed them over."""
        task, self._task = self._task, None
        t_open, self._t_open = self._t_open, 0
        if task is None or not TRACER.active:
            return
        t_commit, blocks, nbytes, copy_ns, copies, lock_ns, lock_takes = task
        t_end = perf_counter_ns()
        args = {
            "shuffle_id": self._state.shuffle_id, "map_id": self.map_id,
            "executor": self._store.executor_id, "blocks": blocks, "bytes": nbytes,
        }
        children: List[tuple] = []
        if self._blocks is not None and TRACER.enabled:
            before, now = self._faults, _thread_minor_faults()
            if before is not None and before[0] == now[0]:  # one thread's count
                args["minor_faults"] = now[1] - before[1]
            t = t_open
            for name, ns, turns in (
                ("write.task.copy", copy_ns, copies),
                ("write.task.lock_wait", lock_ns, lock_takes),
            ):
                if turns:
                    children.append((name, t, t + ns, {"turns": turns}))
                    t += ns
            for reduce_id, length, (t_in, t_close, t_copy, t_copied, t_out) in self._blocks:
                cuts = (t_close, t_copy, t_copied, t_out)
                children.append((
                    "write.block", t_in, t_out, {"reduce_id": reduce_id, "bytes": length},
                    list(zip(_WRITE_BLOCK_PHASES, cuts, cuts[1:])),
                ))
            children.append(("write.task.commit", t_commit, t_end))
        self._blocks = None
        TRACER.record_spans(None, (("write.task", t_open, t_end, args, children),))

    @property
    def is_retry_discard(self) -> bool:
        return self._discard
