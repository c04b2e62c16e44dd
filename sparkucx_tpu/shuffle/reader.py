"""Reduce-side reader (L5) — fetch iterator with windowing, metrics, aggregation.

Counterpart of ``UcxShuffleReader`` + ``UcxShuffleClient``
(compat/spark_3_0/UcxShuffleReader.scala:74-199, UcxShuffleClient.scala:17-96):

* batch fetch of this reducer's blocks, split into request windows of
  ``max_blocks_per_request`` (the client's recursive-halving splitter,
  UcxShuffleClient.scala:53-58, here a plain chunking),
* a pull loop that spins ``transport.progress()`` while results are pending and
  charges the wait to ``fetch_wait_time`` — the reference reflects into Spark's
  private results queue to do this (UcxShuffleReader.scala:110-134); our iterator
  owns its queue so no reflection is needed,
* then the standard deserialize -> aggregate -> sort pipeline
  (UcxShuffleReader.scala:137-199), with pluggable deserializer/aggregator/
  ordering instead of Spark's Serializer/Aggregator/ExternalSorter.

Metrics mirror ``ShuffleReadMetricsReporter``: records_read, remote_bytes_read,
fetch_wait_time (UcxShuffleReader.scala:118-123,148-153).
"""

from __future__ import annotations

import random
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, Iterator, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from sparkucx_tpu.core.block import MemoryBlock, ShuffleBlockId
from sparkucx_tpu.core.operation import (
    BlockNotFoundError,
    ExecutorLostError,
    OperationStatus,
    Request,
    SplitBlockError,
    TenantQuotaExceededError,
    TransportError,
    UnknownTenantError,
)
from sparkucx_tpu.core.transport import ExecutorId, ShuffleTransport
from sparkucx_tpu.memory.pool import MemoryPool
from sparkucx_tpu.utils.trace import TRACER, instant, span

#: under full tracing, the fetch windows whose record turns are timed
#: (``read.window.decode`` / ``read.window.consumer``): numbers 0, 5, 10, ...
#: of the windows this process has opened since tracing came on.  A reader
#: lives for one task; at GroupByTest width a task is one window, but the 1k
#: job's is two (50 + 13 blocks), so an even interval would only ever time
#: the first of a task: odd, the samples rotate
WINDOW_TURNS_EVERY = 5
_windows_traced = 0  # benign race between reader threads: it only picks samples


class _WindowMarks:
    """What a fetch window notes of itself under full tracing, for the
    children of its ``read.window`` span: the time this thread spent fetching
    it (issue and await: one interval, or two where windows are pipelined)
    and, in a sampled window read through ``read()``, the summed turns of the
    deserializer and of the consumer above it."""

    __slots__ = ("fetch_ns", "fetch_turns", "sampled", "decode_ns", "consumer_ns", "turns")

    def __init__(self, sampled: bool) -> None:
        self.fetch_ns = self.fetch_turns = 0
        self.sampled = sampled
        self.decode_ns = self.consumer_ns = self.turns = 0

#: The fail-fast arm of the failure taxonomy (docs/API.md "Failure
#: semantics", machine-checked by analysis ERROR_TAXONOMY): faults every
#: replica answers identically (tenant admission) or that name an executor
#: the membership plane already declared dead.  Retrying burns the failover
#: budget to hit the same wall — the retry path re-raises these immediately.
_FAIL_FAST_ERRORS = (TenantQuotaExceededError, UnknownTenantError, ExecutorLostError, SplitBlockError)


@dataclass
class ShuffleReadMetrics:
    """UcxShuffleReader.scala:118-123,148-153 reporter fields (+ retry count,
    which the reference has no analogue for — it never retries)."""

    records_read: int = 0
    remote_bytes_read: int = 0
    remote_blocks_fetched: int = 0
    fetch_wait_ns: int = 0
    blocks_retried: int = 0
    #: combine/sort runs spilled to disk (the ExternalSorter spill counter)
    spills: int = 0
    #: blocks served by a replica executor after the primary died / hung
    failovers: int = 0
    #: fetch windows or retry attempts abandoned at the fetch deadline
    fetch_timeouts: int = 0
    #: duplicate fetches issued to replica holders for straggling blocks
    hedges_issued: int = 0
    #: hedged fetches that beat the straggling primary (replica bytes won)
    hedge_wins: int = 0
    #: hedged fetches the primary beat (hedge buffer quarantined)
    hedge_losses: int = 0
    #: bytes of the largest fetch window issued: windows are cut by count
    #: (``max_blocks_per_request``), never by bytes
    window_bytes_max: int = 0
    #: blocks (and their bytes) read where they lay: views of this
    #: executor's received shard, no buffer and no copy
    #: (``transport.resident_blocks``)
    resident_blocks: int = 0
    resident_bytes: int = 0
    #: of ``resident_blocks``, the blocks (and their bytes) staged in pieces
    #: — longer than a peer region — that were put together from their
    #: pieces' views into one array: the one copy a borrowed read makes
    #: (span ``read.block_assemble``)
    assembled_blocks: int = 0
    assembled_bytes: int = 0
    #: blocks a transport's fetch copied (or received) into a result buffer;
    #: ``resident_blocks + copied_blocks == remote_blocks_fetched``
    copied_blocks: int = 0
    #: ``read_batches()``: batches handed out, one a block (``records_read``
    #: counts the records in them)
    record_batches: int = 0
    #: blocks (and their bytes) of windows this executor never received — a
    #: task re-placed after its partition's owner was lost — pulled from the
    #: executors that staged them or from their replicas (``_refetch_window``);
    #: counted among ``copied_blocks`` too
    refetched_blocks: int = 0
    refetched_bytes: int = 0
    #: refetched blocks (and their bytes) a replica holder served, the
    #: executor that staged them being dead (each is a ``failovers`` too)
    replica_blocks: int = 0
    replica_bytes: int = 0


class BlockFetchResult:
    """One fetched block.

    ``data`` is served zero-copy: a read-only memoryview of the fetch buffer,
    valid while the result is attached to it.  The streaming ``read()`` path
    calls ``release()`` once the block's deserializer is exhausted, so record
    decoding never copies the payload a second time.  When the fetch iterator
    advances past a result nobody released, it ``detach()``es it — copying the
    bytes out only if the buffer is pooled (about to be recycled), so the
    ``data`` *property* stays valid for collect-into-list consumers; only a
    captured memoryview object itself goes stale at that point.  Constructing
    with a plain ``bytes`` payload keeps the old copying contract.

    A *borrowed* block (``buf is None``, ``pooled=False``: the block already
    lay in this process and the transport handed out
    ``resident_blocks``) has no fetch buffer at all: ``data`` is a read-only
    view of the executor's received shard, which nothing writes while the
    shuffle is registered, and ``release()`` / ``detach()`` copy nothing and
    hand nothing back.  The view holds a reference to its shard: a consumer
    of raw ``fetch_blocks()`` that keeps ``data`` past ``unregister_shuffle``
    keeps that shard's memory (or mapping) alive and goes on reading the
    bytes that were written — never freed memory; one that wants the bytes
    without the shard takes ``bytes(data)``.  ``read()`` keeps nothing: each
    value it yields owns its bytes."""

    __slots__ = ("block_id", "_data", "_buf", "_pooled", "_san", "_released")

    def __init__(
        self,
        block_id: ShuffleBlockId,
        data,
        buf: Optional[MemoryBlock] = None,
        pooled: bool = False,
        sanitizer=None,
    ) -> None:
        self.block_id = block_id
        self._data = data
        self._buf = buf
        self._pooled = pooled
        self._san = sanitizer
        self._released = False
        if sanitizer is not None:
            sanitizer.export_view(buf)

    @property
    def pooled(self) -> bool:
        """``data`` lies in a pooled fetch buffer that is recycled at
        ``release()`` / ``detach()``: bytes kept longer must be copied out."""
        return self._pooled

    @property
    def data(self):
        if self._released and self._san is not None:
            self._san.check_view_released(
                f"BlockFetchResult({self.block_id.name}).data"
            )
        return self._data

    def release(self) -> None:
        """Consumer is done with ``data``: hand the fetch buffer back without
        any copy.  ``data`` must not be touched afterwards — under sanitize
        mode a later ``data`` access raises; in normal mode a pooled result
        degrades to ``b""``.  Idempotent in BOTH modes (the fetch iterator's
        ``finally: detach()`` safety net depends on it)."""
        buf, self._buf = self._buf, None
        if buf is not None:
            if self._san is not None:
                self._san.release_view(buf)
            if self._pooled:
                self._data = b""
                self._released = True
            buf.close()

    def detach(self) -> None:
        """Make ``data`` outlive the buffer: copy it out if (and only if) the
        buffer is pooled, then hand the buffer back.  Idempotent; ``data``
        stays valid afterwards (it is a private copy), so this never trips
        the use-after-release check."""
        buf, self._buf = self._buf, None
        if buf is not None:
            if self._pooled:
                self._data = bytes(self._data)
            if self._san is not None:
                self._san.release_view(buf)
            buf.close()


class DeviceRead(NamedTuple):
    """What ``TpuShuffleReader.read_device`` returns: one reduce task's blocks
    on its executor's device."""

    #: (rows, lane) int32 ``jax.Array`` on the owning executor's device; rows
    #: that no entry of ``table`` covers are unspecified
    packed: Any
    #: (B, 2) int64 — per block of ``block_ids``, its starting row in
    #: ``packed`` and its true byte length
    table: np.ndarray
    #: the blocks, in (reduce, map) order
    block_ids: List[ShuffleBlockId]


class OrderedDeviceRead(NamedTuple):
    """What ``TpuShuffleReader.read_device`` returns under ``key_ordering``:
    one reduce task's fixed-width records on its executor's device, sorted
    there."""

    #: (capacity, record_bytes / 4) int32 ``jax.Array`` on the owning
    #: executor's device: its first ``num_records`` rows are the task's
    #: records in non-decreasing order of their first ``key_bytes`` bytes
    #: (unsigned, most significant first; equal keys in any order), the rows
    #: after them zero.  ``capacity`` is one figure a shuffle, not the task's.
    records: Any
    #: the task's record count, from the block table
    num_records: int
    #: the blocks the records came from, in (reduce, map) order
    block_ids: List[ShuffleBlockId]


def default_deserializer(payload: bytes) -> Iterable[Any]:
    """Record stream per block (the Spark serializer-stream analogue).

    Decodes the typed, NON-EXECUTING wire format of utils/codec.py — block
    payloads arrive from peers over sockets, and the default codec must not
    be an arbitrary-code-execution surface the way Spark's JavaSerializer
    (or pickle) is.  Malformed frames raise ``ValueError``.  For trusted
    single-host runs needing arbitrary Python objects, pass
    :func:`pickle_deserializer` explicitly."""
    from sparkucx_tpu.utils.codec import decode_records

    yield from decode_records(payload)


def serialize_records(records: Iterable[Any]) -> bytes:
    """Writer-side twin of ``default_deserializer`` (typed safe codec)."""
    from sparkucx_tpu.utils.codec import encode_records

    return encode_records(records)


def pickle_deserializer(payload: bytes) -> Iterable[Any]:
    """OPT-IN pickle record stream — executes whatever the bytes describe, so
    use it only when every peer is trusted (single-host runs, tests needing
    arbitrary object graphs).  Never the default: block payloads are
    peer-controlled socket bytes (see parallel/bootstrap.py's rule)."""
    import io
    import pickle

    if not payload:
        return
    bio = io.BytesIO(payload)
    while bio.tell() < len(payload):
        try:
            yield pickle.load(bio)
        except EOFError:
            return


def pickle_serialize_records(records: Iterable[Any]) -> bytes:
    """Writer-side twin of :func:`pickle_deserializer` (opt-in, trusted runs)."""
    import io
    import pickle

    bio = io.BytesIO()
    for rec in records:
        pickle.dump(rec, bio, protocol=pickle.HIGHEST_PROTOCOL)
    return bio.getvalue()


class RaggedBlockError(ValueError):
    """A block handed to a fixed-width serializer whose length is no whole
    number of records (the typed codec's "malformed frame" rule: refused by
    name, never floored)."""

    def __init__(self, nbytes: int, record_bytes: int, block_id: Optional[ShuffleBlockId] = None) -> None:
        self.block_id = block_id
        self.nbytes = nbytes
        self.record_bytes = record_bytes
        where = f"block {block_id.name}" if block_id is not None else "a block"
        super().__init__(
            f"{where} of {nbytes} B is not a whole number of {record_bytes} B records "
            f"({nbytes % record_bytes} B over)"
        )


class FixedWidthSerializer:
    """Records of one fixed width, back to back with no framing — what Spark
    gives a job like TeraSort through ``ShuffleDependency.serializer``; the
    caller names the width, nothing is detected.  A record is ``record_bytes``
    bytes, its first ``key_bytes`` the key.

    Writer side: ``serialize(rows)`` turns an ``(n, record_bytes)`` ``uint8``
    array into a block's bytes — a view of the array where it is contiguous,
    one copy otherwise.  Reader side: hand the serializer to ``get_reader``
    as its ``deserializer``; ``TpuShuffleReader.read_batches()`` then gives
    each block as ONE batch, ``batch(data)``: a read-only ``(n,
    record_bytes)`` view, no Python a record.  Called as a plain
    deserializer (``read()``) it yields ``(key, value)`` ``bytes`` pairs, a
    record at a time."""

    __slots__ = ("record_bytes", "key_bytes")

    def __init__(self, record_bytes: int, key_bytes: int = 0) -> None:
        if not (record_bytes > 0 and 0 <= key_bytes <= record_bytes):
            raise ValueError(f"record_bytes {record_bytes} / key_bytes {key_bytes}: no such record")
        self.record_bytes = int(record_bytes)
        self.key_bytes = int(key_bytes)

    def serialize(self, rows: np.ndarray) -> memoryview:
        rows = np.asarray(rows)
        if rows.dtype != np.uint8 or rows.ndim != 2 or rows.shape[1] != self.record_bytes:
            raise ValueError(
                f"records of shape {rows.shape} {rows.dtype}, not (n, {self.record_bytes}) uint8"
            )
        return memoryview(np.ascontiguousarray(rows).reshape(-1))

    def batch(self, data, block_id: Optional[ShuffleBlockId] = None) -> np.ndarray:
        """The block ``data`` as one batch: a read-only view, no byte moved."""
        flat = np.frombuffer(data, dtype=np.uint8)
        if flat.size % self.record_bytes:
            raise RaggedBlockError(flat.size, self.record_bytes, block_id)
        rows = flat.reshape(-1, self.record_bytes)
        rows.flags.writeable = False
        return rows

    def __call__(self, payload) -> Iterator[Tuple[bytes, bytes]]:
        k = self.key_bytes
        for row in self.batch(payload):
            raw = row.tobytes()
            yield raw[:k], raw[k:]


def _timed_turns(records: Iterable[Any], marks: _WindowMarks) -> Iterator[Any]:
    """``yield from records`` with the clock read at every hand-over: the
    time inside ``next`` is the deserializer's turn, the time this generator
    is suspended at its ``yield`` is the turn of everything above it (the
    caller of ``read()``).  Both are summed into the window's marks."""
    clock = time.perf_counter_ns
    it = iter(records)
    decode_ns = consumer_ns = turns = 0
    try:
        t = clock()
        for rec in it:
            t_out = clock()
            decode_ns += t_out - t
            yield rec
            t = clock()
            consumer_ns += t - t_out
            turns += 1
        decode_ns += clock() - t  # the turn that found the block exhausted
    finally:
        marks.decode_ns += decode_ns
        marks.consumer_ns += consumer_ns
        marks.turns += turns


class TpuShuffleReader:
    """Reads the blocks of reduce partitions [start_partition, end_partition)
    for one reducer — ``ShuffleReader.read()`` (UcxShuffleReader.scala:74)."""

    def __init__(
        self,
        transport: ShuffleTransport,
        executor_id: ExecutorId,
        shuffle_id: int,
        start_partition: int,
        end_partition: int,
        num_mappers: int,
        block_sizes: Callable[[int, int], int],
        max_blocks_per_request: int = 50,
        pool: Optional[MemoryPool] = None,
        deserializer: Callable[[bytes], Iterable[Any]] = default_deserializer,
        aggregator: Optional[Callable[[Any, Any], Any]] = None,
        key_ordering: bool = False,
        sender_of: Optional[Callable[[int], ExecutorId]] = None,
        fetch_retries: int = 1,
        memory_budget: int = 64 << 20,
        spill_dir: Optional[str] = None,
        merge_combiners: Optional[Callable[[Any, Any], Any]] = None,
        credit_bytes: int = 0,
        replica_of: Optional[Callable[[ExecutorId], Sequence[ExecutorId]]] = None,
        fetch_deadline_ms: int = 0,
        fetch_backoff_ms: int = 50,
        fetch_hedge_ms: int = 0,
        fetch_hedge_max_ms: int = 0,
        holders_of: Optional[Callable[[ExecutorId, int], Sequence[ExecutorId]]] = None,
        received_by: Optional[ExecutorId] = None,
    ) -> None:
        self.transport = transport
        self.executor_id = executor_id
        #: the executor an exchange delivered this reader's partitions to (a
        #: collective transport: after ``run_exchange`` block (m, r) lies in
        #: the received shards of r's owner, whoever staged it); None where
        #: blocks lie with their senders until they are fetched (the wire
        #: transport).  A reader placed on ANOTHER executor — a task the
        #: engine re-placed because that one was lost — never received its
        #: windows and pulls every block (``_refetch_window``).
        self.received_by = received_by
        self.shuffle_id = shuffle_id
        self.start_partition = start_partition
        self.end_partition = end_partition
        self.num_mappers = num_mappers
        self.block_sizes = block_sizes
        self.max_blocks_per_request = max(1, max_blocks_per_request)
        self.pool = pool
        self.deserializer = deserializer
        self.aggregator = aggregator
        self.key_ordering = key_ordering
        if key_ordering and isinstance(deserializer, FixedWidthSerializer):
            # the ordered return over fixed-width records is the device's
            # (read_device / read_batches): records are rows of 32-bit lanes
            if deserializer.record_bytes % 4 or not deserializer.key_bytes:
                raise ValueError(
                    f"key_ordering over fixed-width records needs record_bytes a multiple of 4 and a "
                    f"key, not {deserializer.record_bytes} B records with {deserializer.key_bytes} B keys"
                )
        if sender_of is None:
            # binds the id, not the reader: a lambda over ``self`` would put
            # every reader in a reference cycle, and with it the shuffle
            # state its ``block_sizes`` closure holds
            sender_of = lambda m, _local=executor_id: _local  # noqa: E731
        self.sender_of = sender_of
        self.fetch_retries = max(0, fetch_retries)
        self.memory_budget = memory_budget
        self.spill_dir = spill_dir
        self.merge_combiners = merge_combiners
        #: byte budget for credit-based fetch pipelining: issue request
        #: windows ahead of consumption while their result-buffer bytes fit
        #: the budget (``spark.shuffle.tpu.wire.creditBytes``); 0 = the
        #: historical strictly-serial window loop.  Credits account DECODED
        #: bytes (``block_sizes`` is the logical block size, which is what
        #: the result buffers hold) — wire compression (``compress.codec``)
        #: shrinks what travels, never what this budget meters, so a codec
        #: change cannot silently over-issue receive buffers.
        self.credit_bytes = max(0, credit_bytes)
        #: primary executor -> its replica executors (replication-ring
        #: successors; shuffle/resolver.ring_neighbors) — where a block is
        #: re-resolved when the primary dies.  None/empty = no failover.
        self.replica_of = replica_of
        #: per-window (and per retry attempt) completion deadline; a window
        #: that misses it is failed locally and enters the retry/failover path
        #: instead of spinning forever on a hung peer.  0 = wait forever.
        self.fetch_deadline_ms = max(0, fetch_deadline_ms)
        #: base for the jittered, doubling backoff between retry attempts
        self.fetch_backoff_ms = max(0, fetch_backoff_ms)
        #: hedged-fetch floor (``fetch.hedgeMs``): with a window still
        #: incomplete after max(floor, observed rx stall p99), a DUPLICATE
        #: request for each straggling block goes to a replica holder; the
        #: first completion wins bit-identically and the loser's buffer is
        #: quarantined via ``_abandoned``.  0 = hedging off (the default).
        self.fetch_hedge_ms = max(0, fetch_hedge_ms)
        #: hedge-delay ceiling (``fetch.hedgeMaxMs``) clamping the p99-derived
        #: delay, so one pathological stall sample cannot defer hedging
        #: forever.  0 = no ceiling.
        self.fetch_hedge_max_ms = max(0, fetch_hedge_max_ms)
        #: timed-out fetches whose result buffer may still be a recv-thread
        #: scatter target — kept alive until their request completes, then
        #: closed by _sweep_abandoned (single reader thread; no lock)
        self._abandoned: List[Tuple[MemoryBlock, Request]] = []
        #: popularity-aware load spreading: ``holders_of(primary, shuffle_id)``
        #: returns the CURRENT holder set the primary advertises for a hot
        #: shuffle (transport.hot_holders — widened replica sets learned via
        #: HOT_SET_PULL, []/None when cold).  With >1 holder, this reader
        #: deterministically rotates its fetches across them instead of
        #: piling onto the primary.  None = the historical primary-only path.
        self.holders_of = holders_of
        #: where each in-flight block of the current window was ACTUALLY sent
        #: (spread target, not necessarily the primary) — hedges must pick a
        #: different holder than this (single reader thread; no lock)
        self._window_targets: Dict[ShuffleBlockId, ExecutorId] = {}
        #: the marks of the window whose blocks are being yielded, under full
        #: tracing (None otherwise): where ``read()`` adds its record turns
        self._yielding: Optional[_WindowMarks] = None
        #: the executor that served the last block ``_retry_fetch`` returned
        self._pulled_from: Optional[ExecutorId] = None
        self.metrics = ShuffleReadMetrics()

    # -- raw block iterator ------------------------------------------------

    def _block_ids(self) -> List[ShuffleBlockId]:
        return [
            ShuffleBlockId(self.shuffle_id, m, r)
            for r in range(self.start_partition, self.end_partition)
            for m in range(self.num_mappers)
            if self.block_sizes(m, r) > 0
        ]

    def fetch_blocks(self) -> Iterator[BlockFetchResult]:
        """Windowed fetch of all non-empty blocks; yields as windows complete.

        Window size caps one request like ``maxBlocksPerRequest``
        (UcxShuffleConf.scala:88-93); the spin between windows is charged to
        fetch_wait (UcxShuffleReader.scala:118-123).  With ``credit_bytes``
        set, later windows are issued AHEAD of consumption while their bytes
        fit the budget (credit-based pipelining: the wire fills the next
        windows' buffers while this thread deserializes the current one);
        yield order is window order either way, and ``credit_bytes == 0`` is
        the historical strictly-serial loop."""
        bids = self._block_ids()
        windows = [
            bids[w : w + self.max_blocks_per_request]
            for w in range(0, len(bids), self.max_blocks_per_request)
        ]
        if self.credit_bytes > 0 and len(windows) > 1:
            yield from self._fetch_windows_pipelined(windows)
            return
        for window in windows:
            # open the window span BEFORE issuing: with obs.traceContext on,
            # the fetch request carries (trace_id, span_id) over the wire and
            # every server's serve span — primary or replica — parents here
            wctx = self._start_window_span(len(window))
            marks = self._window_marks(wctx)
            try:
                with TRACER.activate(wctx):
                    requests = self._issue_window(window, wctx)
                    self._await_window(requests, len(window))
                if marks is not None:  # issue and await: one real interval
                    marks.fetch_ns = time.perf_counter_ns() - wctx.t0
                    marks.fetch_turns = 1
                    self._yielding = marks
                yield from self._yield_window(requests, wctx)
            finally:
                self._end_window_span(wctx, marks)
        self._sweep_abandoned()
        self._flush_read_counters()

    def _fetch_windows_pipelined(self, windows) -> Iterator[BlockFetchResult]:
        from collections import deque

        from sparkucx_tpu.transport.pipeline import CreditGate

        gate = CreditGate(self.credit_bytes)
        costs = [
            sum(self.block_sizes(b.map_id, b.reduce_id) for b in w) for w in windows
        ]
        issued: deque = deque()  # (window, wctx, marks, requests, cost) awaiting completion
        nxt = 0
        while nxt < len(windows) or issued:
            while nxt < len(windows):
                cost = costs[nxt]
                if not issued:
                    gate.acquire(cost)  # head window always admits (oversized-alone)
                elif not gate.try_acquire(cost):
                    break  # budget full: stop issuing ahead
                # per-window span opened at ISSUE time: windows overlap, so
                # each carries its own explicit ctx rather than the thread
                # stack (start_span/end_span straddle the pipeline)
                wctx = self._start_window_span(len(windows[nxt]))
                marks = self._window_marks(wctx)
                with TRACER.activate(wctx):
                    reqs = self._issue_window(windows[nxt], wctx)
                if marks is not None:
                    marks.fetch_ns = time.perf_counter_ns() - wctx.t0
                issued.append((windows[nxt], wctx, marks, reqs, cost))
                nxt += 1
            window, wctx, marks, requests, cost = issued.popleft()
            try:
                t_await = time.perf_counter_ns() if marks is not None else 0
                with TRACER.activate(wctx):
                    self._await_window(requests, len(window))
                if marks is not None:
                    # issued ahead and awaited here, other windows drained
                    # between: the two turns this thread spent on the fetch
                    marks.fetch_ns += time.perf_counter_ns() - t_await
                    marks.fetch_turns = 2
                    self._yielding = marks
                yield from self._yield_window(requests, wctx)
            finally:
                self._end_window_span(wctx, marks)
                # credits return when the window is consumed (or the caller
                # abandons the iterator / a fetch raises or times out) — the
                # gate drains to zero either way, so one dead peer's windows
                # can never wedge the pipeline's budget
                gate.release(cost)
        self._sweep_abandoned()
        self._flush_read_counters()

    def _spread_target(self, bid: ShuffleBlockId) -> ExecutorId:
        """Where to send the fetch for ``bid``: the primary, unless the
        primary advertises a widened holder set for this (hot) shuffle — then
        a deterministic-per-reader rotation over the sorted holders, so N
        concurrent reducers spread a fan-in across every holder instead of
        piling onto one server, while any single reader stays deterministic
        (retries and the bit-equality contract rely on that).  A block an
        exchange delivered (``received_by``) is fetched where it was
        received, whoever staged it."""
        if self.received_by is not None:
            return self.received_by
        primary = self.sender_of(bid.map_id)
        if self.holders_of is None:
            return primary
        try:
            holders = sorted(set(self.holders_of(primary, bid.shuffle_id) or ()))
        except (TransportError, OSError):
            return primary  # advertisement pull failed: serve from primary
        # never rotate onto ourselves: a co-located copy is the local store
        # path's business, and the wire transport has no loopback connection
        # to its own executor (falling out of _issue_window unguarded)
        holders = [e for e in holders if e != self.executor_id]
        if len(holders) < 2 or primary not in holders:
            return primary
        return holders[
            (self.executor_id + bid.map_id + bid.reduce_id) % len(holders)
        ]

    def _issue_window(
        self, window: List[ShuffleBlockId], wctx=None
    ) -> List[Tuple[ShuffleBlockId, Any, Optional[Request]]]:
        """Issue one window's fetches.  Its bytes, once a window: the
        ``bytes`` argument of its ``read.window`` span (``wctx``) and
        ``metrics.window_bytes_max``.

        Blocks addressed to this reader's own executor, on a transport that
        hands out ``resident_blocks``, are already in this process: they are
        borrowed where they lie — ``(bid, view, None)``, no buffer and no
        request.  Everything else, and a borrow that raises, is a fetch into
        a result buffer — ``(bid, buf, req)`` — whose copying path names the
        block at fault and fails it alone, into ``_retry_fetch``.  A borrow
        that raises ``ExecutorLostError`` — the shards died with the executor
        that received them — ends the task there: no fetch can find them.

        A window this reader's executor never received (``received_by`` is
        another executor: a re-placed task) is pulled, a block at a time,
        from where it was staged or replicated (``_refetch_window``)."""
        sizes = [self.block_sizes(bid.map_id, bid.reduce_id) for bid in window]
        nbytes = sum(sizes)
        if nbytes > self.metrics.window_bytes_max:
            self.metrics.window_bytes_max = nbytes
        if wctx is not None:
            wctx.args["bytes"] = nbytes
        if self.received_by is not None and self.received_by != self.executor_id:
            return self._refetch_window(window, sizes, wctx)
        groups: dict = {}
        for bid, size in zip(window, sizes):
            target = self._spread_target(bid)
            self._window_targets[bid] = target
            groups.setdefault(target, []).append((bid, size))
        resident = getattr(self.transport, "resident_blocks", None)
        requests: List[Tuple[ShuffleBlockId, Any, Optional[Request]]] = []
        for sender, items in groups.items():
            bids = [bid for bid, _ in items]
            if resident is not None and sender == self.executor_id:
                assembled: List[int] = []
                try:
                    views = resident(bids, assembled)
                except ExecutorLostError:
                    raise  # what received them is dead: typed, at once, no byte
                except Exception:
                    pass  # the fetch below fails the block at fault, alone
                else:
                    requests.extend((bid, view, None) for bid, view in zip(bids, views))
                    if assembled:  # blocks staged in pieces: one copy each
                        self.metrics.assembled_blocks += len(assembled)
                        self.metrics.assembled_bytes += sum(assembled)
                    continue
            buffers = self._alloc_bufs([size for _, size in items])
            reqs = self.transport.fetch_blocks_by_block_ids(
                sender, bids, buffers, [None] * len(items)
            )
            requests.extend(zip(bids, buffers, reqs))
        return requests

    def _refetch_window(
        self, window: List[ShuffleBlockId], sizes: List[int], wctx=None
    ) -> List[Tuple[ShuffleBlockId, Any, Optional[Request]]]:
        """Pull one window of a partition this executor never received: the
        task was re-placed here after the executor the exchange delivered its
        blocks to was lost, and their received copy went with it.  Each block
        comes through the pull path (``_retry_fetch``) from the executor that
        staged it (``sender_of``) or, that one being dead, from a replica
        holder (``replica_of``); no batch fetch is tried first: this executor
        has no shard to slice.  Returns completed requests, a block each.

        Span ``read.refetch``, once a window (``args``: ``blocks``, ``bytes``,
        ``from_replica``), made from clock marks as a child of the window's
        ``read.window``; under full tracing its children ``read.refetch.block``
        a pulled block (``executor``, ``replica``, ``bytes``).  Counters
        ``refetched_blocks`` / ``refetched_bytes`` / ``replica_blocks`` /
        ``replica_bytes``."""
        clock = time.perf_counter_ns
        marks = [] if wctx is not None and TRACER.enabled else None
        metrics = self.metrics
        replicas0 = metrics.replica_blocks
        requests: List[Tuple[ShuffleBlockId, Any, Optional[Request]]] = []
        t0 = t = clock()
        try:
            for bid, size in zip(window, sizes):
                result, buf = self._retry_fetch(bid, None, None, refetch=True)
                metrics.refetched_blocks += 1
                metrics.refetched_bytes += size
                req = Request(result.stats)
                req.complete(result)
                requests.append((bid, buf, req))
                if marks is not None:
                    served_by, t_prev, t = self._pulled_from, t, clock()
                    marks.append(("read.refetch.block", t_prev, t, {
                        "executor": served_by, "bytes": size,
                        "replica": served_by != self.sender_of(bid.map_id),
                    }))
        except BaseException:
            for _, buf, _ in requests:
                buf.close()
            raise
        finally:
            if wctx is not None:
                with TRACER.executor_scope(self.executor_id):
                    TRACER.record_spans(wctx, ((
                        "read.refetch", t0, clock(),
                        {"blocks": len(requests), "bytes": sum(sizes[: len(requests)]),
                         "from_replica": metrics.replica_blocks - replicas0},
                        marks or (),
                    ),))
        return requests

    def _start_window_span(self, num_blocks: int):
        """Open the per-window ``read.window`` span (explicit start/end: the
        pipelined path overlaps windows, so the span can't live on the
        thread-local stack).  Ended by ``_end_window_span`` in the read
        loop's ``finally``.  None when tracing is off."""
        if not TRACER.active:
            return None
        with TRACER.executor_scope(self.executor_id):
            return TRACER.start_span(
                "read.window", shuffle_id=self.shuffle_id, blocks=num_blocks
            )

    def _window_marks(self, wctx) -> Optional[_WindowMarks]:
        """The marks of the window whose span was just opened: under full
        tracing only (the children of ``read.window`` are ``enabled``-only;
        the span itself is the flight recorder's too).  One window in
        ``WINDOW_TURNS_EVERY`` of the process has its record turns timed."""
        global _windows_traced
        if wctx is None or not TRACER.enabled:
            _windows_traced = 0  # tracing is off: the next count starts anew
            return None
        n = _windows_traced
        _windows_traced = n + 1
        return _WindowMarks(n % WINDOW_TURNS_EVERY == 0)

    def _end_window_span(self, wctx, marks: Optional[_WindowMarks] = None) -> None:
        """Close ``read.window`` and, from the window's marks, lay its
        children inside it, end to end from its open: ``read.window.fetch``,
        the time this thread spent fetching the window (issue, await, the copy
        out of the received shards) — the real interval from the window's
        open where issue and await are one, a summed span of two ``turns``
        where the window was issued ahead of consumption; then — a sampled
        window that ``read()`` drained — the summed spans
        ``read.window.decode`` and ``read.window.consumer``.  Their turns
        interleave record by record, so each is one event whose ``dur`` is
        the sum of its turns (docs/OBSERVABILITY.md "summed span"); what is
        left of the window after them is the hand-off of its blocks and, of a
        pipelined window, the time it waited its turn."""
        self._yielding = None
        if wctx is None:
            return
        with TRACER.executor_scope(self.executor_id):
            TRACER.end_span(wctx)
            if marks is None or not marks.fetch_turns:
                return
            fetched = wctx.t0 + marks.fetch_ns
            TRACER.record_spans(
                wctx,
                (("read.window.fetch", wctx.t0, fetched),),
                args={"turns": 2} if marks.fetch_turns == 2 else None,
            )
            if marks.sampled and marks.turns:
                decoded = fetched + marks.decode_ns
                TRACER.record_spans(
                    wctx,
                    (
                        ("read.window.decode", fetched, decoded),
                        ("read.window.consumer", decoded, decoded + marks.consumer_ns),
                    ),
                    args={"turns": marks.turns},
                )

    def _flush_read_counters(self) -> None:
        """Surface the reader's telemetry through the transport's
        StatsAggregator, where the metrics registry's ``ops`` provider picks
        it up (``sparkucx_tpu_ops_*_total{kind="read"}``): once a task, how
        its blocks were read — borrowed or copied — and, if any, its
        failover counters, what a re-placed task pulled (``refetched_blocks``
        / ``refetched_bytes`` / ``replica_blocks`` / ``replica_bytes``) and, of
        a batch read, ``record_batches`` / ``batch_records``; of a task that
        read a block staged in pieces, ``assembled_blocks`` / ``assembled_bytes``."""
        agg = getattr(self.transport, "stats_agg", None)
        if agg is None:
            return
        m = self.metrics
        counters = dict(
            resident_blocks=m.resident_blocks,
            resident_bytes=m.resident_bytes,
            copied_blocks=m.copied_blocks,
        )
        if (
            m.failovers
            or m.blocks_retried
            or m.fetch_timeouts
            or m.hedges_issued
        ):
            counters.update(
                failovers=m.failovers,
                blocks_retried=m.blocks_retried,
                fetch_timeouts=m.fetch_timeouts,
                hedges_issued=m.hedges_issued,
                hedge_wins=m.hedge_wins,
                hedge_losses=m.hedge_losses,
            )
        if m.refetched_blocks:
            counters.update(
                refetched_blocks=m.refetched_blocks,
                refetched_bytes=m.refetched_bytes,
                replica_blocks=m.replica_blocks,
                replica_bytes=m.replica_bytes,
                # beside them whether or not any rose
                failovers=m.failovers,
                blocks_retried=m.blocks_retried,
                fetch_timeouts=m.fetch_timeouts,
            )
        if m.record_batches:
            counters.update(record_batches=m.record_batches, batch_records=m.records_read)
        if m.assembled_blocks:
            counters.update(assembled_blocks=m.assembled_blocks, assembled_bytes=m.assembled_bytes)
        agg.record_counters("read", **counters)

    def _hedge_delay_ns(self) -> int:
        """Hedge delay for the current window: max(observed rx stall p99 over
        all wire lanes, the ``fetch.hedgeMs`` floor), clamped to the
        ``fetch.hedgeMaxMs`` ceiling.  0 = hedging off.  The p99 seeds from
        ``wire_lane_stats`` so early windows (no samples yet) hedge at the
        floor and later windows adapt to what this link actually delivers."""
        if self.fetch_hedge_ms <= 0:
            return 0
        floor = self.fetch_hedge_ms * 1_000_000
        delay = floor
        lanes = getattr(self.transport, "wire_lane_stats", None)
        if lanes is not None:
            try:
                for lane in lanes():
                    delay = max(delay, int(lane.get("rx_stall_p99_ns", 0)))
            except Exception:
                delay = floor
        if self.fetch_hedge_max_ms > 0:
            delay = min(delay, max(self.fetch_hedge_max_ms * 1_000_000, floor))
        return delay

    @staticmethod
    def _window_settled(requests, hedges) -> bool:
        """A window is settled once every block's primary request OR its
        hedge has completed — a stalled primary whose hedge already won must
        not keep the window spinning toward the deadline."""
        for i, (_, _, req) in enumerate(requests):
            if req is None or req.completed():  # borrowed: nothing in flight
                continue
            h = hedges.get(i)
            if h is not None and h[1].completed():
                continue
            return False
        return True

    def _issue_hedges(self, requests, hedges) -> None:
        """One duplicate fetch per straggling block, to a different holder.

        Candidates are the advertised hot-set holders (``holders_of``) plus
        the replication-ring successors (``replica_of``), minus the executor
        the straggling fetch was ACTUALLY sent to — racing the same stalled
        server is exactly the failure hedging exists to break — and minus
        (when the transport scores peers) any executor whose circuit breaker
        rejects the probe.  With several admissible holders the pick rotates
        deterministically per (reader, block), spreading hedge load instead
        of always hammering the first ring successor.  Hedge buffers are
        allocated OUTSIDE the credit gate on purpose: hedges exist to break
        stalls, and gating them on credits held by the very window that is
        stalled would deadlock; the overdraft is bounded by one buffer per
        straggling block, and losers drain through the ``_abandoned``
        quarantine."""
        if self.replica_of is None and self.holders_of is None:
            return
        allows = getattr(self.transport, "breaker_allows", None)
        for i, (bid, _, req) in enumerate(requests):
            if req is None or req.completed() or i in hedges:
                continue
            primary = self.sender_of(bid.map_id)
            actual = self._window_targets.get(bid, primary)
            candidates: List[ExecutorId] = []
            if self.holders_of is not None:
                try:
                    candidates += sorted(
                        set(self.holders_of(primary, bid.shuffle_id) or ())
                    )
                except (TransportError, OSError):
                    pass
            if primary not in candidates:
                candidates.append(primary)
            if self.replica_of is not None:
                candidates += [
                    e for e in self.replica_of(primary) if e not in candidates
                ]
            admissible = [
                e
                for e in candidates
                if e != actual
                and e != self.executor_id
                and (allows is None or allows(e))
            ]
            if not admissible:
                continue
            target = admissible[
                (self.executor_id + bid.map_id + bid.reduce_id) % len(admissible)
            ]
            size = self.block_sizes(bid.map_id, bid.reduce_id)
            hbuf = None
            try:
                hbuf = self._alloc_buf(size)
                hreq = self.transport.fetch_block(
                    target, bid.shuffle_id, bid.map_id, bid.reduce_id, hbuf
                )
            except (TransportError, OSError):
                # dead replica or allocation under memory pressure: hedging
                # is best-effort — the primary path still owns correctness
                if hbuf is not None:
                    hbuf.close()
                continue
            hedges[i] = (hbuf, hreq, target)
            self.metrics.hedges_issued += 1
            instant(
                "fetch.hedge",
                shuffle_id=bid.shuffle_id, map_id=bid.map_id,
                reduce_id=bid.reduce_id, executor=target,
            )

    def _resolve_hedges(self, requests, hedges) -> None:
        """First completion wins; the loser's buffer is quarantined (it may
        still be a recv-scatter target) and swept once its request settles.
        Ties — both completed successfully — go to the primary: the bytes are
        bit-identical by the deterministic-refetch contract, and the hedge
        buffer is the one safe to discard either way."""
        record = getattr(self.transport, "record_peer_failure", None)
        for i, (hbuf, hreq, target) in hedges.items():
            bid, buf, req = requests[i]
            primary_ok = (
                req.completed()
                and req.wait(0).status == OperationStatus.SUCCESS
            )
            hedge_won = False
            if not primary_ok and hreq.completed():
                hresult = hreq.wait(0)
                if hresult.status == OperationStatus.SUCCESS:
                    size = self.block_sizes(bid.map_id, bid.reduce_id)
                    if int(hresult.stats.recv_size) != size:
                        hbuf.close()
                        raise TransportError(
                            f"hedged fetch of {bid} from executor {target} "
                            f"returned {hresult.stats.recv_size} B, expected "
                            f"{size} B — replica diverges from primary"
                        )
                    hedge_won = True
            if hedge_won:
                # replica bytes win: quarantine the straggling primary fetch
                # and charge the stall to the primary's health score — a
                # consistently-hedged peer trips its breaker and later
                # fetches route straight to the ring
                self._abandoned.append((buf, req))
                requests[i] = (bid, hbuf, hreq)
                self.metrics.hedge_wins += 1
                if record is not None:
                    record(
                        self._window_targets.get(bid, self.sender_of(bid.map_id)),
                        f"hedged fetch of {bid} lost to replica {target}",
                    )
                instant(
                    "fetch.hedge_win",
                    shuffle_id=bid.shuffle_id, map_id=bid.map_id,
                    reduce_id=bid.reduce_id, executor=target,
                )
            else:
                self._abandoned.append((hbuf, hreq))
                self.metrics.hedge_losses += 1
        hedges.clear()

    def _await_window(self, requests, num_blocks: int) -> None:
        t0 = time.monotonic_ns()
        deadline_ns = self.fetch_deadline_ms * 1_000_000
        hedge_ns = self._hedge_delay_ns()
        hedges: dict = {}  # request index -> (hedge_buf, hedge_req, executor)
        hedged = False
        # wakeup park between polls when the transport supports it
        # (use_wakeup; GlobalWorkerRpcThread.scala:46-58) — a local fetch
        # completes on the first poll so the wait never fires there
        park = getattr(self.transport, "wait_for_activity", None)
        while not self._window_settled(requests, hedges):
            now = time.monotonic_ns()
            if deadline_ns and now - t0 > deadline_ns:
                # hung peer: stop spinning, let _yield_window fail the
                # incomplete fetches over to replicas — this bounds the
                # fetch_wait charge per window to the deadline
                self.metrics.fetch_timeouts += 1
                break
            if hedge_ns and not hedged and now - t0 > hedge_ns:
                hedged = True
                self._issue_hedges(requests, hedges)
            self.transport.progress()
            if park is not None and not self._window_settled(requests, hedges):
                park(0.002)
        self.metrics.fetch_wait_ns += time.monotonic_ns() - t0
        if hedges:
            self._resolve_hedges(requests, hedges)

    def _yield_window(self, requests, wctx=None) -> Iterator[BlockFetchResult]:
        prev: Optional[BlockFetchResult] = None
        metrics = self.metrics
        try:
            self._sweep_abandoned()
            for bid, buf, req in requests:
                if req is None:
                    # borrowed: ``buf`` is the block itself, a read-only view
                    # of the received shard — no buffer to hand back
                    view, buf = buf, None
                    nbytes = view.size
                    metrics.resident_blocks += 1
                    metrics.resident_bytes += nbytes
                else:
                    if not req.completed():
                        # window hit its deadline with this fetch outstanding; the
                        # recv thread may still scatter into buf, so quarantine it
                        # (closed by a later sweep once the request settles) and
                        # fail over with a fresh buffer
                        self._abandoned.append((buf, req))
                        with TRACER.activate(wctx):
                            result, buf = self._retry_fetch(bid, None, None)
                    else:
                        result = req.wait(0)
                        if result.status != OperationStatus.SUCCESS:
                            # replica failover under the window span: the replica
                            # server's serve span parents here too, so the merged
                            # trace shows primary AND replica children
                            with TRACER.activate(wctx):
                                result, buf = self._retry_fetch(bid, buf, result)
                    # Zero-copy hand-off: a read-only view of the recv bytes.
                    # The old `bytes(...)` here copied every fetched block a
                    # second time; now the copy happens only in detach(), and
                    # only for pooled buffers nobody released in time.
                    view = buf.host_view()[: result.stats.recv_size]
                    view.flags.writeable = False
                    nbytes = int(result.stats.recv_size)
                    metrics.copied_blocks += 1
                metrics.remote_bytes_read += nbytes
                metrics.remote_blocks_fetched += 1
                pooled = buf is not None and self.pool is not None
                prev = BlockFetchResult(
                    bid,
                    memoryview(view),
                    buf,
                    pooled=pooled,
                    sanitizer=self.pool.sanitizer if pooled else None,
                )
                yield prev
                prev.detach()
        finally:
            if prev is not None:
                prev.detach()

    def _alloc_bufs(self, sizes: List[int]) -> List[MemoryBlock]:
        if self.pool is not None:
            return self.pool.get_many(sizes)
        return [MemoryBlock(np.zeros(s, dtype=np.uint8), size=s) for s in sizes]

    def _alloc_buf(self, size: int) -> MemoryBlock:
        return self._alloc_bufs([size])[0]

    def _sweep_abandoned(self) -> None:
        """Close quarantined buffers whose requests have since settled; a
        buffer whose request is still live may be a recv-scatter target and
        must stay alive (bounded: one per timed-out fetch attempt)."""
        still: List[Tuple[MemoryBlock, Request]] = []
        for buf, req in self._abandoned:
            if req.completed():
                buf.close()
            else:
                still.append((buf, req))
        self._abandoned = still

    def _retry_fetch(self, bid: ShuffleBlockId, buf: Optional[MemoryBlock], failed, refetch: bool = False):
        """Per-block pull-path retry + replica failover — the straggler/failure
        escape hatch next to the batch path.  The reference logs failed sends
        and gives up (SURVEY.md section 5.3: "No retry, no re-fetch fallback");
        here a failed/timed-out batch fetch falls back to
        ``transport.fetch_block`` (the per-block AM ids 3/4 analogue), up to
        ``fetch_retries`` attempts against the primary and then the same
        against each replica executor (``replica_of``, the replication-ring
        successors), with a jittered doubling backoff between attempts.  A
        replica refetch must be deterministic — same bytes the primary staged
        — so its size is asserted against the committed block length.

        ``buf is None`` means the original buffer was quarantined (its request
        never completed); each attempt then allocates a fresh buffer, and a
        timed-out attempt quarantines its buffer too.  Returns
        ``(result, buffer_holding_the_bytes)``.

        ``refetch``: the block is one of a window this executor never
        received (``_refetch_window``: a re-placed task) and nothing has
        failed yet — the first attempt is the block's first fetch, so a block
        it serves is no ``blocks_retried``; one a later attempt serves is.

        Fail-fast faults (``_FAIL_FAST_ERRORS``) are NOT retried: tenant
        admission rejections (UnknownTenantError / TenantQuotaExceededError)
        hit the same registry budgets on every replica, and an
        ``ExecutorLostError`` a fetch came back with says that what was asked
        for died with an executor (received shards, an exchange that depended
        on it): no candidate holds another copy of THAT.  They propagate
        immediately.  A candidate the membership has already declared dead
        (the transport's ``peer_alive``) is another matter: it is neither
        asked nor slept on — no attempt, no backoff, the next candidate at
        once — so a block whose stager died is served by its replica holder
        at the cost of a live fetch.  The backoff is for a peer that lives
        and fails.  Where every candidate is dead nothing is asked at all:
        ``ExecutorLostError`` of the stager where the block had no replica
        holder, ``BlockNotFoundError`` where those are lost too.
        ``ResourceExhaustedError`` (memory-pressure shed, the third arm of
        the failure taxonomy) IS retried: it inherits the jittered doubling
        backoff, which is exactly the back-off-and-retry contract the typed
        error promises — a later attempt lands after the server's watermark
        sweep freed room.

        When the transport scores peers (``breaker_allows``), candidates
        whose circuit breaker is open are skipped, so a gray-failing primary
        routes straight to the replica ring without burning a full deadline
        per attempt; if EVERY candidate's breaker rejects, the full list is
        kept (an open breaker must delay, never strand, a block)."""
        if failed is not None and isinstance(failed.error, _FAIL_FAST_ERRORS):
            if buf is not None:
                buf.close()
            raise failed.error
        if failed is not None:
            last_error = failed.error
        else:
            last_error = "not fetched yet" if refetch else "fetch deadline exceeded"
        size = self.block_sizes(bid.map_id, bid.reduce_id)
        primary = self.sender_of(bid.map_id)
        candidates: List[ExecutorId] = [primary]
        if self.holders_of is not None:
            # hot-set holders are first-class failover candidates: a widened
            # replica set exists precisely because this block draws fire
            try:
                candidates += [
                    e
                    for e in sorted(set(self.holders_of(primary, bid.shuffle_id) or ()))
                    if e not in candidates
                ]
            except (TransportError, OSError):
                pass
        if self.replica_of is not None:
            candidates += [
                e for e in self.replica_of(primary)
                if e != primary and e not in candidates
            ]
        alive = getattr(self.transport, "peer_alive", None)
        if alive is not None:
            living = [e for e in candidates if alive(e)]
            if not living:
                if buf is not None:
                    buf.close()
                if len(candidates) == 1:
                    raise ExecutorLostError(
                        primary, getattr(self.transport, "membership_epoch", 0),
                        f"it staged {bid} and the block has no replica holder",
                    )
                raise BlockNotFoundError(
                    bid.shuffle_id, bid.map_id, bid.reduce_id,
                    f"executor {primary} that staged it is lost, and so are its "
                    f"other holders {candidates[1:]}",
                )
            candidates = living
        allows = getattr(self.transport, "breaker_allows", None)
        if allows is not None and len(candidates) > 1:
            admitted = [e for e in candidates if allows(e)]
            if admitted:
                candidates = admitted
        deadline_ns = self.fetch_deadline_ms * 1_000_000
        # same wakeup park as the batch window loop above — the retry path
        # exists exactly for slow/straggling peers, where busy-spinning
        # progress() would burn the GIL against the recv thread
        park = getattr(self.transport, "wait_for_activity", None)
        record = getattr(self.transport, "record_peer_failure", None)
        attempt = 0
        for executor in candidates:
            for _ in range(self.fetch_retries):
                if attempt > 0 and self.fetch_backoff_ms:
                    base = (self.fetch_backoff_ms / 1000.0) * (2 ** min(attempt - 1, 6))
                    time.sleep(random.uniform(base / 2.0, base))
                attempt += 1
                if buf is None:
                    buf = self._alloc_buf(size)
                try:
                    req = self.transport.fetch_block(
                        executor, bid.shuffle_id, bid.map_id, bid.reduce_id, buf
                    )
                except (TransportError, OSError) as e:
                    if isinstance(e, _FAIL_FAST_ERRORS):
                        buf.close()
                        raise
                    last_error = e  # dead peer at connect time: next candidate
                    continue
                t0 = time.monotonic_ns()
                timed_out = False
                while not req.completed():
                    if deadline_ns and time.monotonic_ns() - t0 > deadline_ns:
                        timed_out = True
                        break
                    self.transport.progress()
                    if park is not None and not req.completed():
                        park(0.002)
                self.metrics.fetch_wait_ns += time.monotonic_ns() - t0
                if timed_out:
                    self.metrics.fetch_timeouts += 1
                    self._abandoned.append((buf, req))
                    buf = None  # never reuse a possibly-still-scattering buffer
                    if record is not None:
                        # a timeout the transport never saw as a frame error:
                        # charge it to the peer's health score here so hung
                        # (not dead) peers still trip their breaker
                        record(
                            executor,
                            f"fetch of {bid} timed out after "
                            f"{self.fetch_deadline_ms} ms",
                        )
                    last_error = TransportError(
                        f"fetch of {bid} from executor {executor} timed out "
                        f"after {self.fetch_deadline_ms} ms"
                    )
                    continue
                result = req.wait(0)
                if result.status == OperationStatus.SUCCESS:
                    if executor != primary:
                        # deterministic-refetch contract: the replica serves
                        # the exact bytes the primary staged, so the committed
                        # length must match to the byte
                        if int(result.stats.recv_size) != size:
                            buf.close()
                            raise TransportError(
                                f"replica refetch of {bid} from executor "
                                f"{executor} returned {result.stats.recv_size} B, "
                                f"expected {size} B — replica diverges from primary"
                            )
                        self.metrics.failovers += 1
                        if refetch:
                            self.metrics.replica_blocks += 1
                            self.metrics.replica_bytes += size
                    self._pulled_from = executor
                    if refetch and attempt == 1:
                        return result, buf  # its first fetch: nothing was retried
                    self.metrics.blocks_retried += 1
                    instant(
                        "fetch.retry",
                        shuffle_id=bid.shuffle_id, map_id=bid.map_id,
                        reduce_id=bid.reduce_id, executor=executor,
                        failover=executor != primary,
                    )
                    return result, buf
                last_error = result.error
                if isinstance(last_error, _FAIL_FAST_ERRORS):
                    buf.close()
                    raise last_error
        if buf is not None:
            buf.close()
        raise TransportError(
            f"fetch of {bid} failed after {attempt} attempt"
            f"{'' if attempt == 1 else 's'} across executors {candidates}: {last_error}"
        )

    # -- record pipeline ---------------------------------------------------

    def read_device(self):
        """This task's blocks read ON THE DEVICE: every non-empty block of its
        partition range, in (reduce, map) order, gathered into one packed
        ``jax.Array`` on the owning executor's device — the bytes never visit
        the host (for a consumer that runs on the chip; ``fetch_blocks`` and
        ``read`` are the host forms).  Needs the received shards retained in
        HBM (``conf.keep_device_recv``) and a range this reader's executor
        owns: the transport raises its typed ``TransportError`` otherwise.
        No retry, failover or hedge applies — the blocks are local after the
        exchange — so the fault counters of ``metrics`` stay 0; blocks and
        bytes are counted.  Span ``read.device``, once a task.

        Returns a ``DeviceRead``.  With ``key_ordering`` the reader's
        ``deserializer`` must be a ``FixedWidthSerializer`` and the return is
        an ``OrderedDeviceRead``: the task's records sorted on the device by
        their key (``_read_ordered``; span ``read.ordered`` instead)."""
        if self.key_ordering:
            return self._read_ordered(to_host=False)
        fetch = getattr(self.transport, "fetch_blocks_device", None)
        if fetch is None:
            raise TransportError(
                f"{type(self.transport).__name__} has no device-resident fetch"
            )
        bids = self._block_ids()
        with TRACER.executor_scope(self.executor_id), span(
            "read.device", shuffle_id=self.shuffle_id,
            reduce_id=self.start_partition, blocks=len(bids),
        ) as ctx:
            packed, table = fetch(bids, shuffle_id=self.shuffle_id)
            if ctx is not None:
                row_bytes = int(packed.shape[1]) * 4
                ctx.args["rows"] = int((-(-table[:, 1] // row_bytes)).sum())
        self.metrics.remote_blocks_fetched += len(bids)
        self.metrics.remote_bytes_read += int(table[:, 1].sum())
        return DeviceRead(packed, table, bids)

    def _read_ordered(self, to_host: bool):
        """The ordered return over fixed-width records: the task's blocks
        located and gathered on its executor's device as ``read_device``
        does, and sorted THERE by the serializer's key — the one ordered path
        for such records; nothing is sorted on the host.  An
        ``OrderedDeviceRead``, or with ``to_host`` the read-only ``(n,
        record_bytes)`` batch of its first ``n`` rows after one D2H.

        Span ``read.ordered``, once a task (``args``: ``blocks``, ``records``,
        ``bytes``, ``capacity`` and ``in_flight``: the ordered reads in flight
        on the executor when this one opened — 0 from one task thread), over
        ``read.device.locate``, ``fetch.device_gather``, ``read.ordered.sort``
        and — ``to_host`` — ``read.ordered.d2h``.

        As many task threads as the executor has slots may each run one: a
        reader is its task's own, the transport's tables are read under its
        lock, and each read holds its own gathered buffer, sorted array and
        landing block (``orderedread`` gauge ``in_flight``)."""
        serializer = self.deserializer
        if not isinstance(serializer, FixedWidthSerializer):
            raise TypeError(
                "key_ordering on the device needs a FixedWidthSerializer as the reader's "
                f"deserializer, not {type(serializer).__name__}; read() orders any records on the host"
            )
        fetch = getattr(self.transport, "fetch_blocks_ordered", None)
        if fetch is None:
            raise TransportError(f"{type(self.transport).__name__} has no device-resident fetch")
        bids, width = self._block_ids(), serializer.record_bytes
        with TRACER.executor_scope(self.executor_id), span(
            "read.ordered", shuffle_id=self.shuffle_id,
            reduce_id=self.start_partition, blocks=len(bids),
        ) as ctx:
            if ctx is not None:
                ctx.args["in_flight"] = self.transport.ordered_in_flight()
            records, n = fetch(bids, self.shuffle_id, width, serializer.key_bytes, flat=to_host)
            if ctx is not None:
                ctx.args.update(records=n, bytes=n * width, capacity=int(records.size) * 4 // width)
            if to_host:
                batch = self.transport.ordered_to_host(records, n, width)
            else:
                self.transport.ordered_handed_out(records)
        self.metrics.remote_blocks_fetched += len(bids)
        self.metrics.remote_bytes_read += n * width
        self.metrics.records_read += n
        if not to_host:
            return OrderedDeviceRead(records, n, bids)
        self.metrics.record_batches += 1
        return batch

    def read_batches(self) -> Iterator[np.ndarray]:
        """This task's records a block at a time: one read-only ``(n,
        record_bytes)`` ``uint8`` batch a non-empty block, in (reduce, map)
        order, records unordered as written — for a consumer that works on
        arrays (a sort, a columnar engine), never a record at a time.  The
        reader's ``deserializer`` must be a ``FixedWidthSerializer``.

        The normal path: ``fetch_blocks()``'s windows, credit gate, retries,
        failover and hedges.  A block borrowed where it lay
        (``resident_blocks``) gives a batch that is a view of the received
        shard and lives by ``BlockFetchResult``'s rule for borrowed data; a
        block a fetch copied into a pooled buffer gives a batch that owns its
        bytes, taken before the buffer goes back.  ``metrics.records_read``
        counts the records (the sum of the batches' lengths).

        With ``key_ordering``: ONE batch a task — all its records in
        non-decreasing order of their first ``key_bytes`` bytes (unsigned,
        most significant first; equal keys in any order), sorted on the
        executor's device over the shards kept in HBM and brought to the host
        in one D2H (``_read_ordered``; ``conf.keep_device_recv``, else the
        transport's typed "device shards not retained").  The batch owns its
        bytes and outlives the shuffle.

        ``aggregator`` over batches is not supported and raises here, rather
        than fall into ``ExternalCombiner`` a record at a time: batches are
        combined on the DEVICE lane — ``read_device()`` under ``key_ordering``
        and ``ops.relational.grouped_sum_records`` over what it hands out, as
        the query runner's batch lane does (``query/batch.py``).

        Span ``read.batches``, once a task: a summed span of the reader's own
        turns — issuing and awaiting the windows, the look-ups, the hand-out
        of each batch — WITHOUT the caller's turns between batches
        (``args``: ``blocks``, ``records``, ``bytes``, ``turns``); under
        ``key_ordering`` ``read.ordered`` instead."""
        serializer = self.deserializer
        if not isinstance(serializer, FixedWidthSerializer):
            raise TypeError(
                "read_batches() needs a FixedWidthSerializer as the reader's deserializer, "
                f"not {type(serializer).__name__}"
            )
        if self.aggregator is not None:
            raise NotImplementedError(
                "an aggregator over record batches is not supported on the host: read() combines a "
                "record at a time, and batches are combined on the device lane (read_device() under "
                "key_ordering, then ops.relational.grouped_sum_records: query/batch.py)"
            )
        if self.key_ordering:
            batch = self._read_ordered(to_host=True)
            return iter((batch,) if len(batch) else ())
        return self._batches(serializer)

    def _batches(self, serializer: "FixedWidthSerializer") -> Iterator[np.ndarray]:
        metrics = self.metrics
        clock = time.perf_counter_ns
        timed = TRACER.active
        t_open = t = clock() if timed else 0
        own_ns = 0
        try:
            for blk in self.fetch_blocks():
                try:
                    batch = serializer.batch(blk.data, blk.block_id)
                    if blk.pooled:  # the buffer is recycled at release: own the bytes
                        batch = batch.copy()
                        batch.flags.writeable = False
                finally:
                    blk.release()
                metrics.records_read += len(batch)
                metrics.record_batches += 1
                if timed:
                    own_ns += clock() - t
                yield batch
                if timed:
                    t = clock()
            if timed:
                own_ns += clock() - t  # the turn that found the task drained
        finally:
            if timed and TRACER.active:
                with TRACER.executor_scope(self.executor_id):
                    TRACER.record_spans(
                        None,
                        (("read.batches", t_open, t_open + own_ns),),
                        args={  # a reader lives for one task: its metrics are the task's
                            "shuffle_id": self.shuffle_id, "reduce_id": self.start_partition,
                            "blocks": metrics.record_batches, "records": metrics.records_read,
                            "bytes": metrics.records_read * serializer.record_bytes,
                            "turns": metrics.record_batches + 1,
                        },
                    )

    def read(self) -> Iterator[Any]:
        """deserialize -> combine -> sort (UcxShuffleReader.scala:137-199).

        Combine and sort run through the spillable ``ExternalCombiner``
        (shuffle/external.py) under ``memory_budget`` — the ExternalSorter
        role the reference's pipeline delegates to Spark — so a reduce
        partition larger than memory streams through sorted disk runs instead
        of OOMing."""
        def stream() -> Iterator[Any]:
            # Release each block as soon as its deserializer is exhausted:
            # the decoder reads straight out of the fetch buffer (zero-copy)
            # and the pooled buffer recycles without the detach() copy.
            for blk in self.fetch_blocks():
                try:
                    marks = self._yielding
                    if marks is None or not marks.sampled:
                        yield from self.deserializer(blk.data)
                    else:  # a sampled window under full tracing
                        yield from _timed_turns(self.deserializer(blk.data), marks)
                finally:
                    blk.release()

        records: Iterator[Any] = stream()

        def counted(it):
            for rec in it:
                self.metrics.records_read += 1
                yield rec

        records = counted(records)
        if self.aggregator is None and not self.key_ordering:
            return records  # pure streaming, nothing materializes

        from sparkucx_tpu.shuffle.external import ExternalCombiner

        combiner = ExternalCombiner(
            aggregator=self.aggregator,
            key_ordering=self.key_ordering,
            memory_budget=self.memory_budget,
            spill_dir=self.spill_dir,
            merge_combiners=self.merge_combiners,
        )
        try:
            combiner.insert_all(records)
        except BaseException:
            combiner.close()  # reclaim spilled runs; mkstemp files don't self-delete
            raise
        self.metrics.spills = combiner.spill_count

        def drain(c):
            try:
                yield from c
            finally:
                c.close()

        return drain(combiner)
