"""Peer block server + socket transport — the multi-process serving path (L3).

Two reference capabilities live here, both speaking the AM protocol of
``Definitions.scala:22-29`` over TCP frames (core/definitions.py):

1. **The executor<->executor serving path** (upstream SparkUCX, partly commented
   out in the fork — UcxShuffleTransport.handleFetchBlockRequest :305-323,
   UcxWorkerWrapper.scala:397-448, GlobalWorkerRpcThread.scala:22-44): a server
   thread answers batched ``FetchBlockReq`` by reading registered blocks /
   staged-store blocks in parallel and replying with ONE ack frame laid out
   ``[sizes | data...]`` exactly like the reference's single bounce-buffer reply.
2. **The store daemon role** (the out-of-repo DPU daemon on port 1338,
   CommonUcxShuffleManager.scala:84-89): ``InitExecutorReq`` handshakes an
   executor's store context, ``MapperInfo`` installs commit metadata — so a
   ``BlockServer`` *is* the daemon the reference only talks to.

``PeerTransport`` implements the full ``ShuffleTransport`` trait over this wire:
completions arrive on a receiver thread but requests only *complete* under
``progress()`` (results park in a queue), preserving the reference's explicit-poll
contract (ShuffleTransport.scala:158-165).
"""

from __future__ import annotations

import json
import os
import queue
import socket
import struct
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Deque, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.block import Block, BlockId, MemoryBlock, ShuffleBlockId
from sparkucx_tpu.core.definitions import (
    CHUNK_CODEC_EXT_SIZE,
    CHUNK_HEADER_SIZE,
    FRAME_HEADER_SIZE,
    MAX_FRAME_BYTES,
    REPLICA_ENTRY_SIZE,
    REPLICA_HEADER_SIZE,
    REPLICA_TRACE_EXT_SIZE,
    TRACE_EXT_SIZE,
    AmId,
    MapperInfo,
    pack_chunk_codec_ext,
    pack_chunk_hdr,
    pack_frame,
    pack_frame_prefix,
    pack_hot_set,
    pack_member_event,
    pack_replica_ack,
    pack_replica_put,
    pack_replica_trace_ext,
    pack_trace_ext,
    pack_wire_hello,
    unpack_chunk_codec_ext,
    unpack_chunk_hdr,
    unpack_frame_header,
    unpack_hot_set,
    unpack_member_event,
    unpack_replica_ack,
    unpack_replica_put,
    unpack_replica_trace_ext,
    unpack_trace_ext,
    unpack_wire_hello,
)
from sparkucx_tpu.core.operation import (
    BlockCorruptError,
    OperationCallback,
    OperationResult,
    OperationStats,
    OperationStatus,
    Request,
    ResourceExhaustedError,
    TenantQuotaExceededError,
    TransportError,
    UnknownTenantError,
)
from sparkucx_tpu.service.reactor import Reactor
from sparkucx_tpu.core.transport import ExecutorId, ShuffleTransport
# tier-(a) wire compression policy + page formats; ops.compress keeps its jax
# imports function-local, so this pulls no accelerator stack into the transport
from sparkucx_tpu.ops.compress import CompressSpec, encode_chunk
from sparkucx_tpu.service.popularity import BlockPopularity
from sparkucx_tpu.store.hbm_store import HbmBlockStore
from sparkucx_tpu.testing import faults
from sparkucx_tpu.obs.metrics import (
    MetricsRegistry,
    close_http_server,
    counter_dict_provider,
    start_http_server,
    stats_aggregator_provider,
    tracer_provider,
    wire_lane_provider,
)
from sparkucx_tpu.obs.recorder import FlightRecorder
from sparkucx_tpu.utils.checksum import crc32c
from sparkucx_tpu.utils.pagecodec import CODEC_RAW, CodecError, decode_page
from sparkucx_tpu.utils.logging import get_logger
from sparkucx_tpu.utils.stats import StatsAggregator
from sparkucx_tpu.utils.trace import TRACER, instant

logger = get_logger("transport.peer")

_TAG = struct.Struct("<Q")
_COUNT = struct.Struct("<I")
_TRIPLE = struct.Struct("<iii")
_SIZE = struct.Struct("<q")
#: Tenant header extension of FETCH_BLOCK_REQ: <u32 len><utf-8 app_id> after
#: the block triples.  Absent by default (single-tenant frames stay
#: byte-identical to the golden captures); unpack_batch_fetch_req reads
#: exactly ``count`` triples, so old servers ignore the extension.
_APP = struct.Struct("<I")
#: Negative size codes in fetch-reply size lists.  -1 is the historical
#: block-not-found (retryable through replica failover); -2/-3 are the
#: tenant admission rejections, surfaced client-side as the typed
#: UnknownTenantError / TenantQuotaExceededError which readers treat as
#: NOT retryable (every replica enforces the same registry).  -4 is the
#: gray-failure arm: the serving store hit its hard watermark
#: (``store.hardWatermark``) mid-serve — surfaced as ResourceExhaustedError,
#: which readers treat as RETRYABLE WITH BACKOFF (pressure is per-executor
#: and transient; the soft-watermark sweep clears it).
SIZE_NOT_FOUND = -1
SIZE_UNKNOWN_TENANT = -2
SIZE_QUOTA_EXCEEDED = -3
SIZE_RESOURCE_EXHAUSTED = -4
#: CRC32C trailer appended to chunk / ReplicaPut headers when
#: ``spark.shuffle.tpu.wire.checksum`` is on.  Receivers detect it by header
#: length — the knob never changes frame layout when off (golden frames).
_CRC = struct.Struct("<I")
_MAX_FRAME = MAX_FRAME_BYTES  # shared frame ceiling (core/definitions.py)
#: The encoded-chunk pool's byte cap lives on the conf
#: (``spark.shuffle.tpu.compress.cacheBytes``, 0 disables the pool).  Encoded
#: pages are typically a fraction of their raw chunks, so the 128 MiB default
#: covers on the order of a GiB of hot raw blocks; past the cap the pool
#: LRU-evicts — a cap, not a correctness boundary (a miss just re-encodes).


def apply_wire_sockopts(
    sock: socket.socket,
    conf: Optional[TpuShuffleConf] = None,
    *,
    sndbuf: int = 0,
    rcvbuf: int = 0,
) -> None:
    """TCP_NODELAY + kernel buffer sizing for every wire socket (both ends).

    Small control frames (acks, ``MapperInfo``) must not eat Nagle delays, so
    NODELAY is unconditional.  ``conf.wire_sock_buf_bytes``
    (``spark.shuffle.tpu.wire.sockBufBytes``), when set, overrides BOTH
    directions' kernel buffers; otherwise the caller's per-direction defaults
    apply (0 = leave the platform default alone)."""
    try:
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    except OSError:
        pass
    override = conf.wire_sock_buf_bytes if conf is not None else 0
    for opt, val in (
        (socket.SO_SNDBUF, override or sndbuf),
        (socket.SO_RCVBUF, override or rcvbuf),
    ):
        if val:
            try:
                sock.setsockopt(socket.SOL_SOCKET, opt, val)
            except OSError:
                pass


def _peername(sock: socket.socket) -> str:
    """Best-effort ``host:port`` of the remote end, for error messages."""
    try:
        name = sock.getpeername()
        return f"{name[0]}:{name[1]}"
    except (OSError, AttributeError, IndexError, TypeError):
        return "?"


def recv_exact(
    sock: socket.socket, n: int, *, idle_ok: bool = False, peer: str = ""
) -> Optional[bytearray]:
    """Receive exactly ``n`` bytes into ONE preallocated buffer.

    ``recv_into`` a sliding memoryview of a single bytearray: the historical
    implementation collected per-``recv`` bytes chunks and paid a second full
    copy joining them.  Returns ``None`` on EOF.  A bytearray is accepted
    everywhere the old bytes was (struct unpacking, json, ``np.frombuffer``,
    ``bytes + bytearray`` concatenation).

    When the socket carries a timeout (``conf.wire_timeout_ms``), a read that
    times out with part of the buffer already received means the peer hung
    mid-frame: raise an addressed OSError.  With ``idle_ok`` (the wait for the
    NEXT frame header), a timeout with zero bytes received is a quiet
    connection, not a fault — keep waiting."""
    out = bytearray(n)
    mv = memoryview(out)
    got = 0
    while got < n:
        try:
            r = sock.recv_into(mv[got:], n - got)
        except socket.timeout:
            if idle_ok and got == 0:
                continue
            raise OSError(
                f"peer {peer or _peername(sock)} hung mid-frame: read timed out "
                f"with {got}/{n} B received"
            ) from None
        if r == 0:
            return None
        got += r
    return out


def recv_frame(sock: socket.socket, peer: str = "") -> Optional[Tuple[AmId, bytes, bytes]]:
    hdr = recv_exact(sock, FRAME_HEADER_SIZE, idle_ok=True, peer=peer)
    if hdr is None:
        return None
    am_id, hlen, blen = unpack_frame_header(hdr)
    if hlen + blen > _MAX_FRAME:
        raise ValueError(f"frame too large from peer {peer or _peername(sock)}")
    header = recv_exact(sock, hlen, peer=peer) if hlen else b""
    body = recv_exact(sock, blen, peer=peer) if blen else b""
    if (hlen and header is None) or (blen and body is None):
        return None
    return am_id, header, body


def pack_batch_fetch_req(
    tag: int,
    block_ids: Sequence[ShuffleBlockId],
    app_id: Optional[str] = None,
    trace: Optional[Tuple[int, int]] = None,
) -> bytes:
    """Header: tag + count + (sid, mid, rid) triples — the batched variant of the
    reference's 12-byte fetch header (UcxWorkerWrapper.scala:96-126).

    With ``app_id`` (tenants.enabled) the requesting tenant rides as a
    self-describing extension after the triples (``_APP`` length + utf-8
    bytes); the triples then carry TENANT-LOCAL shuffle ids, which the server
    translates through its registry.  With ``trace`` (obs.traceContext) the
    issuing span's (trace_id, span_id) rides as a magic-prefixed 20-byte
    trailer AFTER the app extension (core/definitions.py ``_TRACE_EXT``).
    Both None (the default) emits the historical bytes exactly."""
    out = bytearray(_TAG.pack(tag) + _COUNT.pack(len(block_ids)))
    for b in block_ids:
        out += _TRIPLE.pack(b.shuffle_id, b.map_id, b.reduce_id)
    if app_id:
        raw = app_id.encode("utf-8")
        out += _APP.pack(len(raw)) + raw
    if trace is not None:
        out += pack_trace_ext(trace[0], trace[1])
    return bytes(out)


def split_fetch_req_trace(header: bytes) -> Tuple[Optional[Tuple[int, int]], bytes]:
    """Split a FETCH_BLOCK_REQ header into ``(trace_ctx, header-without-ext)``.

    The trace ext is the LAST 20 bytes when present.  Beyond the magic check,
    the remaining length must be structurally consistent — either the ext
    directly follows the triples, or an app extension accounts for EXACTLY
    the bytes in between — so an app_id whose utf-8 tail happens to contain
    the magic bytes can never be mis-split."""
    base = _TAG.size + _COUNT.size
    if len(header) < base + TRACE_EXT_SIZE:
        return None, header
    ctx = unpack_trace_ext(header)
    if ctx is None:
        return None, header
    (count,) = _COUNT.unpack_from(header, _TAG.size)
    pos = base + count * _TRIPLE.size
    rem = len(header) - pos
    if rem < TRACE_EXT_SIZE:
        return None, header
    if rem != TRACE_EXT_SIZE:
        if rem < _APP.size + TRACE_EXT_SIZE:
            return None, header
        (n,) = _APP.unpack_from(header, pos)
        if _APP.size + n + TRACE_EXT_SIZE != rem:
            return None, header
    return ctx, header[:-TRACE_EXT_SIZE]


def unpack_batch_fetch_req(header: bytes) -> Tuple[int, List[ShuffleBlockId]]:
    (tag,) = _TAG.unpack_from(header, 0)
    (count,) = _COUNT.unpack_from(header, _TAG.size)
    ids = []
    pos = _TAG.size + _COUNT.size
    for _ in range(count):
        s, m, r = _TRIPLE.unpack_from(header, pos)
        ids.append(ShuffleBlockId(s, m, r))
        pos += _TRIPLE.size
    return tag, ids


def unpack_fetch_req_app_id(header: bytes, count: int) -> Optional[str]:
    """The tenant extension of a FETCH_BLOCK_REQ header, or None when absent
    (single-tenant frame) or malformed (treated as absent — the request then
    resolves in the untranslated namespace, exactly like an old client)."""
    pos = _TAG.size + _COUNT.size + count * _TRIPLE.size
    if len(header) < pos + _APP.size:
        return None
    (n,) = _APP.unpack_from(header, pos)
    raw = bytes(header[pos + _APP.size : pos + _APP.size + n])
    if n == 0 or len(raw) != n:
        return None
    try:
        return raw.decode("utf-8")
    except UnicodeDecodeError:
        return None


class _ServerGroup:
    """Server-side stripe group: the K accepted lane sockets of one client
    ``_StripeGroup``, plus one sender thread per lane so chunk frames bound
    for different lanes hit the kernel concurrently (the GIL is released
    inside ``sendmsg``/``sendall``, so K senders really do overlap socket
    copies — a single serving thread would serialize them).

    Each lane's sender shares a per-connection send lock with the lane's
    ``_serve_conn`` thread, so control acks (InitExecutorAck) interleave with
    chunk frames only at frame granularity, never mid-frame.  Queues are
    bounded: a slow wire backpressures the resolve loop instead of buffering
    the whole reply in queued iovecs."""

    def __init__(self, group_id: int, nlanes: int, chunk_bytes: int) -> None:
        self.group_id = group_id
        self.nlanes = max(1, nlanes)
        self.chunk_bytes = max(4096, chunk_bytes)
        self._lock = threading.Lock()
        self._lanes: Dict[int, socket.socket] = {}  #: guarded by self._lock
        self._queues: Dict[int, "queue.Queue"] = {}  #: guarded by self._lock
        self._ready = threading.Event()  # set once all nlanes registered
        self.broken = False  # one dead lane poisons the group (benign flag,
        # single transition False->True, read without the lock by design)
        #: per-lane tx telemetry, each entry written only by its sender thread
        self.tx_bytes: Dict[int, int] = {}
        self.tx_frames: Dict[int, int] = {}

    def register(self, lane: int, conn: socket.socket, send_lock: threading.Lock) -> None:
        with self._lock:
            self._lanes[lane] = conn
            q: "queue.Queue" = queue.Queue(maxsize=64)
            self._queues[lane] = q
            self.tx_bytes[lane] = 0
            self.tx_frames[lane] = 0
            ready = len(self._lanes) == self.nlanes
        threading.Thread(
            target=self._send_loop, args=(lane, conn, q, send_lock), daemon=True
        ).start()
        if ready:
            self._ready.set()

    def ready(self, timeout: float = 5.0) -> bool:
        """True once every lane has said hello — striping before that would
        address lanes that do not exist yet.  A timed-out or broken group
        makes the caller fall back to the single-frame reply."""
        return self._ready.wait(timeout) and not self.broken

    def enqueue(self, lane: int, parts: list) -> None:
        with self._lock:
            q = self._queues.get(lane)
        while True:
            if q is None or self.broken:
                raise OSError("stripe group lane gone")
            try:  # bounded wait so a group broken mid-put cannot hang the server
                q.put(parts, timeout=0.25)
                return
            except queue.Full:
                continue

    def _send_loop(self, lane: int, conn: socket.socket, q: "queue.Queue", send_lock: threading.Lock) -> None:
        while not self.broken:
            try:
                parts = q.get(timeout=0.25)
            except queue.Empty:
                continue
            if parts is None:
                return
            try:
                with send_lock:
                    if hasattr(conn, "sendmsg"):
                        BlockServer._sendmsg_all(conn, parts)
                    else:
                        conn.sendall(b"".join(bytes(p) for p in parts))
            except OSError:
                self.close()
                return
            self.tx_bytes[lane] += sum(len(p) for p in parts)
            self.tx_frames[lane] += 1

    def drop_lane(self, lane: int) -> None:
        """A lane's serve thread saw EOF/error: the group can no longer
        stripe (chunks for that lane would be lost), so poison it."""
        self.close(keep_lane=lane)

    def close(self, keep_lane: Optional[int] = None) -> None:
        self.broken = True
        with self._lock:
            queues = list(self._queues.values())
            lanes = [c for ln, c in self._lanes.items() if ln != keep_lane]
            self._queues.clear()
            self._lanes.clear()
        for q in queues:
            try:
                q.put_nowait(None)  # early wakeup; senders also poll `broken`
            except queue.Full:
                pass
        for conn in lanes:
            # shutdown (not close) so each lane's _serve_conn thread observes
            # the death and runs its own cleanup exactly once
            try:
                conn.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass


class _ConnState:
    """Per-connection serve state, shared by the thread-per-connection loop
    and the reactor's frame-at-a-time serving: the stripe group this lane
    joined (via WIRE_HELLO), its lane id, and the send lock the lane's group
    sender thread shares with the serving code."""

    __slots__ = ("peer", "send_lock", "group", "lane", "use_sendmsg")

    def __init__(self, conn: socket.socket) -> None:
        self.peer = _peername(conn)
        self.send_lock = threading.Lock()
        self.group: Optional[_ServerGroup] = None
        self.lane = -1
        self.use_sendmsg = hasattr(conn, "sendmsg")


class BlockServer:
    """Serves registered blocks + staged-store blocks to peers.

    The reply layout for a batch is ``header=[tag, count, size*count]``,
    ``body=concat(payloads)`` — the reference's one-pooled-buffer reply
    (UcxWorkerWrapper.scala:397-448); sizes of -1 mark per-block failures.
    Reads are parallelized across ``num_io_threads`` like the reference's
    ForkJoin ``ioThreadPool`` (UcxWorkerWrapper.scala:69-71,416-422).
    """

    def __init__(
        self,
        conf: Optional[TpuShuffleConf] = None,
        store: Optional[HbmBlockStore] = None,
        registry_lookup: Optional[Callable[[BlockId], Optional[Block]]] = None,
        host: str = "127.0.0.1",
        port: int = 0,
        member_sink: Optional[Callable[[int, int, int, int], None]] = None,
        tenants=None,
        executor_id: Optional[int] = None,
        metrics: Optional[MetricsRegistry] = None,
        popularity: Optional[BlockPopularity] = None,
        hot_sink: Optional[Callable[[int, bool], None]] = None,
        hot_set_provider: Optional[Callable[[], Dict[int, List[int]]]] = None,
    ) -> None:
        self.conf = conf or TpuShuffleConf()
        self.store = store
        self.registry_lookup = registry_lookup
        #: popularity-aware serving tier (serve.hotThresholdFetchesPerSec):
        #: per-block fetch-rate tracker, the owner's reaction hook for
        #: promote/demote transitions (the transport widens/narrows the
        #: replica set there), and the advertisement source HOT_SET_PULL
        #: replies from.  All None by default — the off path never touches
        #: the tracker lock.
        self.popularity = popularity
        self.hot_sink = hot_sink
        self.hot_set_provider = hot_set_provider
        #: obs plane: which executor this server serves for (trace-event
        #: attribution in the shared-process loopback mesh) and the metrics
        #: registry METRICS_PULL answers from (None = empty exposition)
        self.executor_id = executor_id
        self.metrics = metrics
        #: TenantRegistry of the owning process (service/tenants.py), or None
        #: for the historical single-tenant server.  With a registry, FETCH
        #: requests carrying the tenant extension get their shuffle ids
        #: translated and their reply bytes drawn from per-tenant CreditGates.
        self.tenants = tenants
        #: membership-frame sink: called as (am_id, epoch, subject, observer)
        #: for every MemberSuspect/MemberRejoin frame a peer sends us
        self.member_sink = member_sink
        self._srv = socket.socket()
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind((host, port))
        self._srv.listen(64)
        self.address: Tuple[str, int] = self._srv.getsockname()
        self._running = True
        self._io = (
            ThreadPoolExecutor(max_workers=self.conf.num_io_threads)
            if self.conf.num_io_threads > 1
            else None
        )
        self._accepted: list = []
        self._accepted_lock = threading.Lock()
        # Stripe groups announced by WIRE_HELLO frames (striped wire path);
        # a group forms as its K lane connections each say hello.
        self._groups: Dict[int, _ServerGroup] = {}  #: guarded by self._groups_lock
        self._groups_lock = threading.Lock()
        #: tier-(a) wire compression policy (conf compress.codec); off =
        #: chunk frames byte-identical to the pinned golden captures
        self._compress = CompressSpec.from_conf(self.conf)
        #: serve-side compression telemetry: decoded (raw) vs wire bytes
        #: streamed through chunk frames, and how many pages actually encoded
        #: vs fell back to raw.  Aggregated per reply under _compress_lock.
        self.compress_stats: Dict[str, int] = {
            "raw_bytes": 0,
            "wire_bytes": 0,
            "encoded_chunks": 0,
            "raw_chunks": 0,
            "cache_hits": 0,
            "cache_misses": 0,
            "cache_evictions": 0,
        }  #: guarded by self._compress_lock
        self._compress_lock = threading.Lock()
        #: serve-side encoded-chunk pool: sealed blocks are immutable for the
        #: life of their shuffle id, so each (block, offset, len) chunk pays
        #: the encoder exactly once and every later fetch of the same chunk —
        #: other reducers, credit-window re-issues, retry/failover replays —
        #: serves the cached encoding (or the cached "unprofitable, ship raw"
        #: verdict, so incompressible blocks never re-attempt the encoder).
        #: Maps (bid, offset, len) -> (codec_id, encoded | None); insertion
        #: order doubles as recency order (hits re-insert at the MRU end), so
        #: eviction from the front is LRU.  Evicted once the encoded bytes
        #: held exceed ``compress.cacheBytes`` (0 = pool off, every chunk
        #: re-encodes).
        self._encoded_pool: Dict[tuple, tuple] = {}  #: guarded by self._compress_lock
        self._encoded_pool_bytes = 0  #: guarded by self._compress_lock
        self._encoded_pool_cap = self.conf.compress_cache_bytes
        # Serving plane: by default, numListenerThreads accept loops on one
        # listen socket (UcxShuffleConf.scala:73-78; the kernel load-balances
        # accepts) and a thread per accepted connection.  With server.workers
        # set (or tenants.enabled), the shared reactor holds every idle
        # connection in one selector and serves frames from a bounded pool —
        # the scalable plane for many-tenant fan-in.
        self._reactor: Optional[Reactor] = None
        self._threads: list = []
        if (
            self.conf.server_workers > 0
            or self.conf.tenants_enabled
            or self.conf.server_accept_backlog > 0
        ):
            # server.acceptBacklog implies the reactor plane: shedding needs
            # the one place that owns the resident-connection count
            self._reactor = Reactor(
                self.conf.server_workers,
                name=f"blocksrv-{self.address[1]}",
                accept_backlog=self.conf.server_accept_backlog,
            )
            self._reactor.add_listener(self._srv, self._on_accept)
        else:
            self._threads = [
                threading.Thread(target=self._accept_loop, daemon=True)
                for _ in range(max(1, self.conf.num_listener_threads))
            ]
            for t in self._threads:
                t.start()
        self.handshaken: Dict[int, bytes] = {}  # executor_id -> context blob

    def address_bytes(self) -> bytes:
        return f"{self.address[0]}:{self.address[1]}".encode()

    def compress_snapshot(self) -> Dict[str, int]:
        """Consistent copy of :attr:`compress_stats` (serve threads aggregate
        per striped reply under the same lock)."""
        with self._compress_lock:
            return dict(self.compress_stats)

    def drop_shuffle_chunks(self, shuffle_id: int) -> int:
        """Purge the shuffle's cached encodings from the encoded-chunk pool.

        The pool's safety argument is that sealed blocks are immutable for
        the life of their shuffle id — so when the id is unregistered (and a
        later shuffle, or a recomputed lineage-cache round, may legitimately
        reuse it) every cached encoding keyed by that id must go, or a serve
        thread could ship stale bytes for a fresh block.  Returns the number
        of chunks dropped."""
        with self._compress_lock:
            doomed = [
                k for k in self._encoded_pool
                if isinstance(k[0], ShuffleBlockId) and k[0].shuffle_id == shuffle_id
            ]
            for k in doomed:
                _, enc = self._encoded_pool.pop(k)
                if enc is not None:
                    self._encoded_pool_bytes -= len(enc)
            return len(doomed)

    def _accept_loop(self) -> None:
        while self._running:
            try:
                conn, _ = self._srv.accept()
                # deep send window default: one reply batch is tens of MiB
                apply_wire_sockopts(conn, self.conf, sndbuf=4 << 20)
                # mid-frame reads (and stuck sends) may not hang forever; idle
                # header waits are exempt inside recv_exact(idle_ok=True)
                if self.conf.wire_timeout_ms:
                    conn.settimeout(self.conf.wire_timeout_ms / 1000.0)
            except OSError:
                return
            with self._accepted_lock:
                self._accepted.append(conn)
            threading.Thread(target=self._serve_conn, args=(conn,), daemon=True).start()

    def _on_accept(self, conn: socket.socket) -> None:
        """Reactor accept path: same socket setup as ``_accept_loop``, but the
        connection parks in the shared selector instead of owning a thread."""
        apply_wire_sockopts(conn, self.conf, sndbuf=4 << 20)
        # accepted from a non-blocking listener: restore blocking reads (with
        # the usual mid-frame timeout) for the frame-at-a-time workers
        if self.conf.wire_timeout_ms:
            conn.settimeout(self.conf.wire_timeout_ms / 1000.0)
        else:
            conn.setblocking(True)
        with self._accepted_lock:
            self._accepted.append(conn)
        state = _ConnState(conn)
        self._reactor.add_connection(
            conn,
            lambda c, s=state: self._serve_frame(c, s),
            on_close=lambda c, s=state: self._drop_conn(c, s),
        )

    def _fire_hot_transitions(self, transitions) -> None:
        """Emit the promote/demote trace instants and hand each shuffle-level
        transition to the owning transport's hot sink (which widens or
        narrows the replica advertisement).  Sink errors are contained — a
        failed widen must never fail the fetch that triggered it."""
        for sid, hot in transitions:
            if hot:
                instant("serve.promote", shuffle_id=sid)
            else:
                instant("serve.demote", shuffle_id=sid)
            if self.hot_sink is not None:
                try:
                    self.hot_sink(sid, hot)
                except Exception:
                    logger.exception(
                        "hot-set %s of shuffle %d failed",
                        "promote" if hot else "demote", sid,
                    )

    def sweep_popularity(self) -> None:
        """Cool-down pass (rate-limited inside the tracker): demote blocks
        whose fetch rate decayed below the hysteresis edge, firing
        ``serve.demote`` for each shuffle whose last hot block cooled."""
        pop = self.popularity
        if pop is not None:
            self._fire_hot_transitions(pop.maybe_sweep())

    def _resolve_one(self, bid: ShuffleBlockId):
        """Resolve to a ``(buffer, offset, length)`` view or None.

        Registry blocks serve their stable ``memory_view`` zero-copy —
        memory-backed blocks hand back their payload array, file-backed ones
        a cached read-only mmap of the segment (materializing a fresh buffer
        per fetch — alloc + copy + page faults — was the measured wall of
        this path); only blocks with no mappable view (``memory_view() is
        None``) materialize under the block lock.  Store blocks serve a
        zero-copy view of host staging.  Either way the reply path sends the
        view without another copy.

        Popularity tier (serve.hotThresholdFetchesPerSec > 0): every resolve
        folds into the block's fetch-rate EWMA; a hot block is served from
        the store's decoded-block cache when pinned there (bypassing the
        eviction tiers — no restage, no LRU bump below), and admitted to it
        on the miss that follows promotion."""
        pop = self.popularity
        hot = False
        if pop is not None:
            hot, transitions = pop.observe(bid.shuffle_id, bid.map_id, bid.reduce_id)
            if transitions:
                self._fire_hot_transitions(transitions)
        if hot and self.store is not None:
            cached = self.store.serve_cache_get(
                bid.shuffle_id, bid.map_id, bid.reduce_id
            )
            if cached is not None:
                return cached
        resolved = self._resolve_one_tiers(bid)
        if hot and self.store is not None and isinstance(resolved, tuple):
            staging, off, ln = resolved
            if ln:
                flat = np.asarray(staging).reshape(-1).view(np.uint8)
                self.store.serve_cache_offer(
                    bid.shuffle_id, bid.map_id, bid.reduce_id,
                    bytes(flat[off : off + ln]),
                )
        return resolved

    def _resolve_one_tiers(self, bid: ShuffleBlockId):
        """The historical registry -> replica -> staging resolution."""
        if self.registry_lookup is not None:
            blk = self.registry_lookup(bid)
            if blk is not None:
                with blk.lock:
                    view = blk.memory_view()
                    if view is not None:
                        return view, 0, int(view.size)
                    mb = blk.get_memory_block()
                # hand back the materialized buffer as a view, not bytes — the
                # reply path then sends it without a second copy
                return mb.host_view(), 0, int(mb.size)
        if self.store is not None:
            # Replica tier BEFORE staging: apply_mapper_info installs entries
            # for maps this executor does NOT hold into the local block table
            # with sender-relative offsets, so block_staging_view on a
            # non-owner would happily serve garbage bytes for a remote map.
            # Replica keys are exactly those remote maps (ownership partitions
            # maps across executors), so they must win the lookup.
            view = self.store.replica_view(bid.shuffle_id, bid.map_id, bid.reduce_id)
            if view is not None:
                return view
            try:
                return self.store.block_staging_view(
                    bid.shuffle_id, bid.map_id, bid.reduce_id
                )
            except TenantQuotaExceededError:
                # restage-on-fetch needed HBM headroom the owning tenant no
                # longer has: a typed, addressed admission failure — NOT the
                # retryable block-not-found
                return SIZE_QUOTA_EXCEEDED
            except ResourceExhaustedError:
                # restage-on-fetch hit the store's hard watermark: this
                # executor is under memory pressure RIGHT NOW, but the
                # eviction sweep clears it — retryable with backoff
                return SIZE_RESOURCE_EXHAUSTED
            except TransportError:
                return None
        return None

    def _assemble_reply(self, entries) -> Tuple[bytes, "np.ndarray"]:
        """Build ``(sizes blob, one contiguous body)`` from resolved views —
        the reference's single pooled reply buffer (UcxWorkerWrapper.scala:397-448),
        gathered through the native threaded batch copy (ts_batch_copy, the
        ForkJoin ioThreadPool analogue).  Fallback for platforms without
        ``socket.sendmsg``; the primary reply path is the vectored
        ``_reply_parts`` + ``_sendmsg_all``, which skips this copy."""
        from sparkucx_tpu import native

        sizes, total = [], 0
        for e in entries:
            if e is None or isinstance(e, int):
                sizes.append(SIZE_NOT_FOUND if e is None else e)
            else:
                sizes.append(e[2])
                total += e[2]
        body = np.empty(total, dtype=np.uint8)
        by_staging: Dict[int, Tuple[np.ndarray, list]] = {}
        pos = 0
        for e in entries:
            if e is None or isinstance(e, int):
                continue
            staging, off, ln = e
            if ln:
                key = id(staging)
                if key not in by_staging:
                    by_staging[key] = (staging.reshape(-1).view(np.uint8), [])
                by_staging[key][1].append((pos, off, ln))
            pos += ln
        for src, segs in by_staging.values():
            native.batch_copy(body, src, segs, max_threads=self.conf.num_io_threads)
        blob = b"".join(_SIZE.pack(s) for s in sizes)
        return blob, body

    def _reply_parts(self, entries) -> Tuple[bytes, list, int]:
        """(sizes blob, zero-copy body views in order, total bytes) — the
        scatter-gather form of ``_assemble_reply``: store-backed views go to
        the wire as memoryviews of the staging buffer itself, no intermediate
        contiguous body is built (the kernel gathers via sendmsg iovecs —
        the single-pooled-buffer copy of UcxWorkerWrapper.scala:397-448
        replaced by vectored IO)."""
        sizes, parts, total = [], [], 0
        for e in entries:
            if e is None or isinstance(e, int):
                sizes.append(SIZE_NOT_FOUND if e is None else e)
                continue
            staging, off, ln = e
            if ln:
                parts.append(memoryview(staging.reshape(-1).view(np.uint8))[off : off + ln])
            sizes.append(ln)
            total += ln
        return b"".join(_SIZE.pack(s) for s in sizes), parts, total

    @staticmethod
    def _sendmsg_all(conn: socket.socket, parts: list) -> None:
        """sendall over an iovec list, handling partial sends and the
        IOV_MAX window (1024 on Linux)."""
        bufs = [memoryview(p) for p in parts if len(p)]
        i = 0
        while i < len(bufs):
            sent = conn.sendmsg(bufs[i : i + 1024])
            while sent > 0:
                if sent >= bufs[i].nbytes:
                    sent -= bufs[i].nbytes
                    i += 1
                else:
                    bufs[i] = bufs[i][sent:]
                    sent = 0

    def _serve_fetch_striped(self, group: _ServerGroup, tag: int, bids, entries) -> None:
        """Stream a fetch reply as striped chunk frames, size manifest last.

        Chunks are enqueued to the group's lane senders as each block
        resolves — store read overlaps wire send instead of assembling the
        whole reply first — and every chunk frame addresses its destination
        ``(tag, block, offset within block)``, so the lanes need no mutual
        ordering.  The manifest (a FetchBlockReqAck with ``body_len == 0``
        carrying the sizes) goes last on lane 0; the client completes the
        batch once the manifest AND every payload byte have arrived."""
        sizes: List[int] = []
        seq = 0
        chunk = group.chunk_bytes
        checksum = self.conf.wire_checksum
        cspec = self._compress
        pool_cap = self._encoded_pool_cap
        raw_total = wire_total = encoded_chunks = raw_chunks = 0
        cache_hits = cache_misses = cache_evictions = 0
        for i, e in enumerate(entries):
            if e is None or isinstance(e, int):
                sizes.append(SIZE_NOT_FOUND if e is None else e)
                continue
            staging, off, ln = e
            sizes.append(ln)
            if not ln:
                continue
            view = memoryview(staging.reshape(-1).view(np.uint8))[off : off + ln]
            pos = 0
            while pos < ln:
                n = min(chunk, ln - pos)
                hdr = pack_chunk_hdr(tag, i, seq, pos)
                wire = view[pos : pos + n]
                if cspec.enabled:
                    # codec ext on EVERY chunk of the reply (uniform header
                    # length); unprofitable pages ship codec_id=0 raw.  The
                    # chunk offset stays the RAW offset — the client resolves
                    # its scatter destination with decoded coordinates.
                    key = (bids[i], pos, n)
                    hit = None
                    if pool_cap > 0:
                        with self._compress_lock:
                            hit = self._encoded_pool.pop(key, None)
                            if hit is not None:
                                # LRU refresh: re-insert at the MRU end
                                # (insertion order IS recency order)
                                self._encoded_pool[key] = hit
                    if hit is not None:
                        cid, enc = hit
                        cache_hits += 1
                    else:
                        cache_misses += 1
                        # encode OUTSIDE the lock: a concurrent reply racing
                        # on the same chunk just produces the same bytes
                        cid, enc = encode_chunk(cspec, wire)
                        cost = len(enc) if enc is not None else 0
                        if pool_cap > 0:
                            with self._compress_lock:
                                while (
                                    self._encoded_pool_bytes + cost > pool_cap
                                    and self._encoded_pool
                                ):
                                    oldest = next(iter(self._encoded_pool))
                                    _, old = self._encoded_pool.pop(oldest)
                                    cache_evictions += 1
                                    if old is not None:
                                        self._encoded_pool_bytes -= len(old)
                                if key not in self._encoded_pool:
                                    self._encoded_pool[key] = (cid, enc)
                                    self._encoded_pool_bytes += cost
                    if enc is not None:
                        wire = enc
                        encoded_chunks += 1
                    else:
                        raw_chunks += 1
                    hdr += pack_chunk_codec_ext(cid, n)
                if checksum:
                    # 4 B CRC32C trailer, always LAST in the header; it
                    # covers the WIRE (encoded) payload so corruption is
                    # caught before the decoder ever parses the page.  The
                    # client detects both extensions by header length, so
                    # frames stay byte-identical with the knobs off.
                    hdr += _CRC.pack(crc32c(wire))
                prefix = pack_frame_prefix(AmId.FETCH_BLOCK_CHUNK, hdr, len(wire))
                # chaos hook AFTER the crc: an armed garble models payload
                # corrupted in flight, which the client-side crc must catch
                payload = faults.transform(
                    "peer.server.chunk", wire, tag=tag, block=i
                )
                group.enqueue(seq % group.nlanes, [prefix, memoryview(payload)])
                raw_total += n
                wire_total += len(wire)
                seq += 1
                pos += n
        if cspec.enabled:
            with self._compress_lock:
                self.compress_stats["raw_bytes"] += raw_total
                self.compress_stats["wire_bytes"] += wire_total
                self.compress_stats["encoded_chunks"] += encoded_chunks
                self.compress_stats["raw_chunks"] += raw_chunks
                self.compress_stats["cache_hits"] += cache_hits
                self.compress_stats["cache_misses"] += cache_misses
                self.compress_stats["cache_evictions"] += cache_evictions
        blob = b"".join(_SIZE.pack(s) for s in sizes)
        manifest = pack_frame(
            AmId.FETCH_BLOCK_REQ_ACK, _TAG.pack(tag) + _COUNT.pack(len(sizes)) + blob, b""
        )
        group.enqueue(0, [manifest])

    def _serve_conn(self, conn: socket.socket) -> None:
        state = _ConnState(conn)
        try:
            while self._running:
                frame = recv_frame(conn, peer=state.peer)
                if frame is None:
                    return
                self._dispatch_frame(conn, state, *frame)
        except (OSError, ValueError, struct.error):
            # malformed frame or dead socket: drop THIS connection, keep serving
            # (the reference's endpoint error handler evicts one endpoint,
            # UcxWorkerWrapper.scala:248-253)
            pass
        finally:
            self._drop_conn(conn, state)

    def _serve_frame(self, conn: socket.socket, state: _ConnState) -> bool:
        """Reactor worker entry: serve exactly ONE frame; True re-arms the
        connection in the selector.  The header read blocks only briefly —
        the selector fired because bytes are pending — and the dispatch is
        the same code the per-connection threads run."""
        if not self._running:
            return False
        try:
            frame = recv_frame(conn, peer=state.peer)
            if frame is None:
                return False
            self._dispatch_frame(conn, state, *frame)
            return True
        except (OSError, ValueError, struct.error):
            return False

    def _drop_conn(self, conn: socket.socket, state: _ConnState) -> None:
        """Connection teardown shared by both serving planes (idempotent)."""
        if state.group is not None:
            state.group.drop_lane(state.lane)
            with self._groups_lock:
                if self._groups.get(state.group.group_id) is state.group:
                    del self._groups[state.group.group_id]
            state.group = None
        try:
            conn.close()
        except OSError:
            pass
        with self._accepted_lock:
            try:
                self._accepted.remove(conn)
            except ValueError:
                pass

    def _serve_fetch_req(self, conn: socket.socket, state: _ConnState, header: bytes) -> None:
        # obs plane: a trailing trace ext re-parents this serve under the
        # requesting reducer's fetch span (merged-trace view); stripped before
        # any of the historical parsing below sees the header
        trace_ctx, header = split_fetch_req_trace(header)
        if trace_ctx is not None and TRACER.active:
            (count,) = _COUNT.unpack_from(header, _TAG.size)
            with TRACER.executor_scope(self.executor_id):
                with TRACER.activate(TRACER.remote_context(*trace_ctx)):
                    with TRACER.span("server.serve", blocks=count):
                        self._serve_fetch_req_inner(conn, state, header)
            return
        self._serve_fetch_req_inner(conn, state, header)

    def _serve_fetch_req_inner(
        self, conn: socket.socket, state: _ConnState, header: bytes
    ) -> None:
        # popularity cool-down piggybacks on serve traffic (rate-limited
        # inside the tracker); explicit sweeps remain available to owners
        self.sweep_popularity()
        tag, bids = unpack_batch_fetch_req(header)
        app_id = unpack_fetch_req_app_id(header, len(bids))
        gate = None
        code: Optional[int] = None
        if app_id is not None:
            # tenant-addressed request: translate its local shuffle ids (or
            # reject the whole batch with the typed unknown-tenant code — a
            # server with no registry cannot admit ANY tenant traffic)
            if self.tenants is None:
                code = SIZE_UNKNOWN_TENANT
            else:
                try:
                    bids = [
                        ShuffleBlockId(
                            self.tenants.translate(app_id, b.shuffle_id),
                            b.map_id,
                            b.reduce_id,
                        )
                        for b in bids
                    ]
                    gate = self.tenants.gate(app_id)
                except UnknownTenantError:
                    code = SIZE_UNKNOWN_TENANT
        if code is not None:
            entries = [code] * len(bids)
        elif self._io is not None:
            # executor.map is lazy-in-order: all resolves run concurrently,
            # iteration yields each block as soon as it (and its
            # predecessors) complete
            entries = self._io.map(self._resolve_one, bids)
        else:
            entries = map(self._resolve_one, bids)
        group = state.group
        if group is not None and group.ready():
            if gate is None:
                self._serve_fetch_striped(group, tag, bids, entries)
                return
            # per-tenant wire credits: the whole reply's bytes are held
            # against the tenant's gate while its chunks stream, so one
            # tenant's fan-in cannot monopolize every lane
            entries = list(entries)
            total = sum(e[2] for e in entries if isinstance(e, tuple))
            gate.acquire(total)
            try:
                self._serve_fetch_striped(group, tag, bids, entries)
            finally:
                gate.release(total)
            return
        entries = list(entries)
        if state.use_sendmsg:
            sizes, parts, total = self._reply_parts(entries)
            reply_hdr = _TAG.pack(tag) + _COUNT.pack(len(bids)) + sizes
            prefix = pack_frame_prefix(AmId.FETCH_BLOCK_REQ_ACK, reply_hdr, total)
            if gate is not None:
                gate.acquire(total)
            try:
                with state.send_lock:
                    self._sendmsg_all(conn, [prefix] + parts)
            finally:
                if gate is not None:
                    gate.release(total)
            return
        sizes, body = self._assemble_reply(entries)
        reply_hdr = _TAG.pack(tag) + _COUNT.pack(len(bids)) + sizes
        if gate is not None:
            gate.acquire(body.size)
        try:
            with state.send_lock:
                conn.sendall(
                    pack_frame_prefix(AmId.FETCH_BLOCK_REQ_ACK, reply_hdr, body.size)
                )
                if body.size:
                    conn.sendall(memoryview(body))
        finally:
            if gate is not None:
                gate.release(body.size)

    def _dispatch_frame(
        self, conn: socket.socket, state: _ConnState, am_id: AmId, header: bytes, body: bytes
    ) -> None:
        peer, send_lock = state.peer, state.send_lock
        faults.check("peer.server.frame", peer=peer, am_id=int(am_id), executor=self.executor_id)
        if am_id == AmId.FETCH_BLOCK_REQ:
            self._serve_fetch_req(conn, state, header)
        elif am_id == AmId.WIRE_HELLO:
            gid, lane, nlanes, chunk_bytes = unpack_wire_hello(header)
            with self._groups_lock:
                group = self._groups.get(gid)
                if group is None:
                    group = self._groups[gid] = _ServerGroup(gid, nlanes, chunk_bytes)
            state.group, state.lane = group, lane
            group.register(lane, conn, send_lock)
        elif am_id == AmId.MAPPER_INFO:
            info = MapperInfo.unpack(body)
            if self.store is not None:
                try:
                    self.store.apply_mapper_info(info)
                except TransportError:
                    pass  # shuffle not created on this server yet
        elif am_id == AmId.REPLICA_PUT:
            # header extensions after the entry table, detected by the
            # residue mod entry size: 0 plain, 4 crc, 8 codec, 12
            # codec+crc (core/definitions.py).  The crc trailer is
            # always LAST and covers the WIRE (possibly encoded) body —
            # except for the obs trace ext, which (when present) trails
            # even the crc and shifts every residue by 2: strip it first,
            # then the historical dispatch below runs unchanged.
            trace_ctx = None
            residue = (len(header) - REPLICA_HEADER_SIZE) % REPLICA_ENTRY_SIZE
            if residue % 4 == 2:
                trace_ctx = unpack_replica_trace_ext(header)
                if trace_ctx is not None:
                    header = header[:-REPLICA_TRACE_EXT_SIZE]
                    residue = (len(header) - REPLICA_HEADER_SIZE) % REPLICA_ENTRY_SIZE
            if residue in (4, 12):
                # wire.checksum trailer: verify before installing; a
                # corrupt replica gets NO ack, so the pusher's
                # replication_wait names this successor as stalled
                # instead of the store holding silently bad bytes
                (want,) = _CRC.unpack(bytes(header[-4:]))
                header = header[:-4]
                if crc32c(body) != want:
                    sid, src, rnd, _ = unpack_replica_put(header)
                    logger.warning(
                        "replica round (shuffle=%d, src=%d, round=%d) from "
                        "peer %s failed crc32c — discarded, not acked",
                        sid, src, rnd, peer,
                    )
                    return
            if residue in (8, 12):
                # compress.codec ext: the whole round body is one
                # encoded page; a decode failure is handled exactly
                # like a crc mismatch — discard, no ack
                codec_id, raw_len = unpack_chunk_codec_ext(
                    header, len(header) - CHUNK_CODEC_EXT_SIZE
                )
                header = header[:-CHUNK_CODEC_EXT_SIZE]
                if codec_id != CODEC_RAW or raw_len != len(body):
                    decoded = bytearray(raw_len)
                    try:
                        decode_page(codec_id, body, decoded)
                    except CodecError as e:
                        sid, src, rnd, _ = unpack_replica_put(header)
                        logger.warning(
                            "replica round (shuffle=%d, src=%d, round=%d) "
                            "from peer %s failed page decode (%s) — "
                            "discarded, not acked",
                            sid, src, rnd, peer, e,
                        )
                        return
                    body = decoded
            sid, src, rnd, entries = unpack_replica_put(header)
            faults.check(
                "replica.apply", shuffle_id=sid, src_executor=src, round_idx=rnd
            )
            if self.store is not None:
                try:
                    if trace_ctx is not None and TRACER.active:
                        # parent the apply under the pusher's replica.push span
                        with TRACER.executor_scope(self.executor_id):
                            with TRACER.activate(TRACER.remote_context(*trace_ctx)):
                                with TRACER.span(
                                    "server.replica_apply",
                                    shuffle_id=sid,
                                    src_executor=src,
                                    round=rnd,
                                ):
                                    self.store.put_replica(sid, src, rnd, entries, body)
                    else:
                        self.store.put_replica(sid, src, rnd, entries, body)
                except ResourceExhaustedError as e:
                    # store hard watermark: handled like a crc mismatch —
                    # discard, no ack — so the pusher's replication_wait
                    # names this successor stalled instead of the serving
                    # connection dying under memory pressure
                    logger.warning(
                        "replica round (shuffle=%d, src=%d, round=%d) from "
                        "peer %s shed under memory pressure (%s) — not acked",
                        sid, src, rnd, peer, e,
                    )
                    return
            with send_lock:
                conn.sendall(
                    pack_frame(AmId.REPLICA_ACK, pack_replica_ack(sid, src, rnd))
                )
        elif am_id in (AmId.MEMBER_SUSPECT, AmId.MEMBER_REJOIN):
            epoch, subject, observer = unpack_member_event(header)
            if self.member_sink is not None:
                self.member_sink(int(am_id), epoch, subject, observer)
        elif am_id == AmId.TRACE_PULL:
            # obs plane: hand the puller this executor's slice of the trace
            # ring (the loopback mesh shares one process-wide TRACER, so
            # events are attributed by their executor scope; merge_events
            # dedups overlap by uid).  Runs on a serving worker thread —
            # never the reactor loop lane (reactor-discipline).
            (tag,) = _TAG.unpack_from(header)
            events = TRACER.events
            if self.executor_id is not None:
                events = [e for e in events if e.get("eid") == self.executor_id]
            payload = json.dumps(
                {
                    "executor": self.executor_id,
                    "events": events,
                    "dropped": TRACER.dropped,
                }
            ).encode()
            with send_lock:
                conn.sendall(pack_frame(AmId.TRACE_PULL, _TAG.pack(tag), payload))
        elif am_id == AmId.METRICS_PULL:
            (tag,) = _TAG.unpack_from(header)
            text = self.metrics.prometheus_text() if self.metrics is not None else ""
            with send_lock:
                conn.sendall(pack_frame(AmId.METRICS_PULL, _TAG.pack(tag), text.encode()))
        elif am_id == AmId.HOT_SET_PULL:
            # popularity plane: hand the puller this executor's advertised
            # hot-set table — {shuffle: [holder ids]} for every shuffle whose
            # replica set is currently widened.  Readers rotate their fetches
            # across the holders.  Empty table when nothing is hot (or the
            # popularity tier is off) — a valid, cheap reply.
            (tag,) = _TAG.unpack_from(header)
            hot = self.hot_set_provider() if self.hot_set_provider is not None else {}
            with send_lock:
                conn.sendall(
                    pack_frame(AmId.HOT_SET_PULL, _TAG.pack(tag), pack_hot_set(hot))
                )
        elif am_id == AmId.INIT_EXECUTOR_REQ:
            (eid,) = _TAG.unpack_from(header)
            self.handshaken[eid] = body
            with send_lock:
                conn.sendall(pack_frame(AmId.INIT_EXECUTOR_ACK, header, b""))

    def close(self) -> None:
        self._running = False
        try:
            self._srv.close()
        except OSError:
            pass
        with self._groups_lock:
            groups, self._groups = list(self._groups.values()), {}
        for g in groups:
            g.close()
        with self._accepted_lock:
            accepted, self._accepted = list(self._accepted), []
        for conn in accepted:
            try:
                conn.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                conn.close()
            except OSError:
                pass
        if self._reactor is not None:
            # after the conns are shut down, so no worker is blocked mid-frame
            self._reactor.close()
        if self._io is not None:
            self._io.shutdown(wait=False)


class _PeerConnection:
    """One client connection: sender + receiver thread parking acks for progress().

    The endpoint-cache entry of the reference (UcxWorkerWrapper.scala:64,233-276).
    Fetch-ack bodies are received **directly into the caller's result buffers**
    (``ack_buffers`` lookup) — the RNDV-into-registered-bounce-buffer receive
    (UcxWorkerWrapper.scala:142-185) rather than parking a parsed copy; the
    parked frame then carries an empty body and progress() only completes
    requests.  ``activity`` is set whenever a frame parks (the wakeup doorbell).
    """

    def __init__(
        self,
        address: Tuple[str, int],
        ack_buffers: Optional[Callable[[int], Optional[list]]] = None,
        ack_done: Optional[Callable[[int], None]] = None,
        activity: Optional[threading.Event] = None,
        conf: Optional[TpuShuffleConf] = None,
        lane: int = 0,
        chunk_sink: Optional[Callable[[int, int, int, int], Optional[memoryview]]] = None,
        chunk_done: Optional[Callable[[int, int, bool], Optional[bytes]]] = None,
        manifest_sink: Optional[Callable[[bytes], Optional[bytes]]] = None,
    ) -> None:
        #: host:port of the server end — every raised error names it
        self.peer = f"{address[0]}:{address[1]}"
        timeout_ms = conf.wire_timeout_ms if conf is not None else 30000
        self._timeout_s: Optional[float] = (timeout_ms / 1000.0) if timeout_ms else None
        try:
            self.sock = socket.create_connection(address, timeout=self._timeout_s or 30)
        except socket.timeout:
            raise OSError(f"connect to peer {self.peer} timed out after {timeout_ms} ms") from None
        # the connect timeout persists as the socket timeout: mid-frame reads
        # and stuck sends fail after wire_timeout_ms instead of hanging; the
        # idle wait for the next frame header is exempt (idle_ok below).
        # wire_timeout_ms = 0 clears it — the historical block-forever wire.
        self.sock.settimeout(self._timeout_s)
        # deep recv window default keeps the scatter recv fed between polls
        apply_wire_sockopts(self.sock, conf, rcvbuf=4 << 20)
        self.pending: Dict[int, Callable[[bytes, bytes], None]] = {}
        self.lock = threading.Lock()
        #: parked (am_id, header, body, scattered) frames; ``scattered`` marks
        #: acks whose payload already sits in the caller's result buffers
        self.inbox: Deque[Tuple[AmId, bytes, bytes, bool]] = deque()
        self.inbox_lock = threading.Lock()
        self.ack_buffers = ack_buffers
        self.ack_done = ack_done
        self.activity = activity
        #: striped-wire role (lane of a _StripeGroup): chunk_sink maps a chunk
        #: to its destination view, chunk_done/manifest_sink account receive
        #: progress and hand back the manifest header once the batch completes
        self.lane = lane
        self.chunk_sink = chunk_sink
        self.chunk_done = chunk_done
        self.manifest_sink = manifest_sink
        # per-lane telemetry — written only by this connection's recv thread,
        # read racily by wire_lane_stats() (monotonic counters, no lock needed)
        self.rx_bytes = 0
        self.rx_syscalls = 0
        self.rx_stall_ns = 0
        self.stall_samples: Deque[int] = deque(maxlen=4096)
        #: reusable landing buffer for ENCODED chunk payloads (compressed wire
        #: path): wire bytes land here, then decode into the chunk's final
        #: destination view — written only by this connection's recv thread,
        #: so the pool needs no lock (same contract as the rx_* counters)
        self._codec_scratch: Optional[bytearray] = None
        #: the exception that killed the recv loop (None for a clean EOF) —
        #: _fail_conn_inflight surfaces a typed error (BlockCorruptError)
        #: instead of the generic connection-lost one when it is set
        self.last_error: Optional[Exception] = None
        self.alive = True
        self.recv_thread = threading.Thread(target=self._recv_loop, daemon=True)
        self.recv_thread.start()

    # -- counted zero-copy receive primitives (recv thread only) -----------

    def _recv_exact(self, n: int, idle_ok: bool = False) -> Optional[bytearray]:
        out = bytearray(n)
        mv = memoryview(out)
        got = 0
        while got < n:
            try:
                r = self.sock.recv_into(mv[got:], n - got)
            except socket.timeout:
                # idle between frames is normal; hung MID-frame is a fault
                if idle_ok and got == 0:
                    if not self.alive:
                        return None
                    continue
                raise OSError(
                    f"peer {self.peer} (lane {self.lane}) hung mid-frame: read "
                    f"timed out with {got}/{n} B received"
                ) from None
            if r == 0:
                return None
            got += r
            self.rx_bytes += r
            self.rx_syscalls += 1
        return out

    def _recv_into(self, mv: memoryview, what: str = "") -> None:
        """recv_into a caller-owned destination until full — the zero-copy
        scatter receive (no staging allocation, no join copy).  ``what``
        carries block context (tag/block id) into any raised error."""
        while mv.nbytes:
            try:
                n = self.sock.recv_into(mv, mv.nbytes)
            except socket.timeout:
                raise OSError(
                    f"peer {self.peer} (lane {self.lane}) hung mid-body{what}: "
                    f"read timed out with {mv.nbytes} B still expected"
                ) from None
            if n == 0:
                raise OSError(f"peer {self.peer} (lane {self.lane}) closed mid-body{what}")
            self.rx_bytes += n
            self.rx_syscalls += 1
            mv = mv[n:]

    def _recv_ack_into_buffers(self, header: bytes, blen: int) -> bool:
        """Scatter a fetch-ack body straight into the batch's result buffers.
        Returns False when the buffers are unknown (caller falls back to a
        parked bytes body)."""
        if self.ack_buffers is None:
            return False
        (tag,) = _TAG.unpack_from(header, 0)
        (count,) = _COUNT.unpack_from(header, _TAG.size)
        sizes = [
            _SIZE.unpack_from(header, _TAG.size + _COUNT.size + i * _SIZE.size)[0]
            for i in range(count)
        ]
        # Trust the FRAME boundary, not the header: a skewed/buggy peer whose
        # size list disagrees with blen would otherwise make us read past the
        # frame into the next one.  Fall back to the parked-bytes path, which
        # fails loudly instead of completing with corrupt data.
        if sum(s for s in sizes if s > 0) != blen:
            return False
        bufs = self.ack_buffers(tag)
        if bufs is None or len(bufs) != count:
            return False
        for i in range(count):
            size = sizes[i]
            if size <= 0:
                continue
            view = bufs[i].host_view() if bufs[i] is not None else None
            if view is not None and size <= view.size:
                self._recv_into(memoryview(view)[:size], what=f" (fetch tag {tag}, block {i})")
            else:  # oversized/unknown: drain and let progress() report failure
                if self._recv_exact(size) is None:
                    raise OSError(
                        f"peer {self.peer} (lane {self.lane}) closed mid-body "
                        f"(fetch tag {tag}, block {i})"
                    )
        return True

    def _park(self, am_id: AmId, header: bytes, body: bytes, scattered: bool) -> None:
        # park — completion happens under progress() (explicit-poll contract)
        with self.inbox_lock:
            self.inbox.append((am_id, header, body, scattered))
        if self.activity is not None:
            self.activity.set()

    def _codec_buf(self, n: int) -> memoryview:
        """Recv-thread-only scratch for encoded chunk payloads (grown, never
        shrunk): one live landing buffer per lane, reused chunk to chunk."""
        if self._codec_scratch is None or len(self._codec_scratch) < n:
            self._codec_scratch = bytearray(max(n, 1 << 16))
        return memoryview(self._codec_scratch)[:n]

    def _recv_chunk(self, header: bytes, blen: int) -> None:
        """Receive one striped chunk straight into its destination buffer.

        The chunk is self-addressing — (tag, block, offset within block) —
        so this lane needs no coordination with its siblings.  If this chunk
        is the batch's last missing piece, park the manifest header here so
        progress() completes the batch on whichever lane finished last.

        Header extensions are detected by header length (24 plain, +8 codec
        ext, +4 crc trailer last — core/definitions.py).  An encoded chunk
        lands in this lane's scratch and decodes into the destination view;
        the crc covers the ENCODED bytes, so corruption is caught before the
        decoder parses anything, and a decode failure (CodecError) surfaces
        as ``BlockCorruptError`` exactly like a crc mismatch.  Either kills
        this lane — the batch then fails typed and the reducer-side failover
        (``_retry_fetch``) re-sources the block from a replica holder.
        Receive accounting is in DECODED bytes (``raw_len``), matching the
        manifest totals the stripe tracker sums."""
        tag, block, seq, offset = unpack_chunk_hdr(header)
        ext = len(header) - CHUNK_HEADER_SIZE
        want = None
        codec_id: Optional[int] = None
        raw_len = blen
        if ext == 4:
            (want,) = _CRC.unpack_from(header, CHUNK_HEADER_SIZE)
        elif ext in (CHUNK_CODEC_EXT_SIZE, CHUNK_CODEC_EXT_SIZE + 4):
            codec_id, raw_len = unpack_chunk_codec_ext(header, CHUNK_HEADER_SIZE)
            if ext == CHUNK_CODEC_EXT_SIZE + 4:
                (want,) = _CRC.unpack_from(header, CHUNK_HEADER_SIZE + CHUNK_CODEC_EXT_SIZE)
        mv = self.chunk_sink(tag, block, offset, raw_len) if raw_len else None
        ok = False
        try:
            what = f" (fetch tag {tag}, block {block}, chunk offset {offset})"
            if codec_id is None or (codec_id == CODEC_RAW and raw_len == blen):
                # plain chunk (or explicit raw fallback): payload IS the slice
                data = b""
                if mv is not None:
                    self._recv_into(mv, what=what)
                    data = mv
                elif blen:  # unknown tag / oversized target: drain off the wire
                    data = self._recv_exact(blen)
                    if data is None:
                        raise OSError(
                            f"peer {self.peer} (lane {self.lane}) closed mid-chunk "
                            f"(fetch tag {tag}, block {block})"
                        )
                if want is not None and blen and crc32c(data) != want:
                    raise BlockCorruptError(
                        -1, -1, block,
                        f"striped chunk (fetch tag {tag}, block {block}, offset "
                        f"{offset}) from peer {self.peer} lane {self.lane} failed "
                        "its crc32c check",
                    )
            else:
                # encoded page: wire bytes -> lane scratch, verify, decode
                # into the final destination (still one write into the
                # result buffer; the scatter offsets are raw coordinates)
                enc = self._codec_buf(blen)
                self._recv_into(enc, what=what)
                if want is not None and crc32c(enc) != want:
                    raise BlockCorruptError(
                        -1, -1, block,
                        f"striped chunk (fetch tag {tag}, block {block}, offset "
                        f"{offset}) from peer {self.peer} lane {self.lane} failed "
                        "its crc32c check",
                    )
                if mv is not None:
                    try:
                        decode_page(codec_id, enc, mv)
                    except CodecError as e:
                        raise BlockCorruptError(
                            -1, -1, block,
                            f"striped chunk (fetch tag {tag}, block {block}, "
                            f"offset {offset}) from peer {self.peer} lane "
                            f"{self.lane} failed page decode: {e}",
                        ) from None
            ok = True
        finally:
            # the done callback must run even when the socket dies mid-chunk:
            # it clears the tag's scattering mark so a later sweep can fail it
            done_hdr = self.chunk_done(tag, raw_len if ok else 0, mv is not None)
        if done_hdr is not None:
            self._park(AmId.FETCH_BLOCK_REQ_ACK, done_hdr, b"", True)

    def _recv_loop(self) -> None:
        try:
            while self.alive:
                faults.check("peer.client.recv", peer=self.peer, lane=self.lane)
                t0 = time.monotonic_ns()
                hdr = self._recv_exact(FRAME_HEADER_SIZE, idle_ok=True)
                stall = time.monotonic_ns() - t0
                self.rx_stall_ns += stall
                self.stall_samples.append(stall)
                if hdr is None:
                    break
                hdr = faults.transform("peer.client.frame", hdr, peer=self.peer, lane=self.lane)
                am_id, hlen, blen = unpack_frame_header(hdr)
                if hlen + blen > _MAX_FRAME:
                    raise ValueError(f"frame too large from peer {self.peer}")
                if am_id == AmId.SERVER_BUSY:
                    # load shed: the server refused this connection over its
                    # accept backlog and closes right after.  Die typed so
                    # in-flight batches fail RETRYABLE (backoff + retry)
                    # instead of with the generic connection-lost error.
                    self.last_error = ResourceExhaustedError(
                        detail=f"peer {self.peer} shed the connection "
                        "(accept backlog full)"
                    )
                    break
                header = self._recv_exact(hlen) if hlen else b""
                if hlen and header is None:
                    break
                if am_id == AmId.FETCH_BLOCK_CHUNK and self.chunk_done is not None:
                    self._recv_chunk(header, blen)
                    continue
                if (
                    am_id == AmId.FETCH_BLOCK_REQ_ACK
                    and blen == 0
                    and self.manifest_sink is not None
                ):
                    # striped reply manifest: sizes only, payload rides (or
                    # rode) chunk frames — completion may be here or on a
                    # sibling lane still scattering
                    done_hdr = self.manifest_sink(bytes(header))
                    if done_hdr is not None:
                        self._park(am_id, done_hdr, b"", True)
                    continue
                scattered = False
                if am_id == AmId.FETCH_BLOCK_REQ_ACK and self.ack_buffers is not None:
                    (tag,) = _TAG.unpack_from(header, 0)
                    try:
                        scattered = self._recv_ack_into_buffers(header, blen)
                    finally:
                        if self.ack_done is not None:
                            self.ack_done(tag)
                if not scattered:
                    body = self._recv_exact(blen) if blen else b""
                    if blen and body is None:
                        break
                else:
                    body = b""  # payload already scattered into result buffers
                self._park(am_id, header, body, scattered)
        except (OSError, ValueError, struct.error, TransportError) as e:
            self.last_error = e
        self.alive = False
        if self.activity is not None:
            self.activity.set()  # wake parked waiters so they observe the death
        try:  # release the fd as soon as the peer is gone
            self.sock.close()
        except OSError:
            pass

    def send(self, frame: bytes) -> None:
        with self.lock:
            self.sock.sendall(frame)

    def drain_one(self) -> Optional[Tuple[AmId, bytes, bytes, bool]]:
        with self.inbox_lock:
            return self.inbox.popleft() if self.inbox else None

    def close(self) -> None:
        self.alive = False
        try:
            self.sock.close()
        except OSError:
            pass


class _StripeGroup:
    """Client-side bundle of K lane connections acting as ONE logical peer
    connection — it lives in the transport's conn cache and duck-types
    ``_PeerConnection`` (alive / send / drain_one / inbox / close), so the
    progress() pump, eviction, zombie retirement, and failure sweeps all work
    on it unchanged.

    Requests and non-fetch AMs travel on lane 0; fetch replies return as a
    size manifest plus self-addressing chunks striped across every lane
    (core/definitions.py, AM ids 5-6).  ``alive`` is all-lanes-alive: a chunk
    lost with one lane makes the group's in-flight batches unrecoverable, so
    a single dead lane fails the whole bundle fast."""

    def __init__(self, group_id: int, lanes: List[_PeerConnection]) -> None:
        self.group_id = group_id
        self.lanes = lanes

    @property
    def peer(self) -> str:
        return self.lanes[0].peer if self.lanes else "?"

    @property
    def alive(self) -> bool:
        return all(lane.alive for lane in self.lanes)

    @property
    def inbox(self) -> bool:
        # truthiness only (zombie retirement): any lane still holding frames
        return any(lane.inbox for lane in self.lanes)

    @property
    def last_error(self) -> Optional[Exception]:
        # a typed lane death (e.g. BlockCorruptError) wins over plain EOFs
        for lane in self.lanes:
            if isinstance(lane.last_error, TransportError):
                return lane.last_error
        for lane in self.lanes:
            if lane.last_error is not None:
                return lane.last_error
        return None

    def send(self, frame: bytes) -> None:
        self.lanes[0].send(frame)

    def drain_one(self) -> Optional[Tuple[AmId, bytes, bytes, bool]]:
        for lane in self.lanes:
            frame = lane.drain_one()
            if frame is not None:
                return frame
        return None

    def close(self) -> None:
        for lane in self.lanes:
            lane.close()

    def lane_stats(self) -> List[Dict[str, int]]:
        return [
            {
                "lane": lane.lane,
                "rx_bytes": lane.rx_bytes,
                "rx_syscalls": lane.rx_syscalls,
                "rx_stall_ns": lane.rx_stall_ns,
                "rx_stall_p99_ns": _stall_p99_ns(lane),
            }
            for lane in self.lanes
        ]


def _stall_p99_ns(conn: "_PeerConnection") -> int:
    """p99 of the connection's recent frame-stall samples (time spent waiting
    for the next frame header).  Snapshot + sort of a bounded deque; the recv
    thread appends concurrently, which at worst skews one sample."""
    samples = sorted(conn.stall_samples)
    if not samples:
        return 0
    return samples[min(len(samples) - 1, int(0.99 * len(samples)))]


class _StripeRx:
    """Per-tag striped-receive accounting; every field is guarded by the
    transport's ``_tag_lock`` (mutated from multiple lane recv threads)."""

    __slots__ = ("manifest", "total", "received")

    def __init__(self) -> None:
        self.manifest: Optional[bytes] = None  # manifest header, once landed
        self.total: Optional[int] = None  # payload bytes promised by the sizes
        self.received = 0  # chunk payload bytes landed across all lanes


#: EWMA smoothing factor for per-peer fetch latency and error rate — heavy
#: enough that a handful of samples move the score, light enough that one
#: outlier does not trip anything by itself.
_HEALTH_ALPHA = 0.25

#: Circuit-breaker states (closed = healthy traffic flows; open = peer is
#: sick, new fetches skip it for the replica ring; half-open = cooldown
#: elapsed, exactly one probe request is in flight to test recovery).
BREAKER_CLOSED = "closed"
BREAKER_OPEN = "open"
BREAKER_HALF_OPEN = "half_open"


class _PeerHealth:
    """Per-executor health score + circuit breaker; every field is guarded by
    the transport's ``_health_lock`` (a leaf lock: no calls out while held)."""

    __slots__ = (
        "latency_ewma_ns",
        "error_ewma",
        "consecutive_failures",
        "state",
        "opened_at_ns",
        "probe_inflight",
        "successes",
        "failures",
        "trips",
    )

    def __init__(self) -> None:
        self.latency_ewma_ns = 0.0  # EWMA of observed fetch completion latency
        self.error_ewma = 0.0  # EWMA of the error indicator (1=fail, 0=ok)
        self.consecutive_failures = 0
        self.state = BREAKER_CLOSED
        self.opened_at_ns = 0
        self.probe_inflight = False
        self.successes = 0
        self.failures = 0
        self.trips = 0


class PeerTransport(ShuffleTransport):
    """ShuffleTransport over TCP peers — the socket twin of the loopback
    transport, used by multi-process deployments and the Spark shim."""

    def __init__(
        self,
        conf: Optional[TpuShuffleConf] = None,
        executor_id: ExecutorId = 0,
        store: Optional[HbmBlockStore] = None,
    ) -> None:
        self.conf = conf or TpuShuffleConf()
        self.executor_id = executor_id
        self.store = store if store is not None else HbmBlockStore(self.conf, executor_id=executor_id)
        self._registry: Dict[BlockId, Block] = {}  #: guarded by self._registry_lock
        self._registry_lock = threading.Lock()
        self.server: Optional[BlockServer] = None
        # Connection cache keyed by (executor, slot): callers map onto
        # num_client_workers parallel connections per peer by thread identity —
        # the reference's thread->worker routing ``threadId % numWorkers``
        # (UcxShuffleTransport.scala:277-279, UcxShuffleConf.scala:80-86).
        self._conns: Dict[Tuple[ExecutorId, int], Union[_PeerConnection, _StripeGroup]] = {}  #: guarded by self._conn_lock
        self._conn_addrs: Dict[ExecutorId, Tuple[str, int]] = {}  #: guarded by self._conn_lock
        self._conn_lock = threading.Lock()
        self._slot_local = threading.local()
        self._slot_rr = 0  #: guarded by self._tag_lock
        self._connecting: Dict[Tuple[ExecutorId, int], threading.Event] = {}  #: guarded by self._conn_lock
        self._next_tag = 0  #: guarded by self._tag_lock
        self._tag_lock = threading.Lock()
        self._inflight: Dict[int, Tuple[List[Request], List[MemoryBlock], List[Optional[OperationCallback]], Optional[Union[_PeerConnection, _StripeGroup]]]] = {}  #: guarded by self._tag_lock
        # tag -> count of lane recv threads currently writing the tag's result
        # buffers (a counter, not a set: with striping, several lanes scatter
        # one tag concurrently and set-discard would drop siblings' marks)
        self._scattering: Dict[int, int] = {}  #: guarded by self._tag_lock
        #: striped-receive progress per in-flight tag (striped groups only)
        self._stripe_rx: Dict[int, _StripeRx] = {}  #: guarded by self._tag_lock
        self._zombies: List[_PeerConnection] = []  #: guarded by self._conn_lock (evicted, not yet drained)
        # -- neighbor replication (client side of REPLICA_PUT/REPLICA_ACK) --
        #: outstanding REPLICA_ACKs per shuffle this executor pushed
        self._replica_pending: Dict[int, int] = {}  #: guarded by self._tag_lock
        #: shuffles whose replica push is still queued or in flight
        self._replica_pushing: set = set()  #: guarded by self._tag_lock
        #: outstanding acks per shuffle broken down by successor executor —
        #: lets replication_wait name WHICH neighbor stalled, not just that one did
        self._replica_unacked: Dict[int, Dict[ExecutorId, int]] = {}  #: guarded by self._tag_lock
        #: replication jobs awaiting the replicator worker, oldest first —
        #: ``(shuffle_id, neighbors | None)`` tuples; None = the ring's
        #: ``replication.factor`` successors (seal-time push), an explicit
        #: list = a popularity widen job pushing to the extra holders only
        self._replica_queue: deque = deque()  #: guarded by self._tag_lock
        self._replica_worker: Optional[threading.Thread] = None  #: guarded by self._tag_lock
        self._replica_run = True  #: guarded by self._tag_lock (close() clears)
        self._replica_wake = threading.Event()
        #: replication telemetry: rounds/bytes pushed, acks seen, failed sends,
        #: rounds dropped by the backlog cap, and the live backlog gauge (bytes
        #: of replica payload admitted to the wire but not yet sent)
        self.replica_stats: Dict[str, int] = {
            "pushed_rounds": 0,
            "pushed_bytes": 0,
            "acks": 0,
            "failed": 0,
            "dropped_rounds": 0,
            "replica_backlog_bytes": 0,
        }  #: guarded by self._tag_lock
        #: Optional ClusterMembership installed by elastic owners (the SPMD
        #: driver / loopback harness); peer-observed wire failures and rejoin
        #: announcements feed it.  None = membership-unaware (the default).
        self.membership = None
        #: Popularity-aware serving tier (serve.hotThresholdFetchesPerSec):
        #: the per-block fetch-rate tracker the block server observes into
        #: (None = tier off, zero overhead), the advertised holder sets of
        #: currently-hot shuffles (served to readers via HOT_SET_PULL), and
        #: the reader-side TTL cache of peers' advertisements.
        self.popularity: Optional[BlockPopularity] = (
            BlockPopularity(self.conf.serve_hot_threshold_fetches_per_sec)
            if self.conf.serve_hot_threshold_fetches_per_sec > 0
            else None
        )
        self._hot_shuffles: Dict[int, List[ExecutorId]] = {}  #: guarded by self._tag_lock
        self._hot_holders_cache: Dict[ExecutorId, Tuple[float, Dict[int, List[int]]]] = {}  #: guarded by self._tag_lock
        #: Gray-failure plane: per-executor health scores + circuit breakers.
        #: Scoring (latency/error EWMAs) is always on — pure bookkeeping, no
        #: behavior change; the breaker only trips when
        #: ``breaker.failureThreshold`` > 0.  _health_lock is a LEAF lock:
        #: nothing is called while it is held.
        self._health: Dict[ExecutorId, _PeerHealth] = {}  #: guarded by self._health_lock
        self._health_lock = threading.Lock()
        #: Multi-tenant identity of this executor's fetches: with
        #: ``conf.tenants_enabled`` and an ``app_id`` set, every
        #: FETCH_BLOCK_REQ carries the tenant header extension and its triples
        #: use tenant-local shuffle ids (servers translate via their
        #: registry).  None (the default) emits the historical frames.
        self.app_id: Optional[str] = None
        self.stats_agg = StatsAggregator() if self.conf.collect_stats else None
        #: obs plane: this executor's unified metrics surface.  Subsystem
        #: providers are registered below; stores/services owned elsewhere
        #: (eviction manager, tenant registry, the cluster's elastic stats)
        #: register theirs through the same object.  METRICS_PULL serves it.
        self.metrics = MetricsRegistry(executor_id=executor_id)
        #: obs plane: TRACE_PULL/METRICS_PULL replies waiting on their tag
        self._pull_pending: Dict[int, dict] = {}  #: guarded by self._tag_lock
        self._metrics_http = None
        #: always-on flight recorder: ring stays warm, TransportError /
        #: elastic-recovery / chaos triggers capture postmortem bundles
        self.recorder = FlightRecorder(
            TRACER,
            executor_id=executor_id,
            postmortem_dir=self.conf.obs_postmortem_dir or None,
            ring_capacity=self.conf.obs_ring_capacity,
        )
        self.recorder.attach_registry(self.metrics)
        self.recorder.attach_membership(self._membership_snapshot)
        self.recorder.install()
        self._register_metrics_providers()
        #: Wakeup doorbell (conf.use_wakeup): recv threads set it when an ack
        #: parks, so fetch loops can sleep in wait_for_activity() instead of
        #: busy-spinning progress() against the receiver's GIL slices.
        self._activity = threading.Event()
        # asynchronous neighbor replication: seal() hands the sealed shuffle
        # to a background push thread (no frames at replication_factor = 0)
        self.store.on_seal = self._on_store_seal

    def _ack_buffers(self, tag: int) -> Optional[list]:
        """Recv-thread lookup: the batch's result buffers, WITHOUT popping the
        inflight entry (progress() still owns completion).  Marks the tag as
        scattering so a concurrent eviction cannot fail-and-release the buffers
        while the recv thread writes into them; ``_ack_buffers_done`` clears."""
        with self._tag_lock:
            entry = self._inflight.get(tag)
            if entry is None:
                return None
            self._scattering[tag] = self._scattering.get(tag, 0) + 1
            return list(entry[1])

    def _ack_buffers_done(self, tag: int) -> None:
        with self._tag_lock:
            self._unmark_scattering_locked(tag)

    def _unmark_scattering_locked(self, tag: int) -> None:
        """Caller holds self._tag_lock."""
        left = self._scattering.get(tag, 0) - 1
        if left > 0:
            self._scattering[tag] = left
        else:
            self._scattering.pop(tag, None)

    # -- striped-wire receive callbacks (called from lane recv threads) ----

    def _chunk_buffers(self, tag: int, block: int, offset: int, nbytes: int) -> Optional[memoryview]:
        """Resolve one chunk's destination: a view of the batch's result
        buffer at the chunk's final offset (the zero-copy scatter target).
        Marks the tag as scattering so eviction cannot fail-and-release the
        buffer mid-write; ``_chunk_done`` clears the mark and accounts."""
        with self._tag_lock:
            entry = self._inflight.get(tag)
            if entry is None or not 0 <= block < len(entry[1]):
                return None
            buf = entry[1][block]
            view = buf.host_view() if buf is not None else None
            if view is None or offset + nbytes > view.size:
                return None  # oversized block: drain; progress() reports failure
            self._scattering[tag] = self._scattering.get(tag, 0) + 1
            return memoryview(view)[offset : offset + nbytes]

    def _chunk_done(self, tag: int, nbytes: int, scattered: bool) -> Optional[bytes]:
        """Account one received chunk.  Returns the manifest header iff this
        chunk completed the batch (manifest seen AND all payload bytes in), so
        the calling lane parks the completion frame for progress()."""
        with self._tag_lock:
            if scattered:
                self._unmark_scattering_locked(tag)
            rx = self._stripe_rx.get(tag)
            if rx is None:
                return None
            rx.received += nbytes
            return self._stripe_complete_locked(tag)

    def _on_manifest(self, header: bytes) -> Optional[bytes]:
        """A striped reply's size manifest landed (FetchBlockReqAck with an
        empty body).  Returns the header iff the batch is now complete —
        either here or, for unknown tags, immediately (parked for the generic
        frame handler, which drops stale tags)."""
        if len(header) < _TAG.size + _COUNT.size:
            return header  # runt header: parked; _handle_frame ignores it
        (tag,) = _TAG.unpack_from(header, 0)
        (count,) = _COUNT.unpack_from(header, _TAG.size)
        if len(header) < _TAG.size + _COUNT.size + count * _SIZE.size:
            return header  # truncated sizes: let _handle_frame fail the batch
        total = 0
        for i in range(count):
            (s,) = _SIZE.unpack_from(header, _TAG.size + _COUNT.size + i * _SIZE.size)
            if s > 0:
                total += s
        with self._tag_lock:
            rx = self._stripe_rx.get(tag)
            if rx is None:
                return header  # unknown/failed tag: park; handler discards
            rx.manifest = bytes(header)
            rx.total = total
            return self._stripe_complete_locked(tag)

    def _stripe_complete_locked(self, tag: int) -> Optional[bytes]:
        """Caller holds self._tag_lock."""
        rx = self._stripe_rx.get(tag)
        if rx is None or rx.total is None or rx.received < rx.total:
            return None
        del self._stripe_rx[tag]
        return rx.manifest

    def wire_lane_stats(self) -> List[Dict[str, int]]:
        """Per-lane receive telemetry for striped connections: bytes,
        recv_into syscalls, and cumulative frame-stall time per lane.
        Single-lane connections report as lane 0 of their key."""
        with self._conn_lock:
            conns = list(self._conns.items())
        out: List[Dict[str, int]] = []
        for (eid, slot), conn in conns:
            if isinstance(conn, _StripeGroup):
                for s in conn.lane_stats():
                    out.append({"executor": eid, "slot": slot, **s})
            else:
                out.append(
                    {
                        "executor": eid,
                        "slot": slot,
                        "lane": 0,
                        "rx_bytes": conn.rx_bytes,
                        "rx_syscalls": conn.rx_syscalls,
                        "rx_stall_ns": conn.rx_stall_ns,
                        "rx_stall_p99_ns": _stall_p99_ns(conn),
                    }
                )
        return out

    def compress_stats(self) -> Dict[str, int]:
        """Serve-side wire-compression telemetry (tier a): decoded vs wire
        bytes this executor streamed through chunk frames, plus the page
        encode/raw-fallback split.  All zeros when ``compress.codec`` is off
        or no striped reply has been served yet."""
        if self.server is None:
            return {"raw_bytes": 0, "wire_bytes": 0, "encoded_chunks": 0, "raw_chunks": 0}
        return self.server.compress_snapshot()

    # -- obs plane ---------------------------------------------------------

    def _replica_stats_snapshot(self) -> Dict[str, int]:
        with self._tag_lock:
            return dict(self.replica_stats)

    def _membership_snapshot(self) -> Optional[dict]:
        """Flight-recorder leg: the executor's membership view, or None when
        membership-unaware (elastic off)."""
        m = self.membership
        if m is None:
            return None
        try:
            return m.snapshot()  # {"epoch", "alive", "dead"}
        except Exception:
            return None

    # -- gray-failure plane: peer health + circuit breakers ----------------

    def _health_of(self, executor_id: ExecutorId) -> _PeerHealth:
        """Caller holds self._health_lock."""
        h = self._health.get(executor_id)
        if h is None:
            h = self._health[executor_id] = _PeerHealth()
        return h

    def record_peer_success(self, executor_id: ExecutorId, latency_ns: int = 0) -> None:
        """A fetch against ``executor_id`` completed: fold the latency into
        the EWMA, clear the failure streak, and close a half-open breaker
        (the probe came back)."""
        with self._health_lock:
            h = self._health_of(executor_id)
            h.successes += 1
            h.consecutive_failures = 0
            h.error_ewma += _HEALTH_ALPHA * (0.0 - h.error_ewma)
            if latency_ns > 0:
                if h.latency_ewma_ns == 0.0:
                    h.latency_ewma_ns = float(latency_ns)
                else:
                    h.latency_ewma_ns += _HEALTH_ALPHA * (latency_ns - h.latency_ewma_ns)
            if h.state != BREAKER_CLOSED:
                h.state = BREAKER_CLOSED
                h.probe_inflight = False

    def record_peer_failure(self, executor_id: ExecutorId, reason: str = "") -> None:
        """A fetch against ``executor_id`` failed at the wire level (send
        failure, dead connection, timeout).  Trips the breaker open once the
        failure streak reaches ``breaker.failureThreshold`` (0 = never); a
        failed half-open probe re-opens with a fresh cooldown."""
        threshold = self.conf.breaker_failure_threshold
        with self._health_lock:
            h = self._health_of(executor_id)
            h.failures += 1
            h.consecutive_failures += 1
            h.error_ewma += _HEALTH_ALPHA * (1.0 - h.error_ewma)
            if threshold <= 0:
                return
            if h.state == BREAKER_HALF_OPEN or (
                h.state == BREAKER_CLOSED and h.consecutive_failures >= threshold
            ):
                if h.state != BREAKER_OPEN:
                    h.trips += 1
                h.state = BREAKER_OPEN
                h.opened_at_ns = time.monotonic_ns()
                h.probe_inflight = False
        if threshold > 0 and reason:
            logger.debug("peer %s health: %s", executor_id, reason)

    def breaker_allows(self, executor_id: ExecutorId) -> bool:
        """Gate a new fetch against ``executor_id``.  Closed (or breaker off)
        admits; open rejects until ``breaker.cooldownMs`` elapses, then flips
        half-open and admits EXACTLY ONE probe — further fetches are rejected
        until the probe resolves through record_peer_success/_failure."""
        if self.conf.breaker_failure_threshold <= 0:
            return True
        with self._health_lock:
            h = self._health.get(executor_id)
            if h is None or h.state == BREAKER_CLOSED:
                return True
            if h.state == BREAKER_OPEN:
                cooldown_ns = self.conf.breaker_cooldown_ms * 1_000_000
                if time.monotonic_ns() - h.opened_at_ns < cooldown_ns:
                    return False
                h.state = BREAKER_HALF_OPEN
                h.probe_inflight = True
                return True
            # half-open: one probe at a time
            if h.probe_inflight:
                return False
            h.probe_inflight = True
            return True

    def breaker_state(self, executor_id: ExecutorId) -> str:
        with self._health_lock:
            h = self._health.get(executor_id)
            return h.state if h is not None else BREAKER_CLOSED

    def health_snapshot(self) -> Dict[int, Dict[str, object]]:
        """Per-executor health view for postmortems (kill_executor captures
        this) and white-box tests."""
        with self._health_lock:
            return {
                eid: {
                    "state": h.state,
                    "latency_ewma_ns": int(h.latency_ewma_ns),
                    "error_ewma": round(h.error_ewma, 4),
                    "consecutive_failures": h.consecutive_failures,
                    "successes": h.successes,
                    "failures": h.failures,
                    "trips": h.trips,
                }
                for eid, h in self._health.items()
            }

    def _health_view(self) -> Dict[str, int]:
        """Metrics-registry leg (family ``health``): fleet-level roll-up of
        the per-peer scores — counts by breaker state plus cumulative
        success/failure/trip counters."""
        with self._health_lock:
            if not self._health:
                return {}
            out = {
                "peers": len(self._health),
                "open": 0,
                "half_open": 0,
                "successes": 0,
                "failures": 0,
                "trips": 0,
                "latency_ewma_ns_max": 0,
            }
            for h in self._health.values():
                if h.state == BREAKER_OPEN:
                    out["open"] += 1
                elif h.state == BREAKER_HALF_OPEN:
                    out["half_open"] += 1
                out["successes"] += h.successes
                out["failures"] += h.failures
                out["trips"] += h.trips
                out["latency_ewma_ns_max"] = max(
                    out["latency_ewma_ns_max"], int(h.latency_ewma_ns)
                )
            return out

    def _register_metrics_providers(self) -> None:
        """Wire this transport's scattered telemetry surfaces into the one
        registry: op summaries, per-lane wire counters, replication and
        store replica-tier accounting, serve-side compression, and the trace
        ring's own health.  Cluster-owned surfaces (elastic, eviction,
        tenants) register from their owners (transport/tpu.py)."""
        if self.stats_agg is not None:
            self.metrics.register("ops", stats_aggregator_provider(self.stats_agg))
        self.metrics.register("wire", wire_lane_provider(self.wire_lane_stats))
        self.metrics.register(
            "replica", counter_dict_provider("replica", self._replica_stats_snapshot)
        )
        self.metrics.register(
            "replica_tier", counter_dict_provider("replica", self.store.replica_stats)
        )
        self.metrics.register("compress", counter_dict_provider("compress", self.compress_stats))
        # dynamic closures: membership and the eviction manager attach AFTER
        # construction (elastic wiring, service plane) — resolve at scrape time
        self.metrics.register(
            "elastic", counter_dict_provider("elastic", self._elastic_view)
        )
        self.metrics.register(
            "eviction", counter_dict_provider("eviction", self._eviction_view)
        )
        self.metrics.register(
            "reactor", counter_dict_provider("reactor", self._reactor_view)
        )
        self.metrics.register(
            "health", counter_dict_provider("health", self._health_view)
        )
        self.metrics.register(
            "serve", counter_dict_provider("serve", self._serve_view)
        )
        self.metrics.register("obs", tracer_provider(TRACER))

    def _elastic_view(self) -> Dict[str, int]:
        m = self.membership
        if m is None:
            return {}
        snap = m.snapshot()
        return {
            "epoch": snap["epoch"],
            "alive": len(snap["alive"]),
            "dead": len(snap["dead"]),
        }

    def _eviction_view(self) -> Dict[str, int]:
        ev = getattr(self.store, "eviction", None)
        out = dict(ev.eviction_stats()) if ev is not None else {}
        # watermark-sweep telemetry rides the eviction family: sweeps ARE
        # out-of-band eviction epochs, just triggered by store.softWatermark
        wm = getattr(self.store, "watermark_stats", None)
        if wm is not None:
            out.update(wm())
        return out

    def _reactor_view(self) -> Dict[str, int]:
        srv = self.server
        reactor = getattr(srv, "_reactor", None) if srv is not None else None
        return reactor.stats() if reactor is not None else {}

    def _serve_view(self) -> Dict[str, int]:
        """``serve`` metrics family: popularity-tracker counters, serve-cache
        counters, and the live widened-advertisement gauge.  Empty when the
        tier is fully off."""
        out: Dict[str, int] = {}
        if self.popularity is not None:
            out.update(self.popularity.snapshot())
        cache = getattr(self.store, "serve_cache", None)
        if cache is not None:
            out.update(cache.snapshot())
        if self.popularity is not None:
            with self._tag_lock:
                out["advertised_hot_shuffles"] = len(self._hot_shuffles)
        return out

    def _pull(self, executor_id: ExecutorId, am_id: AmId, timeout: float = 5.0) -> bytes:
        """Blocking pull RPC on the peer plane (TRACE_PULL / METRICS_PULL):
        send the tagged request, pump progress() until the tagged reply parks
        and drains — the same explicit-poll contract every fetch follows."""
        with self._tag_lock:
            tag = self._next_tag
            self._next_tag += 1
            pending = self._pull_pending[tag] = {"done": threading.Event(), "body": b""}
        try:
            conn = self._connection(executor_id)
            conn.send(pack_frame(am_id, _TAG.pack(tag)))
            deadline = time.monotonic() + timeout
            while not pending["done"].is_set():
                if time.monotonic() > deadline:
                    raise TransportError(
                        f"{am_id.name} from executor {executor_id} timed out "
                        f"after {timeout:.1f}s"
                    )
                self.progress()
                self.wait_for_activity(0.005)
            return pending["body"]
        finally:
            with self._tag_lock:
                self._pull_pending.pop(tag, None)

    def pull_trace(self, executor_id: ExecutorId, timeout: float = 5.0) -> dict:
        """Fetch a peer executor's trace buffer: ``{"executor", "events",
        "dropped"}`` (TpuShuffleCluster.export_trace merges these)."""
        body = self._pull(executor_id, AmId.TRACE_PULL, timeout=timeout)
        try:
            return json.loads(body.decode())
        except (UnicodeDecodeError, json.JSONDecodeError) as e:
            raise TransportError(f"malformed TRACE_PULL reply from executor {executor_id}: {e}")

    def pull_metrics(self, executor_id: ExecutorId, timeout: float = 5.0) -> str:
        """Fetch a peer executor's Prometheus text exposition."""
        return self._pull(executor_id, AmId.METRICS_PULL, timeout=timeout).decode(
            errors="replace"
        )

    def _hot_set_view(self) -> Dict[int, List[int]]:
        """Block-server provider: snapshot of this executor's advertised
        hot-set table for HOT_SET_PULL replies."""
        with self._tag_lock:
            return {sid: list(h) for sid, h in self._hot_shuffles.items()}

    def hot_holders(self, executor_id: ExecutorId, shuffle_id: int) -> List[ExecutorId]:
        """Current holder set the primary advertises for a hot shuffle, or
        ``[]`` when nothing is advertised (cold shuffle / tier off).  Served
        from a TTL cache (``spark.shuffle.tpu.serve.holdersTtlMs``; 0 =
        re-pull every fetch) so readers learn widened sets without a
        round-trip per fetch; pull failures are non-fatal (an empty table is
        cached, and the reader just keeps fetching from the primary)."""
        if self.conf.serve_hot_threshold_fetches_per_sec <= 0:
            return []
        now = time.monotonic()
        with self._tag_lock:
            cached = self._hot_holders_cache.get(executor_id)
        ttl_s = self.conf.serve_holders_ttl_ms / 1e3
        if cached is not None and now - cached[0] < ttl_s:
            return list(cached[1].get(shuffle_id, []))
        try:
            table = unpack_hot_set(
                self._pull(executor_id, AmId.HOT_SET_PULL, timeout=1.0)
            )
        except (TransportError, OSError, struct.error):
            table = {}
        with self._tag_lock:
            self._hot_holders_cache[executor_id] = (now, table)
        return list(table.get(shuffle_id, []))

    def wait_for_activity(self, timeout: float = 0.01) -> None:
        """Park until a recv thread posts an ack (or timeout) — the wakeup-mode
        progress contract (GlobalWorkerRpcThread.scala:46-58).  No-op when
        ``use_wakeup`` is off (pure busy-spin, like UCX without wakeup)."""
        if self.conf.use_wakeup:
            self._activity.wait(timeout)
            self._activity.clear()

    # -- lifecycle ---------------------------------------------------------

    def init(self) -> bytes:
        host, port = self.conf.listener_address
        host = host if host != "0.0.0.0" else "127.0.0.1"
        self.server = BlockServer(
            self.conf, store=self.store, registry_lookup=self.registered_block,
            host=host, port=port, member_sink=self._on_member_event,
            tenants=getattr(self.store, "tenants", None),
            executor_id=self.executor_id, metrics=self.metrics,
            popularity=self.popularity, hot_sink=self._on_hot_transition,
            hot_set_provider=self._hot_set_view,
        )
        if self.conf.obs_metrics_port > 0:
            try:
                self._metrics_http = start_http_server(
                    self.metrics, self.conf.obs_metrics_port
                )
            except OSError:
                # loopback clusters build one transport per virtual executor
                # on one host: first bind wins the scrape port, the rest skip
                self._metrics_http = None
        return self.server.address_bytes()

    def close(self) -> None:
        self.recorder.close()  # unhook TransportError capture before teardown
        if self._metrics_http is not None:
            close_http_server(self._metrics_http)
            self._metrics_http = None
        with self._tag_lock:
            self._replica_run = False
            self._replica_queue.clear()
        self._replica_wake.set()
        if self.stats_agg is not None:
            for s in self.wire_lane_stats():
                self.stats_agg.record_counters(
                    "wire",
                    rx_bytes=s["rx_bytes"],
                    rx_syscalls=s["rx_syscalls"],
                    rx_stall_ns=s["rx_stall_ns"],
                )
        with self._conn_lock:
            conns = list(self._conns.values()) + self._zombies
            self._conns.clear()
            self._zombies = []
        for c in conns:
            c.close()
        # snapshot + clear under the tag lock: a recv thread can still be
        # resolving an ack while we tear down (found by the lock-discipline pass)
        with self._tag_lock:
            inflight = list(self._inflight.values())
            self._inflight.clear()
            self._stripe_rx.clear()
        for reqs, _, _, _ in inflight:
            for r in reqs:
                if not r.completed():
                    r.cancel()
        if self.server is not None:
            self.server.close()
        self.store.close()

    # -- membership --------------------------------------------------------

    def add_executor(self, executor_id: ExecutorId, address: bytes) -> None:
        host, _, port = address.decode().rpartition(":")
        with self._conn_lock:
            self._conn_addrs[executor_id] = (host, int(port))

    def remove_executor(self, executor_id: ExecutorId) -> None:
        with self._conn_lock:
            self._conn_addrs.pop(executor_id, None)
            doomed = [k for k in self._conns if k[0] == executor_id]
            conns = [self._conns.pop(k) for k in doomed]
        for conn in conns:
            conn.close()

    # -- gossip-free membership observations -------------------------------
    #
    # No heartbeats: liveness is observation-driven.  A wire failure sends a
    # MEMBER_SUSPECT to every peer; an executor coming back announces itself
    # with MEMBER_REJOIN.  Both land in the local ClusterMembership when one
    # is installed (self.membership), and are silently dropped otherwise —
    # membership-unaware deployments see zero behavior change.

    def note_peer_failed(self, executor_id: ExecutorId, reason: str) -> None:
        """Report a wire failure against ``executor_id``: suspect it locally
        (debounced by ``membership.suspectAfterMs``) and, only when the
        suspicion NEWLY killed the executor, tell the other peers — re-observed
        failures of an already-dead peer must not re-broadcast every progress
        pump.  Called from the send path and progress(), NEVER from ``_evict``
        — broadcasting opens connections, and a broadcast failure must not
        recurse into eviction."""
        if self.membership is None:
            return
        if self.membership.suspect(executor_id, reason):
            self._broadcast_member_event(AmId.MEMBER_SUSPECT, executor_id)

    def announce_rejoin(self) -> None:
        """This executor is back: mark self alive and tell every peer, so the
        full mesh returns at the next shuffle's epoch check."""
        if self.membership is None:
            return
        self.membership.mark_alive(self.executor_id)
        self._broadcast_member_event(AmId.MEMBER_REJOIN, self.executor_id)

    def _broadcast_member_event(self, am_id: AmId, subject: ExecutorId) -> None:
        epoch = self.membership.epoch if self.membership is not None else 0
        frame = pack_frame(am_id, pack_member_event(epoch, subject, self.executor_id))
        with self._conn_lock:
            eids = [e for e in self._conn_addrs if e != subject]
        for eid in eids:
            try:
                self._connection(eid).send(frame)
            except (TransportError, OSError):
                pass  # best-effort: an unreachable peer learns from its own wire

    def _on_member_event(
        self, am_id: int, epoch: int, subject: ExecutorId, observer: ExecutorId
    ) -> None:
        """BlockServer sink for MEMBER_SUSPECT/MEMBER_REJOIN frames (runs on a
        server conn thread).  Rumors about ourselves are ignored — a live
        executor is the authority on its own liveness."""
        if self.membership is None or subject == self.executor_id:
            return
        if am_id == AmId.MEMBER_SUSPECT:
            self.membership.suspect(
                subject, f"peer {observer} reported a wire failure (epoch {epoch})"
            )
        elif am_id == AmId.MEMBER_REJOIN:
            self.membership.mark_alive(subject)

    def _slot(self) -> int:
        # Round-robin threads onto worker slots via a thread-local (raw thread
        # idents are pointer-aligned, so ident % n would collapse onto slot 0).
        slot = getattr(self._slot_local, "slot", None)
        if slot is None:
            with self._tag_lock:
                slot = self._slot_rr % max(1, self.conf.num_client_workers)
                self._slot_rr += 1
            self._slot_local.slot = slot
        return slot

    def pre_connect(self) -> None:
        """Eager connection establishment (UcxExecutorRpcEndpoint.scala:19-39)."""
        with self._conn_lock:
            missing = [e for e in self._conn_addrs if (e, self._slot()) not in self._conns]
        for eid in missing:
            self._connection(eid)

    def _connection(self, executor_id: ExecutorId) -> _PeerConnection:
        # Two racing threads must not both build a connection for one key (the
        # loser's socket would be orphaned from the cache and progress() would
        # never drain its acks) — but the blocking TCP connect must NOT happen
        # under the global lock, or one unreachable peer stalls every healthy
        # fetch for the connect timeout.  A per-key pending event gates racers
        # while the winner connects outside the lock.
        key = (executor_id, self._slot())
        while True:
            with self._conn_lock:
                conn = self._conns.get(key)
                if conn is not None and conn.alive:
                    return conn
                pending = self._connecting.get(key)
                if pending is None:
                    addr = self._conn_addrs.get(executor_id)
                    if addr is None:
                        raise TransportError(f"unknown executor {executor_id}")
                    if conn is not None:  # dead cached conn: release its fd
                        del self._conns[key]
                        conn.close()
                    pending = threading.Event()
                    self._connecting[key] = pending
                    break
            pending.wait(timeout=60)
        try:
            conn = self._open_connection(addr)
        except OSError:
            with self._conn_lock:
                self._connecting.pop(key, None)
            pending.set()
            raise
        with self._conn_lock:
            self._conns[key] = conn
            self._connecting.pop(key, None)
        pending.set()
        return conn

    def _open_connection(self, addr: Tuple[str, int]) -> Union[_PeerConnection, _StripeGroup]:
        """One lane (wire.streams = 1, the byte-identical historical wire) or
        a K-lane stripe group announced to the server via WIRE_HELLO.

        With ``compress.codec`` on, even ``wire.streams = 1`` uses the stripe
        (chunked-reply) path as a single-lane group: the codec ext rides
        chunk headers, so compressed replies need per-chunk framing — and the
        monolithic single-lane reply stays byte-identical to its golden
        capture, pinned at codec=off only."""
        streams = max(1, self.conf.wire_streams)
        if streams == 1 and self.conf.wire_compress_codec == "off":
            return _PeerConnection(
                addr,
                ack_buffers=self._ack_buffers,
                ack_done=self._ack_buffers_done,
                activity=self._activity,
                conf=self.conf,
            )
        group_id = int.from_bytes(os.urandom(8), "little")
        lanes: List[_PeerConnection] = []
        try:
            for lane in range(streams):
                c = _PeerConnection(
                    addr,
                    activity=self._activity,
                    conf=self.conf,
                    lane=lane,
                    chunk_sink=self._chunk_buffers,
                    chunk_done=self._chunk_done,
                    manifest_sink=self._on_manifest,
                )
                lanes.append(c)
                c.send(
                    pack_frame(
                        AmId.WIRE_HELLO,
                        pack_wire_hello(group_id, lane, streams, self.conf.wire_chunk_bytes),
                    )
                )
        except OSError:
            for c in lanes:
                c.close()
            raise
        return _StripeGroup(group_id, lanes)

    # -- server side -------------------------------------------------------

    def register(self, block_id: BlockId, block: Block) -> None:
        with self._registry_lock:
            self._registry[block_id] = block

    def mutate(self, block_id: BlockId, block: Block, callback: Optional[OperationCallback]) -> None:
        with self._registry_lock:
            self._registry[block_id] = block
        if callback is not None:
            callback(OperationResult(OperationStatus.SUCCESS))

    def unregister(self, block_id: BlockId) -> None:
        with self._registry_lock:
            block = self._registry.pop(block_id, None)
        if block is not None:
            block.close()  # release serving resources (cached mmaps) eagerly

    def unregister_shuffle(self, shuffle_id: int) -> None:
        with self._registry_lock:
            doomed = [
                b for b in self._registry
                if isinstance(b, ShuffleBlockId) and b.shuffle_id == shuffle_id
            ]
            blocks = [self._registry.pop(b) for b in doomed]
        for block in blocks:
            block.close()
        if self.server is not None:
            # no tier may serve a stale hit after removal: the decoded-block
            # ServeCache drops via store.remove_shuffle below, the encoded-
            # chunk pool must drop here (same shuffle-id immutability scope)
            self.server.drop_shuffle_chunks(shuffle_id)
        self.store.remove_shuffle(shuffle_id)

    def registered_block(self, block_id: BlockId) -> Optional[Block]:
        with self._registry_lock:
            return self._registry.get(block_id)

    # -- client side -------------------------------------------------------

    def fetch_blocks_by_block_ids(
        self,
        executor_id: ExecutorId,
        block_ids: Sequence[BlockId],
        result_buffers: Sequence[MemoryBlock],
        callbacks: Sequence[Optional[OperationCallback]],
    ) -> List[Request]:
        if not (len(block_ids) == len(result_buffers) == len(callbacks)):
            raise ValueError("length mismatch")
        for b in block_ids:
            if not isinstance(b, ShuffleBlockId):
                raise TransportError(f"PeerTransport fetches ShuffleBlockIds, got {b!r}")
        requests = [Request(OperationStats()) for _ in block_ids]
        # window by maxBlocksPerRequest (UcxShuffleClient.scala:53-58)
        step = self.conf.max_blocks_per_request
        for w in range(0, len(block_ids), step):
            self._send_batch(
                executor_id,
                list(block_ids[w : w + step]),
                requests[w : w + step],
                list(result_buffers[w : w + step]),
                list(callbacks[w : w + step]),
            )
        return requests

    def _send_batch(self, executor_id, bids, reqs, bufs, cbs) -> None:
        with self._tag_lock:
            tag = self._next_tag
            self._next_tag += 1
            self._inflight[tag] = (reqs, bufs, cbs, None)
        conn = None
        try:
            conn = self._connection(executor_id)
            with self._tag_lock:
                if tag in self._inflight:
                    self._inflight[tag] = (reqs, bufs, cbs, conn)
                    if isinstance(conn, _StripeGroup):
                        # reply will arrive as manifest + chunks on the
                        # group's lanes: start the receive accounting now,
                        # before any chunk can race the request send
                        self._stripe_rx[tag] = _StripeRx()
            trace = None
            if self.conf.obs_trace_context and TRACER.active:
                ctx = TRACER.current_context()
                if ctx is not None:
                    trace = (ctx.trace_id, ctx.span_id)
            conn.send(
                pack_frame(
                    AmId.FETCH_BLOCK_REQ,
                    pack_batch_fetch_req(
                        tag,
                        bids,
                        app_id=self.app_id if self.conf.tenants_enabled else None,
                        trace=trace,
                    ),
                )
            )
        except (TransportError, OSError) as e:
            # endpoint failure: evict the cached connection and fail the batch —
            # the reference's error-handler drop-from-cache path
            # (UcxShuffleTransport.scala:93-103, UcxWorkerWrapper.scala:248-253),
            # distinguishing connection reset like its CONNECTION_RESET branch.
            reset = isinstance(e, (ConnectionResetError, BrokenPipeError))
            logger.warning(
                "send to executor %s failed%s: %s",
                executor_id,
                " (connection reset)" if reset else "",
                e,
            )
            self._evict(executor_id)
            self.note_peer_failed(executor_id, f"fetch send failed: {e}")
            self.record_peer_failure(executor_id, f"fetch send failed: {e}")
            with self._tag_lock:
                self._inflight.pop(tag, None)
                self._stripe_rx.pop(tag, None)
            err = e if isinstance(e, TransportError) else TransportError(str(e))
            # A send can race the recv thread tearing the socket down after a
            # typed death (ServerBusy shed, crc mismatch): the OSError here is
            # just "fd closed" — surface the recv loop's killer instead, same
            # contract as _fail_conn_inflight.
            base = getattr(conn, "last_error", None) if conn is not None else None
            if isinstance(base, (BlockCorruptError, ResourceExhaustedError)):
                err = base
            for req, buf, cb in zip(reqs, bufs, cbs):
                req.stats.mark_done()
                result = OperationResult(OperationStatus.FAILURE, error=err, stats=req.stats)
                req.complete(result)
                if cb is not None:
                    cb(result)

    def _evict(self, executor_id: ExecutorId) -> None:
        key = (executor_id, self._slot())
        with self._conn_lock:
            conn = self._conns.pop(key, None)
            if conn is not None:
                # keep the evicted conn visible to progress() until every tag
                # riding it resolves — a mid-scatter ack must still be able to
                # park and complete (or be swept once the recv thread dies)
                self._zombies.append(conn)
        if conn is not None:
            conn.close()
            # Other batches still riding this connection will never get acks —
            # fail them now rather than leaving their reducers spinning.
            self._fail_conn_inflight([conn])

    def _fail_conn_inflight(self, conns) -> None:
        # honor acks that already arrived: drain parked frames first so only
        # genuinely unanswered batches are failed
        for conn in conns:
            while True:
                frame = conn.drain_one()
                if frame is None:
                    break
                self._handle_frame(frame)
        with self._tag_lock:
            doomed = [
                (tag, entry)
                for tag, entry in self._inflight.items()
                # a tag mid-scatter is skipped: its recv thread owns the
                # buffers right now; it will either park the frame (normal
                # completion) or die, after which the next sweep collects it
                if entry[3] in conns and tag not in self._scattering
            ]
            for tag, _ in doomed:
                del self._inflight[tag]
                self._stripe_rx.pop(tag, None)
        for tag, (reqs, bufs, cbs, conn) in doomed:
            peer = getattr(conn, "peer", "?")
            logger.warning(
                "connection to peer %s lost with %d in-flight request(s)", peer, len(reqs)
            )
            # Surface the recv loop's typed killer when it carries more signal
            # than "connection lost" — a crc mismatch (BlockCorruptError) must
            # reach the reducer as corruption, and a load-shed
            # (ResourceExhaustedError) as retryable pressure, not as a
            # generic peer death.
            base = getattr(conn, "last_error", None)
            if isinstance(base, (BlockCorruptError, ResourceExhaustedError)):
                err: TransportError = base
            else:
                err = TransportError(f"peer connection lost ({peer}, fetch tag {tag})")
            for req, buf, cb in zip(reqs, bufs, cbs):
                if req.completed():
                    continue
                req.stats.mark_done()
                result = OperationResult(OperationStatus.FAILURE, error=err, stats=req.stats)
                req.complete(result)
                if cb is not None:
                    cb(result)

    def progress(self) -> None:
        """Drain parked ack frames and complete their requests — the explicit
        progress pump (ShuffleTransport.scala:158-165).  Also detects dead
        connections and fails their in-flight batches (the reference only logs
        and leaks them, UcxWorkerWrapper.scala:351-353 — we do better)."""
        with self._conn_lock:
            by_conn = [(eid, conn) for (eid, _slot), conn in self._conns.items()]
            zombies = list(self._zombies)
        conns = [conn for _eid, conn in by_conn]
        for eid, conn in by_conn + [(None, z) for z in zombies]:
            while True:
                frame = conn.drain_one()
                if frame is None:
                    break
                self._handle_frame(frame, from_executor=eid)
        dead = [c for c in conns + zombies if not c.alive]
        if dead:
            self._fail_conn_inflight(dead)
            # attribute the deaths while we still know which executor each
            # cached conn belongs to (zombies lost that mapping; the original
            # eviction already reported them)
            for eid, conn in by_conn:
                if not conn.alive:
                    why = getattr(conn, "last_error", None)
                    self.note_peer_failed(
                        eid, f"peer connection died: {why if why is not None else 'EOF'}"
                    )
                    self.record_peer_failure(
                        eid, f"peer connection died: {why if why is not None else 'EOF'}"
                    )
        if zombies:
            # retire zombies once nothing references them: no inflight tag
            # rides them and their inbox is drained
            with self._tag_lock:
                riding = {entry[3] for entry in self._inflight.values()}
            with self._conn_lock:
                self._zombies = [z for z in self._zombies if z in riding or z.inbox]

    def _handle_frame(
        self,
        frame: Tuple[AmId, bytes, bytes, bool],
        from_executor: Optional[ExecutorId] = None,
    ) -> None:
        am_id, header, body, scattered = frame
        if am_id == AmId.REPLICA_ACK:
            try:
                sid, src, _rnd = unpack_replica_ack(header)
            except struct.error:
                return
            if src == self.executor_id:
                # from_executor (when the draining path knows the conn's peer)
                # attributes the ack to its successor for replication_wait
                self._replica_acked(sid, executor_id=from_executor)
            return
        if am_id in (AmId.TRACE_PULL, AmId.METRICS_PULL, AmId.HOT_SET_PULL):
            # pull-RPC reply (obs / popularity plane): tag echo in the header,
            # JSON event buffer / Prometheus text / packed hot-set table in
            # the body
            if len(header) < _TAG.size:
                return
            (tag,) = _TAG.unpack_from(header, 0)
            with self._tag_lock:
                pending = self._pull_pending.get(tag)
            if pending is not None:
                pending["body"] = bytes(body)
                pending["done"].set()
            return
        if am_id != AmId.FETCH_BLOCK_REQ_ACK:
            return
        if len(header) < _TAG.size + _COUNT.size:
            return  # not even a tag to resolve; the recv loop killed the conn
        (tag,) = _TAG.unpack_from(header, 0)
        (count,) = _COUNT.unpack_from(header, _TAG.size)
        with self._tag_lock:
            entry = self._inflight.pop(tag, None)
            # normally already gone for striped tags; covers the server's
            # unstriped-fallback reply and malformed manifests
            self._stripe_rx.pop(tag, None)
        if entry is None:
            return
        reqs, bufs, cbs, _conn = entry
        # validate BEFORE unpacking: a truncated header must fail the batch,
        # not raise struct.error out of progress() with the entry already popped
        truncated = len(header) < _TAG.size + _COUNT.size + count * _SIZE.size
        sizes = (
            []
            if truncated
            else [
                _SIZE.unpack_from(header, _TAG.size + _COUNT.size + i * _SIZE.size)[0]
                for i in range(count)
            ]
        )
        # Scattered acks (explicit flag from the recv thread): the payload
        # already sits in the result buffers; only completion remains here.
        pre_filled = scattered
        # A peer whose size list disagrees with the frame body (or with the
        # batch size) produced an ack we cannot slice safely: fail the whole
        # batch with FAILURE results instead of raising mid-loop out of
        # progress() and leaving the rest of the batch incomplete.
        malformed = (
            truncated
            or count != len(reqs)
            or (not pre_filled and sum(s for s in sizes if s > 0) != len(body))
        )
        if malformed:
            err = TransportError(
                f"malformed fetch ack: {count} sizes summing to "
                f"{sum(s for s in sizes if s > 0)} B for a {len(reqs)}-request "
                f"batch with a {len(body)} B body"
            )
            for req, cb in zip(reqs, cbs):
                if req.completed():
                    continue
                req.stats.mark_done()
                result = OperationResult(OperationStatus.FAILURE, error=err, stats=req.stats)
                req.complete(result)
                if cb is not None:
                    cb(result)
            return
        pos = 0
        for i, (req, buf, cb) in enumerate(zip(reqs, bufs, cbs)):
            size = sizes[i]
            if size < 0:
                req.stats.mark_done()
                peer = getattr(_conn, "peer", "?")
                if size == SIZE_UNKNOWN_TENANT:
                    err: TransportError = UnknownTenantError(
                        self.app_id or "?",
                        f"peer {peer} rejected the fetch: tenant not registered there",
                    )
                elif size == SIZE_QUOTA_EXCEEDED:
                    err = TenantQuotaExceededError(
                        self.app_id or "?",
                        -1,
                        detail=f"peer {peer} could not stage the block within quota",
                    )
                elif size == SIZE_RESOURCE_EXHAUSTED:
                    # gray-failure arm: the peer is under memory pressure —
                    # typed retryable, readers back off and retry (same or a
                    # replica holder) instead of failing the job
                    err = ResourceExhaustedError(
                        detail=f"peer {peer} is under memory pressure serving this block"
                    )
                else:
                    err = TransportError("block not found on peer")
                result = OperationResult(
                    OperationStatus.FAILURE, error=err, stats=req.stats
                )
            else:
                view = buf.host_view()
                if size > view.size:
                    pos += size
                    req.stats.mark_done()
                    result = OperationResult(
                        OperationStatus.FAILURE,
                        error=TransportError(
                            f"block ({size} B) exceeds result buffer ({view.size} B)"
                        ),
                        stats=req.stats,
                    )
                else:
                    if not pre_filled:
                        view[:size] = np.frombuffer(body[pos : pos + size], dtype=np.uint8)
                        pos += size
                    buf.size = size
                    req.stats.mark_done(recv_size=size)
                    if from_executor is not None:
                        # health scoring: a completed fetch is this peer's
                        # success sample (latency folds into the EWMA)
                        self.record_peer_success(from_executor, req.stats.elapsed_ns())
                    result = OperationResult(OperationStatus.SUCCESS, stats=req.stats, data=buf)
                    if self.stats_agg is not None:
                        self.stats_agg.record("fetch", req.stats)
            req.complete(result)
            if cb is not None:
                cb(result)

    # -- staged-store extensions ------------------------------------------

    def init_executor(self, num_mappers: int, num_reducers: int) -> None:
        """Handshake with every known peer (InitExecutorReq/Ack,
        UcxWorkerWrapper.scala:286-322).  Blocks until acked like the reference."""
        with self._conn_lock:
            eids = list(self._conn_addrs)
        for eid in eids:
            conn = self._connection(eid)
            conn.send(
                pack_frame(
                    AmId.INIT_EXECUTOR_REQ,
                    _TAG.pack(self.executor_id),
                    f"{num_mappers}x{num_reducers}".encode(),
                )
            )
            # spin for the ack (the reference blocks at :320)
            import time as _time

            deadline = _time.monotonic() + 10
            acked = False
            while _time.monotonic() < deadline and not acked:
                frame = conn.drain_one()
                if frame is None:
                    _time.sleep(0.001)
                    continue
                if frame[0] == AmId.INIT_EXECUTOR_ACK:
                    acked = True
                else:
                    self._handle_frame(frame)
            if not acked:
                raise TransportError(f"InitExecutorAck timeout from executor {eid}")

    def commit_block(self, mapper_info_blob: bytes, callback: Optional[OperationCallback] = None) -> None:
        """Broadcast MapperInfo to all peers (AM id 2 — the reference sends to its
        local DPU; here every peer's server learns the commit)."""
        MapperInfo.unpack(mapper_info_blob)  # validate
        with self._conn_lock:
            eids = list(self._conn_addrs)
        for eid in eids:
            try:
                self._connection(eid).send(pack_frame(AmId.MAPPER_INFO, b"", mapper_info_blob))
            except (TransportError, OSError):
                pass
        if callback is not None:
            callback(OperationResult(OperationStatus.SUCCESS))

    # -- asynchronous neighbor replication --------------------------------

    def replication_neighbors(self) -> List[ExecutorId]:
        """The ``replication_factor`` ring successors of this executor among
        the known cluster members (self + every added peer), sorted-id ring —
        the redistribution-plan placement of arXiv:2112.01075 degenerated to
        nearest ICI neighbors."""
        from sparkucx_tpu.shuffle.resolver import ring_neighbors

        with self._conn_lock:
            peers = list(self._conn_addrs)
        return ring_neighbors(
            self.executor_id, [self.executor_id] + peers, self.conf.replication_factor
        )

    def _on_store_seal(self, shuffle_id: int) -> None:
        """Store seal hook: enqueue the shuffle for the single replicator
        worker (never blocks the sealing caller; the map-side superstep
        proceeds immediately).

        The queue is bounded by ``replication.maxBacklogBytes``: when the live
        backlog gauge is over the cap, the OLDEST still-queued shuffle is
        dropped (its rounds counted in ``dropped_rounds``) rather than letting
        a slow successor grow the backlog without bound.  Dropping replicas is
        safe — replication is best-effort durability, and a shuffle whose
        replicas were dropped simply becomes unrecoverable if its primary
        later dies (the degraded-recovery path reports exactly that)."""
        if self.conf.replication_factor <= 0:
            return
        with self._tag_lock:
            cap = self.conf.replication_max_backlog_bytes
            if (
                cap
                and self.replica_stats["replica_backlog_bytes"] > cap
                and self._replica_queue
            ):
                dropped, _ = self._replica_queue.popleft()
                self._replica_pushing.discard(dropped)
                try:
                    self.replica_stats["dropped_rounds"] += self.store.num_rounds(dropped)
                except TransportError:
                    self.replica_stats["dropped_rounds"] += 1
                logger.warning(
                    "replica backlog over %d B: dropped queued shuffle %d",
                    cap, dropped,
                )
            self._enqueue_replica_job_locked(shuffle_id, None)
        self._replica_wake.set()

    def _enqueue_replica_job_locked(
        self, shuffle_id: int, neighbors: Optional[List[ExecutorId]]
    ) -> None:
        """Queue one replication job (caller holds ``_tag_lock``; caller sets
        ``_replica_wake`` after releasing it).  ``neighbors=None`` = the ring
        successors resolved at push time; a list = a popularity widen job."""
        self._replica_pushing.add(shuffle_id)
        self._replica_queue.append((shuffle_id, neighbors))
        worker = self._replica_worker
        if worker is None or not worker.is_alive():
            worker = threading.Thread(
                target=self._replica_loop,
                daemon=True,
                name=f"replicator-{self.executor_id}",
            )
            self._replica_worker = worker
            worker.start()

    def _on_hot_transition(self, shuffle_id: int, hot: bool) -> None:
        """Block-server hot sink (runs on a serve thread, must stay cheap).

        Promote: widen the shuffle's replica set to ``serve.hotReplicas``
        ring successors by queuing a push to the holders BEYOND the seal-time
        ``replication.factor`` set (those already hold the rounds), and
        advertise the full holder list through HOT_SET_PULL so readers
        spread their fetches.  Demote: drop the advertisement — readers fall
        back to the primary; the pushed copies stay (never below the
        fault-tolerance floor, and a re-promotion reuses them for free)."""
        if not hot:
            with self._tag_lock:
                self._hot_shuffles.pop(shuffle_id, None)
            return
        from sparkucx_tpu.shuffle.resolver import widened_ring_neighbors

        with self._conn_lock:
            peers = list(self._conn_addrs)
        members = [self.executor_id] + peers
        base, extra = widened_ring_neighbors(
            self.executor_id,
            members,
            self.conf.replication_factor,
            self.conf.serve_hot_replicas,
        )
        with self._tag_lock:
            self._hot_shuffles[shuffle_id] = sorted(
                {self.executor_id, *base, *extra}
            )
            if extra:
                self._enqueue_replica_job_locked(shuffle_id, extra)
        if extra:
            self._replica_wake.set()

    def _replica_loop(self) -> None:
        """Single replicator worker: drains the seal queue one shuffle at a
        time, so replica pushes never fan out into thread-per-seal."""
        while True:
            with self._tag_lock:
                if not self._replica_run:
                    return
                job = self._replica_queue.popleft() if self._replica_queue else None
            if job is None:
                if not self._replica_wake.wait(timeout=0.2):
                    with self._tag_lock:
                        # idle and nothing queued: retire; the next seal respawns
                        if not self._replica_queue:
                            self._replica_worker = None
                            return
                self._replica_wake.clear()
                continue
            self._replicate_push(*job)

    def _replicate_push(
        self, shuffle_id: int, neighbors: Optional[List[ExecutorId]] = None
    ) -> None:
        """Push one shuffle's sealed rounds to ``neighbors`` (None = the
        ring's ``replication.factor`` successors; an explicit list = a
        popularity widen job targeting only the extra holders)."""
        try:
            faults.check("replica.push", shuffle_id=shuffle_id, executor=self.executor_id)
            if neighbors is None:
                neighbors = self.replication_neighbors()
            rounds = self.store.replica_source(shuffle_id) if neighbors else []
            round_bytes = sum(len(body) for _, _, body in rounds)
            with self._tag_lock:
                self._replica_pending[shuffle_id] = (
                    self._replica_pending.get(shuffle_id, 0) + len(neighbors) * len(rounds)
                )
                unacked = self._replica_unacked.setdefault(shuffle_id, {})
                for eid in neighbors:
                    unacked[eid] = unacked.get(eid, 0) + len(rounds)
                self.replica_stats["replica_backlog_bytes"] += round_bytes * len(neighbors)
            checksum = self.conf.wire_checksum
            cspec = CompressSpec.from_conf(self.conf)
            trace_on = self.conf.obs_trace_context and TRACER.active
            for eid in neighbors:
                for rnd, entries, body in rounds:
                    header = pack_replica_put(shuffle_id, self.executor_id, rnd, entries)
                    wire_body = body
                    if cspec.enabled:
                        # whole-round page encode; the codec ext rides after
                        # the entry table, before the crc trailer (residues
                        # 8/12, core/definitions.py)
                        cid, enc = encode_chunk(cspec, body)
                        if enc is not None:
                            wire_body = enc
                        header += pack_chunk_codec_ext(cid, len(body))
                    if checksum:
                        # self-describing: receivers detect the crc tail by
                        # header length (knob off = golden replica frames);
                        # the crc covers the WIRE (possibly encoded) body
                        header += _CRC.pack(crc32c(wire_body))
                    span_ctx = None
                    if trace_on:
                        # trace ext rides LAST (after crc): the receiver
                        # strips it before the crc/codec residue dispatch
                        with TRACER.executor_scope(self.executor_id):
                            span_ctx = TRACER.start_span(
                                "replica.push",
                                shuffle_id=shuffle_id,
                                round=rnd,
                                dst=eid,
                            )
                        header += pack_replica_trace_ext(
                            span_ctx.trace_id, span_ctx.span_id
                        )
                    frame = pack_frame(AmId.REPLICA_PUT, header, wire_body)
                    try:
                        self._connection(eid).send(frame)
                        with self._tag_lock:
                            self.replica_stats["pushed_rounds"] += 1
                            self.replica_stats["pushed_bytes"] += len(body)
                    except (TransportError, OSError) as e:
                        logger.warning(
                            "replication of shuffle %d round %d to executor %s failed: %s",
                            shuffle_id, rnd, eid, e,
                        )
                        self._replica_acked(shuffle_id, failed=True, executor_id=eid)
                    finally:
                        if span_ctx is not None:
                            with TRACER.executor_scope(self.executor_id):
                                TRACER.end_span(span_ctx)
                        with self._tag_lock:
                            self.replica_stats["replica_backlog_bytes"] = max(
                                0, self.replica_stats["replica_backlog_bytes"] - len(body)
                            )
        except Exception:
            logger.exception("replicator for shuffle %d died", shuffle_id)
        finally:
            with self._tag_lock:
                # a widen job can queue behind the seal push for the same
                # shuffle: the pushing flag (replication_wait's gate) must
                # survive until the LAST queued job for the shuffle drains
                if all(s != shuffle_id for s, _ in self._replica_queue):
                    self._replica_pushing.discard(shuffle_id)
            self._activity.set()

    def _replica_acked(
        self,
        shuffle_id: int,
        failed: bool = False,
        executor_id: Optional[ExecutorId] = None,
    ) -> None:
        with self._tag_lock:
            left = self._replica_pending.get(shuffle_id, 0) - 1
            self._replica_pending[shuffle_id] = max(0, left)
            self.replica_stats["failed" if failed else "acks"] += 1
            unacked = self._replica_unacked.get(shuffle_id)
            if unacked:
                if executor_id is None:
                    # ack arrived on a path that lost its origin (zombie conn):
                    # settle any outstanding successor so totals still converge
                    executor_id = next(
                        (e for e, c in unacked.items() if c > 0), None
                    )
                if executor_id is not None and unacked.get(executor_id, 0) > 0:
                    unacked[executor_id] -= 1

    def replication_wait(
        self, shuffle_id: int, timeout: float = 10.0, strict: bool = False
    ) -> bool:
        """Pump progress until every replica push for ``shuffle_id`` is acked
        (or failed-and-accounted).  True = replication settled.  Tests and
        graceful shutdown use this; the data path never has to.

        ``strict`` turns a timeout into a ``TransportError`` naming the
        successor executor(s) whose acks never came — the operator-facing
        answer to "which neighbor is stalling my replication?"."""
        deadline = time.monotonic() + timeout
        while True:
            with self._tag_lock:
                settled = (
                    shuffle_id not in self._replica_pushing
                    and self._replica_pending.get(shuffle_id, 0) == 0
                )
            if settled:
                return True
            if time.monotonic() > deadline:
                if strict:
                    with self._tag_lock:
                        stalled = sorted(
                            e
                            for e, c in self._replica_unacked.get(shuffle_id, {}).items()
                            if c > 0
                        )
                    raise TransportError(
                        f"replication of shuffle {shuffle_id} did not settle in "
                        f"{timeout:.1f}s: successor executor(s) {stalled} have "
                        f"unacknowledged replica rounds"
                    )
                return False
            self.progress()
            self.wait_for_activity(0.005)

    def fetch_block(
        self,
        executor_id: ExecutorId,
        shuffle_id: int,
        map_id: int,
        reduce_id: int,
        result_buffer: MemoryBlock,
        callback: Optional[OperationCallback] = None,
    ) -> Request:
        [req] = self.fetch_blocks_by_block_ids(
            executor_id,
            [ShuffleBlockId(shuffle_id, map_id, reduce_id)],
            [result_buffer],
            [callback],
        )
        return req
