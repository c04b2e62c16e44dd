"""Shuffle daemon — the host-engine boundary (L7 wire side).

The reference preserves Spark compatibility by splitting into a JVM plugin and an
out-of-repo daemon: the plugin (``spark.shuffle.manager`` =
``UcxShuffleManager``) speaks AM ids 0-4 to a DPU-side daemon on port 1338
(CommonUcxShuffleManager.scala:84-89, Definitions.scala:22-29).  This module is
that daemon, TPU-side: a standalone process hosting a ``TpuShuffleManager`` and
serving a framed protocol any host engine can speak — the JVM shim under
``jvm/`` (the ``spark.shuffle.manager`` entry point), ``benchmark/run.py``, or
tests.

Protocol: the data-plane messages are exactly AM ids 0-4 (handshake, commit,
fetch — see core/definitions.py and transport/peer.py's BlockServer which serves
them); shuffle *lifecycle* adds daemon ops >= 16 (the part Spark does through the
ShuffleManager SPI rather than the wire, so the reference has no AM ids for it):

==================  ==  =======================================================
CreateShuffle       16  header: json {shuffle_id, num_mappers, num_reducers}
OpenMapWriter       17  header: json {shuffle_id, map_id} -> writer handle
WritePartition      18  header: json {writer, reduce_id}; body: bytes (repeat ok)
                        or json {writer, reduce_ids, lengths}; body: the blocks
CommitMap           19  header: json {writer} -> partition lengths
RunExchange         20  header: json {shuffle_id} (optional: see the stage boundary)
FetchBlock           3  AM FetchBlockReq (batched form, peer.py framing)
RemoveShuffle       21  header: json {shuffle_id}
Stats               22  header: json {shuffle_id}
Shutdown            23  —
OfferLanding        27  header: json {name, capacity} (a same-host client's landing)
==================  ==  =======================================================

Every control op gets an ``Ack`` (id 24) with ``{ok, error?, ...result}``.

The stage boundary: a reduce task speaks ``FetchBlock`` and nothing else (the
JVM shim's ``TpuShuffleReader`` calls ``fetchBlocks`` only; of the classes
under ``jvm/src`` only ``InteropCheck`` ever sends ``RunExchange``), and the
barrier between the map and the reduce stage is Spark's scheduler's, which the
wire never sees.  So the daemon runs the exchange itself: the first
``FetchBlock`` of a shuffle that is registered, not yet exchanged and whose
maps have **all** committed runs it, once a shuffle; fetches of that shuffle
that arrive meanwhile wait for it and are then served.  With a map still
uncommitted a fetch answers as ever (size -1, nothing started).  An exchange
that fails fails every fetch that waited on it and every later fetch of that
shuffle (their blocks come back ``None``); the connections and the daemon
live on.  ``RunExchange`` is still served as it always was — an engine that
knows its own barrier may send it — and shares the guard: racing a first
fetch, there is one exchange.

The body of a ``WritePartition``: a written block's bytes are touched once on
each side of the wire.  The client sends fixed header + JSON and the body as
two buffers of one vectored ``sendmsg`` (never joined: no copy of the body).
The daemon reads the fixed header and the JSON as for any op, resolves the
writer's partition stream, has the store reserve the body's extent in the
live staging round (``MapWriter.reserve``: every admission check, the tenant
charge and the rollover happen there, under the store's lock, before a byte is
read) and receives the socket straight into that extent (``recv_into``), outside every
lock — no frame buffer, no ``bytes`` of it, no copy at ``close_partition``,
which only records the block.  One path for every body size.  The receive runs
under ``conf.wire_timeout_ms`` (the wait for the next frame's header stays
unbounded): a body that stalls or whose sender dies ends that connection only
and gives the round's in-flight count back, so the spill, seal or removal that
waits for it (``inflight_wait_ns``) goes on; its extent is a hole no block
names, and the uncommitted map's retry writes it again.  A frame refused
before its body is read (unknown writer, sealed shuffle, a body larger than a
region) is acked with the error after the body is dropped unread-into-memory:
the connection stays in step.  The bytes on the wire are what they were.

A batch a frame (PR 59): a ``WritePartition`` header may instead name several
blocks of its one writer, ``{"writer", "reduce_ids": [...], "lengths":
[...]}``, the body the blocks back to back in that order; consecutive equal
reduce ids continue one partition, as repeated frames do.  The daemon parses
the header once and checks the frame whole before a byte of body is read
(writer known, reduce ids non-decreasing, as many lengths as ids, none
negative, their sum the body's length: a frame that fails is dropped unread
and refused whole), then does block by block what a one-block frame does —
the partition's stream, ``reserve``, the socket received straight into the
extent, ``end_receive`` — and sends **one ack that still names every block**:
``{"ok": true, "written": [n, ...]}``.  A block refused at admission ends the
frame there: the rest of the body is dropped unread, the ack carries
``error``, the refused ``reduce_id`` and the blocks ``written`` before it
(they stay recorded), and the connection stays in step.  A body that stalls
or a sender that dies is what it is for a one-block frame, for the block in
flight.  ``DaemonClient.write_partition`` fills such frames: nothing reads a
block before its map commits, so a ``bytes`` block waits on the connection —
by reference, never copied — until ``WRITE_BATCH_BYTES`` are pending, a block
of another writer arrives or any other op is made on the connection
(``commit_map`` first of all), and the pending blocks then go out as one
vectored ``sendmsg``.  What a map task is acknowledged is its commit, and
``commit_map`` returns only after the daemon has acked every block of the map
by its byte count and then the commit.

The body of a ``FetchBlock`` reply lands once too: the daemon sends views of
the received shards with one ``sendmsg`` (``_serve_fetch``), and
``DaemonClient.fetch_blocks`` receives the body straight into a landing
buffer the connection keeps and hands each block out as a read-only view of
where it landed — no frame-sized buffer a reply, no copy a block; a buffer
a caller still holds views of is never written again.

A same-host client's landing (PR 60): the daemon holds the host's chips, so
its clients are on its host, and the body of a reply need not cross the
loopback socket — one copy into the socket and one out of it, through one TCP
stream.  A ``DaemonClient`` that connected to a loopback address makes a
mapping after its first reply has told it the size (``/dev/shm``, an
unguessable name, mode 0600, created exclusively), offers ``(name,
capacity)`` in ``OfferLanding`` and unlinks the name on the ack; the daemon
opens it (no link followed, its own user's file, long enough, no larger than
a frame: ``attach_landing``) and keeps it for the connection.  Every fetch
request then says in its ``tag`` whether the landing is free
(``TAG_LANDING_FREE``: no view of it lives — ``fetch_blocks``' own rule for
its ``bytearray``); where it is and the reply fits, ``_serve_fetch`` copies
the located blocks into the mapping back to back and sends the reply's two
headers alone, marked ``TAG_BODY_MAPPED``, with a body length of 0.  Not
free, too large, nothing offered: the reply it always was, byte for byte.
The header on the socket is the hand-over — the daemon writes the mapping
only between such a request and its reply, the client reads it only after
the header — and no mapping is unmapped under a live view: both sides hold
an ``mmap`` through Python's buffer protocol, which unmaps with the last
holder.  A refused offer leaves the socket for the connection's life.
``docs/SHIM_PROTOCOL.md`` has the frames; a peer that never offers (the JVM
shim, a remote client) sees none of it.

Telemetry: every served frame is counted per op (``frames``, ``body_bytes``,
``serve_ns`` from the frame header's arrival to the reply sent, ``ack_ns`` the
reply's send, from its start to the frame's end, ``blocks`` the blocks a
``write_partition`` frame recorded — ``blocks / frames`` is the batch depth;
always on) — the ``daemon``
family of the cluster's metrics registry.  A span ``daemon.<op>`` over the
``serve_ns`` interval is recorded only while full tracing is on
(``TRACER.enabled``), not under the flight recorder's ``recording``.  The
stage boundary has plain rows of the same family (``stage_stats()``:
``stage_exchanges``, ``stage_waiters``, ``stage_wait_ns``, the gauge
``connections`` and ``connections_peak``) and two spans recorded like
``exchange.superstep``: ``daemon.stage_exchange`` (once a shuffle) and
``daemon.stage_wait`` (once a fetch that waited).

A frame by phase (PR 36), under full tracing only and at the cost of plain
clock reads while the frame runs: the site hands its marks to
``Tracer.record_spans`` after the frame's span has closed, one call and one
take of the ring's lock (``utils/trace.py``), so the children partition their
parent.  ``daemon.write_partition.meta`` / ``.admit`` / ``.body`` / ``.record``
/ ``.ack`` on one ``write_partition`` frame in ``WRITE_PHASES_EVERY`` of a
connection (a frame's ``admit`` / ``body`` / ``record`` are the sums of its
blocks' shares, laid end to end); ``daemon.fetch_block.locate`` / ``.send`` on every ``fetch_block``
frame; and the root ``daemon.client_turn.<op>`` of those same frames: the
connection's wait for its client, from the end of the frame before on this
connection to this frame's begin (``docs/OBSERVABILITY.md`` has the table).
Untraced, a frame pays one dict's truth test and a ``None`` check a phase.
"""

from __future__ import annotations

import ipaddress
import json
import mmap
import os
import secrets
import socket
import stat
import sys
import threading
from contextlib import nullcontext
from time import perf_counter_ns
from typing import Dict, List, Optional, Tuple

import numpy as np

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.definitions import (
    FRAME_HEADER_SIZE,
    MAX_FRAME_BYTES,
    AmId,
    pack_frame,
    pack_frame_prefix,
    unpack_frame_header,
)
from sparkucx_tpu.core.operation import TransportError
from sparkucx_tpu.obs.metrics import (
    MetricSample,
    MetricsRegistry,
    counter_dict_provider,
    labelled_counter_provider,
    sample,
)
from sparkucx_tpu.service.reactor import Reactor
from sparkucx_tpu.shuffle.manager import TpuShuffleManager
from sparkucx_tpu.transport.peer import (
    BlockServer,
    apply_wire_sockopts,
    pack_batch_fetch_req,
    recv_exact,
    unpack_batch_fetch_req,
)
from sparkucx_tpu.utils.logging import get_logger
from sparkucx_tpu.utils.trace import TRACER, span
import struct

logger = get_logger("shuffle.daemon")
#: the longest a frame waits for another thread's exchange of its shuffle: a
#: serving thread (one of a bounded pool under the reactor) is never parked
#: for good behind an exchange that hangs; its fetch then answers size -1
_STAGE_WAIT_S = 600.0
#: under full tracing, the ``write_partition`` frames of a connection that are
#: recorded by phase: numbers 1, 10, 19, ... counted from 0 since tracing came
#: on (the first sampled frame has a frame before it to end its client's
#: turn).  Odd, so that of a 25k map task's two frames (108 and 92 blocks at
#: ``WRITE_BATCH_BYTES``) both kinds are sampled in turn; at 1k, where a map
#: task is one frame, it samples 11 of a job's 100.
WRITE_PHASES_EVERY = 9
_WRITE_PHASES = tuple(
    "daemon.write_partition." + phase for phase in ("meta", "admit", "body", "record", "ack")
)
_TAG = struct.Struct("<Q")
_COUNT = struct.Struct("<I")
_SIZE = struct.Struct("<q")


class DaemonOp:
    CREATE_SHUFFLE = 16
    OPEN_MAP_WRITER = 17
    WRITE_PARTITION = 18
    COMMIT_MAP = 19
    RUN_EXCHANGE = 20
    REMOVE_SHUFFLE = 21
    STATS = 22
    SHUTDOWN = 23
    ACK = 24
    # obs plane (PR 14): control-plane pulls of the daemon-side telemetry
    EXPORT_TRACE = 25
    METRICS = 26
    # a same-host client's landing for its fetch replies (PR 60)
    OFFER_LANDING = 27


#: op id -> the name it is counted and traced under (``daemon.<name>``)
OP_NAMES = {
    DaemonOp.CREATE_SHUFFLE: "create_shuffle",
    DaemonOp.OPEN_MAP_WRITER: "open_map_writer",
    DaemonOp.WRITE_PARTITION: "write_partition",
    DaemonOp.COMMIT_MAP: "commit_map",
    DaemonOp.RUN_EXCHANGE: "run_exchange",
    DaemonOp.REMOVE_SHUFFLE: "remove_shuffle",
    DaemonOp.STATS: "stats",
    DaemonOp.SHUTDOWN: "shutdown",
    DaemonOp.EXPORT_TRACE: "export_trace",
    DaemonOp.METRICS: "metrics",
    DaemonOp.OFFER_LANDING: "offer_landing",
    int(AmId.FETCH_BLOCK_REQ): "fetch_block",
}


def _frame(op: int, header: dict, body: bytes = b"") -> bytes:
    # reuse the AM frame layout with op ids beyond the AM enum
    payload = json.dumps(header).encode()
    return struct.pack("<IQQ", op, len(payload), len(body)) + payload + body


def _recv_body(sock: socket.socket, view: memoryview, timeout_ms: int) -> None:
    """Fill ``view`` from the socket: a frame's body straight into the place
    the caller chose for it.  Mid-frame, so under ``conf.wire_timeout_ms``
    (0 = none): a sender that stalls raises ``socket.timeout``, one that
    closes ``ConnectionError`` — both ``OSError``, the connection's end.  The
    socket waits for its next frame without a timeout, as before."""
    n = len(view)
    if not n:
        return
    try:  # what has arrived already (all of a small body): no wait, so no timeout to set
        got = sock.recv_into(view, n, socket.MSG_DONTWAIT)
        if not got:
            raise ConnectionError(f"peer closed before a body of {n} B")
    except BlockingIOError:
        got = 0
    if got == n:
        return
    if timeout_ms:
        sock.settimeout(timeout_ms / 1000.0)
    try:
        while got < n:
            r = sock.recv_into(view[got:])
            if not r:
                raise ConnectionError(f"peer closed mid-body with {got}/{n} B received")
            got += r
    finally:
        if timeout_ms:
            sock.settimeout(None)


def _drop_body(sock: socket.socket, n: int, timeout_ms: int) -> None:
    """Read ``n`` bytes of a refused frame off the socket and keep none:
    the connection stays in step with its peer, nothing of ``n`` is allocated."""
    scratch = memoryview(bytearray(min(n, 1 << 16)))
    while n:
        part = scratch[: min(n, len(scratch))]
        _recv_body(sock, part, timeout_ms)
        n -= len(part)


#: pending bytes at which ``DaemonClient.write_partition`` sends its batch: one
#: ``WritePartition`` frame carries the blocks of one map writer up to here (a
#: block that alone reaches it goes alone).  Fixed by
#: ``scripts/probe_wire_batches.py`` on the chip's host at 1.6 KB and 625 KB
#: blocks, one and four connections (its docstring has the table: 1.6 KB
#: blocks are level from 1 MiB up, 625 KB blocks still gain 22% from 8 MiB to
#: here); not a conf key: no deployment has a reason to set it
WRITE_BATCH_BYTES = 64 << 20
#: a kept landing buffer longer than this many times the reply at hand is let
#: go (``DaemonClient.fetch_blocks``): "much larger" by what the client sees
LANDING_SLACK = 4
#: what ``fetch_blocks`` hands out for an empty block of a reply without a body
_NO_BYTES = memoryview(b"")
#: bit 0 of a fetch request's ``tag``: the landing this connection offered is
#: free — no view of it lives, the daemon may write it
TAG_LANDING_FREE = 1
#: bit 1 of a fetch reply's ``tag``: the body is in the landing, none follows
TAG_BODY_MAPPED = 2
#: a landing is offered at this many times the reply at hand (in whole pages):
#: inside PR 41's rule — at least the reply, at most ``LANDING_SLACK`` times
#: it — with room both ways, so the replies of one stage (a reduce task's
#: blocks differ by a few percent at 25k, by a third at 1k) find it fits
LANDING_HEADROOM = 2
#: where a landing's name lives until both processes hold its pages: POSIX
#: shared memory's directory (``shm_open``'s)
_SHM_DIR = "/dev/shm"


def attach_landing(name: str, capacity: int) -> np.ndarray:
    """The daemon's side of ``OfferLanding``: the pages a same-host client
    made under ``name``, as bytes this process may write.  Refused (raises;
    the op's ack says why) unless the name is a plain one that opens without
    following a link, the file is a regular one **of this process's own
    user** and at least ``capacity`` long, and ``capacity`` is one a frame
    may have.  Every page is there before the first copy (``posix_fallocate``:
    no work on a file its creator allocated): a full ``/dev/shm`` is an
    ``ENOSPC`` here, never a ``SIGBUS`` under a reply.  The mapping lives as
    long as the array does."""
    if not name or name.startswith(".") or "/" in name:
        raise ValueError(f"not a landing's name: {name!r}")
    if not 0 < capacity <= MAX_FRAME_BYTES:
        raise ValueError(f"a landing of {capacity} B")
    fd = os.open(os.path.join(_SHM_DIR, name), os.O_RDWR | os.O_NOFOLLOW | os.O_CLOEXEC)
    try:
        st = os.fstat(fd)
        if not stat.S_ISREG(st.st_mode):
            raise ValueError(f"landing {name!r} is not a regular file")
        if st.st_uid != os.geteuid():
            raise PermissionError(f"landing {name!r} belongs to user {st.st_uid}, not to the daemon's")
        if st.st_size < capacity:
            raise ValueError(f"landing {name!r} is {st.st_size} B, offered as {capacity} B")
        os.posix_fallocate(fd, 0, capacity)
        return np.frombuffer(mmap.mmap(fd, capacity), dtype=np.uint8)
    finally:
        os.close(fd)


def make_landing(path: str, capacity: int) -> Optional[mmap.mmap]:
    """The client's side of ``OfferLanding``: a new file at ``path`` — created
    exclusively, mode 0600, no link followed — of ``capacity`` bytes, every
    page allocated now (none is missing under the daemon's copy later), and
    mapped.  ``None``, and no file left, where this host gives none (no
    ``/dev/shm``, no room in it).  The caller unlinks ``path``."""
    try:
        fd = os.open(path, os.O_CREAT | os.O_EXCL | os.O_RDWR | os.O_NOFOLLOW | os.O_CLOEXEC, 0o600)
    except OSError:
        return None
    try:
        os.posix_fallocate(fd, 0, capacity)
        return mmap.mmap(fd, capacity)
    except (OSError, ValueError):
        os.unlink(path)
        return None
    finally:
        os.close(fd)


def _is_loopback(sock: socket.socket) -> bool:
    """Did this socket connect to a loopback address?  Then its peer is a
    process of this host."""
    if sock.family not in (socket.AF_INET, socket.AF_INET6):
        return False
    try:
        return ipaddress.ip_address(sock.getpeername()[0]).is_loopback
    except (OSError, ValueError):
        return False


def _recv_landing(sock: socket.socket, view: memoryview, peer: str) -> None:
    """Fill ``view`` from the socket: a fetch reply's body straight into the
    client's landing buffer.  Mid-frame, under the socket's own timeout, in
    ``recv_exact``'s words: a read that times out means the daemon hung, one
    that returns nothing that it closed — both ``OSError``, both saying how
    far the body got."""
    n, got = len(view), 0
    while got < n:
        try:
            r = sock.recv_into(view[got:], n - got)
        except socket.timeout:
            raise OSError(
                f"peer {peer} hung mid-frame: read timed out with {got}/{n} B received"
            ) from None
        if r == 0:
            raise ConnectionError(f"daemon {peer} closed the connection mid-body with {got}/{n} B received")
        got += r


def _read_frame(sock) -> Optional[Tuple[int, dict, bytes]]:
    hdr = recv_exact(sock, FRAME_HEADER_SIZE)
    if hdr is None:
        return None
    return _read_frame_rest(sock, *struct.unpack("<IQQ", hdr))


def _read_meta(sock, hlen: int, blen: int) -> Optional[dict]:
    """The JSON header of the frame whose fixed header said so; None at EOF."""
    if hlen + blen > MAX_FRAME_BYTES:
        raise ValueError(f"frame too large ({hlen + blen} B)")
    header = recv_exact(sock, hlen) if hlen else b""
    if header is None:
        return None
    return json.loads(header) if header else {}


def _read_frame_rest(sock, op: int, hlen: int, blen: int) -> Optional[Tuple[int, dict, bytes]]:
    """The JSON header and body of the frame whose fixed header said so."""
    meta = _read_meta(sock, hlen, blen)
    body = recv_exact(sock, blen) if blen and meta is not None else b""
    if meta is None or body is None:
        return None
    return op, meta, body


class _ConnTurn:
    """What full tracing keeps of one connection: where its last frame's span
    ended (0: none yet) and how many ``write_partition`` frames it has sent."""

    __slots__ = ("t_end", "writes")

    def __init__(self) -> None:
        self.t_end = 0
        self.writes = 0


class _StageExchange:
    """One shuffle's exchange at the stage boundary: ``done`` is set when the
    thread that claimed it has finished, ``error`` is what it raised."""

    __slots__ = ("done", "error")

    def __init__(self) -> None:
        self.done = threading.Event()
        self.error: Optional[BaseException] = None


class ShuffleDaemon:
    """Hosts a TpuShuffleManager behind the wire protocol."""

    def __init__(
        self,
        conf: Optional[TpuShuffleConf] = None,
        num_executors: Optional[int] = None,
        host: str = "127.0.0.1",
        port: int = 0,
    ) -> None:
        self.conf = conf or TpuShuffleConf()
        self.manager = TpuShuffleManager(self.conf, num_executors=num_executors)
        self._srv = socket.socket()
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind((host, port))
        self._srv.listen(16)
        self.address: Tuple[str, int] = self._srv.getsockname()
        self._running = True
        # _serve runs per-connection threads; every handle-table touch goes
        # through _lock — a second connection's OPEN/COMMIT must never race a
        # stream rebinding mid-dispatch (analysis: lock-discipline pass).
        self._writers: Dict[int, object] = {}  #: guarded by self._lock
        #: writer handle -> its one open partition stream (the sequential protocol)
        self._streams: Dict[int, object] = {}  #: guarded by self._lock
        self._next_writer = 0  #: guarded by self._lock
        #: per-op frame counters, always on: op id -> [frames, body_bytes,
        #: serve_ns, ack_ns, blocks, mapped]; the ``daemon`` family of the cluster's registry
        self._op_stats: Dict[int, List[int]] = {}  #: guarded by self._lock
        #: shuffle id -> its exchange at the stage boundary, from the frame
        #: that claims it to ``RemoveShuffle``: the per-shuffle guard
        self._stages: Dict[int, _StageExchange] = {}  #: guarded by self._lock
        #: always on: exchanges a fetch started, fetches that waited for
        #: another thread's exchange and for how long, connections open now
        #: and the most that ever were
        self._stage_stats: Dict[str, int] = dict.fromkeys(
            ("stage_exchanges", "stage_waiters", "stage_wait_ns", "connections", "connections_peak"), 0
        )  #: guarded by self._lock
        self._lock = threading.Lock()
        #: connection -> its ``_ConnTurn``, while full tracing is on (empty
        #: otherwise).  No lock: a connection is served by one thread at a
        #: time on either plane, so an entry has one writer, and each dict
        #: operation is atomic
        self._turns: Dict[socket.socket, _ConnTurn] = {}
        #: connection -> the landing its client offered (``OfferLanding``), as
        #: bytes of the mapping both processes hold; dropped — and with it
        #: this side of the mapping — when the connection closes or offers
        #: anew.  No lock, for ``_turns``' reason
        self._landings: Dict[socket.socket, np.ndarray] = {}
        #: ``t_ack``: when the calling thread last began to send a reply;
        #: ``blocks``: how many blocks its ``write_partition`` frame recorded;
        #: ``t_mapped``: when its ``fetch_block`` frame had copied its reply's
        #: body into the connection's landing (0: the body went over the socket)
        self._tls = threading.local()
        self.manager.cluster.metrics.register(
            "daemon", labelled_counter_provider("daemon", "op", self.op_stats)
        )
        self.manager.cluster.metrics.register("daemon.stage", self._stage_samples)
        # Serving plane: thread-per-connection by default; with
        # server.workers set (or tenants.enabled) the shared reactor holds
        # every idle client in one selector and serves frames from a bounded
        # pool (service/reactor.py) — same dispatch code either way.
        self._reactor: Optional[Reactor] = None
        self._thread: Optional[threading.Thread] = None
        if self.conf.server_workers > 0 or self.conf.tenants_enabled:
            self._reactor = Reactor(self.conf.server_workers, name="sparkucx-daemon")
            self._reactor.add_listener(self._srv, self._on_accept)
        else:
            self._thread = threading.Thread(target=self._accept_loop, daemon=True)
            self._thread.start()

    # ------------------------------------------------------------------

    @property
    def running(self) -> bool:
        """True until close() — the CLI main loop polls this."""
        return self._running

    def _accept_loop(self) -> None:
        while self._running:
            try:
                conn, _ = self._srv.accept()
                apply_wire_sockopts(conn, self.conf)
            except OSError:
                return
            self._connection_opened()
            threading.Thread(target=self._serve, args=(conn,), daemon=True).start()

    def _on_accept(self, conn: socket.socket) -> None:
        """Reactor accept path: restore blocking reads (the listener is
        non-blocking under the selector), then park the connection."""
        apply_wire_sockopts(conn, self.conf)
        conn.setblocking(True)
        self._connection_opened()
        self._reactor.add_connection(conn, self._serve_step, self._connection_closed)

    def _connection_opened(self) -> None:
        with self._lock:
            stats = self._stage_stats
            stats["connections"] += 1
            stats["connections_peak"] = max(stats["connections_peak"], stats["connections"])

    def _connection_closed(self, conn=None) -> None:
        self._turns.pop(conn, None)
        self._landings.pop(conn, None)
        with self._lock:
            self._stage_stats["connections"] -= 1

    def op_stats(self) -> List[Dict[str, object]]:
        """One row of counters per op served so far (the ``daemon`` family)."""
        rows: Dict[str, List[int]] = {}
        with self._lock:
            for op, counts in self._op_stats.items():
                row = rows.setdefault(OP_NAMES.get(op, "unknown"), [0, 0, 0, 0, 0, 0])
                for i, value in enumerate(counts):
                    row[i] += value
        return [
            {"op": name, "frames": f, "body_bytes": b, "serve_ns": s, "ack_ns": a, "blocks": n, "mapped": m}
            for name, (f, b, s, a, n, m) in sorted(rows.items())
        ]

    def stage_stats(self) -> Dict[str, int]:
        """The stage boundary's counters and the connection gauge."""
        with self._lock:
            return dict(self._stage_stats)

    def _stage_samples(self) -> List[MetricSample]:
        stats = self.stage_stats()
        return [
            sample("daemon", "connections", stats.pop("connections")),
            sample("daemon", "connections_peak", stats.pop("connections_peak"), kind="counter"),
            *(sample("daemon", name + "_total", value, kind="counter") for name, value in stats.items()),
        ]

    def _ack(self, conn, ok: bool, body: bytes = b"", **extra) -> None:
        frame = _frame(DaemonOp.ACK, {"ok": ok, **extra}, body)
        self._tls.t_ack = perf_counter_ns()  # ack_ns runs from here to the frame's end
        conn.sendall(frame)

    def _serve_step(self, conn: socket.socket) -> bool:
        """Read + dispatch exactly one frame; True keeps the connection.
        The unit of work for both serving planes — the per-connection threads
        loop over it, the reactor re-arms the connection after each True.

        The frame is on the clock from its fixed header's arrival to the
        reply sent (``serve_ns``; span ``daemon.<op>`` under full tracing
        only — one a frame would cost every untraced run and flush the
        flight recorder's tail); waiting for the next frame's header is
        outside.  This runs once a frame: it reads the clock three times and
        adds six ints, and anything more shows in the job's time.  What full
        tracing adds to the span is in ``_serve_traced``."""
        if not self._running:
            return False
        try:
            hdr = recv_exact(conn, FRAME_HEADER_SIZE)
            if hdr is None:
                return False
            t0 = perf_counter_ns()
            op, hlen, body_bytes = struct.unpack("<IQQ", hdr)
            tls = self._tls
            tls.blocks = tls.t_mapped = 0
            if TRACER.enabled:
                served = self._serve_traced(conn, op, hlen, body_bytes)
            else:
                if self._turns:  # tracing went off: the next count starts anew
                    self._turns.clear()
                served = self._serve_frame(conn, op, hlen, body_bytes)
            if not served:
                return False
            t1 = perf_counter_ns()
            t_ack = getattr(tls, "t_ack", 0)
            with self._lock:
                row = self._op_stats.get(op)
                if row is None:
                    row = self._op_stats[op] = [0, 0, 0, 0, 0, 0]
                row[0] += 1
                row[1] += body_bytes
                row[2] += t1 - t0
                if t_ack > t0:
                    row[3] += t1 - t_ack
                row[4] += tls.blocks
                if tls.t_mapped:
                    row[5] += 1
            return True
        except (OSError, ValueError):
            # dead socket or an unparseable/oversized frame: drop THIS
            # connection, keep serving others (the endpoint-eviction policy,
            # UcxWorkerWrapper.scala:248-253)
            return False

    def _serve_traced(self, conn: socket.socket, op: int, hlen: int, blen: int) -> bool:
        """``_serve_frame`` under full tracing: the span ``daemon.<op>`` as
        ever, and after it has closed — off the frame's clock — the frame by
        phase and the connection's wait for its client, from plain clock
        marks (module docstring).  The phases lie between the span's own
        bounds (``ctx.t0`` / ``ctx.t1``), so they partition it; the client's
        turn runs from the span of the connection's frame before to this
        one's, so a connection's spans and turns tile its time."""
        turn = self._turns.get(conn)
        if turn is None:
            turn = self._turns[conn] = _ConnTurn()
        marks = None
        if op == DaemonOp.WRITE_PARTITION:
            if turn.writes % WRITE_PHASES_EVERY == 1:
                marks = []
            turn.writes += 1
        with TRACER.span("daemon." + OP_NAMES.get(op, "unknown")) as ctx:
            served = self._serve_frame(conn, op, hlen, blen, marks)
        t_begin, t_end = ctx.t0, ctx.t1
        waited_from, turn.t_end = turn.t_end, t_end
        t_ack = getattr(self._tls, "t_ack", 0)
        if not served or t_ack <= t_begin:
            return served
        if op == int(AmId.FETCH_BLOCK_REQ):
            send = ("daemon.fetch_block.send", t_ack, t_end)
            t_mapped = self._tls.t_mapped
            if t_mapped:  # the body went into the landing: the copy, inside the send
                send += (None, (("daemon.fetch_block.send.mapped", t_ack, t_mapped),))
            phases = (("daemon.fetch_block.locate", t_begin, t_ack), send)
        elif marks is not None and len(marks) == 3:  # a frame refused on the way has fewer
            cuts = (t_begin, *marks, t_ack, t_end)
            phases = zip(_WRITE_PHASES, cuts, cuts[1:])
        else:
            return served
        TRACER.record_spans(ctx, phases)
        if waited_from:
            TRACER.record_spans(None, (("daemon.client_turn." + OP_NAMES[op], waited_from, t_begin),))
        return served

    def _serve_frame(
        self, conn: socket.socket, op: int, hlen: int, blen: int, marks: Optional[List[int]] = None
    ) -> bool:
        """The rest of the frame whose fixed header said so, dispatched and
        answered; False when the peer went away mid-frame.  ``marks``, where
        full tracing samples this ``WritePartition`` frame by phase, takes
        the clock at its phase boundaries."""
        if op == DaemonOp.WRITE_PARTITION:
            return self._serve_write(conn, hlen, blen, marks)
        frame = _read_frame_rest(conn, op, hlen, blen)
        if frame is None:
            return False
        op, meta, body = frame
        try:
            self._dispatch(conn, op, meta, body)
        except Exception as e:
            self._ack(conn, False, error=f"{type(e).__name__}: {e}")
        return True

    def _serve_write(
        self, conn: socket.socket, hlen: int, blen: int, marks: Optional[List[int]] = None
    ) -> bool:
        """A ``WritePartition`` frame, of one block (``reduce_id``; the body
        is the block) or of several of one writer (``reduce_ids`` and
        ``lengths``; the body is the blocks back to back).  The JSON header
        is read as any op's and checked whole (``_frame_blocks``) before a
        byte of the body is read; the body is not read as any op's — block by
        block the store reserves its extent and the socket is received
        straight into it (``PartitionWriterStream.reserve``), outside every
        lock.  An error of admission (unknown writer, sealed shuffle, quota,
        a body larger than a region) is raised before a byte of that block is
        read: the rest of the body is dropped unread-into-memory, the error
        acked — with the refused ``reduce_id`` and the blocks ``written``
        before it where the frame named several — and the connection kept.
        A body that stalls past ``conf.wire_timeout_ms`` or whose sender
        closes ends this connection only, as a dead socket always did; the
        stream of the block in flight gives the round's in-flight count back
        on the way out.  One ack a frame: ``written`` is the body's length
        for the one-block form, the list of every block's for the other.

        ``marks`` (a sampled frame under full tracing, else None) gets three
        cuts once the frame is served: the clock after the JSON header is
        parsed, that plus the blocks' admissions (stream found, extent
        reserved), that plus their receives; what is left before the ack is
        their records."""
        meta = _read_meta(conn, hlen, blen)
        if meta is None:
            return False
        timed = marks is not None
        t_meta = t_last = perf_counter_ns() if timed else 0
        admit_ns = body_ns = 0
        timeout_ms = self.conf.wire_timeout_ms
        batch = "reduce_ids" in meta
        try:
            handle, reduce_ids, lengths = self._frame_blocks(meta, blen)
        except (KeyError, TypeError, ValueError) as e:
            _drop_body(conn, blen, timeout_ms)
            self._ack(conn, False, error=f"{type(e).__name__}: {e}")
            return True
        written: List[int] = []
        left = blen  # of the body, not yet read
        refused: Optional[Exception] = None
        for reduce_id, nbytes in zip(reduce_ids, lengths):
            try:
                stream = self._partition_stream(handle, reduce_id)
                view = stream.reserve(nbytes)
            except Exception as e:
                refused = e
                break
            if timed:
                t_admit = perf_counter_ns()
            try:
                _recv_body(conn, view, timeout_ms)
            except BaseException:  # the socket's own failure: the connection's end
                stream.end_receive(nbytes, False)
                raise
            left -= nbytes
            if timed:
                t_body = perf_counter_ns()
            try:
                stream.end_receive(nbytes, True)
            except Exception as e:  # the buffered path refuses at its ``write``
                refused = e
                break
            written.append(nbytes)
            if timed:
                admit_ns += t_admit - t_last
                body_ns += t_body - t_admit
                t_last = perf_counter_ns()
        self._tls.blocks = len(written)
        if refused is not None:
            _drop_body(conn, left, timeout_ms)
            named = {"reduce_id": reduce_id, "written": written} if batch else {}
            self._ack(conn, False, error=f"{type(refused).__name__}: {refused}", **named)
            return True
        if timed:
            marks += (t_meta, t_meta + admit_ns, t_meta + admit_ns + body_ns)
        self._ack(conn, True, written=written if batch else blen)
        return True

    def _frame_blocks(self, meta: dict, blen: int) -> Tuple[int, List[int], List[int]]:
        """``(writer, reduce ids, lengths)`` of a ``WritePartition`` header,
        either form.  The several-block form is checked whole here, before a
        byte of its body is read: the writer is open, the reduce ids do not
        go backwards, every block has a length, none negative, and together
        they are the body."""
        handle = int(meta["writer"])
        if "reduce_ids" not in meta:
            return handle, [int(meta["reduce_id"])], [blen]
        reduce_ids = [int(r) for r in meta["reduce_ids"]]
        lengths = [int(n) for n in meta["lengths"]]
        with self._lock:
            if handle not in self._writers:
                raise KeyError(handle)
        if len(lengths) != len(reduce_ids):
            raise ValueError(f"{len(reduce_ids)} reduce ids but {len(lengths)} lengths")
        if any(b < a for a, b in zip(reduce_ids, reduce_ids[1:])):
            raise ValueError(f"reduce ids go backwards: {reduce_ids}")
        if lengths and min(lengths) < 0:
            raise ValueError(f"a negative length: {min(lengths)}")
        if sum(lengths) != blen:
            raise ValueError(f"lengths sum to {sum(lengths)} B, the body is {blen} B")
        return handle, reduce_ids, lengths

    def _partition_stream(self, handle: int, reduce_id: int):
        """The open stream of ``(writer, reduce_id)``; opening it closes the
        writer's open stream of another partition (the sequential protocol).
        Once a block: two takes of the daemon's lock where a new partition
        opens, one where the last one goes on."""
        with self._lock:
            writer = self._writers[handle]
            stream = self._streams.get(handle)
        if stream is not None:
            if stream.reduce_id == reduce_id:
                return stream
            stream.close()  # outside the lock: it takes the store's
        stream = writer.get_partition_writer(reduce_id).open_stream()
        with self._lock:
            self._streams[handle] = stream
        return stream

    def _serve(self, conn: socket.socket) -> None:
        try:
            while self._serve_step(conn):
                pass
        finally:
            self._connection_closed(conn)
            conn.close()

    def _exchange_once(self, shuffle_id: int, explicit: bool) -> None:
        """The per-shuffle guard round the exchange.  The first frame to ask
        claims it and runs it; a frame that finds it running waits and shares
        its outcome.  ``explicit`` is a ``RunExchange`` frame, which behaves
        as before there was a guard: it always tries (a second one is told
        "already exchanged"; one sent too early can be sent again), and a
        failure of its own leaves no trace.  A failure of the exchange a
        fetch started stays, and fails the later fetches of the shuffle too:
        two hundred reduce tasks do not each seal and fail again."""
        with self._lock:
            stage = self._stages.get(shuffle_id)
            mine = stage is None or (explicit and stage.done.is_set())
            if mine:
                stage = self._stages[shuffle_id] = _StageExchange()
        if mine:
            try:
                with nullcontext() if explicit else span("daemon.stage_exchange", shuffle_id=shuffle_id):
                    self.manager.run_exchange(shuffle_id)
            except BaseException as e:
                stage.error = e
                raise
            finally:
                with self._lock:
                    if explicit:
                        if stage.error is not None and self._stages.get(shuffle_id) is stage:
                            del self._stages[shuffle_id]
                    elif stage.error is None:
                        self._stage_stats["stage_exchanges"] += 1
                stage.done.set()
            return
        if not stage.done.is_set():
            t0 = perf_counter_ns()
            with span("daemon.stage_wait", shuffle_id=shuffle_id):
                finished = stage.done.wait(_STAGE_WAIT_S)
            with self._lock:
                self._stage_stats["stage_waiters"] += 1
                self._stage_stats["stage_wait_ns"] += perf_counter_ns() - t0
            if not finished:
                raise TransportError(
                    f"the exchange of shuffle {shuffle_id} is still running after {_STAGE_WAIT_S:.0f} s"
                )
        if stage.error is not None:
            raise TransportError(f"the exchange of shuffle {shuffle_id} failed: {stage.error}")

    def _exchange_at_first_fetch(self, shuffle_id: int) -> None:
        """Before a fetch locates its blocks: run or await the shuffle's
        exchange if this is the stage boundary.  Raises nothing: a shuffle
        that is unknown, has a map uncommitted or whose exchange failed is
        answered block by block, as a fetch always was (size -1)."""
        mgr = self.manager
        try:
            boundary = not mgr.cluster.meta(shuffle_id).exchanged and mgr.exchange_ready(shuffle_id)
        except (KeyError, TransportError):  # no such shuffle
            return
        if not boundary:
            return
        try:
            self._exchange_once(shuffle_id, explicit=False)
        except Exception as e:  # the frame's boundary: its blocks answer -1
            logger.warning("fetch of shuffle %d finds no exchange: %s: %s", shuffle_id, type(e).__name__, e)

    def _dispatch(self, conn, op: int, meta: dict, body: bytes) -> None:
        mgr = self.manager
        if op == DaemonOp.CREATE_SHUFFLE:
            mgr.register_shuffle(int(meta["shuffle_id"]), int(meta["num_mappers"]), int(meta["num_reducers"]))
            self._ack(conn, True)
        elif op == DaemonOp.OPEN_MAP_WRITER:
            writer = mgr.get_writer(int(meta["shuffle_id"]), int(meta["map_id"]))
            with self._lock:
                handle = self._next_writer
                self._next_writer += 1
                self._writers[handle] = writer
            self._ack(conn, True, writer=handle)
        elif op == DaemonOp.COMMIT_MAP:
            handle = int(meta["writer"])
            with self._lock:
                stream = self._streams.pop(handle, None)
                writer = self._writers.pop(handle)
            if stream is not None:
                stream.close()
            lengths = writer.commit_all_partitions()
            self._ack(conn, True, body=np.asarray(lengths, dtype="<i8").tobytes())
        elif op == DaemonOp.RUN_EXCHANGE:
            self._exchange_once(int(meta["shuffle_id"]), explicit=True)
            self._ack(conn, True)
        elif op == DaemonOp.REMOVE_SHUFFLE:
            sid = int(meta["shuffle_id"])
            mgr.unregister_shuffle(sid)
            with self._lock:
                self._stages.pop(sid, None)
            self._ack(conn, True)
        elif op == DaemonOp.STATS:
            sid = int(meta["shuffle_id"])
            meta_obj = mgr.cluster.meta(sid)
            sizes = {
                f"{m}": [ln for (_, ln) in info.partitions]
                for m, info in meta_obj.mapper_infos.items()
            }
            self._ack(conn, True, num_mappers=meta_obj.num_mappers,
                      num_reducers=meta_obj.num_reducers, exchanged=meta_obj.exchanged,
                      block_lengths=sizes)
        elif op == DaemonOp.EXPORT_TRACE:
            # merge the daemon-side executors' trace buffers to a file the
            # CLIENT named — the daemon owns the cluster, so the trace lives
            # on its side of the control socket
            count = mgr.cluster.export_trace(str(meta["path"]))
            self._ack(conn, True, events=count)
        elif op == DaemonOp.METRICS:
            self._ack(conn, True, body=mgr.cluster.metrics_text().encode())
        elif op == DaemonOp.OFFER_LANDING:
            # a refusal is the end of it for the connection: the landing it
            # had goes first, and what ``attach_landing`` raises is acked
            self._landings.pop(conn, None)
            self._landings[conn] = attach_landing(str(meta["name"]), int(meta["capacity"]))
            self._ack(conn, True)
        elif op == int(AmId.FETCH_BLOCK_REQ):
            # data-plane fetch: batched AM form (binary batch header travels in
            # the body so the JSON control framing stays uniform)
            tag, bids = unpack_batch_fetch_req(body)
            self._serve_fetch(conn, tag, bids)
        elif op == DaemonOp.SHUTDOWN:
            self._ack(conn, True)
            self.close()
        else:
            self._ack(conn, False, error=f"unknown op {op}")

    def _serve_fetch(self, conn, tag, bids) -> None:
        # Resolve each block to a zero-copy view and stream the reply as a
        # vectored sendmsg over the views — the wire bytes are identical to
        # the historical [sizes | data...] frame, but no monolithic reply
        # body is ever assembled (and no per-block bytes() copies are paid).
        # Where the connection offered a landing, the request says it is free
        # (``TAG_LANDING_FREE``) and the body fits, the views are copied into
        # it back to back instead (numpy's slice assignment: off the
        # interpreter lock) and the reply is its two headers, marked
        # ``TAG_BODY_MAPPED``, with a body length of 0.  The header on the
        # socket is the hand-over: the landing is written only here, between
        # such a request and its reply.
        for shuffle_id in {bid.shuffle_id for bid in bids}:
            self._exchange_at_first_fetch(shuffle_id)
        parts, sizes = [], []
        for bid in bids:
            try:
                meta_obj = self.manager.cluster.meta(bid.shuffle_id)
                consumer = meta_obj.owner_of_reduce(bid.reduce_id)
                view, length = self.manager.cluster.locate_received_block(
                    consumer, bid.shuffle_id, bid.map_id, bid.reduce_id
                )
                seg = np.ascontiguousarray(view[:length]).reshape(-1).view(np.uint8)
                if length:
                    parts.append(seg)
                sizes.append(int(length))
            except Exception:
                sizes.append(-1)
        blob = b"".join(_SIZE.pack(s) for s in sizes)
        total = sum(p.nbytes for p in parts)
        landing = self._landings.get(conn) if tag & TAG_LANDING_FREE else None
        mapped = landing is not None and 0 < total <= len(landing)
        reply_hdr = _TAG.pack(tag | TAG_BODY_MAPPED if mapped else tag) + _COUNT.pack(len(bids)) + blob
        prefix = pack_frame_prefix(AmId.FETCH_BLOCK_REQ_ACK, reply_hdr, 0 if mapped else total)
        self._tls.t_ack = perf_counter_ns()  # the reply is this op's ack
        if mapped:
            pos = 0
            for seg in parts:
                landing[pos : pos + seg.nbytes] = seg
                pos += seg.nbytes
            self._tls.t_mapped = perf_counter_ns()
            conn.sendall(prefix)
        elif hasattr(conn, "sendmsg"):
            BlockServer._sendmsg_all(conn, [prefix] + parts)
        else:
            conn.sendall(b"".join([prefix] + [bytes(p) for p in parts]))

    def close(self) -> None:
        self._running = False
        try:
            self._srv.close()
        except OSError:
            pass
        if self._reactor is not None:
            self._reactor.close()
        self.manager.stop()


class DaemonClient:
    """What the JVM shim (jvm/TpuShuffleManager.java) speaks — also usable from
    Python for tests and tooling."""

    def __init__(self, address: Tuple[str, int], conf: Optional[TpuShuffleConf] = None) -> None:
        self._sock = socket.create_connection(address, timeout=30)
        apply_wire_sockopts(self._sock, conf)
        self._peer = f"{address[0]}:{address[1]}"
        self._lock = threading.Lock()
        #: where the body of a ``fetch_blocks`` reply lands: the one buffer
        #: this connection keeps across calls.  Written again only while no
        #: view of it lives; replaced by the buffer of a reply it could not take
        self._landing: Optional[bytearray] = None  #: guarded by self._lock
        #: the landing this connection offered and the daemon took: a mapping
        #: both processes hold, its name gone.  The daemon writes it only
        #: between a request that says it is free and that request's reply
        self._mapped: Optional[mmap.mmap] = None  #: guarded by self._lock
        #: may this connection (still) offer one?  Its daemon is on this host
        #: (a loopback address; the proof is the daemon's attach) and has
        #: refused none
        self._may_offer = _is_loopback(self._sock)  #: guarded by self._lock
        #: always on, bumped once a reply (``fetch_stats()``)
        self._fetch_stats: Dict[str, int] = dict.fromkeys(
            ("fetch_replies", "landed_reused", "landed_fresh", "landed_mapped", "mapped_bytes",
             "landings_offered", "landings_refused", "view_blocks", "view_bytes"), 0
        )  #: guarded by self._lock
        #: blocks ``write_partition`` holds back, all of ``_pending_writer``,
        #: with their reduce ids and their bytes in all
        self._pending: List[bytes] = []  #: guarded by self._lock
        self._pending_ids: List[int] = []  #: guarded by self._lock
        self._pending_bytes = 0  #: guarded by self._lock
        self._pending_writer = -1  #: guarded by self._lock
        #: writers a frame of whose blocks was refused, lost or acked short:
        #: ``commit_map`` refuses them (empty while every flush is acked whole)
        self._unacked: set = set()  #: guarded by self._lock
        #: always on (``write_stats()``)
        self._write_stats: Dict[str, int] = dict.fromkeys(
            ("write_frames", "write_blocks", "flushes_full", "flushes_forced", "sent_at_once"), 0
        )  #: guarded by self._lock

    def _call(self, op: int, header: dict, body: bytes = b"") -> Tuple[dict, bytes]:
        with self._lock:
            self._flush("flushes_forced")  # the daemon sees this connection's ops in the caller's order
            return self._exchange(op, header, (body,))

    def _exchange(self, op: int, header: dict, bodies) -> Tuple[dict, bytes]:
        """One frame out, its ack in (caller holds the lock).  The frame's
        bytes in the frame's order, no body joined to its header or to
        another: one vectored send (1,024 buffers a call), and the rest of a
        short one in a loop."""
        payload = json.dumps(header).encode()
        prefix = struct.pack("<IQQ", op, len(payload), sum(map(len, bodies))) + payload
        sock = self._sock
        BlockServer._sendmsg_all(sock, [prefix, *bodies])
        frame = _read_frame(sock)
        if frame is None:
            raise ConnectionError("daemon closed connection")
        _, meta, ack_body = frame
        if not meta.get("ok"):
            error = meta.get("error", "daemon error")
            if "reduce_id" in meta:  # a batch refused at one of its blocks
                error += (
                    f" (block of reduce partition {meta['reduce_id']}, after "
                    f"{len(meta.get('written', ()))} blocks of the frame were recorded)"
                )
            raise RuntimeError(error)
        return meta, ack_body

    def _flush(self, why: str) -> None:
        """Send the pending blocks as one ``WritePartition`` frame and check
        its ack block by block (caller holds the lock).  The list is empty
        again whatever the outcome: a map whose frame was refused or lost is
        retried whole, never resumed."""
        blocks = self._pending
        if not blocks:
            return
        writer, reduce_ids = self._pending_writer, self._pending_ids
        self._pending, self._pending_ids, self._pending_bytes = [], [], 0
        self._write_stats[why] += 1
        lengths = [len(b) for b in blocks]
        self._write_frame(writer, {"writer": writer, "reduce_ids": reduce_ids, "lengths": lengths}, blocks, lengths)

    def _write_frame(self, writer: int, header: dict, blocks, want) -> None:
        """One ``WritePartition`` frame out and its ack checked against
        ``want``, the byte count (or counts) sent (caller holds the lock).
        The writer is ``_unacked`` from the send until the ack says so."""
        stats = self._write_stats
        stats["write_frames"] += 1
        stats["write_blocks"] += len(blocks)
        self._unacked.add(writer)
        meta, _ = self._exchange(DaemonOp.WRITE_PARTITION, header, blocks)
        if meta.get("written") != want:
            raise RuntimeError(
                f"daemon {self._peer} acked other blocks than were sent: {meta.get('written')} for {want}"
            )
        self._unacked.discard(writer)

    def flush(self) -> None:
        """Send what ``write_partition`` holds back, now."""
        with self._lock:
            self._flush("flushes_forced")

    def create_shuffle(self, shuffle_id: int, num_mappers: int, num_reducers: int) -> None:
        self._call(DaemonOp.CREATE_SHUFFLE, {
            "shuffle_id": shuffle_id, "num_mappers": num_mappers, "num_reducers": num_reducers,
        })

    def open_map_writer(self, shuffle_id: int, map_id: int) -> int:
        meta, _ = self._call(DaemonOp.OPEN_MAP_WRITER, {"shuffle_id": shuffle_id, "map_id": map_id})
        return int(meta["writer"])

    def write_partition(self, writer: int, reduce_id: int, data: bytes) -> None:
        """One block (or a further piece of the partition last written) of a
        map writer.  ``bytes`` are not sent yet: the block waits on this
        connection, by reference, and goes out with its writer's other
        pending blocks as ONE ``WritePartition`` frame when
        ``WRITE_BATCH_BYTES`` are pending, when a block of another writer
        arrives, and before any other op on this connection — ``commit_map``
        first of all, which therefore returns only after the daemon has acked
        every block of the map by its byte count and then the commit;
        ``flush()`` does it on demand.  So the daemon's refusal of a block
        (quota, a sealed shuffle, memory pressure) is raised, as the same
        ``RuntimeError``, by the call that flushed it — this one, a later
        ``write_partition`` or the map's ``commit_map`` — and a map whose
        flush raised is retried whole.  A block nothing flushed before
        ``close()`` is never sent: its map did not commit.  Commit a map on
        the connection that wrote it (or ``flush()`` that one first).

        Data that is not ``bytes`` (a ``bytearray``, a ``memoryview``: the
        caller may change it) is sent at once in a frame of its own, after
        what was pending.

        Counters (``write_stats()``): ``write_frames`` / ``write_blocks``
        sent; ``flushes_full`` (the byte bound) / ``flushes_forced``
        (another writer, another op, ``flush()``); ``sent_at_once``."""
        if type(data) is not bytes:
            with self._lock:
                self._flush("flushes_forced")
                self._write_stats["sent_at_once"] += 1
                self._write_frame(writer, {"writer": writer, "reduce_id": reduce_id}, (data,), len(data))
            return
        with self._lock:
            if writer != self._pending_writer:
                self._flush("flushes_forced")
                self._pending_writer = writer
            self._pending.append(data)
            self._pending_ids.append(reduce_id)
            self._pending_bytes += len(data)
            if self._pending_bytes >= WRITE_BATCH_BYTES:
                self._flush("flushes_full")

    def write_stats(self) -> Dict[str, int]:
        """The write leg's counters (``write_partition`` names them)."""
        with self._lock:
            return dict(self._write_stats)

    def commit_map(self, writer: int) -> np.ndarray:
        """Commit the map: its pending blocks first, each acked by its byte
        count, then the commit.  A writer that lost a block on the way (a
        frame refused, lost or acked short, here or at an earlier flush) is
        not committed: the map task is retried whole, under a new writer."""
        with self._lock:
            self._flush("flushes_forced")
            if writer in self._unacked:
                raise RuntimeError(
                    f"map writer {writer} was not acked every block it sent: not committed; retry the map task"
                )
            _, body = self._exchange(DaemonOp.COMMIT_MAP, {"writer": writer}, (b"",))
        return np.frombuffer(body, dtype="<i8")

    def run_exchange(self, shuffle_id: int) -> None:
        self._call(DaemonOp.RUN_EXCHANGE, {"shuffle_id": shuffle_id})

    def fetch_blocks(self, block_ids) -> list:
        """Batched data-plane fetch (AM ids 3/4): one entry a requested block,
        in request order — ``None`` for a block the daemon could not serve,
        else a **read-only ``memoryview``** of the block's bytes where the
        reply landed (zero-length for an empty block).  A view compares equal
        to the ``bytes`` written and is valid for as long as the caller holds
        it; a value that must outlive it is copied (``bytes(view[a:b])``, as
        ``default_deserializer`` does).

        Where the daemon is on this host the body does not cross the socket
        (PR 60).  After the first reply with a body the client makes a mapping
        of ``LANDING_HEADROOM`` times that reply (``/dev/shm``, an unguessable
        name, mode 0600, created exclusively, its pages allocated), offers
        name and capacity in one ``OfferLanding`` op and unlinks the name on
        the ack: both processes hold the pages, no name is left.  From then
        on every request says whether that landing is free — the rule below,
        applied to the mapping — and a reply that finds it free and fits
        comes as its two headers alone, marked ``TAG_BODY_MAPPED``: the
        daemon has copied the blocks into the landing, and the views handed
        out are of the mapping.  Not free, or too large: the reply comes over
        the socket as ever.  A reply outside the landing's range (longer than
        it, or under a ``LANDING_SLACK``-th of a landing of more than a few
        pages) is followed by a new offer at its size; the old mapping lives
        as long as its views (Python unmaps an ``mmap`` with its last holder,
        never under one), past ``close()`` too.  A refused offer (``ok:
        false``; no ``/dev/shm``) is the end of it for this connection: the
        socket serves it.  A daemon elsewhere, or a peer that never offers,
        sees the wire it always saw.

        Over the socket the reply's body is received once, straight into this
        connection's landing buffer, and nothing is copied out of it.  The buffer is kept
        across calls and written again only when nothing else refers to it (a
        view holds its owner, so ``sys.getrefcount`` sees every holder — the
        rule of ``HbmBlockStore._recycle_rounds``) and it is long enough;
        otherwise this reply gets a new one, which is the kept one from then
        on (the old one lives as long as its views).  A kept buffer more than
        ``LANDING_SLACK`` times the reply at hand is let go the same way: a
        straggler's reply is not held for the life of the connection.

        Counters, once a reply (``fetch_stats()``): ``fetch_replies``;
        ``landed_reused`` / ``landed_fresh``, replies received into the kept
        buffer / into a new one (a reply without a body lands nowhere and
        counts in neither) — ``landed_reused / fetch_replies`` is the share of
        replies that cost no allocation and no first touch of new pages, near
        1 for a caller that lets a task's blocks go before its next fetch;
        ``view_blocks`` / ``view_bytes``, the views handed out and their bytes;
        ``landed_mapped`` / ``mapped_bytes``, replies whose body was in the
        mapping and their bytes; ``landings_offered`` / ``landings_refused``."""
        with self._lock:
            self._flush("flushes_forced")
            sock, peer = self._sock, self._peer
            mapped = self._mapped
            # (3 = the attribute, ``mapped`` and getrefcount's argument)
            free = mapped is not None and sys.getrefcount(mapped) == 3
            sock.sendall(pack_frame(
                AmId.FETCH_BLOCK_REQ, b"", pack_batch_fetch_req(TAG_LANDING_FREE if free else 0, block_ids)
            ))
            hdr = recv_exact(sock, FRAME_HEADER_SIZE, idle_ok=True, peer=peer)
            if hdr is None:
                raise ConnectionError("daemon closed connection")
            _, hlen, blen = unpack_frame_header(hdr)
            if hlen + blen > MAX_FRAME_BYTES:
                raise ValueError(f"frame too large from peer {peer}")
            header = recv_exact(sock, hlen, peer=peer)
            if header is None:
                raise ConnectionError("daemon closed connection")
            (tag,) = _TAG.unpack_from(header)
            (count,) = _COUNT.unpack_from(header, _TAG.size)
            sizes = struct.unpack_from(f"<{count}q", header, _TAG.size + _COUNT.size)
            total = sum(s for s in sizes if s > 0)
            stats = self._fetch_stats
            if tag & TAG_BODY_MAPPED:
                if blen or not (free and 0 < total <= len(mapped)):
                    raise ValueError(
                        f"fetch reply from peer {peer} puts {total} B into a landing this request did not give it"
                    )
                view = memoryview(mapped)[:total].toreadonly()
                self._landing = None  # the socket's buffer is not kept beside a landing that serves
                stats["landed_mapped"] += 1
                stats["mapped_bytes"] += total
            elif total != blen:
                raise ValueError(f"fetch reply from peer {peer} names other sizes than its body's {blen} B")
            elif blen:
                buf = self._landing
                # (3 = the attribute, ``buf`` and getrefcount's argument)
                reused = (
                    buf is not None
                    and sys.getrefcount(buf) == 3
                    and blen <= len(buf) <= LANDING_SLACK * blen
                )
                if not reused:
                    buf = self._landing = bytearray(blen)
                view = memoryview(buf)
                _recv_landing(sock, view[:blen], peer)
                view = view.toreadonly()
                stats["landed_reused" if reused else "landed_fresh"] += 1
            else:
                view = _NO_BYTES
            out, pos, missing = [], 0, 0
            for s in sizes:
                if s < 0:
                    out.append(None)
                    missing += 1
                else:
                    out.append(view[pos : pos + s])
                    pos += s
            stats["fetch_replies"] += 1
            stats["view_blocks"] += count - missing
            stats["view_bytes"] += total
            # (a landing is whole pages: one of a page is not "much larger" than any reply)
            if self._may_offer and total and not (
                mapped is not None and total <= len(mapped) <= LANDING_SLACK * max(total, mmap.PAGESIZE)
            ):
                self._offer_landing(total)
        return out

    def _offer_landing(self, reply_bytes: int) -> None:
        """Make a landing for replies like the one at hand and offer it
        (caller holds the lock; ``fetch_blocks`` has the rule).  The name
        lives from the exclusive create to the daemon's ack — one round
        trip — and is unlinked whatever the answer.  What this host cannot
        give (no ``/dev/shm``, no room in it) and what the daemon refuses end
        the offers of this connection; a connection that dies under the offer
        raises as under any op."""
        stats = self._fetch_stats
        stats["landings_offered"] += 1
        page = mmap.PAGESIZE
        capacity = -(-min(LANDING_HEADROOM * reply_bytes, MAX_FRAME_BYTES) // page) * page
        name = "sparkucx-landing-" + secrets.token_hex(16)
        path = os.path.join(_SHM_DIR, name)
        mapping = make_landing(path, capacity)
        if mapping is not None:
            try:
                self._exchange(DaemonOp.OFFER_LANDING, {"name": name, "capacity": capacity}, (b"",))
            except RuntimeError:  # ``ok: false``: the daemon could not attach, or is an older one and knows no such op
                mapping = None
            finally:
                os.unlink(path)
        if mapping is None:
            stats["landings_refused"] += 1
            self._may_offer = False
        self._mapped = mapping

    def fetch_stats(self) -> Dict[str, int]:
        """The receive's counters (``fetch_blocks`` names them)."""
        with self._lock:
            return dict(self._fetch_stats)

    def register_metrics(self, registry: MetricsRegistry) -> None:
        """The ``daemonclient`` family of ``registry``.  A client lives in the
        engine's process, which has no registry of its own: one that has adds
        the counters here, beside its other always-on ones."""
        registry.register(
            "daemonclient",
            counter_dict_provider("daemonclient", lambda: {**self.fetch_stats(), **self.write_stats()}),
        )

    def remove_shuffle(self, shuffle_id: int) -> None:
        self._call(DaemonOp.REMOVE_SHUFFLE, {"shuffle_id": shuffle_id})

    def stats(self, shuffle_id: int) -> dict:
        meta, _ = self._call(DaemonOp.STATS, {"shuffle_id": shuffle_id})
        return meta

    def export_trace(self, path: str) -> int:
        """Ask the daemon to write its merged Perfetto trace to ``path``
        (a path on the DAEMON's filesystem); returns the event count."""
        meta, _ = self._call(DaemonOp.EXPORT_TRACE, {"path": path})
        return int(meta.get("events", 0))

    def metrics_text(self) -> str:
        """The daemon cluster's Prometheus exposition."""
        _, body = self._call(DaemonOp.METRICS, {})
        return body.decode(errors="replace")

    def shutdown(self) -> None:
        try:
            self._call(DaemonOp.SHUTDOWN, {})
        except (ConnectionError, OSError):
            pass

    def close(self) -> None:
        """Close the connection.  Blocks ``write_partition`` still holds back
        are let go unsent: no commit covered them.  The landing is this
        object's and its views': it goes with the last of them, not here."""
        try:
            self._sock.close()
        except OSError:
            pass


def main(argv=None) -> None:
    import argparse

    from sparkucx_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser(prog="sparkucx-tpu-daemon")
    p.add_argument("--port", type=int, default=1338)  # the reference's DPU port
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--executors", type=int, default=1)
    args = p.parse_args(argv)
    daemon = ShuffleDaemon(num_executors=args.executors, host=args.host, port=args.port)
    print(f"shuffle daemon on {daemon.address[0]}:{daemon.address[1]}", flush=True)
    try:
        while daemon.running:
            import time

            time.sleep(0.5)
    except KeyboardInterrupt:
        daemon.close()


if __name__ == "__main__":
    main()
