"""Striped zero-copy wire path tests (PR 5).

Pins the four contracts the striped transport adds on top of the peer wire:

* **streams=1 bit-equality** — with one lane, the bytes on the wire are
  EXACTLY the pre-striping frame format (golden-byte pin, both directions),
  and AM ids 5/6 never appear.
* **chunk-frame oracle** — a striped fetch (streams=2/4) returns byte-for-byte
  what the single-frame path returns, including failures and empty blocks.
* **stripe reassembly** — chunks are self-addressing, so ANY interleaving
  across lanes (including manifest-first, manifest-last, shuffled chunks)
  reassembles correctly and completes exactly once.
* **credit accounting** — the CreditGate never admits past its budget (except
  the documented oversized-alone case), drains to zero, and the reader's
  credit-pipelined fetch yields the same stream as the serial loop.

Plus the zero-copy primitives under adversity: short reads, partial vectored
sends, and the sanitizer-enabled pooled-rx release contract.
"""

import random
import socket
import struct
import threading
import time

import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.block import BytesBlock, MemoryBlock, ShuffleBlockId
from sparkucx_tpu.core.definitions import (
    FRAME_HEADER_SIZE,
    AmId,
    pack_chunk_hdr,
    pack_frame,
    pack_frame_prefix,
    pack_wire_hello,
    unpack_chunk_hdr,
    unpack_frame_header,
    unpack_wire_hello,
)
from sparkucx_tpu.core.operation import OperationStats, OperationStatus, Request
from sparkucx_tpu.memory.pool import MemoryPool
from sparkucx_tpu.memory.sanitizer import SanitizerError
from sparkucx_tpu.shuffle.reader import TpuShuffleReader
from sparkucx_tpu.transport.peer import (
    BlockServer,
    PeerTransport,
    _StripeRx,
    pack_batch_fetch_req,
    recv_exact,
    recv_frame,
)
from sparkucx_tpu.transport.pipeline import CreditGate

_TAG = struct.Struct("<Q")
_COUNT = struct.Struct("<I")
_SIZE = struct.Struct("<q")


def _buf(n):
    return MemoryBlock(np.zeros(n, dtype=np.uint8), size=n)


def _drive(t, reqs, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not all(r.completed() for r in reqs):
        t.progress()
        if time.monotonic() > deadline:
            raise TimeoutError("requests did not complete")
        time.sleep(0.001)


def _pair(streams=1, chunk_bytes=1 << 20, **kw):
    conf = TpuShuffleConf(wire_streams=streams, wire_chunk_bytes=chunk_bytes, **kw)
    a = PeerTransport(conf, executor_id=1)
    b = PeerTransport(conf, executor_id=2)
    a.init()
    a.add_executor(2, b.init())
    return a, b


# ---------------------------------------------------------------------------
# fake sockets for adversity injection
# ---------------------------------------------------------------------------


class ShortReadSock:
    """recv_into hands out at most ``step`` bytes per call (short reads)."""

    def __init__(self, data: bytes, step: int = 3):
        self.data = memoryview(bytes(data))
        self.pos = 0
        self.step = step

    def recv_into(self, mv, n):
        n = min(n, self.step, len(self.data) - self.pos)
        if n <= 0:
            return 0  # EOF
        mv[:n] = self.data[self.pos : self.pos + n]
        self.pos += n
        return n


class PartialSendSock:
    """sendmsg/sendall accept at most ``step`` bytes per call, splitting
    mid-iovec; everything sent accumulates in ``out``."""

    def __init__(self, step: int = 5):
        self.out = bytearray()
        self.step = step

    def sendmsg(self, bufs):
        budget = self.step
        sent = 0
        for b in bufs:
            n = min(budget - sent, b.nbytes)
            self.out += bytes(b[:n])
            sent += n
            if sent >= budget:
                break
        return sent

    def sendall(self, data):
        self.out += bytes(data)


# ---------------------------------------------------------------------------
# zero-copy receive / vectored send primitives
# ---------------------------------------------------------------------------


class TestRecvExact:
    def test_short_reads_reassemble(self):
        payload = bytes(range(256)) * 7
        got = recv_exact(ShortReadSock(payload, step=3), len(payload))
        assert got is not None and bytes(got) == payload

    def test_eof_mid_read_returns_none(self):
        assert recv_exact(ShortReadSock(b"abc", step=2), 10) is None

    def test_zero_length(self):
        got = recv_exact(ShortReadSock(b"", step=1), 0)
        assert got is not None and bytes(got) == b""

    def test_result_is_bytes_compatible(self):
        """bytearray results must work everywhere bytes did."""
        got = recv_exact(ShortReadSock(_TAG.pack(42) + b"xy", step=2), 10)
        assert _TAG.unpack_from(got)[0] == 42
        assert np.frombuffer(got, dtype=np.uint8).shape == (10,)
        assert (b"prefix" + got).endswith(b"xy")

    def test_recv_frame_over_short_reads(self):
        frame = pack_frame(AmId.MAPPER_INFO, b"hdr", b"body-bytes")
        am_id, header, body = recv_frame(ShortReadSock(frame, step=4))
        assert am_id == AmId.MAPPER_INFO
        assert bytes(header) == b"hdr" and bytes(body) == b"body-bytes"


class TestSendmsgAll:
    def test_partial_sends_preserve_stream(self):
        parts = [memoryview(bytes([i]) * (10 + i)) for i in range(7)]
        sock = PartialSendSock(step=5)
        BlockServer._sendmsg_all(sock, list(parts))
        assert bytes(sock.out) == b"".join(bytes(p) for p in parts)

    def test_iov_window_beyond_1024(self):
        parts = [b"a"] * 1500 + [b"bc"]
        sock = PartialSendSock(step=64)
        BlockServer._sendmsg_all(sock, parts)
        assert bytes(sock.out) == b"a" * 1500 + b"bc"


# ---------------------------------------------------------------------------
# chunk-frame protocol
# ---------------------------------------------------------------------------


class TestChunkProtocol:
    def test_chunk_header_roundtrip(self):
        hdr = pack_chunk_hdr(2**40, 7, 123, 2**33 + 5)
        assert unpack_chunk_hdr(hdr) == (2**40, 7, 123, 2**33 + 5)

    def test_hello_roundtrip(self):
        hdr = pack_wire_hello(2**63 + 1, 3, 4, 1 << 20)
        assert unpack_wire_hello(hdr) == (2**63 + 1, 3, 4, 1 << 20)

    def test_am_ids_pinned(self):
        # wire constants: renumbering is a protocol break
        assert int(AmId.FETCH_BLOCK_CHUNK) == 5
        assert int(AmId.WIRE_HELLO) == 6
        assert int(AmId.REPLICA_PUT) == 7
        assert int(AmId.REPLICA_ACK) == 8
        assert int(AmId.MEMBER_SUSPECT) == 9
        assert int(AmId.MEMBER_REJOIN) == 10

    def test_member_event_roundtrip(self):
        from sparkucx_tpu.core.definitions import (
            pack_member_event,
            unpack_member_event,
        )

        hdr = pack_member_event(2**40, 7, 3)
        assert unpack_member_event(hdr) == (2**40, 7, 3)


# ---------------------------------------------------------------------------
# streams=1 bit-equality pin (raw golden bytes on a real socket)
# ---------------------------------------------------------------------------


class TestSingleLaneBitEquality:
    def test_fetch_reply_bytes_pinned(self):
        """A streams=1 fetch reply must be EXACTLY the pre-striping frame:
        one FETCH_BLOCK_REQ_ACK, header=[tag, count, sizes], body=concat —
        no chunk frames, no manifest split."""
        payloads = [b"alpha-block", b"", b"g" * 4097]
        srv = BlockServer(TpuShuffleConf())
        lookup = {}
        for i, p in enumerate(payloads):
            lookup[ShuffleBlockId(9, i, 0)] = BytesBlock(p)
        srv.registry_lookup = lookup.get
        try:
            sock = socket.create_connection(srv.address, timeout=10)
            bids = list(lookup)
            req = pack_frame(AmId.FETCH_BLOCK_REQ, pack_batch_fetch_req(77, bids))
            sock.sendall(req)
            hdr = recv_exact(sock, FRAME_HEADER_SIZE)
            am_id, hlen, blen = unpack_frame_header(hdr)
            header = recv_exact(sock, hlen)
            body = recv_exact(sock, blen)
            # golden reply, constructed by hand from the documented layout
            golden_hdr = (
                _TAG.pack(77)
                + _COUNT.pack(3)
                + b"".join(_SIZE.pack(len(p)) for p in payloads)
            )
            assert am_id == AmId.FETCH_BLOCK_REQ_ACK
            assert bytes(header) == golden_hdr
            assert bytes(body) == b"".join(payloads)
            sock.close()
        finally:
            srv.close()

    def test_request_bytes_pinned(self):
        """The client request frame layout is pinned byte-for-byte."""
        bids = [ShuffleBlockId(1, 2, 3), ShuffleBlockId(4, 5, 6)]
        golden = (
            struct.pack("<IQQ", 3, 4 + 8 + 2 * 12, 0)
            + _TAG.pack(9)
            + _COUNT.pack(2)
            + struct.pack("<iii", 1, 2, 3)
            + struct.pack("<iii", 4, 5, 6)
        )
        assert pack_frame(AmId.FETCH_BLOCK_REQ, pack_batch_fetch_req(9, bids)) == golden

    def test_single_lane_emits_no_stripe_ams(self):
        """With wire.streams=1 the client opens a plain connection: no
        WIRE_HELLO handshake, so the server never forms a stripe group."""
        a, b = _pair(streams=1)
        try:
            bid = ShuffleBlockId(0, 0, 0)
            b.register(bid, BytesBlock(b"plain"))
            buf = _buf(16)
            reqs = a.fetch_blocks_by_block_ids(2, [bid], [buf], [None])
            _drive(a, reqs)
            assert reqs[0].wait(0).status == OperationStatus.SUCCESS
            assert b.server._groups == {}  # no hello ever arrived
        finally:
            a.close()
            b.close()


# ---------------------------------------------------------------------------
# striped fetch: oracle vs single-frame path
# ---------------------------------------------------------------------------


def _fetch_all(streams, payloads, chunk_bytes=64 << 10, missing=()):
    a, b = _pair(streams=streams, chunk_bytes=chunk_bytes)
    try:
        bids = []
        for i, p in enumerate(payloads):
            bid = ShuffleBlockId(0, i, 0)
            if i not in missing:
                b.register(bid, BytesBlock(p))
            bids.append(bid)
        bufs = [_buf(max(len(p), 1)) for p in payloads]
        reqs = a.fetch_blocks_by_block_ids(2, bids, bufs, [None] * len(bids))
        _drive(a, reqs)
        out = []
        for p, buf, r in zip(payloads, bufs, reqs):
            res = r.wait(0)
            if res.status == OperationStatus.SUCCESS:
                out.append(bytes(buf.host_view()[: res.stats.recv_size].tobytes()))
            else:
                out.append(None)
        return out
    finally:
        a.close()
        b.close()


class TestStripedOracle:
    PAYLOADS = [
        np.random.default_rng(3).integers(0, 256, size=n, dtype=np.uint8).tobytes()
        for n in (1 << 20, 3 * (1 << 18) + 17, 5, 1, 1 << 16)
    ]

    @pytest.mark.parametrize("streams", [2, 4])
    def test_striped_matches_single_frame(self, streams):
        oracle = _fetch_all(1, self.PAYLOADS)
        got = _fetch_all(streams, self.PAYLOADS)
        assert got == oracle

    def test_striped_with_missing_blocks(self):
        oracle = _fetch_all(1, self.PAYLOADS, missing={1, 3})
        got = _fetch_all(4, self.PAYLOADS, missing={1, 3})
        assert got == oracle
        assert got[1] is None and got[3] is None

    def test_chunk_smaller_than_block(self):
        # many chunks per block, odd remainder chunk
        p = [bytes(range(256)) * 600]  # 150 KiB
        assert _fetch_all(4, p, chunk_bytes=4096) == _fetch_all(1, p)

    def test_dead_server_fails_striped_batch(self):
        a, b = _pair(streams=4)
        try:
            bid = ShuffleBlockId(0, 0, 0)
            b.register(bid, BytesBlock(b"x" * 1024))
            buf = _buf(1024)
            reqs = a.fetch_blocks_by_block_ids(2, [bid], [buf], [None])
            _drive(a, reqs)  # establish group + one good fetch
            b.server.close()  # server gone: all lanes die
            buf2 = _buf(1024)
            reqs2 = a.fetch_blocks_by_block_ids(2, [bid], [buf2], [None])
            _drive(a, reqs2)
            assert reqs2[0].wait(0).status == OperationStatus.FAILURE
        finally:
            a.close()
            b.close()


# ---------------------------------------------------------------------------
# stripe reassembly under deliberately shuffled lane interleaving
# ---------------------------------------------------------------------------


class TestStripeReassembly:
    """Drive the transport's chunk/manifest callbacks directly — the exact
    code lane recv threads run — in adversarial orderings."""

    def _seed(self, a, tag, sizes):
        reqs = [Request(OperationStats()) for _ in sizes]
        bufs = [_buf(n) for n in sizes]
        with a._tag_lock:
            a._inflight[tag] = (reqs, bufs, [None] * len(sizes), None)
            a._stripe_rx[tag] = _StripeRx()
        return reqs, bufs

    def _manifest_hdr(self, tag, sizes):
        return (
            _TAG.pack(tag)
            + _COUNT.pack(len(sizes))
            + b"".join(_SIZE.pack(s) for s in sizes)
        )

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    @pytest.mark.parametrize("manifest_at", ["first", "middle", "last"])
    def test_shuffled_interleavings_complete_once(self, seed, manifest_at):
        a = PeerTransport(TpuShuffleConf(), executor_id=1)
        try:
            rng = random.Random(seed)
            payloads = [bytes([i]) * n for i, n in enumerate((5000, 0, 1, 12345))]
            sizes = [len(p) for p in payloads]
            tag = 1000 + seed
            reqs, bufs = self._seed(a, tag, [max(n, 1) for n in sizes])
            chunk = 512
            events = []
            for blk, p in enumerate(payloads):
                for off in range(0, len(p), chunk):
                    events.append(("chunk", blk, off, p[off : off + chunk]))
            rng.shuffle(events)
            idx = {"first": 0, "middle": len(events) // 2, "last": len(events)}[manifest_at]
            events.insert(idx, ("manifest",))
            completions = []
            for ev in events:
                if ev[0] == "manifest":
                    done = a._on_manifest(self._manifest_hdr(tag, sizes))
                else:
                    _, blk, off, data = ev
                    mv = a._chunk_buffers(tag, blk, off, len(data))
                    assert mv is not None
                    mv[:] = data
                    done = a._chunk_done(tag, len(data), True)
                if done is not None:
                    completions.append(done)
            assert len(completions) == 1  # completes exactly once
            assert a._stripe_rx == {}  # accounting fully drained
            assert a._scattering == {}
            a._handle_frame((AmId.FETCH_BLOCK_REQ_ACK, completions[0], b"", True))
            for p, buf, req in zip(payloads, bufs, reqs):
                res = req.wait(0)
                assert res.status == OperationStatus.SUCCESS
                assert buf.host_view()[: len(p)].tobytes() == p
        finally:
            a.close()

    def test_unknown_tag_chunk_is_drained_not_scattered(self):
        a = PeerTransport(TpuShuffleConf(), executor_id=1)
        try:
            assert a._chunk_buffers(999, 0, 0, 64) is None
            assert a._chunk_done(999, 64, False) is None  # no rx state: ignored
        finally:
            a.close()

    def test_oversized_chunk_rejected(self):
        a = PeerTransport(TpuShuffleConf(), executor_id=1)
        try:
            tag = 5
            self._seed(a, tag, [16])
            # offset+len beyond the result buffer: no view, drained instead
            assert a._chunk_buffers(tag, 0, 8, 16) is None
            assert a._chunk_buffers(tag, 1, 0, 8) is None  # bad block index
            with a._tag_lock:
                assert tag not in a._scattering
        finally:
            a.close()

    def test_scattering_counter_survives_concurrent_lanes(self):
        """Two lanes scattering one tag: the mark must persist until BOTH
        finish (a set would drop the sibling's mark on first done)."""
        a = PeerTransport(TpuShuffleConf(), executor_id=1)
        try:
            tag = 6
            self._seed(a, tag, [4096])
            mv1 = a._chunk_buffers(tag, 0, 0, 1024)
            mv2 = a._chunk_buffers(tag, 0, 1024, 1024)
            assert mv1 is not None and mv2 is not None
            with a._tag_lock:
                assert a._scattering[tag] == 2
            a._chunk_done(tag, 1024, True)
            with a._tag_lock:
                assert a._scattering[tag] == 1  # sibling still writing
            a._chunk_done(tag, 1024, True)
            with a._tag_lock:
                assert tag not in a._scattering
        finally:
            a.close()


# ---------------------------------------------------------------------------
# credit-budget accounting
# ---------------------------------------------------------------------------


class TestCreditGate:
    def test_never_exceeds_budget(self):
        gate = CreditGate(1000)
        peak = []
        stop = threading.Event()

        def worker():
            rng = random.Random(threading.get_ident())
            while not stop.is_set():
                n = rng.randint(1, 400)
                gate.acquire(n)
                peak.append(gate.used)
                time.sleep(0)
                gate.release(n)

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        time.sleep(0.25)
        stop.set()
        for t in threads:
            t.join()
        assert max(peak) <= 1000
        assert gate.used == 0  # drains to zero

    def test_oversized_request_admitted_alone(self):
        gate = CreditGate(100)
        assert gate.acquire(500, timeout=1.0)  # nothing in flight: admitted
        assert not gate.try_acquire(1)  # and nothing else fits now
        gate.release(500)
        assert gate.used == 0

    def test_acquire_blocks_until_release(self):
        gate = CreditGate(100)
        gate.acquire(80)
        assert not gate.acquire(40, timeout=0.05)  # would exceed: times out
        done = threading.Event()

        def releaser():
            time.sleep(0.05)
            gate.release(80)
            done.set()

        threading.Thread(target=releaser).start()
        assert gate.acquire(40, timeout=2.0)
        done.wait(2.0)
        gate.release(40)
        assert gate.used == 0

    def test_stall_time_accounted(self):
        gate = CreditGate(10)
        gate.acquire(10)
        threading.Timer(0.05, gate.release, args=(10,)).start()
        gate.acquire(5, timeout=2.0)
        assert gate.stall_ns >= 25_000_000  # waited at least ~25ms

    def test_invalid_budget_rejected(self):
        with pytest.raises(ValueError):
            CreditGate(0)


class TestReaderCreditPipelining:
    def _reader(self, transport, pool, credit_bytes, sizes):
        return TpuShuffleReader(
            transport,
            executor_id=1,
            shuffle_id=0,
            start_partition=0,
            end_partition=1,
            num_mappers=len(sizes),
            block_sizes=lambda m, r: sizes[m],
            max_blocks_per_request=2,
            pool=pool,
            sender_of=lambda m: 2,
            credit_bytes=credit_bytes,
        )

    @pytest.mark.parametrize("credit_bytes", [0, 4096, 1 << 30])
    def test_pipelined_stream_matches_serial(self, credit_bytes):
        payloads = [bytes([40 + i]) * (100 + 512 * i) for i in range(9)]
        sizes = [len(p) for p in payloads]
        a, b = _pair(streams=1)
        pool = MemoryPool(TpuShuffleConf())
        try:
            for i, p in enumerate(payloads):
                b.register(ShuffleBlockId(0, i, 0), BytesBlock(p))
            reader = self._reader(a, pool, credit_bytes, sizes)
            got = []
            for blk in reader.fetch_blocks():
                got.append(bytes(blk.data))
                blk.release()
            assert got == payloads  # window order, every byte intact
            assert reader.metrics.remote_blocks_fetched == len(payloads)
            assert reader.metrics.remote_bytes_read == sum(sizes)
            # bytes that arrive from elsewhere: a pooled buffer a block, none borrowed
            assert (reader.metrics.copied_blocks, reader.metrics.resident_blocks) == (len(payloads), 0)
            assert sum(s["requests"] for s in pool.stats().values()) == len(payloads)
        finally:
            a.close()
            b.close()
            pool.close()

    def test_pipelined_over_striped_wire(self):
        payloads = [bytes([i]) * (1 << 16) for i in range(8)]
        sizes = [len(p) for p in payloads]
        a, b = _pair(streams=4, chunk_bytes=8192)
        pool = MemoryPool(TpuShuffleConf())
        try:
            for i, p in enumerate(payloads):
                b.register(ShuffleBlockId(0, i, 0), BytesBlock(p))
            reader = self._reader(a, pool, 1 << 17, sizes)
            got = [bytes(blk.data) for blk in reader.fetch_blocks()]
            assert got == payloads
        finally:
            a.close()
            b.close()
            pool.close()


# ---------------------------------------------------------------------------
# sanitizer-enabled pooled-rx release contract + batch checkout
# ---------------------------------------------------------------------------


class TestPooledRxRelease:
    def test_release_contract_under_sanitizer(self):
        """Fetched pooled blocks released by the consumer must recycle
        cleanly, and use-after-release must raise under sanitize mode."""
        payloads = [b"first-block-payload", b"second" * 100]
        a, b = _pair(streams=1)
        pool = MemoryPool(TpuShuffleConf(sanitize=True))
        try:
            for i, p in enumerate(payloads):
                b.register(ShuffleBlockId(0, i, 0), BytesBlock(p))
            reader = TpuShuffleReader(
                a, 1, 0, 0, 1, 2,
                block_sizes=lambda m, r: len(payloads[m]),
                pool=pool,
                sender_of=lambda m: 2,
                credit_bytes=1 << 20,
            )
            it = reader.fetch_blocks()
            blk = next(it)
            assert bytes(blk.data) == payloads[0]
            blk.release()
            with pytest.raises(SanitizerError, match="use-after-release"):
                _ = blk.data
            blk.release()  # idempotent in sanitize mode too
            rest = list(it)
            assert bytes(rest[-1].data) == payloads[-1]  # detached: still valid
        finally:
            a.close()
            b.close()
            pool.close()

    def test_get_many_order_sizes_and_recycle(self):
        pool = MemoryPool(TpuShuffleConf(sanitize=True))
        sizes = [100, 5000, 100, 64, 5000]
        blocks = pool.get_many(sizes)
        assert [b.size for b in blocks] == sizes
        assert len({id(b) for b in blocks}) == len(blocks)
        views = [b.host_view() for b in blocks]
        for i, v in enumerate(views):
            v[: sizes[i]] = i  # distinct backing storage
        for i, v in enumerate(views):
            assert (v[: sizes[i]] == i).all()
        del views
        for b in blocks:
            b.close()
        pool.close()  # no leaked slabs -> no ResourceWarning

    def test_get_many_rejects_bad_size(self):
        pool = MemoryPool(TpuShuffleConf())
        with pytest.raises(ValueError):
            pool.get_many([64, 0])
        pool.close()


# ---------------------------------------------------------------------------
# wire timeouts (spark.shuffle.tpu.wire.timeoutMs) — stalled peers die at the
# deadline instead of blocking a lane forever; idle connections are exempt
# ---------------------------------------------------------------------------


class TestWireTimeouts:
    def test_server_times_out_hung_midframe_client(self):
        """A client that stalls mid-frame-header is cut loose at the timeout
        (strict mid-frame read); an idle client that sent nothing is not."""
        srv = BlockServer(TpuShuffleConf(wire_timeout_ms=200))
        try:
            idle = socket.create_connection(srv.address, timeout=10)
            hung = socket.create_connection(srv.address, timeout=10)
            hung.sendall(b"\x01\x00\x00")  # 3 of 20 header bytes, then silence
            hung.settimeout(5)
            assert hung.recv(1) == b""  # server closed the hung conn
            hung.close()
            # the idle conn (zero bytes sent) must still be alive and serving
            time.sleep(0.3)  # well past wire_timeout_ms
            idle.sendall(
                pack_frame(AmId.FETCH_BLOCK_REQ, pack_batch_fetch_req(5, [ShuffleBlockId(0, 0, 0)]))
            )
            hdr = recv_exact(idle, FRAME_HEADER_SIZE)
            assert hdr is not None  # got a reply: conn survived idling
            idle.close()
        finally:
            srv.close()

    def test_client_times_out_midbody_with_addressed_error(self):
        """A server that stalls mid-ack-body fails the fetch at the client's
        timeout, and the error names the peer address and fetch tag."""
        lst = socket.socket()
        lst.bind(("127.0.0.1", 0))
        lst.listen(1)
        addr = lst.getsockname()

        def stalling_server():
            conn, _ = lst.accept()
            hdr = recv_exact(conn, FRAME_HEADER_SIZE)
            _, hlen, blen = unpack_frame_header(hdr)
            req_hdr = recv_exact(conn, hlen + blen)
            tag = _TAG.unpack_from(req_hdr)[0]
            # ack claims a 1000 B body but only 100 B ever arrive
            ack_hdr = _TAG.pack(tag) + _COUNT.pack(1) + _SIZE.pack(1000)
            conn.sendall(
                struct.pack("<IQQ", int(AmId.FETCH_BLOCK_REQ_ACK), len(ack_hdr), 1000)
                + ack_hdr
                + b"\x55" * 100
            )
            time.sleep(3)  # hold the socket open, never send the rest
            conn.close()

        t = threading.Thread(target=stalling_server, daemon=True)
        t.start()
        a = PeerTransport(TpuShuffleConf(wire_timeout_ms=200), executor_id=1)
        try:
            a.add_executor(9, f"{addr[0]}:{addr[1]}".encode())
            buf = _buf(1000)
            t0 = time.monotonic()
            [req] = a.fetch_blocks_by_block_ids(9, [ShuffleBlockId(0, 0, 0)], [buf], [None])
            _drive(a, [req], timeout=10)
            res = req.wait(1)
            assert res.status == OperationStatus.FAILURE
            assert "127.0.0.1" in str(res.error)  # peer named, not a bare reset
            assert time.monotonic() - t0 < 2.5  # timeout fired, no 3 s stall
        finally:
            a.close()
            lst.close()
            t.join(timeout=10)


# ---------------------------------------------------------------------------
# chaos on the striped wire (fault harness): reset mid-fetch, stalled lane
# ---------------------------------------------------------------------------


class TestChaosLanes:
    @pytest.fixture(autouse=True)
    def _clean_faults(self):
        from sparkucx_tpu.testing import faults

        faults.reset()
        yield
        faults.reset()

    def test_midfetch_reset_recovers_without_data_loss(self):
        """Severing the serving connection mid-fetch (connection reset) kills
        a lane of the stripe group; the reader's retry reforms the group (or
        falls back to a fresh connection) and every byte still arrives."""
        from sparkucx_tpu.testing import faults

        payloads = [bytes([i]) * (1 << 16) for i in range(6)]
        a, b = _pair(streams=4, chunk_bytes=8192)
        try:
            for i, p in enumerate(payloads):
                b.register(ShuffleBlockId(0, i, 0), BytesBlock(p))
            faults.arm(
                "peer.server.frame",
                faults.sever("reset mid-fetch"),
                times=1,
                match={"am_id": int(AmId.FETCH_BLOCK_REQ)},
            )
            reader = TpuShuffleReader(
                a, 1, 0, 0, 1, len(payloads),
                block_sizes=lambda m, r: len(payloads[m]),
                max_blocks_per_request=2,
                sender_of=lambda m: 2,
                fetch_retries=3,
                fetch_backoff_ms=5,
            )
            got = [bytes(blk.data) for blk in reader.fetch_blocks()]
            assert got == payloads  # no data loss through the reset
            assert faults.fired.get("peer.server.frame") == 1  # it DID fire
            assert reader.metrics.blocks_retried >= 1
        finally:
            a.close()
            b.close()

    def test_stalled_lane_times_out_then_retry_succeeds(self):
        """A lane that stalls forever (peer alive but wedged) trips the fetch
        deadline; the reader abandons the window and the retry refetches every
        byte.  Pins timeout-driven failover, not just reset-driven."""
        from sparkucx_tpu.testing import faults

        payloads = [b"stall-me" * 512, b"ok" * 300]
        a, b = _pair(streams=1, wire_timeout_ms=10_000)
        try:
            for i, p in enumerate(payloads):
                b.register(ShuffleBlockId(0, i, 0), BytesBlock(p))
            # wedge the server for the first fetch request only: the client
            # sees silence (not EOF), so only the deadline can save the window
            # the serve thread is wedged 1 s; retries starve on the same conn
            # until it wakes, so the retry budget (4 x 400 ms) must outlast it
            faults.arm(
                "peer.server.frame",
                faults.stall(1.0),
                times=1,
                match={"am_id": int(AmId.FETCH_BLOCK_REQ)},
            )
            reader = TpuShuffleReader(
                a, 1, 0, 0, 1, len(payloads),
                block_sizes=lambda m, r: len(payloads[m]),
                max_blocks_per_request=len(payloads),
                sender_of=lambda m: 2,
                fetch_retries=3,
                fetch_deadline_ms=400,
                fetch_backoff_ms=5,
            )
            t0 = time.monotonic()
            got = [bytes(blk.data) for blk in reader.fetch_blocks()]
            assert got == payloads
            assert reader.metrics.fetch_timeouts >= 1  # deadline actually fired
            assert time.monotonic() - t0 < 8  # bounded, not wedged
        finally:
            a.close()
            b.close()


# ---------------------------------------------------------------------------
# wire.checksum: CRC32C integrity on the striped wire (elasticity PR)
# ---------------------------------------------------------------------------


class TestCrc32c:
    def test_known_vectors(self):
        """google/crc32c reference vectors: byte-compatibility with every
        hardware implementation is the whole point of picking Castagnoli."""
        from sparkucx_tpu.utils.checksum import crc32c

        assert crc32c(b"") == 0x00000000
        assert crc32c(b"a") == 0xC1D04330
        assert crc32c(b"abc") == 0x364B3FB7
        assert crc32c(b"123456789") == 0xE3069283
        # the iSCSI 32x zero-byte vector (RFC 3720 B.4)
        assert crc32c(b"\x00" * 32) == 0x8A9136AA

    def test_incremental_matches_oneshot(self):
        from sparkucx_tpu.utils.checksum import crc32c

        data = bytes(range(256)) * 5
        assert crc32c(data[128:], crc32c(data[:128])) == crc32c(data)

    def test_detects_single_bit_flip(self):
        from sparkucx_tpu.utils.checksum import crc32c

        data = bytearray(b"x" * 100)
        want = crc32c(bytes(data))
        data[50] ^= 0x01
        assert crc32c(bytes(data)) != want


class TestWireChecksum:
    def test_checksum_off_frames_are_golden(self):
        """Knob off (the default): chunk headers carry NO crc trailer — the
        striped wire stays byte-identical to the pre-checksum protocol."""
        from sparkucx_tpu.core.definitions import CHUNK_HEADER_SIZE

        a, b = _pair(streams=2, chunk_bytes=512)
        try:
            assert not a.conf.wire_checksum
            bid = ShuffleBlockId(0, 0, 0)
            b.register(bid, BytesBlock(b"p" * 2000))
            seen = []
            orig = a._chunk_done

            def spy(tag, nbytes, scattered):
                seen.append(nbytes)
                return orig(tag, nbytes, scattered)

            a._chunk_done = spy
            buf = _buf(2048)
            reqs = a.fetch_blocks_by_block_ids(2, [bid], [buf], [None])
            _drive(a, reqs)
            assert reqs[0].wait(0).status == OperationStatus.SUCCESS
            assert seen, "no chunks arrived"
            # header-length detection is the protocol: knob off means every
            # header is exactly CHUNK_HEADER_SIZE (spy proves chunks flowed)
            assert CHUNK_HEADER_SIZE == 24
        finally:
            a.close()
            b.close()

    @pytest.mark.parametrize("streams", [2, 4])
    def test_checksum_on_clean_fetch(self, streams):
        payload = bytes(np.random.default_rng(5).integers(0, 256, 6000, dtype=np.uint8))
        a, b = _pair(streams=streams, chunk_bytes=1024, wire_checksum=True)
        try:
            bid = ShuffleBlockId(3, 0, 0)
            b.register(bid, BytesBlock(payload))
            buf = _buf(8192)
            reqs = a.fetch_blocks_by_block_ids(2, [bid], [buf], [None])
            _drive(a, reqs)
            res = reqs[0].wait(0)
            assert res.status == OperationStatus.SUCCESS, str(res.error)
            assert bytes(res.data.host_view()[: res.data.size]) == payload
        finally:
            a.close()
            b.close()

    def test_corrupted_chunk_raises_block_corrupt(self):
        """Payload garbled in flight (after the crc was computed) must surface
        as a typed BlockCorruptError, not silent garbage or a generic loss."""
        from sparkucx_tpu.core.operation import BlockCorruptError
        from sparkucx_tpu.testing import faults

        a, b = _pair(streams=2, chunk_bytes=1024, wire_checksum=True)
        try:
            bid = ShuffleBlockId(4, 0, 0)
            b.register(bid, BytesBlock(b"q" * 4000))
            faults.arm("peer.server.chunk", faults.garble(), times=1)
            buf = _buf(4096)
            reqs = a.fetch_blocks_by_block_ids(2, [bid], [buf], [None])
            _drive(a, reqs)
            res = reqs[0].wait(0)
            assert res.status == OperationStatus.FAILURE
            assert isinstance(res.error, BlockCorruptError), type(res.error)
            assert "crc32c" in str(res.error)
        finally:
            faults.reset()
            a.close()
            b.close()

    def test_corruption_failover_to_replica(self):
        """End to end: a corrupt primary fetch fails its lane, and the
        reader's retry failover refetches the block from the replica holder —
        'bytes arrived but are wrong' heals exactly like 'peer died'."""
        from sparkucx_tpu.testing import faults

        payloads = [b"heal-me" * 300]
        a, b = _pair(streams=2, chunk_bytes=1024, wire_checksum=True)
        try:
            b.register(ShuffleBlockId(0, 0, 0), BytesBlock(payloads[0]))
            faults.arm("peer.server.chunk", faults.garble(), times=1)
            reader = TpuShuffleReader(
                a, 1, 0, 0, 1, 1,
                block_sizes=lambda m, r: len(payloads[m]),
                sender_of=lambda m: 2,
                fetch_retries=2,
                fetch_backoff_ms=5,
            )
            got = [bytes(blk.data) for blk in reader.fetch_blocks()]
            assert got == payloads
            assert reader.metrics.blocks_retried >= 1
        finally:
            faults.reset()
            a.close()
            b.close()


# ---------------------------------------------------------------------------
# bounded replicator (elasticity PR)
# ---------------------------------------------------------------------------


def _stage_rounds(t, sid, num_reducers=1, seed=0):
    rng = np.random.default_rng(seed)
    t.store.create_shuffle(sid, 1, num_reducers)
    w = t.store.map_writer(sid, 0)
    for r in range(num_reducers):
        w.write_partition(r, rng.integers(0, 256, 300, dtype=np.uint8).tobytes())
    w.commit()


class TestBoundedReplicator:
    def _pair_repl(self, **kw):
        kw.setdefault("staging_capacity_per_executor", 1 << 20)
        kw.setdefault("replication_factor", 1)
        conf = TpuShuffleConf(**kw)
        a = PeerTransport(conf, executor_id=0)
        b = PeerTransport(conf, executor_id=1)
        a.add_executor(1, b.init())
        a.init()
        b.add_executor(0, a.server.address_bytes())
        return a, b

    def test_single_worker_settles_many_seals(self):
        """Thread-per-seal is gone: many seals drain through ONE worker and
        all settle; the backlog gauge returns to zero."""
        from sparkucx_tpu.testing import faults

        a, b = self._pair_repl()
        try:
            for sid in range(5):
                _stage_rounds(a, sid, seed=sid)
                a.store.seal(sid)
            for sid in range(5):
                assert a.replication_wait(sid, timeout=10.0, strict=True)
            assert a.replica_stats["replica_backlog_bytes"] == 0
            assert a.replica_stats["pushed_rounds"] >= 5
        finally:
            a.close()
            b.close()

    def test_backlog_cap_drops_oldest(self):
        """Backlog over replication.maxBacklogBytes: the OLDEST queued shuffle
        is dropped (accounted in dropped_rounds), never an unbounded queue."""
        from sparkucx_tpu.testing import faults

        a, b = self._pair_repl(replication_max_backlog_bytes=1)
        try:
            faults.arm("replica.push", faults.stall(0.5))
            with a._tag_lock:  # simulate a stuck backlog from a slow successor
                a.replica_stats["replica_backlog_bytes"] = 10
            for sid in (21, 22, 23):
                _stage_rounds(a, sid, seed=sid)
                a.store.seal(sid)
            deadline = time.monotonic() + 3
            while a.replica_stats["dropped_rounds"] < 1 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert a.replica_stats["dropped_rounds"] >= 1
            faults.reset()
            with a._tag_lock:
                a.replica_stats["replica_backlog_bytes"] = 0
        finally:
            faults.reset()
            a.close()
            b.close()

    def test_strict_wait_names_stalled_successor(self):
        """An ack lost mid-apply leaves the push unsettled; strict wait raises
        a TransportError NAMING the successor whose acks never came."""
        from sparkucx_tpu.core.operation import TransportError
        from sparkucx_tpu.testing import faults

        a, b = self._pair_repl()
        try:
            faults.arm("replica.apply", faults.sever(), times=1)
            _stage_rounds(a, 5)
            a.store.seal(5)
            with pytest.raises(TransportError, match=r"successor executor\(s\) \[1\]"):
                a.replication_wait(5, timeout=0.7, strict=True)
        finally:
            faults.reset()
            a.close()
            b.close()

    def test_replica_put_checksum_discards_corrupt_round(self):
        """A REPLICA_PUT whose crc trailer does not match its body is
        discarded — no replica installed, no ack — and the serving thread
        survives to install the next (valid) round.  The trailer is detected
        by header length, so the receiver needs no conf agreement with the
        pusher (hand-crafted frames over a raw socket prove it)."""
        from sparkucx_tpu.core.definitions import pack_replica_put
        from sparkucx_tpu.utils.checksum import crc32c

        a, b = self._pair_repl()
        sock = None
        try:
            body = b"replica-round-payload" * 16
            sock = socket.create_connection(b.server.address, timeout=10)
            # round 0 targets (map 0, reduce 0) with a deliberately wrong crc
            bad = pack_replica_put(9, 0, 0, [(0, 0, len(body))]) + struct.pack(
                "<I", crc32c(body) ^ 0xDEADBEEF
            )
            sock.sendall(pack_frame(AmId.REPLICA_PUT, bad, body))
            # round 1 targets (map 0, reduce 1) with a valid crc
            good = pack_replica_put(9, 0, 1, [(0, 1, len(body))]) + struct.pack(
                "<I", crc32c(body)
            )
            sock.sendall(pack_frame(AmId.REPLICA_PUT, good, body))
            # the first (and only) ack on the wire is for the VALID round:
            # the corrupt one produced no ack, and the conn survived it
            hdr = recv_exact(sock, FRAME_HEADER_SIZE)
            am_id, hlen, blen = unpack_frame_header(hdr)
            recv_exact(sock, hlen + blen)
            assert am_id == AmId.REPLICA_ACK
            assert b.store.replica_view(9, 0, 0) is None
            assert b.store.replica_view(9, 0, 1) is not None
        finally:
            if sock is not None:
                sock.close()
            a.close()
            b.close()

    def test_checksum_on_replica_roundtrip(self):
        """Clean wire with checksum on: replicas install and ack normally."""
        a, b = self._pair_repl(wire_checksum=True)
        try:
            _stage_rounds(a, 12)
            a.store.seal(12)
            assert a.replication_wait(12, timeout=10.0, strict=True)
            assert b.store.replica_view(12, 0, 0) is not None
        finally:
            a.close()
            b.close()


# ---------------------------------------------------------------------------
# compress.codec: per-chunk page codecs on the striped wire (compression PR)
# ---------------------------------------------------------------------------


def _compressible_payloads():
    """Exchange-shaped payloads (u32 words: low-cardinality keys, runs,
    near-sequential columns) plus noise, empties, and sub-chunk blocks —
    every fallback path of the codec ext in one batch."""
    rng = np.random.default_rng(11)
    alpha = rng.integers(0, 50, size=1 << 15, dtype=np.uint64).astype("<u4")
    return [
        alpha.tobytes(),  # dictionary/rle-friendly
        bytes(1 << 16),  # zero runs
        (np.uint32(7) + np.cumsum(
            rng.integers(0, 9, size=1 << 14), dtype=np.int64
        ).astype(np.uint32)).astype("<u4").tobytes(),  # delta-friendly
        rng.integers(0, 256, size=(1 << 15) + 17, dtype=np.uint8).tobytes(),  # noise
        b"",  # empty block
        b"tiny",  # under the min-chunk gate
    ]


class TestWireCompression:
    def test_codec_wire_constants_pinned(self):
        """Codec ids and the chunk-header extension are wire format —
        renumbering or re-packing is a protocol break."""
        from sparkucx_tpu.core.definitions import (
            CHUNK_CODEC_EXT_SIZE,
            CHUNK_HEADER_SIZE,
            pack_chunk_codec_ext,
        )
        from sparkucx_tpu.utils.pagecodec import (
            CODEC_DELTA,
            CODEC_DICT,
            CODEC_RAW,
            CODEC_RLE,
        )

        assert (CODEC_RAW, CODEC_DICT, CODEC_RLE, CODEC_DELTA) == (0, 1, 2, 3)
        assert CHUNK_CODEC_EXT_SIZE == 8
        assert pack_chunk_codec_ext(2, 4096) == struct.pack("<II", 2, 4096)
        # header-length detection table: 24 plain, +8 codec, +4 crc (crc LAST)
        assert CHUNK_HEADER_SIZE == 24
        assert unpack_chunk_hdr(pack_chunk_hdr(9, 1, 2, 3) + pack_chunk_codec_ext(1, 8)) == (9, 1, 2, 3)

    def test_default_is_off(self):
        """codec=off is the default, keeping the golden frames above (single
        lane AND striped) byte-identical to the pre-compression protocol."""
        assert TpuShuffleConf().wire_compress_codec == "off"
        assert TpuShuffleConf().compress_min_chunk_bytes == 4096

    @pytest.mark.parametrize("codec", ["dict", "rle", "delta"])
    @pytest.mark.parametrize("streams", [1, 4])
    def test_compressed_fetch_matches_stock(self, codec, streams):
        """Oracle: a compressed fetch returns byte-for-byte what the stock
        (codec=off) wire returns, for every payload shape and lane count —
        including the raw-fallback and sub-chunk-gate paths."""
        payloads = _compressible_payloads()
        oracle = _fetch_all(1, payloads)

        a, b = _pair(
            streams=streams, chunk_bytes=16 << 10, wire_compress_codec=codec
        )
        try:
            bids = []
            for i, p in enumerate(payloads):
                bid = ShuffleBlockId(0, i, 0)
                b.register(bid, BytesBlock(p))
                bids.append(bid)
            bufs = [_buf(max(len(p), 1)) for p in payloads]
            reqs = a.fetch_blocks_by_block_ids(2, bids, bufs, [None] * len(bids))
            _drive(a, reqs)
            got = []
            for p, buf, r in zip(payloads, bufs, reqs):
                res = r.wait(0)
                assert res.status == OperationStatus.SUCCESS, str(res.error)
                got.append(bytes(buf.host_view()[: res.stats.recv_size].tobytes()))
            assert got == oracle
            snap = b.server.compress_snapshot()
            assert snap["encoded_chunks"] >= 1  # compression actually engaged
            assert snap["raw_chunks"] >= 1  # and the noise block fell back raw
            assert snap["wire_bytes"] < snap["raw_bytes"]
        finally:
            a.close()
            b.close()

    @pytest.mark.parametrize("checksum", [False, True])
    def test_garbled_compressed_chunk_raises_block_corrupt(self, checksum):
        """A compressed chunk garbled in flight surfaces as the SAME typed
        BlockCorruptError on both detection paths: the crc trailer when
        checksum is on (it covers the ENCODED bytes, so it fires before the
        decoder parses anything), the decoder's CodecError otherwise."""
        from sparkucx_tpu.core.operation import BlockCorruptError
        from sparkucx_tpu.testing import faults

        a, b = _pair(
            streams=2, chunk_bytes=1024,
            wire_compress_codec="rle", wire_checksum=checksum,
        )
        try:
            bid = ShuffleBlockId(4, 0, 0)
            b.register(bid, BytesBlock(bytes(64 << 10)))  # zeros: always encodes
            faults.arm("peer.server.chunk", faults.garble(), times=1)
            buf = _buf(64 << 10)
            reqs = a.fetch_blocks_by_block_ids(2, [bid], [buf], [None])
            _drive(a, reqs)
            res = reqs[0].wait(0)
            assert res.status == OperationStatus.FAILURE
            assert isinstance(res.error, BlockCorruptError), type(res.error)
            if checksum:
                assert "crc32c" in str(res.error)
        finally:
            faults.reset()
            a.close()
            b.close()

    def test_corruption_failover_heals_compressed_fetch(self):
        """End to end on the compressed wire: the decode failure kills the
        lane, and the reader's retry refetches the block intact — corruption
        enters the same failover path as a dead peer."""
        from sparkucx_tpu.testing import faults

        payloads = [bytes(16 << 10)]
        a, b = _pair(streams=2, chunk_bytes=1024, wire_compress_codec="rle")
        try:
            b.register(ShuffleBlockId(0, 0, 0), BytesBlock(payloads[0]))
            faults.arm("peer.server.chunk", faults.garble(), times=1)
            reader = TpuShuffleReader(
                a, 1, 0, 0, 1, 1,
                block_sizes=lambda m, r: len(payloads[m]),
                sender_of=lambda m: 2,
                fetch_retries=2,
                fetch_backoff_ms=5,
            )
            got = [bytes(blk.data) for blk in reader.fetch_blocks()]
            assert got == payloads
            assert reader.metrics.blocks_retried >= 1
        finally:
            faults.reset()
            a.close()
            b.close()

    def test_single_lane_with_codec_uses_chunk_frames(self):
        """compress.codec on forces the stripe (chunked) path even at
        streams=1 — the codec ext rides chunk headers, which the single-frame
        reply has nowhere to carry."""
        a, b = _pair(streams=1, wire_compress_codec="rle")
        try:
            bid = ShuffleBlockId(0, 0, 0)
            b.register(bid, BytesBlock(bytes(32 << 10)))
            buf = _buf(32 << 10)
            reqs = a.fetch_blocks_by_block_ids(2, [bid], [buf], [None])
            _drive(a, reqs)
            assert reqs[0].wait(0).status == OperationStatus.SUCCESS
            assert b.server._groups, "no stripe group formed for the codec path"
            assert b.server.compress_snapshot()["encoded_chunks"] >= 1
        finally:
            a.close()
            b.close()


class TestReplicaCompression:
    """REPLICA_PUT whole-round page compression: same codec ext, same
    discard-no-ack contract as a crc mismatch."""

    def _pair_repl(self, **kw):
        kw.setdefault("staging_capacity_per_executor", 1 << 20)
        kw.setdefault("replication_factor", 1)
        conf = TpuShuffleConf(**kw)
        a = PeerTransport(conf, executor_id=0)
        b = PeerTransport(conf, executor_id=1)
        a.add_executor(1, b.init())
        a.init()
        b.add_executor(0, a.server.address_bytes())
        return a, b

    def test_compressed_replica_roundtrip(self):
        """A compressible round pushed over a codec-on wire installs the
        exact original bytes on the successor (encode on push, decode on
        install)."""
        a, b = self._pair_repl(wire_compress_codec="rle")
        try:
            payload = bytes(4096)  # zero page: always encodes
            a.store.create_shuffle(31, 1, 1)
            w = a.store.map_writer(31, 0)
            w.write_partition(0, payload)
            w.commit()
            a.store.seal(31)
            assert a.replication_wait(31, timeout=10.0, strict=True)
            view = b.store.replica_view(31, 0, 0)
            assert view is not None
            arr, off, ln = view
            assert ln == len(payload)
            got = arr.reshape(-1).view(np.uint8)[off : off + ln].tobytes()
            assert got == payload
        finally:
            a.close()
            b.close()

    def test_corrupt_codec_round_discarded_no_ack(self):
        """A REPLICA_PUT whose codec ext claims an encoded body that fails to
        decode is discarded without an ack — and the serving thread survives
        to install the next (valid, raw-codec-ext) round.  Hand-crafted
        frames: the receiver needs no conf agreement with the pusher."""
        from sparkucx_tpu.core.definitions import pack_chunk_codec_ext, pack_replica_put
        from sparkucx_tpu.utils.pagecodec import CODEC_RAW, CODEC_RLE

        a, b = self._pair_repl()
        sock = None
        try:
            body = b"replica-round-payload" * 16
            sock = socket.create_connection(b.server.address, timeout=10)
            # round 0: codec ext claims an rle page, body is garbage for it
            bad = pack_replica_put(8, 0, 0, [(0, 0, 64)]) + pack_chunk_codec_ext(
                CODEC_RLE, 64
            )
            sock.sendall(pack_frame(AmId.REPLICA_PUT, bad, body))
            # round 1: raw codec ext with the true length — valid
            good = pack_replica_put(8, 0, 1, [(0, 1, len(body))]) + pack_chunk_codec_ext(
                CODEC_RAW, len(body)
            )
            sock.sendall(pack_frame(AmId.REPLICA_PUT, good, body))
            hdr = recv_exact(sock, FRAME_HEADER_SIZE)
            am_id, hlen, blen = unpack_frame_header(hdr)
            recv_exact(sock, hlen + blen)
            assert am_id == AmId.REPLICA_ACK  # first ack is for the VALID round
            assert b.store.replica_view(8, 0, 0) is None
            assert b.store.replica_view(8, 0, 1) is not None
        finally:
            if sock is not None:
                sock.close()
            a.close()
            b.close()


# ---------------------------------------------------------------------------
# tiered eviction x the wire: rounds demoted mid-fetch serve from every tier
# ---------------------------------------------------------------------------


class TestDemoteMidFetch:
    @pytest.mark.parametrize("streams", [1, 2])
    def test_round_demoted_between_windows_bit_identical(self, streams):
        """A sealed round demoted host->disk BETWEEN fetch windows keeps
        serving bit-identically: the next fetch lands on the memmap tier and
        the eviction manager transparently restages the round to RAM
        (service/eviction.py restage-on-fetch), on both the monolithic and
        the striped serve paths."""
        from sparkucx_tpu.service.eviction import EvictionManager

        a, b = _pair(streams=streams)
        try:
            rng = np.random.default_rng(11)
            b.store.create_shuffle(3, 1, 4)
            w = b.store.map_writer(3, 0)
            oracle = {}
            for r in range(4):
                data = rng.integers(0, 256, size=700 + 41 * r, dtype=np.uint8).tobytes()
                oracle[r] = data
                w.write_partition(r, data)
            w.commit()
            b.store.seal(3)
            ev = EvictionManager(b.store)
            b.store.eviction = ev

            def fetch(r):
                buf = _buf(len(oracle[r]))
                req = a.fetch_block(2, 3, 0, r, buf)
                _drive(a, [req])
                res = req.wait(0)
                assert res.status == OperationStatus.SUCCESS, str(res.error)
                return buf.host_view()[: buf.size].tobytes()

            assert fetch(0) == oracle[0]  # served from the resident tier
            while b.store.round_tier(3, 0) != "disk":  # demote mid-stream
                assert b.store.demote_round(3, 0) is not None
            assert fetch(1) == oracle[1]  # cold fetch: restage-on-fetch
            assert b.store.round_tier(3, 0) == "host"
            assert ev.eviction_stats()["restages"] >= 1
            assert fetch(2) == oracle[2]
            assert fetch(3) == oracle[3]
        finally:
            a.close()
            b.close()
