"""A ``WritePartition`` body received straight into staging.

The daemon has the store reserve a body's extent and receives the socket into
it, outside every lock; the client sends header and body without joining
them.  What the configurations state — byte-exact, exactly once — has to hold
across rollover (both arms), spill, seal, remove, a sender that dies or
stalls mid-body, and a partition of several frames, on both serving planes.
The records and the answers are the upstream gate job's
(``benchmark/references/groupby.py``)."""

import json
import queue
import socket
import struct
import sys
import threading
import time
from contextlib import closing

import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.block import ShuffleBlockId
from sparkucx_tpu.core.operation import TransportError
from sparkucx_tpu.shuffle.daemon import DaemonClient, DaemonOp, ShuffleDaemon, _frame, _read_frame
from sparkucx_tpu.store.hbm_store import HbmBlockStore

#: every join and wait of this file; a test that passes takes a second or two
TIMEOUT = 60
PLANES = pytest.mark.parametrize(
    "plane", [{}, {"server_workers": 3}], ids=["thread-a-connection", "reactor"]
)


@pytest.fixture
def switchy():
    """Threads change places often, as on a host with fewer cores than tasks."""
    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    yield
    sys.setswitchinterval(before)


@pytest.fixture
def make_daemon():
    daemons = []

    def make(plane, **conf):
        d = ShuffleDaemon(TpuShuffleConf(**plane, **conf), num_executors=1, port=0)
        daemons.append(d)
        return d

    yield make
    for d in daemons:
        d.close()


def gate_records(mappers=5, seed=31):
    from benchmark.references import groupby

    return groupby.make_records({"mappers": mappers, "pairs_per_mapper": 100, "value_bytes": 25000,
                                 "reducers": 200, "keys": "uniform-int31"}, seed)


def store_of(daemon) -> HbmBlockStore:
    return daemon.manager.cluster.transports[0].store


def run_all(targets, timeout=TIMEOUT):
    threads = [threading.Thread(target=t, daemon=True) for t in targets]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
    assert not any(t.is_alive() for t in threads), "a thread hangs"


def in_time(fn, *args, timeout=TIMEOUT):
    """``fn(*args)`` under a time limit of its own; its result."""
    out = []
    run_all([lambda: out.append(fn(*args))], timeout)
    assert out, f"{fn.__name__} raised"
    return out[0]


def write_maps(daemon, shuffle_id, records, connections, maps=None):
    """Map tasks ``maps`` (all by default) over ``connections`` connections
    at once, each task to the connection that frees first."""
    todo = queue.Queue()
    for m in range(records.num_mappers) if maps is None else maps:
        todo.put(m)
    errors = []

    def slot():
        try:
            with closing(DaemonClient(daemon.address)) as client:
                while True:
                    try:
                        m = todo.get_nowait()
                    except queue.Empty:
                        return
                    writer = client.open_map_writer(shuffle_id, m)
                    for r, payload in records.blocks[m]:
                        client.write_partition(writer, r, payload)
                    client.commit_map(writer)
        except Exception as e:  # the thread's boundary: the test reads it
            errors.append(e)

    run_all([slot] * connections)
    assert not errors, errors


def fetch_all(daemon, shuffle_id, records):
    got = {}
    with closing(DaemonClient(daemon.address)) as client:
        for r in range(records.reducers):
            mappers = records.mappers_of(r)
            payloads = client.fetch_blocks([ShuffleBlockId(shuffle_id, m, r) for m in mappers])
            got.update({(m, r): p for m, p in zip(mappers, payloads)})
    return got


def written(records):
    return {(m, r): payload for m, parts in enumerate(records.blocks) for r, payload in parts}


class RawClient:
    """A connection that sends a frame's bytes in the pieces a test chooses."""

    def __init__(self, daemon):
        self.sock = socket.create_connection(daemon.address, timeout=TIMEOUT)

    def call(self, op, header, body=b""):
        self.sock.sendall(_frame(op, header, body))
        return self.ack()

    def ack(self):
        frame = _read_frame(self.sock)
        assert frame is not None, "the daemon closed the connection"
        return frame[1]

    def send_part(self, op, header, body, upto):
        """The frame's header and the first ``upto`` bytes of its body;
        returns the rest of the body."""
        self.sock.sendall(_frame(op, header, body)[: len(_frame(op, header)) + upto])
        return body[upto:]

    def closed_by_peer(self):
        """True once the daemon has closed this connection (EOF or reset)."""
        try:
            return self.sock.recv(1) == b""
        except ConnectionError:
            return True

    def close(self):
        self.sock.close()


@PLANES
def test_four_connections_write_across_ram_rollovers(make_daemon, plane, switchy):
    """(a) Five map tasks of 2.5 MB from four connections into a 4 MiB round
    that rolls over several times on the RAM arm: every block is recorded
    where its socket put it, no copy is timed, and all read back exact."""
    daemon = make_daemon(plane, staging_capacity_per_executor=4 << 20)
    records = gate_records()
    with closing(DaemonClient(daemon.address)) as driver:
        driver.create_shuffle(0, records.num_mappers, records.reducers)
        write_maps(daemon, 0, records, connections=4)
        stats = store_of(daemon).write_stats()
        assert stats["rollovers"] >= 2 and stats["ram_rounds"] == stats["rollovers"]
        assert stats["inplace_blocks"] == stats["staged_blocks"] == records.num_blocks
        assert stats["inplace_bytes"] == stats["staged_bytes"] == records.total_bytes
        assert stats["inplace_fallbacks"] == 0 and stats["copy_ns"] == 0
        assert stats["inflight_wait_ns"] == 0  # the RAM arm waits for nobody
        assert "sparkucx_tpu_store_inplace_blocks_total" in driver.metrics_text()
        in_time(driver.run_exchange, 0)
        assert fetch_all(daemon, 0, records) == written(records)
        in_time(driver.remove_shuffle, 0)


@PLANES
def test_a_later_job_over_the_daemon_puts_its_rolled_rounds_before_the_exchange(make_daemon, plane, switchy):
    """(a') The same job again on the same daemon: its rounds are written into
    buffers the first gave back, so every round that rolls is put on the
    device by the connection thread whose record found it final (PR 57,
    ``_EarlyRounds``) — a receive still landing in a rolled round holds its
    put — the exchange takes every copy, and all read back exact."""
    daemon = make_daemon(plane, staging_capacity_per_executor=4 << 20)
    records = gate_records()
    with closing(DaemonClient(daemon.address)) as driver:
        for sid in (0, 1):
            driver.create_shuffle(sid, records.num_mappers, records.reducers)
            before = store_of(daemon).write_stats()
            write_maps(daemon, sid, records, connections=4)
            stats = store_of(daemon).write_stats()
            rolled = stats["rollovers"] - before["rollovers"]
            puts = stats["early_round_puts"] - before["early_round_puts"]
            # the last rollover's round may have found no record after it became final
            assert rolled >= 2 and (puts == 0 if sid == 0 else rolled - 1 <= puts <= rolled)
            in_time(driver.run_exchange, sid)
            assert fetch_all(daemon, sid, records) == written(records)
            after = store_of(daemon).write_stats()
            assert after["early_rounds_dropped"] == 0 and after["inplace_fallbacks"] == 0
            assert store_of(daemon)._early_round_bytes == 0
            in_time(driver.remove_shuffle, sid)


@PLANES
def test_a_spill_waits_for_a_slow_senders_body(make_daemon, plane, switchy):
    """(b) No RAM tier, so every rollover spills and reuses the buffer: while
    one sender sits mid-body, the writers that fill the round wait for it at
    the rollover (``inflight_wait_ns``), and nothing is torn."""
    daemon = make_daemon(plane, staging_capacity_per_executor=4 << 20, max_host_pool_bytes=0)
    store, records = store_of(daemon), gate_records()
    slow_map = 0
    (r0, payload0), rest = records.blocks[slow_map][0], records.blocks[slow_map][1:]
    with closing(DaemonClient(daemon.address)) as driver:
        driver.create_shuffle(0, records.num_mappers, records.reducers)
        slow = RawClient(daemon)
        handle = slow.call(DaemonOp.OPEN_MAP_WRITER, {"shuffle_id": 0, "map_id": slow_map})["writer"]
        tail = slow.send_part(DaemonOp.WRITE_PARTITION, {"writer": handle, "reduce_id": r0},
                              payload0, len(payload0) // 2)
        state = store._state(0)
        deadline = time.monotonic() + TIMEOUT
        while not state.inflight and time.monotonic() < deadline:
            time.sleep(0.001)
        assert state.inflight == {0: 1}, "the body's receive never began"

        def finish_slowly():
            deadline = time.monotonic() + TIMEOUT
            while not state.draining and time.monotonic() < deadline:
                time.sleep(0.001)
            assert state.draining, "no rollover ever waited for the body"
            time.sleep(0.05)
            slow.sock.sendall(tail)
            assert slow.ack()["ok"]
            for r, payload in rest:
                assert slow.call(DaemonOp.WRITE_PARTITION, {"writer": handle, "reduce_id": r}, payload)["ok"]
            assert slow.call(DaemonOp.COMMIT_MAP, {"writer": handle})["ok"]

        others = [m for m in range(records.num_mappers) if m != slow_map]
        run_all([finish_slowly, lambda: write_maps(daemon, 0, records, connections=3, maps=others)])
        slow.close()
        stats = store.write_stats()
        assert stats["inflight_wait_ns"] > 0 and stats["spilled_bytes"] > 0 and stats["ram_rounds"] == 0
        assert stats["inplace_blocks"] == records.num_blocks and stats["inplace_fallbacks"] == 0
        assert not state.inflight and not state.draining
        in_time(driver.run_exchange, 0)
        assert fetch_all(daemon, 0, records) == written(records)
        in_time(driver.remove_shuffle, 0)


@PLANES
def test_a_partition_of_several_frames(make_daemon, plane, rng):
    """(c) A partition in three frames with another writer's block landing
    after its first (the extent cannot grow: back to the buffered path, the
    abandoned extent padding), and one in two frames with nothing between
    (grown in place): both contiguous and exact."""
    daemon = make_daemon(plane, staging_capacity_per_executor=1 << 20)
    store = store_of(daemon)
    a0, a1, b0 = (rng.integers(0, 256, size=n, dtype=np.uint8).tobytes() for n in (3001, 5003, 777))
    with closing(DaemonClient(daemon.address)) as a, closing(DaemonClient(daemon.address)) as b:
        a.create_shuffle(0, 2, 2)
        wa, wb = a.open_map_writer(0, 0), b.open_map_writer(0, 1)
        a.write_partition(wa, 0, a0[:1000])
        a.flush()
        b.write_partition(wb, 0, b0)  # lands at the tail a's extent ended at
        b.flush()
        a.write_partition(wa, 0, a0[1000:2000])  # this and the next three: one frame at the commit
        a.write_partition(wa, 0, a0[2000:])
        a.write_partition(wa, 1, a1[:4000])
        a.write_partition(wa, 1, a1[4000:])
        assert a.commit_map(wa).tolist() == [len(a0), len(a1)]
        assert b.commit_map(wb).tolist() == [len(b0), 0]
        stats = store.write_stats()
        assert stats["inplace_fallbacks"] == 1 and stats["copy_ns"] > 0
        assert stats["inplace_blocks"] == 2 and stats["inplace_bytes"] == len(a1) + len(b0)
        assert stats["staged_blocks"] == 3 and stats["staged_bytes"] == len(a0) + len(a1) + len(b0)
        # the abandoned extent (1,000 B in one aligned row of 1,024) is padding
        align = store.conf.block_alignment
        padded = sum(-(-len(x) // align) * align for x in (a0, a1, b0))
        assert int(store._state(0).region_used.sum()) == padded + -(-1000 // align) * align
        a.run_exchange(0)
        bids = [ShuffleBlockId(0, 0, 0), ShuffleBlockId(0, 0, 1), ShuffleBlockId(0, 1, 0)]
        assert a.fetch_blocks(bids) == [a0, a1, b0]
        a.remove_shuffle(0)


@PLANES
@pytest.mark.parametrize("death", ["closes", "stalls"])
def test_a_sender_lost_mid_body_costs_its_connection_only(make_daemon, plane, death):
    """(d) A peer that closes mid-body, and one that stalls past a short
    ``wire_timeout_ms``: that connection drops and the others go on; the
    round's count is given back, so a following rollover (a spill: no RAM
    tier), the seal and the removal do not hang; the retry commits and reads
    back exact; the hole is never served."""
    daemon = make_daemon(plane, staging_capacity_per_executor=4 << 20, max_host_pool_bytes=0,
                         wire_timeout_ms=300)
    store, records = store_of(daemon), gate_records()
    (r0, p0), (r1, p1) = records.blocks[0][:2]
    with closing(DaemonClient(daemon.address)) as driver:
        driver.create_shuffle(0, records.num_mappers, records.reducers)
        doomed = RawClient(daemon)
        handle = doomed.call(DaemonOp.OPEN_MAP_WRITER, {"shuffle_id": 0, "map_id": 0})["writer"]
        assert doomed.call(DaemonOp.WRITE_PARTITION, {"writer": handle, "reduce_id": r0}, p0)["ok"]
        doomed.send_part(DaemonOp.WRITE_PARTITION, {"writer": handle, "reduce_id": r1},
                         b"\xee" * len(p1), len(p1) // 2)
        state = store._state(0)
        deadline = time.monotonic() + TIMEOUT
        while not state.inflight and time.monotonic() < deadline:
            time.sleep(0.001)
        assert state.inflight == {0: 1}
        if death == "closes":
            doomed.close()
        else:
            assert in_time(doomed.closed_by_peer), "the daemon kept a connection that stalled mid-body"
            doomed.close()
        deadline = time.monotonic() + TIMEOUT
        while state.inflight and time.monotonic() < deadline:
            time.sleep(0.001)
        assert not state.inflight, "the lost body's count was never given back"
        assert (0, r1) not in state.blocks  # the hole: no entry names it
        assert driver.stats(0)["num_mappers"] == records.num_mappers  # the others go on
        # the retry of map 0 and the other maps: rollovers that spill
        write_maps(daemon, 0, records, connections=3)
        stats = store.write_stats()
        assert stats["rollovers"] >= 2 and stats["spilled_bytes"] > 0
        assert stats["staged_blocks"] == records.num_blocks
        in_time(driver.run_exchange, 0)  # the seal
        got = fetch_all(daemon, 0, records)
        assert got == written(records) and got[(0, r1)] == p1
        in_time(driver.remove_shuffle, 0)


@PLANES
def test_discarded_empty_and_oversized_bodies(make_daemon, plane, rng):
    """(e) A frame to a ``discard`` writer (a retry after a commit) is read
    and dropped; a zero-length body is a block of no bytes, and a frame of no
    bytes before a partition's data changes nothing; a body larger than a
    region goes back to the buffered path with nothing reserved, is staged
    in pieces there and fetched whole, the connection kept."""
    daemon = make_daemon(plane, staging_capacity_per_executor=1 << 20)
    store = store_of(daemon)
    first, second = (rng.integers(0, 256, size=n, dtype=np.uint8).tobytes() for n in (4099, 2111))
    with closing(DaemonClient(daemon.address)) as client:
        client.create_shuffle(0, 2, 3)
        w = client.open_map_writer(0, 0)
        client.write_partition(w, 0, first)
        client.write_partition(w, 1, b"")
        client.write_partition(w, 2, b"")
        client.write_partition(w, 2, second)
        assert client.commit_map(w).tolist() == [len(first), 0, len(second)]
        retry = client.open_map_writer(0, 0)  # first commit wins: its writes are swallowed
        client.write_partition(retry, 0, b"\xee" * 5000)
        client.commit_map(retry)
        stats = store.write_stats()
        assert stats["staged_blocks"] == 3 and stats["inplace_blocks"] == 3
        assert stats["inplace_bytes"] == len(first) + len(second) and stats["inplace_fallbacks"] == 0
        w1 = client.open_map_writer(0, 1)
        oversized = rng.integers(0, 256, size=(1 << 20) + 1, dtype=np.uint8).tobytes()
        client.write_partition(w1, 0, oversized)
        assert not store._state(0).inflight
        client.write_partition(w1, 1, second)  # the same connection, the same writer
        client.commit_map(w1)
        stats = store.write_stats()
        assert (stats["inplace_blocks"], stats["inplace_fallbacks"]) == (4, 1)
        assert (stats["split_blocks"], stats["split_pieces"], stats["split_bytes"]) == (1, 2, len(oversized))
        client.run_exchange(0)
        bids = [ShuffleBlockId(0, 0, 0), ShuffleBlockId(0, 0, 1), ShuffleBlockId(0, 0, 2), ShuffleBlockId(0, 1, 0),
                ShuffleBlockId(0, 1, 1)]
        assert client.fetch_blocks(bids) == [first, b"", second, oversized, second]
        client.remove_shuffle(0)


class ShortSends:
    """A socket whose ``sendmsg`` takes a few bytes of its first buffer a
    call, and keeps what went out."""

    def __init__(self, sock, at_most):
        self._sock, self._at_most, self.sent = sock, at_most, bytearray()

    def sendmsg(self, buffers):
        first = bytes(buffers[0][: self._at_most])
        n = self._sock.send(first)
        self.sent += first[:n]
        return n

    def __getattr__(self, name):
        return getattr(self._sock, name)


@PLANES
@pytest.mark.parametrize("held", [True, False], ids=["batched", "at-once"])
@pytest.mark.parametrize("at_most", [1, 7, 4096])
def test_the_client_sends_the_same_bytes_over_short_sends(make_daemon, plane, at_most, held, rng):
    """(f) ``DaemonClient`` loops on a short ``sendmsg``: the bytes on the
    wire are the joined frame's, and the daemon serves them — the frame of
    several blocks a ``bytes`` block waits for, and the one-block frame that
    data the caller may change goes out in at once."""
    daemon = make_daemon(plane, staging_capacity_per_executor=1 << 20)
    payload = rng.integers(0, 256, size=9001 if at_most > 1 else 301, dtype=np.uint8).tobytes()
    with closing(DaemonClient(daemon.address)) as client:
        client.create_shuffle(0, 1, 1)
        w = client.open_map_writer(0, 0)
        real = client._sock
        client._sock = stub = ShortSends(real, at_most)
        client.write_partition(w, 0, payload if held else memoryview(payload))
        assert bool(stub.sent) is not held
        lengths = client.commit_map(w)
        client._sock = real
        header = {"writer": w, "reduce_ids": [0], "lengths": [len(payload)]} if held else {"writer": w, "reduce_id": 0}
        assert bytes(stub.sent) == (
            _frame(DaemonOp.WRITE_PARTITION, header, payload) + _frame(DaemonOp.COMMIT_MAP, {"writer": w})
        )
        assert lengths.tolist() == [len(payload)]
        client.run_exchange(0)
        assert client.fetch_blocks([ShuffleBlockId(0, 0, 0)]) == [payload]
        client.remove_shuffle(0)


def test_the_wire_format_of_a_call_is_the_joined_frame():
    """The vectored send changes no byte: fixed header, JSON, body."""
    a, b = socket.socketpair()
    with closing(a), closing(b):
        client = DaemonClient.__new__(DaemonClient)
        client._sock, client._lock, client._pending = a, threading.Lock(), []
        body = b"\x01\x02\x03" * 1000
        b.sendall(_frame(DaemonOp.ACK, {"ok": True}))
        client._call(DaemonOp.WRITE_PARTITION, {"writer": 3, "reduce_id": 5}, body)
        want = _frame(DaemonOp.WRITE_PARTITION, {"writer": 3, "reduce_id": 5}, body)
        got = bytearray()
        while len(got) < len(want):
            got += b.recv(len(want) - len(got))
        assert bytes(got) == want
        op, hlen, blen = struct.unpack("<IQQ", want[:20])
        assert (op, blen) == (DaemonOp.WRITE_PARTITION, len(body))
        assert json.loads(want[20 : 20 + hlen]) == {"writer": 3, "reduce_id": 5}


# -- the store's side alone: MapWriter.reserve / end_receive --------------------


@pytest.fixture
def store():
    s = HbmBlockStore(TpuShuffleConf(staging_capacity_per_executor=1 << 16, max_host_pool_bytes=0))
    s.create_shuffle(0, 4, 2)
    yield s
    s.close()


def receive(writer, reduce_id, data, pieces=1, close=True):
    """``data`` into partition ``reduce_id`` through ``reserve``, as a socket would."""
    writer.open_partition(reduce_id)
    step = -(-len(data) // pieces) or 1
    for at in range(0, max(len(data), 1), step):
        part = data[at : at + step]
        view = writer.reserve(len(part))
        if view is None:
            writer.write(part)
        else:
            view[:] = part
            writer.end_receive(len(part), True)
    if close:
        writer.close_partition()


def test_a_reservation_stays_with_its_round_through_a_spill(store, rng):
    """A partition reserved and received but not closed when its round is
    spilled and the buffer reused: its entry names the round it was made in,
    and the bytes come back from there."""
    a, b = store.map_writer(0, 0), store.map_writer(0, 1)
    early = rng.integers(0, 256, size=5000, dtype=np.uint8).tobytes()
    filler = rng.integers(0, 256, size=40000, dtype=np.uint8).tobytes()
    receive(a, 0, early, close=False)
    receive(b, 0, filler)
    receive(b, 1, filler)  # does not fit: the round spills, a's partition still open
    assert store.num_rounds(0) == 2 and store.write_stats()["spilled_bytes"] > 0
    a.close_partition()
    a.commit(), b.commit()
    assert store._state(0).blocks[(0, 0)].round == 0
    assert store.read_block(0, 0, 0) == early
    assert store.read_block(0, 1, 0) == filler and store.read_block(0, 1, 1) == filler
    assert store.write_stats()["inplace_blocks"] == 3


def test_a_further_frame_after_the_round_moved_on_falls_back(store, rng):
    """The second frame of a partition whose round was spilled meanwhile: the
    first frame's bytes are read back from the spilled round, the partition
    is staged whole in the live one."""
    a, b = store.map_writer(0, 0), store.map_writer(0, 1)
    data = rng.integers(0, 256, size=9000, dtype=np.uint8).tobytes()
    filler = rng.integers(0, 256, size=40000, dtype=np.uint8).tobytes()
    a.open_partition(0)
    a.reserve(4000)[:] = data[:4000]
    a.end_receive(4000, True)
    receive(b, 0, filler)
    receive(b, 1, filler)  # the round rolls over
    assert a.reserve(5000) is None
    a.write(data[4000:])
    a.close_partition()
    a.commit(), b.commit()
    assert store._state(0).blocks[(0, 0)].round == 1
    assert store.read_block(0, 0, 0) == data
    stats = store.write_stats()
    assert stats["inplace_fallbacks"] == 1 and stats["inplace_blocks"] == 2 and stats["staged_blocks"] == 3


def test_write_after_a_reservation_goes_through_the_buffered_path(store, rng):
    data = rng.integers(0, 256, size=6000, dtype=np.uint8).tobytes()
    w = store.map_writer(0, 0)
    w.open_partition(1)
    w.reserve(2500)[:] = data[:2500]
    w.end_receive(2500, True)
    w.write(data[2500:])
    assert w.reserve(10) is None  # order: nothing may overtake the buffered bytes
    w.close_partition()
    w.commit()
    assert store.read_block(0, 0, 1) == data
    assert store.write_stats()["inplace_fallbacks"] == 1


def test_a_lost_body_loses_the_partition_and_gives_everything_back(store):
    w = store.map_writer(0, 0)
    w.open_partition(0)
    view = w.reserve(3000)
    state = store._state(0)
    assert state.inflight == {0: 1}
    view[:100] = b"\xee" * 100
    w.end_receive(3000, False)
    assert not state.inflight and (0, 0) not in state.blocks
    with pytest.raises(TransportError, match="lost a body"):
        w.reserve(10)
    with pytest.raises(TransportError, match="lost a body"):
        w.close_partition()
    with pytest.raises(TransportError, match="open partition"):
        w.commit()
    retry = store.map_writer(0, 0)  # never committed: a fresh attempt, not a discard
    receive(retry, 0, b"again" * 500)
    retry.commit()
    assert store.read_block(0, 0, 0) == b"again" * 500
    assert in_time(store.seal, 0) and in_time(lambda: store.remove_shuffle(0) or True)


def test_reserve_refuses_what_close_partition_refuses(store):
    w = store.map_writer(0, 0)
    w.open_partition(0)
    # a body over a region is no refusal any more: it goes to the buffered
    # path, which stages it in pieces, and nothing is reserved for it
    assert w.reserve((1 << 16) + 1) is None
    assert not store._state(0).host_staging_allocated and not store._state(0).inflight  # nothing allocated
    store.seal(0)
    with pytest.raises(TransportError, match="already sealed"):
        w.reserve(10)
    store.remove_shuffle(0)
    with pytest.raises(TransportError, match="unknown shuffle"):
        w.reserve(10)


@pytest.mark.parametrize("waiter", ["seal", "remove_shuffle", "close"])
def test_seal_remove_and_close_wait_for_a_receive_in_flight(store, waiter):
    """Whoever hands a round's buffer on waits until the receive into it has
    ended, admits no new reservation meanwhile, and counts the wait."""
    w, other = store.map_writer(0, 0), store.map_writer(0, 1)
    w.open_partition(0)
    other.open_partition(0)
    view = w.reserve(2000)
    state = store._state(0)
    done, late = threading.Event(), []

    def wait_then_act():
        getattr(store, waiter)(*(() if waiter == "close" else (0,)))
        done.set()

    def reserve_late():
        try:
            other.reserve(10)
            late.append("admitted")
        except TransportError as e:
            late.append(str(e))

    t = threading.Thread(target=wait_then_act, daemon=True)
    t.start()
    deadline = time.monotonic() + TIMEOUT
    while not state.draining and time.monotonic() < deadline:
        time.sleep(0.001)
    assert state.draining == 1 and not done.is_set()
    t2 = threading.Thread(target=reserve_late, daemon=True)
    t2.start()
    time.sleep(0.05)
    assert not done.is_set() and not late, "a buffer was handed on, or a reservation admitted, under a receive"
    view[:] = b"\x07" * 2000
    w.end_receive(2000, True)
    t.join(TIMEOUT), t2.join(TIMEOUT)
    assert done.is_set() and not t.is_alive() and not t2.is_alive()
    assert late and late[0] != "admitted"  # sealed, or gone, by the time it was let in
    assert store.write_stats()["inflight_wait_ns"] > 0


def test_shared_memory_staging_receives_in_place_too():
    """The arena's mapping is written through the same view; ``remove_shuffle``
    (which unmaps it) comes after the receive."""
    import os

    s = HbmBlockStore(TpuShuffleConf(staging_capacity_per_executor=1 << 16, use_shm_staging=True,
                                     shm_namespace=f"inplace{os.getpid()}"))
    try:
        s.create_shuffle(0, 1, 2)
        w = s.map_writer(0, 0)
        receive(w, 1, b"a" * 1000 + b"b" * 500, pieces=2)
        w.commit()
        assert s.read_block(0, 0, 1) == b"a" * 1000 + b"b" * 500
        assert s.write_stats()["inplace_blocks"] == 1 and s.write_stats()["inplace_fallbacks"] == 0
        s.remove_shuffle(0)
    finally:
        s.close()
