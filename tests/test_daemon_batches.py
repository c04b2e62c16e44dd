"""A map task's blocks a batch a frame (PR 59).

``WritePartition`` may carry several blocks of one writer, and
``DaemonClient.write_partition`` fills such frames: a ``bytes`` block waits on
the connection until the byte bound is reached, a block of another writer
arrives or any other op is made.  What the configurations state — every
acknowledged write read back exactly once, byte-exact — is a map task's
commit: it has to return only after every block was acked by its byte count,
and a block refused, lost or acked short has to fail the map before it.  Both
serving planes."""

import socket
import sys
import threading
import time
from contextlib import closing

import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.block import ShuffleBlockId
from sparkucx_tpu.core.operation import ResourceExhaustedError
from sparkucx_tpu.service.tenants import TenantRegistry
from sparkucx_tpu.shuffle import daemon as wire
from sparkucx_tpu.shuffle.daemon import DaemonClient, DaemonOp, ShuffleDaemon, _frame, _read_frame

TIMEOUT = 60
PLANES = pytest.mark.parametrize(
    "plane", [{}, {"server_workers": 3}], ids=["thread-a-connection", "reactor"]
)


@pytest.fixture
def make_daemon():
    daemons = []

    def make(plane, **conf):
        conf.setdefault("staging_capacity_per_executor", 1 << 20)
        d = ShuffleDaemon(TpuShuffleConf(**plane, **conf), num_executors=1, port=0)
        daemons.append(d)
        return d

    yield make
    for d in daemons:
        d.close()


@pytest.fixture
def one_frame_a_map(monkeypatch):
    """No byte bound in the way: a map task's blocks ride in one frame."""
    monkeypatch.setattr(wire, "WRITE_BATCH_BYTES", 1 << 40)


def store_of(daemon):
    return daemon.manager.cluster.transports[0].store


def write_row(daemon, frames=None) -> dict:
    """The daemon's ``write_partition`` row — with ``frames``, once it has
    counted that many: a frame is counted after its ack is sent, so a client
    whose last call was that frame's may be ahead of the row."""
    deadline = time.monotonic() + TIMEOUT
    while True:
        rows = {row["op"]: row for row in daemon.op_stats()}
        row = rows.get("write_partition", {"frames": 0, "blocks": 0, "body_bytes": 0})
        if frames is None or row["frames"] >= frames or time.monotonic() > deadline:
            return row
        time.sleep(0.001)


def payloads(rng, sizes):
    return [rng.integers(0, 256, size=n, dtype=np.uint8).tobytes() for n in sizes]


def batch_frame(writer, reduce_ids, blocks, lengths=None) -> bytes:
    header = {"writer": writer, "reduce_ids": list(reduce_ids),
              "lengths": [len(b) for b in blocks] if lengths is None else lengths}
    return _frame(DaemonOp.WRITE_PARTITION, header, b"".join(blocks))


class RawClient:
    """A connection that sends the bytes a test chooses."""

    def __init__(self, daemon):
        self.sock = socket.create_connection(daemon.address, timeout=TIMEOUT)

    def call(self, op, header, body=b""):
        return self.send(_frame(op, header, body))

    def send(self, frame: bytes) -> dict:
        self.sock.sendall(frame)
        reply = _read_frame(self.sock)
        assert reply is not None, "the daemon closed the connection"
        return reply[1]

    def open_writer(self, shuffle_id, map_id) -> int:
        return self.call(DaemonOp.OPEN_MAP_WRITER, {"shuffle_id": shuffle_id, "map_id": map_id})["writer"]

    def close(self):
        self.sock.close()


# -- a batch round-trips ----------------------------------------------------


@PLANES
@pytest.mark.parametrize("blocks", [1, 63, 200])
def test_a_batch_round_trips_byte_exact(make_daemon, plane, blocks, one_frame_a_map, rng):
    """One frame a map task at 1, 63 and 200 blocks, with an empty block and
    a partition continued over two entries of the frame: write -> commit ->
    exchange -> fetch gives back every byte, and both sides count one frame."""
    daemon = make_daemon(plane)
    sizes = [int(n) for n in rng.integers(1, 3000, size=blocks)]
    if blocks > 1:
        sizes[blocks // 2] = 0
    data = payloads(rng, sizes)
    tail = payloads(rng, [777])[0]  # the last partition goes on in a further entry
    with closing(DaemonClient(daemon.address)) as client:
        client.create_shuffle(0, 1, blocks)
        w = client.open_map_writer(0, 0)
        for r, block in enumerate(data):
            client.write_partition(w, r, block)
        client.write_partition(w, blocks - 1, tail)
        assert write_row(daemon)["frames"] == 0  # nothing has crossed yet
        want = data[:-1] + [data[-1] + tail]
        assert client.commit_map(w).tolist() == [len(b) for b in want]
        row = write_row(daemon)
        assert (row["frames"], row["blocks"]) == (1, blocks + 1)
        assert row["body_bytes"] == sum(sizes) + len(tail)
        assert client.write_stats() == {"write_frames": 1, "write_blocks": blocks + 1, "flushes_full": 0,
                                        "flushes_forced": 1, "sent_at_once": 0}
        client.run_exchange(0)
        assert client.fetch_blocks([ShuffleBlockId(0, 0, r) for r in range(blocks)]) == want
        client.remove_shuffle(0)


@PLANES
def test_a_block_longer_than_a_region_rides_in_a_batch(make_daemon, plane, one_frame_a_map, rng):
    """PR 58's block over a peer region between two small ones of one frame:
    staged in pieces, the blocks after it in place, all read back whole."""
    daemon = make_daemon(plane)
    store = store_of(daemon)
    data = payloads(rng, [5000, (1 << 20) + 4097, 3000, 0, 2000])
    with closing(DaemonClient(daemon.address)) as client:
        client.create_shuffle(0, 1, len(data))
        w = client.open_map_writer(0, 0)
        for r, block in enumerate(data):
            client.write_partition(w, r, block)
        assert client.commit_map(w).tolist() == [len(b) for b in data]
        assert write_row(daemon)["frames"] == 1
        stats = store.write_stats()
        assert (stats["split_blocks"], stats["split_bytes"]) == (1, len(data[1]))
        assert stats["inplace_blocks"] == 4 and stats["inplace_fallbacks"] == 1
        client.run_exchange(0)
        assert client.fetch_blocks([ShuffleBlockId(0, 0, r) for r in range(len(data))]) == data
        client.remove_shuffle(0)


@PLANES
def test_the_one_block_frame_still_serves_and_mixes_with_batches(make_daemon, plane, rng):
    """The frame of before: its ack is ``written: <n>`` as ever, and a
    partition begun in one goes on in a batch's first entry."""
    daemon = make_daemon(plane)
    a, b, c, d = payloads(rng, [1200, 800, 1600, 50])
    raw = RawClient(daemon)
    with closing(raw), closing(DaemonClient(daemon.address)) as client:
        client.create_shuffle(0, 1, 3)
        w = raw.open_writer(0, 0)
        assert raw.call(DaemonOp.WRITE_PARTITION, {"writer": w, "reduce_id": 0}, a) == {"ok": True, "written": len(a)}
        assert raw.send(batch_frame(w, [0, 1], [b, c])) == {"ok": True, "written": [len(b), len(c)]}
        assert raw.call(DaemonOp.WRITE_PARTITION, {"writer": w, "reduce_id": 2}, d)["ok"]
        assert raw.send(batch_frame(w, [], [])) == {"ok": True, "written": []}  # a frame of no blocks
        lengths = np.frombuffer(_commit(raw, w), dtype="<i8")
        assert lengths.tolist() == [len(a) + len(b), len(c), len(d)]
        row = write_row(daemon)
        assert (row["frames"], row["blocks"]) == (4, 4)
        client.run_exchange(0)
        assert client.fetch_blocks([ShuffleBlockId(0, 0, r) for r in range(3)]) == [a + b, c, d]
        client.remove_shuffle(0)


def _commit(raw: RawClient, writer: int) -> bytes:
    raw.sock.sendall(_frame(DaemonOp.COMMIT_MAP, {"writer": writer}))
    _, meta, body = _read_frame(raw.sock)
    assert meta["ok"], meta
    return bytes(body)


# -- a frame that fails its check is refused whole --------------------------

BAD_FRAMES = {
    "lengths-short-of-the-body": lambda w: (batch_frame(w, [0, 1], [b"a" * 300, b"b" * 300], [300, 299]), "lengths sum"),
    "lengths-past-the-body": lambda w: (batch_frame(w, [0, 1], [b"a" * 300, b"b" * 300], [300, 301]), "lengths sum"),
    "reduce-ids-backwards": lambda w: (batch_frame(w, [1, 0], [b"a" * 300, b"b" * 300]), "go backwards"),
    "unknown-writer": lambda w: (batch_frame(w + 1000, [0, 1], [b"a" * 300, b"b" * 300]), "KeyError"),
    "fewer-lengths-than-ids": lambda w: (batch_frame(w, [0, 1], [b"a" * 600], [600]), "2 reduce ids but 1 lengths"),
    "a-negative-length": lambda w: (batch_frame(w, [0, 1], [b"a" * 600], [601, -1]), "negative length"),
    "lengths-that-are-no-list": lambda w: (
        _frame(DaemonOp.WRITE_PARTITION, {"writer": w, "reduce_ids": [0], "lengths": 600}, b"a" * 600), "TypeError"),
}


@PLANES
@pytest.mark.parametrize("case", list(BAD_FRAMES))
def test_a_frame_that_fails_its_check_is_refused_whole(make_daemon, plane, case, rng):
    """Checked before a byte of body is read: nothing of it is recorded, the
    body is dropped, the error acked, and the connection is in step for the
    frame that follows."""
    daemon = make_daemon(plane)
    store = store_of(daemon)
    good = payloads(rng, [900, 0, 1100])
    raw = RawClient(daemon)
    with closing(raw), closing(DaemonClient(daemon.address)) as client:
        client.create_shuffle(0, 1, 3)
        w = raw.open_writer(0, 0)
        frame, says = BAD_FRAMES[case](w)
        ack = raw.send(frame)
        assert ack["ok"] is False and says in ack["error"] and "written" not in ack
        assert not store._state(0).blocks and not store._state(0).inflight
        assert store.write_stats()["staged_blocks"] == 0
        assert raw.send(batch_frame(w, [0, 1, 2], good)) == {"ok": True, "written": [len(b) for b in good]}
        _commit(raw, w)
        client.run_exchange(0)
        assert client.fetch_blocks([ShuffleBlockId(0, 0, r) for r in range(3)]) == good
        client.remove_shuffle(0)


# -- a block refused mid-frame ----------------------------------------------


def refuse_by_quota(daemon, monkeypatch, blocks, at):
    """A tenant's quota that the block at ``at`` crosses."""
    store = store_of(daemon)
    align = store.conf.block_alignment
    registry = TenantRegistry()
    store.tenants = registry
    quota = sum(-(-len(b) // align) * align for b in blocks[:at]) + len(blocks[at]) // 2
    registry.register("a", hbm_quota_bytes=quota)
    store._state(0).app_id = "a"  # the wire names no tenant: the test does

    def lift():
        registry.register("a", hbm_quota_bytes=1 << 30)

    return "TenantQuotaExceededError", lift


def refuse_by_pressure(daemon, monkeypatch, blocks, at):
    """The store under memory pressure at the ``at``-th reservation."""
    store = store_of(daemon)
    real, seen = store.check_memory_pressure, []

    def shed(site, nbytes=0):
        if site == "reserve_partition":
            seen.append(nbytes)
            if len(seen) == at + 1:
                raise ResourceExhaustedError(nbytes, 0, 0, "shed")
        return real(site, nbytes)

    monkeypatch.setattr(store, "check_memory_pressure", shed)
    return "ResourceExhaustedError", lambda: monkeypatch.setattr(store, "check_memory_pressure", real)


@PLANES
@pytest.mark.parametrize("cause", [refuse_by_quota, refuse_by_pressure], ids=["quota", "memory-pressure"])
def test_a_block_refused_mid_frame_ends_the_frame_there(make_daemon, plane, cause, monkeypatch, one_frame_a_map, rng):
    """The ack names the refused reduce id and the blocks recorded before it
    (they stay recorded), the rest of the body is dropped and the connection
    kept; over ``DaemonClient`` the refusal is the flushing call's
    ``RuntimeError``, the map does not commit, and its retry — a new writer,
    every block again — does, and reads back exact."""
    daemon = make_daemon(plane)
    store = store_of(daemon)
    ids, at = [0, 2, 3, 5, 6, 7], 3
    blocks = payloads(rng, [700, 1300, 600, 2100, 900, 400])
    raw = RawClient(daemon)
    with closing(raw), closing(DaemonClient(daemon.address)) as client:
        client.create_shuffle(0, 2, 8)
        w = raw.open_writer(0, 0)
        error, lift = cause(daemon, monkeypatch, blocks, at)
        ack = raw.send(batch_frame(w, ids, blocks))
        assert ack["ok"] is False and ack["error"].startswith(error)
        assert ack["reduce_id"] == ids[at] and ack["written"] == [len(b) for b in blocks[:at]]
        state = store._state(0)
        assert sorted(state.blocks) == [(0, r) for r in ids[:at]] and not state.inflight
        assert raw.call(DaemonOp.STATS, {"shuffle_id": 0})["ok"]  # in step: the rest of the body was dropped
        assert write_row(daemon)["blocks"] == at
        lift()
        # the same refusal met by a client: map task 1, its blocks pending until the commit
        error, lift = cause(daemon, monkeypatch, blocks, at)
        w1 = client.open_map_writer(0, 1)
        for r, block in zip(ids, blocks):
            client.write_partition(w1, r, block)
        with pytest.raises(RuntimeError, match=rf"{error}.*reduce partition {ids[at]}, after {at} blocks"):
            client.commit_map(w1)
        with pytest.raises(RuntimeError, match="not acked every block"):
            client.commit_map(w1)  # nor later: nothing pends, the writer stays refused
        assert client.stats(0)["block_lengths"] == {}  # no map has committed
        lift()
        for m in (0, 1):  # the retries: new writers, every block again
            retry = client.open_map_writer(0, m)
            for r, block in zip(ids, blocks):
                client.write_partition(retry, r, block)
            written = dict(zip(ids, blocks))
            assert client.commit_map(retry).tolist() == [len(written.get(r, b"")) for r in range(8)]
        client.run_exchange(0)
        for m in (0, 1):
            assert client.fetch_blocks([ShuffleBlockId(0, m, r) for r in ids]) == blocks
        client.remove_shuffle(0)


@PLANES
def test_a_batch_for_a_sealed_shuffle_is_refused_at_its_first_block(make_daemon, plane, rng):
    daemon = make_daemon(plane)
    store = store_of(daemon)
    blocks = payloads(rng, [500, 600])
    raw = RawClient(daemon)
    with closing(raw):
        raw.call(DaemonOp.CREATE_SHUFFLE, {"shuffle_id": 0, "num_mappers": 2, "num_reducers": 2})
        w = raw.open_writer(0, 0)
        store.seal(0)
        ack = raw.send(batch_frame(w, [0, 1], blocks))
        assert ack["ok"] is False and "already sealed" in ack["error"]
        assert (ack["reduce_id"], ack["written"]) == (0, [])
        assert raw.call(DaemonOp.STATS, {"shuffle_id": 0})["ok"]


@PLANES
def test_a_sender_that_dies_mid_batch_gives_the_rounds_count_back(make_daemon, plane, one_frame_a_map, rng):
    """The connection ends as it does for a one-block frame, for the block
    in flight: the round's in-flight count comes back, the blocks before it
    stay recorded under the dead writer, no entry names the hole, and the
    map's retry commits and reads back exact."""
    daemon = make_daemon(plane, wire_timeout_ms=2000)
    store = store_of(daemon)
    blocks = payloads(rng, [900, 50_000, 700])
    frame = batch_frame(0, [0, 1, 2], blocks)
    with closing(DaemonClient(daemon.address)) as client:
        client.create_shuffle(0, 1, 3)
        doomed = RawClient(daemon)
        assert doomed.open_writer(0, 0) == 0
        doomed.sock.sendall(frame[: len(frame) - len(blocks[2]) - len(blocks[1]) // 2])
        state = store._state(0)
        deadline = time.monotonic() + TIMEOUT
        while not state.inflight and time.monotonic() < deadline:
            time.sleep(0.001)
        assert state.inflight == {0: 1}
        doomed.close()
        deadline = time.monotonic() + TIMEOUT
        while state.inflight and time.monotonic() < deadline:
            time.sleep(0.001)
        assert not state.inflight, "the lost body's count was never given back"
        assert sorted(state.blocks) == [(0, 0)]
        retry = client.open_map_writer(0, 0)
        for r, block in enumerate(blocks):
            client.write_partition(retry, r, block)
        assert client.commit_map(retry).tolist() == [len(b) for b in blocks]
        client.run_exchange(0)
        assert client.fetch_blocks([ShuffleBlockId(0, 0, r) for r in range(3)]) == blocks
        client.remove_shuffle(0)


# -- the client: when the pending blocks go --------------------------------


def _other_shuffle(client):
    client.create_shuffle(9, 1, 1)
    w = client.open_map_writer(9, 0)
    client.write_partition(w, 0, b"other")
    client.commit_map(w)
    client.run_exchange(9)


FLUSHING_OPS = {
    "stats": lambda c, w2: c.stats(0),
    "fetch_blocks": lambda c, w2: c.fetch_blocks([ShuffleBlockId(9, 0, 0)]),
    "remove_shuffle": lambda c, w2: c.remove_shuffle(9),
    "metrics_text": lambda c, w2: c.metrics_text(),
    "a-second-writers-block": lambda c, w2: c.write_partition(w2, 0, b"second"),
    "data-that-is-not-bytes": lambda c, w2: c.write_partition(w2, 0, bytearray(b"mutable")),
    "open_map_writer": lambda c, w2: c.open_map_writer(0, 2),
    "flush": lambda c, w2: c.flush(),
    "commit_map": lambda c, w2: c.commit_map(w2),
}


@PLANES
@pytest.mark.parametrize("op", list(FLUSHING_OPS))
def test_every_other_op_sends_what_is_pending_first(make_daemon, plane, op):
    """The daemon sees one connection's ops in the order the caller made
    them: whatever follows a held block finds it recorded."""
    daemon = make_daemon(plane)
    store = store_of(daemon)
    with closing(DaemonClient(daemon.address)) as client:
        _other_shuffle(client)
        client.create_shuffle(0, 3, 2)
        w, w2 = client.open_map_writer(0, 0), client.open_map_writer(0, 1)
        before = write_row(daemon)["frames"]
        client.write_partition(w, 0, b"held")
        client.write_partition(w, 1, b"back")
        assert write_row(daemon)["frames"] == before and not store._state(0).blocks
        seen = []
        real = daemon._dispatch

        def spy(conn, op_id, meta, body):  # what the daemon's store held when the op arrived
            seen.append(sorted(store._state(0).blocks))
            return real(conn, op_id, meta, body)

        daemon._dispatch = spy
        FLUSHING_OPS[op](client, w2)
        daemon._dispatch = real
        assert write_row(daemon, before + 1)["frames"] >= before + 1
        # (w, 0) is recorded when (w, 1) opens; (w, 1) stays open until the commit
        assert (0, 0) in store._state(0).blocks
        assert all((0, 0) in blocks for blocks in seen)
        assert client.commit_map(w).tolist() == [4, 4]


@PLANES
def test_close_lets_go_of_what_no_commit_covered(make_daemon, plane):
    daemon = make_daemon(plane)
    with closing(DaemonClient(daemon.address)) as driver:
        driver.create_shuffle(0, 1, 1)
        client = DaemonClient(daemon.address)
        w = client.open_map_writer(0, 0)
        client.write_partition(w, 0, b"never sent")
        client.close()
        assert write_row(daemon)["frames"] == 0 and not store_of(daemon)._state(0).blocks
        assert driver.stats(0)["block_lengths"] == {}
        with pytest.raises(OSError):
            client.flush()  # the pending block meets a closed socket, not a silent drop


@PLANES
@pytest.mark.parametrize("kind", [bytearray, memoryview], ids=["bytearray", "memoryview"])
def test_data_the_caller_may_change_is_sent_at_once(make_daemon, plane, kind, rng):
    daemon = make_daemon(plane)
    first, second = payloads(rng, [1500, 900])
    with closing(DaemonClient(daemon.address)) as client:
        client.create_shuffle(0, 1, 2)
        w = client.open_map_writer(0, 0)
        client.write_partition(w, 0, first)  # held back
        mutable = bytearray(second)
        client.write_partition(w, 1, mutable if kind is bytearray else memoryview(mutable))
        row = write_row(daemon, 2)
        assert (row["frames"], row["blocks"]) == (2, 2)  # the held block first, then this one
        mutable[:] = bytes(len(mutable))  # the caller's to change from here on
        assert client.write_stats() == {"write_frames": 2, "write_blocks": 2, "flushes_full": 0,
                                        "flushes_forced": 1, "sent_at_once": 1}
        assert client.commit_map(w).tolist() == [len(first), len(second)]
        client.run_exchange(0)
        assert client.fetch_blocks([ShuffleBlockId(0, 0, 0), ShuffleBlockId(0, 0, 1)]) == [first, second]
        client.remove_shuffle(0)


@PLANES
def test_the_byte_bound_and_the_counters(make_daemon, plane, monkeypatch, rng):
    """A frame holds as many blocks as reach the bound, a block that alone
    reaches it goes alone, and ``blocks / frames`` on the daemon and
    ``write_stats()`` on the client count what was sent."""
    monkeypatch.setattr(wire, "WRITE_BATCH_BYTES", 4000)
    daemon = make_daemon(plane)
    sizes = [5000] + [1000] * 9 + [300]  # 5000 alone | 4 x 1000 | 4 x 1000 | 1000 + 300 at the commit
    data = payloads(rng, sizes)
    with closing(DaemonClient(daemon.address)) as client:
        client.create_shuffle(0, 1, len(data))
        w = client.open_map_writer(0, 0)
        sent = [1, 1, 1, 1, 2, 2, 2, 2, 3, 3, 3]  # frames on the wire after each block
        frames = []
        for r, block in enumerate(data):
            client.write_partition(w, r, block)
            frames.append(write_row(daemon, sent[r])["frames"])
        assert frames == sent
        assert client.commit_map(w).tolist() == sizes
        row = write_row(daemon)
        assert (row["frames"], row["blocks"], row["body_bytes"]) == (4, 11, sum(sizes))
        assert client.write_stats() == {"write_frames": 4, "write_blocks": 11, "flushes_full": 3,
                                        "flushes_forced": 1, "sent_at_once": 0}
        text = client.metrics_text()
        assert 'sparkucx_tpu_daemon_blocks_total{op="write_partition"} 11' in text
        assert 'sparkucx_tpu_daemon_frames_total{op="write_partition"} 4' in text
        assert 'sparkucx_tpu_daemon_blocks_total{op="commit_map"} 0' in text
        client.run_exchange(0)
        assert client.fetch_blocks([ShuffleBlockId(0, 0, r) for r in range(len(data))]) == data
        client.remove_shuffle(0)


# -- the commit waits for every block's ack ---------------------------------


class FakeDaemon:
    """The other end of a socket pair: reads the frames a client sends and
    answers each with what the test queued."""

    def __init__(self):
        self.near, self.far = socket.socketpair()
        self.frames = []

    def client(self) -> DaemonClient:
        client = DaemonClient.__new__(DaemonClient)
        real = socket.create_connection
        try:
            socket.create_connection = lambda *a, **k: self.near
            DaemonClient.__init__(client, ("fake", 0))
        finally:
            socket.create_connection = real
        return client

    def serve(self, *acks):
        def run():
            for ack in acks:
                frame = _read_frame(self.far)
                if frame is None:
                    return
                self.frames.append((frame[0], frame[1], bytes(frame[2])))
                self.far.sendall(_frame(DaemonOp.ACK, *ack) if ack is not None else b"")
                if ack is None:
                    self.far.close()
                    return

        thread = threading.Thread(target=run, daemon=True)
        thread.start()
        return thread

    def close(self):
        self.near.close()
        self.far.close()


def test_the_write_counters_register_with_the_fetch_counters():
    from sparkucx_tpu.obs.metrics import MetricsRegistry

    fake = FakeDaemon()
    with closing(fake):
        client = fake.client()
        client.write_partition(7, 0, b"a" * 300)
        served = fake.serve(({"ok": True, "written": [300]},))
        client.flush()
        served.join(TIMEOUT)
        registry = MetricsRegistry()
        client.register_metrics(registry)
        text = registry.prometheus_text()
        assert "sparkucx_tpu_daemonclient_write_blocks 1" in text and "sparkucx_tpu_daemonclient_fetch_replies 0" in text


@pytest.mark.parametrize("ack", [
    ({"ok": True, "written": [300, 199]},),
    ({"ok": True, "written": [300]},),
    ({"ok": True, "written": 500},),
    ({"ok": True},),
    None,
], ids=["a-short-block", "a-block-missing", "the-one-block-ack", "no-written", "the-daemon-gone"])
def test_a_block_not_acked_by_its_byte_count_fails_the_map_before_its_commit(ack):
    fake = FakeDaemon()
    with closing(fake):
        client = fake.client()
        client.write_partition(7, 0, b"a" * 300)
        client.write_partition(7, 1, b"b" * 200)
        served = fake.serve(ack, ({"ok": True}, np.asarray([300, 200], dtype="<i8").tobytes()))
        with pytest.raises((RuntimeError, ConnectionError), match="acked other blocks|closed connection"):
            client.commit_map(7)
        if ack is not None:
            with pytest.raises(RuntimeError, match="not acked every block"):
                client.commit_map(7)
        fake.near.close()
        served.join(TIMEOUT)
        assert not served.is_alive()
        # one frame crossed: the blocks'.  No commit was ever sent
        assert [(op, meta) for op, meta, _ in fake.frames] == [
            (DaemonOp.WRITE_PARTITION, {"writer": 7, "reduce_ids": [0, 1], "lengths": [300, 200]})
        ]
        assert fake.frames[0][2] == b"a" * 300 + b"b" * 200


def test_a_block_sent_at_once_is_held_to_its_byte_count_too():
    fake = FakeDaemon()
    with closing(fake):
        client = fake.client()
        served = fake.serve(({"ok": True, "written": 299},))
        with pytest.raises(RuntimeError, match="acked other blocks"):
            client.write_partition(7, 0, bytearray(b"a" * 300))
        served.join(TIMEOUT)
        with pytest.raises(RuntimeError, match="not acked every block"):
            client.commit_map(7)
        assert [meta for _, meta, _ in fake.frames] == [{"writer": 7, "reduce_id": 0}]


def test_commit_map_returns_after_the_blocks_ack_then_the_commits():
    fake = FakeDaemon()
    with closing(fake):
        client = fake.client()
        client.write_partition(7, 0, b"a" * 300)
        client.write_partition(7, 0, b"b" * 200)
        served = fake.serve(({"ok": True, "written": [300, 200]},),
                            ({"ok": True}, np.asarray([500], dtype="<i8").tobytes()))
        assert client.commit_map(7).tolist() == [500]
        served.join(TIMEOUT)
        assert [op for op, _, _ in fake.frames] == [DaemonOp.WRITE_PARTITION, DaemonOp.COMMIT_MAP]
        assert fake.frames[0][1] == {"writer": 7, "reduce_ids": [0, 0], "lengths": [300, 200]}


def test_a_frame_of_more_blocks_than_one_sendmsg_takes(rng):
    """Over IOV_MAX (1,024) buffers a call: the frame still goes out whole,
    empty blocks and all."""
    fake = FakeDaemon()
    with closing(fake):
        client = fake.client()
        blocks = [bytes([i % 251]) * (i % 7) for i in range(2500)]
        for r, block in enumerate(blocks):
            client.write_partition(3, r, block)
        served = fake.serve(({"ok": True, "written": [len(b) for b in blocks]},))
        client.flush()
        served.join(TIMEOUT)
        [(op, meta, body)] = fake.frames
        assert meta["reduce_ids"] == list(range(2500)) and body == b"".join(blocks)


# -- threads on one client --------------------------------------------------


@PLANES
def test_three_threads_on_one_client_lose_no_block(make_daemon, plane, monkeypatch, rng):
    """The pending list is the connection's, under its lock: three map tasks
    written through one client at once (a block of another writer flushes)
    commit what they wrote."""
    monkeypatch.setattr(wire, "WRITE_BATCH_BYTES", 20_000)
    daemon = make_daemon(plane, staging_capacity_per_executor=8 << 20)
    maps = {m: payloads(rng, [int(n) for n in rng.integers(0, 4000, size=40)]) for m in range(3)}
    errors = []
    with closing(DaemonClient(daemon.address)) as client:
        client.create_shuffle(0, 3, 40)

        def task(m):
            try:
                w = client.open_map_writer(0, m)
                for r, block in enumerate(maps[m]):
                    client.write_partition(w, r, block)
                assert client.commit_map(w).tolist() == [len(b) for b in maps[m]]
            except BaseException as e:  # the thread's boundary: the test reads it
                errors.append(e)

        before = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=task, args=(m,), daemon=True) for m in maps]
            for t in threads:
                t.start()
            for t in threads:
                t.join(TIMEOUT)
        finally:
            sys.setswitchinterval(before)
        assert not errors and not any(t.is_alive() for t in threads), errors
        stats = client.write_stats()
        assert stats["write_blocks"] == 120 == write_row(daemon)["blocks"]
        assert stats["write_frames"] == stats["flushes_full"] + stats["flushes_forced"] == write_row(daemon)["frames"]
        client.run_exchange(0)
        for m, blocks in maps.items():
            assert client.fetch_blocks([ShuffleBlockId(0, m, r) for r in range(40)]) == blocks
        client.remove_shuffle(0)


def test_the_append_path_is_a_list_append():
    """``write_partition`` of a held block touches no socket: on a client
    whose socket is gone it still returns (PR 35's lesson is timed on the
    chip by ``scripts/probe_wire_batches.py``; here only that no I/O runs)."""
    fake = FakeDaemon()
    client = fake.client()
    fake.close()
    for r in range(1000):
        client.write_partition(1, r, b"x" * 100)
    assert client.write_stats()["write_frames"] == 0
    with pytest.raises(OSError):
        client.flush()
