"""A fetch reply lands once.

``DaemonClient.fetch_blocks`` receives a reply's body straight into the
connection's landing buffer and hands each block out as a read-only view of
where it landed.  What has to hold: the same bytes, ``None`` and request
order as ever; a buffer with live views is never written again; the kept
buffer follows the sizes of the replies; a reply that breaks off leaves
nothing handed out.  A real ``ShuffleDaemon`` on loopback, but for the
failure paths, which need a daemon that misbehaves.

This file is the socket's landing: its clients never offer a mapping, as a
client on another host never does (``_may_offer``), so every reply here takes
the path that stays the one fallback.  ``test_daemon_mapped_landing.py`` has
the same-host client's."""

import socket
import struct
import sys
import threading
from contextlib import closing

import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.block import ShuffleBlockId
from sparkucx_tpu.core.definitions import MAX_FRAME_BYTES, AmId
from sparkucx_tpu.obs.metrics import MetricsRegistry
from sparkucx_tpu.shuffle.daemon import LANDING_SLACK, DaemonClient, ShuffleDaemon
from sparkucx_tpu.shuffle.reader import default_deserializer

#: every join and wait of this file
TIMEOUT = 60
#: the counters of the mapped landing, on a client that never offers
NOT_MAPPED = dict.fromkeys(("landed_mapped", "mapped_bytes", "landings_offered", "landings_refused"), 0)


@pytest.fixture(scope="module")
def daemon():
    d = ShuffleDaemon(TpuShuffleConf(), num_executors=1, port=0)
    yield d
    d.close()


@pytest.fixture
def client(daemon):
    with closing(DaemonClient(daemon.address)) as c:
        c._may_offer = False
        yield c


def stage(client, shuffle_id, payloads, reducers=None):
    """One shuffle whose map task ``m`` wrote ``payloads[m][r]`` to reduce
    partition ``r`` (``None``: nothing written), exchanged."""
    reducers = reducers or max(len(p) for p in payloads)
    client.create_shuffle(shuffle_id, len(payloads), reducers)
    for m, parts in enumerate(payloads):
        writer = client.open_map_writer(shuffle_id, m)
        for r, payload in enumerate(parts):
            if payload is not None:
                client.write_partition(writer, r, payload)
        client.commit_map(writer)
    client.run_exchange(shuffle_id)


def blob(rng, n):
    return rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()


@pytest.mark.parametrize("blocks", [1, 13, 63])
def test_a_reply_is_the_written_bytes_in_request_order(client, rng, blocks):
    """1, 13 and 63 blocks a reply; past one block, with an empty block and a
    block the daemon cannot serve in the middle."""
    written = [blob(rng, int(rng.integers(1, 4000))) for _ in range(blocks)]
    if blocks > 1:
        written[blocks // 3] = b""
    sid = 100 + blocks
    stage(client, sid, [[w] for w in written])
    bids = [ShuffleBlockId(sid, m, 0) for m in range(blocks)]
    expect = list(written)
    if blocks > 1:
        bids.insert(blocks // 2, ShuffleBlockId(sid, 0, 99))
        expect.insert(blocks // 2, None)
    got = client.fetch_blocks(bids)
    assert got == expect
    assert [None if g is None else bytes(g) for g in got] == expect
    for g in got:
        if g is not None:
            assert isinstance(g, memoryview) and g.readonly and g.format == "B"
            with pytest.raises(TypeError):
                g[:1] = b"x"
    stats = client.fetch_stats()
    assert stats == {
        "fetch_replies": 1, "landed_reused": 0, "landed_fresh": 1,
        "view_blocks": blocks, "view_bytes": sum(len(w) for w in written), **NOT_MAPPED,
    }
    client.remove_shuffle(sid)


def test_a_reply_without_a_body_lands_nowhere(client):
    stage(client, 120, [[b""], [None]])
    got = client.fetch_blocks([ShuffleBlockId(120, 0, 0), ShuffleBlockId(120, 7, 0), ShuffleBlockId(120, 1, 0)])
    assert got == [b"", None, b""] and got[0].readonly and len(got[2]) == 0
    assert client.fetch_stats() == {
        "fetch_replies": 1, "landed_reused": 0, "landed_fresh": 0, "view_blocks": 2, "view_bytes": 0, **NOT_MAPPED,
    }
    client.remove_shuffle(120)


def test_blocks_held_across_fetches_stay_intact(client, rng):
    """A buffer with live views is never written again: the replies that
    find it held land in new buffers; once the holder lets go, the kept
    buffer is written again."""
    written = [[blob(rng, 3000) for _ in range(5)] for _ in range(3)]
    stage(client, 130, written)

    def fetch(r):
        return client.fetch_blocks([ShuffleBlockId(130, m, r) for m in range(3)])

    def expect(r):
        return [written[m][r] for m in range(3)]

    first = fetch(0)
    second = fetch(1)
    third = fetch(2)
    assert first == expect(0) and second == expect(1) and third == expect(2)
    stats = client.fetch_stats()
    assert (stats["landed_fresh"], stats["landed_reused"]) == (3, 0)
    kept = first[1][10:20]  # a slice of a view holds the buffer as the view did
    del first, third
    fourth = fetch(3)  # the kept buffer is the third reply's, and free
    assert client.fetch_stats()["landed_reused"] == 1
    assert fourth == expect(3) and second == expect(1) and kept == written[1][0][10:20]
    del fourth
    assert fetch(4) == expect(4)
    assert fetch(0) == expect(0)
    stats = client.fetch_stats()
    assert (stats["fetch_replies"], stats["landed_fresh"], stats["landed_reused"]) == (6, 3, 3)
    assert second == expect(1) and kept == written[1][0][10:20]
    client.remove_shuffle(130)


def test_decoded_values_own_their_bytes(client, groupbytest):
    """The decoder's one copy of each value is what makes a view safe to hand
    out: records decoded from one reply are whole after later replies have
    been written over it."""
    records = groupbytest.records(4)
    stage(client, 140, [[dict(parts).get(r) for r in range(records.reducers)] for parts in records.blocks],
          reducers=records.reducers)
    size = [sum(len(p) for parts in records.blocks for r, p in parts if r == q) for q in range(records.reducers)]
    largest = max(range(records.reducers), key=size.__getitem__)
    others = [r for r in range(records.reducers) if r != largest and LANDING_SLACK * size[r] >= size[largest]][:6]
    assert len(others) == 6

    def decoded(r):
        bids = [ShuffleBlockId(140, m, r) for m in records.mappers_of(r)]
        return [kv for payload in client.fetch_blocks(bids) for kv in default_deserializer(payload)]

    first = decoded(largest)
    for r in others:  # each lands where the largest reply lay
        decoded(r)
    stats = client.fetch_stats()
    assert (stats["fetch_replies"], stats["landed_fresh"], stats["landed_reused"]) == (7, 1, 6)
    check = records.check(largest, full=True)
    for key, value in first:
        check.add(key, value)
    assert check.ok()
    client.remove_shuffle(140)


def test_the_kept_buffer_follows_the_replies(client, rng):
    """Too short for the reply at hand, or more than ``LANDING_SLACK`` times
    as long: the reply gets a buffer of its own size, and that one is kept."""
    sizes = [20_000, 21_000, 400_000, 90_000, 110_000, 100 * LANDING_SLACK, 100, 100 * LANDING_SLACK + 1]
    written = [blob(rng, n) for n in sizes]
    stage(client, 150, [written])

    def fetch(r):
        [got] = client.fetch_blocks([ShuffleBlockId(150, 0, r)])
        assert got == written[r]
        del got
        stats = client.fetch_stats()
        return stats["landed_fresh"], stats["landed_reused"], len(client._landing)

    assert fetch(0) == (1, 0, 20_000)
    assert fetch(0) == (1, 1, 20_000)
    assert fetch(1) == (2, 1, 21_000)  # 1,000 B too short
    assert fetch(0) == (2, 2, 21_000)  # a shorter reply fits
    assert fetch(2) == (3, 2, 400_000)  # much larger than the one before
    assert fetch(3) == (4, 2, 90_000)  # 400,000 is let go: over 4 x 90,000
    assert fetch(2) == (5, 2, 400_000)
    assert fetch(4) == (5, 3, 400_000)  # not over 4 x 110,000: kept
    assert fetch(5) == (6, 3, 400)
    assert fetch(6) == (6, 4, 400)  # 4 x 100 exactly: kept
    assert fetch(7) == (7, 4, 401)
    assert fetch(6) == (8, 4, 100)  # one byte over 4 x 100
    client.remove_shuffle(150)


def test_threads_share_one_client(client, rng):
    """Reduce tasks of one executor on one client, as the JVM shim's share its
    ``synchronized`` one: each thread's blocks are its own whatever the others
    fetch meanwhile."""
    written = [[blob(rng, 2000 + 100 * r) for r in range(4)] for _ in range(5)]
    stage(client, 160, written)
    rounds, errors = 60, []

    def task(reducers):
        try:
            for i in range(rounds):
                r = reducers[i % len(reducers)]
                got = client.fetch_blocks([ShuffleBlockId(160, m, r) for m in range(5)])
                for m, g in enumerate(got):
                    if g != written[m][r]:
                        raise AssertionError(f"block ({m}, {r}) of round {i} differs")
        except Exception as e:  # the thread's boundary: the test reads it
            errors.append(e)

    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=task, args=(rs,), daemon=True) for rs in ([0, 1], [2, 3], [3, 0])]
        for t in threads:
            t.start()
        for t in threads:
            t.join(TIMEOUT)
    finally:
        sys.setswitchinterval(before)
    assert not any(t.is_alive() for t in threads), "a thread hangs"
    assert not errors, errors
    stats = client.fetch_stats()
    assert stats["fetch_replies"] == 3 * rounds == stats["landed_reused"] + stats["landed_fresh"]
    assert stats["view_blocks"] == 3 * rounds * 5
    client.remove_shuffle(160)


def test_the_counters_register_as_a_family(client):
    stage(client, 170, [[b"abc"]])
    client.fetch_blocks([ShuffleBlockId(170, 0, 0)])
    registry = MetricsRegistry()
    client.register_metrics(registry)
    text = registry.prometheus_text()
    for name, value in (("fetch_replies", 1), ("landed_reused", 0), ("landed_fresh", 1),
                        ("view_blocks", 1), ("view_bytes", 3)):
        assert f"sparkucx_tpu_daemonclient_{name} {value}" in text
    client.remove_shuffle(170)


# ---------------------------------------------------------------------------
# a reply that breaks off


def reply_prefix(sizes, body_len=None):
    header = struct.pack("<QI", 0, len(sizes)) + b"".join(struct.pack("<q", s) for s in sizes)
    body_len = sum(s for s in sizes if s > 0) if body_len is None else body_len
    return struct.pack("<IQQ", int(AmId.FETCH_BLOCK_REQ_ACK), len(header),
                       body_len) + header


class FakeDaemon:
    """Accepts one connection, reads one request, answers with ``reply`` and
    then closes (``hang`` false) or keeps the connection open and silent."""

    def __init__(self, reply: bytes, hang: bool) -> None:
        self._srv = socket.socket()
        self._srv.bind(("127.0.0.1", 0))
        self._srv.listen(1)
        self.address = self._srv.getsockname()
        self._release = threading.Event()
        self._thread = threading.Thread(target=self._serve, args=(reply, hang), daemon=True)
        self._thread.start()

    def _serve(self, reply, hang):
        conn, _ = self._srv.accept()
        with conn:
            conn.recv(1 << 16)
            conn.sendall(reply)
            if hang:
                self._release.wait(TIMEOUT)

    def close(self):
        self._release.set()
        self._thread.join(TIMEOUT)
        self._srv.close()
        assert not self._thread.is_alive()


@pytest.mark.parametrize(
    "reply, hang, error, words",
    [
        (reply_prefix([600, -1, 400]) + b"x" * 450, False, ConnectionError, r"mid-body with 450/1000 B received"),
        (reply_prefix([600, -1, 400]) + b"x" * 450, True, OSError, r"hung mid-frame: read timed out with 450/1000 B received"),
        (reply_prefix([600, 400])[:30], True, OSError, r"hung mid-frame: read timed out with 10/28 B received"),
        (reply_prefix([1], body_len=MAX_FRAME_BYTES), False, ValueError, r"frame too large from peer 127\.0\.0\.1:\d+"),
        (reply_prefix([600, -1, 400], body_len=900), False, ValueError, r"other sizes than its body's 900 B"),
        (reply_prefix([600, 400])[:30], False, ConnectionError, r"daemon closed connection"),
        (b"", False, ConnectionError, r"daemon closed connection"),
    ],
    ids=["closed-mid-body", "hung-mid-body", "hung-mid-header", "over-the-frame-ceiling", "sizes-other-than-the-body",
         "closed-mid-header", "closed-before-a-reply"],
)
def test_a_reply_that_breaks_off_hands_nothing_out(reply, hang, error, words):
    fake = FakeDaemon(reply, hang)
    try:
        with closing(DaemonClient(fake.address)) as client:
            client._sock.settimeout(0.3)
            with pytest.raises(error, match=words):
                client.fetch_blocks([ShuffleBlockId(0, 0, 0), ShuffleBlockId(0, 1, 0), ShuffleBlockId(0, 2, 0)])
            assert client.fetch_stats() == dict.fromkeys(
                ("fetch_replies", "landed_reused", "landed_fresh", "view_blocks", "view_bytes", *NOT_MAPPED), 0
            )
            if error is ValueError:  # refused before any allocation
                assert client._landing is None
    finally:
        fake.close()
