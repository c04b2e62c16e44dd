"""A same-host client's fetch reply lands in a mapping both processes hold.

After its first reply with a body a ``DaemonClient`` on loopback offers the
daemon a landing (``OfferLanding``: a file under ``/dev/shm`` both map, its
name gone on the ack); from then on a request says whether that landing is
free, and a reply that finds it free and fits comes as its two headers — the
blocks are in the mapping.  What has to hold, on both serving planes: the
bytes, ``None`` and request order of the socket's replies, whichever way a
reply came; a landing with live views is never written and never unmapped; the
landing follows the sizes of the replies; a refused offer leaves the socket;
no name outlives the offer's round trip; a peer that never offers sees the
wire it always saw.  ``test_daemon_landing.py`` has the socket's landing."""

import collections
import gc
import json
import os
import signal
import socket
import struct
import subprocess
import sys
import threading
import time
from contextlib import closing

import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.block import ShuffleBlockId
from sparkucx_tpu.core.definitions import MAX_FRAME_BYTES, AmId
from sparkucx_tpu.shuffle import daemon as wire
from sparkucx_tpu.shuffle.daemon import (
    LANDING_HEADROOM,
    LANDING_SLACK,
    TAG_BODY_MAPPED,
    TAG_LANDING_FREE,
    DaemonClient,
    DaemonOp,
    ShuffleDaemon,
    attach_landing,
    _read_frame,
)
from sparkucx_tpu.utils.trace import TRACER
from test_daemon_landing import blob, stage  # one shuffle staged over a client; random bytes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: every join and wait of this file
TIMEOUT = 60
SHM = "/dev/shm"
PREFIX = "sparkucx-landing-"
#: the daemon's two serving planes: a thread a connection, or the reactor's pool
PLANES = {"threads": {}, "reactor": {"server_workers": 2}}


@pytest.fixture(scope="module", params=list(PLANES))
def daemon(request):
    d = ShuffleDaemon(TpuShuffleConf(**PLANES[request.param]), num_executors=1, port=0)
    yield d
    d.close()


@pytest.fixture
def client(daemon):
    with closing(DaemonClient(daemon.address)) as c:
        yield c


def landed(client):
    """``(landed_fresh, landed_reused, landed_mapped, landings_offered)``."""
    s = client.fetch_stats()
    return s["landed_fresh"], s["landed_reused"], s["landed_mapped"], s["landings_offered"]


def names_in_shm():
    return {n for n in os.listdir(SHM) if n.startswith(PREFIX)}


def no_name_is_left():
    """A landing's name lives one round trip; another worker's may be seen in
    passing, so look again before saying one stayed."""
    for _ in range(200):
        if not names_in_shm():
            return True
        time.sleep(0.01)
    return False


# ---------------------------------------------------------------------------
# the same bytes, whichever way


@pytest.mark.parametrize("blocks", [1, 13, 63])
def test_mapped_and_socket_replies_are_the_written_bytes_in_request_order(client, rng, blocks):
    """The first reply comes over the socket and the later ones through the
    mapping; both name every block as written, an empty block and a block the
    daemon cannot serve in the middle included."""
    written = [[blob(rng, int(rng.integers(3000, 4000))) for _ in range(3)] for _ in range(blocks)]
    if blocks > 1:
        for r in range(3):
            written[blocks // 3][r] = b""
    sid = 600 + blocks
    stage(client, sid, written)
    for r in range(3):
        bids = [ShuffleBlockId(sid, m, r) for m in range(blocks)]
        expect = [written[m][r] for m in range(blocks)]
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
        del got, g
    stats = client.fetch_stats()
    sizes = [sum(len(written[m][r]) for m in range(blocks)) for r in range(3)]
    assert stats == {
        "fetch_replies": 3, "landed_fresh": 1, "landed_reused": 0, "landed_mapped": 2,
        "mapped_bytes": sizes[1] + sizes[2], "landings_offered": 1, "landings_refused": 0,
        "view_blocks": 3 * blocks, "view_bytes": sum(sizes),
    }
    assert no_name_is_left()
    client.remove_shuffle(sid)


def test_a_reply_without_a_body_is_the_reply_it_always_was(client, rng):
    """Nothing to size a landing by: no offer.  And with a landing, a reply
    without a body carries no mark and touches no mapping."""
    stage(client, 620, [[b"", blob(rng, 500)], [None, blob(rng, 700)]])
    empty = [ShuffleBlockId(620, 0, 0), ShuffleBlockId(620, 7, 0), ShuffleBlockId(620, 1, 0)]
    assert client.fetch_blocks(empty) == [b"", None, b""]
    assert landed(client) == (0, 0, 0, 0)
    full = [ShuffleBlockId(620, 0, 1), ShuffleBlockId(620, 1, 1)]
    assert [len(g) for g in client.fetch_blocks(full)] == [500, 700]  # over the socket, then the offer
    assert [len(g) for g in client.fetch_blocks(full)] == [500, 700]  # through the mapping
    assert landed(client) == (1, 0, 1, 1)
    assert client.fetch_blocks(empty) == [b"", None, b""]
    assert landed(client) == (1, 0, 1, 1) and client.fetch_stats()["fetch_replies"] == 4
    client.remove_shuffle(620)


# ---------------------------------------------------------------------------
# a landing with live views is the holder's


def test_a_client_holding_a_view_gets_its_next_reply_over_the_socket(client, rng):
    written = [[blob(rng, 3000) for _ in range(6)] for _ in range(3)]
    stage(client, 630, written)

    def fetch(r):
        return client.fetch_blocks([ShuffleBlockId(630, m, r) for m in range(3)])

    def expect(r):
        return [written[m][r] for m in range(3)]

    first = fetch(0)  # the socket; the offer follows
    held = fetch(1)  # the mapping
    assert landed(client) == (1, 0, 1, 1)
    kept = held[1][10:20]  # a slice of a view holds the mapping as the view did
    del held
    second = fetch(2)  # the mapping is held: the socket, into a buffer of its own
    third = fetch(3)
    assert landed(client) == (3, 0, 1, 1)
    assert first == expect(0) and second == expect(2) and third == expect(3)
    assert kept == written[1][1][10:20]
    del kept
    fourth = fetch(4)  # free again: the same landing, no new offer
    assert landed(client) == (3, 0, 2, 1)
    assert fourth == expect(4) and second == expect(2) and third == expect(3)
    client.remove_shuffle(630)


def test_a_request_says_free_only_while_no_view_lives(daemon, rng):
    """The bit on the wire, seen from the daemon's side of ``_serve_fetch``."""
    seen = []
    serve = daemon._serve_fetch

    def spy(conn, tag, bids):
        seen.append(tag)
        return serve(conn, tag, bids)

    daemon._serve_fetch = spy
    try:
        with closing(DaemonClient(daemon.address)) as client:
            stage(client, 640, [[blob(rng, 900)]])
            bid = [ShuffleBlockId(640, 0, 0)]
            client.fetch_blocks(bid)  # nothing offered yet
            held = client.fetch_blocks(bid)  # free
            client.fetch_blocks(bid)  # held
            del held
            client.fetch_blocks(bid)  # free
            client.remove_shuffle(640)
    finally:
        del daemon._serve_fetch
    assert seen == [0, TAG_LANDING_FREE, 0, TAG_LANDING_FREE]


def test_no_mapping_is_unmapped_under_a_live_view(daemon, rng):
    """A view held past its landing's replacement, past ``close()`` and past
    the client itself still reads its bytes: the mapping goes with its last
    holder, on the client's side and whatever the daemon did with its own."""
    written = [blob(rng, 5000), blob(rng, 200_000), blob(rng, 6000)]
    client = DaemonClient(daemon.address)
    stage(client, 650, [written])

    def fetch(r):
        [got] = client.fetch_blocks([ShuffleBlockId(650, 0, r)])
        return got

    assert fetch(0) == written[0]  # the socket, then a landing of 2 x 5,000 B in pages
    small = fetch(0)  # the mapping
    assert landed(client) == (1, 0, 1, 1)
    first_landing = client._mapped
    assert fetch(1) == written[1]  # too large: the socket, then a new offer — ``small`` holds the old landing
    assert landed(client) == (2, 0, 1, 2) and client._mapped is not first_landing
    large = fetch(1)  # the new landing
    assert landed(client) == (2, 0, 2, 2)
    del first_landing
    gc.collect()
    assert small == written[0] and large == written[1]
    client.remove_shuffle(650)
    client.close()
    for _ in range(500):  # the daemon drops its side of the landing with the connection
        if not daemon._landings:
            break
        time.sleep(0.01)
    assert not daemon._landings
    del client
    gc.collect()
    assert small == written[0] and large == written[1]
    assert bytes(small[-10:]) == written[0][-10:] and bytes(large[-10:]) == written[1][-10:]
    holder = small.obj  # the mapping itself: it cannot be closed while a view is out
    with pytest.raises(BufferError):
        holder.close()
    del small, large
    holder.close()
    assert no_name_is_left()


# ---------------------------------------------------------------------------
# the landing follows the replies


def test_a_larger_reply_comes_over_the_socket_then_a_new_offer_then_mapped(client, rng):
    page = wire.mmap.PAGESIZE
    sizes = [20_000, 30_000, 2 * page * 5 + 1, 100 * page, 1000]
    written = [blob(rng, n) for n in sizes]
    stage(client, 660, [written])

    def fetch(r):
        [got] = client.fetch_blocks([ShuffleBlockId(660, 0, r)])
        assert got == written[r]
        del got
        return (*landed(client), len(client._mapped))

    capacity = -(-LANDING_HEADROOM * 20_000 // page) * page
    assert capacity == 10 * page
    assert fetch(0) == (1, 0, 0, 1, capacity)  # the socket; offered at twice the reply, in whole pages
    assert fetch(0) == (1, 0, 1, 1, capacity)
    assert fetch(1) == (1, 0, 2, 1, capacity)  # a larger reply that fits
    assert fetch(2) == (2, 0, 2, 2, 21 * page)  # one byte over the landing: the socket, a new offer at its size
    assert fetch(2) == (2, 0, 3, 2, 21 * page)  # mapped
    assert fetch(3) == (3, 0, 3, 3, 200 * page)
    assert fetch(3) == (3, 0, 4, 3, 200 * page)
    # a reply under a LANDING_SLACK-th of the landing still lands in it, and the landing is let go for one its size
    assert 200 * page > LANDING_SLACK * page
    assert fetch(4) == (3, 0, 5, 4, page)
    assert fetch(4) == (3, 0, 6, 4, page)
    assert client.fetch_stats()["landings_refused"] == 0
    assert no_name_is_left()
    client.remove_shuffle(660)


def test_one_offer_serves_a_stage_of_like_replies(client, rng):
    """Replies between a half and twice the first keep one landing."""
    sizes = [10_000, 19_000, 5_200, 12_345, 20_000, 5_120]  # the landing is 20,480 B: five pages
    written = [blob(rng, n) for n in sizes]
    stage(client, 670, [written])
    for r in range(len(sizes)):
        [got] = client.fetch_blocks([ShuffleBlockId(670, 0, r)])
        assert got == written[r]
        del got
    assert landed(client) == (1, 0, len(sizes) - 1, 1)
    assert client.fetch_stats()["mapped_bytes"] == sum(sizes[1:])
    client.remove_shuffle(670)


# ---------------------------------------------------------------------------
# a refused offer


def tamper_wrong_owner(path, header, monkeypatch, tmp_path):
    monkeypatch.setattr(os, "geteuid", lambda real=os.geteuid(): real + 1)


def tamper_short_file(path, header, monkeypatch, tmp_path):
    os.truncate(path, header["capacity"] - 1)


def tamper_symlink(path, header, monkeypatch, tmp_path):
    target = tmp_path / "elsewhere"
    target.write_bytes(bytes(header["capacity"]))
    os.unlink(path)
    os.symlink(target, path)


def tamper_no_such_name(path, header, monkeypatch, tmp_path):
    os.unlink(path)
    open(path, "wb").close()  # for the client's own unlink
    header["name"] = header["name"][:-1] + ("0" if header["name"][-1] != "0" else "1")


@pytest.mark.parametrize("tamper", [tamper_wrong_owner, tamper_short_file, tamper_symlink, tamper_no_such_name],
                         ids=["wrong-owner", "short-file", "a-symlink", "no-such-name"])
def test_a_refused_offer_leaves_the_socket_for_the_connections_life(client, rng, monkeypatch, tmp_path, tamper):
    written = [blob(rng, 3000 - 100 * r) for r in range(5)]
    stage(client, 680, [written])
    exchange, refusals = client._exchange, []

    def tampered(op, header, bodies):
        if op != DaemonOp.OFFER_LANDING:
            return exchange(op, header, bodies)
        tamper(os.path.join(SHM, header["name"]), header, monkeypatch, tmp_path)
        try:
            return exchange(op, header, bodies)
        except RuntimeError as e:
            refusals.append(str(e))
            raise

    client._exchange = tampered
    for r in range(5):
        [got] = client.fetch_blocks([ShuffleBlockId(680, 0, r)])
        assert got == written[r]
        del got
    monkeypatch.undo()
    stats = client.fetch_stats()
    assert (stats["landings_offered"], stats["landings_refused"], stats["landed_mapped"]) == (1, 1, 0)
    assert (stats["landed_fresh"], stats["landed_reused"]) == (1, 4)  # the socket's kept buffer, as ever
    assert len(refusals) == 1 and client._mapped is None and not client._may_offer
    assert no_name_is_left()
    # the daemon serves on: this connection's other ops, and a client that offers well
    assert client.stats(680)["exchanged"] is True
    with closing(DaemonClient(client._sock.getpeername())) as other:
        for r in range(3):
            [got] = other.fetch_blocks([ShuffleBlockId(680, 0, r)])
            assert got == written[r]
            del got
        assert landed(other) == (1, 0, 2, 1)
    client.remove_shuffle(680)


@pytest.mark.parametrize(
    "name, capacity, error",
    [("", 4096, ValueError), ("../etc/passwd", 4096, ValueError), (".hidden", 4096, ValueError),
     ("a/b", 4096, ValueError), (PREFIX + "x", 0, ValueError), (PREFIX + "x", MAX_FRAME_BYTES + 1, ValueError),
     (PREFIX + "not-there", 4096, FileNotFoundError)],
    ids=["empty", "dot-dot", "a-dot-name", "a-path", "no-capacity", "over-a-frame", "not-there"],
)
def test_the_daemon_attaches_only_a_plain_name_of_a_frames_size(name, capacity, error):
    with pytest.raises(error):
        attach_landing(name, capacity)


def test_the_daemon_attaches_no_directory_and_maps_what_it_was_offered():
    name = PREFIX + "test-" + os.urandom(8).hex()
    path = os.path.join(SHM, name)
    os.mkdir(path)
    try:
        with pytest.raises(OSError):
            attach_landing(name, 4096)
    finally:
        os.rmdir(path)
    with open(path, "wb") as f:
        f.write(bytes(range(256)) * 32)
    try:
        landing = attach_landing(name, 8192)
    finally:
        os.unlink(path)
    assert landing.dtype == np.uint8 and len(landing) == 8192 and landing.flags.writeable
    assert bytes(landing[:256]) == bytes(range(256))


def test_a_client_that_cannot_make_a_landing_stays_on_the_socket(client, rng, monkeypatch):
    """No ``/dev/shm`` on this host: counted as a refusal, once."""
    monkeypatch.setattr(wire, "_SHM_DIR", "/nonexistent-shm-dir")
    written = [blob(rng, 2000)]
    stage(client, 690, [written])
    for _ in range(3):
        [got] = client.fetch_blocks([ShuffleBlockId(690, 0, 0)])
        assert got == written[0]
        del got
    stats = client.fetch_stats()
    assert (stats["landings_offered"], stats["landings_refused"], stats["landed_mapped"]) == (1, 1, 0)
    assert (stats["landed_fresh"], stats["landed_reused"]) == (1, 2)
    client.remove_shuffle(690)


def test_a_client_of_a_daemon_elsewhere_never_offers(daemon, rng, monkeypatch):
    monkeypatch.setattr(wire, "_is_loopback", lambda sock: False)
    with closing(DaemonClient(daemon.address)) as client:
        stage(client, 700, [[blob(rng, 2000)]])
        for _ in range(3):
            client.fetch_blocks([ShuffleBlockId(700, 0, 0)])
        assert landed(client) == (1, 2, 0, 0)
        client.remove_shuffle(700)
    monkeypatch.undo()
    one, other = socket.socketpair()
    with closing(socket.create_connection(daemon.address)) as tcp, closing(one), closing(other):
        assert wire._is_loopback(tcp) is True and wire._is_loopback(one) is False


# ---------------------------------------------------------------------------
# a peer that never offers: today's wire


def raw_fetch(sock, tag, bids):
    body = struct.pack("<QI", tag, len(bids)) + b"".join(struct.pack("<iii", *b) for b in bids)
    sock.sendall(struct.pack("<IQQ", int(AmId.FETCH_BLOCK_REQ), 0, len(body)) + body)
    out = b""
    while len(out) < 20:
        out += sock.recv(20 - len(out))
    _, hlen, blen = struct.unpack("<IQQ", out)
    while len(out) < 20 + hlen + blen:
        out += sock.recv(20 + hlen + blen - len(out))
    return out


@pytest.mark.parametrize("tag", [0, TAG_LANDING_FREE, 0x1122334455667788, 0xFFFFFFFFFFFFFFFF],
                         ids=["zero", "the-free-bit", "the-fixtures", "every-bit"])
def test_a_connection_that_never_offered_gets_todays_bytes_whatever_its_tag(daemon, client, rng, tag):
    """The JVM shim's tag is its own: without an offer no bit of it means
    anything, and the reply is the tag's echo, the sizes and the body."""
    written = [blob(rng, 700), b"", blob(rng, 1300)]
    stage(client, 710, [[w] for w in written])
    bids = [(710, 0, 0), (710, 1, 0), (710, 9, 0), (710, 2, 0)]
    header = struct.pack("<QI", tag, 4) + struct.pack("<4q", 700, 0, -1, 1300)
    want = struct.pack("<IQQ", int(AmId.FETCH_BLOCK_REQ_ACK), len(header), 2000) + header + written[0] + written[2]
    with closing(socket.create_connection(daemon.address)) as raw:
        assert raw_fetch(raw, tag, bids) == want
        assert raw_fetch(raw, tag, bids) == want
    client.remove_shuffle(710)


def test_the_mapped_reply_on_the_wire_is_its_two_headers_and_the_blocks_are_in_the_landing(daemon, client, rng):
    """What ``docs/SHIM_PROTOCOL.md`` says of a client that offers, spoken raw."""
    written = [blob(rng, 700), b"", blob(rng, 1300)]
    stage(client, 715, [[w] for w in written])
    bids = [(715, 0, 0), (715, 1, 0), (715, 9, 0), (715, 2, 0)]
    sizes = struct.pack("<4q", 700, 0, -1, 1300)
    name = PREFIX + "test-" + os.urandom(8).hex()
    path = os.path.join(SHM, name)
    with closing(socket.create_connection(daemon.address)) as raw:
        landing = wire.make_landing(path, 4096)
        try:
            offer = json.dumps({"name": name, "capacity": 4096}).encode()
            raw.sendall(struct.pack("<IQQ", DaemonOp.OFFER_LANDING, len(offer), 0) + offer)
            assert _read_frame(raw)[1] == {"ok": True}
        finally:
            os.unlink(path)
        # bit 0 set: no body on the socket, bit 1 of the echoed tag set, the blocks back to back from offset 0
        header = struct.pack("<QI", TAG_LANDING_FREE | TAG_BODY_MAPPED, 4) + sizes
        assert raw_fetch(raw, TAG_LANDING_FREE, bids) == struct.pack(
            "<IQQ", int(AmId.FETCH_BLOCK_REQ_ACK), len(header), 0) + header
        assert landing[:2000] == written[0] + written[2]
        # bit 0 clear: the landing is the client's — the reply it always was, and not a byte of the landing touched
        landing[:2000] = bytes(2000)
        header = struct.pack("<QI", 0, 4) + sizes
        assert raw_fetch(raw, 0, bids) == struct.pack(
            "<IQQ", int(AmId.FETCH_BLOCK_REQ_ACK), len(header), 2000) + header + written[0] + written[2]
        assert landing[:2000] == bytes(2000)
        # a reply over the landing's capacity crosses the socket whatever the bit says
        big = [(715, 0, 0), (715, 2, 0)] * 3
        reply = raw_fetch(raw, TAG_LANDING_FREE, big)
        assert struct.unpack_from("<IQQ", reply)[2] == 6000 and struct.unpack_from("<Q", reply, 20)[0] == TAG_LANDING_FREE
    client.remove_shuffle(715)


def test_fixture_12_is_an_offer_and_one_of_a_name_nobody_made_is_refused(daemon):
    """The offer as the fixture has it, replayed raw: the daemon parses it,
    finds no such file, says so, and keeps the connection."""
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    try:
        import gen_shim_fixtures as gen
    finally:
        sys.path.pop(0)
    frame = gen.fixtures()["12_offer_landing.bin"]
    with open(os.path.join(gen.FIXTURE_DIR, "12_offer_landing.bin"), "rb") as f:
        assert f.read() == frame
    op, hlen, blen = struct.unpack_from("<IQQ", frame)
    assert (op, blen) == (DaemonOp.OFFER_LANDING, 0) and len(frame) == 20 + hlen
    with closing(socket.create_connection(daemon.address)) as raw:
        raw.sendall(frame)
        _, meta, body = _read_frame(raw)
        assert meta["ok"] is False and "FileNotFoundError" in meta["error"] and body == b""
        raw.sendall(gen.fixtures()["05_run_exchange.bin"])  # in step: the next op is answered (no such shuffle)
        assert _read_frame(raw)[1]["ok"] is False


# ---------------------------------------------------------------------------
# a client that dies


CHILD = r"""
import json, sys
sys.path.insert(0, {root!r})
from sparkucx_tpu.core.block import ShuffleBlockId
from sparkucx_tpu.shuffle.daemon import DaemonClient
client = DaemonClient(({host!r}, {port}))
bid = [ShuffleBlockId(720, 0, 0)]
first = bytes(client.fetch_blocks(bid)[0])
held = client.fetch_blocks(bid)
print(json.dumps({{"stats": client.fetch_stats(), "equal": first == bytes(held[0])}}), flush=True)
sys.stdin.readline()
"""


def test_a_client_killed_after_its_first_mapped_reply_leaves_no_name(daemon, client, rng):
    """The name went at the offer's ack: a client that dies holding the
    landing (and a view of it) leaves nothing in ``/dev/shm``, the daemon lets
    its side go with the connection and serves the others."""
    written = blob(rng, 50_000)
    stage(client, 720, [[written]])
    host, port = daemon.address
    before = daemon.stage_stats()["connections"]
    child = subprocess.Popen(
        [sys.executable, "-c", CHILD.format(root=ROOT, host=host, port=port)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    try:
        report = json.loads(child.stdout.readline())
        assert report["equal"] is True
        assert report["stats"]["landed_mapped"] == 1 and report["stats"]["landings_offered"] == 1
        assert daemon.stage_stats()["connections"] == before + 1
        assert len(daemon._landings) >= 1
        assert no_name_is_left()
        child.send_signal(signal.SIGKILL)
        child.wait(TIMEOUT)
    finally:
        child.kill()
        child.wait(TIMEOUT)
        child.stdin.close()
        child.stdout.close()
    for _ in range(500):
        if daemon.stage_stats()["connections"] == before:
            break
        time.sleep(0.01)
    assert daemon.stage_stats()["connections"] == before
    assert no_name_is_left()
    for _ in range(3):  # the daemon serves its other connections, mapped and all
        [got] = client.fetch_blocks([ShuffleBlockId(720, 0, 0)])
        assert got == written
        del got
    assert landed(client) == (1, 0, 2, 1)
    client.remove_shuffle(720)


# ---------------------------------------------------------------------------
# threads of one client


def test_threads_share_one_client_and_one_landing(client, rng):
    """Reduce tasks of one executor on one client: a thread that finds the
    landing held by another's views is served over the socket, and each
    thread's blocks are its own whatever the others fetch meanwhile."""
    written = [[blob(rng, 2000 + 100 * r) for r in range(4)] for _ in range(5)]
    stage(client, 730, written)
    rounds, errors = 60, []

    def task(reducers):
        try:
            for i in range(rounds):
                r = reducers[i % len(reducers)]
                got = client.fetch_blocks([ShuffleBlockId(730, m, r) for m in range(5)])
                if i % 3 == 0:
                    time.sleep(0)  # hold the views over a switch
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
    assert stats["fetch_replies"] == 3 * rounds
    assert stats["landed_reused"] + stats["landed_fresh"] + stats["landed_mapped"] == 3 * rounds
    assert stats["landed_mapped"] > 0 and stats["landings_offered"] == 1 and stats["landings_refused"] == 0
    assert stats["view_blocks"] == 3 * rounds * 5
    client.remove_shuffle(730)


# ---------------------------------------------------------------------------
# it counts itself


def test_the_daemon_counts_its_mapped_replies_and_the_offers(rng):
    with closing(ShuffleDaemon(TpuShuffleConf(), num_executors=1, port=0)) as daemon, \
            closing(DaemonClient(daemon.address)) as client:
        stage(client, 740, [[blob(rng, 4000)] for _ in range(3)])
        bids = [ShuffleBlockId(740, m, 0) for m in range(3)]
        for _ in range(4):
            client.fetch_blocks(bids)
        held = client.fetch_blocks(bids)
        client.fetch_blocks(bids)  # over the socket: ``held``
        del held
        client.stats(740)  # a frame is counted after its reply is sent: one more frame, and the last fetch is in
        rows = {r["op"]: r for r in daemon.op_stats()}
        assert (rows["fetch_block"]["frames"], rows["fetch_block"]["mapped"]) == (6, 4)
        assert (rows["offer_landing"]["frames"], rows["offer_landing"]["mapped"]) == (1, 0)
        assert all(r["mapped"] == 0 for op, r in rows.items() if op != "fetch_block")
        text = client.metrics_text()
        assert 'sparkucx_tpu_daemon_mapped_total{op="fetch_block"} 4' in text
        stats = client.fetch_stats()
        assert (stats["landed_mapped"], stats["mapped_bytes"]) == (4, 4 * 12_000)
        from sparkucx_tpu.obs.metrics import MetricsRegistry

        registry = MetricsRegistry()
        client.register_metrics(registry)
        text = registry.prometheus_text()
        for name, value in (("landed_mapped", 4), ("mapped_bytes", 48_000), ("landings_offered", 1),
                            ("landings_refused", 0), ("landed_fresh", 2)):
            assert f"sparkucx_tpu_daemonclient_{name} {value}" in text


def test_a_mapped_frames_copy_is_a_span_inside_its_send(daemon, client, rng):
    """Under full tracing ``locate`` and ``send`` keep their cuts, and a frame
    whose body went into the landing has one child more under ``send``:
    ``daemon.fetch_block.send.mapped``, over the copy."""
    stage(client, 750, [[blob(rng, 30_000)] for _ in range(4)])
    bids = [ShuffleBlockId(750, m, 0) for m in range(4)]
    client.fetch_blocks(bids)  # the socket; the landing is offered
    TRACER.clear()
    TRACER.enable()
    try:
        client.fetch_blocks(bids)
        held = client.fetch_blocks(bids)
        client.fetch_blocks(bids)  # over the socket
        del held
        client.stats(750)  # a frame's phases are recorded after its reply: one more frame, and they are in
    finally:
        TRACER.disable()
    events = [e for e in TRACER.events if e["ph"] == "X" and e["name"].startswith("daemon.fetch_block")]
    TRACER.clear()
    names = collections.Counter(e["name"] for e in events)
    assert names == {"daemon.fetch_block": 3, "daemon.fetch_block.locate": 3, "daemon.fetch_block.send": 3,
                     "daemon.fetch_block.send.mapped": 2}
    by_id = {e["span_id"]: e for e in events}

    def bounds(e):
        return round(e["ts"] * 1e3), round((e["ts"] + e["dur"]) * 1e3)

    for copy in (e for e in events if e["name"] == "daemon.fetch_block.send.mapped"):
        send = by_id[copy["parent_id"]]
        assert send["name"] == "daemon.fetch_block.send"
        assert by_id[send["parent_id"]]["name"] == "daemon.fetch_block"
        assert bounds(copy)[0] == bounds(send)[0] and bounds(copy)[1] <= bounds(send)[1]
    for send in (e for e in events if e["name"] == "daemon.fetch_block.send"):
        frame = by_id[send["parent_id"]]
        [locate] = [e for e in events if e["name"].endswith(".locate") and e["parent_id"] == frame["span_id"]]
        assert bounds(locate) == (bounds(frame)[0], bounds(send)[0]) and bounds(send)[1] == bounds(frame)[1]
    client.remove_shuffle(750)
