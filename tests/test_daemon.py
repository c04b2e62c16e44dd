"""Tests for the shuffle daemon + client — the JVM-shim protocol surface."""

import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.block import ShuffleBlockId
from sparkucx_tpu.shuffle.daemon import DaemonClient, DaemonOp, ShuffleDaemon
from sparkucx_tpu.shuffle.reader import default_deserializer


@pytest.fixture(scope="module")
def daemon():
    d = ShuffleDaemon(
        TpuShuffleConf(staging_capacity_per_executor=1 << 20, num_executors=2),
        num_executors=2,
    )
    yield d
    d.close()


@pytest.fixture
def client(daemon):
    c = DaemonClient(daemon.address)
    yield c
    c.close()


class TestDaemonFlow:
    def test_full_shuffle_through_wire(self, client, rng):
        M, R, SID = 3, 4, 0
        client.create_shuffle(SID, M, R)
        oracle = {}
        for m in range(M):
            w = client.open_map_writer(SID, m)
            for r in range(R):
                payload = rng.integers(0, 256, size=int(rng.integers(1, 3000)), dtype=np.uint8).tobytes()
                oracle[(m, r)] = payload
                # stream in two chunks to exercise repeated WritePartition
                client.write_partition(w, r, payload[: len(payload) // 2])
                client.write_partition(w, r, payload[len(payload) // 2 :])
            lengths = client.commit_map(w)
            assert lengths.tolist() == [len(oracle[(m, r)]) for r in range(R)]
        stats = client.stats(SID)
        assert stats["num_mappers"] == M and not stats["exchanged"]
        client.run_exchange(SID)
        assert client.stats(SID)["exchanged"]

        bids = [ShuffleBlockId(SID, m, r) for m in range(M) for r in range(R)]
        blocks = client.fetch_blocks(bids)
        for bid, blk in zip(bids, blocks):
            assert blk == oracle[(bid.map_id, bid.reduce_id)]
        client.remove_shuffle(SID)

    def test_groupbytest_records_over_the_socket_equal_the_plain_groupby(self, groupbytest):
        """The upstream gate job's record shape through a daemon at the
        default conf, the client side speaking sockets only: every committed
        length, every served block and every group is the reference's."""
        records = groupbytest.records(4)
        daemon = ShuffleDaemon(TpuShuffleConf(), num_executors=2, port=0)
        try:
            client = DaemonClient(daemon.address)
            client.create_shuffle(0, records.num_mappers, records.reducers)
            for m, parts in enumerate(records.blocks):
                writer = client.open_map_writer(0, m)
                for r, payload in parts:
                    client.write_partition(writer, r, payload)
                written = dict(parts)
                assert client.commit_map(writer).tolist() == [
                    len(written.get(r, b"")) for r in range(records.reducers)
                ]
            client.run_exchange(0)
            checks = []
            for r in range(records.reducers):
                check = records.check(r, full=True)
                bids = [ShuffleBlockId(0, m, r) for m in records.mappers_of(r)]
                for payload in client.fetch_blocks(bids):
                    assert payload is not None
                    for key, value in default_deserializer(payload):
                        check.add(key, value)
                assert check.ok(), f"reduce task {r} differs from the plain GroupBy"
                checks.append(check)
            assert records.complete(checks)
            assert set(daemon.manager.cluster.executed_lowerings()["exchange"]) == {"dense"}
            client.remove_shuffle(0)
            client.close()
        finally:
            daemon.close()

    def test_error_propagation(self, client):
        with pytest.raises(RuntimeError, match="unknown shuffle|KeyError"):
            client.run_exchange(777)

    def test_fetch_miss_returns_none(self, client):
        client.create_shuffle(1, 1, 1)
        w = client.open_map_writer(1, 0)
        client.write_partition(w, 0, b"only")
        client.commit_map(w)
        client.run_exchange(1)
        [hit, miss] = client.fetch_blocks([ShuffleBlockId(1, 0, 0), ShuffleBlockId(1, 0, 99)])
        assert hit == b"only"
        assert miss is None
        client.remove_shuffle(1)

    def test_two_clients_one_daemon(self, daemon, rng):
        # two executor connections writing different maps of one shuffle
        c1, c2 = DaemonClient(daemon.address), DaemonClient(daemon.address)
        try:
            c1.create_shuffle(2, 2, 2)
            w1 = c1.open_map_writer(2, 0)
            c1.write_partition(w1, 0, b"from-c1")
            c1.commit_map(w1)
            w2 = c2.open_map_writer(2, 1)
            c2.write_partition(w2, 1, b"from-c2")
            c2.commit_map(w2)
            c1.run_exchange(2)
            [a] = c2.fetch_blocks([ShuffleBlockId(2, 0, 0)])
            [b] = c1.fetch_blocks([ShuffleBlockId(2, 1, 1)])
            assert a == b"from-c1" and b == b"from-c2"
            c1.remove_shuffle(2)
        finally:
            c1.close()
            c2.close()

    def test_unknown_op_acks_error(self, daemon):
        import socket
        import struct

        s = socket.create_connection(daemon.address)
        s.sendall(struct.pack("<IQQ", 99, 2, 0) + b"{}")
        hdr = b""
        while len(hdr) < 20:
            hdr += s.recv(20 - len(hdr))
        op, hlen, blen = struct.unpack("<IQQ", hdr)
        payload = b""
        while len(payload) < hlen:
            payload += s.recv(hlen - len(payload))
        assert b'"ok": false' in payload
        s.close()

    def test_hostile_frames_cannot_take_the_daemon_down(self, daemon):
        """Protocol fuzz at the Spark-facing boundary: oversized length
        claims, truncated frames, garbage headers, and random byte storms
        each cost at most their own connection — the daemon keeps serving
        well-formed clients afterwards (the endpoint-eviction policy,
        UcxWorkerWrapper.scala:248-253)."""
        import socket
        import struct

        rng = np.random.default_rng(0)
        hostile = [
            # oversized header+body claim (the _MAX_FRAME guard): must be
            # dropped without streaming terabytes
            struct.pack("<IQQ", DaemonOp.CREATE_SHUFFLE, 1 << 60, 1 << 60),
            # truncated: header promises more bytes than ever arrive
            struct.pack("<IQQ", DaemonOp.CREATE_SHUFFLE, 64, 0) + b"{\"x\"",
            # valid frame layout, unparseable JSON header
            struct.pack("<IQQ", DaemonOp.CREATE_SHUFFLE, 7, 0) + b"not-js}",
            # random byte storm (may parse as a huge claim or garbage op)
            rng.integers(0, 256, size=333, dtype=np.uint8).tobytes(),
            # shorter than one frame header
            b"\x01\x02\x03",
        ]
        for i, frame in enumerate(hostile):
            s = socket.create_connection(daemon.address, timeout=5)
            try:
                s.settimeout(5)
                # the daemon may RST mid-send/shutdown when it drops the
                # connection — that reset IS the expected eviction behavior
                try:
                    s.sendall(frame)
                    s.shutdown(socket.SHUT_WR)
                    while s.recv(4096):  # drain any reply, bounded
                        pass
                except (socket.timeout, OSError):
                    pass
            finally:
                s.close()
            # after each hostile connection, a fresh well-formed client works
            probe = DaemonClient(daemon.address)
            try:
                sid = 900 + i
                probe.create_shuffle(sid, 1, 1)
                w = probe.open_map_writer(sid, 0)
                probe.write_partition(w, 0, b"still-alive")
                probe.commit_map(w)
                probe.run_exchange(sid)
                [blk] = probe.fetch_blocks([ShuffleBlockId(sid, 0, 0)])
                assert blk == b"still-alive"
                probe.remove_shuffle(sid)
            finally:
                probe.close()


class TestGoldenWireFixtures:
    """The jvm/fixtures/*.bin frames are the EXACT bytes the Java shim's
    DaemonClient encodes (FixtureCheck.java re-encodes them in CI).  Here the
    Python side holds up its half of the contract: the generator reproduces
    the committed files bit-for-bit (drift guard), and a live daemon driven by
    the raw fixture bytes executes a full write -> exchange -> fetch cycle."""

    def _gen(self):
        import importlib
        import os
        import sys

        scripts = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts")
        sys.path.insert(0, scripts)
        try:
            mod = importlib.import_module("gen_shim_fixtures")
            return importlib.reload(mod)
        finally:
            sys.path.remove(scripts)

    def test_fixture_files_match_generator(self):
        import os

        gen = self._gen()
        for name, frame in gen.fixtures().items():
            path = os.path.join(gen.FIXTURE_DIR, name)
            with open(path, "rb") as f:
                assert f.read() == frame, f"fixture {name} drifted — regen + sync FixtureCheck.java"

    def test_daemon_decodes_java_frames_end_to_end(self):
        import os
        import socket
        import struct

        from sparkucx_tpu.shuffle.daemon import _read_frame

        gen = self._gen()
        fx = {n: open(os.path.join(gen.FIXTURE_DIR, n), "rb").read() for n in gen.fixtures()}
        d = ShuffleDaemon(
            TpuShuffleConf(staging_capacity_per_executor=1 << 20, num_executors=1),
            num_executors=1,
        )
        client = DaemonClient(d.address)  # side channel for the non-fixture maps
        raw = socket.create_connection(d.address)

        def send_fixture(name, expect_ok=True):
            raw.sendall(fx[name])
            frame = _read_frame(raw)
            assert frame is not None
            op, meta, body = frame
            if expect_ok:
                assert meta.get("ok") is True, f"{name}: {meta}"
            return meta, body

        try:
            send_fixture("01_create_shuffle.bin")  # shuffle 7: 4 maps x 8 reduces

            # burn writer handles 0-2 so the fixture writer lands on handle 3
            # (the handle baked into 03/04), and give the fetch fixture's maps
            # (0 and 3) real payloads
            burn = [client.open_map_writer(gen.SHUFFLE_ID, m) for m in (0, 1, 3)]
            assert burn == [0, 1, 2]
            payload_m0 = b"\xaa" * 100
            payload_m3 = b"\xbb" * 300
            payload_m1r6 = b"\xcc" * 77  # fixture 09's only reduce-6 block
            client.write_partition(burn[0], gen.REDUCE_ID, payload_m0)
            client.write_partition(burn[1], 6, payload_m1r6)
            client.write_partition(burn[2], gen.REDUCE_ID, payload_m3)

            meta, _ = send_fixture("02_open_map_writer.bin")  # map 2 -> handle 3
            assert meta["writer"] == gen.WRITER

            send_fixture("03_write_partition.bin")  # 256 bytes to reduce 5
            _, commit_body = send_fixture("04_commit_map.bin")
            lengths = np.frombuffer(commit_body, dtype="<i8")
            assert lengths[gen.REDUCE_ID] == len(gen.WRITE_BODY)

            for w in burn:
                client.commit_map(w)

            send_fixture("05_run_exchange.bin")

            def raw_fetch(name):
                raw.sendall(fx[name])
                hdr = b""
                while len(hdr) < 20:
                    hdr += raw.recv(20 - len(hdr))
                _, hlen, blen = struct.unpack("<IQQ", hdr)
                reply_hdr = b""
                while len(reply_hdr) < hlen:
                    reply_hdr += raw.recv(hlen - len(reply_hdr))
                body = b""
                while len(body) < blen:
                    body += raw.recv(blen - len(body))
                tag, count = struct.unpack_from("<QI", reply_hdr)
                sizes = [
                    struct.unpack_from("<q", reply_hdr, 12 + 8 * i)[0] for i in range(count)
                ]
                return tag, count, sizes, body

            # batched fetch exactly as the Java client frames it
            tag, count, sizes, body = raw_fetch("06_fetch.bin")
            assert tag == gen.FETCH_TAG and count == len(gen.FETCH_MAPS)
            assert sizes == [len(payload_m0), len(payload_m3)]
            assert body[: sizes[0]] == payload_m0
            assert body[sizes[0] :] == payload_m3

            # the AQE partial-map read (Spark 3.x startMapIndex/endMapIndex):
            # maps [1, 3) x reduce 5 — map 1 committed nothing there (empty
            # block, size 0), map 2 holds the fixture's 256-byte write
            tag, count, sizes, body = raw_fetch("08_fetch_aqe_maprange.bin")
            assert tag == gen.FETCH_TAG and count == len(gen.AQE_MAPS)
            assert sizes == [0, len(gen.WRITE_BODY)]
            assert body == gen.WRITE_BODY

            # the AQE COALESCED read (09): reduce range 5..6 across EVERY
            # mapper — present and empty cells mixed; empties must answer
            # size 0 (a real committed-empty block), never -1 (a miss)
            tag, count, sizes, body = raw_fetch("09_fetch_coalesced_empty.bin")
            assert tag == gen.FETCH_TAG and count == len(gen.COALESCE_MAPS)
            assert sizes == [
                len(payload_m0), 0,              # map 0: r5 block, r6 empty
                0, len(payload_m1r6),            # map 1: r5 empty, r6 block
                len(gen.WRITE_BODY), 0,          # map 2: the fixture write
                len(payload_m3), 0,              # map 3
            ]
            assert body == payload_m0 + payload_m1r6 + gen.WRITE_BODY + payload_m3

            send_fixture("07_remove_shuffle.bin")
            with pytest.raises(RuntimeError):
                client.stats(gen.SHUFFLE_ID)
        finally:
            raw.close()
            client.close()
            d.close()


    def test_daemon_serves_the_java_batch_frame(self):
        """11: several blocks of one writer in one ``WritePartition`` frame,
        the bytes ``DaemonClient.java`` ``writePartitions`` encodes — written
        after fixture 03's one-block frame (partition 5 goes on), acked block
        by block, committed by fixture 04 and read back whole."""
        import os
        import socket

        from sparkucx_tpu.shuffle.daemon import _read_frame

        gen = self._gen()
        fx = {n: open(os.path.join(gen.FIXTURE_DIR, n), "rb").read() for n in gen.fixtures()}
        d = ShuffleDaemon(
            TpuShuffleConf(staging_capacity_per_executor=1 << 20, num_executors=1), num_executors=1
        )
        client = DaemonClient(d.address)
        raw = socket.create_connection(d.address)

        def send_fixture(name):
            raw.sendall(fx[name])
            _, meta, body = _read_frame(raw)
            assert meta.get("ok") is True, f"{name}: {meta}"
            return meta, body

        try:
            send_fixture("01_create_shuffle.bin")
            burn = [client.open_map_writer(gen.SHUFFLE_ID, m) for m in (0, 1, 3)]
            assert burn == [0, 1, 2]
            assert send_fixture("02_open_map_writer.bin")[0]["writer"] == gen.WRITER
            meta, _ = send_fixture("11_write_partitions.bin")
            assert meta == {"ok": True, "written": [len(b) for b in gen.BATCH_BODIES]}
            _, commit_body = send_fixture("04_commit_map.bin")
            want = {1: gen.BATCH_BODIES[0], 5: gen.BATCH_BODIES[1] + gen.BATCH_BODIES[2], 6: gen.BATCH_BODIES[3]}
            assert np.frombuffer(commit_body, dtype="<i8").tolist() == [
                len(want.get(r, b"")) for r in range(gen.NUM_REDUCERS)
            ]
            for w in burn:
                client.commit_map(w)
            send_fixture("05_run_exchange.bin")
            bids = [ShuffleBlockId(gen.SHUFFLE_ID, gen.MAP_ID, r) for r in range(gen.NUM_REDUCERS)]
            assert client.fetch_blocks(bids) == [want.get(r, b"") for r in range(gen.NUM_REDUCERS)]
            row = {r["op"]: r for r in d.op_stats()}["write_partition"]
            assert (row["frames"], row["blocks"]) == (1, len(gen.BATCH_BODIES))
            send_fixture("07_remove_shuffle.bin")
        finally:
            raw.close()
            client.close()
            d.close()


class TestErrorEdges:
    """The error/edge wire paths the first eight fixtures skipped
    (VERDICT r4 item 6): oversized-frame rejection and daemon restart
    mid-job."""

    def test_oversized_frame_drops_connection_daemon_survives(self):
        import socket

        gen = TestGoldenWireFixtures._gen(self)
        import os

        oversized = open(
            os.path.join(gen.FIXTURE_DIR, "10_oversized_frame.bin"), "rb"
        ).read()
        d = ShuffleDaemon(
            TpuShuffleConf(staging_capacity_per_executor=1 << 18, num_executors=1),
            num_executors=1,
        )
        try:
            raw = socket.create_connection(d.address)
            raw.sendall(oversized)
            raw.settimeout(10)
            # the daemon must refuse BEFORE reading/allocating the 2 GiB body:
            # this connection is dropped (endpoint-eviction policy)
            assert raw.recv(1) == b"", "daemon accepted an oversized frame"
            raw.close()
            # ...and keeps serving new connections
            c = DaemonClient(d.address)
            c.create_shuffle(55, 1, 1)
            w = c.open_map_writer(55, 0)
            c.write_partition(w, 0, b"alive")
            c.commit_map(w)
            c.run_exchange(55)
            [blk] = c.fetch_blocks([ShuffleBlockId(55, 0, 0)])
            assert blk == b"alive"
            c.close()
        finally:
            d.close()

    def test_daemon_restart_mid_job(self, rng):
        """Kill the daemon after a partial map stage; a fresh daemon on a new
        port serves the re-run job from clean state — the task-retry
        discipline the reference never had (SURVEY §5.3: it only logs)."""
        conf = TpuShuffleConf(staging_capacity_per_executor=1 << 18, num_executors=1)
        d1 = ShuffleDaemon(conf, num_executors=1)
        c1 = DaemonClient(d1.address)
        c1.create_shuffle(9, 2, 2)
        w = c1.open_map_writer(9, 0)
        c1.write_partition(w, 0, b"lost-on-restart")
        c1.commit_map(w)  # map 0 committed; map 1 never runs
        d1.close()  # daemon dies mid-job
        c1.close()

        # driver-side retry: fresh daemon, SAME shuffle id, full re-run
        d2 = ShuffleDaemon(conf, num_executors=1)
        c2 = DaemonClient(d2.address)
        try:
            c2.create_shuffle(9, 2, 2)  # no stale state: re-create succeeds
            oracle = {}
            for m in range(2):
                w = c2.open_map_writer(9, m)
                for r in range(2):
                    payload = rng.integers(0, 256, size=200, dtype=np.uint8).tobytes()
                    oracle[(m, r)] = payload
                    c2.write_partition(w, r, payload)
                c2.commit_map(w)
            c2.run_exchange(9)
            bids = [ShuffleBlockId(9, m, r) for m in range(2) for r in range(2)]
            for bid, blk in zip(bids, c2.fetch_blocks(bids)):
                assert blk == oracle[(bid.map_id, bid.reduce_id)]
        finally:
            c2.close()
            d2.close()
