"""Tests for L0 contracts: block ids, memory blocks, operations, wire frames, config."""

import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf, parse_size
from sparkucx_tpu.core.block import (
    Block,
    BytesBlock,
    FileBackedBlock,
    MemoryBlock,
    ShuffleBlockId,
)
from sparkucx_tpu.core.definitions import (
    FRAME_HEADER_SIZE,
    AmId,
    MapperInfo,
    pack_fetch_req,
    pack_frame,
    unpack_fetch_req,
    unpack_frame_header,
)
from sparkucx_tpu.core.operation import (
    OperationResult,
    OperationStats,
    OperationStatus,
    Request,
)


class TestShuffleBlockId:
    def test_roundtrip(self):
        bid = ShuffleBlockId(3, 17, 42)
        data = bid.serialize()
        assert len(data) == bid.serialized_size() == 12
        assert ShuffleBlockId.deserialize(data) == bid

    def test_ordering_and_hash(self):
        a, b = ShuffleBlockId(0, 1, 2), ShuffleBlockId(0, 1, 3)
        assert a < b
        assert len({a, b, ShuffleBlockId(0, 1, 2)}) == 2

    def test_negative_ids_roundtrip(self):
        bid = ShuffleBlockId(-1, 0, 5)
        assert ShuffleBlockId.deserialize(bid.serialize()) == bid


class TestMemoryBlock:
    def test_host_view_and_close_hook(self):
        closed = []
        mb = MemoryBlock(np.arange(16, dtype=np.uint8), size=10, _on_close=closed.append)
        assert mb.host_view().tolist() == list(range(10))
        mb.close()
        mb.close()  # idempotent
        assert len(closed) == 1

    def test_to_bytes(self):
        mb = MemoryBlock(np.arange(8, dtype=np.uint8), size=4)
        assert mb.to_bytes() == bytes([0, 1, 2, 3])


class TestBlocks:
    def test_bytes_block(self):
        blk = BytesBlock(b"hello world")
        out = np.zeros(blk.get_size(), dtype=np.uint8)
        blk.get_block(out)
        assert out.tobytes() == b"hello world"

    def test_get_memory_block_default(self):
        # The reference stubs this as ??? (ShuffleTransport.scala:43); ours works.
        mb = BytesBlock(b"abc").get_memory_block()
        assert mb.to_bytes() == b"abc"

    def test_file_backed_block(self, tmp_path):
        p = tmp_path / "data.bin"
        p.write_bytes(b"0123456789")
        blk = FileBackedBlock(str(p), offset=2, length=5)
        out = np.zeros(5, dtype=np.uint8)
        blk.get_block(out)
        assert out.tobytes() == b"23456"
        # zero-copy serving view: a read-only mmap of just the segment,
        # created once (the peer server sends straight from the page cache)
        view = blk.memory_view()
        assert view.tobytes() == b"23456" and not view.flags.writeable
        assert blk.memory_view() is view  # cached, not re-mapped per fetch

    def test_file_backed_block_close_releases_mapping(self, tmp_path):
        """close() must release the cached mmap's fd NOW (unregistration used
        to just drop the registry entry, leaking one fd per served spill
        segment for the life of the process) and stay reusable after."""
        p = tmp_path / "data.bin"
        p.write_bytes(b"0123456789")
        blk = FileBackedBlock(str(p), offset=0, length=10)
        view = blk.memory_view()
        mapping = view._mmap  # the mmap.mmap owning the fd
        assert not mapping.closed
        del view
        blk.close()
        assert mapping.closed, "close() left the mapping (and its fd) open"
        blk.close()  # idempotent
        # the block is still servable: a fresh mapping is created on demand
        assert blk.memory_view().tobytes() == b"0123456789"
        # with an exported view alive, close() defers to GC instead of raising
        survivor = blk.memory_view()
        blk.close()
        assert survivor.tobytes() == b"0123456789"

    def test_unregister_closes_file_backed_blocks(self, tmp_path):
        from sparkucx_tpu.config import TpuShuffleConf
        from sparkucx_tpu.transport.tpu import TpuShuffleCluster

        cluster = TpuShuffleCluster(TpuShuffleConf(num_executors=1), num_executors=1)
        t = cluster.transport(0)
        p = tmp_path / "seg.bin"
        p.write_bytes(b"x" * 64)
        blk = FileBackedBlock(str(p), offset=0, length=64)
        bid = ShuffleBlockId(7, 0, 0)
        t.register(bid, blk)
        view = blk.memory_view()
        mapping = view._mmap
        del view
        t.unregister(bid)
        assert mapping.closed, "unregister left the block's mmap open"

    def test_file_backed_block_arbitrary_offset_and_empty(self, tmp_path):
        p = tmp_path / "odd.bin"
        payload = bytes(range(256)) * 40
        p.write_bytes(payload)
        # offsets far from any page boundary must still map correctly
        blk = FileBackedBlock(str(p), offset=4097, length=300)
        assert blk.memory_view().tobytes() == payload[4097 : 4097 + 300]
        empty = FileBackedBlock(str(p), offset=8, length=0)
        assert empty.memory_view().size == 0


class TestRequest:
    def test_complete_and_wait(self):
        req = Request()
        req.complete(OperationResult(OperationStatus.SUCCESS))
        assert req.completed()
        assert req.wait(timeout=1).status == OperationStatus.SUCCESS

    def test_poll_drives_completion(self):
        req = Request()
        state = {"calls": 0}

        def poll():
            state["calls"] += 1
            if state["calls"] >= 3:
                req.complete(OperationResult(OperationStatus.SUCCESS))
                return True
            return False

        req.attach_poll(poll)
        assert not req.completed()
        assert not req.completed()
        assert req.completed()
        assert state["calls"] == 3

    def test_cancel(self):
        req = Request()
        req.cancel()
        assert req.is_cancelled()
        assert req.wait().status == OperationStatus.CANCELED

    def test_stats_elapsed(self):
        stats = OperationStats()
        stats.mark_done(recv_size=128)
        assert stats.recv_size == 128
        assert stats.elapsed_ns() >= 0


class TestWireFrames:
    def test_frame_roundtrip(self):
        frame = pack_frame(AmId.FETCH_BLOCK_REQ, b"hdr", b"body!")
        am, hlen, blen = unpack_frame_header(frame)
        assert am == AmId.FETCH_BLOCK_REQ
        assert frame[FRAME_HEADER_SIZE : FRAME_HEADER_SIZE + hlen] == b"hdr"
        assert frame[FRAME_HEADER_SIZE + hlen :] == b"body!"
        assert blen == 5

    def test_fetch_req_roundtrip(self):
        assert unpack_fetch_req(pack_fetch_req(1, 2, 3)) == (1, 2, 3)

    def test_mapper_info_roundtrip(self):
        mi = MapperInfo(shuffle_id=7, map_id=3, partitions=((0, 100), (128, 50), (256, 0)))
        assert MapperInfo.unpack(mi.pack()) == mi

    def test_am_ids_match_reference(self):
        # 0-4: Definitions.scala:22-29 verbatim.  5-6: striped-wire extensions
        # (FetchBlockChunk / WireHello, docs/SHIM_PROTOCOL.md), 7-8:
        # replication extensions (ReplicaPut / ReplicaAck), 9-10: membership
        # gossip (MemberSuspect / MemberRejoin), 11-12: observability pulls
        # (TracePull / MetricsPull), 13: accept-backlog shed (ServerBusy),
        # 14: hot-holder advertisement (HotSetPull) — peer plane only, never
        # emitted at wire.streams=1 / replication.factor=0 / elastic off /
        # server.acceptBacklog=0 / serve.hotThresholdFetchesPerSec=0 with no
        # export/scrape call, so reference parity holds for every frame a
        # stock deployment sees.
        #
        # The pin list is generated from the SOURCE of core/definitions.py by
        # the analyzer's wire-schema extractor, then cross-checked against the
        # runtime enum: a new AmId cannot land without showing up here AND in
        # SHIM_PROTOCOL.md (the wire-schema pass gates the doc side in CI).
        import inspect

        from sparkucx_tpu.analysis.protocol import extract_am_ids
        from sparkucx_tpu.core import definitions

        extracted = extract_am_ids(inspect.getsource(definitions))
        assert extracted == {a.name: int(a) for a in AmId}
        assert sorted(extracted.values()) == list(range(15))
        assert AmId.FETCH_BLOCK_CHUNK == 5 and AmId.WIRE_HELLO == 6
        assert AmId.REPLICA_PUT == 7 and AmId.REPLICA_ACK == 8
        assert AmId.MEMBER_SUSPECT == 9 and AmId.MEMBER_REJOIN == 10
        assert AmId.TRACE_PULL == 11 and AmId.METRICS_PULL == 12
        assert AmId.SERVER_BUSY == 13
        assert AmId.HOT_SET_PULL == 14


class TestConf:
    def test_parse_size(self):
        assert parse_size("4k") == 4096
        assert parse_size("1m") == 1 << 20
        assert parse_size("30MB") == 30 << 20
        assert parse_size(512) == 512
        with pytest.raises(ValueError):
            parse_size("nope")

    def test_defaults_match_reference(self):
        c = TpuShuffleConf()
        assert c.min_buffer_size == 4096  # UcxShuffleConf.scala:33-39
        assert c.min_allocation_size == 1 << 20  # :41-48
        assert c.max_blocks_per_request == 50  # :88-93
        assert c.num_io_threads == 1  # :66-71
        assert c.use_wakeup is True  # :58-64
        assert c.serve_from_store is True  # UcxShuffleBlockResolver.scala:86

    def test_from_spark_conf(self):
        c = TpuShuffleConf.from_spark_conf(
            {
                "spark.shuffle.tpu.memory.preAllocateBuffers": "4k:16,1m:4",
                "spark.shuffle.tpu.memory.minBufferSize": "8k",
                "spark.shuffle.tpu.listener.sockaddr": "127.0.0.1:4242",
                "spark.shuffle.tpu.maxBlocksPerRequest": "10",
                "spark.shuffle.tpu.numExecutors": "8",
                "spark.executor.cores": "4",
            }
        )
        assert c.prealloc_buffers == {4096: 16, 1 << 20: 4}
        assert c.min_buffer_size == 8192
        assert c.listener_address == ("127.0.0.1", 4242)
        assert c.max_blocks_per_request == 10
        assert c.num_executors == 8
        assert c.num_client_workers == 4  # falls back to spark.executor.cores

    def test_from_spark_conf_sizes_and_service_knobs(self):
        # Parse/convert coverage for every knob the conf-registry analyzer
        # pass tracks that the round-trip test above doesn't touch: size
        # suffixes, ms durations, and the service-plane integers.
        c = TpuShuffleConf.from_spark_conf(
            {
                "spark.shuffle.tpu.numListenerThreads": "5",
                "spark.shuffle.tpu.wire.creditBytes": "32m",
                "spark.shuffle.tpu.wire.sockBufBytes": "8m",
                "spark.shuffle.tpu.membership.suspectAfterMs": "250",
            }
        )
        assert c.num_listener_threads == 5
        assert c.wire_credit_bytes == 32 << 20
        assert c.wire_sock_buf_bytes == 8 << 20
        assert c.membership_suspect_after_ms == 250

    def test_from_spark_conf_passes_over_a_key_that_left(self):
        # gatherImpl was a key until PR 44 (the gather's lowering is the
        # platform's own, as the scatter's always was): a Spark conf that
        # still sets it builds the default conf, as with any key not read
        c = TpuShuffleConf.from_spark_conf({"spark.shuffle.tpu.gatherImpl": "tiled"})
        assert c == TpuShuffleConf.from_spark_conf({})
        assert not hasattr(c, "gather_impl")
        with pytest.raises(TypeError, match="gather_impl"):
            TpuShuffleConf(gather_impl="xla")

    def test_validation(self):
        with pytest.raises(ValueError):
            TpuShuffleConf(block_alignment=100).validate()
        with pytest.raises(ValueError):
            TpuShuffleConf().replace(max_blocks_per_request=0)
