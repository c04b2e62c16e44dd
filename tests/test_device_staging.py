"""Device staging rounds (conf.device_staging): map output written as device
arrays, placed into HBM staging by the block-scatter kernel at seal, with no
host round trip.

The core check is bit-identity against the host-path oracle: the SAME payload
stream written via ``write_partition_device`` and via the host ``MapWriter``
must produce identical MapperInfo offset tables and identical post-exchange
bytes, for every host_recv_mode and for 1- and 8-executor meshes.  Alongside:
the no-host-round-trip guarantee (the host staging buffer is never allocated
for device rounds), uneven multi-round D2H rollover, the writer-layer conf
gate, the sealed-round geometry validation, and the reader's zero-copy block
views that the device path's consumers rely on."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.block import ShuffleBlockId
from sparkucx_tpu.core.operation import TransportError
from sparkucx_tpu.shuffle.reader import (
    BlockFetchResult,
    TpuShuffleReader,
    serialize_records,
)
from sparkucx_tpu.shuffle.writer import DeviceMapWriter, TpuShuffleMapOutputWriter
from sparkucx_tpu.store.hbm_store import HbmBlockStore
from sparkucx_tpu.transport.tpu import TpuShuffleCluster

ALIGN = 128
LANE = ALIGN // 4


def _rows_for(payload: bytes):
    """Bytes -> the device write unit: a (rows, lane) int32 array, one row per
    ``ALIGN`` bytes, zero-padded tail."""
    padded = -(-len(payload) // ALIGN) * ALIGN
    buf = np.zeros(padded, dtype=np.uint8)
    buf[: len(payload)] = np.frombuffer(payload, dtype=np.uint8)
    return jnp.asarray(buf.view(np.int32).reshape(-1, LANE))


def _conf(device: bool, n: int, cap: int, mode: str = "array", **kw) -> TpuShuffleConf:
    return TpuShuffleConf(
        **kw,
        staging_capacity_per_executor=cap,
        block_alignment=ALIGN,
        num_executors=n,
        device_staging=device,
        gather_impl="xla",
        host_recv_mode=mode,
        keep_device_recv=(mode == "device"),
    )


def _exchange(device: bool, n: int, M: int, R: int, cap: int, mode: str = "array", **kw):
    """Write rng(7) payloads (0-3000 bytes, uneven) through the chosen path,
    commit, exchange.  Same seed both paths -> byte-identical input stream."""
    cluster = TpuShuffleCluster(_conf(device, n, cap, mode, **kw), num_executors=n)
    meta = cluster.create_shuffle(0, M, R)
    rng = np.random.default_rng(7)
    oracle, infos = {}, {}
    for m in range(M):
        t = cluster.transport(meta.map_owner[m])
        w = t.store.map_writer(0, m)
        for r in range(R):
            payload = rng.integers(
                0, 256, size=int(rng.integers(0, 3000)), dtype=np.uint8
            ).tobytes()
            oracle[(m, r)] = payload
            if device:
                w.write_partition_device(r, _rows_for(payload), length=len(payload))
            else:
                w.write_partition(r, payload)
        info = w.commit()
        infos[m] = info
        t.commit_block(info.pack())
    cluster.run_exchange(0)
    return cluster, meta, oracle, infos


class TestDeviceWriteBitIdentity:
    """Device writes vs the host MapWriter oracle: same blocks, same MapperInfo
    offsets, same post-exchange bytes."""

    @pytest.mark.parametrize(
        "mode,n",
        [("array", 1), ("array", 8), ("memmap", 4), ("device", 4)],
    )
    def test_post_exchange_bytes_match_host_path(self, mode, n):
        M = R = 8
        host_c, host_meta, oracle, host_infos = _exchange(False, n, M, R, 1 << 20, mode)
        dev_c, dev_meta, _, dev_infos = _exchange(True, n, M, R, 1 << 20, mode)
        for m in range(M):
            assert dev_infos[m].partitions == host_infos[m].partitions, m
        for m in range(M):
            for r in range(R):
                consumer = dev_meta.owner_of_reduce(r)
                h_view, h_len = host_c.locate_received_block(consumer, 0, m, r)
                d_view, d_len = dev_c.locate_received_block(consumer, 0, m, r)
                assert d_len == h_len == len(oracle[(m, r)])
                assert bytes(d_view) == bytes(h_view) == oracle[(m, r)]

    @pytest.mark.parametrize("n", [1, 8])
    def test_host_staging_never_allocated(self, n):
        dev_c, dev_meta, *_ = _exchange(True, n, 8, 8, 1 << 20)
        for e in range(n):
            assert not dev_c.transport(e).store.host_staging_allocated(0)
        host_c, host_meta, *_ = _exchange(False, n, 8, 8, 1 << 20)
        writers = {host_meta.map_owner[m] for m in range(8)}
        assert all(host_c.transport(e).store.host_staging_allocated(0) for e in writers)

    @pytest.mark.parametrize(
        "tier, conf", [("disk", {"max_host_pool_bytes": 0}), ("host", {})], ids=["disk-tier", "ram-tier"]
    )
    def test_uneven_multi_round_rollover(self, tier, conf):
        # cap=16384 with ~12KB of uneven payloads per mapper forces D2H
        # rollovers mid-write; rounds must reassemble bit-identically and the
        # host staging buffer must STILL never be allocated (rollover snapshots
        # are standalone D2H copies, not the staging buffer) — with the
        # snapshots spilled (no RAM tier) and kept in RAM (the default budget)
        n, M, R, cap = 2, 4, 4, 8192
        host_c, _, oracle, host_infos = _exchange(False, n, M, R, cap, **conf)
        dev_c, dev_meta, _, dev_infos = _exchange(True, n, M, R, cap, **conf)
        assert dev_c.transport(0).store.num_rounds(0) >= 2
        for c in (host_c, dev_c):
            store = c.transport(0).store
            assert {store.round_tier(0, k) for k in range(store.num_rounds(0) - 1)} == {tier}
        stats = dev_c.transport(0).store.write_stats()
        assert stats["ram_rounds"] == (stats["rollovers"] if tier == "host" else 0)
        assert stats["pool_hits"] == stats["pool_misses"] == 0  # a device round takes no host buffer
        for m in range(M):
            assert dev_infos[m].partitions == host_infos[m].partitions
        for m in range(M):
            for r in range(R):
                consumer = dev_meta.owner_of_reduce(r)
                d_view, d_len = dev_c.locate_received_block(consumer, 0, m, r)
                assert bytes(d_view) == oracle[(m, r)]
        for e in range(n):
            assert not dev_c.transport(e).store.host_staging_allocated(0)
        # a removed device shuffle's snapshots are the runtime's copies: none
        # is kept; a host shuffle's RAM rounds are the next shuffle's
        dev_c.remove_shuffle(0)
        host_c.remove_shuffle(0)
        for c in (dev_c, host_c):
            for t in c.transports:
                assert t.store._ram_round_bytes == 0
                held = t.store.write_stats()["pool_held_bytes"]
                assert (held > 0) == (c is host_c and tier == "host")


def _standalone_store(device_staging: bool = True) -> HbmBlockStore:
    store = HbmBlockStore(_conf(device_staging, 1, 1 << 20), device=jax.devices()[0])
    store.create_shuffle(0, 1, 4)
    return store


class TestSealPayloads:
    def test_seal_returns_device_arrays_no_host_round_trip(self):
        store = _standalone_store()
        w = store.map_writer(0, 0)
        w.write_partition_device(0, _rows_for(b"x" * 777), length=777)
        w.write_partition_device(1, _rows_for(b"y" * 130), length=130)
        w.commit()
        rounds = store.seal(0)
        assert rounds, "seal returned no rounds"
        for payload, sizes in rounds:
            assert isinstance(payload, jax.Array), type(payload)
        assert not store.host_staging_allocated(0)
        stats = store.stats(0)
        assert stats["host_staging_allocated"] is False
        assert stats["device_mode"] is True

    def test_host_and_device_staged_writes_seal_to_the_same_payload(self):
        """One map task, two partitions of 4,096 bytes, written -> committed
        -> sealed -> removed on ONE store, shuffle after shuffle: the sealed
        round of the host byte path and of the device path hold the same
        bytes, and the device path keeps its payload off the host."""
        rng = np.random.default_rng(0)
        conf = _conf(True, 1, 1 << 20)
        conf.spill_to_disk = False
        stores = {impl: HbmBlockStore(conf, device=jax.devices()[0]) for impl in ("host", "device")}
        for sid in range(2):  # the second shuffle reuses what the first built
            blocks = [rng.integers(0, 256, size=4096, dtype=np.uint8).tobytes() for _ in range(2)]
            sealed = {}
            for impl, store in stores.items():
                store.create_shuffle(sid, 1, 2)
                w = store.map_writer(sid, 0)
                for r, block in enumerate(blocks):
                    if impl == "host":
                        w.write_partition(r, block)
                    else:
                        w.write_partition_device(r, _rows_for(block), length=len(block))
                info = w.commit()
                payload, sizes = store.seal(sid)[-1]
                assert store.host_staging_allocated(sid) == (impl == "host")
                sealed[impl] = (np.asarray(payload).tobytes(), np.asarray(sizes), info.partitions)
                store.remove_shuffle(sid)
            assert sealed["host"][0] == sealed["device"][0]
            np.testing.assert_array_equal(sealed["host"][1], sealed["device"][1])
            assert sealed["host"][2] == sealed["device"][2]
            assert sealed["host"][0][: 2 * 4096] == blocks[0] + blocks[1]

    def test_read_block_serves_device_round(self):
        store = _standalone_store()
        w = store.map_writer(0, 0)
        w.write_partition_device(0, _rows_for(b"z" * 300), length=300)
        w.commit()
        assert store.read_block(0, 0, 0) == b"z" * 300


class TestGuards:
    def _store(self, device=True):
        return _standalone_store(device_staging=device)

    def test_host_then_device_write_rejected(self):
        w = self._store().map_writer(0, 0)
        w.write_partition(0, b"a" * 10)
        with pytest.raises(TransportError, match="cannot mix"):
            w.write_partition_device(1, _rows_for(b"b" * 10), length=10)

    def test_device_then_host_write_rejected(self):
        w = self._store().map_writer(0, 0)
        w.write_partition_device(0, _rows_for(b"a" * 10), length=10)
        with pytest.raises(TransportError, match="cannot mix"):
            w.write_partition(1, b"b" * 10)

    def test_wrong_lane_shape_rejected(self):
        w = self._store().map_writer(0, 0)
        with pytest.raises(TransportError, match="must be"):
            w.write_partition_device(0, jnp.zeros((4, LANE + 1), jnp.int32))

    def test_out_of_order_reduce_rejected(self):
        w = self._store().map_writer(0, 0)
        w.write_partition_device(3, _rows_for(b"a" * 10), length=10)
        with pytest.raises(TransportError, match="increasing"):
            w.write_partition_device(1, _rows_for(b"b" * 10), length=10)

    def test_device_map_writer_conf_gate(self):
        store = self._store(device=False)
        with pytest.raises(TransportError, match="deviceStaging"):
            DeviceMapWriter(store, 0, 0)

    def test_map_output_writer_conf_gate(self):
        store = self._store(device=False)
        mow = TpuShuffleMapOutputWriter(store, transport=None, shuffle_id=0, map_id=0, num_partitions=2)
        with pytest.raises(TransportError, match="deviceStaging"):
            mow.write_partition_device(0, _rows_for(b"a" * 10))

    def test_divergent_executor_geometry_is_named(self):
        # satellite: sealed-round shape validation must name the offending
        # executor instead of failing deep inside the collective
        n = 2
        cluster = TpuShuffleCluster(_conf(False, n, 1 << 20), num_executors=n)
        meta = cluster.create_shuffle(0, 2, 2)
        for m in range(2):
            t = cluster.transport(meta.map_owner[m])
            w = t.store.map_writer(0, m)
            for r in range(2):
                w.write_partition(r, b"q" * 200)
            t.commit_block(w.commit().pack())
        bad_store = cluster.transport(1).store
        real_seal = bad_store.seal
        bad_store.seal = lambda sid: [
            (np.pad(p, ((0, 4), (0, 0))), sizes) for p, sizes in real_seal(sid)
        ]
        with pytest.raises(TransportError, match="executor 1 sealed round 0"):
            cluster.run_exchange(0)


class TestWriterLayer:
    def test_device_map_writer_roundtrip(self):
        store = _standalone_store()
        w = DeviceMapWriter(store, 0, 0)
        w.write_partition(0, _rows_for(b"m" * 513), length=513)
        w.write_partition(2, _rows_for(b"n" * 64), length=64)
        info = w.commit()
        assert info.partitions[0][1] == 513
        assert store.read_block(0, 0, 0) == b"m" * 513
        assert store.read_block(0, 0, 2) == b"n" * 64


class TestReaderZeroCopy:
    """The fetch iterator serves read-only memoryviews of the fetch buffer
    (shuffle/reader.py): no per-block copy on the pool-less path, copy only
    when a pooled buffer is about to be recycled."""

    def _shuffled(self):
        n = 2
        cluster = TpuShuffleCluster(_conf(False, n, 1 << 20), num_executors=n)
        meta = cluster.create_shuffle(0, 2, 2)
        payloads = {}
        for m in range(2):
            t = cluster.transport(meta.map_owner[m])
            w = t.store.map_writer(0, m)
            for r in range(2):
                data = serialize_records([(f"k{m}{r}", m * 10 + r)])
                payloads[(m, r)] = data
                w.write_partition(r, data)
            t.commit_block(w.commit().pack())
        cluster.run_exchange(0)
        return cluster, meta, payloads

    def _reader(self, cluster, meta, payloads, r):
        consumer = meta.owner_of_reduce(r)
        return TpuShuffleReader(
            cluster.transport(consumer), consumer, 0, r, r + 1, 2,
            block_sizes=lambda m, rr: len(payloads[(m, rr)]),
            sender_of=lambda m: meta.map_owner[m],
        )

    def test_pool_less_fetch_serves_readonly_views(self):
        cluster, meta, payloads = self._shuffled()
        blocks = list(self._reader(cluster, meta, payloads, 0).fetch_blocks())
        assert blocks
        for blk in blocks:
            assert isinstance(blk.data, memoryview)
            assert blk.data.readonly
            # pool-less: data stays valid after the iterator detached it
            assert bytes(blk.data) == payloads[(blk.block_id.map_id, 0)]

    def test_read_streams_records(self):
        cluster, meta, payloads = self._shuffled()
        r = 1
        got = sorted(self._reader(cluster, meta, payloads, r).read())
        assert got == sorted([(f"k{m}{r}", m * 10 + r) for m in range(2)])

    def test_pooled_detach_copies_and_release_drops(self):
        class _Buf:
            closed = 0

            def close(self):
                self.closed += 1

        view = memoryview(b"payload")
        pooled = BlockFetchResult(ShuffleBlockId(0, 0, 0), view, _Buf(), pooled=True)
        pooled.detach()
        assert isinstance(pooled.data, bytes) and pooled.data == b"payload"
        pooled.detach()  # idempotent
        assert pooled._buf is None

        buf = _Buf()
        dropped = BlockFetchResult(ShuffleBlockId(0, 0, 0), view, buf, pooled=True)
        dropped.release()
        assert dropped.data == b"" and buf.closed == 1

    def test_unpooled_detach_keeps_view_without_copy(self):
        class _Buf:
            def close(self):
                pass

        view = memoryview(b"payload")
        blk = BlockFetchResult(ShuffleBlockId(0, 0, 0), view, _Buf(), pooled=False)
        blk.detach()
        assert blk.data is view
