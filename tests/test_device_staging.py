"""Device staging rounds (conf.device_staging): map output written as device
arrays — a block a call, or a map task's packed output in one — placed into
the shuffle's HBM staging array by the block-scatter kernel as it is written,
with no host round trip and nothing of the producer's arrays kept.

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

    @pytest.mark.parametrize("n", [1, 4])
    def test_a_device_staged_job_allocates_and_keeps_no_host_buffer(self, n):
        """Under a budget its staging size alone exceeds — where a host-staged
        job's buffer is the free list's floor — a device-staged shuffle has no
        host buffer to keep: nothing allocated, nothing on the free list."""
        cap = 1 << 20
        for device in (True, False):
            cluster, *_ = _exchange(device, n, 8, 8, cap, "device", max_host_pool_bytes=cap - 1)
            cluster.remove_shuffle(0)
            for t in cluster.transports:
                stats = t.store.write_stats()
                host = {k: stats[k] for k in ("pool_hits", "pool_misses", "pool_dropped_busy",
                                              "pool_kept_over_budget", "pool_held_bytes")}
                if device:
                    assert not any(host.values()) and t.store._free_rounds == {}
                    assert stats["device_staged_bytes"] == stats["staged_bytes"] > 0
                else:  # the same job from the host: its one buffer is kept
                    assert host == {"pool_hits": 0, "pool_misses": 1, "pool_dropped_busy": 0,
                                    "pool_kept_over_budget": 1, "pool_held_bytes": cap}

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


# -- a map task's packed device output, staged as it is written ---------------


def _pack(blocks):
    """[(reduce_id, bytes)] -> (packed (rows, LANE) int32 host array, reducer
    ids, byte lengths): the blocks back to back, each from a fresh row."""
    rows = [-(-len(data) // ALIGN) for _, data in blocks]
    flat = np.zeros(sum(rows) * ALIGN, dtype=np.uint8)
    at = 0
    for (_, data), n in zip(blocks, rows):
        flat[at : at + len(data)] = np.frombuffer(data, dtype=np.uint8)
        at += n * ALIGN
    return flat.view(np.int32).reshape(-1, LANE), [r for r, _ in blocks], [len(d) for _, d in blocks]


def _job_blocks(mappers, reducers, seed, max_bytes=6000):
    """Per mapper [(reduce_id, bytes)] in reducer order; reducer 3 gets no
    block, mapper 2 none for reducers 0-1, mapper 4 none at all."""
    rng = np.random.default_rng(seed)
    return [
        [
            (r, rng.integers(0, 256, size=int(rng.integers(1, max_bytes)), dtype=np.uint8).tobytes())
            for r in range(reducers)
            if not (r == 3 or m == 4 or (m == 2 and r < 2))
        ]
        for m in range(mappers)
    ]


def _manager_conf(staging, executors, **kw):
    return TpuShuffleConf(keep_device_recv=True, host_recv_mode="device", block_alignment=ALIGN,
                          staging_capacity_per_executor=staging, num_executors=executors,
                          device_staging=True, **kw)


def _write_job(mgr, sid, job, reducers, how):
    """One job's map side by ``how`` ('host' streams, 'block' device writes,
    'packed' device write a task) and its exchange; returns the producers'
    device arrays (already deleted: the store keeps nothing of them)."""
    mgr.register_shuffle(sid, len(job), reducers)
    meta = mgr.cluster.meta(sid)
    for m, blocks in enumerate(job):
        writer = mgr.get_writer(sid, m)
        device = mgr.cluster.transport(meta.map_owner[m]).device
        if how == "host":
            for r, data in blocks:
                with writer.get_partition_writer(r).open_stream() as stream:
                    stream.write(data)
        elif how == "block":
            for r, data in blocks:
                rows = jax.device_put(_pack([(r, data)])[0], device)
                writer.write_partition_device(r, rows, length=len(data))
                rows.delete()
        else:
            packed, ids, lengths = _pack(blocks)
            packed = jax.device_put(packed, device)
            writer.write_partitions_device(packed, ids, lengths)
            packed.delete()  # the caller's to let go as soon as the call returns
        lengths = writer.commit_all_partitions()
        assert [int(n) for n in lengths if n] == [len(d) for _, d in blocks]
    mgr.run_exchange(sid)


def _raw(payload):
    return [bytes(payload)]


def _read_both_ways(mgr, sid, reducers):
    """{(map, reduce): bytes} by ``read_device()``, checked against ``read()``."""
    out = {}
    for r in range(reducers):
        got = mgr.get_reader(sid, r, r + 1).read_device()
        host = np.asarray(got.packed).reshape(-1).view(np.uint8)
        mine = {
            (b.map_id, b.reduce_id): host[row * ALIGN : row * ALIGN + n].tobytes()
            for (row, n), b in zip(got.table.tolist(), got.block_ids)
        }
        assert sorted(mgr.get_reader(sid, r, r + 1, deserializer=_raw).read()) == sorted(mine.values())
        out.update(mine)
    return out


@pytest.mark.parametrize("executors, staging, rounds", [
    (1, 1 << 20, "one"), (4, 1 << 20, "one"), (1, 1 << 15, "several"), (4, 1 << 15, "several"),
], ids=["1x-one-round", "4x-one-round", "1x-several-rounds", "4x-several-rounds"])
def test_packed_block_and_host_writes_are_the_same_shuffle(executors, staging, rounds):
    """The same job written three ways through the manager's writers — host
    streams, a device array a block, ONE packed device array a task — gives
    the same offset tables and, through seal -> exchange -> ``read()`` and
    ``read_device()``, the same bytes; the packed way in one dispatch a task
    (one more where a task crosses a staging round)."""
    from sparkucx_tpu.shuffle.manager import TpuShuffleManager

    mappers, reducers = 6, 8
    job = _job_blocks(mappers, reducers, seed=executors * 100 + staging)
    want = {(m, r): data for m, blocks in enumerate(job) for r, data in blocks}
    tables = {}
    for how in ("host", "block", "packed"):
        with TpuShuffleManager(_manager_conf(staging, executors), num_executors=executors) as mgr:
            _write_job(mgr, 0, job, reducers, how)
            cluster = mgr.cluster
            meta = cluster.meta(0)
            assert (len(meta.recv_sizes) == 1) == (rounds == "one")
            assert _read_both_ways(mgr, 0, reducers) == want
            tables[how] = [
                cluster.transport(meta.map_owner[m]).store.mapper_info(0, m) for m in range(mappers)
            ]
            stats = [t.store.write_stats() for t in cluster.transports]
            staged = sum(s["staged_bytes"] for s in stats)
            assert staged == sum(len(d) for d in want.values())
            on_device = sum(s["device_staged_bytes"] for s in stats)
            dispatches = sum(s["scatter_dispatches"] for s in stats)
            rollovers = sum(s["rollovers"] for s in stats)
            for t in cluster.transports:
                assert t.store.host_staging_allocated(0) == (how == "host" and t.store.stats(0)["num_blocks"] > 0)
            if how == "host":
                assert on_device == dispatches == 0 and cluster.executed_lowerings()["scatter"] == []
            else:
                assert on_device == staged and sum(s["copy_ns"] for s in stats) == 0
                assert sum(s["device_staged_blocks"] for s in stats) == len(want)
                assert set(cluster.executed_lowerings()["scatter"]) == {"xla"}
                if how == "block":
                    assert dispatches == len(want)
                else:  # a task that writes nothing dispatches nothing
                    tasks = sum(bool(blocks) for blocks in job)
                    assert tasks <= dispatches <= tasks + rollovers
                    assert dispatches == tasks or rounds == "several"
    assert tables["host"] == tables["block"] == tables["packed"]


def _packed_store(blocks, capacity=1 << 20, reducers=8, **conf):
    """A one-executor store with one map task's packed output written and
    committed, the producer's array deleted."""
    c = _conf(True, 1, capacity, **conf)
    store = HbmBlockStore(c, device=jax.devices()[0])
    store.create_shuffle(0, 2, reducers)
    w = store.map_writer(0, 0)
    packed, ids, lengths = _pack(blocks)
    packed = jnp.asarray(packed)
    w.write_partitions_device(packed, ids, lengths)
    packed.delete()
    return store, w.commit()


class TestPackedDeviceWrite:
    BLOCKS = [(0, b"a" * 700), (1, b"b" * 128), (4, b"c" * 1), (6, bytes(range(256)) * 9)]

    def test_every_block_is_served_with_the_producers_array_gone(self):
        """Nothing of the producer's array is kept: with it deleted right
        after the write, ``read_block``, the serving view and the replica
        source read the staging array — while the round is live, after the
        seal, and from each tier the sealed round is demoted to."""
        store, info = _packed_store(self.BLOCKS)
        want = dict(self.BLOCKS)
        assert [n for _, n in info.partitions if n] == [len(d) for _, d in self.BLOCKS]

        def served():
            for r, data in want.items():
                assert store.read_block(0, 0, r) == data
                arr, off, n = store.block_staging_view(0, 0, r)
                assert bytes(arr[off : off + n]) == data
            (rnd, entries, body), = store.replica_source(0)
            assert [n for _, _, n in entries] == [len(want[r]) for _, r, _ in entries]
            assert body == b"".join(want[r] for _, r, _ in entries)

        served()  # the live round: slices of the device staging array
        (payload, sizes), = store.seal(0)
        assert isinstance(payload, jax.Array) and payload.devices() == {store.device}
        assert int(sizes.sum()) * ALIGN == sum(-(-len(d) // ALIGN) * ALIGN for d in want.values())
        served()  # the sealed round
        assert store.demote_round(0, 0) == "hbm->host"
        served()
        assert store.demote_round(0, 0) == "host->disk"
        served()
        assert not store.host_staging_allocated(0)

    def test_a_round_the_exchange_consumed_is_a_clean_refusal(self):
        store, _ = _packed_store(self.BLOCKS)
        (payload, _), = store.seal(0)
        payload.delete()  # what a donating exchange leaves of its send buffer
        with pytest.raises(TransportError, match="no longer resident"):
            store.read_block(0, 0, 0)
        assert store.block_staging_view(0, 0, 0) is None

    def test_one_dispatch_a_task_and_no_compile_in_a_second_job(self):
        from benchmark.counters import CompileCounter

        compiles = CompileCounter()
        store = HbmBlockStore(_conf(True, 1, 1 << 20), device=jax.devices()[0])
        rng = np.random.default_rng(11)
        marks = []
        for sid in range(3):
            store.create_shuffle(sid, 3, 8)
            for m in range(3):
                # tasks of nearby sizes: another number of blocks, other
                # lengths, the same packed capacity
                blocks = [(r, rng.integers(0, 256, 900 + 50 * m + r, dtype=np.uint8).tobytes())
                          for r in range(8 - m)]
                packed, ids, lengths = _pack(blocks)
                capacity = np.zeros((128, LANE), dtype=np.int32)
                capacity[: len(packed)] = packed
                w = store.map_writer(sid, m)
                w.write_partitions_device(jnp.asarray(capacity), ids, lengths)
                w.commit()
                assert store.read_block(sid, m, ids[-1]) == blocks[-1][1]
            store.seal(sid)
            store.remove_shuffle(sid)
            marks.append(compiles.snapshot())
        stats = store.write_stats()
        assert stats["scatter_dispatches"] == 9 and stats["device_staged_blocks"] == 3 * (8 + 7 + 6)
        assert compiles.since(marks[0])["compiles"] == 0  # jobs two and three reuse job one's
        assert len(store.scatter_lowerings()) == 1  # 8, 7 and 6 blocks share one bucket

    def test_a_task_across_a_rollover_lands_in_both_rounds(self):
        # 8 KiB of staging, a task of 4 x 3,000 B: the third block opens round 1
        blocks = [(r, bytes([r + 1]) * 3000) for r in range(4)]
        store, info = _packed_store(blocks, capacity=8192)
        assert store.num_rounds(0) == 2 and info.rounds == (0, 0, 1, 1, 0, 0, 0, 0)
        stats = store.write_stats()
        assert stats["scatter_dispatches"] == 2 and stats["rollovers"] == 1
        assert stats["device_staged_bytes"] == 12000
        for r, data in blocks:
            assert store.read_block(0, 0, r) == data
        rounds = store.seal(0)
        assert isinstance(rounds[0][0], np.ndarray) and isinstance(rounds[1][0], jax.Array)
        for r, data in blocks:
            assert store.read_block(0, 0, r) == data

    def test_a_retry_after_the_commit_dispatches_nothing(self):
        store, first = _packed_store(self.BLOCKS)
        before = store.write_stats()
        retry = store.map_writer(0, 0)
        assert retry.is_retry_discard
        packed, ids, lengths = _pack([(r, b"\xff" * len(d)) for r, d in self.BLOCKS])
        retry.write_partitions_device(jnp.asarray(packed), ids, lengths)
        assert retry.commit() == first
        assert store.write_stats() == before
        assert store.read_block(0, 0, 0) == self.BLOCKS[0][1]

    def test_host_and_device_writes_still_do_not_mix(self):
        store, _ = _packed_store(self.BLOCKS)
        with pytest.raises(TransportError, match="cannot mix"):
            store.map_writer(0, 1).write_partition(0, b"host bytes")
        host = _standalone_store()
        w = host.map_writer(0, 0)
        w.write_partition(0, b"host bytes")
        packed, ids, lengths = _pack([(1, b"x" * 10), (2, b"y" * 200)])
        with pytest.raises(TransportError, match="cannot mix"):
            w.write_partitions_device(jnp.asarray(packed), ids, lengths)
        assert host.write_stats()["scatter_dispatches"] == 0

    @pytest.mark.parametrize("ids, lengths, error", [
        ([1, 1], [10, 10], "increasing"),
        ([2, 1], [10, 10], "increasing"),
        ([0, 1, 2], [10, 10], "reduce ids"),
        ([0, 1], [10, 100000], "packed array of"),
        ([0, 9], [10, 10], "out of range"),
    ], ids=["repeated", "decreasing", "table-sizes", "overrun", "reducer-range"])
    def test_a_malformed_task_is_refused_before_anything_is_staged(self, ids, lengths, error):
        store = _standalone_store()
        w = store.map_writer(0, 0)
        with pytest.raises((TransportError, ValueError), match=error):
            w.write_partitions_device(jnp.zeros((4, LANE), jnp.int32), ids, lengths)
        assert store.write_stats()["scatter_dispatches"] == 0 and store.stats(0)["num_blocks"] == 0
        assert store.stats(0)["device_mode"] is None

    def test_a_write_after_the_seal_is_refused(self):
        store, _ = _packed_store(self.BLOCKS)
        late = store.map_writer(0, 1)
        store.seal(0)
        with pytest.raises(TransportError, match="already sealed"):
            late.write_partitions_device(jnp.zeros((1, LANE), jnp.int32), [0], [5])

    @pytest.mark.parametrize("sealed", [False, True], ids=["live-round", "sealed-round"])
    def test_remove_releases_the_staging_array(self, sealed):
        import gc
        import weakref

        store, _ = _packed_store(self.BLOCKS)
        st = store._state(0)  # what a reader that resolved the state before the removal holds
        if sealed:
            store.seal(0)
        array = st.sealed_payload[-1] if sealed else st.device_staging
        ref, nbytes = weakref.ref(array), int(array.nbytes)
        del array
        gc.disable()
        try:
            store.remove_shuffle(0)
            assert ref() is None  # let go at the removal, not at a collection
        finally:
            gc.enable()
        assert nbytes == 1 << 20 and store.write_stats()["released_device_bytes"] == nbytes
        assert st.device_staging is None and st.sealed_payload is None
        with pytest.raises(TransportError):
            store.read_block(0, 0, 0)
        with pytest.raises(TransportError, match="unknown shuffle"):
            store.seal(0)


class TestPackedWriterLayer:
    def test_map_output_writer_records_the_lengths_and_keeps_the_order(self):
        store = _standalone_store()
        mow = TpuShuffleMapOutputWriter(store, transport=None, shuffle_id=0, map_id=0, num_partitions=4)
        packed, ids, lengths = _pack([(0, b"p" * 300), (2, b"q" * 129)])
        mow.write_partitions_device(jnp.asarray(packed), ids, lengths)
        with pytest.raises(TransportError, match="increasing"):
            mow.write_partition_device(2, _rows_for(b"r" * 10), length=10)
        mow.write_partition_device(3, _rows_for(b"r" * 10), length=10)
        assert mow._partition_lengths.tolist() == [300, 0, 129, 10]
        assert store.read_block(0, 0, 2) == b"q" * 129 and store.read_block(0, 0, 3) == b"r" * 10

    def test_conf_gate_on_both_surfaces(self):
        store = _standalone_store(device_staging=False)
        mow = TpuShuffleMapOutputWriter(store, transport=None, shuffle_id=0, map_id=0, num_partitions=2)
        packed, ids, lengths = _pack([(0, b"p" * 300)])
        with pytest.raises(TransportError, match="deviceStaging"):
            mow.write_partitions_device(jnp.asarray(packed), ids, lengths)

    def test_device_map_writer_takes_a_packed_task(self):
        store = _standalone_store()
        w = DeviceMapWriter(store, 0, 0)
        packed, ids, lengths = _pack([(0, b"m" * 513), (2, b"n" * 64)])
        w.write_partitions(jnp.asarray(packed), ids, lengths)
        info = w.commit()
        assert [n for _, n in info.partitions] == [513, 0, 64, 0]
        assert store.read_block(0, 0, 0) == b"m" * 513 and store.read_block(0, 0, 2) == b"n" * 64
        assert store.write_stats()["scatter_dispatches"] == 1
