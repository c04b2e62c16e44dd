"""host_recv_mode: the post-exchange host-memory budget (SURVEY §7 "HBM
budget", host half; VERDICT r4 item 8).

'array' keeps a RAM copy per round (the historical behavior), 'memmap' spills
each round's received shards to disk and serves fetches through read-only
``np.memmap`` views, 'device' keeps no host copy at all and slices the
HBM-resident shard per fetch."""

import os



import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.block import MemoryBlock, ShuffleBlockId
from sparkucx_tpu.core.operation import OperationStatus, TransportError
from sparkucx_tpu.transport.tpu import TpuShuffleCluster

N_EXEC = 4


def _buf(n):
    return MemoryBlock(np.zeros(n, dtype=np.uint8), size=n)


def _write_shuffle(cluster, shuffle_id, M, R, rng, block=2000):
    meta = cluster.create_shuffle(shuffle_id, M, R)
    oracle = {}
    for m in range(M):
        t = cluster.transport(meta.map_owner[m])
        w = t.store.map_writer(shuffle_id, m)
        for r in range(R):
            payload = rng.integers(0, 256, size=block, dtype=np.uint8).tobytes()
            oracle[(m, r)] = payload
            w.write_partition(r, payload)
        t.commit_block(w.commit().pack())
    return meta, oracle


def _fetch_all(cluster, meta, shuffle_id, M, R, oracle):
    for r in range(R):
        consumer = meta.owner_of_reduce(r)
        t = cluster.transport(consumer)
        bufs = [_buf(8192) for _ in range(M)]
        reqs = t.fetch_blocks_by_block_ids(
            consumer, [ShuffleBlockId(shuffle_id, m, r) for m in range(M)],
            bufs, [None] * M,
        )
        for m in range(M):
            res = reqs[m].wait(5)
            assert res.status == OperationStatus.SUCCESS, str(res.error)
            assert bufs[m].host_view()[: bufs[m].size].tobytes() == oracle[(m, r)]


class TestMemmapMode:
    def test_multi_round_vs_oracle_and_cleanup(self, rng, tmp_path):
        conf = TpuShuffleConf(
            staging_capacity_per_executor=N_EXEC * 4096,
            block_alignment=128,
            num_executors=N_EXEC,
            host_recv_mode="memmap",
            spill_dir=str(tmp_path),
        )
        cluster = TpuShuffleCluster(conf, num_executors=N_EXEC)
        M, R = 3 * N_EXEC, 8
        meta, oracle = _write_shuffle(cluster, 0, M, R, rng)
        cluster.run_exchange(0)
        assert len(meta.recv_shards) > 1, "test should spill multiple rounds"
        # every shard view is a read-only disk-backed mapping, not RAM
        for rnd in meta.recv_shards:
            for shard in rnd:
                assert isinstance(shard, np.memmap)
                assert not shard.flags.writeable
        spilled = [p for p, _ in meta.recv_spill_paths]
        assert spilled and all(os.path.exists(p) for p in spilled)
        _fetch_all(cluster, meta, 0, M, R, oracle)
        cluster.remove_shuffle(0)
        assert not any(os.path.exists(p) for p in spilled), "spill files leaked"


class TestMemmapDiskCap:
    def test_recv_spill_charged_against_cap(self, rng, tmp_path):
        """spill_disk_cap_bytes bounds the received-shard spill too — a
        too-small cap is a TransportError at exchange, not silent disk fill."""
        conf = TpuShuffleConf(
            staging_capacity_per_executor=1 << 18,
            block_alignment=128,
            num_executors=N_EXEC,
            host_recv_mode="memmap",
            spill_dir=str(tmp_path),
            spill_disk_cap_bytes=4096,  # far below one received round
        )
        cluster = TpuShuffleCluster(conf, num_executors=N_EXEC)
        _write_shuffle(cluster, 0, 4, 4, rng, block=512)
        with pytest.raises(TransportError, match="spill_disk_cap_bytes"):
            cluster.run_exchange(0)

    def test_cap_released_on_remove(self, rng, tmp_path):
        """remove_shuffle returns its spill bytes to the budget."""
        conf = TpuShuffleConf(
            staging_capacity_per_executor=1 << 18,
            block_alignment=128,
            num_executors=N_EXEC,
            host_recv_mode="memmap",
            spill_dir=str(tmp_path),
            spill_disk_cap_bytes=16 << 20,  # fits one shuffle, not two
        )
        cluster = TpuShuffleCluster(conf, num_executors=N_EXEC)
        for sid in range(3):  # three sequential shuffles reuse the budget
            meta, oracle = _write_shuffle(cluster, sid, 4, 4, rng, block=512)
            cluster.run_exchange(sid)
            _fetch_all(cluster, meta, sid, 4, 4, oracle)
            cluster.remove_shuffle(sid)
        assert cluster._recv_spill_bytes == 0


class TestDeviceMode:
    def test_no_host_copy_vs_oracle(self, rng):
        conf = TpuShuffleConf(
            staging_capacity_per_executor=1 << 18,
            block_alignment=128,
            num_executors=N_EXEC,
            host_recv_mode="device",
            keep_device_recv=True,
        )
        cluster = TpuShuffleCluster(conf, num_executors=N_EXEC)
        M, R = 8, 8
        meta, oracle = _write_shuffle(cluster, 0, M, R, rng)
        cluster.run_exchange(0)
        assert meta.recv_shards is None, "device mode must keep no host copy"
        assert meta.recv_device is not None
        _fetch_all(cluster, meta, 0, M, R, oracle)

    def test_requires_keep_device_recv(self, rng):
        conf = TpuShuffleConf(
            staging_capacity_per_executor=1 << 18,
            block_alignment=128,
            num_executors=N_EXEC,
            host_recv_mode="device",
        )
        cluster = TpuShuffleCluster(conf, num_executors=N_EXEC)
        meta, _ = _write_shuffle(cluster, 0, 2, 2, rng, block=64)
        with pytest.raises(TransportError, match="keep_device_recv"):
            cluster.run_exchange(0)

    def test_unknown_mode_rejected(self, rng):
        conf = TpuShuffleConf(
            staging_capacity_per_executor=1 << 18,
            num_executors=N_EXEC,
            host_recv_mode="ram",
        )
        cluster = TpuShuffleCluster(conf, num_executors=N_EXEC)
        _write_shuffle(cluster, 0, 2, 2, rng, block=64)
        with pytest.raises(ValueError, match="host_recv_mode"):
            cluster.run_exchange(0)


class TestHostBudgetStructural:
    """The budget claim in structural form.  A direct ru_maxrss comparison is
    NOT meaningful on this virtual CPU mesh: ``np.asarray`` of a cpu-backend
    jax shard is zero-copy (the 'array'-mode host shards alias the jax
    buffers that exist in both modes), and XLA:CPU's pooled allocator never
    returns freed pages to the OS, so peak RSS measures the allocator
    high-water mark, not retention (measured: 653 vs 620 MiB for a 160 MiB
    dataset).  On real TPU hardware the D2H in 'array' mode is a genuine host
    copy per round — what 'memmap'/'device' eliminate.  What CAN be asserted
    portably: after a multi-round memmap exchange, every retained recv shard
    is file-backed (zero RAM-backed recv bytes), their file sizes cover the
    received data, and fetches never resurrect a RAM copy."""

    def test_memmap_retains_zero_ram_backed_recv_bytes(self, rng, tmp_path):
        conf = TpuShuffleConf(
            staging_capacity_per_executor=N_EXEC * 4096,
            block_alignment=128,
            num_executors=N_EXEC,
            host_recv_mode="memmap",
            spill_dir=str(tmp_path),
        )
        cluster = TpuShuffleCluster(conf, num_executors=N_EXEC)
        M, R = 3 * N_EXEC, 8
        meta, oracle = _write_shuffle(cluster, 0, M, R, rng)
        cluster.run_exchange(0)
        assert len(meta.recv_shards) >= 3, "should spill multiple rounds"
        ram_backed = sum(
            shard.nbytes
            for rnd in meta.recv_shards
            for shard in rnd
            if not isinstance(shard, np.memmap)
        )
        assert ram_backed == 0, f"{ram_backed} recv bytes retained in RAM"
        on_disk = sum(os.path.getsize(p) for p, _ in meta.recv_spill_paths)
        received = sum(int(s.sum()) for s in meta.recv_sizes) * conf.block_alignment
        assert on_disk >= received > 0
        assert cluster._recv_spill_bytes == on_disk
        # fetches serve from the mappings without converting them to arrays
        _fetch_all(cluster, meta, 0, M, R, oracle)
        assert all(
            isinstance(shard, np.memmap)
            for rnd in meta.recv_shards
            for shard in rnd
        )


# -- a local read borrows the received shards (PR 39) -------------------------

import contextlib
import dataclasses
import gc

from sparkucx_tpu.shuffle.manager import TpuShuffleManager
from sparkucx_tpu.shuffle.reader import TpuShuffleReader

MODES = ["array", "memmap", "device"]
MAPPERS, REDUCERS = 6, 8


class _CopyOnly:
    """A transport facet that can only fetch into result buffers: the inner
    one with ``resident_blocks`` taken away."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        if name == "resident_blocks":
            raise AttributeError(name)
        return getattr(self._inner, name)


@contextlib.contextmanager
def _local_job(mode, executors, tmp_path, rng, block=3000, region=8192):
    """Ragged blocks through a manager (over several staging rounds at the
    default ``region``); two windows a reduce task.  Yields (manager, meta,
    {(m, r): bytes})."""
    conf = TpuShuffleConf(
        staging_capacity_per_executor=executors * region,
        block_alignment=128,
        num_executors=executors,
        host_recv_mode=mode,
        keep_device_recv=mode == "device",
        spill_dir=str(tmp_path),
        max_blocks_per_request=4,
    )
    with TpuShuffleManager(conf, num_executors=executors) as mgr:
        oracle = _write_job(mgr, 0, rng, block)
        yield mgr, mgr.cluster.meta(0), oracle


def _write_job(mgr, shuffle_id, rng, block):
    mgr.register_shuffle(shuffle_id, MAPPERS, REDUCERS)
    oracle = {}
    for m in range(MAPPERS):
        writer = mgr.get_writer(shuffle_id, m)
        for r in range(REDUCERS):
            oracle[(m, r)] = rng.integers(0, 256, int(rng.integers(1, block)), dtype=np.uint8).tobytes()
            with writer.get_partition_writer(r).open_stream() as stream:
                stream.write(oracle[(m, r)])
        writer.commit_all_partitions()
    mgr.run_exchange(shuffle_id)
    return oracle


def _fetched(reader):
    return [(b.block_id, bytes(b.data), b.data.readonly) for b in reader.fetch_blocks()]


def _pool_counts(pool):
    stats = pool.stats()
    return sum(s["requests"] for s in stats.values()), sum(s["free"] for s in stats.values())


@pytest.mark.parametrize("executors", [1, 4])
@pytest.mark.parametrize("mode", MODES)
def test_a_local_read_borrows_what_the_copying_fetch_copies(rng, tmp_path, mode, executors):
    """The same blocks, bytes, order and read metrics as the fetch into pooled
    buffers — with no buffer taken, no block copied."""
    same = ("remote_bytes_read", "remote_blocks_fetched", "window_bytes_max", "records_read",
            "blocks_retried", "failovers", "fetch_timeouts")
    with _local_job(mode, executors, tmp_path, rng) as (mgr, meta, oracle):
        assert len(meta.recv_sizes) > 1  # several rounds: a block's shard is its round's
        for r in range(REDUCERS):
            consumer = meta.owner_of_reduce(r)
            borrowed = mgr.get_reader(0, r, r + 1)
            before = mgr.pool.stats()
            got = _fetched(borrowed)
            assert mgr.pool.stats() == before  # the pool: not touched
            assert [(bid.map_id, data) for bid, data, _ in got] == [
                (m, mgr.cluster.locate_received_block(consumer, 0, m, r)[0].tobytes())
                for m in range(MAPPERS)
            ] == [(m, oracle[(m, r)]) for m in range(MAPPERS)]
            assert all(readonly for _, _, readonly in got)
            metrics = borrowed.metrics
            assert (metrics.resident_blocks, metrics.copied_blocks) == (MAPPERS, 0)
            assert metrics.resident_bytes == metrics.remote_bytes_read == sum(len(d) for _, d, _ in got)

            copying = mgr.get_reader(0, r, r + 1)
            copying.transport = _CopyOnly(copying.transport)
            assert _fetched(copying) == got
            requests, free = _pool_counts(mgr.pool)
            again = mgr.get_reader(0, r, r + 1)
            again.transport = _CopyOnly(again.transport)
            assert _fetched(again) == got
            # a buffer a block taken from the pool, and every one handed back
            assert _pool_counts(mgr.pool) == (requests + MAPPERS, free)
            assert (again.metrics.resident_blocks, again.metrics.copied_blocks) == (0, MAPPERS)
            for name in same:
                assert getattr(again.metrics, name) == getattr(metrics, name), name


@pytest.mark.parametrize("mode", MODES)
def test_data_kept_past_unregister_still_reads_what_was_written(rng, tmp_path, mode):
    """A raw ``fetch_blocks()`` consumer that keeps ``data``: the view holds
    its shard (its mapping, its D2H array) by reference count, so removing
    the shuffle and running another through the same stores frees nothing
    under it."""
    with _local_job(mode, 2, tmp_path, rng) as (mgr, meta, oracle):
        kept = [(b.block_id, b.data) for r in range(REDUCERS)
                for b in mgr.get_reader(0, r, r + 1).fetch_blocks()]
        assert len(kept) == MAPPERS * REDUCERS
        spilled = [path for path, _ in meta.recv_spill_paths]
        assert bool(spilled) == (mode == "memmap")
        mgr.unregister_shuffle(0)
        del meta
        gc.collect()
        assert not any(os.path.exists(path) for path in spilled)
        other = _write_job(mgr, 1, np.random.default_rng(99), 3000)  # other bytes, the same stores
        assert [bytes(b.data) for b in mgr.get_reader(1, 0, 1).fetch_blocks()] == [
            other[(m, 0)] for m in range(MAPPERS)
        ]
        for bid, data in kept:
            assert data.readonly and bytes(data) == oracle[(bid.map_id, bid.reduce_id)]


@pytest.mark.parametrize("mode", ["array", "memmap"])
def test_a_borrow_that_raises_fails_its_block_alone_through_retry(rng, tmp_path, mode, monkeypatch):
    """A block whose table entry points past what its consumer received: the
    borrow raises, the window takes the copying fetch, that names the block
    with the typed error and ``_retry_fetch`` pulls it."""
    with _local_job(mode, 2, tmp_path, rng, block=400, region=1 << 19) as (mgr, meta, oracle):
        r = REDUCERS - 1
        consumer = meta.owner_of_reduce(r)
        m = next(m for m in range(MAPPERS) if meta.map_owner[m] == consumer)  # its pull path has the block
        info = meta.mapper_infos[m]
        offset, length = info.partitions[r]
        part = meta.recv_shards[info.round_of(r)][consumer]
        assert offset % meta.region_bytes + part.nbytes + length < meta.region_bytes
        moved = info.partitions[:r] + ((offset + part.nbytes, length),) + info.partitions[r + 1:]
        meta.mapper_infos[m] = dataclasses.replace(info, partitions=moved)
        with pytest.raises(TransportError, match="lies past"):
            mgr.cluster.transport(consumer).resident_blocks([ShuffleBlockId(0, m, r)])

        retried = []
        retry = TpuShuffleReader._retry_fetch

        def spy(self, bid, buf, failed):
            retried.append((bid, buf is not None, failed.status, failed.error))
            return retry(self, bid, buf, failed)

        monkeypatch.setattr(TpuShuffleReader, "_retry_fetch", spy)
        reader = mgr.get_reader(0, r, r + 1)
        got = _fetched(reader)
        assert [(bid.map_id, data) for bid, data, _ in got] == [(i, oracle[(i, r)]) for i in range(MAPPERS)]
        [(bid, had_buffer, status, error)] = retried
        assert bid == ShuffleBlockId(0, m, r) and had_buffer and status == OperationStatus.FAILURE
        assert isinstance(error, TransportError) and "lies past" in str(error)
        metrics = reader.metrics
        assert metrics.blocks_retried == 1 and metrics.failovers == 0
        # the window at fault was copied whole, the task's other window borrowed
        window = 4 if m < 4 else MAPPERS - 4
        assert (metrics.copied_blocks, metrics.resident_blocks) == (window, MAPPERS - window)
        assert _pool_counts(mgr.pool)[0] == window  # and only that window took buffers


@pytest.mark.parametrize("mode", MODES)
def test_a_window_addressed_to_another_executor_is_still_copied(rng, tmp_path, mode):
    """The choice is the target's: a block whose fetch names another executor
    goes into a pooled buffer as before, whatever the transport could lend."""
    with _local_job(mode, 2, tmp_path, rng) as (mgr, meta, oracle):
        r = 0
        consumer = meta.owner_of_reduce(r)
        reader = mgr.get_reader(0, r, r + 1)
        reader.received_by = None  # the fetch's target is sender_of's again, not the exchange's
        reader.sender_of = lambda m: 1 - consumer
        got = _fetched(reader)
        assert [data for _, data, _ in got] == [oracle[(m, r)] for m in range(MAPPERS)]
        assert (reader.metrics.resident_blocks, reader.metrics.copied_blocks) == (0, MAPPERS)
        requests, free = _pool_counts(mgr.pool)
        assert requests == MAPPERS and free >= 1  # taken from the pool, and handed back
        mixed = mgr.get_reader(0, r, r + 1)
        mixed.received_by = None
        mixed.sender_of = lambda m: consumer if m % 2 else 1 - consumer
        assert sorted(_fetched(mixed)) == sorted(got)
        assert (mixed.metrics.resident_blocks, mixed.metrics.copied_blocks) == (MAPPERS // 2, MAPPERS - MAPPERS // 2)
