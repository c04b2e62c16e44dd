"""A reduce task's records a block at a time (``TpuShuffleReader.read_batches``
with a ``FixedWidthSerializer``): the same bytes, order and metrics as the
record read, ownership of a copied block's batch, the ragged block, the batch
kept past ``unregister_shuffle`` — and the store's two tiers under it: a job
with rounds in RAM and on disk, exchanged from the mapping, removed."""

import contextlib
import gc
import os

import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.block import ShuffleBlockId
from sparkucx_tpu.core.operation import TransportError
from sparkucx_tpu.shuffle.manager import TpuShuffleManager
from sparkucx_tpu.shuffle.reader import (
    FixedWidthSerializer,
    RaggedBlockError,
    default_deserializer,
    serialize_records,
)
from sparkucx_tpu.utils.trace import TRACER

MODES = ["array", "memmap", "device"]
MAPPERS, REDUCERS = 6, 8
WIDTH, KEY = 20, 4
SERIALIZER = FixedWidthSerializer(WIDTH, KEY)


class _CopyOnly:
    """A transport facet that can only fetch into result buffers: the inner
    one with ``resident_blocks`` taken away."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        if name == "resident_blocks":
            raise AttributeError(name)
        return getattr(self._inner, name)


def _pool_counts(pool):
    stats = pool.stats()
    return sum(s["requests"] for s in stats.values()), sum(s["free"] for s in stats.values())


@contextlib.contextmanager
def _manager(mode, executors, tmp_path, region=8192, **conf):
    conf = TpuShuffleConf(
        staging_capacity_per_executor=executors * region,
        block_alignment=128,
        num_executors=executors,
        host_recv_mode=mode,
        keep_device_recv=mode == "device",
        spill_dir=str(tmp_path),
        max_blocks_per_request=4,
        **conf,
    )
    with TpuShuffleManager(conf, num_executors=executors) as mgr:
        yield mgr


def _write_records(mgr, shuffle_id, rng, most=120, encode=SERIALIZER.serialize):
    """Ragged blocks of whole records through ``get_writer``; returns {(m, r):
    the block's records as an (n, WIDTH) array}."""
    mgr.register_shuffle(shuffle_id, MAPPERS, REDUCERS)
    rows = {}
    for m in range(MAPPERS):
        writer = mgr.get_writer(shuffle_id, m)
        for r in range(REDUCERS):
            rows[(m, r)] = rng.integers(0, 256, (int(rng.integers(1, most)), WIDTH), dtype=np.uint8)
            with writer.get_partition_writer(r).open_stream() as stream:
                stream.write(encode(rows[(m, r)]))
        writer.commit_all_partitions()
    mgr.run_exchange(shuffle_id)
    return rows


def _pairs(rows):
    return [(bytes(row[:KEY]), bytes(row[KEY:])) for row in rows]


def test_the_serializer_is_a_view_both_ways():
    rows = np.arange(60, dtype=np.uint8).reshape(3, WIDTH)
    wire = SERIALIZER.serialize(rows)
    assert np.shares_memory(np.frombuffer(wire, dtype=np.uint8), rows) and bytes(wire) == rows.tobytes()
    assert bytes(SERIALIZER.serialize(rows[:, ::-1])) == rows[:, ::-1].tobytes()  # not contiguous: one copy
    batch = SERIALIZER.batch(wire)
    assert batch.shape == (3, WIDTH) and np.shares_memory(batch, rows) and not batch.flags.writeable
    assert list(SERIALIZER(bytes(wire))) == _pairs(rows)
    assert SERIALIZER.batch(b"").shape == (0, WIDTH)
    for wrong in (np.zeros((3, WIDTH + 1), np.uint8), np.zeros((3, WIDTH), np.int8), np.zeros(WIDTH, np.uint8)):
        with pytest.raises(ValueError):
            SERIALIZER.serialize(wrong)
    with pytest.raises(ValueError):
        FixedWidthSerializer(0)
    with pytest.raises(ValueError):
        FixedWidthSerializer(10, 11)


@pytest.mark.parametrize("executors", [1, 4])
@pytest.mark.parametrize("mode", MODES)
def test_the_batch_read_is_the_record_read_of_the_same_bytes(rng, tmp_path, mode, executors):
    """Blocks, order, bytes and read metrics of ``read_batches()`` against the
    typed codec's ``read()`` of the same records and against the serializer's
    own record stream; ``records_read`` counts records."""
    same = ("remote_bytes_read", "remote_blocks_fetched", "resident_blocks", "copied_blocks", "records_read")
    with _manager(mode, executors, tmp_path, region=4096) as mgr:
        rows = _write_records(mgr, 0, rng)
        typed = _write_records(mgr, 1, np.random.default_rng(0),
                               encode=lambda block: serialize_records(_pairs(block)))
        assert len(mgr.cluster.meta(0).recv_sizes) > 1  # several staging rounds
        for r in range(REDUCERS):
            before = mgr.pool.stats()
            reader = mgr.get_reader(0, r, r + 1, deserializer=SERIALIZER)
            batches = list(reader.read_batches())
            assert mgr.pool.stats() == before  # borrowed: the pool is not touched
            assert len(batches) == MAPPERS and all(not b.flags.writeable and not b.flags.owndata for b in batches)
            assert all(np.array_equal(b, rows[(m, r)]) for m, b in enumerate(batches))
            metrics = reader.metrics
            records = sum(len(rows[(m, r)]) for m in range(MAPPERS))
            assert (metrics.records_read, metrics.record_batches) == (records, MAPPERS)
            assert (metrics.resident_blocks, metrics.copied_blocks) == (MAPPERS, 0)
            assert metrics.remote_bytes_read == records * WIDTH

            by_record = mgr.get_reader(0, r, r + 1, deserializer=SERIALIZER)
            assert list(by_record.read()) == [pair for b in batches for pair in _pairs(b)]
            assert by_record.metrics.record_batches == 0
            for name in same:
                assert getattr(by_record.metrics, name) == getattr(metrics, name), name

            codec = mgr.get_reader(1, r, r + 1, deserializer=default_deserializer)
            assert list(codec.read()) == [pair for m in range(MAPPERS) for pair in _pairs(typed[(m, r)])]
            assert codec.metrics.records_read == sum(len(typed[(m, r)]) for m in range(MAPPERS))
        counters = mgr.cluster.stats.counters("read")
        assert counters["record_batches"] == MAPPERS * REDUCERS
        assert counters["batch_records"] == sum(len(block) for block in rows.values())


@pytest.mark.parametrize("mode", MODES)
def test_a_copied_blocks_batch_owns_its_bytes(rng, tmp_path, mode):
    """A block a fetch copied into a pooled buffer: its batch is taken before
    the buffer goes back, so the pool is whole after the task and the batch
    survives the buffers' next use."""
    with _manager(mode, 2, tmp_path) as mgr:
        rows = _write_records(mgr, 0, rng)
        warm = mgr.get_reader(0, 0, 1, deserializer=SERIALIZER)
        warm.transport = _CopyOnly(warm.transport)
        list(warm.read_batches())
        requests, free = _pool_counts(mgr.pool)
        reader = mgr.get_reader(0, 0, 1, deserializer=SERIALIZER)
        reader.transport = _CopyOnly(reader.transport)
        kept = list(reader.read_batches())
        assert _pool_counts(mgr.pool) == (requests + MAPPERS, free)  # every buffer handed back
        assert (reader.metrics.copied_blocks, reader.metrics.resident_blocks) == (MAPPERS, 0)
        assert all(b.base is None or b.base.flags.owndata for b in kept) and not any(b.flags.writeable for b in kept)
        other = mgr.get_reader(0, 1, 2, deserializer=SERIALIZER)  # the same buffers, other bytes
        other.transport = _CopyOnly(other.transport)
        assert all(np.array_equal(b, rows[(m, 1)]) for m, b in enumerate(other.read_batches()))
        assert all(np.array_equal(b, rows[(m, 0)]) for m, b in enumerate(kept))
        # a mixed task: some blocks borrowed, some copied
        mixed = mgr.get_reader(0, 2, 3, deserializer=SERIALIZER)
        consumer = mgr.cluster.meta(0).owner_of_reduce(2)
        mixed.received_by = None  # the fetch's target is sender_of's again, not the exchange's
        mixed.sender_of = lambda m: consumer if m % 2 else 1 - consumer
        got = sorted(b.tobytes() for b in mixed.read_batches())  # by sender, then by mapper
        assert got == sorted(rows[(m, 2)].tobytes() for m in range(MAPPERS))
        metrics = mixed.metrics
        assert metrics.resident_blocks + metrics.copied_blocks == metrics.remote_blocks_fetched == MAPPERS
        assert metrics.copied_blocks == MAPPERS - MAPPERS // 2


def test_a_ragged_block_raises_by_name(rng, tmp_path):
    with _manager("array", 1, tmp_path) as mgr:
        mgr.register_shuffle(0, 2, 1)
        for m, nbytes in enumerate((3 * WIDTH, 3 * WIDTH + 7)):
            writer = mgr.get_writer(0, m)
            with writer.get_partition_writer(0).open_stream() as stream:
                stream.write(bytes(nbytes))
            writer.commit_all_partitions()
        mgr.run_exchange(0)
        reader = mgr.get_reader(0, 0, 1, deserializer=SERIALIZER)
        batches = reader.read_batches()
        assert next(batches).shape == (3, WIDTH)
        with pytest.raises(RaggedBlockError, match=r"shuffle_0_1_0.* 67 B .* 20 B records \(7 B over\)") as caught:
            next(batches)
        assert caught.value.block_id == ShuffleBlockId(0, 1, 0) and isinstance(caught.value, ValueError)
        assert reader.metrics.records_read == 3  # never a floor division of the ragged one
        with pytest.raises(RaggedBlockError):
            list(mgr.get_reader(0, 0, 1, deserializer=SERIALIZER).read())


def test_what_is_not_a_batch_read_raises(rng, tmp_path):
    with _manager("array", 1, tmp_path) as mgr:
        _write_records(mgr, 0, rng)
        with pytest.raises(TypeError, match="FixedWidthSerializer"):
            mgr.get_reader(0, 0, 1).read_batches()
        reader = mgr.get_reader(0, 0, 1, deserializer=SERIALIZER, aggregator=lambda a, b: a)
        with pytest.raises(NotImplementedError, match="not supported"):
            reader.read_batches()
        assert reader.metrics.remote_blocks_fetched == 0  # refused before a block is touched
        # the ordered return is the device's: host-received shards have none
        reader = mgr.get_reader(0, 0, 1, deserializer=SERIALIZER, key_ordering=True)
        with pytest.raises(TransportError, match="device shards not retained"):
            reader.read_batches()
        assert reader.metrics.remote_blocks_fetched == 0


@pytest.mark.parametrize("mode", MODES)
def test_a_batch_kept_past_unregister_still_reads_what_was_written(rng, tmp_path, mode):
    with _manager(mode, 2, tmp_path) as mgr:
        rows = _write_records(mgr, 0, rng)
        kept = {r: list(mgr.get_reader(0, r, r + 1, deserializer=SERIALIZER).read_batches()) for r in range(REDUCERS)}
        spilled = [path for path, _ in mgr.cluster.meta(0).recv_spill_paths]
        mgr.unregister_shuffle(0)
        gc.collect()
        assert not any(os.path.exists(path) for path in spilled)
        _write_records(mgr, 1, np.random.default_rng(99))  # other bytes, the same stores
        for r, batches in kept.items():
            assert all(np.array_equal(b, rows[(m, r)]) for m, b in enumerate(batches))


def test_the_task_records_one_summed_span(rng, tmp_path):
    """``read.batches``: the reader's own turns, without the caller's."""
    import time

    with _manager("array", 1, tmp_path) as mgr:
        rows = _write_records(mgr, 0, rng)
        TRACER.clear()
        reader = mgr.get_reader(0, 0, 1, deserializer=SERIALIZER)
        for _ in reader.read_batches():
            time.sleep(0.01)  # the caller's turn
        [event] = [ev for ev in TRACER.events if ev["name"] == "read.batches"]
        records = sum(len(rows[(m, 0)]) for m in range(MAPPERS))
        args = event["args"]
        assert (args["blocks"], args["records"], args["bytes"]) == (MAPPERS, records, records * WIDTH)
        assert args["turns"] == MAPPERS + 1 and event["dur"] < MAPPERS * 10_000 / 2  # us: the sleeps are not in it


def _spill_files(store):
    return [path for st in store._shuffles.values() for path, _ in st.spill_files]


def test_a_job_on_both_tiers_is_exchanged_from_the_mapping_and_removed_whole(rng, tmp_path):
    """Rounds past ``max_host_pool_bytes`` go to the disk tier and are put on
    the device from the mapping (``exchange.h2d.disk``, ``disk_rounds``); the
    batches are what was written; removing the job unlinks every file and
    gives every RAM round's buffer back; the next job takes them."""
    region = 8192
    with _manager("array", 1, tmp_path, region=region, max_host_pool_bytes=3 * region) as mgr:
        store = mgr.cluster.transport(0).store
        for sid in (0, 1):
            TRACER.clear()
            before = dict(store.write_stats())
            rows = _write_records(mgr, sid, rng)
            stats = {k: v - before[k] for k, v in store.write_stats().items()}
            rounds = len(mgr.cluster.meta(sid).recv_sizes)
            assert stats["ram_rounds"] == 3 and stats["recycled_rounds"] == rounds - 1 - 3 >= 2
            assert stats["rollovers"] == rounds - 1 and stats["spilled_bytes"] > 0
            files = _spill_files(store)
            assert len(files) == stats["recycled_rounds"] and all(os.path.exists(f) for f in files)
            if sid:  # the first job's round buffers came back and are this one's
                assert stats["pool_hits"] == 3 and stats["pool_misses"] == 1
            names = [ev["name"] for ev in TRACER.events]
            assert names.count("exchange.h2d.disk") == names.count("store.spill") == stats["recycled_rounds"]
            assert names.count("exchange.h2d") == rounds
            for r in range(REDUCERS):
                batches = list(mgr.get_reader(sid, r, r + 1, deserializer=SERIALIZER).read_batches())
                assert all(np.array_equal(b, rows[(m, r)]) for m, b in enumerate(batches))
            mgr.unregister_shuffle(sid)
            gc.collect()
            assert not any(os.path.exists(f) for f in files) and store._spill_dir is None
            after = store.write_stats()
            assert store._ram_round_bytes == 0 and after["pool_dropped_busy"] == 0
            assert after["pool_held_bytes"] == 3 * region  # every RAM round's buffer, up to the budget
        counters = mgr.cluster.stats.counters("exchange.assemble")
        assert counters["disk_rounds"] == 2 * stats["recycled_rounds"]
        assert counters["disk_bytes"] == counters["disk_rounds"] * region
