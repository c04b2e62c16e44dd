"""Tests for L4-L7: writer/reader/resolver/manager — the GroupByTest-style flow.

The reference's integration gate is stock Spark GroupByTest on a 2-executor
cluster (buildlib/test.sh:163-167); here the same shape runs through the manager
API: map tasks partition (key, value) records by hash, the collective superstep
moves blocks, reducers aggregate + sort and the result is checked against a pure
CPU groupBy oracle.
"""

import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.operation import TransportError
from sparkucx_tpu.shuffle.manager import TpuShuffleManager
from sparkucx_tpu.shuffle.reader import serialize_records

N_EXEC = 4


@pytest.fixture(scope="module")
def manager():
    conf = TpuShuffleConf(
        staging_capacity_per_executor=1 << 20,
        num_executors=N_EXEC,
        max_blocks_per_request=3,  # force windowing in tests
    )
    mgr = TpuShuffleManager(conf, num_executors=N_EXEC)
    yield mgr
    mgr.stop()


def _write_records(manager, shuffle_id, map_id, num_reducers, records):
    """Partition records by hash(key) % R and write through the SPI writer."""
    writer = manager.get_writer(shuffle_id, map_id)
    by_part = {}
    for k, v in records:
        by_part.setdefault(hash(k) % num_reducers, []).append((k, v))
    for r in sorted(by_part):
        pw = writer.get_partition_writer(r)
        with pw.open_stream() as stream:
            stream.write(serialize_records(by_part[r]))
    return writer.commit_all_partitions()


class TestGroupByFlow:
    def test_groupby_end_to_end(self, manager, rng):
        M, R, SID = 6, 8, 0
        manager.register_shuffle(SID, M, R)
        oracle = {}
        for m in range(M):
            records = [(f"key-{int(rng.integers(0, 50))}", int(rng.integers(0, 1000))) for _ in range(200)]
            for k, v in records:
                oracle[k] = oracle.get(k, 0) + v
            lengths = _write_records(manager, SID, m, R, records)
            assert lengths.sum() > 0
        assert manager.exchange_ready(SID)
        manager.run_exchange(SID)

        got = {}
        for r in range(R):
            reader = manager.get_reader(
                SID, r, r + 1, aggregator=lambda a, b: a + b, key_ordering=True
            )
            out = list(reader.read())
            keys = [k for k, _ in out]
            assert keys == sorted(keys)  # key_ordering honored
            for k, v in out:
                assert hash(k) % R == r  # partition integrity
                got[k] = v
            assert reader.metrics.records_read >= len(out)
        assert got == oracle

    def test_reader_range_spanning_partitions(self, manager, rng):
        M, R, SID = 2, 8, 1
        manager.register_shuffle(SID, M, R)
        for m in range(M):
            _write_records(manager, SID, m, R, [(f"k{i}", i) for i in range(64)])
        manager.run_exchange(SID)
        # one reader over an executor's full contiguous range (R/N_EXEC partitions)
        meta = manager.cluster.meta(SID)
        start, end = meta.peer_ranges[0]
        reader = manager.get_reader(SID, start, end)
        records = list(reader.read())
        expected = [
            (f"k{i}", i) for i in range(64) if start <= hash(f"k{i}") % R < end
        ] * M
        assert sorted(map(str, records)) == sorted(map(str, expected))
        # windowing actually happened (max_blocks_per_request=3)
        assert reader.metrics.remote_blocks_fetched > 3

    def test_metrics_accounting(self, manager):
        # Deterministic partition placement (hash() is seed-randomized).
        M, R, SID = 1, 2, 2
        manager.register_shuffle(SID, M, R)
        writer = manager.get_writer(SID, 0)
        for r, records in [(0, [("a", 1)]), (1, [("b", 2), ("c", 3)])]:
            pw = writer.get_partition_writer(r)
            with pw.open_stream() as stream:
                stream.write(serialize_records(records))
        writer.commit_all_partitions()
        manager.run_exchange(SID)
        reader = manager.get_reader(SID, 0, 1)
        records = list(reader.read())
        m = reader.metrics
        assert records == [("a", 1)]
        assert m.remote_bytes_read > 0
        assert m.remote_blocks_fetched == 1
        assert m.records_read == 1
        assert m.fetch_wait_ns >= 0


class TestGroupByTestAtTheDefaultConf:
    """The upstream gate job's record shape through the manager at the conf a
    user gets by default, against the plain GroupBy: what executed is the
    platform's own lowering, and a warm manager builds nothing new."""

    @pytest.fixture(scope="class")
    def warm(self, groupbytest):
        from benchmark.counters import CompileCounter

        compiles = CompileCounter()
        with TpuShuffleManager(TpuShuffleConf(), num_executors=2) as mgr:
            self._job(groupbytest, mgr, 0, groupbytest.records(4))
            yield mgr, compiles

    @staticmethod
    def _job(groupbytest, mgr, shuffle_id, records):
        groupbytest.write_and_exchange(mgr, shuffle_id, records)
        checks = []
        for r in range(records.reducers):
            check = records.check(r, full=True)
            reader = mgr.get_reader(shuffle_id, r, r + 1)
            for key, value in reader.read():
                check.add(key, value)
            metrics = reader.metrics
            assert (metrics.blocks_retried, metrics.failovers, metrics.fetch_timeouts) == (0, 0, 0)
            assert check.ok(), f"reduce task {r} differs from the plain GroupBy"
            checks.append(check)
        assert records.complete(checks)
        mgr.unregister_shuffle(shuffle_id)

    def test_only_the_platforms_own_lowering_executed(self, warm):
        mgr, compiles = warm
        ran = mgr.cluster.executed_lowerings()
        assert set(ran["exchange"]) == {"dense"}  # 'local' / 'ragged' on the chip
        assert ran["gather"] == []  # the host read gathers nothing on the device
        assert compiles.since()["compiles"] >= 1

    def test_a_second_smaller_shuffle_compiles_nothing(self, warm, groupbytest):
        mgr, compiles = warm
        before = compiles.snapshot()
        self._job(groupbytest, mgr, 1, groupbytest.records(2, seed=30))
        assert compiles.since(before)["compiles"] == 0


class TestRoundBuffersFromJobToJob:
    """Two multi-round GroupByTest jobs back to back through one manager at the
    default conf but for a small staging capacity: every completed round stays
    in RAM, and the second job's every round is written in a buffer the
    removed first job gave back."""

    @staticmethod
    def _pool(mgr):
        return [t.store.write_stats() for t in mgr.cluster.transports]

    @pytest.mark.parametrize("executors", [1, 4])
    def test_the_second_job_allocates_nothing(self, groupbytest, executors):
        conf = TpuShuffleConf(staging_capacity_per_executor=1 << 20)
        with TpuShuffleManager(conf, num_executors=executors) as mgr:
            rounds = []
            for shuffle_id, seed in enumerate((29, 31)):
                before = self._pool(mgr)
                records = groupbytest.records(4, seed=seed)
                groupbytest.write_and_exchange(mgr, shuffle_id, records)
                rounds.append([t.store.num_rounds(shuffle_id) for t in mgr.cluster.transports])
                tiers = {
                    t.store.round_tier(shuffle_id, k)
                    for t, n in zip(mgr.cluster.transports, rounds[-1]) for k in range(n)
                }
                assert tiers == {"host"}
                checks = []
                for r in range(records.reducers):
                    check = records.check(r, full=True)
                    for key, value in mgr.get_reader(shuffle_id, r, r + 1).read():
                        check.add(key, value)
                    assert check.ok(), f"reduce task {r} differs from the plain GroupBy"
                    checks.append(check)
                assert records.complete(checks)
                mgr.unregister_shuffle(shuffle_id)
                after = self._pool(mgr)
                taken = [
                    (b["pool_hits"] - a["pool_hits"], b["pool_misses"] - a["pool_misses"])
                    for a, b in zip(before, after)
                ]
                if shuffle_id == 0:
                    assert taken == [(0, n) for n in rounds[0]]
                else:
                    assert rounds[1] == rounds[0] and min(rounds[1]) > 1
                    assert taken == [(n, 0) for n in rounds[1]]
                for b, n in zip(after, rounds[-1]):
                    assert b["spilled_bytes"] == 0 and b["ram_rounds"] == b["rollovers"]
                    assert b["pool_dropped_busy"] == 0 and b["pool_held_bytes"] == n * (1 << 20)


class TestTeraSortFlow:
    def test_terasort_style_global_sort(self, manager, rng):
        """TeraSort shape (BASELINE.md config: 'TeraSort 10GB'): range-partition
        random keys so partition order == global order, sort within partitions,
        verify the concatenation is globally sorted and complete."""
        M, R, SID = 4, 8, 30
        manager.register_shuffle(SID, M, R)
        all_keys = []
        bounds = [int(2**32 * (i + 1) / R) for i in range(R - 1)]  # range partitioner

        def partition_of(key):
            import bisect

            return bisect.bisect_right(bounds, key)

        for m in range(M):
            keys = [int(k) for k in rng.integers(0, 2**32, size=500)]
            all_keys.extend(keys)
            writer = manager.get_writer(SID, m)
            by_part = {}
            for k in keys:
                by_part.setdefault(partition_of(k), []).append((k, f"row-{k}"))
            for r in sorted(by_part):
                pw = writer.get_partition_writer(r)
                with pw.open_stream() as stream:
                    stream.write(serialize_records(by_part[r]))
            writer.commit_all_partitions()
        manager.run_exchange(SID)

        merged = []
        for r in range(R):
            reader = manager.get_reader(SID, r, r + 1, key_ordering=True)
            part = [k for k, _ in reader.read()]
            assert part == sorted(part)  # sorted within partition
            if merged and part:
                assert merged[-1] <= part[0]  # range partitioning: global order
            merged.extend(part)
        assert merged == sorted(all_keys)  # complete and globally sorted
        manager.unregister_shuffle(SID)


class TestWriterProtocol:
    def test_partition_order_enforced(self, manager):
        manager.register_shuffle(10, 1, 4)
        w = manager.get_writer(10, 0)
        w.get_partition_writer(2)
        with pytest.raises(TransportError, match="increasing order"):
            w.get_partition_writer(1)

    def test_double_commit_rejected(self, manager):
        manager.register_shuffle(11, 1, 2)
        w = manager.get_writer(11, 0)
        pw = w.get_partition_writer(0)
        with pw.open_stream() as s:
            s.write(b"x")
        w.commit_all_partitions()
        with pytest.raises(TransportError, match="already committed"):
            w.commit_all_partitions()

    def test_commit_registers_blocks_with_transport(self, manager):
        from sparkucx_tpu.core.block import ShuffleBlockId

        manager.register_shuffle(12, 1, 2)
        w = manager.get_writer(12, 0)
        pw = w.get_partition_writer(1)
        with pw.open_stream() as s:
            s.write(b"registered!")
        w.commit_all_partitions()
        meta = manager.cluster.meta(12)
        owner = meta.map_owner[0]
        blk = manager.cluster.transport(owner).registered_block(ShuffleBlockId(12, 0, 1))
        assert blk is not None
        assert blk.get_size() == len(b"registered!")

    def test_write_lengths_reported(self, manager):
        manager.register_shuffle(13, 1, 3)
        w = manager.get_writer(13, 0)
        for r, size in [(0, 10), (2, 500)]:
            pw = w.get_partition_writer(r)
            with pw.open_stream() as s:
                s.write(b"z" * size)
        lengths = w.commit_all_partitions()
        assert lengths.tolist() == [10, 0, 500]


class TestResolver:
    def test_get_block_data_from_store(self, manager):
        manager.register_shuffle(20, 1, 2)
        _write_records(manager, 20, 0, 2, [("p", 1)])
        meta = manager.cluster.meta(20)
        owner = meta.map_owner[0]
        resolver = manager.resolvers[owner]
        r = next(r for r in range(2) if manager.cluster.transport(owner).store.block_length(20, 0, r))
        data = resolver.get_block_data(20, 0, r)
        assert len(data) > 0

    def test_unregister_shuffle_cleans_everything(self, manager):
        from sparkucx_tpu.core.block import ShuffleBlockId

        manager.register_shuffle(21, 1, 2)
        _write_records(manager, 21, 0, 2, [("q", 1), ("r", 2)])
        meta = manager.cluster.meta(21)
        owner = meta.map_owner[0]
        manager.unregister_shuffle(21)
        t = manager.cluster.transport(owner)
        assert t.registered_block(ShuffleBlockId(21, 0, 0)) is None
        with pytest.raises(TransportError):
            t.store.read_block(21, 0, 0)
        with pytest.raises(KeyError):
            manager.get_writer(21, 0)


class TestManagerLifecycle:
    def test_unknown_shuffle(self, manager):
        with pytest.raises(KeyError):
            manager.get_reader(999, 0, 1)

    def test_stop_idempotent(self):
        mgr = TpuShuffleManager(
            TpuShuffleConf(staging_capacity_per_executor=1 << 18, num_executors=2),
            num_executors=2,
        )
        mgr.stop()
        mgr.stop()


class _FlakyTransport:
    """Delegating wrapper that fails the batch fetch of one block N times —
    the batch path breaks, the per-block pull path still works."""

    def __init__(self, inner, fail_bid, fail_times=1):
        self.inner = inner
        self.fail_bid = fail_bid
        self.remaining = fail_times

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def fetch_blocks_by_block_ids(self, executor_id, bids, bufs, cbs):
        from sparkucx_tpu.core.operation import (
            OperationResult, OperationStats, OperationStatus, Request, TransportError,
        )

        out = []
        for bid, buf, cb in zip(bids, bufs, cbs):
            if bid == self.fail_bid and self.remaining > 0:
                self.remaining -= 1
                req = Request(OperationStats())
                req.stats.mark_done()
                req.complete(OperationResult(
                    OperationStatus.FAILURE,
                    error=TransportError("injected batch-fetch failure"),
                    stats=req.stats,
                ))
                out.append(req)
            else:
                out.extend(self.inner.fetch_blocks_by_block_ids(executor_id, [bid], [buf], [cb]))
        return out


class TestFetchRetry:
    """The reference never retries a failed fetch (SURVEY.md section 5.3); the
    reader's pull-path fallback must recover and count the retry."""

    def _shuffled_cluster(self):
        from sparkucx_tpu.config import TpuShuffleConf
        from sparkucx_tpu.transport.tpu import TpuShuffleCluster

        conf = TpuShuffleConf(
            staging_capacity_per_executor=1 << 20, block_alignment=128, num_executors=2
        )
        cluster = TpuShuffleCluster(conf, num_executors=2)
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

    def test_batch_failure_recovers_via_pull_path(self):
        from sparkucx_tpu.core.block import ShuffleBlockId
        from sparkucx_tpu.shuffle.reader import TpuShuffleReader

        cluster, meta, payloads = self._shuffled_cluster()
        r = 0
        consumer = meta.owner_of_reduce(r)
        flaky = _FlakyTransport(cluster.transport(consumer), ShuffleBlockId(0, 1, r))
        reader = TpuShuffleReader(
            flaky, consumer, 0, r, r + 1, 2,
            block_sizes=lambda m, rr: len(payloads[(m, rr)]),
            sender_of=lambda m: meta.map_owner[m],
            fetch_retries=1,
        )
        got = {blk.block_id.map_id: blk.data for blk in reader.fetch_blocks()}
        assert got == {0: payloads[(0, r)], 1: payloads[(1, r)]}
        assert reader.metrics.blocks_retried == 1
        assert reader.metrics.remote_blocks_fetched == 2

    def test_retries_disabled_raises(self):
        from sparkucx_tpu.core.block import ShuffleBlockId
        from sparkucx_tpu.core.operation import TransportError
        from sparkucx_tpu.shuffle.reader import TpuShuffleReader

        cluster, meta, payloads = self._shuffled_cluster()
        r = 0
        consumer = meta.owner_of_reduce(r)
        flaky = _FlakyTransport(cluster.transport(consumer), ShuffleBlockId(0, 1, r))
        reader = TpuShuffleReader(
            flaky, consumer, 0, r, r + 1, 2,
            block_sizes=lambda m, rr: len(payloads[(m, rr)]),
            sender_of=lambda m: meta.map_owner[m],
            fetch_retries=0,
        )
        with pytest.raises(TransportError, match="injected"):
            list(reader.fetch_blocks())
