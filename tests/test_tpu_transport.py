"""End-to-end shuffle through TpuShuffleCluster on the virtual 8-executor mesh.

This is the minimum end-to-end slice of SURVEY.md section 7: M mappers write
partition blocks into per-executor staging, ONE collective superstep moves
everything, R reducers fetch and verify against a CPU shuffle oracle — the
GroupByTest-equivalent without Spark.
"""

import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.block import MemoryBlock, ShuffleBlockId
from sparkucx_tpu.core.operation import OperationStatus, TransportError
from sparkucx_tpu.transport.tpu import TpuShuffleCluster

N_EXEC = 8


def _buf(n):
    return MemoryBlock(np.zeros(n, dtype=np.uint8), size=n)


@pytest.fixture(scope="module")
def cluster():
    conf = TpuShuffleConf(
        staging_capacity_per_executor=1 << 20, block_alignment=128, num_executors=N_EXEC
    )
    return TpuShuffleCluster(conf, num_executors=N_EXEC)


def _run_shuffle(cluster, shuffle_id, num_mappers, num_reducers, rng, max_block=2000):
    """Write random blocks, commit, exchange. Returns the oracle dict."""
    meta = cluster.create_shuffle(shuffle_id, num_mappers, num_reducers)
    oracle = {}
    for m in range(num_mappers):
        owner = meta.map_owner[m]
        t = cluster.transport(owner)
        w = t.store.map_writer(shuffle_id, m)
        for r in range(num_reducers):
            size = int(rng.integers(0, max_block))
            payload = rng.integers(0, 256, size=size, dtype=np.uint8).tobytes()
            oracle[(m, r)] = payload
            w.write_partition(r, payload)
        t.commit_block(w.commit().pack())
    cluster.run_exchange(shuffle_id)
    return meta, oracle


class TestEndToEndShuffle:
    def test_full_shuffle_vs_oracle(self, cluster, rng):
        M, R = 16, 24
        meta, oracle = _run_shuffle(cluster, 0, M, R, rng)
        # every reducer fetches every one of its blocks on its owning executor
        for r in range(R):
            consumer = meta.owner_of_reduce(r)
            t = cluster.transport(consumer)
            bids = [ShuffleBlockId(0, m, r) for m in range(M)]
            bufs = [_buf(4096) for _ in range(M)]
            reqs = t.fetch_blocks_by_block_ids(consumer, bids, bufs, [None] * M)
            while not all(q.completed() for q in reqs):
                t.progress()
            for m in range(M):
                res = reqs[m].wait(1)
                assert res.status == OperationStatus.SUCCESS, str(res.error)
                assert bufs[m].host_view()[: bufs[m].size].tobytes() == oracle[(m, r)]

    def test_skewed_and_empty_partitions(self, cluster, rng):
        M, R = 4, 8
        meta = cluster.create_shuffle(1, M, R)
        # all data goes to reducer 5; everything else empty
        big = rng.integers(0, 256, size=30_000, dtype=np.uint8).tobytes()
        for m in range(M):
            t = cluster.transport(meta.map_owner[m])
            w = t.store.map_writer(1, m)
            for r in range(R):
                w.write_partition(r, big if r == 5 else b"")
            t.commit_block(w.commit().pack())
        cluster.run_exchange(1)
        consumer = meta.owner_of_reduce(5)
        t = cluster.transport(consumer)
        bufs = [_buf(32768) for _ in range(M)]
        reqs = t.fetch_blocks_by_block_ids(
            consumer, [ShuffleBlockId(1, m, 5) for m in range(M)], bufs, [None] * M
        )
        for m in range(M):
            assert reqs[m].wait(1).status == OperationStatus.SUCCESS
            assert bufs[m].host_view()[: bufs[m].size].tobytes() == big
        # empty block fetch succeeds with zero size
        consumer0 = meta.owner_of_reduce(0)
        t0 = cluster.transport(consumer0)
        [req] = t0.fetch_blocks_by_block_ids(consumer0, [ShuffleBlockId(1, 0, 0)], [_buf(64)], [None])
        res = req.wait(1)
        assert res.status == OperationStatus.SUCCESS
        assert res.stats.recv_size == 0

    def test_fetch_wrong_owner_fails(self, cluster, rng):
        meta, _ = _run_shuffle(cluster, 2, 4, 8, rng, max_block=100)
        r = 0
        wrong = (meta.owner_of_reduce(r) + 1) % N_EXEC
        t = cluster.transport(wrong)
        [req] = t.fetch_blocks_by_block_ids(wrong, [ShuffleBlockId(2, 0, r)], [_buf(256)], [None])
        res = req.wait(1)
        assert res.status == OperationStatus.FAILURE
        assert "owned by" in str(res.error)

    def test_exchange_requires_all_commits(self, cluster, rng):
        meta = cluster.create_shuffle(3, 4, 4)
        t = cluster.transport(meta.map_owner[0])
        w = t.store.map_writer(3, 0)
        w.write_partition(0, b"x")
        t.commit_block(w.commit().pack())
        with pytest.raises(TransportError, match="before all maps committed"):
            cluster.run_exchange(3)

    def test_double_exchange_rejected(self, cluster, rng):
        _run_shuffle(cluster, 4, 2, 2, rng, max_block=50)
        with pytest.raises(TransportError, match="already exchanged"):
            cluster.run_exchange(4)

    def test_fetch_before_exchange_fails(self, cluster, rng):
        meta = cluster.create_shuffle(5, 1, 1)
        t = cluster.transport(meta.owner_of_reduce(0))
        [req] = t.fetch_blocks_by_block_ids(0, [ShuffleBlockId(5, 0, 0)], [_buf(8)], [None])
        assert req.wait(1).status == OperationStatus.FAILURE


class TestMultiRound:
    def test_spill_shuffle_end_to_end(self, rng):
        # Staging deliberately too small for one round: data spills across
        # multiple collective rounds and every block still arrives intact.
        conf = TpuShuffleConf(
            staging_capacity_per_executor=N_EXEC * 4096,  # 4 KiB per peer region
            block_alignment=128,
            num_executors=N_EXEC,
        )
        cluster = TpuShuffleCluster(conf, num_executors=N_EXEC)
        M, R = 3 * N_EXEC, 8  # 3 maps/executor x 2 KiB padded blocks > 4 KiB regions
        meta = cluster.create_shuffle(0, M, R)
        oracle = {}
        for m in range(M):
            t = cluster.transport(meta.map_owner[m])
            w = t.store.map_writer(0, m)
            for r in range(R):
                payload = rng.integers(0, 256, size=2000, dtype=np.uint8).tobytes()
                oracle[(m, r)] = payload
                w.write_partition(r, payload)
            t.commit_block(w.commit().pack())
        rounds = max(t.store.num_rounds(0) for t in cluster.transports)
        assert rounds > 1, "test should actually spill"
        cluster.run_exchange(0)
        for r in range(R):
            consumer = meta.owner_of_reduce(r)
            t = cluster.transport(consumer)
            bufs = [_buf(4096) for _ in range(M)]
            reqs = t.fetch_blocks_by_block_ids(
                consumer, [ShuffleBlockId(0, m, r) for m in range(M)], bufs, [None] * M
            )
            for m in range(M):
                res = reqs[m].wait(5)
                assert res.status == OperationStatus.SUCCESS, str(res.error)
                assert bufs[m].host_view()[: bufs[m].size].tobytes() == oracle[(m, r)]


class TestPullFallback:
    def test_fetch_block_from_peer_store(self, cluster, rng):
        # The straggler path: read a peer's staged block directly, pre-exchange.
        meta = cluster.create_shuffle(6, 2, 2)
        owner = meta.map_owner[1]
        t_owner = cluster.transport(owner)
        w = t_owner.store.map_writer(6, 1)
        w.write_partition(0, b"straggler-block")
        w.write_partition(1, b"")
        t_owner.commit_block(w.commit().pack())

        fetcher = cluster.transport((owner + 1) % N_EXEC)
        out = _buf(64)
        req = fetcher.fetch_block(owner, 6, 1, 0, out)
        while not req.completed():
            fetcher.progress()
        assert req.wait(1).status == OperationStatus.SUCCESS
        assert out.host_view()[: out.size].tobytes() == b"straggler-block"

    def test_fetch_block_missing(self, cluster):
        cluster.create_shuffle(7, 1, 1)
        fetcher = cluster.transport(0)
        req = fetcher.fetch_block(0, 7, 0, 0, _buf(8))
        while not req.completed():
            fetcher.progress()
        assert req.wait(1).status == OperationStatus.FAILURE


class TestResidentBlocks:
    """``resident_blocks``: the blocks an executor received, where they lie —
    what a local read borrows in place of a fetch into a result buffer."""

    def test_views_are_the_located_blocks_in_order_and_copy_nothing(self, cluster, rng):
        meta, oracle = _run_shuffle(cluster, 20, 2 * N_EXEC, 2 * N_EXEC, rng)
        for r in range(2 * N_EXEC):
            consumer = meta.owner_of_reduce(r)
            bids = [ShuffleBlockId(20, m, r) for m in reversed(range(2 * N_EXEC)) if oracle[(m, r)]]
            [shard] = [rnd[consumer] for rnd in meta.recv_shards]
            writeable = shard.flags.writeable
            views = cluster.transport(consumer).resident_blocks(bids)
            assert len(views) == len(bids)
            for bid, view in zip(bids, views):
                located, length = cluster.locate_received_block(consumer, 20, bid.map_id, r)
                assert view.dtype == np.uint8 and view.ndim == 1 and view.size == length
                assert view.tobytes() == located.tobytes() == oracle[(bid.map_id, r)]
                assert not view.flags.writeable and memoryview(view).readonly
                assert np.shares_memory(view, shard)  # no copy: the shard's own bytes
            assert shard.flags.writeable == writeable  # the views are read-only, the shard as it was
        assert cluster.transport(0).resident_blocks([]) == []

    def test_every_sender_chunk_start_is_summed_once_a_call(self, cluster, rng, monkeypatch):
        meta, oracle = _run_shuffle(cluster, 21, 3 * N_EXEC, N_EXEC, rng)
        r = N_EXEC - 1
        consumer = meta.owner_of_reduce(r)
        bids = [ShuffleBlockId(21, m, r) for m in range(3 * N_EXEC) if oracle[(m, r)]]
        senders = {meta.map_owner[b.map_id] for b in bids}
        assert len(senders) < len(bids)  # several blocks a sender: the sum would repeat

        class Counting:
            def __init__(self, sizes):
                self.sizes, self.sums = sizes, 0

            def __getitem__(self, key):
                self.sums += 1
                return self.sizes[key]

        counted = [Counting(sizes) for sizes in meta.recv_sizes]
        monkeypatch.setattr(meta, "recv_sizes", counted)
        views = cluster.transport(consumer).resident_blocks(bids)
        assert [v.tobytes() for v in views] == [oracle[(b.map_id, r)] for b in bids]
        assert sum(c.sums for c in counted) == len(senders)

    @pytest.mark.parametrize("sid, case, match", [
        (22, "wrong-owner", "owned by"),
        (24, "not-exchanged", "not exchanged"),
        (26, "unknown-shuffle", "unknown shuffle"),
        (28, "another-shuffle", "not from shuffle"),
        (30, "map-never-committed", "never committed"),
    ], ids=lambda v: v if isinstance(v, str) and "-" in v else "")
    def test_it_raises_the_typed_errors_of_the_single_lookup(self, cluster, rng, sid, case, match):
        meta, oracle = _run_shuffle(cluster, sid, 2, N_EXEC, rng, max_block=100)
        consumer = meta.owner_of_reduce(0)
        good = ShuffleBlockId(sid, 0, 0)
        t = cluster.transport(consumer)
        if case == "wrong-owner":
            t, bids = cluster.transport((consumer + 1) % N_EXEC), [good]
        elif case == "not-exchanged":
            cluster.create_shuffle(sid + 1, 1, 1)
            bids = [ShuffleBlockId(sid + 1, 0, 0)]
        elif case == "unknown-shuffle":
            bids = [ShuffleBlockId(9999, 0, 0)]
        elif case == "another-shuffle":
            bids = [good, ShuffleBlockId(sid + 1, 0, 0)]
        else:
            bids = [good, ShuffleBlockId(sid, 7, 0)]
        with pytest.raises(TransportError, match=match):
            t.resident_blocks(bids)


class TestStats:
    def test_fetch_stats_recv_size(self, cluster, rng):
        meta, oracle = _run_shuffle(cluster, 8, 2, 2, rng, max_block=500)
        r = 0
        consumer = meta.owner_of_reduce(r)
        t = cluster.transport(consumer)
        [req] = t.fetch_blocks_by_block_ids(consumer, [ShuffleBlockId(8, 1, r)], [_buf(1024)], [None])
        res = req.wait(1)
        assert res.stats.recv_size == len(oracle[(1, r)])
        assert res.stats.elapsed_ns() > 0


class TestRegistry:
    def test_upstream_registry_parity(self, cluster):
        from sparkucx_tpu.core.block import BytesBlock

        t = cluster.transport(0)
        bid = ShuffleBlockId(99, 0, 0)
        t.register(bid, BytesBlock(b"reg"))
        assert t.registered_block(bid) is not None
        t.unregister_shuffle(99)
        assert t.registered_block(bid) is None


class TestHierarchicalCluster:
    """numSlices > 1 routes the cluster's superstep through the two-phase
    ICI+DCN exchange (ops/hierarchy.py) — same results, different lowering."""

    def test_full_shuffle_vs_oracle_two_slices(self, rng):
        conf = TpuShuffleConf(
            staging_capacity_per_executor=1 << 20,
            block_alignment=128,
            num_executors=N_EXEC,
            num_slices=2,
        )
        cluster = TpuShuffleCluster(conf, num_executors=N_EXEC)
        M, R = 8, 16
        meta, oracle = _run_shuffle(cluster, 0, M, R, rng)
        for r in range(R):
            consumer = meta.owner_of_reduce(r)
            t = cluster.transport(consumer)
            bids = [ShuffleBlockId(0, m, r) for m in range(M)]
            bufs = [_buf(4096) for _ in range(M)]
            t.fetch_blocks_by_block_ids(consumer, bids, bufs, [None] * M)
            for m, buf in enumerate(bufs):
                got = buf.host_view()[: buf.size].tobytes()
                assert got == oracle[(m, r)], f"mismatch map={m} reduce={r}"

    def test_invalid_factorization_rejected(self):
        with pytest.raises(ValueError, match="divisible"):
            TpuShuffleConf().replace(num_executors=8, num_slices=3)


class TestRoundAssembly:
    """The round's global send array is made from one piece per executor, each
    put onto its own device: a view of the sealed round under the default
    conf, a zero piece made on the device for an executor with fewer rounds,
    and never a global host buffer.  Two executors, 1 MiB of staging each;
    counts and bytes on the CPU mesh, no rate."""

    ROUND_BYTES = 2 << 20  # two executors' staging buckets
    BLOCK = 150_000

    # mappers on executor 0 and on executor 1 -> their staging rounds
    ONE_DEVICE_SEALED = (6, 1)  # 2 rounds and 1: the single round seals onto its device
    BOTH_SPILL = (9, 4)  # 3 rounds and 2: views of the completed rounds, then the live buffers

    @staticmethod
    def _cluster(**conf):
        conf = TpuShuffleConf(
            staging_capacity_per_executor=1 << 20, block_alignment=128, num_executors=2, **conf
        )
        return TpuShuffleCluster(conf, num_executors=2)

    def _stage(self, cluster, shuffle_id, mappers, rng):
        """Write ``mappers[e]`` map outputs on executor ``e`` (two blocks of
        150 KB each) and commit; the exchange is left to the caller."""
        owners = [0] * mappers[0] + [1] * mappers[1]
        meta = cluster.create_shuffle(shuffle_id, len(owners), 2, map_owner=owners)
        oracle = {}
        for m, owner in enumerate(owners):
            t = cluster.transport(owner)
            w = t.store.map_writer(shuffle_id, m)
            for r in range(2):
                payload = rng.integers(0, 256, size=self.BLOCK - 7 * m - r, dtype=np.uint8).tobytes()
                oracle[(m, r)] = payload
                w.write_partition(r, payload)
            t.commit_block(w.commit().pack())
        return meta, oracle

    @staticmethod
    def _read_back(cluster, shuffle_id, meta, oracle):
        for r in range(2):
            consumer = meta.owner_of_reduce(r)
            t = cluster.transport(consumer)
            maps = sorted(m for m, rr in oracle if rr == r)
            bids = [ShuffleBlockId(shuffle_id, m, r) for m in maps]
            bufs = [_buf(1 << 18) for _ in maps]
            reqs = t.fetch_blocks_by_block_ids(consumer, bids, bufs, [None] * len(maps))
            while not all(q.completed() for q in reqs):
                t.progress()
            for m, req, buf in zip(maps, reqs, bufs):
                res = req.wait(1)
                assert res.status == OperationStatus.SUCCESS, str(res.error)
                assert buf.host_view()[: buf.size].tobytes() == oracle[(m, r)], (m, r)

    @pytest.mark.parametrize("depth", [1, 2])
    @pytest.mark.parametrize("mappers", [ONE_DEVICE_SEALED, BOTH_SPILL])
    def test_unequal_round_counts_read_back_exact(self, rng, mappers, depth):
        cluster = self._cluster(pipeline_depth=depth)
        meta, oracle = self._stage(cluster, 0, mappers, rng)
        rounds = [t.store.num_rounds(0) for t in cluster.transports]
        assert rounds[0] > rounds[1] >= 1  # executor 1 contributes None at the end
        cluster.run_exchange(0)
        assert len(meta.recv_sizes) == rounds[0]
        self._read_back(cluster, 0, meta, oracle)

    @pytest.mark.parametrize("depth", [1, 2])
    @pytest.mark.parametrize(
        "tier, kinds_sealed",
        [({"max_host_pool_bytes": 0}, {np.memmap, np.ndarray}), ({}, {np.ndarray})],
        ids=["disk-tier", "ram-tier"],
    )
    def test_exchange_never_writes_into_a_sealed_round(self, rng, monkeypatch, depth, tier, kinds_sealed):
        """On the CPU ``device_put`` may alias host memory and the exchange
        donates its input: the sealed rounds (memmap or RAM rounds, and the
        live buffer), which the pull fallback reads afterwards, hold the same
        bits after the exchange as when they were sealed."""
        cluster = self._cluster(pipeline_depth=depth, **tier)
        self._stage(cluster, 0, self.BOTH_SPILL, rng)
        at_seal = {}
        for t in cluster.transports:
            def seal(shuffle_id, store=t.store, real=t.store.seal):
                out = real(shuffle_id)
                at_seal[store.executor_id] = [np.array(p) for p, _ in out]
                return out

            monkeypatch.setattr(t.store, "seal", seal)
        cluster.run_exchange(0)
        kinds = set()
        for t in cluster.transports:
            sealed = t.store._state(0).sealed_payload
            assert len(sealed) == len(at_seal[t.executor_id]) >= 2
            for before, after in zip(at_seal[t.executor_id], sealed):
                kinds.add(type(after))
                np.testing.assert_array_equal(np.asarray(after), before)
        assert kinds == kinds_sealed

    @pytest.mark.parametrize(
        "mappers, host_pieces", [(ONE_DEVICE_SEALED, 2), (BOTH_SPILL, 5)]
    )
    def test_default_conf_puts_views_and_copies_nothing(self, rng, mappers, host_pieces):
        cluster = self._cluster()
        self._stage(cluster, 0, mappers, rng)
        cluster.run_exchange(0)
        counters = cluster.stats.counters("exchange.assemble")
        # every host-resident sealed round, whole, and nothing for the
        # device-sealed round or the None of the executor with fewer rounds
        assert counters == {"direct_bytes": host_pieces << 20, "copied_bytes": 0}

    def test_quota_chunked_plan_copies_each_window_once(self, rng):
        cluster = self._cluster(slot_quota_rows=1024)  # 4 windows of a 4,096-row slot
        meta, oracle = self._stage(cluster, 0, self.BOTH_SPILL, rng)
        cluster.run_exchange(0)
        counters = cluster.stats.counters("exchange.assemble")
        assert counters["copied_bytes"] > 0
        # a strided window at n=2 is never a view; every host byte is put once
        assert counters["direct_bytes"] == 0 and counters["copied_bytes"] <= 5 << 20
        self._read_back(cluster, 0, meta, oracle)

    def test_submit_allocates_no_global_host_buffer(self, rng, monkeypatch):
        """tracemalloc's peak between the start of a submit and its
        collective dispatch stays under a quarter of the round's bytes: no
        array of ``n * bucketed`` rows is allocated on the host."""
        import tracemalloc

        from sparkucx_tpu.testing import faults

        cluster = self._cluster(pipeline_depth=1)  # serial: no drain thread allocating beside it
        self._stage(cluster, 0, self.BOTH_SPILL, rng)
        start, peaks = [], []

        def at_submit(**_ctx):
            tracemalloc.reset_peak()
            start.append(tracemalloc.get_traced_memory()[0])

        real = cluster._exchange_fn

        def recording(*args, **kwargs):
            fn = real(*args, **kwargs)

            def dispatch(data, size_mat):
                peaks.append(tracemalloc.get_traced_memory()[1] - start[-1])
                return fn(data, size_mat)

            return dispatch

        monkeypatch.setattr(cluster, "_exchange_fn", recording)
        tracemalloc.start()
        try:
            with faults.injected_faults(("exchange.submit", at_submit)):
                cluster.run_exchange(0)
        finally:
            tracemalloc.stop()
        assert len(peaks) == len(start) == 3  # one submit a round
        assert max(peaks) < self.ROUND_BYTES // 4, peaks
