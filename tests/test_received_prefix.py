"""The exchange's D2H moves what was received, not the shard.

A consumer's received shard holds a tight sender-major prefix of
``size_matrix[:, j].sum()`` rows; the plan executor brings back that prefix
rounded up to a power of two of rows and to a sixteenth of the shard at
least, the shard itself where the bucket reaches it, and nothing for a
consumer that received nothing.  Every block a
reduce task reads is the staged block byte for byte, in both host receive
modes and under single-shot and chunked plans.  Sizes and counts on the CPU
mesh; no rate.
"""

import dataclasses

import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.block import MemoryBlock, ShuffleBlockId
from sparkucx_tpu.core.operation import OperationStatus, TransportError
from sparkucx_tpu.shuffle.manager import TpuShuffleManager
from sparkucx_tpu.transport.tpu import TpuShuffleCluster

ROW = 128
STAGING = 1 << 16  # 512 rows an executor: slots of 512, 256, 128 rows at n = 1, 2, 4

KINDS = ("full", "one-empty", "single-row", "pow2-exact", "ragged")


def size_matrix(kind: str, n: int) -> np.ndarray:
    """Rows sender ``i`` stages for consumer ``j``."""
    slot = STAGING // ROW // n
    i, j = np.indices((n, n))
    if kind == "full":  # every shard's used rows are the shard
        return np.full((n, n), slot)
    if kind == "pow2-exact":  # used rows are a bucket, a quarter of the shard
        return np.full((n, n), slot // 4)
    sizes = (37 * i + 53 * j + 11) % slot
    if kind != "ragged":
        sizes[:, n - 1] = 0  # the last consumer receives nothing
        if kind == "single-row":
            sizes[0, n - 1] = 1  # or one row, from one sender
    return sizes


def bucket_rows(used: int, shard_rows: int) -> int:
    """Rows of one shard that cross to the host."""
    if not used:
        return 0
    return min(max(1 << (used - 1).bit_length(), shard_rows // 16), shard_rows)


def expected_d2h(sizes: np.ndarray, plan) -> dict:
    """The ``exchange.d2h`` counters of a one-round shuffle under ``plan``."""
    n, q = len(sizes), plan.slot_rows
    shard_rows = n * q
    out = dict(
        shard_bytes=0, moved_bytes=0, used_bytes=0, skipped_shards=0, sliced_shards=0,
        kept_shards=0, fresh_shards=0,  # PR 43: on the CPU backend no landing block is kept
    )
    for chunk in range(plan.chunks_per_round[0]):
        used = np.clip(sizes - chunk * q, 0, q).sum(axis=0)
        for u in used:
            rows = bucket_rows(int(u), shard_rows)
            out["shard_bytes"] += shard_rows * ROW
            out["moved_bytes"] += rows * ROW
            out["used_bytes"] += int(u) * ROW
            out["skipped_shards"] += rows == 0
            out["sliced_shards"] += 0 < rows < shard_rows
            out["fresh_shards"] += rows > 0
    return out


def make_cluster(n: int, monkeypatch=None, **conf):
    conf = TpuShuffleConf(
        staging_capacity_per_executor=STAGING, block_alignment=ROW, num_executors=n, **conf
    )
    cluster = TpuShuffleCluster(conf, num_executors=n)
    plans = []
    if monkeypatch is not None:
        real = cluster.planner.plan

        def plan(ctx):
            plans.append(real(ctx))
            return plans[-1]

        monkeypatch.setattr(cluster.planner, "plan", plan)
    return cluster, plans


def stage(cluster, shuffle_id: int, sizes: np.ndarray, rng):
    """Map task ``i`` runs on executor ``i`` and writes two blocks for every
    consumer, ``sizes[i, j]`` rows together and ragged in their last row."""
    n = len(sizes)
    meta = cluster.create_shuffle(shuffle_id, n, 2 * n, map_owner=list(range(n)))
    oracle = {}
    for i in range(n):
        t = cluster.transport(i)
        w = t.store.map_writer(shuffle_id, i)
        for j in range(n):
            first = -(-int(sizes[i, j]) // 2)
            for k, rows in enumerate((first, int(sizes[i, j]) - first)):
                length = rows * ROW - ((i + j) % 5 if rows else 0)
                payload = rng.integers(0, 256, size=length, dtype=np.uint8).tobytes()
                oracle[(i, 2 * j + k)] = payload
                w.write_partition(2 * j + k, payload)
        t.commit_block(w.commit().pack())
    assert [t.store.num_rounds(shuffle_id) for t in cluster.transports] == [1] * n
    return meta, oracle


def read_back(cluster, shuffle_id: int, meta, oracle):
    """Every block through ``locate_received_block`` and through a fetch."""
    for (m, r), staged in oracle.items():
        consumer = meta.owner_of_reduce(r)
        view, length = cluster.locate_received_block(consumer, shuffle_id, m, r)
        assert length == len(staged) and view.tobytes() == staged, (m, r)
    for r in sorted({r for _, r in oracle}):
        consumer = meta.owner_of_reduce(r)
        t = cluster.transport(consumer)
        maps = sorted(m for m, rr in oracle if rr == r)
        bufs = [MemoryBlock(np.zeros(STAGING, dtype=np.uint8), size=STAGING) for _ in maps]
        reqs = t.fetch_blocks_by_block_ids(
            consumer, [ShuffleBlockId(shuffle_id, m, r) for m in maps], bufs, [None] * len(maps)
        )
        for m, req, buf in zip(maps, reqs, bufs):
            res = req.wait(1)
            assert res.status == OperationStatus.SUCCESS, str(res.error)
            assert buf.host_view()[: buf.size].tobytes() == oracle[(m, r)], (m, r)


@pytest.mark.parametrize("chunked", [False, True], ids=["single-shot", "chunked"])
@pytest.mark.parametrize("mode", ["array", "memmap"])
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("n", [1, 2, 4])
def test_every_block_reads_back_as_staged(rng, monkeypatch, tmp_path, n, kind, mode, chunked):
    slot = STAGING // ROW // n
    cluster, plans = make_cluster(
        n, monkeypatch, host_recv_mode=mode, spill_dir=str(tmp_path),
        slot_quota_rows=slot // 4 if chunked else 0,
    )
    sizes = size_matrix(kind, n)
    meta, oracle = stage(cluster, 0, sizes, rng)
    cluster.run_exchange(0)
    [plan] = plans
    assert plan.single_shot is not chunked
    np.testing.assert_array_equal(meta.recv_sizes[0], sizes.T)
    read_back(cluster, 0, meta, oracle)
    want = expected_d2h(sizes, plan)
    assert cluster.stats.counters("exchange.d2h") == want
    if plan.single_shot:
        # a host part is what crossed: the bucketed prefix, empty where nothing was received
        parts = [int(p.nbytes) for p in meta.recv_shards[0]]
        assert sum(parts) == want["moved_bytes"]
        assert parts == [bucket_rows(int(u), n * slot) * ROW for u in sizes.sum(axis=0)]
    cluster.remove_shuffle(0)


@pytest.mark.parametrize("n", [1, 2, 4])
def test_the_counters_are_the_bucketed_used_bytes(rng, n):
    """Three of the shapes by hand: whole shards, skipped shards, a slice."""
    slot = STAGING // ROW // n
    shard = n * slot * ROW
    for shuffle_id, (kind, moved, skipped, sliced) in enumerate([
        ("full", n * shard, 0, 0),
        ("pow2-exact", n * shard // 4, 0, n),
        ("one-empty", None, 1, None),
    ]):
        cluster, _ = make_cluster(n)
        sizes = size_matrix(kind, n)
        stage(cluster, shuffle_id, sizes, rng)
        cluster.run_exchange(shuffle_id)
        got = cluster.stats.counters("exchange.d2h")
        assert got["shard_bytes"] == n * shard and got["skipped_shards"] == skipped
        if moved is not None:
            assert (got["moved_bytes"], got["sliced_shards"]) == (moved, sliced)
        used_bytes = int(sizes.sum()) * ROW
        assert got["used_bytes"] == used_bytes <= got["moved_bytes"] < max(2 * used_bytes, 1)


def test_device_receive_moves_nothing_to_the_host(rng):
    cluster, _ = make_cluster(2, host_recv_mode="device", keep_device_recv=True)
    meta, oracle = stage(cluster, 0, size_matrix("one-empty", 2), rng)
    cluster.run_exchange(0)
    assert cluster.stats.counters("exchange.d2h") == {}
    assert meta.recv_shards is None
    for (m, r), staged in oracle.items():
        view, _ = cluster.locate_received_block(meta.owner_of_reduce(r), 0, m, r)
        assert view.tobytes() == staged


@pytest.mark.parametrize("mode", ["array", "memmap"])
def test_kept_device_shards_stay_whole(rng, tmp_path, mode):
    """``keep_device_recv`` keeps the full shards on the chip, sliced or not."""
    cluster, _ = make_cluster(2, host_recv_mode=mode, spill_dir=str(tmp_path), keep_device_recv=True)
    meta, oracle = stage(cluster, 0, size_matrix("single-row", 2), rng)
    cluster.run_exchange(0)
    assert [int(a.shape[0]) for a in meta.recv_device[0]] == [STAGING // ROW] * 2
    assert [int(p.nbytes) for p in meta.recv_shards[0]] == [64 * ROW, 32 * ROW]
    read_back(cluster, 0, meta, oracle)


@pytest.mark.parametrize("n", [1, 4])
def test_a_second_identical_shuffle_compiles_nothing(rng, n):
    from benchmark.counters import CompileCounter

    cluster, _ = make_cluster(n)
    sizes = size_matrix("ragged", n)  # whole and sliced shards at n=4, one slice at n=1
    compiles = CompileCounter()
    for shuffle_id in range(2):
        mark = compiles.snapshot()
        meta, oracle = stage(cluster, shuffle_id, sizes, rng)
        cluster.run_exchange(shuffle_id)
        read_back(cluster, shuffle_id, meta, oracle)
        cluster.remove_shuffle(shuffle_id)
        built = compiles.since(mark)["compiles"]
        assert built >= 1 if shuffle_id == 0 else built == 0
    assert cluster.stats.counters("exchange.d2h")["sliced_shards"] == 2


@pytest.mark.parametrize("mode", ["array", "memmap"])
def test_a_read_past_a_host_parts_end_raises(rng, tmp_path, mode):
    """A block whose table entry points past what its consumer received is
    refused with the typed error: no short slice passes for it."""
    cluster, _ = make_cluster(2, host_recv_mode=mode, spill_dir=str(tmp_path))
    sizes = size_matrix("single-row", 2)
    meta, oracle = stage(cluster, 0, sizes, rng)
    cluster.run_exchange(0)
    part = meta.recv_shards[0][1]
    assert part.nbytes == 32 * ROW  # consumer 1 received one row: the least bucket of a 512-row shard
    # the block consumer 1 did receive, moved past the bucket in its sender's table
    info = meta.mapper_infos[0]
    offset, length = info.partitions[2]
    assert length and meta.owner_of_reduce(2) == 1
    moved = info.partitions[:2] + ((offset + 32 * ROW, length),) + info.partitions[3:]
    meta.mapper_infos[0] = dataclasses.replace(info, partitions=moved)
    with pytest.raises(TransportError, match="lies past"):
        cluster.locate_received_block(1, 0, 0, 2)
    [req] = cluster.transport(1).fetch_blocks_by_block_ids(
        1, [ShuffleBlockId(0, 0, 2)], [MemoryBlock(np.zeros(ROW, np.uint8), size=ROW)], [None]
    )
    res = req.wait(1)
    assert res.status == OperationStatus.FAILURE and isinstance(res.error, TransportError)
    meta.mapper_infos[0] = info
    read_back(cluster, 0, meta, oracle)


@pytest.mark.parametrize("mode", ["array", "memmap"])
@pytest.mark.parametrize("executors", [1, 2, 4])
def test_reader_read_sees_the_plain_groupby(groupbytest, tmp_path, executors, mode):
    """The gate job's records over several staging rounds through the
    manager: a map task writes its partitions in reducer order, so a round
    fills one or two consumers' regions and the others receive nothing."""
    conf = TpuShuffleConf(
        staging_capacity_per_executor=1 << 20, host_recv_mode=mode, spill_dir=str(tmp_path)
    )
    with TpuShuffleManager(conf, num_executors=executors) as mgr:
        records = groupbytest.records(4)
        groupbytest.write_and_exchange(mgr, 0, records)
        checks = []
        for r in range(records.reducers):
            check = records.check(r, full=True)
            for key, value in mgr.get_reader(0, r, r + 1).read():
                check.add(key, value)
            assert check.ok(), f"reduce task {r} differs from the plain GroupBy"
            checks.append(check)
        assert records.complete(checks)
        d2h = mgr.cluster.stats.counters("exchange.d2h")
        assert records.total_bytes <= d2h["moved_bytes"] <= d2h["shard_bytes"]
        if executors > 1:
            assert d2h["skipped_shards"] > 0 and d2h["moved_bytes"] < d2h["shard_bytes"]
        mgr.unregister_shuffle(0)
