"""A (map, reduce) block longer than a peer region, on the served path.

Such a block is staged as consecutive pieces in successive staging rounds
(``store/writer.py`` ``MapWriter._close_split``), committed as one entry that
names them (``MapperInfo.splits``), exchanged round by round like any other
rows, and handed back as the bytes that were written: one array put together
from the pieces' views on the host read, the whole block on the pull path and
through the daemon.  A reader that takes a block out of one round refuses it
typed, by name, before a byte moves.  The records and the answers are the
Zipf-keyed gate job's (``benchmark/references/groupby-zipf.py``) with the law
raised until every map task's hottest block is about three regions long.

Sizes and counts on the CPU mesh; no rate."""

import struct
import threading
from contextlib import closing

import numpy as np
import pytest

from benchmark.cells import load_module
from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.block import ShuffleBlockId
from sparkucx_tpu.core.definitions import MapperInfo
from sparkucx_tpu.core.operation import (
    ResourceExhaustedError,
    SplitBlockError,
    TenantQuotaExceededError,
    TransportError,
)
from sparkucx_tpu.service.tenants import TenantRegistry
from sparkucx_tpu.shuffle.manager import TpuShuffleManager
from sparkucx_tpu.shuffle.reader import FixedWidthSerializer
from sparkucx_tpu.store import writer as store_writer
from sparkucx_tpu.store.hbm_store import HbmBlockStore, default_peer_ranges
from sparkucx_tpu.testing import faults
from sparkucx_tpu.utils.trace import TRACER

zipf = load_module("references", "groupby-zipf")

N = 4
STAGING = 8 << 20
REGION = STAGING // N
ROW = 512
#: the gate job's record shapes; s = 3 gives rank 1 five records in six, so
#: every map task has ONE block of 2.9 to 3.1 regions (reducer 123) among 40
#: of a record or two
CONFIG = {"mappers": 4, "pairs_per_mapper": 300, "value_bytes": 25000, "reducers": 200,
          "keys": "zipf", "zipf_s": 3.0, "distinct_keys": 200_000}
HOT = 123


@pytest.fixture(scope="module")
def records():
    made = zipf.make_records(CONFIG, seed=3_000_000_029)
    over = [(m, r) for m, parts in enumerate(made.blocks) for r, p in parts if len(p) > REGION]
    assert over == [(m, HOT) for m in range(CONFIG["mappers"])]
    assert all(2.5 < len(dict(parts)[HOT]) / REGION < 3.5 for parts in made.blocks)
    return made


@pytest.fixture
def tracer():
    """The process-wide tracer, enabled and cleared; back to what it was afterwards."""
    enabled, recording = TRACER.enabled, TRACER.recording
    TRACER.clear()
    TRACER.enabled = True
    yield TRACER
    TRACER.enabled, TRACER.recording = enabled, recording
    TRACER.clear()


def manager(**conf):
    conf.setdefault("staging_capacity_per_executor", STAGING)
    return TpuShuffleManager(TpuShuffleConf(**conf), num_executors=N)


def write_map(mgr, shuffle_id, m, parts):
    writer = mgr.get_writer(shuffle_id, m)
    for r, payload in parts:
        with writer.get_partition_writer(r).open_stream() as stream:
            stream.write(payload)
    writer.commit_all_partitions()


def read_all(mgr, shuffle_id, records, place=None):
    """Every reduce task against the plain GroupBy (group count, crc32 of
    every value under its key, key in its partition); the readers' metrics."""
    checks, metrics = [], []
    for r in range(records.reducers):
        check = records.check(r, full=True)
        reader = mgr.get_reader(shuffle_id, r, r + 1) if place is None else place(r)
        for key, value in reader.read():
            check.add(key, value)
        assert check.ok(), f"reduce task {r} differs from the plain GroupBy"
        checks.append(check)
        metrics.append(reader.metrics)
    assert records.complete(checks)
    return metrics


def stores(mgr):
    return [t.store for t in mgr.cluster.transports]


def split_counters(mgr):
    stats = [s.write_stats() for s in stores(mgr)]
    return [sum(s[name] for s in stats) for name in ("split_blocks", "split_pieces", "split_bytes", "split_rollovers")]


# -- the job ----------------------------------------------------------------------


@pytest.mark.parametrize("conf", [{"host_recv_mode": "array"}, {"host_recv_mode": "memmap"},
                                  {"host_recv_mode": "device", "keep_device_recv": True}],
                         ids=["array", "memmap", "device"])
def test_a_block_of_three_regions_is_written_exchanged_and_read_back_exact(records, groupbytest, tracer, tmp_path, conf):
    if conf["host_recv_mode"] == "memmap":
        conf = dict(conf, spill_dir=str(tmp_path))
    with manager(**conf) as mgr:
        groupbytest.write_and_exchange(mgr, 0, records)
        hot_bytes = sum(len(dict(parts)[HOT]) for parts in records.blocks)
        blocks, pieces, nbytes, rollovers = split_counters(mgr)
        assert (blocks, nbytes) == (CONFIG["mappers"], hot_bytes)
        # a block of 2.9 to 3.1 regions behind a few small ones: three or four pieces, a rollover between two
        assert 3 * blocks <= pieces <= 4 * blocks and rollovers == pieces - blocks
        for m, store in enumerate(stores(mgr)):
            entry = store._state(0).blocks[(m, HOT)]
            assert [rnd for rnd, _, _ in entry.pieces] == list(range(entry.round, entry.round + len(entry.pieces)))
            assert sum(n for _, _, n in entry.pieces) == entry.length == len(dict(records.blocks[m])[HOT])
            assert (entry.round, entry.offset) == entry.pieces[0][:2]
            assert store.num_rounds(0) > entry.pieces[-1][0]
            assert store.write_stats()["largest_block_bytes"] == entry.length
        metrics = read_all(mgr, 0, records)
        # one copy a split block, on the task that reads it; every other block is borrowed as ever
        assert [m.assembled_blocks for m in metrics].count(0) == records.reducers - 1
        hot = metrics[HOT]
        assert (hot.assembled_blocks, hot.assembled_bytes) == (CONFIG["mappers"], hot_bytes)
        assert hot.resident_blocks == CONFIG["mappers"] and hot.copied_blocks == 0
        assert not any(m.copied_blocks or m.blocks_retried or m.failovers for m in metrics)
        mgr.unregister_shuffle(0)
    events = tracer.events
    splits = [e for e in events if e["name"] == "store.block_split"]
    assert len(splits) == CONFIG["mappers"]
    assert sorted(e["args"]["map_id"] for e in splits) == list(range(CONFIG["mappers"]))
    assert all(e["args"]["reduce_id"] == HOT and e["args"]["pieces"] == e["args"]["rollovers"] + 1 for e in splits)
    assert sum(e["args"]["bytes"] for e in splits) == hot_bytes
    assembled = [e for e in events if e["name"] == "read.block_assemble"]
    assert len(assembled) == CONFIG["mappers"] and sum(e["args"]["bytes"] for e in assembled) == hot_bytes
    assert sum(e["args"]["pieces"] for e in assembled) == pieces


def test_a_one_piece_block_is_still_a_borrowed_view_and_a_split_one_is_one_read_only_array(records, groupbytest):
    with manager() as mgr:
        groupbytest.write_and_exchange(mgr, 0, records)
        meta = mgr.cluster.meta(0)
        owner = meta.owner_of_reduce(HOT)
        small = next(r for r, _ in records.blocks[0] if r != HOT and meta.owner_of_reduce(r) == owner)
        assembled = []
        whole, borrowed = mgr.cluster.transport(owner).resident_blocks(
            [ShuffleBlockId(0, 0, HOT), ShuffleBlockId(0, 0, small)], assembled)
        want = dict(records.blocks[0])
        assert whole.tobytes() == want[HOT] and borrowed.tobytes() == want[small]
        assert assembled == [len(want[HOT])]
        assert borrowed.base is not None and whole.base is None  # a view of the shard; an array of its own
        assert not whole.flags.writeable and not borrowed.flags.writeable and whole.flags.c_contiguous
        view, length = mgr.cluster.locate_received_block(owner, 0, 0, HOT)
        assert length == len(want[HOT]) and view.tobytes() == want[HOT]


def test_four_writers_open_at_once_stage_their_split_blocks_between_each_others(groupbytest):
    """Sixteen map tasks, the four of an executor written from four threads
    with every writer open before the first byte: each piece is copied
    outside the store's lock under its round's in-flight count, other
    tasks' blocks land between two pieces, and the entry names each piece
    where it went."""
    config = {**CONFIG, "mappers": 16, "pairs_per_mapper": 120}
    made = zipf.make_records(config, seed=41)
    assert all(len(dict(parts)[HOT]) > REGION for parts in made.blocks)
    errors = []
    with manager() as mgr:
        mgr.register_shuffle(0, made.num_mappers, made.reducers)
        writers = [mgr.get_writer(0, m) for m in range(made.num_mappers)]  # all open: no copy under the lock

        def task(m):
            try:
                for r, payload in made.blocks[m]:
                    with writers[m].get_partition_writer(r).open_stream() as stream:
                        stream.write(payload)
                writers[m].commit_all_partitions()
            except Exception as e:  # the thread's boundary
                errors.append(e)

        threads = [threading.Thread(target=task, args=(m,)) for m in range(made.num_mappers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not errors and not any(t.is_alive() for t in threads)
        blocks, pieces, _, _ = split_counters(mgr)
        assert blocks == made.num_mappers and pieces >= 2 * blocks
        for store in stores(mgr):
            stats = store.write_stats()
            # a task's last blocks may find it the only writer left open: those keep the lock
            assert 0 < stats["unlocked_copy_blocks"] <= stats["staged_blocks"]
            assert not store._state(0).inflight
        mgr.run_exchange(0)
        metrics = read_all(mgr, 0, made)
        assert metrics[HOT].assembled_blocks == made.num_mappers


def test_a_retried_attempt_is_swallowed_and_a_lost_attempts_pieces_stay_holes(records, groupbytest, monkeypatch):
    """First commit wins: a second attempt of a committed map writes nothing.
    An attempt whose copy raises in the block's SECOND piece loses the
    partition — the first piece stays a hole that no entry names — and the
    next attempt (never committed: not a discard) stages the block again."""
    with manager() as mgr:
        mgr.register_shuffle(0, records.num_mappers, records.reducers)
        store = stores(mgr)[0]
        copies = []
        real = store_writer._copy_chunks

        def failing(staging, start, chunks):
            copies.append(start)
            if len(copies) == 2:
                raise MemoryError("the copy of the second piece")
            real(staging, start, chunks)

        attempt = mgr.get_writer(0, 0)
        monkeypatch.setattr(store_writer, "_copy_chunks", failing)
        stream = attempt.get_partition_writer(HOT).open_stream()
        stream.write(dict(records.blocks[0])[HOT])
        with pytest.raises(TransportError, match="lost its copy into staging"):
            stream.close()
        monkeypatch.setattr(store_writer, "_copy_chunks", real)
        state = store._state(0)
        assert (0, HOT) not in state.blocks and state.round == 1 and not state.inflight
        assert store.write_stats()["split_blocks"] == 0
        with pytest.raises(TransportError):
            attempt.commit_all_partitions()
        for m, parts in enumerate(records.blocks):
            write_map(mgr, 0, m, parts)
        entry = state.blocks[(0, HOT)]
        assert entry.pieces[0][0] >= 1  # behind the hole the lost attempt left
        retry = mgr.get_writer(0, 0)  # committed: its writes are swallowed
        with retry.get_partition_writer(HOT).open_stream() as stream:
            stream.write(b"\xee" * (3 * REGION))
        retry.commit_all_partitions()
        assert state.blocks[(0, HOT)] is entry and store.write_stats()["split_blocks"] == 1
        mgr.run_exchange(0)
        read_all(mgr, 0, records)


def test_a_piece_in_a_spilled_round_is_exchanged_and_pulled_from_the_disk_tier(records, groupbytest, tmp_path):
    """With no RAM budget every rollover spills: all pieces but a block's
    last lie in ``np.memmap`` rounds, the exchange puts them from there and
    ``read_block`` joins them from there."""
    with manager(max_host_pool_bytes=0, spill_dir=str(tmp_path)) as mgr:
        mgr.register_shuffle(0, records.num_mappers, records.reducers)
        for m, parts in enumerate(records.blocks):
            write_map(mgr, 0, m, parts)
        for m, store in enumerate(stores(mgr)):
            entry = store._state(0).blocks[(m, HOT)]
            tiers = [store.round_tier(0, rnd) for rnd, _, _ in entry.pieces]
            assert tiers[:-1] == ["disk"] * (len(tiers) - 1)  # the last went with a later block's rollover, or not
            assert store.write_stats()["spilled_bytes"] > 0
            assert store.read_block(0, m, HOT) == dict(records.blocks[m])[HOT]
        mgr.run_exchange(0)
        for m, store in enumerate(stores(mgr)):  # after the seal: from the sealed payloads
            assert store.read_block(0, m, HOT) == dict(records.blocks[m])[HOT]
            view, offset, length = store.block_staging_view(0, m, HOT)
            assert bytes(view[offset : offset + length]) == dict(records.blocks[m])[HOT]
        read_all(mgr, 0, records)


def test_a_replaced_task_pulls_split_blocks_whole_from_staging_and_from_replicas(records, groupbytest, tracer):
    """The owner of the hot partition dies after the exchange: its task is
    re-placed and pulls every block (``read_block``) — three from the
    staging of the executors that ran their map tasks, the dead executor's
    own from its ring successor's replica tier, where a split block lies
    whole in the body of its first piece's round."""
    with manager(replication_factor=1, elastic=True) as mgr:
        groupbytest.write_and_exchange(mgr, 0, records)
        lost = mgr.cluster.meta(0).owner_of_reduce(HOT)
        faults.kill_executor(mgr.cluster.transport(lost))
        survivors = [e for e in range(N) if e != lost]

        def place(r):
            if mgr.cluster.meta(0).owner_of_reduce(r) != lost:
                return mgr.get_reader(0, r, r + 1)
            return mgr.get_reader(0, r, r + 1, executor_id=survivors[r % len(survivors)])

        metrics = read_all(mgr, 0, records, place)
        hot = metrics[HOT]
        assert hot.refetched_blocks == CONFIG["mappers"] and hot.replica_blocks == 1
        assert hot.refetched_bytes == sum(len(dict(parts)[HOT]) for parts in records.blocks)
        successor = (lost + 1) % N
        body = stores(mgr)[successor].replica_block(0, lost, lost, HOT)  # map ``lost`` ran on executor ``lost``
        assert body.tobytes() == dict(records.blocks[lost])[HOT]
    pulled = [e for e in tracer.events if e["name"] == "read.block_assemble"]
    assert len(pulled) >= CONFIG["mappers"] - 1  # the staging pulls; the replica's body is whole already


def test_an_executor_lost_mid_exchange_is_restaged_piece_by_piece(records, groupbytest):
    """The recovery rebuilds the dead executor's rounds from its replicas: a
    split block's pieces go back each into its own round."""
    with manager(replication_factor=1, elastic=True) as mgr:
        faults.arm("exchange.submit", lambda **_: faults.kill_executor(mgr.cluster.transport(2)),
                   times=1, match={"shuffle_id": 0, "round": 2})
        try:
            groupbytest.write_and_exchange(mgr, 0, records)
        finally:
            faults.reset()
        assert mgr.cluster.elastic_stats["recoveries"] == 1
        assert mgr.cluster.elastic_stats["restaged_blocks"] == len(records.blocks[2])
        assert mgr.cluster.elastic_stats["restaged_bytes"] == sum(len(p) for _, p in records.blocks[2])
        read_all(mgr, 0, records)


# -- the daemon --------------------------------------------------------------------


@pytest.mark.parametrize("plane", [{}, {"server_workers": 3}], ids=["thread-a-connection", "reactor"])
def test_the_daemon_sends_an_over_region_body_to_the_buffered_path_and_fetches_it_whole(plane, rng):
    from sparkucx_tpu.shuffle.daemon import DaemonClient, ShuffleDaemon

    daemon = ShuffleDaemon(TpuShuffleConf(staging_capacity_per_executor=1 << 20, **plane), num_executors=1, port=0)
    try:
        store = daemon.manager.cluster.transports[0].store
        small, big, later = (rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
                             for n in (4099, int(2.5 * (1 << 20)) + 13, 70_001))
        with closing(DaemonClient(daemon.address)) as client:
            client.create_shuffle(0, 1, 3)
            w = client.open_map_writer(0, 0)
            client.write_partition(w, 0, small)
            client.write_partition(w, 1, big)
            client.write_partition(w, 2, later)
            assert client.commit_map(w).tolist() == [len(small), len(big), len(later)]
            stats = store.write_stats()
            assert (stats["inplace_blocks"], stats["inplace_fallbacks"]) == (2, 1)
            assert (stats["split_blocks"], stats["split_pieces"], stats["split_bytes"]) == (1, 3, len(big))
            assert not store._state(0).inflight
            client.run_exchange(0)
            bids = [ShuffleBlockId(0, 0, r) for r in range(3)]
            assert [bytes(b) for b in client.fetch_blocks(bids)] == [small, big, later]
            client.remove_shuffle(0)
    finally:
        daemon.close()


def test_a_partition_of_several_frames_that_outgrows_a_region_takes_its_received_bytes_along(rng):
    """Frames of a partition received in place until the next would pass the
    region: what was received leaves its extent for the buffered path (the
    extent stays a hole), and the block is staged in pieces."""
    store = HbmBlockStore(TpuShuffleConf(staging_capacity_per_executor=1 << 16, block_alignment=128))
    store.create_shuffle(0, 1, 1)
    body = rng.integers(0, 256, size=150_000, dtype=np.uint8).tobytes()
    w = store.map_writer(0, 0)
    w.open_partition(0)
    for lo in (0, 30_000):
        view = w.reserve(30_000)
        view[:] = body[lo : lo + 30_000]
        w.end_receive(30_000, True)
    assert w.reserve(30_000) is None  # 90,000 B over the 65,536 of a region
    w.write(body[60_000:90_000])
    assert w.reserve(60_000) is None  # on the buffered path for good
    w.write(body[90_000:])
    w.close_partition()
    info = w.commit()
    assert len(info.splits[0]) == 4 and info.partitions[0][1] == len(body)
    stats = store.write_stats()
    assert (stats["inplace_blocks"], stats["inplace_fallbacks"], stats["split_blocks"]) == (0, 1, 1)
    assert store.read_block(0, 0, 0) == body
    store.close()


# -- the commit record -------------------------------------------------------------


def test_the_commit_record_carries_pieces_and_an_unsplit_task_packs_the_parents_bytes():
    plain = MapperInfo(7, 3, ((0, 1000), (0, 0), (4096, 77)), None)
    rounds = MapperInfo(7, 3, ((0, 1000), (0, 0), (4096, 77)), (0, 0, 2))
    # the layout of the commit before pieces existed, written out by hand
    head = struct.pack("<iii", 7, 3, 3) + struct.pack("<qqqqqq", 0, 1000, 0, 0, 4096, 77)
    assert plain.pack() == head
    assert rounds.pack() == head + b"\x01" + struct.pack("<iii", 0, 0, 2)
    assert MapperInfo.unpack(plain.pack()) == plain and MapperInfo.unpack(rounds.pack()) == rounds
    pieces = ((1, 1024, 3072), (2, 0, 4096), (3, 0, 3079))
    split = MapperInfo(7, 3, ((0, 1000), (1024, 10247), (4096, 77)), (0, 1, 3), {1: pieces})
    blob = split.pack()
    assert blob.startswith(head[:12]) and len(blob) == len(rounds.pack()) + 1 + 4 + 8 + 3 * 20
    back = MapperInfo.unpack(blob)
    assert back == split and back.splits == {1: pieces} and back.partitions[1] == (1024, 10247)
    assert back.round_of(1) == 1
    # a first piece in round 0 and nothing behind it: the rounds' tail is left out, the pieces' is not
    early = MapperInfo(7, 3, ((0, 9000),), None, {0: ((0, 0, 4096), (1, 0, 4096), (2, 0, 808))})
    assert MapperInfo.unpack(early.pack()) == early


@pytest.mark.parametrize("damage", ["unknown-tail", "bytes-behind", "cut-short", "two-rounds-tails"])
def test_a_commit_record_with_a_tail_the_decoder_does_not_know_fails_typed_not_short(damage):
    split = MapperInfo(7, 3, ((1024, 10247),), (1,), {0: ((1, 1024, 3072), (2, 0, 4096), (3, 0, 3079))})
    blob = {
        "unknown-tail": MapperInfo(7, 3, ((1024, 10247),), (1,)).pack() + b"\x03" + b"\x00" * 8,
        "bytes-behind": split.pack() + b"\x00",
        "cut-short": split.pack()[:-5],
        "two-rounds-tails": MapperInfo(7, 3, ((1024, 10247),), (1,)).pack() + b"\x01\x00\x00\x00\x00",
    }[damage]
    with pytest.raises(TransportError, match="commit record of map 3 of shuffle 7"):
        MapperInfo.unpack(blob)


def test_an_unsplit_map_task_commits_the_blob_it_always_did_and_a_split_one_round_trips(groupbytest):
    """Through the store: a task whose blocks all fit packs header, entries
    and (past round 0) the rounds' tail, byte for byte by hand; the task with
    a block over a region adds the pieces' tail, and ``mapper_info`` (the
    SPMD executor's way to a commit) says the same as the writer did."""
    store = HbmBlockStore(TpuShuffleConf(staging_capacity_per_executor=8192, block_alignment=128))
    store.create_shuffle(0, 2, 4, peer_ranges=default_peer_ranges(4, 2))
    first = store.map_writer(0, 0)
    first.write_partition(0, b"a" * 3000)
    first.write_partition(1, b"b" * 2000)  # region 0 cannot take it: round 1
    first.write_partition(3, b"c" * 100)
    info = first.commit()
    assert info.splits is None
    by_hand = struct.pack("<iii", 0, 0, 4) + struct.pack("<qq", 0, 3000) + struct.pack("<qq", 0, 2000) \
        + struct.pack("<qq", 0, 0) + struct.pack("<qq", 4096, 100) + b"\x01" + struct.pack("<iiii", 0, 1, 0, 1)
    assert info.pack() == by_hand and store.mapper_info(0, 0).pack() == by_hand
    second = store.map_writer(0, 1)
    second.write_partition(2, b"d" * 10_000)
    split = second.commit()
    assert split.splits == {2: ((1, 4096 + 128, 3968), (2, 4096, 4096), (3, 4096, 1936))}
    assert split.partitions[2] == (4096 + 128, 10_000) and split.round_of(2) == 1
    assert MapperInfo.unpack(split.pack()) == split == store.mapper_info(0, 1)
    peer = HbmBlockStore(TpuShuffleConf(staging_capacity_per_executor=8192, block_alignment=128))
    peer.create_shuffle(0, 2, 4, peer_ranges=default_peer_ranges(4, 2))
    peer.apply_mapper_info(split)  # a peer's table names the pieces too
    assert peer._state(0).blocks[(1, 2)].pieces == split.splits[2] and peer.block_length(0, 1, 2) == 10_000
    assert store.block_offset(0, 1, 2) == 4096 + 128


# -- what a block guarantees, for the block as a whole -------------------------------


def test_a_tenant_quota_that_ends_in_the_middle_of_a_block_refuses_it_whole_with_nothing_recorded():
    store = HbmBlockStore(TpuShuffleConf(staging_capacity_per_executor=8192, block_alignment=128))
    reg = TenantRegistry()
    store.tenants = reg
    reg.register("a", hbm_quota_bytes=6000)  # a region and a half: the block's second piece would pass it
    sid = reg.sid_for("a", 0)
    store.create_shuffle(sid, 1, 2, peer_ranges=default_peer_ranges(2, 2), app_id="a")
    w = store.map_writer(sid, 0)
    w.write_partition(0, b"s" * 500)
    used = reg.usage("a")
    w.open_partition(1)
    w.write(b"x" * 10_000)
    with pytest.raises(TenantQuotaExceededError):
        w.close_partition()
    state = store._state(sid)
    assert (sid, 1) not in state.blocks and (0, 1) not in state.blocks and len(state.blocks) == 1
    assert state.round == 0 and state.region_used.tolist() == [512, 0] and not state.inflight
    assert reg.usage("a") == used and store.write_stats()["split_blocks"] == 0
    store.close()


def test_a_block_that_fails_after_its_first_piece_gives_its_charge_back_and_names_nothing(monkeypatch):
    """Sealed under a block between two of its pieces (four writers open:
    the copy leaves the lock): the first piece stays a hole, the tenant's
    usage is what it was, the partition is lost."""
    store = HbmBlockStore(TpuShuffleConf(staging_capacity_per_executor=8192, block_alignment=128))
    reg = TenantRegistry()
    store.tenants = reg
    reg.register("a", hbm_quota_bytes=1 << 20)
    sid = reg.sid_for("a", 0)
    store.create_shuffle(sid, 2, 2, peer_ranges=default_peer_ranges(2, 2), app_id="a")
    w, other = store.map_writer(sid, 0), store.map_writer(sid, 1)  # two open: the copy leaves the lock
    real = store_writer._copy_chunks
    sealer = []

    def seal_during_the_first_copy(staging, start, chunks):
        real(staging, start, chunks)
        if not sealer:
            sealer.append(threading.Thread(target=store.seal, args=(sid,)))
            sealer[0].start()
            while not store._state(sid).draining:  # the seal waits for this copy, then wins the lock
                pass

    monkeypatch.setattr(store_writer, "_copy_chunks", seal_during_the_first_copy)
    w.open_partition(0)
    w.write(b"x" * 10_000)
    with pytest.raises(TransportError, match="already sealed"):
        w.close_partition()
    sealer[0].join(30)
    state = store._state(sid)
    assert not state.blocks and not state.inflight and reg.usage("a") == 0
    with pytest.raises(TransportError, match="lost a body"):
        w.close_partition()
    assert other is not None
    store.close()


def test_a_shed_block_fails_typed_before_any_piece(monkeypatch):
    store = HbmBlockStore(TpuShuffleConf(staging_capacity_per_executor=8192, block_alignment=128))
    store.create_shuffle(0, 1, 1)
    w = store.map_writer(0, 0)
    w.open_partition(0)
    w.write(b"x" * 20_000)

    def shed(site, nbytes=0):
        assert (site, nbytes) == ("close_partition", 20_096)  # the whole block, padded
        raise ResourceExhaustedError(nbytes, 0, 0, "shed")

    monkeypatch.setattr(store, "check_memory_pressure", shed)
    with pytest.raises(ResourceExhaustedError):
        w.close_partition()
    assert not store.host_staging_allocated(0) and store._state(0).round == 0
    store.close()


# -- the readers that take a block from one round refuse it, typed ---------------------


def test_the_device_fetch_the_ordered_read_and_the_spmd_read_refuse_a_split_block_by_name(records, groupbytest):
    with manager(keep_device_recv=True) as mgr:
        groupbytest.write_and_exchange(mgr, 0, records)
        owner = mgr.cluster.meta(0).owner_of_reduce(HOT)
        with pytest.raises(SplitBlockError, match=r"map=0, reduce=123\) is staged in [34] pieces.*device fetch") as refused:
            mgr.get_reader(0, HOT, HOT + 1).read_device()
        assert (refused.value.map_id, refused.value.reduce_id) == (0, HOT) and isinstance(refused.value, TransportError)
        with pytest.raises(SplitBlockError, match="device fetch"):
            mgr.cluster.transport(owner).fetch_blocks_device([ShuffleBlockId(0, 2, HOT)])
        width = zipf.record_bytes(CONFIG["value_bytes"])
        assert width % 4  # the gate job's records are no whole lanes: the ordered read refuses them first
        small = next(r for r, _ in records.blocks[0] if r != HOT)
        packed, table = mgr.get_reader(0, small, small + 1).read_device()[:2]  # a task without one reads on
        assert int(table[:, 1].sum()) == sum(len(dict(parts).get(small, b"")) for parts in records.blocks)


def test_the_ordered_read_refuses_a_split_block_by_name(rng):
    """Fixed-width records of whole lanes, one block over a region: the sort
    on the device takes its blocks through the same gather."""
    with manager(keep_device_recv=True, staging_capacity_per_executor=1 << 20) as mgr:
        mgr.register_shuffle(0, 1, 4)
        rows = rng.integers(0, 256, size=(8000, 100), dtype=np.uint8)
        writer = mgr.get_writer(0, 0)
        for r, part in ((0, rows[:100]), (1, rows[100:])):  # 790,000 B over a 262,144 B region
            with writer.get_partition_writer(r).open_stream() as stream:
                stream.write(part.tobytes())
        writer.commit_all_partitions()
        mgr.run_exchange(0)
        serializer = FixedWidthSerializer(100, 10)
        with pytest.raises(SplitBlockError, match="reduce=1"):
            list(mgr.get_reader(0, 1, 2, deserializer=serializer, key_ordering=True).read_batches())
        ordered = list(mgr.get_reader(0, 0, 1, deserializer=serializer, key_ordering=True).read_batches())
        assert sum(len(b) for b in ordered) == 100
        batches = list(mgr.get_reader(0, 1, 2, deserializer=serializer).read_batches())  # the host read: whole
        assert len(batches) == 1 and batches[0].tobytes() == rows[100:].tobytes()


def test_the_spmd_executor_refuses_a_split_block_by_name():
    from sparkucx_tpu.transport import spmd

    info = MapperInfo(0, 0, ((0, 10_000),), None, {0: ((0, 0, 4096), (1, 0, 4096), (2, 0, 1808))})
    executor = object.__new__(spmd.SpmdShuffleExecutor)
    executor.executor_id = 0
    executor._meta = {0: (1, 1, [(0, 1)])}
    executor._recv = {0: ([], [])}
    executor._mapper_infos = {0: {0: info}}
    with pytest.raises(SplitBlockError, match="SPMD executor"):
        executor.read_received_block(0, 0, 0)


def test_the_device_write_keeps_its_refusal_and_names_itself():
    import jax.numpy as jnp

    with manager(device_staging=True, staging_capacity_per_executor=1 << 16) as mgr:
        mgr.register_shuffle(0, 1, 4)
        writer = mgr.get_writer(0, 0)
        packed = jnp.zeros((200, 128), dtype=jnp.int32)  # 102,400 B: over a 16,384 B region
        with pytest.raises(TransportError, match=r"exceeds a whole region \(16384 B\) on the device write"):
            writer.write_partitions_device(packed, [0], [102_400])
