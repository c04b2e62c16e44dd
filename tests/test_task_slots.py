"""One manager from as many task threads as an executor has slots: four
ordered reads in flight on one device (``read_batches()`` and
``read_device()`` under ``key_ordering``) against the plain TeraSort of
``benchmark/references/terasort-ordered.py`` on seeded records, the
``orderedread`` statement of tasks in flight, four map tasks writing one
region with the round put behind them (PR 51's ``_PutBehind``), the same
four copying into the store AT ONCE (PR 53: the extent under the store's
lock, the copy outside it), and the tracer's spans of sibling task threads.

The CPU mesh: bytes, orders and counts, no rate."""

import threading

import jax
import numpy as np
import pytest

import sparkucx_tpu.store.hbm_store as hbm_store
import sparkucx_tpu.store.writer as store_writer
import sparkucx_tpu.transport.tpu as tpu
from benchmark.cells import load_module
from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.shuffle.manager import TpuShuffleManager
from sparkucx_tpu.shuffle.reader import FixedWidthSerializer, OrderedDeviceRead
from sparkucx_tpu.utils.trace import TRACER, span

ordered = load_module("references", "terasort-ordered")

SLOTS = 4
#: TeraSort's shapes at a few records, four reduce tasks a slot
CONFIG = {"mappers": 4, "records_per_mapper": 1600, "record_bytes": 100, "key_bytes": 10, "reducers": 16,
          "keys": "uniform-bytes"}
TERASORT = FixedWidthSerializer(100, 10)


def hbm_manager(staging=1 << 21):
    return TpuShuffleManager(
        TpuShuffleConf(keep_device_recv=True, host_recv_mode="device", staging_capacity_per_executor=staging),
        num_executors=1,
    )


def write_job(mgr, sid, records, threads=1):
    """The job's map tasks from ``threads`` task threads, then the exchange."""
    mgr.register_shuffle(sid, records.num_mappers, records.reducers)

    def map_task(m):
        writer = mgr.get_writer(sid, m)
        for r, payload in records.blocks[m]:
            with writer.get_partition_writer(r).open_stream() as stream:
                stream.write(payload)
        writer.commit_all_partitions()

    in_threads(threads, map_task, range(records.num_mappers))
    mgr.run_exchange(sid)


def in_threads(count, task, items):
    """Every item through ``task`` on ``count`` threads that start together,
    each taking the next item when it is free; returns the results by item."""
    items = list(items)
    results, errors, at = {}, [], iter(items)
    take = threading.Lock()
    start = threading.Barrier(count)

    def work():
        try:
            start.wait(timeout=60)
            while True:
                with take:
                    item = next(at, None)
                if item is None:
                    return
                results[item] = task(item)
        except BaseException as e:  # the thread's boundary: reported below
            errors.append(e)

    threads = [threading.Thread(target=work, name=f"task-slot-{k}") for k in range(count)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    return [results[item] for item in items]


@pytest.fixture
def four_at_the_sort(monkeypatch):
    """The first four dispatches of the ordering executable wait for each
    other: four ordered reads are in flight at once, whatever the host."""
    meet = threading.Barrier(SLOTS)
    real = tpu.ordered_records
    calls = []

    def meeting(*args, **kwargs):
        calls.append(threading.get_ident())
        if len(calls) <= SLOTS:
            meet.wait(timeout=60)
        return real(*args, **kwargs)

    monkeypatch.setattr(tpu, "ordered_records", meeting)
    return calls


def in_flight(mgr):
    row = mgr.cluster.ordered_read_stats()[0]
    return {k: row[k] for k in ("in_flight", "in_flight_device_bytes", "in_flight_peak", "in_flight_device_bytes_peak")}


@pytest.mark.parametrize("seed", [17, 3_000_000_019])
def test_four_ordered_reads_in_flight_each_get_their_own_records(seed, four_at_the_sort):
    """``read_batches()`` from four task threads over one shuffle: every batch
    the plain sort's slice byte for byte and in order, the gauge at four and
    back to none, what four hold on the device by shape — and the batches
    still exact after the shuffle has gone and more tasks have landed."""
    records = ordered.make_records(CONFIG, seed)
    with hbm_manager() as mgr:
        write_job(mgr, 0, records)
        assert in_flight(mgr) == dict.fromkeys(in_flight(mgr), 0)

        def reduce_task(r):
            reader = mgr.get_reader(0, r, r + 1, deserializer=TERASORT, key_ordering=True)
            [batch] = list(reader.read_batches())
            check = records.check(r)
            check.add(batch)
            assert check.ok() and reader.metrics.records_read == len(batch)
            return batch

        kept = in_threads(SLOTS, reduce_task, range(records.reducers))
        capacity = mgr.cluster._ordered_geometry(mgr.cluster.meta(0), 100)[2]
        held = 2 * capacity * 100  # a task's gathered segment and its sorted array
        assert in_flight(mgr) == {"in_flight": 0, "in_flight_device_bytes": 0, "in_flight_peak": SLOTS,
                                  "in_flight_device_bytes_peak": SLOTS * held}
        assert len(set(four_at_the_sort[:SLOTS])) == SLOTS  # four threads met at the sort
        for r, batch in enumerate(kept):
            assert not batch.flags.writeable and np.array_equal(batch, records.sorted_partition(r))
        # the shuffle goes, four more tasks of another job land: what was handed out stays
        mgr.unregister_shuffle(0)
        again = ordered.make_records(CONFIG, seed + 1)
        write_job(mgr, 1, again, threads=SLOTS)
        landed = in_threads(SLOTS, lambda r: list(mgr.get_reader(
            1, r, r + 1, deserializer=TERASORT, key_ordering=True).read_batches())[0], range(SLOTS))
        for r, batch in enumerate(landed):
            assert np.array_equal(batch, again.sorted_partition(r))
        for r, batch in enumerate(kept):
            assert np.array_equal(batch, records.sorted_partition(r))
        assert in_flight(mgr)["in_flight"] == 0 and in_flight(mgr)["in_flight_peak"] == SLOTS
        text = mgr.cluster.metrics_text().replace("sparkucx_tpu_", "")
        assert 'orderedread_in_flight{executor="0"} 0' in text  # a gauge keeps its name
        assert f'orderedread_in_flight_peak_total{{executor="0"}} {SLOTS}' in text


def test_four_device_reads_in_flight_are_out_of_the_gauge_at_their_hand_out(four_at_the_sort):
    records = ordered.make_records(CONFIG, 23)
    with hbm_manager() as mgr:
        write_job(mgr, 0, records, threads=SLOTS)

        def reduce_task(r):
            read = mgr.get_reader(0, r, r + 1, deserializer=TERASORT, key_ordering=True).read_device()
            assert isinstance(read, OrderedDeviceRead)
            return read

        reads = in_threads(SLOTS, reduce_task, range(records.reducers))
        counted = in_flight(mgr)
        assert counted["in_flight"] == 0 and counted["in_flight_device_bytes"] == 0
        assert counted["in_flight_peak"] == SLOTS
        for r, read in enumerate(reads):
            want = records.sorted_partition(r)
            host = np.asarray(read.records).view(np.uint8).reshape(-1, 100)
            assert read.num_records == len(want) and np.array_equal(host[: len(want)], want)
        # a consumer that brings its array across itself does not count twice
        mgr.cluster.ordered_handed_out(reads[0].records)
        assert in_flight(mgr)["in_flight"] == 0
        stats = mgr.cluster.ordered_read_stats()[0]
        assert stats["tasks"] == stats["sort_dispatches"] == records.reducers and stats["d2h_bytes"] == 0


def test_an_ordered_read_that_fails_is_out_of_the_gauge(monkeypatch):
    records = ordered.make_records(CONFIG, 5)

    def broken(*args, **kwargs):
        raise RuntimeError("planted")

    with hbm_manager() as mgr:
        write_job(mgr, 0, records)
        reader = mgr.get_reader(0, 2, 3, deserializer=TERASORT, key_ordering=True)
        monkeypatch.setattr(tpu, "ordered_records", broken)
        with pytest.raises(RuntimeError, match="planted"):
            list(reader.read_batches())
        assert in_flight(mgr)["in_flight"] == 0 and in_flight(mgr)["in_flight_device_bytes"] == 0
        assert in_flight(mgr)["in_flight_peak"] == 1
        assert mgr.cluster.ordered_read_stats()[0]["tasks"] == 0  # a task counts once it is dispatched
        monkeypatch.undo()
        # the D2H's failure too
        monkeypatch.setattr(tpu, "_start_landing", broken)
        with pytest.raises(RuntimeError, match="planted"):
            list(mgr.get_reader(0, 2, 3, deserializer=TERASORT, key_ordering=True).read_batches())
        assert in_flight(mgr)["in_flight"] == 0 and in_flight(mgr)["in_flight_device_bytes"] == 0


def test_the_read_span_says_how_many_were_in_flight_when_it_opened(four_at_the_sort):
    records = ordered.make_records(CONFIG, 31)
    with hbm_manager() as mgr:
        write_job(mgr, 0, records)
        TRACER.enable()
        TRACER.clear()
        try:
            in_threads(SLOTS, lambda r: list(mgr.get_reader(
                0, r, r + 1, deserializer=TERASORT, key_ordering=True).read_batches()), range(8))
            tasks = [e for e in TRACER.events if e.get("name") == "read.ordered"]
        finally:
            TRACER.disable()
            TRACER.clear()
        assert len(tasks) == 8 and len({e["tid"] for e in tasks}) == SLOTS
        seen = sorted(e["args"]["in_flight"] for e in tasks)
        # the four that met at the sort opened with 0..3 others in flight at most; nobody ever saw four
        assert seen[0] == 0 and all(0 <= n < SLOTS for n in seen)
        alone = list(mgr.get_reader(0, 9, 10, deserializer=TERASORT, key_ordering=True).read_batches())
        assert len(alone) == 1


def test_spans_of_four_task_threads_nest_by_thread_and_keep_their_own_tid():
    """A reader of the ring tells siblings from children: a span's parent is
    the span open on ITS thread, never a sibling's that happens to be open,
    and every event carries the thread that recorded it."""
    meet = threading.Barrier(SLOTS)

    def task(k):
        with span("task.outer", slot=k) as outer:
            meet.wait(timeout=60)  # all four outer spans are open now
            with span("task.inner", slot=k) as inner:
                meet.wait(timeout=60)
                assert TRACER.current_context() is inner
            assert TRACER.current_context() is outer
        assert TRACER.current_context() is None
        return threading.get_ident()

    TRACER.enable()
    TRACER.clear()
    try:
        idents = in_threads(SLOTS, task, range(SLOTS))
        events = [e for e in TRACER.events if e.get("name", "").startswith("task.")]
    finally:
        TRACER.disable()
        TRACER.clear()
    assert len(events) == 2 * SLOTS and len(set(idents)) == SLOTS
    outer = {e["args"]["slot"]: e for e in events if e["name"] == "task.outer"}
    inner = {e["args"]["slot"]: e for e in events if e["name"] == "task.inner"}
    assert len({e["tid"] for e in outer.values()}) == SLOTS
    for k in range(SLOTS):
        assert inner[k]["tid"] == outer[k]["tid"] and inner[k]["parent_id"] == outer[k]["span_id"]
        assert inner[k]["trace_id"] == outer[k]["trace_id"] and outer[k]["parent_id"] == 0
        # its siblings were open all the while, and are no parents of it
        for other in range(SLOTS):
            if other != k:
                assert outer[other]["ts"] < inner[k]["ts"] < outer[other]["ts"] + outer[other]["dur"]
                assert inner[k]["parent_id"] != outer[other]["span_id"]


PIECE = 1 << 14


def test_four_map_tasks_of_one_region_with_the_round_put_behind_them(monkeypatch):
    """PR 51's round behind the writer under an executor's four task threads
    through the manager: pieces become final in whatever order the four close
    their blocks, every piece is put once, the sealed round is the staging
    byte for byte, and the ordered reads give the plain sort back."""
    monkeypatch.setattr(hbm_store, "SEAL_PUT_PIECE_BYTES", PIECE)
    records = ordered.make_records({**CONFIG, "mappers": 8}, 41)
    with hbm_manager(staging=1 << 21) as mgr:
        store = mgr.cluster.transports[0].store
        # a job before: the store's free list holds the staging buffer the next one takes
        write_job(mgr, 7, ordered.make_records(CONFIG, 1))
        mgr.unregister_shuffle(7)
        before = store.write_stats()
        mgr.register_shuffle(0, records.num_mappers, records.reducers)

        def map_task(m):
            writer = mgr.get_writer(0, m)
            for r, payload in records.blocks[m]:
                with writer.get_partition_writer(r).open_stream() as stream:
                    stream.write(payload)
            writer.commit_all_partitions()

        in_threads(SLOTS, map_task, range(records.num_mappers))
        state = store._state(0)
        behind = state.put_behind
        assert behind is not None and not behind.owner
        host = state.staging.copy()
        early = store.write_stats()["early_put_pieces"] - before["early_put_pieces"]
        assert early >= int(state.region_used[0]) // PIECE - 1 > 4  # all but the piece the last writer stood in
        mgr.run_exchange(0)
        after = store.write_stats()
        assert after["early_put_dropped"] == before["early_put_dropped"]
        pieces = early + after["seal_put_pieces"] - before["seal_put_pieces"]
        assert pieces == -(-int(state.region_used[0]) // PIECE)  # every reached piece once
        sealed = mgr.cluster.meta(0)
        assert len(sealed.recv_sizes) == 1
        received = np.asarray(sealed.recv_device[0][0]).reshape(-1).view(np.uint8)
        used = int(state.region_used[0])
        assert np.array_equal(received[:used], host[:used])
        batches = in_threads(SLOTS, lambda r: list(mgr.get_reader(
            0, r, r + 1, deserializer=TERASORT, key_ordering=True).read_batches())[0], range(records.reducers))
        for r, batch in enumerate(batches):
            assert np.array_equal(batch, records.sorted_partition(r))


def copies_that_meet(monkeypatch):
    """The first four copies into staging wait for each other inside
    ``store_writer._copy_chunks``: four map tasks copy at once, whatever the
    host — under a lock held round the copy they could never meet."""
    meet = threading.Barrier(SLOTS)
    real = store_writer._copy_chunks
    calls = []

    def meeting(staging, start, chunks):
        calls.append(threading.get_ident())
        if len(calls) <= SLOTS:
            meet.wait(timeout=60)
        return real(staging, start, chunks)

    monkeypatch.setattr(store_writer, "_copy_chunks", meeting)
    return calls


def test_four_map_tasks_copy_into_one_store_at_once(monkeypatch):
    """PR 53: the slots' map tasks take their extents under the store's lock
    and copy outside it — four copies in flight into one region, every block
    written with the four slots busy counted as copied outside the lock, the
    round still put behind them piece by piece, sealed as the staging byte
    for byte and read back as the plain sort."""
    monkeypatch.setattr(hbm_store, "SEAL_PUT_PIECE_BYTES", PIECE)
    records = ordered.make_records({**CONFIG, "mappers": 8}, 53)
    with hbm_manager(staging=1 << 21) as mgr:
        store = mgr.cluster.transports[0].store
        write_job(mgr, 7, ordered.make_records(CONFIG, 1))  # the job before: the free list holds its buffer
        mgr.unregister_shuffle(7)
        before = store.write_stats()
        calls = copies_that_meet(monkeypatch)
        mgr.register_shuffle(0, records.num_mappers, records.reducers)
        busy = threading.Barrier(SLOTS)  # two waves of four tasks: each writes with four writers open

        def map_task(m):
            writer = mgr.get_writer(0, m)
            busy.wait(timeout=60)
            for r, payload in records.blocks[m]:
                with writer.get_partition_writer(r).open_stream() as stream:
                    stream.write(payload)
            busy.wait(timeout=60)
            writer.commit_all_partitions()

        in_threads(SLOTS, map_task, range(records.num_mappers))
        assert len(set(calls[:SLOTS])) == SLOTS  # four threads met inside their copies
        state = store._state(0)
        behind = state.put_behind
        assert behind is not None and not behind.owner and not behind.open and state.inflight == {}
        after = store.write_stats()
        blocks = sum(len(b) for b in records.blocks)
        nbytes = sum(len(payload) for b in records.blocks for _, payload in b)
        assert after["unlocked_copy_blocks"] - before["unlocked_copy_blocks"] == blocks == len(calls)
        assert after["unlocked_copy_bytes"] - before["unlocked_copy_bytes"] == nbytes
        assert after["staged_blocks"] - before["staged_blocks"] == blocks
        assert after["inflight_wait_ns"] == before["inflight_wait_ns"]  # nobody had to wait for a copy
        host = state.staging.copy()
        used = int(state.region_used[0])
        # every piece wholly below the used prefix went before the seal, whoever's copy ended last
        assert after["early_put_pieces"] - before["early_put_pieces"] == used // PIECE > 4
        mgr.run_exchange(0)
        sealed = store.write_stats()
        assert sealed["early_put_dropped"] == before["early_put_dropped"]
        assert sealed["seal_put_pieces"] - before["seal_put_pieces"] == -(-used // PIECE) - used // PIECE
        received = np.asarray(mgr.cluster.meta(0).recv_device[0][0]).reshape(-1).view(np.uint8)
        assert np.array_equal(received[:used], host[:used])
        batches = in_threads(SLOTS, lambda r: list(mgr.get_reader(
            0, r, r + 1, deserializer=TERASORT, key_ordering=True).read_batches())[0], range(records.reducers))
        for r, batch in enumerate(batches):
            assert np.array_equal(batch, records.sorted_partition(r))


@pytest.mark.parametrize("threads", [1, SLOTS])
def test_the_writers_open_decide_where_a_task_copies(threads):
    """What the store observes, not a size: a job written one map task at a
    time keeps the one-take atom (``unlocked_copy_blocks`` 0: nobody can
    wait for its copies), the same job from four slots copies outside the
    lock — through staging rounds that roll over with copies in flight —
    and both read back as the plain sort."""
    records = ordered.make_records({**CONFIG, "mappers": 8, "records_per_mapper": 16000}, 59)
    with hbm_manager(staging=1 << 21) as mgr:
        store = mgr.cluster.transports[0].store
        before = store.write_stats()
        write_job(mgr, 0, records, threads=threads)
        after = store.write_stats()
        blocks = sum(len(b) for b in records.blocks)
        assert after["staged_blocks"] - before["staged_blocks"] == blocks
        unlocked = after["unlocked_copy_blocks"] - before["unlocked_copy_blocks"]
        assert unlocked == 0 if threads == 1 else blocks // 2 < unlocked <= blocks
        assert after["rollovers"] - before["rollovers"] > 0
        assert store._state(0).open_writers == 0
        assert store._state(0).inflight == {}
        batches = in_threads(SLOTS, lambda r: list(mgr.get_reader(
            0, r, r + 1, deserializer=TERASORT, key_ordering=True).read_batches())[0], range(records.reducers))
        for r, batch in enumerate(batches):
            assert np.array_equal(batch, records.sorted_partition(r))
