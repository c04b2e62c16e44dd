"""The ordered return over fixed-width records: ``get_reader(...,
deserializer=FixedWidthSerializer(w, k), key_ordering=True)`` — a reduce
task's records sorted by key ON THE DEVICE (``jit_ordered_records``) over
shards kept in HBM, as ``read_device()`` (an array on the executor's device)
and as ``read_batches()`` (one batch a task after one D2H) — against the plain
TeraSort of ``benchmark/references/terasort-ordered.py`` on seeded records,
through manager -> store -> exchange -> reader, on 1 and on 4 CPU devices.

The CPU mesh: bytes, orders and counts, no rate."""

import contextlib

import jax
import numpy as np
import pytest

from benchmark.cells import load_module
from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.operation import TransportError
from sparkucx_tpu.shuffle.manager import TpuShuffleManager
from sparkucx_tpu.shuffle.reader import (
    DeviceRead,
    FixedWidthSerializer,
    OrderedDeviceRead,
    RaggedBlockError,
)
from sparkucx_tpu.transport.tpu import ordered_records
from sparkucx_tpu.utils.trace import TRACER

ordered = load_module("references", "terasort-ordered")
terasort = ordered.terasort

#: TeraSort's shapes at a few records: 100 B records straddle 512 B rows, and
#: the 7 reducers' blocks of a mapper have 7 different record counts
CONFIG = {"mappers": 4, "records_per_mapper": 900, "record_bytes": 100, "key_bytes": 10, "reducers": 7,
          "keys": "uniform-bytes"}
TERASORT = FixedWidthSerializer(100, 10)
ORDERED_FAMILY = ("tasks", "records", "bytes", "capacity_records", "sort_dispatches", "d2h_bytes", "d2h_ns")


def hbm_conf(staging=1 << 20, **kw):
    kw = {"keep_device_recv": True, "host_recv_mode": "device", **kw}
    return TpuShuffleConf(staging_capacity_per_executor=staging, **kw)


@contextlib.contextmanager
def shuffled(blocks, reducers, executors=1, conf=None, serializer=TERASORT):
    """A manager over ``executors`` CPU devices with shuffle 0 written and
    exchanged: ``blocks[m]`` = ``[(reduce_id, rows or bytes)]`` in reducer
    order, as a map task's writer is given them."""
    with TpuShuffleManager(conf or hbm_conf(), num_executors=executors) as mgr:
        mgr.register_shuffle(0, len(blocks), reducers)
        for m, parts in enumerate(blocks):
            writer = mgr.get_writer(0, m)
            for r, payload in parts:
                if isinstance(payload, np.ndarray):
                    payload = serializer.serialize(payload)
                with writer.get_partition_writer(r).open_stream() as stream:
                    stream.write(payload)
            writer.commit_all_partitions()
        mgr.run_exchange(0)
        yield mgr


def ordered_reader(mgr, r, serializer=TERASORT, end=None):
    return mgr.get_reader(0, r, (r + 1) if end is None else end, deserializer=serializer, key_ordering=True)


def one_batch(mgr, r, serializer=TERASORT):
    batches = list(ordered_reader(mgr, r, serializer).read_batches())
    assert len(batches) == 1
    return batches[0]


def by_key_bytes(rows, key_bytes):
    """The rows in Python's own order of their keys as ``bytes`` (unsigned,
    most significant first), stable."""
    order = sorted(range(len(rows)), key=lambda i: (bytes(rows[i, :key_bytes]), i))
    return rows[order]


@pytest.mark.parametrize("seed", [11, 3_000_000_019])
@pytest.mark.parametrize("executors, staging", [(1, 1 << 20), (4, 1 << 20), (1, 1 << 17), (4, 81920)],
                         ids=["1x-one-round", "4x-one-round", "1x-several-rounds", "4x-several-rounds"])
def test_every_task_comes_back_as_the_plain_terasorts_slice(executors, staging, seed):
    """Byte for byte the reference's plain sort, through both forms, and the
    reference's own consumers agree: the timed check, the warm-up's full
    check on the batches as handed out, TeraValidate's three over the job."""
    records = ordered.make_records(CONFIG, seed)
    with shuffled(records.blocks, records.reducers, executors, hbm_conf(staging)) as mgr:
        rounds = len(mgr.cluster.meta(0).recv_sizes)
        assert (rounds == 1) == (staging == 1 << 20)
        checks = []
        for r in range(records.reducers):
            want = records.sorted_partition(r)
            reader = ordered_reader(mgr, r)
            batches = list(reader.read_batches())
            assert len(batches) == 1 and not batches[0].flags.writeable
            assert batches[0].dtype == np.uint8 and np.array_equal(batches[0], want)
            assert reader.metrics.records_read == len(want) and reader.metrics.record_batches == 1
            assert reader.metrics.remote_bytes_read == want.size
            on_device = ordered_reader(mgr, r).read_device()
            assert isinstance(on_device, OrderedDeviceRead) and on_device.num_records == len(want)
            owner = mgr.cluster.transport(mgr.cluster.meta(0).owner_of_reduce(r))
            assert on_device.records.devices() == {owner.device}
            assert on_device.records.dtype == np.int32 and on_device.records.shape[1] == 25
            host = np.asarray(on_device.records).view(np.uint8).reshape(-1, 100)
            assert np.array_equal(host[: len(want)], want) and not host[len(want):].any()
            assert [(b.map_id, b.reduce_id) for b in on_device.block_ids] == [(m, r) for m in records.mappers_of(r)]
            for full in (False, True):
                check = records.check(r, full)
                check.add(batches[0])
                assert check.ok()
            checks.append(check)
        assert records.complete(checks)


def test_a_shuffles_tasks_share_one_capacity_and_one_executable():
    """75 tasks of 75 record counts: one static shape a shuffle, worked out
    from the sealed size matrix — the reference's own figure — and never a
    power of two of bytes."""
    records = ordered.make_records(CONFIG, 5)
    geometry = ordered.geometry({**CONFIG, "store": {"staging_bytes": 1 << 20, "alignment": 512,
                                                     "ram_budget_bytes": 1 << 30}}, 1)
    with shuffled(records.blocks, records.reducers) as mgr:
        shapes = {ordered_reader(mgr, 0).read_device().records.shape}
        compiled = ordered_records._cache_size()  # the first task compiled, or found, the shuffle's executable
        shapes |= {ordered_reader(mgr, r).read_device().records.shape for r in range(1, records.reducers)}
        capacity = geometry["sort_capacity_records"]
        assert shapes == {(capacity, 25)}
        assert geometry["largest_reducer_records"] <= capacity < 2 * geometry["largest_reducer_records"]
        assert mgr.cluster._ordered_geometry(mgr.cluster.meta(0), 100) == (25, 128, capacity)
        assert ordered_records._cache_size() == compiled
        gathers = [key for key in mgr.cluster._exchange_cache if key[0] == "gather"]
        assert gathers == [("gather", None, 4, capacity // 128 * 25)]
        stats = mgr.cluster.ordered_read_stats()[0]
        assert stats["capacity_records"] == records.reducers * capacity
        assert stats["sort_dispatches"] == stats["tasks"] == records.reducers


def planted(rng, width=100):
    """One reducer's records in three blocks whose keys collide on their
    first eight bytes and differ in bytes 8-9, whose keys are equal with
    other values, and whose key bytes are >= 0x80 in every position."""
    rows = rng.integers(0, 256, size=(700, width), dtype=np.uint8)
    rows[:200, :8] = rows[0, :8]                 # a two-lane sort leaves these as written
    rows[200:260, :10] = rows[200, :10]          # one key, sixty values: the tie rule
    for p in range(10):                          # 0x7F.. < 0x80..: a signed compare has it backwards
        rows[300 + 2 * p, :10] = 0x7F
        rows[301 + 2 * p, :10] = 0x7F
        rows[300 + 2 * p, p] = 0x80
    rows[400:420, :10] = 0xFF
    rows[420:440, :10] = 0x00
    rows = rows[rng.permutation(len(rows))]
    return [rows[:333], rows[333:334], rows[334:]]  # 333, 1 and 366 records: unequal, straddling


@pytest.mark.parametrize("executors", [1, 4])
def test_all_ten_key_bytes_order_unsigned_and_ties_keep_every_record(rng, executors):
    blocks = planted(rng)
    rows = np.concatenate(blocks)
    with shuffled([[(1, b)] for b in blocks], 3, executors) as mgr:
        got = one_batch(mgr, 1)
        keys = [bytes(k) for k in got[:, :10]]
        assert keys == sorted(keys)
        assert keys == [bytes(k) for k in by_key_bytes(rows, 10)[:, :10]]
        # equal keys come in any order among themselves: as a multiset
        assert sorted(bytes(row) for row in got) == sorted(bytes(row) for row in rows)
        # the same records ordered by eight key bytes only is another result,
        # and the reference's order check says so
        eight = one_batch(mgr, 1, FixedWidthSerializer(100, 8))
        assert [bytes(k) for k in eight[:, :8]] == sorted(bytes(k) for k in rows[:, :8])
        assert ordered.out_of_order(eight, 10) > 0 and ordered.out_of_order(got, 10) == 0
        # a signed compare of the lanes would put 0x80.. before 0x7F..
        signed = rows[np.lexsort([rows[:, :12].view("<i4")[:, i] for i in (2, 1, 0)])]
        assert ordered.out_of_order(signed, 10) > 0


@pytest.mark.parametrize("width, key_bytes", [(8, 4), (20, 4), (20, 8), (100, 8), (100, 10), (36, 10), (12, 12)])
@pytest.mark.parametrize("alignment", [128, 512])
def test_key_widths_record_widths_and_row_widths(rng, width, key_bytes, alignment):
    serializer = FixedWidthSerializer(width, key_bytes)
    blocks = [rng.integers(0, 256, size=(n, width), dtype=np.uint8) for n in (150, 1, 77, 300)]
    for block in blocks:
        block[:, : key_bytes - 1] &= 0x81  # few distinct leading bytes: the last key byte decides often
    rows = np.concatenate(blocks)
    conf = hbm_conf(block_alignment=alignment)
    with shuffled([[(0, b)] for b in blocks], 1, conf=conf, serializer=serializer) as mgr:
        got = one_batch(mgr, 0, serializer)
        assert got.shape == rows.shape
        assert [bytes(k) for k in got[:, :key_bytes]] == sorted(bytes(k) for k in rows[:, :key_bytes])
        assert sorted(bytes(row) for row in got) == sorted(bytes(row) for row in rows)
        assert np.array_equal(got, by_key_bytes(rows, key_bytes))  # and stable, as it happens


def test_a_task_without_records_and_a_range_of_partitions(rng):
    records = ordered.make_records(CONFIG, 7)
    blocks = [[(r, p) for r, p in parts if r != 3] for parts in records.blocks]  # nobody writes to reducer 3
    with shuffled(blocks, records.reducers) as mgr:
        reader = ordered_reader(mgr, 3)
        assert list(reader.read_batches()) == [] and reader.metrics.records_read == 0
        empty = ordered_reader(mgr, 3).read_device()
        assert empty.num_records == 0 and empty.block_ids == [] and not np.asarray(empty.records).any()
        before = mgr.cluster.ordered_read_stats()[0]
        assert before["tasks"] == 2 and before["sort_dispatches"] == 0  # nothing to order: no dispatch
        # partitions 1..2 read as one range: a range partitioner's neighbours, in key order across both
        [both] = list(ordered_reader(mgr, 1, end=3).read_batches())
        want = np.concatenate([records.sorted_partition(1), records.sorted_partition(2)])
        assert np.array_equal(both, want)


def test_shards_not_retained_raise_the_transports_typed_error():
    records = ordered.make_records(CONFIG, 3)
    with shuffled(records.blocks, records.reducers, conf=TpuShuffleConf(staging_capacity_per_executor=1 << 20)) as mgr:
        for call in ("read_batches", "read_device"):
            reader = ordered_reader(mgr, 0)
            with pytest.raises(TransportError, match="device shards not retained"):
                getattr(reader, call)()
            assert reader.metrics.remote_blocks_fetched == 0
        # read() keeps ExternalCombiner, a record at a time, over host-received shards
        pairs = list(ordered_reader(mgr, 0).read())
        assert [k + v for k, v in pairs] == [bytes(row) for row in by_key_bytes(records.rows_of(0), 10)]


def test_what_cannot_be_ordered_on_the_device_raises(rng):
    records = ordered.make_records(CONFIG, 3)
    with shuffled(records.blocks, records.reducers) as mgr:
        for width, key in ((10, 4), (102, 10), (100, 0)):
            with pytest.raises(ValueError, match="multiple of 4 and a key"):
                mgr.get_reader(0, 0, 1, deserializer=FixedWidthSerializer(width, key), key_ordering=True)
        mgr.get_reader(0, 0, 1, deserializer=FixedWidthSerializer(10, 4))  # unordered: any width
        with pytest.raises(TypeError, match="FixedWidthSerializer"):
            mgr.get_reader(0, 0, 1, key_ordering=True).read_device()
        reader = mgr.get_reader(0, 0, 1, deserializer=TERASORT, key_ordering=True, aggregator=lambda a, b: a)
        with pytest.raises(NotImplementedError, match="aggregator"):
            reader.read_batches()
    ragged = [[(0, bytes(250))]]
    with shuffled(ragged, 1) as mgr:
        with pytest.raises(RaggedBlockError, match=r"shuffle_0_0_0.* 250 B .* 100 B records"):
            ordered_reader(mgr, 0).read_batches()


def test_the_unordered_forms_are_what_they_were():
    """Without ``key_ordering``: ``read_device()`` a ``DeviceRead`` of
    row-aligned blocks in a power-of-two bucket, ``read_batches()`` a batch a
    block as written — and an ordered read in between changes neither."""
    records = ordered.make_records(CONFIG, 9)
    with shuffled(records.blocks, records.reducers) as mgr:
        def unordered(r):
            got = mgr.get_reader(0, r, r + 1, deserializer=TERASORT).read_device()
            batches = list(mgr.get_reader(0, r, r + 1, deserializer=TERASORT).read_batches())
            return got, batches

        first, batches = unordered(2)
        one_batch(mgr, 2)
        again, _ = unordered(2)
        assert isinstance(first, DeviceRead) and first.packed.shape == again.packed.shape
        rows = first.packed.shape[0]
        assert rows & (rows - 1) == 0 and first.packed.shape[1] == 128
        assert np.array_equal(first.table, again.table)
        host = np.asarray(first.packed).reshape(-1).view(np.uint8)
        written = [p for parts in records.blocks for r, p in parts if r == 2]
        assert first.table[:, 1].tolist() == [len(p) for p in written]
        starts = first.table[:, 0]
        assert np.array_equal(np.diff(starts), -(-first.table[:-1, 1] // 512))  # back to back, row-aligned
        for (row, length), payload in zip(first.table.tolist(), written):
            assert host[row * 512 : row * 512 + length].tobytes() == payload
        assert [b.tobytes() for b in batches] == written


def test_spans_and_counters_once_a_task():
    records = ordered.make_records(CONFIG, 13)
    with shuffled(records.blocks, records.reducers) as mgr:
        one_batch(mgr, 0)  # compiled before the spans are read
        before = mgr.cluster.ordered_read_stats()[0]
        TRACER.enable()
        TRACER.clear()
        try:
            batch = one_batch(mgr, 4)
            ordered_reader(mgr, 5).read_device()
            events = [e for e in TRACER.events if e.get("ph") == "X"]
        finally:
            TRACER.disable()
            TRACER.clear()
        after = mgr.cluster.ordered_read_stats()[0]
        names = [e["name"] for e in events]
        inner = ["read.device.locate", "fetch.device_gather", "read.ordered.sort"]
        assert sorted(names) == sorted(["read.ordered"] * 2 + inner * 2 + ["read.ordered.d2h"])
        tasks = [e for e in events if e["name"] == "read.ordered"]
        capacity = mgr.cluster._ordered_geometry(mgr.cluster.meta(0), 100)[2]
        assert tasks[0]["args"]["records"] == len(batch) and tasks[0]["args"]["bytes"] == batch.size
        assert tasks[0]["args"]["capacity"] == capacity and tasks[0]["args"]["blocks"] == CONFIG["mappers"]
        for child in (e for e in events if e["name"] != "read.ordered"):
            parent = min((t for t in tasks if t["ts"] <= child["ts"] <= t["ts"] + t["dur"]), key=lambda t: t["dur"])
            assert child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1
        [d2h] = [e for e in events if e["name"] == "read.ordered.d2h"]
        assert d2h["args"] == {"records": len(batch), "bytes": batch.size, "capacity": capacity}
        rose = {k: after[k] - before[k] for k in ORDERED_FAMILY}
        n5 = records.expected[5][0]
        assert rose["tasks"] == rose["sort_dispatches"] == 2
        assert rose["records"] == len(batch) + n5 and rose["bytes"] == 100 * rose["records"]
        assert rose["capacity_records"] == 2 * capacity
        assert rose["d2h_bytes"] == capacity * 100 and rose["d2h_ns"] > 0  # read_batches() alone crosses
        text = mgr.cluster.metrics_text()
        assert 'orderedread_sort_dispatches_total{executor="0"}' in text.replace("sparkucx_tpu_", "")


def test_the_ordering_executable_is_named_for_the_trace():
    """``jit_ordered_records`` in a device trace, whatever it is built from."""
    records = ordered.make_records(CONFIG, 2)
    with shuffled(records.blocks, records.reducers) as mgr:
        one_batch(mgr, 0)
        assert ordered_records.__name__ == "ordered_records"
        segment = jax.ShapeDtypeStruct((25, 128), np.int32)
        table = jax.ShapeDtypeStruct((2, 4), np.int32)
        lowered = ordered_records.lower(table, segment, record_lanes=25, key_bytes=10, flat=True)
        assert "jit_ordered_records" in lowered.as_text()


def test_an_ordered_batch_outlives_its_shuffle():
    records = ordered.make_records(CONFIG, 21)
    with shuffled(records.blocks, records.reducers) as mgr:
        kept = one_batch(mgr, 6)
        want = records.sorted_partition(6)
        mgr.unregister_shuffle(0)
        assert np.array_equal(kept, want)


# -- ``ordered_records`` fed directly: what no gather's accidental zeros hide --

def placed(blocks, width, lane=128, capacity_slots=None, poison=True):
    """``blocks`` = ``[(segment, (n, width) uint8 rows or None)]`` laid out as
    the slot-aligned gather leaves them — each from a slot boundary of its
    segment, in order — as ``(table, segments, rows in table order)``.  Every
    place no block covers is **poisoned**: all ones, or zeros (a key before
    every real key).  ``None`` is an entry the locate leaves at (0, 0)."""
    lanes = width // 4
    slot_records = lane // np.gcd(lanes, lane)
    slot_rows = slot_records * lanes // lane
    count = 1 + max(seg for seg, _ in blocks)
    used = [0] * count
    for seg, rows in blocks:
        used[seg] += -(-len(rows) // slot_records) if rows is not None else 0
    slots = capacity_slots or max(used)
    places = np.zeros((count, slots * slot_records, width), dtype=np.uint8)
    if poison:
        places[:, 0::2] = 0xFF
    table = np.zeros((2, 1 << (len(blocks) - 1).bit_length()), dtype=np.int32)
    at = [0] * count
    for i, (seg, rows) in enumerate(blocks):
        if rows is None:
            continue
        places[seg, at[seg]: at[seg] + len(rows)] = rows
        table[:, i] = (seg * slots * slot_records + at[seg], len(rows))
        at[seg] += -(-len(rows) // slot_records) * slot_records
    segments = [places[s].reshape(-1).view(np.int32).reshape(slots * slot_rows, lane) for s in range(count)]
    rows = [r for _, r in blocks if r is not None and len(r)]
    return table, segments, np.concatenate(rows) if rows else np.zeros((0, width), np.uint8)


def _real_keys(rng, n, width):
    rows = rng.integers(0, 256, size=(n, width), dtype=np.uint8)
    rows[:, 0] |= 1  # no real key of zeros: the poison's would come first
    return rows


def _cases():
    def poisoned(rng):
        return [(0, _real_keys(rng, n, 100)) for n in (300, 1, 129)], {}

    def exactly_full(rng):  # every place a record: no padding row, nothing to zero
        return [(0, _real_keys(rng, n, 100)) for n in (256, 128, 384)], {}

    def one_record(rng):
        return [(0, _real_keys(rng, 1, 100))], {"capacity_slots": 3}

    def empty_entries(rng):  # a bucket of 8 with 5 used, a block of 0 records between two full ones
        rows = [_real_keys(rng, 128, 100), None, _real_keys(rng, 128, 100), _real_keys(rng, 0, 100),
                _real_keys(rng, 77, 100)]
        return [(0, r) for r in rows], {}

    def two_segments(rng):  # blocks of two staging rounds: the second segment's places follow the first's
        return [(0, _real_keys(rng, 200, 100)), (0, _real_keys(rng, 50, 100)),
                (1, _real_keys(rng, 130, 100)), (1, _real_keys(rng, 3, 100))], {"capacity_slots": 4}

    def equal_keys(rng):  # one key on all ten bytes, every value kept, in the order of their places
        rows = [_real_keys(rng, n, 100) for n in (150, 90)]
        for r in rows:
            r[:, :10] = rows[0][0, :10]
        return [(0, r) for r in rows], {}

    def key_all_ones(rng):  # a real key of 0xFF.. still comes before the padding
        rows = _real_keys(rng, 140, 100)
        rows[::3, :10] = 0xFF
        return [(0, rows)], {}

    return [poisoned, exactly_full, one_record, empty_entries, two_segments, equal_keys, key_all_ones]


@pytest.mark.parametrize("flat", [True, False], ids=["flat", "rows"])
@pytest.mark.parametrize("case", _cases(), ids=lambda f: f.__name__)
def test_ordered_records_against_numpy(rng, case, flat):
    """The executable alone, its segments as the ``dma`` gather may leave
    them: total order by (key, place), every record once, padding last and
    zero whatever the uncovered places held."""
    blocks, kw = case(rng)
    table, segments, rows = placed(blocks, 100, **kw)
    capacity = segments[0].size // 25
    got = np.asarray(ordered_records(table, *segments, record_lanes=25, key_bytes=10, flat=flat))
    assert got.dtype == np.int32 and got.shape == ((capacity * 25,) if flat else (capacity, 25))
    got = got.reshape(-1).view(np.uint8).reshape(capacity, 100)
    assert np.array_equal(got[: len(rows)], by_key_bytes(rows, 10))
    assert not got[len(rows):].any()


@pytest.mark.parametrize("width, key_bytes, lane", [(8, 4, 128), (20, 8, 128), (100, 10, 128), (12, 12, 128),
                                                    (36, 10, 32), (100, 10, 32)])
def test_ordered_records_key_widths_masked_and_unmasked(rng, width, key_bytes, lane):
    """A last key lane that is masked (10 of 12 bytes) and one that is not
    (4, 8, 12: the padding sorts by a flag lane), over poisoned places, at
    128- and 32-lane rows."""
    blocks = [rng.integers(0, 256, size=(n, width), dtype=np.uint8) for n in (150, 1, 77)]
    for block in blocks:
        block[:, : key_bytes - 1] &= 0x81  # few distinct leading bytes: the last key byte decides often
        block[:, key_bytes - 1] |= 1       # and no real key of zeros
    table, segments, rows = placed([(0, b) for b in blocks], width, lane=lane)
    got = np.asarray(ordered_records(table, *segments, record_lanes=width // 4, key_bytes=key_bytes, flat=True))
    got = got.view(np.uint8).reshape(-1, width)
    assert np.array_equal(got[: len(rows)], by_key_bytes(rows, key_bytes))
    assert not got[len(rows):].any()
