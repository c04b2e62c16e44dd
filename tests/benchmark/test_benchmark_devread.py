"""The device-read cell's own files: the reference's check of a packed buffer
on the device against its NumPy form and against the plain GroupBy's
``TaskCheck`` on seeded records, and the driver's refusal of a program without
``read_device``.

CPU backend: counts and bytes, no rate."""

import numpy as np
import pytest

from benchmark.cells import load_module

hbm = load_module("references", "groupby-hbm")
groupby = load_module("references", "groupby")

ROW = 512
CONFIG = {"mappers": 5, "pairs_per_mapper": 60, "value_bytes": 700, "reducers": 7, "keys": "uniform-int31"}
WIDTH = groupby.HEADER_BYTES + CONFIG["value_bytes"]


@pytest.fixture(scope="module")
def records():
    return hbm.make_records(CONFIG, seed=3_000_000_019)


def packed_task(records, reduce_id, gap_rows=0):
    """One reduce task's blocks laid out as ``read_device`` returns them: each
    block from a row boundary, in map order; ``gap_rows`` unused rows between
    blocks (as between two rounds' buckets).  Returns (flat uint8, table)."""
    parts = [payload for m in records.mappers_of(reduce_id)
             for r, payload in records.blocks[m] if r == reduce_id]
    table, chunks, row = [], [], 0
    for payload in parts:
        rows = -(-len(payload) // ROW)
        table.append((row, len(payload)))
        chunks.append(np.frombuffer(payload, np.uint8))
        chunks.append(np.full((rows + gap_rows) * ROW - len(payload), 0xA5, np.uint8))  # not zeros
        row += rows + gap_rows
    flat = np.concatenate(chunks) if chunks else np.zeros(0, np.uint8)
    return flat, np.asarray(table, dtype=np.int64).reshape(-1, 2)


def on_device(flat):
    import jax.numpy as jnp

    return jnp.asarray(flat.view(np.int32).reshape(-1, ROW // 4))


def numbers_three_ways(records, flat, table, reduce_id):
    device = hbm.device_numbers(on_device(flat), table, reduce_id, records.reducers, records.value_bytes)
    host = hbm.host_numbers(flat, table, reduce_id, records.reducers, records.value_bytes, ROW)
    plain = groupby.TaskCheck(records, reduce_id)
    for key, value in hbm.host_records(flat, table, records.value_bytes, ROW):
        plain.add(key, value.tobytes())
    return device, host, (plain.records, plain.bytes, plain.digest, plain.misplaced)


@pytest.mark.parametrize("gap_rows", [0, 5], ids=["packed", "bucket-gaps"])
@pytest.mark.parametrize("reduce_id", range(CONFIG["reducers"]))
def test_device_check_equals_numpy_and_the_plain_task_check(records, reduce_id, gap_rows):
    flat, table = packed_task(records, reduce_id, gap_rows)
    counts = table[:, 1] // WIDTH
    assert len(set(counts.tolist())) > 1  # ragged: blocks of different record counts
    device, host, plain = numbers_three_ways(records, flat, table, reduce_id)
    assert device == host == plain
    assert device[3] == 0 and device[:3] == records.expected[reduce_id]
    check = records.check(reduce_id)
    check.add(on_device(flat), table)
    assert check.ok()


def test_whole_blocks_of_one_record_count(records):
    """Every block cut to its first record: equal counts, one slot a block."""
    flat, table = packed_task(records, 2)
    table[:, 1] = WIDTH
    device, host, plain = numbers_three_ways(records, flat, table, 2)
    assert device == host == plain and device[0] == len(table) and device[1] == len(table) * CONFIG["value_bytes"]


@pytest.mark.parametrize("where, caught", [
    ("value-lead", True),    # one of the first 8 bytes of a value: the digest moves
    ("key", True),           # the key's last byte: it hashes to another reducer
    ("frame", True),         # the record's tag
    ("length-field", True),  # the value's length
    ("value-tail", False),   # past the first 8 bytes: only the full check's crc32 sees it
])
def test_a_tampered_byte(records, where, caught):
    reduce_id = 4
    flat, table = packed_task(records, reduce_id)
    flat = flat.copy()
    at = int(table[1, 0]) * ROW + WIDTH  # the second record of the second block
    offset = {"value-lead": groupby.HEADER_BYTES + 3, "key": 13, "frame": 0, "length-field": 18,
              "value-tail": groupby.HEADER_BYTES + 100}[where]
    flat[at + offset] ^= 0x01
    device, host, plain = numbers_three_ways(records, flat, table, reduce_id)
    assert device == host
    if where in ("value-lead", "key", "value-tail"):
        assert device == plain  # the plain check decodes neither the frame nor the length field
    check = records.check(reduce_id)
    check.add(on_device(flat), table)
    assert check.ok() == (not caught)
    if where == "key":
        assert device[3] == 1
    full = records.check(reduce_id, full=True)
    full.add(on_device(flat), table)
    assert not full.ok()  # the crc32 of every value: any byte


def test_a_misplaced_key_and_a_block_cut_short(records):
    # reducer 1's bytes handed to reducer 2's task: every key is misplaced
    flat, table = packed_task(records, 1)
    device, host, plain = numbers_three_ways(records, flat, table, 2)
    assert device == host == plain and device[3] == device[0] > 0
    # a block that is no whole number of records
    table = table.copy()
    table[0, 1] -= 1
    device, host, _ = numbers_three_ways(records, flat, table, 1)
    assert device == host and device[3] == 1 and device[0] == records.expected[1][0] - 1
    check = records.check(1)
    check.add(on_device(flat), table)
    assert not check.ok()


def test_a_task_without_blocks_and_a_task_that_raised(records):
    empty = np.zeros((0, 2), np.int64)
    assert hbm.device_numbers(on_device(np.zeros(0, np.uint8)), empty, 0, 7, 700) == (0, 0, 0, 0)
    assert hbm.host_numbers(np.zeros(0, np.uint8), empty, 0, 7, 700, ROW) == (0, 0, 0, 0)
    flat, table = packed_task(records, 0)
    check = records.check(0)
    check.add(on_device(flat), table)
    check.fail()
    assert not check.ok()


def test_the_full_check_is_the_plain_groupbys(records):
    checks = []
    for r in range(records.reducers):
        flat, table = packed_task(records, r, gap_rows=r % 2)
        check = records.check(r, full=True)
        check.add(on_device(flat), table)
        assert check.ok()
        checks.append(check)
    assert records.complete(checks)
    assert sum(len(v) for c in checks for v in c.groups.values()) == CONFIG["mappers"] * CONFIG["pairs_per_mapper"]
    # one task's groups lost: the job's full read is not complete
    checks[3].groups.clear()
    assert not records.complete(checks)


def test_tasks_of_nearby_sizes_share_the_check_executable(records):
    from benchmark.counters import CompileCounter

    compiles = CompileCounter()
    tasks = [packed_task(records, r) for r in range(records.reducers)]
    for r, (flat, table) in enumerate(tasks):  # every shape once
        records.check(r).add(on_device(flat), table)
    mark = compiles.snapshot()
    for r, (flat, table) in enumerate(tasks):
        records.check(r).add(on_device(flat), table)
    assert compiles.since(mark)["compiles"] == 0
    assert hbm._device_fn.cache_info().currsize <= 4


# -- the driver ---------------------------------------------------------------


def test_the_driver_refuses_a_reader_without_read_device():
    driver = load_module("traffic", "manager-devread")
    from sparkucx_tpu.shuffle.reader import TpuShuffleReader

    driver.require_device_read(TpuShuffleReader)  # this program: accepted

    class ParentReader:  # the parent commit's: fetch_blocks and read only
        def fetch_blocks(self):
            return iter(())

        def read(self):
            return iter(())

    with pytest.raises(SystemExit, match="read_device"):
        driver.require_device_read(ParentReader)


def test_the_driver_fails_before_any_record_is_made(monkeypatch):
    """``start`` on a program without ``read_device``: no records, no manager."""
    import sparkucx_tpu.shuffle.reader as reader_module

    driver = load_module("traffic", "manager-devread")
    monkeypatch.delattr(reader_module.TpuShuffleReader, "read_device")
    traffic = driver.Traffic(cell=None, args=None)  # nothing of either is touched before the refusal
    with pytest.raises(SystemExit):
        traffic.start(conf=None, parts={})
    assert traffic.manager is None and not hasattr(traffic, "records")
    traffic.close()


def test_the_entry_reads_through_get_reader_only():
    """The cell's read is ``manager.get_reader(...).read_device()``: the
    driver names no transport method."""
    import inspect

    driver = load_module("traffic", "manager-devread")
    source = inspect.getsource(driver)
    assert "get_reader(" in source and ".read_device()" in source
    assert "fetch_blocks_device" not in source and "fetch_blocks_to_device" not in source
