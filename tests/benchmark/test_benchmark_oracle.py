"""The records, the plain reference and the comparison that decides
``correct`` — and that the comparison notices what it must: a corrupted byte,
a duplicated record, a misplaced record."""

import json
import os

import pytest

from benchmark.jobs import run_job, run_window
from benchmark.references.groupby import HEADER_BYTES, make_records, record_bytes
from benchmark.spans import SpanLog
from sparkucx_tpu.shuffle.reader import default_deserializer, serialize_records

CONFIG = {"mappers": 3, "pairs_per_mapper": 50, "value_bytes": 64, "reducers": 7, "keys": "uniform-int31"}


class MemoryEntry:
    """A plain shuffle in a dict, with a hook to damage what a reducer reads."""

    def __init__(self, damage=None):
        self.shuffles = {}
        self.damage = damage

    def create(self, shuffle_id, mappers, reducers):
        self.shuffles[shuffle_id] = {r: [] for r in range(reducers)}

    def write_map(self, shuffle_id, map_id, parts):
        for reduce_id, payload in parts:
            self.shuffles[shuffle_id][reduce_id].append(payload)

    def exchange(self, shuffle_id):
        pass

    def read(self, shuffle_id, reduce_id, mappers, consume):
        payloads = list(self.shuffles[shuffle_id][reduce_id])
        if self.damage is not None:
            payloads = self.damage(self.shuffles[shuffle_id], reduce_id, payloads)
        for payload in payloads:
            for key, value in default_deserializer(payload):
                consume(key, value)
        return 0

    def remove(self, shuffle_id):
        del self.shuffles[shuffle_id]


def first_nonempty(blocks_by_reducer):
    return min(r for r, payloads in blocks_by_reducer.items() if payloads)


def corrupt_lead_byte(shuffle, reduce_id, payloads):
    if reduce_id == first_nonempty(shuffle):
        damaged = bytearray(payloads[0])
        damaged[HEADER_BYTES] ^= 0x01  # first byte of the first value
        payloads[0] = bytes(damaged)
    return payloads


def corrupt_late_byte(shuffle, reduce_id, payloads):
    if reduce_id == first_nonempty(shuffle):
        damaged = bytearray(payloads[0])
        damaged[HEADER_BYTES + 40] ^= 0x01  # past the cheap digest's 8 bytes
        payloads[0] = bytes(damaged)
    return payloads


def duplicate_record(shuffle, reduce_id, payloads):
    if reduce_id == first_nonempty(shuffle):
        payloads.append(payloads[0][: record_bytes(CONFIG["value_bytes"])])
    return payloads


def misplace_record(shuffle, reduce_id, payloads):
    # the first reducer's first record surfaces in the last reducer instead
    first, last = first_nonempty(shuffle), max(shuffle)
    width = record_bytes(CONFIG["value_bytes"])
    if reduce_id == first:
        payloads[0] = payloads[0][width:]
    elif reduce_id == last:
        payloads.append(shuffle[first][0][:width])
    return payloads


@pytest.fixture(scope="module")
def records():
    return make_records(CONFIG, seed=11)


def test_records_are_the_programs_wire_format(records):
    for parts in records.blocks:
        for reduce_id, payload in parts:
            decoded = list(default_deserializer(payload))
            assert serialize_records(decoded) == payload
            assert all(key % CONFIG["reducers"] == reduce_id for key, _ in decoded)
            assert all(len(value) == CONFIG["value_bytes"] for _, value in decoded)
    assert records.total_bytes == 3 * 50 * record_bytes(64)
    assert sum(n for n, _, _ in records.expected) == 150


def test_same_seed_same_records_other_seed_other_records(records):
    again = make_records(CONFIG, seed=11)
    other = make_records(CONFIG, seed=12)
    assert again.blocks == records.blocks and again.expected == records.expected
    assert other.blocks != records.blocks
    # ... of the same shape: every seed stages the same ragged blocks
    shape = lambda recs: [[(r, len(p)) for r, p in parts] for parts in recs.blocks]
    assert shape(other) == shape(records)
    assert len({len(p) for parts in records.blocks for _, p in parts}) > 1, "blocks are ragged"


def test_reference_is_the_plain_groupby(records):
    import zlib

    groups = {}
    for parts in records.blocks:
        for _, payload in parts:
            for key, value in default_deserializer(payload):
                groups.setdefault(key, []).append(zlib.crc32(value))
    assert {k: sorted(v) for k, v in groups.items()} == {k: sorted(v) for k, v in records.groups.items()}


def test_a_sound_job_passes_both_checks(records):
    entry = MemoryEntry()
    for full in (False, True):
        job = run_job(entry, records, 0, SpanLog(), full=full)
        assert (job.failed, job.tasks) == (0, 3 + 7)
        assert len(job.read_task_s) == 7
        entry.remove(0)


@pytest.mark.parametrize("damage, timed_sees_it", [
    (corrupt_lead_byte, True),
    (corrupt_late_byte, False),  # only the full comparison's crc32 can
    (duplicate_record, True),
    (misplace_record, True),
], ids=["corrupted-byte", "corrupted-byte-past-the-digest", "duplicated-record", "misplaced-record"])
def test_damage_fails_tasks(records, damage, timed_sees_it):
    entry = MemoryEntry(damage)
    full = run_job(entry, records, 0, SpanLog(), full=True)
    timed = run_job(entry, records, 1, SpanLog(), full=False)
    assert full.failed >= 1
    assert (timed.failed >= 1) == timed_sees_it


@pytest.mark.parametrize("damage", [corrupt_lead_byte, duplicate_record, misplace_record],
                         ids=["corrupted-byte", "duplicated-record", "misplaced-record"])
def test_damage_shows_in_the_window(records, damage):
    """What run.py turns into ``correct: false`` and ``failed`` > 0."""
    window = run_window(MemoryEntry(damage), records, seconds=0.05, trace=False,
                        control=lambda event, **fields: {})
    assert window.warmup.failed >= 1
    assert window.jobs and all(job.failed >= 1 for job in window.jobs)
    assert not window.sound()
    sound = run_window(MemoryEntry(), records, seconds=0.05, trace=False,
                       control=lambda event, **fields: {})
    assert sound.sound() and sum(job.failed for job in sound.jobs) == 0


def test_a_task_that_raises_is_a_failed_task(records):
    def explode(shuffle, reduce_id, payloads):
        if reduce_id == 3:
            raise OSError("fetch failed")
        return payloads

    job = run_job(MemoryEntry(explode), records, 0, SpanLog())
    assert job.failed == 1


def test_window_is_whole_jobs_and_round_trips_as_json(records):
    events = []
    window = run_window(MemoryEntry(), records, seconds=0.05, trace=True,
                        control=lambda event, **fields: events.append(event) or {})
    assert events[0] == "job_done" and events[1] == "window_start" and events[-1] == "window_end"
    # one profiler session over two consecutive jobs, never the window's first;
    # the device numbers are of the one that ran shorter
    assert events.count("trace_start") == events.count("trace_stop") == 1
    start, stop = events.index("trace_start"), events.index("trace_stop")
    assert events[start:stop + 1] == ["trace_start", "job_done", "trace_stop"]
    assert events.count("job_done") == len(window.jobs) + 1
    first = events[:start].count("job_done") - 1  # less the warm-up job's
    assert first >= 1 and window.traced_job in (first, first + 1)
    other = 2 * first + 1 - window.traced_job
    assert window.jobs[window.traced_job].seconds <= window.jobs[other].seconds
    lo, hi = window.traced_ns
    assert (hi - lo) / 1e9 >= window.jobs[window.traced_job].seconds
    assert window.job_bytes == records.total_bytes
    from benchmark.jobs import WindowResult

    again = WindowResult.from_json(json.loads(json.dumps(window.to_json())))
    assert again.to_json() == window.to_json()
