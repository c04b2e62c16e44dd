"""The local operators over a reduce partition's key-ordered records
(``ops/relational.py`` ``grouped_sum_records`` / ``merge_join_records``)
against plain Python over the same records: what ``sort_rows`` hands out —
``(capacity, lanes)`` int32, the first ``count`` rows in key order — in, a few
rows and an ``info`` vector out."""

import jax.numpy as jnp
import numpy as np
import pytest

from sparkucx_tpu.ops.relational import (
    INFO_GROUPS,
    INFO_OVERFLOW,
    INFO_ROWS,
    INFO_TOTAL,
    grouped_sum_records,
    merge_join_records,
    oracle_grouped_sum_records,
)
from sparkucx_tpu.ops.sort import sort_rows

KEY = 8


def records_of(*columns):
    """``(n, 8 * columns)`` ``uint8`` records of little-endian 8-byte columns."""
    cols = [np.asarray(c, dtype="<u8") for c in columns]
    return np.stack(cols, axis=1).view(np.uint8).reshape(len(cols[0]), 8 * len(cols))


def ordered(records, capacity=None, key_bytes=KEY):
    """The records as the ordered device read hands them out."""
    n, width = records.shape
    capacity = capacity or max(32, -(-n // 32) * 32)
    padded = np.zeros((capacity, width), np.uint8)
    padded[:n] = records
    rows = sort_rows(jnp.asarray(padded.view(np.int32).reshape(capacity, width // 4)),
                     -(-key_bytes // 4), n, key_bytes=key_bytes)
    return rows, np.int32(n)


def grouped(records, threshold=None, out_capacity=None, key_bytes=KEY):
    rows, count = ordered(records, key_bytes=key_bytes)
    threshold = 0 if threshold is None else threshold
    out, info = grouped_sum_records(
        rows, count, np.array([threshold & 0xFFFFFFFF, threshold >> 32], np.uint32), key_bytes=key_bytes,
        value_lane=2, having=None if out_capacity is None and not threshold else "gt",
        out_capacity=out_capacity or rows.shape[0])
    info = np.asarray(info)
    return np.asarray(out).view(np.uint8).reshape(out.shape[0], -1)[: info[INFO_ROWS]], info


def by_key_bytes(records, key_bytes=KEY):
    return records[np.argsort([r[:key_bytes].tobytes() for r in records], kind="stable")] if len(records) else records


CASES = {
    "several rows a key": lambda rng: records_of(rng.integers(0, 40, 500), rng.integers(1, 5001, 500)),
    "all keys distinct": lambda rng: records_of(rng.permutation(300) + 7, rng.integers(1, 5001, 300)),
    "one key": lambda rng: records_of(np.full(77, 12345), rng.integers(1, 5001, 77)),
    "keys equal in the low lane and different in the high one": lambda rng: records_of(
        rng.integers(0, 6, 400).astype(np.uint64) + (rng.integers(0, 5, 400).astype(np.uint64) << np.uint64(32)),
        rng.integers(1, 5001, 400)),
    "values that need the high word": lambda rng: records_of(
        rng.integers(0, 9, 200), rng.integers(0, 1 << 40, 200, dtype=np.uint64)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_grouped_sum_is_the_plain_sum(rng, case):
    records = CASES[case](rng)
    got, info = grouped(records)
    want = oracle_grouped_sum_records(by_key_bytes(records), KEY, 8)
    assert np.array_equal(got, want)
    assert info[INFO_ROWS] == info[INFO_TOTAL] == info[INFO_GROUPS] == len(want) and not info[INFO_OVERFLOW]


@pytest.mark.parametrize("threshold, passes", [(0, "everything"), (1 << 62, "nothing"), (30_000, "some")])
def test_the_predicate_is_strictly_greater_and_only_survivors_are_compacted(rng, threshold, passes):
    records = records_of(rng.integers(0, 50, 600), rng.integers(1, 5001, 600))
    records[:12, 8:] = records_of(np.full(12, 2500))[:, :8]
    records[:12, :8] = records_of(np.full(12, 99))[:, :8]  # a group of exactly 30,000: not over it
    got, info = grouped(records, threshold=threshold, out_capacity=64)
    want = oracle_grouped_sum_records(by_key_bytes(records), KEY, 8, threshold)
    assert np.array_equal(got, want) and info[INFO_GROUPS] == 51
    assert {"everything": len(want) == 51, "nothing": len(want) == 0, "some": 0 < len(want) < 51}[passes]
    assert 99 not in got[:, :8].view("<u8") or threshold != 30_000


def test_an_empty_partition_gives_no_group():
    got, info = grouped(np.zeros((0, 16), np.uint8), out_capacity=8)
    assert len(got) == 0 and list(info) == [0, 0, 0, 0]


def test_more_survivors_than_room_is_told_not_cut_silently(rng):
    records = records_of(np.arange(100), np.full(100, 7))
    got, info = grouped(records, threshold=1, out_capacity=16)
    assert info[INFO_ROWS] == 16 and info[INFO_TOTAL] == 100 and len(got) == 16


@pytest.mark.parametrize("values, overflow", [
    ([(1 << 31) - 1, 1, 5], False),          # past 2**31: exact
    ([0xFFFFFFFF, 0xFFFFFFFF, 2], False),    # past 2**32: exact
    ([(1 << 62), (1 << 62) - 1], False),     # 2**63 - 1: the largest sum
    ([(1 << 62), (1 << 62)], True),          # 2**63: flagged, not wrapped
    ([(1 << 63) + 5], True),                 # a value with its top bit set
])
def test_a_sum_is_exact_or_flagged_never_wrapped(values, overflow):
    records = records_of([3] * len(values) + [4], values + [10])
    got, info = grouped(records)
    assert bool(info[INFO_OVERFLOW]) is overflow
    if not overflow:
        assert [int(v) for v in got[:, 8:].view("<u8").ravel()] == [sum(values), 10]


def test_a_key_of_ten_bytes_is_compared_over_all_ten(rng):
    """Keys that share their first eight bytes and differ in the two after
    them (the third lane, masked to the key's bytes) are different groups."""
    n = 120
    records = np.zeros((n, 24), np.uint8)
    records[:, :8] = 0xAB
    records[:, 8:10] = rng.integers(0, 3, (n, 1)).astype(np.uint8)
    records[:, 10:12] = rng.integers(0, 256, (n, 2))  # after the key: not compared
    records[:, 16:] = records_of(rng.integers(1, 100, n))
    rows, count = ordered(records, key_bytes=10)
    out, info = grouped_sum_records(rows, count, np.zeros(2, np.uint32), key_bytes=10, value_lane=4,
                                    having=None, out_capacity=16)
    info = np.asarray(info)
    assert info[INFO_ROWS] == 3
    got = np.asarray(out).view(np.uint8).reshape(16, 24)[:3]
    sums = {int(k): int(records[records[:, 8] == k, 16:].view("<u8").sum()) for k in range(3)}
    assert {int(r[8]): int(r[16:].view("<u8")[0]) for r in got} == sums


# -- the merge join ---------------------------------------------------------------

def join(probe_records, build_records, join_type, out_capacity=256):
    probe, probe_count = ordered(probe_records)
    build, build_count = ordered(build_records, capacity=32)
    out, info = merge_join_records(probe, probe_count, build, build_count, key_bytes=KEY,
                                   join_type=join_type, out_capacity=out_capacity)
    info = np.asarray(info)
    return np.asarray(out).view(np.uint8).reshape(out.shape[0], -1)[: info[INFO_ROWS]], info


def plain_join(probe_records, build_records, join_type):
    build_records, out = by_key_bytes(build_records), []
    if join_type == "left_semi":
        held = {b[:KEY].tobytes() for b in build_records}
        return [p.tobytes() for p in by_key_bytes(probe_records) if p[:KEY].tobytes() in held]
    for b in build_records:
        out += [p.tobytes() + b[KEY:].tobytes() for p in probe_records if p[:KEY].tobytes() == b[:KEY].tobytes()]
    return out


JOINS = {
    "several probe rows a key": lambda rng: (
        records_of(rng.integers(0, 60, 700), rng.integers(1, 5001, 700)),
        records_of(rng.choice(60, 9, replace=False), np.arange(9), np.arange(9) * 3)),
    "absent keys": lambda rng: (
        records_of(rng.integers(0, 30, 300) * 2, rng.integers(1, 5001, 300)),
        records_of(np.arange(12) * 5 + 1, np.arange(12), np.arange(12))),  # odd keys: half are no probe row's
    "empty build": lambda rng: (
        records_of(rng.integers(0, 30, 100), rng.integers(1, 5001, 100)), np.zeros((0, 24), np.uint8)),
    "empty probe": lambda rng: (np.zeros((0, 16), np.uint8), records_of(np.arange(5), np.arange(5), np.arange(5))),
    "keys that differ in the high lane only": lambda rng: (
        records_of(rng.integers(0, 4, 200).astype(np.uint64) + (rng.integers(0, 4, 200).astype(np.uint64) << np.uint64(32)),
                   rng.integers(1, 5001, 200)),
        records_of([1 + (2 << 32), 3, 2 + (1 << 32)], [7, 8, 9], [1, 2, 3])),
    "a build key held twice": lambda rng: (
        records_of(rng.integers(0, 10, 150), rng.integers(1, 5001, 150)),
        records_of([4, 4, 6], [1, 2, 3], [9, 9, 9])),
}


@pytest.mark.parametrize("join_type", ["inner", "left_semi"])
@pytest.mark.parametrize("case", sorted(JOINS))
def test_the_merge_join_is_the_plain_join(rng, case, join_type):
    probe, build = JOINS[case](rng)
    got, info = join(probe, build, join_type, out_capacity=512)
    want = plain_join(probe, build, join_type)
    assert info[INFO_ROWS] == info[INFO_TOTAL] == len(want)
    assert sorted(r.tobytes() for r in got) == sorted(want)
    keys = [r[:KEY].tobytes() for r in got]
    assert keys == sorted(keys)  # the output is key-ordered: it feeds the next operator as it is


def test_a_join_that_outgrows_its_room_says_so(rng):
    probe = records_of(np.full(300, 5), rng.integers(1, 50, 300))
    got, info = join(probe, records_of([5], [1], [1]), "inner", out_capacity=64)
    assert info[INFO_ROWS] == 64 and info[INFO_TOTAL] == 300


def test_the_join_composes_with_the_sum(rng):
    """lines joined with the surviving orders and summed by key: the second
    half of Q18's reduce task."""
    lines = records_of(rng.integers(0, 80, 900), rng.integers(100, 5001, 900))
    orders = records_of([11, 30, 31], [501, 502, 503], [9000, 8000, 7000])
    probe, probe_count = ordered(lines)
    build, build_count = ordered(orders, capacity=32)
    joined, info = merge_join_records(probe, probe_count, build, build_count, key_bytes=KEY,
                                      join_type="inner", out_capacity=128)
    out, info2 = grouped_sum_records(joined, info[INFO_ROWS], np.zeros(2, np.uint32), key_bytes=KEY,
                                     value_lane=2, having=None, out_capacity=32)
    info2 = np.asarray(info2)
    got = np.asarray(out).view("<u8").reshape(32, 4)[: info2[INFO_ROWS]]
    keys = lines[:, :8].view("<u8").ravel()
    want = [[k, int(lines[keys == k, 8:].view("<u8").sum()), c, p]
            for k, c, p in ([11, 501, 9000], [30, 502, 8000], [31, 503, 7000])]
    assert sorted(got.tolist()) == sorted(want)


def test_unknown_parameters_are_refused():
    rows, count = ordered(records_of([1], [1]))
    with pytest.raises(ValueError, match="unknown join_type"):
        merge_join_records(rows, count, rows, count, key_bytes=KEY, join_type="left_outer", out_capacity=8)
    with pytest.raises(ValueError, match="unknown having"):
        grouped_sum_records(rows, count, np.zeros(2, np.uint32), key_bytes=KEY, value_lane=2, having="lt",
                            out_capacity=8)
    with pytest.raises(ValueError, match="8-byte value"):
        grouped_sum_records(rows, count, np.zeros(2, np.uint32), key_bytes=KEY, value_lane=3, having=None,
                            out_capacity=8)
