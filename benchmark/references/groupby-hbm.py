"""GroupByTest records for a reduce side that runs on the device, and the
plain check of one reduce task's packed buffer.

The records are ``references/groupby.py``'s, from the same ``--seed`` (its
generator is loaded, not copied).  What differs is the consumer: a reduce task
hands over no ``(key, value)`` stream but one packed ``(rows, 128)`` int32
buffer on the chip and the ``(B, 2)`` table of each block's starting row and
true byte length.  A record's width is fixed (19 bytes of framing, then the
value), so a block of ``length`` bytes holds ``length // width`` records at
known offsets, and the task's four numbers — records, value bytes, the sum of
the first 8 bytes of every value mod 2**64, keys that do not hash to this
reducer — are read off the buffer where it lies:

``device_numbers``  plain ``jax.numpy`` (no kernel of the program, no 64-bit
                    type: byte sums leave the chip as 8 limbs), what every
                    timed task runs;
``host_numbers``    the same four numbers by NumPy over the same bytes on the
                    host — the reference of the reference (tier-1 compares the
                    two, and both with ``groupby.TaskCheck``).

Nothing here imports the code under test.
"""

from __future__ import annotations

import functools
import zlib
from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np

from benchmark.cells import load_module

groupby = load_module("references", "groupby")

HEADER_BYTES = groupby.HEADER_BYTES
#: bytes of a record the check reads: the framing and the value's first 8
LEAD_BYTES = HEADER_BYTES + 8
_MASK = (1 << 64) - 1
#: the framing of every record, apart from its key and its value's length
_FRAME = {0: ord("t"), 1: 0, 2: 0, 3: 0, 4: 2, 5: ord("i"), 14: ord("b")}


@dataclass
class Records(groupby.Records):
    """The plain GroupBy's records; ``check`` gives the device consumers."""

    value_bytes: int = 0

    def check(self, reduce_id: int, full: bool = False) -> "TaskCheck":
        return (FullCheck if full else TaskCheck)(self, reduce_id)


def make_records(config: dict, seed: int) -> Records:
    if int(config["value_bytes"]) < 8:
        raise ValueError("the device check reads the first 8 bytes of every value")
    made = groupby.make_records(config, seed)
    return Records(made.reducers, made.blocks, made.expected, made.groups,
                   value_bytes=int(config["value_bytes"]))


def _bucket(n: int) -> int:
    """The next power of two: tasks of nearby sizes share one executable."""
    return 1 << max(int(n) - 1, 0).bit_length()


def record_slots(table: np.ndarray, width: int, row_bytes: int) -> Tuple[np.ndarray, np.ndarray, int]:
    """Per block of the table: the byte offset of its first record in the
    packed buffer and how many records it holds, and the bytes that belong to
    no whole record (0 in a sound block)."""
    lengths = table[:, 1].astype(np.int64)
    return table[:, 0].astype(np.int64) * row_bytes, lengths // width, int((lengths % width).sum())


@functools.lru_cache(maxsize=None)
def _device_fn(blocks: int, per_block: int, width: int, reducers: int):
    """The jitted check over ``blocks`` x ``per_block`` record slots."""
    import jax
    import jax.numpy as jnp

    def task_numbers(packed, starts, counts, reduce_id):
        lane = packed.shape[1]
        k = jnp.arange(per_block, dtype=jnp.int32)
        live = k[None, :] < counts[:, None]                            # (B, K)
        first = starts[:, None] + k[None, :] * width                   # byte offset of the record
        at = first[:, :, None] + jnp.arange(LEAD_BYTES, dtype=jnp.int32)  # (B, K, 27)
        at = jnp.where(live[:, :, None], at, 0)
        word = packed[at // (4 * lane), (at // 4) % lane].astype(jnp.uint32)
        byte = (word >> (8 * (at % 4)).astype(jnp.uint32)) & 0xFF      # little-endian words
        byte = jnp.where(live[:, :, None], byte, 0)

        def big_endian(lo, hi):
            value = jnp.zeros(byte.shape[:2], dtype=jnp.uint32)
            for j in range(lo, hi):
                value = (value << 8) | byte[:, :, j]
            return value

        framed = live
        for j, want in _FRAME.items():
            framed = framed & (byte[:, :, j] == want)
        key_hi, key_lo = big_endian(6, 10), big_endian(10, 14)
        # (hi * 2**32 + lo) mod reducers in 32 bits; a key is non-negative
        owner = ((key_hi % reducers) * ((1 << 32) % reducers) + key_lo % reducers) % reducers
        placed = framed & (key_hi >> 31 == 0) & (owner == reduce_id.astype(jnp.uint32))
        records = jnp.sum(live, dtype=jnp.uint32)
        misplaced = records - jnp.sum(placed, dtype=jnp.uint32)
        value_bytes = jnp.sum(jnp.where(live, big_endian(15, 19), 0), dtype=jnp.uint32)
        limbs = jnp.sum(byte[:, :, HEADER_BYTES:], axis=(0, 1), dtype=jnp.uint32)  # (8,)
        return jnp.concatenate([jnp.stack([records, value_bytes, misplaced]), limbs])

    return jax.jit(task_numbers)


def device_numbers(packed, table: np.ndarray, reduce_id: int, reducers: int,
                   value_bytes: int) -> Tuple[int, int, int, int]:
    """(records, value bytes, digest, misplaced) of one task's packed buffer,
    read on the device the buffer lies on; eleven 32-bit numbers come back.
    A block that is no whole number of records counts as one misplaced key."""
    width = HEADER_BYTES + value_bytes
    row_bytes = int(packed.shape[1]) * 4
    if int(packed.shape[0]) * row_bytes >= 1 << 31:
        raise ValueError("a packed buffer of 2 GiB or more needs 64-bit offsets")
    starts, counts, stray = record_slots(table, width, row_bytes)
    if not len(table):
        return 0, 0, 0, 0
    b, k = _bucket(len(table)), _bucket(counts.max())
    if b * k > 1 << 24:
        raise ValueError("byte sums of more than 2**24 records overflow a 32-bit limb")
    pad = b - len(table)
    fn = _device_fn(b, k, width, reducers)
    out = np.asarray(fn(packed, np.pad(starts, (0, pad)).astype(np.int32),
                        np.pad(counts, (0, pad)).astype(np.int32), np.int32(reduce_id)))
    digest = sum(int(limb) << (8 * i) for i, limb in enumerate(out[3:])) & _MASK
    return int(out[0]), int(out[1]), digest, int(out[2]) + bool(stray)


def host_records(host: np.ndarray, table: np.ndarray, value_bytes: int, row_bytes: int):
    """Every (key, value) of a packed buffer's bytes on the host, block by
    block in the table's order; the value is a view."""
    width = HEADER_BYTES + value_bytes
    starts, counts, _ = record_slots(table, width, row_bytes)
    for start, count in zip(starts.tolist(), counts.tolist()):
        for at in range(start, start + count * width, width):
            yield int.from_bytes(host[at + 6 : at + 14].tobytes(), "big", signed=True), \
                host[at + HEADER_BYTES : at + width]


def host_numbers(host: np.ndarray, table: np.ndarray, reduce_id: int, reducers: int,
                 value_bytes: int, row_bytes: int) -> Tuple[int, int, int, int]:
    """``device_numbers`` by NumPy over the same bytes (``host`` is the packed
    buffer as flat uint8)."""
    width = HEADER_BYTES + value_bytes
    starts, counts, stray = record_slots(table, width, row_bytes)
    first = np.concatenate([s + np.arange(c) * width for s, c in zip(starts, counts)] or [np.zeros(0, np.int64)])
    lead = host[first[:, None] + np.arange(LEAD_BYTES)]                # (N, 27)
    framed = np.ones(len(first), dtype=bool)
    for j, want in _FRAME.items():
        framed &= lead[:, j] == want
    keys = np.ascontiguousarray(lead[:, 6:14]).view(">i8").reshape(-1)
    placed = framed & (keys >= 0) & (keys % reducers == reduce_id)
    lengths = np.ascontiguousarray(lead[:, 15:19]).view(">u4").reshape(-1)
    leads = np.ascontiguousarray(lead[:, HEADER_BYTES:]).view("<u8").reshape(-1)
    return (len(first), int(lengths.sum(dtype=np.uint64)), int(leads.sum(dtype=np.uint64)),
            int(len(first) - placed.sum()) + bool(stray))


class TaskCheck:
    """What a timed reduce task does with its packed buffer: the four numbers,
    read on the chip; the task ends when they are on the host."""

    __slots__ = ("reference", "reduce_id", "numbers", "failed")

    def __init__(self, reference: Records, reduce_id: int) -> None:
        self.reference = reference
        self.reduce_id = reduce_id
        self.numbers = (0, 0, 0, 0)
        self.failed = False

    def add(self, packed, table: np.ndarray) -> None:
        """The consumer: called once, with the task's whole read."""
        ref = self.reference
        self.numbers = device_numbers(packed, table, self.reduce_id, ref.reducers, ref.value_bytes)

    def fail(self) -> None:
        """The task raised: whatever it had read, it failed."""
        self.failed = True

    def ok(self) -> bool:
        records, value_bytes, digest, misplaced = self.numbers
        want = self.reference.expected[self.reduce_id]
        return not self.failed and not misplaced and (records, value_bytes, digest) == want


class FullCheck(TaskCheck):
    """The warm-up job's consumer: the cheap check on the chip, then the
    packed buffer brought to the host for the plain GroupBy's own comparison —
    group count and a crc32 of every value under its key."""

    __slots__ = ("groups",)

    def __init__(self, reference: Records, reduce_id: int) -> None:
        super().__init__(reference, reduce_id)
        self.groups: Dict[int, List[int]] = {}

    def add(self, packed, table: np.ndarray) -> None:
        super().add(packed, table)
        row_bytes = int(packed.shape[1]) * 4
        host = np.asarray(packed).reshape(-1).view(np.uint8)
        if self.numbers != host_numbers(host, table, self.reduce_id, self.reference.reducers,
                                        self.reference.value_bytes, row_bytes):
            self.failed = True
        for key, value in host_records(host, table, self.reference.value_bytes, row_bytes):
            self.groups.setdefault(key, []).append(zlib.crc32(value))

    def ok(self) -> bool:
        if not super().ok():
            return False
        want = self.reference.groups
        return all(sorted(crcs) == sorted(want.get(key, ())) for key, crcs in self.groups.items())
