"""TeraSort's records and the plain TeraSort they are checked against.

Source job: Hadoop TeraGen / TeraSort (sortbenchmark.org: 100-byte records,
10-byte keys compared as unsigned bytes, most significant first) as Spark runs
it (``ehiggs/spark-terasort``: one map task a ``TeraInputFormat`` split,
``TeraSortPartitioner(partitions)``, ``repartitionAndSortWithinPartitions``).
The partitioner, **written from memory of ``TeraSortPartitioner.scala``** (no
network here): a key's first 7 bytes as a big-endian integer, divided by
``(2**56 - 1) // reducers``; the one prefix range that division sends to
``reducers`` is kept in the last partition (``assumed`` in the configuration's
file).  Records are serialized back to back with no framing: a block is
``n * record_bytes`` bytes.

This benchmark measures the job's *shuffle*: the program hands a reduce task
its records unordered, and the sort is the reference's.  What a correct
shuffle returns is TeraSort's answer — all the job's records sorted by key
(ties by the value's bytes, so the answer is unique), cut at the
partitioner's range bounds — computed from the generated arrays, never from
what a shuffle returned, a partition at a time so that it fits.

Which partition each record of a mapper goes to (the key's 7-byte prefix)
comes from the fixed layout stream, the same for every ``--seed``: every run
stages the same blocks in the same rounds.  The last ``key_bytes - 7`` bytes
of a key and every value come from ``--seed`` (the value bytes are seed bytes:
TeraGen's row id and filler are read by nothing on the path).

Nothing here imports the code under test.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional, Tuple

import numpy as np

from benchmark.cells import load_module

#: the generator's stream for the block layout, apart from every ``--seed``'s
LAYOUT_DRAW = load_module("references", "groupby").LAYOUT_DRAW
#: bytes of a key the partitioner reads, and the integers they make
PREFIX_BYTES = 7
PREFIX_END = 1 << (8 * PREFIX_BYTES)
#: bytes after the key that the timed check sums
LEAD_BYTES = 8
_MASK = (1 << 64) - 1


def _check_config(config: dict) -> Tuple[int, int, int]:
    width, key, reducers = int(config["record_bytes"]), int(config["key_bytes"]), int(config["reducers"])
    if config["keys"] != "uniform-bytes":
        raise ValueError(f"unknown key distribution {config['keys']!r}")
    if not (PREFIX_BYTES < 8 <= key and key + LEAD_BYTES <= width and width % 4 == 0 and reducers >= 1):
        raise ValueError(f"no TeraSort record: {width} B records, {key} B keys, {reducers} reducers")
    return width, key, reducers


def range_step(reducers: int) -> int:
    """``TeraSortPartitioner.rangePerPart``: (max - min) / partitions over the
    7-byte prefixes."""
    return (PREFIX_END - 1) // reducers


def partition_of(prefix: np.ndarray, reducers: int) -> np.ndarray:
    """The reduce partition of every 7-byte key prefix (``uint64``)."""
    return np.minimum(prefix // np.uint64(range_step(reducers)), np.uint64(reducers - 1)).astype(np.int64)


def range_of(reduce_id: int, reducers: int) -> Tuple[int, int]:
    """The prefixes ``[lo, hi)`` that partition holds; the last one's reach
    the end."""
    step = range_step(reducers)
    return reduce_id * step, (reduce_id + 1) * step if reduce_id < reducers - 1 else PREFIX_END


def prefixes(rows: np.ndarray) -> np.ndarray:
    """Every record's 7-byte key prefix as an integer."""
    return np.ascontiguousarray(rows[:, :8]).view(">u8").ravel() >> np.uint64(8)


def sort_records(rows: np.ndarray) -> np.ndarray:
    """The reference's comparator: the records ordered as unsigned bytes, most
    significant first — by key, ties by the value's bytes — in a new array.
    Ordered by the first eight bytes as one integer; where two records share
    those (never at this key width but by design of a test), the whole is
    ordered as ``record_bytes``-byte strings instead."""
    lead = np.ascontiguousarray(rows[:, :8]).view(">u8").ravel()
    order = np.argsort(lead, kind="stable")
    lead = lead[order]
    if np.any(lead[1:] == lead[:-1]):
        width = rows.shape[1]
        whole = np.ascontiguousarray(rows).view(f"V{width}").ravel()
        return np.sort(whole).view(np.uint8).reshape(-1, width)
    return rows[order]


def checksum(rows: np.ndarray) -> int:
    """An order-free sum over every byte of every record (TeraValidate's
    checksum in kind: its own is a sum of a CRC a row): the records'
    little-endian 32-bit words, summed mod 2**64."""
    return int(np.ascontiguousarray(rows).reshape(-1).view("<u4").sum(dtype=np.uint64))


def lead_sum(rows: np.ndarray, key_bytes: int) -> int:
    """The sum mod 2**64 of the 8 bytes after the key of every record."""
    lead = np.ascontiguousarray(rows[:, key_bytes : key_bytes + LEAD_BYTES]).view("<u8")
    return int(lead.sum(dtype=np.uint64))


def layout(config: dict, m: int) -> Tuple[np.ndarray, np.ndarray]:
    """Mapper ``m``'s key prefixes grouped by partition (inside a partition in
    the order drawn) and ``bounds``: partition ``r``'s records are
    ``[bounds[r], bounds[r + 1])``.  The same for every ``--seed``."""
    _, _, reducers = _check_config(config)
    draw = np.random.default_rng([LAYOUT_DRAW, m])
    prefix = draw.integers(0, PREFIX_END, size=int(config["records_per_mapper"]), dtype=np.uint64)
    part = partition_of(prefix, reducers)
    order = np.argsort(part, kind="stable")
    return prefix[order], np.searchsorted(part[order], np.arange(reducers + 1))


class Records:
    """One job's map output and what a correct shuffle of it returns."""

    def __init__(self, config: dict, blocks, expected, total_checksum: int) -> None:
        self.record_bytes, self.key_bytes, self.reducers = _check_config(config)
        #: blocks[m] = [(reduce_id, records back to back)] for the non-empty
        #: partitions of mapper m, in reducer order
        self.blocks: List[List[Tuple[int, bytes]]] = blocks
        #: per reducer: records, bytes, sum of the 8 bytes after every key —
        #: what every timed reduce task checks
        self.expected: List[Tuple[int, int, int]] = expected
        #: ``checksum`` over the whole job
        self.checksum = total_checksum
        self._mappers_of: List[List[int]] = [[] for _ in range(self.reducers)]
        for m, parts in enumerate(blocks):
            for r, _ in parts:
                self._mappers_of[r].append(m)

    @property
    def num_mappers(self) -> int:
        return len(self.blocks)

    @property
    def num_blocks(self) -> int:
        return sum(len(parts) for parts in self.blocks)

    @property
    def total_bytes(self) -> int:
        return sum(len(payload) for parts in self.blocks for _, payload in parts)

    @property
    def total_records(self) -> int:
        return self.total_bytes // self.record_bytes

    def mappers_of(self, reduce_id: int) -> List[int]:
        return self._mappers_of[reduce_id]

    def rows_of(self, reduce_id: int) -> np.ndarray:
        """The generated records of one partition, as made (unordered)."""
        found = [
            np.frombuffer(payload, dtype=np.uint8)
            for parts in self.blocks for r, payload in parts if r == reduce_id
        ]
        flat = np.concatenate(found) if found else np.empty(0, dtype=np.uint8)
        return flat.reshape(-1, self.record_bytes)

    def sorted_partition(self, reduce_id: int) -> np.ndarray:
        """The plain TeraSort's slice for one partition: its records in key
        order."""
        return sort_records(self.rows_of(reduce_id))

    def check(self, reduce_id: int, full: bool = False) -> "TaskCheck":
        """The consumer of one reduce task's batches."""
        return (FullCheck if full else TaskCheck)(self, reduce_id)

    def complete(self, checks: List["FullCheck"]) -> bool:
        """TeraValidate's three over the job's full read (every task already
        ``ok``): each partition's largest key below the next one's smallest,
        the record count, the checksum."""
        ends = [(c.smallest, c.largest) for c in checks if c.records]
        in_order = all(a[1] < b[0] for a, b in zip(ends, ends[1:]))
        return (
            in_order
            and sum(c.records for c in checks) == self.total_records
            and sum(c.checksum for c in checks) & _MASK == self.checksum
        )


def _mapper(config: dict, seed: int, m: int):
    """One mapper's blocks and its part of the reference: the prefixes from the
    layout stream, one draw of ``--seed``'s stream for everything else, no
    Python a record."""
    width, key, reducers = _check_config(config)
    prefix, bounds = layout(config, m)
    n = len(prefix)
    rng = np.random.default_rng([seed, m])
    words = rng.integers(0, 2**64, size=-(-n * width // 8), dtype=np.uint64)
    rows = words.view(np.uint8)[: n * width].reshape(n, width)
    rows[:, :PREFIX_BYTES] = prefix.astype(">u8").view(np.uint8).reshape(n, 8)[:, 1:]
    blocks = [
        (r, rows[bounds[r] : bounds[r + 1]].tobytes())
        for r in range(reducers)
        if bounds[r + 1] > bounds[r]
    ]
    lead = np.ascontiguousarray(rows[:, key : key + LEAD_BYTES]).view("<u8").ravel()
    digests = [int(lead[bounds[r] : bounds[r + 1]].sum(dtype=np.uint64)) for r in range(reducers)]
    return blocks, np.diff(bounds), digests, checksum(rows)


def make_records(config: dict, seed: int) -> Records:
    """The output of the configuration's ``mappers`` mappers from ``seed``,
    made side by side on a few threads (numpy releases the interpreter lock on
    buffers of this size); the result does not depend on how many."""
    width, _, reducers = _check_config(config)
    num_mappers = int(config["mappers"])
    with ThreadPoolExecutor(max_workers=min(6, num_mappers)) as pool:
        made = list(pool.map(lambda m: _mapper(config, seed, m), range(num_mappers)))
    counts = np.zeros(reducers, dtype=np.int64)
    digests = [0] * reducers
    total = 0
    for _, mapper_counts, mapper_digests, mapper_checksum in made:
        counts += mapper_counts
        digests = [(a + b) & _MASK for a, b in zip(digests, mapper_digests)]
        total = (total + mapper_checksum) & _MASK
    expected = [(int(c), int(c) * width, digests[r]) for r, c in enumerate(counts)]
    return Records(config, [blocks for blocks, _, _, _ in made], expected, total)


class TaskCheck:
    """What a timed reduce task does with every batch, one vectorised pass:
    records, bytes, every key's prefix inside the task's range, and the sum of
    the 8 bytes after every key."""

    __slots__ = ("reference", "reduce_id", "lo", "hi", "records", "bytes", "digest", "misplaced")

    def __init__(self, reference: Records, reduce_id: int) -> None:
        self.reference = reference
        self.reduce_id = reduce_id
        self.lo, self.hi = (np.uint64(v) for v in range_of(reduce_id, reference.reducers))
        self.records = self.bytes = self.digest = self.misplaced = 0

    def add(self, batch: np.ndarray) -> None:
        prefix = prefixes(batch)
        self.misplaced += int(np.count_nonzero((prefix < self.lo) | (prefix >= self.hi)))
        self.records += len(batch)
        self.bytes += batch.size
        self.digest = (self.digest + lead_sum(batch, self.reference.key_bytes)) & _MASK

    def fail(self) -> None:
        """The task raised: whatever it had read, it failed."""
        self.misplaced += 1

    def ok(self) -> bool:
        want = self.reference.expected[self.reduce_id]
        return not self.misplaced and (self.records, self.bytes, self.digest) == want


class FullCheck(TaskCheck):
    """The warm-up job's consumer: the cheap check, and then the task's
    batches concatenated, sorted by the reference's own comparator and held
    byte for byte against the plain TeraSort's slice for the partition.  It
    keeps the batches as they were handed out (views of the received shards,
    alive while the shuffle is registered) until ``ok`` — off the job's clock,
    before the shuffle is removed — and notes what ``Records.complete``
    needs."""

    __slots__ = ("batches", "smallest", "largest", "checksum", "verdict")

    def __init__(self, reference: Records, reduce_id: int) -> None:
        super().__init__(reference, reduce_id)
        self.batches: List[np.ndarray] = []
        self.smallest: Optional[bytes] = None
        self.largest: Optional[bytes] = None
        self.checksum = 0
        self.verdict: Optional[bool] = None

    def add(self, batch: np.ndarray) -> None:
        super().add(batch)
        self.batches.append(batch)

    def ok(self) -> bool:
        if self.verdict is None:
            self.verdict = super().ok() and self._equals_the_plain_sort()
            self.batches = []
        return self.verdict

    def _equals_the_plain_sort(self) -> bool:
        width, key = self.reference.record_bytes, self.reference.key_bytes
        got = np.concatenate(self.batches) if self.batches else np.empty((0, width), dtype=np.uint8)
        got = sort_records(got)
        if len(got):
            self.smallest, self.largest = got[0, :key].tobytes(), got[-1, :key].tobytes()
            self.checksum = checksum(got)
        want = self.reference.sorted_partition(self.reduce_id)
        return got.shape == want.shape and bool(np.array_equal(got, want))


def geometry(config: dict, chips: int) -> dict:
    """What the job is to the store, from the layout alone (no value is made,
    so it is the same for every ``--seed``): bytes, records and blocks, the
    smallest and largest block and reduce task, and — for the store the
    configuration's ``store`` block describes (the default conf's staging
    buffer, block alignment and RAM budget of round buffers, on ``chips``
    executors with the reducers dealt to them in contiguous, balanced ranges)
    — the staging rounds a job takes and how many of its rollovers find the
    RAM budget full and take the disk tier.  A round rolls when the region of
    the block's owner cannot take the block, padded to the alignment."""
    width, _, reducers = _check_config(config)
    store = config["store"]
    align, region = int(store["alignment"]), int(store["staging_bytes"]) // chips
    block_records = np.stack([np.diff(layout(config, m)[1]) for m in range(int(config["mappers"]))])
    block_bytes = block_records * width
    base, extra = divmod(reducers, chips)
    owner = np.repeat(np.arange(chips), [base + (p < extra) for p in range(chips)])
    used = np.zeros(chips, dtype=np.int64)
    rounds = 1
    for row in block_bytes:
        for r in np.flatnonzero(row):
            padded = -(-int(row[r]) // align) * align
            if used[owner[r]] + padded > region:
                rounds += 1
                used[:] = 0
            used[owner[r]] += padded
    ram_rounds = int(store["ram_budget_bytes"]) // int(store["staging_bytes"])
    reducer_bytes = block_bytes.sum(axis=0)
    return {
        "job_bytes": int(block_bytes.sum()),
        "records": int(block_records.sum()),
        "blocks": int(np.count_nonzero(block_bytes)),
        "smallest_block_bytes": int(block_bytes[block_bytes > 0].min()),
        "largest_block_bytes": int(block_bytes.max()),
        "smallest_reducer_bytes": int(reducer_bytes.min()),
        "largest_reducer_bytes": int(reducer_bytes.max()),
        "rounds": rounds,
        "rollovers": rounds - 1,
        "rollovers_to_disk": max(0, rounds - 1 - ram_rounds),
    }
