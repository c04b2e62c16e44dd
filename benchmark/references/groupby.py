"""GroupByTest records and the plain GroupBy they are checked against.

Source job: ``org.apache.spark.examples.GroupByTest <mappers> <pairs>
<value_bytes> <reducers>`` — every mapper emits ``pairs`` (random int key,
``value_bytes`` random bytes) records, hash-partitioned over ``reducers``
(Spark's HashPartitioner on a non-negative int: ``key mod reducers``), all
kept.  Nothing here imports the code under test: the records are laid out in
the typed record codec's wire format by hand (``t <u32 2> i <i64 key> b <u32
len> <value>``, big-endian), and the reference is taken from the generated
arrays, never from what a shuffle returned.
"""

from __future__ import annotations

import zlib
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np

#: bytes before the value in one serialized (int key, bytes value) record
HEADER_BYTES = 19
#: the generator's stream for the block layout, apart from every ``--seed``'s
LAYOUT_DRAW = 0
_MASK = (1 << 64) - 1


def record_bytes(value_bytes: int) -> int:
    return HEADER_BYTES + value_bytes


@dataclass
class Records:
    """One job's map output and what a correct shuffle of it returns."""

    reducers: int
    #: blocks[m] = [(reduce_id, serialized records)] for the non-empty
    #: reducers of mapper m, in reducer order
    blocks: List[List[Tuple[int, bytes]]]
    #: per reducer: records, value bytes, sum of the first 8 bytes of every
    #: value (little-endian, mod 2**64) — what every timed reduce task checks
    expected: List[Tuple[int, int, int]]
    #: the plain GroupBy: key -> crc32 of every value under it (the full check)
    groups: Dict[int, List[int]]

    @property
    def num_mappers(self) -> int:
        return len(self.blocks)

    @property
    def num_blocks(self) -> int:
        return sum(len(parts) for parts in self.blocks)

    @property
    def total_bytes(self) -> int:
        return sum(len(payload) for parts in self.blocks for _, payload in parts)

    def mappers_of(self, reduce_id: int) -> List[int]:
        """Mappers that wrote a non-empty block for this reducer (what Spark's
        MapStatus tells a reduce task)."""
        return self._mappers_of[reduce_id]

    def check(self, reduce_id: int, full: bool = False) -> "TaskCheck":
        """The consumer of one reduce task's records."""
        return (FullCheck if full else TaskCheck)(self, reduce_id)

    def complete(self, checks: List["FullCheck"]) -> bool:
        """Every key of the reference surfaced in the task it hashes to (the
        tasks of the job's full read, each already ``ok``)."""
        return sum(len(c.groups) for c in checks) == len(self.groups)

    def __post_init__(self) -> None:
        self._mappers_of: List[List[int]] = [[] for _ in range(self.reducers)]
        for m, parts in enumerate(self.blocks):
            for r, _ in parts:
                self._mappers_of[r].append(m)


def _mapper(config: dict, seed: int, m: int):
    """One mapper's blocks and its part of the reference: one rng draw for the
    keys, one for the values, no per-record Python except the crc32.  Records
    are grouped by reducer; inside a block they keep the order drawn."""
    pairs = int(config["pairs_per_mapper"])
    vbytes = int(config["value_bytes"])
    reducers = int(config["reducers"])
    width = record_bytes(vbytes)
    # Which reducer each record goes to is one fixed draw, the same for every
    # ``--seed``: block sizes are ragged as the source's are, and every run
    # stages the same rows in the same rounds (on four chips which peer region
    # fills first decides whether a job takes 9 rounds or 10, PERF.md section
    # 6).  The rest of a key and every value come from ``--seed``.
    layout = np.random.default_rng([LAYOUT_DRAW, m])
    part = np.sort(layout.integers(0, reducers, size=pairs))
    rng = np.random.default_rng([seed, m])
    keys = rng.integers(0, (2**31 - 1) // reducers, size=pairs, dtype=np.int64) * reducers + part
    # the random words fill whole records; the 19 bytes before each value are
    # then overwritten with the codec's framing, so nothing is copied twice
    words = rng.integers(0, 2**64, size=-(-pairs * width // 8), dtype=np.uint64)
    rows = words.view(np.uint8)[: pairs * width].reshape(pairs, width)
    rows[:, :6] = np.frombuffer(b"t" + (2).to_bytes(4, "big") + b"i", dtype=np.uint8)
    rows[:, 6:14] = keys.astype(">i8").view(np.uint8).reshape(pairs, 8)
    rows[:, 14:HEADER_BYTES] = np.frombuffer(b"b" + vbytes.to_bytes(4, "big"), dtype=np.uint8)

    bounds = np.searchsorted(part, np.arange(reducers + 1))
    blocks = [
        (r, rows[bounds[r] : bounds[r + 1]].tobytes())
        for r in range(reducers)
        if bounds[r + 1] > bounds[r]
    ]
    first8 = np.zeros((pairs, 8), dtype=np.uint8)
    first8[:, : min(8, vbytes)] = rows[:, HEADER_BYTES : HEADER_BYTES + 8]
    lead = first8.view("<u8").reshape(pairs)
    digests = {
        int(r): int(lead[bounds[r] : bounds[r + 1]].sum(dtype=np.uint64))
        for r in np.flatnonzero(np.diff(bounds))
    }
    crcs = [zlib.crc32(rows[i, HEADER_BYTES:]) for i in range(pairs)]
    return blocks, np.diff(bounds), digests, keys.tolist(), crcs


def make_records(config: dict, seed: int) -> Records:
    """The output of the configuration's ``mappers`` mappers from ``seed``.
    Mappers are made side by side on a few threads (numpy and zlib release the
    interpreter lock on buffers of this size); the result does not depend on
    how many."""
    if config["keys"] != "uniform-int31":
        raise ValueError(f"unknown key distribution {config['keys']!r}")
    num_mappers = int(config["mappers"])
    reducers = int(config["reducers"])
    vbytes = int(config["value_bytes"])
    with ThreadPoolExecutor(max_workers=min(8, num_mappers)) as pool:
        made = list(pool.map(lambda m: _mapper(config, seed, m), range(num_mappers)))
    blocks: List[List[Tuple[int, bytes]]] = []
    groups: Dict[int, List[int]] = {}
    counts = np.zeros(reducers, dtype=np.int64)
    digests = [0] * reducers
    for mapper_blocks, mapper_counts, mapper_digests, keys, crcs in made:
        blocks.append(mapper_blocks)
        counts += mapper_counts
        for r, digest in mapper_digests.items():
            digests[r] = (digests[r] + digest) & _MASK
        for key, crc in zip(keys, crcs):
            groups.setdefault(key, []).append(crc)
    expected = [(int(c), int(c) * vbytes, digests[r]) for r, c in enumerate(counts)]
    return Records(reducers, blocks, expected, groups)


class TaskCheck:
    """What a timed reduce task does with every record, in the stream: count,
    bytes, the partition rule and a cheap digest — no crc32 on the clock."""

    __slots__ = ("reference", "reduce_id", "reducers", "records", "bytes", "digest", "misplaced")

    def __init__(self, reference: Records, reduce_id: int) -> None:
        self.reference = reference
        self.reduce_id = reduce_id
        self.reducers = reference.reducers
        self.records = self.bytes = self.digest = self.misplaced = 0

    def add(self, key: int, value) -> None:
        if key % self.reducers != self.reduce_id:
            self.misplaced += 1
        self.records += 1
        self.bytes += len(value)
        self.digest = (self.digest + int.from_bytes(value[:8], "little")) & _MASK

    def fail(self) -> None:
        """The task raised: whatever it had read, it failed."""
        self.misplaced += 1

    def ok(self) -> bool:
        want = self.reference.expected[self.reduce_id]
        return not self.misplaced and (self.records, self.bytes, self.digest) == want


class FullCheck(TaskCheck):
    """The warm-up job's consumer: the cheap check plus the plain GroupBy's
    own comparison — group count and a crc32 of every value under its key."""

    __slots__ = ("groups",)

    def __init__(self, reference: Records, reduce_id: int) -> None:
        super().__init__(reference, reduce_id)
        self.groups: Dict[int, List[int]] = {}

    def add(self, key: int, value) -> None:
        super().add(key, value)
        self.groups.setdefault(key, []).append(zlib.crc32(value))

    def ok(self) -> bool:
        if not super().ok():
            return False
        want = self.reference.groups
        return all(sorted(crcs) == sorted(want.get(key, ())) for key, crcs in self.groups.items())
