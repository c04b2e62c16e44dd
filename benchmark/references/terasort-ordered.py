"""TeraSort's ordered return: the records, partitioner and plain TeraSort of
``terasort.py`` (imported, not copied) with a reduce side that is handed its
range **already in key order**.

Source job: ``ehiggs/spark-terasort``'s ``repartitionAndSortWithinPartitions``
after ``TeraSortPartitioner`` — the reduce side of that call *is* the ordered
return: a reduce task gets its partition's records sorted by their 10-byte
key, compared as unsigned bytes, most significant first
(sortbenchmark.org).  Here the program sorts and this reference only looks:

* a **timed** reduce task checks, in one vectorised pass over each batch it is
  handed, ``terasort.TaskCheck``'s four (records, bytes, every key's prefix
  inside the task's range, the sum of the 8 bytes after every key) **and that
  every key is >= the key before it over all ``key_bytes`` bytes**, from one
  batch to the next too;
* the **warm-up** job holds each task's batches *as handed out* — never sorted
  again here — byte for byte against the plain TeraSort's slice for the
  partition (``terasort.Records.sorted_partition``: by key, ties by the
  value's bytes).  The program owes no order among records of one key, so the
  records inside a run of equal keys are compared as a multiset; then
  TeraValidate's three over the job.

Every limit is 0.  Nothing here imports the code under test.
"""

from __future__ import annotations

from math import lcm
from typing import List, Optional

import numpy as np

from benchmark.cells import load_module

terasort = load_module("references", "terasort")


def record_fields(width: int, key_bytes: int) -> np.dtype:
    """A record as the check reads it, one field a number: ``high`` the key's
    first eight bytes big-endian, ``low`` the key bytes after them big-endian,
    ``lead`` the eight bytes after the key.  NumPy walks a field of a
    ``width``-byte record in one strided pass (1.0–1.6 ms over 340,000 records
    where a copy of the same columns out of the ``uint8`` array takes 1.8–4.0)."""
    tail = key_bytes - 8
    if tail not in (0, 1, 2, 4, 8):
        raise ValueError(f"a key of {key_bytes} bytes: the bytes after the eighth are no integer's")
    fields = {"high": (">u8", 0), "lead": ("<u8", key_bytes)}
    if tail:
        fields["low"] = (f">u{tail}", 8)
    return np.dtype({"names": list(fields), "formats": [f for f, _ in fields.values()],
                     "offsets": [o for _, o in fields.values()], "itemsize": width})


def as_records(rows: np.ndarray, key_bytes: int) -> np.ndarray:
    """``rows`` (``(n, width)`` ``uint8``) seen through ``record_fields``."""
    rows = np.ascontiguousarray(rows)
    return rows.view(record_fields(rows.shape[1], key_bytes)).ravel()


def falls(records: np.ndarray, high: Optional[np.ndarray] = None) -> int:
    """Records whose key is smaller than the key before them, over all the
    key's bytes as unsigned bytes: where the first eight bytes fall, and —
    only where they are equal — where the bytes after them do.  ``high`` is
    ``records["high"]`` in native order where the caller has it already."""
    if high is None:
        high = records["high"].astype(np.uint64)
    down = int(np.count_nonzero(high[1:] < high[:-1]))
    if "low" in records.dtype.names:
        ties = np.flatnonzero(high[1:] == high[:-1])
        low = records["low"]
        down += int(np.count_nonzero(low[ties + 1] < low[ties]))
    return down


def out_of_order(rows: np.ndarray, key_bytes: int) -> int:
    """``falls`` of a batch: how far it is from non-decreasing key order."""
    return falls(as_records(rows, key_bytes))


class OrderedTaskCheck(terasort.TaskCheck):
    """``terasort.TaskCheck``'s four, and the order of the keys, from the
    record's fields: one strided pass a number over a batch of 34 MB (the
    check reads 18 bytes a record)."""

    __slots__ = ("unordered", "last_key")

    def __init__(self, reference, reduce_id: int) -> None:
        super().__init__(reference, reduce_id)
        self.unordered = 0
        self.last_key: Optional[bytes] = None

    def add(self, batch: np.ndarray) -> None:
        key = self.reference.key_bytes
        records = as_records(batch, key)
        high = records["high"].astype(np.uint64)
        prefix = high >> np.uint64(8)
        self.misplaced += int(np.count_nonzero((prefix < self.lo) | (prefix >= self.hi)))
        self.records += len(batch)
        self.bytes += batch.size
        self.digest = (self.digest + int(records["lead"].sum(dtype=np.uint64))) & terasort._MASK
        if len(batch):
            first = batch[0, :key].tobytes()
            # from one batch to the next too (``bytes`` compare as the keys do)
            self.unordered += falls(records, high) + (self.last_key is not None and first < self.last_key)
            self.last_key = batch[-1, :key].tobytes()

    def ok(self) -> bool:
        return not self.unordered and super().ok()


class OrderedFullCheck(OrderedTaskCheck):
    """The warm-up job's consumer: the timed check, and then the batches as
    they were handed out held byte for byte against the plain TeraSort's slice
    for the partition — off the job's clock, before the shuffle is removed —
    and what ``Records.complete`` needs."""

    __slots__ = ("batches", "smallest", "largest", "checksum", "verdict")

    def __init__(self, reference, reduce_id: int) -> None:
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
            self.verdict = super().ok() and self._is_the_plain_sorts_slice()
            self.batches = []
        return self.verdict

    def _is_the_plain_sorts_slice(self) -> bool:
        width, key = self.reference.record_bytes, self.reference.key_bytes
        got = np.concatenate(self.batches) if self.batches else np.empty((0, width), dtype=np.uint8)
        if len(got):
            self.smallest, self.largest = got[0, :key].tobytes(), got[-1, :key].tobytes()
            self.checksum = terasort.checksum(got)
        want = self.reference.sorted_partition(self.reduce_id)
        if got.shape != want.shape:
            return False
        if np.array_equal(got, want):
            return True
        # the same keys in the same places, and inside a run of equal keys the
        # same records in another order: the reference's comparator (ties by
        # the value's bytes) puts such a hand-out into the plain sort's order
        same_keys = np.array_equal(got[:, :key], want[:, :key])
        return bool(same_keys and np.array_equal(terasort.sort_records(got), want))


class Records(terasort.Records):
    """``terasort.Records`` whose consumers expect the ordered return."""

    def check(self, reduce_id: int, full: bool = False) -> OrderedTaskCheck:
        return (OrderedFullCheck if full else OrderedTaskCheck)(self, reduce_id)


def make_records(config: dict, seed: int) -> Records:
    """``terasort.make_records``: the same records from the same seed."""
    made = terasort.make_records(config, seed)
    return Records(config, made.blocks, made.expected, made.checksum)


def geometry(config: dict, chips: int) -> dict:
    """``terasort.geometry`` for the store the configuration's ``store`` block
    describes, and what the ordered return adds, from the layout alone: the
    smallest and largest reduce task in records, and the record places a task
    is sorted at when every block starts on a boundary where store rows
    (``alignment`` bytes) and records meet — ``lcm(record_bytes, alignment)``
    bytes, a *slot* — so that no record straddles a row it shares with another
    block: the largest task's blocks, each rounded up to whole slots."""
    width, _, reducers = terasort._check_config(config)
    out = terasort.geometry(config, chips)
    slot_records = lcm(width, int(config["store"]["alignment"])) // width
    block_records = np.stack([np.diff(terasort.layout(config, m)[1]) for m in range(int(config["mappers"]))])
    task_records = block_records.sum(axis=0)
    places = (-(-block_records // slot_records) * slot_records).sum(axis=0)
    out.update({
        "smallest_reducer_records": int(task_records.min()),
        "largest_reducer_records": int(task_records.max()),
        "slot_records": slot_records,
        "sort_capacity_records": int(places.max()),
    })
    return out
