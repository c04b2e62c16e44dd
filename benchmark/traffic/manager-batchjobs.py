"""Traffic driver: whole jobs from one client thread in the harness's own
process on ``TpuShuffleManager``, with a reduce side that consumes record
batches.

The harness and the map side are ``manager-jobs``'s (its ``Entry`` is reused
for ``create`` / ``write_map`` / ``exchange`` / ``remove``).  A reduce task is
``get_reader(sid, r, r + 1, deserializer=FixedWidthSerializer(record_bytes,
key_bytes)).read_batches()`` — the configuration names the record's width, as
Spark's ``ShuffleDependency.serializer`` does — drained a batch at a time (one
block: a read-only ``(n, record_bytes)`` array) into the reference's check;
never a record at a time.  Sent by the shuffle of a ``sortByKey`` /
``repartitionAndSortWithinPartitions`` stage over fixed-width binary records
through a Python / Arrow engine.

A program whose reader has no batch read is refused in ``start``, before any
record is made.  The line ``batchread:`` gives, summed over every reduce task
of the run, what the readers' metrics counted (``records_read``,
``record_batches``, ``resident_blocks``, ``copied_blocks``), each store's
``write_stats()`` for the tiers (``ram_rounds``, ``recycled_rounds``,
``spilled_bytes``, the free list) and the harness's memory.
"""

from __future__ import annotations

import json
import resource
from typing import List

from benchmark.cells import load_module
from benchmark.jobs import run_window

shipped = load_module("traffic", "manager-jobs")

#: what the line ``batchread:`` sums of every reader's metrics
COUNTED = ("records_read", "record_batches", "remote_blocks_fetched", "resident_blocks", "copied_blocks")
#: what it prints of each store's ``write_stats()``
TIERS = ("rollovers", "ram_rounds", "recycled_rounds", "spilled_bytes", "pool_hits", "pool_misses",
         "pool_dropped_busy", "pool_held_bytes")


def require_batch_read():
    """The program's fixed-width serializer class; exit at once on a program
    that cannot run this traffic."""
    from sparkucx_tpu.shuffle import reader

    serializer = getattr(reader, "FixedWidthSerializer", None)
    if serializer is None or not callable(getattr(reader.TpuShuffleReader, "read_batches", None)):
        raise SystemExit(
            "benchmark: traffic manager-batchjobs needs shuffle.reader.FixedWidthSerializer and "
            "TpuShuffleReader.read_batches(); this program has none"
        )
    return serializer


def mem_available_gb() -> float:
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return round(int(line.split()[1]) / 1e6, 3)
    except (OSError, ValueError, IndexError):
        pass
    return -1.0


class Entry(shipped.Entry):
    """``TpuShuffleManager`` in the client's own process; reduce tasks read
    record batches."""

    def __init__(self, manager, serializer) -> None:
        super().__init__(manager)
        self.serializer = serializer
        self.counted = dict.fromkeys(COUNTED, 0)

    def read(self, shuffle_id: int, reduce_id: int, mappers: List[int], consume) -> int:
        """Drain one reduce task into ``consume(batch)``; returns the fetches
        that were retried, failed over or timed out."""
        reader = self.manager.get_reader(shuffle_id, reduce_id, reduce_id + 1, deserializer=self.serializer)
        for batch in reader.read_batches():
            consume(batch)
        metrics = reader.metrics
        for name in COUNTED:
            self.counted[name] += getattr(metrics, name)
        return sum(getattr(metrics, name) for name in shipped.FAULT_COUNTERS)


class Traffic(shipped.Traffic):
    def start(self, conf, parts: dict):
        self.serializer_class = require_batch_read()
        manager = super().start(conf, parts)
        parts["mem_available_gb"] = mem_available_gb()  # what the stores' RAM budget was taken from
        return manager

    def run(self, control, parts: dict):
        config = self.cell.config
        entry = Entry(self.manager, self.serializer_class(config["record_bytes"], config["key_bytes"]))
        window = run_window(entry, self.records, self.args.seconds, bool(self.args.trace), control)
        jobs = len(window.jobs) + 1  # and the warm-up job
        print("batchread: " + json.dumps({
            "jobs_read": jobs, **entry.counted,
            "records_a_job": entry.counted["records_read"] / jobs,
            "stores": [{name: t.store.write_stats()[name] for name in TIERS}
                       for t in self.manager.cluster.transports],
            "harness_rss_peak_gb": round(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6, 3),
            "mem_available_gb": mem_available_gb(),
        }), flush=True)
        return window
