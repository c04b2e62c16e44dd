"""Traffic driver: whole jobs from one client thread in the harness's own
process on ``TpuShuffleManager``, with a reduce side that is handed its
records **in key order**.

The harness, the map side and the batch-consuming ``Entry`` are
``manager-batchjobs``'s (loaded, not copied).  A reduce task is
``get_reader(sid, r, r + 1, deserializer=FixedWidthSerializer(record_bytes,
key_bytes), key_ordering=True).read_batches()`` drained into the reference's
check: one read-only ``(n, record_bytes)`` batch a task, all its records
sorted by key on the executor's chip over shards kept in HBM and brought to
the host in one D2H.  Sent by the shuffle of a ``sortByKey`` /
``repartitionAndSortWithinPartitions`` stage over fixed-width binary records
on an engine that keeps the shuffle in device memory and wants each partition
back sorted — TeraSort itself.

A program whose ``read_batches`` cannot order is refused in ``start``, before
any record is made.  Beyond what ``run.py`` decides ``correct`` on, a run is
unsound here when a block gather of another lowering than the platform's own
ran (``dma`` on the chip, ``xla`` on the CPU) and when, in any job, the
program's ``orderedread`` counters did not rise by exactly: one ``tasks`` a
reduce task, one ``sort_dispatches`` a non-empty reduce task (an order that
came from anywhere but the device's executable), the job's own ``records``,
and ``d2h_bytes`` of exactly the tasks' ``capacity_records`` x
``record_bytes`` (a sorted buffer that crossed to the host other than in its
one D2H would move other bytes).  The line ``sorted:`` prints the counters'
rise over the run, the readers' summed metrics and the fullest device's
``bytes_in_use`` after each removed job.
"""

from __future__ import annotations

import json
from typing import Dict, List

from benchmark.cells import load_module
from benchmark.jobs import run_window

batchjobs = load_module("traffic", "manager-batchjobs")

#: the ``orderedread`` family's counters
ORDERED = ("tasks", "records", "bytes", "capacity_records", "sort_dispatches", "d2h_bytes", "d2h_ns")


def require_ordered_read():
    """The program's fixed-width serializer class; exit at once on a program
    whose ``read_batches`` cannot order (``manager-devread``'s
    ``require_device_read`` for this traffic)."""
    serializer = batchjobs.require_batch_read()
    from sparkucx_tpu.transport.tpu import TpuShuffleCluster

    if not callable(getattr(TpuShuffleCluster, "ordered_read_stats", None)):
        raise SystemExit(
            "benchmark: traffic manager-sortedjobs needs read_batches() under key_ordering and the "
            "orderedread counters (TpuShuffleCluster.ordered_read_stats); this program has none"
        )
    return serializer


def ordered_counters(cluster) -> Dict[str, int]:
    """The ``orderedread`` family summed over the executors."""
    rows = cluster.ordered_read_stats()
    return {name: sum(int(row[name]) for row in rows) for name in ORDERED}


class Entry(batchjobs.Entry):
    """``manager-batchjobs``'s entry whose readers order, and which holds
    every job to the ``orderedread`` counters."""

    def __init__(self, manager, serializer, records) -> None:
        super().__init__(manager, serializer)
        self.records = records
        self.bytes_in_use: List[int] = []
        #: what was wrong with a job's counters, by shuffle
        self.miscounted: Dict[int, str] = {}
        self._mark = ordered_counters(manager.cluster)

    def read(self, shuffle_id: int, reduce_id: int, mappers: List[int], consume) -> int:
        reader = self.manager.get_reader(shuffle_id, reduce_id, reduce_id + 1,
                                         deserializer=self.serializer, key_ordering=True)
        for batch in reader.read_batches():
            consume(batch)
        metrics = reader.metrics
        for name in batchjobs.COUNTED:
            self.counted[name] += getattr(metrics, name)
        return sum(getattr(metrics, name) for name in batchjobs.shipped.FAULT_COUNTERS)

    def remove(self, shuffle_id: int) -> None:
        now = ordered_counters(self.manager.cluster)
        rose = {name: now[name] - self._mark[name] for name in ORDERED}
        self._mark = now
        records = self.records
        nonempty = sum(1 for n, _, _ in records.expected if n)
        want = {"tasks": records.reducers, "sort_dispatches": nonempty, "records": records.total_records,
                "bytes": records.total_bytes, "d2h_bytes": rose["capacity_records"] * records.record_bytes}
        wrong = {name: (rose[name], value) for name, value in want.items() if rose[name] != value}
        if wrong:
            self.miscounted[shuffle_id] = ", ".join(f"{k} rose {a}, not {b}" for k, (a, b) in wrong.items())
        super().remove(shuffle_id)
        devices = self.manager.cluster.mesh.devices.reshape(-1)
        self.bytes_in_use.append(max(int((d.memory_stats() or {}).get("bytes_in_use", 0)) for d in devices))


class Traffic(batchjobs.Traffic):
    def start(self, conf, parts: dict):
        require_ordered_read()
        return super().start(conf, parts)

    def entry(self) -> Entry:
        config = self.cell.config
        return Entry(self.manager, self.serializer_class(config["record_bytes"], config["key_bytes"]), self.records)

    def run(self, control, parts: dict):
        cluster = self.manager.cluster
        before = ordered_counters(cluster)
        entry = self.entry()
        window = run_window(entry, self.records, self.args.seconds, bool(self.args.trace), control)
        after = ordered_counters(cluster)
        platform = cluster.mesh.devices.reshape(-1)[0].platform
        want = "dma" if platform == "tpu" else "xla"
        ran = sorted(set(cluster.executed_lowerings()["gather"]))
        jobs = len(window.jobs) + 1  # and the warm-up job
        unsound = [f"shuffle {sid}: {why}" for sid, why in sorted(entry.miscounted.items())]
        if ran != [want]:
            unsound.append(f"gather lowering {ran}, not [{want!r}]")
        print("sorted: " + json.dumps({
            "jobs_read": jobs, "gather": ran, "expected": want,
            "orderedread": {name: after[name] - before[name] for name in ORDERED},
            **entry.counted, "records_a_job": entry.counted["records_read"] / jobs,
            "bytes_in_use_after_job": entry.bytes_in_use, "unsound": unsound,
        }), flush=True)
        if unsound:
            window.warmup.failed += 1  # the one way a driver has to say: not this run
        return window
