"""Traffic driver: ``manager-sortedjobs``' job as its executor runs it — the
configuration's ``task_slots`` task slots over ONE ``TpuShuffleManager`` in
the harness's own process, map tasks and then ordered reads running side by
side.

The harness, the records, ``manager-sortedjobs``' ``Entry`` (its map side, its
ordered ``read``, its hold of every job to the ``orderedread`` counters) and
the reference's checks are loaded, not copied.  What is new is who calls them:

* **the driver** (Spark's driver; the harness's main thread) owns the job's
  clock and the stage barrier, and calls ``create``, ``exchange`` and
  ``remove`` and nothing else;
* **``task_slots`` slots**, each a thread of this process with an ``Entry`` of
  its own and one task at a time — the in-process entry point's own truth (a
  Python engine's worker threads, a JVM executor's task threads): the slots'
  Python shares one interpreter lock; the block copies, the transfers, the
  chip and NumPy's passes over a batch do not.

A job: create -> **map stage**, the map tasks in index order, each handed to
the slot that frees first (``get_writer(sid, m)``, one stream a non-empty
reducer, commit, byte count checked) -> **barrier** -> ``run_exchange`` ->
**reduce stage**, the reduce tasks in reducer order, each to the slot that
frees first: ``get_reader(sid, r, r + 1, deserializer=FixedWidthSerializer(
record_bytes, key_bytes), key_ordering=True).read_batches()`` drained into the
reference's check on the slot's own thread -> remove.  Closed loop; every
block is read once.  The warm-up job runs at the same depth, so the one job
compared byte for byte runs what the timed jobs run.

Spans, as ``daemon-tasks`` records them: ``job.write`` (first map task handed
out -> last commit returned), ``job.exchange`` (``run_exchange``), ``job.read``
(-> last record consumed); ``task.map`` / ``task.reduce``, one a task on its
slot's clock; ``job.slot``, one a slot and job over the job's interval.

The window is ``benchmark.jobs.run_window``'s (warm-up job compared in full,
``gc.freeze``, whole jobs for ``--seconds``, two traced jobs from the middle)
over this driver's ``run_job``.  Beyond ``manager-sortedjobs``' rules a run is
unsound when, in any job, the driver's own count of tasks handed out and not
yet returned never reached the slots (or the stage's tasks, where those are
fewer) in either stage: that job was not this traffic.  Where the program has
the ``orderedread`` gauges of tasks in flight they are printed, and a job that
leaves one in flight or a peak over the slots is unsound too; a program without
them is refused nothing.  Lines: ``sorted:`` as ``manager-sortedjobs`` prints
it, and ``tasks:`` — the depth each stage reached, the in-flight counters, the
stores' early and seal puts a job and the landing pool's hits and misses.
"""

from __future__ import annotations

import gc
import json
import queue
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

from benchmark.cells import load_module
from benchmark.jobs import TRACED_JOBS, JobResult, WindowResult
from benchmark.spans import Span

sortedjobs = load_module("traffic", "manager-sortedjobs")
batchjobs = sortedjobs.batchjobs
now = time.perf_counter_ns
#: the ``orderedread`` family's statement of tasks in flight (printed where the
#: program has it)
IN_FLIGHT = ("in_flight", "in_flight_peak", "in_flight_device_bytes_peak")
#: counters of the stores' ``write_stats()`` the ``tasks:`` line reports
PUTS = ("early_put_pieces", "seal_put_pieces", "early_put_dropped")


def run_task(entry, records, op: str, shuffle_id: int, index: int, check) -> dict:
    """One map or reduce task on its slot's thread and clock.  A task that
    raises is a failed task (a reduce task's check is failed; the comparison
    itself is the driver's, off the job's clock); the job goes on."""
    ok, faults = True, 0
    t0 = now()
    try:
        if op == "map":
            entry.write_map(shuffle_id, index, records.blocks[index])
        else:
            faults = entry.read(shuffle_id, index, records.mappers_of(index), check.add)
    except Exception as e:  # task boundary: count it, name it, go on
        ok = False
        if check is not None:
            check.fail()
        print(f"{op} task {index} of shuffle {shuffle_id}: {type(e).__name__}: {e}", flush=True)
    return {"ok": ok, "faults": faults, "t0": t0, "t1": now()}


class Slots:
    """The task slots: threads of this process, an entry each.  ``run_stage``
    hands every task of a stage, in order, to the slot that frees first."""

    def __init__(self, entries: list, records) -> None:
        self.entries = entries
        self.records = records
        self.done: "queue.SimpleQueue" = queue.SimpleQueue()
        self.inboxes = [queue.SimpleQueue() for _ in entries]
        self.free: deque = deque(range(len(entries)))  # the slot that freed first at the left
        self.threads = [threading.Thread(target=self._serve, args=(k,), name=f"task-slot-{k}", daemon=True)
                        for k in range(len(entries))]
        for thread in self.threads:
            thread.start()

    def _serve(self, k: int) -> None:
        entry, inbox = self.entries[k], self.inboxes[k]
        while True:
            task = inbox.get()
            if task is None:
                return
            self.done.put((k, run_task(entry, self.records, *task)))

    def run_stage(self, tasks: List[tuple]) -> Tuple[List[dict], int]:
        """Every task of one stage; returns their replies in task order and
        the most tasks that were handed out and not yet returned at once."""
        replies: List[Optional[dict]] = [None] * len(tasks)
        running: Dict[int, int] = {}
        at = peak = 0
        while at < len(tasks) or running:
            while at < len(tasks) and self.free:
                k = self.free.popleft()
                running[k] = at
                self.inboxes[k].put(tasks[at])
                at += 1
            peak = max(peak, len(running))
            k, reply = self.done.get()
            replies[running.pop(k)] = reply
            self.free.append(k)
        return replies, peak

    def close(self) -> None:
        for inbox in self.inboxes:
            inbox.put(None)
        for thread in self.threads:
            thread.join(timeout=10)


class Executor:
    """What the driver runs a job on: its own entry for the stage boundaries,
    the slots, the records."""

    def __init__(self, entry, slots: Slots, records, task_slots: int) -> None:
        self.entry = entry
        self.slots = slots
        self.records = records
        #: what the configuration says: a stage has to reach it
        self.task_slots = task_slots
        self.spans: List[Span] = []
        #: (map stage, reduce stage) depth reached, a job
        self.depths: List[Tuple[int, int]] = []
        #: what was wrong with a job's depth, by shuffle
        self.shallow: Dict[int, str] = {}

    def run_job(self, shuffle_id: int, full: bool = False) -> JobResult:
        """One whole job on the driver's clock, first map task handed out to
        last record consumed."""
        records, slots = self.records, self.slots
        mappers, reducers = records.num_mappers, records.reducers
        self.entry.create(shuffle_id, mappers, reducers)
        t_job = now()
        maps, map_depth = slots.run_stage([("map", shuffle_id, m, None) for m in range(mappers)])
        t_barrier = now()  # the last commit has returned: the exchange may run
        self.entry.exchange(shuffle_id)
        t_exchanged = now()
        checks = [records.check(r, full) for r in range(reducers)]
        reduces, reduce_depth = slots.run_stage([("reduce", shuffle_id, r, checks[r]) for r in range(reducers)])
        t_end = now()
        self.spans += [("job.write", t_job, t_barrier), ("job.exchange", t_barrier, t_exchanged),
                       ("job.read", t_exchanged, t_end)]
        self.spans += [("job.slot", t_job, t_end)] * len(slots.entries)
        for name, stage in (("task.map", maps), ("task.reduce", reduces)):
            self.spans += [(name, r["t0"], r["t1"]) for r in stage]
        # the comparison is off the job's clock
        failed = sum(not r["ok"] for r in maps) + sum(not c.ok() for c in checks)
        if full and not failed and not records.complete(checks):
            failed = 1
        self.depths.append((map_depth, reduce_depth))
        want = (min(self.task_slots, mappers), min(self.task_slots, reducers))
        if (map_depth, reduce_depth) != want:
            self.shallow[shuffle_id] = f"tasks in flight reached {map_depth} / {reduce_depth}, not {want[0]} / {want[1]}"
        return JobResult((t_end - t_job) / 1e9, mappers + reducers, failed, sum(r["faults"] for r in reduces),
                         [(r["t1"] - r["t0"]) / 1e9 for r in reduces])


def run_window(executor: Executor, seconds: float, trace: bool, control: Callable[..., Dict]) -> WindowResult:
    """``benchmark.jobs.run_window`` over ``executor.run_job``: the same
    warm-up, control events and choice of traced jobs."""
    records = executor.records
    out = WindowResult(job_bytes=records.total_bytes, job_blocks=records.num_blocks)
    shuffle_id = 0

    def finish(sid: int) -> None:
        control("job_done", shuffle_id=sid)
        executor.entry.remove(sid)

    t0 = time.perf_counter()
    out.warmup = executor.run_job(shuffle_id, full=True)
    finish(shuffle_id)
    out.warmup_s = time.perf_counter() - t0
    gc.freeze()  # the records and the reference live as long as the run
    executor.spans.clear()
    control("window_start")
    t_window = time.perf_counter()
    traced = []  # (index among the jobs, start ns, end ns) of the jobs in the session
    while True:
        elapsed = time.perf_counter() - t_window
        untraced = trace and len(traced) < TRACED_JOBS
        if elapsed >= seconds and out.jobs and not untraced:
            break
        shuffle_id += 1
        # the profiler takes whole jobs from the middle of the window
        tracing = bool(untraced and out.jobs and (traced or elapsed >= seconds / 2))
        if tracing and not traced:
            control("trace_start")
        t0 = now()
        out.jobs.append(executor.run_job(shuffle_id))
        if tracing:
            traced.append((len(out.jobs) - 1, t0, now()))
            if len(traced) == TRACED_JOBS:
                control("trace_stop")
        finish(shuffle_id)
    control("window_end")
    if traced:
        index, lo, hi = min(traced, key=lambda job: out.jobs[job[0]].seconds)
        out.traced_job, out.traced_ns = index, [lo, hi]
    out.spans = list(executor.spans)
    return out


def in_flight_counters(cluster) -> Optional[Dict[str, int]]:
    """The ``orderedread`` family's tasks in flight, over the executors (the
    gauge summed, the peaks' largest); ``None`` on a program without them."""
    rows = cluster.ordered_read_stats()
    if not all(name in row for row in rows for name in IN_FLIGHT):
        return None
    return {"in_flight": sum(int(row["in_flight"]) for row in rows),
            **{name: max(int(row[name]) for row in rows) for name in IN_FLIGHT[1:]}}


class Traffic(sortedjobs.Traffic):
    slots: Optional[Slots] = None

    def run(self, control, parts: dict):
        cluster = self.manager.cluster
        task_slots = int(self.cell.config["task_slots"])
        entry = self.entry()
        self.slots = Slots([self.entry() for _ in range(task_slots)], self.records)
        executor = Executor(entry, self.slots, self.records, task_slots)
        before = sortedjobs.ordered_counters(cluster)
        stores = [t.store for t in cluster.transports]
        puts_before = [s.write_stats() for s in stores]
        unsound: List[str] = []
        in_flight_after: List[int] = []

        def watching(event: str, **fields):
            # between a job's last task and its removal: nothing is in flight
            counted = in_flight_counters(cluster) if event == "job_done" else None
            if counted is not None:
                in_flight_after.append(counted["in_flight"])
                if counted["in_flight"] or counted["in_flight_peak"] > task_slots:
                    unsound.append(f"shuffle {fields['shuffle_id']}: ordered reads in flight {counted}")
            return control(event, **fields)

        window = run_window(executor, self.args.seconds, bool(self.args.trace), watching)
        after = sortedjobs.ordered_counters(cluster)
        platform = cluster.mesh.devices.reshape(-1)[0].platform
        want = "dma" if platform == "tpu" else "xla"
        ran = sorted(set(cluster.executed_lowerings()["gather"]))
        jobs = len(window.jobs) + 1  # and the warm-up job
        unsound += [f"shuffle {sid}: {why}" for found in (entry.miscounted, executor.shallow)
                    for sid, why in sorted(found.items())]
        if ran != [want]:
            unsound.append(f"gather lowering {ran}, not [{want!r}]")
        counted = {name: sum(slot.counted[name] for slot in self.slots.entries) for name in batchjobs.COUNTED}
        print("sorted: " + json.dumps({
            "jobs_read": jobs, "gather": ran, "expected": want,
            "orderedread": {name: after[name] - before[name] for name in sortedjobs.ORDERED},
            **counted, "records_a_job": counted["records_read"] / jobs,
            "bytes_in_use_after_job": entry.bytes_in_use, "unsound": unsound,
        }), flush=True)
        landing = getattr(cluster, "_landing", lambda: None)()
        puts_after = [s.write_stats() for s in stores]
        print("tasks: " + json.dumps({
            "jobs": len(window.jobs), "task_slots": task_slots,
            "depth_reached": {"map": sorted({m for m, _ in executor.depths}),
                              "reduce": sorted({r for _, r in executor.depths})},
            "in_flight": in_flight_counters(cluster), "in_flight_after_job": sorted(set(in_flight_after)),
            "puts_a_job": {name: sum(a.get(name, 0) - b.get(name, 0) for a, b in zip(puts_after, puts_before)) / jobs
                           for name in PUTS},
            "landing": {k: v for k, v in landing.stats().items() if k in ("hits", "misses", "held_bytes")}
            if landing is not None else None,
        }), flush=True)
        if unsound:
            window.warmup.failed += 1  # the one way a driver has to say: not this run
        return window

    def close(self) -> None:
        if self.slots is not None:
            self.slots.close()
        super().close()
