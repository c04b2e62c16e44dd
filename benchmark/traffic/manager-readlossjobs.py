"""Traffic driver: whole jobs from one client thread in the harness's own
process on ``TpuShuffleManager``, each of which loses an executor AFTER its
exchange, under its reduce stage.

The harness, the map side and the record loop of a reduce task are
``manager-jobs``'s (its ``Entry`` is loaded, not copied; ``rss_gb`` is
``manager-lossjobs``'s).  What differs: ``exchange`` runs
``manager.run_exchange`` whole and then kills the traffic file's
``lost_executor`` with the program's own ``faults.kill_executor`` — before the
first reduce task, so every task of the partitions it had received is still to
run — and ``read`` asks for a task's reader where an engine's scheduler would
run the task: on the partition's owner where that lives
(``get_reader(sid, r, r + 1)``), on ``survivors[r mod len(survivors)]`` where
it does not (``get_reader(sid, r, r + 1, executor_id=e)``).  After the
shuffle's removal the executor rejoins, before the next ``create``.  Sent by a
Spark stage on preemptible or restarted TPU-VM workers with the shuffle
service's replication on, which loses an executor while its reduce stage runs.

``read`` returns the reader's fault counters as ``manager-jobs`` does, so the
benchmark's ``fetch_faults`` reads the event here (a block a replica served is
a failover).  The event is part of what ``correct`` vouches for: a task fails
by name where the membership was not whole before ``create`` or not the
survivors during the reads, where the cluster's ``recoveries`` rose at all
(the exchange had returned: nothing runs again), where an undisturbed task
reports a fault counter or a copied block, and where a re-placed task's pulled
blocks, replica-served blocks, failovers, retried blocks or timeouts differ
from what ``references/<reference>.py`` ``replaced_task`` says of that task by
the layout alone.  A job whose exchange raised hands the error to each of its
reduce tasks.

A program whose manager cannot place a reader of a lost partition is refused
in ``start``, before any record is made.  The line ``readloss:`` gives, a
run: the readers' counters summed over warm-up and window (pulled blocks and
bytes, those of them from replicas, failovers, retried, timed out), the
elastic counters' rise (recoveries, replicated bytes, received-shard bytes
dropped at the kills), the received-shard bytes still referenced for the dead
executor after a kill (0), the median seconds a kill took (the harness's
stand-in for a death, on the job's clock inside ``job.exchange``), and the replica tier's bytes, the free lists' held
bytes and the harness's resident memory after the removal of the warm-up job
(the window's start), of the first timed job and of the last.
"""

from __future__ import annotations

import inspect
import json
import statistics
import time
from typing import List

from benchmark.cells import load_module
from benchmark.jobs import run_window

shipped = load_module("traffic", "manager-jobs")
rss_gb = load_module("traffic", "manager-lossjobs").rss_gb

#: what the line ``readloss:`` sums of every reader's ``ShuffleReadMetrics``
SUMMED = ("refetched_blocks", "refetched_bytes", "replica_blocks", "replica_bytes",
          "failovers", "blocks_retried", "fetch_timeouts", "resident_blocks", "copied_blocks")
#: and prints of ``cluster.elastic_stats``, as its rise over the run
COUNTED = ("recoveries", "replicated_rounds", "replicated_bytes", "lost_recv_shards", "lost_recv_bytes")


def require_replaceable_reader() -> None:
    """Exit at once on a program that cannot run this traffic: one whose
    manager cannot place a reader of a lost partition on a live executor (its
    reader has no ``received_by``: a task placed off the owner asks its own
    executor for every block), or whose dead executor's received shards stay
    served (its cluster has no ``drop_received_of``)."""
    from sparkucx_tpu.shuffle.reader import TpuShuffleReader
    from sparkucx_tpu.transport.tpu import TpuShuffleCluster, TpuShuffleTransport

    places = "received_by" in inspect.signature(TpuShuffleReader.__init__).parameters
    dies = callable(getattr(TpuShuffleCluster, "drop_received_of", None))
    rejoins = callable(getattr(TpuShuffleTransport, "restart", None))
    if not (places and dies and rejoins):
        raise SystemExit(
            "benchmark: traffic manager-readlossjobs needs a manager that places a reduce task of a lost "
            "partition on a live executor (TpuShuffleReader received_by), a dead executor whose received "
            "shards die with it (TpuShuffleCluster.drop_received_of) and one that rejoins "
            "(TpuShuffleTransport.restart); this program has "
            f"received_by={places}, drop_received_of={dies}, restart={rejoins}"
        )


class Entry(shipped.Entry):
    """``TpuShuffleManager`` in the client's own process; after every
    exchange ``lost`` die, and their partitions' tasks are re-placed."""

    def __init__(self, manager, lost: List[int], replaced_task) -> None:
        super().__init__(manager)
        self.cluster = manager.cluster
        self.lost = list(lost)
        #: reduce_id -> what the reference says that task must pull, or None
        self.replaced_task = replaced_task
        self.survivors = [e for e in range(self.cluster.num_executors) if e not in self.lost]
        #: why the current job's event was not the one asked for (its next
        #: reduce task raises it), and the error its exchange raised (every
        #: reduce task of the job raises it)
        self.unsound = None
        self.exchange_error = None
        self.recoveries = self.cluster.elastic_stats["recoveries"]
        #: the readers' counters, summed over every task of the run
        self.summed = dict.fromkeys(SUMMED, 0)
        self.replaced = 0
        #: received-shard bytes still referenced for the dead after each kill
        self.dead_recv_bytes: List[int] = []
        #: seconds each job's kill took: the harness's stand-in for a death
        #: (the dying store unmaps its rounds), on the job's clock inside
        #: ``job.exchange`` although no survivor waits for it in a deployment
        self.kill_s: List[float] = []
        #: (resident GB, replica bytes, free-list bytes) after every removal
        self.levels: List[tuple] = []

    def _flag(self, why: str) -> None:
        if self.unsound is None:
            self.unsound = why

    def create(self, shuffle_id: int, mappers: int, reducers: int) -> None:
        alive = self.cluster.membership.alive()
        if alive != list(range(self.cluster.num_executors)):
            self._flag(f"the membership was not whole before shuffle {shuffle_id}: alive {alive}")
        super().create(shuffle_id, mappers, reducers)

    def _held_for_the_dead(self, shuffle_id: int) -> int:
        """Bytes of the shuffle's received shards, host and device, the
        cluster still references for the executors just killed."""
        meta = self.cluster.meta(shuffle_id)
        held = 0
        for rounds in (meta.recv_shards, meta.recv_device):
            for rnd in rounds or ():
                held += sum(int(rnd[e].nbytes) for e in self.lost if rnd[e] is not None)
        return held

    def exchange(self, shuffle_id: int) -> None:
        from sparkucx_tpu.core.operation import TransportError
        from sparkucx_tpu.testing import faults

        try:
            super().exchange(shuffle_id)
        except TransportError as e:  # the stage failed: each of its reduce tasks says why
            self.exchange_error = e.with_traceback(None)
            return
        self.after_exchange(shuffle_id)
        t0 = time.perf_counter()
        for executor in self.lost:
            faults.kill_executor(self.cluster.transport(executor))
        self.kill_s.append(time.perf_counter() - t0)
        self.dead_recv_bytes.append(self._held_for_the_dead(shuffle_id))
        alive = self.cluster.membership.alive()
        if alive != self.survivors:
            self._flag(f"alive {alive} under the reads of shuffle {shuffle_id}, not {self.survivors}")

    def after_exchange(self, shuffle_id: int) -> None:
        """Between the exchange's return and the kill (a control's hook)."""

    def read(self, shuffle_id: int, reduce_id: int, mappers: List[int], consume) -> int:
        if self.exchange_error is not None:
            raise self.exchange_error
        if self.unsound is not None:
            why, self.unsound = self.unsound, None
            raise AssertionError(why)
        owner = self.cluster.meta(shuffle_id).owner_of_reduce(reduce_id)
        want = self.replaced_task(reduce_id)
        if (want is None) != (owner not in self.lost):
            raise AssertionError(f"the reference re-places the tasks of other partitions than owner {owner}'s")
        if want is None:  # where the exchange delivered it
            reader = self.manager.get_reader(shuffle_id, reduce_id, reduce_id + 1)
        else:  # where the scheduler re-places it
            self.replaced += 1
            reader = self.manager.get_reader(shuffle_id, reduce_id, reduce_id + 1, executor_id=want["executor"])
        for key, value in reader.read():
            consume(key, value)
        metrics = reader.metrics
        for name in SUMMED:
            self.summed[name] += getattr(metrics, name)
        faults = sum(getattr(metrics, name) for name in shipped.FAULT_COUNTERS)
        if want is None:
            if faults or metrics.copied_blocks or metrics.resident_blocks != len(mappers):
                raise AssertionError(
                    f"an undisturbed task saw the loss: {faults} fetch(es) retried, failed over or timed out, "
                    f"{metrics.copied_blocks} block(s) copied, {metrics.resident_blocks} of {len(mappers)} borrowed")
        else:
            got = (metrics.refetched_blocks, metrics.refetched_bytes, metrics.replica_blocks, metrics.replica_bytes,
                   metrics.failovers, metrics.blocks_retried, metrics.fetch_timeouts, metrics.resident_blocks)
            asked = (want["pulled_blocks"], want["pulled_bytes"], want["replica_blocks"], want["replica_bytes"],
                     want["replica_blocks"], 0, 0, 0)
            if got != asked or reader.executor_id != want["executor"]:
                raise AssertionError(
                    f"a re-placed task on executor {reader.executor_id} pulled (blocks, bytes, from replicas, their "
                    f"bytes, failovers, retried, timed out, borrowed) {got}, the layout says {asked} on "
                    f"executor {want['executor']}")
        rose = self.cluster.elastic_stats["recoveries"] - self.recoveries
        if rose:
            self.recoveries += rose
            raise AssertionError(f"recoveries rose by {rose}: the exchange had returned, nothing runs again")
        return faults

    def remove(self, shuffle_id: int) -> None:
        super().remove(shuffle_id)
        self.exchange_error = None
        for executor in self.lost:
            self.cluster.rejoin_executor(executor)
        stores = [t.store for t in self.cluster.transports]
        self.levels.append((rss_gb(), sum(s.replica_stats()["replica_bytes"] for s in stores),
                            sum(s.write_stats()["pool_held_bytes"] for s in stores)))


class Traffic(shipped.Traffic):
    def start(self, conf, parts: dict):
        require_replaceable_reader()
        return super().start(conf, parts)

    def lost(self) -> List[int]:
        return load_module("references", self.cell.config["reference"]).lost_executors(self.cell.traffic)

    def entry(self) -> Entry:
        reference = load_module("references", self.cell.config["reference"])
        lost = self.lost()
        # worked out once, off the jobs' clock
        table = reference.replaced_tasks(self.cell.config, {"lost_executor": lost}, self.cell.chips)
        return Entry(self.manager, lost, table.__getitem__)

    def run(self, control, parts: dict):
        entry = self.entry()
        cluster = self.manager.cluster
        before = dict(cluster.elastic_stats)

        def guarded(event, **fields):
            # the harness counts the staging rounds of an exchanged shuffle at
            # job_done; a shuffle whose exchange raised has none to count
            if event == "job_done" and entry.exchange_error is not None:
                return {}
            return control(event, **fields)

        window = run_window(entry, self.records, self.args.seconds, bool(self.args.trace), guarded)
        if entry.unsound is not None and window.jobs:
            # found after the last job's tasks were counted: it is that job's
            print(f"after shuffle {len(window.jobs)}: AssertionError: {entry.unsound}", flush=True)
            window.jobs[-1].failed += 1
        jobs = len(window.jobs) + 1  # and the warm-up job
        # after the warm-up job's removal (the window's start), the first timed job's and the last's
        marks = [entry.levels[0], entry.levels[min(1, len(entry.levels) - 1)], entry.levels[-1]]
        print("readloss: " + json.dumps({
            "jobs": jobs, "lost_executors": entry.lost, "survivors": entry.survivors,
            "replaced_tasks": entry.replaced, **entry.summed,
            **{name: cluster.elastic_stats.get(name, 0) - before.get(name, 0) for name in COUNTED},
            "dead_recv_bytes_after_kill_max": max(entry.dead_recv_bytes, default=0),
            "kill_s_per_job_median": round(statistics.median(entry.kill_s), 4) if entry.kill_s else None,
            "alive_at_end": cluster.membership.alive(), "epoch": cluster.membership.epoch,
            "replica_bytes_after_remove": [mark[1] for mark in marks],
            "replica_bytes_after_remove_max": max(level[1] for level in entry.levels),
            "pool_held_bytes_after_remove": [mark[2] for mark in marks],
            "pool_held_bytes": [t.store.write_stats()["pool_held_bytes"] for t in cluster.transports],
            "rss_gb_after_remove": [mark[0] for mark in marks],
            "rss_gb_after_remove_max": max(level[0] for level in entry.levels),
        }), flush=True)
        return window
