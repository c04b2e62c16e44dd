"""Traffic driver: whole jobs from one client thread in the harness's own
process on ``TpuShuffleManager``, each of which loses an executor in the
middle of its exchange.

The harness, the map side and the reduce side are ``manager-jobs``'s (its
``Entry`` is loaded, not copied).  What differs is ``exchange``: before it
calls ``manager.run_exchange`` it arms the program's own fault point
(``sparkucx_tpu.testing.faults``, point ``exchange.submit``) to kill the
traffic file's ``lost_executor`` at the submit of staging round
``lost_at_round`` — once, in that shuffle — so every map task has committed
and that many rounds have gone through when the executor dies.  The exchange
returns recovered (the replicas of the dead executor's sealed rounds, the
shrunk mesh), the reduce tasks read as ever, and after the shuffle's removal
the executor rejoins, before the next ``create``.  Sent by a Spark stage on
preemptible or restarted TPU-VM workers with the shuffle service's
replication on.

The event is part of what ``correct`` vouches for.  A job whose exchange
raised hands the error to each of its reduce tasks (a task's line names it,
as a ``FetchFailed`` would); a job in which the cluster's ``recoveries`` did
not rise by exactly one, or before which the membership was not whole again,
fails its first reduce task by name; a reduce task whose reader reports a
retried, failed-over or timed-out fetch fails too (in ``manager-jobs`` that is
a count beside a sound task; here the recovery is the exchange's, and a reduce
task that saw anything of it was not served by a recovered shuffle).

A program that cannot lose the same executor twice is refused in ``start``,
before any record is made.  The line ``loss:`` gives, a run: the elastic
counters' rise over warm-up and window (recoveries, restaged blocks and bytes,
sub-exchanges, replicated rounds and bytes, nanoseconds), the replica tier's
bytes, the free lists' held bytes and the harness's resident memory after the
removal of the warm-up job (the window's start), of the first timed job and
of the last.
"""

from __future__ import annotations

import json
from typing import List

from benchmark.cells import load_module
from benchmark.jobs import run_window

shipped = load_module("traffic", "manager-jobs")

#: what the line ``loss:`` prints of ``cluster.elastic_stats``, as its rise over the run
COUNTED = ("recoveries", "restaged_blocks", "restaged_bytes", "degraded_subexchanges",
           "replicated_rounds", "replicated_bytes", "replicate_ns", "recover_ns")


def require_repeatable_loss() -> None:
    """Exit at once on a program that cannot run this traffic: one whose
    rejoined executor cannot be lost again (a transport has no ``restart``)."""
    from sparkucx_tpu.transport.tpu import TpuShuffleTransport

    if not callable(getattr(TpuShuffleTransport, "restart", None)):
        raise SystemExit(
            "benchmark: traffic manager-lossjobs needs an executor that can be lost, rejoin and be lost "
            "again (TpuShuffleTransport.restart); this program has none"
        )


def rss_gb() -> float:
    """Resident memory of this process now, GB (``VmRSS``); -1 where unknown."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return round(int(line.split()[1]) / 1e6, 3)
    except (OSError, ValueError, IndexError):
        pass
    return -1.0


class Entry(shipped.Entry):
    """``TpuShuffleManager`` in the client's own process; every exchange
    loses ``lost`` at the submit of staging round ``at_round``."""

    def __init__(self, manager, lost: List[int], at_round: int) -> None:
        super().__init__(manager)
        self.cluster = manager.cluster
        self.lost, self.at_round = list(lost), int(at_round)
        #: why the current job's event was not the one asked for (its first
        #: reduce task raises it), and the error its exchange raised (every
        #: reduce task of the job raises it)
        self.unsound = None
        self.exchange_error = None
        #: (resident GB, replica bytes, free-list bytes) after every removal
        self.levels: List[tuple] = []

    def create(self, shuffle_id: int, mappers: int, reducers: int) -> None:
        alive = self.cluster.membership.alive()
        if alive != list(range(self.cluster.num_executors)) and self.unsound is None:
            self.unsound = f"the membership was not whole before shuffle {shuffle_id}: alive {alive}"
        super().create(shuffle_id, mappers, reducers)

    def exchange(self, shuffle_id: int) -> None:
        from sparkucx_tpu.core.operation import TransportError
        from sparkucx_tpu.testing import faults

        def die(**_ctx):
            for executor in self.lost:
                faults.kill_executor(self.cluster.transport(executor))

        before = self.cluster.elastic_stats["recoveries"]
        armed = faults.arm("exchange.submit", die, times=1,
                           match={"shuffle_id": shuffle_id, "round": self.at_round})
        try:
            super().exchange(shuffle_id)
        except TransportError as e:  # the stage failed: each of its reduce tasks says why
            self.exchange_error = e.with_traceback(None)  # and not the frames that hold its rounds
        finally:
            faults.disarm(armed)
        rose = self.cluster.elastic_stats["recoveries"] - before
        if self.exchange_error is None and rose != 1 and self.unsound is None:
            self.unsound = (f"recoveries rose by {rose}, not 1, in shuffle {shuffle_id} "
                            f"(the kill fired {armed.fired} time(s))")

    def read(self, shuffle_id: int, reduce_id: int, mappers: List[int], consume) -> int:
        if self.exchange_error is not None:
            raise self.exchange_error
        if self.unsound is not None:
            why, self.unsound = self.unsound, None
            raise AssertionError(why)
        faults = super().read(shuffle_id, reduce_id, mappers, consume)
        if faults:
            raise AssertionError(f"{faults} fetch(es) retried, failed over or timed out: "
                                 "the recovery is the exchange's, a reduce task never sees it")
        return 0

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
        require_repeatable_loss()
        return super().start(conf, parts)

    def entry(self) -> Entry:
        traffic = self.cell.traffic
        at_round = traffic["rehearse"]["lost_at_round"] if self.args.rehearse else traffic["lost_at_round"]
        return Entry(self.manager, [traffic["lost_executor"]], at_round)

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
        print("loss: " + json.dumps({
            "jobs": jobs, "lost_executors": entry.lost, "lost_at_round": entry.at_round,
            **{name: cluster.elastic_stats[name] - before[name] for name in COUNTED},
            "alive_at_end": cluster.membership.alive(), "epoch": cluster.membership.epoch,
            "replica_bytes_after_remove": [mark[1] for mark in marks],
            "replica_bytes_after_remove_max": max(level[1] for level in entry.levels),
            "pool_held_bytes_after_remove": [mark[2] for mark in marks],
            "pool_held_bytes": [t.store.write_stats()["pool_held_bytes"] for t in cluster.transports],
            "rss_gb_after_remove": [mark[0] for mark in marks],
            "rss_gb_after_remove_max": max(level[0] for level in entry.levels),
        }), flush=True)
        return window
