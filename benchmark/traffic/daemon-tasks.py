"""Traffic driver: whole jobs as a Spark cluster runs them — a driver, and task
slots with a ``ShuffleDaemon`` connection each, map and reduce tasks running
side by side — over the daemon's socket, and a stage boundary that no client
announces.

Three kinds of process:

* **the harness** (``run.py``) holds the chip and the ``ShuffleDaemon`` and runs
  no loop of the job's: its interpreter is the daemon's;
* **the coordinator** (Spark's driver; this file run with role ``coordinator``)
  owns the job's clock and the stage barrier.  Its own ``DaemonClient`` sends
  ``create_shuffle`` and ``remove_shuffle`` and nothing else; it relays the
  window's control events to the harness exactly as ``daemon-jobs`` does;
* **``task_slots`` slots** (this file with role ``slot``; the configuration's
  ``task_slots``): each makes the records from the seed, holds one
  ``DaemonClient`` for the whole run, never touches JAX's devices and runs one
  task at a time.  A slot is a process, so that the four clients' own Python
  shares no interpreter lock, as four JVM task threads share none.

A job: create -> **map stage**, the map tasks in index order, each handed to
the slot that frees first (open writer, one ``write_partition`` frame a
non-empty reducer in reducer order, ``commit_map``, byte count checked) ->
**barrier**, no reduce task starts before the last commit is acknowledged ->
**reduce stage**, the reduce tasks in reducer order, each to the slot that
frees first: one ``fetch_blocks`` of its ``mappers_of(r)`` blocks, every record
decoded and handed to the reference's check -> remove.  **No process sends
``RunExchange``**: the daemon runs the exchange at the first fetch.  Closed
loop; every block is read once.

The job's clock runs from the first map task handed out to the last record
consumed, on ``perf_counter_ns`` (one clock for every process of a host).
Spans: ``job.write`` (first map task handed out -> last commit acknowledged),
``job.exchange`` (-> the first fetch reply received by any slot: what the stage
boundary costs the job, seen from the clients), ``job.read`` (-> last record
consumed); ``task.map`` / ``task.reduce``, one a task, on its slot's clock; and
``job.slot``, one a slot and job over the job's interval, so that a reader
finds the slots' time without knowing how many there are.

The window is ``benchmark.jobs.run_window``'s (warm-up job compared in full,
``gc.freeze``, whole jobs for ``--seconds``, two traced jobs from the middle)
with one difference: a slot that dies ends it after the job that noticed,
which counts one failed task more, so the run still ends in a result line and
``correct`` is false.  A program whose daemon runs no exchange at the stage
boundary is refused in ``start`` (exit 1), as ``manager-devread`` refuses a
reader without ``read_device``.  The line ``tasks:`` gives the daemon's
``stage_stats()`` and the stores' ``lock_wait_ns`` / ``copy_ns`` over the
window.
"""

from __future__ import annotations

import gc
import json
import os
import queue
import resource
import signal
import subprocess
import sys
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

T_PROCESS = time.perf_counter()
if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark.cells import load_cell, load_module
from benchmark.jobs import TRACED_JOBS, JobResult, WindowResult
from benchmark.spans import Span

shipped = load_module("traffic", "daemon-jobs")
now = time.perf_counter_ns
#: counters of the stores' ``write_stats()`` the ``tasks:`` line reports
STORE_COUNTERS = ("staged_blocks", "staged_bytes", "copy_ns", "lock_wait_ns", "rollovers", "rollover_ns")


def require_stage_exchange(daemon_class) -> None:
    """Exit at once on a program that cannot run this traffic: its reduce
    tasks would be told "not exchanged yet" for every block."""
    if not callable(getattr(daemon_class, "stage_stats", None)):
        raise SystemExit(
            f"benchmark: traffic daemon-tasks needs a {daemon_class.__name__} that runs the exchange "
            "at a shuffle's first fetch; this program's waits for a RunExchange no task sends"
        )


def max_rss_bytes() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


class Pipe:
    """One JSON object a line, to a parent over this process's stdout and back
    over its stdin.  Whatever else the process prints goes to stderr."""

    def __init__(self) -> None:
        self._out = os.fdopen(os.dup(sys.stdout.fileno()), "w")
        sys.stdout = sys.stderr

    def send(self, message: dict) -> None:
        self._out.write(json.dumps(message) + "\n")
        self._out.flush()

    def receive(self) -> Optional[dict]:
        line = sys.stdin.readline()
        return json.loads(line) if line else None

    def call(self, event: str, **fields) -> dict:
        self.send({"event": event, **fields})
        reply = self.receive()
        if reply is None:
            raise ConnectionError("the parent closed the pipe")
        return reply


# -- a task slot ------------------------------------------------------------


class Entry(shipped.Entry):
    """A slot's ``DaemonClient``; a read notes when its fetch reply arrived."""

    t_reply = 0

    def read(self, shuffle_id: int, reduce_id: int, mappers: List[int], consume) -> int:
        bids = [self._block_id(shuffle_id, m, reduce_id) for m in mappers]
        payloads = self.client.fetch_blocks(bids)
        self.t_reply = now()
        for bid, payload in zip(bids, payloads):
            if payload is None:
                raise AssertionError(f"daemon could not serve {bid}")
            for key, value in self._decode(payload):
                consume(key, value)
        return 0


def run_task(entry: Entry, records, task: dict) -> dict:
    """One map or reduce task on this slot's clock.  A task that raises, or
    whose counts differ from the reference, is a failed task."""
    sid, index = task["shuffle_id"], task["index"]
    out: Dict[str, object] = {}
    if task["op"] == "map":
        entry.frame_ns = [] if task["frames"] else None
        t0 = now()
        try:
            entry.write_map(sid, index, records.blocks[index])
            ok = True
        except Exception as e:  # task boundary: count it, name it, go on
            ok = False
            print(f"map task {index} of shuffle {sid}: {type(e).__name__}: {e}", flush=True)
        t1 = now()
        out["frame_ns"] = entry.frame_ns or []
    else:
        check = records.check(index, task["full"])
        entry.t_reply = 0
        t0 = now()
        try:
            entry.read(sid, index, records.mappers_of(index), check.add)
        except Exception as e:  # task boundary
            check.fail()
            print(f"reduce task {index} of shuffle {sid}: {type(e).__name__}: {e}", flush=True)
        t1 = now()
        ok = check.ok()
        out["t_reply"] = entry.t_reply
        out["groups"] = len(check.groups) if task["full"] else 0
    return {"ok": ok, "t0": t0, "t1": t1, **out}


def slot_main(spec: dict) -> int:
    pipe = Pipe()
    config = load_cell(spec["workload"], spec["rehearse"]).config
    parts = {"client_imports": time.perf_counter() - T_PROCESS}
    t0 = time.perf_counter()
    records = load_module("references", config["reference"]).make_records(config, spec["seed"])
    parts["records"] = time.perf_counter() - t0
    address = pipe.call(
        "ready", parts=parts, mappers=records.num_mappers, reducers=records.reducers,
        job_bytes=records.total_bytes, job_blocks=records.num_blocks, groups=len(records.groups),
    )["address"]
    entry = Entry(address)
    try:
        while True:
            task = pipe.receive()
            if task is None:  # the coordinator is gone
                return 1
            if task["op"] == "freeze":
                gc.freeze()  # the records live as long as the run
                pipe.send({"ok": True})
            elif task["op"] == "end":
                pipe.send({"max_rss_bytes": max_rss_bytes()})
                return 0
            else:
                pipe.send(run_task(entry, records, task))
    finally:
        entry.close()


# -- the coordinator ----------------------------------------------------------


class Slots:
    """The coordinator's side of the slot processes: hands each task to the
    slot that freed first and gathers the replies.  A slot whose pipe ends has
    died: its task in flight is lost, ``deaths`` counts it, and the tasks
    still to run go to the slots that are left."""

    def __init__(self, program: str, spec: dict, count: int) -> None:
        self.inbox: "queue.Queue" = queue.Queue()
        self.deaths = 0
        self.children = [
            subprocess.Popen([sys.executable, program, json.dumps({**spec, "role": "slot", "slot": k})],
                             stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
            for k in range(count)
        ]
        self.free: deque = deque()  # live slots with no task, the one that freed first at the left
        for k, child in enumerate(self.children):
            threading.Thread(target=self._listen, args=(k, child), daemon=True).start()

    def _listen(self, k: int, child) -> None:
        for line in child.stdout:
            self.inbox.put((k, json.loads(line)))
        self.inbox.put((k, None))

    def send(self, k: int, message: dict) -> None:
        try:
            self.children[k].stdin.write(json.dumps(message) + "\n")
            self.children[k].stdin.flush()
        except OSError:
            pass  # its listener reports the death

    def ready(self, address) -> dict:
        """Every slot's ``ready`` message answered with the daemon's address;
        returns the job's shape as the first slot to arrive gave it."""
        shape: Dict[str, object] = {}
        parts: Dict[str, float] = {}
        waiting = set(range(len(self.children)))
        while waiting:
            k, message = self.inbox.get()
            waiting.discard(k)
            if message is None:
                self._lost(k)
                continue
            for name, seconds in message.pop("parts").items():
                parts[name] = max(parts.get(name, 0.0), seconds)  # they set up side by side
            message.pop("event")
            shape = shape or message
            self.send(k, {"address": address})
            self.free.append(k)
        return {**shape, "parts": parts}

    def _lost(self, k: int) -> None:
        self.deaths += 1
        if k in self.free:
            self.free.remove(k)

    def run_stage(self, tasks: List[dict]) -> List[Optional[dict]]:
        """Every task of one stage, in order, each to the slot that frees
        first; returns their replies in task order (``None``: never ran, or
        its slot died)."""
        replies: List[Optional[dict]] = [None] * len(tasks)
        running: Dict[int, int] = {}
        at = 0
        while at < len(tasks) or running:
            while at < len(tasks) and self.free:
                k = self.free.popleft()
                self.send(k, tasks[at])
                running[k] = at
                at += 1
            if not running:
                break  # no slot is left: the rest never run
            k, message = self.inbox.get()
            if message is None:
                self._lost(k)
                running.pop(k, None)
                continue
            replies[running.pop(k)] = message
            self.free.append(k)
        return replies

    def broadcast(self, message: dict) -> List[dict]:
        """One message to every idle slot, and their replies."""
        asked = list(self.free)
        for k in asked:
            self.send(k, message)
        out = []
        for _ in asked:
            k, reply = self.inbox.get()
            if reply is None:
                self._lost(k)
            else:
                out.append(reply)
        return out

    def close(self) -> None:
        for child in self.children:
            try:
                child.stdin.close()
            except OSError:
                pass
        for child in self.children:
            try:
                child.wait(timeout=10)
            except subprocess.TimeoutExpired:
                child.kill()
                child.wait()
            child.stdout.close()


class Cluster:
    """What the coordinator runs a job on: its own control connection, the
    slots, and the job's shape."""

    def __init__(self, address, slots: Slots, task_slots: int, shape: dict) -> None:
        from sparkucx_tpu.shuffle.daemon import DaemonClient

        self.client = DaemonClient(tuple(address))
        self.slots = slots
        self.task_slots = task_slots
        self.mappers, self.reducers = int(shape["mappers"]), int(shape["reducers"])
        self.groups = int(shape["groups"])
        #: set for the traced runs' timed jobs: slots time every frame
        self.frame_ns: Optional[List[int]] = None
        self.spans: List[Span] = []

    def run_job(self, shuffle_id: int, full: bool = False) -> JobResult:
        """One whole job on the coordinator's clock, first map task handed out
        to last record consumed."""
        slots = self.slots
        deaths = slots.deaths
        self.client.create_shuffle(shuffle_id, self.mappers, self.reducers)
        frames = self.frame_ns is not None
        t_job = now()
        maps = slots.run_stage([{"op": "map", "shuffle_id": shuffle_id, "index": m, "frames": frames}
                                for m in range(self.mappers)])
        t_barrier = now()  # the last commit is acknowledged: the reduce stage may start
        reduces = slots.run_stage([{"op": "reduce", "shuffle_id": shuffle_id, "index": r, "full": full}
                                   for r in range(self.reducers)])
        t_end = now()
        replied = [t for t in (r["t_reply"] for r in reduces if r) if t]
        t_first = min(max(min(replied), t_barrier), t_end) if replied else t_end
        self.spans += [("job.write", t_job, t_barrier), ("job.exchange", t_barrier, t_first),
                       ("job.read", t_first, t_end)]
        self.spans += [("job.slot", t_job, t_end)] * self.task_slots
        for name, stage in (("task.map", maps), ("task.reduce", reduces)):
            self.spans += [(name, r["t0"], r["t1"]) for r in stage if r]
        if frames:
            self.frame_ns += [ns for r in maps if r for ns in r["frame_ns"]]
        # the comparison is off the job's clock
        failed = sum(not (r and r["ok"]) for r in maps + reduces) + (slots.deaths - deaths)
        if full and not failed and sum(r["groups"] for r in reduces) != self.groups:
            failed = 1  # a key of the reference surfaced in no task
        return JobResult((t_end - t_job) / 1e9, self.mappers + self.reducers, failed, 0,
                         [(r["t1"] - r["t0"]) / 1e9 for r in reduces if r])

    def remove(self, shuffle_id: int) -> None:
        self.client.remove_shuffle(shuffle_id)

    def close(self) -> None:
        self.client.close()


def run_window(cluster: Cluster, shape: dict, seconds: float, trace: bool,
               control: Callable[..., Dict]) -> WindowResult:
    """``benchmark.jobs.run_window`` over ``cluster.run_job``: the same
    warm-up, control events and choice of traced jobs; ends early, after at
    least one timed job, once a slot has died."""
    out = WindowResult(job_bytes=int(shape["job_bytes"]), job_blocks=int(shape["job_blocks"]))
    slots = cluster.slots
    shuffle_id = 0

    def finish(sid: int) -> None:
        control("job_done", shuffle_id=sid)
        cluster.remove(sid)

    t0 = time.perf_counter()
    out.warmup = cluster.run_job(shuffle_id, full=True)
    finish(shuffle_id)
    out.warmup_s = time.perf_counter() - t0
    slots.broadcast({"op": "freeze"})
    cluster.spans.clear()
    if trace:
        cluster.frame_ns = out.frame_ns
    control("window_start")
    t_window = time.perf_counter()
    traced = []  # (index among the jobs, start ns, end ns) of the jobs in the session
    while True:
        elapsed = time.perf_counter() - t_window
        untraced = trace and len(traced) < TRACED_JOBS and not slots.deaths
        if (elapsed >= seconds or slots.deaths) and out.jobs and not untraced:
            break
        shuffle_id += 1
        # the profiler takes whole jobs from the middle of the window
        tracing = bool(untraced and out.jobs and (traced or elapsed >= seconds / 2))
        if tracing and not traced:
            control("trace_start")
        t0 = now()
        out.jobs.append(cluster.run_job(shuffle_id))
        if tracing:
            traced.append((len(out.jobs) - 1, t0, now()))
            if len(traced) == TRACED_JOBS or slots.deaths:
                control("trace_stop")
        finish(shuffle_id)
    control("window_end")
    if traced:
        index, lo, hi = min(traced, key=lambda job: out.jobs[job[0]].seconds)
        out.traced_job, out.traced_ns = index, [lo, hi]
    out.spans = list(cluster.spans)
    return out


def coordinator_main(spec: dict) -> int:
    pipe = Pipe()
    config = load_cell(spec["workload"], spec["rehearse"]).config
    task_slots = int(config["task_slots"])
    slots = Slots(spec["program"], spec, task_slots)
    try:
        address = pipe.call("ready")["address"]
        shape = slots.ready(address)
        parts = shape.pop("parts")
        parts["cpu_count"] = float(os.cpu_count() or 0)
        parts["cpus_usable"] = float(len(os.sched_getaffinity(0)))
        if not shape:
            raise RuntimeError("no task slot came up")
        cluster = Cluster(address, slots, task_slots, shape)
        try:
            window = run_window(cluster, shape, spec["seconds"], spec["trace"], pipe.call)
        finally:
            cluster.close()
        rss = [reply["max_rss_bytes"] for reply in slots.broadcast({"op": "end"})]
        parts["slots_rss_gb"] = sum(rss) / 1e9
        parts["slots_lost"] = float(slots.deaths)
    finally:
        slots.close()
    pipe.send({"event": "result", "window": window.to_json(), "parts": parts})
    return 0


# -- the harness's side -------------------------------------------------------


class Traffic(shipped.Traffic):
    #: the file the coordinator and the slots run (a control beside a copy of
    #: the benchmark names its own)
    program = os.path.abspath(__file__)

    def __init__(self, cell, args) -> None:
        self.cell = cell
        self.daemon = None
        spec = {"role": "coordinator", "program": self.program, "workload": cell.name,
                "rehearse": args.rehearse, "seed": args.seed, "seconds": args.seconds,
                "trace": bool(args.trace)}
        env = dict(os.environ, JAX_PLATFORMS="cpu")  # the chip is the harness's
        # a session of their own: close() reaches the slots through the group
        # even when the coordinator is already gone
        self.child = subprocess.Popen(
            [sys.executable, self.program, json.dumps(spec)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env, start_new_session=True,
        )

    def start(self, conf, parts: dict):
        from sparkucx_tpu.shuffle.daemon import ShuffleDaemon

        require_stage_exchange(ShuffleDaemon)
        return super().start(conf, parts)

    def _counters(self) -> dict:
        stores = [t.store.write_stats() for t in self.daemon.manager.cluster.transports]
        return {**self.daemon.stage_stats(),
                **{name: sum(s.get(name, 0) for s in stores) for name in STORE_COUNTERS}}

    def run(self, control, parts: dict):
        marks: Dict[str, dict] = {}

        def marking(event: str, **fields):
            if event in ("window_start", "window_end"):
                marks[event] = self._counters()
            return control(event, **fields)

        window = super().run(marking, parts)
        parts["harness_rss_gb"] = max_rss_bytes() / 1e9
        before, after = marks["window_start"], marks["window_end"]
        gauges = ("connections", "connections_peak")
        print("tasks: " + json.dumps({
            "jobs": len(window.jobs), "task_slots": int(self.cell.config["task_slots"]),
            **{name: after[name] if name in gauges else after[name] - before[name] for name in after},
        }), flush=True)
        return window

    def close(self) -> None:
        if self.daemon is not None:
            self.daemon.close()
        child = self.child
        try:
            child.stdin.close()
        except OSError:
            pass
        try:
            # a run that reached its result has nothing left to wait for
            child.wait(timeout=10 if self.daemon is not None else 0)
        except subprocess.TimeoutExpired:
            pass
        try:
            os.killpg(child.pid, signal.SIGKILL)  # the slots too, whatever they were at
        except ProcessLookupError:
            pass
        child.wait()
        child.stdout.close()


def child_main(spec: dict) -> int:
    return slot_main(spec) if spec["role"] == "slot" else coordinator_main(spec)


if __name__ == "__main__":
    sys.exit(child_main(json.loads(sys.argv[1])))
