"""The one traffic generator: whole shuffle jobs, back to back, closed loop.

A *job* is one shuffle through an entry point: create -> every map task (one
partition stream per non-empty reducer, in reducer order, then commit) ->
exchange -> every reduce task drained once.  One client runs them in a closed
loop: a Spark stage does not start before the one before it ends.

The loop knows nothing of the cell it runs: the traffic driver's entry object
does the calls (``traffic/<driver>.py``), the records carry the data and the
reference (``references/<reference>.py``), and ``control`` tells the process
that holds the chip where jobs begin and end.
"""

from __future__ import annotations

import gc
import time
from dataclasses import asdict, dataclass, field
from typing import Callable, Dict, List, Optional

from benchmark.spans import SpanLog


@dataclass
class JobResult:
    seconds: float
    tasks: int
    failed: int
    faults: int
    read_task_s: List[float]


def run_job(entry, records, shuffle_id: int, log: SpanLog, full: bool = False) -> JobResult:
    """One whole job on the client's clock, first write to last record
    consumed.  A task that raises, or whose counts differ from the
    reference, is a failed task; the job goes on."""
    mappers, reducers = records.num_mappers, records.reducers
    failed = faults = 0
    read_task_s: List[float] = []
    entry.create(shuffle_id, mappers, reducers)
    t_job = time.perf_counter_ns()
    with log.span("job.write"):
        for m, parts in enumerate(records.blocks):
            try:
                entry.write_map(shuffle_id, m, parts)
            except Exception as e:  # task boundary: count it, name it, go on
                failed += 1
                print(f"map task {m} of shuffle {shuffle_id}: {type(e).__name__}: {e}", flush=True)
    with log.span("job.exchange"):
        entry.exchange(shuffle_id)
    checks = []
    with log.span("job.read"):
        for r in range(reducers):
            check = records.check(r, full)
            t0 = time.perf_counter_ns()
            try:
                faults += entry.read(shuffle_id, r, records.mappers_of(r), check.add)
            except Exception as e:  # task boundary
                check.fail()
                print(f"reduce task {r} of shuffle {shuffle_id}: {type(e).__name__}: {e}", flush=True)
            read_task_s.append((time.perf_counter_ns() - t0) / 1e9)
            checks.append(check)
    seconds = (time.perf_counter_ns() - t_job) / 1e9
    # the comparison is off the job's clock
    failed += sum(not c.ok() for c in checks)
    if full and not failed and not records.complete(checks):
        failed = 1
    return JobResult(seconds, mappers + reducers, failed, faults, read_task_s)


@dataclass
class WindowResult:
    jobs: List[JobResult] = field(default_factory=list)
    warmup: Optional[JobResult] = None
    #: index among ``jobs`` of the traced job the device numbers are of, and
    #: its interval on ``perf_counter_ns``
    traced_job: Optional[int] = None
    traced_ns: Optional[List[int]] = None
    spans: list = field(default_factory=list)
    frame_ns: List[int] = field(default_factory=list)
    #: map-output bytes and blocks of one job
    job_bytes: int = 0
    job_blocks: int = 0
    #: seconds of the warm-up job with its full comparison
    warmup_s: float = 0.0

    def sound(self) -> bool:
        """No task failed: neither in the warm-up job, compared in full with
        the reference, nor in a timed job."""
        return self.warmup is not None and not self.warmup.failed and not any(j.failed for j in self.jobs)

    def to_json(self) -> dict:
        return asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "WindowResult":
        d = dict(d, jobs=[JobResult(**j) for j in d["jobs"]], spans=[tuple(s) for s in d["spans"]])
        if d["warmup"] is not None:
            d["warmup"] = JobResult(**d["warmup"])
        return cls(**d)


#: consecutive jobs in the profiler's one session; the device numbers are of
#: the one that ran shorter.  Some jobs run long for a reason of the host's (the
#: interpreter's full collection unmaps the removed shuffles' spill files inside
#: one job in four to six, PERF.md section 6; a stall), never two in a row.
TRACED_JOBS = 2


def run_window(entry, records, seconds: float, trace: bool,
               control: Callable[..., Dict]) -> WindowResult:
    """Warm-up (one full-size job, compared in full with the reference), then
    whole jobs until ``seconds`` have elapsed.  ``control(event, **fields)``
    reaches the process that holds the chip: ``window_start`` / ``window_end``
    bracket the measured jobs, ``job_done`` comes before a shuffle is removed,
    ``trace_start`` / ``trace_stop`` bracket the profiler's session."""
    log = SpanLog()
    out = WindowResult(job_bytes=records.total_bytes, job_blocks=records.num_blocks)
    shuffle_id = 0

    def finish(sid: int) -> None:
        control("job_done", shuffle_id=sid)
        entry.remove(sid)

    t0 = time.perf_counter()
    out.warmup = run_job(entry, records, shuffle_id, log, full=True)
    finish(shuffle_id)
    out.warmup_s = time.perf_counter() - t0
    # the records and the reference are a few hundred thousand objects that
    # live as long as the run: keep the collector from walking them in the window
    gc.freeze()
    log.spans.clear()
    if trace:
        entry.frame_ns = out.frame_ns
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
        t0 = time.perf_counter_ns()
        out.jobs.append(run_job(entry, records, shuffle_id, log))
        if tracing:
            traced.append((len(out.jobs) - 1, t0, time.perf_counter_ns()))
            if len(traced) == TRACED_JOBS:
                control("trace_stop")
        finish(shuffle_id)
    control("window_end")
    if traced:
        index, lo, hi = min(traced, key=lambda job: out.jobs[job[0]].seconds)
        out.traced_job, out.traced_ns = index, [lo, hi]
    out.spans = list(log.spans)
    return out
