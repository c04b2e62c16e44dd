"""Map-side write: the longest ``write.task`` span of each timed job (those
that begin inside its ``job.write``), median over the jobs, ms — as
``read_task_max_ms`` is for the reduce side.  Map tasks run one after another
in a closed loop, so a job's write is the sum of its tasks; this parts from
``write_task_p50_ms`` where some tasks write into pages the process has not
touched yet (a rejoined executor's fresh staging) or roll a round.  Left out
where no such span was recorded (an untraced run, the parent)."""

import bisect

from benchmark.measured import median


def read(run):
    tasks = sorted((t0, t1 - t0) for name, t0, t1 in run.program_spans if name == "write.task")
    if not tasks:
        return None
    starts = [t0 for t0, _ in tasks]
    longest = []
    for name, lo, hi in run.spans:
        if name == "job.write":
            inside = tasks[bisect.bisect_left(starts, lo) : bisect.bisect_right(starts, hi)]
            if inside:
                longest.append(max(ns for _, ns in inside))
    value = median(longest)
    return None if value is None else value / 1e6
