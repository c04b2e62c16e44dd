"""Reduce-side read: a job's slowest reduce task (its whole read on the
client's clock, as ``read_task_p95_ms`` times it), median over the timed jobs,
ms.  One task in flight, so a stage ends when its straggler does: where the
reduce partitions are level it reads near ``read_task_p95_ms``, where one
holds a popular key it is that partition's read."""

from benchmark.measured import median


def read(run):
    slowest = [max(job.read_task_s) for job in run.jobs if job.read_task_s]
    value = median(slowest)
    return None if value is None else value * 1e3
