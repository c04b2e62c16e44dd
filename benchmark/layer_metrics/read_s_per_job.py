"""Reduce-side read: seconds of the benchmark's span round all reduce tasks of
a job (``job.read``), median over the timed jobs."""

from benchmark.measured import median
from benchmark.spans import durations


def read(run):
    return median(durations(run.spans, "job.read"))
