"""Map-side write: seconds of the benchmark's span round all map tasks of a
job (``job.write``), median over the timed jobs."""

from benchmark.measured import median
from benchmark.spans import durations


def read(run):
    return median(durations(run.spans, "job.write"))
