"""Plan executor: seconds of the benchmark's span round ``run_exchange``
(``job.exchange``), median over the timed jobs."""

from benchmark.measured import median
from benchmark.spans import durations


def read(run):
    return median(durations(run.spans, "job.exchange"))
