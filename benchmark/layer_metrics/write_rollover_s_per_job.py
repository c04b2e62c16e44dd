"""Map-side write: seconds of the program's ``store.rollover`` spans (a full
staging round snapshotted, spilled and replaced by a fresh buffer) inside each
job's ``job.write``, median over the timed jobs.  ``write_s_per_job`` less
this is the per-block copies."""

from benchmark.inner_spans import seconds_inside_per_job


def read(run):
    return seconds_inside_per_job(run, "store.rollover")
