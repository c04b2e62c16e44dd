"""Map-side write: seconds of the program's ``store.spill`` spans (the disk
tier: spill file created, the round's used regions copied, flushed) inside
each job's ``job.write``, median over the timed jobs.  A child of
``store.rollover``: the disk tier's share of ``write_rollover_s_per_job``."""

from benchmark.inner_spans import seconds_inside_per_job


def read(run):
    return seconds_inside_per_job(run, "store.spill")
