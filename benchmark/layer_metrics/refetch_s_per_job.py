"""Reduce-side read: seconds of the program's ``read.refetch`` span (one
fetch window of a re-placed reduce task, whose partition's received copy died
with its executor: every block pulled, one at a time, from the staging of the
executor that ran its map task or from a replica tier) inside each timed
job's ``job.read``, median over the jobs: what the loss costs the reduce
stage.  Left out where the span was not recorded (an untraced run, a job
that lost nothing, a program before the span existed)."""

from benchmark.device_path import span_seconds_per_job


def read(run):
    return span_seconds_per_job(run, "read.refetch", outer="job.read")
