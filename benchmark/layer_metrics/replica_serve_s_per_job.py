"""Reduce-side read: seconds of the program's ``store.read.replica`` span (a
store serving one block out of its replica tier: the body of a round its ring
predecessor pushed before the exchange) inside each timed job's ``job.read``,
median over the jobs: the replica holder's share of ``refetch_s_per_job``.
Left out where the span was not recorded (an untraced run, a job in which no
reader went to a replica)."""

from benchmark.device_path import span_seconds_per_job


def read(run):
    return span_seconds_per_job(run, "store.read.replica", outer="job.read")
