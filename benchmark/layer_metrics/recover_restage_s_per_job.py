"""Plan executor: seconds of the program's ``exchange.recover.restage`` span
(the dead executor's staging rounds rebuilt on the host from its ring
successor's replicas, a block at a time) inside each timed job's
``job.exchange``, median over the jobs.  A child of ``exchange.recover``: the
host share of ``recover_s_per_job`` before any round runs again.  Left out
where the span was not recorded (an untraced run, a job that lost nothing, a
program before the span existed)."""

from benchmark.device_path import span_seconds_per_job


def read(run):
    return span_seconds_per_job(run, "exchange.recover.restage", outer="job.exchange")
