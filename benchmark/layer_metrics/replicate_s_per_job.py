"""Plan executor: seconds of the program's ``exchange.replicate`` span (every
executor's sealed rounds copied to its ring successor's replica tier, before
the exchange's first round is submitted) inside each timed job's
``job.exchange``, median over the jobs: what the guarantee costs a job that
loses nothing.  Left out where the span was not recorded (an untraced run, a
conf with replication off)."""

from benchmark.device_path import span_seconds_per_job


def read(run):
    return span_seconds_per_job(run, "exchange.replicate", outer="job.exchange")
