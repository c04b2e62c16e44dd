"""Plan executor: seconds of the program's ``exchange.recover`` span (from the
abort of the exchange an executor died under to the recovered shuffle: the
dead executor's rounds restaged from replicas, every round run again on the
shrunk mesh) inside each timed job's ``job.exchange``, median over the jobs:
the time to recover, as a layer of the job.  Left out where the span was not
recorded (an untraced run, a job that lost nothing)."""

from benchmark.device_path import span_seconds_per_job


def read(run):
    return span_seconds_per_job(run, "exchange.recover", outer="job.exchange")
