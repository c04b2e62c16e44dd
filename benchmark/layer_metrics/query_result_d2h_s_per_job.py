"""Query runner: seconds of the program's ``query.result.d2h`` spans (the
wait for one reduce task's result rows on the host: with one task in flight
it is the sync that ends every task, behind which its reads' sorts and its
operators run) inside each timed query's ``job.read``, median over the
queries.  Left out where the span was not recorded."""

from benchmark.device_path import span_seconds_per_job


def read(run):
    return span_seconds_per_job(run, "query.result.d2h", outer="job.read")
