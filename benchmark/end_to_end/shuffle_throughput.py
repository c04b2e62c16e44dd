"""Map-output bytes of the jobs completed in the window over the sum of their
job times (first write to last record consumed), on the client's clock, MB/s."""


def read(run):
    return run.job_bytes * len(run.jobs) / sum(j.seconds for j in run.jobs) / 1e6
