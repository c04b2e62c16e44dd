"""Fetches retried, failed over or timed out in the timed jobs
(``ShuffleReadMetrics``); non-zero on a healthy host is a finding."""


def read(run):
    return run.fetch_faults
