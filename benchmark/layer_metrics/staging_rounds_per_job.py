"""Staging rounds (= collective dispatches) a job took:
``len(cluster.meta(shuffle_id).recv_sizes)``, median over the timed jobs."""

from benchmark.measured import median


def read(run):
    return median(run.rounds)
