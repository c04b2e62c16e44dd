"""Seal and plan: seconds of the program's ``store.seal_put`` spans (a
single-round shuffle's host staging put on the chip, in pieces above 64 MiB)
inside each job's ``job.exchange``, median over the timed jobs.  It is the
time the calls hold the thread, not the DMA.  A multi-round shuffle puts its
rounds in the exchange and a device-staged one has nothing to put: neither
records the span."""

from benchmark.device_path import span_seconds_per_job


def read(run):
    return span_seconds_per_job(run, "store.seal_put", outer="job.exchange")
