"""Map-side write on the device: seconds of the program's
``store.device_stage`` spans (one scatter dispatch a map task: the plan built,
the donated staging array handed to ``block_scatter``) inside each job's
``job.write``, median over the timed jobs.  It is the time the calls hold the
writer's thread, not the DMA.  Only a device-staged shuffle records it."""

from benchmark.device_path import span_seconds_per_job


def read(run):
    return span_seconds_per_job(run, "store.device_stage", outer="job.write")
