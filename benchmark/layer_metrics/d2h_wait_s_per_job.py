"""Plan executor: seconds of the program's ``exchange.d2h`` spans (the drain
observing one sub-round's received prefixes arrive on the host: **the wait
for the D2H**, not a dispatch) inside each timed job's ``job.exchange``,
median over the jobs.  What crosses is each received shard's used rows
rounded up to a power-of-two bucket, so it grows with the bytes received and
with the bucket's overshoot.  Left out where the span was not recorded (an
untraced run, ``host_recv_mode`` ``device`` or a single-shot ``memmap``)."""

from benchmark.device_path import span_seconds_per_job


def read(run):
    return span_seconds_per_job(run, "exchange.d2h", outer="job.exchange")
