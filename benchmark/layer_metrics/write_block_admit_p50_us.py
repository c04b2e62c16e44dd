"""Inside ``write.block``: ``write.block.admit`` — ``close_partition`` from
its entry to the start of the copy: the watermark gate, the store's lock
taken, the tenant charged and, where the region was full, the rollover —
median over the sampled blocks, us."""

from benchmark.device_path import span_p50_us


def read(run):
    return span_p50_us(run, "write.block.admit")
