"""Inside ``write.block``: ``write.block.record`` — the table entry, the
region's used count and the lock's release, up to ``close_partition``'s
return — median over the sampled blocks, us."""

from benchmark.device_path import span_p50_us


def read(run):
    return span_p50_us(run, "write.block.record")
