"""Inside ``write.block``: ``write.block.copy`` — the block's chunks copied
into staging, the one phase that grows with the bytes — median over the
sampled blocks, us."""

from benchmark.device_path import span_p50_us


def read(run):
    return span_p50_us(run, "write.block.copy")
