"""Map-side write on the device: the least time the chip could take to
scatter the traced job's blocks once (every block is written by one map task)
over the device time of the executables ``jit_block_scatter`` in the trace,
percent.  HBM-bound; the zero fill of the staging array is in neither term."""

from benchmark.device_path import block_kernel_roofline


def read(run):
    return block_kernel_roofline(run, "jit_block_scatter(")
