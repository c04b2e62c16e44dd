"""Reduce-side read on the device: the least time the chip could take to
gather the traced job's blocks once (every block is read by one reduce task)
over the device time of the executables ``jit_block_gather`` in the trace,
percent.  HBM-bound.  The exchange's own copy runs the same kernel under
another module name (``jit_local_fn``) and is not counted."""

from benchmark.device_path import block_kernel_roofline


def read(run):
    return block_kernel_roofline(run, "jit_block_gather(")
