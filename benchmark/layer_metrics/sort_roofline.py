"""Reduce-side read, ordered: the least time the chip could take to read the
traced job's used rows once and write them back at HBM bandwidth (every
record of the job is ordered once, by one reduce task) over the device time
of the executables ``jit_ordered_records`` in the trace, percent.  It counts
the work, not the algorithm: a comparison sort passes over its operands many
times and reads a few percent, whatever implements it."""

from benchmark.device_path import block_kernel_roofline


def read(run):
    return block_kernel_roofline(run, "jit_ordered_records(")
