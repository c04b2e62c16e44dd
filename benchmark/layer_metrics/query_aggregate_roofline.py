"""Query runner: the least time the chip could take to read the traced
query's aggregate inputs once and write their outputs once at HBM bandwidth
(``query_path.aggregate_bytes``) over the device time of the executables
``jit_grouped_sum_records`` in the trace, percent.  It counts the work, not
the algorithm.  HBM-bound."""

from benchmark.query_path import aggregate_bytes, operator_roofline


def read(run):
    return operator_roofline(run, "jit_grouped_sum_records(", aggregate_bytes)
