"""Query runner: the least time the chip could take to read the traced
query's join inputs once and write their outputs once at HBM bandwidth
(``query_path.join_bytes``) over the device time of the executables
``jit_merge_join_records`` in the trace, percent.  It counts the work, not the
algorithm: a join that looks its few build keys up in an ordered partition
does not read the partition whole, and still reads a few percent — the keys'
one pass into comparable form is most of its time.  HBM-bound."""

from benchmark.query_path import join_bytes, operator_roofline


def read(run):
    return operator_roofline(run, "jit_merge_join_records(", join_bytes)
