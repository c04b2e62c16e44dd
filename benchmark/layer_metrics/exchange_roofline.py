"""Exchange kernels: the least time the chips could take for the traced job's
exchange (``peaks.exchange_min_seconds``: staged rows that carried payload x
the row's bytes, against HBM bandwidth on one chip and the interconnect on
several) over the device time of the exchange executables in the trace,
percent.  One chip is bound by HBM bandwidth, several by the interconnect.

The executables are told apart by their module names in the trace, which are
the jitted functions' names in ``ops/exchange.py``; a module of another name
is not counted, and with none found the metric is left out.
"""

from benchmark.peaks import exchange_min_seconds

#: module names in the trace start with one of these, then "(<fingerprint>)"
EXCHANGE_MODULES = ("jit_local_fn(", "jit__exchange_shard_ragged(")


def read(run):
    if run.reduction is None or not run.jobs:
        return None
    device_s = sum(
        seconds for name, seconds in run.reduction.module_s.items()
        if name.startswith(EXCHANGE_MODULES)
    )
    if device_s <= 0:
        return None
    used_rows = run.stat_delta("used_rows") / len(run.jobs)  # every job stages the same rows
    least = exchange_min_seconds(run.device_kind, run.chips, used_rows, run.row_bytes)
    return 100.0 * least / device_s
