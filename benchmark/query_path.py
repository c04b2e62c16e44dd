"""What the readers of the query operators share: the bytes a query's
aggregates and joins must move, counted from the configuration's layout (its
``geometry`` block and its operators' capacities) — the work, whatever
implements it — and their roofline against the operator executables' device
time in the profiler's trace of the traced query.

The bytes are the one Q18 configuration's (``tpch-q18-sf10-hbm``, the only
cell these metrics list): the readers see a ``Run``, which carries no
configuration, so the file is named here.  A second query configuration
brings its own counts.
"""

from __future__ import annotations

import json
import os
from typing import Callable, Optional

from benchmark.peaks import peaks_for

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(HERE, "configs", "tpch-q18-sf10-hbm.json")
#: bytes of a row of each operator's output: (o_orderkey, sum) from the first
#: aggregate; an orders record from the semi join; a lineitem record with the
#: order's three columns from the inner join, and from the second aggregate
SURVIVOR_ROW, ORDER_ROW, LINE_ROW = 16, 32, 40
MAX_LINES = 7


def _config() -> dict:
    with open(CONFIG) as f:
        return json.load(f)


def aggregate_bytes(config: dict) -> int:
    """Both aggregates of a query, all reduce tasks: shuffle A's records read
    once and a task's ``max_groups`` survivor rows written; the joined lines
    (at most 7 an order handed on) read once and ``max_groups`` result rows
    written."""
    tasks, groups = int(config["partitions"]), int(config["max_groups_per_task"])
    read = int(config["geometry"]["shuffles"]["A"]["bytes"]) + tasks * MAX_LINES * groups * LINE_ROW
    return read + tasks * groups * (SURVIVOR_ROW + LINE_ROW)


def join_bytes(config: dict) -> int:
    """Both joins of a query, all reduce tasks: shuffles B and C read once
    (the probe sides), the build sides' ``max_groups`` rows read, and the
    output arrays written."""
    tasks, groups = int(config["partitions"]), int(config["max_groups_per_task"])
    shuffles = config["geometry"]["shuffles"]
    read = int(shuffles["B"]["bytes"]) + int(shuffles["C"]["bytes"]) + tasks * groups * (SURVIVOR_ROW + ORDER_ROW)
    return read + tasks * groups * (ORDER_ROW + MAX_LINES * LINE_ROW)


def operator_roofline(run, module: str, count: Callable[[dict], int]) -> Optional[float]:
    """``count``'s bytes at the device kind's HBM bandwidth over the device
    time of the executables whose module name starts with ``module`` in the
    traced job, percent; ``None`` where the trace has no such executable (a
    program without the operators, a run without a device trace)."""
    if run.reduction is None:
        return None
    device_s = sum(s for name, s in run.reduction.module_s.items() if name.startswith(module))
    if device_s <= 0:
        return None
    least = count(_config()) / peaks_for(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / device_s
