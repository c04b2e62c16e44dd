"""End-to-end query DAG runner with lineage-keyed cross-query shuffle reuse.

* :mod:`sparkucx_tpu.query.dag` — StageDag (scan/exchange/aggregate/join/sort)
  and its canonical serialization.
* :mod:`sparkucx_tpu.query.lineage` — the lineage hash and the admission-
  controlled LineageCache of sealed shuffles.
* :mod:`sparkucx_tpu.query.runner` — QueryRunner, compiling DAGs onto the
  manager SPI / ExchangePlan executor, per tenant.
* :mod:`sparkucx_tpu.query.batch` — the runner's batch lane: record arrays a
  split, every exchange left on the device, the stages after it run there.
"""

from sparkucx_tpu.query.batch import (
    BatchLaneRefusedError,
    BatchResult,
    QueryCapacityError,
    QuerySumOverflowError,
    RecordSplit,
)
from sparkucx_tpu.query.dag import Stage, StageDag
from sparkucx_tpu.query.lineage import (
    BYTE_AFFECTING_PLAN_FIELDS,
    SCHEDULE_ONLY_PLAN_FIELDS,
    SERVE_ONLY_PLAN_FIELDS,
    CacheEntry,
    LineageCache,
    conf_byte_signature,
    fingerprint_rows,
    lineage_key,
    plan_byte_signature,
)
from sparkucx_tpu.query.runner import QueryRunner

__all__ = [
    "Stage",
    "StageDag",
    "LineageCache",
    "CacheEntry",
    "QueryRunner",
    "RecordSplit",
    "BatchResult",
    "BatchLaneRefusedError",
    "QuerySumOverflowError",
    "QueryCapacityError",
    "BYTE_AFFECTING_PLAN_FIELDS",
    "SCHEDULE_ONLY_PLAN_FIELDS",
    "SERVE_ONLY_PLAN_FIELDS",
    "conf_byte_signature",
    "fingerprint_rows",
    "lineage_key",
    "plan_byte_signature",
]
