"""Device-resident operators: the shuffle collective and the workloads on it.

Everything here is a compiled SPMD program over the executor mesh — specs are
static (capacities, widths), data is runtime (sizes, validity) — so one
compilation serves every batch.  See each module's docstring for the reference
behavior it reproduces.
"""

from sparkucx_tpu.ops.combine import CombineSpec
from sparkucx_tpu.ops.columnar import (
    ColumnarSpec,
    build_columnar_shuffle,
    run_columnar_shuffle,
    shard_rows_host,
    unpack_shard_prefixes,
)
from sparkucx_tpu.ops.exchange import (
    ExchangeSpec,
    build_exchange,
    gather_rows,
    make_mesh,
    oracle_exchange,
    pack_chunks_slots,
    unpack_received,
)
from sparkucx_tpu.ops.hierarchy import (
    build_hierarchical_exchange,
    make_hierarchical_mesh,
)
from sparkucx_tpu.ops.pallas_kernels import build_block_gather, pack_plan
from sparkucx_tpu.ops.skew import (
    ExchangePlan,
    chunk_size_rows,
    plan_exchange,
    quota_slot_rows,
    reassemble_round,
    slice_subround,
    staging_occupancy,
)
from sparkucx_tpu.ops.relational import (
    AggregateSpec,
    JoinSpec,
    build_grouped_aggregate,
    build_hash_join,
    grouped_sum_records,
    hash_owners_host,
    merge_join_records,
    oracle_aggregate,
    oracle_join,
    plan_join_capacities,
    run_grouped_aggregate,
    run_hash_join,
    run_plan_grouped_aggregate,
)
from sparkucx_tpu.ops.sort import (
    SortSpec,
    build_distributed_sort,
    merge_sorted_runs,
    oracle_sort,
    run_distributed_sort,
    run_external_sort,
)
from sparkucx_tpu.ops.tc import (
    TcSpec,
    build_tc_prep,
    build_tc_step,
    oracle_tc,
    run_transitive_closure,
)

__all__ = [
    "ColumnarSpec",
    "build_columnar_shuffle",
    "run_columnar_shuffle",
    "shard_rows_host",
    "unpack_shard_prefixes",
    "ExchangeSpec",
    "build_exchange",
    "gather_rows",
    "make_mesh",
    "oracle_exchange",
    "pack_chunks_slots",
    "unpack_received",
    "build_hierarchical_exchange",
    "make_hierarchical_mesh",
    "build_block_gather",
    "pack_plan",
    "ExchangePlan",
    "chunk_size_rows",
    "plan_exchange",
    "quota_slot_rows",
    "reassemble_round",
    "slice_subround",
    "staging_occupancy",
    "AggregateSpec",
    "JoinSpec",
    "build_grouped_aggregate",
    "build_hash_join",
    "grouped_sum_records",
    "merge_join_records",
    "hash_owners_host",
    "oracle_aggregate",
    "oracle_join",
    "plan_join_capacities",
    "run_grouped_aggregate",
    "run_hash_join",
    "SortSpec",
    "build_distributed_sort",
    "merge_sorted_runs",
    "oracle_sort",
    "run_distributed_sort",
    "run_external_sort",
    "TcSpec",
    "build_tc_prep",
    "build_tc_step",
    "oracle_tc",
    "run_transitive_closure",
]
