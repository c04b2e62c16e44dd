"""TeraSort on device: the whole job, not just the shuffle transport.

The reference accelerates only the block-fetch layer under Spark's sortByKey;
here sampling, range partitioning, the all-to-all, and both local sorts run
as one jitted SPMD program over the executor mesh (ops/sort.py).  The host
driver handles the one data-dependent decision — splitter-skew overflow —
by re-running with doubled receive headroom.

Run: python examples/03_terasort.py               (any backend; up to 4 executors)

The served-path form of this job's shuffle alone (100-byte records through
get_writer / run_exchange / get_reader(...).read_batches(), the sort left to the
consumer) is the benchmark cell ts10gb-batchjobs-1chip: benchmark/traffic/manager-batchjobs.py.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from sparkucx_tpu.ops.exchange import make_mesh
from sparkucx_tpu.ops.sort import SortSpec, oracle_sort, run_distributed_sort


def main() -> None:
    import jax

    n = min(4, len(jax.devices()))
    total = 40_000  # 100 B rows: uint32 key + 24 int32 payload lanes
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 1 << 32, size=total, dtype=np.uint32)
    payload = rng.integers(-(2**31), 2**31, size=(total, 24), dtype=np.int32)

    spec = SortSpec(
        num_executors=n,
        capacity=-(-total // n),
        recv_capacity=2 * -(-total // n),  # headroom over the balanced share
        width=24,
    )
    mesh = make_mesh(n)
    out_keys, out_payload = run_distributed_sort(mesh, spec, keys, payload)

    want_keys, want_payload = oracle_sort(keys, payload)
    assert np.array_equal(out_keys, want_keys)
    assert np.array_equal(out_payload, want_payload)  # stable: payloads row-exact
    print(f"OK: {total} rows sorted across {n} executors, row-exact vs the oracle")


if __name__ == "__main__":
    main()
