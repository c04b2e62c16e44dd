"""Out-of-core TeraSort: sorting a dataset larger than device capacity.

One chip's HBM bounds the rows a single device sort can hold; the
"TeraSort 10GB" workload (BASELINE configs[1]) exceeds it.  run_external_sort
chains full-capacity device sorts — one compiled function reused across
batches — and merges the sorted runs on the host, moving only (key, index)
pairs through the merge levels and placing each run's payload once.

Run: python examples/06_external_sort.py          (any backend; up to 4 executors)
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from sparkucx_tpu.ops.exchange import make_mesh
from sparkucx_tpu.ops.sort import SortSpec, oracle_sort, run_external_sort


def main() -> None:
    import jax

    n = min(4, len(jax.devices()))
    cap = 2_000                      # per-executor device capacity per batch
    total = 6 * n * cap + 123        # ~6 device batches, ragged tail
    rng = np.random.default_rng(17)
    keys = rng.integers(0, 1 << 32, size=total, dtype=np.uint32)
    payload = rng.integers(-(2**31), 2**31, size=(total, 24), dtype=np.int32)

    spec = SortSpec(
        num_executors=n, capacity=cap,
        recv_capacity=cap if n == 1 else 2 * cap, width=24,
    )
    out_keys, out_payload = run_external_sort(make_mesh(n), spec, keys, payload)

    want_keys, want_payload = oracle_sort(keys, payload)
    assert np.array_equal(out_keys, want_keys)
    assert np.array_equal(out_payload, want_payload)  # stable across batch merges
    batches = -(-total // (n * cap))
    print(
        f"OK: {total} rows sorted through {batches} device batches of "
        f"{n * cap} rows + host merge, row-exact vs the oracle"
    )


if __name__ == "__main__":
    main()
