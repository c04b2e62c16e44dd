#!/usr/bin/env python
"""Integration gate: transitive closure on the executor mesh vs the CPU oracle.

The ``run_tc_test`` analogue (buildlib/test.sh:175-179): the reference runs
Spark's SparkTC example through the plugin as half its CI gate; here the
device-resident closure (ops/tc.py) runs on a real multi-device mesh at
SparkTC's default shape (200 random edges over 100 vertices) and must match
the host oracle exactly.

Env knobs (test.sh style): EXECUTORS, VERTICES, EDGES, SEED.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from sparkucx_tpu.ops.exchange import make_mesh  # noqa: E402
from sparkucx_tpu.ops.tc import TcSpec, oracle_tc, run_transitive_closure  # noqa: E402


def main() -> int:
    n = int(os.environ.get("EXECUTORS", "4"))
    vertices = int(os.environ.get("VERTICES", "100"))
    num_edges = int(os.environ.get("EDGES", "200"))  # SparkTC defaults
    seed = int(os.environ.get("SEED", "0"))

    rng = np.random.default_rng(seed)
    edges = rng.integers(0, vertices, size=(num_edges, 2), dtype=np.uint32)

    # capacities: closure can approach vertices^2 pairs; hash-balanced shards
    per_shard = max(256, (2 * vertices * vertices) // n)
    spec = TcSpec(
        num_executors=n,
        edge_capacity=max(64, 2 * num_edges // n + num_edges % n),
        tc_capacity=per_shard,
        join_capacity=4 * per_shard,
    )
    mesh = make_mesh(n)
    t0 = time.perf_counter()
    got, rounds = run_transitive_closure(mesh, spec, edges, max_rounds=vertices)
    dt = time.perf_counter() - t0
    want = oracle_tc(edges)
    if not np.array_equal(got, want):
        print(f"FAIL: closure mismatch ({len(got)} pairs, want {len(want)})")
        return 1
    print(
        f"tc test OK: {num_edges} edges over {vertices} vertices -> "
        f"{len(got)} closure pairs in {rounds} rounds across {n} executors "
        f"({dt:.1f}s)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
