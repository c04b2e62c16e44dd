"""Tests for the device-resident columnar shuffle (GpuColumnarExchange analogue)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from sparkucx_tpu.ops.columnar import (
    ColumnarSpec,
    build_columnar_shuffle,
    owners_from_partitions,
)
from sparkucx_tpu.ops.exchange import make_mesh

N = 8
CAP = 64
W = 16


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(N)


@pytest.fixture(scope="module")
def fn(mesh):
    spec = ColumnarSpec(
        num_executors=N, capacity=CAP, recv_capacity=N * CAP, width=W,
        dtype=np.dtype(np.float32), impl="dense",
    )
    return build_columnar_shuffle(mesh, spec)


def _place(mesh, rows, owners):
    return (
        jax.device_put(rows, NamedSharding(mesh, P("ex", None))),
        jax.device_put(owners, NamedSharding(mesh, P("ex"))),
    )


def _oracle(rows, owners, n, cap):
    """Receiver j's rows: sender-major, each sender's rows in original order."""
    out = {j: [] for j in range(n)}
    for i in range(n):
        for k in range(cap):
            dest = owners[i * cap + k]
            if 0 <= dest < n:
                out[dest].append(rows[i * cap + k])
    return out


class TestColumnarShuffle:
    @pytest.mark.parametrize(
        "n, cap, width, recv_cap, impl",
        [
            (N, CAP, W, N * CAP, "dense"),
            # 4,096 rows of 128 B over four executors, 2x balanced headroom,
            # the lowering left to the platform
            (4, 1024, 32, 2048, "auto"),
        ],
        ids=["512_rows_of_64B_over_8", "4096_rows_of_128B_over_4"],
    )
    def test_random_vs_oracle(self, rng, n, cap, width, recv_cap, impl):
        mesh = make_mesh(n)
        fn = build_columnar_shuffle(
            mesh,
            ColumnarSpec(
                num_executors=n, capacity=cap, recv_capacity=recv_cap, width=width, impl=impl
            ),
        )
        assert fn.spec.impl == "dense"
        rows = rng.normal(size=(n * cap, width)).astype(np.float32)
        owners = rng.integers(0, n, size=n * cap).astype(np.int32)
        recv, counts = fn(*_place(mesh, rows, owners))
        recv, counts = np.asarray(recv), np.asarray(counts)
        assert int(counts.sum()) == n * cap
        expected = _oracle(rows, owners, n, cap)
        for j in range(n):
            total = int(counts[j].sum())
            got = recv[j * recv_cap : j * recv_cap + total]
            want = np.stack(expected[j]) if expected[j] else np.zeros((0, width), np.float32)
            assert got.shape == want.shape
            assert np.array_equal(got, want), f"receiver {j}"

    def test_padding_rows_not_sent(self, mesh, fn, rng):
        rows = rng.normal(size=(N * CAP, W)).astype(np.float32)
        owners = np.full(N * CAP, N, dtype=np.int32)  # all padding
        owners[5] = 3
        recv, counts = fn(*_place(mesh, rows, owners))
        counts = np.asarray(counts)
        assert counts.sum() == 1
        got = np.asarray(recv)[3 * fn.spec.recv_capacity]
        assert np.array_equal(got, rows[5])

    def test_skew_all_to_one(self, mesh, fn, rng):
        rows = rng.normal(size=(N * CAP, W)).astype(np.float32)
        owners = np.zeros(N * CAP, dtype=np.int32)  # everything to executor 0
        recv, counts = fn(*_place(mesh, rows, owners))
        counts = np.asarray(counts)
        assert counts[0].sum() == N * CAP
        got = np.asarray(recv)[: N * CAP]
        expected = _oracle(rows, owners, N, CAP)[0]
        assert np.array_equal(got, np.stack(expected))

    def test_jit_reuse_no_retrace(self, mesh, fn, rng):
        for _ in range(3):
            rows = rng.normal(size=(N * CAP, W)).astype(np.float32)
            owners = rng.integers(0, N, size=N * CAP).astype(np.int32)
            recv, counts = fn(*_place(mesh, rows, owners))
            assert int(np.asarray(counts).sum()) == N * CAP

    def test_ragged_lowering(self, mesh):
        spec = ColumnarSpec(
            num_executors=N, capacity=CAP, recv_capacity=N * CAP, width=W, impl="ragged"
        )
        f = build_columnar_shuffle(mesh, spec)
        rows = jax.ShapeDtypeStruct((N * CAP, W), np.float32)
        owners = jax.ShapeDtypeStruct((N * CAP,), np.int32)
        text = f.lower(rows, owners).as_text()
        assert "ragged_all_to_all" in text or "ragged-all-to-all" in text


class TestOwnersFromPartitions:
    def test_contiguous_ranges_match_store(self):
        from sparkucx_tpu.store.hbm_store import default_peer_ranges

        R, n = 10, 4
        ranges = default_peer_ranges(R, n)
        pids = jnp.arange(R, dtype=jnp.int32)
        owners = np.asarray(owners_from_partitions(pids, R, n))
        for p, (s, e) in enumerate(ranges):
            for r in range(s, e):
                assert owners[r] == p

    def test_padding_maps_to_n(self):
        pids = jnp.array([-1, 0, 5, 99], dtype=jnp.int32)
        owners = np.asarray(owners_from_partitions(pids, 6, 3))
        assert owners[0] == 3 and owners[3] == 3
        assert 0 <= owners[1] < 3 and 0 <= owners[2] < 3


class TestRunColumnarShuffle:
    """Overflow-retry wrapper for device-resident repartitioning."""

    def test_skewed_destinations_trigger_retry(self, rng):
        from sparkucx_tpu.ops.columnar import ColumnarSpec, run_columnar_shuffle
        from sparkucx_tpu.ops.exchange import make_mesh

        n, cap = 4, 256
        mesh = make_mesh(n)
        rows = rng.normal(size=(n * cap, 4)).astype(np.float32)
        owners = np.zeros(n * cap, np.int32)  # everything to executor 0
        spec = ColumnarSpec(
            num_executors=n, capacity=cap, recv_capacity=cap, width=4, impl="dense"
        )
        recv, counts = run_columnar_shuffle(mesh, spec, rows, owners)
        per_dest = np.asarray(counts).sum(axis=1)
        assert per_dest[0] == n * cap and per_dest[1:].sum() == 0
        got = np.asarray(recv)[: n * cap]
        assert sorted(map(tuple, got)) == sorted(map(tuple, rows))

    def test_no_retry_when_balanced(self, rng):
        from sparkucx_tpu.ops.columnar import ColumnarSpec, run_columnar_shuffle
        from sparkucx_tpu.ops.exchange import make_mesh

        n, cap = 4, 64
        mesh = make_mesh(n)
        rows = rng.normal(size=(n * cap, 2)).astype(np.float32)
        owners = (np.arange(n * cap) % n).astype(np.int32)
        spec = ColumnarSpec(
            num_executors=n, capacity=cap, recv_capacity=2 * cap, width=2, impl="dense"
        )
        recv, counts = run_columnar_shuffle(mesh, spec, rows, owners)
        assert int(np.asarray(counts).sum()) == n * cap


class TestGatherRows:
    """gather_rows is the plain row gather at every width (the 25..32-lane
    chunking it once did measured slower on the chip, PR 48)."""

    def test_equivalence_across_widths(self):
        from sparkucx_tpu.ops.exchange import gather_rows

        rng = np.random.default_rng(0)
        idx = rng.permutation(257).astype(np.int32)
        for w in (1, 8, 24, 25, 31, 32, 33, 100):
            rows = rng.normal(size=(257, w)).astype(np.float32)
            got = np.asarray(jax.jit(gather_rows)(rows, idx))
            np.testing.assert_array_equal(got, rows[idx], err_msg=f"width {w}")
