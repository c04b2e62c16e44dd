"""Tests for the shuffle exchange collective on the virtual 8-device CPU mesh.

The dense lowering executes here; the ragged lowering (TPU-only kernel) is checked
down to StableHLO.  Both produce the same tight sender-major receive layout, so
these oracle tests pin the contract for both.
"""

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from sparkucx_tpu.ops.exchange import (
    ExchangeSpec,
    build_exchange,
    exclusive_cumsum,
    make_mesh,
    oracle_exchange,
    pack_chunks_slots,
    unpack_received,
)

N = 8
LANE = 32           # 128-byte rows in tests (lane=128 / 512 B on real TPU)
ROW_BYTES = LANE * 4
SLOT_ROWS = 64      # per-peer region: 8 KiB


def _spec(impl="dense"):
    return ExchangeSpec(
        num_executors=N,
        send_rows=N * SLOT_ROWS,
        recv_rows=N * SLOT_ROWS,
        lane=LANE,
        impl=impl,
    )


def _run_exchange(chunks, spec, mesh, fn):
    bufs, sizes = zip(*[pack_chunks_slots(chunks[i], SLOT_ROWS, ROW_BYTES) for i in range(N)])
    data = np.concatenate(bufs, axis=0)
    size_mat = np.stack(sizes).astype(np.int32)
    data_j = jax.device_put(data, NamedSharding(mesh, P("ex", None)))
    sm_j = jax.device_put(size_mat, NamedSharding(mesh, P("ex", None)))
    recv, recv_sizes = fn(data_j, sm_j)
    return np.asarray(recv), np.asarray(recv_sizes)


def _padded(chunk):
    pad = (-len(chunk)) % ROW_BYTES
    return chunk + b"\x00" * pad


def _verify_against_oracle(chunks, recv, recv_sizes, spec):
    padded = [[_padded(c) for c in row] for row in chunks]
    expected = oracle_exchange(padded)
    for j in range(N):
        shard = recv[j * spec.recv_rows : (j + 1) * spec.recv_rows].reshape(-1).view(np.uint8).tobytes()
        total = int(recv_sizes[j].sum()) * ROW_BYTES
        assert shard[:total] == expected[j], f"receiver {j} mismatch"
        per_sender = unpack_received(shard, recv_sizes[j], ROW_BYTES)
        for i in range(N):
            assert per_sender[i][: len(chunks[i][j])] == chunks[i][j]


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(N)


@pytest.fixture(scope="module")
def dense_fn(mesh):
    return build_exchange(mesh, _spec())


class TestDenseExchange:
    def test_random_skewed_vs_oracle(self, mesh, dense_fn, rng):
        spec = dense_fn.spec
        max_bytes = SLOT_ROWS * ROW_BYTES // 2
        chunks = [
            [rng.integers(0, 256, size=int(rng.integers(0, max_bytes)), dtype=np.uint8).tobytes() for _ in range(N)]
            for _ in range(N)
        ]
        recv, recv_sizes = _run_exchange(chunks, spec, mesh, dense_fn)
        _verify_against_oracle(chunks, recv, recv_sizes, spec)

    def test_empty_chunks(self, mesh, dense_fn):
        # Empty partitions are the common case in skewed shuffles.
        chunks = [[b"" for _ in range(N)] for _ in range(N)]
        chunks[3][5] = b"only-block" * 3
        recv, recv_sizes = _run_exchange(chunks, dense_fn.spec, mesh, dense_fn)
        assert recv_sizes[5][3] == 1  # 30 bytes -> 1 row
        assert recv_sizes.sum() == 1
        _verify_against_oracle(chunks, recv, recv_sizes, dense_fn.spec)

    def test_identity_diagonal(self, mesh, dense_fn):
        # Every executor keeps one local chunk (self-send over the collective).
        chunks = [[b"" if i != j else bytes([i]) * 200 for j in range(N)] for i in range(N)]
        recv, recv_sizes = _run_exchange(chunks, dense_fn.spec, mesh, dense_fn)
        _verify_against_oracle(chunks, recv, recv_sizes, dense_fn.spec)

    def test_reuse_compiled_across_supersteps(self, mesh, dense_fn):
        # One compiled exchange serves many supersteps (no retrace): different data.
        for step in range(3):
            chunks = [
                [bytes([step, i, j]) * (10 * (i + j + 1)) for j in range(N)] for i in range(N)
            ]
            recv, recv_sizes = _run_exchange(chunks, dense_fn.spec, mesh, dense_fn)
            _verify_against_oracle(chunks, recv, recv_sizes, dense_fn.spec)

    def test_full_slots(self, mesh, dense_fn, rng):
        spec = dense_fn.spec
        full = SLOT_ROWS * ROW_BYTES
        chunks = [
            [rng.integers(0, 256, size=full, dtype=np.uint8).tobytes() for _ in range(N)]
            for _ in range(N)
        ]
        recv, recv_sizes = _run_exchange(chunks, spec, mesh, dense_fn)
        assert int(recv_sizes.sum()) == N * N * SLOT_ROWS
        _verify_against_oracle(chunks, recv, recv_sizes, spec)


    def test_full_64k_slots_on_four_executors_chained(self, rng):
        """The deployment's row width (512-byte rows) on a narrower mesh:
        four executors, every 64 KiB slot full, the received buffer fed back
        as the next superstep's send.  With full slots the exchange is a
        transpose of slots, so two supersteps give the input back."""
        n, lane, slot = 4, 128, 128
        spec = ExchangeSpec(
            num_executors=n, send_rows=n * slot, recv_rows=n * slot, lane=lane, impl="dense"
        )
        mesh4 = make_mesh(n)
        fn = build_exchange(mesh4, spec)
        sharding = NamedSharding(mesh4, P("ex", None))
        data = rng.integers(-100, 100, size=(n * n * slot, lane), dtype=np.int32)
        sizes = jax.device_put(np.full((n, n), slot, dtype=np.int32), sharding)
        once, recv_sizes = fn(jax.device_put(data, sharding), sizes)
        once = np.asarray(once)
        slots = data.reshape(n, n, slot, lane)  # [sender, receiver]
        np.testing.assert_array_equal(
            once.reshape(n, n, slot, lane), slots.transpose(1, 0, 2, 3)
        )
        assert int(np.asarray(recv_sizes).sum()) == n * n * slot
        twice, _ = fn(jax.device_put(once, sharding), sizes)
        np.testing.assert_array_equal(np.asarray(twice), data)


class TestRaggedLowering:
    def test_ragged_lowers_to_stablehlo(self, mesh):
        # XLA:CPU can't execute ragged-all-to-all, but tracing/lowering must work —
        # this pins the TPU path's graph without TPU hardware.
        spec = _spec(impl="ragged")
        fn = build_exchange(mesh, spec)
        data = jax.ShapeDtypeStruct((N * spec.send_rows, LANE), np.int32)
        sizes = jax.ShapeDtypeStruct((N, N), np.int32)
        text = fn.lower(data, sizes).as_text()
        assert "ragged_all_to_all" in text or "ragged-all-to-all" in text

    def test_auto_resolves_dense_on_cpu(self, mesh):
        fn = build_exchange(mesh, _spec(impl="auto"))
        assert fn.spec.impl == "dense"


def _auto_spec(name):
    from sparkucx_tpu.ops.columnar import ColumnarSpec
    from sparkucx_tpu.ops.relational import AggregateSpec, JoinSpec
    from sparkucx_tpu.ops.sort import SortSpec
    from sparkucx_tpu.ops.tc import TcSpec

    return {
        "exchange": lambda: ExchangeSpec(num_executors=4, send_rows=64, recv_rows=64),
        "columnar": lambda: ColumnarSpec(num_executors=4, capacity=8, recv_capacity=8, width=1),
        "sort": lambda: SortSpec(num_executors=4, capacity=8, recv_capacity=16),
        "tc": lambda: TcSpec(num_executors=4, edge_capacity=8, tc_capacity=8, join_capacity=8),
        "aggregate": lambda: AggregateSpec(
            num_executors=4, capacity=8, recv_capacity=8, aggs=("sum",)
        ),
        "join": lambda: JoinSpec(
            num_executors=4, build_capacity=8, build_recv_capacity=8, build_width=1,
            probe_capacity=8, probe_recv_capacity=8, probe_width=1, out_capacity=8,
        ),
    }[name]()


class TestCollectiveImplRule:
    """"Ragged on TPU, dense elsewhere" is ``resolve_collective_impl``'s to
    decide; the six specs' ``resolve_impl`` ask it and test no platform."""

    @pytest.mark.parametrize("platform,want", [("tpu", "ragged"), ("cpu", "dense")])
    @pytest.mark.parametrize(
        "name", ["exchange", "columnar", "sort", "tc", "aggregate", "join"]
    )
    def test_every_spec_resolves_auto_by_it(self, name, platform, want):
        import inspect
        from dataclasses import replace

        from sparkucx_tpu.ops.exchange import resolve_collective_impl

        spec = _auto_spec(name)
        assert spec.impl == "auto"
        assert resolve_collective_impl("auto", platform) == want
        assert spec.resolve_impl(platform).impl == want
        named = replace(spec, impl="dense")  # a caller's own choice passes through
        assert resolve_collective_impl("dense", platform) == "dense"
        assert named.resolve_impl(platform) == named
        assert "tpu" not in inspect.getsource(type(spec).resolve_impl).split('"""')[-1]

    def test_platform_defaults_to_the_first_device(self):
        from sparkucx_tpu.ops.exchange import resolve_collective_impl

        assert jax.devices()[0].platform == "cpu"
        assert resolve_collective_impl("auto") == "dense"


class TestLocalLowering:
    """The n=1 degenerate exchange lowers to the Pallas DMA prefix copy on
    TPU ('local'); its resolve/validate logic is platform-independent and the
    kernel itself runs on hardware in every one-chip cell of ``benchmark/``."""

    def test_auto_resolves_local_on_tpu_n1(self):
        spec = ExchangeSpec(num_executors=1, send_rows=64, recv_rows=64)
        assert spec.resolve_impl(platform="tpu").impl == "local"

    def test_auto_resolves_ragged_on_tpu_n_gt_1(self):
        spec = ExchangeSpec(num_executors=4, send_rows=64, recv_rows=64)
        assert spec.resolve_impl(platform="tpu").impl == "ragged"

    def test_auto_resolves_dense_on_cpu_n1(self):
        spec = ExchangeSpec(num_executors=1, send_rows=64, recv_rows=64)
        assert spec.resolve_impl(platform="cpu").impl == "dense"

    def test_local_rejected_for_multi_executor(self):
        spec = ExchangeSpec(num_executors=2, send_rows=64, recv_rows=64, impl="local")
        with pytest.raises(ValueError, match="n=1 degenerate"):
            spec.validate()


class TestPacking:
    def test_slot_packing_offsets(self):
        buf, sizes = pack_chunks_slots([b"a" * 100, b"b" * 300], slot_rows=8, row_bytes=128)
        assert sizes.tolist() == [1, 3]  # 100 B -> 1 row, 300 B -> 3 rows
        raw = buf.reshape(-1).view(np.uint8)
        assert raw[:100].tobytes() == b"a" * 100
        assert raw[8 * 128 : 8 * 128 + 300].tobytes() == b"b" * 300

    def test_slot_overflow_raises(self):
        with pytest.raises(ValueError, match="exceeds slot"):
            pack_chunks_slots([b"x" * 2048], slot_rows=8, row_bytes=128)

    def test_unpack_received(self):
        shard = b"A" * 256 + b"B" * 128
        parts = unpack_received(shard, np.array([2, 1]), 128)
        assert parts == [b"A" * 256, b"B" * 128]


class TestSpec:
    def test_exclusive_cumsum(self):
        import jax.numpy as jnp

        got = exclusive_cumsum(jnp.array([3, 1, 4, 1]))
        assert got.tolist() == [0, 3, 4, 8]

    def test_mesh_size_mismatch_raises(self, mesh):
        with pytest.raises(ValueError, match="mesh size"):
            build_exchange(mesh, ExchangeSpec(num_executors=4, send_rows=64, recv_rows=64))

    def test_slot_divisibility(self, mesh):
        with pytest.raises(ValueError, match="divisible"):
            build_exchange(
                mesh, ExchangeSpec(num_executors=N, send_rows=1001, recv_rows=1001, impl="dense")
            )

    def test_row_bytes(self):
        assert _spec().row_bytes == ROW_BYTES
        assert ExchangeSpec(num_executors=1, send_rows=8, recv_rows=8).row_bytes == 512
