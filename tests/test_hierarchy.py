"""Hierarchical (ICI+DCN) exchange vs the flat lowering and the CPU oracle —
bit-identical contract on a factored (2 slices x 4 chips) CPU mesh."""

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from sparkucx_tpu.ops.exchange import (
    ExchangeSpec,
    build_exchange,
    make_mesh,
    oracle_exchange,
    pack_chunks_slots,
    unpack_received,
)
from sparkucx_tpu.ops.hierarchy import build_hierarchical_exchange, make_hierarchical_mesh

S, C = 2, 4
N = S * C
SLOT = 16
LANE = 32  # 128-byte rows keep the test light


def _spec(slot=SLOT, lane=LANE):
    return ExchangeSpec(
        num_executors=N, send_rows=N * slot, recv_rows=N * slot, lane=lane, impl="dense"
    )


def _random_inputs(rng, slot=SLOT, lane=LANE):
    spec = _spec(slot, lane)
    data = rng.integers(-(2**31), 2**31 - 1, size=(N * spec.send_rows, lane), dtype=np.int32)
    sizes = rng.integers(0, slot + 1, size=(N, N), dtype=np.int32)
    return spec, data, sizes


class TestHierarchicalExchange:
    @pytest.mark.parametrize(
        "slot, lane, supersteps",
        # the second case: 64 KiB slots of 512-byte rows (the deployment's row
        # width), the received buffer fed back as the next superstep's send
        [(SLOT, LANE, 1), (128, 128, 2)],
        ids=["2KiB_slots", "64KiB_slots_chained"],
    )
    def test_bit_identical_to_flat(self, rng, slot, lane, supersteps):
        spec, data, sizes = _random_inputs(rng, slot, lane)

        flat_mesh = make_mesh(N)
        flat = build_exchange(flat_mesh, spec)
        sh = NamedSharding(flat_mesh, P("ex", None))
        f_recv, f_size_mat = jax.device_put(data, sh), jax.device_put(sizes, sh)
        for _ in range(supersteps):
            f_recv, f_sizes = flat(f_recv, f_size_mat)

        hmesh = make_hierarchical_mesh(S, C)
        hier = build_hierarchical_exchange(hmesh, spec)
        hsh = NamedSharding(hmesh, P(("dcn", "ici"), None))
        h_recv, h_size_mat = jax.device_put(data, hsh), jax.device_put(sizes, hsh)
        for _ in range(supersteps):
            h_recv, h_sizes = hier(h_recv, h_size_mat)

        assert np.array_equal(np.asarray(f_sizes), np.asarray(h_sizes))
        assert np.array_equal(np.asarray(f_recv), np.asarray(h_recv))

    def test_bytes_vs_oracle(self, rng):
        spec = _spec()
        row_bytes = LANE * 4
        chunks = [
            [
                rng.integers(0, 256, size=int(rng.integers(0, SLOT * row_bytes)), dtype=np.uint8).tobytes()
                for _ in range(N)
            ]
            for _ in range(N)
        ]
        bufs, size_rows = zip(
            *[pack_chunks_slots(chunks[i], SLOT, row_bytes) for i in range(N)]
        )
        data = np.concatenate(bufs)
        sizes = np.stack(size_rows)

        hmesh = make_hierarchical_mesh(S, C)
        hier = build_hierarchical_exchange(hmesh, spec)
        hsh = NamedSharding(hmesh, P(("dcn", "ici"), None))
        recv, recv_sizes = hier(jax.device_put(data, hsh), jax.device_put(sizes, hsh))

        recv_np = np.asarray(recv).reshape(N, spec.recv_rows * LANE).view(np.uint8)
        sizes_np = np.asarray(recv_sizes)
        want = oracle_exchange([[_pad(c, row_bytes) for c in row] for row in chunks])
        for j in range(N):
            got = b"".join(unpack_received(recv_np[j].tobytes(), sizes_np[j], row_bytes))
            assert got == want[j], f"receiver {j} mismatch"

    def test_mesh_shape_validation(self):
        spec = _spec()
        with pytest.raises(ValueError, match="mesh axes"):
            build_hierarchical_exchange(make_mesh(N), spec)
        hmesh = make_hierarchical_mesh(S, C)
        bad = ExchangeSpec(num_executors=4, send_rows=4 * SLOT, recv_rows=4 * SLOT, lane=LANE)
        with pytest.raises(ValueError, match="mesh"):
            build_hierarchical_exchange(hmesh, bad)

    def test_other_factorization(self, rng):
        # 4 slices x 2 chips over the same 8 devices
        spec, data, sizes = _random_inputs(rng)
        flat = build_exchange(make_mesh(N), spec)
        sh = NamedSharding(make_mesh(N), P("ex", None))
        f_recv, _ = flat(jax.device_put(data, sh), jax.device_put(sizes, sh))

        hmesh = make_hierarchical_mesh(4, 2)
        hier = build_hierarchical_exchange(hmesh, spec)
        hsh = NamedSharding(hmesh, P(("dcn", "ici"), None))
        h_recv, _ = hier(jax.device_put(data, hsh), jax.device_put(sizes, hsh))
        assert np.array_equal(np.asarray(f_recv), np.asarray(h_recv))


def _pad(chunk: bytes, row_bytes: int) -> bytes:
    rows = -(-len(chunk) // row_bytes)
    return chunk + b"\0" * (rows * row_bytes - len(chunk))
