"""Standalone verification of the ragged exchange's offset/size formulas.

XLA:CPU has no ragged-all-to-all kernel, so the multi-device CPU mesh only ever
executes the dense lowering — the exact offset math that would corrupt data on a
real pod (``ragged_params``, the layout contract of the reference's reply
packing, UcxWorkerWrapper.scala:397-448) is verified here instead by:

1. simulating ``jax.lax.ragged_all_to_all`` semantics in numpy, parameterized
   by the SAME ``ragged_params`` expressions the jitted collective traces, and
   property-testing the simulated receive buffers against ``oracle_exchange``
   for random n x n size matrices (n up to 8);
2. differentially comparing the simulation against the dense lowering actually
   executed on the 8-device CPU mesh (both must produce bit-identical tight
   sender-major receive buffers);
3. lowering the ragged impl on the CPU mesh (compile-time trace check).

A regression in any input/output offset formula fails 1 and 2.
"""

import numpy as np
import pytest

from sparkucx_tpu.ops.exchange import (
    ExchangeSpec,
    build_exchange,
    make_mesh,
    oracle_exchange,
    pack_chunks_slots,
    ragged_params,
    unpack_received,
)

ROW = 512
LANE = ROW // 4


def simulate_ragged_exchange(staged, sizes, slot_rows, recv_rows):
    """Numpy model of ``jax.lax.ragged_all_to_all`` over the executor axis.

    ``staged[i]`` is executor i's (n*slot_rows, lane) staging buffer; the
    update rule mirrors the documented semantics: sender i's rows
    ``[input_offsets[j], +send_sizes[j])`` land in receiver j's output at
    ``[output_offsets[j], +send_sizes[j])`` — with every parameter produced by
    ``ragged_params`` (xp=np), the same expressions the TPU path traces.
    """
    n = sizes.shape[0]
    outs = [np.zeros((recv_rows, staged[i].shape[1]), dtype=staged[i].dtype) for i in range(n)]
    for i in range(n):
        input_offsets, send_sizes, output_offsets, _recv_sizes = ragged_params(
            sizes, i, slot_rows, xp=np
        )
        for j in range(n):
            s = int(send_sizes[j])
            src = staged[i][int(input_offsets[j]) : int(input_offsets[j]) + s]
            outs[j][int(output_offsets[j]) : int(output_offsets[j]) + s] = src
    return outs


def random_chunks(rng, n, slot_rows, full=False):
    """Per-(sender, receiver) random byte chunks fitting the slot layout."""
    chunks = []
    for i in range(n):
        row = []
        for j in range(n):
            if full:
                nbytes = slot_rows * ROW
            else:
                rows = int(rng.integers(0, slot_rows + 1))
                nbytes = 0 if rows == 0 else int(rng.integers((rows - 1) * ROW + 1, rows * ROW + 1))
            row.append(rng.integers(0, 256, size=nbytes, dtype=np.uint8).tobytes())
        chunks.append(row)
    return chunks


def row_padded(chunk):
    pad = (-len(chunk)) % ROW
    return chunk + b"\x00" * pad


class TestRaggedParamsProperties:
    @pytest.mark.parametrize("trial", range(20))
    def test_simulated_ragged_matches_oracle(self, trial):
        rng = np.random.default_rng(1000 + trial)
        n = int(rng.integers(1, 9))
        slot_rows = int(rng.integers(1, 17))
        chunks = random_chunks(rng, n, slot_rows)
        staged, size_rows = zip(
            *(pack_chunks_slots(chunks[i], slot_rows, ROW) for i in range(n))
        )
        sizes = np.stack(size_rows)
        recv_rows = n * slot_rows
        outs = simulate_ragged_exchange(list(staged), sizes, slot_rows, recv_rows)
        expected = oracle_exchange(
            [[row_padded(c) for c in sender] for sender in chunks]
        )
        for j in range(n):
            got = np.asarray(outs[j]).reshape(-1).view(np.uint8)
            total = int(sizes[:, j].sum()) * ROW
            assert got[:total].tobytes() == expected[j], f"receiver {j} corrupted (n={n})"
            # per-sender split must also line up (unpack_received contract)
            parts = unpack_received(got[:total].tobytes(), sizes[:, j], ROW)
            for i in range(n):
                assert parts[i] == row_padded(chunks[i][j])

    @pytest.mark.parametrize("n", [1, 2, 5, 8])
    def test_full_slots(self, n):
        # every chunk exactly fills its slot: offsets are pure slot arithmetic
        rng = np.random.default_rng(n)
        slot_rows = 4
        chunks = random_chunks(rng, n, slot_rows, full=True)
        staged, size_rows = zip(
            *(pack_chunks_slots(chunks[i], slot_rows, ROW) for i in range(n))
        )
        sizes = np.stack(size_rows)
        outs = simulate_ragged_exchange(list(staged), sizes, slot_rows, n * slot_rows)
        expected = oracle_exchange(chunks)
        for j in range(n):
            got = np.asarray(outs[j]).reshape(-1).view(np.uint8)
            assert got.tobytes() == expected[j]

    def test_empty_and_skewed(self):
        # adversarial skew: one hot receiver, several empty senders
        n, slot_rows = 6, 8
        chunks = [[b""] * n for _ in range(n)]
        rng = np.random.default_rng(7)
        for i in range(n):
            chunks[i][3] = rng.integers(0, 256, size=slot_rows * ROW, dtype=np.uint8).tobytes()
        staged, size_rows = zip(
            *(pack_chunks_slots(chunks[i], slot_rows, ROW) for i in range(n))
        )
        sizes = np.stack(size_rows)
        outs = simulate_ragged_exchange(list(staged), sizes, slot_rows, n * slot_rows)
        expected = oracle_exchange(chunks)
        for j in range(n):
            got = np.asarray(outs[j]).reshape(-1).view(np.uint8)
            total = int(sizes[:, j].sum()) * ROW
            assert got[:total].tobytes() == expected[j]


class TestCompactLayoutParams:
    """The compact-input-layout variant (``slot_rows=None``) — the parameter
    set the columnar shuffle and distributed sort pass to ragged_all_to_all
    (ops/columnar.py size_matrix_from_owners / columnar_shard_ragged)."""

    @pytest.mark.parametrize("trial", range(10))
    def test_compact_simulation_matches_sender_major_contract(self, trial):
        rng = np.random.default_rng(2000 + trial)
        n = int(rng.integers(1, 9))
        sizes = rng.integers(0, 6, size=(n, n)).astype(np.int32)
        width = 4

        def tag(i, j, k):  # distinguishable row content
            return np.full(width, i * 10000 + j * 100 + k, dtype=np.int32)

        # sender i's compact payload: chunks for j = 0..n-1 back to back
        payloads = []
        for i in range(n):
            rows = [tag(i, j, k) for j in range(n) for k in range(sizes[i, j])]
            buf = np.stack(rows) if rows else np.zeros((0, width), np.int32)
            payloads.append(buf)

        recv_cap = max(1, int(sizes.sum(axis=0).max()))
        outs = [np.zeros((recv_cap, width), np.int32) for _ in range(n)]
        for i in range(n):
            input_offsets, send_sizes, output_offsets, _ = ragged_params(
                sizes, i, None, xp=np
            )
            for j in range(n):
                s = int(send_sizes[j])
                src = payloads[i][int(input_offsets[j]) : int(input_offsets[j]) + s]
                outs[j][int(output_offsets[j]) : int(output_offsets[j]) + s] = src

        for j in range(n):
            expected = [tag(i, j, k) for i in range(n) for k in range(sizes[i, j])]
            total = len(expected)
            if total:
                assert np.array_equal(outs[j][:total], np.stack(expected)), (
                    f"receiver {j} sender-major layout corrupted (n={n})"
                )


class TestRaggedVsDenseDifferential:
    """The dense lowering executes on the CPU mesh; the ragged simulation uses
    the traced formulas — both must land every byte identically."""

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_dense_execution_matches_ragged_simulation(self, seed):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        rng = np.random.default_rng(seed)
        n = 8
        slot_rows = int(rng.integers(2, 9))
        chunks = random_chunks(rng, n, slot_rows)
        staged, size_rows = zip(
            *(pack_chunks_slots(chunks[i], slot_rows, ROW) for i in range(n))
        )
        sizes = np.stack(size_rows)

        sim = simulate_ragged_exchange(list(staged), sizes, slot_rows, n * slot_rows)

        spec = ExchangeSpec(
            num_executors=n,
            send_rows=n * slot_rows,
            recv_rows=n * slot_rows,
            lane=LANE,
            impl="dense",
        )
        mesh = make_mesh(n)
        fn = build_exchange(mesh, spec)
        data = jax.device_put(
            np.concatenate(staged), NamedSharding(mesh, P("ex", None))
        )
        size_mat = jax.device_put(sizes, NamedSharding(mesh, P("ex", None)))
        recv, recv_sizes = fn(data, size_mat)
        recv = np.asarray(recv)
        recv_sizes = np.asarray(recv_sizes)
        for j in range(n):
            total = int(sizes[:, j].sum())
            shard = recv[j * n * slot_rows : (j + 1) * n * slot_rows]
            assert np.array_equal(recv_sizes[j], sizes[:, j])
            assert np.array_equal(
                shard[:total], sim[j][:total]
            ), f"dense execution != ragged simulation at receiver {j}"


class TestRaggedOnTpu:
    def test_ragged_n1_roundtrip_real_chip(self):
        """On real TPU hardware: execute the ragged lowering (n=1 degenerate
        self-exchange) over several non-trivially sized payloads and assert
        against pack_chunks_slots + oracle.  Skipped where ragged can't run."""
        import jax

        if jax.devices()[0].platform != "tpu":
            pytest.skip("ragged_all_to_all executes only on TPU")
        from jax.sharding import NamedSharding, PartitionSpec as P

        rng = np.random.default_rng(5)
        slot_rows = 64
        for nbytes in (1, ROW - 1, 17 * ROW + 13, slot_rows * ROW):
            chunk = rng.integers(0, 256, size=nbytes, dtype=np.uint8).tobytes()
            staged, sizes = pack_chunks_slots([chunk], slot_rows, ROW)
            spec = ExchangeSpec(
                num_executors=1, send_rows=slot_rows, recv_rows=slot_rows,
                lane=LANE, impl="ragged",
            )
            mesh = make_mesh(1)
            fn = build_exchange(mesh, spec)
            recv, recv_sizes = fn(
                jax.device_put(staged, NamedSharding(mesh, P("ex", None))),
                jax.device_put(sizes[None, :], NamedSharding(mesh, P("ex", None))),
            )
            got = np.asarray(recv).reshape(-1).view(np.uint8)
            total = int(np.asarray(recv_sizes)[0, 0]) * ROW
            assert got[:total].tobytes() == row_padded(chunk), f"nbytes={nbytes}"


class TestRaggedLowering:
    def test_ragged_impl_lowers_on_cpu_mesh(self):
        # compile-time trace check: the ragged path must build a valid HLO even
        # where no CPU kernel exists to run it
        n, slot_rows = 8, 4
        spec = ExchangeSpec(
            num_executors=n,
            send_rows=n * slot_rows,
            recv_rows=n * slot_rows,
            lane=LANE,
            impl="ragged",
        )
        mesh = make_mesh(n)
        fn = build_exchange(mesh, spec)
        import jax

        data = jax.ShapeDtypeStruct((n * n * slot_rows, LANE), np.int32)
        sizes = jax.ShapeDtypeStruct((n, n), np.int32)
        lowered = fn.lower(data, sizes)
        assert "ragged" in lowered.as_text().lower()
