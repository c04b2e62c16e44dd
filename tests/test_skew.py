"""Skew-aware exchange planning (ops/skew.py; conf slotQuotaRows).

Two layers of pinning: planner geometry as pure-host property tests (quota
bucketing, chunk row conservation, slice/reassemble round-trip vs a direct
oracle), and transport bit-equality — a quota-capped cluster run must produce
byte-for-byte the receive state of the default single-shot run, across all
three host_recv_modes, multi-round spill, and device staging.  The quota only
reshapes staging/wire geometry; it must never touch bytes.
"""

import os

import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.block import MemoryBlock, ShuffleBlockId
from sparkucx_tpu.core.operation import OperationStatus
from sparkucx_tpu.ops.skew import (
    ExchangePlan,
    chunk_size_rows,
    pad_rows_pow2,
    piece_slices,
    plan_exchange,
    quota_slot_rows,
    reassemble_round,
    slice_subround,
    staging_occupancy,
)
from sparkucx_tpu.transport.tpu import TpuShuffleCluster
from sparkucx_tpu.utils.stats import StatsAggregator

N_EXEC = 4


def zipf_size_matrix(executors: int, max_peer_rows: int, alpha: float) -> np.ndarray:
    """A deterministic Zipf-skewed exchange size matrix: ``sizes[i, j]`` rows
    from sender i to destination j follow ``(rank + 1) ** -alpha`` scaled so
    each sender's hottest lane is ``max_peer_rows`` (min 1 row), with the rank
    order permuted per sender (seeded) so the hot destination varies — the
    shape real shuffle workloads take (TPC-DS/TPC-H are Zipf-skewed)."""
    n = executors
    rng = np.random.default_rng(0)
    weights = (np.arange(1, n + 1, dtype=np.float64)) ** (-alpha)
    base = np.maximum(1, np.round(max_peer_rows * weights / weights[0])).astype(np.int64)
    sizes = np.empty((n, n), dtype=np.int32)
    for i in range(n):
        sizes[i] = base[rng.permutation(n)]
    return sizes


# ----------------------------------------------------------------------
# planner geometry (pure host, no mesh)


class TestQuotaSlotRows:
    def test_pow2_bucket(self):
        assert quota_slot_rows(100, 0) == 128  # no quota: pow2 of the slot
        assert quota_slot_rows(64, 0) == 64  # pow2 slot is a fixed point
        assert quota_slot_rows(1, 0) == 1

    def test_cap_then_bucket(self):
        assert quota_slot_rows(100, 64) == 64
        assert quota_slot_rows(100, 50) == 64  # cap 50, then pow2
        assert quota_slot_rows(8, 1000) == 8  # quota above slot: inert

    def test_rejects_nonpositive_slot(self):
        with pytest.raises(ValueError, match="slot_rows"):
            quota_slot_rows(0, 16)


class TestPlanExchange:
    def test_chunk_counts_cover_hottest_lane(self):
        plan = plan_exchange([100, 0, 5], 128, 32)
        assert plan.slot_rows == 32
        assert plan.chunks_per_round == (4, 1, 1)  # ceil(100/32), min 1
        assert plan.num_subrounds == 6

    def test_empty_round_still_runs_one_subround(self):
        # SPMD lockstep: every executor must dispatch every collective
        plan = plan_exchange([0], 128, 32)
        assert plan.chunks_per_round == (1,)

    def test_subround_order_chunk_major(self):
        plan = ExchangePlan(slot_rows=16, chunks_per_round=(2, 1))
        assert plan.subrounds() == [(0, 0, 2), (0, 1, 2), (1, 0, 1)]

    def test_staged_rows_reduction_on_zipf_skew(self):
        """The acceptance geometry: on a Zipf-skewed matrix whose hottest lane
        sits just past a pow2 boundary, the quota plan stages (and, dense,
        wires) strictly fewer rows than the single-shot pow2 bucket."""
        n = 8
        sizes = zipf_size_matrix(n, 2200, 1.2)
        assert int(sizes.max()) == 2200
        slot = quota_slot_rows(int(sizes.max()), 0)  # single-shot bucket: 4096
        quota = quota_slot_rows(slot, int(np.ceil(sizes.mean())))
        plan = plan_exchange([int(sizes.max())], slot, quota)
        single_shot = n * n * slot
        assert plan.staged_rows(n) < single_shot
        # quota plan covers the data: chunks * slot >= hottest lane
        assert plan.chunks_per_round[0] * plan.slot_rows >= int(sizes.max())


class TestChunkGeometry:
    def test_row_conservation_and_cap(self, rng):
        """Summing chunk_size_rows over a plan's chunks reproduces the size
        row exactly, and no chunk exceeds the quota slot."""
        for _ in range(20):
            n = int(rng.integers(1, 9))
            slot = int(rng.integers(1, 200))
            sizes = rng.integers(0, slot + 1, size=n).astype(np.int32)
            q = quota_slot_rows(slot, int(rng.integers(1, slot + 1)))
            nchunks = plan_exchange([int(sizes.max())], slot, q).chunks_per_round[0]
            chunks = [chunk_size_rows(sizes, c, q) for c in range(nchunks)]
            assert all(int(c.max(initial=0)) <= q for c in chunks)
            np.testing.assert_array_equal(np.sum(chunks, axis=0), sizes)

    def test_slice_reassemble_matches_direct_oracle(self, rng):
        """Sender-side slicing + a simulated compacting exchange + receiver
        reassembly reproduces, byte for byte, the tight sender-major buffer a
        single-shot exchange produces (sliced straight from the payloads)."""
        n, lane = 5, 4
        row_bytes = lane * 4
        slot = 23
        q = 8  # ceil(23/8) = 3 sub-rounds
        nchunks = plan_exchange([slot], slot, q).chunks_per_round[0]
        sizes = rng.integers(0, slot + 1, size=(n, n)).astype(np.int32)
        payloads = [
            rng.integers(-100, 100, size=(n * slot, lane), dtype=np.int32)
            for _ in range(n)
        ]
        sub_size_mats = [
            np.stack([chunk_size_rows(sizes[i], c, q) for i in range(n)])
            for c in range(nchunks)
        ]
        for j in range(n):
            # what the dense lowering compacts for consumer j in sub-round c
            sub_shards = []
            for c in range(nchunks):
                pieces = [
                    slice_subround(payloads[i], n, c, q)[
                        j * q : j * q + int(sub_size_mats[c][i, j])
                    ]
                    for i in range(n)
                ]
                sub_shards.append(
                    np.concatenate(pieces).reshape(-1).view(np.uint8)
                )
            got = reassemble_round(
                sub_shards, [m[:, j] for m in sub_size_mats], row_bytes
            )
            want = np.concatenate(
                [payloads[i][j * slot : j * slot + int(sizes[i, j])] for i in range(n)]
            ).reshape(-1).view(np.uint8)
            assert bytes(got) == bytes(want), f"consumer {j} diverged"

    def test_slice_subround_all_pad_window(self):
        p = np.arange(2 * 4 * 3, dtype=np.int32).reshape(8, 3)  # n=2, slot=4
        out = slice_subround(p, 2, chunk=2, quota_slot=2)  # window [4, 6) >= slot
        assert out.shape == (4, 3) and not out.any()

    def test_slice_subround_rejects_ragged_payload(self):
        with pytest.raises(ValueError, match="not a multiple"):
            slice_subround(np.zeros((7, 4), dtype=np.int32), 2, 0, 2)

    def test_piece_slices_skips_zero_rows(self):
        subs = [np.array([2, 0, 1]), np.array([0, 0, 3])]
        assert piece_slices(subs) == [(0, 0, 2), (0, 2, 1), (1, 0, 3)]

    def test_reassemble_empty_is_empty(self):
        out = reassemble_round([np.zeros(0, np.uint8)], [np.array([0, 0])], 16)
        assert out.dtype == np.uint8 and out.size == 0

    def test_staging_occupancy(self):
        used, padded = staging_occupancy(np.array([3, 0, 5]), 8)
        assert (used, padded) == (8, 16)

    def test_pad_rows_pow2(self):
        a = np.ones((5, 2), dtype=np.int32)
        out = pad_rows_pow2(a)
        assert out.shape == (8, 2) and int(out.sum()) == 10
        same = pad_rows_pow2(np.ones((4, 2), dtype=np.int32))
        assert same.shape == (4, 2)


# ----------------------------------------------------------------------
# conf surface


class TestConf:
    def test_spark_key_parses(self):
        conf = TpuShuffleConf.from_spark_conf(
            {"spark.shuffle.tpu.slotQuotaRows": "64"}
        )
        assert conf.slot_quota_rows == 64

    def test_negative_rejected(self):
        with pytest.raises(ValueError, match="slot_quota_rows"):
            TpuShuffleConf(slot_quota_rows=-1).validate()


# ----------------------------------------------------------------------
# padding telemetry


class TestPaddingTelemetry:
    def test_record_rows_and_padding_fraction(self):
        stats = StatsAggregator()
        stats.record_rows("exchange.lanes", used_rows=6, padded_rows=2)
        stats.record_rows("exchange.lanes", used_rows=2, padded_rows=6)
        s = stats.summary("exchange.lanes")
        assert (s.used_rows, s.padded_rows) == (8, 8)
        assert s.padding_fraction == 0.5
        assert "padding=50.0%" in stats.report()

    def test_padding_fraction_zero_when_unpopulated(self):
        from sparkucx_tpu.utils.stats import StatsSummary

        assert StatsSummary().padding_fraction == 0.0

    def test_pipeline_drain_carries_occupancy(self):
        from sparkucx_tpu.transport.pipeline import RoundPipeline

        stats = StatsAggregator()
        pipe = RoundPipeline(
            1,
            lambda rnd: rnd,
            lambda rnd, t: t,
            name="p",
            stats=stats,
            result_rows=lambda r: (10, 6),
        )
        pipe.run(2)
        s = stats.summary("p.drain")
        assert (s.used_rows, s.padded_rows) == (20, 12)
        assert s.padding_fraction == pytest.approx(12 / 32)


# ----------------------------------------------------------------------
# pack_chunks_slots tail hygiene (np.empty fast path)


class TestPackChunksSlots:
    def test_final_row_tail_zeroed(self):
        from sparkucx_tpu.ops.exchange import pack_chunks_slots

        row_bytes = 16
        chunks = [b"\xff" * 5, b"", b"\xaa" * 16, b"\xbb" * 17]
        buf, sizes = pack_chunks_slots(chunks, slot_rows=4, row_bytes=row_bytes)
        np.testing.assert_array_equal(sizes, [1, 0, 1, 2])
        flat = buf.reshape(-1).view(np.uint8)
        for j, chunk in enumerate(chunks):
            start = j * 4 * row_bytes
            rows = -(-len(chunk) // row_bytes)
            assert flat[start : start + len(chunk)].tobytes() == chunk
            # the used final row's tail is zero (it DOES reach receivers)
            tail = flat[start + len(chunk) : start + rows * row_bytes]
            assert not tail.any()

    def test_oversized_chunk_rejected(self):
        from sparkucx_tpu.ops.exchange import pack_chunks_slots

        with pytest.raises(ValueError, match="exceeds slot"):
            pack_chunks_slots([b"x" * 100], slot_rows=2, row_bytes=16)


# ----------------------------------------------------------------------
# transport bit-equality: quota vs default through the full cluster


def _buf(n):
    return MemoryBlock(np.zeros(n, dtype=np.uint8), size=n)


def _write_skewed(cluster, shuffle_id, M, R, seed=77):
    """Zipf-flavored writes: reduce 0 is hot (big blocks), the rest cold —
    the skew the quota exists to absorb.  Same seed -> identical streams."""
    meta = cluster.create_shuffle(shuffle_id, M, R)
    rng = np.random.default_rng(seed)
    oracle = {}
    for m in range(M):
        t = cluster.transport(meta.map_owner[m])
        w = t.store.map_writer(shuffle_id, m)
        for r in range(R):
            size = int(rng.integers(2000, 3000)) if r == 0 else int(rng.integers(1, 300))
            payload = rng.integers(0, 256, size=size, dtype=np.uint8).tobytes()
            oracle[(m, r)] = payload
            w.write_partition(r, payload)
        t.commit_block(w.commit().pack())
    return meta, oracle


def _fetch_all(cluster, meta, shuffle_id, M, R, oracle):
    for r in range(R):
        consumer = meta.owner_of_reduce(r)
        t = cluster.transport(consumer)
        bufs = [_buf(8192) for _ in range(M)]
        reqs = t.fetch_blocks_by_block_ids(
            consumer, [ShuffleBlockId(shuffle_id, m, r) for m in range(M)],
            bufs, [None] * M,
        )
        for m in range(M):
            res = reqs[m].wait(5)
            assert res.status == OperationStatus.SUCCESS, str(res.error)
            assert bufs[m].host_view()[: bufs[m].size].tobytes() == oracle[(m, r)]


def _conf(quota, mode="array", **kw):
    return TpuShuffleConf(
        staging_capacity_per_executor=N_EXEC * 4096,
        block_alignment=128,
        num_executors=N_EXEC,
        host_recv_mode=mode,
        slot_quota_rows=quota,
        **kw,
    )


def _exchange(conf, M=3 * N_EXEC, R=8):
    cluster = TpuShuffleCluster(conf, num_executors=N_EXEC)
    meta, oracle = _write_skewed(cluster, 0, M, R)
    cluster.run_exchange(0)
    return cluster, meta, oracle


class TestClusterBitEquality:
    def test_array_mode_matches_default_bitwise(self):
        """Quota-capped multi-round exchange vs the single-shot default, same
        seeded writes: identical logical receive sizes, and every consumer's
        tight shard is a byte-exact prefix of the default's receive buffer."""
        base_cluster, base_meta, oracle = _exchange(_conf(0))
        q_cluster, q_meta, _ = _exchange(_conf(8))
        assert len(base_meta.recv_sizes) > 1, "should spill multiple rounds"
        assert len(q_meta.recv_sizes) == len(base_meta.recv_sizes)
        for rnd in range(len(base_meta.recv_sizes)):
            np.testing.assert_array_equal(
                q_meta.recv_sizes[rnd], base_meta.recv_sizes[rnd]
            )
            for j in range(N_EXEC):
                tight = q_meta.recv_shards[rnd][j]
                used = int(base_meta.recv_sizes[rnd][j].sum()) * 128
                assert tight.nbytes == used  # quota shards carry no padding
                assert bytes(tight) == bytes(base_meta.recv_shards[rnd][j][:used])
        _fetch_all(q_cluster, q_meta, 0, 3 * N_EXEC, 8, oracle)
        # the quota engine ran chunked: padding telemetry was recorded
        drain = q_cluster.stats.summary("exchange.pipeline.drain")
        assert drain.used_rows > 0 and drain.padded_rows > 0

    def test_quota_zero_is_default_path(self):
        """slotQuotaRows=0 (the default) must never enter the quota engine."""
        cluster, meta, oracle = _exchange(_conf(0))
        _fetch_all(cluster, meta, 0, 3 * N_EXEC, 8, oracle)

    def test_memmap_mode(self, tmp_path):
        conf = _conf(8, mode="memmap", spill_dir=str(tmp_path))
        cluster, meta, oracle = _exchange(conf)
        for rnd in meta.recv_shards:
            for shard in rnd:
                # tight shards spill to read-only mappings; a consumer that
                # received nothing keeps an empty array (nothing to map)
                assert isinstance(shard, np.memmap) or shard.nbytes == 0
                if isinstance(shard, np.memmap):
                    assert not shard.flags.writeable
        spilled = [p for p, _ in meta.recv_spill_paths]
        assert spilled and all(os.path.exists(p) for p in spilled)
        _fetch_all(cluster, meta, 0, 3 * N_EXEC, 8, oracle)
        cluster.remove_shuffle(0)
        assert not any(os.path.exists(p) for p in spilled), "spill leaked"

    def test_device_mode(self):
        conf = _conf(8, mode="device", keep_device_recv=True)
        cluster, meta, oracle = _exchange(conf)
        assert meta.recv_shards is None, "device mode must keep no host copy"
        assert meta.recv_device is not None
        _fetch_all(cluster, meta, 0, 3 * N_EXEC, 8, oracle)

    def test_device_staging_rounds(self):
        """Device-sealed rounds take the on-device chunk-slicing arm of the
        quota submit (slice_subround with xp=jnp)."""
        conf = _conf(8, device_staging=True)
        cluster, meta, oracle = _exchange(conf)
        _fetch_all(cluster, meta, 0, 3 * N_EXEC, 8, oracle)

    def test_quota_above_slot_matches_default(self):
        """A quota larger than the staging slot plans one chunk per round —
        geometry identical to the default bucket, bytes identical too."""
        base_cluster, base_meta, oracle = _exchange(_conf(0))
        q_cluster, q_meta, _ = _exchange(_conf(1 << 20))
        assert len(q_meta.recv_sizes) == len(base_meta.recv_sizes)
        for rnd in range(len(base_meta.recv_sizes)):
            np.testing.assert_array_equal(
                q_meta.recv_sizes[rnd], base_meta.recv_sizes[rnd]
            )
        _fetch_all(q_cluster, q_meta, 0, 3 * N_EXEC, 8, oracle)


class TestStoreOccupancy:
    def test_round_max_rows_and_occupancy(self, rng):
        """The store-side planner inputs: per-round hottest-lane rows and the
        (used, padded) occupancy pairs the telemetry reports."""
        conf = _conf(0)
        cluster = TpuShuffleCluster(conf, num_executors=N_EXEC)
        meta, _ = _write_skewed(cluster, 0, 3 * N_EXEC, 8)
        store = cluster.transport(0).store
        maxes = store.round_max_rows(0)
        assert maxes and all(m >= 0 for m in maxes)
        occ = store.stats(0)["round_occupancy"]
        assert len(occ) == len(maxes)
        for used, padded in occ:
            assert used >= 0 and padded >= 0
