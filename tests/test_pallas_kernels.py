"""Ragged block-gather kernels (ops/pallas_kernels.py) and the device-resident
batch fetch built on them (TpuShuffleCluster.fetch_blocks_to_device).

On the CPU test mesh the 'xla' lowering runs compiled; the 'dma' lowering
needs real Mosaic dynamic-size DMA: it is AOT-lowered for the tpu platform
here, and run by the TPU-gated tests at the bottom (on hardware; skipped
here) and by every check of the benchmark's two HBM cells."""

import jax
import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.block import ShuffleBlockId
from sparkucx_tpu.core.operation import TransportError
from sparkucx_tpu.ops.pallas_kernels import (
    build_block_gather,
    build_block_scatter,
    pack_plan,
)
from sparkucx_tpu.transport.tpu import TpuShuffleCluster

ROW = 512
LANE = ROW // 4


def _oracle(src, starts, counts):
    parts = [np.asarray(src)[s : s + c] for s, c in zip(starts, counts)]
    return (
        np.concatenate(parts)
        if parts
        else np.zeros((0, src.shape[1]), dtype=np.asarray(src).dtype)
    )


@pytest.fixture(scope="module")
def src(request):
    rng = np.random.default_rng(7)
    return jax.numpy.asarray(rng.integers(0, 1 << 30, size=(512, LANE), dtype=np.int32))


PLANS = [
    # (byte offset, byte length) pairs — ragged, with empties and sub-row tails
    [(0, ROW), (3 * ROW, 2 * ROW), (10 * ROW, 0), (40 * ROW, 7 * ROW + 17)],
    [(100 * ROW, 30 * ROW), (5 * ROW, 100), (200 * ROW, ROW * 8)],
    [(0, 13)],
    [],
    # every row count from 1 to 12 a block: each residue of the 8-row tile,
    # blocks under one tile among them
    [(i * 16 * ROW, (i + 1) * ROW) for i in range(12)],
]


class TestGatherLowering:
    @pytest.mark.parametrize("plan", PLANS)
    def test_xla_matches_oracle(self, src, plan):
        starts, counts, outs, total = pack_plan(plan, ROW)
        fn = build_block_gather(len(plan), max(total, 1), impl="xla")
        if not len(plan):
            return  # nothing to run; pack_plan handled the degenerate shape
        out = np.asarray(fn(starts, counts, outs, src))
        assert np.array_equal(out[:total], _oracle(src, starts, counts))

    def test_auto_lowering_serves_repeated_dispatches(self):
        """The reply-packing shape: six 64 KiB blocks at every other slot of
        the source, packed by the platform's own lowering ('xla' here), the
        one executable dispatched twice over different sources."""
        rows_each = (64 << 10) // ROW
        plan = [(2 * i * rows_each * ROW, rows_each * ROW) for i in range(6)]
        starts, counts, outs, total = pack_plan(plan, ROW)
        assert total == 6 * rows_each
        fn = build_block_gather(len(plan), total)
        assert fn.impl == "xla"
        rng = np.random.default_rng(0)
        for _ in range(2):
            big = jax.numpy.asarray(
                rng.integers(-100, 100, size=(12 * rows_each, LANE), dtype=np.int32)
            )
            out = np.asarray(fn(starts, counts, outs, big))
            assert np.array_equal(out[:total], _oracle(big, starts, counts))

    def test_pack_plan_rejects_misaligned(self):
        with pytest.raises(ValueError, match="aligned"):
            pack_plan([(ROW + 1, ROW)], ROW)

    def test_pack_plan_rows(self):
        starts, counts, outs, total = pack_plan([(0, 1), (ROW, ROW + 1)], ROW)
        assert counts.tolist() == [1, 2]
        assert outs.tolist() == [0, 1]
        assert total == 3

    @pytest.mark.parametrize("impl", ["bogus", "tiled"])
    def test_unknown_impl(self, impl):
        with pytest.raises(ValueError, match="unknown impl"):
            build_block_gather(1, 1, impl=impl)

    @pytest.mark.parametrize("out_rows", [512, 1])
    def test_dma_lowers_aot_for_tpu(self, out_rows):
        # the gather's twin of TestScatterLowering.test_dma_lowers_aot_for_tpu:
        # the kernel the chip picks, exported for the tpu platform from here,
        # down to the one-row bucket a tiny fetch makes
        from jax import export as jax_export

        import jax.numpy as jnp

        fn = build_block_gather(8, out_rows, impl="dma")
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        exported = jax_export.export(jax.jit(fn), platforms=["tpu"])(
            i32(8), i32(8), i32(8), i32(512, LANE)
        )
        assert "block_gather_dma" in exported.mlir_module()


OUT_ROWS = 256

# (dst slot row, row count) pairs — non-overlapping dst windows, with empties
SCATTER_PLANS = [
    [(3, 5), (40, 0), (64, 8), (200, 3)],
    [(0, 8), (16, 16), (250, 1)],
    [(95, 5)],
    [(0, 0)],
    [(i * 20, i + 1) for i in range(12)],  # every residue of the 8-row tile
]


def _scatter_oracle(dst, src, starts, counts, outs):
    exp = np.asarray(dst).copy()
    s = np.asarray(src)
    for start, count, out in zip(starts, counts, outs):
        exp[start : start + count] = s[out : out + count]
    return exp


def _scatter_args(plan):
    starts = np.asarray([s for s, _ in plan], dtype=np.int32)
    counts = np.asarray([c for _, c in plan], dtype=np.int32)
    outs = np.concatenate([[0], np.cumsum(counts)[:-1]]).astype(np.int32)
    return starts, counts, outs, int(counts.sum())


class TestScatterLowering:
    """build_block_scatter — the inverse kernel: packed src -> slot-layout dst.

    Every case pre-fills dst with a sentinel pattern and asserts both the
    placed blocks AND that uncovered dst rows survive untouched (scatter is a
    read-modify-write; a lowering that zeroes the staging buffer would pass a
    blocks-only check while destroying earlier writes in the same round)."""

    def _dst(self):
        rng = np.random.default_rng(23)
        return jax.numpy.asarray(
            rng.integers(0, 1 << 30, size=(OUT_ROWS, LANE), dtype=np.int32)
        )

    @pytest.mark.parametrize("plan", SCATTER_PLANS)
    def test_xla_matches_oracle(self, src, plan):
        starts, counts, outs, total = _scatter_args(plan)
        dst = self._dst()
        fn = build_block_scatter(len(plan), OUT_ROWS, impl="xla")
        out = np.asarray(fn(starts, counts, outs, src[: max(total, 1)], dst))
        assert np.array_equal(out, _scatter_oracle(dst, src, starts, counts, outs))

    def test_xla_window_clamp_at_buffer_edge(self, src):
        # regression: a block ending exactly at the last dst row must not have
        # its dynamic_slice window clamped backwards (would shift src rows)
        plan = [(OUT_ROWS - 7, 7)]
        starts, counts, outs, total = _scatter_args(plan)
        dst = self._dst()
        fn = build_block_scatter(1, OUT_ROWS, impl="xla", max_block_rows=7)
        out = np.asarray(fn(starts, counts, outs, src[:total], dst))
        assert np.array_equal(out, _scatter_oracle(dst, src, starts, counts, outs))

    def test_zero_count_padding_entries_are_noops(self, src):
        # cache-bucket padding appends (0, 0, total) entries; they must not
        # disturb dst row 0
        starts = np.asarray([10, 0, 0], dtype=np.int32)
        counts = np.asarray([4, 0, 0], dtype=np.int32)
        outs = np.asarray([0, 4, 4], dtype=np.int32)
        dst = self._dst()
        fn = build_block_scatter(3, OUT_ROWS, impl="xla")
        out = np.asarray(fn(starts, counts, outs, src[:4], dst))
        assert np.array_equal(out, _scatter_oracle(dst, src, starts, counts, outs))

    @pytest.mark.parametrize("impl", ["bogus", "tiled"])
    def test_unknown_impl(self, impl):
        with pytest.raises(ValueError, match="unknown impl"):
            build_block_scatter(1, 1, impl=impl)

    @pytest.mark.parametrize("out_rows", [OUT_ROWS, 1])
    def test_dma_lowers_aot_for_tpu(self, out_rows):
        # AOT Mosaic lowering: the dma kernel must export for the tpu platform
        # even from the CPU test mesh (catches pallas lowering regressions
        # without hardware), down to a staging array of one row
        from jax import export as jax_export

        import jax.numpy as jnp

        fn = build_block_scatter(8, out_rows, impl="dma")
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        exported = jax_export.export(jax.jit(fn), platforms=["tpu"])(
            i32(8), i32(8), i32(8), i32(64, LANE), i32(out_rows, LANE)
        )
        assert "block_scatter_dma" in exported.mlir_module()


N_EXEC = 4


@pytest.fixture(scope="module")
def exchanged_cluster():
    conf = TpuShuffleConf(
        staging_capacity_per_executor=1 << 20,
        block_alignment=128,
        num_executors=N_EXEC,
        keep_device_recv=True,  # device-side fetch is the subject under test
    )
    cluster = TpuShuffleCluster(conf, num_executors=N_EXEC)
    rng = np.random.default_rng(11)
    M, R = 8, 8
    meta = cluster.create_shuffle(0, M, R)
    oracle = {}
    for m in range(M):
        t = cluster.transport(meta.map_owner[m])
        w = t.store.map_writer(0, m)
        for r in range(R):
            payload = rng.integers(0, 256, size=int(rng.integers(0, 3000)), dtype=np.uint8).tobytes()
            oracle[(m, r)] = payload
            w.write_partition(r, payload)
        t.commit_block(w.commit().pack())
    cluster.run_exchange(0)
    return cluster, meta, oracle, M, R


class TestDeviceFetch:
    def test_packed_blocks_match_oracle(self, exchanged_cluster):
        cluster, meta, oracle, M, R = exchanged_cluster
        lane = cluster.row_bytes // 4
        for r in range(R):
            consumer = meta.owner_of_reduce(r)
            bids = [ShuffleBlockId(0, m, r) for m in range(M)]
            packed, entries = cluster.fetch_blocks_to_device(consumer, 0, bids)
            packed_bytes = np.asarray(packed).reshape(-1).view(np.uint8)
            assert packed.shape[1] == lane
            for (row_start, length), bid in zip(entries, bids):
                start = int(row_start) * cluster.row_bytes
                got = packed_bytes[start : start + int(length)].tobytes()
                assert got == oracle[(bid.map_id, bid.reduce_id)]

    @pytest.mark.parametrize("nblocks", [3, 5, 6, 7])
    def test_non_pow2_batch_padding(self, exchanged_cluster, nblocks):
        # regression: cache-bucket padding entries must keep the xla lowering's
        # outs+counts non-decreasing — with outs padded to 0 the last real
        # block came back zeroed
        cluster, meta, oracle, M, R = exchanged_cluster
        r = 1
        consumer = meta.owner_of_reduce(r)
        bids = [ShuffleBlockId(0, m, r) for m in range(nblocks)]
        packed, entries = cluster.fetch_blocks_to_device(consumer, 0, bids)
        packed_bytes = np.asarray(packed).reshape(-1).view(np.uint8)
        for (row_start, length), bid in zip(entries, bids):
            start = int(row_start) * cluster.row_bytes
            assert packed_bytes[start : start + int(length)].tobytes() == oracle[
                (bid.map_id, bid.reduce_id)
            ], f"block {bid} corrupted with batch of {nblocks}"

    def test_facet_delegation(self, exchanged_cluster):
        cluster, meta, oracle, M, R = exchanged_cluster
        r = 0
        consumer = meta.owner_of_reduce(r)
        t = cluster.transport(consumer)
        bids = [ShuffleBlockId(0, m, r) for m in range(M)]
        packed, entries = t.fetch_blocks_device(bids)
        packed_bytes = np.asarray(packed).reshape(-1).view(np.uint8)
        row_start, length = entries[2]
        got = packed_bytes[int(row_start) * cluster.row_bytes :][: int(length)].tobytes()
        assert got == oracle[(2, r)]

    def test_empty_request(self, exchanged_cluster):
        cluster, meta, *_ = exchanged_cluster
        packed, entries = cluster.fetch_blocks_to_device(0, 0, [])
        assert packed.shape[0] == 0 and entries.shape == (0, 2)

    def test_wrong_owner_rejected(self, exchanged_cluster):
        cluster, meta, oracle, M, R = exchanged_cluster
        r = 0
        wrong = (meta.owner_of_reduce(r) + 1) % N_EXEC
        with pytest.raises(TransportError, match="owned by"):
            cluster.fetch_blocks_to_device(wrong, 0, [ShuffleBlockId(0, 0, r)])

    def test_disabled_without_device_recv(self):
        conf = TpuShuffleConf(
            staging_capacity_per_executor=1 << 20,
            block_alignment=128,
            num_executors=2,
            keep_device_recv=False,
        )
        cluster = TpuShuffleCluster(conf, num_executors=2)
        cluster.create_shuffle(0, 1, 2)
        t = cluster.transport(0)
        w = t.store.map_writer(0, 0)
        w.write_partition(0, b"x" * 100)
        w.write_partition(1, b"y" * 100)
        t.commit_block(w.commit().pack())
        cluster.run_exchange(0)
        with pytest.raises(TransportError, match="keep_device_recv"):
            cluster.fetch_blocks_to_device(0, 0, [ShuffleBlockId(0, 0, 0)])

    def test_multi_round_fetch(self):
        # tiny regions force a staging rollover -> blocks span two rounds
        conf = TpuShuffleConf(
            staging_capacity_per_executor=4096,
            block_alignment=128,
            num_executors=2,
            keep_device_recv=True,
        )
        cluster = TpuShuffleCluster(conf, num_executors=2)
        meta = cluster.create_shuffle(0, 2, 2)
        rng = np.random.default_rng(3)
        oracle = {}
        for m in range(2):
            t = cluster.transport(meta.map_owner[m])
            w = t.store.map_writer(0, m)
            for r in range(2):
                payload = rng.integers(0, 256, size=1500, dtype=np.uint8).tobytes()
                oracle[(m, r)] = payload
                w.write_partition(r, payload)
            t.commit_block(w.commit().pack())
        cluster.run_exchange(0)
        assert cluster.transport(0).store.num_rounds(0) >= 1
        for r in range(2):
            consumer = meta.owner_of_reduce(r)
            bids = [ShuffleBlockId(0, m, r) for m in range(2)]
            packed, entries = cluster.fetch_blocks_to_device(consumer, 0, bids)
            packed_bytes = np.asarray(packed).reshape(-1).view(np.uint8)
            for (row_start, length), bid in zip(entries, bids):
                start = int(row_start) * cluster.row_bytes
                assert packed_bytes[start : start + int(length)].tobytes() == oracle[
                    (bid.map_id, bid.reduce_id)
                ]


@pytest.mark.skipif(
    jax.devices()[0].platform != "tpu", reason="dynamic-size DMA needs real Mosaic"
)
class TestDmaOnTpu:
    def test_dma_matches_oracle(self, src):
        plan = PLANS[0] + PLANS[1]
        starts, counts, outs, total = pack_plan(plan, ROW)
        fn = build_block_gather(len(plan), total, impl="dma")
        out = np.asarray(fn(starts, counts, outs, src))
        assert np.array_equal(out[:total], _oracle(src, starts, counts))

    def test_dma_scatter_matches_oracle(self, src):
        plan = SCATTER_PLANS[0] + SCATTER_PLANS[1]
        starts = np.asarray([s for s, _ in plan], dtype=np.int32)
        counts = np.asarray([c for _, c in plan], dtype=np.int32)
        outs = np.concatenate([[0], np.cumsum(counts)[:-1]]).astype(np.int32)
        total = int(counts.sum())
        rng = np.random.default_rng(23)
        dst = jax.numpy.asarray(
            rng.integers(0, 1 << 30, size=(OUT_ROWS, LANE), dtype=np.int32)
        )
        expect = _scatter_oracle(dst, src, starts, counts, outs)
        fn = build_block_scatter(len(plan), OUT_ROWS, impl="dma")
        out = np.asarray(fn(starts, counts, outs, src[:total], dst))
        assert np.array_equal(out, expect)
