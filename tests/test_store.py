"""Tests for the HBM block store (NvkvHandler/NvkvShuffleMapOutputWriter semantics)."""

import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.definitions import MapperInfo
from sparkucx_tpu.core.operation import TransportError
from sparkucx_tpu.store.hbm_store import HbmBlockStore, default_peer_ranges

ALIGN = 128


@pytest.fixture
def store():
    s = HbmBlockStore(TpuShuffleConf(staging_capacity_per_executor=1 << 20, block_alignment=ALIGN))
    yield s
    s.close()


class TestPeerRanges:
    def test_balanced(self):
        assert default_peer_ranges(8, 4) == [(0, 2), (2, 4), (4, 6), (6, 8)]

    def test_remainder(self):
        assert default_peer_ranges(10, 4) == [(0, 3), (3, 6), (6, 8), (8, 10)]

    def test_fewer_reducers_than_peers(self):
        ranges = default_peer_ranges(2, 4)
        assert ranges == [(0, 1), (1, 2), (2, 2), (2, 2)]


class TestWriteReadback:
    def test_write_then_read(self, store):
        store.create_shuffle(0, num_mappers=2, num_reducers=4, peer_ranges=default_peer_ranges(4, 2))
        w = store.map_writer(0, 0)
        w.write_partition(0, b"r0-data")
        w.write_partition(2, b"r2-data-xyz")
        w.commit()
        assert store.read_block(0, 0, 0) == b"r0-data"
        assert store.read_block(0, 0, 2) == b"r2-data-xyz"
        assert store.block_length(0, 0, 0) == 7
        assert store.block_length(0, 0, 1) == 0  # never written

    def test_streaming_writes(self, store):
        store.create_shuffle(1, 1, 1)
        w = store.map_writer(1, 0)
        w.open_partition(0)
        for i in range(10):
            w.write(bytes([i]) * 100)
        w.close_partition()
        expected = b"".join(bytes([i]) * 100 for i in range(10))
        assert store.read_block(1, 0, 0) == expected

    def test_sequential_partition_protocol(self, store):
        # NvkvShuffleMapOutputWriter.scala:108 — increasing reduce order enforced.
        store.create_shuffle(2, 1, 4)
        w = store.map_writer(2, 0)
        w.write_partition(2, b"x")
        with pytest.raises(TransportError, match="increasing reduce order"):
            w.open_partition(1)
        with pytest.raises(TransportError, match="no open partition"):
            w.write(b"y")

    def test_double_open_rejected(self, store):
        store.create_shuffle(3, 1, 2)
        w = store.map_writer(3, 0)
        w.open_partition(0)
        with pytest.raises(TransportError, match="still open"):
            w.open_partition(1)

    def test_partition_exceeding_region_rejected(self):
        s = HbmBlockStore(TpuShuffleConf(staging_capacity_per_executor=4096, block_alignment=ALIGN))
        s.create_shuffle(0, 1, 2, peer_ranges=default_peer_ranges(2, 2))
        w = s.map_writer(0, 0)
        w.open_partition(0)
        with pytest.raises(TransportError, match="exceeds a whole region"):
            w.write(b"x" * 4096)

    def test_region_overflow_rolls_over(self):
        # Overflow across partitions spills into a new staging round instead of
        # erroring (multi-round exchange).
        s = HbmBlockStore(TpuShuffleConf(staging_capacity_per_executor=4096, block_alignment=ALIGN))
        s.create_shuffle(1, 2, 2, peer_ranges=default_peer_ranges(2, 2))
        region = s._state(1).region_size
        wa = s.map_writer(1, 0)
        wa.write_partition(0, b"a" * region)
        wa.commit()
        wb = s.map_writer(1, 1)
        wb.write_partition(0, b"c" * 100)  # peer-0 region full -> round 1
        wb.commit()
        assert s.num_rounds(1) == 2
        assert s.read_block(1, 0, 0) == b"a" * region
        assert s.read_block(1, 1, 0) == b"c" * 100
        st = s._state(1)
        assert st.blocks[(0, 0)].round == 0
        assert st.blocks[(1, 0)].round == 1

    def test_empty_partition(self, store):
        store.create_shuffle(4, 1, 2)
        w = store.map_writer(4, 0)
        w.write_partition(0, b"")
        info = w.commit()
        assert info.partitions[0] == (0, 0)
        assert store.read_block(4, 0, 0) == b""


class TestDiskSpillTier:
    """Completed staging rounds move to np.memmap files (the capacity-beyond-RAM
    role of the reference's DPU-attached NVMe, NvkvHandler.scala:160-242), so a
    shuffle larger than the staging RAM budget streams through bounded memory."""

    def _fill_rounds(self, s, shuffle_id, num_rounds, region):
        """Write num_rounds full regions for reducer 0 via distinct mappers;
        returns the oracle {(map_id, 0): payload}."""
        oracle = {}
        for m in range(num_rounds):
            payload = bytes([m + 1]) * region
            w = s.map_writer(shuffle_id, m)
            w.write_partition(0, payload)
            w.commit()
            oracle[(m, 0)] = payload
        return oracle

    def test_rounds_spill_to_memmap_and_read_back(self, tmp_path):
        import os

        s = HbmBlockStore(
            TpuShuffleConf(
                staging_capacity_per_executor=4096,
                block_alignment=ALIGN,
                spill_dir=str(tmp_path),
            )
        )
        # 8 rounds x 4096 B through a 4096 B RAM budget: 8x larger than staging
        s.create_shuffle(0, 8, 1)
        region = s._state(0).region_size
        oracle = self._fill_rounds(s, 0, 8, region)
        assert s.num_rounds(0) == 8
        st = s._state(0)
        assert len(st.prev_rounds) == 7
        assert all(isinstance(p, np.memmap) for p, _ in st.prev_rounds)
        spilled = [f for f in os.listdir(str(tmp_path)) if not f.startswith(".")]
        assert len(spilled) == 1  # the per-store spill subdir
        files = os.listdir(tmp_path / spilled[0])
        assert len(files) == 7
        for (m, r), expect in oracle.items():
            assert s.read_block(0, m, r) == expect, f"round {m} corrupted"
        # zero-copy serving handle works against the memmap too
        arr, off, ln = s.block_staging_view(0, 0, 0)
        assert bytes(arr[off : off + ln]) == oracle[(0, 0)]
        s.remove_shuffle(0)
        assert os.listdir(str(tmp_path)) == []  # files AND subdir reclaimed
        s.close()

    def test_seal_serves_spilled_rounds(self, tmp_path):
        s = HbmBlockStore(
            TpuShuffleConf(
                staging_capacity_per_executor=4096,
                block_alignment=ALIGN,
                spill_dir=str(tmp_path),
            )
        )
        s.create_shuffle(0, 3, 1)
        region = s._state(0).region_size
        oracle = self._fill_rounds(s, 0, 3, region)
        rounds = s.seal(0)
        assert len(rounds) == 3
        for m, (payload, sizes) in enumerate(rounds):
            flat = np.asarray(payload).reshape(-1).view(np.uint8)
            assert flat[:region].tobytes() == oracle[(m, 0)]
            assert int(sizes[0]) == region // ALIGN
        s.close()

    def test_spill_disabled_keeps_ram_snapshots(self, tmp_path):
        s = HbmBlockStore(
            TpuShuffleConf(
                staging_capacity_per_executor=4096,
                block_alignment=ALIGN,
                spill_to_disk=False,
                spill_dir=str(tmp_path),
            )
        )
        s.create_shuffle(0, 2, 1)
        region = s._state(0).region_size
        oracle = self._fill_rounds(s, 0, 2, region)
        st = s._state(0)
        assert len(st.prev_rounds) == 1
        assert not isinstance(st.prev_rounds[0][0], np.memmap)
        import os

        assert os.listdir(str(tmp_path)) == []
        assert s.read_block(0, 0, 0) == oracle[(0, 0)]
        s.close()

    def test_spill_cap_enforced(self, tmp_path):
        s = HbmBlockStore(
            TpuShuffleConf(
                staging_capacity_per_executor=4096,
                block_alignment=ALIGN,
                spill_dir=str(tmp_path),
                spill_disk_cap_bytes=2 * 4096,
            )
        )
        s.create_shuffle(0, 4, 1)
        region = s._state(0).region_size
        self._fill_rounds(s, 0, 3, region)  # two rounds spilled = cap
        with pytest.raises(TransportError, match="spill cap"):
            w = s.map_writer(0, 3)
            w.write_partition(0, b"x" * region)
        s.close()

    def test_shuffle_beyond_ram_budget_end_to_end(self, tmp_path):
        """BASELINE-shaped gate: exchange a shuffle ~10x the configured staging
        RAM budget through multi-round collectives and verify every block
        against the oracle (VERDICT round-1 item 4's done criterion,
        scaled down via the small capacity)."""
        from sparkucx_tpu.transport.tpu import TpuShuffleCluster

        n, M, R = 2, 6, 4
        conf = TpuShuffleConf(
            staging_capacity_per_executor=8192,
            block_alignment=ALIGN,
            num_executors=n,
            spill_dir=str(tmp_path),
        )
        cluster = TpuShuffleCluster(conf, num_executors=n)
        meta = cluster.create_shuffle(0, M, R)
        rng = np.random.default_rng(42)
        region = cluster.transport(0).store._state(0).region_size
        oracle = {}
        for m in range(M):
            t = cluster.transport(meta.map_owner[m])
            w = t.store.map_writer(0, m)
            for r in range(R):
                # ~0.9 region per block forces a rollover nearly every write
                payload = rng.integers(
                    0, 256, size=int(region * 0.9), dtype=np.uint8
                ).tobytes()
                oracle[(m, r)] = payload
                w.write_partition(r, payload)
            t.commit_block(w.commit().pack())
        total = sum(len(v) for v in oracle.values())
        assert total > 10 * conf.staging_capacity_per_executor
        cluster.run_exchange(0)
        for (m, r), expect in oracle.items():
            consumer = meta.owner_of_reduce(r)
            view, ln = cluster.locate_received_block(consumer, 0, m, r)
            assert ln == len(expect)
            assert view[:ln].tobytes() == expect, f"mismatch at ({m},{r})"
        cluster.remove_shuffle(0)
        import os

        leftovers = [
            f for d in os.listdir(str(tmp_path)) for f in os.listdir(tmp_path / d)
        ]
        assert leftovers == []


class TestRolloverKeepsItsBuffer:
    """At a host-staged rollover with the disk tier on, the spilled round's
    RAM buffer is the next round's staging, its used prefixes set back to
    zero; with the tier off the round IS the RAM snapshot and the next round
    takes a new buffer.  Either way a round starts as all zeros."""

    REGION = 8192
    #: share of each region a round fills: falling, so a later round leaves
    #: bytes of an earlier one under rows it never writes unless they were zeroed
    FILLS = (0.97, 0.88, 0.78, 0.68, 0.58)

    def _store(self, tmp_path, spill, regions):
        return HbmBlockStore(
            TpuShuffleConf(
                staging_capacity_per_executor=regions * self.REGION,
                block_alignment=ALIGN,
                spill_to_disk=spill,
                spill_dir=str(tmp_path),
            )
        )

    def _drive(self, s, regions):
        """Two map tasks a round, one block a region each: the first block of
        a round (just over half a region) cannot fit what the round before
        left free, so it rolls.  Returns {round: expected payload} built from
        the commit tables alone (zeros, then each block's bytes at its offset)
        and the (round, live staging buffer) seen after every map task."""
        rng = np.random.default_rng(28)
        s.create_shuffle(0, 2 * len(self.FILLS), regions, peer_ranges=default_peer_ranges(regions, regions))
        expected = {k: np.zeros(regions * self.REGION, dtype=np.uint8) for k in range(len(self.FILLS))}
        live = []
        for k, fill in enumerate(self.FILLS):
            for half in range(2):
                m = 2 * k + half
                w = s.map_writer(0, m)
                blocks = []
                for p in range(regions):
                    first = self.REGION // 2 + 1 + 2 * int(rng.integers(0, 40))  # odd: never a multiple of ALIGN
                    length = first if half == 0 else int(fill * self.REGION) - first - 2 * int(rng.integers(0, 40))
                    assert length % ALIGN
                    blocks.append(rng.integers(1, 256, size=length, dtype=np.uint8).tobytes())
                    w.write_partition(p, blocks[-1])
                info = w.commit()
                for p, data in enumerate(blocks):
                    off, ln = info.partitions[p]
                    assert ln == len(data) and info.round_of(p) == k
                    expected[k][off : off + ln] = np.frombuffer(data, dtype=np.uint8)
                live.append((k, s._state(0).staging))
        return expected, live

    @pytest.mark.parametrize("regions", [1, 4])
    @pytest.mark.parametrize("spill", [True, False], ids=["disk-tier", "ram-snapshots"])
    def test_every_round_is_zeros_but_for_its_blocks(self, tmp_path, spill, regions):
        s = self._store(tmp_path, spill, regions)
        expected, live = self._drive(s, regions)
        st = s._state(0)
        rollovers = len(self.FILLS) - 1
        assert s.num_rounds(0) == rollovers + 1 >= 5
        useds = [used for _, used in st.prev_rounds] + [st.region_used]
        # the point of the traffic: every region of a later round is used less
        assert all((b < a).all() for a, b in zip(useds, useds[1:]))
        stats = s.write_stats()
        assert stats["rollovers"] == rollovers
        first = live[0][1]
        if spill:
            assert all(np.shares_memory(buf, first) for _, buf in live)
            assert all(isinstance(p, np.memmap) for p, _ in st.prev_rounds)
            assert stats["recycled_rounds"] == rollovers
            assert stats["zeroed_bytes"] == stats["spilled_bytes"] == sum(int(u.sum()) for u in useds[:-1])
        else:
            assert stats["recycled_rounds"] == stats["zeroed_bytes"] == 0
            buffers = [p for p, _ in st.prev_rounds] + [st.staging]
            assert not any(
                np.shares_memory(a, b) for i, a in enumerate(buffers) for b in buffers[i + 1 :]
            )
            # and each was the live buffer of its own round only
            assert all(np.shares_memory(buf, buffers[k]) for k, buf in live)
        sealed = s.seal(0)
        assert len(sealed) == rollovers + 1
        for k, (payload, sizes) in enumerate(sealed):
            flat = np.asarray(payload).reshape(-1).view(np.uint8)
            assert flat.size == expected[k].size
            # no stale byte past a used count or in a block's pad
            assert np.array_equal(flat, expected[k]), f"round {k}"
            assert (sizes.astype(np.int64) * ALIGN == useds[k]).all()
        s.close()

    def test_a_view_of_the_unsealed_live_round_is_a_private_copy(self, tmp_path):
        s = self._store(tmp_path, True, 1)
        s.create_shuffle(0, 4, 1)
        first = b"a" * 1001
        w = s.map_writer(0, 0)
        w.write_partition(0, first)
        w.commit()
        buf = s._state(0).staging
        arr, off, ln = s.block_staging_view(0, 0, 0)
        assert not np.shares_memory(arr, buf)
        assert bytes(arr[off : off + ln]) == first
        # the rollover keeps the buffer, zeroes it, and the next round's first
        # block lands on the same bytes
        w = s.map_writer(0, 1)
        w.write_partition(0, b"b" * (self.REGION - 100))
        w.commit()
        assert s.num_rounds(0) == 2 and np.shares_memory(s._state(0).staging, buf)
        assert bytes(buf[:1001]) == b"b" * 1001
        assert bytes(arr[off : off + ln]) == first
        assert s.read_block(0, 0, 0) == first
        s.seal(0)
        # sealed: nothing can roll or append, so the live round is zero-copy
        arr, off, ln = s.block_staging_view(0, 1, 0)
        assert np.shares_memory(arr, buf) and bytes(arr[off : off + ln]) == b"b" * ln
        # and a completed round is served from its memmap, as before
        arr, off, ln = s.block_staging_view(0, 0, 0)
        assert isinstance(arr, np.memmap) and bytes(arr[off : off + ln]) == first
        s.close()

    @pytest.mark.parametrize("length", [100, REGION - 100], ids=["fits", "would-roll"])
    def test_a_writer_opened_before_the_seal_is_refused_after_it(self, tmp_path, length):
        s = self._store(tmp_path, True, 1)
        s.create_shuffle(0, 2, 1)
        w = s.map_writer(0, 0)
        w.write_partition(0, b"a" * 1001)
        w.commit()
        late = s.map_writer(0, 1)
        (payload, _sizes), = s.seal(0)
        before = np.array(payload)
        with pytest.raises(TransportError, match="sealed"):
            late.write_partition(0, b"z" * length)
        assert s.num_rounds(0) == 1 and s.write_stats()["rollovers"] == 0
        assert np.array_equal(np.asarray(payload), before)
        s.close()


class TestAlignmentAndLayout:
    def test_blocks_aligned(self, store):
        store.create_shuffle(0, 2, 2, peer_ranges=default_peer_ranges(2, 1))
        w0 = store.map_writer(0, 0)
        w0.write_partition(0, b"a" * 100)  # pads to 128
        w0.write_partition(1, b"b" * 200)  # pads to 256
        w1 = store.map_writer(0, 1)
        w1.write_partition(0, b"c" * 50)
        assert store.block_offset(0, 0, 0) == 0
        assert store.block_offset(0, 0, 1) == 128
        assert store.block_offset(0, 1, 0) == 128 + 256
        stats = store.stats(0)
        assert stats["bytes_staged"] == 350
        assert stats["bytes_padded"] == 128 + 256 + 128

    def test_peer_major_regions(self, store):
        # Partitions land in their owning peer's region: this IS the exchange's
        # slot layout — no repacking before the collective.
        store.create_shuffle(0, 1, 4, peer_ranges=default_peer_ranges(4, 2))
        w = store.map_writer(0, 0)
        w.write_partition(0, b"p0")   # peer 0 region
        w.write_partition(2, b"p2")   # peer 1 region
        w.write_partition(3, b"p3")   # peer 1 region
        st = store._state(0)
        assert store.block_offset(0, 0, 0) == 0
        assert store.block_offset(0, 0, 2) == st.region_size
        assert store.block_offset(0, 0, 3) == st.region_size + ALIGN
        assert st.region_used.tolist() == [ALIGN, 2 * ALIGN]

    def test_interleaved_mappers_append_within_region(self, store):
        store.create_shuffle(0, 2, 2, peer_ranges=default_peer_ranges(2, 2))
        w0, w1 = store.map_writer(0, 0), store.map_writer(0, 1)
        w0.write_partition(0, b"m0r0")
        w1.write_partition(0, b"m1r0")
        w0.write_partition(1, b"m0r1")
        assert store.block_offset(0, 0, 0) == 0
        assert store.block_offset(0, 1, 0) == ALIGN
        assert store.read_block(0, 1, 0) == b"m1r0"


class TestCommitAndSeal:
    def test_mapper_info_roundtrip(self, store):
        store.create_shuffle(0, 1, 3)
        w = store.map_writer(0, 0)
        w.write_partition(0, b"abc")
        w.write_partition(2, b"defgh")
        info = w.commit()
        assert info == MapperInfo.unpack(info.pack())
        assert info.partitions[0] == (0, 3)
        assert info.partitions[1] == (0, 0)
        assert info.partitions[2] == (128, 5)

    def test_commit_with_open_partition_rejected(self, store):
        store.create_shuffle(0, 1, 2)
        w = store.map_writer(0, 0)
        w.open_partition(0)
        with pytest.raises(TransportError, match="open partition"):
            w.commit()

    def test_apply_mapper_info(self, store):
        # Peer-process metadata install (the DPU-daemon side of AM id 2).
        store.create_shuffle(0, 2, 2)
        store.apply_mapper_info(MapperInfo(0, 1, ((0, 100), (256, 50))))
        assert store.block_length(0, 1, 0) == 100
        assert store.block_offset(0, 1, 1) == 256
        assert 1 in store.stats(0)["committed_maps"]

    def test_seal_returns_slot_payload_and_sizes(self, store):
        store.create_shuffle(0, 1, 4, peer_ranges=default_peer_ranges(4, 2))
        w = store.map_writer(0, 0)
        w.write_partition(0, b"A" * 100)
        w.write_partition(2, b"B" * 300)
        [(payload, sizes)] = store.seal(0)  # single round
        st = store._state(0)
        assert payload.dtype == np.int32
        assert payload.shape[1] == ALIGN // 4  # one row per alignment unit
        assert sizes.tolist() == [1, 3]  # row counts: 100 B -> 1, 300 B -> 3
        raw = np.asarray(payload).reshape(-1).view(np.uint8)
        assert raw[:100].tobytes() == b"A" * 100
        assert raw[st.region_size : st.region_size + 300].tobytes() == b"B" * 300

    def test_read_after_seal(self, store):
        store.create_shuffle(0, 1, 1)
        w = store.map_writer(0, 0)
        w.write_partition(0, b"persist-me")
        store.seal(0)
        assert store.read_block(0, 0, 0) == b"persist-me"

    def test_no_writes_after_seal(self, store):
        store.create_shuffle(0, 1, 1)
        store.seal(0)
        with pytest.raises(TransportError, match="sealed"):
            store.map_writer(0, 0)

    def test_double_seal_rejected(self, store):
        store.create_shuffle(0, 1, 1)
        store.seal(0)
        with pytest.raises(TransportError, match="sealed"):
            store.seal(0)


class TestLifecycle:
    def test_duplicate_shuffle_rejected(self, store):
        store.create_shuffle(0, 1, 1)
        with pytest.raises(TransportError, match="already exists"):
            store.create_shuffle(0, 1, 1)

    def test_remove_shuffle(self, store):
        store.create_shuffle(0, 1, 1)
        store.remove_shuffle(0)
        with pytest.raises(TransportError, match="unknown shuffle"):
            store.read_block(0, 0, 0)

    def test_unknown_block(self, store):
        store.create_shuffle(0, 1, 1)
        with pytest.raises(TransportError, match="no block"):
            store.read_block(0, 0, 0)

    def test_bad_ids(self, store):
        store.create_shuffle(0, 2, 2)
        with pytest.raises(ValueError):
            store.map_writer(0, 5)
        w = store.map_writer(0, 0)
        with pytest.raises(ValueError):
            w.open_partition(7)

    def test_capacity_too_small(self):
        s = HbmBlockStore(TpuShuffleConf(staging_capacity_per_executor=64))
        with pytest.raises(ValueError, match="too small"):
            s.create_shuffle(0, 1, 8, peer_ranges=default_peer_ranges(8, 8))


class TestSpillDirLifecycle:
    """The DEFAULT spill location (spill_dir=None -> per-store system tempdir,
    prefix sparkucx_tpu_spill_e*) must be fully reclaimed: per-shuffle files on
    remove_shuffle, the directory itself on close() or when the last spilled
    shuffle goes away.  Guards the leak where long-lived executors littered
    /tmp with sparkucx_tpu_spill_e* dirs."""

    def _fill_rounds(self, s, shuffle_id, num_rounds, region):
        for m in range(num_rounds):
            w = s.map_writer(shuffle_id, m)
            w.write_partition(0, bytes([m + 1]) * region)
            w.commit()

    def _spilled_store(self):
        s = HbmBlockStore(
            TpuShuffleConf(staging_capacity_per_executor=4096, block_alignment=ALIGN)
        )
        s.create_shuffle(0, 3, 1)
        self._fill_rounds(s, 0, 3, s._state(0).region_size)
        return s

    def test_close_removes_default_tempdir(self):
        import os

        s = self._spilled_store()
        d = s._spill_dir
        assert d is not None and os.path.isdir(d)
        assert os.path.basename(d).startswith("sparkucx_tpu_spill_e")
        s.close()
        assert not os.path.exists(d)

    def test_remove_last_spilled_shuffle_reclaims_dir(self):
        import os

        s = self._spilled_store()
        d = s._spill_dir
        assert d is not None and len(os.listdir(d)) == 2  # 3 rounds, 2 spilled
        s.remove_shuffle(0)
        # files AND the tempdir itself are gone; bookkeeping reset
        assert not os.path.exists(d)
        assert s._spill_dir is None
        # a later spill transparently recreates a fresh dir
        s.create_shuffle(1, 3, 1)
        self._fill_rounds(s, 1, 3, s._state(1).region_size)
        d2 = s._spill_dir
        assert d2 is not None and d2 != d and os.path.isdir(d2)
        s.close()
        assert not os.path.exists(d2)

    def test_no_leftover_spill_dirs_in_tempdir(self, monkeypatch, tmp_path):
        import os
        import tempfile

        # a temp dir of the test's own: in the shared one, other xdist workers
        # make and remove spill dirs between the two listings
        monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))

        def leftovers():
            return {
                f
                for f in os.listdir(tempfile.gettempdir())
                if f.startswith("sparkucx_tpu_spill_e")
            }

        before = leftovers()
        s = self._spilled_store()
        s.close()
        assert leftovers() == before
