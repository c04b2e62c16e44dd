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

    def test_partition_exceeding_region_is_staged_in_pieces(self):
        # A block longer than a region takes the room its region has, rolls
        # the round, takes the next: one entry that names the pieces in order.
        s = HbmBlockStore(TpuShuffleConf(staging_capacity_per_executor=4096, block_alignment=ALIGN))
        s.create_shuffle(0, 1, 2, peer_ranges=default_peer_ranges(2, 2))
        region = s._state(0).region_size
        payload = bytes(range(256)) * 16 + b"tail"
        w = s.map_writer(0, 0)
        w.open_partition(0)
        w.write(payload[:1000])
        w.write(payload[1000:])
        w.close_partition()
        info = w.commit()
        assert info.partitions[0] == (0, len(payload)) and info.round_of(0) == 0
        assert info.splits == {0: ((0, 0, region), (1, 0, region), (2, 0, len(payload) - 2 * region))}
        assert s.num_rounds(0) == 3 and s.read_block(0, 0, 0) == payload
        assert s._state(0).blocks[(0, 0)].padded == -(-len(payload) // ALIGN) * ALIGN

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


#: ``max_host_pool_bytes`` values at which a 4096-byte round never stays in
#: RAM: every rollover reaches the disk tier, the store of before the RAM tier
NO_RAM_ROUNDS = pytest.mark.parametrize("budget", [0, 4095], ids=["no-ram-tier", "under-one-round"])


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

    @NO_RAM_ROUNDS
    def test_rounds_spill_to_memmap_and_read_back(self, tmp_path, budget):
        import os

        s = HbmBlockStore(
            TpuShuffleConf(
                staging_capacity_per_executor=4096,
                block_alignment=ALIGN,
                spill_dir=str(tmp_path),
                max_host_pool_bytes=budget,
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
        stats = s.write_stats()
        assert stats["ram_rounds"] == 0 and stats["rollovers"] == stats["recycled_rounds"] == 7
        # no RAM tier: nothing kept after removal; a RAM tier under one round:
        # the live round's buffer, the store's own staging size, by the floor
        assert stats["pool_held_bytes"] == (4096 if budget else 0) and stats["pool_hits"] == 0
        assert stats["pool_kept_over_budget"] == (1 if budget else 0)
        s.close()

    @NO_RAM_ROUNDS
    def test_seal_serves_spilled_rounds(self, tmp_path, budget):
        s = HbmBlockStore(
            TpuShuffleConf(
                staging_capacity_per_executor=4096,
                block_alignment=ALIGN,
                spill_dir=str(tmp_path),
                max_host_pool_bytes=budget,
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

    @NO_RAM_ROUNDS
    def test_spill_cap_enforced(self, tmp_path, budget):
        s = HbmBlockStore(
            TpuShuffleConf(
                staging_capacity_per_executor=4096,
                block_alignment=ALIGN,
                spill_dir=str(tmp_path),
                spill_disk_cap_bytes=2 * 4096,
                max_host_pool_bytes=budget,
            )
        )
        s.create_shuffle(0, 4, 1)
        region = s._state(0).region_size
        self._fill_rounds(s, 0, 3, region)  # two rounds spilled = cap
        with pytest.raises(TransportError, match="spill cap"):
            w = s.map_writer(0, 3)
            w.write_partition(0, b"x" * region)
        s.close()

    @pytest.mark.parametrize(
        "budget, tiers",
        [(0, {"disk"}), (3 * 8192, {"host", "disk"}), (None, {"host"})],
        ids=["every-round-spilled", "across-the-budget", "default-conf"],
    )
    def test_shuffle_beyond_ram_budget_end_to_end(self, tmp_path, budget, tiers):
        """BASELINE-shaped gate: exchange a shuffle ~10x the configured staging
        RAM budget through multi-round collectives and verify every block
        against the oracle (VERDICT round-1 item 4's done criterion,
        scaled down via the small capacity) — with every completed round on
        the disk tier, with the first three in RAM and the rest on disk, and
        at the default budget, which holds them all."""
        from sparkucx_tpu.transport.tpu import TpuShuffleCluster

        n, M, R = 2, 6, 4
        conf = TpuShuffleConf(
            staging_capacity_per_executor=8192,
            block_alignment=ALIGN,
            num_executors=n,
            spill_dir=str(tmp_path),
            **({} if budget is None else {"max_host_pool_bytes": budget}),
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
        for t in cluster.transports:
            store = t.store
            completed = [store.round_tier(0, k) for k in range(store.num_rounds(0) - 1)]
            assert set(completed) == tiers and completed == sorted(completed, reverse=True)  # host, then disk
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
    """At a host-staged rollover that reaches the disk tier, the spilled
    round's RAM buffer is the next round's staging, its used prefixes set back
    to zero; a round that stays in RAM (under the budget, or with the disk
    tier off) IS its buffer and the next round takes another — a new one, or
    one a removed shuffle gave back.  Every way a round starts as all zeros."""

    REGION = 8192
    #: share of each region a round fills: falling, so a later round leaves
    #: bytes of an earlier one under rows it never writes unless they were zeroed
    FILLS = (0.97, 0.88, 0.78, 0.68, 0.58)

    def _store(self, tmp_path, spill, regions, budget=0):
        return HbmBlockStore(
            TpuShuffleConf(
                staging_capacity_per_executor=regions * self.REGION,
                block_alignment=ALIGN,
                spill_to_disk=spill,
                spill_dir=str(tmp_path),
                max_host_pool_bytes=budget,
            )
        )

    def _dirty_the_free_list(self, s, regions):
        """A shuffle of as many rounds as ``_drive`` writes, every region of
        every round full of 0xFF, sealed, read and removed: its buffers are
        what the next shuffle's rounds are taken from."""
        rounds = len(self.FILLS)
        s.create_shuffle(7, rounds, regions, peer_ranges=default_peer_ranges(regions, regions))
        for m in range(rounds):
            w = s.map_writer(7, m)
            for p in range(regions):
                w.write_partition(p, b"\xff" * self.REGION)
            w.commit()
        assert s.num_rounds(7) == rounds
        # (no name for a sealed round may outlive this line: a round that is
        # still referred to is not taken back)
        assert all((np.asarray(payload).view(np.uint8) == 0xFF).all() for payload, _ in s.seal(7))
        s.remove_shuffle(7)
        stats = s.write_stats()
        assert stats["pool_held_bytes"] == rounds * regions * self.REGION and stats["pool_dropped_busy"] == 0
        return stats

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
    @pytest.mark.parametrize("arm", ["disk-tier", "ram-snapshots", "ram-tier", "pooled"])
    def test_every_round_is_zeros_but_for_its_blocks(self, tmp_path, arm, regions):
        """``disk-tier``: no RAM tier, every rollover spills and keeps its
        buffer.  ``ram-snapshots``: the disk tier off.  ``ram-tier``: the
        default budget, every round stays in RAM in a new buffer.  ``pooled``:
        the same in buffers a removed shuffle filled with non-zero bytes."""
        budget = 0 if arm in ("disk-tier", "ram-snapshots") else 1 << 31
        s = self._store(tmp_path, arm != "ram-snapshots", regions, budget)
        before = self._dirty_the_free_list(s, regions) if arm == "pooled" else s.write_stats()
        expected, live = self._drive(s, regions)
        st = s._state(0)
        rollovers = len(self.FILLS) - 1
        assert s.num_rounds(0) == rollovers + 1 >= 5
        useds = [used for _, used in st.prev_rounds] + [st.region_used]
        # the point of the traffic: every region of a later round is used less
        assert all((b < a).all() for a, b in zip(useds, useds[1:]))
        stats = {k: v - before[k] for k, v in s.write_stats().items()}
        assert stats["rollovers"] == rollovers
        first = live[0][1]
        if arm == "disk-tier":
            assert all(np.shares_memory(buf, first) for _, buf in live)
            assert all(isinstance(p, np.memmap) for p, _ in st.prev_rounds)
            assert stats["recycled_rounds"] == rollovers and stats["ram_rounds"] == 0
            assert stats["zeroed_bytes"] == stats["spilled_bytes"] == sum(int(u.sum()) for u in useds[:-1])
        else:
            assert stats["recycled_rounds"] == stats["zeroed_bytes"] == stats["spilled_bytes"] == 0
            assert stats["ram_rounds"] == rollovers and s._spill_dir is None
            buffers = [p for p, _ in st.prev_rounds] + [st.staging]
            assert not any(
                np.shares_memory(a, b) for i, a in enumerate(buffers) for b in buffers[i + 1 :]
            )
            # and each was the live buffer of its own round only
            assert all(np.shares_memory(buf, buffers[k]) for k, buf in live)
            taken = (stats["pool_hits"], stats["pool_misses"])
            assert taken == ((rollovers + 1, 0) if arm == "pooled" else (0, rollovers + 1))
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


class TestRamRoundTier:
    """Completed rounds stay in RAM — the round's own buffer, no copy — while
    the store's round buffers fit ``conf.max_host_pool_bytes``; past it they
    go to the disk tier as before.  ``remove_shuffle`` gives a shuffle's round
    buffers, zeroed, to the store's free list, which the next shuffle's rounds
    are taken from."""

    ROUND = 4096

    def _store(self, tmp_path, **conf):
        return HbmBlockStore(
            TpuShuffleConf(
                staging_capacity_per_executor=self.ROUND,
                block_alignment=ALIGN,
                spill_dir=str(tmp_path),
                **conf,
            )
        )

    def _fill(self, s, shuffle_id, rounds, salt=0):
        """One full-region block a round; returns {(map, 0): payload}."""
        oracle = {}
        for m in range(rounds):
            payload = bytes([(salt + m) % 255 + 1]) * s._state(shuffle_id).region_size
            w = s.map_writer(shuffle_id, m)
            w.write_partition(0, payload)
            w.commit()
            oracle[(m, 0)] = payload
        return oracle

    @staticmethod
    def _ram_round_bytes(s):
        """Recounted from the states: what ``_ram_round_bytes`` keeps by steps."""
        return sum(
            snap.nbytes
            for st in s._shuffles.values()
            for snap, _ in st.prev_rounds
            if snap is not None and not isinstance(snap, np.memmap)
        )

    def test_default_conf_under_the_budget_never_reaches_the_disk(self, tmp_path):
        import os

        from sparkucx_tpu.utils.trace import TRACER

        s = self._store(tmp_path)
        s.create_shuffle(0, 8, 1)
        enabled, recording = TRACER.enabled, TRACER.recording
        TRACER.clear()
        TRACER.enable()
        try:
            oracle = self._fill(s, 0, 8)
            names = [e["name"] for e in TRACER.events if e["ph"] == "X"]
        finally:
            TRACER.enabled, TRACER.recording = enabled, recording
            TRACER.clear()
        assert names.count("store.rollover") == 7 and "store.spill" not in names
        assert s._spill_dir is None and os.listdir(str(tmp_path)) == []
        assert [s.round_tier(0, k) for k in range(8)] == ["host"] * 8
        stats = s.write_stats()
        assert stats["rollovers"] == stats["ram_rounds"] == 7
        assert stats["spilled_bytes"] == stats["spill_ns"] == stats["recycled_rounds"] == 0
        for (m, r), expect in oracle.items():
            assert s.read_block(0, m, r) == expect
            arr, off, ln = s.block_staging_view(0, m, r)
            assert not isinstance(arr, np.memmap) and bytes(arr[off : off + ln]) == expect
        for m, (payload, sizes) in enumerate(s.seal(0)):
            assert np.asarray(payload).view(np.uint8).tobytes() == oracle[(m, 0)]
            assert int(sizes[0]) == self.ROUND // ALIGN
        s.close()

    @pytest.mark.parametrize("cap", [0, 2 * 4096], ids=["no-disk-cap", "spillDiskCap"])
    def test_a_shuffle_that_crosses_the_budget(self, tmp_path, cap):
        import os

        s = self._store(tmp_path, max_host_pool_bytes=3 * self.ROUND, spill_disk_cap_bytes=cap)
        s.create_shuffle(0, 8, 1)
        assert s.stats(0)["ram_budget_bytes"] == 3 * self.ROUND
        if cap:
            oracle = self._fill(s, 0, 6)  # three rounds in RAM, two on disk = the cap, one live
            with pytest.raises(TransportError, match="spill cap"):
                s.map_writer(0, 6).write_partition(0, b"x" * self.ROUND)
        else:
            oracle = self._fill(s, 0, 8)
        rounds = len(oracle)
        tiers = [s.round_tier(0, k) for k in range(rounds)]
        assert tiers == ["host"] * 3 + ["disk"] * (rounds - 4) + ["host"]  # early RAM, later disk, the live round
        (spill_dir,) = os.listdir(str(tmp_path))
        assert sorted(os.listdir(tmp_path / spill_dir)) == [f"s0_r{k}.bin" for k in range(3, rounds - 1)]
        stats = s.write_stats()
        assert stats["ram_rounds"] == 3 and stats["recycled_rounds"] == rounds - 4
        assert stats["spilled_bytes"] == (rounds - 4) * self.ROUND
        assert s._ram_round_bytes == self._ram_round_bytes(s) == 3 * self.ROUND
        for (m, r), expect in oracle.items():  # byte-exact across both tiers
            assert s.read_block(0, m, r) == expect
            arr, off, ln = s.block_staging_view(0, m, r)
            assert isinstance(arr, np.memmap) == (tiers[m] == "disk") or m == rounds - 1
            assert bytes(arr[off : off + ln]) == expect
        for m, (payload, _sizes) in enumerate(s.seal(0)):
            assert np.asarray(payload).view(np.uint8).tobytes() == oracle[(m, 0)]
        s.remove_shuffle(0)
        assert os.listdir(str(tmp_path)) == []
        # the three RAM rounds fit the budget again as free buffers; the
        # fourth buffer (the live round's) would pass it
        assert s.write_stats()["pool_held_bytes"] == 3 * self.ROUND and s._ram_round_bytes == 0
        assert s.write_stats()["pool_kept_over_budget"] == 0  # the floor never stacks on a list that holds a buffer
        s.close()

    def test_held_bytes_never_exceed_the_budget(self, tmp_path):
        budget = 5 * self.ROUND
        s = self._store(tmp_path, max_host_pool_bytes=budget)

        def held():
            total = s._ram_round_bytes + s.write_stats()["pool_held_bytes"]
            assert s._ram_round_bytes == self._ram_round_bytes(s)
            assert s.write_stats()["pool_held_bytes"] == sum(
                size * len(free) for size, free in s._free_rounds.items()
            )
            assert total <= budget
            return total

        # rounds of two sizes, shuffles alive together, removed in another order
        for sid, (capacity, rounds) in enumerate([(4096, 4), (8192, 3), (4096, 6), (8192, 2), (4096, 3)]):
            s.create_shuffle(sid, rounds, 1, capacity=capacity)
            for m in range(rounds):
                w = s.map_writer(sid, m)
                w.write_partition(0, bytes([sid + 1]) * capacity)
                w.commit()
                held()
            if sid % 2:
                s.remove_shuffle(sid - 1)
                held()
        for sid in (1, 3, 4):
            for m in range(s._state(sid).num_mappers):
                assert s.read_block(sid, m, 0) == bytes([sid + 1]) * s._state(sid).region_size
            s.remove_shuffle(sid)
            held()
        stats = s.write_stats()
        assert s._ram_round_bytes == 0 and 0 < stats["pool_held_bytes"] <= budget
        assert stats["spilled_bytes"] > 0 and stats["ram_rounds"] > 0 and stats["pool_hits"] > 0
        assert stats["pool_dropped_busy"] == 0
        s.close()

    @pytest.mark.parametrize("case", ["not-the-staging-size", "a-second-one", "no-ram-tier"])
    def test_a_buffer_larger_than_the_budget_is_released_unless_the_floor_keeps_it(self, tmp_path, case):
        """Over the budget the free list keeps ONE buffer of the store's own
        staging size (``TestStagingFloor``); every other buffer over the budget
        — of another size, a second one, any with the RAM tier off — is
        released there and then, the collector off."""
        import gc
        import weakref

        budget = {"not-the-staging-size": self.ROUND, "a-second-one": self.ROUND - 1, "no-ram-tier": 0}[case]
        capacity = 2 * self.ROUND if case == "not-the-staging-size" else None  # None: the store's own
        s = self._store(tmp_path, max_host_pool_bytes=budget)
        for sid in range(2):  # two one-round shuffles side by side, as the HBM-held job's 4 GiB
            s.create_shuffle(sid, 1, 1, capacity=capacity)
            w = s.map_writer(sid, 0)
            w.write_partition(0, b"k" * s._state(sid).region_size)
            w.commit()
        gc.collect()
        gc.disable()
        try:
            released = []
            for sid in range(2):
                ref = weakref.ref(s._state(sid).staging)
                s.remove_shuffle(sid)
                released.append(ref() is None)
        finally:
            gc.enable()
        second = case == "a-second-one"  # the first of the store's own size is the floor's, the second is not
        assert released == [not second, True]
        stats = s.write_stats()
        assert stats["pool_kept_over_budget"] == int(second) and stats["pool_held_bytes"] == second * self.ROUND
        assert (stats["pool_hits"], stats["pool_misses"], stats["pool_dropped_busy"]) == (0, 2, 0)
        s.close()

    def test_close_empties_the_free_list(self, tmp_path):
        s = self._store(tmp_path)
        s.create_shuffle(0, 4, 1)
        self._fill(s, 0, 4)
        s.remove_shuffle(0)
        assert s.write_stats()["pool_held_bytes"] == 4 * self.ROUND and len(s._free_rounds[self.ROUND]) == 4
        s.close()
        assert s.write_stats()["pool_held_bytes"] == 0 and s._free_rounds == {} and s._ram_round_bytes == 0

    def test_no_ram_tier_is_the_store_of_before(self, tmp_path):
        """``max_host_pool_bytes=0``: every rollover spills into the same files,
        the buffer is kept from round to round, and nothing outlives a removal."""
        import os

        s = self._store(tmp_path, max_host_pool_bytes=0)
        for sid in range(3):
            s.create_shuffle(sid, 4, 1)
            live = s._state(sid).staging
            oracle = self._fill(s, sid, 4, salt=sid)
            assert np.shares_memory(s._state(sid).staging, live)
            assert [s.round_tier(sid, k) for k in range(4)] == ["disk"] * 3 + ["host"]
            (spill_dir,) = os.listdir(str(tmp_path))
            assert sorted(os.listdir(tmp_path / spill_dir)) == [f"s{sid}_r{k}.bin" for k in range(3)]
            assert all(s.read_block(sid, m, r) == expect for (m, r), expect in oracle.items())
            del live
            s.remove_shuffle(sid)
            assert os.listdir(str(tmp_path)) == []
            stats = s.write_stats()
            assert stats["rollovers"] == stats["recycled_rounds"] == 3 * (sid + 1)
            assert stats["spilled_bytes"] == stats["zeroed_bytes"] == 3 * (sid + 1) * self.ROUND
            assert stats["ram_rounds"] == stats["pool_hits"] == stats["pool_held_bytes"] == 0
            assert stats["pool_misses"] == sid + 1 and stats["pool_dropped_busy"] == 0
        s.close()

    def test_the_disk_tier_off_never_spills_and_shares_the_free_list(self, tmp_path):
        import os

        s = self._store(tmp_path, spill_to_disk=False, max_host_pool_bytes=2 * self.ROUND)
        for sid in range(2):
            s.create_shuffle(sid, 6, 1)
            oracle = self._fill(s, sid, 6, salt=10 * sid)
            # past the budget too: bounded by host memory alone, as ever
            assert [s.round_tier(sid, k) for k in range(6)] == ["host"] * 6
            assert s._ram_round_bytes == 5 * self.ROUND and os.listdir(str(tmp_path)) == []
            assert all(s.read_block(sid, m, r) == expect for (m, r), expect in oracle.items())
            s.remove_shuffle(sid)
            stats = s.write_stats()
            # what is kept after the removal is what the budget allows
            assert stats["pool_held_bytes"] == 2 * self.ROUND and stats["spilled_bytes"] == 0
            assert (stats["pool_hits"], stats["pool_misses"]) == (2 * sid, 6 + 4 * sid)
        s.close()

    def test_the_budget_is_bounded_by_the_hosts_memory(self, tmp_path, monkeypatch):
        from sparkucx_tpu.store import hbm_store

        monkeypatch.setattr(hbm_store, "_mem_available_bytes", lambda: 64 << 30)
        s = self._store(tmp_path)  # the default key: 2 GiB, under an eighth of 64 GiB
        s.create_shuffle(0, 1, 1)
        assert s.stats(0)["ram_budget_bytes"] == TpuShuffleConf().max_host_pool_bytes == 1 << 31
        monkeypatch.setattr(hbm_store, "_mem_available_bytes", lambda: 8 << 30)
        small = self._store(tmp_path)
        small.create_shuffle(0, 1, 1)
        assert small.stats(0)["ram_budget_bytes"] == 1 << 30
        monkeypatch.setattr(hbm_store, "_mem_available_bytes", lambda: None)
        assert hbm_store.ram_round_budget(TpuShuffleConf(max_host_pool_bytes=123)) == 123
        with pytest.raises(ValueError, match="max_host_pool_bytes"):
            TpuShuffleConf(max_host_pool_bytes=-1).validate()
        conf = TpuShuffleConf.from_spark_conf({"spark.shuffle.tpu.memory.maxHostPoolBytes": "512m"})
        assert conf.max_host_pool_bytes == 512 << 20

    def test_a_watermark_keeps_an_unsealed_shuffle_writable(self, tmp_path):
        """RAM rounds count as memory pressure and an unsealed shuffle's are
        nobody's to demote: a round stays in RAM only while the next round's
        writes would still pass the watermark, so the job streams through the
        disk tier as before instead of being shed for good."""
        s = self._store(tmp_path, store_hard_watermark=4 * self.ROUND)
        s.create_shuffle(0, 12, 1)
        oracle = self._fill(s, 0, 12)  # no ResourceExhaustedError
        tiers = [s.round_tier(0, k) for k in range(12)]
        assert tiers == ["host"] * 2 + ["disk"] * 9 + ["host"]
        assert s.memory_pressure_bytes() == 3 * self.ROUND
        assert all(s.read_block(0, m, r) == expect for (m, r), expect in oracle.items())
        s.close()

    def test_a_demoted_round_gives_its_buffer_to_the_free_list(self, tmp_path):
        s = self._store(tmp_path)
        s.create_shuffle(0, 3, 1)
        oracle = self._fill(s, 0, 3)
        s.seal(0)  # the rounds' views go with the list: nothing else refers to them
        assert s.demote_round(0, 0) == "host->disk" and s.round_tier(0, 0) == "disk"
        stats = s.write_stats()
        assert stats["pool_held_bytes"] == self.ROUND and stats["pool_dropped_busy"] == 0
        assert not s._free_rounds[self.ROUND][0].any()
        assert s._ram_round_bytes == self._ram_round_bytes(s) == self.ROUND
        assert s.read_block(0, 0, 0) == oracle[(0, 0)]
        assert s.restage_round(0, 0) and s.round_tier(0, 0) == "host"
        assert s._ram_round_bytes == self._ram_round_bytes(s) == 2 * self.ROUND
        assert s.read_block(0, 0, 0) == oracle[(0, 0)]
        s.close()

    def test_a_state_resolved_before_the_removal_is_refused_not_served_the_next_shuffle(self, tmp_path):
        s = self._store(tmp_path)
        s.create_shuffle(0, 3, 1)
        self._fill(s, 0, 3)
        late = s._state(0)  # what a reader or a writer handle holds
        s.remove_shuffle(0)
        assert late.removed and late.staging is None
        assert [snap for snap, _ in late.prev_rounds] == [None, None]
        s.create_shuffle(1, 3, 1)
        self._fill(s, 1, 3, salt=100)
        s._shuffles[0] = late  # the reader's view of the world, for one call each
        try:
            with pytest.raises(TransportError, match="released"):
                s.read_block(0, 0, 0)
            assert s.block_staging_view(0, 0, 0) is None
            with pytest.raises(TransportError, match="unknown shuffle"):
                s.seal(0)
        finally:
            del s._shuffles[0]
        s.close()


class TestStagingFloor:
    """The free list's floor: an idle store keeps ONE buffer of its own staging
    size although the buffer alone is over ``max_host_pool_bytes`` — the
    one-round job of a 4 GiB ``staging_capacity_per_executor`` under the 2 GiB
    default writes, job after job, into pages the process already holds."""

    ROUND = 1 << 16
    BUDGET = 1 << 15  # the RAM tier is on and a staging round alone is over it

    def _store(self, tmp_path, regions=1, **conf):
        conf.setdefault("max_host_pool_bytes", self.BUDGET)
        return HbmBlockStore(
            TpuShuffleConf(
                staging_capacity_per_executor=regions * self.ROUND, block_alignment=ALIGN,
                spill_dir=str(tmp_path), **conf,
            )
        )

    @staticmethod
    def _job(s, sid, blocks, regions=1):
        """One map task of ``blocks`` = {reduce: payload}, a region a reducer."""
        s.create_shuffle(sid, 1, regions, peer_ranges=default_peer_ranges(regions, regions))
        w = s.map_writer(sid, 0)
        for r in sorted(blocks):
            w.write_partition(r, blocks[r])
        w.commit()

    def test_the_kept_buffer_is_the_next_shuffles_staging(self, tmp_path):
        s = self._store(tmp_path)
        assert s._ram_budget == self.BUDGET < self.ROUND
        held = []
        for sid in range(4):
            self._job(s, sid, {0: bytes([sid + 1]) * 1000})
            held.append(id(s._state(sid).staging))
            assert s.read_block(sid, 0, 0) == bytes([sid + 1]) * 1000
            s.remove_shuffle(sid)
            stats = s.write_stats()
            assert stats["pool_held_bytes"] == self.ROUND > self.BUDGET
            assert [id(b) for b in s._free_rounds[self.ROUND]] == held[:1] and not s._free_rounds[self.ROUND][0].any()
            assert (stats["pool_hits"], stats["pool_misses"], stats["pool_dropped_busy"]) == (sid, 1, 0)
            assert stats["pool_kept_over_budget"] == sid + 1
        assert len(set(held)) == 1  # one buffer, job after job
        s.close()
        assert s.write_stats()["pool_held_bytes"] == 0 and s._free_rounds == {}

    @pytest.mark.parametrize("regions", [1, 4])
    def test_a_job_written_into_a_buffer_another_job_filled_is_exact_and_zero_padded(self, tmp_path, regions):
        """The padding the exchange sends — rows past a region's used count,
        the tail of a block's last row — is zeros in the second job's round
        although the first job left 0xFF in every byte of the buffer."""
        s = self._store(tmp_path, regions=regions)
        self._job(s, 0, {r: b"\xff" * self.ROUND for r in range(regions)}, regions)
        first = s._state(0).staging
        assert first.all()
        del first
        s.remove_shuffle(0)
        rng = np.random.default_rng(47)
        blocks = {r: rng.integers(1, 256, size=int(rng.integers(1, self.ROUND // 3)), dtype=np.uint8).tobytes()
                  for r in range(regions) if r != 2}  # region 2 of 4 stays empty
        self._job(s, 1, blocks, regions)
        assert s.write_stats()["pool_hits"] == 1 and s.write_stats()["pool_kept_over_budget"] == 1
        expected = np.zeros(regions * self.ROUND, dtype=np.uint8)
        for r, payload in blocks.items():
            assert s.read_block(1, 0, r) == payload
            expected[r * self.ROUND : r * self.ROUND + len(payload)] = np.frombuffer(payload, dtype=np.uint8)
        (payload, sizes), = s.seal(1)
        assert np.array_equal(np.asarray(payload).reshape(-1).view(np.uint8), expected)
        assert [int(n) for n in sizes] == [-(-len(blocks.get(r, b"")) // ALIGN) for r in range(regions)]
        del payload
        s.close()

    def test_a_busy_buffer_is_dropped_and_counted_never_reused(self, tmp_path):
        s = self._store(tmp_path)
        self._job(s, 0, {0: b"a" * 3000})
        (view, _sizes), = s.seal(0)  # the sealed round, somebody's across the removal (an exchange's)
        assert np.shares_memory(view, s._state(0).staging)
        s.remove_shuffle(0)
        stats = s.write_stats()
        assert (stats["pool_dropped_busy"], stats["pool_kept_over_budget"], stats["pool_held_bytes"]) == (1, 0, 0)
        self._job(s, 1, {0: b"b" * 3000})
        assert not np.shares_memory(view, s._state(1).staging)
        assert view.reshape(-1).view(np.uint8)[:3000].tobytes() == b"a" * 3000 and s.read_block(1, 0, 0) == b"b" * 3000
        assert (s.write_stats()["pool_hits"], s.write_stats()["pool_misses"]) == (0, 2)
        del view
        s.remove_shuffle(1)  # nobody's: the floor's
        assert s.write_stats()["pool_kept_over_budget"] == 1 and s.write_stats()["pool_held_bytes"] == self.ROUND
        s.close()

    @pytest.mark.parametrize("available, kept", [
        (4 * (1 << 16), True), (4 * (1 << 16) - 1, False), (None, True),
    ], ids=["a-quarter", "over-a-quarter", "no-meminfo"])
    def test_the_floor_is_bounded_by_a_share_of_the_hosts_memory(self, tmp_path, monkeypatch, available, kept):
        from sparkucx_tpu.store import hbm_store

        monkeypatch.setattr(hbm_store, "_mem_available_bytes", lambda: available)
        s = self._store(tmp_path, max_host_pool_bytes=1 << 13)  # under an eighth of either reading
        assert s._ram_budget == 1 << 13
        self._job(s, 0, {0: b"x" * 100})
        s.remove_shuffle(0)
        stats = s.write_stats()
        assert stats["pool_kept_over_budget"] == int(kept) and stats["pool_held_bytes"] == (self.ROUND if kept else 0)
        s.close()
        assert self._store(tmp_path, max_host_pool_bytes=0)._floor_limit == 0  # no RAM tier, no floor

    def test_a_round_that_rolls_still_spills_and_its_live_buffer_is_the_floors(self, tmp_path):
        """``_admit_ram_round``'s verdict does not move: a round of the
        staging size is over the budget and goes to the disk tier, its buffer
        reused from round to round; the removal keeps that one buffer."""
        s = self._store(tmp_path)
        s.create_shuffle(0, 3, 1)
        for m in range(3):
            w = s.map_writer(0, m)
            w.write_partition(0, bytes([m + 1]) * self.ROUND)
            w.commit()
        assert [s.round_tier(0, k) for k in range(3)] == ["disk", "disk", "host"]
        assert all(s.read_block(0, m, 0) == bytes([m + 1]) * self.ROUND for m in range(3))
        s.remove_shuffle(0)
        stats = s.write_stats()
        assert (stats["ram_rounds"], stats["recycled_rounds"], stats["pool_kept_over_budget"]) == (0, 2, 1)
        s.create_shuffle(1, 3, 1)  # ... and with the kept buffer as its staging it spills the same
        for m in range(3):
            w = s.map_writer(1, m)
            w.write_partition(0, bytes([m + 9]) * self.ROUND)
            w.commit()
        assert [s.round_tier(1, k) for k in range(3)] == ["disk", "disk", "host"]
        assert all(s.read_block(1, m, 0) == bytes([m + 9]) * self.ROUND for m in range(3))
        assert s.write_stats()["pool_hits"] == 1 and s.write_stats()["ram_rounds"] == 0
        s.close()

    def test_the_kept_buffer_goes_before_a_live_round_goes_to_disk(self, tmp_path):
        """Where the free list is let go today the kept buffer goes too: a
        smaller shuffle's round that fits the budget stays in RAM, and the
        free list — the floor's buffer — is cleared to make its room."""
        small = self.BUDGET // 2
        s = self._store(tmp_path)
        self._job(s, 0, {0: b"x" * 100})
        s.remove_shuffle(0)
        assert s.write_stats()["pool_held_bytes"] == self.ROUND
        s.create_shuffle(1, 2, 1, capacity=small)
        for m in range(2):
            w = s.map_writer(1, m)
            w.write_partition(0, bytes([m + 1]) * small)
            w.commit()
        assert [s.round_tier(1, k) for k in range(2)] == ["host", "host"]
        assert s.write_stats()["pool_held_bytes"] == 0 and s._free_rounds == {}
        assert s._ram_round_bytes + s.write_stats()["pool_held_bytes"] <= self.BUDGET
        s.close()

    def test_a_demoted_round_is_not_the_floors(self, tmp_path):
        """A demotion sheds memory: the buffer of a sealed one-round shuffle
        sent to the disk tier is over the budget and is released."""
        import gc
        import weakref

        s = self._store(tmp_path)
        self._job(s, 0, {0: b"d" * 5000})
        ref = weakref.ref(s._state(0).staging)
        s.seal(0)
        gc.collect()
        gc.disable()
        try:
            assert s.demote_round(0, 0) == "host->disk"
            assert s.read_block(0, 0, 0) == b"d" * 5000
            stats = s.write_stats()
            assert (stats["pool_held_bytes"], stats["pool_kept_over_budget"]) == (0, 0)
            if not stats["pool_dropped_busy"]:  # (the CPU backend's put may alias the host array)
                assert ref() is None
        finally:
            gc.enable()
        s.close()


class TestRecycledBuffersAreNobodysElse:
    """A round buffer that anything still refers to at ``remove_shuffle`` is
    never given to the next shuffle: its holder keeps its bytes, the collector
    frees it, ``pool_dropped_busy`` counts it."""

    ROUND = 1 << 20  # page-aligned allocations: what a CPU backend's device_put may alias

    def _store(self):
        return HbmBlockStore(TpuShuffleConf(staging_capacity_per_executor=self.ROUND, block_alignment=ALIGN))

    def _fill(self, s, shuffle_id, rounds, salt):
        for m in range(rounds):
            w = s.map_writer(shuffle_id, m)
            w.write_partition(0, bytes([salt + m]) * self.ROUND)
            w.commit()

    def test_views_taken_before_the_removal_hold_their_bytes(self):
        import jax

        s = self._store()
        s.create_shuffle(0, 4, 1)
        self._fill(s, 0, 4, salt=1)
        view, off, ln = s.block_staging_view(0, 0, 0)  # round 0, completed: zero-copy
        assert np.shares_memory(view, s._state(0).prev_rounds[0][0])
        sealed = s.seal(0)
        on_device = jax.device_put(sealed[1][0], jax.devices()[0])  # round 1
        sealed_round = sealed[2][0]  # round 2, as the exchange holds it
        del sealed
        aliased = np.shares_memory(np.asarray(on_device), s._state(0).prev_rounds[1][0])
        s.remove_shuffle(0)
        stats = s.write_stats()
        # the live round was nobody's; of the three others, two (or three,
        # where device_put aliased the host array) were somebody's
        assert stats["pool_dropped_busy"] == 2 + aliased
        assert stats["pool_held_bytes"] == (2 - aliased) * self.ROUND

        s.create_shuffle(1, 4, 1)
        self._fill(s, 1, 4, salt=101)  # the next shuffle fills its rounds
        assert bytes(view[off : off + ln]) == bytes([1]) * self.ROUND
        assert np.asarray(on_device).view(np.uint8).tobytes() == bytes([2]) * self.ROUND
        assert np.asarray(sealed_round).view(np.uint8).tobytes() == bytes([3]) * self.ROUND
        assert all(s.read_block(1, m, 0) == bytes([101 + m]) * self.ROUND for m in range(4))

        # with the references gone the next removal recycles every buffer
        del view, on_device, sealed_round
        before = s.write_stats()
        s.remove_shuffle(1)
        after = s.write_stats()
        assert after["pool_dropped_busy"] == before["pool_dropped_busy"]
        assert after["pool_held_bytes"] == 4 * self.ROUND
        assert all(not buf.any() for buf in s._free_rounds[self.ROUND])
        s.close()

    def test_a_live_sealed_round_on_the_device_is_not_recycled_under_it(self):
        """A single-round shuffle sealed with a device: the device array may
        alias the host staging on the CPU backend, and a reader holds it."""
        import jax

        s = HbmBlockStore(
            TpuShuffleConf(staging_capacity_per_executor=self.ROUND, block_alignment=ALIGN),
            device=jax.devices()[0],
        )
        s.create_shuffle(0, 1, 1)
        self._fill(s, 0, 1, salt=9)
        ((payload, _sizes),) = s.seal(0)
        s.remove_shuffle(0)
        s.create_shuffle(1, 1, 1)
        self._fill(s, 1, 1, salt=77)
        assert np.asarray(payload).view(np.uint8).tobytes() == bytes([9]) * self.ROUND
        assert s.read_block(1, 0, 0) == bytes([77]) * self.ROUND
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

    def _spilled_store(self, budget):
        s = HbmBlockStore(
            TpuShuffleConf(
                staging_capacity_per_executor=4096, block_alignment=ALIGN, max_host_pool_bytes=budget
            )
        )
        s.create_shuffle(0, 3, 1)
        self._fill_rounds(s, 0, 3, s._state(0).region_size)
        return s

    @NO_RAM_ROUNDS
    def test_close_removes_default_tempdir(self, budget):
        import os

        s = self._spilled_store(budget)
        d = s._spill_dir
        assert d is not None and os.path.isdir(d)
        assert os.path.basename(d).startswith("sparkucx_tpu_spill_e")
        s.close()
        assert not os.path.exists(d)

    @NO_RAM_ROUNDS
    def test_remove_last_spilled_shuffle_reclaims_dir(self, budget):
        import os

        s = self._spilled_store(budget)
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

    @NO_RAM_ROUNDS
    def test_no_leftover_spill_dirs_in_tempdir(self, monkeypatch, tmp_path, budget):
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
        s = self._spilled_store(budget)
        assert leftovers() != before
        s.close()
        assert leftovers() == before
