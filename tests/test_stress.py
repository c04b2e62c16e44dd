"""Concurrency stress: many mapper threads writing one shuffle while commits
and reads race — structural-safety evidence the reference never had
(SURVEY.md section 5.2: no race detection, safety is structural only)."""

import threading
import time

import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.block import MemoryBlock, ShuffleBlockId
from sparkucx_tpu.core.operation import OperationStatus, TransportError
from sparkucx_tpu.store.hbm_store import HbmBlockStore
from sparkucx_tpu.transport.tpu import TpuShuffleCluster

N_EXEC = 4


def _payload(m, r):
    rng = np.random.default_rng(1000 * m + r)
    return rng.integers(0, 256, size=int(rng.integers(1, 1200)), dtype=np.uint8).tobytes()


class TestConcurrentShuffle:
    def test_parallel_map_writers_then_exchange(self):
        """All map tasks write concurrently from threads (the Spark executor
        thread-pool shape); one exchange; every block verified."""
        conf = TpuShuffleConf(
            staging_capacity_per_executor=2 << 20, block_alignment=128, num_executors=N_EXEC
        )
        cluster = TpuShuffleCluster(conf, num_executors=N_EXEC)
        M, R = 16, 16
        meta = cluster.create_shuffle(0, M, R)
        errors = []

        def map_task(m):
            try:
                t = cluster.transport(meta.map_owner[m])
                w = t.store.map_writer(0, m)
                for r in range(R):
                    w.write_partition(r, _payload(m, r))
                t.commit_block(w.commit().pack())
            except Exception as e:  # surfaced below
                errors.append((m, e))

        threads = [threading.Thread(target=map_task, args=(m,)) for m in range(M)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert not errors, errors

        cluster.run_exchange(0)

        read_errors = []

        def reduce_task(r):
            try:
                consumer = meta.owner_of_reduce(r)
                t = cluster.transport(consumer)
                bids = [ShuffleBlockId(0, m, r) for m in range(M)]
                bufs = [MemoryBlock(np.zeros(2048, np.uint8), size=2048) for _ in range(M)]
                reqs = t.fetch_blocks_by_block_ids(consumer, bids, bufs, [None] * M)
                for m, (req, buf) in enumerate(zip(reqs, bufs)):
                    res = req.wait(5)
                    assert res.status == OperationStatus.SUCCESS, str(res.error)
                    got = buf.host_view()[: buf.size].tobytes()
                    assert got == _payload(m, r), f"mismatch map={m} reduce={r}"
            except Exception as e:
                read_errors.append((r, e))

        rthreads = [threading.Thread(target=reduce_task, args=(r,)) for r in range(R)]
        for th in rthreads:
            th.start()
        for th in rthreads:
            th.join()
        assert not read_errors, read_errors

    def test_task_retry_race_first_commit_wins(self):
        """Two attempts of the same map task race; exactly one set of writes
        lands (IndexShuffleBlockResolver's check-or-replace semantics)."""
        conf = TpuShuffleConf(
            staging_capacity_per_executor=1 << 20, block_alignment=128, num_executors=2
        )
        cluster = TpuShuffleCluster(conf, num_executors=2)
        meta = cluster.create_shuffle(0, 1, 2)
        t = cluster.transport(meta.map_owner[0])

        barrier = threading.Barrier(2)
        results = []

        def attempt(tag):
            barrier.wait()
            w = t.store.map_writer(0, 0)
            for r in range(2):
                w.write_partition(r, bytes([tag]) * 400)
            info = w.commit()
            results.append((tag, w.is_retry_discard, info))

        threads = [threading.Thread(target=attempt, args=(tag,)) for tag in (1, 2)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()

        # both commits returned a consistent table; the store holds ONE attempt
        t.commit_block(results[0][2].pack())
        cluster.run_exchange(0)
        blocks = [
            cluster.locate_received_block(meta.owner_of_reduce(r), 0, 0, r)[0].tobytes()
            for r in range(2)
        ]
        tags = {b[0] for b in blocks if b}
        assert len(tags) == 1, f"mixed attempts visible: {tags}"
        assert all(len(b) == 400 for b in blocks)

    def test_concurrent_shuffle_create_remove(self):
        """Shuffle lifecycle churn from threads: create/write/exchange/remove
        many shuffles concurrently without cross-talk."""
        conf = TpuShuffleConf(
            staging_capacity_per_executor=1 << 20, block_alignment=128, num_executors=2
        )
        cluster = TpuShuffleCluster(conf, num_executors=2)
        errors = []

        def lifecycle(sid):
            try:
                meta = cluster.create_shuffle(sid, 2, 2)
                for m in range(2):
                    t = cluster.transport(meta.map_owner[m])
                    w = t.store.map_writer(sid, m)
                    for r in range(2):
                        w.write_partition(r, bytes([sid]) * 256)
                    t.commit_block(w.commit().pack())
                cluster.run_exchange(sid)
                for r in range(2):
                    view, ln = cluster.locate_received_block(
                        meta.owner_of_reduce(r), sid, 0, r
                    )
                    assert view.tobytes() == bytes([sid]) * 256
                cluster.remove_shuffle(sid)
            except Exception as e:
                errors.append((sid, e))

        threads = [threading.Thread(target=lifecycle, args=(sid,)) for sid in range(6)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert not errors, errors


ALIGN = 128


#: where completed rounds of 4096 B go: all to the disk tier (no RAM tier, the
#: store of before it), the first three in RAM and the rest to disk, all in RAM
ROUND_TIERS = {
    "disk-tier": {"max_host_pool_bytes": 0},
    "across-the-budget": {"max_host_pool_bytes": 3 * 4096},
    "ram-tier": {},
}


class TestDiskTierConcurrency:
    """Pull-fallback reads racing ``_rollover`` and ``remove_shuffle`` across
    many rounds, on the disk tier and in RAM (VERDICT r4 task 7).  Every
    payload is a single map-distinctive byte repeated over the whole region,
    so ANY torn read — bytes from two rounds, a half-zeroed epoch swap, a
    recycled buffer — shows up as a wrong byte, not a flaky length."""

    def _store(self, tmp_path, **kw):
        conf = TpuShuffleConf(
            staging_capacity_per_executor=4096,
            block_alignment=ALIGN,
            spill_dir=str(tmp_path),
            **kw,
        )
        return HbmBlockStore(conf)

    @staticmethod
    def _pattern(m):
        return bytes([(m % 250) + 1])

    @pytest.mark.parametrize("tier", list(ROUND_TIERS))
    def test_reads_race_rollover_across_rounds(self, tmp_path, tier):
        """Readers hammer committed blocks while a writer forces >= 6 epoch
        rollovers into the memmap tier, the RAM tier or both; every read must
        return the exact pattern of its round."""
        s = self._store(tmp_path, **ROUND_TIERS[tier])
        ROUNDS = 8
        s.create_shuffle(0, ROUNDS, 1)
        region = s.region_bytes(0)
        committed = []  # map ids with a finished commit (reader work list)
        stop = threading.Event()
        failures = []

        def reader():
            rng = np.random.default_rng(threading.get_ident() % (1 << 32))
            # any exception is a failure — committed blocks must stay readable
            # through rollovers; a non-TransportError crash must not pass
            # silently as a dead thread
            try:
                while not stop.is_set() or committed:
                    if not committed:
                        time.sleep(0.0005)
                        continue
                    m = committed[int(rng.integers(0, len(committed)))]
                    expect = self._pattern(m) * region
                    got = s.read_block(0, m, 0)
                    if got != expect:
                        failures.append(f"torn read_block map={m}")
                        return
                    view = s.block_staging_view(0, m, 0)
                    if view is not None:
                        arr, off, ln = view
                        if bytes(arr[off : off + ln]) != expect:
                            failures.append(f"torn staging_view map={m}")
                            return
                    if stop.is_set():
                        return
            except BaseException as e:
                failures.append(f"reader crashed: {type(e).__name__}: {e}")

        readers = [threading.Thread(target=reader) for _ in range(4)]
        for th in readers:
            th.start()
        for m in range(ROUNDS):
            w = s.map_writer(0, m)
            w.write_partition(0, self._pattern(m) * region)
            w.commit()
            committed.append(m)
            time.sleep(0.002)  # give readers a window inside each round
        stop.set()
        for th in readers:
            th.join(timeout=30)
        assert not failures, failures
        assert s.num_rounds(0) >= 6, "staging never rolled over — test lost its point"
        # rounds really went to the tier the case is about
        on_disk = [isinstance(p, np.memmap) for p, _ in s._state(0).prev_rounds]
        assert {"disk-tier": all, "across-the-budget": any, "ram-tier": lambda x: not any(x)}[tier](on_disk)
        assert on_disk == sorted(on_disk)  # RAM first, then disk
        s.remove_shuffle(0)
        s.close()

    @pytest.mark.parametrize("tier", list(ROUND_TIERS))
    def test_reads_race_remove_shuffle(self, tmp_path, tier):
        """remove_shuffle fires while readers are mid-read on completed rounds
        and the next shuffle at once fills its rounds — in the removed one's
        buffers, where they stayed in RAM: each read returns exact bytes or a
        clean TransportError — never torn data, never the next shuffle's,
        never a crash.  Spill accounting drains to zero afterwards."""
        s = self._store(tmp_path, **ROUND_TIERS[tier])
        ROUNDS = 5
        s.create_shuffle(0, ROUNDS, 1)
        region = s.region_bytes(0)
        for m in range(ROUNDS):
            w = s.map_writer(0, m)
            w.write_partition(0, self._pattern(m) * region)
            w.commit()
        failures = []
        started = threading.Barrier(5)

        def reader():
            rng = np.random.default_rng(threading.get_ident() % (1 << 32))
            started.wait()
            try:
                for _ in range(400):
                    m = int(rng.integers(0, ROUNDS))
                    try:
                        got = s.read_block(0, m, 0)
                    except TransportError:
                        return  # shuffle removed underneath us — clean refusal
                    if got != self._pattern(m) * region:
                        failures.append(f"torn read after remove map={m}")
                        return
            except BaseException as e:  # anything else = dirty failure, not clean refusal
                failures.append(f"reader crashed: {type(e).__name__}: {e}")

        readers = [threading.Thread(target=reader) for _ in range(4)]
        for th in readers:
            th.start()
        started.wait()
        time.sleep(0.005)  # land the removal mid-hammer
        s.remove_shuffle(0)
        s.create_shuffle(1, ROUNDS, 1)
        for m in range(ROUNDS):
            w = s.map_writer(1, m)
            w.write_partition(0, b"\xfe" * region)  # no pattern of shuffle 0
            w.commit()
        for th in readers:
            th.join(timeout=30)
        assert not failures, failures
        stats = s.write_stats()
        if tier != "disk-tier":  # the next shuffle did write where the removed one had been
            assert stats["pool_hits"] >= 3 and stats["pool_dropped_busy"] == 0
        s.remove_shuffle(1)
        assert s._spill_bytes == 0, "spill accounting leaked after remove"
        s.close()

    def test_reads_race_remove_shuffle_shm_arm(self, tmp_path):
        """Same race over shm-backed staging (the zero-copy serving tier):
        block_staging_view hands out private copies exactly because the shm
        mapping can be munmapped at any time after the lock drops."""
        from sparkucx_tpu import native

        if not native.native_available():
            pytest.skip(f"native build unavailable: {native.build_error()}")
        s = self._store(tmp_path, use_shm_staging=True)
        M = 4
        s.create_shuffle(0, M, 1)
        region = s.region_bytes(0)
        payload_len = region // M // ALIGN * ALIGN  # all maps fit in ONE round (shm can't roll over)
        for m in range(M):
            w = s.map_writer(0, m)
            w.write_partition(0, self._pattern(m) * payload_len)
            w.commit()
        failures = []
        started = threading.Barrier(5)

        def reader():
            rng = np.random.default_rng(threading.get_ident() % (1 << 32))
            started.wait()
            try:
                for _ in range(300):
                    m = int(rng.integers(0, M))
                    try:
                        view = s.block_staging_view(0, m, 0)
                        if view is None:
                            return  # removed — staging gone, clean refusal
                        arr, off, ln = view
                        got = bytes(arr[off : off + ln])
                    except TransportError:
                        return
                    if got != self._pattern(m) * payload_len:
                        failures.append(f"torn shm read map={m}")
                        return
            except BaseException as e:  # e.g. SIGSEGV-adjacent munmap errors surface here
                failures.append(f"reader crashed: {type(e).__name__}: {e}")

        readers = [threading.Thread(target=reader) for _ in range(4)]
        for th in readers:
            th.start()
        started.wait()
        time.sleep(0.003)
        s.remove_shuffle(0)  # munmaps the shm arena under the store lock
        for th in readers:
            th.join(timeout=30)
        assert not failures, failures
        s.close()

    @pytest.mark.parametrize("tier", ["disk-tier", "across-the-budget"])
    def test_spill_cap_enforced_under_concurrent_writers(self, tmp_path, tier):
        """Writer threads race rollovers against a 2-round disk cap: the cap
        must hold (TransportError, no overshoot) and accounting must stay
        exact through the failures and the final remove."""
        cap = 2 * 4096
        s = self._store(tmp_path, spill_disk_cap_bytes=cap, **ROUND_TIERS[tier])
        M = 10
        s.create_shuffle(0, M, 1)
        region = s.region_bytes(0)
        cap_hits = []
        ok = []

        unexpected = []

        def writer(m):
            try:
                w = s.map_writer(0, m)
                w.write_partition(0, self._pattern(m) * region)
                w.commit()
                ok.append(m)
            except TransportError as e:
                if "spill cap" in str(e):
                    cap_hits.append(m)
                else:
                    unexpected.append(f"map {m}: {e}")
            except BaseException as e:
                unexpected.append(f"map {m} crashed: {type(e).__name__}: {e}")

        threads = [threading.Thread(target=writer, args=(m,)) for m in range(M)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert not unexpected, unexpected
        assert cap_hits, "cap never enforced despite 10 full rounds vs a 2-round cap"
        assert 0 < s._spill_bytes <= cap, f"spilled {s._spill_bytes} B past cap {cap}"
        # committed rounds still read back exactly
        for m in ok:
            assert s.read_block(0, m, 0) == self._pattern(m) * region
        s.remove_shuffle(0)
        assert s._spill_bytes == 0
        s.close()
