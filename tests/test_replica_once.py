"""A replica is written once (PR 46).

The neighbor-replication tier copies each byte of a sealed round ONCE: the
source store snapshots, under its lock, only the round's table, the blocks'
offsets and a reference to the round's array; the blocks are then gathered,
outside the lock, into one destination array that the ring successor's store
installs as it is — the replica's own bytes, never a view of the source's
staging — and a recovery reads it back as a read-only view.  On a chip the
destination comes from the cluster's landing pool; here that path runs with
the predicate patched, as in ``tests/test_recv_landing.py``.  Bytes and
counts; no rate.
"""

import threading

import numpy as np
import pytest

import sparkucx_tpu.store.hbm_store as store_mod
import sparkucx_tpu.transport.tpu as tpu_mod
from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.native import LandingPool
from sparkucx_tpu.store.hbm_store import HbmBlockStore, default_peer_ranges
from sparkucx_tpu.testing import faults
from sparkucx_tpu.transport.tpu import TpuShuffleCluster
from sparkucx_tpu.utils.trace import TRACER

ALIGN = 128
REGION = 4096
REGIONS = 2
MIB = 1 << 20

needs_pool = pytest.mark.skipif(
    "LandingPool.create(MIB) is None", reason="no landing pool here (no compiler, or no NumPy allocator hook)"
)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture
def tracer():
    """The process-wide tracer, enabled and cleared; back to what it was afterwards."""
    enabled, recording = TRACER.enabled, TRACER.recording
    TRACER.enable()
    TRACER.clear()
    yield TRACER
    TRACER.enabled, TRACER.recording = enabled, recording
    TRACER.clear()


def np_alloc(nbytes: int) -> np.ndarray:
    return np.empty(nbytes, dtype=np.uint8)


def make_store(tmp_path, budget: int) -> HbmBlockStore:
    """Rounds of two 4 KiB regions; ``budget`` 0 sends every completed round
    to the disk tier (an ``np.memmap``), a large one keeps them in RAM."""
    return HbmBlockStore(
        TpuShuffleConf(
            staging_capacity_per_executor=REGIONS * REGION,
            block_alignment=ALIGN,
            spill_dir=str(tmp_path),
            max_host_pool_bytes=budget,
        )
    )


def fill(s: HbmBlockStore, shuffle_id: int = 0, maps: int = 7, seed: int = 46):
    """Ragged blocks, an empty one among them, two partitions a map task,
    through enough rounds that RAM / disk rounds and a live round exist.
    Returns {(map, reduce): payload}."""
    rng = np.random.default_rng(seed)
    s.create_shuffle(shuffle_id, maps, REGIONS, peer_ranges=default_peer_ranges(REGIONS, REGIONS))
    oracle = {}
    for m in range(maps):
        w = s.map_writer(shuffle_id, m)
        for r in range(REGIONS):
            n = 0 if (m, r) == (1, 1) else int(rng.integers(900, 1900))
            oracle[(m, r)] = rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
            w.write_partition(r, oracle[(m, r)])
        w.commit()
    assert s.num_rounds(shuffle_id) >= 3
    return oracle


def old_replica_source(s: HbmBlockStore, shuffle_id: int, oracle):
    """What ``replica_source`` returned before PR 46, from the store's own
    table: a round's sorted ``(map, reduce, length)`` entries and the blocks'
    bytes joined in that order."""
    st = s._state(shuffle_id)
    out = []
    for rnd in range(st.round + 1):
        keys = sorted(k for k, e in st.blocks.items() if e.round == rnd and e.local)
        if keys:
            out.append(
                (rnd, [(m, r, st.blocks[(m, r)].length) for m, r in keys], b"".join(oracle[k] for k in keys))
            )
    return out


def source_rounds(s: HbmBlockStore, shuffle_id: int):
    st = s._state(shuffle_id)
    return [snap for snap, _ in st.prev_rounds] + [st.staging]


# -- (i) the body of every round, on every tier --------------------------------------


@pytest.mark.parametrize("alloc", [None, np_alloc], ids=["bytearray", "array"])
@pytest.mark.parametrize("sealed", [False, True], ids=["live", "sealed"])
@pytest.mark.parametrize("budget", [0, 1 << 30], ids=["disk-rounds", "ram-rounds"])
def test_every_rounds_body_is_the_old_body_byte_for_byte(tmp_path, budget, sealed, alloc):
    s = make_store(tmp_path, budget)
    oracle = fill(s)
    tiers = {type(snap) for snap, _ in s._state(0).prev_rounds}
    assert tiers == ({np.memmap} if budget == 0 else {np.ndarray})
    if sealed:
        s.seal(0)
    want = old_replica_source(s, 0, oracle)
    got = s.replica_source(0, alloc)
    assert [(rnd, entries) for rnd, entries, _ in got] == [(rnd, entries) for rnd, entries, _ in want]
    for (_, _, body), (_, _, old) in zip(got, want):
        assert isinstance(body, bytearray if alloc is None else np.ndarray)
        assert bytes(body) == old and len(body) == len(old)
        if alloc is not None:
            assert store_mod._owns_flat_bytes(body)
            assert not any(np.shares_memory(body, snap) for snap in source_rounds(s, 0))
    s.close()


# -- (ii) independence ------------------------------------------------------------------


def test_a_replica_outlives_its_sources_staging_and_its_sources_store(tmp_path):
    src, dst = make_store(tmp_path / "src", 1 << 30), make_store(tmp_path / "dst", 1 << 30)
    oracle = fill(src)
    src.seal(0)
    for rnd, entries, body in src.replica_source(0, np_alloc):
        dst.put_replica(0, 5, rnd, entries, body)
    rounds = source_rounds(src, 0)
    for key in oracle:
        arr, _off, _ln = dst.replica_view(0, *key)
        assert not any(np.shares_memory(arr, snap) for snap in rounds)
    for snap in rounds:  # the next job's bytes, then the executor's death
        snap[:] = 0xEE
    del rounds, snap
    src.close()
    for key, payload in oracle.items():
        arr, off, ln = dst.replica_view(0, *key)
        assert bytes(arr[off : off + ln]) == payload
        assert dst.replica_block(0, 5, *key).tobytes() == payload
        assert dst.replica_block(0, 4, *key) is None  # another source's: not served
    assert dst.replica_stats()["replica_bytes"] == sum(map(len, oracle.values()))
    dst.remove_shuffle(0)
    assert dst.replica_stats() == {"replica_bytes": 0, "replica_rounds": 0, "replica_sources": 0}


# -- (iii) what put_replica installs --------------------------------------------------


def test_put_replica_installs_an_owned_array_and_copies_a_view(tmp_path):
    s = make_store(tmp_path, 0)
    entries = [(0, 0, 300), (0, 1, 0), (1, 0, 724)]
    payload = np.random.default_rng(3).integers(0, 256, size=1024, dtype=np.uint8)

    owned = payload.copy()
    s.put_replica(0, 1, 0, entries, owned)
    arr, off, ln = s.replica_view(0, 1, 0)
    assert arr is owned and (off, ln) == (300, 724) and not arr.flags.writeable
    assert arr.ctypes.data == owned.ctypes.data

    backing = np.concatenate([payload, payload])
    s.put_replica(0, 1, 1, entries, backing[:1024])  # somebody else's buffer shows through
    arr = s._replicas[(0, 1)][1][1]
    assert not np.shares_memory(arr, backing) and arr.tobytes() == payload.tobytes()

    as_bytes, as_bytearray = payload.tobytes(), bytearray(payload.tobytes())
    s.put_replica(0, 2, 0, entries, as_bytes)
    s.put_replica(0, 3, 0, entries, as_bytearray)
    assert s._replicas[(0, 2)][0][1].tobytes() == as_bytes
    assert np.shares_memory(s._replicas[(0, 3)][0][1], np.frombuffer(as_bytearray, dtype=np.uint8))
    assert s.replica_stats() == {"replica_bytes": 4096, "replica_rounds": 4, "replica_sources": 3}

    s.put_replica(0, 1, 0, entries, payload.copy())  # a repeated put replaces
    assert s.replica_stats()["replica_bytes"] == 4096
    block = s.replica_block(0, 1, 1, 0)
    assert block.tobytes() == payload[300:].tobytes() and not block.flags.writeable
    with pytest.raises(ValueError):
        block[0] = 1
    with pytest.raises(Exception, match="table claims"):
        s.put_replica(0, 1, 2, entries, payload[:100].copy())
    s.close()


# -- the copy holds no lock ---------------------------------------------------------------


def test_the_gather_runs_outside_the_stores_lock(tmp_path, monkeypatch):
    """With the gather of a sealed round held on an event, the source store
    still takes writes and answers, and the successor's store serves."""
    src, dst = make_store(tmp_path / "src", 1 << 30), make_store(tmp_path / "dst", 1 << 30)
    oracle = fill(src)
    src.seal(0)
    src.create_shuffle(1, 1, REGIONS, peer_ranges=default_peer_ranges(REGIONS, REGIONS))
    dst.put_replica(9, 0, 0, [(0, 0, 4)], b"abcd")
    entered, release = threading.Event(), threading.Event()
    gather = store_mod._gather_blocks

    def held(dst_arr, source, segments):
        entered.set()
        assert release.wait(30)
        gather(dst_arr, source, segments)

    monkeypatch.setattr(store_mod, "_gather_blocks", held)
    got = []
    replicator = threading.Thread(target=lambda: got.extend(src.replica_source(0, np_alloc)))
    replicator.start()
    try:
        assert entered.wait(30)
        done = []

        def others():
            w = src.map_writer(1, 0)
            w.write_partition(0, b"x" * 100)  # close_partition: the store's lock
            w.commit()
            done.append(src.stats(0)["num_blocks"])
            done.append(src.replica_stats()["replica_rounds"])
            done.append(dst.replica_view(9, 0, 0)[2])
            done.append(dst.replica_view(0, 0, 0))  # not landed yet: None, not a wait

        other = threading.Thread(target=others)
        other.start()
        other.join(30)
        assert not other.is_alive(), "a store call waited for the replica's copy"
        assert done == [len(oracle), 0, 4, None]
        assert not got
    finally:
        release.set()
        replicator.join(30)
    assert [bytes(body) for _, _, body in got] == [old for _, _, old in old_replica_source(src, 0, oracle)]
    src.close()
    dst.close()


def test_what_can_change_under_a_reader_is_gathered_under_the_lock(tmp_path, monkeypatch):
    """The live round of a shuffle that is not sealed is rolled and zeroed by
    the next write: its blocks are copied while the lock is held."""
    s = make_store(tmp_path, 1 << 30)
    fill(s)
    locked = []
    gather = store_mod._gather_blocks

    def watched(dst_arr, source, segments):
        locked.append(s._lock._is_owned())
        gather(dst_arr, source, segments)

    monkeypatch.setattr(store_mod, "_gather_blocks", watched)
    rounds = len(s.replica_source(0))
    assert locked == [True] + [False] * (rounds - 1)  # the live round first, under the lock
    s.seal(0)
    del locked[:]
    s.replica_source(0)
    assert locked == [False] * rounds
    s.close()


# -- the cluster: one copy a byte, whole before the first submit --------------------------


def make_cluster(n: int = 4, **conf_kw) -> TpuShuffleCluster:
    conf_kw.setdefault("staging_capacity_per_executor", n * 4096)
    conf_kw.setdefault("block_alignment", ALIGN)
    conf_kw.setdefault("elastic", True)
    conf_kw.setdefault("replication_factor", 1)
    return TpuShuffleCluster(TpuShuffleConf(num_executors=n, **conf_kw), num_executors=n)


def stage(cluster, shuffle_id: int, maps: int = 12, reducers: int = 8, seed: int = 7):
    meta = cluster.create_shuffle(shuffle_id, maps, reducers)
    rng = np.random.default_rng(seed)
    oracle = {}
    for m in range(maps):
        t = cluster.transport(meta.map_owner[m])
        w = t.store.map_writer(shuffle_id, m)
        for r in range(reducers):
            oracle[(m, r)] = rng.integers(0, 256, size=2000, dtype=np.uint8).tobytes()
            w.write_partition(r, oracle[(m, r)])
        t.commit_block(w.commit().pack())
    return meta, oracle


def read_back(cluster, meta, oracle, shuffle_id: int):
    for (m, r), staged in oracle.items():
        view, length = cluster.locate_received_block(meta.owner_of_reduce(r), shuffle_id, m, r)
        assert bytes(view[:length]) == staged, (m, r)


@pytest.mark.parametrize("factor", [1, 2])
def test_the_replicas_are_whole_when_the_first_round_is_submitted(factor):
    """The guarantee's "before the first submit": at the fault point
    ``exchange.submit`` of round 0 every successor holds every round of the
    executors it stands in for, and each holds bytes of its own."""
    n = 4
    cluster = make_cluster(n, replication_factor=factor)
    meta, oracle = stage(cluster, 0)
    seen = {}

    def at_first_submit(**_ctx):
        seen["rounds"] = [t.store.replica_stats()["replica_rounds"] for t in cluster.transports]
        seen["bytes"] = [t.store.replica_stats()["replica_bytes"] for t in cluster.transports]
        seen["stats"] = dict(cluster.elastic_stats)

    faults.arm("exchange.submit", at_first_submit, times=1, match={"shuffle_id": 0, "round": 0})
    cluster.run_exchange(0)
    rounds = [t.store.num_rounds(0) for t in cluster.transports]
    staged = [sum(len(p) for (m, _), p in oracle.items() if meta.map_owner[m] == e) for e in range(n)]
    assert seen["rounds"] == [sum(rounds[(e - k) % n] for k in range(1, factor + 1)) for e in range(n)]
    assert seen["bytes"] == [sum(staged[(e - k) % n] for k in range(1, factor + 1)) for e in range(n)]
    assert seen["stats"]["replicated_bytes"] == factor * sum(staged)
    assert seen["stats"]["replica_copied_bytes"] == seen["stats"]["replicated_bytes"]
    holders = [
        cluster.transport((2 + k) % n).store.replica_view(0, 2, 0)[0] for k in range(1, factor + 1)
    ]
    assert len({a.ctypes.data for a in holders}) == factor  # a copy a successor
    read_back(cluster, meta, oracle, 0)


def test_the_counters_and_the_span_say_a_byte_was_copied_once(tracer):
    cluster = make_cluster()
    meta, oracle = stage(cluster, 0)
    faults.arm(
        "exchange.submit",
        lambda **_: faults.kill_executor(cluster.transport(2)),
        times=1,
        match={"shuffle_id": 0, "round": 1},
    )
    cluster.run_exchange(0)
    [replicate] = [ev for ev in tracer.events if ev["name"] == "exchange.replicate" and ev.get("ph") == "X"]
    read_back(cluster, meta, oracle, 0)
    stats = cluster.elastic_stats
    total = sum(map(len, oracle.values()))
    assert stats["recoveries"] == 1 and stats["restaged_bytes"] == total // 4
    assert stats["replica_copied_bytes"] == stats["replicated_bytes"] == total
    # the CPU backend has no landing pool: np.empty is what ran
    assert cluster._landing() is None
    assert (stats["replica_landing_hits"], stats["replica_landing_misses"]) == (0, 0)
    assert replicate["args"]["copied_bytes"] == total
    assert (replicate["args"]["landing_hits"], replicate["args"]["landing_misses"]) == (0, 0)
    text = cluster.metrics_text()
    for name in ("replica_copied_bytes", "replica_landing_hits", "replica_landing_misses"):
        assert f"sparkucx_tpu_elastic_{name} " in text, name
    block = cluster.transport(3).store.replica_block(0, 2, 2, 0)
    assert block.tobytes() == oracle[(2, 0)] and not block.flags.writeable


@needs_pool
def test_on_a_copying_runtime_the_second_jobs_replicas_are_kept_blocks(monkeypatch):
    """A cluster whose runtime answers as a chip's does has a landing pool,
    and the replica arrays come from it: a miss a body in the first job, a
    hit a body in the second (the same layout: the same body lengths), and
    the blocks come back at ``remove_shuffle``."""

    def copying_runtime(prefix) -> None:
        """``copy_to_host_async`` as a chip's runtime does it (tests/test_recv_landing.py)."""
        landed = np.array(np.asarray(prefix))
        landed.flags.writeable = False
        prefix._npy_value = landed

    monkeypatch.setattr(tpu_mod, "_d2h_copies", lambda device: True)
    monkeypatch.setattr(tpu_mod, "_start_landing", copying_runtime)
    monkeypatch.setattr(tpu_mod, "LANDING_MIN_BYTES", 1024)
    cluster = make_cluster()
    bodies = []
    for job in range(3):
        meta, oracle = stage(cluster, job)
        before = dict(cluster.elastic_stats)
        cluster.run_exchange(job)
        read_back(cluster, meta, oracle, job)
        rise = {k: cluster.elastic_stats[k] - before[k] for k in
                ("replicated_rounds", "replica_landing_hits", "replica_landing_misses", "replica_copied_bytes")}
        bodies.append(rise)
        arr, _, _ = cluster.transport(1).store.replica_view(job, 0, 0)
        assert store_mod._owns_flat_bytes(arr)
        del arr
        cluster.remove_shuffle(job)
        assert all(t.store.replica_stats()["replica_bytes"] == 0 for t in cluster.transports)
    rounds = bodies[0]["replicated_rounds"]
    assert rounds >= 4 and cluster._landing() is not None
    assert (bodies[0]["replica_landing_hits"], bodies[0]["replica_landing_misses"]) == (0, rounds)
    for later in bodies[1:]:
        assert (later["replica_landing_hits"], later["replica_landing_misses"]) == (rounds, 0)
        assert later["replica_copied_bytes"] == bodies[0]["replica_copied_bytes"]
