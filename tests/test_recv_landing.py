"""Where a received shard lands (PR 43).

On a chip the exchange's D2H lands each received prefix in a NumPy array the
runtime allocates for that one ``jax.Array`` — at 64 MiB a fresh mapping a
shard.  The submit lane makes those allocations from a ``LandingPool`` (a
NumPy data allocator that keeps the large blocks its arrays release), so the
next job's shards land in pages the process already holds.  Whether, a cluster
decides once from what the runtime says (``_d2h_copies``): on the CPU backend
an array already lies in host memory, nothing is allocated and nothing kept.
Here the predicate is patched to a chip's answer and ``_start_landing`` to
what a chip's runtime does at ``copy_to_host_async`` — allocate the
destination from the calling thread's NumPy allocator and copy into it — so
the whole path runs on the CPU mesh and must be the same bytes.  Bytes and
counts; no rate.
"""

import gc
import threading

import numpy as np
import pytest

import sparkucx_tpu.native as native
import sparkucx_tpu.transport.tpu as tpu_mod
from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.block import ShuffleBlockId
from sparkucx_tpu.native import LandingPool
from sparkucx_tpu.transport.tpu import TpuShuffleCluster

try:
    from numpy._core.multiarray import get_handler_name
except ImportError:  # NumPy 1.x
    from numpy.core.multiarray import get_handler_name

ROW = 128
STAGING = 1 << 16  # 512 rows an executor
LANDINGS = ("as-before", "pooled")
MIB = 1 << 20

#: evaluated when a test is set up, not when the file is imported: the first
#: use may build the native library
needs_pool = pytest.mark.skipif(
    "LandingPool.create(MIB) is None", reason="no landing pool here (no compiler, or no NumPy allocator hook)"
)


def copying_runtime(prefix) -> None:
    """``copy_to_host_async`` as a chip's runtime does it: the NumPy array the
    bytes land in is allocated now, by the calling thread, and filled."""
    landed = np.array(np.asarray(prefix))
    landed.flags.writeable = False
    prefix._npy_value = landed


def make_cluster(monkeypatch, landing: str, n: int = 1, **conf) -> TpuShuffleCluster:
    """A cluster on the CPU backend as it is (``as-before``), or one whose
    runtime answers and allocates as a chip's does (``pooled``)."""
    if landing == "pooled":
        monkeypatch.setattr(tpu_mod, "_d2h_copies", lambda device: True)
        monkeypatch.setattr(tpu_mod, "_start_landing", copying_runtime)
        monkeypatch.setattr(tpu_mod, "LANDING_MIN_BYTES", 1024)
    conf = TpuShuffleConf(
        staging_capacity_per_executor=STAGING, block_alignment=ROW, num_executors=n, **conf
    )
    return TpuShuffleCluster(conf, num_executors=n)


def run_job(cluster, shuffle_id: int = 0, seed: int = 7):
    """Eight map tasks an executor, two blocks a consumer each, ragged and
    long enough that every executor's staging rolls over: several rounds."""
    n = cluster.num_executors
    rng = np.random.default_rng(seed)
    meta = cluster.create_shuffle(shuffle_id, 8 * n, 2 * n)
    oracle = {}
    for m in range(8 * n):
        t = cluster.transport(meta.map_owner[m])
        w = t.store.map_writer(shuffle_id, m)
        for r in range(2 * n):
            length = int(rng.integers(1, STAGING // n // 6))
            oracle[(m, r)] = rng.integers(0, 256, size=length, dtype=np.uint8).tobytes()
            w.write_partition(r, oracle[(m, r)])
        t.commit_block(w.commit().pack())
    cluster.run_exchange(shuffle_id)
    return meta, oracle


def d2h(cluster) -> dict:
    return cluster.stats.counters("exchange.d2h")


def read_back(cluster, meta, oracle, shuffle_id: int = 0):
    for (m, r), staged in oracle.items():
        view, length = cluster.locate_received_block(meta.owner_of_reduce(r), shuffle_id, m, r)
        assert length == len(staged) and view.tobytes() == staged, (m, r)


# -- the pool ---------------------------------------------------------------------


@needs_pool
def test_a_released_block_is_the_next_array_of_its_size():
    pool = LandingPool.create(8 * MIB, MIB)
    with pool.allocating():
        assert get_handler_name() == LandingPool.NAME
        a = np.empty(2 * MIB, np.uint8)
    assert get_handler_name() != LandingPool.NAME  # the thread's allocator is back
    a[:] = 7
    addr = a.ctypes.data
    view = a[100:200]
    del a
    assert pool.stats()["kept_blocks"] == 0  # a view holds the block
    del view
    assert pool.stats() == dict(
        hits=0, misses=1, kept_blocks=1, dropped_blocks=0, held_bytes=2 * MIB, budget_bytes=8 * MIB
    )
    with pool.allocating():
        other = np.empty(3 * MIB, np.uint8)  # another size: not this block
        b = np.empty(2 * MIB, np.uint8)
    assert b.ctypes.data == addr and other.ctypes.data != addr
    assert int(b[5]) == 7  # the pages as they were left: nobody touched them
    assert pool.stats()["hits"] == 1 and pool.stats()["held_bytes"] == 0


@needs_pool
def test_zeros_from_a_kept_block_are_zeros():
    pool = LandingPool.create(8 * MIB, MIB)
    with pool.allocating():
        a = np.empty(MIB, np.uint8)
    a[:] = 255
    del a
    with pool.allocating():
        z = np.zeros(MIB, np.uint8)
    assert pool.stats()["hits"] == 1 and not z.any()


@needs_pool
def test_the_budget_bounds_what_is_kept_and_small_blocks_are_not():
    pool = LandingPool.create(2 * MIB, MIB)
    with pool.allocating():
        big = [np.empty(MIB, np.uint8) for _ in range(3)]
        small = np.empty(1000, np.uint8)
        huge = np.empty(3 * MIB, np.uint8)  # over the whole budget: never kept
    del big, small, huge
    s = pool.stats()
    # the third block back took the place of the first
    assert (s["kept_blocks"], s["dropped_blocks"], s["held_bytes"]) == (3, 2, 2 * MIB)
    assert s["misses"] == 4  # the small one is neither a hit nor a miss


@needs_pool
def test_sizes_nobody_asks_for_make_room_for_the_sizes_in_use():
    pool = LandingPool.create(4 * MIB, MIB)
    with pool.allocating():
        stale = [np.empty(2 * MIB, np.uint8) for _ in range(2)]
    del stale  # the budget is full of 2 MiB blocks
    for job in range(3):  # jobs of another shape
        with pool.allocating():
            shards = [np.empty(MIB, np.uint8) for _ in range(3)]
        del shards
    s = pool.stats()
    assert s["hits"] == 6  # the second and third job landed in the first's blocks
    assert s["held_bytes"] == 3 * MIB and s["dropped_blocks"] == 2  # both stale blocks went


@needs_pool
def test_another_thread_keeps_its_own_allocator():
    pool = LandingPool.create(8 * MIB, MIB)
    seen = []
    with pool.allocating():
        t = threading.Thread(target=lambda: seen.append(get_handler_name()))
        t.start()
        t.join(10)
    assert seen and seen[0] != LandingPool.NAME


@needs_pool
def test_an_array_outlives_its_pool():
    pool = LandingPool.create(8 * MIB, MIB)
    with pool.allocating():
        a = np.empty(MIB, np.uint8)
        b = np.empty(MIB, np.uint8)
    a[:] = 3
    del b  # kept
    del pool
    gc.collect()  # retired: the kept block is freed, a's comes back to nobody
    assert int(a.sum()) == 3 * MIB
    del a


def test_no_pool_without_a_budget_or_without_the_library(monkeypatch):
    assert LandingPool.create(0) is None
    monkeypatch.setattr(native, "_load", lambda: None)
    assert LandingPool.create(MIB) is None


# -- the cluster's rule -------------------------------------------------------------


def test_the_cpu_backend_keeps_no_landing_pool():
    cluster = TpuShuffleCluster(TpuShuffleConf(num_executors=2), num_executors=2)
    assert cluster._landing() is None  # np.asarray of an array is a view here


@needs_pool
def test_a_copying_runtime_gets_a_pool_under_the_stores_budget(monkeypatch):
    cluster = make_cluster(monkeypatch, "pooled", n=2, max_host_pool_bytes=3 * MIB)
    pool = cluster._landing()
    assert pool is cluster._landing()  # decided once
    assert pool.stats()["budget_bytes"] == 2 * 3 * MIB  # an executor's figure each


def test_a_copying_runtime_without_the_library_lands_as_before(monkeypatch):
    monkeypatch.setattr(native, "_load", lambda: None)
    cluster = make_cluster(monkeypatch, "pooled")
    assert cluster._landing() is None
    meta, oracle = run_job(cluster)
    read_back(cluster, meta, oracle)
    counters = d2h(cluster)
    assert counters["kept_shards"] == 0 and counters["fresh_shards"] == len(meta.recv_shards)
    cluster.remove_shuffle(0)


# -- a whole exchange under the two landings ------------------------------------------


@needs_pool
@pytest.mark.parametrize("mode", ["array", "memmap"])
@pytest.mark.parametrize("n", [1, 4])
@pytest.mark.parametrize("chunked", [False, True], ids=["single-shot", "chunked"])
@pytest.mark.parametrize("depth", [1, 2])
def test_the_received_shards_are_the_same_bytes_under_both_landings(
    monkeypatch, tmp_path, depth, chunked, n, mode
):
    received = {}
    for landing in LANDINGS:
        with monkeypatch.context() as patch:
            cluster = make_cluster(
                patch, landing, n=n, pipeline_depth=depth, host_recv_mode=mode,
                spill_dir=str(tmp_path / landing),
                slot_quota_rows=STAGING // ROW // n // 4 if chunked else 0,
            )
            for shuffle_id in (0, 1):  # the second job lands where the first did
                meta, oracle = run_job(cluster, shuffle_id)
                assert len(meta.recv_shards) >= 2  # several rounds: the pipeline has something to order
                read_back(cluster, meta, oracle, shuffle_id)
                for rnd in meta.recv_shards:
                    for shard in rnd:
                        assert shard.dtype == np.uint8 and shard.ndim == 1
                        assert mode == "memmap" or chunked or not shard.size or not shard.flags.writeable
                received[landing, shuffle_id] = (
                    [[np.array(shard) for shard in rnd] for rnd in meta.recv_shards],
                    [np.array(s) for s in meta.recv_sizes],
                )
                cluster.remove_shuffle(shuffle_id)
                del meta
                gc.collect()
            counters = d2h(cluster)
            if landing == "as-before":
                assert counters["kept_shards"] == 0
            else:
                assert counters["kept_shards"] > 0  # blocks of the first job took the second's shards
    for shuffle_id in (0, 1):
        (a_shards, a_sizes), (b_shards, b_sizes) = (received[landing, shuffle_id] for landing in LANDINGS)
        assert len(a_shards) == len(b_shards)
        for a_rnd, b_rnd in zip(a_shards, b_shards):
            for a, b in zip(a_rnd, b_rnd):
                np.testing.assert_array_equal(a, b)
        for a, b in zip(a_sizes, b_sizes):
            np.testing.assert_array_equal(a, b)


@needs_pool
@pytest.mark.parametrize("n", [1, 4])
@pytest.mark.parametrize("landing", LANDINGS)
def test_every_shard_of_a_sub_round_is_counted_once(monkeypatch, landing, n):
    cluster = make_cluster(monkeypatch, landing, n=n)
    calls = []
    real = cluster.stats.record_counters

    def record(kind, **counters):
        if kind == "exchange.d2h":
            calls.append(counters)
        real(kind, **counters)

    monkeypatch.setattr(cluster.stats, "record_counters", record)
    for shuffle_id in (0, 1):
        meta, _ = run_job(cluster, shuffle_id)
        rounds = len(meta.recv_shards)
        cluster.remove_shuffle(shuffle_id)
        del meta
        gc.collect()
    assert len(calls) == 2 * rounds  # once a sub-round (single-shot: a round)
    for c in calls:
        assert c["kept_shards"] + c["fresh_shards"] + c["skipped_shards"] == n
    first, second = calls[:rounds], calls[rounds:]
    assert sum(c["kept_shards"] for c in first) <= sum(c["kept_shards"] for c in second)
    if landing == "pooled":
        # every shard of the second job lands in a block the first gave back
        assert all(c["fresh_shards"] == 0 for c in second)
    else:
        assert not any(c["kept_shards"] for c in calls)


@needs_pool
@pytest.mark.parametrize("landing", LANDINGS)
def test_an_empty_shard_brings_nothing_back(monkeypatch, landing):
    cluster = make_cluster(monkeypatch, landing, n=2)
    meta = cluster.create_shuffle(0, 2, 2)  # a job whose second consumer receives nothing
    for m in range(2):
        t = cluster.transport(meta.map_owner[m])
        w = t.store.map_writer(0, m)
        w.write_partition(0, bytes([m + 1]) * 1000)
        t.commit_block(w.commit().pack())
    cluster.run_exchange(0)
    full, empty = meta.recv_shards[0]
    assert empty.dtype == np.uint8 and empty.shape == (0,)
    assert full.size and not full.flags.writeable
    for m in range(2):
        view, length = cluster.locate_received_block(0, 0, m, 0)
        assert length == 1000 and view.tobytes() == bytes([m + 1]) * 1000
    counters = d2h(cluster)
    assert (counters["skipped_shards"], counters["kept_shards"] + counters["fresh_shards"]) == (1, 1)
    cluster.remove_shuffle(0)


# -- a view keeps its landing alive --------------------------------------------------


@needs_pool
@pytest.mark.parametrize("landing", LANDINGS)
def test_resident_blocks_outlive_their_shuffle(monkeypatch, landing):
    cluster = make_cluster(monkeypatch, landing)
    meta, oracle = run_job(cluster)
    bids = [ShuffleBlockId(0, m, r) for (m, r) in oracle]
    views = cluster.resident_blocks(0, bids)
    assert not any(v.flags.writeable for v in views)
    cluster.remove_shuffle(0)
    del meta
    gc.collect()
    # the shards are held by the views: no block came back, and the next job
    # lands elsewhere
    run_job(cluster, shuffle_id=1, seed=8)
    for v, bid in zip(views, bids):
        assert v.tobytes() == oracle[(bid.map_id, bid.reduce_id)], bid
    if landing == "pooled":
        assert d2h(cluster)["kept_shards"] == 0
        held = cluster._landing().stats()["held_bytes"]
        del views, v
        gc.collect()
        assert cluster._landing().stats()["held_bytes"] > held  # the last view gave them back
    cluster.remove_shuffle(1)


@needs_pool
def test_past_the_budget_a_shard_lands_in_fresh_pages(monkeypatch):
    """The pool keeps what the store's rule allows an executor; the rest of
    a job's shards land as before, every job."""
    cluster = make_cluster(monkeypatch, "pooled", max_host_pool_bytes=STAGING)  # one shard's worth
    for shuffle_id in (0, 1, 2):
        meta, oracle = run_job(cluster, shuffle_id)
        read_back(cluster, meta, oracle, shuffle_id)
        rounds = len(meta.recv_shards)
        cluster.remove_shuffle(shuffle_id)
        del meta
        gc.collect()
    counters, stats = d2h(cluster), cluster._landing().stats()
    assert counters["kept_shards"] + counters["fresh_shards"] == 3 * rounds
    assert 0 < counters["kept_shards"] < 2 * rounds
    assert stats["dropped_blocks"] > 0 and stats["held_bytes"] <= STAGING


# -- a recovery under the pool (PR 45) ------------------------------------------------


@needs_pool
@pytest.mark.parametrize("landing", LANDINGS)
def test_a_recovery_allocates_from_the_pool_and_recovers_the_same_bytes(monkeypatch, landing):
    """An executor lost mid-exchange, twice on one cluster: the recovery's
    host arrays (restaged rounds, each sub-exchange's send array, the
    landings, the recovered shards) come from the landing pool where there is
    one — the second recovery lands in the blocks the first gave back — and
    the recovered bytes are the staged bytes under either landing."""
    from sparkucx_tpu.testing import faults

    cluster = make_cluster(monkeypatch, landing, n=4, elastic=True, replication_factor=1)
    pool = cluster._landing()
    assert (pool is not None) == (landing == "pooled")
    hits = []
    try:
        for sid in range(2):
            faults.arm("exchange.submit", lambda **_: faults.kill_executor(cluster.transport(2)),
                       times=1, match={"shuffle_id": sid, "round": 1})
            meta, oracle = run_job(cluster, sid, seed=7 + sid)
            assert cluster.elastic_stats["recoveries"] == sid + 1
            read_back(cluster, meta, oracle, sid)
            cluster.remove_shuffle(sid)
            del meta
            gc.collect()
            assert cluster.rejoin_executor(2)
            if pool is not None:
                hits.append(pool.stats()["hits"])
    finally:
        faults.reset()
    if pool is not None:
        first, second = hits[0], hits[1] - hits[0]
        assert second > first and pool.stats()["held_bytes"] > 0
