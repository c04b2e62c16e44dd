"""A loss AFTER the exchange, on the served path: ``TpuShuffleManager`` ->
writer -> store -> ``run_exchange`` -> the executor dies -> readers.

What dies with the executor is gone (its store and the shards it received:
a read addressed to them is ``ExecutorLostError``, in every receive mode);
the engine re-places the tasks of its partitions on live executors
(``get_reader(..., executor_id=e)``) and those pull every block from the
executor that staged it or from its ring successor's replica — the same
records in the same order, without a sleep for a candidate the membership
already calls dead; the tasks of the other partitions borrow as ever; and
nothing of a job is left after its removal."""

import os
import time

import pytest

from benchmark.cells import load_module
from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.operation import BlockNotFoundError, ExecutorLostError, TransportError
from sparkucx_tpu.testing import faults

reference = load_module("references", "groupby-readloss")

#: the upstream gate job at test size on four executors: 8 map tasks of 60
#: records of 25,000 bytes over 200 reducers, 12 MB a job, through 1 MiB of
#: staging an executor (a dozen staging rounds)
LOST, MAPPERS, CHIPS = 2, 8, 4
CONFIG = {"mappers": MAPPERS, "pairs_per_mapper": 60, "value_bytes": 25000, "reducers": 200,
          "keys": "uniform-int31", "conf": {"replication_factor": 1, "elastic": True}}


@pytest.fixture
def tracer():
    """The process-wide tracer, enabled and cleared; back to what it was afterwards."""
    from sparkucx_tpu.utils.trace import TRACER

    enabled, recording = TRACER.enabled, TRACER.recording
    TRACER.enable()
    TRACER.clear()
    yield TRACER
    TRACER.enabled, TRACER.recording = enabled, recording
    TRACER.clear()


def _manager(**conf_kw):
    from sparkucx_tpu.shuffle.manager import TpuShuffleManager

    conf_kw.setdefault("staging_capacity_per_executor", 1 << 20)
    conf_kw.setdefault("elastic", True)
    conf_kw.setdefault("replication_factor", 1)
    return TpuShuffleManager(TpuShuffleConf(**conf_kw), num_executors=CHIPS)


def _placed(mgr, shuffle_id, r, lost):
    """The task's reader where an engine's scheduler would run it: on the
    partition's owner where that lives, on ``survivors[r mod len]`` where not."""
    owner = mgr.cluster.meta(shuffle_id).owner_of_reduce(r)
    if owner not in lost:
        return mgr.get_reader(shuffle_id, r, r + 1)
    survivors = [e for e in range(CHIPS) if e not in lost]
    return mgr.get_reader(shuffle_id, r, r + 1, executor_id=survivors[r % len(survivors)])


def _run_job(mgr, groupbytest, records, shuffle_id, lose=(LOST,), reducers=None):
    """One whole job through the manager: every map task written and
    committed, the exchange whole, then ``lose`` die, then every reduce task
    (or ``reducers``) read in full where the scheduler places it.  Returns
    ({reducer: [(key, value)]}, {reducer: the reader's metrics})."""
    groupbytest.write_and_exchange(mgr, shuffle_id, records)
    for executor in lose:
        faults.kill_executor(mgr.cluster.transport(executor))
    read, metrics = {}, {}
    for r in range(records.reducers) if reducers is None else reducers:
        reader = _placed(mgr, shuffle_id, r, lose)
        read[r] = [(key, bytes(value)) for key, value in reader.read()]
        metrics[r] = reader.metrics
    return read, metrics


def _equals_the_plain_groupby(records, read) -> bool:
    checks = []
    for r, pairs in read.items():
        check = records.check(r, full=True)
        for key, value in pairs:
            check.add(key, value)
        checks.append(check)
    return all(c.ok() for c in checks) and records.complete(checks)


def _lost_partitions(mgr, shuffle_id, records, lost=(LOST,)):
    meta = mgr.cluster.meta(shuffle_id)
    return [r for r in range(records.reducers) if meta.owner_of_reduce(r) in lost]


# -- the job --------------------------------------------------------------------


def test_a_job_that_loses_an_executor_after_its_exchange_equals_the_plain_groupby_and_the_undisturbed_job(groupbytest):
    records = groupbytest.records(MAPPERS, seed=51)
    with _manager() as undisturbed:
        whole, _ = _run_job(undisturbed, groupbytest, records, 0, lose=())
    with _manager() as mgr:
        read, metrics = _run_job(mgr, groupbytest, records, 0)
        cluster = mgr.cluster
        assert cluster.membership.alive() == [0, 1, 3]
        assert cluster.elastic_stats["recoveries"] == 0  # the exchange had returned: nothing ran again
        assert cluster.elastic_stats["lost_recv_shards"] > 0 and cluster.elastic_stats["lost_recv_bytes"] > 0
    assert _equals_the_plain_groupby(records, read)
    assert read == whole  # byte for byte, record order too
    assert sum(len(pairs) for pairs in read.values()) == MAPPERS * 60
    assert sum(m.refetched_blocks for m in metrics.values()) > 0


def test_a_replaced_tasks_counters_are_the_layouts_and_an_undisturbed_task_borrows_every_block(groupbytest):
    """``references/groupby-readloss.py`` says from the layout alone which
    tasks are re-placed, where, what each pulls and how much of it only a
    replica still holds; the readers' counters say the same, task by task."""
    records = groupbytest.records(MAPPERS, seed=52)
    traffic = {"lost_executor": LOST}
    made = reference.read_loss_geometry(CONFIG, traffic, CHIPS)
    with _manager() as mgr:
        groupbytest.write_and_exchange(mgr, 0, records)
        faults.kill_executor(mgr.cluster.transport(LOST))
        mine = _lost_partitions(mgr, 0, records)
        assert [mine[0], mine[-1]] == made["replaced_partitions"] and len(mine) == made["replaced_tasks"] == 50
        totals = dict.fromkeys(("refetched_blocks", "refetched_bytes", "replica_blocks", "replica_bytes"), 0)
        placed_on, wanted = {}, reference.replaced_tasks(CONFIG, traffic, CHIPS)
        for r in range(records.reducers):
            reader = _placed(mgr, 0, r, (LOST,))
            assert len(list(reader.read())) == records.expected[r][0]
            m, want = reader.metrics, wanted[r]
            blocks = len(records.mappers_of(r))
            assert m.remote_blocks_fetched == blocks
            if r not in mine:
                assert want is None and reader.executor_id == mgr.cluster.meta(0).owner_of_reduce(r)
                assert (m.resident_blocks, m.copied_blocks, m.refetched_blocks) == (blocks, 0, 0)
                assert (m.blocks_retried, m.failovers, m.fetch_timeouts) == (0, 0, 0)
                continue
            assert reader.executor_id == want["executor"] != LOST
            placed_on[reader.executor_id] = placed_on.get(reader.executor_id, 0) + 1
            assert (m.refetched_blocks, m.refetched_bytes) == (want["pulled_blocks"], want["pulled_bytes"])
            assert (m.replica_blocks, m.replica_bytes) == (want["replica_blocks"], want["replica_bytes"])
            assert (m.failovers, m.blocks_retried, m.fetch_timeouts) == (want["replica_blocks"], 0, 0)
            assert (m.resident_blocks, m.copied_blocks) == (0, blocks) and want["unserved_blocks"] == 0
            for name in totals:
                totals[name] += getattr(m, name)
        assert totals == {"refetched_blocks": made["pulled_blocks"], "refetched_bytes": made["pulled_bytes"],
                          "replica_blocks": made["replica_blocks"], "replica_bytes": made["replica_bytes"]}
        assert {str(e): n for e, n in placed_on.items()} == made["tasks_placed_on"]
        assert made["replica_holder"] == 3 and made["lost_map_tasks"] == [2, 6] and made["survivors"] == [0, 1, 3]
        # once a task, in the ``read`` family beside the failover counters
        text = mgr.cluster.metrics_text()
        for name, value in (("refetched_blocks", made["pulled_blocks"]), ("refetched_bytes", made["pulled_bytes"]),
                            ("replica_blocks", made["replica_blocks"]), ("failovers", made["replica_blocks"]),
                            ("blocks_retried", 0), ("fetch_timeouts", 0)):
            assert f'sparkucx_tpu_ops_{name}_total{{kind="read"}} {value}' in text, name
        assert "sparkucx_tpu_elastic_lost_recv_bytes" in text


# -- what dies, dies ------------------------------------------------------------


def _mode_conf(mode, tmp_path):
    if mode == "device":
        return dict(host_recv_mode="device", keep_device_recv=True)
    if mode == "memmap":
        return dict(host_recv_mode="memmap", spill_dir=str(tmp_path))
    return dict(host_recv_mode="array")


@pytest.mark.parametrize("mode", ["array", "memmap", "device"])
def test_a_read_of_the_dead_executors_lost_shards_raises_executor_lost_and_yields_nothing(groupbytest, tmp_path, mode):
    """The shards an executor received are its process's memory: after its
    death the cluster holds none of them, in any receive mode, and a reader
    addressed to them — the default placement of its partitions' tasks —
    raises the typed error at its first window, before a byte."""
    records = groupbytest.records(MAPPERS, seed=53)
    with _manager(**_mode_conf(mode, tmp_path)) as mgr:
        groupbytest.write_and_exchange(mgr, 0, records)
        cluster, meta = mgr.cluster, mgr.cluster.meta(0)
        rounds = meta.recv_device if mode == "device" else meta.recv_shards
        held = sum(int(rnd[LOST].nbytes) for rnd in rounds)
        files = [(p, n) for p, n in meta.recv_spill_paths if f"_e{LOST}_" in os.path.basename(p)]
        assert held > 0 and bool(files) == (mode == "memmap")
        spilled = cluster._recv_spill_bytes
        before_kill = mgr.get_reader(0, 100, 101)  # made while its executor lived
        faults.kill_executor(cluster.transport(LOST))
        assert all(rnd[LOST] is None for rnd in rounds) and meta.recv_lost == {LOST}
        assert cluster.elastic_stats["lost_recv_bytes"] >= held
        assert not any(os.path.exists(p) for p, _ in files)  # unlinked, and their disk budget refunded
        assert cluster._recv_spill_bytes == spilled - sum(n for _, n in files)
        mine = _lost_partitions(mgr, 0, records)
        for reader in (before_kill, mgr.get_reader(0, mine[0], mine[0] + 1), mgr.get_reader(0, mine[-1], mine[-1] + 1)):
            assert reader.executor_id == LOST
            yielded = []
            with pytest.raises(ExecutorLostError, match="died with it") as raised:
                for block in reader.fetch_blocks():
                    yielded.append(block)
            assert not yielded and raised.value.executor_id == LOST
            assert reader.metrics.remote_blocks_fetched == reader.metrics.remote_bytes_read == 0
        with pytest.raises(ExecutorLostError, match="died with it"):
            list(mgr.get_reader(0, mine[1], mine[1] + 1).read())
        with pytest.raises(ExecutorLostError):
            cluster.transport(LOST).resident_blocks([reference_block(0, records, mine[0])])
        if mode == "device":
            with pytest.raises(ExecutorLostError):
                mgr.get_reader(0, mine[0], mine[0] + 1).read_device()
        # the survivors' shards are where they were, and a re-placed task reads the same records
        other = next(r for r in range(records.reducers) if r not in mine)
        assert list(mgr.get_reader(0, other, other + 1).read())
        check = records.check(mine[0], full=True)
        for key, value in _placed(mgr, 0, mine[0], (LOST,)).read():
            check.add(key, value)
        assert check.ok()
        mgr.unregister_shuffle(0)
        assert cluster._recv_spill_bytes == 0 and not os.listdir(tmp_path)


def reference_block(shuffle_id, records, r):
    from sparkucx_tpu.core.block import ShuffleBlockId

    return ShuffleBlockId(shuffle_id, records.mappers_of(r)[0], r)


def test_a_reader_cannot_be_placed_on_a_dead_executor(groupbytest):
    records = groupbytest.records(MAPPERS, seed=54)
    with _manager() as mgr:
        groupbytest.write_and_exchange(mgr, 0, records)
        faults.kill_executor(mgr.cluster.transport(LOST))
        with pytest.raises(ExecutorLostError, match="no reader of shuffle 0 can be placed on it") as raised:
            mgr.get_reader(0, 0, 1, executor_id=LOST)
        assert raised.value.executor_id == LOST and raised.value.epoch == mgr.cluster.membership.epoch
        # any live executor will do, for any partition: an undisturbed one is pulled too when placed elsewhere
        reader = mgr.get_reader(0, 0, 1, executor_id=3)
        check = records.check(0, full=True)
        for key, value in reader.read():
            check.add(key, value)
        assert check.ok() and reader.metrics.refetched_blocks == len(records.mappers_of(0))


# -- the guarantee's edge -------------------------------------------------------


def test_the_loss_of_an_executor_and_its_successor_after_the_exchange_is_refused_typed(groupbytest):
    """The blocks executor 2 staged had their one replica on executor 3: with
    both gone a re-placed task that needs one gets ``BlockNotFoundError`` at
    once — no candidate is asked, none is slept on — and never other bytes."""
    records = groupbytest.records(MAPPERS, seed=55)
    with _manager(fetch_backoff_ms=5_000) as mgr:
        groupbytest.write_and_exchange(mgr, 0, records)
        for executor in (2, 3):
            faults.kill_executor(mgr.cluster.transport(executor))
        mine = _lost_partitions(mgr, 0, records, lost=(2, 3))
        assert len(mine) == 100
        needy = [r for r in mine if any(m % CHIPS == 2 for m in records.mappers_of(r))]
        t0 = time.monotonic()
        for r in (needy[0], needy[-1]):
            reader = _placed(mgr, 0, r, (2, 3))
            assert reader.executor_id in (0, 1)
            with pytest.raises(BlockNotFoundError, match="that staged it is lost, and so are its other holders") as raised:
                list(reader.read())
            assert raised.value.map_id % CHIPS == 2 and raised.value.reduce_id == r
        assert time.monotonic() - t0 < 2.0
        # executor 3's own map output has its replicas on executor 0: a task that needs only that is served
        spared = next(r for r in mine if not any(m % CHIPS == 2 for m in records.mappers_of(r))
                      and any(m % CHIPS == 3 for m in records.mappers_of(r)))
        reader = _placed(mgr, 0, spared, (2, 3))
        assert len(list(reader.read())) == records.expected[spared][0] and reader.metrics.replica_blocks > 0
        mgr.unregister_shuffle(0)
        assert mgr.cluster.rejoin_executor(2) and mgr.cluster.rejoin_executor(3)
        read, _ = _run_job(mgr, groupbytest, records, 1, lose=())
        assert _equals_the_plain_groupby(records, read)


def test_with_replication_off_a_replaced_task_is_refused_typed(groupbytest):
    records = groupbytest.records(MAPPERS, seed=56)
    with _manager(replication_factor=0, elastic=False, fetch_backoff_ms=5_000) as mgr:
        groupbytest.write_and_exchange(mgr, 0, records)
        faults.kill_executor(mgr.cluster.transport(LOST))
        mine = _lost_partitions(mgr, 0, records)
        needy = [r for r in mine if any(m % CHIPS == LOST for m in records.mappers_of(r))]
        t0 = time.monotonic()
        for r in needy[:3]:
            with pytest.raises(ExecutorLostError, match="no replica holder") as raised:
                list(_placed(mgr, 0, r, (LOST,)).read())
            assert raised.value.executor_id == LOST
        assert time.monotonic() - t0 < 2.0
        # the other partitions never notice
        read, metrics = _run_job_reads(mgr, records, [r for r in range(records.reducers) if r not in mine])
        assert all(m.copied_blocks == 0 for m in metrics)


def _run_job_reads(mgr, records, reducers):
    read, metrics = {}, []
    for r in reducers:
        reader = mgr.get_reader(0, r, r + 1)
        read[r] = list(reader.read())
        metrics.append(reader.metrics)
    return read, metrics


# -- the pull path's manners ----------------------------------------------------


def test_no_sleep_is_taken_for_a_candidate_the_membership_calls_dead(groupbytest, monkeypatch):
    """With ``fetch_backoff_ms`` at ten seconds the fifty re-placed tasks,
    a quarter of whose blocks have a dead stager before their replica holder
    in the candidates, read in the time of their copies: the dead one is
    neither asked nor slept on."""
    from sparkucx_tpu.shuffle import reader as reader_module
    from sparkucx_tpu.transport.tpu import TpuShuffleTransport

    records = groupbytest.records(MAPPERS, seed=57)
    slept, asked = [], []
    monkeypatch.setattr(reader_module.time, "sleep", lambda s: slept.append(s))
    fetch_block = TpuShuffleTransport.fetch_block

    def spy(self, executor_id, *args, **kw):
        asked.append(executor_id)
        return fetch_block(self, executor_id, *args, **kw)

    monkeypatch.setattr(TpuShuffleTransport, "fetch_block", spy)
    with _manager(fetch_backoff_ms=10_000) as mgr:
        t0 = time.monotonic()
        read, metrics = _run_job(mgr, groupbytest, records, 0)
        assert time.monotonic() - t0 < 10.0
    assert _equals_the_plain_groupby(records, read)
    assert not slept and LOST not in asked
    made = reference.read_loss_geometry(CONFIG, {"lost_executor": LOST}, CHIPS)
    assert len(asked) == made["pulled_blocks"]  # one live fetch a pulled block, nothing else
    assert sum(m.failovers for m in metrics.values()) == made["replica_blocks"] > 0


def test_the_backoff_stays_for_a_peer_that_lives_and_fails(groupbytest, monkeypatch):
    """A live stager whose store refuses a block is asked ``fetch_retries``
    times, slept on between, and only then the replica holder: the block is a
    retried one and a failover, as before."""
    from sparkucx_tpu.shuffle import reader as reader_module
    from sparkucx_tpu.store.hbm_store import HbmBlockStore

    records = groupbytest.records(MAPPERS, seed=58)
    slept = []
    monkeypatch.setattr(reader_module.time, "sleep", lambda s: slept.append(s))
    with _manager(fetch_backoff_ms=40, fetch_retries=2) as mgr:
        groupbytest.write_and_exchange(mgr, 0, records)
        faults.kill_executor(mgr.cluster.transport(LOST))
        r = next(r for r in _lost_partitions(mgr, 0, records)
                 if {m % CHIPS for m in records.mappers_of(r)} >= {0, LOST})
        flaky = next(m for m in records.mappers_of(r) if m % CHIPS == 0)  # staged on live executor 0
        read_block = HbmBlockStore.read_block

        def refuses(self, shuffle_id, map_id, reduce_id):
            if self is mgr.cluster.transport(0).store and (map_id, reduce_id) == (flaky, r):
                raise TransportError("planted: the stager's read fails")
            return read_block(self, shuffle_id, map_id, reduce_id)

        monkeypatch.setattr(HbmBlockStore, "read_block", refuses)
        reader = _placed(mgr, 0, r, (LOST,))
        check = records.check(r, full=True)
        for key, value in reader.read():
            check.add(key, value)
        m = reader.metrics
        assert check.ok() and len(slept) == 2 and all(0.02 <= s <= 0.08 for s in slept)
        lost_blocks = sum(1 for mp in records.mappers_of(r) if mp % CHIPS == LOST)
        assert (m.blocks_retried, m.failovers, m.replica_blocks) == (1, lost_blocks + 1, lost_blocks + 1)
        assert m.refetched_blocks == len(records.mappers_of(r))


def test_a_traced_refetch_records_its_window_and_its_blocks(groupbytest, tracer):
    """``read.refetch`` once a window of a re-placed task, a child of its
    ``read.window``, with ``read.refetch.block`` a pulled block under it and
    ``store.read.replica`` where a replica tier served; an undisturbed task
    records none of them."""
    records = groupbytest.records(MAPPERS, seed=59)
    with _manager() as mgr:
        groupbytest.write_and_exchange(mgr, 0, records)
        faults.kill_executor(mgr.cluster.transport(LOST))
        mine = _lost_partitions(mgr, 0, records)
        other = next(r for r in range(records.reducers) if r not in mine)
        tracer.clear()
        list(mgr.get_reader(0, other, other + 1).read())
        assert not [ev for ev in tracer.events if ev["name"].startswith(("read.refetch", "store.read.replica"))]
        r = next(r for r in mine if any(m % CHIPS == LOST for m in records.mappers_of(r)))
        reader = _placed(mgr, 0, r, (LOST,))
        list(reader.read())
        events = [ev for ev in tracer.events if ev.get("ph") == "X"]
        lost_instants = [ev for ev in tracer.events if ev["name"] == "exchange.recv_lost"]
    named = lambda name: [ev for ev in events if ev["name"] == name]
    [window], [refetch] = named("read.window")[-1:], named("read.refetch")
    blocks, served = named("read.refetch.block"), named("store.read.replica")
    want = reference.replaced_task(CONFIG, {"lost_executor": LOST}, CHIPS, r)
    assert refetch["parent_id"] == window["span_id"] and refetch["eid"] == reader.executor_id
    assert refetch["args"] == {"blocks": want["pulled_blocks"], "bytes": want["pulled_bytes"],
                               "from_replica": want["replica_blocks"]}
    assert len(blocks) == want["pulled_blocks"] and all(b["parent_id"] == refetch["span_id"] for b in blocks)
    assert sum(b["args"]["bytes"] for b in blocks) == want["pulled_bytes"]
    assert [b["args"]["executor"] for b in blocks] == [3 if m % CHIPS == LOST else m % CHIPS for m in records.mappers_of(r)]
    assert [b["args"]["replica"] for b in blocks] == [m % CHIPS == LOST for m in records.mappers_of(r)]
    assert len(served) == want["replica_blocks"]
    assert window["ts"] <= refetch["ts"] and refetch["ts"] + refetch["dur"] <= window["ts"] + window["dur"] + 1
    assert sum(b["dur"] for b in blocks) <= refetch["dur"] + 1
    assert not lost_instants  # cleared with the kill's; the instant is the kill's, not a read's


# -- job after job --------------------------------------------------------------


def test_three_jobs_in_a_row_lose_executor_2_after_the_exchange_and_regain_it(groupbytest):
    """One manager, the benchmark's loop: exchange, lose executor 2, read
    where the scheduler places the tasks, unregister, rejoin.  The third job
    compiles nothing, and nothing of a job is left after its removal: no
    replica body, no received shard, no pulled buffer outside the pool, and
    the survivors' round buffers back on their free lists, level from job to
    job."""
    import gc

    from benchmark.counters import CompileCounter

    records = groupbytest.records(MAPPERS, seed=60)
    compiles = CompileCounter()
    held, pooled = [], []
    with _manager() as mgr:
        cluster = mgr.cluster
        for sid in range(3):
            mark = compiles.snapshot()
            read, metrics = _run_job(mgr, groupbytest, records, sid)
            assert _equals_the_plain_groupby(records, read), sid
            assert cluster.elastic_stats["recoveries"] == 0
            assert cluster.membership.alive() == [0, 1, 3] and cluster.membership.epoch == 2 * sid + 1
            assert sum(m.refetched_blocks for m in metrics.values()) == sum(
                len(records.mappers_of(r)) for r in _lost_partitions(mgr, sid, records))
            assert sum(t.store.replica_stats()["replica_bytes"] for t in cluster.transports) > 0
            meta = cluster.meta(sid)
            mgr.unregister_shuffle(sid)
            assert meta.recv_shards is None and meta.recv_device is None
            stores = [t.store for t in cluster.transports]
            assert all(s.replica_stats()["replica_bytes"] == 0 for s in stores)
            with pytest.raises(Exception, match="unknown shuffle"):
                cluster.meta(sid)
            assert cluster.rejoin_executor(LOST)
            assert cluster.membership.alive() == [0, 1, 2, 3]
            survivors = [t.store.write_stats() for t in cluster.transports if t.executor_id != LOST]
            assert all(s["pool_dropped_busy"] == 0 for s in survivors), survivors
            held.append([s["pool_held_bytes"] for s in survivors])
            assert cluster.transport(LOST).store.write_stats()["pool_held_bytes"] == 0
            gc.collect()
            stats = mgr.pool.stats()
            carved = sum(s["allocated_bytes"] // size for size, s in stats.items())
            pooled.append((carved, carved - sum(s["free"] for s in stats.values())))
            if sid == 2:
                assert compiles.since(mark)["compiles"] == 0
        assert held[0] == held[1] == held[2] and all(h > 0 for h in held[0])
        # every pulled block's buffer is back in the pool, which the second and third job did not grow
        assert pooled[0] == pooled[1] == pooled[2] and pooled[0][0] > 0 and pooled[0][1] == 0
        assert set(cluster.executed_lowerings()["exchange"]) == {"dense"}
        assert cluster.elastic_stats["lost_recv_bytes"] > 0
