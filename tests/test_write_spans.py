"""The map-side write, measured where it happens (PR 50): a committed map
task's span ``write.task`` with its children ``write.task.copy`` /
``write.task.lock_wait`` (summed spans of the writer's own ``copy_ns`` /
``lock_wait_ns``) and ``write.task.commit``; one buffered-path block in
``WRITE_BLOCK_EVERY`` by phase (``write.block`` ⊃ ``.admit`` / ``.copy`` /
``.record``); the span of a round buffer that did not come from the free list
(``store.round_buffer.fresh``); and a task's page faults (``minor_faults``).

Counts, nesting and identities on the CPU mesh; no duration here is a rate."""

import collections
import threading

import jax
import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.shuffle.daemon import DaemonClient, ShuffleDaemon
from sparkucx_tpu.shuffle.manager import TpuShuffleManager
from sparkucx_tpu.store import writer as store_writer
from sparkucx_tpu.store.writer import WRITE_BLOCK_EVERY
from sparkucx_tpu.transport.tpu import TpuShuffleCluster
from sparkucx_tpu.utils.trace import TRACER

ALIGN = 128
TASK_CHILDREN = ("write.task.copy", "write.task.lock_wait", "write.task.commit")
BLOCK_PHASES = ("write.block.admit", "write.block.copy", "write.block.record")
SWITCHES = {"recording": (False, True), "enabled": (True, True)}
#: the store's own answer for this host's kernel (the fixture below overrides it)
KERNEL_COUNTS_FAULTS = store_writer._kernel_counts_faults


@pytest.fixture
def tracer():
    """The process-wide tracer, cleared; back to what it was afterwards."""
    enabled, recording = TRACER.enabled, TRACER.recording
    TRACER.clear()
    store_writer._blocks_traced = 0  # the sampling count is the process's: a test's own
    # whether the argument is there must not hang on this host's kernel
    # (``test_fresh_pages_show_as_minor_faults`` is the one that reads values)
    store_writer._kernel_counts_faults = lambda: True
    yield TRACER
    store_writer._kernel_counts_faults = KERNEL_COUNTS_FAULTS
    TRACER.enabled, TRACER.recording = enabled, recording
    TRACER.clear()


def spans(tracer, *names):
    return [e for e in tracer.events if e["ph"] == "X" and (not names or e["name"] in names)]


def children_of(tracer, parent):
    return sorted((e for e in spans(tracer) if e["parent_id"] == parent["span_id"]), key=lambda e: e["ts"])


def inside(child, parent, slack_us=0.002):  # ts and dur are rounded apart
    return (parent["ts"] - slack_us <= child["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + slack_us)


def manager(tracer, executors=1, staging=1 << 22, **kw):
    conf = TpuShuffleConf(staging_capacity_per_executor=staging, block_alignment=ALIGN,
                          num_executors=executors, **kw)
    return TpuShuffleManager(conf, num_executors=executors)


def write_task(mgr, sid, m, blocks, size, commit=True):
    """One map task through the manager's writer: ``blocks`` streams of ``size``."""
    writer = mgr.get_writer(sid, m)
    for r in range(blocks):
        with writer.get_partition_writer(r).open_stream() as stream:
            stream.write(bytes([(m + r) % 251]) * size)
    if commit:
        writer.commit_all_partitions()
    return writer


# -- write.task -------------------------------------------------------------


@pytest.mark.parametrize("switch", list(SWITCHES))
def test_one_task_span_a_committed_writer(tracer, switch):
    """Under ``recording`` alone the parent only; under ``enabled`` its three
    children, whose seconds stay inside it, ``copy``'s being exactly what the
    task added to the family's ``copy_ns``."""
    mgr = manager(tracer)
    tracer.enabled, tracer.recording = SWITCHES[switch]
    tracer.clear()
    mgr.register_shuffle(0, 3, 20)
    store = mgr.cluster.transports[0].store
    copy_ns, lock_ns = [], []
    for m in range(3):
        before = store.write_stats()
        write_task(mgr, 0, m, blocks=20, size=3000)
        after = store.write_stats()
        copy_ns.append(after["copy_ns"] - before["copy_ns"])
        lock_ns.append(after["lock_wait_ns"] - before["lock_wait_ns"])
    tasks = spans(tracer, "write.task")
    assert [t["args"]["map_id"] for t in tasks] == [0, 1, 2]
    for t in tasks:
        assert t["parent_id"] == 0  # a root: nothing was open while the task wrote
        assert {k: t["args"][k] for k in ("shuffle_id", "executor", "blocks", "bytes")} == {
            "shuffle_id": 0, "executor": 0, "blocks": 20, "bytes": 20 * 3000}
    names = collections.Counter(e["name"] for e in spans(tracer) if e["name"].startswith("write."))
    if switch == "recording":
        assert names == {"write.task": 3}
        assert all("minor_faults" not in t["args"] for t in tasks)
        return
    assert all(names[c] == 3 for c in TASK_CHILDREN)
    for t, copied, waited in zip(tasks, copy_ns, lock_ns):
        kids = {k["name"]: k for k in children_of(tracer, t) if k["name"] in TASK_CHILDREN}
        assert set(kids) == set(TASK_CHILDREN)
        assert all(k["trace_id"] == t["trace_id"] and inside(k, t) for k in kids.values())
        assert sum(k["dur"] for k in kids.values()) <= t["dur"] + 0.01
        copy, wait, commit = (kids[c] for c in TASK_CHILDREN)
        assert copy["dur"] == copied / 1e3 and copy["args"] == {"turns": 20}
        assert wait["dur"] == waited / 1e3 and wait["args"] == {"turns": 20}
        # summed spans are laid end to end from the task's open; the commit is real
        assert copy["ts"] == t["ts"] and abs(wait["ts"] - (copy["ts"] + copy["dur"])) < 0.002
        assert abs(commit["ts"] + commit["dur"] - (t["ts"] + t["dur"])) < 0.002
        # written and committed on one thread: the thread's own page faults
        assert t["args"]["minor_faults"] >= 0


def test_retry_and_abort_record_no_task(tracer):
    mgr = manager(tracer)
    tracer.enable()
    mgr.register_shuffle(0, 2, 8)
    write_task(mgr, 0, 0, blocks=8, size=500)
    retry = write_task(mgr, 0, 0, blocks=8, size=500)  # first commit wins: discarded
    assert retry.map_writer.is_retry_discard and retry.map_writer._blocks is None
    aborted = write_task(mgr, 0, 1, blocks=8, size=500, commit=False)
    aborted.abort()
    tasks = spans(tracer, "write.task")
    assert [t["args"]["map_id"] for t in tasks] == [0]
    # the discarded retry's blocks were not sampled either
    blocks = spans(tracer, "write.block")
    assert blocks and all(b["parent_id"] == tasks[0]["span_id"] for b in blocks)


def test_a_task_is_recorded_once(tracer):
    """A second ``commit`` of the same writer, or ``end_task`` after a commit
    that ended the task, hands nothing over twice."""
    cluster = TpuShuffleCluster(
        TpuShuffleConf(staging_capacity_per_executor=1 << 20, block_alignment=ALIGN, num_executors=1),
        num_executors=1)
    tracer.enable()
    cluster.create_shuffle(0, 1, 4)
    w = cluster.transports[0].store.map_writer(0, 0)
    for r in range(4):
        w.write_partition(r, b"x" * 100)
    w.commit()
    w.end_task()
    w.commit()
    assert len(spans(tracer, "write.task")) == 1


def test_untraced_takes_no_marks(tracer):
    """Both switches off: no clock at creation, no marks list, no event.  Under
    ``recording`` alone: the task's open and nothing a block."""
    mgr = manager(tracer)
    mgr.register_shuffle(0, 2, 8)
    tracer.enabled = tracer.recording = False
    off = write_task(mgr, 0, 0, blocks=8, size=500, commit=False).map_writer
    assert off._t_open == 0 and off._blocks is None and off._block is None and off._faults is None
    off.commit()
    assert not spans(tracer)
    tracer.recording = True
    rec = write_task(mgr, 0, 1, blocks=8, size=500, commit=False).map_writer
    assert rec._t_open > 0 and rec._blocks is None and rec._block is None and rec._faults is None
    rec.commit()
    assert [e["name"] for e in spans(tracer)] == ["write.task"]


def test_minor_faults_absent_across_threads(tracer):
    """The count is a thread's: a commit on another thread than the writer's
    creation leaves it out (a daemon's serving pool)."""
    mgr = manager(tracer)
    tracer.enable()
    mgr.register_shuffle(0, 2, 4)
    same = write_task(mgr, 0, 0, blocks=4, size=500, commit=False)
    same.commit_all_partitions()
    other = write_task(mgr, 0, 1, blocks=4, size=500, commit=False)
    t = threading.Thread(target=other.commit_all_partitions)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive()
    by_map = {t["args"]["map_id"]: t["args"] for t in spans(tracer, "write.task")}
    assert "minor_faults" in by_map[0] and "minor_faults" not in by_map[1]


def test_minor_faults_left_out_where_the_kernel_keeps_no_count(tracer, monkeypatch):
    """A sandboxed kernel that answers 0 for the whole process (the chip's
    host): no system call a task, no argument — never a misleading 0."""
    import resource

    mgr = manager(tracer)
    tracer.enable()
    mgr.register_shuffle(0, 1, 4)
    calls = []
    real = resource.getrusage
    monkeypatch.setattr(store_writer.resource, "getrusage", lambda who: calls.append(who) or real(who))
    monkeypatch.setattr(store_writer, "_kernel_counts_faults", lambda: False)
    writer = write_task(mgr, 0, 0, blocks=4, size=500, commit=False)
    assert writer.map_writer._faults is None and writer.map_writer._blocks is not None
    writer.commit_all_partitions()
    [task] = spans(tracer, "write.task")
    assert "minor_faults" not in task["args"] and not calls
    assert {k["name"] for k in children_of(tracer, task)} >= set(TASK_CHILDREN)


def test_the_kernels_count_is_asked_once_a_process(monkeypatch):
    import resource

    asked = []
    real = resource.getrusage
    monkeypatch.setattr(store_writer.resource, "getrusage", lambda who: asked.append(who) or real(who))
    fresh = KERNEL_COUNTS_FAULTS.__wrapped__  # the function under the cache
    assert fresh() == (real(resource.RUSAGE_SELF).ru_minflt > 0)
    assert asked == [resource.RUSAGE_SELF]
    KERNEL_COUNTS_FAULTS(), KERNEL_COUNTS_FAULTS(), KERNEL_COUNTS_FAULTS()
    assert len(asked) <= 2  # the cached answer asks at most once more, ever


def test_fresh_pages_show_as_minor_faults(tracer):
    """A task that first-touches a fresh staging round faults at least once a
    page of what it wrote; one that writes into pages the process holds (the
    free list's buffer, touched by the job before) a small fraction of that."""
    if not KERNEL_COUNTS_FAULTS():
        pytest.skip("this kernel keeps no count of minor faults")
    # over glibc's 32 MiB cap on its mmap threshold: ``np.zeros`` then maps fresh
    # pages whatever this process freed before (a 16 MiB round came out of the
    # heap, resident, once a test before it in the worker had freed as much)
    staging = 1 << 26
    mgr = manager(tracer, staging=staging)
    tracer.enable()
    page = 4096
    for sid in (0, 1):
        mgr.register_shuffle(sid, 1, 4)
        write_task(mgr, sid, 0, blocks=4, size=staging // 8)
        mgr.run_exchange(sid)
        mgr.unregister_shuffle(sid)
    first, second = (t["args"] for t in spans(tracer, "write.task"))
    stats = mgr.cluster.transports[0].store.write_stats()
    assert (stats["pool_misses"], stats["pool_hits"]) == (1, 1)
    assert first["minor_faults"] >= first["bytes"] // page
    assert second["minor_faults"] < first["minor_faults"] // 4


# -- write.block --------------------------------------------------------------


def test_block_phases_tile_close_partition_and_reducers_rotate(tracer):
    """One block in ``WRITE_BLOCK_EVERY`` of the process, counted since tracing
    came on; its three children partition ``close_partition``; the sampled
    reduce ids differ from task to task."""
    blocks, tasks = 63, 6  # the 1k job's blocks a task
    mgr = manager(tracer)
    mgr.register_shuffle(0, tasks, blocks)
    write_task(mgr, 0, 0, blocks=5, size=100)  # untraced: the count starts with tracing
    tracer.enable()
    tracer.clear()
    for m in range(1, tasks):
        write_task(mgr, 0, m, blocks=blocks, size=700)
    sampled = spans(tracer, "write.block")
    written = (tasks - 1) * blocks
    assert len(sampled) == -(-written // WRITE_BLOCK_EVERY)
    by_task = {t["span_id"]: t for t in spans(tracer, "write.task")}
    picked = collections.defaultdict(list)
    for b in sampled:
        task = by_task[b["parent_id"]]
        assert inside(b, task) and b["trace_id"] == task["trace_id"] and b["args"]["bytes"] == 700
        picked[task["args"]["map_id"]].append(b["args"]["reduce_id"])
        kids = children_of(tracer, b)
        assert tuple(k["name"] for k in kids) == BLOCK_PHASES
        # no gap, no overlap, and they end where the block ends
        for a, c in zip(kids, kids[1:]):
            assert abs(a["ts"] + a["dur"] - c["ts"]) < 0.002
        assert abs(kids[-1]["ts"] + kids[-1]["dur"] - (b["ts"] + b["dur"])) < 0.002
        assert kids[0]["ts"] >= b["ts"]  # the stream's writes come first
    numbers = [(m - 1) * blocks + r for m, rs in sorted(picked.items()) for r in rs]
    assert numbers == list(range(0, written, WRITE_BLOCK_EVERY))
    assert len({tuple(rs) for rs in picked.values()}) == len(picked) > 1  # they rotate
    assert all(blocks_a_task % WRITE_BLOCK_EVERY for blocks_a_task in (200, 100, 75, 63))


def test_block_copy_is_inside_the_counted_copy(tracer):
    """A sampled block's ``copy`` is one of the intervals ``copy_ns`` sums, its
    ``admit`` holds the rollover where the region was full."""
    mgr = manager(tracer, staging=1 << 20)
    tracer.enable()
    mgr.register_shuffle(0, 1, 2 * WRITE_BLOCK_EVERY)
    # the second sampled block does not fit what those before it left of the
    # 1 MiB round: it rolls it
    size = (1 << 20) // WRITE_BLOCK_EVERY // ALIGN * ALIGN
    assert WRITE_BLOCK_EVERY * size <= 1 << 20 < (WRITE_BLOCK_EVERY + 1) * size
    write_task(mgr, 0, 0, blocks=2 * WRITE_BLOCK_EVERY, size=size)
    [task] = spans(tracer, "write.task")
    first, second = spans(tracer, "write.block")
    assert (first["args"]["reduce_id"], second["args"]["reduce_id"]) == (0, WRITE_BLOCK_EVERY)
    [copy] = [k for k in children_of(tracer, task) if k["name"] == "write.task.copy"]
    assert sum(k["dur"] for b in (first, second) for k in children_of(tracer, b)
               if k["name"] == "write.block.copy") < copy["dur"]
    [rollover] = spans(tracer, "store.rollover")
    admit = children_of(tracer, second)[0]
    assert admit["name"] == "write.block.admit" and inside(rollover, admit)


# -- store.round_buffer.fresh -------------------------------------------------


def test_fresh_round_buffer_fires_once_a_miss_and_never_on_a_hit(tracer):
    """Three jobs of one shape on one store: the first allocates its round
    buffers, the later ones take them back from the free list."""
    mgr = manager(tracer, staging=1 << 20)
    store = mgr.cluster.transports[0].store
    assert tracer.recording and not tracer.enabled  # the flight recorder's too
    tracer.clear()
    for sid in range(3):
        before = store.write_stats()
        mgr.register_shuffle(sid, 2, 4)
        for m in range(2):
            write_task(mgr, sid, m, blocks=4, size=200_000)  # 1.6 MB through 1 MiB rounds
        mgr.run_exchange(sid)
        mgr.unregister_shuffle(sid)
        after = store.write_stats()
        fresh = spans(tracer, "store.round_buffer.fresh")
        tracer.clear()
        misses = after["pool_misses"] - before["pool_misses"]
        assert len(fresh) == misses
        assert all(e["args"] == {"executor": 0, "bytes": 1 << 20} for e in fresh)
        if sid == 0:
            assert misses >= 2  # its first staging and one a RAM rollover
        else:
            assert misses == 0 and after["pool_hits"] - before["pool_hits"] >= 2


# -- the other two write paths ------------------------------------------------


def test_device_write_records_a_task(tracer):
    """The device write: ``store.device_stage`` falls inside ``write.task`` by
    time; nothing was copied on the host, so no ``copy`` child."""
    conf = TpuShuffleConf(keep_device_recv=True, host_recv_mode="device", block_alignment=ALIGN,
                          staging_capacity_per_executor=1 << 20, num_executors=1, device_staging=True)
    mgr = TpuShuffleManager(conf, num_executors=1)
    tracer.enable()
    mgr.register_shuffle(0, 1, 4)
    writer = mgr.get_writer(0, 0)
    lane = ALIGN // 4
    packed = jax.device_put(np.arange(8 * lane, dtype=np.int32).reshape(8, lane),
                            mgr.cluster.transports[0].device)
    writer.write_partitions_device(packed, [0, 1, 2, 3], [2 * ALIGN] * 4)
    writer.commit_all_partitions()
    [task] = spans(tracer, "write.task")
    assert task["args"]["blocks"] == 4 and task["args"]["bytes"] == 8 * ALIGN
    [stage] = spans(tracer, "store.device_stage")
    assert inside(stage, task)
    kids = [k["name"] for k in children_of(tracer, task)]
    assert kids == ["write.task.commit"]
    assert not spans(tracer, "write.block")


def test_daemon_receive_in_place_records_a_task_and_no_block(tracer):
    """Over the socket a body lands in its extent: ``write.task`` with
    ``lock_wait`` (a take a ``reserve`` and one a close) and ``commit``, no
    ``copy``, no ``write.block``; the task ends inside its ``commit_map`` frame."""
    conf = TpuShuffleConf(staging_capacity_per_executor=1 << 20, block_alignment=ALIGN, num_executors=1)
    served = ShuffleDaemon(conf, num_executors=1, port=0)
    client = DaemonClient(served.address)
    try:
        tracer.enable()
        client.create_shuffle(0, 2, 6)
        for m in range(2):
            writer = client.open_map_writer(0, m)
            for r in range(6):
                client.write_partition(writer, r, bytes([m + r]) * 700)
            client.commit_map(writer)
        tracer.disable()
    finally:
        client.close()
        served.close()
    tasks = spans(tracer, "write.task")
    assert [t["args"]["map_id"] for t in tasks] == [0, 1]
    stats = served.manager.cluster.transports[0].store.write_stats()
    assert stats["inplace_blocks"] == 12 and stats["inplace_fallbacks"] == 0
    frames = spans(tracer, "daemon.commit_map")
    for task, frame in zip(tasks, frames):
        assert task["args"]["blocks"] == 6 and task["args"]["bytes"] == 6 * 700
        kids = {k["name"]: k for k in children_of(tracer, task)}
        assert set(kids) == {"write.task.lock_wait", "write.task.commit"}
        assert kids["write.task.lock_wait"]["args"] == {"turns": 12}
        assert inside(kids["write.task.commit"], frame)
    assert not spans(tracer, "write.block", "write.task.copy")
