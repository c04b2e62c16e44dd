"""A buffered block takes its extent under the store's lock and is copied
OUTSIDE it (PR 53): ``MapWriter.close_partition`` is the second client of the
reservation that a partition fed from a socket has used since PR 35
(``_take_extent`` → the bytes → ``_record``).  With a copy held open by an
event: other writers take the lock, close and commit meanwhile; no piece with
a byte of the held extent goes to the device before the copy has ended, in
every order in which four writers' copies can end; a RAM-arm rollover
interleaves and the block is recorded in the round its extent was taken in;
the disk arm, ``seal``, ``remove_shuffle`` and ``close`` wait for the copy; a
copy that raises leaves a hole; the only writer open keeps the one-take atom.

The CPU mesh with ``SEAL_PUT_PIECE_BYTES`` patched small: counts, orders and
bytes, no rate."""

import itertools
import queue
import threading
import time

import jax
import numpy as np
import pytest

import sparkucx_tpu.store.hbm_store as hbm_store
import sparkucx_tpu.store.writer as store_writer
from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.operation import TransportError
from sparkucx_tpu.service.tenants import TenantRegistry
from sparkucx_tpu.store.hbm_store import HbmBlockStore, default_peer_ranges
from sparkucx_tpu.utils.trace import TRACER

ALIGN = 128
PIECE = 1 << 13  # 64 rows
REGION = 16 * PIECE


@pytest.fixture(autouse=True)
def small_sizes(monkeypatch):
    monkeypatch.setattr(hbm_store, "SEAL_PUT_PIECE_BYTES", PIECE)


class HeldCopies:
    """``store_writer._copy_chunks`` with a gate a thread: a copy on a gated
    thread says where its extent starts, waits to be let through, then
    copies (or raises what it was told to).  Other threads copy as ever."""

    def __init__(self, monkeypatch):
        self.real = store_writer._copy_chunks
        self.arrived = queue.Queue()
        self.gates = {}
        self.raises = {}
        monkeypatch.setattr(store_writer, "_copy_chunks", self)

    def __call__(self, staging, start, chunks):
        name = threading.current_thread().name
        gate = self.gates.get(name)
        if gate is not None:
            self.arrived.put((name, start))
            assert gate.wait(30), f"{name} was never let through"
        if name in self.raises:
            self.real(staging, start, [chunks[0][: len(chunks[0]) // 2]])  # half of it is there
            raise self.raises[name]
        self.real(staging, start, chunks)

    def hold(self, name):
        self.gates[name] = threading.Event()

    def release(self, name):
        self.gates[name].set()


class Task(threading.Thread):
    """A map task on its own thread: its blocks, then its commit."""

    def __init__(self, name, store, sid, map_id, blocks, commit=True):
        super().__init__(name=name, daemon=True)
        self.store, self.sid, self.map_id, self.blocks, self.commits = store, sid, map_id, blocks, commit
        self.error = None
        self.writer = None
        self.start()

    def run(self):
        try:
            self.writer = self.store.map_writer(self.sid, self.map_id)
            for reduce_id, data in self.blocks:
                self.writer.write_partition(reduce_id, data)
            if self.commits:
                self.writer.commit()
        except BaseException as e:  # the thread's boundary: the test reads it
            self.error = e

    def done(self, timeout=30):
        self.join(timeout)
        assert not self.is_alive(), f"{self.name} still runs"
        return self


def store_of(capacity, device=True, regions=1, tenants=None, **conf):
    """A store of a long-lived executor: the job before left its staging
    buffer on the free list (a first job, into fresh pages, is never put
    behind its writers)."""
    store = HbmBlockStore(
        TpuShuffleConf(block_alignment=ALIGN, staging_capacity_per_executor=capacity, **conf),
        device=jax.devices()[0] if device else None,
    )
    store.tenants = tenants
    store.create_shuffle(99, 1, regions, peer_ranges=default_peer_ranges(regions, regions))
    writer = store.map_writer(99, 0)
    writer.write_partition(0, b"the job before")
    writer.commit()
    store.remove_shuffle(99)
    return store


def another_slot_writes(store, sid=0):
    """An executor's other task slot has a map task open throughout (it
    never commits): with more than one writer open, copies leave the lock."""
    store._state(sid).open_writers += 1


def data_of(rng, nbytes):
    return rng.integers(1, 256, size=nbytes, dtype=np.uint8).tobytes()


def stats(store, *keys):
    row = store.write_stats()
    return tuple(row[k] for k in keys)


def on_device(store, state):
    """The pieces claimed so far as ``(row, bytes on the device, bytes in
    staging)`` — nobody owns the chain when this is called."""
    behind = state.put_behind
    assert behind is not None and not behind.owner
    if behind.buf is None:
        return []
    device = np.asarray(behind.buf).reshape(-1).view(np.uint8)
    out = []
    for at in range(0, behind.rows, behind.piece_rows):
        if behind.is_put(at):
            lo, hi = at * ALIGN, (at + behind.piece_rows) * ALIGN
            out.append((at, device[lo:hi], state.staging[lo:hi]))
    return out


def sealed_equals_staging(store, sid):
    state = store._state(sid)
    host = state.staging.copy()
    [(payload, sizes)] = store.seal(sid)
    assert (np.asarray(payload).reshape(-1).view(np.uint8) == host).all()
    assert sizes.tolist() == (state.region_used // ALIGN).tolist()


def test_another_writer_closes_and_commits_while_a_copy_is_held_open(monkeypatch):
    """At the parent this deadlocks: the held copy would hold the store's lock."""
    held = HeldCopies(monkeypatch)
    store = store_of(REGION)
    try:
        store.create_shuffle(0, 2, 4)
        another_slot_writes(store)
        state = store._state(0)
        rng = np.random.default_rng(1)
        big, beside, small = data_of(rng, 3 * PIECE + 5), data_of(rng, 2 * PIECE), data_of(rng, 100)
        held.hold("held")
        task = Task("held", store, 0, 0, [(0, big)])
        assert held.arrived.get(timeout=30) == ("held", 0)
        padded = -(-len(big) // ALIGN) * ALIGN
        assert state.inflight == {0: 1} and (0, 0) not in state.blocks
        assert int(state.region_used[0]) == padded  # the extent is taken before a byte moves
        [open_extent] = state.put_behind.open
        assert (open_extent.start, open_extent.padded, open_extent.filled) == (0, padded, 0)
        other = store.map_writer(0, 1)
        other.write_partition(0, beside)  # copied outside the lock too, behind the held extent
        other.write_partition(1, small)  # and a block of 100 bytes
        info = other.commit()
        assert [off for off, _ in info.partitions[:2]] == [padded, padded + 2 * PIECE]
        assert store.read_block(0, 1, 0) == beside and store.read_block(0, 1, 1) == small
        # the held extent starts the round: not a piece may go, whatever lies behind it
        assert stats(store, "early_put_pieces") == (0,) and state.put_behind.cursor == [0]
        assert task.is_alive() and state.inflight == {0: 1}
        held.release("held")
        assert task.done().error is None
        assert state.inflight == {} and not state.put_behind.open
        assert store.read_block(0, 0, 0) == big
        used = int(state.region_used[0])
        assert stats(store, "early_put_pieces") == (used // PIECE,)  # put once the copy had ended
        assert stats(store, "staged_blocks", "unlocked_copy_blocks", "unlocked_copy_bytes") == (
            4, 3, len(big) + len(beside) + len(small))  # the job before staged one
        sealed_equals_staging(store, 0)
    finally:
        store.close()


@pytest.mark.parametrize("regions", [1, 4])
@pytest.mark.parametrize("order", list(itertools.permutations(range(4))), ids=lambda o: "".join(map(str, o)))
def test_no_piece_goes_before_every_copy_into_it_has_ended(order, regions, monkeypatch):
    """Four writers' extents taken in order 0..3, their copies ending in
    ``order``: after each, exactly the pieces below every unfinished extent
    of their region are on the device, and they hold the staging's bytes."""
    held = HeldCopies(monkeypatch)
    store = store_of(regions * REGION, regions=regions)
    try:
        store.create_shuffle(0, 4, 4, peer_ranges=default_peer_ranges(4, regions))
        another_slot_writes(store)  # the first of the four is not alone
        state = store._state(0)
        rng = np.random.default_rng(sum(10**i * k for i, k in enumerate(order)) + regions)
        blocks = [data_of(rng, int(rng.integers(PIECE, 3 * PIECE))) for _ in range(4)]
        tasks, starts = [], []
        for k in range(4):
            held.hold(f"w{k}")
            tasks.append(Task(f"w{k}", store, 0, k, [(k, blocks[k])]))
            name, start = held.arrived.get(timeout=30)
            assert name == f"w{k}"
            starts.append(start)
        region_of = [s // state.region_size for s in starts]
        assert region_of == ([0] * 4 if regions == 1 else [0, 1, 2, 3])
        assert state.inflight == {0: 4} and not state.blocks
        unfinished = set(range(4))
        for k in order:
            held.release(f"w{k}")
            assert tasks[k].done().error is None
            unfinished.discard(k)
            marks = [p * state.region_size + int(state.region_used[p]) for p in range(regions)]
            for u in unfinished:
                marks[region_of[u]] = min(marks[region_of[u]], starts[u])
            final = sum((marks[p] - p * state.region_size) // PIECE for p in range(regions))
            pieces = on_device(store, state)
            assert len(pieces) == final == stats(store, "early_put_pieces")[0]
            for at, device, host in pieces:
                assert (device == host).all(), f"piece at row {at} went before its bytes"
                lo, hi = at * ALIGN, at * ALIGN + PIECE  # and shares no byte with an extent still being copied into
                assert all(hi <= starts[u] or lo >= starts[u] + len(blocks[u]) for u in unfinished)
        assert state.inflight == {} and not state.put_behind.open
        assert stats(store, "unlocked_copy_blocks", "early_put_dropped") == (4, 0)
        for k in range(4):
            assert store.read_block(0, k, k) == blocks[k]
        sealed_equals_staging(store, 0)
    finally:
        store.close()


def test_a_ram_arm_rollover_interleaves_and_the_block_stays_in_its_round(monkeypatch):
    held = HeldCopies(monkeypatch)
    store = store_of(4 * PIECE, device=False)
    try:
        store.create_shuffle(0, 2, 4)
        another_slot_writes(store)
        state = store._state(0)
        rng = np.random.default_rng(2)
        big, fill, rolls = data_of(rng, PIECE + 7), data_of(rng, 2 * PIECE), data_of(rng, 2 * PIECE)
        held.hold("held")
        task = Task("held", store, 0, 0, [(0, big)])
        assert held.arrived.get(timeout=30) == ("held", 0)
        other = store.map_writer(0, 1)
        other.write_partition(0, fill)
        other.write_partition(1, rolls)  # does not fit: the round rolls over, the copy still in flight
        assert state.round == 1 and state.inflight == {0: 1} and len(state.prev_rounds) == 1
        assert stats(store, "rollovers", "ram_rounds", "inflight_wait_ns") == (1, 1, 0)
        other.commit()
        held.release("held")
        assert task.done().error is None
        entry = state.blocks[(0, 0)]
        assert (entry.round, entry.offset, entry.length) == (0, 0, len(big))  # the round it was taken in
        assert state.blocks[(1, 1)].round == 1 and state.inflight == {}
        assert store.read_block(0, 0, 0) == big and store.read_block(0, 1, 1) == rolls
        first, second = store.seal(0)
        assert bytes(first[0].reshape(-1).view(np.uint8)[: len(big)]) == big
        assert bytes(second[0].reshape(-1).view(np.uint8)[: len(rolls)]) == rolls
    finally:
        store.close()


def _disk_arm(store, state, rng):
    fill, rolls = data_of(rng, 2 * PIECE), data_of(rng, 2 * PIECE)
    other = store.map_writer(0, 1)
    other.write_partition(0, fill)

    def act():
        other.write_partition(1, rolls)  # rolls the round over, through the disk tier
        other.commit()

    def after(task, big):
        assert task.error is None and state.round == 1
        assert isinstance(state.prev_rounds[0][0], np.memmap) and state.blocks[(0, 0)].round == 0
        assert store.read_block(0, 0, 0) == big and store.read_block(0, 1, 1) == rolls  # spilled whole
        assert stats(store, "rollovers", "ram_rounds") == (1, 0)

    return act, after


def _seal(store, state, rng):
    def after(task, big):
        assert task.error is None and state.sealed
        assert store.read_block(0, 0, 0) == big  # sealed with the block in it

    return (lambda: store.seal(0)), after


def _remove(store, state, rng):
    def after(task, big):
        assert isinstance(task.error, TransportError) and "unknown shuffle 0" in str(task.error)
        assert state.removed and state.inflight == {} and (0, 0) not in state.blocks

    return (lambda: store.remove_shuffle(0)), after


def _close(store, state, rng):
    def after(task, big):
        assert isinstance(task.error, TransportError) and state.removed and state.inflight == {}

    return store.close, after


@pytest.mark.parametrize("what", [_disk_arm, _seal, _remove, _close], ids=lambda f: f.__name__.strip("_"))
def test_whoever_would_read_zero_or_hand_on_the_round_waits_for_the_copy(what, monkeypatch):
    held = HeldCopies(monkeypatch)
    store = store_of(4 * PIECE, device=False, max_host_pool_bytes=0)  # no RAM tier: a rollover spills
    try:
        store.create_shuffle(0, 3, 4)
        another_slot_writes(store)
        state = store._state(0)
        rng = np.random.default_rng(3)
        big = data_of(rng, PIECE + 9)
        held.hold("held")
        task = Task("held", store, 0, 0, [(0, big)])
        assert held.arrived.get(timeout=30) == ("held", 0)
        act, after = what(store, state, rng)
        waited = stats(store, "inflight_wait_ns")[0]
        actor = threading.Thread(target=act, name="actor", daemon=True)
        actor.start()
        deadline = time.monotonic() + 30
        while not state.draining and time.monotonic() < deadline:
            time.sleep(0.001)
        assert state.draining == 1 and actor.is_alive() and state.inflight == {0: 1}
        # no new extent is held while one drains: a third writer waits at the lock's condition
        # (a removed shuffle's is refused outright: the store no longer knows it)
        third = Task("third", store, 0, 2, [(2, data_of(rng, 1024))], commit=False)
        time.sleep(0.05)
        assert actor.is_alive() and (2, 2) not in state.blocks
        assert third.is_alive() if what in (_disk_arm, _seal) else isinstance(third.done().error, TransportError)
        held.release("held")
        actor.join(30)
        assert not actor.is_alive()
        task.done(), third.done()
        assert stats(store, "inflight_wait_ns")[0] > waited
        after(task, big)
    finally:
        store.close()


@pytest.mark.parametrize("planted", [MemoryError("planted"), KeyboardInterrupt("planted")], ids=["error", "interrupt"])
@pytest.mark.parametrize("alone", [False, True], ids=["outside-the-lock", "under-the-lock"])
def test_a_copy_that_raises_leaves_a_hole_and_the_map_cannot_commit(alone, planted, monkeypatch):
    """Outside the lock and under it (the only writer open): an error fails
    the write typed, an interrupt stays an interrupt (the executor has to see
    it), and either way the store is left as a body that never arrived
    leaves it."""
    held = HeldCopies(monkeypatch)
    tenants = TenantRegistry()
    tenants.register("app", hbm_quota_bytes=1 << 20)
    store = store_of(REGION, tenants=tenants)
    try:
        store.create_shuffle(0, 2, 4, app_id="app")
        state = store._state(0)
        state.open_writers = 0 if alone else 1  # the other slot's task
        rng = np.random.default_rng(4)
        big, good = data_of(rng, 2 * PIECE + 3), data_of(rng, 2 * PIECE)
        held.raises["broken"] = planted
        task = Task("broken", store, 0, 0, [(0, big)]).done()
        if isinstance(planted, Exception):
            assert isinstance(task.error, TransportError) and task.error.__cause__ is planted
        else:
            assert task.error is planted
        padded = -(-len(big) // ALIGN) * ALIGN
        # a hole no entry names; charge and in-flight count are back
        assert (0, 0) not in state.blocks and int(state.region_used[0]) == padded
        assert state.inflight == {} and state.tenant_charged == 0 == tenants.usage("app")
        assert not state.put_behind.open  # and it holds no put cursor
        with pytest.raises(TransportError, match="open partition"):
            task.writer.commit()
        with pytest.raises(TransportError, match="lost"):
            task.writer.close_partition()
        assert 0 not in state.committed_maps
        # the retry writes it again, behind the hole
        retry = store.map_writer(0, 0)
        retry.write_partition(0, big)
        retry.write_partition(1, good)
        retry.commit()
        assert state.blocks[(0, 0)].offset == padded and store.read_block(0, 0, 0) == big
        assert state.tenant_charged == padded + 2 * PIECE == tenants.usage("app")
        # the lost writer never commits: the retry is not alone, and its two blocks leave the lock
        assert stats(store, "unlocked_copy_blocks", "early_put_dropped") == (2, 0)
        sealed_equals_staging(store, 0)  # the hole's half-copied bytes are padding on both sides
    finally:
        store.close()  # waits for anything in flight: a leaked count would hang here


class CountingLock:
    """The store's lock, counting its takes."""

    def __init__(self, lock):
        self.lock, self.takes = lock, 0

    def __enter__(self):
        self.takes += 1
        return self.lock.__enter__()

    def __exit__(self, *exc):
        return self.lock.__exit__(*exc)

    def acquire(self):
        self.takes += 1
        return self.lock.acquire()

    def release(self):
        self.lock.release()


@pytest.mark.parametrize("nbytes", [1, 1600, 1 << 20])
@pytest.mark.parametrize("others, takes", [(0, 1), (1, 2), (3, 2)])
def test_the_only_writer_open_takes_the_lock_once(others, takes, nbytes):
    """With no other writer of the shuffle open, allocate + copy + record are
    one take of the lock, as every block's were: nobody can wait for the
    copy.  With another open, two, whatever the block's size."""
    store = store_of(4 << 20, device=False)
    try:
        store.create_shuffle(0, 4, 4)
        store.check_memory_pressure = lambda *a, **k: None  # the gate's own take, as before
        writer = store.map_writer(0, 0)
        writer.write_partition(0, b"w" * 10)  # the staging's first touch is behind us
        beside = [store.map_writer(0, 1 + k) for k in range(others)]
        counting = store.lock = store._lock = CountingLock(store._lock)
        writer.open_partition(1)
        writer.write(b"x" * nbytes)
        assert counting.takes == 0
        writer.close_partition()
        assert counting.takes == takes
        store.lock = store._lock = counting.lock
        writer.commit()
        unlocked = takes - 1
        assert stats(store, "unlocked_copy_blocks", "unlocked_copy_bytes") == (unlocked, unlocked * nbytes)
        assert store.read_block(0, 0, 1) == b"x" * nbytes
        assert store._state(0).inflight == {} and store._state(0).open_writers == len(beside)
    finally:
        store.close()


def test_a_writer_is_open_from_its_creation_to_its_commit():
    """``open_writers``: a retry that discards is never counted, a second
    ``commit`` gives nothing back twice, an abandoned writer stays open (the
    copies of the shuffle then leave the lock: a microsecond a block)."""
    store = store_of(REGION, device=False)
    try:
        store.create_shuffle(0, 3, 2)
        state = store._state(0)
        first, second = store.map_writer(0, 0), store.map_writer(0, 1)
        assert state.open_writers == 2
        first.write_partition(0, b"a" * 300)
        first.commit()
        first.commit()
        assert state.open_writers == 1
        retry = store.map_writer(0, 0)  # the map is committed: this one discards
        assert state.open_writers == 1
        retry.write_partition(0, b"b" * 300)
        retry.commit()
        assert state.open_writers == 1 and store.read_block(0, 0, 0) == b"a" * 300
        del second  # abandoned
        third = store.map_writer(0, 2)
        third.write_partition(1, b"c" * 300)
        third.commit()
        assert state.open_writers == 1
        assert stats(store, "unlocked_copy_blocks") == (2,)  # the first's and the third's: the second was open
    finally:
        store.close()


@pytest.fixture
def tracer():
    enabled, recording = TRACER.enabled, TRACER.recording
    TRACER.clear()
    store_writer._blocks_traced = 0
    yield TRACER
    TRACER.enabled, TRACER.recording = enabled, recording
    TRACER.clear()


@pytest.mark.parametrize("alone", [True, False], ids=["alone", "beside-another"])
def test_the_block_phases_still_partition_the_close_and_the_task_counts_both_takes(alone, tracer, monkeypatch):
    """``write.block`` ⊃ ``admit`` / ``copy`` / ``record`` with no gap and no
    overlap, under the lock and outside it; ``write.task.copy`` has a turn a
    block and ``write.task.lock_wait`` one more for a block copied outside
    the lock; ``copy_ns`` and ``lock_wait_ns`` are the summed spans."""
    monkeypatch.setattr(store_writer, "WRITE_BLOCK_EVERY", 1)
    store = store_of(REGION, device=False)
    try:
        tracer.enable()
        tracer.clear()
        store.create_shuffle(0, 1, 8)
        if not alone:
            another_slot_writes(store)
        before = store.write_stats()
        writer = store.map_writer(0, 0)
        for r in range(8):
            writer.write_partition(r, bytes([r + 1]) * 3000)
        writer.commit()
        after = store.write_stats()
        events = [e for e in tracer.events if e["ph"] == "X"]
        [task] = [e for e in events if e["name"] == "write.task"]
        kids = {e["name"]: e for e in events if e["parent_id"] == task["span_id"] and e["name"] != "write.block"}
        unlocked = 0 if alone else 8
        assert after["unlocked_copy_blocks"] - before["unlocked_copy_blocks"] == unlocked
        assert kids["write.task.copy"]["args"] == {"turns": 8}
        assert kids["write.task.lock_wait"]["args"] == {"turns": 8 + unlocked}
        assert kids["write.task.copy"]["dur"] == (after["copy_ns"] - before["copy_ns"]) / 1e3
        assert kids["write.task.lock_wait"]["dur"] == (after["lock_wait_ns"] - before["lock_wait_ns"]) / 1e3
        blocks = [e for e in events if e["name"] == "write.block"]
        assert len(blocks) == 8
        for b in blocks:
            phases = sorted((e for e in events if e["parent_id"] == b["span_id"]), key=lambda e: e["ts"])
            assert [p["name"] for p in phases] == list(store_writer._WRITE_BLOCK_PHASES)
            for a, c in zip(phases, phases[1:]):
                assert abs(a["ts"] + a["dur"] - c["ts"]) < 0.002
            assert abs(phases[-1]["ts"] + phases[-1]["dur"] - (b["ts"] + b["dur"])) < 0.002
    finally:
        store.close()
