"""A single round larger than one piece goes to the store's device behind its
writers (``hbm_store._PutBehind``, PR 51): a piece is put as soon as it lies
wholly below its region's ``region_used`` — and below the whole rows received
of every partition still open for a receive in place — with no receive in
flight, the seal puts what is left, and the sealed round is the host staging
byte for byte — whatever interleaving of writers, frames, rollovers and
removals.

The CPU mesh with ``SEAL_PUT_PIECE_BYTES`` patched small: counts and bytes,
no rate."""

import gc
import sys
import threading
import time

import jax
import numpy as np
import pytest

import sparkucx_tpu.store.hbm_store as hbm_store
from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.operation import ResourceExhaustedError, TransportError
from sparkucx_tpu.shuffle.manager import TpuShuffleManager
from sparkucx_tpu.store.hbm_store import HbmBlockStore, default_peer_ranges
from sparkucx_tpu.testing import faults
from sparkucx_tpu.utils.trace import TRACER

ALIGN = 128
PIECE = 1 << 13  # 64 rows
EARLY = ("early_put_pieces", "early_put_bytes", "seal_put_pieces", "early_put_dropped")


@pytest.fixture(autouse=True)
def small_pieces(monkeypatch):
    monkeypatch.setattr(hbm_store, "SEAL_PUT_PIECE_BYTES", PIECE)


@pytest.fixture
def recording():
    """The flight recorder on and the ring empty, as in a served process."""
    before = TRACER.recording
    TRACER.recording = True
    TRACER.clear()
    yield
    TRACER.recording = before
    TRACER.clear()


def piece_puts():
    return [e for e in TRACER.events if e["name"] == "store.piece_put"]


def store_of(capacity, device=True, regions=1, **conf):
    """A store of a long-lived executor: a job before left its staging buffer
    on the free list, so the next round is written into pages the process
    holds (a store's first job, into fresh pages, is never put behind)."""
    store = HbmBlockStore(
        TpuShuffleConf(block_alignment=ALIGN, staging_capacity_per_executor=capacity, **conf),
        device=jax.devices()[0] if device else None,
    )
    store.create_shuffle(99, 1, regions, peer_ranges=default_peer_ranges(regions, regions))
    writer = store.map_writer(99, 0)
    writer.write_partition(0, b"the job before")
    writer.commit()
    assert store._state(99).put_behind is None  # the free list was empty: fresh pages
    store.remove_shuffle(99)
    return store


def job_before(mgr, mappers):
    """The same through a manager: every executor's store has held a round."""
    mgr.register_shuffle(99, mappers, 1)
    for m in range(mappers):
        writer = mgr.get_writer(99, m)
        with writer.get_partition_writer(0).open_stream() as stream:
            stream.write(b"the job before")
        writer.commit_all_partitions()
    mgr.unregister_shuffle(99)


def engaged(store, sid):
    """The shuffle's ``_PutBehind`` once its staging was taken (the first
    write does this; here without one)."""
    state = store._state(sid)
    assert state.put_behind is None or state.host_staging_allocated
    with store._lock:
        assert state.staging is not None
    return state.put_behind


def early(store):
    stats = store.write_stats()
    return tuple(stats[k] for k in EARLY)


def block(rng, lo=1, hi=3000):
    return rng.integers(0, 256, size=int(rng.integers(lo, hi)), dtype=np.uint8).tobytes()


def sealed_equals_staging(store, sid):
    """Seal and compare the device round with the host staging as it stood."""
    state = store._state(sid)
    host = state.staging.copy()
    [(payload, sizes)] = store.seal(sid)
    assert isinstance(payload, jax.Array) and payload.devices() == {store.device}
    assert (np.asarray(payload).reshape(-1).view(np.uint8) == host).all()
    assert sizes.tolist() == (state.region_used // ALIGN).tolist()
    assert state.put_behind is None
    return payload


def live_device_bytes():
    gc.collect()
    return sum(int(a.nbytes) for a in jax.live_arrays())


@pytest.mark.parametrize("regions", [1, 2, 4])
def test_the_sealed_round_is_the_staging_with_pieces_put_before_the_seal(regions, recording):
    """One writer after another: every piece a region's used prefix has
    passed is on the device before the seal, the seal puts the piece each
    region's writer stood in (and a piece across two regions), every
    ``device_put`` is at most a piece, and less than the buffer crosses."""
    capacity = 700 * ALIGN
    puts = []
    real_put = jax.device_put
    store = store_of(capacity, regions=regions)
    try:
        store.create_shuffle(0, 4, 8, peer_ranges=default_peer_ranges(8, regions))
        state = store._state(0)
        assert state.put_behind is None and not state.host_staging_allocated  # decided with the buffer
        behind = engaged(store, 0)
        assert behind is not None and behind.piece_rows == PIECE // ALIGN
        rng = np.random.default_rng(regions)
        jax.device_put = lambda x, *a, **k: (puts.append(int(x.nbytes)), real_put(x, *a, **k))[1]
        try:
            for m in range(4):
                writer = store.map_writer(0, m)
                for r in range(8):
                    writer.write_partition(r, block(rng))
                writer.commit()
            pieces, nbytes, at_seal, dropped = early(store)
            assert pieces > 0 and nbytes == pieces * PIECE == sum(puts) and (at_seal, dropped) == (0, 0)
            # exactly the whole pieces below each region's used prefix, and inside the region
            region_rows, piece_rows = state.region_size // ALIGN, PIECE // ALIGN
            want = 0
            for p in range(regions):
                first = -(-p * region_rows // piece_rows)
                last_whole = (p * region_rows + int(state.region_used[p]) // ALIGN) // piece_rows
                want += max(last_whole - first, 0)
            assert pieces == want
            spans = piece_puts()
            assert len(spans) == pieces
            assert all(e["args"]["bytes"] == PIECE and e["args"]["executor"] == 0 for e in spans)
            assert len({e["args"]["at"] for e in spans}) == pieces and all(e["args"]["at"] % PIECE == 0 for e in spans)
            sealed_equals_staging(store, 0)
        finally:
            jax.device_put = real_put
        pieces, nbytes, at_seal, dropped = early(store)
        # the piece each region's writer stands in, and the piece across a border
        assert 1 <= at_seal <= 2 * regions and dropped == 0
        assert max(puts) <= PIECE and int(state.region_used.sum()) <= sum(puts) < capacity
        assert len(piece_puts()) == pieces  # the seal's puts are ``store.seal_put``'s, not the span's
    finally:
        store.close()


def test_one_region_leaves_the_seal_the_piece_the_writer_stands_in():
    store = store_of(1 << 17)
    try:
        store.create_shuffle(0, 1, 64)
        rng = np.random.default_rng(7)
        writer = store.map_writer(0, 0)
        for r in range(63):
            writer.write_partition(r, block(rng, 500, 1500))
        writer.write_partition(63, b"x")  # the writer stands inside a piece, not on its border
        writer.commit()
        used = int(store._state(0).region_used[0])
        pieces = early(store)[0]
        assert pieces == used // PIECE > 3 and used % PIECE
        sealed_equals_staging(store, 0)
        assert early(store) == (pieces, pieces * PIECE, 1, 0)
        assert early(store)[2] <= hbm_store.SEAL_PUT_PIECES_IN_FLIGHT + 1
    finally:
        store.close()


def test_a_receive_in_place_holds_the_cursor_until_end_receive():
    """``reserve`` moves ``region_used`` before the socket has filled the
    extent: nothing of the round is put while a receive is in flight, not by
    another writer's block either, and the pieces go when it ends."""
    store = store_of(1 << 16)
    try:
        store.create_shuffle(0, 2, 4)
        rng = np.random.default_rng(3)
        body = rng.integers(0, 256, size=3 * PIECE, dtype=np.uint8)
        receiver, other = store.map_writer(0, 0), store.map_writer(0, 1)
        receiver.open_partition(0)
        view = receiver.reserve(body.nbytes)
        assert view is not None and early(store)[0] == 0
        other.write_partition(0, block(rng, PIECE, 2 * PIECE))  # passes piece ends behind the extent
        assert early(store)[0] == 0 and store._state(0).put_behind.cursor == [0]
        view[: body.nbytes // 2] = body[: body.nbytes // 2].tobytes()
        assert early(store)[0] == 0
        view[body.nbytes // 2 :] = body[body.nbytes // 2 :].tobytes()
        receiver.end_receive(body.nbytes, True)
        assert early(store)[0] >= 3  # the extent's pieces, and the other writer's behind it
        receiver.close_partition()
        receiver.commit()
        other.commit()
        sealed_equals_staging(store, 0)
        assert store.read_block(0, 0, 0) == body.tobytes()
    finally:
        store.close()


def test_a_body_that_never_arrives_leaves_a_hole_the_device_has_too():
    store = store_of(1 << 16)
    try:
        store.create_shuffle(0, 2, 4)
        rng = np.random.default_rng(4)
        lost, other = store.map_writer(0, 0), store.map_writer(0, 1)
        lost.open_partition(0)
        view = lost.reserve(2 * PIECE)
        view[:100] = bytes(range(100))
        lost.end_receive(2 * PIECE, False)
        other.write_partition(0, block(rng, 2 * PIECE, 3 * PIECE))
        other.commit()
        assert early(store)[0] >= 3
        sealed_equals_staging(store, 0)
    finally:
        store.close()


# (first frame, second frame): the first frame's PADDED end is a piece's end /
# inside a piece / the first frame alone covers pieces; the second frame is
# received from the first's UNPADDED end, below ``region_used``
@pytest.mark.parametrize(
    "frames", [(PIECE - 100, 200), (PIECE - 100, 3 * PIECE), (2 * PIECE + 1, PIECE - 1), (100, 27), (PIECE - ALIGN, ALIGN)]
)
def test_a_partition_received_over_several_frames_is_put_when_it_is_whole(frames):
    """``reserve`` moves ``region_used`` by the padded total and the next
    frame of the partition lands at the unpadded end: the last row of a frame
    is not final until the partition is closed, whatever ``region_used`` and
    the in-flight count say between the frames."""
    store = store_of(1 << 16)
    try:
        store.create_shuffle(0, 2, 4)
        rng = np.random.default_rng(sum(frames))
        body = rng.integers(1, 256, size=sum(frames), dtype=np.uint8).tobytes()
        receiver, other = store.map_writer(0, 0), store.map_writer(0, 1)
        receiver.open_partition(0)
        got = 0
        for n in frames:
            view = receiver.reserve(n)
            assert view is not None
            view[:] = body[got : got + n]
            receiver.end_receive(n, True)
            got += n
            # between frames: only whole rows received are on the device
            assert early(store)[0] == got // PIECE
        assert engaged(store, 0).open
        receiver.close_partition()
        assert not engaged(store, 0).open and early(store)[0] == -(-got // ALIGN) * ALIGN // PIECE
        receiver.commit()
        other.write_partition(0, block(rng, PIECE, 2 * PIECE))
        other.commit()
        assert store.write_stats()["inplace_fallbacks"] == 0
        sealed_equals_staging(store, 0)
        assert store.read_block(0, 0, 0) == body
    finally:
        store.close()


@pytest.mark.parametrize("how", ["abandoned", "buffered"])
def test_an_open_partition_holds_its_regions_cursor_and_no_others(how):
    """A partition left open between two frames (its connection is slow, or
    gone) holds its region's cursor at its extent; another region's goes on.
    Fed through ``write`` after its first frame it goes back to the buffered
    path: the extent stays as padding and the cursor passes it."""
    store = store_of(1 << 17, regions=2)
    try:
        store.create_shuffle(0, 2, 2, peer_ranges=default_peer_ranges(2, 2))
        rng = np.random.default_rng(5)
        first = rng.integers(1, 256, size=PIECE - 100, dtype=np.uint8).tobytes()
        receiver, other = store.map_writer(0, 0), store.map_writer(0, 1)
        receiver.open_partition(0)
        receiver.reserve(len(first))[:] = first
        receiver.end_receive(len(first), True)
        other.write_partition(0, block(rng, 2 * PIECE, 3 * PIECE))  # region 0, behind the open extent
        assert early(store)[0] == 0 and engaged(store, 0).cursor[0] == 0
        data = block(rng, 2 * PIECE, 3 * PIECE)
        other.write_partition(1, data)  # region 1: nothing open there
        assert early(store)[0] == 2
        other.commit()
        if how == "buffered":
            receiver.write(b"tail")
            receiver.close_partition()
            receiver.commit()
            assert store.write_stats()["inplace_fallbacks"] == 1 and not engaged(store, 0).open
            assert early(store)[0] > 2
            assert store.read_block(0, 0, 0) == first + b"tail"
        else:
            assert early(store)[0] == 2 and engaged(store, 0).open
        sealed_equals_staging(store, 0)
        assert store.read_block(0, 1, 1) == data
    finally:
        store.close()


def test_a_second_shuffle_in_flight_writes_fresh_pages_and_is_not_put_behind():
    """The free list holds ONE buffer: the shuffle that takes it at its first
    write is put behind its writers, the one created beside it writes into
    fresh pages and is not — whichever was created first."""
    store = store_of(1 << 16)
    try:
        store.create_shuffle(0, 1, 4)
        store.create_shuffle(1, 1, 4)
        rng = np.random.default_rng(6)
        for sid in (1, 0):  # the later one writes first and gets the held buffer
            writer = store.map_writer(sid, 0)
            for r in range(4):
                writer.write_partition(r, block(rng, 3000, 6000))
            writer.commit()
        assert store._state(1).put_behind is not None and store._state(0).put_behind is None
        pieces = early(store)[0]
        assert pieces > 0
        sealed_equals_staging(store, 0)
        assert early(store)[0] == pieces and early(store)[2] > 1  # all of shuffle 0 at its seal
        sealed_equals_staging(store, 1)
    finally:
        store.close()


# the second: a staging round over the RAM budget, kept by the free list's
# floor as the HBM-held cells' 4 GiB is; its rollover goes to the disk tier
@pytest.mark.parametrize("budget", [1 << 30, (1 << 15) - 1], ids=["ram-round", "disk-round"])
def test_a_rollover_after_early_puts_drops_the_buffer_and_the_rounds_are_right(budget):
    """The job turned multi-round: what was put of round 0 piece by piece is
    let go, every round is handed on as a host round, and each sealed round
    is its staging byte for byte.  On the RAM arm round 0 — the one buffer
    the free list handed out — is put whole once it is final (PR 57,
    ``tests/test_early_rounds.py``); the later rounds' buffers are fresh."""
    capacity = 1 << 15
    store = store_of(capacity, max_host_pool_bytes=budget)
    floor = live_device_bytes()
    try:
        store.create_shuffle(0, 1, 16)
        rng = np.random.default_rng(5)
        writer = store.map_writer(0, 0)
        want = {}
        for r in range(16):
            want[r] = block(rng, 3000, 6000)
            writer.write_partition(r, want[r])
            if store._state(0).round == 0:
                first_round_pieces = early(store)[0]
        writer.commit()
        state = store._state(0)
        assert state.round >= 2 and state.put_behind is None
        assert first_round_pieces > 0
        assert early(store) == (first_round_pieces, first_round_pieces * PIECE, 0, 1)
        # the dropped buffer and its pieces are gone; the RAM arm holds round 0's whole copy
        assert live_device_bytes() == floor + (capacity if budget > capacity else 0)
        rounds = store.seal(0)
        assert len(rounds) == state.round + 1
        assert not any(isinstance(payload, jax.Array) for payload, _ in rounds)
        assert early(store)[2:] == (0, 1)
        for r, data in want.items():
            assert store.read_block(0, 0, r) == data
    finally:
        store.close()


def test_a_multi_round_exchange_after_early_puts_returns_the_right_bytes():
    conf = TpuShuffleConf(block_alignment=ALIGN, staging_capacity_per_executor=1 << 15, num_executors=1)
    with TpuShuffleManager(conf, num_executors=1) as mgr:
        job_before(mgr, 1)
        mgr.register_shuffle(0, 3, 4)
        rng = np.random.default_rng(6)
        want = {}
        for m in range(3):
            writer = mgr.get_writer(0, m)
            for r in range(4):
                want[(m, r)] = block(rng, 2000, 5000)
                with writer.get_partition_writer(r).open_stream() as stream:
                    stream.write(want[(m, r)])
            writer.commit_all_partitions()
        store = mgr.cluster.transports[0].store
        assert store.num_rounds(0) > 1
        pieces, _, _, dropped = early(store)
        assert pieces > 0 and dropped == 1
        mgr.run_exchange(0)
        for r in range(4):
            got = sorted(mgr.get_reader(0, r, r + 1, deserializer=lambda payload: [bytes(payload)]).read())
            assert got == sorted(want[(m, r)] for m in range(3))
        assert early(store)[2] == 0


@pytest.mark.parametrize("how", ["remove", "abort", "close"])
def test_a_shuffle_gone_before_its_seal_leaves_no_device_array(how):
    floor = live_device_bytes()
    conf = TpuShuffleConf(block_alignment=ALIGN, staging_capacity_per_executor=1 << 16, num_executors=1)
    with TpuShuffleManager(conf, num_executors=1) as mgr:
        job_before(mgr, 1)
        assert live_device_bytes() == floor
        mgr.register_shuffle(0, 2, 8)
        rng = np.random.default_rng(8)
        writer = mgr.get_writer(0, 0)
        for r in range(8):
            with writer.get_partition_writer(r).open_stream() as stream:
                stream.write(block(rng, 2000, 4000))
        store = mgr.cluster.transports[0].store
        pieces = early(store)[0]
        assert pieces > 0 and live_device_bytes() >= floor + (1 << 16)
        released = store.write_stats()["released_device_bytes"]
        if how == "abort":
            writer.abort(RuntimeError("task failed"))
        if how == "close":
            store.close()
        else:
            mgr.unregister_shuffle(0)
            assert store.write_stats()["released_device_bytes"] - released == 1 << 16
        assert early(store) == (pieces, pieces * PIECE, 0, 1)
        assert live_device_bytes() == floor
    assert live_device_bytes() == floor


def test_the_watermark_gate_refusing_means_no_early_put_not_a_failed_write():
    store = store_of(1 << 16)
    try:
        with faults.injected_faults():
            faults.arm(
                "store.mem_pressure",
                faults.fail(ResourceExhaustedError(detail="injected pressure")),
                match={"site": "piece_put"},
            )
            store.create_shuffle(0, 1, 8)
            rng = np.random.default_rng(9)
            writer = store.map_writer(0, 0)
            for r in range(8):
                writer.write_partition(r, block(rng, 2000, 4000))
            writer.commit()
            assert faults.fired["store.mem_pressure"] > 0
        assert early(store) == (0, 0, 0, 0) and store._state(0).put_behind.cursor == [0]
        sealed_equals_staging(store, 0)
        assert early(store)[2] == -(-int(store._state(0).region_used[0]) // PIECE)
    finally:
        store.close()


@pytest.mark.parametrize("error", [jax.errors.JaxRuntimeError, TypeError], ids=["the-runtimes", "this-codes"])
def test_a_put_that_raises_costs_the_early_pieces_and_not_the_write(error, monkeypatch):
    """A put the runtime refuses is logged and the write goes on; any other
    error goes up through the block that ran into it (recorded before: the
    task's retry is a discarded write) — the chain is let go either way and
    the seal puts the round whole."""
    store = store_of(1 << 16)
    try:
        store.create_shuffle(0, 1, 8)
        rng = np.random.default_rng(10)
        real = HbmBlockStore._put_piece
        calls = []

        def failing(self, behind, payload, at):
            calls.append(at)
            if len(calls) == 3:
                raise error("RESOURCE_EXHAUSTED: injected")
            return real(self, behind, payload, at)

        monkeypatch.setattr(HbmBlockStore, "_put_piece", failing)
        writer = store.map_writer(0, 0)
        raised = 0
        for r in range(8):
            try:
                writer.write_partition(r, block(rng, 2000, 4000))
            except TypeError:
                raised += 1
        writer.commit()
        assert raised == (error is TypeError)
        assert early(store) == (2, 2 * PIECE, 0, 1) and store._state(0).put_behind is None
        monkeypatch.setattr(HbmBlockStore, "_put_piece", real)
        sealed_equals_staging(store, 0)
        assert early(store)[2] == -(-int(store._state(0).region_used[0]) // PIECE)
    finally:
        store.close()


@pytest.mark.parametrize("regions", [1, 3])
def test_writers_closing_partitions_concurrently_keep_one_update_chain(regions, monkeypatch):
    """More writer threads than cores, the interpreter switching often, the
    puts slowed so that pieces complete under an owner: never two threads in
    the chain at once, every piece put once, the sealed round exact."""
    mappers, reducers = 12, 6
    inside = []
    overlap = []
    real = HbmBlockStore._put_piece

    def watched(self, behind, payload, at):
        inside.append(threading.get_ident())
        if len(inside) > 1:
            overlap.append(tuple(inside))
        try:
            time.sleep(0.0005)
            return real(self, behind, payload, at)
        finally:
            inside.pop()

    monkeypatch.setattr(HbmBlockStore, "_put_piece", watched)
    store = store_of(1 << 18, regions=regions)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        store.create_shuffle(0, mappers, reducers, peer_ranges=default_peer_ranges(reducers, regions))
        first_pieces = list(engaged(store, 0).cursor)
        errors = []
        written = {}

        def work(m):
            try:
                rng = np.random.default_rng(100 + m)
                writer = store.map_writer(0, m)
                for r in range(reducers):
                    written[(m, r)] = block(rng, 500, 3000)
                    writer.write_partition(r, written[(m, r)])
                writer.commit()
            except BaseException as e:  # the thread's boundary: reported below
                errors.append(e)

        threads = [threading.Thread(target=work, args=(m,)) for m in range(mappers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads) and not errors
        state = store._state(0)
        assert not overlap and not state.put_behind.owner
        pieces = early(store)[0]
        assert pieces > 0 and pieces == sum(c - c0 for c, c0 in zip(state.put_behind.cursor, first_pieces))
        sealed_equals_staging(store, 0)
        for key, data in written.items():
            assert store.read_block(0, *key) == data
        assert early(store)[3] == 0
    finally:
        sys.setswitchinterval(interval)
        store.close()


def test_the_seal_waits_for_the_owner_and_carries_its_chain_on(monkeypatch):
    """A seal that arrives while a writer is inside a put waits for it."""
    real = HbmBlockStore._put_piece
    entered, go = threading.Event(), threading.Event()

    def held(self, behind, payload, at):
        if not entered.is_set():
            entered.set()
            assert go.wait(timeout=30)
        return real(self, behind, payload, at)

    monkeypatch.setattr(HbmBlockStore, "_put_piece", held)
    store = store_of(1 << 16)
    try:
        store.create_shuffle(0, 1, 8)
        rng = np.random.default_rng(11)

        refused = []

        def write():
            writer = store.map_writer(0, 0)
            try:
                for r in range(8):
                    writer.write_partition(r, block(rng, 2000, 4000))
                writer.commit()
            except TransportError as e:  # a block that came after the seal
                refused.append(e)

        writer_thread = threading.Thread(target=write)
        writer_thread.start()
        assert entered.wait(timeout=30)
        sealed = []
        sealer = threading.Thread(target=lambda: sealed.append(store.seal(0)))
        sealer.start()
        sealer.join(timeout=0.3)
        assert sealer.is_alive() and not sealed  # the owner is inside its put
        go.set()
        writer_thread.join(timeout=30)
        sealer.join(timeout=30)
        assert not writer_thread.is_alive() and not sealer.is_alive()
        # who takes the lock when the owner lets go of it is the scheduler's: a
        # block that came after the seal is refused, and either way the device
        # round is the staging the seal saw
        assert all("already sealed" in str(e) for e in refused)
        assert early(store)[0] >= 1
        [[(payload, _)]] = sealed
        state = store._state(0)
        assert (np.asarray(payload).reshape(-1).view(np.uint8) == state.staging).all()
    finally:
        go.set()
        store.close()


def test_four_executors_with_a_large_staging_put_behind_a_cursor_a_region(recording):
    """The CPU mesh, four stores of four regions each: every store puts its
    own round behind its writers and the exchange returns the right bytes."""
    executors, mappers, reducers = 4, 8, 8
    conf = TpuShuffleConf(keep_device_recv=True, host_recv_mode="device", block_alignment=ALIGN,
                          staging_capacity_per_executor=1 << 17, num_executors=executors)
    with TpuShuffleManager(conf, num_executors=executors) as mgr:
        job_before(mgr, mappers)
        mgr.register_shuffle(0, mappers, reducers)
        rng = np.random.default_rng(12)
        want = {}
        for m in range(mappers):
            writer = mgr.get_writer(0, m)
            for r in range(reducers):
                want[(m, r)] = block(rng, 4000, 9000)
                with writer.get_partition_writer(r).open_stream() as stream:
                    stream.write(want[(m, r)])
            writer.commit_all_partitions()
        stores = [t.store for t in mgr.cluster.transports]
        cursors = [list(s._state(0).put_behind.cursor) for s in stores]
        assert all(early(s)[0] > 0 for s in stores)
        # a cursor a region: more than one region of a store has moved
        assert all(sum(c > start for c, start in zip(cur, [0, 4, 8, 12])) > 1 for cur in cursors)
        assert len(piece_puts()) == sum(early(s)[0] for s in stores)
        assert {e["args"]["executor"] for e in piece_puts()} == set(range(executors))
        mgr.run_exchange(0)  # donates the sealed rounds: the reads below hold their bytes
        assert all(early(s)[2] >= 1 and early(s)[3] == 0 for s in stores)
        for r in range(reducers):
            got = sorted(mgr.get_reader(0, r, r + 1, deserializer=lambda payload: [bytes(payload)]).read())
            assert got == sorted(want[(m, r)] for m in range(mappers))


@pytest.mark.parametrize("case", ["fresh-buffer", "one-piece-staging", "device-mode", "no-device", "shm-staging"])
def test_a_store_that_would_not_seal_in_pieces_records_no_piece_put(case, recording, monkeypatch):
    """A store's first job (fresh pages: its write is their first touch), a
    staging round of one piece (the default 64 MiB: nine of the twelve
    cells), a device-staged shuffle, a store without a device and shm staging
    never engage: no ``store.piece_put``, no early counter, nothing on the
    shuffle's state for a block to look at."""
    if case == "one-piece-staging":
        monkeypatch.setattr(hbm_store, "SEAL_PUT_PIECE_BYTES", 64 << 20)
    conf = {"use_shm_staging": True, "shm_namespace": "putbehind"} if case == "shm-staging" else {}
    if case == "device-mode":
        conf["device_staging"] = True
    if case == "fresh-buffer":
        store = HbmBlockStore(
            TpuShuffleConf(block_alignment=ALIGN, staging_capacity_per_executor=1 << 16), device=jax.devices()[0])
    else:
        store = store_of(1 << 16, device=case != "no-device", **conf)
    try:
        store.create_shuffle(0, 1, 4)
        state = store._state(0)
        writer = store.map_writer(0, 0)
        rng = np.random.default_rng(13)
        if case == "device-mode":
            rows = jax.device_put(np.arange(40 * (ALIGN // 4), dtype=np.int32).reshape(40, -1), store.device)
            writer.write_partitions_device(rows, [0, 1], [20 * ALIGN, 20 * ALIGN])
        else:
            for r in range(4):
                writer.write_partition(r, block(rng, 3000, 6000))
        writer.commit()
        assert state.put_behind is None and state.host_staging_allocated == (case != "device-mode")
        assert early(store) == (0, 0, 0, 0) and not piece_puts()
        rounds = store.seal(0)
        assert len(rounds) == 1 and state.put_behind is None
        assert not piece_puts() and early(store)[0] == 0
        if case in ("shm-staging", "fresh-buffer"):  # a round of several pieces, all the seal's
            assert early(store)[2] > 1
        elif case != "one-piece-staging":
            assert early(store)[2] == 0
        if case == "fresh-buffer":  # and the job after it writes into the pages this one leaves
            store.remove_shuffle(0)
            store.create_shuffle(1, 1, 4)
            assert engaged(store, 1) is not None
    finally:
        store.close()
