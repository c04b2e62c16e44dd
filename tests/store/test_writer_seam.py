"""The seam between a map task's writer and the store (PR 56): every change of
a shuffle's staging state is a method of ``HbmBlockStore``; ``store/writer.py``
holds what belongs to one task.  Each case is a count or an identity: the
buffered close and the receive in place leave the same table entry, region
mark and tenant charge for the same bytes; an extent that is lost stays a hole
and gives its charge back; a held extent takes its round's in-flight count and
its end gives it back; a discarded retry changes nothing; and the writer's
source reaches into no private of the store or of the shuffle's state and
writes none of the state's fields."""

import ast
import sys
from dataclasses import asdict
from pathlib import Path

import numpy as np
import pytest

import sparkucx_tpu.store.writer as store_writer
from sparkucx_tpu.analysis.locks import MUTATORS
from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.operation import TransportError
from sparkucx_tpu.service.tenants import TenantRegistry
from sparkucx_tpu.store import HbmBlockStore, MapWriter
from sparkucx_tpu.store.hbm_store import default_peer_ranges

ALIGN = 128
CAPACITY = 1 << 16


def payload(seed, nbytes):
    return np.random.default_rng(seed).integers(1, 256, size=nbytes, dtype=np.uint8).tobytes()


def make_tenants():
    registry = TenantRegistry()
    registry.register("app", hbm_quota_bytes=1 << 20)
    return registry


def make_store(registry):
    """Shuffle 0 of tenant ``app``: four maps, two reducers, a region each."""
    s = HbmBlockStore(TpuShuffleConf(block_alignment=ALIGN, staging_capacity_per_executor=CAPACITY))
    s.tenants = registry
    s.create_shuffle(0, 4, 2, peer_ranges=default_peer_ranges(2, 2), app_id="app")
    return s


@pytest.fixture
def tenants():
    return make_tenants()


@pytest.fixture
def store(tenants):
    s = make_store(tenants)
    yield s
    s.close()


def buffered(writer, reduce_id, data):
    writer.write_partition(reduce_id, data)


def in_place(writer, reduce_id, data, frames=1):
    """``data`` through ``reserve`` / ``end_receive``, as the daemon's socket feeds it."""
    writer.open_partition(reduce_id)
    step = -(-len(data) // frames)
    for at in range(0, len(data), step):
        part = data[at : at + step]
        view = writer.reserve(len(part))
        assert view is not None
        view[:] = part
        writer.end_receive(len(part), True)
    writer.close_partition()


def outside_the_lock(writer, reduce_id, data):
    """The buffered close while another writer of the shuffle is open: the copy leaves the lock."""
    other = writer._store.map_writer(0, 3)
    try:
        writer.write_partition(reduce_id, data)
    finally:
        other.commit()


WAYS_IN = {
    "in-place": in_place,
    "in-place-three-frames": lambda w, r, d: in_place(w, r, d, frames=3),
    "buffered-outside-the-lock": outside_the_lock,
}


def staged_state(store, tenants):
    st = store._state(0)
    return (
        {key: asdict(entry) for key, entry in st.blocks.items()},
        st.region_used.tolist(), st.tenant_charged, tenants.usage("app"), dict(st.inflight),
    )


@pytest.mark.parametrize("way", sorted(WAYS_IN))
@pytest.mark.parametrize("nbytes", [1, 1000, 5 * ALIGN])
def test_every_way_in_leaves_what_the_buffered_close_leaves(way, nbytes, store, tenants):
    """The same bytes at the same offsets under the same entry, mark and
    charge, whichever client of the three steps staged them."""
    first, second = payload(1, nbytes), payload(2, 777)
    reference_tenants = make_tenants()
    reference = make_store(reference_tenants)
    try:
        for s, stage in ((reference, buffered), (store, WAYS_IN[way])):
            writer = s.map_writer(0, 0)
            stage(writer, 0, first)
            stage(writer, 1, second)
            info = writer.commit()
            assert info.partitions == ((0, nbytes), (CAPACITY // 2, 777))
        assert staged_state(store, tenants) == staged_state(reference, reference_tenants)
        padded = -(-nbytes // ALIGN) * ALIGN
        assert staged_state(store, tenants)[1:] == ([padded, 7 * ALIGN], padded + 7 * ALIGN, padded + 7 * ALIGN, {})
        assert (store.read_block(0, 0, 0), store.read_block(0, 0, 1)) == (first, second)
    finally:
        reference.close()


def lost_body(writer, data):
    writer.open_partition(0)
    writer.reserve(len(data))[: len(data) // 2] = data[: len(data) // 2]
    writer.end_receive(len(data), False)


def lost_copy(writer, data, monkeypatch):
    def broken(staging, start, chunks):
        raise MemoryError("planted")

    monkeypatch.setattr(store_writer, "_copy_chunks", broken)
    with pytest.raises(TransportError, match="lost its copy into staging") as raised:
        writer.write_partition(0, data)
    assert isinstance(raised.value.__cause__, MemoryError)
    monkeypatch.undo()


@pytest.mark.parametrize("how", ["body-cut-short", "copy-raised-under-the-lock", "copy-raised-outside-it"])
def test_a_lost_extent_stays_a_hole_and_gives_its_charge_back(how, store, tenants, monkeypatch):
    data, st = payload(3, 1000), store._state(0)
    other = store.map_writer(0, 3) if how == "copy-raised-outside-it" else None
    writer = store.map_writer(0, 0)
    if how == "body-cut-short":
        lost_body(writer, data)
    else:
        lost_copy(writer, data, monkeypatch)
    assert st.blocks == {} and st.region_used.tolist() == [8 * ALIGN, 0]  # a hole no entry names
    assert st.tenant_charged == 0 == tenants.usage("app") and st.inflight == {}
    with pytest.raises(TransportError, match="lost a body mid-receive"):
        writer.close_partition()
    with pytest.raises(TransportError, match="commit with open partition"):
        writer.commit()
    assert 0 not in st.committed_maps
    retry = store.map_writer(0, 0)  # the map's retry writes it again, behind the hole
    retry.write_partition(0, data)
    retry.commit()
    assert st.blocks[(0, 0)].offset == 8 * ALIGN and store.read_block(0, 0, 0) == data
    assert st.tenant_charged == 8 * ALIGN == tenants.usage("app")
    if other is not None:
        other.commit()


def test_a_held_extent_takes_its_rounds_count_and_its_end_gives_it_back(store, tenants):
    st, writer = store._state(0), store.map_writer(0, 0)
    writer.open_partition(1)
    view = writer.reserve(300)
    assert st.inflight == {0: 1} and st.region_used.tolist() == [0, 3 * ALIGN]
    assert st.blocks == {} and st.tenant_charged == 3 * ALIGN  # allocated and charged, not yet named
    second = store.map_writer(0, 1)
    second.open_partition(1)
    second.reserve(10)
    assert st.inflight == {0: 2}
    second.end_receive(10, True)
    assert st.inflight == {0: 1}
    view[:] = b"x" * 300
    writer.end_receive(300, True)
    assert st.inflight == {} and st.blocks == {}  # the record is the close's
    writer.close_partition(), second.close_partition()
    assert sorted(st.blocks) == [(0, 1), (1, 1)] and st.tenant_charged == 4 * ALIGN == tenants.usage("app")
    assert store.read_block(0, 0, 1) == b"x" * 300


def test_a_discarded_retry_changes_nothing(store, tenants):
    first = store.map_writer(0, 0)
    first.write_partition(0, payload(4, 500))
    first.commit()
    before, counters = staged_state(store, tenants), store.write_stats()
    st = store._state(0)
    retry = store.map_writer(0, 0)
    assert retry.is_retry_discard and st.open_writers == 0
    retry.write_partition(0, payload(5, 900))
    retry.open_partition(1)
    assert retry.reserve(100) is None  # a discarded retry feeds ``write``
    retry.write(b"y" * 100)
    retry.close_partition()
    assert retry.commit().partitions == ((0, 500), (0, 0))  # the first attempt's table
    assert staged_state(store, tenants) == before and store.write_stats() == counters
    assert st.open_writers == 0 and st.committed_maps == {0}


def test_the_writer_is_what_the_package_exports_and_the_store_makes(store):
    import sparkucx_tpu.store.hbm_store as hbm_store

    writer = store.map_writer(0, 2)
    assert type(writer) is MapWriter is store_writer.MapWriter and MapWriter.__module__ == "sparkucx_tpu.store.writer"
    for name in ("WRITE_BLOCK_EVERY", "_copy_chunks", "_kernel_counts_faults", "_thread_minor_faults"):
        assert hasattr(store_writer, name) and not hasattr(hbm_store, name)
    assert store.lock is store._lock


STATE_NAMES = {"st", "state", "_state"}
OWNER_NAMES = STATE_NAMES | {"store", "_store"}


def leaf(node):
    """The last name of ``a.b.c`` / ``a``: what an attribute chain hangs on."""
    return node.attr if isinstance(node, ast.Attribute) else node.id if isinstance(node, ast.Name) else None


def state_field(node):
    """``node`` is ``<state>.<field>``, a subscript of one or something that hangs on one."""
    while isinstance(node, (ast.Subscript, ast.Attribute)):
        if isinstance(node, ast.Attribute) and leaf(node.value) in STATE_NAMES:
            return True
        node = node.value
    return False


def seam_breaches(source):
    """``(reach-ins, writes, imports)`` of a writer's source: ``._name`` on
    the store or the state; assignments, augmented assignments, deletions
    and mutating calls on a field of the shuffle's state; run-time imports
    of ``hbm_store``."""
    tree = ast.parse(source)
    reach_ins, writes, imports = [], [], []
    guarded = {id(n) for top in tree.body if isinstance(top, ast.If) and "TYPE_CHECKING" in ast.dump(top.test)
               for n in ast.walk(top)}
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr.startswith("_") and not node.attr.startswith("__"):
            if leaf(node.value) in OWNER_NAMES:
                reach_ins.append(node.attr)
        targets = []
        if isinstance(node, ast.Assign):
            targets = [t for target in node.targets for t in (target.elts if isinstance(target, ast.Tuple) else [target])]
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            targets = [node.target]
        elif isinstance(node, ast.Delete):
            targets = node.targets
        elif isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute) and node.func.attr in MUTATORS:
            targets = [node.func.value]
        writes += [ast.unparse(t) for t in targets if state_field(t)]
        if isinstance(node, (ast.Import, ast.ImportFrom)) and id(node) not in guarded and "hbm_store" in ast.dump(node):
            imports.append(node.lineno)
    return reach_ins, writes, imports


def test_the_writers_source_reaches_into_no_private_and_writes_no_state_field():
    source = Path(store_writer.__file__).read_text()
    assert seam_breaches(source) == ([], [], [])
    assert "self._state" in source and "store.take_extent(" in source  # the walk read the file it meant to


def test_the_walk_finds_what_the_parents_writer_did():
    """The same walk over the lines PR 55's ``MapWriter`` had: each is found."""
    planted = """
from sparkucx_tpu.store.hbm_store import HbmBlockStore
def close(self, st, store, peer, used, grow):
    with self._store._lock:
        store._charge_tenant(st, grow)
        st.region_used[peer] = used + grow
        st.inflight[0] = st.inflight.get(0, 0) + 1
        st.open_writers -= 1
        self._state.committed_maps.add(self.map_id)
        st.put_behind.open.add(self._resv)
        st.device_mode = False
        counters = self._store._write_stats
"""
    reach_ins, writes, imports = seam_breaches(planted)
    assert sorted(reach_ins) == ["_charge_tenant", "_lock", "_write_stats"]
    assert sorted(writes) == ["self._state.committed_maps", "st.device_mode", "st.inflight[0]", "st.open_writers",
                              "st.put_behind.open", "st.region_used[peer]"]
    assert imports == [2]


def python_calls(fn):
    """Names of the Python functions entered while ``fn`` runs, counted."""
    names = []

    def profile(frame, event, arg):
        if event == "call":
            names.append(frame.f_code.co_name)

    sys.setprofile(profile)
    try:
        fn()
    finally:
        sys.setprofile(None)
    return names


@pytest.mark.parametrize("path, calls", [
    ("buffered-one-writer-open", 17), ("buffered-two-writers-open", 20), ("in-place-frame", 14), ("in-place-close", 7),
])
def test_a_block_enters_as_many_python_functions_as_at_pr_55(path, calls):
    """The per-block path, counted: moving the three steps into the store
    added no call (the parent's tree counts the same under this walk), and a
    PR that raises a number here says what the block got for it — three PRs
    each added "one compare" and the 1k cell lost 5.7% (ROADMAP queue 1
    item 5(a))."""
    store = HbmBlockStore(TpuShuffleConf(staging_capacity_per_executor=1 << 20))  # no tenant: the default path
    store.create_shuffle(0, 4, 4)
    writer, data = store.map_writer(0, 0), payload(6, 1000)
    writer.write_partition(0, b"the staging's first touch")
    if path == "buffered-two-writers-open":
        store.map_writer(0, 1)
    if path.startswith("buffered"):
        names = python_calls(lambda: writer.write_partition(1, data))
        assert names.count("take_extent") == names.count("record_extent") == names.count("_copy_chunks") == 1
    else:
        writer.open_partition(1)

        def frame():
            writer.reserve(len(data))[:] = data
            writer.end_receive(len(data), True)

        names = python_calls(frame)
        if path == "in-place-close":
            names = python_calls(writer.close_partition)
    store.close()
    assert len(names) == calls, sorted(names)  # the test's own lambda / ``frame`` is one of them
