"""Analyzer + sanitizer tests (fast, no device work).

Each analysis pass gets three fixture snippets run through ``run_source``:
one that must flag, one that must stay clean, and one exercising the escape
hatch (allowlist / lock held / bucketing rebind / docstring contract).  The
fixtures are source STRINGS — they are parsed, never imported, so they can
reference modules that don't exist.

The sanitizer tests pin the documented lifecycle contracts: release is
idempotent in normal mode; sanitize mode raises on double-release,
use-after-release, and re-pooling with live exported views, and poisons
freed host buffers with 0xDD.
"""

import os
import textwrap

import numpy as np
import pytest

from sparkucx_tpu.analysis import is_allowlisted, run_source
from sparkucx_tpu.analysis.__main__ import main as analysis_main
from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.block import ShuffleBlockId
from sparkucx_tpu.memory.pool import MemoryPool
from sparkucx_tpu.memory.sanitizer import POISON, BufferSanitizer, SanitizerError
from sparkucx_tpu.shuffle.reader import BlockFetchResult


def src(text: str) -> str:
    return textwrap.dedent(text)


def messages(findings):
    return [f.message for f in findings]


# ----------------------------------------------------------------------
# use-after-donate


class TestUseAfterDonate:
    def test_flags_read_after_donating_call(self):
        findings = run_source(
            src(
                """
                def run(spec, buf):
                    fn = build_exchange(spec)
                    out = fn(buf)
                    return buf.sum() + out
                """
            ),
            passes=["use-after-donate"],
        )
        assert len(findings) == 1
        assert "buf" in findings[0].message
        assert "donated" in findings[0].message

    def test_flags_jit_donate_argnums(self):
        findings = run_source(
            src(
                """
                import jax

                def run(x):
                    g = jax.jit(step, donate_argnums=(0,))
                    y = g(x)
                    return x + y
                """
            ),
            passes=["use-after-donate"],
        )
        assert len(findings) == 1
        assert "x" in findings[0].message

    def test_rebind_revives_and_branches_merge(self):
        # rebinding the name after donation makes later reads legal; a read
        # that only happens on the non-donating branch is also legal
        findings = run_source(
            src(
                """
                def run(spec, buf, cond):
                    fn = build_exchange(spec)
                    buf = fn(buf)
                    return buf.sum()

                def branchy(spec, buf, cond):
                    fn = build_exchange(spec)
                    if cond:
                        fn(buf)
                    else:
                        pass
                    return buf
                """
            ),
            passes=["use-after-donate"],
        )
        # `return buf` after the If IS flagged (donated on one branch ->
        # may-donate is must-not-reuse), but `buf = fn(buf)` is not
        assert len(findings) == 1
        assert findings[0].line > 7

    def test_block_scatter_positional_donation(self):
        findings = run_source(
            src(
                """
                def run(b, out):
                    fn = build_block_scatter(1, 2, 3, 4)
                    fn(a, b, c, d, out)
                    return out
                """
            ),
            passes=["use-after-donate"],
        )
        assert len(findings) == 1
        assert "out" in findings[0].message

    def test_ici_exchange_donates_staging(self):
        """The scheduled-exchange builders (ops/ici_exchange.py) carry the
        same donation contracts as their stock counterparts: arg 0 of the
        plain exchange, the staging buffer (arg 4) of the fused send side."""
        findings = run_source(
            src(
                """
                def run(mesh, spec, data, sizes, staging):
                    fn = build_ici_exchange(mesh, spec)
                    fn(data, sizes)
                    fused = build_fused_ici_exchange(mesh, spec, 8)
                    fused(a, b, c, d, staging, sizes)
                    return data.sum() + staging.sum()
                """
            ),
            passes=["use-after-donate"],
        )
        assert len(findings) == 2
        assert any("data" in f.message for f in findings)
        assert any("staging" in f.message for f in findings)


# ----------------------------------------------------------------------
# lock-discipline


LOCK_FIXTURE = """
import threading

class Store:
    def __init__(self):
        self._lock = threading.Lock()
        self._other_lock = threading.Lock()
        self._items = []  #: guarded by self._lock

    def bad(self, x):
        self._items.append(x)

    def wrong_lock(self, x):
        with self._other_lock:
            self._items = [x]

    def good(self, x):
        with self._lock:
            self._items.append(x)

    def helper(self, x):
        \"\"\"Append one item; caller holds ``self._lock``.\"\"\"
        self._items.append(x)
"""


class TestLockDiscipline:
    def test_flags_unguarded_and_wrong_lock(self):
        findings = run_source(src(LOCK_FIXTURE), passes=["lock-discipline"])
        assert len(findings) == 2
        assert any("mutator call '.append()'" in m for m in messages(findings))
        assert any("_other_lock" in m for m in messages(findings))

    def test_init_and_caller_holds_exempt(self):
        findings = run_source(src(LOCK_FIXTURE), passes=["lock-discipline"])
        lines = {f.line for f in findings}
        # __init__ assignment (the annotation line) and the documented helper
        # must not be among the findings
        assert all(l < 20 for l in lines)

    def test_clean_without_annotations(self):
        findings = run_source(
            src(
                """
                class Free:
                    def mutate(self, x):
                        self.items.append(x)
                """
            ),
            passes=["lock-discipline"],
        )
        assert findings == []

    def test_flags_unguarded_credit_counter(self):
        # The striped-wire CreditGate pattern: a byte counter annotated as
        # guarded by a Condition named _lock.  Mutating it without the lock
        # (the augmented-assign form the accounting paths use) must flag;
        # the guarded twin must not.
        findings = run_source(
            src(
                """
                import threading

                class Gate:
                    def __init__(self, budget):
                        self.budget = budget
                        self._lock = threading.Condition()
                        self._used = 0  #: guarded by self._lock

                    def release_racy(self, n):
                        self._used -= n

                    def release(self, n):
                        with self._lock:
                            self._used -= n
                            self._lock.notify_all()
                """
            ),
            passes=["lock-discipline"],
        )
        assert len(findings) == 1
        assert "_used" in findings[0].message


# ----------------------------------------------------------------------
# host-sync


HOSTSYNC_FIXTURE = """
import numpy as np
from sparkucx_tpu.transport.pipeline import RoundPipeline

class Exchanger:
    def _submit(self, r):
        x = self._arrs[r]
        x.block_until_ready()
        return x

    def _drain(self, r, ticket):
        return np.asarray(ticket)

    def _helper(self, t):
        return jax.device_get(t)

    def _run_exchange(self, rounds):
        pipe = RoundPipeline(2, self._submit, self._drain, name="x")
        for r in range(rounds):
            self._helper(r)

    def unrelated(self, x):
        x.block_until_ready()
"""


class TestHostSync:
    def test_flags_stages_and_reachable_callees(self):
        findings = run_source(src(HOSTSYNC_FIXTURE), passes=["host-sync"])
        msgs = messages(findings)
        assert any("block_until_ready" in m and "submit stage" in m for m in msgs)
        assert any("np.asarray" in m and "drain stage" in m for m in msgs)
        assert any("device_get" in m and "via '_helper'" in m for m in msgs)
        # `unrelated` is not a stage and not reachable from _run_exchange
        assert not any("unrelated" in m for m in msgs)
        assert len(findings) == 3

    def test_literal_asarray_not_flagged(self):
        findings = run_source(
            src(
                """
                import numpy as np
                from sparkucx_tpu.transport.pipeline import RoundPipeline

                class E:
                    def _submit(self, r):
                        return np.asarray([0, 1, 2])

                    def _drain(self, r, t):
                        return t

                    def go(self):
                        RoundPipeline(2, self._submit, self._drain)
                """
            ),
            passes=["host-sync"],
        )
        assert findings == []

    def test_drain_findings_are_allowlistable_by_lane(self):
        findings = run_source(
            src(HOSTSYNC_FIXTURE), passes=["host-sync"], filename="transport/fix.py"
        )
        allow = {("transport/fix.py", "host-sync", "drain stage")}
        left = [f for f in findings if not is_allowlisted(f, allow)]
        # the drain-lane finding is suppressed; submit + root survive
        assert len(left) == 2
        assert all("drain stage" not in f.message for f in left)


# ----------------------------------------------------------------------
# cache-hygiene


class TestCacheHygiene:
    def test_flags_raw_shape_params_in_cache_key(self):
        findings = run_source(
            src(
                """
                class S:
                    def get(self, rows, width):
                        key = (rows, width)
                        if key not in self._scatter_cache:
                            self._scatter_cache[key] = build_thing(rows, width)
                        return self._scatter_cache[key]
                """
            ),
            passes=["cache-hygiene"],
        )
        msgs = messages(findings)
        assert any("'rows'" in m for m in msgs)
        assert any("'width'" in m for m in msgs)

    def test_bucketed_param_clean(self):
        findings = run_source(
            src(
                """
                class S:
                    def get(self, rows, width):
                        rows = round_up_to_next_power_of_two(rows)
                        width = bucket_send_rows(width)
                        key = (rows, width)
                        if key not in self._scatter_cache:
                            self._scatter_cache[key] = build_thing(rows, width)
                        return self._scatter_cache[key]
                """
            ),
            passes=["cache-hygiene"],
        )
        assert findings == []

    def test_skew_planner_rebind_counts_as_bucketed(self):
        """Shape params flowing through the skew planner (quota_slot_rows /
        plan_exchange, ops/skew.py) are pow2-bucketed by construction and
        must sanctify a cache key like bucket_send_rows does."""
        findings = run_source(
            src(
                """
                class S:
                    def get(self, rows, depth):
                        rows = quota_slot_rows(rows, self.conf.slot_quota_rows)
                        depth = plan_exchange([depth], depth, 0).slot_rows
                        key = (rows, depth)
                        if key not in self._exchange_cache:
                            self._exchange_cache[key] = build_thing(rows, depth)
                        return self._exchange_cache[key]
                """
            ),
            passes=["cache-hygiene"],
        )
        assert findings == []

    def test_ici_cache_raw_shape_key_flagged(self):
        """A compiled-schedule cache in front of build_ici_exchange keyed on
        raw send_rows is the same recompile bomb the exchange cache pass
        exists to catch — ISSUE 6's cache must stay pow2-bucketed."""
        findings = run_source(
            src(
                """
                class T:
                    def get(self, send_rows, chunks):
                        key = (send_rows, chunks)
                        if key not in self._ici_cache:
                            self._ici_cache[key] = build_ici_exchange(
                                self.mesh, make_spec(send_rows), chunks_per_dest=chunks
                            )
                        return self._ici_cache[key]
                """
            ),
            passes=["cache-hygiene"],
        )
        msgs = messages(findings)
        assert any("'send_rows'" in m for m in msgs)

    def test_ici_cache_bucketed_rebind_clean(self):
        """bucket_send_rows sanctifies the slot geometry and schedule_chunks
        (the pow2 chunk-count clamp, BUCKETING_MARKERS) sanctifies the chunk
        key — the shape the real transports put in front of the cache."""
        findings = run_source(
            src(
                """
                class T:
                    def get(self, send_rows, chunks):
                        send_rows = bucket_send_rows(send_rows, self.n)
                        chunks = schedule_chunks(send_rows // self.n, chunks)
                        key = (send_rows, chunks)
                        if key not in self._ici_cache:
                            self._ici_cache[key] = build_ici_exchange(
                                self.mesh, make_spec(send_rows), chunks_per_dest=chunks
                            )
                        return self._ici_cache[key]
                """
            ),
            passes=["cache-hygiene"],
        )
        assert findings == []

    def test_lru_cache_builder_flagged(self):
        findings = run_source(
            src(
                """
                import functools

                @functools.lru_cache(maxsize=None)
                def build_gather(num_blocks, dtype):
                    return num_blocks
                """
            ),
            passes=["cache-hygiene"],
        )
        assert len(findings) == 1
        assert "num_blocks" in findings[0].message
        assert "bucket" in findings[0].message


# ----------------------------------------------------------------------
# private-access / required-surface / allowlist mechanics


class TestPrivateAndSurface:
    def test_private_access_flagged_self_ok(self):
        findings = run_source(
            src(
                """
                def f(other):
                    return other._guts

                class C:
                    def g(self):
                        return self._mine
                """
            ),
            passes=["private-access"],
        )
        assert len(findings) == 1
        assert "._guts" in findings[0].message

    def test_required_surface_missing_method(self):
        findings = run_source(
            src(
                """
                class HbmBlockStore:
                    def register_shuffle(self):
                        pass
                """
            ),
            passes=["required-surface"],
            filename="store/hbm_store.py",
        )
        assert any("missing" in m for m in messages(findings))

    def test_allowlist_matching_is_narrow(self):
        findings = run_source(
            "def f(o):\n    return o._guts\n",
            passes=["private-access"],
            filename="transport/thing.py",
        )
        (f,) = findings
        assert is_allowlisted(f, {("transport/thing.py", "private-access", "._guts")})
        assert is_allowlisted(f, {("thing.py", "*", "._guts")})
        assert not is_allowlisted(f, {("other.py", "private-access", "._guts")})
        assert not is_allowlisted(f, {("thing.py", "lock-discipline", "._guts")})
        assert not is_allowlisted(f, {("thing.py", "private-access", "._other")})

    def test_no_file_of_the_store_is_excused_a_private_access(self):
        # PR 56: the map task's writer reaches the store through its methods;
        # ten rows for store/hbm_store.py went and none came for store/writer.py
        from sparkucx_tpu.analysis.config import ALLOWLIST, REQUIRED_SURFACE

        assert [row for row in ALLOWLIST if row[0].startswith("store/") and row[1] == "private-access"] == []
        assert "MapWriter" in REQUIRED_SURFACE["store/writer.py"]
        assert "MapWriter" not in REQUIRED_SURFACE["store/hbm_store.py"]


# ----------------------------------------------------------------------
# lock-order (whole-program pass)


class TestLockOrder:
    def test_flags_inverted_acquisition_order(self):
        findings = run_source(
            src(
                """
                class Store:
                    def fwd(self):
                        with self._lock:
                            with self._order_lock:
                                pass

                    def rev(self):
                        with self._order_lock:
                            with self._lock:
                                pass
                """
            ),
            passes=["lock-order"],
        )
        assert len(findings) == 1
        assert "lock-order cycle" in findings[0].message
        assert "Store._lock" in findings[0].message
        assert "Store._order_lock" in findings[0].message

    def test_flags_transitive_self_reacquire(self):
        # get() holds the lock and calls a helper that takes it again — the
        # classic non-reentrant-Lock deadlock, visible only through the call
        # summary, not lexically.
        findings = run_source(
            src(
                """
                class Pool:
                    def get(self):
                        with self._lock:
                            return self._allocate_more()

                    def _allocate_more(self):
                        with self._lock:
                            return 1
                """
            ),
            passes=["lock-order"],
        )
        assert len(findings) == 1
        assert "self-cycle" in findings[0].message
        assert "Pool._lock" in findings[0].message

    def test_flags_blocking_call_under_lock(self):
        findings = run_source(
            src(
                """
                class Tx:
                    def send(self, sock, data):
                        with self._lock:
                            sock.sendall(data)
                """
            ),
            passes=["lock-order"],
        )
        assert len(findings) == 1
        assert "blocking call 'sendall'" in findings[0].message
        assert "Tx._lock" in findings[0].message

    def test_consistent_order_clean(self):
        findings = run_source(
            src(
                """
                class Ok:
                    def a(self):
                        with self._lock:
                            with self._inner_lock:
                                pass

                    def b(self):
                        with self._lock:
                            x = compute()
                            with self._inner_lock:
                                use(x)
                """
            ),
            passes=["lock-order"],
        )
        assert findings == []

    def test_send_lock_exempt_from_blocking_check(self):
        # LOCK_BLOCKING_EXEMPT wildcards *.send_lock: serializing a blocking
        # frame write IS that lock's documented job.
        findings = run_source(
            src(
                """
                class Conn:
                    def write(self, sock, data):
                        with self.send_lock:
                            sock.sendall(data)
                """
            ),
            passes=["lock-order"],
        )
        assert findings == []

    def test_closure_lock_use_invisible(self):
        # Documented limit: a nested def's body runs later, on another
        # thread — its lock use must NOT count as the enclosing method's
        # (the pool.py recycle-closure shape that false-positived as a
        # self-cycle during development).
        findings = run_source(
            src(
                """
                class P:
                    def get(self):
                        with self._lock:
                            def recycle():
                                with self._lock:
                                    pass
                            return recycle
                """
            ),
            passes=["lock-order"],
        )
        assert findings == []

    def test_cross_object_edges_and_dot(self):
        import ast as ast_mod

        from sparkucx_tpu.analysis.base import Program
        from sparkucx_tpu.analysis.lockorder import build_lock_graph, render_dot

        srcs = {
            "transport/peer.py": src(
                """
                class PeerTransport:
                    def seal(self):
                        with self._tag_lock:
                            return self.store.num_rounds()
                """
            ),
            "store/hbm_store.py": src(
                """
                class HbmBlockStore:
                    def num_rounds(self):
                        with self._lock:
                            return 1
                """
            ),
        }
        program = Program(
            modules={k: (ast_mod.parse(v), v) for k, v in srcs.items()},
            docs={},
            tests_text="",
        )
        edges, blocking = build_lock_graph(program)
        # self.store.* resolves through LOCK_ATTR_CLASSES to HbmBlockStore
        assert ("PeerTransport._tag_lock", "HbmBlockStore._lock") in edges
        assert blocking == []
        dot = render_dot(edges)
        assert dot.startswith("digraph lock_order")
        assert '"PeerTransport._tag_lock" -> "HbmBlockStore._lock"' in dot


# ----------------------------------------------------------------------
# reactor-discipline


class TestReactorDiscipline:
    def test_loop_lane_flags_blocking_socket_op_via_chain(self):
        findings = run_source(
            src(
                """
                class Server:
                    def start(self, reactor):
                        reactor.add_listener(self._sock, self._on_accept)

                    def _on_accept(self):
                        self._drain()

                    def _drain(self):
                        return self._sock.recv(4096)
                """
            ),
            passes=["reactor-discipline"],
        )
        assert len(findings) == 1
        assert "blocking socket op 'recv'" in findings[0].message
        assert "loop" in findings[0].message
        assert "(via '_on_accept')" in findings[0].message

    def test_worker_lane_allows_reads_but_flags_join(self):
        findings = run_source(
            src(
                """
                class Conn:
                    def start(self, reactor):
                        reactor.add_connection(self, self._serve, on_close=self._closed)

                    def _serve(self):
                        return self._sock.recv(4096)

                    def _closed(self):
                        self._thread.join()
                """
            ),
            passes=["reactor-discipline"],
        )
        # blocking frame reads are the worker lane's documented design;
        # an untimed join can deadlock the pool against itself
        assert len(findings) == 1
        assert "'join()' without timeout" in findings[0].message
        assert "worker" in findings[0].message

    def test_escape_comment(self):
        findings = run_source(
            src(
                """
                class Server:
                    def start(self, reactor):
                        reactor.add_listener(self._sock, self._on_accept)

                    def _on_accept(self):
                        return self._sock.recv(4096)  #: reactor-ok
                """
            ),
            passes=["reactor-discipline"],
        )
        assert findings == []

    def test_module_without_registrations_ignored(self):
        findings = run_source(
            src(
                """
                class Plain:
                    def fetch(self):
                        return self._sock.recv(4096)
                """
            ),
            passes=["reactor-discipline"],
        )
        assert findings == []


# ----------------------------------------------------------------------
# thread-lifecycle


class TestThreadLifecycle:
    def test_flags_nondaemon_unjoined_thread(self):
        findings = run_source(
            src(
                """
                import threading

                def start(work):
                    t = threading.Thread(target=work)
                    t.start()
                    return t
                """
            ),
            passes=["thread-lifecycle"],
        )
        assert len(findings) == 1
        assert "never joined" in findings[0].message
        assert "'t'" in findings[0].message

    def test_daemon_joined_and_spawn_list_idioms_clean(self):
        findings = run_source(
            src(
                """
                import threading

                def daemonized(work):
                    t = threading.Thread(target=work, daemon=True)
                    t.start()

                def reaped(work):
                    t = threading.Thread(target=work)
                    t.start()
                    t.join()

                def harness(work, n):
                    threads = [threading.Thread(target=work) for _ in range(n)]
                    for t in threads:
                        t.start()
                    for t in threads:
                        t.join()
                """
            ),
            passes=["thread-lifecycle"],
        )
        assert findings == []

    def test_queue_bounds(self):
        findings = run_source(
            src(
                """
                import queue

                def make():
                    a = queue.Queue()
                    b = queue.Queue(maxsize=0)
                    c = queue.SimpleQueue()
                    good = queue.Queue(maxsize=64)
                    also_good = queue.Queue(8)
                    return a, b, c, good, also_good
                """
            ),
            passes=["thread-lifecycle"],
        )
        msgs = messages(findings)
        assert len(findings) == 3
        assert sum("without a positive maxsize" in m for m in msgs) == 2
        assert sum("SimpleQueue" in m for m in msgs) == 1

    def test_escape_comment(self):
        findings = run_source(
            src(
                """
                import threading

                def start(work):
                    t = threading.Thread(target=work)  #: lifecycle: joined by the harness teardown helper
                    t.start()
                    return t
                """
            ),
            passes=["thread-lifecycle"],
        )
        assert findings == []


# ----------------------------------------------------------------------
# resource-balance


class TestResourceBalance:
    def test_flags_unbalanced_acquire(self):
        findings = run_source(
            src(
                """
                class Reader:
                    def admit(self, n):
                        self._gate.acquire(n)
                        self.do_fetch(n)
                """
            ),
            passes=["resource-balance"],
        )
        assert len(findings) == 1
        assert "self._gate.acquire" in findings[0].message
        assert "exception paths" in findings[0].message

    def test_try_finally_sibling_and_enclosing_clean(self):
        findings = run_source(
            src(
                """
                class Reader:
                    def sibling(self, n):
                        self._gate.acquire(n)
                        try:
                            self.do_fetch(n)
                        finally:
                            self._gate.release(n)

                    def enclosing(self, n):
                        try:
                            self._gate.acquire(n)
                            self.do_fetch(n)
                        finally:
                            self._gate.release(n)

                    def handler(self, st, n):
                        try:
                            self.tenants.charge(st, n)
                            self.stage(st)
                        except Exception:
                            self.tenants.release(st, n)
                            raise
                """
            ),
            passes=["resource-balance"],
        )
        assert findings == []

    def test_lock_receivers_skipped(self):
        # lock.acquire() belongs to the lock passes, not resource balance
        findings = run_source(
            src(
                """
                class C:
                    def f(self):
                        self._lock.acquire()
                        self._cond.acquire()
                """
            ),
            passes=["resource-balance"],
        )
        assert findings == []

    def test_escape_comment_and_docstring_transfer(self):
        findings = run_source(
            src(
                """
                class Store:
                    def restage(self, st, n):
                        self._charge_tenant(st, n)  #: balanced by _release_tenant
                        self.promote(st)

                    def _charge_tenant(self, st, n):
                        \"\"\"Claim quota; released by ``_release_tenant`` on removal.\"\"\"
                        self.tenants.charge(st.app_id, n)
                """
            ),
            passes=["resource-balance"],
        )
        assert findings == []

    def test_wrong_release_name_in_comment_still_flags(self):
        findings = run_source(
            src(
                """
                class Store:
                    def restage(self, st, n):
                        self._charge_tenant(st, n)  #: balanced by something_else
                """
            ),
            passes=["resource-balance"],
        )
        assert len(findings) == 1


# ----------------------------------------------------------------------
# wire-schema (whole-program pass; docs injected through run_source)


WIRE_FIXTURE = """
import struct

class AmId:
    FETCH_REQ = 0
    FETCH_ACK = 1

_HDR = struct.Struct("<IQQ")
"""

WIRE_DOC_COMPLETE = (
    "| 0 | FetchReq | request |\n"
    "| 1 | FetchAck | reply |\n"
    "frame prefix is `<IQQ>` little-endian\n"
)


class TestWireSchema:
    def test_flags_undocumented_id_and_struct(self):
        findings = run_source(
            src(WIRE_FIXTURE),
            passes=["wire-schema"],
            docs={"SHIM_PROTOCOL.md": "| 0 | FetchReq | request |\n"},
        )
        msgs = messages(findings)
        assert len(findings) == 2
        assert any("FETCH_ACK=1" in m and "FetchAck" in m for m in msgs)
        assert any("_HDR" in m and "<IQQ" in m for m in msgs)

    def test_complete_doc_clean(self):
        findings = run_source(
            src(WIRE_FIXTURE),
            passes=["wire-schema"],
            docs={"SHIM_PROTOCOL.md": WIRE_DOC_COMPLETE},
        )
        assert findings == []

    def test_duplicate_and_gap_values_flagged_without_doc(self):
        dup = run_source(
            src(
                """
                class AmId:
                    A = 0
                    B = 0
                """
            ),
            passes=["wire-schema"],
        )
        assert len(dup) == 1 and "duplicate values" in dup[0].message
        gap = run_source(
            src(
                """
                class AmId:
                    A = 0
                    B = 2
                """
            ),
            passes=["wire-schema"],
        )
        assert len(gap) == 1 and "not contiguous" in gap[0].message

    def test_doc_checks_skipped_without_doc(self):
        # installed-package runs have no docs/; the shape checks still run
        findings = run_source(src(WIRE_FIXTURE), passes=["wire-schema"])
        assert findings == []

    def test_extractors_roundtrip(self):
        from sparkucx_tpu.analysis.protocol import camel, extract_am_ids, extract_structs

        assert extract_am_ids(src(WIRE_FIXTURE)) == {"FETCH_REQ": 0, "FETCH_ACK": 1}
        assert extract_structs(src(WIRE_FIXTURE)) == {"_HDR": "<IQQ"}
        assert camel("REPLICA_PUT") == "ReplicaPut"
        assert camel("MEMBER_SUSPECT") == "MemberSuspect"


# ----------------------------------------------------------------------
# conf-registry (whole-program pass; docs + tests text injected)


CONF_FIXTURE = """
class Conf:
    alpha: int = 0
    beta: bool = False

    @classmethod
    def from_spark_conf(cls, conf):
        out = cls()
        for name, attr, conv in [
            ("alpha", "alpha", int),
            ("beta.enabled", "beta", bool),
            ("gamma", "gamma_typo", int),
        ]:
            pass
        return out
"""


class TestConfRegistry:
    def test_flags_typo_field_missing_doc_and_missing_test(self):
        findings = run_source(
            src(CONF_FIXTURE),
            passes=["conf-registry"],
            docs={"DEPLOYMENT.md": "| `spark.shuffle.tpu.alpha` | 0 | the alpha |\n"},
            tests_text="conf.alpha == 3",
        )
        msgs = messages(findings)
        assert any("unknown conf field 'gamma_typo'" in m for m in msgs)
        assert any("'spark.shuffle.tpu.beta.enabled' has no DEPLOYMENT.md row" in m for m in msgs)
        assert any("'spark.shuffle.tpu.beta.enabled'" in m and "no test" in m for m in msgs)
        assert not any("alpha" in m and "no test" in m for m in msgs)

    def test_fully_registered_clean(self):
        findings = run_source(
            src(
                """
                class Conf:
                    alpha: int = 0

                    @classmethod
                    def from_spark_conf(cls, conf):
                        out = cls()
                        for name, attr, conv in [("alpha", "alpha", int)]:
                            pass
                        return out
                """
            ),
            passes=["conf-registry"],
            docs={"DEPLOYMENT.md": "| `spark.shuffle.tpu.alpha` | 0 | the alpha |\n"},
            tests_text="spark.shuffle.tpu.alpha",
        )
        assert findings == []

    def test_off_path_default_drift_flagged(self):
        # `elastic` is pinned False in OFF_PATH_DEFAULTS: a fixture class
        # defaulting it True is exactly the flipped-default drift the pass
        # exists to catch
        findings = run_source(
            src(
                """
                class Conf:
                    elastic: bool = True

                    @classmethod
                    def from_spark_conf(cls, conf):
                        return cls()
                """
            ),
            passes=["conf-registry"],
        )
        assert len(findings) == 1
        assert "off-path default drift" in findings[0].message
        assert "'elastic'" in findings[0].message

    def test_fixture_subset_no_stale_pin_noise(self):
        # only the real config.py owes every pinned field; a fixture class
        # defining one knob must not spray "stale pin" findings
        findings = run_source(
            src(
                """
                class Conf:
                    alpha: int = 0

                    @classmethod
                    def from_spark_conf(cls, conf):
                        return cls()
                """
            ),
            passes=["conf-registry"],
        )
        assert findings == []


# ----------------------------------------------------------------------
# lockstep-taint


class TestLockstepTaint:
    def test_flags_direct_telemetry_into_collective_field(self):
        findings = run_source(
            src(
                """
                def plan(registry, plan):
                    snap = registry.snapshot()
                    return replace(plan, chunks_per_round=snap["depth"])
                """
            ),
            passes=["lockstep-taint"],
        )
        assert len(findings) == 1
        assert "chunks_per_round" in findings[0].message
        assert "local telemetry" in findings[0].message

    def test_flags_transitive_helper_flow(self):
        # the satellite-required case: telemetry flows through a module
        # helper before reaching chunks_per_round
        findings = run_source(
            src(
                """
                def _derive(stall_ns):
                    return 2 if stall_ns > 1000 else 8

                def plan(registry, plan):
                    snap = registry.snapshot()
                    depth = _derive(snap["rx_stall_p99_ns"])
                    return replace(plan, chunks_per_round=depth)
                """
            ),
            passes=["lockstep-taint"],
        )
        assert len(findings) == 1
        assert "chunks_per_round" in findings[0].message

    def test_flags_closure_helper_flow(self):
        # nested def capturing tainted state from the enclosing scope
        findings = run_source(
            src(
                """
                def plan(registry, plan):
                    snap = registry.snapshot()

                    def pick():
                        return snap["depth"] + 1

                    return replace(plan, chunks_per_round=pick())
                """
            ),
            passes=["lockstep-taint"],
        )
        assert len(findings) == 1
        assert "chunks_per_round" in findings[0].message

    def test_flags_collective_rewrite_under_tainted_branch(self):
        # implicit flow: the VALUE is a constant but the rewrite only
        # happens on hosts whose local telemetry crossed a threshold
        findings = run_source(
            src(
                """
                def plan(registry, plan):
                    sig = registry.snapshot()
                    if sig["padding"] > 0.5:
                        plan = replace(plan, chunks_per_round=4)
                    return plan
                """
            ),
            passes=["lockstep-taint"],
        )
        assert len(findings) == 1
        assert "telemetry-tainted branch" in findings[0].message

    def test_serve_plane_steering_clean(self):
        # the satellite-required clean fixture: telemetry may steer
        # hedge_ms/streams freely (serve-plane), and the resulting plan
        # object stays clean (absorption)
        findings = run_source(
            src(
                """
                def plan(registry, plan):
                    sig = registry.snapshot()
                    hedge = 5 if sig["rx_stall_p99_ns"] else 0
                    plan = replace(plan, hedge_ms=hedge)
                    if sig["credit_stall_ns"]:
                        plan = replace(plan, streams=2)
                    return replace(plan, chunks_per_round=8)
                """
            ),
            passes=["lockstep-taint"],
        )
        assert findings == []

    def test_conf_and_geometry_clean(self):
        # conf fields and all-gathered geometry are the sanctioned inputs
        findings = run_source(
            src(
                """
                def plan(conf, gathered_rows, plan):
                    rows = int(gathered_rows.max())
                    return replace(
                        plan,
                        chunks_per_round=conf.exchange_chunks_per_round,
                        slot_rows=rows,
                        lowering=conf.exchange_impl,
                    )
                """
            ),
            passes=["lockstep-taint"],
        )
        assert findings == []

    def test_escape_comment(self):
        findings = run_source(
            src(
                """
                def plan(registry, plan):
                    snap = registry.snapshot()
                    return replace(plan, chunks_per_round=snap["d"])  #: lockstep-ok reviewed
                """
            ),
            passes=["lockstep-taint"],
        )
        assert findings == []

    def test_precollective_branch_flagged_raise_exempt(self):
        findings = run_source(
            src(
                """
                def run_exchange(self):
                    snap = self.membership.snapshot()
                    if snap["dead"]:
                        raise RuntimeError("executor lost")
                    if snap["slow"]:
                        self.use_degraded_schedule()
                    self.collective()
                """
            ),
            passes=["lockstep-taint"],
        )
        assert len(findings) == 1
        assert "pre-collective branch" in findings[0].message
        assert findings[0].line == 6  # the schedule branch, not the raise

    def test_registry_partitions_exchange_plan(self):
        # acceptance criterion: COLLECTIVE_FIELDS == ExchangePlan fields
        # minus the declared serve-plane fields, with no overlap
        import dataclasses

        from sparkucx_tpu.analysis.config import (
            COLLECTIVE_FIELDS,
            SERVE_PLANE_FIELDS,
        )
        from sparkucx_tpu.ops.skew import ExchangePlan

        fields = {f.name for f in dataclasses.fields(ExchangePlan)}
        assert set(COLLECTIVE_FIELDS) | set(SERVE_PLANE_FIELDS) == fields
        assert not set(COLLECTIVE_FIELDS) & set(SERVE_PLANE_FIELDS)
        assert set(COLLECTIVE_FIELDS) == fields - set(SERVE_PLANE_FIELDS)

    def test_registry_drift_flagged(self):
        # a plan field the registry never classified must fail the run —
        # the fixture poses as ops/skew.py so the dataclass cross-check fires
        findings = run_source(
            src(
                """
                class ExchangePlan:
                    slot_rows: int
                    chunks_per_round: int
                    single_shot: bool
                    round_order: tuple
                    lowering: str
                    pipeline_depth: int
                    streams: int
                    codec: str
                    quantize_mode: str
                    quantize_block: int
                    hedge_ms: int
                    combine: str
                    mystery_knob: int
                """
            ),
            passes=["lockstep-taint"],
            filename="ops/skew.py",
        )
        assert len(findings) == 1
        assert "mystery_knob" in findings[0].message
        assert "neither COLLECTIVE_FIELDS nor SERVE_PLANE_FIELDS" in findings[0].message

    def test_real_planner_and_spmd_transport_pass(self, capsys):
        # the real AdaptivePlanner steers serve-plane fields from telemetry
        # and the SPMD transport fail-fasts on membership — both must be
        # clean under the pass (acceptance criterion)
        assert analysis_main(["--ci", "--passes", "lockstep-taint"]) == 0
        assert capsys.readouterr().out == ""

    def test_injected_regression_in_real_planner_caught(self):
        # mutate the REAL planner source: steering chunks_per_round from
        # PlanSignals telemetry must flag, at the mutated line — proving
        # the pass guards the actual code, not just toy fixtures
        import sparkucx_tpu.ops.planner as planner_mod

        src = open(planner_mod.__file__).read()
        needle = "plan = dataclasses.replace(plan, hedge_ms=hedge)"
        assert needle in src  # the serve-plane hedge steer in AdaptivePlanner
        mutated = src.replace(
            needle,
            "plan = dataclasses.replace(plan, hedge_ms=hedge, "
            "chunks_per_round=(1 + int(sig.rx_stall_p99_ns > 0),))",
        )
        findings = run_source(
            mutated, passes=["lockstep-taint"], filename="ops/planner.py"
        )
        assert len(findings) == 1
        assert "chunks_per_round" in findings[0].message
        # implicit flow too: widening a serve-plane rewrite that sits under
        # a telemetry branch with a collective field
        mutated2 = src.replace(
            'plan = dataclasses.replace(plan, codec="off")',
            'plan = dataclasses.replace(plan, codec="off", single_shot=True)',
        )
        assert mutated2 != src
        findings2 = run_source(
            mutated2, passes=["lockstep-taint"], filename="ops/planner.py"
        )
        assert len(findings2) == 1
        assert "single_shot" in findings2[0].message
        assert "telemetry-tainted branch" in findings2[0].message


# ----------------------------------------------------------------------
# span-discipline


class TestSpanDiscipline:
    def test_flags_discarded_span(self):
        findings = run_source(
            src(
                """
                def serve(tracer):
                    tracer.start_span("server.serve")
                """
            ),
            passes=["span-discipline"],
        )
        assert len(findings) == 1
        assert "discarded" in findings[0].message

    def test_flags_span_not_closed_in_finally(self):
        findings = run_source(
            src(
                """
                def serve(tracer):
                    ctx = tracer.start_span("server.serve")
                    do_work()
                    tracer.end_span(ctx)
                """
            ),
            passes=["span-discipline"],
        )
        assert len(findings) == 1
        assert "closed on all paths" in findings[0].message

    def test_finally_closed_clean(self):
        findings = run_source(
            src(
                """
                def serve(tracer):
                    ctx = tracer.start_span("server.serve")
                    try:
                        do_work()
                    finally:
                        tracer.end_span(ctx)
                """
            ),
            passes=["span-discipline"],
        )
        assert findings == []

    def test_handoff_requires_docstring(self):
        flagged = run_source(
            src(
                """
                def open_window(tracer):
                    return tracer.start_span("read.window")
                """
            ),
            passes=["span-discipline"],
        )
        assert len(flagged) == 1
        assert "docstring" in flagged[0].message
        clean = run_source(
            src(
                '''
                def open_window(tracer):
                    """Open the window span; ended by close_window."""
                    return tracer.start_span("read.window")
                '''
            ),
            passes=["span-discipline"],
        )
        assert clean == []

    def test_instant_names_checked_against_doc(self):
        doc = {"OBSERVABILITY.md": "| `exchange.plan` | planner resolved |"}
        flagged = run_source(
            src(
                """
                def f():
                    instant("exchange.bogus")
                """
            ),
            passes=["span-discipline"],
            docs=doc,
        )
        assert len(flagged) == 1
        assert "exchange.bogus" in flagged[0].message
        clean = run_source(
            src(
                """
                def f():
                    instant("exchange.plan")
                """
            ),
            passes=["span-discipline"],
            docs=doc,
        )
        assert clean == []

    def test_escape_comment(self):
        findings = run_source(
            src(
                """
                def serve(tracer):
                    tracer.start_span("fire.and.forget")  #: span-ok sampled externally
                """
            ),
            passes=["span-discipline"],
        )
        assert findings == []


# ----------------------------------------------------------------------
# metrics-naming


class TestMetricsNaming:
    DOC = {"OBSERVABILITY.md": "| `ops` | stats |\n| `wire` | lanes |\n"}

    def test_flags_bad_family_and_name(self):
        findings = run_source(
            src(
                """
                def provide():
                    return [sample("Bad-Family", "x", 1)]
                """
            ),
            passes=["metrics-naming"],
        )
        assert any("Bad-Family" in m for m in messages(findings))
        findings = run_source(
            src(
                """
                def provide():
                    return [sample("ops", "camelCase", 1)]
                """
            ),
            passes=["metrics-naming"],
        )
        assert any("snake_case" in m for m in messages(findings))

    def test_undocumented_family_flagged(self):
        findings = run_source(
            src(
                """
                def provide():
                    return [sample("ghost", "x_total", 1)]
                """
            ),
            passes=["metrics-naming"],
            docs=self.DOC,
        )
        assert any(
            "ghost" in m and "no row" in m for m in messages(findings)
        )

    def test_documented_families_clean_and_stale_row_flagged(self):
        findings = run_source(
            src(
                """
                def wire_up(reg):
                    reg.register("ops", counter_dict_provider("ops", get))
                    return sample("wire", "tx_bytes_total", 1)
                """
            ),
            passes=["metrics-naming"],
            docs=self.DOC,
        )
        assert findings == []
        # drop the wire registration: its doc row is now stale
        findings = run_source(
            src(
                """
                def wire_up(reg):
                    reg.register("ops", counter_dict_provider("ops", get))
                """
            ),
            passes=["metrics-naming"],
            docs=self.DOC,
        )
        assert any(
            "wire" in m and "stale" in m for m in messages(findings)
        )

    def test_escape_comment(self):
        findings = run_source(
            src(
                """
                def provide():
                    return [sample("Legacy-Fam", "x", 1)]  #: metric-ok grandfathered
                """
            ),
            passes=["metrics-naming"],
        )
        assert findings == []


# ----------------------------------------------------------------------
# error-taxonomy


class TestErrorTaxonomy:
    API = {"API.md": "BlockNotFoundError UnknownTenantError retryable fail-fast"}

    def test_retry_path_catching_fail_fast_flagged(self):
        findings = run_source(
            src(
                """
                def _retry_fetch(self):
                    try:
                        fetch()
                    except ExecutorLostError:
                        pass
                """
            ),
            passes=["error-taxonomy"],
        )
        assert len(findings) == 1
        assert "ExecutorLostError" in findings[0].message
        assert "fail-fast" in findings[0].message

    def test_broad_catch_without_guards_flagged(self):
        findings = run_source(
            src(
                """
                def _retry_fetch(self):
                    try:
                        fetch()
                    except TransportError:
                        pass
                """
            ),
            passes=["error-taxonomy"],
        )
        assert len(findings) == 1
        assert "silently retried" in findings[0].message

    def test_broad_catch_with_tuple_guard_clean(self):
        # the reader idiom: one module-level fail-fast tuple, isinstance +
        # re-raise inside the broad handler
        findings = run_source(
            src(
                """
                _FF = (TenantQuotaExceededError, UnknownTenantError, ExecutorLostError, SplitBlockError)

                def _retry_fetch(self):
                    try:
                        fetch()
                    except TransportError as e:
                        if isinstance(e, _FF):
                            raise
                """
            ),
            passes=["error-taxonomy"],
        )
        assert findings == []

    def test_unclassified_subclass_flagged(self):
        findings = run_source(
            src(
                """
                class TransportError(RuntimeError):
                    pass

                class NewFangledError(TransportError):
                    pass
                """
            ),
            passes=["error-taxonomy"],
            filename="core/operation.py",
        )
        assert any(
            "NewFangledError" in m and "not classified" in m
            for m in messages(findings)
        )

    def test_stale_taxonomy_entry_flagged(self):
        # a registry entry whose class was deleted must fail
        findings = run_source(
            src(
                """
                class TransportError(RuntimeError):
                    pass
                """
            ),
            passes=["error-taxonomy"],
            filename="core/operation.py",
        )
        assert any("stale registry entry" in m for m in messages(findings))

    def test_escape_comment(self):
        findings = run_source(
            src(
                """
                def _retry_fetch(self):
                    try:
                        fetch()
                    except ExecutorLostError:  #: taxonomy-ok reviewed special case
                        pass
                """
            ),
            passes=["error-taxonomy"],
        )
        assert findings == []

    def test_real_taxonomy_classifies_every_subclass(self, capsys):
        assert analysis_main(["--ci", "--passes", "error-taxonomy"]) == 0
        assert capsys.readouterr().out == ""


# ----------------------------------------------------------------------
# tier-vocabulary


class TestTierVocabulary:
    def test_flags_drifted_compare_literal(self):
        findings = run_source(
            src(
                """
                def pick(conf):
                    if conf.quantize_mode == "bf16":
                        return fancy()
                """
            ),
            passes=["tier-vocabulary"],
        )
        assert len(findings) == 1
        assert "'bf16'" in findings[0].message

    def test_flags_drifted_keyword_and_membership(self):
        findings = run_source(
            src(
                """
                def build(plan):
                    if plan.codec in ("off", "zstd"):
                        return None
                    return compile_exchange(lowering="fast")
                """
            ),
            passes=["tier-vocabulary"],
        )
        assert len(messages(findings)) == 2
        assert any("'zstd'" in m for m in messages(findings))
        assert any("'fast'" in m for m in messages(findings))

    def test_vocabulary_literals_clean(self):
        findings = run_source(
            src(
                """
                def pick(conf, plan):
                    lowering = "stock"
                    if conf.exchange_impl in ("pallas", "auto"):
                        lowering = "pallas"
                    return replace(plan, lowering=lowering, combine="sorted")
                """
            ),
            passes=["tier-vocabulary"],
        )
        assert findings == []

    def test_escape_comment(self):
        findings = run_source(
            src(
                """
                def pick(conf):
                    return conf.codec == "experimental"  #: tier-ok staged rollout
                """
            ),
            passes=["tier-vocabulary"],
        )
        assert findings == []

    def test_doc_vocabulary_enumerated(self):
        # a doc missing a documented knob's tier value must flag
        findings = run_source(
            "x = 1\n",
            passes=["tier-vocabulary"],
            docs={"DEPLOYMENT.md": "| `quantize.mode` | off | `int8` only |"},
        )
        assert any("blockfloat" in m for m in messages(findings))


# ----------------------------------------------------------------------
# CLI


class TestCli:
    def test_ci_clean_at_head(self, capsys):
        assert analysis_main(["--ci"]) == 0
        assert capsys.readouterr().out == ""

    def test_injected_violation_fails_with_file_line(self, tmp_path, capsys):
        bad = tmp_path / "leaky.py"
        bad.write_text(
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._q = []  #: guarded by self._lock\n"
            "    def leak(self, x):\n"
            "        self._q.append(x)\n"
        )
        assert analysis_main(["--ci", "--root", str(tmp_path)]) == 1
        out = capsys.readouterr().out
        assert "leaky.py:7" in out
        assert "[lock-discipline]" in out

    def test_unknown_pass_rejected(self, capsys):
        assert analysis_main(["--passes", "nope"]) == 2

    def test_list_passes(self, capsys):
        assert analysis_main(["--list-passes"]) == 0
        out = capsys.readouterr().out.split()
        for name in (
            "use-after-donate",
            "lock-discipline",
            "host-sync",
            "cache-hygiene",
            "private-access",
            "required-surface",
            "lock-order",
            "reactor-discipline",
            "thread-lifecycle",
            "resource-balance",
            "wire-schema",
            "conf-registry",
            "lockstep-taint",
            "span-discipline",
            "metrics-naming",
            "error-taxonomy",
            "tier-vocabulary",
        ):
            assert name in out

    def test_stale_allowlist_entry_fails_full_run(self, capsys, monkeypatch):
        import sparkucx_tpu.analysis.__main__ as cli

        stale = ("no/such_file.py", "lock-discipline", "never-matches-anything")
        monkeypatch.setattr(cli, "ALLOWLIST", cli.ALLOWLIST | {stale})
        assert analysis_main([]) == 1
        err = capsys.readouterr().err
        assert "stale allowlist entry" in err
        assert "never-matches-anything" in err

    def test_stale_builder_table_entry_fails_full_run(self, capsys, monkeypatch):
        # PR 10 policy extended to the function-pinning tables: a donation
        # entry for a deleted builder (the PR 13 `_run_exchange_quota`
        # cleanup) must fail the default run, not silently match nothing
        import sparkucx_tpu.analysis.__main__ as cli

        monkeypatch.setattr(
            cli,
            "DONATING_BUILDERS",
            {**cli.DONATING_BUILDERS, "_run_exchange_quota": (0,)},
        )
        assert analysis_main([]) == 1
        err = capsys.readouterr().err
        assert "stale DONATING_BUILDERS entry" in err
        assert "_run_exchange_quota" in err

    def test_stale_host_sync_root_fails_full_run(self, capsys, monkeypatch):
        import sparkucx_tpu.analysis.__main__ as cli

        monkeypatch.setattr(
            cli, "HOST_SYNC_ROOTS", cli.HOST_SYNC_ROOTS + ("_assemble",)
        )
        assert analysis_main([]) == 1
        err = capsys.readouterr().err
        assert "stale HOST_SYNC_ROOTS entry" in err
        assert "_assemble" in err

    def test_dump_lock_graph(self, capsys):
        assert analysis_main(["--dump-lock-graph"]) == 0
        out = capsys.readouterr().out
        assert out.startswith("digraph lock_order")
        # the store lock nests inside the transport's tag lock, and the
        # tenant registry lock inside the store lock — the documented chain
        assert '"HbmBlockStore._lock" -> "TenantRegistry._lock"' in out

    def test_tests_tree_private_access_clean(self):
        from sparkucx_tpu.analysis.base import repo_root

        tests_dir = os.path.join(repo_root(), "tests")
        assert analysis_main(
            ["--ci", "--root", tests_dir, "--passes", "private-access",
             "--allowlist", "tests"]
        ) == 0


# ----------------------------------------------------------------------
# runtime buffer sanitizer


@pytest.fixture
def sane_pool():
    pool = MemoryPool(TpuShuffleConf(sanitize=True))
    yield pool
    try:
        pool.close()
    except ResourceWarning:
        pass


class TestSanitizer:
    def test_conf_knob(self):
        assert MemoryPool(TpuShuffleConf()).sanitizer.enabled is False
        assert MemoryPool(TpuShuffleConf(sanitize=True)).sanitizer.enabled is True
        conf = TpuShuffleConf.from_spark_conf({"spark.shuffle.tpu.sanitize": "true"})
        assert conf.sanitize is True

    def test_double_release_raises(self, sane_pool):
        mb = sane_pool.get(100)
        mb.close()
        with pytest.raises(SanitizerError, match="double release"):
            mb.close()

    def test_normal_mode_release_idempotent(self):
        pool = MemoryPool(TpuShuffleConf())
        mb = pool.get(100)
        mb.close()
        mb.close()  # documented no-op
        pool.close()

    def test_freed_buffer_poisoned(self, sane_pool):
        mb = sane_pool.get(64)
        mb.host_view()[:] = 7
        backing = mb.data
        mb.close()
        assert (np.asarray(backing).reshape(-1).view(np.uint8) == POISON).all()
        assert sane_pool.sanitizer.stats()["poisoned_bytes"] > 0

    def test_use_after_release_raises(self, sane_pool):
        mb = sane_pool.get(32)
        r = BlockFetchResult(
            ShuffleBlockId(1, 2, 3),
            memoryview(mb.host_view()),
            mb,
            pooled=True,
            sanitizer=sane_pool.sanitizer,
        )
        r.release()
        with pytest.raises(SanitizerError, match="use-after-release"):
            r.data
        # detach/release stay idempotent even in sanitize mode: the fetch
        # iterator's `finally: prev.detach()` safety net relies on it
        r.detach()
        r.release()

    def test_repool_with_live_view_raises_then_recovers(self, sane_pool):
        mb = sane_pool.get(32)
        r = BlockFetchResult(
            ShuffleBlockId(1, 2, 3),
            memoryview(mb.host_view()),
            mb,
            pooled=True,
            sanitizer=sane_pool.sanitizer,
        )
        with pytest.raises(SanitizerError, match="live exported view"):
            mb.close()
        # the failed close leaves the handle checked out; the legitimate
        # release path (view first, then buffer) still works
        r.release()

    def test_detach_keeps_data_valid(self, sane_pool):
        mb = sane_pool.get(8)
        mb.host_view()[:] = 42
        view = memoryview(mb.host_view()[: mb.size])
        r = BlockFetchResult(
            ShuffleBlockId(0, 0, 0), view, mb, pooled=True,
            sanitizer=sane_pool.sanitizer,
        )
        r.detach()
        assert bytes(r.data)[:4] == b"\x2a\x2a\x2a\x2a"

    def test_disabled_sanitizer_is_noop(self):
        san = BufferSanitizer(enabled=False)
        san.on_checkout(object())
        san.on_double_release(object())
        san.check_view_released("anything")
        assert san.stats()["checkouts"] == 0
