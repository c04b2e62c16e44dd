"""Tests for the distributed sample sort (device-resident TeraSort core)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from sparkucx_tpu.ops.exchange import make_mesh
from sparkucx_tpu.ops.sort import KEY_MAX, SortSpec, build_distributed_sort, oracle_sort

N = 8
CAP = 256
W = 4


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(N)


@pytest.fixture(scope="module")
def fn(mesh):
    spec = SortSpec(
        num_executors=N,
        capacity=CAP,
        recv_capacity=2 * CAP,
        width=W,
        samples_per_shard=64,
        impl="dense",
    )
    return build_distributed_sort(mesh, spec)


def _place(mesh, keys, payload, nvalid):
    return (
        jax.device_put(keys, NamedSharding(mesh, P("ex"))),
        jax.device_put(payload, NamedSharding(mesh, P("ex", None))),
        jax.device_put(nvalid, NamedSharding(mesh, P("ex"))),
    )


def _collect(fn, mesh, keys, payload, nvalid):
    ko, po, cnt = fn(*_place(mesh, keys, payload, nvalid))
    ko = np.asarray(ko).reshape(N, -1)
    po = np.asarray(po).reshape(N, ko.shape[1], -1)
    cnt = np.asarray(cnt)
    got_k = np.concatenate([ko[j, : cnt[j]] for j in range(N)])
    got_p = np.concatenate([po[j, : cnt[j]] for j in range(N)])
    return got_k, got_p, cnt


class TestDistributedSort:
    def test_full_shards_unique_keys(self, fn, mesh, rng):
        keys = rng.permutation(N * CAP).astype(np.uint32)
        payload = keys[:, None].astype(np.int32) * np.arange(1, W + 1, dtype=np.int32)
        nvalid = np.full(N, CAP, np.int32)
        got_k, got_p, cnt = _collect(fn, mesh, keys, payload, nvalid)
        want_k, want_p = oracle_sort(keys, payload)
        assert cnt.sum() == N * CAP
        np.testing.assert_array_equal(got_k, want_k)
        np.testing.assert_array_equal(got_p, want_p)

    def test_ragged_shards_with_padding(self, fn, mesh, rng):
        nvalid = rng.integers(0, CAP + 1, size=N).astype(np.int32)
        nvalid[3] = 0  # empty shard
        keys = np.full(N * CAP, KEY_MAX, dtype=np.uint32)
        payload = np.zeros((N * CAP, W), np.int32)
        real = []
        for j in range(N):
            ks = rng.integers(0, 2**32 - 1, size=nvalid[j], dtype=np.uint64).astype(np.uint32)
            keys[j * CAP : j * CAP + nvalid[j]] = ks
            payload[j * CAP : j * CAP + nvalid[j], 0] = np.arange(nvalid[j])
            real.append(ks)
        got_k, _, cnt = _collect(fn, mesh, keys, payload, nvalid)
        want = np.sort(np.concatenate(real))
        assert cnt.sum() == nvalid.sum()
        np.testing.assert_array_equal(got_k, want)

    def test_duplicate_keys_multiset_preserved(self, fn, mesh, rng):
        keys = rng.integers(0, 7, size=N * CAP, dtype=np.uint64).astype(np.uint32)
        payload = rng.integers(0, 2**31 - 1, size=(N * CAP, W), dtype=np.int64).astype(np.int32)
        nvalid = np.full(N, CAP, np.int32)
        got_k, got_p, cnt = _collect(fn, mesh, keys, payload, nvalid)
        assert cnt.sum() == N * CAP
        np.testing.assert_array_equal(got_k, np.sort(keys))
        # payload rows survive as a multiset, attached to the right key
        want_rows = sorted(map(tuple, np.concatenate([keys[:, None].astype(np.int64), payload], axis=1)))
        got_rows = sorted(map(tuple, np.concatenate([got_k[:, None].astype(np.int64), got_p], axis=1)))
        assert got_rows == want_rows

    def test_shards_are_contiguous_ranges(self, fn, mesh, rng):
        keys = rng.integers(0, 2**32 - 1, size=N * CAP, dtype=np.uint64).astype(np.uint32)
        payload = np.zeros((N * CAP, W), np.int32)
        nvalid = np.full(N, CAP, np.int32)
        ko, _, cnt = fn(*_place(mesh, keys, payload, nvalid))
        ko = np.asarray(ko).reshape(N, -1)
        cnt = np.asarray(cnt)
        hi = np.uint64(0)
        for j in range(N):
            shard = ko[j, : cnt[j]]
            if len(shard) == 0:
                continue
            assert np.all(np.diff(shard.astype(np.int64)) >= 0)  # sorted within shard
            assert np.uint64(shard[0]) >= hi  # ranges ascend across shards
            hi = np.uint64(shard[-1])

    def test_skewed_keys_balanced_by_sampling(self, fn, mesh, rng):
        # all keys in a narrow band: splitters adapt, nothing overflows 2x headroom
        keys = rng.integers(1000, 1100, size=N * CAP, dtype=np.uint64).astype(np.uint32)
        payload = np.zeros((N * CAP, W), np.int32)
        nvalid = np.full(N, CAP, np.int32)
        got_k, _, cnt = _collect(fn, mesh, keys, payload, nvalid)
        assert np.all(cnt <= 2 * CAP)
        np.testing.assert_array_equal(got_k, np.sort(keys))

    def test_valid_rows_with_sentinel_key(self, fn, mesh, rng):
        # Valid rows whose key equals KEY_MAX must survive: they are
        # distinguished from padding only by stable sort + prefix layout.
        keys = rng.integers(0, 1000, size=N * CAP, dtype=np.uint64).astype(np.uint32)
        sent = rng.choice(N * CAP, size=17, replace=False)
        keys[sent] = KEY_MAX
        payload = np.arange(N * CAP, dtype=np.int32)[:, None] * np.ones(W, np.int32)
        nvalid = np.full(N, CAP, np.int32)
        got_k, got_p, cnt = _collect(fn, mesh, keys, payload, nvalid)
        assert cnt.sum() == N * CAP
        np.testing.assert_array_equal(got_k, np.sort(keys))
        # every sentinel-keyed payload row made it through
        assert sorted(got_p[got_k == KEY_MAX][:, 0]) == sorted(np.arange(N * CAP)[sent])

    def test_imbalanced_shards_stay_balanced(self, mesh, rng):
        # One full shard of uniform keys + 7 near-empty shards pinned at key 0:
        # fill-weighted sampling must keep the big shard's rows spread out
        # instead of letting the tiny shards' keys dominate the splitters.
        spec = SortSpec(
            num_executors=N, capacity=CAP, recv_capacity=CAP, width=1,
            samples_per_shard=64, impl="dense",
        )
        f = build_distributed_sort(make_mesh(N), spec)
        keys = np.full(N * CAP, KEY_MAX, dtype=np.uint32)
        nvalid = np.zeros(N, np.int32)
        nvalid[0] = CAP
        keys[:CAP] = rng.integers(0, 2**32 - 1, size=CAP, dtype=np.uint64).astype(np.uint32)
        for j in range(1, N):
            nvalid[j] = 1
            keys[j * CAP] = 0
        payload = np.zeros((N * CAP, 1), np.int32)
        ko, _, cnt = f(*_place(make_mesh(N), keys, payload, nvalid))
        cnt = np.asarray(cnt)
        assert cnt.sum() == nvalid.sum()
        # receive stays within the (deliberately tight) 1x capacity everywhere
        assert np.all(cnt <= CAP), cnt
        got = np.concatenate(
            [np.asarray(ko).reshape(N, -1)[j, : cnt[j]] for j in range(N)]
        )
        valid_keys = np.concatenate([keys[j * CAP : j * CAP + nvalid[j]] for j in range(N)])
        np.testing.assert_array_equal(got, np.sort(valid_keys))

    def test_terasort_rows_over_four_executors(self, rng):
        """TeraSort's row (uint32 key + 24 int32 lanes = 100 B) at 4,096 rows
        over four executors, the lowering left to the platform ('dense' on
        this mesh): no row dropped, keys and payload in the oracle's order."""
        n, cap, width = 4, 1024, 24
        mesh4 = make_mesh(n)
        fn4 = build_distributed_sort(
            mesh4, SortSpec(num_executors=n, capacity=cap, recv_capacity=2 * cap, width=width)
        )
        assert fn4.spec.impl == "dense"
        keys = rng.permutation(n * cap).astype(np.uint32)
        payload = keys[:, None].astype(np.int32) + np.arange(width, dtype=np.int32)
        ko, po, cnt = fn4(*_place(mesh4, keys, payload, np.full(n, cap, np.int32)))
        cnt = np.asarray(cnt)
        assert int(cnt.sum()) == n * cap
        ko = np.asarray(ko).reshape(n, -1)
        po = np.asarray(po).reshape(n, ko.shape[1], width)
        want_k, want_p = oracle_sort(keys, payload)
        np.testing.assert_array_equal(np.concatenate([ko[j, : cnt[j]] for j in range(n)]), want_k)
        np.testing.assert_array_equal(np.concatenate([po[j, : cnt[j]] for j in range(n)]), want_p)

    def test_single_executor_mesh(self):
        mesh1 = make_mesh(1)
        spec = SortSpec(num_executors=1, capacity=64, recv_capacity=64, width=1, impl="dense")
        f = build_distributed_sort(mesh1, spec)
        rng = np.random.default_rng(0)
        keys = rng.permutation(64).astype(np.uint32)
        ko, po, cnt = f(
            jax.device_put(keys, NamedSharding(mesh1, P("ex"))),
            jax.device_put(keys[:, None].astype(np.int32), NamedSharding(mesh1, P("ex", None))),
            jax.device_put(np.array([64], np.int32), NamedSharding(mesh1, P("ex"))),
        )
        np.testing.assert_array_equal(np.asarray(ko), np.arange(64, dtype=np.uint32))
        np.testing.assert_array_equal(np.asarray(po)[:, 0], np.arange(64, dtype=np.int32))
        assert int(np.asarray(cnt)[0]) == 64

    def test_single_lowering_auto_resolution(self):
        # n=1 resolves to 'single' on ANY platform (pure XLA: no collective)
        spec = SortSpec(num_executors=1, capacity=64, recv_capacity=64, width=1)
        assert spec.resolve_impl(platform="cpu").impl == "single"
        assert spec.resolve_impl(platform="tpu").impl == "single"
        multi = SortSpec(num_executors=2, capacity=64, recv_capacity=128, width=1)
        assert multi.resolve_impl(platform="cpu").impl == "dense"
        # single demands n=1 and recv headroom >= capacity
        bad = SortSpec(num_executors=2, capacity=64, recv_capacity=128, width=1, impl="single")
        with pytest.raises(ValueError, match="single"):
            bad.validate()

    def test_single_lowering_vs_oracle_with_padding(self):
        """impl='single' (what n=1 'auto' now runs, incl. the PERF headline):
        nv < capacity padding, a VALID KEY_MAX key, and the recv_capacity >
        capacity pad branch — output must match the other lowerings' contract
        (sorted prefix, zeroed payload tail, KEY_MAX key tail)."""
        mesh1 = make_mesh(1)
        CAP, RECV, NV = 64, 96, 40
        spec = SortSpec(num_executors=1, capacity=CAP, recv_capacity=RECV, width=2, impl="auto")
        f = build_distributed_sort(mesh1, spec)
        assert f.spec.impl == "single"
        rng = np.random.default_rng(7)
        keys = np.full(CAP, 12345, np.uint32)  # padding region deliberately NOT KEY_MAX
        keys[:NV] = rng.integers(0, 1 << 32, size=NV, dtype=np.uint64).astype(np.uint32)
        keys[3] = KEY_MAX  # a genuinely valid max-key row must survive
        payload = np.full((CAP, 2), -7, np.int32)  # garbage padding payload
        payload[:NV] = rng.integers(-100, 100, size=(NV, 2)).astype(np.int32)
        ko, po, cnt = f(
            jax.device_put(keys, NamedSharding(mesh1, P("ex"))),
            jax.device_put(payload, NamedSharding(mesh1, P("ex", None))),
            jax.device_put(np.array([NV], np.int32), NamedSharding(mesh1, P("ex"))),
        )
        ko, po, cnt = np.asarray(ko), np.asarray(po), np.asarray(cnt)
        assert cnt.tolist() == [NV]
        ek, ep = oracle_sort(keys[:NV], payload[:NV])
        np.testing.assert_array_equal(ko[:NV], ek)
        np.testing.assert_array_equal(po[:NV], ep)
        # contract parity with the collective lowerings: zero payload tail,
        # KEY_MAX key tail — caller padding must NOT leak through
        np.testing.assert_array_equal(ko[NV:], np.full(RECV - NV, KEY_MAX, np.uint32))
        np.testing.assert_array_equal(po[NV:], np.zeros((RECV - NV, 2), np.int32))

    def test_unknown_impl_refused(self):
        # 'radix' was an impl until PR 44: a conf written for it gets the
        # check every unknown impl gets, not a silent fall to another sort
        spec = SortSpec(num_executors=1, capacity=8, recv_capacity=8, impl="radix")
        with pytest.raises(ValueError, match="unknown impl 'radix'"):
            spec.validate()
        with pytest.raises(ValueError, match="unknown impl 'radix'"):
            build_distributed_sort(make_mesh(1), spec)

    def test_spec_validation(self, mesh):
        with pytest.raises(ValueError, match="mesh size"):
            build_distributed_sort(mesh, SortSpec(num_executors=4, capacity=8, recv_capacity=8))
        with pytest.raises(ValueError, match="32-bit"):
            SortSpec(
                num_executors=N, capacity=8, recv_capacity=8,
                dtype=np.dtype(np.float64), impl="dense",
            ).validate()
        with pytest.raises(ValueError, match="samples_per_shard"):
            SortSpec(
                num_executors=N, capacity=8, recv_capacity=8,
                samples_per_shard=2, impl="dense",
            ).validate()


def _single_sort_case(name):
    """(keys, payload, capacity) of one shape the n=1 sort must get right:
    the correctness shapes of the kernel sort that left in PR 44, kept on the
    sort that stays."""
    rng = np.random.default_rng(sum(map(ord, name)))

    def ids(n, width=1):  # payload = row id: equality proves the stable order
        return np.arange(n, dtype=np.int32)[:, None] * np.ones(width, np.int32)

    def u32(hi, n):
        return rng.integers(0, hi, size=n, dtype=np.uint64).astype(np.uint32)

    if name.startswith("fuzz"):
        hi = {"fuzz-tiny-keyspace": 4, "fuzz-16bit": 1 << 16, "fuzz-full-range": 1 << 32}[name]
        n, width = int(rng.integers(10, 2000)), int(rng.integers(1, 6))
        return u32(hi, n), rng.integers(-1000, 1000, size=(n, width)).astype(np.int32), 2048
    if name == "heavy-duplicates":
        return u32(3, 777), ids(777), 1024
    if name in ("all-equal", "all-zero", "all-keymax"):
        k = {"all-equal": 7, "all-zero": 0, "all-keymax": 0xFFFFFFFF}[name]
        return np.full(300, k, np.uint32), ids(300), 512
    if name == "sign-bit-keys":  # above 2^31 they bitcast to negative int32 lanes
        return np.array([0, 2**31, 2**31 - 1, 0xFFFFFFFF, 5], np.uint32), ids(5), 8
    if name == "non-multiple-padding":
        return u32(1 << 32, 1000), ids(1000, 2), 1056
    if name == "one-row":
        return np.array([42], np.uint32), ids(1), 8
    if name == "two-rows":
        return np.array([3, 1], np.uint32), ids(2), 8
    if name == "odd-row-count":
        return u32(1 << 32, 1001), ids(1001), 1008
    if name == "float32-payload":
        keys = np.array(
            [0xD0327A78, 0xE9AA5979, 0xF0000000, 0xBF800001, 0, 1, 2, 3, 4, 5, 6, 7],
            np.uint32,
        )
        return keys, rng.normal(size=(12, 2)).astype(np.float32), 16
    if name == "valid-keymax-before-padding":
        return (
            np.array([5, KEY_MAX, 1, KEY_MAX], np.uint32),
            np.array([[50], [91], [10], [92]], np.int32),
            8,
        )
    raise AssertionError(name)


@pytest.mark.parametrize("case", [
    "fuzz-tiny-keyspace", "fuzz-16bit", "fuzz-full-range", "heavy-duplicates",
    "all-equal", "all-zero", "all-keymax", "sign-bit-keys", "non-multiple-padding",
    "one-row", "two-rows", "odd-row-count", "float32-payload",
    "valid-keymax-before-padding",
])
def test_single_sort_shapes_vs_oracle(case):
    """The n=1 sort ('single': what 'auto' runs on one chip) against
    ``oracle_sort``, row for row: unsigned key order, stability under
    duplication, valid KEY_MAX rows ahead of the zeroed padding, a float
    payload carried bit for bit."""
    keys, payload, cap = _single_sort_case(case)
    n, width = payload.shape
    spec = SortSpec(
        num_executors=1, capacity=cap, recv_capacity=cap, width=width,
        dtype=payload.dtype,
    )
    mesh1 = make_mesh(1)
    f = build_distributed_sort(mesh1, spec)
    assert f.spec.impl == "single"
    pk = np.full(cap, 12345, np.uint32)  # padding deliberately NOT KEY_MAX
    pk[:n] = keys
    pp = np.full((cap, width), -7, payload.dtype)
    pp[:n] = payload
    ko, po, cnt = (np.asarray(x) for x in f(*_place(mesh1, pk, pp, np.array([n], np.int32))))
    assert cnt.tolist() == [n]
    want_k, want_p = oracle_sort(keys, payload)
    np.testing.assert_array_equal(ko[:n], want_k)
    np.testing.assert_array_equal(po[:n].view(np.uint32), want_p.view(np.uint32))
    np.testing.assert_array_equal(ko[n:], np.full(cap - n, KEY_MAX, np.uint32))
    np.testing.assert_array_equal(po[n:], np.zeros((cap - n, width), payload.dtype))


class TestRunDistributedSort:
    """Host driver with automatic skew retry (run_distributed_sort)."""

    def test_uniform_keys_roundtrip(self, rng):
        from sparkucx_tpu.ops.sort import SortSpec, oracle_sort, run_distributed_sort
        from sparkucx_tpu.ops.exchange import make_mesh

        n, total = 4, 3000
        keys = rng.integers(0, 1 << 31, size=total, dtype=np.uint32)
        payload = rng.integers(-99, 99, size=(total, 3), dtype=np.int32)
        spec = SortSpec(
            num_executors=n, capacity=1024, recv_capacity=1536, width=3, impl="dense"
        )
        sk, sp = run_distributed_sort(make_mesh(n), spec, keys, payload)
        ok, op = oracle_sort(keys, payload)
        assert np.array_equal(sk, ok)
        # payload rows must travel with their keys (same multiset per key)
        assert sorted(map(tuple, sp)) == sorted(map(tuple, op))

    def test_skewed_keys_trigger_retry(self, rng):
        from sparkucx_tpu.ops.sort import SortSpec, oracle_sort, run_distributed_sort
        from sparkucx_tpu.ops.exchange import make_mesh

        n, total = 4, 2000
        # 90% of keys identical: one range gets almost everything, so the
        # balanced recv_capacity must overflow and the driver must double it
        keys = np.where(
            rng.uniform(size=total) < 0.9,
            np.uint32(7),
            rng.integers(0, 1 << 31, size=total).astype(np.uint32),
        )
        payload = rng.integers(-99, 99, size=(total, 1), dtype=np.int32)
        spec = SortSpec(
            num_executors=n, capacity=512, recv_capacity=600, width=1, impl="dense"
        )
        sk, sp = run_distributed_sort(make_mesh(n), spec, keys, payload)
        ok, _ = oracle_sort(keys, payload)
        assert np.array_equal(sk, ok)

    def test_pathological_skew_raises(self, rng):
        from sparkucx_tpu.ops.sort import SortSpec, run_distributed_sort
        from sparkucx_tpu.ops.exchange import make_mesh

        n, total = 4, 2000
        keys = np.full(total, 7, np.uint32)  # every key identical
        payload = np.zeros((total, 1), np.int32)
        spec = SortSpec(
            num_executors=n, capacity=512, recv_capacity=520, width=1, impl="dense"
        )
        with pytest.raises(RuntimeError, match="skewed"):
            run_distributed_sort(make_mesh(n), spec, keys, payload, max_attempts=1)


class TestExternalSort:
    """Out-of-core driver: device-batch sorts + stable host merge."""

    @pytest.mark.parametrize(
        "n, cap, total, width",
        [
            (4, 200, 5 * 4 * 200 + 37, 3),  # 6 runs, ragged tail
            (2, 1024, 8192, 24),  # TeraSort's 100 B rows in exactly 4 full batches
        ],
        ids=["six_ragged_runs", "terasort_rows_four_full_batches"],
    )
    def test_multi_batch_vs_oracle(self, rng, n, cap, total, width):
        from sparkucx_tpu.ops.exchange import make_mesh
        from sparkucx_tpu.ops.sort import SortSpec, oracle_sort, run_external_sort

        keys = rng.integers(0, 1 << 32, size=total, dtype=np.uint64).astype(np.uint32)
        payload = rng.integers(-99, 99, size=(total, width), dtype=np.int32)
        spec = SortSpec(
            num_executors=n, capacity=cap, recv_capacity=2 * cap, width=width, impl="dense"
        )
        sk, sp = run_external_sort(make_mesh(n), spec, keys, payload)
        ok, op = oracle_sort(keys, payload)
        assert np.array_equal(sk, ok)
        assert np.array_equal(sp, op)

    def test_stability_under_heavy_duplication(self, rng):
        # payload carries the input row index; the stable oracle's permutation
        # must be reproduced row-exact even with only 3 distinct keys spread
        # across many runs
        from sparkucx_tpu.ops.exchange import make_mesh
        from sparkucx_tpu.ops.sort import SortSpec, oracle_sort, run_external_sort

        n, cap = 2, 64
        total = 7 * n * cap + 11
        keys = rng.integers(0, 3, size=total, dtype=np.uint64).astype(np.uint32)
        payload = np.arange(total, dtype=np.int32)[:, None]
        spec = SortSpec(
            num_executors=n, capacity=cap, recv_capacity=2 * cap, width=1, impl="dense"
        )
        sk, sp = run_external_sort(make_mesh(n), spec, keys, payload)
        ok, op = oracle_sort(keys, payload)
        assert np.array_equal(sk, ok)
        assert np.array_equal(sp, op)

    def test_single_batch_delegates(self, rng):
        from sparkucx_tpu.ops.exchange import make_mesh
        from sparkucx_tpu.ops.sort import SortSpec, oracle_sort, run_external_sort

        n, cap = 4, 256
        total = n * cap  # exactly one batch
        keys = rng.integers(0, 1 << 32, size=total, dtype=np.uint64).astype(np.uint32)
        payload = rng.integers(-99, 99, size=(total, 1), dtype=np.int32)
        spec = SortSpec(
            num_executors=n, capacity=cap, recv_capacity=2 * cap, width=1, impl="dense"
        )
        sk, _ = run_external_sort(make_mesh(n), spec, keys, payload)
        ok, _ = oracle_sort(keys, payload)
        assert np.array_equal(sk, ok)

    def test_merge_sorted_runs_edges(self):
        from sparkucx_tpu.ops.sort import merge_sorted_runs

        # odd run count, empty run, all-equal keys
        k1 = np.array([1, 3, 5], np.uint32)
        k2 = np.array([], np.uint32)
        k3 = np.array([2, 3, 3], np.uint32)
        p = lambda k, base: (np.arange(len(k), dtype=np.int32) + base)[:, None]
        mk, mp = merge_sorted_runs([k1, k2, k3], [p(k1, 0), p(k2, 10), p(k3, 20)])
        assert mk.tolist() == [1, 2, 3, 3, 3, 5]
        # stability: run1's key-3 row (payload 1) precedes run3's (21, 22)
        assert mp[:, 0].tolist() == [0, 20, 1, 21, 22, 2]
        with pytest.raises(ValueError):
            merge_sorted_runs([], [])


# -- the one local sort (``sort_rows``): several key lanes, byte-string keys ----

from sparkucx_tpu.ops.sort import key_lanes_of, sort_rows  # noqa: E402


def _lexsort_rows(rows, lanes, valid):
    """NumPy's ``lexsort`` over the first ``lanes`` lanes as ``uint32``
    (stable; lane 0 most significant), valid rows first, padding zeroed."""
    keys = rows[:, :lanes].view(np.uint32)
    order = np.lexsort([keys[:, i] for i in reversed(range(lanes))] + [~valid])
    out = rows[order]
    out[int(valid.sum()):] = 0
    return out


@pytest.mark.parametrize("lanes", [1, 2, 3])
@pytest.mark.parametrize("width", [3, 7, 25])
def test_sort_rows_is_numpys_lexsort(rng, lanes, width):
    """Lanes compared as the ``uint32`` they hold, lane 0 first; few distinct
    values a lane, so later lanes decide and ties test stability; values of
    both signs as ``int32`` (an unsigned compare)."""
    n = 777
    rows = rng.integers(-3, 3, size=(n, width)).astype(np.int32)
    rows[:, 0] *= 0x40000000  # 0x80000000 and 0xC0000000 must sort after 0x40000000
    rows[:, -1] = np.arange(n)  # tells equal keys apart: stability is visible
    valid = np.arange(n) < 700
    want = _lexsort_rows(rows, lanes, valid)
    got = np.asarray(jax.jit(sort_rows, static_argnums=(1,))(jnp.asarray(rows), lanes, 700))
    assert np.array_equal(got, want)
    mask = rng.random(n) < 0.8  # validity as a mask: padding anywhere, not only behind
    got = np.asarray(jax.jit(sort_rows, static_argnums=(1,))(jnp.asarray(rows), lanes, jnp.asarray(mask)))
    assert np.array_equal(got, _lexsort_rows(rows, lanes, mask))


@pytest.mark.parametrize("key_bytes", [1, 3, 4, 5, 8, 10, 12])
def test_sort_rows_orders_byte_string_keys(rng, key_bytes):
    """``key_bytes``: the row's first bytes in memory order, unsigned, most
    significant first — against Python's own order of ``bytes``; the bytes
    after the key in the last lane do not take part; bytes >= 0x80 in every
    position."""
    n, width = 600, 5
    raw = rng.integers(0, 256, size=(n, width * 4), dtype=np.uint8)
    raw[:, :key_bytes] = rng.choice(np.array([0x00, 0x7F, 0x80, 0xFF], np.uint8), size=(n, key_bytes))
    raw[: n // 2, : max(key_bytes - 1, 0)] = raw[0, : max(key_bytes - 1, 0)]  # the last key byte decides
    rows = raw.view(np.int32).reshape(n, width)
    lanes = key_lanes_of(key_bytes)
    fn = jax.jit(lambda r, valid: sort_rows(r, lanes, valid, key_bytes=key_bytes))
    got = np.asarray(fn(jnp.asarray(rows), n)).view(np.uint8).reshape(n, width * 4)
    order = sorted(range(n), key=lambda i: (bytes(raw[i, :key_bytes]), i))  # stable
    assert np.array_equal(got, raw[order])
    # padding anywhere among the rows, whatever its bytes: last, zeroed — also
    # behind a valid key of all 0xFF
    mask = rng.random(n) < 0.7
    got = np.asarray(fn(jnp.asarray(rows), jnp.asarray(mask))).view(np.uint8).reshape(n, width * 4)
    kept = [i for i in order if mask[i]]
    assert np.array_equal(got[: len(kept)], raw[kept]) and not got[len(kept):].any()


def test_sort_rows_refuses_what_is_no_key():
    rows = jnp.zeros((4, 3), jnp.int32)
    with pytest.raises(ValueError, match="key_lanes"):
        sort_rows(rows, 4, 4)
    with pytest.raises(ValueError, match="key_lanes"):
        sort_rows(rows, 0, 4)
    with pytest.raises(ValueError, match="10-byte key is 3 lanes"):
        sort_rows(rows, 2, 4, key_bytes=10)


@pytest.mark.parametrize("key_bytes", [4, 8, 10, 12])
@pytest.mark.parametrize("case", ["poisoned", "all_valid", "one_valid", "none_valid"])
def test_sort_rows_padding_is_last_and_zero_whatever_it_held(rng, case, key_bytes):
    """Rows that are not valid hold what a gather left there — all ones, or
    a key of zeros before every real key — and come out last and zero; with
    every row valid there is nothing to zero, with one row that row leads."""
    n, width = 300, 25
    raw = rng.integers(0, 256, size=(n, width * 4), dtype=np.uint8)
    raw[:, 0] |= 1
    raw[: n // 3, :key_bytes] = raw[0, :key_bytes]  # one key, a hundred values: place order decides
    mask = {"poisoned": rng.random(n) < 0.6, "all_valid": np.ones(n, bool),
            "one_valid": np.arange(n) == 171, "none_valid": np.zeros(n, bool)}[case]
    poison = np.flatnonzero(~mask)
    raw[poison[0::2]] = 0xFF
    raw[poison[1::2]] = 0
    lanes = key_lanes_of(key_bytes)
    fn = jax.jit(lambda r, valid: sort_rows(r, lanes, valid, key_bytes=key_bytes))
    got = np.asarray(fn(jnp.asarray(raw.view(np.int32).reshape(n, width)), jnp.asarray(mask)))
    got = got.view(np.uint8).reshape(n, width * 4)
    kept = sorted(np.flatnonzero(mask), key=lambda i: (bytes(raw[i, :key_bytes]), i))
    assert np.array_equal(got[: len(kept)], raw[kept]) and not got[len(kept):].any()


def test_key_order_is_sort_rows_permutation(rng):
    """``key_order``: the permutation and the count ``sort_rows`` applies."""
    from sparkucx_tpu.ops.sort import key_order

    rows = rng.integers(-5, 5, size=(200, 6)).astype(np.int32)
    mask = rng.random(200) < 0.7
    order, count = jax.jit(key_order)(jnp.asarray(rows[:, :2].T), jnp.asarray(mask))
    order, count = np.asarray(order), int(count)
    assert count == mask.sum() and sorted(order.tolist()) == list(range(200))
    want = _lexsort_rows(rows, 2, mask)
    assert np.array_equal(rows[order][:count], want[:count]) and not mask[order[count:]].any()
