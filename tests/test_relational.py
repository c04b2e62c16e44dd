"""Tests for the device-resident relational operators (GROUP BY, hash join)."""

from dataclasses import replace

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from sparkucx_tpu.ops.exchange import make_mesh
from sparkucx_tpu.ops.relational import (
    KEY_MAX,
    AggregateSpec,
    JoinSpec,
    build_grouped_aggregate,
    build_hash_join,
    hash_owners_host,
    oracle_aggregate,
    oracle_join,
    plan_join_capacities,
    run_grouped_aggregate,
)

N = 8
CAP = 128


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(N)


def _keys_sh(mesh, x):
    return jax.device_put(x, NamedSharding(mesh, P("ex")))


def _rows_sh(mesh, x):
    return jax.device_put(x, NamedSharding(mesh, P("ex", None)))


def _agg_inputs(mesh, keys, values, nvalid):
    return _keys_sh(mesh, keys), _rows_sh(mesh, values), _keys_sh(mesh, nvalid)


def _collect_groups(fn, mesh, keys, values, nvalid):
    gk, gv, gc, ng, rt = fn(*_agg_inputs(mesh, keys, values, nvalid))
    assert np.all(np.asarray(rt) <= fn.spec.recv_capacity), "exchange overflowed"
    gk = np.asarray(gk).reshape(N, -1)
    gv = np.asarray(gv).reshape(N, gk.shape[1], -1)
    gc = np.asarray(gc).reshape(N, -1)
    ng = np.asarray(ng)
    rows = {}
    for j in range(N):
        for g in range(ng[j]):
            k = int(gk[j, g])
            assert k not in rows, "key appeared on two shards"
            rows[k] = (gv[j, g], int(gc[j, g]))
    return rows, ng


class TestGroupedAggregate:
    @pytest.fixture(scope="class")
    def fn(self, mesh):
        spec = AggregateSpec(
            num_executors=N, capacity=CAP, recv_capacity=4 * CAP,
            aggs=("sum", "min", "max"), impl="dense",
        )
        return build_grouped_aggregate(mesh, spec)

    def test_matches_oracle(self, fn, mesh, rng):
        keys = rng.integers(0, 50, size=N * CAP, dtype=np.uint64).astype(np.uint32)
        values = rng.integers(-100, 100, size=(N * CAP, 3), dtype=np.int64).astype(np.int32)
        nvalid = np.full(N, CAP, np.int32)
        rows, ng = _collect_groups(fn, mesh, keys, values, nvalid)
        want_k, want_v, want_c = oracle_aggregate(keys, values, ("sum", "min", "max"))
        assert sorted(rows) == list(want_k)
        for k, v, c in zip(want_k, want_v, want_c):
            got_v, got_c = rows[int(k)]
            np.testing.assert_array_equal(got_v, v)
            assert got_c == c

    def test_padding_rows_excluded(self, fn, mesh, rng):
        nvalid = rng.integers(0, CAP + 1, size=N).astype(np.int32)
        nvalid[2] = 0
        keys = np.zeros(N * CAP, np.uint32)  # padding deliberately key 0
        values = np.zeros((N * CAP, 3), np.int32)
        real_k, real_v = [], []
        for j in range(N):
            ks = rng.integers(0, 20, size=nvalid[j], dtype=np.uint64).astype(np.uint32)
            vs = rng.integers(1, 10, size=(nvalid[j], 3), dtype=np.int64).astype(np.int32)
            keys[j * CAP : j * CAP + nvalid[j]] = ks
            values[j * CAP : j * CAP + nvalid[j]] = vs
            real_k.append(ks)
            real_v.append(vs)
        rows, _ = _collect_groups(fn, mesh, keys, values, nvalid)
        want_k, want_v, want_c = oracle_aggregate(
            np.concatenate(real_k), np.concatenate(real_v), ("sum", "min", "max")
        )
        assert sorted(rows) == list(want_k)
        for k, v, c in zip(want_k, want_v, want_c):
            got_v, got_c = rows[int(k)]
            np.testing.assert_array_equal(got_v, v)
            assert got_c == c

    def test_sentinel_key_is_a_real_group(self, fn, mesh, rng):
        keys = rng.integers(0, 5, size=N * CAP, dtype=np.uint64).astype(np.uint32)
        keys[rng.choice(N * CAP, size=33, replace=False)] = KEY_MAX
        values = np.ones((N * CAP, 3), np.int32)
        nvalid = np.full(N, CAP, np.int32)
        rows, _ = _collect_groups(fn, mesh, keys, values, nvalid)
        assert rows[int(KEY_MAX)][1] == 33

    def test_count_star_no_value_columns(self, mesh, rng):
        spec = AggregateSpec(
            num_executors=N, capacity=CAP, recv_capacity=4 * CAP, aggs=(), impl="dense"
        )
        f = build_grouped_aggregate(mesh, spec)
        keys = rng.integers(0, 10, size=N * CAP, dtype=np.uint64).astype(np.uint32)
        values = np.zeros((N * CAP, 0), np.int32)
        rows, _ = _collect_groups(f, mesh, keys, values, np.full(N, CAP, np.int32))
        want = {int(k): c for k, c in zip(*np.unique(keys, return_counts=True))}
        assert {k: c for k, (_, c) in rows.items()} == want

    def test_float_aggregation(self, mesh, rng):
        spec = AggregateSpec(
            num_executors=N, capacity=CAP, recv_capacity=4 * CAP,
            aggs=("min", "max"), dtype=np.dtype(np.float32), impl="dense",
        )
        f = build_grouped_aggregate(mesh, spec)
        keys = rng.integers(0, 16, size=N * CAP, dtype=np.uint64).astype(np.uint32)
        values = rng.normal(size=(N * CAP, 2)).astype(np.float32)
        rows, _ = _collect_groups(f, mesh, keys, values, np.full(N, CAP, np.int32))
        want_k, want_v, _ = oracle_aggregate(keys, values, ("min", "max"))
        for k, v in zip(want_k, want_v):
            np.testing.assert_allclose(rows[int(k)][0], v, rtol=1e-6)

    @pytest.mark.parametrize("partial", [False, True], ids=["full_rows", "map_side_partials"])
    def test_groupbytest_rows_at_exact_receive_capacity(self, rng, partial):
        """GroupByTest's shape (100 B rows: key + 24 summed lanes), 4,096 rows
        on 64 keys over four executors, the receive buffers sized EXACTLY
        from the host twin of the placement hash: the device must place every
        key where the host said it would, or a shard overflows."""
        n, cap, width = 4, 1024, 24
        keys = rng.integers(0, 64, size=n * cap).astype(np.uint32)
        values = rng.integers(-50, 50, size=(n * cap, width), dtype=np.int64).astype(np.int32)
        if partial:  # a sender ships one row per key it holds
            per_owner = np.zeros(n, np.int64)
            for s in range(n):
                held = np.unique(keys[s * cap : (s + 1) * cap])
                np.add.at(per_owner, hash_owners_host(held, n), 1)
        else:
            per_owner = np.bincount(hash_owners_host(keys, n), minlength=n)
        mesh4 = make_mesh(n)
        fn = build_grouped_aggregate(
            mesh4,
            AggregateSpec(
                num_executors=n, capacity=cap, recv_capacity=int(per_owner.max()),
                aggs=("sum",) * width, partial=partial,
            ),
        )
        gk, gv, gc, ng, rt = fn(*_agg_inputs(mesh4, keys, values, np.full(n, cap, np.int32)))
        np.testing.assert_array_equal(np.asarray(rt).reshape(-1), per_owner)
        ng, gc = np.asarray(ng), np.asarray(gc).reshape(n, -1)
        assert sum(int(gc[j, : ng[j]].sum()) for j in range(n)) == n * cap  # no row dropped
        want_k, want_v, want_c = oracle_aggregate(keys, values, ("sum",) * width)
        assert int(ng.sum()) == len(want_k) == 64
        gk = np.asarray(gk).reshape(n, -1)
        gv = np.asarray(gv).reshape(n, gk.shape[1], width)
        got = {int(gk[j, g]): (gv[j, g], int(gc[j, g])) for j in range(n) for g in range(ng[j])}
        for k, v, c in zip(want_k, want_v, want_c):
            np.testing.assert_array_equal(got[int(k)][0], v)
            assert got[int(k)][1] == c

    def test_spec_validation(self, mesh):
        with pytest.raises(ValueError, match="unknown aggregation"):
            AggregateSpec(
                num_executors=N, capacity=8, recv_capacity=8, aggs=("median",), impl="dense"
            ).validate()
        with pytest.raises(ValueError, match="count_distinct"):
            AggregateSpec(
                num_executors=N, capacity=8, recv_capacity=8,
                aggs=("count_distinct",), impl="dense", partial=True,
            ).validate()
        with pytest.raises(ValueError, match="mesh size"):
            build_grouped_aggregate(
                mesh, AggregateSpec(num_executors=2, capacity=8, recv_capacity=8, aggs=())
            )


def _join_inputs(mesh, bk, bv, bn, pk, pv, pn):
    return (
        _keys_sh(mesh, bk), _rows_sh(mesh, bv), _keys_sh(mesh, bn),
        _keys_sh(mesh, pk), _rows_sh(mesh, pv), _keys_sh(mesh, pn),
    )


def _collect_join(fn, mesh, *args):
    ok, ob, op, cnt, rt = fn(*_join_inputs(mesh, *args))
    rt = np.asarray(rt).reshape(N, 2)
    assert np.all(rt[:, 0] <= fn.spec.build_recv_capacity), "build exchange overflowed"
    assert np.all(rt[:, 1] <= fn.spec.probe_recv_capacity), "probe exchange overflowed"
    ok = np.asarray(ok).reshape(N, -1)
    ob = np.asarray(ob).reshape(N, ok.shape[1], -1)
    op = np.asarray(op).reshape(N, ok.shape[1], -1)
    cnt = np.asarray(cnt)
    rows = []
    for j in range(N):
        n = min(int(cnt[j]), ok.shape[1])
        for i in range(n):
            rows.append((int(ok[j, i]), tuple(ob[j, i]), tuple(op[j, i])))
    return rows, cnt


def _oracle_rows(bk, bv, pk, pv):
    k, b, p = oracle_join(bk, bv, pk, pv)
    return [(int(ki), tuple(bi), tuple(pi)) for ki, bi, pi in zip(k, b, p)]


class TestHashJoin:
    @pytest.fixture(scope="class")
    def fn(self, mesh):
        spec = JoinSpec(
            num_executors=N,
            build_capacity=CAP, build_recv_capacity=4 * CAP, build_width=2,
            probe_capacity=CAP, probe_recv_capacity=4 * CAP, probe_width=1,
            out_capacity=8 * CAP, impl="dense",
        )
        return build_hash_join(mesh, spec)

    def test_many_to_many_matches_oracle(self, fn, mesh, rng):
        bk = rng.integers(0, 40, size=N * CAP, dtype=np.uint64).astype(np.uint32)
        bv = rng.integers(0, 1000, size=(N * CAP, 2), dtype=np.int64).astype(np.int32)
        pk = rng.integers(0, 40, size=N * CAP, dtype=np.uint64).astype(np.uint32)
        pv = rng.integers(0, 1000, size=(N * CAP, 1), dtype=np.int64).astype(np.int32)
        # cap expansion: keep matches under out_capacity by sparsifying probe
        pn = np.full(N, 16, np.int32)
        bn = np.full(N, CAP, np.int32)
        rows, cnt = _collect_join(fn, mesh, bk, bv, bn, pk, pv, pn)
        valid_p = np.concatenate([np.arange(CAP) < pn[j] for j in range(N)])
        want = _oracle_rows(bk, bv, pk[valid_p], pv[valid_p])
        assert sorted(rows) == sorted(want)
        assert cnt.sum() == len(want)

    def test_pk_fk_join(self, fn, mesh, rng):
        # unique build keys (primary key) -> every probe row matches exactly once
        bk = rng.permutation(N * CAP).astype(np.uint32)
        bv = bk[:, None].astype(np.int32) * np.array([1, 7], np.int32)
        pk = rng.integers(0, N * CAP, size=N * CAP, dtype=np.uint64).astype(np.uint32)
        pv = rng.integers(0, 100, size=(N * CAP, 1), dtype=np.int64).astype(np.int32)
        bn = np.full(N, CAP, np.int32)
        pn = np.full(N, CAP, np.int32)
        rows, cnt = _collect_join(fn, mesh, bk, bv, bn, pk, pv, pn)
        assert cnt.sum() == N * CAP  # every probe row found its unique build row
        for k, b, _ in rows:
            assert b == (k, 7 * k)

    @pytest.mark.parametrize(
        "join_type",
        ["inner", "left_outer", "left_semi", "left_anti", "right_outer", "full_outer"],
    )
    def test_pk_fk_half_the_probes_hit(self, rng, join_type):
        """TPC-H's plan shape over four executors: 1,024 dimension rows with
        unique keys, 4,096 fact rows of which about half reference one, every
        capacity taken exactly from ``plan_join_capacities``.  Each arm emits
        the row count set logic gives, and no buffer overflows."""
        n, pcap, bcap = 4, 1024, 256
        nb = n * bcap
        bk = rng.permutation(nb).astype(np.uint32)
        pk = rng.integers(0, 2 * nb, size=n * pcap, dtype=np.uint64).astype(np.uint32)
        brecv, precv, out_cap = plan_join_capacities(bk, pk, n, join_type=join_type)
        hits = int(np.isin(pk, bk).sum())
        unreferenced = int((~np.isin(bk, pk)).sum())
        assert 0 < hits < n * pcap
        want = {
            "inner": hits,
            "left_outer": n * pcap,  # misses null-extend
            "left_semi": hits,  # unique build keys: one row a hit
            "left_anti": n * pcap - hits,
            "right_outer": hits + unreferenced,
            "full_outer": n * pcap + unreferenced,
        }[join_type]
        mesh4 = make_mesh(n)
        fn = build_hash_join(
            mesh4,
            JoinSpec(
                num_executors=n,
                build_capacity=bcap, build_recv_capacity=brecv, build_width=8,
                probe_capacity=pcap, probe_recv_capacity=precv, probe_width=16,
                out_capacity=out_cap, join_type=join_type,
            ),
        )
        out = fn(
            _keys_sh(mesh4, bk), _rows_sh(mesh4, np.zeros((nb, 8), np.int32)),
            _keys_sh(mesh4, np.full(n, bcap, np.int32)),
            _keys_sh(mesh4, pk), _rows_sh(mesh4, np.zeros((n * pcap, 16), np.int32)),
            _keys_sh(mesh4, np.full(n, pcap, np.int32)),
        )
        counts, recv_totals = np.asarray(out[3]), np.asarray(out[4]).reshape(n, 2)
        assert (recv_totals[:, 0] <= brecv).all() and (recv_totals[:, 1] <= precv).all()
        assert (counts <= out_cap).all()
        assert int(counts.sum()) == want

    def test_disjoint_keys_empty_result(self, fn, mesh, rng):
        bk = rng.integers(0, 100, size=N * CAP, dtype=np.uint64).astype(np.uint32)
        pk = rng.integers(1000, 1100, size=N * CAP, dtype=np.uint64).astype(np.uint32)
        z2 = np.zeros((N * CAP, 2), np.int32)
        z1 = np.zeros((N * CAP, 1), np.int32)
        full = np.full(N, CAP, np.int32)
        rows, cnt = _collect_join(fn, mesh, bk, z2, full, pk, z1, full)
        assert rows == [] and cnt.sum() == 0

    def test_empty_sides(self, fn, mesh, rng):
        keys = rng.integers(0, 10, size=N * CAP, dtype=np.uint64).astype(np.uint32)
        z2 = np.zeros((N * CAP, 2), np.int32)
        z1 = np.zeros((N * CAP, 1), np.int32)
        zero = np.zeros(N, np.int32)
        full = np.full(N, CAP, np.int32)
        rows, _ = _collect_join(fn, mesh, keys, z2, zero, keys, z1, full)
        assert rows == []
        rows, _ = _collect_join(fn, mesh, keys, z2, full, keys, z1, zero)
        assert rows == []

    def test_sentinel_probe_key_skips_build_padding(self, fn, mesh):
        # build side: ONE valid KEY_MAX row + padding; a KEY_MAX probe must
        # match exactly the valid row, never the KEY_MAX-forced padding tail.
        bk = np.zeros(N * CAP, np.uint32)
        bk[0] = KEY_MAX
        bv = np.zeros((N * CAP, 2), np.int32)
        bv[0] = (11, 22)
        bn = np.zeros(N, np.int32)
        bn[0] = 1
        pk = np.full(N * CAP, KEY_MAX, np.uint32)
        pv = np.arange(N * CAP, dtype=np.int32)[:, None]
        pn = np.ones(N, np.int32)  # one probe row per shard
        rows, cnt = _collect_join(fn, mesh, bk, bv, bn, pk, pv, pn)
        assert cnt.sum() == N  # each of the N probe rows matched the single build row
        assert all(k == int(KEY_MAX) and b == (11, 22) for k, b, _ in rows)

    def test_overflow_reported_not_silent(self, mesh, rng):
        spec = JoinSpec(
            num_executors=N,
            build_capacity=CAP, build_recv_capacity=8 * CAP, build_width=1,
            probe_capacity=CAP, probe_recv_capacity=8 * CAP, probe_width=1,
            out_capacity=4, impl="dense",  # deliberately tiny output
        )
        f = build_hash_join(mesh, spec)
        keys = np.zeros(N * CAP, np.uint32)  # all rows share one key -> (N*CAP)^2/shard
        ones = np.ones((N * CAP, 1), np.int32)
        full = np.full(N, CAP, np.int32)
        _, _, _, cnt, rt = f(*_join_inputs(mesh, keys, ones, full, keys, ones, full))
        cnt = np.asarray(cnt)
        # the owning shard reports the true total, far beyond out_capacity
        assert cnt.max() == (N * CAP) ** 2

    def test_exchange_overflow_reported(self, mesh, rng):
        # every row hashes to ONE shard whose recv buffer is far too small:
        # recv_totals must report the true routed count, not the truncation.
        spec = JoinSpec(
            num_executors=N,
            build_capacity=CAP, build_recv_capacity=CAP // 4, build_width=1,
            probe_capacity=CAP, probe_recv_capacity=8 * CAP, probe_width=1,
            out_capacity=CAP, impl="dense",
        )
        f = build_hash_join(mesh, spec)
        keys = np.full(N * CAP, 5, np.uint32)
        ones = np.ones((N * CAP, 1), np.int32)
        full = np.full(N, CAP, np.int32)
        _, _, _, _, rt = f(*_join_inputs(mesh, keys, ones, full, keys, ones, full))
        assert np.asarray(rt)[:, 0].max() == N * CAP  # true total, > recv_capacity


class TestRunGroupedAggregate:
    """Host driver with automatic hash-skew retry (run_grouped_aggregate)."""

    def test_roundtrip_vs_oracle(self, rng):
        from sparkucx_tpu.ops.exchange import make_mesh
        from sparkucx_tpu.ops.relational import (
            AggregateSpec, oracle_aggregate, run_grouped_aggregate,
        )

        n, total = 4, 3000
        keys = rng.integers(0, 50, size=total).astype(np.uint32)
        values = rng.integers(-99, 99, size=(total, 2)).astype(np.int32)
        spec = AggregateSpec(
            num_executors=n, capacity=1024, recv_capacity=1536,
            aggs=("sum", "max"), impl="dense",
        )
        gk, gv, gc = run_grouped_aggregate(make_mesh(n), spec, keys, values)
        ok, ov, oc = oracle_aggregate(keys, values, ("sum", "max"))
        assert np.array_equal(gk, ok)
        assert np.array_equal(gv, ov)
        assert np.array_equal(gc, oc)

    def test_single_hot_key_triggers_retry(self, rng):
        from sparkucx_tpu.ops.exchange import make_mesh
        from sparkucx_tpu.ops.relational import (
            AggregateSpec, oracle_aggregate, run_grouped_aggregate,
        )

        n, total = 4, 2000
        keys = np.full(total, 42, np.uint32)  # every row hashes to one shard
        values = rng.integers(0, 10, size=(total, 1)).astype(np.int32)
        spec = AggregateSpec(
            num_executors=n, capacity=512, recv_capacity=600,
            aggs=("sum",), impl="dense",
        )
        gk, gv, gc = run_grouped_aggregate(make_mesh(n), spec, keys, values)
        assert gk.tolist() == [42]
        assert gv[0, 0] == values.sum() and gc[0] == total


class TestFilterPushdown:
    """with_filter / with_filters: WHERE below the exchange, on device."""

    def test_aggregate_scattered_mask_vs_masked_oracle(self, mesh, rng):
        spec = AggregateSpec(
            num_executors=N, capacity=CAP, recv_capacity=4 * CAP,
            aggs=("sum", "min"), impl="dense", with_filter=True,
        )
        fn = build_grouped_aggregate(mesh, spec)
        keys = rng.integers(0, 12, size=N * CAP, dtype=np.uint64).astype(np.uint32)
        values = rng.integers(-100, 100, size=(N * CAP, 2)).astype(np.int32)
        nvalid = np.full(N, CAP, np.int32)
        mask = rng.random(N * CAP) < 0.4  # scattered, not a prefix
        gk, gv, gc, ng, rt = fn(
            _keys_sh(mesh, keys), _rows_sh(mesh, values), _keys_sh(mesh, nvalid),
            _keys_sh(mesh, mask),
        )
        assert int(np.asarray(rt).sum()) == int(mask.sum())
        gk = np.asarray(gk).reshape(N, -1)
        gv = np.asarray(gv).reshape(N, gk.shape[1], -1)
        gc = np.asarray(gc).reshape(N, -1)
        ng = np.asarray(ng)
        rows = [
            (int(gk[j, g]), (int(gv[j, g, 0]), int(gv[j, g, 1])), int(gc[j, g]))
            for j in range(N)
            for g in range(ng[j])
        ]
        wk, wv, wc = oracle_aggregate(keys[mask], values[mask], spec.aggs)
        assert sorted(rows) == sorted(
            (int(k), (int(v[0]), int(v[1])), int(c)) for k, v, c in zip(wk, wv, wc)
        )

    def test_all_rows_filtered_zero_groups(self, mesh, rng):
        spec = AggregateSpec(
            num_executors=N, capacity=CAP, recv_capacity=CAP,
            aggs=(), impl="dense", with_filter=True,
        )
        fn = build_grouped_aggregate(mesh, spec)
        keys = rng.integers(0, 5, size=N * CAP, dtype=np.uint64).astype(np.uint32)
        values = np.zeros((N * CAP, 0), np.int32)
        nvalid = np.full(N, CAP, np.int32)
        mask = np.zeros(N * CAP, bool)
        _, _, _, ng, rt = fn(
            _keys_sh(mesh, keys), _rows_sh(mesh, values), _keys_sh(mesh, nvalid),
            _keys_sh(mesh, mask),
        )
        assert int(np.asarray(ng).sum()) == 0
        assert int(np.asarray(rt).sum()) == 0

    def test_filtered_join_vs_masked_oracle(self, mesh, rng):
        bcap = pcap = 32
        bkeys = rng.integers(0, 20, size=N * bcap, dtype=np.uint64).astype(np.uint32)
        pkeys = rng.integers(0, 20, size=N * pcap, dtype=np.uint64).astype(np.uint32)
        bvals = rng.integers(-50, 50, size=(N * bcap, 1)).astype(np.int32)
        pvals = rng.integers(-50, 50, size=(N * pcap, 1)).astype(np.int32)
        bmask = rng.random(N * bcap) < 0.5
        pmask = rng.random(N * pcap) < 0.5
        spec = JoinSpec(
            num_executors=N,
            build_capacity=bcap, build_recv_capacity=N * bcap, build_width=1,
            probe_capacity=pcap, probe_recv_capacity=N * pcap, probe_width=1,
            out_capacity=4 * N * pcap,
            impl="dense", with_filters=True,
        )
        fn = build_hash_join(mesh, spec)
        ok, ob, op_, oc, rt = fn(
            _keys_sh(mesh, bkeys), _rows_sh(mesh, bvals),
            _keys_sh(mesh, np.full(N, bcap, np.int32)),
            _keys_sh(mesh, pkeys), _rows_sh(mesh, pvals),
            _keys_sh(mesh, np.full(N, pcap, np.int32)),
            _keys_sh(mesh, bmask), _keys_sh(mesh, pmask),
        )
        rt = np.asarray(rt)
        assert rt[:, 0].sum() == bmask.sum() and rt[:, 1].sum() == pmask.sum()
        oc = np.asarray(oc)
        ok, ob, op_ = np.asarray(ok), np.asarray(ob), np.asarray(op_)
        got = sorted(
            (int(ok[i]), int(ob[i, 0]), int(op_[i, 0]))
            for s in range(N)
            for i in range(s * spec.out_capacity, s * spec.out_capacity + int(oc[s]))
        )
        wk, wb, wp = oracle_join(bkeys[bmask], bvals[bmask], pkeys[pmask], pvals[pmask])
        assert got == sorted(zip(wk.tolist(), wb[:, 0].tolist(), wp[:, 0].tolist()))

    def test_driver_with_filter_and_mismatch_raise(self, mesh, rng):
        spec = AggregateSpec(
            num_executors=N, capacity=CAP, recv_capacity=4 * CAP,
            aggs=("sum",), impl="dense", with_filter=True,
        )
        total = 500
        keys = rng.integers(0, 10, size=total, dtype=np.uint64).astype(np.uint32)
        values = rng.integers(-100, 100, size=(total, 1)).astype(np.int32)
        mask = rng.random(total) < 0.3
        gk, gv, gc = run_grouped_aggregate(mesh, spec, keys, values, mask=mask)
        wk, wv, wc = oracle_aggregate(keys[mask], values[mask], spec.aggs)
        assert np.array_equal(gk, wk) and np.array_equal(gv, wv) and np.array_equal(gc, wc)
        # signature mismatches fail with a clear message, not a pjit error
        with pytest.raises(ValueError, match="with_filter"):
            run_grouped_aggregate(mesh, spec, keys, values)
        with pytest.raises(ValueError, match="with_filter"):
            run_grouped_aggregate(
                mesh, replace(spec, with_filter=False), keys, values, mask=mask
            )


class TestLeftOuterJoin:
    def test_left_outer_vs_oracle(self, mesh, rng):
        bkeys = rng.integers(0, 30, size=60, dtype=np.uint64).astype(np.uint32)
        pkeys = rng.integers(0, 60, size=200, dtype=np.uint64).astype(np.uint32)
        bvals = rng.integers(1, 50, size=(60, 2)).astype(np.int32)
        pvals = rng.integers(1, 50, size=(200, 1)).astype(np.int32)
        from sparkucx_tpu.ops.relational import run_hash_join

        jk, jb, jp, jm = run_hash_join(
            mesh, bkeys, bvals, pkeys, pvals, impl="dense", join_type="left_outer"
        )
        wk, wb, wp, wm = oracle_join(bkeys, bvals, pkeys, pvals, join_type="left_outer")
        got = sorted(
            (int(k), tuple(b.tolist()), tuple(p.tolist()), bool(m))
            for k, b, p, m in zip(jk, jb, jp, jm)
        )
        want = sorted(
            (int(k), tuple(b.tolist()), tuple(p.tolist()), bool(m))
            for k, b, p, m in zip(wk, wb, wp, wm)
        )
        assert got == want
        assert not np.asarray(jm).all()  # some rows really were null-extended

    def test_empty_build_side_all_null_extended(self, mesh, rng):
        from sparkucx_tpu.ops.relational import run_hash_join

        pkeys = rng.integers(0, 9, size=50, dtype=np.uint64).astype(np.uint32)
        pvals = rng.integers(1, 9, size=(50, 1)).astype(np.int32)
        jk, jb, jp, jm = run_hash_join(
            mesh,
            np.zeros(0, np.uint32), np.zeros((0, 1), np.int32),
            pkeys, pvals, impl="dense", join_type="left_outer",
        )
        assert len(jk) == 50 and not jm.any()
        assert (jb == 0).all()
        assert sorted(jk.tolist()) == sorted(pkeys.tolist())

    def test_inner_unchanged_by_default(self, mesh, rng):
        # join_type defaults to inner: no matched array, unmatched probes dropped
        from sparkucx_tpu.ops.relational import run_hash_join

        bkeys = np.array([1, 2], np.uint32)
        bvals = np.array([[10], [20]], np.int32)
        pkeys = np.array([2, 3, 2], np.uint32)
        pvals = np.array([[7], [8], [9]], np.int32)
        jk, jb, jp = run_hash_join(mesh, bkeys, bvals, pkeys, pvals, impl="dense")
        assert sorted(jk.tolist()) == [2, 2]

    def test_spec_validation(self):
        with pytest.raises(ValueError, match="join_type"):
            JoinSpec(
                num_executors=N,
                build_capacity=8, build_recv_capacity=8, build_width=1,
                probe_capacity=8, probe_recv_capacity=8, probe_width=1,
                out_capacity=8, impl="dense", join_type="cross",
            ).validate()


class TestPartialAggregate:
    """Map-side partial aggregation below the exchange (spec.partial) —
    Spark's HashAggregateExec(partial); results must be bit-identical to the
    unfused path for integer dtypes."""

    def test_bit_equality_with_unfused_fuzz(self, mesh, rng):
        from sparkucx_tpu.ops.relational import run_grouped_aggregate

        for trial in range(4):
            total = int(rng.integers(100, 2500))
            nkeys = int(rng.integers(1, 60))
            keys = rng.integers(0, nkeys, size=total).astype(np.uint32)
            values = rng.integers(-1000, 1000, size=(total, 3)).astype(np.int32)
            spec = AggregateSpec(
                num_executors=N, capacity=-(-total // N) + 8,
                recv_capacity=4 * max(32, -(-total // N)),
                aggs=("sum", "min", "max"), impl="dense",
            )
            fused = run_grouped_aggregate(mesh, replace(spec, partial=True), keys, values)
            plain = run_grouped_aggregate(mesh, spec, keys, values)
            for f, p in zip(fused, plain):
                np.testing.assert_array_equal(f, p)

    def test_hot_key_sends_one_partial_per_shard(self, mesh, rng):
        """The skew-mitigation property: a single hot key exchanges at most
        one partial row per shard, so recv_totals stays at N even for
        millions of raw rows."""
        spec = AggregateSpec(
            num_executors=N, capacity=CAP, recv_capacity=2 * N,
            aggs=("sum",), impl="dense", partial=True,
        )
        fn = build_grouped_aggregate(mesh, spec)
        keys = np.full(N * CAP, 99, np.uint32)  # one hot key everywhere
        values = np.ones((N * CAP, 1), np.int32)
        nvalid = np.full(N, CAP, np.int32)
        gk, gv, gc, ng, rt = fn(*_agg_inputs(mesh, keys, values, nvalid))
        assert int(np.asarray(rt).sum()) == N  # one partial per sender
        rows, _ = _collect_groups_raw(gk, gv, gc, ng)
        assert rows == {99: ([N * CAP], N * CAP)}

    def test_partial_with_filter_mask(self, mesh, rng):
        """Scattered WHERE masks compose with the partial path (the local
        sort must keep valid sentinel-keyed rows ahead of masked ones)."""
        spec = AggregateSpec(
            num_executors=N, capacity=CAP, recv_capacity=4 * CAP,
            aggs=("sum", "max"), impl="dense", with_filter=True, partial=True,
        )
        fn = build_grouped_aggregate(mesh, spec)
        keys = rng.integers(0, 10, size=N * CAP, dtype=np.uint64).astype(np.uint32)
        keys[rng.choice(N * CAP, size=17, replace=False)] = KEY_MAX
        values = rng.integers(-50, 50, size=(N * CAP, 2)).astype(np.int32)
        nvalid = np.full(N, CAP, np.int32)
        mask = rng.random(N * CAP) < 0.5
        gk, gv, gc, ng, rt = fn(
            _keys_sh(mesh, keys), _rows_sh(mesh, values), _keys_sh(mesh, nvalid),
            _keys_sh(mesh, mask),
        )
        rows, _ = _collect_groups_raw(gk, gv, gc, ng)
        wk, wv, wc = oracle_aggregate(keys[mask], values[mask], spec.aggs)
        assert sorted(rows) == list(wk)
        for k, v, c in zip(wk, wv, wc):
            got_v, got_c = rows[int(k)]
            np.testing.assert_array_equal(got_v, v)
            assert got_c == c

    def test_float_partials_compose(self, mesh, rng):
        """min/max float partials compose exactly (no reassociation), and the
        bitcast count lane survives a float dtype."""
        spec = AggregateSpec(
            num_executors=N, capacity=CAP, recv_capacity=4 * CAP,
            aggs=("min", "max"), dtype=np.dtype(np.float32),
            impl="dense", partial=True,
        )
        fn = build_grouped_aggregate(mesh, spec)
        keys = rng.integers(0, 16, size=N * CAP, dtype=np.uint64).astype(np.uint32)
        values = rng.normal(size=(N * CAP, 2)).astype(np.float32)
        rows, _ = _collect_groups_raw(
            *fn(*_agg_inputs(mesh, keys, values, np.full(N, CAP, np.int32)))[:4]
        )
        wk, wv, wc = oracle_aggregate(keys, values, spec.aggs)
        for k, v, c in zip(wk, wv, wc):
            got_v, got_c = rows[int(k)]
            np.testing.assert_array_equal(np.asarray(got_v, np.float32), v)
            assert got_c == c  # counts rode the bitcast lane exactly


def _collect_groups_raw(gk, gv, gc, ng, *_):
    """_collect_groups without the fn call — for tests that already ran it."""
    gk = np.asarray(gk).reshape(N, -1)
    gv = np.asarray(gv).reshape(N, gk.shape[1], -1)
    gc = np.asarray(gc).reshape(N, -1)
    ng = np.asarray(ng)
    rows = {}
    for j in range(N):
        for g in range(ng[j]):
            k = int(gk[j, g])
            assert k not in rows, "key appeared on two shards"
            rows[k] = (list(gv[j, g]), int(gc[j, g]))
    return rows, ng


class TestAvgCountDistinct:
    def test_avg_fused_vs_oracle(self, mesh, rng):
        from sparkucx_tpu.ops.relational import run_grouped_aggregate

        total = 3000
        keys = rng.integers(0, 40, size=total).astype(np.uint32)
        values = rng.integers(-500, 500, size=(total, 2)).astype(np.int32)
        spec = AggregateSpec(
            num_executors=N, capacity=512, recv_capacity=1024,
            aggs=("avg", "sum"), impl="dense",
        )
        gk, gv, gc = run_grouped_aggregate(mesh, spec, keys, values)
        wk, wv, wc = oracle_aggregate(keys, values, spec.aggs)
        assert gv.dtype == np.float64 and wv.dtype == np.float64
        np.testing.assert_array_equal(gk, wk)
        np.testing.assert_array_equal(gv, wv)  # exact: int sums / int counts
        np.testing.assert_array_equal(gc, wc)

    def test_avg_composes_with_partial(self, mesh, rng):
        from sparkucx_tpu.ops.relational import run_grouped_aggregate

        total = 2000
        keys = rng.integers(0, 25, size=total).astype(np.uint32)
        values = rng.integers(-99, 99, size=(total, 1)).astype(np.int32)
        spec = AggregateSpec(
            num_executors=N, capacity=512, recv_capacity=1024,
            aggs=("avg",), impl="dense",
        )
        fused = run_grouped_aggregate(mesh, replace(spec, partial=True), keys, values)
        plain = run_grouped_aggregate(mesh, spec, keys, values)
        for f, p in zip(fused, plain):
            np.testing.assert_array_equal(f, p)

    def test_count_distinct_vs_oracle(self, mesh, rng):
        from sparkucx_tpu.ops.relational import run_grouped_aggregate

        total = 2500
        keys = rng.integers(0, 30, size=total).astype(np.uint32)
        # few distinct values -> heavy duplication inside groups
        values = rng.integers(0, 12, size=(total, 2)).astype(np.int32)
        values[:, 1] = rng.integers(-3, 3, size=total)
        spec = AggregateSpec(
            num_executors=N, capacity=512, recv_capacity=1024,
            aggs=("count_distinct", "count_distinct"), impl="dense",
        )
        gk, gv, gc = run_grouped_aggregate(mesh, spec, keys, values)
        wk, wv, wc = oracle_aggregate(keys, values, spec.aggs)
        np.testing.assert_array_equal(gk, wk)
        np.testing.assert_array_equal(gv, wv)
        np.testing.assert_array_equal(gc, wc)

    def test_count_distinct_sentinel_and_mask(self, mesh, rng):
        """count_distinct with scattered masks and KEY_MAX keys (the lexsort
        numbering must stay aligned with the main segment numbering)."""
        from sparkucx_tpu.ops.relational import run_grouped_aggregate

        total = 1200
        keys = rng.integers(0, 8, size=total).astype(np.uint32)
        keys[rng.choice(total, size=21, replace=False)] = KEY_MAX
        values = rng.integers(0, 5, size=(total, 1)).astype(np.int32)
        mask = rng.random(total) < 0.6
        spec = AggregateSpec(
            num_executors=N, capacity=256, recv_capacity=1024,
            aggs=("count_distinct",), impl="dense", with_filter=True,
        )
        gk, gv, gc = run_grouped_aggregate(mesh, spec, keys, values, mask=mask)
        wk, wv, wc = oracle_aggregate(keys[mask], values[mask], spec.aggs)
        np.testing.assert_array_equal(gk, wk)
        np.testing.assert_array_equal(gv, wv)
        np.testing.assert_array_equal(gc, wc)


class TestRightFullOuterJoin:
    def _check(self, mesh, rng, join_type, bkeys, bvals, pkeys, pvals):
        from sparkucx_tpu.ops.relational import run_hash_join

        jk, jb, jp, jm = run_hash_join(
            mesh, bkeys, bvals, pkeys, pvals, impl="dense", join_type=join_type
        )
        wk, wb, wp, wm = oracle_join(bkeys, bvals, pkeys, pvals, join_type=join_type)
        got = sorted(
            (int(k), tuple(b.tolist()), tuple(p.tolist()), bool(m))
            for k, b, p, m in zip(jk, jb, jp, jm)
        )
        want = sorted(
            (int(k), tuple(b.tolist()), tuple(p.tolist()), bool(m))
            for k, b, p, m in zip(wk, wb, wp, wm)
        )
        assert got == want
        return jm

    def test_right_outer_vs_oracle(self, mesh, rng):
        bkeys = rng.integers(0, 60, size=80, dtype=np.uint64).astype(np.uint32)
        pkeys = rng.integers(0, 30, size=150, dtype=np.uint64).astype(np.uint32)
        bvals = rng.integers(1, 50, size=(80, 2)).astype(np.int32)
        pvals = rng.integers(1, 50, size=(150, 1)).astype(np.int32)
        jm = self._check(mesh, rng, "right_outer", bkeys, bvals, pkeys, pvals)
        assert not jm.all()  # some build rows really were unmatched

    def test_full_outer_vs_oracle(self, mesh, rng):
        # disjoint key halves guarantee null-extensions on BOTH sides
        bkeys = rng.integers(0, 40, size=70, dtype=np.uint64).astype(np.uint32)
        pkeys = rng.integers(20, 60, size=90, dtype=np.uint64).astype(np.uint32)
        bvals = rng.integers(1, 9, size=(70, 1)).astype(np.int32)
        pvals = rng.integers(1, 9, size=(90, 2)).astype(np.int32)
        jm = self._check(mesh, rng, "full_outer", bkeys, bvals, pkeys, pvals)
        assert not jm.all()

    def test_full_outer_preserves_every_row(self, mesh, rng):
        """Row-conservation law: inner matches + probe-unmatched +
        build-unmatched = full outer output."""
        from sparkucx_tpu.ops.relational import run_hash_join

        bkeys = rng.integers(0, 20, size=50, dtype=np.uint64).astype(np.uint32)
        pkeys = rng.integers(10, 30, size=60, dtype=np.uint64).astype(np.uint32)
        bvals = rng.integers(1, 9, size=(50, 1)).astype(np.int32)
        pvals = rng.integers(1, 9, size=(60, 1)).astype(np.int32)
        inner = run_hash_join(mesh, bkeys, bvals, pkeys, pvals, impl="dense")
        full = run_hash_join(
            mesh, bkeys, bvals, pkeys, pvals, impl="dense", join_type="full_outer"
        )
        p_unmatched = (~np.isin(pkeys, bkeys)).sum()
        b_unmatched = (~np.isin(bkeys, pkeys)).sum()
        assert len(full[0]) == len(inner[0]) + p_unmatched + b_unmatched

    def test_right_outer_empty_probe_side(self, mesh, rng):
        from sparkucx_tpu.ops.relational import run_hash_join

        bkeys = rng.integers(0, 9, size=40, dtype=np.uint64).astype(np.uint32)
        bvals = rng.integers(1, 9, size=(40, 2)).astype(np.int32)
        jk, jb, jp, jm = run_hash_join(
            mesh,
            bkeys, bvals,
            np.zeros(0, np.uint32), np.zeros((0, 1), np.int32),
            impl="dense", join_type="right_outer",
        )
        assert len(jk) == 40 and not jm.any()
        assert (jp == 0).all()
        assert sorted(jk.tolist()) == sorted(bkeys.tolist())

    def test_sentinel_build_key_full_outer(self, mesh):
        """Valid KEY_MAX build rows must null-extend exactly once each, never
        be confused with probe-side padding."""
        from sparkucx_tpu.ops.relational import run_hash_join

        bkeys = np.array([KEY_MAX, 3], np.uint32)
        bvals = np.array([[111], [333]], np.int32)
        pkeys = np.array([3, 4], np.uint32)
        pvals = np.array([[30], [40]], np.int32)
        jk, jb, jp, jm = run_hash_join(
            mesh, bkeys, bvals, pkeys, pvals, impl="dense", join_type="full_outer"
        )
        rows = sorted(zip(jk.tolist(), jb[:, 0].tolist(), jp[:, 0].tolist(), jm.tolist()))
        assert rows == [
            (3, 333, 30, True),          # the inner match
            (4, 0, 40, False),           # probe-side null extension
            (int(KEY_MAX), 111, 0, False),  # build-side null extension
        ]


class TestSemiAntiJoin:
    def test_semi_and_anti_partition_the_probe(self, mesh, rng):
        """Semi + anti outputs together must be exactly the probe rows, split
        by match existence — EXISTS / NOT EXISTS (TPC-H q4/q21/q22)."""
        from sparkucx_tpu.ops.relational import run_hash_join

        bkeys = rng.integers(0, 25, size=40, dtype=np.uint64).astype(np.uint32)
        pkeys = rng.integers(0, 50, size=150, dtype=np.uint64).astype(np.uint32)
        bvals = rng.integers(1, 9, size=(40, 1)).astype(np.int32)
        pvals = rng.integers(1, 9, size=(150, 2)).astype(np.int32)

        semi = run_hash_join(
            mesh, bkeys, bvals, pkeys, pvals, impl="dense", join_type="left_semi"
        )
        anti = run_hash_join(
            mesh, bkeys, bvals, pkeys, pvals, impl="dense", join_type="left_anti"
        )
        for got, jt in ((semi, "left_semi"), (anti, "left_anti")):
            wk, wb, wp = oracle_join(bkeys, bvals, pkeys, pvals, join_type=jt)
            assert sorted(
                (int(k), tuple(p.tolist())) for k, p in zip(got[0], got[2])
            ) == sorted((int(k), tuple(p.tolist())) for k, p in zip(wk, wp)), jt
            assert (got[1] == 0).all(), f"{jt} must zero build lanes"
        # the partition property
        exists = np.isin(pkeys, bkeys)
        assert len(semi[0]) == exists.sum()
        assert len(anti[0]) == (~exists).sum()
        assert len(semi[0]) + len(anti[0]) == len(pkeys)

    def test_semi_emits_each_probe_row_once(self, mesh, rng):
        # heavy build duplication must not multiply semi output
        from sparkucx_tpu.ops.relational import run_hash_join

        bkeys = np.full(90, 7, np.uint32)  # 90 build rows, one key
        bvals = np.arange(90, dtype=np.int32)[:, None]
        pkeys = np.array([7, 7, 8], np.uint32)
        pvals = np.array([[1], [2], [3]], np.int32)
        jk, jb, jp = run_hash_join(
            mesh, bkeys, bvals, pkeys, pvals, impl="dense", join_type="left_semi"
        )
        assert sorted(jp[:, 0].tolist()) == [1, 2]  # the two key-7 probe rows, once each


class TestAggregateSpecFromConf:
    """conf.partial_aggregation enters plans through from_conf — the
    partialAggregation Spark key must actually change the compiled spec."""

    def test_conf_defaults_flow_into_spec(self):
        from sparkucx_tpu.config import TpuShuffleConf

        conf = TpuShuffleConf(num_executors=4)
        spec = AggregateSpec.from_conf(conf, capacity=8, recv_capacity=32, aggs=("sum",))
        assert spec.partial is True  # the documented on-by-default
        assert spec.num_executors == 4
        assert spec.axis_name == conf.mesh_axis_name
        off = AggregateSpec.from_conf(
            TpuShuffleConf(partial_aggregation=False),
            num_executors=2, capacity=8, recv_capacity=32, aggs=("sum",),
        )
        assert off.partial is False
        spec.resolve_impl("cpu").validate()
        off.resolve_impl("cpu").validate()

    def test_explicit_kwargs_win(self):
        from sparkucx_tpu.config import TpuShuffleConf

        spec = AggregateSpec.from_conf(
            TpuShuffleConf(), num_executors=2, capacity=8, recv_capacity=32,
            aggs=("sum",), partial=False,
        )
        assert spec.partial is False

    def test_count_distinct_auto_disables_partial(self):
        from sparkucx_tpu.config import TpuShuffleConf

        spec = AggregateSpec.from_conf(
            TpuShuffleConf(), num_executors=2, capacity=8, recv_capacity=32,
            aggs=("sum", "count_distinct"),
        )
        assert spec.partial is False
        # must not raise despite conf partial_aggregation=True
        spec.resolve_impl("cpu").validate()
