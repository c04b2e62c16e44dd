"""TPC-H-style query pipelines over the device relational operators.

BASELINE.md lists "Spark SQL TPC-H q5/q18" as workload configs.  These tests
run miniature versions of both physical plans — the same operator DAG at small
scale — entirely through the device GROUP BY / hash-join primitives, with host
stage boundaries where Spark would have its own (each stage's output is the
next stage's shuffle input), verified against a numpy oracle.

Which path this is: the operators' OWN SPMD exchange (``ops/relational.py``
``build_grouped_aggregate`` / ``build_hash_join`` over eight virtual devices,
128 rows a shard, ``uint32`` keys) — no manager, no store, no served shuffle.
The served path with the operators on the chip at the source's sizes is the
benchmark's cell ``q18sf10-queryjobs-1chip`` (``QueryRunner``'s batch lane,
``query/batch.py``; ``tests/test_query_batch.py`` is its tier-1 form).
"""

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from sparkucx_tpu.ops.exchange import make_mesh
from sparkucx_tpu.ops.relational import (
    AggregateSpec,
    JoinSpec,
    build_grouped_aggregate,
    build_hash_join,
)

N = 8
CAP = 128


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(N)


def _pad_table(keys, values, cap_per_shard):
    """Scatter rows round-robin over N shards as prefix-valid padded arrays —
    the stage-boundary materialization (each stage's input layout)."""
    width = values.shape[1]
    k = np.zeros(N * cap_per_shard, np.uint32)
    v = np.zeros((N * cap_per_shard, width), np.int32)
    nvalid = np.zeros(N, np.int32)
    for i, (ki, vi) in enumerate(zip(keys, values)):
        j = i % N
        assert nvalid[j] < cap_per_shard, "test table too big for capacity"
        k[j * cap_per_shard + nvalid[j]] = ki
        v[j * cap_per_shard + nvalid[j]] = vi
        nvalid[j] += 1
    return k, v, nvalid


def _shard(mesh, k, v, n):
    return (
        jax.device_put(k, NamedSharding(mesh, P("ex"))),
        jax.device_put(v, NamedSharding(mesh, P("ex", None))),
        jax.device_put(n, NamedSharding(mesh, P("ex"))),
    )


def _groups_to_host(gk, gv, gc, ng, rt, recv_capacity):
    assert np.all(np.asarray(rt) <= recv_capacity), "exchange overflowed"
    gk = np.asarray(gk).reshape(N, -1)
    gv = np.asarray(gv).reshape(N, gk.shape[1], -1)
    gc = np.asarray(gc).reshape(N, -1)
    ng = np.asarray(ng)
    keys = np.concatenate([gk[j, : ng[j]] for j in range(N)])
    vals = np.concatenate([gv[j, : ng[j]] for j in range(N)])
    cnts = np.concatenate([gc[j, : ng[j]] for j in range(N)])
    return keys, vals, cnts


def _join_to_host(ok, ob, op, cnt, rt):
    ok = np.asarray(ok).reshape(N, -1)
    ob = np.asarray(ob).reshape(N, ok.shape[1], -1)
    op = np.asarray(op).reshape(N, ok.shape[1], -1)
    cnt = np.asarray(cnt)
    assert np.all(cnt <= ok.shape[1]), "join output overflowed out_capacity"
    keys = np.concatenate([ok[j, : cnt[j]] for j in range(N)])
    b = np.concatenate([ob[j, : cnt[j]] for j in range(N)])
    p = np.concatenate([op[j, : cnt[j]] for j in range(N)])
    return keys, b, p


def test_q18_large_volume_orders(mesh, rng):
    """Q18 shape: GROUP BY lineitem.orderkey HAVING sum(qty) > T, then join
    the qualifying aggregates with orders."""
    num_orders = 300
    lineitems = 4000
    threshold = 60

    l_orderkey = rng.integers(0, num_orders, size=lineitems, dtype=np.uint64).astype(np.uint32)
    l_quantity = rng.integers(1, 20, size=(lineitems, 1), dtype=np.int64).astype(np.int32)
    o_orderkey = np.arange(num_orders, dtype=np.uint32)
    o_vals = np.stack(
        [rng.integers(0, 50, num_orders), rng.integers(100, 9000, num_orders)], axis=1
    ).astype(np.int32)  # (custkey, totalprice)

    # Stage 1 (device): GROUP BY orderkey SUM(quantity)
    agg = build_grouped_aggregate(
        mesh,
        AggregateSpec(
            num_executors=N, capacity=-(-lineitems // N), recv_capacity=lineitems,
            aggs=("sum",), impl="dense",
        ),
    )
    out = agg(*_shard(mesh, *_pad_table(l_orderkey, l_quantity, -(-lineitems // N))))
    keys, sums, _ = _groups_to_host(*out, recv_capacity=agg.spec.recv_capacity)

    # Stage 2 (host stage boundary): HAVING sum > T
    qual = sums[:, 0] > threshold
    hk, hv = keys[qual], sums[qual]

    # Stage 3 (device): join qualifying aggregates with orders on orderkey
    join = build_hash_join(
        mesh,
        JoinSpec(
            num_executors=N,
            build_capacity=-(-num_orders // N), build_recv_capacity=num_orders, build_width=1,
            probe_capacity=-(-num_orders // N), probe_recv_capacity=num_orders, probe_width=2,
            out_capacity=num_orders, impl="dense",
        ),
    )
    bk, bv, bn = _pad_table(hk, hv, -(-num_orders // N))
    pk, pv, pn = _pad_table(o_orderkey, o_vals, -(-num_orders // N))
    jk, jb, jp = _join_to_host(*join(*_shard(mesh, bk, bv, bn), *_shard(mesh, pk, pv, pn)))

    # Oracle (pure numpy over the same inputs)
    want_sums = np.bincount(l_orderkey, weights=l_quantity[:, 0], minlength=num_orders)
    want_qual = {int(k) for k in np.nonzero(want_sums > threshold)[0]}
    assert {int(k) for k in hk} == want_qual
    assert {int(k) for k in jk} == want_qual  # orders has every orderkey exactly once
    for k, b, p in zip(jk, jb, jp):
        assert b[0] == want_sums[int(k)]
        np.testing.assert_array_equal(p, o_vals[int(k)])


def test_q5_multi_join_then_group(mesh, rng):
    """Q5 shape: customer ⋈ orders on custkey, re-key to orderkey, ⋈ lineitem,
    then GROUP BY nationkey SUM(revenue)."""
    num_cust, num_orders, lineitems, num_nations = 120, 250, 2500, 12

    c_custkey = np.arange(num_cust, dtype=np.uint32)
    c_nation = rng.integers(0, num_nations, size=(num_cust, 1), dtype=np.int64).astype(np.int32)
    o_custkey = rng.integers(0, num_cust, size=num_orders, dtype=np.uint64).astype(np.uint32)
    o_orderkey = np.arange(num_orders, dtype=np.int32)[:, None]
    l_orderkey = rng.integers(0, num_orders, size=lineitems, dtype=np.uint64).astype(np.uint32)
    l_revenue = rng.integers(1, 500, size=(lineitems, 1), dtype=np.int64).astype(np.int32)

    # Stage 1 (device): customer ⋈ orders on custkey -> (custkey, nation, orderkey)
    join1 = build_hash_join(
        mesh,
        JoinSpec(
            num_executors=N,
            build_capacity=-(-num_cust // N), build_recv_capacity=num_cust, build_width=1,
            probe_capacity=-(-num_orders // N), probe_recv_capacity=num_orders, probe_width=1,
            out_capacity=num_orders, impl="dense",
        ),
    )
    _, nation_col, orderkey_col = _join_to_host(
        *join1(
            *_shard(mesh, *_pad_table(c_custkey, c_nation, -(-num_cust // N))),
            *_shard(mesh, *_pad_table(o_custkey, o_orderkey, -(-num_orders // N))),
        )
    )

    # Stage 2 (host boundary): re-key by orderkey, carry nation
    stage2_keys = orderkey_col[:, 0].astype(np.uint32)
    stage2_vals = nation_col.astype(np.int32)

    # Stage 3 (device): ⋈ lineitem on orderkey -> (orderkey, nation, revenue)
    join2 = build_hash_join(
        mesh,
        JoinSpec(
            num_executors=N,
            build_capacity=-(-num_orders // N), build_recv_capacity=num_orders, build_width=1,
            probe_capacity=-(-lineitems // N), probe_recv_capacity=lineitems, probe_width=1,
            out_capacity=lineitems, impl="dense",
        ),
    )
    _, nation2, revenue2 = _join_to_host(
        *join2(
            *_shard(mesh, *_pad_table(stage2_keys, stage2_vals, -(-num_orders // N))),
            *_shard(mesh, *_pad_table(l_orderkey, l_revenue, -(-lineitems // N))),
        )
    )

    # Stage 4 (device): GROUP BY nation SUM(revenue)
    agg = build_grouped_aggregate(
        mesh,
        AggregateSpec(
            num_executors=N, capacity=-(-lineitems // N), recv_capacity=lineitems,
            aggs=("sum",), impl="dense",
        ),
    )
    out = agg(
        *_shard(
            mesh, *_pad_table(nation2[:, 0].astype(np.uint32), revenue2, -(-lineitems // N))
        )
    )
    keys, sums, _ = _groups_to_host(*out, recv_capacity=agg.spec.recv_capacity)
    got = {int(k): int(s) for k, s in zip(keys, sums[:, 0])}

    # Oracle: pure numpy joins
    nation_of_order = c_nation[o_custkey, 0]          # orders ⋈ customer
    nation_of_line = nation_of_order[l_orderkey]      # lineitem ⋈ orders
    want = {}
    for nk, rev in zip(nation_of_line, l_revenue[:, 0]):
        want[int(nk)] = want.get(int(nk), 0) + int(rev)
    assert got == want


def test_q1_pricing_summary(mesh, rng):
    """q1 shape: pure grouped aggregation, several agg columns at once over a
    tiny key domain (returnflag/linestatus combos) — the no-join plan."""
    rows = 600
    # 6 distinct (returnflag, linestatus) combos, encoded as one uint32 key
    flags = rng.integers(0, 6, size=rows).astype(np.uint32)
    qty = rng.integers(1, 51, size=rows).astype(np.int32)
    price = rng.integers(100, 10000, size=rows).astype(np.int32)
    disc = rng.integers(0, 10, size=rows).astype(np.int32)
    values = np.stack([qty, price, disc, qty], axis=1)  # sum, sum, min, max

    spec = AggregateSpec(
        num_executors=N, capacity=CAP, recv_capacity=4 * CAP,
        aggs=("sum", "sum", "min", "max"),
    )
    fn = build_grouped_aggregate(mesh, spec)
    k, v, nv = _pad_table(flags, values, CAP)
    gk, gv, gc, ng, rt = fn(*_shard(mesh, k, v, nv))
    keys, vals, cnts = _groups_to_host(gk, gv, gc, ng, rt, spec.recv_capacity)

    order = np.argsort(keys)
    keys, vals, cnts = keys[order], vals[order], cnts[order]
    assert np.array_equal(keys, np.arange(6, dtype=np.uint32))
    for f in range(6):
        m = flags == f
        assert vals[f, 0] == qty[m].sum(), "sum_qty"
        assert vals[f, 1] == price[m].sum(), "sum_price"
        assert vals[f, 2] == disc[m].min(), "min_disc"
        assert vals[f, 3] == qty[m].max(), "max_qty"
        assert cnts[f] == m.sum(), "count_order"


def test_q3_join_group_topk(mesh, rng):
    """q3 shape: customer⋈orders filter-join, then GROUP BY order with SUM
    (revenue), then host-side top-k — join feeding aggregation feeding sort."""
    n_cust, n_orders = 40, 300
    # build side: customers in the BUILDING segment (the filter), value = custkey
    seg_custs = np.sort(rng.choice(n_cust, size=n_cust // 2, replace=False)).astype(np.uint32)
    cust_vals = seg_custs.astype(np.int32)[:, None]
    # probe side: orders keyed by custkey, value = (orderkey, revenue)
    order_cust = rng.integers(0, n_cust, size=n_orders).astype(np.uint32)
    order_key = np.arange(n_orders, dtype=np.int32)
    # unique revenues: the top-k cut is unambiguous regardless of seed
    revenue = (rng.permutation(n_orders) + 1).astype(np.int32)
    probe_vals = np.stack([order_key, revenue], axis=1)

    jspec = JoinSpec(
        num_executors=N,
        build_capacity=CAP, build_recv_capacity=2 * CAP, build_width=1,
        probe_capacity=CAP, probe_recv_capacity=2 * CAP, probe_width=2,
        out_capacity=2 * CAP,
    )
    jfn = build_hash_join(mesh, jspec)
    bk, bv, bn = _pad_table(seg_custs, cust_vals, CAP)
    pk, pv, pn = _pad_table(order_cust, probe_vals, CAP)
    ok, ob, op, cnt, rt = jfn(*_shard(mesh, bk, bv, bn), *_shard(mesh, pk, pv, pn))
    jkeys, _, jprobe = _join_to_host(ok, ob, op, cnt, rt)

    # stage 2: GROUP BY orderkey, SUM(revenue) over the join output
    aspec = AggregateSpec(
        num_executors=N, capacity=2 * CAP, recv_capacity=4 * CAP, aggs=("sum",)
    )
    afn = build_grouped_aggregate(mesh, aspec)
    ak, av, an = _pad_table(
        jprobe[:, 0].astype(np.uint32), jprobe[:, 1:2], 2 * CAP
    )
    gk, gv, gc, ng, art = afn(*_shard(mesh, ak, av, an))
    keys, vals, _ = _groups_to_host(gk, gv, gc, ng, art, aspec.recv_capacity)

    # stage 3 (host, like Spark's TakeOrdered): top-5 by revenue
    top = np.argsort(-vals[:, 0], kind="stable")[:5]
    got = {(int(keys[i]), int(vals[i, 0])) for i in top}

    # oracle
    in_seg = np.isin(order_cust, seg_custs)
    o_keys, o_rev = order_key[in_seg], revenue[in_seg]
    want_sorted = sorted(zip(o_rev, o_keys), reverse=True)[:5]
    want = {(int(k), int(r)) for r, k in want_sorted}
    assert got == want


def test_q6_forecast_revenue_filtered_aggregate(mesh, rng):
    """q6 shape: scan -> FILTER -> global aggregate, no join — the WHERE
    clause (shipdate/discount/quantity band) pushed down on device via
    ``AggregateSpec.with_filter`` instead of pre-filtering the host table."""
    rows = 700
    qty = rng.integers(1, 60, size=rows).astype(np.int32)
    disc = rng.integers(0, 11, size=rows).astype(np.int32)
    price = rng.integers(100, 10000, size=rows).astype(np.int32)
    revenue = price * disc  # the summed expression, precomputed as a lane
    values = np.stack([revenue], axis=1)
    keys = np.zeros(rows, np.uint32)  # global aggregate: one group

    spec = AggregateSpec(
        num_executors=N, capacity=CAP, recv_capacity=4 * CAP,
        aggs=("sum",), with_filter=True,
    )
    fn = build_grouped_aggregate(mesh, spec)
    k, v, nv = _pad_table(keys, values, CAP)
    predicate = (qty < 24) & (disc >= 5) & (disc <= 7)  # the q6 band
    # mask rows land where _pad_table dealt them: row i -> shard i % N, slot i // N
    m = np.zeros(N * CAP, bool)
    idx = np.arange(rows)
    m[(idx % N) * CAP + idx // N] = predicate
    gk, gv, gc, ng, rt = fn(
        *_shard(mesh, k, v, nv),
        jax.device_put(m, NamedSharding(mesh, P("ex"))),
    )
    keys_h, vals_h, cnts_h = _groups_to_host(gk, gv, gc, ng, rt, spec.recv_capacity)
    if predicate.any():
        assert len(keys_h) == 1 and keys_h[0] == 0
        assert vals_h[0, 0] == revenue[predicate].sum()
        assert cnts_h[0] == predicate.sum()
    else:  # pragma: no cover - rng never produces this at rows=700
        assert len(keys_h) == 0
    # recv totals count only unfiltered rows: the filter saved exchange traffic
    assert np.asarray(rt).sum() == predicate.sum()


def test_q13_customer_order_distribution(mesh, rng):
    """q13 shape: customer LEFT OUTER JOIN orders (customers with zero orders
    must appear), COUNT(orders) per customer, then the count-of-counts
    distribution — the query the left-outer arm exists for."""
    from sparkucx_tpu.ops.relational import run_grouped_aggregate, run_hash_join

    n_cust, n_orders = 80, 400
    custkeys = np.arange(n_cust, dtype=np.uint32)
    cust_vals = np.zeros((n_cust, 1), np.int32)
    # ~25% of customers get no orders at all
    ordering_custs = custkeys[rng.random(n_cust) < 0.75]
    order_cust = ordering_custs[rng.integers(0, len(ordering_custs), size=n_orders)].astype(np.uint32)
    order_vals = np.ones((n_orders, 1), np.int32)

    # probe = customer (the preserved SQL-left side), build = orders
    jk, jb, jp, jm = run_hash_join(
        mesh, order_cust, order_vals, custkeys, cust_vals,
        impl="dense", join_type="left_outer",
    )
    # COUNT(o_orderkey) per customer = matched rows only (NULLs don't count)
    spec = AggregateSpec(
        num_executors=N, capacity=-(-len(jk) // N), recv_capacity=4 * -(-len(jk) // N),
        aggs=("sum",),
    )
    gk, gv, gc = run_grouped_aggregate(
        mesh, spec, jk, jm.astype(np.int32)[:, None]
    )
    # oracle: orders per customer, including zeros
    want = np.bincount(order_cust, minlength=n_cust)
    assert np.array_equal(gk, custkeys)          # every customer present
    assert np.array_equal(gv[:, 0], want)        # COUNT per customer
    # the q13 output: distribution of customers by order count
    dist_keys, dist_counts = np.unique(gv[:, 0], return_counts=True)
    assert dist_counts.sum() == n_cust
    assert (want == 0).sum() == dist_counts[dist_keys == 0].sum()


def test_q4_order_priority_semi_join(mesh, rng):
    """q4 shape: orders SEMI JOIN lineitem (EXISTS a late lineitem), the
    lineitem predicate pushed down as a filter mask, then GROUP BY
    o_orderpriority COUNT(*) — semi join + WHERE pushdown composed."""
    from sparkucx_tpu.ops.columnar import shard_rows_host
    from sparkucx_tpu.ops.relational import run_grouped_aggregate

    num_orders, lineitems = 120, 900
    o_orderkey = np.arange(num_orders, dtype=np.uint32)
    o_priority = rng.integers(0, 5, size=num_orders).astype(np.int32)
    l_orderkey = rng.integers(0, num_orders, size=lineitems, dtype=np.uint64).astype(np.uint32)
    l_late = rng.random(lineitems) < 0.3  # commitdate < receiptdate

    # device semi join with the lineitem filter below the build exchange
    bcap = -(-lineitems // N)
    pcap = -(-num_orders // N)
    spec = JoinSpec(
        num_executors=N,
        build_capacity=bcap, build_recv_capacity=lineitems, build_width=1,
        probe_capacity=pcap, probe_recv_capacity=num_orders, probe_width=1,
        out_capacity=num_orders, impl="dense",
        with_filters=True, join_type="left_semi",
    )
    fn = build_hash_join(mesh, spec)
    bk, bv, bn = shard_rows_host(l_orderkey, np.zeros((lineitems, 1), np.int32), N, bcap)
    bm, _, _ = shard_rows_host(l_late.astype(np.uint32), np.zeros((lineitems, 0), np.int32), N, bcap)
    pk, pv, pn = shard_rows_host(o_orderkey, o_priority[:, None], N, pcap)
    out = fn(
        *_shard(mesh, bk, bv, bn), *_shard(mesh, pk, pv, pn),
        jax.device_put(bm.astype(bool), NamedSharding(mesh, P("ex"))),
        jax.device_put(np.ones(N * pcap, bool), NamedSharding(mesh, P("ex"))),
    )
    jk, _, jp = _join_to_host(*out[:4], out[4])

    # GROUP BY priority COUNT(*) over the qualifying orders
    agg_spec = AggregateSpec(
        num_executors=N, capacity=-(-max(len(jk), 1) // N),
        recv_capacity=4 * -(-max(len(jk), 1) // N), aggs=(),
    )
    gk, gv, gc = run_grouped_aggregate(
        mesh, agg_spec, jp[:, 0].astype(np.uint32), np.zeros((len(jk), 0), np.int32)
    )

    # numpy oracle: orders with >= 1 late lineitem, counted by priority
    exists = np.isin(o_orderkey, np.unique(l_orderkey[l_late]))
    want_k, want_c = np.unique(o_priority[exists], return_counts=True)
    assert np.array_equal(gk, want_k.astype(np.uint32))
    assert np.array_equal(gc, want_c)


def test_q16_supplier_count_distinct_with_exclusion(mesh, rng):
    """q16 shape: COUNT(DISTINCT ps_suppkey) GROUP BY part attributes, after
    excluding complained-about suppliers — a NOT IN anti join feeding a
    count-distinct aggregation (both round-5 vocabulary arms, composed the
    way the real plan composes them)."""
    from sparkucx_tpu.ops.relational import (
        oracle_aggregate,
        run_grouped_aggregate,
        run_hash_join,
    )

    n_parts, n_suppliers = 40, 60
    rows = 800
    # partsupp: (partkey, suppkey) pairs with duplication
    partkey = rng.integers(0, n_parts, size=rows, dtype=np.uint64).astype(np.uint32)
    suppkey = rng.integers(0, n_suppliers, size=rows).astype(np.int32)
    # suppliers with complaints (the NOT IN subquery's result)
    complained = rng.choice(n_suppliers, size=12, replace=False).astype(np.uint32)

    # stage 1: partsupp ANTI JOIN complaints ON suppkey (probe keyed by supp)
    jk, jb, jp = run_hash_join(
        mesh,
        complained, np.zeros((len(complained), 1), np.int32),
        suppkey.astype(np.uint32), np.stack([partkey.astype(np.int32), suppkey], axis=1),
        impl="dense", join_type="left_anti",
    )
    surv_part = jp[:, 0].astype(np.uint32)
    surv_supp = jp[:, 1][:, None].astype(np.int32)

    # stage 2: COUNT(DISTINCT suppkey) GROUP BY partkey over the survivors
    spec = AggregateSpec(
        num_executors=N, capacity=max(1, -(-len(surv_part) // N)) + 8,
        recv_capacity=4 * CAP, aggs=("count_distinct",),
    )
    gk, gv, gc = run_grouped_aggregate(mesh, spec, surv_part, surv_supp)

    keep = ~np.isin(suppkey, complained.astype(np.int64))
    wk, wv, wc = oracle_aggregate(
        partkey[keep], suppkey[keep][:, None], ("count_distinct",)
    )
    np.testing.assert_array_equal(gk, wk)
    np.testing.assert_array_equal(gv, wv)
    np.testing.assert_array_equal(gc, wc)  # per-group COUNT(*) rides along
    # and against the SQL meaning directly
    for k, cnt in zip(gk, gv[:, 0]):
        m = (partkey == k) & keep
        assert cnt == len(np.unique(suppkey[m]))


def test_q22_global_sales_opportunity(mesh, rng):
    """q22 shape: customers with above-average account balance and NO orders —
    a scalar AVG subquery (fused avg), a WHERE filter against it, and a NOT
    EXISTS anti join, then COUNT/SUM per country code."""
    from sparkucx_tpu.ops.relational import (
        oracle_aggregate,
        run_grouped_aggregate,
        run_hash_join,
    )

    n_cust = 300
    custkey = np.arange(n_cust, dtype=np.uint32)
    country = rng.integers(10, 17, size=n_cust).astype(np.uint32)  # cntrycode
    acctbal = rng.integers(-500, 5000, size=n_cust).astype(np.int32)
    # orders: ~half the customers have at least one
    order_cust = rng.choice(n_cust, size=n_cust // 2, replace=False).astype(np.uint32)

    # stage 1: scalar subquery AVG(acctbal) WHERE acctbal > 0 — one global
    # group through the fused-avg aggregation
    pos = acctbal > 0
    # ONE global group: every surviving row lands on a single shard, so the
    # receive buffer must hold all n_cust rows up front (a smaller bound
    # would deterministically retry-recompile)
    spec_avg = AggregateSpec(
        num_executors=N, capacity=max(1, -(-n_cust // N)) + 8,
        recv_capacity=n_cust, aggs=("avg",), with_filter=True,
    )
    ak, av, ac = run_grouped_aggregate(
        mesh, spec_avg, np.zeros(n_cust, np.uint32), acctbal[:, None], mask=pos
    )
    threshold = float(av[0, 0])
    assert threshold == acctbal[pos].astype(np.float64).mean()

    # stage 2: customers above threshold ANTI JOIN orders (NOT EXISTS)
    rich = acctbal.astype(np.float64) > threshold
    jk, jb, jp = run_hash_join(
        mesh,
        order_cust, np.zeros((len(order_cust), 1), np.int32),
        custkey[rich], np.stack([country[rich].astype(np.int32), acctbal[rich]], axis=1),
        impl="dense", join_type="left_anti",
    )

    # stage 3: COUNT(*), SUM(acctbal) GROUP BY cntrycode
    spec_f = AggregateSpec(
        num_executors=N, capacity=max(1, -(-max(len(jk), 1) // N)) + 8,
        recv_capacity=2 * CAP, aggs=("sum",),
    )
    gk, gv, gc = run_grouped_aggregate(
        mesh, spec_f, jp[:, 0].astype(np.uint32), jp[:, 1][:, None]
    )

    want_mask = rich & ~np.isin(custkey, order_cust)
    wk, wv, wc = oracle_aggregate(
        country[want_mask], acctbal[want_mask][:, None], ("sum",)
    )
    np.testing.assert_array_equal(gk, wk)
    np.testing.assert_array_equal(gv, wv)
    np.testing.assert_array_equal(gc, wc)
