"""Compute-in-exchange fused combine (ROADMAP 2): the receive side of the
scheduled ring folds each landed window into a dense per-group accumulator
instead of staging O(rows) — ops/combine.py,
ops/ici_exchange.build_combine_exchange, and the relational fused bodies.

The load-bearing contracts pinned here:

* the scheduled walk matches a numpy oracle exactly at every chunking, and
  is what a TPU mesh gets too (AOT-lowered for the chip's platform here);
* the fused grouped aggregate is bit-identical to the unfused path for exact
  dtypes (int32 everywhere; float32 over integral values, where sums are
  order-independent), for both the dense tier and the sorted fallback;
* the plan-driven route (run_plan_grouped_aggregate through the unified
  executor) composes with quota sub-rounds without changing a bit;
* quantized payloads stay within the per-row QuantizeSpec error bound;
* 'auto' falls back to the bounded sorted tier on high-cardinality keys.
"""

from dataclasses import replace

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from sparkucx_tpu.ops.combine import (
    COMBINE_AGGS,
    CombineSpec,
    acc_init,
    agg_identity,
    combine_window,
    merge_accumulators,
)
from sparkucx_tpu.ops.exchange import ExchangeSpec, make_mesh
from sparkucx_tpu.ops.ici_exchange import build_combine_exchange
from sparkucx_tpu.ops.relational import (
    AggregateSpec,
    oracle_aggregate,
    run_grouped_aggregate,
    run_plan_grouped_aggregate,
)
from sparkucx_tpu.ops.skew import ExchangePlan

N = 4
SLOT = 8
GROUPS = 16
AGGS = ("sum", "min", "max")


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(N)


def _grid_case(rng, cspec, slot=SLOT):
    """Random sender-major slot grid + the numpy fold oracle."""
    lane = cspec.row_width
    data = np.zeros((N, N * slot, lane), np.int32)
    sizes = np.zeros((N, N), np.int32)
    for s in range(N):
        for d in range(N):
            rows = int(rng.integers(0, slot + 1))
            sizes[s, d] = rows
            keys = rng.integers(0, cspec.num_groups, size=rows).astype(np.uint32)
            vals = rng.integers(-50, 50, size=(rows, cspec.width)).astype(np.int32)
            counts = rng.integers(1, 5, size=rows).astype(np.int32)
            data[s, d * slot : d * slot + rows] = np.concatenate(
                [keys.view(np.int32)[:, None], vals, counts[:, None]], axis=1
            )
    exp_v = np.zeros((N, cspec.num_groups, cspec.width), np.int64)
    for c, a in enumerate(cspec.aggs):
        exp_v[:, :, c] = agg_identity(a, np.int32)
    exp_c = np.zeros((N, cspec.num_groups), np.int64)
    for r in range(N):
        for s in range(N):
            for row in data[s, r * slot : r * slot + sizes[s, r]]:
                k = np.uint32(row[0])
                exp_c[r, k] += row[-1]
                for c, a in enumerate(cspec.aggs):
                    if a in ("sum", "avg"):
                        exp_v[r, k, c] += row[1 + c]
                    elif a == "min":
                        exp_v[r, k, c] = min(exp_v[r, k, c], row[1 + c])
                    else:
                        exp_v[r, k, c] = max(exp_v[r, k, c], row[1 + c])
    return data, sizes, exp_v, exp_c


def _run_exchange(mesh, cspec, data, sizes, chunks=2):
    lane = cspec.row_width
    spec = ExchangeSpec(
        num_executors=N, send_rows=N * SLOT, recv_rows=N * SLOT, lane=lane,
        axis_name="ex", impl="dense",
    )
    fn = build_combine_exchange(mesh, spec, cspec, chunks_per_dest=chunks)
    assert fn.schedule.chunks == chunks
    av0 = np.zeros((N, cspec.num_groups, cspec.width), np.int32)
    for c, a in enumerate(cspec.aggs):
        av0[:, :, c] = agg_identity(a, np.int32)
    ac0 = np.zeros((N, cspec.num_groups, 1), np.int32)
    row_sh = NamedSharding(mesh, P("ex", None))
    return fn(
        jax.device_put(data.reshape(N * N * SLOT, lane), row_sh),
        jax.device_put(sizes, row_sh),
        jax.device_put(av0.reshape(N * cspec.num_groups, cspec.width), row_sh),
        jax.device_put(ac0.reshape(N * cspec.num_groups, 1), row_sh),
    )


# ----------------------------------------------------------------------------
# the exchange itself
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("chunks", [1, 2, 4])
def test_combine_exchange_matches_oracle(mesh, rng, chunks):
    """Whole slots, halves, quarters: the windows a schedule cuts must fold to
    the same accumulator."""
    cspec = CombineSpec(num_groups=GROUPS, aggs=AGGS, dtype=np.int32)
    data, sizes, exp_v, exp_c = _grid_case(rng, cspec)
    accv, accc, recv = _run_exchange(mesh, cspec, data, sizes, chunks)
    accv = np.asarray(accv).reshape(N, GROUPS, len(AGGS))
    accc = np.asarray(accc).reshape(N, GROUPS)
    # recv_sizes is the receive-side view: row r = rows each sender sent to r
    assert np.array_equal(np.asarray(recv), sizes.T)
    assert np.array_equal(accc, exp_c)
    assert np.array_equal(accv.astype(np.int64), exp_v)


def test_combine_exchange_lowers_for_tpu_as_the_chip_picks_it():
    """The builder at its defaults (``impl='auto'``, no lowering to name),
    exported for the tpu platform from here: permutes and folds, no kernel —
    the one lowering there is lowers where the chip would run it."""
    from jax import export as jax_export

    cspec = CombineSpec(num_groups=1024, aggs=("sum", "min", "max", "avg"), dtype=np.int32)
    slot, lane = 4096, cspec.row_width
    spec = ExchangeSpec(num_executors=N, send_rows=N * slot, recv_rows=N * slot, lane=lane)
    assert spec.impl == "auto"
    fn = build_combine_exchange(make_mesh(N), spec, cspec, chunks_per_dest=2)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.int32)
    exported = jax_export.export(fn, platforms=["tpu"])(
        i32(N * N * slot, lane), i32(N, N),
        i32(N * cspec.num_groups, cspec.width), i32(N * cspec.num_groups, 1),
    )
    text = exported.mlir_module()
    assert "collective_permute" in text and "tpu_custom_call" not in text


def test_combine_window_and_merge_unit(rng):
    """Single-window fold + accumulator merge vs plain numpy."""
    cspec = CombineSpec(num_groups=8, aggs=("sum", "max"), dtype=np.int32)
    rows = 16
    keys = rng.integers(0, 8, size=rows).astype(np.uint32)
    vals = rng.integers(-9, 9, size=(rows, 2)).astype(np.int32)
    counts = rng.integers(0, 3, size=rows).astype(np.int32)  # some invalid
    window = np.concatenate([keys.view(np.int32)[:, None], vals, counts[:, None]], axis=1)
    av, ac = acc_init(cspec)
    av, ac = combine_window(cspec, window, av, ac)
    for g in range(8):
        hit = (keys == g) & (counts > 0)
        assert int(ac[g, 0]) == counts[hit].sum()
        assert int(av[g, 0]) == vals[hit, 0].sum()
        want_max = vals[hit, 1].max() if hit.any() else agg_identity("max", np.int32)
        assert int(av[g, 1]) == want_max
    # merging with a fresh identity accumulator is the identity
    bv, bc = acc_init(cspec)
    mv, mc = merge_accumulators(cspec, (av, ac), (bv, bc))
    assert np.array_equal(np.asarray(mv), np.asarray(av))
    assert np.array_equal(np.asarray(mc), np.asarray(ac))


def test_combine_spec_validation():
    with pytest.raises(ValueError, match="num_groups"):
        CombineSpec(num_groups=0, aggs=("sum",)).validate()
    with pytest.raises(ValueError, match="count_distinct"):
        CombineSpec(num_groups=4, aggs=("count_distinct",)).validate()
    with pytest.raises(ValueError, match="float dtype"):
        CombineSpec(num_groups=4, aggs=("sum",), quantize_mode="int8").validate()
    q = CombineSpec(
        num_groups=4, aggs=("sum",), dtype=np.float32, quantize_mode="int8"
    )
    q.validate()
    assert q.payload_width > q.width  # packed words + per-block scales
    assert set(COMBINE_AGGS) == {"sum", "min", "max", "avg"}


# ----------------------------------------------------------------------------
# fused grouped aggregate vs unfused — bit-equality for exact dtypes
# ----------------------------------------------------------------------------


def _agg_spec(**kw):
    base = dict(
        num_executors=N, capacity=256, recv_capacity=256,
        aggs=("sum", "min", "max", "avg"), partial=True,
    )
    base.update(kw)
    return AggregateSpec(**base)


def _dense_case(rng, dtype=np.int32, total=700, domain=60):
    keys = rng.integers(0, domain, size=total).astype(np.uint32)
    vals = rng.integers(-100, 100, size=(total, 4)).astype(dtype)
    return keys, vals


@pytest.mark.parametrize("tier", ["dense", "sorted"])
@pytest.mark.parametrize("dtype", [np.int32, np.float32])
def test_fused_bit_identical_to_unfused(mesh, rng, tier, dtype):
    """Exact dtypes: int32 always; float32 over integral values (segment sums
    of exactly-representable integers are order-independent)."""
    keys, vals = _dense_case(rng, dtype=dtype)
    spec = _agg_spec(
        dtype=np.dtype(dtype), combine=tier,
        combine_groups=64 if tier == "dense" else 0,
    )
    ref = run_grouped_aggregate(mesh, replace(spec, combine="off"), keys, vals)
    got = run_grouped_aggregate(mesh, spec, keys, vals)
    for a, b in zip(ref, got):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    ok, _, oc = oracle_aggregate(keys, vals, spec.aggs)
    assert np.array_equal(got[0], ok)
    assert np.array_equal(got[2], oc)


def test_fused_with_filter(mesh, rng):
    keys, vals = _dense_case(rng)
    mask = rng.random(keys.size) < 0.7
    spec = _agg_spec(with_filter=True, combine="dense", combine_groups=64)
    ref = run_grouped_aggregate(mesh, replace(spec, combine="off"), keys, vals, mask=mask)
    got = run_grouped_aggregate(mesh, spec, keys, vals, mask=mask)
    for a, b in zip(ref, got):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


@pytest.mark.parametrize("quota,chunks", [(256, 1), (64, 4), (128, 2)])
def test_plan_driven_quota_subrounds_bit_identical(mesh, rng, quota, chunks):
    """The unified-executor route: quota sub-rounds through execute_plan /
    build_plan_exchange, per-sub-round accumulators merged in finish_round —
    any chunking must reproduce the unfused bytes exactly (int32)."""
    keys, vals = _dense_case(rng, total=600)
    spec = _agg_spec(combine="dense", combine_groups=64)
    ref = run_grouped_aggregate(mesh, replace(spec, combine="off"), keys, vals)
    plan = ExchangePlan(slot_rows=quota, chunks_per_round=(chunks,), combine="dense")
    got = run_plan_grouped_aggregate(mesh, spec, plan, keys, vals)
    for a, b in zip(ref, got):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_plan_driven_non_dense_falls_back(mesh, rng):
    keys, vals = _dense_case(rng, total=300)
    spec = _agg_spec()
    plan = ExchangePlan(slot_rows=256, chunks_per_round=(1,), combine="off")
    ref = run_grouped_aggregate(mesh, spec, keys, vals)
    got = run_plan_grouped_aggregate(mesh, spec, plan, keys, vals)
    for a, b in zip(ref, got):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


# ----------------------------------------------------------------------------
# quantized tier — error-bound vs the unfused oracle
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("tier", ["dense", "sorted"])
def test_quantized_fused_within_error_bound(mesh, rng, tier):
    keys = rng.integers(0, 48, size=600).astype(np.uint32)
    vals = (rng.random((600, 2), np.float32) * 200 - 100).astype(np.float32)
    spec = _agg_spec(
        aggs=("sum", "avg"), dtype=np.dtype(np.float32), quantize_mode="int8",
        combine=tier, combine_groups=64 if tier == "dense" else 0,
    )
    gk, gv, gc = run_grouped_aggregate(mesh, spec, keys, vals)
    ok, ov, oc = oracle_aggregate(keys, vals, spec.aggs)
    assert np.array_equal(gk, ok)
    assert np.array_equal(gc, oc)  # counts are NEVER quantized
    # per partial row the error is bounded by error_bound(row amax); with at
    # most n partial rows per group the group sum error is n * bound
    bound = spec.qspec.error_bound(np.abs(vals).max()) * N + 1e-4
    assert np.abs(gv[:, 0] - ov[:, 0]).max() <= bound * gc.max()
    # the same lossy payload flows through the unfused path — fused results
    # must sit in the same error class
    uk, uv, uc = run_grouped_aggregate(mesh, replace(spec, combine="off"), keys, vals)
    assert np.array_equal(gk, uk)
    assert np.abs(gv - uv).max() <= 2 * bound * gc.max()


def test_unfused_quantized_reuses_donated_accumulator(mesh, rng):
    """Satellite: the unfused quantized fallback threads ONE donated
    dequantize accumulator through repeated calls instead of
    double-buffering — results stay identical call over call."""
    from sparkucx_tpu.ops.relational import build_grouped_aggregate
    from sparkucx_tpu.ops.columnar import shard_rows_host

    spec = _agg_spec(
        aggs=("sum", "avg"), dtype=np.dtype(np.float32), quantize_mode="int8"
    )
    fn = build_grouped_aggregate(mesh, spec)
    keys = rng.integers(0, 32, size=400).astype(np.uint32)
    vals = (rng.random((400, 2), np.float32) * 50).astype(np.float32)
    pk, pv, nv = shard_rows_host(keys, vals, N, spec.capacity, value_dtype=spec.dtype)
    key_sh = NamedSharding(mesh, P("ex"))
    row_sh = NamedSharding(mesh, P("ex", None))
    args = (
        jax.device_put(pk, key_sh),
        jax.device_put(pv, row_sh),
        jax.device_put(nv, key_sh),
    )
    first = [np.asarray(o) for o in fn(*args)]
    assert len(first) == 5  # public contract unchanged
    for _ in range(2):  # the donated buffer round-trips across calls
        again = fn(*args)
        for a, b in zip(first, again):
            assert np.array_equal(a, np.asarray(b))


# ----------------------------------------------------------------------------
# tier resolution — auto / fallback / conf plumbing
# ----------------------------------------------------------------------------


def test_auto_falls_back_to_sorted_on_high_cardinality(mesh, rng):
    """Hash-like keys: the dense accumulator would dwarf the exchanged slot
    grid, so 'auto' must take the bounded sorted tier — and still agree with
    the unfused path bit for bit."""
    keys = rng.integers(0, 1 << 31, size=500).astype(np.uint32)
    vals = rng.integers(-100, 100, size=(500, 4)).astype(np.int32)
    spec = _agg_spec(combine="auto")
    g = 1 << int(np.max(keys)).bit_length()
    resolved = replace(spec, combine_groups=g).resolve_combine()
    assert resolved.combine == "sorted"
    ref = run_grouped_aggregate(mesh, replace(spec, combine="off"), keys, vals)
    got = run_grouped_aggregate(mesh, spec, keys, vals)
    for a, b in zip(ref, got):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_auto_picks_dense_on_small_domain():
    spec = _agg_spec(combine="auto", combine_groups=64)
    assert spec.resolve_combine().combine == "dense"


def test_from_conf_downgrades_like_quantize():
    from sparkucx_tpu.config import TpuShuffleConf

    conf = TpuShuffleConf(num_executors=N, exchange_fused_combine=True)
    on = AggregateSpec.from_conf(
        conf, capacity=64, recv_capacity=64, aggs=("sum",), partial=True
    )
    assert on.combine == "auto"
    off = AggregateSpec.from_conf(
        conf, capacity=64, recv_capacity=64, aggs=("sum",), partial=False
    )
    assert off.combine == "off"  # silent downgrade: fused folds PARTIAL rows
    cd = AggregateSpec.from_conf(
        conf, capacity=64, recv_capacity=64, aggs=("count_distinct",)
    )
    assert cd.combine == "off" and not cd.partial
    plain = AggregateSpec.from_conf(
        TpuShuffleConf(num_executors=N),
        capacity=64, recv_capacity=64, aggs=("sum",), partial=True,
    )
    assert plain.combine == "off"  # default-off knob


def test_validate_rejects_bad_combine():
    with pytest.raises(ValueError, match="combine tier"):
        _agg_spec(impl="dense", combine="fused").validate()
    with pytest.raises(ValueError, match="partial"):
        _agg_spec(impl="dense", partial=False, combine="dense", combine_groups=8).validate()
    with pytest.raises(ValueError, match="combine_groups"):
        _agg_spec(impl="dense", combine="dense").validate()


def test_planner_learns_combine_tier():
    """Satellite: StaticPlanner/AdaptivePlanner fill ExchangePlan.combine from
    all-gathered aggregation geometry; the plan trace instant carries it."""
    from sparkucx_tpu.config import TpuShuffleConf
    from sparkucx_tpu.ops.planner import AdaptivePlanner, PlanContext, StaticPlanner

    conf = TpuShuffleConf(num_executors=N, exchange_fused_combine=True)
    dense_ctx = PlanContext(
        num_executors=N, staging_slot_rows=1024, round_max_rows=(512,),
        used_rows_total=2048, row_bytes=64, agg_partial=True, agg_groups=256,
        agg_width=4,
    )
    plan = StaticPlanner(conf).plan(dense_ctx)
    assert plan.combine == "dense"
    assert plan.describe()["combine"] == "dense"
    # huge domain: static keeps the sorted fallback, adaptive goes off
    wide_ctx = replace_ctx(dense_ctx, agg_groups=1 << 24)
    assert StaticPlanner(conf).plan(wide_ctx).combine == "sorted"
    assert AdaptivePlanner(conf).plan(wide_ctx).combine == "off"
    # no aggregation geometry (raw block shuffle): always off
    raw_ctx = replace_ctx(dense_ctx, agg_partial=False)
    assert StaticPlanner(conf).plan(raw_ctx).combine == "off"
    # knob off: off even with dense geometry
    off_conf = TpuShuffleConf(num_executors=N)
    assert StaticPlanner(off_conf).plan(dense_ctx).combine == "off"


def replace_ctx(ctx, **kw):
    from dataclasses import replace as _r

    return _r(ctx, **kw)
