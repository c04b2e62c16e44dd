"""Device-resident relational operators — grouped aggregation and hash join.

BASELINE.md's remaining workload configs are Spark SQL jobs: "TPC-H q5/q18
SF=10" and "TPC-DS SF=100".  Their physical plans are a small vocabulary:
hash-partition exchange + local aggregation (HashAggregateExec around a
ShuffleExchange) and hash-partition exchange of both sides + local join
(ShuffledHashJoinExec / SortMergeJoinExec).  The reference accelerates only the
exchange *transport* of those plans (the UCX block fetch under Spark SQL's
shuffle); here the whole operator runs on device, the way ops/sort.py runs all
of TeraSort on device:

    hash(key) -> owner  ->  columnar ragged all_to_all (ops/columnar.py)  ->
    local segment-reduce (GROUP BY) or sort-merge expansion (JOIN)

Everything is static-shaped (capacities are compile-time constants, row counts
are runtime data), so one compiled operator serves every batch of every query —
the XLA-friendly design SURVEY.md section 0 calls for, no data-dependent shapes.

Keys are uint32 and travel bitcast through the payload dtype lane exactly as in
ops/sort.py; rows whose index is past ``num_valid`` are padding and never
participate.  Both operators return actual totals so callers detect capacity
overflow and re-run with headroom — the same contract as SortSpec.recv_capacity
(ops/sort.py) and the multi-round spill path (transport/tpu.py).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparkucx_tpu.ops.columnar import (
    ColumnarSpec,
    columnar_body,
    shard_rows_host,
    unpack_shard_prefixes,
)
from sparkucx_tpu.ops.compress import QuantizeSpec, dequantize_rows, quantize_rows
from sparkucx_tpu.ops.exchange import exclusive_cumsum, gather_rows, resolve_collective_impl

#: Padding sort key (sorts last) — ops/sort.py's sentinel, same discipline:
#: valid rows may legitimately carry this key; because received rows are a
#: tight valid prefix, a *stable* sort keeps valid sentinel-keyed rows ahead of
#: padding within the tie, and validity masks do the rest (x64 stays off; no
#: int64 composite keys anywhere).
from sparkucx_tpu.ops.sort import KEY_MAX, comparable_lanes, key_lanes_of  # noqa: E402  (KEY_MAX re-exported)

#: Multiplicative hash constant (Knuth); uint32 wraparound is the mixing step.
_HASH_MULT = np.uint32(2654435761)

#: 'avg' is computed as a fused sum on device (the count is always produced
#: alongside), divided exactly in the host driver — Spark's partial-avg plan
#: (HashAggregateExec emits sum+count partials, the final stage divides).
#: 'count_distinct' counts distinct values of its column per group, on device.
VALID_AGGS = ("sum", "min", "max", "avg", "count_distinct")

#: join_type -> rows emitted per probe row with m build matches.  ONE table
#: serves both the device kernel (xp=jnp in expand_matches) and the host
#: capacity planner (xp=np in plan_join_capacities) so the two can never
#: drift — a divergence would make the exact host plan under-size out_cap.
_JOIN_EMIT = {
    "inner": lambda m, xp: m,
    "left_outer": lambda m, xp: xp.maximum(m, 1),
    "left_semi": lambda m, xp: xp.minimum(m, 1),
    "left_anti": lambda m, xp: 1 - xp.minimum(m, 1),
}

#: right/full outer decompose into a probe-driven base expansion plus an
#: appended pass over unmatched BUILD rows (a build-side match-flag scan —
#: probe-row emission counts alone cannot express them).
_OUTER_BASE = {"right_outer": "inner", "full_outer": "left_outer"}

#: join types whose compiled fn emits the extra ``out_matched`` output
#: (False = null-extended row: zeroed build lanes for an unmatched probe row,
#: zeroed probe lanes for an unmatched build row).
OUTER_JOIN_TYPES = ("left_outer", "right_outer", "full_outer")

JOIN_TYPES = tuple(_JOIN_EMIT) + tuple(_OUTER_BASE)


def _join_emit(join_type: str):
    fn = _JOIN_EMIT.get(join_type)
    if fn is None:
        raise ValueError(
            f"unknown join_type {join_type!r} (valid: {tuple(_JOIN_EMIT)})"
        )
    return fn


def hash_owners(keys: jnp.ndarray, num_executors: int, valid: jnp.ndarray) -> jnp.ndarray:
    """Destination executor per row: multiplicative hash of the uint32 key,
    mod n.  This is Spark SQL's HashPartitioning, computed on device.  Padding
    rows map to ``num_executors`` (the columnar shuffle's never-sent owner)."""
    mixed = (keys.astype(jnp.uint32) * _HASH_MULT) >> 16
    owner = (mixed % jnp.uint32(num_executors)).astype(jnp.int32)
    return jnp.where(valid, owner, num_executors)


def hash_owners_host(keys: "np.ndarray", num_executors: int) -> "np.ndarray":
    """Host-side twin of :func:`hash_owners` (bit-identical placement, numpy
    uint32 wraparound) — lets drivers plan receive capacities from the actual
    key distribution instead of guessing skew headroom."""
    mixed = (keys.astype(np.uint32) * _HASH_MULT) >> np.uint32(16)
    return (mixed % np.uint32(num_executors)).astype(np.int32)


def padded_keys(keys: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Force padding rows to the KEY_MAX sentinel so they sort last."""
    return jnp.where(valid, keys.astype(jnp.uint32), KEY_MAX)


def exchange_keyed_rows(spec: ColumnarSpec, keys, values, valid):
    """Hash-partition (key | values) rows through one columnar exchange.

    Returns (recv_keys uint32, recv_values, recv_valid, recv_total) with the
    received rows tight-packed; every row of a given key lands on exactly one
    executor.  ``recv_total`` is the TRUE row count routed to this shard — a
    value > ``recv_capacity`` means the buffer truncated (overflow the caller
    must surface, same contract as SortSpec.recv_capacity)."""
    rows = jnp.concatenate(
        [jax.lax.bitcast_convert_type(keys.astype(jnp.uint32), spec.dtype)[:, None], values],
        axis=1,
    )
    owners = hash_owners(keys, spec.num_executors, valid)
    recv, recv_sizes = columnar_body(spec, rows, owners)
    total = recv_sizes.sum().astype(jnp.int32)
    ridx = jnp.arange(spec.recv_capacity, dtype=jnp.int32)
    recv_valid = ridx < total
    recv_keys = jax.lax.bitcast_convert_type(recv[:, 0], jnp.uint32)
    return recv_keys, recv[:, 1:], recv_valid, total


# ----------------------------------------------------------------------------
# Grouped aggregation (GROUP BY)
# ----------------------------------------------------------------------------


@dataclass(frozen=True)
class AggregateSpec:
    """Static description of one compiled grouped aggregation.

    ``capacity``: per-executor input rows; ``recv_capacity``: per-executor rows
    after the hash exchange (>= worst-case skew of hash(key) % n — with K
    distinct keys expect ~total/n, so leave headroom like SortSpec does);
    ``aggs``: one of ``VALID_AGGS`` ('sum'|'min'|'max'|'avg'|'count_distinct')
    per value column — 'avg' is a fused sum on device divided by the count in
    the host driver, 'count_distinct' counts distinct column values per group.
    A per-group COUNT is always produced (it is also COUNT(*) when there are
    no value columns)."""

    num_executors: int
    capacity: int
    recv_capacity: int
    aggs: Tuple[str, ...]
    dtype: np.dtype = np.dtype(np.int32)
    axis_name: str = "ex"
    impl: str = "auto"
    #: True compiles the WHERE-pushdown variant: the jitted fn takes a fourth
    #: per-row bool input and filtered rows never enter the exchange (their
    #: owner is the never-sent n) — Spark SQL's Filter below the Exchange,
    #: on device instead of pre-filtered host tables.
    with_filter: bool = False
    #: True performs MAP-SIDE PARTIAL AGGREGATION below the exchange — Spark's
    #: HashAggregateExec(partial) under the ShuffleExchange: each shard first
    #: segment-reduces its own rows to at most one partial row per local
    #: distinct key (agg columns + a count), exchanges the PARTIALS, and the
    #: final merge re-reduces them (sum/min/max/avg compose; count becomes
    #: sum-of-counts).  For GroupByTest-shaped data (a small keyspace over
    #: millions of rows, buildlib/test.sh:163-173) this shrinks exchange
    #: traffic by the group-reduction factor — and it bounds hot-key skew:
    #: each shard sends at most ONE row per key, so a hot key lands
    #: ``num_executors`` partial rows on its owner, not the raw row count.
    #: Results are bit-identical for integer dtypes (int32 adds associate);
    #: 'count_distinct' is rejected (distinct counts do not compose by sum).
    partial: bool = False
    #: OPT-IN LOSSY tier-b payload reduction (ops/compress.py, conf
    #: ``quantize.mode``): 'off' | 'int8' | 'blockfloat'.  Block-quantizes the
    #: PARTIAL-aggregate float value columns around the exchange — quantize
    #: after the map-side reduce, ship int8x4-packed words (bitcast through
    #: the float lane, the count lane's transit trick), dequantize before the
    #: final merge.  Requires ``partial=True`` and a floating ``dtype``; keys
    #: and counts are NEVER quantized, so group identity and COUNT stay
    #: exact.  Per-partial-row error is bounded by
    #: ``QuantizeSpec.error_bound`` per block of ``quantize_block_size``.
    quantize_mode: str = "off"
    quantize_block_size: int = 128
    #: Receive-side COMPUTE-IN-EXCHANGE tier (ops/combine.py, conf
    #: ``exchange.fusedCombine``): 'off' | 'auto' | 'dense' | 'sorted'.
    #: 'dense' folds every landed exchange window into a fixed per-group
    #: accumulator as it arrives — post-exchange memory and drain bytes drop
    #: from O(rows) to O(groups).  It requires ``partial=True``
    #: (the windows are partial-aggregate rows) and every key to lie inside
    #: ``[0, combine_groups)``.  'sorted' is the high-cardinality fallback:
    #: a bounded per-superstep sort/merge into a (recv_capacity) accumulator —
    #: still O(recv_capacity) post-exchange, never the full landed grid.
    #: 'auto' resolves via :meth:`resolve_combine` (dense iff the accumulator
    #: undercuts the slot grid the exchange would otherwise drain);
    #: :func:`run_grouped_aggregate` fills ``combine_groups`` from the actual
    #: key domain first.  Exact dtypes are bit-identical to the unfused path
    #: (tests/test_fused_combine.py pins it); quantized payloads stay inside
    #: the per-row ``QuantizeSpec.error_bound``.
    combine: str = "off"
    #: dense key-domain size (pow2-bucketed — a compile-cache key dimension)
    combine_groups: int = 0

    @property
    def width(self) -> int:
        return len(self.aggs)

    @property
    def qspec(self) -> QuantizeSpec:
        return QuantizeSpec(
            mode=self.quantize_mode, block_size=self.quantize_block_size
        )

    @classmethod
    def from_conf(cls, conf, **kwargs) -> "AggregateSpec":
        """Build a spec with cluster-level defaults taken from a
        ``TpuShuffleConf``: ``partial`` from ``conf.partial_aggregation`` (the
        ``partialAggregation`` Spark key — this is where that knob enters the
        plan), ``num_executors``/``axis_name`` from the conf unless given.
        Explicit kwargs always win.  count_distinct plans default to
        ``partial=False`` regardless of the conf (distinct counts do not
        compose by sum — validate() would reject the combination)."""
        if "count_distinct" in kwargs.get("aggs", ()):
            kwargs.setdefault("partial", False)
        kwargs.setdefault("partial", bool(conf.partial_aggregation))
        kwargs.setdefault("num_executors", conf.num_executors)
        kwargs.setdefault("axis_name", conf.mesh_axis_name)
        explicit_quantize = "quantize_mode" in kwargs
        kwargs.setdefault("quantize_mode", conf.quantize_mode)
        kwargs.setdefault("quantize_block_size", conf.quantize_block_size)
        explicit_combine = "combine" in kwargs
        kwargs.setdefault(
            "combine",
            "auto" if getattr(conf, "exchange_fused_combine", False) else "off",
        )
        spec = cls(**kwargs)
        if (
            not explicit_quantize
            and spec.quantize_mode != "off"
            and not (
                spec.partial and np.issubdtype(np.dtype(spec.dtype), np.floating)
            )
        ):
            # the conf knob is cluster-global; plans it cannot apply to
            # (non-partial, integer dtypes — exactness is the contract there)
            # silently keep the stock path instead of failing validate()
            spec = replace(spec, quantize_mode="off")
        if (
            not explicit_combine
            and spec.combine != "off"
            and (not spec.partial or spec.num_executors < 2)
        ):
            # same discipline as the quantize knob: the fused combine folds
            # PARTIAL rows across an exchange, so non-partial plans (incl.
            # count_distinct, which forces partial=False above) and
            # single-executor meshes keep the stock path silently
            spec = replace(spec, combine="off")
        return spec

    def resolve_combine(self) -> "AggregateSpec":
        """Resolve ``combine='auto'`` to a concrete tier: 'dense' when the
        per-group accumulator undercuts the fused slot grid the exchange
        would otherwise drain (the planner's ``_combine_tier`` rule, made
        spec-local for direct builder users), else the bounded 'sorted'
        fallback.  ``combine_groups`` must already hold the pow2-bucketed
        key-domain size — :func:`run_grouped_aggregate` measures it from the
        actual keys before calling this."""
        if self.combine != "auto":
            return self
        acc_bytes = self.combine_groups * (self.width * 4 + 4)
        staging_bytes = self.num_executors * self.capacity * (self.width + 2) * 4
        dense = self.combine_groups > 0 and acc_bytes < staging_bytes
        return replace(self, combine="dense" if dense else "sorted")

    @property
    def combine_cspec(self):
        """The ``ops/combine.CombineSpec`` of the dense tier (quantization
        rides inside it — one dispatch, both tiers compose)."""
        from sparkucx_tpu.ops.combine import CombineSpec

        return CombineSpec(
            num_groups=max(1, self.combine_groups),
            aggs=self.aggs,
            dtype=self.dtype,
            quantize_mode=self.quantize_mode,
            quantize_block=self.quantize_block_size,
        )

    def resolve_impl(self, platform: Optional[str] = None) -> "AggregateSpec":
        return replace(self, impl=resolve_collective_impl(self.impl, platform))

    def validate(self) -> None:
        if self.impl not in ("ragged", "dense"):
            raise ValueError(f"unknown impl {self.impl!r}")
        if np.dtype(self.dtype).itemsize != 4:
            raise ValueError("value dtype must be 32-bit (keys bitcast through it)")
        for a in self.aggs:
            if a not in VALID_AGGS:
                raise ValueError(f"unknown aggregation {a!r} (valid: {VALID_AGGS})")
        if self.partial and "count_distinct" in self.aggs:
            raise ValueError(
                "count_distinct cannot use partial aggregation (per-shard "
                "distinct counts do not compose by sum); use partial=False"
            )
        if self.quantize_mode != "off":
            self.qspec.validate()
            if not self.partial:
                raise ValueError(
                    "quantization rides the partial-aggregate exchange; "
                    "set partial=True (raw-row exchanges are never quantized)"
                )
            if not np.issubdtype(np.dtype(self.dtype), np.floating):
                raise ValueError(
                    "quantization needs a floating value dtype — integer "
                    "aggregates are exact by contract and stay unquantized"
                )
        if self.combine not in ("off", "auto", "dense", "sorted"):
            raise ValueError(
                f"unknown combine tier {self.combine!r} (off|auto|dense|sorted)"
            )
        if self.combine != "off":
            if not self.partial:
                raise ValueError(
                    "the fused combine folds PARTIAL aggregate rows across "
                    "the exchange; set partial=True (count_distinct can "
                    "therefore never use it)"
                )
            if self.combine == "dense" and self.combine_groups <= 0:
                raise ValueError(
                    "combine='dense' needs combine_groups > 0 (the dense key "
                    "domain; keys must lie in [0, combine_groups))"
                )


def _agg_identity(agg: str, dtype) -> jnp.ndarray:
    if agg in ("sum", "avg", "count_distinct"):
        return jnp.zeros((), dtype)
    info = jnp.finfo(dtype) if jnp.issubdtype(dtype, jnp.floating) else jnp.iinfo(dtype)
    return jnp.array(info.max if agg == "min" else info.min, dtype)


def _segment_reduce(
    aggs: Tuple[str, ...],
    out_cap: int,
    keys,
    vals,
    valid,
    counts=None,
    tight: bool = True,
):
    """Stable key-sort + segment-reduce — the GROUP BY kernel shared by the
    post-exchange final phase and the map-side partial phase.

    ``counts`` carries pre-aggregated row counts when the inputs are partial
    rows (group count = sum of partial counts); None counts raw rows.
    ``tight=True`` asserts valid rows form a prefix (post-exchange compaction
    guarantees it; so does an unmasked local shard) and sorts once; with a
    scattered validity pattern (WHERE-pushdown masks) an extra stable pass on
    the validity flag keeps valid sentinel-keyed rows ahead of invalid ones
    inside the KEY_MAX tie.  Returns (group_keys, group_vals, group_count,
    num_groups); groups are numbered in ascending key order.
    """
    pk = padded_keys(keys, valid)
    order = jnp.argsort(pk, stable=True)
    if not tight:
        order = order[jnp.argsort(jnp.logical_not(valid)[order], stable=True)]
    skeys = keys[order]
    svals = vals[order]
    svalid = valid[order]
    scounts = counts[order] if counts is not None else svalid.astype(jnp.int32)
    prev_differs = jnp.concatenate([jnp.ones(1, bool), skeys[1:] != skeys[:-1]])
    is_start = prev_differs & svalid
    seg = jnp.cumsum(is_start.astype(jnp.int32)) - 1
    # Padding rows scatter out of range and are dropped.
    seg = jnp.where(svalid, seg, out_cap)
    num_groups = is_start.sum().astype(jnp.int32)

    group_keys = jnp.zeros(out_cap, jnp.uint32).at[seg].set(skeys, mode="drop")
    group_count = (
        jnp.zeros(out_cap, jnp.int32)
        .at[seg]
        .add(jnp.where(svalid, scounts, 0), mode="drop")
    )
    cols = []
    for c, agg in enumerate(aggs):
        if agg == "count_distinct":
            cols.append(
                _distinct_count_col(out_cap, pk, vals[:, c], valid).astype(svals.dtype)
            )
            continue
        ident = _agg_identity(agg, svals.dtype)
        col = jnp.where(svalid, svals[:, c], ident)
        acc = jnp.full(out_cap, ident)
        if agg in ("sum", "avg"):
            acc = acc.at[seg].add(col, mode="drop")
        elif agg == "min":
            acc = acc.at[seg].min(col, mode="drop")
        else:
            acc = acc.at[seg].max(col, mode="drop")
        cols.append(acc)
    group_vals = (
        jnp.stack(cols, axis=1) if cols else jnp.zeros((out_cap, 0), svals.dtype)
    )
    return group_keys, group_vals, group_count, num_groups


def _distinct_count_col(out_cap: int, pk, col, valid):
    """COUNT(DISTINCT col) per group: lexsort rows by (validity, key, value)
    — three stable argsorts, innermost first — so each group's values are
    contiguous AND sorted, then count (key, value) pair starts per segment.
    Group numbering (ascending distinct valid keys) matches
    :func:`_segment_reduce`'s, so the scattered counts align with its groups.
    """
    order = jnp.argsort(col, stable=True)
    order = order[jnp.argsort(pk[order], stable=True)]
    order = order[jnp.argsort(jnp.logical_not(valid)[order], stable=True)]
    sk = pk[order]
    sv = col[order]
    svalid = valid[order]
    key_start = jnp.concatenate([jnp.ones(1, bool), sk[1:] != sk[:-1]])
    is_start = key_start & svalid
    seg = jnp.cumsum(is_start.astype(jnp.int32)) - 1
    seg = jnp.where(svalid, seg, out_cap)
    pair_start = key_start | jnp.concatenate([jnp.ones(1, bool), sv[1:] != sv[:-1]])
    return (
        jnp.zeros(out_cap, jnp.int32)
        .at[seg]
        .add((pair_start & svalid).astype(jnp.int32), mode="drop")
    )


def _partial_rows(spec: AggregateSpec, qspec, cap, idx, keys, values, valid, tight):
    """Map-side partial aggregation (HashAggregateExec(partial) below the
    Exchange): reduce locally first, then exchange one row per local distinct
    key carrying (key | agg columns | count).  The count lane travels BITCAST
    through the value dtype, so it is exact for any 32-bit dtype (a float32
    cast would silently round counts > 2^24).  Shared by the unfused body and
    the fused-combine body so the two wire formats can never drift — the
    fused tiers' bit-equality against the unfused path rests on it."""
    lk, lv, lc, lng = _segment_reduce(spec.aggs, cap, keys, values, valid, tight=tight)
    if qspec is not None:
        # tier-b lossy opt-in: quantize the partial value columns on the
        # send side; the packed int32 payload bitcasts through the float
        # dtype lane (bit-preserving — the exchange only moves rows)
        lv = jax.lax.bitcast_convert_type(quantize_rows(qspec, lv), spec.dtype)
    packed = jnp.concatenate(
        [lv, jax.lax.bitcast_convert_type(lc, spec.dtype)[:, None]], axis=1
    )
    return lk, packed, idx < lng


def _aggregate_body(spec: AggregateSpec, keys, values, num_valid, mask=None, dq_acc=None):
    cap = spec.capacity
    idx = jnp.arange(cap, dtype=jnp.int32)
    valid = idx < num_valid[0]
    if mask is not None:
        # WHERE pushdown: filtered rows are simply never-sent (owner n), so
        # invalidity may be scattered — everything downstream sees only the
        # compacted received prefix and is agnostic to the input pattern
        valid &= mask

    counts = None
    qspec = spec.qspec if (spec.partial and spec.quantize_mode != "off") else None
    if spec.partial:
        keys, values, valid = _partial_rows(
            spec, qspec, cap, idx, keys, values, valid, tight=(mask is None)
        )

    payload_width = (
        qspec.quantized_width(spec.width) if qspec is not None else spec.width
    )
    cspec = ColumnarSpec(
        num_executors=spec.num_executors,
        capacity=cap,
        recv_capacity=spec.recv_capacity,
        width=payload_width + (2 if spec.partial else 1),
        dtype=spec.dtype,
        axis_name=spec.axis_name,
        impl=spec.impl,
    )
    rkeys, rvals, rvalid, rtotal = exchange_keyed_rows(cspec, keys, values, valid)
    if spec.partial:
        counts = jax.lax.bitcast_convert_type(rvals[:, -1], jnp.int32)
        rvals = rvals[:, :-1]
        if qspec is not None:
            # receive side: dequantize before the final merge (zero-filled
            # buffer tails dequantize to zero rows; rvalid masks them anyway)
            rvals = dequantize_rows(
                qspec, jax.lax.bitcast_convert_type(rvals, jnp.int32), spec.width
            ).astype(spec.dtype)

    # Final GROUP BY on the received (raw or partial) rows: sum/min/max/avg
    # compose with themselves, counts compose by sum.
    group_keys, group_vals, group_count, num_groups = _segment_reduce(
        spec.aggs, spec.recv_capacity, rkeys, rvals, rvalid, counts=counts
    )
    out = (group_keys, group_vals, group_count, num_groups[None], rtotal[None])
    if dq_acc is not None:
        # donated dequantize accumulator: the extra output matches the
        # donated input's (recv_capacity, width) float geometry, so XLA
        # aliases the buffers and the dequantized merge input stops
        # double-buffering next to the received packed rows — the caller
        # threads the returned array back in on the next call
        return out + (rvals,)
    return out


def _sorted_combine_walk(spec: AggregateSpec, sched, slot_rows, flat, me):
    """High-cardinality fallback tier (``combine='sorted'``): walk the ring
    schedule and merge every landed window into a BOUNDED sorted accumulator
    of ``recv_capacity`` groups via :func:`_segment_reduce` — a per-superstep
    partial sort/merge.  Post-exchange memory is O(recv_capacity) instead of
    the full landed grid, and integer folds stay bit-identical to the unfused
    path (segment sums associate).  Overflow detection is unchanged: distinct
    keys on a shard never exceed its received partial rows, so the driver's
    ``recv_totals`` check still triggers the doubling retry first.

    Scheduled permutes only (``lowering='xla'``) — the bounded merge has no
    kernel epilogue form; the dense tier is the Pallas-fused one."""
    ax = spec.axis_name
    n = spec.num_executors
    qspec = spec.qspec if spec.quantize_mode != "off" else None
    out_cap = spec.recv_capacity
    lane = flat.shape[1]
    idx = jnp.arange(out_cap, dtype=jnp.int32)

    def fold(window, state):
        ak, av, ac, ang = state
        wkeys = jax.lax.bitcast_convert_type(window[:, 0], jnp.uint32)
        wc = jax.lax.bitcast_convert_type(window[:, -1:], jnp.int32)[:, 0]
        wp = window[:, 1:-1]
        if qspec is not None:
            wp = dequantize_rows(
                qspec, jax.lax.bitcast_convert_type(wp, jnp.int32), spec.width
            ).astype(spec.dtype)
        # accumulator rows are partial rows themselves (counts compose by
        # sum), so one segment reduce over [acc | window] IS the merge
        mk = jnp.concatenate([ak, wkeys])
        mv = jnp.concatenate([av, wp], axis=0)
        mc = jnp.concatenate([ac, wc])
        mvalid = jnp.concatenate([idx < ang, wc > 0])
        return _segment_reduce(spec.aggs, out_cap, mk, mv, mvalid, counts=mc, tight=False)

    state = (
        jnp.zeros(out_cap, jnp.uint32),
        jnp.zeros((out_cap, spec.width), spec.dtype),
        jnp.zeros(out_cap, jnp.int32),
        jnp.zeros((), jnp.int32),
    )
    # canonical fold order (ops/combine.py): own slot first, then schedule
    # items in step order
    own = jax.lax.dynamic_slice(flat, (me * slot_rows, 0), (slot_rows, lane))
    state = fold(own, state)
    w = slot_rows // sched.chunks
    for step in sched.steps:
        for item in step:
            d = item.offset
            send_row = ((me + d) % n) * slot_rows + item.chunk * w
            window = jax.lax.dynamic_slice(flat, (send_row, 0), (w, lane))
            got = jax.lax.ppermute(window, ax, [(i, (i + d) % n) for i in range(n)])
            state = fold(got, state)
    return state


def _fused_aggregate_body(
    spec: AggregateSpec, sched, keys, values, num_valid, mask=None
):
    """The COMPUTE-IN-EXCHANGE shard body (``spec.combine != 'off'``): local
    partial reduce, place the partial rows into per-destination slots of the
    sender-major ring grid, then fold every window into the accumulator AS IT
    LANDS (ops/ici_exchange.combine_axis_grid) instead of staging O(rows)
    received rows.  The dense tier
    compacts the (combine_groups,) accumulator through the same
    :func:`_segment_reduce` the unfused final phase uses — single-element
    segments are identity folds, so the output contract (ascending keys,
    counts, num_groups, recv_totals) is preserved bit-for-bit."""
    from sparkucx_tpu.ops.ici_exchange import combine_axis_grid

    cap = spec.capacity
    n = spec.num_executors
    ax = spec.axis_name
    idx = jnp.arange(cap, dtype=jnp.int32)
    valid = idx < num_valid[0]
    if mask is not None:
        valid &= mask
    qspec = spec.qspec if spec.quantize_mode != "off" else None
    keys, values, valid = _partial_rows(
        spec, qspec, cap, idx, keys, values, valid, tight=(mask is None)
    )

    # slot placement: owner-sorted rows land at (owner * cap + rank-within-
    # owner) — each destination's region is a tight valid prefix, the
    # all-zero tail is the count==0 padding the combine fold skips
    rows = jnp.concatenate(
        [jax.lax.bitcast_convert_type(keys.astype(jnp.uint32), spec.dtype)[:, None], values],
        axis=1,
    )
    owners = hash_owners(keys, n, valid)
    sizes = jnp.bincount(owners, length=n + 1)[:n].astype(jnp.int32)
    order = jnp.argsort(owners, stable=True)
    sowners = owners[order]
    start = exclusive_cumsum(sizes)
    pos = idx - start[jnp.clip(sowners, 0, n - 1)]
    dest = jnp.where(sowners < n, sowners * cap + pos, n * cap)
    slot = (
        jnp.zeros((n * cap, rows.shape[1]), spec.dtype)
        .at[dest]
        .set(rows[order], mode="drop")
    )

    me = jax.lax.axis_index(ax)
    # recv_totals keeps the unfused contract (TRUE partial rows hashed to
    # each shard) so the driver's overflow/retry behavior is identical
    sizes_mat = jax.lax.all_gather(sizes, ax)
    rtotal = jnp.sum(sizes_mat[:, me]).astype(jnp.int32)

    if spec.combine == "dense":
        accv, accc = combine_axis_grid(
            ax, n, cap, sched, slot, me, spec.combine_cspec
        )
        # compaction: one segment reduce over the dense domain — every group
        # is its own single-row segment (identity fold, exact for floats too)
        gk, gv, gc, ng = _segment_reduce(
            spec.aggs,
            spec.recv_capacity,
            jnp.arange(spec.combine_groups, dtype=jnp.uint32),
            accv,
            accc[:, 0] > 0,
            counts=accc[:, 0],
            tight=False,
        )
    else:
        gk, gv, gc, ng = _sorted_combine_walk(spec, sched, cap, slot, me)
    return gk, gv, gc, ng[None], rtotal[None]


def build_grouped_aggregate(mesh: Mesh, spec: AggregateSpec):
    """Compile the distributed GROUP BY for ``mesh``.

    Returns jitted ``fn(keys, values, num_valid) ->
    (group_keys, group_values, group_counts, num_groups, recv_totals)`` —
    with ``spec.with_filter`` the signature gains a trailing per-row bool
    ``mask`` (n * capacity,): False rows are dropped before the exchange
    (WHERE pushdown; they count in neither recv_totals nor any group):

    * ``keys``: (n * capacity,) uint32, sharded over ``axis_name``;
    * ``values``: (n * capacity, len(aggs)) of ``dtype``, row-sharded;
    * ``num_valid``: (n,) int32 sharded — valid rows per shard;
    * ``group_keys``: (n * recv_capacity,) uint32 — shard j's first
      ``num_groups[j]`` entries are its distinct keys (each key appears on
      exactly one shard, ascending within the shard);
    * ``group_values``: aggregated value per group/column (aligned rows).
      'avg' columns carry their SUM on device (the fused sum+count pair —
      counts are always produced); the host driver divides exactly;
      'count_distinct' columns carry the per-group distinct value count;
    * ``group_counts``: rows aggregated into each group (COUNT);
    * ``num_groups``: (n,) int32;
    * ``recv_totals``: (n,) int32 — TRUE rows hashed to each shard (with
      ``spec.partial``, PARTIAL rows: at most one per (sender, key) — the
      wire-traffic reduction is visible right here).  Any value
      > ``recv_capacity`` means that shard's exchange truncated and its groups
      are incomplete: re-run with headroom, like SortSpec.recv_capacity.

    With ``spec.combine != 'off'`` (and more than one executor) the exchange
    runs the COMPUTE-IN-EXCHANGE route (:func:`_fused_aggregate_body`):
    identical signature, identical outputs — bit-identical for exact dtypes,
    within ``QuantizeSpec.error_bound`` per partial row when quantized.
    """
    if spec.num_executors != mesh.devices.size:
        raise ValueError(f"spec.num_executors={spec.num_executors} != mesh size {mesh.devices.size}")
    spec = spec.resolve_impl(platform=mesh.devices.reshape(-1)[0].platform)
    if spec.combine == "auto":
        spec = spec.resolve_combine()
    spec.validate()
    ax = spec.axis_name

    if spec.combine != "off" and spec.num_executors > 1:
        # compute-in-exchange route: the shard body IS the scheduled ring
        # (same FAST schedule the ICI exchange builds), folding windows into
        # the accumulator as they land instead of staging received rows
        from sparkucx_tpu.ops.ici_exchange import (
            DEFAULT_CHUNKS_PER_DEST,
            ring_schedule,
            schedule_chunks,
        )

        sched = ring_schedule(
            spec.num_executors,
            schedule_chunks(spec.capacity, DEFAULT_CHUNKS_PER_DEST),
        )
        body = functools.partial(_fused_aggregate_body, spec, sched)
        reuse_dq = False
    else:
        body = functools.partial(_aggregate_body, spec)
        # the unfused quantized fallback reuses ONE donated dequantize
        # accumulator across calls instead of double-buffering the merge
        # input next to the packed received rows
        reuse_dq = spec.partial and spec.quantize_mode != "off"

    def _body(*args):
        args = list(args)
        dq = args.pop() if reuse_dq else None
        m = args.pop() if spec.with_filter else None
        if reuse_dq:
            return body(args[0], args[1], args[2], mask=m, dq_acc=dq)
        return body(args[0], args[1], args[2], mask=m)

    mask_in = (P(ax),) if spec.with_filter else ()
    dq_in = (P(ax, None),) if reuse_dq else ()
    shard = shard_map(
        _body,
        mesh=mesh,
        in_specs=(P(ax), P(ax, None), P(ax)) + mask_in + dq_in,
        out_specs=(P(ax), P(ax, None), P(ax), P(ax), P(ax))
        + ((P(ax, None),) if reuse_dq else ()),
        check_vma=False,
    )
    key_sh = NamedSharding(mesh, P(ax))
    row_sh = NamedSharding(mesh, P(ax, None))
    mask_sh = (key_sh,) if spec.with_filter else ()
    if not reuse_dq:
        fn = jax.jit(
            shard,
            in_shardings=(key_sh, row_sh, key_sh) + mask_sh,
            out_shardings=(key_sh, row_sh, key_sh, key_sh, key_sh),
        )
        fn.spec = spec
        return fn

    inner = jax.jit(
        shard,
        in_shardings=(key_sh, row_sh, key_sh) + mask_sh + (row_sh,),
        out_shardings=(key_sh, row_sh, key_sh, key_sh, key_sh, row_sh),
        donate_argnums=(3 + len(mask_sh),),
    )
    state = {"dq": None}

    def fn(*args):
        if state["dq"] is None:
            state["dq"] = jax.device_put(
                np.zeros(
                    (spec.num_executors * spec.recv_capacity, spec.width), spec.dtype
                ),
                row_sh,
            )
        *outs, dq = inner(*args, state["dq"])
        state["dq"] = dq
        return tuple(outs)

    fn.spec = spec
    return fn


def expand_counts(out_capacity: int, cnt: jnp.ndarray, method: str = "scan"):
    """Output place -> the input row that emits it: row ``i`` of ``cnt`` (int32,
    >= 0) emits ``cnt[i]`` consecutive output rows, in row order.  Returns
    ``(j, within, ok, total)`` over ``out_capacity`` places: ``j[p]`` the
    emitting row (clipped into range), ``within[p]`` the place's rank among
    that row's emissions, ``ok`` the places under the true emission count and
    ``total`` that count, wrap-guarded: the int32 cumsum wraps at ~2.1e9, so a
    float32 shadow sum (exact enough for detection) saturates it at int32 max
    and a caller's ``total > out_capacity`` check cannot pass silently.
    ``method`` is ``jnp.searchsorted``'s (``"sort"`` where the places
    outnumber the rows' logarithm many times over)."""
    offs = exclusive_cumsum(cnt)
    cum = jnp.cumsum(cnt)
    total = jnp.where(
        jnp.sum(cnt.astype(jnp.float32)) > jnp.float32(2**31 - 1),
        jnp.int32(np.iinfo(np.int32).max),
        cum[-1].astype(jnp.int32),
    )
    pos = jnp.arange(out_capacity, dtype=jnp.int32)
    j = jnp.clip(
        jnp.searchsorted(cum, pos, side="right", method=method).astype(jnp.int32), 0, cnt.shape[0] - 1
    )
    return j, pos - offs[j], pos < total, total


def expand_matches(
    out_capacity: int,
    sbk: jnp.ndarray,
    btotal: jnp.ndarray,
    probe_keys: jnp.ndarray,
    probe_valid: jnp.ndarray,
    probe_cap: int,
    build_cap: int,
    join_type: str = "inner",
):
    """Sort-merge match expansion shared by the hash join and the transitive
    closure: given the build side's sorted (padded) keys ``sbk`` with
    ``btotal`` valid rows and the probe keys, emit per output row p its probe
    index ``j[p]`` and build index ``li[p]``.

    Returns (j, li, ok, unmatched, total): ``ok`` masks rows past the true
    emission count; ``unmatched`` marks left-outer null-extension rows (always
    all-False for inner); ``total`` is wrap-guarded — int32 cumsum wraps at
    ~2.1e9 matches, so a float32 shadow sum (exact enough for detection)
    saturates the reported total at int32 max so a caller's ``total >
    out_capacity`` overflow check cannot pass silently.

    Per-probe-row emission by ``join_type`` (m = its build-match count):
    'inner' m rows; 'left_outer' max(m, 1) — the extra row is null-extended
    (its ``li`` is meaningless, ``unmatched`` True, caller substitutes nulls
    for build lanes); 'left_semi' min(m, 1) — EXISTS (``li`` points at the
    first match in SORTED build order; SQL semi emits probe columns only, so
    callers should not read build lanes through it); 'left_anti' 1 if m == 0
    else 0 — NOT EXISTS, ``li`` meaningless and ``unmatched`` True on every
    emitted row."""
    lo = jnp.searchsorted(sbk, probe_keys, side="left").astype(jnp.int32)
    hi = jnp.minimum(jnp.searchsorted(sbk, probe_keys, side="right").astype(jnp.int32), btotal)
    matched = jnp.where(probe_valid, jnp.maximum(hi - lo, 0), 0)
    cnt = jnp.where(probe_valid, _join_emit(join_type)(matched, jnp), 0)
    j, within, ok, total = expand_counts(out_capacity, cnt)
    li = jnp.clip(lo[j] + within, 0, build_cap - 1)
    # semantically all-False for inner/semi (their emitted rows always have a
    # match) — computed uniformly, the caller's null-substitution masks on it
    unmatched = ok & (matched[j] == 0)
    return j, li, ok, unmatched, total


# ----------------------------------------------------------------------------
# Hash join (inner equi-join)
# ----------------------------------------------------------------------------


@dataclass(frozen=True)
class JoinSpec:
    """Static description of one compiled equi-join.

    ``build_*`` is the hash-table (dimension) side, ``probe_*`` the streamed
    (fact) side.  In SQL terms the probe side is the LEFT operand:
    ``SELECT ... FROM probe [LEFT OUTER] JOIN build ON key``.  ``join_type``:

    * ``'inner'`` — m matches emit m rows;
    * ``'left_outer'`` — every valid probe row is preserved; a matchless one
      emits one null-extended output (zeroed build lanes, flagged False in
      the extra ``out_matched`` output).  TPC-H q13 (customer LEFT OUTER JOIN
      orders) puts customer on the probe side;
    * ``'left_semi'`` — EXISTS: each probe row with >= 1 match emits exactly
      one row, build lanes zeroed — SQL semi joins emit probe columns only
      (q4/q21's correlated EXISTS);
    * ``'left_anti'`` — NOT EXISTS: each matchless probe row emits one row,
      build lanes zeroed (q22's NOT EXISTS);
    * ``'right_outer'`` — every valid build row is preserved: inner expansion
      plus one row per matchless build row (zeroed probe lanes, flagged False
      in ``out_matched``);
    * ``'full_outer'`` — both sides preserved: left_outer expansion plus the
      matchless build rows (TPC-DS q97's store/catalog FULL OUTER JOIN).

    ``out_capacity``: per-executor output rows — bound the many-to-many
    expansion (for PK-FK joins like TPC-H's, probe_recv_capacity is enough)."""

    num_executors: int
    build_capacity: int
    build_recv_capacity: int
    build_width: int
    probe_capacity: int
    probe_recv_capacity: int
    probe_width: int
    out_capacity: int
    dtype: np.dtype = np.dtype(np.int32)
    axis_name: str = "ex"
    impl: str = "auto"
    #: True compiles the WHERE-pushdown variant: the jitted fn takes two extra
    #: per-row bool inputs (build_mask, probe_mask) and filtered rows never
    #: enter either exchange — the filtered-join shape of TPC-H q3/q5.
    with_filters: bool = False
    join_type: str = "inner"

    def resolve_impl(self, platform: Optional[str] = None) -> "JoinSpec":
        return replace(self, impl=resolve_collective_impl(self.impl, platform))

    def validate(self) -> None:
        if self.impl not in ("ragged", "dense"):
            raise ValueError(f"unknown impl {self.impl!r}")
        if np.dtype(self.dtype).itemsize != 4:
            raise ValueError("value dtype must be 32-bit (keys bitcast through it)")
        if self.join_type not in JOIN_TYPES:
            raise ValueError(
                f"unknown join_type {self.join_type!r} (valid: {JOIN_TYPES})"
            )


def _join_body(spec: JoinSpec, bkeys, bvals, bnum, pkeys, pvals, pnum,
               bmask=None, pmask=None):
    n = spec.num_executors

    def cspec(cap, recv_cap, width):
        return ColumnarSpec(
            num_executors=n,
            capacity=cap,
            recv_capacity=recv_cap,
            width=width + 1,
            dtype=spec.dtype,
            axis_name=spec.axis_name,
            impl=spec.impl,
        )

    bvalid = jnp.arange(spec.build_capacity, dtype=jnp.int32) < bnum[0]
    pvalid = jnp.arange(spec.probe_capacity, dtype=jnp.int32) < pnum[0]
    if bmask is not None:  # WHERE pushdown (see AggregateSpec.with_filter)
        bvalid &= bmask
        pvalid &= pmask

    # Hash-partition both sides: equal keys co-locate.
    rbk, rbv, rbvalid, rbtotal = exchange_keyed_rows(
        cspec(spec.build_capacity, spec.build_recv_capacity, spec.build_width),
        bkeys, bvals, bvalid,
    )
    rpk, rpv, rpvalid, rptotal = exchange_keyed_rows(
        cspec(spec.probe_capacity, spec.probe_recv_capacity, spec.probe_width),
        pkeys, pvals, pvalid,
    )

    # Sort the build side; padding rows (forced KEY_MAX, stable) occupy exactly
    # the tail [btotal, cap), even when valid rows carry the sentinel key.
    btotal = rbvalid.sum().astype(jnp.int32)
    border = jnp.argsort(padded_keys(rbk, rbvalid), stable=True)
    sbk = padded_keys(rbk, rbvalid)[border]
    sbv = rbv[border]

    # Match range per probe row (hi clamped at btotal so a KEY_MAX probe key
    # never matches build padding), expanded into the static output.  Right
    # and full outer run their probe-driven BASE expansion here; the build
    # side's unmatched rows are appended after it.
    base_type = _OUTER_BASE.get(spec.join_type, spec.join_type)
    j, li, ok, unmatched, total = expand_matches(
        spec.out_capacity, sbk, btotal, rpk, rpvalid,
        spec.probe_recv_capacity, spec.build_recv_capacity,
        join_type=base_type,
    )
    zero = jnp.zeros((), spec.dtype)
    out_keys = jnp.where(ok, rpk[j], jnp.uint32(0))
    if spec.join_type in ("left_semi", "left_anti"):
        # SQL semi/anti joins emit probe columns only — and "the" build match
        # is ambiguous for semi (sorted-build order != host input order)
        out_build = jnp.zeros((spec.out_capacity, spec.build_width), spec.dtype)
    else:
        out_build = jnp.where((ok & ~unmatched)[:, None], sbv[li], zero)
    out_probe = jnp.where(ok[:, None], rpv[j], zero)
    out_matched = ok & ~unmatched
    if spec.join_type in _OUTER_BASE:
        # Build-side match-flag pass: sort the probe keys, binary-search each
        # valid build row, and append the matchless build rows (zeroed probe
        # lanes, matched=False) compacted after the base expansion.  Equal
        # keys are indistinguishable, so clamping the right bound at ptotal
        # handles valid-KEY_MAX vs padding exactly as expand_matches does.
        ptotal = rpvalid.sum().astype(jnp.int32)
        spk = jnp.sort(padded_keys(rpk, rpvalid))
        lob = jnp.searchsorted(spk, sbk, side="left").astype(jnp.int32)
        hib = jnp.minimum(
            jnp.searchsorted(spk, sbk, side="right").astype(jnp.int32), ptotal
        )
        bvalid_sorted = (
            jnp.arange(spec.build_recv_capacity, dtype=jnp.int32) < btotal
        )
        build_unmatched = bvalid_sorted & (jnp.maximum(hib - lob, 0) == 0)
        dest = jnp.where(
            build_unmatched,
            total + exclusive_cumsum(build_unmatched.astype(jnp.int32)),
            spec.out_capacity,  # matched/padding rows scatter out of range
        )
        out_keys = out_keys.at[dest].set(sbk, mode="drop")
        out_build = out_build.at[dest].set(sbv, mode="drop")
        # out_probe and out_matched stay zeros/False on the appended rows.
        ub = build_unmatched.sum().astype(jnp.int32)
        imax = jnp.int32(np.iinfo(np.int32).max)
        total = jnp.where(total > imax - ub, imax, total + ub)  # keep saturation
    outs = (out_keys, out_build, out_probe, total[None], jnp.stack([rbtotal, rptotal])[None, :])
    if spec.join_type in OUTER_JOIN_TYPES:
        outs += (out_matched,)  # out_matched: False = null-extended row
    return outs


def build_hash_join(mesh: Mesh, spec: JoinSpec):
    """Compile the distributed equi-join (``spec.join_type``) for ``mesh``.

    Returns jitted ``fn(build_keys, build_values, build_num, probe_keys,
    probe_values, probe_num) ->
    (out_keys, out_build, out_probe, out_counts, recv_totals)`` — with
    ``spec.with_filters`` the signature gains trailing per-row bool
    ``(build_mask, probe_mask)``: False rows never enter either exchange
    (the filtered-join WHERE pushdown); with an outer ``spec.join_type``
    (left_outer / right_outer / full_outer) the outputs gain a sixth
    ``out_matched`` (n * out_capacity,) bool — False marks a null-extended
    row (zeroed build lanes for an unmatched probe row; zeroed probe lanes
    for an unmatched build row of a right/full outer join):

    * inputs are sharded like build_grouped_aggregate's (keys uint32, values
      (rows, width) of ``dtype``, num (n,) int32);
    * ``out_keys``: (n * out_capacity,) uint32 — join key per output row;
    * ``out_build`` / ``out_probe``: matched value rows, aligned;
    * ``out_counts``: (n,) int32 — emitted rows on each shard.  A count >
      ``out_capacity`` means the emitted prefix was truncated: re-run with a
      larger ``out_capacity`` (same overflow contract as SortSpec);
    * ``recv_totals``: (n, 2) int32 — TRUE (build, probe) rows hashed to each
      shard; a value above the side's recv_capacity means that exchange
      truncated and matches were lost.
    """
    if spec.num_executors != mesh.devices.size:
        raise ValueError(f"spec.num_executors={spec.num_executors} != mesh size {mesh.devices.size}")
    spec = spec.resolve_impl(platform=mesh.devices.reshape(-1)[0].platform)
    spec.validate()
    ax = spec.axis_name

    extra_in = (P(ax), P(ax)) if spec.with_filters else ()
    extra_out = (P(ax),) if spec.join_type in OUTER_JOIN_TYPES else ()
    shard = shard_map(
        functools.partial(_join_body, spec),
        mesh=mesh,
        in_specs=(P(ax), P(ax, None), P(ax)) * 2 + extra_in,
        out_specs=(P(ax), P(ax, None), P(ax, None), P(ax), P(ax, None)) + extra_out,
        check_vma=False,
    )
    key_sh = NamedSharding(mesh, P(ax))
    row_sh = NamedSharding(mesh, P(ax, None))
    fn = jax.jit(
        shard,
        in_shardings=(key_sh, row_sh, key_sh) * 2
        + ((key_sh, key_sh) if spec.with_filters else ()),
        out_shardings=(key_sh, row_sh, row_sh, key_sh, row_sh)
        + ((key_sh,) if spec.join_type in OUTER_JOIN_TYPES else ()),
    )
    fn.spec = spec
    return fn


def run_grouped_aggregate(
    mesh: Mesh,
    spec: AggregateSpec,
    keys: np.ndarray,
    values: np.ndarray,
    max_attempts: int = 3,
    mask: Optional[np.ndarray] = None,
):
    """Host driver: shard, run the compiled GROUP BY, retry with doubled
    ``recv_capacity`` when hash skew overflows a shard — the GroupByTest job
    surface (run_distributed_sort's contract for aggregation).

    ``keys``: (T,) uint32; ``values``: (T, len(aggs)).  With a
    ``spec.with_filter`` spec, ``mask`` (T,) bool is required: False rows are
    dropped on device before the exchange.  Returns (group keys ascending,
    aggregated columns, counts) as host arrays.  When any column is 'avg' the
    value array comes back float64 with avg columns divided exactly by the
    group counts (the device computes the fused sum; counts ride along free).
    """
    n = spec.num_executors
    total = keys.shape[0]
    cap = spec.capacity
    if total > n * cap:
        raise ValueError(f"{total} rows exceed {n} x {cap} capacity")
    if mesh.devices.size != n:
        raise ValueError(f"mesh size {mesh.devices.size} != num_executors {n}")
    if spec.with_filter != (mask is not None):
        raise ValueError(
            "spec.with_filter=True needs a mask argument (and a mask needs "
            "with_filter=True): the compiled signatures differ"
        )

    if spec.combine == "auto":
        # host-side dense-domain detection: the dense fused combine needs
        # every key inside [0, G); measure G from the ACTUAL keys (pow2-
        # bucketed — a compile-cache key dimension) and let resolve_combine
        # keep it only when the accumulator undercuts the exchanged slot
        # grid, else take the bounded sorted fallback
        if keys.size:
            g = 1 << int(np.max(keys)).bit_length()  # pow2 ceil of max+1
            spec = replace(spec, combine_groups=int(g)).resolve_combine()
        else:
            spec = replace(spec, combine="sorted")

    pk, pv, nv = shard_rows_host(keys, values, n, cap, value_dtype=spec.dtype)

    key_sh = NamedSharding(mesh, P(spec.axis_name))
    row_sh = NamedSharding(mesh, P(spec.axis_name, None))
    gk = jax.device_put(pk, key_sh)
    gv = jax.device_put(pv, row_sh)
    gn = jax.device_put(nv, key_sh)
    extra = ()
    if mask is not None:
        # the mask rides the same contiguous deal as its rows; padding = False
        pm, _, _ = shard_rows_host(
            mask.astype(np.uint32), np.zeros((total, 0), np.int32), n, cap
        )
        extra = (jax.device_put(pm.astype(bool), key_sh),)

    attempt_spec = spec
    for _ in range(max_attempts):
        fn = build_grouped_aggregate(mesh, attempt_spec)
        out_k, out_v, out_c, num_groups, recv_totals = fn(gk, gv, gn, *extra)
        if (np.asarray(recv_totals) <= attempt_spec.recv_capacity).all():
            keys_h, vals_h, cnts_h = unpack_shard_prefixes(
                (out_k, out_v, out_c), np.asarray(num_groups),
                attempt_spec.recv_capacity,
            )
            order = np.argsort(keys_h)
            keys_h, vals_h, cnts_h = keys_h[order], vals_h[order], cnts_h[order]
            if "avg" in spec.aggs:
                vals_h = vals_h.astype(np.float64)
                for c, agg in enumerate(spec.aggs):
                    if agg == "avg":
                        vals_h[:, c] /= np.maximum(cnts_h, 1)
            return keys_h, vals_h, cnts_h
        attempt_spec = replace(
            attempt_spec, recv_capacity=2 * attempt_spec.recv_capacity
        )
    raise RuntimeError(
        f"aggregation overflowed recv_capacity {attempt_spec.recv_capacity // 2} "
        f"after {max_attempts} doublings — hash(key) distribution too skewed"
    )


def run_plan_grouped_aggregate(
    mesh: Mesh,
    spec: AggregateSpec,
    plan,
    keys: np.ndarray,
    values: np.ndarray,
    mask: Optional[np.ndarray] = None,
    stats=None,
):
    """Drive one partial grouped aggregation through an ``ExchangePlan`` with
    the UNIFIED EXECUTOR — the compute-in-exchange route composed with quota
    sub-rounds (``plan.chunks_per_round``), exactly the engine the transports
    run raw shuffles through:

    * stage A (once): one jitted shard body does the map-side partial reduce
      and seals the partial rows into the staging slot layout
      (``slot = capacity`` rows per destination, count==0 padding);
    * stage B (per sub-round, via ``transport.executor.execute_plan``): slice
      the quota window out of the sealed payload ON DEVICE
      (``skew.slice_subround``), run the fused-combine exchange
      ``transport.executor.build_plan_exchange`` lowered for the plan
      (``plan.combine == 'dense'`` routes to ``build_combine_exchange``), and
      merge each sub-round's identity-seeded accumulator into the running one
      in ``finish_round`` (``ops/combine.merge_accumulators``, running
      accumulator first — deterministic float order).  The drain ships the
      O(groups) accumulator, never the landed rows;
    * stage C (once): dense compaction through the same
      :func:`_segment_reduce` the single-shot fused body uses.

    Integer results are bit-identical to :func:`run_grouped_aggregate` with
    any quota (segment sums associate).  Only the dense tier composes with
    sub-round chunking (a bounded sorted accumulator cannot merge across
    sub-rounds without a second full sort); plans with ``combine != 'dense'``
    fall back to :func:`run_grouped_aggregate`.
    """
    from sparkucx_tpu.ops.combine import acc_init, merge_accumulators
    from sparkucx_tpu.ops.skew import chunk_size_rows, slice_subround
    from sparkucx_tpu.transport.executor import build_plan_exchange, execute_plan

    if plan.combine != "dense":
        return run_grouped_aggregate(mesh, spec, keys, values, mask=mask)
    if spec.combine == "auto":
        spec = spec.resolve_combine()
    spec = spec.resolve_impl(platform=mesh.devices.reshape(-1)[0].platform)
    spec = replace(spec, combine="dense")
    spec.validate()
    if len(plan.chunks_per_round) != 1:
        raise ValueError(
            "one aggregation is one staging round — plan the quota as "
            f"chunks_per_round=(k,), got {plan.chunks_per_round}"
        )
    n = spec.num_executors
    cap = spec.capacity
    ax = spec.axis_name
    cspec = spec.combine_cspec
    lane = cspec.row_width
    if spec.width + 2 != lane and spec.quantize_mode == "off":
        raise ValueError(f"row lane mismatch: {spec.width + 2} != {lane}")
    q = int(plan.slot_rows)
    G = cspec.num_groups

    key_sh = NamedSharding(mesh, P(ax))
    row_sh = NamedSharding(mesh, P(ax, None))

    # ---- stage A: partial reduce + slot sealing (once) ----
    def _seal(keys, values, num_valid, mask=None):
        idx = jnp.arange(cap, dtype=jnp.int32)
        valid = idx < num_valid[0]
        if mask is not None:
            valid &= mask
        qspec = spec.qspec if spec.quantize_mode != "off" else None
        keys, values, valid = _partial_rows(
            spec, qspec, cap, idx, keys, values, valid, tight=(mask is None)
        )
        rows = jnp.concatenate(
            [
                jax.lax.bitcast_convert_type(keys.astype(jnp.uint32), spec.dtype)[:, None],
                values,
            ],
            axis=1,
        )
        owners = hash_owners(keys, n, valid)
        sizes = jnp.bincount(owners, length=n + 1)[:n].astype(jnp.int32)
        order = jnp.argsort(owners, stable=True)
        sowners = owners[order]
        start = exclusive_cumsum(sizes)
        pos = idx - start[jnp.clip(sowners, 0, n - 1)]
        dest = jnp.where(sowners < n, sowners * cap + pos, n * cap)
        slot = (
            jnp.zeros((n * cap, lane), spec.dtype).at[dest].set(rows[order], mode="drop")
        )
        return slot, sizes[None, :]

    mask_in = (P(ax),) if spec.with_filter else ()
    seal = jax.jit(
        shard_map(
            _seal,
            mesh=mesh,
            in_specs=(P(ax), P(ax, None), P(ax)) + mask_in,
            out_specs=(P(ax, None), P(ax, None)),
            check_vma=False,
        ),
        in_shardings=(key_sh, row_sh, key_sh)
        + ((key_sh,) if spec.with_filter else ()),
        out_shardings=(row_sh, row_sh),
    )

    # ---- stage B: the plan's sub-rounds through the unified executor ----
    exchange = build_plan_exchange(
        mesh,
        num_executors=n,
        send_rows=n * q,
        lane=lane,
        axis_name=ax,
        impl=plan.lowering,
        combine=cspec,
    )

    # one compiled slicer per chunk index (the window offset is static — the
    # plan has few chunks, all pow2-bucketed, so this stays a tiny cache)
    slicers = {}

    def _slicer(chunk: int):
        if chunk not in slicers:

            def _slice(payload, size_row, *, _c=chunk):
                return (
                    slice_subround(payload, n, _c, q, xp=jnp),
                    chunk_size_rows(size_row, _c, q, xp=jnp),
                )

            slicers[chunk] = jax.jit(
                shard_map(
                    _slice,
                    mesh=mesh,
                    in_specs=(P(ax, None), P(ax, None)),
                    out_specs=(P(ax, None), P(ax, None)),
                    check_vma=False,
                ),
                in_shardings=(row_sh, row_sh),
                out_shardings=(row_sh, row_sh),
            )
        return slicers[chunk]

    # identity seed, replicated host-side once — each sub-round donates a
    # fresh device copy to the exchange (merge_accumulators folds them)
    av0, ac0 = acc_init(cspec)
    av_host = np.tile(np.asarray(av0), (n, 1))
    ac_host = np.tile(np.asarray(ac0), (n, 1))

    merge = jax.jit(
        lambda av, ac, bv, bc: merge_accumulators(cspec, (av, ac), (bv, bc)),
        donate_argnums=(0, 1),
    )

    total = keys.shape[0]
    if total > n * cap:
        raise ValueError(f"{total} rows exceed {n} x {cap} capacity")
    if spec.with_filter != (mask is not None):
        raise ValueError("spec.with_filter and mask must agree (see run_grouped_aggregate)")
    pk, pv, nv = shard_rows_host(keys, values, n, cap, value_dtype=spec.dtype)
    extra = ()
    if mask is not None:
        pm, _, _ = shard_rows_host(
            mask.astype(np.uint32), np.zeros((total, 0), np.int32), n, cap
        )
        extra = (jax.device_put(pm.astype(bool), key_sh),)
    payload, size_row = seal(
        jax.device_put(pk, key_sh),
        jax.device_put(pv, row_sh),
        jax.device_put(nv, key_sh),
        *extra,
    )

    def submit(rnd, chunk, nchunks):
        sub_payload, sub_sizes = _slicer(chunk)(payload, size_row)
        return exchange(
            sub_payload,
            sub_sizes,
            jax.device_put(av_host, row_sh),
            jax.device_put(ac_host, row_sh),
        )

    def finish_round(rnd, nchunks, parts):
        accv, accc, recv = parts[0]
        for bv, bc, brecv in parts[1:]:
            accv, accc = merge(accv, accc, bv, bc)
            recv = recv + brecv
        return accv, accc, recv

    results = execute_plan(
        plan,
        submit=submit,
        drain_chunk=lambda rnd, chunk, nchunks, ticket: ticket,
        finish_round=finish_round,
        # the drain-side telemetry now counts the O(groups) accumulator, not
        # O(rows) received rows — the fused route's headline memory win
        result_bytes=lambda r: int(r[0].nbytes + r[1].nbytes),
        occupancy=lambda r: (int(np.asarray(r[2]).sum()), n * cap),
        stats=stats,
        name="aggregate.fused",
    )
    accv, accc, recv_sizes = results[0]

    # ---- stage C: compaction (once) + host finish ----
    def _compact(accv, accc):
        gk, gv, gc, ng = _segment_reduce(
            spec.aggs,
            spec.recv_capacity,
            jnp.arange(G, dtype=jnp.uint32),
            accv,
            accc[:, 0] > 0,
            counts=accc[:, 0],
            tight=False,
        )
        return gk, gv, gc, ng[None]

    compact = jax.jit(
        shard_map(
            _compact,
            mesh=mesh,
            in_specs=(P(ax, None), P(ax, None)),
            out_specs=(P(ax), P(ax, None), P(ax), P(ax)),
            check_vma=False,
        ),
        in_shardings=(row_sh, row_sh),
        out_shardings=(key_sh, row_sh, key_sh, key_sh),
    )
    out_k, out_v, out_c, num_groups = compact(accv, accc)
    if (np.asarray(num_groups) > spec.recv_capacity).any():
        raise RuntimeError(
            f"dense compaction overflowed recv_capacity {spec.recv_capacity}; "
            "re-plan with headroom"
        )
    keys_h, vals_h, cnts_h = unpack_shard_prefixes(
        (out_k, out_v, out_c), np.asarray(num_groups), spec.recv_capacity
    )
    order = np.argsort(keys_h)
    keys_h, vals_h, cnts_h = keys_h[order], vals_h[order], cnts_h[order]
    if "avg" in spec.aggs:
        vals_h = vals_h.astype(np.float64)
        for c, agg in enumerate(spec.aggs):
            if agg == "avg":
                vals_h[:, c] /= np.maximum(cnts_h, 1)
    return keys_h, vals_h, cnts_h


# ----------------------------------------------------------------------------
# CPU oracles
# ----------------------------------------------------------------------------


def oracle_aggregate(
    keys: np.ndarray, values: np.ndarray, aggs: Sequence[str]
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """numpy reference: (distinct keys ascending, aggregated columns, counts).
    Mirrors run_grouped_aggregate's output conventions: 'avg' columns are
    exact float64 sum/count (and flip the whole value array to float64);
    'count_distinct' columns carry per-group distinct value counts."""
    uniq, inv, counts = np.unique(keys, return_inverse=True, return_counts=True)
    cols = []
    for c, agg in enumerate(aggs):
        if agg in ("sum", "avg"):
            s = np.bincount(inv, weights=values[:, c].astype(np.float64), minlength=len(uniq))
            cols.append((s / counts) if agg == "avg" else s.astype(values.dtype))
        elif agg == "count_distinct":
            nd = np.zeros(len(uniq), np.int64)
            for g in range(len(uniq)):
                nd[g] = len(np.unique(values[inv == g, c]))
            cols.append(nd.astype(values.dtype))
        else:
            red = np.minimum if agg == "min" else np.maximum
            ident = (
                np.finfo(values.dtype).max
                if np.issubdtype(values.dtype, np.floating)
                else np.iinfo(values.dtype).max
            )
            if agg == "max":
                ident = -ident if np.issubdtype(values.dtype, np.floating) else np.iinfo(values.dtype).min
            acc = np.full(len(uniq), ident, values.dtype)
            red.at(acc, inv, values[:, c])
            cols.append(acc)
    out = np.stack(cols, axis=1) if cols else np.zeros((len(uniq), 0), values.dtype)
    return uniq, out, counts.astype(np.int32)


def plan_join_capacities(
    build_keys: np.ndarray,
    probe_keys: np.ndarray,
    num_executors: int,
    join_type: str = "inner",
) -> Tuple[int, int, int]:
    """Exact per-shard (build_recv, probe_recv, out) capacities for a hash
    join of these keys, from the host twin of the device placement hash —
    what any driver should do instead of guessing skew headroom.  Key k's
    rows land on its owner shard and emit ``pcount(k) * f(bcount(k))``
    rows there, with f per the join type (inner: b; left_outer: max(b, 1);
    left_semi: min(b, 1); left_anti: b == 0); right/full outer additionally
    emit each probe-matchless build row once on its key's owner shard."""
    n = num_executors
    brecv = max(1, int(np.bincount(hash_owners_host(build_keys, n), minlength=n).max()))
    precv = max(1, int(np.bincount(hash_owners_host(probe_keys, n), minlength=n).max()))
    uk_b, cb = np.unique(build_keys, return_counts=True)
    uk_p, cp = np.unique(probe_keys, return_counts=True)
    present = np.isin(uk_p, uk_b)
    bcount = np.zeros(len(uk_p), np.int64)
    bcount[present] = cb[np.searchsorted(uk_b, uk_p[present])]
    base_type = _OUTER_BASE.get(join_type, join_type)
    per_key = cp * _join_emit(base_type)(bcount, np)
    per_shard = np.zeros(n, np.int64)
    if len(uk_p):
        np.add.at(per_shard, hash_owners_host(uk_p, n), per_key)
    if join_type in _OUTER_BASE:
        only_build = ~np.isin(uk_b, uk_p)
        if only_build.any():
            np.add.at(
                per_shard, hash_owners_host(uk_b[only_build], n), cb[only_build]
            )
    return brecv, precv, max(1, int(per_shard.max()))


def run_hash_join(
    mesh: Mesh,
    build_keys: np.ndarray,
    build_vals: np.ndarray,
    probe_keys: np.ndarray,
    probe_vals: np.ndarray,
    axis_name: str = "ex",
    impl: str = "auto",
    build_capacity: Optional[int] = None,
    probe_capacity: Optional[int] = None,
    join_type: str = "inner",
):
    """Host driver for the equi-join: plan receive/output capacities exactly
    from the placement hash (:func:`plan_join_capacities`), shard both sides,
    run the compiled join, and verify the device placement agreed with the
    host plan.  Returns flat (keys, build_rows, probe_rows) in
    shard-concatenated order — compare as a multiset (``oracle_join`` returns
    one); with an outer ``join_type`` (left/right/full) a fourth ``matched``
    bool array is returned (False rows are null-extended: zeroed build lanes
    for unmatched probe rows, zeroed probe lanes for unmatched build rows).
    ``'left_semi'``/``'left_anti'`` keep the 3-tuple with build lanes zeroed
    (SQL semi/anti emit probe columns only).  The
    capacity-planning + unpack half every join caller needs, like
    run_grouped_aggregate is for GROUP BY.  ``build_capacity``/
    ``probe_capacity`` override the tight per-shard input capacities (callers
    that over-provision exercise the padding paths; tests do)."""
    if build_vals.dtype != probe_vals.dtype:
        raise ValueError(
            f"build/probe value dtypes must match (keys bitcast through them): "
            f"{build_vals.dtype} != {probe_vals.dtype}"
        )
    n = int(mesh.devices.size)
    bcap = build_capacity or max(1, -(-len(build_keys) // n))
    pcap = probe_capacity or max(1, -(-len(probe_keys) // n))
    brecv, precv, out_cap = plan_join_capacities(
        build_keys, probe_keys, n, join_type=join_type
    )
    spec = JoinSpec(
        num_executors=n,
        build_capacity=bcap, build_recv_capacity=brecv,
        build_width=build_vals.shape[1],
        probe_capacity=pcap, probe_recv_capacity=precv,
        probe_width=probe_vals.shape[1],
        out_capacity=out_cap,
        dtype=build_vals.dtype,
        axis_name=axis_name,
        impl=impl,
        join_type=join_type,
    )
    fn = build_hash_join(mesh, spec)
    bk, bv, bn = shard_rows_host(build_keys, build_vals, n, bcap, value_dtype=spec.dtype)
    pk, pv, pn = shard_rows_host(probe_keys, probe_vals, n, pcap, value_dtype=spec.dtype)
    key_sh = NamedSharding(mesh, P(axis_name))
    row_sh = NamedSharding(mesh, P(axis_name, None))
    outs = fn(
        jax.device_put(bk, key_sh), jax.device_put(bv, row_sh), jax.device_put(bn, key_sh),
        jax.device_put(pk, key_sh), jax.device_put(pv, row_sh), jax.device_put(pn, key_sh),
    )
    ok, ob, op_, oc, rt = outs[:5]
    rt = np.asarray(rt)
    if not ((rt[:, 0] <= brecv).all() and (rt[:, 1] <= precv).all()):
        raise RuntimeError(
            f"device hash placement diverged from the host plan (build "
            f"{rt[:, 0].max()}/{brecv}, probe {rt[:, 1].max()}/{precv})"
        )
    oc = np.asarray(oc)
    if not (oc <= out_cap).all():
        raise RuntimeError(
            f"join output overflowed the exact host plan ({oc.max()} > {out_cap})"
        )
    if join_type in OUTER_JOIN_TYPES:
        keys, brows, prows, matched = unpack_shard_prefixes(
            (ok, ob, op_, outs[5]), oc, out_cap
        )
        return keys, brows, prows, matched
    keys, brows, prows = unpack_shard_prefixes((ok, ob, op_), oc, out_cap)
    return keys, brows, prows


def oracle_join(
    build_keys: np.ndarray,
    build_vals: np.ndarray,
    probe_keys: np.ndarray,
    probe_vals: np.ndarray,
    join_type: str = "inner",
):
    """numpy reference equi-join: rows (key, build_row, probe_row), as a
    sorted multiset of tuples for order-insensitive comparison.  With an
    outer ``join_type`` a fourth ``matched`` bool array is returned and
    null-extended rows zero the missing side (run_hash_join's convention):
    'left_outer' emits one zero-build row per matchless probe row,
    'right_outer' inner matches plus one zero-probe row per matchless build
    row, 'full_outer' both; ``'left_semi'`` emits each matched probe row once
    and ``'left_anti'`` each matchless probe row once, both with zeroed build
    lanes (SQL semi/anti emit probe columns only)."""
    from collections import defaultdict

    base_type = _OUTER_BASE.get(join_type, join_type)
    left_outer = base_type == "left_outer"
    by_key = defaultdict(list)
    for k, row in zip(build_keys, build_vals):
        by_key[int(k)].append(row)
    zero_build = np.zeros(build_vals.shape[1], build_vals.dtype)
    keys, brows, prows, matched = [], [], [], []
    for k, prow in zip(probe_keys, probe_vals):
        hits = by_key.get(int(k), ())
        if base_type == "left_semi":
            # probe columns only: one zero-build row per matched probe row
            hits = [zero_build] if hits else []
        elif base_type == "left_anti":
            if not hits:
                keys.append(int(k))
                brows.append(zero_build)
                prows.append(prow)
                matched.append(False)
            continue
        for brow in hits:
            keys.append(int(k))
            brows.append(brow)
            prows.append(prow)
            matched.append(True)
        if left_outer and not hits:
            keys.append(int(k))
            brows.append(zero_build)
            prows.append(prow)
            matched.append(False)
    if join_type in _OUTER_BASE:
        # right/full outer: append each probe-matchless build row once
        probe_keyset = {int(k) for k in probe_keys}
        zero_probe = np.zeros(probe_vals.shape[1], probe_vals.dtype)
        for k, brow in zip(build_keys, build_vals):
            if int(k) not in probe_keyset:
                keys.append(int(k))
                brows.append(brow)
                prows.append(zero_probe)
                matched.append(False)
    outer = join_type in OUTER_JOIN_TYPES
    if not keys:
        out = (
            np.zeros(0, np.uint32),
            np.zeros((0, build_vals.shape[1]), build_vals.dtype),
            np.zeros((0, probe_vals.shape[1]), probe_vals.dtype),
        )
        return out + (np.zeros(0, bool),) if outer else out
    out = (np.array(keys, np.uint32), np.stack(brows), np.stack(prows))
    return out + (np.array(matched),) if outer else out


# ----------------------------------------------------------------------------
# Local operators over a reduce partition's key-ordered records
# ----------------------------------------------------------------------------
#
# What ``sort_rows`` is to the distributed sort: the reduce side's GROUP BY and
# sort-merge JOIN over ONE partition as the ordered device read hands it out
# (``TpuShuffleReader.read_device()`` under ``key_ordering``: a ``(capacity,
# lanes)`` int32 array whose first ``count`` rows are the task's fixed-width
# records in the order of their first ``key_bytes`` bytes, the rows after them
# zero).  No exchange and no mesh: the served shuffle has already put equal
# keys into one partition.  Keys are byte strings of ``key_bytes`` bytes
# compared WHOLE, in the ordered read's own order (``ops.sort.key_order``), so
# an operator's output is key-ordered too and feeds the next one as it is.
# Shapes are static, counts are runtime scalars: one executable a shuffle's
# geometry, nothing compiles after a query's first task.

#: rows an operator's limb sums stay exact at (8-bit limbs in uint32 lanes)
RECORDS_MAX = 1 << 23
#: ``info`` of an operator's result, an int32 vector: rows handed out, rows
#: the operator made (more than handed out = ``out_capacity`` too small), 1
#: where a sum left 63 bits or a value was negative, the input's groups
INFO_ROWS, INFO_TOTAL, INFO_OVERFLOW, INFO_GROUPS = range(4)
HAVING = (None, "gt")
RECORD_JOINS = ("inner", "left_semi")


def _comparable_keys(records: jnp.ndarray, key_bytes: int) -> jnp.ndarray:
    """The records' key lanes as a ``(k, N)`` ``uint32`` array in
    ``ops.sort.comparable_lanes``' form: its lexicographic order, lane 0
    first, is the ordered read's, and two keys are equal iff every lane is."""
    k = key_lanes_of(key_bytes)
    if not 1 <= k <= records.shape[1]:
        raise ValueError(f"a {key_bytes}-byte key in records of {records.shape[1]} lanes")
    lanes = jax.lax.bitcast_convert_type(records[:, :k].T, jnp.uint32)
    return jnp.stack(comparable_lanes(list(lanes), key_bytes)[0])


def _row_count(count) -> jnp.ndarray:
    """A records array's count: a scalar, or the ``info`` vector of the
    operator that made the array (its ``INFO_ROWS``) — taken inside the
    executable, so that chaining operators dispatches nothing between them."""
    count = jnp.asarray(count)
    return (count[INFO_ROWS] if count.ndim else count).astype(jnp.int32)


def _lanes_differ(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Where two ``(k, N)`` key arrays differ in any lane."""
    return (a != b).any(axis=0)


def _lex_before(a: jnp.ndarray, b: jnp.ndarray, or_equal: bool) -> jnp.ndarray:
    """``a < b`` (``<=`` with ``or_equal``) over ``(k, N)`` comparable key
    lanes, lane 0 most significant."""
    out = (a[-1] <= b[-1]) if or_equal else (a[-1] < b[-1])
    for lane in range(a.shape[0] - 2, -1, -1):
        out = (a[lane] < b[lane]) | ((a[lane] == b[lane]) & out)
    return out


def _key_ranges(sorted_keys: jnp.ndarray, count: jnp.ndarray, queries: jnp.ndarray):
    """``[lo, hi)``: the rows of the first ``count`` of ``sorted_keys`` (``(k,
    N)``, ascending) that equal each of ``queries`` (``(k, M)``).  A binary
    search over whole keys, both bounds in one loop of ``log2(N)`` steps of
    ``M`` fetches a lane: cheap where the queries are the few."""
    n = sorted_keys.shape[1]

    def step(_, state):
        out = []
        for (lo, hi), or_equal in zip(state, (False, True)):
            mid = (lo + hi) >> 1
            probe = sorted_keys[:, jnp.clip(mid, 0, n - 1)]
            right = (lo < hi) & _lex_before(probe, queries, or_equal)
            out.append((jnp.where(right, mid + 1, lo), jnp.where((lo < hi) & ~right, mid, hi)))
        return tuple(out)

    zeros = jnp.zeros(queries.shape[1], jnp.int32)
    ends = jnp.broadcast_to(count.astype(jnp.int32), zeros.shape)
    (lo, _), (hi, _) = jax.lax.fori_loop(0, max(1, n).bit_length(), step, ((zeros, ends), (zeros, ends)))
    return lo, hi


def _compact_method(out_capacity: int, rows: int) -> str:
    """``jnp.searchsorted``'s method for ``out_capacity`` places over ``rows``
    counts: a scan of ``log2(rows)`` fetches a place where the places are few,
    one sort where they are not."""
    return "scan" if out_capacity * max(1, rows).bit_length() <= rows else "sort"


@functools.partial(jax.jit, static_argnames=("key_bytes", "value_lane", "having", "out_capacity"))
def grouped_sum_records(records, count, threshold, *, key_bytes: int, value_lane: int,
                        having: Optional[str], out_capacity: int):
    """GROUP BY key, SUM(value) [HAVING SUM(value) > threshold] over one
    partition's key-ordered records (the executable ``jit_grouped_sum_records``
    of a device trace).

    ``records``: ``(capacity, lanes)`` int32, the first ``count`` rows ordered
    by their first ``key_bytes`` bytes (``count``: a scalar, or the ``info``
    of the operator whose ``rows`` these are).  The value is the 8 bytes at lanes
    ``value_lane`` and ``value_lane + 1``: a little-endian non-negative
    integer under 2**63.  ``threshold``: ``(2,)`` uint32, low word first,
    read only under ``having="gt"`` (strictly greater).

    Returns ``(rows, info)``: ``rows`` ``(out_capacity, lanes)`` — a row a
    group that passed, in key order: the group's LAST record with its value
    replaced by the group's sum; zero after them — and ``info`` (``INFO_*``).
    Only the groups that passed are compacted.

    The sum is exact: each value is split into eight 8-bit limbs, a limb's
    running sum over a partition stays under 2**31 (``RECORDS_MAX`` rows), so
    it never wraps and is non-decreasing — a group's sum is the running sum
    at its last row less the one before its first, which a running maximum
    over the group starts carries forward.  No scatter and no gather over the
    partition: two cumulative passes over ``(8, capacity)``.  The limbs are
    put together with carries; a sum past 63 bits, or a value with its top
    bit set, sets ``info[INFO_OVERFLOW]`` — the caller raises, no wrapped sum
    is handed on."""
    capacity, lanes = records.shape
    if capacity > RECORDS_MAX:
        raise ValueError(f"{capacity} records a partition: the limb sums are exact up to {RECORDS_MAX}")
    if having not in HAVING:
        raise ValueError(f"unknown having {having!r} (valid: {HAVING})")
    if not 0 <= value_lane <= lanes - 2:
        raise ValueError(f"an 8-byte value at lane {value_lane} of {lanes}")
    idx = jnp.arange(capacity, dtype=jnp.int32)
    valid = idx < _row_count(count)
    keys = _comparable_keys(records, key_bytes)
    differs = _lanes_differ(keys[:, 1:], keys[:, :-1])
    is_start = valid & jnp.concatenate([jnp.ones(1, bool), differs])
    is_end = valid & jnp.concatenate([differs | ~valid[1:], jnp.ones(1, bool)])

    words = jax.lax.bitcast_convert_type(records[:, value_lane : value_lane + 2].T, jnp.uint32)
    words = jnp.where(valid[None, :], words, jnp.uint32(0))
    limbs = jnp.stack([(words[w] >> (8 * b)) & jnp.uint32(0xFF) for w in (0, 1) for b in range(4)])
    running = jnp.cumsum(limbs, axis=1)
    before_group = jax.lax.cummax(jnp.where(is_start[None, :], running - limbs, jnp.uint32(0)), axis=1)
    carry = jnp.zeros(capacity, jnp.uint32)
    digits = []
    for limb in running - before_group:
        carry = carry + limb
        digits.append(carry & jnp.uint32(0xFF))
        carry = carry >> 8
    low, high = (sum(d << (8 * b) for b, d in enumerate(digits[w : w + 4])) for w in (0, 4))
    unfit = (carry != 0) | (high >> 31 != 0)
    overflow = (is_end & unfit).any() | (words[1] >> 31 != 0).any()

    keep = is_end
    if having == "gt":
        t_low, t_high = threshold[0].astype(jnp.uint32), threshold[1].astype(jnp.uint32)
        keep = keep & ((high > t_high) | ((high == t_high) & (low > t_low)))
    src, _, ok, total = expand_counts(out_capacity, keep.astype(jnp.int32), _compact_method(out_capacity, capacity))
    rows = gather_rows(records, src)
    sums = jax.lax.bitcast_convert_type(jnp.stack([low[src], high[src]], axis=1), records.dtype)
    rows = jnp.where(ok[:, None], rows.at[:, value_lane : value_lane + 2].set(sums), 0)
    info = jnp.stack([jnp.minimum(total, out_capacity), total, overflow.astype(jnp.int32),
                      is_start.sum(dtype=jnp.int32)])
    return rows, info


@functools.partial(jax.jit, static_argnames=("key_bytes", "join_type", "out_capacity"))
def merge_join_records(probe, probe_count, build, build_count, *, key_bytes: int, join_type: str,
                       out_capacity: int):
    """Sort-merge equi-join of one partition's key-ordered records (``probe``,
    the SQL LEFT side: what the ordered device read handed out) against a
    key-ordered ``build`` side — another read, or an operator's ``rows`` —
    on their first ``key_bytes`` bytes, compared whole (the executable
    ``jit_merge_join_records`` of a device trace).

    Both are ``(capacity, lanes)`` int32 with their first ``*_count`` rows in
    the one key order (a count is a scalar or the making operator's ``info``).  ``inner``: a row a matching (probe, build) pair — the
    probe record's lanes, then the build row's lanes after its key — by build
    row, then probe row: key order.  ``left_semi``: each probe record whose
    key the build side holds, once, as it is, in key order.

    Returns ``(rows, info)`` as ``grouped_sum_records`` does (``info``'s
    overflow and groups are 0): ``(out_capacity, lanes)`` rows, zero after
    the ``info[INFO_ROWS]`` handed out; ``info[INFO_TOTAL]`` above
    ``out_capacity`` means the join made more rows than it could hand out.

    Each BUILD key is looked up in the probe side (``_key_ranges``): the
    work is ``build rows x log2(probe rows)`` fetches and ``out_capacity``
    row gathers, whatever the probe side's size — the reduce side's joins
    are a few survivors against a partition."""
    if join_type not in RECORD_JOINS:
        raise ValueError(f"unknown join_type {join_type!r} over records (valid: {RECORD_JOINS})")
    probe_keys, build_keys = _comparable_keys(probe, key_bytes), _comparable_keys(build, key_bytes)
    lo, hi = _key_ranges(probe_keys, _row_count(probe_count), build_keys)
    emits = jnp.arange(build.shape[0], dtype=jnp.int32) < _row_count(build_count)
    if join_type == "left_semi":  # a key the build side holds twice still passes a probe record once
        emits = emits & jnp.concatenate(
            [jnp.ones(1, bool), _lanes_differ(build_keys[:, 1:], build_keys[:, :-1])]
        )
    cnt = jnp.where(emits, hi - lo, 0)
    j, within, ok, total = expand_counts(out_capacity, cnt, _compact_method(out_capacity, build.shape[0]))
    rows = gather_rows(probe, jnp.clip(lo[j] + within, 0, probe.shape[0] - 1))
    if join_type == "inner":
        rows = jnp.concatenate([rows, gather_rows(build, j)[:, key_lanes_of(key_bytes):]], axis=1)
    rows = jnp.where(ok[:, None], rows, 0)
    zero = jnp.zeros((), jnp.int32)
    return rows, jnp.stack([jnp.minimum(total, out_capacity), total, zero, zero])


def oracle_grouped_sum_records(records: np.ndarray, key_bytes: int, value_byte: int,
                               threshold: Optional[int] = None) -> np.ndarray:
    """numpy reference of ``grouped_sum_records`` over ``(n, record_bytes)``
    ``uint8`` records in ANY order: a row a group in key order (keys as byte
    strings), the group's last record in the input's order with its 8-byte
    value at ``value_byte`` replaced by the exact sum (Python integers)."""
    groups: dict = {}
    for row in records:
        key = row[:key_bytes].tobytes()
        total = groups.get(key, (0, None))[0] + int.from_bytes(row[value_byte : value_byte + 8].tobytes(), "little")
        groups[key] = (total, row)
    out = []
    for key in sorted(groups):
        total, row = groups[key]
        if threshold is None or total > threshold:
            row = row.copy()
            row[value_byte : value_byte + 8] = np.frombuffer(total.to_bytes(8, "little"), np.uint8)
            out.append(row)
    return np.stack(out) if out else np.zeros((0, records.shape[1]), np.uint8)
