"""Receive-side combine math for the compute-in-exchange path (ROADMAP 2).

The fused grouped-aggregate exchange stops materializing received rows: as
each scheduled window lands (the FAST ring's supersteps, ops/ici_exchange.py),
it is dequantized and folded into a fixed dense per-group accumulator — the
EQuARX in-collective-compute argument (PAPERS.md, arXiv:2506.17615) applied to
the shuffle's reduce side.  Post-exchange memory and D2H drain bytes go from
O(rows) to O(groups).

This module is the single source of the combine arithmetic.  Both users —
the scheduled walk (ops/ici_exchange.build_combine_exchange) and the
relational fused body (ops/relational.py) — call :func:`combine_window` on
windows in the SAME canonical order (own slot first, then schedule items in
step order), so exact dtypes are bit-identical against the unfused path by
construction (tests/test_fused_combine.py pins it).

Window row layout is the partial-aggregate exchange row
(ops/relational._aggregate_body): ``[key (uint32 bitcast) | payload | count
(int32 bitcast)]``, all lanes in the aggregate dtype.  Validity is exactly
``count > 0``: every real partial row carries count >= 1 and staging padding
rows are all-zero, so no separate valid lane crosses the wire.  The payload
is either ``width`` plain value lanes or the quantized packing
(ops/compress.quantize_rows) dequantized per window as it lands.

``count_distinct`` needs the full value multiset, so partial aggregation —
and therefore the fused combine — rejects it upstream
(``AggregateSpec.validate``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import numpy as np

from sparkucx_tpu.ops.compress import QuantizeSpec

#: aggregates the dense combine accumulator can fold (everything partial
#: aggregation supports; avg lanes carry SUM until the host divides)
COMBINE_AGGS: Tuple[str, ...] = ("sum", "min", "max", "avg")

#: the ``ExchangePlan.combine`` tier vocabulary
COMBINE_TIERS: Tuple[str, ...] = ("off", "dense", "sorted")


def agg_identity(agg: str, dtype):
    """The fold identity of one aggregate column (scalar, numpy dtype)."""
    dtype = np.dtype(dtype)
    if agg == "min":
        info = np.finfo(dtype) if np.issubdtype(dtype, np.floating) else np.iinfo(dtype)
        return dtype.type(info.max)
    if agg == "max":
        info = np.finfo(dtype) if np.issubdtype(dtype, np.floating) else np.iinfo(dtype)
        return dtype.type(info.min)
    return dtype.type(0)


@dataclass(frozen=True)
class CombineSpec:
    """Static geometry of one dense fused-combine accumulator.

    Frozen/hashable — part of the exchange builders' compile-cache keys, so
    callers must bucket ``num_groups`` (pow2, like every other cache key
    dimension) before constructing one.
    """

    #: dense key-domain size: keys are uint32 in [0, num_groups)
    num_groups: int
    #: per value column, in column order (VALID_AGGS minus count_distinct)
    aggs: Tuple[str, ...]
    #: aggregate value dtype (int32, or float32 under quantization)
    dtype: Any = np.int32
    #: lossy payload packing of the landed windows ('off' = plain lanes)
    quantize_mode: str = "off"
    quantize_block: int = 128

    @property
    def width(self) -> int:
        return len(self.aggs)

    @property
    def qspec(self) -> Optional[QuantizeSpec]:
        if self.quantize_mode == "off":
            return None
        return QuantizeSpec(mode=self.quantize_mode, block_size=self.quantize_block)

    @property
    def payload_width(self) -> int:
        """Value lanes of one exchange row (quantized packing included)."""
        q = self.qspec
        return q.quantized_width(self.width) if q is not None else self.width

    @property
    def row_width(self) -> int:
        """Total lanes of one exchange row: key + payload + count."""
        return 1 + self.payload_width + 1

    def validate(self) -> None:
        if self.num_groups <= 0:
            raise ValueError("num_groups must be positive")
        bad = [a for a in self.aggs if a not in COMBINE_AGGS]
        if bad:
            raise ValueError(f"aggregates {bad} not dense-combinable {COMBINE_AGGS}")
        q = self.qspec
        if q is not None:
            q.validate()
            if not np.issubdtype(np.dtype(self.dtype), np.floating):
                raise ValueError("quantized combine requires a float dtype")


def acc_init(spec: CombineSpec):
    """Fresh accumulator ``(acc_vals (G, width), acc_counts (G, 1))`` — every
    column at its fold identity, counts zero.  Traced jnp (callable inside a
    shard body); counts are 2-D, one column beside the value columns."""
    import jax.numpy as jnp

    cols = [
        jnp.full((spec.num_groups, 1), agg_identity(a, spec.dtype), dtype=spec.dtype)
        for a in spec.aggs
    ]
    return jnp.concatenate(cols, axis=1), jnp.zeros((spec.num_groups, 1), jnp.int32)


def combine_window(spec: CombineSpec, window, acc_vals, acc_counts):
    """Fold ONE landed exchange window into the dense accumulator.

    ``window``: ``(rows, spec.row_width)`` in ``spec.dtype`` lanes, the
    sender-major grid region one schedule item delivered.  Pure jnp over
    static shapes (no per-row scatter): a ``(rows, num_groups)`` one-hot mask
    turns every fold into a masked column reduction.  Invalid rows
    (count == 0: staging padding, quota-truncated tails) hit no group.
    """
    import jax
    import jax.numpy as jnp

    keys = jax.lax.bitcast_convert_type(window[:, 0], jnp.uint32)
    counts = jax.lax.bitcast_convert_type(window[:, -1:], jnp.int32)
    payload = window[:, 1:-1]
    q = spec.qspec
    if q is not None:
        from sparkucx_tpu.ops.compress import dequantize_rows

        words = jax.lax.bitcast_convert_type(payload, jnp.int32)
        payload = dequantize_rows(q, words, spec.width).astype(spec.dtype)
    valid = counts[:, 0] > 0
    domain = jnp.arange(spec.num_groups, dtype=jnp.uint32)
    hit = (keys[:, None] == domain[None, :]) & valid[:, None]  # (rows, G)
    acc_counts = acc_counts + jnp.sum(
        jnp.where(hit, counts, 0), axis=0, dtype=jnp.int32
    )[:, None]
    zero = jnp.zeros((), spec.dtype)
    cols = []
    for c, agg in enumerate(spec.aggs):
        col = payload[:, c : c + 1]  # (rows, 1) — broadcasts over the mask
        if agg in ("sum", "avg"):
            cols.append(acc_vals[:, c] + jnp.sum(jnp.where(hit, col, zero), axis=0))
        elif agg == "min":
            ident = agg_identity("min", spec.dtype)
            cols.append(jnp.minimum(acc_vals[:, c], jnp.min(jnp.where(hit, col, ident), axis=0)))
        else:  # max
            ident = agg_identity("max", spec.dtype)
            cols.append(jnp.maximum(acc_vals[:, c], jnp.max(jnp.where(hit, col, ident), axis=0)))
    return jnp.stack(cols, axis=1), acc_counts


def merge_accumulators(spec: CombineSpec, a, b):
    """Merge two dense accumulators (quota sub-rounds, running-plan chaining).

    Associative and commutative for min/max/counts; sum/avg columns merge in
    argument order, which every caller keeps fixed (running accumulator
    first) so float merges stay deterministic."""
    import jax.numpy as jnp

    (av, ac), (bv, bc) = a, b
    cols = []
    for c, agg in enumerate(spec.aggs):
        if agg in ("sum", "avg"):
            cols.append(av[:, c] + bv[:, c])
        elif agg == "min":
            cols.append(jnp.minimum(av[:, c], bv[:, c]))
        else:
            cols.append(jnp.maximum(av[:, c], bv[:, c]))
    return jnp.stack(cols, axis=1), ac + bc
