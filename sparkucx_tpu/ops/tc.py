"""Device-resident transitive closure — the ``SparkTC`` workload.

The reference's integration gate is ``run_groupby_test && run_tc_test``
(buildlib/test.sh:175-179,196): SparkTC computes the transitive closure of a
random edge set by iterating ``tc = (tc union tc.join(edges)).distinct()`` to a
fixpoint, with the driver re-counting after every round.  The reference
accelerates only the shuffle under that job's joins/distincts; here — like
ops/sort.py for TeraSort and ops/relational.py for the SQL plans — the ENTIRE
iteration runs on the executor mesh as one jitted SPMD step:

    hash-exchange tc by dst + edges by src  ->  local sort-merge expansion
    (new paths a->c from a->b and b->c)     ->  union with tc  ->
    hash-exchange pairs by mix(a,b)         ->  local lex-sort dedup (DISTINCT)

The Python-side loop only compares the global pair count between rounds —
exactly the role Spark's driver plays (``while (nextCount != oldCount)``); the
per-round work is 3 ragged collectives + device-local compute, no
data-dependent shapes.

Vertex ids must be < 0xFFFFFFFF (the KEY_MAX padding sentinel — the same
discipline as ops/sort.py).  All capacities are static; every step reports true
totals so overflow is detectable, the SortSpec.recv_capacity contract.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparkucx_tpu.ops.columnar import ColumnarSpec
from sparkucx_tpu.ops.exchange import resolve_collective_impl
from sparkucx_tpu.ops.relational import exchange_keyed_rows, expand_matches, padded_keys
from sparkucx_tpu.ops.sort import KEY_MAX

_MIX_A = np.uint32(2654435761)  # Knuth multiplicative
_MIX_B = np.uint32(40503)       # 16-bit Fibonacci constant, odd


def _pair_mix(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Mix a pair of uint32s into one uint32 partitioning key (only duplicate
    pairs MUST collide; quality just balances shards)."""
    h = a.astype(jnp.uint32) * _MIX_A
    h = h ^ ((h >> 15) | (b.astype(jnp.uint32) * _MIX_B))
    return h * _MIX_A


@dataclass(frozen=True)
class TcSpec:
    """Static description of one compiled TC iteration.

    ``edge_capacity``: per-executor input edges.  ``tc_capacity``: per-executor
    closure rows — must hold each shard's slice of the final closure (hash of
    the pair mix balances shards, so ~|closure|/n with headroom).
    ``join_capacity``: per-executor new-path expansion bound per round.
    ``recv_*`` default to the matching capacity; raise them for skewed graphs
    (a high-degree hub vertex routes all its paths to one shard in the join)."""

    num_executors: int
    edge_capacity: int
    tc_capacity: int
    join_capacity: int
    edge_recv_capacity: Optional[int] = None
    tc_recv_capacity: Optional[int] = None
    axis_name: str = "ex"
    impl: str = "auto"

    @property
    def edge_recv(self) -> int:
        return self.edge_recv_capacity or self.edge_capacity

    @property
    def tc_recv(self) -> int:
        return self.tc_recv_capacity or self.tc_capacity

    def resolve_impl(self, platform: Optional[str] = None) -> "TcSpec":
        return replace(self, impl=resolve_collective_impl(self.impl, platform))

    def validate(self) -> None:
        if self.impl not in ("ragged", "dense"):
            raise ValueError(f"unknown impl {self.impl!r}")


def _lex_dedup(a: jnp.ndarray, b: jnp.ndarray, valid: jnp.ndarray, out_rows: int):
    """Sort pairs lexicographically ((a, b), padding last) and keep one of each
    — the device DISTINCT.  Returns (a', b', count) with the distinct pairs as
    a tight ascending prefix."""
    a = padded_keys(a, valid)
    b = jnp.where(valid, b.astype(jnp.uint32), KEY_MAX)
    # two-pass stable sort = lexicographic (b minor, a major)
    order_b = jnp.argsort(b, stable=True)
    order = order_b[jnp.argsort(a[order_b], stable=True)]
    sa, sb = a[order], b[order]
    svalid = valid[order]
    first = jnp.concatenate(
        [jnp.ones(1, bool), (sa[1:] != sa[:-1]) | (sb[1:] != sb[:-1])]
    ) & svalid
    seg = jnp.where(svalid, jnp.cumsum(first.astype(jnp.int32)) - 1, out_rows)
    count = first.sum().astype(jnp.int32)
    out_a = jnp.full(out_rows, KEY_MAX, jnp.uint32).at[seg].set(sa, mode="drop")
    out_b = jnp.full(out_rows, KEY_MAX, jnp.uint32).at[seg].set(sb, mode="drop")
    return out_a, out_b, count


def _as_val(x: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(x.astype(jnp.uint32), jnp.int32)[:, None]


def _cspec(spec: TcSpec, cap: int, recv: int, width: int) -> ColumnarSpec:
    return ColumnarSpec(
        num_executors=spec.num_executors, capacity=cap, recv_capacity=recv,
        width=width + 1, dtype=np.dtype(np.int32), axis_name=spec.axis_name,
        impl=spec.impl,
    )


def _tc_prep_body(spec: TcSpec, e_src, e_dst, e_num):
    """One-time build-side prep: hash-exchange the immutable edge set by src
    and sort it — every iterated round reuses the result instead of repeating
    the exchange + sort (the edges never change)."""
    e_valid = jnp.arange(spec.edge_capacity, dtype=jnp.int32) < e_num[0]
    rek, rev, revalid, re_total = exchange_keyed_rows(
        _cspec(spec, spec.edge_capacity, spec.edge_recv, 1), e_src, _as_val(e_dst), e_valid
    )
    btotal = revalid.sum().astype(jnp.int32)
    border = jnp.argsort(padded_keys(rek, revalid), stable=True)
    sbk = padded_keys(rek, revalid)[border]
    sbc = jax.lax.bitcast_convert_type(rev[border][:, 0], jnp.uint32)
    return sbk, sbc, btotal[None], re_total[None]


def _tc_step_body(spec: TcSpec, tc_a, tc_b, tc_num, sbk, sbc, btotal):
    tc_valid = jnp.arange(spec.tc_capacity, dtype=jnp.int32) < tc_num[0]

    # 1. co-locate paths a->b (keyed by b) with the pre-sorted edges b->c
    rtk, rtv, rtvalid, rt_total = exchange_keyed_rows(
        _cspec(spec, spec.tc_capacity, spec.tc_recv, 1), tc_b, _as_val(tc_a), tc_valid
    )

    # 2. sort-merge expansion (shared with the hash join): probe = tc rows,
    #    build = edges; each match emits the new path (a, c)
    j, li, new_ok, _, new_total = expand_matches(
        spec.join_capacity, sbk, btotal[0], rtk, rtvalid, spec.tc_recv, spec.edge_recv
    )
    new_a = jnp.where(
        new_ok, jax.lax.bitcast_convert_type(rtv[j][:, 0], jnp.uint32), KEY_MAX
    )
    new_c = jnp.where(new_ok, sbc[li], KEY_MAX)

    # 3. union tc ++ new paths, re-partition by pair hash so duplicates collide
    u_a = jnp.concatenate([jnp.where(tc_valid, tc_a.astype(jnp.uint32), KEY_MAX), new_a])
    u_b = jnp.concatenate([jnp.where(tc_valid, tc_b.astype(jnp.uint32), KEY_MAX), new_c])
    u_valid = jnp.concatenate([tc_valid, new_ok])
    u_cap = spec.tc_capacity + spec.join_capacity
    ruk, ruv, ruvalid, ru_total = exchange_keyed_rows(
        _cspec(spec, u_cap, u_cap, 2),
        _pair_mix(u_a, u_b),
        jnp.concatenate([_as_val(u_a), _as_val(u_b)], axis=1),
        u_valid,
    )

    # 4. DISTINCT -> the next round's tc shard
    da = jax.lax.bitcast_convert_type(ruv[:, 0], jnp.uint32)
    db = jax.lax.bitcast_convert_type(ruv[:, 1], jnp.uint32)
    out_a, out_b, count = _lex_dedup(da, db, ruvalid, spec.tc_capacity)
    global_count = jax.lax.psum(count, spec.axis_name)
    overflow = jnp.stack([rt_total, new_total, ru_total, count])
    return out_a, out_b, count[None], global_count[None], overflow[None, :]


def _resolve(mesh: Mesh, spec: TcSpec) -> TcSpec:
    if spec.num_executors != mesh.devices.size:
        raise ValueError(f"spec.num_executors={spec.num_executors} != mesh size {mesh.devices.size}")
    spec = spec.resolve_impl(platform=mesh.devices.reshape(-1)[0].platform)
    spec.validate()
    return spec


def build_tc_prep(mesh: Mesh, spec: TcSpec):
    """Compile the one-time edge prep: ``fn(e_src, e_dst, e_num) ->
    (sorted_keys, sorted_dsts, btotals, recv_totals)`` — the edge set
    hash-partitioned by src and sorted, per shard.  ``recv_totals`` (n,) above
    ``edge_recv`` means the edge exchange truncated.  Feed the first three
    outputs to every ``build_tc_step`` call."""
    spec = _resolve(mesh, spec)
    ax = spec.axis_name
    shard = shard_map(
        functools.partial(_tc_prep_body, spec),
        mesh=mesh,
        in_specs=(P(ax), P(ax), P(ax)),
        out_specs=(P(ax), P(ax), P(ax), P(ax)),
        check_vma=False,
    )
    key_sh = NamedSharding(mesh, P(ax))
    fn = jax.jit(shard, in_shardings=(key_sh,) * 3, out_shardings=(key_sh,) * 4)
    fn.spec = spec
    return fn


def build_tc_step(mesh: Mesh, spec: TcSpec):
    """Compile one TC iteration for ``mesh``.

    Returns jitted ``fn(tc_a, tc_b, tc_num, sorted_keys, sorted_dsts, btotals)
    -> (tc_a', tc_b', tc_num', global_count, overflow)``:

    * ``tc_a``/``tc_b``: (n * tc_capacity,) uint32 sharded — current closure
      pairs a->b as a tight prefix per shard (tail = KEY_MAX padding);
    * ``tc_num``: (n,) int32 sharded — valid rows per shard;
    * ``sorted_keys``/``sorted_dsts``/``btotals`` — ``build_tc_prep`` outputs
      (the immutable edge set, partitioned and sorted exactly once);
    * outputs: next closure (same layout, now hash-partitioned by pair),
      per-shard and global distinct pair counts, and ``overflow`` (n, 4) int32 —
      per shard: (tc rows received, new paths expanded, union rows received,
      distinct pairs).  Any of the first three above its corresponding capacity
      (tc_recv / join_capacity / tc_capacity + join_capacity), or distinct
      pairs above tc_capacity, means truncation: re-run with more headroom.

    Iterate with ``run_transitive_closure`` (the SparkTC driver loop).
    """
    spec = _resolve(mesh, spec)
    ax = spec.axis_name

    shard = shard_map(
        functools.partial(_tc_step_body, spec),
        mesh=mesh,
        in_specs=(P(ax), P(ax), P(ax)) * 2,
        out_specs=(P(ax), P(ax), P(ax), P(ax), P(ax, None)),
        check_vma=False,
    )
    key_sh = NamedSharding(mesh, P(ax))
    fn = jax.jit(
        shard,
        in_shardings=(key_sh,) * 6,
        out_shardings=(key_sh, key_sh, key_sh, key_sh, NamedSharding(mesh, P(ax, None))),
    )
    fn.spec = spec
    return fn


def run_transitive_closure(
    mesh: Mesh,
    spec: TcSpec,
    edges: np.ndarray,
    max_rounds: int = 64,
) -> Tuple[np.ndarray, int]:
    """The SparkTC driver loop: seed tc = edges, iterate the compiled step
    until the global pair count stops growing (or ``max_rounds``).

    ``edges``: (E, 2) uint32 host array.  Returns (closure pairs (C, 2) uint32
    ascending-unique, rounds executed).  Raises on any capacity overflow and
    when the fixpoint is not reached within ``max_rounds`` (a partial closure
    is never returned silently).
    """
    spec = _resolve(mesh, spec)
    n = spec.num_executors
    prep = build_tc_prep(mesh, spec)
    fn = build_tc_step(mesh, spec)
    key_sh = NamedSharding(mesh, P(spec.axis_name))

    def shard_pairs(pairs: np.ndarray, cap: int):
        """Round-robin pairs over shards as tight padded prefixes."""
        a = np.full(n * cap, 0xFFFFFFFF, np.uint32)
        b = np.full(n * cap, 0xFFFFFFFF, np.uint32)
        num = np.zeros(n, np.int32)
        for s in range(n):
            mine = pairs[s::n]
            if len(mine) > cap:
                raise ValueError(f"shard {s} holds {len(mine)} pairs > capacity {cap}")
            a[s * cap : s * cap + len(mine)] = mine[:, 0]
            b[s * cap : s * cap + len(mine)] = mine[:, 1]
            num[s] = len(mine)
        return (
            jax.device_put(a, key_sh),
            jax.device_put(b, key_sh),
            jax.device_put(num, key_sh),
        )

    edges = np.unique(edges.astype(np.uint32), axis=0)
    if (edges >= 0xFFFFFFFF).any():
        raise ValueError("vertex ids must be < 0xFFFFFFFF (padding sentinel)")
    tc_a, tc_b, tc_num = shard_pairs(edges, spec.tc_capacity)
    e_src, e_dst, e_num = shard_pairs(edges, spec.edge_capacity)
    sbk, sbc, btotals, e_recv_totals = prep(e_src, e_dst, e_num)
    if (np.asarray(e_recv_totals) > spec.edge_recv).any():
        raise RuntimeError(
            f"edge_recv overflow (max {int(np.asarray(e_recv_totals).max())} > "
            f"{spec.edge_recv}) — re-run with more headroom"
        )

    count = int(np.asarray(tc_num).sum())
    converged = False
    rounds = 0
    for rounds in range(1, max_rounds + 1):
        tc_a, tc_b, tc_num, global_count, overflow = fn(
            tc_a, tc_b, tc_num, sbk, sbc, btotals
        )
        ov = np.asarray(overflow)
        caps = (
            spec.tc_recv,
            spec.join_capacity,
            spec.tc_capacity + spec.join_capacity,
            spec.tc_capacity,
        )
        names = ("tc_recv", "join_capacity", "union recv", "tc_capacity")
        for col, (cap, name) in enumerate(zip(caps, names)):
            if (ov[:, col] > cap).any():
                raise RuntimeError(
                    f"round {rounds}: {name} overflow (max {int(ov[:, col].max())} > {cap}) "
                    f"— re-run with more headroom"
                )
        new_count = int(np.asarray(global_count)[0])
        if new_count == count:
            converged = True
            break
        count = new_count
    if not converged:
        raise RuntimeError(
            f"no fixpoint after {max_rounds} rounds ({count} pairs and growing) — "
            f"raise max_rounds (rounds needed ~ graph diameter)"
        )

    # collect: valid prefixes of each shard
    a = np.asarray(tc_a).reshape(n, spec.tc_capacity)
    b = np.asarray(tc_b).reshape(n, spec.tc_capacity)
    num = np.asarray(tc_num)
    pairs = np.concatenate(
        [np.stack([a[s, : num[s]], b[s, : num[s]]], axis=1) for s in range(n)]
    )
    order = np.lexsort((pairs[:, 1], pairs[:, 0]))
    return pairs[order], rounds


def oracle_tc(edges: np.ndarray) -> np.ndarray:
    """CPU reference closure: iterated composition until fixpoint, returned as
    ascending-unique (C, 2) uint32 pairs."""
    tc = {tuple(e) for e in np.unique(edges.astype(np.uint32), axis=0)}
    by_src = {}
    for s, d in tc:
        by_src.setdefault(s, set()).add(d)
    while True:
        new = {(a, c) for a, b in tc for c in by_src.get(b, ())} - tc
        if not new:
            break
        tc |= new
    out = np.array(sorted(tc), np.uint32).reshape(-1, 2)
    return out
