"""Hierarchical (multi-slice) shuffle exchange — ICI + DCN two-phase routing.

SURVEY.md section 5.8's TPU-native mapping for the reference's transport calls
for "ICI for intra-slice, DCN for multi-slice".  The flat exchange
(ops/exchange.py) runs ONE all_to_all over every executor pair — on a
multi-slice deployment that means S*C*(S-1)*C point-to-point DCN flows of
block granularity.  This lowering factors the executor mesh into
``(dcn: slices, ici: chips-per-slice)`` and routes in two phases:

    phase A (ICI):  all_to_all over the chip axis, grouping every chip's
                    payload by DESTINATION CHIP INDEX — after it, chip c of
                    slice s holds everything its slice sends to chip c of any
                    slice;
    phase B (DCN):  all_to_all over the slice axis delivers those aggregates —
                    each datum crosses the slower DCN exactly once, in messages
                    C x bigger than the flat lowering's (the aggregation that
                    makes DCN all-to-alls viable);
    compaction:     the received slot grid is packed into the same tight
                    sender-major layout the flat lowerings produce.

The phases move whole slots (dense) — intra-slice ICI bandwidth is cheap and
XLA overlaps the two collectives; the contract (inputs, outputs, layouts) is
IDENTICAL to ``build_exchange``, and the CPU-mesh tests assert bit-equality
against the flat lowering on a factored mesh.

Flat executor id convention: ``executor = slice * chips_per_slice + chip``
(dcn-major), matching ``Mesh(devices.reshape(S, C), ("dcn", "ici"))``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparkucx_tpu.ops.exchange import ExchangeSpec, exclusive_cumsum


def device_slice_ids(devices) -> "list":
    """Per-device slice ids from the runtime topology, or None when the
    runtime exposes none (CPU meshes, single-slice TPUs without the attr).

    TPU devices carry ``slice_index`` on multi-slice deployments; this is the
    probe the mesh factorization and hop classification derive from.  Pure
    python over device attributes — unit-testable with stand-in objects."""
    ids = [getattr(d, "slice_index", None) for d in devices]
    if any(i is None for i in ids):
        return None
    return [int(i) for i in ids]


def probe_topology(devices):
    """(num_slices, chips_per_slice, devices-in-slice-major-order).

    Derives the (dcn, ici) factorization from ``slice_index`` when the
    runtime exposes it — devices are GROUPED by slice (stable within a
    slice), so each mesh row is one physical slice whatever enumeration
    order ``jax.devices()`` used.  Without slice ids (the pure-python
    fallback: CPU meshes, tests) the flat order is taken as a single slice.
    Raises if the slices are ragged — a (dcn, ici) mesh needs equal rows."""
    devs = list(devices)
    ids = device_slice_ids(devs)
    if ids is None:
        return 1, len(devs), devs
    order = sorted(set(ids))
    groups = [[d for d, i in zip(devs, ids) if i == s] for s in order]
    chips = len(groups[0])
    if any(len(g) != chips for g in groups):
        raise ValueError(
            f"ragged slices: {[len(g) for g in groups]} devices per slice_index"
        )
    return len(groups), chips, [d for g in groups for d in g]


def make_hierarchical_mesh(
    num_slices: int, chips_per_slice: int, devices=None
) -> Mesh:
    """(dcn, ici) mesh over the first S*C devices, slice-major.

    When the devices report a genuinely multi-slice topology
    (``slice_index`` with more than one distinct value) the rows follow the
    PHYSICAL slices (probe_topology groups them), not the flat enumeration
    order.  A request that disagrees with the probed factorization is still
    accepted when it is COMPATIBLE — the requested ``chips_per_slice``
    divides the physical one, so every ici row stays inside one physical
    slice (e.g. splitting a 2x8 deployment as 4x4; the extra dcn hops between
    same-slice rows just ride the conservative DCN path).  An incompatible
    request — one that would put chips of different slices on one ici row,
    where remote DMA cannot reach — raises.  Devices with no slice ids — or
    all on one slice — take the requested factorization as a LOGICAL split
    (CPU meshes, and single-slice tests of the two-phase route)."""
    devs = list(devices if devices is not None else jax.devices())
    n = num_slices * chips_per_slice
    if len(devs) < n:
        raise ValueError(f"need {n} devices, have {len(devs)}")
    devs = devs[:n]
    ids = device_slice_ids(devs)
    if ids is not None and len(set(ids)) > 1:
        s, c, devs = probe_topology(devs)  # slice-major order either way
        if (s, c) != (num_slices, chips_per_slice) and c % chips_per_slice:
            raise ValueError(
                f"runtime topology is {s}x{c} (slice_index); a "
                f"{num_slices}x{chips_per_slice} factorization would mix "
                f"slices on one ici row — chips_per_slice must divide {c}"
            )
    return Mesh(
        np.array(devs).reshape(num_slices, chips_per_slice), ("dcn", "ici")
    )


def hop_kinds(devices) -> np.ndarray:
    """(n, n) hop classification between executors: 'local' | 'ici' | 'dcn'.

    Same-slice pairs ride ICI, cross-slice pairs cross DCN; without slice
    ids every pair is ICI (single-slice fallback).  Pure python + numpy —
    the unit-testable core of the topology probe."""
    devs = list(devices)
    ids = device_slice_ids(devs) or [0] * len(devs)
    n = len(devs)
    kinds = np.empty((n, n), dtype=object)
    for i in range(n):
        for j in range(n):
            kinds[i, j] = (
                "local" if i == j else ("ici" if ids[i] == ids[j] else "dcn")
            )
    return kinds


def hop_schedule(mesh: Mesh, *, chunks_per_dest: int = 1, slot_rows=None):
    """Flow schedule(s) for ``mesh``, classified by fabric — the input the
    scheduled exchange kernel (ops/ici_exchange.py) consumes.

    * (dcn, ici) mesh: a :class:`HierarchicalSchedule` — a ring schedule per
      phase, so intra-slice ICI hops and inter-slice DCN hops get DISTINCT
      schedules (different dims, different chunking, different fabrics).
    * flat mesh, single slice (or no topology attrs): one ICI ring schedule.
    * flat mesh spanning slices: one ring schedule with every hop
      conservatively classified 'dcn' (some source crosses DCN at every
      offset under flat ordering) — use the hierarchical mesh to split them.

    ``chunks_per_dest`` is clamped per phase to a pow2 divisor of that
    phase's transfer-group rows when ``slot_rows`` is given
    (``schedule_chunks``)."""
    from sparkucx_tpu.ops.ici_exchange import (
        HierarchicalSchedule,
        ring_schedule,
        schedule_chunks,
    )

    def clamp(group_rows):
        if group_rows is None:
            return max(1, int(chunks_per_dest))
        return schedule_chunks(group_rows, chunks_per_dest)

    if set(mesh.axis_names) == {"dcn", "ici"}:
        s, c = mesh.shape["dcn"], mesh.shape["ici"]
        # the ici phase is intra-slice ICI only if every mesh row really
        # stays inside one physical slice (make_hierarchical_mesh guarantees
        # it; a hand-built mesh may not) — a mixed row is conservatively
        # 'dcn' so the lowering guard keeps remote DMA off it
        ids = device_slice_ids(mesh.devices.reshape(-1))
        ici_kind = "ici"
        if ids is not None and any(
            len(set(ids[r * c : (r + 1) * c])) > 1 for r in range(s)
        ):
            ici_kind = "dcn"
        ici_group = s * slot_rows if slot_rows is not None else None
        dcn_group = c * slot_rows if slot_rows is not None else None
        ici = ring_schedule(c, clamp(ici_group), kind=ici_kind) if c > 1 else None
        dcn = ring_schedule(s, clamp(dcn_group), kind="dcn") if s > 1 else None
        return HierarchicalSchedule(num_slices=s, chips_per_slice=c, ici=ici, dcn=dcn)
    n = mesh.devices.size
    ids = device_slice_ids(mesh.devices.reshape(-1))
    kind = "ici" if ids is None or len(set(ids)) == 1 else "dcn"
    return ring_schedule(n, clamp(slot_rows), kind=kind)


def region_permutation(order_outer: int, order_inner: int, slot: int) -> jnp.ndarray:
    """Row indices permuting a slot grid from (inner-major regions) to
    (outer-major): new region k = outer*inner_count... returns (rows,) int32.

    Used to regroup regions (a, b) -> (b, a): region at old index
    ``a * order_inner + b`` moves to new index ``b * order_outer + a``."""
    idx = np.empty(order_outer * order_inner * slot, dtype=np.int32)
    pos = 0
    for b in range(order_inner):
        for a in range(order_outer):
            start = (a * order_inner + b) * slot
            idx[pos : pos + slot] = np.arange(start, start + slot, dtype=np.int32)
            pos += slot
    return jnp.asarray(idx)


def compact_slots(flat: jnp.ndarray, recv_sizes: jnp.ndarray, slot: int, recv_rows: int):
    """Pack a sender-major slot grid into the tight layout (the dense
    lowering's compaction, shared shape — ops/exchange.py)."""
    n = recv_sizes.shape[0]
    starts = exclusive_cumsum(recv_sizes)
    cum = jnp.cumsum(recv_sizes)
    total = cum[-1]
    pos = jnp.arange(recv_rows, dtype=jnp.int32)
    k = jnp.clip(jnp.searchsorted(cum, pos, side="right").astype(jnp.int32), 0, n - 1)
    src = k * slot + (pos - starts[k])
    valid = pos < total
    rows = flat[jnp.clip(src, 0, n * slot - 1)]
    return jnp.where(valid[:, None], rows, jnp.zeros((), dtype=flat.dtype))


def _hier_shard(spec: ExchangeSpec, num_slices: int, chips: int, data, size_row):
    slot = spec.slot_rows
    s_idx = jax.lax.axis_index("dcn")
    c_idx = jax.lax.axis_index("ici")
    me = s_idx * chips + c_idx

    # full size matrix: gather over both axes, dcn-major = flat executor order
    sizes = jax.lax.all_gather(size_row, ("dcn", "ici"), tiled=True)  # (n, n)
    recv_sizes = sizes[:, me]

    # phase A prep: regions are dest-flat-major (s' outer, c' inner); regroup
    # to c'-outer so each ICI peer's group is contiguous
    perm_a = region_permutation(num_slices, chips, slot)  # (s',c') -> (c',s')
    grouped = data[perm_a]

    # phase A: ICI all_to_all over the chip axis — after it, this chip holds
    # its slice's aggregate for chip index c_idx of every slice
    a = jax.lax.all_to_all(
        grouped.reshape(chips, num_slices * slot, spec.lane),
        "ici", split_axis=0, concat_axis=0, tiled=True,
    ).reshape(chips * num_slices * slot, spec.lane)
    # layout now: (c_src, s') regions — regroup to s'-outer for the DCN phase
    perm_b = region_permutation(chips, num_slices, slot)  # (c_src,s') -> (s',c_src)
    staged = a[perm_b]

    # phase B: DCN all_to_all over the slice axis — one crossing per datum,
    # messages aggregated across the whole source slice
    b = jax.lax.all_to_all(
        staged.reshape(num_slices, chips * slot, spec.lane),
        "dcn", split_axis=0, concat_axis=0, tiled=True,
    ).reshape(num_slices * chips * slot, spec.lane)
    # layout: (s_src, c_src) regions = flat sender id ascending — compact
    out = compact_slots(b, recv_sizes, slot, spec.recv_rows)
    return out, recv_sizes[None, :]


def build_hierarchical_exchange(mesh: Mesh, spec: ExchangeSpec):
    """Compile the two-phase exchange for a (dcn, ici) mesh.

    Same contract as ``build_exchange`` (ops/exchange.py): jitted
    ``fn(data, size_matrix) -> (recv, recv_sizes)`` with data/sizes sharded
    over the FLAT executor order (slice-major product of the two mesh axes).
    ``spec.num_executors`` must equal S*C.
    """
    if set(mesh.axis_names) != {"dcn", "ici"}:
        raise ValueError(f"mesh axes must be ('dcn', 'ici'), got {mesh.axis_names}")
    num_slices = mesh.shape["dcn"]
    chips = mesh.shape["ici"]
    if spec.num_executors != num_slices * chips:
        raise ValueError(
            f"spec.num_executors={spec.num_executors} != {num_slices}x{chips} mesh"
        )
    spec.validate()

    shard = shard_map(
        functools.partial(_hier_shard, spec, num_slices, chips),
        mesh=mesh,
        in_specs=(P(("dcn", "ici"), None), P(("dcn", "ici"), None)),
        out_specs=(P(("dcn", "ici"), None), P(("dcn", "ici"), None)),
        check_vma=False,
    )
    sharding = NamedSharding(mesh, P(("dcn", "ici"), None))
    donate = (0,) if spec.send_rows == spec.recv_rows else ()
    fn = jax.jit(
        shard,
        in_shardings=(sharding, sharding),
        out_shardings=(sharding, sharding),
        donate_argnums=donate,
    )
    fn.spec = spec
    return fn
