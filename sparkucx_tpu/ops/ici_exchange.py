"""Scheduled inter-chip exchange — FAST-style flow scheduling over the ICI ring.

The stock n>1 data plane (ops/exchange.py) hands the superstep to ONE opaque
collective (``ragged_all_to_all`` / tiled ``all_to_all``) and takes whatever
flow schedule XLA picks.  FAST (PAPERS.md, arXiv:2505.09764) shows that for
all-to-all traffic the schedule itself is the headroom: chunk each
destination's payload and interleave the chunks across link-steps so a hot
lane streams on both ring directions instead of serializing behind one
transfer.  This module applies that argument to the TPU ICI torus:

* **Schedule model** (pure python, unit-testable): a
  :class:`RingSchedule` is a sequence of supersteps; each step carries at
  most one :class:`SendItem` per ring direction, so the per-step link budget
  is honored BY CONSTRUCTION.  Items are enumerated chunk-major
  (chunk 0 of every destination before chunk 1 of any), which is exactly the
  FAST interleaving: a hot destination's chunks land ``dim-1`` steps apart
  rather than back-to-back.  Offsets take the short way around the ring
  (direction +1 for d <= dim/2), antipodal offsets alternate direction by
  chunk parity so both directions carry equal load.
* **Lowerings** of the exchange and of the fused send side:

  - ``'dma'`` — Pallas kernel over ``pltpu.make_async_remote_copy``
    (pallas_kernels.ring_exchange_grid): per step, one remote DMA per ring
    direction, both in flight at once; TPU-only.
  - ``'xla'`` — the portable fallback: the SAME schedule executed as one
    ``jax.lax.ppermute`` per item inside shard_map.  This is what the 8-way
    CPU mesh and the SPMD suite run, so CI exercises the full schedule logic
    (delivery, placement, compaction) without TPU hardware.
  - ``'interpret'`` — the Pallas kernel under ``interpret=True``: on flat
    meshes CI runs it on CPU and asserts bit-equality with stock, so the
    kernel body (schedule walk, remote-copy placement, ring-position ->
    logical-device-id rebasing) is executed without TPU hardware.
    Hierarchical meshes fall back to 'xla' here (jax's interpret discharge
    of remote DMA is single-axis only).

  Remote DMA cannot cross slice boundaries: any ring classified ``'dcn'``
  by the topology probe (flat meshes spanning slices, hand-built (dcn, ici)
  meshes with mixed rows) is forced onto the 'xla' tier by
  :func:`resolve_schedule_lowering`, mirroring the hardcoded permute tier
  of the hierarchical DCN phase.

  Both lowerings land received windows in the SAME sender-major slot grid the
  dense lowering's all_to_all produces and share its compaction math
  (hierarchy.compact_slots), so results are bit-identical to the stock
  collective — pinned by tests/test_ici_exchange.py and the CI ici gate.

* **Fused send side**: :func:`build_fused_ici_exchange` composes the block
  scatter (the device-staging write, ops/pallas_kernels.build_block_scatter)
  with the scheduled exchange in ONE kernel/jit — staging->wire with no
  intermediate HBM round trip and no separate scatter launch.

* **Fused receive side**: :func:`build_combine_exchange` folds every landed
  window into a dense per-group accumulator (ops/combine.py) instead of
  compacting it into a receive buffer.  One lowering on every platform: the
  schedule's permutes with a fold per window.

* **Hierarchy**: on a (dcn, ici) mesh the two phases of the hierarchical
  route (ops/hierarchy.py) each get their OWN ring schedule
  (hierarchy.hop_schedule classifies hops from the device topology): the ICI
  phase may lower to the remote-DMA kernel, the DCN phase always rides
  scheduled XLA permutes (remote DMA cannot cross slices).

Selection: ``spark.shuffle.tpu.exchange.impl`` = ``stock`` (default, the
byte-for-byte ragged/dense path) | ``pallas`` | ``auto`` (pallas on
multi-chip TPU meshes).  The transports key their compiled-exchange caches on
the resolved impl, so both paths coexist per bucket.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparkucx_tpu.ops.exchange import (
    ExchangeSpec,
    build_exchange,
    gather_size_matrix,
)
from sparkucx_tpu.ops.hierarchy import (
    compact_slots,
    device_slice_ids,
    region_permutation,
)

LOWERINGS = ("auto", "dma", "xla", "interpret")

# Per-destination chunks the transports request (clamped per phase by
# schedule_chunks): 2 gives one level of FAST interleaving — a hot lane's
# windows ride both ring directions across two passes — without inflating
# step count; deeper chunking is a benchmark/experiment knob.
DEFAULT_CHUNKS_PER_DEST = 2


# ----------------------------------------------------------------------------
# Schedule model (pure python — no jax below this line until the lowerings)
# ----------------------------------------------------------------------------


@dataclass(frozen=True)
class SendItem:
    """One scheduled transfer: every device sends its chunk ``chunk`` of the
    slot destined ``offset`` hops ahead on the ring, riding the links of
    ``direction`` (+1 / -1).  ``kind`` labels the fabric ('ici' | 'dcn')."""

    offset: int
    chunk: int
    direction: int
    kind: str = "ici"


@dataclass(frozen=True)
class RingSchedule:
    """Supersteps over one ring axis; each step holds <= 1 item per direction.

    SPMD-symmetric: every device executes the same item list, so item
    ``(offset d, chunk c)`` simultaneously means "send my window for ``me+d``"
    and "receive the matching window from ``me-d``"."""

    dim: int
    chunks: int
    kind: str
    steps: Tuple[Tuple[SendItem, ...], ...]

    @property
    def num_steps(self) -> int:
        return len(self.steps)

    def items(self) -> List[SendItem]:
        return [item for step in self.steps for item in step]

    def raw_steps(self) -> Tuple[Tuple[Tuple[int, int, int], ...], ...]:
        """Plain-tuple view for the Pallas kernel (ops/pallas_kernels.py)."""
        return tuple(
            tuple((it.offset, it.chunk, it.direction) for it in step)
            for step in self.steps
        )


@dataclass(frozen=True)
class HierarchicalSchedule:
    """Distinct per-fabric schedules for the two-phase hierarchical route:
    the ICI phase permutes chips within a slice, the DCN phase permutes
    slices.  A phase of dim 1 is ``None`` (nothing to exchange on that axis)."""

    num_slices: int
    chips_per_slice: int
    ici: Optional[RingSchedule]
    dcn: Optional[RingSchedule]

    @property
    def num_steps(self) -> int:
        return sum(s.num_steps for s in (self.ici, self.dcn) if s is not None)


def schedule_chunks(group_rows: int, requested: int) -> int:
    """Clamp a requested per-destination chunk count to a pow2 divisor of the
    transfer group — the bucketing step that keeps chunk windows static and
    compile-cache keys pow2 (analysis/config.py BUCKETING_MARKERS)."""
    if group_rows <= 0:
        raise ValueError(f"group_rows must be positive, got {group_rows}")
    r = max(1, int(requested))
    c = 1 << (r - 1).bit_length()  # pow2 ceil
    c = min(c, group_rows)
    return math.gcd(c, group_rows)  # largest pow2 divisor of group_rows <= c


def ring_schedule(dim: int, chunks_per_dest: int = 1, kind: str = "ici") -> RingSchedule:
    """Build the bidirectional-ring flow schedule for ``dim`` devices.

    Enumeration is chunk-major — chunk 0 of EVERY destination before chunk 1
    of any (the FAST hot-lane interleaving) — split into a '+' and a '-'
    queue by short-way routing; step i pairs the i-th item of each queue, so
    "<= 1 chunk per link direction per step" holds by construction and every
    ``(offset, chunk)`` appears exactly once by enumeration."""
    if dim < 2:
        raise ValueError(f"ring schedule needs dim >= 2, got {dim}")
    if chunks_per_dest < 1:
        raise ValueError(f"chunks_per_dest must be >= 1, got {chunks_per_dest}")
    plus: List[SendItem] = []
    minus: List[SendItem] = []
    for c in range(chunks_per_dest):
        for d in range(1, dim):
            if 2 * d < dim:
                direction = 1
            elif 2 * d > dim:
                direction = -1
            else:  # antipodal offset: alternate by chunk so both rings share it
                direction = 1 if c % 2 == 0 else -1
            item = SendItem(offset=d, chunk=c, direction=direction, kind=kind)
            (plus if direction > 0 else minus).append(item)
    steps = tuple(
        tuple(q[i] for q in (plus, minus) if i < len(q))
        for i in range(max(len(plus), len(minus)))
    )
    return RingSchedule(dim=dim, chunks=chunks_per_dest, kind=kind, steps=steps)


def simulate_ring(schedule: RingSchedule):
    """Pure-python executor for schedule property tests.

    Returns ``(deliveries, link_load)``: ``deliveries[(src, dst, chunk)]`` =
    times that window was sent (must be exactly 1 for every src != dst);
    ``link_load[(step, src, direction)]`` = windows device ``src`` injected
    into that ring direction at that step (must be <= 1)."""
    n = schedule.dim
    deliveries: Dict[Tuple[int, int, int], int] = {}
    link_load: Dict[Tuple[int, int, int], int] = {}
    for si, step in enumerate(schedule.steps):
        for item in step:
            for src in range(n):
                dst = (src + item.offset) % n
                key = (src, dst, item.chunk)
                deliveries[key] = deliveries.get(key, 0) + 1
                lkey = (si, src, item.direction)
                link_load[lkey] = link_load.get(lkey, 0) + 1
    return deliveries, link_load


def step_occupancy(schedule: RingSchedule) -> List[Tuple[int, int]]:
    """Per-superstep (used, idle) link-direction slots per device."""
    return [(len(step), 2 - len(step)) for step in schedule.steps]


def resolve_exchange_impl(
    impl: str, platform: str, num_executors: int
) -> str:
    """conf.exchange_impl -> concrete engine: 'stock' | 'pallas'.

    ``auto`` picks the scheduled kernel only where the remote-DMA path can
    actually win — multi-chip TPU meshes; everywhere else the stock
    collective stays the byte-for-byte default."""
    if impl == "stock":
        return "stock"
    if impl == "pallas":
        return "pallas"
    if impl == "auto":
        return "pallas" if platform == "tpu" and num_executors > 1 else "stock"
    raise ValueError(f"unknown exchange impl {impl!r}")


def resolve_ici_lowering(lowering: str, platform: str) -> str:
    if lowering == "auto":
        return "dma" if platform == "tpu" else "xla"
    if lowering not in ("dma", "xla", "interpret"):
        raise ValueError(f"unknown ici lowering {lowering!r}")
    return lowering


def resolve_schedule_lowering(lowering: str, kind: str) -> str:
    """Fabric guard: remote DMA cannot cross slices, so any ring whose hops
    are classified ``'dcn'`` (hierarchy.hop_schedule — flat meshes spanning
    slices, or hand-built (dcn, ici) meshes whose rows mix slices) is forced
    onto the scheduled-XLA lowering — the same rule the hierarchical route
    hardcodes for its DCN phase.  'interpret' is left alone (debug tier, no
    real DMA)."""
    if kind == "dcn" and lowering == "dma":
        return "xla"
    return lowering


# ----------------------------------------------------------------------------
# Lowerings
# ----------------------------------------------------------------------------


def _axis_grid_xla(ax, dim: int, group_rows: int, sched: Optional[RingSchedule], flat, me):
    """Scheduled-permute equivalent of one tiled all_to_all over ``ax``.

    ``flat`` is the destination-major group layout (group g = rows
    ``[g*group_rows, (g+1)*group_rows)`` for axis-peer g); the result is the
    sender-major grid (row ``k*group_rows + r`` = row r of what peer k sent
    me) — exactly the all_to_all(split0, concat0, tiled) output, one
    ``ppermute`` per scheduled item instead of one opaque collective."""
    if sched is None:  # dim == 1: the group is already mine
        return flat
    lane = flat.shape[1]
    w = group_rows // sched.chunks
    grid = jnp.zeros_like(flat)
    own = jax.lax.dynamic_slice(flat, (me * group_rows, 0), (group_rows, lane))
    grid = jax.lax.dynamic_update_slice(grid, own, (me * group_rows, 0))
    for step in sched.steps:
        for item in step:
            d = item.offset
            send_row = ((me + d) % dim) * group_rows + item.chunk * w
            window = jax.lax.dynamic_slice(flat, (send_row, 0), (w, lane))
            got = jax.lax.ppermute(
                window, ax, [(i, (i + d) % dim) for i in range(dim)]
            )
            recv_row = ((me - d) % dim) * group_rows + item.chunk * w
            grid = jax.lax.dynamic_update_slice(grid, got, (recv_row, 0))
    return grid


def _axis_grid(ax, dim, group_rows, sched, flat, me, lowering, mesh_axes=None):
    """Dispatch one exchange phase to its lowering tier.  ``mesh_axes`` (full
    ordered (name, size) mesh layout) rebases ring positions to logical
    device ids for the remote-DMA tier when ``ax`` is a sub-axis."""
    if sched is None:
        return _axis_grid_xla(ax, dim, group_rows, sched, flat, me)
    lowering = resolve_schedule_lowering(lowering, sched.kind)
    if lowering == "xla":
        return _axis_grid_xla(ax, dim, group_rows, sched, flat, me)
    from sparkucx_tpu.ops.pallas_kernels import ring_exchange_grid

    return ring_exchange_grid(
        ax,
        dim,
        group_rows,
        group_rows // sched.chunks,
        sched.raw_steps(),
        flat,
        mesh_axes=mesh_axes,
        interpret=(lowering == "interpret"),
    )


def _ici_shard(spec: ExchangeSpec, sched: RingSchedule, lowering: str, data, size_row):
    """Flat-mesh shard body: scheduled grid + the dense lowering's compaction
    (bit-identical receive layout and metadata)."""
    me, sizes = gather_size_matrix(spec, size_row)
    recv_sizes = sizes[:, me]
    grid = _axis_grid(
        spec.axis_name, spec.num_executors, spec.slot_rows, sched, data, me, lowering
    )
    out = compact_slots(grid, recv_sizes, spec.slot_rows, spec.recv_rows)
    return out, recv_sizes[None, :]


def _hier_sched_shard(
    spec: ExchangeSpec, sched: HierarchicalSchedule, lowering: str, data, size_row
):
    """Hierarchical shard body: the two-phase route of hierarchy._hier_shard
    with each all_to_all replaced by that phase's OWN scheduled exchange —
    ICI hops may ride the remote-DMA kernel, DCN hops always ride scheduled
    XLA permutes (remote DMA cannot cross slices)."""
    S, C = sched.num_slices, sched.chips_per_slice
    slot = spec.slot_rows
    s_idx = jax.lax.axis_index("dcn")
    c_idx = jax.lax.axis_index("ici")
    me = s_idx * C + c_idx

    sizes = jax.lax.all_gather(size_row, ("dcn", "ici"), tiled=True)
    recv_sizes = sizes[:, me]

    perm_a = region_permutation(S, C, slot)  # (s',c') -> (c',s')
    grouped = data[perm_a]
    # the ICI ring runs over a SUB-axis: ring position c is logical device
    # s_idx * C + c, so the DMA tier needs the full mesh layout to rebase
    a = _axis_grid(
        "ici", C, S * slot, sched.ici, grouped, c_idx, lowering,
        mesh_axes=(("dcn", S), ("ici", C)),
    )
    perm_b = region_permutation(C, S, slot)  # (c_src,s') -> (s',c_src)
    staged = a[perm_b]
    b = _axis_grid("dcn", S, C * slot, sched.dcn, staged, s_idx, "xla")
    out = compact_slots(b, recv_sizes, slot, spec.recv_rows)
    return out, recv_sizes[None, :]


# ----------------------------------------------------------------------------
# Builders (same contract as ops/exchange.build_exchange)
# ----------------------------------------------------------------------------


def build_ici_exchange(
    mesh: Mesh,
    spec: ExchangeSpec,
    *,
    chunks_per_dest: int = 1,
    lowering: str = "auto",
    schedule=None,
):
    """Compile the scheduled exchange: ``fn(data, size_matrix) -> (recv,
    recv_sizes)`` — the exact contract, shardings, and donation rule of
    ``build_exchange`` (see its docstring for the layouts), with the
    collective replaced by the FAST-scheduled ring.

    Accepts flat meshes (one ring over ``spec.axis_name``) and (dcn, ici)
    meshes (a ring per phase — hierarchy.hop_schedule).  ``chunks_per_dest``
    is clamped to a pow2 divisor of each phase's transfer group
    (``schedule_chunks``); pass ``schedule`` to override entirely.
    ``lowering``: 'auto' (remote-DMA kernel on TPU, scheduled permutes
    elsewhere) | 'dma' | 'xla' | 'interpret'.
    """
    if spec.num_executors != mesh.devices.size:
        raise ValueError(
            f"spec.num_executors={spec.num_executors} != mesh size {mesh.devices.size}"
        )
    platform = mesh.devices.reshape(-1)[0].platform
    resolved = spec.resolve_impl(platform=platform)
    resolved.validate()
    if resolved.num_executors == 1:
        return build_exchange(mesh, spec)  # n=1: nothing to schedule
    low = resolve_ici_lowering(lowering, platform)
    hierarchical = set(mesh.axis_names) == {"dcn", "ici"}
    if schedule is None:
        from sparkucx_tpu.ops.hierarchy import hop_schedule

        schedule = hop_schedule(
            mesh, chunks_per_dest=chunks_per_dest, slot_rows=resolved.slot_rows
        )
    if hierarchical:
        if not isinstance(schedule, HierarchicalSchedule):
            raise ValueError("hierarchical mesh needs a HierarchicalSchedule")
        S, C = mesh.shape["dcn"], mesh.shape["ici"]
        if (schedule.num_slices, schedule.chips_per_slice) != (S, C):
            raise ValueError(
                f"schedule factorization {schedule.num_slices}x"
                f"{schedule.chips_per_slice} != mesh {S}x{C}"
            )
        # per-phase mirror of the flat branch's checks: a chunk count that
        # doesn't divide the phase's transfer group would truncate
        # window_rows and silently drop the tail of every transfer
        if schedule.ici is not None:
            if schedule.ici.dim != C:
                raise ValueError(
                    f"ici schedule dim {schedule.ici.dim} != mesh ici axis {C}"
                )
            if (S * resolved.slot_rows) % schedule.ici.chunks:
                raise ValueError(
                    f"ici chunks {schedule.ici.chunks} must divide the ICI "
                    f"transfer group {S * resolved.slot_rows} rows"
                )
        if schedule.dcn is not None:
            if schedule.dcn.dim != S:
                raise ValueError(
                    f"dcn schedule dim {schedule.dcn.dim} != mesh dcn axis {S}"
                )
            if (C * resolved.slot_rows) % schedule.dcn.chunks:
                raise ValueError(
                    f"dcn chunks {schedule.dcn.chunks} must divide the DCN "
                    f"transfer group {C * resolved.slot_rows} rows"
                )
        # effective tier: the DCN phase always rides xla; the ICI phase keeps
        # the DMA tier only when its hops really are intra-slice ICI
        if schedule.ici is None:
            low = "xla"
        else:
            low = resolve_schedule_lowering(low, schedule.ici.kind)
            if low == "interpret":
                # jax's interpret discharge of remote DMA only supports
                # single-axis meshes; the schedule logic is still exercised
                low = "xla"
        body = functools.partial(_hier_sched_shard, resolved, schedule, low)
        pspec = P(("dcn", "ici"), None)
    else:
        if not isinstance(schedule, RingSchedule):
            raise ValueError("flat mesh needs a RingSchedule")
        if schedule.dim != resolved.num_executors:
            raise ValueError(
                f"schedule dim {schedule.dim} != num_executors {resolved.num_executors}"
            )
        if resolved.slot_rows % schedule.chunks:
            raise ValueError(
                f"chunks {schedule.chunks} must divide slot_rows {resolved.slot_rows}"
            )
        # flat mesh spanning slices: hop_schedule classifies every hop 'dcn'
        # (remote DMA cannot cross slices) — ride scheduled permutes instead
        low = resolve_schedule_lowering(low, schedule.kind)
        body = functools.partial(_ici_shard, resolved, schedule, low)
        pspec = P(resolved.axis_name, None)

    shard = shard_map(
        body,
        mesh=mesh,
        in_specs=(pspec, pspec),
        out_specs=(pspec, pspec),
        check_vma=False,
    )
    sharding = NamedSharding(mesh, pspec)
    # Donation rule shared with build_exchange: staging recycles into the
    # receive buffer only when shapes match; the size matrix is never donated.
    donate = (0,) if resolved.send_rows == resolved.recv_rows else ()
    fn = jax.jit(
        shard,
        in_shardings=(sharding, sharding),
        out_shardings=(sharding, sharding),
        donate_argnums=donate,
    )
    fn.spec = resolved
    fn.schedule = schedule
    fn.lowering = low
    return fn


def build_fused_ici_exchange(
    mesh: Mesh,
    spec: ExchangeSpec,
    num_blocks: int,
    *,
    chunks_per_dest: int = 1,
    lowering: str = "auto",
    schedule=None,
    max_block_rows: Optional[int] = None,
):
    """Compile the fused send side: ``fn(starts, counts, outs, packed,
    staging, size_matrix) -> (recv, recv_sizes)`` — block scatter + scheduled
    exchange in ONE launch, no intermediate HBM round trip.

    The plan triple follows ``build_block_scatter`` (per device: starts =
    slot-layout destination rows, counts, outs = packed source offsets,
    zero-count blocks no-ops), shipped as (n, num_blocks) int32 row-sharded
    arrays; ``packed`` is the row-sharded packed map output and ``staging``
    the row-sharded slot-layout staging whose untouched rows carry through.
    On TPU the whole pipeline is one Pallas kernel
    (pallas_kernels.fused_scatter_ring_grid, staging aliased + donated); the
    portable lowering composes the window-scan scatter with the scheduled
    permutes inside the same jit — either way the separate staging kernel
    launch is gone.  Flat meshes only (device staging is a flat-cluster
    feature)."""
    if set(mesh.axis_names) == {"dcn", "ici"}:
        raise ValueError("fused exchange supports flat meshes only")
    if spec.num_executors != mesh.devices.size:
        raise ValueError(
            f"spec.num_executors={spec.num_executors} != mesh size {mesh.devices.size}"
        )
    platform = mesh.devices.reshape(-1)[0].platform
    resolved = spec.resolve_impl(platform=platform)
    resolved.validate()
    if resolved.num_executors == 1:
        raise ValueError("fused ici exchange needs num_executors > 1")
    low = resolve_ici_lowering(lowering, platform)
    if schedule is None:
        # same fabric classification as hierarchy.hop_schedule: a flat mesh
        # spanning slices means every offset crosses DCN for some source
        ids = device_slice_ids(mesh.devices.reshape(-1))
        kind = "ici" if ids is None or len(set(ids)) == 1 else "dcn"
        chunks = schedule_chunks(resolved.slot_rows, chunks_per_dest)
        schedule = ring_schedule(resolved.num_executors, chunks, kind=kind)
    if resolved.slot_rows % schedule.chunks:
        raise ValueError(
            f"chunks {schedule.chunks} must divide slot_rows {resolved.slot_rows}"
        )
    low = resolve_schedule_lowering(low, schedule.kind)
    window = max(1, max_block_rows if max_block_rows is not None else resolved.slot_rows)
    n = resolved.num_executors
    slot = resolved.slot_rows

    def body(starts, counts, outs, packed, staging, size_row):
        starts = starts.reshape(-1)
        counts = counts.reshape(-1)
        outs = outs.reshape(-1)
        me, sizes = gather_size_matrix(resolved, size_row)
        recv_sizes = sizes[:, me]
        if low == "xla":
            from sparkucx_tpu.ops.pallas_kernels import xla_scatter_windows

            staged = xla_scatter_windows(
                window, resolved.send_rows, starts, counts, outs, packed, staging
            )
            grid = _axis_grid_xla(
                resolved.axis_name, n, slot, schedule, staged, me
            )
        else:
            from sparkucx_tpu.ops.pallas_kernels import fused_scatter_ring_grid

            grid, _staged = fused_scatter_ring_grid(
                resolved.axis_name,
                n,
                slot,
                slot // schedule.chunks,
                schedule.raw_steps(),
                starts,
                counts,
                outs,
                packed,
                staging,
                interpret=(low == "interpret"),
            )
        out = compact_slots(grid, recv_sizes, slot, resolved.recv_rows)
        return out, recv_sizes[None, :]

    ax = resolved.axis_name
    pspec = P(ax, None)
    shard = shard_map(
        body,
        mesh=mesh,
        in_specs=(pspec, pspec, pspec, pspec, pspec, pspec),
        out_specs=(pspec, pspec),
        check_vma=False,
    )
    sharding = NamedSharding(mesh, pspec)
    # staging (argnum 4) is consumed by the fused kernel; donation makes the
    # in-kernel scatter a true in-place append on TPU (CPU donation warns).
    donate = (4,) if platform == "tpu" else ()
    fn = jax.jit(
        shard,
        in_shardings=(sharding,) * 6,
        out_shardings=(sharding, sharding),
        donate_argnums=donate,
    )
    fn.spec = resolved
    fn.schedule = schedule
    fn.lowering = low
    return fn


# ----------------------------------------------------------------------------
# Quantized builders (tier-b payload reduction, ops/compress.py)
# ----------------------------------------------------------------------------


def _quantized_prep(mesh: Mesh, spec, quantize, lowering: str, chunks_per_dest, schedule):
    """Shared validation + schedule resolution for the quantized builders
    (flat meshes only — the quantized payload rides one ring)."""
    if set(mesh.axis_names) == {"dcn", "ici"}:
        raise ValueError("quantized exchange supports flat meshes only")
    if spec.num_executors != mesh.devices.size:
        raise ValueError(
            f"spec.num_executors={spec.num_executors} != mesh size {mesh.devices.size}"
        )
    quantize.validate()
    if not quantize.enabled:
        raise ValueError(
            "quantized exchange needs quantize mode 'int8'|'blockfloat'; "
            "use build_ici_exchange for the lossless path"
        )
    platform = mesh.devices.reshape(-1)[0].platform
    resolved = spec.resolve_impl(platform=platform)
    resolved.validate()
    if resolved.num_executors == 1:
        raise ValueError("quantized ici exchange needs num_executors > 1")
    low = resolve_ici_lowering(lowering, platform)
    if schedule is None:
        ids = device_slice_ids(mesh.devices.reshape(-1))
        kind = "ici" if ids is None or len(set(ids)) == 1 else "dcn"
        chunks = schedule_chunks(resolved.slot_rows, chunks_per_dest)
        schedule = ring_schedule(resolved.num_executors, chunks, kind=kind)
    if not isinstance(schedule, RingSchedule):
        raise ValueError("flat mesh needs a RingSchedule")
    if resolved.slot_rows % schedule.chunks:
        raise ValueError(
            f"chunks {schedule.chunks} must divide slot_rows {resolved.slot_rows}"
        )
    low = resolve_schedule_lowering(low, schedule.kind)
    return platform, resolved, low, schedule


def build_quantized_exchange(
    mesh: Mesh,
    spec,
    quantize,
    *,
    chunks_per_dest: int = 1,
    lowering: str = "auto",
    schedule=None,
):
    """Compile the quantized scheduled exchange: ``fn(data, size_matrix) ->
    (recv, recv_sizes)`` where ``data`` is FLOAT32 ``(n * send_rows, lane)``
    — the ``build_ici_exchange`` contract with tier-b block quantization
    (ops/compress.py QuantizeSpec) fused around the collective: quantize on
    the send side, ring-exchange the int8x4-packed int32 payload
    (``quantize.quantized_width(lane)`` lanes — 4x fewer ICI bytes per float
    lane plus scales), dequantize after compaction — all inside ONE jit, so
    staging→wire stays one launch.  OPT-IN LOSSY: per-block error is bounded
    by ``quantize.error_bound`` (tests/test_compress.py tolerance gate); row
    counts and size semantics are unchanged (quantization is per-row)."""
    from sparkucx_tpu.ops.compress import dequantize_rows, quantize_rows

    platform, resolved, low, schedule = _quantized_prep(
        mesh, spec, quantize, lowering, chunks_per_dest, schedule
    )
    n, slot = resolved.num_executors, resolved.slot_rows

    def body(data, size_row):
        me, sizes = gather_size_matrix(resolved, size_row)
        recv_sizes = sizes[:, me]
        q = quantize_rows(quantize, data)
        grid = _axis_grid(resolved.axis_name, n, slot, schedule, q, me, low)
        outq = compact_slots(grid, recv_sizes, slot, resolved.recv_rows)
        out = dequantize_rows(quantize, outq, resolved.lane)
        return out, recv_sizes[None, :]

    pspec = P(resolved.axis_name, None)
    shard = shard_map(
        body, mesh=mesh, in_specs=(pspec, pspec), out_specs=(pspec, pspec),
        check_vma=False,
    )
    sharding = NamedSharding(mesh, pspec)
    # same donation rule as build_ici_exchange: the f32 staging recycles into
    # the f32 receive buffer only when shapes match
    donate = (0,) if resolved.send_rows == resolved.recv_rows else ()
    fn = jax.jit(
        shard,
        in_shardings=(sharding, sharding),
        out_shardings=(sharding, sharding),
        donate_argnums=donate,
    )
    fn.spec = resolved
    fn.schedule = schedule
    fn.lowering = low
    fn.qspec = quantize
    return fn


# ----------------------------------------------------------------------------
# Fused-combine lowering + builder (receive-side compute-in-exchange)
# ----------------------------------------------------------------------------


def combine_axis_grid(ax, dim: int, slot_rows: int, sched: RingSchedule, flat, me, cspec):
    """One fused-combine exchange phase: one ppermute per schedule item, and
    every landed window goes straight into the dense accumulator — the
    sender-major grid is never materialized, so post-exchange memory is
    O(groups).  Returns the ``(acc_vals, acc_counts)`` pair (identity-seeded —
    callers merge running accumulators via ``merge_accumulators``).  Also the
    shard-body entry point for ops/relational.py's fused aggregate, which
    runs its own shard_map.

    Fold order is canonical (own slot, then schedule items in step order) —
    bit-equality with the unfused path for exact dtypes rests on it."""
    from sparkucx_tpu.ops.combine import acc_init, combine_window

    lane = flat.shape[1]
    accv, accc = acc_init(cspec)
    own = jax.lax.dynamic_slice(flat, (me * slot_rows, 0), (slot_rows, lane))
    accv, accc = combine_window(cspec, own, accv, accc)
    w = slot_rows // sched.chunks
    for step in sched.steps:
        for item in step:
            d = item.offset
            send_row = ((me + d) % dim) * slot_rows + item.chunk * w
            window = jax.lax.dynamic_slice(flat, (send_row, 0), (w, lane))
            got = jax.lax.ppermute(
                window, ax, [(i, (i + d) % dim) for i in range(dim)]
            )
            accv, accc = combine_window(cspec, got, accv, accc)
    return accv, accc


def _combine_prep(mesh: Mesh, spec, cspec, chunks_per_dest, schedule):
    """Validation + schedule resolution for the fused-combine builder
    (flat meshes only — the combinable payload rides one ring)."""
    if set(mesh.axis_names) == {"dcn", "ici"}:
        raise ValueError("combine exchange supports flat meshes only")
    if spec.num_executors != mesh.devices.size:
        raise ValueError(
            f"spec.num_executors={spec.num_executors} != mesh size {mesh.devices.size}"
        )
    cspec.validate()
    if spec.lane != cspec.row_width:
        raise ValueError(
            f"spec.lane={spec.lane} != combine row width {cspec.row_width} "
            f"(key + payload + count)"
        )
    resolved = spec.resolve_impl(platform=mesh.devices.reshape(-1)[0].platform)
    resolved.validate()
    if resolved.num_executors == 1:
        raise ValueError("combine ici exchange needs num_executors > 1")
    if schedule is None:
        ids = device_slice_ids(mesh.devices.reshape(-1))
        kind = "ici" if ids is None or len(set(ids)) == 1 else "dcn"
        chunks = schedule_chunks(resolved.slot_rows, chunks_per_dest)
        schedule = ring_schedule(resolved.num_executors, chunks, kind=kind)
    if not isinstance(schedule, RingSchedule):
        raise ValueError("flat mesh needs a RingSchedule")
    if resolved.slot_rows % schedule.chunks:
        raise ValueError(
            f"chunks {schedule.chunks} must divide slot_rows {resolved.slot_rows}"
        )
    return resolved, schedule


def build_combine_exchange(
    mesh: Mesh,
    spec,
    cspec,
    *,
    chunks_per_dest: int = 1,
    schedule=None,
):
    """Compile the fused-combine exchange: ``fn(data, size_matrix, acc_vals,
    acc_counts) -> (acc_vals, acc_counts, recv_sizes)`` — the scheduled ring
    with the receive side REPLACED by the dense per-group fold
    (ops/combine.py): landed windows are dequantized and combined as they
    arrive, never compacted into a recv buffer.

    * ``data``: ``(n * send_rows, lane)`` slot-layout partial-aggregate
      staging, rows in the combine layout ``[key | payload | count]``
      (``cspec.row_width`` lanes, enforced against ``spec.lane``).
    * ``acc_vals`` ``(n * num_groups, width)`` / ``acc_counts``
      ``(n * num_groups, 1)`` — the RUNNING accumulator, merged with this
      exchange's fold and returned.  Both are donated (argnums 2, 3): quota
      sub-rounds thread one accumulator through every call in place instead
      of staging O(rows) per sub-round.  Seed fresh rounds with
      ``ops/combine.acc_init`` under shard_map (or tile its host values).
    * ``recv_sizes``: the usual ``(n, n)`` receive-size metadata — row
      accounting is unchanged, only the payload drain shrinks to O(groups).

    One lowering on every platform: the schedule's permutes with a fold per
    landed window (:func:`combine_axis_grid`).  Flat meshes only."""
    from sparkucx_tpu.ops.combine import merge_accumulators

    resolved, schedule = _combine_prep(mesh, spec, cspec, chunks_per_dest, schedule)
    n, slot = resolved.num_executors, resolved.slot_rows

    def body(data, size_row, accv, accc):
        me, sizes = gather_size_matrix(resolved, size_row)
        recv_sizes = sizes[:, me]
        av, ac = combine_axis_grid(
            resolved.axis_name, n, slot, schedule, data, me, cspec
        )
        accv, accc = merge_accumulators(cspec, (accv, accc), (av, ac))
        return accv, accc, recv_sizes[None, :]

    pspec = P(resolved.axis_name, None)
    shard = shard_map(
        body, mesh=mesh, in_specs=(pspec,) * 4, out_specs=(pspec,) * 3,
        check_vma=False,
    )
    sharding = NamedSharding(mesh, pspec)
    # the running accumulator is consumed and re-emitted with identical
    # shape/sharding every call — donate so sub-round chaining is in place
    fn = jax.jit(
        shard,
        in_shardings=(sharding,) * 4,
        out_shardings=(sharding,) * 3,
        donate_argnums=(2, 3),
    )
    fn.spec = resolved
    fn.schedule = schedule
    fn.cspec = cspec
    return fn


def build_quantized_fused_exchange(
    mesh: Mesh,
    spec,
    quantize,
    num_blocks: int,
    *,
    chunks_per_dest: int = 1,
    lowering: str = "auto",
    schedule=None,
    max_block_rows: Optional[int] = None,
):
    """Quantized twin of ``build_fused_ici_exchange``: ``fn(starts, counts,
    outs, packed, staging, size_matrix) -> (recv, recv_sizes)`` with FLOAT32
    packed/staging — block scatter, send-side quantize, scheduled ring
    exchange of the int32 payload, and receive-side dequantize composed in
    ONE jit/launch.  The scatter always rides the window-scan lowering
    (``xla_scatter_windows`` — the quantize sits between scatter and ring,
    so the monolithic scatter+ring kernel cannot apply); the ring itself
    still lowers per ``lowering`` ('dma' = the remote-DMA Pallas kernel on
    the quantized grid)."""
    from sparkucx_tpu.ops.compress import dequantize_rows, quantize_rows

    platform, resolved, low, schedule = _quantized_prep(
        mesh, spec, quantize, lowering, chunks_per_dest, schedule
    )
    n, slot = resolved.num_executors, resolved.slot_rows
    window = max(1, max_block_rows if max_block_rows is not None else resolved.slot_rows)

    def body(starts, counts, outs, packed, staging, size_row):
        from sparkucx_tpu.ops.pallas_kernels import xla_scatter_windows

        starts = starts.reshape(-1)
        counts = counts.reshape(-1)
        outs = outs.reshape(-1)
        me, sizes = gather_size_matrix(resolved, size_row)
        recv_sizes = sizes[:, me]
        staged = xla_scatter_windows(
            window, resolved.send_rows, starts, counts, outs, packed, staging
        )
        q = quantize_rows(quantize, staged)
        grid = _axis_grid(resolved.axis_name, n, slot, schedule, q, me, low)
        outq = compact_slots(grid, recv_sizes, slot, resolved.recv_rows)
        out = dequantize_rows(quantize, outq, resolved.lane)
        return out, recv_sizes[None, :]

    pspec = P(resolved.axis_name, None)
    shard = shard_map(
        body, mesh=mesh, in_specs=(pspec,) * 6, out_specs=(pspec, pspec),
        check_vma=False,
    )
    sharding = NamedSharding(mesh, pspec)
    # staging (argnum 4) is consumed by the in-jit scatter, exactly like
    # build_fused_ici_exchange (CPU donation warns, so TPU only)
    donate = (4,) if platform == "tpu" else ()
    fn = jax.jit(
        shard,
        in_shardings=(sharding,) * 6,
        out_shardings=(sharding, sharding),
        donate_argnums=donate,
    )
    fn.spec = resolved
    fn.schedule = schedule
    fn.lowering = low
    fn.qspec = quantize
    return fn
