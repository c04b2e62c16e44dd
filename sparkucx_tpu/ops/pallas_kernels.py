"""Pallas TPU kernels for the block data plane — ragged block gather ("fetch
pack") and its inverse, the ragged block scatter ("device staging write").

The hot serving primitive of the reference is packing many variable-length
shuffle blocks into ONE contiguous registered buffer and shipping that single
buffer (``UcxWorkerWrapper.handleFetchBlockRequest``: parallel positioned file
reads into one pooled bounce buffer ``[tag | sizes | data...]``, one AM reply —
UcxWorkerWrapper.scala:397-448).  On TPU the blocks already live in HBM after
the exchange collective (transport/tpu.py), so the equivalent primitive is a
**device-side ragged gather**: copy B variable-length row runs out of an
HBM-resident source into one packed HBM destination, without the bytes ever
visiting the host.

``build_block_scatter`` is the write-side inverse (the NvkvHandler.write
analogue for device-born map output, store/hbm_store.py device staging): copy
B variable-length row runs out of ONE packed device buffer into their
slot-layout staging positions in an HBM-resident staging array, so map output
produced on the chip reaches the exchange without a D2H -> host memcpy -> H2D
round trip.

Two lowerings each (bit-identical results), picked by platform where the
caller names none:

* ``impl='dma'`` — Pallas kernel, one *dynamic-size* HBM->HBM DMA per block,
  K-deep pipelined on a rotating semaphore ring (the DMA engine streams block
  i+1..i+K while block i completes).  This is the TPU analogue of the
  reference's ForkJoin parallel file reads (UcxWorkerWrapper.scala:416-426):
  the DMA engine plays the IO thread pool.  TPU-only (Mosaic supports
  dynamic-size DMA slices; the interpreter does not), and what the chip runs.
* ``impl='xla'`` — pure jnp: searchsorted + take for the gather, masked
  ``dynamic_update_slice`` windows for the scatter; what every other backend
  runs, tested against a NumPy oracle (tests/test_pallas_kernels.py).

Sizes here are **rows** of ``lane`` 32-bit elements — the exchange's wire unit
(one row = the store's block alignment; ops/exchange.py module docstring).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# Pipelining depth of the dynamic-DMA path: how many block copies may be in
# flight at once (the numIoThreads analogue, UcxShuffleConf.scala:66-71).
DMA_PIPELINE_DEPTH = 8


def _gather_dma_kernel(starts_ref, counts_ref, outs_ref, src_ref, out_ref, sems):
    """One dynamic-size DMA per block, K-deep pipelined.

    Grid-free: a single program walks all B blocks with a fori_loop, starting
    DMA i and waiting on DMA i-K, so up to K copies are in flight.  The wait
    reconstructs the same descriptor (the standard Pallas double-buffer
    pattern); empty blocks are skipped symmetrically on start and wait.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    num_blocks = starts_ref.shape[0]
    k = DMA_PIPELINE_DEPTH

    def get_dma(i):
        return pltpu.make_async_copy(
            src_ref.at[pl.ds(starts_ref[i], counts_ref[i])],
            out_ref.at[pl.ds(outs_ref[i], counts_ref[i])],
            sems.at[jax.lax.rem(i, k)],
        )

    def body(i, _):
        # clamp so the traced SMEM read stays in bounds even when i < k (the
        # i >= k predicate discards the value but not the read itself)
        @pl.when(jnp.logical_and(i >= k, counts_ref[jnp.maximum(i - k, 0)] > 0))
        def _wait_prev():
            get_dma(i - k).wait()

        @pl.when(counts_ref[i] > 0)
        def _start():
            get_dma(i).start()

        return 0

    jax.lax.fori_loop(0, num_blocks, body, 0)

    def drain(i, _):
        @pl.when(counts_ref[i] > 0)
        def _wait():
            get_dma(i).wait()

        return 0

    jax.lax.fori_loop(jnp.maximum(num_blocks - k, 0), num_blocks, drain, 0)


@jax.named_scope("block_gather")
def _pallas_gather(out_rows: int, starts, counts, outs, src):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        _gather_dma_kernel,
        out_shape=jax.ShapeDtypeStruct((out_rows, src.shape[1]), src.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA((DMA_PIPELINE_DEPTH,))],
        ),
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        name="block_gather_dma",
    )(starts, counts, outs, src)


@jax.named_scope("block_gather")
def _xla_gather(out_rows: int, starts, counts, outs, src):
    """Portable lowering: map each output row to its source row.

    Output row p belongs to block b iff outs[b] <= p < outs[b]+counts[b]; rows
    not covered by any block keep zeros.  Blocks must be packed (outs =
    exclusive cumsum of counts) for the searchsorted inversion to hold — the
    wrapper guarantees it.
    """
    ends = outs + counts
    pos = jnp.arange(out_rows, dtype=jnp.int32)
    b = jnp.clip(
        jnp.searchsorted(ends, pos, side="right").astype(jnp.int32),
        0,
        jnp.maximum(starts.shape[0] - 1, 0),
    )
    src_row = starts[b] + (pos - outs[b])
    covered = (pos >= outs[b]) & (pos < ends[b])
    rows = src[jnp.clip(src_row, 0, src.shape[0] - 1)]
    return jnp.where(covered[:, None], rows, jnp.zeros((), dtype=src.dtype))


def build_block_gather(num_blocks: int, out_rows: int, impl: Optional[str] = None):
    """Compile a ragged block gather: ``fn(starts, counts, outs, src) -> packed``.

    * ``starts``/``counts``/``outs``: (num_blocks,) int32 — source row offset,
      row count, and destination row offset per block.  Destinations must be
      packed ascending (``outs`` = exclusive cumsum of ``counts``) — the layout
      ``pack_plan`` produces and the reference's reply buffer uses.
    * ``src``: (S, lane) int32 — HBM-resident source (a received exchange shard).
    * returns (out_rows, lane) int32 — blocks packed back-to-back.  Rows past
      the packed total are UNSPECIFIED (the Pallas paths leave the buffer
      uninitialized there; the xla path happens to zero it) — callers must
      slice ``[:total_rows]``.

    ``impl``: 'dma' (TPU, pipelined dynamic-size DMAs) | 'xla' (pure jnp).
    Default, and what every caller but a test takes: 'dma' on TPU else 'xla'.
    """
    if impl is None:
        impl = "dma" if jax.devices()[0].platform == "tpu" else "xla"
    if impl == "xla":
        f = functools.partial(_xla_gather, out_rows)
    elif impl == "dma":
        f = functools.partial(_pallas_gather, out_rows)
    else:
        raise ValueError(f"unknown impl {impl!r}")
    f.__name__ = "block_gather"  # the executable is jit_block_gather, not jit__unknown
    fn = jax.jit(f)
    fn.impl = impl
    return fn


def _scatter_dma_kernel(starts_ref, counts_ref, outs_ref, src_ref, dst_ref, out_ref, sems):
    """Inverse of ``_gather_dma_kernel``: packed src -> scattered dst slots.

    ``dst_ref`` is aliased to ``out_ref`` (input_output_aliases), so rows not
    covered by any block keep their prior staging contents — that is what makes
    this an *append* into a partially-filled staging round rather than a
    rebuild.  Same K-deep rotating-semaphore pipeline as the gather.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del dst_ref  # present only to carry the alias; all writes go through out_ref
    num_blocks = starts_ref.shape[0]
    k = DMA_PIPELINE_DEPTH

    def get_dma(i):
        return pltpu.make_async_copy(
            src_ref.at[pl.ds(outs_ref[i], counts_ref[i])],
            out_ref.at[pl.ds(starts_ref[i], counts_ref[i])],
            sems.at[jax.lax.rem(i, k)],
        )

    def body(i, _):
        @pl.when(jnp.logical_and(i >= k, counts_ref[jnp.maximum(i - k, 0)] > 0))
        def _wait_prev():
            get_dma(i - k).wait()

        @pl.when(counts_ref[i] > 0)
        def _start():
            get_dma(i).start()

        return 0

    jax.lax.fori_loop(0, num_blocks, body, 0)

    def drain(i, _):
        @pl.when(counts_ref[i] > 0)
        def _wait():
            get_dma(i).wait()

        return 0

    jax.lax.fori_loop(jnp.maximum(num_blocks - k, 0), num_blocks, drain, 0)


@jax.named_scope("block_scatter")
def _pallas_scatter(out_rows: int, starts, counts, outs, src, dst):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # dst is operand 4 of the FULL input tuple (scalar-prefetch args included in
    # the alias numbering), aliased to output 0: untouched rows pass through.
    return pl.pallas_call(
        _scatter_dma_kernel,
        out_shape=jax.ShapeDtypeStruct((out_rows, src.shape[1]), src.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA((DMA_PIPELINE_DEPTH,))],
        ),
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        name="block_scatter_dma",
    )(starts, counts, outs, src, dst)


@jax.named_scope("block_scatter")
def _xla_scatter(window: int, out_rows: int, starts, counts, outs, src, dst):
    """Portable lowering: one masked ``dynamic_update_slice`` window per block.

    Each scan step reads a fixed ``window``-row slice of dst at the block's
    start, overwrites the first ``count`` rows from the packed src, and writes
    it back.  Both arrays are padded by ``window`` rows so XLA's slice-start
    clamping can never shift a window (a clamped start would silently copy the
    wrong src rows); zero-count blocks degenerate to read-modify-write no-ops,
    so pow2 batch padding needs no monotonicity trick here.
    """
    lane = src.shape[1]
    src = jnp.pad(src, ((0, window), (0, 0)))
    dst = jnp.pad(dst, ((0, out_rows + window - dst.shape[0]), (0, 0)))
    row_in_window = jnp.arange(window, dtype=jnp.int32)[:, None]

    def body(d, block):
        start, count, out = block
        src_win = jax.lax.dynamic_slice(src, (out, 0), (window, lane))
        cur = jax.lax.dynamic_slice(d, (start, 0), (window, lane))
        new = jnp.where(row_in_window < count, src_win, cur)
        return jax.lax.dynamic_update_slice(d, new, (start, 0)), None

    d, _ = jax.lax.scan(body, dst, (starts, counts, outs))
    return d[:out_rows]


def build_block_scatter(
    num_blocks: int,
    out_rows: int,
    impl: Optional[str] = None,
    max_block_rows: Optional[int] = None,
):
    """Compile a ragged block scatter: ``fn(starts, counts, outs, src, dst) -> dst'``.

    The inverse of :func:`build_block_gather` — the device staging write path
    (store/hbm_store.py ``write_partition_device``):

    * ``starts``: (num_blocks,) int32 — *destination* slot-layout row per block
      (``j * slot_rows + used_j`` in the staging geometry).
    * ``counts``: (num_blocks,) int32 — rows per block; zero-count entries are
      no-ops (how pow2 batch padding is expressed).
    * ``outs``: (num_blocks,) int32 — *source* row offsets in the packed
      buffer; must be the exclusive cumsum of ``counts`` (pack_plan layout).
    * ``src``: (S, lane) int32 — packed device buffer of block payloads.
    * ``dst``: (out_rows, lane) int32 — the staging array; returns a new array
      with the blocks placed and every uncovered row carried over unchanged
      (Pallas paths alias dst to the output; the xla path read-modify-writes).

    ``max_block_rows`` bounds the largest single block (xla path window size;
    defaults to ``out_rows``).  ``impl`` as in ``build_block_gather``.  On TPU
    ``dst`` is donated, making the append in-place.
    """
    if impl is None:
        impl = "dma" if jax.devices()[0].platform == "tpu" else "xla"
    if impl == "xla":
        window = max(1, max_block_rows if max_block_rows is not None else out_rows)
        f = functools.partial(_xla_scatter, window, out_rows)
    elif impl == "dma":
        f = functools.partial(_pallas_scatter, out_rows)
    else:
        raise ValueError(f"unknown impl {impl!r}")
    # Donating dst turns the aliasing into a true in-place append; on CPU
    # donation is unimplemented and would warn every call, so gate it.
    donate = (4,) if jax.devices()[0].platform == "tpu" else ()
    f.__name__ = "block_scatter"  # the executable is jit_block_scatter, not jit__unknown
    fn = jax.jit(f, donate_argnums=donate)
    fn.impl = impl
    return fn


# Public alias: the fused scatter+exchange lowering (ops/ici_exchange.py)
# composes the window-scan scatter with the scheduled ring inside ONE jit.
xla_scatter_windows = _xla_scatter


# ----------------------------------------------------------------------------
# Scheduled inter-chip ring exchange (ops/ici_exchange.py's TPU lowering)
# ----------------------------------------------------------------------------
#
# One kernel invocation per device (inside shard_map over the ring axis)
# executes a static flow schedule of remote DMAs: per step, at most one chunk
# window per ICI link direction (``pltpu.make_async_remote_copy`` — the
# bidirectional-ring pattern of SNIPPETS.md [1]/[3]).  The schedule arrives as
# plain ``(offset, chunk, direction)`` tuples so this module stays free of the
# schedule dataclasses (ops/ici_exchange.py owns those and depends on us).
#
# Remote targets are LOGICAL device ids — the linearized index into the FULL
# shard_map mesh — while the schedule speaks ring POSITIONS along one mesh
# axis.  When that axis is a sub-axis (the ICI phase of a (dcn, ici) mesh)
# the two differ: chip c of slice s is logical id ``s * C + c``, not ``c``.
# ``ring_axis_layout`` provides the position->id affine map; every remote
# signal/copy below goes through it.


def ring_axis_layout(mesh_axes, axis_name):
    """Row-major strides mapping ring positions on one mesh axis to logical
    device ids.

    ``mesh_axes``: ordered ``(name, size)`` pairs of the FULL shard_map mesh
    (row-major, matching ``Mesh(devices.reshape(...), names)``).  Returns
    ``(ring_stride, other_axes)`` with ``other_axes`` = ``(name, stride)`` for
    every non-ring axis, such that the logical id of ring position ``p`` is::

        p * ring_stride + sum(axis_index(name) * stride for other axes)

    Pure python — unit-testable without a mesh (tests/test_ici_exchange.py).
    """
    mesh_axes = tuple((str(n), int(s)) for n, s in mesh_axes)
    names = [n for n, _ in mesh_axes]
    if axis_name not in names:
        raise ValueError(f"ring axis {axis_name!r} not in mesh axes {names}")
    strides = {}
    stride = 1
    for name, size in reversed(mesh_axes):
        strides[name] = stride
        stride *= size
    others = tuple((n, strides[n]) for n, _ in mesh_axes if n != axis_name)
    return strides[axis_name], others


def _ring_device_id(mesh_axes, axis_name):
    """Kernel-side ring-position -> logical-device-id map (traced; must run
    inside shard_map over ``mesh_axes``)."""
    import jax

    ring_stride, other_axes = ring_axis_layout(mesh_axes, axis_name)
    base = 0
    for name, stride in other_axes:
        base = base + jax.lax.axis_index(name) * stride
    return lambda pos: base + pos * ring_stride


def _ring_exchange_steps(
    num_devices, slot_rows, window_rows, steps, me, dev_id, data_ref, out_ref,
    send_sem, recv_sem,
):
    """Shared schedule walk: remote-copy every (offset, chunk) window.

    Sender ``me`` pushes its staging window for destination ``me+d`` into the
    destination's sender-major grid region (rows ``me*slot + chunk*w``).  The
    schedule is SPMD-symmetric, so each step's ``wait()`` pairs my outgoing
    descriptor with the incoming copy of the same (offset, chunk) from
    ``me-d`` — same window size, same semaphore index, both directions of the
    ring in flight at once."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    for step in steps:
        copies = []
        for offset, chunk, direction in step:
            dst_pos = jax.lax.rem(me + offset, num_devices)
            sem_idx = 0 if direction >= 0 else 1
            copy = pltpu.make_async_remote_copy(
                src_ref=data_ref.at[
                    pl.ds(dst_pos * slot_rows + chunk * window_rows, window_rows)
                ],
                dst_ref=out_ref.at[
                    pl.ds(me * slot_rows + chunk * window_rows, window_rows)
                ],
                send_sem=send_sem.at[sem_idx],
                recv_sem=recv_sem.at[sem_idx],
                device_id=dev_id(dst_pos),
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )
            copy.start()
            copies.append(copy)
        for copy in copies:
            copy.wait()


def _ring_barrier(num_devices, offsets, me, dev_id):
    """Rendezvous with every schedule partner before the first remote write —
    a peer's out buffer must exist before bytes land in it (pallas collective
    discipline: barrier on the collective_id semaphore)."""
    import jax
    from jax.experimental.pallas import tpu as pltpu

    barrier = pltpu.get_barrier_semaphore()
    for d in offsets:
        pltpu.semaphore_signal(
            barrier,
            1,
            device_id=dev_id(jax.lax.rem(me + d, num_devices)),
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
    pltpu.semaphore_wait(barrier, len(offsets))


def ring_exchange_grid(
    axis_name: str,
    num_devices: int,
    slot_rows: int,
    window_rows: int,
    steps,
    data,
    *,
    mesh_axes=None,
    interpret: bool = False,
    collective_id: int = 13,
):
    """Pallas scheduled ring exchange: destination-major slots in, sender-major
    received grid out — the remote-DMA equivalent of one tiled all_to_all.

    * ``data``: (num_devices * slot_rows, lane) per-device staging shard.
    * ``steps``: sequence of steps; each step a sequence of
      ``(offset, chunk, direction)`` with at most one item per ring direction
      (ops/ici_exchange.ring_schedule guarantees it).
    * ``mesh_axes``: ordered (name, size) pairs of the FULL shard_map mesh
      when ``axis_name`` is a sub-axis (e.g. the ICI phase of a (dcn, ici)
      mesh) — remote DMA targets logical device ids, so ring positions must
      be rebased per ``ring_axis_layout``.  Defaults to a flat
      ``((axis_name, num_devices),)`` mesh where position == id.
    * returns (num_devices * slot_rows, lane): row ``k*slot_rows + r`` = row r
      of what sender k staged for me — identical layout to the dense
      lowering's all_to_all output (ops/exchange._exchange_shard_dense).

    Must be called inside shard_map over ``axis_name``.  The compiled kernel
    is TPU-only (remote DMA); ``interpret=True`` runs the same kernel body
    under the Pallas interpreter — works on single-axis meshes on any
    platform (the barrier is skipped: interpret discharge is synchronous and
    the barrier semaphore is TPU-only) and is bit-equality-tested against
    the stock collective on the CPU mesh in CI.
    """
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if mesh_axes is None:
        mesh_axes = ((axis_name, num_devices),)
    mesh_axes = tuple((str(n), int(s)) for n, s in mesh_axes)
    if dict(mesh_axes)[axis_name] != num_devices:
        raise ValueError(
            f"ring axis {axis_name!r} has size {dict(mesh_axes)[axis_name]} in "
            f"mesh_axes, expected num_devices={num_devices}"
        )
    steps = tuple(tuple(step) for step in steps)
    offsets = sorted({offset for step in steps for offset, _, _ in step})

    def kernel(data_ref, out_ref, send_sem, recv_sem, local_sem):
        me = jax.lax.axis_index(axis_name)
        dev_id = _ring_device_id(mesh_axes, axis_name)
        if not interpret:  # interpret discharge is synchronous; the barrier
            _ring_barrier(num_devices, offsets, me, dev_id)  # is TPU-only
        # own slot never crosses a link: one local HBM->HBM DMA
        local = pltpu.make_async_copy(
            data_ref.at[pl.ds(me * slot_rows, slot_rows)],
            out_ref.at[pl.ds(me * slot_rows, slot_rows)],
            local_sem,
        )
        local.start()
        local.wait()
        _ring_exchange_steps(
            num_devices, slot_rows, window_rows, steps, me, dev_id,
            data_ref, out_ref, send_sem, recv_sem,
        )

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(
            (num_devices * slot_rows, data.shape[1]), data.dtype
        ),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=collective_id
        ),
        interpret=interpret,
        name="ring_exchange",
    )(data)


def fused_scatter_ring_grid(
    axis_name: str,
    num_devices: int,
    slot_rows: int,
    window_rows: int,
    steps,
    starts,
    counts,
    outs,
    packed,
    staging,
    *,
    mesh_axes=None,
    interpret: bool = False,
    collective_id: int = 14,
):
    """Fused send side: block scatter + scheduled ring exchange, ONE kernel.

    Phase 1 places the packed map-output blocks into the slot-layout staging
    (the ``_scatter_dma_kernel`` pipeline, staging aliased in-place); phase 2
    runs the ring schedule straight out of that staging — the bytes never
    round-trip HBM between the staging write and the wire, and the separate
    scatter kernel launch disappears.

    Returns ``(grid, staged)``: the sender-major received grid plus the
    staging with blocks placed (aliased to the ``staging`` operand).  Same
    plan contract as ``build_block_scatter`` (starts=dst rows, counts,
    outs=packed offsets; zero-count blocks are no-ops).
    """
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if mesh_axes is None:
        mesh_axes = ((axis_name, num_devices),)
    mesh_axes = tuple((str(n), int(s)) for n, s in mesh_axes)
    if dict(mesh_axes)[axis_name] != num_devices:
        raise ValueError(
            f"ring axis {axis_name!r} has size {dict(mesh_axes)[axis_name]} in "
            f"mesh_axes, expected num_devices={num_devices}"
        )
    steps = tuple(tuple(step) for step in steps)
    offsets = sorted({offset for step in steps for offset, _, _ in step})
    k = DMA_PIPELINE_DEPTH

    def kernel(
        starts_ref, counts_ref, outs_ref, packed_ref, staging_ref,
        grid_ref, staged_ref, send_sem, recv_sem, local_sem, scatter_sems,
    ):
        del staging_ref  # aliased to staged_ref; all writes go through it
        me = jax.lax.axis_index(axis_name)
        num_blocks = starts_ref.shape[0]

        def get_dma(i):
            return pltpu.make_async_copy(
                packed_ref.at[pl.ds(outs_ref[i], counts_ref[i])],
                staged_ref.at[pl.ds(starts_ref[i], counts_ref[i])],
                scatter_sems.at[jax.lax.rem(i, k)],
            )

        def body(i, _):
            @pl.when(jnp.logical_and(i >= k, counts_ref[jnp.maximum(i - k, 0)] > 0))
            def _wait_prev():
                get_dma(i - k).wait()

            @pl.when(counts_ref[i] > 0)
            def _start():
                get_dma(i).start()

            return 0

        jax.lax.fori_loop(0, num_blocks, body, 0)

        def drain(i, _):
            @pl.when(counts_ref[i] > 0)
            def _wait():
                get_dma(i).wait()

            return 0

        jax.lax.fori_loop(jnp.maximum(num_blocks - k, 0), num_blocks, drain, 0)

        # staging is complete on THIS device; the barrier also orders every
        # peer's scatter before any remote read of their staging
        dev_id = _ring_device_id(mesh_axes, axis_name)
        if not interpret:  # interpret discharge is synchronous; the barrier
            _ring_barrier(num_devices, offsets, me, dev_id)  # is TPU-only
        local = pltpu.make_async_copy(
            staged_ref.at[pl.ds(me * slot_rows, slot_rows)],
            grid_ref.at[pl.ds(me * slot_rows, slot_rows)],
            local_sem,
        )
        local.start()
        local.wait()
        _ring_exchange_steps(
            num_devices, slot_rows, window_rows, steps, me, dev_id,
            staged_ref, grid_ref, send_sem, recv_sem,
        )

    lane = packed.shape[1]
    # staging is operand 4 of the FULL input tuple (scalar-prefetch args
    # included in the alias numbering), aliased to output 1 (staged)
    return pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((num_devices * slot_rows, lane), packed.dtype),
            jax.ShapeDtypeStruct(staging.shape, staging.dtype),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=(
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ),
            scratch_shapes=[
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA((DMA_PIPELINE_DEPTH,)),
            ],
        ),
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=collective_id
        ),
        interpret=interpret,
        name="fused_scatter_ring",
    )(starts, counts, outs, packed, staging)


def pack_plan(
    offsets_lengths: Sequence[Tuple[int, int]], row_bytes: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Host-side plan: byte (offset, length) pairs -> row-granular (starts,
    counts, outs, total_rows) for ``build_block_gather``.

    Offsets must be row-aligned (the store aligns every block,
    store/hbm_store.py); lengths are padded up to whole rows — the per-block
    padding the reference records at close (NvkvShuffleMapOutputWriter.scala:236-246).
    """
    starts, counts = [], []
    for off, ln in offsets_lengths:
        if off % row_bytes:
            raise ValueError(f"block offset {off} not {row_bytes}-byte aligned")
        starts.append(off // row_bytes)
        counts.append(-(-ln // row_bytes))
    counts_a = np.asarray(counts, dtype=np.int32)
    outs = np.concatenate([[0], np.cumsum(counts_a)[:-1]]).astype(np.int32)
    total = int(counts_a.sum())
    return np.asarray(starts, dtype=np.int32), counts_a, outs, total
