"""Device LSD radix sort with a fused key+payload scatter (Pallas TPU).

Why this exists: the 'single' sort path is one XLA ``argsort`` plus a payload
permutation gather — a compare/lane-shuffle-bound sort and a scattered-read
gather, neither of which moves rows the way the chip moves data fastest.  The
data-movement primitive this package otherwise relies on is the DMA engine on
*contiguous segments* (ops/pallas_kernels.py) — so a faster sort should move
rows in segments, never through an XLA gather.  (No device rate for either
path is on record yet — root PERF.md; ROADMAP queue 1 item 6 decides whether
this kernel stays.)

This module is that sort: least-significant-digit radix over the uint32 key
(lane 0 of the fused row, bitcast — the same key-travels-with-payload layout
as ops/sort.py), ``32 / BITS`` stable counting passes.  Each pass:

1. **XLA side** (cheap, fused): extract the pass digit per row, per-tile
   histograms, and the global destination offset of every (tile, bucket)
   segment — two small exclusive cumsums.  This is the MapperInfo-style
   size-exchange of the collective data plane, at kernel scale.
2. **Pallas kernel** (grid over row tiles): load the tile's rows into VMEM,
   group them stably by digit IN VMEM, and issue one dynamic-size DMA per
   bucket straight to the rows' final positions in HBM — key and payload move
   together, once, in ``tile_rows / B``-row segments (~50 KiB at the default
   shape: real DMA territory, not per-row scatter).

The in-VMEM stable grouping never calls sort or scatter (Mosaic has neither).
It uses the two dynamic-gather shapes Mosaic *does* lower
(``jnp.take_along_axis`` along either axis of a 2D tile):

* build the bucket-major one-hot of the digits, flat-cumsum it along lanes
  (log2 shifted adds) — entry ``b*T + i`` then holds the number of rows with
  digit <= b up to row i, i.e. every row's stable output slot, and the
  permutation we need is this staircase's *inverse*;
* invert by binary search: output slot d is filled by the row at the first
  flat index whose running count reaches d+1 — 17 ``take_along_axis`` probes
  along the lane axis;
* apply the permutation to the whole row tile with ONE ``take_along_axis``
  along the sublane axis (``tpu.dynamic_gather``), then DMA each bucket's now
  contiguous run.

Stability: within a bucket band the flat index is the row index, so equal
digits keep row order — each pass is a stable counting sort, hence LSD works
and the whole sort is stable (the contract ops/sort.py documents).

CPU testing: ``interpret=True`` replaces the dynamic-size segment DMAs with
row-granular static copies (the Pallas interpreter cannot express
dynamic-size DMA — same limitation as _gather_dma_kernel) and runs the rest
as plain jnp, so the full pass structure is differentially fuzzed against
``np.argsort(kind='stable')`` in CI; tests also AOT-lower the kernel for the
TPU target to pin Mosaic compatibility without a chip.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


#: Digit width per pass.  4 bits = 16 buckets x 8 passes: the widest digit
#: whose per-(tile, bucket) DMA segments stay large (tile_rows/16 rows) while
#: the flat cumsum/search band (B * tile_rows lanes) stays a few hundred KiB
#: of VMEM.  256 buckets would halve the passes but shrink segments 16x and
#: blow the band to 2M lanes.
BITS = 4
NUM_BUCKETS = 1 << BITS
NUM_PASSES = 32 // BITS

def _default_tile_rows() -> int:
    """Rows per kernel tile, overridable via SPARKUCX_RADIX_TILE for on-chip
    tuning sweeps — the trade is DMA segment size
    (tile/16 rows per bucket) vs VMEM footprint and per-tile search width.
    A malformed or out-of-range value must not torch a scarce hardware
    window with an import-time traceback: warn and fall back to 8192."""
    raw = os.environ.get("SPARKUCX_RADIX_TILE")
    if raw is None:
        return 8192
    try:
        val = int(raw)
    except ValueError:
        val = -1
    if val < 8 or val % 8:
        import warnings

        warnings.warn(
            f"SPARKUCX_RADIX_TILE={raw!r} is not a multiple of 8 >= 8; "
            "using the 8192 default"
        )
        return 8192
    return val


DEFAULT_TILE_ROWS = _default_tile_rows()


def _cumsum_lanes(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive cumsum along the lane (last) axis of a (1, M) int32 vector,
    as log2(M) statically-shifted adds — Mosaic has no scan primitive."""
    m = x.shape[-1]
    shift = 1
    while shift < m:
        shifted = jnp.pad(x, ((0, 0), (shift, 0)))[:, :m]
        x = x + shifted
        shift *= 2
    return x


def _gather_lanes(table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Element gather along the lane (last) axis, batched over the sublane
    axis: ``out[s, j] = table[s, idx[s, j]]``.  Built as a raw ``lax.gather``
    with exactly the dimension numbers Mosaic's TPU lowering maps to
    ``tpu.dynamic_gather(dims=[1])`` (jnp.take_along_axis constructs a
    different but equivalent spelling that its rule rejects)."""
    dnums = jax.lax.GatherDimensionNumbers(
        offset_dims=(),
        collapsed_slice_dims=(1,),
        start_index_map=(1,),
        operand_batching_dims=(0,),
        start_indices_batching_dims=(0,),
    )
    return jax.lax.gather(
        table, idx[..., None], dnums, slice_sizes=(1, 1),
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
    )


def _gather_sublanes(rows: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Element gather along the sublane (first) axis, batched over lanes:
    ``out[i, l] = rows[idx[i, l], l]`` — applies a row permutation to a 2D
    tile when ``idx`` broadcasts the permutation across lanes.  Raw
    ``lax.gather`` in Mosaic's ``tpu.dynamic_gather(dims=[0])`` spelling."""
    dnums = jax.lax.GatherDimensionNumbers(
        offset_dims=(),
        collapsed_slice_dims=(0,),
        start_index_map=(0,),
        operand_batching_dims=(1,),
        start_indices_batching_dims=(1,),
    )
    return jax.lax.gather(
        rows, idx[..., None], dnums, slice_sizes=(1, 1),
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
    )


def _searchsorted_lanes(cum: jnp.ndarray, queries: jnp.ndarray) -> jnp.ndarray:
    """First index r (per lane) with ``cum[0, r] >= queries[0, lane]`` — a
    vectorized lower-bound over a non-decreasing (1, M) table, via binary
    search whose probes are lane gathers (``tpu.dynamic_gather``).  Returns M
    where no index qualifies."""
    m = cum.shape[-1]
    lo = jnp.zeros_like(queries)
    hi = jnp.full_like(queries, m)
    # the search interval spans m+1 candidate answers (0..m inclusive), so
    # ceil(log2(m+1)) = m.bit_length() halvings are needed — one short left
    # unresolved 2-wide intervals and returned lo-1 on some lanes
    steps = max(1, m.bit_length())
    for _ in range(steps):
        mid = (lo + hi) // 2
        probe = _gather_lanes(cum, jnp.minimum(mid, m - 1))
        ge = probe >= queries
        hi = jnp.where(ge, mid, hi)
        lo = jnp.where(ge, lo, mid + 1)
    return lo


def _bin_kernel(dests_ref, rows_ref, out_ref, scratch_ref, sems, *, shift: int,
                tile_rows: int, interpret: bool):
    """One tile of one radix pass: stable-group rows by this pass's digit in
    VMEM, then DMA each bucket's contiguous run to its global destination."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t = pl.program_id(0)
    big = tile_rows * NUM_BUCKETS
    rows = rows_ref[...]  # (T, L) VMEM
    # NOTE every index below is a static lax.slice — jnp integer indexing
    # lowers through dynamic_slice, which Mosaic does not implement.
    key_lane = jax.lax.slice(rows, (0, 0), (tile_rows, 1)).reshape(tile_rows)
    keys = jax.lax.bitcast_convert_type(key_lane, jnp.uint32)
    digit = jax.lax.shift_right_logical(keys, jnp.uint32(shift)).astype(jnp.int32) & (
        NUM_BUCKETS - 1
    )

    # Bucket-major one-hot band, flat over lanes: entry b*T + i is 1 iff row i
    # has digit b.  Its inclusive cumsum is the stable-slot staircase.
    oh = (digit[None, :] == jax.lax.broadcasted_iota(jnp.int32, (NUM_BUCKETS, 1), 0)).astype(jnp.int32)
    cum = _cumsum_lanes(oh.reshape(1, big))

    # Bucket counts / local starts from the band boundaries (static slices).
    band_end = jax.lax.slice(
        cum.reshape(NUM_BUCKETS, tile_rows), (0, tile_rows - 1), (NUM_BUCKETS, tile_rows)
    ).reshape(NUM_BUCKETS)                                  # inclusive totals
    head = jax.lax.slice(band_end, (0,), (NUM_BUCKETS - 1,))
    local_start = jnp.concatenate([jnp.zeros(1, jnp.int32), head])
    counts = band_end - local_start

    # Invert the staircase: output slot d <- row at the first flat index whose
    # running count is d+1; its row index is that flat index mod T.
    queries = jax.lax.broadcasted_iota(jnp.int32, (1, big), 1) + 1
    first = _searchsorted_lanes(cum, queries)
    perm = jax.lax.slice(
        jax.lax.rem(first, tile_rows), (0, 0), (1, tile_rows)
    ).reshape(tile_rows)                                    # only slots < T real

    # ONE fused key+payload move: the dim-0 dynamic_gather applies the stable
    # grouping to the whole row tile.
    idx = jnp.broadcast_to(perm[:, None], rows.shape).astype(jnp.int32)
    scratch_ref[...] = _gather_sublanes(rows, idx)

    def _scalar(vec, b):  # static-index scalar read without dynamic_slice
        return jax.lax.slice(vec, (b,), (b + 1,)).reshape(())

    def seg_dma(b):
        return pltpu.make_async_copy(
            scratch_ref.at[pl.ds(_scalar(local_start, b), _scalar(counts, b))],
            out_ref.at[pl.ds(dests_ref[t * NUM_BUCKETS + b], _scalar(counts, b))],
            sems.at[b],
        )

    if not interpret:
        # start all bucket segments, then drain: up to B copies in flight per
        # tile (the DMA engine as IO pool, like _gather_dma_kernel); the grid
        # is sequential so scratch is not reused until every DMA completed.
        for b in range(NUM_BUCKETS):
            @pl.when(_scalar(counts, b) > 0)
            def _start(b=b):
                seg_dma(b).start()
        for b in range(NUM_BUCKETS):
            @pl.when(_scalar(counts, b) > 0)
            def _wait(b=b):
                seg_dma(b).wait()
    else:
        # interpreter cannot express dynamic-size DMA: row-granular copies
        # preserve the exact data flow for CPU correctness tests
        def row_copy(b, r):
            dma = pltpu.make_async_copy(
                scratch_ref.at[pl.ds(_scalar(local_start, b) + r, 1)],
                out_ref.at[pl.ds(dests_ref[t * NUM_BUCKETS + b] + r, 1)],
                sems.at[b],
            )
            dma.start()
            dma.wait()

        for b in range(NUM_BUCKETS):
            jax.lax.fori_loop(
                0, _scalar(counts, b), lambda r, _, b=b: (row_copy(b, r), 0)[1], 0
            )


def _radix_pass(rows: jnp.ndarray, shift: int, tile_rows: int, interpret: bool):
    """One stable counting pass: XLA-side histograms/offsets + the Pallas
    binning kernel.  ``rows.shape[0]`` must be a tile multiple."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, lanes = rows.shape
    tiles = n // tile_rows
    keys = jax.lax.bitcast_convert_type(rows[:, 0], jnp.uint32)
    digit = jax.lax.shift_right_logical(keys, jnp.uint32(shift)).astype(jnp.int32) & (
        NUM_BUCKETS - 1
    )
    tiled = digit.reshape(tiles, tile_rows)
    hist = (tiled[:, :, None] == jnp.arange(NUM_BUCKETS, dtype=jnp.int32)).astype(
        jnp.int32
    ).sum(axis=1)                                         # (tiles, B)
    bucket_total = hist.sum(axis=0)
    bucket_start = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(bucket_total)[:-1].astype(jnp.int32)]
    )
    tile_prefix = jnp.concatenate(
        [jnp.zeros((1, NUM_BUCKETS), jnp.int32),
         jnp.cumsum(hist, axis=0)[:-1].astype(jnp.int32)]
    )                                                     # rows of bucket b in tiles < t
    dests = (bucket_start[None, :] + tile_prefix).reshape(-1)  # (tiles*B,)

    kernel = functools.partial(
        _bin_kernel, shift=shift, tile_rows=tile_rows, interpret=interpret
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n, lanes), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tiles,),
            in_specs=[
                pl.BlockSpec((tile_rows, lanes), lambda t, dests: (t, 0)),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((tile_rows, lanes), rows.dtype),
                pltpu.SemaphoreType.DMA((NUM_BUCKETS,)),
            ],
        ),
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=interpret,
        name="radix_bin_pass",
    )(dests, rows)


def clamped_tile_rows(tile_rows: int, n: int) -> int:
    """Shrink an oversized tile toward ``n`` while staying a sublane (8-row)
    multiple — ``min(tile_rows, n)`` alone can produce a tile (e.g. 1001) that
    the module's own SPARKUCX_RADIX_TILE validation would reject and whose
    sublane layout Mosaic can't express."""
    return min(tile_rows, -(-max(8, n) // 8) * 8)


def radix_sort_rows(
    rows: jnp.ndarray,
    tile_rows: int = DEFAULT_TILE_ROWS,
    interpret: bool = False,
) -> jnp.ndarray:
    """Stable-sort fused (key | payload) rows by the uint32 key bitcast in
    lane 0 — 8 LSD counting passes, rows moved by segment DMA each pass.

    ``rows``: (N, L) of any 32-bit dtype (the key is bitcast, never value-
    cast).  N not a tile multiple is padded with KEY_MAX rows (zero payload)
    that sort last and are sliced off — callers with their own padding
    discipline (ops/sort.py) keep theirs intact because the sort is stable
    and appended padding stays behind equal-keyed real rows.
    """
    n = rows.shape[0]
    tile_rows = clamped_tile_rows(tile_rows, n)
    padded = -(-n // tile_rows) * tile_rows
    if padded != n:
        # KEY_MAX pad keys must be BITCAST into the row dtype — a value cast
        # (jnp.full) would turn 0xFFFFFFFF into e.g. float32 -1.0's bit
        # pattern, pad rows would sort into the middle, and the final [:n]
        # slice would drop real rows
        pad_keys = jax.lax.bitcast_convert_type(
            jnp.full((padded - n, 1), 0xFFFFFFFF, jnp.uint32), rows.dtype
        )
        pad_rows = jnp.concatenate(
            [pad_keys, jnp.zeros((padded - n, rows.shape[1] - 1), rows.dtype)],
            axis=1,
        )
        rows = jnp.concatenate([rows, pad_rows])
    for p in range(NUM_PASSES):
        rows = _radix_pass(rows, p * BITS, tile_rows, interpret)
    return rows[:n]


def build_radix_sort(
    n_rows: int,
    lanes: int,
    tile_rows: int = DEFAULT_TILE_ROWS,
    interpret: bool = False,
):
    """Compile ``fn(rows (n_rows, lanes) int32) -> stably sorted rows`` (by
    the uint32 key bitcast in lane 0)."""
    fn = jax.jit(
        functools.partial(radix_sort_rows, tile_rows=tile_rows, interpret=interpret)
    )
    fn.impl = "radix"
    return fn
