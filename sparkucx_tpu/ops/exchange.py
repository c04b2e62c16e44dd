"""The shuffle exchange collective — ragged all_to_all over the executor mesh (L3 hot path).

This is the TPU-native replacement for the reference's entire UCX data plane: where
SparkUCX serves each ``FetchBlockReq`` with a UCP active message carrying the block
bytes (UcxWorkerWrapper.scala:96-186, handleFetchBlockRequest :397-448), here a
*superstep* of the shuffle — every reducer fetching from every mapper — lowers to ONE
collective over the ICI mesh, letting XLA schedule the bidirectional ICI links
instead of hand-driving RDMA endpoints.

Data unit: the exchange moves **rows** of ``lane`` int32 lanes (default 128 -> one
512-byte row).  Two reasons: (a) a trailing 128-lane dimension is the shape XLA:TPU
tiles natively — a 1-D byte/int stream gets pathologically padded to (x,1,128)
tiles by the ragged-all-to-all lowering (observed 128x memory blowup); (b) 512 is
exactly the sector alignment the reference's NVKV store enforces on every block
write (NvkvHandler.scala:244-256), so block offsets are row-aligned by
construction.

Protocol (mirrors the reference's two-phase metadata+data design):

1. **Size-matrix exchange** — each executor contributes the row-counts it holds
   for every peer; an ``all_gather`` makes the full n x n matrix available
   device-side.  This is the collective analogue of the ``MapperInfo`` commit
   (NvkvShuffleMapOutputWriter.scala:116-148): senders publish sizes before any
   data moves, exactly like the DPU daemon learns the offset table before serving.
2. **Payload exchange** — two lowerings behind one interface:

   * ``impl='ragged'`` (TPU): offsets are computed inside jit from the gathered
     size matrix (slot starts for send offsets, exclusive column-cumsum for each
     receiver's landing offsets) and fed to ``jax.lax.ragged_all_to_all`` — only
     each region's used prefix crosses the wire.
   * ``impl='dense'`` (portable; XLA:CPU has no ragged-all-to-all kernel): a
     tiled ``lax.all_to_all`` moves whole fixed-size slots, then a static-shaped
     row gather compacts the receive side into the same tight sender-major layout
     the ragged path produces.  This is also the path the driver's virtual-CPU
     ``dryrun_multichip`` executes.
   * ``impl='local'`` (TPU, n=1 only): the degenerate single-executor superstep
     is a device-local prefix copy, so it runs as ONE Pallas DMA gather rather
     than through ragged_all_to_all's single-device lowering (no device rate
     for either is on record yet — root PERF.md).

   All lowerings produce identical receive buffers over the valid (sized)
   prefix, so every layer above is implementation-agnostic; rows past the
   received totals are zeros under the collective lowerings and unspecified
   under 'local'.

Everything is static-shaped: staging capacities are compile-time constants, sizes
are runtime data.  No data-dependent Python control flow — the same compiled
exchange serves every superstep of every shuffle.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.lax import ragged_all_to_all
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def exclusive_cumsum(x, axis: int = -1, xp=jnp):
    return xp.cumsum(x, axis=axis) - x


def gather_rows(rows: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``rows[idx]`` (1-D row index): the row gather of every sort and
    partition here.  It is the plain gather at every width: chunking widths
    of 25..32 lanes into <= 24-lane column slices, as this function did from
    an observation that predates the measurement record, was slower or level
    wherever it was re-measured on a v5e (PERF.md section 6, PR 48: a
    dispatch and a ``block_until_ready`` a piece, ~1 ms of host time in each
    figure, the order of the two forms not in doubt).  Inside an executable's
    own device trace the plain gather of 342,784 rows of 25 lanes is 1.25 ms
    (ledger, PR 54; ``scripts/probe_ordered_passes.py``, PR 55): 3.6 ns an
    index whatever the row's width — a TPU holds such rows padded to 128
    lanes, so each is one 512-byte row to fetch — and a second gather over
    the same indices never pays."""
    return rows[idx]


def resolve_collective_impl(impl: str, platform: Optional[str] = None) -> str:
    """The one place that decides "ragged on TPU, dense elsewhere": ``'auto'``
    is the ragged collective where the backend lowers it, the dense slot
    all_to_all everywhere else (XLA:CPU has no ragged_all_to_all kernel); any
    other value is the caller's own and passes through.  Every spec's
    ``resolve_impl`` calls this and adds only its own degenerate tiers
    (``local``, ``single``).  ``platform`` defaults to the first device's."""
    if impl != "auto":
        return impl
    if platform is None:
        platform = jax.devices()[0].platform
    return "ragged" if platform == "tpu" else "dense"


@dataclass(frozen=True)
class ExchangeSpec:
    """Static description of one compiled exchange.

    ``send_rows`` / ``recv_rows`` are per-executor staging sizes in rows of
    ``lane`` int32 elements (``row_bytes`` = 4*lane, default 512 — the HBM
    analogue of the reference's fixed NVKV buffers, NvkvHandler.scala:26-29).
    ``impl`` is ``'ragged'`` | ``'dense'`` | ``'auto'`` (ragged iff the backend
    lowers it, i.e. TPU).  Layout is always *slot*: peer j's chunk starts at row
    ``j * slot_rows`` — exactly the per-peer region layout the HBM store stages,
    so nothing is repacked between "map output written" and "collective run".
    """

    num_executors: int
    send_rows: int
    recv_rows: int
    lane: int = 128
    axis_name: str = "ex"
    impl: str = "auto"

    @property
    def row_bytes(self) -> int:
        return self.lane * 4

    @property
    def slot_rows(self) -> int:
        """Per-peer region size in rows."""
        return self.send_rows // self.num_executors

    def resolve_impl(self, platform: Optional[str] = None) -> "ExchangeSpec":
        """'auto' -> the fastest lowering the backend executes:

        * TPU, n == 1: ``'local'`` — the collective degenerates to a device-
          local prefix copy, which is exactly what the Pallas DMA gather does,
          so the DMA kernel IS the exchange here;
        * TPU, n > 1: ``'ragged'`` (the ICI collective — network-bound, where
          the local-copy inefficiency is irrelevant);
        * CPU: ``'dense'`` (XLA:CPU has no ragged_all_to_all kernel).
        """
        if self.impl != "auto":
            return self
        impl = resolve_collective_impl(self.impl, platform)
        if impl == "ragged" and self.num_executors == 1:
            impl = "local"
        return replace(self, impl=impl)

    def validate(self) -> None:
        if self.send_rows % self.num_executors:
            raise ValueError("send_rows must be divisible by num_executors (slot layout)")
        if self.impl not in ("ragged", "dense", "local"):
            raise ValueError(f"unknown impl {self.impl!r}")
        if self.impl == "local" and self.num_executors != 1:
            raise ValueError("impl='local' is the n=1 degenerate exchange only")
        if self.lane <= 0:
            raise ValueError("lane must be positive")


def ragged_params(sizes, me, slot_rows: Optional[int], xp=jnp):
    """The ragged lowering's offset/size formulas, factored for standalone
    verification (``xp=np`` in tests, ``xp=jnp`` traced inside the collective —
    the SAME expressions either way, so a formula regression fails the
    property tests in tests/test_ragged_plan.py even though XLA:CPU cannot
    execute ragged_all_to_all itself).

    Given the full (n, n) size matrix (``sizes[i, j]`` = rows i sends j), the
    parameters executor ``me`` passes to ``jax.lax.ragged_all_to_all``:

    * ``input_offsets[j]`` — where j's chunk starts in my send buffer: the
      slot start ``j * slot_rows`` (exchange staging layout), or the compact
      exclusive cumsum when ``slot_rows`` is None (columnar/sort layout);
    * ``send_sizes[j]`` — rows I send j: row ``me`` of the matrix;
    * ``output_offsets[j]`` — where MY chunk lands inside receiver j's buffer:
      rows from senders i < me bound for j, i.e. the exclusive cumsum down
      column j, row ``me``;
    * ``recv_sizes[i]`` — rows I receive from i: column ``me``.

    This is the layout contract of the reference's reply packing
    (UcxWorkerWrapper.scala:397-448: [sizes | data...] sender-major).
    """
    n = sizes.shape[0]
    send_sizes = sizes[me]                                      # (n,)
    recv_sizes = sizes[:, me]                                   # (n,)
    output_offsets = exclusive_cumsum(sizes, axis=0, xp=xp)[me]  # (n,)
    if slot_rows is None:
        input_offsets = exclusive_cumsum(send_sizes, xp=xp)     # (n,)
    else:
        input_offsets = xp.arange(n, dtype=xp.int32) * slot_rows
    return input_offsets, send_sizes, output_offsets, recv_sizes


def _gather_sizes(spec: ExchangeSpec, size_row: jnp.ndarray):
    """Phase 1 (shared): gather the full size matrix device-side."""
    ax = spec.axis_name
    me = jax.lax.axis_index(ax)
    sizes = jax.lax.all_gather(size_row, ax, tiled=True)  # (n, n): sizes[i, j] = i -> j rows
    return me, sizes


# Public alias: the scheduled ICI lowering (ops/ici_exchange.py) shares the
# size-matrix gather so its receive metadata is bit-identical to this module's.
gather_size_matrix = _gather_sizes


@jax.named_scope("exchange_ragged")
def _exchange_shard_ragged(spec: ExchangeSpec, data: jnp.ndarray, size_row: jnp.ndarray):
    """Slot-region staging -> ragged_all_to_all over rows -> tight sender-major recv.

    Only each region's used prefix crosses the wire — the padding between
    regions stays home, unlike the dense lowering."""
    me, sizes = _gather_sizes(spec, size_row)
    input_offsets, send_sizes, output_offsets, recv_sizes = ragged_params(
        sizes, me, spec.slot_rows
    )
    out = jnp.zeros((spec.recv_rows, spec.lane), dtype=data.dtype)
    out = ragged_all_to_all(
        data,
        out,
        input_offsets,
        send_sizes.astype(jnp.int32),
        output_offsets.astype(jnp.int32),
        recv_sizes.astype(jnp.int32),
        axis_name=spec.axis_name,
    )
    return out, recv_sizes[None, :]


@jax.named_scope("exchange_dense")
def _exchange_shard_dense(spec: ExchangeSpec, data: jnp.ndarray, size_row: jnp.ndarray):
    """Slot staging -> tiled all_to_all -> row-gather compaction.

    The compaction maps every output row p to its (sender k, within-chunk delta)
    source inside the received slot grid, producing the same tight sender-major
    layout as the ragged path — one static gather over rows, no data-dependent
    shapes."""
    n = spec.num_executors
    slot = spec.slot_rows
    me, sizes = _gather_sizes(spec, size_row)
    recv_sizes = sizes[:, me]

    slots = data.reshape(n, slot, spec.lane)
    received = jax.lax.all_to_all(slots, spec.axis_name, split_axis=0, concat_axis=0, tiled=True)
    flat = received.reshape(n * slot, spec.lane)

    starts = exclusive_cumsum(recv_sizes)                       # (n,)
    cum = jnp.cumsum(recv_sizes)
    total = cum[-1]
    pos = jnp.arange(spec.recv_rows, dtype=jnp.int32)
    k = jnp.searchsorted(cum, pos, side="right").astype(jnp.int32)
    k = jnp.clip(k, 0, n - 1)
    src = k * slot + (pos - starts[k])
    valid = pos < total
    rows = flat[jnp.clip(src, 0, n * slot - 1)]
    out = jnp.where(valid[:, None], rows, jnp.zeros((), dtype=data.dtype))
    return out, recv_sizes[None, :]


def _build_local_exchange(mesh: Mesh, spec: ExchangeSpec):
    """The n=1 degenerate superstep: one Pallas DMA prefix copy.

    Same contract as the collective lowerings EXCEPT rows past the received
    total are UNSPECIFIED (the collective paths zero them; every consumer
    slices by ``recv_sizes``, which the transports already do)."""
    from sparkucx_tpu.ops.pallas_kernels import build_block_gather

    gather = build_block_gather(1, spec.recv_rows, impl="dma")

    def local_fn(data, size_matrix):
        zero = jnp.zeros(1, dtype=jnp.int32)
        counts = size_matrix[0, :1].astype(jnp.int32)
        with jax.named_scope("exchange_local"):
            recv = gather(zero, counts, zero, data)
        return recv, size_matrix

    sharding = NamedSharding(mesh, P(spec.axis_name, None))
    fn = jax.jit(
        local_fn,
        in_shardings=(sharding, sharding),
        out_shardings=(sharding, sharding),
    )
    fn.spec = spec
    return fn


def build_exchange(mesh: Mesh, spec: ExchangeSpec):
    """Compile the shuffle-superstep exchange for ``mesh``.

    Returns a jitted ``fn(data, size_matrix) -> (recv, recv_sizes)`` where

    * ``data``: (n * send_rows, lane) int32, row-sharded over ``axis_name`` —
      executor i's staging buffer is shard i, slot layout (peer j's chunk at row
      ``j * slot_rows``);
    * ``size_matrix``: (n, n) int32, row-sharded — row i is executor i's send
      sizes in rows (block padding included);
    * ``recv``: (n * recv_rows, lane) row-sharded — shard j holds everything
      executor j received, tightly packed sender-major;
    * ``recv_sizes``: (n, n) int32 row-sharded — row j = rows j received from
      each sender i.

    Rows of ``recv`` past each shard's received total are zeros under the
    collective lowerings and UNSPECIFIED under ``'local'`` — consumers must
    slice by ``recv_sizes`` (all in-tree consumers do).
    """
    if spec.num_executors != mesh.devices.size:
        raise ValueError(f"spec.num_executors={spec.num_executors} != mesh size {mesh.devices.size}")
    spec = spec.resolve_impl(platform=mesh.devices.reshape(-1)[0].platform)
    spec.validate()
    if spec.impl == "local":
        return _build_local_exchange(mesh, spec)
    ax = spec.axis_name
    body = _exchange_shard_ragged if spec.impl == "ragged" else _exchange_shard_dense

    shard = shard_map(
        functools.partial(body, spec),
        mesh=mesh,
        in_specs=(P(ax, None), P(ax, None)),
        out_specs=(P(ax, None), P(ax, None)),
        check_vma=False,
    )
    data_sharding = NamedSharding(mesh, P(ax, None))
    sizes_sharding = NamedSharding(mesh, P(ax, None))
    # Donating the staging buffer halves peak HBM when the recv buffer can alias
    # it (same shape/dtype); XLA can't alias mismatched sizes, so only donate
    # then.  This is what lets the pipelined multi-round engine
    # (transport/pipeline.py) run a ring of in-flight rounds without
    # accumulating one extra staging buffer per round: each round's staging
    # HBM is recycled into its own receive buffer.  The size matrix (argnum 1)
    # is NEVER donated — callers chain exchanges reusing one sizes array.
    donate = (0,) if spec.send_rows == spec.recv_rows else ()
    fn = jax.jit(
        shard,
        in_shardings=(data_sharding, sizes_sharding),
        out_shardings=(data_sharding, sizes_sharding),
        donate_argnums=donate,
    )
    fn.spec = spec
    return fn


# ----------------------------------------------------------------------------
# Host-side planning helpers (used by the writer/transport and by tests)
# ----------------------------------------------------------------------------


def bucket_send_rows(send_rows: int, num_executors: int) -> int:
    """Capacity bucketing for the compiled-exchange cache: round the per-peer
    slot capacity up to the next power of two and rescale to a full staging
    size.

    Shuffles of varying size then share one compiled executable per bucket
    (the transports key ``_exchange_cache`` on the bucketed value and zero-pad
    payloads up to it) instead of recompiling per distinct ``send_rows`` —
    the same trick ``_gather_fn`` plays with request sizes.  The result is
    always a ``num_executors`` multiple, so the slot layout invariant
    (``send_rows % n == 0``) survives bucketing; padding rows carry zero
    sizes and never cross the wire under the ragged lowering."""
    if send_rows <= 0:
        raise ValueError("send_rows must be positive")
    slot = -(-send_rows // num_executors)  # ceil: tolerate non-multiples
    bucket = 1
    while bucket < slot:
        bucket <<= 1
    return bucket * num_executors


def rebucket_slots(payload, num_executors: int, bucketed_rows: int, *, xp=np):
    """Relocate a ``(send_rows, lane)`` slot-layout staging payload into a
    ``(bucketed_rows, lane)`` buffer for a bucketed exchange.

    Padding must be inserted PER SLOT, not appended at the tail: the exchange
    reads peer j's chunk at row ``j * slot_rows`` with ``slot_rows`` derived
    from the (bucketed) capacity, so each region has to move to its new slot
    origin.  Zero rows fill the grown slot tails; the size matrix still counts
    only used rows, so under the ragged lowering the padding never crosses the
    wire.  ``xp`` selects the array namespace: ``np`` relocates host-side,
    ``jnp`` on a committed device array relocates on that device (no host
    round-trip for device-sealed payloads)."""
    rows, lane = payload.shape
    if rows == bucketed_rows:
        return payload
    n = num_executors
    if rows % n or bucketed_rows % n or bucketed_rows < rows:
        raise ValueError(
            f"cannot rebucket {rows} rows to {bucketed_rows} over {n} executors "
            "(both must be executor multiples, and buckets only grow)"
        )
    grid = payload.reshape(n, rows // n, lane)
    padded = xp.pad(grid, ((0, 0), (0, (bucketed_rows - rows) // n), (0, 0)))
    return padded.reshape(bucketed_rows, lane)


def pack_chunks_slots(
    chunks: Sequence[bytes],
    slot_rows: int,
    row_bytes: int = 512,
) -> Tuple[np.ndarray, np.ndarray]:
    """Pack per-peer byte chunks into a slot-layout staging buffer: chunk j starts
    at row ``j * slot_rows``, padded to a whole row (the writer-side 512-byte
    alignment analogue, NvkvHandler.scala:244-256).

    Returns ((n*slot_rows, row_bytes/4) int32 buffer, per-peer sizes in rows).

    The buffer is allocated with ``np.empty``: only each chunk's final-row
    tail (part of a USED row, so it does reach receivers) is zeroed.  Rows
    between the sized prefix and the slot end stay uninitialized — the size
    matrix counts only used rows, so no lowering lets them into valid receive
    output (the same contract staging garbage already rides on).
    """
    n = len(chunks)
    buf = np.empty(n * slot_rows * row_bytes, dtype=np.uint8)
    sizes = np.empty(n, dtype=np.int32)
    for j, chunk in enumerate(chunks):
        nbytes = len(chunk)
        rows = -(-nbytes // row_bytes)
        if rows > slot_rows:
            raise ValueError(f"chunk for peer {j} ({rows} rows) exceeds slot {slot_rows} rows")
        start = j * slot_rows * row_bytes
        buf[start : start + nbytes] = np.frombuffer(chunk, dtype=np.uint8)
        buf[start + nbytes : start + rows * row_bytes] = 0  # final-row tail only
        sizes[j] = rows
    return buf.view(np.int32).reshape(n * slot_rows, row_bytes // 4), sizes


def unpack_received(
    recv_shard_bytes: bytes, recv_sizes_row: np.ndarray, row_bytes: int = 512
) -> List[bytes]:
    """Split one receiver's tight sender-major buffer into per-sender chunks
    (row padding still attached; block-level slicing is the resolver's job)."""
    out: List[bytes] = []
    pos = 0
    for sz in recv_sizes_row:
        nbytes = int(sz) * row_bytes
        out.append(recv_shard_bytes[pos : pos + nbytes])
        pos += nbytes
    return out


def oracle_exchange(per_device_chunks: Sequence[Sequence[bytes]]) -> List[bytes]:
    """CPU reference: device j receives concat over senders i of chunk[i][j]
    (each chunk row-padded by the sender).

    The correctness oracle for the collective (SURVEY.md section 7: "bytes verified
    against a CPU shuffle oracle")."""
    n = len(per_device_chunks)
    return [b"".join(per_device_chunks[i][j] for i in range(n)) for j in range(n)]


def make_mesh(num_executors: int, axis_name: str = "ex", devices=None) -> Mesh:
    """Build the 1-D executor mesh over the first ``num_executors`` devices.

    Topology-aware placement lives in parallel/mesh.py; this is the plain
    test-friendly constructor."""
    devs = list(devices if devices is not None else jax.devices())[:num_executors]
    if len(devs) < num_executors:
        raise ValueError(f"need {num_executors} devices, have {len(devs)}")
    return Mesh(np.array(devs), (axis_name,))
