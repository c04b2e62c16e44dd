"""HBM-staged shuffle block store — the NVKV/DPU-NVMe analogue.

Counterpart of ``NvkvHandler`` (NvkvHandler.scala, 266 LoC): where the reference
stages map output through an 8 KB pinned buffer into DPU-attached NVMe
(``write``/``postWrite`` :213-242, ``read``/``postRead`` :160-211) and tracks a
numMappers x numReducers offset table (:258-265), this store stages map output in a
host staging area carved into **per-peer regions** and seals it into **TPU HBM** as a
single ``jax.device_put`` — one large H2D DMA instead of thousands of small ones,
which is the bandwidth-correct shape for TPU.

Key design departures (TPU-first, each replacing a reference POC shortcut):

* **Dynamic space accounting** instead of the static device carve-up
  ``shuffleId * shuffleBlockSize + mapId * alignedMapBlockSize``
  (NvkvShuffleMapOutputWriter.scala:94-103): regions track a used-watermark and
  overflow is an error, not silent corruption.
* **Peer-major regions**: reduce partitions are owned by executors in contiguous
  ranges; each map task's partition bytes append into the owning peer's region.
  Because Spark map writers emit partitions in increasing reduce order
  (enforced sequentially, NvkvShuffleMapOutputWriter.scala:108), region writes
  stay append-only AND the sealed buffer is already in the exact slot layout the
  exchange collective consumes (ops/exchange.py) — zero repacking between "write
  shuffle output" and "run the all_to_all".
* **Alignment**: every block is padded to ``conf.block_alignment`` (default 128,
  the TPU lane width) — the role NVKV's 512-byte sector alignment plays in
  ``writeRemaining`` (NvkvHandler.scala:244-256).  Padding is recorded per block
  like the reference records it per partition (NvkvShuffleMapOutputWriter.scala:236-246).
* The offset table is the authoritative metadata (``commitPartition`` /
  ``getPartitonOffset``/``getPartitonLength``, NvkvHandler.scala:258-265) and is
  exported as a ``MapperInfo`` blob per map task — the same commit payload the
  reference ships to the DPU daemon (NvkvShuffleMapOutputWriter.scala:116-148).
* ``read_block`` serves a staged block back from HBM (after seal) or the host
  staging area (before seal) — the two arms of the reference's A/B path
  ``spark.dpuTest.enabled`` (compat/spark_3_0/UcxShuffleBlockResolver.scala:86-97).
"""

from __future__ import annotations

import functools
import gc
import shutil
import sys
import threading
from time import perf_counter_ns
import weakref
from bisect import bisect_right
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.definitions import MapperInfo
from sparkucx_tpu.core.operation import (
    BlockNotFoundError,
    ResourceExhaustedError,
    TenantQuotaExceededError,
    TransportError,
)
from sparkucx_tpu.service.eviction import ServeCache
from sparkucx_tpu.store.writer import MapWriter
from sparkucx_tpu.testing import faults
from sparkucx_tpu.utils.logging import get_logger
from sparkucx_tpu.utils.trace import span

logger = get_logger("store.hbm_store")


#: the largest host buffer ``seal`` hands to one ``device_put``: the default
#: staging capacity, so a default-conf round is one put as ever; a larger
#: single round is put in pieces of this size (``HbmBlockStore._put_round``),
#: each as soon as the writers have passed it (``_PutBehind``)
SEAL_PUT_PIECE_BYTES = 64 << 20
#: pieces whose transfer may be outstanding before the next is put: what HBM
#: holds beside the round itself while it is being put
SEAL_PUT_PIECES_IN_FLIGHT = 2


@functools.lru_cache(maxsize=None)
def _update_rows_fn():
    """``fn(buf, piece, at)``: ``buf`` with ``piece`` written at row ``at``;
    ``buf`` is donated, so the update is in place."""
    import jax

    def update_rows(buf, piece, at):
        return jax.lax.dynamic_update_slice(buf, piece, (at, 0))

    return jax.jit(update_rows, donate_argnums=0)


def default_peer_ranges(num_reducers: int, num_peers: int) -> List[Tuple[int, int]]:
    """Contiguous reducer ownership: peer p owns [start, end).  Balanced like
    Spark's range partitioning of reduce ids over executors."""
    base, rem = divmod(num_reducers, num_peers)
    ranges = []
    start = 0
    for p in range(num_peers):
        n = base + (1 if p < rem else 0)
        ranges.append((start, start + n))
        start += n
    return ranges


def _purge_spill_dir(holder: Dict[str, Optional[str]]) -> None:
    """Remove a store's private spill tempdir wholesale.  Module-level so the
    ``weakref.finalize`` registered at store construction holds no reference
    to the store itself — the one spill-dir leak is a store dropped without
    ``close()`` (GC / interpreter exit), and a bound method would keep the
    store alive forever."""
    path = holder.get("dir")
    if path is not None:
        shutil.rmtree(path, ignore_errors=True)
        holder["dir"] = None


def _mem_available_bytes() -> Optional[int]:
    """``MemAvailable`` of ``/proc/meminfo``; None where there is none."""
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        pass
    return None


def ram_round_budget(conf: TpuShuffleConf) -> int:
    """Bytes of host RAM a store may hold as completed staging rounds and as
    recycled round buffers: ``conf.max_host_pool_bytes``, bounded by an eighth
    of the host's ``MemAvailable`` at store creation (a host has at most eight
    chips, each with a store) so that a small host is not overrun.  0 = no RAM
    tier."""
    budget = max(int(conf.max_host_pool_bytes), 0)
    available = _mem_available_bytes() if budget else None
    return budget if available is None else min(budget, available // 8)


def staging_floor_limit() -> int:
    """The largest staging round an idle store keeps although it alone is over
    ``ram_round_budget`` (the floor of its free list, ``_recycle_rounds``): a
    quarter of the host's ``MemAvailable`` at store creation.

    A quarter and not the budget's eighth: the floor is ONE buffer, it takes
    the place of the budget's buffers and never stacks on them, and it is
    memory the operator granted to every live shuffle of this executor
    (``staging_capacity_per_executor``) and the executor held a moment ago —
    the peak does not move, only what an idle store gives back.  An eighth
    needs 34.4 GB available for a 4 GiB round, which a one-chip v5e host
    (39.7 GB at store creation) clears by a seventh; a quarter needs 17.2 GB
    there, and on a four-chip host four idle stores hold 17 of 125 GB."""
    available = _mem_available_bytes()
    return sys.maxsize if available is None else available // 4


def _device_nbytes(array) -> int:
    """Bytes of HBM behind ``array``: 0 for a host array, and for a device
    array already donated to an exchange."""
    import jax

    if isinstance(array, jax.Array) and not array.is_deleted():
        return int(array.nbytes)
    return 0


def _owns_flat_bytes(arr: np.ndarray) -> bool:
    """A flat contiguous ``uint8`` array that owns its memory: nobody else's
    buffer shows through it."""
    return (
        arr.dtype == np.uint8 and arr.ndim == 1 and arr.flags.c_contiguous and arr.flags.owndata
    )


def _gather_blocks(dst: np.ndarray, src: np.ndarray, segments) -> None:
    """The replica tier's one copy: ``segments`` of ``(dst offset, src offset,
    length)`` bytes out of a staging round (a RAM round, the disk tier's
    mapping) into ``dst``.  Slice assignment, one ``memcpy`` a block with the
    interpreter given up: on the chip's host it moves a job's 1.0 GB in
    0.09 s where ``native.batch_copy``'s thread team (a team a call, 36
    calls a job) takes 0.33 (PERF.md section 6, PR 46's probe)."""
    for at, off, ln in segments:
        dst[at : at + ln] = src[off : off + ln]


def _device_block_bytes(array, offset: int, length: int, alignment: int) -> np.ndarray:
    """``length`` bytes at byte ``offset`` (a row boundary) of a device round,
    as uint8 on the host: the block's rows are sliced ON the device, in a
    power-of-two bucket of rows so that blocks of nearby sizes share one
    executable, and only they cross to the host — never the whole round."""
    import jax

    total = int(array.shape[0])
    rows = min(1 << max(-(-length // alignment) - 1, 0).bit_length(), total)
    at = min(offset // alignment, total - rows)  # XLA would clamp the start: do it here
    window = jax.lax.dynamic_slice_in_dim(array, np.int32(at), rows, axis=0)
    skip = offset - at * alignment
    return np.asarray(window).reshape(-1).view(np.uint8)[skip : skip + length]


@dataclass
class _BlockEntry:
    offset: int  # absolute offset in the staging buffer (of its round)
    length: int  # true payload bytes
    padded: int  # bytes including alignment padding
    round: int = 0  # staging round (multi-round spill; round 0 = common case)
    #: False for entries installed from a peer's MapperInfo — their offsets are
    #: sender-relative, so the bytes live on the SENDER, not in local staging.
    #: The replicator only pushes local entries.
    local: bool = True
    #: a block longer than a peer region: its pieces ``(round, offset,
    #: length)`` in order, each an extent of its round's staging, the first
    #: at ``(round, offset)`` above (``HbmBlockStore.take_piece``).  None for
    #: the block of one extent that every other block is
    pieces: Optional[Tuple[Tuple[int, int, int], ...]] = None


@dataclass(eq=False, slots=True)
class _Reservation:
    """The extent an open partition holds in a round's staging while its
    bytes reach it outside the store's lock: the frames of a receive in place
    (``MapWriter.reserve``), the copy of a buffered block
    (``MapWriter.close_partition``).  It belongs to the round it was made in:
    a rollover does not move it.  One extent is one object
    (``_PutBehind.open`` keeps the open ones by identity)."""

    round: int  # the staging round it was made in
    start: int  # absolute offset in the round's buffer
    padded: int  # bytes of the region it takes (a multiple of the alignment)
    filled: int = 0  # bytes received into it so far (a buffered copy: 0 while it runs)


class _PutBehind:
    """A single round on its way to the store's device while it is still
    being written: the state of ``_put_round``'s update chain, kept from the
    first piece whose bytes are final to the seal (PR 51).

    A store that will seal a shuffle's one round straight onto its device —
    it has a device, the staging round is larger than one
    ``SEAL_PUT_PIECE_BYTES`` piece and is the store's own buffer, one its
    free list handed the shuffle (pages the process holds), and as long as
    nothing rolled over and the writes are host writes — does not wait for
    the seal: a piece is put as soon as it lies wholly below its region's
    ``region_used``, and below the rows that every extent still open — a
    partition received in place, a buffered block being copied — has filled
    (``open``; ``final_marks``).
    Such a piece holds its final bytes: ``region_used`` only grows within a
    round, a block once copied is never rewritten, and a retried map's or an
    abandoned reservation's extent stays behind as padding.  An extent is
    taken — ``region_used`` moves by its PADDED total — before a byte is
    there, several writers' copies end in any order, and a partition's next
    frame is received from its UNPADDED end — into the last row of the frame
    before it, below ``region_used`` — so an extent counts only up to its
    last whole row received (a buffered copy: not at all) until the partition
    is recorded, lost or sent back to the buffered path: with four writers a
    copy is in flight nearly always, and the record that completes a piece
    may be an older extent's, finishing last (``HbmBlockStore.record_extent``).  The pieces are
    ``_put_round``'s own (whole pieces at the same offsets, at most
    ``SEAL_PUT_PIECES_IN_FLIGHT`` awaiting their transfer), so the seal puts
    what is left — the piece each writer stands in, a piece that straddles
    two regions — and hands ``buf`` over: the same bytes cross once, earlier.

    ONE owner at a time runs the donated chain ``buf = update(buf, piece,
    at)``: the thread whose ``close_partition`` (or ``end_receive``) took its
    region's final mark past a piece's end claims the pieces that are final under the store's lock
    (``owner``), puts them OUTSIDE it, and looks again before it lets go, so
    a writer that passes a piece meanwhile leaves it to the owner and goes
    on copying.  ``seal``, ``remove_shuffle`` and ``close`` wait for the
    owner on the store's condition.  A rollover, a removal and a put that
    raised take the state off the shuffle (``_drop_put_behind``): the device
    buffer is let go and the shuffle goes on as if nothing had been put.

    ``cursor[p]`` is the next piece (its index in the round) of region
    ``p``'s queue — the pieces that START in region ``p``, in order — and
    ``next_end[p]`` the absolute staging offset the region's used prefix
    must reach for that piece to be final: ``sys.maxsize`` once the queue is
    through or its next piece reaches into the next region.  A record
    compares its region's final mark with it (``final_marks``: an integer a
    region and one an extent still open).  All fields but ``buf`` and
    ``in_flight`` are read and written under the owning store's lock; those
    two belong to the owner (to ``seal`` once it has waited the owner out)."""

    __slots__ = (
        "rows", "piece_rows", "region_rows", "alignment", "cursor", "next_end",
        "open", "owner", "buf", "in_flight",
    )

    def __init__(self, regions: int, region_rows: int, piece_rows: int, alignment: int) -> None:
        self.rows, self.piece_rows = regions * region_rows, piece_rows
        self.region_rows, self.alignment = region_rows, alignment
        self.cursor = [-(-p * region_rows // piece_rows) for p in range(regions)]
        self.next_end = [0] * regions
        for p in range(regions):
            self._aim(p)
        #: the extents whose bytes are on their way into the round outside
        #: the store's lock: from ``take_extent`` with ``hold`` (a
        #: partition's first ``reserve``, a buffered close) until the
        #: partition is recorded, lost or back on the buffered path
        self.open: set = set()
        self.owner = False  # a thread is putting claimed pieces outside the lock
        self.buf = None  # the round on the device: zeros but for the pieces put
        self.in_flight: deque = deque()  # pieces whose transfer may be outstanding

    def _aim(self, p: int) -> None:
        at = self.cursor[p] * self.piece_rows
        end = min(at + self.piece_rows, self.rows)
        inside = at < end <= (p + 1) * self.region_rows
        self.next_end[p] = end * self.alignment if inside else sys.maxsize

    def claim(self, p: int) -> int:
        """Region ``p``'s next piece, taken: the cursor moves past it."""
        k = self.cursor[p]
        self.cursor[p] = k + 1
        self._aim(p)
        return k

    def final_marks(self, region_used) -> List[int]:
        """Per region, the absolute staging offset below which every byte is
        final: its used prefix, held below the first row that an extent
        still open has not wholly received (a buffered copy in flight: its
        first row)."""
        region_bytes = self.region_rows * self.alignment
        marks = [p * region_bytes + int(used) for p, used in enumerate(region_used)]
        for resv in self.open:
            p = resv.start // region_bytes
            marks[p] = min(marks[p], resv.start + resv.filled // self.alignment * self.alignment)
        return marks

    def is_put(self, at: int) -> bool:
        """Whether the piece that starts at row ``at`` was claimed."""
        return at // self.piece_rows < self.cursor[at // self.region_rows]

    def release(self) -> None:
        """Let the device buffer and the pieces go (no owner is active)."""
        self.buf = None
        self.in_flight.clear()


class _EarlyRounds:
    """A multi-round shuffle's completed RAM rounds on the store's device
    before the exchange asks for them (PR 57): what ``_PutBehind`` is to a
    job's one round, for the rounds a job rolls.

    A round that rolled on ``_rollover``'s RAM arm is never written again
    once no extent is open in it — a receive in place or a buffered copy
    taken before the rollover may still be landing (``_ShuffleState
    .inflight``) — and then lies in ``prev_rounds`` until the exchange puts
    it, the link idle meanwhile.  A store with a device puts such a round
    when it becomes final, where its buffer is one the free list handed out
    (pages the process holds: ``_take_staging``'s law, a fact of the buffer):
    ONE ``device_put`` of the round's ``(rows, lane)`` view (a round larger
    than ``SEAL_PUT_PIECE_BYTES`` in pieces, as ``_put_round`` puts it),
    outside the store's lock, on the thread of the writer whose record found
    it ready (``HbmBlockStore.record_extent`` → ``put_behind``).  The host
    round stays the shuffle's backing store and ``seal`` hands it on as
    ever; the copy rides beside it until ``HbmBlockStore.take_early_round``
    hands it to the exchange, which then puts nothing for that round.  What
    the exchange did not take is let go — and counted, ``early_rounds_dropped``
    and ``released_device_bytes`` — by ``release_early_rounds`` (a plan whose
    window is not the staging slot, an aborted exchange), ``remove_shuffle``
    and ``close``; a put that raised costs the shuffle its early copies and
    never the write.

    ONE owner at a time puts (``owner``; ``_PutBehind``'s rule): it claims
    the rounds that are ready under the store's lock, puts them outside it
    and looks again before it lets go; ``seal``, ``remove_shuffle`` and
    ``close`` wait for it on the store's condition.  Every field is read and
    written under the owning store's lock."""

    __slots__ = ("open", "ready", "copies", "owner", "closed")

    def __init__(self) -> None:
        self.open: set = set()  # rounds rolled with an extent still open in them
        self.ready: deque = deque()  # rounds that are final and not yet claimed
        self.copies: Dict[int, object] = {}  # round -> its jax.Array on the store's device
        self.owner = False  # a thread is putting claimed rounds outside the lock
        self.closed = False  # no round is queued or put any more


class _ShuffleState:
    def __init__(
        self,
        shuffle_id: int,
        num_mappers: int,
        num_reducers: int,
        peer_ranges: List[Tuple[int, int]],
        capacity: int,
        alignment: int,
        staging: Optional[np.ndarray] = None,
        staging_closer=None,
        alloc: Callable[[int], np.ndarray] = functools.partial(np.zeros, dtype=np.uint8),
    ) -> None:
        self.shuffle_id = shuffle_id
        #: ``alloc(nbytes)``: an all-zero uint8 round buffer — the owning
        #: store's ``_take_round_buffer`` (its free list, then ``np.zeros``)
        self._alloc = alloc
        self.num_mappers = num_mappers
        self.num_reducers = num_reducers
        self.peer_ranges = peer_ranges
        self.alignment = alignment
        self.staging_closer = staging_closer
        n = len(peer_ranges)
        self.region_size = (capacity // n) // alignment * alignment
        if self.region_size <= 0:
            raise ValueError(f"staging capacity {capacity} too small for {n} regions")
        if staging is not None:
            if staging.size < n * self.region_size:
                raise ValueError("provided staging buffer too small")
            self._staging = staging[: n * self.region_size]
        else:
            self._staging = None  # allocated lazily on first host-path touch
        #: Write-path mode latch: None until the first partition lands, then
        #: False (host MapWriter.write) or True (write_partition_device) — a
        #: shuffle is host- or device-staged, never both.
        self.device_mode: Optional[bool] = None
        #: The live device round: ONE ``(total_rows, lane)`` int32 staging
        #: array on the owning store's device, zeros but for the blocks the
        #: scatter has placed.  Made at the first device write of a round
        #: (``HbmBlockStore._stage_device``), handed to ``seal`` as it is, None
        #: in between.  Nothing of a producer's array is kept beside it.
        self.device_staging: Optional[object] = None  # jax.Array
        #: Multi-round spill state: when a region fills, the whole staging epoch
        #: is snapshotted and writing continues in a fresh round — the exchange
        #: then runs one collective per round.  This is the data-volume scaling
        #: the reference windows with maxBlocksPerRequest/numOutstanding
        #: (SURVEY.md section 5.7) applied to the bulk-synchronous plane.
        self.round = 0
        #: (staging, region_used) of each completed round: the round's own RAM
        #: buffer, or its ``np.memmap`` on the disk tier; ``staging`` is None
        #: once ``remove_shuffle`` took a RAM round's buffer back
        self.prev_rounds: List[Tuple[Optional[np.ndarray], np.ndarray]] = []
        #: (path, nbytes) of rounds spilled to the disk tier (conf.spill_to_disk)
        self.spill_files: List[Tuple[str, int]] = []
        self.region_used = np.zeros(n, dtype=np.int64)
        self.blocks: Dict[Tuple[int, int], _BlockEntry] = {}  # (map, reduce) -> entry
        self.committed_maps: set = set()
        self.sealed_payload: Optional[object] = None  # jax.Array | np.ndarray
        self._range_starts = [r[0] for r in peer_ranges]
        #: Owning tenant (multi-tenant service, service/tenants.py); None for
        #: single-tenant shuffles — no charges, no translation, no wire ext.
        self.app_id: Optional[str] = None
        #: Bytes currently charged against the owning tenant's HBM quota
        #: (region allocations + restaged rounds, minus disk-tier demotions).
        self.tenant_charged = 0  #: guarded by the owning store's _lock
        #: Latched by remove_shuffle/close before the staging is released.  A
        #: reader that resolved this state before the removal must get a clean
        #: refusal, so the lazy ``staging`` property never re-allocates (and
        #: never serves fresh zeros as block bytes) once this is set.
        self.removed = False  #: guarded by self._lock
        #: staging round -> extents being filled outside the store's lock
        #: (``HbmBlockStore.take_extent`` with ``hold`` takes one; a socket's
        #: ``end_receive``, a buffered ``close_partition`` gives it back);
        #: whoever would read, zero or hand on a round's buffer waits for its
        #: count to reach zero (``HbmBlockStore._await_receives``)
        #: (read and written under the owning store's ``_lock``)
        self.inflight: Dict[int, int] = {}
        #: map writers of the shuffle created and not yet committed (a
        #: retry that discards is not counted; an abandoned one stays).  With
        #: one open nobody can wait at the lock for its copy, and a buffered
        #: block keeps the lock through allocate + copy + record; with more,
        #: the copy leaves the lock (``MapWriter.close_partition``)
        #: (under the owning store's ``_lock``)
        self.open_writers = 0
        #: waiters in ``_await_receives``: no new reservation is admitted
        #: while one drains, so a stream of writers cannot starve it
        #: (under the owning store's ``_lock``)
        self.draining = 0
        #: the round's pieces put behind the writers, where the store will
        #: seal this shuffle's one round onto its device (``_PutBehind``):
        #: set at the staging's first touch (``HbmBlockStore._take_staging``);
        #: None before and otherwise, after a rollover, and once sealed or removed
        #: (under the owning store's ``_lock``)
        self.put_behind: Optional[_PutBehind] = None
        #: whether the live staging round's buffer is one the store's free
        #: list handed out — pages the process holds — and not a fresh one
        #: (``HbmBlockStore._take_staging``, ``_rollover``)
        #: (under the owning store's ``_lock``)
        self.live_held = False
        #: the completed RAM rounds on their way to the store's device before
        #: the exchange (``_EarlyRounds``); None until a round qualifies
        #: (under the owning store's ``_lock``)
        self.early_rounds: Optional[_EarlyRounds] = None

    def pieces(self) -> Optional[_PutBehind]:
        """The staging round as ``SEAL_PUT_PIECE_BYTES`` pieces, nothing put
        yet; None for a round of one piece."""
        region_rows = self.region_size // self.alignment
        piece_rows = SEAL_PUT_PIECE_BYTES // self.alignment
        if len(self.peer_ranges) * region_rows <= piece_rows:
            return None
        return _PutBehind(len(self.peer_ranges), region_rows, piece_rows, self.alignment)

    def settled(self, resv: _Reservation) -> None:
        """``resv``'s partition was recorded, lost or sent back to the
        buffered path (caller holds the owning store's ``_lock``): nothing
        more is received into its extent, which no longer holds a put
        cursor."""
        if self.put_behind is not None:
            self.put_behind.open.discard(resv)

    @property
    def staging(self) -> Optional[np.ndarray]:
        """Host staging buffer, allocated on first touch; None once the
        shuffle was removed.  Device-staged shuffles never read this property,
        so the buffer is never allocated for them — the observable form of
        the tentpole's "no host round trip" guarantee
        (``HbmBlockStore.host_staging_allocated``)."""
        if self._staging is None and not self.removed:
            self._staging = self._alloc(len(self.peer_ranges) * self.region_size)
        return self._staging

    @staging.setter
    def staging(self, value: Optional[np.ndarray]) -> None:
        self._staging = value

    @property
    def host_staging_allocated(self) -> bool:
        return self._staging is not None

    def owner_of(self, reduce_id: int) -> int:
        if not (0 <= reduce_id < self.num_reducers):
            raise ValueError(f"reduce_id {reduce_id} out of range [0, {self.num_reducers})")
        return bisect_right(self._range_starts, reduce_id) - 1

    @property
    def sealed(self) -> bool:
        return self.sealed_payload is not None


class HbmBlockStore:
    """Per-executor staged shuffle store.  See module docstring."""

    def __init__(
        self, conf: Optional[TpuShuffleConf] = None, device=None, executor_id: int = 0
    ) -> None:
        self.conf = conf or TpuShuffleConf()
        self.device = device
        self.executor_id = executor_id
        self._shuffles: Dict[int, _ShuffleState] = {}  #: guarded by self._lock
        # Commits that raced ahead of create_shuffle (a peer's MapperInfo can
        # arrive before this process registers the shuffle); applied at creation.
        self._pending_infos: Dict[int, List[MapperInfo]] = {}  #: guarded by self._lock
        self._lock = threading.RLock()
        #: the same lock under the name a map task's writer takes it by: the
        #: only writer open holds it across ``take_extent``, its copy and ``record_extent``
        self.lock = self._lock
        #: on ``_lock``: woken when a receive in place ends and when a waiter
        #: for such receives is done (``_await_receives``, ``take_extent``)
        self._cond = threading.Condition(self._lock)
        # disk round tier accounting (conf.spill_to_disk).  The tempdir path
        # lives in a plain dict holder so the weakref.finalize below can purge
        # it when the store is dropped WITHOUT close() (GC / interpreter
        # exit) — the one path that used to leak sparkucx_tpu_spill_e* dirs.
        self._spill_holder: Dict[str, Optional[str]] = {"dir": None}  #: guarded by self._lock
        self._spill_finalizer = weakref.finalize(self, _purge_spill_dir, self._spill_holder)
        self._spill_bytes = 0  #: guarded by self._lock
        #: Map-side write counters (the ``store`` metrics family): plain ints,
        #: always on, bumped once a map task at its commit (``staged_*`` from
        #: its block table, ``copy_ns`` from the clock round each block's copy,
        #: ``lock_wait_ns`` from the clock round each block's wait for this
        #: store's lock: what other writers' copies and rollovers cost it)
        #: and once a staging round (``rollovers``, ``spilled_bytes``, ``*_ns``;
        #: ``ram_rounds``: rollovers whose round stayed in RAM;
        #: ``recycled_rounds``: host rollovers that spilled and kept their
        #: buffer, and ``zeroed_bytes``: what they set back to zero in it).
        #: ``rollover_ns`` includes the ``spill_ns`` of the round it spilled;
        #: ``spill_ns`` also counts the eviction manager's demotions.
        #: ``released_device_bytes``: HBM a removed shuffle gave back at its
        #: removal (its device-sealed round here; its received shards, which
        #: the cluster counts through ``count_released_device``).
        #: The free list of round buffers: ``pool_hits`` / ``pool_misses``
        #: (round buffers taken from it / allocated), ``pool_dropped_busy``
        #: (buffers of a removed or demoted round not taken back because
        #: something still referred to them), ``pool_kept_over_budget``
        #: (buffers the free list's floor — not the budget — kept: a removed
        #: shuffle's one staging round larger than the budget) and the gauge
        #: ``pool_held_bytes`` (what the free list holds now).
        #: The device write path (``_stage_device``), once a dispatch:
        #: ``scatter_dispatches``, the blocks and true bytes they placed
        #: (``device_staged_blocks`` / ``device_staged_bytes``; ``staged_*``
        #: count both paths at commit) and ``device_stage_ns``, the time the
        #: dispatches held the writer's thread — not the DMA.
        #: The receive in place (``MapWriter.reserve``; a daemon's store):
        #: ``inplace_blocks`` / ``inplace_bytes`` (blocks recorded where a
        #: socket put them, and their bytes) and ``inplace_fallbacks``
        #: (partitions that went back to the buffered path), added at
        #: ``commit`` like ``copy_ns``; ``inflight_wait_ns``: what a spill, a
        #: seal, a removal and ``close`` waited for receives and copies in
        #: flight.  The buffered close: ``unlocked_copy_blocks`` /
        #: ``unlocked_copy_bytes`` (blocks closed while their shuffle had
        #: more than one writer open, whose copy into staging ran outside
        #: this store's lock, and their bytes; added at ``commit``;
        #: ``copy_ns`` counts every copy, ``lock_wait_ns`` both takes of
        #: such a block).
        #: What unequal blocks do to staging: ``rollover_tail_bytes`` (once a
        #: rollover: the free bytes of the region whose overflow rolled the
        #: round) and the gauge ``largest_block_bytes`` (once a map task at
        #: its commit: the longest block any committed task staged here).
        #: The single round put behind the writers (``_PutBehind``):
        #: ``early_put_pieces`` / ``early_put_bytes`` (pieces put on the
        #: device before their shuffle's seal, and their bytes),
        #: ``seal_put_pieces`` (pieces of a round larger than one piece that
        #: ``seal`` itself still put) and ``early_put_dropped`` (device
        #: buffers with pieces in them that a rollover, a removal or a failed
        #: put discarded).
        #: The completed rounds of a multi-round job put before the exchange
        #: (``_EarlyRounds``): ``early_round_puts`` / ``early_round_bytes``
        #: (rounds put on the device when they became final, and their
        #: bytes) and ``early_rounds_dropped`` (such copies the exchange did
        #: not take: let go by a removal, ``close``, an aborted exchange, a
        #: plan whose window is not the staging slot, a put that raised;
        #: their bytes join ``released_device_bytes``).
        #: guarded by self._lock
        self._write_stats: Dict[str, int] = dict.fromkeys(
            ("staged_blocks", "staged_bytes", "rollovers", "spilled_bytes",
             "rollover_ns", "spill_ns", "copy_ns", "released_device_bytes",
             "recycled_rounds", "zeroed_bytes", "ram_rounds", "pool_hits",
             "pool_misses", "pool_dropped_busy", "pool_kept_over_budget", "pool_held_bytes",
             "device_staged_blocks", "device_staged_bytes", "scatter_dispatches",
             "device_stage_ns", "lock_wait_ns", "inplace_blocks", "inplace_bytes",
             "inplace_fallbacks", "inflight_wait_ns", "rollover_tail_bytes",
             "largest_block_bytes", "early_put_pieces", "early_put_bytes",
             "seal_put_pieces", "early_put_dropped", "unlocked_copy_blocks",
             "unlocked_copy_bytes", "early_round_puts", "early_round_bytes",
             "early_rounds_dropped", "split_blocks", "split_pieces", "split_bytes",
             "split_rollovers"), 0
        )
        #: RAM tier of completed rounds (``_rollover``): capacity bytes of the
        #: RAM rounds live shuffles hold plus the free list never exceed
        #: ``_ram_budget`` while the disk tier is on; 0 = every rollover spills
        self._ram_budget = ram_round_budget(self.conf)
        #: what one staging round kept over the budget may be (``_floor_keeps``);
        #: no RAM tier, no floor
        self._floor_limit = staging_floor_limit() if self._ram_budget else 0
        self._ram_round_bytes = 0  #: guarded by self._lock
        #: HBM the early copies of completed rounds hold and the exchange
        #: has not taken (``_EarlyRounds``): at most ``_ram_budget``
        self._early_round_bytes = 0  #: guarded by self._lock
        #: buffer size -> all-zero round buffers of removed shuffles and
        #: demoted rounds, that nothing else refers to (``_recycle_rounds``)
        self._free_rounds: Dict[int, List[np.ndarray]] = {}  #: guarded by self._lock
        #: Optional TenantRegistry (service/tenants.py).  When set, shuffles
        #: created with an ``app_id`` are admission-checked: region
        #: allocations charge the tenant's HBM quota and over-quota writes
        #: raise TenantQuotaExceededError.  Written once at service wiring.
        self.tenants = None
        #: Optional EvictionManager hook (service/eviction.py): notified on
        #: every block access so disk-tier rounds restage transparently.
        #: Written once at service wiring.
        self.eviction = None
        #: Bounded serve-side decoded-block cache (popularity tier): hot
        #: blocks pinned ABOVE the eviction tiers, so demotion/restage churn
        #: never hits the hot set.  None when serve.cacheBytes is 0 (default)
        #: — the off path allocates nothing and touches no new locks.
        self.serve_cache: Optional[ServeCache] = (
            ServeCache(self.conf.serve_cache_bytes)
            if self.conf.serve_cache_bytes > 0
            else None
        )
        #: build_block_scatter compile cache keyed by pow2-bucketed geometry —
        #: the _gather_fn discipline (transport/tpu.py) applied to the write
        #: path, so varying-shape device rounds share a handful of compiles.
        self._scatter_cache: Dict[Tuple[int, int, int], object] = {}  #: guarded by self._lock
        # -- neighbor-replication tier (REPLICA_PUT landing zone) ----------
        #: (shuffle_id, src_executor) -> round -> ((map, reduce) -> (offset,
        #: length) index, contiguous body array).  Bodies are whole replicated
        #: rounds, so replica_view serves zero-copy like block_staging_view.
        self._replicas: Dict[Tuple[int, int], Dict[int, Tuple[Dict[Tuple[int, int], Tuple[int, int]], np.ndarray]]] = {}  #: guarded by self._lock
        self._replica_bytes = 0  #: guarded by self._lock
        #: Post-seal hook (PeerTransport installs its replication push here).
        #: Written once at transport construction, invoked by seal() AFTER the
        #: store lock is released — implementations may call back into the
        #: store freely.
        self.on_seal: Optional[Callable[[int], None]] = None
        # -- memory-pressure watermarks (gray-failure load shedding) -------
        #: out-of-band soft-watermark eviction sweeps kicked so far
        self._watermark_sweeps = 0  #: guarded by self._lock
        #: single-flight latch: at most one sweep thread runs at a time
        self._sweeping = False  #: guarded by self._lock

    @property
    def _spill_dir(self) -> Optional[str]:
        return self._spill_holder["dir"]

    @_spill_dir.setter
    def _spill_dir(self, value: Optional[str]) -> None:
        """Caller holds self._lock (both writers: _spill_round's lazy mkdtemp
        and _release_spill's last-shuffle rmdir)."""
        self._spill_holder["dir"] = value

    def _shm_staging(self, shuffle_id: int, nbytes: int):
        """Shared-memory staging for single-host zero-copy serving
        (conf.use_shm_staging — the NVKV shared-store analogue)."""
        from sparkucx_tpu import native

        name = f"/{self.conf.shm_namespace}_e{self.executor_id}_s{shuffle_id}"
        arena = native.SharedArena(name, nbytes, create=True)

        def closer(_arena=arena):
            _arena.close()
            _arena.unlink()

        return arena.array, closer

    # -- lifecycle ---------------------------------------------------------

    def create_shuffle(
        self,
        shuffle_id: int,
        num_mappers: int,
        num_reducers: int,
        peer_ranges: Optional[Sequence[Tuple[int, int]]] = None,
        capacity: Optional[int] = None,
        app_id: Optional[str] = None,
    ) -> None:
        if app_id is not None and self.tenants is not None:
            self.tenants.resolve(app_id)  # typed UnknownTenantError if not registered
        with self._lock:
            if shuffle_id in self._shuffles:
                raise TransportError(f"shuffle {shuffle_id} already exists")
            ranges = list(peer_ranges) if peer_ranges is not None else default_peer_ranges(num_reducers, 1)
            cap = capacity if capacity is not None else self.conf.staging_capacity_per_executor
            staging, closer = None, None
            if self.conf.use_shm_staging:
                n = len(ranges)
                region = (cap // n) // self.conf.block_alignment * self.conf.block_alignment
                staging, closer = self._shm_staging(shuffle_id, max(n * region, 1))
            self._shuffles[shuffle_id] = _ShuffleState(
                shuffle_id,
                num_mappers,
                num_reducers,
                ranges,
                cap,
                self.conf.block_alignment,
                staging=staging,
                staging_closer=closer,
                alloc=functools.partial(self._take_staging, shuffle_id),
            )
            self._shuffles[shuffle_id].app_id = app_id
            pending = self._pending_infos.pop(shuffle_id, [])
        for info in pending:
            self.apply_mapper_info(info)

    def remove_shuffle(self, shuffle_id: int) -> None:
        """unregisterShuffle analogue (UcxShuffleTransport.scala:249-259).
        The shm closer runs under the store lock so no reader holding the lock
        can see a staging mapping that is about to be munmapped."""
        with self._lock:
            st = self._shuffles.pop(shuffle_id, None)
            if st is not None:
                st.removed = True
                # nothing below may zero, unmap or hand a buffer to the free
                # list under a receive or a piece's put: wait them out (no
                # new one is admitted)
                self._await_quiet(st)
                early = self._drop_put_behind(st)
                self._release_early_rounds(st)  # before the round buffers are offered to the free list
                # The live staging round, the RAM rounds and the device-sealed
                # payload are released HERE, not at the interpreter's next
                # collection: a writer or reader handle may keep the state
                # object reachable long after (a 4 GiB buffer and 4 GiB of HBM
                # a shuffle under a one-round conf).  ``removed`` is latched
                # first, so a reader that resolved the state before gets the
                # same clean refusal as on the shm arm.
                rounds = self._detach_host_rounds(st)
                if st.staging_closer is not None:
                    st.staging_closer()
                self._write_stats["released_device_bytes"] += sum(
                    _device_nbytes(payload)
                    for payload in (st.device_staging, early, *(st.sealed_payload or ()))
                )
                st.sealed_payload = st.device_staging = early = None
                self._recycle_rounds(rounds, floor=True)
                self._release_spill(st)
                self._release_tenant(st, st.tenant_charged)
            for key in [k for k in self._replicas if k[0] == shuffle_id]:
                for _index, arr in self._replicas[key].values():
                    self._replica_bytes -= int(arr.size)
                del self._replicas[key]
        # Serve-cache entries of the removed shuffle are dropped WITHOUT a
        # per-entry quota release: the blanket _release_tenant above already
        # returned st.tenant_charged, which includes every cache charge.
        # Sequential lock scopes — the cache lock is a leaf, never nested
        # under self._lock.
        if self.serve_cache is not None:
            self.serve_cache.invalidate_shuffle(shuffle_id)
        if self.eviction is not None:
            # the LRU access table must not outlive the shuffle: recycled ids
            # (lineage-cache recomputes) would inherit stale recency
            self.eviction.forget_shuffle(shuffle_id)

    def close(self) -> None:
        with self._lock:
            states, self._shuffles = list(self._shuffles.values()), {}
            self._replicas.clear()
            self._replica_bytes = 0
            for st in states:
                st.removed = True
                self._await_quiet(st)  # the shm closer unmaps the buffer
                self._drop_put_behind(st)
                self._release_early_rounds(st)
                if st.staging_closer is not None:
                    st.staging = None
                    st.staging_closer()
                self._release_spill(st)
                self._release_tenant(st, st.tenant_charged)
            # The mkdtemp'd spill dir is store-private, so close() may remove
            # it wholesale even when foreign files crept in (the rmdir in
            # _release_spill only handles the empty-dir case).
            _purge_spill_dir(self._spill_holder)
            self._spill_bytes = 0
            self._free_rounds.clear()
            self._write_stats["pool_held_bytes"] = 0
            self._ram_round_bytes = 0

    def _charge_tenant(self, st: _ShuffleState, nbytes: int) -> None:
        """Admission check at allocation time (caller holds self._lock): claim
        ``nbytes`` against the owning tenant's HBM quota.  Raises the typed
        TenantQuotaExceededError BEFORE any state mutation, so a rejected
        write leaves the store exactly as it was.  The charge is tracked in
        ``st.tenant_charged`` and released by ``_release_tenant`` on shuffle
        removal, store close, or tier demotion — ownership transfers to the
        shuffle state, not the calling frame."""
        if self.tenants is None or st.app_id is None or nbytes <= 0:
            return
        self.tenants.charge(st.app_id, st.shuffle_id, nbytes)
        st.tenant_charged += nbytes

    def _release_tenant(self, st: _ShuffleState, nbytes: int) -> None:
        """Return quota bytes (caller holds self._lock): shuffle removal,
        store close, or a round demoted off the HBM/host tiers."""
        if self.tenants is None or st.app_id is None or nbytes <= 0:
            return
        self.tenants.release(st.app_id, nbytes)
        st.tenant_charged = max(0, st.tenant_charged - nbytes)

    def _state(self, shuffle_id: int) -> _ShuffleState:
        with self._lock:
            st = self._shuffles.get(shuffle_id)
        if st is None:
            raise TransportError(f"unknown shuffle {shuffle_id}")
        return st

    # -- memory-pressure watermarks (gray-failure load shedding) ----------

    def _pressure_locked(self) -> int:
        """Host bytes this store is holding live (caller holds self._lock):
        every shuffle's staged bytes in RAM rounds plus the replica tier.
        Disk-tier (memmap) rounds are excluded — they are exactly the bytes
        the watermark machinery already shed."""
        total = self._replica_bytes
        for st in self._shuffles.values():
            total += int(st.region_used.sum())
            for snap, used in st.prev_rounds:
                if not isinstance(snap, np.memmap):
                    total += int(used.sum())
        return total

    def memory_pressure_bytes(self) -> int:
        with self._lock:
            return self._pressure_locked()

    def _check_pressure_locked(self, site: str, nbytes: int) -> bool:
        """Watermark gate body; caller holds ``self._lock``.  Raises the
        typed RETRYABLE ``ResourceExhaustedError`` past the hard watermark;
        returns True when the soft watermark is crossed — the caller MUST
        call ``_kick_watermark_sweep()`` AFTER releasing the lock (the kick
        takes the lock itself, and the sweep latch must never be reached
        through a held-lock path).  The ``store.mem_pressure`` fault point
        fires first either way, so chaos tests inject pressure without
        configuring watermarks."""
        faults.check("store.mem_pressure", site=site, nbytes=nbytes)
        soft = self.conf.store_soft_watermark
        hard = self.conf.store_hard_watermark
        if soft <= 0 and hard <= 0:
            return False
        pressure = self._pressure_locked()
        if hard > 0 and pressure + nbytes > hard:
            raise ResourceExhaustedError(
                requested=nbytes,
                used=pressure,
                watermark=hard,
                detail=f"store hard watermark at {site} (executor {self.executor_id})",
            )
        return soft > 0 and pressure + nbytes > soft

    def check_memory_pressure(self, site: str, nbytes: int = 0) -> None:
        """Gate an allocation-bearing mutation against the watermarks
        (``store.softWatermark`` / ``store.hardWatermark``); called BEFORE any
        state changes, so a shed write leaves the store exactly as it was.

        Soft watermark crossed: kick one out-of-band eviction sweep (demote
        one round a tier down) and admit the write.  Hard watermark crossed:
        raise the typed RETRYABLE ``ResourceExhaustedError`` — on the wire it
        becomes ``SIZE_RESOURCE_EXHAUSTED`` and clients back off and retry.
        Both knobs default 0 = off, the byte-identical store."""
        with self._lock:
            kick = self._check_pressure_locked(site, nbytes)
        if kick:
            self._kick_watermark_sweep()

    def _kick_watermark_sweep(self) -> None:
        """Single-flight out-of-band eviction sweep: demote ONE round a tier
        down (the EvictionManager's documented demotion order), off-thread so
        the writer that crossed the soft watermark never blocks on IO."""
        ev = self.eviction
        if ev is None:
            return
        with self._lock:
            if self._sweeping:
                return
            self._sweeping = True
            self._watermark_sweeps += 1

        def _sweep() -> None:
            try:
                ev.run_epoch(max_demotions=1)
            except Exception:
                pass  # shedding pressure is best-effort; the hard gate holds
            finally:
                with self._lock:
                    self._sweeping = False

        threading.Thread(
            target=_sweep, daemon=True, name=f"wm-sweep-e{self.executor_id}"
        ).start()

    def watermark_stats(self) -> Dict[str, int]:
        """Watermark telemetry for the metrics registry (eviction family)."""
        with self._lock:
            return {
                "watermark_sweeps": self._watermark_sweeps,
                "pressure_bytes": self._pressure_locked(),
            }

    def write_stats(self) -> Dict[str, int]:
        """The map-side write counters plus this store's ``executor`` id —
        one row of the ``store`` metrics family."""
        with self._lock:
            return {"executor": self.executor_id, **self._write_stats}

    def count_released_device(self, array) -> None:
        """Count a device array a removed shuffle lets go of (its received
        shard here, which the cluster holds) as ``released_device_bytes``."""
        nbytes = _device_nbytes(array)
        with self._lock:
            self._write_stats["released_device_bytes"] += nbytes

    def _rollover(self, st: _ShuffleState, peer: int) -> None:
        """Hand the completed staging epoch on and start the next round
        (caller holds self._lock); ``peer`` is the region that could not take
        the caller's block.

        While the store's round buffers fit its RAM budget
        (``conf.max_host_pool_bytes``; ``_admit_ram_round``) the round's
        buffer itself becomes the ``prev_rounds`` entry — no copy, no file —
        and the next round takes a buffer from the store's free list, which
        ``remove_shuffle`` gives a removed shuffle's round buffers back to
        (``ram_rounds``, ``pool_hits``); only a miss allocates, as the
        reference recycles its registered buffers (MemoryPool.scala:117-138).

        Past the budget, with ``conf.spill_to_disk`` (default), the completed
        round moves to an ``np.memmap`` file — the capacity-beyond-memory tier
        the reference gets from DPU-attached NVMe (NvkvHandler.scala:160-242);
        ``read_block``/``block_staging_view``/``seal`` serve spilled rounds
        through the memmap transparently — and the RAM buffer STAYS as the
        next round's staging: every used byte of it is in the memmap, so each
        region's used prefix is set back to zero on pages that are resident
        (``recycled_rounds``, ``zeroed_bytes``) and nothing is allocated.
        With the disk tier off every round stays in RAM, bounded by host
        memory alone.

        What every consumer relies on holds exactly on each arm: a round
        starts as all zeros (a buffer of the free list was zeroed when it was
        taken back, a new one is ``np.zeros``), so rows past a region's used
        count and the pad bytes of a block are zeros in whatever leaves the
        host.

        Receives in place (``MapWriter.reserve``): on the RAM arm a receive
        in flight ends in the completed round's buffer, which lives on in
        ``prev_rounds``; the disk arm first waits until none is in flight into
        the live round (``_await_receives``: the lock is released meanwhile,
        one integer compare where there is none) and then spills whatever is
        the live round by then.

        Span ``store.rollover`` (once a staging round); its child
        ``store.spill`` fires only on the disk arm, where the rollover's self
        time is the zeroing of the used prefixes; on the RAM arm it is
        bookkeeping."""
        # the job has turned multi-round: what was put of this round piece
        # by piece is let go, and a round is put whole once it is final
        self._drop_put_behind(st)
        with self._rollover_span(st, peer):
            staging, used = st.staging, st.region_used
            if self._admit_ram_round(staging.nbytes, reuse=True):
                st.prev_rounds.append((staging, used))
                self._ram_round_bytes += staging.nbytes
                self._queue_early_round(st)
                st.live_held = bool(self._free_rounds.get(staging.nbytes))  # what the take below hands out
                st.staging = self._take_round_buffer(staging.nbytes)
                self._write_stats["ram_rounds"] += 1
            else:
                # the spill reads the buffer and the reuse zeroes it: not under
                # a receive.  The lock is released while this waits, so the
                # shuffle may be gone and the round another by the end
                if self._await_receives(st, live_only=True):
                    if st.removed or st.sealed:
                        raise TransportError(
                            f"shuffle {st.shuffle_id} was "
                            f"{'removed' if st.removed else 'sealed'} during a rollover"
                        )
                    staging, used = st.staging, st.region_used
                st.prev_rounds.append((self._spill_round(st, staging), used))
                for p in np.flatnonzero(used):
                    start = int(p) * st.region_size
                    staging[start : start + int(used[p])] = 0
                self._write_stats["recycled_rounds"] += 1
                self._write_stats["zeroed_bytes"] += int(used.sum())
            st.region_used = np.zeros(len(used), dtype=used.dtype)
            st.round += 1

    # -- bytes on their way into a round outside the lock --------------------

    def _await_receives(self, st: _ShuffleState, live_only: bool = False) -> bool:
        """Return with no receive and no buffered copy in flight into any
        round of ``st`` (with ``live_only``: into its live round) —
        caller holds self._lock, which is RELEASED while this waits, so the
        state may have changed by the time it returns.  No new extent of the
        shuffle is taken meanwhile (``take_extent`` waits), and every one in
        flight ends: a receive runs under ``conf.wire_timeout_ms``, a copy is
        a ``memcpy``, and each gives its count back on every way out
        (``MapWriter.end_receive`` / ``close_partition``).  Nothing in flight is
        one compare.
        True when it waited: the caller then looks at the state again."""

        def pending():
            return st.inflight.get(st.round) if live_only else st.inflight

        if not pending():
            return False
        t0 = perf_counter_ns()
        st.draining += 1
        try:
            while pending():
                self._cond.wait(timeout=1.0)
        finally:
            st.draining -= 1
            self._cond.notify_all()
            self._write_stats["inflight_wait_ns"] += perf_counter_ns() - t0
        return True

    # -- the single round put behind the writers (``_PutBehind``) -----------

    def _await_quiet(self, st: _ShuffleState) -> bool:
        """Return with no receive in flight into any round of ``st`` and no
        owner putting pieces of its live round or its completed rounds — caller holds self._lock,
        RELEASED while this waits.  True when it waited: the caller then
        looks at the state again."""
        waited = False
        while self._await_receives(st) or self._await_put_owner(st):
            waited = True  # either wait may have let the other begin
        return waited

    def _await_put_owner(self, st: _ShuffleState) -> bool:
        """Wait the owner of ``st``'s update chain, and the owner putting its
        completed rounds (``_EarlyRounds``), out (caller holds self._lock,
        released meanwhile): it is inside a ``device_put``, an update or the
        wait for an older piece's transfer, and takes this lock to let go.
        No new reservation is admitted meanwhile."""
        owned = [o for o in (st.put_behind, st.early_rounds) if o is not None and o.owner]
        if not owned:
            return False
        st.draining += 1
        try:
            while any(o.owner for o in owned):
                self._cond.wait(timeout=1.0)
        finally:
            st.draining -= 1
            self._cond.notify_all()
        return True

    def _drop_put_behind(self, st: _ShuffleState):
        """Take the pieces put behind the writers off ``st`` (caller holds
        self._lock): the device buffer is let go here — and returned, for a
        removal to count — or by the owner when it comes back for the lock,
        and whatever seals the round now puts all of it."""
        behind, st.put_behind = st.put_behind, None
        if behind is None:
            return None
        if behind.buf is not None or behind.owner:
            self._write_stats["early_put_dropped"] += 1
        if behind.owner:
            return None
        buf = behind.buf
        behind.release()
        return buf

    def _claim_pieces(self, st: _ShuffleState, behind: _PutBehind):
        """The pieces of ``st``'s live round that hold their final bytes and
        nobody has put, claimed for the caller (caller holds self._lock):
        ``(piece indices, the round as rows)``, and ``behind.owner`` set;
        ``((), None)`` where another thread owns the chain, the state was
        taken off the shuffle, no piece is final (``_PutBehind.final_marks``:
        an extent whose bytes are still on their way — a copy in flight, a
        partition open between two frames — holds its region's pieces from
        its last whole row on; never the in-flight count, which with four
        writers is hardly ever zero), or the watermark gate refuses — no early put then, not a failed
        write: the seal puts the piece."""
        if st.put_behind is not behind or behind.owner:
            return (), None
        marks = behind.final_marks(st.region_used)
        final = [p for p, mark in enumerate(marks) if behind.next_end[p] <= mark]
        if not final:
            return (), None
        try:
            self._check_pressure_locked("piece_put", SEAL_PUT_PIECE_BYTES)
        except ResourceExhaustedError:
            return (), None
        pieces = []
        for p in final:
            while behind.next_end[p] <= marks[p]:
                pieces.append(behind.claim(p))
        behind.owner = True
        return pieces, st.staging.view(np.int32).reshape(-1, st.alignment // 4)

    def put_behind(self, st: _ShuffleState) -> None:
        """Put the pieces of ``st``'s live round that are final now, on the
        calling thread — a writer whose block passed a piece's end — as the
        one owner of the update chain: claimed under self._lock, put OUTSIDE
        it, and claimed again until none is left, so a writer that passes a
        piece meanwhile goes on copying.  A put the runtime refuses (the
        device is out of memory, or lost) costs the shuffle its early pieces,
        never the write: the seal puts the round whole and raises what is
        still wrong.  Any other error is this code's and goes up through the
        write; the chain is let go either way.

        Span ``store.piece_put``, once a piece: **the time the calls hold
        this thread** (the runtime's staging of the source, the update's
        dispatch, the wait for an older piece where two are in flight), NOT
        the DMA."""
        import jax

        behind = st.put_behind
        if behind is None:
            return self._put_early_rounds(st)
        with self._lock:
            pieces, payload = self._claim_pieces(st, behind)
        row_bytes = behind.alignment
        while pieces:
            put = nbytes = 0
            try:
                for k in pieces:
                    at = k * behind.piece_rows
                    piece_bytes = (min(at + behind.piece_rows, behind.rows) - at) * row_bytes
                    with span(
                        "store.piece_put", shuffle_id=st.shuffle_id, executor=self.executor_id,
                        at=at * row_bytes, bytes=piece_bytes,
                    ):
                        self._put_piece(behind, payload, at)
                    put += 1
                    nbytes += piece_bytes
            except jax.errors.JaxRuntimeError:
                logger.warning(
                    "shuffle %d: a piece put behind the writer failed; the seal puts the round",
                    st.shuffle_id, exc_info=True,
                )
            finally:
                with self._lock:
                    behind.owner = False
                    self._write_stats["early_put_pieces"] += put
                    self._write_stats["early_put_bytes"] += nbytes
                    if st.put_behind is not behind:  # dropped meanwhile: this owner lets go
                        behind.release()
                    elif put < len(pieces):  # the cursor is past a piece that was not put
                        self._drop_put_behind(st)
                    self._cond.notify_all()
            with self._lock:
                pieces, payload = self._claim_pieces(st, behind)

    def _put_piece(self, behind: _PutBehind, payload: np.ndarray, at: int) -> None:
        """One link of the update chain (its owner's call): the piece of
        ``payload`` that starts at row ``at`` onto ``self.device`` and into
        ``behind.buf``, which each update donates back (in place: HBM holds
        the round once and the pieces in flight)."""
        import jax
        import jax.numpy as jnp

        if behind.buf is None:
            behind.buf = jnp.zeros(payload.shape, dtype=jnp.int32, device=self.device)
        if len(behind.in_flight) == SEAL_PUT_PIECES_IN_FLIGHT:
            # the host runs ahead of the transfers: unchecked, every piece
            # would sit in HBM beside the round (3 GiB more at 4 GiB)
            behind.in_flight.popleft().block_until_ready()
        piece = jax.device_put(payload[at : at + behind.piece_rows], self.device)
        behind.buf = _update_rows_fn()(behind.buf, piece, np.int32(at))
        behind.in_flight.append(piece)

    # -- completed rounds put before the exchange (``_EarlyRounds``) ---------

    def _queue_early_round(self, st: _ShuffleState) -> None:
        """``st``'s live round has just rolled into ``prev_rounds`` on the RAM
        arm (caller holds self._lock, ``st.round`` is still its index): where
        this store has a device and the round's buffer came from the free
        list, the round waits for its put — ready at once, or when the last
        extent still open in it is settled (``receive_ended``)."""
        early = st.early_rounds
        if self.device is None or not st.live_held or (early is not None and early.closed):
            return
        if early is None:
            early = st.early_rounds = _EarlyRounds()
        if st.inflight.get(st.round):
            early.open.add(st.round)
        else:
            early.ready.append(st.round)

    def _claim_early_rounds(self, st: _ShuffleState, early: _EarlyRounds):
        """The rounds of ``st`` that are final and nobody has put, claimed
        for the caller (caller holds self._lock): ``[(round, the round as
        rows, its used bytes a region)]`` and ``early.owner`` set; ``[]``
        where another thread owns the puts, the shuffle is sealed or removed,
        nothing is ready, the copies the exchange has not taken would pass
        the RAM rounds' budget (with the disk tier off a shuffle's RAM rounds
        have no bound of their own: no round is put early from there on), or
        the watermark gate refuses — the rounds then stay queued, a later
        record asks again, and the exchange puts whatever was not put."""
        if early.owner or early.closed or not early.ready or st.sealed or st.removed:
            return []
        lane = st.alignment // 4
        claimed, nbytes, over_budget = [], 0, False
        for rnd in early.ready:
            staging, used = st.prev_rounds[rnd]
            if self._early_round_bytes + nbytes + staging.nbytes > self._ram_budget:
                over_budget = True
                break
            claimed.append((rnd, staging.view(np.int32).reshape(-1, lane), used))
            nbytes += staging.nbytes
        if claimed:
            try:
                self._check_pressure_locked("round_put", nbytes)
            except ResourceExhaustedError:
                return []
        if over_budget:
            # the budget comes back only when the exchange takes its copies:
            # what is queued now, and rolls later, is the exchange's to put
            early.closed = True
            early.ready.clear()
            early.open.clear()
        else:
            for _ in claimed:
                early.ready.popleft()
        if claimed:
            self._early_round_bytes += nbytes  # given back for a round that was not put
            early.owner = True
        return claimed

    def _put_early_rounds(self, st: _ShuffleState) -> None:
        """Put the completed rounds of ``st`` that are final now, on the
        calling thread — a writer whose record found one ready — as the one
        owner: claimed under self._lock, put OUTSIDE it, and claimed again
        until none is left.  A round the device has no room for beside what
        it holds (``memory_stats``, where the runtime gives one) is left to
        the exchange's put.  A put the runtime refuses is logged and costs
        the shuffle its early copies, never the write; any other error is
        this code's and goes up through the write, the copies let go either
        way.

        Span ``store.round_put``, once a round: **the time the call holds
        this thread** (the runtime's staging of the source), NOT the DMA."""
        import jax

        early = st.early_rounds
        if early is None:
            return
        with self._lock:
            claimed = self._claim_early_rounds(st, early)
        while claimed:
            put: Dict[int, object] = {}
            failed = True
            try:
                for rnd, payload, used in claimed:
                    if not self._device_has_room(int(payload.nbytes)):
                        continue
                    faults.check("store.round_put", shuffle_id=st.shuffle_id, round=rnd)
                    with span(
                        "store.round_put", shuffle_id=st.shuffle_id, executor=self.executor_id,
                        round=rnd, bytes=int(payload.nbytes),
                    ):
                        put[rnd], _ = self._put_round(payload, st.pieces(), used, st.alignment)
                failed = False
            except jax.errors.JaxRuntimeError:
                logger.warning(
                    "shuffle %d: a completed round's early put failed; the exchange puts its rounds",
                    st.shuffle_id, exc_info=True,
                )
            finally:
                with self._lock:
                    early.owner = False
                    nbytes = sum(int(a.nbytes) for a in put.values())
                    self._early_round_bytes -= sum(int(p.nbytes) for _, p, _ in claimed) - nbytes
                    self._write_stats["early_round_puts"] += len(put)
                    self._write_stats["early_round_bytes"] += nbytes
                    early.copies.update(put)
                    if failed:
                        self._release_early_rounds(st)
                    self._cond.notify_all()
            with self._lock:
                claimed = self._claim_early_rounds(st, early)

    def _device_has_room(self, nbytes: int) -> bool:
        """Whether ``nbytes`` more fit this store's device beside what is in
        use there, by the runtime's own count; True where it gives none (the
        CPU backend)."""
        stats = self.device.memory_stats() or {}
        limit = stats.get("bytes_limit")
        return not limit or stats.get("bytes_in_use", 0) + nbytes <= limit

    def take_early_round(self, shuffle_id: int, round_idx: int):
        """The copy of sealed round ``round_idx`` that was put on this
        store's device before the seal, handed over — the exchange donates
        it and puts nothing for the round — or None: the round was not put
        early, or its copy was let go."""
        with self._lock:
            st = self._shuffles.get(shuffle_id)
            early = st.early_rounds if st is not None else None
            array = early.copies.pop(round_idx, None) if early is not None else None
            if array is not None:
                self._early_round_bytes -= int(array.nbytes)
            return array

    def release_early_rounds(self, shuffle_id: int) -> None:
        """Let go of every early copy of the shuffle's rounds that the
        exchange has not taken (a plan whose window is not the staging slot,
        an aborted exchange): counted in ``early_rounds_dropped`` and
        ``released_device_bytes``; the host rounds are what they were."""
        with self._lock:
            st = self._shuffles.get(shuffle_id)
            if st is not None:
                self._release_early_rounds(st)

    def _release_early_rounds(self, st: _ShuffleState) -> None:
        """Close ``st``'s early rounds and let their copies go (caller holds
        self._lock; no owner is putting but the caller)."""
        early = st.early_rounds
        if early is None:
            return
        early.closed = True
        early.open.clear()
        early.ready.clear()
        nbytes = sum(int(a.nbytes) for a in early.copies.values())
        self._early_round_bytes -= nbytes
        self._write_stats["early_rounds_dropped"] += len(early.copies)
        self._write_stats["released_device_bytes"] += nbytes
        early.copies.clear()

    # -- RAM rounds and the free list of round buffers ---------------------

    def _admit_ram_round(self, nbytes: int, reuse: bool) -> bool:
        """Whether a completed round whose buffer is ``nbytes`` stays in RAM
        (caller holds self._lock).  With the disk tier off: always (bounded by
        host memory, as ever).  With it on: while the RAM rounds of live
        shuffles and the free list, this round counted, fit ``_ram_budget`` —
        with ``reuse`` the next round's buffer leaves the free list if one of
        this size is there; otherwise the free list (buffers of other sizes)
        is let go before a live round is sent to disk — and, where a watermark
        is set, while
        the next round, full, and one more block of its size would not cross
        it: that is what ``check_memory_pressure`` sees before the next
        rollover, and an unsealed shuffle's RAM rounds are not the eviction
        manager's to demote, so nothing else would bring the pressure down
        again."""
        if not self.conf.spill_to_disk:
            return True
        if self._ram_round_bytes + nbytes > self._ram_budget:
            return False
        marks = [w for w in (self.conf.store_soft_watermark, self.conf.store_hard_watermark) if w > 0]
        if marks and self._pressure_locked() + 2 * nbytes > min(marks):
            return False
        held = self._write_stats["pool_held_bytes"]
        if not (reuse and self._free_rounds.get(nbytes)) and (
            self._ram_round_bytes + held + nbytes > self._ram_budget
        ):
            self._free_rounds.clear()
            self._write_stats["pool_held_bytes"] = 0
        return True

    def _take_staging(self, shuffle_id: int, nbytes: int) -> np.ndarray:
        """A shuffle's staging round at its first touch (``_ShuffleState
        .staging``; caller holds self._lock; never shm staging, which is
        handed to the state whole).  Here the store knows what the round will
        be written into, and a store with a device puts it behind its writers
        (``_PutBehind``) only where that is a buffer of the free list — pages
        the process holds: every job of a long-lived executor but its first.
        A fresh buffer's write is the first touch of its pages, which a put
        gains nothing on — on the chip's host it cost that job 1.2 s (PERF.md
        section 6, PR 51).  The rest of ``seal``'s ``device_put_here`` — host
        writes, one round — is known only later: a rollover drops the state,
        a device-staged shuffle never comes here."""
        held = bool(self._free_rounds.get(nbytes))  # what the take below hands out
        buf = self._take_round_buffer(nbytes)
        st = self._shuffles.get(shuffle_id)
        if held and st is not None:
            st.live_held = True
            if self.device is not None:
                st.put_behind = st.pieces()
        return buf

    def _take_round_buffer(self, nbytes: int) -> np.ndarray:
        """An all-zero uint8 buffer for a staging round (caller holds
        self._lock, as every path that touches a shuffle's lazy ``staging``
        does): from the free list when it holds one of this size (pages the
        process already holds), else ``np.zeros`` (calloc: untouched pages)."""
        free = self._free_rounds.get(nbytes)
        if free:
            self._write_stats["pool_hits"] += 1
            self._write_stats["pool_held_bytes"] -= nbytes
            return free.pop()
        self._write_stats["pool_misses"] += 1
        # fresh pages: the copies that touch them first pay the faults
        # (``write.task``'s ``minor_faults``), not this call
        with span("store.round_buffer.fresh", executor=self.executor_id, bytes=nbytes):
            return np.zeros(nbytes, dtype=np.uint8)

    def _detach_host_rounds(self, st: _ShuffleState) -> List[Tuple[np.ndarray, np.ndarray]]:
        """Take a removed shuffle's private host round buffers out of its
        state (caller holds self._lock): every RAM round of ``prev_rounds``
        (its entry keeps the used counts and loses the buffer) and the live
        staging.  Returns them as ``(buffer, region_used)`` for
        ``_recycle_rounds``.  Disk-tier entries stay (``_release_spill``), shm
        staging is only dropped (its closer unmaps it)."""
        rounds = []
        for i, (snap, used) in enumerate(st.prev_rounds):
            if snap is not None and not isinstance(snap, np.memmap):
                self._ram_round_bytes -= snap.nbytes
                st.prev_rounds[i] = (None, used)
                rounds.append((snap, used))
        live, st.staging = st.staging, None  # ``removed`` is latched: the property allocates nothing
        if live is not None and st.staging_closer is None and not isinstance(live, np.memmap):
            rounds.append((live, st.region_used))
        return rounds

    def _floor_keeps(self, nbytes: int, regions: int) -> bool:
        """Whether the free list's floor keeps a removed shuffle's buffer that
        the budget has no room for (caller holds self._lock): it is exactly
        one staging round of this store's conf over ``regions`` peers — memory
        the operator granted to every live shuffle of the executor — no larger
        than ``_floor_limit``, and it would be the only thing the RAM tier
        holds: no other free buffer, no RAM round of a live shuffle."""
        align = self.conf.block_alignment
        own = (self.conf.staging_capacity_per_executor // regions) // align * align * regions
        return (
            nbytes == own
            and nbytes <= self._floor_limit
            and not self._ram_round_bytes
            and not self._write_stats["pool_held_bytes"]
        )

    def _recycle_rounds(
        self, rounds: List[Tuple[np.ndarray, np.ndarray]], floor: bool = False
    ) -> None:
        """Give round buffers that no state holds any more to the free list
        (caller holds self._lock, and NO other name for any of the buffers;
        ``rounds`` is emptied).  Decided from what the store observes of each:

        * only a buffer that owns its memory is kept (a D2H snapshot of a
          device round is a view of the runtime's copy; shm staging never
          gets here);
        * only while the free list and the RAM rounds fit ``_ram_budget`` —
          but with ``floor`` (a removal) an empty free list keeps ONE buffer
          of the store's own staging size although it is over the budget
          (``_floor_keeps``, ``pool_kept_over_budget``): a one-round 4 GiB
          job then writes into pages the process holds, job after job; any
          other buffer over the budget is released as ever;
        * a buffer something else still refers to — a ``block_staging_view``,
          a sealed round in the exchange's hands, a ``jax.device_put`` that
          aliases the host array on the CPU backend — is NEVER taken: it is
          left to the collector as ever, and counted (``pool_dropped_busy``).
          A view of an owner array holds the owner as its ``base``, so the
          owner's reference count sees every such holder.

        A buffer taken has each region's used prefix set back to zero here, on
        pages that are resident: the free list holds only all-zero buffers."""
        stats = self._write_stats
        collected = False
        while rounds:
            buf, used = rounds.pop()
            nbytes = buf.nbytes
            if not buf.flags.owndata:
                continue
            over = self._ram_round_bytes + stats["pool_held_bytes"] + nbytes > self._ram_budget
            if over and not (floor and self._floor_keeps(nbytes, len(used))):
                continue
            # (2 = only ``buf`` + getrefcount's argument, as in core/block.py)
            if sys.getrefcount(buf) > 2 and not collected:
                # The runtime parks the reference it held on the host source
                # of a finished transfer until its own next call or the next
                # collection (jax issue 14882: a callback of ``gc``).  A
                # young-generation pass makes it let go of what it is done
                # with; what it still needs stays referenced.
                collected = True
                gc.collect(0)
            if sys.getrefcount(buf) > 2:
                stats["pool_dropped_busy"] += 1
                continue
            region = nbytes // len(used)
            for p in np.flatnonzero(used):
                start = int(p) * region
                buf[start : start + int(used[p])] = 0
            self._free_rounds.setdefault(nbytes, []).append(buf)
            stats["pool_held_bytes"] += nbytes
            if over:
                stats["pool_kept_over_budget"] += 1

    @contextmanager
    def _rollover_span(self, st: _ShuffleState, peer: int):
        """Span ``store.rollover`` and the ``rollovers`` / ``rollover_ns`` /
        ``rollover_tail_bytes`` counters round one rollover's body
        (caller holds self._lock).  The tail is what region ``peer``, whose
        overflow rolls the round, had free: a round rolls when ONE region
        cannot take the next block, so it rolls with up to a block's bytes
        unused there — little under level blocks, up to the largest block
        under skewed ones."""
        t0 = perf_counter_ns()
        tail = st.region_size - int(st.region_used[peer])
        with span(
            "store.rollover", shuffle_id=st.shuffle_id, round=st.round,
            executor=self.executor_id, bytes=int(st.region_used.sum()), tail_bytes=tail,
        ):
            yield
        self._write_stats["rollovers"] += 1
        self._write_stats["rollover_tail_bytes"] += tail
        self._write_stats["rollover_ns"] += perf_counter_ns() - t0

    def _rollover_device(self, st: _ShuffleState, peer: int) -> None:
        """Device-round analogue of ``_rollover``: pull the full device
        staging array D2H ONCE as the round snapshot (the spill boundary is
        where a host copy is unavoidable — HBM cannot hold every round), let
        it go, and continue in a fresh device round, whose array the next
        device write makes (caller holds self._lock).  The lazy host staging
        buffer stays unallocated.  The snapshot stays in RAM or goes to the
        disk tier by ``_rollover``'s own decision (``_admit_ram_round``); it
        is the runtime's copy, so nothing of it is taken from or given to the
        free list.  Same ``store.rollover`` span and counters as
        ``_rollover``; its self time here is the wait for the scatters and
        the D2H."""
        with self._rollover_span(st, peer):
            snap = np.asarray(self._device_round(st)).reshape(-1).view(np.uint8)
            st.device_staging = None
            if self._admit_ram_round(snap.nbytes, reuse=False):
                self._ram_round_bytes += snap.nbytes
                self._write_stats["ram_rounds"] += 1
            else:
                snap = self._spill_round(st, snap)
            st.prev_rounds.append((snap, st.region_used))
            st.region_used = np.zeros_like(st.region_used)
            st.round += 1

    def _spill_round(
        self,
        st: _ShuffleState,
        staging: np.ndarray,
        round_idx: Optional[int] = None,
        region_used: Optional[np.ndarray] = None,
    ) -> np.ndarray:
        """Write one round's staging to the disk tier; returns the memmap that
        replaces the RAM snapshot (caller holds self._lock).

        The file is logically full-capacity (so block offsets are unchanged)
        but only each region's used prefix is written — the rest stays a sparse
        hole, so disk writes and the spillDiskCap budget are proportional to
        bytes actually staged, not to stagingCapacity.

        Defaults spill the LIVE round (rollover); the eviction manager passes
        ``round_idx``/``region_used`` to demote an already-completed round.
        Span ``store.spill`` covers file create + region copies + flush."""
        import os
        import tempfile

        if round_idx is None:
            round_idx = st.round
        if region_used is None:
            region_used = st.region_used
        if self._spill_dir is None:
            if self.conf.spill_dir is not None:
                os.makedirs(self.conf.spill_dir, exist_ok=True)
            self._spill_dir = tempfile.mkdtemp(
                prefix=f"sparkucx_tpu_spill_e{self.executor_id}_",
                dir=self.conf.spill_dir,
            )
        cap = self.conf.spill_disk_cap_bytes
        nbytes = int(region_used.sum())
        if cap and self._spill_bytes + nbytes > cap:
            raise TransportError(
                f"disk spill cap exceeded: {self._spill_bytes} B spilled + "
                f"{nbytes} B round > spillDiskCap {cap} B"
            )
        path = os.path.join(self._spill_dir, f"s{st.shuffle_id}_r{round_idx}.bin")
        t0 = perf_counter_ns()
        with span(
            "store.spill", shuffle_id=st.shuffle_id, round=round_idx,
            executor=self.executor_id, bytes=nbytes,
        ):
            mm = np.memmap(path, dtype=np.uint8, mode="w+", shape=staging.shape)
            for p in range(len(st.peer_ranges)):
                used = int(region_used[p])
                if used:
                    start = p * st.region_size
                    mm[start : start + used] = staging[start : start + used]
            mm.flush()
        st.spill_files.append((path, nbytes))
        self._spill_bytes += nbytes
        self._write_stats["spilled_bytes"] += nbytes
        self._write_stats["spill_ns"] += perf_counter_ns() - t0
        return mm

    def _unspill_file(self, st: _ShuffleState, path: str) -> None:
        """Drop one spill file after its round restaged to RAM (caller holds
        self._lock): unlink, return its budget, forget the bookkeeping entry.
        A later re-demotion simply recreates the file."""
        import os

        for i, (p, nbytes) in enumerate(st.spill_files):
            if p == path:
                self._spill_bytes -= nbytes
                del st.spill_files[i]
                break
        try:
            os.unlink(path)
        except OSError:
            pass

    def _release_spill(self, st: _ShuffleState) -> None:
        """Unlink a removed shuffle's spill files (caller holds self._lock).

        The state object is deliberately NOT mutated: a reader that resolved
        the state before removal keeps serving correct bytes — open memmaps
        stay readable after unlink (the inode lives until the mapping drops),
        and GC reclaims everything once in-flight readers finish."""
        import os

        for path, nbytes in st.spill_files:
            self._spill_bytes -= nbytes
            try:
                os.unlink(path)
            except OSError:
                pass
        st.spill_files = []
        if self._spill_dir is not None and not any(
            s.spill_files for s in self._shuffles.values()
        ):
            try:
                os.rmdir(self._spill_dir)
            except OSError:
                pass  # non-empty (foreign files) or already gone
            else:
                self._spill_dir = None

    # -- device staging rounds (conf.device_staging) -----------------------

    def _scatter_fn(self, num_blocks: int, max_rows: int, out_rows: int):
        """Compiled block scatter ``fn(plan, src, dst) -> dst'`` for the
        staging geometry, pow2-bucketed on batch size and largest-block window
        so map tasks of nearby sizes reuse a handful of compiles (the
        exchange's ``_gather_fn`` discipline; the executable still specializes
        on ``src``'s row count, so a producer that wants ONE executable hands
        over buffers of one size).  ``plan`` is the ``(3, B)`` int32 host
        array of ``(staging row, rows, source row)`` a block, an argument of
        the one dispatch; ``dst`` is donated on the chip.  Returns ``(fn,
        bucketed_num_blocks)``; callers pad the plan to the bucket with
        zero-count entries.  Caller holds ``self._lock`` (its one call site is
        ``_stage_device``)."""
        b = max(1 << max(num_blocks - 1, 0).bit_length(), 1)
        w = max(1 << max(max_rows - 1, 0).bit_length(), 1)
        key = (b, w, out_rows)
        fn = self._scatter_cache.get(key)
        if fn is None:
            import jax

            from sparkucx_tpu.ops.pallas_kernels import build_block_scatter

            scatter = build_block_scatter(b, out_rows, max_block_rows=w)

            def block_scatter(plan, src, dst):
                return scatter(plan[0], plan[1], plan[2], src, dst)

            # the executable stays jit_block_scatter; dst is donated where
            # build_block_scatter donates it (the in-place append)
            donate = (2,) if jax.devices()[0].platform == "tpu" else ()
            fn = jax.jit(block_scatter, donate_argnums=donate)
            fn.impl = scatter.impl
            self._scatter_cache[key] = fn
        return fn, b

    def scatter_lowerings(self) -> List[str]:
        """``fn.impl`` of every block scatter this store has compiled."""
        with self._lock:
            return [fn.impl for fn in self._scatter_cache.values()]

    def _device_round(self, st: _ShuffleState):
        """The live device round's staging array, made as zeros on this
        executor's device (never staged through device 0) where no device
        write has made it yet (caller holds self._lock)."""
        if st.device_staging is None:
            import jax.numpy as jnp

            total_rows = len(st.peer_ranges) * (st.region_size // st.alignment)
            st.device_staging = jnp.zeros(
                (total_rows, st.alignment // 4), dtype=jnp.int32, device=self.device
            )
        return st.device_staging

    def _stage_device(self, st: _ShuffleState, src, run: List[Tuple[int, int, int, int]]) -> None:
        """Place ``run`` — ``(staging row, rows, source row, bytes)`` a block
        — out of the producer's packed array ``src`` into the live device
        round by ONE block-scatter dispatch (caller holds self._lock).  No
        host byte moves and no copy of ``src`` is made: it is the kernel's
        source as it stands, the staging array its donated destination.

        Span ``store.device_stage`` and ``device_stage_ns``, once a dispatch:
        **the time the call holds the writer's thread** (the plan, the
        dispatch; the first of a round also the zero fill's), NOT the DMA,
        which is asynchronous."""
        if not run:
            return
        import jax

        t0 = perf_counter_ns()
        nbytes = sum(block[3] for block in run)
        with span(
            "store.device_stage", shuffle_id=st.shuffle_id,
            executor=self.executor_id, blocks=len(run), bytes=nbytes,
        ):
            dst = self._device_round(st)
            fn, b = self._scatter_fn(len(run), max(block[1] for block in run), int(dst.shape[0]))
            # zero-count entries pad the plan to its bucket
            plan = np.zeros((3, b), dtype=np.int32)
            plan[:, : len(run)] = np.asarray(run, dtype=np.int64)[:, :3].T
            if self.device is not None:
                src = jax.device_put(src, self.device)  # where it already is: as it is
            st.device_staging = fn(plan, src, dst)
        stats = self._write_stats
        stats["scatter_dispatches"] += 1
        stats["device_staged_blocks"] += len(run)
        stats["device_staged_bytes"] += nbytes
        stats["device_stage_ns"] += perf_counter_ns() - t0

    # -- write path --------------------------------------------------------

    def map_writer(self, shuffle_id: int, map_id: int) -> MapWriter:
        st = self._state(shuffle_id)
        if st.sealed:
            raise TransportError(f"shuffle {shuffle_id} already sealed")
        if not (0 <= map_id < st.num_mappers):
            raise ValueError(f"map_id {map_id} out of range [0, {st.num_mappers})")
        with self._lock:
            discard = map_id in st.committed_maps  # first commit wins (task retry)
            st.open_writers += not discard  # until its ``commit``
        return MapWriter(self, st, map_id, discard=discard)

    # -- a block's three steps: the buffered close and the receive in place --
    # -- (``store/writer.py``) make them; callers hold ``lock`` -------------

    def take_extent(
        self, st: _ShuffleState, reduce_id: int, padded: int,
        resv: Optional[_Reservation], hold: bool,
    ) -> Optional[Tuple[np.ndarray, int, Optional[_Reservation]]]:
        """Everything a block of partition ``reduce_id`` needs under the
        store's lock before a byte of it moves (caller holds the lock): no
        waiter is draining the shuffle, the removed / sealed / device-mode
        checks, the tenant charge (an over-quota write fails typed with
        nothing allocated, rolled over or copied), the rollover when the
        region cannot take the block, the first touch of the staging round
        and the region allocate — ``region_used`` moves by ``padded`` bytes,
        or by what ``padded`` adds to ``resv``, the extent the writer's open
        partition already holds (a further frame of a receive in place).
        ``(staging, start, resv)``: the live round's buffer, the extent's
        absolute offset in it and its reservation.

        With ``hold`` the caller fills the extent OUTSIDE the lock: it
        becomes a ``_Reservation`` of the round it was made in (joining
        ``put_behind.open`` where the round is put behind its writers) and
        the round's in-flight count is taken, to be given back by
        ``receive_ended``; without, ``resv`` comes back None.  None when
        ``resv`` cannot grow in place, and nothing was changed: the partition
        goes back to the buffered path (``MapWriter._unreserve``)."""
        while st.draining:  # a waiter of ``_await_receives`` drains the shuffle: the lock is released meanwhile
            self._cond.wait(timeout=1.0)
        if st.removed:
            raise TransportError(f"unknown shuffle {st.shuffle_id}")
        if st.sealed:
            # a writer opened before the seal: the sealed rounds are immutable
            # (zero-copy views, the runtime's H2D source), and a rollover here
            # would zero the buffer they alias
            raise TransportError(f"shuffle {st.shuffle_id} already sealed")
        if st.device_mode:
            raise TransportError(
                f"shuffle {st.shuffle_id} already has device-staged rounds — "
                "host and device writes cannot mix"
            )
        peer = st.owner_of(reduce_id)
        base = peer * st.region_size
        used = int(st.region_used[peer])
        grow = padded
        if resv is not None:
            grow -= resv.padded
            if not (
                resv.round == st.round
                and resv.start + resv.padded == base + used
                and used + grow <= st.region_size
            ):
                return None
        st.device_mode = False
        self._charge_tenant(st, grow)  #: balanced by _release_tenant
        try:
            # a rollover may wait, lock released, for copies in flight: look again
            while resv is None and used + padded > st.region_size:
                self._refuse_shm_overflow(st)
                self._rollover(st, peer)
                used = int(st.region_used[peer])
        except BaseException:
            self._release_tenant(st, grow)
            raise
        staging = st.staging  # its first touch says whether the round is put behind its writers
        if resv is not None:
            start = resv.start
            resv.padded = padded
        else:
            start = base + used
            if hold:
                resv = _Reservation(st.round, start, padded)
                if st.put_behind is not None:
                    st.put_behind.open.add(resv)  # until ``_ShuffleState.settled``
        st.region_used[peer] = used + grow
        if hold:
            st.inflight[resv.round] = st.inflight.get(resv.round, 0) + 1
        return staging, start, resv

    def record_extent(
        self, st: _ShuffleState, key: Tuple[int, int], length: int,
        start: int, padded: int, round_idx: int, resv: Optional[_Reservation],
    ) -> bool:
        """The table record of block ``key`` = (map, reduce), whose last byte
        is in its extent (caller holds the store's lock): the entry names the
        extent and the round it was taken in, and the extent no longer holds
        a put cursor.  True when the record took its region's final mark past
        the end of a piece to put (``_PutBehind``), or finds a completed round
        final and waiting for its put (``_EarlyRounds``): the caller then
        calls ``put_behind`` outside the lock."""
        st.blocks[key] = _BlockEntry(offset=start, length=length, padded=padded, round=round_idx)
        if resv is not None:
            st.settled(resv)
        behind = st.put_behind
        if behind is None:
            early = st.early_rounds
            return early is not None and bool(early.ready) and not early.owner
        p = start // st.region_size
        end = behind.next_end[p]
        # the used prefix has to be past the piece's end before an extent still open can matter
        return p * st.region_size + int(st.region_used[p]) >= end and behind.final_marks(st.region_used)[p] >= end

    def take_piece(
        self, st: _ShuffleState, reduce_id: int, left: int, first: bool, hold: bool,
    ) -> Tuple[np.ndarray, int, int, int, int, Optional[_Reservation]]:
        """The next piece of a block of partition ``reduce_id`` that is longer
        than a peer region, ``left`` padded bytes of it still without a place
        (caller holds the lock): ``take_extent``'s checks, then the room the
        block's region has in the live round — a full region first rolls the
        round — up to ``left``.  ``(staging, start, taken bytes, the piece's
        round, rollovers this piece forced, resv)``.  Every piece but a
        block's last ends its region, so only the last is padded.

        ``first``: the block's whole padded length (``left``) is charged to
        its tenant here, before any piece has a place — an over-quota block
        fails typed with nothing allocated, rolled over or copied; the charge
        of a block that fails later is given back by ``lose_extent``, and the
        pieces it had placed stay holes that no entry names.  ``hold`` as in
        ``take_extent``: the piece is filled outside the lock, its round's
        in-flight count taken until ``receive_ended``."""
        # ``take_extent``'s checks, which it keeps inline (a call a block)
        while st.draining:
            self._cond.wait(timeout=1.0)
        if st.removed:
            raise TransportError(f"unknown shuffle {st.shuffle_id}")
        if st.sealed:
            raise TransportError(f"shuffle {st.shuffle_id} already sealed")
        if st.device_mode:
            raise TransportError(
                f"shuffle {st.shuffle_id} already has device-staged rounds — "
                "host and device writes cannot mix"
            )
        peer = st.owner_of(reduce_id)
        st.device_mode = False
        if first:
            self._charge_tenant(st, left)  #: balanced by _release_tenant
        rolled = 0
        try:
            # a rollover may wait, lock released, for copies in flight: look again
            while int(st.region_used[peer]) >= st.region_size:
                self._refuse_shm_overflow(st)
                self._rollover(st, peer)
                rolled += 1
        except BaseException:
            if first:
                self._release_tenant(st, left)
            raise
        staging = st.staging
        used = int(st.region_used[peer])
        taken = min(left, st.region_size - used)
        start = peer * st.region_size + used
        resv = None
        if hold:
            resv = _Reservation(st.round, start, taken)
            if st.put_behind is not None:
                st.put_behind.open.add(resv)  # until ``_ShuffleState.settled``
            st.inflight[resv.round] = st.inflight.get(resv.round, 0) + 1
        st.region_used[peer] = used + taken
        return staging, start, taken, st.round, rolled, resv

    def record_pieces(
        self, st: _ShuffleState, key: Tuple[int, int], length: int, padded: int,
        pieces: Sequence[Tuple[int, int, int]], rollovers: int,
    ) -> bool:
        """The table record of block ``key`` = (map, reduce), staged as
        ``pieces`` of ``(round, offset, length)`` whose last byte is in place
        (caller holds the lock): ONE entry that names them in order.  True
        where a completed round is final and waits for its put, as
        ``record_extent`` answers (a block's pieces rolled every round but
        its last: none is put behind its writers)."""
        rnd, offset, _ = pieces[0]
        st.blocks[key] = _BlockEntry(
            offset=offset, length=length, padded=padded, round=rnd, pieces=tuple(pieces)
        )
        counters = self._write_stats
        counters["split_blocks"] += 1
        counters["split_pieces"] += len(pieces)
        counters["split_bytes"] += length
        counters["split_rollovers"] += rollovers
        early = st.early_rounds
        return early is not None and bool(early.ready) and not early.owner

    def lose_extent(self, st: _ShuffleState, padded: int, resv: Optional[_Reservation]) -> None:
        """A partition's extent of ``padded`` bytes will never be recorded: its
        bytes did not fully arrive, or it went back to the buffered path
        (caller holds the store's lock).  It stays a hole that no entry names,
        its tenant charge is given back and it holds no put cursor."""
        self._release_tenant(st, padded)
        if resv is not None:
            st.settled(resv)

    def extent_received(self, st: _ShuffleState, resv: _Reservation) -> np.ndarray:
        """What was received into ``resv`` so far, where it lies in the round
        the extent was made in (caller holds the store's lock)."""
        staging = st.staging if resv.round == st.round else st.prev_rounds[resv.round][0]
        return staging[resv.start : resv.start + resv.filled]

    def receive_ended(self, st: _ShuffleState, resv: _Reservation, received: int) -> None:
        """The bytes on their way into ``resv`` outside the lock have ended,
        ``received`` more of them there (a buffered copy, a body cut short:
        0): its round's in-flight count is given back (caller holds the lock)."""
        resv.filled += received
        left = st.inflight[resv.round] - 1
        if left:
            st.inflight[resv.round] = left
        else:
            del st.inflight[resv.round]
            early = st.early_rounds
            if early is not None and resv.round in early.open:
                # the last extent open in a completed round: it is final now
                early.open.discard(resv.round)
                early.ready.append(resv.round)
            if st.draining:
                self._cond.notify_all()

    def _refuse_shm_overflow(self, st: _ShuffleState) -> None:
        """A region of shm staging is full: there is no next round."""
        if st.staging_closer is not None:
            raise TransportError(
                "region overflow with shm staging — multi-round spill "
                "requires private staging; raise stagingCapacity"
            )

    def place_device_blocks(self, st: _ShuffleState, map_id: int, packed, blocks, total: int) -> None:
        """A map task's device-path write, validated by its caller
        (``MapWriter.write_partitions_device``): ``blocks`` of ``(reduce_id,
        peer, length, rows)`` lie back to back in ``packed``, ``total`` padded
        bytes.  Under the lock: the checks, the tenant charge, a table entry
        a block, one scatter dispatch a staging round touched."""
        self.check_memory_pressure("write_partition_device", total)
        align = st.alignment
        with self._lock:
            if st.sealed:
                raise TransportError(f"shuffle {st.shuffle_id} already sealed")
            if st.device_mode is False:
                raise TransportError(
                    f"shuffle {st.shuffle_id} already has host-staged blocks — "
                    "host and device writes cannot mix"
                )
            st.device_mode = True
            self._charge_tenant(st, total)  #: balanced by _release_tenant
            # (staging row, rows, source row, bytes) of the blocks bound
            # for the live round; dispatched when it rolls and at the end
            run: List[Tuple[int, int, int, int]] = []
            src_row = 0
            for reduce_id, peer, length, rows in blocks:
                padded = rows * align
                if int(st.region_used[peer]) + padded > st.region_size:
                    # what is recorded so far is placed before anything
                    # can raise: the table never names an unplaced block
                    self._stage_device(st, packed, run)
                    run = []
                    self._refuse_shm_overflow(st)
                    self._rollover_device(st, peer)
                start = peer * st.region_size + int(st.region_used[peer])
                if rows:
                    run.append((start // align, rows, src_row, length))
                st.blocks[(map_id, reduce_id)] = _BlockEntry(
                    offset=start, length=length, padded=padded, round=st.round
                )
                st.region_used[peer] += padded
                src_row += rows
            self._stage_device(st, packed, run)

    def commit_map(
        self, st: _ShuffleState, map_id: int, adds: Optional[Dict[str, int]], largest: int
    ) -> None:
        """Map ``map_id`` is committed.  ``adds``, once a writer (None for a
        retry that discards and a second ``commit``): the writer is no
        longer open, and what it counted joins the write counters by name,
        ``largest`` (its longest block) the gauge ``largest_block_bytes``."""
        with self._lock:
            st.committed_maps.add(map_id)
            if adds is not None:
                st.open_writers -= 1
                counters = self._write_stats
                for name, n in adds.items():
                    counters[name] += n
                counters["largest_block_bytes"] = max(counters["largest_block_bytes"], largest)

    def apply_mapper_info(self, info: MapperInfo) -> None:
        """Install commit metadata received from a peer process (AM id 2 inbound —
        what the DPU daemon does with MapperInfo).  Commits for a shuffle this
        process hasn't created yet are queued and applied at creation."""
        with self._lock:
            if info.shuffle_id not in self._shuffles:
                self._pending_infos.setdefault(info.shuffle_id, []).append(info)
                return
        st = self._state(info.shuffle_id)
        with self._lock:
            splits = info.splits or {}
            for r, (off, ln) in enumerate(info.partitions):
                if ln:
                    padded = -(-ln // st.alignment) * st.alignment
                    st.blocks[(info.map_id, r)] = _BlockEntry(
                        off, ln, padded, info.round_of(r), local=False, pieces=splits.get(r)
                    )
            st.committed_maps.add(info.map_id)

    # -- seal + exchange hand-off -----------------------------------------

    def seal(self, shuffle_id: int):
        """Freeze the staging area and stage it into device HBM.

        Returns a list with one ``(payload, send_sizes)`` entry per staging
        round (a single entry in the common no-spill case) — payload is that
        round's slot-layout staging buffer shaped ``(total_rows, lane)`` int32
        where one row is ``alignment`` bytes (the exchange's wire unit; a
        ``jax.Array`` on ``self.device`` when set, else host ndarray);
        ``send_sizes[p]`` is the used row count of peer p's region (the round's
        exchange size-matrix row).

        The sealed payloads must stay valid until ``remove_shuffle``: the
        quota-capped exchange (ops/skew.py, conf.slot_quota_rows) slices chunk
        windows out of them across multiple pipelined sub-rounds, and the pull
        fallback reads blocks from them after the exchange.
        """
        st = self._state(shuffle_id)
        with self._lock:
            if st.removed:  # resolved before a removal that took its rounds
                raise TransportError(f"unknown shuffle {shuffle_id}")
            if st.sealed:
                raise TransportError(f"shuffle {shuffle_id} already sealed")
            # the rounds are handed on as they are: not under a receive, and
            # the update chain of a round put behind its writers has one owner
            if self._await_quiet(st) and (st.removed or st.sealed):  # the lock was released
                raise TransportError(f"shuffle {shuffle_id} was removed or sealed during its seal")
            lane = st.alignment // 4
            out = []
            # Staging (completed rounds) stays host-resident until
            # remove_shuffle — it is the shuffle's backing store, the same
            # retention contract as Spark's map-output files on disk.  The
            # single-round common case seals straight to device; a
            # multi-round shuffle's payloads are handed on as host rounds,
            # and the exchange takes each one's copy from the device where
            # the store put it there when it became final (``_EarlyRounds``:
            # at most ``_ram_budget`` of HBM a store) and puts it itself
            # where not.
            device_put_here = self.device is not None and not st.prev_rounds
            for staging, used in st.prev_rounds:
                payload = staging.view(np.int32).reshape(-1, lane)
                out.append((payload, (used // st.alignment).astype(np.int32)))
            final_sizes = (st.region_used // st.alignment).astype(np.int32)
            if st.device_mode:
                # Device write path: the final round seals as the HBM-resident
                # staging array the scatters filled — zero device_put, zero
                # host staging, nothing left to do here but hand it over.
                payload, st.device_staging = self._device_round(st), None
            else:
                payload = st.staging.view(np.int32).reshape(-1, lane)
                if device_put_here:
                    # the time the calls hold this thread (the runtime's
                    # staging of the source), NOT the DMA: the transfer is
                    # asynchronous and shows as the exchange drain's wait
                    with span(
                        "store.seal_put", shuffle_id=shuffle_id,
                        executor=self.executor_id, bytes=int(payload.nbytes),
                    ):
                        payload, pieces = self._put_round(
                            payload, st.put_behind or st.pieces(), st.region_used, st.alignment
                        )
                        self._write_stats["seal_put_pieces"] += pieces
            st.put_behind = None  # ``_put_round`` has handed its buffer over, or it never began
            out.append((payload, final_sizes))
            st.sealed_payload = [p for p, _ in out]
        # Replication hook, outside the lock: the sealed rounds are now
        # immutable, so the background replicator can snapshot them safely.
        cb = self.on_seal
        if cb is not None:
            cb(shuffle_id)
        return out

    def _put_round(
        self, payload: np.ndarray, behind: Optional[_PutBehind], region_used: np.ndarray, alignment: int
    ):
        """A whole round onto ``self.device``: the single round at its seal
        (caller holds self._lock and has waited the owner of ``behind``, the
        shuffle's ``put_behind``, out) or a completed round put before the
        exchange (``_put_early_rounds``: ``behind`` fresh, its owner's call
        outside the lock).  ``(the round on the device, pieces put)``.  A
        round of up to ``SEAL_PUT_PIECE_BYTES`` — ``behind`` None — is one
        ``device_put``, as ever.  A larger one goes in pieces of that size,
        ``SEAL_PUT_PIECES_IN_FLIGHT`` at a time, into a zeroed device buffer
        that each update donates back (in place: HBM holds the round once and
        a few pieces): ONE ``device_put`` of 4 GiB ran at 0.30 GiB/s on a v5e
        host where 1 GiB and less run at 4.9 (PERF.md section 6, PR 27).  A
        piece no region's used prefix reaches is not put at all: it is zeros
        on the host and stays zeros on the device.  Nor is a piece that was
        put behind the writers (``_PutBehind``): this call carries that chain
        on from where its owner left it — the piece each region's writer
        stood in, a piece across two regions, everything where nothing was
        put early — and hands its buffer over."""
        import jax
        import jax.numpy as jnp

        if behind is None:
            return jax.device_put(payload, self.device), 0
        rows, lane = payload.shape
        region_rows = behind.region_rows
        used_rows = -(-region_used // alignment)
        pieces = 0
        for at in range(0, rows, behind.piece_rows):
            if behind.is_put(at):
                continue
            end = min(at + behind.piece_rows, rows)
            first, last = at // region_rows, (end - 1) // region_rows
            # used rows of region p lie in [p * region_rows, p * region_rows + used_rows[p])
            if not any(
                p * region_rows + int(used_rows[p]) > at for p in range(first, last + 1)
            ):
                continue
            self._put_piece(behind, payload, at)
            pieces += 1
        if behind.buf is None:  # not a used row in the round
            behind.buf = jnp.zeros((rows, lane), dtype=jnp.int32, device=self.device)
        return behind.buf, pieces

    def num_rounds(self, shuffle_id: int) -> int:
        st = self._state(shuffle_id)
        return st.round + 1

    def region_bytes(self, shuffle_id: int) -> int:
        """Per-peer region size in bytes — public form of the staging geometry
        the transports need for offset math (was reached via ``_state``)."""
        return self._state(shuffle_id).region_size

    def round_max_rows(self, shuffle_id: int) -> List[int]:
        """Per staging round, this executor's hottest destination region in
        rows (completed rollover rounds first, the live round last) — the
        local input to the skew planner (ops/skew.plan_exchange; the SPMD
        executor all-gathers these so every process derives one schedule)."""
        st = self._state(shuffle_id)
        with self._lock:
            maxes = [int(used.max()) // st.alignment for _, used in st.prev_rounds]
            maxes.append(int(st.region_used.max()) // st.alignment)
        return maxes

    def host_staging_allocated(self, shuffle_id: int) -> bool:
        """True when the host staging buffer exists for this shuffle.  The
        device write path's no-host-round-trip guarantee is observable here:
        it stays False for device-staged shuffles (rollover snapshots live in
        ``prev_rounds`` / the memmap spill tier, never in host staging)."""
        return self._state(shuffle_id).host_staging_allocated

    def committed_map_ids(self, shuffle_id: int) -> frozenset:
        """Snapshot of map ids with a successful commit (getPartitonOffset-table
        coverage, NvkvHandler.scala:258-265)."""
        st = self._state(shuffle_id)
        with self._lock:
            return frozenset(st.committed_maps)

    def mapper_info(self, shuffle_id: int, map_id: int) -> MapperInfo:
        """Reconstruct a committed map's MapperInfo from the offset table —
        what a peer's AM id 2 blob would carry (used by the SPMD executor when
        the commit landed in the store before the info arrived)."""
        st = self._state(shuffle_id)
        with self._lock:
            if map_id not in st.committed_maps:
                raise TransportError(f"map {map_id} not committed in shuffle {shuffle_id}")
            parts, rounds, splits = [], [], {}
            for r in range(st.num_reducers):
                e = st.blocks.get((map_id, r))
                parts.append((e.offset, e.length) if e is not None else (0, 0))
                rounds.append(e.round if e is not None else 0)
                if e is not None and e.pieces is not None:
                    splits[r] = e.pieces
        return MapperInfo(
            shuffle_id, map_id, tuple(parts), tuple(rounds) if any(rounds) else None, splits or None
        )

    # -- tiered eviction (service/eviction.py drives these) ----------------

    def _round_nbytes(self, st: _ShuffleState, round_idx: int) -> int:
        """Staged (padded) bytes of one round (caller holds self._lock)."""
        used = (
            st.prev_rounds[round_idx][1]
            if round_idx < len(st.prev_rounds)
            else st.region_used
        )
        return int(used.sum())

    def _tier_of(self, st: _ShuffleState, round_idx: int) -> str:
        """Which tier currently backs a round (caller holds self._lock):
        ``'hbm'`` (live device payload), ``'host'`` (RAM snapshot/staging),
        ``'disk'`` (np.memmap spill)."""
        if round_idx < len(st.prev_rounds):
            arr = st.prev_rounds[round_idx][0]
            return "disk" if isinstance(arr, np.memmap) else "host"
        if st.sealed:
            payload = st.sealed_payload[round_idx]
            if hasattr(payload, "is_deleted"):
                if not payload.is_deleted():
                    return "hbm"
            elif not st.host_staging_allocated:
                # demoted device round: the snapshot in sealed_payload is the
                # only backing (device shuffles never allocate host staging)
                return "disk" if isinstance(payload, np.memmap) else "host"
        return "disk" if st.host_staging_allocated and isinstance(st.staging, np.memmap) else "host"

    def round_tier(self, shuffle_id: int, round_idx: int) -> Optional[str]:
        """Public tier probe; None for unknown shuffles/rounds."""
        with self._lock:
            st = self._shuffles.get(shuffle_id)
            if st is None or not (0 <= round_idx <= st.round):
                return None
            return self._tier_of(st, round_idx)

    def round_bytes(self, shuffle_id: int, round_idx: int) -> int:
        """Staged bytes of one round — the footprint the eviction manager's
        restage plan orders by (arXiv:2112.01075)."""
        with self._lock:
            st = self._shuffles.get(shuffle_id)
            if st is None or not (0 <= round_idx <= st.round):
                return 0
            return self._round_nbytes(st, round_idx)

    def eviction_candidates(self) -> List[Tuple[int, int, str, int]]:
        """``(shuffle_id, round, tier, staged_bytes)`` for every SEALED round
        — the eviction manager's demotion/restage work list.  Unsealed
        shuffles are excluded: their rounds are still being written and their
        HBM payloads may be owned by an in-flight exchange."""
        out: List[Tuple[int, int, str, int]] = []
        with self._lock:
            for sid, st in self._shuffles.items():
                if not st.sealed:
                    continue
                for r in range(st.round + 1):
                    out.append((sid, r, self._tier_of(st, r), self._round_nbytes(st, r)))
        return out

    def demote_round(self, shuffle_id: int, round_idx: int) -> Optional[str]:
        """Move one sealed round ONE tier down: ``hbm -> host`` (drop the
        device payload, keep/snapshot the host bytes) or ``host -> disk``
        (``_spill_round`` memmap, the RAM buffer to the store's free list
        unless something still refers to it, tenant quota bytes returned).
        Returns the transition performed, or None when nothing moved (unknown
        round, unsealed shuffle, already on disk, shm staging, or
        spill_to_disk off).  ``read_block``/``block_staging_view`` keep
        serving the round at every tier."""
        with self._lock:
            st = self._shuffles.get(shuffle_id)
            if st is None or not st.sealed or not (0 <= round_idx <= st.round):
                return None
            lane = st.alignment // 4
            tier = self._tier_of(st, round_idx)
            if tier == "hbm":
                payload = st.sealed_payload[round_idx]
                if st.device_mode:
                    # Device shuffles have no host staging: snapshot D2H once
                    # (the same boundary _rollover_device pays), THEN delete.
                    st.sealed_payload[round_idx] = np.asarray(payload)
                else:
                    st.sealed_payload[round_idx] = st.staging.view(np.int32).reshape(-1, lane)
                try:
                    payload.delete()
                except Exception:
                    pass  # already donated to an exchange
                return "hbm->host"
            if tier != "host" or not self.conf.spill_to_disk:
                return None
            if st.staging_closer is not None:
                return None  # shm staging is shared with other processes
            nbytes = self._round_nbytes(st, round_idx)
            freed = []  # the RAM buffer the memmap replaces, for the free list
            if round_idx < len(st.prev_rounds):
                snap, used = st.prev_rounds[round_idx]
                mm = self._spill_round(st, snap, round_idx, used)
                st.prev_rounds[round_idx] = (mm, used)
                st.sealed_payload[round_idx] = mm.view(np.int32).reshape(-1, lane)
                self._ram_round_bytes -= snap.nbytes
                freed.append((snap, used))
            elif st.device_mode:
                host = st.sealed_payload[round_idx]
                flat = np.asarray(host).reshape(-1).view(np.uint8)
                mm = self._spill_round(st, flat, round_idx, st.region_used)
                st.sealed_payload[round_idx] = mm.view(np.int32).reshape(-1, lane)
            else:
                snap = st.staging
                mm = self._spill_round(st, snap, round_idx, st.region_used)
                st.staging = mm
                st.sealed_payload[round_idx] = mm.view(np.int32).reshape(-1, lane)
                freed.append((snap, st.region_used))
            snap = None  # _recycle_rounds wants the list's the only name left
            self._recycle_rounds(freed)
            self._release_tenant(st, nbytes)
            return "host->disk"

    def restage_round(self, shuffle_id: int, round_idx: int) -> bool:
        """Promote one disk-tier round back to host RAM (restage-on-fetch).
        Re-charges the owning tenant's quota FIRST — an over-quota tenant
        gets the typed TenantQuotaExceededError and the round stays on disk,
        still serveable through the memmap.  The spill file is dropped once
        the RAM copy is installed (a later demotion recreates it)."""
        # span OUTSIDE the store lock: restage-on-fetch runs under a serve
        # thread's remote trace context, so the restage shows up as a child
        # of the reducer's window in the merged trace
        kick = False
        try:
            with span("store.restage", shuffle_id=shuffle_id, round=round_idx), self._lock:
                st = self._shuffles.get(shuffle_id)
                if st is None or not (0 <= round_idx <= st.round):
                    return False
                if self._tier_of(st, round_idx) != "disk":
                    return False
                lane = st.alignment // 4
                # watermark gate BEFORE the quota charge: a pressured store
                # must not admit the very bytes its sweep is trying to shed.
                # The soft-watermark kick is deferred past the lock release
                # (try/finally) — the sweep latch is never reached through a
                # held-lock path.
                kick = self._check_pressure_locked(
                    "restage_round", self._round_nbytes(st, round_idx)
                )
                self._charge_tenant(st, self._round_nbytes(st, round_idx))  #: balanced by _release_tenant
                if round_idx < len(st.prev_rounds):
                    mm, used = st.prev_rounds[round_idx]
                    arr = np.array(mm)
                    st.prev_rounds[round_idx] = (arr, used)
                    self._ram_round_bytes += arr.nbytes
                    if st.sealed:
                        st.sealed_payload[round_idx] = arr.view(np.int32).reshape(-1, lane)
                elif st.device_mode:
                    mm = st.sealed_payload[round_idx]
                    arr = np.array(mm)
                    st.sealed_payload[round_idx] = arr
                else:
                    mm = st.staging
                    arr = np.array(mm)
                    st.staging = arr
                    if st.sealed:
                        st.sealed_payload[round_idx] = arr.view(np.int32).reshape(-1, lane)
                path = getattr(mm, "filename", None)
                if path:
                    self._unspill_file(st, str(path))
                return True
        finally:
            if kick:
                self._kick_watermark_sweep()

    # -- read path (serve staged blocks) ----------------------------------

    def _live_device_block(self, st: _ShuffleState, rnd: int, offset: int, length: int) -> np.ndarray:
        """A block of a device shuffle's LAST round on the host (caller holds
        self._lock): out of the staging array while the round is being
        written, out of the sealed payload after.  A payload the exchange
        consumed (it donates its send buffer where the receive buffer can
        alias it) or that was removed is a clean refusal: there is no second
        copy, on the device or the host."""
        array = st.device_staging
        if array is None and st.sealed_payload is not None:
            array = st.sealed_payload[-1]
        if isinstance(array, np.ndarray):  # demoted to the host or the disk tier
            flat = array.reshape(-1).view(np.uint8)
            return flat[offset : offset + length]
        if array is None or array.is_deleted():
            raise TransportError(
                f"device round {rnd} of shuffle {st.shuffle_id} is no longer resident"
            )
        return _device_block_bytes(array, offset, length, st.alignment)

    def read_block(self, shuffle_id: int, map_id: int, reduce_id: int) -> bytes:
        """Direct block read — HBM after seal, host staging before
        (the two arms of UcxShuffleBlockResolver.getBlockData,
        compat/spark_3_0/UcxShuffleBlockResolver.scala:86-97).

        The exchange collective *donates* sealed device payloads (the aliasing
        that halves peak HBM), so post-exchange the HBM copy may be deleted;
        the host staging area is retained until ``remove_shuffle`` exactly so
        this read — the pull-fallback/retry path — keeps working."""
        with self._lock:
            st = self._shuffles.get(shuffle_id)
        e = st.blocks.get((map_id, reduce_id)) if st is not None else None
        if e is None:
            # Replica tier: a ring neighbor's pushed copy serves even for a
            # shuffle this executor never created locally (failover serving).
            replica = self.replica_view(shuffle_id, map_id, reduce_id)
            if replica is not None:
                with span(
                    "store.read.replica",
                    shuffle_id=shuffle_id, map_id=map_id, reduce_id=reduce_id,
                ):
                    arr, off, ln = replica
                    return arr[off : off + ln].tobytes()
            if st is None:
                raise TransportError(f"unknown shuffle {shuffle_id}")
            raise BlockNotFoundError(shuffle_id, map_id, reduce_id, "not staged")
        if e.length == 0:
            return b""
        if e.pieces is None:
            return self._extent_bytes(st, e.round, e.offset, e.length)
        # a block longer than a region: its pieces, each where its round is now
        with span(
            "read.block_assemble", shuffle_id=shuffle_id, map_id=map_id, reduce_id=reduce_id,
            executor=self.executor_id, pieces=len(e.pieces), bytes=e.length,
        ):
            return b"".join(self._extent_bytes(st, *piece) for piece in e.pieces)

    def _extent_bytes(self, st: _ShuffleState, rnd: int, offset: int, length: int) -> bytes:
        """``length`` bytes at ``offset`` of staging round ``rnd``, from the
        tier that holds the round now (no lock held on entry)."""
        shuffle_id = st.shuffle_id
        # Eviction hook (no lock held): bumps the round's LRU clock and
        # transparently restages a disk-tier round to RAM before we serve.
        ev = self.eviction
        if ev is not None:
            ev.on_access(shuffle_id, rnd)
        sealed = st.sealed_payload  # one read: remove_shuffle may clear it
        if sealed is not None:
            payload = sealed[rnd]
            if not hasattr(payload, "is_deleted"):
                flat = np.asarray(payload).reshape(-1).view(np.uint8)
                return flat[offset : offset + length].tobytes()
            if not payload.is_deleted():
                return _device_block_bytes(payload, offset, length, st.alignment).tobytes()
        # Lock: (prev_rounds, staging) must be read atomically vs _rollover,
        # and the bytes copy must complete before a concurrent remove_shuffle
        # can munmap shm staging (the closer also runs under this lock).
        with self._lock:
            if rnd < len(st.prev_rounds):
                staging = st.prev_rounds[rnd][0]
            elif st.device_mode:
                # Live device round: the block's rows of the device staging
                # array (one small D2H) — there is no host staging.
                return self._live_device_block(st, rnd, offset, length).tobytes()
            else:
                staging = st.staging
            if staging is None:
                raise TransportError(f"shuffle {shuffle_id} staging already released")
            return staging[offset : offset + length].tobytes()

    def block_staging_view(
        self, shuffle_id: int, map_id: int, reduce_id: int
    ) -> Optional[Tuple[np.ndarray, int, int]]:
        """Serving handle: (host staging uint8 array, offset, length) for a
        staged block, or None when unknown — what the batch reply's native
        gather (``ts_batch_copy``) and the vectored reply read from.

        Zero-copy wherever the bytes can no longer change: a COMPLETED round
        (its RAM snapshot or memmap is never written again) and the live
        round of a SEALED shuffle (no writer can be opened and
        ``close_partition`` refuses one opened before the seal, so nothing
        rolls or appends; staging is retained until ``remove_shuffle`` as the
        shuffle's backing store, and a holder of the view keeps the array
        alive past that).  The live round of a shuffle that is NOT yet sealed
        is handed out as a private copy taken under the store lock: a
        rollover keeps the buffer and zeroes it for the next round
        (``_rollover``), so a view into it would read the next round's bytes.
        shm-backed staging is always a private copy (``remove_shuffle`` may
        munmap it once the lock is released), and so is a block staged in
        pieces (``_BlockEntry.pieces``), whose bytes lie in several rounds."""
        st = self._state(shuffle_id)
        e = st.blocks.get((map_id, reduce_id))
        if e is None:
            return None
        if e.pieces is not None:
            # no one buffer holds a block longer than a region: a private
            # copy, put together from its pieces (``read_block``)
            data = np.frombuffer(self.read_block(shuffle_id, map_id, reduce_id), dtype=np.uint8)
            return data, 0, e.length
        ev = self.eviction
        if ev is not None:
            ev.on_access(shuffle_id, e.round)
        with self._lock:
            live = e.round >= len(st.prev_rounds)
            if live and st.device_mode:
                # Live device round: a private host copy of the block (the
                # device array is donated on by the next write and let go by
                # a rollover); None once the exchange took the sealed array.
                try:
                    return np.array(self._live_device_block(st, e.round, e.offset, e.length)), 0, e.length
                except TransportError:
                    return None
            staging = st.staging if live else st.prev_rounds[e.round][0]
            if staging is None:
                return None
            if st.staging_closer is not None or (live and not st.sealed):
                return np.array(staging[e.offset : e.offset + e.length]), 0, e.length
        return staging, e.offset, e.length

    # -- serve-side decoded-block cache (popularity tier) -----------------

    def serve_cache_get(
        self, shuffle_id: int, map_id: int, reduce_id: int
    ) -> Optional[Tuple[np.ndarray, int, int]]:
        """Serving handle from the hot-block cache, shaped like
        ``block_staging_view`` — ``(uint8 array, offset, length)`` — or None
        on miss/disabled.  A hit bypasses the eviction tiers entirely: no
        ``on_access`` bump, no restage, no store lock."""
        cache = self.serve_cache
        if cache is None:
            return None
        data = cache.get((shuffle_id, map_id, reduce_id))
        if data is None:
            return None
        return np.frombuffer(data, dtype=np.uint8), 0, len(data)

    def serve_cache_offer(
        self, shuffle_id: int, map_id: int, reduce_id: int, data: bytes
    ) -> bool:
        """Pin one hot decoded block in the serve cache, charging its bytes
        against the owning tenant's quota (``#: balanced by _release_tenant``
        — released when LRU pressure or shuffle removal drops the entry).
        Returns False when the cache is off, the block outsizes the whole
        budget, or the tenant has no quota headroom — the fetch still serves
        from the normal tiers, the block just isn't pinned.

        Lock discipline: three SEQUENTIAL scopes (charge under the store
        lock, insert under the cache's leaf lock, release evictees under the
        store lock again) — the two locks never nest."""
        cache = self.serve_cache
        if cache is None or not data or len(data) > cache.capacity_bytes:
            return False
        key = (shuffle_id, map_id, reduce_id)
        with self._lock:
            st = self._shuffles.get(shuffle_id)
            if st is not None:
                try:
                    self._charge_tenant(st, len(data))  #: balanced by _release_tenant
                except TenantQuotaExceededError:
                    return False
        evicted = cache.put(key, data)
        if evicted:
            with self._lock:
                for (sid, _m, _r), nbytes in evicted:
                    est = self._shuffles.get(sid)
                    if est is not None:
                        self._release_tenant(est, nbytes)
        return True

    def block_length(self, shuffle_id: int, map_id: int, reduce_id: int) -> int:
        """getPartitonLength analogue (NvkvHandler.scala:258-265)."""
        e = self._state(shuffle_id).blocks.get((map_id, reduce_id))
        return e.length if e is not None else 0

    def block_offset(self, shuffle_id: int, map_id: int, reduce_id: int) -> int:
        """getPartitonOffset analogue; of a block staged in pieces, its first
        piece's (``MapperInfo.partitions`` says the same)."""
        e = self._state(shuffle_id).blocks.get((map_id, reduce_id))
        if e is None:
            raise TransportError(f"no block ({shuffle_id},{map_id},{reduce_id}) staged")
        return e.offset

    # -- neighbor-replication tier (REPLICA_PUT/failover serving) ----------

    def replica_source(
        self, shuffle_id: int, alloc: Optional[Callable[[int], np.ndarray]] = None
    ) -> List[Tuple[int, List[Tuple[int, int, int]], object]]:
        """Snapshot this executor's sealed rounds for replication: one
        ``(round, [(map, reduce, length)...], body)`` per staging round,
        body = the unpadded block payloads concatenated in table order (a
        block staged in pieces whole, in its first piece's round).  Only
        locally staged entries are included — entries installed from peers'
        MapperInfo carry sender-relative offsets and no local bytes.

        A replicated byte is copied ONCE, outside the store's lock.  Under the
        lock only what is small is taken, a round: the sorted table, each
        block's offset, and a reference to the round's array — a sealed
        round is never written again before ``remove_shuffle``, and the
        reference keeps its buffer off the free list by the rule
        ``_recycle_rounds`` applies to every holder.  Then, with the lock
        released, each round's blocks are gathered into one destination:
        ``alloc(nbytes)``'s ``uint8`` array where the caller names where a
        body lies (the cluster's landing pool, ``_replicate_sealed``), else a
        ``bytearray`` (the wire's frame wants a bytes-like).  What can still
        change or go under a reader — the live round of a shuffle not yet
        sealed, shm staging (``remove_shuffle`` unmaps it), a device round's
        per-block D2H — is gathered with the lock held, as ever."""
        st = self._state(shuffle_id)

        def gather(nbytes, segments, source, staged, further):
            body = bytearray(nbytes) if alloc is None else alloc(nbytes)
            dst = np.frombuffer(body, dtype=np.uint8) if alloc is None else body
            if source is None:  # a device round's last: one small D2H a block
                for (at, _off, ln), e in zip(segments, staged):
                    dst[at : at + ln] = self._live_device_block(st, e.round, e.offset, e.length)
            else:
                _gather_blocks(dst, source, segments)
            for later, pieces in further:  # a split block's pieces past its first
                _gather_blocks(dst, later, pieces)
            return body

        def host_round(rnd):
            source = st.prev_rounds[rnd][0] if rnd < len(st.prev_rounds) else st.staging
            if source is None:
                raise TransportError(f"shuffle {shuffle_id} staging already released")
            return source

        plans = []  # (round, entries, body gathered under the lock or None, gather's arguments)
        with self._lock:
            by_round: Dict[int, List[Tuple[int, int]]] = {}
            for key, e in st.blocks.items():
                if e.local:
                    by_round.setdefault(e.round, []).append(key)
            for rnd in sorted(by_round):
                entries: List[Tuple[int, int, int]] = []
                segments: List[Tuple[int, int, int]] = []  # (body offset, round offset, length)
                staged: List[_BlockEntry] = []  # the blocks of ``segments``
                #: a block staged in pieces is replicated WHOLE, in the body of
                #: its first piece's round: round -> the segments of its later pieces
                later: Dict[int, List[Tuple[int, int, int]]] = {}
                pos = 0
                for key in sorted(by_round[rnd]):
                    e = st.blocks[key]
                    entries.append((key[0], key[1], e.length))
                    if e.pieces is not None:
                        segments.append((pos, e.offset, e.pieces[0][2]))
                        staged.append(e)
                        at = pos + e.pieces[0][2]
                        for piece_round, off, ln in e.pieces[1:]:
                            later.setdefault(piece_round, []).append((at, off, ln))
                            at += ln
                        pos += e.length
                    elif e.length:
                        segments.append((pos, e.offset, e.length))
                        staged.append(e)
                        pos += e.length
                live = rnd >= len(st.prev_rounds) or any(k >= len(st.prev_rounds) for k in later)
                source = None
                if not (live and st.device_mode):
                    source = host_round(rnd)
                args = (pos, segments, source, staged, [(host_round(k), later[k]) for k in sorted(later)])
                under_lock = live and (
                    source is None or not st.sealed or st.staging_closer is not None
                )
                plans.append((rnd, entries, gather(*args) if under_lock else None, args))
        return [
            (rnd, entries, gather(*args) if body is None else body)
            for rnd, entries, body, args in plans
        ]

    def put_replica(
        self,
        shuffle_id: int,
        src_executor: int,
        round_idx: int,
        entries: Sequence[Tuple[int, int, int]],
        body,
    ) -> None:
        """Install one replicated round pushed by a ring neighbor.  ``body``
        is the concatenated unpadded payloads in ``entries`` order; a repeated
        put for the same (shuffle, src, round) replaces the old copy (the
        replicator may re-push after a transient failure)."""
        # a pressured receiver sheds replica installs (best-effort durability:
        # the pushing neighbor accounts it as a failed push and moves on)
        self.check_memory_pressure("put_replica", len(body))
        index: Dict[Tuple[int, int], Tuple[int, int]] = {}
        pos = 0
        for m, r, ln in entries:
            index[(m, r)] = (pos, ln)
            pos += ln
        if pos != len(body):
            raise TransportError(
                f"replica round (shuffle={shuffle_id}, src={src_executor}, "
                f"round={round_idx}) table claims {pos} B but body is {len(body)} B"
            )
        # The sender hands ownership over, so what owns its bytes is installed
        # as it is: a flat ``uint8`` array that owns its memory (the cluster's
        # one gather, ``replica_source(alloc=...)``) becomes the tier's array,
        # read-only from here on; bytes bodies wrap zero-copy (np.frombuffer
        # over bytes never copies), as does a decoded bytearray from the
        # compressed replica path (transport/peer.py).  Anything else — a view
        # of somebody's buffer, a non-contiguous memoryview — is copied: a
        # replica is its own bytes, never a window into its source.
        if not len(body):
            arr = np.empty(0, dtype=np.uint8)
        elif isinstance(body, np.ndarray) and _owns_flat_bytes(body):
            arr = body
            arr.flags.writeable = False
        elif isinstance(body, (bytes, bytearray)):
            arr = np.frombuffer(body, dtype=np.uint8)
        else:
            arr = np.frombuffer(bytes(body), dtype=np.uint8)
        with self._lock:
            rounds = self._replicas.setdefault((shuffle_id, src_executor), {})
            old = rounds.get(round_idx)
            if old is not None:
                self._replica_bytes -= int(old[1].size)
            rounds[round_idx] = (index, arr)
            self._replica_bytes += int(arr.size)

    def replica_view(
        self, shuffle_id: int, map_id: int, reduce_id: int
    ) -> Optional[Tuple[np.ndarray, int, int]]:
        """Zero-copy serving handle into a replicated round — the failover
        analogue of ``block_staging_view``.  None when no replica of the block
        has landed (including: replication disabled, or still in flight)."""
        with self._lock:
            for (sid, _src), rounds in self._replicas.items():
                if sid != shuffle_id:
                    continue
                for index, arr in rounds.values():
                    hit = index.get((map_id, reduce_id))
                    if hit is not None:
                        return arr, hit[0], hit[1]
        return None

    def replica_block(
        self, shuffle_id: int, src_executor: int, map_id: int, reduce_id: int
    ) -> Optional[np.ndarray]:
        """The replicated bytes of one block FROM A NAMED SOURCE executor —
        the restage path's accessor (elastic recovery rebuilds a dead
        executor's staging from its ring-successor's replica tier, and must
        not accidentally serve a same-keyed block replicated from a different
        source): a read-only ``uint8`` view of the replica round where it
        lies — a replica is never written again, and the view keeps its array
        alive past ``remove_shuffle``.  None when no replica of (src, block)
        landed here."""
        with self._lock:
            rounds = self._replicas.get((shuffle_id, src_executor))
            if not rounds:
                return None
            for index, arr in rounds.values():
                hit = index.get((map_id, reduce_id))
                if hit is not None:
                    view = arr[hit[0] : hit[0] + hit[1]]
                    view.flags.writeable = False
                    return view
        return None

    def replica_stats(self) -> Dict[str, int]:
        """Replica-tier accounting across all shuffles."""
        with self._lock:
            return {
                "replica_bytes": self._replica_bytes,
                "replica_rounds": sum(len(r) for r in self._replicas.values()),
                "replica_sources": len(self._replicas),
            }

    # -- introspection -----------------------------------------------------

    def stats(self, shuffle_id: int) -> Dict[str, object]:
        st = self._state(shuffle_id)
        # per staging round (rollovers then the live round), (used, padded)
        # rows of the slot layout — the store-side view of the imbalance the
        # skew planner (conf.slot_quota_rows) caps.  Computed inline: _lock is
        # a plain (non-reentrant) Lock, so this must not call the locked
        # round_max_rows helper.
        slot_rows = st.region_size // st.alignment
        occupancy = []
        for _, used in st.prev_rounds:
            u = int(used.sum()) // st.alignment
            occupancy.append((u, int(used.size) * slot_rows - u))
        u = int(st.region_used.sum()) // st.alignment
        occupancy.append((u, int(st.region_used.size) * slot_rows - u))
        with self._lock:
            replica_bytes = sum(
                int(arr.size)
                for (sid, _src), rounds in self._replicas.items()
                if sid == shuffle_id
                for _index, arr in rounds.values()
            )
        return {
            "ram_budget_bytes": self._ram_budget,
            "replica_bytes": replica_bytes,
            "num_blocks": len(st.blocks),
            "bytes_staged": int(sum(e.length for e in st.blocks.values())),
            "bytes_padded": int(sum(e.padded for e in st.blocks.values())),
            "region_used": st.region_used.tolist(),
            "region_size": st.region_size,
            "round_occupancy": occupancy,
            "committed_maps": sorted(st.committed_maps),
            "sealed": st.sealed,
            "device_mode": st.device_mode,
            "host_staging_allocated": st.host_staging_allocated,
        }
