"""Tiered eviction: epoch/LRU demotion of sealed rounds, restage-on-fetch.

The store already has three tiers for a sealed round's bytes — HBM-resident
``jax.Array`` exchange payload, host staging snapshot, and ``np.memmap`` disk
spill (``HbmBlockStore._spill_round``) — but until now a round only ever
moved DOWN at rollover time and never back.  The EvictionManager turns those
tiers into a managed cache:

* **Demotion**: every epoch (the constructor's ``epoch_ms`` once
  :meth:`start` was called, or a manual :meth:`run_epoch`), the
  least-recently-fetched sealed rounds are
  demoted one tier (``hbm`` -> ``host`` -> ``disk``) through
  ``HbmBlockStore.demote_round``.  Cold shuffles drain out of HBM and RAM;
  fetches keep working at every tier (``read_block`` serves memmaps too).
* **Restage-on-fetch**: the store notifies :meth:`on_access` on every block
  read; a fetch that lands on a disk-tier round restages it to host RAM
  (``restage_round``) so the rest of the round's fan-in runs at RAM speed.
  Restages are timed into the StatsAggregator (``eviction.restage`` kind) —
  ``restage_p99_ns`` is the tail penalty a cold fetch pays.
* **Restage ordering**: when several rounds must come back (a cold shuffle's
  whole fan-in arriving at once), :meth:`restage_plan` orders them by
  ascending staged footprint — the memory-footprint-aware scheduling of
  arXiv:2112.01075 applied to tier promotion: smallest rounds first, so peak
  transient staging (memmap pages + the new RAM copy coexist during the
  copy) grows as slowly as service is restored.

Quota interplay: demoting a round to disk releases its bytes from the owning
tenant's HBM charge, and restaging re-charges them — so a tenant over its
quota gets a typed ``TenantQuotaExceededError`` from the restage, which the
serving plane returns over the wire as a fail-fast addressed error.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

from sparkucx_tpu.core.operation import OperationStats
from sparkucx_tpu.utils.stats import StatsAggregator


class ServeCache:
    """Bounded serve-side decoded-block cache ABOVE the eviction tiers.

    Hot blocks — promoted by the popularity tracker — are pinned here as
    immutable ``bytes`` in a byte-budgeted LRU (``serve.cacheBytes``), so a
    fetch storm on a demoted round is served from RAM without paying the
    disk restage, and demotion/restage churn below never touches the hot
    set.  The cache stores COPIES (decoded payload snapshots), never views
    into the store's staging buffers: entries stay valid across demotion,
    restage, and round rollover, and are dropped only by LRU pressure or
    :meth:`invalidate_shuffle` when the shuffle itself is removed.

    Quota interplay is orchestrated by the store, not here: the store
    charges the owning tenant BEFORE :meth:`put` and releases the bytes of
    whatever :meth:`put`/:meth:`invalidate_shuffle` return as evicted —
    sequential lock scopes, so ``ServeCache._lock`` stays a leaf and never
    nests with ``HbmBlockStore._lock``.
    """

    def __init__(self, capacity_bytes: int) -> None:
        self.capacity_bytes = int(capacity_bytes)
        self._lock = threading.Lock()  # LEAF: no calls out while held
        #: (shuffle_id, map_id, reduce_id) -> payload; guarded by self._lock
        self._entries: "OrderedDict[Tuple[int, int, int], bytes]" = OrderedDict()
        self._used = 0  #: guarded by self._lock
        self.stats: Dict[str, int] = {
            "cache_hits": 0,
            "cache_misses": 0,
            "cache_evictions": 0,
            "cache_rejects": 0,
        }  #: guarded by self._lock

    def get(self, key: Tuple[int, int, int]) -> Optional[bytes]:
        """Cached payload for ``(shuffle, map, reduce)`` or None; a hit
        refreshes the entry's LRU position."""
        with self._lock:
            data = self._entries.get(key)
            if data is None:
                self.stats["cache_misses"] += 1
                return None
            self._entries.move_to_end(key)
            self.stats["cache_hits"] += 1
            return data

    def put(self, key: Tuple[int, int, int], data: bytes) -> List[Tuple[Tuple[int, int, int], int]]:
        """Insert (or refresh) one decoded block; evicts LRU entries to fit.
        Returns ``[(key, nbytes)]`` for every entry evicted so the caller can
        release their tenant charges.  A block larger than the whole budget
        is rejected (counted, nothing evicted for it)."""
        nbytes = len(data)
        with self._lock:
            if nbytes > self.capacity_bytes:
                self.stats["cache_rejects"] += 1
                return []
            prev = self._entries.pop(key, None)
            if prev is not None:
                self._used -= len(prev)
            evicted: List[Tuple[Tuple[int, int, int], int]] = []
            while self._used + nbytes > self.capacity_bytes and self._entries:
                old_key, old_data = self._entries.popitem(last=False)
                self._used -= len(old_data)
                self.stats["cache_evictions"] += 1
                evicted.append((old_key, len(old_data)))
            self._entries[key] = data
            self._used += nbytes
            if prev is not None:
                evicted.append((key, len(prev)))
            return evicted

    def invalidate_shuffle(self, shuffle_id: int) -> List[Tuple[Tuple[int, int, int], int]]:
        """Drop every entry of one shuffle (shuffle removal); returns the
        dropped ``[(key, nbytes)]`` so the caller releases tenant charges."""
        with self._lock:
            doomed = [k for k in self._entries if k[0] == shuffle_id]
            out: List[Tuple[Tuple[int, int, int], int]] = []
            for k in doomed:
                data = self._entries.pop(k)
                self._used -= len(data)
                out.append((k, len(data)))
            return out

    @property
    def used_bytes(self) -> int:
        with self._lock:
            return self._used

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def snapshot(self) -> Dict[str, int]:
        """Counter snapshot for MetricsRegistry export."""
        with self._lock:
            out = dict(self.stats)
            out["cache_used_bytes"] = self._used
            out["cache_entries"] = len(self._entries)
            return out


class EvictionManager:
    """LRU tier demotion + restage policy over one ``HbmBlockStore``."""

    def __init__(
        self,
        store,
        stats: Optional[StatsAggregator] = None,
        epoch_ms: int = 0,
        restage_on_fetch: bool = True,
    ) -> None:
        self._store = store
        self._stats = stats if stats is not None else StatsAggregator()
        self.epoch_ms = int(epoch_ms)
        self.restage_on_fetch = restage_on_fetch
        self._access: Dict[Tuple[int, int], int] = {}  #: guarded by self._lock
        self._clock = 0  #: guarded by self._lock
        self._demotions = 0  #: guarded by self._lock
        self._restages = 0  #: guarded by self._lock
        self._closed = False  #: guarded by self._lock
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._wake = threading.Event()

    # -- access tracking / restage-on-fetch ------------------------------
    def on_access(self, shuffle_id: int, round_idx: int) -> None:
        """Store hook: a block of ``(shuffle_id, round_idx)`` is being read.
        Bumps the LRU clock; a disk-tier round is restaged first so the fetch
        (and the rest of its fan-in) serves from RAM."""
        with self._lock:
            self._clock += 1
            self._access[(shuffle_id, round_idx)] = self._clock
            restage = self.restage_on_fetch and not self._closed
        if restage and self._store.round_tier(shuffle_id, round_idx) == "disk":
            self.restage(shuffle_id, round_idx)

    def forget_shuffle(self, shuffle_id: int) -> None:
        """Store hook on ``remove_shuffle``: drop the shuffle's LRU-clock
        entries so the access table can't grow monotonically across shuffle
        lifetimes (and a recycled shuffle id can't inherit the old id's
        recency, surviving demotion sweeps it should lose)."""
        with self._lock:
            for key in [k for k in self._access if k[0] == shuffle_id]:
                del self._access[key]

    def restage(self, shuffle_id: int, round_idx: int) -> bool:
        """Promote one round disk -> host, timed into ``eviction.restage``.
        Raises TenantQuotaExceededError when the owning tenant has no quota
        headroom left for the round's bytes."""
        op = OperationStats()
        moved = self._store.restage_round(shuffle_id, round_idx)
        if moved:
            op.mark_done(self._store.round_bytes(shuffle_id, round_idx))
            self._stats.record("eviction.restage", op)
            with self._lock:
                self._restages += 1
        return moved

    # -- demotion ---------------------------------------------------------
    def run_epoch(self, max_demotions: Optional[int] = None) -> int:
        """One demotion sweep: order every demotable sealed round by LRU
        clock (never-fetched rounds first) and demote each one tier, up to
        ``max_demotions`` (None = all candidates).  Returns demotion count."""
        candidates = self._store.eviction_candidates()
        with self._lock:
            access = dict(self._access)
        candidates.sort(key=lambda c: (access.get((c[0], c[1]), 0), -c[3]))
        demoted = 0
        for sid, rnd, _tier, _nbytes in candidates:
            if max_demotions is not None and demoted >= max_demotions:
                break
            if self._store.demote_round(sid, rnd) is not None:
                demoted += 1
        if demoted:
            with self._lock:
                self._demotions += demoted
            self._stats.record_counters("eviction", demotions=demoted)
        return demoted

    # -- restage planning -------------------------------------------------
    def restage_plan(
        self, rounds: Sequence[Tuple[int, int]]
    ) -> List[Tuple[int, int]]:
        """Order ``(shuffle_id, round_idx)`` pairs for bulk restage: ascending
        staged footprint (arXiv:2112.01075's memory-footprint-aware ordering
        applied to tier promotion), ties broken by round order so the plan is
        deterministic across processes."""
        return sorted(
            rounds,
            key=lambda r: (self._store.round_bytes(r[0], r[1]), r[0], r[1]),
        )

    def restage_all(self, shuffle_id: int) -> int:
        """Bring every disk-tier round of a shuffle back to host RAM, in
        footprint-bounded plan order.  Returns the number restaged."""
        demoted = [
            (sid, rnd)
            for sid, rnd, tier, _ in self._store.eviction_candidates()
            if sid == shuffle_id and tier == "disk"
        ]
        count = 0
        for sid, rnd in self.restage_plan(demoted):
            if self.restage(sid, rnd):
                count += 1
        return count

    # -- background epochs -------------------------------------------------
    def start(self) -> None:
        """Run :meth:`run_epoch` every ``epoch_ms`` on a daemon thread.
        No-op when epoch_ms == 0 (manual epochs only)."""
        if self.epoch_ms <= 0 or self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._epoch_loop, name="sparkucx-eviction", daemon=True
        )
        self._thread.start()

    def _epoch_loop(self) -> None:
        while True:
            self._wake.wait(timeout=self.epoch_ms / 1000.0)
            with self._lock:
                if self._closed:
                    return
            try:
                self.run_epoch()
            except Exception:
                # Eviction is best-effort background hygiene: a transient
                # store error (shuffle being removed mid-sweep) must not kill
                # the epoch thread.
                pass

    def close(self) -> None:
        with self._lock:
            self._closed = True
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    # -- observability -----------------------------------------------------
    def eviction_stats(self) -> Dict[str, int]:
        """Demotion/restage counters + restage tail latency, for report()."""
        with self._lock:
            demotions, restages = self._demotions, self._restages
        summ = self._stats.summary("eviction.restage")
        p99 = getattr(summ, "p99_ns", None) if summ is not None else None
        return {
            "demotions": demotions,
            "restages": restages,
            "restage_p99_ns": int(p99) if p99 is not None else 0,
        }
