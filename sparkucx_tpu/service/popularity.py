"""Per-block fetch-rate tracking for the popularity-aware serving tier."""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Tuple


class _BlockRate:
    """One block's fetch-rate state (all fields guarded by the owning
    tracker's ``_lock``)."""

    __slots__ = ("ewma", "last_ns", "hot")

    def __init__(self, now_ns: int) -> None:
        self.ewma = 0.0  # fetches/sec EWMA of instantaneous 1/dt rates
        self.last_ns = now_ns
        self.hot = False


class BlockPopularity:
    """Per-block fetch-rate EWMAs driving the popularity-aware serving tier.

    The same EWMA shape as the transport's ``_PeerHealth`` latency tracker,
    pointed at demand instead of health: every served fetch folds its
    instantaneous rate (``1e9 / dt_ns`` since the block's previous fetch)
    into a per-block EWMA.  A block whose rate crosses
    ``serve.hotThresholdFetchesPerSec`` is *hot*; the serving plane reacts at
    shuffle granularity (replication pushes whole sealed rounds), so
    :meth:`observe` reports shuffle-level transitions — the first block of a
    shuffle to heat up promotes the shuffle, and the shuffle demotes only
    when :meth:`sweep` finds every one of its blocks cooled below HALF the
    threshold (hysteresis: the promote and demote edges never chatter on a
    rate hovering at the threshold).  Cooling is rate-decay aware: a block
    that simply stops being fetched demotes once ``1e9 / elapsed_ns`` falls
    under the demote edge, even though no new sample ever arrives.

    ``now_ns`` is injectable for deterministic tests.  ``_lock`` is a LEAF:
    no calls out while held (the lock-order pass pins this via
    LOCK_ATTR_CLASSES).
    """

    #: demote edge = threshold * _COOL_FRACTION (hysteresis band)
    _COOL_FRACTION = 0.5
    #: cold entries idle this long are forgotten (memory bound)
    _IDLE_GC_NS = 60 * 1_000_000_000

    def __init__(
        self,
        hot_threshold_per_sec: float,
        alpha: float = 0.25,
        now_ns: Optional[Callable[[], int]] = None,
    ) -> None:
        self.hot_threshold = float(hot_threshold_per_sec)
        self.alpha = float(alpha)
        self._now_ns = now_ns if now_ns is not None else time.monotonic_ns
        self._rates: Dict[Tuple[int, int, int], _BlockRate] = {}  #: guarded by self._lock
        self._hot_counts: Dict[int, int] = {}  #: shuffle -> hot-block count; guarded by self._lock
        self.stats: Dict[str, int] = {"promotions": 0, "demotions": 0}  #: guarded by self._lock
        self._last_sweep_ns = 0  #: guarded by self._lock
        self._lock = threading.Lock()  # LEAF: no calls out while held

    def observe(
        self, shuffle_id: int, map_id: int, reduce_id: int
    ) -> Tuple[bool, List[Tuple[int, bool]]]:
        """Fold one served fetch into the block's EWMA.  Returns
        ``(block_is_hot, [(shuffle_id, True)] when this fetch promoted the
        shuffle)`` — the serving plane widens the shuffle's replica set on
        that transition and admits the block to the serve cache while hot."""
        if self.hot_threshold <= 0:
            return False, []
        now = self._now_ns()
        key = (shuffle_id, map_id, reduce_id)
        with self._lock:
            r = self._rates.get(key)
            if r is None:
                self._rates[key] = _BlockRate(now)
                return False, []
            dt = max(now - r.last_ns, 1)
            r.last_ns = now
            r.ewma = self.alpha * (1e9 / dt) + (1.0 - self.alpha) * r.ewma
            transitions: List[Tuple[int, bool]] = []
            if not r.hot and r.ewma >= self.hot_threshold:
                r.hot = True
                self.stats["promotions"] += 1
                n = self._hot_counts.get(shuffle_id, 0)
                self._hot_counts[shuffle_id] = n + 1
                if n == 0:
                    transitions.append((shuffle_id, True))
            return r.hot, transitions

    def sweep(self, now_ns: Optional[int] = None) -> List[Tuple[int, bool]]:
        """Cool-down pass: demote hot blocks whose effective rate —
        ``min(ewma, 1e9 / elapsed_ns)``, so silence decays the rate — fell
        below the demote edge, and forget long-idle cold blocks.  Returns
        ``[(shuffle_id, False)]`` for every shuffle whose LAST hot block
        cooled (the serving plane drops the widened advertisement then)."""
        now = self._now_ns() if now_ns is None else now_ns
        cool_edge = self.hot_threshold * self._COOL_FRACTION
        transitions: List[Tuple[int, bool]] = []
        with self._lock:
            for key, r in list(self._rates.items()):
                elapsed = max(now - r.last_ns, 1)
                effective = min(r.ewma, 1e9 / elapsed)
                if r.hot:
                    if effective < cool_edge:
                        r.hot = False
                        r.ewma = effective
                        self.stats["demotions"] += 1
                        n = self._hot_counts.get(key[0], 1) - 1
                        if n <= 0:
                            self._hot_counts.pop(key[0], None)
                            transitions.append((key[0], False))
                        else:
                            self._hot_counts[key[0]] = n
                elif elapsed > self._IDLE_GC_NS:
                    del self._rates[key]
        return transitions

    def maybe_sweep(
        self, min_interval_ns: int = 1_000_000_000
    ) -> List[Tuple[int, bool]]:
        """Rate-limited :meth:`sweep`, safe to call on every served batch:
        at most one cool-down pass per ``min_interval_ns`` actually scans."""
        if self.hot_threshold <= 0:
            return []
        now = self._now_ns()
        with self._lock:
            if now - self._last_sweep_ns < min_interval_ns:
                return []
            self._last_sweep_ns = now
        return self.sweep(now)

    def is_hot(self, shuffle_id: int) -> bool:
        with self._lock:
            return self._hot_counts.get(shuffle_id, 0) > 0

    def hot_shuffles(self) -> List[int]:
        with self._lock:
            return sorted(self._hot_counts)

    def snapshot(self) -> Dict[str, int]:
        """Counter snapshot for MetricsRegistry export (``serve`` family)."""
        with self._lock:
            return {
                "promotions": self.stats["promotions"],
                "demotions": self.stats["demotions"],
                "tracked_blocks": len(self._rates),
                "hot_blocks": sum(self._hot_counts.values()),
                "hot_shuffles": len(self._hot_counts),
            }
