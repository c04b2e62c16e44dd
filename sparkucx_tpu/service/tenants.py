"""Tenant registry + admission control (the multi-tenant half of ROADMAP 4).

One shuffle service, many Spark applications: each app registers under its
``app_id`` with an HBM byte quota, and every store region allocation is
admission-checked against that budget at the moment the bytes are claimed
(``HbmBlockStore`` calls :meth:`TenantRegistry.charge` under its own lock from
``close_partition`` / ``write_partition_device``).  An over-quota write raises
the typed :class:`~sparkucx_tpu.core.operation.TenantQuotaExceededError`
instead of eating a neighbor tenant's HBM; an operation naming an app that
never registered raises
:class:`~sparkucx_tpu.core.operation.UnknownTenantError`.

Shuffle ids become ``(app_id, shuffle_id)``: every tenant keeps its own local
shuffle-id namespace and the registry translates to a process-unique internal
id (:meth:`sid_for` / :meth:`translate`) used by the store and transport.  On
the wire the tenant rides as a self-describing ``FETCH_BLOCK_REQ`` header
extension (transport/peer.py) — absent by default, so single-tenant frames
stay byte-identical to the golden captures.

Fairness: the reduce-side ``CreditGate`` (transport/pipeline.py) is
generalized here to per-tenant byte budgets — :meth:`gate` hands out one gate
per tenant, and the serving plane acquires reply bytes against the requesting
tenant's gate, so one tenant's fan-in cannot starve every lane.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional, Tuple

from sparkucx_tpu.core.operation import TenantQuotaExceededError, UnknownTenantError
from sparkucx_tpu.transport.pipeline import CreditGate

#: Internal shuffle ids allocated for tenant-owned shuffles start here, far
#: above any id a single-tenant caller passes directly, so translated and
#: untranslated ids never collide in one store.
TENANT_SID_BASE = 1 << 20


class Tenant:
    """One registered application: quota, usage, and its wire-credit gate."""

    def __init__(self, app_id: str, hbm_quota_bytes: int, credit_bytes: int) -> None:
        self.app_id = app_id
        #: HBM staging budget in bytes; 0 = unlimited (no admission checks).
        self.hbm_quota_bytes = int(hbm_quota_bytes)
        #: Per-tenant serving-plane byte budget (CreditGate budget); 0 = no gate.
        self.credit_bytes = int(credit_bytes)
        self.used_bytes = 0  #: guarded by TenantRegistry._lock
        self._gate: Optional[CreditGate] = None  #: guarded by TenantRegistry._lock

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (
            f"Tenant({self.app_id!r}, used={self.used_bytes},"
            f" quota={self.hbm_quota_bytes})"
        )


class TenantRegistry:
    """Thread-safe registry of tenants and their shuffle-id namespaces.

    The registry is the single admission-control authority of a serving
    process: the store charges/releases HBM bytes through it, the transport
    translates ``(app_id, local shuffle id)`` pairs through it, and the
    serving plane draws per-tenant wire credits from it.
    """

    def __init__(
        self,
        default_quota_bytes: int = 0,
        default_credit_bytes: int = 0,
    ) -> None:
        #: Quota applied when ``register`` is called without one; 0 = unlimited.
        self.default_quota_bytes = int(default_quota_bytes)
        #: Serving-plane CreditGate budget per tenant; 0 disables the gates.
        self.default_credit_bytes = int(default_credit_bytes)
        self._tenants: Dict[str, Tenant] = {}  #: guarded by self._lock
        self._sids: Dict[Tuple[str, int], int] = {}  #: guarded by self._lock
        self._next_sid = TENANT_SID_BASE  #: guarded by self._lock
        self._lock = threading.Lock()

    # -- lifecycle -----------------------------------------------------
    def register(
        self,
        app_id: str,
        hbm_quota_bytes: Optional[int] = None,
        credit_bytes: Optional[int] = None,
    ) -> Tenant:
        """Register (or re-register) an application.  Re-registering updates
        the budgets but keeps usage and the shuffle-id namespace — the
        executor-restart case, where the app reconnects mid-flight."""
        with self._lock:
            t = self._tenants.get(app_id)
            if t is None:
                t = Tenant(
                    app_id,
                    self.default_quota_bytes if hbm_quota_bytes is None else hbm_quota_bytes,
                    self.default_credit_bytes if credit_bytes is None else credit_bytes,
                )
                self._tenants[app_id] = t
            else:
                if hbm_quota_bytes is not None:
                    t.hbm_quota_bytes = int(hbm_quota_bytes)
                if credit_bytes is not None:
                    t.credit_bytes = int(credit_bytes)
            return t

    def unregister(self, app_id: str) -> None:
        """Drop a tenant: its charges, its shuffle-id translations, its gate.
        Unknown app_ids are ignored (unregister is idempotent)."""
        with self._lock:
            self._tenants.pop(app_id, None)
            for key in [k for k in self._sids if k[0] == app_id]:
                del self._sids[key]

    def resolve(self, app_id: str) -> Tenant:
        """The tenant for ``app_id``, or a typed UnknownTenantError."""
        with self._lock:
            t = self._tenants.get(app_id)
        if t is None:
            raise UnknownTenantError(app_id)
        return t

    def known(self, app_id: str) -> bool:
        with self._lock:
            return app_id in self._tenants

    def app_ids(self):
        with self._lock:
            return sorted(self._tenants)

    # -- (app_id, shuffle_id) namespace --------------------------------
    def sid_for(self, app_id: str, shuffle_id: int) -> int:
        """Get-or-allocate the internal shuffle id for a tenant's local
        ``shuffle_id``.  The allocating side (the app creating its shuffle)
        uses this; serving-side lookups use :meth:`translate`."""
        with self._lock:
            if app_id not in self._tenants:
                raise UnknownTenantError(app_id, "register before creating shuffles")
            key = (app_id, int(shuffle_id))
            sid = self._sids.get(key)
            if sid is None:
                sid = self._next_sid
                self._next_sid += 1
                self._sids[key] = sid
            return sid

    def translate(self, app_id: str, shuffle_id: int) -> int:
        """Serving-side translation of a wire ``(app_id, shuffle_id)`` pair to
        the internal store id.  Unknown tenants raise UnknownTenantError;
        a known tenant with an unknown local shuffle id returns the local id
        untranslated (the store then reports its usual unknown-shuffle error,
        which the wire maps to block-not-found — retryable, unlike tenant
        errors)."""
        with self._lock:
            if app_id not in self._tenants:
                raise UnknownTenantError(app_id)
            return self._sids.get((app_id, int(shuffle_id)), int(shuffle_id))

    # -- admission control ---------------------------------------------
    def charge(self, app_id: str, shuffle_id: int, nbytes: int) -> None:
        """Claim ``nbytes`` of HBM staging against the tenant's quota.
        Called by the store at region-allocation time (and at restage time by
        the eviction manager), under the store lock — this lock nests inside
        it, never the other way around."""
        if nbytes <= 0:
            return
        with self._lock:
            t = self._tenants.get(app_id)
            if t is None:
                raise UnknownTenantError(app_id, "charge on unregistered tenant")
            if t.hbm_quota_bytes and t.used_bytes + nbytes > t.hbm_quota_bytes:
                raise TenantQuotaExceededError(
                    app_id,
                    shuffle_id,
                    requested=nbytes,
                    quota=t.hbm_quota_bytes,
                    used=t.used_bytes,
                )
            t.used_bytes += nbytes

    def release(self, app_id: str, nbytes: int) -> None:
        """Return previously charged bytes (shuffle removed, round demoted to
        disk, store closed).  Tolerates unknown tenants — release must never
        fail a cleanup path."""
        if nbytes <= 0:
            return
        with self._lock:
            t = self._tenants.get(app_id)
            if t is not None:
                t.used_bytes = max(0, t.used_bytes - nbytes)

    def usage(self, app_id: str) -> int:
        with self._lock:
            t = self._tenants.get(app_id)
            return 0 if t is None else t.used_bytes

    # -- per-tenant wire credits ----------------------------------------
    def gate(self, app_id: str) -> Optional[CreditGate]:
        """The tenant's serving-plane CreditGate (lazily created), or None
        when the tenant has no credit budget — callers skip gating then.
        Unknown tenants raise, like every other tenant-addressed operation."""
        with self._lock:
            t = self._tenants.get(app_id)
            if t is None:
                raise UnknownTenantError(app_id)
            if t.credit_bytes <= 0:
                return None
            if t._gate is None:
                t._gate = CreditGate(t.credit_bytes)
            return t._gate

    # -- observability ---------------------------------------------------
    def stats(self) -> Dict[str, Dict[str, int]]:
        """Per-tenant usage snapshot: used/quota bytes and shuffle count."""
        with self._lock:
            out = {}
            for app_id, t in self._tenants.items():
                out[app_id] = {
                    "used_bytes": t.used_bytes,
                    "quota_bytes": t.hbm_quota_bytes,
                    "num_shuffles": sum(1 for k in self._sids if k[0] == app_id),
                }
            return out
