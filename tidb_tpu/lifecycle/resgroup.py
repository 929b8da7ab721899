"""Per-statement resource groups: token-bucket device-time quotas.

Reference: TiDB's resource-control subsystem (`CREATE RESOURCE GROUP
... RU_PER_SEC = n [BURSTABLE]`, user->group binding, the runaway
QUERY_LIMIT watchdog) — here the contended resource is the accelerator
itself, so one RU is one *device millisecond*.  Every device dispatch
(a mesh statement's one program, each tile of the tile loop, each MPP
rung, each micro-batch) passes through `dispatch_admission`, so groups
are admitted and charged per dispatch:

* **admit** — refill the statement's group by wall-clock elapsed x
  RU_PER_SEC and require a non-negative balance.  A depleted
  non-burstable group waits *in line* (interruptibly, polling the
  statement's QueryScope so KILL/timeout still preempt a throttled
  statement) up to a bounded budget, then raises the typed retriable
  `ResourceGroupThrottled`.  A depleted *burstable* group proceeds on
  debt — unless another group with a positive balance is waiting to
  dispatch, in which case it yields the device before this dispatch
  (the weighted-fair property: when quotas bind, device share tracks
  the RU_PER_SEC ratio because each group can only spend what its
  refill rate grants).
* **charge** — measured device milliseconds debit the bucket (balances
  go negative: debt is repaid out of future refill), feed the
  `resgroup_*` RU counters, and accumulate on the scope for
  QUERY_LIMIT enforcement: a statement past its group's limit is
  cancelled through the scope with reason ``resource_group`` — the
  same seam KILL rides.

The registry is domain-owned (one control plane per server); the
*group object* rides `QueryScope.resgroup`, so the dispatcher never
needs a domain lookup and fan-out workers inherit the binding through
`attach_scope`.  The registry mutex is a leaf: it is never held across
a wait or another lock acquisition (the admission wait POLLS
`scope.wait`, deliberately not a Condition — a held-lock wait is
exactly the hazard the lock witness and lint/concur's lock-wait rule
ban).
"""

from __future__ import annotations

import os
import time
from contextlib import contextmanager
from typing import Dict, Optional

from ..errors import ResourceGroupThrottled
from ..metrics import REGISTRY
from ..util_concurrency import make_lock

#: the implicit group every statement lands in absent a binding;
#: unlimited (ru_per_sec=0) so single-tenant deployments never throttle
DEFAULT_GROUP = "default"

#: bounded in-line wait for refill before ResourceGroupThrottled
#: (non-burstable depleted groups); overridable for tests
_MAX_WAIT_MS_ENV = "TIDB_TPU_RESGROUP_MAX_WAIT_MS"
_DEFAULT_MAX_WAIT_MS = 2000.0

#: admission poll period — short enough that a KILL reaches a parked
#: statement within milliseconds, long enough to not spin
_POLL_S = 0.005

#: a group counts as *contending* for the weighted-fair gate while a
#: thread is parked at its admission OR it dispatched this recently —
#: back-to-back dispatches never park, so recency is what makes two
#: busy statements visible to each other
_CONTEND_S = 0.05


def _max_wait_ms() -> float:
    try:
        return float(os.environ.get(_MAX_WAIT_MS_ENV,
                                    _DEFAULT_MAX_WAIT_MS))
    except ValueError:
        return _DEFAULT_MAX_WAIT_MS


class ResourceGroup:
    """One named group: a token bucket of device-milliseconds.

    Token state is guarded by the owning registry's mutex (one lock for
    the whole control plane: group counts are tiny and the hot path
    touches it twice per dispatch).  Balance may go negative — burstable
    debt and the unavoidable overshoot of charging *after* a dispatch
    completes — and is repaid from refill before new work admits.
    """

    __slots__ = ("name", "ru_per_sec", "burstable", "query_limit_ms",
                 "priority", "_reg", "_tokens", "_last_refill",
                 "_waiting", "_consumed", "_throttled", "_vtime",
                 "_last_arrival")

    def __init__(self, name: str, reg: "ResourceGroupRegistry",
                 ru_per_sec: int = 0, burstable: bool = False,
                 query_limit_ms: int = 0, priority: int = 1):
        self.name = name
        self._reg = reg
        self.ru_per_sec = int(ru_per_sec)
        self.burstable = bool(burstable)
        self.query_limit_ms = int(query_limit_ms)
        self.priority = max(1, int(priority))
        self._tokens = float(self.ru_per_sec)  # start with 1s of budget
        self._last_refill = time.monotonic()
        self._waiting = 0  # threads parked at admission
        self._consumed = 0.0  # lifetime RU (device-ms)
        self._throttled = 0  # ResourceGroupThrottled raises
        self._vtime = 0.0  # weighted-fair virtual finish tag
        self._last_arrival = 0.0  # monotonic of the last admit attempt

    # ---- bucket (callers hold reg._mu) ----------------------------------
    def _refill_locked(self, now: float):
        if self.ru_per_sec <= 0:
            return
        dt = now - self._last_refill
        if dt > 0:
            # cap at one second of budget: an idle group may burst one
            # refill period, not accumulate unbounded credit
            self._tokens = min(self._tokens + dt * self.ru_per_sec,
                               float(self.ru_per_sec))
        self._last_refill = now

    def _tokens_ok_locked(self, now: float) -> bool:
        self._refill_locked(now)
        if self.ru_per_sec <= 0:
            return True  # unlimited group
        if self._tokens > 0:
            return True
        if self.burstable:
            # debt allowed — but yield this dispatch to any group
            # that has budget and is waiting for the device
            return not self._reg._tokenful_waiters_locked(self)
        return False

    def _admissible_locked(self, now: float,
                           skip_priority: bool = False) -> bool:
        if not self._tokens_ok_locked(now):
            return False
        if skip_priority:
            # the bounded-wait pass-through: priority shapes the
            # admission ORDER, it never becomes a quota of its own
            tag = max(self._reg._vclock, self._vtime)
            self._vtime = tag + 1.0 / self.priority
            self._reg._vclock = tag
            return True
        return self._reg._priority_turn_locked(self, now)

    # ---- admission / charge ---------------------------------------------
    def admit(self, scope) -> float:
        """Block (interruptibly) until this group may dispatch once
        more; returns the milliseconds spent throttled.  Raises the
        scope's termination error if cancelled while waiting, or
        ResourceGroupThrottled past the bounded refill wait."""
        mu = self._reg._mu
        now = time.monotonic()
        with mu:
            self._last_arrival = now
            if self._admissible_locked(now):
                return 0.0
            self._waiting += 1
        t0 = now
        max_wait_s = _max_wait_ms() / 1000.0
        try:
            while True:
                if scope.wait(_POLL_S):
                    scope.check()  # cancelled while throttled
                now = time.monotonic()
                with mu:
                    self._last_arrival = now
                    if self._admissible_locked(now):
                        return (now - t0) * 1000.0
                if now - t0 >= max_wait_s:
                    wait_ms = (now - t0) * 1000.0
                    with mu:
                        # never throttle on priority alone: a group the
                        # weighted-fair gate kept holding back passes
                        # through at the wait bound if its tokens allow
                        if self._admissible_locked(
                                now, skip_priority=True):
                            return wait_ms
                        self._throttled += 1
                    REGISTRY.inc("resgroup_throttled_total")
                    REGISTRY.inc(
                        f"resgroup_{self.name}_throttled_total")
                    raise ResourceGroupThrottled(self.name, wait_ms)
        finally:
            with mu:
                self._waiting -= 1

    def charge(self, ms: float, scope) -> None:
        """Debit `ms` device-milliseconds; enforce QUERY_LIMIT through
        the scope (reason ``resource_group``)."""
        if ms < 0:
            ms = 0.0
        with self._reg._mu:
            self._refill_locked(time.monotonic())
            if self.ru_per_sec > 0:
                self._tokens -= ms
            self._consumed += ms
            limit = self.query_limit_ms
        REGISTRY.inc("resgroup_ru_consumed_total", ms)
        REGISTRY.inc(f"resgroup_{self.name}_ru_consumed_total", ms)
        total = scope.charge_device_ms(ms)
        if limit > 0 and total > limit:
            # the runaway watchdog: cancel through the scope so the
            # statement unwinds at its next seam with ONE reason
            scope.cancel("resource_group")

    # ---- reads -----------------------------------------------------------
    def snapshot(self) -> dict:
        with self._reg._mu:
            self._refill_locked(time.monotonic())
            return {
                "name": self.name,
                "ru_per_sec": self.ru_per_sec,
                "burstable": self.burstable,
                "query_limit_ms": self.query_limit_ms,
                "priority": self.priority,
                "tokens": round(self._tokens, 3),
                "waiting": self._waiting,
                "consumed_ru": round(self._consumed, 3),
                "throttled": self._throttled,
            }


class ResourceGroupRegistry:
    """The domain's named groups + user->group bindings."""

    def __init__(self):
        self._mu = make_lock(
            "lifecycle.resgroup:ResourceGroupRegistry._mu")
        self._groups: Dict[str, ResourceGroup] = {}
        self._bindings: Dict[str, str] = {}  # user -> group name
        self._groups[DEFAULT_GROUP] = ResourceGroup(DEFAULT_GROUP, self)
        self._plane = None  # coord plane for definition replication
        self._applied_version = 0  # last shared-store version applied
        self._vclock = 0.0  # weighted-fair virtual clock (SFQ)

    # callers hold self._mu
    def _tokenful_waiters_locked(self, skip: ResourceGroup) -> bool:
        for g in self._groups.values():
            if g is skip or g._waiting <= 0:
                continue
            if g.ru_per_sec <= 0 or g._tokens > 0:
                return True
        return False

    def _priority_turn_locked(self, g: ResourceGroup,
                              now: float) -> bool:
        """Weighted-fair admission order (start-time fair queueing over
        unit dispatches): a request's start tag is max(virtual clock, the
        group's finish tag), each admitted dispatch advances the finish
        tag by 1/PRIORITY, and a group dispatches only while no
        *contending* group holds a smaller start tag — so under
        sustained contention admissions track the priority ratio, and a
        group re-arriving after idling starts AT the clock (no banked
        virtual credit).  The gate is inert unless some contending
        group carries a DIFFERENT priority — equal-priority fleets keep
        the original FIFO+token behavior bit-for-bit, and a group
        running alone never pays the gate."""
        contenders = [o for o in self._groups.values()
                      if o is not g and (
                          o._waiting > 0
                          or now - o._last_arrival <= _CONTEND_S)]
        if not any(o.priority != g.priority for o in contenders):
            return True
        tag = max(self._vclock, g._vtime)
        for o in contenders:
            if max(self._vclock, o._vtime) + 1e-9 < tag:
                return False  # someone further behind goes first
        g._vtime = tag + 1.0 / g.priority
        self._vclock = tag
        return True

    # ---- DDL surface -----------------------------------------------------
    def create(self, name: str, ru_per_sec: int = 0,
               burstable: bool = False, query_limit_ms: int = 0,
               priority: int = 1,
               if_not_exists: bool = False) -> ResourceGroup:
        with self._mu:
            g = self._groups.get(name)
            if g is not None:
                if if_not_exists:
                    return g
                raise ValueError(
                    f"resource group {name!r} already exists")
            g = ResourceGroup(name, self, ru_per_sec, burstable,
                              query_limit_ms, priority)
            self._groups[name] = g
            return g

    def alter(self, name: str, ru_per_sec: Optional[int] = None,
              burstable: Optional[bool] = None,
              query_limit_ms: Optional[int] = None,
              priority: Optional[int] = None) -> ResourceGroup:
        with self._mu:
            g = self._groups.get(name)
            if g is None:
                raise KeyError(name)
            if ru_per_sec is not None:
                g.ru_per_sec = int(ru_per_sec)
                # re-seed one refill period so a raised quota takes
                # effect immediately rather than after the debt drains
                g._tokens = min(g._tokens, float(g.ru_per_sec))
                g._last_refill = time.monotonic()
            if burstable is not None:
                g.burstable = bool(burstable)
            if query_limit_ms is not None:
                g.query_limit_ms = int(query_limit_ms)
            if priority is not None:
                g.priority = max(1, int(priority))
            return g

    def drop(self, name: str, if_exists: bool = False):
        if name == DEFAULT_GROUP:
            raise ValueError("cannot drop the default resource group")
        with self._mu:
            if name not in self._groups:
                if if_exists:
                    return
                raise KeyError(name)
            del self._groups[name]
            self._bindings = {u: g for u, g in self._bindings.items()
                              if g != name}

    def bind_user(self, user: str, group: str):
        with self._mu:
            if group not in self._groups:
                raise KeyError(group)
            self._bindings[user] = group

    # ---- coord-plane replication (ISSUE 18 lifecycle (e)) ----------------
    def attach_plane(self, plane) -> None:
        """Opt this registry into fleet-wide definition replication:
        DDL publishes the full definition set into the coord plane's
        versioned shared store (it rides the membership broadcast), and
        `resolve` pulls newer versions before binding a statement.
        Detached registries (the default, and every standalone test
        domain) never touch the process-global plane."""
        self._plane = plane

    def defs_snapshot(self) -> dict:
        """The replicable definition state: quotas and bindings only —
        live token balances, debt and counters are per-host runtime
        state and never travel."""
        with self._mu:
            return {
                "groups": [
                    {"name": g.name, "ru_per_sec": g.ru_per_sec,
                     "burstable": g.burstable,
                     "query_limit_ms": g.query_limit_ms,
                     "priority": g.priority}
                    for g in self._groups.values()],
                "bindings": dict(self._bindings),
            }

    def publish(self) -> int:
        """Push this registry's definitions into the shared store
        (called from the DDL path after a successful mutation).  The
        publisher immediately adopts the version it wrote so its own
        next resolve() does not re-apply the echo."""
        plane = self._plane
        if plane is None:
            return 0
        doc = self.defs_snapshot()
        ver = plane.shared_put("resgroups", doc)
        with self._mu:
            if ver > self._applied_version:
                self._applied_version = ver
        REGISTRY.inc("resgroup_defs_published_total")
        return ver

    def maybe_sync(self) -> None:
        """Adopt newer fleet definitions if any arrived.  The common
        path is one integer compare against the plane's local shared
        cache — no RPC, no registry lock — so calling this on every
        statement-scope bind is free."""
        plane = self._plane
        if plane is None:
            return
        with self._mu:
            applied = self._applied_version
        try:
            if plane.shared_version("resgroups") <= applied:
                return
            doc, ver = plane.shared_get("resgroups")
        except Exception:
            REGISTRY.inc("resgroup_sync_errors_total")
            return
        if not isinstance(doc, dict):
            return
        with self._mu:
            if ver <= self._applied_version:
                return  # raced another sync
            self._apply_defs_locked(doc)
            self._applied_version = ver
        REGISTRY.inc("resgroup_defs_applied_total")

    def _apply_defs_locked(self, doc: dict) -> None:
        """Converge on the published definition set idempotently:
        update-in-place preserves live token balances and debt (a
        replicated ALTER must not hand every host a fresh bucket),
        absent groups are dropped, the default group survives with its
        replicated quota."""
        seen = set()
        for spec in doc.get("groups") or []:
            name = str(spec.get("name") or "")
            if not name:
                continue
            seen.add(name)
            g = self._groups.get(name)
            if g is None:
                self._groups[name] = ResourceGroup(
                    name, self, spec.get("ru_per_sec") or 0,
                    bool(spec.get("burstable")),
                    spec.get("query_limit_ms") or 0,
                    spec.get("priority") or 1)
                continue
            new_ru = int(spec.get("ru_per_sec") or 0)
            if new_ru != g.ru_per_sec:
                g.ru_per_sec = new_ru
                g._tokens = min(g._tokens, float(new_ru))
                g._last_refill = time.monotonic()
            g.burstable = bool(spec.get("burstable"))
            g.query_limit_ms = int(spec.get("query_limit_ms") or 0)
            g.priority = max(1, int(spec.get("priority") or 1))
        seen.add(DEFAULT_GROUP)
        for name in [n for n in self._groups if n not in seen]:
            del self._groups[name]
        self._bindings = {str(u): str(gn) for u, gn in
                          (doc.get("bindings") or {}).items()}

    # ---- resolution ------------------------------------------------------
    def get(self, name: str) -> Optional[ResourceGroup]:
        with self._mu:
            return self._groups.get(name)

    def resolve(self, user: str = "",
                sysvar: str = "") -> ResourceGroup:
        """The statement's group: session sysvar (non-empty) wins, then
        the user binding, then default.  Unknown names fall back to
        default rather than failing the statement — a dropped group
        must not break every bound session."""
        self.maybe_sync()  # adopt newer fleet definitions first
        with self._mu:
            name = sysvar or self._bindings.get(
                user.split("@", 1)[0], "") or DEFAULT_GROUP
            g = self._groups.get(name)
            if g is None:
                g = self._groups[DEFAULT_GROUP]
            return g

    def snapshot(self) -> list:
        with self._mu:
            groups = list(self._groups.values())
            bindings = dict(self._bindings)
        out = [g.snapshot() for g in groups]
        for row in out:
            row["users"] = sorted(
                u for u, gn in bindings.items() if gn == row["name"])
        return out


def scope_group(scope) -> Optional[ResourceGroup]:
    """The group riding a scope, or None (no session / unbound)."""
    return getattr(scope, "resgroup", None)


@contextmanager
def dispatch_admission(lock):
    """ONE dispatch's trip through the device door: weighted-fair
    admission against the statement's resource group, then `lock`
    (DISPATCH_LOCK), then — after release — charge the measured device
    time.  With no group bound this degenerates to `with lock:` plus
    two clock reads.

    The registry mutex is never held while waiting or while acquiring
    `lock`, and charging happens after the lock is released, so no new
    lock-order edges appear.

    The clock starts INSIDE the lock: the tenant is billed for measured
    device time on its dispatch, never for sitting in the DISPATCH_LOCK
    queue behind other tenants' dispatches — queue time is the scheduler's
    cost, and billing it would make one tenant's burst drain everyone
    else's RU budget.  That queue time is the trace's
    ``copr.dispatch.wait`` span: from asking for `lock` to holding it
    (the group's own wait before it stays ``resgroup.throttle``)."""
    from ..trace import span
    from .scope import current_scope

    scope = current_scope()
    group = scope_group(scope)
    if group is not None:
        _throttled_admit(group, scope)
    elapsed_ms = 0.0
    try:
        with span("copr.dispatch.wait"):
            lock.acquire()
        try:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                elapsed_ms = (time.perf_counter() - t0) * 1000.0
        finally:
            lock.release()
    finally:
        if group is not None:
            group.charge(elapsed_ms, scope)


@contextmanager
def chunk_admission():
    """Lock-free variant for dispatch paths that do not serialize on
    DISPATCH_LOCK (the per-tile engine loop, the serving
    micro-batcher's vmapped launch): admit + time + charge around one
    device call."""
    from .scope import current_scope

    scope = current_scope()
    group = scope_group(scope)
    if group is not None:
        _throttled_admit(group, scope)
    t0 = time.perf_counter()
    try:
        yield
    finally:
        if group is not None:
            group.charge((time.perf_counter() - t0) * 1000.0, scope)


def _throttled_admit(group: ResourceGroup, scope):
    """admit() + observability: the wait (if any) lands in the trace as
    a pre-timed ``resgroup.throttle`` span (phase `throttle_ms`) and
    the `resgroup_throttle_wait_ms` histogram."""
    wait_ms = group.admit(scope)
    if wait_ms > 0:
        REGISTRY.observe_hist("resgroup_throttle_wait_ms", wait_ms)
        from ..trace import current_trace

        tr = current_trace()
        if tr is not None:
            wait_ns = int(wait_ms * 1e6)
            tr.add_span("resgroup.throttle", wait_ns,
                        start_ns=time.perf_counter_ns() - wait_ns,
                        group=group.name)
