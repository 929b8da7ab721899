"""QueryScope: one statement's deadline + cancel flag, carried in a
contextvar alongside the trace recorder's span plane.

Reference: the reference enforces statement lifecycle *everywhere*, not
just at operator boundaries — expensivequery.go kills statements past
max_execution_time, the kill flag is polled inside coprocessor workers
and backoff sleeps (store/tikv/backoff.go checks vars.Killed), and
tidb-server drains connections on SIGTERM (server.go gracefulShutdown).

Here the TCR is a black-box batch device (PAPERS.md, "Query Processing
on Tensor Computation Runtimes"): an in-flight XLA dispatch cannot be
interrupted, so the *host-side* seams around each dispatch are the only
cancellation points we control.  Every blocking seam — backoff sleeps,
the distsql per-task loop, the copr mesh dispatch and tile loop, MPP
rung transitions, 2PC prewrite batches, DDL backfill batches — checks ONE
QueryScope between units of device work, so `KILL`, max_execution_time,
memory cancel, admission overload and server drain all ride the same
mechanism and report one termination reason.

The disabled path stays cheap: with no scope active, `current_scope()`
returns a process-global null scope whose check() is a no-op — one
contextvar read, mirroring the trace recorder's NOOP span contract.
Scope state is plain host Python; it must never capture into a compiled
program (lint.kernelcheck traces the kernel corpus under an active
deadline and asserts jaxpr parity).
"""

from __future__ import annotations

import threading
import time
from contextvars import ContextVar
from typing import Optional

from ..errors import (
    MaxExecutionTimeExceeded,
    QueryKilledError,
    ServerShutdownError,
    TiDBTPUError,
)
from ..util_concurrency import make_lock, witness_wait_check

#: termination reasons, in precedence order (first cancel wins)
REASONS = ("killed", "timeout", "mem_quota", "overload", "shutdown",
           "resource_group")


class QueryScope:
    """Deadline + cancel event + termination reason for ONE statement.

    Thread-safe: fan-out workers observe the same event the session
    thread (or the watchdog, or the draining server) sets.  The first
    cancel() fixes the reason; later cancels are ignored so a KILL
    racing a deadline reports deterministically.
    """

    __slots__ = ("start", "deadline", "cancel_event", "_reason", "_mu",
                 "resgroup", "_device_ms")

    def __init__(self, timeout_s: Optional[float] = None):
        self.start = time.monotonic()
        self.deadline = (self.start + timeout_s) if timeout_s else None
        self.cancel_event = threading.Event()
        self._reason: Optional[str] = None
        self._mu = make_lock("lifecycle.scope:QueryScope._mu")
        # resource-group binding (lifecycle/resgroup.py): the session
        # resolves the statement's group once at execute() and fan-out
        # workers inherit it via attach_scope — the dispatcher charges
        # device time against it per dispatch
        self.resgroup: Optional[str] = None
        self._device_ms = 0.0

    # ---- cancellation ---------------------------------------------------
    @property
    def reason(self) -> Optional[str]:
        with self._mu:
            return self._reason

    def cancel(self, reason: str):
        """Request termination; the statement unwinds at its next
        host-side seam.  First reason wins."""
        with self._mu:
            if self._reason is None:
                self._reason = reason
        self.cancel_event.set()

    def _deadline_passed(self) -> bool:
        if self.deadline is not None and time.monotonic() >= self.deadline:
            with self._mu:
                if self._reason is None:
                    self._reason = "timeout"
            self.cancel_event.set()
            return True
        return False

    def cancelled(self) -> bool:
        return self.cancel_event.is_set() or self._deadline_passed()

    # ---- device-time accounting (resource groups) -----------------------
    def charge_device_ms(self, ms: float) -> float:
        """Accumulate measured device time for QUERY_LIMIT enforcement;
        returns the statement's running total."""
        with self._mu:
            self._device_ms += ms
            return self._device_ms

    @property
    def device_ms(self) -> float:
        with self._mu:
            return self._device_ms

    # ---- the seam API ---------------------------------------------------
    def check(self):
        """Raise the termination error if this scope is cancelled or past
        its deadline.  Called between units of device work (a dispatch in
        flight cannot be interrupted; the next one must not start)."""
        if self.cancel_event.is_set() or self._deadline_passed():
            raise self.error()

    def wait(self, timeout_s: float) -> bool:
        """Interruptible sleep: block up to timeout_s OR until cancelled,
        whichever comes first; True when the scope is cancelled.  This is
        what Backoffer sleeps on, so KILL takes effect mid-backoff with
        bounded latency instead of after the full expo sleep."""
        if timeout_s <= 0:
            return self.cancelled()
        # held-lock waits deadlock under load (the canceller may need a
        # lower-ranked lock to reach cancel()); the witness trips here
        witness_wait_check("QueryScope.wait")
        if self.deadline is not None:
            timeout_s = min(timeout_s,
                            max(self.deadline - time.monotonic(), 0.0))
        return self.cancel_event.wait(timeout_s) or self.cancelled()

    def remaining_s(self) -> Optional[float]:
        if self.deadline is None:
            return None
        return max(self.deadline - time.monotonic(), 0.0)

    def error(self) -> TiDBTPUError:
        """The typed MySQL-coded error for this scope's termination."""
        r = self.reason or "killed"
        if r == "timeout":
            return MaxExecutionTimeExceeded()
        if r == "shutdown":
            return ServerShutdownError()
        return QueryKilledError()


class _NullScope(QueryScope):
    """Process-global scope when none is active: check() is a no-op and
    cancel() is swallowed (a global flag would poison every later
    statement).  wait() still sleeps — on an event nobody ever sets — so
    seam code needs no None-guards."""

    __slots__ = ()

    def cancel(self, reason: str):  # noqa: ARG002 - deliberately inert
        pass

    def cancelled(self) -> bool:
        return False

    def check(self):
        pass


NULL_SCOPE = _NullScope()

# the statement's scope (None = no lifecycle enforcement in this context)
_CUR: ContextVar[Optional[QueryScope]] = ContextVar(
    "tidb_tpu_lifecycle", default=None)


def current_scope() -> QueryScope:
    """The active scope, or the inert null scope — never None, so seams
    call `current_scope().check()` unconditionally."""
    sc = _CUR.get()
    return sc if sc is not None else NULL_SCOPE


def scope_active() -> bool:
    return _CUR.get() is not None


def scope_check():
    """Module-level seam hook: raise if the active statement was killed,
    timed out, or is being drained.  One contextvar read when inactive."""
    sc = _CUR.get()
    if sc is not None:
        sc.check()


def activate_scope(scope: QueryScope):
    """Install `scope` as current; returns the token for deactivate."""
    return _CUR.set(scope)


def deactivate_scope(token):
    _CUR.reset(token)


class _AttachCtx:
    __slots__ = ("_scope", "_token")

    def __init__(self, scope: QueryScope):
        self._scope = scope
        self._token = None

    def __enter__(self):
        self._token = _CUR.set(self._scope)
        return self._scope

    def __exit__(self, *exc):
        _CUR.reset(self._token)
        return False


def attach_scope(scope: Optional[QueryScope]):
    """Re-enter a scope on another thread (fan-out workers capture the
    submitting thread's scope, same shape as trace.attach)."""
    if not isinstance(scope, QueryScope) or isinstance(scope, _NullScope):
        return _NullAttach()
    return _AttachCtx(scope)


class _NullAttach:
    __slots__ = ()

    def __enter__(self):
        return NULL_SCOPE

    def __exit__(self, *exc):
        return False


def classify_termination(exc: Optional[BaseException],
                         scope: Optional[QueryScope]) -> str:
    """Map a statement outcome to its termination reason:
    ok | killed | timeout | mem_quota | overload | shutdown | error.
    A statement that COMPLETED is 'ok' even if a cancel raced its final
    moments (drain/watchdog firing as the result ships must not record
    a phantom interruption); for failed statements the scope's recorded
    reason wins over exception-type inference (a KILL surfacing as a
    generic error mid-fan-out still reports 'killed')."""
    if exc is None:
        return "ok"
    if scope is not None and scope.reason is not None:
        return scope.reason
    from ..errors import MemoryQuotaExceededError

    if isinstance(exc, MaxExecutionTimeExceeded):
        return "timeout"
    if isinstance(exc, MemoryQuotaExceededError):
        return "mem_quota"
    if isinstance(exc, ServerShutdownError):
        return "shutdown"
    if isinstance(exc, QueryKilledError):
        return "killed"
    return "error"
