"""SQL plan management (bindinfo-lite).

Reference: bindinfo/handle.go:122 (the bind-record cache consulted before
planning), :545 (capture), bindinfo/session_handle.go (SESSION scope).
Grammar matches the reference:

    CREATE [GLOBAL | SESSION] BINDING FOR <stmt> USING <hinted stmt>
    DROP   [GLOBAL | SESSION] BINDING FOR <stmt>
    SHOW   [GLOBAL | SESSION] BINDINGS

Bindings key on the normalized digest of the original statement; when a
statement's digest matches, the HINTED statement's AST is planned instead
and its /*+ ... */ hints override the optimizer knobs for that plan only
(the planner consults them through Session._pctx).  Supported hints:
MERGE_JOIN, HASH_JOIN, INL_JOIN / INDEX_JOIN, INL_HASH_JOIN,
NO_INDEX_JOIN.  Global bindings live on the Domain, session bindings on
the Session; SESSION shadows GLOBAL (bindinfo/session_handle.go order).
"""

from __future__ import annotations

import re
from typing import Optional, Tuple

from ..errors import PlanError
from ..parser import parse
from .domain import sql_digest

_BINDING_RE = re.compile(
    r"^\s*(create|drop)\s+(?:(global|session)\s+)?binding\s+for\s",
    re.I | re.S)
_SHOW_RE = re.compile(
    r"^\s*show\s+(?:(global|session)\s+)?bindings\s*;?\s*$", re.I)
_HINT_RE = re.compile(r"/\*\+(.*?)\*/", re.S)


def is_binding_stmt(sql: str) -> bool:
    return bool(_BINDING_RE.match(sql) or _SHOW_RE.match(sql))


def extract_hints(sql: str) -> frozenset:
    names = set()
    for body in _HINT_RE.findall(sql):
        for tok in re.split(r"[\s,()]+", body):
            if tok:
                names.add(tok.lower())
    return frozenset(names)


def _split_for_using(tail: str) -> Tuple[str, str]:
    """'<orig> USING <hinted>' -> (orig, hinted): the splitting USING is at
    paren-depth 0, outside quotes, followed by a statement keyword (so JOIN
    ... USING (cols) never matches)."""
    low = tail.lower()
    depth = 0
    i, n = 0, len(tail)
    while i < n:
        c = tail[i]
        if c in "'\"":
            q = c
            i += 1
            while i < n and tail[i] != q:
                i += 2 if tail[i] == "\\" else 1
            i += 1
            continue
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        elif depth == 0 and low.startswith("using", i) and \
                (i == 0 or not low[i - 1].isalnum()) and \
                (i + 5 >= n or not low[i + 5].isalnum()):
            rest = low[i + 5:].lstrip()
            if re.match(r"(/\*|select|insert|update|delete)\b", rest) or \
                    rest.startswith("/*"):
                return tail[:i].strip(), tail[i + 5:].strip()
        i += 1
    raise PlanError("CREATE BINDING requires USING <hinted statement>")


def _store(session, is_global: bool) -> dict:
    if is_global:
        if not hasattr(session.domain, "bindings"):
            session.domain.bindings = {}
        return session.domain.bindings
    if not hasattr(session, "_bindings"):
        session._bindings = {}
    return session._bindings


def _bump(session, is_global: bool):
    if is_global:
        session.domain.bindings_version = getattr(
            session.domain, "bindings_version", 0) + 1
    else:
        session._bindings_version = getattr(
            session, "_bindings_version", 0) + 1


def handle(session, sql: str):
    from .session import ResultSet

    m = _SHOW_RE.match(sql)
    if m:
        scope = (m.group(1) or "session").lower()
        rows = []
        for scope_name, store in (("session", _store(session, False)),
                                  ("global", _store(session, True))):
            if scope in (scope_name,) or m.group(1) is None:
                for digest, b in sorted(store.items()):
                    rows.append((b["original"], b["hinted"], scope_name))
        return ResultSet(["Original_sql", "Bind_sql", "Scope"], rows,
                         is_query=True)
    m = _BINDING_RE.match(sql)
    verb = m.group(1).lower()
    is_global = (m.group(2) or "session").lower() == "global"
    # binding DDL short-circuits the normal statement path (execute()
    # dispatches here before parsing), so the privilege and snapshot
    # guards must run here (ADVICE r4 #3):
    # - it is a write: reject under SET tidb_snapshot
    # - GLOBAL bindings rewrite every session's plans: SUPER required
    #   (TiDB gates global bind DDL the same way)
    if session._snapshot_ts is not None:
        from ..errors import ExecutorError

        raise ExecutorError(
            "can not execute write statement when 'tidb_snapshot' is set")
    if is_global:
        session.domain.priv.require(
            session.user, "super",
            roles=tuple(getattr(session, "active_roles", ())))
    tail = sql[m.end():].strip().rstrip(";")
    if verb == "create":
        orig, hinted = _split_for_using(tail)
        # both sides must parse, and they must normalize to the SAME
        # digest (bindinfo/handle.go CreateBindRecord validation): the
        # binding carries HINTS for the user's statement — it never
        # substitutes the stored literals for the incoming ones
        parse(orig)
        clean = re.sub(r"/\*.*?\*/", " ", hinted, flags=re.S)
        parse(clean)
        if sql_digest(orig) != sql_digest(clean):
            raise PlanError(
                "CREATE BINDING: the hinted statement must match the "
                "original (same normalized digest)")
        store = _store(session, is_global)
        store[sql_digest(orig)] = {
            "original": orig,
            "hinted": hinted,
            "hints": extract_hints(hinted),
        }
        _bump(session, is_global)
        return ResultSet()
    # DROP
    digest = sql_digest(tail)
    store = _store(session, is_global)
    if store.pop(digest, None) is not None:
        if is_global:
            # a dropped captured binding must be RE-capturable: forget the
            # sighting count so two fresh sightings trigger capture again
            getattr(session.domain, "_capture_seen", {}).pop(digest, None)
        _bump(session, is_global)
    return ResultSet()


def apply_binding(session, stmt) -> Tuple[object, Optional[frozenset]]:
    """Attach a matched binding's HINTS to the user's statement
    (handle.go:122 — the match runs on the normalized digest before
    planning).  The incoming statement is NEVER swapped for the stored
    text: literals differ between digest-equal statements, and executing
    the stored literals would return another query's answer."""
    sql = getattr(stmt, "_sql_text", None)
    if sql is None:
        return stmt, None
    # EXPLAIN wraps the statement: bindings match the inner text
    probe = re.sub(r"^\s*(explain|trace)\s+(analyze\s+)?", "", sql,
                   flags=re.I)
    digest = sql_digest(probe)
    b = _store(session, False).get(digest) or \
        _store(session, True).get(digest)
    if b is None:
        return stmt, None
    from ..metrics import REGISTRY

    REGISTRY.inc("binding_hits_total")
    return stmt, b["hints"]


# ---------------------------------------------------------------------------
# baseline capture (bindinfo/handle.go:545 CaptureBaselines role)
# ---------------------------------------------------------------------------


def _plan_hints(phys) -> frozenset:
    """Derive optimizer hints that pin the CURRENT plan's join choices
    (what the reference encodes as bind SQL hint comments)."""
    hints = set()

    def walk(p):
        nm = type(p).__name__
        if nm == "PhysMergeJoin":
            hints.add("merge_join")
        elif nm == "PhysIndexJoin":
            hints.add("inl_join")
        elif nm in ("PhysHashJoin", "PhysDeviceJoinReader"):
            # the device broadcast join IS the hash join relocated into
            # the cop task; HASH_JOIN re-plans to the same family
            hints.add("hash_join")
        for c in getattr(p, "children", []):
            walk(c)
        r = getattr(p, "reader", None)
        if r is not None:
            walk(r)

    walk(phys)
    return frozenset(hints)


def maybe_capture(session, sql: str, stmt, phys) -> None:
    """When tidb_capture_plan_baselines is on, a SELECT digest seen for
    the SECOND time captures a GLOBAL binding that pins its current plan
    (handle.go:545 — capture runs off stmt-summary frequency >= 2).

    Guards mirror explicit CREATE GLOBAL BINDING (handle()): capture
    publishes into every session's plans, so only SUPER sessions
    capture, never under tidb_snapshot, and never from a plan that a
    SESSION binding shaped (a private experiment must not go global)."""
    try:
        if not session.vars.get_bool("tidb_capture_plan_baselines"):
            return
    except Exception:
        return
    from ..parser import ast

    if not isinstance(stmt, (ast.SelectStmt,)):
        return
    if session._snapshot_ts is not None:
        return
    if not session.domain.priv.check(
            session.user, "super",
            roles=tuple(getattr(session, "active_roles", ()))):
        return
    digest = sql_digest(sql)
    if digest in _store(session, False):
        return  # session-binding-shaped plan: don't promote it globally
    dom = session.domain
    seen = getattr(dom, "_capture_seen", None)
    if seen is None:
        seen = dom._capture_seen = {}
    if len(seen) >= 4096 and digest not in seen:
        seen.clear()  # bounded, like the stmt-summary cap
    n = seen.get(digest, 0) + 1
    seen[digest] = n
    # capture exactly on the second sighting; DROP BINDING resets the
    # counter (handle() pops _capture_seen), so a dropped captured
    # binding is recapturable by two fresh sightings without paying the
    # hint walk on every later execution
    if n != 2:
        return
    store = _store(session, True)
    if digest in store:
        return  # explicit binding wins
    hints = _plan_hints(phys)
    if not hints:
        return  # nothing plan-shaping to pin: a binding would be noise
    hint_txt = "/*+ " + ", ".join(sorted(h.upper() for h in hints)) + " */ "
    m = re.match(r"\s*select\b", sql, re.I)
    if m is None:
        return
    hinted = sql[:m.end() - 6] + "select " + hint_txt + sql[m.end():]
    store[digest] = {
        "original": sql,
        "hinted": hinted,
        "hints": hints,
        "captured": True,
    }
    _bump(session, True)
