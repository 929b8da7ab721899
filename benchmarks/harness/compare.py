"""The comparison that decides `correct`: the rows the wire clients
received in the timed window against the plain reference's, exactly.
Numbers as exact decimals (the text the server sent, parsed; no float),
everything else as text."""

from __future__ import annotations

from decimal import Decimal, InvalidOperation


def same_value(got, want) -> bool:
    if got is None or want is None:
        return got is None and want is None
    if isinstance(want, str):
        return got == want
    try:
        return Decimal(got) == Decimal(want)
    except InvalidOperation:
        return False


def same_rows(got: list, want: list) -> bool:
    if got is None or len(got) != len(want):
        return False
    return all(len(g) == len(w) and all(same_value(a, b)
                                        for a, b in zip(g, w))
               for g, w in zip(got, want))


def judge(statements: list, answers: dict) -> dict:
    """`answers[(query, index)]` is the reference's row list.  Every
    statement the window completed is compared.  Returns the numbers
    compared, each beside its limit, and the first difference."""
    wrong = failed = compared = 0
    first = None
    for st in statements:
        if not st.ok:
            failed += 1
            first = first or {"sql": st.sql[:120], "error": st.error}
            continue
        compared += 1
        want = answers[(st.query, st.index)]
        if not same_rows(st.rows, want):
            wrong += 1
            first = first or {"sql": st.sql[:120],
                              "got": _clip(st.rows), "want": _clip(want)}
    numbers = {
        "stmts_wrong": {"value": wrong, "limit": 0},
        "stmts_failed": {"value": failed, "limit": 0},
        "stmts_compared": {"value": compared, "at_least": 1},
    }
    ok = wrong == 0 and failed == 0 and compared >= 1
    return {"correct": ok, "numbers": numbers, "first_difference": first}


def _clip(rows, n: int = 3):
    if rows is None:
        return None
    return [[str(v) for v in r] for r in rows[:n]]
