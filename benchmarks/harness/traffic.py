"""The one general traffic generator.  A mix is a data file
(`traffic/<name>.json`): loop kind, clients, statement classes with
integer weights.  Each class names a query file, whose substitution
parameters are a small fixed set of tuples; the seed decides the order
and nothing else, so every seed offers the same work.

    {"loop": "closed", "clients": 1,
     "classes": [{"query": "q1", "weight": 1}], ...}
"""

from __future__ import annotations

import random
import threading
import time


def deck(mix: dict, queries: dict, seed: int, client: int) -> list:
    """One client's cyclic order of (query name, tuple index): every
    class `weight` times for each of its parameter tuples, shuffled by
    the seed."""
    cards = []
    for cls in mix["classes"]:
        q = queries[cls["query"]]
        for i in range(len(q["params"])):
            cards += [(cls["query"], i)] * int(cls["weight"])
    random.Random(f"{seed}/{client}").shuffle(cards)
    return cards


def render(query: dict, index: int) -> str:
    return query["sql"].format(**query["params"][index])


class Statement:
    __slots__ = ("query", "index", "sql", "t_send_ns", "t_done_ns", "ok",
                 "error", "types", "rows", "client")

    def latency_ms(self) -> float:
        return (self.t_done_ns - self.t_send_ns) / 1e6


def _one(cli, client: int, name: str, index: int, sql: str, annotate):
    from .wire import ServerError

    st = Statement()
    st.query, st.index, st.sql, st.client = name, index, sql, client
    st.types, st.rows, st.error = None, None, None
    with annotate(f"stmt:{name}"):
        st.t_send_ns = time.perf_counter_ns()
        try:
            st.types, st.rows = cli.query(sql)
            st.ok = True
        except ServerError as e:
            st.ok, st.error = False, str(e)
        st.t_done_ns = time.perf_counter_ns()
    return st


class NoAnnotation:
    def __init__(self, _name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def closed_loop(connect, mix: dict, queries: dict, seed: int,
                seconds: float, annotate=NoAnnotation,
                max_statements: int = 0):
    """Drive `clients` closed loops for `seconds`: each sends its next
    statement when the last row of the previous one has come.  A
    statement begun inside the window is waited for.  Returns
    (statements in completion order, t0_ns, t_end_ns of the last one)."""
    n_clients = int(mix.get("clients", 1))
    clients = [connect() for _ in range(n_clients)]
    decks = [deck(mix, queries, seed, c) for c in range(n_clients)]
    done, mu = [], threading.Lock()
    t0 = time.perf_counter_ns()
    deadline = t0 + int(seconds * 1e9)

    def loop(c: int):
        i = 0
        while time.perf_counter_ns() < deadline:
            if max_statements and i >= max_statements:
                break
            name, index = decks[c][i % len(decks[c])]
            st = _one(clients[c], c, name, index,
                      render(queries[name], index), annotate)
            with mu:
                done.append(st)
            i += 1

    try:
        if n_clients == 1:
            loop(0)  # on the caller's thread: no thread to schedule
        else:
            threads = [threading.Thread(target=loop, args=(c,),
                                        name=f"bench-client-{c}")
                       for c in range(n_clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
    finally:
        for cli in clients:
            cli.close()
    t_end = max((s.t_done_ns for s in done), default=time.perf_counter_ns())
    return done, t0, t_end
