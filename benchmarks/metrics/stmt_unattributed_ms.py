"""server, wire: the client's send-to-last-row time less the union of the
statement's program spans clipped to that interval, median: what no span
of the program covers (the socket and the client).  Reads whatever spans
the program has, so it also says how dark an older program's statements
were."""

from harness.stats import median


def uncovered_ns(t0: int, t1: int, spans: list) -> int:
    """ns of [t0, t1) under none of the spans."""
    dark, cur = 0, t0
    for s, e in sorted((sp["start_ns"], sp["start_ns"] + sp["dur_ns"])
                       for sp in spans if sp["dur_ns"]):
        s, e = min(max(s, t0), t1), min(e, t1)
        if e <= cur:
            continue
        dark += max(s - cur, 0)
        cur = e
    return dark + max(t1 - cur, 0)


def read(run):
    per = [uncovered_ns(st.t_send_ns, st.t_done_ns, sp) / 1e6
           for st, sp in zip(run["statements"], run["spans"]) if sp]
    return median(per) if per else None
