"""server, wire: what the server does around `Session.execute`, per
statement, median: `admission.wait` + `server.handoff` (loop thread to pool
thread) + `session.account` (the trace's finishing, slow log, SLO, after
the root closed) + `server.respond` (back to the loop, result encode and
write).  None where the program has no `server.handoff` span."""

from harness.spans import named, per_statement_ms
from harness.stats import median


def read(run):
    if not any(named(sp, "server.handoff") for sp in run["spans"]):
        return None
    return median(per_statement_ms(
        run["spans"], "admission.wait", "server.handoff", "session.account",
        "server.respond"))
