"""Take the program's finished span trees, as they are, through the hook
the program's trace recorder offers (`chain_export_hook`), and flatten
them.  Spans stay on the host's `perf_counter_ns` clock."""

from __future__ import annotations


class SpanCollector:
    def __init__(self):
        self.traces = []

    def start(self):
        from tidb_tpu.trace import recorder

        recorder.chain_export_hook(self._on_trace)

    def stop(self):
        from tidb_tpu.trace import recorder

        recorder.unchain_export_hook(self._on_trace)

    def _on_trace(self, tr):
        self.traces.append(tr)

    def flattened(self, start: int = 0) -> list:
        """Of the traces from `start` on: [{sql, start_ns, end_ns, spans: [{name, start_ns, dur_ns, depth,
        attrs}]}] in the order the statements finished."""
        out = []
        for tr in self.traces[start:]:
            spans = []
            _walk(tr.root, 0, spans)
            out.append({"sql": tr.sql, "start_ns": tr.root.start_ns,
                        "end_ns": tr.root.start_ns + (tr.root.dur_ns or 0),
                        "spans": spans})
        return out


def _walk(span, depth: int, out: list):
    out.append({"name": span.name, "start_ns": span.start_ns,
                "dur_ns": span.dur_ns or 0, "depth": depth,
                "attrs": dict(span.attrs or {})})
    for c in list(span.children):
        _walk(c, depth + 1, out)


def attach(statements: list, traces: list) -> list:
    """For each client statement, the span list of the server-side
    execution that began inside its send-to-last-row interval ([] where
    none did)."""
    by_sql = {}
    for tr in traces:
        by_sql.setdefault(tr["sql"], []).append(tr)
    out = []
    for st in statements:
        found = next((tr for tr in by_sql.get(st.sql, ())
                      if st.t_send_ns <= tr["start_ns"] <= st.t_done_ns), None)
        out.append(found["spans"] if found else [])
    return out


def named(spans: list, *names) -> list:
    return [s for s in spans if s["name"] in names]


def per_statement_ms(span_lists: list, *names) -> list:
    """Summed time of the named spans, for each statement that has any."""
    return [sum(s["dur_ns"] for s in found) / 1e6
            for found in (named(sp, *names) for sp in span_lists) if found]
