#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip.  It makes the cell's configuration's tables
from the seed, loads them into a `Domain`, serves that with
`tidb_tpu.server.MySQLServer`, and drives the cell's traffic mix over a
socket with the benchmark's own MySQL client.  Every end-to-end number is
read on that client's clock; `correct` compares the rows those clients
received in the window with the plain reference's.  Earlier lines of
standard output are JSON notes; the last is the result.

Everything of one cell is data found by name: `BENCHMARK.json` gives the
cell its configuration and traffic mix, `configs/<name>.json`,
`traffic/<name>.json`, `queries/<name>.json` with the reference beside it
in `queries/<name>.py`, and `metrics/<name>.py` for each per-layer metric.

Options of the harness's own, which no cell uses: `--sf` runs the cell at
another scale factor, `--rehearse-cpu 1` lets it run without a TPU (both
mark the result `"rehearsal": true`; a rehearsal's numbers are never a
device's), `--control 1` also puts the lower-precision control in the
program's place and reports whether the comparison caught it.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import compare, spans as spans_mod, stats, traffic  # noqa: E402

FALLBACK_COUNTERS = (
    "mesh_scan_errors_total",
    "cop_tasks_device_fallback_total",
    "mpp_fallback_total",
    "mpp_tree_fallback_total",
    "mesh_failover_retries_total",
)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def note(obj: dict):
    print(json.dumps(obj), flush=True)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def resolve(workload: str) -> tuple:
    """(the benchmark's description, the cell's entry)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return bench, cell
    raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json")


def metrics_of(bench: dict, group: str, cell: str) -> list:
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


class Compiles:
    """Counts backend compiles as JAX reports them."""

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw):
        if event == COMPILE_EVENT:
            self.n += 1


def counters() -> dict:
    from tidb_tpu.metrics import REGISTRY

    return dict(REGISTRY.snapshot())


def warm_up(served, mix, queries, seed, collector, compiles) -> dict:
    """Every parameter tuple of every class once, then on until two
    statements in a row make the same number of passes and compile
    nothing.  Returns seconds of the first statement of each class."""
    cli = served.client()
    first = {}
    n, passes, quiet = 0, [], 0
    cards = traffic.deck(mix, queries, seed, client=-1)
    least = max(int(mix["warmup"]["least"]), len(set(cards)))
    try:
        while n < int(mix["warmup"]["most"]):
            name, index = cards[n % len(cards)]
            before = compiles.n
            n_traces = len(collector.traces)
            t0 = time.perf_counter()
            cli.query(traffic.render(queries[name], index))
            dt = time.perf_counter() - t0
            first.setdefault(name, dt)
            new = collector.flattened(n_traces)
            sp = new[-1]["spans"] if new else []
            passes.append(len(spans_mod.named(sp, "copr.chunk")))
            n += 1
            steady = (compiles.n == before and len(passes) > 1
                      and passes[-1] == passes[-2])
            quiet = quiet + 1 if steady else 0
            if n >= least and quiet >= 2:
                break
    finally:
        cli.close()
    note({"warm_up": {"statements": n, "passes": passes,
                      "first_statement_s": first}})
    return first


def device_note(devices) -> dict:
    stats_ = [d.memory_stats() or {} for d in devices]
    peak = max((s.get("peak_bytes_in_use") or 0) for s in stats_)
    note({"peak_bytes_in_use": peak,
          "bytes_limit": stats_[0].get("bytes_limit"),
          "bytes_in_use": [s.get("bytes_in_use") for s in stats_]})
    return peak


def read_trace(trace_dir, sent, window, statements, span_lists, rehearsal):
    """Reduce the profiler's trace.  Returns (device_trace for the
    readers, breakdown for the result line)."""
    from harness import xplane

    raw = xplane.read(xplane.find_xplane(trace_dir), rehearsal=rehearsal)
    off = xplane.clock_offset(raw["annotations"], sent)
    red = xplane.reduce(raw, (window[0] + off, window[1] + off))
    busy = xplane.first_device(raw)
    red["stmt_busy_s"] = [busy.within(st.t_send_ns + off, st.t_done_ns + off)
                          for st in statements]
    host = []
    for st, sp in zip(statements, span_lists):
        t0, t1 = st.t_send_ns + off, st.t_done_ns + off
        host.append((t0, t1, [(f"client: {st.query} on the wire", t0, t1, 0)]
                     + [(s["name"], s["start_ns"] + off,
                         s["start_ns"] + s["dur_ns"] + off, s["depth"] + 1)
                        for s in sp
                        if s["name"] != "wire.write" and s["dur_ns"]]))
    note({"device_programs": xplane.top(red["module_time"], 5),
          "trace_devices": red["devices"], "clock_offset_ns": off})
    return red, {"device_ops": xplane.top(red["op_time"]),
                 "idle_gaps": xplane.name_gaps(red["gaps"], host)}


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sf", type=float, default=None,
                    help="rehearsal: another scale factor than the cell's")
    ap.add_argument("--rehearse-cpu", type=int, choices=(0, 1), default=0,
                    help="rehearsal: run without a TPU")
    ap.add_argument("--keep-trace", default="",
                    help="copy the traced run's .xplane.pb into this directory")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also judge the lower-precision control")
    return ap.parse_args(argv)


class Annotated:
    """A `jax.profiler.TraceAnnotation` around one client statement, and
    the host clock read just inside it: the pair that ties the host's
    `perf_counter_ns` to the trace's clock."""

    def __init__(self, name: str, sent: list):
        import jax.profiler

        self.name, self.sent = name, sent
        self.inner = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self.inner.__enter__()
        self.sent.append((self.name, time.perf_counter_ns()))

    def __exit__(self, *exc):
        return self.inner.__exit__(*exc)


def start_profiler(trace_dir: str):
    import jax.profiler

    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def judge_control(statements, used, refs, queries, tables, answers):
    """Put the lower-precision control's answers in the program's place
    and judge them as a run's are judged."""
    lower = {(q, i): refs[q].control(tables, queries[q]["params"][i])
             for q, i in used}
    fake = []
    for st in statements:
        f = traffic.Statement()
        f.query, f.index, f.sql, f.ok, f.error = (
            st.query, st.index, st.sql, True, None)
        f.rows = [tuple(None if v is None else str(v) for v in r)
                  for r in lower[(st.query, st.index)]]
        fake.append(f)
    cv = compare.judge(fake, answers)
    note({"control": {"caught": not cv["correct"], "numbers": cv["numbers"],
                      "first_difference": cv["first_difference"]}})


def end_to_end(statements, window_s, setup_s, queries, rows) -> dict:
    ok = [st for st in statements if st.ok]
    lat = [st.latency_ms() for st in ok]
    values = {"setup_s": setup_s}
    if lat:
        values.update({
            "stmt_p50_ms": stats.median(lat),
            "stmt_p95_ms": stats.percentile(lat, 95.0),
            "stmts_per_s": stats.rate(len(ok), window_s),
            "rows_per_s": stats.rate(
                sum(stats.statement_rows(queries[st.query], rows)
                    for st in ok), window_s),
        })
    tuples = sorted({(st.query, st.index) for st in ok})
    note({"window_s": window_s, "statements": len(statements),
          "completed": len(ok), "rows": rows,
          "latency_ms_min_max": [min(lat), max(lat)] if lat else None,
          "p50_ms_by_tuple": {f"{q}/{i}": stats.median(
              [st.latency_ms() for st in ok if (st.query, st.index) == (q, i)])
              for q, i in tuples},
          "all_end_to_end": values})
    return values


def main(argv=None) -> int:
    args = parse_args(argv)
    bench, cell = resolve(args.workload)
    config = load_json("configs", cell["config"] + ".json")
    mix = load_json("traffic", cell["traffic"] + ".json")
    queries = {c["query"]: load_json("queries", c["query"] + ".json")
               for c in mix["classes"]}
    refs = {name: importlib.import_module(f"queries.{name}")
            for name in queries}
    sf = config["scale_factor"] if args.sf is None else args.sf
    rehearsal = bool(args.rehearse_cpu) or sf != config["scale_factor"]

    import jax

    import tidb_tpu.ops  # noqa: F401 — configures jax (x64, compile cache)

    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.rehearse_cpu:
        print(f"run.py: no TPU: jax.devices() = {devices}", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"run.py: the cell asks for {cell['chips']} chips, "
              f"jax.devices() = {devices}", file=sys.stderr)
        return 2
    devices = devices[:cell["chips"]]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    peaks = None
    if not args.rehearse_cpu:
        from harness.peaks import peaks_for

        peaks = peaks_for(device["kind"])
    note({"cell": cell["name"], "seed": args.seed, "scale_factor": sf,
          "rehearsal": rehearsal, "device": device,
          "compile_cache_dir": jax.config.jax_compilation_cache_dir})

    from harness import serve

    # set-up: load, serve, warm up
    compiles = Compiles()
    t0 = time.perf_counter()
    domain, tables, load_s = serve.load(config, args.seed, sf, note)
    rows = {t: int(len(next(iter(cols.values()))))
            for t, cols in tables.items()}
    setup = {"load_s": time.perf_counter() - t0}
    note({"setup_load": load_s})
    served = serve.Served(domain)
    collector = spans_mod.SpanCollector()
    collector.start()
    trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
    try:
        first = warm_up(served, mix, queries, args.seed, collector, compiles)
        setup["first_stmt_s"] = sum(first.values())
        collector.traces.clear()
        if args.trace:
            start_profiler(trace_dir)
        else:
            collector.stop()

        # the window
        sent = []
        c0, n_compiles = counters(), compiles.n
        setup_s = time.perf_counter() - T_START
        statements, w0, w1 = traffic.closed_loop(
            served.client, mix, queries, args.seed, args.seconds,
            (lambda name: Annotated(name, sent)) if args.trace
            else traffic.NoAnnotation)
        compile_events = compiles.n - n_compiles
        c1 = counters()
        if args.trace:
            jax.profiler.stop_trace()
            collector.stop()
        peak = device_note(devices)
    finally:
        served.stop()
    window_s = (w1 - w0) / 1e9
    moved = {k: c1.get(k, 0) - c0.get(k, 0) for k in FALLBACK_COUNTERS}
    if compile_events or any(moved.values()):
        note({"warning": "inside the window", "backend_compiles":
              compile_events, "fallback_counters_moved": moved})

    # the reference, on the host, once the server is stopped and the
    # device's peak is read
    t_ref = time.perf_counter()
    used = sorted({(st.query, st.index) for st in statements})
    answers = {(q, i): refs[q].reference(tables, queries[q]["params"][i])
               for q, i in used}
    verdict = compare.judge(statements, answers)
    note({"reference_s": time.perf_counter() - t_ref, "tuples": len(used)})
    if verdict["first_difference"]:
        note({"first_difference": verdict["first_difference"]})
    if args.control:
        judge_control(statements, used, refs, queries, tables, answers)

    values = end_to_end(statements, window_s, setup_s, queries, rows)
    result = {"correct": verdict["correct"], "attempted": len(statements),
              "failed": sum(1 for st in statements if not st.ok),
              "metrics": {}, "device": dict(device, memory_peak_bytes=peak)}
    if rehearsal:
        result["rehearsal"] = True
    if not args.trace:
        for m in metrics_of(bench, "end_to_end", cell["name"]):
            if m["name"] in values:
                result["metrics"][m["name"]] = {"value": values[m["name"]],
                                                "unit": m["unit"]}
    else:
        span_lists = spans_mod.attach(statements, collector.flattened())
        dev, breakdown = read_trace(trace_dir, sent, (w0, w1),
                                    statements, span_lists,
                                    bool(args.rehearse_cpu))
        if args.keep_trace:
            from harness import xplane

            os.makedirs(args.keep_trace, exist_ok=True)
            shutil.copy(xplane.find_xplane(trace_dir), args.keep_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        run = {"statements": statements, "spans": span_lists,
               "fallbacks_moved": moved, "compile_events": compile_events,
               "device_trace": dev, "setup": setup, "config": config,
               "mix": mix, "queries": queries, "rows": rows, "peaks": peaks,
               "stmt_bytes": [stats.statement_bytes(
                   queries[st.query], config, rows) for st in statements]}
        for m in metrics_of(bench, "per_layer", cell["name"]):
            v = importlib.import_module(f"metrics.{m['name']}").read(run)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["device"].update(busy_s=dev["busy_s"],
                                window_s=dev["window_s"])
        result["breakdown"] = breakdown
    result["compared"] = verdict["numbers"]
    for name, n in verdict["numbers"].items():
        print(f"compared {name}: {json.dumps(n)}", file=sys.stderr)
    print(f"correct: {verdict['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
