"""Reduce a `jax.profiler` trace (`*.xplane.pb`) to what the benchmark
reports: the union of the intervals in which an operation ran on each
device, time by operation and by program, and the idle gaps named by what
the host was doing.  Read with `jax.profiler.ProfileData`, nothing else.

Device planes are `/device:TPU:<n>`; on each, the line `XLA Ops` holds one
event for each operation run and `XLA Modules` one for each program.  In
a rehearsal on the CPU there is no device plane, and the events that carry
an `hlo_op` on the PjRt client's threads stand in, so that the code below
runs; a rehearsal's numbers are never a device's.

Host spans meet the trace's clock through the `TraceAnnotation` the
harness opens around every client statement: the same moment read on
`perf_counter_ns` and found in the trace gives the offset.
"""

from __future__ import annotations

import bisect
import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read(path: str, rehearsal: bool = False) -> dict:
    """{devices: {plane: {ops: [(name, start_ns, end_ns)], modules: [...]}},
    annotations: [(name, start_ns, end_ns)]} with times as the trace has
    them."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, annotations = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [_triple(e) for e in line.events]
                elif line.name == MODULES_LINE:
                    modules = [_triple(e) for e in line.events]
            devices[plane.name] = {"ops": ops, "modules": modules or ops}
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                stand_in = rehearsal and line.name.startswith("tf_XLAPjRt")
                for e in line.events:
                    if e.name.startswith("stmt:"):
                        annotations.append(_triple(e))
                    elif stand_in and _stat(e, "hlo_op") is not None:
                        d = devices.setdefault(
                            "/rehearsal:CPU", {"ops": [], "modules": []})
                        d["ops"].append(_triple(e))
                        mod = _stat(e, "hlo_module")
                        d["modules"].append(
                            (str(mod),) + _triple(e)[1:])
    annotations.sort(key=lambda t: t[1])
    return {"devices": devices, "annotations": annotations}


def _triple(e):
    s = int(e.start_ns)
    return (short_name(e.name), s, s + int(e.duration_ns))


def short_name(name: str) -> str:
    """`%fusion.22 = (u32[...]) fusion(...)` -> `fusion.22`: the HLO
    instruction's own name, which is what the trace repeats run to run."""
    return name.split(" = ", 1)[0].lstrip("%")[:96]


def _stat(e, key: str):
    for k, v in e.stats:
        if k == key:
            return v
    return None


def clock_offset(annotations: list, sent: list) -> int:
    """trace time minus host time, ns.  `sent` is [(name, perf_counter_ns
    just inside the annotation)] in order; the i-th annotation of the
    trace is the i-th the harness opened.  The median of the differences,
    so that one late reading does no harm."""
    n = min(len(annotations), len(sent))
    if n == 0:
        raise ValueError("no statement annotation found in the trace")
    diffs = sorted(annotations[i][1] - sent[i][1] for i in range(n))
    return diffs[n // 2]


class Busy:
    """The union of one device's operation intervals, indexed so that the
    busy time inside any interval is two bisections."""

    def __init__(self, ops: list):
        self.starts, self.ends, self.before = [], [], [0]
        for s, e in sorted((s, e) for _, s, e in ops):
            if self.ends and s <= self.ends[-1]:
                if e > self.ends[-1]:
                    self.before[-1] += e - self.ends[-1]
                    self.ends[-1] = e
            else:
                self.starts.append(s)
                self.ends.append(e)
                self.before.append(self.before[-1] + e - s)

    def within(self, t0: int, t1: int) -> float:
        """Busy seconds inside [t0, t1)."""
        return (self._upto(t1) - self._upto(t0)) / 1e9 if t1 > t0 else 0.0

    def _upto(self, t: int) -> int:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0
        return self.before[i] - max(0, self.ends[i - 1] - t)

    def gaps(self, w0: int, w1: int) -> list:
        """[(start_ns, end_ns)] inside the window in which nothing ran."""
        out, cur = [], w0
        i = max(bisect.bisect_right(self.starts, w0) - 1, 0)
        for s, e in zip(self.starts[i:], self.ends[i:]):
            if s >= w1:
                break
            if s > cur:
                out.append((cur, s))
            cur = max(cur, e)
        if cur < w1:
            out.append((cur, w1))
        return out


def first_device(trace: dict) -> Busy:
    for _name, d in sorted(trace["devices"].items()):
        return Busy(d["ops"])
    raise ValueError("the trace holds no device plane")


def reduce(trace: dict, window: tuple) -> dict:
    """`window` is (start_ns, end_ns) on the trace's clock.  busy_s is the
    union of operation intervals inside it, averaged over the devices;
    the idle gaps are the first device's."""
    w0, w1 = window
    if not trace["devices"]:
        raise ValueError("the trace holds no device plane")
    per_device, op_time, module_time = [], {}, {}
    for _name, d in sorted(trace["devices"].items()):
        per_device.append(Busy(d["ops"]).within(w0, w1))
        for times, events in ((op_time, d["ops"]),
                              (module_time, d["modules"])):
            for n, s, e in events:
                if e > w0 and s < w1:
                    times[n] = times.get(n, 0.0) + (
                        min(e, w1) - max(s, w0)) / 1e9
    return {"busy_s": sum(per_device) / len(per_device),
            "window_s": (w1 - w0) / 1e9, "op_time": op_time,
            "module_time": module_time,
            "gaps": first_device(trace).gaps(w0, w1),
            "devices": len(per_device)}


def name_gaps(gaps: list, statements: list, top: int = 10) -> list:
    """Total idle seconds by what the host was doing: each gap goes to the
    deepest host span open at its middle.  `statements` is
    [(start_ns, end_ns, [(name, start_ns, end_ns, depth)])] on the trace's
    clock, one entry a client statement with its own span first."""
    statements = sorted(statements, key=lambda st: st[0])
    starts = [st[0] for st in statements]
    by = {}
    for s, e in gaps:
        mid = (s + e) // 2
        best = None
        i = bisect.bisect_right(starts, mid)
        for t0, t1, spans in statements[max(i - 64, 0):i]:
            if t1 <= mid:
                continue
            for name, hs, he, depth in spans:
                if hs <= mid < he and (best is None or depth >= best[1]):
                    best = (name, depth)
        label = best[0] if best else "no statement open"
        by[label] = by.get(label, 0.0) + (e - s) / 1e9
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:top]]


def top(times: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(times.items(), key=lambda kv: -kv[1])[:n]]
