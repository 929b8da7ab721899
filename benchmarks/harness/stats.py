"""Percentile, rate and byte-count arithmetic; nothing but the standard
library, so the self-check can hold it to made-up inputs."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    closest ranks (numpy's default); raises on no values."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def rate(work: float, seconds: float) -> float:
    """All the work over all the time."""
    if seconds <= 0:
        raise ValueError("rate over no time")
    return work / seconds


def statement_bytes(query: dict, config: dict, rows: dict) -> int:
    """Bytes a statement must read: the rows of each table it names times
    the wire width of each column its SQL text names, each once.  A
    function of the query file, the configuration file and the rows
    loaded; whatever implements the scan, the work is the same."""
    total = 0
    for table, columns in query["columns"].items():
        width = sum(config["wire_bytes"][c] for c in columns)
        total += rows[table] * width
    return total


def statement_rows(query: dict, rows: dict) -> int:
    """Base-table rows named by the statement's FROM clause."""
    return sum(rows[t] for t in query["columns"])
