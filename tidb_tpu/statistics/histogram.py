"""Equi-depth histograms + Count-Min sketch + FM sketch.

Reference: statistics/histogram.go:42 (equi-depth Histogram with per-bucket
count/repeat), statistics/cmsketch.go:40, statistics/fmsketch.go.  Vectorized
builds: one np.sort per column instead of the reference's per-row insertion.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import numpy as np


def sorted_ints(values: np.ndarray) -> np.ndarray:
    """An integer column sorted, in the narrowest integer type that holds
    it (a 16-bit sort is a counting sort, a 32-bit one half the bytes)."""
    if len(values):
        lo, hi = int(values.min()), int(values.max())
        for dt in (np.int16, np.int32):
            if np.iinfo(dt).min <= lo and hi <= np.iinfo(dt).max:
                values = values.astype(dt, copy=False)
                break
    return np.sort(values)


@dataclass
class Bucket:
    upper: float  # inclusive upper bound
    lower: float
    count: int  # rows in this bucket
    repeat: int  # rows equal to upper


class Histogram:
    """Equi-depth histogram over numeric representations (strings hash to
    dictionary codes before reaching here)."""

    def __init__(self, buckets: List[Bucket], null_count: int, ndv: int,
                 total: int):
        self.buckets = buckets
        self.null_count = null_count
        self.ndv = ndv
        self.total = total  # non-null rows

    @staticmethod
    def build(values: np.ndarray, null_count: int = 0,
              n_buckets: int = 64, presorted: bool = False) -> "Histogram":
        n = len(values)
        if n == 0:
            return Histogram([], null_count, 0, 0)
        if presorted and values.dtype.kind in "iu" \
                and max(abs(int(values[0])), abs(int(values[-1]))) < 1 << 53:
            # sorted integers that float64 holds exactly: read as they are
            v = values
        else:
            v = (values.astype(np.float64, copy=False) if presorted
                 else np.sort(values.astype(np.float64, copy=False)))
        ndv = int(np.count_nonzero(v[1:] != v[:-1])) + 1
        per = max(n // n_buckets, 1)
        buckets: List[Bucket] = []
        i = 0
        while i < n:
            j = min(i + per, n)
            upper = v[j - 1]
            right = int(np.searchsorted(v, upper, "right"))
            # extend to include all duplicates of upper (repeat semantics;
            # a NaN upper has none)
            if upper == upper:
                j = max(j, right)
            repeat = int(right - np.searchsorted(v, upper, "left"))
            buckets.append(Bucket(float(upper), float(v[i]), j - i, repeat))
            i = j
        return Histogram(buckets, null_count, ndv, n)

    @staticmethod
    def from_counts(values: np.ndarray, counts: np.ndarray,
                    null_count: int = 0,
                    n_buckets: int = 64) -> "Histogram":
        """The histogram `build` makes of an integer column whose distinct
        values, sorted, are `values`, with `counts[i]` rows of each."""
        if len(values) and max(abs(int(values[0])),
                               abs(int(values[-1]))) >= 1 << 53:
            # `build` reads such a column as float64: values that round
            # to one float are one value
            f = values.astype(np.float64)
            first = np.flatnonzero(np.concatenate(([True], f[1:] != f[:-1])))
            values, counts = f[first], np.add.reduceat(counts, first)
        n = int(counts.sum()) if len(counts) else 0
        if n == 0:
            return Histogram([], null_count, 0, 0)
        ends = np.cumsum(counts)  # a value's rows end before ends[k]
        per = max(n // n_buckets, 1)
        buckets: List[Bucket] = []
        i = 0
        while i < n:
            # the bucket's upper is the value at row min(i + per, n) - 1,
            # and takes all its duplicates
            k = int(np.searchsorted(ends, min(i + per, n) - 1, "right"))
            j = int(ends[k])
            lower = values[int(np.searchsorted(ends, i, "right"))]
            buckets.append(Bucket(float(values[k]), float(lower), j - i,
                                  int(counts[k])))
            i = j
        return Histogram(buckets, null_count, len(values), n)

    # ------------------------------------------------------------------
    def row_count(self) -> int:
        return self.total + self.null_count

    def less_row_count(self, x: float) -> float:
        """Estimated rows with value < x."""
        acc = 0.0
        for b in self.buckets:
            if x > b.upper:
                acc += b.count
            elif x <= b.lower:
                break
            else:
                width = b.upper - b.lower
                frac = (x - b.lower) / width if width > 0 else 0.0
                acc += (b.count - b.repeat) * frac
                break
        return acc

    def equal_row_count(self, x: float) -> float:
        for b in self.buckets:
            if b.lower <= x <= b.upper:
                if x == b.upper:
                    return float(b.repeat)
                return max(b.count / max(self.ndv_in_bucket(), 1), 1.0)
        return 0.0

    def ndv_in_bucket(self) -> int:
        return max(self.ndv // max(len(self.buckets), 1), 1)

    def between_row_count(self, lo: Optional[float], hi: Optional[float],
                          lo_open: bool = False,
                          hi_open: bool = True) -> float:
        """rows in [lo, hi) by default; None = unbounded."""
        if self.total == 0:
            return 0.0
        a = self.less_row_count(lo) + (self.equal_row_count(lo) if lo_open else 0.0) \
            if lo is not None else 0.0
        b = self.less_row_count(hi) + (0.0 if hi_open else self.equal_row_count(hi)) \
            if hi is not None else float(self.total)
        return max(b - a, 0.0)


class CMSketch:
    """Count-Min sketch for point-equality estimates (cmsketch.go:40)."""

    def __init__(self, depth: int = 4, width: int = 2048):
        self.depth = depth
        self.width = width
        self.table = np.zeros((depth, width), dtype=np.int64)
        self.count = 0

    _SEEDS = (0x9E3779B97F4A7C15, 0xC2B2AE3D27D4EB4F,
              0x165667B19E3779F9, 0x27D4EB2F165667C5)

    def _hash(self, vals: np.ndarray) -> np.ndarray:
        """[depth, n] bucket indices (splitmix-style avalanche)."""
        x = vals.astype(np.uint64)
        out = np.empty((self.depth, len(vals)), dtype=np.int64)
        w = np.uint64(self.width)
        pow2 = self.width & (self.width - 1) == 0
        h = np.empty_like(x)
        t = np.empty_like(x)
        for d in range(self.depth):
            # in place: at tens of millions of rows the temporaries of
            # the expression form cost more than the arithmetic
            np.add(x, np.uint64(self._SEEDS[d]), out=h)
            for shift, mul in ((30, 0xBF58476D1CE4E5B9),
                               (27, 0x94D049BB133111EB)):
                np.right_shift(h, np.uint64(shift), out=t)
                np.bitwise_xor(h, t, out=h)
                np.multiply(h, np.uint64(mul), out=h)
            np.right_shift(h, np.uint64(31), out=t)
            np.bitwise_xor(h, t, out=h)
            # a 64-bit modulo is a division a row; a power of two masks
            if pow2:
                np.bitwise_and(h, w - np.uint64(1), out=t)
            else:
                np.remainder(h, w, out=t)
            out[d] = t
        return out

    def insert_batch(self, vals: np.ndarray, counts=None):
        """Count every value of `vals` once, or `counts[i]` times."""
        idx = self._hash(vals)
        for d in range(self.depth):
            self.table[d] += np.bincount(
                idx[d], weights=counts, minlength=self.width
            ).astype(np.int64)
        self.count += len(vals) if counts is None else int(counts.sum())

    def query(self, val: int) -> int:
        idx = self._hash(np.array([val], dtype=np.int64))
        est = min(int(self.table[d][idx[d][0]]) for d in range(self.depth))
        # noise correction (classic CM bias adjustment)
        noise = self.count / self.width
        return max(int(est - noise), 0)


class FMSketch:
    """Flajolet-Martin distinct-count sketch (statistics/fmsketch.go)."""

    def __init__(self, max_size: int = 10000):
        self.max_size = max_size
        self.mask = np.uint64(0)
        self.hashset: set = set()

    def insert_batch(self, vals: np.ndarray):
        x = vals.astype(np.uint64)
        h = x * np.uint64(0x9E3779B97F4A7C15)
        h = h ^ (h >> np.uint64(29))
        for v in h:
            v = np.uint64(v)
            if (v & self.mask) == 0:
                self.hashset.add(int(v))
                if len(self.hashset) > self.max_size:
                    self.mask = (self.mask << np.uint64(1)) | np.uint64(1)
                    self.hashset = {
                        s for s in self.hashset
                        if (np.uint64(s) & self.mask) == 0
                    }

    def ndv(self) -> int:
        return (int(self.mask) + 1) * len(self.hashset)
