"""Chunk: an ordered batch of equal-length Columns.

Reference: /root/reference/util/chunk/chunk.go:32 (Chunk), :152-166
(RequiredRows early stop), iterator.go (Iterator4Chunk).  Executors pull
chunks through ``Next(chunk)``; a chunk of 0 rows signals exhaustion.
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence

import numpy as np

from ..types import FieldType
from .column import Column

# Default max rows per chunk flowing between root executors (reference
# variable tidb_max_chunk_size, default 1024).
DEFAULT_CHUNK_SIZE = 1024


class Chunk:
    __slots__ = ("columns",)

    def __init__(self, columns: List[Column]):
        self.columns = columns
        if columns:
            n = len(columns[0])
            for c in columns[1:]:
                assert len(c) == n, "ragged chunk"

    # ---- constructors --------------------------------------------------
    @staticmethod
    def empty(ftypes: Sequence[FieldType]) -> "Chunk":
        return Chunk([Column.from_values(ft, []) for ft in ftypes])

    @staticmethod
    def from_columns(columns: List[Column]) -> "Chunk":
        return Chunk(columns)

    # ---- shape ---------------------------------------------------------
    @property
    def num_rows(self) -> int:
        return len(self.columns[0]) if self.columns else 0

    @property
    def num_cols(self) -> int:
        return len(self.columns)

    @property
    def ftypes(self) -> List[FieldType]:
        return [c.ftype for c in self.columns]

    def __len__(self) -> int:
        return self.num_rows

    # ---- access --------------------------------------------------------
    def col(self, i: int) -> Column:
        return self.columns[i]

    def row(self, i: int) -> tuple:
        return tuple(c.get(i) for c in self.columns)

    def iter_rows(self) -> Iterator[tuple]:
        for i in range(self.num_rows):
            yield self.row(i)

    def to_pylist(self) -> list:
        """List of row tuples (test/result-set friendly)."""
        return [self.row(i) for i in range(self.num_rows)]

    # ---- transforms ----------------------------------------------------
    def take(self, idx: np.ndarray) -> "Chunk":
        return Chunk([c.take(idx) for c in self.columns])

    def filter(self, mask: np.ndarray) -> "Chunk":
        return Chunk([c.filter(mask) for c in self.columns])

    def slice(self, start: int, stop: int) -> "Chunk":
        return Chunk([c.slice(start, stop) for c in self.columns])

    def select(self, col_idx: Sequence[int]) -> "Chunk":
        return Chunk([self.columns[i] for i in col_idx])

    def append(self, other: "Chunk") -> "Chunk":
        assert self.num_cols == other.num_cols
        return Chunk([a.concat(b) for a, b in zip(self.columns, other.columns)])

    def split(self, max_rows: int = DEFAULT_CHUNK_SIZE) -> Iterator["Chunk"]:
        n = self.num_rows
        if n == 0:
            return
        for s in range(0, n, max_rows):
            yield self.slice(s, min(s + max_rows, n))

    def nbytes(self) -> int:
        return sum(c.nbytes() for c in self.columns)

    def __repr__(self):
        return f"Chunk(rows={self.num_rows}, cols={self.num_cols})"


def chunk_from_pylists(ftypes: Sequence[FieldType], cols: Sequence[Sequence]) -> Chunk:
    assert len(ftypes) == len(cols)
    return Chunk([Column.from_values(ft, vs) for ft, vs in zip(ftypes, cols)])


def concat_chunks(chunks: Sequence[Chunk]) -> Optional[Chunk]:
    """All the chunks' rows as one chunk, each column copied once."""
    chunks = [c for c in chunks if c is not None and c.num_rows >= 0]
    if not chunks:
        return None
    if len(chunks) == 1:
        return chunks[0]
    cols = []
    for parts in zip(*(c.columns for c in chunks)):
        valid = None
        if any(p.valid is not None for p in parts):
            valid = np.concatenate([p.validity() for p in parts])
        cols.append(Column(parts[0].ftype,
                           np.concatenate([p.data for p in parts]), valid))
    return Chunk(cols)
