"""Columnar vector with Arrow-style validity.

Reference: /root/reference/util/chunk/column.go:59-67 — nullBitmap / offsets /
data / elemBuf.  TPU-native departure: instead of byte-packed bitmaps and
variable-length byte buffers, a Column is

- ``data``: a dense numpy array of the type's physical dtype (object dtype for
  host-side strings), always length ``n``
- ``valid``: None (all rows valid) or a bool numpy array, True = non-NULL

Fixed-width everything means a column converts to a jax array with zero copies
or reshapes; strings are dictionary-encoded before they reach a device (see
store/blockstore.py).
"""

from __future__ import annotations

from typing import Iterable, Optional, Sequence

import numpy as np

from ..types import FieldType, TypeKind


class Column:
    __slots__ = ("ftype", "data", "valid")

    def __init__(self, ftype: FieldType, data: np.ndarray, valid: Optional[np.ndarray] = None):
        self.ftype = ftype
        self.data = data
        if valid is not None and valid.dtype != np.bool_:
            valid = valid.astype(np.bool_)
        if valid is not None and bool(valid.all()):
            valid = None  # normalize: all-valid -> None
        self.valid = valid

    # ---- constructors -------------------------------------------------
    @staticmethod
    def _object_fill(ftype: FieldType) -> object:
        """NULL placeholder inside object-dtype data arrays."""
        if ftype.kind == TypeKind.DECIMAL:
            return 0  # wide decimal: exact Python ints
        return ""  # STRING / JSON

    @staticmethod
    def from_values(ftype: FieldType, values: Sequence) -> "Column":
        """Build from a python sequence of PHYSICAL-repr values (scaled ints
        for decimals, member indexes for enums, ...); None entries -> NULL."""
        n = len(values)
        valid = np.fromiter((v is not None for v in values), dtype=np.bool_, count=n)
        all_valid = bool(valid.all())
        dt = ftype.np_dtype
        if dt == object:
            fill = Column._object_fill(ftype)
            data = np.empty(n, dtype=object)
            for i, v in enumerate(values):
                data[i] = v if v is not None else fill
        else:
            data = np.zeros(n, dtype=dt)
            if all_valid:
                data[:] = np.asarray(values, dtype=dt)
            else:
                for i, v in enumerate(values):
                    if v is not None:
                        data[i] = v
        return Column(ftype, data, None if all_valid else valid)

    @staticmethod
    def nulls(ftype: FieldType, n: int) -> "Column":
        if ftype.np_dtype == object:
            data = np.empty(n, dtype=object)
            data[:] = Column._object_fill(ftype)
        else:
            data = np.zeros(n, dtype=ftype.np_dtype)
        return Column(ftype, data, np.zeros(n, dtype=np.bool_))

    @staticmethod
    def constant(ftype: FieldType, value, n: int) -> "Column":
        if value is None:
            return Column.nulls(ftype, n)
        if ftype.np_dtype == object:
            data = np.empty(n, dtype=object)
            data[:] = value
        else:
            data = np.full(n, value, dtype=ftype.np_dtype)
        return Column(ftype, data)

    # ---- basic properties ---------------------------------------------
    def __len__(self) -> int:
        return len(self.data)

    @property
    def has_nulls(self) -> bool:
        return self.valid is not None

    def validity(self) -> np.ndarray:
        """Materialized bool validity array (True = non-NULL)."""
        if self.valid is None:
            return np.ones(len(self.data), dtype=np.bool_)
        return self.valid

    def null_count(self) -> int:
        return 0 if self.valid is None else int((~self.valid).sum())

    def is_null(self, i: int) -> bool:
        return self.valid is not None and not bool(self.valid[i])

    def get(self, i: int):
        """Python scalar at row i (None for NULL)."""
        if self.is_null(i):
            return None
        v = self.data[i]
        if isinstance(v, np.generic):
            return v.item()
        return v

    # ---- transforms ----------------------------------------------------
    def take(self, idx: np.ndarray) -> "Column":
        return Column(
            self.ftype,
            self.data[idx],
            None if self.valid is None else self.valid[idx],
        )

    def filter(self, mask: np.ndarray) -> "Column":
        return Column(
            self.ftype,
            self.data[mask],
            None if self.valid is None else self.valid[mask],
        )

    def slice(self, start: int, stop: int) -> "Column":
        return Column(
            self.ftype,
            self.data[start:stop],
            None if self.valid is None else self.valid[start:stop],
        )

    def concat(self, other: "Column") -> "Column":
        data = np.concatenate([self.data, other.data])
        if self.valid is None and other.valid is None:
            valid = None
        else:
            valid = np.concatenate([self.validity(), other.validity()])
        return Column(self.ftype, data, valid)

    def copy(self) -> "Column":
        return Column(
            self.ftype,
            self.data.copy(),
            None if self.valid is None else self.valid.copy(),
        )

    def to_pylist(self) -> list:
        return [self.get(i) for i in range(len(self))]

    def nbytes(self) -> int:
        if self.data.dtype != object:
            b = self.data.nbytes
        elif self.ftype.kind == TypeKind.DECIMAL:
            b = 16 * len(self.data)  # exact ints of up to 38 digits
        else:
            b = sum(len(str(x)) for x in self.data)
        if self.valid is not None:
            b += self.valid.nbytes
        return int(b)

    def __repr__(self):
        return f"Column({self.ftype!r}, n={len(self)}, nulls={self.null_count()})"
