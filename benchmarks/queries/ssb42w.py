"""Plain reference of SSB Q4.2 at scale factor 100: `ssb42.py`'s query
answered in blocks of the fact table (the shared engine is `_ssbw.py`).
`control` is the same with float32 sums."""

from . import _ssbw as w
from .ssb42 import Q


def reference(tables, _p):
    return w.reference(tables, Q)


def control(tables, _p):
    return w.control(tables, Q)
