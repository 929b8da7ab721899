"""Plain reference of SSB Q3.2 at scale factor 100: `ssb32.py`'s query
answered in blocks of the fact table (the shared engine is `_ssbw.py`).
`control` is the same with float32 sums."""

from . import _ssbw as w
from .ssb32 import Q


def reference(tables, _p):
    return w.reference(tables, Q)


def control(tables, _p):
    return w.control(tables, Q)
