"""Shape-class bucketing policy.

Sizes pad UP to a class, and padded slots are masked — never read as
data.  A compiled XLA program is specialized on its operand shapes, so
bucketing makes the program cache key a function of the size CLASS
rather than the literal size: a TopN limit changing 5 -> 7 or a
micro-batch filling 3 of 4 slots reuse the same compiled program, and so
does a table growing 130 -> 140 tiles a shard.

Two rules.  Small things whose padding costs next to nothing (TopN
budgets, probe-key pads, micro-batch slots, the plan cache's row class)
take the next power of two: `shape_bucket`.  A shard's tile count, whose
padding is scanned at full price by every statement, steps in eighths of
its power of two: `tile_bucket`.
"""

from __future__ import annotations


def shape_bucket(n: int, floor: int = 1) -> int:
    """Next power of two >= max(n, floor)."""
    n = max(int(n), int(floor), 1)
    return 1 << (n - 1).bit_length()


def tile_bucket(n: int) -> int:
    """Tiles of one shard for `n` tiles of rows: the next power of two up
    to 8, then the next multiple of an eighth of n's own power of two and
    never of less than 8 (16, 24, ... 64, 72, 80, ... 128, 144, ...).
    At most 12.5% of padding (7 tiles under 64), eight shapes an octave,
    and above 8 always whole groups of 8 tiles, which the dense
    aggregate's blocked row view needs (copr/parallel._RowView)."""
    n = int(n)
    if n <= 8:
        return shape_bucket(n)
    g = max(8, 1 << (n.bit_length() - 4))
    return -(-n // g) * g


def topn_budget(limit: int) -> int:
    """Device TopN budget for a LIMIT: pow2-bucketed with a floor of 16
    so nearby limits share one compiled kernel (the exact limit is
    re-applied host-side by the final merge)."""
    from . import shape_buckets_enabled

    if not shape_buckets_enabled():
        return max(int(limit), 1)
    return shape_bucket(limit, floor=16)
