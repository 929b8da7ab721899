"""What the plain references share: dates, exact decimals, blocks."""

from decimal import ROUND_HALF_UP, Decimal, localcontext

import numpy as np

BLOCK = 1 << 22


def days(iso: str) -> int:
    return int(np.datetime64(iso).astype("datetime64[D]").astype(int))


def iso(day: int) -> str:
    return str(np.datetime64(int(day), "D"))


def dec(scaled: int, scale: int) -> Decimal:
    """An integer of `scale` implied decimal places, as an exact Decimal."""
    return Decimal(int(scaled)).scaleb(-scale)


def avg(total_scaled: int, count: int, scale: int) -> Decimal:
    """MySQL's AVG of a decimal: four more places, rounded half up."""
    with localcontext() as ctx:
        ctx.prec = 60
        q = Decimal(1).scaleb(-(scale + 4))
        return (dec(total_scaled, scale) / count).quantize(
            q, rounding=ROUND_HALF_UP)


def from_float(x: float, scale: int) -> Decimal:
    """A float result put back on the decimal grid (the control's path)."""
    with localcontext() as ctx:
        ctx.prec = 60
        return Decimal(float(x)).quantize(Decimal(1).scaleb(-scale),
                                          rounding=ROUND_HALF_UP)


def blocks(n: int):
    for s in range(0, n, BLOCK):
        yield slice(s, min(s + BLOCK, n))
