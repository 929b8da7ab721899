"""Expression tree -> jax computation (device eval path).

The device analog of expr/builtins.py: the same trees the host evaluates with
numpy are traced into a jitted XLA program here.  Values flow as
(data, valid) pairs of jnp arrays; dict-encoded string columns arrive as
int32 code arrays (the planner/engine rewrites string constants to codes
before compilation — see jax_engine.rewrite_for_dict).

Everything here must be jit-traceable: no data-dependent Python control flow,
static shapes only (TILE-padded), jnp.where instead of branching.
"""

from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from ..expr.expression import ColumnExpr, Constant, Expression, ScalarFunc
from ..types import FieldType, TypeKind, common_compare_type
from ..types.values import parse_date, parse_datetime


class JaxUnsupported(Exception):
    """Raised when an expression/DAG can't run on the device; callers fall
    back to the CPU engine (the canFuncBePushed miss path)."""


JVal = Tuple[jnp.ndarray, jnp.ndarray]  # (data, valid)


def _np_dtype_for(ft: FieldType):
    if ft.kind == TypeKind.JSON or (ft.kind == TypeKind.DECIMAL
                                    and ft.is_wide_decimal):
        # object-dtype host representations never land on the device
        raise JaxUnsupported(f"{ft.sql_name()} column is host-only")
    if ft.kind == TypeKind.FLOAT:
        return jnp.float64
    if ft.kind == TypeKind.DATE:
        return jnp.int32
    if ft.kind == TypeKind.STRING:
        return jnp.int32  # dictionary codes
    return jnp.int64


def compile_expr(e: Expression, cols: Dict[int, JVal], n) -> JVal:
    """`e` over the column environment `cols`; `n` is the rows' shape, a
    length or (a dense aggregate's row view) a tuple."""
    if isinstance(e, ColumnExpr):
        if e.index not in cols:
            raise JaxUnsupported(f"column {e.index} not device-resident")
        return cols[e.index]
    if isinstance(e, Constant):
        slot = getattr(e, "param_slot", None)
        if slot is not None and "__params__" in cols:
            return _param_const(e, slot, cols["__params__"], n)
        return _const(e, n)
    if isinstance(e, ScalarFunc):
        fn = _FUNCS.get(e.name)
        if fn is None:
            raise JaxUnsupported(f"function {e.name} not device-compilable")
        args = [compile_expr(a, cols, n) for a in e.args]
        return fn(e, args, n)
    raise JaxUnsupported(f"expression {e!r}")


def _param_const(e: Constant, slot, params, n: int) -> JVal:
    """A hoisted constant (serving/params.py ParamConst): its value reads
    from the runtime parameter vectors at EXECUTION time instead of being
    baked into the program as an XLA literal — parameter-different queries
    of the same shape class share one compiled program, and the
    micro-batcher vmaps over a stack of these vectors."""
    which, idx = slot
    pi, pf = params
    src = pf[idx] if which == "f" else pi[idx]
    return (
        jnp.broadcast_to(src.astype(_np_dtype_for(e.ftype)),
                         n if isinstance(n, tuple) else (n,)),
        jnp.ones(n, dtype=jnp.bool_),
    )


def _const(e: Constant, n: int) -> JVal:
    ft = e.ftype
    if e.value is None:
        return (
            jnp.zeros(n, dtype=_np_dtype_for(ft)),
            jnp.zeros(n, dtype=jnp.bool_),
        )
    v = e.value
    if ft.kind == TypeKind.STRING:
        if not isinstance(v, (int,)):
            raise JaxUnsupported("raw string constant on device")
        # dictionary code constant (rewritten)
        return jnp.full(n, v, dtype=jnp.int32), jnp.ones(n, dtype=jnp.bool_)
    if ft.kind == TypeKind.DATE and isinstance(v, str):
        v = parse_date(v)
    if ft.kind == TypeKind.DATETIME and isinstance(v, str):
        v = parse_datetime(v)
    return (
        jnp.full(n, v, dtype=_np_dtype_for(ft)),
        jnp.ones(n, dtype=jnp.bool_),
    )


def _to_f64(v: JVal, ft: FieldType) -> jnp.ndarray:
    d = v[0]
    if ft.kind == TypeKind.DECIMAL:
        return d.astype(jnp.float64) / (10.0 ** ft.scale)
    return d.astype(jnp.float64)


def _udiv_const(x: jnp.ndarray, p: int) -> jnp.ndarray:
    """Exact trunc(x / p) for NON-NEGATIVE int64 x and a small positive
    constant p, without integer division.

    TPUs have no integer-divide unit: XLA emulates `int64 //` with a long
    software sequence (~100ns/row measured on v5e — a single 2M-row decimal
    rescale cost ~0.2s, dominating Q1/Q6).  Instead: split x into 32-bit
    halves so every f64 intermediate is exact (< 2^53 needs p <= ~2e6),
    divide with a reciprocal multiply, and absorb f64 rounding with one
    multiply-back fixup.  Exact for all x >= 0 when p <= 1_000_000;
    callers fall back to native emulation above that.
    """
    if p == 1:
        return x
    inv = 1.0 / p
    hi = jax.lax.shift_right_logical(x, 32)
    lo = jnp.bitwise_and(x, 0xFFFFFFFF)
    # hi < 2^32 is f64-exact; q1 may still be off by 1 from inv rounding
    q1 = jnp.floor(hi.astype(jnp.float64) * inv).astype(jnp.int64)
    r1 = hi - q1 * p  # in (-p, 2p) even when q1 is off by one
    rest = (r1 << 32) + lo  # |rest| < 2p*2^32 <= 2^53 for p <= 1e6
    q2 = jnp.floor(rest.astype(jnp.float64) * inv).astype(jnp.int64)
    q = (q1 << 32) + q2
    rem = x - q * p
    q = q + (rem >= p).astype(jnp.int64) - (rem < 0).astype(jnp.int64)
    rem = x - q * p
    return q + (rem >= p).astype(jnp.int64) - (rem < 0).astype(jnp.int64)


def _chunk_const(p: int):
    """Factor p into chunks each <= 1e6 (trunc division composes across
    positive factors); None if a prime factor is too big for the f64 trick."""
    factors = []
    rem = p
    for q in (2, 3, 5, 7, 11, 13):
        while rem % q == 0:
            factors.append(q)
            rem //= q
    if rem > 1:
        if rem > 1_000_000:
            return None
        factors.append(rem)
    chunks, cur = [], 1
    for f in sorted(factors, reverse=True):
        if cur * f <= 1_000_000:
            cur *= f
        else:
            chunks.append(cur)
            cur = f
    chunks.append(cur)
    return chunks


def _utrunc_div(x: jnp.ndarray, p: int) -> jnp.ndarray:
    """trunc(x / p) for non-negative x, chunking p when needed."""
    if p <= 1_000_000:
        return _udiv_const(x, p)
    chunks = _chunk_const(p)
    if chunks is None:
        return x // p
    for c in chunks:
        x = _udiv_const(x, c)
    return x


def _round_div_pow10(d: jnp.ndarray, p: int) -> jnp.ndarray:
    """round-half-away-from-zero of d / p (p = 10^k), division-free:
    the MySQL decimal rounding rule (types/mydecimal.go analog).
    Rounds via the remainder (not abs(d)+p/2, which overflows at int64 max)."""
    ad = jnp.abs(d)
    q = _utrunc_div(ad, p)
    rem = ad - q * p
    q = q + (2 * rem >= p).astype(jnp.int64)
    return jnp.sign(d).astype(jnp.int64) * q


def _floordiv_const(d: jnp.ndarray, p: int) -> jnp.ndarray:
    """Python-semantics d // p (floor) for int64 d, division-free."""
    if _chunk_const(p) is None:
        return d // p
    ad = jnp.abs(d)
    q = _utrunc_div(ad, p)
    rem_nz = (ad - q * p) != 0
    return jnp.where(d >= 0, q, -q - rem_nz.astype(jnp.int64))


def _to_scaled(v: JVal, ft: FieldType, scale: int) -> jnp.ndarray:
    d = v[0]
    if ft.kind == TypeKind.DECIMAL:
        ds = scale - ft.scale
        if ds == 0:
            return d.astype(jnp.int64)
        if ds > 0:
            return d.astype(jnp.int64) * (10 ** ds)
        return _round_div_pow10(d.astype(jnp.int64), 10 ** (-ds))
    if ft.kind == TypeKind.FLOAT:
        return jnp.round(d * (10.0 ** scale)).astype(jnp.int64)
    return d.astype(jnp.int64) * (10 ** scale)


_FUNCS: Dict[str, Callable] = {}


def _reg(*names):
    def deco(fn):
        for nm in names:
            _FUNCS[nm] = fn
        return fn

    return deco


def _both_valid(a: JVal, b: JVal) -> jnp.ndarray:
    return a[1] & b[1]


# ---- arithmetic ------------------------------------------------------------


@_reg("+", "-", "*", "/", "div", "%")
def _arith(e: ScalarFunc, args, n):
    op = e.name
    a, b = args
    fa, fb = e.args[0].ftype, e.args[1].ftype
    out = e.ftype
    valid = _both_valid(a, b)
    if out.kind == TypeKind.FLOAT:
        x, y = _to_f64(a, fa), _to_f64(b, fb)
        if op == "+":
            r = x + y
        elif op == "-":
            r = x - y
        elif op == "*":
            r = x * y
        elif op == "/":
            bad = y == 0.0
            r = x / jnp.where(bad, 1.0, y)
            valid = valid & ~bad
        elif op == "%":
            bad = y == 0.0
            r = jnp.fmod(x, jnp.where(bad, 1.0, y))
            valid = valid & ~bad
        else:
            raise JaxUnsupported("float div")
        return r, valid
    if out.kind == TypeKind.DECIMAL:
        sa = fa.scale if fa.kind == TypeKind.DECIMAL else 0
        sb = fb.scale if fb.kind == TypeKind.DECIMAL else 0
        if op in ("+", "-"):
            s = out.scale
            x, y = _to_scaled(a, fa, s), _to_scaled(b, fb, s)
            return (x + y if op == "+" else x - y), valid
        if op == "*":
            x, y = _to_scaled(a, fa, sa), _to_scaled(b, fb, sb)
            r = x * y
            drop = sa + sb - out.scale
            if drop > 0:
                r = _round_div_pow10(r, 10 ** drop)
            elif drop < 0:
                r = r * (10 ** (-drop))
            return r, valid
        if op == "/":
            x = _to_f64(a, fa)
            y = _to_f64(b, fb)
            bad = y == 0.0
            r = x / jnp.where(bad, 1.0, y)
            valid = valid & ~bad
            return jnp.round(r * 10.0 ** out.scale).astype(jnp.int64), valid
        raise JaxUnsupported(f"decimal {op}")
    # int domain
    x, y = a[0].astype(jnp.int64), b[0].astype(jnp.int64)
    if op == "+":
        r = x + y
    elif op == "-":
        r = x - y
    elif op == "*":
        r = x * y
    elif op in ("div", "/"):
        bad = y == 0
        safe = jnp.where(bad, 1, y)
        r = jnp.sign(x) * jnp.sign(safe) * (jnp.abs(x) // jnp.abs(safe))
        valid = valid & ~bad
    elif op == "%":
        bad = y == 0
        safe = jnp.where(bad, 1, y)
        r = jnp.sign(x) * (jnp.abs(x) % jnp.abs(safe))
        valid = valid & ~bad
    else:
        raise JaxUnsupported(op)
    return r, valid


@_reg("unaryminus")
def _neg(e, args, n):
    v = args[0]
    if e.ftype.kind == TypeKind.FLOAT:
        return -_to_f64(v, e.args[0].ftype), v[1]
    return -v[0], v[1]


# ---- comparisons -----------------------------------------------------------


@_reg("=", "!=", "<", "<=", ">", ">=")
def _cmp(e, args, n):
    a, b = args
    fa, fb = e.args[0].ftype, e.args[1].ftype
    ct = common_compare_type(fa, fb)
    if ct.kind == TypeKind.STRING:
        # both sides must be dictionary codes (int32) by now
        x, y = a[0].astype(jnp.int64), b[0].astype(jnp.int64)
    elif ct.kind == TypeKind.DECIMAL:
        s = max(
            fa.scale if fa.kind == TypeKind.DECIMAL else 0,
            fb.scale if fb.kind == TypeKind.DECIMAL else 0,
        )
        if TypeKind.FLOAT in (fa.kind, fb.kind):
            x, y = _to_f64(a, fa), _to_f64(b, fb)
        else:
            x, y = _to_scaled(a, fa, s), _to_scaled(b, fb, s)
    elif ct.kind == TypeKind.FLOAT:
        x, y = _to_f64(a, fa), _to_f64(b, fb)
    elif ct.kind in (TypeKind.DATE, TypeKind.DATETIME):
        x = _temporal_to(ct.kind, a, fa)
        y = _temporal_to(ct.kind, b, fb)
    else:
        x, y = a[0].astype(jnp.int64), b[0].astype(jnp.int64)
    op = e.name
    r = {
        "=": lambda: x == y,
        "!=": lambda: x != y,
        "<": lambda: x < y,
        "<=": lambda: x <= y,
        ">": lambda: x > y,
        ">=": lambda: x >= y,
    }[op]()
    return r.astype(jnp.int64), _both_valid(a, b)


def _temporal_to(kind, v: JVal, ft: FieldType):
    d = v[0]
    if kind == TypeKind.DATE:
        if ft.kind == TypeKind.DATETIME:
            return _floordiv_const(d.astype(jnp.int64), 86_400_000_000)
        return d.astype(jnp.int64)
    if ft.kind == TypeKind.DATE:
        return d.astype(jnp.int64) * 86_400_000_000
    return d.astype(jnp.int64)


# ---- logic -----------------------------------------------------------------


def _truth(v: JVal) -> jnp.ndarray:
    return v[0] != 0


@_reg("and")
def _and(e, args, n):
    a, b = args
    ta, tb = _truth(a), _truth(b)
    is_false = (a[1] & ~ta) | (b[1] & ~tb)
    valid = is_false | (a[1] & b[1])
    return (~is_false).astype(jnp.int64), valid


@_reg("or")
def _or(e, args, n):
    a, b = args
    is_true = (a[1] & _truth(a)) | (b[1] & _truth(b))
    valid = is_true | (a[1] & b[1])
    return is_true.astype(jnp.int64), valid


@_reg("xor")
def _xor(e, args, n):
    a, b = args
    return (_truth(a) ^ _truth(b)).astype(jnp.int64), _both_valid(a, b)


@_reg("not")
def _not(e, args, n):
    v = args[0]
    return (~_truth(v)).astype(jnp.int64), v[1]


@_reg("&", "|", "^", "<<", ">>")
def _bitops(e, args, n):
    a, b = args
    x, y = a[0].astype(jnp.int64), b[0].astype(jnp.int64)
    op = e.name
    if op == "&":
        r = x & y
    elif op == "|":
        r = x | y
    elif op == "^":
        r = x ^ y
    elif op == "<<":
        sh = jnp.clip(y, 0, 63)
        r = jnp.where((y < 0) | (y > 63), 0, x << sh)
    else:
        sh = jnp.clip(y, 0, 63)
        r = jnp.where((y < 0) | (y > 63), 0, x >> sh)
    return r, _both_valid(a, b)


@_reg("~")
def _bitneg(e, args, n):
    v = args[0]
    return ~v[0].astype(jnp.int64), v[1]


@_reg("nulleq")
def _nulleq(e, args, n):
    a, b = args
    sub = ScalarFunc("=", [e.args[0], e.args[1]], e.ftype)
    eq, _ = _cmp(sub, [a, b], n)
    both_null = ~a[1] & ~b[1]
    r = both_null | ((eq != 0) & a[1] & b[1])
    return r.astype(jnp.int64), jnp.ones(n, dtype=jnp.bool_)


@_reg("isnull")
def _isnull(e, args, n):
    v = args[0]
    return (~v[1]).astype(jnp.int64), jnp.ones(n, dtype=jnp.bool_)


@_reg("isnotnull")
def _isnotnull(e, args, n):
    v = args[0]
    return v[1].astype(jnp.int64), jnp.ones(n, dtype=jnp.bool_)


@_reg("istrue")
def _istrue(e, args, n):
    v = args[0]
    return (_truth(v) & v[1]).astype(jnp.int64), jnp.ones(n, dtype=jnp.bool_)


@_reg("isfalse")
def _isfalse(e, args, n):
    v = args[0]
    return (~_truth(v) & v[1]).astype(jnp.int64), jnp.ones(n, dtype=jnp.bool_)


@_reg("in")
def _in(e, args, n):
    target = args[0]
    hit = jnp.zeros(n, dtype=jnp.bool_)
    any_null_item = jnp.zeros(n, dtype=jnp.bool_)
    ft = e.args[0].ftype
    for it_expr, it in zip(e.args[1:], args[1:]):
        sub = ScalarFunc("=", [e.args[0], it_expr],
                         e.ftype)
        eq, _ = _cmp(sub, [target, it], n)
        hit = hit | ((eq != 0) & it[1])
        any_null_item = any_null_item | ~it[1]
    valid = target[1] & (hit | ~any_null_item)
    return hit.astype(jnp.int64), valid


# ---- control ---------------------------------------------------------------


def _cast_to(v: JVal, src: FieldType, dst: FieldType) -> JVal:
    k, tk = src.kind, dst.kind
    d, valid = v
    if tk == TypeKind.FLOAT:
        return _to_f64(v, src), valid
    if tk == TypeKind.DECIMAL:
        return _to_scaled(v, src, dst.scale), valid
    if tk in (TypeKind.INT, TypeKind.UINT, TypeKind.BOOL):
        if k == TypeKind.FLOAT:
            return jnp.round(d).astype(jnp.int64), valid
        if k == TypeKind.DECIMAL:
            p = 10 ** src.scale
            return _round_div_pow10(d.astype(jnp.int64), p), valid
        return d.astype(jnp.int64), valid
    if tk == TypeKind.DATE:
        if k == TypeKind.DATETIME:
            return _floordiv_const(
                d.astype(jnp.int64), 86_400_000_000
            ).astype(jnp.int32), valid
        return d.astype(jnp.int32), valid
    if tk == TypeKind.DATETIME:
        if k == TypeKind.DATE:
            return d.astype(jnp.int64) * 86_400_000_000, valid
        return d.astype(jnp.int64), valid
    raise JaxUnsupported(f"device cast {src} -> {dst}")


@_reg("cast")
def _cast(e, args, n):
    return _cast_to(args[0], e.args[0].ftype, e.ftype)


@_reg("if")
def _if(e, args, n):
    c, a, b = args
    cond = _truth(c) & c[1]
    ta = _cast_to(a, e.args[1].ftype, e.ftype)
    tb = _cast_to(b, e.args[2].ftype, e.ftype)
    return jnp.where(cond, ta[0], tb[0]), jnp.where(cond, ta[1], tb[1])


@_reg("ifnull")
def _ifnull(e, args, n):
    a, b = args
    ta = _cast_to(a, e.args[0].ftype, e.ftype)
    tb = _cast_to(b, e.args[1].ftype, e.ftype)
    return jnp.where(a[1], ta[0], tb[0]), jnp.where(a[1], True, tb[1])


@_reg("nullif")
def _nullif(e, args, n):
    a, b = args
    sub = ScalarFunc("=", [e.args[0], e.args[1]], e.ftype)
    eq, _ = _cmp(sub, [a, b], n)
    cond = (eq != 0) & a[1] & b[1]
    ta = _cast_to(a, e.args[0].ftype, e.ftype)
    return ta[0], a[1] & ~cond


@_reg("coalesce")
def _coalesce(e, args, n):
    data, valid = _cast_to(args[0], e.args[0].ftype, e.ftype)
    for i, v in enumerate(args[1:], start=1):
        tv = _cast_to(v, e.args[i].ftype, e.ftype)
        need = ~valid
        data = jnp.where(need, tv[0], data)
        valid = valid | (need & tv[1])
    return data, valid


@_reg("case")
def _case(e, args, n):
    has_else = len(args) % 2 == 1
    dt = _np_dtype_for(e.ftype)
    data = jnp.zeros(n, dtype=dt)
    valid = jnp.zeros(n, dtype=jnp.bool_)
    assigned = jnp.zeros(n, dtype=jnp.bool_)
    for i in range(0, len(args) - (1 if has_else else 0), 2):
        cond, val = args[i], args[i + 1]
        m = _truth(cond) & cond[1] & ~assigned
        tv = _cast_to(val, e.args[i + 1].ftype, e.ftype)
        data = jnp.where(m, tv[0], data)
        valid = jnp.where(m, tv[1], valid)
        assigned = assigned | m
    if has_else:
        m = ~assigned
        tv = _cast_to(args[-1], e.args[-1].ftype, e.ftype)
        data = jnp.where(m, tv[0], data)
        valid = jnp.where(m, tv[1], valid)
    return data, valid


@_reg("greatest", "least")
def _extremes(e, args, n):
    is_max = e.name == "greatest"
    data, valid = _cast_to(args[0], e.args[0].ftype, e.ftype)
    for i, v in enumerate(args[1:], start=1):
        tv = _cast_to(v, e.args[i].ftype, e.ftype)
        m = tv[0] > data if is_max else tv[0] < data
        data = jnp.where(m, tv[0], data)
        valid = valid & tv[1]
    return data, valid


# ---- math ------------------------------------------------------------------


@_reg("abs")
def _abs(e, args, n):
    v = args[0]
    if e.ftype.kind == TypeKind.FLOAT and e.args[0].ftype.kind != TypeKind.FLOAT:
        return jnp.abs(_to_f64(v, e.args[0].ftype)), v[1]
    return jnp.abs(v[0]), v[1]


@_reg("floor", "ceil", "ceiling")
def _floor_ceil(e, args, n):
    v = args[0]
    ft = e.args[0].ftype
    if ft.kind == TypeKind.DECIMAL:
        s = 10 ** ft.scale
        d = v[0].astype(jnp.int64)
        r = (_floordiv_const(d, s) if e.name == "floor"
             else -_floordiv_const(-d, s))
        return r, v[1]
    x = _to_f64(v, ft)
    r = jnp.floor(x) if e.name == "floor" else jnp.ceil(x)
    return r.astype(jnp.int64), v[1]


@_reg("round")
def _round(e, args, n):
    v = args[0]
    ft = e.args[0].ftype
    d = int(e.args[1].value) if len(e.args) > 1 else 0
    if ft.kind == TypeKind.DECIMAL:
        drop = ft.scale - e.ftype.scale if d >= 0 else ft.scale - d
        x = v[0].astype(jnp.int64)
        if drop > 0:
            x = _round_div_pow10(x, 10 ** drop)
        if d < 0:
            x = x * (10 ** (-d)) * (10 ** e.ftype.scale)
        return x, v[1]
    if ft.kind == TypeKind.FLOAT:
        x = v[0]
        p = 10.0 ** d
        return jnp.sign(x) * jnp.floor(jnp.abs(x) * p + 0.5) / p, v[1]
    x = v[0].astype(jnp.int64)
    if d < 0:
        p = 10 ** (-d)
        x = jnp.sign(x) * ((jnp.abs(x) + p // 2) // p) * p
    return x, v[1]


def _sfloat(name, jf, domain=None):
    @_reg(name)
    def impl(e, args, n, _jf=jf, _domain=domain):
        v = args[0]
        x = _to_f64(v, e.args[0].ftype)
        valid = v[1]
        if _domain is not None:
            ok = _domain(x)
            valid = valid & ok
            x = jnp.where(ok, x, 1.0)
        return _jf(x), valid
    return impl


_sfloat("sqrt", jnp.sqrt, lambda x: x >= 0)
_sfloat("exp", jnp.exp)
_sfloat("ln", jnp.log, lambda x: x > 0)
_sfloat("log2", jnp.log2, lambda x: x > 0)
_sfloat("log10", jnp.log10, lambda x: x > 0)
_sfloat("sin", jnp.sin)
_sfloat("cos", jnp.cos)
_sfloat("tan", jnp.tan)
_sfloat("atan", jnp.arctan)


@_reg("pow", "power")
def _pow(e, args, n):
    a, b = args
    x = _to_f64(a, e.args[0].ftype)
    y = _to_f64(b, e.args[1].ftype)
    return jnp.power(x, y), _both_valid(a, b)


@_reg("sign")
def _sign(e, args, n):
    v = args[0]
    return jnp.sign(_to_f64(v, e.args[0].ftype)).astype(jnp.int64), v[1]


@_reg("mod")
def _mod(e, args, n):
    e2 = ScalarFunc("%", e.args, e.ftype, e.meta)
    return _arith(e2, args, n)


# ---- temporal --------------------------------------------------------------


def _as_us(v: JVal, ft: FieldType) -> jnp.ndarray:
    if ft.kind == TypeKind.DATE:
        return v[0].astype(jnp.int64) * 86_400_000_000
    return v[0].astype(jnp.int64)


def _civil(us: jnp.ndarray):
    # all divisions are by small constants: the division-free path keeps
    # year()/month()/extract() off XLA's int64-divide emulation
    fd = _floordiv_const
    days = fd(us, 86_400_000_000)
    z = days + 719468
    era = fd(jnp.where(z >= 0, z, z - 146096), 146097)
    doe = z - era * 146097
    yoe = fd(doe - fd(doe, 1460) + fd(doe, 36524) - fd(doe, 146096), 365)
    y = yoe + era * 400
    doy = doe - (365 * yoe + fd(yoe, 4) - fd(yoe, 100))
    mp = fd(5 * doy + 2, 153)
    d = doy - fd(153 * mp + 2, 5) + 1
    m = jnp.where(mp < 10, mp + 3, mp - 9)
    y = jnp.where(m <= 2, y + 1, y)
    return y, m, d


@_reg("year")
def _year(e, args, n):
    return _civil(_as_us(args[0], e.args[0].ftype))[0], args[0][1]


@_reg("month")
def _month(e, args, n):
    return _civil(_as_us(args[0], e.args[0].ftype))[1], args[0][1]


@_reg("day", "dayofmonth")
def _day(e, args, n):
    return _civil(_as_us(args[0], e.args[0].ftype))[2], args[0][1]


@_reg("quarter")
def _quarter(e, args, n):
    m = _civil(_as_us(args[0], e.args[0].ftype))[1]
    return _floordiv_const(m + 2, 3), args[0][1]


@_reg("dayofweek")
def _dayofweek(e, args, n):
    us = _as_us(args[0], e.args[0].ftype)
    return (_floordiv_const(us, 86_400_000_000) + 4) % 7 + 1, args[0][1]


@_reg("weekday")
def _weekday(e, args, n):
    us = _as_us(args[0], e.args[0].ftype)
    return (_floordiv_const(us, 86_400_000_000) + 3) % 7, args[0][1]


@_reg("unix_timestamp")
def _unix_ts(e, args, n):
    return _floordiv_const(_as_us(args[0], e.args[0].ftype), 1_000_000), args[0][1]


@_reg("date")
def _datefn(e, args, n):
    us = _as_us(args[0], e.args[0].ftype)
    return _floordiv_const(us, 86_400_000_000).astype(jnp.int32), args[0][1]


@_reg("datediff")
def _datediff(e, args, n):
    a = _floordiv_const(_as_us(args[0], e.args[0].ftype), 86_400_000_000)
    b = _floordiv_const(_as_us(args[1], e.args[1].ftype), 86_400_000_000)
    return a - b, _both_valid(args[0], args[1])


_US_PER = {
    "microsecond": 1,
    "second": 1_000_000,
    "minute": 60_000_000,
    "hour": 3_600_000_000,
    "day": 86_400_000_000,
    "week": 7 * 86_400_000_000,
}


@_reg("date_add", "date_sub")
def _date_addsub(e, args, n):
    unit = e.meta.get("unit", "day")
    if unit not in _US_PER:
        raise JaxUnsupported(f"device date_{e.name} unit {unit}")
    sign = 1 if e.name == "date_add" else -1
    v, delta = args
    us = _as_us(v, e.args[0].ftype) + sign * delta[0].astype(jnp.int64) * _US_PER[unit]
    valid = _both_valid(v, delta)
    if e.ftype.kind == TypeKind.DATE:
        return _floordiv_const(us, 86_400_000_000).astype(jnp.int32), valid
    return us, valid


# ---- bounded integer evaluation (dense aggregate arguments) ---------------
#
# The sums of a dense aggregate run on native 32-bit lanes (fusion.
# dense_agg_results): an `int64` on the TPU is a pair of u32 arrays, and
# every widened column or product is a full-length temporary.  Where the
# column statistics bound an argument, it is evaluated here as an exact
# sum of int32 terms instead; where they do not, `bounded_int` declines
# and the caller keeps `compile_expr`'s int64 value.

_I32_MAX = (1 << 31) - 1
#: a bounded value past this is left to the wrapping int64 arithmetic
_BOUND_MAX = 1 << 62


class _Dry:
    """Stands in for an array where only the bounds are wanted: the
    dry run that names a program's lanes (fusion.agg_lanes) and the
    trace that emits them are one code."""

    def _same(self, *_a, **_k):
        return self

    __add__ = __radd__ = __sub__ = __mul__ = __rmul__ = _same
    __and__ = __rshift__ = __lshift__ = __neg__ = astype = _same


DRY = _Dry()


def _fits_i32(lo: int, hi: int) -> bool:
    return -_I32_MAX <= lo and hi <= _I32_MAX


class Lanes:
    """An exact integer, `const + sum(x << shift)` over int32 `terms`
    (x, shift, lo, hi) with static bounds lo <= x <= hi.  `vcols` are the
    NULLable scan columns whose validity the value inherits."""

    __slots__ = ("terms", "const", "vcols")

    def __init__(self, terms, const=0, vcols=frozenset()):
        self.terms = terms
        self.const = const
        self.vcols = vcols

    def bounds(self):
        lo = self.const + sum(t[2] << t[1] for t in self.terms)
        hi = self.const + sum(t[3] << t[1] for t in self.terms)
        return lo, hi

    def single(self):
        """The whole value as one int32 (x, lo, hi), or None."""
        lo, hi = self.bounds()
        if not self.terms or not _fits_i32(lo, hi):
            return None
        x = None
        for t, s, _lo, _hi in self.terms:
            t = t << s if s else t
            x = t if x is None else x + t
        return (x + self.const if self.const else x), lo, hi


def _split16(term):
    """One int32 term as its low 16 bits and the arithmetic rest: exact
    in two's complement for negative values too."""
    x, s, lo, hi = term
    return [(x & 0xFFFF, s, 0, 0xFFFF), (x >> 16, s + 16, lo >> 16, hi >> 16)]


def _merged(terms, limit: int):
    """Add up the terms of equal shift while the sum stays within
    +-limit: one add a row, one lane fewer."""
    out = []
    for t in sorted(terms, key=lambda t: t[1]):
        if t[2] == t[3] == 0:
            continue
        p = out[-1] if out else None
        if p is not None and p[1] == t[1] \
                and -limit <= p[2] + t[2] and p[3] + t[3] <= limit:
            out[-1] = (p[0] + t[0], t[1], p[2] + t[2], p[3] + t[3])
        else:
            out.append(t)
    return out


def _norm(terms, const, vcols):
    v = Lanes(_merged(terms, _I32_MAX), const, vcols)
    lo, hi = v.bounds()
    return v if -_BOUND_MAX < lo and hi < _BOUND_MAX else None


def _scale_term(term, y, ylo: int, yhi: int):
    """term * y as int32 terms, or None: y is a Python int (ylo == yhi)
    or an int32 array within [ylo, yhi].  A product past int32 is formed
    from the term's 16-bit halves."""
    x, s, lo, hi = term
    if lo == hi == 0:
        return []
    if not _fits_i32(ylo, yhi):
        return None
    c = (lo * ylo, lo * yhi, hi * ylo, hi * yhi)
    if _fits_i32(min(c), max(c)):
        return [(x * y, s, min(c), max(c))]
    if 0 <= lo and hi <= 0xFFFF:
        return None
    out = []
    for half in _split16(term):
        part = _scale_term(half, y, ylo, yhi)
        if part is None:
            return None
        out += part
    return out


def _mul_lanes(a: Lanes, b: Lanes):
    vcols = a.vcols | b.vcols
    if not a.terms:
        a, b = b, a
    if not b.terms:
        c, terms = b.const, []
        for t in a.terms:
            part = _scale_term(t, c, c, c)
            if part is None:
                return None
            terms += part
        return _norm(terms, a.const * c, vcols)
    # the multiplier is the side that is one int32; the narrower first
    sides = sorted(((a, b), (b, a)),
                   key=lambda p: max(map(abs, p[1].bounds())))
    for wide, narrow in sides:
        one = narrow.single()
        if one is None:
            continue
        y, ylo, yhi = one
        terms = []
        parts = list(wide.terms)
        if wide.const:
            if not _fits_i32(wide.const, wide.const):
                continue
            parts.append((wide.const, 0, wide.const, wide.const))
        for t in parts:
            part = _scale_term(t, y, ylo, yhi)
            if part is None:
                break
            terms += part
        else:
            return _norm(terms, 0, vcols)
    return None


def _rescaled(v, ft: FieldType, scale: int):
    """`_to_scaled` for an upward rescale by a power of ten; None for a
    rounding one and for floats."""
    if v is None or ft.kind == TypeKind.FLOAT:
        return None
    ds = scale - (ft.scale if ft.kind == TypeKind.DECIMAL else 0)
    if ds < 0:
        return None
    return v if ds == 0 else _mul_lanes(v, Lanes([], 10 ** ds))


_BOUNDED_KINDS = (TypeKind.INT, TypeKind.UINT, TypeKind.DECIMAL,
                  TypeKind.BOOL)


def bounded_int(e: Expression, wire: dict):
    """`e` as exact int32 `Lanes`, or None where it cannot be had: the
    part of `_arith` and `_to_scaled` that is `+`, `-`, `*`, unary minus
    and an upward rescale by a power of ten, over integer and decimal
    columns whose statistics fit int32 and literal constants.  `wire`
    maps a scan column to (array or DRY, lo, hi, nullable)."""
    if e.ftype.kind not in _BOUNDED_KINDS:
        return None
    if isinstance(e, ColumnExpr):
        w = wire.get(e.index)
        if w is None or not _fits_i32(w[1], w[2]):
            return None
        x, lo, hi, nullable = w
        return Lanes([(x.astype(jnp.int32), 0, lo, hi)], 0,
                     frozenset([e.index]) if nullable else frozenset())
    if isinstance(e, Constant):
        if (getattr(e, "param_slot", None) is not None
                or isinstance(e.value, bool)
                or not isinstance(e.value, int)):
            return None
        return Lanes([], int(e.value))
    if not isinstance(e, ScalarFunc):
        return None
    if e.name == "unaryminus":
        v = bounded_int(e.args[0], wire)
        return None if v is None else _mul_lanes(v, Lanes([], -1))
    if e.name not in ("+", "-", "*"):
        return None
    a, b = (bounded_int(x, wire) for x in e.args)
    fa, fb = e.args[0].ftype, e.args[1].ftype
    out_scale = e.ftype.scale if e.ftype.kind == TypeKind.DECIMAL else 0
    if e.name == "*":
        if a is None or b is None:
            return None
        r = _mul_lanes(a, b)
        drop = sum(f.scale for f in (fa, fb)
                   if f.kind == TypeKind.DECIMAL) - out_scale
        if r is None or drop > 0:
            return None
        return r if drop == 0 else _mul_lanes(r, Lanes([], 10 ** -drop))
    a, b = _rescaled(a, fa, out_scale), _rescaled(b, fb, out_scale)
    if a is None or b is None:
        return None
    if e.name == "-":
        b = _mul_lanes(b, Lanes([], -1))
        if b is None:
            return None
    return _norm(a.terms + b.terms, a.const + b.const, a.vcols | b.vcols)


def int_bounds(e: Expression, stats: dict):
    """(lo, hi) of `e` by interval arithmetic over `stats`, scan column
    -> (lo, hi), in `e`'s own scale; None outside `bounded_int`'s grammar
    (columns, integer literals, `+`, `-`, `*`, unary minus, upward
    rescales).  No width is asked of the columns: this is the bound a
    sum's state is sized by, not a lane."""
    if e.ftype.kind not in _BOUNDED_KINDS:
        return None
    if isinstance(e, ColumnExpr):
        return stats.get(e.index)
    if isinstance(e, Constant):
        if (getattr(e, "param_slot", None) is not None
                or isinstance(e.value, bool)
                or not isinstance(e.value, int)):
            return None
        return int(e.value), int(e.value)
    if not isinstance(e, ScalarFunc) or e.name not in (
            "unaryminus", "+", "-", "*"):
        return None
    args = [int_bounds(x, stats) for x in e.args]
    if any(a is None for a in args):
        return None
    if e.name == "unaryminus":
        return -args[0][1], -args[0][0]
    out_scale = e.ftype.scale if e.ftype.kind == TypeKind.DECIMAL else 0
    scales = [x.ftype.scale if x.ftype.kind == TypeKind.DECIMAL else 0
              for x in e.args]
    (alo, ahi), (blo, bhi) = args
    if e.name == "*":
        up = out_scale - sum(scales)
        c = (alo * blo, alo * bhi, ahi * blo, ahi * bhi)
        return None if up < 0 else (min(c) * 10 ** up, max(c) * 10 ** up)
    if min(out_scale - sc for sc in scales) < 0:
        return None
    ma, mb = (10 ** (out_scale - sc) for sc in scales)
    if e.name == "-":
        return alo * ma - bhi * mb, ahi * ma - blo * mb
    return alo * ma + blo * mb, ahi * ma + bhi * mb


def lane_limbs(v: Lanes, limit: int):
    """The terms of `v` as limbs within +-limit (limit >= 0xFFFF), each
    (x, shift): what a block sum of 2^31 // (limit + 1) rows holds in an
    int32 accumulator."""
    terms = []
    for t in v.terms:
        while not (-limit <= t[2] and t[3] <= limit):
            low, t = _split16(t)
            terms.append(low)
        terms.append(t)
    return [(t[0], t[1]) for t in _merged(terms, limit)]
