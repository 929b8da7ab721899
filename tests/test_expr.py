"""Expression engine tests — vectorized eval + NULL semantics.

Reference model: expression/builtin_*_test.go and evaluator_test.go.
"""

import numpy as np
import pytest

from tidb_tpu.chunk import Chunk, Column, chunk_from_pylists
from tidb_tpu.expr import ColumnExpr, Constant, ScalarFunc, eval_expr, eval_bool_mask
from tidb_tpu.expr.builtins import infer_ftype
from tidb_tpu.types import (
    TypeKind,
    parse_date,
    ty_date,
    ty_decimal,
    ty_float,
    ty_int,
    ty_string,
)


def col(i, ft, name="c"):
    return ColumnExpr(i, ft, name)


def lit(v, ft):
    return Constant(v, ft)


def fn(name, *args, meta=None, ftype=None):
    meta = meta or {}
    if ftype is None:
        ftype = infer_ftype(name, [a.ftype for a in args], meta)
    return ScalarFunc(name, list(args), ftype, meta)


@pytest.fixture
def chk():
    return chunk_from_pylists(
        [ty_int(), ty_float(), ty_int(), ty_string()],
        [
            [1, 2, None, 4],
            [1.5, None, 3.5, -2.0],
            [10, 20, 30, 40],
            ["apple", "Banana", None, "cherry"],
        ],
    )


def test_add_int(chk):
    e = fn("+", col(0, ty_int()), col(2, ty_int()))
    out = eval_expr(e, chk)
    assert out.to_pylist() == [11, 22, None, 44]


def test_mixed_float(chk):
    e = fn("*", col(0, ty_int()), col(1, ty_float()))
    assert eval_expr(e, chk).to_pylist() == [1.5, None, None, -8.0]


def test_division_by_zero_yields_null(chk):
    e = fn("/", col(0, ty_int()), lit(0, ty_int()))
    out = eval_expr(e, chk)
    assert out.to_pylist() == [None, None, None, None]
    e2 = fn("div", lit(7, ty_int()), lit(2, ty_int()))
    assert eval_expr(e2, chk).to_pylist() == [3, 3, 3, 3]
    e3 = fn("div", lit(-7, ty_int()), lit(2, ty_int()))
    assert eval_expr(e3, chk).to_pylist() == [-3] * 4  # truncates toward zero


def test_int_div_decimal_result(chk):
    e = fn("/", lit(7, ty_int()), lit(2, ty_int()))
    out = eval_expr(e, chk)
    assert out.ftype.kind == TypeKind.DECIMAL and out.ftype.scale == 4
    assert out.to_pylist()[0] == 35000  # 3.5000 scaled


def test_decimal_arith():
    chk = chunk_from_pylists(
        [ty_decimal(10, 2), ty_decimal(10, 2)], [[150, 299], [100, -50]]
    )  # 1.50, 2.99 ; 1.00, -0.50
    add = fn("+", col(0, ty_decimal(10, 2)), col(1, ty_decimal(10, 2)))
    assert eval_expr(add, chk).to_pylist() == [250, 249]
    mul = fn("*", col(0, ty_decimal(10, 2)), col(1, ty_decimal(10, 2)))
    out = eval_expr(mul, chk)
    assert out.ftype.scale == 4
    assert out.to_pylist() == [15000, -14950]  # 1.5000, -1.4950


def test_comparisons_and_mask(chk):
    pred = fn(">", col(0, ty_int()), lit(1, ty_int()))
    mask = eval_bool_mask([pred], chk)
    assert mask.tolist() == [False, True, False, True]  # NULL -> False


def test_three_valued_logic():
    chk = chunk_from_pylists([ty_int(), ty_int()], [[1, 0, None], [None, 0, None]])
    a, b = col(0, ty_int()), col(1, ty_int())
    res_and = eval_expr(fn("and", a, b), chk)
    assert res_and.to_pylist() == [None, 0, None]
    res_or = eval_expr(fn("or", a, b), chk)
    assert res_or.to_pylist() == [1, 0, None]
    # false AND null = false; true OR null = true
    chk2 = chunk_from_pylists([ty_int(), ty_int()], [[0, 1], [None, None]])
    assert eval_expr(fn("and", col(0, ty_int()), col(1, ty_int())), chk2).to_pylist() == [0, None]
    assert eval_expr(fn("or", col(0, ty_int()), col(1, ty_int())), chk2).to_pylist() == [None, 1]


def test_is_null(chk):
    e = fn("isnull", col(0, ty_int()))
    assert eval_expr(e, chk).to_pylist() == [0, 0, 1, 0]


def test_in_with_nulls():
    chk = chunk_from_pylists([ty_int()], [[1, 5, None]])
    e = fn("in", col(0, ty_int()), lit(1, ty_int()), lit(2, ty_int()))
    assert eval_expr(e, chk).to_pylist() == [1, 0, None]
    # no match + null item -> NULL
    e2 = fn("in", col(0, ty_int()), lit(2, ty_int()), lit(None, ty_int()))
    assert eval_expr(e2, chk).to_pylist() == [None, None, None]


def test_like(chk):
    e = fn("like", col(3, ty_string()), lit("%an%", ty_string()))
    assert eval_expr(e, chk).to_pylist() == [0, 1, None, 0]
    e2 = fn("like", col(3, ty_string()), lit("_pple", ty_string()))
    assert eval_expr(e2, chk).to_pylist() == [1, 0, None, 0]


def test_case_when(chk):
    e = fn(
        "case",
        fn(">", col(0, ty_int()), lit(1, ty_int())), lit("big", ty_string()),
        lit("small", ty_string()),
    )
    assert eval_expr(e, chk).to_pylist() == ["small", "big", "small", "big"]


def test_if_ifnull_coalesce(chk):
    e = fn("ifnull", col(0, ty_int()), lit(-1, ty_int()))
    assert eval_expr(e, chk).to_pylist() == [1, 2, -1, 4]
    e2 = fn("coalesce", col(0, ty_int()), col(2, ty_int()))
    assert eval_expr(e2, chk).to_pylist() == [1, 2, 30, 4]
    e3 = fn("if", fn("isnull", col(0, ty_int())), lit(0, ty_int()), col(0, ty_int()))
    assert eval_expr(e3, chk).to_pylist() == [1, 2, 0, 4]


def test_string_funcs(chk):
    e = fn("upper", col(3, ty_string()))
    assert eval_expr(e, chk).to_pylist() == ["APPLE", "BANANA", None, "CHERRY"]
    e2 = fn("substring", col(3, ty_string()), lit(2, ty_int()), lit(3, ty_int()))
    assert eval_expr(e2, chk).to_pylist() == ["ppl", "ana", None, "her"]
    e3 = fn("concat", col(3, ty_string()), lit("!", ty_string()))
    assert eval_expr(e3, chk).to_pylist() == ["apple!", "Banana!", None, "cherry!"]
    e4 = fn("length", col(3, ty_string()))
    assert eval_expr(e4, chk).to_pylist() == [5, 6, None, 6]


def test_cast(chk):
    e = fn("cast", col(1, ty_float()), meta={"target": ty_int()})
    assert eval_expr(e, chk).to_pylist() == [2, None, 4, -2]
    e2 = fn("cast", col(0, ty_int()), meta={"target": ty_string()})
    assert eval_expr(e2, chk).to_pylist() == ["1", "2", None, "4"]
    e3 = fn("cast", lit("12.7", ty_string()), meta={"target": ty_decimal(10, 1)})
    assert eval_expr(e3, chk).to_pylist() == [127] * 4


def test_temporal():
    d0 = parse_date("1998-09-02")
    chk = chunk_from_pylists([ty_date()], [[d0, d0 + 120, None]])
    assert eval_expr(fn("year", col(0, ty_date())), chk).to_pylist() == [1998, 1998, None]
    assert eval_expr(fn("month", col(0, ty_date())), chk).to_pylist() == [9, 12, None]
    assert eval_expr(fn("dayofmonth", col(0, ty_date())), chk).to_pylist() == [2, 31, None]
    e = fn("date_add", col(0, ty_date()), lit(1, ty_int()), meta={"unit": "year"})
    out = eval_expr(e, chk)
    assert out.to_pylist()[0] == parse_date("1999-09-02")
    e2 = fn("date_sub", col(0, ty_date()), lit(108, ty_int()), meta={"unit": "day"})
    assert eval_expr(e2, chk).to_pylist()[0] == parse_date("1998-05-17")
    e3 = fn("datediff", col(0, ty_date()), col(0, ty_date()))
    assert eval_expr(e3, chk).to_pylist() == [0, 0, None]


def test_math():
    chk = chunk_from_pylists([ty_float()], [[4.0, 2.25, -1.0]])
    assert eval_expr(fn("sqrt", col(0, ty_float())), chk).to_pylist() == [2.0, 1.5, None]
    assert eval_expr(fn("abs", col(0, ty_float())), chk).to_pylist() == [4.0, 2.25, 1.0]
    assert eval_expr(fn("floor", col(0, ty_float())), chk).to_pylist() == [4, 2, -1]
    assert eval_expr(fn("ceil", col(0, ty_float())), chk).to_pylist() == [4, 3, -1]
    r = eval_expr(fn("round", lit(2.675, ty_float()), lit(2, ty_int()), meta={"digits": 2}), chk)
    assert r.to_pylist()[0] == pytest.approx(2.68)


def test_pushdown_registry():
    from tidb_tpu.expr.pushdown import can_push_expr

    e = fn("+", col(0, ty_int()), lit(1, ty_int()))
    assert can_push_expr(e)
    s = fn("upper", col(0, ty_string(), "s"))
    assert not can_push_expr(s)
    # string equality pushable only when dict-encoded
    eq = fn("=", col(0, ty_string(), "s"), lit("x", ty_string()))
    assert not can_push_expr(eq)
    assert can_push_expr(eq, dict_cols={0})
    assert not can_push_expr(e, blacklist={"+"})


def _fold(sql_expr):
    """(unfolded, folded) planner expression of one constant SQL expression."""
    from tidb_tpu.parser import parse
    from tidb_tpu.planner.columns import Schema
    from tidb_tpu.planner.expr_build import ExprBuilder, fold_constant

    ast_expr = parse("select " + sql_expr)[0].fields[0].expr
    raw = ExprBuilder(Schema([]), fold_constants=False).build(ast_expr)
    return raw, fold_constant(raw)


@pytest.mark.parametrize("sql_expr, value, precision, scale", [
    ("0.06 - 0.01", 5, 2, 2),
    ("0.09 + 0.01", 10, 2, 2),
    ("0.02 - 0.01", 1, 2, 2),
    ("0.01 - 0.01", 0, 2, 2),
    ("0.5 * 0.5", 25, 2, 2),
    ("1 / 4", 2500, 4, 4),
    ("0.02 - 0.05", -3, 2, 2),
    ("-(1234.5 * 3)", -37035, 5, 1),
    ("999999999999999.5 + 0.5", 10 ** 16, 17, 1),
])
def test_fold_constant_types_decimal_by_value(sql_expr, value, precision, scale):
    """A folded decimal constant is as wide as its value, not as the
    operator's worst case (DECIMAL(38, s), which no device program takes)."""
    from tidb_tpu.expr.pushdown import can_push_expr

    raw, c = _fold(sql_expr)
    assert raw.ftype.kind == TypeKind.DECIMAL and raw.ftype.precision == 38
    assert isinstance(c, Constant) and c.value == value
    assert c.ftype.kind == TypeKind.DECIMAL
    assert (c.ftype.precision, c.ftype.scale) == (precision, scale)
    assert c.ftype.scale == raw.ftype.scale
    assert c.ftype.nullable == raw.ftype.nullable
    assert not c.ftype.is_wide_decimal and can_push_expr(c)


@pytest.mark.parametrize("sql_expr, value", [
    ("999999999999999999.5 + 999999999999999999.5", 19999999999999999990),
    ("9999999999999999999 + 1.5", 100000000000000000005),
    ("null + 0.5", None),
    ("1.0 / 0", None),
])
def test_fold_constant_keeps_wide_and_null_types(sql_expr, value):
    """19 digits and more stay on the exact host path; NULL has no digits."""
    raw, c = _fold(sql_expr)
    assert isinstance(c, Constant) and c.value == value
    assert c.ftype == raw.ftype and c.ftype.is_wide_decimal


def test_fold_constant_keeps_declared_narrow_type():
    raw, c = _fold("cast(1 as decimal(10,2))")
    assert c.value == 100 and c.ftype == raw.ftype
    assert (c.ftype.precision, c.ftype.scale) == (10, 2)


@pytest.mark.parametrize("name, const, column, pushable", [
    # 0.06 - 0.01 beside a scale-4 column: 5 -> 500, fits
    ("<", "0.06 - 0.01", ty_decimal(12, 4), True),
    ("<", "999999999999999.5 + 0.5", ty_decimal(12, 1), True),
    # 17 digits at scale 1, raised to scale 4: 10**16 * 1000 passes int64
    ("<", "999999999999999.5 + 0.5", ty_decimal(12, 4), False),
    ("in", "999999999999999.5 + 0.5", ty_decimal(12, 4), False),
    ("+", "999999999999999.5 + 0.5", ty_decimal(12, 4), False),
    ("coalesce", "999999999999999.5 + 0.5", ty_decimal(12, 4), False),
    # a literal is typed by its digits too, and meets the same gate
    ("<", "9999999999999999.5", ty_decimal(12, 4), False),
])
def test_pushdown_refuses_constant_raised_past_int64(name, const, column,
                                                     pushable):
    """The device raises a decimal operand to the finest scale beside it
    with a plain int64 multiply; a constant that would wrap there stays on
    the exact host path, whole expression with it."""
    from tidb_tpu.expr.pushdown import can_push_expr

    _, c = _fold(const)
    assert can_push_expr(c)
    e = fn(name, col(0, column), c)
    assert can_push_expr(e) is pushable
    assert can_push_expr(fn("not", fn("isnull", e))) is pushable
