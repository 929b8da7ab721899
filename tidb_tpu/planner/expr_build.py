"""AST expression -> resolved, typed Expression trees.

Reference: planner/core/expression_rewriter.go — name resolution against the
child plan's schema, type inference per builtin, constant folding
(expression/constant_fold.go), aggregate extraction, subquery hooks.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Tuple

import numpy as np

from ..chunk import Chunk, Column
from ..errors import PlanError, UnknownColumnError
from ..expr.aggregation import AGG_FUNCS, AggDesc
from ..expr.builtins import REGISTRY, infer_ftype
from ..expr.expression import ColumnExpr, Constant, Expression, ScalarFunc
from ..parser import ast
from ..types import (
    DECIMAL_INT64_DIGITS,
    FieldType,
    TypeKind,
    ty_bool,
    ty_date,
    ty_datetime,
    ty_decimal,
    ty_float,
    ty_int,
    ty_null,
    ty_string,
    ty_uint,
)
from ..types.values import parse_date, parse_datetime
from .columns import Schema

_BINOP_CANON = {
    "<>": "!=", "&&": "and", "||": "or", "<=>": "nulleq",
}

_TEMPORAL_CMP = {"=", "!=", "nulleq", "<", "<=", ">", ">=", "in"}


def _normalize_temporal_consts(name: str,
                               args: List[Expression]) -> List[Expression]:
    """Fold string literals to DATE/DATETIME constants when compared against
    a temporal expression: `l_shipdate <= '1998-09-02'` plans with an int
    day constant, so the predicate is device-compilable (jax_eval rejects
    raw string constants) and the CPU engine skips per-row parsing."""
    if name not in _TEMPORAL_CMP:
        return args
    target = None
    for a in args:
        if a.ftype.kind in (TypeKind.DATE, TypeKind.DATETIME) and not (
            isinstance(a, Constant)
        ):
            target = a.ftype.kind
            break
    if target is None:
        return args
    out: List[Expression] = []
    for a in args:
        if (isinstance(a, Constant) and a.ftype.kind == TypeKind.STRING
                and isinstance(a.value, str)):
            try:
                if target == TypeKind.DATE:
                    a = Constant(parse_date(a.value), ty_date(False))
                else:
                    a = Constant(parse_datetime(a.value), ty_datetime(False))
            except (ValueError, IndexError):
                pass  # not a temporal literal; leave for runtime semantics
        out.append(a)
    return out

_TYPE_NAME_TO_FT = {
    "signed": lambda p, s: ty_int(),
    "unsigned": lambda p, s: ty_uint(),
    "char": lambda p, s: ty_string(),
    "binary": lambda p, s: ty_string(),
    "double": lambda p, s: ty_float(),
    "float": lambda p, s: ty_float(),
    "decimal": lambda p, s: ty_decimal(p or 10, s),
    "date": lambda p, s: ty_date(),
    "datetime": lambda p, s: ty_datetime(),
}


def literal_to_constant(v, type_hint: str = "") -> Constant:
    if v is None:
        return Constant(None, ty_null())
    if type_hint == "date":
        return Constant(parse_date(str(v)), ty_date(False))
    if type_hint in ("datetime", "timestamp"):
        return Constant(parse_datetime(str(v)), ty_datetime(False))
    if type_hint == "decimal":
        text = str(v)
        neg = text.startswith("-")
        digits = text.lstrip("+-")
        intpart, _, frac = digits.partition(".")
        scaled = int((intpart or "0") + frac)
        if neg:
            scaled = -scaled
        prec = max(len(intpart) + len(frac), 1)
        return Constant(scaled, ty_decimal(prec, len(frac), False))
    if isinstance(v, bool):
        return Constant(int(v), ty_int(False))
    if isinstance(v, int):
        if abs(v) >= (1 << 63):
            # past BIGINT range: exact wide-decimal literal (mydecimal's
            # 65-digit domain), host-evaluated
            return Constant(v, ty_decimal(max(len(str(abs(v))), 19), 0,
                                          False))
        return Constant(v, ty_int(False))
    if isinstance(v, float):
        return Constant(v, ty_float(False))
    return Constant(str(v), ty_string(False))


class ExprBuilder:
    """Stateful expression rewriter bound to one input schema.

    agg_collector: called for aggregate FuncCalls; returns the Expression
    that stands for the aggregate's value (a ColumnExpr onto the agg node's
    output).  None -> aggregates are illegal in this context.
    subquery_handler: called for sub-SELECT expressions with
    (query_ast, kind in {'scalar','in','exists'}, extra) -> Expression.
    """

    def __init__(self, schema: Schema,
                 agg_collector: Optional[Callable] = None,
                 subquery_handler: Optional[Callable] = None,
                 outer_schemas: Optional[List[Schema]] = None,
                 param_values: Optional[list] = None,
                 fold_constants: bool = True,
                 alias_fields: Optional[dict] = None,
                 window_collector: Optional[Callable] = None):
        self.schema = schema
        self.agg_collector = agg_collector
        self.subquery_handler = subquery_handler
        self.outer_schemas = outer_schemas or []
        self.param_values = param_values
        self.fold = fold_constants
        # SELECT-alias fallback scope (HAVING/ORDER BY): name -> Expression
        self.alias_fields = alias_fields or {}
        self.window_collector = window_collector

    # ------------------------------------------------------------------
    def build(self, e: ast.Expr) -> Expression:
        out = self._build(e)
        if self.fold:
            out = fold_constant(out)
        return out

    def build_bool(self, e: ast.Expr) -> List[Expression]:
        """WHERE/HAVING/ON: split top-level AND into conjuncts."""
        conds = []
        for sub in split_and(e):
            conds.append(self.build(sub))
        return conds

    # ------------------------------------------------------------------
    def _build(self, e: ast.Expr) -> Expression:
        if isinstance(e, ast.Literal):
            return literal_to_constant(e.value, e.type_hint)
        if isinstance(e, ast.ColumnRef):
            return self._column(e)
        if isinstance(e, ast.BinaryOp):
            return self._binop(e)
        if isinstance(e, ast.UnaryOp):
            return self._unop(e)
        if isinstance(e, ast.FuncCall):
            return self._func(e)
        if isinstance(e, ast.CaseWhen):
            return self._case(e)
        if isinstance(e, ast.Cast):
            return self._cast(e)
        if isinstance(e, ast.InList):
            return self._in_list(e)
        if isinstance(e, ast.InSubquery):
            return self._subquery(e.query, "in", negated=e.negated,
                                  operand=e.expr)
        if isinstance(e, ast.Between):
            return self._between(e)
        if isinstance(e, ast.Exists):
            return self._subquery(e.query, "exists", negated=e.negated)
        if isinstance(e, ast.ScalarSubquery):
            return self._subquery(e.query, "scalar")
        if isinstance(e, ast.Param):
            if self.param_values is None or e.index >= len(self.param_values):
                raise PlanError("missing parameter value")
            return literal_to_constant(self.param_values[e.index])
        if isinstance(e, ast.Variable):
            raise PlanError("variable reference outside SET/session context")
        if isinstance(e, ast.Interval):
            raise PlanError("INTERVAL outside DATE_ADD/DATE_SUB")
        if isinstance(e, ast.Default):
            raise PlanError("DEFAULT outside INSERT/UPDATE")
        raise PlanError(f"unsupported expression {type(e).__name__}")

    # ------------------------------------------------------------------
    def _column(self, e: ast.ColumnRef) -> Expression:
        col = self.schema.try_resolve(e.name, e.table)
        if col is not None:
            return col.to_expr()
        if not e.table and e.name.lower() in self.alias_fields:
            return self.alias_fields[e.name.lower()]
        # correlated reference into an enclosing query block: resolve to the
        # outer column's uid — the subquery planner decorrelates or rejects
        for sc in self.outer_schemas:
            oc = sc.try_resolve(e.name, e.table)
            if oc is not None:
                return oc.to_expr()
        raise UnknownColumnError(
            f"{e.table + '.' if e.table else ''}{e.name}"
        )

    def _make_func(self, name: str, args: List[Expression],
                   meta: Optional[dict] = None) -> ScalarFunc:
        meta = meta or {}
        if name not in REGISTRY:
            raise PlanError(f"unknown function {name!r}")
        args = _normalize_temporal_consts(name, args)
        ft = infer_ftype(name, [a.ftype for a in args], meta)
        return ScalarFunc(name, args, ft, meta)

    def _binop(self, e: ast.BinaryOp) -> Expression:
        op = _BINOP_CANON.get(e.op, e.op)
        if op in ("is", "is not"):
            operand = self._build(e.left)
            if isinstance(e.right, ast.Literal):
                v = e.right.value
                if v is None:
                    return self._make_func(
                        "isnull" if op == "is" else "isnotnull", [operand]
                    )
                if isinstance(v, bool):
                    fn = "istrue" if v else "isfalse"
                    out = self._make_func(fn, [operand])
                    if op == "is not":
                        out = self._make_func("not", [out])
                    return out
            raise PlanError("IS requires NULL/TRUE/FALSE")
        left = self._build(e.left)
        right = self._build(e.right)
        if op == "not like":  # NOT LIKE = not(like(...))
            return self._make_func("not",
                                   [self._make_func("like", [left, right])])
        return self._make_func(op, [left, right])

    def _unop(self, e: ast.UnaryOp) -> Expression:
        operand = self._build(e.operand)
        if e.op == "+":
            return operand
        if e.op == "-":
            return self._make_func("unaryminus", [operand])
        if e.op == "not":
            return self._make_func("not", [operand])
        if e.op == "~":
            return self._make_func("~", [operand])
        raise PlanError(f"unary op {e.op!r}")

    def _func(self, e: ast.FuncCall) -> Expression:
        name = e.name.lower()
        if e.over is not None:
            if self.window_collector is None:
                raise PlanError(
                    f"window function {name}() not allowed in this context"
                )
            args = [self._build(a) for a in e.args
                    if not isinstance(a, ast.Star)]
            partition = [self._build(x) for x in e.over.partition_by]
            order = [(self._build(it.expr), it.desc)
                     for it in e.over.order_by]
            return self.window_collector(name, args, partition, order,
                                         e.over)
        if name in AGG_FUNCS:
            if self.agg_collector is None:
                raise PlanError(f"aggregate {name}() not allowed here")
            args = []
            for a in e.args:
                if isinstance(a, ast.Star):
                    args = []
                    break
                args.append(self._build(a))
            return self.agg_collector(name, args, e.distinct)
        # date_add/date_sub: second arg is Interval
        if name in ("date_add", "date_sub", "adddate", "subdate"):
            canon = "date_add" if name in ("date_add", "adddate") else "date_sub"
            base = self._build(e.args[0])
            iv = e.args[1]
            if isinstance(iv, ast.Interval):
                amount = self._build(iv.value)
                unit = iv.unit
            else:
                amount = self._build(iv)
                unit = "day"
            return self._make_func(canon, [base, amount], {"unit": unit})
        if name == "extract":
            iv = e.args[0]
            unit = iv.unit if isinstance(iv, ast.Interval) else "day"
            return self._make_func(
                "extract", [self._build(e.args[1])], {"unit": unit}
            )
        if name in ("timestampadd", "timestampdiff"):
            # first arg is a bare unit keyword (SECOND, DAY, MONTH, ...) —
            # depending on the word it parses as a column ref or a
            # zero-arg function call (MONTH, DATE are also functions);
            # MySQL also accepts the ODBC SQL_TSI_* spellings
            unit = _bare_word(e.args[0], "day")
            if unit.startswith("sql_tsi_"):
                unit = unit[len("sql_tsi_"):]
            if unit not in ("microsecond", "second", "minute", "hour",
                            "day", "week", "month", "quarter", "year"):
                raise PlanError(f"invalid {name.upper()} unit {unit!r}")
            rest = [self._build(a) for a in e.args[1:]]
            return self._make_func(name, rest, {"unit": unit})
        if name == "get_format":
            # GET_FORMAT(DATE|DATETIME|TIME, 'locale'): the first arg is a
            # bare keyword, not an expression
            kindc = Constant(_bare_word(e.args[0], "date"), ty_string(False))
            return self._make_func(name, [kindc, self._build(e.args[1])])
        args = [self._build(a) for a in e.args]
        return self._make_func(name, args)

    def _case(self, e: ast.CaseWhen) -> Expression:
        args: List[Expression] = []
        if e.operand is not None:
            op = self._build(e.operand)
            for w, t in e.branches:
                args.append(self._make_func("=", [op, self._build(w)]))
                args.append(self._build(t))
        else:
            for w, t in e.branches:
                args.append(self._build(w))
                args.append(self._build(t))
        if e.else_expr is not None:
            args.append(self._build(e.else_expr))
        return self._make_func("case", args)

    def _cast(self, e: ast.Cast) -> Expression:
        mk = _TYPE_NAME_TO_FT.get(e.type_name.lower())
        if mk is None:
            raise PlanError(f"CAST target {e.type_name!r}")
        target = mk(e.precision, e.scale)
        arg = self._build(e.expr)
        return self._make_func("cast", [arg],
                               {"target": target.with_nullable(arg.ftype.nullable)})

    def _in_list(self, e: ast.InList) -> Expression:
        args = [self._build(e.expr)] + [self._build(x) for x in e.items]
        out = self._make_func("in", args)
        if e.negated:
            out = self._make_func("not", [out])
        return out

    def _between(self, e: ast.Between) -> Expression:
        x = self._build(e.expr)
        lo = self._build(e.low)
        hi = self._build(e.high)
        ge = self._make_func(">=", [x, lo])
        le = self._make_func("<=", [x, hi])
        out = self._make_func("and", [ge, le])
        if e.negated:
            out = self._make_func("not", [out])
        return out

    def _subquery(self, query, kind: str, negated: bool = False,
                  operand=None) -> Expression:
        if self.subquery_handler is None:
            raise PlanError("subquery not allowed in this context")
        return self.subquery_handler(query, kind, negated, operand)


class CorrelatedColumn(Exception):
    """Raised when a name resolves only in an enclosing block; the caller
    (subquery planner) catches it to build an Apply."""

    def __init__(self, col):
        self.col = col
        super().__init__(str(col))


def split_and(e: ast.Expr) -> List[ast.Expr]:
    if isinstance(e, ast.BinaryOp) and e.op in ("and", "&&"):
        return split_and(e.left) + split_and(e.right)
    return [e]


def expr_uids(exprs) -> set:
    """Every column uid referenced by `exprs` (the shared walk used by
    the plan builder, the decorrelator, and the join-tree compiler)."""
    out: set = set()
    for e in exprs:
        e.collect_columns(out)
    return out


def fold_constant(e: Expression) -> Expression:
    """Bottom-up constant folding (expression/constant_fold.go)."""
    if isinstance(e, ScalarFunc):
        e = ScalarFunc(e.name, [fold_constant(a) for a in e.args],
                       e.ftype, e.meta)
        if e.name in ("rand", "sleep", "now", "curdate", "version",
                      "connection_id", "database", "found_rows", "row"):
            return e
        if all(isinstance(a, Constant) for a in e.args):
            dual = Chunk([Column.from_values(ty_int(False), [0])])
            try:
                v = e.eval(dual)
            except Exception:
                return e
            if v.valid is not None and not bool(v.valid[0]):
                return Constant(None, e.ftype)
            x = v.data[0]
            if isinstance(x, np.generic):
                x = x.item()
            # NOTE: DECIMAL constants store the scaled-int representation,
            # matching Column.constant / the cop IR wire format.
            return Constant(x, _folded_type(x, e.ftype))
    return e


def _folded_type(x, ft: FieldType) -> FieldType:
    """Type of a folded constant.  Decimal arithmetic is inferred at its
    worst case, DECIMAL(38, s), which is host-only; a folded value whose
    digits fit int64 is typed by its digits, as literal_to_constant types a
    literal, so the constant can be pushed down.  The scale never changes."""
    if not ft.is_wide_decimal or not isinstance(x, int):
        return ft
    prec = max(len(str(abs(x))), ft.scale)
    if prec > DECIMAL_INT64_DIGITS:
        return ft
    return ty_decimal(prec, ft.scale, ft.nullable)


def _bare_word(node, default: str) -> str:
    """The identifier a bare keyword argument parsed into (column ref or
    zero-arg function call), lowercased."""
    import tidb_tpu.parser.ast as _ast

    if isinstance(node, _ast.ColumnRef):
        return node.name.lower()
    if isinstance(node, _ast.FuncCall):
        return node.name.lower()
    v = getattr(node, "value", None)
    return str(v).lower() if v is not None else default
