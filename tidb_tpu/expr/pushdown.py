"""Pushdown eligibility: which expressions may execute on the device.

Reference: expression/expr_to_pb.go:310 ``canFuncBePushed`` + the
``mysql.expr_pushdown_blacklist`` reload (executor/reload_expr_pushdown_
blacklist.go:37-39).  The device engine (copr/) compiles a numeric/dict-code
subset of the builtin surface with jax; anything else stays in root executors.

A session-level blacklist lets users (and tests) force functions to the host,
mirroring the reference's feature gate.
"""

from __future__ import annotations

from typing import Iterable, Set

from ..types import DECIMAL_INT64_DIGITS, TypeKind
from .aggregation import AggDesc
from .expression import ColumnExpr, Constant, Expression, ScalarFunc

# Functions the jax engine implements over fixed-width numeric data
# (see copr/jax_eval.py).  Strings participate only via dictionary codes:
# =, !=, in over dict-encoded columns are rewritten to code comparisons
# by the planner before pushdown.
PUSHABLE_FUNCS: Set[str] = {
    "+", "-", "*", "/", "div", "%", "unaryminus",
    "=", "!=", "<", "<=", ">", ">=", "nulleq",
    "and", "or", "not", "xor",
    "isnull", "isnotnull", "istrue", "isfalse",
    "in", "if", "ifnull", "coalesce", "case", "cast",
    "abs", "ceil", "ceiling", "floor", "round",
    "sqrt", "exp", "ln", "log2", "log10", "pow", "power", "mod", "sign",
    "sin", "cos", "tan", "atan",
    "year", "month", "day", "dayofmonth", "quarter",
    "date", "date_add", "date_sub", "datediff", "dayofweek", "weekday",
    "unix_timestamp",
    "&", "|", "^", "<<", ">>", "~",
    "greatest", "least", "nullif",
}

PUSHABLE_AGGS: Set[str] = {
    "count", "sum", "avg", "min", "max", "first_row",
    "bit_and", "bit_or", "bit_xor",
}

#: string functions whose value on a dictionary-encoded column is a pure
#: per-entry function of that ONE column (constants allowed): computed
#: group keys built from these lower to device-side dict-code re-mapping
#: (copr/fusion.build_key_remap) — the host evaluates once per DICTIONARY
#: entry, rows re-map in code space.  All are non-null-introducing for
#: non-null inputs, so the source column's validity plane carries through.
DICT_COMPUTABLE_FUNCS: Set[str] = {
    "substr", "substring", "mid", "left", "right",
    "upper", "lower", "ucase", "lcase",
    "concat", "reverse", "trim", "ltrim", "rtrim",
}

#: INT-valued per-entry functions of one dict column (ISSUE 12 satellite:
#: the zero-host-tail follow-up (a)): `LENGTH(c)` / `ASCII(c)` group keys
#: lower to the same code-space re-mapping, with the mapping operand
#: carrying the computed INT value per dictionary code instead of an
#: output-dictionary code.
DICT_COMPUTABLE_INT_FUNCS: Set[str] = {
    "length", "char_length", "character_length", "ascii",
}

#: predicate heads a computed-dict-column predicate may use: the whole
#: predicate is evaluated ONCE per dictionary entry on the host and
#: lowers to a code-set membership test over the source column's codes
#: (`WHERE SUBSTR(c,1,2)='ab'`, LIKE/NOT-LIKE patterns, `LENGTH(c)>3`).
DICT_PRED_HEADS: Set[str] = {
    "=", "!=", "<", "<=", ">", ">=", "in", "like",
}

# Kinds with fixed-width device representations.  STRING is device-eligible
# only when dictionary-encoded (decided per column by the block store).
DEVICE_KINDS = {
    TypeKind.INT, TypeKind.UINT, TypeKind.BOOL, TypeKind.FLOAT,
    TypeKind.DECIMAL, TypeKind.DATE, TypeKind.DATETIME,
}


def _consts_fit_beside(e: ScalarFunc) -> bool:
    """The device raises a decimal operand to the finest scale beside it
    (or its function's own) by a plain int64 multiply.  A decimal Constant's
    precision counts its value's digits (a literal's, a folded constant's),
    so one that the multiply would carry past int64 is known here, and the
    expression keeps the exact host path."""
    fts = [a.ftype for a in e.args] + [e.ftype]
    s = max((ft.scale for ft in fts if ft.kind == TypeKind.DECIMAL), default=0)
    return all(a.ftype.precision + s - a.ftype.scale <= DECIMAL_INT64_DIGITS
               for a in e.args
               if isinstance(a, Constant) and a.ftype.kind == TypeKind.DECIMAL)


def can_push_expr(e: Expression, blacklist: Set[str] = frozenset(),
                  dict_cols: Set[int] = frozenset()) -> bool:
    """True if the whole expression tree can run on the device.

    dict_cols: unique_ids of string columns that are dictionary-encoded in
    the block store (equality/IN on them compiles to code comparison).
    """
    if isinstance(e, Constant):
        if e.ftype.kind == TypeKind.DECIMAL and e.ftype.is_wide_decimal:
            return False
        return e.ftype.kind in DEVICE_KINDS or e.value is None or isinstance(
            e.value, str
        )
    if isinstance(e, ColumnExpr):
        if e.ftype.kind == TypeKind.DECIMAL and e.ftype.is_wide_decimal:
            return False  # object storage: exact host path only
        if e.ftype.kind in DEVICE_KINDS:
            return True
        key = e.unique_id if e.unique_id >= 0 else e.index
        return e.ftype.kind == TypeKind.STRING and key in dict_cols
    if isinstance(e, ScalarFunc):
        if e.name not in blacklist and can_push_dict_pred(e, dict_cols):
            # computed predicate over ONE dict column: lowers to a
            # code-set membership test at analysis time
            # (jax_engine.rewrite_for_dict), so the device only ever
            # sees integer code comparisons
            return True
        if e.name in blacklist or e.name not in PUSHABLE_FUNCS:
            return False
        if e.name in ("=", "!=", "in", "<", "<=", ">", ">="):
            # string comparisons only against dict-encoded columns; range
            # ops work because dictionaries are sorted (code order ==
            # string order; jax_engine.rewrite_for_dict maps const bounds)
            kinds = [a.ftype.kind for a in e.args]
            if TypeKind.STRING in kinds:
                col_args = [a for a in e.args if isinstance(a, ColumnExpr)]
                const_args = [a for a in e.args if isinstance(a, Constant)]
                if len(col_args) != 1 or len(const_args) != len(e.args) - 1:
                    return False
                c = col_args[0]
                if c.ftype.kind != TypeKind.STRING:
                    # ENUM/SET/temporal vs string literal: member/temporal
                    # coercion is host-side semantics — don't push
                    return False
                key = c.unique_id if c.unique_id >= 0 else c.index
                if key not in dict_cols:
                    return False
                return True
        elif any(a.ftype.kind == TypeKind.STRING for a in e.args):
            return False
        if not _consts_fit_beside(e):
            return False
        return all(can_push_expr(a, blacklist, dict_cols) for a in e.args)
    return False


def _computed_dict_tree_columns(e: Expression):
    """Column leaves when `e` is a computed (non-bare-column) tree of
    dictionary-computable string/int functions over STRING column leaves
    plus non-NULL constants; None otherwise.  The generalization of
    `dict_computable_columns` that also admits INT-valued roots
    (LENGTH/ASCII...) — ISSUE 12 satellite (a)."""
    if not isinstance(e, ScalarFunc):
        return None
    if e.ftype.kind not in (TypeKind.STRING, TypeKind.INT, TypeKind.UINT):
        return None
    cols = []

    def walk(x) -> bool:
        if isinstance(x, Constant):
            return x.value is not None
        if isinstance(x, ColumnExpr):
            cols.append(x)
            return x.ftype.kind == TypeKind.STRING
        if isinstance(x, ScalarFunc):
            if x.name not in DICT_COMPUTABLE_FUNCS \
                    and x.name not in DICT_COMPUTABLE_INT_FUNCS:
                return False
            return all(walk(a) for a in x.args)
        return False

    if not walk(e) or not cols:
        return None
    return cols


def dict_pred_source(e: Expression):
    """The column leaves of a code-set-loweable predicate, or None.

    Shape: a DICT_PRED_HEADS comparison whose ONE non-constant operand
    is either a dict-encoded STRING column inside a computed tree
    (`SUBSTR(c,1,2)='ab'`, `LENGTH(c)>3`) or, for LIKE, the bare column
    itself; every other operand is a non-NULL constant.  Boolean
    combinations are handled by the callers' recursion (and/or/not are
    ordinary pushable functions once the leaves lower).  The host
    evaluates the WHOLE predicate once per dictionary entry
    (fusion.dict_pred_codes) and the device tests code membership."""
    if not isinstance(e, ScalarFunc) or e.name not in DICT_PRED_HEADS:
        return None
    var_args = [a for a in e.args if not isinstance(a, Constant)]
    if len(var_args) != 1:
        return None
    if any(isinstance(a, Constant) and a.value is None for a in e.args):
        return None
    v = var_args[0]
    if e.name == "like" and isinstance(v, ColumnExpr):
        if v.ftype.kind != TypeKind.STRING:
            return None
        return [v]
    cols = _computed_dict_tree_columns(v)
    if cols is None:
        return None
    return cols


def can_push_dict_pred(e: Expression,
                       dict_cols: Set[int] = frozenset()) -> bool:
    """True when a predicate lowers to a code-set membership test over
    exactly ONE dict-encoded string column (ISSUE 12: LIKE / computed
    string predicates on the device probe path)."""
    cols = dict_pred_source(e)
    if cols is None:
        return False
    keys = {(c.unique_id if c.unique_id >= 0 else c.index) for c in cols}
    return len(keys) == 1 and next(iter(keys)) in dict_cols


def dict_computable_columns(e: Expression):
    """The STRUCTURAL half of the remap eligibility check, shared by the
    planner gate (can_remap_group_key), the engine's remap builder
    (fusion._single_dict_column) and plancheck's registry exemption —
    ONE walker so the three layers can never drift apart.

    Returns the list of ColumnExpr leaves when `e` is a STRING-typed
    tree of dictionary-computable functions over STRING column leaves
    plus non-NULL constants, referencing at least one column; None
    otherwise.  Callers apply their own column-identity check (uid vs
    scan index vs store dictionary membership)."""
    if not isinstance(e, ScalarFunc) or e.ftype.kind != TypeKind.STRING:
        return None
    cols = []

    def walk(x) -> bool:
        if isinstance(x, Constant):
            return x.value is not None
        if isinstance(x, ColumnExpr):
            cols.append(x)
            return x.ftype.kind == TypeKind.STRING
        if isinstance(x, ScalarFunc):
            if x.name not in DICT_COMPUTABLE_FUNCS:
                return False
            return all(walk(a) for a in x.args)
        return False

    if not walk(e) or not cols:
        return None
    return cols


def can_remap_group_key(e: Expression,
                        dict_cols: Set[int] = frozenset()) -> bool:
    """True when a computed group key lowers to a device-side dict-code
    re-mapping (copr/fusion.build_key_remap): a tree of
    dictionary-computable string (or, since ISSUE 12, INT-valued:
    LENGTH/ASCII) functions over exactly ONE dict-encoded string column
    plus constants.  The host evaluates the function once per dictionary
    entry; rows re-map in code space — no host tail."""
    cols = dict_computable_columns(e)
    if cols is None:
        cols = _computed_dict_tree_columns(e)
    if cols is None:
        return False
    keys = {(c.unique_id if c.unique_id >= 0 else c.index) for c in cols}
    return len(keys) == 1 and next(iter(keys)) in dict_cols


def can_push_agg(agg: AggDesc, blacklist: Set[str] = frozenset(),
                 dict_cols: Set[int] = frozenset()) -> bool:
    if agg.name not in PUSHABLE_AGGS or agg.name in blacklist:
        return False
    if agg.distinct:
        return False  # distinct aggs stay serial on host (reference: aggregate.go:166)
    if agg.name in ("min", "max", "first_row"):
        # dict codes are order-preserving only if the dictionary is sorted;
        # blockstore guarantees sorted dictionaries, so allow them.
        return all(
            a.ftype.kind in DEVICE_KINDS
            or (isinstance(a, ColumnExpr) and (
                (a.unique_id if a.unique_id >= 0 else a.index) in dict_cols))
            for a in agg.args
        )
    return all(can_push_expr(a, blacklist, dict_cols) for a in agg.args)
