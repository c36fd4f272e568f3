"""Engine-internal typed expression IR.

Reference: Trino lowers analyzed AST expressions to its own IR (sql/ir/, 29
files: Call, Constant, Comparison, Logical, ...) which the bytecode compilers
consume (sql/gen/ExpressionCompiler.java:38). Ours is the input to the JAX
tracer in ops/project.py — jit + XLA fusion replaces bytecode generation.

Every node is typed (``dtype``). The analyzer (planner/analyzer.py) produces
only well-typed trees; the compiler assumes them.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import numpy as np

from .types import (BIGINT, BOOLEAN, DATE, DOUBLE, DataType, TypeKind,
                    common_super_type, decimal)


class Expr:
    dtype: DataType


@dataclass(frozen=True)
class ColumnRef(Expr):
    index: int          # position in the input batch
    dtype: DataType
    name: str = ""      # for debugging / explain


@dataclass(frozen=True)
class Literal(Expr):
    value: object       # python int/float/bool/str/None; DECIMAL as scaled int
    dtype: DataType


@dataclass(frozen=True)
class Param(Expr):
    """A literal's place in a parametrised expression (`parametrise`):
    the value is a traced operand, element `slot` of the values' integer
    vector (BOOLEAN, integer kinds, DATE, TIMESTAMP, short DECIMAL as its
    scaled int) or of their float64 vector (DOUBLE), cast to `dtype`."""
    slot: int
    dtype: DataType


@dataclass(frozen=True)
class ArrayParam:
    """A per-code lookup table's place (`DictPredicate.lut`,
    `DictValueMap.values`) or a member set's (`InSet.members`): array
    operand `slot`. The pool's length, or the set's capacity, is the
    program's shape and stays in the template."""
    slot: int
    length: int

    def __len__(self) -> int:
        return self.length


@dataclass(frozen=True)
class Arith(Expr):
    """+ - * / following Trino's decimal scale rules
    (spi/type/DecimalOperators semantics for short decimals):
    add/sub -> max scale; mul -> s1+s2; div -> lowered to DOUBLE."""
    op: str             # '+', '-', '*', '/'
    left: Expr
    right: Expr
    dtype: DataType


@dataclass(frozen=True)
class Negate(Expr):
    arg: Expr
    dtype: DataType


@dataclass(frozen=True)
class Compare(Expr):
    op: str             # '=', '<>', '<', '<=', '>', '>='
    left: Expr
    right: Expr
    dtype: DataType = BOOLEAN


@dataclass(frozen=True)
class Logical(Expr):
    """AND/OR with Kleene three-valued logic (Trino sql/ir/Logical.java)."""
    op: str             # 'and', 'or'
    args: tuple         # tuple[Expr, ...]
    dtype: DataType = BOOLEAN


@dataclass(frozen=True)
class Not(Expr):
    arg: Expr
    dtype: DataType = BOOLEAN


@dataclass(frozen=True)
class IsNull(Expr):
    arg: Expr
    negated: bool = False
    dtype: DataType = BOOLEAN


@dataclass(frozen=True)
class InList(Expr):
    arg: Expr
    values: tuple       # tuple[Literal, ...] coerced to arg's physical rep
    dtype: DataType = BOOLEAN


@dataclass(frozen=True)
class InSet(Expr):
    """x IN <the members a folded subquery gave> (`InSubqueryRef` after
    `Executor.fold_in_subquery`; never made by the planner). Where an
    `InList`'s length is the SQL text's shape, a set's follows the data,
    so the members are one array operand, tested in one expression:
    `count` distinct values in arg's physical rep, ascending, padded to
    a `batch.bucket_capacity` by repeating the last (a duplicate cannot
    change membership; zeros under a count of 0, which matches no row),
    so sets of different length, the empty one too, share a program.
    Never NULL: the fold handles a NULL member."""
    arg: Expr
    members: object     # tuple of scalars, or ArrayParam once parametrised
    count: Expr         # BIGINT Literal, or its Param
    dtype: DataType = BOOLEAN


@dataclass(frozen=True)
class Between(Expr):
    arg: Expr
    low: Expr
    high: Expr
    dtype: DataType = BOOLEAN


@dataclass(frozen=True)
class Case(Expr):
    """Searched CASE. whens = ((cond, value), ...)."""
    whens: tuple
    default: Optional[Expr]
    dtype: DataType


@dataclass(frozen=True)
class Cast(Expr):
    arg: Expr
    dtype: DataType


@dataclass(frozen=True)
class DictPredicate(Expr):
    """Boolean predicate over a dictionary-encoded VARCHAR column, evaluated
    host-side over the string pool into a code->bool lookup table at plan
    time (LIKE, =, IN on strings). Device work is a single gather.

    This is the TPU answer to Trino's LikeMatcher DFA (likematcher/) and
    dictionary-aware processing in PageProcessor (SURVEY.md §7 strings)."""
    arg: Expr           # must be a VARCHAR ColumnRef
    lut: tuple          # tuple[bool, ...], len == dictionary size
    dtype: DataType = BOOLEAN


@dataclass(frozen=True)
class ScalarSubqueryRef(Expr):
    """Uncorrelated scalar subquery: holds the planned subplan. The executor
    runs it once, extracts the single value, and substitutes a Literal
    before tracing (Trino: uncorrelated subqueries execute as independent
    stages feeding a semi-join/filter; here they fold to a constant)."""
    plan: object        # L.OutputNode (opaque to avoid import cycle)
    dtype: DataType

    def __hash__(self):
        return id(self.plan)

    def __eq__(self, other):
        return self is other

    def __repr__(self):
        # never the plan's own repr: its scans carry their tables'
        # schemas, dictionaries and all (EXPLAIN prints the sub-plan)
        return f"ScalarSubqueryRef(plan=<{type(self.plan).__name__}>)"


@dataclass(frozen=True)
class DerivedDict(Expr):
    """VARCHAR expression computed by transforming the string pool
    host-side (e.g. substring over every pool entry) and remapping codes
    through `lut` into a deduplicated `pool`. Device work is one gather;
    canonical codes make GROUP BY / joins on the derived value correct
    even when source strings collide after the transform
    (SURVEY.md §7 strings policy)."""
    arg: Expr           # VARCHAR ColumnRef (or nested DerivedDict)
    lut: tuple          # old code -> new code (int), len == source pool
    pool: tuple         # deduplicated transformed pool (new code -> str)
    dtype: DataType     # VARCHAR
    null_code: Optional[int] = None   # coalesce: NULL rows take this
    #                                   code and become valid


@dataclass(frozen=True, eq=False)
class InSubqueryRef(Expr):
    """x IN (uncorrelated subquery) in a non-conjunct position (inside OR,
    select items). The executor folds it to InList over the executed
    subquery's values, with Kleene NULL injection when the subquery
    contains NULLs (x IN S is NULL when unmatched and S has NULL).
    Top-level conjuncts never reach this node — they decorrelate to
    semi/anti joins first. Hashes by identity (carries a plan)."""
    arg: "Expr"
    plan: object                 # logical plan of the subquery
    arg_field: object            # Optional[Field] — probe dictionary
    sub_field: object            # Optional[Field] — subquery dictionary

    @property
    def dtype(self):
        from .types import BOOLEAN
        return BOOLEAN

    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return self is other

    def __repr__(self):
        # as ScalarSubqueryRef's: the sub-plan is EXPLAIN's to print
        return (f"InSubqueryRef(arg={self.arg!r}, "
                f"plan=<{type(self.plan).__name__}>)")


@dataclass(frozen=True)
class ScalarFunc(Expr):
    """Generic elementwise scalar function (abs/round/mod/coalesce/...).

    The engine analog of Trino's operator/scalar/ built-ins resolved via
    InternalFunctionBundle — evaluated branch-free in ops/project.py."""
    name: str
    args: tuple                  # tuple[Expr, ...]
    dtype: DataType
    params: tuple = ()           # static extras (e.g. round digits)


@dataclass(frozen=True)
class DictValueMap(Expr):
    """Map dictionary codes to precomputed host values (e.g. length(col)):
    one device gather through a per-code LUT."""
    arg: Expr                    # varchar codes
    values: tuple                # per-code value
    dtype: DataType


@dataclass(frozen=True)
class ArrayConst(Expr):
    """ARRAY[...] of constants: device sees pool id 0, the single-entry
    element pool rides in the expression (the dictionary discipline,
    types.py ARRAY)."""
    pool: tuple                  # ((elem, elem, ...),)
    dtype: DataType


@dataclass(frozen=True)
class DecimalAvg(Expr):
    """Exact decimal AVG finalizer: round-half-away-from-zero of
    sum/count at the argument's scale (Trino avg(decimal) semantics,
    computed with integer ops on device)."""
    sum: Expr
    count: Expr
    dtype: DataType


@dataclass(frozen=True)
class ExtractField(Expr):
    """EXTRACT(YEAR/MONTH/DAY FROM date_expr) — computes civil fields from
    epoch days on device."""
    part: str           # 'year', 'month', 'day'
    arg: Expr
    dtype: DataType = BIGINT


# --------------------------------------------------------------------------
# Constructors with type inference (used by the analyzer)
# --------------------------------------------------------------------------

def arith(op: str, left: Expr, right: Expr) -> Expr:
    lt, rt = left.dtype, right.dtype
    if op == '/':
        # Trino returns DECIMAL with complex scale rules; we lower division
        # to DOUBLE (documented deviation; exact where it matters — avg —
        # is handled by aggregate finalizers).
        if TypeKind.DOUBLE in (lt.kind, rt.kind) or \
           TypeKind.DECIMAL in (lt.kind, rt.kind):
            return Arith(op, left, right, DOUBLE)
        return Arith(op, left, right, common_super_type(lt, rt))
    if op == '*' and lt.kind is TypeKind.DECIMAL and rt.kind is TypeKind.DECIMAL:
        out = decimal(min(18, lt.precision + rt.precision), lt.scale + rt.scale)
        return Arith(op, left, right, out)
    if {lt.kind, rt.kind} == {TypeKind.DATE} and op == '-':
        return Arith(op, left, right, BIGINT)  # date difference in days
    return Arith(op, left, right, common_super_type(lt, rt))


def comparable(left: Expr, right: Expr) -> tuple:
    """Common comparison type for two sides (analyzer inserts Casts)."""
    return common_super_type(left.dtype, right.dtype)


def walk(expr: Expr):
    """Yield every node in the tree (pre-order)."""
    yield expr
    children = ()
    if isinstance(expr, Arith):
        children = (expr.left, expr.right)
    elif isinstance(expr, (Negate, Not, Cast, ExtractField, DictPredicate,
                           DerivedDict, DictValueMap)):
        children = (expr.arg,)
    elif isinstance(expr, ScalarFunc):
        children = expr.args
    elif isinstance(expr, InSubqueryRef):
        children = (expr.arg,)
    elif isinstance(expr, IsNull):
        children = (expr.arg,)
    elif isinstance(expr, Compare):
        children = (expr.left, expr.right)
    elif isinstance(expr, Logical):
        children = expr.args
    elif isinstance(expr, InList):
        children = (expr.arg,)
    elif isinstance(expr, InSet):
        children = (expr.arg, expr.count)
    elif isinstance(expr, Between):
        children = (expr.arg, expr.low, expr.high)
    elif isinstance(expr, Case):
        children = tuple(c for w in expr.whens for c in w) + \
            ((expr.default,) if expr.default is not None else ())
    elif isinstance(expr, DecimalAvg):
        children = (expr.sum, expr.count)
    for c in children:
        yield from walk(c)


def referenced_columns(expr: Expr) -> set:
    return {n.index for n in walk(expr) if isinstance(n, ColumnRef)}


def remap_columns(expr: Expr, mapping) -> Expr:
    """Rebuild an expression with ColumnRef indices translated through
    `mapping` (used by the column-pruning optimizer pass)."""
    if isinstance(expr, ColumnRef):
        return ColumnRef(mapping[expr.index], expr.dtype, expr.name)
    if isinstance(expr, (Literal, Param, ArrayConst)):
        return expr
    if isinstance(expr, Arith):
        return Arith(expr.op, remap_columns(expr.left, mapping),
                     remap_columns(expr.right, mapping), expr.dtype)
    if isinstance(expr, Negate):
        return Negate(remap_columns(expr.arg, mapping), expr.dtype)
    if isinstance(expr, Compare):
        return Compare(expr.op, remap_columns(expr.left, mapping),
                       remap_columns(expr.right, mapping))
    if isinstance(expr, Logical):
        return Logical(expr.op, tuple(remap_columns(a, mapping)
                                      for a in expr.args))
    if isinstance(expr, Not):
        return Not(remap_columns(expr.arg, mapping))
    if isinstance(expr, IsNull):
        return IsNull(remap_columns(expr.arg, mapping), expr.negated)
    if isinstance(expr, InList):
        return InList(remap_columns(expr.arg, mapping), expr.values)
    if isinstance(expr, InSet):
        return InSet(remap_columns(expr.arg, mapping), expr.members,
                     expr.count)
    if isinstance(expr, Between):
        return Between(remap_columns(expr.arg, mapping),
                       remap_columns(expr.low, mapping),
                       remap_columns(expr.high, mapping))
    if isinstance(expr, Case):
        return Case(tuple((remap_columns(c, mapping),
                           remap_columns(v, mapping))
                          for c, v in expr.whens),
                    None if expr.default is None
                    else remap_columns(expr.default, mapping), expr.dtype)
    if isinstance(expr, Cast):
        return Cast(remap_columns(expr.arg, mapping), expr.dtype)
    if isinstance(expr, DictPredicate):
        return DictPredicate(remap_columns(expr.arg, mapping), expr.lut)
    if isinstance(expr, DecimalAvg):
        return DecimalAvg(remap_columns(expr.sum, mapping),
                          remap_columns(expr.count, mapping), expr.dtype)
    if isinstance(expr, ExtractField):
        return ExtractField(expr.part, remap_columns(expr.arg, mapping),
                            expr.dtype)
    if isinstance(expr, DerivedDict):
        return DerivedDict(remap_columns(expr.arg, mapping), expr.lut,
                           expr.pool, expr.dtype, expr.null_code)
    if isinstance(expr, ScalarFunc):
        return ScalarFunc(expr.name,
                          tuple(remap_columns(a, mapping)
                                for a in expr.args),
                          expr.dtype, expr.params)
    if isinstance(expr, DictValueMap):
        return DictValueMap(remap_columns(expr.arg, mapping), expr.values,
                            expr.dtype)
    if isinstance(expr, ScalarSubqueryRef):
        return expr          # no column refs into the enclosing batch
    if isinstance(expr, InSubqueryRef):
        return InSubqueryRef(remap_columns(expr.arg, mapping), expr.plan,
                             expr.arg_field, expr.sub_field)
    raise NotImplementedError(type(expr).__name__)


def transform(expr: Expr, fn) -> Expr:
    """Pre-order structural rewrite: fn(node) -> replacement or None (to
    recurse into children). Generic over all IR dataclasses."""
    import dataclasses
    r = fn(expr)
    if r is not None:
        return r
    if not dataclasses.is_dataclass(expr):
        return expr
    changes = {}
    for f in dataclasses.fields(expr):
        v = getattr(expr, f.name)
        nv = _transform_value(v, fn)
        if nv is not v:
            changes[f.name] = nv
    return dataclasses.replace(expr, **changes) if changes else expr


def _transform_value(v, fn):
    if isinstance(v, Expr):
        return transform(v, fn)
    if isinstance(v, tuple):
        items = tuple(_transform_value(x, fn) for x in v)
        if any(a is not b for a, b in zip(items, v)):
            return items
    return v


# --------------------------------------------------------------------------
# Literals as operands: the jit key of a filter/project program is the
# expression's shape, the literal values are traced arguments
# --------------------------------------------------------------------------

_INT_SLOT_KINDS = (TypeKind.BOOLEAN, TypeKind.INTEGER, TypeKind.BIGINT,
                   TypeKind.DATE, TypeKind.TIMESTAMP, TypeKind.DECIMAL)


def parametrise(exprs):
    """(template, values) of `exprs`: an Expr, None, or a nested tuple of
    them (a filter and a projection list together share one slot space).

    The template is `exprs` with every value-carrying leaf whose value
    decides neither Python control flow nor an array shape replaced by a
    slot: a non-NULL `Literal` of a numeric, boolean or temporal type
    (the members of an `InList` too; its length stays) by `Param`, the
    lookup table of a `DictPredicate` / `DictValueMap` and the members
    of an `InSet` (their capacity stays) by `ArrayParam`.
    NULL and VARCHAR literals, `ScalarFunc.params`, `DerivedDict` pools,
    `ArrayConst`, cast targets, scales and operators are shape and stay.
    Two expressions that differ only in slot values have equal templates,
    so they share one compiled program.

    `values` is `(ints, floats, arrays)`: an int64 vector, a float64
    vector (None where the template has no such slot) and a tuple of
    lookup arrays, all numpy; the caller puts them on the device once.
    Subquery refs must be folded first (Executor.fold_scalars)."""
    ints, floats, arrays = [], [], []

    def leaf(e):
        if isinstance(e, Literal):
            v = e.value
            if e.dtype.kind in _INT_SLOT_KINDS and \
                    isinstance(v, (int, np.integer)) and \
                    -2 ** 63 <= v < 2 ** 63:
                ints.append(int(v))
                t = e.dtype
                if t.kind is TypeKind.DECIMAL and t.precision <= 18:
                    # 0.09 is decimal(1,2) and 0.10 decimal(2,2): the
                    # digits of a value are not the program's shape
                    t = decimal(18, t.scale)
                return Param(len(ints) - 1, t)
            if e.dtype.kind is TypeKind.DOUBLE and \
                    isinstance(v, (int, float, np.number)):
                floats.append(float(v))
                return Param(len(floats) - 1, e.dtype)
            return e        # NULL, VARCHAR, anything unusual: static
        if isinstance(e, (DictPredicate, DictValueMap)):
            table = e.lut if isinstance(e, DictPredicate) else e.values
            if len(table):      # an empty pool is a branch of its own
                arrays.append(np.asarray(table, dtype=e.dtype.np_dtype))
                return type(e)(transform(e.arg, leaf),
                               ArrayParam(len(arrays) - 1, len(table)),
                               e.dtype)
        if isinstance(e, InSet) and not isinstance(e.members, ArrayParam):
            arrays.append(np.asarray(e.members,
                                     dtype=e.arg.dtype.np_dtype))
            return InSet(transform(e.arg, leaf),
                         ArrayParam(len(arrays) - 1, len(e.members)),
                         transform(e.count, leaf))
        return None

    def over(x):
        if isinstance(x, tuple):
            return tuple(over(i) for i in x)
        return None if x is None else transform(x, leaf)

    template = over(exprs)
    values = (np.asarray(ints, dtype=np.int64) if ints else None,
              np.asarray(floats, dtype=np.float64) if floats else None,
              tuple(arrays))
    return template, values


def slot_count(values) -> int:
    """Slots bound by `parametrise`'s `values` (a lookup array is one)."""
    ints, floats, arrays = values
    return (0 if ints is None else len(ints)) + \
        (0 if floats is None else len(floats)) + len(arrays)
