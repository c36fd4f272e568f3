"""Expression evaluation: IR -> traced JAX ops (filter + project).

This is the replacement for Trino's runtime bytecode generation tier:
ExpressionCompiler/PageFunctionCompiler emit a per-query PageProcessor class
(sql/gen/ExpressionCompiler.java:38, sql/gen/PageFunctionCompiler.java:103,
operator/project/PageProcessor.java:56); we trace the expression tree into
the enclosing jitted stage program and let XLA fuse the elementwise chain
into the surrounding matmuls/reductions — codegen for free.

Every expression evaluates to ``(data, valid)`` with SQL three-valued logic:
- arithmetic/comparison: result valid = all inputs valid
- AND/OR: Kleene logic (Trino sql/ir/Logical.java semantics)
- filters treat NULL as false (WHERE semantics)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..exec.profiler import recorded_jit

from .. import ir
from ..batch import Batch, Column
from ..types import TypeKind

# --------------------------------------------------------------------------
# decimal rescaling (Trino HALF_UP semantics, DecimalConversions.java)
# --------------------------------------------------------------------------


def rescale(data: jax.Array, from_scale: int, to_scale: int,
            xp=jnp) -> jax.Array:
    """`xp` selects the array namespace (jnp on device, np for the
    host-routed point-query path in exec/router.py) so the HALF_UP
    rounding rule cannot drift between the two executions."""
    if to_scale == from_scale:
        return data
    if to_scale > from_scale:
        return data * (10 ** (to_scale - from_scale))
    d = 10 ** (from_scale - to_scale)
    half = d // 2
    # round half away from zero, like Trino's HALF_UP
    pos = (data + half) // d
    neg = -((-data + half) // d)
    return xp.where(data >= 0, pos, neg)


_FLIPPED_CMP = {'<': '>', '<=': '>=', '>': '<', '>=': '<=',
                '=': '=', '<>': '<>'}


def _decimal_compare(a: jax.Array, sa: int, b: jax.Array, sb: int,
                     op: str, xp=jnp) -> jax.Array:
    """Exact comparison of scaled-int64 decimals at different scales.

    Never multiplies either operand: the larger-scale side is split into
    (hi, lo) by floor division, and ``a <op> b/10^k`` is decided from
    ``a`` vs ``hi`` plus the sign of ``lo`` — int64-overflow-free where
    ``a * 10^k`` would wrap (Trino compares on Int128, Decimals.java).
    `xp` is unused (pure operators) but accepted for symmetry with the
    other shared helpers the host router path calls."""
    if sa == sb:
        return _apply_cmp(op, a, b)
    if sa > sb:
        return _decimal_compare(b, sb, a, sa, _FLIPPED_CMP[op], xp)
    d = 10 ** (sb - sa)
    hi = b // d                      # floor div: lo is always in [0, d)
    lo = b - hi * d
    eq0 = lo == 0
    if op == '=':
        return (a == hi) & eq0
    if op == '<>':
        return (a != hi) | ~eq0
    if op == '>':                    # a > hi + lo/d  <=>  a > hi
        return a > hi
    if op == '>=':
        return (a > hi) | ((a == hi) & eq0)
    if op == '<':
        return (a < hi) | ((a == hi) & ~eq0)
    return a <= hi                   # '<='


def _apply_cmp(op: str, l: jax.Array, r: jax.Array) -> jax.Array:
    if op == '=':
        return l == r
    if op == '<>':
        return l != r
    if op == '<':
        return l < r
    if op == '<=':
        return l <= r
    if op == '>':
        return l > r
    return l >= r


def _to_comparable(expr: ir.Expr, data: jax.Array, target,
                   xp=jnp) -> jax.Array:
    """Rescale/convert one comparison operand to the common type."""
    t = expr.dtype
    # DECIMAL comparison targets never reach here: eval_expr routes them
    # through _decimal_compare (upscaling to a common scale wraps int64)
    assert target.kind is not TypeKind.DECIMAL
    if target.kind is TypeKind.DOUBLE:
        if t.kind is TypeKind.DECIMAL:
            return data.astype(xp.float64) / (10 ** t.scale)
        return data.astype(xp.float64)
    if target.kind is TypeKind.TIMESTAMP and t.kind is TypeKind.DATE:
        return data.astype(xp.int64) * 86_400_000_000
    return data


# --------------------------------------------------------------------------
# date decomposition (days since epoch -> civil), Hinnant's algorithm —
# branch-free integer math, vectorizes cleanly on TPU
# --------------------------------------------------------------------------


def days_from_civil(y: jax.Array, m: jax.Array, d) -> jax.Array:
    """Inverse of civil_from_days (Hinnant), for date_trunc
    reconstruction."""
    y = y - (m <= 2)
    era = y // 400
    yoe = y - era * 400
    mp = jnp.where(m > 2, m - 3, m + 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


def civil_from_days(days: jax.Array):
    z = days.astype(jnp.int64) + 719468
    # floor division is already era-correct for negative z (the C++ original
    # adjusts by -146096 only because C++ division truncates)
    era = z // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = jnp.where(mp < 10, mp + 3, mp - 9)
    year = jnp.where(m <= 2, y + 1, y)
    return year, m, d


# --------------------------------------------------------------------------
# evaluator
# --------------------------------------------------------------------------


def _operand(param: ir.Param, values) -> jax.Array:
    """The scalar a slot holds, at the dtype its literal had."""
    ints, floats, _ = values
    vec = floats if param.dtype.kind is TypeKind.DOUBLE else ints
    return vec[param.slot].astype(param.dtype.np_dtype)


def _lookup_table(table, values, dtype) -> jax.Array:
    """A per-code table: an array operand, or the static tuple."""
    if isinstance(table, ir.ArrayParam):
        return values[2][table.slot]
    return jnp.asarray(table, dtype=dtype)


# What `d IN members` costs on a v5e, int64, rows x capacity (chip run
# of PR 42, PERF.md section 6). Every pair compared in one fused
# reduction: 1.8 ps a pair whatever the live count (262,144 x 1,024 in
# 1.03 ms, 16.7M x 1,024 in 31.3 ms, 16.7M x 4,096 in 117 ms). Rows and
# members merged through one sort, then one gather: 28-40 ns a row
# whatever the capacity (7.5 ms and 666 ms). A binary search pays the
# gather's 22 ns an index at every step: 150-170 ns a row. The first
# two meet near 20,000 members.
IN_SET_PAIR_NS = 0.0018
IN_SET_MERGE_NS_PER_ROW = 40.0


def in_set(d: jax.Array, members: jax.Array, count) -> jax.Array:
    """d IN members[:count] (`ir.InSet`: ascending; past `count` a
    member repeats, or none exists). Every pair compared in one
    expression (XLA fuses the compare into the reduction, rows x members
    is never in memory), up to the capacity at which one merge of rows
    and members costs less: a capacity is a shape, so the form is the
    program's and not the data's."""
    capacity = members.shape[0]
    if capacity * IN_SET_PAIR_NS <= IN_SET_MERGE_NS_PER_ROW:
        found = jnp.any(d[:, None] == members[None, :], axis=1)
    else:
        at = jnp.searchsorted(members, d, method="sort")
        found = members[jnp.minimum(at, capacity - 1)] == d
    return found & (count > 0)


def eval_expr(expr: ir.Expr, batch: Batch, values=None):
    """Evaluate an IR expression over a batch. Returns (data, valid).

    `values` are the operands of a parametrised expression
    (`ir.parametrise`): where a literal stood, an `ir.Param` reads its
    slot at the literal's dtype, so the arithmetic is what the constant
    gave. An expression with no slots needs none."""
    n = batch.capacity

    if isinstance(expr, ir.ColumnRef):
        col = batch.columns[expr.index]
        return col.data, col.valid

    if isinstance(expr, ir.Literal):
        if expr.value is None:
            z = jnp.zeros(n, dtype=expr.dtype.np_dtype)
            return z, jnp.zeros(n, dtype=jnp.bool_)
        if expr.dtype.kind is TypeKind.VARCHAR:
            # string literal: code 0 into its single-entry pool (the
            # planner attaches the dictionary via field_for)
            return (jnp.zeros(n, dtype=jnp.int32),
                    jnp.ones(n, dtype=jnp.bool_))
        v = jnp.full(n, expr.value, dtype=expr.dtype.np_dtype)
        return v, jnp.ones(n, dtype=jnp.bool_)

    if isinstance(expr, ir.Param):
        return (jnp.broadcast_to(_operand(expr, values), n),
                jnp.ones(n, dtype=jnp.bool_))

    if isinstance(expr, ir.Arith):
        ld, lv = eval_expr(expr.left, batch, values)
        rd, rv = eval_expr(expr.right, batch, values)
        valid = lv & rv
        out = expr.dtype
        lt, rt = expr.left.dtype, expr.right.dtype
        if out.kind is TypeKind.DECIMAL:
            if expr.op == '*':
                res = ld.astype(jnp.int64) * rd.astype(jnp.int64)
            else:
                l = rescale(ld, lt.scale, out.scale) if lt.kind is TypeKind.DECIMAL \
                    else ld.astype(jnp.int64) * (10 ** out.scale)
                r = rescale(rd, rt.scale, out.scale) if rt.kind is TypeKind.DECIMAL \
                    else rd.astype(jnp.int64) * (10 ** out.scale)
                res = l + r if expr.op == '+' else l - r
            return res, valid
        if out.kind is TypeKind.DOUBLE:
            l = _to_comparable(expr.left, ld, out)
            r = _to_comparable(expr.right, rd, out)
            if expr.op == '+':
                res = l + r
            elif expr.op == '-':
                res = l - r
            elif expr.op == '*':
                res = l * r
            else:
                # division by zero yields NULL (documented deviation: Trino
                # raises DIVISION_BY_ZERO; a vectorized engine can't raise
                # per-row, so we degrade to NULL rather than emit a bogus
                # value marked valid)
                res = l / jnp.where(r == 0, jnp.float64(1), r)
                valid = valid & (r != 0)
            return res, valid
        # integer-like (BIGINT/INTEGER/DATE)
        l = ld.astype(out.np_dtype)
        r = rd.astype(out.np_dtype)
        if expr.op == '+':
            res = l + r
        elif expr.op == '-':
            res = l - r
        elif expr.op == '*':
            res = l * r
        else:
            # SQL integer division truncates toward zero; // floors.
            safe_r = jnp.where(r == 0, jnp.ones_like(r), r)
            q = l // safe_r
            rem = l - q * safe_r
            q = q + jnp.where((rem != 0) & ((l < 0) != (r < 0)), 1, 0
                              ).astype(q.dtype)
            res = q
            valid = valid & (r != 0)  # NULL on div-by-zero (see above)
        return res, valid

    if isinstance(expr, ir.Negate):
        d, v = eval_expr(expr.arg, batch, values)
        return -d, v

    if isinstance(expr, ir.Compare):
        target = ir.comparable(expr.left, expr.right)
        ld, lv = eval_expr(expr.left, batch, values)
        rd, rv = eval_expr(expr.right, batch, values)
        op = expr.op
        if target.kind is TypeKind.DECIMAL:
            # exact scaled-int comparison without upscaling either side
            # (rescaling a decimal(p,2) column to scale 12 multiplies by
            # 1e10 and silently wraps int64 — TPC-H q11's HAVING)
            sa = expr.left.dtype.scale \
                if expr.left.dtype.kind is TypeKind.DECIMAL else 0
            sb = expr.right.dtype.scale \
                if expr.right.dtype.kind is TypeKind.DECIMAL else 0
            res = _decimal_compare(ld.astype(jnp.int64), sa,
                                   rd.astype(jnp.int64), sb, op)
            return res, lv & rv
        l = _to_comparable(expr.left, ld, target)
        r = _to_comparable(expr.right, rd, target)
        return _apply_cmp(op, l, r), lv & rv

    if isinstance(expr, ir.Logical):
        parts = [eval_expr(a, batch, values) for a in expr.args]
        d, v = parts[0]
        for (d2, v2) in parts[1:]:
            if expr.op == 'and':
                # Kleene AND: false dominates null
                out_v = (v & v2) | (v & ~d) | (v2 & ~d2)
                d = d & d2
            else:
                out_v = (v & v2) | (v & d) | (v2 & d2)
                d = d | d2
            v = out_v
        return d, v

    if isinstance(expr, ir.Not):
        d, v = eval_expr(expr.arg, batch, values)
        return ~d, v

    if isinstance(expr, ir.IsNull):
        d, v = eval_expr(expr.arg, batch, values)
        res = v if expr.negated else ~v
        return res, jnp.ones_like(v)

    if isinstance(expr, ir.InList):
        d, v = eval_expr(expr.arg, batch, values)
        res = jnp.zeros_like(v)
        for lit in expr.values:
            member = _operand(lit, values).astype(d.dtype) \
                if isinstance(lit, ir.Param) \
                else jnp.asarray(lit.value, dtype=d.dtype)
            res = res | (d == member)
        return res, v

    if isinstance(expr, ir.InSet):
        d, v = eval_expr(expr.arg, batch, values)
        members = _lookup_table(expr.members, values, d.dtype)
        count = _operand(expr.count, values) \
            if isinstance(expr.count, ir.Param) else expr.count.value
        return in_set(d, members.astype(d.dtype), count), v

    if isinstance(expr, ir.Between):
        # x BETWEEN lo AND hi == (x >= lo) AND (x <= hi) with Kleene AND
        # (Trino rewrites the same way), so a definite FALSE on one side
        # dominates a NULL on the other.
        lowered = ir.Logical('and', (
            ir.Compare('>=', expr.arg, expr.low),
            ir.Compare('<=', expr.arg, expr.high),
        ))
        return eval_expr(lowered, batch, values)

    if isinstance(expr, ir.Case):
        default = expr.default
        if default is not None:
            acc_d, acc_v = eval_expr(default, batch, values)
            acc_d = acc_d.astype(expr.dtype.np_dtype)
        else:
            acc_d = jnp.zeros(n, dtype=expr.dtype.np_dtype)
            acc_v = jnp.zeros(n, dtype=jnp.bool_)
        # reverse order: first matching WHEN wins
        for cond, val in reversed(expr.whens):
            cd, cv = eval_expr(cond, batch, values)
            vd, vv = eval_expr(val, batch, values)
            take = cd & cv
            acc_d = jnp.where(take, vd.astype(expr.dtype.np_dtype), acc_d)
            acc_v = jnp.where(take, vv, acc_v)
        return acc_d, acc_v

    if isinstance(expr, ir.Cast):
        d, v = eval_expr(expr.arg, batch, values)
        src, dst = expr.arg.dtype, expr.dtype
        if src == dst:
            return d, v
        if dst.kind is TypeKind.DECIMAL:
            if src.kind is TypeKind.DECIMAL:
                return rescale(d, src.scale, dst.scale), v
            if src.kind is TypeKind.DOUBLE:
                # HALF_UP (away from zero), matching rescale(); jnp.round is
                # half-to-even and would disagree at *.5
                xs = d.astype(jnp.float64) * (10 ** dst.scale)
                half_up = jnp.where(xs >= 0, jnp.floor(xs + 0.5),
                                    jnp.ceil(xs - 0.5))
                return half_up.astype(jnp.int64), v
            return d.astype(jnp.int64) * (10 ** dst.scale), v
        if dst.kind is TypeKind.DOUBLE:
            if src.kind is TypeKind.DECIMAL:
                return d.astype(jnp.float64) / (10 ** src.scale), v
            return d.astype(jnp.float64), v
        if dst.kind in (TypeKind.BIGINT, TypeKind.INTEGER):
            if src.kind is TypeKind.DECIMAL:
                return rescale(d, src.scale, 0).astype(dst.np_dtype), v
            return d.astype(dst.np_dtype), v
        if dst.kind is TypeKind.DATE:
            if src.kind is TypeKind.TIMESTAMP:
                return (d // 86_400_000_000).astype(jnp.int32), v
            return d.astype(jnp.int32), v
        if dst.kind is TypeKind.TIMESTAMP:
            if src.kind is TypeKind.DATE:
                return d.astype(jnp.int64) * 86_400_000_000, v
            return d.astype(jnp.int64), v
        raise NotImplementedError(f"cast {src} -> {dst}")

    if isinstance(expr, ir.ArrayConst):
        return (jnp.zeros(n, dtype=jnp.int32),
                jnp.ones(n, dtype=jnp.bool_))

    if isinstance(expr, ir.DerivedDict):
        d, v = eval_expr(expr.arg, batch, values)
        lut = jnp.asarray(expr.lut, dtype=jnp.int32)
        codes = jnp.clip(d.astype(jnp.int32), 0, len(expr.lut) - 1)
        out = lut[codes]
        if expr.null_code is not None:    # varchar coalesce-to-literal
            out = jnp.where(v, out, jnp.int32(expr.null_code))
            v = jnp.ones_like(v)
        return out, v

    if isinstance(expr, ir.DictPredicate):
        d, v = eval_expr(expr.arg, batch, values)
        if len(expr.lut) == 0:      # empty pool: no code can match
            return jnp.zeros_like(d, dtype=jnp.bool_), v
        lut = _lookup_table(expr.lut, values, jnp.bool_)
        codes = jnp.clip(d.astype(jnp.int32), 0, len(expr.lut) - 1)
        return lut[codes], v

    if isinstance(expr, ir.DecimalAvg):
        from .aggregate import avg_decimal_finalize
        sd, sv = eval_expr(expr.sum, batch, values)
        cd, cv = eval_expr(expr.count, batch, values)
        res = avg_decimal_finalize(sd, cd, xp=jnp)
        return res, sv & cv & (cd != 0)

    if isinstance(expr, ir.ExtractField):
        d, v = eval_expr(expr.arg, batch, values)
        is_ts = expr.arg.dtype.kind is TypeKind.TIMESTAMP
        micros_in_day = 86_400_000_000
        if expr.part.startswith('trunc_'):
            unit = expr.part[len('trunc_'):]
            if unit in ('hour', 'minute', 'second'):   # timestamp only
                step = {'hour': 3_600_000_000, 'minute': 60_000_000,
                        'second': 1_000_000}[unit]
                return d - d % step, v
            days = d // micros_in_day if is_ts else d
            if unit == 'day':
                out = days
            elif unit == 'week':
                # epoch day 0 = Thursday; Monday-based weeks (ISO)
                out = days - (days + 3) % 7
            else:
                year, month, _day = civil_from_days(days)
                if unit == 'month':
                    out = days_from_civil(year, month, 1)
                elif unit == 'quarter':
                    q_month = ((month - 1) // 3) * 3 + 1
                    out = days_from_civil(year, q_month, 1)
                else:                                  # year
                    out = days_from_civil(year, jnp.ones_like(month), 1)
            out = out.astype(d.dtype)
            return (out * micros_in_day if is_ts else out), v
        if is_ts:
            days = d // micros_in_day
            rem = d - days * micros_in_day
            if expr.part == 'hour':
                return rem // 3_600_000_000, v
            if expr.part == 'minute':
                return (rem // 60_000_000) % 60, v
            if expr.part == 'second':
                return (rem // 1_000_000) % 60, v
            d = days
        year, month, day = civil_from_days(d)
        res = {'year': year, 'month': month, 'day': day}[expr.part]
        return res.astype(jnp.int64), v

    if isinstance(expr, ir.DictValueMap):
        d, v = eval_expr(expr.arg, batch, values)
        lut = _lookup_table(expr.values, values, expr.dtype.np_dtype)
        codes = jnp.clip(d.astype(jnp.int32), 0, len(expr.values) - 1)
        return lut[codes], v

    if isinstance(expr, ir.ScalarFunc):
        return eval_scalar_func(expr, batch, values)

    raise NotImplementedError(f"eval of {type(expr).__name__}")


def eval_scalar_func(expr: ir.ScalarFunc, batch: Batch, values=None):
    """Built-in scalar functions (reference: operator/scalar/ — MathFunctions,
    ConditionalFunctions), branch-free with three-valued logic."""
    name = expr.name
    parts = [eval_expr(a, batch, values) for a in expr.args]

    if name == "coalesce":
        d, v = parts[-1]
        d = d.astype(expr.dtype.np_dtype)
        for pd, pv in reversed(parts[:-1]):
            d = jnp.where(pv, pd.astype(expr.dtype.np_dtype), d)
            v = pv | v
        return d, v

    if name == "nullif":
        (ad, av), (bd, bv) = parts
        eq = av & bv & (ad == bd.astype(ad.dtype))
        return ad, av & ~eq

    if name in ("greatest", "least"):
        op = jnp.maximum if name == "greatest" else jnp.minimum
        d, v = parts[0]
        d = d.astype(expr.dtype.np_dtype)
        for pd, pv in parts[1:]:
            d = op(d, pd.astype(expr.dtype.np_dtype))
            v = v & pv          # NULL if any argument is NULL (Trino)
        return d, v

    (d, v) = parts[0]
    t = expr.args[0].dtype
    if name == "abs":
        return jnp.abs(d), v
    if name == "round":
        digits = expr.params[0] if expr.params else 0
        if t.kind is TypeKind.DECIMAL:
            # round at `digits` decimal places, keep the scale
            if digits >= t.scale:
                return d, v
            return rescale(rescale(d, t.scale, digits), digits, t.scale), v
        factor = jnp.float64(10.0 ** digits)
        xs = d.astype(jnp.float64) * factor
        half_up = jnp.where(xs >= 0, jnp.floor(xs + 0.5),
                            jnp.ceil(xs - 0.5))
        return half_up / factor, v
    if name in ("floor", "ceil"):
        if t.kind is TypeKind.DECIMAL:
            s = 10 ** t.scale
            # on scaled ints: floor -> toward -inf, ceil -> toward +inf
            q = d // s if name == "floor" else -((-d) // s)
            return q, v
        if jnp.issubdtype(d.dtype, jnp.floating):
            op = jnp.floor if name == "floor" else jnp.ceil
            return op(d), v
        return d.astype(jnp.int64), v
    if name == "mod":
        (rd, rv) = parts[1]
        r = rd.astype(d.dtype)
        safe = jnp.where(r == 0, jnp.ones_like(r), r)
        if jnp.issubdtype(d.dtype, jnp.floating):
            res = d - jnp.trunc(d / safe) * safe
        else:
            q = d // safe
            rem = d - q * safe
            # SQL mod truncates toward zero: sign follows the dividend
            res = jnp.where((rem != 0) & ((d < 0) != (r < 0)),
                            rem - safe, rem)
            res = jnp.where(rem == 0, rem, res)
        return res, v & parts[1][1] & (rd != 0)
    if name == "sqrt":
        x = d.astype(jnp.float64)
        return jnp.sqrt(jnp.abs(x)), v & (x >= 0)
    if name == "power":
        (rd, rv) = parts[1]
        return jnp.power(d.astype(jnp.float64),
                         rd.astype(jnp.float64)), v & rv
    if name == "exp":
        return jnp.exp(d.astype(jnp.float64)), v
    if name == "ln":
        x = d.astype(jnp.float64)
        return jnp.log(jnp.where(x > 0, x, jnp.float64(1))), v & (x > 0)

    # ---- two-limb decimal accumulation (sum over DECIMAL) ------------
    # The reference accumulates wide sums in Int128State
    # (spi/type/Int128.java); here the planner splits each unscaled
    # value into (hi = x >> 32, lo = x & 0xffffffff) so two ordinary
    # int64 segment sums carry the state exactly (lo is canonical
    # non-negative; sums of up to 2^31 rows cannot wrap), and the
    # post-agg combine hi*2^32 + lo is exact while |total| < 2^63.
    if name == "$limb_hi":
        x = d.astype(jnp.int64)
        return jax.lax.shift_right_arithmetic(x, 32), v
    if name == "$limb_lo":
        x = d.astype(jnp.int64)
        return jnp.bitwise_and(x, jnp.int64(0xFFFFFFFF)), v
    if name == "$limb_combine":
        # raw unscaled combine (NULL when either limb sum is NULL —
        # both are NULL together for empty/all-NULL groups)
        (lod, lov) = parts[1]
        hi = d.astype(jnp.int64)
        return (hi << 32) + lod.astype(jnp.int64), v & lov

    # ---- HyperLogLog building blocks (approx_distinct) ---------------
    # The reference keeps an HLL sketch object per group
    # (operator/aggregation/ApproximateCountDistinctAggregation.java +
    # airlift HyperLogLog). TPU redesign: the sketch IS a relational
    # rewrite — registers become (group, bucket) rows of an inner
    # max-aggregate, so partials merge through the ordinary mergeable-
    # aggregation machinery (chunked + distributed for free) with
    # bounded 2^p-per-group state. These scalars are the hash-side
    # primitives of that rewrite.
    if name in ("$hll_bucket", "$hll_rho"):
        p = expr.params[0]
        h = _hll_hash64(d)
        if name == "$hll_bucket":
            return jax.lax.shift_right_logical(h, 64 - p), v
        w = jax.lax.shift_left(h, p)
        rho = jnp.minimum(jax.lax.clz(w) + 1, 64 - p + 1)
        return rho.astype(jnp.int64), v
    if name == "$hll_pow":
        # 2^-rho contribution to the harmonic mean; NULL passes through
        return jnp.exp2(-d.astype(jnp.float64)), v
    if name == "$hll_est":
        # finisher over (V = occupied registers, S = sum 2^-rho):
        # raw HLL estimate with linear-counting correction for the
        # small range, 0 for all-NULL/empty groups
        m = float(expr.params[0])
        (vd, vv) = parts[0]
        (sd, sv) = parts[1]
        V = jnp.where(vv, vd, 0).astype(jnp.float64)
        S = jnp.where(sv, sd, 0.0).astype(jnp.float64)
        alpha = 0.7213 / (1.0 + 1.079 / m)
        raw = alpha * m * m / (S + (m - V))
        zeros = m - V
        lin = m * jnp.log(jnp.where(zeros > 0, m / jnp.maximum(zeros, 0.5),
                                    1.0))
        est = jnp.where((raw <= 2.5 * m) & (zeros > 0), lin, raw)
        est = jnp.where(V == 0, 0.0, est)
        return jnp.round(est).astype(jnp.int64), jnp.ones_like(vv)
    raise NotImplementedError(f"scalar function {name}")


def _hll_hash64(d):
    """splitmix64 finalizer over the lane value (int64 two's-complement
    wraparound arithmetic; logical shifts via lax). Doubles hash their
    bit pattern; dictionary codes hash as ints (code identity == string
    identity within a pool)."""
    if jnp.issubdtype(d.dtype, jnp.floating):
        x = jax.lax.bitcast_convert_type(d.astype(jnp.float64), jnp.int64)
    else:
        x = d.astype(jnp.int64)
    x = x + jnp.int64(-7046029254386353131)          # 0x9E3779B97F4A7C15
    x = x ^ jax.lax.shift_right_logical(x, 30)
    x = x * jnp.int64(-4658895280553007687)          # 0xBF58476D1CE4E5B9
    x = x ^ jax.lax.shift_right_logical(x, 27)
    x = x * jnp.int64(-7723592293110705685)          # 0x94D049BB133111EB
    x = x ^ jax.lax.shift_right_logical(x, 31)
    return x


def filter_mask(expr: ir.Expr, batch: Batch, values=None) -> jax.Array:
    """WHERE semantics: NULL -> excluded."""
    d, v = eval_expr(expr, batch, values)
    return d & v


def apply_filter(batch: Batch, expr: ir.Expr, values=None) -> Batch:
    """Filter = AND into the live mask; no data movement (the TPU analog of
    Trino's SelectedPositions, operator/project/SelectedPositions.java)."""
    return batch.with_live(batch.live & filter_mask(expr, batch, values))


def project(batch: Batch, exprs, values=None) -> Batch:
    """Evaluate projection list into a new Batch (same capacity/live)."""
    cols = []
    for e in exprs:
        d, v = eval_expr(e, batch, values)
        cols.append(Column(data=d, valid=v))
    return Batch(columns=tuple(cols), live=batch.live)


@recorded_jit(static_argnums=(2,))
def filter_rows(batch: Batch, values, filter_expr) -> Batch:
    """Jitted filter alone (a Filter over no Project: a join's filtered
    probe side), keyed and fed as `filter_project`."""
    return apply_filter(batch, filter_expr, values)


@recorded_jit(static_argnums=(2, 3))
def filter_project(batch: Batch, values, filter_expr,
                   project_exprs) -> Batch:
    """Jitted fused filter+project — the PageProcessor equivalent
    (operator/project/PageProcessor.java:99). The expressions are static
    (hashable IR) and parametrised (`ir.parametrise`): the program is
    keyed by their shape, the literals arrive in `values`, so a statement
    with new literals runs the program the last one compiled."""
    b = apply_filter(batch, filter_expr, values) \
        if filter_expr is not None else batch
    return project(b, project_exprs, values)
