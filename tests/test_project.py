"""Expression evaluation tests — the PageProcessor-equivalent layer.

Reference tests: core/trino-main/src/test/.../operator/project/ and
QueryAssertions expression assertions (SURVEY.md §4.1)."""

import numpy as np
import pytest

from trino_tpu import ir
from trino_tpu.batch import batch_from_numpy
from trino_tpu.ops.project import (apply_filter, civil_from_days, eval_expr,
                                   filter_project, rescale)
from trino_tpu.types import BIGINT, BOOLEAN, DATE, DOUBLE, decimal


def make_batch():
    a = np.array([1, 2, 3, 4], dtype=np.int64)
    b = np.array([10, 20, 30, 40], dtype=np.int64)
    return batch_from_numpy([a, b], pad_multiple=4)


def col(i, dtype=BIGINT, name=""):
    return ir.ColumnRef(i, dtype, name)


def lit(v, dtype=BIGINT):
    return ir.Literal(v, dtype)


def evaluate(expr, batch, n=4):
    d, v = eval_expr(expr, batch)
    return np.asarray(d)[:n], np.asarray(v)[:n]


def test_arith_and_compare():
    batch = make_batch()
    d, v = evaluate(ir.arith('+', col(0), col(1)), batch)
    np.testing.assert_array_equal(d, [11, 22, 33, 44])
    assert v.all()
    d, v = evaluate(ir.Compare('>', col(1), lit(20)), batch)
    np.testing.assert_array_equal(d, [False, False, True, True])


def test_decimal_arith_scales():
    # 1.50 * 0.10 -> scale 4; 1.50 + 0.1 (scale1) -> scale 2
    a = np.array([150, 250], dtype=np.int64)   # decimal(12,2)
    batch = batch_from_numpy([a], pad_multiple=2)
    c = col(0, decimal(12, 2))
    prod = ir.arith('*', c, ir.Literal(10, decimal(2, 2)))  # 0.10
    assert prod.dtype.scale == 4
    d, _ = evaluate(prod, batch, n=2)
    np.testing.assert_array_equal(d, [1500, 2500])  # 0.1500, 0.2500

    s = ir.arith('+', c, ir.Literal(1, decimal(2, 1)))  # 0.1
    assert s.dtype.scale == 2
    d, _ = evaluate(s, batch, n=2)
    np.testing.assert_array_equal(d, [160, 260])


def test_rescale_half_up():
    import jax.numpy as jnp
    x = jnp.array([125, 135, -125, -135], dtype=jnp.int64)
    out = np.asarray(rescale(x, 2, 1))
    np.testing.assert_array_equal(out, [13, 14, -13, -14])


def test_kleene_and_with_nulls():
    a = np.array([1, 1, 0, 0], dtype=np.bool_)
    valid = np.array([True, False, True, False])
    batch = batch_from_numpy([a, a], valids=[valid, None], pad_multiple=4)
    e = ir.Logical('and', (col(0, BOOLEAN), col(1, BOOLEAN)))
    d, v = evaluate(e, batch)
    # row0: T and T = T; row1: NULL and T = NULL; row2: F and F = F;
    # row3: NULL and F = F (false dominates)
    np.testing.assert_array_equal(v, [True, False, True, True])
    np.testing.assert_array_equal(d & v, [True, False, False, False])


def test_filter_nulls_excluded():
    a = np.array([5, 6, 7, 8], dtype=np.int64)
    valid = np.array([True, True, False, True])
    batch = batch_from_numpy([a], valids=[valid], pad_multiple=4)
    out = apply_filter(batch, ir.Compare('>', col(0), lit(5)))
    np.testing.assert_array_equal(np.asarray(out.live)[:4],
                                  [False, True, False, True])


def test_between_and_in():
    batch = make_batch()
    d, _ = evaluate(ir.Between(col(0), lit(2), lit(3)), batch)
    np.testing.assert_array_equal(d, [False, True, True, False])
    d, _ = evaluate(ir.InList(col(0), (lit(1), lit(4))), batch)
    np.testing.assert_array_equal(d, [True, False, False, True])


def test_case_first_match_wins():
    batch = make_batch()
    e = ir.Case(
        whens=(
            (ir.Compare('<', col(0), lit(3)), lit(100)),
            (ir.Compare('<', col(0), lit(4)), lit(200)),
        ),
        default=lit(300), dtype=BIGINT)
    d, _ = evaluate(e, batch)
    np.testing.assert_array_equal(d, [100, 100, 200, 300])


def test_civil_from_days():
    import jax.numpy as jnp
    import datetime
    days = []
    expect = []
    for s in ["1970-01-01", "1992-02-29", "1998-12-01", "2000-03-01",
              "1995-01-27", "1900-01-01"]:
        dt = datetime.date.fromisoformat(s)
        days.append((dt - datetime.date(1970, 1, 1)).days)
        expect.append((dt.year, dt.month, dt.day))
    y, m, d = civil_from_days(jnp.asarray(days, dtype=jnp.int32))
    for i, (ey, em, ed) in enumerate(expect):
        assert (int(y[i]), int(m[i]), int(d[i])) == (ey, em, ed)


def test_dict_predicate():
    codes = np.array([0, 1, 2, 1], dtype=np.int32)
    batch = batch_from_numpy([codes], pad_multiple=4)
    from trino_tpu.types import VARCHAR
    e = ir.DictPredicate(col(0, VARCHAR), (False, True, False))
    d, _ = evaluate(e, batch)
    np.testing.assert_array_equal(d, [False, True, False, True])


def test_filter_project_jit_caches():
    batch = make_batch()
    f = ir.Compare('>=', col(0), lit(2))
    p = (ir.arith('*', col(0), col(1)),)
    out = filter_project(batch, None, f, p)
    live = np.asarray(out.live)[:4]
    np.testing.assert_array_equal(live, [False, True, True, True])
    np.testing.assert_array_equal(np.asarray(out.columns[0].data)[:4],
                                  [10, 40, 90, 160])


def test_integer_division_truncates_toward_zero():
    a = np.array([-7, 7, -7, 7], dtype=np.int64)
    b = np.array([2, -2, -2, 2], dtype=np.int64)
    batch = batch_from_numpy([a, b], pad_multiple=4)
    d, v = evaluate(ir.arith('/', col(0), col(1)), batch)
    np.testing.assert_array_equal(d, [-3, -3, 3, 3])
    assert v.all()


def test_division_by_zero_is_null():
    a = np.array([7, 7, 7, 7], dtype=np.int64)
    b = np.array([0, 2, 0, 1], dtype=np.int64)
    batch = batch_from_numpy([a, b], pad_multiple=4)
    d, v = evaluate(ir.arith('/', col(0), col(1)), batch)
    np.testing.assert_array_equal(v, [False, True, False, True])


def test_between_kleene_false_dominates_null():
    # 5 BETWEEN 10 AND NULL -> FALSE (not NULL)
    a = np.array([5], dtype=np.int64)
    batch = batch_from_numpy([a], pad_multiple=1)
    e = ir.Between(col(0), lit(10), ir.Literal(None, BIGINT))
    d, v = evaluate(e, batch, n=1)
    assert v[0] and not d[0]


def test_cast_double_to_decimal_half_up():
    import jax.numpy as jnp
    a = np.array([2.5, -2.5, 2.4], dtype=np.float32)
    batch = batch_from_numpy([a], pad_multiple=4)
    e = ir.Cast(col(0, DOUBLE), decimal(4, 0))
    d, _ = evaluate(e, batch, n=3)
    np.testing.assert_array_equal(d, [3, -3, 2])


def test_decimal_compare_no_int64_overflow():
    # TPC-H q11's HAVING: decimal(p,2) sums compared against a scale-12
    # threshold.  Upscaling the column by 1e10 wraps int64 for values
    # >= ~9.2e8 scaled; the split (hi, lo) comparison must stay exact.
    # threshold = 800000.000000123456 at scale 12 (8.0e17 scaled);
    # column at scale 2: 2e9 scaled (= 2e7) would wrap to 2e19 if upscaled
    big = np.array([2_000_000_000, 90_000_000, 70_000_000],
                   dtype=np.int64)
    batch = batch_from_numpy([big], pad_multiple=4)
    threshold = 800_000 * 10 ** 12 + 123_456    # scale-12 scaled int
    e = ir.Compare('>', col(0, decimal(12, 2)),
                   lit(threshold, decimal(18, 12)))
    d, v = evaluate(e, batch, n=3)
    np.testing.assert_array_equal(d, [True, True, False])
    assert v.all()
    # flipped orientation and the remaining operators
    for op, want in [('<', [False, False, True]), ('=', [False] * 3),
                     ('<>', [True] * 3), ('>=', [True, True, False]),
                     ('<=', [False, False, True])]:
        d, _ = evaluate(ir.Compare(op, col(0, decimal(12, 2)),
                                   lit(threshold, decimal(18, 12))),
                        batch, n=3)
        np.testing.assert_array_equal(d, want, err_msg=op)
        # flipped operand order must agree
        d2, _ = evaluate(ir.Compare(op, lit(threshold, decimal(18, 12)),
                                    col(0, decimal(12, 2))), batch, n=3)
        flip = {'<': '>', '>': '<', '<=': '>=', '>=': '<=',
                '=': '=', '<>': '<>'}[op]
        d3, _ = evaluate(ir.Compare(flip, col(0, decimal(12, 2)),
                                    lit(threshold, decimal(18, 12))),
                         batch, n=3)
        np.testing.assert_array_equal(d2, d3, err_msg=f"flip {op}")
    # exact equality across scales (lo == 0), both orientations
    exact = 900_000 * 10 ** 12                  # 900000.000000000000
    eq = np.array([90_000_000], dtype=np.int64)  # 900000.00 at scale 2
    b2 = batch_from_numpy([eq], pad_multiple=4)
    d, _ = evaluate(ir.Compare('=', col(0, decimal(12, 2)),
                               lit(exact, decimal(18, 12))), b2, n=1)
    assert d[0]
    d, _ = evaluate(ir.Compare('=', lit(exact, decimal(18, 12)),
                               col(0, decimal(12, 2))), b2, n=1)
    assert d[0]


# ---------------------------------------------------------------------------
# literals as operands (ir.parametrise): the program is keyed by the
# expression's shape, the values are traced arguments
# ---------------------------------------------------------------------------

def _operand_batch():
    """big int64 | int32 | date | bool (with a NULL) | double | decimal(12,2)
    | decimal(15,6) | varchar codes"""
    big = np.array([-5, 0, 7, 2 ** 40, 24, -(2 ** 40)], dtype=np.int64)
    i32 = np.array([-3, 0, 3, 2 ** 30, 24, 9], dtype=np.int32)
    day = np.array([9131, 9204, 9205, 9206, 10000, 0], dtype=np.int32)
    flag = np.array([True, False, True, False, True, False])
    dbl = np.array([0.1, 0.30000000000000004, -2.5, 1e300, 24.0, 0.0])
    d2 = np.array([2400, 2399, 2401, -50, 5, 7], dtype=np.int64)
    d6 = np.array([24_000_000, 23_999_999, 24_000_001, -500_000,
                   50_000, 70_001], dtype=np.int64)
    codes = np.array([0, 1, 2, 1, 0, 2], dtype=np.int32)
    valid = np.array([True, True, False, True, True, True])
    return batch_from_numpy(
        [big, i32, day, flag, dbl, d2, d6, codes],
        valids=[None, None, None, valid, None, None, None, None],
        pad_multiple=8)


def _literal_cases():
    from trino_tpu.types import INTEGER, TIMESTAMP, VARCHAR
    big, i32, day = col(0), col(1, INTEGER), col(2, DATE)
    flag, dbl = col(3, BOOLEAN), col(4, DOUBLE)
    d2, d6 = col(5, decimal(12, 2)), col(6, decimal(15, 6))
    code = col(7, VARCHAR)
    cases = {
        "bigint": ir.Compare('<', big, lit(2 ** 40)),
        "integer": ir.arith('+', i32, lit(-7, INTEGER)),
        "date": ir.Logical('and', (ir.Compare('>=', day, lit(9131, DATE)),
                                   ir.Compare('<', day, lit(9205, DATE)))),
        "date_minus_date": ir.arith('-', day, lit(9204, DATE)),
        "timestamp_vs_date": ir.Compare(
            '>', ir.Cast(day, TIMESTAMP), lit(795139200000000, TIMESTAMP)),
        "boolean": ir.Logical('or', (flag, lit(False, BOOLEAN))),
        "double": ir.arith('/', dbl, lit(3.0, DOUBLE)),
        "double_times": ir.arith('*', dbl, lit(0.1, DOUBLE)),
        "double_compare": ir.Compare('<=', dbl, lit(0.3, DOUBLE)),
        "decimal_same_scale": ir.Compare('>=', d2,
                                         lit(2400, decimal(4, 2))),
        # the q11 case: scales 2 and 6, never rescaled to a common one
        "decimal_scales_lt": ir.Compare('<', d2,
                                        lit(24_000_000, decimal(8, 6))),
        "decimal_scales_eq": ir.Compare('=', d6, lit(2400, decimal(4, 2))),
        "decimal_scales_ge": ir.Compare('>=', d6, lit(-50, decimal(4, 2))),
        "decimal_vs_int": ir.Compare('<', d2, lit(24)),
        "decimal_arith": ir.arith('*', d2, ir.arith(
            '-', lit(1, decimal(1, 0)), lit(6, decimal(2, 2)))),
        "decimal_plus_int": ir.arith('+', d2, lit(3)),
        "between": ir.Between(d2, lit(5, decimal(1, 2)),
                              lit(2400, decimal(4, 2))),
        "in": ir.InList(big, (lit(7), lit(24), lit(-5))),
        "in_dates": ir.InList(day, (lit(9204, DATE), lit(0, DATE))),
        "case": ir.Case(
            whens=((ir.Compare('<', big, lit(1)), lit(100)),
                   (ir.Compare('<', big, lit(25)), lit(200))),
            default=lit(300), dtype=BIGINT),
        "case_no_default": ir.Case(
            whens=((ir.Compare('=', i32, lit(24, INTEGER)),
                    lit(1.5, DOUBLE)),), default=None, dtype=DOUBLE),
        "cast_literal": ir.arith('+', dbl, ir.Cast(lit(150, decimal(3, 2)),
                                                   DOUBLE)),
        "cast_literal_decimal": ir.Compare(
            '<', d6, ir.Cast(lit(24), decimal(12, 2))),
        "null_literal": ir.Logical('or', (ir.Compare('>', big, lit(0)),
                                          lit(None, BOOLEAN))),
        "null_coalesce": ir.ScalarFunc(
            "coalesce", (lit(None, BIGINT), big, lit(9)), BIGINT),
        "varchar_literal": ir.Compare('=', code, ir.Literal("x", VARCHAR)),
        "dict_predicate": ir.DictPredicate(code, (False, True, True)),
        "dict_predicate_empty": ir.DictPredicate(code, ()),
        "dict_value_map": ir.arith(
            '+', ir.DictValueMap(code, (8, 10, 9), BIGINT), lit(1)),
        "round_param": ir.ScalarFunc("round", (ir.arith(
            '*', dbl, lit(2.5, DOUBLE)),), DOUBLE, params=(1,)),
        "mod": ir.ScalarFunc("mod", (big, lit(7)), BIGINT),
        "negate": ir.Negate(lit(5), BIGINT),
    }
    return cases


@pytest.mark.parametrize("name", sorted(_literal_cases()))
def test_literals_as_operands_match_static_literals(name):
    """Each expression form, once with its literals as static constants
    (the unparametrised IR) and once with them as operands: the arrays
    are the same bit for bit, values and validity and dtype."""
    import jax
    import jax.numpy as jnp
    expr = _literal_cases()[name]
    batch = _operand_batch()
    # one jitted program of the static IR, as the parent ran it. One form
    # differs: XLA folds a DOUBLE division by a constant into a multiply by
    # its reciprocal (an ulp off), and an operand is divided by, as IEEE
    # 754 and op-by-op evaluation of the static IR do
    static = (lambda b: eval_expr(expr, b))
    want_d, want_v = (static if name == "double" else jax.jit(static))(batch)
    template, values = ir.parametrise(expr)
    values = jax.tree_util.tree_map(jnp.asarray, values)
    assert not any(isinstance(n, ir.Literal) and n.value is not None
                   and n.dtype.kind.value != "varchar"
                   for n in ir.walk(template)), template
    got_d, got_v = jax.jit(
        lambda b, v: eval_expr(template, b, v))(batch, values)
    assert got_d.dtype == want_d.dtype and got_d.shape == want_d.shape
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(
        np.asarray(got_d).view(np.uint8), np.asarray(want_d).view(np.uint8))
    # and through the jitted operator, filter and projection together
    if expr.dtype == BOOLEAN:
        (tf, te), vals = ir.parametrise((expr, (col(0),)))
        out = filter_project(batch, jax.tree_util.tree_map(
            jnp.asarray, vals), tf, te)
        np.testing.assert_array_equal(
            np.asarray(out.live),
            np.asarray(batch.live & want_d & want_v))


def test_folded_scalar_subquery_is_a_slot():
    """fold_scalars turns an uncorrelated scalar subquery into a Literal
    before parametrise runs, so its value is a slot like any other: two
    statements whose subqueries give different values share a template,
    and the operand gives what the constant gave."""
    from trino_tpu.exec.session import Session
    from trino_tpu.planner import logical as L
    from trino_tpu.sql.parser import parse
    s = Session(default_schema="tiny")
    ex = s.executor

    def folded(threshold):
        rel = s.planner().plan_query(parse(
            "SELECT n_nationkey FROM nation WHERE n_nationkey > "
            f"(SELECT max(r_regionkey) + {threshold} FROM region)"))
        stack, node = [rel.node], None
        while stack:
            n = stack.pop()
            if isinstance(n, L.FilterNode) and any(
                    isinstance(e, ir.ScalarSubqueryRef)
                    for e in ir.walk(n.predicate)):
                node = n
            stack.extend(L.children(n))
        assert node is not None
        return node, ex.fold_scalars(node.predicate)

    node, pred = folded(3)
    assert not any(isinstance(e, ir.ScalarSubqueryRef)
                   for e in ir.walk(pred))
    template, values = ir.parametrise(pred)
    assert values[0].tolist() == [7]            # max(r_regionkey) = 4
    template2, values2 = ir.parametrise(folded(10)[1])
    assert template2 == template and values2[0].tolist() == [14]
    import jax
    import jax.numpy as jnp
    child = ex.run(node.child)
    want = eval_expr(pred, child)
    got = eval_expr(template, child,
                    jax.tree_util.tree_map(jnp.asarray, values))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    rows = s.execute("SELECT count(*) FROM nation WHERE n_nationkey > "
                     "(SELECT max(r_regionkey) + 3 FROM region)").rows
    assert rows[0][0] == 17                      # keys 8..24


def _template_pairs():
    from trino_tpu.types import VARCHAR
    c, code = col(0), col(1, VARCHAR)
    dec = col(2, decimal(12, 2))
    base = ir.Compare('<', c, lit(5))
    return {
        # what must stay in the template: (a, b, same template?)
        "values_only": (base, ir.Compare('<', c, lit(6)), True),
        "decimal_digits": (ir.Compare('<', dec, lit(9, decimal(1, 2))),
                           ir.Compare('<', dec, lit(10, decimal(2, 2))),
                           True),
        "in_values": (ir.InList(c, (lit(1), lit(2))),
                      ir.InList(c, (lit(3), lit(9))), True),
        "lut_values": (ir.DictPredicate(code, (True, False, False)),
                       ir.DictPredicate(code, (False, False, True)), True),
        "value_map_values": (ir.DictValueMap(code, (1, 2, 3), BIGINT),
                             ir.DictValueMap(code, (4, 5, 6), BIGINT),
                             True),
        "null": (ir.Logical('or', (base, lit(True, BOOLEAN))),
                 ir.Logical('or', (base, lit(None, BOOLEAN))), False),
        "in_length": (ir.InList(c, (lit(1), lit(2))),
                      ir.InList(c, (lit(1), lit(2), lit(3))), False),
        "scalar_func_param": (
            ir.ScalarFunc("round", (col(0, DOUBLE),), DOUBLE, params=(1,)),
            ir.ScalarFunc("round", (col(0, DOUBLE),), DOUBLE, params=(2,)),
            False),
        "varchar_literal": (
            ir.Compare('=', code, ir.Literal("a", VARCHAR)),
            ir.Compare('=', code, ir.Literal("b", VARCHAR)), False),
        "pool_length": (ir.DictPredicate(code, (True, False)),
                        ir.DictPredicate(code, (True, False, False)),
                        False),
        "literal_type": (base, ir.Compare('<', c, lit(5, DOUBLE)), False),
        "decimal_scale": (ir.Compare('<', dec, lit(5, decimal(3, 2))),
                          ir.Compare('<', dec, lit(5, decimal(3, 1))),
                          False),
        "operator": (base, ir.Compare('<=', c, lit(5)), False),
        "cast_target": (ir.Cast(lit(5), DOUBLE),
                        ir.Cast(lit(5), decimal(12, 2)), False),
    }


@pytest.mark.parametrize("name", sorted(_template_pairs()))
def test_template_holds_shape_not_values(name):
    a, b, same = _template_pairs()[name]
    ta, va = ir.parametrise((a, (a, col(0))))
    tb, vb = ir.parametrise((b, (b, col(0))))
    assert (ta == tb) is same
    if same:
        assert hash(ta) == hash(tb)
        assert ir.slot_count(va) == ir.slot_count(vb) > 0
        flat_a = [np.asarray(x).tolist() for x in (va[0], va[1], *va[2])
                  if x is not None]
        flat_b = [np.asarray(x).tolist() for x in (vb[0], vb[1], *vb[2])
                  if x is not None]
        assert flat_a != flat_b


def test_parametrise_leaves_no_slots_where_nothing_to_bind():
    exprs = (None, (col(0), ir.arith('+', col(0), col(1))))
    template, values = ir.parametrise(exprs)
    assert template == exprs and values == (None, None, ())
    assert ir.slot_count(values) == 0
