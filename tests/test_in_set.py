"""A folded IN subquery's member set (`ir.InSet`): one array operand,
tested in one program keyed by the set's capacity.

The members follow the data (TPC-H Q18's HAVING keeps 69 to 666 orders),
so they are no part of the program: `Executor.fold_in_subquery` pads them
to a `bucket_capacity`, `ir.parametrise` takes them out as an operand,
and `ops.project.in_set` compares every pair. The reference is
`numpy.isin`; SQL semantics (NULL members, VARCHAR pools) through a
session over memory tables, on the device route."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu import ir
from trino_tpu.batch import batch_from_numpy, bucket_capacity
from trino_tpu.catalog import Catalog
from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.exec.executor import member_set
from trino_tpu.exec.profiler import RECORDER
from trino_tpu.exec.session import Session
from trino_tpu.ops.project import eval_expr, filter_rows
from trino_tpu.types import BIGINT

I64 = np.iinfo(np.int64)
KEY = ir.ColumnRef(0, BIGINT, "k")
ROWS = 5000


def probe_side(members, seed=11):
    """(batch, keys, countable): 5,000 keys of which about half are
    members (where there are any), the int64 extremes among them; every
    seventh NULL, every eleventh dead, the pad to 5,120 dead too."""
    rng = np.random.default_rng(seed)
    pool = np.asarray(sorted(members) or [0], dtype=np.int64)
    keys = np.where(rng.random(ROWS) < 0.5, rng.choice(pool, ROWS),
                    rng.integers(I64.min, I64.max, ROWS, dtype=np.int64,
                                 endpoint=True))
    keys[:4] = (I64.min, I64.max, -1, 0)
    valid = np.arange(ROWS) % 7 != 3
    live = np.arange(ROWS) % 11 != 5
    batch = batch_from_numpy([keys], valids=[valid])
    batch = batch.with_live(batch.live & jnp.asarray(
        np.pad(live, (0, batch.capacity - ROWS))))
    return batch, keys, valid & live


def bound(expr):
    template, values = ir.parametrise(expr)
    return template, jax.tree_util.tree_map(jnp.asarray, values)


def members_of(n, seed=5):
    """n distinct int64 members: negative, positive, both extremes."""
    rng = np.random.default_rng(seed)
    vals = {I64.min, I64.max, -1}
    while len(vals) < n:
        vals.update(rng.integers(-10 ** 12, 10 ** 12, n).tolist())
    return sorted(vals)[:n - 1] + [I64.max] if n > 1 else [I64.min][:n]


@pytest.mark.parametrize("n", [0, 1, 2, 69, 1023, 1024, 1025, 3000, 20000])
def test_the_set_program_agrees_with_numpy_isin(n):
    """Every size about the lattice's edges, the empty set among them,
    and one past the capacity where the merge form takes over (24,576):
    rows that are NULL or dead never pass, whatever they hold."""
    members = members_of(n)
    expr = member_set(KEY, members)
    assert len(expr.members) == bucket_capacity(n) and \
        expr.count == ir.Literal(n, BIGINT)
    assert set(expr.members[:n]) == set(members) and \
        set(expr.members[n:]) <= ({members[-1]} if n else {0})
    batch, keys, countable = probe_side(members)
    template, values = bound(expr)
    assert template.members == ir.ArrayParam(0, bucket_capacity(n))
    want = countable & np.isin(keys, np.asarray(members, dtype=np.int64))
    assert want.sum() > 100 or not n
    got = np.asarray(filter_rows(batch, values, template).live)
    np.testing.assert_array_equal(got[:ROWS], want)
    assert not got[ROWS:].any()
    # NOT IN: a NULL key is UNKNOWN either way, a dead row stays dead
    got = np.asarray(filter_rows(batch, values, ir.Not(template)).live)
    np.testing.assert_array_equal(
        got[:ROWS], countable & ~np.isin(keys, members))
    # and the unparametrised form (a fused chunk pipeline's constant)
    d, v = eval_expr(expr, batch)
    np.testing.assert_array_equal(
        np.asarray(d & v & batch.live)[:ROWS], want)


def test_a_zero_among_no_members_matches_nothing():
    """The empty set's pad is zeros: a key 0 must not find them."""
    batch = batch_from_numpy([np.zeros(8, dtype=np.int64)])
    template, values = bound(member_set(KEY, ()))
    assert not np.asarray(filter_rows(batch, values, template).live).any()


def test_sets_of_one_capacity_share_one_compiled_program():
    """69, 666 and no members at all: one template, so one program; a
    set past the capacity is another shape, here as anywhere."""
    batch, keys, countable = probe_side(members_of(666))
    compiles = []
    for n in (666, 69, 0, 118):
        members = members_of(n, seed=n)
        template, values = bound(member_set(KEY, members))
        before = RECORDER.totals()
        got = np.asarray(filter_rows(batch, values, template).live)
        after = RECORDER.totals()
        compiles.append(after["compiles"] - before["compiles"])
        assert after["hits"] + after["compiles"] == \
            before["hits"] + before["compiles"] + 1
        np.testing.assert_array_equal(
            got[:ROWS], countable & np.isin(keys, members))
    assert compiles[1:] == [0, 0, 0]
    small, none, large = (bound(member_set(KEY, members_of(n)))[0]
                          for n in (69, 0, 1025))
    assert small == none and hash(small) == hash(none) and small != large


# --------------------------------------------------------------------------
# through SQL: the fold, NULL members, VARCHAR pools
# --------------------------------------------------------------------------

SETUP = [
    "CREATE TABLE m.s.probe (id bigint, k bigint, name varchar)",
    "INSERT INTO m.s.probe VALUES (1, 10, 'ann'), (2, 20, 'bob'), "
    "(3, 30, 'cy'), (4, NULL, NULL), (5, -9223372036854775807, 'dee'), "
    "(6, 9223372036854775807, 'eve')",
    "CREATE TABLE m.s.members (v bigint, name varchar)",
    "INSERT INTO m.s.members VALUES (20, 'bob'), (9223372036854775807, "
    "'eve'), (-9223372036854775807, 'zed'), (77, 'ann'), (20, 'bob')",
    "CREATE TABLE m.s.holed (v bigint, name varchar)",
    "INSERT INTO m.s.holed VALUES (20, 'bob'), (NULL, NULL), (77, 'zed')",
]


@pytest.fixture(scope="module")
def session():
    cat = Catalog()
    cat.register("m", MemoryConnector())
    s = Session(catalog=cat, default_cat="m", default_schema="s")
    for sql in SETUP:
        s.execute(sql)
    s.execute("SET SESSION routing_mode = device")
    return s


def ids(session, where):
    """ids the predicate keeps; `id = 0` keeps the IN away from the
    conjunct position, where it would become a semi join."""
    return [r[0] for r in session.execute(
        f"SELECT id FROM probe WHERE id = 0 OR {where} ORDER BY id").rows]


@pytest.mark.parametrize("where,want", [
    # the int64 extremes and a duplicate member; a NULL key is UNKNOWN
    ("k IN (SELECT v FROM members)", [2, 5, 6]),
    ("k NOT IN (SELECT v FROM members)", [1, 3]),
    # no member: FALSE, and NOT IN keeps every key that is not NULL
    ("k IN (SELECT v FROM members WHERE v = 1)", []),
    ("k NOT IN (SELECT v FROM members WHERE v = 1)", [1, 2, 3, 5, 6]),
    # a NULL member: an unmatched key is UNKNOWN, not FALSE, so WHERE
    # drops it under IN and under NOT IN alike; a matched one is TRUE
    ("k IN (SELECT v FROM holed)", [2]),
    ("k NOT IN (SELECT v FROM holed)", []),
    # VARCHAR through the pools: 'zed' is in no probe row's pool
    ("name IN (SELECT name FROM members)", [1, 2, 6]),
    ("name NOT IN (SELECT name FROM members)", [3, 5]),
    ("name IN (SELECT name FROM holed)", [2]),
    ("name NOT IN (SELECT name FROM holed)", []),
], ids=lambda v: v if isinstance(v, str) else "")
def test_sql_semantics_of_a_folded_in_subquery(session, where, want):
    ex = session.executor
    probes0 = ex.stats.in_set_probes
    assert ids(session, where) == want
    # the set program ran, once: the fold made an `ir.InSet`
    assert ex.stats.in_set_probes == probes0 + 1
