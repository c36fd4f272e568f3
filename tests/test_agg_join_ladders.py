"""The aggregation ladder (global, direct, sort) and the join ladder
(dense-LUT, sort-merge/sorted, expand; mark and membership joins) on
their edge-case inputs, each compared with an independent answer: the
sqlite oracle (tests/oracle.py) for SQL statements, numpy for kernel
calls. Never one strategy against another.

The inputs: NULLs in keys and values, packed multi-column keys with
NULL groups, sparse int64 keys at both ends of the range, more groups
than the planned capacity, DATE / DECIMAL / dictionary VARCHAR keys,
DISTINCT, the spill tier under a memory limit, merges of partial
states on both sides of SORT_SMALL_ROWS; joins on key domains the dense
LUT refuses, duplicate build keys under a plan that claimed a unique
build, builds larger than their probe, and stars of 2 to 5 dimensions.
"""

from types import SimpleNamespace

import numpy as np
import pytest

from oracle import assert_rows_match, load_oracle, oracle_query
from trino_tpu.batch import Field, Schema, batch_from_numpy, batch_to_numpy
from trino_tpu.catalog import default_catalog
from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.connectors.tpch.datagen import TableData
from trino_tpu.exec.executor import SORT_SMALL_ROWS, Executor
from trino_tpu.exec.session import Session
from trino_tpu.ops.aggregate import AggSpec
from trino_tpu.planner import logical as L
from trino_tpu.types import BIGINT

I64 = np.iinfo(np.int64)
SMALL, BIG = 1500, 6000       # rows on either side of SORT_SMALL_ROWS
assert SMALL < SORT_SMALL_ROWS < BIG


def table(name, cols, primary_key=()):
    """TableData of BIGINT columns from {name: array | (array, valid)}."""
    arrays, valids = [], []
    for v in cols.values():
        a, ok = v if isinstance(v, tuple) else (v, None)
        arrays.append(np.asarray(a, dtype=np.int64))
        valids.append(ok)
    return TableData(
        name, Schema.of(*[Field(c, BIGINT) for c in cols]), arrays,
        primary_key=primary_key,
        valids=valids if any(v is not None for v in valids) else None)


def session_over(tables):
    """(session, oracle) over `tables` as memory tables m.s.<name>; the
    sqlite side holds the same rows under the bare names."""
    cat = default_catalog()
    mem = MemoryConnector()
    cat.register("m", mem)
    for t in tables:
        mem.create_table("s", t.name, t)
    return (Session(catalog=cat, default_cat="m", default_schema="s"),
            load_oracle(tables))


def check(session, oracle, sql, ordered=False):
    got = session.execute(sql).rows
    assert_rows_match(got, oracle_query(oracle, sql), rel_tol=1e-9,
                      abs_tol=0.01, ordered=ordered)


def ran(session, op):
    return session.executor.strategy_decisions.get(op)


# ---- aggregation: SQL over crafted tables vs sqlite -----------------------

def agg_tables(n, seed):
    rng = np.random.default_rng(seed)
    ends = np.concatenate([
        I64.min + 1 + rng.integers(0, 40, n // 2),
        I64.max - rng.integers(0, 40, n - n // 2)])
    rng.shuffle(ends)
    return [
        table("nulls", {
            "k": (rng.integers(-40, 160, n), rng.random(n) > 0.1),
            "v": (rng.integers(-(1 << 52), 1 << 52, n),
                  rng.random(n) > 0.1)}),
        table("multi", {
            "k1": (rng.integers(0, 12, n), rng.random(n) > 0.2),
            "k2": (rng.integers(-5, 7, n), rng.random(n) > 0.2),
            "v": rng.integers(-1000, 1000, n)}),
        table("ends", {"k": ends, "v": rng.integers(-1000, 1000, n)}),
    ]


AGG_SQL = {
    "nulls": "SELECT k, sum(v), count(v), min(v), max(v), count(*) "
             "FROM nulls GROUP BY k",
    "multi": "SELECT k1, k2, sum(v), count(*) FROM multi GROUP BY k1, k2",
    "ends": "SELECT k, sum(v), min(v), count(*) FROM ends GROUP BY k",
}


@pytest.fixture(scope="module", params=[SMALL, BIG], ids=["small", "big"])
def agg_env(request):
    return session_over(agg_tables(request.param, seed=request.param))


@pytest.mark.parametrize("name", sorted(AGG_SQL))
def test_sort_aggregate_edge_keys_match_oracle(agg_env, name):
    """NULL keys group together and NULL values drop out of sum/min/max;
    two keys pack into one word with their NULL groups apart; keys at
    both ends of int64 cannot pack and take the general kernel. Small
    tables sort (key, ...) operands, big ones a packed word."""
    session, oracle = agg_env
    check(session, oracle, AGG_SQL[name])
    assert ran(session, "AggregateNode") == "sort"


@pytest.mark.parametrize("form,rows,scale", [
    ("permutation", 70_000, 1 << 40), ("carried", 300_000, 1)])
def test_more_groups_than_capacity_retries_and_matches_oracle(
        form, rows, scale, monkeypatch):
    """An expression key has no NDV statistics, so the plan sizes the
    output at the default; 70,000 groups overflow it and the executor
    grows the capacity and sorts again. On the permutation form of the
    packed sort aggregate (a sum argument too wide to share the keys'
    sort word), and on the value-carrying form where the default is far
    enough under the input's capacity that the groups are read back
    dense; in place (70,000 rows of narrow values) nothing can overflow
    and nothing retries."""
    import trino_tpu.ops.aggregate as aggregate
    # the default capacity (65,536) is dense for 300,000 rows at 4,
    # which the chip's timings would only ask for past 8 million
    monkeypatch.setattr(aggregate, "IN_PLACE_FACTOR", 4)
    n = 70_000
    rng = np.random.default_rng(3)
    session, oracle = session_over([table("wide", {
        "k": rng.permutation(rows) % n * 7919,
        "v": rng.integers(0, 9, rows) * scale})])
    check(session, oracle,
          "SELECT k + 1, sum(v), count(*) FROM wide GROUP BY k + 1")
    assert session.executor.stats.agg_capacity_retries > 0
    if form == "carried":
        # the second capacity is within IN_PLACE_FACTOR of the input's
        assert session.executor.stats.agg_capacity_retries == 1
        session, oracle = session_over([table("wide", {
            "k": rng.permutation(n) * 7919, "v": rng.integers(0, 9, n)})])
        check(session, oracle,
              "SELECT k + 1, sum(v), count(*) FROM wide GROUP BY k + 1")
        assert session.executor.stats.agg_capacity_retries == 0


@pytest.fixture(scope="module")
def tpch():
    s = Session(default_schema="tiny")
    conn = s.catalog.connector("tpch")
    return s, load_oracle([conn.get_table("tiny", t) for t in
                           ("customer", "orders", "lineitem")])


TYPED_KEY_SQL = {
    "date": "SELECT o_orderdate, count(*), sum(o_totalprice), "
            "avg(o_totalprice) FROM orders WHERE o_orderkey <= 6400 "
            "GROUP BY o_orderdate ORDER BY o_orderdate",
    "decimal": "SELECT o_totalprice, count(*), max(o_custkey) FROM orders "
               "WHERE o_orderkey <= 6400 GROUP BY o_totalprice "
               "ORDER BY o_totalprice",
    "varchar-dict": "SELECT o_orderpriority, o_custkey, count(*), "
                    "min(o_orderkey) FROM orders GROUP BY "
                    "o_orderpriority, o_custkey "
                    "ORDER BY o_orderpriority, o_custkey",
    "distinct": "SELECT o_orderpriority, count(DISTINCT o_custkey) "
                "FROM orders GROUP BY o_orderpriority "
                "ORDER BY o_orderpriority",
}


@pytest.mark.parametrize("name", sorted(TYPED_KEY_SQL))
def test_sort_aggregate_typed_keys_match_oracle(tpch, name):
    """DATE, DECIMAL and dictionary-coded VARCHAR keys, and a DISTINCT
    aggregate (which only the sort kernel serves)."""
    session, oracle = tpch
    check(session, oracle, TYPED_KEY_SQL[name], ordered=True)
    assert ran(session, "AggregateNode") == "sort"


def test_aggregate_under_memory_limit_spills_and_matches_oracle(tpch):
    _, oracle = tpch
    sql = ("SELECT o_custkey, count(*), sum(o_totalprice), "
           "min(o_orderdate), max(o_orderkey) FROM orders "
           "GROUP BY o_custkey")
    s = Session(default_schema="tiny")
    s.execute("SET SESSION query_max_memory_mb = 1")   # peak is 1.4 MB
    check(s, oracle, sql)
    assert s.executor.stats.spilled_aggregations > 0


def np_merge(keys, sums, counts):
    want = {}
    for k, s, c in zip(keys.tolist(), sums.tolist(), counts.tolist()):
        a = want.setdefault(k, [0, 0])
        a[0] += s
        a[1] += c
    return sorted((k, s, c) for k, (s, c) in want.items())


@pytest.mark.parametrize("rows", [SMALL // 2, BIG // 2],
                         ids=["general", "packed-word"])
def test_merge_group_aggregate_matches_numpy(rows):
    """The FINAL merge of partial states (the chunked driver's, the
    spill tier's, a task's fold): two pages of (key, sum, count) states
    merge to numpy's answer through the general kernel below
    SORT_SMALL_ROWS and the packed-word kernel above it."""
    from trino_tpu.exec.executor import concat_batches
    rng = np.random.default_rng(rows)
    cols = [np.concatenate([rng.integers(0, rows // 3, rows)
                            for _ in range(2)]),
            rng.integers(-(1 << 30), 1 << 30, 2 * rows),
            rng.integers(1, 5, 2 * rows)]
    merged = concat_batches(
        batch_from_numpy([c[:rows] for c in cols]),
        batch_from_numpy([c[rows:] for c in cols]))
    assert (merged.capacity > SORT_SMALL_ROWS) == (rows > SMALL)
    node = SimpleNamespace(strategy="sort", group_keys=(0,))
    out = Executor(default_catalog()).merge_group_aggregate(
        node, merged, (AggSpec("sum", 1), AggSpec("sum", 2)),
        merged.capacity)
    arrays, _ = batch_to_numpy(out)
    assert sorted(zip(*(a.tolist() for a in arrays))) == np_merge(*cols)


# ---- joins: key domains the dense LUT refuses ----------------------------

def sparse_tables(n_probe, n_build, seed=11, dup_build=False):
    rng = np.random.default_rng(seed)
    bk = rng.choice(1 << 40, n_build, replace=False) * 1009
    if dup_build:
        bk[1::2] = bk[0::2][:len(bk[1::2])]
    hits = rng.choice(bk, n_probe // 2)
    pk = np.concatenate([hits, rng.integers(0, 1 << 50, n_probe - len(hits))])
    rng.shuffle(pk)
    return [
        table("probe", {"pk": (pk, rng.random(n_probe) > 0.05),
                        "pv": rng.integers(0, 9, n_probe)}),
        table("build", {"bk": (bk, rng.random(n_build) > 0.05),
                        "bv": rng.integers(0, 99, n_build)},
              primary_key=("bk",)),
    ]


JOIN_SQL = {
    "inner": "SELECT pk, pv, bv FROM probe JOIN build ON pk = bk",
    "left": "SELECT pk, pv, bv FROM probe LEFT JOIN build ON pk = bk",
    "semi": "SELECT pk, pv FROM probe WHERE EXISTS "
            "(SELECT 1 FROM build WHERE bk = pk)",
    "anti": "SELECT pk, pv FROM probe WHERE NOT EXISTS "
            "(SELECT 1 FROM build WHERE bk = pk)",
}


@pytest.fixture(scope="module", params=[(900, 300), (5000, 1200)],
                ids=["small", "big"])
def sparse_env(request):
    return session_over(sparse_tables(*request.param))


@pytest.mark.parametrize("kind", sorted(JOIN_SQL))
def test_sparse_key_joins_match_oracle(sparse_env, kind):
    """Keys spread over 2^50 leave no dense LUT: unique-build inner and
    left joins take the merge kernel when both sides are small and
    sorted probing otherwise; membership joins probe the sorted build."""
    session, oracle = sparse_env
    check(session, oracle, JOIN_SQL[kind])
    assert ran(session, "JoinNode") in ("sort-merge", "sorted")


def test_duplicate_build_keys_fall_back_to_expansion():
    """The build's primary-key metadata lies (every key twice): the
    unique-build kernel reports the duplicates and the join expands."""
    session, oracle = session_over(sparse_tables(900, 300, seed=12,
                                                 dup_build=True))
    check(session, oracle, JOIN_SQL["inner"])
    assert session.executor.stats.join_fallbacks > 0
    assert ran(session, "JoinNode") == "expand"


def test_build_larger_than_probe_matches_oracle():
    session, oracle = session_over(sparse_tables(300, 3000, seed=13))
    for kind in ("inner", "left"):
        check(session, oracle, JOIN_SQL[kind])


# ---- stars: one fact, k unique-keyed dimensions ---------------------------

def star_tables(k, fact_rows=1 << 12, dim_rows=256, hit_rate=0.9,
                seed=40231, dup_dim=None):
    """One fact with k foreign-key columns and a value, k dimensions of
    one payload column each. `hit_rate` is the share of fact keys a
    dimension holds (keys past its range miss, so each inner hop drops
    1 - hit_rate of the rows). `dup_dim` doubles every key of that
    dimension under its primary-key claim."""
    rng = np.random.default_rng(seed + k)
    span = max(1, int(dim_rows / hit_rate))
    fact = {f"f_d{i}key": rng.integers(0, span, fact_rows)
            for i in range(k)}
    fact["f_value"] = rng.integers(0, 1 << 20, fact_rows)
    tables = [table("fact", fact)]
    for i in range(k):
        keys = np.arange(dim_rows)
        if i == dup_dim:
            keys[1::2] = keys[0::2]
        tables.append(table(
            f"dim{i}", {f"d{i}_key": keys,
                        f"d{i}_attr": rng.integers(0, 1000, dim_rows)},
            primary_key=(f"d{i}_key",)))
    return tables


def star_sql(k, agg=False):
    joins = " ".join(f"JOIN dim{i} ON f_d{i}key = d{i}_key"
                     for i in range(k))
    if agg:
        exprs = "".join(f" + d{i}_attr" for i in range(k))
        return f"SELECT sum(f_value{exprs}), count(*) FROM fact {joins}"
    cols = ", ".join(f"d{i}_attr" for i in range(k))
    return f"SELECT f_value, {cols} FROM fact {joins}"


@pytest.mark.parametrize("hit_rate", [0.9, 0.1])
@pytest.mark.parametrize("k", [2, 3, 4, 5])
def test_star_joins_match_oracle(k, hit_rate):
    session, oracle = session_over(star_tables(k, hit_rate=hit_rate))
    check(session, oracle, star_sql(k))
    assert ran(session, "JoinNode") == "dense-lut"


def test_star_with_duplicated_dimension_key_matches_oracle():
    session, oracle = session_over(star_tables(3, dup_dim=1))
    check(session, oracle, star_sql(3))
    assert session.executor.stats.join_fallbacks > 0


def test_star_on_the_mesh_matches_oracle():
    from trino_tpu.parallel.dist_executor import MeshExecutor
    from trino_tpu.parallel.mesh import make_mesh
    session, oracle = session_over(star_tables(3, hit_rate=0.7))
    session.executor = MeshExecutor(session.catalog, make_mesh(8))
    check(session, oracle, star_sql(3, agg=True))


def test_repeated_star_statement_compiles_nothing_new():
    from trino_tpu.exec.profiler import RECORDER
    session, _ = session_over(star_tables(3, hit_rate=0.7))
    sql = star_sql(3, agg=True)
    first = session.execute(sql).rows
    session.execute(sql)            # decisions settle
    before = RECORDER.totals()["compiles"]
    assert session.execute(sql).rows == first
    assert session.execute(sql).rows == first
    assert RECORDER.totals()["compiles"] == before


# ---- what the planner predicts is what the executor has -------------------

def test_planner_picks_only_strategies_the_executor_has():
    from tpch_full import QUERIES
    from trino_tpu.exec.executor import _subtree_nodes
    s = Session(default_schema="tiny")
    seen = set()
    for qid in sorted(QUERIES):
        _stmt, rel = s.plan(QUERIES[qid])
        seen |= {n.strategy for n in _subtree_nodes(rel.node)
                 if isinstance(n, L.AggregateNode)}
    assert seen and seen <= {"global", "direct", "sort"}, seen


def test_q3_at_sf1_predicts_the_sort_aggregate_that_runs():
    """q3's GROUP BY is high-cardinality (est 1M groups at sf1): the
    plan names the sort kernel, which is the one every split of q3
    runs. EXPLAIN ANALYZE (at tiny) reports no other strategy ran."""
    from tpch_full import QUERIES
    plan = [r[0] for r in Session(default_schema="sf1").execute(
        "EXPLAIN " + QUERIES[3]).rows]
    assert any(r.lstrip().startswith("Aggregate[sort,") for r in plan)
    (verdict,) = [r for r in plan if r.startswith("agg strategy:")]
    assert verdict.startswith("agg strategy: sort (est ")
    assert "kernel off" not in verdict and "[ran:" not in verdict
    analyzed = [r[0] for r in Session(default_schema="tiny").execute(
        "EXPLAIN ANALYZE " + QUERIES[3]).rows]
    (verdict,) = [r for r in analyzed if r.startswith("agg strategy:")]
    assert verdict.startswith("agg strategy: sort") and \
        "[ran:" not in verdict


def test_explain_carries_strategy_lines():
    s = Session(default_schema="tiny")
    rows = [r[0] for r in s.execute(
        "EXPLAIN SELECT o_custkey, count(*) FROM orders "
        "GROUP BY o_custkey").rows]
    assert any(r.startswith("agg strategy: sort") for r in rows)
    rows2 = [r[0] for r in s.execute(
        "EXPLAIN SELECT c_name, o_orderdate FROM customer, orders "
        "WHERE c_custkey = o_custkey").rows]
    assert any(r.startswith("join strategy:") for r in rows2)
    assert not any("distribution:" in r or "multiway" in r
                   for r in rows + rows2)


@pytest.mark.parametrize("statement", [
    "SET SESSION multiway_max_dims = 2",
    "SET SESSION mxu_agg = true",
    "SET SESSION enable_pallas_gather = false"])
def test_removed_session_properties_are_unknown(statement):
    from trino_tpu.exec.session import SESSION_PROPERTY_DEFAULTS
    assert len(SESSION_PROPERTY_DEFAULTS) == 38
    s = Session(default_schema="tiny")
    with pytest.raises(KeyError, match="unknown session property"):
        s.execute(statement)


def test_strategy_decision_metrics_move():
    from trino_tpu.metrics import (AGG_STRATEGY_DECISIONS,
                                   JOIN_STRATEGY_DECISIONS)
    # pre-initialized families (lint also enforces this)
    for strat in ("global", "direct", "sort"):
        assert AGG_STRATEGY_DECISIONS.has_sample(strategy=strat)
    assert not AGG_STRATEGY_DECISIONS.has_sample(strategy="mxu")
    joins = ("dense-lut", "dense-lut-packed", "sort-probe", "sort-merge",
             "sorted", "expand")
    for strat in joins:
        assert JOIN_STRATEGY_DECISIONS.has_sample(strategy=strat)
    s = Session(default_schema="tiny")
    before = AGG_STRATEGY_DECISIONS.value(strategy="direct")
    jsnap = {st: JOIN_STRATEGY_DECISIONS.value(strategy=st)
             for st in joins}
    s.execute("SELECT l_returnflag, count(*) FROM lineitem "
              "GROUP BY l_returnflag")
    s.execute("SELECT n_name FROM nation, region "
              "WHERE n_regionkey = r_regionkey AND r_name = 'ASIA'")
    assert AGG_STRATEGY_DECISIONS.value(strategy="direct") > before
    strategy = ran(s, "JoinNode")
    assert strategy in jsnap
    assert JOIN_STRATEGY_DECISIONS.value(strategy=strategy) > \
        jsnap[strategy]


def test_operator_stats_table_has_strategy_column():
    """The system table carries the per-operator strategy column and
    surfaces what the scheduler rollup recorded."""
    from trino_tpu.server.system_connector import SystemConnector
    sched = SimpleNamespace(operator_history=[
        {"query_id": "q1", "operator": "AggregateNode", "rows": 10,
         "wall_ms": 1.0, "calls": 1, "strategy": "sort"}])
    conn = SystemConnector(SimpleNamespace(scheduler=sched))
    data = conn.get_table("runtime", "operator_stats")
    names = [f.name for f in data.schema.fields]
    j = names.index("strategy")
    # decode through the schema dictionary: the recorded value survives
    code = int(data.columns[j][0])
    assert data.schema.fields[j].dictionary[code] == "sort"


def test_star_statement_records_its_join_strategy():
    session, _ = session_over(star_tables(3))
    session.execute(star_sql(3))
    assert session.executor.strategy_decisions == {"JoinNode": "dense-lut"}
