"""Session surface tests: DDL/DML, SHOW/DESCRIBE, SET SESSION,
EXPLAIN (ANALYZE), information_schema, system.runtime.

Reference patterns: trino-memory connector tests, information_schema
connector, SystemSessionProperties, EXPLAIN ANALYZE output
(SURVEY.md §2.5, §2.11, §5.5, §5.6).
"""

import pytest

from trino_tpu.client.client import Client
from trino_tpu.exec.session import Session
from trino_tpu.server.coordinator import CoordinatorServer


@pytest.fixture()
def session():
    return Session(default_cat="memory", default_schema="default")


@pytest.fixture(scope="module")
def tpch_session():
    return Session(default_schema="tiny")


def test_create_insert_select_drop(session):
    session.execute("CREATE TABLE default.t (a bigint, b varchar)")
    r = session.execute(
        "INSERT INTO t VALUES (1, 'x'), (2, 'y'), (3, NULL)")
    assert r.rows == [(3,)]
    got = session.execute("SELECT a, b FROM t ORDER BY a").rows
    assert got == [(1, "x"), (2, "y"), (3, None)]
    session.execute("INSERT INTO t VALUES (4, 'z')")
    got = session.execute(
        "SELECT count(*), count(b) FROM t").rows
    assert got == [(4, 3)]
    session.execute("DROP TABLE t")
    with pytest.raises(Exception):
        session.execute("SELECT * FROM t")


def test_ctas(session, tpch_session):
    tpch_session.execute("""
        CREATE TABLE memory.default.top_nations AS
        SELECT n_name, n_regionkey FROM tpch.tiny.nation
        WHERE n_regionkey = 1""")
    got = tpch_session.execute(
        "SELECT n_name FROM memory.default.top_nations "
        "ORDER BY n_name").rows
    assert len(got) == 5
    assert got[0][0] == "ARGENTINA"
    tpch_session.execute("DROP TABLE memory.default.top_nations")


def test_show_catalogs_schemas_tables(tpch_session):
    cats = [r[0] for r in tpch_session.execute("SHOW CATALOGS").rows]
    assert "tpch" in cats and "memory" in cats and "tpcds" in cats
    schemas = [r[0] for r in tpch_session.execute(
        "SHOW SCHEMAS FROM tpch").rows]
    assert "tiny" in schemas and "sf1" in schemas
    tables = [r[0] for r in tpch_session.execute("SHOW TABLES").rows]
    assert "lineitem" in tables


def test_describe(tpch_session):
    rows = tpch_session.execute("DESCRIBE nation").rows
    names = [r[0] for r in rows]
    assert names == ["n_nationkey", "n_name", "n_regionkey", "n_comment"]


def test_set_show_session(tpch_session):
    rows = dict((r[0], r[1]) for r in
                tpch_session.execute("SHOW SESSION").rows)
    assert rows["distributed"] == "False"
    tpch_session.execute("SET SESSION query_max_rows = 5000")
    rows = dict((r[0], r[1]) for r in
                tpch_session.execute("SHOW SESSION").rows)
    assert rows["query_max_rows"] == "5000"


def test_set_session_distributed_swaps_executor(tpch_session):
    from trino_tpu.parallel.dist_executor import MeshExecutor
    tpch_session.execute("SET SESSION distributed = true")
    assert isinstance(tpch_session.executor, MeshExecutor)
    r = tpch_session.execute("SELECT count(*) FROM lineitem")
    assert r.rows[0][0] > 0
    tpch_session.execute("SET SESSION distributed = false")
    assert not isinstance(tpch_session.executor, MeshExecutor)


def test_explain(tpch_session):
    text = "\n".join(r[0] for r in tpch_session.execute(
        "EXPLAIN SELECT count(*) FROM lineitem WHERE l_quantity > 10"
    ).rows)
    assert "TableScan" in text and "Aggregate" in text


def test_explain_analyze_has_stats(tpch_session):
    text = "\n".join(r[0] for r in tpch_session.execute(
        "EXPLAIN ANALYZE SELECT l_returnflag, count(*) FROM lineitem "
        "GROUP BY l_returnflag").rows)
    assert "rows]" in text and "ms" in text


def test_information_schema(tpch_session):
    rows = tpch_session.execute("""
        SELECT table_name FROM tpch.information_schema.tables
        WHERE table_schema = 'tiny' ORDER BY table_name""").rows
    assert ("lineitem",) in rows
    cols = tpch_session.execute("""
        SELECT column_name, data_type
        FROM tpch.information_schema.columns
        WHERE table_name = 'nation' AND table_schema = 'tiny'
        ORDER BY ordinal_position""").rows
    assert cols[0][0] == "n_nationkey"


def test_system_runtime_queries():
    coord = CoordinatorServer(Session(default_schema="tiny")).start()
    try:
        client = Client(coord.uri, user="sys")
        client.execute("SELECT 1")
        rows = client.execute(
            "SELECT query_id, state, user FROM system.runtime.queries "
            "ORDER BY query_id").rows
        assert len(rows) >= 1
        assert any(r[2] == "sys" for r in rows)
        nodes = client.execute(
            "SELECT node_id, state FROM system.runtime.nodes").rows
        assert isinstance(nodes, list)
    finally:
        coord.stop()


def test_join_distribution_property_flips_plan():
    s = Session(default_schema="tiny")
    sql = ("SELECT c_name FROM customer c JOIN orders o"
           " ON c.c_custkey = o.o_custkey LIMIT 1")
    auto = s.execute("EXPLAIN " + sql).rows
    assert any("dist=broadcast" in r[0] for r in auto), auto
    s.execute("SET SESSION join_distribution_type = 'partitioned'")
    forced = s.execute("EXPLAIN " + sql).rows
    assert any("dist=partitioned" in r[0] for r in forced), forced
    # stats flip: a 0-byte threshold pushes every build to partitioned
    s.execute("SET SESSION join_distribution_type = 'auto'")
    s.execute("SET SESSION broadcast_join_threshold_mb = 0")
    tiny = s.execute("EXPLAIN " + sql).rows
    assert any("dist=partitioned" in r[0] for r in tiny), tiny


def test_query_deadline_enforced():
    import pytest as _pytest
    from trino_tpu.exec.executor import QueryDeadlineError
    s = Session(default_schema="tiny")
    s.execute("SET SESSION query_max_run_time_s = 0.000001")
    with _pytest.raises(QueryDeadlineError):
        s.execute("SELECT count(*) FROM lineitem, orders"
                  " WHERE l_orderkey = o_orderkey")
    s.execute("SET SESSION query_max_run_time_s = 0")
    r = s.execute("SELECT count(*) FROM nation")
    assert r.rows[0][0] == 25


def test_scan_cache_lru_eviction():
    s = Session(default_schema="tiny")
    s.execute("SET SESSION scan_cache_max_mb = 0")
    resident = s.executor.resident
    for t in ("nation", "region", "supplier", "customer", "orders"):
        s.execute(f"SELECT count(*) FROM {t}")
        # a zero budget keeps nothing resident
        assert len(resident) == 0 and resident.total_bytes() == 0
    # results stay correct with nothing kept
    assert s.execute("SELECT count(*) FROM nation").rows[0][0] == 25
    s.execute("SET SESSION scan_cache_max_mb = 1024")
    s.execute("SELECT count(*) FROM nation")
    s.execute("SELECT count(*) FROM region")
    assert {k[3] for k in resident.keys()} == {"nation", "region"}


def test_dynamic_filtering_toggle():
    s = Session(default_schema="tiny")
    sql = ("SELECT count(*) FROM lineitem, orders"
           " WHERE l_orderkey = o_orderkey AND o_orderkey < 100")
    want = s.execute(sql).rows
    s.execute("SET SESSION dynamic_filtering = false")
    got = s.execute(sql).rows
    assert got == want
