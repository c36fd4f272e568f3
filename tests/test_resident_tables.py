"""Tables resident on the device once (exec/device_cache.ResidentSet):
a scanned column goes onto the device when a statement first needs it
and is found there by every later statement, whatever its literals.

The served single-node route (coordinator alone, client over HTTP)
under statements that never repeat, checked against the benchmark's
plain numpy references; the one budget and its eviction; zone-map
pruning on the resident copy; table versions; the `scan` / `evict` /
`execute` spans.
"""

import importlib
import os
import sys

import numpy as np
import pytest

from trino_tpu.batch import Field, Schema
from trino_tpu.client.client import Client
from trino_tpu.connectors.tpch.datagen import TableData
from trino_tpu.exec import device_cache
from trino_tpu.exec.session import Session
from trino_tpu.server.coordinator import CoordinatorServer
from trino_tpu.types import BIGINT
from trino_tpu.utils import tracing

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
SCHEMA = "tpch.tiny"


def bench_module(name):
    """A plain reference or the comparison of benchmark/: they import
    nothing of the program."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return importlib.import_module(name)


def reference_tables(session, templates, schema="tiny"):
    """The connector's generated columns, as benchmark/deploy.py hands
    them to the references."""
    out = {}
    for t in templates:
        for table, names in t.TABLES.items():
            data = session.catalog.get_table("tpch", schema, table)
            entry = out.setdefault(table, {"columns": {}, "dictionary": {}})
            for name in names:
                i = data.schema.index_of(name)
                entry["columns"][name] = np.asarray(data.columns[i])
                pool = data.schema.fields[i].dictionary
                if pool is not None:
                    entry["dictionary"][name] = tuple(pool)
    return out


class Served:
    """Coordinator with no worker, client over HTTP, every statement on
    the coordinator's device executor (at `tiny` the router would send
    them to the host interpreter) and traced."""

    def __init__(self):
        self.session = Session(default_schema="tiny")
        self.coord = CoordinatorServer(self.session).start()
        self.client = Client(self.coord.uri, user="resident")
        self.client.execute("SET SESSION routing_mode = device")
        self.client.execute("SET SESSION enable_tracing = true")
        self.resident = self.session.executor.resident

    def run(self, sql):
        """-> (rows, {span name: [spans]})"""
        res = self.client.execute(sql)
        info = self.client.query_info(res.query_id)
        assert info["route"] == "device" and not info.get("distributed")
        spans = self.client._request(
            "GET", f"{self.coord.uri}/v1/query/{res.query_id}/trace")["spans"]
        by = {}
        for sp in spans:
            by.setdefault(sp["name"], []).append(sp)
        return res.rows, by


@pytest.fixture
def served():
    s = Served()
    try:
        yield s
    finally:
        s.coord.stop()


def column_keys(resident, table):
    return sorted((k for k in resident.keys()
                   if k[0] == "column" and k[3] == table),
                  key=lambda k: (k[4] is not None, k[4]))


def execute_attrs(by):
    (ex,) = by["execute"]
    return ex["attributes"]


# ---------------------------------------------------------------------------
# (i) the served single-node route under traffic that never repeats
# ---------------------------------------------------------------------------

def test_join_statements_find_their_tables_resident(served):
    q3 = bench_module("queries.q3")
    compare = bench_module("compare")
    tables = reference_tables(served.session, [q3])
    params = [{"segment": s, "day": d} for s, d in zip(
        q3.SEGMENTS + q3.SEGMENTS[:3], (3, 9, 14, 20, 26, 30, 7, 18))]
    assert len({q3.render(p, SCHEMA) for p in params}) == 8
    kept = []
    for n, p in enumerate(params):
        rows, by = served.run(q3.render(p, SCHEMA))
        assert compare.mismatched_cells(
            rows, q3.reference(tables, p), q3.COLUMNS) == (0, None)
        attrs = execute_attrs(by)
        scans = by["scan"]
        assert sorted(sp["attributes"]["table"] for sp in scans) == \
            ["customer", "lineitem", "orders"]
        want = "miss" if n == 0 else "hit"
        assert all(sp["attributes"]["resident"] == want for sp in scans)
        if n == 0:
            assert attrs["scanPutBytes"] == attrs["residentBytes"] > 0
        else:
            assert attrs["scanPutBytes"] == 0
            assert all(sp["attributes"]["putBytes"] == 0 for sp in scans)
        kept.append((attrs["residentBytes"], attrs["residentEntries"]))
    assert kept[1] == kept[7] == kept[0]
    # ONE entry a table and column, and one live mask a table
    for table, t_cols in q3.TABLES.items():
        keys = column_keys(served.resident, table)
        assert len(keys) == len(t_cols) + 1 and keys[0][4] is None
    assert kept[0][1] == len(served.resident) == sum(
        len(c) + 1 for c in q3.TABLES.values())
    assert served.resident.total_bytes() == kept[0][0]


def test_scan_statements_share_columns_between_templates(served):
    q6, q1 = bench_module("queries.q6"), bench_module("queries.q1")
    compare = bench_module("compare")
    tables = reference_tables(served.session, [q6, q1])
    p6 = [{"year": y, "discount": d, "quantity": q} for y, d, q in
          ((1993, 2, 24), (1995, 5, 25), (1996, 7, 24), (1997, 9, 25))]
    p1 = [{"delta": d} for d in (60, 77, 101, 120)]
    kept, puts = [], []
    for n in range(8):
        t, p = (q6, p6[n // 2]) if n % 2 == 0 else (q1, p1[n // 2])
        rows, by = served.run(t.render(p, SCHEMA))
        assert compare.mismatched_cells(
            rows, t.reference(tables, p), t.COLUMNS) == (0, None)
        attrs = execute_attrs(by)
        kept.append(attrs["residentBytes"])
        puts.append(attrs["scanPutBytes"])
    # q6 puts its four columns and the live mask, the first q1 the three
    # columns q6 does not read; after that nothing is put again
    lineitem = served.session.catalog.get_table("tpch", "tiny", "lineitem")
    cap = served.session.executor._scan_capacity(lineitem.num_rows)
    width = {name: np.asarray(lineitem.columns[
        lineitem.schema.index_of(name)]).dtype.itemsize
        for name in q1.TABLES["lineitem"]}
    assert puts[0] == cap * (1 + sum(width[c] for c in
                                     q6.TABLES["lineitem"]))
    assert puts[1] == cap * sum(width[c] for c in q1.TABLES["lineitem"]
                                if c not in q6.TABLES["lineitem"])
    assert puts[2:] == [0] * 6
    assert kept[1] == kept[7] == puts[0] + puts[1]
    keys = column_keys(served.resident, "lineitem")
    assert len(keys) == len(q1.TABLES["lineitem"]) + 1 == \
        len(served.resident)


# ---------------------------------------------------------------------------
# (ii) the budget comes from the device
# ---------------------------------------------------------------------------

def test_default_budget_is_derived_from_the_device(monkeypatch):
    from trino_tpu.exec import profiler
    here = device_cache.default_resident_bytes()
    stats = profiler.device_memory_stats()
    if stats.get("bytesLimit"):
        assert 0 < here < stats["bytesLimit"]
    else:                       # the CPU reports no limit: still finite
        assert here == device_cache.HOST_RESIDENT_BYTES < 1 << 40
    monkeypatch.setattr(profiler, "device_memory_stats",
                        lambda device=None: {"bytesLimit": 16 << 30})
    assert device_cache.default_resident_bytes() == 8 << 30
    # the session's default defers to it; an explicit value overrides
    s = Session(default_schema="tiny")
    assert s.properties["scan_cache_max_mb"] == -1
    s.execute("SELECT count(*) FROM nation")
    assert s.executor.scan_cache_max_bytes == 8 << 30
    s.execute("SET SESSION scan_cache_max_mb = 24")
    s.execute("SELECT count(*) FROM nation")
    assert s.executor.scan_cache_max_bytes == 24 << 20


# ---------------------------------------------------------------------------
# (iii) eviction under a tiny explicit budget
# ---------------------------------------------------------------------------

def test_tiny_budget_evicts_and_answers_stay_right(served):
    q6, q1 = bench_module("queries.q6"), bench_module("queries.q1")
    compare = bench_module("compare")
    tables = reference_tables(served.session, [q6, q1])
    served.client.execute("SET SESSION scan_cache_max_mb = 1")
    budget = 1 << 20
    evicted = []
    for t, p in ((q6, {"year": 1994, "discount": 3, "quantity": 25}),
                 (q1, {"delta": 65}),
                 (q6, {"year": 1996, "discount": 8, "quantity": 24}),
                 (q1, {"delta": 111})):
        rows, by = served.run(t.render(p, SCHEMA))
        assert compare.mismatched_cells(
            rows, t.reference(tables, p), t.COLUMNS) == (0, None)
        attrs = execute_attrs(by)
        assert 0 < attrs["residentBytes"] <= budget
        assert served.resident.total_bytes() == attrs["residentBytes"]
        # under a budget smaller than the working set every statement
        # puts again what the one before pushed out
        assert attrs["scanPutBytes"] > 0
        (ex,) = by["execute"]
        for ev in by.get("evict", ()):
            assert ev["parentSpanId"] == ex["spanId"]
            assert ev["attributes"]["entries"] > 0
            evicted.append(ev["attributes"]["bytes"])
    assert evicted and all(b > 0 for b in evicted)
    assert sum(evicted) == served.resident.evicted_bytes


def test_entry_larger_than_the_budget_is_not_kept():
    rs = device_cache.ResidentSet(max_bytes=100)
    assert rs.put(("column", "a"), "A", 60)
    assert not rs.put(("column", "b"), "B", 101)
    assert rs.keys() == [("column", "a")] and rs.total_bytes() == 60
    freed = []
    assert rs.put(("build", "c"), "C", 50, on_evict=lambda: freed.append(1))
    assert rs.keys() == [("build", "c")]          # "a" went: LRU
    assert (rs.evicted_entries, rs.evicted_bytes) == (1, 60)
    rs.max_bytes = 10                             # a shrunk budget evicts
    assert len(rs) == 0 and rs.total_bytes() == 0 and freed == [1]


# ---------------------------------------------------------------------------
# (iv) zone-map pruning selects rows OF the resident copy
# ---------------------------------------------------------------------------

N, ZONE = 16384, 1024


@pytest.fixture
def sorted_table():
    s = Session(default_schema="tiny")
    rng = np.random.default_rng(11)
    data = TableData("sorted", Schema((Field("k", BIGINT),
                                       Field("x", BIGINT))),
                     [np.arange(N, dtype=np.int64),
                      rng.integers(0, 1000, N)])
    s.catalog.connector("memory").create_table("default", "sorted", data)
    s.execute(f"SET SESSION zone_map_rows = {ZONE}")
    return s, data


def test_zone_pruning_narrows_live_of_the_one_copy(sorted_table):
    s, data = sorted_table
    ex = s.executor
    sql = ("SELECT count(*), sum(x), min(k) FROM memory.default.sorted "
           "WHERE k >= {lo} AND k < {hi}")
    x = np.asarray(data.columns[1])
    for lo, hi in ((3000, 5000), (0, 10), (9000, 16384), (20000, 30000)):
        before = ex.stats.scan_zones_pruned
        s.execute("SET SESSION enable_zone_map_pruning = true")
        on = s.execute(sql.format(lo=lo, hi=hi)).rows
        assert ex.stats.scan_zones_pruned > before
        s.execute("SET SESSION enable_zone_map_pruning = false")
        off = s.execute(sql.format(lo=lo, hi=hi)).rows
        m = slice(lo, min(hi, N))
        n = len(x[m])
        assert on == off == [(n, int(x[m].sum()) if n else None,
                              lo if n else None)]
        # the predicate made no second copy: k, x and the live mask
        keys = column_keys(ex.resident, "sorted")
        assert [k[4] for k in keys] == [None, 0, 1]
    cap = ex._scan_capacity(N)
    assert ex.resident.total_bytes() == cap * (1 + 8 + 8)
    # a statement whose zones are cut puts its mask and nothing else
    s.execute("SET SESSION enable_zone_map_pruning = true")
    s.execute(sql.format(lo=100, hi=200))
    assert ex.scan_put_bytes == cap
    s.execute("SELECT count(*) FROM memory.default.sorted WHERE k >= 0")
    assert ex.scan_put_bytes == 0


# ---------------------------------------------------------------------------
# (v) a table's version is part of what a resident entry is
# ---------------------------------------------------------------------------

def test_dml_drops_the_resident_copy(sorted_table):
    s, _ = sorted_table
    ex = s.executor
    count = "SELECT count(*), max(k) FROM memory.default.sorted"
    assert s.execute(count).rows == [(N, N - 1)]
    assert column_keys(ex.resident, "sorted")
    s.execute("INSERT INTO memory.default.sorted VALUES (99999, 1)")
    assert not column_keys(ex.resident, "sorted")
    assert s.execute(count).rows == [(N + 1, 99999)]
    s.execute("DELETE FROM memory.default.sorted WHERE k > 10")
    assert not column_keys(ex.resident, "sorted")
    assert s.execute(count).rows == [(11, 10)]


def test_a_new_table_version_is_never_served_the_old_copy(sorted_table):
    """Without any invalidation: the connector holds a new TableData
    (what every mutation makes), and the entry made from the old one is
    replaced under the same key."""
    s, data = sorted_table
    ex = s.executor
    total = "SELECT sum(x) FROM memory.default.sorted"
    assert s.execute(total).rows == [(int(np.asarray(
        data.columns[1]).sum()),)]
    entries = len(ex.resident)
    doubled = TableData("sorted", data.schema,
                        [data.columns[0], np.asarray(data.columns[1]) * 2])
    s.catalog.connector("memory")._tables[("default", "sorted")] = doubled
    assert s.execute(total).rows == [(2 * int(np.asarray(
        data.columns[1]).sum()),)]
    assert ex.scan_put_bytes > 0 and len(ex.resident) == entries
    assert all(ex.resident.get(k)[0] is doubled
               for k in column_keys(ex.resident, "sorted"))


# ---------------------------------------------------------------------------
# (vi) spans and counters
# ---------------------------------------------------------------------------

def test_scan_and_execute_spans(served):
    sql = ("SELECT l_returnflag, count(*) FROM lineitem "
           "WHERE l_quantity < {q} GROUP BY l_returnflag")
    _, first = served.run(sql.format(q=10))
    _, second = served.run(sql.format(q=20))
    for by, resident in ((first, "miss"), (second, "hit")):
        (ex,), (scan,) = by["execute"], by["scan"]
        assert scan["parentSpanId"] == ex["spanId"]
        attrs = scan["attributes"]
        assert attrs["table"] == "lineitem"
        assert attrs["columns"] == "l_quantity,l_returnflag"
        assert attrs["resident"] == resident
        assert attrs["zonesPruned"] == 0
        assert (attrs["putBytes"] > 0) == (resident == "miss")
        assert ex["attributes"]["scanPutBytes"] == attrs["putBytes"]
        assert ex["attributes"]["residentEntries"] == 3
        assert ex["attributes"]["residentBytes"] == \
            served.resident.total_bytes()
        assert "evict" not in by


def test_nothing_is_built_with_tracing_off(monkeypatch):
    s = Session(default_schema="tiny")

    def refuse(*a, **kw):
        raise AssertionError("a span was built with tracing off")
    monkeypatch.setattr(tracing, "Span", refuse)
    monkeypatch.setattr(tracing, "_annotation", refuse)
    assert tracing.current() is tracing.NOOP
    s.execute("SET SESSION scan_cache_max_mb = 0")      # evicts, too
    orders = s.catalog.get_table("tpch", "tiny", "orders")
    keys = np.asarray(orders.columns[orders.schema.index_of("o_orderkey")])
    assert s.execute("SELECT count(*) FROM orders WHERE o_orderkey < 100"
                     ).rows == [(int((keys < 100).sum()),)]
    assert s.executor.scan_put_bytes > 0


# ---------------------------------------------------------------------------
# the structure key of a statement's subtrees (the decision cache's key)
# does not walk the tables' dictionaries
# ---------------------------------------------------------------------------

def test_structure_key_digests_a_schema_once(monkeypatch):
    from trino_tpu.planner import logical as L
    from trino_tpu.server import serde
    s = Session(default_schema="tiny")
    customer = s.catalog.get_table("tpch", "tiny", "customer")
    pool = customer.schema.field("c_name").dictionary
    assert len(pool) == customer.num_rows          # a name a row

    def scan(cols, predicate=None):
        return L.ScanNode("tpch", "tiny", "customer", customer.schema,
                          cols, (), predicate)
    ex = s.executor
    key = ex.build_structure_key(scan((0, 6)))
    # the same structure from new node objects: the same key; another
    # column set or a literal in the predicate: another key
    assert ex.build_structure_key(scan((0, 6))) == key
    assert ex.build_structure_key(scan((0, 5))) != key
    assert ex.build_structure_key(scan((0, 6), predicate=7)) != \
        ex.build_structure_key(scan((0, 6), predicate=8)) != key
    # the text that is hashed holds the schema's digest, not its pools,
    # and the digest was made once for all of the above
    text = serde.structure_text(scan((0, 6)))
    assert pool[0] not in text and "$schema" in text
    assert pool[0] in serde.dumps(scan((0, 6)))    # the wire form has it
    assert len(text) < 2000 < len(serde.dumps(customer.schema))
    monkeypatch.setattr(serde, "dumps", lambda obj: pytest.fail(
        "a known schema was serialised again"))
    assert ex.build_structure_key(scan((0, 6))) == key
    # a different schema gives a different key for the same node shape
    other = TableData("customer", Schema((Field("k", BIGINT),)),
                      [np.arange(3)])
    monkeypatch.undo()
    assert ex.build_structure_key(L.ScanNode(
        "tpch", "tiny", "customer", other.schema, (0, 6), ())) != key
