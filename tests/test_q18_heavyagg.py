"""TPC-H Q18 (benchmark/queries/q18.py: the text, the parameter domain
and the plain numpy reference) on the served routes: whole on the
coordinator's device executor (the single-node deployment) and as split
tasks through a worker, checked cell by cell with the benchmark's own
comparison. A second statement with another QUANTITY runs the programs
the first one compiled; the `aggregate` / `join` / `sort` spans of the
whole-statement route; EXPLAIN prints the IN subquery as a sub-plan.
"""

import time

import numpy as np
import pytest

from trino_tpu.client.client import Client
from trino_tpu.exec.profiler import RECORDER
from trino_tpu.exec.session import Session
from trino_tpu.server.coordinator import CoordinatorServer
from trino_tpu.server.worker import WorkerServer

from test_resident_tables import bench_module, reference_tables
from test_tracing_phases import CountingAnnotation

# at `tiny` TPC-H's own 312-315 keep no order; these keep 3,404, 799, 54,
# 1 and 0 (21,246, 5,343, 376, 7 and 0 lineitems)
QUANTITIES = (150, 200, 250, 300, 313)

q18 = bench_module("queries.q18")
compare = bench_module("compare")


class Served:
    """A coordinator, `workers` workers and a client over HTTP. With no
    worker every statement runs whole on the coordinator's device
    executor (at `tiny` the router would send it to the host
    interpreter, so the route is asked for)."""

    def __init__(self, workers=0):
        self.session = Session()
        self.coord = CoordinatorServer(self.session).start()
        self.workers = [WorkerServer(
            f"q18-w{i}", self.coord.uri, announce_interval_s=0.2,
            catalog=self.session.catalog).start() for i in range(workers)]
        deadline = time.monotonic() + 30
        while len(self.coord.state.active_nodes()) < workers:
            assert time.monotonic() < deadline, "a worker never announced"
            time.sleep(0.02)
        self.client = Client(self.coord.uri, user="q18")
        if workers:
            # tiny's lineitem (60,104 rows) and orders (15,000) in splits
            self.coord.state.scheduler.split_rows = 8192
        else:
            self.client.execute("SET SESSION routing_mode = device")

    def run(self, sql):
        """-> (rows, query info, spans)"""
        res = self.client.execute(sql)
        info = self.client.query_info(res.query_id)
        spans = self.client._request(
            "GET", f"{self.coord.uri}/v1/query/{res.query_id}/trace")["spans"]
        return res.rows, info, spans

    def stop(self):
        for w in self.workers:
            w.stop()
        self.coord.stop()


@pytest.fixture(scope="module")
def single():
    s = Served()
    yield s
    s.stop()


@pytest.fixture(scope="module")
def tiny_tables(single):
    return reference_tables(single.session, [q18])


def mismatched(rows, tables, quantity, narrow=False):
    want = q18.reference(tables, {"quantity": quantity}, narrow=narrow)
    return compare.mismatched_cells(rows, want, q18.COLUMNS), len(want)


@pytest.mark.parametrize("quantity", QUANTITIES)
def test_single_node_route_matches_the_reference(single, tiny_tables,
                                                 quantity):
    rows, info, _ = single.run(q18.render({"quantity": quantity},
                                          "tpch.tiny"))
    assert info["route"] == "device" and not info.get("distributed")
    (n, first), want_rows = mismatched(rows, tiny_tables, quantity)
    assert (n, first) == (0, None)
    assert len(rows) == want_rows and (want_rows > 0) == (quantity < 313)


@pytest.mark.parametrize("quantity", QUANTITIES)
def test_worker_route_matches_the_reference(tiny_tables, quantity,
                                            worker_cluster):
    rows, info, _ = worker_cluster.run(q18.render({"quantity": quantity},
                                                  "tpch.tiny"))
    assert info["distributed"] and not (
        info.get("fallbackReason") or "").startswith("task failure")
    (n, first), _ = mismatched(rows, tiny_tables, quantity)
    assert (n, first) == (0, None)


@pytest.fixture(scope="module")
def worker_cluster():
    s = Served(workers=1)
    yield s
    s.stop()


def test_a_new_quantity_compiles_nothing(single):
    """The validation set warms up, the sets a run draws follow: they
    keep 7, 376, 70 and 0 of 60,104 lineitems and meet the programs the
    first statement built (`batch.compaction_capacity`,
    `SORT_GENERAL_ROWS`; no capacity taken from the last run's group
    count). A set that keeps a third of the table (150) is another
    shape, here as anywhere."""
    # texts no other test of this file sends: a re-sent text finds its
    # plan cached, and with it the subquery's answer
    single.run(q18.render({"quantity": 299}, "tpch.tiny"))
    before = RECORDER.totals()
    for quantity in (252, 270, 314):
        single.run(q18.render({"quantity": quantity}, "tpch.tiny"))
    after = RECORDER.totals()
    assert after["compiles"] == before["compiles"]
    assert after["hits"] > before["hits"]


def test_sf1_parameter_sets_and_the_float32_control():
    """At sf1 TPC-H's own sets keep rows (312-315: 11, 11, 10, 7; the
    validation set 62), a total price passes 2^24 cents, and the
    statement after the warm-up compiles nothing."""
    s = Served()
    try:
        s.client.execute("SET SESSION routing_mode = auto")
        tables = reference_tables(s.session, [q18], "sf1")
        rows, info, _ = s.run(q18.render(q18.VALIDATION, "tpch.sf1"))
        assert info["route"] == "device" and not info.get("distributed")
        assert mismatched(rows, tables, 300) == ((0, None), 62)
        before = RECORDER.totals()["compiles"]
        for quantity, kept in ((313, 11), (315, 7)):
            rows, info, _ = s.run(q18.render({"quantity": quantity},
                                             "tpch.sf1"))
            assert info["route"] == "device"
            assert mismatched(rows, tables, quantity) == ((0, None), kept)
        assert RECORDER.totals()["compiles"] == before
        # the control: the reference in float32 is not the exact one
        exact = q18.reference(tables, {"quantity": 313})
        narrow = q18.reference(tables, {"quantity": 313}, narrow=True)
        assert len(narrow) == len(exact) == 11 and narrow != exact
        (n, _), _ = mismatched(rows, tables, 313, narrow=True)
        assert n > 0
    finally:
        s.stop()


def test_operator_spans_of_the_whole_statement_route(single, tiny_tables):
    answer = q18.reference(tiny_tables, {"quantity": 249})
    kept = len(answer)
    assert 0 < kept < 100        # every order it keeps is in the answer
    lineitems = int(np.isin(
        tiny_tables["lineitem"]["columns"]["l_orderkey"],
        [row[2] for row in answer]).sum())
    single.client.execute("SET SESSION enable_tracing = true")
    single.client.execute("SET SESSION enable_profiling = true")
    try:
        _, _, spans = single.run(q18.render({"quantity": 249}, "tpch.tiny"))
    finally:
        single.client.execute("SET SESSION enable_profiling = false")
        single.client.execute("SET SESSION enable_tracing = false")
    by = {}
    for sp in spans:
        by.setdefault(sp["name"], []).append(sp)
    (execute,) = by["execute"]
    assert execute["attributes"]["aggCapacityRetries"] == 0
    assert execute["attributes"]["spilledOperators"] == 0
    # the subquery's aggregate and the statement's; two joins; the top-n
    assert [len(by[n]) for n in ("aggregate", "join", "sort")] == [2, 2, 1]
    lo = execute["startTimeUnixNano"]
    hi = lo + execute["durationMs"] * 1e6
    for name in ("aggregate", "join", "sort"):
        for sp in by[name]:
            assert lo <= sp["startTimeUnixNano"] <= hi + 1e6
    ids = {sp["spanId"] for sp in spans}
    assert all(sp["parentSpanId"] in ids for n in ("aggregate", "join",
                                                   "sort") for sp in by[n])
    aggs = sorted(by["aggregate"], key=lambda sp: sp["startTimeUnixNano"])
    inner, outer = (sp["attributes"] for sp in aggs)
    assert inner["strategy"] == outer["strategy"] == "sort"
    # GROUP BY l_orderkey over all of lineitem: one group an order
    assert inner["inputRows"] == 60_104 and inner["groups"] == 15_000
    assert inner["capacityRetries"] == 0 and \
        inner["capacity"] >= inner["groups"]
    assert outer["groups"] == kept and outer["inputRows"] == lineitems
    for sp in by["join"]:
        a = sp["attributes"]
        assert a["kind"] == "inner" and a["strategy"] and \
            a["probeRows"] <= a["probeCapacity"] and \
            a["buildRows"] <= a["buildCapacity"] and a["domain"] > 0
    (sort,) = by["sort"]
    assert sort["attributes"] == {"capacity": outer["capacity"],
                                  "rows": kept, "limit": 100}
    # the operators' own walls, children taken out, lie inside execute's
    own = sum(sp["durationMs"] for n in ("aggregate", "join", "sort")
              for sp in by[n])
    assert 0 < own <= execute["durationMs"]
    assert single.session.executor._open_operators == []


def test_tracing_off_builds_no_operator_span(single, monkeypatch):
    import jax.profiler
    CountingAnnotation.names = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", CountingAnnotation)
    _, _, spans = single.run(q18.render({"quantity": 250}, "tpch.tiny"))
    assert spans == [] and CountingAnnotation.names == []
    executor = single.session.executor
    assert executor._operator_spans is False
    assert executor._open_operators == []
    # and on: each operator span has its twin on the profiler's clock
    single.client.execute("SET SESSION enable_tracing = true")
    try:
        single.run(q18.render({"quantity": 251}, "tpch.tiny"))
    finally:
        single.client.execute("SET SESSION enable_tracing = false")
    for name, n in (("tt:aggregate", 2), ("tt:join", 2), ("tt:sort", 1)):
        assert CountingAnnotation.names.count(name) == n


def test_a_worker_task_opens_operator_spans_beside_its_splits(
        worker_cluster):
    """A traced task's operators have spans too: in a split they hang
    under `worker-task` beside the `split` lap (or under the operator
    they run inside), say which split they are and nothing else, and are
    nobody's `compile` parent. tests/test_tracing_phases.py holds the
    split loop's spans to more."""
    worker_cluster.client.execute("SET SESSION enable_tracing = true")
    try:
        _, info, spans = worker_cluster.run(
            q18.render({"quantity": 201}, "tpch.tiny"))
    finally:
        worker_cluster.client.execute("SET SESSION enable_tracing = false")
    ids = {sp["spanId"]: sp for sp in spans}
    assert info["distributed"] and "worker-task" in {
        sp["name"] for sp in spans}
    operators = [sp for sp in spans if sp["name"] in (
        "aggregate", "join", "sort", "filter-project", "dynamic-filter")]
    in_splits = [sp for sp in operators if "split" in sp["attributes"]]
    assert {"aggregate", "join"} <= {sp["name"] for sp in in_splits}
    for sp in in_splits:
        assert list(sp["attributes"]) == ["split"]
        assert ids[sp["parentSpanId"]]["name"] in (
            "worker-task", "join", "aggregate", "filter-project")
    taken = {sp["spanId"] for sp in operators}
    assert not [sp for sp in spans if sp["name"] == "compile"
                and sp["parentSpanId"] in taken and
                "split" in ids[sp["parentSpanId"]]["attributes"]]
    ex = worker_cluster.workers[0].task_manager._executor
    assert ex._operator_spans is False and ex._operator_split is None
    assert ex._open_operators == []


def test_explain_prints_the_in_subquery_as_a_sub_plan():
    """Not as the `repr` of its plan, whose scans carry their tables'
    dictionaries (40,000 characters at sf1, c_name's 1.5M entries at
    sf10)."""
    s = Session()
    res = s.execute("EXPLAIN " + q18.render({"quantity": 300},
                                            "tpch.tiny"))
    text = "\n".join(str(r[0]) for r in res.rows)
    assert len(text) < 4000 and "dictionary=" not in text
    lines = text.splitlines()
    at = next(i for i, ln in enumerate(lines) if "InSubqueryRef" in ln)
    indent = len(lines[at]) - len(lines[at].lstrip())
    assert lines[at + 1].strip() == "Subquery"
    sub = lines[at + 2]
    assert sub.strip().startswith("Output[l_orderkey]") and \
        len(sub) - len(sub.lstrip()) == indent + 4
    # the subquery's scan is pruned to what it reads
    scans = [ln for ln in lines if "TableScan[tpch.tiny.lineitem]" in ln]
    assert len(scans) == 2 and all(
        "-> [l_orderkey, l_quantity]" in ln for ln in scans)


# a Q18-shaped statement (GROUP BY the fact table's key, HAVING on a
# decimal sum, as an IN subquery) and a q3-shaped one (three keys, a sum
# of a product) over all of lineitem: 60,104 rows take the packed sort
# aggregate; the first one's keys and argument share one sort word, the
# second one's 36 + 32 bits do not
CARRIED_SQL = (
    "SELECT o_orderkey, o_totalprice FROM {s}orders WHERE o_orderkey IN ("
    "SELECT l_orderkey FROM {s}lineitem GROUP BY l_orderkey "
    "HAVING sum(l_quantity) > 240) ORDER BY o_totalprice DESC, o_orderkey")
PERMUTED_SQL = (
    "SELECT l_orderkey, l_partkey, l_suppkey, "
    "sum(l_extendedprice * (1 - l_discount)) AS revenue FROM {s}lineitem "
    "GROUP BY l_orderkey, l_partkey, l_suppkey "
    "ORDER BY revenue DESC, l_orderkey, l_partkey, l_suppkey LIMIT 10")


@pytest.mark.parametrize("sql,carried", [(CARRIED_SQL, True),
                                         (PERMUTED_SQL, False)],
                         ids=["q18_shaped", "q3_shaped"])
def test_the_aggregate_span_says_what_rode_the_sort(single, sql, carried):
    from oracle import assert_rows_match, load_oracle, oracle_query
    conn = single.session.catalog.connector("tpch")
    oracle = load_oracle([conn.get_table("tiny", t)
                          for t in ("orders", "lineitem")])
    single.client.execute("SET SESSION enable_tracing = true")
    try:
        rows, info, spans = single.run(sql.format(s="tpch.tiny."))
    finally:
        single.client.execute("SET SESSION enable_tracing = false")
    assert info["route"] == "device" and not info.get("distributed")
    want = oracle_query(oracle, sql.format(s=""))
    assert len(want) > 5
    assert_rows_match(rows, want, rel_tol=1e-12, abs_tol=0.005,
                      ordered=True)
    aggregate = min((sp for sp in spans if sp["name"] == "aggregate"),
                    key=lambda sp: sp["startTimeUnixNano"])["attributes"]
    assert aggregate["strategy"] == "sort"
    assert aggregate["inputCapacity"] > 8192
    if carried:
        # under l_orderkey's 16 bits the two limbs of the decimal sum:
        # 2 bits for the high one (NULL and 0), 16 (13 measured) for
        # the low one
        assert aggregate["valueBits"] == 18
        assert aggregate["outputForm"] == "in-place"
        assert aggregate["capacity"] == aggregate["inputCapacity"]
        assert aggregate["groups"] == 15_000
    else:
        assert aggregate["valueBits"] == 0
        assert aggregate["outputForm"] == "dense"
    assert aggregate["capacityRetries"] == 0


def test_operations_guide_lists_the_aggregate_span_attributes():
    import os
    path = os.path.join(os.path.dirname(__file__), "..", "docs",
                        "operations.md")
    with open(path) as f:
        row = next(ln for ln in f if ln.startswith("| `aggregate`, `join`"))
    assert "`valueBits`" in row and "`outputForm`" in row
