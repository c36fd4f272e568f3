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
from test_tracing_phases import (ROUNDING_NS, SPLIT_PHASES,
                                 CountingAnnotation, _inside, _interval)

# at `tiny` TPC-H's own 312-315 keep no order; these keep 3,404, 799, 54,
# 1 and 0 (21,246, 5,343, 376, 7 and 0 lineitems)
QUANTITIES = (150, 200, 250, 300, 313)
# through a worker also these, which keep 1,860, 227, 12 and 1 orders
WORKER_QUANTITIES = QUANTITIES + (175, 225, 275, 299)

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


@pytest.mark.parametrize("quantity", WORKER_QUANTITIES)
def test_worker_route_matches_the_reference(tiny_tables, quantity,
                                            worker_cluster):
    rows, info, _ = worker_cluster.run(q18.render({"quantity": quantity},
                                                  "tpch.tiny"))
    assert info["distributed"] and not (
        info.get("fallbackReason") or "").startswith("task failure")
    (n, first), want_rows = mismatched(rows, tiny_tables, quantity)
    assert (n, first) == (0, None)
    assert len(rows) == want_rows and (want_rows > 0) == (quantity < 313)


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
    they run inside), say which split they are and, a join, the form of
    its LUT, and are nobody's `compile` parent. tests/test_tracing_phases.py holds the
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
        said = dict(sp["attributes"])
        assert isinstance(said.pop("split"), int)
        if sp["name"] == "join":
            # every lap says which LUT it probed, a refusal also why
            # and that the LUT's miss stood in for the dynamic filter
            form, bits = said.pop("lutForm"), said.pop("wordBits")
            assert said.pop("dynamicFilter") == "lut"
            assert bits in ((8, 16, 32, 64) if form == "packed" else (32,))
            assert (form == "rows") == ("packRefused" in said)
            said.pop("packRefused", None)
        assert not said
        assert ids[sp["parentSpanId"]]["name"] in (
            "worker-task", "join", "aggregate", "filter-project")
    taken = {sp["spanId"] for sp in operators}
    assert not [sp for sp in spans if sp["name"] == "compile"
                and sp["parentSpanId"] in taken and
                "split" in ids[sp["parentSpanId"]]["attributes"]]
    ex = worker_cluster.workers[0].task_manager._executor
    assert ex._operator_spans is False and ex._operator_split is None
    assert ex._open_operators == []


def by_name(spans):
    out = {}
    for sp in spans:
        out.setdefault(sp["name"], []).append(sp)
    return out


def traced(served, quantity, profiling=False, sql=None):
    """-> (rows, spans) of a Q18 no other test sends (or of `sql`),
    traced."""
    served.client.execute("SET SESSION enable_tracing = true")
    if profiling:
        served.client.execute("SET SESSION enable_profiling = true")
    try:
        rows, info, spans = served.run(
            sql or q18.render({"quantity": quantity}, "tpch.tiny"))
    finally:
        served.client.execute("SET SESSION enable_profiling = false")
        served.client.execute("SET SESSION enable_tracing = false")
    assert bool(info.get("distributed")) == bool(served.workers)
    assert served.workers or info["route"] == "device"
    return rows, spans


@pytest.mark.parametrize("profiling", [False, True],
                         ids=["tracing", "fenced"])
def test_a_worker_folds_the_subquery_under_one_span_beside_its_split(
        worker_cluster, tiny_tables, profiling):
    """The IN subquery is folded by the WORKER's executor, inside the
    first split of the `orders` task: one `subquery-fold` a statement,
    under that task's `worker-task`, beside the `split` lap it ran in,
    the subquery's own operators under it."""
    quantity = 247 if profiling else 248
    kept = len(q18.reference(tiny_tables, {"quantity": quantity}))
    assert 0 < kept < 100        # every order it keeps is in the answer
    # a worker's executor keeps what it scans whole (the subquery's
    # columns; a split's rows come through the task's pipeline): as a
    # worker's first Q18 meets it
    ex = worker_cluster.workers[0].task_manager._executor
    ex.resident.clear()
    rows, spans = traced(worker_cluster, quantity, profiling)
    assert len(rows) == kept
    ids = {sp["spanId"]: sp for sp in spans}
    by = by_name(spans)
    (fold,) = by["subquery-fold"]
    task = ids[fold["parentSpanId"]]
    # 15,000 orders in splits of 8,192; lineitem's tasks have 8, customer's 1
    # bound once a task: the Project's slot, and the Filter's member set
    # with its count, tested once a split in one program
    assert task["name"] == "worker-task" and \
        task["attributes"]["splits"] == 2 and \
        task["attributes"]["literalSlots"] == 3 and \
        task["attributes"]["inSetProbes"] == 2 and \
        task["attributes"]["inSetCapacity"] == 1024
    assert fold["attributes"] == dict(
        fold["attributes"], kind="in", split=0, inputRows=60_104,
        members=kept)
    # tiny's lineitem padded to 60,416: two 8-byte columns and the mask
    assert fold["attributes"]["putBytes"] >= 2 * 8 * 60_104
    assert sorted(fold["attributes"]) == [
        "fetchedSlots", "inputRows", "kind", "members", "putBytes", "split"]
    # beside the lap, not under it: the lap's parent is the fold's, the
    # lap covers it
    (lap,) = [sp for sp in by["split"]
              if sp["parentSpanId"] == task["spanId"]
              and sp["attributes"]["index"] == 0]
    assert _inside(fold, lap) and _inside(fold, task)
    # the subquery's plan under it, whole-statement form: its scan of
    # lineitem, its 15,000-group aggregate
    under = by_name(sp for sp in spans
                    if sp["parentSpanId"] == fold["spanId"])
    (scan,) = under["scan"]
    assert scan["attributes"]["table"] == "lineitem" and \
        scan["attributes"]["putBytes"] == fold["attributes"]["putBytes"]
    (aggregate,) = under["aggregate"]
    assert aggregate["attributes"]["groups"] == 15_000 and \
        aggregate["attributes"]["inputRows"] == 60_104
    assert "split" not in aggregate["attributes"]
    assert all(_inside(sp, fold) for sps in under.values() for sp in sps)
    # the five laps of every split still touch exactly, in every task
    for wt in by["worker-task"]:
        laps = sorted((sp for n in SPLIT_PHASES for sp in by[n]
                       if sp["parentSpanId"] == wt["spanId"]),
                      key=lambda sp: sp["startTimeUnixNano"])
        assert len(laps) == 5 * wt["attributes"]["splits"]
        assert [sp["name"] for sp in laps[:5]] == [
            "split-read", "split-put", "split", "split-fetch", "split-emit"]
        for a, b in zip(laps, laps[1:]):
            assert abs(_interval(a)[1] - _interval(b)[0]) <= ROUNDING_NS
    assert ex._operator_split is None and ex._open_operators == []
    # the next statement finds the columns where this one put them
    _, spans = traced(worker_cluster, quantity - 10, profiling)
    (fold,) = by_name(spans)["subquery-fold"]
    assert fold["attributes"]["putBytes"] == 0 and \
        fold["attributes"]["inputRows"] == 60_104


def test_the_single_node_route_folds_under_execute(single, tiny_tables):
    kept = len(q18.reference(tiny_tables, {"quantity": 246}))
    assert 0 < kept < 100
    rows, spans = traced(single, 246)
    assert len(rows) == kept
    by = by_name(spans)
    (fold,) = by["subquery-fold"]
    (execute,) = by["execute"]
    assert fold["parentSpanId"] == execute["spanId"] and \
        _inside(fold, execute)
    assert fold["attributes"] == dict(
        fold["attributes"], kind="in", inputRows=60_104, members=kept)
    assert "split" not in fold["attributes"]
    # tiny's 60,416 group slots are under the floor a compaction pays
    # from; the members are tested once, in one program, at one capacity
    assert fold["attributes"]["fetchedSlots"] == 60_416
    assert execute["attributes"]["inSetProbes"] == 1 and \
        execute["attributes"]["inSetCapacity"] == 1024
    # what the fold's scans put is part of what the statement's did;
    # resident columns cost neither
    assert 0 <= fold["attributes"]["putBytes"] <= \
        execute["attributes"]["scanPutBytes"]
    (aggregate,) = [sp for sp in by["aggregate"]
                    if sp["parentSpanId"] == fold["spanId"]]
    assert aggregate["attributes"]["groups"] == 15_000
    # the filter that tests the member set opens behind the fold
    assert not [sp for sp in by["filter-project"]
                if _inside(fold, sp) and sp["spanId"] != fold["spanId"]
                and sp["parentSpanId"] == execute["spanId"]]


def test_a_scalar_subquery_folds_under_the_same_span(single):
    rows, spans = traced(
        single, None,
        sql="SELECT count(*) FROM tpch.tiny.orders WHERE o_totalprice > "
            "(SELECT avg(o_totalprice) FROM tpch.tiny.orders "
            "WHERE o_custkey < 1399)")
    assert 0 < rows[0][0] < 15_000
    (fold,) = by_name(spans)["subquery-fold"]
    assert fold["attributes"] == dict(
        fold["attributes"], kind="scalar", members=1, inputRows=15_000)


def test_tracing_off_builds_no_fold_span(single, worker_cluster,
                                         monkeypatch):
    import jax.profiler
    CountingAnnotation.names = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", CountingAnnotation)
    for served, quantity in ((single, 245), (worker_cluster, 245)):
        _, _, spans = served.run(q18.render({"quantity": quantity},
                                            "tpch.tiny"))
        assert spans == []
    assert CountingAnnotation.names == []
    # and on: the span has its twin on the profiler's clock, one a statement
    for served, quantity in ((single, 244), (worker_cluster, 244)):
        traced(served, quantity)
    assert CountingAnnotation.names.count("tt:subquery-fold") == 2


def test_the_fold_runs_once_a_task_not_once_a_split(worker_cluster,
                                                    monkeypatch):
    """Three `orders` splits bind the task's IN list once
    (`bound_exprs`), so the subquery's scan reads lineitem once."""
    from trino_tpu.exec.executor import Executor
    folds = []
    fold_span = Executor._fold_span

    def counted(self, kind):
        folds.append(kind)
        return fold_span(self, kind)
    monkeypatch.setattr(Executor, "_fold_span", counted)
    scheduler = worker_cluster.coord.state.scheduler
    monkeypatch.setattr(scheduler, "split_rows", 5000)
    _, spans = traced(worker_cluster, 243)
    by = by_name(spans)
    (fold,) = by["subquery-fold"]
    task = next(sp for sp in by["worker-task"]
                if sp["spanId"] == fold["parentSpanId"])
    assert task["attributes"]["splits"] == 3
    assert folds == ["in"] and fold["attributes"]["inputRows"] == 60_104


def test_a_task_fetches_the_live_members_and_tests_them_in_one_program(
        worker_cluster, tiny_tables, monkeypatch):
    """A task's split loop is a chunked loop, and the fold compacts
    before it fetches all the same (one sync a task): what comes to the
    host has the compacted capacity, not the subquery's. The members
    are one operand of one program a split, so an `orders` split
    dispatches as much with a dozen members as with over a hundred, and
    the answer still goes with its task."""
    from trino_tpu.batch import compaction_capacity
    ex = worker_cluster.workers[0].task_manager._executor
    # tiny's subquery answers in 60,416 slots, under the floor a
    # compaction pays from: lowered for this executor, as sf10's 67M
    # slots are over it
    monkeypatch.setattr(ex, "COMPACT_MIN_ROWS", 1024)
    seen = {}
    for quantity in (271, 231):
        rows, spans = traced(worker_cluster, quantity)
        ids = {sp["spanId"]: sp for sp in spans}
        (fold,) = by_name(spans)["subquery-fold"]
        members = fold["attributes"]["members"]
        # every order the subquery keeps is in the answer, a top-100
        assert min(members, 100) == len(rows) == len(
            q18.reference(tiny_tables, {"quantity": quantity}))
        assert fold["attributes"]["fetchedSlots"] == \
            compaction_capacity(members, 60_416) == 1024
        task = ids[fold["parentSpanId"]]
        laps = sorted((sp for sp in spans if sp["name"] == "split"
                       and sp["parentSpanId"] == task["spanId"]),
                      key=lambda sp: sp["attributes"]["index"])
        assert task["attributes"]["inSetProbes"] == len(laps) == 2 and \
            task["attributes"]["inSetCapacity"] == 1024
        seen[quantity] = (members,
                          [sp["attributes"]["dispatches"] for sp in laps])
        # no other task of the statement tests a set
        assert [wt["attributes"]["inSetProbes"]
                for wt in by_name(spans)["worker-task"]
                if wt is not task] == [0]
        assert ex._scalar_cache == {} and ex._bound_exprs == {}
    (few, laps_few), (many, laps_many) = seen[271], seen[231]
    assert few < 30 < 100 < many and laps_few == laps_many
    # the filter, the join and the projection: three programs a split
    assert laps_few[1] == 3


def test_folded_answers_go_with_the_task_and_the_statement(
        single, worker_cluster):
    """An executor keeps a folded subquery's answer (keyed by the ref,
    which holds the subquery's plan) no longer than the plan nodes'
    bound expressions: a worker's executor, which lives as long as the
    worker, holds none between tasks."""
    worker_cluster.run(q18.render({"quantity": 242}, "tpch.tiny"))
    ex = worker_cluster.workers[0].task_manager._executor
    assert ex._scalar_cache == {} and ex._bound_exprs == {}
    # the coordinator's executor ran the final stage
    ex = worker_cluster.session.executor
    assert ex._scalar_cache == {} and ex._bound_exprs == {}
    # whole on one executor the answer serves the statement (bound once
    # a plan node) and goes where the next statement begins
    ex = single.session.executor
    single.run(q18.render({"quantity": 242}, "tpch.tiny"))
    assert len(ex._scalar_cache) == 1
    single.run("SELECT count(*) FROM tpch.tiny.region")
    assert ex._scalar_cache == {}
    ex._scalar_cache["ref"] = ((1,), False)
    ex.release_all_reservations()
    assert ex._scalar_cache == {}


def test_operations_guide_lists_the_fold_span():
    import os
    path = os.path.join(os.path.dirname(__file__), "..", "docs",
                        "operations.md")
    with open(path) as f:
        row = next(ln for ln in f if ln.startswith("| `subquery-fold`"))
    for attribute in ("`kind`", "`inputRows`", "`members`", "`putBytes`",
                      "`split`", "`fetchedSlots`"):
        assert attribute in row
    with open(path) as f:
        row = next(ln for ln in f if ln.startswith("| `worker-task`"))
    assert "`inSetProbes`" in row and "`inSetCapacity`" in row


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
