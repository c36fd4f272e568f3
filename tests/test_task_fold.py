"""A worker task folds its splits' partial aggregates on the device and
stages one page (server/tasks.py `_run_splits`).

The reference in every case is the recipe the loop had before: run the
fragment root over each split alone, fetch it, and (for an aggregate)
hand the per-split partials to `merge_partials`, the coordinator's own
final step. Tasks run on a bare `TaskManager`, no HTTP; the end-to-end
cases go through a coordinator and one worker.
"""

import time

import numpy as np
import pytest

from trino_tpu.batch import (batch_from_numpy, batch_to_numpy,
                             bucket_capacity)
from trino_tpu.client.client import Client
from trino_tpu.exec.chunked import analyze, merge_partials
from trino_tpu.exec.executor import Executor
from trino_tpu.exec.memory import batch_bytes
from trino_tpu.exec.session import Session
from trino_tpu.planner.optimizer import prune_plan
from trino_tpu.server.coordinator import CoordinatorServer
from trino_tpu.server.failureinjector import DELAY, FailureInjector
from trino_tpu.server.tasks import (Split, TaskManager, _partial_entry,
                                    decode_columns, encode_columns,
                                    encode_fragment, partition_assignment)
from trino_tpu.server.worker import WorkerServer
from trino_tpu.sql.parser import parse
from trino_tpu.utils.tracing import format_traceparent, new_span_id, \
    new_trace_id

SPLIT_ROWS = 8192           # tiny lineitem: 8 splits

Q6 = ("SELECT sum(l_extendedprice * l_discount) AS revenue FROM lineitem "
      "WHERE l_shipdate >= DATE '1994-01-01' "
      "AND l_shipdate < DATE '1995-01-01' "
      "AND l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24")
Q1 = ("SELECT l_returnflag, l_linestatus, sum(l_quantity) AS q, "
      "sum(l_extendedprice * (1 - l_discount)) AS d, "
      "avg(l_discount) AS a, count(*) AS c FROM lineitem "
      "WHERE l_shipdate <= DATE '1998-09-02' "
      "GROUP BY l_returnflag, l_linestatus "
      "ORDER BY l_returnflag, l_linestatus")
# q3's shape without its joins: thousands of groups a split, none shared
# between splits, partials above SORT_SMALL_ROWS that enter compacted
BY_ORDER = ("SELECT l_orderkey, sum(l_extendedprice * (1 - l_discount)) AS r, "
            "min(l_discount) AS lo, max(l_tax) AS hi, count(*) AS c "
            "FROM lineitem WHERE l_shipdate > DATE '1995-03-15' "
            "GROUP BY l_orderkey ORDER BY r DESC, l_orderkey LIMIT 10")
Q3 = ("SELECT l_orderkey, sum(l_extendedprice * (1 - l_discount)) AS revenue, "
      "o_orderdate, o_shippriority FROM customer, orders, lineitem "
      "WHERE c_mktsegment = 'BUILDING' AND c_custkey = o_custkey "
      "AND l_orderkey = o_orderkey AND o_orderdate < DATE '1995-03-15' "
      "AND l_shipdate > DATE '1995-03-15' "
      "GROUP BY l_orderkey, o_orderdate, o_shippriority "
      "ORDER BY revenue DESC, o_orderdate, l_orderkey LIMIT 10")
CONCAT = ("SELECT l_orderkey, l_quantity FROM lineitem "
          "WHERE l_shipdate > DATE '1998-11-01'")
SORTED = CONCAT + " ORDER BY l_quantity DESC, l_orderkey"
AGGREGATES = {"q6": Q6, "q1": Q1, "by_order": BY_ORDER}


@pytest.fixture(scope="module")
def session():
    return Session(default_schema="tiny")


def _fragment(session, sql):
    """(fragment dict, splits, fragment root) as
    `scheduler._run_source_stage` cuts them."""
    rel = session.planner().plan_query(parse(sql))
    root = prune_plan(rel.node)
    analysis = analyze(root, session.catalog, SPLIT_ROWS,
                       allow_sort_merge=True)
    top = analysis.merge_agg if analysis.merge_agg is not None else (
        analysis.merge_sort if analysis.merge_sort is not None
        else root.child)
    frag = {"root": top, "driver": analysis.driver}
    if analysis.merge_agg is not None:
        frag["merge_agg"] = True
    d = analysis.driver
    splits = [Split(d.catalog, d.schema_name, d.table, start,
                    min(SPLIT_ROWS, analysis.driver_rows - start))
              for start in range(0, analysis.driver_rows, SPLIT_ROWS)]
    return frag, splits, top


def _per_split(session, frag, splits, held=None):
    """The loop as it was: each split's output alone, fetched. `held`
    takes the device bytes each would hold in a folding task."""
    ex = Executor(session.catalog)
    root, driver = frag["root"], frag["driver"]
    cap = bucket_capacity(max(s.count for s in splits))
    ex.enter_chunk_mode()
    outs = []
    for s in splits:
        data = session.catalog.get_table(s.catalog, s.schema_name, s.table)
        arrays = [np.asarray(data.columns[i])[s.start:s.start + s.count]
                  for i in driver.column_indices]
        ex._subst[id(driver)] = batch_from_numpy(arrays, capacity=cap)
        ex._subst_opaque.add(id(driver))
        out = ex.run(root)
        if held is not None:
            held.append(batch_bytes(_partial_entry(out)))
        outs.append(batch_to_numpy(out))
    return outs


def _run_task(tm, task_id, frag, splits, partition=None, traced=True,
              wait_s=120.0):
    tp = format_traceparent(new_trace_id(), new_span_id()) if traced \
        else None
    task = tm.create_or_update(task_id, encode_fragment(frag), splits,
                               partition=partition, traceparent=tp)
    deadline = time.monotonic() + wait_s
    while task.state in ("PENDING", "RUNNING") and \
            time.monotonic() < deadline:
        time.sleep(0.01)
    # stats and spans land with the terminal transition
    while not task.stats and time.monotonic() < deadline:
        time.sleep(0.01)
    return task


def _span(task, name):
    return [s for s in task.spans if s["name"] == name]


def _rows(arrs, vals):
    return sorted(tuple((a[i].item(), bool(v[i])) for a, v in
                        zip(arrs, vals)) for i in range(len(arrs[0])))


def _merged_rows(session, node, column_sets):
    partials = [batch_from_numpy(a, valids=v) for a, v in column_sets]
    return _rows(*batch_to_numpy(
        merge_partials(Executor(session.catalog), node, partials)))


@pytest.mark.parametrize("shape", sorted(AGGREGATES))
def test_task_stages_one_page_equal_to_the_merge_of_its_splits(session,
                                                                shape):
    frag, splits, node = _fragment(session, AGGREGATES[shape])
    per_split = _per_split(session, frag, splits)
    tm = TaskManager(session.catalog)
    task = _run_task(tm, f"fold-{shape}", frag, splits)
    assert task.state == "FINISHED", task.error
    assert task.splits_done == len(splits) > 1
    assert list(task.buffers) == [0] and len(task.buffers[0]) == 1
    got = decode_columns(task.buffers[0][0])
    assert _rows(*got) == _merged_rows(session, node, per_split)
    if shape == "by_order":     # an order or two straddle two splits
        assert 2048 < len(got[0][0]) <= sum(len(a[0]) for a, _ in per_split)
    (wt,) = _span(task, "worker-task")
    assert (wt["attributes"]["foldedSplits"], wt["attributes"]["pagesOut"],
            wt["attributes"]["flushes"]) == (len(splits), 1, 0)
    (merge,), (emit,) = _span(task, "task-merge"), _span(task, "task-emit")
    assert merge["attributes"]["partials"] == len(splits)
    assert emit["attributes"]["rows"] == len(got[0][0])
    assert emit["attributes"]["bytes"] == task.bytes_out == \
        len(task.buffers[0][0])
    assert task.rows_out == len(got[0][0])
    # the five phases still tile every split; nothing left in the pool
    for name in ("split-read", "split-put", "split", "split-fetch",
                 "split-emit"):
        assert len(_span(task, name)) == len(splits)
    assert tm.memory_info()["revocable"] == 0
    assert tm.memory_info()["reserved"] == 0


@pytest.mark.parametrize("shape", sorted(AGGREGATES))
def test_merge_of_merges_equals_one_merge(session, shape):
    """What the coordinator does with task pages (and a task with its own
    flushes): merging merged partials is merging the partials."""
    frag, splits, node = _fragment(session, AGGREGATES[shape])
    per_split = _per_split(session, frag, splits)
    ex = Executor(session.catalog)

    def merged(column_sets):
        return batch_to_numpy(merge_partials(
            ex, node, [batch_from_numpy(a, valids=v)
                       for a, v in column_sets]))
    halves = [merged(per_split[:3]), merged(per_split[3:4]),
              merged(per_split[4:])]
    assert _rows(*merged(halves)) == _rows(*merged(per_split))


@pytest.mark.parametrize("shape", sorted(AGGREGATES))
def test_a_low_budget_flushes_several_pages_with_the_same_answer(session,
                                                                  shape):
    frag, splits, node = _fragment(session, AGGREGATES[shape])
    held = []
    per_split = _per_split(session, frag, splits, held)
    tm = TaskManager(session.catalog)
    # room for about three partials as they are held; nobody acks, so
    # staging must not wait for a consumer
    tm.max_buffer_bytes = 3 * max(held)
    tm.backpressure_timeout_s = 0.0
    task = _run_task(tm, f"flush-{shape}", frag, splits)
    assert task.state == "FINISHED", task.error
    pages = task.buffers[0]
    (wt,) = _span(task, "worker-task")
    assert wt["attributes"]["flushes"] >= 1
    assert wt["attributes"]["pagesOut"] == len(pages) == \
        wt["attributes"]["flushes"] + len(_span(task, "task-emit"))
    assert 1 < len(pages) < len(splits)
    assert wt["attributes"]["foldedSplits"] == len(splits)
    assert _merged_rows(session, node, [decode_columns(p) for p in pages]) \
        == _merged_rows(session, node, per_split)
    # a flush is the `split-emit` of the split that passed the budget
    emitted = [s["attributes"]["bytes"] for s in _span(task, "split-emit")]
    assert sum(b > 0 for b in emitted) == wt["attributes"]["flushes"]
    assert sum(emitted) + sum(s["attributes"]["bytes"] for s in
                              _span(task, "task-emit")) == task.bytes_out
    assert tm.memory_info()["revocable"] == 0


@pytest.mark.parametrize("how", ["cancelled", "failed", "deadline"])
def test_a_task_that_does_not_finish_stages_nothing(session, how):
    frag, splits, _ = _fragment(session, Q1)
    inj = FailureInjector()
    tm = TaskManager(session.catalog, injector=inj)
    tid = f"stop-{how}"
    if how == "failed":
        inj.inject("WORKER_TASK_RUN", match_sql=f"{tid}:5")
        task = _run_task(tm, tid, frag, splits, traced=False)
        assert task.state == "FAILED" and "injected" in task.error
    elif how == "deadline":
        inj.inject("WORKER_TASK_RUN", match_sql=f"{tid}:4", fault=DELAY,
                   delay_s=0.3)
        task = tm.create_or_update(tid, encode_fragment(frag), splits,
                                   deadline=time.time() + 3600)
        # the cutoff passes while split 4 sleeps
        while task.splits_done < 3 and task.state == "RUNNING" or \
                task.state == "PENDING":
            time.sleep(0.005)
        task.deadline = time.monotonic()
        task = _run_task(tm, tid, frag, splits, traced=False)
        assert task.state == "FAILED" and "deadline" in task.error
    else:
        inj.inject("WORKER_TASK_RUN", match_sql=f"{tid}:4", fault=DELAY,
                   delay_s=0.3)
        task = tm.create_or_update(tid, encode_fragment(frag), splits)
        while task.splits_done < 3 and task.state in ("PENDING", "RUNNING"):
            time.sleep(0.005)
        tm.cancel(tid)
        task = _run_task(tm, tid, frag, splits, traced=False)
        assert task.state == "CANCELED"
    assert 3 <= task.splits_done < len(splits)
    assert task.total_pages() == 0 and task.rows_out == 0 and \
        task.bytes_out == 0
    assert tm.memory_info()["revocable"] == 0
    assert tm.memory_info()["reserved"] == 0


@pytest.mark.parametrize("mode", ["concat", "sorted-runs", "partitioned",
                                  "partitioned-aggregate", "unmarked"])
def test_other_fragments_still_stage_a_page_a_split(session, mode):
    """Byte for byte what the loop staged before: one page a split (one
    a partition a split), each the encoding of that split's output. A
    merge aggregate without the coordinator's mark is one of them: the
    worker does not guess from the node's type."""
    sql = {"concat": CONCAT, "partitioned": CONCAT, "sorted-runs": SORTED,
           "partitioned-aggregate": BY_ORDER, "unmarked": Q1}[mode]
    frag, splits, _ = _fragment(session, sql)
    if mode == "unmarked":
        del frag["merge_agg"]
    partition = {"keys": [0], "count": 3} \
        if mode.startswith("partitioned") else None
    want = {}
    for arrs, vals in _per_split(session, frag, splits):
        if partition is None:
            want.setdefault(0, []).append(encode_columns(arrs, vals))
            continue
        part = partition_assignment(arrs, vals, [0], 3)
        for p in range(3):
            m = part == p
            if m.any():
                want.setdefault(p, []).append(encode_columns(
                    [a[m] for a in arrs], [v[m] for v in vals]))
    tm = TaskManager(session.catalog)
    task = _run_task(tm, f"plain-{mode}", frag, splits, partition=partition)
    assert task.state == "FINISHED", task.error
    assert {b: list(p) for b, p in task.buffers.items() if p} == want
    (wt,) = _span(task, "worker-task")
    assert (wt["attributes"]["foldedSplits"], wt["attributes"]["flushes"],
            wt["attributes"]["pagesOut"]) == (
                0, 0, sum(len(p) for p in want.values()))
    assert not _span(task, "task-merge") and not _span(task, "task-emit")
    if partition is None:
        assert len(task.buffers[0]) == len(splits)


# ---------------------------------------------------------------------------
# through a coordinator and one worker
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cluster(session):
    coord = CoordinatorServer(session).start()
    coord.state.scheduler.split_rows = SPLIT_ROWS
    worker = WorkerServer("fold-w0", coord.uri, announce_interval_s=0.1,
                          catalog=session.catalog).start()
    deadline = time.time() + 5
    while not coord.state.active_nodes() and time.time() < deadline:
        time.sleep(0.05)
    yield coord, worker
    coord.stop()
    worker.stop()


def _protocol(row):
    from decimal import Decimal
    return tuple(str(v) if isinstance(v, Decimal) else v for v in row)


@pytest.mark.parametrize("budget", [None, 600])
@pytest.mark.parametrize("name", ["q6", "q1", "by_order", "q3"])
def test_served_statement_merges_one_page_a_task(session, cluster, name,
                                                 budget):
    coord, worker = cluster
    sql = dict(AGGREGATES, q3=Q3)[name]
    want = [_protocol(r) for r in session.execute(sql).rows]
    coord.state.scheduler.spool.clear()
    tm = worker.task_manager
    saved = tm.max_buffer_bytes
    client = Client(coord.uri, user="fold")
    client.execute("SET SESSION enable_tracing = true")
    try:
        if budget is not None:
            tm.max_buffer_bytes = budget
        res = client.execute(sql)
    finally:
        tm.max_buffer_bytes = saved
        client.execute("SET SESSION enable_tracing = false")
    assert res.state == "FINISHED"
    assert [tuple(r) for r in res.rows] == want
    assert client.query_info(res.query_id)["distributed"]
    spans = client._request(
        "GET", f"{coord.uri}/v1/query/{res.query_id}/trace")["spans"]
    final = next(s for s in spans if s["name"] == "final-stage")
    task = max((s for s in spans if s["name"] == "worker-task"),
               key=lambda s: s["startTimeUnixNano"])
    assert task["attributes"]["foldedSplits"] == \
        task["attributes"]["splits"] == 8
    flushes = task["attributes"]["flushes"]
    # the last flush may leave nothing for the task's end
    assert final["attributes"]["pages"] == task["attributes"]["pagesOut"] \
        in (flushes, flushes + 1)
    if budget is None or name == "q6":      # eight q6 partials: 152 bytes
        assert (flushes, final["attributes"]["pages"]) == (0, 1)
    else:
        assert flushes >= 1 and final["attributes"]["pages"] > 1
    assert tm.memory_info()["revocable"] == 0
