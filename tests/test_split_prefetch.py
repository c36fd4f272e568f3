"""A worker task stages its splits' inputs ahead of the split loop
(server/tasks.py `_split_decoder` + exec/prefetch.py `PrefetchPipeline`,
the chunked driver's pipeline with a second caller).

The contracts: depth 0 and depth 2 stage the same pages, bit for bit;
the loop keeps its five laps, its checks and its chaos point in split
order; nothing staged outlives a task, however it ends; a fault in the
feeder is the task's failure, and the scheduler's retry answers right.
Tasks run on a bare `TaskManager` as in test_task_fold.py, whose
fragments and helpers these tests share.
"""

import sys
import threading
import time

import pytest

from trino_tpu.client.client import Client
from trino_tpu.server.coordinator import CoordinatorServer
from trino_tpu.metrics import SCAN_PREFETCH_BUFFERS
from trino_tpu.server.failureinjector import (DELAY, RAISE, SCAN_PREFETCH,
                                              FailureInjector)
from trino_tpu.server.tasks import (Split, TaskManager, decode_columns,
                                    encode_fragment)
from trino_tpu.server.worker import WorkerServer

from test_task_fold import (BY_ORDER, CONCAT, Q1, Q6, SPLIT_ROWS, _fragment,
                            _protocol, _rows, _run_task, _span,
                            session)  # noqa: F401
from test_tracing_phases import ROUNDING_NS, SPLIT_PHASES
from test_tracing_phases import _interval as interval

SHAPES = {"folded": (Q1, None), "concat": (CONCAT, None),
          "partitioned": (BY_ORDER, {"keys": [0], "count": 3})}


def _manager(session, depth, injector=None):
    tm = TaskManager(session.catalog, injector=injector)
    tm._executor.prefetch_depth = depth
    return tm


def _decoding_threads(monkeypatch):
    """Names of the threads that ran each split's decode, in call
    order."""
    ran = []
    make = TaskManager._split_decoder

    def spied(self, task, driver_scan, cap):
        decode = make(self, task, driver_scan, cap)

        def counted(si):
            ran.append(threading.current_thread().name)
            return decode(si)
        return counted
    monkeypatch.setattr(TaskManager, "_split_decoder", spied)
    return ran


def _nothing_staged(tm):
    info = tm.memory_info()
    return (info["reserved"], info["revocable"],
            SCAN_PREFETCH_BUFFERS.value(),
            [t.name for t in threading.enumerate()
             if t.name == "scan-prefetch"]) == (0, 0, 0, [])


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_depth_two_and_depth_zero_stage_the_same_pages(session, shape,
                                                       monkeypatch):
    sql, partition = SHAPES[shape]
    frag, splits, _ = _fragment(session, sql)
    assert len(splits) == 8
    ran = _decoding_threads(monkeypatch)
    pages, tasks = {}, {}
    for depth in (0, 2):
        tm = _manager(session, depth)
        del ran[:]
        task = _run_task(tm, f"stage-{shape}-{depth}", frag, splits,
                         partition=partition)
        assert task.state == "FINISHED", task.error
        pages[depth] = {b: list(p) for b, p in task.buffers.items() if p}
        tasks[depth] = task
        (wt,) = _span(task, "worker-task")
        attrs = wt["attributes"]
        inline = sum(name == f"task-{task.task_id}" for name in ran)
        assert attrs["prefetchedSplits"] + inline == len(splits) == len(ran)
        assert attrs["prefetchedSplits"] == (len(splits) if depth else 0)
        assert 0 <= attrs["prefetchStalls"] <= attrs["prefetchedSplits"]
        assert attrs["stageMs"] > 0
        puts = sorted(_span(task, "split-put"),
                      key=lambda s: s["attributes"]["index"])
        assert [s["attributes"]["bytes"] for s in puts] == \
            [s.count * puts[0]["attributes"]["bytes"] // splits[0].count
             for s in splits]
        assert puts[0]["attributes"]["bytes"] > 0
        assert sum(s["attributes"]["ahead"] for s in puts) == \
            attrs["prefetchedSplits"] - attrs["prefetchStalls"]
        assert _nothing_staged(tm)
    assert pages[0] and pages[2] == pages[0]
    assert tasks[2].rows_out == tasks[0].rows_out


def test_the_five_laps_of_a_split_still_touch(session):
    frag, splits, _ = _fragment(session, Q6)
    task = _run_task(_manager(session, 2), "laps", frag, splits)
    assert task.state == "FINISHED", task.error
    (wt,) = _span(task, "worker-task")
    laps = sorted((s for s in task.spans if s["name"] in SPLIT_PHASES),
                  key=lambda s: s["startTimeUnixNano"])
    assert [s["name"] for s in laps] == list(SPLIT_PHASES) * len(splits)
    assert [s["attributes"]["index"] for s in laps[::5]] == \
        list(range(len(splits)))
    for a, b in zip(laps, laps[1:]):
        assert a["parentSpanId"] == b["parentSpanId"] == wt["spanId"]
        assert abs(interval(a)[1] - b["startTimeUnixNano"]) <= ROUNDING_NS
    # the feeder's thread carries no tracer: no span of it anywhere
    assert {s["name"] for s in task.spans} <= set(SPLIT_PHASES) | {
        "task-lock-wait", "task-decode", "worker-task", "pin-builds",
        "compile", "task-merge", "task-emit", "filter-project",
        "aggregate", "split-spans"}


class _Keys:
    """An injector that fires nothing and keeps every site key."""

    def __init__(self):
        self.keys = []

    def maybe_fail(self, point, key=""):
        self.keys.append((point, key))


def test_the_loops_chaos_point_fires_in_split_order(session):
    frag, splits, _ = _fragment(session, Q1)
    seen = _Keys()
    tm = _manager(session, 2, injector=seen)
    task = _run_task(tm, "order", frag, splits, traced=False)
    assert task.state == "FINISHED", task.error
    loop = [k for p, k in seen.keys if p == "WORKER_TASK_RUN"]
    assert loop == ["order"] + [f"order:{i}" for i in range(len(splits))]
    feeder = [k for p, k in seen.keys if p == SCAN_PREFETCH]
    assert feeder == [f"chunk@{i}" for i in range(len(splits))]
    # a fault at split 5 finds five splits done, whatever was staged
    inj = FailureInjector()
    inj.inject("WORKER_TASK_RUN", match_sql="stop:5")
    tm = _manager(session, 2, injector=inj)
    task = _run_task(tm, "stop", frag, splits, traced=False)
    assert task.state == "FAILED" and "injected" in task.error
    assert task.splits_done == 5 and task.total_pages() == 0
    assert _nothing_staged(tm)


@pytest.mark.parametrize("how", ["finished", "feeder-fault",
                                 "connector-error", "cancelled"])
def test_nothing_staged_outlives_a_task(session, how):
    frag, splits, _ = _fragment(session, Q1)
    inj = FailureInjector()
    tm = _manager(session, 2, injector=inj)
    tid = f"end-{how}"
    if how == "feeder-fault":
        inj.inject(SCAN_PREFETCH, match_sql="chunk@4", fault=RAISE)
    elif how == "connector-error":
        d = splits[4]
        splits = splits[:4] + [Split(d.catalog, d.schema_name, "nosuch",
                                     d.start, d.count)] + splits[5:]
    if how == "cancelled":
        inj.inject("WORKER_TASK_RUN", match_sql=f"{tid}:4", fault=DELAY,
                   delay_s=0.3)
        task = tm.create_or_update(tid, encode_fragment(frag), splits)
        while task.splits_done < 3 and task.state in ("PENDING", "RUNNING"):
            time.sleep(0.005)
        tm.cancel(tid)
    task = _run_task(tm, tid, frag, splits, traced=False)
    if how == "finished":
        assert task.state == "FINISHED", task.error
    elif how == "cancelled":
        assert task.state == "CANCELED"
    else:
        # the feeder's fault surfaces from the loop's wait for split 4
        assert task.state == "FAILED"
        assert ("injected" if how == "feeder-fault" else "nosuch") \
            in task.error
        assert task.splits_done == 4
    if how != "finished":
        assert task.total_pages() == 0 and task.rows_out == 0
    assert _nothing_staged(tm)
    # and the manager's next task runs as if nothing had happened
    frag, splits, _ = _fragment(session, Q1)
    again = _run_task(tm, tid + "-again", frag, splits, traced=False)
    assert again.state == "FINISHED", again.error
    want = _run_task(_manager(session, 0), tid + "-serial", frag, splits,
                     traced=False)
    assert again.buffers == want.buffers
    assert _nothing_staged(tm)


def test_revoked_batches_are_decoded_on_the_loops_thread(session,
                                                         monkeypatch):
    """Under pressure and a hostile scheduler: the pool revokes what is
    staged as fast as it can while the task runs, so decodes run on both
    threads at once; the page is the serial loop's all the same."""
    frag, splits, _ = _fragment(session, Q1)
    want = _run_task(_manager(session, 0), "revoke-serial", frag, splits)
    ran = _decoding_threads(monkeypatch)
    inj = FailureInjector()
    # hold the loop while the feeder fills its two slots
    inj.inject("WORKER_TASK_RUN", match_sql="revoke:1", fault=DELAY,
               delay_s=0.3)
    tm = _manager(session, 2, injector=inj)
    stop = threading.Event()
    freed = []

    def pressure():
        while not stop.is_set():
            freed.append(tm._executor.pool.request_revocation(1 << 40))
            time.sleep(0.001)

    presser = threading.Thread(target=pressure)
    interval0 = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        presser.start()
        task = _run_task(tm, "revoke", frag, splits, wait_s=60.0)
    finally:
        sys.setswitchinterval(interval0)
        stop.set()
        presser.join(timeout=10)
    assert not presser.is_alive()
    assert task.state == "FINISHED", task.error
    # the partials the task holds are revocable too, and a fold of
    # spilled partials may order its groups otherwise: the same rows
    (page,), (serial,) = task.buffers[0], want.buffers[0]
    assert _rows(*decode_columns(page)) == _rows(*decode_columns(serial))
    assert sum(freed) > 0
    (wt,) = _span(task, "worker-task")
    inline = sum(name == "task-revoke" for name in ran)
    assert inline >= 1
    # a revoked split was decoded twice: once to be staged, once inline
    assert wt["attributes"]["prefetchedSplits"] + inline == len(splits)
    assert len(ran) == len(splits) + inline
    assert _nothing_staged(tm)


@pytest.fixture
def two_workers(session):
    coord = CoordinatorServer(session).start()
    coord.state.scheduler.split_rows = SPLIT_ROWS
    workers = [WorkerServer(f"prefetch-w{i}", coord.uri,
                            announce_interval_s=0.1,
                            catalog=session.catalog).start()
               for i in range(2)]
    deadline = time.time() + 5
    while len(coord.state.active_nodes()) < 2 and time.time() < deadline:
        time.sleep(0.05)
    yield coord, workers
    for w in workers:
        w.stop()
    coord.stop()


def test_a_feeder_fault_is_retried_by_the_scheduler(session, two_workers):
    """One fault in one feeder, whichever worker's: its task fails, the
    scheduler runs the task's splits again on the other worker, and the
    statement's answer is the undisturbed one."""
    coord, workers = two_workers
    want = [_protocol(r) for r in session.execute(Q1).rows]
    sched = coord.state.scheduler
    inj = FailureInjector()
    inj.inject(SCAN_PREFETCH, match_sql="chunk@2", fault=RAISE)
    for w in workers:
        w.task_manager._executor.failure_injector = inj
    client = Client(coord.uri, user="prefetch")
    res = client.execute(Q1)
    assert res.state == "FINISHED"
    assert [tuple(r) for r in res.rows] == want
    assert inj.injected_by_fault[RAISE] == 1
    info = client.query_info(res.query_id)
    assert info["distributed"] and not info.get("fallbackReason")
    assert sched.stats["task_retries"] == 1
    assert all(_nothing_staged(w.task_manager) for w in workers)
