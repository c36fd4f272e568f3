"""Three query streams on a coordinator and a worker (TPC-H's throughput
test, clause 5.3): the device lock serves waiters in arrival order, each
statement's answer, route and stage rollup are its own under
concurrency, and a traced statement says how many were ahead of it, how
long it waited for the lock and how long it held it, and how long its
task waited for the worker's lock.
"""

import sys
import threading
import time

import pytest

from trino_tpu.client.client import Client
from trino_tpu.exec.session import Session
from trino_tpu.server.coordinator import ArrivalOrderLock, CoordinatorServer
from trino_tpu.server.worker import WorkerServer

from test_resident_tables import bench_module, reference_tables
from test_tracing_phases import ROUNDING_NS
from test_tracing_phases import _inside as inside
from test_tracing_phases import _interval as interval

q6 = bench_module("queries.q6")
q1 = bench_module("queries.q1")
compare = bench_module("compare")

STREAMS = 3
JOIN_S = 60.0


def wait_until(cond, what, timeout_s=10.0):
    deadline = time.monotonic() + timeout_s
    while not cond():
        assert time.monotonic() < deadline, what
        time.sleep(0.001)


# ---------------------------------------------------------------------------
# the lock
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("reentrant", [False, True],
                         ids=["held-once", "held-twice"])
@pytest.mark.parametrize("waiters", [3, 8])
def test_lock_serves_waiters_in_arrival_order(waiters, reentrant):
    lock = ArrivalOrderLock()
    assert lock.acquire() == 0
    if reentrant:
        assert lock.acquire() == 0      # the holder never waits for itself
    got, ahead = [], {}

    def ask(i):
        ahead[i] = lock.acquire()
        got.append(i)
        # hold it long enough for a thread that asked later to be awake
        # and ready to run: an unordered lock would let it in
        time.sleep(0.002)
        lock.release()

    threads = [threading.Thread(target=ask, args=(i,)) for i in
               range(waiters)]
    for i, th in enumerate(threads):
        th.start()
        wait_until(lambda: len(lock._waiters) == i + 1,
                   f"waiter {i} never queued")
    if reentrant:
        lock.release()
        time.sleep(0.01)
        assert got == []                # one release of two hands nothing on
    lock.release()
    for th in threads:
        th.join(JOIN_S)
        assert not th.is_alive()
    assert got == list(range(waiters))
    # the holder and the waiters before it
    assert ahead == {i: i + 1 for i in range(waiters)}
    assert lock.acquire() == 0          # free again
    lock.release()


def test_lock_release_by_another_thread_is_refused():
    lock = ArrivalOrderLock()
    with lock:
        errors = []

        def steal():
            try:
                lock.release()
            except RuntimeError as e:
                errors.append(e)

        th = threading.Thread(target=steal)
        th.start()
        th.join(JOIN_S)
        assert len(errors) == 1


def test_lock_under_contention_loses_no_update_and_no_waiter():
    """More threads than cores, a short switch interval: the count under
    the lock is exact (mutual exclusion), every thread gets through (no
    hand-over is lost), and nobody waits behind more than the others."""
    lock, threads_n, rounds = ArrivalOrderLock(), 32, 200
    state = {"n": 0, "inside": 0, "worst_ahead": 0}

    def work():
        for _ in range(rounds):
            ahead = lock.acquire()
            with lock:                  # re-entrant under contention
                state["inside"] += 1
                assert state["inside"] == 1
                n = state["n"]
                state["worst_ahead"] = max(state["worst_ahead"], ahead)
                state["n"] = n + 1
                state["inside"] -= 1
            lock.release()

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work) for _ in range(threads_n)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(JOIN_S)
            assert not th.is_alive()
    finally:
        sys.setswitchinterval(old)
    assert state["n"] == threads_n * rounds
    assert 1 <= state["worst_ahead"] <= threads_n - 1
    assert lock._owner is None and not lock._waiters


# ---------------------------------------------------------------------------
# three clients, a coordinator and a worker
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cluster():
    session = Session()
    coord = CoordinatorServer(session).start()
    # tiny's lineitem (60,104 rows) in eight splits
    coord.state.scheduler.split_rows = 8192
    worker = WorkerServer("streams-w0", coord.uri, announce_interval_s=0.1,
                          catalog=session.catalog).start()
    wait_until(coord.state.active_nodes, "the worker never announced")
    yield coord, worker, session
    worker.stop()
    coord.stop()


@pytest.fixture(scope="module")
def streams(cluster):
    """Two rounds of q6 and q1 from each of three clients at once, the
    second round traced: every statement with parameters no other has,
    clients one template apart. -> the statements, each with its rows,
    `/v1/query/{id}` and spans."""
    coord, _, session = cluster
    templates = (q6, q1)
    clients = [Client(coord.uri, user=f"stream-{c}") for c in range(STREAMS)]
    # compile both templates' programs once, so that the streams below
    # meet at the lock and not behind one compile
    for t in templates:
        clients[0].execute(t.render(t.VALIDATION, "tpch.tiny"))
    pools = {t.NAME: [p for p in t.domain() if p != t.VALIDATION]
             for t in templates}
    statements, lock = [], threading.Lock()

    def stream(c, traced):
        client = clients[c]
        for turn in range(len(templates)):
            t = templates[(c + turn) % len(templates)]
            # disjoint slices of each domain, a new set every round
            params = pools[t.NAME][c + STREAMS * int(traced)]
            res = client.execute(t.render(params, "tpch.tiny"))
            rec = {"client": c, "template": t, "params": params,
                   "traced": traced, "rows": res.rows,
                   "query_id": res.query_id,
                   "info": client.query_info(res.query_id),
                   "spans": client._request(
                       "GET", f"{coord.uri}/v1/query/{res.query_id}/trace"
                   )["spans"]}
            with lock:
                statements.append(rec)

    for traced in (False, True):
        if traced:
            clients[0].execute("SET SESSION enable_tracing = true")
        try:
            threads = [threading.Thread(target=stream, args=(c, traced))
                       for c in range(STREAMS)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(JOIN_S)
                assert not th.is_alive()
        finally:
            if traced:
                clients[0].execute("SET SESSION enable_tracing = false")
    assert len(statements) == 2 * STREAMS * len(templates)
    return statements


@pytest.fixture(scope="module")
def tiny_tables(cluster):
    return reference_tables(cluster[2], [q6, q1])


@pytest.mark.parametrize("client", range(STREAMS))
def test_every_answer_is_the_references(streams, tiny_tables, client):
    mine = [s for s in streams if s["client"] == client]
    assert [s["template"].NAME for s in mine if not s["traced"]] == \
        [("q6", "q1")[(client + turn) % 2] for turn in range(2)]
    for s in mine:
        t = s["template"]
        want = t.reference(tiny_tables, s["params"])
        assert compare.mismatched_cells(s["rows"], want, t.COLUMNS) == \
            (0, None), (t.NAME, s["params"])


def test_no_text_is_sent_twice(streams):
    texts = {s["template"].render(s["params"], "tpch.tiny")
             for s in streams}
    assert len(texts) == len(streams)


def test_every_statement_ran_as_worker_tasks(streams):
    for s in streams:
        assert s["info"]["distributed"] is True, s["info"]
        assert s["info"]["fallbackReason"] is None
        assert s["info"]["stageStats"]["tasks"] >= 1


def test_a_statements_stage_rollup_is_its_own(cluster, streams):
    """The scheduler keeps one statement's rollup on itself; the
    dispatcher takes it while it still holds the lock, so another
    stream's `execute` cannot have replaced it."""
    coord = cluster[0]
    rollups = {}
    for s in streams:
        tq = coord.state.tracker.get(s["query_id"])
        st = tq.stage_stats
        assert st["query_id"] == s["query_id"]
        assert st["tasks"] and {t["query_id"] for t in st["tasks"]} == \
            {s["query_id"]}
        assert s["info"]["stageStats"]["tasks"] == len(st["tasks"])
        rollups[id(st)] = s["query_id"]
    assert len(rollups) == len(streams)      # no two share one rollup


# ---------------------------------------------------------------------------
# the spans of a statement that waited
# ---------------------------------------------------------------------------

def named(spans, name):
    return [sp for sp in spans if sp["name"] == name]


def test_untraced_statements_have_no_spans(streams):
    assert all(s["spans"] == [] for s in streams if not s["traced"])


# One clock pair a process (utils/tracing.py): spans of the coordinator's
# and the worker's tracers, on any thread, lie on the exported clock as
# they lay on the monotonic one. `inside` allows what `durationMs` is
# rounded by (ROUNDING_NS) and nothing for a clock.


def test_traced_statement_has_wait_ahead_held_and_task_lock_wait(streams):
    traced = [s for s in streams if s["traced"]]
    assert len(traced) == STREAMS * 2
    for s in traced:
        spans = s["spans"]
        ids = {sp["spanId"]: sp for sp in spans}
        query, = named(spans, "query")
        wait, = named(spans, "exec-lock-wait")
        held, = named(spans, "exec-lock-held")
        assert wait["parentSpanId"] == held["parentSpanId"] == \
            query["spanId"]
        assert 0 <= wait["attributes"]["ahead"] <= STREAMS - 1
        # served once the wait is over, and inside the statement
        assert interval(held)[0] >= interval(wait)[1] - ROUNDING_NS
        assert inside(wait, query) and inside(held, query)
        # nothing hangs under the held span: what runs under the lock is
        # still the query's
        assert not [sp for sp in spans
                    if sp["parentSpanId"] == held["spanId"]]
        for name in ("plan-distributed", "source-stage", "final-stage"):
            for sp in named(spans, name):
                assert sp["parentSpanId"] == query["spanId"]
                assert inside(sp, held), (sp, held)
        tasks = named(spans, "worker-task")
        waits = named(spans, "task-lock-wait")
        assert tasks and len(waits) == len(tasks)
        for tw, task in zip(sorted(waits, key=interval),
                            sorted(tasks, key=interval)):
            stage = ids[tw["parentSpanId"]]
            assert stage["name"] == "source-stage"
            assert tw["parentSpanId"] == task["parentSpanId"]
            # the worker's tracer, the coordinator's clock
            assert inside(tw, stage) and inside(task, stage)
            assert interval(tw)[1] <= interval(task)[0] + ROUNDING_NS


def test_traced_streams_held_the_lock_one_at_a_time_in_arrival_order(
        streams):
    traced = [s for s in streams if s["traced"]]

    def hold(s):
        return interval(named(s["spans"], "exec-lock-held")[0])

    # one at a time: a hold's end is read before the release and the
    # next one's start after the acquire
    served = sorted(traced, key=hold)
    for a, b in zip(served, served[1:]):
        assert hold(b)[0] >= hold(a)[1] - ROUNDING_NS, (hold(a), hold(b))
    # in arrival order: the `ahead` statements a statement found at the
    # lock are the ones served just before it, and all of them but the
    # holder were waiting themselves, so they got the lock after this
    # statement had begun to wait
    for i, s in enumerate(served):
        wait, = named(s["spans"], "exec-lock-wait")
        ahead = wait["attributes"]["ahead"]
        assert ahead <= i, (ahead, i)
        for queued in served[i - ahead + 1:i] if ahead else ():
            assert hold(queued)[0] >= interval(wait)[0], (wait, queued)
        # and it waited for every one of them
        if ahead:
            assert interval(wait)[1] >= \
                hold(served[i - 1])[1] - ROUNDING_NS
    # somebody did wait: three streams met at the lock
    assert max(named(s["spans"], "exec-lock-wait")[0]["attributes"]["ahead"]
               for s in traced) >= 1
