"""Memory-pressure survival chain tests (round 9).

Reference patterns: MemoryPool reserve/revoke (memory/MemoryPool.java:44,
execution/MemoryRevokingScheduler.java:47), the spilling operators' must-
be-identical-results contract, ClusterMemoryManager + the total-
reservation-dominant LowMemoryKiller, OutputBuffer byte bounds, and
resource-group soft memory limits (InternalResourceGroup).
"""

import json
import threading
import time
from urllib.request import urlopen

import pytest

from trino_tpu.exec.memory import (ExceededMemoryLimitError,
                                   MemoryAccountingError, MemoryPool,
                                   parse_bytes)
from trino_tpu.exec.session import Session

# four aggregate states, so that the join + aggregation's real working
# set at tiny (1.4 MB) exceeds the smallest query_max_memory_mb (1 MB)
JOIN_Q = """
SELECT o_custkey, count(*) AS c, sum(o_totalprice) AS s,
       min(o_orderdate) AS first_order, max(o_orderkey) AS last_key
FROM orders JOIN customer ON o_custkey = c_custkey
WHERE c_acctbal > 0
GROUP BY o_custkey
ORDER BY s DESC, o_custkey LIMIT 50
"""

AGG_Q = """
SELECT l_returnflag, l_linestatus, sum(l_quantity) AS q, count(*) AS c,
       min(l_discount) AS mn, max(l_tax) AS mx
FROM lineitem GROUP BY l_returnflag, l_linestatus
ORDER BY l_returnflag, l_linestatus
"""


@pytest.fixture(scope="module")
def baseline():
    s = Session(default_schema="tiny")
    join_rows = s.execute(JOIN_Q).rows
    agg_rows = s.execute(AGG_Q).rows
    peak = s.executor.pool.peak
    return {"join": join_rows, "agg": agg_rows, "peak": peak}


# -- pool semantics ---------------------------------------------------------

def test_pool_revocable_reservations_and_callbacks():
    pool = MemoryPool(1000, strict=True)
    freed = []

    def spill(target):
        take = min(target, 600)
        pool.free_revocable(take, tag="cache")
        freed.append(take)
        return take

    pool.register_revocation(spill, tag="cache")
    pool.reserve_revocable(600, tag="cache")
    pool.reserve(300)
    # 600 revocable + 300 user: the next 300-byte reserve is 200 over
    # the limit and must trigger revocation (spill) instead of failing
    pool.reserve(300)
    assert freed == [200]
    assert pool.reserved == 600
    assert pool.revocable == 400
    pool.free(600)
    pool.free_revocable(400, tag="cache")
    pool.close()


def test_pool_limit_raises_without_revocable():
    pool = MemoryPool(100, strict=True)
    pool.reserve(80)
    with pytest.raises(ExceededMemoryLimitError):
        pool.reserve(30)
    assert pool.reserved == 80        # failed reserve takes nothing
    pool.free(80)
    pool.close()


def test_pool_double_free_detected_strict():
    pool = MemoryPool(1000, strict=True)
    pool.reserve(100)
    with pytest.raises(MemoryAccountingError):
        pool.free(200)


def test_pool_close_detects_leak():
    pool = MemoryPool(1000, strict=True)
    pool.reserve(64, tag="q1")
    with pytest.raises(MemoryAccountingError):
        pool.close()
    # non-strict: counted, ledger zeroed
    pool2 = MemoryPool(1000, strict=False)
    pool2.reserve(64, tag="q1")
    pool2.close()
    assert pool2.accounting_errors == 1
    assert pool2.reserved == 0


def test_pool_accounting_error_metric_nonstrict():
    from trino_tpu.metrics import MEMORY_ACCOUNTING_ERRORS
    before = MEMORY_ACCOUNTING_ERRORS.value()
    pool = MemoryPool(1000, strict=False)
    pool.reserve(10)
    pool.free(50)                     # clamped + counted, no raise
    assert pool.reserved == 0
    assert MEMORY_ACCOUNTING_ERRORS.value() == before + 1


def test_pool_holder_ledger_attribution():
    pool = MemoryPool(1 << 20, strict=True)
    pool.reserve(100, tag="q1")
    pool.reserve(300, tag="q2")
    assert pool.query_bytes("q2") == 300
    snap = pool.snapshot()
    assert snap["holders"] == {"q1": 100, "q2": 300}
    pool.free(100, tag="q1")
    pool.free(300, tag="q2")
    pool.close()


def test_parse_bytes():
    assert parse_bytes("1024") == 1024
    assert parse_bytes("2GB") == 2 << 30
    assert parse_bytes("512MB") == 512 << 20
    assert parse_bytes("64kB") == 64 << 10


# -- spill-vs-resident bit-exactness ---------------------------------------

@pytest.mark.parametrize("frac", [2, 4])
def test_spill_join_agg_bitexact_at_pool_fractions(baseline, frac):
    """The acceptance shape: a query whose working set exceeds its pool
    spills and returns results identical to the resident run — at 50%
    and 25% of the measured working set."""
    s = Session(default_schema="tiny")
    limit = max(1, baseline["peak"] // frac)
    s.executor.pool.set_limit(limit)
    s.properties["query_max_memory_mb"] = max(1, limit >> 20)
    got = s.execute(JOIN_Q).rows
    assert got == baseline["join"]
    got2 = s.execute(AGG_Q).rows
    assert got2 == baseline["agg"]
    st = s.executor.stats
    if frac >= 4:
        assert st.spilled_joins + st.spilled_aggregations >= 1


def test_spill_disabled_fails_cleanly(baseline):
    s = Session(default_schema="tiny")
    s.execute("SET SESSION spill_enabled = false")
    s.execute("SET SESSION query_max_memory_mb = 1")
    with pytest.raises(ExceededMemoryLimitError):
        s.execute(JOIN_Q)
    # raising the limit restores service on the same session
    s.execute("SET SESSION query_max_memory_mb = 4096")
    assert s.execute("SELECT count(*) FROM nation").rows[0][0] == 25


def test_chunked_partial_state_spills_under_pressure():
    """The chunked driver's partial-aggregation state is revocable:
    under a small pool the revocation callback moves partials to host
    and the merge re-aggregates partition-wise — results identical."""
    q = ("SELECT l_orderkey, sum(l_quantity) AS q FROM lineitem "
         "GROUP BY l_orderkey ORDER BY q DESC, l_orderkey LIMIT 20")
    s = Session(default_schema="tiny")
    want = s.execute(q).rows
    s2 = Session(default_schema="tiny")
    s2.execute("SET SESSION spill_chunk_rows = 8192")
    s2.execute("SET SESSION query_max_memory_mb = 2")
    got = s2.execute(q).rows
    assert got == want


def test_spill_chaos_spool_write_fault_no_wrong_answer(baseline):
    """Chaos interaction: SPOOL_WRITE faults (clean raise AND payload
    corruption) during spill degrade to the RAM copy — the query
    retries nothing, loses nothing, and returns exact results."""
    from trino_tpu.exec.spill import get_spiller
    from trino_tpu.server.failureinjector import FailureInjector
    s = Session(default_schema="tiny")
    s.executor.spill_force_disk = True
    s.executor.pool.set_limit(max(1, baseline["peak"] // 4))
    s.properties["query_max_memory_mb"] = max(
        1, (baseline["peak"] // 4) >> 20)
    spiller = get_spiller(s.executor)
    inj = FailureInjector()
    inj.inject("SPOOL_WRITE", times=2, fault="RAISE")
    inj.inject("SPOOL_WRITE", times=2, fault="CORRUPT")
    spiller.injector = inj
    got = s.execute(JOIN_Q).rows
    assert got == baseline["join"]
    assert inj.injected_count >= 1
    assert spiller.write_recoveries >= 1


# -- cluster arbitration: the low-memory killer -----------------------------

def test_oom_killer_picks_dominant_query_others_complete():
    from trino_tpu.server.coordinator import CoordinatorState
    from trino_tpu.server.memorymanager import ClusterMemoryManager
    from trino_tpu.server.statemachine import (QueryStateMachine,
                                               TrackedQuery)
    state = CoordinatorState(Session(default_schema="tiny"))
    mm = ClusterMemoryManager(state, cluster_limit_bytes=1000,
                              kill_after_ticks=1)
    big = TrackedQuery("q-big", "SELECT 1", "u", QueryStateMachine("q-big"))
    small = TrackedQuery("q-small", "SELECT 2", "u",
                         QueryStateMachine("q-small"))
    state.tracker.register(big)
    state.tracker.register(small)
    big.state_machine.transition("RUNNING")
    small.state_machine.transition("RUNNING")
    pool = state.session.executor.pool
    pool.reserve(900, tag="q-big")
    pool.reserve(200, tag="q-small")
    try:
        mm.tick()
        assert big.state == "FAILED"
        assert big.state_machine.error_name == "QUERY_EXCEEDED_MEMORY"
        assert "low-memory killer" in big.state_machine.error
        assert small.state == "RUNNING"       # others complete
        assert mm.queries_killed == 1
    finally:
        pool.free(900, tag="q-big")
        pool.free(200, tag="q-small")


def test_memory_manager_revokes_before_killing():
    from trino_tpu.server.coordinator import CoordinatorState
    from trino_tpu.server.memorymanager import ClusterMemoryManager
    state = CoordinatorState(Session(default_schema="tiny"))
    mm = ClusterMemoryManager(state, cluster_limit_bytes=1000,
                              kill_after_ticks=1)
    pool = state.session.executor.pool

    def spill(target):
        take = min(target, pool.holder_revocable.get("partials", 0))
        pool.free_revocable(take, tag="partials")
        return take

    h = pool.register_revocation(spill, tag="partials")
    pool.reserve_revocable(800, tag="partials")
    pool.reserve(400, tag="q1")
    try:
        mm.tick()                 # 1200 > 1000: revocation covers it
        assert pool.revocable <= 600
        assert mm.queries_killed == 0
    finally:
        pool.free(400, tag="q1")
        spill(1 << 62)
        pool.unregister_revocation(h)


# -- exchange backpressure --------------------------------------------------

def test_backpressure_bounds_producer_buffer_bytes():
    from trino_tpu.catalog import default_catalog
    from trino_tpu.server.tasks import TaskManager, WorkerTask
    tm = TaskManager(default_catalog())
    tm.max_buffer_bytes = 20_000
    task = WorkerTask("bp1", "", [])
    task.state = "RUNNING"
    page = b"x" * 6000
    peaks = []

    def producer():
        for _ in range(12):
            tm._stage_page(task, 0, page, 1)

    t = threading.Thread(target=producer, daemon=True)
    t.start()
    drained = 0
    deadline = time.monotonic() + 30
    while drained < 12 and time.monotonic() < deadline:
        with task.cond:
            peaks.append(task.buffered_bytes)
            if task.buffers.get(0):
                drained += 1
                task.buffered_bytes -= len(task.buffers[0].pop(0))
                task.cond.notify_all()
        time.sleep(0.01)          # slow consumer
    t.join(timeout=10)
    assert drained == 12
    assert max(peaks) <= tm.max_buffer_bytes
    assert task.backpressure_waits >= 1
    assert task.rows_out == 12


def test_backpressure_releases_on_cancel():
    from trino_tpu.catalog import default_catalog
    from trino_tpu.server.tasks import TaskManager, WorkerTask
    tm = TaskManager(default_catalog())
    tm.max_buffer_bytes = 1_000
    task = WorkerTask("bp2", "", [])
    task.state = "RUNNING"
    tm.tasks["bp2"] = task
    done = threading.Event()

    def producer():
        tm._stage_page(task, 0, b"a" * 900, 1)
        tm._stage_page(task, 0, b"b" * 900, 1)   # blocks until cancel
        done.set()

    threading.Thread(target=producer, daemon=True).start()
    time.sleep(0.2)
    assert not done.is_set()          # producer paused on a full buffer
    tm.cancel("bp2")
    assert done.wait(5)               # cancel wakes it


# -- memory-aware admission (resource groups) -------------------------------

def test_soft_memory_limit_keeps_queries_queued():
    from trino_tpu.server.resourcegroups import (ResourceGroupConfig,
                                                 ResourceGroupManager)
    rgm = ResourceGroupManager(ResourceGroupConfig(
        "root", hard_concurrency_limit=4,
        soft_memory_limit_bytes=1000))
    ran = []
    rgm.set_cluster_memory(5000)          # over the soft limit
    rgm.submit("u", lambda: ran.append("a"))
    assert ran == []                      # queued, not rejected
    info = rgm.info()[0]
    assert info["queued"] == 1
    assert info["memoryUsageBytes"] == 5000
    assert info["softMemoryLimitBytes"] == 1000
    # memory drops: the tick admits the queued query and records its wait
    time.sleep(0.02)
    runnable = rgm.set_cluster_memory(100)
    for r in runnable:
        r()
    assert ran == ["a"]
    info = rgm.info()[0]
    assert info["queued"] == 0
    assert info["totalQueueWaitSeconds"] > 0
    assert info["avgQueueWaitSeconds"] > 0


def test_queue_wait_recorded_on_finished():
    from trino_tpu.server.resourcegroups import (ResourceGroupConfig,
                                                 ResourceGroupManager)
    rgm = ResourceGroupManager(ResourceGroupConfig(
        "root", hard_concurrency_limit=1, max_queued=5))
    ran = []
    rgm.submit("u", lambda: ran.append("first"))
    rgm.submit("u", lambda: ran.append("second"))
    time.sleep(0.02)
    nxt = rgm.finished("root")
    assert nxt is not None
    nxt()
    assert ran == ["first", "second"]
    info = rgm.info()[0]
    assert info["totalQueueWaitSeconds"] >= 0.01
    assert info["totalAdmitted"] == 2


# -- HTTP surfaces ----------------------------------------------------------

def test_query_exceeded_memory_surfaces_to_client():
    from trino_tpu.client.client import Client, QueryError
    from trino_tpu.server.coordinator import CoordinatorServer
    session = Session(default_schema="tiny")
    session.properties["spill_enabled"] = False
    session.properties["query_max_memory_mb"] = 1
    coord = CoordinatorServer(session).start()
    try:
        client = Client(coord.uri, user="oom")
        with pytest.raises(QueryError) as ei:
            client.execute(
                "SELECT sum(l_quantity), sum(l_extendedprice), "
                "sum(l_discount), sum(l_tax) FROM lineitem")
        assert ei.value.error_name == "QUERY_EXCEEDED_MEMORY"
        # the killer error is a USER error: no dispatch retry burned
        session.properties["query_max_memory_mb"] = 4096
        r = client.execute("SELECT count(*) FROM region")
        assert r.rows[0][0] == 5
    finally:
        coord.stop()


def test_memory_endpoint_and_system_table():
    from trino_tpu.client.client import Client
    from trino_tpu.server.coordinator import CoordinatorServer
    coord = CoordinatorServer(Session(default_schema="tiny")).start()
    try:
        client = Client(coord.uri, user="mem")
        client.execute("SELECT 1")
        with urlopen(f"{coord.uri}/v1/memory", timeout=5) as r:
            snap = json.loads(r.read())
        assert "reserved" in snap and "revocable" in snap
        assert "coordinator" in snap["nodes"]
        rows = client.execute(
            "SELECT group_name, running, total_queue_wait_seconds "
            "FROM system.runtime.resource_groups").rows
        assert rows and rows[0][0] == "root"
    finally:
        coord.stop()


def test_worker_status_reports_memory():
    from trino_tpu.server.worker import WorkerServer
    w = WorkerServer("mem-w0", "http://127.0.0.1:1",
                     announce_interval_s=30).start()
    try:
        with urlopen(f"{w.uri}/v1/status", timeout=5) as r:
            body = json.loads(r.read())
        assert body["memory"]["pool"] == "general"
        assert "reserved" in body["memory"]
        assert "outputBufferBytes" in body["memory"]
    finally:
        w.stop()
