"""Every worker of a process computes on a local device of its own
(server/worker.py `_next_local_device`, server/tasks.py
`TaskManager.device`, exec/executor.py `Executor.device`), on the 8
virtual CPU devices of tests/conftest.py.

The contracts: a coordinator and four workers answer TPC-H q3, q1, q6
and q18 exactly as one executor and as the plain numpy references of
benchmark/queries/ do; the four `worker-task` spans of a stage name four
devices; what a task puts, pins and holds is on its worker's device; the
first worker of a process is on device 0, a one-device process puts
every worker there, a fifth wraps round; the compile recorder tells a
shape's first compile on a second device from a new literal; a stage's
splits are dealt in one order whatever the heartbeats' timing; the stage
spans count their hedges.
"""

import itertools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu.client.client import Client
from trino_tpu.exec.executor import Executor
from trino_tpu.exec.profiler import (CompileRecorder, device_label,
                                     device_memory_stats, instrument)
from trino_tpu.exec.session import Session
from trino_tpu.server import scheduler as scheduler_module
from trino_tpu.server import worker as worker_module
from trino_tpu.server.coordinator import CoordinatorServer
from trino_tpu.server.failureinjector import DELAY, FailureInjector
from trino_tpu.server.tasks import TaskManager
from trino_tpu.server.worker import WorkerServer

from test_resident_tables import bench_module, reference_tables
from test_task_fold import Q3, _fragment, _run_task, _span

compare = bench_module("compare")
TEMPLATES = {name: bench_module(f"queries.{name}")
             for name in ("q1", "q3", "q6", "q18")}
# each template's validation set, and one more a window could draw; q18
# at `tiny` keeps no order at TPC-H's own 300-315 (test_q18_heavyagg.py)
PARAMETERS = {
    "q3": [TEMPLATES["q3"].VALIDATION, {"segment": "MACHINERY", "day": 4}],
    "q1": [TEMPLATES["q1"].VALIDATION],
    "q6": [TEMPLATES["q6"].VALIDATION],
    "q18": [{"quantity": 200}, {"quantity": 250}],
}
WORKERS = 4


class Cluster:
    """A coordinator, four workers and a traced client over HTTP, all in
    this process: tiny's lineitem in 30 splits, orders in 8."""

    def __init__(self):
        self.session = Session()
        self.coord = CoordinatorServer(self.session).start()
        self.workers = [WorkerServer(
            f"dev-w{i}", self.coord.uri, announce_interval_s=0.2,
            catalog=self.session.catalog).start() for i in range(WORKERS)]
        deadline = time.monotonic() + 30
        while len(self.coord.state.active_nodes()) < WORKERS:
            assert time.monotonic() < deadline, "a worker never announced"
            time.sleep(0.02)
        self.coord.state.scheduler.split_rows = 2048
        self.client = Client(self.coord.uri, user="devices")
        self.client.execute("SET SESSION enable_tracing = true")

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
def cluster():
    c = Cluster()
    yield c
    c.stop()


@pytest.fixture(scope="module")
def tables(cluster):
    return reference_tables(cluster.session, TEMPLATES.values())


def tasks_by_stage(spans):
    """[[worker-task attributes, ...] a stage] in the stages' order."""
    stages = sorted((s for s in spans if s["name"] == "source-stage"),
                    key=lambda s: s["startTimeUnixNano"])
    return [[t["attributes"] for t in spans if t["name"] == "worker-task"
             and t["parentSpanId"] == st["spanId"]] for st in stages]


# ---------------------------------------------------------------------------
# which device a worker takes
# ---------------------------------------------------------------------------

def test_the_cluster_s_workers_took_four_devices_in_a_row(cluster):
    local = jax.local_devices()
    assert len(local) == 8
    took = [w.task_manager.device for w in cluster.workers]
    first = local.index(took[0])
    # the count goes on from wherever the process's earlier tests left it
    assert took == [local[(first + i) % 8] for i in range(WORKERS)]
    assert all(w.task_manager._executor.device is d
               for w, d in zip(cluster.workers, took))


@pytest.mark.parametrize("devices, want", [(1, [0, 0, 0]),
                                           (4, [0, 1, 2, 3, 0, 1]),
                                           (8, [0, 1, 2, 3, 4])])
def test_the_k_th_worker_of_a_process_is_on_device_k_modulo_their_number(
        monkeypatch, devices, want):
    local = jax.local_devices()[:devices]
    monkeypatch.setattr(worker_module, "_WORKERS_STARTED", itertools.count())
    monkeypatch.setattr(jax, "local_devices", lambda: local)
    got = [worker_module._next_local_device() for _ in want]
    assert got == [local[i] for i in want]


def test_a_task_manager_that_stands_alone_is_bound_to_no_device():
    session = Session(default_schema="tiny")
    tm = TaskManager(session.catalog)
    assert tm.device is None and tm._executor.device is None
    assert session.executor.device is None
    # the null context: the thread's default device stays what it was
    with session.executor.on_device():
        assert jnp.zeros(4).devices() == {jax.local_devices()[0]}


def test_binding_to_the_process_s_first_device_is_the_identity():
    """A worker on device 0 (every one-worker process) dispatches as an
    executor bound to none does: nothing committed, no thread pointed
    anywhere; its spans and fingerprints still name the device."""
    first, second = jax.local_devices()[:2]
    catalog = Session().catalog
    ex = Executor(catalog, device=first)
    assert ex.device is first and ex.put_device is None
    assert ex.device_label == "cpu:0" and ex.resident.device is None
    before = jax.config.jax_default_device
    with ex.on_device():
        assert jax.config.jax_default_device is before
    bound = Executor(catalog, device=second)
    assert bound.put_device is second and bound.resident.device is second
    with bound.on_device():
        assert jax.config.jax_default_device is second
    assert jax.config.jax_default_device is before
    assert Executor(catalog).put_device is None


def test_a_thread_under_on_device_creates_on_the_executor_s_device():
    dev = jax.local_devices()[5]
    ex = Executor(Session().catalog, device=dev)
    seen = {}

    def work():
        seen["outside"] = jnp.zeros(4).devices()
        with ex.on_device():
            seen["zeros"] = jnp.zeros(4).devices()
            seen["asarray"] = jnp.asarray(np.arange(4)).devices()
            seen["program"] = (jnp.arange(8) * 2).devices()
        seen["after"] = jnp.zeros(4).devices()

    th = threading.Thread(target=work)
    th.start()
    th.join()
    first = {jax.local_devices()[0]}
    assert seen == {"outside": first, "zeros": {dev}, "asarray": {dev},
                    "program": {dev}, "after": first}
    # committed: a program follows it on a thread that has no context
    placed = ex._place(np.arange(8))
    assert placed.devices() == {dev} and (placed + 1).devices() == {dev}


# ---------------------------------------------------------------------------
# answers
# ---------------------------------------------------------------------------

CASES = [(name, i) for name, sets in sorted(PARAMETERS.items())
         for i in range(len(sets))]


@pytest.mark.parametrize("name, i", CASES)
def test_four_workers_answer_as_one_executor_and_the_reference(
        cluster, tables, name, i):
    t, params = TEMPLATES[name], PARAMETERS[name][i]
    sql = t.render(params, "tpch.tiny")
    rows, info, spans = cluster.run(sql)
    assert info["distributed"] and not info.get("fallbackReason")
    want = t.reference(tables, params)
    assert compare.mismatched_cells(rows, want, t.COLUMNS) == (0, None)
    assert len(rows) == len(want) > 0
    # one executor, no worker, no split: the same cells, in the
    # protocol's form (decimals and dates as text)
    alone = Session().execute(sql).rows
    assert rows == [[v if v is None or isinstance(v, (int, float, str))
                     else str(v) for v in r] for r in alone]
    stages = tasks_by_stage(spans)
    took = {device_label(w.task_manager.device): w.node_id
            for w in cluster.workers}
    for tasks in stages:
        # a task names its worker's device and no other
        assert all(took[a["device"]] == a["node"] for a in tasks)
    # the lineitem stage (the last; q18's reads lineitem too): 30 splits
    # over four workers, four tasks on four devices
    assert len(stages[-1]) == WORKERS
    assert {a["device"] for a in stages[-1]} == set(took)
    assert sorted(a["splits"] for a in stages[-1]) == [7, 7, 8, 8]


def test_stage_spans_count_their_hedges(cluster):
    _, _, spans = cluster.run(TEMPLATES["q3"].render(
        {"segment": "FURNITURE", "day": 9}, "tpch.tiny"))
    stages = [s for s in spans if s["name"] in ("source-stage",
                                                "build-stage")]
    assert {s["name"] for s in stages} == {"source-stage", "build-stage"}
    assert all(s["attributes"]["hedges"] == 0 for s in stages)


def test_a_hedged_straggler_shows_on_its_stage(cluster):
    sched = cluster.coord.state.scheduler
    sql = ("SELECT l_orderkey, l_quantity FROM tpch.tiny.lineitem "
           "WHERE l_shipdate > DATE '1998-11-{day}'")
    cluster.run(sql.format(day="02"))        # the fragment's programs
    inj = FailureInjector(seed=43)
    inj.inject("WORKER_TASK_RUN", times=1, fault=DELAY, delay_s=3.0)
    cluster.workers[1].task_manager.injector = inj
    sched.hedge_min_s, sched.hedge_multiplier = 0.1, 2.0
    try:
        _, info, spans = cluster.run(sql.format(day="03"))
    finally:
        sched.hedge_min_s, sched.hedge_multiplier = 2.0, 4.0
        cluster.workers[1].task_manager.injector = None
    assert info["distributed"]
    (stage,) = [s for s in spans if s["name"] == "source-stage"]
    assert stage["attributes"]["hedges"] >= 1
    # the twin ran on another worker's device
    tasks = [t["attributes"] for t in spans if t["name"] == "worker-task"]
    assert len(tasks) > WORKERS or len({a["device"] for a in tasks}) >= 3


def test_one_finished_peer_of_four_is_no_median_to_hedge_on(cluster):
    """Three workers compile (here: are delayed) while the fourth finds
    its programs ready: four times its wall passes, and nothing is
    hedged until half the stage's units have finished."""
    sched = cluster.coord.state.scheduler
    sql = ("SELECT l_orderkey, l_quantity FROM tpch.tiny.lineitem "
           "WHERE l_shipdate > DATE '1998-11-{day}'")
    cluster.run(sql.format(day="04"))        # the fragment's programs
    for w in cluster.workers[1:]:
        w.task_manager.injector = FailureInjector(seed=44)
        w.task_manager.injector.inject("WORKER_TASK_RUN", times=1,
                                       fault=DELAY, delay_s=2.0)
    sched.hedge_min_s, sched.hedge_multiplier = 0.1, 2.0
    try:
        t0 = time.monotonic()
        _, info, spans = cluster.run(sql.format(day="05"))
        wall = time.monotonic() - t0
    finally:
        sched.hedge_min_s, sched.hedge_multiplier = 2.0, 4.0
        for w in cluster.workers[1:]:
            w.task_manager.injector = None
    assert info["distributed"] and wall >= 2.0
    (stage,) = [s for s in spans if s["name"] == "source-stage"]
    assert stage["attributes"]["hedges"] == 0
    tasks = [t["attributes"] for t in spans if t["name"] == "worker-task"]
    assert len(tasks) == WORKERS


# ---------------------------------------------------------------------------
# where a task's arrays are
# ---------------------------------------------------------------------------

def test_what_a_task_puts_pins_and_holds_is_on_its_device(monkeypatch):
    """q3 as one fragment: both build sides pinned by the task, lineitem
    streamed through the feeder, the partials held and folded."""
    session = Session(default_schema="tiny")
    frag, splits, _ = _fragment(session, Q3)
    dev = jax.local_devices()[3]
    seen = {}
    make = TaskManager._split_decoder
    loop = TaskManager._run_splits

    def spied_decoder(self, task, driver_scan, cap):
        decode = make(self, task, driver_scan, cap)
        if self is not seen["manager"]:     # another test's straggler
            return decode

        def counted(si):
            chunk = decode(si)
            seen["threads"].add(threading.current_thread().name)
            seen["staged"] += [c.data.devices() for c in chunk.columns] \
                + [chunk.live.devices()]
            return chunk
        return counted

    def spied_loop(self, task, ex, root, driver_scan, pipeline, lap, held,
                   *rest):
        if self is not seen["manager"]:
            return loop(self, task, ex, root, driver_scan, pipeline, lap,
                        held, *rest)
        # the pinned builds are the substitutions made before the loop
        seen["pinned"] = [leaf.devices() for b in ex._subst.values()
                          for leaf in jax.tree_util.tree_leaves(b)]
        out = loop(self, task, ex, root, driver_scan, pipeline, lap, held,
                   *rest)
        seen["held"] = [leaf.devices() for b in held.state.device
                        for leaf in jax.tree_util.tree_leaves(b)]
        # the LUT and, of a packed one, the offsets beside it
        seen["luts"] = [leaf.devices()
                        for lut, packed, _ in ex._chunk_lut_cache.values()
                        for leaf in jax.tree_util.tree_leaves((lut, packed))
                        if isinstance(leaf, jax.Array)]
        return out

    monkeypatch.setattr(TaskManager, "_split_decoder", spied_decoder)
    monkeypatch.setattr(TaskManager, "_run_splits", spied_loop)
    pages = {}
    for device in (dev, None):
        tm = TaskManager(session.catalog, device=device)
        seen.update(staged=[], threads=set(), manager=tm)
        task = _run_task(tm, f"on-{device_label(device)}", frag, splits)
        assert task.state == "FINISHED", task.error
        pages[device] = {b: list(p) for b, p in task.buffers.items() if p}
        (wt,) = _span(task, "worker-task")
        assert wt["attributes"].get("device") == device_label(device)
        want = {device or jax.local_devices()[0]}
        assert "scan-prefetch" in seen["threads"]
        for what in ("staged", "pinned", "held", "luts"):
            assert seen[what] and all(d == want for d in seen[what]), what
        assert tm.memory_info()["reserved"] == 0
    # the same page, bit for bit, wherever it was computed
    assert pages[dev] and pages[dev] == pages[None]


def test_resident_columns_and_their_budget_are_the_device_s_own():
    dev = jax.local_devices()[2]
    ex = Executor(Session().catalog, device=dev)
    data = ex.catalog.get_table("tpch", "tiny", "nation")
    col, put = ex._resident_column(("tpch", "tiny", "nation"), 0, data,
                                   1024, None)
    assert put > 0 and col.data.devices() == {dev}
    assert ex.resident.device is dev
    stats = device_memory_stats(dev)
    assert stats["device"] == "cpu:2" and stats["platform"] == "cpu"
    assert "device" not in device_memory_stats()


def test_a_worker_s_status_reports_its_own_device(cluster):
    import json
    from urllib.request import urlopen
    for w in cluster.workers:
        with urlopen(f"{w.uri}/v1/status", timeout=10) as r:
            status = json.loads(r.read().decode())
        assert status["device"]["device"] == \
            device_label(w.task_manager.device)


# ---------------------------------------------------------------------------
# the compile recorder: one compile a device
# ---------------------------------------------------------------------------

def test_a_shape_s_compile_on_a_second_device_is_keyed_by_shape():
    rec = CompileRecorder()
    fn = instrument(jax.jit(lambda x, k: x * k, static_argnums=1),
                    "devices.scale", recorder=rec)
    d0, d1 = jax.local_devices()[:2]

    def on(device, k):
        rec.bind_stats(None, device_label(device))
        return fn(jax.device_put(np.arange(8), device), k)

    on(d0, 2)
    on(d1, 2)       # the same shape and literal, another device
    on(d1, 2)       # a hit there
    on(d1, 3)       # a new literal on a device that has met the shape
    events = [(e.hit, e.key, e.fingerprint.rsplit("@", 1)[-1])
              for e in rec.events]
    assert events == [(False, "shape", "cpu:0"), (False, "shape", "cpu:1"),
                      (True, "", "cpu:1"), (False, "literal", "cpu:1")]
    assert rec.totals()["shapeKeyedCompiles"] == 2
    # an executor bound to no device keeps the bare fingerprint
    rec.bind_stats(None)
    fn(jnp.arange(8), 5)
    assert "@" not in rec.events[-1].fingerprint


# ---------------------------------------------------------------------------
# placement
# ---------------------------------------------------------------------------

class _Node:
    def __init__(self, node_id, memory):
        self.node_id, self.memory = node_id, memory


def test_peers_are_dealt_splits_in_one_order_whatever_they_last_reported():
    limit = 64 << 30
    nodes = [_Node("w2", {"reserved": 0, "limit": limit}),
             _Node("w0", {"reserved": 300 << 20, "limit": limit}),
             _Node("w3", None),
             _Node("w1", {"reserved": 41 << 20, "limit": limit})]
    for order in itertools.permutations(nodes):
        got = sorted(order, key=scheduler_module._placement_key)
        assert [n.node_id for n in got] == ["w0", "w1", "w2", "w3"]
    # a node an eighth of its pool fuller than its peers comes last
    nodes[1].memory["reserved"] = 9 << 30
    got = sorted(nodes, key=scheduler_module._placement_key)
    assert [n.node_id for n in got] == ["w1", "w2", "w3", "w0"]
    # no limit on record: any reservation is pressure
    assert scheduler_module._placement_key(
        _Node("w9", {"reserved": 5})) == (1, "w9")


def test_a_stage_s_odd_splits_go_to_the_same_workers_every_time(cluster):
    """30 lineitem splits over four workers: two workers take eight.
    Which two must not depend on what each last reported."""
    q6 = TEMPLATES["q6"]
    took = []
    for n, discount in enumerate((3, 4, 8)):
        # stale reports of small reservations, another worker's each time
        for k, node in enumerate(cluster.coord.state.active_nodes()):
            node.memory = {"reserved": ((k + n) % WORKERS) << 20,
                           "limit": 64 << 30}
        _, _, spans = cluster.run(q6.render(
            dict(q6.VALIDATION, discount=discount), "tpch.tiny"))
        took.append(sorted((a["node"], a["splits"])
                           for a in tasks_by_stage(spans)[-1]))
    assert took[0] == took[1] == took[2]
    assert took[0] == [("dev-w0", 8), ("dev-w1", 8), ("dev-w2", 7),
                       ("dev-w3", 7)]
