"""Device-time profiling, JIT-compile observability, query history and
latency-regression detection (round 10)."""

import json
import os
import time

import jax
import pytest

from trino_tpu.exec.profiler import (RECORDER, CompileRecorder,
                                     device_memory_stats, instrument)
from trino_tpu.exec.session import Session
from trino_tpu.server.history import (HistoryEventListener,
                                      QueryHistoryStore, is_regressed,
                                      plan_fingerprint, robust_baseline)
from trino_tpu.server.statemachine import (QueryStateMachine,
                                           QueryTracker, TrackedQuery)

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(TESTS_DIR)


# ---------------------------------------------------------------------------
# compile recorder
# ---------------------------------------------------------------------------

def test_recorder_counts_compiles_and_hits():
    rec = CompileRecorder()
    f = instrument(jax.jit(lambda x: x * 2), "test.double",
                   recorder=rec)
    import jax.numpy as jnp
    f(jnp.ones(8))                    # compile
    f(jnp.ones(8))                    # hit
    f(jnp.ones(16))                   # new shape: compile
    t = rec.totals()
    assert t["compiles"] == 2 and t["hits"] == 1
    assert t["compileSeconds"] > 0
    entries = rec.snapshot()
    assert len(entries) == 2          # two fingerprints, same site
    assert all(e["site"] == "test.double" for e in entries)
    hit_entry = next(e for e in entries if e["hits"] == 1)
    assert hit_entry["compiles"] == 1
    assert hit_entry["last_compile_ms"] > 0


def test_recorder_silent_inside_outer_trace():
    """A jit site called during another site's trace must not record —
    the outer program owns the compile."""
    rec = CompileRecorder()
    inner = instrument(jax.jit(lambda x: x + 1), "test.inner",
                       recorder=rec)

    @jax.jit
    def outer(x):
        return inner(x) * 3

    import jax.numpy as jnp
    outer(jnp.ones(4))
    assert rec.totals()["compiles"] == 0
    inner(jnp.ones(4))                # eager boundary: records
    assert rec.totals()["compiles"] == 1


def test_exec_stats_jit_compiles_agree_with_recorder():
    """The satellite fix: every jit site routes through the recorder, so
    ExecStats.jit_compiles (thread-bound attribution) moves in lockstep
    with the process recorder during a single-threaded query."""
    s = Session(default_schema="tiny")
    s.execute("SELECT count(*) FROM region")       # warm common kernels
    stats0 = s.executor.stats.jit_compiles
    rec0 = RECORDER.totals()["compiles"]
    # the first statement of this shape in the process: its fused filter
    # compiles (a literal alone would compile nothing: it is an operand)
    s.execute("SELECT count(*) FROM nation WHERE n_nationkey > 17")
    d_stats = s.executor.stats.jit_compiles - stats0
    d_rec = RECORDER.totals()["compiles"] - rec0
    assert d_stats >= 1
    assert d_stats == d_rec


def test_device_memory_stats_shape():
    st = device_memory_stats()
    assert st.get("platform") == "cpu"
    assert "bytesInUse" in st and "bytesLimit" in st


# ---------------------------------------------------------------------------
# fenced device/host/compile attribution
# ---------------------------------------------------------------------------

def test_profile_split_sums_to_wall():
    s = Session(default_schema="tiny")
    s.execute("SET SESSION enable_profiling = true")
    s.execute("SELECT l_returnflag, sum(l_quantity) FROM lineitem "
              "GROUP BY l_returnflag ORDER BY l_returnflag")
    ns = s.executor.node_stats
    assert ns, "profiled run produced no node stats"
    for st in ns.values():
        wall, rows, device_s, host_s, compile_s = st
        assert wall >= 0 and device_s >= 0 and host_s >= 0 \
            and compile_s >= 0
        # the fence splits wall exactly into components
        assert abs(wall - (device_s + host_s + compile_s)) < 1e-9


def test_profiling_off_adds_zero_fences(monkeypatch):
    """With enable_profiling off, the dispatch path must never fence —
    a per-node sync would serialize the whole async pipeline."""
    s = Session(default_schema="tiny")
    s.execute("SELECT count(*) FROM nation")       # warm compiles
    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda *a, **k: (calls.append(1),
                                         real(*a, **k))[1])
    s.execute("SELECT count(*) FROM nation")
    assert calls == []
    assert s.executor.node_stats == {}
    # and turning profiling on uses the fence
    s.execute("SET SESSION enable_profiling = true")
    s.execute("SELECT count(*) FROM nation")
    assert len(calls) > 0


def test_explain_analyze_renders_device_split():
    s = Session(default_schema="tiny")
    text = "\n".join(r[0] for r in s.execute(
        "EXPLAIN ANALYZE SELECT n_regionkey, count(*) FROM nation "
        "GROUP BY n_regionkey").rows)
    assert "(device " in text and "+ compile " in text, text
    assert "rows]" in text


# ---------------------------------------------------------------------------
# query history store + regression detector
# ---------------------------------------------------------------------------

def _rec(i, elapsed, fp_sql="SELECT 1 FROM t", state="FINISHED",
         **extra):
    return dict({"query_id": f"q{i}", "sql": fp_sql, "user": "u",
                 "state": state, "elapsed_s": elapsed, "rows": 1,
                 "bytes_shuffled": 0, "spills": 0}, **extra)


def test_history_store_persists_and_reloads(tmp_path):
    path = str(tmp_path / "hist.jsonl")
    store = QueryHistoryStore(path=path)
    for i, el in enumerate((1.0, 1.1, 0.9)):
        store.record(_rec(i, el))
    assert len(store) == 3
    # dedup by query id (eviction flush after the completion event)
    store.record(_rec(0, 5.0))
    assert len(store) == 3
    # a fresh store reloads the ring from disk
    again = QueryHistoryStore(path=path)
    assert len(again) == 3
    fp = plan_fingerprint("SELECT   1 from T;")
    assert [r["query_id"] for r in again.for_fingerprint(fp)] == \
        ["q0", "q1", "q2"]


def test_fingerprint_normalizes_statement_shape():
    assert plan_fingerprint("SELECT 1  FROM t") == \
        plan_fingerprint("select 1 from t;")
    assert plan_fingerprint("SELECT 1 FROM t") != \
        plan_fingerprint("SELECT 2 FROM t")


def test_regression_detector_flags_3x_and_stays_quiet_on_jitter(
        tmp_path):
    from trino_tpu.metrics import LATENCY_REGRESSIONS
    store = QueryHistoryStore(path=str(tmp_path / "h.jsonl"))
    jitter = (1.0, 1.08, 0.95, 1.02, 0.9, 1.1)
    for i, el in enumerate(jitter):
        assert store.record(_rec(i, el)) is None
    # jittered value inside the envelope: quiet
    assert store.record(_rec(50, 1.05)) is None
    # synthetic 3x slowdown: flagged, logged, counted
    before = LATENCY_REGRESSIONS.value()
    verdict = store.record(_rec(51, 3.0))
    assert verdict is not None and verdict["metric"] == "elapsed_s"
    assert LATENCY_REGRESSIONS.value() == before + 1
    flagged = [r for r in store.snapshot() if r["query_id"] == "q51"]
    assert flagged and flagged[0]["regressed"]


def test_detector_needs_min_baseline_and_skips_failures(tmp_path):
    store = QueryHistoryStore(path=str(tmp_path / "h.jsonl"))
    # too few priors: never judged
    for i, el in enumerate((1.0, 1.0)):
        store.record(_rec(i, el))
    assert store.record(_rec(10, 30.0)) is None
    # failed queries neither build baselines nor get judged
    for i in range(20, 26):
        store.record(_rec(i, 1.0, state="FAILED"))
    assert store.record(_rec(30, 30.0, state="FAILED")) is None


def test_robust_baseline_and_rule():
    med, mad = robust_baseline([1.0, 1.1, 0.9, 1.0, 1.2])
    assert abs(med - 1.0) < 1e-9
    assert mad == pytest.approx(0.1)
    assert is_regressed(3.0, med, mad)
    assert not is_regressed(1.3, med, mad)       # inside the ratio gate
    assert not is_regressed(0.5, med, mad)


def test_tracker_eviction_flushes_history_and_env_cap(tmp_path,
                                                      monkeypatch):
    store = QueryHistoryStore(path=str(tmp_path / "h.jsonl"))
    tracker = QueryTracker(max_history=2)
    tracker.on_evict = store.record_tracked
    for i in range(5):
        tq = TrackedQuery(f"ev{i}", f"SELECT {i}", "u",
                          QueryStateMachine(f"ev{i}"))
        tq.elapsed_s = 0.5
        tq.state_machine.fail("boom")
        tracker.register(tq)
        time.sleep(0.002)      # distinct ended_at ordering
    # cap held, evicted queries flushed to the store
    done = [q for q in tracker.all() if q.state_machine.is_done()]
    assert len(done) == 2
    evicted_ids = {r["query_id"] for r in store.snapshot()}
    assert {"ev0", "ev1", "ev2"} <= evicted_ids
    # the cap is env-configurable
    monkeypatch.setenv("TRINO_TPU_QUERY_HISTORY", "7")
    assert QueryTracker().max_history == 7
    monkeypatch.setenv("TRINO_TPU_QUERY_HISTORY", "bogus")
    assert QueryTracker().max_history == 100


def test_completed_event_feeds_listener(tmp_path):
    from trino_tpu.events import QueryCompletedEvent
    store = QueryHistoryStore(path=str(tmp_path / "h.jsonl"))
    li = HistoryEventListener(store)
    li.query_completed(QueryCompletedEvent(
        "qz", "u", "SELECT 1", "FINISHED", None, 0.2, 1, 0,
        time.time(), spills=3))
    (rec,) = store.snapshot()
    assert rec["spills"] == 3 and rec["state"] == "FINISHED"


# ---------------------------------------------------------------------------
# cluster surface: /v1/jit, system tables, worker device stats,
# distributed EXPLAIN ANALYZE split
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    os.environ["TRINO_TPU_HISTORY_PATH"] = str(
        tmp_path_factory.mktemp("hist") / "query_history.jsonl")
    try:
        from trino_tpu.server.coordinator import CoordinatorServer
        from trino_tpu.server.failuredetector import \
            HeartbeatFailureDetector
        from trino_tpu.server.worker import WorkerServer
        session = Session(default_schema="tiny")
        coord = CoordinatorServer(session).start()
        coord.state.scheduler.split_rows = 8192
        workers = [WorkerServer(f"prof-w{i}", coord.uri,
                                announce_interval_s=0.1,
                                catalog=session.catalog).start()
                   for i in range(2)]
        detector = HeartbeatFailureDetector(coord.state,
                                            interval_s=0.2).start()
        deadline = time.time() + 5
        while len(coord.state.active_nodes()) < 2 and \
                time.time() < deadline:
            time.sleep(0.05)
        yield coord, workers, session
        detector.stop()
        for w in workers:
            w.stop()
        coord.stop()
    finally:
        os.environ.pop("TRINO_TPU_HISTORY_PATH", None)


DIST_SQL = ("SELECT l_returnflag, count(*) AS c FROM lineitem "
            "GROUP BY l_returnflag ORDER BY l_returnflag")


def test_v1_jit_route_serves_recorder(cluster):
    from urllib.request import urlopen
    coord, workers, session = cluster
    from trino_tpu.client.client import Client
    Client(coord.uri, user="prof").execute("SELECT count(*) FROM nation")
    with urlopen(f"{coord.uri}/v1/jit", timeout=10) as resp:
        payload = json.loads(resp.read().decode())
    assert payload["totals"]["compiles"] >= 1
    assert payload["entries"], "no jit-cache entries after a query"
    e = payload["entries"][0]
    assert {"site", "fingerprint", "compiles", "hits"} <= set(e)


def test_system_runtime_jit_cache_queryable(cluster):
    coord, workers, session = cluster
    from trino_tpu.client.client import Client
    client = Client(coord.uri, user="prof")
    client.execute("SELECT count(*) FROM nation")
    r = client.execute("SELECT site, fingerprint, compiles, cache_hits, "
                       "compile_ms FROM system.runtime.jit_cache")
    assert r.state == "FINISHED" and len(r.rows) >= 1
    assert any(int(row[2]) >= 1 for row in r.rows)


def test_system_runtime_query_history_end_to_end(cluster):
    coord, workers, session = cluster
    from trino_tpu.client.client import Client
    client = Client(coord.uri, user="prof")
    r = client.execute("SELECT count(*) FROM region")
    deadline = time.time() + 5
    while time.time() < deadline:
        rows = client.execute(
            "SELECT query_id, state, regressed FROM "
            "system.runtime.query_history").rows
        if any(row[0] == r.query_id for row in rows):
            break
        time.sleep(0.05)
    assert any(row[0] == r.query_id and row[1] == "FINISHED"
               for row in rows)
    # and the ring persisted to the JSONL file
    path = os.environ["TRINO_TPU_HISTORY_PATH"]
    with open(path) as f:
        ids = [json.loads(line)["query_id"] for line in f if line.strip()]
    assert r.query_id in ids


def test_worker_status_and_nodes_table_carry_device_stats(cluster):
    from urllib.request import urlopen
    coord, workers, session = cluster
    with urlopen(f"{workers[0].uri}/v1/status", timeout=10) as resp:
        st = json.loads(resp.read().decode())
    assert st["device"]["platform"] == "cpu"
    assert "bytesInUse" in st["device"]
    # the heartbeat carried it into the node inventory + system table
    deadline = time.time() + 5
    while time.time() < deadline:
        if all(n.device is not None
               for n in coord.state.nodes.values()):
            break
        time.sleep(0.05)
    from trino_tpu.client.client import Client
    r = Client(coord.uri, user="prof").execute(
        "SELECT node_id, reserved_bytes, device_bytes_in_use, "
        "device_bytes_limit FROM system.runtime.nodes")
    assert len(r.rows) >= 2
    for row in r.rows:
        assert int(row[2]) >= 0     # zeros on CPU, live bytes on TPU


def test_distributed_explain_analyze_renders_split(cluster):
    import re
    coord, workers, session = cluster
    coord.state.scheduler.spool.clear()
    from trino_tpu.client.client import Client
    r = Client(coord.uri, user="prof").execute(
        "EXPLAIN ANALYZE " + DIST_SQL)
    text = "\n".join(row[0] for row in r.rows)
    assert "Distributed execution" in text
    m = re.search(r"operator \w+: rows=\d+, wall=[\d.]+ms "
                  r"\(device [\d.]+ \+ host [\d.]+ \+ "
                  r"compile [\d.]+\), calls=\d+", text)
    assert m, text


# ---------------------------------------------------------------------------
# bench --check-regressions gate
# ---------------------------------------------------------------------------

def _round_file(tmp_path, name, configs):
    detail = {cfg: {"tpu_steady_ms": v, "speedup": 1.0}
              for cfg, v in configs.items()}
    line = json.dumps({"metric": "tpch_e2e_sql_to_result_wall_ms",
                       "value": 1.0, "detail": detail})
    p = tmp_path / name
    p.write_text(json.dumps({"n": 1, "cmd": "python bench.py", "rc": 0,
                             "tail": "noise\n" + line + "\n"}))
    return str(p)


def test_check_regressions_flags_injected_3x(tmp_path):
    import bench
    paths = [_round_file(tmp_path, f"BENCH_r0{i}.json", {"q": v})
             for i, v in enumerate((100.0, 110.0, 95.0, 105.0), 1)]
    ok, report = bench.check_regressions(paths)
    assert ok and report["configs"]["q"]["status"] == "ok"
    # injected 3x latency regression in a new round: gate trips
    paths.append(_round_file(tmp_path, "BENCH_r05.json", {"q": 315.0}))
    ok2, report2 = bench.check_regressions(paths)
    assert not ok2
    assert report2["configs"]["q"]["status"] == "REGRESSED"
    assert report2["regressions"] == ["q"]


def test_check_regressions_passes_current_trajectory():
    """The acceptance gate: the repo's own BENCH_r*.json rounds must
    pass (a regression here means the build actually got slower)."""
    import glob

    import bench
    paths = sorted(glob.glob(os.path.join(REPO_ROOT, "BENCH_r*.json")))
    ok, report = bench.check_regressions(paths)
    assert ok, report


def test_check_regressions_tolerates_unparseable_rounds(tmp_path):
    import bench
    bad = tmp_path / "BENCH_r01.json"
    bad.write_text("not json")
    killed = tmp_path / "BENCH_r02.json"
    killed.write_text(json.dumps({"n": 2, "rc": 124, "tail": ""}))
    ok, report = bench.check_regressions([str(bad), str(killed)])
    assert ok and report["rounds"] == 0


def test_bench_main_check_regressions_exit_codes(tmp_path, monkeypatch):
    import bench
    for i, v in enumerate((100.0, 101.0, 99.0), 1):
        _round_file(tmp_path, f"BENCH_r0{i}.json", {"q": v})
    monkeypatch.chdir(tmp_path)
    assert bench.main(["--check-regressions"]) == 0
    _round_file(tmp_path, "BENCH_r04.json", {"q": 900.0})
    assert bench.main(["--check-regressions"]) == 1


# ---------------------------------------------------------------------------
# a new literal compiles nothing: filter_project is keyed by the
# expression's shape, its literals are operands bound once a plan node
# ---------------------------------------------------------------------------

TINY = "tpch.tiny"
# two parameter sets a template (TPC-H 2.4), the second with every value
# new; q3's keep the join outputs inside the capacities of the first
LITERAL_PAIRS = {
    "q6": ({"year": 1994, "discount": 6, "quantity": 24},
           {"year": 1997, "discount": 9, "quantity": 25}),
    "q1": ({"delta": 90}, {"delta": 63}),
    "q3": ({"segment": "BUILDING", "day": 15},
           {"segment": "MACHINERY", "day": 31}),
}


def _filter_project_compiles():
    return sum(e["compiles"] for e in RECORDER.snapshot()
               if e["site"] in ("project.filter_project",
                                "executor.filter_project_fused"))


@pytest.fixture(scope="module", params=["single-node", "one-worker"])
def literal_route(request):
    """(client, coordinator, name of the span that carries literalSlots):
    the coordinator alone on its device route, or with one worker and
    8,192-row split tasks. Traced, so the spans can be read."""
    from trino_tpu.client.client import Client
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.worker import WorkerServer
    session = Session(default_schema="tiny")
    coord = CoordinatorServer(session).start()
    worker = None
    client = Client(coord.uri, user="literals")
    try:
        if request.param == "one-worker":
            coord.state.scheduler.split_rows = 8192
            worker = WorkerServer("lit-w0", coord.uri,
                                  announce_interval_s=0.1,
                                  catalog=session.catalog).start()
            deadline = time.time() + 5
            while not coord.state.active_nodes() and \
                    time.time() < deadline:
                time.sleep(0.05)
        else:
            client.execute("SET SESSION routing_mode = device")
        client.execute("SET SESSION enable_tracing = true")
        yield client, coord, session, request.param
    finally:
        if worker is not None:
            worker.stop()
        coord.stop()


@pytest.mark.parametrize("name", ["q6", "q1", "q3"])
def test_second_statement_with_new_literals_compiles_nothing(
        literal_route, name):
    # the benchmark's templates with their plain numpy references
    from test_resident_tables import bench_module, reference_tables
    client, coord, session, route = literal_route
    template = bench_module(f"queries.{name}")
    compare = bench_module("compare")
    tables = reference_tables(session, [template])
    readings = []
    for params in LITERAL_PAIRS[name]:
        coord.state.scheduler.spool.clear()
        t0, fp0 = RECORDER.totals(), _filter_project_compiles()
        res = client.execute(template.render(params, TINY))
        t1, fp1 = RECORDER.totals(), _filter_project_compiles()
        assert compare.mismatched_cells(
            res.rows, template.reference(tables, params),
            template.COLUMNS) == (0, None)
        info = client.query_info(res.query_id)
        spans = client._request(
            "GET", f"{coord.uri}/v1/query/{res.query_id}/trace")["spans"]
        if route == "one-worker":
            assert info["distributed"], info.get("fallbackReason")
            carriers = [s for s in spans if s["name"] == "worker-task"]
        else:
            assert info["route"] == "device" and not info["distributed"]
            carriers = [s for s in spans if s["name"] == "execute"]
        assert carriers and all(
            "literalSlots" in s["attributes"] for s in carriers)
        readings.append({
            "literal": t1["literalKeyedCompiles"]
            - t0["literalKeyedCompiles"],
            "filter_project": fp1 - fp0,
            "slots": sum(s["attributes"]["literalSlots"]
                         for s in carriers),
            "compile_spans": [s["attributes"]["site"] for s in spans
                              if s["name"] == "compile" and
                              "filter_project" in s["attributes"]["site"]],
        })
    first, second = readings
    # the literals were bound as operands, once a plan node: a task of
    # many splits binds what a task of one split binds
    assert first["slots"] > 0 and second["slots"] == first["slots"]
    assert second["filter_project"] == 0 and not second["compile_spans"]
    assert second["literal"] == 0
