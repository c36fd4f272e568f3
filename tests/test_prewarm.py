"""Round-16 cold-start elimination (exec/prewarm.py + friends).

The contracts under test:

- AOT pre-warming: warm a fingerprint once off the query path, then a
  query-path execution of the same statement performs ZERO fresh
  top-level compiles (CompileRecorder-verified, in a fresh process so
  in-process trace caches can't fake it) and credits prewarm hits +
  compile-seconds-saved.
- Shape canonicalization: `bucket_capacity` lands every data-dependent
  cardinality on the enumerable {2^k, 1.5*2^k} lattice, and a sweep of
  TPC-H-shaped statements adds only a bounded number of distinct
  compiled shapes per jit site.
- Shared persistent compile cache: the JAX_COMPILATION_CACHE_DIR contract —
  explicit opt-in persists programs even under JAX_PLATFORMS=cpu,
  explicit "off" wins, and cpu-only defaults to inactive.
- Compile-aware routing: a host-eligible statement routes to the
  bit-exact numpy interpreter while its device program is cold, and the
  SAME fingerprint routes to device once the background warm lands.
- Joining-worker handshake: a worker started with TRINO_TPU_PREWARM=1
  pulls the coordinator's warm-manifest and compiles the canonical
  shapes before announcing ACTIVE.
- The `bench.py --cold-start` regression series gates (median+MAD) and
  bites on an injected cold-wall blowup.
- Prewarm OFF is inert: no cold signal, no threads, no property flips.
"""

import json
import os
import subprocess
import sys
import time
from urllib.request import Request, urlopen

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from trino_tpu.batch import bucket_capacity, pad_capacity   # noqa: E402
from trino_tpu.client.client import Client                  # noqa: E402
from trino_tpu.exec.prewarm import (DEFAULT_MAX_SHAPE,      # noqa: E402
                                    PrewarmEngine,
                                    canonical_lattice,
                                    compile_cache_stats,
                                    prewarm_enabled_by_env)
from trino_tpu.exec.profiler import RECORDER                # noqa: E402
from trino_tpu.exec.session import Session                  # noqa: E402
from trino_tpu.server.coordinator import CoordinatorServer  # noqa: E402
from trino_tpu.server.history import (QueryHistoryStore,    # noqa: E402
                                      plan_fingerprint)
from trino_tpu.server.security import internal_headers      # noqa: E402
from trino_tpu.server.worker import WorkerServer            # noqa: E402


def _run_child(code: str, env_extra: dict, timeout=300):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
    env.pop("TRINO_TPU_PREWARM", None)
    env.update(env_extra)
    return subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, env=env,
                          cwd=REPO_ROOT, timeout=timeout)


# ---------------------------------------------------------------------------
# capacity lattice
# ---------------------------------------------------------------------------

def test_bucket_capacity_edges():
    assert bucket_capacity(0) == 1024
    assert bucket_capacity(1) == 1024
    assert bucket_capacity(1023) == 1024
    assert bucket_capacity(1024) == 1024           # exact power stays
    assert bucket_capacity(1025) == 1536           # next half-step
    assert bucket_capacity(1536) == 1536           # exact 1.5*2^k stays
    assert bucket_capacity(1537) == 2048
    assert bucket_capacity(3072) == 3072
    assert bucket_capacity(3073) == 4096
    for k in range(10, 21):
        assert bucket_capacity(1 << k) == 1 << k
        assert bucket_capacity((1 << k) + 1) == 3 << (k - 1)
        assert bucket_capacity(3 << (k - 1)) == 3 << (k - 1)


def test_pad_capacity_edges():
    assert pad_capacity(0) == 1024
    assert pad_capacity(1) == 1024
    assert pad_capacity(1024) == 1024
    assert pad_capacity(1025) == 2048
    assert pad_capacity(5, multiple=4) == 8
    assert pad_capacity(0, multiple=4) == 4


def test_canonical_lattice_covers_every_bucket():
    lat = canonical_lattice(DEFAULT_MAX_SHAPE)
    assert lat[:4] == [1024, 1536, 2048, 3072]
    assert lat == sorted(lat)
    lat_set = set(lat)
    for n in (0, 1, 999, 1024, 1025, 5000, 123457, 999999):
        assert bucket_capacity(n) in lat_set, n


def test_odd_cardinalities_land_on_few_buckets():
    # 541 odd cardinalities collapse to the lattice points in range —
    # the whole point of canonicalization: an enumerable shape set
    ns = range(1, 20000, 37)
    caps = {bucket_capacity(n) for n in ns}
    assert caps <= set(canonical_lattice(1 << 15))
    assert len(caps) <= 10


# ---------------------------------------------------------------------------
# history ranking (top_fingerprints)
# ---------------------------------------------------------------------------

def _hist_rec(qid, sql, end, state="FINISHED"):
    return {"query_id": qid, "sql": sql, "state": state,
            "fingerprint": plan_fingerprint(sql), "end_time": end,
            "elapsed_s": 0.01}


def test_top_fingerprints_ranking():
    store = QueryHistoryStore(path="")
    now = time.time()
    # 3 recent runs beat 5 day-old runs under the 1h-half-life decay
    for i in range(3):
        store.record(_hist_rec(f"a{i}", "SELECT 1", now - 60))
    for i in range(5):
        store.record(_hist_rec(f"b{i}", "SELECT 2", now - 86400))
    store.record(_hist_rec("c0", "SELECT 3", now, state="FAILED"))
    top = store.top_fingerprints(5)
    fps = [e["fingerprint"] for e in top]
    assert fps[0] == plan_fingerprint("SELECT 1")
    assert plan_fingerprint("SELECT 2") in fps
    assert plan_fingerprint("SELECT 3") not in fps   # non-FINISHED
    assert top[0]["count"] == 3
    assert top[0]["sql"] == "SELECT 1"
    assert top[0]["score"] > top[1]["score"]
    assert len(store.top_fingerprints(1)) == 1
    assert store.top_fingerprints(0) == []


def test_top_fingerprints_keeps_latest_sql_per_fingerprint():
    store = QueryHistoryStore(path="")
    now = time.time()
    # same fingerprint, different raw text (normalization collapses
    # case/whitespace); the manifest should re-plan the latest text
    store.record(_hist_rec("x0", "SELECT count(*) FROM nation", now - 50))
    store.record(_hist_rec("x1", "select   COUNT(*) from NATION",
                           now - 10))
    top = store.top_fingerprints(1)
    assert top[0]["count"] == 2
    assert top[0]["sql"] == "select   COUNT(*) from NATION"


# ---------------------------------------------------------------------------
# AOT pre-warming (fresh process: no in-process trace cache can hide)
# ---------------------------------------------------------------------------

def test_fresh_process_aot_warm_then_zero_fresh_compiles():
    code = """
import json
from trino_tpu.exec.session import Session
from trino_tpu.exec.prewarm import PrewarmEngine
from trino_tpu.exec.profiler import RECORDER
from trino_tpu.server.history import plan_fingerprint
s = Session(default_schema="tiny")
eng = PrewarmEngine(session=s, enabled=True)
sql = "SELECT count(*), sum(s_acctbal) FROM supplier"
fp = plan_fingerprint(sql)
assert eng.device_cold(fp)
assert eng.warm_fingerprint(fp, sql)
assert not eng.device_cold(fp)
t0 = RECORDER.totals()
assert t0["compiles"] > 0            # the warm really compiled
res = s.execute(sql)
t1 = RECORDER.totals()
assert t1["compiles"] == t0["compiles"], (t0, t1)   # 0 fresh compiles
assert t1["prewarmHits"] > 0, t1
assert t1["compileSecondsSaved"] > 0, t1
print("PREWARM_OK", json.dumps(t1))
"""
    p = _run_child(code, {})
    assert p.returncode == 0 and "PREWARM_OK" in p.stdout, \
        p.stdout + p.stderr


def test_warm_all_respects_top_n_and_marks_warm():
    store = QueryHistoryStore(path="")
    now = time.time()
    store.record(_hist_rec("w0", "SELECT count(*) FROM region", now))
    store.record(_hist_rec("w1", "SELECT count(*) FROM nation", now - 5))
    s = Session(default_schema="tiny")
    eng = PrewarmEngine(session=s, history=store, enabled=True, top_n=1)
    assert eng.warm_all() == 1
    assert eng.warm_rounds == 1
    assert eng.is_warm(plan_fingerprint("SELECT count(*) FROM region"))
    assert eng.device_cold(plan_fingerprint("SELECT count(*) FROM nation"))


def test_warm_budget_exhaustion_stops_the_pass():
    store = QueryHistoryStore(path="")
    now = time.time()
    for i in range(4):
        store.record(_hist_rec(f"b{i}", f"SELECT {i} FROM region", now))
    s = Session(default_schema="tiny")
    eng = PrewarmEngine(session=s, history=store, enabled=True,
                        top_n=4, budget_s=0.0)
    assert eng.warm_all() == 0           # budget gone before the first


# ---------------------------------------------------------------------------
# shape canonicalization at the jit boundary
# ---------------------------------------------------------------------------

def test_warm_shapes_compiles_once_per_lattice_point():
    eng = PrewarmEngine(enabled=True)
    assert eng.warm_shapes([1024, 1536]) == 2
    c0 = RECORDER.site_shape_counts().get("prewarm.shape", 0)
    assert c0 >= 2
    # a second engine warming the same shapes adds no distinct shapes
    eng2 = PrewarmEngine(enabled=True)
    assert eng2.warm_shapes([1024, 1536]) == 2
    assert RECORDER.site_shape_counts().get("prewarm.shape", 0) == c0


def test_distinct_shapes_bounded_over_tpch_sweep():
    """The canonicalization lint: a sweep of TPC-H-shaped statements
    with varied constants/cardinalities may add only a bounded number
    of distinct compiled shapes per jit site (measured as growth so the
    lint is independent of what ran earlier in this process)."""
    s = Session(default_schema="tiny")
    before = RECORDER.site_shape_counts()
    sweep = [
        "SELECT count(*) FROM lineitem",
        "SELECT sum(l_extendedprice * l_discount) FROM lineitem "
        "WHERE l_quantity < 24",
        "SELECT sum(l_extendedprice * l_discount) FROM lineitem "
        "WHERE l_quantity < 10",
        "SELECT l_returnflag, count(*) FROM lineitem "
        "GROUP BY l_returnflag ORDER BY l_returnflag",
        "SELECT l_linestatus, sum(l_quantity) FROM lineitem "
        "WHERE l_shipdate > DATE '1995-03-15' GROUP BY l_linestatus",
        "SELECT o_orderpriority, count(*) FROM orders "
        "GROUP BY o_orderpriority ORDER BY o_orderpriority",
        "SELECT count(*) FROM orders WHERE o_orderdate < DATE "
        "'1995-03-15'",
        "SELECT n_name, count(*) FROM nation, region "
        "WHERE n_regionkey = r_regionkey GROUP BY n_name "
        "ORDER BY n_name LIMIT 5",
    ]
    for sql in sweep:
        s.execute(sql)
    after = RECORDER.site_shape_counts()
    grown = {site: n - before.get(site, 0) for site, n in after.items()}
    # expression-keyed sites (filter/project) legitimately add a couple
    # of fingerprints per distinct statement; the lint is that no site
    # explodes past that
    for site, n in grown.items():
        assert n <= 2 * len(sweep), (site, n, grown)
    # the canonicalization property proper: once the adaptive strategy
    # decisions settle (one re-execution pass), further re-executions
    # add ZERO distinct shapes anywhere — every data-dependent
    # cardinality lands back on an already-compiled lattice program
    for sql in sweep:               # adaptation pass (strategy flips)
        s.execute(sql)
    settled = RECORDER.site_shape_counts()
    for sql in sweep:               # steady state: must be pure reuse
        s.execute(sql)
    again = RECORDER.site_shape_counts()
    assert again == settled, {k: again[k] - settled.get(k, 0)
                              for k in again
                              if again[k] != settled.get(k, 0)}


def test_jit_distinct_shapes_gauge_renders():
    from trino_tpu.metrics import REGISTRY
    text = REGISTRY.render()
    assert "# TYPE trino_tpu_jit_distinct_shapes gauge" in text
    assert 'trino_tpu_jit_distinct_shapes{site="exec.fused_chunk"}' \
        in text


# ---------------------------------------------------------------------------
# shared persistent compile cache: placed from outside through
# JAX_COMPILATION_CACHE_DIR, else one fixed path inside the checkout
# ---------------------------------------------------------------------------

def test_compile_cache_default_inactive_on_cpu():
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        pytest.skip("operator placed a compile cache for this run")
    import trino_tpu
    assert trino_tpu.COMPILE_CACHE_DIR is None
    st = compile_cache_stats()
    assert st["active"] is False and st["dir"] is None


def test_compile_cache_env_dir_persists_on_cpu(tmp_path):
    cache = str(tmp_path / "cc")
    code = """
import os, jax, trino_tpu
assert trino_tpu.COMPILE_CACHE_DIR == os.environ["JAX_COMPILATION_CACHE_DIR"]
assert jax.config.jax_compilation_cache_dir == trino_tpu.COMPILE_CACHE_DIR
import jax.numpy as jnp
jax.jit(lambda x: x * 3 + 1)(jnp.arange(2048)).block_until_ready()
files = os.listdir(trino_tpu.COMPILE_CACHE_DIR)
assert files, "a placed cache persisted nothing on CPU"
from trino_tpu.exec.prewarm import compile_cache_stats
st = compile_cache_stats()
assert st["active"] and st["files"] >= 1 and st["bytes"] > 0, st
print("CACHE_OK", len(files))
"""
    p = _run_child(code, {"JAX_COMPILATION_CACHE_DIR": cache})
    assert p.returncode == 0 and "CACHE_OK" in p.stdout, \
        p.stdout + p.stderr
    assert os.listdir(cache)        # visible to OTHER processes: shared


def test_compile_cache_unset_is_fixed_path_in_checkout():
    # not a CPU-only run: the default applies. Importing the package
    # initialises no backend, so naming tpu here needs no chip.
    code = """
import os, jax, trino_tpu
want = os.path.join(os.getcwd(), ".jax_cache")
assert trino_tpu.COMPILE_CACHE_DIR == want, trino_tpu.COMPILE_CACHE_DIR
assert jax.config.jax_compilation_cache_dir == want
print("FIXED_OK")
"""
    p = _run_child(code, {"JAX_PLATFORMS": "tpu,cpu"})
    assert p.returncode == 0 and "FIXED_OK" in p.stdout, \
        p.stdout + p.stderr
    with open(os.path.join(REPO_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_env_dir_wins_over_default(tmp_path):
    cache = str(tmp_path / "placed")
    code = """
import os, jax, trino_tpu
assert trino_tpu.COMPILE_CACHE_DIR == os.environ["JAX_COMPILATION_CACHE_DIR"]
assert jax.config.jax_compilation_cache_dir == trino_tpu.COMPILE_CACHE_DIR
print("PLACED_OK")
"""
    p = _run_child(code, {"JAX_PLATFORMS": "tpu,cpu",
                          "JAX_COMPILATION_CACHE_DIR": cache})
    assert p.returncode == 0 and "PLACED_OK" in p.stdout, \
        p.stdout + p.stderr


def test_compile_cache_disabled_wins(tmp_path):
    code = """
import trino_tpu
assert trino_tpu.COMPILE_CACHE_DIR is None
from trino_tpu.exec.prewarm import compile_cache_stats
assert compile_cache_stats()["active"] is False
print("OFF_OK")
"""
    p = _run_child(code, {"JAX_ENABLE_COMPILATION_CACHE": "false",
                          "JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert p.returncode == 0 and "OFF_OK" in p.stdout, p.stdout + p.stderr


# ---------------------------------------------------------------------------
# compile-aware routing: cold -> host, warm -> device, bit-exact
# ---------------------------------------------------------------------------

@pytest.fixture
def coord():
    session = Session(default_schema="tiny")
    c = CoordinatorServer(session, max_concurrency=8).start()
    # deterministic router verdicts (same treatment as test_serving)
    c.state.dispatcher.serving.history = None
    session.history_store = None
    yield c
    c.stop()


def test_cold_routes_host_then_warm_routes_device(coord):
    eng = coord.state.prewarm
    assert eng is not None
    eng.enabled = True
    client = Client(coord.uri, user="prewarm", poll_interval_s=0.005)
    # rows-estimate alone would route this to device; only the cold
    # window may send it host
    client.execute("SET SESSION router_host_max_rows = 0")
    sql = "SELECT count(*) FROM region"
    fp = plan_fingerprint(sql)
    assert eng.device_cold(fp)
    r1 = client.execute(sql)
    assert client.query_info(r1.query_id)["route"] == "host"
    # the serving layer kicked a background warm; wait for it to land
    deadline = time.time() + 30
    while eng.device_cold(fp) and time.time() < deadline:
        time.sleep(0.05)
    assert not eng.device_cold(fp)
    r2 = client.execute(sql)
    assert client.query_info(r2.query_id)["route"] == "device"
    assert r1.rows == r2.rows        # bit-exact across the swap
    eng.enabled = False


def test_device_run_marks_fingerprint_warm(coord):
    eng = coord.state.prewarm
    eng.enabled = True
    client = Client(coord.uri, user="prewarm", poll_interval_s=0.005)
    # not host-eligible (grouped aggregation): runs on device even cold,
    # and the completed run itself closes the cold window
    sql = ("SELECT n_regionkey, count(*) FROM nation "
           "GROUP BY n_regionkey ORDER BY n_regionkey")
    fp = plan_fingerprint(sql)
    assert eng.device_cold(fp)
    r = client.execute(sql)
    assert client.query_info(r.query_id)["route"] == "device"
    assert not eng.device_cold(fp)
    eng.enabled = False


def test_status_and_jit_expose_prewarm_surface(coord):
    with urlopen(f"{coord.uri}/v1/status", timeout=10) as resp:
        status = json.loads(resp.read().decode())
    assert "compileCache" in status and "prewarm" in status
    assert status["prewarm"]["enabled"] is False
    assert status["compileCache"]["active"] in (True, False)
    with urlopen(f"{coord.uri}/v1/jit", timeout=10) as resp:
        jit = json.loads(resp.read().decode())
    assert "distinctShapes" in jit and "prewarm" in jit
    for k in ("prewarmedPrograms", "prewarmHits", "compileSecondsSaved"):
        assert k in jit["prewarm"]


def test_system_tables_expose_prewarm_columns(coord):
    client = Client(coord.uri, user="prewarm", poll_interval_s=0.005)
    r = client.execute("SELECT site, fingerprint, prewarmed, "
                       "prewarm_hits FROM system.runtime.jit_cache")
    assert r.columns[-2:] == ["prewarmed", "prewarm_hits"]
    r = client.execute("SELECT fingerprint, prewarm_rank, prewarm_score "
                       "FROM system.runtime.query_history")
    assert r.columns[-2:] == ["prewarm_rank", "prewarm_score"]


# ---------------------------------------------------------------------------
# joining-worker warm-manifest handshake
# ---------------------------------------------------------------------------

def test_joining_worker_pulls_manifest_and_warms(monkeypatch):
    session = Session(default_schema="tiny")
    coord = CoordinatorServer(session).start()
    try:
        coord.state.prewarm.enabled = True
        monkeypatch.setenv("TRINO_TPU_PREWARM", "1")
        # a tight budget keeps the shape warm to a handful of lattice
        # points so the join isn't slow in CI
        monkeypatch.setenv("TRINO_TPU_PREWARM_BUDGET_S", "5")
        w = WorkerServer("prewarm-w0", coord.uri,
                         announce_interval_s=0.1,
                         catalog=session.catalog).start()
        try:
            deadline = time.time() + 15
            while not coord.state.active_nodes() and \
                    time.time() < deadline:
                time.sleep(0.05)
            assert coord.state.active_nodes(), "worker never ACTIVE"
            assert w.prewarm_manifest is not None
            assert w.prewarm_manifest["shapes"][:2] == [1024, 1536]
            assert w.prewarm is not None
            assert w.prewarm.shape_warms > 0
            # the worker's status heartbeat reports its warm state
            req = Request(f"{w.uri}/v1/status",
                          headers=internal_headers())
            with urlopen(req, timeout=10) as resp:
                st = json.loads(resp.read().decode())
            assert st["prewarm"]["shapeWarms"] == w.prewarm.shape_warms
            assert "compileCache" in st
        finally:
            w.kill()
    finally:
        coord.stop()


def test_manifest_shape(coord):
    m = coord.state.prewarm.manifest()
    assert set(m) == {"enabled", "fingerprints", "shapes", "budget_s"}
    assert m["shapes"] == canonical_lattice()


# ---------------------------------------------------------------------------
# prewarm OFF is today's behavior exactly
# ---------------------------------------------------------------------------

def test_prewarm_off_is_inert(monkeypatch):
    monkeypatch.delenv("TRINO_TPU_PREWARM", raising=False)
    assert prewarm_enabled_by_env() is False
    s = Session(default_schema="tiny")
    eng = PrewarmEngine(session=s)
    assert eng.enabled is False
    assert s.properties["prewarm_chunks"] is False   # no property flip
    assert eng.device_cold("deadbeef") is False      # no cold signal
    assert eng.maybe_start() is False                # no threads
    eng.ensure_warming("deadbeef", "SELECT 1")
    assert eng._threads == []


def test_prewarm_chunks_bit_exact():
    s = Session(default_schema="tiny")
    s.executor.enable_fact_cache = False
    s.execute("SET SESSION spill_chunk_rows = 8192")
    sql = ("SELECT l_returnflag, count(*), sum(l_extendedprice) "
           "FROM lineitem GROUP BY l_returnflag ORDER BY l_returnflag")
    baseline = s.execute(sql).rows
    assert s.executor.chunk_spans["chunks"] > 1      # chunked path ran
    s.execute("SET SESSION prewarm_chunks = true")
    warmed = s.execute(sql).rows
    assert warmed == baseline


# ---------------------------------------------------------------------------
# bench --cold-start regression series
# ---------------------------------------------------------------------------

def _cold_round(tmp_path, name, q6_cold, q6_steady=50.0):
    recs = [{"query": q, "cold_ms": q6_cold, "steady_ms": q6_steady,
             "ratio": round(q6_cold / q6_steady, 2)}
            for q in ("q3", "q5", "q6")]
    (tmp_path / name).write_text(json.dumps(
        {"metric": "cold_start", "records": recs, "passed": True}))


def test_load_bench_round_parses_cold_record(tmp_path):
    import bench
    _cold_round(tmp_path, "BENCH_cold_r01.json", 120.0, 60.0)
    cfg = bench.load_bench_round(str(tmp_path / "BENCH_cold_r01.json"))
    assert cfg["cold_q6"] == 120.0
    assert cfg["cold_q6_ratio"] == 2.0
    assert cfg["cold_q3"] == 120.0 and cfg["cold_q5"] == 120.0


def test_check_regressions_gates_cold_series(tmp_path, monkeypatch):
    import bench
    _cold_round(tmp_path, "BENCH_cold_r01.json", 100.0)
    _cold_round(tmp_path, "BENCH_cold_r02.json", 110.0)
    _cold_round(tmp_path, "BENCH_cold_r03.json", 95.0)
    monkeypatch.chdir(tmp_path)
    assert bench.main(["--check-regressions"]) == 0
    # injected regression: the cold wall blows up 9x in a new round
    _cold_round(tmp_path, "BENCH_cold_r04.json", 900.0)
    assert bench.main(["--check-regressions"]) == 1
