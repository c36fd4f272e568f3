#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served SQL path still runs
on the chip.

One process (a chip belongs to one process): an in-process
`CoordinatorServer` and `WorkerServer`, a `Client` over HTTP, and TPC-H
q6, q1, q3 and q18 against the `tpch` connector's own generator at sf10
(60M-row lineitem). Every session property stays at its default, so
a statement runs the way a user gets it. Each answer is compared with a
plain numpy reference computed here from the same generated columns,
independent of the engine.

    python chip_smoke.py            one chip: the four queries
    python chip_smoke.py --chips 4  four chips: q3 at sf10 over a Mesh of
                                    the four devices vs one device vs
                                    numpy, and nothing else

Exits non-zero, without a result line, when JAX finds no TPU; it never
sets JAX_PLATFORMS and never falls back. Any phase's exception ends the
run non-zero. The last line of standard output is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}.

The readings printed on earlier lines (seconds, compile counts, peak
bytes) are smoke readings, not benchmark results.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

Q6 = """
SELECT sum(l_extendedprice * l_discount) AS revenue
FROM {s}.lineitem
WHERE l_shipdate >= DATE '1994-01-01'
  AND l_shipdate < DATE '1994-01-01' + INTERVAL '1' YEAR
  AND l_discount BETWEEN 0.05 AND 0.07
  AND l_quantity < 24
"""

Q1 = """
SELECT l_returnflag, l_linestatus,
       sum(l_quantity) AS sum_qty,
       sum(l_extendedprice) AS sum_base_price,
       sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price,
       sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) AS sum_charge,
       avg(l_quantity) AS avg_qty,
       avg(l_extendedprice) AS avg_price,
       avg(l_discount) AS avg_disc,
       count(*) AS count_order
FROM {s}.lineitem
WHERE l_shipdate <= DATE '1998-12-01' - INTERVAL '90' DAY
GROUP BY l_returnflag, l_linestatus
ORDER BY l_returnflag, l_linestatus
"""

Q3 = """
SELECT l_orderkey,
       sum(l_extendedprice * (1 - l_discount)) AS revenue,
       o_orderdate, o_shippriority
FROM {s}.customer, {s}.orders, {s}.lineitem
WHERE c_mktsegment = 'BUILDING'
  AND c_custkey = o_custkey
  AND l_orderkey = o_orderkey
  AND o_orderdate < DATE '1995-03-15'
  AND l_shipdate > DATE '1995-03-15'
GROUP BY l_orderkey, o_orderdate, o_shippriority
ORDER BY revenue DESC, o_orderdate, l_orderkey
LIMIT 10
"""


def _benchmark_q18():
    """benchmark/queries/q18.py: Q18's text, its validation parameter
    and its plain numpy reference have one copy, the benchmark's."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "benchmark_q18", os.path.join(os.path.dirname(os.path.abspath(
            __file__)), "benchmark", "queries", "q18.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_Q18 = _benchmark_q18()
# with TPC-H's validation value (2.4.18.4), as the benchmark's warm-up
# sends it; the schema stays a slot, as in the three texts above
Q18 = _Q18.render(_Q18.VALIDATION, "{s}")

TABLES = ("customer", "orders", "lineitem")


def say(msg: str) -> None:
    print(f"[smoke {time.strftime('%H:%M:%S')}] {msg}", flush=True)


# ---------------------------------------------------------------------------
# plain numpy references over the generated columns (decimal(12,2) columns
# hold integers scaled by 100, dates are days since 1970-01-01, varchar
# columns are dictionary codes)
# ---------------------------------------------------------------------------

def col(table, name):
    return np.asarray(table.columns[table.schema.index_of(name)])


def days(s: str) -> int:
    return int((np.datetime64(s) - np.datetime64("1970-01-01")).astype(int))


def numpy_q6(t):
    li = t["lineitem"]
    ship, disc = col(li, "l_shipdate"), col(li, "l_discount")
    qty, price = col(li, "l_quantity"), col(li, "l_extendedprice")
    m = (ship >= days("1994-01-01")) & (ship < days("1995-01-01")) & \
        (disc >= 5) & (disc <= 7) & (qty < 2400)
    return [(int((price[m] * disc[m]).sum()) / 1e4,)]


def numpy_q1(t):
    li = t["lineitem"]
    m = col(li, "l_shipdate") <= days("1998-12-01") - 90
    rf, ls = col(li, "l_returnflag")[m], col(li, "l_linestatus")[m]
    qty, price = col(li, "l_quantity")[m], col(li, "l_extendedprice")[m]
    disc, tax = col(li, "l_discount")[m], col(li, "l_tax")[m]
    rf_pool = li.schema.field("l_returnflag").dictionary
    ls_pool = li.schema.field("l_linestatus").dictionary
    gid = rf.astype(np.int64) * len(ls_pool) + ls
    n_groups = len(rf_pool) * len(ls_pool)
    disc_price = price * (100 - disc)                 # scaled 1e4
    charge = disc_price * (100 + tax)                 # scaled 1e6

    def gsum(v):                # exact int64 sums, one pass per group
        return np.array([int(v[gid == g].sum()) for g in range(n_groups)])
    cnt = np.bincount(gid, minlength=n_groups)
    s_qty, s_price = gsum(qty), gsum(price)
    s_disc_price, s_charge, s_disc = gsum(disc_price), gsum(charge), \
        gsum(disc)

    def avg(total, n):          # avg(decimal(12,2)) keeps scale 2, HALF_UP
        return int((2 * int(total) + int(n)) // (2 * int(n))) / 1e2
    rows = []
    for g in range(n_groups):
        if cnt[g] == 0:
            continue
        rows.append((rf_pool[g // len(ls_pool)], ls_pool[g % len(ls_pool)],
                     s_qty[g] / 1e2, s_price[g] / 1e2,
                     s_disc_price[g] / 1e4, s_charge[g] / 1e6,
                     avg(s_qty[g], cnt[g]), avg(s_price[g], cnt[g]),
                     avg(s_disc[g], cnt[g]), int(cnt[g])))
    return sorted(rows)


def _filtered_orders_q3(t):
    cust, orders = t["customer"], t["orders"]
    seg = cust.schema.field("c_mktsegment").dictionary.index("BUILDING")
    building = np.zeros(int(col(cust, "c_custkey").max()) + 1, dtype=bool)
    building[col(cust, "c_custkey")[col(cust, "c_mktsegment") == seg]] = True
    od = col(orders, "o_orderdate")
    keep = (od < days("1995-03-15")) & building[col(orders, "o_custkey")]
    return (col(orders, "o_orderkey")[keep], od[keep],
            col(orders, "o_shippriority")[keep])


def numpy_q3(t):
    li = t["lineitem"]
    okey, odate, oprio = _filtered_orders_q3(t)
    order = np.argsort(okey, kind="stable")
    okey, odate, oprio = okey[order], odate[order], oprio[order]
    lm = col(li, "l_shipdate") > days("1995-03-15")
    lk = col(li, "l_orderkey")[lm]
    pos = np.clip(np.searchsorted(okey, lk), 0, len(okey) - 1)
    hit = okey[pos] == lk
    rev = (col(li, "l_extendedprice")[lm][hit] *
           (100 - col(li, "l_discount")[lm][hit]))     # scaled 1e4, int64
    # per-order sums stay far below 2^53, so float64 weights are exact
    sums = np.bincount(pos[hit], weights=rev,
                       minlength=len(okey)).astype(np.int64)
    live = np.nonzero(sums > 0)[0]
    top = live[np.lexsort((okey[live], odate[live], -sums[live]))][:10]
    return [(int(okey[i]), sums[i] / 1e4, int(odate[i]), int(oprio[i]))
            for i in top]


def numpy_q18(t):
    tables = {}
    for name, names in _Q18.TABLES.items():
        pools = {c: t[name].schema.field(c).dictionary for c in names}
        tables[name] = {
            "columns": {c: col(t[name], c) for c in names},
            "dictionary": {c: p for c, p in pools.items() if p is not None}}
    return [(name, custkey, orderkey, orderdate, total / 1e2, qty / 1e2)
            for name, custkey, orderkey, orderdate, total, qty in
            _Q18.reference(tables, _Q18.VALIDATION)]


def _as_day(v) -> int:
    return days(str(v)) if not isinstance(v, (int, np.integer)) else int(v)


def check_rows(name: str, got_rows, want_rows, date_cols=()) -> None:
    """Exact on ints and strings, 1e-9 relative on decimals and averages
    (the protocol carries decimals as strings; float() of them is exact
    to far below that)."""
    assert len(got_rows) == len(want_rows), \
        f"{name}: {len(got_rows)} rows, reference has {len(want_rows)}"
    for r, (got, want) in enumerate(zip(got_rows, want_rows)):
        assert len(got) == len(want), (name, r, got, want)
        for c, (g, w) in enumerate(zip(got, want)):
            if c in date_cols:
                ok = _as_day(g) == w
            elif isinstance(w, str):
                ok = g == w
            elif isinstance(w, (int, np.integer)):
                ok = int(g) == int(w)
            else:
                ok = abs(float(g) - float(w)) <= 1e-9 * max(1.0, abs(w))
            assert ok, f"{name}: row {r} col {c}: got {g!r}, want {w!r}"


QUERIES = (
    # name, sql ({s} = catalog.schema), reference, date columns
    ("q6", Q6, numpy_q6, ()),
    ("q1", Q1, numpy_q1, ()),
    ("q3", Q3, numpy_q3, (2,)),
    ("q18", Q18, numpy_q18, (3,)),
)

SCHEMA = "sf10"     # the tpch connector's schema every phase runs at


# ---------------------------------------------------------------------------
# the device, the caches, the data
# ---------------------------------------------------------------------------

def require_tpu():
    """The device as JAX reports it; non-zero exit when it is no TPU."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py: needs a TPU and JAX found {dev.platform} "
              f"({dev.device_kind}); nothing was run", file=sys.stderr,
              flush=True)
        sys.exit(2)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


class CacheCounter:
    """Persistent compile-cache hits and misses, as JAX itself reports
    them (jax.monitoring events)."""

    def __init__(self):
        self.hits = self.misses = 0
        from jax import monitoring
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def peak_bytes() -> int:
    """Read straight from the device: a backend that cannot report its
    peak is an error here, not a zero."""
    import jax
    return max(int(d.memory_stats()["peak_bytes_in_use"])
               for d in jax.local_devices())


def generate(catalog, schema: str):
    t0 = time.monotonic()
    tables = {t: catalog.get_table("tpch", schema, t) for t in TABLES}
    say(f"generate {schema}: {time.monotonic() - t0:.1f}s, " +
        ", ".join(f"{t}={tables[t].num_rows:,}" for t in TABLES))
    return tables


def ingest_probe(tables) -> None:
    """Host->device seconds for the lineitem columns q6 scans, measured
    outside the served path and freed again (the served path's own
    ingest is inside each cold run)."""
    import jax
    li = tables["lineitem"]
    cols = [np.ascontiguousarray(col(li, c)) for c in
            ("l_shipdate", "l_discount", "l_quantity", "l_extendedprice")]
    nbytes = sum(c.nbytes for c in cols)
    t0 = time.monotonic()
    on_dev = jax.block_until_ready([jax.device_put(c) for c in cols])
    dt = time.monotonic() - t0
    say(f"ingest probe: {nbytes / 1e6:.0f} MB host->device in {dt:.2f}s "
        f"({nbytes / 1e6 / max(dt, 1e-9):.0f} MB/s)")
    del on_dev


# ---------------------------------------------------------------------------
# one chip: client -> coordinator -> worker -> device
# ---------------------------------------------------------------------------

def run_served(cache: CacheCounter, sch: str = SCHEMA) -> None:
    """`sch` is an argument so that the logic can be rehearsed off-chip
    at a small scale; main() always runs SCHEMA."""
    from trino_tpu.client.client import Client
    from trino_tpu.exec.profiler import RECORDER
    from trino_tpu.exec.session import Session
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.worker import WorkerServer

    session = Session()
    tables = generate(session.catalog, sch)
    ingest_probe(tables)

    coord = CoordinatorServer(session).start()
    # one process, one catalog: the worker scans the tables the
    # coordinator planned against instead of generating them again
    worker = WorkerServer("smoke-w0", coord.uri, announce_interval_s=0.5,
                          catalog=session.catalog).start()
    try:
        deadline = time.monotonic() + 30
        while not coord.state.active_nodes():
            assert time.monotonic() < deadline, "worker never announced"
            time.sleep(0.05)
        # the client's own patience (not a session property): a cold
        # sf10 query spends minutes in the TPU compiler
        client = Client(coord.uri, user="chip-smoke", timeout_s=900.0)
        sched = coord.state.dispatcher.scheduler
        executors = {"coordinator": session.executor,
                     "worker": worker.task_manager._executor}
        for name, sql, reference, date_cols in QUERIES:
            sql = sql.format(s=f"tpch.{sch}")
            t0 = time.monotonic()
            want = reference(tables)
            say(f"{name} {sch}: numpy reference {time.monotonic() - t0:.1f}s"
                f", {len(want)} rows")
            for run in ("cold", "warm"):
                # the warm run repeats the SQL with the coordinator's
                # exchange spool dropped, so every split runs on the
                # device again (compiled programs and data stay)
                sched.spool.clear()
                before = RECORDER.totals()["compiles"]
                t0 = time.monotonic()
                res = client.execute(sql)
                secs = time.monotonic() - t0
                info = client.query_info(res.query_id)
                route = "worker tasks" if info["distributed"] \
                    else info["route"]
                # routed to the device: split tasks on the worker's
                # device executor, or the coordinator's device route —
                # never the host interpreter, a cache or a micro-batch,
                # and never the coordinator's local re-run that the
                # scheduler degrades to when a worker task fails or
                # times out (it would hide the failure behind a right
                # answer)
                fallback = info.get("fallbackReason") or ""
                assert (info["distributed"] or info["route"] == "device") \
                    and not fallback.startswith("task failure"), \
                    f"{name}: not on the device: route={info['route']!r} " \
                    f"({info.get('routeReason')}), fallback={fallback!r}"
                check_rows(f"{name} {run}", res.rows, want, date_cols)
                strategies = {k: dict(ex.strategy_decisions)
                              for k, ex in executors.items()
                              if ex.strategy_decisions}
                say(f"{name} {sch} {run}: {secs:.2f}s, {len(res.rows)} rows"
                    f" match numpy; route={route}"
                    f" (reason={info.get('routeReason')!r},"
                    f" fallback={fallback!r});"
                    f" compiles={RECORDER.totals()['compiles'] - before};"
                    f" strategies={strategies};"
                    f" peak_bytes_in_use={peak_bytes()}")
                expect_strategies(name, strategies)
        say(f"compile cache at {compile_cache_dir()}: "
            f"{cache.hits} hits, {cache.misses} misses; "
            f"{RECORDER.totals()['compiles']} jit compiles recorded")
    finally:
        worker.stop()
        coord.stop()


def expect_strategies(name: str, strategies: dict) -> None:
    """What the defaults must have picked on the chip."""
    aggs = {per_ex.get("AggregateNode") for per_ex in strategies.values()}
    if name == "q6":
        assert "global" in aggs, (name, strategies)
    elif name == "q1":
        assert "direct" in aggs, (name, strategies)
    else:
        assert "sort" in aggs, (name, strategies)
        joins = {per_ex.get("JoinNode") for per_ex in strategies.values()}
        assert joins - {None}, f"{name}: no join strategy recorded"


def compile_cache_dir():
    import trino_tpu
    return trino_tpu.COMPILE_CACHE_DIR


# ---------------------------------------------------------------------------
# four chips: q3 over a Mesh of the four devices vs one device vs numpy
# ---------------------------------------------------------------------------

def run_four_chips(schema: str) -> None:
    """One cold run each, at the same time: the one-device session spends
    most of its cold run in the compiler on the host, which the mesh
    half's device time hides — a four-chip host is charged four times a
    second, so this mode runs nothing twice and nothing in sequence that
    need not be."""
    from concurrent.futures import ThreadPoolExecutor

    import jax
    from trino_tpu.exec.session import Session
    from trino_tpu.parallel.dist_executor import MeshExecutor

    n_dev = len(jax.devices())
    assert n_dev == 4, f"--chips 4 needs four devices, JAX found {n_dev}"
    one = Session(default_schema=schema)
    tables = generate(one.catalog, schema)
    mesh_s = Session(catalog=one.catalog, default_schema=schema)
    mesh_s.execute("SET SESSION distributed = true")
    ex = mesh_s.executor
    assert isinstance(ex, MeshExecutor) and ex.n_shards == 4, \
        f"distributed session runs {type(ex).__name__}"
    assert set(ex.mesh.devices.flat) == set(jax.devices())
    sql = Q3.format(s=f"tpch.{schema}")
    # fenced per-operator times (EXPLAIN ANALYZE's instrument), so each
    # cold run says where its seconds went without a second run
    for sess in (one, mesh_s):
        sess.execute("SET SESSION enable_profiling = true")

    def cold_run(label: str, sess):
        t0 = time.monotonic()
        res = sess.execute(sql)
        secs = time.monotonic() - t0
        slowest = sorted(sess.executor.node_stats.values(),
                         reverse=True)[:6]
        say(f"q3 {schema} {label} cold: {secs:.2f}s, "
            f"{len(res.rows)} rows; "
            f"strategies={dict(sess.executor.strategy_decisions)}; "
            f"peak_bytes_in_use={peak_bytes()}; slowest plan nodes, "
            f"inclusive (wall s, rows out, device s, host s, compile s): "
            + "; ".join(f"({w:.1f}, {r:,}, {d:.1f}, {h:.1f}, {c:.1f})"
                        for w, r, d, h, c in slowest))
        return res.rows

    with ThreadPoolExecutor(max_workers=2) as pool:
        on_mesh = pool.submit(cold_run, "mesh of 4", mesh_s)
        on_one = pool.submit(cold_run, "one device", one)
        t0 = time.monotonic()
        want = numpy_q3(tables)
        say(f"q3 {schema}: numpy reference {time.monotonic() - t0:.1f}s")
        mesh_rows = on_mesh.result()
        # every device holds a shard of the scanned fact table (code that
        # has only met virtual devices may put everything on the first)
        scanned = [ex.resident.get(key)[1] for key in ex.resident.keys()
                   if key[3] == "lineitem" and key[4] is None]
        assert scanned, "the mesh executor kept no scanned lineitem column"
        shards = scanned[0].addressable_shards
        holders = {s.device for s in shards}
        rows = [s.data.shape[0] for s in shards]
        assert holders == set(jax.devices()) and min(rows) > 0, \
            f"scan shards sit on {sorted(str(d) for d in holders)}: {rows}"
        say(f"scanned lineitem batch: {len(shards)} shards on "
            f"{len(holders)} devices, rows per shard {rows}")
        check_rows("q3 mesh of 4", mesh_rows, want, (2,))
        one_rows = on_one.result()
    check_rows("q3 one device", one_rows, want, (2,))
    assert mesh_rows == one_rows, \
        "q3 over four devices differs from q3 on one device"
    one_dev = {d for key in one.executor.resident.keys()
               if key[0] == "column" and key[4] is None
               for d in one.executor.resident.get(key)[1].devices()}
    assert len(one_dev) == 1, f"one-device session used {one_dev}"
    say(f"one-device session scanned onto {sorted(str(d) for d in one_dev)}"
        f"; mesh = one device = numpy on {len(want)} rows")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only q3 at sf10 over a Mesh of four devices "
                         "vs one device vs numpy")
    args = ap.parse_args(argv)
    if not __debug__:
        sys.exit("chip_smoke.py checks with assert statements: "
                 "run it without -O")

    device = require_tpu()
    # a fresh data cache of its own: nothing stale (generated tables,
    # routing history, persisted decisions) stands in for this run
    data_cache = tempfile.mkdtemp(prefix="chip_smoke_data_")
    os.environ["TRINO_TPU_DATA_CACHE"] = data_cache
    try:
        cache = CacheCounter()
        t0 = time.monotonic()
        say(f"device: {device}; data cache {data_cache}; "
            f"compile cache {compile_cache_dir()}")
        if args.chips == 4:
            run_four_chips(SCHEMA)
        else:
            assert device["count"] == 1, \
                f"one chip expected, JAX found {device['count']} " \
                f"(use --chips 4 on a four-chip host)"
            run_served(cache)
        say(f"compile cache: {cache.hits} hits, {cache.misses} misses; "
            f"total {time.monotonic() - t0:.0f}s")
    finally:
        shutil.rmtree(data_cache, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
