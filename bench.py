"""Driver benchmark: prints ONE JSON line.

Round-2 workloads — END-TO-END (SQL text -> host result) per the
round-1 verdict, BASELINE.md configs 2-4:

  q6_sf1   : TPC-H q6 at SF1   — scan + filter/project + global agg
  q3_sf10  : TPC-H q3 at SF10  — 3-way join + group-by, single chip
  q5_sf100 : TPC-H q5-shaped at SF100 — 6-way join; lineitem (600M rows,
             ~19GB) exceeds HBM, so it streams through the bounded-memory
             chunked driver (exec/chunked.py). Only q5's columns are
             generated (dbgen formulas; full SF100 generation needs >75GB
             host RAM) — the "q5-shaped SF100 run".

Methodology (testing/trino-benchto-benchmarks/.../tpch.yaml: prewarm then
measured runs, concurrency 1): per config we report cold (first run incl.
XLA compile + host->device ingest), steady-state median end-to-end wall
(parse -> plan -> execute -> decode), and an identical-results check
against the CPU baseline. Scan data is device-resident in steady state
for EVERY config — configs 2-3 via the int64 scan cache, config 4 via
the narrowed fact-column cache (exec/device_cache.py: int32/int8 range-
compressed columns, 7.8 GB in HBM for SF100 q5's lineitem) — matching
the reference benchmarks reading in-memory pages; the chunked driver
still bounds per-chunk intermediates. Baselines are single-node
vectorized numpy implementations of the same queries (the stand-in for
the single-node Java operator pipeline). The default mode measures a
chip: it exits non-zero when JAX finds no TPU and never writes a CPU
time under a `tpu_*` key.

Config order is information value (round-3 verdict): q5 SF100 first so
a driver timeout can't starve it. vs_baseline = cpu_ms / tpu_steady_ms
for the headline config (q3_sf10 when present).
"""

import json
import os
import statistics
import sys
import threading
import time

import numpy as np

PREWARM = 1
RUNS = 3
# Hard self-budget, kept WELL below any plausible driver timeout (round-2's
# single end-of-run emit was erased by an rc=124 driver kill).  A watchdog
# thread force-emits whatever has finished and exits before this expires.
BUDGET_S = float(os.environ.get("TRINO_TPU_BENCH_BUDGET_S", 780))
T0 = time.monotonic()

_emit_lock = threading.Lock()
_detail = {}


def emit(final=False):
    """Print the CUMULATIVE result as one complete JSON line.

    Called after EVERY finished config (not only at exit) so that a driver
    timeout preserves every config that completed.  The driver records the
    last JSON line it sees; each emission is a full, self-contained record.
    """
    with _emit_lock:
        headline = _detail.get("q3_sf10") or _detail.get("q5_sf100") \
            or _detail.get("q6_sf1")
        if headline is None:
            return
        print(json.dumps({
            "metric": "tpch_e2e_sql_to_result_wall_ms",
            "value": headline["tpu_steady_ms"],
            "unit": "ms",
            "vs_baseline": headline["speedup"],
            "detail": dict(_detail, elapsed_s=round(time.monotonic() - T0, 1),
                           final=final),
        }), flush=True)


def _watchdog():
    deadline = T0 + BUDGET_S - 10
    while time.monotonic() < deadline:
        time.sleep(min(5.0, max(0.1, deadline - time.monotonic())))
    _detail["watchdog"] = "budget expired; emitting finished configs"
    emit(final=True)
    sys.stdout.flush()
    os._exit(0)

Q6 = """
SELECT sum(l_extendedprice * l_discount) AS revenue
FROM lineitem
WHERE l_shipdate >= DATE '1994-01-01'
  AND l_shipdate < DATE '1994-01-01' + INTERVAL '1' YEAR
  AND l_discount BETWEEN 0.05 AND 0.07
  AND l_quantity < 24
"""

Q3 = """
SELECT l_orderkey, sum(l_extendedprice * (1 - l_discount)) AS revenue,
       o_orderdate, o_shippriority
FROM customer, orders, lineitem
WHERE c_mktsegment = 'BUILDING'
  AND c_custkey = o_custkey
  AND l_orderkey = o_orderkey
  AND o_orderdate < DATE '1995-03-15'
  AND l_shipdate > DATE '1995-03-15'
GROUP BY l_orderkey, o_orderdate, o_shippriority
ORDER BY revenue DESC, o_orderdate, l_orderkey
LIMIT 10
"""

Q5 = """
SELECT n_name, sum(l_extendedprice * (1 - l_discount)) AS revenue
FROM customer, orders, lineitem, supplier, nation, region
WHERE c_custkey = o_custkey
  AND l_orderkey = o_orderkey
  AND l_suppkey = s_suppkey
  AND c_nationkey = s_nationkey
  AND s_nationkey = n_nationkey
  AND n_regionkey = r_regionkey
  AND r_name = 'ASIA'
  AND o_orderdate >= DATE '1994-01-01'
  AND o_orderdate < DATE '1994-01-01' + INTERVAL '1' YEAR
GROUP BY n_name
ORDER BY revenue DESC
"""


# ---------------------------------------------------------------------------
# CPU baselines: single-node vectorized numpy over the same host arrays
# ---------------------------------------------------------------------------

def col(table, name):
    return np.asarray(table.columns[table.schema.index_of(name)])


def _days(s):
    return (np.datetime64(s) - np.datetime64("1970-01-01")).astype(int)


def numpy_q6(tables):
    li = tables["lineitem"]
    ship = col(li, "l_shipdate")
    disc = col(li, "l_discount")
    qty = col(li, "l_quantity")
    price = col(li, "l_extendedprice")
    m = (ship >= _days("1994-01-01")) & (ship < _days("1995-01-01")) & \
        (disc >= 5) & (disc <= 7) & (qty < 2400)
    return int((price[m] * disc[m]).sum())


def numpy_q3(tables):
    cust, orders, li = tables["customer"], tables["orders"], \
        tables["lineitem"]
    seg_pool = cust.schema.field("c_mktsegment").dictionary
    seg_code = seg_pool.index("BUILDING")
    ck = col(cust, "c_custkey")[col(cust, "c_mktsegment") == seg_code]
    cutoff = _days("1995-03-15")
    od = col(orders, "o_orderdate")
    om = od < cutoff
    okey, ocust = col(orders, "o_orderkey")[om], \
        col(orders, "o_custkey")[om]
    od_f, oprio = od[om], col(orders, "o_shippriority")[om]
    ck_sorted = np.sort(ck)
    pos = np.clip(np.searchsorted(ck_sorted, ocust), 0,
                  len(ck_sorted) - 1)
    keep = ck_sorted[pos] == ocust
    okey, od_f, oprio = okey[keep], od_f[keep], oprio[keep]
    order_o = np.argsort(okey, kind="stable")
    okey_s, od_s = okey[order_o], od_f[order_o]
    lk = col(li, "l_orderkey")
    lm = col(li, "l_shipdate") > cutoff
    lk, price, disc = lk[lm], col(li, "l_extendedprice")[lm], \
        col(li, "l_discount")[lm]
    pos = np.clip(np.searchsorted(okey_s, lk), 0, len(okey_s) - 1)
    keep = okey_s[pos] == lk
    lk = lk[keep]
    rev = price[keep] * (100 - disc[keep])     # scaled 1e4
    uniq, inv = np.unique(lk, return_inverse=True)
    sums = np.bincount(inv, weights=rev.astype(np.float64))
    upos = np.clip(np.searchsorted(okey_s, uniq), 0, len(okey_s) - 1)
    order = np.lexsort((uniq, od_s[upos], -sums))
    top = order[:10]
    return [(int(uniq[i]), float(sums[i]) / 1e4) for i in top]


def numpy_q5(tables, chunk=1 << 26):
    nat, reg = tables["nation"], tables["region"]
    sup, cust = tables["supplier"], tables["customer"]
    orders, li = tables["orders"], tables["lineitem"]
    r_pool = reg.schema.field("r_name").dictionary
    asia = r_pool.index("ASIA")
    asia_regionkeys = col(reg, "r_regionkey")[col(reg, "r_name") == asia]
    asia_nations = col(nat, "n_nationkey")[
        np.isin(col(nat, "n_regionkey"), asia_regionkeys)]
    od = col(orders, "o_orderdate")
    om = (od >= _days("1994-01-01")) & (od < _days("1995-01-01"))
    okey, ocust = col(orders, "o_orderkey")[om], \
        col(orders, "o_custkey")[om]
    c_nation = col(cust, "c_nationkey")      # custkey dense 1..N
    o_nation = c_nation[ocust - 1]
    ok = np.isin(o_nation, asia_nations)
    okey, o_nation = okey[ok], o_nation[ok]
    order_o = np.argsort(okey, kind="stable")
    okey_s, onat_s = okey[order_o], o_nation[order_o]
    s_nation = col(sup, "s_nationkey")
    acc = np.zeros(25, dtype=np.float64)
    n = li.num_rows
    lk_all, ls_all = col(li, "l_orderkey"), col(li, "l_suppkey")
    price_all, disc_all = col(li, "l_extendedprice"), \
        col(li, "l_discount")
    for start in range(0, n, chunk):
        lk = lk_all[start:start + chunk]
        ls = ls_all[start:start + chunk]
        price = price_all[start:start + chunk]
        disc = disc_all[start:start + chunk]
        pos = np.clip(np.searchsorted(okey_s, lk), 0, len(okey_s) - 1)
        keep = okey_s[pos] == lk
        snat = s_nation[ls[keep] - 1]
        match = snat == onat_s[pos[keep]]
        rev = (price[keep][match] * (100 - disc[keep][match])
               ).astype(np.float64)
        acc += np.bincount(snat[match], weights=rev, minlength=25)
    n_pool = nat.schema.field("n_name").dictionary
    name_of = {int(k): n_pool[int(c)]
               for k, c in zip(col(nat, "n_nationkey"),
                               col(nat, "n_name"))}
    return [(name_of[i], acc[i] / 1e4)
            for i in np.argsort(-acc) if acc[i] > 0]


# ---------------------------------------------------------------------------
# q5-shaped SF100 generation (pruned columns, dbgen formulas)
# ---------------------------------------------------------------------------

def q5_tables(scale: float, seed: int = 19920101):
    """The q5 columns only, same shapes/distributions as datagen.py.
    Persisted through the on-disk table cache (connectors/diskcache.py)
    so generation cost is paid once per machine, not per bench run."""
    from trino_tpu.connectors.diskcache import load_table, save_table
    from trino_tpu.connectors.tpch.datagen import TableData as _TD
    dataset = f"bench_q5_sf{scale:g}_s{seed}"
    names = ["region", "nation", "supplier", "customer", "orders",
             "lineitem"]
    cached = {}
    for nm in names:
        t = load_table(dataset, nm, _TD)
        if t is None:
            break
        cached[nm] = t
    else:
        return cached
    tables = _q5_tables_generate(scale, seed)
    for t in tables.values():
        save_table(dataset, t)
    return tables


def _q5_tables_generate(scale: float, seed: int = 19920101):
    from trino_tpu.batch import Field, Schema
    from trino_tpu.connectors.tpch.datagen import (ENDDATE, NATIONS,
                                                   REGIONS, STARTDATE,
                                                   TableData, _codes_for,
                                                   retail_price_cents)
    from trino_tpu.types import BIGINT, DATE, VARCHAR, decimal
    rng = np.random.default_rng(seed)
    t = {}
    t["region"] = TableData(
        "region", Schema.of(Field("r_regionkey", BIGINT),
                            Field("r_name", VARCHAR,
                                  dictionary=tuple(sorted(REGIONS)))),
        [np.arange(5, dtype=np.int64),
         _codes_for(REGIONS, sorted(REGIONS))],
        primary_key=("r_regionkey",))
    n_names = [n for n, _ in NATIONS]
    t["nation"] = TableData(
        "nation", Schema.of(Field("n_nationkey", BIGINT),
                            Field("n_name", VARCHAR,
                                  dictionary=tuple(sorted(n_names))),
                            Field("n_regionkey", BIGINT)),
        [np.arange(25, dtype=np.int64),
         _codes_for(n_names, sorted(n_names)),
         np.array([r for _, r in NATIONS], dtype=np.int64)],
        primary_key=("n_nationkey",))
    n_supp = int(scale * 10_000)
    t["supplier"] = TableData(
        "supplier", Schema.of(Field("s_suppkey", BIGINT),
                              Field("s_nationkey", BIGINT)),
        [np.arange(1, n_supp + 1, dtype=np.int64),
         rng.integers(0, 25, n_supp).astype(np.int64)],
        primary_key=("s_suppkey",))
    n_cust = int(scale * 150_000)
    t["customer"] = TableData(
        "customer", Schema.of(Field("c_custkey", BIGINT),
                              Field("c_nationkey", BIGINT)),
        [np.arange(1, n_cust + 1, dtype=np.int64),
         rng.integers(0, 25, n_cust).astype(np.int64)],
        primary_key=("c_custkey",))
    n_ord = int(scale * 1_500_000)
    idx = np.arange(n_ord, dtype=np.int64)
    orderkey = (idx // 8) * 32 + (idx % 8) + 1
    m_active = max(1, n_cust - n_cust // 3)
    j = rng.integers(1, m_active + 1, n_ord).astype(np.int64)
    o_custkey = np.clip(j + (j - 1) // 2, 1, n_cust)
    o_orderdate = rng.integers(STARTDATE, ENDDATE - 151 + 1,
                               n_ord).astype(np.int32)
    t["orders"] = TableData(
        "orders", Schema.of(Field("o_orderkey", BIGINT),
                            Field("o_custkey", BIGINT),
                            Field("o_orderdate", DATE)),
        [orderkey, o_custkey, o_orderdate],
        primary_key=("o_orderkey",))
    lines_per_order = rng.integers(1, 8, n_ord)
    l_orderkey = np.repeat(orderkey, lines_per_order)
    n_li = len(l_orderkey)
    l_partkey = rng.integers(1, int(scale * 200_000) + 1,
                             n_li).astype(np.int64)
    li_i = rng.integers(0, 4, n_li).astype(np.int64)
    l_suppkey = ((l_partkey + li_i * (n_supp // 4 + (l_partkey - 1)
                                      // n_supp)) % n_supp) + 1
    l_quantity = rng.integers(1, 51, n_li).astype(np.int64)
    l_extendedprice = l_quantity * retail_price_cents(l_partkey)
    del l_partkey, li_i, l_quantity
    l_discount = rng.integers(0, 11, n_li).astype(np.int64)
    d122 = decimal(12, 2)
    t["lineitem"] = TableData(
        "lineitem", Schema.of(Field("l_orderkey", BIGINT),
                              Field("l_suppkey", BIGINT),
                              Field("l_extendedprice", d122),
                              Field("l_discount", d122)),
        [l_orderkey, l_suppkey, l_extendedprice, l_discount])
    return t


class BenchConnector:
    """Prebuilt q5-shaped tables under one schema."""
    name = "bench"

    def __init__(self, tables, schema):
        self._tables = tables
        self._schema = schema
        self._cache = {schema: tables}         # stats-probe shape

    def scale_for_schema(self, schema):
        return schema

    def schema_names(self):
        return [self._schema]

    def table_names(self, schema):
        return sorted(self._tables)

    def get_table(self, schema, table):
        return self._tables[table]


# ---------------------------------------------------------------------------
# --scan-micro: zone-map pruning + prefetch-pipeline scan-path microbench
# ---------------------------------------------------------------------------

def scan_micro(rows=None, runs=3, out_path="BENCH_scan_micro.json"):
    """Microbenchmark of the round-14 scan path, three claims in one
    artifact:

    1. `records`: a clustered table swept across predicate
       selectivities with zone-map pruning on vs off — end-to-end
       engine walls (scan cache invalidated so the scan really runs),
       zones/rows-pruned counters, and a bit-exactness check between
       the two modes.
    2. `decode`: the same data written as multi-stripe ORC (zlib) and
       multi-row-group parquet, re-read with read-level `predicates=` —
       decoded rows and skipped stripes/row groups per selectivity
       prove statistics pruning cuts decode work (>= 10x at 0.01%).
    3. `prefetch`: a multi-chunk aggregation with the fact cache
       disabled so exec/chunked.py really decodes per chunk, at
       prefetch_depth 0 (serial) vs 2 (pipelined); chunk_spans record
       decode/compute/wall so overlap is visible (pipelined wall <
       serial decode+compute sum).

    Under JAX_PLATFORMS=cpu the shape shrinks to a smoke configuration
    (walls meaningless there; the decode-reduction ratios are
    measurement-grade anywhere since they count rows, not seconds)."""
    import tempfile

    import jax

    from trino_tpu.batch import Field, Schema
    from trino_tpu.connectors.parquetdir import flatten_table
    from trino_tpu.connectors.tpch.datagen import TableData
    from trino_tpu.exec.session import Session
    from trino_tpu.formats.orc import read_orc_file, write_orc
    from trino_tpu.formats.parquet import read_parquet_file, write_parquet
    from trino_tpu.types import BIGINT, DOUBLE

    on_tpu = jax.default_backend() == "tpu"
    mode = "device" if on_tpu else "cpu"
    if rows is None:
        rows = (1 << 24) if on_tpu else (1 << 17)
    zone_rows = max(1024, rows // 64)            # 64 zones / stripes
    rng = np.random.default_rng(14)
    selectivities = (0.0001, 0.01, 0.5, 1.0)

    # clustered key -> tight zones; v is the aggregated payload
    data = TableData("scan_micro", Schema((
        Field("k", BIGINT), Field("v", DOUBLE))),
        [np.arange(rows, dtype=np.int64),
         rng.standard_normal(rows)])

    s = Session()
    s.catalog.connector("memory").create_table("default", "scan_micro",
                                               data)
    s.execute(f"SET SESSION zone_map_rows = {zone_rows}")

    records = []
    for sel in selectivities:
        lim = max(1, int(rows * sel))
        q = (f"SELECT count(*) AS c, sum(v) AS sv FROM "
             f"memory.default.scan_micro WHERE k < {lim}")
        rec = {"selectivity": sel, "rows": rows, "zone_rows": zone_rows}
        results = {}
        for setting in ("true", "false"):
            s.execute(f"SET SESSION enable_zone_map_pruning = {setting}")
            s.execute(q)                         # warm (compile + plan)
            st = s.executor.stats
            zones0, rowsp0 = st.scan_zones_pruned, st.scan_rows_pruned
            walls = []
            for _ in range(runs):
                s.executor.invalidate_scan_cache()
                t0 = time.monotonic()
                results[setting] = s.execute(q).rows
                walls.append(time.monotonic() - t0)
            tag = "prune_on" if setting == "true" else "prune_off"
            rec[f"{tag}_ms"] = round(min(walls) * 1000, 3)
            if setting == "true":
                rec["zones_pruned_per_run"] = \
                    (st.scan_zones_pruned - zones0) // runs
                rec["rows_pruned_per_run"] = \
                    (st.scan_rows_pruned - rowsp0) // runs
        rec["identical"] = results["true"] == results["false"]
        records.append(rec)

    # ---- claim 2: file-level decode reduction ---------------------------
    tmp = tempfile.mkdtemp(prefix="scan_micro_")
    flat = flatten_table(data, "bench")
    orc_path = os.path.join(tmp, "scan_micro.orc")
    pq_path = os.path.join(tmp, "scan_micro.parquet")
    write_orc(orc_path, *flat, stripe_rows=zone_rows,
              compression="zlib")
    write_parquet(pq_path, *flat, row_group_rows=zone_rows)
    decode = []
    for sel in selectivities:
        lim = max(1, int(rows * sel))
        pred = {"k": (0, lim - 1)}
        of = read_orc_file(orc_path, predicates=pred)
        pf = read_parquet_file(pq_path, predicates=pred)
        decode.append({
            "selectivity": sel,
            "orc_decoded_rows": int(len(of.columns[0])),
            "orc_skipped_stripes": of.skipped_stripes,
            "orc_total_stripes": of.total_stripes,
            "parquet_decoded_rows": int(len(pf.columns[0])),
            "parquet_skipped_row_groups": pf.skipped_row_groups,
            "parquet_total_row_groups": pf.total_row_groups,
            "decode_reduction_x": round(
                rows / max(1, len(of.columns[0])), 1)})
    for p in (orc_path, pq_path):
        try:
            os.remove(p)
        except OSError:
            pass

    # ---- claim 3: prefetch overlap (chunked driver really decoding) ----
    s2 = Session()
    s2.executor.enable_fact_cache = False        # force per-chunk decode
    s2.execute("SET SESSION spill_chunk_rows = 8192")
    s2.execute("SET SESSION enable_zone_map_pruning = false")
    pq_sql = ("SELECT l_returnflag, count(*) AS c, "
              "sum(l_extendedprice) AS s FROM tpch.tiny.lineitem "
              "GROUP BY l_returnflag ORDER BY l_returnflag")
    prefetch = {}
    pf_results = {}
    for depth in (0, 2):
        s2.execute(f"SET SESSION prefetch_depth = {depth}")
        s2.execute(pq_sql)                       # warm (compile)
        walls, spans = [], None
        for _ in range(runs):
            t0 = time.monotonic()
            pf_results[depth] = s2.execute(pq_sql).rows
            walls.append(time.monotonic() - t0)
            spans = getattr(s2.executor, "chunk_spans", None)
        ent = {"wall_ms": round(min(walls) * 1000, 3)}
        if spans:
            for k2, v2 in spans.items():
                ent[k2] = round(v2, 4) if isinstance(v2, float) else v2
        prefetch[f"depth{depth}"] = ent
    prefetch["identical"] = pf_results.get(0) == pf_results.get(2)
    d2 = prefetch["depth2"]
    if "decode_s" in d2 and "compute_s" in d2 and "wall_s" in d2:
        # the overlap headline: the pipelined loop's own wall vs the
        # serialized sum of its decode+compute spans (same run, so no
        # cross-run noise enters the comparison)
        prefetch["serialized_sum_ms"] = round(
            (d2["decode_s"] + d2["compute_s"]) * 1000, 3)
        prefetch["overlap_win"] = \
            d2["wall_s"] * 1000 < prefetch["serialized_sum_ms"]

    out = {"metric": "scan_micro_ms", "device": str(jax.devices()[0]),
           "mode": mode, "smoke": not on_tpu, "records": records,
           "decode": decode, "prefetch": prefetch}
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return out


# ---------------------------------------------------------------------------
# --chaos: seeded randomized fault-injection soak (round-7 robustness PR)
# ---------------------------------------------------------------------------

# name -> (sql, unordered): unordered queries (no ORDER BY) compare as
# multisets — page arrival order legitimately varies under retry/hedging
CHAOS_QUERIES = {
    "agg": (("SELECT l_returnflag, l_linestatus, sum(l_quantity) AS q, "
             "count(*) AS c FROM lineitem WHERE l_shipdate <= DATE "
             "'1998-09-02' GROUP BY l_returnflag, l_linestatus "
             "ORDER BY l_returnflag, l_linestatus"), False),
    "concat": (("SELECT l_orderkey, l_quantity FROM lineitem "
                "WHERE l_shipdate > DATE '1998-11-01'"), True),
    "sort": (("SELECT l_orderkey, l_linenumber FROM lineitem "
              "WHERE l_shipdate > DATE '1998-10-01' "
              "ORDER BY l_orderkey, l_linenumber"), False),
}


def _chaos_rows(rows):
    return [tuple(v if v is None or isinstance(v, (int, float, str, bool))
                  else str(v) for v in r) for r in rows]


def chaos_soak(n_seeds=None, cluster=None, out_path="BENCH_chaos.json"):
    """Seeded chaos soak: run the query matrix under generated fault
    schedules (crash / delay / drop / corrupt at every distributed
    control-plane point) and require bit-identical results vs the
    fault-free run — zero wrong-answer escapes, corrupted pages always
    caught by the CRC32C page checksums and recovered via task retry.

    CPU smoke path: a 3-worker in-process cluster over real HTTP, tiny
    schema, small splits. Emits BENCH_chaos.json with injected-fault
    counts and recovery latencies (fault wall minus fault-free median).
    Pass `cluster=(coord, workers, session)` to reuse a live cluster
    (the slow-tier pytest soak does); `out_path=None` skips the file."""
    from trino_tpu.client.client import Client, QueryError
    from trino_tpu.exec.session import Session
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.failuredetector import HeartbeatFailureDetector
    from trino_tpu.server.failureinjector import FailureInjector
    from trino_tpu.server.worker import WorkerServer

    n = n_seeds if n_seeds is not None else \
        int(os.environ.get("TRINO_TPU_CHAOS_SEEDS", 50))
    budget_s = float(os.environ.get("TRINO_TPU_CHAOS_BUDGET_S", 600))
    t_start = time.monotonic()
    owns = cluster is None
    detector = None
    if owns:
        session = Session(default_schema="tiny")
        coord = CoordinatorServer(session, retry_policy="QUERY").start()
        coord.state.scheduler.split_rows = 8192
        workers = [WorkerServer(f"chaos-w{i}", coord.uri,
                                announce_interval_s=0.1,
                                catalog=session.catalog).start()
                   for i in range(3)]
        detector = HeartbeatFailureDetector(coord.state,
                                            interval_s=0.2).start()
    else:
        coord, workers, session = cluster
        detector = coord.state.failure_detector
    sched = coord.state.scheduler
    saved = (sched.max_task_retries, sched.hedge_min_s,
             sched.hedge_multiplier)
    # chaos schedules can burn several retry rounds; hedge threshold
    # sits well below the injected straggler delays (up to 1s) so DELAY
    # faults actually exercise the speculative re-dispatch path
    sched.max_task_retries = 8
    sched.hedge_min_s, sched.hedge_multiplier = 0.3, 2.0
    client = Client(coord.uri, user="chaos", timeout_s=120)

    def wait_active(k=3, timeout=5.0):
        deadline = time.time() + timeout
        while len(coord.state.active_nodes()) < k and \
                time.time() < deadline:
            time.sleep(0.05)

    wait_active()
    # fault-free baselines THROUGH the cluster (also warms the worker
    # fragments so XLA compile doesn't pollute recovery latencies)
    baselines, base_wall = {}, {}
    for name, (q, unordered) in CHAOS_QUERIES.items():
        walls = []
        for _ in range(2):
            sched.spool.clear()
            t0 = time.monotonic()
            r = client.execute(q)
            walls.append(time.monotonic() - t0)
        rows = _chaos_rows(r.rows)
        baselines[name] = sorted(rows) if unordered else rows
        base_wall[name] = min(walls)

    rec = {"metric": "chaos_soak", "schedules": 0, "queries_run": 0,
           "wrong_answers": 0, "failed_queries": 0, "injected_total": 0,
           "injected_by_fault": {}, "corrupt_detected": 0,
           "recovery_latency_s": [], "task_retries": 0,
           "hedged_tasks": 0, "spool_hits": 0, "budget_exhausted": False}
    retries0 = sched.stats["task_retries"]
    hedged0 = sched.stats["hedged_tasks"]
    spool0 = sched.stats["spool_hits"]
    crc0 = sched.stats["checksum_failures"]
    for seed in range(n):
        if time.monotonic() - t_start > budget_s:
            rec["budget_exhausted"] = True
            break
        inj = FailureInjector.from_seed(seed, max_delay_s=1.0)
        sched.failure_injector = inj
        if detector is not None:
            detector.injector = inj
        for w in workers:
            w.task_manager.injector = inj
        try:
            for name, (q, unordered) in CHAOS_QUERIES.items():
                sched.spool.clear()
                fired_before = inj.injected_count
                t0 = time.monotonic()
                try:
                    r = client.execute(q)
                except QueryError:
                    rec["failed_queries"] += 1
                    continue
                wall = time.monotonic() - t0
                rec["queries_run"] += 1
                got = _chaos_rows(r.rows)
                if unordered:
                    got = sorted(got)
                if got != baselines[name]:
                    rec["wrong_answers"] += 1
                if inj.injected_count > fired_before:
                    rec["recovery_latency_s"].append(
                        round(max(0.0, wall - base_wall[name]), 3))
        finally:
            sched.failure_injector = None
            if detector is not None:
                detector.injector = None
            for w in workers:
                w.task_manager.injector = None
        rec["schedules"] += 1
        rec["injected_total"] += inj.injected_count
        for fault, cnt in inj.injected_by_fault.items():
            if cnt:
                rec["injected_by_fault"][fault] = \
                    rec["injected_by_fault"].get(fault, 0) + cnt
        inj.clear()
        wait_active()
    rec["task_retries"] = sched.stats["task_retries"] - retries0
    rec["hedged_tasks"] = sched.stats["hedged_tasks"] - hedged0
    rec["spool_hits"] = sched.stats["spool_hits"] - spool0
    rec["corrupt_detected"] = sched.stats["checksum_failures"] - crc0 + \
        sched.spool.checksum_rejects
    lat = sorted(rec["recovery_latency_s"])
    rec["recovery_p50_s"] = lat[len(lat) // 2] if lat else 0.0
    rec["recovery_p95_s"] = lat[int(len(lat) * 0.95)] if lat else 0.0
    rec["elapsed_s"] = round(time.monotonic() - t_start, 1)
    sched.max_task_retries, sched.hedge_min_s, sched.hedge_multiplier = \
        saved
    if owns:
        if detector is not None:
            detector.stop()
        for w in workers:
            w.stop()
        coord.stop()
    if out_path:
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=1)
    print(json.dumps(rec), flush=True)
    return rec


# ---------------------------------------------------------------------------
# --overload: deadlines / cancellation / admission-control soak (round-22)
# ---------------------------------------------------------------------------

def overload_soak(cluster=None, out_path="BENCH_overload.json"):
    """Query-lifetime enforcement soak: saturating admission against a
    shrunken resource group (queue-full + queued-time rejections),
    HANG-wedged distributed queries that only the coordinator-stamped
    deadline can unstick, and a mass-cancel wave DELETEing mid-flight
    queries. Hard gates: 0 wrong answers among everything that
    FINISHED, every expired/canceled query terminal on every node
    within grace, and worker memory pools drained to zero. Emits
    BENCH_overload.json; the cancel-to-terminal and deadline-overshoot
    walls gate as their own --check-regressions series."""
    from trino_tpu.client.client import Client, QueryError
    from trino_tpu.exec.session import Session
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.failureinjector import (DELAY, HANG,
                                                  FailureInjector)
    from trino_tpu.server.worker import WorkerServer

    t_start = time.monotonic()
    owns = cluster is None
    if owns:
        session = Session(default_schema="tiny")
        coord = CoordinatorServer(session, retry_policy="QUERY").start()
        coord.state.scheduler.split_rows = 8192
        workers = [WorkerServer(f"ovl-w{i}", coord.uri,
                                announce_interval_s=0.1,
                                catalog=session.catalog).start()
                   for i in range(3)]
    else:
        coord, workers, session = cluster
    sched = coord.state.scheduler
    deadline = time.time() + 5
    while len(coord.state.active_nodes()) < 3 and time.time() < deadline:
        time.sleep(0.05)

    q_agg, _ = CHAOS_QUERIES["agg"]
    # fault-free baseline THROUGH the cluster (rows as the protocol
    # serializes them) — also warms the worker fragments so XLA compile
    # never eats a deadline
    want = _chaos_rows(
        Client(coord.uri, user="overload").execute(q_agg).rows)

    rec = {"metric": "overload", "submitted": 0, "finished": 0,
           "wrong_answers": 0, "rejected_queue_full": 0,
           "rejected_queued_deadline": 0, "deadline_kills": 0,
           "canceled": 0, "unexpected_errors": 0, "errors": []}

    def note_error(stage, e):
        rec["unexpected_errors"] += 1
        if len(rec["errors"]) < 8:
            rec["errors"].append(f"{stage}: {e}")

    # -- wave 1: saturating admission against a shrunken root group ----
    client_sets = Client(coord.uri, user="overload")
    client_sets.execute("SET SESSION query_max_queued_time_s = 0.5")
    root = coord.state.dispatcher.resource_groups.root
    saved_rg = (root.config.hard_concurrency_limit,
                root.config.max_queued)
    root.config.hard_concurrency_limit = 1
    root.config.max_queued = 2
    lock = threading.Lock()

    def one_query():
        rec["submitted"] += 1
        try:
            r = Client(coord.uri, user="overload",
                       timeout_s=120).execute(q_agg)
        except QueryError as e:
            with lock:
                if e.error_name == "QUERY_QUEUE_FULL":
                    rec["rejected_queue_full"] += 1
                elif e.error_name == "QUERY_EXCEEDED_QUEUED_TIME":
                    rec["rejected_queued_deadline"] += 1
                else:
                    note_error("admission", e)
            return
        with lock:
            rec["finished"] += 1
            if _chaos_rows(r.rows) != want:
                rec["wrong_answers"] += 1

    try:
        threads = [threading.Thread(target=one_query)
                   for _ in range(10)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        root.config.hard_concurrency_limit, root.config.max_queued = \
            saved_rg
        # reset via the session dict, not a SET statement: a SET issued
        # while the deadline property is still armed gets stamped with
        # that deadline and can itself be killed mid-drain
        session.properties.pop("query_max_queued_time_s", None)

    # -- wave 2: HANG-wedged queries unstuck only by their deadline ----
    n_hang = 3
    deadline_s = 1.0
    client_sets.execute(
        f"SET SESSION query_max_run_time_s = {deadline_s}")
    inj = FailureInjector(seed=722)
    inj.inject("WORKER_TASK_RUN", times=4 * n_hang, fault=HANG,
               delay_s=8.0)
    for w in workers:
        w.task_manager.injector = inj
    overshoots = []
    try:
        for _ in range(n_hang):
            # drop spooled task results so the query actually re-runs
            # on the workers (and hits the HANG) instead of being
            # served from the exchange spool
            sched.spool.clear()
            rec["submitted"] += 1
            t0 = time.monotonic()
            try:
                Client(coord.uri, user="overload",
                       timeout_s=30).execute(q_agg)
                note_error("hang", "wedged query FINISHED under a "
                                   "deadline that should have fired")
            except QueryError as e:
                wall = time.monotonic() - t0
                if e.error_name == "QUERY_EXCEEDED_RUN_TIME":
                    rec["deadline_kills"] += 1
                    overshoots.append(
                        round(max(0.0, wall - deadline_s) * 1000, 1))
                else:
                    note_error("hang", e)
    finally:
        inj.clear()                       # release every live HANG
        for w in workers:
            w.task_manager.injector = None
        session.properties.pop("query_max_run_time_s", None)

    # -- wave 3: mass-cancel of mid-flight distributed queries ---------
    n_cancel = 4
    inj = FailureInjector(seed=723)
    inj.inject("WORKER_TASK_RUN", times=8 * n_cancel, fault=DELAY,
               delay_s=1.0)
    for w in workers:
        w.task_manager.injector = inj
    cancel_walls = []
    try:
        # same spool hazard as wave 2: released wave-2 tasks may have
        # spooled their pages, and a spool-served query FINISHES before
        # the DELETE can land
        sched.spool.clear()
        cancel_client = Client(coord.uri, user="overload")
        live = []
        for _ in range(n_cancel):
            rec["submitted"] += 1
            doc = cancel_client._submit(q_agg)
            live.append((doc["id"], doc.get("nextUri")))
        # wait until the wave is mid-flight (remote tasks dispatched —
        # the exec lock serializes dispatch, so the rest of the wave is
        # canceled wherever it stands: queued, planning, or waiting),
        # then DELETE everything back-to-back
        deadline = time.time() + 15
        while time.time() < deadline and not any(
                sched._live_tasks.get(qid) for qid, _ in live):
            time.sleep(0.02)
        for qid, next_uri in live:
            t0 = time.monotonic()
            try:
                cancel_client._request("DELETE", next_uri)
            except Exception as e:  # noqa: BLE001
                note_error("cancel", e)
                continue
            tq = coord.state.tracker.get(qid)
            deadline = time.time() + 10
            while not tq.state_machine.is_done() and \
                    time.time() < deadline:
                time.sleep(0.01)
            if tq.state == "CANCELED":
                rec["canceled"] += 1
                cancel_walls.append(
                    round((time.monotonic() - t0) * 1000, 1))
            else:
                note_error("cancel", f"{qid} ended {tq.state}")
    finally:
        inj.clear()
        for w in workers:
            w.task_manager.injector = None

    # -- grace: every node terminal, every pool drained ----------------
    def all_tasks_terminal():
        return all(t.state not in ("PENDING", "RUNNING")
                   for w in workers
                   for t in list(w.task_manager.tasks.values()))

    def pools_drained():
        return all(w.task_manager.memory_info().get("reserved", 0) == 0
                   for w in workers)

    grace = time.time() + 15
    while not (all_tasks_terminal() and pools_drained()) and \
            time.time() < grace:
        time.sleep(0.05)
    rec["tasks_terminal"] = all_tasks_terminal()
    rec["pools_drained"] = pools_drained()

    cancel_walls.sort()
    overshoots.sort()
    rec["cancel_terminal_p50_ms"] = \
        cancel_walls[len(cancel_walls) // 2] if cancel_walls else None
    rec["cancel_terminal_max_ms"] = \
        cancel_walls[-1] if cancel_walls else None
    rec["deadline_overshoot_p50_ms"] = \
        overshoots[len(overshoots) // 2] if overshoots else None
    rec["rejected_total"] = (rec["rejected_queue_full"] +
                             rec["rejected_queued_deadline"])
    rec["elapsed_s"] = round(time.monotonic() - t_start, 1)
    rec["passed"] = bool(
        rec["wrong_answers"] == 0 and rec["unexpected_errors"] == 0 and
        rec["deadline_kills"] == n_hang and
        rec["canceled"] == n_cancel and rec["finished"] >= 1 and
        rec["tasks_terminal"] and rec["pools_drained"])
    if owns:
        for w in workers:
            w.stop()
        coord.stop()
    if out_path:
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=1)
    print(json.dumps(rec), flush=True)
    return rec


# ---------------------------------------------------------------------------
# --write-chaos: exactly-once distributed-write soak (round-18 PR)
# ---------------------------------------------------------------------------

WRITE_CHAOS_SRC = ("SELECT o_orderkey, o_custkey, o_orderstatus, "
                   "o_totalprice FROM tpch.tiny.orders")


def write_chaos_soak(n_seeds=None, out_path="BENCH_write_chaos.json"):
    """Seeded write-chaos soak: distributed CTAS with kills injected at
    each write-protocol boundary (WRITE_STAGE / WRITE_COMMIT /
    WRITE_PUBLISH, faults rotating through RAISE / CRASH / DELAY plus
    torn-journal CORRUPT appends, some seeds with forced duplicate
    hedged attempts). Every seed's committed table must equal the
    fault-free row multiset — 0 lost rows, 0 duplicate rows — and leave
    0 orphaned staging files or journals. Pre-intent failures are
    retried under the SAME query id, so the soak also proves commit
    idempotence across whole-query retries. Emits BENCH_write_chaos.json
    with per-point commit-wall percentiles for the regression gate."""
    import shutil as _shutil
    import tempfile
    from collections import Counter

    from trino_tpu.connectors.orcdir import OrcConnector
    from trino_tpu.exec.session import Session
    from trino_tpu.server import writeprotocol as wp
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.failureinjector import (CORRUPT, CRASH, DELAY,
                                                  RAISE, WRITE_COMMIT,
                                                  WRITE_POINTS,
                                                  FailureInjector)
    from trino_tpu.server.worker import WorkerServer

    n = n_seeds if n_seeds is not None else \
        int(os.environ.get("TRINO_TPU_WRITE_CHAOS_SEEDS", 27))
    budget_s = float(os.environ.get("TRINO_TPU_WRITE_CHAOS_BUDGET_S", 420))
    t_start = time.monotonic()
    root = tempfile.mkdtemp(prefix="write_chaos_")
    os.makedirs(os.path.join(root, "out"))
    session = Session(default_schema="tiny")
    conn = OrcConnector(root)
    session.catalog.register("orc", conn)
    coord = CoordinatorServer(session, retry_policy="QUERY").start()
    sched = coord.state.scheduler
    sched.split_rows = 4096
    workers = [WorkerServer(f"wchaos-w{i}", coord.uri,
                            announce_interval_s=0.1,
                            catalog=session.catalog).start()
               for i in range(3)]
    deadline = time.time() + 5
    while len(coord.state.active_nodes()) < 3 and time.time() < deadline:
        time.sleep(0.05)

    baseline = Counter(_chaos_rows(session.execute(WRITE_CHAOS_SRC).rows))
    rec = {"metric": "write_chaos", "seeds": 0, "writes_committed": 0,
           "failed_writes": 0, "query_retries": 0, "lost_rows": 0,
           "dup_rows": 0, "orphans": 0, "hedged_seeds": 0,
           "attempts_deduped": 0, "injected_total": 0,
           "injected_by_fault": {}, "injected_by_point": {},
           "points": {}, "budget_exhausted": False}
    walls = {p: [] for p in WRITE_POINTS}
    try:
        for seed in range(n):
            if time.monotonic() - t_start > budget_s:
                rec["budget_exhausted"] = True
                break
            point = WRITE_POINTS[seed % len(WRITE_POINTS)]
            fault = (RAISE, CRASH, DELAY)[(seed // 3) % 3]
            if point == WRITE_COMMIT and seed % 9 == 4:
                fault = CORRUPT          # torn intent-journal append
            inj = FailureInjector(seed=seed)
            inj.inject(point, times=1, fault=fault)
            sched.failure_injector = inj
            for w in workers:
                w.task_manager.injector = inj
            sched.force_write_hedge = seed % 4 == 3
            if sched.force_write_hedge:
                rec["hedged_seeds"] += 1
            tbl = f"w{seed}"
            qid = f"wchaos_{seed}"
            sql = f"CREATE TABLE orc.out.{tbl} AS {WRITE_CHAOS_SRC}"
            res = None
            t0 = time.monotonic()
            for _attempt in range(3):
                try:
                    res = sched.execute(sql, query_id=qid)
                    break
                except Exception:
                    # pre-intent abort: the QUERY retry policy reruns
                    # the same query id — exactly-once must hold
                    rec["query_retries"] += 1
            wall_ms = (time.monotonic() - t0) * 1000
            sched.failure_injector = None
            sched.force_write_hedge = False
            for w in workers:
                w.task_manager.injector = None
            rec["seeds"] += 1
            rec["injected_total"] += inj.injected_count
            rec["injected_by_point"][point] = \
                rec["injected_by_point"].get(point, 0) + inj.injected_count
            for f, cnt in inj.injected_by_fault.items():
                if cnt:
                    rec["injected_by_fault"][f] = \
                        rec["injected_by_fault"].get(f, 0) + cnt
            if res is None:
                rec["failed_writes"] += 1
                continue
            rec["writes_committed"] += 1
            walls[point].append(wall_ms)
            wr = (sched.last_query or {}).get("write") or {}
            rec["attempts_deduped"] += int(wr.get("deduped", 0))
            got = Counter(_chaos_rows(session.execute(
                f"SELECT o_orderkey, o_custkey, o_orderstatus, "
                f"o_totalprice FROM orc.out.{tbl}").rows))
            rec["lost_rows"] += sum((baseline - got).values())
            rec["dup_rows"] += sum((got - baseline).values())
            td = conn._table_dir("out", tbl)
            rec["orphans"] += len(os.listdir(wp.staging_dir(td))) \
                if os.path.isdir(wp.staging_dir(td)) else 0
            rec["orphans"] += sum(1 for f in os.listdir(td)
                                  if f.endswith(".journal")
                                  or f.startswith(".tmp."))
            conn.drop_table("out", tbl)
        # nothing may survive outside the published tables either
        for dirpath, dirnames, filenames in os.walk(root):
            rec["orphans"] += sum(1 for d in dirnames if d == ".staging")
            rec["orphans"] += sum(1 for f in filenames
                                  if f.endswith(".journal")
                                  or f.startswith(".tmp."))
    finally:
        sched.failure_injector = None
        sched.force_write_hedge = False
        for w in workers:
            w.task_manager.injector = None
            w.stop()
        coord.stop()
        _shutil.rmtree(root, ignore_errors=True)
    for point, ws in walls.items():
        if ws:
            ws = sorted(ws)
            rec["points"][point] = {
                "commits": len(ws),
                "p50_ms": round(ws[len(ws) // 2], 1),
                "p95_ms": round(ws[int(len(ws) * 0.95)], 1)}
    rec["elapsed_s"] = round(time.monotonic() - t_start, 1)
    rec["passed"] = (rec["lost_rows"] == 0 and rec["dup_rows"] == 0
                     and rec["orphans"] == 0
                     and rec["failed_writes"] == 0
                     and rec["injected_total"] >= rec["seeds"])
    if out_path:
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=1)
    print(json.dumps(rec), flush=True)
    return rec


COORD_CHAOS_PHASES = ("QUEUED", "PLANNING", "RUNNING", "FINISHING",
                      "WRITE_COMMIT")


def coordinator_chaos_soak(n_seeds=None,
                           out_path="BENCH_coordinator_chaos.json"):
    """Seeded coordinator-kill soak (round 20 acceptance): for every
    seed, bring up a primary + warm standby sharing one durable query
    ledger and spool root plus two workers, submit a query through a
    multi-address client, and kill the primary at a rotating lifecycle
    phase (QUEUED / PLANNING / RUNNING / FINISHING / WRITE_COMMIT —
    the write phase crashes the staged-write commit mid-flight so
    exactly-once must hold across the failover). Promotion alternates
    by seed parity between detector-driven and admin `PUT
    /v1/info/state`. The client must finish every seed with bit-exact
    rows and NO visible error: 0 wrong results, 0 lost rows, 0
    duplicate rows. Emits BENCH_coordinator_chaos.json with
    failover-to-first-result percentiles for the regression gate."""
    import shutil as _shutil
    import tempfile
    import threading
    from collections import Counter
    from urllib.request import Request, urlopen

    from trino_tpu.client.client import Client
    from trino_tpu.connectors.orcdir import OrcConnector
    from trino_tpu.exec.session import Session
    from trino_tpu.metrics import COORDINATOR_FAILOVERS
    from trino_tpu.server import ledger as led
    from trino_tpu.server import writeprotocol as wp
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.failureinjector import (CRASH, DELAY,
                                                  WRITE_COMMIT,
                                                  FailureInjector)
    from trino_tpu.server.security import internal_headers
    from trino_tpu.server.worker import WorkerServer

    n = n_seeds if n_seeds is not None else \
        int(os.environ.get("TRINO_TPU_COORD_CHAOS_SEEDS", 20))
    budget_s = float(os.environ.get("TRINO_TPU_COORD_CHAOS_BUDGET_S",
                                    600))
    t_start = time.monotonic()
    read_sql = ("SELECT n_regionkey, count(*) AS c FROM nation "
                "GROUP BY n_regionkey ORDER BY n_regionkey")
    read_expect = [(0, 5), (1, 5), (2, 5), (3, 5), (4, 5)]
    write_src = ("SELECT o_orderkey, o_custkey, o_orderstatus, "
                 "o_totalprice FROM tpch.tiny.orders")
    rec = {"metric": "coordinator_chaos", "seeds": 0,
           "wrong_results": 0, "lost_rows": 0, "dup_rows": 0,
           "client_errors": 0, "failovers": 0,
           "detector_promotions": 0, "admin_promotions": 0,
           "kills_by_phase": {}, "resumed_by_mode": {},
           "budget_exhausted": False}
    fo_walls = []
    write_baseline = None
    for seed in range(n):
        if time.monotonic() - t_start > budget_s:
            rec["budget_exhausted"] = True
            break
        phase = COORD_CHAOS_PHASES[seed % len(COORD_CHAOS_PHASES)]
        admin = seed % 2 == 1           # else detector-driven
        write_phase = phase == "WRITE_COMMIT"
        root = tempfile.mkdtemp(prefix="coord_chaos_")
        ledger = os.path.join(root, "query.ledger")
        spool = os.path.join(root, "spool")
        s1 = Session(default_schema="tiny")
        s2 = Session(default_schema="tiny")
        conn2 = None
        if write_phase:
            os.makedirs(os.path.join(root, "orc", "out"))
            s1.catalog.register("orc", OrcConnector(
                os.path.join(root, "orc")))
            conn2 = OrcConnector(os.path.join(root, "orc"))
            s2.catalog.register("orc", conn2)
        primary = CoordinatorServer(s1, ledger_path=ledger,
                                    node_id=f"p{seed}",
                                    spool_root=spool).start()
        standby = CoordinatorServer(s2, ledger_path=ledger,
                                    node_id=f"s{seed}", role="standby",
                                    peer_uri=primary.uri,
                                    spool_root=spool,
                                    standby_interval_s=0.1,
                                    auto_promote=not admin).start()
        workers = [WorkerServer(f"cc{seed}w{i}", primary.uri,
                                announce_interval_s=0.1,
                                catalog=s1.catalog).start()
                   for i in range(2)]
        deadline = time.time() + 10
        while len(primary.state.active_nodes()) < 2 and \
                time.time() < deadline:
            time.sleep(0.02)
        for w in workers:
            w.announce_once()           # learn the standby address now
        inj = FailureInjector(seed=seed)
        if write_phase:
            primary.state.scheduler.split_rows = 4096
            primary.state.scheduler.failure_injector = inj
            # the commit dies mid-flight on the (sealed) primary; the
            # promoted standby re-executes and must dedup to one table
            inj.inject(WRITE_COMMIT, times=1, fault=CRASH)
            sql = f"CREATE TABLE orc.out.c{seed} AS {write_src}"
        else:
            primary.state.dispatcher.failure_injector = inj
            if phase in ("RUNNING", "FINISHING"):
                inj.inject("EXECUTION", times=1, fault=DELAY,
                           delay_s=1.5, match_sql="n_regionkey")
            sql = read_sql
        client = Client([primary.uri, standby.uri],
                        user=f"chaos{seed}", timeout_s=120)
        out = {}

        def run(client=client, sql=sql, out=out):
            try:
                out["r"] = client.execute(sql)
            except Exception as e:  # noqa: BLE001 — the gate counts it
                out["err"] = e

        t = threading.Thread(target=run)
        t.start()
        # kill when the primary's registry first shows the query at (or
        # past) the target phase — a bounded watch, so late phases that
        # flash by still get a kill near the boundary
        target = "RUNNING" if write_phase else phase
        observed = None
        deadline = time.time() + 8
        while time.time() < deadline and observed is None:
            for tq in primary.state.tracker.all():
                if led._rank(tq.state) >= led._rank(target):
                    observed = tq.state
                    break
            if observed is None:
                time.sleep(0.002)
        if phase == "FINISHING" and observed == "RUNNING":
            time.sleep(1.2)             # drift toward the boundary
        t_kill = time.monotonic()
        primary.kill()
        rec["kills_by_phase"][phase] = \
            rec["kills_by_phase"].get(phase, 0) + 1
        if admin:
            try:
                req = Request(f"{standby.uri}/v1/info/state",
                              data=json.dumps(
                                  {"state": "PRIMARY"}).encode(),
                              headers={"Content-Type":
                                       "application/json",
                                       **internal_headers()},
                              method="PUT")
                with urlopen(req, timeout=15):
                    pass
                rec["admin_promotions"] += 1
            except Exception:  # noqa: BLE001 — client error will gate
                pass
        else:
            rec["detector_promotions"] += 1
        t.join(timeout=120)
        rec["seeds"] += 1
        if "r" not in out or t.is_alive():
            rec["client_errors"] += 1
        else:
            r = out["r"]
            fo_walls.append((time.monotonic() - t_kill) * 1000)
            rec["failovers"] += r.failovers
            if write_phase:
                got = Counter(_chaos_rows(s2.execute(
                    f"SELECT o_orderkey, o_custkey, o_orderstatus, "
                    f"o_totalprice FROM orc.out.c{seed}").rows))
                if write_baseline is None:
                    write_baseline = Counter(
                        _chaos_rows(s2.execute(write_src).rows))
                rec["lost_rows"] += sum(
                    (write_baseline - got).values())
                rec["dup_rows"] += sum((got - write_baseline).values())
            else:
                if [tuple(x) for x in r.rows] != read_expect:
                    rec["wrong_results"] += 1
            tq = standby.state.tracker.get(r.query_id)
            mode = getattr(tq, "resumed", None) if tq else None
            if mode:
                rec["resumed_by_mode"][mode] = \
                    rec["resumed_by_mode"].get(mode, 0) + 1
        for w in workers:
            w.kill()
        standby.kill()
        for c in (primary, standby):
            c.state.dispatcher.pool.shutdown(wait=False)
        _shutil.rmtree(root, ignore_errors=True)
    if fo_walls:
        ws = sorted(fo_walls)
        rec["failover_to_result_p50_ms"] = round(ws[len(ws) // 2], 1)
        rec["failover_to_result_p99_ms"] = round(
            ws[min(len(ws) - 1, int(len(ws) * 0.99))], 1)
    rec["coordinator_failovers_total"] = COORDINATOR_FAILOVERS.value()
    rec["elapsed_s"] = round(time.monotonic() - t_start, 1)
    rec["passed"] = (rec["wrong_results"] == 0 and rec["lost_rows"] == 0
                     and rec["dup_rows"] == 0
                     and rec["client_errors"] == 0
                     and rec["failovers"] >= rec["seeds"] > 0)
    if out_path:
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=1)
    print(json.dumps(rec), flush=True)
    return rec


def memory_pressure_soak(n_queries=None, out_path="BENCH_memory.json"):
    """Memory-pressure soak (round 9 acceptance): >= 20 concurrent
    queries against a 3-worker cluster with every executor pool clamped
    to 25% of the measured working set. Requires 0 wrong answers and 0
    worker crashes — queries must survive by spilling (host-spill
    radix partitioning, revocable partial state) or fail cleanly with
    QUERY_EXCEEDED_MEMORY, never by taking a worker down. Emits
    BENCH_memory.json with spill/backpressure/killer counters."""
    import threading as _th

    from trino_tpu.client.client import Client, QueryError
    from trino_tpu.exec.session import Session
    from trino_tpu.metrics import REGISTRY
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.failuredetector import HeartbeatFailureDetector
    from trino_tpu.server.worker import WorkerServer

    n = n_queries if n_queries is not None else \
        int(os.environ.get("TRINO_TPU_MEMSOAK_QUERIES", 24))
    queries = {
        "join_agg": ("SELECT o_custkey, count(*) AS c, "
                     "sum(o_totalprice) AS s FROM orders JOIN customer "
                     "ON o_custkey = c_custkey WHERE c_acctbal > 0 "
                     "GROUP BY o_custkey ORDER BY s DESC, o_custkey "
                     "LIMIT 50"),
        "wide_agg": ("SELECT l_returnflag, l_linestatus, "
                     "sum(l_quantity) AS q, count(*) AS c, "
                     "min(l_discount) AS mn, max(l_tax) AS mx "
                     "FROM lineitem GROUP BY l_returnflag, l_linestatus "
                     "ORDER BY l_returnflag, l_linestatus"),
        "big_group": ("SELECT l_orderkey, sum(l_quantity) AS q "
                      "FROM lineitem GROUP BY l_orderkey "
                      "ORDER BY q DESC, l_orderkey LIMIT 20"),
        "point": "SELECT count(*) FROM nation",
    }
    # 1) measure the working set at an unconstrained pool (rows
    # normalized like the protocol does — Decimal/date render as text)
    t_start = time.monotonic()
    session = Session(default_schema="tiny")
    baselines = {}
    for name, q in queries.items():
        baselines[name] = _chaos_rows(session.execute(q).rows)
    working_set = session.executor.pool.peak
    limit = max(1 << 20, working_set // 4)

    # 2) cluster with every pool clamped to 25%
    session.properties["query_max_memory_mb"] = max(1, limit >> 20)
    session.executor.pool.set_limit(limit)
    coord = CoordinatorServer(session, max_concurrency=4).start()
    coord.state.scheduler.split_rows = 8192
    workers = [WorkerServer(f"mem-w{i}", coord.uri,
                            announce_interval_s=0.1,
                            catalog=session.catalog).start()
               for i in range(3)]
    for w in workers:
        w.task_manager._executor.pool.set_limit(limit)
        w.task_manager.max_buffer_bytes = 1 << 20   # exercise backpressure
    detector = HeartbeatFailureDetector(coord.state,
                                        interval_s=0.2).start()
    coord.state.memory_manager.interval_s = 0.2
    coord.state.memory_manager.start()

    reg0 = REGISTRY.snapshot()
    rec = {"metric": "memory_pressure_soak", "queries": 0,
           "wrong_answers": 0, "failed_queries": 0,
           "oom_user_errors": 0, "worker_crashes": 0,
           "concurrent": n, "working_set_bytes": int(working_set),
           "pool_limit_bytes": int(limit)}
    lock = _th.Lock()

    def one(i: int) -> None:
        name = list(queries)[i % len(queries)]
        client = Client(coord.uri, user=f"soak{i}", timeout_s=180)
        try:
            rows = client.execute(queries[name]).rows
        except QueryError as e:
            with lock:
                if e.error_name == "QUERY_EXCEEDED_MEMORY":
                    rec["oom_user_errors"] += 1      # clean user error
                else:
                    rec["failed_queries"] += 1
            return
        except Exception:    # noqa: BLE001 — client-side transport
            with lock:       # failure: count it, never lose the thread
                rec["failed_queries"] += 1
            return
        with lock:
            rec["queries"] += 1
            if _chaos_rows(rows) != baselines[name]:
                rec["wrong_answers"] += 1

    threads = [_th.Thread(target=one, args=(i,), daemon=True)
               for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)

    # 3) no worker crashed: every worker still answers /v1/status ACTIVE
    from urllib.request import urlopen
    for w in workers:
        try:
            with urlopen(f"{w.uri}/v1/status", timeout=5) as resp:
                ok = resp.status == 200
        except Exception:
            ok = False
        if not ok:
            rec["worker_crashes"] += 1

    after = REGISTRY.snapshot()

    def delta(key):
        return int(after.get(key, 0) - reg0.get(key, 0))

    rec["spill_bytes"] = delta(("trino_tpu_spill_bytes_total",))
    rec["spill_partitions"] = delta(("trino_tpu_spill_partitions_total",))
    rec["revocations"] = delta(("trino_tpu_memory_revocations_total",))
    rec["backpressure_waits"] = delta(
        ("trino_tpu_exchange_backpressure_waits_total",))
    rec["queries_killed_oom"] = delta(
        ("trino_tpu_queries_killed_oom_total",))
    rec["elapsed_s"] = round(time.monotonic() - t_start, 1)
    rec["passed"] = (rec["wrong_answers"] == 0 and
                     rec["worker_crashes"] == 0 and
                     rec["failed_queries"] == 0)
    coord.state.memory_manager.stop()
    detector.stop()
    for w in workers:
        w.stop()
    coord.stop()
    if out_path:
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=1)
    print(json.dumps(rec), flush=True)
    return rec


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[i]


def concurrency_soak(n_clients=None, queries_per_client=None,
                     out_path="BENCH_concurrency.json"):
    """High-concurrency serving soak (round-11 acceptance): >= 100 mixed
    clients against one coordinator with the serving layer fully on
    (plan cache, result cache, CPU/TPU cost routing, micro-batching).
    Point/cached/small-aggregate traffic runs host-side WITHOUT the
    device exec lock while scan-heavy plans keep the device, so the mix
    must not serialize. Requires 0 wrong answers vs the uncached oracle
    (every HTTP result — cache hits, micro-batched rows, host-routed
    rows — compared bit-exact against a direct pre-server execution),
    nonzero result-cache/router/micro-batch counters, and a post-write
    rerun proving catalog-version invalidation. Emits
    BENCH_concurrency.json with throughput and p50/p99 per mix."""
    import tempfile
    import threading as _th

    from trino_tpu.client.client import Client, QueryError
    from trino_tpu.exec.session import Session
    from trino_tpu.metrics import REGISTRY
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.resourcegroups import (ResourceGroupConfig,
                                                 ResourceGroupManager)

    n = n_clients if n_clients is not None else \
        int(os.environ.get("TRINO_TPU_CONCURRENCY_CLIENTS", 120))
    per = queries_per_client if queries_per_client is not None else \
        int(os.environ.get("TRINO_TPU_CONCURRENCY_QUERIES", 5))
    t_start = time.monotonic()
    # fresh history file: stale medians from earlier rounds (cold
    # compile walls) would bias the router's baseline input
    hist = tempfile.NamedTemporaryFile(prefix="concurrency_hist_",
                                       suffix=".jsonl", delete=False)
    saved_hist_env = os.environ.get("TRINO_TPU_HISTORY_PATH")
    os.environ["TRINO_TPU_HISTORY_PATH"] = hist.name

    session = Session(default_schema="tiny")
    session.execute("CREATE TABLE memory.s.counters (k bigint, v bigint)")
    session.execute("INSERT INTO memory.s.counters VALUES (1, 10), (2, 20)")

    mixes = {
        "point": [f"SELECT n_name FROM nation WHERE n_nationkey = {k}"
                  for k in range(25)],
        "cached": ["SELECT r_name FROM region ORDER BY r_name",
                   "SELECT count(*) FROM supplier",
                   "SELECT v FROM memory.s.counters WHERE k = 2"],
        "small_agg": ["SELECT min(s_suppkey), max(s_suppkey) "
                      "FROM supplier",
                      "SELECT count(*) FROM customer"],
        "scan_heavy": [
            "SELECT l_returnflag, l_linestatus, sum(l_quantity) AS q, "
            "count(*) AS c FROM lineitem "
            "GROUP BY l_returnflag, l_linestatus "
            "ORDER BY l_returnflag, l_linestatus",
            "SELECT count(*) FROM orders JOIN customer "
            "ON o_custkey = c_custkey WHERE c_acctbal > 0"],
    }
    # uncached oracle: every distinct statement executed directly (no
    # serving layer) BEFORE the server starts — the soak's bit-exact
    # reference for cached/host/micro-batched paths alike
    oracle = {}
    for qs in mixes.values():
        for q in qs:
            oracle[q] = _chaos_rows(session.execute(q).rows)

    session.properties["enable_result_cache"] = True
    session.properties["enable_microbatch"] = True
    session.properties["microbatch_window_ms"] = 4.0
    coord = CoordinatorServer(session, max_concurrency=32).start()
    # the coordinator's history store is bound now: restore the env so
    # later stores in this process keep their configured path
    if saved_hist_env is None:
        os.environ.pop("TRINO_TPU_HISTORY_PATH", None)
    else:
        os.environ["TRINO_TPU_HISTORY_PATH"] = saved_hist_env
    coord.state.dispatcher.resource_groups = ResourceGroupManager(
        ResourceGroupConfig("root", hard_concurrency_limit=32,
                            max_queued=100_000))

    reg0 = REGISTRY.snapshot()
    # one statement is planned before the clients start, so whichever of
    # them sends it first finds its plan whatever the interleaving: left
    # to the herd a plan-cache hit needs a second client to arrive after
    # the first has planned a text and before its result is cached
    coord.state.dispatcher.serving.plan_entry(mixes["scan_heavy"][0])
    mix_names = list(mixes)
    lock = _th.Lock()
    latencies = {m: [] for m in mix_names}
    rec = {"metric": "concurrency_soak", "clients": n,
           "queries_per_client": per, "queries": 0, "wrong_answers": 0,
           "failed_queries": 0}

    def one(i: int) -> None:
        mix = mix_names[i % len(mix_names)]
        qs = mixes[mix]
        client = Client(coord.uri, user=f"conc{i}", timeout_s=180,
                        poll_interval_s=0.005)
        for j in range(per):
            q = qs[(i + j) % len(qs)]
            t0 = time.monotonic()
            try:
                rows = client.execute(q).rows
            except Exception:  # noqa: BLE001 — QueryError/transport both
                with lock:     # count as failures; the thread lives on
                    rec["failed_queries"] += 1
                continue
            ms = (time.monotonic() - t0) * 1000
            with lock:
                rec["queries"] += 1
                latencies[mix].append(ms)
                if _chaos_rows(rows) != oracle[q]:
                    rec["wrong_answers"] += 1

    threads = [_th.Thread(target=one, args=(i,), daemon=True)
               for i in range(n)]
    t_soak = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    soak_s = time.monotonic() - t_soak

    # post-write rerun: the cached counter read must reflect the write
    # (catalog-version invalidation), not the cached page
    client = Client(coord.uri, user="writer")
    pre = client.execute("SELECT count(*) FROM memory.s.counters").rows
    again = client.execute("SELECT count(*) FROM memory.s.counters").rows
    client.execute("INSERT INTO memory.s.counters VALUES (3, 30)")
    post = client.execute("SELECT count(*) FROM memory.s.counters").rows
    rec["invalidation_proven"] = (pre == again ==
                                  [[2]]) and post == [[3]]

    after = REGISTRY.snapshot()

    def delta(*key):
        return int(after.get(tuple(key), 0) - reg0.get(tuple(key), 0))

    rec["throughput_qps"] = round(rec["queries"] / max(soak_s, 1e-9), 1)
    rec["soak_seconds"] = round(soak_s, 2)
    rec["mixes"] = {}
    for m in mix_names:
        vals = sorted(latencies[m])
        rec["mixes"][m] = {
            "queries": len(vals),
            "p50_ms": round(_percentile(vals, 0.50), 1),
            "p99_ms": round(_percentile(vals, 0.99), 1)}
    rec["plan_cache_hits"] = delta("trino_tpu_plan_cache_hits_total")
    rec["plan_cache_misses"] = delta("trino_tpu_plan_cache_misses_total")
    rec["result_cache_hits"] = delta("trino_tpu_result_cache_hits_total")
    rec["result_cache_invalidations"] = delta(
        "trino_tpu_result_cache_invalidations_total")
    rec["router_host"] = delta("trino_tpu_router_decisions_total", "host")
    rec["router_device"] = delta("trino_tpu_router_decisions_total",
                                 "device")
    rec["microbatch_queries"] = delta(
        "trino_tpu_microbatch_queries_total")
    rec["microbatch_batches"] = delta(
        "trino_tpu_microbatch_batches_total")
    rec["elapsed_s"] = round(time.monotonic() - t_start, 1)
    rec["passed"] = (rec["wrong_answers"] == 0 and
                     rec["failed_queries"] == 0 and
                     rec["queries"] == n * per and
                     rec["result_cache_hits"] > 0 and
                     rec["plan_cache_hits"] > 0 and
                     rec["router_host"] > 0 and
                     rec["router_device"] > 0 and
                     rec["invalidation_proven"])
    coord.stop()
    if out_path:
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=1)
    print(json.dumps(rec), flush=True)
    return rec


def elastic_soak(duration_s=None, out_path="BENCH_soak.json"):
    """Sustained elastic-membership soak (round-15 acceptance): a
    minutes-long mixed workload — point + cached + scan-heavy + writes
    across >= 3 tenants — with chaos injection, per-tenant soft memory
    limits, and CPU/TPU routing all ON simultaneously, while a worker
    is admin-drained (PUT /v1/info/state) and a fresh worker joins
    mid-run. Gated on: 0 wrong answers (every read bit-exact vs a
    pre-server oracle, every write accounted for in a final count), 0
    failed queries, 0 orphaned splits on the drained worker, the drain
    reaching LEFT, the joiner actually receiving splits, and per-tenant
    p99 SLOs — the fair-share acceptance is that beta (the saturating
    scan tenant) cannot push alpha's point p99 past its SLO, because
    alpha's host-eligible queries overflow to the lock-free host tier
    under device contention. Emits BENCH_soak.json; the smoke path
    (TRINO_TPU_SOAK_DURATION_S of a few seconds) runs in tier-1."""
    import tempfile
    import threading as _th
    from urllib.request import Request as _Req
    from urllib.request import urlopen as _uo

    from trino_tpu.client.client import Client
    from trino_tpu.metrics import REGISTRY, SOAK_SLO_VIOLATIONS
    from trino_tpu.exec.session import Session
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.failuredetector import HeartbeatFailureDetector
    from trino_tpu.server.failureinjector import FailureInjector
    from trino_tpu.server.resourcegroups import tenant_tree
    from trino_tpu.server.security import internal_headers
    from trino_tpu.server.telemetry import (histogram_deltas,
                                            percentile_from_buckets)
    from trino_tpu.server.worker import WorkerServer

    dur = duration_s if duration_s is not None else \
        float(os.environ.get("TRINO_TPU_SOAK_DURATION_S", 180))
    per_tenant = int(os.environ.get("TRINO_TPU_SOAK_CLIENTS", 3))
    # cluster flight recorder cadence: ~20 samples over the soak so the
    # p99-over-time series has real resolution even on the smoke path
    tel_interval = float(os.environ.get("TRINO_TPU_SOAK_TELEMETRY_S",
                                        0)) or max(0.5, dur / 20.0)
    slo_ms = {
        "alpha": float(os.environ.get("TRINO_TPU_SOAK_SLO_ALPHA_MS",
                                      5000)),
        "beta": float(os.environ.get("TRINO_TPU_SOAK_SLO_BETA_MS",
                                     60000)),
        "gamma": float(os.environ.get("TRINO_TPU_SOAK_SLO_GAMMA_MS",
                                      5000)),
    }
    t_start = time.monotonic()
    # fresh history file (same reason as concurrency_soak: stale
    # medians would bias the router baseline)
    hist = tempfile.NamedTemporaryFile(prefix="soak_hist_",
                                       suffix=".jsonl", delete=False)
    saved_hist_env = os.environ.get("TRINO_TPU_HISTORY_PATH")
    os.environ["TRINO_TPU_HISTORY_PATH"] = hist.name

    session = Session(default_schema="tiny")
    session.execute(
        "CREATE TABLE memory.s.soak_log (k bigint, v bigint)")

    # tenant mixes: (sql, unordered, is_write). alpha = interactive
    # point/cached traffic (host tier), beta = scan-heavy distributed
    # saturator (device tier + cluster), gamma = cached reads + writes
    # (writes also bump the catalog version, which keeps invalidating
    # the result cache so beta's scans stay honest distributed work)
    mixes = {
        "alpha": [(f"SELECT n_name FROM nation WHERE n_nationkey = {k}",
                   False, False) for k in range(12)] +
                 [("SELECT r_name FROM region ORDER BY r_name",
                   False, False)],
        "beta": [(q, unordered, False)
                 for q, unordered in CHAOS_QUERIES.values()],
        "gamma": [("INSERT INTO memory.s.soak_log VALUES (1, 1)",
                   False, True),
                  ("SELECT count(*) FROM supplier", False, False),
                  ("SELECT min(s_suppkey), max(s_suppkey) FROM supplier",
                   False, False)],
    }
    oracle = {}
    for qs in mixes.values():
        for q, unordered, is_write in qs:
            if not is_write:
                rows = _chaos_rows(session.execute(q).rows)
                oracle[q] = sorted(rows) if unordered else rows

    session.properties["enable_result_cache"] = True
    session.properties["enable_microbatch"] = True
    # keep the host tier for genuinely small queries only: beta's
    # lineitem scans (~60k rows) must stay device/cluster work so the
    # drain/join path is exercised by real split placement, while
    # alpha's point lookups remain host-eligible for fair-share
    # overflow under contention
    session.properties["router_host_max_rows"] = 4096
    coord = CoordinatorServer(session, max_concurrency=16,
                              retry_policy="QUERY",
                              telemetry_interval_s=tel_interval).start()
    if saved_hist_env is None:
        os.environ.pop("TRINO_TPU_HISTORY_PATH", None)
    else:
        os.environ["TRINO_TPU_HISTORY_PATH"] = saved_hist_env
    # per-tenant isolation: one resource group per tenant with a soft
    # memory limit (round-9 admission gate), fair-share routing reads
    # the tenant off each query
    coord.state.dispatcher.resource_groups = tenant_tree(
        {"alpha": {"hard_concurrency_limit": 8},
         "beta": {"hard_concurrency_limit": 4,
                  "soft_memory_limit_bytes": 1 << 31},
         "gamma": {"hard_concurrency_limit": 4}},
        max_queued=100_000)
    sched = coord.state.scheduler
    sched.split_rows = 8192
    sched.max_task_retries = 8
    sched.hedge_min_s, sched.hedge_multiplier = 0.5, 2.0
    workers = [WorkerServer(f"soak-w{i}", coord.uri,
                            announce_interval_s=0.1,
                            heartbeat_interval_s=0.1,
                            catalog=session.catalog,
                            drain_timeout_s=60.0,
                            telemetry_interval_s=tel_interval).start()
               for i in range(3)]
    detector = HeartbeatFailureDetector(coord.state,
                                        interval_s=0.2).start()
    coord.state.memory_manager.start()

    def wait_active(k, timeout=10.0):
        deadline = time.time() + timeout
        while len(coord.state.active_nodes()) < k and \
                time.time() < deadline:
            time.sleep(0.05)
        return len(coord.state.active_nodes()) >= k

    wait_active(3)
    stats0 = dict(sched.stats)
    reg0 = REGISTRY.snapshot()
    # baseline flight-recorder sample: the first sample of a fresh ring
    # carries counter totals since process start; everything after this
    # timestamp is genuine per-interval soak deltas
    telemetry = coord.state.telemetry
    tel_baseline_ts = telemetry.recorder.sample_once()["ts"]
    lock = _th.Lock()
    latencies = {t: [] for t in mixes}
    rec = {"metric": "soak", "duration_s": dur, "queries": 0,
           "wrong_answers": 0, "failed_queries": 0, "writes_ok": 0,
           "chaos_schedules": 0, "injected_total": 0}
    stop_at = time.monotonic() + dur
    mismatches = []

    def one(tenant: str, i: int) -> None:
        qs = mixes[tenant]
        client = Client(coord.uri, user=f"{tenant}-{i}", timeout_s=180,
                        poll_interval_s=0.005)
        j = 0
        while time.monotonic() < stop_at:
            q, unordered, is_write = qs[(i + j) % len(qs)]
            j += 1
            t0 = time.monotonic()
            try:
                rows = client.execute(q).rows
            except Exception as e:  # noqa: BLE001 — any failure counts
                with lock:
                    rec["failed_queries"] += 1
                    if len(mismatches) < 5:
                        mismatches.append(f"{tenant}: {q[:60]}: {e}")
                continue
            ms = (time.monotonic() - t0) * 1000
            with lock:
                rec["queries"] += 1
                latencies[tenant].append(ms)
                if is_write:
                    rec["writes_ok"] += 1
                else:
                    got = _chaos_rows(rows)
                    if unordered:
                        got = sorted(got)
                    if got != oracle[q]:
                        rec["wrong_answers"] += 1
                        if len(mismatches) < 5:
                            mismatches.append(f"{tenant}: {q[:60]}")

    threads = [_th.Thread(target=one, args=(t, i), daemon=True)
               for t in mixes for i in range(per_tenant)]
    t_soak = time.monotonic()
    for t in threads:
        t.start()

    # --- the orchestrated membership events, chaos rotating throughout
    drain_at = t_soak + dur * 0.30
    join_at = t_soak + dur * 0.45
    next_chaos = t_soak
    w0, w3 = workers[0], None
    drain_requested = False
    seed = 0
    last_inj = None
    while time.monotonic() < stop_at:
        now = time.monotonic()
        if now >= next_chaos:
            inj = FailureInjector.from_seed(seed, max_delay_s=0.5)
            seed += 1
            sched.failure_injector = inj
            detector.injector = inj
            for w in workers:
                w.task_manager.injector = inj
            # drop spooled stage outputs so repeat fingerprints dispatch
            # REAL tasks: the soak must exercise live split placement
            # (and the drain/join membership), not replay the durable
            # spool's dedup of identical (fragment, splits) work
            sched.spool.clear()
            rec["chaos_schedules"] += 1
            if last_inj is not None:
                rec["injected_total"] += last_inj.injected_count
            last_inj = inj
            next_chaos = now + max(2.0, dur / 12.0)
        if not drain_requested and now >= drain_at:
            req = _Req(f"{w0.uri}/v1/info/state",
                       data=json.dumps({"state": "DRAINING"}).encode(),
                       method="PUT",
                       headers={"Content-Type": "application/json",
                                **internal_headers()})
            with _uo(req, timeout=10) as resp:
                assert resp.status == 200, resp.status
            drain_requested = True
        if w3 is None and now >= join_at:
            w3 = WorkerServer("soak-w3", coord.uri,
                              announce_interval_s=0.1,
                              heartbeat_interval_s=0.1,
                              catalog=session.catalog,
                              telemetry_interval_s=tel_interval).start()
            workers.append(w3)
            sched.spool.clear()   # next scans place splits on the joiner
        time.sleep(0.05)
    if last_inj is not None:
        rec["injected_total"] += last_inj.injected_count
    for t in threads:
        t.join(timeout=300)
    soak_s = time.monotonic() - t_soak
    sched.failure_injector = None
    detector.injector = None
    for w in workers:
        w.task_manager.injector = None

    # --- drain postconditions: w0 deregistered with nothing orphaned
    deadline = time.time() + 60
    while not w0.drained() and time.time() < deadline:
        time.sleep(0.05)
    rec["drain_completed"] = w0.drained()
    with coord.state.nodes_lock:
        rec["drained_node_deregistered"] = \
            w0.node_id not in coord.state.nodes
    rec["orphaned_splits"] = len(w0.task_manager.inflight()) + \
        len(w0.task_manager.unflushed())
    rec["join_received_splits"] = any(
        t.get("node") == "soak-w3" for t in sched.task_history)
    # write accounting: every acknowledged INSERT must be visible
    final = Client(coord.uri, user="gamma-audit").execute(
        "SELECT count(*) FROM memory.s.soak_log").rows
    rec["writes_visible"] = int(final[0][0]) == rec["writes_ok"]

    after = REGISTRY.snapshot()

    def delta(*key):
        return int(after.get(tuple(key), 0) - reg0.get(tuple(key), 0))

    rec["throughput_qps"] = round(rec["queries"] / max(soak_s, 1e-9), 1)
    rec["soak_seconds"] = round(soak_s, 2)
    rec["splits_migrated"] = sched.stats["splits_migrated"] - \
        stats0.get("splits_migrated", 0)
    rec["task_retries"] = sched.stats["task_retries"] - \
        stats0["task_retries"]
    rec["hedged_tasks"] = sched.stats["hedged_tasks"] - \
        stats0["hedged_tasks"]
    rec["lifecycle_transitions"] = {
        st: delta("trino_tpu_node_lifecycle_transitions_total", st)
        for st in ("ACTIVE", "DRAINING", "DRAINED", "LEFT", "FAILED")}
    rec["membership_rearbitrations"] = \
        coord.state.memory_manager.membership_rearbitrations
    rec["router_host"] = delta("trino_tpu_router_decisions_total",
                               "host")
    rec["router_device"] = delta("trino_tpu_router_decisions_total",
                                 "device")
    # --- p99-over-time from the cluster flight recorder. The SLO gate
    # reads its per-tenant p99 off the recorder's per-interval histogram
    # deltas of trino_tpu_tenant_query_seconds (the series BENCH_soak
    # emits), with the client-side latency list kept as the summary
    # p50/p99 fields --check-regressions parses.
    telemetry.collect()          # final round: flush the partial interval
    tel_samples = telemetry.recorder.since(tel_baseline_ts)
    tel_rec = {"interval_s": tel_interval,
               "samples": len(tel_samples),
               "ring_bytes": telemetry.recorder.ring_bytes(),
               "nodes": sorted({r[1] for r in telemetry.rows()}),
               "p99_series_ms": {}, "p99_ms": {},
               "interval_slo_violations": {}}
    fam = "trino_tpu_tenant_query_seconds"
    rec["tenants"] = {}
    slo_ok = True
    for tname in mixes:
        deltas = histogram_deltas(tel_samples, fam, labelval=tname)
        series, viol, merged = [], 0, {}
        for d in deltas:
            p = percentile_from_buckets(d["buckets"], 0.99)
            for le, c in d["buckets"]:
                merged[le] = merged.get(le, 0.0) + c
            if p is None:
                continue
            series.append([round(d["ts"], 3), round(p * 1000, 1)])
            if p * 1000 > slo_ms[tname]:
                viol += 1
                SOAK_SLO_VIOLATIONS.inc()
        soak_p99 = percentile_from_buckets(list(merged.items()), 0.99)
        tel_rec["p99_series_ms"][tname] = series
        tel_rec["p99_ms"][tname] = round(soak_p99 * 1000, 1) \
            if soak_p99 is not None else None
        tel_rec["interval_slo_violations"][tname] = viol
        # the gate: the recorder-derived whole-soak p99 within SLO
        ok = soak_p99 is not None and soak_p99 * 1000 <= slo_ms[tname]
        if not ok:
            SOAK_SLO_VIOLATIONS.inc()
            slo_ok = False
        vals = sorted(latencies[tname])
        p99 = round(_percentile(vals, 0.99), 1) if vals else 0.0
        rec["tenants"][tname] = {
            "queries": len(vals),
            "p50_ms": round(_percentile(vals, 0.50), 1) if vals else 0.0,
            "p99_ms": p99, "slo_ms": slo_ms[tname], "slo_ok": ok}
    # --- host/device utilization over the soak (round-21): per-interval
    # deltas of the cumulative busy counter (trino_tpu_node_busy_ms_total)
    # out of the flight-recorder ring, normalized to a fleet-wide busy
    # fraction. The counter form is what works here: the in-process fleet
    # shares one registry, so the instantaneous busy-fraction gauge is
    # last-writer-wins across workers, while counter increments from
    # every worker accumulate — the recorder's delta encoding then yields
    # exactly the busy-ms each interval saw
    fam_busy = "trino_tpu_node_busy_ms_total"
    fleet = max(1, len(workers))
    busy_series = {}
    for tier in ("device", "host"):
        pts = []
        for s in tel_samples:
            iv_ms = s.get("interval_s", 0.0) * 1000
            if iv_ms <= 0:
                continue
            delta = s["values"].get(f"{fam_busy}|{tier}", 0.0)
            pts.append([round(s["ts"], 3),
                        round(min(1.0, delta / (iv_ms * fleet)), 4)])
        busy_series[tier] = pts
    tel_rec["busy_fraction_series"] = busy_series
    tel_rec["busy_fraction_mean"] = {
        tier: (round(sum(v for _, v in pts) / len(pts), 4) if pts
               else None)
        for tier, pts in busy_series.items()}
    rec["telemetry"] = tel_rec
    # live-stats folds landed (heartbeats actually streamed) + the
    # per-node utilization view the folds produced
    rec["live_stats_folds"] = coord.state.livestats.folds
    rec["utilization"] = coord.state.livestats.utilization()
    # the fair-share acceptance, stated explicitly: the saturating scan
    # tenant did not push the point tenant past its SLO
    rec["fair_share_held"] = rec["tenants"]["alpha"]["slo_ok"]
    if mismatches:
        rec["sample_failures"] = mismatches
    rec["elapsed_s"] = round(time.monotonic() - t_start, 1)
    rec["passed"] = (rec["wrong_answers"] == 0 and
                     rec["failed_queries"] == 0 and
                     rec["orphaned_splits"] == 0 and
                     rec["drain_completed"] and
                     rec["drained_node_deregistered"] and
                     rec["join_received_splits"] and
                     rec["writes_visible"] and
                     rec["queries"] > 0 and
                     slo_ok)
    detector.stop()
    coord.state.memory_manager.stop()
    for w in workers:
        w.stop()
    coord.stop()
    if out_path:
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=1)
    print(json.dumps(rec), flush=True)
    return rec


# ---------------------------------------------------------------------------
# --cold-start: fresh-process cold walls vs in-process steady walls
# ---------------------------------------------------------------------------

COLD_QUERIES = {"q3": Q3, "q5": Q5, "q6": Q6}


def _cold_child(query: str) -> int:
    """Child half of --cold-start: one fresh-process execution of the
    named query, timed end to end — everything a cold coordinator pays
    (interpreter start already spent, then imports, planning, ingest,
    and XLA compiles).

    With TRINO_TPU_PREWARM on, the child first runs the AOT warm the
    coordinator would run at boot (PrewarmEngine.warm_fingerprint, off
    the measured path), then times the first query-path execution —
    the cold latency the prewarm subsystem actually delivers. With
    prewarm off it times the raw unwarmed cold path (the baseline the
    parent reports as `seed_ms`). Emits one JSON line and exits."""
    t_start = time.monotonic()
    from trino_tpu.exec.prewarm import (PrewarmEngine,
                                        prewarm_enabled_by_env)
    from trino_tpu.exec.profiler import RECORDER
    from trino_tpu.exec.session import Session
    from trino_tpu.server.history import plan_fingerprint
    schema = os.environ.get("TRINO_TPU_COLD_SCHEMA", "tiny")
    session = Session(default_schema=schema)
    sql = COLD_QUERIES[query]
    prewarmed = False
    if prewarm_enabled_by_env():
        eng = PrewarmEngine(session=session, enabled=True)
        prewarmed = eng.warm_fingerprint(plan_fingerprint(sql), sql)
    before = RECORDER.totals()
    t0 = time.monotonic()
    res = session.execute(sql)
    cold_ms = (time.monotonic() - t0) * 1000
    tot = RECORDER.totals()
    print(json.dumps({
        "metric": "cold_child", "query": query,
        "cold_ms": round(cold_ms, 1),
        "startup_ms": round((t0 - t_start) * 1000, 1),
        "rows": len(res.rows), "prewarmed": prewarmed,
        "fresh_compiles": tot["compiles"] - before["compiles"],
        "prewarm_hits": tot["prewarmHits"],
        "compile_s": tot["compileSeconds"]}), flush=True)
    return 0


def cold_start(queries=None, cold_runs=None, steady_runs=None,
               out_path="BENCH_cold_r01.json", ratio_gate=3.0):
    """Cold-start gate: fresh-process cold walls vs in-process steady
    walls for the headline TPC-H shapes.

    Every cold sample is a subprocess (`bench.py --cold-child q`), so it
    pays real imports, planning, ingest, and XLA compiles — nothing
    in-process trace caches can hide. Per query: one prewarm-OFF child
    measures the raw unwarmed cold wall (reported as `seed_ms`, the
    worst case; it also seeds the shared persistent compile cache),
    then the timed children run the boot-time AOT warm first and
    measure the first query-path execution — the cold start the
    prewarm subsystem actually delivers. The children share one compile
    cache: where JAX_COMPILATION_CACHE_DIR is set, that directory,
    otherwise the fixed in-checkout path (placed explicitly so CPU-only
    children cache too).
    Gate: prewarmed cold / steady < ratio_gate for every query.

    One process per chip: EVERY child runs before this parent first
    touches JAX (a parent that has run a query holds the chip, and a
    child that needs it then fails or hangs), so the in-process steady
    walls are all measured after the last child has exited."""
    import statistics as _st
    import subprocess
    import sys as _sys
    queries = queries or list(COLD_QUERIES)
    cold_runs = int(cold_runs or
                    os.environ.get("TRINO_TPU_COLD_RUNS", 2))
    steady_runs = int(steady_runs or 5)
    schema = os.environ.get("TRINO_TPU_COLD_SCHEMA", "tiny")
    env = dict(os.environ)
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".jax_cache"))

    def child(q, prewarm):
        cenv = dict(env)
        cenv["TRINO_TPU_PREWARM"] = "1" if prewarm else "0"
        p = subprocess.run(
            [_sys.executable, os.path.abspath(__file__),
             "--cold-child", q],
            capture_output=True, text=True, env=cenv,
            cwd=os.path.dirname(os.path.abspath(__file__)), timeout=600)
        rec = None
        for line in p.stdout.splitlines():
            line = line.strip()
            if line.startswith("{"):
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
        if rec is None:
            raise RuntimeError(
                f"cold child {q} produced no record (rc={p.returncode}): "
                f"{p.stderr[-500:]}")
        return rec

    # unwarmed worst case first (it also populates the shared XLA
    # cache), then the prewarmed timed children — for every query,
    # before the parent imports the engine
    seeds = {q: child(q, prewarm=False) for q in queries}
    all_colds = {q: [child(q, prewarm=True) for _ in range(cold_runs)]
                 for q in queries}

    from trino_tpu.exec.session import Session
    steady_session = Session(default_schema=schema)
    records, passed = [], True
    for q in queries:
        seed, colds = seeds[q], all_colds[q]
        cold_ms = _st.median(c["cold_ms"] for c in colds)
        steady_session.execute(COLD_QUERIES[q])     # in-process warm
        walls = []
        for _ in range(steady_runs):
            t0 = time.monotonic()
            steady_session.execute(COLD_QUERIES[q])
            walls.append((time.monotonic() - t0) * 1000)
        steady_ms = _st.median(walls)
        ratio = cold_ms / max(steady_ms, 1e-6)
        ok = ratio < ratio_gate
        passed = passed and ok
        records.append({
            "query": q, "cold_ms": round(cold_ms, 1),
            "cold_runs": [c["cold_ms"] for c in colds],
            "seed_ms": seed["cold_ms"],
            "startup_ms": round(_st.median(
                c["startup_ms"] for c in colds), 1),
            "fresh_compiles": colds[-1]["fresh_compiles"],
            "prewarm_hits": colds[-1].get("prewarm_hits", 0),
            "steady_ms": round(steady_ms, 1),
            "ratio": round(ratio, 2), "passed": ok})
        print(json.dumps({"metric": "cold_start_progress", **records[-1]}),
              flush=True)
    rec = {"metric": "cold_start", "schema": schema,
           "ratio_gate": ratio_gate, "cold_runs": cold_runs,
           "steady_runs": steady_runs,
           "compile_cache": env["JAX_COMPILATION_CACHE_DIR"],
           "records": records, "passed": passed}
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec), flush=True)
    return rec


# ---------------------------------------------------------------------------
# --check-regressions: history-based latency gate over BENCH_r*.json
# ---------------------------------------------------------------------------

def load_bench_round(path, key="tpu_steady_ms"):
    """Extract per-config steady-state walls from one BENCH round file.

    Accepts the driver format ({"n","cmd","rc","tail"} where `tail`
    carries the emitted JSON lines — the LAST parseable line wins, the
    same cumulative-emit contract bench uses) or a raw emitted record.
    Returns {config: <key>} — `key` is the one the series' producer
    writes — or None when the round produced no
    usable record (e.g. an rc=124 driver kill before the first emit)."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    if isinstance(doc, dict) and "tail" in doc:
        recs = []
        for line in doc["tail"].splitlines():
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                recs.append(json.loads(line))
            except ValueError:
                continue              # torn tail line
        doc = recs[-1] if recs else None
    if not isinstance(doc, dict):
        return None
    if str(doc.get("metric", "")).startswith("scan_micro"):
        # --scan-micro rounds gate on the pruned-scan walls per
        # selectivity plus the two prefetch-pipeline walls: a slower
        # pruned scan or pipeline in a later round reads as a
        # regressed scan_micro_* config
        out = {}
        for r in doc.get("records", ()):
            ms = r.get("prune_on_ms")
            if ms is not None:
                out[f"scan_micro_sel{r['selectivity']}"] = float(ms)
        for depth, d in (doc.get("prefetch") or {}).items():
            if isinstance(d, dict) and "wall_ms" in d:
                out[f"scan_micro_prefetch_{depth}"] = float(d["wall_ms"])
        return out or None
    if str(doc.get("metric", "")) == "soak":
        # --soak rounds gate on per-tenant p99s (the SLO surface) plus
        # overall throughput inverted into a wall-like number so a
        # throughput collapse reads as a regression under the same
        # bigger-is-worse median+MAD rule
        out = {}
        for tname, d in (doc.get("tenants") or {}).items():
            if isinstance(d, dict) and "p99_ms" in d:
                out[f"soak_{tname}_p99"] = float(d["p99_ms"])
        qps = doc.get("throughput_qps")
        if qps:
            out["soak_ms_per_query"] = 1000.0 / float(qps)
        return out or None
    if str(doc.get("metric", "")) == "write_chaos":
        # --write-chaos rounds gate on the per-chaos-point commit walls:
        # a slower staged-write/commit/publish path in a later round
        # reads as a regressed write_chaos_* config (correctness — lost
        # or duplicate rows, orphans — already hard-fails the soak)
        out = {}
        for point, d in (doc.get("points") or {}).items():
            if isinstance(d, dict) and "p50_ms" in d:
                out[f"write_chaos_{point.lower()}_p50"] = float(d["p50_ms"])
        return out or None
    if str(doc.get("metric", "")) == "overload":
        # --overload rounds gate on the enforcement latencies: a slower
        # cancel-to-terminal fan-out or a bigger deadline overshoot in
        # a later round reads as a regressed overload_* config
        # (correctness — wrong answers, leaked tasks, undrained pools —
        # already hard-fails the soak itself)
        out = {}
        for key, cfg in (("cancel_terminal_p50_ms", "overload_cancel_p50"),
                         ("cancel_terminal_max_ms", "overload_cancel_max"),
                         ("deadline_overshoot_p50_ms",
                          "overload_deadline_overshoot_p50")):
            if doc.get(key) is not None:
                out[cfg] = float(doc[key])
        return out or None
    if str(doc.get("metric", "")) == "coordinator_chaos":
        # --coordinator-chaos rounds gate on the failover-to-first-
        # result walls: a slower promotion/replay/resume path in a
        # later round reads as a regressed coordinator_chaos_* config
        # (correctness — wrong/lost/duplicate rows or client-visible
        # errors — already hard-fails the soak itself)
        out = {}
        for pct in ("p50", "p99"):
            ms = doc.get(f"failover_to_result_{pct}_ms")
            if ms is not None:
                out[f"coordinator_chaos_failover_{pct}"] = float(ms)
        return out or None
    if str(doc.get("metric", "")) == "cold_start":
        # --cold-start rounds gate on the fresh-process cold wall AND
        # the cold/steady ratio per query: a compile-cache or prewarm
        # break in a later round shows as a blown-up cold_q* config
        out = {}
        for r in doc.get("records", ()):
            if r.get("cold_ms") is not None:
                out[f"cold_{r['query']}"] = float(r["cold_ms"])
            if r.get("ratio") is not None:
                out[f"cold_{r['query']}_ratio"] = float(r["ratio"])
        return out or None
    detail = doc.get("detail", doc)
    out = {}
    for cfg, d in detail.items():
        if not isinstance(d, dict):
            continue
        ms = d.get(key)
        if ms is not None:
            out[cfg] = float(ms)
    return out or None


def check_regressions(paths=None, ratio=None, mad_k=None,
                      min_prior=2, key="tpu_steady_ms"):
    """Diff the newest BENCH_r*.json round against the prior rounds'
    per-config baselines with the SAME median+MAD rule the query-history
    detector applies (server/history.py): a config regresses when its
    steady wall exceeds median * ratio AND the robust MAD envelope.
    Returns (ok, report); configs with fewer than `min_prior` baseline
    rounds are reported but never judged."""
    import glob as _glob

    from trino_tpu.server.history import (MAD_K, RATIO, is_regressed,
                                          robust_baseline)
    ratio = RATIO if ratio is None else ratio
    mad_k = MAD_K if mad_k is None else mad_k
    if paths is None:
        paths = sorted(_glob.glob("BENCH_r*.json"))
    rounds = [(p, load_bench_round(p, key)) for p in paths]
    rounds = [(p, r) for p, r in rounds if r]
    report = {"metric": "bench_regression_check", "rounds": len(rounds),
              "configs": {}, "regressions": []}
    if len(rounds) < 2:
        report["note"] = "need at least 2 parseable rounds to compare"
        return True, report
    latest_path, latest = rounds[-1]
    report["latest"] = latest_path
    for cfg, cur in sorted(latest.items()):
        prior = [r[cfg] for _, r in rounds[:-1] if cfg in r]
        entry = {"steady_ms": cur, "baseline_rounds": len(prior)}
        if len(prior) < min_prior:
            entry["status"] = "insufficient-baseline"
        else:
            med, mad = robust_baseline(prior)
            entry["baseline_median_ms"] = round(med, 1)
            entry["baseline_mad_ms"] = round(mad, 1)
            if is_regressed(cur, med, mad, ratio=ratio, mad_k=mad_k):
                entry["status"] = "REGRESSED"
                report["regressions"].append(cfg)
            else:
                entry["status"] = "ok"
        report["configs"][cfg] = entry
    return not report["regressions"], report


# ---------------------------------------------------------------------------

def run_config(session, sql, runs=RUNS, prewarm=PREWARM):
    """End-to-end timings: cold (first exec: compiles + ingest), then
    steady-state median."""
    t0 = time.monotonic()
    result = session.execute(sql)
    cold_ms = (time.monotonic() - t0) * 1000
    for _ in range(max(0, prewarm - 1)):
        session.execute(sql)
    times = []
    for _ in range(runs):
        t0 = time.monotonic()
        result = session.execute(sql)
        times.append((time.monotonic() - t0) * 1000)
    return result, cold_ms, statistics.median(times)


def op_stats(session, reg_before=None):
    """Per-config operator attribution for the BENCH payloads: the
    executor's adaptive-path counters (nonzero only) plus per-operator
    dispatch wall-ms deltas from the metrics registry — so the perf
    trajectory names operators, not just end-to-end walls."""
    import dataclasses
    from trino_tpu.metrics import REGISTRY
    st = {k: v for k, v in
          dataclasses.asdict(session.executor.stats).items() if v}
    out = {"exec": st}
    if reg_before is not None:
        after = REGISTRY.snapshot()
        wall = {}
        for key, v in after.items():
            if key[0] == "trino_tpu_operator_wall_ms_total":
                d = v - reg_before.get(key, 0)
                if d > 0:
                    wall[key[1]] = round(d, 1)
        out["operator_wall_ms"] = wall
        key = ("trino_tpu_task_output_bytes_total",)
        out["bytes_shuffled"] = int(after.get(key, 0) -
                                    reg_before.get(key, 0))
        key = ("trino_tpu_operator_rows_total", "scan")
        out["rows_scanned"] = int(after.get(key, 0) -
                                  reg_before.get(key, 0))
    return out


def reg_snapshot():
    from trino_tpu.metrics import REGISTRY
    return REGISTRY.snapshot()


def budget_left(frac):
    return (time.monotonic() - T0) < BUDGET_S * frac


def cached_baseline(key: str, fn):
    """CPU baselines are deterministic per dataset, so their (result,
    wall) pair is measured once per machine and cached beside the table
    cache — the same once-per-machine treatment as datagen. The cached
    cpu_ms is the wall measured on this host on first computation."""
    import pickle
    from trino_tpu.connectors.diskcache import cache_root
    os.makedirs(cache_root(), exist_ok=True)
    path = os.path.join(cache_root(), f"baseline_{key}.pkl")
    if os.path.exists(path):
        with open(path, "rb") as f:
            rec = pickle.load(f)
        return rec["result"], rec["cpu_ms"], True
    t0 = time.monotonic()
    result = fn()
    cpu_ms = (time.monotonic() - t0) * 1000
    with open(path, "wb") as f:
        pickle.dump({"result": result, "cpu_ms": cpu_ms}, f)
    return result, cpu_ms, False


def build_parser():
    """Flag-style subcommands (each former ad-hoc `"--x" in sys.argv`
    check is now a declared argparse flag, so `--help` documents the
    full surface and typos fail loudly instead of silently running the
    default bench). Exactly one mode runs per invocation; with no mode
    flag the TPC-H e2e bench runs as before."""
    import argparse
    p = argparse.ArgumentParser(
        prog="bench.py",
        description="trino-tpu driver benchmark and operational soaks "
                    "(one JSON line per result)")
    mode = p.add_argument_group("modes (default: TPC-H e2e bench)")
    mode.add_argument("--chaos", action="store_true",
                      help="seeded fault-injection soak -> "
                           "BENCH_chaos.json")
    mode.add_argument("--write-chaos", action="store_true",
                      help="exactly-once write soak: seeded kills at "
                           "WRITE_STAGE/WRITE_COMMIT/WRITE_PUBLISH, "
                           "0 lost/0 dup rows + 0 orphans required -> "
                           "BENCH_write_chaos.json")
    mode.add_argument("--overload", action="store_true",
                      help="deadline/cancellation/admission-control "
                           "soak: saturating load + HANG faults + "
                           "mass-cancel wave -> BENCH_overload.json")
    mode.add_argument("--coordinator-chaos", action="store_true",
                      help="seeded coordinator-kill failover soak "
                           "(primary + warm standby, kill at every "
                           "query phase) -> BENCH_coordinator_chaos"
                           ".json")
    mode.add_argument("--memory-pressure", action="store_true",
                      help="concurrent soak at 25%% pool -> "
                           "BENCH_memory.json")
    mode.add_argument("--scan-micro", action="store_true",
                      help="zone-map pruning + prefetch pipeline "
                           "scan-path microbench across predicate "
                           "selectivities -> BENCH_scan_micro.json")
    mode.add_argument("--cold-start", action="store_true",
                      help="fresh-process cold walls vs in-process "
                           "steady walls for q3/q5/q6 (prewarm + shared "
                           "compile cache on for the children) -> "
                           "BENCH_cold_r01.json; exit 1 when any "
                           "cold/steady ratio >= 3")
    p.add_argument("--cold-child", metavar="QUERY",
                   help=argparse.SUPPRESS)
    mode.add_argument("--check-regressions", action="store_true",
                      help="gate the newest BENCH_r*.json round against "
                           "prior rounds (median+MAD); exit 1 on a "
                           "regression")
    mode.add_argument("--concurrency", action="store_true",
                      help="high-concurrency serving soak (plan/result "
                           "caches, CPU/TPU routing, micro-batching) -> "
                           "BENCH_concurrency.json")
    mode.add_argument("--soak", action="store_true",
                      help="sustained elastic-membership soak: mixed "
                           "multi-tenant load + chaos + drain/join "
                           "mid-run -> BENCH_soak.json")
    soak = p.add_argument_group("--soak options")
    soak.add_argument("--duration", type=float, default=None,
                      help="soak duration seconds (default: 180 or "
                           "TRINO_TPU_SOAK_DURATION_S)")
    conc = p.add_argument_group("--concurrency options")
    conc.add_argument("--clients", type=int, default=None,
                      help="concurrent clients (default: 120 or "
                           "TRINO_TPU_CONCURRENCY_CLIENTS)")
    conc.add_argument("--queries-per-client", type=int, default=None,
                      help="statements each client runs (default: 5)")
    gate = p.add_argument_group("--check-regressions options")
    gate.add_argument("--rounds-glob", default="BENCH_r*.json",
                      help="round files to diff (default: BENCH_r*.json)")
    gate.add_argument("--ratio", type=float, default=None,
                      help="regression ratio gate (default: history "
                           "detector's 2.0)")
    gate.add_argument("--mad-k", type=float, default=None,
                      help="MAD envelope multiplier (default: 6.0)")
    return p


def main(argv=None):
    args = build_parser().parse_args(argv)
    if args.cold_child:
        return _cold_child(args.cold_child)
    if args.cold_start:
        rec = cold_start()
        return 0 if rec["passed"] else 1
    if args.chaos:
        chaos_soak()
        return 0
    if args.write_chaos:
        rec = write_chaos_soak()
        return 0 if rec["passed"] else 1
    if args.overload:
        rec = overload_soak()
        return 0 if rec["passed"] else 1
    if args.coordinator_chaos:
        rec = coordinator_chaos_soak()
        return 0 if rec["passed"] else 1
    if args.memory_pressure:
        memory_pressure_soak()
        return 0
    if args.scan_micro:
        scan_micro()
        return 0
    if args.concurrency:
        rec = concurrency_soak(n_clients=args.clients,
                               queries_per_client=args.queries_per_client)
        return 0 if rec["passed"] else 1
    if args.soak:
        rec = elastic_soak(duration_s=args.duration)
        return 0 if rec["passed"] else 1
    if args.check_regressions:
        import glob as _glob
        ok, report = check_regressions(
            sorted(_glob.glob(args.rounds_glob)),
            ratio=args.ratio, mad_k=args.mad_k)
        # the scan-path trajectory gates as its own series the same way
        # (BENCH_scan_micro.json + later rounds' BENCH_scan_micro_r*.json)
        scan_paths = sorted(_glob.glob("BENCH_scan_micro*.json"))
        if scan_paths:
            ok4, report4 = check_regressions(scan_paths,
                                             ratio=args.ratio,
                                             mad_k=args.mad_k)
            report["scan_micro"] = report4
            ok = ok and ok4
        # the elastic soak gates as its own series (BENCH_soak.json +
        # later rounds' BENCH_soak_r*.json): a per-tenant p99 SLO
        # blowout or a throughput collapse in a later round fails here
        soak_paths = sorted(_glob.glob("BENCH_soak*.json"))
        if soak_paths:
            ok5, report5 = check_regressions(soak_paths,
                                             ratio=args.ratio,
                                             mad_k=args.mad_k)
            report["soak"] = report5
            ok = ok and ok5
        # the exactly-once write trajectory gates as its own series
        # (BENCH_write_chaos.json + later rounds'
        # BENCH_write_chaos_r*.json): a slower commit path at any chaos
        # point in a later round fails here
        wc_paths = sorted(_glob.glob("BENCH_write_chaos*.json"))
        if wc_paths:
            ok8, report8 = check_regressions(wc_paths,
                                             ratio=args.ratio,
                                             mad_k=args.mad_k)
            report["write_chaos"] = report8
            ok = ok and ok8
        # the lifecycle-enforcement trajectory gates as its own series
        # (BENCH_overload.json + later rounds' BENCH_overload_r*.json):
        # a slower cancel fan-out or deadline overshoot fails here
        ovl_paths = sorted(_glob.glob("BENCH_overload*.json"))
        if ovl_paths:
            ok10, report10 = check_regressions(ovl_paths,
                                               ratio=args.ratio,
                                               mad_k=args.mad_k)
            report["overload"] = report10
            ok = ok and ok10
        # the coordinator-failover trajectory gates as its own series
        # (BENCH_coordinator_chaos.json + later rounds'
        # BENCH_coordinator_chaos_r*.json): a slower failover-to-first-
        # result wall in a later round fails here
        cc_paths = sorted(_glob.glob("BENCH_coordinator_chaos*.json"))
        if cc_paths:
            ok9, report9 = check_regressions(cc_paths,
                                             ratio=args.ratio,
                                             mad_k=args.mad_k)
            report["coordinator_chaos"] = report9
            ok = ok and ok9
        # the cold-start trajectory gates as its own series
        # (BENCH_cold_r*.json): a regressed fresh-process cold wall or
        # cold/steady ratio in a later round fails here
        cold_paths = sorted(_glob.glob("BENCH_cold*.json"))
        if cold_paths:
            ok6, report6 = check_regressions(cold_paths,
                                             ratio=args.ratio,
                                             mad_k=args.mad_k)
            report["cold_start"] = report6
            ok = ok and ok6
        # the multichip trajectory gates as its own series too: each
        # driver round lands a MULTICHIP_r*.json whose tail carries the
        # dryrun's emitted JSON line (rounds before the partitioned-join
        # step emitted none, and one that named a virtual-CPU wall
        # tpu_steady_ms — they parse to nothing and are skipped)
        mc_paths = sorted(_glob.glob("MULTICHIP_r*.json"))
        if mc_paths:
            ok3, report3 = check_regressions(
                mc_paths, ratio=args.ratio, mad_k=args.mad_k,
                key="virtual_cpu_steady_ms")
            report["multichip"] = report3
            ok = ok and ok3
        print(json.dumps(report), flush=True)
        return 0 if ok else 1
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench.py: the default mode measures a TPU and JAX found "
              f"{dev.platform} ({dev.device_kind}); nothing was run. "
              f"Off-chip, use the tests or the named --*-micro / gate "
              f"modes.", file=sys.stderr, flush=True)
        return 1
    threading.Thread(target=_watchdog, daemon=True).start()
    from trino_tpu.exec.session import Session
    _detail.update({"device": str(dev),
                    "device_kind": dev.device_kind,
                    "device_count": len(jax.devices()),
                    "prewarm": PREWARM, "runs": RUNS,
                    "budget_s": BUDGET_S})
    only = os.environ.get("TRINO_TPU_BENCH_ONLY", "")
    configs = only.split(",") if only else ["q5", "q6", "q3"]

    # ---- config 4 FIRST: q5-shaped SF100, chunked -------------------
    # Emitted first (round-3 verdict: order configs by information
    # value so a driver timeout can't starve the most important one).
    # The fact table's q5 columns live device-resident in narrowed
    # dtypes (7.8 GB in HBM, exec/device_cache.py); the chunked driver
    # slices chunks from HBM, so steady state never crosses the host
    # link. Cold pays one narrowed ingest + XLA compiles.
    if "q5" in configs and \
            os.environ.get("TRINO_TPU_BENCH_SKIP_SF100") != "1":
        scale = float(os.environ.get("TRINO_TPU_BENCH_SF100_SCALE", 100))
        t0 = time.monotonic()
        tables100 = q5_tables(scale)
        gen_s = time.monotonic() - t0
        from trino_tpu.catalog import Catalog
        cat = Catalog()
        cat.register("bench", BenchConnector(tables100, "q5"))
        s100 = Session(catalog=cat, default_cat="bench",
                       default_schema="q5")
        chunk = int(os.environ.get("TRINO_TPU_BENCH_CHUNK_ROWS",
                                   33_554_432))
        s100.properties["spill_chunk_rows"] = chunk
        s100.executor.spill_chunk_rows = chunk
        cpu_q5, cpu_q5_ms, _ = cached_baseline(
            f"q5_sf{scale:g}", lambda: numpy_q5(tables100))
        reg0 = reg_snapshot()
        res, cold, steady = run_config(s100, Q5, runs=1, prewarm=1)
        got = [(r[0], round(float(r[1]), 2)) for r in res.rows]
        want = [(n, round(v, 2)) for n, v in cpu_q5]
        assert got == want, (got[:3], want[:3])
        st = s100.executor.stats
        _detail["q5_sf100"] = {
            "tpu_cold_ms": round(cold, 1),
            "tpu_steady_ms": round(steady, 1),
            "cpu_ms": round(cpu_q5_ms, 1),
            "speedup": round(cpu_q5_ms / steady, 2),
            "gen_s": round(gen_s, 1), "scale": scale,
            "rows_lineitem": tables100["lineitem"].num_rows,
            "chunked": True, "verified": True,
            "fact_cache_chunks": st.fact_cache_chunks,
            "chunk_lut_joins": st.chunk_lut_joins,
            "operator_stats": op_stats(s100, reg0),
            "note": "steady slices device-resident narrowed columns; "
                    "cold pays one narrowed ingest over the host link"}
        emit()
        del s100, tables100, cat

    # ---- config 2: q6 SF1 end-to-end --------------------------------
    if "q6" in configs and budget_left(0.92):
        t0 = time.monotonic()
        session = Session(default_schema="sf1")
        tables = {"lineitem": session.catalog.get_table("tpch", "sf1",
                                                        "lineitem")}
        gen1_s = time.monotonic() - t0
        cpu_q6, cpu_q6_ms, _ = cached_baseline("q6_sf1",
                                               lambda: numpy_q6(tables))
        reg0 = reg_snapshot()
        res, cold, steady = run_config(session, Q6)
        got = float(res.rows[0][0])
        assert abs(got - cpu_q6 / 1e4) < 1e-2, (got, cpu_q6 / 1e4)
        _detail["q6_sf1"] = {
            "tpu_cold_ms": round(cold, 1),
            "tpu_steady_ms": round(steady, 1),
            "cpu_ms": round(cpu_q6_ms, 1), "gen_s": round(gen1_s, 1),
            "speedup": round(cpu_q6_ms / steady, 2), "verified": True,
            "operator_stats": op_stats(session, reg0)}
        emit()

    # ---- config 3: q3 SF10 end-to-end -------------------------------
    # 0.85: with the round-5 caches q3 runs warm in ~60-90 s, so it can
    # still land before the watchdog even after a slow q5 cold
    if "q3" in configs and budget_left(0.85):
        t0 = time.monotonic()
        session10 = Session(default_schema="sf10")
        tables10 = {t: session10.catalog.get_table("tpch", "sf10", t)
                    for t in ["customer", "orders", "lineitem"]}
        gen10_s = time.monotonic() - t0
        cpu_q3, cpu_q3_ms, _ = cached_baseline(
            "q3_sf10", lambda: numpy_q3(tables10))
        reg0 = reg_snapshot()
        res, cold, steady = run_config(session10, Q3)
        got = [(int(r[0]), round(float(r[1]), 2)) for r in res.rows]
        want = [(k, round(v, 2)) for k, v in cpu_q3]
        assert got == want, (got[:3], want[:3])
        _detail["q3_sf10"] = {
            "tpu_cold_ms": round(cold, 1),
            "tpu_steady_ms": round(steady, 1),
            "cpu_ms": round(cpu_q3_ms, 1), "gen_s": round(gen10_s, 1),
            "speedup": round(cpu_q3_ms / steady, 2), "verified": True,
            "operator_stats": op_stats(session10, reg0)}
        emit()
        del session10, tables10

    emit(final=True)
    return 0


if __name__ == "__main__":
    sys.exit(main() or 0)
