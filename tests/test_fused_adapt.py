"""Adaptive fused-chunk re-optimization (AdaptivePlanner.java:87's role,
replayed through the cross-run decision cache): a plain first run
measures per-join probe-key spans; later runs compile a windowed-gather
variant sized by those measurements, with an in-program correctness
flag that forces a plain rerun when new data violates the guesses.
"""

import numpy as np
import pytest

from trino_tpu.exec.session import Session

Q = """
SELECT o_orderpriority, count(*) AS c, sum(l_quantity) AS q
FROM lineitem, orders
WHERE l_orderkey = o_orderkey AND o_orderdate >= DATE '1996-01-01'
GROUP BY o_orderpriority ORDER BY o_orderpriority
"""


@pytest.fixture
def chunked_session():
    s = Session(default_schema="tiny")
    s.properties["spill_chunk_rows"] = 8192
    s.executor.spill_chunk_rows = 8192
    return s


def test_adaptation_records_then_applies(chunked_session):
    s = chunked_session
    ex = s.executor
    want = s.execute(Q).rows
    assert ex.stats.fused_chunk_pipelines >= 1
    skey = None
    recs = [k for k in ex._decision_cache if k[0] == "fusedadapt"]
    assert recs, "plain run must record span measurements"
    rec = ex._decision_cache[recs[0]]
    assert len(rec) >= 1 and all(v >= 0 for v in rec)

    # second run compiles the adapted program and must match exactly
    got = s.execute(Q).rows
    assert got == want


def test_violation_falls_back_to_plain(chunked_session):
    """Poison the recorded measurements so the adapted program's window
    is far too small: the in-program flag must catch it and the plain
    rerun must still produce correct results."""
    s = chunked_session
    ex = s.executor
    want = s.execute(Q).rows
    recs = [k for k in ex._decision_cache if k[0] == "fusedadapt"]
    assert recs
    key = recs[0]
    n = len(ex._decision_cache[key])
    ex._decision_cache[key] = tuple([8] * n)     # absurdly small
    got = s.execute(Q).rows
    assert got == want
    # the poisoned record was dropped (plain rerun re-measures next run)
    rec = ex._decision_cache.get(key)
    assert rec is None or rec != tuple([8] * n)


def test_mid_query_data_is_not_recorded_for_mutable_catalogs():
    from trino_tpu.catalog import Catalog
    from trino_tpu.connectors.memory import MemoryConnector
    cat = Catalog()
    cat.register("m", MemoryConnector())
    s = Session(catalog=cat, default_cat="m", default_schema="s")
    s.properties["spill_chunk_rows"] = 1024
    s.executor.spill_chunk_rows = 1024
    s.execute("CREATE TABLE m.s.f (k bigint, v bigint)")
    s.execute("CREATE TABLE m.s.d (k bigint, w bigint)")
    rows = ", ".join(f"({i % 97}, {i})" for i in range(3000))
    s.execute(f"INSERT INTO m.s.f VALUES {rows}")
    s.execute("INSERT INTO m.s.d SELECT DISTINCT k, k * 2 FROM m.s.f")
    q = ("SELECT sum(v + w) FROM m.s.f, m.s.d WHERE f.k = d.k")
    r1 = s.execute(q).rows
    r2 = s.execute(q).rows
    assert r1 == r2
    assert not [k for k in s.executor._decision_cache
                if k[0] == "fusedadapt"]


def test_direct_agg_cutoff_is_stats_driven():
    """Sparse groups (few rows per group) take the sort kernel even when
    the domain product fits the direct bound; dense groups keep the
    direct strategy. Session property direct_agg_max_groups tunes the
    bound (GroupByHash.java:82-93's strategy choice)."""
    from trino_tpu.sql.parser import parse
    s = Session(default_schema="tiny")

    def strategy_of(sql):
        rel = s.planner().plan_query(parse(sql))
        from trino_tpu.planner import logical as L

        def find(n):
            if isinstance(n, L.AggregateNode):
                return n
            for c in L.children(n):
                f = find(c)
                if f is not None:
                    return f
            return None
        return find(rel.node).strategy

    # lineitem tiny = 60k rows over 3 flags -> dense: direct
    assert strategy_of(
        "SELECT l_returnflag, count(*) FROM lineitem "
        "GROUP BY l_returnflag") == "direct"
    # region: 5 rows over a 5-value dictionary -> 1 row/group: sort
    assert strategy_of(
        "SELECT r_name, count(*) FROM region GROUP BY r_name") == "sort"
    # property forces the bound down
    s.properties["direct_agg_max_groups"] = 1
    assert strategy_of(
        "SELECT l_returnflag, count(*) FROM lineitem "
        "GROUP BY l_returnflag") == "sort"
    s.properties["direct_agg_max_groups"] = 64


def test_transfer_encodings_roundtrip():
    """Delta/plane transfer encodings decode to the original narrow
    column on device (device_cache ingest path)."""
    import numpy as np

    from trino_tpu.exec.device_cache import (decode_transfer,
                                             encode_transfer)
    rng = np.random.default_rng(3)
    cases = [
        np.sort(rng.integers(0, 1 << 30, 100_000)).astype(np.int32),
        rng.integers(-1 << 40, 1 << 40, 50_000).astype(np.int64),
        rng.integers(0, 120, 10_000).astype(np.int8),
        rng.integers(0, 1 << 15, 30_000).astype(np.int16),
        np.arange(100_000, dtype=np.int32) * 3,          # pure delta
        rng.normal(size=1000),                           # float: raw
        np.asarray([7], dtype=np.int32),                 # size<2: raw
    ]
    import jax
    for arr in cases:
        enc, payload, meta = encode_transfer(arr)
        meta = dict(meta, enc=enc, dtype=str(arr.dtype))
        dev = decode_transfer(enc, jax.device_put(
            np.ascontiguousarray(payload)), meta)
        got = np.asarray(dev)
        assert got.dtype == arr.dtype, (enc, got.dtype, arr.dtype)
        assert np.array_equal(got, arr), enc


def test_fact_cache_disk_tier_detects_changed_table(tmp_path, monkeypatch):
    """A regenerated table (same name, new contents) must not serve the
    stale narrowed cache (fingerprint check)."""
    import numpy as np

    from trino_tpu.batch import Field, Schema
    from trino_tpu.exec.device_cache import FactTableCache
    from trino_tpu.types import BIGINT
    monkeypatch.setenv("TRINO_TPU_DATA_CACHE", str(tmp_path))

    class T:
        def __init__(self, vals):
            self.columns = [np.asarray(vals, dtype=np.int64)]
            self.valids = None
            self.num_rows = len(vals)
            self.schema = Schema.of(Field("x", BIGINT))

    fc = FactTableCache()
    key = ("bench", "s", "t", (0,))
    t1 = T(np.arange(10_000))
    c1 = fc.load(key, t1, [0], persist_ok=True)
    assert np.asarray(c1[0].data)[5] == 5
    fc.invalidate()
    t2 = T(np.arange(10_000) * 7)       # regenerated, same shape
    c2 = fc.load(key, t2, [0], persist_ok=True)
    assert np.asarray(c2[0].data)[5] == 35
