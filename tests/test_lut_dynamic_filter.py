"""Where a split's join probes the task's LUT, no dynamic filter runs in
front of it (`Executor._run_join_inner`, PR 50): a probe key outside the
pinned build's key range has no LUT entry, so the LUT's miss is the range
test. Held here: the output is the range-filtered join's, array for
array; a task's laps run no `_dynamic_filter` and say so; every other
join keeps the filter.
"""

import numpy as np
import pytest

from trino_tpu.batch import batch_from_numpy
from trino_tpu.catalog import Catalog
from trino_tpu.exec.executor import Executor
from trino_tpu.exec.session import Session
from trino_tpu.planner import logical as L
from trino_tpu.utils import tracing

from test_chunk_lut_packed import _all_slots
from test_q18_heavyagg import Served, q18
from test_resident_tables import bench_module
from test_tracing_phases import _inside

q3 = bench_module("queries.q3")

# tiny's `orders` (15,000 rows) is over the clusters' split_rows: its
# filtered rows are a build stage, pinned in the lineitem stage's task
ORDERS_JOIN = (
    "SELECT o_orderpriority, count(*) AS n, sum(l_extendedprice) AS revenue "
    "FROM tpch.tiny.lineitem JOIN tpch.tiny.orders "
    "ON l_orderkey = o_orderkey WHERE o_orderdate < DATE '1996-05-01' "
    "GROUP BY o_orderpriority ORDER BY o_orderpriority")
ORDERS_EXIST = (
    "SELECT l_returnflag, count(*) AS n, sum(l_extendedprice) AS revenue "
    "FROM tpch.tiny.lineitem l WHERE EXISTS (SELECT 1 FROM tpch.tiny.orders o "
    "WHERE o.o_orderkey = l.l_orderkey AND o.o_orderdate < DATE '1996-05-02') "
    "GROUP BY l_returnflag ORDER BY l_returnflag")

DOMAIN = 5000
# the live, valid build keys lie in [LO, HI): below, above and between
# them a probe key is in the domain and absent
LO, HI = 1000, 4000


def _node(kind="inner"):
    return L.JoinNode(kind=kind, left=None, right=None, left_keys=(1,),
                      right_keys=(0,), residual=None, build_unique=True,
                      output=(), build_key_domain=DOMAIN)


# the build's payload decides the LUT's form: dates and a constant ride
# in the word; two columns of 35 bits each do not (`packRefused` = bits)
PAYLOADS = {
    "packed": lambda rng, nb: [
        rng.integers(8035, 9200, nb).astype(np.int32),
        np.zeros(nb, np.int32)],
    "rows": lambda rng, nb: [
        rng.integers(0, 1 << 34, nb).astype(np.int64) + i for i in (0, 1)],
}


def _build(rng, form, duplicate=False):
    """A pinned build: unique keys in [LO, HI), one payload column with
    NULLs; and what the range must not be read over and the LUT must not
    hold: dead rows whose keys lie outside [LO, HI) and a NULL key whose
    slot holds a number. -> (batch, {key: payload values})"""
    nb = 700
    keys = rng.permutation(np.arange(LO, HI))[:nb].astype(np.int64)
    key_ok, live = np.ones(nb, bool), np.ones(nb, bool)
    dead = np.arange(0, 40)
    keys[dead[:20]] = np.arange(20, 40)                 # under LO
    keys[dead[20:]] = np.arange(HI + 300, HI + 320)     # over HI
    live[dead] = False
    keys[40], key_ok[40] = 4500, False                  # the NULL key
    if duplicate:
        keys[60] = keys[61]
    cols = PAYLOADS[form](rng, nb)
    valids = [rng.random(nb) > .2, None]
    batch = batch_from_numpy([keys] + cols, valids=[key_ok] + valids)
    batch = batch.with_live(np.asarray(batch.live) &
                            np.pad(live, (0, batch.capacity - nb)))
    rows = {}
    for i in np.flatnonzero(live & key_ok):
        rows.setdefault(int(keys[i]), []).append(tuple(
            int(c[i]) if v is None or v[i] else None
            for c, v in zip(cols, valids)))
    return batch, rows


def _keys(rng, content, n):
    pools = {
        "below": rng.integers(0, LO, n),
        "above": rng.integers(HI, DOMAIN, n),
        "negative": rng.integers(-60, 0, n),
        "past-domain": rng.integers(DOMAIN, DOMAIN + 60, n),
        "inside": rng.integers(LO, HI, n),      # present and absent
    }
    if content in pools:
        return pools[content]
    assert content == "mixed"
    pick = rng.integers(0, len(pools), n)
    return np.choose(pick, [pools[k] for k in sorted(pools)])


PROBES = ("mixed", "below", "above", "negative", "past-domain", "inside")


def _probe(rng, content, n=3000, capacity=4096):
    """Probe rows of `content` keys with NULL keys and dead rows among
    them (a mask and the capacity's tail)."""
    batch = batch_from_numpy(
        [rng.integers(0, 9, n).astype(np.int32),
         _keys(rng, content, n).astype(np.int64)],
        valids=[None, rng.random(n) > .08], capacity=capacity)
    return batch.with_live(np.asarray(batch.live) &
                           (rng.random(capacity) > .1))


def _arrays(batch):
    return [np.asarray(a) for c in batch.columns
            for a in (c.data, c.valid)] + [np.asarray(batch.live)]


def _live_rows(batch):
    """The batch's live rows in slot order, None for a NULL."""
    return [row for row in _all_slots(batch) if row is not None]


def _reference(probe, build_rows, kind="inner"):
    """The join in plain Python over the probe's live rows, in slot
    order: an inner join's matches (one a build row of the key), a semi
    join's rows that have one, an anti join's that have none."""
    out = []
    for tag, key in _live_rows(probe):
        hits = build_rows.get(key, []) if key is not None else []
        if kind == "inner":
            out += [(tag, key, key) + payload for payload in hits]
        elif bool(hits) == (kind == "semi"):
            out.append((tag, key))
    return out


def _chunk_executor():
    ex = Executor(Catalog())
    ex.enter_chunk_mode()
    return ex


def _joined(ex, node, probe, build, laps=1):
    """`_run_join_inner` under a traced task's `join` span, `laps`
    times: (the last output, what the span says)."""
    with tracing.use(tracing.Tracer()):
        ex._operator_spans = True
        ex.operator_span("join")
        try:
            for _ in range(laps):
                out = ex._run_join_inner(node, probe, build)
            said = dict(ex._open_operators[-1][1].attributes)
        finally:
            ex._close_operators(0)
            ex._operator_spans = False
    return out, said


@pytest.fixture
def filter_calls(monkeypatch):
    """Every call of `Executor._dynamic_filter` in the process, as
    (chunk mode, the join's kind)."""
    calls = []
    inner = Executor._dynamic_filter

    def counted(self, node, probe, build):
        calls.append((self.chunk_mode, node.kind))
        return inner(self, node, probe, build)

    monkeypatch.setattr(Executor, "_dynamic_filter", counted)
    return calls


# -- (a) the LUT's miss is the range test, bit for bit ----------------------

@pytest.mark.parametrize("content", PROBES)
@pytest.mark.parametrize("form", sorted(PAYLOADS))
def test_the_luts_miss_is_the_range_test(form, content, filter_calls):
    rng = np.random.default_rng(50)
    (build, build_rows), node = _build(rng, form), _node()
    probe = _probe(rng, content)
    ex = _chunk_executor()
    out, said = _joined(ex, node, probe, build, laps=2)
    assert filter_calls == []
    assert said["dynamicFilter"] == "lut" and said["lutForm"] == form
    assert (ex.stats.lut_filtered_joins, ex.stats.chunk_lut_joins,
            ex.stats.packed_lut_joins) == (2, 2, 2 * (form == "packed"))
    # the parent's order: the range mask over the probe, then the LUT
    other = _chunk_executor()
    masked = other._dynamic_filter(node, probe, build)
    want = other._chunk_lut_join(node, masked, build, DOMAIN)
    assert filter_calls == [(True, "inner")]
    # the mask did something: a NULL key at the least, and every key
    # outside the build's range
    killed = int(np.sum(np.asarray(probe.live) & ~np.asarray(masked.live)))
    assert killed > 0
    assert out.capacity == want.capacity == probe.capacity
    for got, expected in zip(_arrays(out), _arrays(want), strict=True):
        assert got.dtype == expected.dtype
        assert np.array_equal(got, expected)
    rows = _live_rows(out)
    assert rows == _reference(probe, build_rows)
    # only keys inside the build's range can match, and some do
    assert bool(rows) == (content in ("mixed", "inside"))
    if rows:
        assert any(None in r[3:] for r in rows)         # a NULL payload


# -- (b) a task's laps: no filter, and a span and a counter that say so ----

@pytest.fixture(scope="module")
def cluster():
    s = Served(workers=1)
    yield s
    s.stop()


@pytest.fixture(scope="module")
def single():
    s = Served()
    yield s
    s.stop()


def _traced(served, sql):
    served.coord.state.scheduler.spool.clear()
    served.client.execute("SET SESSION enable_tracing = true")
    try:
        rows, info, spans = served.run(sql)
    finally:
        served.client.execute("SET SESSION enable_tracing = false")
    return rows, info, spans


def _split_joins(spans):
    return [s for s in spans
            if s["name"] == "join" and "split" in s["attributes"]]


def test_a_tasks_laps_run_no_dynamic_filter(cluster, filter_calls):
    stats = cluster.workers[0].task_manager._executor.stats
    before = (stats.lut_filtered_joins, stats.chunk_lut_joins)
    _, info, spans = _traced(cluster, ORDERS_JOIN)
    assert info["distributed"] and not info.get("fallbackReason")
    joins = _split_joins(spans)
    # tiny's lineitem in splits of 8,192 rows: eight laps of one task
    assert len(joins) == 8 and len({s["parentSpanId"] for s in joins}) == 1
    assert sorted(s["attributes"]["split"] for s in joins) == list(range(8))
    for s in joins:
        assert s["attributes"]["dynamicFilter"] == "lut"
        assert s["attributes"]["lutForm"] == "packed"
    assert not [s for s in spans if s["name"] == "dynamic-filter"]
    assert filter_calls == []
    assert (stats.lut_filtered_joins - before[0],
            stats.chunk_lut_joins - before[1]) == (8, 8)
    # a task's block ships each distinct (name, attributes) pair once:
    # the laps' joins say the same but for `split`
    assert len({tuple(sorted((k, v) for k, v in s["attributes"].items()
                             if k != "split")) for s in joins}) == 1


def test_a_semi_joins_laps_keep_the_filter(cluster, single, filter_calls):
    stats = cluster.workers[0].task_manager._executor.stats
    before = stats.lut_filtered_joins
    rows, info, spans = _traced(cluster, ORDERS_EXIST)
    assert info["distributed"] and not info.get("fallbackReason")
    joins = _split_joins(spans)
    ids = {s["spanId"]: s for s in spans}
    assert len(joins) == 8
    assert all(s["attributes"] == {"split": s["attributes"]["split"],
                                   "dynamicFilter": "range"} for s in joins)
    filters = [s for s in spans if s["name"] == "dynamic-filter"]
    assert len(filters) == 8
    for s in filters:
        assert ids[s["parentSpanId"]] in joins
        assert _inside(s, ids[s["parentSpanId"]])
    assert filter_calls == [(True, "semi")] * 8
    assert stats.lut_filtered_joins == before
    assert rows == single.run(ORDERS_EXIST)[0] and len(rows) == 3


# -- (c) every other join keeps the filter ---------------------------------

def test_a_build_the_lut_refuses_keeps_the_filter(filter_calls, monkeypatch):
    """A pinned build with a key twice: the LUT's validation refuses it
    once a task, every lap runs the range test and the general ladder,
    and `_chunk_lut_join` is entered once a lap (a dictionary lookup)."""
    rng = np.random.default_rng(51)
    (build, build_rows), node = _build(rng, "packed", duplicate=True), _node()
    assert max(len(v) for v in build_rows.values()) == 2
    probe = _probe(rng, "mixed")
    entered = []
    inner = Executor._chunk_lut_join

    def counted(self, *args):
        entered.append(1)
        return inner(self, *args)

    monkeypatch.setattr(Executor, "_chunk_lut_join", counted)
    ex = _chunk_executor()
    out, said = _joined(ex, node, probe, build, laps=3)
    assert said["dynamicFilter"] == "range" and \
        said["packRefused"] == "validation" and "lutForm" not in said
    assert filter_calls == [(True, "inner")] * 3 and len(entered) == 3
    assert (ex.stats.lut_filtered_joins, ex.stats.chunk_lut_joins,
            ex.stats.join_fallbacks) == (0, 0, 3)
    assert sorted(_live_rows(out)) == sorted(_reference(probe, build_rows))


@pytest.mark.parametrize("kind", ["semi", "anti"])
def test_a_membership_join_in_chunk_mode_is_as_it_was(kind, filter_calls):
    """A semi join runs the range test in front; an anti join never had
    one (`apply_dynamic_filter` skips the kinds that keep the rows that
    do not match). Neither counts as a LUT-filtered join."""
    rng = np.random.default_rng(52)
    (build, build_rows), node = _build(rng, "packed"), _node(kind)
    probe = _probe(rng, "mixed")
    ex = _chunk_executor()
    out, said = _joined(ex, node, probe, build, laps=2)
    assert filter_calls == [(True, "semi")] * 2 * (kind == "semi")
    assert said.get("dynamicFilter") == ("range" if kind == "semi" else None)
    assert (ex.stats.lut_filtered_joins, ex.stats.chunk_lut_joins) == (0, 0)
    assert len(out.columns) == len(probe.columns)
    rows = _live_rows(out)
    assert rows and rows == _reference(probe, build_rows, kind)


def test_the_whole_statement_route_compacts_on_the_filter(filter_calls):
    """`chunk_mode` false: the filter compacts the probe there, as
    tests/test_aggregate.py::test_dynamic_filter_compaction expects."""
    s = Session(default_cat="memory", default_schema="default")
    s.execute("CREATE TABLE big AS SELECT o_orderkey k, o_totalprice v "
              "FROM tpch.tiny.orders")
    s.execute("CREATE TABLE dim (k bigint, name varchar)")
    s.execute("INSERT INTO dim VALUES (97, 'a'), (101, 'b'), (103, 'c')")
    r = s.execute("SELECT count(*) FROM big, dim WHERE big.k = dim.k")
    assert r.rows == [(3,)]
    assert filter_calls == [(False, "inner")]
    assert s.executor.stats.dynamic_filter_compactions >= 1
    assert s.executor.stats.lut_filtered_joins == 0
    s.execute("SET SESSION dynamic_filtering = false")
    del filter_calls[:]
    r = s.execute("SELECT count(*), sum(v) FROM big, dim WHERE big.k = dim.k")
    assert r.rows[0][0] == 3 and filter_calls == []


@pytest.mark.parametrize("filtering", ["true", "false"])
def test_dynamic_filtering_off_gives_equal_rows(cluster, single, filtering):
    sql = q3.render({"segment": "MACHINERY", "day": 9}, "tpch.tiny")
    want, _, _ = single.run(sql)
    cluster.coord.state.scheduler.spool.clear()
    cluster.client.execute(f"SET SESSION dynamic_filtering = {filtering}")
    try:
        rows, info, _ = cluster.run(sql)
    finally:
        cluster.client.execute("SET SESSION dynamic_filtering = true")
    assert info["distributed"] and not info.get("fallbackReason")
    assert rows == want and len(rows) == 10


# -- (d) q3 and Q18 through a worker: the single-node route's rows ---------

STATEMENTS = {
    "q3-building": (q3, {"segment": "BUILDING", "day": 15}),
    "q3-household": (q3, {"segment": "HOUSEHOLD", "day": 20}),
    "q3-automobile": (q3, {"segment": "AUTOMOBILE", "day": 2}),
    "q18-150": (q18, {"quantity": 150}),
    "q18-225": (q18, {"quantity": 225}),
}


@pytest.mark.parametrize("statement", sorted(STATEMENTS))
def test_a_workers_rows_are_the_single_nodes(cluster, single, statement,
                                             filter_calls):
    template, params = STATEMENTS[statement]
    sql = template.render(params, "tpch.tiny")
    want, single_info, _ = single.run(sql)
    assert single_info["route"] == "device" and \
        not single_info.get("distributed")
    # the whole-statement route's joins ran the range test, none in
    # chunk mode
    assert filter_calls and not any(chunked for chunked, _ in filter_calls)
    del filter_calls[:]
    rows, info, spans = _traced(cluster, sql)
    assert info["distributed"] and not info.get("fallbackReason")
    assert rows == want and rows
    joins = _split_joins(spans)
    assert joins and all(s["attributes"]["dynamicFilter"] == "lut"
                         for s in joins)
    # both forms of the LUT: q3's builds ride in the word, a Q18's
    # lineitem stage probes row ids
    forms = {s["attributes"]["lutForm"] for s in joins}
    assert forms == ({"packed"} if template is q3 else {"packed", "rows"})
    assert not [s for s in spans if s["name"] == "dynamic-filter"
                and "split" in s["attributes"]]
    assert not any(chunked for chunked, _ in filter_calls)
