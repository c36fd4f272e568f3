"""The gather sites of the join and aggregate kernels, each against a
plain numpy answer: the dense-LUT probe in its one-shot and reused-LUT
forms, the build payload's gathers on both sides of the 63-column
validity word, the windowed probe of the chunked driver (escapes counted,
the plain rerun taken), the sort aggregate's group read-back through the
permutation, the direct aggregate at the planner's larger domains, and
the one Pallas kernel left (the scan gather of a small build's payload,
`ops/pallas_gather.py`) in the interpreter: its gate, its parity with
`take`, and its site.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from trino_tpu.batch import batch_from_numpy, batch_to_numpy
from trino_tpu.ops import join as J
from trino_tpu.ops import pallas_gather as pg
from trino_tpu.ops.aggregate import (MAX_DIRECT_GROUPS, AggSpec,
                                     direct_group_aggregate,
                                     key_pack_plan_words,
                                     packed_sort_group_aggregate,
                                     sort_group_aggregate)

KINDS = ["inner", "left", "semi", "anti"]


def rows_of(batch):
    """The live rows as tuples, None for NULL."""
    arrays, valids = batch_to_numpy(batch)
    return [tuple(a[i].item() if v[i] else None
                  for a, v in zip(arrays, valids))
            for i in range(len(arrays[0]))]


def with_dead_rows(batch, dead):
    live = np.asarray(batch.live).copy()
    live[:len(dead)] &= ~dead
    return batch.with_live(jnp.asarray(live))


def np_join(kind, probe_rows, build_by_key, n_build_cols):
    """probe_rows: (key or None, value) of the live probe rows in order;
    build_by_key: {key: tuple of payload values or None}."""
    out = []
    for pk, pv in probe_rows:
        hit = build_by_key.get(pk) if pk is not None else None
        if kind == "semi":
            if hit is not None:
                out.append((pk, pv))
        elif kind == "anti":
            if hit is None:
                out.append((pk, pv))
        elif hit is not None:
            out.append((pk, pv, pk) + hit)
        elif kind == "left":
            out.append((pk, pv) + (None,) * n_build_cols)
    return out


# ---------------------------------------------------------------------------
# the dense-LUT probe: one shot (LUT built inside) and a reused LUT
# ---------------------------------------------------------------------------

def dense_fixture(seed=11, domain=2048, nb=500, n_probe=3000):
    rng = np.random.default_rng(seed)
    bk = rng.permutation(domain)[:nb].astype(np.int64)
    b1 = rng.integers(-1000, 1000, nb).astype(np.int64)
    b1_valid = rng.random(nb) > .2
    b2 = rng.integers(0, 100, nb).astype(np.int32)
    build = batch_from_numpy([bk, b1, b2], valids=[None, b1_valid, None])
    # keys on both sides of the domain (misses), NULL keys, dead rows
    pk = rng.integers(-10, domain + 10, n_probe).astype(np.int64)
    pk_valid = rng.random(n_probe) > .1
    pv = rng.integers(0, 50, n_probe).astype(np.int64)
    dead = rng.random(n_probe) < .15
    probe = with_dead_rows(
        batch_from_numpy([pk, pv], valids=[pk_valid, None]), dead)
    build_by_key = {
        int(k): (int(x) if ok else None, int(y))
        for k, x, ok, y in zip(bk, b1, b1_valid, b2)}
    probe_rows = [(int(k) if ok else None, int(v))
                  for k, ok, v, d in zip(pk, pk_valid, pv, dead) if not d]
    return probe, build, domain, probe_rows, build_by_key


@pytest.mark.parametrize("kind", KINDS)
def test_dense_join_one_shot_and_reused_lut_match_numpy(kind):
    probe, build, domain, probe_rows, build_by_key = dense_fixture()
    want = np_join(kind, probe_rows, build_by_key, 3)
    assert 0 < len(want) < len(probe_rows) or kind == "left"
    one_shot, dup, oob = J.join_unique_build_dense(
        probe, build, (0,), (0,), kind, domain)
    assert (int(dup), int(oob)) == (0, 0)
    assert rows_of(one_shot) == want
    lut, dup, oob = J.dense_build_lut(build, (0,), domain)
    assert (int(dup), int(oob)) == (0, 0)
    reused = J.dense_join_with_lut(probe, build, lut, (0,), (0,), kind)
    assert rows_of(reused) == want
    # the same LUT, another probe: nothing of the first one is kept
    again = J.dense_join_with_lut(
        probe.with_live(jnp.zeros_like(probe.live)), build, lut, (0,),
        (0,), kind)
    assert rows_of(again) == []


# ---------------------------------------------------------------------------
# the payload's gathers: a validity word up to 63 columns, a mask a column
# past it
# ---------------------------------------------------------------------------

def wide_build(n_cols, seed, nb=200, domain=512, n_probe=700):
    rng = np.random.default_rng(seed)
    bk = rng.permutation(domain)[:nb].astype(np.int64)
    cols = [rng.integers(-2**40, 2**40, nb).astype(np.int64)
            for _ in range(n_cols - 1)]
    valids = [rng.random(nb) > .3 for _ in cols]
    build = batch_from_numpy([bk] + cols, valids=[None] + valids)
    pk = rng.integers(0, domain, n_probe).astype(np.int64)
    probe = batch_from_numpy([pk, np.arange(n_probe)])
    build_by_key = {
        int(k): tuple(int(c[i]) if v[i] else None
                      for c, v in zip(cols, valids))
        for i, k in enumerate(bk)}
    probe_rows = [(int(k), i) for i, k in enumerate(pk)]
    return probe, build, domain, probe_rows, build_by_key


@pytest.mark.parametrize("form", ["with-lut", "compacted"])
@pytest.mark.parametrize("n_cols", [63, 64])
def test_build_payload_validity_on_both_sides_of_the_word(n_cols, form):
    """63 build columns pack their validity into one gathered int64
    (bits 1 to 62); a 64th takes the per-column masks."""
    probe, build, domain, probe_rows, build_by_key = wide_build(
        n_cols, seed=n_cols)
    want = np_join("inner", probe_rows, build_by_key, n_cols)
    assert want and any(None in row for row in want)
    if form == "with-lut":
        lut, _, _ = J.dense_build_lut(build, (0,), domain)
        got = J.dense_join_with_lut(probe, build, lut, (0,), (0,), "inner")
    else:
        words, rows, dup, oob, count = J.dense_probe(
            probe, build, (0,), (0,), domain)
        assert (int(dup), int(oob), int(count)) == (0, 0, len(want))
        got = J.dense_join_compacted(probe, words, rows, build, (0,), (0,),
                                     1024)
    assert rows_of(got) == want


# ---------------------------------------------------------------------------
# the windowed probe of a value-packed LUT (the chunked driver's, over
# near-sorted keys): a dynamic_slice of the LUT, escapes counted
# ---------------------------------------------------------------------------

PACKED_DTYPES = ("int64", "int64")


def packed_fixture(seed=12, domain=1 << 16, nb=9000):
    rng = np.random.default_rng(seed)
    bk = rng.permutation(domain)[:nb].astype(np.int64)
    bval = rng.integers(-500, 500, nb).astype(np.int64)
    bval_valid = rng.random(nb) > .25
    build = batch_from_numpy([bk, bval], valids=[None, bval_valid])
    meta, los = ((1, 16, 1, 17),), jnp.asarray([-500])
    lut, exp, oob, occ = J.dense_build_packed_lut(
        build, (0,), domain, meta, "int32", los)
    assert (int(exp), int(oob), int(occ)) == (nb, 0, nb)
    build_by_key = {int(k): (int(v) if ok else None,)
                    for k, v, ok in zip(bk, bval, bval_valid)}
    return lut, los, meta, domain, build_by_key


def packed_probe(keys, valid=None, dead=None):
    keys = np.asarray(keys, dtype=np.int64)
    n = len(keys)
    probe = batch_from_numpy([keys, np.arange(n)],
                             valids=[valid, None])
    dead = np.zeros(n, bool) if dead is None else dead
    valid = np.ones(n, bool) if valid is None else valid
    rows = [(int(k) if ok else None, i)
            for i, (k, ok, d) in enumerate(zip(keys, valid, dead)) if not d]
    return with_dead_rows(probe, dead), rows


@pytest.mark.parametrize("kind", KINDS)
def test_windowed_probe_over_near_sorted_keys_matches_numpy(kind):
    lut, los, meta, domain, build_by_key = packed_fixture()
    rng = np.random.default_rng(13)
    # a chunk of an ascending fact scan: 2,048 keys inside 6,000 entries
    # of the 65,536, locally shuffled, with NULL keys and dead rows
    keys = 40_000 + np.sort(rng.integers(0, 6000, 2048))
    keys = keys.reshape(-1, 16)[:, rng.permutation(16)].reshape(-1)
    probe, probe_rows = packed_probe(keys, valid=rng.random(2048) > .05,
                                     dead=rng.random(2048) < .1)
    want = np_join(kind, probe_rows, build_by_key, 2)
    got, escaped, span = J.dense_join_packed_windowed(
        probe, lut, los, (0,), meta, 0, PACKED_DTYPES, kind, 8192)
    assert int(escaped) == 0
    live_keys = [k for k, _ in probe_rows if k is not None]
    assert int(span) == max(live_keys) - min(live_keys) + 1
    assert rows_of(got) == want
    whole = J.dense_join_packed(probe, lut, los, (0,), meta, 0,
                                PACKED_DTYPES, kind)
    assert rows_of(whole) == want


def test_windowed_probe_counts_the_keys_outside_its_window():
    """Keys spread over the whole domain against a 1,024-entry window:
    every in-domain key the slice does not cover is counted and comes
    back unmatched, so the driver knows the answer is unusable."""
    lut, los, meta, domain, build_by_key = packed_fixture()
    rng = np.random.default_rng(14)
    keys = rng.integers(0, domain, 2048)
    probe, probe_rows = packed_probe(keys)
    got, escaped, span = J.dense_join_packed_windowed(
        probe, lut, los, (0,), meta, 0, PACKED_DTYPES, "inner", 1024)
    lo = int(keys.min())
    inside = (keys >= lo) & (keys < lo + 1024)
    assert int(escaped) == int((~inside).sum()) > 0
    assert int(span) == int(keys.max()) - lo + 1
    want = np_join("inner", [r for r, ok in zip(probe_rows, inside) if ok],
                   build_by_key, 2)
    assert rows_of(got) == want


def test_windowed_probe_does_not_count_misses_as_escapes():
    """Out-of-domain keys, NULL keys and dead rows far from the window
    are misses, not escapes: they never match in the whole LUT either."""
    lut, los, meta, domain, build_by_key = packed_fixture()
    rng = np.random.default_rng(15)
    keys = 1000 + np.sort(rng.integers(0, 900, 1024))
    keys[::5] = -7
    keys[1::5] = domain + 3
    valid = np.ones(1024, bool)
    valid[2::5] = False
    keys[2::5] = 60_000            # NULL: its data is never read
    dead = np.zeros(1024, bool)
    dead[3::5] = True
    keys[3::5] = 50_000            # in the domain, but the row is dead
    probe, probe_rows = packed_probe(keys, valid=valid, dead=dead)
    got, escaped, span = J.dense_join_packed_windowed(
        probe, lut, los, (0,), meta, 0, PACKED_DTYPES, "left", 1024)
    assert int(escaped) == 0
    assert int(span) <= 900
    assert rows_of(got) == np_join("left", probe_rows, build_by_key, 2)


def test_a_violated_window_takes_the_plain_rerun():
    """Through the chunked driver: a recorded key span far too small
    makes the adapted program's window escape, the run is thrown away
    and the plain program's answer stands (numpy's)."""
    from trino_tpu.exec.session import Session
    s = Session(default_schema="tiny")
    s.properties["spill_chunk_rows"] = 8192
    s.executor.spill_chunk_rows = 8192
    sql = ("SELECT count(*), sum(l_quantity) FROM lineitem, orders "
           "WHERE l_orderkey = o_orderkey "
           "AND o_orderdate >= DATE '1996-01-01'")
    first = s.execute(sql).rows
    ex = s.executor
    assert ex.stats.fused_chunk_pipelines >= 1
    assert ex.stats.escaped_window_reruns == 0
    recs = [k for k in ex._decision_cache if k[0] == "fusedadapt"]
    assert recs
    ex._decision_cache[recs[0]] = tuple(
        [8] * len(ex._decision_cache[recs[0]]))
    reruns = ex.stats.escaped_window_reruns
    second = s.execute(sql).rows
    assert s.executor.stats.escaped_window_reruns == reruns + 1
    get = s.catalog.get_table
    orders, lineitem = (get("tpch", "tiny", t)
                        for t in ("orders", "lineitem"))

    def column(table, name):
        return np.asarray(table.columns[table.schema.index_of(name)])
    cutoff = (np.datetime64("1996-01-01") -
              np.datetime64("1970-01-01")).astype(int)
    kept = column(orders, "o_orderkey")[
        column(orders, "o_orderdate") >= cutoff]
    hit = np.isin(column(lineitem, "l_orderkey"), kept)
    want_count = int(hit.sum())
    want_sum = int(column(lineitem, "l_quantity")[hit].sum())
    for rows in (first, second):
        (count, total), = rows
        assert count == want_count
        assert round(float(total) * 100) == want_sum


# ---------------------------------------------------------------------------
# the sort aggregate's group read-back through the permutation
# ---------------------------------------------------------------------------

def np_group_by(keys, key_valids, live, values, value_valid):
    """{(k1, k2): (sum, count, min, max, count_star)} with None for a
    NULL key and for an aggregate over no value."""
    out = {}
    for i in np.nonzero(live)[0]:
        key = tuple(int(k[i]) if v[i] else None
                    for k, v in zip(keys, key_valids))
        acc = out.setdefault(key, [])
        acc.append(int(values[i]) if value_valid[i] else None)
    answer = {}
    for key, acc in out.items():
        seen = [x for x in acc if x is not None]
        answer[key] = ((sum(seen), len(seen), min(seen), max(seen))
                       if seen else (None, 0, None, None)) + (len(acc),)
    return answer


@pytest.mark.parametrize("kernel", ["general", "packed"])
@pytest.mark.parametrize("n", [4096, 98_304],
                         ids=["under-65536", "over-65536"])
def test_group_read_back_through_the_permutation(kernel, n):
    """Keys of every output group are read at the group's first sorted
    row, through the sort's permutation: the same `take` whatever the
    table's length."""
    rng = np.random.default_rng(n)
    k1 = rng.integers(-3, 40, n).astype(np.int64)
    k2 = rng.integers(0, 7, n).astype(np.int32)
    v = rng.integers(-10**9, 10**9, n).astype(np.int64)
    k1_valid, v_valid = rng.random(n) > .1, rng.random(n) > .2
    dead = rng.random(n) < .3
    b = with_dead_rows(
        batch_from_numpy([k1, k2, v], valids=[k1_valid, None, v_valid]),
        dead)
    aggs = (AggSpec("sum", 2), AggSpec("count", 2), AggSpec("min", 2),
            AggSpec("max", 2), AggSpec("count_star", None))
    if kernel == "general":
        out = sort_group_aggregate(b, (0, 1), aggs, 1024)
    else:
        kmins, bits, splits = key_pack_plan_words(b, (0, 1))
        out = packed_sort_group_aggregate(
            b, jnp.asarray(kmins), (0, 1), bits, aggs, 1024, splits)
    want = np_group_by([k1, k2], [k1_valid, np.ones(n, bool)], ~dead, v,
                       v_valid)
    got = {r[:2]: r[2:] for r in rows_of(out)}
    assert len(got) == int(np.asarray(out.live).sum()) == len(want)
    assert got == want
    assert any(k[0] is None for k in got)


# ---------------------------------------------------------------------------
# the direct aggregate past q1's six groups
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("domains", [(3, 4), (16,), (8, 8)],
                         ids=["G=12", "G=16", "G=64"])
def test_direct_aggregate_at_larger_domains_matches_numpy(domains):
    groups = int(np.prod(domains))
    assert groups <= MAX_DIRECT_GROUPS
    rng = np.random.default_rng(groups)
    n = 6000
    keys = [rng.integers(0, d, n).astype(np.int32) for d in domains]
    # the last group of the domain has no row at all
    keys[0][np.all([k == d - 1 for k, d in zip(keys, domains)],
                   axis=0)] = 0
    key_valids = [rng.random(n) > .1] + [np.ones(n, bool)] * (len(keys) - 1)
    v = rng.integers(-2**44, -2**43, n).astype(np.int64)
    v_valid = rng.random(n) > .2
    dead = rng.random(n) < .4
    b = with_dead_rows(
        batch_from_numpy(keys + [v], valids=key_valids + [v_valid]), dead)
    nk = len(keys)
    aggs = (AggSpec("sum", nk), AggSpec("count", nk), AggSpec("min", nk),
            AggSpec("max", nk), AggSpec("count_star", None))
    out = direct_group_aggregate(b, tuple(range(nk)), domains, aggs)
    assert out.capacity == groups
    want = np_group_by(keys, key_valids, ~dead, v, v_valid)
    # a NULL key contributes to no group
    want = {k: a for k, a in want.items() if None not in k}
    got = {r[:nk]: r[nk:] for r in rows_of(out)}
    assert got == want and len(got) == groups - 1
    assert all(a[0] < 0 for a in got.values())


def test_direct_aggregate_sums_past_2_to_the_53_exactly():
    n = 4096 * 8
    vals = np.full(n, -(2**44) + 17, dtype=np.int64)
    b = batch_from_numpy([np.full(n, 11, dtype=np.int32), vals])
    out = direct_group_aggregate(b, (0,), (12,), (AggSpec("sum", 1),))
    assert rows_of(out) == [(11, n * (-(2**44) + 17))]


# ---------------------------------------------------------------------------
# the scan gather: on a TPU, a small build's payload rides one kernel call
# (here the Pallas interpreter runs the kernel's logic)
# ---------------------------------------------------------------------------

def np_take(table, idx):
    table, idx = np.asarray(table), np.asarray(idx)
    ok = (idx >= 0) & (idx < len(table))
    return np.where(ok, table[np.clip(idx, 0, len(table) - 1)],
                    np.zeros((), table.dtype))


@pytest.mark.parametrize("n,w", [(pg.TILE, pg.SLAB),       # aligned
                                 (3000, 5000),             # ragged tails
                                 (17, 129)])               # under a tile
def test_scan_gather_matches_take(n, w):
    rng = np.random.default_rng(n + w)
    tables = [
        jnp.asarray(rng.integers(-(1 << 62), 1 << 62, w)),
        jnp.asarray(rng.integers(-100, 100, w).astype(np.int8)),
        jnp.asarray(rng.integers(0, 2, w).astype(bool)),
        jnp.asarray(rng.normal(size=w).astype(np.float32)),
        jnp.asarray(rng.integers(-(1 << 30), 1 << 30, w).astype(np.int32))]
    idx = rng.integers(0, w, n)
    idx[::7] = -1                     # a miss reads 0
    idx[3::11] = w + 3                # and so does an index past the table
    got = pg.gather_columns(tables, jnp.asarray(idx), interpret=True)
    for g, t in zip(got, tables):
        assert g.dtype == t.dtype and g.shape == (n,)
        assert np.array_equal(np.asarray(g), np_take(t, idx))


def test_scan_gather_splits_more_planes_than_one_call_carries():
    rng = np.random.default_rng(1)
    w, n = 1000, 900
    tables = [jnp.asarray(rng.integers(-(1 << 50), 1 << 50, w))
              for _ in range(pg.MAX_PLANES + 3)]      # two planes each
    idx = rng.integers(0, w, n)
    got = pg.gather_columns(tables, jnp.asarray(idx), interpret=True)
    for g, t in zip(got, tables):
        assert np.array_equal(np.asarray(g), np.asarray(t)[idx])


def test_platform_and_table_size_decide_and_nothing_else(monkeypatch):
    import jax
    small = [jnp.zeros(2048, jnp.int64), jnp.zeros(2048, jnp.int32)]
    assert not pg.gather_supported(small)             # this is a CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pg.gather_supported(small)
    assert pg.gather_supported([jnp.zeros(pg.SCAN_MAX_ELEMS, jnp.int8)])
    assert not pg.gather_supported(
        [jnp.zeros(pg.SCAN_MAX_ELEMS + 1, jnp.int8)])
    assert not pg.gather_supported(small + [jnp.zeros(2048, jnp.float64)])
    assert not pg.gather_supported(small + [jnp.zeros(1024, jnp.int32)])
    assert not pg.gather_supported([])


@pytest.mark.parametrize("kind", ["inner", "left"])
def test_a_small_builds_payload_rides_the_kernel(monkeypatch, kind):
    """What a TPU does at `_gather_build_payload`, with the interpreter
    standing in for the chip: the validity word and both payload columns
    in one call, and numpy's join all the same."""
    probe, build, domain, probe_rows, build_by_key = dense_fixture(seed=21)
    calls = []
    supported, gather = pg.gather_supported, pg.gather_columns

    def gather_interpreted(tables, idx):
        calls.append([(str(t.dtype), t.shape[0]) for t in tables])
        return gather(tables, idx, interpret=True)
    monkeypatch.setattr(pg, "gather_supported",
                        lambda tables, interpret=True: supported(tables,
                                                                 True))
    monkeypatch.setattr(pg, "gather_columns", gather_interpreted)
    lut, _, _ = J.dense_build_lut(build, (0,), domain)
    # the traced function itself: a cached program would not see the patch
    got = J.dense_join_with_lut.__wrapped__.__wrapped__(
        probe, build, lut, (0,), (0,), kind)
    assert calls == [[("int64", 1024), ("int64", 1024), ("int32", 1024)]]
    assert rows_of(got) == np_join(kind, probe_rows, build_by_key, 3)
