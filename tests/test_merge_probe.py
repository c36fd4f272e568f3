"""Phase 1 of the two-phase unique-build join as ONE merge sort
(`ops/join.merge_probe`) against the LUT form it stands in for
(`dense_probe`), both handing their match words to
`dense_join_compacted`: the two batches bit for bit and row for row, and
each against a plain numpy join. Then the rule that picks the form
(`merge_probe_form`: the word's bits, the two capacities) at its
boundaries, the duplicate and wide-key reports and where the executor
goes after them, and TPC-H q3 / q18 at `tiny` through the served
single-node route under either form, with what the `join` span says.
"""

import os

import numpy as np
import pytest

import trino_tpu  # noqa: F401 — x64 before any array
import jax.numpy as jnp

from trino_tpu.batch import Batch, batch_from_numpy, bucket_capacity
from trino_tpu.exec.executor import SORT_SMALL_ROWS
from trino_tpu.ops import join as J

from test_agg_join_ladders import check, ran, session_over, table
from test_q18_heavyagg import Served
from test_resident_tables import bench_module

q3 = bench_module("queries.q3")
q18 = bench_module("queries.q18")


# ---- the two kernels on crafted batches ----------------------------------

def side(keys, payload, key_valid=None, live=None, capacity=None):
    """A batch of key column(s) ++ one payload column. `keys` is one
    array or a tuple of arrays (a two-column key)."""
    keys = keys if isinstance(keys, tuple) else (keys,)
    arrays = [np.asarray(k, dtype=np.int64) for k in keys] + \
        [np.asarray(payload, dtype=np.int64)]
    valids = [key_valid] * len(keys) + [None]
    b = batch_from_numpy(arrays, valids, capacity=capacity, pad_multiple=8)
    if live is not None:
        mask = np.zeros(b.capacity, dtype=bool)
        mask[:len(live)] = live
        b = b.with_live(b.live & jnp.asarray(mask))
    return b


def case_null_keys(rng):
    bk = rng.permutation(4000)[:900]
    pk = rng.integers(0, 4000, 5000)
    return dict(probe=side(pk, np.arange(5000), rng.random(5000) > 0.1),
                build=side(bk, bk * 3, rng.random(900) > 0.1),
                domain=4096)


def case_dead_rows(rng):
    bk = rng.permutation(4000)[:900]
    pk = rng.integers(0, 4000, 5000)
    return dict(probe=side(pk, np.arange(5000), live=rng.random(5000) > 0.3),
                build=side(bk, bk * 3, live=rng.random(900) > 0.3),
                domain=4096)


def case_no_match(rng):
    return dict(probe=side(rng.integers(0, 2000, 5000) * 2, np.arange(5000)),
                build=side(rng.permutation(2000)[:600] * 2 + 1,
                           np.arange(600)),
                domain=4096)


def case_every_row_matched(rng):
    bk = rng.permutation(700)
    return dict(probe=side(rng.integers(0, 700, 5000), np.arange(5000)),
                build=side(bk, bk + 11), domain=1024)


def case_empty_build(rng):
    return dict(probe=side(rng.integers(0, 700, 5000), np.arange(5000)),
                build=side(np.arange(64), np.arange(64),
                           live=np.zeros(64, dtype=bool)),
                domain=1024)


def case_range_ends(rng):
    # the build's least and greatest keys, probes on them, one below the
    # least, one above the greatest, and row 0 / the last row matched
    bk = np.concatenate([[7, 4090], rng.permutation(4000)[:500] + 50])
    pk = rng.integers(0, 4096, 5000)
    pk[[0, 1, 2, 3, -1]] = [7, 4090, 6, 4091, 4090]
    return dict(probe=side(pk, np.arange(5000)), build=side(bk, bk * 5),
                domain=4096)


def case_odd_capacities(rng):
    bk = rng.permutation(3000)[:777]
    pk = rng.integers(0, 3000, 5003)
    return dict(probe=side(pk, np.arange(5003), capacity=5003),
                build=side(bk, bk + 1, capacity=777), domain=3001)


def case_two_column_key(rng):
    # a (k0, k1) key, 32 bits a column in ops/join._combined_key: no
    # domain a LUT could span
    b0, b1 = rng.integers(0, 5, 800), rng.permutation(800)
    pick = rng.integers(0, 800, 5000)
    p0, p1 = b0[pick].copy(), b1[pick].copy()
    p1[::3] += 1000                      # a third of the probes miss
    return dict(probe=side((p0, p1), np.arange(5000),
                           rng.random(5000) > 0.05),
                build=side((b0, b1), np.arange(800) * 9), domain=None,
                keys=(0, 1))


def case_build_larger_than_probe(rng):
    # the build's positions set the word's position bits (9,000 > 5,000)
    bk = rng.permutation(20000)[:9000]
    return dict(probe=side(rng.integers(0, 20000, 5000), np.arange(5000)),
                build=side(bk, bk * 2), domain=20480)


CASES = [case_build_larger_than_probe, case_null_keys, case_dead_rows, case_no_match,
         case_every_row_matched, case_empty_build, case_range_ends,
         case_odd_capacities, case_two_column_key]


def rows_of(batch: Batch, n_keys: int):
    """[(key tuple | None, payload)] of the live rows of one side."""
    live = np.asarray(batch.live)
    cols = [(np.asarray(c.data), np.asarray(c.valid))
            for c in batch.columns]
    out = []
    for i in np.nonzero(live)[0]:
        key = tuple(int(cols[k][0][i]) for k in range(n_keys)) \
            if all(cols[k][1][i] for k in range(n_keys)) else None
        out.append((int(i), key, int(cols[n_keys][0][i])))
    return out


def same_batch(a: Batch, b: Batch):
    assert a.capacity == b.capacity and len(a.columns) == len(b.columns)
    assert np.array_equal(np.asarray(a.live), np.asarray(b.live))
    for ca, cb in zip(a.columns, b.columns):
        assert ca.data.dtype == cb.data.dtype
        assert np.array_equal(np.asarray(ca.data), np.asarray(cb.data))
        assert np.array_equal(np.asarray(ca.valid), np.asarray(cb.valid))


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__[5:])
def test_merge_pair_equals_the_dense_pair_and_a_plain_join(case):
    c = case(np.random.default_rng(3501))
    probe, build, domain = c["probe"], c["build"], c["domain"]
    keys = c.get("keys", (0,))
    nk = len(keys)
    assert probe.capacity > SORT_SMALL_ROWS

    words, rows, dup, wide, count = J.merge_probe(probe, build, keys, keys)
    assert words.shape == rows.shape == \
        (probe.capacity + build.capacity,)
    assert (int(dup), int(wide)) == (0, 0)

    by_key = {k: (i, v) for i, k, v in rows_of(build, nk) if k is not None}
    want = [(pi, pk, pv, by_key[pk]) for pi, pk, pv in rows_of(probe, nk)
            if pk is not None and pk in by_key]
    assert int(count) == len(want)
    assert len(want) == {case_no_match: 0, case_empty_build: 0,
                         case_every_row_matched: 5000}.get(case, len(want))

    new_cap = min(bucket_capacity(len(want)), probe.capacity)
    out = J.dense_join_compacted(probe, words, rows, build, keys, keys,
                                 new_cap)
    assert out.capacity == new_cap
    live = np.asarray(out.live)
    # matched rows first, in the probe's row order, nothing after them
    assert live[:len(want)].all() and not live[len(want):].any()
    data = [np.asarray(col.data) for col in out.columns]
    valid = [np.asarray(col.valid) for col in out.columns]
    for row, (pi, pk, pv, (bi, bv)) in enumerate(want):
        got = [int(d[row]) for d in data]
        assert got == list(pk) + [pv] + list(pk) + [bv], (row, pi, bi)
        assert all(v[row] for v in valid)
    # a dead slot's build columns are NULL
    for v in valid[nk + 1:]:
        assert not v[len(want):].any()

    if domain is not None:
        d_words, d_rows, d_dup, d_oob, d_count = J.dense_probe(
            probe, build, keys, keys, domain)
        assert (int(d_dup), int(d_oob), int(d_count)) == (0, 0, len(want))
        same_batch(out, J.dense_join_compacted(
            probe, d_words, d_rows, build, keys, keys, new_cap))


def test_duplicate_build_keys_are_counted_by_both_forms():
    rng = np.random.default_rng(3502)
    bk = rng.permutation(3000)[:600]
    bk[1:41:2] = bk[0:40:2]              # twenty keys twice
    bk[100] = bk[101] = bk[102]          # one key three times
    probe = side(rng.integers(0, 3000, 5000), np.arange(5000))
    build = side(bk, np.arange(600))
    _, _, dup, wide, _ = J.merge_probe(probe, build, (0,), (0,))
    _, _, d_dup, _, _ = J.dense_probe(probe, build, (0,), (0,), 4096)
    assert int(dup) == int(d_dup) == 22 and int(wide) == 0


def test_build_keys_wider_than_the_key_field_are_reported():
    """5,000 probe rows leave the key field 62 - 13 = 49 bits, its top
    value kept free: a build spanning 2^49 - 2 fits, 2^49 - 1 does not."""
    probe = side(np.arange(5000), np.arange(5000))
    for span, n_wide in (((1 << 49) - 2, 0), ((1 << 49) - 1, 1)):
        build = side(np.array([3, 10, 3 + span]), np.arange(3))
        _, _, dup, wide, count = J.merge_probe(probe, build, (0,), (0,))
        assert (int(dup), int(wide), int(count)) == (0, n_wide, 2)


def test_a_key_span_past_an_int64_is_wide_and_joins_nothing_wrongly():
    """Keys 2^63 and more apart: `key - kmin` wraps to a negative on
    both sides, which is neither a fit nor a match."""
    lo, hi = -(1 << 62) - 5, (1 << 62) + 5
    pk = np.arange(5000, dtype=np.int64)
    pk[:3] = [lo, hi, lo + 1]
    build = side(np.array([lo, hi]), np.arange(2))
    words, rows, dup, wide, count = J.merge_probe(
        side(pk, np.arange(5000)), build, (0,), (0,))
    assert (int(dup), int(wide), int(count)) == (0, 1, 1)
    words = np.asarray(words)
    # probe row 0 on build row 0, and nothing else
    (word,) = words[words != np.iinfo(np.int64).max].tolist()
    idx_bits = (len(words) - 1).bit_length()
    assert word >> idx_bits == 0
    assert int(rows[word & ((1 << idx_bits) - 1)]) == 0


# ---- the rule that picks the form ----------------------------------------

N60 = 60_011_520
# capacities meet where 2 sorts and a scan of (n + m) cost the gather of n
MEET = (J.GATHER_NS_PER_INDEX /
        (2 * J.SORT_NS_PER_WORD + J.SCAN_NS_PER_WORD)) - 1


@pytest.mark.parametrize("n,m,span,bits", [
    # TPC-H at SF10: q3's and q18's lineitem joins, q3's orders join
    (N60, 1_572_864, 1 << 26, 27 + 1 + 26),
    (N60, 8_192, 1 << 26, 27 + 1 + 26),
    (15_000_576, 393_216, 1 << 21, 22 + 1 + 24),
    # by bits: 36 key bits beside 26 position bits are 63, 37 are not;
    # a span of 2^k needs k + 1 bits (the field's top value stays free)
    (N60, 8_192, (1 << 36) - 1, 63),
    (N60, 8_192, 1 << 36, None),
    # nothing known of the keys: the field is all the room there is
    (N60, 8_192, None, 63),
    # the build's positions need the bits when it is the larger side
    (4_096, 8_192, 1 << 10, 11 + 1 + 13),
    # by capacities: a build of twice its probe merges, of 2.2 times not
    (1 << 20, 2 << 20, 1 << 20, 21 + 1 + 21),
    (1 << 20, int((1 << 20) * MEET) - 1, 1 << 20, 21 + 1 + 22),
    (1 << 20, int((1 << 20) * MEET) + 1, 1 << 20, None),
    (250_000, 1_572_864, 1 << 26, None),      # a worker's split
])
def test_merge_form_is_picked_by_bits_and_by_capacities(n, m, span, bits):
    assert 2.0 < MEET < 2.2
    assert J.merge_probe_form(n, m, span) == bits
    if bits is not None:
        assert J.merge_probe_word_bits(n, m, span) == bits <= 63
        assert J.merge_probe_wins(n, m)


# ---- through the executor -------------------------------------------------

def fact_and_dim(dup=False, seed=3503):
    rng = np.random.default_rng(seed)
    dk = rng.permutation(3000)[:800]
    if dup:
        dk[1::2] = dk[0::2]
    return [table("fact", {"fk": (rng.integers(0, 3000, 6000),
                                  rng.random(6000) > 0.05),
                           "fv": rng.integers(0, 9, 6000)}),
            table("dim", {"dk": dk, "dv": rng.integers(0, 99, 800)},
                  primary_key=("dk",))]


FACT_DIM_SQL = "SELECT fk, fv, dv FROM fact JOIN dim ON fk = dk"


@pytest.mark.parametrize("gather_ns,strategy", [
    (J.GATHER_NS_PER_INDEX, "sort-probe"), (0.0, "dense-lut")],
    ids=["merge", "lut"])
def test_selective_join_takes_the_form_the_rule_picks(monkeypatch,
                                                      gather_ns, strategy):
    """A quarter of 6,000 fact rows find one of 800 dimension keys, so
    the join compacts; with the gather costed at nothing the rule keeps
    the LUT."""
    monkeypatch.setattr(J, "GATHER_NS_PER_INDEX", gather_ns)
    session, oracle = session_over(fact_and_dim())
    check(session, oracle, FACT_DIM_SQL)
    assert ran(session, "JoinNode") == strategy
    assert session.executor.stats.join_fallbacks == 0
    assert session.executor.stats.join_domain_fallbacks == 0


def test_duplicates_under_a_unique_claim_fall_to_the_expansion():
    session, oracle = session_over(fact_and_dim(dup=True))
    check(session, oracle, FACT_DIM_SQL)
    assert session.executor.stats.join_fallbacks == 1
    assert ran(session, "JoinNode") == "expand"


@pytest.fixture(scope="module")
def single():
    s = Served()
    yield s
    s.stop()


def join_spans(served, sql):
    served.client.execute("SET SESSION enable_tracing = true")
    try:
        rows, info, spans = served.run(sql)
    finally:
        served.client.execute("SET SESSION enable_tracing = false")
    assert info["route"] == "device" and not info.get("distributed")
    return rows, [sp["attributes"] for sp in sorted(
        (sp for sp in spans if sp["name"] == "join"),
        key=lambda sp: sp["startTimeUnixNano"])]


@pytest.mark.parametrize("template,params", [
    (q3, {"segment": "BUILDING", "day": 15}),
    (q3, {"segment": "MACHINERY", "day": 4}),
    (q18, {"quantity": 200}),
    (q18, {"quantity": 250}),
], ids=["q3_building", "q3_machinery", "q18_200", "q18_250"])
def test_tpch_rows_are_the_same_under_either_form(single, monkeypatch,
                                                  template, params):
    sql = template.render(params, "tpch.tiny")
    merged, spans = join_spans(single, sql)
    assert len(merged) > 0 and len(spans) == 2
    # lineitem (60,104 rows) probes the other join's few thousand rows:
    # one merge sort of both, then the compaction
    top = next(a for a in spans if a["probeCapacity"] > 60_000)
    assert top["strategy"] == "sort-probe"
    assert top["sortRows"] == top["probeCapacity"] + top["buildCapacity"]
    assert top["wordBits"] == J.merge_probe_word_bits(
        top["probeCapacity"], top["buildCapacity"], top["domain"]) <= 63
    # the same statement with the gather costed at nothing: the rule
    # keeps the LUT, the span says so and carries no word
    monkeypatch.setattr(J, "GATHER_NS_PER_INDEX", 0.0)
    gathered, spans = join_spans(single, sql)
    assert gathered == merged
    for a in spans:
        assert a["strategy"] != "sort-probe"
        assert "wordBits" not in a and "sortRows" not in a


def test_operations_guide_lists_the_join_span_attributes():
    path = os.path.join(os.path.dirname(__file__), "..", "docs",
                        "operations.md")
    with open(path) as f:
        text = f.read()
    row = next(ln for ln in text.splitlines()
               if ln.startswith("| `aggregate`, `join`"))
    assert "`wordBits`" in row and "`sortRows`" in row
    assert "`sort-probe`" in text
