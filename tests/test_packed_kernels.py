"""Differential tests: packed (2-operand-sort) kernels vs the general
kernels. The packed paths activate in production only above
SORT_SMALL_ROWS (cheap-compile threshold), so no end-to-end test crosses
them on CPU — these call the kernels directly on small inputs and also
force the executor dispatch through them.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import trino_tpu.exec.executor as E
from trino_tpu.batch import (Batch, Column, batch_from_numpy,
                             batch_to_numpy)
from trino_tpu.ops.aggregate import (AggSpec, key_pack_plan,
                                     packed_sort_group_aggregate,
                                     sort_group_aggregate)
from trino_tpu.ops.sort import sort_batch, sort_batch_packed, sort_pack_plan


def rows_of(batch):
    arrays, valids = batch_to_numpy(batch)
    return [tuple(a[i].item() if v[i] else None
                  for a, v in zip(arrays, valids))
            for i in range(len(arrays[0]))]


def rand_batch(n=4000, seed=0, with_nulls=True):
    rng = np.random.default_rng(seed)
    k1 = rng.integers(-50, 50, n).astype(np.int64)
    k2 = rng.integers(0, 7, n).astype(np.int64)
    v = rng.integers(-1000, 1000, n).astype(np.int64)
    valids = None
    if with_nulls:
        valids = [rng.random(n) > 0.1, rng.random(n) > 0.2,
                  rng.random(n) > 0.15]
    return batch_from_numpy([k1, k2, v], valids=valids)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_packed_agg_matches_general(seed):
    b = rand_batch(seed=seed)
    aggs = (AggSpec("sum", 2), AggSpec("count", 2), AggSpec("min", 2),
            AggSpec("max", 2), AggSpec("count_star", None))
    plan = key_pack_plan(b, (0, 1))
    assert plan is not None
    kmins, bits = plan
    got = packed_sort_group_aggregate(b, jnp.asarray(kmins), (0, 1),
                                      bits, aggs, 1024)
    want = sort_group_aggregate(b, (0, 1), aggs, 1024)
    assert sorted(rows_of(got), key=repr) == \
        sorted(rows_of(want), key=repr)


def test_packed_agg_all_null_key():
    n = 512
    b = batch_from_numpy(
        [np.zeros(n, dtype=np.int64), np.arange(n, dtype=np.int64)],
        valids=[np.zeros(n, dtype=bool), None])
    aggs = (AggSpec("sum", 1),)
    plan = key_pack_plan(b, (0,))
    kmins, bits = plan
    got = packed_sort_group_aggregate(b, jnp.asarray(kmins), (0,), bits,
                                      aggs, 64)
    want = sort_group_aggregate(b, (0,), aggs, 64)
    assert sorted(rows_of(got), key=repr) == \
        sorted(rows_of(want), key=repr)


@pytest.mark.parametrize("wide", [False, True])
@pytest.mark.parametrize("asc,nf", [(True, False), (True, True),
                                    (False, False), (False, True)])
def test_packed_sort_matches_general(asc, nf, wide):
    b = rand_batch(seed=3)
    if wide:
        # a 61-bit span on the leading key pushes the second key into a
        # second word: the LSD radix over words must order like the
        # general multi-operand sort, ties and NULLs included
        k1 = b.columns[0]
        stretched = jnp.where(k1.data > 0, k1.data << 54, k1.data)
        b = Batch((Column(stretched, k1.valid),) + b.columns[1:], b.live)
    keys = ((0, asc, nf), (1, not asc, not nf))
    plan = sort_pack_plan(b, keys)
    assert plan is not None
    kmins, bits, splits = plan
    assert len(splits) == (2 if wide else 1)
    got = sort_batch_packed(b, jnp.asarray(kmins), keys, bits, 100,
                            splits)
    want = sort_batch(b, keys, 100)
    assert rows_of(got) == rows_of(want)


@pytest.mark.parametrize("top", [(1 << 32) - 100, (1 << 32) + 100,
                                 1 << 20])
@pytest.mark.parametrize("tie", [False, True])
def test_order_by_layout_does_not_follow_the_measures_span(top, tie):
    """A measure of 32 bits, of 33 and of 21 before a 9-bit key, 4,096
    rows: one layout (62 - 12 - 12 = 38 bits and 12), one program, and
    the general sort's order. With a 45-bit tie-breaker behind them
    (q3's shape: the keys take two words) each word's first key takes
    its word's room."""
    rng = np.random.default_rng(35)
    measure = rng.integers(0, top, 4096)
    measure[7] = top
    b = batch_from_numpy(
        [measure, rng.integers(0, 400, 4096),
         rng.integers(0, 1 << 45, 4096)],
        [rng.random(4096) > 0.02, None, None])
    keys = ((0, False, False), (1, True, False))
    want = ((38, 12), ((0, 2),))
    if tie:
        keys += ((2, True, False),)
        want = ((38, 12, 50), ((0, 2), (2, 3)))
    kmins, bits, splits = sort_pack_plan(b, keys)
    assert (bits, splits) == want
    got = sort_batch_packed(b, jnp.asarray(kmins), keys, bits, 10, splits)
    assert rows_of(got) == rows_of(sort_batch(b, keys, 10))


def test_pack_plan_refuses_wide_domains():
    n = 64
    b = batch_from_numpy(
        [np.array([0, 1 << 60] * (n // 2), dtype=np.int64),
         np.array([0, 1 << 60] * (n // 2), dtype=np.int64)])
    assert key_pack_plan(b, (0, 1)) is None


def test_executor_dispatch_through_packed(monkeypatch):
    """Force the production dispatch (threshold crossed) end-to-end."""
    monkeypatch.setattr(E, "SORT_SMALL_ROWS", 16)
    from trino_tpu.exec.session import Session
    s = Session(default_schema="tiny")
    got = s.execute(
        "SELECT l_returnflag, l_linestatus, sum(l_quantity) q, count(*)"
        " FROM lineitem GROUP BY l_returnflag, l_linestatus"
        " ORDER BY q DESC, l_returnflag, l_linestatus").rows
    monkeypatch.setattr(E, "SORT_SMALL_ROWS", 1 << 40)
    s2 = Session(default_schema="tiny")
    want = s2.execute(
        "SELECT l_returnflag, l_linestatus, sum(l_quantity) q, count(*)"
        " FROM lineitem GROUP BY l_returnflag, l_linestatus"
        " ORDER BY q DESC, l_returnflag, l_linestatus").rows
    assert got == want


def test_packed_key_bits_are_rounded_to_a_lattice():
    """Key bits are static arguments of the packed kernels: batches
    whose spans differ a little (the splits of one scan) must share one
    program, so the bits round up to multiples of 4."""
    from trino_tpu.ops.aggregate import key_pack_plan_words
    plans = []
    for span in (70_000, 90_000, 120_000):          # 17 bits each
        b = batch_from_numpy([np.arange(4096, dtype=np.int64) * span
                              // 4096 + 10**9,
                              np.arange(4096, dtype=np.int32) % 5])
        plans.append(key_pack_plan_words(b, (0, 1)))
    assert {p[1] for p in plans} == {(20, 4)}
    assert {p[2] for p in plans} == {((0, 2),)}
    wide = batch_from_numpy([np.array([0, (1 << 61) - 8] * 512)])
    assert key_pack_plan_words(wide, (0,))[1] == (62,)   # capped, not 64
    # 25 + 25 bits and 12 index bits fit lsd_word_sort's one-operand
    # form; 28 + 28 would not, so the measured bits stay
    tight = batch_from_numpy([np.array([0, (1 << 25) - 8] * 2048),
                              np.array([0, (1 << 25) - 8] * 2048)])
    assert key_pack_plan_words(tight, (0, 1))[1] == (25, 25)


def test_mostly_dead_batch_aggregates_through_the_small_kernel(monkeypatch):
    """A selective join's split leaves a few live rows in a big batch:
    they are compacted and take the general kernel (data-independent
    statics) — no packed program per split."""
    monkeypatch.setattr(E, "SORT_SMALL_ROWS", 64)
    from trino_tpu.exec.profiler import RECORDER
    from trino_tpu.exec.session import Session
    sql = ("SELECT o_orderkey, o_orderdate, sum(o_totalprice) s, count(*)"
           " FROM orders WHERE o_totalprice > 400000"
           " GROUP BY o_orderkey, o_orderdate ORDER BY s DESC LIMIT 20")

    def packed_calls():
        return sum(e["compiles"] + e["hits"] for e in RECORDER.snapshot()
                   if e["site"] == "aggregate.packed_sort_group_aggregate")
    before = packed_calls()
    s = Session(default_schema="tiny")
    got = s.execute(sql).rows
    assert packed_calls() == before
    monkeypatch.setattr(E, "SORT_SMALL_ROWS", 1 << 40)
    want = Session(default_schema="tiny").execute(sql).rows
    assert got == want and 0 < len(got) <= 20


def test_compact_gather_matches_sort():
    b = rand_batch(seed=5)
    import jax.numpy as jnp2
    live = np.asarray(b.live).copy()
    live[::3] = False
    b = b.with_live(jnp2.asarray(live))
    cap = 2048
    got = E._compact_gather(b, cap)
    want = E._compact_sort(b, cap)
    assert rows_of(got) == rows_of(want)


def test_two_phase_dense_join_matches(monkeypatch):
    """Selective big-probe inner joins compact before build gathers;
    results must equal the single-kernel dense join."""
    monkeypatch.setattr(E, "SORT_SMALL_ROWS", 16)
    from trino_tpu.exec.session import Session
    s = Session(default_schema="tiny")
    sql = ("SELECT o_orderkey, o_totalprice, c_name"
           " FROM orders o JOIN customer c ON o.o_custkey = c.c_custkey"
           " WHERE c.c_acctbal < -900"
           " ORDER BY o_orderkey LIMIT 50")
    got = s.execute(sql).rows
    assert s.executor.stats.dynamic_filter_compactions >= 1
    monkeypatch.setattr(E, "SORT_SMALL_ROWS", 1 << 40)
    want = Session(default_schema="tiny").execute(sql).rows
    assert got == want and len(got) > 0


def test_three_column_join_keys():
    """>2-column equi-joins overflowed the fixed 32-bit key packing and
    silently collided; range-compressed packing fixes them."""
    import sqlite3
    from trino_tpu.catalog import Catalog
    from trino_tpu.connectors.memory import MemoryConnector
    from trino_tpu.exec.session import Session as S
    cat = Catalog()
    cat.register("m", MemoryConnector())
    s = S(catalog=cat, default_cat="m", default_schema="s")
    s.execute("CREATE TABLE m.s.l (a bigint, b bigint, c bigint,"
              " v bigint)")
    s.execute("CREATE TABLE m.s.r (a bigint, b bigint, c bigint,"
              " w bigint)")
    rows_l, rows_r = [], []
    import random
    rnd = random.Random(11)
    for i in range(300):
        rows_l.append((rnd.randrange(5), rnd.randrange(70000),
                       rnd.randrange(1 << 33), i))
    for i in range(120):
        rows_r.append((rnd.randrange(5), rnd.randrange(70000),
                       rnd.randrange(1 << 33), i))
    rows_r += rows_l[:40]                       # guarantee matches
    s.execute("INSERT INTO m.s.l VALUES " + ",".join(
        str(r) for r in rows_l))
    s.execute("INSERT INTO m.s.r VALUES " + ",".join(
        str(r) for r in rows_r))
    got = s.execute(
        "SELECT count(*), sum(v + w) FROM l, r"
        " WHERE l.a = r.a AND l.b = r.b AND l.c = r.c").rows
    o = sqlite3.connect(":memory:")
    o.execute("CREATE TABLE l (a,b,c,v)")
    o.execute("CREATE TABLE r (a,b,c,w)")
    o.executemany("INSERT INTO l VALUES (?,?,?,?)", rows_l)
    o.executemany("INSERT INTO r VALUES (?,?,?,?)", rows_r)
    want = o.execute(
        "SELECT count(*), sum(v + w) FROM l, r"
        " WHERE l.a = r.a AND l.b = r.b AND l.c = r.c").fetchall()
    assert [tuple(x) for x in got] == want
    assert got[0][0] >= 40


def test_multiword_packing_wide_group_by():
    """q10's shape: many group keys whose combined width exceeds one
    int64 pack into MULTIPLE words sorted LSD-radix style (stable
    2-operand sorts) — results identical to the general kernel."""
    import numpy as np

    from trino_tpu.batch import batch_from_numpy
    from trino_tpu.ops.aggregate import (key_pack_plan,
                                         key_pack_plan_words,
                                         sort_group_aggregate)
    rng = np.random.default_rng(11)
    n = 20_000
    cols = [rng.integers(0, 1 << 17, n),       # 7 wide keys > 62 bits
            rng.integers(0, 1 << 17, n),
            rng.integers(0, 1 << 21, n),
            rng.integers(0, 1 << 17, n),
            rng.integers(0, 25, n),
            rng.integers(0, 1 << 17, n),
            rng.integers(0, 1 << 17, n),
            rng.integers(0, 1000, n)]          # value
    b = batch_from_numpy(cols)
    keys = tuple(range(7))
    assert key_pack_plan(b, keys) is None       # single word: too wide
    plan = key_pack_plan_words(b, keys)
    assert plan is not None
    kmins, bits, splits = plan
    assert len(splits) >= 2
    aggs = (AggSpec("sum", 7), AggSpec("count_star", None))
    got = packed_sort_group_aggregate(b, jnp.asarray(kmins), keys, bits,
                                      aggs, 1 << 15, splits)
    want = sort_group_aggregate(b, keys, aggs, 1 << 15)

    def rows(batch):
        live = np.asarray(batch.live)
        out = []
        for i in np.nonzero(live)[0]:
            out.append(tuple(int(np.asarray(c.data)[i])
                             for c in batch.columns))
        return sorted(out)
    assert rows(got) == rows(want)


def test_multiword_packing_nulls_and_dead_rows():
    import numpy as np

    from trino_tpu.batch import batch_from_numpy
    from trino_tpu.ops.aggregate import (key_pack_plan_words,
                                         sort_group_aggregate)
    rng = np.random.default_rng(3)
    n = 5000
    k1 = rng.integers(0, 1 << 40, n)
    k2 = rng.integers(0, 1 << 40, n)
    v = rng.integers(0, 100, n)
    valid1 = rng.random(n) > 0.1
    b = batch_from_numpy([k1, k2, v], valids=[valid1, None, None])
    plan = key_pack_plan_words(b, (0, 1))
    kmins, bits, splits = plan
    assert len(splits) == 2                     # 42+42 bits -> 2 words
    aggs = (AggSpec("sum", 2), AggSpec("count", 2))
    got = packed_sort_group_aggregate(b, jnp.asarray(kmins), (0, 1),
                                      bits, aggs, 8192, splits)
    want = sort_group_aggregate(b, (0, 1), aggs, 8192)
    gl, wl = int(np.asarray(got.live).sum()), \
        int(np.asarray(want.live).sum())
    assert gl == wl
    def total(batch, j):
        live = np.asarray(batch.live)
        return int(np.asarray(batch.columns[j].data)[live].sum())
    assert total(got, 2) == total(want, 2)
    assert total(got, 3) == total(want, 3)


def test_key_span_measures_combined_packed_key():
    """Multi-key packed joins window by the COMBINED key (32 bits per
    trailing column); _key_span measuring keys[0] alone underestimated
    by ~2^32, so adapted windows always escaped (ADVICE round-5)."""
    import numpy as np

    from trino_tpu.exec.chunked import _key_span
    from trino_tpu.ops.join import _combined_key

    b = batch_from_numpy([np.array([5, 5, 5, 5], dtype=np.int64),
                          np.array([1, 9, 2, 7], dtype=np.int64)])
    key, _ = _combined_key(b, (0, 1))
    k = np.asarray(key)[np.asarray(b.live)]
    assert int(_key_span(b, (0, 1))) == int(k.max() - k.min() + 1)
    # the old keys[0]-only measurement would collapse distinct combined
    # keys: a second leading-key value must widen the span past 2^32
    b3 = batch_from_numpy([np.array([5, 6], dtype=np.int64),
                           np.array([1, 1], dtype=np.int64)])
    assert int(_key_span(b3, (0, 1))) == (1 << 32) + 1
    # single-key measurement is unchanged
    assert int(_key_span(b, (1,))) == 9
    # and a NULL-masked row is excluded from the extent
    b2 = batch_from_numpy([np.array([5, 5, 5], dtype=np.int64),
                           np.array([1, 2, 1000], dtype=np.int64)],
                          valids=[None, np.array([True, True, False])])
    assert int(_key_span(b2, (0, 1))) == 2


# ---- the value-carrying form of the packed sort aggregate -----------------
# (key bits + argument bits fit one sort word: the aggregates' inputs ride
# the sort, nothing is gathered through a permutation)

CARRIED_ROWS = 12_000         # over SORT_GENERAL_ROWS, where it engages
ALL_FUNCS = (AggSpec("sum", 1), AggSpec("count", 1), AggSpec("min", 1),
             AggSpec("max", 1), AggSpec("count_star", None))


def numpy_group_by(arrays, valids, live, keys, aggs):
    """{key tuple (None = NULL): state tuple (None = NULL)}, in Python
    integers: the plain answer."""
    groups = {}
    for i in np.nonzero(live)[0]:
        k = tuple(int(arrays[j][i]) if valids[j][i] else None for j in keys)
        groups.setdefault(k, []).append(i)
    out = {}
    for k, rows in groups.items():
        state = []
        for spec in aggs:
            if spec.func == "count_star":
                state.append(len(rows))
                continue
            vals = [int(arrays[spec.arg_index][i]) for i in rows
                    if valids[spec.arg_index][i]]
            if spec.func == "count":
                state.append(len(vals))
            elif not vals:
                state.append(None)
            else:
                state.append({"sum": sum, "min": min,
                              "max": max}[spec.func](vals))
        out[k] = tuple(state)
    return out


def carried_case(name):
    """(arrays, valids, live, key columns, aggs, carried?) of one case."""
    rng = np.random.default_rng(len(name))
    n = CARRIED_ROWS
    k = rng.integers(100, 400, n)
    v = rng.integers(0, 5000, n)
    w = rng.integers(0, 90, n)
    ok = [np.ones(n, bool) for _ in range(3)]
    live = np.ones(n, bool)
    keys, aggs, carried = (0,), ALL_FUNCS, True
    if name == "null_keys":
        ok[0] = rng.random(n) > 0.2
    elif name == "null_arguments":
        # keys 100-119 see no valid argument: sum NULL, count 0
        ok[1] = (rng.random(n) > 0.3) & (k >= 120)
    elif name == "dead_rows_interleaved":
        live = rng.random(n) > 0.4
        ok[0] = rng.random(n) > 0.1
        ok[1] = rng.random(n) > 0.1
    elif name == "all_dead":
        live = np.zeros(n, bool)
    elif name == "negative_values":
        k, v = k - 1000, v - 4000
    elif name == "two_aggregates_two_columns":
        ok[1], ok[2] = rng.random(n) > 0.2, rng.random(n) > 0.2
        aggs = (AggSpec("sum", 1), AggSpec("max", 2), AggSpec("count", 2),
                AggSpec("min", 1), AggSpec("sum", 2))
    elif name == "two_keys_count_star_alone":
        keys, aggs = (0, 2), (AggSpec("count_star", None),)
        ok[2] = rng.random(n) > 0.2
    elif name in ("bits_63_taken", "bits_64_falls_back"):
        # 16 bits of key (rounded from 14 measured; 15 where the room is
        # short) under 47 or 48 of argument: the word has 63
        k = rng.integers(0, 10_000, n)
        top = (1 << 46) if name == "bits_63_taken" else (1 << 47)
        v = rng.integers(0, 300, n) * (top // 300)
        v[:2] = 0, top
        ok[1] = rng.random(n) > 0.1
        aggs = (AggSpec("sum", 1), AggSpec("count", 1), AggSpec("max", 1))
        carried = name == "bits_63_taken"
    else:
        assert name == "plain"
    return [k, v, w], ok, live, keys, aggs, carried


@pytest.mark.parametrize("form", ["in-place", "dense"])
@pytest.mark.parametrize("name", [
    "plain", "null_keys", "null_arguments", "dead_rows_interleaved",
    "all_dead", "negative_values", "two_aggregates_two_columns",
    "two_keys_count_star_alone", "bits_63_taken", "bits_64_falls_back"])
def test_carried_aggregate_matches_numpy_and_the_general_kernel(name, form):
    from trino_tpu.ops.aggregate import key_pack_plan_words
    arrays, valids, live, keys, aggs, carried = carried_case(name)
    b = batch_from_numpy(arrays, valids=valids)
    live_d = np.zeros(b.capacity, bool)
    live_d[:len(live)] = live
    b = Batch(b.columns, jnp.asarray(live_d))
    kmins, bits, splits, values = key_pack_plan_words(b, keys, aggs=aggs)
    assert (values is not None) == carried
    if name == "bits_63_taken":
        assert sum(bits) + sum(values[1]) == 63
    vmins, value_bits = values or (None, None)
    # 1,024 is over every case's group count but those of the two-key
    # case and of bits_*'s 10,000 keys, which overflow the dense form
    in_place = carried and form == "in-place"
    capacity = b.capacity if form == "in-place" else 1024
    got = packed_sort_group_aggregate(
        b, jnp.asarray(kmins), keys, bits, aggs, capacity, splits,
        None if vmins is None else jnp.asarray(vmins), value_bits, in_place)
    assert got.capacity == (b.capacity if in_place else capacity)
    permuted = packed_sort_group_aggregate(
        b, jnp.asarray(kmins), keys, bits, aggs, capacity, splits)
    n_keys = len(keys)
    answer = {r[:n_keys]: r[n_keys:] for r in rows_of(got)}
    assert len(answer) == int(np.asarray(got.live).sum())
    want = numpy_group_by(arrays, valids, live, keys, aggs)
    if len(want) <= capacity:
        assert answer == want
        # the same rows in the same (key) order as the permutation form
        assert rows_of(got) == rows_of(permuted)
        general = sort_group_aggregate(b, keys, aggs, capacity)
        assert sorted(rows_of(general), key=repr) == \
            sorted(rows_of(got), key=repr)
    else:
        # groups past the capacity are dropped and the live count says
        # so (the executor retries); what the value-carrying form keeps
        # is right (the permutation form's last kept group is not)
        assert len(answer) == capacity == \
            int(np.asarray(permuted.live).sum())
        assert not carried or all(want[key] == state
                                  for key, state in answer.items())
    if name == "null_arguments":
        assert answer[(100,)][:2] == (None, 0)
    # dtypes are the permutation form's, plane by plane
    for c, p in zip(got.columns, permuted.columns):
        assert (c.data.dtype, c.valid.dtype) == (p.data.dtype, p.valid.dtype)


def jaxpr_equations(jaxpr):
    """Every equation of `jaxpr`, those of its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else (value,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from jaxpr_equations(inner)


def addressed_reads(form, capacity):
    """[(primitive, number of indices)] of the gather and scatter
    equations the packed sort aggregate traces to, Q18's statics."""
    import jax
    n = 16_384
    b = batch_from_numpy([np.arange(n) // 4, np.arange(n) % 50 * 100])
    aggs = (AggSpec("sum", 1),)
    carried = (None, None) if form == "permutation" else \
        (jnp.zeros(1, jnp.int64), (16,))
    jaxpr = jax.make_jaxpr(
        lambda batch, kmins, vmins: packed_sort_group_aggregate(
            batch, kmins, (0,), (28,), aggs, capacity, ((0, 1),),
            vmins, carried[1], form == "in-place"))(
        b, jnp.zeros(1, jnp.int64), carried[0])
    out = []
    for eqn in jaxpr_equations(jaxpr.jaxpr):
        name = eqn.primitive.name
        if name == "gather" or name.startswith("scatter"):
            out.append((name, int(np.prod(eqn.invars[1].aval.shape[:-1]))))
    return out


def test_carried_aggregate_addresses_nothing_through_a_permutation():
    """The property the value-carrying form exists for, read off the
    traced program: in place it has no gather and no scatter at all;
    dense it gathers at the group capacity only. The same walk over the
    permutation form finds its gathers at the input's length and its
    scatter, so it looks in the right place."""
    n, groups = 16_384, 2_048
    assert addressed_reads("in-place", groups) == []
    dense = addressed_reads("dense", groups)
    assert dense and all(kind == "gather" and indices == groups
                         for kind, indices in dense)
    permuted = addressed_reads("permutation", groups)
    by_length = {}
    for kind, indices in permuted:
        by_length.setdefault((kind.split("-")[0], indices), []).append(kind)
    # live[perm], w[perm], data[perm], valid[perm]: four arrays at the
    # input's length (six 32-bit planes on the chip); the start_lut
    # scatter; eight and more arrays at the group capacity
    assert len(by_length[("gather", n)]) == 4
    assert len(by_length[("scatter", n)]) == 1
    assert len(by_length[("gather", groups)]) >= 8
