"""Pallas tiled-gather kernel tests (interpret mode on CPU so tier-1
exercises the real kernel logic): bit-exact parity with the jnp.take
path for windowed offsets, miss sentinels, multi-payload gathers and
non-tile-aligned tails, plus the three probe-site integrations
(ops/join.py dense gather, the windowed-LUT chunk probe, and the
aggregate group readback) with clean fallback when disabled."""

import numpy as np
import pytest

import jax.numpy as jnp

from trino_tpu.batch import batch_from_numpy, batch_to_numpy
from trino_tpu.ops import pallas_gather as pg


def rows_of(batch):
    arrays, valids = batch_to_numpy(batch)
    return [tuple(a[i].item() if v[i] else None
                  for a, v in zip(arrays, valids))
            for i in range(len(arrays[0]))]


def _ref(tables, idx, fills):
    return pg._xla_gather(tables, idx, fills)


@pytest.mark.parametrize("n,w", [(pg.TILE, pg.SLAB),       # aligned
                                 (3000, 5000),             # ragged tail
                                 (17, 129)])               # tiny
def test_gather_matches_take(n, w):
    rng = np.random.default_rng(n + w)
    tables = [
        jnp.asarray(rng.integers(-(1 << 62), 1 << 62, w)),
        jnp.asarray(rng.integers(-100, 100, w).astype(np.int8)),
        jnp.asarray(rng.integers(0, 2, w).astype(bool)),
        jnp.asarray(rng.normal(size=w)),
        jnp.asarray(rng.normal(size=w).astype(np.float32)),
        jnp.asarray(rng.integers(-(1 << 30), 1 << 30, w)
                    .astype(np.int32))]
    idx = jnp.asarray(rng.integers(0, w, n))
    fills = [0, -1, False, 0.0, 0.0, 7]
    got = pg.gather_columns(tables, idx, fills, mode="interpret")
    want = _ref(tables, idx, fills)
    for g, t, wv in zip(got, tables, want):
        assert g.dtype == t.dtype
        assert np.array_equal(np.asarray(g), np.asarray(wv),
                              equal_nan=True)


def test_gather_miss_sentinel_fills():
    rng = np.random.default_rng(0)
    w, n = 2048, 1500
    t = jnp.asarray(rng.integers(-(1 << 40), 1 << 40, w))
    idx = np.asarray(rng.integers(0, w, n))
    idx[::7] = -1                                # miss sentinel
    idx[::11] = w + 3                            # out of range -> fill
    got = pg.gather_columns([t], jnp.asarray(idx), [-5],
                            mode="interpret")[0]
    want = _ref([t], jnp.asarray(idx), [-5])[0]
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert (np.asarray(got)[::7] == -5).all()


def test_gather_many_tables_plane_groups():
    """More int32 planes than one pallas_call carries -> the wrapper
    splits into groups; results stay exact per table."""
    rng = np.random.default_rng(1)
    w, n = 1000, 900
    n_tables = pg.MAX_PLANES + 3          # int64 tables: 2 planes each
    tables = [jnp.asarray(rng.integers(-(1 << 50), 1 << 50, w))
              for _ in range(n_tables)]
    idx = jnp.asarray(rng.integers(0, w, n))
    got = pg.gather_columns(tables, idx, mode="interpret")
    for g, t in zip(got, tables):
        assert np.array_equal(np.asarray(g), np.asarray(t[idx]))


def test_gather_fallback_when_disabled_or_oversized():
    rng = np.random.default_rng(2)
    t = jnp.asarray(rng.integers(0, 100, 64))
    idx = jnp.asarray(rng.integers(0, 64, 32))
    off = pg.gather_columns([t], idx, mode="off")[0]
    assert np.array_equal(np.asarray(off), np.asarray(t[idx]))
    # above the scan cap the wrapper must fall back, not fail
    big = jnp.zeros(pg.SCAN_MAX_ELEMS + 1, dtype=jnp.int64)
    out = pg.gather_columns([big], idx, mode="interpret")[0]
    assert np.asarray(out).shape == (32,)


def test_windowed_near_sorted_no_escapes():
    rng = np.random.default_rng(3)
    w = 1 << 15
    lut = jnp.asarray(rng.integers(-(1 << 40), 1 << 40, w))
    planes = pg.prepare_word_planes(lut)
    idx = jnp.sort(jnp.asarray(rng.integers(0, w, 4096)))
    word, esc = pg.gather_word_windowed(planes, idx, "int64",
                                        mode="interpret")
    assert int(esc) == 0
    assert np.array_equal(np.asarray(word),
                          np.asarray(lut[idx].astype(jnp.int64)))


def test_windowed_escapes_counted_and_filled():
    """Scattered indices overflow their tile's window: every escaped
    row must come back as the miss word (0) and be counted, so the
    chunked driver's escape check forces the plain rerun."""
    rng = np.random.default_rng(4)
    w = 1 << 15
    lut = jnp.asarray(rng.integers(1, 1 << 40, w))   # nonzero words
    planes = pg.prepare_word_planes(lut)
    idx = jnp.asarray(rng.integers(0, w, 2048))
    word, esc = pg.gather_word_windowed(planes, idx, "int64",
                                        mode="interpret")
    got, want = np.asarray(word), np.asarray(lut[idx].astype(jnp.int64))
    mism = got != want
    assert int(esc) > 0
    assert mism.sum() == int(esc)
    assert (got[mism] == 0).all()


def test_windowed_miss_sentinel_not_escaped():
    rng = np.random.default_rng(5)
    w = 8192
    lut = jnp.asarray(rng.integers(1, 1 << 30, w).astype(np.int32))
    planes = pg.prepare_word_planes(lut)
    idx = np.sort(rng.integers(0, w, 1024))
    idx[::5] = -1
    word, esc = pg.gather_word_windowed(planes, jnp.asarray(idx),
                                        "int32", mode="interpret")
    assert int(esc) == 0
    got = np.asarray(word)
    assert (got[::5] == 0).all()
    ok = idx >= 0
    assert np.array_equal(got[ok], np.asarray(lut)[idx[ok]])


# ---------------------------------------------------------------------------
# probe-site integrations: kernel on vs off must be row-identical
# ---------------------------------------------------------------------------

def _join_fixture(seed=11, domain=2048, nb=500, np_=3000):
    rng = np.random.default_rng(seed)
    bk = rng.permutation(domain)[:nb].astype(np.int64)
    build = batch_from_numpy(
        [bk, rng.integers(-1000, 1000, nb).astype(np.int64),
         rng.normal(size=nb)],
        valids=[None, rng.random(nb) > .2, None])
    probe = batch_from_numpy(
        [rng.integers(-10, domain + 10, np_).astype(np.int64),
         rng.integers(0, 50, np_).astype(np.int64)],
        valids=[rng.random(np_) > .1, None])
    return probe, build, domain


@pytest.mark.parametrize("kind", ["inner", "left", "semi", "anti"])
def test_dense_join_site_parity(kind):
    from trino_tpu.ops.join import join_unique_build_dense
    probe, build, domain = _join_fixture()
    out_off, d0, o0 = join_unique_build_dense(
        probe, build, (0,), (0,), kind, domain)
    out_on, d1, o1 = join_unique_build_dense(
        probe, build, (0,), (0,), kind, domain, "interpret")
    assert rows_of(out_off) == rows_of(out_on)
    assert int(d0) == int(d1) and int(o0) == int(o1)


def test_windowed_join_site_parity():
    from trino_tpu.ops.join import (dense_build_packed_lut,
                                    dense_join_packed,
                                    dense_join_packed_windowed)
    rng = np.random.default_rng(12)
    domain, nb, np_ = 4096, 800, 2048
    bk = rng.permutation(domain)[:nb].astype(np.int64)
    bval = rng.integers(-500, 500, nb).astype(np.int64)
    build = batch_from_numpy([bk, bval])
    meta, los = ((1, 16, 1, 17),), jnp.asarray([-500])
    lut, exp, oob, occ = dense_build_packed_lut(build, (0,), domain,
                                                meta, "int32", los)
    probe = batch_from_numpy(
        [np.sort(rng.integers(0, domain, np_)).astype(np.int64),
         rng.integers(0, 9, np_).astype(np.int64)])
    out_dtypes = ("int64", "int64")
    planes = pg.prepare_word_planes(lut)
    o_xla, e_xla, s_xla = dense_join_packed_windowed(
        probe, lut, los, (0,), meta, 0, out_dtypes, "inner", 8192)
    o_pal, e_pal, s_pal = dense_join_packed_windowed(
        probe, lut, los, (0,), meta, 0, out_dtypes, "inner", 8192,
        word_dtype="int32", gather_mode="interpret", lut_planes=planes)
    assert int(e_xla) == 0 and int(e_pal) == 0
    assert int(s_xla) == int(s_pal)
    assert rows_of(o_xla) == rows_of(o_pal)
    # and both agree with the full-table probe
    o_full = dense_join_packed(probe, lut, los, (0,), meta, 0,
                               out_dtypes, "inner", "interpret")
    assert rows_of(o_full) == rows_of(o_pal)


def test_aggregate_group_gather_parity():
    from trino_tpu.ops.aggregate import AggSpec, sort_group_aggregate
    rng = np.random.default_rng(13)
    n = 4000
    b = batch_from_numpy(
        [rng.integers(0, 40, n), rng.integers(-5, 5, n),
         rng.integers(-100, 100, n)],
        valids=[rng.random(n) > .1, None, rng.random(n) > .2])
    aggs = (AggSpec("sum", 2), AggSpec("count", 2), AggSpec("min", 2),
            AggSpec("max", 2), AggSpec("count_star", None))
    off = sort_group_aggregate(b, (0, 1), aggs, 512)
    on = sort_group_aggregate(b, (0, 1), aggs, 512, "interpret")
    assert rows_of(off) == rows_of(on)


def test_session_property_end_to_end():
    """SET SESSION enable_pallas_gather = true routes the dense join
    probes through the kernel (interpret mode on CPU) and the results
    stay identical to the default path."""
    from trino_tpu.exec.session import Session
    sql = ("SELECT o_orderkey, o_totalprice, c_name"
           " FROM orders o JOIN customer c ON o.o_custkey = c.c_custkey"
           " ORDER BY o_orderkey LIMIT 20")
    want = Session(default_schema="tiny").execute(sql).rows
    s = Session(default_schema="tiny")
    s.execute("SET SESSION enable_pallas_gather = true")
    got = s.execute(sql)
    assert got.rows == want
    assert s.executor.gather_mode() == "interpret"
    assert s.executor.stats.pallas_gather_calls >= 1
    # and off again
    s.execute("SET SESSION enable_pallas_gather = false")
    got2 = s.execute(sql)
    assert got2.rows == want
    assert s.executor.gather_mode() == "off"


def test_gather_micro_harness(tmp_path):
    """bench.py --gather-micro smoke: emits the JSON artifact with
    kernel-vs-take records (interpret mode under JAX_PLATFORMS=cpu)."""
    import bench
    out = bench.gather_micro(table_sizes=[1024], probe_rows=2048,
                             n_tables=2, runs=1,
                             out_path=str(tmp_path / "gm.json"))
    assert out["smoke"] is True and out["mode"] == "interpret"
    kinds = {r["kind"] for r in out["records"]}
    assert kinds == {"scan", "windowed"}
    for r in out["records"]:
        assert r["kernel_ns_per_elem"] > 0
        assert r["take_ns_per_elem"] > 0
    import json
    assert json.load(open(tmp_path / "gm.json"))["records"]
