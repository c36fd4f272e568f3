"""A split loop's join over a pinned build (`Executor._chunk_lut_join`):
the LUT's word carries the build's payload where it fits one, so a probe
is one gather; where it does not, the row-id LUT runs as before.

The reference in every case is the row-id form itself, `dense_build_lut`
and `dense_join_with_lut` over the same batches.
"""

import numpy as np
import pytest

from trino_tpu.batch import batch_from_numpy
from trino_tpu.catalog import Catalog
from trino_tpu.exec.executor import Executor
from trino_tpu.exec.profiler import RECORDER
from trino_tpu.ops.join import (dense_build_lut, dense_join_with_lut,
                                pack_refusal)
from trino_tpu.planner import logical as L
from trino_tpu.utils import tracing

DOMAIN = 5000


def _all_slots(batch):
    """Every slot of the batch in order: None for a dead row, else its
    values with None for a NULL."""
    live = np.asarray(batch.live)
    return [tuple(np.asarray(c.data)[i].item()
                  if np.asarray(c.valid)[i] else None
                  for c in batch.columns) if live[i] else None
            for i in range(batch.capacity)]


def _node(kind, right_keys=(0,)):
    return L.JoinNode(kind=kind, left=None, right=None,
                      left_keys=(1,) * len(right_keys),
                      right_keys=right_keys, residual=None,
                      build_unique=True, output=(),
                      build_key_domain=DOMAIN)


def _probe(rng, n=3000, capacity=4096):
    """Probe keys that hit, miss, leave the domain on both sides and are
    NULL; rows that are dead (the capacity's tail and a mask)."""
    keys = rng.integers(-50, DOMAIN + 50, n).astype(np.int64)
    batch = batch_from_numpy(
        [rng.integers(0, 9, n).astype(np.int32), keys],
        valids=[None, rng.random(n) > .05], capacity=capacity)
    live = np.asarray(batch.live) & (rng.random(capacity) > .1)
    return batch.with_live(live)


def _build(rng, payload, valids=None, nb=900, keys=None):
    keys = rng.permutation(DOMAIN)[:nb].astype(np.int64) \
        if keys is None else keys
    return batch_from_numpy([keys] + payload,
                            valids=[None] + (valids or [None] * len(payload)))


def _reference(node, probe, build):
    lut, dup, oob = dense_build_lut(build, node.right_keys, DOMAIN)
    assert int(dup) == 0 and int(oob) == 0
    return dense_join_with_lut(probe, build, lut, node.left_keys,
                               node.right_keys, node.kind)


def _chunk_join(node, probe, build, laps=1):
    """`_chunk_lut_join` under a traced statement's `join` span:
    (output, the executor, what the span says)."""
    ex = Executor(Catalog())
    ex.enter_chunk_mode()
    with tracing.use(tracing.Tracer()):
        ex._operator_spans = True
        ex.operator_span("join")
        try:
            for _ in range(laps):
                out = ex._chunk_lut_join(node, probe, build, DOMAIN)
            said = dict(ex._open_operators[-1][1].attributes)
        finally:
            ex._close_operators(0)
            ex._operator_spans = False
    return out, ex, said


PAYLOADS = {
    # an int32 date-like column with NULLs and a constant: q3's build
    "int32": lambda rng, nb: (
        [rng.integers(8035, 9200, nb).astype(np.int32),
         np.zeros(nb, np.int32)],
        [rng.random(nb) > .2, None]),
    # a negative least value, an int64 column wide enough for two planes
    "int64": lambda rng, nb: (
        [rng.integers(-700, 300, nb).astype(np.int64),
         rng.integers(-(1 << 33), 1 << 33, nb).astype(np.int64)],
        [None, rng.random(nb) > .3]),
    # the key alone: a presence bit, an int8 word
    "key-only": lambda rng, nb: ([], []),
}


@pytest.mark.parametrize("kind", ["inner", "left"])
@pytest.mark.parametrize("payload", sorted(PAYLOADS))
def test_packed_probe_matches_the_row_id_form(kind, payload):
    rng = np.random.default_rng(45)
    cols, valids = PAYLOADS[payload](rng, 900)
    build, probe, node = _build(rng, cols, valids), _probe(rng), _node(kind)
    out, ex, said = _chunk_join(node, probe, build, laps=3)
    assert said == {"strategy": "dense-lut-packed", "lutForm": "packed",
                    "wordBits": {"int32": 32, "int64": 64,
                                 "key-only": 8}[payload]}
    assert ex.strategy_decisions["JoinNode"] == "dense-lut-packed"
    assert (ex.stats.chunk_lut_joins, ex.stats.packed_lut_joins) == (3, 3)
    want = _reference(node, probe, build)
    assert [str(c.data.dtype) for c in out.columns] == \
        [str(c.data.dtype) for c in want.columns]
    assert out.capacity == want.capacity == probe.capacity
    got_rows, want_rows = _all_slots(out), _all_slots(want)
    assert got_rows == want_rows
    live = [r for r in got_rows if r is not None]
    matched = [r for r in live if r[2] is not None]
    # the fixture reaches every case it names
    assert matched and (kind == "inner" or len(matched) < len(live))
    if cols:
        assert any(None in r[3:] for r in matched)      # a NULL payload


REFUSALS = {
    "float": lambda rng, nb: [rng.random(nb), np.arange(nb, dtype=np.int32)],
    "columns": lambda rng, nb: [np.arange(nb, dtype=np.int32)] * 5,
    # two columns of 35 bits each: 70 bits of payload
    "bits": lambda rng, nb: [
        rng.integers(0, 1 << 34, nb).astype(np.int64) + i for i in (0, 1)],
}


@pytest.mark.parametrize("kind", ["inner", "left"])
@pytest.mark.parametrize("reason", sorted(REFUSALS))
def test_a_payload_that_does_not_fit_runs_the_row_id_form(kind, reason):
    rng = np.random.default_rng(7)
    build, probe = _build(rng, REFUSALS[reason](rng, 900)), _probe(rng)
    node = _node(kind)
    out, ex, said = _chunk_join(node, probe, build, laps=2)
    assert said == {"strategy": "dense-lut", "lutForm": "rows",
                    "wordBits": 32, "packRefused": reason}
    assert (ex.stats.chunk_lut_joins, ex.stats.packed_lut_joins) == (2, 0)
    want = _reference(node, probe, build)
    # bit for bit: the same program over the same LUT
    for got_col, want_col in zip(out.columns, want.columns):
        assert np.array_equal(np.asarray(got_col.data),
                              np.asarray(want_col.data))
        assert np.array_equal(np.asarray(got_col.valid),
                              np.asarray(want_col.valid))
    assert np.array_equal(np.asarray(out.live), np.asarray(want.live))


def test_a_two_column_key_is_refused_before_any_fetch():
    rng = np.random.default_rng(8)
    build = _build(rng, [np.arange(900, dtype=np.int32)])
    assert pack_refusal(build, (0, 1)) == "key"


@pytest.mark.parametrize("fault", ["duplicate", "out-of-domain"])
def test_a_build_no_lut_vouches_for_leaves_the_lut_path_as_before(fault):
    """A build with a key twice, or one outside the plan's domain: the
    join goes to the caller's general paths (None), as it did, and its
    span says the packed form's validation refused."""
    rng = np.random.default_rng(9)
    keys = rng.permutation(DOMAIN)[:900].astype(np.int64)
    keys[17] = keys[400] if fault == "duplicate" else DOMAIN + 3
    build = _build(rng, [np.arange(900, dtype=np.int32)], keys=keys)
    out, ex, said = _chunk_join(_node("inner"), _probe(rng), build, laps=2)
    assert out is None and said == {"packRefused": "validation"}
    assert (ex.stats.chunk_lut_joins, ex.stats.packed_lut_joins) == (0, 0)
    assert ex.stats.join_domain_fallbacks == (fault == "out-of-domain")


def test_ranges_of_one_width_class_share_one_program():
    """The word's statics are the schema's (columns, width classes,
    offsets); what follows the data (each column's least value) is an
    operand: another statement's dates run the program there is."""
    def calls(site):
        return sum(e["compiles"] for e in RECORDER.snapshot()
                   if e["site"] == site), \
            sum(e["hits"] for e in RECORDER.snapshot()
                if e["site"] == site)

    rng = np.random.default_rng(10)
    # capacities no other test of the process joins at
    probe = _probe(rng, n=2000, capacity=3072)
    node = _node("inner")
    before = {s: calls(s) for s in ("join.dense_join_packed",
                                    "join.dense_build_packed_lut")}
    for lo, span in ((8035, 1100), (-40, 3000)):     # 11 and 12 bits
        dates = rng.integers(lo, lo + span, 700).astype(np.int32)
        build = _build(rng, [dates, np.zeros(700, np.int32)], nb=700)
        out, ex, said = _chunk_join(node, probe, build)
        assert said["lutForm"] == "packed"
        assert _all_slots(out) == _all_slots(_reference(node, probe, build))
    for site, (compiles, hits) in before.items():
        now = calls(site)
        assert now[0] - compiles == 1, site
        assert now[1] - hits == 1, site
