"""Unit tests for the columnar Batch/Column data model (Trino Page/Block
analog; reference tests: core/trino-spi/src/test/.../TestPage.java)."""

import jax
import numpy as np
import pytest

from trino_tpu.batch import (Batch, Field, Schema, batch_from_numpy,
                             batch_to_numpy, decode_column, pad_capacity)
from trino_tpu.types import BIGINT, VARCHAR, decimal


def test_pad_capacity_buckets():
    assert pad_capacity(1) == 1024
    assert pad_capacity(1024) == 1024
    assert pad_capacity(1025) == 2048


def test_roundtrip_with_padding():
    a = np.arange(10, dtype=np.int64)
    b = np.array([1.5, 2.5] * 5, dtype=np.float32)
    batch = batch_from_numpy([a, b])
    assert batch.capacity == 1024
    assert int(batch.live.sum()) == 10
    arrays, valids = batch_to_numpy(batch)
    np.testing.assert_array_equal(arrays[0], a)
    np.testing.assert_allclose(arrays[1], b)
    assert valids[0].all()


def test_null_mask_roundtrip():
    a = np.arange(4, dtype=np.int64)
    valid = np.array([True, False, True, False])
    batch = batch_from_numpy([a], valids=[valid])
    arrays, valids = batch_to_numpy(batch)
    np.testing.assert_array_equal(valids[0], valid)


def test_schema_lookup_and_decode():
    schema = Schema.of(
        Field("k", BIGINT),
        Field("s", VARCHAR, dictionary=("apple", "banana")),
        Field("d", decimal(12, 2)),
    )
    assert schema.index_of("s") == 1
    vals = decode_column(schema.field("s"),
                         np.array([1, 0]), np.array([True, True]))
    assert vals == ["banana", "apple"]
    from decimal import Decimal
    dec = decode_column(schema.field("d"),
                        np.array([12345, -50]), np.array([True, False]))
    assert dec == [Decimal("123.45"), None]
    # exactness beyond 2^53 (float would corrupt the low digits)
    big = decode_column(schema.field("d"),
                        np.array([9007199254740995]), np.array([True]))
    assert big == [Decimal("90071992547409.95")]


@pytest.mark.parametrize("masks", ["none", "some", "no-valids"])
@pytest.mark.parametrize("n", [0, 1, 700, 1024])
@pytest.mark.parametrize("columns", [0, 3])
def test_a_put_is_one_transfer_and_sends_no_mask_it_need_not(
        monkeypatch, columns, n, masks):
    """`n` 0, 1, under and at the capacity; no column at all; a null
    mask on some columns, on none (`valids` of Nones), no `valids`."""
    cap = 1024
    rng = np.random.default_rng(n + columns)
    arrays = [rng.integers(-9, 9, n).astype(dt)
              for dt in (np.int64, np.int32, np.float64)[:columns]]
    valids = None if masks == "no-valids" else [
        rng.integers(0, 2, n).astype(bool)
        if masks == "some" and i != 1 else None for i in range(columns)]
    puts = []
    put = jax.device_put
    monkeypatch.setattr(jax, "device_put",
                        lambda x, *a, **k: puts.append(x) or put(x, *a, **k))
    batch = batch_from_numpy(arrays, valids=valids, capacity=cap)
    assert len(puts) == 1
    masked = [i for i in range(columns)
              if valids is not None and valids[i] is not None]
    # the data columns, the masks that exist, and `live`
    assert len(puts[0]) == columns + len(masked) + 1
    rows = n if columns else 0
    assert batch.capacity == cap and len(batch.columns) == columns
    np.testing.assert_array_equal(np.asarray(batch.live),
                                  np.arange(cap) < rows)
    for i, col in enumerate(batch.columns):
        assert col.data.dtype == arrays[i].dtype
        np.testing.assert_array_equal(np.asarray(col.data)[:n], arrays[i])
        assert not np.asarray(col.data)[n:].any()
        if i in masked:
            assert col.valid is not batch.live
            np.testing.assert_array_equal(np.asarray(col.valid)[:n],
                                          valids[i])
            assert not np.asarray(col.valid)[n:].any()
        else:
            assert col.valid is batch.live
    got, got_valids = batch_to_numpy(batch)
    for i in range(columns):
        np.testing.assert_array_equal(got[i], arrays[i])
        np.testing.assert_array_equal(
            got_valids[i], valids[i] if i in masked else np.ones(n, bool))
    # the mask of a batch of the same capacity and rows, handed back:
    # the same batch, and nothing but the data crosses
    del puts[:]
    again = batch_from_numpy(arrays, valids=valids, capacity=cap,
                             live=batch.live)
    assert len(puts) == 1 and len(puts[0]) == columns + len(masked)
    assert again.live is batch.live
    assert jax.tree_util.tree_structure(again) == \
        jax.tree_util.tree_structure(batch)
    for a, b in zip(jax.tree_util.tree_leaves(again),
                    jax.tree_util.tree_leaves(batch)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for i, col in enumerate(again.columns):
        assert (col.valid is again.live) == (i not in masked)
