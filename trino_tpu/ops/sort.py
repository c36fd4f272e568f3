"""Sort / TopN / Limit kernels.

Reference: OrderByOperator over a PagesIndex with compiled comparators
(operator/OrderByOperator.java, sql/gen/OrderingCompiler.java:71) and
TopNOperator (operator/topn/). Here: one multi-operand `lax.sort` whose key
encoding bakes in direction and null placement, then a full-batch gather —
XLA's sort is a parallel bitonic-style network that suits the TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..exec.profiler import recorded_jit

from ..batch import Batch, Column


def _sort_key_encoding(col: Column, ascending: bool, nulls_first: bool):
    """Encode (valid, data) into operands whose ascending lexicographic
    order realizes the requested direction + null placement."""
    if nulls_first:
        null_rank = jnp.where(col.valid, 1, 0)
    else:
        null_rank = jnp.where(col.valid, 0, 1)
    # normalize NULL slots: garbage data must not order NULL rows among
    # themselves (window peer groups require NULLs to compare equal)
    data = jnp.where(col.valid, col.data, jnp.zeros((), col.data.dtype))
    if not ascending:
        if jnp.issubdtype(data.dtype, jnp.bool_):
            data = ~data
        elif jnp.issubdtype(data.dtype, jnp.floating):
            data = -data
        else:
            data = jnp.invert(data)   # order-reversing, overflow-safe
    return null_rank.astype(jnp.int8), data


@recorded_jit(static_argnums=(1, 2))
def sort_batch(batch: Batch, keys: tuple, limit) -> Batch:
    """keys: tuple of (col_index, ascending, nulls_first). Dead rows sort
    last; an optional limit marks only the first `limit` rows live (TopN)."""
    n = batch.capacity
    operands = [(~batch.live).astype(jnp.int8)]
    for (idx, asc, nf) in keys:
        nr, data = _sort_key_encoding(batch.columns[idx], asc, nf)
        operands.append(nr)
        operands.append(data)
    num_keys = len(operands)
    operands.append(jnp.arange(n, dtype=jnp.int32))
    sorted_ops = jax.lax.sort(tuple(operands), num_keys=num_keys)
    perm = sorted_ops[-1]

    cols = tuple(Column(data=c.data[perm], valid=c.valid[perm])
                 for c in batch.columns)
    live = batch.live[perm]
    if limit is not None:
        live = live & (jnp.arange(n) < limit)
    return Batch(columns=cols, live=live)


def sort_pack_plan(batch: Batch, keys: tuple, fetch=None):
    """Range-compress integer ORDER BY keys into int64 words (direction
    and null placement baked into the rank encoding) so the big sort is
    always (word, index) — measurement and bit layout shared with the
    aggregation kernels (ops.aggregate.key_pack_plan_words; the +3 slack
    there keeps the DESC rank range clear of the nulls-first slot 0 and
    the ASC range clear of the nulls-last slot 2^b - 1). Returns (kmins,
    bits, word_splits), or None when a key is not integer-typed or the
    keys need more than three words.

    The bits are statics of sort_batch_packed, and an ORDER BY's
    leading key is as a rule a computed measure whose span moves with
    every literal (q3's revenue at SF10: 33 bits for 152 of its 155
    parameter sets, 32 for three, and each layout a 15 s compile inside
    somebody's statement). So in every word that takes lsd_word_sort's
    one-operand form, the word's first key gets all the room the others
    leave: the layout then follows the capacity, the split into words
    and the other keys' rounded bits, not the measure."""
    from .aggregate import key_pack_plan_words
    plan = key_pack_plan_words(batch, tuple(idx for idx, _, _ in keys),
                               fetch=fetch)
    if plan is None:
        return None
    kmins, bits, splits = plan
    idx_bits = max(1, (batch.capacity - 1).bit_length())
    widened = []
    for s, e in splits:
        others = tuple(-(-b // 4) * 4 for b in bits[s + 1:e])
        room = 62 - idx_bits - sum(others)
        widened += (room,) + others if room >= bits[s] else bits[s:e]
    return kmins, tuple(widened), splits


@recorded_jit(static_argnums=(2, 3, 4, 5))
def sort_batch_packed(batch: Batch, kmins, keys: tuple, key_bits: tuple,
                      limit, word_splits: tuple = None) -> Batch:
    """sort_batch via packed int64 key words (see sort_pack_plan): rank
    within each key's field realizes ASC/DESC + null placement; dead
    rows pack to int64.max in every word. One word sorts directly;
    several run an LSD radix of stable sorts from the least-significant
    word up (as packed_sort_group_aggregate does), so every sort is 2
    operands at any key count and width."""
    n = batch.capacity
    if word_splits is None:
        word_splits = ((0, len(keys)),)
    words = []
    for (s, e) in word_splits:
        packed = jnp.zeros(n, dtype=jnp.int64)
        for j in range(s, e):
            (idx, asc, nf), b = keys[j], key_bits[j]
            col = batch.columns[idx]
            span_max = (1 << b) - 1
            norm = col.data.astype(jnp.int64) - kmins[j] + 1
            rank = norm if asc else (span_max - 1) - norm
            null_slot = 0 if nf else span_max
            rank = jnp.where(col.valid, rank, null_slot)
            packed = (packed << b) | rank
        words.append(jnp.where(batch.live, packed,
                               jnp.iinfo(jnp.int64).max))
    from .aggregate import lsd_word_sort
    perm = lsd_word_sort(words, [sum(key_bits[s:e])
                                 for (s, e) in word_splits])
    out_n = n
    if limit is not None and int(limit) < n:
        # TopN: dead rows sort last, so the winners live in the prefix —
        # slice the permutation BEFORE the payload gathers. The gathers
        # are the kernel's whole cost at scale (the sort itself is 2
        # operands); a LIMIT 10 over millions must not gather millions.
        from ..batch import bucket_capacity
        out_n = min(n, max(1024, bucket_capacity(int(limit))))
        perm = perm[:out_n]
    cols = tuple(Column(data=c.data[perm], valid=c.valid[perm])
                 for c in batch.columns)
    live = batch.live[perm]
    if limit is not None:
        live = live & (jnp.arange(out_n) < limit)
    return Batch(columns=cols, live=live)


@recorded_jit()
def limit_batch(batch: Batch, count: jax.Array) -> Batch:
    """Keep the first `count` live rows (in current order)."""
    rank = jnp.cumsum(batch.live.astype(jnp.int64)) - 1
    return batch.with_live(batch.live & (rank < count))
