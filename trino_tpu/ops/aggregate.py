"""Group-by aggregation kernels — scatter-free, TPU-first.

Reference: Trino's HashAggregationOperator (operator/HashAggregationOperator.java:45)
with GroupByHash picking a strategy by key shape (GroupByHash.java:82-93 —
BigintGroupByHash vs FlatGroupByHash SWAR table), and compiled accumulators
(operator/aggregation/AccumulatorCompiler.java:88).

TPU constraints drive the redesign (measured on v5e: a 6-slot scatter-add
over 6M rows costs ~500ms because XLA TPU serializes scatters, while a full
masked reduction over the same rows costs ~0.1ms):

- **direct** (small dense domains — dictionary/boolean keys): group id is a
  mixed-radix code; each (group, aggregate) cell is a *masked full
  reduction*. XLA fuses the G x A reductions over one data pass; no scatter,
  no hash table. (The analog of BigintGroupByHash's dense mode.)
- **sort** (general keys): sort (dead rows last), segment boundaries by
  adjacent-difference, then per-aggregate: sums/counts via `cumsum` +
  boundary differencing, min/max via a segmented scan. Exact (sorts real
  key values, no hash collisions), static shapes throughout. Three
  kernels by what is sorted. `sort_group_aggregate` (small batches):
  one multi-operand lexicographic `lax.sort` of the key columns and the
  row index. `packed_sort_group_aggregate`, permutation form: the keys
  range-compressed into int64 words, each sorted with the row position
  in its low bits; arguments and group results are *gathered* through
  the permutation and the segment extents come from one scatter: on the
  chip a gather costs 22 ns an index and 32-bit plane whatever it
  fetches, 16.4 of the 17.45 s of TPC-H Q18's aggregate at SF10
  (PERF.md section 5). Value-carrying form
  (where keys and arguments fit one 63-bit word): the word carries the
  arguments where it carried the position, every sorted column is a
  shift and a mask of the sorted word, and a group's totals are read at
  its segment's last row: no gather, no scatter.

Both paths produce *partial aggregate states* (sum/count/min/max); AVG is
decomposed by the planner into (sum, count) and finalized in the
post-projection, like Trino's PARTIAL -> FINAL split. States merge across
shards with psum/all_gather collectives (parallel/exchange.py).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..exec.profiler import recorded_jit
from jax import lax

from ..batch import Batch, Column, live_first_order

AGG_FUNCS = ("sum", "count", "count_star", "min", "max")

# direct strategy is a G x A unrolled reduction graph; keep G bounded so
# compile time and graph size stay sane (planner enforces the same bound)
MAX_DIRECT_GROUPS = 64


@dataclass(frozen=True)
class AggSpec:
    func: str                 # one of AGG_FUNCS
    arg_index: Optional[int]  # column in the input batch (None for count_star)
    distinct: bool = False    # sum/count DISTINCT (sort strategy only)

    def __post_init__(self):
        assert self.func in AGG_FUNCS, self.func
        assert (self.arg_index is None) == (self.func == "count_star")
        assert not (self.distinct and self.func not in ("sum", "count"))


def _identity(func: str, dtype) -> object:
    if func == "sum" or func.startswith("count"):
        return 0
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.inf if func == "min" else -jnp.inf
    info = jnp.iinfo(dtype)
    return info.max if func == "min" else info.min


# --------------------------------------------------------------------------
# direct (dense small-domain) strategy — masked reductions
# --------------------------------------------------------------------------

@recorded_jit(static_argnums=(1, 2, 3))
def direct_group_aggregate(batch: Batch, key_indices: tuple,
                           domains: tuple, aggs: tuple) -> Batch:
    """Group by small-domain integer/dictionary keys.

    domains[i] = exclusive upper bound of key column i's values (dictionary
    size). Output has exactly prod(domains) rows; group g's keys decode as
    mixed-radix digits of g. Groups with no rows are not live.
    """
    out_capacity = 1
    for d in domains:
        out_capacity *= d
    assert out_capacity <= MAX_DIRECT_GROUPS, \
        "direct strategy domain too large; planner should pick sort"

    gid = jnp.zeros(batch.capacity, dtype=jnp.int32)
    key_valid = jnp.ones(batch.capacity, dtype=jnp.bool_)
    for ki, d in zip(key_indices, domains):
        col = batch.columns[ki]
        gid = gid * d + jnp.clip(col.data.astype(jnp.int32), 0, d - 1)
        key_valid = key_valid & col.valid
    contributes = batch.live & key_valid

    # per-group boolean masks, reused across aggregates (XLA keeps these
    # fused into the reduction pass; nothing is materialized at [n, G])
    group_masks = [contributes & (gid == g) for g in range(out_capacity)]
    group_count = jnp.stack([m.sum(dtype=jnp.int64) for m in group_masks])
    group_live = group_count > 0

    out_cols = []
    g_idx = jnp.arange(out_capacity, dtype=jnp.int32)
    radix = out_capacity
    for ki, d in zip(key_indices, domains):
        radix //= d
        digit = (g_idx // radix) % d
        out_cols.append(Column(
            data=digit.astype(batch.columns[ki].data.dtype),
            valid=group_live))

    for spec in aggs:
        if spec.func == "count_star":
            out_cols.append(Column(data=group_count, valid=group_live))
            continue
        col = batch.columns[spec.arg_index]
        data = col.data
        if spec.func == "count":
            cnt = jnp.stack([(m & col.valid).sum(dtype=jnp.int64)
                             for m in group_masks])
            out_cols.append(Column(data=cnt, valid=group_live))
            continue
        cnt = jnp.stack([(m & col.valid).sum(dtype=jnp.int64)
                         for m in group_masks])
        if spec.func == "sum":
            acc_dtype = jnp.int64 if jnp.issubdtype(data.dtype, jnp.integer) \
                else data.dtype
            vals = data.astype(acc_dtype)
            state = jnp.stack([
                jnp.where(m & col.valid, vals, 0).sum() for m in group_masks])
        else:
            ident = _identity(spec.func, data.dtype)
            red = jnp.min if spec.func == "min" else jnp.max
            state = jnp.stack([
                red(jnp.where(m & col.valid, data, ident))
                for m in group_masks])
        out_cols.append(Column(data=state, valid=group_live & (cnt > 0)))
    return Batch(columns=tuple(out_cols), live=group_live)


# --------------------------------------------------------------------------
# sort-based general strategy — cumsum / segmented scan
# --------------------------------------------------------------------------

def _segmented_scan(vals: jax.Array, boundary: jax.Array, op):
    """Inclusive segmented scan: position i holds op-reduction of its
    segment's values up to i. boundary[i]=True starts a new segment."""
    def combine(a, b):
        fa, va = a
        fb, vb = b
        return fa | fb, jnp.where(fb, vb, op(va, vb))
    _, out = lax.associative_scan(combine, (boundary, vals))
    return out


@recorded_jit(static_argnums=(1, 2, 3))
def sort_group_aggregate(batch: Batch, key_indices: tuple, aggs: tuple,
                         out_capacity: int) -> Batch:
    """Group by arbitrary key columns via lexicographic sort.

    Exact (sorts real key values, not hashes). Output capacity is a static
    bound; if the true group count exceeds it, excess groups are dropped —
    callers size it from stats and the executor grows + retries on
    overflow (SURVEY.md §7 hard part 1). NULL keys group together (SQL
    GROUP BY treats NULLs as equal).

    Scatter-free: group states are read out of running scans at segment-end
    positions located with searchsorted.
    """
    n = batch.capacity
    operands = [(~batch.live).astype(jnp.int8)]
    for ki in key_indices:
        col = batch.columns[ki]
        operands.append((~col.valid).astype(jnp.int8))
        # NULL keys must form ONE group: normalize masked-out data so the
        # boundary detector can't split NULL rows on garbage values
        operands.append(jnp.where(col.valid, col.data,
                                  jnp.zeros((), col.data.dtype)))
    n_group_ops = len(operands)
    # DISTINCT aggregate columns join the sort key (after the group keys) so
    # duplicates within a group are adjacent; they do NOT define segment
    # boundaries. At most one distinct column (planner enforces).
    distinct_cols = sorted({s.arg_index for s in aggs if s.distinct})
    distinct_pos = {}
    for di in distinct_cols:
        col = batch.columns[di]
        distinct_pos[di] = len(operands)
        operands.append((~col.valid).astype(jnp.int8))
        operands.append(col.data)
    num_keys = len(operands)
    operands.append(jnp.arange(n, dtype=jnp.int32))   # payload: row index
    sorted_ops = jax.lax.sort(tuple(operands), num_keys=num_keys)
    perm = sorted_ops[-1]
    live_s = batch.live[perm]

    diff = jnp.zeros(n, dtype=jnp.bool_)
    for op in sorted_ops[1:n_group_ops]:  # key operands only (skip dead flag)
        diff = diff | (op != jnp.roll(op, 1))
    first = jnp.arange(n) == 0
    boundary = live_s & (first | diff)

    # distinct markers: first occurrence of each distinct valid value
    # within a group (the distinct column participates in the sort, so
    # duplicates are adjacent — Trino: MarkDistinct + filtered accumulator)
    distinct_fresh = {}
    for di in distinct_cols:
        p = distinct_pos[di]
        dvinv_s, ddata_s = sorted_ops[p], sorted_ops[p + 1]
        distinct_fresh[di] = boundary | \
            (ddata_s != jnp.roll(ddata_s, 1)) | \
            (dvinv_s != jnp.roll(dvinv_s, 1))
    return _grouped_reduce(batch, key_indices, aggs, out_capacity, perm,
                           live_s, boundary, distinct_fresh)


def _grouped_reduce(batch: Batch, key_indices: tuple, aggs: tuple,
                    out_capacity: int, perm, live_s, boundary,
                    distinct_fresh) -> Batch:
    """Shared segment machinery for the sorted aggregation kernels: given
    the sort permutation and group boundaries, locate segment extents and
    reduce every aggregate — used by both the general multi-operand kernel
    and the packed 2-operand kernel (traced inside their jits)."""
    n = batch.capacity
    seg = jnp.cumsum(boundary.astype(jnp.int32)) - 1      # 0-based group id
    num_groups = boundary.sum()

    g = jnp.arange(out_capacity)
    group_live = g < num_groups
    # segment extents per output group: scatter each boundary position at
    # its group id (unique indices), then end[g] = start[g+1] - 1 — one
    # scatter + one gather instead of two searchsorteds (searchsorted
    # lowers to ~24 serial gather rounds; pathological at 10M+ rows)
    pos = jnp.arange(n, dtype=jnp.int32)
    sidx = jnp.where(boundary & (seg < out_capacity), seg, out_capacity)
    start_lut = jnp.zeros(out_capacity + 1, dtype=jnp.int32)
    start_lut = start_lut.at[sidx].max(pos, mode="drop")
    start_c = jnp.clip(start_lut[:out_capacity], 0, n - 1)
    next_start = start_lut[jnp.clip(g + 1, 0, out_capacity)]
    end_pos = jnp.where(g + 1 < num_groups,
                        jnp.clip(next_start - 1, 0, n - 1), n - 1)

    out_cols = []
    rep = perm[start_c]                   # representative row per group
    for ki in key_indices:
        col = batch.columns[ki]
        out_cols.append(Column(data=col.data[rep],
                               valid=col.valid[rep] & group_live))

    def seg_total(values_sorted):
        """Per-group totals of a sorted value array via cumsum diff."""
        cs = jnp.cumsum(values_sorted)
        upto_end = cs[end_pos]
        before_start = jnp.where(start_c > 0, cs[jnp.clip(start_c - 1,
                                                          0, n - 1)], 0)
        return jnp.where(group_live, upto_end - before_start, 0)

    for spec in aggs:
        if spec.func == "count_star":
            cnt = seg_total(live_s.astype(jnp.int64))
            out_cols.append(Column(data=cnt, valid=group_live))
            continue
        col = batch.columns[spec.arg_index]
        data_s = col.data[perm]
        valid_s = col.valid[perm] & live_s
        if spec.distinct:
            marker = valid_s & distinct_fresh[spec.arg_index]
            if spec.func == "count":
                out_cols.append(Column(data=seg_total(
                    marker.astype(jnp.int64)), valid=group_live))
            else:  # sum distinct
                acc_dtype = jnp.int64 if jnp.issubdtype(
                    col.data.dtype, jnp.integer) else col.data.dtype
                vals = jnp.where(marker, data_s.astype(acc_dtype), 0)
                cnt = seg_total(marker.astype(jnp.int64))
                out_cols.append(Column(data=seg_total(vals),
                                       valid=group_live & (cnt > 0)))
            continue
        cnt = seg_total(valid_s.astype(jnp.int64))
        if spec.func == "count":
            out_cols.append(Column(data=cnt, valid=group_live))
            continue
        if spec.func == "sum":
            acc_dtype = jnp.int64 if jnp.issubdtype(col.data.dtype,
                                                    jnp.integer) \
                else col.data.dtype
            vals = jnp.where(valid_s, data_s.astype(acc_dtype), 0)
            state = seg_total(vals)
        else:
            ident = _identity(spec.func, col.data.dtype)
            vals = jnp.where(valid_s, data_s, ident)
            op = jnp.minimum if spec.func == "min" else jnp.maximum
            scanned = _segmented_scan(vals, boundary, op)
            state = jnp.where(group_live, scanned[end_pos], ident)
        out_cols.append(Column(data=state, valid=group_live & (cnt > 0)))
    return Batch(columns=tuple(out_cols), live=group_live)


# --------------------------------------------------------------------------
# packed sort strategy — range-compressed keys, 2-operand sort
# --------------------------------------------------------------------------

def key_pack_plan(batch: Batch, key_indices: tuple, fetch=None):
    """Measure per-key [min, max] on device (ONE fused fetch) and derive a
    static packing layout: key i occupies ceil(log2(span+3)) bits; slot 0
    and the top slot stay free for NULL placement and direction
    reversal. Returns (kmins host array, bits tuple) or None if the
    combined width exceeds 62 bits or a key isn't integer-typed.

    Why: XLA TPU compile cost for lax.sort is dominated by OPERAND COUNT
    (measured v5e: 2 operands ~40s, 4 ~170s, 6 ~460s, nearly flat in
    rows). Collapsing any number of integer keys into ONE int64 keeps
    every big sort at (packed, index) — the same range-compression idea
    as BigintGroupByHash's dense path, applied to the sort domain."""
    # `fetch` (the executor's cross-run decision cache) turns the
    # min/max measurement into a zero-round-trip host decision on
    # re-execution
    plan = _measure_key_bits(batch, key_indices, fetch)
    if plan is None:
        return None
    kmins, bits = plan
    if sum(bits) > 62:
        return None
    return kmins, bits


def key_pack_plan_words(batch: Batch, key_indices: tuple, fetch=None,
                        max_words: int = 3, aggs=None):
    """key_pack_plan generalized to MULTIPLE packed words: keys are
    assigned IN ORDER to words of <=62 bits each, and the sort becomes
    an LSD-radix sequence of stable 2-operand sorts (least-significant
    word first) — wide GROUP BYs (TPC-H q10's 7 keys ~ 111 bits) stay
    at compile-cheap operand counts instead of exploding into the
    general kernel's 2-per-key sort. Returns (kmins, bits, word_splits)
    where word_splits are (start, end) key ranges per word; None when
    any single key exceeds 62 bits, a key isn't integer-typed, or more
    than max_words words would be needed.

    The bits are static arguments of the packed kernels and come from
    the data, so they are rounded up to multiples of 4 (capped at 62):
    batches whose key spans differ a little — the splits of one scan,
    the partitions of one spill — share one compiled program instead of
    compiling one each. Where the rounding would cost a sort (one more
    word, or a word that no longer fits lsd_word_sort's one-operand
    form at this capacity), the measured bits stay.

    With `aggs` the result has a fourth element, the layout of the
    aggregates' arguments in the sort word, `(vmins, value_bits)` over
    `value_columns(aggs)`, or None. Their [min, max] ride the keys' fetch
    and their bits are rounded as the keys' are, slot 0 for NULL. They
    are given where the sort word has room for them below the keys
    (one word, key bits + value bits <= 63), every argument is an
    integer or a boolean, and a 64-bit running sum of each field over
    the batch cannot wrap (bits + log2 capacity <= 62): then
    packed_sort_group_aggregate carries the values through its sort and
    gathers nothing through a permutation."""
    values = value_columns(aggs or ())
    plan = _measure_key_bits(batch, key_indices + values, fetch)
    if plan is None and values:
        # an argument that is not an integer (nothing was fetched yet):
        # the keys alone
        values = None
        plan = _measure_key_bits(batch, key_indices, fetch)
    if plan is None:
        return None
    n_keys = len(key_indices)
    kmins, bits = plan[0][:n_keys], plan[1][:n_keys]
    vmins, value_bits = plan[0][n_keys:], plan[1][n_keys:]
    if max(bits) > 62:
        return None
    idx_bits = max(1, (batch.capacity - 1).bit_length())

    def words(bits):
        """Greedy in-order assignment -> (word splits, sort cost as
        (number of words, number of two-operand passes))."""
        splits, start, cur = [], 0, 0
        for i, b in enumerate(bits):
            if cur + b > 62:
                splits.append((start, i))
                start, cur = i, 0
            cur += b
        splits.append((start, len(bits)))
        wide = sum(sum(bits[s:e]) + 1 + idx_bits > 63 for s, e in splits)
        return tuple(splits), (len(splits), wide)
    rounded = tuple(min(62, -(-b // 4) * 4) for b in bits)
    if words(rounded)[1] <= words(bits)[1]:
        bits = rounded
    splits = words(bits)[0]
    if len(splits) > max_words:
        return None
    if aggs is None:
        return kmins, bits, splits
    carried = None
    if values is not None and len(splits) == 1:
        # two bits say "one value" to the kernel and stay two
        rounded = tuple(b if b == 2 else -(-b // 4) * 4
                        for b in value_bits)
        for vb in (rounded, value_bits):
            if sum(bits) + sum(vb) <= 63 and \
                    max(vb, default=0) + idx_bits <= 62:
                carried = (vmins, vb)
                break
    return kmins, bits, splits, carried


@recorded_jit(static_argnums=(1,))
def _column_ranges(batch: Batch, indices: tuple) -> jax.Array:
    """int64 [live rows, min 0, max 0, min 1, max 1, ...] of columns
    `indices` over their live valid rows: ONE program and one vector to
    fetch, where a dozen eager reductions a column cost a worker's
    250,000-row split a millisecond and a half of dispatch."""
    big = jnp.iinfo(jnp.int64)
    stats = [jnp.sum(batch.live, dtype=jnp.int64)]
    for i in indices:
        col = batch.columns[i]
        m = batch.live & col.valid
        data = col.data.astype(jnp.int64)
        stats.append(jnp.min(jnp.where(m, data, big.max)))
        stats.append(jnp.max(jnp.where(m, data, big.min)))
    return jnp.stack(stats)


def _measure_key_bits(batch: Batch, key_indices: tuple, fetch=None):
    """Shared measurement: per-key [min, max] -> (kmins, bits) with no
    total-width cap (key_pack_plan applies the single-word cap).
    `fetch` takes the device vector of _column_ranges (the batch's live
    count leads it) and gives it back on the host."""
    for ki in key_indices:
        dtype = batch.columns[ki].data.dtype
        if not jnp.issubdtype(dtype, jnp.integer) and dtype != jnp.bool_:
            return None
    stats = _column_ranges(batch, tuple(key_indices))
    vals = (fetch(stats) if fetch is not None else np.asarray(stats))[1:]
    kmins, bits = [], []
    for i in range(len(key_indices)):
        lo, hi = int(vals[2 * i]), int(vals[2 * i + 1])
        if hi < lo:
            lo, hi = 0, 0
        kmins.append(lo)
        bits.append(max(2, int(hi - lo + 3).bit_length()))
    return np.asarray(kmins, dtype=np.int64), tuple(bits)


def lsd_word_sort(words, word_bits) -> jax.Array:
    """Stable LSD radix over packed int64 key words (most significant
    first; live words < 2^bits, dead rows hold int64.max) -> the int32
    row permutation. Where a word and the row position fit one int64
    (bits + 1 + log2 n <= 63) the pass is a ONE-operand unstable sort of
    (word << idx_bits | position): every key differs, so the order is
    the stable order, at about a quarter of the (word, index) stable
    sort's TPU compile time (12 s against 47 s at 262,144 rows) and one
    operand less to move. Wider words keep the two-operand stable
    sort."""
    n = words[0].shape[0]
    idx_bits = max(1, (n - 1).bit_length())
    perm = None
    for w, b in zip(reversed(words), reversed(word_bits)):
        wp = w if perm is None else w[perm]
        if b + 1 + idx_bits <= 63:
            key = (jnp.minimum(wp, jnp.int64(1) << b) << idx_bits) | \
                jnp.arange(n, dtype=jnp.int64)
            (ordered,) = jax.lax.sort((key,), num_keys=1, is_stable=False)
            pos = (ordered & ((1 << idx_bits) - 1)).astype(jnp.int32)
            perm = pos if perm is None else perm[pos]
        else:
            cur = jnp.arange(n, dtype=jnp.int32) if perm is None else perm
            _, perm = jax.lax.sort((wp, cur), num_keys=1, is_stable=True)
    return perm


def value_columns(aggs) -> tuple:
    """The columns `aggs` read, each once, in the order their fields
    take in a value-carrying sort word (two aggregates over one column
    share its bits; count(*) needs none)."""
    return tuple(sorted({s.arg_index for s in aggs
                         if s.arg_index is not None}))


# The value-carrying form leaves its groups where their segments end, in
# a batch of the input's capacity, unless the plan's group capacity is
# more than this factor below it: then they are read back to the group
# capacity (dense). From two timings at 60,011,520 rows (my chip runs,
# PR 33; the kernel itself 0.445 s): the read-back is 0.074 s for the
# mask's sort and 0.225 us a group slot (ten 32-bit planes at 22 ns an
# index): 3.77 s at 16,777,216 slots, 0.100 s at 131,072; left in
# place, Q18's HAVING filter and the compaction after it read 60M rows
# and not 16.7M, which costs them 0.15 s. The two meet near n / 128.
IN_PLACE_FACTOR = 128


def in_place_output(out_capacity: int, capacity: int) -> bool:
    """Whether the value-carrying form keeps its groups in place for an
    input of `capacity` rows and a plan that expects `out_capacity`
    groups."""
    return out_capacity * IN_PLACE_FACTOR >= capacity


@recorded_jit(static_argnums=(2, 3, 4, 5, 6, 8, 9))
def packed_sort_group_aggregate(batch: Batch, kmins, key_indices: tuple,
                                key_bits: tuple, aggs: tuple,
                                out_capacity: int,
                                word_splits: tuple = None,
                                vmins=None,
                                value_bits: tuple = None,
                                in_place: bool = False) -> Batch:
    """sort_group_aggregate with all keys packed into int64 words (see
    key_pack_plan / key_pack_plan_words). One word sorts directly;
    multiple words run an LSD radix (lsd_word_sort): stable sorts from
    the least-significant word up, so even 7-key GROUP BYs never exceed
    two sort operands per pass (XLA TPU sort compile cost is
    operand-count bound). Dead rows pack to int64.max in every word so
    they sort last. No DISTINCT support (callers route distinct to the
    general kernel).

    Two forms, chosen by what the plan measured. With `value_bits` (the
    keys' one word has room for the aggregates' arguments below it) the
    word carries them through the sort and nothing is gathered through a
    permutation: see _carried_group_aggregate; `in_place` leaves the
    groups at their segments' ends in a batch of the input's capacity
    and `out_capacity` is not read. Without, the word carries
    the row's position, the sort gives the row permutation, and keys,
    arguments and group results are gathered through it
    (_grouped_reduce)."""
    n = batch.capacity
    if word_splits is None:
        word_splits = ((0, len(key_indices)),)
    words = []
    for (s, e) in word_splits:
        w = jnp.zeros(n, dtype=jnp.int64)
        for j in range(s, e):
            col = batch.columns[key_indices[j]]
            norm = col.data.astype(jnp.int64) - kmins[j] + 1
            norm = jnp.where(col.valid, norm, 0)      # NULL slot
            w = (w << key_bits[j]) | norm
        words.append(w)
    if value_bits is not None:
        assert len(words) == 1
        return _carried_group_aggregate(
            batch, words[0], kmins, key_indices, key_bits, aggs,
            None if in_place else out_capacity, vmins, value_bits)
    words = [jnp.where(batch.live, w, jnp.iinfo(jnp.int64).max)
             for w in words]
    perm = lsd_word_sort(words, [sum(key_bits[s:e])
                                 for (s, e) in word_splits])
    live_s = batch.live[perm]

    first = jnp.arange(n) == 0
    diff = jnp.zeros(n, dtype=jnp.bool_)
    for w in words:
        ws = w[perm]
        diff = diff | (ws != jnp.roll(ws, 1))
    boundary = live_s & (first | diff)
    return _grouped_reduce(batch, key_indices, aggs, out_capacity, perm,
                           live_s, boundary, {})


def _carried_group_aggregate(batch: Batch, key_word, kmins,
                             key_indices: tuple, key_bits: tuple,
                             aggs: tuple, out_capacity: int, vmins,
                             value_bits: tuple) -> Batch:
    """The value-carrying form of packed_sort_group_aggregate (traced
    inside its jit). The sort word is `keys << value bits | arguments`,
    each argument range-compressed as a key is (`- vmin + 1`, slot 0 for
    NULL), dead rows int64.max: ONE int64 operand, unstable, since equal
    words are equal rows. Sorted keys, arguments, validity and the live
    mask are shifts and masks of the sorted word.

    A group's totals are read at its segment's LAST row: a cumulative
    sum less its value before the segment's first row, which a
    cumulative max carries forward (the summed fields are >= 0, so the
    running sums never fall); min and max are a cumulative max of the
    field under the segment's first position. No gather, no scatter.

    The output is the input's capacity with `live` at the segment ends
    (in place: `out_capacity` None), or those rows brought to
    `out_capacity` in key order by one live_first_order of the
    segment-end mask and a gather a plane (dense: groups past it are
    dropped and the caller retries, as on the permutation form).
    in_place_output says which the executor asks for."""
    n = batch.capacity
    big = jnp.iinfo(jnp.int64).max
    columns = value_columns(aggs)
    w = key_word
    for j, vi in enumerate(columns):
        col = batch.columns[vi]
        norm = col.data.astype(jnp.int64) - vmins[j] + 1
        w = (w << value_bits[j]) | jnp.where(col.valid, norm, 0)
    (ws,) = jax.lax.sort((jnp.where(batch.live, w, big),), num_keys=1,
                         is_stable=False)

    def field(shift: int, bits: int):
        return (ws >> shift) & ((1 << bits) - 1)

    live_s = ws != big
    ks = ws >> sum(value_bits)
    pos = jnp.arange(n, dtype=jnp.int32)
    # a dead row's key part is above every live one's: the last live
    # row ends its segment against it
    boundary = live_s & ((pos == 0) | (ks != jnp.roll(ks, 1)))
    last = live_s & ((pos == n - 1) | (ks != jnp.roll(ks, -1)))

    # the segment's first position, carried to its every row
    start = lax.cummax(jnp.where(boundary, pos, 0))

    def seg_total(x):
        """Each segment's total of x >= 0, at the segment's last row."""
        cs = jnp.cumsum(x)
        return cs - lax.cummax(jnp.where(boundary, cs - x, 0))

    def seg_max(x, bits: int):
        """Each segment's running maximum of 0 <= x < 2^bits: `start`
        never falls, so above x it makes a cumulative max segmented.
        (One pass; lax.associative_scan alone at 60M rows ended the
        process on the chip with SIGSEGV: PERF.md, PR 33.)"""
        packed = (start.astype(jnp.int64) << bits) | x
        return lax.cummax(packed) & ((1 << bits) - 1)

    out_cols = []
    shift = sum(value_bits) + sum(key_bits)
    for j, ki in enumerate(key_indices):
        shift -= key_bits[j]
        f = field(shift, key_bits[j])
        valid = last & (f != 0)
        data = jnp.where(valid, f + kmins[j] - 1, 0)
        out_cols.append(Column(
            data=data.astype(batch.columns[ki].data.dtype), valid=valid))

    fields, counts = {}, {}
    for j, vi in enumerate(columns):
        shift -= value_bits[j]
        f = jnp.where(live_s, field(shift, value_bits[j]), 0)
        fields[vi] = (f, value_bits[j], vmins[j] - 1)
        counts[vi] = seg_total((f != 0).astype(jnp.int32)).astype(jnp.int64)
    for spec in aggs:
        if spec.func == "count_star":
            cnt = (pos + 1 - start).astype(jnp.int64)
            out_cols.append(Column(data=cnt, valid=last))
            continue
        cnt = counts[spec.arg_index]
        if spec.func == "count":
            out_cols.append(Column(data=cnt, valid=last))
            continue
        f, bits, base = fields[spec.arg_index]
        if spec.func == "sum":
            # two bits hold NULL and ONE value (a decimal sum's high
            # limb): its fields add up to the count
            state = (cnt if bits == 2 else seg_total(f)) + cnt * base
        else:
            # min is the max of the field counted down from 2^bits
            top = 1 << bits
            state = seg_max(f, bits) if spec.func == "max" else \
                top - seg_max(jnp.where(f != 0, top - f, 0), bits)
            state = (state + base).astype(
                batch.columns[spec.arg_index].data.dtype)
        out_cols.append(Column(data=state, valid=last & (cnt > 0)))
    out = Batch(columns=tuple(out_cols), live=last)
    if out_capacity is None:
        return out
    idx = live_first_order(last, out_capacity)
    return Batch(tuple(Column(c.data[idx], c.valid[idx])
                       for c in out.columns), last[idx])


# --------------------------------------------------------------------------
# global (ungrouped) aggregation — Trino's AggregationOperator
# --------------------------------------------------------------------------

@recorded_jit(static_argnums=(1,))
def global_aggregate(batch: Batch, aggs: tuple) -> Batch:
    """No GROUP BY: one output row, always live (SQL: aggregates over an
    empty input produce one row of NULLs / zero counts). Pure masked
    reductions."""
    out_cols = []
    one = jnp.ones(1, dtype=jnp.bool_)
    for spec in aggs:
        if spec.func == "count_star":
            cnt = batch.live.sum(dtype=jnp.int64)[None]
            out_cols.append(Column(data=cnt, valid=one))
            continue
        col = batch.columns[spec.arg_index]
        m = batch.live & col.valid
        cnt = m.sum(dtype=jnp.int64)[None]
        if spec.func == "count":
            out_cols.append(Column(data=cnt, valid=one))
            continue
        if spec.func == "sum":
            acc_dtype = jnp.int64 if jnp.issubdtype(col.data.dtype,
                                                    jnp.integer) \
                else col.data.dtype
            state = jnp.where(m, col.data.astype(acc_dtype), 0).sum()[None]
        else:
            ident = _identity(spec.func, col.data.dtype)
            red = jnp.min if spec.func == "min" else jnp.max
            state = red(jnp.where(m, col.data, ident))[None]
        out_cols.append(Column(data=state, valid=cnt > 0))
    return Batch(columns=tuple(out_cols), live=one)


# --------------------------------------------------------------------------
# host-side finalizers (AVG quotient etc.)
# --------------------------------------------------------------------------

def avg_decimal_finalize(sums, counts, xp=np):
    """Exact decimal AVG: round-half-away-from-zero of sum/count at the
    input scale (Trino avg(decimal) keeps the argument scale).

    Works with either numpy (host finalization) or jax.numpy (device, used
    by the DecimalAvg IR node in ops/project.py) — single implementation so
    the subtle signed-remainder rounding cannot drift between paths."""
    counts = xp.where(counts == 0, 1, counts)
    q = sums // counts
    rem = sums - q * counts
    # adjust toward zero first (floor for negatives), then round
    neg = sums < 0
    q = xp.where(neg & (rem != 0), q + 1, q)
    rem = xp.where(neg, sums - q * counts, rem)
    up = (2 * xp.abs(rem) >= counts).astype(xp.int64)
    return xp.where(neg, q - up, q + up)
