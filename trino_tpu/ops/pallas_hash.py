"""Pallas VMEM-resident hash-table kernel — hash aggregation + join build.

Every heavy grouping path so far is sort-based (ops/aggregate.py) or
dense-LUT (ops/join.py): q18's 1.5M-group aggregate pays a full
lexicographic `lax.sort` because its key domain is sparse.  The
hash-based alternative the literature keeps landing on ("Global Hash
Tables Strike Back!", "Hash-Based vs. Sort-Based Group-By-Aggregate" —
PAPERS.md) needs data-dependent insertion, which XLA TPU can only
express as serialized scatters (~80 ns/row PER scatter op, one per
aggregate).  This kernel does the whole insert-or-accumulate in ONE
pass over the input with the table resident in VMEM:

- **one global table, sequential grid**: TPU grid steps run in order on
  a core, so the table planes are an output block REVISITED by every
  step (the accumulator pattern of `pallas_gather._scan_kernel`) — a
  shared global hash table with zero races, exactly the structure the
  GPU literature reaches with atomics.
- **open addressing, linear probing**: slot = splitmix64(key + SEED) %
  T (computed in XLA — the kernel has no 64-bit multiplier), probe
  bound MAX_PROBES, occupancy capped at LOAD_NUM/LOAD_DEN of T.  A row
  that exhausts its probes or would breach the load cap is COUNTED as
  an escape; the caller must discard the run and radix-partition the
  batch with the spill tier's splitmix64 partitioner
  (`exec/spill._partition_ids`) so each partition re-enters the kernel
  — the same partitions the round-9 host-spill tier uses, so memory
  pressure composes bit-exactly. SEED decorrelates the slot hash from
  the partitioner (both are splitmix64; without a distinct seed a
  power-of-two partition count would leave only T/P reachable slots
  per partition).
- **int32 bit-planes for 64-bit lanes**: Mosaic has no i64, so keys and
  sum states ride (lo, hi) int32 plane pairs (the `pallas_gather.py`
  trick).  64-bit accumulation is exact two's-complement limb
  arithmetic: lo adds with an unsigned-compare carry into hi, so hash
  sums match the XLA int64 sort-path sums bit for bit, wrap included.
- **insert-or-accumulate is scalar-core work**: the per-row body is a
  probe `while_loop` plus a handful of scalar VMEM reads/writes per
  aggregate.  That is the honest TPU cost model for data-dependent
  writes (~tens of ns/row on the scalar core) — orders of magnitude
  under the sort path's O(n log n) at high cardinality, and ONE pass
  over HBM instead of the sort's several.

Aggregation contract (`hash_group_aggregate`): integer-typed keys
packed into ONE int64 word by the executor's range-compression plan
(`ops.aggregate.key_pack_plan` — lossless, so equality is exact; no
hash-collision risk ever reaches results), integer-typed aggregate
arguments, funcs sum/count/count_star/min/max, no DISTINCT (the
strategy gate routes DISTINCT to the sort kernel).  Output is a batch
of capacity `table_slots` whose live mask marks occupied slots: key
columns decode from the packed word (digit 0 = NULL, NULLs group
together), aggregate states are bit-exact vs `sort_group_aggregate`.
Group order is slot order — no operator here guarantees row order.

Join build (`build_join_table`): the SAME kernel with the aggregate
layout (min(row_id), count(*)) — the build side of a hash join IS a
hash aggregation of row ids by key.  Duplicate build keys show up as
inserted_rows > occupied_slots (one fused validation fetch, like the
dense LUT's dup check); probing (`hash_join_probe`) walks the linear
chain with MAX_PROBES rounds of `pallas_gather`-fused multi-plane
gathers.  Because insertion never displaces beyond MAX_PROBES (that is
an escape), a probe that sees MAX_PROBES non-empty non-matching slots
is a DEFINITIVE miss — no escape path exists on the probe side.

Session wiring: `enable_pallas_hash` = auto | true | false. On a TPU
backend the kernels do not compile yet (see `resolve_mode`): auto is
off there and true raises. Off-chip, true runs the kernel logic through
the Pallas interpreter (tier-1) and auto is off. Every site keeps its
sort path for when the mode is off.
"""

from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..batch import Batch, Column
from ..exec.profiler import recorded_jit
from .aggregate import AggSpec

SUB = 8                      # sublane rows per input block
LANES = 128                  # lanes per row
# in-kernel row/lane splits are shifts and masks: the Mosaic lowering of
# scalar `//` and `%` re-traces helper functions at lowering time, when
# the package's jax_enable_x64 is back on, and then refuses the int64
# constants they produce ("64-bit types are not supported")
_LANE_BITS = 7
BLOCK = SUB * LANES          # rows inserted per grid step
MAX_PROBES = 16              # linear-probe bound (breach = escape)
LOAD_NUM, LOAD_DEN = 5, 8    # occupancy cap 0.625 * T keeps probes short
# table sizes are powers of two in [MIN, MAX] slots; the per-call VMEM
# budget (key planes + state planes) additionally caps the choice
MIN_TABLE_SLOTS = 1 << 10
MAX_TABLE_SLOTS = 1 << 17
VMEM_TABLE_BYTES = 8 << 20
MAX_HASH_AGGS = 8

# empty-slot sentinel: the int64 pattern (hi=INT32_MIN, lo=0) == i64 min.
# Packed aggregation keys are always >= 0; join keys that equal i64 min
# (never a real key) are force-escaped in the wrapper, not inserted.
_EMPTY_HI = -(1 << 31)
_EMPTY_LO = 0
EMPTY_KEY = -(1 << 63)
_I32MIN = -(1 << 31)          # python int: jnp constants would be
                              # captured by the kernel closure

# slot-hash seed: decorrelates the in-table slot from the radix
# partitioner's splitmix64 (server/tasks.partition_assignment mixes
# key + column_position; this constant collides with neither)
_SLOT_SEED = np.uint64(0xD1B54A32D192ED03)

# aggregate kinds in the kernel's static layout
_K_COUNT, _K_SUM, _K_MIN, _K_MAX = 0, 1, 2, 3
_KIND = {"count": _K_COUNT, "count_star": _K_COUNT, "sum": _K_SUM,
         "min": _K_MIN, "max": _K_MAX}


# Why the kernels of this module are OFF on a TPU backend (jax 0.9.0,
# libtpu 0.0.34, compiled for a described v5e): the insert and probe
# bodies are scalar-core loops that read and write single table words in
# VMEM, and Mosaic has neither form —
#   ValueError: Cannot store scalars to VMEM
# for `tk_lo[sr, sl] = klo`, and once stores go through a one-row
# read-modify-write,
#   MosaicError: cannot statically prove that index in dimension 1 is a
#   multiple of 128 (vector.load ... -> vector<1x1xi32>)
# for the scalar reads `slot_ref[r, l]` / `tk_hi[sr, sl]`. The table
# (up to 2.6 MB) does not fit SMEM, so compiling needs a vector-form
# rewrite of the probe loop, not a spec change. Until then `auto` is off
# on the chip by this rule (no run-time fallback), `true` raises, and
# tests/test_chip_compile.py pins the refusal so the day it compiles the
# test says so. Off-chip the interpreter runs the kernel logic as before.
TPU_REFUSAL = "Cannot store scalars to VMEM"


def resolve_mode(setting) -> str:
    """Session-property value -> kernel mode ('interpret' | 'off'):
    interpret is the CPU/tier-1 path; on a TPU backend the kernels do
    not compile (see TPU_REFUSAL above), so auto is off and true
    raises."""
    s = str(setting).lower()
    on_tpu = jax.default_backend() == "tpu"
    if s in ("true", "1"):
        if on_tpu:
            raise NotImplementedError(
                "the Pallas hash-table kernels do not compile for TPU "
                f"(Mosaic: {TPU_REFUSAL}); leave the property at auto")
        return "interpret"
    return "off"


def _splitmix64(x: jax.Array) -> jax.Array:
    """uint64 -> uint64 avalanche (the partitioner's mix, jnp form)."""
    z = x + jnp.uint64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return z ^ (z >> jnp.uint64(31))


def hash_slot(key: jax.Array, table_slots: int) -> jax.Array:
    """Home slot per int64 key (computed in XLA; the kernel only walks
    the probe chain from here)."""
    h = _splitmix64(key.astype(jnp.int64).view(jnp.uint64) + _SLOT_SEED)
    return (h % jnp.uint64(table_slots)).astype(jnp.int32)


def agg_layout(aggs: tuple):
    """Static kernel layout: per-agg (kind, lo, hi, cnt, vlo, vhi) plane
    indices (-1 = unused) plus (state_planes, value_planes) totals."""
    layout = []
    ns = nv = 0
    for spec in aggs:
        kind = _KIND[spec.func]
        if kind == _K_COUNT:
            layout.append((kind, -1, -1, ns, -1, -1))
            ns += 1
        else:
            layout.append((kind, ns, ns + 1, ns + 2, nv, nv + 1))
            ns += 3
            nv += 2
    return tuple(layout), ns, max(nv, 1)


def max_table_slots(aggs: tuple) -> int:
    """Largest power-of-two table the VMEM budget allows for this
    aggregate layout (2 key planes + state planes, 4 B each)."""
    _, ns, _ = agg_layout(aggs)
    cap = VMEM_TABLE_BYTES // (4 * (2 + ns))
    t = MIN_TABLE_SLOTS
    while t * 2 <= min(cap, MAX_TABLE_SLOTS):
        t *= 2
    return t


def pick_table_slots(est_groups: int, aggs: tuple) -> Tuple[int, bool]:
    """(table_slots, fits): the smallest table whose load cap covers
    `est_groups`; fits=False means even the largest table cannot and
    the caller should radix-partition upfront."""
    cap = max_table_slots(aggs)
    t = MIN_TABLE_SLOTS
    while t * LOAD_NUM // LOAD_DEN < est_groups and t < cap:
        t *= 2
    return t, t * LOAD_NUM // LOAD_DEN >= est_groups


# --------------------------------------------------------------------------
# the insert-or-accumulate kernel
# --------------------------------------------------------------------------

def _u32_lt(a: jax.Array, b: jax.Array) -> jax.Array:
    """Unsigned 32-bit compare of int32 bit patterns."""
    return (a ^ _I32MIN) < (b ^ _I32MIN)


def _insert_kernel(layout: tuple, table_slots: int):
    t_rows = table_slots // LANES
    load_cap = table_slots * LOAD_NUM // LOAD_DEN

    def kernel(slot_ref, klo_ref, khi_ref, vb_ref, val_ref,
               tk_lo, tk_hi, st_ref, sc_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            tk_lo[...] = jnp.full((t_rows, LANES), _EMPTY_LO, jnp.int32)
            tk_hi[...] = jnp.full((t_rows, LANES), _EMPTY_HI, jnp.int32)
            st_ref[...] = jnp.zeros(st_ref.shape, jnp.int32)
            sc_ref[0, 0] = jnp.int32(0)
            sc_ref[0, 1] = jnp.int32(0)

        def row(j, carry):
            esc, occ = carry
            r = j >> _LANE_BITS
            l = j & (LANES - 1)
            slot = slot_ref[r, l]
            alive = slot >= 0
            klo = klo_ref[r, l]
            khi = khi_ref[r, l]

            def probe_cond(c):
                return c[2] == 0

            def probe_body(c):
                s, p, _ = c
                sr = s >> _LANE_BITS
                sl = s & (LANES - 1)
                thi = tk_hi[sr, sl]
                tlo = tk_lo[sr, sl]
                empty = (thi == _EMPTY_HI) & (tlo == _EMPTY_LO)
                match = (~empty) & (thi == khi) & (tlo == klo)
                out = jnp.where(match, 1,
                                jnp.where(empty, 2, 0)).astype(jnp.int32)
                p2 = p + jnp.int32(1)
                out = jnp.where((out == 0) & (p2 >= MAX_PROBES),
                                jnp.int32(3), out)
                nxt = jnp.where(s + 1 >= table_slots, 0,
                                s + 1).astype(jnp.int32)
                return (jnp.where(out == 0, nxt, s), p2, out)

            s_f, _, outcome = jax.lax.while_loop(
                probe_cond, probe_body,
                (jnp.where(alive, slot, 0), jnp.int32(0), jnp.int32(0)))
            claim = alive & (outcome == 2) & (occ < load_cap)
            ok = (alive & (outcome == 1)) | claim
            esc = esc + jnp.where(alive & ~ok, 1, 0).astype(jnp.int32)
            occ = occ + jnp.where(claim, 1, 0).astype(jnp.int32)
            sr = s_f >> _LANE_BITS
            sl = s_f & (LANES - 1)

            @pl.when(claim)
            def _():
                tk_lo[sr, sl] = klo
                tk_hi[sr, sl] = khi

            @pl.when(ok)
            def _():
                vb = vb_ref[r, l]
                for a, (kind, lo_p, hi_p, cnt_p, vlo_p,
                        vhi_p) in enumerate(layout):
                    bit = (vb >> a) & 1
                    cnt = st_ref[cnt_p, sr, sl]
                    if kind == _K_SUM:
                        alo = st_ref[lo_p, sr, sl]
                        ahi = st_ref[hi_p, sr, sl]
                        blo = val_ref[vlo_p, r, l]
                        bhi = val_ref[vhi_p, r, l]
                        slo = alo + blo
                        # exact i64 limb add: carry via unsigned compare
                        co = _u32_lt(slo, blo).astype(jnp.int32)
                        st_ref[lo_p, sr, sl] = slo
                        st_ref[hi_p, sr, sl] = ahi + bhi + co
                    elif kind in (_K_MIN, _K_MAX):
                        alo = st_ref[lo_p, sr, sl]
                        ahi = st_ref[hi_p, sr, sl]
                        blo = val_ref[vlo_p, r, l]
                        bhi = val_ref[vhi_p, r, l]
                        less = (bhi < ahi) | ((bhi == ahi) &
                                              _u32_lt(blo, alo))
                        better = less if kind == _K_MIN else \
                            (bhi > ahi) | ((bhi == ahi) &
                                           _u32_lt(alo, blo))
                        take = (bit == 1) & ((cnt == 0) | better)
                        st_ref[lo_p, sr, sl] = jnp.where(take, blo, alo)
                        st_ref[hi_p, sr, sl] = jnp.where(take, bhi, ahi)
                    st_ref[cnt_p, sr, sl] = cnt + bit
            return esc, occ

        esc0 = sc_ref[0, 0]
        occ0 = sc_ref[0, 1]
        esc, occ = jax.lax.fori_loop(0, BLOCK, row,
                                     (esc0, occ0))
        sc_ref[0, 0] = esc
        sc_ref[0, 1] = occ
    return kernel


def _pad_rows(x: jax.Array, fill) -> jax.Array:
    pad = (-x.shape[-1]) % BLOCK
    if pad == 0:
        return x
    width = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, width, constant_values=fill)


def _hash_insert(slot: jax.Array, klo: jax.Array, khi: jax.Array,
                 vbits: jax.Array, vals: jax.Array, layout: tuple,
                 table_slots: int, interpret: bool):
    """Run the insert-or-accumulate kernel. slot/klo/khi/vbits are
    [n] int32 (slot -1 = skip row), vals [NV, n] int32 value planes.
    Returns (tk_lo, tk_hi [T], states [NS, T], esc, occ int32)."""
    _, ns, nv = agg_layout_from(layout)
    n = slot.shape[0]
    slot = _pad_rows(slot, -1)
    klo = _pad_rows(klo, 0)
    khi = _pad_rows(khi, 0)
    vbits = _pad_rows(vbits, 0)
    vals = _pad_rows(vals, 0)
    npad = slot.shape[0]
    nb = npad // BLOCK
    t_rows = table_slots // LANES
    # traced with 64-bit off (kernel body AND index maps): the package
    # enables jax_enable_x64 and Mosaic refuses 64-bit types
    with jax.enable_x64(False):
        outs = pl.pallas_call(
            _insert_kernel(layout, table_slots),
            grid=(nb,),
            in_specs=[
                pl.BlockSpec((SUB, LANES), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((SUB, LANES), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((SUB, LANES), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((SUB, LANES), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((nv, SUB, LANES), lambda i: (0, i, 0),
                             memory_space=pltpu.VMEM)],
            out_specs=[
                pl.BlockSpec((t_rows, LANES), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((t_rows, LANES), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((ns, t_rows, LANES), lambda i: (0, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 2), lambda i: (0, 0),
                             memory_space=pltpu.SMEM)],
            out_shape=[
                jax.ShapeDtypeStruct((t_rows, LANES), jnp.int32),
                jax.ShapeDtypeStruct((t_rows, LANES), jnp.int32),
                jax.ShapeDtypeStruct((ns, t_rows, LANES), jnp.int32),
                jax.ShapeDtypeStruct((1, 2), jnp.int32)],
            interpret=interpret,
        )(slot.reshape(nb * SUB, LANES), klo.reshape(nb * SUB, LANES),
          khi.reshape(nb * SUB, LANES), vbits.reshape(nb * SUB, LANES),
          vals.reshape(nv, nb * SUB, LANES))
    tk_lo, tk_hi, st, sc = outs
    return (tk_lo.reshape(table_slots), tk_hi.reshape(table_slots),
            st.reshape(st.shape[0], table_slots), sc[0, 0], sc[0, 1])


def agg_layout_from(layout: tuple):
    """(layout, state_planes, value_planes) totals from a built layout
    (shared by _hash_insert so callers can't disagree with it)."""
    ns = nv = 0
    for kind, lo_p, hi_p, cnt_p, vlo_p, vhi_p in layout:
        ns = max(ns, cnt_p + 1, hi_p + 1)
        nv = max(nv, vhi_p + 1)
    return layout, ns, max(nv, 1)


def _split64(v: jax.Array):
    """int64 -> (lo, hi) int32 planes."""
    lo = (v & 0xFFFFFFFF).astype(jnp.uint32).astype(jnp.int32)
    hi = (v >> 32).astype(jnp.int32)
    return lo, hi


def _join64(lo: jax.Array, hi: jax.Array) -> jax.Array:
    return (hi.astype(jnp.int64) << 32) | \
        (lo.astype(jnp.int64) & 0xFFFFFFFF)


# --------------------------------------------------------------------------
# hash aggregation over a packed key word
# --------------------------------------------------------------------------

def supports_aggs(batch: Batch, aggs: tuple) -> bool:
    """Hash-agg eligibility for the value side: no DISTINCT (routed to
    sort), <= MAX_HASH_AGGS aggregates, integer-typed arguments only
    (float sums are order-dependent; the sort path is the oracle)."""
    if len(aggs) > MAX_HASH_AGGS:
        return False
    for a in aggs:
        if a.distinct or a.func not in _KIND:
            return False
        if a.arg_index is not None:
            dt = batch.columns[a.arg_index].data.dtype
            if not (jnp.issubdtype(dt, jnp.integer) or
                    dt == jnp.bool_):
                return False
    return True


@recorded_jit(static_argnums=(2, 3, 4, 5, 6))
def hash_group_aggregate(batch: Batch, kmins, key_indices: tuple,
                         key_bits: tuple, aggs: tuple,
                         table_slots: int, mode: str):
    """Group-by via the VMEM hash table. Keys are packed into one int64
    word with the SAME range-compression layout as
    `packed_sort_group_aggregate` (kmins/key_bits from
    `ops.aggregate.key_pack_plan`), values accumulate as exact int64
    limbs.  Returns (out_batch, escaped, n_groups): `escaped > 0` means
    load-cap or probe-bound breach — the caller MUST discard the batch
    and radix-partition (exec/executor.Executor.hash_aggregate owns
    that loop).  Output capacity is `table_slots`; live = occupied."""
    n = batch.capacity
    packed = jnp.zeros(n, dtype=jnp.int64)
    for j, (ki, b) in enumerate(zip(key_indices, key_bits)):
        col = batch.columns[ki]
        norm = col.data.astype(jnp.int64) - kmins[j] + 1
        packed = (packed << b) | jnp.where(col.valid, norm, 0)
    slot = jnp.where(batch.live, hash_slot(packed, table_slots), -1)
    klo, khi = _split64(packed)

    layout, ns, nv = agg_layout(aggs)
    vbits = jnp.zeros(n, dtype=jnp.int32)
    vplanes: List[jax.Array] = [jnp.zeros(n, jnp.int32)] * nv
    for a, spec in enumerate(aggs):
        if spec.arg_index is None:
            bit = batch.live
        else:
            bit = batch.live & batch.columns[spec.arg_index].valid
        vbits = vbits | (bit.astype(jnp.int32) << a)
        kind, lo_p, hi_p, cnt_p, vlo_p, vhi_p = layout[a]
        if kind != _K_COUNT:
            col = batch.columns[spec.arg_index]
            v = jnp.where(bit, col.data.astype(jnp.int64), 0)
            vplanes[vlo_p], vplanes[vhi_p] = _split64(v)

    tk_lo, tk_hi, st, esc, occ = _hash_insert(
        slot, klo, khi, vbits, jnp.stack(vplanes), layout, table_slots,
        mode == "interpret")

    occupied = ~((tk_hi == _EMPTY_HI) & (tk_lo == _EMPTY_LO))
    key64 = _join64(tk_lo, tk_hi)

    out_cols: List[Column] = []
    rem = key64
    rev = []
    for j in range(len(key_indices) - 1, -1, -1):
        b = key_bits[j]
        digit = rem & ((1 << b) - 1)
        rem = rem >> b
        col = batch.columns[key_indices[j]]
        rev.append(Column(
            data=(digit - 1 + kmins[j]).astype(col.data.dtype),
            valid=occupied & (digit != 0)))
    out_cols.extend(reversed(rev))

    for a, spec in enumerate(aggs):
        kind, lo_p, hi_p, cnt_p, vlo_p, vhi_p = layout[a]
        cnt = st[cnt_p].astype(jnp.int64)
        if kind == _K_COUNT:
            out_cols.append(Column(data=cnt, valid=occupied))
            continue
        v64 = _join64(st[lo_p], st[hi_p])
        valid = occupied & (cnt > 0)
        if kind == _K_SUM:
            out_cols.append(Column(data=v64, valid=valid))
        else:
            dt = batch.columns[spec.arg_index].data.dtype
            out_cols.append(Column(data=v64.astype(dt), valid=valid))
    out = Batch(columns=tuple(out_cols), live=occupied)
    return out, esc.astype(jnp.int64), occ.astype(jnp.int64)


# --------------------------------------------------------------------------
# hybrid hash join: build = hash aggregation of row ids, probe = chained
# multi-plane gathers
# --------------------------------------------------------------------------

_JOIN_LAYOUT = ((_K_MIN, 0, 1, 2, 0, 1),)    # min(row_id) + its count


def join_table_slots(build_rows: int) -> Tuple[int, bool]:
    """(table_slots, fits) for a join build of `build_rows` candidate
    keys — same sizing rule as the aggregate table (3 state planes)."""
    cap = MIN_TABLE_SLOTS
    limit = min(MAX_TABLE_SLOTS, VMEM_TABLE_BYTES // (4 * 5))
    while cap * LOAD_NUM // LOAD_DEN < build_rows and cap < limit:
        cap *= 2
    return cap, cap * LOAD_NUM // LOAD_DEN >= build_rows


@recorded_jit(static_argnums=(1, 2, 3))
def build_join_table(build: Batch, build_keys: tuple, table_slots: int,
                     mode: str):
    """Hash-join build: insert every valid build key with min(row_id)
    as the payload (duplicate keys keep the smallest row, their count
    reveals them).  Returns (tk_lo, tk_hi, src [T] int32 row ids,
    dup_rows, escaped) — dup_rows > 0 breaks a unique-build contract,
    escaped > 0 means the table overflowed and the caller must degrade
    to the partitioned (hybrid) path."""
    from .join import _combined_key
    bk, bk_valid = _combined_key(build, build_keys)
    ok = build.live & bk_valid & (bk != EMPTY_KEY)
    forced = jnp.sum(build.live & bk_valid & (bk == EMPTY_KEY),
                     dtype=jnp.int64)
    slot = jnp.where(ok, hash_slot(bk, table_slots), -1)
    klo, khi = _split64(bk)
    rows = jnp.arange(build.capacity, dtype=jnp.int64)
    rlo, rhi = _split64(rows)
    vbits = ok.astype(jnp.int32)            # bit 0: min(row_id) valid
    tk_lo, tk_hi, st, esc, occ = _hash_insert(
        slot, klo, khi, vbits, jnp.stack([rlo, rhi]), _JOIN_LAYOUT,
        table_slots, mode == "interpret")
    n_ok = jnp.sum(ok, dtype=jnp.int64)
    escaped = esc.astype(jnp.int64) + forced
    dup_rows = n_ok - forced - esc.astype(jnp.int64) - \
        occ.astype(jnp.int64)
    return tk_lo, tk_hi, st[0], dup_rows, escaped


@recorded_jit(static_argnums=(5, 6, 7, 8))
def hash_join_probe(probe: Batch, build: Batch, tk_lo, tk_hi, src,
                    probe_keys: tuple, build_keys: tuple, kind: str,
                    gather_mode: str = "off"):
    """Probe a built (and dup/escape-validated) hash table: MAX_PROBES
    rounds of fused (key_lo, key_hi, row_id) gathers walk each probe's
    linear chain; an empty slot or an exhausted chain is a definitive
    miss (insertion never displaces past MAX_PROBES).  Payload columns
    materialize through the shared dense-join gather machinery
    (`ops.join._gather_build_payload`), riding the Pallas tiled gather
    when enabled.  Returns the joined batch; bit-exact vs the sorted
    searchsorted join."""
    from .join import _combined_key, _gather_build_payload
    table_slots = tk_lo.shape[0]
    pk, pk_valid = _combined_key(probe, probe_keys)
    ok = probe.live & pk_valid & (pk != EMPTY_KEY)
    slot = jnp.where(ok, hash_slot(pk, table_slots), 0)
    unresolved = ok
    found = jnp.full(probe.capacity, -1, dtype=jnp.int32)
    for _ in range(MAX_PROBES):
        from . import pallas_gather
        outs = pallas_gather.gather_columns(
            [tk_lo, tk_hi, src], slot,
            fills=[_EMPTY_LO, _EMPTY_HI, -1], mode=gather_mode)
        key_at = _join64(outs[0], outs[1])
        empty = key_at == EMPTY_KEY
        hit = unresolved & ~empty & (key_at == pk)
        found = jnp.where(hit, outs[2], found)
        unresolved = unresolved & ~empty & ~hit
        slot = jnp.where(slot + 1 >= table_slots, 0, slot + 1)
    matched = found >= 0
    if kind == "semi":
        return probe.with_live(probe.live & matched)
    if kind == "anti":
        return probe.with_live(probe.live & ~matched)
    src_c = jnp.clip(found, 0, build.capacity - 1)
    return _gather_build_payload(probe, build, src_c, matched, pk,
                                 build_keys, kind, gather_mode)


# --------------------------------------------------------------------------
# fused multiway star probe: k resident dimension tables, one pass
# --------------------------------------------------------------------------

MAX_MULTI_DIMS = 5           # q5-class stars top out here; the planner cap


def multiway_table_bytes(k: int, table_slots: int) -> int:
    """Resident VMEM footprint of k fused dimension tables: 3 int32
    planes each (key_lo, key_hi, src row id)."""
    return 3 * 4 * k * table_slots


def _multiprobe_kernel(k: int, table_slots: int):
    """Per fact block, walk all k probe chains in ONE kernel pass.

    Dimension planes arrive stacked [k, t_rows, LANES] and stay VMEM
    resident across the whole grid (index map pins them to block 0).
    Each row short-circuits: once it misses a dimension it is dead for
    every later one — exactly the ladder's live-mask AND, but without k
    intermediate materializations.  Per-dimension miss counters (rows
    that were still alive entering dimension d and failed there) ride
    an SMEM (1, k) accumulator, the `_insert_kernel` esc/occ pattern.
    """
    t_rows = table_slots // LANES

    def kernel(slot_ref, klo_ref, khi_ref, tk_lo, tk_hi, src_ref,
               found_ref, sc_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            for d in range(k):
                sc_ref[0, d] = jnp.int32(0)

        def row(j, miss):
            r = j >> _LANE_BITS
            l = j & (LANES - 1)
            # slot encoding: -2 dead fact row (skip entirely), -1 live
            # row whose key is NULL/sentinel (counts as a miss), else
            # the home slot.  The dead/live split is per row, so dim 0's
            # plane answers it for all dims.
            alive = slot_ref[0, r, l] != -2
            out_miss = []
            for d in range(k):
                slot = slot_ref[d, r, l]
                klo = klo_ref[d, r, l]
                khi = khi_ref[d, r, l]
                ok = alive & (slot >= 0)

                def probe_cond(c):
                    return c[2] == 0

                def probe_body(c, d=d):
                    s, p, _ = c
                    sr = s >> _LANE_BITS
                    sl = s & (LANES - 1)
                    thi = tk_hi[d, sr, sl]
                    tlo = tk_lo[d, sr, sl]
                    empty = (thi == _EMPTY_HI) & (tlo == _EMPTY_LO)
                    match = (~empty) & (thi == khi) & (tlo == klo)
                    out = jnp.where(match, 1,
                                    jnp.where(empty, 3,
                                              0)).astype(jnp.int32)
                    p2 = p + jnp.int32(1)
                    out = jnp.where((out == 0) & (p2 >= MAX_PROBES),
                                    jnp.int32(3), out)
                    nxt = jnp.where(s + 1 >= table_slots, 0,
                                    s + 1).astype(jnp.int32)
                    return (jnp.where(out == 0, nxt, s), p2, out)

                s_f, _, outcome = jax.lax.while_loop(
                    probe_cond, probe_body,
                    (jnp.where(ok, slot, 0), jnp.int32(0),
                     jnp.where(ok, jnp.int32(0), jnp.int32(3))))
                hit = ok & (outcome == 1)
                sr = s_f >> _LANE_BITS
                sl = s_f & (LANES - 1)
                found_ref[d, r, l] = jnp.where(
                    hit, src_ref[d, sr, sl], jnp.int32(-1))
                out_miss.append(
                    miss[d] + jnp.where(alive & ~hit,
                                        1, 0).astype(jnp.int32))
                alive = hit
            return tuple(out_miss)

        miss0 = tuple(sc_ref[0, d] for d in range(k))
        miss = jax.lax.fori_loop(0, BLOCK, row, miss0)
        for d in range(k):
            sc_ref[0, d] = miss[d]
    return kernel


def _multi_probe(slot: jax.Array, klo: jax.Array, khi: jax.Array,
                 tk_lo: jax.Array, tk_hi: jax.Array, src: jax.Array,
                 interpret: bool):
    """Run the fused star-probe kernel. slot/klo/khi are [k, n] int32
    (slot -2 = dead row, -1 = NULL key), tk_lo/tk_hi/src [k, T] int32
    table planes. Returns (found [k, n_pad] int32, miss [k] int32)."""
    k, table_slots = tk_lo.shape
    slot = _pad_rows(slot, -2)
    klo = _pad_rows(klo, 0)
    khi = _pad_rows(khi, 0)
    npad = slot.shape[-1]
    nb = npad // BLOCK
    t_rows = table_slots // LANES
    with jax.enable_x64(False):      # see _hash_insert
        found, sc = pl.pallas_call(
            _multiprobe_kernel(k, table_slots),
            grid=(nb,),
            in_specs=[
                pl.BlockSpec((k, SUB, LANES), lambda i: (0, i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((k, SUB, LANES), lambda i: (0, i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((k, SUB, LANES), lambda i: (0, i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((k, t_rows, LANES), lambda i: (0, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((k, t_rows, LANES), lambda i: (0, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((k, t_rows, LANES), lambda i: (0, 0, 0),
                             memory_space=pltpu.VMEM)],
            out_specs=[
                pl.BlockSpec((k, SUB, LANES), lambda i: (0, i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, k), lambda i: (0, 0),
                             memory_space=pltpu.SMEM)],
            out_shape=[
                jax.ShapeDtypeStruct((k, nb * SUB, LANES), jnp.int32),
                jax.ShapeDtypeStruct((1, k), jnp.int32)],
            interpret=interpret,
        )(slot.reshape(k, nb * SUB, LANES),
          klo.reshape(k, nb * SUB, LANES),
          khi.reshape(k, nb * SUB, LANES),
          tk_lo.reshape(k, t_rows, LANES),
          tk_hi.reshape(k, t_rows, LANES),
          src.reshape(k, t_rows, LANES))
    return found.reshape(k, npad), sc[0]


@recorded_jit(static_argnums=(4, 5))
def multiway_probe(probe: Batch, tk_lo, tk_hi, src,
                   probe_keys: tuple, mode: str):
    """Fused star probe: k dup/escape-validated dimension tables
    (stacked `build_join_table` planes, ALL sized to one shared
    `table_slots` so the stack is rectangular) probed in a single
    Pallas pass over the fact batch.  `probe_keys` is a tuple of
    per-dimension fact-side key index tuples.  Returns
    (found [k, n] int32 build row ids, -1 = miss at-or-before that
    dimension; miss [k] int64 per-dimension miss counters) — payload
    gathers stay in the caller, which shares the dense-join machinery
    with the pairwise ladder for bit-exactness."""
    from .join import _combined_key
    table_slots = tk_lo.shape[1]
    slots, klos, khis = [], [], []
    for pk_idx in probe_keys:
        pk, pk_valid = _combined_key(probe, pk_idx)
        ok = probe.live & pk_valid & (pk != EMPTY_KEY)
        slot = jnp.where(ok, hash_slot(pk, table_slots),
                         jnp.where(probe.live, -1, -2))
        klo, khi = _split64(pk)
        slots.append(slot)
        klos.append(jnp.where(ok, klo, 0))
        khis.append(jnp.where(ok, khi, 0))
    found, miss = _multi_probe(
        jnp.stack(slots), jnp.stack(klos), jnp.stack(khis),
        tk_lo, tk_hi, src, mode == "interpret")
    return found[:, :probe.capacity], miss.astype(jnp.int64)


def shard_join(probe: Batch, build: Batch, probe_keys: tuple,
               build_keys: tuple, kind: str, table_slots: int,
               mode: str, gather_mode: str = "off"):
    """Shard-local fused build + probe: the per-chip body of the
    mesh-partitioned join (parallel/stages.partitioned_hash_join_step).
    Deliberately NOT a jit entry of its own — it traces inside the
    enclosing shard_map program, so build, probe, and their validation
    counters stay in ONE XLA module with zero host round trips; the
    caller psums (dup_rows, escaped) across the mesh and owns the
    degrade decision (dup -> expansion join, escape -> skew, host
    equi-join). Returns (joined, dup_rows, escaped)."""
    tk_lo, tk_hi, src, dup_rows, escaped = build_join_table(
        build, build_keys, table_slots, mode)
    joined = hash_join_probe(probe, build, tk_lo, tk_hi, src,
                             probe_keys, build_keys, kind, gather_mode)
    return joined, dup_rows, escaped
