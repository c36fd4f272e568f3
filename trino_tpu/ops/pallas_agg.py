"""Pallas MXU group-aggregation kernel — one HBM pass for grouped sums.

The direct (dense small-domain) aggregation strategy in XLA form
(ops/aggregate.py) evaluates G x A masked reductions; XLA fuses them into a
few passes over the batch. This kernel does the whole thing in ONE pass by
turning grouping into a matmul on the systolic array (the canonical
scatter-free TPU trick):

    partial[g, c] = onehot[g, :] @ parts[:, c]

- int64 values ride as two int32 planes (hi/lo), since Mosaic has no
  64-bit lanes;
- each value is split in-kernel into five 12-bit limbs plus a negative-count
  column, all exactly representable in f32; the one-hot matmul with
  Precision.HIGHEST (bf16x3) then accumulates them exactly (every partial
  sum stays below 2^24);
- per-block partials [n_blocks, SUB, G, C] are combined in XLA as int64:
  sum_g v = sum_limbs(limb_sum << 12k) - (neg_count << 60).

Exact for |value| < 2^59 — any SUM whose inputs exceed that is at overflow
risk in int64 regardless (Trino short decimals stop at 2^63 too).

Reference role: compiled accumulators + GroupByHash's dense mode
(operator/aggregation/AccumulatorCompiler.java:88, BigintGroupByHash).

Measured (v5e, TPC-H SF1 q1 shape, G=6, A=6): 7.4ms vs 2.1ms for the XLA
masked-reduction path — the custom-call boundary forces the hi/lo planes to
materialize in HBM, which costs more than the fused single-pass XLA graph
saves at small G; the win region is larger group counts, where the XLA
path's unrolled G x A reduction graph grows linearly while this stays one
matmul pass. The strategy gate therefore picks the kernel as the LARGE end
of the direct-domain arm: `mxu_agg` = auto (default) routes direct
aggregates with G >= Executor.MXU_AGG_MIN_GROUPS here on TPU backends and
keeps the fused XLA graph below it; true/false force either way. (Round-12
folded the kernel into the gate — it previously idled behind an opt-in
nobody turned on.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..exec.profiler import recorded_jit
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..batch import Batch, Column
from .aggregate import AggSpec

BLK = 2048          # lane-dim elements per sublane row (VMEM-sized)
SUB = 8             # sublane rows per grid step
BLOCK_ELEMS = BLK * SUB
LIMBS = 5           # 12-bit limbs -> 60 bits
COLS_PER_AGG = LIMBS + 1              # + negative-count column

# VMEM budget guard: onehot [SUB,G,BLK] + parts [SUB,C,BLK] f32
MAX_GROUPS = 16
MAX_AGGS = 8


def supports(aggs, domains) -> bool:
    g = int(np.prod(domains)) if domains else 0
    if not (0 < g <= MAX_GROUPS and len(aggs) <= MAX_AGGS):
        return False
    return all(a.func in ("sum", "count", "count_star") and not a.distinct
               for a in aggs)


def _kernel(n_groups: int, n_cols: int, n_aggs: int):
    def kernel(gid_ref, hi_ref, lo_ref, out_ref):
        gid = gid_ref[0]                                       # [SUB,BLK]
        onehot = jnp.stack(
            [(gid == g).astype(jnp.float32) for g in range(n_groups)],
            axis=1)                                            # [SUB,G,BLK]
        cols = []
        for a in range(n_aggs):
            hi, lo = hi_ref[a], lo_ref[a]
            cols.append((lo & 0xFFF).astype(jnp.float32))
            cols.append(((lo >> 12) & 0xFFF).astype(jnp.float32))
            cols.append(((((lo >> 24) & 0xFF) +
                          ((hi & 0xF) * 256))).astype(jnp.float32))
            cols.append(((hi >> 4) & 0xFFF).astype(jnp.float32))
            cols.append(((hi >> 16) & 0xFFF).astype(jnp.float32))
            cols.append(((hi >> 31) & 1).astype(jnp.float32))
        while len(cols) < n_cols:
            cols.append(jnp.zeros_like(cols[0]))
        parts = jnp.stack(cols, axis=1)                        # [SUB,C,BLK]
        r = jax.lax.dot_general(
            onehot, parts, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)               # [SUB,G,C]
        out_ref[...] = r[None]
    return kernel


@recorded_jit(static_argnums=(3, 4))
def _mxu_sums(gid: jax.Array, hi: jax.Array, lo: jax.Array,
              n_groups: int, interpret: bool) -> jax.Array:
    """gid [n] int32 (n_groups = miss), hi/lo [A, n] int32 ->
    int64 totals [n_groups, A_cols] where A_cols = hi.shape[0]."""
    n_aggs, n = hi.shape
    n_cols = ((n_aggs * COLS_PER_AGG + 7) // 8) * 8
    nb = n // BLOCK_ELEMS
    with jax.enable_x64(False):
        out = pl.pallas_call(
            _kernel(n_groups, n_cols, n_aggs),
            grid=(nb,),
            in_specs=[
                pl.BlockSpec((1, SUB, BLK), lambda i: (i, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((n_aggs, SUB, BLK), lambda i: (0, i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((n_aggs, SUB, BLK), lambda i: (0, i, 0),
                             memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((1, SUB, n_groups, n_cols),
                                   lambda i: (i, 0, 0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((nb, SUB, n_groups, n_cols),
                                           jnp.float32),
            interpret=interpret,
        )(gid.reshape(nb, SUB, BLK), hi.reshape(n_aggs, nb * SUB, BLK),
          lo.reshape(n_aggs, nb * SUB, BLK))
    acc = out.astype(jnp.int64).sum(axis=(0, 1))         # [G, n_cols]
    tot = jnp.zeros((n_groups, n_aggs), dtype=jnp.int64)
    for a in range(n_aggs):
        base = a * COLS_PER_AGG
        col = jnp.zeros((n_groups,), dtype=jnp.int64)
        for p in range(LIMBS):
            col = col + (acc[:, base + p] << (12 * p))
        col = col - (acc[:, base + LIMBS] << 60)
        tot = tot.at[:, a].set(col)
    return tot


@recorded_jit(static_argnums=(1, 2, 3, 4))
def direct_group_aggregate_mxu(batch: Batch, key_indices: tuple,
                               domains: tuple, aggs: tuple,
                               interpret: bool = False) -> Batch:
    """Drop-in for ops.aggregate.direct_group_aggregate when supports()
    holds: same output layout (key digit columns, then aggregate states)."""
    n_groups = 1
    for d in domains:
        n_groups *= d

    cap = batch.capacity
    pad = (-cap) % BLOCK_ELEMS
    n = cap + pad

    gid = jnp.zeros(cap, dtype=jnp.int32)
    key_valid = jnp.ones(cap, dtype=jnp.bool_)
    for ki, d in zip(key_indices, domains):
        col = batch.columns[ki]
        gid = gid * d + jnp.clip(col.data.astype(jnp.int32), 0, d - 1)
        key_valid = key_valid & col.valid
    contributes = batch.live & key_valid
    gid = jnp.where(contributes, gid, n_groups)     # miss group
    gid = jnp.pad(gid, (0, pad), constant_values=n_groups)

    # value planes: one per aggregate + a leading live-count plane
    planes = [jnp.where(contributes, 1, 0).astype(jnp.int64)]
    for spec in aggs:
        if spec.func == "count_star":
            planes.append(planes[0])
        else:
            col = batch.columns[spec.arg_index]
            m = contributes & col.valid
            if spec.func == "count":
                planes.append(jnp.where(m, 1, 0).astype(jnp.int64))
            else:
                planes.append(jnp.where(m, col.data.astype(jnp.int64), 0))
        # validity companion: non-null contributor count per group
        if spec.func == "sum":
            col = batch.columns[spec.arg_index]
            planes.append(jnp.where(contributes & col.valid, 1, 0)
                          .astype(jnp.int64))
    v = jnp.stack([jnp.pad(p, (0, pad)) for p in planes])
    hi = (v >> 32).astype(jnp.int32)
    lo = (v & 0xFFFFFFFF).astype(jnp.uint32).astype(jnp.int32)

    tot = _mxu_sums(gid, hi, lo, n_groups, interpret)  # [G, planes]

    group_count = tot[:, 0]
    group_live = group_count > 0
    out_cols = []
    g_idx = jnp.arange(n_groups, dtype=jnp.int32)
    radix = n_groups
    for ki, d in zip(key_indices, domains):
        radix //= d
        digit = (g_idx // radix) % d
        out_cols.append(Column(
            data=digit.astype(batch.columns[ki].data.dtype),
            valid=group_live))
    plane = 1
    for spec in aggs:
        state = tot[:, plane]
        plane += 1
        if spec.func in ("count", "count_star"):
            out_cols.append(Column(data=state, valid=group_live))
        else:                                   # sum + its validity plane
            cnt = tot[:, plane]
            plane += 1
            out_cols.append(Column(data=state,
                                   valid=group_live & (cnt > 0)))
    return Batch(columns=tuple(out_cols), live=group_live)
