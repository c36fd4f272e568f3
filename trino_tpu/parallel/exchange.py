"""Collective exchange: the data plane, TPU edition.

Reference mapping (SURVEY.md §2.7/§2.8):

- hash repartition (PartitionedOutputOperator.java:48 ->
  partitioned OutputBuffer -> HTTP pull -> ExchangeOperator.java:44)
  ==> `lax.all_to_all` over ICI inside the jitted stage program
  (`repartition_by_key` below);
- broadcast build side (BroadcastOutputBuffer.java:56)
  ==> replicated sharding / `all_gather`;
- partial-aggregate merge at stage boundary (HashAggregationOperator
  PARTIAL on workers -> FINAL after exchange)
  ==> `lax.psum` / `pmin` / `pmax` on the dense group-state tables.

These run *inside* shard_map bodies. Static shapes force the bucket layout:
each shard sorts rows by destination and exchanges fixed-capacity buckets
(dead-row padding rides along); capacity per destination equals the local
capacity, so no row can overflow — the cost is n_shards x memory during the
exchange, to be tightened with two-pass sizing later (SURVEY.md §7 hard
part 1 trade-off, made explicit here).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..batch import Batch, Column
from .mesh import AXIS


def _hash64(x: jax.Array) -> jax.Array:
    """splitmix64 finalizer — the wire-partitioning hash
    (Trino: InterpretedHashGenerator / XxHash64 over channels)."""
    x = x.astype(jnp.uint64)
    x = (x ^ (x >> 30)) * jnp.uint64(0xbf58476d1ce4e5b9)
    x = (x ^ (x >> 27)) * jnp.uint64(0x94d049bb133111eb)
    x = x ^ (x >> 31)
    return x


def partition_of(key: jax.Array, n_parts: int) -> jax.Array:
    return (_hash64(key) % jnp.uint64(n_parts)).astype(jnp.int32)


def repartition_by_key(batch: Batch, key_index: int, n_shards: int,
                       axis: str = AXIS) -> Batch:
    """Inside shard_map: move every live row to shard
    hash(key) % n_shards. Output capacity = n_shards * local capacity.

    Algorithm (static shapes throughout):
    1. dest[i] = hash partition of row i (dead rows -> own shard, stay put
       as padding)
    2. sort rows by dest -> contiguous destination runs
    3. view as [n_shards, capacity] buckets, all_to_all over the mesh axis
    4. flatten received buckets; live mask survives the ride
    """
    cap = batch.capacity
    key_col = batch.columns[key_index]
    me = lax.axis_index(axis)
    dest = jnp.where(batch.live & key_col.valid,
                     partition_of(key_col.data.astype(jnp.int64), n_shards),
                     me)

    order = jax.lax.sort((dest, jnp.arange(cap, dtype=jnp.int32)),
                         num_keys=1)[1]
    dest_sorted = dest[order]
    # bucket (d, j) pulls the j-th row of destination-run d — a pure gather
    # (XLA TPU serializes scatters; gathers vectorize), dead-padded past
    # each run's end
    starts = jnp.searchsorted(dest_sorted, jnp.arange(n_shards))
    ends = jnp.searchsorted(dest_sorted, jnp.arange(n_shards), side="right")
    j = jnp.arange(cap)
    src = starts[:, None] + j[None, :]                    # [n_shards, cap]
    in_run = src < ends[:, None]
    src_c = jnp.clip(src, 0, cap - 1)

    def exchange(x, fill):
        x_sorted = x[order]
        buckets = jnp.where(in_run, x_sorted[src_c], fill)
        out = lax.all_to_all(buckets, axis, split_axis=0, concat_axis=0,
                             tiled=False)
        return out.reshape(n_shards * cap)

    new_cols = tuple(Column(data=exchange(c.data,
                                          jnp.zeros((), c.data.dtype)),
                            valid=exchange(c.valid, False))
                     for c in batch.columns)
    new_live = exchange(batch.live, False)
    return Batch(columns=new_cols, live=new_live)


def merge_partial_states(partial: Batch, agg_funcs: Tuple[str, ...],
                         n_keys: int, axis: str = AXIS) -> Batch:
    """Merge per-shard dense aggregate tables (direct strategy) into the
    final table, replicated on all shards. agg_funcs[i] names the i-th
    aggregate column's function (after n_keys key columns)."""
    # NB: only psum and all_gather here. The installed TPU compiler takes
    # min/max all-reduce on 32-bit lanes but not on the engine's 64-bit
    # ones ("UNIMPLEMENTED: Supported lowering only of Sum all reduce" for
    # s64/f64 pmin/pmax, compiled for a described v5e 2x2), so min/max
    # merge rides an all_gather + local reduce.
    cols = list(partial.columns)
    out_cols = []
    for i, col in enumerate(cols):
        if i < n_keys:
            out_cols.append(col)    # identical on every shard (decoded ids)
            continue
        func = agg_funcs[i - n_keys]
        if func in ("sum", "count", "count_star"):
            # invalid (empty-group) states hold 0, safe to sum directly
            data = lax.psum(col.data, axis)
        elif func in ("min", "max"):
            if jnp.issubdtype(col.data.dtype, jnp.integer):
                ident = jnp.iinfo(col.data.dtype).max if func == "min" \
                    else jnp.iinfo(col.data.dtype).min
            else:
                ident = jnp.inf if func == "min" else -jnp.inf
            masked = jnp.where(col.valid, col.data, ident)
            gathered = lax.all_gather(masked, axis)   # [n_shards, cap]
            data = (jnp.min if func == "min" else jnp.max)(gathered, axis=0)
        else:
            raise ValueError(func)
        valid = lax.psum(col.valid.astype(jnp.int32), axis) > 0
        out_cols.append(Column(data=data, valid=valid))
    live = lax.psum(partial.live.astype(jnp.int32), axis) > 0
    # key validity should reflect merged liveness
    out_cols[:n_keys] = [Column(data=c.data, valid=live)
                         for c in out_cols[:n_keys]]
    return Batch(columns=tuple(out_cols), live=live)
