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


def join_filter_bounds(build: Batch, build_keys: Tuple[int, ...],
                       axis: str = AXIS):
    """Global [min, max] per build key, computed INSIDE the sharded
    stage body — the batched form of dynamic filtering. The old mesh
    path fetched per-key bounds eagerly, dispatching one tiny
    cross-module all-reduce per probe; those independent rendezvous
    deadlock intermittently on the virtual-device runtime (TPC-DS q77).
    Here every key's (min, -max) rides ONE all_gather in the SAME
    program as the join, so there is no mid-execution rendezvous to
    miss. The sign flip is the line-102 idiom above: min(-x) = -max(x),
    one local reduce shape serves both bounds through the sum-only /
    all_gather collective contract."""
    imax = jnp.iinfo(jnp.int64).max
    stats = []
    for bk_i in build_keys:
        col = build.columns[bk_i]
        m = build.live & col.valid
        d = col.data.astype(jnp.int64)
        stats.append(jnp.min(jnp.where(m, d, imax)))
        stats.append(jnp.min(jnp.where(m, -d, imax)))
    gathered = lax.all_gather(jnp.stack(stats), axis)   # [n_shards, 2K]
    merged = jnp.min(gathered, axis=0)
    kmins = merged[0::2]
    kmaxs = -merged[1::2]
    return kmins, kmaxs


def apply_filter_bounds(probe: Batch, probe_keys: Tuple[int, ...],
                        kmins, kmaxs) -> Tuple[Batch, jax.Array]:
    """Prune probe rows whose key falls outside the build's [min, max]
    (per key pair, all inside the stage program). Returns the filtered
    batch and the local pruned-row count (caller psums it into the
    dynamic_filter_rows_pruned metric). NULL keys stay live — they are
    dropped by join semantics, not by the filter."""
    keep = probe.live
    for j, pk_i in enumerate(probe_keys):
        col = probe.columns[pk_i]
        d = col.data.astype(jnp.int64)
        keep = keep & (~col.valid | ((d >= kmins[j]) & (d <= kmaxs[j])))
    pruned = jnp.sum(probe.live, dtype=jnp.int64) - \
        jnp.sum(keep, dtype=jnp.int64)
    return probe.with_live(keep), pruned


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
