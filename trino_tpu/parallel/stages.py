"""Distributed stage programs: whole plan fragments as SPMD programs.

Reference: a Trino PlanFragment runs as N tasks exchanging pages
(PlanFragmenter.java:126, SURVEY.md §3.3); here a fragment is ONE jitted
`shard_map` program over the mesh — scan shards play the role of tasks,
collectives play the exchanges. XLA sees the whole stage (scan -> filter ->
project -> repartition -> join -> partial agg -> merge) and fuses across
operator boundaries, which is the reference's PageProcessor codegen +
exchange serde collapsed into one compile.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import ir
from ..batch import Batch
from ..ops.aggregate import direct_group_aggregate
from ..ops.join import join_unique_build
from ..ops.project import apply_filter, project
from .exchange import merge_partial_states, repartition_by_key
from .mesh import AXIS, shard_map


def sharded_agg_step(mesh, filter_expr, pre_exprs, key_indices: tuple,
                     domains: tuple, aggs: tuple):
    """Distributed GROUP BY (q1 shape): per-shard filter/project/partial
    aggregate, then collective merge. The dense direct-strategy table makes
    the merge a pure psum/pmin/pmax — no key exchange at all (every shard
    shares the same group-id space), which is strictly cheaper than the
    reference's hash repartition between PARTIAL and FINAL."""
    agg_funcs = tuple(a.func for a in aggs)
    n_keys = len(key_indices)

    def body(local: Batch) -> Batch:
        if filter_expr is not None:
            local = apply_filter(local, filter_expr)
        if pre_exprs is not None:
            local = project(local, pre_exprs)
        partial = direct_group_aggregate(local, key_indices, domains, aggs)
        return merge_partial_states(partial, agg_funcs, n_keys)

    mapped = shard_map(body, mesh=mesh, in_specs=(P(AXIS),),
                       out_specs=P())
    return jax.jit(mapped)


def sharded_join_agg_step(mesh, n_shards: int,
                          probe_filter, probe_key: int,
                          build_filter, build_key: int,
                          post_exprs, agg_keys: tuple, domains: tuple,
                          aggs: tuple):
    """Distributed equi-join + aggregation (q3/q5 shape):

    probe shards --filter--> all_to_all(hash(key))    [PartitionedOutput]
    build shards --filter--> all_to_all(hash(key))    [+ExchangeOperator]
    -> co-partitioned local joins (build stays unique per partition,
       since hash partitioning sends all rows of one key to one shard)
    -> post-project -> partial dense aggregate -> psum merge [FINAL agg]
    """
    agg_funcs = tuple(a.func for a in aggs)
    n_keys = len(agg_keys)

    def body(probe: Batch, build: Batch):
        if probe_filter is not None:
            probe = apply_filter(probe, probe_filter)
        if build_filter is not None:
            build = apply_filter(build, build_filter)
        probe = repartition_by_key(probe, probe_key, n_shards)
        build = repartition_by_key(build, build_key, n_shards)
        joined, dup = join_unique_build(probe, build, (probe_key,),
                                        (build_key,), "inner")
        if post_exprs is not None:
            joined = project(joined, post_exprs)
        partial = direct_group_aggregate(joined, agg_keys, domains, aggs)
        # surface build-key duplicates: hash partitioning co-locates all
        # rows of a key, so a duplicate would silently drop join rows —
        # the caller must check total_dups == 0 and fall back to the
        # general expansion path (MeshExecutor) otherwise
        total_dups = jax.lax.psum(dup, AXIS)
        return merge_partial_states(partial, agg_funcs, n_keys), total_dups

    mapped = shard_map(body, mesh=mesh,
                       in_specs=(P(AXIS), P(AXIS)),
                       out_specs=(P(), P()))
    return jax.jit(mapped)


def broadcast_join_step(mesh, probe_filter, probe_keys: tuple,
                        build_keys: tuple, post_exprs):
    """Broadcast-build join (DetermineJoinDistributionType's REPLICATED
    choice): build side replicated, probe stays sharded, no exchange on the
    probe — output remains row-sharded for downstream stages."""

    def body(probe: Batch, build: Batch) -> Batch:
        if probe_filter is not None:
            probe = apply_filter(probe, probe_filter)
        joined, _dup = join_unique_build(probe, build, probe_keys,
                                         build_keys, "inner")
        if post_exprs is not None:
            joined = project(joined, post_exprs)
        return joined

    mapped = shard_map(body, mesh=mesh, in_specs=(P(AXIS), P()),
                       out_specs=P(AXIS))
    return jax.jit(mapped)
