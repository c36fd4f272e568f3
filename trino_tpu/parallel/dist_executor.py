"""Distributed plan executor: any logical plan over the device mesh.

Reference: the coordinator's planDistribution + worker task execution
(SqlQueryExecution.java:517, SURVEY.md §3.3) — a fragmented plan runs as
tasks on every worker, exchanging pages. TPU-native redesign (the
"How to Scale Your Model" recipe): keep the SINGLE global array program the
local executor already runs, place scan batches row-sharded over the mesh
(`NamedSharding(mesh, P('workers'))`), and let XLA's SPMD partitioner
insert the collectives a Trino cluster does by hand:

- masked group reductions    -> cross-shard psum      (= PARTIAL->FINAL agg)
- lax.sort for sort-groupby  -> distributed sort      (= hash repartition)
- join gathers               -> all_gather/all_to_all (= broadcast/
                                                         partitioned join)

The logical plan needs NO distributed rewrite: sharding is layout, not
semantics. Hand-tuned shard_map stage programs (parallel/stages.py) remain
the fast path for hot shapes; this executor is the general one — every SQL
feature the local executor supports runs distributed unchanged.

Join distribution (DetermineJoinDistributionType's choice, on the mesh):
the planner stamps JoinNode.distribution from build-size stats; BROADCAST
joins run the replicated default path below (XLA reads the build from
every shard), PARTITIONED joins hash-repartition both sides over the mesh
and run the VMEM hash kernel per shard
(parallel/stages.partitioned_hash_join_step) — each chip owns 1/N of the
key space. Skewed or duplicate-key partitions degrade exactly like the
single-chip hybrid join (host equi-join / expansion fallback).

Scheduling note: one process drives the whole mesh (single-controller JAX),
so the coordinator/worker HTTP runtime (server/) carries control-plane
semantics (states, liveness, retries) while data-plane parallelism lives
in XLA collectives over ICI. That division is the core architectural
difference from the reference's page-shuttling workers.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..batch import Batch, bucket_capacity, pad_capacity
from ..catalog import Catalog
from ..exec.executor import Executor, compact_batch
from ..exec.profiler import recorded_jit
from ..planner import logical as L
from .mesh import AXIS, make_mesh, pad_to_multiple


@recorded_jit(static_argnums=(2, 3))
def _batched_dynamic_filter(probe: Batch, build: Batch,
                            probe_keys: tuple, build_keys: tuple):
    """ALL of one join's dynamic-filter bounds, mask, and pruned count
    in ONE jitted program. Over sharded operands GSPMD lowers the
    reductions into a single XLA module, so the mesh pays exactly one
    collective rendezvous per join — the structural fix for the old
    eager path, which dispatched one tiny cross-module all-reduce per
    bound and intermittently deadlocked the virtual-device runtime
    (rendezvous.cc "only 7 of 8 arrived", TPC-DS q77). Semantics match
    Executor.apply_dynamic_filter bit for bit."""
    keep = probe.live
    for pk_i, bk_i in zip(probe_keys, build_keys):
        bk = build.columns[bk_i]
        m = build.live & bk.valid
        info = jnp.iinfo(bk.data.dtype)
        kmin = jnp.min(jnp.where(m, bk.data, info.max))
        kmax = jnp.max(jnp.where(m, bk.data, info.min))
        pk = probe.columns[pk_i]
        keep = keep & pk.valid & (pk.data >= kmin) & (pk.data <= kmax)
    pruned = jnp.sum(probe.live, dtype=jnp.int64) - \
        jnp.sum(keep, dtype=jnp.int64)
    return keep, pruned


class MeshExecutor(Executor):
    """Executor whose scans land row-sharded on the mesh. Every operator
    kernel (already jitted) then runs as an SPMD program; XLA propagates
    shardings through the plan and inserts ICI collectives where global
    semantics require them."""

    # repartitioning doubles a side n_shards x during the exchange
    # (parallel/exchange.py's static bucket layout); above this estimate
    # the partitioned path would trade the gather win for an HBM cliff,
    # so the gate degrades to broadcast
    MESH_EXCHANGE_BUDGET_BYTES = 8 << 30

    def __init__(self, catalog: Catalog, mesh: Optional[Mesh] = None):
        super().__init__(catalog)
        self.mesh = mesh if mesh is not None else make_mesh()
        self.n_shards = self.mesh.devices.size
        # rows shard over every mesh axis (a 2-D hosts x chips mesh keeps
        # the inner collectives on ICI — see mesh.make_mesh_2d)
        self._row_sharding = NamedSharding(
            self.mesh, P(tuple(self.mesh.axis_names)))
        # Dynamic filtering used to be hard-pinned OFF here (a set-proof
        # property): its eager per-probe min/max over SHARDED build
        # columns dispatched a tiny cross-module all-reduce per bound,
        # and those independent rendezvous intermittently deadlocked the
        # virtual-CPU-device runtime (rendezvous.cc "only 7 of 8
        # arrived", deterministic on TPC-DS q77). The batched design
        # (_batched_dynamic_filter + join_filter_bounds inside
        # partitioned_hash_join_step) folds every filter collective into
        # the operator's own program, so that deadlock class cannot
        # occur; this flag remains as the session escape hatch
        # (mesh_dynamic_filtering=off).
        self.mesh_dynamic_filtering = True
        # compiled partitioned-join stage programs, keyed by static shape
        self._partitioned_steps: dict = {}

    def _decision_salt(self) -> tuple:
        # mesh knobs change decision values for the same plan structure
        # (the pruned-row count flips with the filter hatch; dup/escape
        # totals depend on the shard fanout)
        return super()._decision_salt() + (self.n_shards,
                                           self.mesh_dynamic_filtering)

    def _shard_batch(self, batch: Batch) -> Batch:
        """Row-shard a batch over the mesh (no-op for batches already
        laid out this way), padding odd capacities with dead rows."""
        batch = pad_to_multiple(batch, self.n_shards)
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, self._row_sharding), batch)

    def _scan_capacity(self, rows: int) -> int:
        cap = super()._scan_capacity(rows)
        if cap % self.n_shards != 0:
            # odd capacity (mesh size does not divide the 1024-row
            # buckets): pad with dead rows to the next shard multiple
            # instead of silently staying single-device — the live mask
            # keeps padding invisible to every kernel
            cap = pad_capacity(cap, self.n_shards * 8)
        return cap

    def _place(self, host):
        # the resident copy of a table IS the row-sharded placement
        return jax.device_put(host, self._row_sharding)

    # -- dynamic filtering (batched collectives) -----------------------

    def apply_dynamic_filter(self, node: L.JoinNode, probe: Batch,
                             build: Batch) -> Batch:
        if not (self.enable_dynamic_filtering and
                self.mesh_dynamic_filtering):
            return probe
        if node.kind in ("anti", "left", "mark") or node.null_aware:
            return probe
        pairs = tuple(
            (pk, bk)
            for pk, bk in zip(node.left_keys, node.right_keys)
            if jnp.issubdtype(build.columns[bk].data.dtype, jnp.integer)
            and jnp.issubdtype(probe.columns[pk].data.dtype, jnp.integer))
        if not pairs:
            return probe
        keep, pruned = _batched_dynamic_filter(
            probe, build, tuple(p for p, _ in pairs),
            tuple(b for _, b in pairs))
        probe = probe.with_live(keep)
        pruned = self.fetch_ints(node, "dfpruned", pruned)[0]
        if pruned:
            self._note_pruned(pruned)
        if probe.capacity >= (1 << 16) and not self.chunk_mode:
            live = self.fetch_ints(node, "dflive",
                                   jnp.sum(probe.live))[0]
            new_cap = bucket_capacity(live)
            if new_cap * 4 <= probe.capacity:
                self.stats.dynamic_filter_compactions += 1
                probe = compact_batch(probe, new_cap)
        return probe

    def _note_pruned(self, pruned: int) -> None:
        from ..metrics import DYNAMIC_FILTER_ROWS_PRUNED
        self.stats.dynamic_filter_rows_pruned += pruned
        DYNAMIC_FILTER_ROWS_PRUNED.inc(pruned)

    # -- join distribution (broadcast vs partitioned) ------------------

    def run_multijoin(self, node):
        # The fused star kernel assumes a single-device VMEM-resident
        # build set; on a mesh the pairwise ladder keeps the
        # partitioned/broadcast machinery per hop instead.
        self._note_multijoin_degrade("mesh", len(node.dims))
        return self._run_multijoin_ladder(node)

    def _run_join_inner(self, node: L.JoinNode, probe: Batch,
                        build: Batch) -> Batch:
        mode = "partitioned" if self._partitioned_eligible(
            node, probe, build) else "broadcast"
        from ..metrics import JOIN_DISTRIBUTION_DECISIONS
        JOIN_DISTRIBUTION_DECISIONS.inc(mode=mode)
        self.strategy_decisions["JoinDistribution"] = mode
        if mode == "partitioned":
            out = self._mesh_partitioned_join(node, probe, build)
            if out is not None:
                return out
            # dup build keys or an unjoinable degrade: the replicated
            # ladder below handles it (expansion path included)
            self.strategy_decisions["JoinDistribution"] = "broadcast"
        return super()._run_join_inner(node, probe, build)

    def _partitioned_eligible(self, node: L.JoinNode, probe: Batch,
                              build: Batch) -> bool:
        """May this join hash-repartition over the mesh? The planner's
        stats gate asks for it (JoinNode.distribution, estimated build
        bytes vs broadcast_join_threshold_mb); the executor additionally
        requires the shape the per-shard kernel supports. Everything
        else broadcasts — that is today's replicated path, always
        correct."""
        if self.n_shards <= 1:
            return False
        if getattr(node, "distribution", "auto") != "partitioned":
            return False
        if node.kind != "inner" or node.residual is not None or \
                node.null_aware:
            return False
        if len(node.left_keys) != 1:
            # multi-key joins arrive here single-keyed via the packed
            # key column (Executor.pack_join_keys); a genuinely
            # multi-key shape cannot co-partition on one hash
            return False
        if self.hash_mode() == "off":
            return False
        for side, keys in ((probe, node.left_keys),
                           (build, node.right_keys)):
            for k in keys:
                if not jnp.issubdtype(side.columns[k].data.dtype,
                                      jnp.integer):
                    return False
        n_cols = len(probe.columns) + len(build.columns) + 2
        est = (probe.capacity + build.capacity) * self.n_shards * \
            8 * n_cols
        if est > self.MESH_EXCHANGE_BUDGET_BYTES:
            return False
        return True

    def _mesh_partitioned_join(self, node: L.JoinNode, probe: Batch,
                               build: Batch) -> Optional[Batch]:
        """The tentpole path: hash-repartition both sides over the mesh
        (splitmix64 fanout, all_to_all) and run the VMEM hash join
        per shard, with the dynamic-filter collectives batched into the
        same program. Returns None when the build broke the unique-key
        contract (caller expands on the replicated path)."""
        from ..metrics import MESH_REPARTITION_BYTES
        from ..ops import pallas_hash as ph
        from .stages import partitioned_hash_join_step
        n = self.n_shards
        probe = pad_to_multiple(probe, n)
        build = pad_to_multiple(build, n)
        # per-shard table sized for the 1/N key slice with 2x slack:
        # heavier skew escapes at runtime and degrades below, exactly
        # like a single-chip table overflow
        slots, _ = ph.join_table_slots(
            max(ph.MIN_TABLE_SLOTS, 2 * build.capacity // n))
        df = bool(self.enable_dynamic_filtering and
                  self.mesh_dynamic_filtering)
        skey = (n, node.left_keys, node.right_keys, node.kind, slots,
                probe.capacity, build.capacity, self.hash_mode(),
                self.gather_mode(), df)
        step = self._partitioned_steps.get(skey)
        if step is None:
            step = partitioned_hash_join_step(
                self.mesh, n, node.left_keys, node.right_keys,
                node.kind, slots, self.hash_mode(), self.gather_mode(),
                dynamic_filter=df)
            self._partitioned_steps[skey] = step
        out, dup, esc, pruned = step(self._shard_batch(probe),
                                     self._shard_batch(build))
        # exchange accounting (static estimate: each side moves its full
        # padded capacity once, data + valid + live planes)
        MESH_REPARTITION_BYTES.inc(
            probe.capacity * (len(probe.columns) * 9 + 1) +
            build.capacity * (len(build.columns) * 9 + 1))
        self.stats.hash_join_calls += 1
        self.stats.mesh_partitioned_joins += 1
        dup, esc, pruned = self.fetch_ints(
            node, f"meshjoin{slots}", dup, esc, pruned)
        if pruned:
            self._note_pruned(pruned)
        if esc > 0:
            # skewed partition overflowed its shard table: degrade to
            # the host equi-join over the same splitmix64 fanout (the
            # single-chip hybrid join's graceful path)
            self.stats.hash_join_escapes += 1
            host = self._partitioned_hash_join(node, probe, build)
            if host is None:
                return None
            self._note_strategy("JoinNode", "hybrid-hash", "join")
            return host
        if dup > 0:
            return None
        self._note_strategy("JoinNode", "hybrid-hash", "join")
        # the repartitioned output rides at n_shards x probe capacity
        # (the exchange's static bucket layout): compact by the fused
        # live count before anything downstream pays for the padding
        live = self.fetch_ints(node, "meshjoinlive",
                               jnp.sum(out.live))[0]
        return self.maybe_compact(out, live=live)
