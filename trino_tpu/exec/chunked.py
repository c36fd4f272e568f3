"""Bounded-memory plan execution: the big table streams in chunks.

Reference: Trino's spill tier — SpillableHashAggregationBuilder merges
partial aggregation states spilled to disk, and the spilling join processes
partitions one at a time (operator/aggregation/builder/
SpillableHashAggregationBuilder.java, operator/join/PartitionedConsumption.java,
spiller/FileSingleStreamSpiller.java:59), triggered by memory watermarks
(execution/MemoryRevokingScheduler.java:47).

TPU redesign: host RAM is the spill tier and the *scan* is the spill
boundary. The plan's largest table (the fact table: every TPC-H/DS query has
one) never materializes on device; it streams through the compiled pipeline
in fixed-size chunks:

    for chunk in fact_table:            # host -> device, bounded HBM
        partial = run(plan_path(chunk)) # filter/project/joins/partial agg,
                                        # one jitted pipeline, reused trace
    merged = re_aggregate(concat(partials))   # MERGE step
    result = run(rest_of_plan, merged)

Join build sides (dimension tables) are computed once and pinned for the
whole loop — the analog of Trino's build-side LookupSource living across
probe pages. Chunks all share one padded capacity, so the whole loop hits
one XLA compilation.

Shapes handled: any Filter/Project/Join(probe-side)/Aggregate path above
the driver scan. The merge point is the first aggregate above the scan
(partial states merge by re-aggregation, Trino's PARTIAL->FINAL split) or
the plan root (outputs concatenate on host). Paths containing Sort/Window/
SetOp below the merge point, distinct aggregates, or the driver on a join
BUILD side fall back to single-shot execution.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..batch import (Batch, Column, batch_from_numpy, batch_to_numpy,
                     bucket_capacity)
from ..planner import logical as L
from .prefetch import PrefetchPipeline
from .profiler import instrument, recorded_jit


@recorded_jit(static_argnums=(0, 1), site="exec.slice_widen")
def _slice_widen(cap: int, wide_names: tuple, datas, valids,
                 start, end, num_rows):
    """Slice one chunk straight from device-resident narrowed columns
    (exec/device_cache.py): dynamic_slice + widen to the engine's lane
    dtype + live mask. The slice offset clamps so the last (short) chunk
    re-reads the tail of the previous one, with the live mask excluding
    the overlap — every chunk shares ONE trace and never touches the
    host link."""
    idx = jnp.arange(cap, dtype=jnp.int64)
    s0 = jnp.clip(start, 0, jnp.maximum(num_rows - cap, 0))
    cols = []
    for a, v, wn in zip(datas, valids, wide_names):
        sl = jax.lax.dynamic_slice(a, (s0,), (cap,))
        data = sl if str(sl.dtype) == wn else sl.astype(jnp.dtype(wn))
        valid = jnp.ones(cap, jnp.bool_) if v is None else \
            jax.lax.dynamic_slice(v, (s0,), (cap,))
        cols.append(Column(data, valid))
    live = ((s0 + idx) >= start) & ((s0 + idx) < end)
    return Batch(tuple(cols), live)

# partial-state merge functions (HashAggregationOperator's
# intermediate-state combine): min/max idempotent, sums/counts add
MERGE_FUNC = {"sum": "sum", "count": "sum", "count_star": "sum",
              "min": "min", "max": "max"}


def _fused_join_ok(node: L.JoinNode) -> bool:
    return (node.kind in ("inner", "left", "semi", "anti") and
            node.build_key_domain is not None and node.build_unique and
            node.residual is None and not node.null_aware and
            len(node.left_keys) == 1)


def _spine_joins(target: L.PlanNode, driver: L.ScanNode) \
        -> Optional[List[L.JoinNode]]:
    """JoinNodes on the driver's probe spine, bottom-up (the order
    compile_fused_chunk's emit() appends them). None when any spine
    join can't run in the fused pipeline."""
    joins: List[L.JoinNode] = []

    def walk(node) -> bool:
        if node is driver:
            return True
        if isinstance(node, (L.FilterNode, L.ProjectNode,
                             L.AggregateNode)):
            return walk(node.child)
        if isinstance(node, L.JoinNode):
            if _fused_join_ok(node) and walk(node.left):
                joins.append(node)
                return True
            return False
        return False

    return joins if walk(target) else None


def compile_fused_chunk(executor, target: L.PlanNode,
                        driver: L.ScanNode, lut_specs=None, adapt=None):
    """Compose the whole per-chunk path (joins with prebuilt LUTs,
    filters, projections, the partial aggregate) into ONE traced
    function so every chunk is a single device dispatch with zero host
    syncs and no per-operator intermediate materialization — XLA fuses
    across what the per-node executor would run as 6-8 separate
    programs. Supported shape: Filter/Project chains, single-key
    unique-build dense joins (driver on the probe side), and a
    direct/global partial aggregate on top.

    `lut_specs` maps id(join node) -> spec from _fused_luts: ("rows",)
    joins gather per payload column off a row-id LUT; ("packed", meta,
    bkey, out_dtypes) joins decode everything from ONE
    value-packed gather, and their entry of `luts` is the pair (LUT,
    the word's offsets `los`): the spec holds the schema's statics, the
    operands what follows the data.

    `adapt` applies a previous run's measurements (AdaptivePlanner.java:87's
    role, replayed through the cross-run decision cache): {join_idx: W}
    probes a packed join through a W-sized LUT window (near-sorted
    keys). A window is a guess that new data may invalidate, so the
    program reports the escaped rows and per-join key spans in a stats
    vector the DRIVER must verify (nonzero escaped => rerun the plain
    program).

    Returns (fn, join_nodes) where fn(chunk, builds, luts) ->
    (partial Batch, stats int64[1 + n_joins]); stats layout:
    [escaped_total, span_0, span_1, ...].
    None when the shape doesn't apply (caller uses the per-node loop)."""
    from ..ops.aggregate import (AggSpec, direct_group_aggregate,
                                 global_aggregate)
    from ..ops.join import (dense_join_packed, dense_join_packed_windowed,
                            dense_join_with_lut)
    from ..ops.project import apply_filter, filter_project

    joins: List[L.JoinNode] = []
    windows = adapt or {}

    def emit(node):
        """Returns f(chunk, builds, luts) -> (Batch, stats dict) or
        None. stats: {"escaped": scalar, "spans": [...]}."""
        if node is driver:
            return lambda chunk, builds, luts: (chunk, {
                "escaped": jnp.int64(0), "spans": []})
        if isinstance(node, L.FilterNode):
            child = emit(node.child)
            if child is None:
                return None
            pred = executor.fold_scalars(node.predicate)

            def run_filter(chunk, b, l, _child=child, _pred=pred):
                bt, st = _child(chunk, b, l)
                return apply_filter(bt, _pred), st
            return run_filter
        if isinstance(node, L.ProjectNode):
            child = emit(node.child)
            if child is None:
                return None
            exprs = executor.fold_scalars_tuple(node.exprs)

            def run_project(chunk, b, l, _child=child, _exprs=exprs):
                bt, st = _child(chunk, b, l)
                return filter_project(bt, None, None, _exprs), st
            return run_project
        if isinstance(node, L.JoinNode):
            if not _fused_join_ok(node):
                return None
            child = emit(node.left)
            if child is None:
                return None
            idx = len(joins)
            joins.append(node)
            lk, rk, kind = node.left_keys, node.right_keys, node.kind
            spec = lut_specs.get(id(node)) if lut_specs else None
            window = windows.get(idx)

            def run_join(chunk, b, l, _child=child, _idx=idx,
                         _lk=lk, _rk=rk, _kind=kind, _spec=spec,
                         _win=window):
                bt, st = _child(chunk, b, l)
                esc = jnp.int64(0)
                if _spec is not None and _spec[0] == "packed":
                    _, meta, bkey, out_dtypes = _spec
                    lut, los = l[_idx]
                    if _win is not None:
                        out, esc, span = dense_join_packed_windowed(
                            bt, lut, los, _lk, meta, bkey, out_dtypes,
                            _kind, _win)
                    else:
                        out = dense_join_packed(
                            bt, lut, los, _lk, meta, bkey, out_dtypes,
                            _kind)
                        span = _key_span(bt, _lk)
                else:
                    out = dense_join_with_lut(bt, b[_idx], l[_idx], _lk,
                                              _rk, _kind)
                    span = _key_span(bt, _lk)
                return out, {"escaped": st["escaped"] + esc,
                             "spans": st["spans"] + [span]}
            return run_join
        if isinstance(node, L.AggregateNode):
            child = emit(node.child)
            if child is None:
                return None
            if any(a.distinct for a in node.aggs):
                return None
            aggs = tuple(AggSpec(a.func, a.arg.index
                                 if a.arg is not None else None)
                         for a in node.aggs)
            if node.strategy == "global":
                def run_gagg(chunk, b, l, _child=child, _aggs=aggs):
                    bt, st = _child(chunk, b, l)
                    return global_aggregate(bt, _aggs), st
                return run_gagg
            if node.strategy == "direct":
                keys, domains = node.group_keys, node.key_domains

                def run_dagg(chunk, b, l, _child=child, _aggs=aggs,
                             _keys=keys, _domains=domains):
                    bt, st = _child(chunk, b, l)
                    return direct_group_aggregate(
                        bt, _keys, _domains, _aggs), st
                return run_dagg
            return None
        return None

    inner = emit(target)
    if inner is None:
        return None

    def fn(chunk, builds, luts):
        out, st = inner(chunk, builds, luts)
        return out, jnp.stack([st["escaped"]] + st["spans"])
    return fn, joins


def _key_span(batch: Batch, keys: tuple):
    """Probe-key extent of live rows (windowing measurement).

    Measured over the COMBINED packed key — the same key the windowed
    probe (dense_join_packed_windowed) slices by. Measuring keys[0]
    alone underestimated multi-key packed joins by ~2^32 per trailing
    column, so the adapted window always escaped: every run compiled
    the adapted program, failed verification, dropped the record, reran
    plain, and re-recorded the same bad span — a permanent ~2x
    device-work cycle (ADVICE round-5 low)."""
    from ..ops.join import _combined_key
    key, valid = _combined_key(batch, keys)
    ok = batch.live & valid
    big = jnp.int64(1) << 62
    lo = jnp.min(jnp.where(ok, key, big))
    hi = jnp.max(jnp.where(ok, key, -big))
    return jnp.maximum(hi - lo + 1, 0)


def _fused_luts(executor, joins) -> Optional[tuple]:
    """Build + validate the dense LUT for every fused join, choosing
    value-packed LUTs whenever the payload fits one word (probe = ONE
    gather) and falling back to row-id LUTs otherwise. LUT+spec pairs
    reuse the cross-run cache for deterministic builds; their stats and
    dup/oob validations ride the persistent decision cache (sync-free on
    replay). Uncacheable builds fuse all stats into one device fetch and
    all validations into a second. Any violation aborts the fused path
    (the per-node loop has the graceful fallbacks)."""
    from ..ops.join import (dense_build_lut, dense_build_packed_lut,
                            pack_refusal, packed_word_dtype,
                            payload_ranges, plan_packed_word)
    n = len(joins)
    builds = [executor.run(j.right) for j in joins]
    luts: List[object] = [None] * n
    specs: List[object] = [None] * n
    fresh: List[int] = []
    keys: List[object] = [None] * n
    for k, node in enumerate(joins):
        keys[k] = executor.build_structure_key(node.right)
        hit = executor._lut_cache.get((keys[k], node.build_key_domain)) \
            if keys[k] is not None else None
        if hit is not None:
            luts[k], specs[k] = hit
        else:
            fresh.append(k)
    if fresh:
        # min/max of integer payload columns (packing layouts are
        # host-side statics). Cacheable builds (deterministic catalogs)
        # fetch per build through the cross-run decision cache — the tag
        # carries right_keys/kind/domain because the SAME build subtree
        # joined on a different key has different stats layout and
        # validation semantics; the structure hash alone covers only
        # j.right. A FRESH process then replays with zero device syncs.
        # Uncacheable builds keep the old behavior: ALL their stats fuse
        # into one fetch and all their validations into a second.
        def build_one(k, mins, maxs):
            """Build LUT k; returns (dup_signal, oob) device scalars."""
            b, j = builds[k], joins[k]
            if j.kind in ("semi", "anti"):
                # presence bit only
                plan = ((), np.zeros(0, np.int64), 1)
            elif pack_refusal(b, j.right_keys) is None:
                plan = plan_packed_word(b, j.right_keys[0], mins, maxs)
            else:
                plan = None
            if plan is not None:
                meta, los, bits = plan
                wd = packed_word_dtype(bits)
                los = executor._place(los)
                lut, exp, oob, occ = dense_build_packed_lut(
                    b, j.right_keys, j.build_key_domain, meta, wd, los)
                lut = (lut, los)
                specs[k] = ("packed", meta, j.right_keys[0],
                            tuple(str(c.data.dtype) for c in b.columns))
                dup_sig = exp - occ           # >0 = duplicate keys
            else:
                lut, dup, oob = dense_build_lut(b, j.right_keys,
                                                j.build_key_domain)
                specs[k] = ("rows",)
                dup_sig = dup.astype(jnp.int64)
            luts[k] = lut
            return dup_sig, oob

        def join_tag(base, j):
            return (f"{base}:{tuple(j.right_keys)}:{j.kind}:"
                    f"{j.build_key_domain}")

        cacheable = [k for k in fresh if keys[k] is not None]
        fused_rest = [k for k in fresh if keys[k] is None]
        for k in cacheable:
            j = joins[k]
            vals = np.asarray(executor.fetch_ints(
                j.right, join_tag("fusedminmax", j),
                payload_ranges(builds[k], j.right_keys)), dtype=np.int64)
            dup_sig, oob = build_one(k, vals[0::2], vals[1::2])
            check = executor.fetch_ints(
                j.right, join_tag("fusedlutcheck", j), dup_sig, oob)
            if check[0] != 0 or check[1] != 0:
                return None
        if fused_rest:
            all_parts = [payload_ranges(builds[k], joins[k].right_keys)
                         for k in fused_rest]
            vals = np.asarray(jnp.concatenate(all_parts))
            pos, checks = 0, []
            for k, ps in zip(fused_rest, all_parts):
                vk = vals[pos:pos + len(ps)]
                pos += len(ps)
                checks.extend(build_one(k, vk[0::2], vk[1::2]))
            if int(np.asarray(jnp.stack(checks)).sum()) != 0:
                return None
        for k in fresh:
            if keys[k] is not None:
                if len(executor._lut_cache) >= 4:
                    executor._lut_cache.pop(
                        next(iter(executor._lut_cache)))
                executor._lut_cache[(keys[k], joins[k].build_key_domain)] \
                    = (luts[k], specs[k])
    return tuple(builds), tuple(luts), tuple(specs)


# adaptive re-optimization safety margin: windows pad the measured
# maxima so ordinary chunk-to-chunk variance doesn't trip the rerun
# path; real data changes still do (and then re-measure)
_ADAPT_MARGIN = 1.25


def _fused_adaptation(executor, skey, spine, specs):
    """Build the `adapt` argument for compile_fused_chunk from a
    previous run's recorded measurements (cross-run decision cache):
    window sizes for packed joins with near-sorted probe keys. None on
    the first-ever run (the plain program measures)."""
    from ..batch import bucket_capacity
    if skey is None:
        return None
    if not executor._decision_loaded:
        executor._load_decisions()
    rec = executor._decision_cache.get(
        ("fusedadapt", skey, executor._decision_salt()))
    if rec is None or len(rec) != len(spine):
        return None
    windows = {}
    for i, j in enumerate(spine):
        span = rec[i]
        domain = j.build_key_domain
        if specs[i] is not None and specs[i][0] == "packed" and \
                span > 0 and domain:
            w = bucket_capacity(int(span * _ADAPT_MARGIN))
            if w * 2 <= domain:      # window must actually shrink reads
                windows[i] = w
    return windows or None


def _verify_record_adaptation(executor, skey, adapt, chunk_stats) -> bool:
    """ONE fetch over the run's stacked per-chunk stats: the escaped
    window rows plus the per-join span maxima. Plain runs record the
    spans for the next run's adaptation; adapted runs verify their
    guesses — False means results are unusable and the caller must
    rerun plain (the stale record is removed so the rerun
    re-measures)."""
    key = ("fusedadapt", skey, executor._decision_salt()) \
        if skey is not None else None
    if adapt is None and (key is None or key in executor._decision_cache):
        return True      # nothing to verify or record: skip the sync
    stk = jnp.stack(chunk_stats)
    vals = np.asarray(jnp.concatenate(
        [jnp.sum(stk[:, :1], axis=0), jnp.max(stk[:, 1:], axis=0)]))
    if int(vals[0]) > 0:
        # stale guesses: drop the record so the rerun runs PLAIN and
        # re-measures
        if key is not None:
            executor._decision_cache.pop(key, None)
            executor._decision_dirty = True
        return False
    if adapt is None and key is not None:
        executor._decision_cache[key] = tuple(int(v) for v in vals[1:])
        executor._decision_dirty = True
    return True


class ChunkAnalysis:
    """Where to cut the plan for chunked execution."""

    def __init__(self, driver: L.ScanNode, merge_agg: Optional[L.AggregateNode],
                 build_roots: List[L.PlanNode], driver_rows: int,
                 merge_sort: Optional["L.SortNode"] = None):
        self.driver = driver
        self.merge_agg = merge_agg          # None = concat at root
        self.build_roots = build_roots      # pinned once, reused per chunk
        self.driver_rows = driver_rows
        # distributed ORDER BY: the fragment's top Sort — per-split
        # outputs are sorted RUNS the consumer merges order-preservingly
        # (MergeOperator.java's role); only the scheduler opts in
        self.merge_sort = merge_sort


def _scan_rows(catalog, node: L.ScanNode) -> int:
    return catalog.get_table(node.catalog, node.schema_name,
                             node.table).num_rows


def analyze(root: L.OutputNode, catalog, chunk_rows: int,
            allow_sort_merge: bool = False) -> Optional[ChunkAnalysis]:
    """Pick the driver scan and validate the path up to the merge point.
    With allow_sort_merge, a Sort directly below the output becomes the
    fragment top: per-split outputs are sorted runs for an
    order-preserving merge (the distributed scheduler's MergeOperator
    path; the local chunked driver keeps its re-sort semantics)."""
    parents: Dict[int, L.PlanNode] = {}

    def walk(node):
        for c in L.children(node):
            parents[id(c)] = node
            walk(c)
    walk(root)

    scans = [n for n in _all_nodes(root) if isinstance(n, L.ScanNode)]
    if not scans:
        return None
    driver = max(scans, key=lambda s: _scan_rows(catalog, s))
    driver_rows = _scan_rows(catalog, driver)
    if driver_rows <= chunk_rows:
        return None

    build_roots: List[L.PlanNode] = []
    merge_agg: Optional[L.AggregateNode] = None
    merge_sort: Optional[L.SortNode] = None
    node: L.PlanNode = driver
    while True:
        parent = parents.get(id(node))
        if parent is None:
            break
        if isinstance(parent, (L.FilterNode, L.ProjectNode)):
            pass
        elif isinstance(parent, L.JoinNode):
            if parent.left is not node:
                return None       # driver on the build side: can't stream
            build_roots.append(parent.right)
        elif isinstance(parent, L.AggregateNode):
            if any(a.distinct for a in parent.aggs):
                return None       # distinct needs global dedup
            if any(a.func not in MERGE_FUNC for a in parent.aggs):
                return None
            merge_agg = parent
            break
        elif isinstance(parent, L.OutputNode):
            break                 # concat mode
        elif allow_sort_merge and isinstance(parent, L.SortNode) and \
                isinstance(parents.get(id(parent)), L.OutputNode):
            merge_sort = parent
            break
        else:
            return None           # Sort/Window/SetOp/Limit below merge point
        node = parent
    return ChunkAnalysis(driver, merge_agg, build_roots, driver_rows,
                         merge_sort=merge_sort)


def _all_nodes(node):
    yield node
    for c in L.children(node):
        yield from _all_nodes(c)


# TRINO_TPU_CHUNK_PROFILE=1 (shared helper in device_cache): per-phase
# walls to stderr, with a blocking sync per chunk so device time
# attributes to its dispatch (diagnostic only — the sync serializes the
# chunk pipeline)
from .device_cache import prof as _prof
from .device_cache import profile_enabled as _profile_enabled


def execute_chunked(executor, root: L.OutputNode) -> Optional[Batch]:
    """Run `root` with the driver scan streamed in chunks. Returns None if
    the plan shape doesn't support chunking (caller falls back)."""
    chunk_rows = executor.spill_chunk_rows
    plan = analyze(root, executor.catalog, chunk_rows)
    if plan is None:
        return None

    # pin join build sides once (HashBuilderOperator builds once, probes
    # stream); scalar subqueries are folded+cached by the executor anyway.
    # Builds of DETERMINISTIC sources additionally persist across runs in
    # a structural-hash cache (the scan cache's policy extended to build
    # subtrees): a repeated chunked query skips minutes of build joins.
    _prof("pin builds: start")
    for b in plan.build_roots:
        if id(b) not in executor._subst:
            executor._subst[id(b)] = executor.run_cached_build(b)
    _prof("pin builds: done")

    data = executor.catalog.get_table(plan.driver.catalog,
                                      plan.driver.schema_name,
                                      plan.driver.table)
    per_chunk_target = plan.merge_agg if plan.merge_agg is not None \
        else root.child

    # spillable partial-aggregation state: device partials hold REVOCABLE
    # reservations; under memory pressure the pool's revocation request
    # moves them to host pages and the merge step re-aggregates
    # partition-wise (exec/spill.PartialState)
    from .spill import PartialState
    partial_state = PartialState(executor) \
        if plan.merge_agg is not None else None
    concat_arrays: List[list] = []
    concat_valids: List[list] = []
    # one shared padded capacity => one jit trace for every chunk
    cap = bucket_capacity(min(chunk_rows, plan.driver_rows))

    # device-resident narrowed fact columns: when the driver scan fits
    # the HBM budget in its narrowest dtypes, chunks slice straight from
    # device memory (steady state never touches the host link)
    fact = None
    if executor.enable_fact_cache and cap <= plan.driver_rows:
        key = (plan.driver.catalog, plan.driver.schema_name,
               plan.driver.table, tuple(plan.driver.column_indices))
        if executor.fact_cache.estimate_bytes(
                data, plan.driver.column_indices) <= \
                executor.fact_cache.max_bytes:
            # the load makes its room in the one resident set: the
            # least recently used scanned columns go first
            fact = executor.fact_cache.load(
                key, data, plan.driver.column_indices,
                persist_ok=plan.driver.catalog in ("tpch", "tpcds",
                                                   "bench"))
    if fact is not None:
        fact_datas = tuple(c.data for c in fact)
        fact_valids = tuple(c.valid for c in fact)
        fact_wide = tuple(str(c.wide_dtype) for c in fact)

    # fused pipeline: the whole per-chunk path as ONE program per chunk
    # (zero host syncs in the loop; LUTs prebuilt + validated once)
    fused = None
    if plan.merge_agg is not None and not executor.profile and \
            plan.merge_agg.strategy in ("global", "direct") and \
            not any(a.distinct for a in plan.merge_agg.aggs):
        # the strategy gate mirrors compile_fused_chunk's emit() support
        # so LUTs are never built (device work + a blocking validation
        # fetch) for a plan the fused compiler would then reject
        spine = _spine_joins(per_chunk_target, plan.driver)
        bl = _fused_luts(executor, spine) if spine is not None else None
        if bl is not None:
            builds, luts, specs = bl
            # one jitted wrapper per (plan structure, packing layout,
            # adaptation), reused across runs so re-executions hit the
            # in-memory trace cache (a replan produces new node objects
            # but identical static values)
            skey = executor.build_structure_key(per_chunk_target)
            adapt = _fused_adaptation(executor, skey, spine, specs)
            ckey = (skey, specs, repr(adapt)) \
                if skey is not None else None
            jitted = executor._fused_cache.get(ckey) \
                if ckey is not None else None
            if jitted is None:
                mine = compile_fused_chunk(
                    executor, per_chunk_target, plan.driver,
                    {id(j): s for j, s in zip(spine, specs)}, adapt)
                if mine is not None:
                    # routed through the compile recorder: the first
                    # chunk call records the actual XLA compile (site
                    # exec.fused_chunk, fingerprint = plan-structure
                    # hash), bumping ExecStats.jit_compiles via the
                    # thread binding — re-used traces count as hits
                    jitted = instrument(jax.jit(mine[0]),
                                        site="exec.fused_chunk",
                                        fingerprint=skey or "adhoc")
                    if ckey is not None:
                        if len(executor._fused_cache) >= 8:
                            executor._fused_cache.pop(
                                next(iter(executor._fused_cache)))
                        executor._fused_cache[ckey] = jitted
            if jitted is not None:
                fused = (jitted, builds, luts, skey, adapt)
                executor.stats.fused_chunk_pipelines += 1
    _prof(f"luts+fused ready (fused={fused is not None}, "
          f"adapt={fused[4] if fused else None}, "
          f"fact={fact is not None})")

    # ---- chunk schedule: zone-map pruning skips whole chunks -------------
    # per_chunk_target contains the residual Filter above the driver scan,
    # so a skipped chunk (provably zero matching rows) contributes nothing
    # in BOTH merge-agg and concat modes — bit-exact with skipping off.
    starts_all = list(range(0, plan.driver_rows, chunk_rows))
    starts_list = starts_all
    if plan.driver.predicate is not None and \
            executor.enable_zone_map_pruning:
        from . import zonemap
        zm = zonemap.zone_map_for(data, executor.zone_map_rows)
        starts_list = [
            s for s in starts_all
            if zonemap.range_may_match(
                zm, plan.driver.predicate, plan.driver.column_indices,
                s, min(chunk_rows, plan.driver_rows - s))]
        if not starts_list:
            # keep one chunk so downstream shapes/merges stay on the
            # ordinary path; its rows die at the residual filter
            starts_list = starts_all[:1]
        skipped = len(starts_all) - len(starts_list)
        if skipped:
            executor.stats.scan_chunks_skipped += skipped
            from ..metrics import SCAN_ZONES_PRUNED
            SCAN_ZONES_PRUNED.inc(skipped)
            executor.strategy_decisions[
                f"TableScan[{plan.driver.table}]"] = \
                f"chunks-skipped:{skipped}/{len(starts_all)}"
            _prof(f"zone maps: {skipped}/{len(starts_all)} chunks skipped")

    def _decode_chunk(start: int) -> Batch:
        arrays = [np.asarray(data.columns[i])
                  [start:start + chunk_rows]
                  for i in plan.driver.column_indices]
        valids = None
        if data.valids is not None:
            valids = [None if data.valids[i] is None else
                      np.asarray(data.valids[i])
                      [start:start + chunk_rows]
                      for i in plan.driver.column_indices]
        return batch_from_numpy(arrays, valids=valids, capacity=cap)

    # ---- prefetch pipeline: overlap host decode+stage with compute -------
    # depth 0 (or a device-resident fact table, which decodes nothing)
    # keeps the serial loop exactly
    depth = int(executor.prefetch_depth or 0) \
        if fact is None and len(starts_list) > 1 else 0
    pipeline = PrefetchPipeline(executor, starts_list, _decode_chunk, depth)

    # ---- compile warm: overlap the fused XLA compile with chunk-0 decode -
    # a zero-row dummy at the shared capacity has the identical trace
    # signature (Batch is an all-array pytree; dtypes come from the real
    # columns), so this warms the very program chunk 0 will call. The
    # output is discarded — bit-exactness is untouched — and the recorder
    # books the compile to the prewarm context so the loop's first call
    # counts as a prewarm hit.
    if fused is not None and depth > 0 and \
            getattr(executor, "prewarm_chunks", False):
        from .profiler import RECORDER

        def _warm_fused():
            try:
                dummy = batch_from_numpy(
                    [np.asarray(data.columns[i])[:0]
                     for i in plan.driver.column_indices],
                    capacity=cap)
                with RECORDER.prewarm_context():
                    jax.block_until_ready(
                        fused[0](dummy, fused[1], fused[2]))
            except Exception:
                pass    # warm is best-effort; the loop compiles anyway

        threading.Thread(target=_warm_fused, name="chunk-warm",
                         daemon=True).start()

    chunk_stats: List[object] = []
    compute_s = 0.0
    t_loop = time.monotonic()
    executor.enter_chunk_mode()
    try:
        for start in starts_list:
            # chunk-boundary cooperative cancel: a terminate()/deadline
            # on a long chunked scan frees the exec lock between chunks
            executor.check_cancel()
            if fact is not None:
                chunk = _slice_widen(
                    cap, fact_wide, fact_datas, fact_valids, start,
                    min(start + chunk_rows, plan.driver_rows),
                    plan.driver_rows)
            else:
                chunk = pipeline.next(start)
            t0 = time.monotonic()
            if fused is not None:
                out, stats_vec = fused[0](chunk, fused[1], fused[2])
                chunk_stats.append(stats_vec)
                if _profile_enabled():
                    jax.block_until_ready(out)
                    _prof(f"chunk@{start} done")
            else:
                executor._subst[id(plan.driver)] = chunk
                executor._subst_opaque.add(id(plan.driver))
                try:
                    out = executor.run(per_chunk_target)
                finally:
                    executor._subst.pop(id(plan.driver), None)
                    executor._subst_opaque.discard(id(plan.driver))
                    # the per-chunk path recomputes these nodes next
                    # iteration; release their reservations now so the
                    # pool reflects only pinned builds + partials
                    executor.release_path_reservations(
                        per_chunk_target, keep=executor._subst)
            executor.stats.agg_spill_chunks += 1
            if fact is not None:
                executor.stats.fact_cache_chunks += 1
            if partial_state is not None:
                partial_state.add(out)
            else:
                arrs, vals = batch_to_numpy(out)
                concat_arrays.append(arrs)
                concat_valids.append(vals)
            compute_s += time.monotonic() - t0
    except BaseException:
        if partial_state is not None:
            partial_state.close()       # drop revocable reservations
        raise
    finally:
        pipeline.close()
        executor.exit_chunk_mode()
        # per-run span attribution for the overlap proof (bench.py
        # --scan-micro compares pipelined wall against the serial run's
        # decode+compute span sum)
        executor.chunk_spans = {
            "chunks": len(starts_list),
            "decode_s": pipeline.decode_s,
            "compute_s": compute_s,
            "wall_s": time.monotonic() - t_loop,
            "prefetched": pipeline.served,
        }

    if plan.merge_agg is None:
        ncols = len(concat_arrays[0])
        arrs = [np.concatenate([c[j] for c in concat_arrays])
                for j in range(ncols)]
        vals = [np.concatenate([c[j] for c in concat_valids])
                for j in range(ncols)]
        merged = batch_from_numpy(arrs, valids=vals)
        # structure-faithful: the concat of all chunks IS root.child's
        # deterministic value, so decisions above it stay cacheable
        executor._subst[id(root.child)] = merged
        try:
            return executor.run(root)
        finally:
            executor._subst.clear()
            executor._subst_opaque.clear()

    if fused is not None and chunk_stats:
        ok = _verify_record_adaptation(executor, fused[3], fused[4],
                                       chunk_stats)
        if not ok:
            # the adaptation's window/capacity guesses were violated by
            # this run's data: results would be wrong — rerun with the
            # plain program (the stale measurement was just invalidated,
            # so the retry does not re-adapt)
            executor.stats.escaped_window_reruns += 1
            partial_state.close()
            _prof("adaptation violated; plain rerun")
            return execute_chunked(executor, root)
    _prof("chunk loop dispatched; merging")
    merged = partial_state.merge(plan.merge_agg)
    # structure-faithful (see concat mode above): decisions above the
    # merge point replay from the cross-run cache
    executor._subst[id(plan.merge_agg)] = merged
    try:
        return executor.run(root)
    finally:
        executor._subst.clear()
        executor._subst_opaque.clear()


# --------------------------------------------------------------------------
# streaming-build join: build sides bigger than device memory
# --------------------------------------------------------------------------

def streaming_build_join(executor, node: L.JoinNode,
                         probe: Batch) -> Optional[Batch]:
    """Inner/semi/anti unique-build join whose BUILD side streams from
    host in chunks (PartitionedConsumption.java's partition-at-a-time
    idea, reshaped for the dense-LUT kernel).

    TPU shape: the LUT is DOMAIN-sized no matter how many build rows
    exist, so the build only ever occupies one chunk of HBM at a time —
    each chunk scatters its global row ids into a persistent LUT. Probe
    lookups then yield global row ids; matched rows compact, and build
    payload columns are gathered HOST-side (numpy fancy-indexing over the
    mmap'd table) at the compacted size, so the full build never
    materializes on device. Requires: single int key with known domain,
    build = Scan or Filter(Scan) (the planner's pruned-scan shape), and a
    planner uniqueness proof. Returns None when the shape doesn't apply
    (caller uses the resident-build path)."""
    import jax.numpy as jnp

    if node.kind not in ("inner", "semi", "anti") or \
            node.build_key_domain is None or not node.build_unique or \
            len(node.right_keys) != 1 or node.residual is not None or \
            node.null_aware:
        return None
    build_root = node.right
    pred = None
    if isinstance(build_root, L.FilterNode):
        pred = executor.fold_scalars(build_root.predicate)
        scan = build_root.child
    else:
        scan = build_root
    if not isinstance(scan, L.ScanNode):
        return None

    data = executor.catalog.get_table(scan.catalog, scan.schema_name,
                                      scan.table)
    chunk_rows = executor.spill_chunk_rows or data.num_rows
    domain = node.build_key_domain
    key_in_scan = node.right_keys[0]

    from ..ops.join import build_lut_chunk
    lut = jnp.full(domain + 1, -1, dtype=jnp.int32)
    cap = bucket_capacity(min(chunk_rows, data.num_rows))
    expected = jnp.zeros((), dtype=jnp.int64)   # in-domain valid build rows
    oob = jnp.zeros((), dtype=jnp.int64)        # valid keys outside domain
    for start in range(0, data.num_rows, chunk_rows):
        arrays = [np.asarray(data.columns[i])[start:start + chunk_rows]
                  for i in scan.column_indices]
        valids = None
        if data.valids is not None:
            valids = [None if data.valids[i] is None else
                      np.asarray(data.valids[i])[start:start + chunk_rows]
                      for i in scan.column_indices]
        chunk = batch_from_numpy(arrays, valids=valids, capacity=cap)
        if pred is not None:
            from ..ops.project import apply_filter
            chunk = apply_filter(chunk, pred)
        lut, n_in, n_oob = build_lut_chunk(lut, chunk, key_in_scan,
                                           domain, start)
        expected = expected + n_in
        oob = oob + n_oob
        executor.stats.agg_spill_chunks += 1

    # Runtime validation of the planner's uniqueness proof: every resident
    # path checks dup/oob and degrades gracefully; mirror that here. A
    # duplicate build key would silently keep only the max row id, and an
    # out-of-domain key would be clipped into a real slot — both produce
    # wrong answers, so fall back to the resident-build path instead.
    # (occupied-slot counting avoids a second domain-sized count array:
    # dup rows exist iff scattered rows exceed occupied slots.)
    occupied = jnp.sum((lut[:domain] >= 0).astype(jnp.int64))
    expected_h, oob_h, occupied_h = (int(x) for x in
                                     np.asarray(jnp.stack(
                                         (expected, oob, occupied))))
    if oob_h > 0 or occupied_h != expected_h:
        return None

    # probe: global row ids out of the LUT
    pk = probe.columns[node.left_keys[0]]
    p_idx = jnp.where(pk.valid, jnp.clip(pk.data, 0, domain - 1), domain)
    src = lut[p_idx]
    matched = (src >= 0) & pk.valid & probe.live & \
        (pk.data >= 0) & (pk.data < domain)
    if node.kind == "semi":
        return probe.with_live(probe.live & matched)
    if node.kind == "anti":
        return probe.with_live(probe.live & ~matched)

    live = int(jnp.sum(matched))
    new_cap = bucket_capacity(live)
    from .executor import _compact_gather
    probe_plus = Batch(probe.columns + (Column(
        src, matched),), probe.live & matched)
    compacted = _compact_gather(probe_plus, new_cap)
    src_host = np.asarray(compacted.columns[-1].data)
    src_ok = np.asarray(compacted.columns[-1].valid) & \
        np.asarray(compacted.live)
    src_host = np.where(src_ok, src_host, 0)

    # host-side payload gather from the table's mmap'd columns
    out_cols = list(compacted.columns[:-1])
    for j, ti in enumerate(scan.column_indices):
        col_np = np.asarray(data.columns[ti])[src_host]
        valid_np = src_ok.copy()
        if data.valids is not None and data.valids[ti] is not None:
            valid_np &= np.asarray(data.valids[ti])[src_host]
        out_cols.append(Column(jnp.asarray(col_np),
                               jnp.asarray(valid_np)))
    return Batch(tuple(out_cols), compacted.live)


def merge_partials(executor, node: L.AggregateNode,
                   partials: List[Batch]) -> Batch:
    """FINAL step: concat partial states, re-aggregate with merge
    functions over the partial layout (keys at 0..n_keys-1, states
    after)."""
    from ..ops.aggregate import AggSpec, global_aggregate
    from .executor import concat_all

    merged = concat_all(partials)
    n_keys = len(node.group_keys)
    merge_aggs = tuple(AggSpec(MERGE_FUNC[a.func], n_keys + j)
                       for j, a in enumerate(node.aggs))
    if node.strategy == "global":
        return global_aggregate(merged, merge_aggs)
    # groups <= live partial rows: that bound is safe and far below the
    # planner's NDV product on join outputs, which would put the merge
    # and everything after it (HAVING, ORDER BY) at the estimate's size
    capacity = bucket_capacity(int(np.asarray(merged.live).sum()))
    return executor.merge_group_aggregate(node, merged, merge_aggs,
                                          capacity)
