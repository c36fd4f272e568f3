"""Single-node plan executor.

Reference: the worker-side execution stack — LocalExecutionPlanner turns a
fragment into operator pipelines (sql/planner/LocalExecutionPlanner.java:549)
and Driver pushes pages between operators (operator/Driver.java:372). Here a
plan node maps to a jitted kernel call; XLA fuses within each call, and
adjacent Filter/Project nodes are evaluated inside one jit (the fusion
PageProcessor codegen gives Trino). The distributed variant lives in
parallel/ (stages over a mesh); this executor is also the per-shard body.

Adaptive fallbacks (SURVEY.md §7 hard part 1):
- sort-aggregation output capacity doubles and re-runs when the group table
  fills (the analog of GroupByHash rehash);
- joins with duplicate build keys fall back to a host expansion join until
  the device expansion kernel lands.
"""

from __future__ import annotations

import math
import os
import time
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import ir
from ..batch import (Batch, Column, batch_from_numpy, batch_to_numpy,
                     bucket_capacity, compaction_capacity)
from ..catalog import Catalog
from ..ops.aggregate import (AggSpec, direct_group_aggregate,
                             global_aggregate, sort_group_aggregate)
from ..batch import live_first_order, pad_capacity
from ..ops.join import (join_expand, join_mark, join_unique_build,
                        join_unique_build_dense, join_unique_build_merge)
from ..ops.project import (apply_filter, filter_project, filter_rows,
                           project)
from ..ops.sort import limit_batch, sort_batch
from ..planner import logical as L
from ..utils import tracing


@dataclass
class ExecStats:
    """Per-query execution counters (OperatorStats pyramid, minimal)."""
    scans: int = 0
    rows_scanned: int = 0
    join_fallbacks: int = 0
    join_expansion_retries: int = 0
    join_domain_fallbacks: int = 0   # dense-LUT stats were stale
    agg_capacity_retries: int = 0
    dynamic_filter_compactions: int = 0
    agg_spill_chunks: int = 0
    fact_cache_chunks: int = 0       # chunks sliced from device-resident
    chunk_lut_joins: int = 0         # sync-free reused-LUT probes
    packed_lut_joins: int = 0        # those of them whose LUT's word
                                     # carries the build's payload (one
                                     # gather a probe)
    lut_filtered_joins: int = 0      # those of them entered with no
                                     # dynamic filter in front: the
                                     # LUT's miss is the range test
    value_puts: int = 0              # ValuesNodes put on the device
                                     # (run_values calls)
    literal_slots: int = 0           # literals and lookup tables bound as
                                     # operands of a filter/project
                                     # program (bound_exprs memo fills)
    in_set_probes: int = 0           # dispatches of a filter/project
                                     # program that tests a folded IN
                                     # subquery's member set (ir.InSet)
    fused_chunk_pipelines: int = 0   # whole-chunk-path single programs
    jit_compiles: int = 0            # new jitted programs built (fused
                                     # chunk pipelines compiled fresh)
    escaped_window_reruns: int = 0   # adapted fused runs whose window /
                                     # capacity guesses were violated
    spilled_joins: int = 0           # joins retried through host-spill
                                     # radix partitioning (exec/spill.py)
    spilled_aggregations: int = 0    # aggregations/partial states spilled
    spilled_sorts: int = 0           # sorts retried on host (TopN under
                                     # pressure)
    dynamic_filter_rows_pruned: int = 0   # probe rows cut by build-side
                                          # bounds before the join ran
    scan_zones_pruned: int = 0       # zone-map row ranges skipped at scan
                                     # materialization (exec/zonemap.py)
    scan_rows_pruned: int = 0        # rows those zones would have decoded
    scan_chunks_skipped: int = 0     # chunked-driver chunks skipped whole
    scan_prefetched_chunks: int = 0  # chunks served from the prefetch
                                     # pipeline (exec/chunked.py)
    scan_prefetch_stalls: int = 0    # consumer waits on an unstaged chunk


class QueryDeadlineError(RuntimeError):
    """query_max_run_time_s exceeded (QUERY_MAX_RUN_TIME's role).

    Non-retryable user error: retrying cannot beat a wall clock that
    already ran out, so the dispatcher surfaces it straight to the
    client (same taxonomy path as QUERY_EXCEEDED_MEMORY)."""
    error_name = "QUERY_EXCEEDED_RUN_TIME"
    error_code = 4


class QueryTerminatedError(RuntimeError):
    """terminate() requested cancellation of the running query; the
    executor raises this at the next cooperative check point (plan-node,
    chunk, spill-partition, or prefetch boundary) so the exec lock frees
    within a bounded grace. Carries USER_CANCELED taxonomy — the state
    machine has usually already recorded the real reason."""
    error_name = "USER_CANCELED"
    error_code = 2


# serializes ExecStats->metrics snapshot diffs across task threads
import threading as _threading  # noqa: E402

_FLUSH_LOCK = _threading.Lock()


def _subtree_scans(node: "L.PlanNode"):
    if isinstance(node, L.ScanNode):
        yield node
    for c in L.children(node):
        yield from _subtree_scans(c)


def _subtree_nodes(node: "L.PlanNode"):
    yield node
    for c in L.children(node):
        yield from _subtree_nodes(c)


class Executor:
    def __init__(self, catalog: Catalog, device=None):
        self.catalog = catalog
        # the one local device this executor computes on (a worker's
        # chip, server/tasks.py), or None for the process's default
        # device (a session's executor): every host-to-device transfer
        # is committed there and programs follow their operands; what a
        # thread creates on the device without an operand (`on_device`)
        # lands there too
        self.device = device
        from .profiler import device_label
        self.device_label = device_label(device)
        # where transfers are committed and this executor's threads are
        # pointed: nowhere for the process's first device, which is
        # where everything lands unasked. A worker on device 0 (every
        # one-worker process) then dispatches exactly as an executor
        # bound to none does: a commit and a thread's default device
        # are no identity to the persistent compile cache (another key
        # for the same program: a cold set-up once a cell) and perhaps
        # not to `jit`'s dispatch (PERF.md section 6, PR 43)
        self.put_device = None if device is None or \
            device == jax.local_devices()[0] else device
        # everything this executor keeps on the device across statements
        # (exec/device_cache.ResidentSet): scanned table columns, the
        # chunked driver's fact tables and pinned builds — one budget
        from .device_cache import FactTableCache, ResidentSet
        self.resident = ResidentSet(device=self.put_device)
        # host-to-device bytes of this statement's scans (`scanPutBytes`
        # on the `execute` span)
        self.scan_put_bytes = 0
        # operator spans (`filter-project`, `aggregate`, `join`, `sort`,
        # `dynamic-filter`) of a traced statement: on inside execute()
        # and while a worker's traced task pins its builds and runs its
        # splits (server/tasks.py); the stack holds (context manager,
        # span) of those still open
        self._operator_spans = False
        self._open_operators: List[tuple] = []
        # in a worker's split loop: (the `worker-task` span's id, the
        # split's index). The spans then hang under the task, BESIDE the
        # `split` lap (whose self time and `compile` children stay what
        # they were), and carry `split` and nothing else
        self._operator_split: Optional[tuple] = None
        self._scalar_cache: Dict[object, object] = {}
        self.stats = ExecStats()
        self.profile = False           # EXPLAIN ANALYZE per-node timing
        self.node_stats: Dict[int, tuple] = {}   # id(node) -> (wall_s, rows)
        from .memory import MemoryPool, parse_bytes
        # per-query memory limit: TRINO_TPU_QUERY_MAX_MEMORY env (bytes,
        # B/kB/MB/GB suffixes) or the 64 GiB default; the session applies
        # its query_max_memory_mb property per query via set_limit
        env_limit = os.environ.get("TRINO_TPU_QUERY_MAX_MEMORY")
        self.pool = MemoryPool(parse_bytes(env_limit) if env_limit
                               else (64 << 30))
        self._node_bytes: Dict[int, int] = {}
        # id(plan node) -> (node, template, device values) of the
        # filter/project program it dispatches (bound_exprs); per-query
        # state, dropped where _node_bytes is
        self._bound_exprs: Dict[int, tuple] = {}
        # host-spill survival chain (exec/spill.py): when a join/agg
        # reservation cannot fit even after revocation, the operator
        # retries partition-wise through the host/disk tier
        self.enable_spill = True
        self.spill_partitions = 8
        self.spill_force_disk = False     # tests/chaos: all spills to disk
        self.spiller = None               # lazy HostSpiller
        self._kill_reason: Optional[str] = None   # LowMemoryKiller's flag
        self._cancel_reason: Optional[str] = None  # terminate() fan-out
        self._no_decisions = 0            # >0: bypass the decision cache
                                          # (partition-wise spill phases)
        # executor-owned caches hold REVOCABLE reservations: under
        # pressure the pool asks this callback to spill them (drop; they
        # re-run or re-ingest on next use)
        self._revocation_handle = self.pool.register_revocation(
            self._revoke_caches, tag="executor-caches")
        # chunked-mode substitutions: id(plan node) -> precomputed Batch
        # (streamed scan chunk, pinned build side, or merged partials)
        self._subst: Dict[int, Batch] = {}
        # ids of substitutions whose batch is NOT derivable from the
        # node's structure key (worker split chunks, streamed driver
        # chunks, merged partials). Pinned deterministic builds are
        # structure-faithful and do NOT register here, so decision
        # caching stays live through the chunked build phase.
        self._subst_opaque: set = set()
        # bounded-memory aggregation: process scan chains in chunks of this
        # many rows (the spill-to-host analog; None = off)
        self.spill_chunk_rows: Optional[int] = None
        # per-query record of the strategy each operator class actually
        # ran with (EXPLAIN `agg strategy:` lines, operator_stats column)
        self.strategy_decisions: Dict[str, str] = {}
        # session-property knobs (exec/session.py wires these per query)
        self.enable_dynamic_filtering = True
        self.enable_merge_join = True
        # zone-map scan pruning (exec/zonemap.py): skip decoding /
        # materializing row ranges the pushed-down scan predicate
        # provably cannot match. Advisory — the residual filter always
        # re-runs, so "off" is bit-exact with "on".
        self.enable_zone_map_pruning = True
        from .zonemap import DEFAULT_ZONE_ROWS
        self.zone_map_rows = DEFAULT_ZONE_ROWS
        # prefetch pipeline depth (exec/prefetch.py): how many decoded+staged
        # chunks of the chunked driver, or splits of a worker task, may sit
        # ahead of the device (0 = the serial loop)
        self.prefetch_depth = 2
        self.prewarm_chunks = False
        # seeded FailureInjector (server/failureinjector.py) for chaos
        # coverage of executor-side worker threads; None outside tests
        self.failure_injector = None
        self.deadline: Optional[float] = None     # time.monotonic() cutoff
        # build sides estimated above this stream chunk-wise through the
        # dense LUT instead of materializing on device (0/None = off)
        self.stream_build_bytes: Optional[int] = None
        # chunk-mode state: inside the chunked driver loop every host
        # sync stalls the dispatch pipeline, so joins
        # build+validate their dense LUT once per pinned build and then
        # probe sync-free; compaction (which needs a row count) is
        # skipped for the loop's duration
        self.chunk_mode = False
        self._chunk_lut_cache: Dict[tuple, object] = {}
        # cross-run caches for the FUSED chunk pipeline: jitted per-chunk
        # programs keyed by plan-structure hash, and validated dense LUTs
        # keyed by (build structure, domain)
        self._fused_cache: Dict[str, object] = {}
        self._lut_cache: Dict[tuple, object] = {}
        # device-resident narrowed fact columns (exec/device_cache.py):
        # steady-state chunked scans slice HBM instead of re-streaming
        # the host link; entries of the one resident set
        self.fact_cache = FactTableCache(self.resident)
        self.enable_fact_cache = True
        # cross-run DECISION cache: every data-dependent host decision
        # (join dup/oob validation, live counts for compaction capacity,
        # key-packing layouts) is a pure function of a deterministic
        # subtree, so its fetched integers are cached by structure key.
        # Steady-state re-execution then runs the whole plan as one
        # async dispatch chain with a single final result fetch.
        self._decision_cache: Dict[tuple, tuple] = {}
        # the decision cache persists to disk (keys are sha256 wire-form
        # hashes — stable across processes), so a FRESH process replays a
        # previous run's decisions: identical capacities/layouts mean the
        # persistent XLA code cache hits too, collapsing cold-start to
        # ingest + cached-program load. The reference's analog is the
        # long-lived JVM keeping ExpressionCompiler output warm
        # (sql/gen/ExpressionCompiler.java:38).
        self._decision_dirty = False
        self._decision_loaded = False
        # per-execution memo of build_structure_key: id(node) -> (node,
        # key). The node reference keeps temporaries alive so CPython
        # cannot reuse their id within one execution; cleared at query
        # start
        self._skey_memo: Dict[int, tuple] = {}

    # ------------------------------------------------------------------

    def on_device(self):
        """Context of a thread that works for this executor (a worker's
        task thread, its split feeder): arrays made without an operand
        (`jnp.asarray`, `jnp.zeros`, a bare `jax.device_put`) and
        programs without a committed one land on `self.device` and not
        on the process's first. `jax.default_device` is the thread's
        own; with no device, or the process's first, it is the null
        context (`put_device`)."""
        if self.put_device is None:
            return nullcontext()
        return jax.default_device(self.put_device)

    def bind_recorder(self) -> None:
        """Compiles recorded on this thread are this executor's, on its
        device (exec/profiler.py)."""
        from .profiler import RECORDER
        RECORDER.bind_stats(self.stats, self.device_label)

    def _revoke_caches(self, target_bytes: int) -> int:
        """Revocation callback: evict cached build batches (revocable
        reservations) until the target is met. Evicted builds re-run on
        next use — correctness never depends on the cache."""
        return self.resident.evict_kind("build", target_bytes)

    def request_kill(self, reason: str) -> None:
        """Cluster LowMemoryKiller's hook: the next plan-node boundary
        raises MemoryKilledError (surfaced as QUERY_EXCEEDED_MEMORY)."""
        self._kill_reason = reason

    def request_cancel(self, reason: str) -> None:
        """terminate() fan-out's hook: the next cooperative check point
        raises QueryTerminatedError so a locally-executing query frees
        the exec lock within a bounded grace."""
        self._cancel_reason = reason

    def check_cancel(self) -> None:
        """Cooperative cancellation/deadline check, called between plan
        nodes (run()), between driver chunks (exec/chunked.py), between
        spill partitions (exec/spill.py), and by the prefetch pipeline —
        the boundaries where a stuck query can actually be stopped."""
        if self._kill_reason is not None:
            from .memory import MemoryKilledError
            raise MemoryKilledError(self._kill_reason)
        if self._cancel_reason is not None:
            raise QueryTerminatedError(self._cancel_reason)
        if self.deadline is not None:
            import time as _t
            if _t.monotonic() > self.deadline:
                raise QueryDeadlineError(
                    "query exceeded query_max_run_time_s")

    class _NoDecisions:
        def __init__(self, ex):
            self.ex = ex

        def __enter__(self):
            self.ex._no_decisions += 1

        def __exit__(self, *exc):
            self.ex._no_decisions -= 1
            return False

    def no_decisions(self) -> "Executor._NoDecisions":
        """Bypass the cross-run decision cache inside the block — the
        spill paths run the SAME plan node over per-partition data, so
        cached counts would poison replay."""
        return Executor._NoDecisions(self)

    @property
    def scan_cache_max_bytes(self) -> int:
        """The resident set's budget (session property
        `scan_cache_max_mb`; None = derive it from the device)."""
        return self.resident.max_bytes

    @scan_cache_max_bytes.setter
    def scan_cache_max_bytes(self, value: Optional[int]) -> None:
        self.resident.max_bytes = value

    def invalidate_scan_cache(self) -> None:
        """Drop everything resident: scanned columns, the fact tables
        that alias the same tables, and the builds made from them."""
        self.resident.clear()
        # decision values never cache for mutable catalogs, but clearing
        # costs nothing and removes any doubt after DML
        self._decision_cache.clear()

    def flush_metrics(self) -> None:
        """Mirror ExecStats deltas since the last flush into the process
        metrics registry (trino_tpu_exec_events_total{event=...}).
        ExecStats stays the cheap cumulative in-object view (bench and
        tests read it directly); the registry gets increments so
        /v1/metrics scrapes see the same counters fleet-wide. Guarded by
        its own lock (NOT the executor lock — flushing must never block
        behind a running query)."""
        import dataclasses

        from ..metrics import EXEC_EVENTS, OPERATOR_ROWS
        with _FLUSH_LOCK:
            cur = dataclasses.asdict(self.stats)
            prev = getattr(self, "_stats_flushed", {})
            for k, v in cur.items():
                d = v - prev.get(k, 0)
                if d:
                    EXEC_EVENTS.inc(d, event=k)
            d = cur["rows_scanned"] - prev.get("rows_scanned", 0)
            if d:
                OPERATOR_ROWS.inc(d, operator="scan")
            self._stats_flushed = cur

    def execute(self, root: L.OutputNode) -> Batch:
        assert isinstance(root, L.OutputNode)
        self.bind_recorder()
        self._kill_reason = None
        self._cancel_reason = None
        self.strategy_decisions = {}
        # release reservations surviving from the previous query (the root
        # batch lives until its results are drained)
        self.release_all_reservations()
        self._subst.clear()
        self._subst_opaque.clear()
        self._skey_memo.clear()
        self.scan_put_bytes = 0
        try:
            if self.spill_chunk_rows:
                from .chunked import execute_chunked
                out = execute_chunked(self, root)
                if out is not None:
                    return out
            self._operator_spans = tracing.current().enabled
            return self.run(root.child)
        finally:
            self._operator_spans = False
            self.save_decisions()

    def run(self, node: L.PlanNode) -> Batch:
        # bind this executor's stats to the dispatch thread so the
        # compile recorder attributes fresh XLA compiles here
        self.bind_recorder()
        sub = self._subst.get(id(node))
        if sub is not None:
            return sub
        self.check_cancel()
        from .memory import ExceededMemoryLimitError, MemoryKilledError, \
            batch_bytes
        try:
            out = self._dispatch_spanned(node)
            b = batch_bytes(out)
            self.pool.reserve(b)
        except MemoryKilledError:
            raise                         # the killer's verdict is final
        except ExceededMemoryLimitError:
            # memory-pressure survival: joins/aggregations retry through
            # the host-spill radix partitioner; anything else fails
            # cleanly as QUERY_EXCEEDED_MEMORY
            out = self._spill_retry(node)
            b = batch_bytes(out)
            self.pool.reserve(b)
        # memory accounting: reserve this node's output, release the
        # children's (their batches die once the parent has consumed them)
        # — the operator->query context pyramid collapsed to plan nodes
        self._node_bytes[id(node)] = b
        for c in L.children(node):
            if id(c) in self._subst:
                continue    # pinned (chunked-mode build/merge): lives on
            self.pool.free(self._node_bytes.pop(id(c), 0))
        return out

    def _spill_retry(self, node: L.PlanNode) -> Batch:
        """Retry a memory-failed Join/Aggregate partition-wise through
        the host-spill tier (exec/spill.py). The innermost failing
        operator spills first; if its shape is unsupported, the original
        error propagates so an enclosing operator (or the query
        boundary) handles it."""
        if not self.enable_spill or \
                not isinstance(node, (L.JoinNode, L.AggregateNode,
                                      L.SortNode)):
            raise
        # drop this subtree's partial reservations from the failed
        # attempt; the spill path re-executes the children bounded
        self.release_path_reservations(node, keep=self._subst)
        from .spill import spill_aggregate, spill_join, spill_sort
        if isinstance(node, L.JoinNode):
            out = spill_join(self, node)
        elif isinstance(node, L.AggregateNode):
            out = spill_aggregate(self, node)
        else:
            out = spill_sort(self, node)
        if out is None:
            raise
        return out

    def operator_span(self, name: str, **attributes) -> None:
        """Open the `name` span of the plan node being dispatched, once
        its children have run: the span is the operator's own wall, not
        its subtree's. `_dispatch_spanned` closes it behind the node's
        fence, so under `enable_profiling` that wall holds the
        operator's device time; with tracing alone it is the dispatch
        and whatever the operator fetched. A no-op unless a traced
        statement runs whole on this executor (`execute`) or a traced
        worker task runs (`_operator_split` says how its split loop's
        spans differ)."""
        if not self._operator_spans:
            return
        if self._operator_split is None:
            cm = tracing.current().span(name, **{
                k: v for k, v in attributes.items() if v is not None})
        else:
            parent, index = self._operator_split
            cm = tracing.current().split_span(
                name, parent, index, depth=len(self._open_operators))
        self._open_operators.append((cm, cm.__enter__()))

    def _close_operators(self, depth: int) -> None:
        while len(self._open_operators) > depth:
            self._open_operators.pop()[0].__exit__(None, None, None)

    def stamp_operator(self, in_splits: bool = False,
                       **attributes) -> None:
        """Attributes for the innermost open operator span, if any. In a
        split loop only those a caller marks `in_splits`: hundreds of
        splits say the same, so what they say has to be little (it is
        shipped once a kind of span: `Tracer.split_span`)."""
        if self._open_operators and (self._operator_split is None or
                                     in_splits):
            self._open_operators[-1][1].attributes.update(attributes)

    def _known_rows(self, node: L.PlanNode) -> Optional[int]:
        """Live rows `node` gave, where the profiled dispatch counted
        them (`node_stats`); a span never pays a device sync for one."""
        stat = self.node_stats.get(id(node)) if self.profile else None
        return stat[1] if stat else None

    def _dispatch_spanned(self, node: L.PlanNode) -> Batch:
        depth = len(self._open_operators)
        try:
            return self._dispatch_timed(node)
        finally:
            self._close_operators(depth)

    def _dispatch_timed(self, node: L.PlanNode) -> Batch:
        if self.profile:
            import time
            from .profiler import RECORDER
            c0 = RECORDER.thread_compile_seconds()
            t0 = time.monotonic()
            out = self.dispatch(node)
            t1 = time.monotonic()
            # fencing per node serializes XLA async dispatch, so profiled
            # times cover the node's own device work (OperatorStats role,
            # operator/OperatorStats.java:37). The fence splits wall into
            # components: device = time blocked on the fence, compile =
            # recorder-attributed compile seconds during the dispatch,
            # host = the dispatch remainder; the three sum to wall
            # exactly (the misattribution He et al. warn about — async
            # device time landing on whichever later op blocks — cannot
            # happen across a fence).
            jax.block_until_ready(out)
            t2 = time.monotonic()
            compile_s = min(max(RECORDER.thread_compile_seconds() - c0,
                                0.0), t1 - t0)
            device_s = t2 - t1
            host_s = (t1 - t0) - compile_s
            rows = int(jnp.sum(out.live))
            op = type(node).__name__
            self.node_stats[id(node)] = (t2 - t0, rows, device_s,
                                         host_s, compile_s)
            from ..metrics import (OPERATOR_COMPILE_MS,
                                   OPERATOR_DEVICE_MS, OPERATOR_ROWS)
            OPERATOR_ROWS.inc(rows, operator=op)
            OPERATOR_DEVICE_MS.inc(device_s * 1000, operator=op)
            if compile_s:
                OPERATOR_COMPILE_MS.inc(compile_s * 1000, operator=op)
        else:
            # always-on operator metrics: host dispatch wall only (device
            # work stays async — a per-node sync here would serialize the
            # whole pipeline, which is exactly what profile mode pays for)
            import time as _time
            t0 = _time.monotonic()
            out = self.dispatch(node)
            from ..metrics import OPERATOR_DISPATCHES, OPERATOR_WALL_MS
            op = type(node).__name__
            OPERATOR_DISPATCHES.inc(operator=op)
            OPERATOR_WALL_MS.inc((_time.monotonic() - t0) * 1000,
                                 operator=op)
        return out

    def build_structure_key(self, node: L.PlanNode) -> Optional[str]:
        """Cross-run cache key for a DETERMINISTIC build subtree: the
        hash of its canonical wire form (`serde.structure_text`: a
        table's schema, dictionaries and all, enters as a digest made
        once), or None when any scan reads a mutable catalog (memory
        tables change between runs)."""
        scans = [s for s in _subtree_scans(node)]
        if any(s.catalog not in ("tpch", "tpcds", "bench")
               for s in scans) or not scans:
            return None
        import hashlib
        from ..server import serde
        return hashlib.sha256(
            serde.structure_text(node).encode()).hexdigest()

    def _decision_salt(self) -> tuple:
        """Session knobs that change runtime decision values for the
        SAME plan structure (dynamic filtering alters intermediate live
        counts, merge-join toggles which kernel's dup check runs)."""
        return (self.enable_dynamic_filtering, self.enable_merge_join,
                bool(self.stream_build_bytes), self.spill_chunk_rows)

    _DECISION_CACHE_FILE = "decisions.pkl"

    def _decision_path(self) -> Optional[str]:
        if os.environ.get("TRINO_TPU_DECISION_CACHE") == "0":
            return None
        from ..connectors.diskcache import cache_root
        return os.path.join(cache_root(), self._DECISION_CACHE_FILE)

    def _load_decisions(self) -> None:
        """Merge the on-disk decision cache in (once per executor).
        Entries exist only for immutable generator catalogs, so merging
        stale files is safe; corruption just means a cold start."""
        self._decision_loaded = True
        path = self._decision_path()
        if path is None or not os.path.isfile(path):
            return
        import pickle
        try:
            with open(path, "rb") as f:
                disk = pickle.load(f)
            for k, v in disk.items():
                self._decision_cache.setdefault(k, v)
        except Exception:
            pass

    # on-disk entry cap: this session's entries always survive; older
    # disk entries backfill up to the cap so the file can't grow without
    # bound across workloads (entries are ~150 B each)
    _DECISION_FILE_MAX = 65536

    def save_decisions(self) -> None:
        """Persist new decision values (atomic tmp+rename; merge with
        any concurrent writer's file first). The dirty flag clears only
        after a successful write so transient disk failures retry."""
        if not self._decision_dirty:
            return
        path = self._decision_path()
        if path is None:
            self._decision_dirty = False
            return
        import pickle
        try:
            merged = dict(self._decision_cache)
            if os.path.isfile(path):
                with open(path, "rb") as f:
                    for k, v in pickle.load(f).items():
                        if len(merged) >= self._DECISION_FILE_MAX:
                            break
                        merged.setdefault(k, v)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = path + f".tmp{os.getpid()}"
            with open(tmp, "wb") as f:
                pickle.dump(merged, f)
            os.replace(tmp, path)
            self._decision_dirty = False
        except Exception:
            pass

    def decisions_cacheable(self, node) -> bool:
        """May `node`'s runtime decision values go through the cross-run
        decision cache? Chunk mode bypasses (the driver chunk differs per
        iteration); an OPAQUE substitution anywhere in the subtree
        bypasses (per-split worker data, streamed chunks, merge batches
        carry data the structure key doesn't describe — split 2 of a
        worker task must not reuse split 1's counts). Structure-faithful
        substitutions (pinned deterministic builds) do NOT bypass."""
        if self.chunk_mode or self._no_decisions:
            return False
        if not self._subst_opaque:
            return True
        return not any(id(n) in self._subst_opaque
                       for n in _subtree_nodes(node))

    def fetch_ints(self, node, tag: str, *vals) -> tuple:
        """Fetch small device integers (validation flags, row counts,
        min/max stats) as host ints — through the cross-run decision
        cache when `node`'s subtree is deterministic. On a hit the
        blocking device round trip is skipped entirely; the device-side
        computation of `vals` was async-dispatched and is dead code XLA
        never waits on."""
        key = None
        if node is not None and self.decisions_cacheable(node):
            skey = self.memo_structure_key(node)
            if skey is not None:
                if not self._decision_loaded:
                    self._load_decisions()
                key = (tag, skey, self._decision_salt())
                hit = self._decision_cache.get(key)
                if hit is not None:
                    return hit
        # one device vector (a plan's measurements) comes as it is
        flat = vals[0] if len(vals) == 1 and jnp.ndim(vals[0]) == 1 else \
            jnp.stack([jnp.asarray(v).astype(jnp.int64) for v in vals])
        out = tuple(int(v) for v in np.asarray(flat))
        if key is not None:
            if len(self._decision_cache) >= 4096:
                self._decision_cache.clear()
            self._decision_cache[key] = out
            self._decision_dirty = True
        return out

    def memo_structure_key(self, node: L.PlanNode) -> Optional[str]:
        """build_structure_key with a per-execution id(node) memo: a join
        makes several decision fetches against the same subtree and the
        serde+sha walk is O(subtree) host work each time. The memo holds
        the NODE too, not just its id — short-lived dataclasses.replace
        temporaries (packed-key joins) would otherwise free their id for
        reuse by a later temp, which would inherit the wrong key and
        poison the cross-run decision cache."""
        nid = id(node)
        hit = self._skey_memo.get(nid)
        if hit is not None:
            return hit[1]
        skey = self.build_structure_key(node)
        self._skey_memo[nid] = (node, skey)
        return skey

    def run_cached_build(self, node: L.PlanNode) -> Batch:
        """Execute a chunked-mode build subtree with a cross-run cache:
        the key is the subtree's wire-form hash (serde is canonical), so
        a re-planned but structurally identical build reuses the pinned
        device batch. Only deterministic generator catalogs participate
        (a memory-connector table can change between runs)."""
        key = self.build_structure_key(node)
        if key is None:
            return self.run(node)
        hit = self.resident.get(("build", key))
        if hit is not None:
            return hit
        out = self.run(node)
        from .memory import batch_bytes
        b = batch_bytes(out)
        # the key holds the subtree's literals, so the entries are
        # bounded in bytes by the resident set's budget. A kept batch
        # outlives the query: its reservation moves from the per-query
        # ledger to the pool's REVOCABLE one (freed when it is evicted,
        # or reclaimed under pressure by the revocation callback)
        if self.resident.put(
                ("build", key), out, b, on_evict=lambda: \
                self.pool.free_revocable(b, tag="build-cache")):
            self.pool.free(self._node_bytes.pop(id(node), 0))
            self.pool.reserve_revocable(b, tag="build-cache")
        return out

    def release_all_reservations(self) -> None:
        """Free every per-node reservation (the distributed scheduler's
        merge path runs plan nodes without execute()'s per-query cleanup
        — under a small pool those leaked bytes starve later queries),
        and with them the plan nodes' bound expressions and the folded
        subqueries' answers: all keyed by the identity of nodes that go
        with the query or the task (a subquery's key holds its whole
        plan: a worker's executor would keep one alive for every Q18 it
        has ever run)."""
        for b in self._node_bytes.values():
            self.pool.free(b)
        self._node_bytes.clear()
        self._bound_exprs.clear()
        self._scalar_cache.clear()

    def release_path_reservations(self, node: L.PlanNode, keep) -> None:
        """Free reservations of `node`'s subtree (chunked mode: the
        per-chunk pipeline recomputes these next iteration). Nodes in
        `keep` (pinned substitutions) stay reserved."""
        if id(node) not in keep:
            self.pool.free(self._node_bytes.pop(id(node), 0))
            for c in L.children(node):
                self.release_path_reservations(c, keep)

    def dispatch(self, node: L.PlanNode) -> Batch:
        if isinstance(node, L.ScanNode):
            return self.run_scan(node)
        if isinstance(node, L.FilterNode):
            # fuse Filter over Project/Scan chains into one jit call
            if isinstance(node.child, L.ProjectNode):
                (pred, exprs), values = self.bound_exprs(
                    node, node.predicate, node.child.exprs)
                child = self.run(node.child.child)
                self.operator_span("filter-project")
                return filter_project_fused(child, values, exprs, pred)
            child = self.run(node.child)
            # a subquery of the predicate has a span of its own
            (pred, _), values = self.bound_exprs(node, node.predicate, ())
            self.operator_span("filter-project")
            return filter_rows(child, values, pred)
        if isinstance(node, L.ProjectNode):
            if isinstance(node.child, L.FilterNode):
                (pred, exprs), values = self.bound_exprs(
                    node, node.child.predicate, node.exprs)
                child = self.run(node.child.child)
            else:
                (pred, exprs), values = self.bound_exprs(
                    node, None, node.exprs)
                child = self.run(node.child)
            self.operator_span("filter-project")
            return filter_project(child, values, pred, exprs)
        if isinstance(node, L.AggregateNode):
            return self.run_aggregate(node)
        if isinstance(node, L.JoinNode):
            return self.run_join(node)
        if isinstance(node, L.WindowNode):
            return self.run_window(node)
        if isinstance(node, L.SortNode):
            keys = tuple((k.index, k.ascending, k.nulls_first)
                         for k in node.keys)
            child = self.run(node.child)
            self.operator_span("sort", capacity=child.capacity,
                               rows=self._known_rows(node.child),
                               limit=node.limit)
            # at scale, pack ORDER BY keys into one int64 so the sort
            # stays 2-operand (see SORT_SMALL_ROWS)
            if keys and child.capacity > SORT_GENERAL_ROWS:
                from ..ops.sort import sort_batch_packed, sort_pack_plan
                plan = sort_pack_plan(
                    child, keys,
                    fetch=lambda v: self.fetch_ints(node, "sortpackv", v))
                if plan is not None:
                    kmins, bits, splits = plan
                    return sort_batch_packed(child, jnp.asarray(kmins),
                                             keys, bits, node.limit,
                                             splits)
            return sort_batch(child, keys, node.limit)
        if isinstance(node, L.LimitNode):
            return limit_batch(self.run(node.child),
                               jnp.asarray(node.count, dtype=jnp.int64))
        if isinstance(node, L.OutputNode):
            return self.run(node.child)
        if isinstance(node, L.ValuesNode):
            return self.run_values(node)
        if isinstance(node, L.SetOpNode):
            return self.run_setop(node)
        if isinstance(node, L.UnnestNode):
            return self.run_unnest(node)
        raise NotImplementedError(type(node).__name__)

    def run_unnest(self, node: L.UnnestNode) -> Batch:
        """UNNEST expansion (operator/unnest/UnnestOperator.java:42):
        repeat each live row once per element of its array. Arrays are
        pool ids (types.py), so the expansion is a host-edge transform
        like the other pool operations — flat offsets are precomputed
        per pool, rows gather through np.repeat."""
        child = self.run(node.child)
        arrays, valids = batch_to_numpy(child)
        ids = arrays[node.array_col]
        id_valid = valids[node.array_col]
        pool = node.array_pool
        lengths = np.array([len(t) for t in pool], dtype=np.int64)
        flat = [v for t in pool for v in t]
        offsets = np.concatenate([[0], np.cumsum(lengths)])
        reps = np.where(id_valid, lengths[ids], 0)   # NULL array: 0 rows
        row_idx = np.repeat(np.arange(len(ids)), reps)
        within = np.arange(len(row_idx)) - np.repeat(
            np.cumsum(reps) - reps, reps)
        elem_pos = offsets[ids[row_idx]] + within
        elem_vals = [flat[int(p)] for p in elem_pos]
        elem_valid = np.array([v is not None for v in elem_vals],
                              dtype=np.bool_)
        t = node.element_dtype
        from ..types import TypeKind as TK
        if t.kind is TK.VARCHAR:
            index = {s: i for i, s in enumerate(node.element_pool or ())}
            elem = np.array([index.get(v, 0) for v in elem_vals],
                            dtype=np.int32)
        else:
            elem = np.array([v if v is not None else 0
                             for v in elem_vals], dtype=t.np_dtype)
        out_arrays = [a[row_idx] for a in arrays] + [elem]
        out_valids = [v[row_idx] for v in valids] + [elem_valid]
        if node.ordinality:
            out_arrays.append((within + 1).astype(np.int64))
            out_valids.append(np.ones(len(row_idx), dtype=np.bool_))
        return batch_from_numpy(out_arrays, valids=out_valids,
                                device=self.put_device)

    def run_values(self, node: L.ValuesNode) -> Batch:
        # a materialised broadcast build arrives as a ValuesNode with a
        # data-dependent row count, and its Batch is an argument of the
        # join programs: on the capacity lattice every row count of one
        # bucket shares their compiled forms (1,024 up to 1,024 rows)
        self.stats.value_puts += 1
        cap = bucket_capacity(node.num_rows)
        if node.arrays:
            return batch_from_numpy(list(node.arrays),
                                    valids=list(node.valids),
                                    capacity=cap, device=self.put_device)
        # zero-column values (SELECT without FROM): live mask only
        live = np.zeros(cap, dtype=np.bool_)
        live[:node.num_rows] = True
        return Batch(columns=(), live=self._place(live))

    def run_setop(self, node: L.SetOpNode) -> Batch:
        left = remap_codes(self.run(node.left), node.left_remaps)
        right = remap_codes(self.run(node.right), node.right_remaps)
        if node.op == "union_all":
            return concat_batches(left, right)
        return self.run_setop_host(node.op, left, right)

    def run_setop_host(self, op: str, left: Batch, right: Batch) -> Batch:
        """DISTINCT/INTERSECT/EXCEPT variants, host-side. NULLs compare
        equal (set ops use IS NOT DISTINCT semantics, like GROUP BY)."""
        from collections import Counter
        la, lv = batch_to_numpy(left)
        ra, rv = batch_to_numpy(right)

        def rows_of(arrays, valids):
            n = len(arrays[0]) if arrays else 0
            return [tuple(arrays[j][i].item() if valids[j][i] else None
                          for j in range(len(arrays)))
                    for i in range(n)]

        lrows, rrows = rows_of(la, lv), rows_of(ra, rv)

        def dedup(rows):
            seen, out = set(), []
            for r in rows:
                if r not in seen:
                    seen.add(r)
                    out.append(r)
            return out

        if op == "union":
            out = dedup(lrows + rrows)
        elif op == "intersect":
            rset = set(rrows)
            out = [r for r in dedup(lrows) if r in rset]
        elif op == "intersect_all":
            rcount = Counter(rrows)
            used: Counter = Counter()
            out = []
            for r in lrows:
                if used[r] < rcount.get(r, 0):
                    used[r] += 1
                    out.append(r)
        elif op == "except":
            rset = set(rrows)
            out = [r for r in dedup(lrows) if r not in rset]
        elif op == "except_all":
            rcount = Counter(rrows)
            used = Counter()
            out = []
            for r in lrows:
                if used[r] < rcount.get(r, 0):
                    used[r] += 1
                else:
                    out.append(r)
        else:
            raise NotImplementedError(op)

        ncols = len(la)
        arrays = []
        valids = []
        for j in range(ncols):
            vals = [r[j] for r in out]
            valid = np.array([v is not None for v in vals], dtype=np.bool_)
            data = np.array([v if v is not None else 0 for v in vals],
                            dtype=la[j].dtype)
            arrays.append(data)
            valids.append(valid)
        if not arrays:
            live = np.zeros(pad_capacity(len(out)), dtype=np.bool_)
            live[:len(out)] = True
            return Batch(columns=(), live=self._place(live))
        return batch_from_numpy(arrays, valids=valids, device=self.put_device)

    # ------------------------------------------------------------------

    def run_scan(self, node: L.ScanNode) -> Batch:
        if node.catalog == "system" or \
                node.schema_name == "information_schema":
            # volatile introspection state: never scan-cache (a cached
            # batch would pin the first snapshot, and its dictionary
            # codes go stale against freshly planned decode scopes)
            data = self.catalog.get_table(node.catalog, node.schema_name,
                                          node.table)
            self.stats.scans += 1
            self.stats.rows_scanned += data.num_rows
            return self._host_batch(data, node.column_indices)
        tracer = tracing.current()
        evicted = (self.resident.evicted_entries,
                   self.resident.evicted_bytes)
        with tracer.span("scan") as sp:
            batch, facts = self._scan_table(node)
            if sp is not None:
                fields = node.table_schema.fields
                sp.attributes.update(
                    facts, table=node.table, columns=",".join(
                        fields[i].name for i in node.column_indices))
        self.scan_put_bytes += facts["putBytes"]
        entries = self.resident.evicted_entries - evicted[0]
        if entries and tracer.enabled:
            # the budget made room during the scan: a sibling of `scan`
            # under `execute`, stamped where the scan ended
            now = time.monotonic()
            tracer.record("evict", now, now, entries=entries,
                          bytes=self.resident.evicted_bytes - evicted[1])
        return batch

    def _scan_table(self, node: L.ScanNode):
        """The scan's batch and the facts of its `scan` span. The
        table's columns come from the device copy the resident set keeps
        (put there now where one is missing); a statement's zone-map
        verdict only narrows `live`, it never makes a second copy."""
        pruning = node.predicate is not None and self.enable_zone_map_pruning
        data = self._pruned_decode(node) if pruning else None
        self.stats.scans += 1
        if data is not None:
            # the connector decoded only the stripes / row groups the
            # predicate may match: this statement's own rows, not the
            # table, so nothing of it stays resident
            batch = self._host_batch(data, node.column_indices)
            self.stats.rows_scanned += data.num_rows
            from .memory import batch_bytes
            return batch, {"resident": "miss", "zonesPruned": 0,
                           "putBytes": batch_bytes(batch)}
        data = self.catalog.get_table(node.catalog, node.schema_name,
                                      node.table)
        table = (node.catalog, node.schema_name, node.table)
        cap = self._scan_capacity(data.num_rows)
        live, put = self._resident_column(table, None, data, cap, None)
        columns = []
        for i in node.column_indices:
            col, b = self._resident_column(table, i, data, cap, live)
            columns.append(col)
            put += b
        kept_rows, pruned = data.num_rows, 0
        if pruning:
            live, kept_rows, pruned = self._zone_live(node, data, live, cap)
            put += cap if pruned else 0
        self.stats.rows_scanned += kept_rows
        return Batch(columns=tuple(columns), live=live), {
            "resident": "miss" if put else "hit", "zonesPruned": pruned,
            "putBytes": put}

    def _host_batch(self, data, column_indices) -> Batch:
        """The connector's rows put whole and kept nowhere."""
        arrays = [data.columns[i] for i in column_indices]
        valids = None if data.valids is None else \
            [data.valids[i] for i in column_indices]
        return batch_from_numpy(arrays, valids=valids, device=self.put_device)

    def _scan_capacity(self, rows: int) -> int:
        """Padded capacity of a table's device copy."""
        return pad_capacity(rows)

    def _place(self, host: np.ndarray):
        """A host array onto this executor's device(s)."""
        return jax.device_put(host, self.put_device)

    def _resident_column(self, table: tuple, index, data, cap: int, live):
        """-> (table column `index` as a device Column at `cap`, bytes
        put now). `index` None is the table's live mask (a bare array),
        which is also the validity mask of every column without NULLs.
        The entry holds the connector's TableData it was made from: a
        mutated table is a new TableData (its version), so a stale copy
        is never served and is replaced under the same key."""
        key = ("column",) + table + (index,)
        hit = self.resident.get(key)
        if hit is not None and hit[0] is data:
            return hit[1], 0
        rows = data.num_rows

        def put(host, dtype):
            padded = np.zeros(cap, dtype=dtype)
            padded[:rows] = host
            return self._place(padded)

        if index is None:
            value, nbytes = put(True, np.bool_), cap
        else:
            valid, nbytes = live, 0
            if data.valids is not None and data.valids[index] is not None:
                valid, nbytes = put(data.valids[index], np.bool_), cap
            host = np.asarray(data.columns[index])
            value = Column(data=put(host, host.dtype), valid=valid)
            nbytes += cap * host.dtype.itemsize
        self.resident.put(key, (data, value), nbytes)
        return value, nbytes

    def _pruned_decode(self, node: L.ScanNode):
        """A connector-side pruned decode (ORC stripe / Parquet row-group
        skipping) when the scan carries a pushed predicate, the connector
        supports it, and the full table is not already decoded in its
        cache; else None. Dictionary-encoded scan columns disqualify the
        pruned path: a pruned decode rebuilds string pools from surviving
        rows only, and those codes would not line up with the
        dictionaries the plan was analyzed against."""
        try:
            conn = self.catalog.connector(node.catalog)
        except KeyError:
            return None
        if not hasattr(conn, "get_table_pruned") or \
                (node.schema_name, node.table) in \
                getattr(conn, "_cache", {}) or \
                any(node.table_schema.fields[i].dictionary is not None
                    for i in node.column_indices):
            return None
        from .zonemap import column_ranges
        ranges = column_ranges(node.predicate, node.column_indices,
                               node.table_schema)
        if not ranges:
            return None
        try:
            return conn.get_table_pruned(node.schema_name, node.table,
                                         ranges)
        except Exception:
            return None      # fall back to the full decode

    def _zone_live(self, node: L.ScanNode, data, live, cap: int):
        """-> (live mask, rows kept, zones pruned): `live` of the
        resident copy with the row ranges taken out that the pushed
        predicate provably cannot match (zone-map evaluation on the
        host). Rows keep their places, so the post-residual-filter row
        stream is identical to the unpruned scan's; where no zone is
        cut the resident mask itself comes back and nothing is put."""
        from . import zonemap
        zm = zonemap.zone_map_for(data, self.zone_map_rows)
        idx = zonemap.surviving_zone_indices(zm, node.predicate,
                                             node.column_indices)
        pruned = zm.num_zones - len(idx)
        if pruned == 0:
            return live, data.num_rows, 0
        keep = np.zeros(cap, dtype=np.bool_)
        for i in idx:
            keep[zm.starts[i]:zm.starts[i] + zm.counts[i]] = True
        kept_rows = sum(zm.counts[i] for i in idx)
        self.stats.scan_zones_pruned += pruned
        self.stats.scan_rows_pruned += data.num_rows - kept_rows
        from ..metrics import SCAN_ZONES_PRUNED
        SCAN_ZONES_PRUNED.inc(pruned)
        self.strategy_decisions[
            f"TableScan[{node.table}]"] = \
            f"zone-pruned:{pruned}/{zm.num_zones}"
        return self._place(keep), kept_rows, pruned

    def run_window(self, node: L.WindowNode) -> Batch:
        from ..ops.window import WinSpec, window_compute
        child = self.run(node.child)
        keys = tuple((k.index, k.ascending, k.nulls_first)
                     for k in node.order_by)
        specs = tuple(WinSpec(s.func, s.arg, s.frame, s.offset, s.default)
                      for s in node.specs)
        return window_compute(child, node.partition_by, keys, specs)

    def run_aggregate(self, node: L.AggregateNode) -> Batch:
        aggs = tuple(AggSpec(
            a.func,
            a.arg.index if a.arg is not None else None,
            a.distinct)
            for a in node.aggs)
        child = self.run(node.child)
        self.operator_span("aggregate", inputCapacity=child.capacity,
                           inputRows=self._known_rows(node.child))
        return self.aggregate_batch(node, child, aggs)

    def _note_strategy(self, op: str, strategy: str, kind: str) -> None:
        """Record the strategy an operator actually ran with: the
        per-query EXPLAIN/operator_stats surface plus the
        {agg,join}_strategy_decisions counter families."""
        self.strategy_decisions[op] = strategy
        self.stamp_operator(strategy=strategy)
        from ..metrics import (AGG_STRATEGY_DECISIONS,
                               JOIN_STRATEGY_DECISIONS)
        if kind == "agg":
            AGG_STRATEGY_DECISIONS.inc(strategy=strategy)
        else:
            JOIN_STRATEGY_DECISIONS.inc(strategy=strategy)

    def aggregate_batch(self, node: L.AggregateNode, child: Batch, aggs):
        """One partial aggregation (the PARTIAL step)."""
        if node.strategy == "global":
            self._note_strategy("AggregateNode", "global", "agg")
            return global_aggregate(child, aggs)
        if node.strategy == "direct":
            self._note_strategy("AggregateNode", "direct", "agg")
            return direct_group_aggregate(child, node.group_keys,
                                          node.key_domains, aggs)
        capacity = min(node.out_capacity, child.capacity)   # groups <= rows
        # big inputs: pack all keys into one int64 so the sort has 2
        # operands — the general kernel's 2-per-key operand count makes
        # XLA TPU compiles explode at scale (see SORT_COMPILE_BUDGET)
        pack = None
        # pack when rows are big: the general kernel sorts ~2 operands
        # per key and XLA TPU sort compiles explode in operand count
        # with the rows (q10's 7-key GROUP BY was a >900s compile at
        # 131k rows). Up to SORT_GENERAL_ROWS they finish, and the
        # general kernel's statics do not depend on the data — a packed
        # layout's key bits do, so packing small per-split batches
        # compiled one program per split (q18)
        if not any(a.distinct for a in aggs) and node.group_keys and \
                child.capacity > SORT_GENERAL_ROWS:
            from ..ops.aggregate import (in_place_output,
                                         key_pack_plan_words,
                                         packed_sort_group_aggregate)
            live = []

            def fetch(stats):       # the live count leads the vector
                vals = self.fetch_ints(node, "aggpackv", stats)
                live.append(vals[0])
                return vals
            pack = key_pack_plan_words(child, node.group_keys, fetch=fetch,
                                       aggs=aggs)
            if live:
                self.stamp_operator(inputRows=live[0])
            if live and live[0] <= SORT_SMALL_ROWS:
                # a mostly dead batch (a selective join's split): the
                # few live rows move to a small batch and take the
                # general kernel, whose statics do not depend on the data
                child = compact_batch(child, SORT_SMALL_ROWS)
                capacity = min(capacity, SORT_SMALL_ROWS)
                pack = None
        self._note_strategy("AggregateNode", "sort", "agg")
        retries = self.stats.agg_capacity_retries
        # where the plan found room for the aggregates' arguments in the
        # keys' sort word, the kernel carries them through its sort
        carried = pack[3] if pack is not None else None
        vmins, value_bits = (jnp.asarray(carried[0]), carried[1]) \
            if carried else (None, None)
        while True:
            if pack is not None:
                kmins, bits, splits = pack[:3]
                out = packed_sort_group_aggregate(
                    child, jnp.asarray(kmins), node.group_keys, bits,
                    aggs, capacity, splits, vmins, value_bits,
                    carried is not None and
                    in_place_output(capacity, child.capacity))
            else:
                out = sort_group_aggregate(child, node.group_keys, aggs,
                                           capacity)
            n_groups = self.fetch_ints(node, f"agggroups{capacity}",
                                       jnp.sum(out.live))[0]
            # in place (the value-carrying form, where the capacity is
            # near the input's) nothing can have been dropped
            if n_groups < capacity or out.capacity >= child.capacity:
                break
            capacity *= 4
            self.stats.agg_capacity_retries += 1
        self.stamp_operator(
            groups=n_groups, capacity=out.capacity,
            capacityRetries=self.stats.agg_capacity_retries - retries,
            valueBits=sum(carried[1]) if carried else 0,
            outputForm="in-place" if carried and
            out.capacity == child.capacity else "dense")
        if n_groups == 0 and not node.group_keys:
            # zero-key sort aggregation (global DISTINCT) over an empty
            # input: SQL still requires one output row (0 counts / NULL
            # sums) — duplicates are irrelevant on empty input, so the
            # plain global kernel supplies it
            plain = tuple(AggSpec(a.func, a.arg_index) for a in aggs)
            return global_aggregate(child, plain)
        return out

    def merge_group_aggregate(self, node: L.AggregateNode,
                              merged: Batch, merge_aggs,
                              capacity: int) -> Batch:
        """FINAL merge of grouped partial states (keys at 0..n_keys-1,
        mergeable states after) — shared by the chunked driver's
        PartialState and the spill tier's partial pages."""
        from ..ops.aggregate import (key_pack_plan_words,
                                     packed_sort_group_aggregate,
                                     sort_group_aggregate)
        n_keys = len(node.group_keys)
        keys = tuple(range(n_keys))
        # the same compile-cost rule as aggregate_batch: past
        # SORT_SMALL_ROWS the keys pack into int64 words so every sort
        # is (word, index)
        if n_keys and merged.capacity > SORT_SMALL_ROWS:
            # measured on this batch, never through the decision cache:
            # the spill tier merges several partitions under one node
            pack = key_pack_plan_words(merged, keys)
            if pack is not None:
                kmins, bits, splits = pack
                return packed_sort_group_aggregate(
                    merged, jnp.asarray(kmins), keys, bits, merge_aggs,
                    capacity, splits)
        return sort_group_aggregate(merged, keys, merge_aggs, capacity)

    # ---- uncorrelated scalar subqueries (fold to constants) ----------

    def fold_scalars(self, expr):
        """Replace ScalarSubqueryRef / InSubqueryRef with computed
        constants before tracing (Trino runs uncorrelated subqueries as
        separate stages; here the subplan executes eagerly and memoized)."""
        if expr is None:
            return None
        has_sub = any(isinstance(e, (ir.ScalarSubqueryRef,
                                     ir.InSubqueryRef))
                      for e in ir.walk(expr))
        if not has_sub:
            return expr

        def fn(e):
            if isinstance(e, ir.ScalarSubqueryRef):
                return ir.Literal(self.scalar_value(e), e.dtype)
            if isinstance(e, ir.InSubqueryRef):
                return self.fold_in_subquery(e)
            return None
        return ir.transform(expr, fn)

    @contextmanager
    def _fold_span(self, kind: str):
        """The `subquery-fold` span of a subquery this executor runs to
        fold it (not of a memoised answer): everything from the
        subquery's plan to its values on the host. `inputRows` and
        `putBytes` are what its scans read and put on the device (0: the
        columns were resident); the caller stamps `members` and
        `fetchedSlots` (the capacity of what came to the host). On a whole
        statement it lies under `execute`. In a worker's split loop it
        hangs under `worker-task` BESIDE the `split` lap it ran in, as
        the operators' spans do, says which (`split`), and is the
        thread's context meanwhile: the subquery's own `scan`,
        `aggregate` and `compile` spans nest under it, whole-statement
        form (it runs once a task, not once a split)."""
        tracer = tracing.current()
        if not tracer.enabled:
            yield None
            return
        split, self._operator_split = self._operator_split, None
        where = {} if split is None else {"split": split[1]}
        rows0, put0 = self.stats.rows_scanned, self.scan_put_bytes
        try:
            with tracer.span("subquery-fold", split and split[0],
                             kind=kind, **where) as sp:
                yield sp
                sp.attributes.update(
                    inputRows=self.stats.rows_scanned - rows0,
                    putBytes=self.scan_put_bytes - put0)
        finally:
            self._operator_split = split

    def fold_in_subquery(self, ref: ir.InSubqueryRef) -> ir.Expr:
        """Execute the subquery and fold x IN (...) to an `ir.InSet` over
        its distinct members (`member_set`), mapping varchar values into
        the probe's dictionary and injecting Kleene NULL when the
        subquery produced one (x IN S is NULL for unmatched x when S
        contains NULL)."""
        if ref not in self._scalar_cache:
            with self._fold_span("in") as sp:
                # the members come to the host: the live ones, not the
                # subquery's whole capacity (Q18's HAVING keeps hundreds
                # of 67M group slots). In a task's split loop too: the
                # fold runs once a task and ends in this fetch anyway,
                # so the live count's one sync, which chunk mode spares
                # a per-chunk loop, costs nothing here
                batch = self.run(ref.plan)
                live = None
                if self.chunk_mode and \
                        batch.capacity >= self.COMPACT_MIN_ROWS:
                    (live,) = self.fetch_ints(ref.plan, "complive",
                                              jnp.sum(batch.live))
                batch = self.maybe_compact(batch, live, node=ref.plan)
                arrays, valids = batch_to_numpy(batch)
                if sp is not None:
                    sp.attributes.update(members=len(arrays[0]),
                                         fetchedSlots=batch.capacity)
            vals, has_null = [], False
            arg_t = ref.arg.dtype
            from ..types import TypeKind as TK
            for v, ok in zip(arrays[0], valids[0]):
                if not ok:
                    has_null = True
                    continue
                v = v.item() if hasattr(v, "item") else v
                if arg_t.kind is TK.VARCHAR:
                    # translate through pools: sub code -> string -> probe
                    s = ref.sub_field.dictionary[int(v)]
                    pool = ref.arg_field.dictionary if ref.arg_field \
                        else None
                    if pool is None or s not in pool:
                        continue            # absent: can never match
                    v = pool.index(s)
                vals.append(v)
            self._scalar_cache[ref] = (tuple(sorted(set(vals))), has_null)
        vals, has_null = self._scalar_cache[ref]
        folded: ir.Expr = member_set(ref.arg, vals)
        if has_null:
            from ..types import BOOLEAN
            folded = ir.Logical("or", (folded,
                                       ir.Literal(None, BOOLEAN)))
        return folded

    def fold_scalars_tuple(self, exprs):
        return tuple(self.fold_scalars(e) for e in exprs)

    def bound_exprs(self, node: L.PlanNode, predicate, exprs):
        """((predicate, exprs) as a template, its values on the device)
        for the filter/project program `node` dispatches, made once a
        plan node: subqueries folded, literals taken out as operands
        (`ir.parametrise`) and put on the device. A task's 240 splits
        dispatch the same node, so they pass device arrays: no IR walk
        and no host-to-device transfer a split."""
        hit = self._bound_exprs.get(id(node))
        if hit is None:
            template, values = ir.parametrise(
                (self.fold_scalars(predicate),
                 self.fold_scalars_tuple(exprs)))
            self.stats.literal_slots += ir.slot_count(values)
            # uncommitted arrays: a mesh executor's sharded batch places
            # the program, the operands follow it
            values = jax.tree_util.tree_map(jnp.asarray, values)
            set_capacity = max(
                (len(e.members) for t in (template[0],) + template[1]
                 if t is not None for e in ir.walk(t)
                 if isinstance(e, ir.InSet)), default=0)
            # the node reference keeps its id from being reused
            hit = self._bound_exprs[id(node)] = (node, template, values,
                                                 set_capacity)
        _, template, values, set_capacity = hit
        if set_capacity:
            self.stats.in_set_probes += 1
        return template, values

    def in_set_capacity(self) -> int:
        """The largest member capacity among the set programs bound for
        the running statement or task (`inSetCapacity`); 0: none."""
        return max((capacity for *_, capacity
                    in self._bound_exprs.values()), default=0)

    def scalar_value(self, ref: ir.ScalarSubqueryRef):
        # keyed by the ref itself (hashes by plan identity) so the cache
        # keeps the plan object alive — id() reuse cannot alias entries
        if ref not in self._scalar_cache:
            with self._fold_span("scalar") as sp:
                batch = self.run(ref.plan)
                arrays, valids = batch_to_numpy(batch)
                if sp is not None:
                    sp.attributes.update(members=len(arrays[0]),
                                         fetchedSlots=batch.capacity)
            if len(arrays[0]) > 1:
                raise RuntimeError(
                    "scalar subquery returned more than one row")
            if len(arrays[0]) == 0 or not bool(valids[0][0]):
                val = None
            else:
                v = arrays[0][0]
                val = v.item() if hasattr(v, "item") else v
            self._scalar_cache[ref] = val
        return self._scalar_cache[ref]

    # compact when live rows fit in 1/SHRINK of capacity: every dead lane
    # still pays full price in the join's random gathers, while compaction
    # itself is cheap (ascending-index gathers are quasi-sequential HBM)
    COMPACT_SHRINK = 2
    # a batch under this is too small for a compaction to pay
    COMPACT_MIN_ROWS = 1 << 16

    def maybe_compact(self, batch: Batch,
                      live: Optional[int] = None,
                      node: Optional[L.PlanNode] = None) -> Batch:
        """Compact when live rows shrank enough. `live` should be passed
        when the caller already synced a row count (join totals): a
        jnp.sum fetch is a blocking device sync, so every avoidable one
        matters to end-to-end latency. `node` keys
        the cross-run decision cache when the count must be fetched."""
        if live is None:
            if batch.capacity < self.COMPACT_MIN_ROWS:
                return batch
            if self.chunk_mode:
                return batch          # the chunked loop stays sync-free
            live = self.fetch_ints(node, "complive",
                                   jnp.sum(batch.live))[0]
        new_cap = compaction_capacity(live, batch.capacity)
        if new_cap * self.COMPACT_SHRINK <= batch.capacity:
            self.stats.dynamic_filter_compactions += 1
            return compact_batch(batch, new_cap)
        return batch

    def run_join(self, node: L.JoinNode) -> Batch:
        probe = self.run(node.left)
        # oversized build sides stream chunk-wise into the dense LUT
        # instead of materializing on device (spill tier v2; the decision
        # must precede running the build child)
        if self.stream_build_bytes:
            est = self._estimate_build_bytes(node.right)
            if est is not None and est > self.stream_build_bytes:
                from .chunked import streaming_build_join
                out = streaming_build_join(self, node, probe)
                if out is not None:
                    return out
        build = self.run(node.right)
        self.operator_span("join", kind=node.kind,
                           probeCapacity=probe.capacity,
                           probeRows=self._known_rows(node.left),
                           buildCapacity=build.capacity,
                           buildRows=self._known_rows(node.right),
                           domain=node.build_key_domain)
        # >2-column keys (or values past 2^31) overflow the kernels'
        # fixed 32-bit-per-column packing: range-compress both sides'
        # keys into ONE appended int64 column (shared min/max so equality
        # is preserved), run the join single-key, strip the extras after
        packed = self.pack_join_keys(probe, build, node.left_keys,
                                     node.right_keys, node=node)
        if packed is not None:
            probe2, build2, pk, bk = packed
            import dataclasses as _dc
            residual2 = node.residual
            if residual2 is not None:
                # kernel layout gains the packed column after the probe
                # columns: shift build-side references right by one
                n_probe = len(probe.columns)

                def _shift(e):
                    if isinstance(e, ir.ColumnRef) and \
                            e.index >= n_probe:
                        return ir.ColumnRef(e.index + 1, e.dtype, e.name)
                    return None
                residual2 = ir.transform(node.residual, _shift)
            node2 = _dc.replace(node, left_keys=pk, right_keys=bk,
                                residual=residual2,
                                build_key_domain=None)
            out = self._run_join_inner(node2, probe2, build2)
            return _strip_packed_columns(out, node, len(probe.columns),
                                         len(build.columns))
        return self._run_join_inner(node, probe, build)

    def _estimate_build_bytes(self, node: L.PlanNode) -> Optional[int]:
        """Size of a Scan/Filter(Scan) build side, for the streaming
        decision (shape must match streaming_build_join's support)."""
        scan = node.child if isinstance(node, L.FilterNode) else node
        if not isinstance(scan, L.ScanNode):
            return None
        try:
            rows = self.catalog.get_table(scan.catalog, scan.schema_name,
                                          scan.table).num_rows
        except Exception:        # noqa: BLE001 — stats probe only
            return None
        return rows * max(1, len(scan.column_indices)) * 8

    def pack_join_keys(self, probe: Batch, build: Batch, pkeys, bkeys,
                       node=None):
        """None when the fixed 32-bit packing is safe (<=2 in-range
        columns); else (probe', build', probe_keys', build_keys') with
        one range-compressed key column appended to each side."""
        if len(pkeys) <= 1:
            return None
        if len(pkeys) == 2:
            # the fixed packing is fine when trailing key values fit 31
            # bits — ONE fused fetch for the check
            stats = []
            for side, keys in ((build, bkeys), (probe, pkeys)):
                for ki in keys[1:]:
                    col = side.columns[ki]
                    m = side.live & col.valid
                    d = col.data.astype(jnp.int64)
                    stats.append(jnp.min(jnp.where(m, d, 0)))
                    stats.append(jnp.max(jnp.where(m, d, 0)))
            vals = self.fetch_ints(node, "jpack31", *stats)
            if all(0 <= int(vals[i]) and int(vals[i + 1]) < (1 << 31)
                   for i in range(0, len(vals), 2)):
                return None
        stats = []
        big = jnp.iinfo(jnp.int64)
        for side, keys in ((probe, pkeys), (build, bkeys)):
            for ki in keys:
                col = side.columns[ki]
                m = side.live & col.valid
                d = col.data.astype(jnp.int64)
                stats.append(jnp.min(jnp.where(m, d, big.max)))
                stats.append(jnp.max(jnp.where(m, d, big.min)))
        vals = self.fetch_ints(node, "jpack", *stats)
        k = len(pkeys)
        kmins, bits, total = [], [], 0
        for i in range(k):
            lo = min(int(vals[2 * i]), int(vals[2 * (k + i)]))
            hi = max(int(vals[2 * i + 1]), int(vals[2 * (k + i) + 1]))
            if hi < lo:
                lo, hi = 0, 0
            b = max(2, int(hi - lo + 3).bit_length())
            kmins.append(lo)
            bits.append(b)
            total += b
        if total > 62:
            raise RuntimeError(
                "multi-column join key spans exceed 62 packed bits")
        kmins_d = jnp.asarray(np.asarray(kmins, dtype=np.int64))
        bits = tuple(bits)
        probe2 = _append_packed_key(probe, kmins_d, pkeys, bits)
        build2 = _append_packed_key(build, kmins_d, bkeys, bits)
        return (probe2, build2, (len(probe.columns),),
                (len(build.columns),))

    def _run_join_inner(self, node: L.JoinNode, probe: Batch,
                        build: Batch) -> Batch:
        domain = node.build_key_domain
        if self.chunk_mode and node.kind == "inner" and \
                node.build_unique and domain is not None:
            # a split's join over the task's pinned build: a probe key
            # outside the build's key range has no LUT entry, so the
            # LUT's miss IS the dynamic filter's range test (and chunk
            # mode compacts on neither): no eager op runs in front
            out = self._chunk_lut_join(node, probe, build, domain)
            if out is not None:
                self.stats.lut_filtered_joins += 1
                self.stamp_operator(in_splits=True, dynamicFilter="lut")
                return out
        probe = self.apply_dynamic_filter(node, probe, build)
        if node.kind == "mark":
            return self.run_mark_join(node, probe, build)
        if node.kind in ("semi", "anti"):
            return self.run_membership_join(node, probe, build)
        probe = self.maybe_compact(probe, node=node)
        if node.build_unique:
            out = self.try_unique_join(node, probe, build, domain)
            if out is not None:
                return out            # already compacted (fused sync)
            # planner's uniqueness proof was wrong — degrade gracefully
            self.stats.join_fallbacks += 1
        cap = probe.capacity
        while True:
            out, total, oob = join_expand(probe, build, node.left_keys,
                                          node.right_keys, node.kind,
                                          cap, domain)
            total, oob = self.fetch_ints(node, f"expand{cap}:{domain}",
                                         total, oob)
            if oob > 0:             # stale stats: keys escaped the domain
                domain = None
                self.stats.join_domain_fallbacks += 1
                continue
            if total <= cap:
                self._note_strategy("JoinNode", "expand", "join")
                # `total` IS the live row count: reuse it instead of
                # paying a second device sync inside maybe_compact
                return self.maybe_compact(out, live=total) \
                    if node.kind == "inner" else out
            cap = bucket_capacity(total)  # coarse: caches across runs
            self.stats.join_expansion_retries += 1

    def try_unique_join(self, node: L.JoinNode, probe: Batch,
                        build: Batch, domain) -> Optional[Batch]:
        """Unique-build fast paths. Small inner/left joins take the
        gather-free sort-merge kernel (the fastest primitive on TPU is
        the sort network); a large inner join finds its build rows
        first (one merge sort of both sides, or the dense LUT's gather)
        and compacts before it gathers payloads; dense LUT / sorted
        probing remain for the rest. None = build had duplicate keys
        (caller expands)."""
        # Compile-cost gate for the multi-operand merge sort, measured in
        # SORT OPERAND-ELEMENTS (rows x sort operands, where each column
        # contributes data+valid operands). Measured on v5e: ~240M
        # operand-elements compile in ~2 min, ~190M in the merge kernel
        # ran past 10 MINUTES (its flood scans compound the sort), while
        # <64M compiles in tens of seconds. Above the gate the dense-LUT
        # /gather path carries the join: it compiles in seconds at any
        # size (9.4s at 60M measured) and runs at gather speed.
        # chunk mode: build+validate the dense LUT once per pinned build,
        # then probe every chunk sync-free (see _chunk_lut_join; an inner
        # join was there first, _run_join_inner, and is here because its
        # build failed validation)
        if self.chunk_mode and domain is not None and \
                node.kind == "left":
            out = self._chunk_lut_join(node, probe, build, domain)
            if out is not None:
                return out
        n_sort_ops = 2 * (len(probe.columns) + len(build.columns)) + 4
        merge_ok = self.enable_merge_join and \
            n_sort_ops <= MAX_SORT_OPERANDS and \
            (probe.capacity + build.capacity) <= SORT_SMALL_ROWS
        # every branch fuses (dup[, oob], live-count) into ONE device
        # fetch, then compacts with the known count — one device sync
        # per join instead of three
        if node.kind in ("inner", "left") and merge_ok and \
                len(probe.columns) <= 63 and len(build.columns) <= 63:
            out, dup = join_unique_build_merge(
                probe, build, node.left_keys, node.right_keys, node.kind)
            dup, live = self.fetch_ints(node, "jmerge", dup,
                                        jnp.sum(out.live))
            if dup == 0:
                self._note_strategy("JoinNode", "sort-merge", "join")
                return self.maybe_compact(out, live=live)
            return None
        if node.kind == "inner" and probe.capacity > SORT_SMALL_ROWS and \
                (domain is not None or not self.chunk_mode):
            # two-phase: find each probe row's build row, THEN decide —
            # a selective join compacts matched rows before paying
            # per-column build gathers at full probe capacity (gathers
            # are the dense join's whole cost). Phase 1 is one merge
            # sort of both sides where its word fits and the capacities
            # say it beats the LUT's gather, else the LUT. (A chunked
            # loop's join with no domain stays on the sorted kernel: its
            # chunks keep one shape.)
            from ..ops.join import (dense_join_compacted, dense_probe,
                                    merge_probe, merge_probe_form)
            word_bits = merge_probe_form(probe.capacity, build.capacity,
                                         domain)
            phase1 = None
            if word_bits is not None:
                phase1 = merge_probe(probe, build, node.left_keys,
                                     node.right_keys)
                tag, strategy = "jmerge2", "sort-probe"
            elif domain is not None:
                phase1 = dense_probe(probe, build, node.left_keys,
                                     node.right_keys, domain)
                tag, strategy = f"jdense2:{domain}", "dense-lut"
            if phase1 is not None:
                # escaped: build keys outside the domain (the LUT) or
                # wider than the word's key field (the merge)
                words, rows, *counts = phase1
                dup, escaped, live = self.fetch_ints(node, tag, *counts)
                if escaped != 0:
                    self.stats.join_domain_fallbacks += 1
                    domain = None
                elif dup != 0:
                    return None
                else:
                    new_cap = compaction_capacity(live, probe.capacity)
                    if new_cap * self.COMPACT_SHRINK <= probe.capacity:
                        self._note_strategy("JoinNode", strategy, "join")
                        if word_bits is not None:
                            self.stamp_operator(wordBits=word_bits,
                                                sortRows=words.shape[0])
                        self.stats.dynamic_filter_compactions += 1
                        return dense_join_compacted(
                            probe, words, rows, build, node.left_keys,
                            node.right_keys, new_cap)
                    if word_bits is None:
                        # unselective, and the LUT form has vouched for
                        # the domain: one shot at the probe's capacity
                        self._note_strategy("JoinNode", "dense-lut",
                                            "join")
                        return join_unique_build_dense(
                            probe, build, node.left_keys,
                            node.right_keys, node.kind, domain)[0]
                    # unselective after a merge: the one-shot kernels
                    # below, which check the domain themselves
        if domain is not None:
            out, dup, oob = join_unique_build_dense(
                probe, build, node.left_keys, node.right_keys,
                node.kind, domain)
            dup, oob, live = self.fetch_ints(
                node, f"jdense:{domain}", dup, oob, jnp.sum(out.live))
            if oob == 0:
                if dup != 0:
                    return None
                self._note_strategy("JoinNode", "dense-lut", "join")
                return self.maybe_compact(out, live=live)
            self.stats.join_domain_fallbacks += 1
        out, dup = join_unique_build(probe, build, node.left_keys,
                                     node.right_keys, node.kind)
        dup, live = self.fetch_ints(node, "jsorted", dup,
                                    jnp.sum(out.live))
        if dup == 0:
            self._note_strategy("JoinNode", "sorted", "join")
            return self.maybe_compact(out, live=live)
        return None

    def _chunk_lut_join(self, node: L.JoinNode, probe: Batch,
                        build: Batch, domain: int) -> Optional[Batch]:
        """Chunk-mode unique-build join: the dense LUT is built and
        validated ONCE per pinned build side, cached for the life of the
        chunked loop, and every subsequent probe chunk joins sync-free
        at probe capacity (no compaction). None = validation failed
        (caller takes the general fallbacks) or kernel limits don't
        apply.

        The LUT's word carries the build's payload where it fits one
        (`_packed_chunk_lut`): a probe is then one gather, where the
        row-id form pays one for the row and one for each payload
        column and the validity word. The join's span says which form
        ran (`lutForm`, `wordBits`) and why not the packed one
        (`packRefused`)."""
        if len(probe.columns) > 63 or len(build.columns) > 63:
            return None
        key = (id(node), domain)
        rec = self._chunk_lut_cache.get(key)
        if rec is None:
            rec = self._packed_chunk_lut(node, build, domain)
            if rec == "validation":
                # the row-id LUT's own checks would say the same
                rec = (None, None, {"packRefused": rec})
            elif isinstance(rec, str):
                rec = self._row_chunk_lut(node, build, domain, rec)
            self._chunk_lut_cache[key] = rec
        lut, packed, said = rec
        self.stamp_operator(in_splits=True, **said)
        if lut is None:
            return None
        from ..ops.join import dense_join_packed, dense_join_with_lut
        self.stats.chunk_lut_joins += 1
        if packed is None:
            self._note_strategy("JoinNode", "dense-lut", "join")
            return dense_join_with_lut(probe, build, lut, node.left_keys,
                                       node.right_keys, node.kind)
        los, meta, out_dtypes = packed
        self.stats.packed_lut_joins += 1
        self._note_strategy("JoinNode", "dense-lut-packed", "join")
        return dense_join_packed(probe, lut, los, node.left_keys, meta,
                                 node.right_keys[0], out_dtypes, node.kind)

    def _packed_chunk_lut(self, node: L.JoinNode, build: Batch,
                          domain: int):
        """A pinned build's value-packed LUT as `_chunk_lut_cache` keeps
        it, (lut, (los, meta, out dtypes), what the join's span says),
        or the first reason there is none (`key`, `columns`, `float`:
        ops.join.pack_refusal, with no fetch; `bits`: plan_packed_word;
        `validation`: a duplicate or out-of-domain build key). Two
        fetches, once a task: the payload's ranges, then the checks."""
        from ..ops.join import (dense_build_packed_lut, pack_refusal,
                                packed_word_dtype, payload_ranges,
                                plan_packed_word)
        refused = pack_refusal(build, node.right_keys)
        if refused is not None:
            return refused
        ranges = np.asarray(payload_ranges(build, node.right_keys))
        plan = plan_packed_word(build, node.right_keys[0], ranges[0::2],
                                ranges[1::2])
        if plan is None:
            return "bits"
        meta, los, bits = plan
        los = self._place(los)
        lut, expected, oob, occupied = dense_build_packed_lut(
            build, node.right_keys, domain, meta, packed_word_dtype(bits),
            los)
        expected, oob, occupied = (int(v) for v in np.asarray(
            jnp.stack((expected, oob, occupied))))
        if oob != 0 or occupied != expected:
            self.stats.join_domain_fallbacks += oob > 0
            return "validation"
        return (lut, (los, meta,
                      tuple(str(c.data.dtype) for c in build.columns)),
                {"lutForm": "packed", "wordBits": lut.dtype.itemsize * 8})

    def _row_chunk_lut(self, node: L.JoinNode, build: Batch, domain: int,
                       refused: str):
        """A pinned build's row-id LUT as `_chunk_lut_cache` keeps it,
        (lut, None, what the join's span says: why it is not the packed
        one), with no LUT after a duplicate or out-of-domain build key
        (one fetch)."""
        from ..ops.join import dense_build_lut
        lut, dup, oob = dense_build_lut(build, node.right_keys, domain)
        dup, oob = (int(v) for v in np.asarray(jnp.stack(
            (dup.astype(jnp.int64), oob))))
        if dup == 0 and oob == 0:
            return (lut, None, {"lutForm": "rows", "wordBits": 32,
                                "packRefused": refused})
        self.stats.join_domain_fallbacks += oob > 0
        return (None, None, {"packRefused": refused})

    def enter_chunk_mode(self) -> None:
        self.chunk_mode = True

    def exit_chunk_mode(self) -> None:
        self.chunk_mode = False
        self._chunk_lut_cache.clear()

    def apply_dynamic_filter(self, node: L.JoinNode, probe: Batch,
                             build: Batch) -> Batch:
        """Dynamic filtering (server/DynamicFilterService.java:103 +
        operator/DynamicFilterSourceOperator): the build side's key range
        prunes probe rows before the join. TPU adaptation: the filter is a
        live-mask AND (free), and when it kills most of the probe the
        batch is compacted to a smaller capacity so every downstream
        kernel (sort/join/agg) runs at the reduced size — the analog of
        Trino skipping probe splits entirely.

        Skipped for anti joins (they keep non-matching rows), left joins
        (outer rows survive), and mark joins (non-matching rows carry
        mark=false)."""
        if not self.enable_dynamic_filtering:
            return probe
        if node.kind in ("anti", "left", "mark") or node.null_aware:
            return probe
        # eager ops on the build's key range, in every split of a
        # worker's task: a span of their own inside `join`
        depth = len(self._open_operators)
        self.operator_span("dynamic-filter")
        try:
            return self._dynamic_filter(node, probe, build)
        finally:
            self._close_operators(depth)
            self.stamp_operator(in_splits=True, dynamicFilter="range")

    def _dynamic_filter(self, node: L.JoinNode, probe: Batch,
                        build: Batch) -> Batch:
        for pk_i, bk_i in zip(node.left_keys, node.right_keys):
            bk = build.columns[bk_i]
            m = build.live & bk.valid
            info = jnp.iinfo(bk.data.dtype) if \
                jnp.issubdtype(bk.data.dtype, jnp.integer) else None
            if info is None:
                continue
            kmin = jnp.min(jnp.where(m, bk.data, info.max))
            kmax = jnp.max(jnp.where(m, bk.data, info.min))
            pk = probe.columns[pk_i]
            keep = pk.valid & (pk.data >= kmin) & (pk.data <= kmax)
            probe = probe.with_live(probe.live & keep)
        if probe.capacity >= (1 << 16) and not self.chunk_mode:
            # small probes skip the sync; so does the chunked loop (the
            # range mask above still applies — only compaction needs the
            # row-count round trip)
            live = self.fetch_ints(node, "dflive",
                                   jnp.sum(probe.live))[0]
            new_cap = compaction_capacity(live, probe.capacity)
            if new_cap * 4 <= probe.capacity:
                self.stats.dynamic_filter_compactions += 1
                probe = compact_batch(probe, new_cap)
        return probe

    def run_mark_join(self, node: L.JoinNode, probe: Batch,
                      build: Batch) -> Batch:
        """EXISTS truth as an appended boolean column (JoinNode.Type.MARK
        in the reference): every probe row survives; the mark powers
        disjunctive EXISTS filters downstream. Build duplicates are
        irrelevant (membership semantics)."""
        domain = node.build_key_domain
        if node.residual is None:
            out = None
            if domain is not None:
                dout, _dup, oob = join_unique_build_dense(
                    probe, build, node.left_keys, node.right_keys,
                    "semi", domain)
                if self.fetch_ints(node, f"markoob:{domain}",
                                   oob)[0] == 0:
                    out = dout
                else:
                    self.stats.join_domain_fallbacks += 1
            if out is None:
                out, _dup = join_unique_build(
                    probe, build, node.left_keys, node.right_keys, "semi")
            mark = out.live          # live & matched
        else:
            residual = self.fold_scalars(node.residual)
            cap = probe.capacity
            while True:
                mark, total, oob = join_mark(
                    probe, build, node.left_keys, node.right_keys,
                    residual, cap, domain)
                total, oob = self.fetch_ints(
                    node, f"markexp{cap}:{domain}", total, oob)
                if oob > 0:
                    domain = None
                    self.stats.join_domain_fallbacks += 1
                    continue
                if total <= cap:
                    break
                cap = bucket_capacity(total)
                self.stats.join_expansion_retries += 1
            mark = probe.live & mark
        return Batch(probe.columns +
                     (Column(mark, jnp.ones_like(mark)),), probe.live)

    def run_membership_join(self, node: L.JoinNode, probe: Batch,
                            build: Batch) -> Batch:
        """semi/anti joins. Build duplicates are irrelevant (membership);
        residuals go through the mark-join expansion kernel."""
        if node.null_aware:
            # NOT IN: any NULL in the subquery output -> no row can pass
            bk = build.columns[node.right_keys[0]]
            if self.fetch_ints(node, "nullaware",
                               jnp.any(build.live & ~bk.valid))[0]:
                return probe.with_live(jnp.zeros_like(probe.live))
        domain = node.build_key_domain
        if node.residual is None:
            if domain is not None:
                out, _dup, oob = join_unique_build_dense(
                    probe, build, node.left_keys, node.right_keys,
                    node.kind, domain)
                if self.fetch_ints(node, f"memoob:{domain}",
                                   oob)[0] == 0:
                    self._note_strategy("JoinNode", "dense-lut", "join")
                    return out
                self.stats.join_domain_fallbacks += 1
            out, _dup = join_unique_build(probe, build, node.left_keys,
                                          node.right_keys, node.kind)
            self._note_strategy("JoinNode", "sorted", "join")
            return out
        residual = self.fold_scalars(node.residual)
        cap = probe.capacity
        while True:
            mark, total, oob = join_mark(probe, build, node.left_keys,
                                         node.right_keys, residual, cap,
                                         domain)
            total, oob = self.fetch_ints(
                node, f"memexp{cap}:{domain}", total, oob)
            if oob > 0:
                domain = None
                self.stats.join_domain_fallbacks += 1
                continue
            if total <= cap:
                break
            cap = bucket_capacity(total)
            self.stats.join_expansion_retries += 1
        live = probe.live & (mark if node.kind == "semi" else ~mark)
        return probe.with_live(live)

    def result_to_host(self, root: L.OutputNode, batch: Batch):
        """Compact + return (names, columns, valids) on host. Selective
        results compact on device first so the host fetch moves live rows,
        not padded capacity (a 60M-capacity TopN result is 10 rows).
        Small batches skip the live-count probe: it is one more device
        sync and the fetch moves little data anyway."""
        # mid-size results only probe when the decision cache can absorb
        # the sync on re-execution (deterministic subtree); one-shot
        # mutable-catalog queries keep the old 64K threshold — for them
        # the probe costs a round trip and the fetch moves little data
        probe_floor = (1 << 13) if self.decisions_cacheable(root) and \
            self.memo_structure_key(root) is not None else (1 << 16)
        if batch.columns and batch.capacity >= probe_floor:
            live = self.fetch_ints(root, "resultlive",
                                   jnp.sum(batch.live))[0]
            new_cap = compaction_capacity(live, batch.capacity)
            if new_cap * 2 <= batch.capacity:
                batch = compact_batch(batch, new_cap)
        arrays, valids = batch_to_numpy(batch)
        # decisions taken during result materialization (resultlive)
        # happen after execute()'s save — persist them too
        self.save_decisions()
        return list(root.names), arrays, valids


import functools

from .profiler import recorded_jit


def explain_strategy_lines(root: L.PlanNode, executor) -> List[str]:
    """EXPLAIN's `agg strategy:` / `join strategy:` verdict lines: what
    the per-operator strategy gate will pick for this plan (pre-order,
    matching explain_text). After EXPLAIN ANALYZE the executor's
    recorded decision is appended when it differs from the prediction
    (e.g. a dense-lut plan whose stale stats sent it to sort-merge)."""
    lines: List[str] = []
    ran = executor.strategy_decisions

    def verdict(predicted: str, op: str) -> str:
        actual = ran.get(op)
        if actual is not None and actual != predicted.split(" ")[0]:
            return f"{predicted} [ran: {actual}]"
        return predicted

    def walk(node: L.PlanNode) -> None:
        if isinstance(node, L.AggregateNode) and \
                node.strategy != "global":
            if node.strategy == "direct":
                g = 1
                for d in node.key_domains:
                    g *= d
                pred = f"direct ({g} groups)"
            else:
                pred = f"sort (est {node.out_capacity} groups)"
            lines.append("agg strategy: "
                         + verdict(pred, "AggregateNode"))
        elif isinstance(node, L.JoinNode):
            if node.build_key_domain is not None and node.build_unique:
                pred = f"dense-lut (domain {node.build_key_domain})"
            elif not node.build_unique:
                pred = "expand"
            else:
                pred = "sort-merge"
            lines.append("join strategy: " + verdict(pred, "JoinNode"))
        for c in L.children(node):
            walk(c)

    walk(root)
    return lines


def member_set(arg: ir.Expr, vals) -> ir.InSet:
    """arg IN vals (distinct, ascending, in arg's physical rep) as one
    array operand of a program keyed by their bucket capacity, not by
    their number: Q18's 69 to 666 members share one, and so does no
    member at all."""
    from ..types import BIGINT
    vals = tuple(vals)
    pad = bucket_capacity(len(vals)) - len(vals)
    return ir.InSet(arg, vals + (vals[-1:] or (0,)) * pad,
                    ir.Literal(len(vals), BIGINT))


@recorded_jit(static_argnums=(2, 3))
def filter_project_fused(batch: Batch, values, exprs, predicate) -> Batch:
    """Project-then-filter in one jit (Filter over Project), keyed by
    the expressions' shape and fed their literals as `filter_project`."""
    projected = project(batch, exprs, values)
    return apply_filter(projected, predicate, values)


def remap_codes(batch: Batch, remaps) -> Batch:
    """Translate dictionary codes through per-column LUTs (merged set-op
    pools). One device gather per remapped column."""
    if all(r is None for r in remaps):
        return batch
    cols = []
    for col, rm in zip(batch.columns, remaps):
        if rm is None:
            cols.append(col)
        else:
            lut = jnp.asarray(np.asarray(rm, dtype=np.int32))
            cols.append(Column(jnp.take(lut, col.data, axis=0), col.valid))
    return Batch(tuple(cols), batch.live)


# XLA TPU compile cost for lax.sort blows up in BOTH dimensions
# (measured v5e): rows x operands — 60M x 4 operands = 119s, 60M x 12 =
# 385s — and operand count alone: a 1.57M x 22-operand sort ran past 8
# MINUTES while a 22-argument non-sort kernel compiled in 1.4s. So big
# sorts must stay under an operand-element budget AND a hard operand
# cap; above either, sort the minimum. A gather through the sort's
# permutation is the expensive way to move a payload (1.33 s a 60M-row
# column whatever it holds, against 0.17 s for a one-operand 60M-row
# int64 sort: PERF.md, PR 33), so what fits beside the keys in ONE
# int64 word rides the sort (`live_first_order`, `lsd_word_sort`'s row
# position, the value-carrying form of `packed_sort_group_aggregate`)
# and only what does not is gathered.
SORT_COMPILE_BUDGET = 1 << 26
MAX_SORT_OPERANDS = 12
# rows below which a multi-operand sort still compiles in seconds;
# above it every sort should be (packed key, index) or argsort+gather.
# Set from the installed compiler (libtpu 0.0.34, compile seconds for a
# described v5e): a 12-operand stable sort takes 2 s at 2,048 rows, 11 s
# at 4,096, 44 s at 6,144, 184 s at 16,384; the 10-operand sort of a
# 4-column compaction took 193 s at 262,144 rows and q3's 3-key ORDER BY
# 534 s at 1M. A (packed key, index) sort costs 16-48 s from 25,600 rows
# up and is nearly flat in rows after that.
SORT_SMALL_ROWS = 1 << 11
# input capacity up to which an aggregate and an ORDER BY take the
# general kernels all the same: their statics are the shapes alone,
# where a packed kernel's key bits are read from the data, so two
# statements of one template that keep other rows can need two programs
# (ROADMAP S11). It is the capacity a compaction out of a 60M-row batch
# lands on at the least (`batch.compaction_capacity`), so a selective
# join's few thousand rows meet one aggregate and one sort whatever the
# statement's literals kept. The price is the compile, once a shape.
SORT_GENERAL_ROWS = 1 << 13


def compact_batch(batch: Batch, new_capacity: int) -> Batch:
    """Move live rows (in order) into a smaller-capacity batch.
    Small shapes: ONE multi-operand stable sort by deadness + free
    slicing (the fastest primitive on TPU is the sort network,
    SURVEY.md §7 hard part 1). Large shapes: 2-operand argsort of
    deadness + per-column gathers, trading gather runtime for a compile
    that finishes (SORT_COMPILE_BUDGET).
    Caller guarantees new_capacity >= live count."""
    n_operands = 2 + 2 * len(batch.columns)
    if batch.capacity <= SORT_SMALL_ROWS and \
            n_operands <= MAX_SORT_OPERANDS:
        return _compact_sort(batch, new_capacity)
    return _compact_gather(batch, new_capacity)


@recorded_jit(static_argnums=(2, 3))
def _append_packed_key(batch: Batch, kmins, keys: tuple,
                       bits: tuple) -> Batch:
    """Append one int64 column packing the key columns by shared range
    compression (see pack_join_keys); valid = AND of the key validities,
    so NULL keys keep their never-match semantics."""
    packed = jnp.zeros(batch.capacity, dtype=jnp.int64)
    valid = jnp.ones(batch.capacity, dtype=jnp.bool_)
    for j, (ki, b) in enumerate(zip(keys, bits)):
        col = batch.columns[ki]
        norm = col.data.astype(jnp.int64) - kmins[j] + 1
        packed = (packed << b) | jnp.where(col.valid, norm, 0)
        valid = valid & col.valid
    return Batch(batch.columns + (Column(packed, valid),), batch.live)


def _strip_packed_columns(out: Batch, node: L.JoinNode, n_probe: int,
                          n_build: int) -> Batch:
    """Remove the appended key columns so the output matches
    node.output."""
    cols = list(out.columns)
    if node.kind in ("inner", "left"):
        # layout: probe cols + packed_p + build cols + packed_b
        del cols[n_probe + 1 + n_build]
        del cols[n_probe]
    elif node.kind == "mark":
        # probe cols + packed_p + mark
        del cols[n_probe]
    else:                               # semi/anti: probe cols + packed
        del cols[n_probe]
    return Batch(tuple(cols), out.live)


@recorded_jit(static_argnums=(1,))
def _compact_sort(batch: Batch, new_capacity: int) -> Batch:
    operands = [(~batch.live).astype(jnp.int8)]
    for c in batch.columns:
        operands.append(c.data)
        operands.append(c.valid)
    operands.append(batch.live)
    out = jax.lax.sort(tuple(operands), num_keys=1, is_stable=True)
    cols = []
    for i in range(len(batch.columns)):
        cols.append(Column(out[1 + 2 * i][:new_capacity],
                           out[2 + 2 * i][:new_capacity]))
    return Batch(tuple(cols), out[-1][:new_capacity])


@recorded_jit(static_argnums=(1,))
def _compact_gather(batch: Batch, new_capacity: int) -> Batch:
    idx = live_first_order(batch.live, new_capacity)
    cols = tuple(Column(jnp.take(c.data, idx, axis=0),
                        jnp.take(c.valid, idx, axis=0))
                 for c in batch.columns)
    return Batch(cols, jnp.take(batch.live, idx, axis=0))


@recorded_jit()
def concat_batches(a: Batch, b: Batch) -> Batch:
    """UNION ALL: columnwise concatenation on device (UnionNode lowering —
    Trino's union is a pass-through exchange, ours is one concat per
    column; capacity is the sum so no rows can drop)."""
    cols = tuple(
        Column(jnp.concatenate([ca.data, cb.data]),
               jnp.concatenate([ca.valid, cb.valid]))
        for ca, cb in zip(a.columns, b.columns))
    return Batch(cols, jnp.concatenate([a.live, b.live]))


# batches concatenated per program: XLA TPU compile time grows with the
# square of a program's parameter count (q1's 17-column partials: 3 s for
# 25 batches, 10 s for 60, 154 s for 240 — described-chip compiles)
CONCAT_FAN_IN = 16


def concat_all(batches) -> Batch:
    """Concatenate any number of same-layout batches, in order, as a
    tree of CONCAT_FAN_IN-ary programs: a level's calls share one
    compiled program, so N partial pages cost a handful of small
    compiles. (Folding them through the pairwise concat_batches
    compiled N-1 programs of growing shape — one per split of a
    split-streamed query; one N-ary program is a single compile, but of
    thousands of parameters.)"""
    batches = list(batches)
    while len(batches) > 1:
        batches = [concat_many(tuple(batches[i:i + CONCAT_FAN_IN]))
                   if i + 1 < len(batches) else batches[i]
                   for i in range(0, len(batches), CONCAT_FAN_IN)]
    return batches[0]


@recorded_jit()
def concat_many(batches: tuple) -> Batch:
    """N-ary columnwise concatenation in ONE program (see concat_all)."""
    n_cols = len(batches[0].columns)
    cols = tuple(
        Column(jnp.concatenate([b.columns[i].data for b in batches]),
               jnp.concatenate([b.columns[i].valid for b in batches]))
        for i in range(n_cols))
    return Batch(cols, jnp.concatenate([b.live for b in batches]))
