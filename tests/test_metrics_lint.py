"""Observability-surface lints (tier-1 CI guards).

Two invariants the metrics/tracing layer depends on, enforced as tests so
they hold as the server grows:

1. Every `/v1/...` HTTP route must flow through the declarative ROUTES
   table (server/routes.py) — that is what guarantees each route has a
   pre-initialized `trino_tpu_http_requests_total{server,route}` counter.
   A handler with inline path literals would dodge the metrics surface,
   so the do_* dispatch methods are checked to be table-driven only.

2. Every pytest marker used under tests/ must be declared in pytest.ini
   (an undeclared marker silently deselects nothing and rots).
"""

import configparser
import inspect
import os
import re

from trino_tpu.metrics import HTTP_REQUESTS
from trino_tpu.server import coordinator, worker
from trino_tpu.server.routes import route_label

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(TESTS_DIR)

SERVERS = (
    (coordinator, coordinator._Handler),
    (worker, worker._WorkerHandler),
)


def test_every_route_has_a_preinitialized_counter():
    """A cold server's /v1/metrics must already list every route at 0 —
    new routes added to ROUTES get this for free via register_routes."""
    for module, _handler in SERVERS:
        for method, pattern, *_ in module.ROUTES:
            label = route_label(method, pattern)
            assert HTTP_REQUESTS.has_sample(
                server=module.SERVER_NAME, route=label), \
                f"{module.__name__}: route {label} has no counter sample"


def test_route_handlers_exist_and_are_complete():
    for module, handler in SERVERS:
        for method, pattern, fn_name, _auth in module.ROUTES:
            assert callable(getattr(handler, fn_name, None)), \
                f"{module.__name__}: ROUTES references missing " \
                f"{fn_name}"
            assert method in ("GET", "POST", "DELETE", "PUT")


def test_no_inline_route_dispatch_outside_the_table():
    """do_GET/do_POST/... must stay pure table dispatchers: any inline
    '/v1' literal or parts[...] comparison in them means a route was
    added OUTSIDE the ROUTES table — invisible to the request counters.
    That is exactly the regression this lint exists to catch."""
    for module, handler in SERVERS:
        for do in ("do_GET", "do_POST", "do_DELETE", "do_PUT"):
            fn = getattr(handler, do, None)
            if fn is None:
                continue
            src = inspect.getsource(fn)
            assert "/v1" not in src, \
                f"{module.__name__}.{do} hardcodes a /v1 path — " \
                f"add the route to ROUTES instead"
            assert "parts[" not in src, \
                f"{module.__name__}.{do} matches path segments " \
                f"inline — add the route to ROUTES instead"


# acceptance-scraped metric families that MUST render on a cold server
# (pre-initialized at import — a missing sample reads as "metric never
# existed" to a scraper, round-9 memory surface included)
REQUIRED_FAMILIES = (
    "trino_tpu_memory_reserved_bytes",
    "trino_tpu_memory_revocable_bytes",
    "trino_tpu_memory_revocations_total",
    "trino_tpu_memory_accounting_errors_total",
    "trino_tpu_spill_bytes_total",
    "trino_tpu_spill_partitions_total",
    "trino_tpu_spill_retries_total",
    "trino_tpu_queries_killed_oom_total",
    "trino_tpu_exchange_backpressure_waits_total",
    "trino_tpu_pageserde_crc_failures_total",
    "trino_tpu_sched_task_retries_total",
    # round-10 performance-introspection surface: JIT-compile
    # observability, fenced device-time attribution, query history +
    # latency-regression detection
    "trino_tpu_jit_compiles_total",
    "trino_tpu_jit_cache_hits_total",
    "trino_tpu_jit_compile_seconds",
    "trino_tpu_operator_device_ms_total",
    "trino_tpu_operator_compile_ms_total",
    "trino_tpu_query_latency_regressions_total",
    "trino_tpu_query_history_records_total",
    # round-11 high-concurrency serving surface: plan/result caches,
    # cost-based CPU/TPU co-routing, micro-batched point dispatch
    "trino_tpu_plan_cache_hits_total",
    "trino_tpu_plan_cache_misses_total",
    "trino_tpu_plan_cache_evictions_total",
    "trino_tpu_result_cache_hits_total",
    "trino_tpu_result_cache_misses_total",
    "trino_tpu_result_cache_invalidations_total",
    "trino_tpu_router_decisions_total",
    "trino_tpu_microbatch_queries_total",
    "trino_tpu_microbatch_batches_total",
    # the per-operator strategy gate's decision counters
    "trino_tpu_agg_strategy_decisions_total",
    "trino_tpu_join_strategy_decisions_total",
    # the mesh executor's batched dynamic-filter pruning
    "trino_tpu_dynamic_filter_rows_pruned_total",
    # round-14 scan-path surface: zone-map pruning + the chunked-driver
    # prefetch pipeline
    "trino_tpu_scan_splits_pruned_total",
    "trino_tpu_scan_zones_pruned_total",
    "trino_tpu_scan_prefetch_buffers_in_use",
    "trino_tpu_scan_prefetch_stall_seconds",
    # round-15 elastic-membership / tenancy surface: lifecycle
    # transitions, drain handoffs, per-tenant accounting, soak SLOs
    "trino_tpu_node_lifecycle_transitions_total",
    "trino_tpu_splits_migrated_total",
    "trino_tpu_tenant_queries_total",
    "trino_tpu_soak_slo_violations_total",
    # round-16 cold-start surface: AOT prewarm accounting + the
    # shape-canonicalization distinct-shape gauge
    "trino_tpu_prewarm_compiles_total",
    "trino_tpu_prewarm_hits_total",
    "trino_tpu_compile_seconds_saved_total",
    "trino_tpu_jit_distinct_shapes",
    # round-18 exactly-once distributed writes: staged attempts, commit
    # outcomes, first-success-wins dedup, orphan sweeps
    "trino_tpu_write_tasks_total",
    "trino_tpu_write_attempts_deduped_total",
    "trino_tpu_write_commits_total",
    "trino_tpu_write_orphans_swept_total",
    # round-19 timeline + flight recorder: critical-path attribution and
    # the bounded telemetry ring's sample/eviction accounting
    "trino_tpu_timeline_queries_total",
    "trino_tpu_critical_path_seconds",
    "trino_tpu_telemetry_samples_total",
    "trino_tpu_telemetry_ring_evictions_total",
    # round-20 coordinator crash recovery: durable query ledger,
    # warm-standby promotion, resumption accounting
    "trino_tpu_coordinator_failovers_total",
    "trino_tpu_ledger_records_total",
    "trino_tpu_ledger_bytes",
    "trino_tpu_queries_resumed_total",
    # round-21 live query observability: heartbeat-streamed task stats,
    # stuck-query diagnosis, per-node host/device utilization
    "trino_tpu_task_heartbeats_total",
    "trino_tpu_live_stats_bytes_total",
    "trino_tpu_stuck_queries_diagnosed_total",
    "trino_tpu_node_busy_fraction",
    "trino_tpu_node_busy_ms_total",
    # round-22 query-lifetime enforcement: deadlines, cancellation
    # fan-out, orphan reaping, overload admission control
    "trino_tpu_queries_deadline_exceeded_total",
    "trino_tpu_queries_rejected_total",
    "trino_tpu_tasks_abandoned_total",
    "trino_tpu_cancel_propagations_total",
    "trino_tpu_retry_budget_exhausted_total",
    "trino_tpu_microbatch_follower_timeouts_total",
    "trino_tpu_backpressure_deadline_degrades_total",
)


def test_required_families_render_preinitialized():
    from trino_tpu.metrics import REGISTRY
    text = REGISTRY.render()
    for family in REQUIRED_FAMILIES:
        assert f"# TYPE {family} " in text, \
            f"{family} missing from a cold registry render"
        # at least one sample line (pre-initialized, not just declared)
        assert any(line.startswith(family) and " " in line
                   for line in text.splitlines()
                   if not line.startswith("#")), \
            f"{family} declared but renders no sample"


def test_markers_used_are_declared_in_pytest_ini():
    ini = configparser.ConfigParser()
    ini.read(os.path.join(REPO_ROOT, "pytest.ini"))
    declared = {line.strip().split(":")[0]
                for line in ini["pytest"]["markers"].splitlines()
                if line.strip()}
    builtin = {"parametrize", "skip", "skipif", "xfail", "usefixtures",
               "filterwarnings"}
    used = set()
    pat = re.compile(r"pytest\.mark\.([a-zA-Z_][a-zA-Z0-9_]*)")
    for fname in os.listdir(TESTS_DIR):
        if not fname.endswith(".py"):
            continue
        with open(os.path.join(TESTS_DIR, fname)) as f:
            used.update(pat.findall(f.read()))
    undeclared = used - declared - builtin
    assert not undeclared, \
        f"markers used but not declared in pytest.ini: {undeclared}"
