"""Mesh joins + batched dynamic filtering (tier-1, 8 devices).

The quick-tier guards for the mesh join surface: a join on the mesh is
the single-device ladder under GSPMD and must be bit-exact against the
single-chip executor with dynamic filtering on AND off, the TPC-DS q77
shape that used to deadlock the mesh (rendezvous.cc "only 7 of 8 arrived" — one tiny
cross-module all-reduce per filter bound) must complete with filtering
ON, and the pruned-row observability surface must light up. Reference
pattern: TestDynamicFiltering / AbstractTestJoinQueries on a
DistributedQueryRunner.
"""

import numpy as np
import pytest

from oracle import assert_rows_match, load_oracle, oracle_query
from trino_tpu.exec.session import Session
from trino_tpu.parallel.dist_executor import MeshExecutor
from trino_tpu.parallel.mesh import make_mesh

JOIN_AGG = """
    SELECT n_name, count(*) AS c
    FROM customer, nation
    WHERE c_nationkey = n_nationkey
    GROUP BY n_name ORDER BY c DESC, n_name"""

# selective build side: the dynamic filter's min/max bounds prune most
# probe rows before the exchange
SELECTIVE = """
    SELECT count(*) FROM lineitem, orders
    WHERE l_orderkey = o_orderkey AND o_totalprice > 500000"""

PROBE_ROWS = """
    SELECT l_orderkey, l_linenumber, o_totalprice
    FROM lineitem, orders
    WHERE l_orderkey = o_orderkey AND o_totalprice > 400000
    ORDER BY l_orderkey, l_linenumber"""


def mesh_session(n_devices=8, **props):
    s = Session(default_schema="tiny")
    s.executor = MeshExecutor(s.catalog, make_mesh(n_devices))
    for k, v in props.items():
        s.properties[k] = v
    return s


@pytest.fixture(scope="module")
def ref():
    return Session(default_schema="tiny")


@pytest.mark.parametrize("filtering", [True, False], ids=["df-on", "df-off"])
@pytest.mark.parametrize("sql", [JOIN_AGG, SELECTIVE],
                         ids=["join-agg", "selective"])
def test_mesh_join_rows_equal_single_chip(ref, sql, filtering):
    """The GSPMD join a mesh of chips runs == the single-chip executor,
    row for row, with the batched filter on and off (PROBE_ROWS: the
    next test)."""
    s = mesh_session(mesh_dynamic_filtering=filtering)
    assert s.execute(sql).rows == ref.execute(sql).rows


def test_probe_rows_bit_exact_filtering_on_vs_off(ref):
    """Distributed probe output must be IDENTICAL with the batched
    filter collectives on vs off — pruning is an optimization, never a
    semantics change (and off is the session escape hatch)."""
    on = mesh_session()
    off = mesh_session(mesh_dynamic_filtering=False)
    want = ref.execute(PROBE_ROWS).rows
    rows_on = on.execute(PROBE_ROWS).rows
    rows_off = off.execute(PROBE_ROWS).rows
    assert rows_on == want
    assert rows_off == want
    assert on.executor.stats.dynamic_filter_rows_pruned > 0
    assert off.executor.stats.dynamic_filter_rows_pruned == 0


def test_pruned_row_counters_nonzero_on_selective_join(ref):
    """The observability satellite: a selective join must move both the
    executor stat and the prometheus family."""
    from trino_tpu.metrics import DYNAMIC_FILTER_ROWS_PRUNED
    before = DYNAMIC_FILTER_ROWS_PRUNED.value()
    s = mesh_session()
    assert s.execute(SELECTIVE).rows == ref.execute(SELECTIVE).rows
    pruned = s.executor.stats.dynamic_filter_rows_pruned
    assert pruned > 0
    assert DYNAMIC_FILTER_ROWS_PRUNED.value() - before >= pruned


def test_run_scan_pads_odd_capacity_to_shard_multiple():
    """Satellite: a mesh whose size does not divide the 1024-row padding
    buckets (6 on the virtual 8-device host) must PAD and shard rather
    than silently staying single-device."""
    s = Session(default_schema="tiny")
    s.executor = MeshExecutor(s.catalog, make_mesh(6))
    ref_count = Session(default_schema="tiny").execute(
        "SELECT count(*) FROM lineitem").rows
    assert s.execute("SELECT count(*) FROM lineitem").rows == ref_count
    # the resident copy must be an exact shard multiple and actually
    # laid out across all 6 devices
    resident = s.executor.resident
    (live,) = [resident.get(k)[1] for k in resident.keys()
               if k[3] == "lineitem" and k[4] is None]
    assert live.shape[0] % 6 == 0
    assert len(live.sharding.device_set) == 6


def test_q77_completes_on_mesh_with_filtering_on():
    """The deadlock-class repro: TPC-DS q77 (five CTE join+agg arms,
    LEFT JOINs, ROLLUP) used to hang the virtual mesh when each filter
    bound dispatched its own collective. With the bounds batched into
    one program per join it must just run — filtering stays ON."""
    from tpcds_queries import QUERIES

    s = Session(default_cat="tpcds", default_schema="tiny")
    s.executor = MeshExecutor(s.catalog, make_mesh(8))
    assert s.executor.enable_dynamic_filtering
    assert s.executor.mesh_dynamic_filtering
    rows = s.execute(QUERIES[77]).rows
    assert 0 < len(rows) <= 100
    assert s.executor.stats.dynamic_filter_rows_pruned > 0
