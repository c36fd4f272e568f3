"""Chunked-driver prefetch pipeline tests (round 14).

exec/chunked.py overlaps host decode+stage of chunk k+1 with device
compute of chunk k through a bounded double-buffered worker
(exec/prefetch.py PrefetchPipeline). The contracts under test:

- prefetch_depth=0 recovers the serial loop exactly (bit-exact rows);
- staged buffers are REVOCABLE memory-pool reservations tagged
  "scan-prefetch": pressure revokes them and the consumer silently
  re-decodes inline — correctness never depends on staging;
- chaos faults injected at the SCAN_PREFETCH point surface on the
  consumer thread as ordinary retryable failures, and the retry is
  bit-exact (0 wrong answers).

The fact cache is disabled throughout: device-resident fact tables
decode nothing per chunk, which bypasses the pipeline by design.
"""

import time

import numpy as np
import pytest

from trino_tpu.batch import batch_from_numpy
from trino_tpu.exec.prefetch import PrefetchPipeline
from trino_tpu.exec.session import Session
from trino_tpu.server.failureinjector import (RAISE, SCAN_PREFETCH,
                                              FailureInjector,
                                              InjectedFailure)

SQL = ("SELECT l_returnflag, count(*) AS c, sum(l_extendedprice) AS s "
       "FROM lineitem GROUP BY l_returnflag ORDER BY l_returnflag")


@pytest.fixture(scope="module")
def session():
    s = Session(default_schema="tiny")
    s.executor.enable_fact_cache = False     # force per-chunk decode
    s.execute("SET SESSION spill_chunk_rows = 8192")
    return s


def test_depth0_is_serial_and_pipeline_bit_exact(session):
    s = session
    s.execute("SET SESSION prefetch_depth = 0")
    serial = s.execute(SQL).rows
    spans0 = s.executor.chunk_spans
    assert spans0["chunks"] > 1              # the chunked path really ran
    assert spans0["prefetched"] == 0         # depth 0: no pipeline at all

    s.execute("SET SESSION prefetch_depth = 2")
    piped = s.execute(SQL).rows
    spans2 = s.executor.chunk_spans
    assert piped == serial
    assert spans2["prefetched"] == spans2["chunks"]

    # staged-buffer gauge must return to zero after the run
    from trino_tpu.metrics import SCAN_PREFETCH_BUFFERS
    assert SCAN_PREFETCH_BUFFERS.value() == 0


def test_chaos_fault_in_prefetch_is_retryable(session):
    s = session
    s.execute("SET SESSION prefetch_depth = 2")
    want = s.execute(SQL).rows
    inj = FailureInjector(seed=3)
    inj.inject(SCAN_PREFETCH, times=1, fault=RAISE)
    s.executor.failure_injector = inj
    try:
        with pytest.raises(InjectedFailure):
            s.execute(SQL)
        got = s.execute(SQL).rows            # retry: injection exhausted
    finally:
        s.executor.failure_injector = None
    assert got == want


def test_staged_buffers_revocable_under_pressure(session):
    ex = session.executor
    starts = [0, 8, 16]

    def decode(start):
        return batch_from_numpy([np.arange(start, start + 8,
                                           dtype=np.int64)])

    pipe = PrefetchPipeline(ex, starts, decode, depth=len(starts))
    try:
        deadline = time.time() + 5
        while len(pipe._staged) < len(starts) and time.time() < deadline:
            time.sleep(0.01)
        assert len(pipe._staged) == len(starts)
        # visible in the pool snapshot (the /v1/memory payload) as a
        # tagged revocable holder
        snap = ex.pool.snapshot()
        assert snap["revocable_holders"].get("scan-prefetch", 0) > 0
        freed = ex.pool.request_revocation(1 << 40)
        assert freed > 0
        assert not pipe._staged
        # the consumer re-decodes revoked chunks inline — same data
        for st in starts:
            got = np.asarray(pipe.next(st).columns[0].data)[:8]
            np.testing.assert_array_equal(
                got, np.arange(st, st + 8, dtype=np.int64))
    finally:
        pipe.close()
    assert ex.pool.snapshot()["revocable_holders"].get(
        "scan-prefetch", 0) == 0


def test_prefetch_composes_with_zone_pruning(session):
    """Chunk skipping (zone maps) and the pipeline stack: the pipeline
    only decodes the SURVIVING chunk list, and results stay bit-exact
    against serial-unpruned."""
    s = session
    s.execute("SET SESSION zone_map_rows = 8192")
    sql = ("SELECT count(*) AS c, sum(l_quantity) AS q FROM lineitem "
           "WHERE l_orderkey < 25000")
    s.execute("SET SESSION enable_zone_map_pruning = false")
    s.execute("SET SESSION prefetch_depth = 0")
    base = s.execute(sql).rows
    chunks_all = s.executor.chunk_spans["chunks"]
    s.execute("SET SESSION enable_zone_map_pruning = true")
    s.execute("SET SESSION prefetch_depth = 2")
    got = s.execute(sql).rows
    spans = s.executor.chunk_spans
    assert got == base
    assert spans["chunks"] < chunks_all      # zones skipped whole chunks
    assert spans["prefetched"] == spans["chunks"]
    s.execute("SET SESSION enable_zone_map_pruning = true")
