"""The decode->stage pipeline of a loop over a scan's pieces: the chunked
driver's chunks (exec/chunked.py) and a worker task's splits
(server/tasks.py `_run_splits`)."""

from __future__ import annotations

import queue
import threading
import time
from typing import Dict

from ..batch import Batch
from .device_cache import prof as _prof


class PrefetchPipeline:
    """Bounded double-buffered decode->stage pipeline ("Revisiting
    Co-Processing..." overlap, PAPERS.md): a worker thread decodes piece
    k+1 from host columns and stages its device transfer
    (batch_from_numpy) while the device computes piece k. A piece is
    named by its key in `starts`: a chunk's first row, a split's index.

    Every staged piece holds a REVOCABLE reservation in the memory pool,
    so arbitration/backpressure see the prefetch buffer and can reclaim
    it under pressure: a revoked piece is simply re-decoded inline by the
    consumer — correctness never depends on staging. Faults injected at
    the SCAN_PREFETCH chaos point raise out of next() on the consumer
    thread, surfacing as an ordinary retryable query/task failure.
    `depth` bounds how many pieces may sit decoded-but-unconsumed; at 0
    there is no thread and no staging, and next() decodes inline: the
    serial loop exactly."""

    def __init__(self, executor, starts, decode, depth: int):
        self.executor = executor
        self.pool = executor.pool
        self.decode = decode
        self.depth = depth
        self.decode_s = 0.0
        self.served = 0                     # pieces consumed from staging
        self._staged: Dict[int, tuple] = {}
        self._lock = threading.Lock()
        self._slots = threading.Semaphore(depth)
        self._queue: "queue.Queue[tuple]" = queue.Queue()
        self._stop = False
        if depth <= 0:
            return
        self._revocation = self.pool.register_revocation(
            self._revoke, tag="scan-prefetch")
        self._thread = threading.Thread(
            target=self._run, args=(list(starts),),
            name="scan-prefetch", daemon=True)
        self._thread.start()

    def _gauge(self) -> None:
        from ..metrics import SCAN_PREFETCH_BUFFERS
        SCAN_PREFETCH_BUFFERS.set(len(self._staged))

    def _revoke(self, target_bytes: int) -> int:
        """Memory-pool revocation callback: drop staged pieces (newest
        kept longest would not matter — the consumer re-decodes any
        missing piece inline)."""
        freed = 0
        with self._lock:
            for s in list(self._staged):
                if freed >= target_bytes:
                    break
                _, b = self._staged.pop(s)
                self.pool.free_revocable(b, tag="scan-prefetch")
                freed += b
            self._gauge()
        return freed

    def _decode_timed(self, start: int) -> Batch:
        t0 = time.monotonic()
        batch = self.decode(start)
        self.decode_s += time.monotonic() - t0
        return batch

    def _run(self, starts) -> None:
        # the feeder works for its executor: what it puts lands on that
        # executor's device (the thread's own default device)
        with self.executor.on_device():
            self._feed(starts)

    def _feed(self, starts) -> None:
        from .memory import batch_bytes
        try:
            for s in starts:
                self._slots.acquire()
                if self._stop:
                    return
                inj = self.executor.failure_injector
                if inj is not None:
                    from ..server.failureinjector import SCAN_PREFETCH
                    inj.maybe_fail(SCAN_PREFETCH, f"chunk@{s}")
                batch = self._decode_timed(s)
                b = batch_bytes(batch)
                self.pool.reserve_revocable(b, tag="scan-prefetch")
                with self._lock:
                    self._staged[s] = (batch, b)
                    self._gauge()
                _prof(f"prefetch: chunk@{s} staged")
                self._queue.put(("chunk", s))
            self._queue.put(("done", None))
        except BaseException as e:          # surfaces in next()
            self._queue.put(("error", e))

    def next(self, expected_start: int) -> Batch:
        if self.depth <= 0:
            return self._decode_timed(expected_start)
        from ..metrics import SCAN_PREFETCH_STALL_SECONDS
        t0 = time.monotonic()
        while True:
            # bounded waits so a stuck prefetch worker (chaos HANG, dead
            # source) can't pin a canceled query on the exec lock — the
            # cooperative check raises and close() reaps the thread
            try:
                kind, val = self._queue.get(timeout=0.25)
                break
            except queue.Empty:
                self.executor.check_cancel()
        wait = time.monotonic() - t0
        if wait > 1e-4:
            self.executor.stats.scan_prefetch_stalls += 1
            SCAN_PREFETCH_STALL_SECONDS.inc(wait)
        if kind == "error":
            raise val
        assert kind == "chunk" and val == expected_start, \
            f"prefetch out of order: {kind} {val} != {expected_start}"
        with self._lock:
            hit = self._staged.pop(expected_start, None)
            self._gauge()
        self._slots.release()
        if hit is None:                     # revoked under pressure
            return self._decode_timed(expected_start)
        batch, b = hit
        self.pool.free_revocable(b, tag="scan-prefetch")
        self.executor.stats.scan_prefetched_chunks += 1
        self.served += 1
        return batch

    def close(self) -> None:
        if self.depth <= 0:
            return
        self._stop = True
        self._slots.release()               # unblock a waiting worker
        self._thread.join(timeout=10)
        with self._lock:
            for s in list(self._staged):
                _, b = self._staged.pop(s)
                self.pool.free_revocable(b, tag="scan-prefetch")
            self._gauge()
        self.pool.unregister_revocation(self._revocation)
