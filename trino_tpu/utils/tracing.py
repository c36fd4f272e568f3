"""Distributed span tracing with W3C trace-context propagation.

Reference: Trino wires OpenTelemetry spans through the whole query path —
TracingModule at bootstrap (server/Server.java:106), spans around planning
(SqlQueryExecution.java:473,501), split scheduling
(split/SplitManager.java:85), decorators like tracing/TracingMetadata.java,
semantic attributes in tracing/TrinoAttributes.java — and propagates the
context over every internal HTTP hop so one query yields one trace.

Here: a dependency-free tracer with the same shape — named spans with
attributes and random 64-bit span ids, parent/child nesting via a
thread-local context stack, a W3C `traceparent` header
(`00-<trace_id>-<span_id>-01`) carried on every internal hop (statement
POST, task create, exchange pulls, spooled-segment gets), and remote spans
adopted back into the originating tracer so the coordinator can serve the
stitched query trace as OTLP-like JSON. Disabled tracers are zero-overhead
no-ops.

The tracer travels with the query, not with a shared object: the
dispatcher (and a worker task) activates one per thread with `use()`,
and whatever runs below finds it with `current()` — the scheduler, the
session, the compile recorder. A thread the query spawns carries it on
with `use(tracer, parent=span_id)`. While a tracer is enabled every live
span is also a `jax.profiler.TraceAnnotation("tt:<name>")`, so a JAX
profiler trace taken meanwhile shows the program's spans on the
profiler's own clock, above the device's operations.
"""

from __future__ import annotations

import os
import threading
import time
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_ROOT_SPAN_ID = "0" * 16
# profiler annotations of the program's spans; `bench:` belongs to the
# benchmark's anchors (benchmark/trace_reduce.py)
ANNOTATION_PREFIX = "tt:"


def new_trace_id() -> str:
    return os.urandom(16).hex()


def new_span_id() -> str:
    return os.urandom(8).hex()


def format_traceparent(trace_id: str, span_id: str) -> str:
    return f"00-{trace_id}-{span_id}-01"


def parse_traceparent(header: Optional[str]) -> Optional[Tuple[str, str]]:
    """-> (trace_id, parent_span_id) or None on anything malformed."""
    if not header:
        return None
    parts = header.strip().split("-")
    if len(parts) != 4 or len(parts[1]) != 32 or len(parts[2]) != 16:
        return None
    try:
        int(parts[1], 16), int(parts[2], 16)
    except ValueError:
        return None
    return parts[1], parts[2]


@dataclass
class Span:
    name: str
    start: float                       # time.monotonic()
    end: Optional[float] = None
    attributes: Dict[str, object] = field(default_factory=dict)
    trace_id: str = ""
    span_id: str = ""
    # parent SPAN ID (not name: one query spawns many same-named task
    # spans, so a name link is ambiguous); None = trace root
    parent_id: Optional[str] = None
    service: str = "trino-tpu"
    start_unix: float = 0.0            # time.time() at start

    @property
    def duration_ms(self) -> float:
        return ((self.end or time.monotonic()) - self.start) * 1000

    def to_dict(self) -> dict:
        return {"name": self.name,
                "traceId": self.trace_id,
                "spanId": self.span_id,
                "parentSpanId": self.parent_id,
                "service": self.service,
                "startTimeUnixNano": int(self.start_unix * 1e9),
                "durationMs": round(self.duration_ms, 3),
                "attributes": self.attributes}


class Tracer:
    """Collects spans per thread; `span()` nests via a context stack.

    A tracer created via `from_traceparent` roots its first spans under
    the remote parent, so worker-side spans stitch under the coordinator
    span that dispatched the task. `adopt()` merges spans shipped back
    from remote processes (already-exported dicts) into this tracer's
    trace.
    """

    def __init__(self, enabled: bool = True,
                 trace_id: Optional[str] = None,
                 parent_span_id: Optional[str] = None,
                 service: str = "trino-tpu"):
        self.enabled = enabled
        self.trace_id = trace_id or new_trace_id()
        self.remote_parent = parent_span_id
        self.service = service
        self.spans: List[Span] = []
        self._foreign: List[dict] = []
        self._local = threading.local()
        self._lock = threading.Lock()

    @classmethod
    def from_traceparent(cls, header: Optional[str],
                         enabled: bool = True,
                         service: str = "trino-tpu") -> "Tracer":
        ctx = parse_traceparent(header)
        if ctx is None:
            return cls(enabled=enabled, service=service)
        return cls(enabled=enabled, trace_id=ctx[0],
                   parent_span_id=ctx[1], service=service)

    def _stack(self) -> list:
        if not hasattr(self._local, "stack"):
            self._local.stack = []
        return self._local.stack

    def current_span(self) -> Optional[Span]:
        """The innermost span open on this thread, None when there is
        none or tracing is off."""
        if not self.enabled:
            return None
        stack = self._stack()
        return stack[-1] if stack else None

    def _context_parent(self) -> Optional[str]:
        """Parent for a span opened now on this thread: the innermost
        open span, else the span `use(parent=...)` named for this
        thread, else the adopted remote parent."""
        stack = self._stack()
        if stack:
            return stack[-1].span_id
        return getattr(self._local, "base", None) or self.remote_parent

    def traceparent(self) -> Optional[str]:
        """Header value for the CURRENT context (innermost open span on
        this thread, else the adopted remote parent). None when tracing
        is off, so callers can skip the header entirely."""
        if not self.enabled:
            return None
        return format_traceparent(
            self.trace_id, self._context_parent() or _ROOT_SPAN_ID)

    @contextmanager
    def span(self, name: str, parent: Optional[str] = None, **attributes):
        """A live span. `parent` (a span id) overrides the thread's
        context: a helper thread names the span it works for."""
        if not self.enabled:
            yield None
            return
        s = Span(name, time.monotonic(), attributes=attributes,
                 trace_id=self.trace_id, span_id=new_span_id(),
                 parent_id=parent or self._context_parent(),
                 service=self.service, start_unix=time.time())
        stack = self._stack()
        stack.append(s)
        try:
            with _annotation(name):
                yield s
        finally:
            s.end = time.monotonic()
            stack.pop()
            with self._lock:
                self.spans.append(s)

    def record(self, name: str, start: float, end: float,
               parent: Optional[str] = None, **attributes) -> None:
        """A span known only after the fact (a call turned out to have
        compiled): `start` and `end` are `time.monotonic()` readings."""
        if not self.enabled:
            return
        s = Span(name, start, end, attributes=attributes,
                 trace_id=self.trace_id, span_id=new_span_id(),
                 parent_id=parent or self._context_parent(),
                 service=self.service,
                 start_unix=time.time() - (time.monotonic() - start))
        with self._lock:
            self.spans.append(s)

    @contextmanager
    def laps(self):
        """Back-to-back spans for the phases of a loop: yields
        `lap(name, **attributes)`, which closes the phase before and
        opens the next at the same instant, so no moment between two
        phases is left without a name (a sibling `with span()` pair
        leaves a sliver, and whatever thread takes the GIL there
        stretches it). The open phase is the thread's innermost span
        like any other; leaving the block closes it."""
        if not self.enabled:
            yield lambda name, **attributes: None
            return
        stack = self._stack()
        parent = self._context_parent()
        unix0, mono0 = time.time(), time.monotonic()
        current = None                 # (span, its annotation)

        def close(now: float) -> None:
            nonlocal current
            if current is not None:
                s, note = current
                note.__exit__(None, None, None)
                s.end = now
                stack.remove(s)
                with self._lock:
                    self.spans.append(s)
                current = None

        def lap(name: str, **attributes) -> Span:
            nonlocal current
            now = time.monotonic()
            close(now)
            s = Span(name, now, attributes=attributes,
                     trace_id=self.trace_id, span_id=new_span_id(),
                     parent_id=parent, service=self.service,
                     start_unix=unix0 + (now - mono0))
            stack.append(s)
            note = _annotation(name)
            note.__enter__()
            current = (s, note)
            return s

        try:
            yield lap
        finally:
            close(time.monotonic())

    def adopt(self, span_dicts, offset_s: float = 0.0) -> None:
        """Merge remote spans (exported dicts shipped back in task
        results) into this trace. Spans from another trace id are kept
        too — a mis-stitched span is more diagnosable than a dropped
        one.

        `offset_s` is the remote node's estimated clock offset (remote
        clock minus local clock, measured at announce time): remote
        `startTimeUnixNano` stamps are rebased onto the local clock so
        cross-node timeline intervals cannot go negative when a worker's
        wall clock is skewed. Spans are copied, not mutated in place."""
        if not self.enabled or not span_dicts:
            return
        adopted = []
        for d in span_dicts:
            if not isinstance(d, dict):
                continue
            if offset_s and "startTimeUnixNano" in d:
                d = dict(d)
                d["startTimeUnixNano"] = int(
                    d["startTimeUnixNano"] - offset_s * 1e9)
            adopted.append(d)
        with self._lock:
            self._foreign.extend(adopted)

    def export(self) -> List[dict]:
        with self._lock:
            return [s.to_dict() for s in self.spans] + list(self._foreign)

    def clear(self) -> None:
        with self._lock:
            self.spans.clear()
            self._foreign.clear()


NOOP = Tracer(enabled=False)


def _annotation(name: str):
    """The span's twin on the JAX profiler's clock; costs a flag test
    while no profile is being taken. Never built for a disabled tracer
    (`span()` returns before it gets here)."""
    try:
        import jax.profiler
        return jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name)
    except ImportError:          # spans work without jax
        return nullcontext()


# -- the carrier: one tracer per query, found by whatever runs below -------

_active = threading.local()


def carried() -> Optional[Tracer]:
    """The tracer `use()` activated on this thread, NOOP included; None
    outside any `use()` (a bare Session then keeps its own)."""
    return getattr(_active, "tracer", None)


def current() -> Tracer:
    """This thread's active tracer; NOOP by default."""
    return getattr(_active, "tracer", None) or NOOP


@contextmanager
def use(tracer: Tracer, parent: Optional[str] = None):
    """Activate `tracer` on this thread. On a thread the query spawned,
    `parent` is the span id its top-level spans hang under (the spawner
    reads it from the span it has open)."""
    prev = getattr(_active, "tracer", None)
    prev_base = getattr(tracer._local, "base", None)
    _active.tracer = tracer
    if parent is not None:
        tracer._local.base = parent
    try:
        yield tracer
    finally:
        _active.tracer = prev
        tracer._local.base = prev_base
