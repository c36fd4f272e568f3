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

One clock pair a process: a span is timed on `time.monotonic()` and its
exported `startTimeUnixNano` is that reading carried onto the wall clock
through ONE `(time.time_ns(), time.monotonic())` pair read at import
(`unix_ns`). Spans of one process therefore nest and touch on the
exported clock exactly as they did on the monotonic one, whichever
tracer or thread made them, to the microsecond `durationMs` is rounded
to. Spans of another process come through `adopt(offset_s)`: the offset
between the two processes' pairs, which a worker's announce measures
(`CLOCK_ID` tells the coordinator a worker of its own process: 0).

Spans of a task's split loop (`split_span`: an operator's wall inside
one split, hundreds a task, each saying which split and little else)
are kept and shipped as five integers each (`export(compact=True)`, one
block a parent span) and become span dicts only where a trace is read
(`export()`): as dicts they were 40% of a task's status JSON and 7 ms of
a 240-split stage's hand-over. What else such a span says (the form of a
join's LUT) it says in every split alike, so a block ships each distinct
(name, attributes) once and a span's first integer names the pair.
"""

from __future__ import annotations

import os
import threading
import time
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_ROOT_SPAN_ID = "0" * 16
# the process's one clock pair (see above): a pair read per span puts a
# thread switch between its two reads, and with it milliseconds between
# a span and its own parent
_UNIX0_NS, _MONO0 = time.time_ns(), time.monotonic()
# names the pair: two tracers that see the same id stamp on one clock
CLOCK_ID = os.urandom(8).hex()
# integers a `split_span` is shipped as: index into the block's names
# (and its `attributes`, where it has them), split, depth, start (ns
# after the block's), duration (us)
_ROW = 5
# profiler annotations of the program's spans; `bench:` belongs to the
# benchmark's anchors (benchmark/trace_reduce.py)
ANNOTATION_PREFIX = "tt:"


def new_trace_id() -> str:
    return os.urandom(16).hex()


def new_span_id() -> str:
    return os.urandom(8).hex()


def unix_ns(mono: float) -> int:
    """A `time.monotonic()` reading of this process on the wall clock,
    in ns, through the process's clock pair."""
    return _UNIX0_NS + round((mono - _MONO0) * 1e9)


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


class _SplitRow(list):
    """A `split_span` as the tracer keeps it: [name, split, depth,
    start, end, attributes]."""

    @property
    def attributes(self) -> dict:
        return self[5]


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

    @property
    def duration_ms(self) -> float:
        return ((self.end or time.monotonic()) - self.start) * 1000

    def to_dict(self) -> dict:
        return {"name": self.name,
                "traceId": self.trace_id,
                "spanId": self.span_id,
                "parentSpanId": self.parent_id,
                "service": self.service,
                "startTimeUnixNano": unix_ns(self.start),
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
        # split-loop spans by parent span: [name, split, depth, start,
        # end, attributes] in the order they opened (a parent before
        # its children)
        self._split_spans: Dict[str, List[list]] = {}
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
                 service=self.service)
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

    @contextmanager
    def split_span(self, name: str, parent: str, split: int,
                   depth: int = 0):
        """A live span of a task's split loop, in the compact form (see
        the module's docstring): a child of `parent` (depth 0) or of the
        split span last opened one level up, with `split` its
        attribute and whatever the caller puts in the yielded row's
        `attributes` (a few small values that most splits repeat). It is not
        the thread's context: what opens or is recorded meanwhile hangs
        where it would have (an operator's span lies BESIDE the `split`
        lap it runs in)."""
        if not self.enabled:
            yield None
            return
        row = _SplitRow((name, split, depth, time.monotonic(), None, {}))
        with self._lock:
            self._split_spans.setdefault(parent, []).append(row)
        try:
            with _annotation(name):
                yield row
        finally:
            row[4] = time.monotonic()

    def record(self, name: str, start: float, end: float,
               parent: Optional[str] = None, **attributes) -> None:
        """A span known only after the fact (a call turned out to have
        compiled): `start` and `end` are `time.monotonic()` readings."""
        if not self.enabled:
            return
        s = Span(name, start, end, attributes=attributes,
                 trace_id=self.trace_id, span_id=new_span_id(),
                 parent_id=parent or self._context_parent(),
                 service=self.service)
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
                     parent_id=parent, service=self.service)
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

        `offset_s` is the remote process's span clock minus this one's
        (both through their clock pairs, measured at announce time; 0
        for a node of this process): remote `startTimeUnixNano` stamps
        are rebased onto the local clock so cross-node timeline
        intervals cannot go negative when a worker's wall clock is
        skewed. A block of split-loop spans is rebased as one span.
        Spans are copied, not mutated in place."""
        if not self.enabled or not span_dicts:
            return
        adopted, offset_ns = [], round(offset_s * 1e9)
        for d in span_dicts:
            if not isinstance(d, dict):
                continue
            if offset_ns and "startTimeUnixNano" in d:
                d = dict(d)
                d["startTimeUnixNano"] = \
                    int(d["startTimeUnixNano"]) - offset_ns
            adopted.append(d)
        with self._lock:
            self._foreign.extend(adopted)

    def _split_blocks(self) -> List[dict]:
        """This tracer's split-loop spans as shipped: one block a
        parent span, `_ROW` integers a span."""
        blocks = []
        for parent, rows in self._split_spans.items():
            # a kind of span: its name and what it says besides `split`
            of = [(r[0], tuple(sorted(r[5].items()))) for r in rows]
            kinds = sorted(set(of), key=repr)
            index = {kind: i for i, kind in enumerate(kinds)}
            t0 = unix_ns(rows[0][3])
            flat = []
            for kind, (_, split, depth, start, end, _) in zip(of, rows):
                flat += (index[kind], split, depth, unix_ns(start) - t0,
                         round(((end or start) - start) * 1e6))
            block = {"attributes": [dict(a) for _, a in kinds]} \
                if any(a for _, a in kinds) else {}
            blocks.append({"name": "split-spans", **block,
                           "splitSpans": flat,
                           "names": [n for n, _ in kinds],
                           "traceId": self.trace_id,
                           "spanId": new_span_id(),
                           "parentSpanId": parent,
                           "service": self.service,
                           "startTimeUnixNano": t0})
        return blocks

    def export(self, compact: bool = False) -> List[dict]:
        """The trace's spans as dicts. `compact` leaves split-loop
        spans in their blocks (what a task ships; `adopt` takes them)."""
        with self._lock:
            out = [s.to_dict() for s in self.spans] + \
                self._split_blocks() + list(self._foreign)
        if compact:
            return out
        spans = []
        for d in out:
            if "splitSpans" in d:
                spans.extend(_expand(d))
            else:
                spans.append(d)
        return spans

    def clear(self) -> None:
        with self._lock:
            self.spans.clear()
            self._split_spans.clear()
            self._foreign.clear()


NOOP = Tracer(enabled=False)


def _expand(block: dict) -> List[dict]:
    """A block of split-loop spans as span dicts. Ids follow the
    block's own, one a row; a row's parent is the row last seen one
    level up (the deepest there is, should a block skip a level), the
    block's parent at depth 0."""
    flat, names = block["splitSpans"], block["names"]
    attributes = block.get("attributes") or [{}] * len(names)
    id0, t0 = int(block["spanId"], 16), block["startTimeUnixNano"]
    parents, spans = [block["parentSpanId"]], []
    for i in range(0, len(flat), _ROW):
        name, split, depth, start, micros = flat[i:i + _ROW]
        span_id = f"{(id0 + i // _ROW) % (1 << 64):016x}"
        depth = min(depth, len(parents) - 1)
        del parents[depth + 1:]
        spans.append({"name": names[name],
                      "traceId": block["traceId"],
                      "spanId": span_id,
                      "parentSpanId": parents[depth],
                      "service": block["service"],
                      "startTimeUnixNano": t0 + start,
                      "durationMs": micros / 1000,
                      "attributes": {"split": split,
                                     **attributes[name]}})
        parents.append(span_id)
    return spans


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
