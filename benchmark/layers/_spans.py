"""Shared by the readers of the program's phase spans (the span
catalogue is in docs/operations.md, "Distributed tracing"): spans by
name, a span's interval, self time, and the per-statement-then-median
reduction the span metrics share.

A program older than the phase spans has none of them: `has_phases`
tells, and a reader then has nothing to read and returns None.
"""

import statistics

# spans only a program with the phase spans writes
_PHASE_MARKS = ("split-read", "exec-lock-wait")


def named(spans, name):
    return [sp for sp in spans or () if sp.get("name") == name]


def interval(sp):
    """(start_ns, end_ns) on the host clock."""
    s0 = int(sp.get("startTimeUnixNano", 0))
    return s0, s0 + int(float(sp.get("durationMs", 0.0)) * 1e6)


def has_phases(spans) -> bool:
    return any(sp.get("name") in _PHASE_MARKS for sp in spans or ())


def union_ms(intervals, lo, hi) -> float:
    """Milliseconds of [lo, hi] that the intervals cover together."""
    total, at = 0, lo
    for s, e in sorted(intervals):
        s, e = max(s, at), min(e, hi)
        if e > s:
            total += e - s
            at = e
    return total / 1e6


def by_parent(spans) -> dict:
    """{parent span id: [children]}, built once a statement."""
    kids = {}
    for sp in spans or ():
        kids.setdefault(sp.get("parentSpanId"), []).append(sp)
    return kids


def self_ms(kids: dict, sp) -> float:
    """The span's duration minus what its children cover (overlapping
    children counted once); `kids` is `by_parent` of its statement."""
    lo, hi = interval(sp)
    return (hi - lo) / 1e6 - union_ms(
        [interval(c) for c in kids.get(sp.get("spanId"), ())], lo, hi)


def per_statement_median(run, value_of):
    """`value_of(spans)` per statement of the window (None: the
    statement has nothing to read), then the median."""
    vals = [v for v in (value_of(s.get("spans") or ())
                        for s in run["statements"]) if v is not None]
    return statistics.median(vals) if vals else None


def per_split_ms(run, name, self_time=False):
    """Summed wall (or self time) of the statement's `name` spans over
    its count of `split` spans, median per statement, in ms a split."""
    def value(spans):
        splits = len(named(spans, "split"))
        if not splits or not has_phases(spans):
            return None
        mine = named(spans, name)
        if self_time:
            kids = by_parent(spans)
            return sum(self_ms(kids, sp) for sp in mine) / splits
        return sum(float(sp["durationMs"]) for sp in mine) / splits
    return per_statement_median(run, value)


def compile_ms(run, key):
    """`compile` spans whose `key` attribute is `key`, summed per
    split-streamed statement (0 where it compiled nothing), then the
    median, in ms."""
    def value(spans):
        if not has_phases(spans) or not named(spans, "split"):
            return None
        return sum(float(sp["durationMs"]) for sp in named(spans, "compile")
                   if (sp.get("attributes") or {}).get("key") == key)
    return per_statement_median(run, value)
