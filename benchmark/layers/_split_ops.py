"""Shared by the readers of what runs INSIDE a worker's `split` lap (the
span catalogue is in docs/operations.md, "Distributed tracing"): the
operator spans a traced task opens beside the lap (`filter-project`,
`join`, `aggregate`, `sort`; under `worker-task`, carrying `split`, the
lap's `index`) and the lap's `dispatches` counter.

`split_run_ms` is the lap's self time, its `compile` children taken
out; these metrics say where that goes. Per statement, over its count
of `split` laps, then the median over statements, as `split_run_ms`.
In a statement that compiled nothing `split_join_ms + split_agg_ms +
split_filter_ms (+ sort's) + split_unnamed_ms` is `split_run_ms`.

A program older than these spans opens no operator span that carries
`split`: `inside` tells, and a reader then has nothing to read and
returns None. One that does and ran no join reads 0 ms of join.
"""

from layers import _spans

# top-level operators of a split; `dynamic-filter` lies inside `join`
OPERATORS = ("filter-project", "join", "aggregate", "sort")


def _attributes(sp) -> dict:
    return sp.get("attributes") or {}


def inside(spans):
    """(the statement's `split` laps, its top-level operator spans that
    carry `split`), or None where it ran no split or its program names
    nothing inside one. Top-level: beside a lap, under the lap's parent.
    An operator that runs inside another (a subquery's) hangs under
    that one and is part of its wall already."""
    laps = _spans.named(spans, "split")
    tasks = {lap.get("parentSpanId") for lap in laps}
    ops = [sp for sp in spans or () if sp.get("name") in OPERATORS
           and "split" in _attributes(sp)
           and sp.get("parentSpanId") in tasks]
    return (laps, ops) if laps and ops else None


def operator_ms(run, name):
    """Summed wall of the statement's `name` spans that carry `split`
    over its count of laps: ms a split."""
    def value(spans):
        found = inside(spans)
        if found is None:
            return None
        laps, ops = found
        return sum(float(sp["durationMs"]) for sp in ops
                   if sp["name"] == name) / len(laps)
    return _spans.per_statement_median(run, value)


def unnamed_ms(run):
    """What of a lap neither a `compile` child nor an operator span of
    that split covers, summed over the statement's laps, over their
    count: ms a split. An operator span belongs to the lap with its
    parent (the task) and its index."""
    def value(spans):
        found = inside(spans)
        if found is None:
            return None
        laps, ops = found
        kids = _spans.by_parent(spans)
        mine = {}
        for sp in ops:
            key = (sp.get("parentSpanId"), sp["attributes"]["split"])
            mine.setdefault(key, []).append(_spans.interval(sp))
        total = 0.0
        for lap in laps:
            lo, hi = _spans.interval(lap)
            named = [_spans.interval(c)
                     for c in kids.get(lap.get("spanId"), ())]
            named += mine.get((lap.get("parentSpanId"),
                               _attributes(lap).get("index")), [])
            total += (hi - lo) / 1e6 - _spans.union_ms(named, lo, hi)
        return total / len(laps)
    return _spans.per_statement_median(run, value)


def dispatches(run):
    """Programs a split dispatched through `recorded_jit`, cached or
    not: the laps' `dispatches` summed over their count. None where the
    program stamps none."""
    def value(spans):
        laps = _spans.named(spans, "split")
        if not laps or not all("dispatches" in _attributes(sp)
                               for sp in laps):
            return None
        return sum(int(sp["attributes"]["dispatches"])
                   for sp in laps) / len(laps)
    return _spans.per_statement_median(run, value)
