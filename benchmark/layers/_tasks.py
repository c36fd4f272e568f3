"""Shared by the readers of a stage's worker tasks (span catalogue:
docs/operations.md, "Distributed tracing"): a leaf stage is a
`source-stage` span (a join's build side has one too, under its
`build-stage`), and each task a worker ran for it is a `worker-task`
span under it, carrying its `node` and, where the worker's executor is
bound to a device of its own, that `device`. A statement that ran no
worker task (the single-node route) gives nothing to read."""

from layers import _spans


def stages(spans):
    """[[worker-task span, ...] a stage], the stages that ran a task."""
    kids = _spans.by_parent(spans)
    out = []
    for st in _spans.named(spans, "source-stage"):
        tasks = [c for c in kids.get(st.get("spanId"), ())
                 if c.get("name") == "worker-task"]
        if tasks:
            out.append(tasks)
    return out


def covered_ms(intervals) -> float:
    """Milliseconds that the intervals cover together."""
    return _spans.union_ms(intervals, min(s for s, _ in intervals),
                           max(e for _, e in intervals))
