"""Scheduling and exchange: how many devices a statement's worker tasks
ran on: distinct `device` over its `worker-task` spans, median per
statement. With four workers on a four-chip host 4 is sound; fewer says
workers share a chip or a worker got no task. A program whose
`worker-task` spans name no device gives nothing to read."""

from layers import _spans


def read(run):
    def value(spans):
        devices = {(sp.get("attributes") or {}).get("device")
                   for sp in _spans.named(spans, "worker-task")} - {None}
        return len(devices) or None
    return _spans.per_statement_median(run, value)
