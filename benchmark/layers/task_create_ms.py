"""Scheduling and exchange: the hand-over of a stage's fragment (with
its broadcast builds inside: 35 MB for a q3's probe stage) to every
worker: the union of the statement's `task-create` intervals, median
per statement, in ms. The creates of one stage run at the same time, one
a worker, so the union is what the stage waited, not what they add up
to."""

from layers import _spans, _tasks


def read(run):
    def value(spans):
        creates = [_spans.interval(sp)
                   for sp in _spans.named(spans, "task-create")]
        return _tasks.covered_ms(creates) if creates else None
    return _spans.per_statement_median(run, value)
