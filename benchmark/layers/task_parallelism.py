"""Scheduling and exchange: how many worker tasks ran at once, over the
time any ran: the statement's `worker-task` walls summed (every leaf
stage, build sides too) over the union of their intervals, median per
statement. 1.0 is one task after another (one worker, or stages whose
tasks queue on one executor); 4.0 is four workers busy from a stage's
first moment to its last."""

from layers import _span_sums, _spans, _tasks


def read(run):
    def value(spans):
        tasks = [t for stage in _tasks.stages(spans) for t in stage]
        if not tasks:
            return None
        covered = _tasks.covered_ms([_spans.interval(t) for t in tasks])
        if not covered:
            return None
        return sum(map(_span_sums.wall_ms, tasks)) / covered
    return _spans.per_statement_median(run, value)
