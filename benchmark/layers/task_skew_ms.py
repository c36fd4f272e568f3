"""Scheduling and exchange: what a stage's slowest worker costs: for
each leaf stage the longest `worker-task` wall less the shortest, summed
over the statement's stages, median per statement, in ms. A stage waits
for its last task, so this is time the other workers' devices stand
idle; 0 for a stage of one task."""

from layers import _span_sums, _spans, _tasks


def read(run):
    def value(spans):
        stages = _tasks.stages(spans)
        if not stages:
            return None
        walls = [[_span_sums.wall_ms(t) for t in tasks] for tasks in stages]
        return sum(max(w) - min(w) for w in walls)
    return _spans.per_statement_median(run, value)
