"""Scheduling and exchange: the stages a statement runs to its end
before its probe stage may start (a join's build sides as stages of
their own, one after another): summed wall of its `build-stage` spans,
median per statement, in ms. A statement with no such stage gives
nothing to read."""

from layers import _span_sums


def read(run):
    return _span_sums.per_statement(run, "build-stage", _span_sums.wall_ms)
