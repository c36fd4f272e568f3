"""Shared by the readers that sum something over a statement's spans of
one name (span catalogue: docs/operations.md, "Distributed tracing"):
`subquery-fold`, the span an executor opens around a subquery it runs to
fold it into the statement (under `execute` on the single-node route,
under `worker-task` beside the `split` lap it ran in on a worker), and
`build-stage`. A statement without the span gives nothing to read."""

from layers import _spans


def per_statement(run, name, value_of_span):
    """`value_of_span(span)` summed over the statement's `name` spans,
    then the median over the statements that have one; None where none
    has."""
    def value(spans):
        mine = _spans.named(spans, name)
        if not mine:
            return None
        return sum(value_of_span(sp) for sp in mine)
    return _spans.per_statement_median(run, value)


def wall_ms(sp) -> float:
    return float(sp["durationMs"])
