"""Worker tasks and executor: what folding its subqueries costs a
statement: the scans, the aggregate, the fetch of the members: summed
wall of its `subquery-fold` spans, median per statement, in ms. Fenced
in the traced run, and the span ends with the members on the host, so
the wall holds the device's time. Through a worker it is the first
split of the stage whose fragment carries the subquery, and what the
stages behind it wait for."""

from layers import _span_sums


def read(run):
    return _span_sums.per_statement(run, "subquery-fold",
                                    _span_sums.wall_ms)
