"""Worker tasks and executor, single-node route: the aggregations of a
statement (each dispatched AggregateNode's own work, its children's
taken out; fenced in the traced run, so the wall holds the device's
time): summed wall of its `aggregate` spans, median per statement, in
ms. A program that writes no `aggregate` span gives nothing to read."""

from layers import _spans


def read(run):
    def value(spans):
        mine = _spans.named(spans, "aggregate")
        if not mine:
            return None
        return sum(float(sp["durationMs"]) for sp in mine)
    return _spans.per_statement_median(run, value)
