"""Worker tasks and executor, single-node route: the joins of a
statement (each dispatched JoinNode's own work once its probe and build
sides have run; fenced in the traced run): summed wall of its `join`
spans, median per statement, in ms. A program that writes no `join`
span gives nothing to read."""

from layers import _spans


def read(run):
    def value(spans):
        mine = _spans.named(spans, "join")
        if not mine:
            return None
        return sum(float(sp["durationMs"]) for sp in mine)
    return _spans.per_statement_median(run, value)
