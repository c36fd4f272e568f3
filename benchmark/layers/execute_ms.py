"""Worker tasks and executor, single-node route: the statement on the
coordinator's device executor, from the first plan node's dispatch to
the answer's arrays on the host (`execute` span wall), median per
statement, in ms."""

from layers import _spans


def read(run):
    def value(spans):
        mine = _spans.named(spans, "execute")
        if not mine:
            return None
        return sum(float(sp["durationMs"]) for sp in mine)
    return _spans.per_statement_median(run, value)
