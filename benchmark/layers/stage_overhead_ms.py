"""Scheduling and exchange: what a source stage costs the coordinator
around its worker tasks (task create with the split list, page drain,
polls, spool and record): `source-stage` wall minus the union of the
`worker-task` intervals under it, summed over the statement's source
stages, median per statement, in ms."""

from layers import _spans


def read(run):
    def value(spans):
        stages = _spans.named(spans, "source-stage")
        if not stages:
            return None
        total, kids = 0.0, _spans.by_parent(spans)
        for st in stages:
            lo, hi = _spans.interval(st)
            tasks = [_spans.interval(c) for c in kids.get(st["spanId"], ())
                     if c.get("name") == "worker-task"]
            total += (hi - lo) / 1e6 - _spans.union_ms(tasks, lo, hi)
        return total
    return _spans.per_statement_median(run, value)
