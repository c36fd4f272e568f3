"""Parse, plan, admission: how long a statement waited for the
coordinator's device lock behind the statements that had asked before it
(three streams: the other two's): summed wall of its `exec-lock-wait`
spans, median per statement, in ms. Read only where the span says how
many were `ahead`: a program that hands the lock out in no order (an
older one) gives nothing to read."""

from layers import _spans


def read(run):
    def value(spans):
        mine = [sp for sp in _spans.named(spans, "exec-lock-wait")
                if "ahead" in (sp.get("attributes") or {})]
        if not mine:
            return None
        return sum(float(sp["durationMs"]) for sp in mine)
    return _spans.per_statement_median(run, value)
