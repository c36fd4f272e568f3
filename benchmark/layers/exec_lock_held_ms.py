"""Parse, plan, admission: how long a statement then held the
coordinator's device lock (planning, its stages, the final merge: the
whole of `scheduler.execute`, one statement at a time): summed wall of
its `exec-lock-held` spans, median per statement, in ms. Against the
same templates' latency with one client (`worker.scan`) it says what
the waiting streams cost the statement that runs. A program that writes
no `exec-lock-held` span gives nothing to read."""

from layers import _spans


def read(run):
    def value(spans):
        mine = _spans.named(spans, "exec-lock-held")
        if not mine:
            return None
        return sum(float(sp["durationMs"]) for sp in mine)
    return _spans.per_statement_median(run, value)
