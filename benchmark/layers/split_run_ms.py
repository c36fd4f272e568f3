"""Worker tasks and executor: running the split's fragment
(`ex.run(root)`: dispatch, and whatever the host waits for inside it):
self time of the statement's `split` spans, their `compile` children
taken out, over their count, median per statement, in ms a split."""

from layers import _spans


def read(run):
    return _spans.per_split_ms(run, "split", self_time=True)
