"""Worker tasks and executor: putting the split's columns on the device (`batch_from_numpy`): summed wall of the statement's
`split-put` spans over its `split` spans, median per statement, in ms a
split."""

from layers import _spans


def read(run):
    return _spans.per_split_ms(run, "split-put")
