"""Worker tasks and executor: fetching the split's result from the device (`batch_to_numpy`, a sync): summed wall of the statement's
`split-fetch` spans over its `split` spans, median per statement, in ms a
split."""

from layers import _spans


def read(run):
    return _spans.per_split_ms(run, "split-fetch")
